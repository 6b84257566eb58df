"""Plain collaborative sampling (CollaFuse Alg. 2) with the serve runtime's
stated randomness, for the serve cells' check.  Imports nothing of the
program.

What the runtime states and this file follows:

* linear beta schedule 1e-4..0.02 over T steps; alpha-bar read at real
  timesteps by linear interpolation, with alpha-bar(0) = 1;
* a DDPM step from t to t_prev: ``(x - coef * eps) * inv_sqrt_alpha +
  sigma * noise`` with alpha = abar(t)/abar(t_prev), coef = (1 - alpha) /
  sqrt(1 - abar(t)), sigma = sqrt(1 - alpha) for t > 1 and 0 at t <= 1;
* the server runs t = T .. t_cut + 1 (t_prev = t - 1); the client runs
  t_cut steps over ``linspace(M, 1, t_cut)`` with M = floor(t_cut + t_cut /
  T * (T - t_cut)), each step landing on the next entry and the last on 0;
* noise is addressed, never chained: with ``ks, kc = split(key)``, a
  group's start is ``rows(fold_in(fold_in(ks, gseed), 0))`` and its server
  step s draws ``rows(fold_in(fold_in(ks, gseed), 1 + s))``; request r's
  client step c draws ``rows(fold_in(fold_in(kc, rseed), c))``, where
  ``rows(k)`` gives image i the normal draw of ``fold_in(k, i)``;
* gseed is a 31-bit blake2b digest of the group's (t_cut, stride, labels)
  identity and rseed the request's arrival number, masked to 31 bits.
"""
from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import unet


def group_seed(t_cut: int, y: np.ndarray, stride: int = 1) -> int:
    y = np.asarray(y, np.float32)
    head = repr((int(t_cut), int(stride), y.shape, y.dtype.str)).encode()
    digest = hashlib.blake2b(head + b"|" + y.tobytes(), digest_size=4)
    return int.from_bytes(digest.digest(), "little") & 0x7FFFFFFF


def alpha_bar_grid(T: int):
    betas = jnp.linspace(1e-4, 0.02, T, dtype=jnp.float32)
    return jnp.concatenate([jnp.ones((1,), jnp.float32),
                            jnp.cumprod(1.0 - betas)])


def step_coefficients(grid, t, t_prev):
    T = grid.shape[0] - 1
    steps = jnp.arange(T + 1, dtype=jnp.float32)
    ab = lambda s: jnp.interp(jnp.clip(s, 0.0, float(T)), steps, grid)
    ab_t, ab_p = ab(t), ab(t_prev)
    alpha = ab_t / jnp.clip(ab_p, 1e-12)
    beta = 1.0 - alpha
    inv_sqrt_alpha = 1.0 / jnp.sqrt(jnp.clip(alpha, 1e-12))
    coef = beta / jnp.sqrt(jnp.clip(1.0 - ab_t, 1e-12))
    sigma = jnp.where(t > 1.0, jnp.sqrt(jnp.clip(beta, 0.0)), 0.0)
    return inv_sqrt_alpha, coef, sigma


def rows(key, shape):
    return jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(key, i), shape[1:], jnp.float32))(
        jnp.arange(shape[0]))


def client_table(T: int, t_cut: int):
    m = int(t_cut + (t_cut / T) * (T - t_cut))
    t = jnp.linspace(float(m), 1.0, t_cut, dtype=jnp.float32)
    return t, jnp.concatenate([t[1:], jnp.zeros((1,), jnp.float32)])


@partial(jax.jit, static_argnames=("cfg_items", "T", "t_cut", "dtype"))
def sample(server_params, client_params, key, y, gseed, rseed, *,
           cfg_items, T: int, t_cut: int, dtype):
    """K requests at one cut, each (B, H, W, C): ``y`` (K, B, n_classes),
    ``gseed``/``rseed`` (K,), ``client_params`` stacked (K, ...) — each
    request's own client net.  Returns the samples (K, B, H, W, C), computed
    in ``dtype``."""
    cfg = dict(cfg_items)
    K, B = y.shape[:2]
    shape = (B, cfg["image_size"], cfg["image_size"], cfg["channels"])
    grid = alpha_bar_grid(T)
    ks, kc = jax.random.split(key)
    gkeys = jax.vmap(lambda s: jax.random.fold_in(ks, s))(gseed)
    rkeys = jax.vmap(lambda s: jax.random.fold_in(kc, s))(rseed)
    yk = y.astype(dtype)
    sp = unet.cast(server_params, dtype)
    cp = unet.cast(client_params, dtype)

    def advance(x, eps, noise, t, t_prev):
        a, c, s = step_coefficients(grid, t, t_prev)
        return ((x - c.astype(dtype) * eps) * a.astype(dtype)
                + s.astype(dtype) * noise)

    x = jax.vmap(lambda k: rows(jax.random.fold_in(k, 0), shape))(gkeys)
    x = x.astype(dtype)

    def server_step(s, x):
        t = jnp.float32(T) - s.astype(jnp.float32)
        eps = unet.forward(sp, x.reshape((K * B,) + shape[1:]),
                           jnp.full((K * B,), t), yk.reshape(K * B, -1), cfg)
        noise = jax.vmap(lambda k: rows(jax.random.fold_in(k, 1 + s),
                                        shape))(gkeys).astype(dtype)
        return advance(x, eps.reshape(x.shape), noise, t, t - 1.0)

    x = jax.lax.fori_loop(0, T - t_cut, server_step, x)
    ct, ctp = client_table(T, t_cut)

    def client_step(c, x):
        t, t_prev = ct[c], ctp[c]
        eps = jax.vmap(lambda p, xr, yr: unet.forward(
            p, xr, jnp.full((B,), t), yr, cfg))(cp, x, yk)
        noise = jax.vmap(lambda k: rows(jax.random.fold_in(k, c),
                                        shape))(rkeys).astype(dtype)
        return advance(x, eps, noise, t, t_prev)

    return jax.lax.fori_loop(0, t_cut, client_step, x)
