"""The system under test as the loops build it: the program's U-Net
configuration, apply function and schedule, and the weights and keys the
benchmark makes from the seed."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import gen
from bench.reference import unet as ref_unet


def unet_cfg(cfg: dict) -> dict:
    return cfg["unet"]


def frozen(d: dict) -> tuple:
    """A hashable copy of a configuration dict (lists become tuples)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def program_unet_config(cfg: dict):
    from repro.configs.ddpm_unet import UNetConfig
    u = unet_cfg(cfg)
    return UNetConfig(name=cfg["name"], **{
        k: tuple(v) if isinstance(v, list) else v for k, v in u.items()})


def apply_fn(cfg: dict):
    from repro.core.unet import unet_apply
    ucfg = program_unet_config(cfg)
    return lambda p, x, t, y: unet_apply(p, x, t, y, ucfg)


def schedule(cfg: dict):
    from repro.core.schedules import DiffusionSchedule
    return DiffusionSchedule.linear(cfg["T"], cfg["beta_min"],
                                    cfg["beta_max"])


def root_key(seed: int):
    """A raw uint32 PRNG key from any non-negative integer seed."""
    return jnp.asarray(gen.seed_words(seed)[:2], jnp.uint32)


def keys(seed: int) -> dict:
    root = root_key(seed)
    return {name: jax.random.fold_in(root, i) for i, name in
            enumerate(("weights", "runtime", "data", "sample"), start=1)}


@partial(jax.jit, static_argnames=("ucfg",))
def _init_one(key, ucfg):
    return ref_unet.init(key, dict(ucfg))


def init_one(cfg: dict):
    """``key -> params`` of one net, one jitted program."""
    ucfg = frozen(unet_cfg(cfg))
    return lambda key: _init_one(key, ucfg)


@partial(jax.jit, static_argnames=("ucfg", "n_clients"))
def _serve_weights(key, ucfg, n_clients):
    make = lambda k: ref_unet.init(k, dict(ucfg))
    server = make(jax.random.fold_in(key, 0))
    clients = jax.vmap(lambda i: make(jax.random.fold_in(key, 1 + i)))(
        jnp.arange(n_clients))
    return server, clients


def serve_weights(cfg: dict, key, n_clients: int):
    """(server net, client nets stacked on a leading axis) in one call:
    net 0 is the server, net 1 + c client c."""
    return _serve_weights(key, frozen(unet_cfg(cfg)), n_clients)
