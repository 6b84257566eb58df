"""DDPM U-Net denoiser ε_θ(x_t, t, y) [Ho et al. 2020; CollaFuse §4.1].

NHWC, pure-JAX pytrees. Attribute conditioning y is a multi-hot vector
(B, n_classes) projected into the time-embedding space — this covers the
paper's one-hot DDPM conditioning and our synthetic multi-attribute labels
(DESIGN.md §2). Both the server model ε_θs and every client model ε_θc are
instances of this network (the paper uses identical architectures; only the
data and the timestep ranges differ).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.ddpm_unet import UNetConfig
from repro.models.layers import dense_init, sinusoidal_embedding


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def conv_init(key, kh, kw, cin, cout, dtype, scale=None):
    fan_in = kh * kw * cin
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return {
        "w": (jax.random.normal(key, (kh, kw, cin, cout)) * scale).astype(dtype),
        "b": jnp.zeros((cout,), dtype),
    }


def conv(p, x, stride: int = 1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def gn_init(c, dtype):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def groupnorm(p, x, groups: int, eps: float = 1e-5):
    B, H, W, C = x.shape
    g = min(groups, C)
    while C % g:
        g -= 1
    xg = x.reshape(B, H, W, g, C // g).astype(jnp.float32)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    y = xg.reshape(B, H, W, C) * p["scale"].astype(jnp.float32) + \
        p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def res_block_init(key, cin, cout, time_dim, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "gn1": gn_init(cin, dtype),
        "conv1": conv_init(k1, 3, 3, cin, cout, dtype),
        "time": dense_init(k2, time_dim, cout, dtype),
        "gn2": gn_init(cout, dtype),
        "conv2": conv_init(k3, 3, 3, cout, cout, dtype, scale=1e-3),
    }
    if cin != cout:
        p["skip"] = conv_init(k4, 1, 1, cin, cout, dtype)
    return p


def res_block(p, x, emb, groups: int):
    h = conv(p["conv1"], jax.nn.silu(groupnorm(p["gn1"], x, groups)))
    h = h + (jax.nn.silu(emb) @ p["time"])[:, None, None, :]
    h = conv(p["conv2"], jax.nn.silu(groupnorm(p["gn2"], h, groups)))
    if "skip" not in p:
        return x + h
    # the 1x1 skip conv as the matmul it is: vmapped over a sharded client
    # axis, XLA on the CPU returns wrong values for the 1x1 convolution and
    # right ones for the matmul (pinned in tests/test_sharding.py)
    return x @ p["skip"]["w"][0, 0] + p["skip"]["b"] + h


def attn_block_init(key, c, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "gn": gn_init(c, dtype),
        "wq": dense_init(kq, c, c, dtype),
        "wk": dense_init(kk, c, c, dtype),
        "wv": dense_init(kv, c, c, dtype),
        "wo": dense_init(ko, c, c, dtype, scale=1e-3),
    }


def attn_block(p, x, n_heads: int, groups: int):
    B, H, W, C = x.shape
    h = groupnorm(p["gn"], x, groups).reshape(B, H * W, C)
    dh = C // n_heads
    split = lambda t: t.reshape(B, H * W, n_heads, dh).transpose(0, 2, 1, 3)
    q, k, v = split(h @ p["wq"]), split(h @ p["wk"]), split(h @ p["wv"])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    w = jax.nn.softmax(logits / math.sqrt(dh), axis=-1).astype(v.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v).transpose(0, 2, 1, 3)
    o = o.reshape(B, H * W, C) @ p["wo"]
    return x + o.reshape(B, H, W, C)


# ---------------------------------------------------------------------------
# U-Net
# ---------------------------------------------------------------------------


def _level_widths(cfg: UNetConfig) -> List[int]:
    return [cfg.base_width * m for m in cfg.width_mults]


def init_unet(key, cfg: UNetConfig) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    widths = _level_widths(cfg)
    keys = iter(jax.random.split(key, 1024))
    nk = lambda: next(keys)
    td = cfg.time_dim

    params: Dict = {
        "time_mlp": {
            "w1": dense_init(nk(), td, td, dtype),
            "w2": dense_init(nk(), td, td, dtype),
        },
        "label_proj": dense_init(nk(), cfg.n_classes, td, dtype),
        "stem": conv_init(nk(), 3, 3, cfg.channels, widths[0], dtype),
        "out_gn": gn_init(widths[0], dtype),
        "out_conv": conv_init(nk(), 3, 3, widths[0], cfg.channels, dtype,
                              scale=1e-3),
    }

    res = cfg.image_size
    down, skips_c = [], [widths[0]]
    cin = widths[0]
    for i, w in enumerate(widths):
        level = {"res": [], "attn": []}
        for _ in range(cfg.n_res_blocks):
            level["res"].append(res_block_init(nk(), cin, w, td, dtype))
            level["attn"].append(attn_block_init(nk(), w, dtype)
                                 if res in cfg.attn_resolutions else None)
            cin = w
            skips_c.append(w)
        if i < len(widths) - 1:
            level["down"] = conv_init(nk(), 3, 3, w, w, dtype)
            skips_c.append(w)
            res //= 2
        down.append(level)
    params["down"] = down

    params["mid"] = {
        "res1": res_block_init(nk(), cin, cin, td, dtype),
        "attn": attn_block_init(nk(), cin, dtype),
        "res2": res_block_init(nk(), cin, cin, td, dtype),
    }

    up = []
    for i, w in reversed(list(enumerate(widths))):
        level = {"res": [], "attn": []}
        for _ in range(cfg.n_res_blocks + 1):
            sc = skips_c.pop()
            level["res"].append(res_block_init(nk(), cin + sc, w, td, dtype))
            level["attn"].append(attn_block_init(nk(), w, dtype)
                                 if res in cfg.attn_resolutions else None)
            cin = w
        if i > 0:
            level["up"] = conv_init(nk(), 3, 3, w, w, dtype)
            res *= 2
        up.append(level)
    params["up"] = up
    return params


def unet_apply(params, x, t, y, cfg: UNetConfig):
    """x: (B,H,W,C); t: (B,) real-valued timesteps; y: (B, n_classes)
    multi-hot conditioning (zeros = unconditional). Returns ε̂ same shape."""
    g = cfg.groupnorm_groups
    temb = sinusoidal_embedding(jnp.asarray(t, jnp.float32), cfg.time_dim)
    temb = temb.astype(x.dtype)
    tm = params["time_mlp"]
    emb = jax.nn.silu(temb @ tm["w1"]) @ tm["w2"]
    emb = emb + y.astype(emb.dtype) @ params["label_proj"]

    h = conv(params["stem"], x)
    skips = [h]
    for i, level in enumerate(params["down"]):
        for rp, ap in zip(level["res"], level["attn"]):
            h = res_block(rp, h, emb, g)
            if ap is not None:
                h = attn_block(ap, h, cfg.n_heads, g)
            skips.append(h)
        if "down" in level:
            h = conv(level["down"], h, stride=2)
            skips.append(h)

    mid = params["mid"]
    h = res_block(mid["res1"], h, emb, g)
    h = attn_block(mid["attn"], h, cfg.n_heads, g)
    h = res_block(mid["res2"], h, emb, g)

    for level in params["up"]:
        for rp, ap in zip(level["res"], level["attn"]):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = res_block(rp, h, emb, g)
            if ap is not None:
                h = attn_block(ap, h, cfg.n_heads, g)
        if "up" in level:
            B, H, W, C = h.shape
            h = jax.image.resize(h, (B, H * 2, W * 2, C), "nearest")
            h = conv(level["up"], h)

    h = jax.nn.silu(groupnorm(params["out_gn"], h, g))
    return conv(params["out_conv"], h)


def unet_param_count(params) -> int:
    return sum(p.size for p in jax.tree.leaves(params))
