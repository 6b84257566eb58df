"""The benchmark's own DDPM U-Net: the weights it hands the program, made
from the seed in one jitted call, and a plain forward pass that the checks
compare the program against.  Nothing here imports the program.

The architecture is Ho et al. 2020's denoiser as ``configs/<name>.json``
sizes it: a sinusoidal time embedding through a two-layer MLP, a multi-hot
label projection added to it, NHWC 3x3 convolutions, residual blocks
(GroupNorm, SiLU, conv, time projection, GroupNorm, SiLU, conv, a 1x1 skip
where widths change), single-block self-attention at the listed
resolutions, stride-2 convolutions down, nearest-neighbour x2 and a
convolution up.  The parameter tree is laid out the way the program's
``unet_apply`` reads it.

Every weight is drawn N(0, 1/fan_in), biases are zero and GroupNorm scales
one: unlike a zero-initialised output layer, every layer then moves the
denoiser's output, so a fault anywhere in the network shows in the samples.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp


def widths(cfg: dict) -> List[int]:
    return [cfg["base_width"] * m for m in cfg["width_mults"]]


def init(key, cfg: dict) -> Dict:
    """The whole parameter tree from one key, each leaf from its own
    ``fold_in(key, i)``; jit it to make the weights in one call."""
    dtype = jnp.dtype(cfg["dtype"])
    counter = iter(range(1 << 20))

    def w(*shape):
        fan_in = math.prod(shape[:-1])
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    def conv(kh, cin, cout):
        return {"w": w(kh, kh, cin, cout), "b": jnp.zeros((cout,), dtype)}

    def gn(c):
        return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}

    td = cfg["time_dim"]

    def res(cin, cout):
        p = {"gn1": gn(cin), "conv1": conv(3, cin, cout), "time": w(td, cout),
             "gn2": gn(cout), "conv2": conv(3, cout, cout)}
        if cin != cout:
            p["skip"] = conv(1, cin, cout)
        return p

    def attn(c):
        return {"gn": gn(c), "wq": w(c, c), "wk": w(c, c), "wv": w(c, c),
                "wo": w(c, c)}

    ws = widths(cfg)
    params = {"time_mlp": {"w1": w(td, td), "w2": w(td, td)},
              "label_proj": w(cfg["n_classes"], td),
              "stem": conv(3, cfg["channels"], ws[0]),
              "out_gn": gn(ws[0]),
              "out_conv": conv(3, ws[0], cfg["channels"])}
    size = cfg["image_size"]
    skip_c = [ws[0]]
    cin = ws[0]
    down = []
    for i, c in enumerate(ws):
        level = {"res": [], "attn": []}
        for _ in range(cfg["n_res_blocks"]):
            level["res"].append(res(cin, c))
            level["attn"].append(attn(c) if size in cfg["attn_resolutions"]
                                 else None)
            cin = c
            skip_c.append(c)
        if i < len(ws) - 1:
            level["down"] = conv(3, c, c)
            skip_c.append(c)
            size //= 2
        down.append(level)
    params["down"] = down
    params["mid"] = {"res1": res(cin, cin), "attn": attn(cin),
                     "res2": res(cin, cin)}
    up = []
    for i, c in reversed(list(enumerate(ws))):
        level = {"res": [], "attn": []}
        for _ in range(cfg["n_res_blocks"] + 1):
            level["res"].append(res(cin + skip_c.pop(), c))
            level["attn"].append(attn(c) if size in cfg["attn_resolutions"]
                                 else None)
            cin = c
        if i > 0:
            level["up"] = conv(3, c, c)
            size *= 2
        up.append(level)
    params["up"] = up
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Plain forward
# ---------------------------------------------------------------------------

def _conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _group_norm(p, x, groups, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _res(p, x, emb, groups):
    h = _conv(p["conv1"], _silu(_group_norm(p["gn1"], x, groups)))
    h = h + (_silu(emb) @ p["time"])[:, None, None, :]
    h = _conv(p["conv2"], _silu(_group_norm(p["gn2"], h, groups)))
    skip = x if "skip" not in p else \
        jnp.einsum("bhwc,cd->bhwd", x, p["skip"]["w"][0, 0]) + p["skip"]["b"]
    return skip + h


def _attn(p, x, heads, groups):
    b, hh, ww, c = x.shape
    n, dh = hh * ww, c // heads
    h = _group_norm(p["gn"], x, groups).reshape(b, n, c)
    q, k, v = ((h @ p[name]).reshape(b, n, heads, dh)
               for name in ("wq", "wk", "wv"))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    att = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, n, c)
    return x + (o @ p["wo"]).reshape(b, hh, ww, c)


def time_embedding(t, dim: int):
    """[cos, sin] of t times 10000^(-i/half), i < half."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def forward(params, x, t, y, cfg: dict):
    """eps-prediction of (B, H, W, C) images ``x`` at timesteps ``t`` (B,)
    under multi-hot labels ``y`` (B, n_classes), in ``x``'s dtype."""
    dtype = x.dtype
    g = cfg["groupnorm_groups"]
    heads = cfg["n_heads"]
    emb = time_embedding(t, cfg["time_dim"]).astype(dtype)
    emb = _silu(emb @ params["time_mlp"]["w1"]) @ params["time_mlp"]["w2"]
    emb = emb + y.astype(dtype) @ params["label_proj"]
    h = _conv(params["stem"], x)
    skips = [h]
    for level in params["down"]:
        for rp, ap in zip(level["res"], level["attn"]):
            h = _res(rp, h, emb, g)
            if ap is not None:
                h = _attn(ap, h, heads, g)
            skips.append(h)
        if "down" in level:
            h = _conv(level["down"], h, stride=2)
            skips.append(h)
    h = _res(params["mid"]["res1"], h, emb, g)
    h = _attn(params["mid"]["attn"], h, heads, g)
    h = _res(params["mid"]["res2"], h, emb, g)
    for level in params["up"]:
        for rp, ap in zip(level["res"], level["attn"]):
            h = _res(rp, jnp.concatenate([h, skips.pop()], axis=-1), emb, g)
            if ap is not None:
                h = _attn(ap, h, heads, g)
        if "up" in level:
            h = _conv(level["up"], jnp.repeat(jnp.repeat(h, 2, 1), 2, 2))
    h = _silu(_group_norm(params["out_gn"], h, g))
    return _conv(params["out_conv"], h).astype(dtype)


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)
