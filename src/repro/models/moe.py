"""Mixture-of-Experts layer.

Two execution modes:

* ``dense``: every expert computed on every token, combined with router
  weights. Exact, simple, used for reduced smoke configs (<=4 experts).
* ``ep`` (expert-parallel): capacity-based token dispatch with
  ``jax.lax.all_to_all`` inside ``jax.shard_map``. Experts are sharded over
  the "model" mesh axis, tokens over the batch axes. This is the production
  path exercised by the multi-pod dry-run — the all-to-all traffic it emits
  is what the roofline's collective term measures for MoE archs.

Both modes share the same parameters and the same top-k router, and agree
numerically up to capacity drops (tested in tests/test_moe.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import dense_init


def moe_init(key, cfg: ArchConfig, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, kg, ku, kd = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    return {
        "router": dense_init(kr, d, e, jnp.float32),  # router kept fp32
        "w_gate": (jax.random.normal(kg, (e, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ku, (e, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(kd, (e, f, d)) / math.sqrt(f)).astype(dtype),
    }


def _router(params, x, top_k: int):
    """x: (N, D) -> (probs (N,E) f32, topk_w (N,k) f32, topk_idx (N,k) i32)."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, top_k)
    topk_w = topk_w / jnp.clip(topk_w.sum(-1, keepdims=True), 1e-9)
    return probs, topk_w, topk_idx


def _aux_loss(probs, topk_idx, n_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    counts = jnp.zeros((n_experts,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    f = counts / jnp.clip(counts.sum(), 1.0)
    p = probs.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_ffn(w_gate, w_up, w_down, tokens):
    """tokens: (E, C, D) grouped per expert -> (E, C, D)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", tokens, w_gate))
    u = jnp.einsum("ecd,edf->ecf", tokens, w_up)
    return jnp.einsum("ecf,efd->ecd", g * u, w_down)


# ---------------------------------------------------------------------------
# dense mode
# ---------------------------------------------------------------------------


def moe_dense(params, x, cfg: ArchConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D). Computes every expert on every token (smoke configs)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    probs, topk_w, topk_idx = _router(params, xt, cfg.top_k)
    combine = jnp.zeros_like(probs)
    combine = combine.at[jnp.arange(xt.shape[0])[:, None], topk_idx].set(topk_w)
    g = jax.nn.silu(jnp.einsum("nd,edf->nef", xt, params["w_gate"]))
    u = jnp.einsum("nd,edf->nef", xt, params["w_up"])
    y_e = jnp.einsum("nef,efd->ned", g * u, params["w_down"])
    y = jnp.einsum("ned,ne->nd", y_e, combine.astype(y_e.dtype))
    aux = _aux_loss(probs, topk_idx, cfg.n_experts)
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# expert-parallel mode (shard_map + all_to_all)
# ---------------------------------------------------------------------------


def _dispatch_local(xt, topk_w, topk_idx, n_experts: int, capacity: int):
    """Pack tokens into per-expert slots (E, C) on this shard.

    Returns (buffer (E*C, D), meta needed to undo the packing).
    """
    N, D = xt.shape
    k = topk_idx.shape[1]
    M = N * k
    flat_e = topk_idx.reshape(M)
    flat_w = topk_w.reshape(M)
    token_id = jnp.repeat(jnp.arange(N), k)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(M) - first
    keep = pos_in_e < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos_in_e, 0)

    buf = jnp.zeros((n_experts * capacity, D), xt.dtype)
    vals = xt[token_id[order]] * keep[:, None].astype(xt.dtype)
    buf = buf.at[slot].add(vals)  # unkept assignments all add into slot 0 *0
    meta = dict(order=order, keep=keep, slot=slot,
                token_id=token_id, weight=flat_w)
    return buf, meta


def _combine_local(buf_out, meta, N: int):
    """Inverse of _dispatch_local: (E*C, D) -> (N, D) weighted by router."""
    order, keep, slot = meta["order"], meta["keep"], meta["slot"]
    token_id, weight = meta["token_id"], meta["weight"]
    gathered = buf_out[slot] * keep[:, None].astype(buf_out.dtype)
    w_sorted = weight[order].astype(buf_out.dtype)
    y = jnp.zeros((N, buf_out.shape[-1]), buf_out.dtype)
    y = y.at[token_id[order]].add(gathered * w_sorted[:, None])
    return y


def moe_ep(params, x, cfg: ArchConfig, mesh, batch_axes, model_axis="model"):
    """Expert-parallel MoE: shard_map over the full mesh.

    x: (B, S, D) batch-sharded over ``batch_axes``; experts sharded over
    ``model_axis``. Emits one all-to-all pair per layer (dispatch + return).
    """
    P = jax.sharding.PartitionSpec
    ep = mesh.shape[model_axis]
    assert cfg.n_experts % ep == 0, (cfg.n_experts, ep)
    e_loc = cfg.n_experts // ep

    def local(x_loc, router, w_gate, w_up, w_down):
        B, S, D = x_loc.shape
        xt = x_loc.reshape(-1, D)
        N = xt.shape[0]
        probs, topk_w, topk_idx = _router({"router": router}, xt, cfg.top_k)
        aux = _aux_loss(probs, topk_idx, cfg.n_experts)
        capacity = max(int(cfg.top_k * N / cfg.n_experts * cfg.capacity_factor), 4)

        buf, meta = _dispatch_local(xt, topk_w, topk_idx, cfg.n_experts, capacity)
        # (E*C, D) -> a2a over model axis: rows grouped by destination shard
        buf = jax.lax.all_to_all(
            buf.reshape(ep, e_loc * capacity, D), model_axis, 0, 0, tiled=True)
        # now rows grouped by source shard: (ep * e_loc * C, D)
        toks = buf.reshape(ep, e_loc, capacity, D).transpose(1, 0, 2, 3)
        toks = toks.reshape(e_loc, ep * capacity, D)
        out = _expert_ffn(w_gate, w_up, w_down, toks)
        out = out.reshape(e_loc, ep, capacity, D).transpose(1, 0, 2, 3)
        out = out.reshape(ep * e_loc * capacity, D)
        out = jax.lax.all_to_all(
            out.reshape(ep, e_loc * capacity, D), model_axis, 0, 0, tiled=True)
        y = _combine_local(out.reshape(-1, D), meta, N)
        # aux is identical on all model shards of the same batch shard; mean
        # over batch shards happens in the loss reduction.
        return y.reshape(B, S, D).astype(x_loc.dtype), aux[None]

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(), P(model_axis, None, None),
                  P(model_axis, None, None), P(model_axis, None, None)),
        out_specs=(P(batch_axes, None, None), P(batch_axes)),
        check_vma=False,
    )
    y, aux = f(x, params["router"], params["w_gate"], params["w_up"],
               params["w_down"])
    return y, jnp.mean(aux)


# ---------------------------------------------------------------------------
# decode-time 2D mode: expert-parallel over "model" × F-parallel over "data"
# ---------------------------------------------------------------------------


def moe_ep2d(params, x, cfg: ArchConfig, mesh, batch_axes,
             model_axis="model", data_axis="data"):
    """Inference MoE: weights are STATIONARY (experts over "model", the
    expert FFN dim over "data"); the token set — tiny at decode — moves
    instead: all-gather tokens over the batch axes, a2a-dispatch over
    "model", partial-F expert compute, psum over "data", slice back.

    Rationale (EXPERIMENTS §Perf, kimi decode hillclimb): the training
    layout FSDP-gathers ~2.1 GB of expert weights per layer per step, which
    at decode (8 tokens/device) made kimi-k2 collective-bound (5.2 s
    roofline term). Moving the 115 KB of tokens instead of the GBs of
    weights removes ~99% of collective bytes. NOT used for train/prefill,
    where the weight gather amortizes over 64k+ tokens per device.
    """
    P = jax.sharding.PartitionSpec
    ep = mesh.shape[model_axis]
    e_loc = cfg.n_experts // ep
    fp = mesh.shape[data_axis]
    assert cfg.d_ff % fp == 0, (cfg.d_ff, fp)

    def local(x_loc, router, w_gate, w_up, w_down):
        B, S, D = x_loc.shape
        xt = x_loc.reshape(-1, D)
        n_loc = xt.shape[0]
        xt_all = jax.lax.all_gather(xt, batch_axes, axis=0, tiled=True)
        N = xt_all.shape[0]
        probs, topk_w, topk_idx = _router({"router": router}, xt_all,
                                          cfg.top_k)
        aux = _aux_loss(probs, topk_idx, cfg.n_experts)
        capacity = max(int(cfg.top_k * N / cfg.n_experts
                           * cfg.capacity_factor), 4)
        buf, meta = _dispatch_local(xt_all, topk_w, topk_idx, cfg.n_experts,
                                    capacity)
        buf = jax.lax.all_to_all(
            buf.reshape(ep, e_loc * capacity, D), model_axis, 0, 0,
            tiled=True)
        toks = buf.reshape(ep, e_loc, capacity, D).transpose(1, 0, 2, 3)
        toks = toks.reshape(e_loc, ep * capacity, D)
        out = _expert_ffn(w_gate, w_up, w_down, toks)  # partial over F slice
        out = jax.lax.psum(out, data_axis)
        out = out.reshape(e_loc, ep, capacity, D).transpose(1, 0, 2, 3)
        out = out.reshape(ep * e_loc * capacity, D)
        out = jax.lax.all_to_all(
            out.reshape(ep, e_loc * capacity, D), model_axis, 0, 0,
            tiled=True)
        y_all = _combine_local(out.reshape(-1, D), meta, N)
        shard = jax.lax.axis_index(batch_axes)
        y = jax.lax.dynamic_slice_in_dim(y_all, shard * n_loc, n_loc, axis=0)
        return y.reshape(B, S, D).astype(x_loc.dtype), aux[None]

    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(),
                  P(model_axis, None, data_axis),
                  P(model_axis, None, data_axis),
                  P(model_axis, data_axis, None)),
        out_specs=(P(batch_axes, None, None), P(batch_axes)),
        check_vma=False,
    )
    y, aux = f(x, params["router"], params["w_gate"], params["w_up"],
               params["w_down"])
    return y, jnp.mean(aux)


def moe_apply(params, x, cfg: ArchConfig, runtime) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if runtime is not None and runtime.mesh is not None:
        if runtime.moe_mode == "ep":
            return moe_ep(params, x, cfg, runtime.mesh, runtime.batch_axes,
                          runtime.model_axis)
        if runtime.moe_mode == "ep2d":
            return moe_ep2d(params, x, cfg, runtime.mesh, runtime.batch_axes,
                            runtime.model_axis)
    return moe_dense(params, x, cfg)
