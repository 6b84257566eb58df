"""Partition rules: parameter / optimizer / activation sharding.

Mesh axes (launch/mesh.py): ``("data", "model")`` single pod (16×16) or
``("pod", "data", "model")`` multi-pod (2×16×16). Batch shards over
("pod","data"); tensor-parallel weights over "model"; FSDP (ZeRO-style)
weight+optimizer sharding over "data".

Rules are name/shape-driven over the param pytree (DESIGN.md §7):

  embed (V,D)          -> ("model", None)        vocab-parallel
  unembed (D,V)        -> (None, "model")
  wq/wk/wv (D,H·dh)    -> ("data", "model")      Megatron in-proj + FSDP
  wo (H·dh, D)         -> ("model", "data")      Megatron out-proj + FSDP
  w_gate/w_up (D,F)    -> ("data", "model")
  w_down (F,D)         -> ("model", "data")
  MoE experts (E,D,F)  -> ("model", "data", None) expert-parallel + FSDP
  MoE w_down (E,F,D)   -> ("model", None, "data")
  router (D,E)         -> replicated (fp32)
  mamba z/x/dt_proj    -> ("data", "model")      heads/channels over model
  mamba bc_proj (D,2N) -> ("data", None)         B,C shared across heads
  mamba out_proj (di,D)-> ("model", "data")      partial-sum + all-reduce
    (originally FSDP-only — the model axis was idle and every model shard
     recomputed the full layer; fixed in §Perf mamba2 hillclimb cycle 2)
  norms / scalars      -> replicated

Stacked layer subtrees (leading L axis from scan-over-layers) get a leading
``None``. Optimizer moments inherit the param spec (FSDP comes from the
"data" factor already present).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

STACKED_PREFIXES = ("layers", "mamba", "enc_layers", "dec_layers")

# leaf-name -> spec for 2D weights (non-stacked form)
_RULES_2D = {
    "wq": P("data", "model"), "wk": P("data", "model"),
    "wv": P("data", "model"), "wo": P("model", "data"),
    "w_gate": P("data", "model"), "w_up": P("data", "model"),
    "w_down": P("model", "data"),
    "w1": P("data", "model"), "w2": P("model", "data"),
    # mamba2: head/channel dims over "model" (the split-projection layout
    # exists exactly so these shard cleanly), BC replicated (shared across
    # heads), Megatron-style partial-sum out_proj.
    "z_proj": P("data", "model"), "x_proj": P("data", "model"),
    "dt_proj": P("data", "model"), "bc_proj": P("data", None),
    "out_proj": P("model", "data"),
    "time": P(None, None),
}

_RULES_3D_MOE = {
    "w_gate": P("model", "data", None), "w_up": P("model", "data", None),
    "w_down": P("model", None, "data"),
}

# inference layout (moe_ep2d): expert FFN dim over "data" so decode never
# all-gathers expert weights — see models/moe.moe_ep2d.
_RULES_3D_MOE_INFER = {
    "w_gate": P("model", None, "data"), "w_up": P("model", None, "data"),
    "w_down": P("model", "data", None),
}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "idx"):
            names.append(f"[{p.idx}]")
    return tuple(names)


def _drop_data(spec: P) -> P:
    """Inference layout: weights tensor-parallel only — drop the FSDP
    "data" factor (at decode the per-layer weight all-gather dwarfs the
    few tokens of useful traffic; weights replicate over "data" instead
    and every arch fits HBM at decode — EXPERIMENTS §Perf)."""
    out = []
    for e in spec:
        if e == "data":
            out.append(None)
        elif isinstance(e, tuple):
            kept = tuple(a for a in e if a != "data")
            out.append(kept if kept else None)
        else:
            out.append(e)
    return P(*out)


def param_spec_for(path, leaf, inference: bool = False) -> P:
    names = _path_names(path)
    name = names[-1] if names else ""
    stacked = any(n in STACKED_PREFIXES for n in names[:-1]) or \
        (names and names[0] in STACKED_PREFIXES)
    nd = leaf.ndim
    base_nd = nd - 1 if stacked else nd

    if name in ("embed", "tok_embed"):
        return P("model", None)
    if name == "unembed":
        return P(None, "model")
    if name == "router":
        return P(None, None, None) if stacked else P(None, None)

    spec = None
    if base_nd == 3 and name in _RULES_3D_MOE:
        # the ep2d inference layout keeps "data" (it carries the expert-FFN
        # dim there — weights are stationary by construction)
        rules = _RULES_3D_MOE_INFER if inference else _RULES_3D_MOE
        spec = rules[name]
    elif base_nd == 2 and name in _RULES_2D:
        spec = _RULES_2D[name]
        if inference:
            spec = _drop_data(spec)

    if spec is None:
        spec = P(*([None] * base_nd))
    if stacked:
        spec = P(None, *spec)
    assert len(spec) == nd, (names, leaf.shape, spec)
    return spec


def param_specs(params, inference: bool = False) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, l: param_spec_for(p, l, inference), params)


def opt_state_specs(params) -> Any:
    ps = param_specs(params)
    return {"m": ps, "v": ps, "step": P()}


# ---------------------------------------------------------------------------
# Stacked-client axis (vectorized CollaFuse engine, core/collab.py)
# ---------------------------------------------------------------------------

CLIENT_AXIS = "clients"


def client_stacked_specs(stacked_params, inference: bool = False,
                         client_axis: str = CLIENT_AXIS):
    """Specs for a client-stacked param pytree (leading (n_clients,) axis on
    every leaf): shard ONLY the stack axis — k identical-shape models train
    as pure model parallelism over clients, no cross-client collectives.

    Within-client dims stay replicated on purpose: the vmapped client axis
    lowers convolutions to feature_group_count=k grouped convs, whose
    feature dims XLA SPMD cannot partition independently of the group axis
    (combining "clients" with the per-client FSDP factors trips
    "feature dimension not divisible by feature_group_count"). Per-client
    FSDP over an inner axis is a ROADMAP open item."""
    del inference
    return jax.tree.map(
        lambda leaf: P(client_axis, *([None] * (leaf.ndim - 1))),
        stacked_params)


def client_opt_specs(stacked_params, client_axis: str = CLIENT_AXIS):
    """AdamW moments follow the stacked param specs; the per-client ``step``
    scalar is a (n_clients,) vector sharded over the client axis."""
    ps = client_stacked_specs(stacked_params, client_axis=client_axis)
    return {"m": ps, "v": ps, "step": P(client_axis)}


def client_batch_spec(ndim: int, client_axis: str = CLIENT_AXIS) -> P:
    """Round inputs xs/ys/mask are (n_batches, n_clients, B, ...) — the
    validity mask of the masked ragged engine is just the ndim=3 case:
    shard the client axis (dim 1), replicate the scanned batch dim."""
    return P(None, client_axis, *([None] * (ndim - 2)))


def shard_round_batches(mesh, xs, ys, mask=None):
    """Place padded round stacks (and the ragged-validity mask, when given)
    on ``mesh`` with the client axis sharded — the data-side counterpart of
    ``shard_vectorized_state``. The mask follows xs/ys's spec on its three
    shared dims, so a (client, batch) cell and its validity always live on
    the same shard (masking is local; no collectives)."""
    put = lambda a: jax.device_put(
        a, NamedSharding(mesh, sanitize_spec(client_batch_spec(a.ndim),
                                             a.shape, mesh)))
    if mask is None:
        return put(xs), put(ys), None
    return put(xs), put(ys), put(mask)


def cohort_uid_spec(client_axis: str = CLIENT_AXIS) -> P:
    """The (tier,) registry-uid vector of an identity-keyed cohort round
    (core/collab.make_vectorized_round(identity_keyed=True)): one id per
    cohort SLOT, so it shards with the slot axis — each shard folds its
    own clients' identities locally, no collectives."""
    return P(client_axis)


def shard_cohort_round(mesh, xs, ys, mask, uids):
    """Place one federated round's operands (repro.train's padded cohort
    stacks + the uid vector) on ``mesh`` — ``shard_round_batches`` plus
    the identity vector, so a cohort slot, its validity, and its uid
    always live on the same shard."""
    xs, ys, mask = shard_round_batches(mesh, xs, ys, mask)
    uids = jax.device_put(uids, NamedSharding(
        mesh, sanitize_spec(cohort_uid_spec(), uids.shape, mesh)))
    return xs, ys, mask, uids


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """The one constructor of every mesh in the repo.  Axis types are set
    explicitly to ``Auto``: the installed jax defaults ``jax.make_mesh`` to
    ``Explicit`` axes, under which jit refuses the placement-by-propagation
    these specs rely on (a vmapped client axis against a sharded operand).
    A mesh smaller than the host takes the first devices."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_client_mesh(n_clients: int):
    """1-D ``clients`` mesh over the most local devices that evenly divide
    n_clients (1 device on a plain CPU host — specs still apply, making the
    layout portable to real multi-device runs unchanged)."""
    n_dev = len(jax.devices())
    use = max(d for d in range(1, n_dev + 1) if n_clients % d == 0)
    return make_mesh((use,), (CLIENT_AXIS,))


def shard_client_stack(mesh, client_params, client_opt):
    """Place a client-stacked (params, AdamW state) pair on ``mesh``: every
    leaf's leading client axis over ``clients`` (client_stacked_specs /
    client_opt_specs), so each device holds its own clients' nets."""
    put = lambda tree, spec_tree: jax.tree.map(
        lambda x, s: jax.device_put(
            x, NamedSharding(mesh, sanitize_spec(s, x.shape, mesh))),
        tree, spec_tree)
    copt_specs = client_opt_specs(client_params)
    return (put(client_params, client_stacked_specs(client_params)),
            {k: put(client_opt[k], copt_specs[k])
             for k in ("m", "v", "step")})


def replicate(mesh, tree):
    """Every leaf of ``tree`` whole on every device of ``mesh``."""
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P())), tree)


def shard_vectorized_state(state, mesh):
    """Place a VectorizedCollabState on ``mesh``: stacked client trees over
    the ``clients`` axis, server model/opt replicated. jit then follows the
    input shardings — the vectorized round needs no collectives except the
    psum implied by the shared server update."""
    state.server_params = replicate(mesh, state.server_params)
    state.server_opt = replicate(mesh, state.server_opt)
    state.client_params, state.client_opt = shard_client_stack(
        mesh, state.client_params, state.client_opt)
    return state


# ---------------------------------------------------------------------------
# Batched sampling engine (core/sample_plan.py + core/sampler.py)
# ---------------------------------------------------------------------------


def sample_stack_spec(ndim: int, lead_axis: str = CLIENT_AXIS,
                      batch_axis: str = "data") -> P:
    """Sampling-engine stacks are (G|R, B, ...): the group/request lead
    axis shards over the "clients" mesh dimension (requests are
    client-parallel work, exactly like the stacked training axis) and the
    request-batch axis B over "data". ``sanitize_spec`` drops either axis
    when the wave size doesn't divide the mesh."""
    return P(lead_axis, batch_axis, *([None] * (ndim - 2)))


def sample_plan_specs(tables):
    """PartitionSpecs for a sample_plan.PlanTables: step tables and index
    vectors shard their lead (group/request) axis over "clients"; only
    group_y carries a request-batch dim to put on "data". Returned as the
    same NamedTuple so it zips leaf-for-leaf with the tables pytree."""
    return type(tables)(
        group_y=sample_stack_spec(tables.group_y.ndim),
        group_t=P(CLIENT_AXIS, None),
        group_t_prev=P(CLIENT_AXIS, None),
        group_active=P(CLIENT_AXIS, None),
        group_seed=P(CLIENT_AXIS),
        request_group=P(CLIENT_AXIS),
        request_client=P(CLIENT_AXIS),
        request_seed=P(CLIENT_AXIS),
        client_t=P(CLIENT_AXIS, None),
        client_t_prev=P(CLIENT_AXIS, None),
        client_active=P(CLIENT_AXIS, None))


def inject_specs(inject):
    """Specs for a sample_plan.InjectTables (cache-hit handoffs entering
    the engine): injected rows are group-axis work — lead axis over
    "clients", request batch over "data", exactly like the scanned
    stacks, so a hit row lands where its scan row would have."""
    return type(inject)(x=sample_stack_spec(inject.x.ndim),
                        y=sample_stack_spec(inject.y.ndim))


def handoff_spec(ndim: int, batch_axis: str = "data") -> P:
    """One cached server handoff x̂_{t_ζ} — a single (B, ...) entry of
    serve/prefix_cache.PrefixCache: no lead group axis (entries are
    per-group), batch over "data", pixels replicated."""
    return P(batch_axis, *([None] * (ndim - 1)))


def _place_tuple(mesh, tree, specs):
    return type(tree)(*[
        jax.device_put(a, NamedSharding(
            mesh, sanitize_spec(s, a.shape, mesh)))
        for a, s in zip(tree, specs)])


def shard_sample_plan(mesh, tables):
    """Place plan tables on ``mesh`` with the sampling specs — the
    inference counterpart of ``shard_round_batches``."""
    return _place_tuple(mesh, tables, sample_plan_specs(tables))


def shard_inject(mesh, inject):
    """Place a plan's injected cache-hit rows on ``mesh`` — the serve
    counterpart of ``shard_sample_plan`` for the InjectTables operand."""
    return _place_tuple(mesh, inject, inject_specs(inject))


# ---------------------------------------------------------------------------
# Activations / inputs
# ---------------------------------------------------------------------------


def mesh_batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_axis_size(mesh) -> int:
    n = 1
    for a in mesh_batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def batch_spec_for(mesh, global_batch: int, trailing: int) -> P:
    """Shard the leading batch dim over ("pod","data") when divisible, else
    replicate (long_500k has global_batch=1)."""
    axes = mesh_batch_axes(mesh)
    if global_batch % batch_axis_size(mesh) == 0:
        return P(axes, *([None] * trailing))
    return P(*([None] * (trailing + 1)))


def kv_cache_spec(mesh, cfg, global_batch: int) -> P:
    """Stacked cache (L, B, Hkv, C, dh). Heads over "model" when divisible;
    otherwise shard the sequence dim over "model" (GQA kv < model size —
    e.g. kv=8 on a 16-way model axis) and let SPMD reduce the partial
    softmax. Batch over ("pod","data") when divisible."""
    axes = mesh_batch_axes(mesh)
    bspec = axes if global_batch % batch_axis_size(mesh) == 0 else None
    if cfg.n_kv_heads and cfg.n_kv_heads % mesh.shape["model"] == 0:
        return P(None, bspec, "model", None, None)
    return P(None, bspec, None, "model", None)


def ssm_state_specs(mesh, cfg, global_batch: int, state_tree) -> Any:
    """Hybrid/SSM decode-state tree: mamba ssm/conv states + optional shared
    KV. Shard batch when divisible; heads of ssm state over "model" when
    divisible (mamba2 heads are plentiful: 80)."""
    axes = mesh_batch_axes(mesh)
    batch_ok = global_batch % batch_axis_size(mesh) == 0
    bspec = axes if batch_ok else None
    model = mesh.shape["model"]

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name == "ssm":
            # (..., B, H, P, N) with 1-2 leading stack dims
            lead = leaf.ndim - 4
            h_ok = cfg.ssm_n_heads % model == 0
            return P(*([None] * lead), bspec, "model" if h_ok else None,
                     None, None)
        if name == "conv":
            lead = leaf.ndim - 3
            return P(*([None] * lead), bspec, None, None)
        if name in ("k", "v"):  # shared attn cache (G, B, Hkv, C, dh)
            h_ok = cfg.n_kv_heads and cfg.n_kv_heads % model == 0
            if h_ok:
                return P(None, bspec, "model", None, None)
            return P(None, bspec, None, "model", None)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, state_tree)


def sanitize_spec(spec: P, shape, mesh) -> P:
    """Drop mesh axes from dims they don't evenly divide (e.g. vocab 51865
    on a 16-way axis — JAX in_shardings require exact divisibility) and
    axes the mesh doesn't have (a clients-only mesh has no "data"/"model")."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(a for a in axes if a in mesh.shape)
        if not kept:
            out.append(None)
            continue
        size = 1
        for a in kept:
            size *= mesh.shape[a]
        if shape[i] % size != 0:
            out.append(None)
        else:
            out.append(kept if isinstance(entry, tuple) else kept[0])
    return P(*out)


def with_sharding(tree, spec_tree, mesh):
    return jax.tree.map(
        lambda sds, spec: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=NamedSharding(mesh,
                                   sanitize_spec(spec, sds.shape, mesh))),
        tree, spec_tree)
