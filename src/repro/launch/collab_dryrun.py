import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the PAPER'S OWN technique on the production mesh: lower +
compile one full Alg.-1 collaborative step (client fwd/bwd/update + server
fwd/bwd/update from the re-noised payload), one Alg.-2 server denoise
pass — global batch sharded over ("pod","data"), server model replicated
(DESIGN.md §4) — and one VECTORIZED multi-client round (core/collab.py):
k stacked client models sharded over a dedicated "clients" mesh axis,
per-batch client updates vmapped, one concatenated server update, scanned
over batches in a single program. The ``ragged_round`` entry compiles the
MASKED engine — padded (n_batches, k, B_max) stacks plus a validity mask
sharded like the data — proving heterogeneous-client rounds lower on the
same mesh with no extra collectives beyond the dense round's. The
``vectorized_sample`` entry compiles the batched SAMPLING engine
(core/sampler.make_sample_engine): one program serving k+1 requests with
heterogeneous cut points (GM, ICM, and two collaborative cuts, plus one
dedup'd duplicate), request/group stacks sharded ("clients", "data")
per sharding/specs.sample_plan_specs. The ``train_runtime`` entry
compiles the IDENTITY-KEYED cohort round of the federated training
runtime (repro.train): the masked engine plus a (tier,) registry-uid
vector sharded with the cohort axis (specs.cohort_uid_spec) — proving a
partial-participation tier round lowers on the same mesh with the same
collectives as the dense round.

    PYTHONPATH=src python -m repro.launch.collab_dryrun [--multi-pod] \
        [--image-size 64] [--batch 256] [--t-cut 200] [--T 1000] \
        [--clients 4] [--round-batches 2]
"""
import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import numpy as np

from repro.configs.ddpm_unet import CONFIG, UNetConfig
from repro.core.collab import make_vectorized_round
from repro.core.protocol import client_losses, server_loss
from repro.core.sample_plan import SampleRequest, plan_requests
from repro.core.sampler import make_sample_engine, server_denoise
from repro.core.schedules import DiffusionSchedule
from repro.core.splitting import CutPoint
from repro.core.unet import init_unet, unet_apply
from repro.launch.dryrun import collective_census
from repro.launch.mesh import make_production_mesh
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.sharding.specs import (CLIENT_AXIS, client_opt_specs,
                                  client_stacked_specs, cohort_uid_spec,
                                  make_mesh, mesh_batch_axes,
                                  sample_plan_specs, sanitize_spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--T", type=int, default=1000)
    ap.add_argument("--t-cut", type=int, default=200)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--round-batches", type=int, default=2)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    baxes = mesh_batch_axes(mesh)
    ucfg = dataclasses.replace(
        CONFIG, image_size=args.image_size, base_width=128,
        width_mults=(1, 2, 2, 4), attn_resolutions=(16,), time_dim=512,
        dtype="float32")
    sched = DiffusionSchedule.linear(args.T)
    cut = CutPoint(args.T, args.t_cut)
    apply_fn = lambda p, x, t, y: unet_apply(p, x, t, y, ucfg)
    opt_cfg = AdamWConfig(lr=1e-3)

    def collab_step(cp, co, sp, so, x0, y, key):
        def closs(c):
            return client_losses(c, x0, y, key, sched, cut, apply_fn)
        (lc, payload), gc = jax.value_and_grad(closs, has_aux=True)(cp)
        cp, co, _ = adamw_update(cp, gc, co, opt_cfg)
        ls, gs = jax.value_and_grad(server_loss)(sp, payload, sched, apply_fn)
        sp, so, _ = adamw_update(sp, gs, so, opt_cfg)
        return cp, co, sp, so, lc, ls

    shapes = jax.eval_shape(functools.partial(init_unet, cfg=ucfg),
                            jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        shapes)
    opt = jax.eval_shape(init_opt_state, params)
    opt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), opt)
    bsh = NamedSharding(mesh, P(baxes, None, None, None))
    x0 = jax.ShapeDtypeStruct(
        (args.batch, args.image_size, args.image_size, 3), jnp.float32,
        sharding=bsh)
    yv = jax.ShapeDtypeStruct((args.batch, ucfg.n_classes), jnp.float32,
                              sharding=NamedSharding(mesh, P(baxes, None)))
    keyv = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)

    # --- vectorized multi-client round on a ("clients", "data") mesh -----
    k = args.clients
    n_dev = len(jax.devices())
    if n_dev % k or ucfg.base_width % k:
        raise SystemExit(
            f"--clients {k}: must divide the device count ({n_dev}) and the "
            f"UNet base width ({ucfg.base_width}). XLA SPMD partitions the "
            "vmapped per-client convs as grouped convolutions whose feature "
            "dim interleaves clients x channels, so the sharded client count "
            "must tile the channel blocks (powers of two here).")
    cmesh = make_mesh((k, n_dev // k), (CLIENT_AXIS, "data"))
    csh = lambda s, spec: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=jax.sharding.NamedSharding(
            cmesh, sanitize_spec(spec, s.shape, cmesh)))
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((k,) + s.shape, s.dtype), shapes)
    cparams = jax.tree.map(csh, stacked, client_stacked_specs(stacked))
    copt_shapes = {
        "m": stacked, "v": stacked,
        "step": jax.ShapeDtypeStruct((k,), jnp.int32)}
    cspecs = client_opt_specs(stacked)
    copt = {kk: jax.tree.map(csh, copt_shapes[kk], cspecs[kk])
            for kk in ("m", "v")}
    copt["step"] = csh(copt_shapes["step"], cspecs["step"])
    crep = jax.sharding.NamedSharding(cmesh, P())
    sparams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=crep),
        shapes)
    sopt = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=crep),
        jax.eval_shape(init_opt_state, shapes))
    per_client_b = max(args.batch // k, 1)
    xs = csh(jax.ShapeDtypeStruct(
        (args.round_batches, k, per_client_b, args.image_size,
         args.image_size, 3), jnp.float32),
        P(None, CLIENT_AXIS, "data", None, None, None))
    ys = csh(jax.ShapeDtypeStruct(
        (args.round_batches, k, per_client_b, ucfg.n_classes), jnp.float32),
        P(None, CLIENT_AXIS, "data", None))
    ckey = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=crep)
    round_fn = make_vectorized_round(sched, cut, apply_fn, opt_cfg,
                                     masked=False)
    masked_round_fn = make_vectorized_round(sched, cut, apply_fn, opt_cfg,
                                            masked=True)
    mask = csh(jax.ShapeDtypeStruct(
        (args.round_batches, k, per_client_b), jnp.float32),
        P(None, CLIENT_AXIS, "data"))
    cohort_round_fn = make_vectorized_round(sched, cut, apply_fn, opt_cfg,
                                            masked=True, identity_keyed=True)
    uids = csh(jax.ShapeDtypeStruct((k,), jnp.int32), cohort_uid_spec())

    # --- batched sampling engine: k requests, heterogeneous cuts ---------
    # one request per client; cuts span GM (0), the configured t_cut, its
    # half, and ICM (T) — plus a duplicate of request 0 so the plan carries
    # a dedup'd group. The (G|R, B) stacks shard over ("clients", "data").
    cut_menu = [args.t_cut, max(args.t_cut // 2, 1), 0, args.T]
    reqs = []
    for c in range(k):
        yy = np.zeros((per_client_b, ucfg.n_classes), np.float32)
        yy[:, c % ucfg.n_classes] = 1.0
        reqs.append(SampleRequest(client=c, t_cut=cut_menu[c % len(cut_menu)],
                                  y=yy))
    reqs.append(SampleRequest(client=0, t_cut=reqs[0].t_cut, y=reqs[0].y))
    plan = plan_requests(reqs, args.T, n_clients=k)
    tables = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=jax.sharding.NamedSharding(
                cmesh, sanitize_spec(s, a.shape, cmesh))),
        plan.tables, sample_plan_specs(plan.tables))
    sample_engine = make_sample_engine(
        sched, apply_fn, (args.image_size, args.image_size, 3),
        use_pallas=False, jit=False)

    results = {}
    for name, fn, fargs, fmesh in (
        ("collab_train_step",
         collab_step, (params, opt, params, opt, x0, yv, keyv), mesh),
        ("server_denoise",
         lambda p, k_, y: server_denoise(
             p, k_, y, (args.batch, args.image_size, args.image_size, 3),
             sched, cut, apply_fn), (params, keyv, yv), mesh),
        ("vectorized_round",
         round_fn, (cparams, copt, sparams, sopt, xs, ys, ckey), cmesh),
        ("ragged_round",
         masked_round_fn,
         (cparams, copt, sparams, sopt, xs, ys, mask, ckey), cmesh),
        ("train_runtime",
         cohort_round_fn,
         (cparams, copt, sparams, sopt, xs, ys, mask, uids, ckey), cmesh),
        ("vectorized_sample",
         sample_engine, (sparams, cparams, ckey, tables), cmesh),
    ):
        t0 = time.time()
        with fmesh:
            compiled = jax.jit(fn).lower(*fargs).compile()
        cost = compiled.cost_analysis() or {}
        census = collective_census(compiled.as_text())
        mem = compiled.memory_analysis()
        results[name] = {
            "compile_s": round(time.time() - t0, 1),
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "collectives": census,
            "collective_bytes": sum(c["bytes"] for c in census.values()),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        }
        print(name, json.dumps(results[name]))

    tag = "collafuse_unet__%s" % ("pod2x16x16" if args.multi_pod
                                  else "pod16x16")
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump({"tag": tag, "unet": dataclasses.asdict(ucfg),
                   "T": args.T, "t_cut": args.t_cut, "batch": args.batch,
                   "results": results}, f, indent=1)
    print("saved", tag)


if __name__ == "__main__":
    main()
