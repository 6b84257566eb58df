#!/usr/bin/env python3
"""Drive the collaborative serve and train paths once on a TPU, at the full
width of the paper's DDPM U-Net (``configs/ddpm_unet.CONFIG``: 32x32 RGB,
base width 64, width mults (1, 2, 2), attention at 16x16, 8 labels), with
random weights made from a seed.

    python3 chip_smoke.py               # one chip: serve, then train
    python3 chip_smoke.py --four-chips  # four chips: the sharded client
                                        # mesh against one device

One process touches JAX and starts no children.  It exits non-zero, and
prints no result, when JAX finds no TPU (``JAX_PLATFORMS=cpu`` included).

One chip, in order:

* serve — ``ServeRuntime`` as ``launch/collab_serve`` builds it for
  ``--unet-config paper``: 16 requests from 3 clients at two cut points,
  served cold and then again warm.  The warm pass must hit the prefix
  cache and trace nothing new; every output is finite and of the
  sample shape; the lowered server and client stages both contain the
  Pallas ``ddpm_step`` (``tpu_custom_call``); and one batched step of
  that kernel agrees with its ``jnp`` oracle on the chip.
* train — ``TrainRuntime`` as ``launch/collab_train.fresh_runtime``
  builds it: 4 clients, full participation (one tier, one round
  program), 3 rounds with finite losses.  A checkpoint taken after the
  first round and restored into a fresh runtime must finish the run
  bitwise equal to the uninterrupted one.

``--four-chips`` runs only this: the same train runtime, 4 clients on a
4-device ``clients`` mesh (one client per chip) and on a one-device mesh
of the same host, 3 rounds each, with float32 dots and convolutions at
"highest" precision; per-round losses and post-round parameters must
agree within the tolerances below.

Each phase prints its wall seconds, compile seconds (XLA compile or
persistent-cache load) apart from trace/lower seconds, engine traces,
persistent-cache requests and hits, prefix-cache hits, physical model
calls, losses and the device's peak bytes in use.  The last line of
standard output is the JSON result, and only a run in which every check
passed prints it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# ---------------------------------------------------------------------------
# Tolerances, each fixed before any chip run.
# ---------------------------------------------------------------------------

# Pallas ddpm_step against its jnp oracle, on identical inputs and
# coefficients: both evaluate (x - c*e)*a + s*n in float32 and may differ
# only in rounding order (fused multiply-add or not), i.e. a few ulps of
# the largest term.  Per element: |pallas - oracle| <= K_ULP * eps32 *
# (|a*x| + |a*c*e| + |s*n|).  K_ULP = 8 is twice the worst rounding chain
# of that three-term expression.
KERNEL_K_ULP = 8.0

# Four devices against one, same float32 round program, both run with
# float32 dots and convolutions at FOUR_CHIP_PRECISION.  At the TPU's
# default precision a float32 dot rounds its operands to bfloat16, and
# the two partitionings then round differently at every step: a run at
# default precision drifted to a 4e-2 loss gap by round 2, which no
# tolerance can tell from a fault.  At "highest" the programs differ only
# in how XLA partitions them: the server gradient's sum over the four
# clients' rows becomes a cross-device all-reduce and reduction orders
# change, i.e. ~1e-6 relative per reduction.  AdamW turns that into
# larger parameter differences: where a gradient coordinate is near zero,
# rounding can flip its sign, and the early steps are ~lr*sign(g), so a
# flipped coordinate moves by up to 2*lr.  Over 3 rounds x 4 batches of
# lr = 1e-3 that is a small set of coordinates moved by <= 0.024, against
# parameters of rms ~0.03-0.05.  A sharding fault (a client trained on
# another client's rows, a server update from one device's quarter batch)
# moves parameters by a whole update, ~lr per coordinate per step, i.e.
# > 10% of the norm after 12 steps.
FOUR_CHIP_PRECISION = "highest"
FOUR_CHIP_PARAM_RTOL = 1e-2      # ||p4 - p1|| / ||p1||, per net per round
# Losses are means over thousands of elements: rounding moves them ~1e-6;
# the parameter drift above moves them further, a fault by percent.
FOUR_CHIP_LOSS_RTOL = 1e-3       # |l4 - l1| / |l1|, per round

# ---------------------------------------------------------------------------
# What the phases run.  The CLI arguments are the entry points' own.
# ---------------------------------------------------------------------------

SERVE_ARGV = ["--unet-config", "paper", "--clients", "3", "--T", "1000",
              "--max-wave", "4", "--batch", "4", "--seed", "0"]
# client -> cut point t_zeta: two cut points, so two shape buckets
SERVE_CUTS = (200, 100, 100)
TRAIN_ARGV = ["--unet-config", "paper", "--clients", "4", "--policy", "full",
              "--rounds", "3", "--T", "1000", "--t-cut", "200", "--batch", "8",
              "--batches-per-round", "4", "--n-per-client", "64",
              "--seed", "0"]
KERNEL_MARKER = "tpu_custom_call"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Compile accounting from JAX's monitoring events.  ``compile_s`` is
    time in XLA compile or in loading from the persistent cache;
    ``trace_lower_s`` is tracing plus lowering to StableHLO.  A program
    that asked the persistent cache and missed is kept with its compile
    seconds."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self._asked = threading.local()
        self.requests = self.hits = 0
        self.compile_s = self.trace_lower_s = 0.0
        self.missed = []                     # (program, compile seconds)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == CACHE_REQUEST:
            self._asked.hit = False
            with self._lock:
                self.requests += 1
        elif event == CACHE_HIT:
            self._asked.hit = True
            with self._lock:
                self.hits += 1

    def _duration(self, event, secs, fun_name="?", **_):
        with self._lock:
            if event == BACKEND_COMPILE:
                self.compile_s += secs
                if getattr(self._asked, "hit", None) is False:
                    self.missed.append((fun_name, secs))
                self._asked.hit = None
            elif event in (TRACE, LOWER):
                self.trace_lower_s += secs

    def snapshot(self):
        with self._lock:
            return (self.requests, self.hits, self.compile_s,
                    self.trace_lower_s, len(self.missed))

    def since(self, snap) -> str:
        req, hits, comp, tl, n_missed = snap
        with self._lock:
            missed = sorted(self.missed[n_missed:], key=lambda m: -m[1])
            return (f"compile_s={self.compile_s - comp} "
                    f"trace_lower_s={self.trace_lower_s - tl} "
                    f"persistent_cache_requests={self.requests - req} "
                    f"persistent_cache_hits={self.hits - hits} "
                    f"persistent_cache_misses={len(missed)} "
                    f"slowest_misses={[(n, round(s, 3)) for n, s in missed[:4]]}")


class Recorder:
    """Stands in for one of a runtime's jitted programs and keeps the
    arguments of its first call, so the smoke can lower that call again
    (the trace is cached: the runtime's trace counter does not move) and
    read where its operands were placed."""

    def __init__(self, fn):
        self.fn = fn
        self.args = None

    def __call__(self, *args):
        if self.args is None:
            self.args = args
        return self.fn(*args)

    def lowered_text(self) -> str:
        return self.fn.lower(*self.args).as_text()


def peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not measured"))


def serve_queue(n_classes: int, batch: int):
    """16 requests, 8 per cut point.  Each bucket gets two waves of 4 over
    labels {0, 1}: the first computes both server prefixes, the second
    finds them in the prefix cache, so the cold pass compiles every
    signature the warm pass presents."""
    import numpy as np
    from repro.core.sample_plan import SampleRequest
    eye = np.eye(n_classes, dtype=np.float32)
    y = lambda label: np.broadcast_to(eye[label], (batch, n_classes)).copy()
    cut0 = [SampleRequest(client=0, t_cut=SERVE_CUTS[0], y=y(i % 2))
            for i in range(8)]
    cut1 = [SampleRequest(client=1 + i % 2, t_cut=SERVE_CUTS[1 + i % 2],
                          y=y((i // 2) % 2)) for i in range(8)]
    return cut0 + cut1


def serve_report(tag, rep, log_line):
    print(f"{tag}: wall_s={rep['wall_s']} waves={rep['waves']} "
          f"latency_p50_s={rep['latency_p50_s']} "
          f"engine_traces={rep['engine_traces']} "
          f"cache_hits={rep['cache_hits']} "
          f"cache_misses={rep['cache_misses']} "
          f"server_calls_physical={rep['server_calls_physical']} "
          f"client_calls_physical={rep['client_calls_physical']} "
          f"{log_line}")


def kernel_vs_oracle(jax, jnp, sched, shape):
    """One batched ddpm_step through the Pallas kernel and through its jnp
    oracle, same inputs and coefficients; returns the worst ratio of the
    difference to the bound KERNEL_K_ULP allows (must be <= 1)."""
    from repro.kernels.ddpm_step.kernel import ddpm_step_pallas_batched
    from repro.kernels.ddpm_step.ops import step_coefficients
    from repro.kernels.ddpm_step.ref import ddpm_step_ref
    kx, ke, kn = jax.random.split(jax.random.PRNGKey(1), 3)
    x, e, n = (jax.random.normal(k, shape, jnp.float32)
               for k in (kx, ke, kn))
    t = jnp.linspace(1.0, float(sched.T), shape[0])  # sigma = 0 at t = 1
    a, c, s = step_coefficients(sched, t)
    col = lambda v: v.reshape((-1,) + (1,) * (len(shape) - 1))
    pallas = jax.jit(ddpm_step_pallas_batched)(x, e, n, a, c, s)
    oracle = jax.jit(ddpm_step_ref)(x, e, n, col(a), col(c), col(s))
    bound = KERNEL_K_ULP * jnp.finfo(jnp.float32).eps * (
        jnp.abs(col(a) * x) + jnp.abs(col(a * c) * e) + jnp.abs(col(s) * n))
    return float(jnp.max(jnp.abs(pallas - oracle) / bound))


def serve_phase(jax, log):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.schedules import DiffusionSchedule
    from repro.launch import collab_serve

    t0, snap = time.perf_counter(), log.snapshot()
    args = collab_serve.parse_args(SERVE_ARGV)
    key = jax.random.PRNGKey(args.seed)
    sp, cp, apply_fn = collab_serve.build_models(args, key)
    sched = DiffusionSchedule.linear(args.T)
    rt = collab_serve.make_runtime(args, sp, cp, apply_fn, sched, key)
    rt._server_stage = server = Recorder(rt._server_stage)
    rt._client_stage = client = Recorder(rt._client_stage)
    queue = serve_queue(args.n_classes, args.batch)
    print(f"serve/setup: unet={collab_serve.UNET_CONFIGS[args.unet_config]} "
          f"requests={len(queue)} clients={args.clients} "
          f"cuts={sorted(set(SERVE_CUTS))} T={args.T} "
          f"max_wave={args.max_wave} batch={args.batch} "
          f"wall_s={time.perf_counter() - t0} {log.since(snap)}")

    outs = {}
    for tag in ("cold", "warm"):
        snap = log.snapshot()
        outs[tag], rep = rt.process(queue)
        serve_report(f"serve/{tag}", rep, log.since(snap))
    assert rep["cache_hits"] >= 1, rep
    assert rep["engine_traces"] == 0, rep
    want = (args.batch, args.image_size, args.image_size, 3)
    for tag, o in outs.items():
        assert all(x.shape == want for x in o), (tag, [x.shape for x in o])
        assert all(bool(jnp.isfinite(x).all()) for x in o), tag

    traces = rt.traces
    for name, stage in (("server", server), ("client", client)):
        assert KERNEL_MARKER in stage.lowered_text(), \
            f"no {KERNEL_MARKER} in the lowered {name} stage"
    assert rt.traces == traces, "lowering the stages again re-traced them"
    print(f"serve/kernel: {KERNEL_MARKER} in the server and client stages")

    worst = kernel_vs_oracle(jax, jnp, sched, (args.max_wave, args.batch,
                                               args.image_size,
                                               args.image_size, 3))
    print(f"serve/pallas_vs_jnp: worst |diff| / ({KERNEL_K_ULP:g} ulp bound)"
          f" = {worst}")
    assert np.isfinite(worst) and worst <= 1.0, worst
    print(f"serve/peak_bytes_in_use: {peak_bytes(jax)}")


def train_setup(args_list):
    import jax
    from repro.launch import collab_train
    args = collab_train.parse_args(args_list)
    key = jax.random.PRNGKey(args.seed)
    init_one, apply_fn = collab_train.build_model(args, key)
    data = collab_train.make_data(args, key)
    return args, key, init_one, apply_fn, data


def train_report(tag, rep, extra=""):
    print(f"{tag}: cohort={rep['cohort']} tier={rep['tier']} "
          f"wall_s={rep['wall_s']} engine_traces={rep['engine_traces']} "
          f"client_loss={rep['client_loss']} "
          f"server_loss={rep['server_loss']} {extra}")
    assert math.isfinite(rep["client_loss"]) and \
        math.isfinite(rep["server_loss"]), rep


def train_phase(jax, log):
    from repro.launch import collab_train
    from repro.train import TrainRuntime

    args, key, init_one, apply_fn, data = train_setup(TRAIN_ARGV)
    full = collab_train.fresh_runtime(args, key, init_one, apply_fn, data)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "train.msgpack")
        for r in range(args.rounds):
            snap = log.snapshot()
            train_report(f"train/round{r}", full.run_round(), log.since(snap))
            if r == 0:
                t0 = time.perf_counter()
                full.save(path)
                save_s = time.perf_counter() - t0
        assert full.traces == 1, full.traces         # one tier, one program
        snap, t0 = log.snapshot(), time.perf_counter()
        resumed = TrainRuntime.restore(
            collab_train.make_train_config(args), init_one, apply_fn, path,
            mesh=collab_train.make_mesh(args))
        restore_s = time.perf_counter() - t0
    for uid, (x, y) in enumerate(data):
        resumed.attach_data(uid, x, y)
    for r in range(1, args.rounds):
        snap = log.snapshot()
        train_report(f"train/resumed/round{r}", resumed.run_round(),
                     log.since(snap))
    collab_train.assert_runtimes_bitwise(full, resumed)
    print(f"train/resume: checkpoint after round 0 saved in {save_s} s, "
          f"restored in {restore_s} s; the resumed run ends bitwise equal "
          "to the uninterrupted one")
    print(f"train/peak_bytes_in_use: {peak_bytes(jax)}")


def rel_l2(jax, a, b) -> float:
    """||a - b|| / ||b|| over a whole pytree, on the host in float64."""
    import numpy as np
    la, lb = ([np.asarray(x, np.float64) for x in jax.tree.leaves(t)]
              for t in (a, b))
    num = sum(float(np.sum((x - y) ** 2)) for x, y in zip(la, lb))
    return (num / sum(float(np.sum(y ** 2)) for y in lb)) ** 0.5


def four_chip_phase(jax, log):
    from repro.launch import collab_train
    from repro.sharding.specs import CLIENT_AXIS, make_mesh

    args, key, init_one, apply_fn, data = train_setup(TRAIN_ARGV)
    runtimes = {
        "4dev": collab_train.fresh_runtime(args, key, init_one, apply_fn,
                                           data),
        "1dev": collab_train.fresh_runtime(
            args, key, init_one, apply_fn, data,
            mesh=make_mesh((1,), (CLIENT_AXIS,))),
    }
    assert runtimes["4dev"].mesh.devices.size == 4, runtimes["4dev"].mesh
    engine = runtimes["4dev"]._engine = Recorder(runtimes["4dev"]._engine)

    def both_rounds():
        """One round of both runtimes at once, one thread each (so round 0
        compiles both programs at once), at FOUR_CHIP_PRECISION: the
        precision is thread-local state, set in each thread."""
        reps = {}

        def one(name):
            with jax.default_matmul_precision(FOUR_CHIP_PRECISION):
                reps[name] = runtimes[name].run_round()
        threads = [threading.Thread(target=one, args=(n,)) for n in runtimes]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert set(reps) == set(runtimes), "a round thread failed"
        return reps

    print(f"four_chips/precision: float32 dots and convolutions at "
          f"{FOUR_CHIP_PRECISION!r}")
    worst = {"loss": 0.0, "params": 0.0}
    for r in range(args.rounds):
        snap = log.snapshot()
        reps = both_rounds()
        if r == 0:
            print(f"four_chips/compile: {log.since(snap)}")
        for n, rep in reps.items():
            train_report(f"four_chips/{n}/round{r}", rep)
        for k in ("client_loss", "server_loss"):
            gap = abs(reps["4dev"][k] - reps["1dev"][k]) / abs(reps["1dev"][k])
            worst["loss"] = max(worst["loss"], gap)
        four, one = runtimes["4dev"], runtimes["1dev"]
        gaps = {"server": rel_l2(jax, four.server_params, one.server_params)}
        for u in one.registry.uids():
            gaps[f"client{u}"] = rel_l2(jax, four.registry.get(u).params,
                                        one.registry.get(u).params)
        worst["params"] = max(worst["params"], *gaps.values())
        print(f"four_chips/round{r}/params: ||p4 - p1|| / ||p1|| = {gaps}")

    cp = jax.tree.leaves(engine.args[0])[0]
    devices = {s.device.id for s in cp.addressable_shards}
    print(f"four_chips/placement: stacked client params {cp.shape} as "
          f"{cp.sharding}, one shard of {cp.addressable_shards[0].data.shape}"
          f" on each of devices {sorted(devices)}")
    assert len(devices) == 4 and \
        cp.addressable_shards[0].data.shape[0] == 1, cp.sharding
    print(f"four_chips/worst: loss gap {worst['loss']} "
          f"(rtol {FOUR_CHIP_LOSS_RTOL}), params {worst['params']} "
          f"(rtol {FOUR_CHIP_PARAM_RTOL})")
    assert worst["loss"] <= FOUR_CHIP_LOSS_RTOL, worst
    assert worst["params"] <= FOUR_CHIP_PARAM_RTOL, worst
    print(f"four_chips/peak_bytes_in_use (device 0): {peak_bytes(jax)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip client-mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.common import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog(jax)
    t0 = time.perf_counter()
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"jax {jax.__version__}, compile cache {cache_dir}")
    phases = ((four_chip_phase,) if args.four_chips
              else (serve_phase, train_phase))
    for phase in phases:
        snap, ts = log.snapshot(), time.perf_counter()
        phase(jax, log)
        print(f"{phase.__name__}: wall_s={time.perf_counter() - ts} "
              f"{log.since(snap)}")
    print(f"chip_smoke: wall_s={time.perf_counter() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
