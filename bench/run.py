#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process touches JAX and starts no children.  It finds the cell in
``BENCHMARK.json``, its configuration, traffic, loop and per-layer metric
readers under ``bench/`` by name, and exits non-zero with no result when
any is missing or when JAX finds no TPU or fewer chips than the cell asks
for (``JAX_PLATFORMS=cpu`` included).

A run sets up (weights, runtime, every program its traffic uses, warm-up),
measures for ``--seconds``, checks what the timed path produced against the
plain reference, and prints, last on standard error, each compared number
beside its limit, and last on standard output one JSON line: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; the compared numbers come last, under ``checks``.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root
of the checkout (``BENCH_COMPILE_CACHE_DIR`` names another directory), so
only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, flops, trace  # noqa: E402


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


class Context:
    """What a loop gets: the cell's files, the run's arguments, and the
    hooks that mark the end of set-up, wrap the window in a trace and read
    the device's peak memory."""

    def __init__(self, jax, cell, cfg, traffic, args, log):
        self.jax, self.cell, self.cfg, self.traffic = jax, cell, cfg, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.log = log
        self.n_chips = int(cell["chips"])
        self.use_pallas, self.interpret = None, False   # the program's pick
        self.fault = None
        self.setup_s = None
        self.setup_compile = None
        self.profile = {}       # the trace's events, once it is stopped
        self.say = say

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START
        self.setup_compile = self.log.snapshot()

    def traced(self):
        return trace.capture(self.profile) if self.trace else \
            contextlib.nullcontext()

    def memory(self):
        return common.device_info(self.jax, self.n_chips)["memory_peak_bytes"]


def load_reader(name: str):
    path = common.metric_file(name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(workload: str):
    """(manifest, cell, configuration, traffic, loop module, e2e and
    per-layer metric entries), or SystemExit when a piece is missing."""
    man = common.manifest()
    cell = common.find(man["workloads"], workload, "workload")
    cfg = common.load_json(common.config_file(cell["config"]))
    traffic = common.load_json(common.traffic_file(cell["traffic"]))
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")
    e2e = common.cell_metrics(man, workload, "end_to_end")
    per_layer = common.cell_metrics(man, workload, "per_layer")
    for m in per_layer:
        if not common.metric_file(m["name"]).exists():
            raise SystemExit(f"bench: no reader bench/metrics/{m['name']}.py")
    return man, cell, cfg, traffic, loop, e2e, per_layer


def record_for(ctx, out, tr) -> dict:
    u = ctx.cfg["unet"]
    rec = dict(out["record"])
    rec.update({"cfg": ctx.cfg, "traffic": ctx.traffic, "cell": ctx.cell,
                "n_chips": ctx.n_chips, "setup": ctx.setup_compile,
                "flops_per_image": flops.unet_forward_flops(u),
                "peaks": common.peaks_for(
                    ctx.jax.devices()[0].device_kind),
                "trace": tr})
    return rec


def execute(ctx, loop, e2e, per_layer) -> dict:
    """Run the loop and build the result line (a dict)."""
    log0 = ctx.log.snapshot()
    out = loop.run(ctx)
    tr = None
    if ctx.trace:
        t0 = time.perf_counter()
        tr = trace.reduce(*ctx.profile["events"])
        ctx.profile.clear()
        if tr is not None:
            say(f"bench/trace: traced_s={tr['traced_s']} read_s="
                f"{tr['window_s']} busy_s={tr['busy_s']} reading_s="
                f"{time.perf_counter() - t0}")
        for name, sec in (tr or {}).get("op_s", {}).items():
            if "ddpm_step" in name:     # the Pallas kernel, by its HLO name
                say(f"bench/trace: {tr['op_n'][name]} runs, {sec} s: "
                    f"{name[:300]}")
    setup = common.CompileLog.delta(log0, ctx.setup_compile)
    say(f"bench/setup: setup_s={ctx.setup_s} {setup}")
    devs = ctx.jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "device_kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": out["memory"]}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if ctx.trace:
        rec = record_for(ctx, out, tr)
        for m in per_layer:
            v = load_reader(m["name"])(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            device["busy_s"], device["window_s"] = tr["busy_s"], \
                tr["window_s"]
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    else:
        values = dict(out["e2e"], setup_s=ctx.setup_s)
        for m in e2e:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim}
              for k, (v, lim) in out["checks"].items() if lim is not None}
    result["correct"] = all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    for k, (v, lim) in out["checks"].items():
        say(f"check {k} = {v} (limit {lim})")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _, cell, cfg, traffic, loop, e2e, per_layer = resolve(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        say(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devs)} x {devs[0].platform}")
        return 1
    cache = os.environ.get("BENCH_COMPILE_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log = common.CompileLog(jax)
    say(f"bench: {args.workload} on {len(devs)} x {devs[0].device_kind}, "
        f"jax {jax.__version__}, compile cache {cache}")
    ctx = Context(jax, cell, cfg, traffic, args, log)
    result = execute(ctx, loop, e2e, per_layer)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
