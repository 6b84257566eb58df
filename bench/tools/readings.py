#!/usr/bin/env python3
"""Read the control's numbers at a cell's own size, on the chip, for
setting the cell's limits (the program's own come from its runs): on each
``--seeds`` seed, the plain reference computed in bfloat16 put in the
program's place, compared with the reference in float32 at highest
precision, on requests of the shapes the check compares (at each cut one
wave and one more request of each client).  One process for all seeds.

    python3 bench/tools/readings.py --workload <name> --seeds 1,2,3 --control
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402


def control_serve(cfg, traffic, seed):
    from bench import gen
    from bench.local import LocalContext
    from bench.loops import serve
    ctx = LocalContext(cfg, traffic, seed, 0.0)
    stream = gen.ServeStream(traffic, cfg["unet"]["n_classes"], seed)
    cuts = traffic["client_cuts"]
    specs, rid = [], 10_000
    for cut in sorted(set(cuts)):
        clients = [c for c, k in enumerate(cuts) if k == cut]
        for i in range(int(traffic["max_wave"]) + len(clients)):
            client, _, y = stream.take(cut)
            if i >= int(traffic["max_wave"]):
                client = clients[i - int(traffic["max_wave"])]
            specs.append((client, cut, y, rid))
            rid += 1
    ref = serve.reference_outputs(ctx, specs, "float32", "highest")
    low = serve.reference_outputs(ctx, specs, "bfloat16", "default")
    return {k: v for k, (v, _) in serve.compare(low, ref, {}).items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true", required=True)
    args = ap.parse_args()
    import jax
    cache = str(ROOT / ".jax_cache")
    import os
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("BENCH_COMPILE_CACHE_DIR") or cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    man = common.manifest()
    cell = common.find(man["workloads"], args.workload, "workload")
    cfg = common.load_json(common.config_file(cell["config"]))
    traffic = common.load_json(common.traffic_file(cell["traffic"]))
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        print(json.dumps({"seed": seed, "side": "control",
                          "readings": control_serve(cfg, traffic, seed)}),
              flush=True)


if __name__ == "__main__":
    main()
