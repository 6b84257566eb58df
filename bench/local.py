"""Drive a loop in this process without ``run.py``'s look for a chip: the
CPU tests run whole cells at toy sizes through it (with faults planted in
the program), and ``tools/readings.py`` reads the check's numbers on the
chip at a cell's own size."""
from __future__ import annotations

import contextlib
import copy
import time
import types

from bench import common


class LocalContext:
    """The ``run.py`` context's interface, for a configuration and traffic
    given as dicts."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 fault=None, say=None):
        import jax
        self.jax = jax
        self.cfg, self.traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
        self.cell = {"name": "local", "chips": 1}
        self.seed, self.seconds = seed, float(seconds)
        self.trace = False
        self.log = common.CompileLog(jax)
        self.n_chips = 1
        self.use_pallas, self.interpret = None, False   # the program's pick
        self.fault = fault
        self.setup_s = None
        self.say = say or (lambda *a: None)
        self._t0 = time.perf_counter()

    def setup_done(self):
        self.setup_s = time.perf_counter() - self._t0
        self.setup_compile = self.log.snapshot()

    def traced(self):
        return contextlib.nullcontext()

    def memory(self):
        return common.device_info(self.jax, 1)["memory_peak_bytes"]


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float, **kw
             ) -> dict:
    """The loop's output for one run: ``e2e``, ``record``, ``checks`` (each
    (value, limit)), ``attempted``, ``failed``, ``memory``, and ``correct``
    as ``run.py`` decides it."""
    import importlib
    import math
    ctx = LocalContext(cfg, traffic, seed, seconds, **kw)
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")
    out = loop.run(ctx)
    out["correct"] = all(math.isfinite(v) and v <= lim
                         for v, lim in out["checks"].values()
                         if lim is not None)
    out["ctx"] = types.SimpleNamespace(setup_s=ctx.setup_s)
    return out
