"""What every loop shares: the manifest and the files found by name, the
compile accounting read from ``jax.monitoring``, the device description and
its peak memory, and the weights made from the seed."""
from __future__ import annotations

import json
import threading
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(MANIFEST)


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def config_file(name: str) -> Path:
    return BENCH_DIR / "configs" / f"{name}.json"


def traffic_file(name: str) -> Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_file(name: str) -> Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


def cell_metrics(man: dict, workload: str, section: str) -> list:
    """The metrics of ``section`` that ``workload`` reports: those without a
    ``workloads`` list, and those whose list names it."""
    return [m for m in man[section]
            if "workloads" not in m or workload in m["workloads"]]


# ---------------------------------------------------------------------------
# Compile accounting (the events chip_smoke.py reads)
# ---------------------------------------------------------------------------

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """``compile_s`` is time in XLA compile or in loading from the
    persistent cache, ``trace_lower_s`` tracing plus lowering to StableHLO;
    ``requests``/``hits`` count persistent-cache lookups."""

    def __init__(self, jax):
        self._lock = threading.Lock()
        self.requests = self.hits = 0
        self.compile_s = self.trace_lower_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        with self._lock:
            if event == CACHE_REQUEST:
                self.requests += 1
            elif event == CACHE_HIT:
                self.hits += 1

    def _duration(self, event, secs, **_):
        with self._lock:
            if event == BACKEND_COMPILE:
                self.compile_s += secs
                self.compiles += 1
            elif event in (TRACE, LOWER):
                self.trace_lower_s += secs

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.compile_s,
                    "trace_lower_s": self.trace_lower_s,
                    "compiles": self.compiles,
                    "cache_requests": self.requests,
                    "cache_hits": self.hits}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


# ---------------------------------------------------------------------------
# Device
# ---------------------------------------------------------------------------

def device_info(jax, n_chips: int) -> dict:
    devs = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "device_kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]
