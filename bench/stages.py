"""Per-stage device time and the host's part in the gaps between waves,
from a profiler trace of the serve runtime.

The serve runtime's engine stages lower as ``jit_server_stage`` and
``jit_client_stage``; each execution is one event on a device plane's
``XLA Modules`` line.  Its synchronous ``obs`` spans are profiler
annotations on the host planes (``serve/plan``, ``serve/account``, ...),
on the same clock.  Its registry histograms ``server_scan_steps`` and
``client_scan_steps`` hold the padded step count of every wave dispatched,
in dispatch order, which is the order the device runs the stages in.

``events`` reads those from a ``jax.profiler.ProfileData``; the rest is
arithmetic on event lists (``bench.trace.Event``: name, start and end in
nanoseconds) over the part of the traced window that ``bench.trace.reduce``
reads, ``(lo, hi)`` from ``read_part``.  Each function returns None where
the trace holds nothing to read: a program that names no stage, a CPU
trace with no module line, a run with tracing off.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIXES = ("serve/", "train/")
SERVER, CLIENT = "jit_server_stage", "jit_client_stage"

_ID_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """A module event's program name, without the ``(id)`` the profiler
    appends: ``jit_server_stage(42)`` -> ``jit_server_stage``."""
    return _ID_SUFFIX.sub("", name)


def events(data) -> dict:
    """``modules``: per device plane, the programs it ran (``module_name``
    of each ``XLA Modules`` event); ``spans``: the host events under the
    program's prefixes."""
    modules: Dict[str, List[trace.Event]] = {}
    spans: List[trace.Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            mods = [(module_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for line in plane.lines if line.name == MODULES_LINE
                    for e in line.events]
            if mods:
                modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(PROGRAM_PREFIXES))
    return {"modules": modules, "spans": spans}


def read_part(devices: Dict[str, List[trace.Event]],
              host: List[trace.Event]) -> Optional[Tuple[float, float]]:
    """The part of the ``WINDOW`` annotation that ``trace.reduce`` reads:
    from its start to the end of the last operation every device
    recorded."""
    win = [(a, b) for n, a, b in host if n == trace.WINDOW]
    if not devices or not win:
        return None
    lo, end = win[0]
    hi = min([end] + [max(b for _, _, b in ops) for ops in devices.values()])
    return (lo, hi) if hi > lo else None


def _runs(stages: Optional[dict], name: str) -> Optional[List[list]]:
    """Per device, the runs of program ``name`` in device order, each
    ``[start, end, inside]`` with ``inside`` true when it lies wholly in
    the read part.  None when no device ran it."""
    if not stages or not stages.get("read"):
        return None
    lo, hi = stages["read"]
    per_dev = [[[a, b, lo <= a and b <= hi] for n, a, b in sorted(
        mods, key=lambda e: e[1]) if n == name]
        for mods in stages["modules"].values()]
    per_dev = [r for r in per_dev if r]
    return per_dev or None


def step_ms(stages: Optional[dict], name: str, hist: str) -> Optional[float]:
    """Milliseconds a step of program ``name``: the device time of its runs
    that lie wholly in the read part, over the steps those runs scanned
    (the k-th run is the k-th wave dispatched while tracing, whose steps
    are the k-th entry of histogram ``hist``), averaged over the
    devices."""
    per_dev = _runs(stages, name)
    steps = (stages or {}).get("steps", {}).get(hist)
    if per_dev is None or not steps:
        return None
    out = []
    for runs in per_dev:
        pairs = [(b - a, s) for (a, b, inside), s in zip(runs, steps)
                 if inside]
        n = sum(s for _, s in pairs)
        if n:
            out.append(sum(t for t, _ in pairs) / n * 1e-6)
    return sum(out) / len(out) if out else None


def wave_gaps(stages: Optional[dict]) -> List[Tuple[float, float]]:
    """On the first device, each (end of a client stage, start of the next
    server stage) with both runs wholly in the read part."""
    clients, servers = _runs(stages, CLIENT), _runs(stages, SERVER)
    if clients is None or servers is None:
        return []
    gaps = []
    for a, b, inside in clients[0]:
        nxt = [s for s in servers[0] if s[0] >= b]
        if inside and nxt and nxt[0][2]:
            gaps.append((b, nxt[0][0]))
    return gaps


def dispatch_gap_ms(stages: Optional[dict]) -> Optional[float]:
    """Mean milliseconds on the device timeline from the end of one wave's
    client stage to the start of the next wave's server stage."""
    gaps = wave_gaps(stages)
    return sum(b - a for a, b in gaps) / len(gaps) * 1e-6 if gaps else None


def span_ms(stages: Optional[dict], name: str) -> Optional[float]:
    """Mean milliseconds of the host annotation ``name`` that lie wholly
    in the read part."""
    if not stages or not stages.get("read"):
        return None
    lo, hi = stages["read"]
    d = [b - a for n, a, b in stages["spans"]
         if n == name and lo <= a and b <= hi]
    return sum(d) / len(d) * 1e-6 if d else None


def split_gap(a: float, b: float, spans: Sequence[trace.Event]
              ) -> Dict[str, float]:
    """Nanoseconds of the interval [a, b] under each ``serve/`` span,
    every instant given to the innermost (shortest) one that covers it,
    and to ``none`` where none does."""
    cover = [(e - s, n, s, e) for n, s, e in spans
             if n.startswith("serve/") and s < b and e > a]
    cuts = sorted({a, b} | {min(max(x, a), b) for _, _, s, e in cover
                            for x in (s, e)})
    out: Dict[str, float] = {}
    for x, y in zip(cuts, cuts[1:]):
        mid = 0.5 * (x + y)
        inner = [(d, n) for d, n, s, e in cover if s <= mid <= e]
        key = min(inner)[1].split("/", 1)[1] if inner else "none"
        out[key] = out.get(key, 0.0) + (y - x)
    return out


def gap_report(stages: Optional[dict]) -> List[str]:
    """One line per inter-wave gap: its milliseconds, split by the
    innermost serve span covering each part of it."""
    lines = []
    for a, b in wave_gaps(stages):
        parts = split_gap(a, b, stages["spans"])
        lines.append(f"{(b - a) * 1e-6:.3f} ms: " + " ".join(
            f"{k}={v * 1e-6:.3f}" for k, v in
            sorted(parts.items(), key=lambda kv: -kv[1])))
    return lines
