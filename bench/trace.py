"""From a profiler trace to device numbers: busy time (the union of the
intervals in which an operation ran on a device), the traced window, time
per kernel, the operations that took most time, and the longest idle gaps
named by the benchmark's own host annotation that covers them.

``capture`` records a trace with JAX's profiler and marks the traced span
with the host annotation ``WINDOW``; the loops mark their calls into the
program with ``annotate``.  It keeps the host side to those annotations
(no Python tracer, host level 1), so that the host loop is not slowed.
It takes the trace from the profiler in memory: nothing is written to
disk, and the profiler skips its conversion to a trace viewer's JSON.
``events`` reads the planes of a ``jax.profiler.ProfileData``; ``reduce``
is pure arithmetic on event lists.
"""
from __future__ import annotations

import contextlib
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench/window"
PREFIX = "bench/"
# per device plane, the line whose events are the operations it ran
OPS_LINE = "XLA Ops"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@contextlib.contextmanager
def capture(sink: dict):
    """Trace the body; on exit ``sink["events"]`` holds what ``events``
    read from the trace."""
    import jax
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.devices()       # the backend is up before the session starts
    session = _profiler.ProfilerSession(opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield sink
    finally:
        sink["events"] = events(session.stop_and_get_profile_data())


def annotate(name: str):
    """A host span in the trace (a no-op outside a capture)."""
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)


def events(data) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(operations per device plane, the benchmark's host annotations) of
    a ``jax.profiler.ProfileData``."""
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CUSTOM"):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PREFIX))
    return devices, host


_ATTRS = re.compile(r'\b(kind|calls|body|custom_call_target)='
                    r'"?([%\w.\-]+)')
NAME_MAX = 120


def short_name(op: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction, some
    tens of kilobytes for a loop: keep its name, opcode and what it runs
    (``%fusion.3 fusion kOutput %fused_computation.3``), at most
    ``NAME_MAX`` characters."""
    name, eq, rest = op.partition(" = ")
    if not eq:
        return op[:NAME_MAX]
    i = 0
    if rest.startswith("("):            # a tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    j = rest.find(" ", i)
    opcode = rest[j + 1:].split("(", 1)[0] if j >= 0 else ""
    parts = [name, opcode] + [v for _, v in _ATTRS.findall(rest)]
    return " ".join(p for p in parts if p)[:NAME_MAX]


def union(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(devices: Dict[str, List[Event]], host: List[Event],
           top: int = 10) -> Optional[dict]:
    """Seconds, over the ``WINDOW`` annotation as far as every device
    recorded operations: ``traced_s`` (the whole annotation),
    ``window_s`` (the part read: up to the end of the last operation that
    every device recorded -- the profiler keeps a bounded number of device
    events, and a long window outlasts them), ``busy_s`` (the union of
    operations inside it, averaged over the devices), ``op_s`` and
    ``op_n`` (each operation name's summed time and count of the runs that
    lie wholly inside it, per device), ``device_ops`` (the ``top`` of
    those, named by ``short_name``) and ``idle_gaps`` (the ``top``
    longest gaps between operations, each named by the innermost
    benchmark annotation that covers its middle).  None when nothing ran
    on a device inside the window."""
    win = [(a, b) for n, a, b in host if n == WINDOW]
    if not devices or not win:
        return None
    lo, end = win[0]
    hi = min([end] + [max(b for _, _, b in ops) for ops in devices.values()])
    if hi <= lo:
        return None
    n_dev = len(devices)
    busy = 0.0
    per_op: Dict[str, float] = defaultdict(float)
    per_n: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for ops in devices.values():
        merged = union(((a, b) for _, a, b in ops), lo, hi)
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, a, b in ops:
            if lo <= a and b <= hi:
                per_op[name] += b - a
                per_n[name] += 1
    spans = [(n[len(PREFIX):], a, b) for n, a, b in host if n != WINDOW]

    def cause(a, b):
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        return min(cover)[1] if cover else "no annotation"

    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "traced_s": (end - lo) * ns,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n_dev,
        "op_s": {n: v * ns / n_dev for n, v in per_op.items()},
        "op_n": {n: v / n_dev for n, v in per_n.items()},
        "device_ops": [[short_name(n), v * ns / n_dev] for n, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[cause(a, b), (b - a) * ns] for a, b in gaps[:top]],
    }
