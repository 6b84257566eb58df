"""Telemetry subsystem tests (repro.obs) + the report-schema
conformance gate.

Covers the obs contract's mechanical pieces (obs/__init__.py):

  * registry semantics — counters are monotone totals diffed per frame,
    gauges are absolute reads (optionally callback-backed), histogram
    windows reproduce the pre-obs float64 percentile math exactly;
  * kind discipline — every report key carries ONE delta-or-gauge
    classification; re-declaring a key with the other kind raises;
  * RecompileGuard — the shared jit trace counter counts COMPILES, not
    calls (new signature => +1, cache hit => +0);
  * tracing — fake-clock span math, parent nesting, retire-frame
    attribution (a span ended after frame N closes lands in frame N+1),
    and the structurally-inert NullTracer singleton;
  * sinks — JSONL records round-trip line by line with the pinned
    schema version; Chrome trace events are complete "X" slices in µs
    grouped on their root span's track, open spans excluded;
  * CONFORMANCE (the "idle ticks must not change the report shape"
    invariant, now mechanical): every ``_empty_report`` key of BOTH
    runtimes is classified in the registry, the key set matches the
    declared schema exactly (drift in either direction fails), and an
    idle serve frame reports the same key set as ``_empty_report``.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (DELTA, GAUGE, OBS_SCHEMA_VERSION, MetricsRegistry,
                       NULL_TRACER, ObsConfig, RecompileGuard, Telemetry,
                       Tracer, chrome_trace_events)
from repro.obs.export import JsonlSink
from repro.obs.metrics import Histogram, KINDS


class FakeClock:
    """Deterministic injectable clock: each read advances by ``dt``."""

    def __init__(self, t0: float = 100.0, dt: float = 1.0):
        self.t = t0
        self.dt = dt

    def __call__(self) -> float:
        t, self.t = self.t, self.t + self.dt
        return t


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_deltas_against_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(4)
    snap = reg.snapshot()
    c.inc(2)
    assert c.value == 7                      # lifetime total is monotone
    assert reg.delta("hits", snap) == 2      # the frame reports movement
    assert reg.deltas(snap) == {"hits": 2}
    # counters born after the snapshot diff against an implicit zero
    reg.counter("late").inc(3)
    assert reg.delta("late", snap) == 3


def test_gauge_reads_absolute_state_and_callbacks():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(9)
    assert reg.read_gauge("depth") == 9
    state = {"n": 2}
    reg.gauge("live", fn=lambda: state["n"])
    state["n"] = 5
    assert reg.read_gauge("live") == 5       # always the current state
    vals = reg.values()
    assert vals["depth"] == 9 and vals["live"] == 5


def test_histogram_window_matches_pre_obs_percentile_math():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in (0.5, 0.1):
        h.observe(v)
    snap = reg.snapshot()
    frame = [0.3, 0.9, 0.2, 0.7]
    for v in frame:
        h.observe(v)
    win = reg.window("lat", snap)
    assert win.dtype == np.float64
    np.testing.assert_array_equal(win, np.asarray(frame, np.float64))
    # exact float64 np.percentile — the arithmetic the reports used
    assert Histogram.percentile(win, 95) == float(np.percentile(
        np.asarray(frame, np.float64), 95))
    assert Histogram.percentile(np.asarray([], np.float64), 95) == 0.0


def test_kind_discipline():
    reg = MetricsRegistry()
    reg.counter("c")
    reg.gauge("g")
    reg.histogram("h")
    assert reg.kind_of("c") == DELTA
    assert reg.kind_of("g") == GAUGE
    assert reg.kind_of("h") == DELTA
    reg.declare("derived_rate", DELTA)
    reg.declare("derived_rate", DELTA)               # idempotent
    with pytest.raises(ValueError):
        reg.declare("derived_rate", GAUGE)           # schema fork
    with pytest.raises(ValueError):
        reg.declare("x", "rate")                     # unknown kind
    # explicit declaration wins over the instrument default
    reg.declare("pending", GAUGE)
    reg.counter("pending")
    assert reg.kind_of("pending") == GAUGE


def test_recompile_guard_counts_traces_not_calls():
    reg = MetricsRegistry()
    guard = RecompileGuard(reg.counter("engine_traces"))
    fn = jax.jit(guard.wrap(lambda x: x * 2.0))
    a = jnp.ones((3,))
    fn(a)
    assert guard.count == 1
    fn(a + 1)
    fn(a + 2)
    assert guard.count == 1                  # same signature: no retrace
    fn(jnp.ones((5,)))                       # new shape: one more trace
    assert guard.count == 2
    snap = reg.snapshot()
    fn(a)
    assert reg.delta("engine_traces", snap) == 0   # steady-state frame


# ---------------------------------------------------------------------------
# Tracing
# ---------------------------------------------------------------------------


def test_span_math_and_nesting_with_fake_clock():
    clock = FakeClock(t0=10.0, dt=1.0)
    tr = Tracer(clock)
    with tr.span("wave", bucket="b0") as w:          # t0 = 10
        with tr.span("plan") as p:                   # t0 = 11, t1 = 12
            pass
    done = tr.drain()
    assert [s.name for s in done] == ["plan", "wave"]
    p, w = done
    assert p.parent == w.sid                 # nesting from the stack
    assert w.parent is None
    assert (p.t0, p.t1, p.duration_s) == (11.0, 12.0, 1.0)
    assert (w.t0, w.t1) == (10.0, 13.0)
    assert w.attrs == {"bucket": "b0"}
    assert tr.drain() == []                  # drain empties the buffer


def test_async_span_retire_frame_attribution():
    tr = Tracer(FakeClock())
    s = tr.start("wave", wave=0)
    assert s.t1 < 0 and s.frame == -1        # open
    tr.frame += 1                            # a report frame closed
    tr.end(s, device_wait_s=0.25)
    assert s.frame == 1                      # attributed to retire frame
    assert s.attrs["device_wait_s"] == 0.25
    tr.end(None)                             # disabled-path convenience


def test_explicit_parent_beats_stack():
    tr = Tracer(FakeClock())
    w = tr.start("wave")
    with tr.span("plan"):
        with tr.span("cache_probe", parent=w) as c:
            pass
    tr.end(w)
    assert c.parent == w.sid


def test_null_tracer_is_structurally_inert():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.start("wave") is None
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")   # shared const
    with NULL_TRACER.span("wave") as s:
        assert s is None
    NULL_TRACER.end(None)
    assert NULL_TRACER.drain() == []
    assert NULL_TRACER.frame == 0


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------


def test_jsonl_sink_roundtrips_line_by_line(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    clock = FakeClock()
    sink = JsonlSink(path, clock)
    sink.meta(runtime="serve", T=16)
    sink.metrics(0, {"waves": np.int64(3), "cache_bytes": 1024})
    tr = Tracer(FakeClock())
    with tr.span("wave", bucket="cut4"):
        pass
    sink.spans(tr.drain())
    # flushed per write: readable BEFORE close (the tail -f contract)
    recs = [json.loads(l) for l in open(path)]
    sink.close()
    assert [r["kind"] for r in recs] == ["meta", "metrics", "span"]
    assert all(r["schema"] == OBS_SCHEMA_VERSION for r in recs)
    assert recs[1]["frame"] == 0
    assert recs[1]["metrics"] == {"waves": 3, "cache_bytes": 1024}
    assert recs[2]["name"] == "wave"
    assert recs[2]["attrs"] == {"bucket": "cut4"}


def test_chrome_trace_events_shape():
    tr = Tracer(FakeClock(t0=1.0, dt=0.5))
    w = tr.start("wave")                     # t0 = 1.0
    with tr.span("plan", parent=w):          # t0 = 1.5, t1 = 2.0
        pass
    tr.end(w)                                # t1 = 2.5
    open_span = tr.start("wave")             # never ended
    evs = chrome_trace_events(tr.drain() + [open_span])
    assert [e["name"] for e in evs] == ["plan", "wave"]
    assert all(e["ph"] == "X" for e in evs)
    plan, wave = evs
    assert plan["ts"] == 1.5e6 and plan["dur"] == 0.5e6      # µs
    assert plan["tid"] == wave["tid"] == w.sid   # one lane per wave tree
    assert plan["args"]["parent"] == w.sid


def test_profiler_hook_degrades_without_raising(tmp_path):
    from repro.obs import ProfilerHook

    class Boom:
        def start_trace(self, outdir):
            raise RuntimeError("no backend")

        def stop_trace(self):                        # pragma: no cover
            raise RuntimeError("never started")

    hook = ProfilerHook(2, str(tmp_path), profiler=Boom())
    hook.step()                              # must not raise
    assert hook.failed is not None and not hook.active
    hook.step()                              # stays a no-op
    hook.stop()


# ---------------------------------------------------------------------------
# Telemetry bundle
# ---------------------------------------------------------------------------


def test_telemetry_disabled_is_inert():
    obs = Telemetry()
    assert obs.enabled is False
    assert obs.tracer is NULL_TRACER
    obs.meta(runtime="serve")
    obs.step()
    obs.frame_closed(obs.registry.snapshot())
    obs.close()
    assert obs.spans() == []
    assert obs.tracer.frame == 0             # never advanced


def test_obs_config_activation():
    assert ObsConfig().active is False
    assert ObsConfig(enabled=True).active is True
    assert ObsConfig(jsonl_path="/tmp/x.jsonl").active is True
    assert ObsConfig(trace_path="/tmp/x.json").active is True
    assert ObsConfig(profile_waves=2).active is True


def test_telemetry_frames_and_sinks(tmp_path):
    path = str(tmp_path / "run.jsonl")
    obs = Telemetry(ObsConfig(jsonl_path=path), clock=FakeClock())
    obs.meta(runtime="test")
    c = obs.registry.counter("waves")
    snap = obs.registry.snapshot()
    c.inc(2)
    s = obs.tracer.start("wave", wave=0)
    obs.tracer.end(s)
    obs.frame_closed(snap, extra={"wall_s": 0.5})
    snap2 = obs.registry.snapshot()
    c.inc(1)
    obs.frame_closed(snap2)
    obs.close()
    recs = [json.loads(l) for l in open(path)]
    kinds = [r["kind"] for r in recs]
    assert kinds == ["meta", "metrics", "span", "metrics"]
    m0, m1 = recs[1], recs[3]
    assert (m0["frame"], m1["frame"]) == (0, 1)
    assert m0["metrics"]["waves"] == 2       # frame delta, not total
    assert m0["metrics"]["wall_s"] == 0.5
    assert m1["metrics"]["waves"] == 1
    assert recs[2]["frame"] == 0             # span closed inside frame 0
    assert len(obs.spans()) == 1


# ---------------------------------------------------------------------------
# Report-schema conformance (both runtimes)
# ---------------------------------------------------------------------------


def _serve_runtime():
    from repro.core.schedules import DiffusionSchedule
    from repro.serve import ServeConfig, ServeRuntime
    sp = {"a": jnp.float32(0.2), "b": jnp.float32(0.0)}
    cp = {"a": jnp.linspace(0.1, 0.5, 3), "b": jnp.zeros((3,))}
    return ServeRuntime(
        ServeConfig(T=16, image_shape=(4, 4, 3), max_wave=4),
        sp, cp, lambda p, x, t, y: x * p["a"] + p["b"],
        DiffusionSchedule.linear(16), jax.random.PRNGKey(0))


def _train_runtime():
    from repro.train import TrainConfig, TrainRuntime

    def init_one(key):
        return {"a": jax.random.uniform(key, (), minval=0.1, maxval=0.6),
                "b": jnp.float32(0.0)}

    return TrainRuntime(
        TrainConfig(T=60, t_cut=20, image_shape=(6, 6, 3), n_classes=4,
                    batch_size=4, batches_per_round=2),
        init_one, lambda p, x, t, y: x * p["a"] + p["b"],
        jax.random.PRNGKey(0))


def test_serve_report_schema_conformance():
    from repro.serve.runtime import _SERVE_REPORT_SCHEMA
    rt = _serve_runtime()
    report_keys = set(rt._empty_report())
    # every report key classified; every classified key still reported
    assert report_keys == set(_SERVE_REPORT_SCHEMA), (
        "serve report keys drifted from _SERVE_REPORT_SCHEMA")
    for k in report_keys:
        assert rt.registry.kind_of(k) in KINDS, f"unclassified key {k!r}"
    # the audited PR-6/PR-7 semantics, now pinned as registry kinds
    assert rt.registry.kind_of("cache_entries") == GAUGE
    assert rt.registry.kind_of("cache_bytes") == GAUGE
    assert rt.registry.kind_of("cache_hits") == DELTA
    assert rt.registry.kind_of("engine_traces") == DELTA


def test_serve_idle_frame_matches_empty_report():
    rt = _serve_runtime()
    rt.start_report()
    rep = rt.finish_report()
    empty = rt._empty_report()
    assert set(rep) == set(empty), "idle tick changed the report shape"
    # an idle frame's deltas are all zero (wall_s excepted: real elapsed
    # time is a legitimate per-frame delta even with nothing retired);
    # gauges report resident state
    for k, kind in rt.registry.kinds().items():
        if (k in rep and k != "wall_s" and kind == DELTA
                and isinstance(rep[k], (int, float))):
            assert rep[k] == 0, f"idle frame delta {k!r} = {rep[k]!r}"
    assert rep["cache_entries"] == 0 and rep["cache_bytes"] == 0


def test_train_report_schema_conformance():
    from repro.train.runtime import _TRAIN_REPORT_SCHEMA
    rt = _train_runtime()
    report_keys = set(rt._empty_report())
    assert report_keys == set(_TRAIN_REPORT_SCHEMA), (
        "train report keys drifted from _TRAIN_REPORT_SCHEMA")
    for k in report_keys:
        assert rt.metrics.kind_of(k) in KINDS, f"unclassified key {k!r}"
    # round/seen/pending/dp_* are absolute state; losses/walls are frames
    assert rt.metrics.kind_of("round") == GAUGE
    assert rt.metrics.kind_of("pending_payloads") == GAUGE
    assert rt.metrics.kind_of("dp_epsilon") == GAUGE
    assert rt.metrics.kind_of("client_loss") == DELTA
    assert rt.metrics.kind_of("barrier_stall_s") == DELTA


def test_runtimes_default_to_inert_obs():
    for rt in (_serve_runtime(), _train_runtime()):
        assert rt.obs.enabled is False
        assert rt.obs.tracer is NULL_TRACER


# ---------------------------------------------------------------------------
# Stable program names and spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_recompile_guard_keeps_the_wrapped_name():
    reg = MetricsRegistry()
    guard = RecompileGuard(reg.counter("engine_traces"))

    def server_stage(x):
        return x + 1.0

    fn = jax.jit(guard.wrap(server_stage))
    text = fn.lower(jnp.ones((3,))).as_text()
    assert text.startswith("module @jit_server_stage"), text[:80]
    assert guard.count == 1
    fn(jnp.ones((3,)))
    fn(jnp.ones((3,)) + 1)
    assert guard.count == 1                  # still one trace a signature
    fn(jnp.ones((4,)))
    assert guard.count == 2


def test_runtime_programs_keep_their_names():
    serve, train = _serve_runtime(), _train_runtime()
    assert serve._server_stage.__name__ == "server_stage"
    assert serve._client_stage.__name__ == "client_stage"
    assert train._engine.__name__ == "round_fn"


def _host_event_names(body):
    """Names of the host events a profiler session records around
    ``body()`` (host tracer level 1, no Python tracer)."""
    from jax._src.lib import _profiler
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.devices()
    session = _profiler.ProfilerSession(opts)
    try:
        body()
    finally:
        data = session.stop_and_get_profile_data()
    return [e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_tracer_spans_are_profiler_annotations():
    tr = Tracer(FakeClock(), runtime="train")

    def body():
        w = tr.start("round")
        with tr.span("fedavg", parent=w):
            with tr.span("checkpoint"):
                pass
        tr.end(w)

    names = _host_event_names(body)
    assert {"train/fedavg", "train/checkpoint"} <= set(names)
    # asynchronous spans describe no host activity: not annotated
    assert "train/round" not in names


@pytest.mark.parametrize("enabled", [True, False])
def test_serve_annotations_only_with_obs_on(enabled):
    from repro.core.sample_plan import SampleRequest
    from repro.core.schedules import DiffusionSchedule
    from repro.serve import ServeConfig, ServeRuntime
    sp = {"a": jnp.float32(0.2), "b": jnp.float32(0.0)}
    cp = {"a": jnp.linspace(0.1, 0.5, 3), "b": jnp.zeros((3,))}
    rt = ServeRuntime(
        ServeConfig(T=16, image_shape=(4, 4, 3), max_wave=4),
        sp, cp, lambda p, x, t, y: x * p["a"] + p["b"],
        DiffusionSchedule.linear(16), jax.random.PRNGKey(0),
        obs=ObsConfig(enabled=enabled))
    y = np.eye(4, dtype=np.float32)[:2]
    queue = [SampleRequest(client=c, t_cut=4, y=y) for c in range(3)]
    names = [n for n in _host_event_names(lambda: rt.process(queue))
             if n.startswith("serve/")]
    if enabled:
        assert {"serve/plan", "serve/cache_probe", "serve/enqueue_server",
                "serve/cache_fill", "serve/enqueue_client", "serve/account",
                "serve/retire"} <= set(names), names
        assert "serve/wave" not in names
    else:
        assert names == []
