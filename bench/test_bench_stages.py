"""Per-stage step time, the gap between waves and the dispatch block, from
synthetic module and span event lists, and from a CPU trace of the serve
runtime, which has no module line."""
import types

import pytest

from bench import stages, trace

MS = 1e6  # ns
SRV, CLI = stages.SERVER, stages.CLIENT


def _stages(read=(0.0, 100 * MS)):
    """Two whole waves (900 and 800 server steps at 0.02 ms, 100 and 200
    client steps at 0.03 ms), then a third cut off by the read part's end;
    other programs between them."""
    mods = [(SRV, 1 * MS, 19 * MS), (CLI, 19 * MS, 22 * MS),
            ("jit_sum", 22 * MS, 23 * MS),
            (SRV, 25 * MS, 41 * MS), (CLI, 41 * MS, 47 * MS),
            (SRV, 50 * MS, 68 * MS), (CLI, 68 * MS, 104 * MS)]
    spans = [("serve/account", 2 * MS, 22.5 * MS),
             ("serve/retire", 22.5 * MS, 23 * MS),
             ("serve/plan", 23 * MS, 24.5 * MS),
             ("serve/cache_probe", 23.5 * MS, 24 * MS),
             ("serve/enqueue_server", 24.5 * MS, 24.8 * MS),
             ("serve/account", 26 * MS, 48 * MS),
             ("serve/account", 51 * MS, 101 * MS)]     # past the read part
    return {"modules": {"/device:TPU:0": mods}, "spans": spans,
            "read": read,
            "steps": {"server_scan_steps": [900, 800, 900, 800],
                      "client_scan_steps": [100, 200, 100, 200]}}


def test_step_ms_over_the_runs_wholly_read():
    s = _stages()
    # 18 ms + 16 ms over 1,700 steps; the third server run (18 ms, 900
    # steps) lies inside too
    assert stages.step_ms(s, SRV, "server_scan_steps") == \
        pytest.approx((18 + 16 + 18) / 2600)
    # the third client run ends after the read part: out, with its steps
    assert stages.step_ms(s, CLI, "client_scan_steps") == \
        pytest.approx((3 + 6) / 300)


def test_step_ms_averages_over_devices():
    s = _stages()
    s["modules"]["/device:TPU:1"] = [(SRV, 0.0, 9 * MS)]
    one = (18 + 16 + 18) / 2600
    assert stages.step_ms(s, SRV, "server_scan_steps") == \
        pytest.approx((one + 9 / 900) / 2)


def test_dispatch_gap_between_client_end_and_next_server_start():
    # 22 -> 25 and 47 -> 50; the third client run is not wholly read
    assert stages.wave_gaps(_stages()) == [(22 * MS, 25 * MS),
                                           (47 * MS, 50 * MS)]
    assert stages.dispatch_gap_ms(_stages()) == pytest.approx(3.0)


def test_dispatch_block_is_the_mean_account_span_read():
    assert stages.span_ms(_stages(), "serve/account") == \
        pytest.approx((20.5 + 22) / 2)
    assert stages.span_ms(_stages(), "serve/nothing") is None


def test_gap_split_by_the_innermost_span():
    s = _stages()
    parts = stages.split_gap(22 * MS, 25 * MS, s["spans"])
    assert parts == pytest.approx({"account": 0.5 * MS, "retire": 0.5 * MS,
                                   "plan": 1.0 * MS, "cache_probe": 0.5 * MS,
                                   "enqueue_server": 0.3 * MS,
                                   "none": 0.2 * MS})
    assert sum(parts.values()) == pytest.approx(3 * MS)
    lines = stages.gap_report(s)
    assert len(lines) == 2 and lines[0].startswith("3.000 ms: plan=1.000")


@pytest.mark.parametrize("case", ["no_record", "no_modules", "no_steps",
                                  "unnamed_programs"])
def test_nothing_to_read_reads_none(case):
    s = _stages()
    if case == "no_record":
        s = None
    elif case == "no_modules":
        s["modules"] = {}
    elif case == "no_steps":
        s["steps"] = {}
    else:                        # a program whose stages are all jit_traced
        s["modules"] = {"/device:TPU:0": [("jit_traced", a, b) for _, a, b
                                          in s["modules"]["/device:TPU:0"]]}
    assert stages.step_ms(s, SRV, "server_scan_steps") is None
    assert stages.step_ms(s, CLI, "client_scan_steps") is None
    if case != "no_steps":       # the gap needs no step counts
        assert stages.dispatch_gap_ms(s) is None
    if case == "no_record":
        assert stages.span_ms(s, "serve/account") is None


def test_readers_read_the_record():
    from bench import run
    rec = {"stages": _stages()}
    assert run.load_reader("engine.server_step_ms.unique")(rec) == \
        pytest.approx(52 / 2600)
    assert run.load_reader("engine.client_step_ms.unique")(rec) == \
        pytest.approx(9 / 300)
    assert run.load_reader("serve.dispatch_gap_ms.unique")(rec) == \
        pytest.approx(3.0)
    assert run.load_reader("serve.dispatch_block_ms.unique")(rec) == \
        pytest.approx(21.25)
    for name in ("engine.server_step_ms.unique",
                 "engine.client_step_ms.unique",
                 "serve.dispatch_gap_ms.unique",
                 "serve.dispatch_block_ms.unique"):
        assert run.load_reader(name)({"trace": None}) is None


def test_read_part_is_what_reduce_reads():
    window = [(trace.WINDOW, 0.0, 100 * MS)]
    dev = {"/device:TPU:0": [("fusion.1", 0.0, 10 * MS),
                             ("fusion.2", 30 * MS, 60 * MS)]}
    lo, hi = stages.read_part(dev, window)
    assert (lo, hi) == (0.0, 60 * MS)
    assert trace.reduce(dev, window)["window_s"] == pytest.approx(
        (hi - lo) * 1e-9)
    assert stages.read_part({}, window) is None


def _plane(name, lines):
    ev = lambda n, a, d: types.SimpleNamespace(name=n, start_ns=a,
                                               duration_ns=d)
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[ev(*e) for e in evs])
        for ln, evs in lines])


def test_events_read_module_names_and_program_spans():
    data = types.SimpleNamespace(planes=[
        _plane("/device:TPU:0", [
            ("XLA Modules", [("jit_server_stage(12)", 10, 5),
                             ("jit_client_stage(13)", 15, 2)]),
            ("XLA Ops", [("%while.27 = ...", 10, 5)])]),
        _plane("/device:CUSTOM:0", [("XLA Modules", [("x(1)", 0, 1)])]),
        _plane("/host:CPU", [("python", [("serve/plan", 1, 2),
                                         ("train/fedavg", 4, 1),
                                         ("bench/poll", 0, 9),
                                         ("PjitFunction", 3, 1)])])])
    got = stages.events(data)
    assert got["modules"] == {"/device:TPU:0": [
        ("jit_server_stage", 10, 15), ("jit_client_stage", 15, 17)]}
    assert got["spans"] == [("serve/plan", 1, 3), ("train/fedavg", 4, 5)]


def test_cpu_trace_of_the_runtime_has_spans_and_no_modules():
    """Under the benchmark's capture the runtime's annotations are on the
    host clock; a CPU trace has no module line, so the stage readers read
    nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax._src.lib import _profiler
    from repro.core.sample_plan import SampleRequest
    from repro.core.schedules import DiffusionSchedule
    from repro.obs import ObsConfig
    from repro.serve import ServeConfig, ServeRuntime
    rt = ServeRuntime(
        ServeConfig(T=16, image_shape=(4, 4, 3), max_wave=4),
        {"a": jnp.float32(0.2)}, {"a": jnp.linspace(0.1, 0.5, 3)},
        lambda p, x, t, y: x * p["a"], DiffusionSchedule.linear(16),
        jax.random.PRNGKey(0), obs=ObsConfig(enabled=True))
    queue = [SampleRequest(client=c, t_cut=4,
                           y=np.eye(4, dtype=np.float32)[:2])
             for c in range(3)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.devices()
    session = _profiler.ProfilerSession(opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            rt.process(queue)
    finally:
        data = session.stop_and_get_profile_data()
    got = stages.events(data)
    names = {n for n, _, _ in got["spans"]}
    assert {"serve/plan", "serve/enqueue_server", "serve/account"} <= names
    assert got["modules"] == {}
    got["read"] = (0.0, float("inf"))
    got["steps"] = {"server_scan_steps": [12], "client_scan_steps": [4]}
    assert stages.step_ms(got, SRV, "server_scan_steps") is None
    assert stages.dispatch_gap_ms(got) is None
    assert stages.span_ms(got, "serve/account") > 0
