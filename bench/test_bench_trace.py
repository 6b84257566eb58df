"""The trace reduction: busy time as the union of device operations, the
idle share, per-operation time and count, and idle gaps named by the host
annotation that covers them."""
import pytest

from bench import trace

MS = 1e6  # ns


def _events():
    window = [(trace.WINDOW, 0.0, 100 * MS)]
    host = window + [("bench/poll", 10 * MS, 30 * MS),
                     ("bench/run_round", 0.0, 100 * MS),
                     ("bench/poll", 60 * MS, 95 * MS)]
    dev = {"/device:TPU:0": [
        ("fusion.1", 0.0, 10 * MS),
        ("fusion.1", 5 * MS, 8 * MS),             # overlaps the first
        ("_kernel_batched", 30 * MS, 40 * MS),
        ("convolution.2", 40 * MS, 60 * MS),
        ("fusion.1", 95 * MS, 110 * MS),          # runs past the window
    ]}
    return dev, host


def test_busy_is_the_union_clipped_to_the_window():
    r = trace.reduce(*_events())
    # [0,10] + [30,60] + [95,100] = 45 ms
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.045)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.55)


def test_op_time_and_count_inside_the_window():
    r = trace.reduce(*_events())
    assert r["op_s"]["_kernel_batched"] == pytest.approx(0.010)
    assert r["op_n"]["_kernel_batched"] == 1
    # the run that crosses the window's end is left out of op time
    assert r["op_n"]["fusion.1"] == 2
    assert r["op_s"]["fusion.1"] == pytest.approx(0.013)
    assert r["device_ops"][0][0] == "convolution.2"


def test_gaps_named_by_the_innermost_annotation():
    r = trace.reduce(*_events())
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    assert gaps == {0.035: "poll", 0.02: "poll"}


def test_busy_averages_over_devices():
    dev, host = _events()
    dev["/device:TPU:1"] = [("fusion.9", 0.0, 110 * MS)]
    r = trace.reduce(dev, host)
    assert r["busy_s"] == pytest.approx((0.045 + 0.1) / 2)


def test_read_only_as_far_as_the_device_recorded():
    dev, host = _events()
    dev["/device:TPU:0"] = dev["/device:TPU:0"][:-1]   # events end at 60 ms
    r = trace.reduce(dev, host)
    assert r["traced_s"] == pytest.approx(0.1)
    assert r["window_s"] == pytest.approx(0.06)
    # [0, 10] and [30, 60]; the silence after 60 ms is not read as idle
    assert r["busy_s"] == pytest.approx(0.040)


def test_devices_are_read_as_far_as_all_recorded():
    dev, host = _events()
    dev["/device:TPU:1"] = [("fusion.9", 0.0, 50 * MS)]
    r = trace.reduce(dev, host)
    assert r["window_s"] == pytest.approx(0.05)
    # device 0: [0, 10] + [30, 50]; device 1: [0, 50]
    assert r["busy_s"] == pytest.approx((0.030 + 0.05) / 2)


def test_nothing_on_a_device_reads_none():
    dev, host = _events()
    assert trace.reduce({}, host) is None
    late = {"/device:TPU:0": [("fusion.1", -5 * MS, -1 * MS)]}
    assert trace.reduce(late, host) is None


def test_union_merges_and_clips():
    assert trace.union([(5, 8), (0, 3), (2, 6), (9, 20)], 1, 10) == \
        [(1, 8), (9, 10)]


def test_capture_records_the_window_annotation():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    sink = {}
    with trace.capture(sink):
        with trace.annotate("probe"):
            f(jnp.ones(4)).block_until_ready()
    _, host = sink["events"]
    names = {n for n, _, _ in host}
    assert {trace.WINDOW, "bench/probe"} <= names


LOOP = ("%while.27 = (s32[]{:T(128)}, f32[8,4,32,32,3]{3,2,4,1,0:T(8,128)S(1)}"
        ", (f32[64]{0:T(128)}, bf16[3,3,64,64]{3,2,1,0:T(8,128)(2,1)})) "
        "while((s32[]{:T(128)}, f32[64]{0:T(128)}) %tuple.361), "
        "condition=%region_74.124.clone, body=%region_0.123.sunk" +
        "".join(f", /*index={i}*/f32[64]{{0:T(128)}}" for i in range(2000)))
FUSION = ("%fusion.1848 = (f32[32,64]{1,0:T(8,128)S(1)}, f32[32,32,32,64]"
          "{3,0,2,1:T(8,128)S(1)}) fusion(f32[32,64]{1,0:T(8,128)} "
          "%bitcast.1699, f32[64]{0:T(128)} %get-tuple-element.8450), "
          "kind=kOutput, calls=%fused_computation.266.clone.clone")
KERNEL = ('%ddpm_step_pallas_batched.8 = f32[8,96,128]{2,1,0:T(8,128)S(1)} '
          'custom-call(f32[8,3]{1,0:T(8,128)S(1)} %copy-done.216), '
          'custom_call_target="tpu_custom_call", backend_config={}')


@pytest.mark.parametrize("op,short", [
    (LOOP, "%while.27 while %region_0.123.sunk"),
    (FUSION, "%fusion.1848 fusion kOutput %fused_computation.266.clone.clone"),
    (KERNEL, "%ddpm_step_pallas_batched.8 custom-call tpu_custom_call"),
    ("%copy.975 = f32[32,32,32,64]{2,3,1,0:T(8,128)S(1)} copy(f32[32,32,32,"
     "64]{3,0,2,1:T(8,128)} %get-tuple-element.7573)", "%copy.975 copy"),
    ("convolution.2", "convolution.2"),
])
def test_short_name_keeps_name_opcode_and_what_it_runs(op, short):
    assert trace.short_name(op) == short


def test_breakdown_names_stay_short_however_long_the_hlo():
    """The result is one line of JSON: operations named by their whole HLO
    text made it tens of kilobytes."""
    import json
    ops = [(LOOP, 0.0, 50 * MS), (FUSION, 50 * MS, 60 * MS),
           (KERNEL + "x" * 20000, 60 * MS, 70 * MS)]
    dev, host = _events()
    dev["/device:TPU:0"] = ops
    r = trace.reduce(dev, host)
    assert all(len(n) <= trace.NAME_MAX for n, _ in r["device_ops"])
    assert len(json.dumps({"device_ops": r["device_ops"],
                           "idle_gaps": r["idle_gaps"]})) < 2000
    # time per operation is still kept under the whole name
    assert r["op_s"][KERNEL + "x" * 20000] == pytest.approx(0.010)
