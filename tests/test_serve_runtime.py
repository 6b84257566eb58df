"""Serve-runtime tests: cache correctness (warm-vs-cold bitwise
equivalence, eviction under pressure, fresh samples on re-submission),
scheduler semantics (policy invariance, shape-stable steady state with
one signature per bucket and zero re-traces), the strided server phase
end to end, and the padding-invariance property of the scheduler's fixed
tiers (``ragged`` marker — the PR-2 discipline applied to the serve
subsystem's padded G/R/H axes)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import hypothesis, st
from repro.core.sample_plan import (SampleRequest, group_key, pad_plan,
                                    plan_requests, stable_group_seed)
from repro.core.sampler import check_engine_plan, make_sample_engine
from repro.core.schedules import DiffusionSchedule
from repro.serve import ServeConfig, ServeRuntime

T = 16
SCHED = DiffusionSchedule.linear(T)
IMG = (4, 4, 3)
B, NC, K = 2, 3, 3

SP = {"a": jnp.float32(0.2), "b": jnp.float32(0.0)}
CP = {"a": jnp.linspace(0.1, 0.5, K), "b": jnp.zeros((K,))}


def apply_fn(p, x, t, y):
    return x * p["a"] + p["b"]


def _req(client: int, t_cut: int, label: int) -> SampleRequest:
    y = np.broadcast_to(np.eye(NC, dtype=np.float32)[label],
                        (B, NC)).copy()
    return SampleRequest(client=client, t_cut=t_cut, y=y)


def _queue():
    """Two cut-depth buckets x two labels with repeats both inside and
    across waves — the traffic shape the cache monetizes."""
    return [_req(0, 4, 0), _req(1, 8, 0), _req(2, 4, 0), _req(0, 4, 1),
            _req(1, 8, 0), _req(2, 8, 1), _req(0, 4, 0), _req(1, 4, 1)]


def _rt(seed: int = 0, **over) -> ServeRuntime:
    over.setdefault("max_wave", 4)
    cfg = ServeConfig(T=T, image_shape=IMG, **over)
    return ServeRuntime(cfg, SP, CP, apply_fn, SCHED,
                        jax.random.PRNGKey(seed))


def _assert_same(outs_a, outs_b):
    assert len(outs_a) == len(outs_b)
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Cache correctness
# ---------------------------------------------------------------------------


def test_warm_vs_cold_bitwise_equivalence():
    """A cache-hit wave produces bitwise the same samples as a cold run
    with the same keys — across a cold pass, a warm pass, and a second
    warm pass (stable group seeds + arrival-id request seeds)."""
    rt, cold = _rt(cache=True), _rt(cache=False)
    q = _queue()
    for p in range(3):
        outs, rep = rt.process(q)
        couts, crep = cold.process(q)
        _assert_same(outs, couts)
        if p:
            assert rep["cache_hits"] >= 1
            assert rep["requests_from_cache"] == len(q)
            assert rep["server_calls_physical"] == 0   # scan axis S == 0
            assert rep["server_calls_saved_by_cache"] == \
                crep["server_calls_logical"]
        assert crep["server_calls_physical"] > 0
        assert rep["server_calls_saved_by_dedup"] == \
            crep["server_calls_saved_by_dedup"]


def test_resubmission_draws_fresh_samples():
    """Replaying a queue reuses cached PREFIXES but never reuses client
    noise: arrival ids advance, so the user gets new samples."""
    rt = _rt(cache=True)
    q = _queue()
    outs1, _ = rt.process(q)
    outs2, rep2 = rt.process(q)
    assert rep2["cache_hits"] >= 1
    for a, b in zip(outs1, outs2):
        assert float(jnp.abs(a - b).max()) > 1e-6


def test_eviction_under_pressure_stays_correct():
    """A one-entry cache thrashes (evictions > 0) but never corrupts:
    outputs stay bitwise equal to the cache-less run."""
    rt = _rt(cache=True, cache_max_entries=1)
    cold = _rt(cache=False)
    q = _queue()
    for _ in range(2):
        outs, _ = rt.process(q)
        couts, _ = cold.process(q)
        _assert_same(outs, couts)
    assert rt.cache.stats.evictions > 0
    assert len(rt.cache) <= 1


def test_icm_groups_never_pollute_cache_telemetry():
    """Zero-step (ICM, t_ζ=T) prefixes are uncacheable by design — the
    runtime must neither probe nor insert them, so steady-state traffic
    containing ICM requests still reports hit_rate 1.0 with no
    ever-growing miss/rejected counters."""
    rt, cold = _rt(cache=True), _rt(cache=False)
    q = [_req(0, T, 0), _req(1, 8, 0)]          # ICM + cacheable
    for _ in range(3):
        outs, rep = rt.process(q)
        couts, _ = cold.process(q)
        _assert_same(outs, couts)
    assert rep["cache_misses"] == 0 and rep["cache_hit_rate"] == 1.0
    assert rt.cache.stats.rejected == 0
    assert len(rt.cache) == 1                    # only the t_ζ=8 prefix


def test_cache_key_isolation_across_runtimes():
    """Different base keys -> different key-schedule fingerprints: two
    runtimes can never alias each other's cache entries."""
    rt0, rt1 = _rt(seed=0), _rt(seed=1)
    gk = group_key(4, _req(0, 4, 0).y)
    assert rt0._cache_key(gk) != rt1._cache_key(gk)
    assert rt0._cache_key(gk) == _rt(seed=0)._cache_key(gk)


# ---------------------------------------------------------------------------
# Scheduler semantics
# ---------------------------------------------------------------------------


def test_policy_invariance_fifo_vs_depth():
    """Bucketing is a pure performance knob: fifo (PR-3 arrival-order
    waves) and depth buckets produce bitwise identical outputs, in
    arrival order, for the same traffic."""
    a, b = _rt(policy="depth"), _rt(policy="fifo")
    q = _queue()
    outs_a, rep_a = a.process(q)
    outs_b, rep_b = b.process(q)
    _assert_same(outs_a, outs_b)
    # depth buckets eliminate intra-wave depth padding; fifo pays it
    assert rep_a["padded_model_calls"] < rep_b["padded_model_calls"]


def test_steady_state_one_signature_per_bucket():
    """Shape stability: after the cold and first-warm passes, repeated
    traffic presents exactly one compiled signature per bucket and the
    engine never re-traces (the compile guard the CI smoke asserts)."""
    rt = _rt(cache=True)
    q = _queue()
    rt.process(q)
    rt.process(q)
    traces_before = rt.traces
    _, rep = rt.process(q)
    assert rep["engine_traces"] == 0
    assert rt.traces == traces_before
    assert rep["max_signatures_per_bucket"] == 1
    assert rep["buckets"] == 2          # cuts {4, 8}


def test_strided_runtime_warm_vs_cold():
    """The strided-DDIM server phase composes with the cache: bitwise
    warm-vs-cold, and the prefix costs ⌈(T−t_ζ)/stride⌉ calls."""
    rt = _rt(cache=True, server_stride=3)
    cold = _rt(cache=False, server_stride=3)
    q = [_req(0, 4, 0), _req(1, 8, 1), _req(2, 4, 0)]
    for p in range(2):
        outs, rep = rt.process(q)
        couts, crep = cold.process(q)
        _assert_same(outs, couts)
    assert rep["cache_hits"] >= 1
    # groups (4,y0) and (8,y1): ceil(12/3) + ceil(8/3) = 4 + 3
    assert crep["server_calls_logical"] == 7


# ---------------------------------------------------------------------------
# Pipelined waves (PR 6): overlap is a pure performance knob
# ---------------------------------------------------------------------------


def test_pipelined_bitwise_equals_sequential():
    """The double-buffered pipelined loop must be bitwise-identical to
    the per-wave-barrier loop — outputs, cache traffic, and physical
    call counts — across cold, warm, and straggler-stalled passes."""
    pipe = _rt(pipeline=True)
    barrier = _rt(pipeline=False)
    stalled = _rt(pipeline=True, straggle_s=0.001)
    q = _queue()
    for p in range(3):
        outs_p, rep_p = pipe.process(q)
        outs_b, rep_b = barrier.process(q)
        outs_s, _ = stalled.process(q)
        _assert_same(outs_p, outs_b)
        _assert_same(outs_p, outs_s)
        for k in ("cache_hits", "cache_misses", "cache_insertions",
                  "requests_from_cache", "server_calls_physical",
                  "client_calls_physical", "max_signatures_per_bucket"):
            assert rep_p[k] == rep_b[k], k
    assert pipe.cache.keys() == barrier.cache.keys()


def test_split_stages_compose_to_fused_engine():
    """make_sample_engine(split=True)'s stage composition is bitwise the
    fused engine — the single-source-of-truth contract the pipelined
    runtime rests on (both derive their phase key from the same
    jax.random.split)."""
    key = jax.random.PRNGKey(3)
    hit_key = group_key(4, _req(0, 4, 0).y)
    stored = jnp.arange(np.prod((B,) + IMG), dtype=jnp.float32
                        ).reshape((B,) + IMG) * 0.01
    lookup = lambda gk: stored if gk == hit_key else None
    reqs = [_req(0, 4, 0), _req(1, 8, 0), _req(2, 4, 1)]
    plan = plan_requests(reqs, T, group_seed_fn=stable_group_seed,
                         lookup_fn=lookup, image_shape=IMG)
    fused = make_sample_engine(SCHED, apply_fn, IMG)
    server, client = make_sample_engine(SCHED, apply_fn, IMG, split=True)
    out_f, hand_f = fused(SP, CP, key, plan.tables, plan.inject)
    hand_s = server(SP, key, plan.tables)
    out_s = client(CP, key, plan.tables, hand_s, plan.inject)
    np.testing.assert_array_equal(np.asarray(hand_s), np.asarray(hand_f))
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_f))


def test_non_pow2_max_wave_keeps_pow2_tiers():
    """Regression (PR 6): scheduler.tier with a non-pow2 cap used to
    return the raw cap (min(8, 6) = 6), leaking a non-pow2 tier into the
    signature menu.  The cap now rounds UP, and a max_wave=6 runtime
    serves correctly with pow2 group tiers."""
    from repro.serve.scheduler import WaveScheduler, tier

    def pow2ceil(n):
        t = 1
        while t < n:
            t *= 2
        return t

    for cap in (3, 5, 6, 7):
        for n in range(1, 10):
            t = tier(n, cap)
            assert t & (t - 1) == 0, (n, cap, t)       # power of two
            assert t == min(pow2ceil(n), pow2ceil(cap))
    assert tier(5, 6) == 8 and tier(3, 6) == 4 and tier(7, 4) == 4
    sch = WaveScheduler(max_wave=6)
    assert sch.group_tier(5) == 8                      # was 6 pre-fix
    rt, cold = _rt(max_wave=6), _rt(max_wave=6, cache=False)
    q = _queue()
    for _ in range(2):
        outs, rep = rt.process(q)
        couts, _ = cold.process(q)
        _assert_same(outs, couts)
    assert rep["max_signatures_per_bucket"] == 1


def test_report_gauge_vs_delta_cache_fields():
    """cache_entries/cache_bytes are gauges (absolute occupancy, idle
    ticks included); every other cache field is a per-call delta."""
    rt = _rt(cache=True)
    rt.process(_queue())
    idle = rt.process([])[1]
    assert idle["cache_entries"] == len(rt.cache) > 0
    assert idle["cache_bytes"] == rt.cache.stats.bytes_in_use > 0
    for k in ("cache_hits", "cache_misses", "cache_insertions",
              "cache_evictions", "cache_rejected"):
        assert idle[k] == 0, k
    warm = rt.process(_queue())[1]
    assert warm["cache_insertions"] == 0       # all prefixes already held
    assert warm["cache_hits"] > 0


# ---------------------------------------------------------------------------
# Padding invariance of the scheduler's fixed tiers (ragged marker)
# ---------------------------------------------------------------------------

_PAD_ENGINE = make_sample_engine(SCHED, apply_fn, IMG)


@pytest.mark.ragged
@hypothesis.settings(max_examples=4, deadline=None)
@hypothesis.given(gpad=st.integers(min_value=0, max_value=2),
                  rpad=st.integers(min_value=0, max_value=2),
                  ipad=st.integers(min_value=0, max_value=2))
def test_tier_padding_invariance(gpad, rpad, ipad):
    """pad_plan's inert rows — all-masked scan groups, all-masked
    requests, zero inject rows — never change real outputs, bitwise:
    exactly the property that lets the scheduler pad every wave to fixed
    (G, R, H) tiers for one compile per bucket."""
    key = jax.random.PRNGKey(13)
    hit_key = group_key(4, _req(0, 4, 0).y)
    stored = jnp.arange(np.prod((B,) + IMG), dtype=jnp.float32
                        ).reshape((B,) + IMG) * 0.01
    lookup = lambda gk: stored if gk == hit_key else None
    reqs = [_req(0, 4, 0), _req(1, 8, 0), _req(2, 4, 1)]
    plan = plan_requests(reqs, T, group_seed_fn=stable_group_seed,
                         lookup_fn=lookup, image_shape=IMG)
    assert plan.n_hits == 1 and plan.n_groups == 2
    base_out, base_hand = _PAD_ENGINE(SP, CP, key, plan.tables, plan.inject)
    padded = pad_plan(plan, n_groups=plan.n_groups + gpad,
                      n_requests=plan.n_requests + rpad,
                      n_inject=plan.n_hits + ipad)
    out, hand = _PAD_ENGINE(SP, CP, key, padded.tables, padded.inject)
    np.testing.assert_array_equal(np.asarray(out[:len(reqs)]),
                                  np.asarray(base_out))
    np.testing.assert_array_equal(np.asarray(hand[:plan.n_groups]),
                                  np.asarray(base_hand))


def test_pad_plan_validation():
    plan = plan_requests([_req(0, 4, 0)], T)
    with pytest.raises(ValueError):
        pad_plan(plan, n_groups=0)
    with pytest.raises(ValueError):
        pad_plan(plan, n_inject=1)      # no inject tables on this plan
    # stride and server update rule travel together (check_engine_plan)
    strided = plan_requests([_req(0, 4, 0)], T, server_stride=2)
    with pytest.raises(ValueError):
        check_engine_plan(False, strided)
    with pytest.raises(ValueError):
        check_engine_plan(True, plan)
    check_engine_plan(True, strided)
    check_engine_plan(False, plan)
    cfg_bad = dataclasses.replace(ServeConfig(T=T, image_shape=IMG))
    with pytest.raises(ValueError):
        ServeRuntime(cfg_bad, SP, CP, apply_fn,
                     DiffusionSchedule.linear(T + 1),
                     jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Continuous admission (PR 7): admission timing is a pure performance knob
# ---------------------------------------------------------------------------


def test_continuous_bitwise_equals_depth():
    """policy="continuous" (admission at wave boundaries) must produce
    bitwise the same outputs as policy="depth" (admission at queue-drain
    boundaries) for the same arrival order — seeds are arrival-/content-
    stable and partial-wave padding is inert, so WHEN a request is bound
    into a wave can never leak into its samples."""
    cont, depth = _rt(policy="continuous"), _rt(policy="depth")
    q = _queue()
    for _ in range(3):                      # cold / warm / steady
        outs_c, rep_c = cont.process(q)
        outs_d, rep_d = depth.process(q)
        _assert_same(outs_c, outs_d)
    # steady state: zero re-traces, and no signature outside depth's menu
    assert rep_c["engine_traces"] == 0
    assert rep_c["max_signatures_per_bucket"] == 1
    assert set(rep_c["signatures_per_bucket"]) <= \
        set(rep_d["signatures_per_bucket"])


def test_continuous_submit_poll_matches_process():
    """The incremental submit()/poll() loop is the same code path as
    process(): one-at-a-time submission over a live poll loop retires
    every ticket with bitwise the outputs a depth process() call returns,
    and drain() leaves the runtime idle."""
    cont, depth = _rt(policy="continuous"), _rt(policy="depth")
    q = _queue()
    outs_d, _ = depth.process(q)
    tickets, done = [], []
    for r in q:                              # open-loop, one per submit
        tickets.extend(cont.submit([r]))
        done.extend(cont.poll())             # non-blocking admission turn
    done.extend(cont.drain())
    assert not cont.busy
    assert sorted(t.rid for t in done) == [t.rid for t in tickets]
    rep = cont.finish_report()
    assert rep["requests"] == len(q)
    _assert_same([t.output for t in tickets], outs_d)


def test_continuous_partial_wave_padding_invariance():
    """A request served alone in a partially-refilled wave is bitwise the
    request served inside a full wave (same arrival id ⇒ same seeds;
    pad_plan's inert rows carry the rest)."""
    solo, full = _rt(policy="continuous"), _rt(policy="depth")
    r = _req(0, 4, 0)
    outs_solo, rep = solo.process([r])           # 1-request wave
    outs_full, _ = full.process([r, _req(1, 4, 1),
                                 _req(2, 4, 0), _req(0, 4, 1)])
    _assert_same(outs_solo, outs_full[:1])       # both hold arrival id 0
    assert rep["requests"] == 1 and rep["waves"] == 1


def test_submit_requires_continuous_policy():
    with pytest.raises(ValueError):
        _rt(policy="depth").submit([_req(0, 4, 0)])


def test_process_refused_while_continuous_busy():
    rt = _rt(policy="continuous")
    rt.submit([_req(0, 4, 0)])
    with pytest.raises(RuntimeError):
        rt.process([_req(1, 8, 1)])
    rt.drain()
    rt.finish_report()
    rt.process([_req(1, 8, 1)])                  # idle again → fine


# ---------------------------------------------------------------------------
# Per-request SLO + latency accounting (PR 7)
# ---------------------------------------------------------------------------


def test_ticket_timestamps_monotone():
    """enqueue ≤ admit ≤ dispatch ≤ retire on every ticket, and the
    report rows carry the same ordering relative to the frame start."""
    rt = _rt(policy="continuous")
    tickets = rt.submit(_queue())
    rt.drain()
    rep = rt.finish_report()
    for t in tickets:
        assert t.t_enqueue <= t.t_admit <= t.t_dispatch <= t.t_retire
        assert t.latency_s > 0.0 and t.admit_wait_s >= 0.0
    for row in rep["per_request"]:
        assert 0.0 <= row["admit_s"] <= row["dispatch_s"] <= row["retire_s"]
    # rows are in RETIREMENT order (waves interleave buckets), but every
    # submitted ticket retires exactly once
    assert sorted(row["rid"] for row in rep["per_request"]) == \
        [t.rid for t in tickets]


def test_slo_accounting_default_and_override():
    """slo_s is accounting only: the per-call default applies to requests
    without their own deadline, a per-request slo_s overrides it, and a
    0.0-second deadline is tracked and missed (falsy-zero guard)."""
    rt = _rt(policy="continuous")
    q = [_req(0, 4, 0),                                   # default slo
         dataclasses.replace(_req(1, 8, 1), slo_s=1e4),   # generous
         dataclasses.replace(_req(2, 4, 0), slo_s=0.0),   # impossible
         _req(0, 8, 1)]                                   # default slo
    _, rep = rt.process(q, slo_s=1e-12)
    assert rep["slo_tracked"] == 4
    # defaults (1e-12 s) and the 0.0 deadline miss; the 1e4 s one holds
    assert rep["slo_misses"] == 3
    assert rep["slo_miss_rate"] == pytest.approx(0.75)
    rows = {r["rid"]: r for r in rep["per_request"]}
    assert rows[1]["slo_s"] == 1e4 and not rows[1]["slo_miss"]
    assert rows[2]["slo_s"] == 0.0 and rows[2]["slo_miss"]
    # no deadlines anywhere → nothing tracked, rate 0.0 (not NaN)
    _, rep2 = _rt(policy="depth").process(_queue())
    assert rep2["slo_tracked"] == 0 and rep2["slo_miss_rate"] == 0.0


def test_open_loop_enqueue_t_charges_queueing_delay():
    """enqueue_t back-dates a request's arrival (open-loop load): its
    latency must include the pre-submit queueing the caller measured."""
    import time as _time
    rt = _rt(policy="depth")
    t0 = _time.perf_counter()
    _, rep = rt.process([_req(0, 4, 0)], enqueue_t=[t0 - 1.0])
    row = rep["per_request"][0]
    assert row["latency_s"] >= 1.0 and row["enqueue_s"] < 0.0
    with pytest.raises(ValueError):
        rt.process([_req(0, 4, 0)], enqueue_t=[t0, t0])   # length mismatch


def test_pipelined_latency_not_inflated_by_retirement():
    """Satellite 3 (the latency-accounting audit): recorded latency is
    enqueue → OBSERVED completion, via a ready probe that runs during
    stalls and polls.  Pre-PR-7, a pipelined wave retired only when the
    in-flight window filled — so with a straggle stall per wave, wave
    i's recorded latency absorbed wave i+1's whole stall (≈ 2× stall
    for the first wave).  Post-fix, both modes record ≈ one stall plus
    device time for the first wave."""
    stall = 0.08
    q = [_req(i % K, 4, i % 2) for i in range(8)]   # one bucket, 2 waves
    for pipeline in (False, True):
        rt = _rt(cache=False, pipeline=pipeline, straggle_s=stall)
        rt.process(q)                    # warm-up: compile both stages
        _, rep = rt.process(q)
        first_wave = [r for r in rep["per_request"] if r["rid"] < 12]
        assert len(first_wave) == 4
        worst = max(r["latency_s"] for r in first_wave)
        # pre-fix pipelined: ≥ 2 stalls (~0.16 s); post-fix: ~1 stall
        assert worst < 1.5 * stall, (pipeline, worst)
        # the queue still pays both stalls overall
        assert rep["wall_s"] >= 2 * stall


# ---------------------------------------------------------------------------
# fifo mixed-batch arrival order (PR-7 satellite) + admit semantics
# ---------------------------------------------------------------------------


def _req_b(client: int, t_cut: int, label: int, batch: int) -> SampleRequest:
    y = np.broadcast_to(np.eye(NC, dtype=np.float32)[label],
                        (batch, NC)).copy()
    return SampleRequest(client=client, t_cut=t_cut, y=y)


def test_fifo_mixed_batch_stays_in_arrival_order():
    """Regression: fifo waves were keyed by (t_cut=-1, B) buckets, so a
    mixed-batch queue was silently re-bucketed by B — out of arrival
    order, contradicting the policy's contract.  fifo now chunks in
    arrival order, breaking a wave when B changes (one plan = one B)."""
    from repro.serve.scheduler import WaveScheduler

    sch = WaveScheduler(max_wave=4, policy="fifo")
    q = [_req_b(0, 4, 0, 2), _req_b(1, 8, 1, 2), _req_b(2, 4, 0, 4),
         _req_b(0, 8, 1, 2), _req_b(1, 4, 0, 4), _req_b(2, 8, 1, 4)]
    waves = sch.waves(q)
    # arrival order preserved end to end (pre-fix: [0, 1, 3, 2, 4, 5])
    assert [i for w in waves for i in w.queue_idx] == list(range(6))
    # every wave is single-B, and B breaks force the expected chunking
    for w in waves:
        assert len({r.y.shape[0] for r in w.requests}) == 1
    assert [list(w.queue_idx) for w in waves] == [[0, 1], [2], [3], [4, 5]]
    # uniform-B queues keep the PR-3 chunking exactly
    uni = sch.waves(_queue())
    assert [list(w.queue_idx) for w in uni] == [[0, 1, 2, 3], [4, 5, 6, 7]]

    # end to end: mixed-B fifo serves bitwise what depth serves
    fifo, depth = _rt(policy="fifo"), _rt(policy="depth")
    outs_f, _ = fifo.process(q)
    outs_d, _ = depth.process(q)
    _assert_same(outs_f, outs_d)


def test_admit_pops_oldest_head_first():
    """scheduler.admit is FIFO across buckets: the bucket whose HEAD
    ticket arrived earliest dispatches next, up to max_wave tickets."""
    from collections import OrderedDict, deque
    from types import SimpleNamespace

    from repro.serve.scheduler import WaveBucket, WaveScheduler

    sch = WaveScheduler(max_wave=2, policy="continuous")
    bA, bB = WaveBucket(4, 2), WaveBucket(8, 2)
    pending = OrderedDict()
    pending[bA] = deque(SimpleNamespace(rid=r) for r in (5, 6, 9))
    pending[bB] = deque(SimpleNamespace(rid=r) for r in (3,))
    got = []
    while (adm := sch.admit(pending)) is not None:
        b, take = adm
        got.append((b, [t.rid for t in take]))
    assert got == [(bB, [3]), (bA, [5, 6]), (bA, [9])]
    assert all(not q_ for q_ in pending.values())


# ---------------------------------------------------------------------------
# Report edge cases + key rotation (PR-7 satellites)
# ---------------------------------------------------------------------------


def test_report_edge_cases_schema_complete():
    """Empty queue, single request, and all-hits traffic all produce the
    SAME report schema with finite values — zero (never NaN) percentiles
    and a 0.0 hit rate when there were no lookups."""
    import math

    rt = _rt(cache=True)
    empty = rt.process([])[1]
    single = rt.process([_req(0, 4, 0)])[1]
    rt.process(_queue())
    all_hits = rt.process(_queue())[1]          # warm: every prefix hits
    assert set(empty) == set(single) == set(all_hits)
    for k in ("latency_p50_s", "latency_p95_s", "latency_p99_s",
              "admit_wait_p50_s", "admit_wait_p95_s", "slo_miss_rate",
              "cache_hit_rate", "req_per_s", "samples_per_s"):
        assert empty[k] == 0.0, k
    assert empty["per_request"] == [] and empty["requests"] == 0
    assert single["requests"] == 1 and single["latency_p50_s"] > 0.0
    assert single["latency_p50_s"] <= single["latency_p99_s"]
    assert all_hits["cache_misses"] == 0 and all_hits["cache_hit_rate"] == 1.0
    for rep in (empty, single, all_hits):
        for k, v in rep.items():
            if isinstance(v, float):
                assert math.isfinite(v), (k, v)


def test_rotate_key_starts_fresh_cache_epoch():
    """rotate_key swaps the base PRNG key and clears the cache (every
    entry is addressed by the old key fingerprint — permanently
    unreachable); it refuses to run mid-stream or mid-frame."""
    rt = _rt(seed=0, cache=True)
    q = _queue()
    outs_old, _ = rt.process(q)
    assert len(rt.cache) > 0
    rt.rotate_key(jax.random.PRNGKey(42))
    assert len(rt.cache) == 0 and rt.cache.stats.clears == 1
    outs_new, rep = rt.process(q)
    # a different base key draws different noise — outputs must change
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(outs_old, outs_new))
    # same key in a fresh runtime (same arrival ids) reproduces bitwise —
    # which also proves no hit served stale old-key content — and the
    # post-rotation pass behaves exactly like a cold fill (same in-pass
    # repeat hits, same misses: nothing carried over)
    fresh = _rt(seed=42, cache=True)
    fresh._next_rid = len(q)                    # align arrival ids
    outs_ref, rep_ref = fresh.process(q)
    _assert_same(outs_new, outs_ref)
    assert rep["cache_hits"] == rep_ref["cache_hits"]
    assert rep["cache_misses"] == rep_ref["cache_misses"]

    busy = _rt(policy="continuous")
    busy.submit([_req(0, 4, 0)])
    with pytest.raises(RuntimeError):
        busy.rotate_key(jax.random.PRNGKey(7))
    busy.drain()
    with pytest.raises(RuntimeError):           # frame still open
        busy.rotate_key(jax.random.PRNGKey(7))
    busy.finish_report()
    busy.rotate_key(jax.random.PRNGKey(7))      # idle + closed → fine


def test_rotate_for_epoch_idempotent_and_addressed():
    """PR 9: the DP-epoch hook — rotate_for_epoch(e, base) rotates to
    fold_in(base, e) exactly once per epoch (idempotent re-fires from
    repeated callbacks are no-ops) and refuses negative epochs."""
    base = jax.random.PRNGKey(9)
    rt = _rt(seed=0, cache=True)
    q = _queue()
    rt.process(q)
    assert len(rt.cache) > 0
    assert rt.rotate_for_epoch(1, base) is True
    assert len(rt.cache) == 0 and rt.cache.stats.clears == 1
    rt.process(q)
    # same epoch again: no-op — the warm cache survives
    assert rt.rotate_for_epoch(1, base) is False
    assert rt.cache.stats.clears == 1 and len(rt.cache) > 0
    outs_e1, _ = rt.process(q)
    # new epoch rotates again
    assert rt.rotate_for_epoch(2, base) is True
    assert rt.cache.stats.clears == 2 and len(rt.cache) == 0
    # the rotation key is ADDRESSED (fold_in(base, epoch)): a fresh
    # runtime seeded with that key reproduces epoch 1 bitwise
    fresh = _rt(seed=0, cache=True)
    fresh.rotate_key(jax.random.fold_in(base, 1))
    fresh._next_rid = rt._next_rid - len(q)     # align arrival ids
    outs_ref, _ = fresh.process(q)
    _assert_same(outs_e1, outs_ref)
    with pytest.raises(ValueError):
        rt.rotate_for_epoch(-1, base)


# ---------------------------------------------------------------------------
# Observability: mid-flight report frames + retire-frame span attribution
# ---------------------------------------------------------------------------


def test_finish_report_with_waves_in_flight_and_span_attribution():
    """finish_report() is legal while waves are still in flight: the
    frame covers what RETIRED during it (the dispatch shows up as a wave
    delta, the requests do not), the in-flight work lands in the NEXT
    frame, and the wave span — opened in frame N, closed at observed
    completion in frame N+1 — is attributed to its retire frame, exactly
    like the ticket latency percentiles (PR-7 audit)."""
    from repro.obs import ObsConfig
    cfg = ServeConfig(T=T, image_shape=IMG, max_wave=4,
                      policy="continuous", pipeline=True)
    rt = ServeRuntime(cfg, SP, CP, apply_fn, SCHED,
                      jax.random.PRNGKey(0), obs=ObsConfig(enabled=True))

    # frame 0: one wave submitted, drained, and reported normally
    rt.submit([_req(0, 4, 0), _req(1, 4, 1)])
    rt.drain()
    rep0 = rt.finish_report()
    assert rep0["requests"] == 2 and rep0["waves"] == 1

    # frame 1: dispatch a wave but close the frame BEFORE it retires
    tickets_b = rt.submit([_req(2, 4, 0)])
    bucket, take = rt.scheduler.admit(rt._pending)
    rt._dispatch(bucket.label(), list(take))
    assert rt._inflight                      # genuinely still in flight
    rep1 = rt.finish_report()
    assert rep1["waves"] == 1                # the dispatch is frame-1 work
    assert rep1["requests"] == 0             # but nothing retired in it
    assert rep1["latency_p50_s"] == 0.0      # empty percentile window

    # frame 2: the wave retires here and is reported here
    rt.drain()
    rep2 = rt.finish_report()
    assert rep2["requests"] == 1 and rep2["waves"] == 0
    assert tickets_b[0].output is not None

    spans = rt.obs.spans()
    waves = [s for s in spans if s.name == "wave"]
    assert len(waves) == 2
    # tickets link to their wave's span id
    assert tickets_b[0].span_id == waves[1].sid
    assert {r["span_id"] for r in rep0["per_request"]} == {waves[0].sid}
    # retire-frame attribution across the frame boundary
    assert waves[0].frame == 0               # opened + retired in frame 0
    assert waves[1].frame == 2               # opened frame 1, retired 2
    # the host-side children of wave B closed inside frame 1; only the
    # retire probe crossed into frame 2 with the wave span itself
    kids = {s.name: s for s in spans if s.parent == waves[1].sid}
    assert {"plan", "enqueue_server", "enqueue_client",
            "retire"} <= set(kids)
    assert kids["plan"].frame == 1
    assert kids["enqueue_client"].frame == 1
    assert kids["retire"].frame == 2
    assert waves[1].attrs["device_wait_s"] >= 0.0


def test_scan_step_histograms_hold_each_wave_padded_widths():
    """Once per dispatched wave, ``server_scan_steps``/``client_scan_steps``
    observe the step widths of the tables the engine stages were handed —
    padded widths, so a wave whose prefixes all hit the cache reads 0."""
    rt = _rt(cache=True)
    handed = {"server": [], "client": []}
    server, client = rt._server_stage, rt._client_stage

    def spy_server(params, key, tables):
        handed["server"].append(tables.group_t.shape[1])
        return server(params, key, tables)

    def spy_client(params, key, tables, handoff, inject):
        handed["client"].append(tables.client_t.shape[1])
        return client(params, key, tables, handoff, inject)

    rt._server_stage, rt._client_stage = spy_server, spy_client
    snap = rt.registry.snapshot()
    _, cold = rt.process(_queue())
    _, warm = rt.process(_queue())
    server_steps = rt.registry.window("server_scan_steps", snap).tolist()
    client_steps = rt.registry.window("client_scan_steps", snap).tolist()
    assert server_steps == handed["server"]
    assert client_steps == handed["client"]
    assert len(server_steps) == cold["waves"] + warm["waves"]
    assert 0 in server_steps and max(server_steps) > 0   # warm hits
    assert set(client_steps) == {4, 8}                   # the two cuts


@pytest.mark.parametrize("cache", [True, False])
def test_dispatch_spans_sit_under_their_wave_in_order(cache):
    from repro.obs import ObsConfig
    rt = ServeRuntime(ServeConfig(T=T, image_shape=IMG, max_wave=4,
                                  cache=cache),
                      SP, CP, apply_fn, SCHED, jax.random.PRNGKey(0),
                      obs=ObsConfig(enabled=True))
    rt.process(_queue())
    spans = rt.obs.spans()
    waves = [s for s in spans if s.name == "wave"]
    assert waves
    expected = ["enqueue_server"] + (["cache_fill"] if cache else []) + \
        ["enqueue_client", "account"]
    for w in waves:
        assert "rids" not in w.attrs
        kids = sorted((s for s in spans if s.parent == w.sid
                       and s.name in ("enqueue_server", "cache_fill",
                                      "enqueue_client", "account")),
                      key=lambda s: s.t0)
        assert [s.name for s in kids] == expected
        assert all(w.t0 <= s.t0 <= s.t1 <= w.t1 for s in kids)
