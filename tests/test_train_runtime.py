"""Federated training runtime tests (repro.train).

The contract under test (train/runtime.py design notes):

  * differential — the identity-keyed cohort round (vmap/scan engine)
    matches the sequential eager oracle ``train_round_reference(uids=)``
    at the repo's established oracle tolerance;
  * BITWISE tier-padding invariance — a cohort padded along the client
    axis to its participation tier equals the unpadded engine run
    exactly (params, moments, step counters, metrics), and the padded
    slots come back untouched;
  * BITWISE mid-run resume — checkpoint after round j, restore, finish:
    identical to the uninterrupted run (full state incl. RNG);
  * shape stability — drifting cohort sizes compile at most ONE engine
    signature per participation tier (jit trace-counter guard);
  * policy inertness — participation, mid-round dropout, join/leave only
    choose WHO trains; an absent client's net, moments, and counters are
    bitwise-frozen while it sits out.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.collab import (CollabState, make_vectorized_round,
                               stack_clients, train_round_reference,
                               unstack_clients)
from repro.core.schedules import DiffusionSchedule
from repro.core.splitting import CutPoint
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.train import (ParticipationConfig, TrainConfig, TrainRuntime,
                         participation_tier, sample_cohort, sample_drops,
                         sample_lags)
from repro.train.participation import TAG_DROP, uid_scores
from repro.train.registry import ClientRegistry

SCHED = DiffusionSchedule.linear(60)
CUT = CutPoint(60, 20)
OPT = AdamWConfig(lr=1e-3)


def tiny_apply(params, x, t, y):
    return x * params["a"] + params["b"]


def tiny_init(key):
    return {"a": jax.random.uniform(key, (), minval=0.1, maxval=0.6),
            "b": jnp.float32(0.0)}


def tiny_data(seed, n, img=6, n_classes=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, img, img, 3)).astype(np.float32))
    y = jnp.zeros((n, n_classes)).at[:, seed % n_classes].set(1.0)
    return x, y


def tiny_config(**kw):
    base = dict(T=60, t_cut=20, image_shape=(6, 6, 3), n_classes=4,
                batch_size=4, batches_per_round=2, lr=1e-3)
    base.update(kw)
    return TrainConfig(**base)


def make_runtime(key, sizes, **cfg_kw):
    rt = TrainRuntime(tiny_config(**cfg_kw), tiny_init, tiny_apply, key)
    for i, n in enumerate(sizes):
        rt.register_client(*tiny_data(i, n))
    return rt


def trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def assert_trees_close(a, b, **tol):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), **tol)


# ---------------------------------------------------------------------------
# registry / participation units
# ---------------------------------------------------------------------------


def test_registry_uids_permanent():
    reg = ClientRegistry()
    a = reg.register()
    b = reg.register()
    assert (a, b) == (0, 1)
    reg.leave(a)
    assert reg.active_uids() == [b]
    assert reg.uids() == [a, b]           # departed, not deleted
    c = reg.register()
    assert c == 2                          # never reuses 0
    with pytest.raises(ValueError):
        reg.register(uid=1)                # no identity collisions
    reg.rejoin(a)
    assert reg.active_uids() == [0, 1, 2]


def test_participation_tier():
    assert [participation_tier(n) for n in (0, 1, 2, 3, 4, 5, 8, 9)] == \
        [1, 1, 2, 4, 4, 8, 8, 16]
    assert participation_tier(9, cap=8) == 8
    # regression (PR 6): non-pow2 caps round UP instead of leaking a
    # non-pow2 tier into the signature menu
    assert participation_tier(5, cap=6) == 8
    assert participation_tier(3, cap=6) == 4
    assert participation_tier(9, cap=7) == 8


def test_fixed_policy_requires_cohort_k():
    """Regression (PR 6): policy='fixed' with the default cohort_k=0
    used to silently fall through to a min_cohort fill of 1."""
    with pytest.raises(ValueError, match="cohort_k"):
        ParticipationConfig(policy="fixed")
    with pytest.raises(ValueError, match="cohort_k"):
        ParticipationConfig(policy="fixed", cohort_k=0)
    assert ParticipationConfig(policy="fixed", cohort_k=1).cohort_k == 1
    # other policies keep the 0 default without complaint
    assert ParticipationConfig(policy="bernoulli").cohort_k == 0
    with pytest.raises(ValueError):
        ParticipationConfig(lag_p=1.5)
    with pytest.raises(ValueError):
        ParticipationConfig(lag_max=0)


def test_cohort_draws_are_identity_keyed(key):
    """One client's participation draw must not depend on the roster:
    adding client 9 never flips clients 0-4's membership."""
    cfg = ParticipationConfig(policy="bernoulli", p=0.5)
    for r in range(8):
        small = sample_cohort(cfg, key, r, [0, 1, 2, 3, 4])
        big = sample_cohort(cfg, key, r, [0, 1, 2, 3, 4, 9])
        assert [u for u in big if u != 9] == small
    # deterministic given (key, round)
    assert sample_cohort(cfg, key, 3, [0, 1, 2]) == \
        sample_cohort(cfg, key, 3, [0, 1, 2])
    # fixed-k picks exactly k
    fx = ParticipationConfig(policy="fixed", cohort_k=2)
    assert len(sample_cohort(fx, key, 0, [0, 1, 2, 3, 4])) == 2
    assert sample_cohort(ParticipationConfig(policy="full"), key, 0,
                         [3, 1, 2]) == [1, 2, 3]


def test_min_cohort_floor(key):
    cfg = ParticipationConfig(policy="bernoulli", p=0.0, min_cohort=1)
    for r in range(4):
        assert len(sample_cohort(cfg, key, r, [0, 1, 2])) == 1


def test_sample_drops_bounds(key):
    cfg = ParticipationConfig(drop_p=1.0)
    drops = sample_drops(cfg, key, 0, [0, 1, 2], n_batches=3)
    assert set(drops) == {0, 1, 2}
    assert all(0 <= d < 3 for d in drops.values())
    assert sample_drops(ParticipationConfig(drop_p=0.0), key, 0, [0],
                        3) == {}


def test_sample_drops_slot0_semantics(key):
    """Slot 0 means 'connected, then immediately gone': the member never
    trains a single batch.  The slot is the conditioned score mapped
    over the round — score s < drop_p/n_batches ⇒ slot 0 — and a slot-0
    drop in plan_round leaves the member's mask all-zero."""
    cohort, nb = [0, 1, 2, 3, 4, 5, 6, 7], 3
    cfg = ParticipationConfig(drop_p=1.0)
    scores = uid_scores(key, TAG_DROP, 0, cohort)
    drops = sample_drops(cfg, key, 0, cohort, n_batches=nb)
    for u, s in zip(cohort, scores):
        assert drops[u] == min(int(s * nb), nb - 1)
        assert (drops[u] == 0) == (s < 1.0 / nb)
    # plan-level semantics: a forced slot-0 drop masks the whole member
    reg = ClientRegistry()
    for i in range(2):
        reg.register(*tiny_data(i, 8))
    from repro.train import plan_round
    plan = plan_round(reg, [0, 1], 0, key, n_batches=nb, batch_size=4,
                      image_shape=(6, 6, 3), n_classes=4, drops={0: 0})
    m = np.asarray(plan.mask)
    assert m[:, 0, :].sum() == 0          # slot-0 member: zero cells
    assert m[:, 1, :].sum() > 0           # the other member trains


def test_sample_lags_bounds_and_addressing(key):
    """Lags land in {1..lag_max}, only for members whose TAG_LAG score
    clears lag_p, and one member's draw never depends on the roster."""
    cfg = ParticipationConfig(lag_p=1.0, lag_max=3)
    lags = sample_lags(cfg, key, 0, [0, 1, 2, 3, 4, 5, 6, 7])
    assert set(lags) == {0, 1, 2, 3, 4, 5, 6, 7}
    assert all(1 <= v <= 3 for v in lags.values())
    assert len(set(lags.values())) > 1          # spread across the range
    assert sample_lags(ParticipationConfig(lag_p=0.0), key, 0, [0]) == {}
    half = ParticipationConfig(lag_p=0.5, lag_max=2)
    small = sample_lags(half, key, 3, [0, 1, 2])
    big = sample_lags(half, key, 3, [0, 1, 2, 9])
    assert {u: v for u, v in big.items() if u != 9} == small
    # lag_max=1 forces every straggler exactly one round late
    one = sample_lags(ParticipationConfig(lag_p=1.0, lag_max=1), key, 0,
                      [0, 1, 2])
    assert set(one.values()) == {1}


# ---------------------------------------------------------------------------
# differential: cohort round vs the sequential eager oracle
# ---------------------------------------------------------------------------


def _cohort_fixture(key, cohort=(0, 2, 3), nb=2, B=4):
    pop = [{"a": jnp.float32(0.4 + 0.1 * c), "b": jnp.float32(0.01 * c)}
           for c in range(5)]
    rng = np.random.default_rng(7)
    m = len(cohort)
    xs = jnp.asarray(rng.normal(size=(nb, m, B, 6, 6, 3)).astype(np.float32))
    ys = jnp.zeros((nb, m, B, 4)).at[..., 0].set(1.0)
    mask = jnp.ones((nb, m, B), jnp.float32).at[1, 1, 2:].set(0.0)
    uids = np.asarray(cohort, np.int32)
    return pop, xs, ys, mask, uids


def test_cohort_round_matches_eager_oracle(key):
    """Engine (identity-keyed, ragged mask) vs train_round_reference with
    the same registry uids — same semantics, plain loops."""
    pop, xs, ys, mask, uids = _cohort_fixture(key)
    round_fn = make_vectorized_round(SCHED, CUT, tiny_apply, OPT,
                                     identity_keyed=True)
    cp = stack_clients([pop[u] for u in uids])
    co = stack_clients([init_opt_state(pop[u]) for u in uids])
    sp = {"a": jnp.float32(0.5), "b": jnp.float32(0.0)}
    cp2, co2, sp2, so2, _ = round_fn(cp, co, sp, init_opt_state(sp),
                                     xs, ys, mask, jnp.asarray(uids), key)
    ref = CollabState(
        server_params=dict(sp), server_opt=init_opt_state(sp),
        client_params=[dict(pop[u]) for u in uids],
        client_opt=[init_opt_state(pop[u]) for u in uids])
    train_round_reference(ref, xs, ys, key, SCHED, CUT, tiny_apply, OPT,
                          mask=mask, uids=uids)
    assert_trees_close(unstack_clients(cp2, 3), ref.client_params,
                       atol=1e-7, rtol=1e-6)
    assert_trees_close(sp2, ref.server_params, atol=1e-7, rtol=1e-6)
    assert_trees_close(unstack_clients(co2, 3), ref.client_opt,
                       atol=1e-7, rtol=1e-6)
    assert_trees_close(so2, ref.server_opt, atol=1e-7, rtol=1e-6)


def test_identity_vs_position_keying_differ(key):
    """Registry keying is real: seating uids (0,2,3) draws differently
    than position keying (0,1,2) would — the non-contiguous uid's stream
    follows its identity."""
    pop, xs, ys, mask, uids = _cohort_fixture(key)
    ident = make_vectorized_round(SCHED, CUT, tiny_apply, OPT,
                                  identity_keyed=True)
    pos = make_vectorized_round(SCHED, CUT, tiny_apply, OPT)
    cp = stack_clients([pop[u] for u in uids])
    co = stack_clients([init_opt_state(pop[u]) for u in uids])
    sp = {"a": jnp.float32(0.5), "b": jnp.float32(0.0)}
    a = ident(cp, co, sp, init_opt_state(sp), xs, ys, mask,
              jnp.asarray(uids), key)
    b = pos(cp, co, sp, init_opt_state(sp), xs, ys, mask, key)
    assert not trees_equal(a[0], b[0])
    # ...and arange uids reproduce position keying exactly
    c = ident(cp, co, sp, init_opt_state(sp), xs, ys, mask,
              jnp.arange(3, dtype=jnp.int32), key)
    assert trees_equal(c[0], b[0]) and trees_equal(c[2], b[2])


def test_identity_keyed_requires_mask():
    with pytest.raises(ValueError, match="identity_keyed"):
        make_vectorized_round(SCHED, CUT, tiny_apply, OPT, masked=False,
                              identity_keyed=True)


# ---------------------------------------------------------------------------
# BITWISE: tier padding is inert
# ---------------------------------------------------------------------------


def test_tier_padding_bitwise(key):
    """A cohort of 3 seated in a tier-4 (and tier-8) stack with all-masked
    pad slots is bitwise-identical to the unpadded run — params, moments,
    step counters — and the pad slots come back untouched."""
    pop, xs, ys, mask, uids = _cohort_fixture(key)
    round_fn = make_vectorized_round(SCHED, CUT, tiny_apply, OPT,
                                     identity_keyed=True)
    sp = {"a": jnp.float32(0.5), "b": jnp.float32(0.0)}
    cp = stack_clients([pop[u] for u in uids])
    co = stack_clients([init_opt_state(pop[u]) for u in uids])
    base = round_fn(cp, co, sp, init_opt_state(sp), xs, ys, mask,
                    jnp.asarray(uids), key)
    nb, m, B = mask.shape
    for tier in (4, 8):
        pad = tier - m
        xsP = jnp.concatenate([xs, jnp.zeros((nb, pad) + xs.shape[2:])], 1)
        ysP = jnp.concatenate([ys, jnp.zeros((nb, pad) + ys.shape[2:])], 1)
        maskP = jnp.concatenate([mask, jnp.zeros((nb, pad, B))], 1)
        uidsP = jnp.asarray(list(uids) + [int(uids[0])] * pad, jnp.int32)
        cpP = stack_clients([pop[u] for u in uids] + [pop[uids[0]]] * pad)
        coP = stack_clients([init_opt_state(pop[u]) for u in uids] +
                            [init_opt_state(pop[uids[0]])] * pad)
        out = round_fn(cpP, coP, sp, init_opt_state(sp), xsP, ysP, maskP,
                       uidsP, key)
        got_p = unstack_clients(out[0], tier)
        got_o = unstack_clients(out[1], tier)
        assert trees_equal(got_p[:m], unstack_clients(base[0], m)), tier
        assert trees_equal(got_o[:m], unstack_clients(base[1], m)), tier
        assert trees_equal(out[2], base[2]), tier       # server params
        assert trees_equal(out[3], base[3]), tier       # server opt
        for s in range(m, tier):                        # pads untouched
            assert trees_equal(got_p[s], pop[uids[0]]), (tier, s)
            assert int(got_o[s]["step"]) == 0, (tier, s)


# ---------------------------------------------------------------------------
# runtime loop: churn, signatures, absence, resume
# ---------------------------------------------------------------------------


def test_runtime_one_signature_per_tier(key):
    rt = make_runtime(key, sizes=[12, 8, 6, 12, 10],
                      participation=ParticipationConfig(
                          policy="bernoulli", p=0.6, drop_p=0.25))
    reps = rt.run(8)
    last = reps[-1]
    assert any(r["strict_subset"] and r["cohort_size"] for r in reps)
    assert last["max_signatures_per_tier"] == 1
    assert rt.traces == len(last["signatures_per_tier"])
    assert rt.total_steps > 0
    # seen counters track the mask exactly
    assert sum(r.seen for r in rt.registry.records()) == \
        sum(rep["real_samples"] for rep in reps)


def test_runtime_on_mesh_compiles_once_and_matches(key):
    """On a ``clients`` mesh the round compiles once for its tier (round 0
    sees an unplaced server net, later rounds the mesh-placed output of
    the last round) and trains exactly what the mesh-less runtime does."""
    from repro.sharding.specs import make_client_mesh
    sizes, full = [8, 8, 8, 8], ParticipationConfig(policy="full")
    plain = make_runtime(key, sizes=sizes, participation=full)
    meshed = TrainRuntime(tiny_config(participation=full), tiny_init,
                          tiny_apply, key, mesh=make_client_mesh(4))
    for i, n in enumerate(sizes):
        meshed.register_client(*tiny_data(i, n))
    plain.run(3)
    reps = meshed.run(3)
    assert meshed.traces == 1 and reps[-1]["engine_traces"] == 0
    assert trees_equal(meshed.server_params, plain.server_params)
    for u in plain.registry.uids():
        assert trees_equal(meshed.registry.get(u).params,
                           plain.registry.get(u).params), u


def test_runtime_absent_client_is_frozen(key):
    """A client that leaves keeps params/opt bitwise-frozen while away
    and trains again after rejoin."""
    rt = make_runtime(key, sizes=[10, 10, 10],
                      participation=ParticipationConfig(policy="full"))
    rt.run(1)
    frozen_p = jax.tree.map(jnp.copy, rt.registry.get(1).params)
    frozen_o = jax.tree.map(jnp.copy, rt.registry.get(1).opt)
    rt.leave(1)
    rt.run(3)
    assert trees_equal(rt.registry.get(1).params, frozen_p)
    assert trees_equal(rt.registry.get(1).opt, frozen_o)
    rt.rejoin(1)
    rt.run(1)
    assert not trees_equal(rt.registry.get(1).params, frozen_p)


def test_runtime_join_mid_run_and_empty_data(key):
    """Late joiners train from their join round on; a data-less client is
    masked out (zero seen), never a crash or NaN."""
    rt = make_runtime(key, sizes=[10, 10],
                      participation=ParticipationConfig(policy="full"),
                      fedavg_every=1)
    rt.run(2)
    uid = rt.register_client(*tiny_data(5, 9))      # joins at round 2
    empty = rt.register_client(None, None)          # registered, no data
    reps = rt.run(2)
    assert rt.registry.get(uid).seen > 0
    assert rt.registry.get(empty).seen == 0
    for rec in rt.registry.records():
        if rec.params is not None:
            assert np.isfinite(np.asarray(
                jax.tree.leaves(rec.params)[0])).all()
    assert reps[-1]["n_registered"] == 4


def test_runtime_resume_bitwise(key, tmp_path):
    """Interrupt after round 2 of 5, restore, finish — bitwise equal to
    the uninterrupted run (params, opt states, EMA, counters, RNG)."""
    kw = dict(sizes=[10, 6, 12],
              participation=ParticipationConfig(policy="bernoulli", p=0.7,
                                                drop_p=0.2),
              fedavg_every=2, ema_decay=0.9)
    full = make_runtime(key, **kw)
    full.run(5)
    half = make_runtime(key, **kw)
    half.run(2)
    path = str(tmp_path / "rt.msgpack")
    half.save(path)
    resumed = TrainRuntime.restore(
        tiny_config(participation=kw["participation"], fedavg_every=2,
                    ema_decay=0.9), tiny_init, tiny_apply, path)
    for i in range(3):
        resumed.attach_data(i, *tiny_data(i, kw["sizes"][i]))
    resumed.run(3)
    assert resumed.round == full.round
    assert resumed.total_steps == full.total_steps
    assert trees_equal(resumed.server_params, full.server_params)
    assert trees_equal(resumed.server_opt, full.server_opt)
    assert trees_equal(resumed.ema_server, full.ema_server)
    for u in full.registry.uids():
        assert trees_equal(resumed.registry.get(u).params,
                           full.registry.get(u).params), u
        assert trees_equal(resumed.registry.get(u).opt,
                           full.registry.get(u).opt), u
        assert resumed.registry.get(u).seen == full.registry.get(u).seen


def test_runtime_fedavg_skips_departed_member(key):
    """A client that trained early in a FedAvg window and then LEFT must
    not receive (or contribute to) the aggregation — departure freezes
    its net bitwise until rejoin, even across a window boundary."""
    rt = make_runtime(key, sizes=[10, 10, 10],
                      participation=ParticipationConfig(policy="full"),
                      fedavg_every=2)
    rt.run(1)                               # round 0: all three train
    frozen = jax.tree.map(jnp.copy, rt.registry.get(1).params)
    rt.leave(1)
    rt.run(1)                               # round 1 ends the window
    assert trees_equal(rt.registry.get(1).params, frozen)
    # the remaining members did aggregate (identical post-average nets)
    assert trees_equal(rt.registry.get(0).params,
                       rt.registry.get(2).params)
    assert not trees_equal(rt.registry.get(0).params, frozen)


def test_runtime_tier_cap_bounds_cohort(key):
    """tier_cap bounds the COHORT, not just the stack: 5 full-participation
    clients under tier_cap=2 train in rotating capped cohorts instead of
    crashing, and only capped tiers ever compile."""
    rt = make_runtime(key, sizes=[8] * 5,
                      participation=ParticipationConfig(policy="full"),
                      tier_cap=2)
    reps = rt.run(4)
    assert all(0 < r["cohort_size"] <= 2 for r in reps)
    assert all(r["tier"] <= 2 for r in reps)
    assert max(rt._sigs) <= 2
    # the capped selection rotates: over a few rounds more than one
    # distinct cohort appears (scores are round-keyed)
    assert len({tuple(r["cohort"]) for r in reps}) > 1


def test_runtime_dropout_shrinks_seen(key):
    """drop_p=1: every member drops mid-round, so seen counts stay below
    the no-dropout run's — and nothing NaNs."""
    kw = dict(sizes=[12, 12], batches_per_round=3)
    a = make_runtime(key, participation=ParticipationConfig(
        policy="full", drop_p=0.0), **kw)
    b = make_runtime(key, participation=ParticipationConfig(
        policy="full", drop_p=1.0), **kw)
    a.run(3)
    b.run(3)
    seen_a = sum(r.seen for r in a.registry.records())
    seen_b = sum(r.seen for r in b.registry.records())
    assert seen_b < seen_a
    assert np.isfinite(float(b.server_params["a"]))


def test_runtime_ema_track(key):
    rt = make_runtime(key, sizes=[8],
                      participation=ParticipationConfig(policy="full"),
                      ema_decay=0.5)
    s0 = jax.tree.map(jnp.copy, rt.server_params)
    rt.run(1)
    want = jax.tree.map(lambda e, p: 0.5 * e + 0.5 * p, s0,
                        rt.server_params)
    assert_trees_close(rt.ema_server, want, atol=0, rtol=0)
    assert rt.sampling_server_params() is rt.ema_server


def test_whole_cohort_dropout_round(key, monkeypatch):
    """The degenerate round async mode hits constantly: EVERY member
    drops at slot 0 (connected, instantly gone).  plan_round must bail
    to an empty round — finite losses, registry bitwise-untouched, and
    a clean pass through fedavg.average_cohort's zero-seen guard."""
    import repro.train.runtime as rt_mod
    rt = make_runtime(key, sizes=[10, 8, 12],
                      participation=ParticipationConfig(policy="full",
                                                        drop_p=1.0),
                      fedavg_every=1)
    before = {u: (jax.tree.map(jnp.copy, rt.registry.get(u).params),
                  jax.tree.map(jnp.copy, rt.registry.get(u).opt))
              for u in rt.registry.uids()}
    monkeypatch.setattr(rt_mod, "sample_drops",
                        lambda cfg, k, r, cohort, nb: {int(u): 0
                                                       for u in cohort})
    rep = rt.run_round()
    assert rep["cohort_size"] == 3 and rep["mid_round_drops"] == 3
    assert rep["real_samples"] == 0 and rep["tier"] == 0
    assert np.isfinite(rep["client_loss"]) and rep["client_loss"] == 0.0
    assert not rep["fedavg_applied"]            # zero-seen guard: no-op
    assert rt.round == 1                        # cursor still advances
    for u, (p, o) in before.items():
        assert trees_equal(rt.registry.get(u).params, p), u
        assert trees_equal(rt.registry.get(u).opt, o), u
        assert rt.registry.get(u).seen == 0


# ---------------------------------------------------------------------------
# async (staleness-tolerant) aggregation — PR 6
# ---------------------------------------------------------------------------

LAGGY = dict(policy="bernoulli", p=0.7, drop_p=0.2)


def _async_pair(key, sync_kw=None, async_kw=None, **common):
    """Twin runtimes differing only in aggregation mode."""
    a = make_runtime(key, async_mode=True, **(async_kw or {}), **common)
    s = make_runtime(key, async_mode=False, **(sync_kw or {}), **common)
    return a, s


def _registry_state(rt):
    return ([(u, rt.registry.get(u).params, rt.registry.get(u).opt,
              rt.registry.get(u).seen) for u in rt.registry.uids()],
            rt.server_params, rt.server_opt)


def _assert_bitwise(rt_a, rt_b):
    (ca, spa, soa), (cb, spb, sob) = _registry_state(rt_a), \
        _registry_state(rt_b)
    assert trees_equal(spa, spb) and trees_equal(soa, sob)
    for (u, p, o, seen), (u2, p2, o2, seen2) in zip(ca, cb):
        assert u == u2 and seen == seen2, (u, seen, seen2)
        assert trees_equal(p, p2), u
        assert trees_equal(o, o2), u


def test_async_without_lag_is_bitwise_sync(key):
    """Rung 1 of the bitwise ladder: lag_p=0 ⇒ the async machinery is
    inert and every quantity matches sync exactly."""
    common = dict(sizes=[10, 6, 12],
                  participation=ParticipationConfig(**LAGGY),
                  fedavg_every=2, ema_decay=0.9)
    a, s = _async_pair(key, **common)
    ra = a.run(5)
    rs = s.run(5)
    assert a._pending == []
    _assert_bitwise(a, s)
    assert all(r["stragglers"] == 0 and r["stale_merges"] == 0
               for r in ra + rs)


def test_async_full_weight_lag1_drain_is_bitwise_sync(key):
    """Rung 2: every payload exactly one round late (lag_max=1) at full
    merge weight (stale_alpha=1 ⇒ w=1 ⇒ payload returned AS-IS), FedAvg
    off so nothing reads the registry between upload and delivery —
    after drain() the async run equals sync bitwise."""
    part = ParticipationConfig(lag_p=0.6, lag_max=1, **LAGGY)
    common = dict(sizes=[10, 6, 12], participation=part)
    a, s = _async_pair(key, async_kw=dict(stale_alpha=1.0), **common)
    ra = a.run(6)
    s.run(6)
    assert sum(r["stragglers"] for r in ra) > 0   # injection really fired
    assert sum(r["stale_merges"] for r in ra) > 0
    a.drain()
    _assert_bitwise(a, s)


def test_async_tolerance_vs_sync(key):
    """Rung 3 (the documented tolerance): general staleness-weighted
    merging deviates from the sync trajectory, but on the smoke-scale
    workload the final params stay within atol 5e-2 (the bound stated in
    train/runtime.py's module docstring) and everything stays finite."""
    part = ParticipationConfig(lag_p=0.5, lag_max=2, **LAGGY)
    common = dict(sizes=[10, 6, 12], participation=part, fedavg_every=2)
    a, s = _async_pair(key, **common)
    ra = a.run(8)
    s.run(8)
    merged = a.drain()
    n_straggled = sum(r["stragglers"] for r in ra)
    assert n_straggled > 0
    # every enqueued payload lands exactly once (in-round or at drain);
    # a straggler that trained zero real cells never enqueues, so <=
    assert 0 < sum(r["stale_merges"] for r in ra) + merged <= n_straggled
    assert a._pending == []
    for (u, p, o, _), pa in zip(_registry_state(a)[0],
                                _registry_state(s)[0]):
        for x, y in zip(jax.tree.leaves(p), jax.tree.leaves(pa[1])):
            assert np.isfinite(np.asarray(x)).all()
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       atol=5e-2)
    for x, y in zip(jax.tree.leaves(a.server_params),
                    jax.tree.leaves(s.server_params)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=5e-2)


def test_async_busy_client_sits_out(key):
    """While a straggler's upload is in flight its uid must not be
    sampled into a cohort (its net is wherever its upload is)."""
    part = ParticipationConfig(policy="full", lag_p=1.0, lag_max=2)
    rt = make_runtime(key, sizes=[8, 8], async_mode=True,
                      participation=part)
    r0 = rt.run_round()
    assert r0["stragglers"] == 2 and r0["pending_payloads"] == 2
    busy = {p["uid"] for p in rt._pending}
    r1 = rt.run_round()
    assert not busy.intersection(r1["cohort"])
    total = sum(r["stale_merges"] for r in [rt.run_round()
                                            for _ in range(3)])
    assert total > 0                      # uploads eventually land


def test_async_leave_discards_orphaned_payload(key):
    """Regression (PR 9): a uid that leaves with a stale upload in
    flight and later REJOINS must not receive the orphaned payload —
    ``leave`` discards the uid's pending entries at departure, so the
    rejoined client's frozen net stays bitwise-untouched until it
    trains again."""
    part = ParticipationConfig(policy="full", lag_p=1.0, lag_max=2)
    rt = make_runtime(key, sizes=[8, 8], async_mode=True,
                      participation=part)
    rt.run_round()
    assert {int(p["uid"]) for p in rt._pending} == {0, 1}
    frozen = jax.tree.map(jnp.copy, rt.registry.get(0).params)
    rt.leave(0)
    # the orphan is dropped at departure, not parked until delivery
    assert {int(p["uid"]) for p in rt._pending} == {1}
    rt.rejoin(0)                               # rejoin BEFORE the due round
    # the rejoined record is the frozen departed net, bitwise — rejoin
    # reactivates, it does not reinitialise or deliver anything
    assert trees_equal(rt.registry.get(0).params, frozen)
    # run well past the orphan's would-be due round (computed round 0,
    # lag <= 2): any payload uid 0 ever holds in flight from here on was
    # computed AFTER the rejoin — the orphan never reappears
    enqueued, merged = 2, 0
    for _ in range(4):
        rep = rt.run_round()
        enqueued += rep["stragglers"]
        merged += rep["stale_merges"]
        assert all(int(p["compute_round"]) >= 1
                   for p in rt._pending if int(p["uid"]) == 0)
    merged += rt.drain()
    # conservation: every upload lands exactly once EXCEPT the orphan,
    # which was dropped at leave() — neither delivered nor duplicated
    assert merged == enqueued - 1


def test_async_resume_bitwise_with_pending(key, tmp_path):
    """State-dict v2 carries the pending queue: interrupt with uploads
    in flight, restore, finish, drain — bitwise equal to the
    uninterrupted async run."""
    part = ParticipationConfig(lag_p=0.8, lag_max=3, **LAGGY)
    kw = dict(sizes=[10, 6, 12], participation=part, async_mode=True,
              fedavg_every=2, ema_decay=0.9)
    full = make_runtime(key, **kw)
    full.run(6)
    half = make_runtime(key, **kw)
    half.run(3)
    assert half._pending                       # interrupt mid-flight
    path = str(tmp_path / "rt_async.msgpack")
    half.save(path)
    resumed = TrainRuntime.restore(
        tiny_config(participation=part, async_mode=True, fedavg_every=2,
                    ema_decay=0.9), tiny_init, tiny_apply, path)
    for i in range(3):
        resumed.attach_data(i, *tiny_data(i, kw["sizes"][i]))
    assert len(resumed._pending) == len(half._pending)
    resumed.run(3)
    full.drain()
    resumed.drain()
    assert resumed.round == full.round
    _assert_bitwise(resumed, full)
    assert trees_equal(resumed.ema_server, full.ema_server)


def test_v1_checkpoint_still_restores(key, tmp_path):
    """Backward compatibility: a version-1 state dict (no pending queue)
    restores into an empty queue instead of erroring."""
    rt = make_runtime(key, sizes=[8],
                      participation=ParticipationConfig(policy="full"))
    rt.run(1)
    state = rt.state_dict()
    state["version"] = 1
    del state["pending"]
    from repro.checkpointing import checkpoint as ckpt
    path = str(tmp_path / "v1.msgpack")
    ckpt.save(path, state)
    restored = TrainRuntime.restore(tiny_config(), tiny_init, tiny_apply,
                                    path)
    assert restored._pending == []
    assert restored.round == rt.round
    with pytest.raises(ValueError, match="version"):
        state["version"] = 99
        ckpt.save(path, state)
        TrainRuntime.restore(tiny_config(), tiny_init, tiny_apply, path)


def test_sync_straggler_barrier_is_pure_wall_clock(key):
    """Sync mode with straggler injection is TODAY's semantics plus a
    stall: every quantity bitwise-equals the lag-free run, and the
    report shows the barrier paying max-lag wall seconds."""
    part_lag = ParticipationConfig(lag_p=0.8, lag_max=2, **LAGGY)
    part_free = ParticipationConfig(**LAGGY)
    kw = dict(sizes=[10, 6, 12], fedavg_every=2)
    lagged = make_runtime(key, participation=part_lag, lag_s=0.002, **kw)
    free = make_runtime(key, participation=part_free, **kw)
    rl = lagged.run(4)
    free.run(4)
    _assert_bitwise(lagged, free)
    assert sum(r["stragglers"] for r in rl) > 0
    assert sum(r["barrier_stall_s"] for r in rl) > 0.0
    assert all(r["pending_payloads"] == 0 for r in rl)
