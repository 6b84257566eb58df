"""CollaFuse federated training runtime — persistent Alg.-1 training
under partial participation.  Design notes (the training counterpart of
serve/runtime.py's, and the mirror image of its queue→engine loop):

* **Registry → participation sampler → round plan → engine →
  aggregation → telemetry/checkpoint.**  ``TrainRuntime`` is constructed
  once and runs rounds forever: clients ``register_client``/``leave`` at
  any time (control plane, between rounds); each ``run_round`` samples a
  cohort from the ACTIVE registry (train/participation.py: full /
  bernoulli / fixed-k, plus mid-round dropout), plans it into padded
  fixed-shape stacks (train/rounds.py), runs ONE jitted masked round
  (core/collab.make_vectorized_round(identity_keyed=True)), scatters the
  cohort's updated nets back into the registry, applies the optional
  cross-cohort FedAvg and server-EMA aggregation, and emits a round
  report.  ``run`` loops rounds with periodic durable checkpoints.
* **One compiled signature per participation TIER.**  Cohorts are padded
  along the CLIENT axis to power-of-two tiers with fully-masked slots —
  the client-axis extension of PR 2's row/batch masking.  Batch count
  and batch size are pinned by the config, so a round's jit signature
  depends only on its tier and drifting cohort sizes converge onto the
  tier menu instead of one compile per size.  A python trace counter on
  the jitted engine (incremented only when jit re-traces) is the
  recompile guard; the CI smoke asserts exactly one signature per tier.
* **Identity keying makes participation a pure policy knob.**  Every
  per-client draw is keyed by REGISTRY uid, not stack seat
  (protocol.client_keys), every per-sample draw is row-keyed below that
  (splitting.row_keys), and every runtime purpose folds its own stream
  tag into the ONE base key (participation.TAG_*) — randomness is
  addressed, never chained.  Consequences, pinned by
  tests/test_train_runtime.py: a cohort-of-3 round padded to tier 4 is
  BITWISE equal to the unpadded run (params, opt states, metrics); a
  masked slot is a bitwise no-op (absent clients' nets, moments, and
  step counters are untouched, via the where-skipped AdamW); and cohort
  membership changes never perturb a non-member.  The vectorized round
  is additionally differential-tested against the sequential eager
  oracle (``train_round_reference(uids=)``) at the repo's established
  oracle tolerance.
* **Bitwise mid-run resume.**  ``state_dict``/``save`` persist the FULL
  resumable state — server params/opt, per-client params/opt, registry
  metadata (uids, counters, membership), the cohort cursor, the base
  PRNG key, and the EMA track — through checkpointing/checkpoint.py
  (atomic + fsync'd).  Because all randomness is addressed by
  (base key, tag, round, uid), a run interrupted after round j and
  resumed from its checkpoint replays rounds j+1..n bitwise-identically
  to the uninterrupted run: same cohorts, same drops, same batches, same
  updates (asserted by the CI smoke and tests).  Client DATA is never
  checkpointed (split-learning premise): drivers re-attach each uid's
  local dataset on resume.
* **Aggregation closes the loop to sampling.**  Optional cross-cohort
  FedAvg (``fedavg_every``) averages the cohort members' client nets
  size-weighted by their real trained-sample counts
  (core/fedavg.average_cohort — zero-seen members are weight-guarded,
  absent clients are no-ops), and a server-parameter EMA track
  (``ema_decay``) maintains the smoothed server net that sampling/serve
  should load (``sampling_server_params``).
* **Sharding.**  The runtime is mesh-agnostic; pass ``mesh`` to place
  each round's operands: the round stacks with the cohort specs
  (sharding/specs.shard_cohort_round), the stacked client nets and their
  AdamW state one client per device (shard_client_stack) — both with
  the client axis over "clients" — and the server net replicated.
  launch/collab_dryrun.py's
  ``train_runtime`` entry compiles the identity-keyed cohort round on
  the ("clients", "data") mesh.
* **Async (staleness-tolerant) aggregation — the round barrier falls.**
  Stragglers are injected via the addressed ``TAG_LAG`` stream
  (participation.sample_lags: member straggles with prob ``lag_p``, its
  upload arrives 1..``lag_max`` rounds late).  A straggler still
  COMPUTES its round (the split protocol's server phase holds the
  activations in-round, so the server net always updates on time); only
  the CLIENT-NET upload is late.  ``async_mode=False`` (sync, the
  barrier): the round blocks ``lag_s``·max-lag wall seconds waiting for
  the slowest upload, then applies every payload — semantics identical
  to a lag-free run, just slower.  ``async_mode=True``: late payloads
  are queued and folded in at their arrival round with the
  staleness-decayed weight of core/fedavg.average_stale
  (w = stale_alpha·(1+s)^−stale_decay, FedAsync-style); a busy client
  (upload outstanding) sits out cohort sampling until it lands, and
  ``drain()`` flushes the queue at run end.  Delivery order is
  deterministic (due round, compute round, uid) and the queue
  checkpoints/restores bitwise (state_dict v2).  A client that LEAVES
  discards its outstanding payloads at departure — an orphaned upload
  must never reach the record after a rejoin (pinned by
  tests/test_train_runtime.py).
* **Privacy (DP-FedAvg + secagg) — what the server sees.**  With
  ``TrainConfig(privacy=PrivacyConfig(clip, noise_multiplier, delta,
  secagg))`` enabled, the cross-cohort aggregation boundary
  (``fedavg_every`` — required > 0) switches from
  ``fedavg.average_cohort`` to privacy/dp.py's ``dp_average_cohort``:
  each contributing member's window UPDATE (its net minus the broadcast
  reference ``_dp_ref``) is clipped to ``clip`` in global L2 and summed
  at weight 1 (unweighted — sample-count weights would leak and break
  the C-sensitivity bound); Gaussian noise with std
  ``noise_multiplier·clip`` is added to the SUM (addressed draw:
  ``fold_in(base, TAG_DP, round, uid=0)``, per-leaf fold-ins below);
  the noised mean becomes the new broadcast reference every member
  adopts.  CLIPPING BINDS on the per-member window delta — never on raw
  nets, never per-layer.  With ``secagg`` on, member uploads travel as
  pairwise-masked fixed-point words (privacy/secagg.py) and the server
  provably sees ONLY the sum: masks cancel bitwise in the exact integer
  ring, so secagg on/off is bitwise-identical at the aggregate, and a
  member that left after training is recovered as a SecAgg dropout
  (its pair masks reconstructed and removed).  THE ACCOUNTANT
  (privacy/accountant.py) counts one subsampled-Gaussian release per
  APPLIED DP aggregation at the window-composed sampling rate
  q_window = 1-(1-q)^fedavg_every (q from participation.sampling_rate);
  cumulative ε is in every round report (``dp_epsilon``, monotone
  non-decreasing) and in checkpoint format v3 (v1/v2 still restore,
  with fresh privacy state).  Each applied release bumps ``dp_epoch``
  and fires ``on_dp_epoch`` — serve/runtime.py's ``rotate_for_epoch``
  ties payload-cache key rotation to exactly this boundary.  The
  identity ladder is STRUCTURAL: a disabled PrivacyConfig routes
  through the legacy ``average_cohort`` path untouched, so
  ``clip=inf, noise=0, secagg=off`` is bitwise-equal to the
  pre-privacy runtime (pinned by tests/test_privacy.py and the CI
  smoke).

* **Observability (obs tentpole).**  Round reports are DERIVED VIEWS
  over the shared metrics registry (repro.obs): every report key is
  classified delta-vs-gauge in ``_TRAIN_REPORT_SCHEMA`` (enforced by
  tests/test_obs.py's conformance test), live runtime state (cursor,
  roster, pending queue, privacy ledger) is exposed through callback
  gauges, per-round counters mirror into monotone registry Counters,
  and the jit trace counter is the shared ``RecompileGuard``.  With an
  active ObsConfig each round is one report FRAME and one "round" span
  decomposed into cohort_sample / plan / round_dispatch /
  barrier_stall / fedavg children (plus a "checkpoint" span in
  ``run``), streamed to the JSONL/Perfetto sinks.  The obs contract is
  the serve runtime's exactly: disabled (default) is structurally
  inert — NullTracer singleton, zero span allocations, no sink IO,
  reports and params bitwise-identical to the pre-obs runtime; enabled
  never perturbs training — params/opt/cohorts bitwise-identical with
  ZERO new jit signatures (pinned by the collab_train --smoke obs
  pass).

Reproducibility contract (sync vs async): SYNC mode is bitwise — for a
given base key and registry history every quantity (params, opt,
cohorts, losses) is reproducible to the bit, straggler injection or
not, and equals the lag-free run's exactly; pinned by
tests/test_train_runtime.py's differential tests.  ASYNC mode is
bitwise-deterministic (same config ⇒ same bits, including resume) but
deviates from the sync trajectory once a payload lands late; the
deviation is bounded on the smoke workload — final client/server
params within atol 5e-2 of the sync run (pinned by
``test_async_tolerance_vs_sync``), and collapses back to bitwise
equality when no payload is ever late (lag_p=0) or when every payload
lands one round late at full weight (lag_max=1, stale_alpha=1,
fedavg off, after ``drain()``) — the bitwise ladder the tests walk.

Remaining open (ROADMAP): multi-host cohorts, server-side momentum on
stale merges, adaptive staleness weights from observed lag
distributions.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpointing import checkpoint as ckpt
from repro.core.collab import make_vectorized_round, stack_clients, \
    unstack_clients
from repro.core.fedavg import average_cohort, average_stale
from repro.core.schedules import DiffusionSchedule
from repro.core.splitting import CutPoint
from repro.obs import DELTA, GAUGE, ObsConfig, RecompileGuard, Telemetry
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.privacy.accountant import RdpAccountant
from repro.privacy.dp import TAG_DP, PrivacyConfig, dp_average_cohort
from repro.train.participation import (TAG_INIT, TAG_PART, TAG_ROUND,
                                       ParticipationConfig, sample_cohort,
                                       sample_drops, sample_lags,
                                       sampling_rate, uid_scores)
from repro.train.registry import ClientRegistry
from repro.train.rounds import plan_round

# Delta-vs-gauge classification of every train report key (enforced by
# the registry + the conformance test in tests/test_obs.py).  DELTA keys
# describe THIS round only; GAUGE keys are absolute runtime state at
# report time (cursor, roster, privacy ledger) and must never be summed
# across rounds.
_TRAIN_REPORT_SCHEMA = {
    "round": GAUGE, "n_registered": GAUGE, "n_active": GAUGE,
    "cohort": DELTA, "cohort_size": DELTA, "strict_subset": DELTA,
    "tier": DELTA, "padded_client_slots": DELTA,
    "real_samples": DELTA, "padded_cells": DELTA, "pad_waste_frac": DELTA,
    "mid_round_drops": DELTA, "engine_traces": DELTA,
    "signatures_per_tier": GAUGE, "max_signatures_per_tier": GAUGE,
    "client_loss": DELTA, "server_loss": DELTA,
    "fedavg_applied": DELTA, "seen_total": GAUGE, "wall_s": DELTA,
    "stragglers": DELTA, "stale_merges": DELTA, "barrier_stall_s": DELTA,
    "pending_payloads": GAUGE,
    "dp_epsilon": GAUGE, "dp_epoch": GAUGE, "dp_clip_frac": GAUGE,
}


def _key_pack(key) -> Dict[str, Any]:
    """Checkpointable form of a PRNG key (raw uint32 or typed)."""
    typed = jnp.issubdtype(key.dtype, jax.dtypes.prng_key)
    return {"data": np.asarray(jax.random.key_data(key)),
            "typed": bool(typed)}


def _key_unpack(packed) -> Any:
    data = jnp.asarray(packed["data"])
    return jax.random.wrap_key_data(data) if packed["typed"] else data


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    T: int
    t_cut: int
    image_shape: Tuple[int, int, int]       # (H, W, C)
    n_classes: int
    batch_size: int = 8
    batches_per_round: int = 4              # fixed nb — shape stability
    lr: float = 1e-3
    schedule: str = "linear"
    participation: ParticipationConfig = ParticipationConfig()
    privacy: PrivacyConfig = PrivacyConfig()  # neutral default: disabled
    fedavg_every: int = 0                   # 0 = off
    ema_decay: float = 0.0                  # 0 = off
    tier_cap: Optional[int] = None          # cap on the pow2 cohort tier
    async_mode: bool = False                # True ⇒ staleness-tolerant agg
    stale_alpha: float = 0.6                # async merge weight at s=0
    stale_decay: float = 0.5                # polynomial staleness decay
    lag_s: float = 0.0                      # wall seconds per lag round
                                            # (the sync barrier's stall)

    def cut(self) -> CutPoint:
        return CutPoint(self.T, self.t_cut)

    def sched(self) -> DiffusionSchedule:
        mk = (DiffusionSchedule.linear if self.schedule == "linear"
              else DiffusionSchedule.cosine)
        return mk(self.T)


class TrainRuntime:
    """The persistent federated training loop.  Construct once, register
    clients, ``run`` rounds forever; the registry, compiled signatures,
    counters, and EMA persist across calls (that persistence IS the
    subsystem)."""

    def __init__(self, config: TrainConfig, init_one, apply_fn, key,
                 mesh=None, obs=None):
        self.config = config
        self.sched = config.sched()
        self.cut = config.cut()
        self._init_one = init_one
        self._apply_fn = apply_fn
        self._key = key
        self.mesh = mesh
        self.registry = ClientRegistry()
        # -- observability: metrics registry (always live — reports and
        # sinks derive from it), tracer + sinks (only when active).  The
        # round report keys are classified delta-vs-gauge up front; the
        # runtime's live state is exposed through callback gauges so a
        # JSONL frame always carries the current cursor/roster/ledger.
        self._obs = obs if isinstance(obs, Telemetry) \
            else Telemetry(obs if isinstance(obs, ObsConfig) else None)
        self._clock = self._obs.clock
        self.metrics = self._obs.registry
        self.metrics.declare_all(_TRAIN_REPORT_SCHEMA)
        self._c = {name: self.metrics.counter(name) for name in (
            "rounds", "real_samples", "padded_cells", "mid_round_drops",
            "stragglers", "stale_merges")}
        self.metrics.gauge("round", fn=lambda: self.round)
        self.metrics.gauge("n_registered", fn=lambda: len(self.registry))
        self.metrics.gauge("n_active",
                           fn=lambda: len(self.registry.active_uids()))
        self.metrics.gauge("pending_payloads",
                           fn=lambda: len(self._pending))
        self.metrics.gauge("seen_total", fn=lambda: sum(
            r.seen for r in self.registry.records()))
        self.metrics.gauge("dp_epoch", fn=lambda: self.dp_epoch)
        self.metrics.gauge("dp_epsilon", fn=lambda: (
            0.0 if self._accountant is None
            else float(self._accountant.epsilon())))
        self.round = 0                       # cohort cursor
        self.total_steps = 0                 # real (client, batch) cells
        self._sigs: Dict[int, set] = {}      # tier -> signatures seen
        # outstanding straggler uploads (async mode): each entry is
        # {uid, params, opt, compute_round, due_round, n_real} — ordered
        # deterministically at delivery, checkpointed in state_dict v2
        self._pending: List[Dict] = []
        # -- privacy state (see the DP/secagg design note above) --------
        self.dp_epoch = 0                    # applied DP releases so far
        self.on_dp_epoch = None              # callback(epoch) per release
        self._dp_clip_frac = 0.0             # last release's clip fraction
        if config.privacy.enabled:
            if not config.fedavg_every:
                raise ValueError(
                    "privacy is enforced at the cross-cohort aggregation "
                    "boundary: PrivacyConfig enabled requires "
                    "fedavg_every > 0")
            self._accountant = RdpAccountant(
                config.privacy.noise_multiplier, config.privacy.delta)
            # the broadcast reference deltas are clipped against —
            # addressed init (TAG_DP slot 0), updated to each release's
            # noised mean, checkpointed in format v3
            self._dp_ref = init_one(
                jax.random.fold_in(jax.random.fold_in(key, TAG_DP), 0))
        else:
            self._accountant = None
            self._dp_ref = None
        self.server_params = init_one(
            jax.random.fold_in(jax.random.fold_in(key, TAG_INIT), 0))
        self.server_opt = init_opt_state(self.server_params)
        self.ema_server = (jax.tree.map(jnp.copy, self.server_params)
                           if config.ema_decay > 0.0 else None)

        raw = make_vectorized_round(self.sched, self.cut, apply_fn,
                                    AdamWConfig(lr=config.lr), masked=True,
                                    identity_keyed=True, jit=False)

        # the shared RecompileGuard (obs/metrics.py): its body runs only
        # when jit (re-)traces — a new (tier, nb, B) signature — so the
        # counter is the compile guard the CI smoke asserts on (steady
        # cohort churn: zero)
        self._guard = RecompileGuard(self.metrics.counter("engine_traces"))
        self._engine = jax.jit(self._guard.wrap(raw))
        self._obs.meta(runtime="train", T=config.T, t_cut=config.t_cut,
                       fedavg_every=config.fedavg_every,
                       async_mode=config.async_mode,
                       privacy=config.privacy.enabled)

    @property
    def traces(self) -> int:
        """Lifetime engine re-trace (XLA compile) count — the shared
        RecompileGuard's counter."""
        return self._guard.count

    @property
    def obs(self) -> Telemetry:
        """The runtime's telemetry bundle (registry + tracer + sinks).
        Long-lived drivers call ``obs.close()`` at shutdown to flush the
        JSONL stream / Perfetto trace / profiler session."""
        return self._obs

    # -- control plane -----------------------------------------------------
    def register_client(self, x=None, y=None, uid: Optional[int] = None
                        ) -> int:
        """Admit a client: permanent uid, identity-keyed fresh net.  The
        init key is ``fold_in(fold_in(base, TAG_INIT), 1 + uid)`` (slot 0
        is the server), so a client's init depends only on its identity —
        join order and roster size never matter."""
        uid = self.registry.register(x=x, y=y, uid=uid,
                                     joined_round=self.round)
        rec = self.registry.get(uid)
        ik = jax.random.fold_in(
            jax.random.fold_in(self._key, TAG_INIT), 1 + uid)
        rec.params = self._init_one(ik)
        rec.opt = init_opt_state(rec.params)
        return uid

    def leave(self, uid: int) -> None:
        """Deactivate a client.  Any outstanding straggler payload of its
        is DISCARDED here, not merely skipped at delivery: a uid that
        leaves and later rejoins must never receive (or be corrupted by)
        an upload computed before it left — the orphan would otherwise
        sit in the queue and pass the ``active`` check after the rejoin.
        Pinned by tests/test_train_runtime.py."""
        self.registry.leave(uid)
        self._pending = [p for p in self._pending
                         if int(p["uid"]) != int(uid)]

    def rejoin(self, uid: int) -> None:
        self.registry.rejoin(uid)

    def attach_data(self, uid: int, x, y) -> None:
        self.registry.attach_data(uid, x, y)

    # -- reporting ---------------------------------------------------------
    def _empty_report(self) -> Dict:
        """Zeroed report with the FULL key set — empty rounds must not
        change the schema consumers sum over."""
        return {
            "round": self.round, "n_registered": len(self.registry),
            "n_active": len(self.registry.active_uids()),
            "cohort": [], "cohort_size": 0, "strict_subset": False,
            "tier": 0, "padded_client_slots": 0,
            "real_samples": 0, "padded_cells": 0, "pad_waste_frac": 0.0,
            "mid_round_drops": 0, "engine_traces": 0,
            "signatures_per_tier": {t: len(s)
                                    for t, s in sorted(self._sigs.items())},
            "max_signatures_per_tier": max(
                (len(s) for s in self._sigs.values()), default=0),
            "client_loss": 0.0, "server_loss": 0.0,
            "fedavg_applied": False, "seen_total": 0, "wall_s": 0.0,
            "stragglers": 0, "stale_merges": 0, "barrier_stall_s": 0.0,
            "pending_payloads": len(self._pending),   # gauge, not delta
            # privacy gauges (0.0/0 schema constants while disabled)
            "dp_epsilon": 0.0, "dp_epoch": 0, "dp_clip_frac": 0.0,
        }

    def _dp_report(self) -> Dict:
        """Per-round privacy gauges: cumulative ε at the configured δ
        (monotone non-decreasing — the accountant only accumulates),
        the DP epoch counter, and the last release's clip fraction."""
        if self._accountant is None:
            return {"dp_epsilon": 0.0, "dp_epoch": 0, "dp_clip_frac": 0.0}
        return {"dp_epsilon": float(self._accountant.epsilon()),
                "dp_epoch": int(self.dp_epoch),
                "dp_clip_frac": float(self._dp_clip_frac)}

    # -- async delivery ----------------------------------------------------
    def _deliver(self, payload: Dict, delivery_round: int) -> bool:
        """Fold one late upload into its client's record at the
        staleness-decayed weight.  The client's OPT state is replaced
        wholesale (it is client-owned and travels with the upload); only
        params are mixed.  Returns False when the client left while its
        upload was in flight — departure freezes the record (registry
        contract), so the payload is discarded."""
        rec = self.registry.get(int(payload["uid"]))
        if not rec.active:
            return False
        s = max(int(delivery_round) - int(payload["compute_round"]) - 1, 0)
        rec.params = average_stale(rec.params, payload["params"], s,
                                   self.config.stale_alpha,
                                   self.config.stale_decay)
        rec.opt = payload["opt"]
        n_real = int(payload["n_real"])
        rec.seen += n_real
        rec.window_seen += n_real
        rec.window_member = True
        return True

    @staticmethod
    def _delivery_order(p: Dict) -> tuple:
        return (int(p["due_round"]), int(p["compute_round"]),
                int(p["uid"]))

    def _deliver_due(self) -> int:
        """Merge every pending payload whose due round has arrived, in
        deterministic (due round, compute round, uid) order."""
        due = [p for p in self._pending
               if int(p["due_round"]) <= self.round]
        if not due:
            return 0
        self._pending = [p for p in self._pending
                         if int(p["due_round"]) > self.round]
        return sum(int(self._deliver(p, self.round))
                   for p in sorted(due, key=self._delivery_order))

    def drain(self) -> int:
        """Flush every outstanding straggler payload NOW — the end-of-run
        step that makes an async run's final registry state include all
        computed work.  Payloads not yet due merge at the staleness their
        due round implies (as if they had arrived on time); returns the
        number merged."""
        pending, self._pending = self._pending, []
        return sum(
            int(self._deliver(p, max(self.round, int(p["due_round"]))))
            for p in sorted(pending, key=self._delivery_order))

    # -- the loop ----------------------------------------------------------
    def run_round(self) -> Dict:
        """One federated round: deliver due async payloads → sample
        cohort → plan → one engine call → scatter-back (stragglers
        enqueue instead, async mode) → aggregate → report.  Advances the
        cohort cursor even when the round is empty (no active client, no
        data), so the round→randomness mapping never depends on data
        availability.

        With obs enabled each round is one report FRAME over the metrics
        registry and one "round" span decomposed into cohort_sample /
        plan / round_dispatch / barrier_stall / fedavg children (the
        checkpoint span lives in ``run``); disabled, the NullTracer
        makes all of it structurally inert."""
        t0 = self._clock()
        cfg = self.config
        tr = self._obs.tracer
        snap = self.metrics.snapshot()
        rspan = tr.start("round", round=self.round)
        self._obs.step()
        with tr.span("cohort_sample", parent=rspan):
            stale_merges = self._deliver_due() if self._pending else 0
            active = self.registry.active_uids()
            busy = {int(p["uid"]) for p in self._pending}
            if busy:
                # a client whose upload is still in flight sits the round
                # out — it can't also train (its net is wherever its
                # upload is)
                active = [u for u in active if u not in busy]
            cohort = sample_cohort(cfg.participation, self._key,
                                   self.round, active)
            if cfg.tier_cap is not None and len(cohort) > cfg.tier_cap:
                # the cap bounds the compiled cohort axis, so it must
                # bound the cohort itself: keep the tier_cap members with
                # the smallest participation scores (same addressed draw
                # the sampler used — deterministic, identity-keyed, fair
                # across rounds), overflow members sit this round out
                scores = uid_scores(self._key, TAG_PART, self.round,
                                    cohort)
                order = np.lexsort((np.asarray(cohort), scores))
                cohort = sorted(int(cohort[i])
                                for i in order[:cfg.tier_cap])
            drops = sample_drops(cfg.participation, self._key, self.round,
                                 cohort, cfg.batches_per_round)
            lags = sample_lags(cfg.participation, self._key, self.round,
                               cohort)
        report = self._empty_report()
        with tr.span("plan", parent=rspan, cohort_size=len(cohort)):
            plan = plan_round(
                self.registry, cohort, self.round, self._key,
                n_batches=cfg.batches_per_round, batch_size=cfg.batch_size,
                image_shape=cfg.image_shape, n_classes=cfg.n_classes,
                tier_cap=cfg.tier_cap, drops=drops)
        report.update({"cohort": list(cohort), "cohort_size": len(cohort),
                       "strict_subset": len(cohort) < len(active),
                       "mid_round_drops": len(drops),
                       "stragglers": len(lags),
                       "stale_merges": stale_merges})
        self._c["mid_round_drops"].inc(len(drops))
        self._c["stragglers"].inc(len(lags))
        self._c["stale_merges"].inc(stale_merges)
        if plan is None:
            with tr.span("fedavg", parent=rspan):
                report["fedavg_applied"] = self._maybe_fedavg()
            self._update_ema()
            self.round += 1
            self._c["rounds"].inc()
            report.update(self._dp_report())
            report["pending_payloads"] = len(self._pending)
            report["wall_s"] = self._clock() - t0
            tr.end(rspan, empty=True)
            self._obs.frame_closed(snap, extra={
                "round": self.round - 1, "wall_s": report["wall_s"]})
            return report

        with tr.span("round_dispatch", parent=rspan, tier=plan.tier,
                     cohort_size=len(plan.cohort)):
            members = [self.registry.get(u) for u in plan.cohort]
            pad = plan.tier - len(members)
            cp = stack_clients([m.params for m in members] +
                               [members[0].params] * pad)
            co = stack_clients([m.opt for m in members] +
                               [members[0].opt] * pad)
            xs, ys, mask, uids = plan.xs, plan.ys, plan.mask, plan.uids
            if self.mesh is not None:
                # the server net is placed too, before every round: an
                # unplaced tree (fresh or restored) and the round's own
                # mesh-placed output would otherwise be two signatures
                from repro.sharding.specs import (replicate,
                                                  shard_client_stack,
                                                  shard_cohort_round)
                cp, co = shard_client_stack(self.mesh, cp, co)
                self.server_params, self.server_opt = replicate(
                    self.mesh, (self.server_params, self.server_opt))
                xs, ys, mask, uids = shard_cohort_round(self.mesh, xs, ys,
                                                        mask, uids)
            rkey = jax.random.fold_in(
                jax.random.fold_in(self._key, TAG_ROUND), self.round)
            cp, co, self.server_params, self.server_opt, metrics = \
                self._engine(cp, co, self.server_params, self.server_opt,
                             xs, ys, mask, uids, rkey)
            jax.block_until_ready(self.server_params)
        self._sigs.setdefault(plan.tier, set()).add(plan.signature())

        stall = 0.0
        if lags and not cfg.async_mode:
            # THE BARRIER: sync aggregation waits for the slowest upload
            # before the round can close (lag_s wall seconds per lag
            # round) — then applies every payload as if nobody lagged
            stall = cfg.lag_s * max(lags.values())
            if stall > 0.0:
                with tr.span("barrier_stall", parent=rspan,
                             seconds=stall):
                    time.sleep(stall)

        # scatter ONLY the real cohort slots back; pad slots are discarded
        # (the engine left them bitwise-untouched anyway).  In async mode
        # a straggler's payload is ENQUEUED for its due round instead of
        # applied — its record (params, opt, counters, window flags)
        # stays untouched until the upload lands.
        new_p = unstack_clients(cp, plan.tier)
        new_o = unstack_clients(co, plan.tier)
        mask_np = np.asarray(plan.mask)
        for m, rec in enumerate(members):
            n_real = int(mask_np[:, m, :].sum())
            uid = int(plan.cohort[m])
            if cfg.async_mode and uid in lags and n_real > 0:
                self._pending.append({
                    "uid": uid, "params": new_p[m], "opt": new_o[m],
                    "compute_round": int(self.round),
                    "due_round": int(self.round + lags[uid]),
                    "n_real": n_real,
                })
                continue
            rec.params, rec.opt = new_p[m], new_o[m]
            rec.seen += n_real
            rec.window_seen += n_real
            rec.window_member = True
        cells = mask_np.any(axis=2)                 # (nb, tier)
        self.total_steps += int(cells.sum())
        self._c["real_samples"].inc(plan.real_samples)
        self._c["padded_cells"].inc(plan.padded_cells)

        report.update(self._losses(metrics, mask_np))
        with tr.span("fedavg", parent=rspan):
            report["fedavg_applied"] = self._maybe_fedavg()
        self._update_ema()
        self.round += 1
        self._c["rounds"].inc()
        report.update(self._dp_report())
        report.update({
            "tier": plan.tier, "padded_client_slots": pad,
            "real_samples": plan.real_samples,
            "padded_cells": plan.padded_cells,
            "pad_waste_frac": plan.padded_cells / plan.mask.size,
            "engine_traces": self.metrics.delta("engine_traces", snap),
            "signatures_per_tier": {t: len(s)
                                    for t, s in sorted(self._sigs.items())},
            "max_signatures_per_tier": max(len(s)
                                           for s in self._sigs.values()),
            "seen_total": sum(r.seen for r in self.registry.records()),
            "barrier_stall_s": stall,
            "pending_payloads": len(self._pending),
            "wall_s": self._clock() - t0,
        })
        tr.end(rspan, tier=plan.tier)
        self._obs.frame_closed(snap, extra={
            "round": self.round - 1, "wall_s": report["wall_s"]})
        return report

    def run(self, n_rounds: int, checkpoint_path: Optional[str] = None,
            checkpoint_every: int = 1) -> List[Dict]:
        """Run ``n_rounds`` rounds; checkpoint after every
        ``checkpoint_every``-th completed round (and once more at the
        end) when a path is given — the periodic persistence that makes
        mid-run interruption recoverable."""
        reports = []
        saved_at = -1
        tr = self._obs.tracer
        for i in range(n_rounds):
            reports.append(self.run_round())
            if checkpoint_path and checkpoint_every > 0 and \
                    (i + 1) % checkpoint_every == 0:
                with tr.span("checkpoint", round=self.round):
                    self.save(checkpoint_path)
                saved_at = i
        if checkpoint_path and saved_at != n_rounds - 1:
            with tr.span("checkpoint", round=self.round):
                self.save(checkpoint_path)
        return reports

    # -- aggregation -------------------------------------------------------
    def _maybe_fedavg(self) -> bool:
        cfg = self.config
        if not cfg.fedavg_every or (self.round + 1) % cfg.fedavg_every:
            return False
        recs = self.registry.records()
        if not recs:
            return False
        # a member that LEFT since it trained neither contributes nor
        # receives — departure freezes its net bitwise until rejoin (the
        # registry contract), so membership is gated on active here
        members = [r.window_member and r.active for r in recs]
        if cfg.privacy.enabled:
            return self._dp_fedavg(recs, members)
        # legacy (non-private) path — kept verbatim: the identity ladder
        # is structural, a disabled PrivacyConfig must run these exact
        # operations (pinned by tests/test_privacy.py and the CI smoke)
        new = average_cohort([r.params for r in recs],
                             [r.window_seen for r in recs], members)
        applied = any(m and r.window_seen > 0
                      for m, r in zip(members, recs))
        for r, p in zip(recs, new):
            r.params = p
            r.window_seen = 0
            r.window_member = False
        return applied

    def _dp_fedavg(self, recs, members) -> bool:
        """The DP aggregation release (privacy/dp.dp_average_cohort) at
        the fedavg boundary: clip member window deltas against the
        broadcast reference, secagg-sum, noise, broadcast the new
        reference; charge the accountant ONCE per applied release at the
        window-composed sampling rate; bump the DP epoch."""
        cfg = self.config
        # a mask-agreement party that trained this window but departed
        # before uploading is a SecAgg DROPOUT — its pair masks are
        # reconstructed and removed by the recovery path
        dropped = [int(r.uid) for r in recs
                   if r.window_member and not r.active]
        new, new_ref, stats = dp_average_cohort(
            [r.params for r in recs], [r.window_seen for r in recs],
            members, self._dp_ref, [r.uid for r in recs],
            clip=cfg.privacy.clip,
            noise_multiplier=cfg.privacy.noise_multiplier,
            base_key=self._key, round_idx=self.round,
            secagg=cfg.privacy.secagg, dropped_uids=dropped)
        applied = bool(stats["applied"])
        if applied:
            self._dp_ref = new_ref
            self._dp_clip_frac = float(stats["clip_frac"])
            q = sampling_rate(cfg.participation,
                              len(self.registry.active_uids()))
            # one release covers the whole window: a member joining ANY
            # of its fedavg_every rounds contributes to this release
            q_window = 1.0 - (1.0 - q) ** max(int(cfg.fedavg_every), 1)
            self._accountant.charge(q_window)
            self.dp_epoch += 1
            if self.on_dp_epoch is not None:
                self.on_dp_epoch(self.dp_epoch)
        for r, p in zip(recs, new):
            r.params = p
            r.window_seen = 0
            r.window_member = False
        return applied

    def _update_ema(self) -> None:
        d = self.config.ema_decay
        if self.ema_server is None or d <= 0.0:
            return
        self.ema_server = jax.tree.map(
            lambda e, p: (d * e.astype(jnp.float32) +
                          (1.0 - d) * p.astype(jnp.float32)).astype(p.dtype),
            self.ema_server, self.server_params)

    def sampling_server_params(self):
        """The server net inference should load: the EMA track when
        enabled, else the raw trained params."""
        return (self.server_params if self.ema_server is None
                else self.ema_server)

    def _losses(self, metrics, mask_np) -> Dict[str, float]:
        valid = mask_np.any(axis=2)                 # (nb, tier)
        if not valid.any():
            return {"client_loss": 0.0, "server_loss": 0.0}
        cl = np.asarray(metrics["client_loss"])
        out = {"client_loss": float(cl[valid].mean())}
        b_srv = int(np.nonzero(valid.any(axis=1))[0][-1])
        sl = np.asarray(metrics.get("server_loss", np.zeros(len(valid))))
        out["server_loss"] = float(sl[b_srv])
        return out

    # -- persistence -------------------------------------------------------
    def state_dict(self) -> Dict:
        """The FULL resumable state.  Client data is deliberately absent
        (it never leaves the client's record): re-attach by uid after
        ``restore``."""
        clients = {}
        for rec in self.registry.records():
            clients[str(rec.uid)] = {
                "params": rec.params, "opt": rec.opt,
                "seen": int(rec.seen),
                "window_seen": int(rec.window_seen),
                "window_member": bool(rec.window_member),
                "joined_round": int(rec.joined_round),
                "active": bool(rec.active),
            }
        privacy = None
        if self._accountant is not None:
            privacy = {"dp_ref": self._dp_ref,
                       "dp_epoch": int(self.dp_epoch),
                       "accountant": self._accountant.state_dict()}
        return {
            # v3 adds the privacy state (broadcast DP reference, epoch
            # counter, accountant); v2 added the async pending-payload
            # queue; v1/v2 checkpoints still restore — see ``restore``
            "version": 3,
            "privacy": privacy,
            "round": int(self.round),
            "total_steps": int(self.total_steps),
            "base_key": _key_pack(self._key),
            "server_params": self.server_params,
            "server_opt": self.server_opt,
            "ema_server": self.ema_server,
            "clients": clients,
            "pending": [
                {"uid": int(p["uid"]), "params": p["params"],
                 "opt": p["opt"],
                 "compute_round": int(p["compute_round"]),
                 "due_round": int(p["due_round"]),
                 "n_real": int(p["n_real"])}
                for p in self._pending],
        }

    def save(self, path: str) -> None:
        ckpt.save(path, self.state_dict())

    @classmethod
    def restore(cls, config: TrainConfig, init_one, apply_fn, path: str,
                mesh=None, obs=None) -> "TrainRuntime":
        """Rebuild a runtime from a checkpoint: params, opt states,
        registry, cohort cursor, and RNG all resume where they stopped —
        continuing from here is bitwise-equal to never having stopped.
        Data is not in the checkpoint: call ``attach_data(uid, x, y)``
        for every client that should keep training."""
        state = ckpt.load(path)
        if state.get("version") not in (1, 2, 3):
            raise ValueError(f"unknown checkpoint version "
                             f"{state.get('version')!r}")
        rt = cls(config, init_one, apply_fn, _key_unpack(state["base_key"]),
                 mesh=mesh, obs=obs)
        priv = state.get("privacy")
        if priv is not None:
            if not config.privacy.enabled:
                raise ValueError(
                    "checkpoint carries DP state (format v3) but the "
                    "config's PrivacyConfig is disabled — resuming a DP "
                    "run without its privacy config would silently stop "
                    "clipping/noising mid-stream")
            rt._dp_ref = priv["dp_ref"]
            rt.dp_epoch = int(priv["dp_epoch"])
            rt._accountant = RdpAccountant.from_state(priv["accountant"])
        # (v1/v2, or v3 saved with privacy disabled: the fresh privacy
        # state from __init__ stands — a pre-privacy run resumes with an
        # uncharged accountant, exactly what it has spent)
        rt.round = int(state["round"])
        rt.total_steps = int(state["total_steps"])
        rt.server_params = state["server_params"]
        rt.server_opt = state["server_opt"]
        rt.ema_server = state["ema_server"]
        rt._pending = [dict(p) for p in state.get("pending", [])]
        for uid_s in sorted(state["clients"], key=int):
            d = state["clients"][uid_s]
            uid = int(uid_s)
            rt.registry.register(uid=uid,
                                 joined_round=int(d["joined_round"]))
            rec = rt.registry.get(uid)
            rec.params, rec.opt = d["params"], d["opt"]
            rec.seen = int(d["seen"])
            rec.window_seen = int(d["window_seen"])
            rec.window_member = bool(d["window_member"])
            rec.active = bool(d["active"])
        return rt
