"""Serve loop: ``ServeRuntime.submit``/``poll`` under a closed-loop backlog.

Set-up makes the weights, builds the runtime and runs one full wave of
misses at each cut (labels no traffic uses, so nothing it caches is ever
hit by traffic).  Then the window: each cut is kept ``backlog_waves`` full
waves deep, and the loop polls.  A poll retires every wave it finds done
and dispatches new ones; dispatching waits for the device, so one poll can
retire several waves at once.  The window therefore runs from poll to
poll: it opens when the first poll that retires anything returns, and
closes when the first poll that retires anything returns ``seconds`` or
more later.  ``serve_samples_per_s`` is the images of every request the
polls in between retired, over that time.

A traced run records the opening poll and ends its trace before the
window opens.

After the window a sample of the retired requests is compared with the
plain reference (``bench/reference/sampler.py``) once the runtime is freed:
at each cut one whole wave, drawn from the seed among the window's full
waves of that cut, and one more request of each client at that cut from
outside that wave.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import gen, program, trace

# how long the loop waits for a retirement before it gives the run up
GRACE_S = 60.0


def _requests_cls():
    from repro.core.sample_plan import SampleRequest
    return SampleRequest


class Driver:
    """Submits requests and keeps, per submission, what the check needs."""

    def __init__(self, rt):
        self.rt = rt
        self.sent = []          # (ticket, client, cut, y)
        self.Request = _requests_cls()

    def submit(self, client, cut, y):
        with trace.annotate("submit"):
            t = self.rt.submit([self.Request(client=client, t_cut=cut,
                                             y=y)])[0]
        self.sent.append((t, client, cut, y))
        return t

    def poll(self):
        with trace.annotate("poll"):
            return self.rt.poll()

    def drain(self):
        with trace.annotate("drain"):
            self.rt.drain()


def warm_signatures(drv: Driver, traffic: dict, n_classes: int):
    """One full wave of misses at each cut: the only shapes unique labels
    present."""
    wave = int(traffic["max_wave"])
    batch = int(traffic["images_per_request"])
    i = 0
    for cut in sorted(set(traffic["client_cuts"])):
        client = traffic["client_cuts"].index(cut)
        for _ in range(wave):
            drv.submit(client, cut, np.broadcast_to(
                gen.warm_vector(i, n_classes), (batch, n_classes)).copy())
            i += 1
        drv.drain()


def closed_window(drv: Driver, stream, traffic: dict, seconds: float,
                  on_open=None):
    """(open, close, the tickets the window's polls retired, the polls as
    [seconds after the opening, requests retired])."""
    cuts = sorted(set(traffic["client_cuts"]))
    depth = int(traffic["backlog_waves"]) * int(traffic["max_wave"])
    waiting = {c: [] for c in cuts}
    retired, polls = [], []
    t_open = t_close = None
    last = time.perf_counter()
    while t_close is None:
        for c in cuts:
            waiting[c] = [t for t in waiting[c] if t.t_admit < 0]
            while len(waiting[c]) < depth:
                waiting[c].append(drv.submit(*stream.take(c)))
        retired_now = drv.poll()
        now = time.perf_counter()
        if not retired_now:
            if now - last > GRACE_S:
                raise RuntimeError(f"no request retired in {GRACE_S} s")
            time.sleep(0.0005)
            continue
        last = now
        if t_open is None:
            # what the opening poll retired ran before the window; the
            # hook (a traced run ends its trace there) runs before it opens
            if on_open is not None:
                on_open()
            t_open = time.perf_counter()
            continue
        retired += retired_now
        polls.append([now - t_open, len(retired_now)])
        if now >= t_open + seconds:
            t_close = now
    return t_open, t_close, retired, polls


def check_sample(window, cuts, wave: int, rng) -> list:
    """At each cut, one whole wave of the window (a wave's tickets share
    their admission time) drawn by ``rng`` among its full waves, and one
    more request of each client at that cut from outside that wave."""
    sample = []
    for cut in sorted(set(cuts)):
        mine = [t for t in window if t.request.t_cut == cut]
        waves = {}
        for t in mine:
            waves.setdefault(t.t_admit, []).append(t)
        full = [w for _, w in sorted(waves.items()) if len(w) == wave]
        if not full:
            raise RuntimeError(f"no full wave at cut {cut} retired in the "
                               "window")
        chosen = full[int(rng.integers(len(full)))]
        sample += chosen
        inside = {id(t) for t in chosen}
        for client in [c for c, k in enumerate(cuts) if k == cut]:
            rest = [t for t in mine if t.request.client == client and
                    id(t) not in inside]
            if not rest:
                raise RuntimeError(f"client {client} has no request outside "
                                   "the sampled wave in the window")
            sample.append(rest[int(rng.integers(len(rest)))])
    return sample


def build(ctx):
    """Set-up: weights, the runtime and one wave of every shape.  Returns
    (runtime, driver, the seed's request stream)."""
    jax = ctx.jax
    cfg, traffic = ctx.cfg, ctx.traffic
    from repro.obs import ObsConfig
    from repro.serve.runtime import ServeConfig, ServeRuntime

    ucfg = program.unet_cfg(cfg)
    keys = program.keys(ctx.seed)
    with trace.annotate("weights"):
        sp, cps = program.serve_weights(cfg, keys["weights"],
                                        len(traffic["client_cuts"]))
        jax.block_until_ready((sp, cps))
    scfg = ServeConfig(
        T=cfg["T"], image_shape=(ucfg["image_size"], ucfg["image_size"],
                                 ucfg["channels"]),
        max_wave=int(traffic["max_wave"]), policy=traffic["policy"],
        server_stride=1, cache=bool(traffic["cache"]),
        pipeline=bool(traffic["pipeline"]),
        use_pallas=ctx.use_pallas, interpret=ctx.interpret)
    rt = ServeRuntime(scfg, sp, cps, program.apply_fn(cfg),
                      program.schedule(cfg), keys["runtime"],
                      obs=ObsConfig(enabled=True) if ctx.trace else None)
    del sp, cps
    if ctx.fault is not None:
        ctx.fault(rt)
    drv = Driver(rt)
    warm_signatures(drv, traffic, ucfg["n_classes"])
    return rt, drv, gen.ServeStream(traffic, ucfg["n_classes"], ctx.seed)


def run(ctx) -> dict:
    traffic = ctx.traffic
    batch = int(traffic["images_per_request"])
    cuts = list(traffic["client_cuts"])
    rt, drv, stream = build(ctx)
    traces_warm = rt.traces
    ctx.setup_done()

    state = {}
    tracing = contextlib.ExitStack()
    tracing.enter_context(ctx.traced())

    def on_open():
        # the trace covers the opening poll, steady traffic of the same
        # waves: the profiler keeps about 12 s of device events, and
        # writing it out stalls the host, which must not fall in the window
        t0 = time.perf_counter()
        tracing.close()
        state["trace_stop_s"] = time.perf_counter() - t0
        state["snap"] = rt.registry.snapshot()
        state["compiles"] = ctx.log.snapshot()

    with tracing:
        t_open, t_close, window, polls = closed_window(
            drv, stream, traffic, ctx.seconds, on_open)
    counters = rt.registry.deltas(state["snap"])
    window_s = t_close - t_open
    compiles_in_window = ctx.log.snapshot()["compiles"] - \
        state["compiles"]["compiles"]
    images = sum(int(t.request.y.shape[0]) for t in window)
    e2e = {"serve_samples_per_s": images / window_s}
    served = [s for s in drv.sent if s[0].t_retire >= 0]
    repeats = gen.count_repeats([(c, k, y) for _, c, k, y in served
                                 if y.max() == 1.0 or y.sum() == 0])
    waves = sorted({(t.t_admit, t.t_retire, t.request.t_cut)
                    for t in window})
    ctx.say(f"serve/window: requests={len(window)} images={images} "
            f"window_s={window_s} engine_traces={rt.traces - traces_warm} "
            f"compiles={compiles_in_window} repeats={repeats} "
            f"trace_stop_s={state['trace_stop_s']} "
            f"counters={counters}")
    ctx.say("serve/polls: " + " ".join(f"{t:.3f}:{n}" for t, n in polls))
    ctx.say("serve/waves: " + " ".join(
        f"{a - t_open:.3f}-{r - t_open:.3f}@{c}" for a, r, c in waves))
    # ---- the check ------------------------------------------------------
    rng = np.random.default_rng(gen.seed_words(ctx.seed + 1))
    rid = {id(s[0]): i for i, s in enumerate(drv.sent)}
    index = {id(s[0]): s for s in drv.sent}
    sample = check_sample(window, cuts, int(traffic["max_wave"]), rng)
    specs = [(index[id(t)][1], index[id(t)][2], index[id(t)][3], rid[id(t)])
             for t in sample]
    produced = [np.asarray(t.output) for t in sample]
    memory = ctx.memory()
    rec = {"window_s": window_s, "counters": counters,
           "requests": len(window), "images_per_request": batch}
    del rt, drv, window, served, sample
    t_ref = time.perf_counter()
    ref = reference_outputs(ctx, specs, "float32", "highest")
    ctx.say(f"serve/reference: {len(specs)} requests in "
            f"{time.perf_counter() - t_ref} s")
    checks = compare(produced, ref, traffic["check"]["limits"])
    checks["repeats"] = (repeats, 0)
    return {"e2e": e2e, "record": rec, "checks": checks,
            "attempted": rec["requests"], "failed": 0, "memory": memory}


def reference_outputs(ctx, specs, dtype: str, precision: str) -> list:
    """The plain reference's images for each (client, cut, labels, arrival
    number) in ``specs``, from weights and keys made from the seed anew,
    computed in ``dtype`` at ``precision``; requests of one cut run as one
    batch."""
    import jax
    import jax.numpy as jnp
    from bench.reference import sampler
    cfg = ctx.cfg
    keys = program.keys(ctx.seed)
    sp, cps = program.serve_weights(cfg, keys["weights"],
                                    len(ctx.traffic["client_cuts"]))
    out = [None] * len(specs)
    by_cut = {}
    for i, s in enumerate(specs):
        by_cut.setdefault(s[1], []).append(i)
    with jax.default_matmul_precision(precision):
        for cut, idx in sorted(by_cut.items()):
            clients = np.asarray([specs[i][0] for i in idx])
            ys = np.stack([specs[i][2] for i in idx])
            imgs = sampler.sample(
                sp, jax.tree.map(lambda a: a[clients], cps),
                keys["runtime"], jnp.asarray(ys),
                jnp.asarray([sampler.group_seed(cut, y) for y in ys],
                            jnp.int32),
                jnp.asarray([specs[i][3] & 0x7FFFFFFF for i in idx],
                            jnp.int32),
                cfg_items=program.frozen(program.unet_cfg(cfg)), T=cfg["T"],
                t_cut=cut, dtype=jnp.dtype(dtype))
            imgs = np.asarray(imgs.astype(jnp.float32))
            for j, i in enumerate(idx):
                out[i] = imgs[j]
    return out


def compare(produced, ref, limits: dict) -> dict:
    """Worst, over the sampled requests, of the relative L2 gap of their
    images from the reference's, and of the largest pixel gap over the
    reference's RMS.  A request that is not finite reads infinite."""
    worst_l2 = worst_max = 0.0
    for p, r in zip(produced, ref):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        if not np.isfinite(p).all():
            worst_l2 = worst_max = float("inf")
            continue
        d = p - r
        worst_l2 = max(worst_l2, float(np.linalg.norm(d) / np.linalg.norm(r)))
        worst_max = max(worst_max, float(np.abs(d).max() /
                                         np.sqrt(np.mean(r * r))))
    return {"sample_rel_l2": (worst_l2, limits.get("sample_rel_l2")),
            "sample_max_gap": (worst_max, limits.get("sample_max_gap"))}
