"""Sharding-spec unit tests (the dry-run exercises the full configs; these
check the rules themselves on one device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_arch, get_shape, reduced
from repro.launch.shapes import skip_reason
from repro.models import api
from repro.sharding import specs as S


def _abstract_params(arch):
    cfg = get_arch(arch)
    import functools
    return cfg, jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ["granite-8b", "kimi-k2-1t-a32b",
                                  "mamba2-2.7b", "whisper-base"])
def test_param_specs_cover_tree(arch):
    cfg, shapes = _abstract_params(arch)
    specs = S.param_specs(shapes)
    flat_s, _ = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))
    flat_p = jax.tree.leaves(shapes)
    assert len(flat_s) == len(flat_p)
    for spec, leaf in zip(flat_s, flat_p):
        assert len(spec) == leaf.ndim, (spec, leaf.shape)


def test_moe_experts_expert_parallel():
    cfg, shapes = _abstract_params("kimi-k2-1t-a32b")
    specs = S.param_specs(shapes)
    s = specs["layers"]["moe"]["w_gate"]
    assert s == P(None, "model", "data", None)  # stacked + EP + FSDP
    assert specs["layers"]["moe"]["router"] == P(None, None, None)


def test_megatron_pattern_dense():
    cfg, shapes = _abstract_params("granite-8b")
    specs = S.param_specs(shapes)
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["layers"]["attn"]["wo"] == P(None, "model", "data")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["embed"] == P("model", None)


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_sanitize_drops_indivisible():
    mesh = FakeMesh({"data": 16, "model": 16})
    assert S.sanitize_spec(P("model", None), (51865, 512), mesh) == \
        P(None, None)
    assert S.sanitize_spec(P("model", None), (65536, 512), mesh) == \
        P("model", None)
    assert S.sanitize_spec(P(("pod", "data"), None), (48, 4),
                           FakeMesh({"pod": 2, "data": 16})) == P(None, None)
    assert S.sanitize_spec(P(("pod", "data"), None), (64, 4),
                           FakeMesh({"pod": 2, "data": 16})) == \
        P(("pod", "data"), None)


def test_skip_reasons_match_design_doc():
    long = get_shape("long_500k")
    runs, skips = [], []
    from repro.configs.base import ARCH_IDS
    for a in ARCH_IDS:
        cfg = get_arch(a)
        (runs if skip_reason(cfg, long) is None else skips).append(cfg.name)
    assert sorted(runs) == ["granite-8b", "mamba2-2.7b", "zamba2-1.2b"]
    assert len(skips) == 7
    # no skips anywhere else
    for sname in ("train_4k", "prefill_32k", "decode_32k"):
        sh = get_shape(sname)
        for a in ARCH_IDS:
            assert skip_reason(get_arch(a), sh) is None


def test_serve_plan_and_inject_specs_on_mesh():
    """The serve operands' specs cover every leaf, place on a
    ("clients","data")-style mesh, and the engine runs on the placed
    operands — plan tables, injected cache-hit rows, and a single cached
    handoff entry (the serve-runtime layout, ISSUE 4)."""
    from repro.core.sample_plan import SampleRequest, group_key, \
        plan_requests
    from repro.core.sampler import make_sample_engine
    from repro.core.schedules import DiffusionSchedule
    T, B, img = 8, 2, (4, 4, 3)
    y = np.broadcast_to(np.eye(2, dtype=np.float32)[0], (B, 2)).copy()
    reqs = [SampleRequest(0, 2, y), SampleRequest(1, 4, y)]
    stored = jnp.zeros((B,) + img)
    plan = plan_requests(
        reqs, T, n_clients=2, image_shape=img,
        lookup_fn=lambda gk: stored if gk == group_key(2, y) else None)
    assert plan.n_groups == 1 and plan.n_hits == 1
    # specs zip leaf-for-leaf and match ranks
    for tree, spec_tree in ((plan.tables, S.sample_plan_specs(plan.tables)),
                            (plan.inject, S.inject_specs(plan.inject))):
        for leaf, spec in zip(tree, spec_tree):
            assert len(spec) == leaf.ndim, (spec, leaf.shape)
    assert S.inject_specs(plan.inject).x == \
        P(S.CLIENT_AXIS, "data", None, None, None)
    assert S.handoff_spec(1 + len(img)) == P("data", None, None, None)
    mesh = S.make_client_mesh(1)
    tables = S.shard_sample_plan(mesh, plan.tables)
    inject = S.shard_inject(mesh, plan.inject)
    entry = jax.device_put(stored, jax.sharding.NamedSharding(
        mesh, S.sanitize_spec(S.handoff_spec(stored.ndim),
                              stored.shape, mesh)))
    assert entry.shape == stored.shape
    sched = DiffusionSchedule.linear(T)
    eng = make_sample_engine(sched, lambda p, x, t, yy: x * p["a"], img)
    sp = {"a": jnp.float32(0.2)}
    cp = {"a": jnp.linspace(0.1, 0.2, 2)}
    out, hand = eng(sp, cp, jax.random.PRNGKey(0), tables, inject)
    assert out.shape == (2, B) + img and hand.shape == (1, B) + img


def test_inference_layout_drops_fsdp():
    """Decode layout: no "data" factor on dense weights (no FSDP gathers);
    MoE experts carry the FFN dim on "data" instead (weights stationary)."""
    cfg, shapes = _abstract_params("kimi-k2-1t-a32b")
    infer = S.param_specs(shapes, inference=True)
    assert infer["layers"]["attn"]["wq"] == P(None, None, "model")
    assert infer["layers"]["attn"]["wo"] == P(None, "model", None)
    assert infer["layers"]["moe"]["w_gate"] == P(None, "model", None, "data")
    assert infer["layers"]["moe"]["w_down"] == P(None, "model", "data", None)
    cfg2, shapes2 = _abstract_params("mamba2-2.7b")
    infer2 = S.param_specs(shapes2, inference=True)
    assert infer2["mamba"]["x_proj"] == P(None, None, "model")
    assert infer2["mamba"]["out_proj"] == P(None, "model", None)


_FOUR_DEVICE_ROUND = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core.collab import CollabConfig, build_denoiser
    from repro.sharding.specs import CLIENT_AXIS, make_client_mesh, make_mesh
    from repro.train import ParticipationConfig, TrainConfig, TrainRuntime
    key = jax.random.PRNGKey(0)
    init_one, apply_fn = build_denoiser(key, CollabConfig(
        image_size=8, n_classes=4))
    cfg = TrainConfig(T=20, t_cut=5, image_shape=(8, 8, 3), n_classes=4,
                      batch_size=2, batches_per_round=1,
                      participation=ParticipationConfig(policy="full"))
    rts = []
    for mesh in (make_client_mesh(4), make_mesh((1,), (CLIENT_AXIS,))):
        rt = TrainRuntime(cfg, init_one, apply_fn, key, mesh=mesh)
        for c in range(4):
            x = jax.random.normal(jax.random.fold_in(key, c), (4, 8, 8, 3))
            rt.register_client(x, np.eye(4, dtype=np.float32)[[c] * 4])
        rt.run(2)
        rts.append(rt)
    four, one = rts
    assert four.mesh.devices.size == 4 and four.traces == 1
    def rel(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        num = sum(float(np.sum((np.asarray(x, np.float64) - np.asarray(y)) ** 2))
                  for x, y in zip(la, lb))
        return (num / sum(float(np.sum(np.asarray(y, np.float64) ** 2))
                          for y in lb)) ** 0.5
    gaps = [rel(four.server_params, one.server_params)] + [
        rel(four.registry.get(u).params, one.registry.get(u).params)
        for u in one.registry.uids()]
    print("FOUR_DEVICE_ROUND_OK", max(gaps))
""")


def test_four_device_client_mesh_round_matches_one_device():
    """The U-Net round with one client per device (4 CPU devices) trains
    what the one-device round does.  Only the reduction order of the
    server update differs (~1e-7 relative in float32 over 2 rounds); a
    misplaced or mis-partitioned client moves its net by a whole update
    (~1e-2).  This is what caught XLA's CPU partitioner returning wrong
    values for the vmapped 1x1 skip convolution."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_ROUND], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "FOUR_DEVICE_ROUND_OK" in r.stdout, r.stdout + r.stderr[-4000:]
    gap = float(r.stdout.split("FOUR_DEVICE_ROUND_OK")[1].split()[0])
    assert gap < 1e-4, gap


# A vmapped 1x1 convolution and the same 1x1 as a matmul, over a clients
# axis sharded on 4 devices against the unsharded program; prints the
# worst difference relative to the largest output, per matmul precision.
_SKIP_CONV = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.unet import conv, conv_init
    from repro.sharding.specs import CLIENT_AXIS, make_mesh
    mesh = make_mesh((4,), (CLIENT_AXIS,))
    key = jax.random.PRNGKey(0)
    ps = jax.vmap(lambda k: conv_init(k, 1, 1, 32, 64, jnp.float32))(
        jax.random.split(key, 4))
    x = jax.random.normal(key, (4, 2, 8, 8, 32))
    put = lambda t: jax.tree.map(lambda a: jax.device_put(
        a, NamedSharding(mesh, P(CLIENT_AXIS))), t)
    forms = {"conv": conv, "matmul": lambda p, a: a @ p["w"][0, 0] + p["b"]}
    for prec in ("default", "highest"):
        gaps = {}
        with jax.default_matmul_precision(prec):
            for name, f in forms.items():
                ref = np.asarray(jax.jit(jax.vmap(f))(ps, x))
                out = np.asarray(jax.jit(jax.vmap(f))(put(ps), put(x)))
                gaps[name] = float(np.abs(out - ref).max() / np.abs(ref).max())
        print("SKIP_CONV", prec, gaps["conv"], gaps["matmul"])
""")


def test_unet_skip_is_a_matmul_because_sharded_1x1_conv_is_wrong():
    """Why core/unet.res_block computes its 1x1 skip as a matmul: vmapped
    over a ``clients`` axis sharded on 4 CPU devices, XLA returns wrong
    values for the 1x1 ``conv_general_dilated`` (off by more than the
    output itself) and exact ones for the matmul.  If the conv assertion
    fails, XLA partitions the convolution correctly and the skip can be
    ``conv`` again."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    r = subprocess.run([sys.executable, "-c", _SKIP_CONV], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    rows = [line.split()[1:] for line in r.stdout.splitlines()
            if line.startswith("SKIP_CONV")]
    assert [p for p, *_ in rows] == ["default", "highest"], \
        r.stdout + r.stderr[-4000:]
    for prec, conv_gap, matmul_gap in rows:
        assert float(matmul_gap) <= 1e-6, (prec, matmul_gap)
        assert float(conv_gap) > 0.1, (
            f"at {prec} precision the sharded 1x1 conv now matches "
            f"({conv_gap}): the U-Net skip can be conv() again")
