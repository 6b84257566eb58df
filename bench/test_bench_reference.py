"""The benchmark's own U-Net and samplers against the program they check,
on the CPU (exact float32): the weights it makes have the program's layout,
its forward pass is the program's, and its reference sampler reproduces
the sampling engine's images bit for bit up to float32 rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, program
from bench.conftest import TOY_UNET
from bench.reference import sampler, unet


@pytest.mark.parametrize(
    "name", [c["name"] for c in common.manifest()["configs"]])
def test_weights_have_the_program_layout_and_size(name):
    from repro.core.unet import init_unet
    cfg = common.load_json(common.config_file(name))
    ours = jax.eval_shape(program.init_one(cfg), jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: init_unet(k, program.program_unet_config(cfg)),
        jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert unet.param_count(ours) == cfg["parameters"]


def test_forward_matches_the_program():
    from repro.core.unet import unet_apply
    cfg = {"name": "toy", "unet": TOY_UNET}
    p = program.init_one(cfg)(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 3))
    t = jnp.asarray([1.0, 37.5, 999.0])
    y = jnp.asarray(np.eye(12)[[0, 3, 7]], jnp.float32)
    got = unet_apply(p, x, t, y, program.program_unet_config(cfg))
    want = unet.forward(p, x, t, y, TOY_UNET)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_group_seed_is_the_programs():
    from repro.core.sample_plan import group_key, stable_group_seed
    y = np.broadcast_to(np.eye(8, dtype=np.float32)[2], (4, 8))
    for cut in (0, 100, 200):
        assert sampler.group_seed(cut, y) == \
            stable_group_seed(group_key(cut, y, 1))


def test_sampler_matches_the_engine():
    from repro.core.sample_plan import (SampleRequest, plan_requests,
                                        stable_group_seed)
    from repro.core.sampler import make_sample_engine
    cfg = {"name": "toy", "unet": TOY_UNET, "T": 12, "beta_min": 1e-4,
           "beta_max": 0.02}
    sp, cps = program.serve_weights(cfg, jax.random.PRNGKey(2), 2)
    key = jax.random.PRNGKey(9)
    eye = np.eye(12, dtype=np.float32)
    reqs = [SampleRequest(client=c, t_cut=5, y=np.stack([eye[c], eye[4]]))
            for c in (0, 1)]
    plan = plan_requests(reqs, 12, n_clients=2,
                         group_seed_fn=stable_group_seed,
                         request_seeds=[7, 8])
    engine = make_sample_engine(program.schedule(cfg), program.apply_fn(cfg),
                                (8, 8, 3), use_pallas=False)
    got, _ = engine(sp, cps, key, plan.tables)
    want = sampler.sample(
        sp, cps, key, jnp.stack([r.y for r in reqs]),
        jnp.asarray([sampler.group_seed(5, r.y) for r in reqs], jnp.int32),
        jnp.asarray([7, 8], jnp.int32), cfg_items=program.frozen(TOY_UNET),
        T=12, t_cut=5, dtype=jnp.float32)
    scale = float(jnp.sqrt(jnp.mean(want ** 2)))
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale


def test_serve_weights_are_each_nets_own_init():
    cfg = {"name": "toy", "unet": TOY_UNET}
    key = jax.random.PRNGKey(4)
    sp, cps = program.serve_weights(cfg, key, 2)
    one = program.init_one(cfg)
    for got, want in ((sp, one(jax.random.fold_in(key, 0))),
                      (jax.tree.map(lambda a: a[1], cps),
                       one(jax.random.fold_in(key, 2)))):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
