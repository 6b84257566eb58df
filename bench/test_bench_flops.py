"""FLOPs and bytes from shapes: the U-Net forward count against XLA's cost
analysis at batch 1 for both configurations, and the kernel's bytes."""
import pytest

from bench import common, flops


@pytest.mark.parametrize(
    "name", [c["name"] for c in common.manifest()["configs"]])
def test_unet_flops_match_xla_cost_analysis(name):
    import jax
    import jax.numpy as jnp

    from bench import program
    cfg = common.load_json(common.config_file(name))
    u = cfg["unet"]
    params = jax.eval_shape(program.init_one(cfg), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, u["image_size"], u["image_size"],
                              u["channels"]), jnp.float32)
    fn = program.apply_fn(cfg)
    compiled = jax.jit(lambda p, x: fn(p, x, jnp.ones((1,)),
                                       jnp.zeros((1, u["n_classes"])))
                       ).lower(params, x).compile()
    xla = compiled.cost_analysis()["flops"]
    mine = flops.unet_forward_flops(u)
    # XLA also counts the elementwise work (norms, activations, adds)
    assert 0.975 * xla <= mine <= xla
    assert mine / 1e9 == pytest.approx(cfg["forward_gflop_per_image"],
                                       abs=1e-3)


def test_same_padding_taps():
    # 32 wide, 3 taps: the two border outputs lose one tap each
    assert flops._taps(32, 3, 1) == 32 * 3 - 2
    # stride 2 from 32: 16 outputs, padding only at the high end
    assert flops._taps(32, 3, 2) == 16 * 3 - 1
    assert flops._taps(16, 1, 1) == 16


def test_ddpm_step_bytes():
    u = {"image_size": 32, "channels": 3}
    # 4 images of 32x32x3 = 12288 floats = 96 lanes-rows exactly
    assert flops.ddpm_step_bytes(8, 4, u) == 4 * 8 * 12288 * 4
    # 1 image of 5x5x3 = 75 floats pads to one 128-lane row
    assert flops.ddpm_step_bytes(1, 1, {"image_size": 5, "channels": 3}) \
        == 4 * 128 * 4
