"""Compile the main path's TPU programs for a described TPU v5e.

Nothing runs and no chip is needed: the TPU compiler is installed, and it
compiles for a topology that is described, not attached.  What interpret
mode cannot show is refused here: a Pallas block the chip's tiling does
not allow, a kernel over its fast-memory budget, a program that does not
fit the chip's 16 GiB.  The topology is described inside a fixture (only
one process may load the TPU library at a time, so never while a module
is imported), and every test of this kind stays in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.ddpm_unet import CONFIG
from repro.core.unet import init_unet, unet_apply
from repro.kernels.ddpm_step.kernel import (ddpm_step_pallas,
                                            ddpm_step_pallas_batched)

V5E_HBM_BYTES = 16 * 2**30
IMAGE = (CONFIG.image_size, CONFIG.image_size, CONFIG.channels)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler to describe it with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name,fn,shape,n_coef", [
    # one request of the per-request samplers: batch 8 at CONFIG's image
    ("single", ddpm_step_pallas, (8,) + IMAGE, ()),
    # the sampling engine's stacked step: K = 8 requests of batch 4, each
    # at its own timestep
    ("batched", ddpm_step_pallas_batched, (8, 4) + IMAGE, (8,)),
])
def test_ddpm_step_kernel_compiles_for_v5e(one_chip, name, fn, shape,
                                           n_coef):
    x = _spec(shape, one_chip)
    coef = _spec(n_coef, one_chip)
    compiled = jax.jit(fn).lower(x, x, x, coef, coef, coef).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_unet_forward_compiles_and_fits_v5e(one_chip):
    """One forward of the paper's U-Net, unchanged, at batch 8."""
    shapes = jax.eval_shape(functools.partial(init_unet, cfg=CONFIG),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype),
                          shapes)
    B = 8
    compiled = jax.jit(functools.partial(unet_apply, cfg=CONFIG)).lower(
        params, _spec((B,) + IMAGE, one_chip), _spec((B,), one_chip),
        _spec((B, CONFIG.n_classes), one_chip)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
             mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
