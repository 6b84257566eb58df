"""MoE layer tests: router invariants + dense↔expert-parallel agreement.

The in-process test uses a (1,1) debug mesh (this pytest process sees one
CPU device by design); the 8-device all-to-all path is exercised in a
subprocess with XLA_FLAGS host-device override — real shard boundaries,
real collectives (interpreted on CPU)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced
from repro.launch.mesh import make_debug_mesh
from repro.models.moe import moe_dense, moe_ep, moe_init
from repro.models.transformer import Runtime


def _cfg(capacity_factor=8.0):
    import dataclasses
    cfg = reduced(get_arch("dbrx-132b"))
    return dataclasses.replace(cfg, capacity_factor=capacity_factor)


def test_router_topk_normalized(key):
    cfg = _cfg()
    p = moe_init(key, cfg, jnp.float32)
    from repro.models.moe import _router
    x = jax.random.normal(key, (32, cfg.d_model))
    probs, w, idx = _router(p, x, cfg.top_k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), np.ones(32), atol=1e-5)
    assert idx.shape == (32, cfg.top_k)
    assert int(idx.max()) < cfg.n_experts


def test_dense_mode_shapes_and_aux(key):
    cfg = _cfg()
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    y, aux = moe_dense(p, x, cfg)
    assert y.shape == x.shape
    # perfectly balanced router would give aux ~= 1.0; ours is near it
    assert 0.5 < float(aux) < 4.0


def test_ep_equals_dense_single_shard(key):
    """On a (1,1) mesh with ample capacity the a2a path must agree with the
    dense path bit-for-bit up to summation order."""
    cfg = _cfg(capacity_factor=8.0)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    y_d, aux_d = moe_dense(p, x, cfg)
    mesh = make_debug_mesh()
    y_e, aux_e = moe_ep(p, x, cfg, mesh, ("data",))
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(float(aux_e), float(aux_d), rtol=1e-4)


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_arch, reduced
    from repro.launch.mesh import make_debug_mesh
    from repro.models.moe import moe_dense, moe_ep, moe_init
    cfg = dataclasses.replace(reduced(get_arch("dbrx-132b")),
                              capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (4, 16, cfg.d_model))
    y_d, aux_d = moe_dense(p, x, cfg)
    mesh = make_debug_mesh(2, 4)
    y_e, aux_e = jax.jit(
        lambda xx: moe_ep(p, xx, cfg, mesh, ("data",)))(x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d),
                               atol=1e-4, rtol=1e-3)
    print("MOE_EP_8DEV_OK", float(aux_d), float(aux_e))
""")


@pytest.mark.slow  # subprocess + 8-device XLA compile
def test_ep_equals_dense_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MOE_EP_8DEV_OK" in r.stdout, r.stdout + r.stderr


def test_capacity_drops_tokens(key):
    """With tiny capacity the ep path drops overflow tokens: outputs shrink
    toward zero instead of diverging (graceful degradation)."""
    cfg = _cfg(capacity_factor=0.1)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 32, cfg.d_model))
    mesh = make_debug_mesh()
    y, _ = moe_ep(p, x, cfg, mesh, ("data",))
    y_full, _ = moe_dense(p, x, cfg)
    assert float(jnp.abs(y).mean()) < float(jnp.abs(y_full).mean()) + 1e-6
    assert np.isfinite(np.asarray(y)).all()


def test_ep2d_equals_dense_single_shard(key):
    """Decode-layout (weights-stationary) MoE must agree with dense."""
    from repro.models.moe import moe_ep2d
    cfg = _cfg(capacity_factor=8.0)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 4, cfg.d_model))
    y_d, _ = moe_dense(p, x, cfg)
    mesh = make_debug_mesh()
    y_e, _ = moe_ep2d(p, x, cfg, mesh, ("data",))
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d), atol=1e-4,
                               rtol=1e-3)


_SUBPROCESS_2D = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_arch, reduced
    from repro.launch.mesh import make_debug_mesh
    from repro.models.moe import moe_dense, moe_ep2d, moe_init
    cfg = dataclasses.replace(reduced(get_arch("dbrx-132b")),
                              capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    p = moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (4, 2, cfg.d_model))
    y_d, _ = moe_dense(p, x, cfg)
    mesh = make_debug_mesh(2, 4)
    y_e, _ = jax.jit(lambda xx: moe_ep2d(p, xx, cfg, mesh, ("data",)))(x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_d),
                               atol=1e-4, rtol=1e-3)
    print("MOE_EP2D_8DEV_OK")
""")


@pytest.mark.slow  # subprocess + 8-device XLA compile
def test_ep2d_equals_dense_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_2D], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MOE_EP2D_8DEV_OK" in r.stdout, r.stdout + r.stderr
