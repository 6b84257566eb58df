"""A serve run at toy size on the CPU, past run.py's look for a chip: sound,
it comes out correct; with the timed path broken underneath, it does not.
The faults: a denoising step that returns its state unchanged, half of each
request's images left out (the other half copied in their place), and each
request handed another request's images."""
import jax.numpy as jnp
import pytest

from bench.conftest import SEED
from bench.local import run_cell


def _wrap_client_stage(fn):
    def fault(rt):
        stage = rt._client_stage
        rt._client_stage = lambda *a, **k: fn(stage(*a, **k))
    return fault


def half_left_out(out):
    half = out.shape[1] // 2
    return out.at[:, half:].set(out[:, :half])


def answers_swapped(out):
    return jnp.roll(out, 1, axis=0)


def test_sound_run_is_correct(toy_cfg, toy_traffic):
    out = run_cell(toy_cfg, toy_traffic("unique"), SEED, 1.0)
    assert out["correct"], out["checks"]
    assert out["checks"]["repeats"][0] == 0
    assert out["e2e"]["serve_samples_per_s"] > 0
    assert out["attempted"] == out["record"]["requests"] > 0


def test_step_returning_its_state_is_caught(toy_cfg, toy_traffic,
                                            monkeypatch):
    import repro.core.sampler as sampler
    monkeypatch.setattr(sampler, "ddpm_step_batched",
                        lambda x_t, *a, **k: x_t)
    out = run_cell(toy_cfg, toy_traffic("unique"), SEED, 1.0)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [half_left_out, answers_swapped])
def test_broken_answers_are_caught(toy_cfg, toy_traffic, fault):
    out = run_cell(toy_cfg, toy_traffic("unique"), SEED, 1.0,
                   fault=_wrap_client_stage(fault))
    assert not out["correct"], out["checks"]
