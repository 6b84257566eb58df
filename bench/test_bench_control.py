"""The control: the plain reference computed in bfloat16, put in the
program's place, against the reference in float32 at highest precision.
Each cell's limits must fail it.  At toy size on the CPU: 100 sampling
steps."""
import pytest

from bench import common, gen
from bench.conftest import SEED
from bench.local import LocalContext
from bench.loops import serve

CELLS = common.manifest()["workloads"]


@pytest.mark.parametrize("traffic", sorted({w["traffic"] for w in CELLS}))
def test_serve_control_fails_the_limits(toy_cfg, toy_traffic, traffic):
    cfg = dict(toy_cfg, T=100)
    t = toy_traffic(traffic)
    t["client_cuts"] = [25, 25]
    ctx = LocalContext(cfg, t, SEED, 0.0)
    stream = gen.ServeStream(t, cfg["unet"]["n_classes"], SEED)
    specs = [stream.take(25) + (1000 + i,) for i in range(2)]
    ref = serve.reference_outputs(ctx, specs, "float32", "highest")
    low = serve.reference_outputs(ctx, specs, "bfloat16", "default")
    checks = serve.compare(low, ref, t["check"]["limits"])
    assert any(v > lim for v, lim in checks.values() if lim is not None), \
        checks


def test_every_cell_holds_a_limit():
    for w in CELLS:
        limits = common.load_json(
            common.traffic_file(w["traffic"]))["check"]["limits"]
        assert limits and all(v > 0 for v in limits.values()), w["name"]
