"""Toy sizes for the benchmark's CPU tests: the cells' own traffic and
limits, a small U-Net, few steps and one cut where a cell has several."""
import pytest

from bench import common

TOY_UNET = dict(image_size=8, channels=3, base_width=8, width_mults=[1],
                n_res_blocks=1, attn_resolutions=[8], n_heads=2,
                time_dim=16, n_classes=12, groupnorm_groups=4, dropout=0.0,
                dtype="float32")
SEED = 2 ** 31 + 4242


@pytest.fixture
def toy_cfg():
    return dict(name="toy", unet=dict(TOY_UNET), T=16, beta_min=1e-4,
                beta_max=0.02)


@pytest.fixture
def toy_traffic():
    def make(name):
        t = common.load_json(common.traffic_file(name))
        t.update(client_cuts=[4, 4], images_per_request=2, max_wave=2)
        return t
    return make
