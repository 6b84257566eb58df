"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (device count is locked at first jax init —
dryrun.py sets XLA_FLAGS before any import).

Target hardware: TPU v5e. 256 chips/pod as a (16, 16) ("data", "model")
mesh; the 2-pod deployment adds a leading "pod" axis — for CollaFuse this
axis is also the server/client tier split (DESIGN.md §4).
"""
from __future__ import annotations

from repro.sharding.specs import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many real devices exist (CPU tests)."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline; see EXPERIMENTS §Roofline)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
