"""Start-up shared by the entry points: ``launch/collab_serve.py``,
``launch/collab_train.py`` and the repo-root ``chip_smoke.py``.

* ``enable_compile_cache`` turns on JAX's persistent compilation cache,
  so a second process (or a resumed runtime in the same process) loads
  the compiled round and sampling programs instead of compiling them
  again.  Call it before anything compiles.
* ``add_unet_config_arg`` / ``apply_unet_config`` are the one way both
  CLIs choose the U-Net preset by name.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Optional

import jax

from repro.configs.ddpm_unet import CONFIG

# A fixed path inside the checkout (never a temporary or per-process name),
# so every process run from this checkout finds what an earlier one wrote.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# "small": build_denoiser's reduced preset, resized to the CLI's image
# size and label count (the CPU smokes); "paper": configs/ddpm_unet.CONFIG
# exactly as published — no width, depth or resolution cut.
UNET_CONFIGS = {"small": None, "paper": CONFIG}


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache on an accelerator and
    return its directory (None on the CPU backend, whose executables are
    tied to the host's CPU features).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    no other directory is set here; otherwise the cache lives at
    ``COMPILE_CACHE_DIR``.  Every program is written, however quickly it
    compiled, so a second process can load all of them."""
    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def add_unet_config_arg(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--unet-config", choices=tuple(UNET_CONFIGS),
                    default="small",
                    help="U-Net preset: small (reduced, resized to "
                         "--image-size/--n-classes) or paper "
                         "(configs/ddpm_unet.CONFIG as published; selects "
                         "the U-Net, and its 32x32 images and 8 labels "
                         "replace --image-size/--n-classes)")


def apply_unet_config(args: argparse.Namespace) -> None:
    """A named full-size preset selects the U-Net denoiser
    (``args.denoiser``), and its image size and label count replace
    ``--image-size``/``--n-classes``."""
    cfg = UNET_CONFIGS[args.unet_config]
    if cfg is not None:
        args.denoiser = "unet"
        args.image_size, args.n_classes = cfg.image_size, cfg.n_classes
