"""Shared tutorial bootstrap: an 8-device virtual CPU mesh when no
multi-chip TPU slice is attached (the conftest env dance), real devices
otherwise. Every tutorial is a standalone script: `python tutorials/NN-*.py`.
"""

import os
import pathlib
import sys

# run from anywhere: the repo root is the package root
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def get_mesh(min_devices: int = 8):
    """An ``(min_devices,)`` mesh named "x". Default: a virtual CPU mesh
    (the demos run anywhere); TDTPU_LOCAL_DEVICES (the launch.sh knob)
    overrides the size, and TDTPU_TUTORIAL_TPU=1 runs on a real slice
    with enough chips instead."""
    import jax

    min_devices = int(os.environ.get("TDTPU_LOCAL_DEVICES", min_devices))
    if os.environ.get("TDTPU_TUTORIAL_TPU") != "1":
        try:
            # Must happen before any backend is touched.
            jax.config.update("jax_num_cpu_devices", min_devices)
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu import runtime
    from triton_distributed_tpu.config import enable_compile_cache

    enable_compile_cache()
    runtime.initialize_distributed()
    devs = jax.devices()
    assert len(devs) >= min_devices, (
        f"need {min_devices} devices, have {len(devs)}"
    )
    return Mesh(np.asarray(devs[:min_devices]), ("x",))
