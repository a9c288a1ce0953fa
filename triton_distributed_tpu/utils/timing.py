"""Timing helpers (≡ reference utils.perf_func CUDA-event timing, utils.py:186-198)."""

from __future__ import annotations

import time

import jax


def perf_func(fn, *args, iters: int = 10, warmup: int = 3):
    """Return (last_output, mean_ms). Fences device completion each phase.

    XLA has no user-visible event API like CUDA events; wall-clock around
    ``jax.block_until_ready`` on pre-compiled functions is the
    TPU-standard measurement. The ``iters`` dispatches are queued
    back to back and fenced once, so per-dispatch host overhead rides
    along as a near-constant offset — it shifts every measured config
    equally, so rankings (the autotuner's consumer) survive.
    """
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    return out, (t1 - t0) * 1e3 / iters
