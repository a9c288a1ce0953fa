"""Process bootstrap and mesh construction.

Reference equivalent: ``utils.initialize_distributed`` (python/triton_dist/
utils.py:91-111) which reads RANK/WORLD_SIZE env, inits NCCL, then boots
NVSHMEM by broadcasting a unique id. On TPU the whole chain collapses into
``jax.distributed.initialize`` (multi-host rendezvous via the coordinator)
plus ``jax.devices()`` mesh discovery — symmetric memory needs no separate
runtime because every shard_map program allocates identically on every
device.
"""

from __future__ import annotations

import logging
import os
import random
import time
from dataclasses import dataclass
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)


@dataclass
class DistContext:
    """Handle describing this process's view of the distributed system."""

    mesh: Mesh
    rank: int                 # process index (host), not device index
    world_size: int           # number of processes
    num_devices: int          # global device count
    local_devices: tuple      # devices attached to this process
    axis_name: str = "x"

    @property
    def is_multihost(self) -> bool:
        return self.world_size > 1


_CONTEXT: DistContext | None = None


def initialize_distributed(
    axis_name: str = "x",
    mesh_shape: Sequence[int] | None = None,
    axis_names: Sequence[str] | None = None,
    seed: int | None = 42,
) -> DistContext:
    """Initialize the distributed runtime and build the default mesh.

    Multi-host: controlled by the standard JAX env vars
    (``COORDINATOR_ADDRESS``/``JAX_COORDINATOR_ADDRESS``, ``JAX_NUM_PROCESSES``,
    ``JAX_PROCESS_ID``) which ``launch.sh`` sets; on a single host this is a
    no-op and the mesh covers the locally visible devices.

    Returns a :class:`DistContext`. Mirrors reference utils.py:91-111 but the
    bootstrap (NCCL pg + NVSHMEM uniqueid broadcast) is replaced by
    ``jax.distributed.initialize``.
    """
    global _CONTEXT
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    nproc = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    # Must run before any backend touch: jax.distributed.initialize has to
    # precede backend initialization, so the "already initialized" guard
    # checks the distributed client state, not jax.process_count().
    already = jax.distributed.is_initialized()
    if coord and nproc > 1 and not already:
        _initialize_with_retry(
            coord, nproc, int(os.environ.get("JAX_PROCESS_ID", "0"))
        )

    devices = jax.devices()
    if mesh_shape is None:
        mesh_devices = np.asarray(devices)
        mesh = Mesh(mesh_devices, (axis_name,))
    else:
        axis_names = tuple(axis_names or _default_axis_names(len(mesh_shape)))
        mesh_devices = np.asarray(devices).reshape(tuple(mesh_shape))
        mesh = Mesh(mesh_devices, axis_names)
        # keep ctx.axis_name pointing at a real axis of the mesh (the
        # last/innermost axis is the conventional comm axis)
        if axis_name not in axis_names:
            axis_name = axis_names[-1]

    ctx = DistContext(
        mesh=mesh,
        rank=jax.process_index(),
        world_size=jax.process_count(),
        num_devices=len(devices),
        local_devices=tuple(jax.local_devices()),
        axis_name=axis_name,
    )
    _CONTEXT = ctx
    if seed is not None:
        init_seed(ctx.rank, seed)
    return ctx


def _initialize_with_retry(
    coord: str,
    nproc: int,
    pid: int,
    *,
    retries: int | None = None,
    backoff: float | None = None,
    cap: float | None = None,
    sleep=time.sleep,
    initialize=None,
) -> None:
    """``jax.distributed.initialize`` with bounded exponential backoff.

    Multi-host rendezvous is the single flakiest step of a pod-scale
    launch: the coordinator process may simply not be listening yet
    (scheduler skew), or a transient DNS/conntrack blip drops the first
    connection. The reference framework retries nothing — one refused
    connection kills the whole job. Here each attempt backs off
    ``backoff * 2**attempt`` seconds (clamped to ``cap``) with ±50%
    jitter so restarting workers don't re-dogpile the coordinator, and
    the terminal failure names the coordinator address instead of
    surfacing the raw rendezvous exception from deep inside jax.

    Knobs (env): ``TDTPU_BOOTSTRAP_RETRIES`` (default 5 attempts),
    ``TDTPU_BOOTSTRAP_BACKOFF`` (base seconds, default 0.5),
    ``TDTPU_BOOTSTRAP_BACKOFF_CAP`` (default 8.0).
    """
    retries = retries if retries is not None else int(
        os.environ.get("TDTPU_BOOTSTRAP_RETRIES", "5")
    )
    backoff = backoff if backoff is not None else float(
        os.environ.get("TDTPU_BOOTSTRAP_BACKOFF", "0.5")
    )
    cap = cap if cap is not None else float(
        os.environ.get("TDTPU_BOOTSTRAP_BACKOFF_CAP", "8.0")
    )
    initialize = initialize or jax.distributed.initialize
    retries = max(int(retries), 1)
    last = None
    for attempt in range(retries):
        try:
            initialize(
                coordinator_address=coord,
                num_processes=nproc,
                process_id=pid,
            )
            return
        except Exception as e:                  # noqa: BLE001 — rendezvous
            last = e                            # errors surface as various
            if attempt == retries - 1:          # RuntimeError/XlaRuntimeError
                break                           # subclasses across jax versions
            delay = min(cap, backoff * (2.0 ** attempt))
            delay *= 0.5 + random.random()      # ±50% de-dogpile jitter
            logger.warning(
                "jax.distributed.initialize attempt %d/%d against "
                "coordinator %s failed (%s); retrying in %.2fs",
                attempt + 1, retries, coord, e, delay,
            )
            sleep(delay)
    try:
        from triton_distributed_tpu.runtime import health

        health.broadcast_signal(
            "bootstrap_exhausted", f"host:{pid}",
            detail=f"rendezvous with {coord!r} failed after {retries} "
                   f"attempt(s): {last}",
        )
    except Exception:           # the ledger must not mask the real error
        logger.exception("bootstrap: health broadcast failed")
    raise RuntimeError(
        f"jax.distributed.initialize failed after {retries} attempt(s) "
        f"rendezvousing with coordinator {coord!r} "
        f"(num_processes={nproc}, process_id={pid}). Check that the "
        "coordinator process is reachable on that address/port and that "
        "JAX_NUM_PROCESSES/JAX_PROCESS_ID are consistent across hosts. "
        f"Last error: {last}"
    ) from last


def _default_axis_names(ndim: int) -> tuple[str, ...]:
    base = ("dp", "pp", "tp", "sp", "ep")
    if ndim <= len(base):
        return base[:ndim]
    return tuple(f"ax{i}" for i in range(ndim))


def init_seed(rank: int, seed: int = 42) -> None:
    """Seed host-side RNGs deterministically per rank (reference utils.py:75-88)."""
    np.random.seed(seed + rank)
    try:
        import random

        random.seed(seed + rank)
    except Exception:
        pass


def get_context() -> DistContext:
    if _CONTEXT is None:
        return initialize_distributed()
    return _CONTEXT


def finalize_distributed() -> None:
    global _CONTEXT
    _CONTEXT = None
    if jax.process_count() > 1:
        jax.distributed.shutdown()
