"""Dense AllToAll kernel (equal splits).

Reference: the transport layer under fast_all_to_all
(python/triton_dist/kernels/nvidia/low_latency_all_to_all.py:36-118) —
one block per peer, putmem_nbi of that peer's range, fence, signal. The
MoE splits-aware dispatch/combine built on this lives in
``kernels/moe_all_to_all.py``.

TPU re-design: one kernel per device issues n-1 concurrent RDMAs, slice j
of the local input going to peer j's slot me, then waits for its n-1
arrivals. The recv DMA semaphore plays the role of the reference's
``signal_op/signal_wait_until`` call-count protocol.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import lang
from triton_distributed_tpu.config import interp_key
from triton_distributed_tpu.utils.testing import chaos_delay


def _a2a_kernel(n, axis, mesh_axes, x_ref, out_ref, send_sem, recv_sem):
    me = lang.my_pe(axis)
    m = x_ref.shape[0] // n

    out_ref[pl.ds(me * m, m)] = x_ref[pl.ds(me * m, m)]
    lang.barrier_all(axis, mesh_axes)

    handles = []
    for i in range(n - 1):
        pi = jax.lax.rem(me + 1 + i, n)
        peer = lang.pe_flat(axis, pi, mesh_axes)
        chaos_delay(site="all_to_all", step=i, me=me, n=n)
        handles.append(
            lang.putmem_signal_nbi_block(
                out_ref.at[pl.ds(me * m, m)],      # lands in peer's slot `me`
                x_ref.at[pl.ds(pi * m, m)],        # my rows destined to peer
                send_sem.at[i],
                recv_sem.at[i],
                peer,
            )
        )
    lang.quiet(*handles)
    for h in handles:
        h.wait_recv()


@functools.lru_cache(maxsize=256)
def _build_a2a_call(mesh_axes, axis, n, local_shape, dtype, collective_id,
                    chaos=False):
    """Bare per-device Pallas a2a call — usable inside any shard_map over
    a mesh with ``mesh_axes`` (the device variant; ≡ how flash_decode
    exposes sp_gqa_fwd_batch_decode_device for composition)."""
    assert local_shape[0] % n == 0, (
        f"per-device rows {local_shape[0]} not divisible by {n}"
    )
    call = lang.shmem_call(
        functools.partial(_a2a_kernel, n, axis, mesh_axes),
        out_shape=jax.ShapeDtypeStruct(local_shape, dtype),
        in_specs=lang.vmem_specs(1),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
        ],
        collective_id=collective_id,
        name="a2a_dense",
    )
    return lang.maybe_instrument(
        call, axis=axis, site="all_to_all", collective_id=collective_id, n=n
    )


def all_to_all_device(x_loc, n, axis, mesh_axes, *, collective_id: int = 4):
    """Dense a2a on this device's shard, callable inside shard_map.

    ``x_loc``: (rows, ...) with rows divisible by ``n`` (= size of
    ``axis``). Row block j goes to peer j's block ``me``.
    """
    if n == 1:
        return x_loc
    call = _build_a2a_call(
        tuple(mesh_axes), axis, n, tuple(x_loc.shape),
        jnp.dtype(x_loc.dtype), collective_id, interp_key(),
    )
    return call(x_loc)


@functools.lru_cache(maxsize=256)
def _build_all_to_all(mesh, axis, shape, dtype, collective_id, chaos):
    n = mesh.shape[axis]
    local_shape = (shape[0] // n,) + tuple(shape[1:])
    call = _build_a2a_call(
        mesh.axis_names, axis, n, local_shape, dtype, collective_id, chaos
    )
    fn = jax.shard_map(
        call, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    return jax.jit(fn)


def all_to_all(x, mesh, axis: str = "x", *, collective_id: int = 4):
    """Equal-split AllToAll along dim 0 (row block j of device i → row block
    i of device j). Input/output sharded P(axis) on dim 0."""
    n = mesh.shape[axis]
    if n == 1:
        return x
    fn = _build_all_to_all(
        mesh, axis, x.shape, x.dtype, collective_id, interp_key()
    )
    return fn(x)


def all_to_all_xla(x, mesh, axis: str = "x"):
    """lax.all_to_all reference implementation (correctness baseline)."""

    def per_device(xs):
        n = jax.lax.axis_size(axis)
        xs = xs.reshape((n, xs.shape[0] // n) + xs.shape[1:])
        out = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0, tiled=False)
        return out.reshape((-1,) + out.shape[2:])

    fn = jax.shard_map(
        per_device, mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False
    )
    return jax.jit(fn)(x)
