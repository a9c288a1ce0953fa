"""ReduceScatter engines.

Reference: python/triton_dist/kernels/nvidia/reduce_scatter.py — 2D
scatter+ring_reduce pipeline with dedicated streams (:46-181, :692-861)
and 1D ring variants (:287-523).

TPU re-design: a reduce ring over ICI. At step s each device sends its
partial accumulation of shard ``(me+1+s)`` to its *left* neighbor while
receiving the partial of shard ``(me+2+s)`` from the right, adding its own
contribution; after n-1 steps device ``me`` holds the fully-reduced shard
``me``. The add runs on the VPU between DMAs — compute/comm overlap within
the kernel replaces the reference's multi-stream orchestration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import lang
from triton_distributed_tpu.config import fused_vmem_budget, interp_key
from triton_distributed_tpu.lang import wire as wirelib
from triton_distributed_tpu.runtime import ring_neighbors
from triton_distributed_tpu.utils.testing import chaos_delay


def ring_reduce_core(
    n, axis, mesh_axes, make_partial, out_ref, acc_ref, recv_ref, send_sem, recv_sem, ack_sem
):
    """Reduce ring with explicit flow control, parametrized over the
    per-destination contribution producer.

    ``make_partial(dst)`` returns this device's contribution to destination
    shard ``dst``; it is invoked *between* a slot DMA's start and wait, so a
    compute-heavy producer (e.g. the GEMM-RS matmul) overlaps the transfer.

    The receive buffer is double-buffered and the consumer acks its sender
    (my *right* neighbor, since data flows leftward) after folding a slot
    into the accumulator; a sender re-uses a slot only after the ack for
    its previous use. Without the ack, a fast sender two steps ahead could
    overwrite a slot the receiver hasn't consumed (semaphore credits alone
    don't stop that — they count arrivals, not consumption)."""
    me = lang.my_pe(axis)
    left, right = ring_neighbors(me, n)
    left, right = lang.pe_flat(axis, left, mesh_axes), lang.pe_flat(axis, right, mesh_axes)

    lang.neighbor_barrier(axis, left, right, site="reduce_scatter", me=me, n=n)

    # acc starts as my contribution to shard (me+1), the first one I forward.
    acc_ref[:] = make_partial(jax.lax.rem(me + 1, n))

    for s in range(n - 1):
        chaos_delay(site="reduce_scatter", step=s, me=me, n=n)
        if s >= 2:
            # left must have consumed my slot (s-2) before I rewrite it
            pltpu.semaphore_wait(ack_sem, 1)
        dma = lang.remote_copy(
            acc_ref,
            recv_ref.at[s % 2],
            send_sem.at[s % 2],
            recv_sem.at[s % 2],
            left,
        )
        dma.start()
        # produce my contribution to the next destination while the
        # accumulator is in flight
        nxt = jax.lax.rem(me + 2 + s, n)
        partial = make_partial(nxt)
        dma.wait()  # send drained (acc reusable) + my slot s%2 arrival landed
        # received: partial sum of shard (me+2+s) accumulated so far by the
        # ring to my right; fold in my own contribution.
        acc_ref[:] = recv_ref[s % 2] + partial
        # tell my sender (right neighbor) this slot is free again
        lang.signal_op(ack_sem, 1, pe=right)

    out_ref[:] = acc_ref[:]
    # drain leftover acks: n-1 received, max(n-3, 0) consumed in-loop
    pltpu.semaphore_wait(ack_sem, min(2, n - 1))


def _ring_rs_kernel(n, axis, mesh_axes, x_ref, out_ref, acc_ref, recv_ref, send_sem, recv_sem, ack_sem):
    m = out_ref.shape[0]
    ring_reduce_core(
        n,
        axis,
        mesh_axes,
        lambda dst: x_ref[pl.ds(dst * m, m)],
        out_ref,
        acc_ref,
        recv_ref,
        send_sem,
        recv_sem,
        ack_sem,
    )


def _ring_rs_kernel_w(
    n, axis, mesh_axes, quant,
    x_ref, out_ref,
    acc_ref, qbuf_ref, sbuf_ref, recvq_ref, recvs_ref,
    send_sem, recv_sem, s_send_sem, s_recv_sem, ack_sem,
):
    """Quantized-wire twin of :func:`_ring_rs_kernel` (VMEM-resident):
    each hop's partial accumulation is quantized per ROW (lang.wire,
    chunk_rows=1) into the 1-byte ``qbuf`` + f32 scale plane and both
    rails flow leftward; the receive side dequant-accumulates in f32.
    Same ack-credit flow control as ring_reduce_core (a sender may not
    rewrite a recv slot its receiver hasn't folded)."""
    me = lang.my_pe(axis)
    m = out_ref.shape[0]
    left, right = ring_neighbors(me, n)
    left = lang.pe_flat(axis, left, mesh_axes)
    right = lang.pe_flat(axis, right, mesh_axes)

    lang.neighbor_barrier(axis, left, right, site="reduce_scatter", me=me, n=n)
    acc_ref[:] = x_ref[pl.ds(jax.lax.rem(me + 1, n) * m, m)]

    for s in range(n - 1):
        chaos_delay(site="reduce_scatter", step=s, me=me, n=n)
        if s >= 2:
            pltpu.semaphore_wait(ack_sem, 1)
        # per-row symmetric quantization of the outgoing partial
        wirelib.quant_rows_into(qbuf_ref, sbuf_ref, acc_ref, quant)
        dma_q = lang.remote_copy(
            qbuf_ref, recvq_ref.at[s % 2],
            send_sem.at[s % 2], recv_sem.at[s % 2], left,
        )
        dma_s = lang.remote_copy(
            sbuf_ref, recvs_ref.at[s % 2],
            s_send_sem.at[s % 2], s_recv_sem.at[s % 2], left,
        )
        dma_q.start()
        dma_s.start()
        nxt = jax.lax.rem(me + 2 + s, n)
        dma_q.wait()   # send drained (qbuf reusable) + arrival landed
        dma_s.wait()
        wirelib.dequant_add_rows_into(
            acc_ref, recvq_ref.at[s % 2], recvs_ref.at[s % 2],
            x_ref.at[pl.ds(nxt * m, m)],
        )
        lang.signal_op(ack_sem, 1, pe=right)

    out_ref[:] = acc_ref[:]
    pltpu.semaphore_wait(ack_sem, min(2, n - 1))


def _rs_stream_kernel(
    n, axis, mesh_axes, schedule, x_hbm, out_hbm, w0, w1, r0, r1,
    copy_sem, send_sem, recv_sem, ack_sem,
):
    """HBM-streaming reduce ring: each destination's contribution is
    DMA'd straight from the HBM input into the ring slabs (no
    whole-payload VMEM residency — RS at activation-scale payloads); the
    fold-in add streams tiles through VMEM. Protocol: kernels/ring.py."""
    from triton_distributed_tpu.kernels.gemm_rs import ew_add_pipeline
    from triton_distributed_tpu.kernels.ring import reduce_ring

    m = out_hbm.shape[0]

    def partial_into(dst, dst_ref):
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(dst * m, m)], dst_ref, copy_sem
        )
        cp.start()
        cp.wait()

    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (r0, r1),
        send_sem, recv_sem, ack_sem, partial_into,
        ew_add_pipeline(m, out_hbm.shape[1], out_hbm.dtype.itemsize),
        schedule=schedule,
    )


def _rs_stream_kernel3(
    n, axis, mesh_axes, schedule, x_hbm, out_hbm, w0, w1, w2, r0, r1, r2,
    copy_sem, send_sem, recv_sem, ack_sem,
):
    """Triple-buffered twin of :func:`_rs_stream_kernel` (schedule depth
    3): identical protocol with one extra in-flight slot of slack — the
    ack credit arrives at ``s >= 3`` instead of ``s >= 2``."""
    from triton_distributed_tpu.kernels.gemm_rs import ew_add_pipeline
    from triton_distributed_tpu.kernels.ring import reduce_ring

    m = out_hbm.shape[0]

    def partial_into(dst, dst_ref):
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(dst * m, m)], dst_ref, copy_sem
        )
        cp.start()
        cp.wait()

    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1, w2), (r0, r1, r2),
        send_sem, recv_sem, ack_sem, partial_into,
        ew_add_pipeline(m, out_hbm.shape[1], out_hbm.dtype.itemsize),
        schedule=schedule,
    )


def _rs_stream_kernel_w(
    n, axis, mesh_axes, fmt, schedule,
    x_hbm, out_hbm, w0, w1,
    wq0, wq1, ws0, ws1, rq0, rq1, rs0, rs1,
    copy_sem, send_sem, recv_sem, ack_sem, s_send_sem, s_recv_sem,
):
    """Quantized-wire twin of :func:`_rs_stream_kernel` — the last bf16
    leg of the standalone RS family (ROADMAP PR-3 follow-on): the
    HBM-streaming reduce ring now ships each hop's partial as a 1-byte
    payload + per-chunk f32 scale plane (the fused gemm_rs wire
    kernel's exact shape: per-hop quant_pipeline into the wq/ws rails,
    f32 dequant-accumulate on receive — one bounded rounding per hop).
    The bf16 recv slabs are gone; arrivals land in the 1-byte rq slabs."""
    from triton_distributed_tpu.kernels.ring import RSWireRefs, reduce_ring

    m = out_hbm.shape[0]
    cols = out_hbm.shape[1]

    def partial_into(dst, dst_ref):
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(dst * m, m)], dst_ref, copy_sem
        )
        cp.start()
        cp.wait()

    wire = RSWireRefs(
        fmt=fmt, wq=(wq0, wq1), ws=(ws0, ws1), rq=(rq0, rq1), rs=(rs0, rs1),
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        quantize=wirelib.quant_pipeline(m, cols, fmt),
        dequant_add=wirelib.dequant_add_pipeline(m, cols, fmt),
    )
    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (None, None),
        send_sem, recv_sem, ack_sem, partial_into, None, wire=wire,
        schedule=schedule,
    )


def _rs_stream_kernel_w3(
    n, axis, mesh_axes, fmt, schedule,
    x_hbm, out_hbm, w0, w1, w2,
    wq0, wq1, wq2, ws0, ws1, ws2, rq0, rq1, rq2, rs0, rs1, rs2,
    copy_sem, send_sem, recv_sem, ack_sem, s_send_sem, s_recv_sem,
):
    """Triple-buffered twin of :func:`_rs_stream_kernel_w` (schedule
    depth 3): every wire rail grows a third slot."""
    from triton_distributed_tpu.kernels.ring import RSWireRefs, reduce_ring

    m = out_hbm.shape[0]
    cols = out_hbm.shape[1]

    def partial_into(dst, dst_ref):
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(dst * m, m)], dst_ref, copy_sem
        )
        cp.start()
        cp.wait()

    wire = RSWireRefs(
        fmt=fmt, wq=(wq0, wq1, wq2), ws=(ws0, ws1, ws2),
        rq=(rq0, rq1, rq2), rs=(rs0, rs1, rs2),
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        quantize=wirelib.quant_pipeline(m, cols, fmt),
        dequant_add=wirelib.dequant_add_pipeline(m, cols, fmt),
    )
    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1, w2), (None, None, None),
        send_sem, recv_sem, ack_sem, partial_into, None, wire=wire,
        schedule=schedule,
    )


@functools.lru_cache(maxsize=256)
def _build_rs_stream_w(mesh, axis, rows, cols, dtype, stacked,
                       collective_id, ikey, wire, schedule=None):
    """Quantized-wire HBM-streaming reduce ring (2-D payloads, per-chunk
    scales — the lang.wire streaming layout of the fused gemm_rs wire)."""
    from triton_distributed_tpu.config import compiling_for_tpu

    wirelib.require_inkernel(wire, "reduce_scatter")
    n = mesh.shape[axis]
    m_local = rows // n
    d = 2 if schedule is None else int(schedule.depth)
    fmt = wirelib.make_wire_format(wire, m_local, strict=compiling_for_tpu())
    assert fmt is not None, (wire, m_local)   # gated by the entry
    slab = jax.ShapeDtypeStruct((m_local, cols), dtype)
    qslab = jax.ShapeDtypeStruct((m_local, cols), fmt.wire_dtype)
    sslab = jax.ShapeDtypeStruct(
        (fmt.chunks(m_local), wirelib.SCALE_LANES), jnp.float32
    )
    kernel = _rs_stream_kernel_w if d == 2 else _rs_stream_kernel_w3
    call = lang.shmem_call(
        functools.partial(
            kernel, n, axis, mesh.axis_names, fmt, schedule
        ),
        # out + bf16 work slots + quantized work/scale + recv/scale slots
        # (HBM workspaces ride as ANY outputs — Mosaic has no HBM scratch)
        out_shape=[slab] + [slab] * d
                  + [qslab] * d + [sslab] * d
                  + [qslab] * d + [sslab] * d,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + 5 * d),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.REGULAR,
            pltpu.SemaphoreType.DMA((d,)),   # scale rail
            pltpu.SemaphoreType.DMA((d,)),
        ],
        collective_id=collective_id,
        name=f"rs_ring_stream_{wire}w",
    )
    call = lang.maybe_instrument(
        call, axis=axis, site="reduce_scatter", collective_id=collective_id,
        n=n,
    )
    body = (lambda s: call(s[0])[0]) if stacked else (lambda s: call(s)[0])
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis) if stacked else P(None),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _build_rs_stream(mesh, axis, rows, cols, dtype, stacked, collective_id,
                     ikey, schedule=None):
    n = mesh.shape[axis]
    d = 2 if schedule is None else int(schedule.depth)
    slab = jax.ShapeDtypeStruct((rows // n, cols), dtype)
    kernel = _rs_stream_kernel if d == 2 else _rs_stream_kernel3
    call = lang.shmem_call(
        functools.partial(kernel, n, axis, mesh.axis_names, schedule),
        # ring slabs ride as extra ANY outputs (Mosaic has no HBM scratch)
        out_shape=[slab] * (1 + 2 * d),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + 2 * d),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        collective_id=collective_id,
        name="rs_ring_stream",
    )
    body = (lambda s: call(s[0])[0]) if stacked else (lambda s: call(s)[0])
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis) if stacked else P(None),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


def _vmem_ring_fits(n, local_shape, itemsize) -> bool:
    """The VMEM ring keeps the whole per-device contribution + acc + two
    recv slots resident; prefer it for small payloads (lower latency),
    stream through HBM otherwise."""
    slab = int(np.prod(local_shape)) * itemsize
    return (n + 3) * slab <= fused_vmem_budget() // 2


def _streamable(m_local: int, cols: int, itemsize: int) -> bool:
    """The streaming engine's fold-in add needs a TPU-lowerable divisor
    blocking of the (m_local, cols) slab (≡ gemm_rs's pick_mm_blocks
    guard); shapes without one must stay on the VMEM ring rather than
    crash at Mosaic trace time."""
    from triton_distributed_tpu.config import compiling_for_tpu
    from triton_distributed_tpu.kernels.ag_gemm import _divisor_block

    strict = compiling_for_tpu()
    return (
        _divisor_block(m_local, 512, 8 * (4 // itemsize), strict) is not None
        and _divisor_block(cols, 2048, 128, strict) is not None
    )


def _resolve_rs_wire(wire_dtype, rows, cols, n, itemsize):
    """The wire :func:`reduce_scatter` will actually ship: None unless
    the payload reshapes to 2-D columns wide enough that the per-row
    scale plane saves bytes. 'auto' uses the standalone-ring byte
    threshold (a reduce ring is pure comm, like a gather). 'int8-mxu'
    carries its int8 payload — a reduce ring accumulates, it has no MXU
    consumer to fold scales into."""
    w = wirelib.wire_payload(wirelib.normalize_wire(wire_dtype))
    if w is None:
        return None
    eligible = rows % n == 0 and cols * itemsize > cols + wirelib.SCALE_LANES * 4
    if w == "auto":
        if not eligible:
            return None
        from triton_distributed_tpu.runtime.topology import (
            auto_allgather_wire,
        )

        return auto_allgather_wire((rows // n) * cols * itemsize)
    if not eligible:
        raise ValueError(
            f"reduce_scatter wire_dtype={w!r} needs a 2-D-reshapeable "
            f"payload with cols·itemsize > cols + "
            f"{wirelib.SCALE_LANES * 4} (a pinned wire format is a "
            f"contract); got rows={rows} cols={cols} itemsize={itemsize}"
        )
    return w


def reduce_scatter(
    x, mesh, axis: str = "x", *, stacked: bool = False, collective_id: int = 3,
    wire_dtype=None, schedule=None,
):
    """ReduceScatter: sums per-device (M, ...) contributions and scatters the
    row-shards along ``axis``.

    ``stacked=False``: ``x`` is a replicated (M, ...) array (every device
    contributes the same values). ``stacked=True``: ``x`` is (n, M, ...)
    sharded on dim 0 — device i contributes slice ``x[i]`` (the normal case,
    e.g. partial GEMM outputs).

    Two engines by payload size: the VMEM-resident ring (low latency) and
    the HBM-streaming ring (no VMEM cap — activation-scale payloads;
    trailing dims ride as a free 2D view of the contiguous array).

    ``wire_dtype``: quantized ring wire ('fp8'/'int8' — per-hop
    quantized partials with f32 scales, f32 dequant-accumulate; 'auto'
    — compressed above the standalone-ring byte threshold). Carried by
    the VMEM ring (per-row scales), the HBM-streaming engine (per-chunk
    scales via the fused gemm_rs wire pipelines — round 8) and the XLA
    twin; only payloads too ragged to stream fall back to the bf16
    wire.

    ``schedule``: an explicit :class:`~triton_distributed_tpu.tune.schedule.
    RingSchedule` for the HBM-streaming engines (``None`` loads any
    persisted searched winner, falling back to the canonical default).
    The VMEM rings ignore it — they have no streaming schedule to vary.

    Host entry ≡ reference ``reduce_scatter_2d_op`` (reduce_scatter.py:863).
    """
    n = mesh.shape[axis]
    full_shape = x.shape[1:] if stacked else x.shape
    rows = full_shape[0]
    cols = int(np.prod(full_shape[1:], dtype=np.int64)) if len(full_shape) > 1 else 1
    if n == 1:
        return x[0] if stacked else x
    assert full_shape[0] % n == 0, f"dim0 {full_shape[0]} not divisible by {n}"
    local_shape = (full_shape[0] // n,) + tuple(full_shape[1:])
    wire = _resolve_rs_wire(wire_dtype, rows, cols, n, x.dtype.itemsize)
    from triton_distributed_tpu.tune.schedule import resolve_schedule

    sched = resolve_schedule(
        "reduce_scatter.stream", (rows, cols), (n,), wire, schedule
    )
    if wire == "fp8" and not wirelib.inkernel_wire_ok("fp8"):
        # the Pallas VMEM ring dequantizes in-kernel; this Mosaic lacks
        # the f8 casts — explicit fp8 raises, auto stays exact
        if wirelib.normalize_wire(wire_dtype) == "fp8":
            wirelib.require_inkernel("fp8", "reduce_scatter")
        wire = None
    if wire is not None:
        # the wire ring is VMEM-resident; its working set is ~half the
        # bf16 ring's (1-byte recv slots), so the same fit gate applies
        if _vmem_ring_fits(n, local_shape, x.dtype.itemsize):
            x2d = x.reshape(((n,) if stacked else ()) + (rows, cols))
            fn = _build_reduce_scatter_w(
                mesh, axis, (rows, cols), x.dtype, stacked, collective_id,
                interp_key(), wire,
            )
            return fn(x2d).reshape(full_shape)
        from triton_distributed_tpu.config import compiling_for_tpu

        if _streamable(rows // n, cols, x.dtype.itemsize) and \
                wirelib.wire_blockable(
                    rows // n, cols, wire, compiling_for_tpu()
                ):
            # activation-scale payloads: the HBM-streaming wire ring
            # (per-hop quant pipelines + scale rail, the fused gemm_rs
            # wire shape — the last bf16 leg of the standalone RS)
            x2d = x.reshape(((n,) if stacked else ()) + (rows, cols))
            fn = _build_rs_stream_w(
                mesh, axis, rows, cols, x.dtype, stacked, collective_id,
                interp_key(), wire, sched,
            )
            return fn(x2d).reshape(full_shape)
        _warn_rs_wire_once()
        wire = None
    if not _vmem_ring_fits(n, local_shape, x.dtype.itemsize) and _streamable(
        rows // n, cols, x.dtype.itemsize
    ):
        x2d = x.reshape(((n,) if stacked else ()) + (rows, cols))
        fn = _build_rs_stream(
            mesh, axis, rows, cols, x.dtype, stacked, collective_id,
            interp_key(), sched,
        )
        return fn(x2d).reshape(full_shape)
    fn = _build_reduce_scatter(
        mesh, axis, tuple(full_shape), x.dtype, stacked, collective_id,
        interp_key(),
    )
    return fn(x)


_rs_wire_warned = [False]


def _warn_rs_wire_once():
    if not _rs_wire_warned[0]:
        _rs_wire_warned[0] = True
        import logging

        logging.getLogger(__name__).warning(
            "reduce_scatter: payload exceeds the VMEM ring and admits "
            "no streaming wire blocking; shipping the bf16 wire"
        )


@functools.lru_cache(maxsize=256)
def _build_reduce_scatter_w(mesh, axis, full_shape, dtype, stacked,
                            collective_id, chaos, wire):
    """Quantized-wire VMEM reduce ring (2-D payloads; per-row scales)."""
    wirelib.require_inkernel(wire, "reduce_scatter")
    n = mesh.shape[axis]
    m_local = full_shape[0] // n
    cols = full_shape[1]
    wdt = jnp.dtype(
        jnp.float8_e4m3fn if wire == "fp8" else jnp.int8
    )
    call = lang.shmem_call(
        functools.partial(_ring_rs_kernel_w, n, axis, mesh.axis_names, wire),
        out_shape=jax.ShapeDtypeStruct((m_local, cols), dtype),
        in_specs=lang.vmem_specs(1),
        scratch_shapes=[
            pltpu.VMEM((m_local, cols), dtype),                   # acc
            pltpu.VMEM((m_local, cols), wdt),                     # qbuf
            pltpu.VMEM((m_local, wirelib.SCALE_LANES), jnp.float32),
            pltpu.VMEM((2, m_local, cols), wdt),                  # recv q
            pltpu.VMEM((2, m_local, wirelib.SCALE_LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),                        # scale rail
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        collective_id=collective_id,
        name=f"rs_ring_{wire}w",
    )
    call = lang.maybe_instrument(
        call, axis=axis, site="reduce_scatter", collective_id=collective_id,
        n=n,
    )
    body = (lambda s: call(s[0])) if stacked else call
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis) if stacked else P(None),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _build_reduce_scatter(mesh, axis, full_shape, dtype, stacked, collective_id, chaos):
    n = mesh.shape[axis]
    m_local = full_shape[0] // n
    local_shape = (m_local,) + tuple(full_shape[1:])

    call = lang.shmem_call(
        functools.partial(_ring_rs_kernel, n, axis, mesh.axis_names),
        out_shape=jax.ShapeDtypeStruct(local_shape, dtype),
        in_specs=lang.vmem_specs(1),
        scratch_shapes=[
            pltpu.VMEM(local_shape, dtype),
            pltpu.VMEM((2,) + local_shape, dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
        collective_id=collective_id,
        name="rs_ring",
    )
    call = lang.maybe_instrument(
        call, axis=axis, site="reduce_scatter", collective_id=collective_id,
        n=n,
    )
    body = (lambda s: call(s[0])) if stacked else call
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis) if stacked else P(None),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)


def reduce_scatter_xla(x, mesh, axis: str = "x", *, stacked: bool = False,
                       wire_dtype=None):
    """lax.psum_scatter reference implementation (correctness baseline).

    ``wire_dtype`` ('fp8'/'int8'): a manual ppermute reduce ring whose
    hops carry per-row-quantized partials (lang.wire, chunk_rows=1) —
    the numerics twin of the Pallas wire ring, and a genuine byte saver
    on DCN where psum_scatter cannot compress."""
    wire = wirelib.normalize_wire(wire_dtype)
    assert wire != "auto", "resolve 'auto' at the reduce_scatter entry"
    n = mesh.shape[axis]
    full_shape = x.shape[1:] if stacked else x.shape
    rows = full_shape[0]
    cols = int(np.prod(full_shape[1:], dtype=np.int64)) if len(full_shape) > 1 else 1
    if wire is not None:
        fmt = wirelib.WireFormat(quant=wire, chunk_rows=1)
        m_local = rows // n

        def body(s):
            s = s[0] if stacked else s
            s2 = s.reshape(rows, cols)
            me = jax.lax.axis_index(axis)
            perm = [(i, (i - 1) % n) for i in range(n)]

            def stripe(i):
                return jax.lax.dynamic_slice(
                    s2, (i * m_local, 0), (m_local, cols)
                )

            def step(h, acc):
                q, sc = wirelib.quantize_slab(acc, fmt)
                q = jax.lax.ppermute(q, axis, perm=perm)
                sc = jax.lax.ppermute(sc, axis, perm=perm)
                arrived = wirelib.dequantize_slab(q, sc, fmt, jnp.float32)
                nxt = jax.lax.rem(me + 2 + h, n)
                return (arrived + stripe(nxt).astype(jnp.float32)).astype(
                    s.dtype
                )

            acc = stripe(jax.lax.rem(me + 1, n))
            acc = jax.lax.fori_loop(0, n - 1, step, acc)
            return acc.reshape((m_local,) + tuple(full_shape[1:]))
    else:
        def body(s):
            s = s[0] if stacked else s
            return jax.lax.psum_scatter(
                s, axis, scatter_dimension=0, tiled=True
            )

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis) if stacked else P(None),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(fn)(x)
