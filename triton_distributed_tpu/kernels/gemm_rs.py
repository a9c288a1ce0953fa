"""GEMM-ReduceScatter: row-parallel TP overlap of matmul with reduction.

Reference: python/triton_dist/kernels/nvidia/gemm_reduce_scatter.py —
producer GEMM in rank-swizzled tile order signalling per-destination
barriers (:124-235), consumer ``reduce_scatter_2d_op`` on separate
streams (reduce_scatter.py:863), host entries ``gemm_rs_op``/``gemm_rs``
(:498-560).

TPU re-design: a reduce ring in which each step's contribution is
*computed into the ring* by the MXU while the previous partial is in
flight — the matmul for the next destination shard overlaps the RDMA of
the current accumulator, replacing the reference's GEMM-stream /
RS-stream pair with single-kernel software pipelining. Tile order is
rank-swizzled by construction: device ``me`` computes destination shards
``me+1, me+2, …, me`` so every shard's partial flows leftward and ends
fully reduced on its owner.

The fused engine is HBM-streaming: operands and the ring slabs live in
HBM (ANY memory space); the per-destination matmul and the fold-in add
are tiled ``emit_pipeline`` loops whose blocks are double-buffered
HBM→VMEM DMAs. There is no whole-working-set VMEM gate — the engine
engages at the north-star shapes (the whole point of the reference's
persistent producer GEMM, gemm_reduce_scatter.py:124-235).

Engines: ``PALLAS_FUSED`` (streaming ring, ICI), ``XLA_RING``
(ppermute+dot loop, DCN path), ``XLA_NAIVE`` (dot → psum_scatter
baseline, ≡ the torch reference impl in test_gemm_rs.py).
"""

from __future__ import annotations

import enum
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import lang
from triton_distributed_tpu.config import fused_vmem_budget, interp_key
from triton_distributed_tpu.kernels.ag_gemm import (
    _divisor_block,
    _warn_once,
    mm_pipeline,
    pick_mm_blocks,
)
from triton_distributed_tpu.kernels.ring import RSWireRefs, reduce_ring
from triton_distributed_tpu.lang import wire as wirelib
from triton_distributed_tpu.runtime import (
    LinkKind,
    detect_topology,
    mesh_axes_size,
)


#: GEMM-RS tile targets, swept on a v5e at the Llama-7B down-projection
#: north-star shard (8192×3584 @ 3584×8192 bf16): (512, whole-K, 1024) →
#: 167 TFLOP/s vs 147 for the shared ag_gemm targets. The 4096 bk target
#: yields whole-K for K-shards ≤ 4096 and shrinks under the VMEM budget
#: elsewhere.
_RS_TILE_TARGETS = (512, 4096, 1024)


class GemmRSMethod(enum.Enum):
    PALLAS_FUSED = "pallas_fused"
    XLA_RING = "xla_ring"
    XLA_NAIVE = "xla_naive"


def ew_add_pipeline(m, n, itemsize):
    """Tiled elementwise-add pipeline over HBM refs: dst = a + b.
    Blocks stream through VMEM double-buffered; used to fold a received
    ring partial into the locally computed one. Under an active
    shmemlint recorder the fold is recorded as an AddEvent — the
    provenance edge the SL008 reduce-contract pass accumulates — and
    the value-level pipeline is skipped (evaluator pipelines only ever
    recorded access hulls)."""
    from triton_distributed_tpu.config import compiling_for_tpu

    bm = _divisor_block(m, 512, 8 * (4 // itemsize), compiling_for_tpu())
    bn = _divisor_block(n, 2048, 128, compiling_for_tpu())

    def inner(a_ref, b_ref, o_ref):
        o_ref[...] = a_ref[...] + b_ref[...]

    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    pipe = pltpu.emit_pipeline(
        inner, grid=(m // bm, n // bn), in_specs=[spec, spec], out_specs=[spec]
    )

    def run(a_hbm, b_hbm, o_hbm):
        from triton_distributed_tpu.analysis import events

        rec = events.active_recorder()
        if rec is not None:
            rec.emit(events.AddEvent(
                a_region=a_hbm.region(), b_region=b_hbm.region(),
                dst_region=o_hbm.region(),
            ))
            return
        pipe(a_hbm, b_hbm, o_hbm)

    return run


def mm_q8_rs_pipeline(mb, nb, kb, bm, bk, bn, fmt, acc_ref, *, m_off=0):
    """s8×s8→s32 producer for the wire reduce ring: the partial runs on
    the MXU's native int8 path (int8 weights + activations) and is
    quantized for the wire STRAIGHT OFF THE ACCUMULATOR — the epilogue
    (mm_q8_pipeline's ``as·bs`` rescale shape) writes the f32-rescaled
    partial slab AND its wire copy (int8 payload + per-chunk scale row)
    in one pass, so the separate quant_pipeline read-back over HBM is
    gone. Requires ``nb == 1`` (the out tile spans every column, so a
    row block IS a scale chunk: ``fmt.chunk_rows == bm``)."""
    assert nb == 1 and fmt.chunk_rows == bm, (nb, fmt.chunk_rows, bm)
    qmax = fmt.qmax

    def inner(aq_ref, as_ref, bq_ref, bs_ref, o_ref, q_ref, s_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            aq_ref[...], bq_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

        @pl.when(pl.program_id(2) == kb - 1)
        def _():
            # rank-1 a_scale[chunk]·b_scale[n] rescale on the s32
            # accumulator (mm_q8_pipeline's epilogue shape) → the f32
            # partial tile; its wire quantization happens HERE, off the
            # same accumulator values, before the tile leaves VMEM
            t = acc_ref[...].astype(jnp.float32) * (
                as_ref[:, :1] * bs_ref[...]
            )
            o_ref[...] = t.astype(o_ref.dtype)
            row = jnp.max(jnp.abs(t), axis=1, keepdims=True)
            chunk = jnp.max(row, axis=0, keepdims=True)
            scale = jnp.maximum(chunk, 1e-12) / qmax
            s_ref[...] = jnp.broadcast_to(
                scale, (1, wirelib.SCALE_LANES)
            ).astype(jnp.float32)
            y = t / scale
            if fmt.quant == "int8":
                y = jnp.clip(jnp.round(y), -127, 127)
            q_ref[...] = y.astype(q_ref.dtype)

    pipe = pltpu.emit_pipeline(
        inner,
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (m_off + i, kk)),
            pl.BlockSpec(
                (1, wirelib.SCALE_LANES), lambda i, j, kk: (m_off + i, 0)
            ),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec(
                (1, wirelib.SCALE_LANES), lambda i, j, kk: (i, 0)
            ),
        ],
    )

    def run(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_hbm, wq_hbm, ws_hbm):
        from triton_distributed_tpu.analysis import events

        rec = events.active_recorder()
        if rec is not None:
            # symbolic twin: the product is locally-owned data in the
            # work slab, immediately re-quantized into the wire rails —
            # the same Write+Quant provenance mm_pipeline+quant_pipeline
            # would leave, minus the value-level HBM read-back
            rec.emit(events.WriteEvent(region=dst_hbm.region()))
            rec.emit(events.QuantEvent(
                src_region=dst_hbm.region(), q_region=wq_hbm.region(),
                s_region=ws_hbm.region(), chunk_rows=fmt.chunk_rows,
            ))
            return
        pipe(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_hbm, wq_hbm, ws_hbm)

    return run


def mm_q8_partial_pipeline(mb, nb, kb, bm, bk, bn, acc_ref, *, m_off=0):
    """s8×s8→s32 producer WITHOUT the fused wire epilogue: the rescaled
    f32 partial lands in the destination slab only, and the ring
    harness's separate ``quant_pipeline`` read-back pass makes the wire
    copy afterwards (the ``GridSchedule.epilogue="readback"`` placement
    — one extra HBM round-trip per hop, but no ``nb == 1`` /
    chunk-geometry constraint on the out tiling)."""

    def inner(aq_ref, as_ref, bq_ref, bs_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            aq_ref[...], bq_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

        @pl.when(pl.program_id(2) == kb - 1)
        def _():
            o_ref[...] = (
                acc_ref[...].astype(jnp.float32)
                * (as_ref[:, :1] * bs_ref[...])
            ).astype(o_ref.dtype)

    pipe = pltpu.emit_pipeline(
        inner,
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (m_off + i, kk)),
            pl.BlockSpec(
                (1, wirelib.SCALE_LANES), lambda i, j, kk: (m_off + i, 0)
            ),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
    )

    def run(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_hbm):
        from triton_distributed_tpu.analysis import events

        rec = events.active_recorder()
        if rec is not None:
            # symbolic twin: a locally computed partial in the work slab
            # (the wire quantization is the harness's read-back pass)
            rec.emit(events.WriteEvent(region=dst_hbm.region()))
            return
        pipe(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_hbm)

    return run


def _fused_kernel(
    n, axis, mesh_axes, blocks, schedule,
    a_hbm, b_hbm, out_hbm, w0, w1, r0, r1, acc_ref, send_sem, recv_sem, ack_sem,
):
    """HBM-streaming compute-into-the-ring GEMM-RS.

    Step ``s`` (destination shard ``me+1+s``): the matmul pipeline for the
    *next* destination runs between a ring DMA's start and its recv wait,
    so each transfer hides under a full shard matmul. Double-buffered work
    and recv slabs with the ack-based flow control of
    kernels/reduce_scatter.py:ring_reduce_core (a sender may not rewrite a
    slot its receiver hasn't folded in — semaphore credits count arrivals,
    not consumption)."""
    m_local = out_hbm.shape[0]
    n_out = out_hbm.shape[1]
    k = a_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m_local // bm, n_out // bn, k // bk

    def partial_into(dst, dst_ref):
        # dst_ref = A[dst·m_local : (dst+1)·m_local, :] @ B   (streamed)
        mm_pipeline(mb, nb, kb, bm, bk, bn, acc_ref, m_off=dst * mb, out_m_off=0)(
            a_hbm, b_hbm, dst_ref
        )

    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (r0, r1),
        send_sem, recv_sem, ack_sem, partial_into,
        ew_add_pipeline(m_local, n_out, out_hbm.dtype.itemsize),
        site="gemm_rs", schedule=schedule,
    )


def _fused_kernel_w(
    n, axis, mesh_axes, blocks, fmt, schedule,
    a_hbm, b_hbm, out_hbm, w0, w1,
    wq0, wq1, ws0, ws1, rq0, rq1, rs0, rs1,
    acc_ref, send_sem, recv_sem, ack_sem, s_send_sem, s_recv_sem,
):
    """Quantized-wire twin of :func:`_fused_kernel`: each hop's freshly
    computed partial is quantized to the lang.wire layout before its
    RDMA, and the receive side dequant-accumulates in f32 (one rounding
    per hop — the RS-side contract that keeps reduction error bounded).
    The bf16 recv slabs of the raw engine are gone; the wire lands in
    the 1-byte rq slabs + rs scale planes."""
    m_local = out_hbm.shape[0]
    n_out = out_hbm.shape[1]
    k = a_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m_local // bm, n_out // bn, k // bk

    def partial_into(dst, dst_ref):
        mm_pipeline(mb, nb, kb, bm, bk, bn, acc_ref, m_off=dst * mb, out_m_off=0)(
            a_hbm, b_hbm, dst_ref
        )

    wire = RSWireRefs(
        fmt=fmt, wq=(wq0, wq1), ws=(ws0, ws1), rq=(rq0, rq1), rs=(rs0, rs1),
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        quantize=wirelib.quant_pipeline(m_local, n_out, fmt),
        dequant_add=wirelib.dequant_add_pipeline(m_local, n_out, fmt),
    )
    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (None, None),
        send_sem, recv_sem, ack_sem, partial_into, None,
        site="gemm_rs", wire=wire, schedule=schedule,
    )


def _fused_kernel_mxw(
    n, axis, mesh_axes, blocks, fmt, schedule,
    aq_hbm, as_hbm, bq_hbm, bs_hbm, out_hbm, w0, w1,
    wq0, wq1, ws0, ws1, rq0, rq1, rs0, rs1,
    acc_ref, send_sem, recv_sem, ack_sem, s_send_sem, s_recv_sem,
):
    """int8-MXU-producer twin of :func:`_fused_kernel_w` (carried-forward
    ROADMAP item): with int8 weights + activations the producer matmul
    runs the MXU's native s8×s8→s32 path, and the wire is quantized off
    an ACCUMULATOR at both places a hop's payload is born —
    ``RSWireRefs.quantize=None`` tells the ring harness the read-back
    quantize pass is gone:

    * the FIRST send (a pure local partial) quantizes straight off the
      producer's s32 accumulator (:func:`mm_q8_rs_pipeline`'s fused
      epilogue into wq/ws slot 0);
    * every later send must ship the FOLDED running sum, not the local
      partial — the fold itself re-quantizes off its f32 accumulator
      into the next send's rail pair
      (:func:`lang.wire.dequant_add_requant_pipeline`). Shipping the
      raw local partial here loses every upstream contribution — the
      delivery contract (SL008: one fold per rank) is what catches
      that, which is exactly why this family gates through shmemlint.
    """
    m_local = out_hbm.shape[0]
    n_out = out_hbm.shape[1]
    k = aq_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m_local // bm, n_out // bn, k // bk
    wq, ws = (wq0, wq1), (ws0, ws1)
    produced = [0]
    folded = [0]

    def partial_into(dst, dst_ref):
        i = produced[0]
        produced[0] += 1
        if i == 0:
            # the hop-0 payload: local partial, wire-quantized off the
            # producer accumulator into send slot 0
            mm_q8_rs_pipeline(
                mb, nb, kb, bm, bk, bn, fmt, acc_ref, m_off=dst * mb
            )(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_ref, wq[0], ws[0])
        else:
            # later partials only feed the fold; their wire copy is the
            # fold's requantize (writing a rail here would be dead work)
            mm_q8_partial_pipeline(
                mb, nb, kb, bm, bk, bn, acc_ref, m_off=dst * mb
            )(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_ref)

    deq_req = wirelib.dequant_add_requant_pipeline(m_local, n_out, fmt)
    deq = wirelib.dequant_add_pipeline(m_local, n_out, fmt)

    def dequant_add(a_hbm, q_hbm, s_hbm, dst_hbm):
        s = folded[0]
        folded[0] += 1
        if s < n - 2:
            # fold step s feeds send step s+1 (slot (s+1) % 2): requant
            # the accumulated sum into that slot's rail pair
            slot = (s + 1) % 2
            deq_req(a_hbm, q_hbm, s_hbm, dst_hbm, wq[slot], ws[slot])
        else:
            # final fold lands in out_hbm; nothing ships after it
            deq(a_hbm, q_hbm, s_hbm, dst_hbm)

    wire = RSWireRefs(
        fmt=fmt, wq=wq, ws=ws, rq=(rq0, rq1), rs=(rs0, rs1),
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        quantize=None,   # producer/fold-quantized: the rails are written
        dequant_add=dequant_add,
    )
    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (None, None),
        send_sem, recv_sem, ack_sem, partial_into, None,
        site="gemm_rs", wire=wire, schedule=schedule,
    )


def _fused_kernel_mxr(
    n, axis, mesh_axes, blocks, fmt, schedule,
    aq_hbm, as_hbm, bq_hbm, bs_hbm, out_hbm, w0, w1,
    wq0, wq1, ws0, ws1, rq0, rq1, rs0, rs1,
    acc_ref, send_sem, recv_sem, ack_sem, s_send_sem, s_recv_sem,
):
    """The READBACK epilogue placement of the int8-MXU producer (the
    ``GridSchedule.epilogue="readback"`` alternative to
    :func:`_fused_kernel_mxw`): the s8×s8→s32 producer writes only the
    f32 partial, and the ring harness's ``quant_pipeline`` read-back
    pass makes each hop's wire copy — the pre-fusion pipeline shape,
    kept searchable so the grid schedule search prices the fused
    epilogue AGAINST it instead of assuming it."""
    m_local = out_hbm.shape[0]
    n_out = out_hbm.shape[1]
    k = aq_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m_local // bm, n_out // bn, k // bk

    def partial_into(dst, dst_ref):
        mm_q8_partial_pipeline(
            mb, nb, kb, bm, bk, bn, acc_ref, m_off=dst * mb
        )(aq_hbm, as_hbm, bq_hbm, bs_hbm, dst_ref)

    wire = RSWireRefs(
        fmt=fmt, wq=(wq0, wq1), ws=(ws0, ws1), rq=(rq0, rq1), rs=(rs0, rs1),
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        quantize=wirelib.quant_pipeline(m_local, n_out, fmt),
        dequant_add=wirelib.dequant_add_pipeline(m_local, n_out, fmt),
    )
    reduce_ring(
        n, axis, mesh_axes, out_hbm, (w0, w1), (None, None),
        send_sem, recv_sem, ack_sem, partial_into, None,
        site="gemm_rs", wire=wire, schedule=schedule,
    )


def _specs(axis, batch_axes, dcn_axis=None):
    """(in_specs, out_specs) for GEMM-RS under shard_map over the full mesh.

    Activation rows may additionally be sharded over ``batch_axes`` (DP);
    the reduce-scatter then runs over ``axis`` within each DP group and the
    output rows end up sharded over (*batch_axes, axis) — the Megatron
    sequence-parallel layout, the exact inverse of ag_gemm's.
    Hierarchical (``dcn_axis``): the TP factor spans (axis, dcn_axis)
    axis-MAJOR (matching ag_gemm's hierarchical layout): K cols and
    output rows sharded P((axis, dcn_axis))."""
    ba = tuple(batch_axes)
    # a 1-tuple of axis names is equivalent to the bare name for both
    # PartitionSpec and lax collectives, so no flat/hier branching
    tp_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)
    a_spec = P(ba if ba else None, tp_axes)
    b_spec = P(tp_axes, None)
    out_spec = P(ba + tp_axes, None)
    return (a_spec, b_spec), out_spec


@functools.lru_cache(maxsize=256)
def _build_fused(
    mesh, axis, batch_axes, a_shape, b_shape, dtype, out_dtype, collective_id,
    chaos, dcn_axis=None, wire=None, schedule=None,
):
    """Fused engine. ``dcn_axis`` set = hierarchical (≡ the reference's
    inter-node GEMM-RS, reduce_scatter.py:524-545): the fused ring
    reduces intra-slice over ``axis`` (each slice sums its own K
    stripe), then a ``lax.psum_scatter`` leg crosses DCN — adding the
    other slices' stripes and scattering rows axis-major.

    Round 5 (VERDICT r4 #5): the DCN leg is CHUNKED for overlap — the
    fused ring runs once per N-column chunk, and since chunk c's
    ``psum_scatter`` depends only on chunk c's ring while chunk c+1's
    ring has no dependency on it at all, XLA's async collective
    machinery flies each chunk's DCN transfer under the NEXT chunk's
    Mosaic call (the mirror of ag_gemm's chunked rail; ≡ the reference
    overlapping the inter-node p2p stage of RS on its own stream,
    reduce_scatter.py:524-545). Exposed DCN time drops from the whole
    leg to ~1/C of it. Falls back to the serial leg when the column
    chunk admits no divisor blocking."""
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    dp = mesh_axes_size(mesh, batch_axes)
    m_local = a_shape[0] // (dp * n)
    k_local = a_shape[1] // (n * nd)
    n_out = b_shape[1]
    blocks = pick_mm_blocks(
        m_local, k_local, n_out, dtype.itemsize, targets=_RS_TILE_TARGETS
    )
    if blocks is None:
        raise ValueError(
            f"gemm_rs PALLAS_FUSED: no divisor blocking for shard "
            f"({m_local}, {k_local}) @ ({k_local}, {n_out}); use XLA_RING"
        )

    if n == 1:
        collective_id = None  # degenerate path uses no barrier semaphore
    fmt = None
    rail_fmt = None
    # the grid schedule (tune.schedule.GridSchedule) governs the MXU
    # producer's epilogue placement and demotion policy; its rail knob
    # maps onto the inner reduce ring's scale-rail assignment. A plain
    # RingSchedule (or None) leaves today's behavior byte-identical.
    # Duck-typed on the classes' `kind` tag, not isinstance — the tune
    # module may be loaded twice (its CLI runs it as __main__), and two
    # copies of GridSchedule must still dispatch here.
    from triton_distributed_tpu.tune.schedule import RingSchedule

    epilogue, demote = "accumulator", "auto"
    if getattr(schedule, "kind", "ring") == "grid":
        epilogue, demote = schedule.epilogue, schedule.demote
        schedule = (
            RingSchedule(scale_rail="payload")
            if schedule.rail == "shared" else None
        )
    mx = wire == "int8-mxu" and dcn_axis is None
    if mx and (n_out // blocks[2] != 1 or m_local % blocks[0]):
        # the accumulator-epilogue quantizer needs the out tile to span
        # every column (a row block IS a scale chunk); otherwise run the
        # ordinary int8 wire with its separate quantize pass
        if demote == "strict":
            raise ValueError(
                f"gemm_rs int8-mxu: shard ({m_local}, {k_local}) @ "
                f"({k_local}, {n_out}) blocks to {blocks} — the "
                "accumulator epilogue needs a full-width out tile and "
                "chunk-aligned rows, and the schedule pins "
                "demote='strict'"
            )
        mx = False
        wire = "int8"
    if mx:
        wirelib.require_mxu("gemm_rs")
        fmt = wirelib.WireFormat(quant="int8", chunk_rows=blocks[0])
    elif wire is not None and dcn_axis is not None:
        # hierarchical: the wire rides the DCN LEG (the quantized
        # ppermute reduce ring replacing psum_scatter — XLA-side
        # quant/dequant, any backend); intra-slice rings stay raw.
        # The rail reduces (m_local, ·) partials in nd stripes of
        # m_local/nd rows each.
        if m_local % nd == 0:
            rail_fmt = wirelib.make_wire_format(
                wirelib.wire_payload(wire), m_local // nd, strict=False
            )
    elif wire is not None:
        from triton_distributed_tpu.config import compiling_for_tpu

        wirelib.require_inkernel(
            wirelib.wire_payload(wire), "gemm_rs"
        )
        fmt = wirelib.make_wire_format(
            wirelib.wire_payload(wire), m_local, strict=compiling_for_tpu()
        )
        if fmt is None:
            raise ValueError(
                f"gemm_rs wire={wire!r}: slab of {m_local} rows admits no "
                "legal scale chunking; use the bf16 wire"
            )

    def mk_call(n_cols, blk, cid):
        slab = jax.ShapeDtypeStruct((m_local, n_cols), out_dtype)
        if mx:
            qslab = jax.ShapeDtypeStruct((m_local, n_cols), fmt.wire_dtype)
            sslab = jax.ShapeDtypeStruct(
                (fmt.chunks(m_local), wirelib.SCALE_LANES), jnp.float32
            )
            mx_kernel = (
                _fused_kernel_mxr if epilogue == "readback"
                else _fused_kernel_mxw
            )
            return lang.shmem_call(
                functools.partial(
                    mx_kernel, n, axis, mesh.axis_names, blk, fmt,
                    schedule,
                ),
                out_shape=[slab, slab, slab,
                           qslab, qslab, sslab, sslab,
                           qslab, qslab, sslab, sslab],
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 11,
                scratch_shapes=[
                    pltpu.VMEM((blk[0], blk[2]), jnp.int32),  # s32 acc
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.REGULAR,
                    pltpu.SemaphoreType.DMA((2,)),   # scale rail
                    pltpu.SemaphoreType.DMA((2,)),
                ],
                collective_id=cid,
                vmem_limit_bytes=fused_vmem_budget(),
                name="gemm_rs_fused_int8mxw",
            )
        if fmt is not None:
            qslab = jax.ShapeDtypeStruct((m_local, n_cols), fmt.wire_dtype)
            sslab = jax.ShapeDtypeStruct(
                (fmt.chunks(m_local), wirelib.SCALE_LANES), jnp.float32
            )
            return lang.shmem_call(
                functools.partial(
                    _fused_kernel_w, n, axis, mesh.axis_names, blk, fmt,
                    schedule,
                ),
                # out + bf16 work pair + quantized work/scale pairs +
                # quantized recv/scale pairs (HBM workspaces as outputs)
                out_shape=[slab, slab, slab,
                           qslab, qslab, sslab, sslab,
                           qslab, qslab, sslab, sslab],
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 11,
                scratch_shapes=[
                    pltpu.VMEM((blk[0], blk[2]), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.REGULAR,
                    pltpu.SemaphoreType.DMA((2,)),   # scale rail
                    pltpu.SemaphoreType.DMA((2,)),
                ],
                collective_id=cid,
                vmem_limit_bytes=fused_vmem_budget(),
                name=f"gemm_rs_fused_{wirelib.wire_payload(wire)}w",
            )
        return lang.shmem_call(
            functools.partial(
                _fused_kernel, n, axis, mesh.axis_names, blk, schedule
            ),
            # work/recv ring slabs are HBM workspaces (Mosaic supports
            # scratch only in vmem/smem/semaphore space, so they ride as
            # extra outputs — the symmetric-workspace pattern of the
            # reference's ctx).
            out_shape=[slab, slab, slab, slab, slab],
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
            scratch_shapes=[
                pltpu.VMEM((blk[0], blk[2]), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.REGULAR,
            ],
            collective_id=cid,
            vmem_limit_bytes=fused_vmem_budget(),
            name="gemm_rs_fused",
        )

    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)

    n_chunks = 1
    chunk_blocks = None
    if dcn_axis is not None and nd > 1:
        for c in (4, 2):
            if n_out % c:
                continue
            chunk_blocks = pick_mm_blocks(
                m_local, k_local, n_out // c, dtype.itemsize,
                targets=_RS_TILE_TARGETS,
            )
            if chunk_blocks is not None:
                n_chunks = c
                break

    if dcn_axis is None:
        call = lang.maybe_instrument(
            mk_call(n_out, blocks, collective_id),
            axis=axis, site="gemm_rs", collective_id=collective_id, n=n,
        )

        if mx:
            def body(a, b):
                # quantize both operands in XLA; the kernel's MXU path
                # consumes s8×s8→s32 and quantizes the wire partial
                # straight off the accumulator epilogue
                aq, asc = wirelib.quantize_slab(a, fmt)
                bq, bsc = wirelib.quantize_cols(b)
                return call(aq, asc, bq, bsc)[0]
        else:
            def body(a, b):
                return call(a, b)[0]
    elif n_chunks == 1:
        call = mk_call(n_out, blocks, collective_id)

        def body(a, b):
            # serial DCN leg fallback (no admissible column chunking) —
            # quantized rail when the wire is on
            part = call(a, b)[0]
            if rail_fmt is not None:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_reduce_scatter,
                )

                return dcn_wire_reduce_scatter(
                    part, dcn_axis, nd, rail_fmt
                )
            return jax.lax.psum_scatter(
                part, dcn_axis, scatter_dimension=0, tiled=True
            )
    else:
        nc = n_out // n_chunks
        # distinct collective_ids per chunk ring: strict per-chunk
        # rendezvous (a skewed neighbor's chunk-c+1 signal must not
        # satisfy a chunk-c wait); the offset range is reserved in the
        # registry's rail ledger, so disjointness from every other
        # chunked family is checked, not maintained by comment
        from triton_distributed_tpu.kernels.registry import rail_collective_id

        chunk_calls = [
            mk_call(
                nc, chunk_blocks,
                rail_collective_id("gemm_rs.dcn_chunks", collective_id, s),
            )
            for s in range(n_chunks)
        ]

        def dcn_rs(part):
            # manual reduce-scatter as a ppermute ring (the
            # gemm_rs_device stripe pattern over dcn_axis): XLA
            # async-converts collective-permute — a sync psum_scatter
            # would serialize the whole leg (verified in the compiled
            # schedule), while these hops get start/done windows the
            # next chunk's Mosaic call slots into. With the rail wire
            # on, each hop moves the per-hop-quantized partial + scale
            # plane (~2× fewer DCN bytes, f32 dequant-accumulate).
            if rail_fmt is not None:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_reduce_scatter,
                )

                return dcn_wire_reduce_scatter(
                    part, dcn_axis, nd, rail_fmt
                )
            me = jax.lax.axis_index(dcn_axis)
            m_s = part.shape[0] // nd
            perm = [(i, (i - 1) % nd) for i in range(nd)]

            def stripe(i):
                return jax.lax.dynamic_slice(
                    part, (i * m_s, 0), (m_s, part.shape[1])
                )

            acc = stripe(jax.lax.rem(me + 1, nd))
            for s in range(nd - 1):
                acc = jax.lax.ppermute(acc, dcn_axis, perm=perm)
                acc = acc + stripe(jax.lax.rem(me + 2 + s, nd))
            return acc

        def body(a, b):
            scattered = []
            for s in range(n_chunks):
                part = chunk_calls[s](a, b[:, s * nc:(s + 1) * nc])[0]
                # this chunk's DCN ring has no consumer until the final
                # concat — its hops fly under chunk s+1's Mosaic ring
                scattered.append(dcn_rs(part))
            return jnp.concatenate(scattered, axis=1)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def gemm_rs_device(a_loc, b_loc, axis, *, out_dtype=None, wire=None):
    """Per-device XLA-ring GEMM-RS body — usable inside any shard_map.

    The accumulator flows leftward around the ring while the next
    destination's partial matmul runs, overlapped by XLA async permute.

    ``wire`` ('fp8'/'int8'): each hop's partial sum is quantized to the
    lang.wire layout before its permute and dequant-accumulated in f32
    on arrival — the same per-hop requantization semantics (and byte
    counts) as the fused wire ring."""
    n = jax.lax.axis_size(axis)
    out_dtype = out_dtype or a_loc.dtype
    m_local = a_loc.shape[0] // n
    me = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % n) for i in range(n)]
    fmt = None
    if wire is not None:
        from triton_distributed_tpu.config import compiling_for_tpu

        fmt = wirelib.make_wire_format(
            wire, m_local, strict=compiling_for_tpu()
        )

    def partial(dst):
        rows = jax.lax.dynamic_slice(
            a_loc, (dst * m_local, 0), (m_local, a_loc.shape[1])
        )
        return jnp.dot(rows, b_loc, preferred_element_type=jnp.float32).astype(
            out_dtype
        )

    if fmt is None:
        def step(s, acc):
            acc = jax.lax.ppermute(acc, axis, perm=perm)
            return acc + partial(jax.lax.rem(me + 2 + s, n))

        acc = partial(jax.lax.rem(me + 1, n))
        return jax.lax.fori_loop(0, n - 1, step, acc)

    def step_w(s, acc):
        q, sc = wirelib.quantize_slab(acc, fmt)
        q = jax.lax.ppermute(q, axis, perm=perm)
        sc = jax.lax.ppermute(sc, axis, perm=perm)
        arrived = wirelib.dequantize_slab(q, sc, fmt, jnp.float32)
        return (
            arrived + partial(jax.lax.rem(me + 2 + s, n)).astype(jnp.float32)
        ).astype(out_dtype)

    acc = partial(jax.lax.rem(me + 1, n))
    return jax.lax.fori_loop(0, n - 1, step_w, acc)


@functools.lru_cache(maxsize=256)
def _build_xla_ring(mesh, axis, batch_axes, out_dtype, dcn_axis=None,
                    wire=None):
    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)

    def body(a_loc, b_loc):
        part = gemm_rs_device(
            a_loc, b_loc, axis, out_dtype=out_dtype,
            wire=wirelib.wire_payload(wire),
        )
        if dcn_axis is not None:
            nd = jax.lax.axis_size(dcn_axis)
            w_rail = wirelib.wire_payload(wire)
            rail_fmt = (
                wirelib.make_wire_format(
                    w_rail, part.shape[0] // nd, strict=False
                )
                if w_rail is not None and part.shape[0] % nd == 0
                else None
            )
            if rail_fmt is not None:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_reduce_scatter,
                )

                part = dcn_wire_reduce_scatter(
                    part, dcn_axis, nd, rail_fmt
                )
            else:
                part = jax.lax.psum_scatter(
                    part, dcn_axis, scatter_dimension=0, tiled=True
                )
        return part

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _build_xla_naive(mesh, axis, batch_axes, out_dtype, dcn_axis=None):
    tp_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)

    def body(a_loc, b_loc):
        full = jnp.dot(a_loc, b_loc, preferred_element_type=jnp.float32).astype(
            out_dtype
        )
        return jax.lax.psum_scatter(full, tp_axes, scatter_dimension=0, tiled=True)

    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _engine_tuner(mesh, axis, batch_axes, out_dtype, collective_id,
                  dcn_axis=None, wire=None):
    """Measured engine selection for ``method=None`` (see
    ag_gemm._engine_tuner for the contract incl. why out_dtype,
    collective_id and wire belong in the name/key)."""
    from triton_distributed_tpu.tune.autotuner import method_tuner

    def run(a, b, *, method):
        return gemm_rs(
            a, b, mesh, axis, batch_axes=batch_axes,
            method=GemmRSMethod(method), out_dtype=out_dtype,
            collective_id=collective_id, dcn_axis=dcn_axis, wire_dtype=wire,
        )

    return method_tuner(
        f"gemm_rs[{dict(mesh.shape)}|{axis}|{batch_axes}|{out_dtype}|"
        f"{collective_id}|{dcn_axis}|w{wire}]",
        run, GemmRSMethod,
    )


@functools.lru_cache(maxsize=64)
def _wire_tuner(mesh, axis, batch_axes, out_dtype, collective_id,
                dcn_axis=None):
    """Measured wire-dtype selection for ``wire_dtype='auto'`` (see
    ag_gemm._wire_tuner)."""
    from triton_distributed_tpu.tune.autotuner import wire_tuner

    def run(a, b, *, wire_dtype):
        dp = mesh_axes_size(mesh, tuple(batch_axes))
        method = auto_gemm_rs_method(
            mesh, axis, a, b, dp=dp, dcn_axis=dcn_axis
        )
        return gemm_rs(
            a, b, mesh, axis, batch_axes=batch_axes, method=method,
            out_dtype=out_dtype, collective_id=collective_id,
            dcn_axis=dcn_axis, wire_dtype=wire_dtype,
        )

    return wire_tuner(
        f"gemm_rs_wire[{dict(mesh.shape)}|{axis}|{batch_axes}|{out_dtype}|"
        f"{collective_id}|{dcn_axis}]",
        run,
    )


def auto_gemm_rs_method(mesh, axis, a, b, dp: int = 1,
                        dcn_axis: str | None = None) -> GemmRSMethod:
    """Topology + shape blockability decide the engine; fallbacks are
    logged (nobody should benchmark XLA believing it is the fused kernel).
    A cross-slice TP factor declared as ``dcn_axis`` keeps the fused
    engine intra-slice; only ``axis`` itself crossing DCN forces XLA."""
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    topo = detect_topology(mesh, axis)
    if topo.link_kind == LinkKind.DCN:
        _warn_once(
            ("gemm_rs", "dcn", axis),
            f"gemm_rs: axis {axis!r} crosses DCN; using XLA_RING engine "
            "(pass the cross-slice factor as dcn_axis= to keep the fused "
            "engine intra-slice)",
        )
        return GemmRSMethod.XLA_RING
    m_local = a.shape[0] // (dp * n)
    blocks = pick_mm_blocks(
        m_local, a.shape[1] // (n * nd), b.shape[1], a.dtype.itemsize,
        targets=_RS_TILE_TARGETS,
    )
    if blocks is None:
        _warn_once(
            ("gemm_rs", "blocks", a.shape, b.shape),
            f"gemm_rs: shard ({m_local}, {a.shape[1] // (n * nd)}) @ "
            f"({a.shape[1] // (n * nd)}, {b.shape[1]}) admits no divisor "
            "blocking; falling back to XLA_RING",
        )
        return GemmRSMethod.XLA_RING
    return GemmRSMethod.PALLAS_FUSED


def resolve_gemm_rs_wire(
    mesh, axis, a, b, *, batch_axes=(), method=None, wire_dtype=None,
    out_dtype=None, dcn_axis: str | None = None, dp: int | None = None,
) -> str | None:
    """The wire format :func:`gemm_rs` will ACTUALLY ship (mirror of
    ag_gemm.resolve_ag_gemm_wire): None unless a ring engine runs and
    the OUTPUT slab — what the reduce ring moves — admits the lang.wire
    layout; 'auto' consults the measured wire tuner, else the perf
    model's comm-bound test at the per-step shapes."""
    from triton_distributed_tpu.config import compiling_for_tpu

    # a reduce ring accumulates — 'int8-mxu' has no MXU consumer here
    # and resolves to its int8 payload wire
    w = wirelib.wire_payload(wirelib.normalize_wire(wire_dtype))
    if w is None:
        return None
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    if dp is None:
        dp = mesh_axes_size(mesh, tuple(batch_axes))
    if n * nd == 1:
        return None
    if method == GemmRSMethod.XLA_NAIVE:
        return None  # psum_scatter — no ring to compress
    if dcn_axis is not None:
        # the DCN rail wire: the quantized ppermute reduce ring replaces
        # psum_scatter on the leg (XLA-side — any backend); intra-slice
        # Pallas rings stay raw
        m_s = a.shape[0] // (dp * n * nd * nd)
        n_out = b.shape[1]
        if a.shape[0] % (dp * n * nd * nd) or not wirelib.wire_blockable(
            max(m_s, 1), n_out, "fp8", False
        ):
            if w == "auto":
                return None
            raise ValueError(
                f"gemm_rs wire_dtype={w!r}: DCN rail stripe admits no "
                "legal wire chunking (a pinned wire format is a "
                "contract); use wire_dtype='auto' or the bf16 wire"
            )
        if w == "auto":
            from triton_distributed_tpu.runtime.topology import (
                auto_allgather_wire,
            )

            out_itemsize = jnp.dtype(out_dtype or a.dtype).itemsize
            return auto_allgather_wire(m_s * n_out * out_itemsize)
        return w
    m_local = a.shape[0] // (dp * n)
    k_local = a.shape[1] // n
    n_out = b.shape[1]
    out_itemsize = jnp.dtype(out_dtype or a.dtype).itemsize
    strict = compiling_for_tpu()
    inkernel = method == GemmRSMethod.PALLAS_FUSED
    if w == "auto":
        if not wirelib.wire_blockable(m_local, n_out, "fp8", strict):
            return None
        if inkernel and not wirelib.inkernel_wire_ok("fp8"):
            return None  # no silent fp8→int8 numerics switch
        from triton_distributed_tpu.tune.autotuner import tuned_method_or_none

        tuned = tuned_method_or_none(
            lambda: _wire_tuner(
                mesh, axis, tuple(batch_axes), jnp.dtype(a.dtype), 6,
                dcn_axis,
            ),
            a, b, key="wire_dtype",
        )
        if tuned is not None:
            return wirelib.normalize_wire(tuned)
        from triton_distributed_tpu.tune.perf_model import auto_wire_dtype

        return wirelib.normalize_wire(auto_wire_dtype(
            m_local, k_local, n_out, out_itemsize,
            slab_bytes=m_local * n_out * out_itemsize,
        ))
    if inkernel:
        wirelib.require_inkernel(w, "gemm_rs")
    if not wirelib.wire_blockable(m_local, n_out, w, strict):
        raise ValueError(
            f"gemm_rs wire_dtype={w!r}: slab ({m_local}, {n_out}) admits "
            "no legal wire chunking/blocking (a pinned wire format is a "
            "contract); use wire_dtype='auto' or the bf16 wire"
        )
    return w


def resolve_gemm_rs_method(
    a_mesh, axis, a, b, *, batch_axes=(), method=None, out_dtype=None,
    collective_id: int = 6, dcn_axis: str | None = None, wire_dtype=None,
) -> GemmRSMethod:
    """The engine :func:`gemm_rs` will ACTUALLY run for these arguments
    (mirror of ag_gemm.resolve_ag_gemm_method): explicit ``method``,
    else the tuned winner, else the heuristic — with the safety recheck
    demoting a fused winner that is not buildable in this environment."""
    if method is not None:
        return method
    from triton_distributed_tpu.tune.autotuner import tuned_method_or_none

    batch_axes = tuple(batch_axes)
    dp = mesh_axes_size(a_mesh, batch_axes)
    out_dtype = out_dtype or a.dtype
    m = tuned_method_or_none(
        lambda: _engine_tuner(
            a_mesh, axis, batch_axes, jnp.dtype(out_dtype), collective_id,
            dcn_axis, wirelib.normalize_wire(wire_dtype),
        ),
        a, b,
    )
    auto = functools.partial(
        auto_gemm_rs_method, a_mesh, axis, a, b, dp=dp, dcn_axis=dcn_axis
    )
    method = GemmRSMethod(m) if m else auto()
    if method == GemmRSMethod.PALLAS_FUSED and auto() != method:
        # persisted winner may not be buildable in this environment
        method = auto()
    return method


def gemm_rs(
    a,
    b,
    mesh,
    axis: str = "x",
    *,
    batch_axes: tuple = (),
    method: GemmRSMethod | None = None,
    out_dtype=None,
    collective_id: int = 6,
    dcn_axis: str | None = None,
    wire_dtype=None,
    schedule=None,
):
    """Fused (A @ B) → ReduceScatter for row-parallel TP.

    ``wire_dtype``: what the reduce ring ships (docs/PERF.md "Quantized
    wire"). None/'bf16' — the raw partials (default, today's numerics);
    'fp8'/'int8' — each hop's partial quantized to a 1-byte payload +
    per-chunk f32 scales (lang.wire), dequant-accumulated in f32 on
    receive so reduction error is one bounded rounding per hop;
    'int8-mxu' — additionally run the producer GEMM itself on s8×s8→s32
    and quantize each hop's wire partial straight off the accumulator
    epilogue (no separate read-back quantize pass); 'auto' — the
    measured wire tuner, else the perf model picks the compressed wire
    exactly on comm-bound shapes. Inference-grade transport.

    ``schedule``: an explicit :class:`tune.schedule.RingSchedule` for
    the fused reduce ring (scale-rail assignment, buffer depth). None
    resolves a persisted schedule-search winner for this
    (shape, mesh, wire) key, falling back to the canonical default —
    byte-identical to the pre-schedule kernel.

    ``a``: (M, K) with rows sharded over ``batch_axes`` (DP) and cols
    P(axis) — each device holds a K/n column shard. ``b``: (K, N) sharded
    P(axis, None) — row-parallel weight. Returns (M, N) with rows sharded
    over ``(*batch_axes, axis)``: within each DP group device i owns
    fully-reduced row shard i (sequence-parallel layout).

    ``dcn_axis``: hierarchical TP spanning slices (≡ the reference's
    inter-node GEMM-RS, reduce_scatter.py:524-545): K cols and output
    rows sharded P((axis, dcn_axis)) axis-major; the fused Pallas ring
    reduces intra-slice, a psum_scatter leg crosses DCN.

    Host entry ≡ reference ``gemm_rs`` (gemm_reduce_scatter.py:547).
    """
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    batch_axes = tuple(batch_axes)
    dp = mesh_axes_size(mesh, batch_axes)
    out_dtype = out_dtype or a.dtype
    assert (
        a.shape[0] % (dp * n * nd) == 0
        and a.shape[1] % (n * nd) == 0
        and b.shape[0] % (n * nd) == 0
    )
    assert a.shape[1] == b.shape[0], f"contract dim mismatch {a.shape} @ {b.shape}"
    if n * nd == 1:
        return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)
    method = resolve_gemm_rs_method(
        mesh, axis, a, b, batch_axes=batch_axes, method=method,
        out_dtype=out_dtype, collective_id=collective_id, dcn_axis=dcn_axis,
        wire_dtype=wire_dtype,
    )
    wire = resolve_gemm_rs_wire(
        mesh, axis, a, b, batch_axes=batch_axes, method=method,
        wire_dtype=wire_dtype, out_dtype=out_dtype, dcn_axis=dcn_axis, dp=dp,
    )
    if method == GemmRSMethod.PALLAS_FUSED:
        from triton_distributed_tpu.tune.schedule import resolve_schedule

        if (wirelib.normalize_wire(wire_dtype) == "int8-mxu"
                and wire == "int8" and dcn_axis is None
                and wirelib.inkernel_s8_dot_ok()):
            # the caller asked for the MXU consumer; resolve_gemm_rs_wire
            # reports the payload ('int8') since that is what the ring
            # ships — re-upgrade for the builder
            wire = "int8-mxu"
        # the MXU-producer wire resolves the GRID family (epilogue
        # placement / demotion policy, tune.schedule.GridSchedule); the
        # plain wires resolve the ring family as before
        fam = (
            "gemm_rs.mx_epilogue" if wire == "int8-mxu"
            else "gemm_rs.fused"
        )
        sched = resolve_schedule(fam, a.shape, (n * nd,), wire, schedule)
        fn = _build_fused(
            mesh, axis, batch_axes, a.shape, b.shape, a.dtype, out_dtype,
            collective_id, interp_key(), dcn_axis, wire, sched,
        )
    elif method == GemmRSMethod.XLA_RING:
        fn = _build_xla_ring(
            mesh, axis, batch_axes, out_dtype, dcn_axis, wire
        )
    else:
        fn = _build_xla_naive(mesh, axis, batch_axes, out_dtype, dcn_axis)
    return fn(a, b)
