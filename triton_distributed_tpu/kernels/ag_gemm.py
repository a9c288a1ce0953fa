"""AllGather-GEMM: tensor-parallel overlap of activation gather with matmul.

Reference: python/triton_dist/kernels/nvidia/allgather_gemm.py — context
(:407-490), producer copy engines + consumer persistent GEMM waiting
per-tile on shard-arrival barriers (:133-254, dl.wait+consume_token
:224-227), rank-swizzled tile order (:205-219), host entry ``ag_gemm``
(:539) and the multi-stream dispatcher (:586-661).

TPU re-design — no streams, two engines instead:

* ``PALLAS_FUSED``: ONE persistent Pallas kernel per device runs an
  HBM-streaming ring. Operands and the gathered-A workspace live in HBM
  (ANY memory space); the matmul is a tiled ``emit_pipeline`` whose
  (m, n, k) blocks are double-buffered HBM→VMEM DMAs, so the engine has
  no whole-working-set VMEM gate and engages at any shape (the Llama-7B
  TP8 north-star included — the reference's persistent TMA consumer GEMM,
  allgather_gemm.py:133-254, translated to Mosaic's DMA pipeline). At
  ring step ``s`` the kernel (1) waits on the recv DMA semaphore for
  shard ``(me-s)`` — the hardware equivalent of dl.wait+consume_token
  (:224-227) — (2) starts the RDMA forwarding that shard to the right
  neighbor (HBM→HBM over ICI, touching no VMEM), and (3) streams the
  shard through the MXU while the forward is in flight. Each rank starts
  on its own local shard, so the reference's rank-swizzled tile order
  falls out of the ring schedule naturally.
* ``XLA_RING``: shard_map loop of ``ppermute`` + ``jnp.dot`` — XLA's
  async collective-permute overlaps the hop with the matmul. This is the
  DCN path, mirroring the reference's inter-node engine
  (allgather.py:291-468).
* ``XLA_NAIVE``: all_gather → dot (the torch_ag_gemm-style baseline,
  reference test_ag_gemm.py).
"""

from __future__ import annotations

import enum
import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import lang
from triton_distributed_tpu.config import fused_vmem_budget, interp_key
from triton_distributed_tpu.kernels.ring import AGWireRefs, ag_forward_ring
from triton_distributed_tpu.lang import wire as wirelib
from triton_distributed_tpu.runtime import (
    LinkKind,
    detect_topology,
    mesh_axes_size,
)

logger = logging.getLogger(__name__)
_warned = set()


def _warn_once(key, msg):
    if key not in _warned:
        _warned.add(key)
        logger.warning(msg)


class AGGemmMethod(enum.Enum):
    PALLAS_FUSED = "pallas_fused"
    XLA_RING = "xla_ring"
    XLA_NAIVE = "xla_naive"


# ------------------------------------------------------------- block chooser

#: default tile targets for the streaming matmul pipeline (bm, bk, bn).
#: Swept on a real v5e at the Llama-7B TP8 north-star shard
#: (8192×8192 @ 8192×3584 bf16) with the paired-median methodology:
#: (512, 2048, 1792) → 167 TFLOP/s vs 157 for (512, 512, 1792) and 161-162
#: for the 4096-bk / 1024-bm variants. GEMM-RS carries its own targets
#: (its north-star shape prefers whole-K tiles — see gemm_rs.py).
_TILE_TARGETS = (512, 2048, 1792)


def _divisor_block(dim: int, target: int, mult: int, strict: bool) -> int | None:
    """Largest divisor of ``dim`` ≤ ``target``, preferring multiples of
    ``mult`` (the hardware tile granule). ``strict`` (real-TPU): an
    unaligned *interior* block shape is a Mosaic lowering error, so only a
    multiple-of-mult divisor or the whole dim (single block — ragged
    edges are padded, interiors never misalign) is acceptable; off-TPU the
    interpreter ignores tiling and any divisor works."""
    best = None
    for b in range(min(target, dim), 0, -1):
        if dim % b == 0:
            if b % mult == 0:
                return b
            if best is None:
                best = b
    if strict and best != dim:
        return None
    return best


def pick_mm_blocks(m: int, k: int, n: int, itemsize: int,
                   budget: int | None = None, targets=None):
    """(bm, bk, bn) for the streaming matmul pipeline, or None if the shape
    admits no (TPU-lowerable) divisor blocking. Shrinks targets until the
    double-buffered tile working set fits the VMEM budget."""
    from triton_distributed_tpu.config import compiling_for_tpu

    budget = budget or fused_vmem_budget()
    strict = compiling_for_tpu()
    sublane = 8 * (4 // itemsize)  # (8·packing, 128) native tile
    tm, tk, tn = targets or _TILE_TARGETS
    while True:
        bm = _divisor_block(m, tm, sublane, strict)
        # bk is A's lane dim and B's sublane dim; 128 covers both granules
        bk = _divisor_block(k, tk, 128, strict)
        bn = _divisor_block(n, tn, 128, strict)
        if bm is None or bk is None or bn is None:
            return None
        # 2 A-tiles + 2 B-tiles + 2 out-tiles + 1 f32 accumulator
        work = 2 * (bm * bk + bk * bn) * itemsize + 2 * bm * bn * itemsize + 4 * bm * bn
        if work <= budget:
            return bm, bk, bn
        if tm <= 64 and tk <= 128 and tn <= 128:
            return None  # pathological budget
        tm, tk, tn = max(tm // 2, 64), max(tk // 2, 128), max(tn // 2, 128)


def mm_pipeline(mb, nb, kb, bm, bk, bn, acc_ref, *, m_off=0, n_off=0, out_m_off=None):
    """Tiled (m, n, k) matmul pipeline over HBM refs: C[out_m_off:, n_off:]
    = A[m_off:, :] @ B[:, n_off:] for one (mb·bm, kb·bk)×(kb·bk, nb·bn)
    slab. Offsets are *block* offsets (may be traced), so callers address
    shard windows without slicing the HBM refs (index arithmetic replaces
    the reference's rank-swizzled tile-id remap, allgather_gemm.py:205-219).
    ``out_m_off`` defaults to ``m_off`` (in-place shard layout); pass 0 to
    write a compact (mb·bm)-row slab (the GEMM-RS work buffers)."""
    if out_m_off is None:
        out_m_off = m_off

    def inner(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(pl.program_id(2) == kb - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return pltpu.emit_pipeline(
        inner,
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (m_off + i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, n_off + j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (out_m_off + i, n_off + j))
        ],
    )


def mm_q8_pipeline(mb, nb, kb, bm, bk, bn):
    """Tiled s8×s8 matmul pipeline with the wire scales folded into the
    accumulator epilogue — the dequant-free int8-MXU consumer. Operates
    over pre-sliced HBM refs ``(aq, asc, bq, bsc, out)``: aq the
    (mb·bm, kb·bk) int8 wire slab, asc its (mb, SCALE_LANES) scale
    plane (the int8-mxu wire pins ``chunk_rows == bm`` so row-block i's
    scale is exactly plane row i), bq/bsc the per-out-channel quantized
    weight (lang.wire.quantize_cols). The MXU runs its native s8×s8→s32
    path (2× the bf16 rate on v5e — the W8A8 grouped-GEMM measurement,
    kernels/group_gemm.py) and the rank-1 ``a_scale[chunk]·b_scale[n]``
    correction lands on the s32 accumulator at the last K step — exact,
    both scales are constant over the K reduction, the same epilogue
    shape as group_gemm's dequant epilogue. No per-arrival dequant pass
    runs and no bf16 copy of the slab ever exists."""

    def mk(acc_ref):
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
                # (1,1) chunk scale × (1,bn) channel scales → (1,bn),
                # sublane-broadcast onto the (bm,bn) accumulator (the
                # lane-replicated scale-plane idiom — never a scalar)
                o_ref[...] = (
                    acc_ref[...].astype(jnp.float32)
                    * (as_ref[:, :1] * bs_ref[...])
                ).astype(o_ref.dtype)

        return pltpu.emit_pipeline(
            inner,
            grid=(mb, nb, kb),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec(
                    (1, wirelib.SCALE_LANES), lambda i, j, kk: (i, 0)
                ),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            ],
            out_specs=[pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))],
        )

    def run(acc_ref, aq_hbm, as_hbm, bq_hbm, bs_hbm, out_hbm):
        if wirelib.epilogue_consume(aq_hbm, as_hbm, out_hbm):
            return  # symbolic: the provenance edge replaces the pipeline
        mk(acc_ref)(aq_hbm, as_hbm, bq_hbm, bs_hbm, out_hbm)

    return run


# ----------------------------------------------------------- fused engine


def _fused_kernel(
    n, axis, mesh_axes, blocks, publish_local, schedule,
    x_hbm, b_hbm, out_hbm, ag_hbm, acc_ref, local_sem, send_sem, recv_sem,
):
    """HBM-streaming ring AG-GEMM. Per step: wait shard arrival → start
    forwarding it → stream it through the MXU while the RDMA is in flight
    (the ring protocol lives in kernels/ring.ag_forward_ring)."""
    me = lang.my_pe(axis)
    m = x_hbm.shape[0]  # shard rows
    k = x_hbm.shape[1]
    nl = b_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m // bm, nl // bn, k // bk

    # Publish the local shard into the gathered workspace (HBM→HBM local
    # DMA ≡ local_copy_and_barrier_all, allgather_gemm.py:100-117) — ONLY
    # when the caller wants the gathered activations back: the ring
    # forwards and consumes the local shard straight from x_hbm, so slab
    # ``me`` is otherwise never read and the copy would be dead bandwidth
    # on the overlap-critical step 0.
    if publish_local:
        cp = pltpu.make_async_copy(x_hbm, ag_hbm.at[pl.ds(me * m, m)], local_sem)
        cp.start()

    def consume(s, src, a_hbm, a_row_off):
        # Stream this shard through the MXU while the forward is in flight.
        mm_pipeline(
            mb, nb, kb, bm, bk, bn, acc_ref,
            m_off=a_row_off // bm, out_m_off=src * mb,
        )(a_hbm, b_hbm, out_hbm)

    ag_forward_ring(
        n, axis, mesh_axes, x_hbm, ag_hbm, m, send_sem, recv_sem, consume,
        site="ag_gemm", schedule=schedule,
    )
    if publish_local:
        cp.wait()


def _fused_kernel_w(
    n, axis, mesh_axes, blocks, publish_local, fmt, schedule,
    x_hbm, xq_hbm, xs_hbm, b_hbm,
    out_hbm, ag_hbm, agq_hbm, ags_hbm,
    acc_ref, local_sem, send_sem, recv_sem, s_send_sem, s_recv_sem,
):
    """Quantized-wire twin of :func:`_fused_kernel`: the ring moves the
    host-quantized slab (xq/xs, lang.wire layout) plus its scale plane
    and dequantizes each arrival into the bf16 ``ag_hbm`` workspace
    before the matmul pipeline consumes it. The local shard never
    crosses the wire, so it is consumed exact from ``x_hbm``."""
    me = lang.my_pe(axis)
    m = x_hbm.shape[0]
    k = x_hbm.shape[1]
    nl = b_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m // bm, nl // bn, k // bk

    if publish_local:
        # gathered-A contract: slab ``me`` is the EXACT local slab (it
        # never rode the wire), same as the raw-wire engine
        cp = pltpu.make_async_copy(x_hbm, ag_hbm.at[pl.ds(me * m, m)], local_sem)
        cp.start()

    def consume(s, src, a_hbm, a_row_off):
        mm_pipeline(
            mb, nb, kb, bm, bk, bn, acc_ref,
            m_off=a_row_off // bm, out_m_off=src * mb,
        )(a_hbm, b_hbm, out_hbm)

    wire = AGWireRefs(
        fmt=fmt, local_q=xq_hbm, local_s=xs_hbm, agq=agq_hbm, ags=ags_hbm,
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        dequant=wirelib.dequant_pipeline(m, k, fmt),
    )
    ag_forward_ring(
        n, axis, mesh_axes, x_hbm, ag_hbm, m, send_sem, recv_sem, consume,
        site="ag_gemm", wire=wire, schedule=schedule,
    )
    if publish_local:
        cp.wait()


def _fused_kernel_mx(
    n, axis, mesh_axes, blocks, fmt, schedule,
    xq_hbm, xs_hbm, bq_hbm, bs_hbm,
    out_hbm, agq_hbm, ags_hbm,
    acc_ref, send_sem, recv_sem, s_send_sem, s_recv_sem,
):
    """int8→MXU twin of :func:`_fused_kernel_w`: the ring moves the
    host-quantized slab + scale plane exactly like the int8 wire, but
    the wire ends AT THE MXU — every slab (the local one included, for
    uniform numerics against the per-channel-quantized weight) streams
    through the s8×s8 pipeline with the chunk scale folded into the
    accumulator epilogue. There is no per-arrival dequant pass, no bf16
    gathered workspace, and arrival traffic through VMEM is halved
    (1-byte A tiles)."""
    m = xq_hbm.shape[0]
    k = xq_hbm.shape[1]
    nl = bq_hbm.shape[1]
    bm, bk, bn = blocks
    mb, nb, kb = m // bm, nl // bn, k // bk
    pipe = mm_q8_pipeline(mb, nb, kb, bm, bk, bn)

    def consume(s, src, a_hbm, a_row_off):
        del a_hbm, a_row_off  # int8 wire refs replace the bf16 workspace
        if s == 0:
            q_slab, s_rows = xq_hbm, xs_hbm
        else:
            q_slab = agq_hbm.at[pl.ds(src * m, m)]
            s_rows = ags_hbm.at[pl.ds(src * mb, mb)]
        pipe(acc_ref, q_slab, s_rows, bq_hbm, bs_hbm,
             out_hbm.at[pl.ds(src * m, m)])

    wire = AGWireRefs(
        fmt=fmt, local_q=xq_hbm, local_s=xs_hbm, agq=agq_hbm, ags=ags_hbm,
        s_send_sem=s_send_sem, s_recv_sem=s_recv_sem,
        dequant=None,   # the epilogue IS the dequant
    )
    ag_forward_ring(
        n, axis, mesh_axes, xq_hbm, agq_hbm, m, send_sem, recv_sem, consume,
        site="ag_gemm", wire=wire, schedule=schedule,
    )


def _specs(axis, batch_axes, dcn_axis=None):
    """(in_specs, out_specs) for AG-GEMM under shard_map over the full mesh.

    Activation rows may additionally be sharded over ``batch_axes`` (data
    parallelism): the kernel then gathers only the ``axis`` (sequence/TP)
    factor of the rows inside each DP group. Hierarchical (``dcn_axis``):
    the TP factor spans (axis, dcn_axis) with axis-MAJOR row order, so
    the rail-gathered rows per ring slab are contiguous."""
    ba = tuple(batch_axes)
    # a 1-tuple of axis names is equivalent to the bare name for both
    # PartitionSpec and lax collectives, so no flat/hier branching
    tp_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)
    a_spec = P(ba + tp_axes, None)
    b_spec = P(None, tp_axes)
    out_spec = P(ba if ba else None, tp_axes)
    return (a_spec, b_spec), out_spec


@functools.lru_cache(maxsize=256)
def _build_fused(
    mesh, axis, batch_axes, a_shape, b_shape, dtype, out_dtype, collective_id,
    chaos, return_gathered=True, dcn_axis=None, wire=None,
    b_prequant=False, schedule=None,
):
    """Fused engine. ``dcn_axis`` set = the hierarchical decomposition
    (≡ the reference's inter-node AG-GEMM, allgather.py:291-375): the
    DCN rail leg feeds the SAME fused Pallas ring, which runs
    intra-slice over ``axis``. Row layout is axis-major — rows sharded
    P((axis, dcn_axis)) — so railed rows stay slab-contiguous.

    Round 4 (VERDICT r3 #5): the rail is CHUNKED for overlap — instead
    of one serial ``all_gather`` completing before the ring starts, the
    other slices' rows arrive as nd−1 INDEPENDENT ``ppermute`` fetches
    issued up front, and the fused ring runs once per slice chunk
    (local slice first, railed chunks as they land). Nothing in the
    chunk-s ring depends on chunk s+1's fetch, so XLA's async collective
    machinery can fly the DCN legs under the Mosaic calls (≡ the
    reference running inter-node puts concurrently with intra-node
    copies and the consumer GEMM, allgather.py:291-375). Falls back to
    the serial rail when the per-slice slab admits no blocking."""
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    k = a_shape[1]
    n_local = b_shape[1] // (n * nd)
    dp = mesh_axes_size(mesh, batch_axes)
    m_gathered = a_shape[0] // dp  # rows per device after the full AG
    slab_rows = m_gathered // n    # rows per ring step (nd shards railed)
    blocks = pick_mm_blocks(slab_rows, k, n_local, dtype.itemsize)
    if blocks is None:
        raise ValueError(
            f"ag_gemm PALLAS_FUSED: no divisor blocking for shard "
            f"({slab_rows}, {k}) @ ({k}, {n_local}); use XLA_RING"
        )
    if n == 1:
        # degenerate ring: ag_forward_ring early-returns without touching
        # the barrier semaphore, and Mosaic rejects a collective_id on a
        # kernel that never does (same convention as gemm_rs)
        collective_id = None
    fmt = None
    rail_fmt = None
    mx = wire == "int8-mxu"
    if b_prequant and not (mx and dcn_axis is None):
        raise ValueError(
            "b_prequant (weight-resident B) requires wire='int8-mxu' "
            "on a flat mesh"
        )
    m_dev = m_gathered // (n * nd)
    if wire is not None and dcn_axis is not None:
        # hierarchical: the wire rides the DCN RAIL legs (XLA-side
        # quant/dequant around the ppermute fetches / serial gather —
        # Mosaic cast support is irrelevant there); the intra-slice
        # Pallas rings stay on the raw wire. int8-mxu demotes to its
        # int8 payload: the rail dequantizes before any ring consumes.
        rail_fmt = wirelib.make_wire_format(
            wirelib.wire_payload(wire), m_dev, strict=False
        )
        mx = False
    elif mx:
        wirelib.require_mxu("ag_gemm")
        # one scale row per mm row-block: the epilogue's (1, 128) scale
        # operand then indexes plane row i for A row-block i directly
        fmt = wirelib.WireFormat(quant="int8", chunk_rows=blocks[0])
    elif wire is not None:
        from triton_distributed_tpu.config import compiling_for_tpu

        wirelib.require_inkernel(wire, "ag_gemm")
        fmt = wirelib.make_wire_format(
            wire, slab_rows, strict=compiling_for_tpu()
        )
        if fmt is None:
            raise ValueError(
                f"ag_gemm wire={wire!r}: slab of {slab_rows} rows admits "
                "no legal scale chunking; use the bf16 wire"
            )

    def mk_call(m_g, blk, cid):
        if mx:
            nsem = (max(n - 1, 1),)
            return lang.shmem_call(
                functools.partial(
                    _fused_kernel_mx, n, axis, mesh.axis_names, blk, fmt,
                    schedule,
                ),
                out_shape=[
                    jax.ShapeDtypeStruct((m_g, n_local), out_dtype),
                    # the wire workspace IS the gathered representation:
                    # no bf16 twin exists — arrival HBM/VMEM is halved
                    jax.ShapeDtypeStruct((m_g, k), fmt.wire_dtype),
                    jax.ShapeDtypeStruct(
                        (m_g // blk[0], wirelib.SCALE_LANES), jnp.float32
                    ),
                ],
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
                scratch_shapes=[
                    pltpu.VMEM((blk[0], blk[2]), jnp.int32),  # s32 acc
                    pltpu.SemaphoreType.DMA(nsem),
                    pltpu.SemaphoreType.DMA(nsem),
                    pltpu.SemaphoreType.DMA(nsem),   # scale rail
                    pltpu.SemaphoreType.DMA(nsem),
                ],
                collective_id=cid,
                vmem_limit_bytes=fused_vmem_budget(),
                name="ag_gemm_fused_int8mxw",
            )
        if fmt is not None:
            nsem = (max(n - 1, 1),)
            return lang.shmem_call(
                functools.partial(
                    _fused_kernel_w, n, axis, mesh.axis_names, blk,
                    return_gathered, fmt, schedule,
                ),
                out_shape=[
                    jax.ShapeDtypeStruct((m_g, n_local), out_dtype),
                    jax.ShapeDtypeStruct((m_g, k), dtype),      # gathered A
                    # wire workspaces: quantized slabs + scale planes
                    jax.ShapeDtypeStruct((m_g, k), fmt.wire_dtype),
                    jax.ShapeDtypeStruct(
                        (fmt.chunks(m_g), wirelib.SCALE_LANES), jnp.float32
                    ),
                ],
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
                scratch_shapes=[
                    pltpu.VMEM((blk[0], blk[2]), jnp.float32),
                    pltpu.SemaphoreType.DMA,
                    pltpu.SemaphoreType.DMA(nsem),
                    pltpu.SemaphoreType.DMA(nsem),
                    pltpu.SemaphoreType.DMA(nsem),   # scale rail
                    pltpu.SemaphoreType.DMA(nsem),
                ],
                collective_id=cid,
                vmem_limit_bytes=fused_vmem_budget(),
                name=f"ag_gemm_fused_{wire}w",
            )
        return lang.shmem_call(
            functools.partial(
                _fused_kernel, n, axis, mesh.axis_names, blk,
                return_gathered, schedule,
            ),
            out_shape=[
                jax.ShapeDtypeStruct((m_g, n_local), out_dtype),
                jax.ShapeDtypeStruct((m_g, k), dtype),  # gathered A
            ],
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk[0], blk[2]), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
                pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            ],
            collective_id=cid,
            vmem_limit_bytes=fused_vmem_budget(),
            name="ag_gemm_fused",
        )

    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)
    ba = tuple(batch_axes)
    ag_spec = P(ba if ba else None, None)
    chunk_blocks = (
        pick_mm_blocks(m_dev, k, n_local, dtype.itemsize)
        if dcn_axis is not None and nd > 1 else None
    )
    if dcn_axis is None:
        call = lang.maybe_instrument(
            mk_call(m_gathered, blocks, collective_id),
            axis=axis, site="ag_gemm", collective_id=collective_id, n=n,
        )
        if fmt is None:
            body = call
        elif mx and b_prequant:
            def body(a_loc, bq_loc, bs_loc):
                # weight-RESIDENT int8-mxu: B's (bq, bs) pair arrives
                # pre-quantized (quantize_grouped_weights convention) —
                # only the moving A slab quantizes per call
                aq, asc = wirelib.quantize_slab(a_loc, fmt)
                out, agq, ags = call(aq, asc, bq_loc, bs_loc)
                if not return_gathered:
                    return out, agq
                g = wirelib.dequantize_slab(agq, ags, fmt, dtype)
                me = jax.lax.axis_index(axis)
                return out, jax.lax.dynamic_update_slice(
                    g, a_loc, (me * slab_rows, 0)
                )
        elif mx:
            def body(a_loc, b_loc):
                # both operands quantized ONCE in XLA (fuse with their
                # producers); the kernel consumes wire bytes end to end
                aq, asc = wirelib.quantize_slab(a_loc, fmt)
                bq, bsc = wirelib.quantize_cols(b_loc)
                out, agq, ags = call(aq, asc, bq, bsc)
                if not return_gathered:
                    # the gathered output is dead to the caller — hand
                    # back the wire workspace untouched (no dequant ever)
                    return out, agq
                g = wirelib.dequantize_slab(agq, ags, fmt, dtype)
                me = jax.lax.axis_index(axis)
                return out, jax.lax.dynamic_update_slice(
                    g, a_loc, (me * slab_rows, 0)
                )
        else:
            def body(a_loc, b_loc):
                # quantize the local slab ONCE in XLA (fuses with the
                # producer); the ring forwards these exact wire bytes
                aq, asc = wirelib.quantize_slab(a_loc, fmt)
                out = call(a_loc, aq, asc, b_loc)
                return out[0], out[1]
    elif chunk_blocks is None:
        call = mk_call(m_gathered, blocks, collective_id)

        def body(a_loc, b_loc):
            # serial rail fallback: gather my axis-position's rows across
            # slices (axis-major rows → the railed slab is contiguous),
            # over the quantized rail when the wire is on
            if rail_fmt is None:
                ag = jax.lax.all_gather(a_loc, dcn_axis, tiled=True)
            else:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_all_gather,
                )

                ag = dcn_wire_all_gather(a_loc, dcn_axis, rail_fmt)
            return call(ag, b_loc)
    else:
        # distinct collective_ids per chunk ring: strict per-chunk
        # rendezvous on the barrier semaphore (a skewed neighbor's
        # chunk-s+1 signal must not satisfy a chunk-s wait); the offset
        # range is reserved in the registry's rail ledger (checked
        # disjoint from every other chunked family)
        from triton_distributed_tpu.kernels.registry import rail_collective_id

        chunk_calls = [
            mk_call(
                n * m_dev, chunk_blocks,
                rail_collective_id("ag_gemm.dcn_chunks", collective_id, s),
            )
            for s in range(nd)
        ]

        def body(a_loc, b_loc):
            my = jax.lax.axis_index(dcn_axis)
            # nd−1 independent rail fetches, all issued before any ring:
            # chunk s holds slice (my − s)'s rows. With the rail wire on,
            # each fetch moves the once-quantized payload + scale plane
            # (≈2× fewer DCN bytes) and dequantizes on arrival.
            if rail_fmt is not None:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_fetches,
                )

                chunks = dcn_wire_fetches(a_loc, dcn_axis, nd, rail_fmt)
            else:
                chunks = [a_loc] + [
                    jax.lax.ppermute(
                        a_loc, dcn_axis,
                        [(i, (i + s) % nd) for i in range(nd)],
                    )
                    for s in range(1, nd)
                ]
            pieces = [
                chunk_calls[s](chunks[s], b_loc) for s in range(nd)
            ]
            o = jnp.stack([p[0] for p in pieces])   # (nd, n·m_dev, n_local)
            g = jnp.stack([p[1] for p in pieces])   # (nd, n·m_dev, k)
            order = jnp.mod(my - jnp.arange(nd), nd)  # chunk idx per slice

            def reorder(x):
                # chunk-major → the axis-major global row order the
                # out_specs promise: [axis pos][slice][m_dev]
                x = jnp.take(x, order, axis=0)
                x = x.reshape(nd, n, m_dev, x.shape[-1])
                return jnp.transpose(x, (1, 0, 2, 3)).reshape(
                    n * nd * m_dev, x.shape[-1]
                )

            if not return_gathered:
                # the gathered-A output is dead to the caller — a flat
                # reshape satisfies the shape without paying a ~full-A
                # gather+transpose copy per step
                return reorder(o), g.reshape(n * nd * m_dev, k)
            return reorder(o), reorder(g)
    if b_prequant:
        # (a, bq, bs): the scale row shards like B's columns
        in_specs = tuple(in_specs) + (in_specs[1],)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(out_specs, ag_spec),
        check_vma=False,
    )
    return jax.jit(fn)


def ag_gemm_device(a_loc, b_loc, axis, *, out_dtype=None, wire=None,
                   b_quant=None):
    """Per-device XLA-ring AG-GEMM body — usable inside any shard_map.

    ppermute hops overlap the next step's dot via XLA async collective
    permute (the reference's comm-stream/GEMM-stream overlap, expressed
    through the XLA scheduler instead of streams).

    ``wire`` ('fp8'/'int8'): the hops carry the ONCE-quantized slab +
    per-chunk scales (lang.wire layout — the same bytes the fused wire
    ring ships) and each arrival is dequantized before its dot; the own
    shard never crosses the wire and is consumed exact.

    ``wire='int8-mxu'``: the standalone AG→matmul twin of the fused
    int8→MXU engine — identical rails, but every arriving slab (and the
    local one, for uniform numerics) feeds an s8×s8→s32 dot against the
    per-out-channel-quantized B with the chunk·channel scale product
    folded onto the accumulator; no dequantized copy of A ever exists.

    ``b_quant``: a PRE-QUANTIZED ``(bq (K, N) int8, bs (1, N) f32)``
    pair for the int8-mxu consumer (weight-residency: serving layers
    already holding ``quantize_grouped_weights``-style dicts pass the
    pair through instead of paying a per-call ``quantize_cols`` of B —
    the ROADMAP carried-forward item the engine's steady-state decode
    loop makes measurable). Only consumed when ``wire='int8-mxu'`` and
    the slab admits the wire layout; ``b_loc`` may then be None."""
    n = jax.lax.axis_size(axis)
    m_local = a_loc.shape[0]
    out_dtype = out_dtype or a_loc.dtype
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    mx = wire == "int8-mxu"
    fmt = None
    if wire is not None:
        from triton_distributed_tpu.config import compiling_for_tpu

        fmt = wirelib.make_wire_format(
            wire, m_local, strict=compiling_for_tpu()
        )
    if mx and fmt is not None:
        if b_quant is not None:
            bq, bs = b_quant          # resident pair: no per-call quant
        else:
            bq, bs = wirelib.quantize_cols(b_loc)
        q, sc = wirelib.quantize_slab(a_loc, fmt)
        # per-row expand of the lane-replicated chunk scales (XLA side —
        # the fused kernel instead pins chunk_rows == block_m)
        row_scale = jnp.repeat(sc[:, :1], fmt.chunk_rows, axis=0)

        def s8_tile(q_cur, rs_cur):
            acc = jax.lax.dot_general(
                q_cur, bq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return (acc.astype(jnp.float32) * rs_cur * bs).astype(out_dtype)

        out = jnp.zeros((n * m_local, bq.shape[1]), out_dtype)
        out = jax.lax.dynamic_update_slice(
            out, s8_tile(q, row_scale), (me * m_local, 0)
        )

        def step_mx(s, carry):
            q_cur, sc_cur, out = carry
            q_cur = jax.lax.ppermute(q_cur, axis, perm=perm)
            sc_cur = jax.lax.ppermute(sc_cur, axis, perm=perm)
            src = jax.lax.rem(me + n - s, n)
            rs_cur = jnp.repeat(sc_cur[:, :1], fmt.chunk_rows, axis=0)
            out = jax.lax.dynamic_update_slice(
                out, s8_tile(q_cur, rs_cur), (src * m_local, 0)
            )
            return q_cur, sc_cur, out

        _, _, out = jax.lax.fori_loop(1, n, step_mx, (q, sc, out))
        return out
    if mx:
        if b_loc is None:
            # a resident pair whose slab admits no wire layout: widen
            # ONCE here (the degradation twin of the dequant-free path)
            bq, bs = b_quant
            b_loc = (bq.astype(jnp.float32) * bs).astype(a_loc.dtype)
        fmt = None  # no legal chunking: stay on the exact wire

    out = jnp.zeros((n * m_local, b_loc.shape[1]), out_dtype)
    if fmt is None:
        def step(s, carry):
            a_cur, out = carry
            src = jax.lax.rem(me + n - s, n)
            tile = jnp.dot(a_cur, b_loc, preferred_element_type=jnp.float32)
            out = jax.lax.dynamic_update_slice(
                out, tile.astype(out_dtype), (src * m_local, 0)
            )
            a_next = jax.lax.ppermute(a_cur, axis, perm=perm)
            return a_next, out

        a_cur, out = jax.lax.fori_loop(0, n - 1, step, (a_loc, out))
        src = jax.lax.rem(me + 1, n)  # after n-1 hops I hold shard me+1
        tile = jnp.dot(a_cur, b_loc, preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(
            out, tile.astype(out_dtype), (src * m_local, 0)
        )

    # quantized wire: own shard exact, remote shards dequantized from the
    # once-quantized payload + scale plane riding the permute hops
    tile = jnp.dot(a_loc, b_loc, preferred_element_type=jnp.float32)
    out = jax.lax.dynamic_update_slice(
        out, tile.astype(out_dtype), (me * m_local, 0)
    )
    q, sc = wirelib.quantize_slab(a_loc, fmt)

    def step_w(s, carry):
        q_cur, sc_cur, out = carry
        q_cur = jax.lax.ppermute(q_cur, axis, perm=perm)
        sc_cur = jax.lax.ppermute(sc_cur, axis, perm=perm)
        src = jax.lax.rem(me + n - s, n)
        a_cur = wirelib.dequantize_slab(q_cur, sc_cur, fmt, a_loc.dtype)
        tile = jnp.dot(a_cur, b_loc, preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice(
            out, tile.astype(out_dtype), (src * m_local, 0)
        )
        return q_cur, sc_cur, out

    _, _, out = jax.lax.fori_loop(1, n, step_w, (q, sc, out))
    return out


@functools.lru_cache(maxsize=256)
def _build_xla_ring(mesh, axis, batch_axes, out_dtype, dcn_axis=None,
                    wire=None, b_prequant=False):
    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)
    if b_prequant:
        # resident int8-mxu weights: body takes (a, bq, bs) — no
        # per-call quantize_cols of B (flat mesh only; the host entry
        # widens for hierarchical calls)
        assert dcn_axis is None and wire == "int8-mxu"
        (a_spec, b_spec), _ = (in_specs, out_specs)

        def body_q(a_loc, bq_loc, bs_loc):
            return ag_gemm_device(
                a_loc, None, axis, out_dtype=out_dtype, wire=wire,
                b_quant=(bq_loc, bs_loc),
            )

        return jax.jit(jax.shard_map(
            body_q, mesh=mesh, in_specs=(a_spec, b_spec, b_spec),
            out_specs=out_specs, check_vma=False,
        ))

    def body(a_loc, b_loc):
        if dcn_axis is not None:
            # same rail/ring split as the fused engine: DCN leg via
            # lax, ppermute ring intra-slice over nd× slabs — with the
            # wire on, the rail leg ships the quantized payload too
            w_rail = wirelib.wire_payload(wire)
            rail_fmt = (
                wirelib.make_wire_format(w_rail, a_loc.shape[0],
                                         strict=False)
                if w_rail is not None else None
            )
            if rail_fmt is not None:
                from triton_distributed_tpu.runtime.multislice import (
                    dcn_wire_all_gather,
                )

                a_loc = dcn_wire_all_gather(a_loc, dcn_axis, rail_fmt)
            else:
                a_loc = jax.lax.all_gather(a_loc, dcn_axis, tiled=True)
        return ag_gemm_device(
            a_loc, b_loc, axis, out_dtype=out_dtype, wire=wire
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _build_gather(mesh, axis, batch_axes, dcn_axis=None):
    """Standalone row-gather used when ``return_gathered=True`` rides an
    XLA engine (the fused engine produces the gathered A for free)."""
    ba = tuple(batch_axes)
    tp_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)
    fn = jax.shard_map(
        lambda x: jax.lax.all_gather(x, tp_axes, tiled=True),
        mesh=mesh,
        in_specs=_specs(axis, batch_axes, dcn_axis)[0][0],
        out_specs=P(ba if ba else None, None),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _build_xla_naive(mesh, axis, batch_axes, out_dtype, dcn_axis=None):
    tp_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)

    def body(a_loc, b_loc):
        a_full = jax.lax.all_gather(a_loc, tp_axes, tiled=True)
        return jnp.dot(a_full, b_loc, preferred_element_type=jnp.float32).astype(
            out_dtype
        )

    in_specs, out_specs = _specs(axis, batch_axes, dcn_axis)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _engine_tuner(mesh, axis, batch_axes, out_dtype, collective_id,
                  return_gathered, dcn_axis=None, wire=None):
    """Measured engine selection for ``method=None`` (≡ wrapping the op
    in contextual_autotune, reference autotuner.py:97): every engine is
    benchmarked end to end per input shape, the winner persists on disk,
    and the MAX consensus keeps multi-process meshes aligned. Engines
    that cannot build for a shape (e.g. unblockable PALLAS_FUSED) fail
    to +inf and lose. out_dtype/collective_id/wire are part of the tuner
    name (and so the cache key): a winner for one out_dtype or wire
    format must not be applied to another it might not even build for."""
    from triton_distributed_tpu.tune.autotuner import method_tuner

    def run(a, b, *, method):
        return ag_gemm(
            a, b, mesh, axis, batch_axes=batch_axes,
            method=AGGemmMethod(method), out_dtype=out_dtype,
            collective_id=collective_id, return_gathered=return_gathered,
            dcn_axis=dcn_axis, wire_dtype=wire,
        )

    return method_tuner(
        f"ag_gemm[{dict(mesh.shape)}|{axis}|{batch_axes}|{out_dtype}|"
        f"{collective_id}|rg{int(return_gathered)}|{dcn_axis}|w{wire}]",
        run, AGGemmMethod,
    )


@functools.lru_cache(maxsize=64)
def _wire_tuner(mesh, axis, batch_axes, out_dtype, collective_id,
                return_gathered, dcn_axis=None, wq=None):
    """Measured wire-dtype selection for ``wire_dtype='auto'``: the
    bf16 wire and the fp8 wire are benchmarked end to end and the
    winner persists (the same thunk-level contract as the engine
    tuners — a wire format is just another config of the whole op).
    ``wq='int8'`` adds the dequant-free 'int8-mxu' candidate (the
    caller's weight intent is what makes its numerics acceptable) and
    is part of the tuner name, so winners never leak across intents."""
    from triton_distributed_tpu.tune.autotuner import wire_tuner

    def run(a, b, *, wire_dtype):
        # engine pinned to the static heuristic: the wire sweep must
        # compare wire formats on ONE engine, not recurse into the
        # engine tuner's own benching mid-measurement
        dp = mesh_axes_size(mesh, tuple(batch_axes))
        method = auto_ag_gemm_method(
            mesh, axis, a, b, dp=dp, dcn_axis=dcn_axis
        )
        return ag_gemm(
            a, b, mesh, axis, batch_axes=batch_axes, method=method,
            out_dtype=out_dtype, collective_id=collective_id,
            return_gathered=return_gathered, dcn_axis=dcn_axis,
            wire_dtype=wire_dtype,
        )

    return wire_tuner(
        f"ag_gemm_wire[{dict(mesh.shape)}|{axis}|{batch_axes}|{out_dtype}|"
        f"{collective_id}|rg{int(return_gathered)}|{dcn_axis}|wq{wq}]",
        run, mxu=(wq == "int8"),
    )


def auto_ag_gemm_method(mesh, axis, a, b, dp: int = 1,
                        dcn_axis: str | None = None) -> AGGemmMethod:
    """≡ reference method auto-selection (allgather.py:54-69): topology +
    shape blockability decide the engine. The streaming fused engine has no
    working-set VMEM gate; it is skipped only when the intra-slice ``axis``
    itself crosses DCN (no Pallas remote DMA across slices — declare the
    cross-slice factor as ``dcn_axis`` for the hierarchical engine) or on
    shapes with no divisor blocking — and the fallback is *logged* so
    nobody silently benchmarks XLA believing it is the fused kernel."""
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    topo = detect_topology(mesh, axis)
    if topo.link_kind == LinkKind.DCN:
        _warn_once(
            ("ag_gemm", "dcn", axis),
            f"ag_gemm: axis {axis!r} crosses DCN; using XLA_RING engine "
            "(pass the cross-slice factor as dcn_axis= to keep the fused "
            "engine intra-slice)",
        )
        return AGGemmMethod.XLA_RING
    slab_rows = a.shape[0] // (dp * n)
    blocks = pick_mm_blocks(
        slab_rows, a.shape[1], b.shape[1] // (n * nd), a.dtype.itemsize
    )
    if blocks is None:
        _warn_once(
            ("ag_gemm", "blocks", a.shape, b.shape),
            f"ag_gemm: shard ({slab_rows}, {a.shape[1]}) @ "
            f"({a.shape[1]}, {b.shape[1] // (n * nd)}) admits no divisor "
            "blocking; falling back to XLA_RING",
        )
        return AGGemmMethod.XLA_RING
    return AGGemmMethod.PALLAS_FUSED


def resolve_ag_gemm_wire(
    mesh, axis, a, b, *, batch_axes=(), method=None, wire_dtype=None,
    dcn_axis: str | None = None, dp: int | None = None,
    wq: str | None = None,
) -> str | None:
    """The wire format :func:`ag_gemm` will ACTUALLY ship for these
    arguments: None (raw bf16 wire) unless a ring engine runs and the
    slab admits the lang.wire layout. ``'auto'`` consults the measured
    wire tuner (when tuning is enabled and args are concrete), else the
    perf model's comm-bound test — compressed exactly when the bf16
    ring transfer, not the shard matmul, is the per-step critical path,
    and picking the dequant-free ``'int8-mxu'`` consumer wire there
    when the caller declared an int8 weight intent (``wq='int8'``).

    Hierarchical (``dcn_axis``) calls resolve the wire for the DCN RAIL
    legs (the payload format the ppermute fetches ship; 'int8-mxu'
    demotes to its 'int8' payload — the rail dequantizes before any MXU
    sees it)."""
    from triton_distributed_tpu.config import compiling_for_tpu

    w = wirelib.normalize_wire(wire_dtype)
    if w is None:
        return None
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    if dp is None:
        dp = mesh_axes_size(mesh, tuple(batch_axes))
    if n * nd == 1:
        return None
    if method == AGGemmMethod.XLA_NAIVE:
        return None  # no ring — nothing to compress
    k = a.shape[1]
    if dcn_axis is not None:
        # the DCN rail wire: XLA-side quant/dequant around the rail legs
        # — runs on any backend, so only payload-layout eligibility gates
        m_dev = a.shape[0] // (dp * n * nd)
        if w == "auto":
            if not wirelib.wire_blockable(m_dev, k, "fp8", False):
                return None
            from triton_distributed_tpu.runtime.topology import (
                auto_allgather_wire,
            )

            # a DCN leg is always comm-bound relative to ICI; compress
            # whenever the payload clears the fixed-cost threshold
            return auto_allgather_wire(m_dev * k * a.dtype.itemsize)
        payload = wirelib.wire_payload(w)
        if not wirelib.wire_blockable(m_dev, k, payload, False):
            raise ValueError(
                f"ag_gemm wire_dtype={w!r}: DCN rail slab ({m_dev}, {k}) "
                "admits no legal wire chunking (a pinned wire format is "
                "a contract); use wire_dtype='auto' or the bf16 wire"
            )
        return payload
    slab_rows = a.shape[0] // (dp * n)
    strict = compiling_for_tpu()
    # in-kernel wire consumption happens only on the fused engine; XLA
    # engines carry fp8 / s8 dots natively regardless of Mosaic support
    inkernel = method == AGGemmMethod.PALLAS_FUSED
    if w == "auto":
        if not wirelib.wire_blockable(slab_rows, k, "fp8", strict):
            return None
        from triton_distributed_tpu.tune.autotuner import tuned_method_or_none

        tuned = tuned_method_or_none(
            lambda: _wire_tuner(
                mesh, axis, tuple(batch_axes), jnp.dtype(a.dtype), 5,
                False, dcn_axis, wq,
            ),
            a, b, key="wire_dtype",
        )
        if tuned is not None:
            w = wirelib.normalize_wire(tuned)
        else:
            from triton_distributed_tpu.tune.perf_model import (
                auto_wire_dtype,
            )

            n_local = b.shape[1] // n
            w = wirelib.normalize_wire(auto_wire_dtype(
                slab_rows, k, n_local, a.dtype.itemsize, consumer_wq=wq,
            ))
        if w == "int8-mxu" and inkernel and not wirelib.inkernel_s8_dot_ok():
            # the caller already declared int8 numerics (wq='int8'), so
            # demoting to the dequant-then-matmul int8 wire is not a
            # silent numerics-class switch — only the MXU feed changes
            w = "int8"
        if w == "fp8" and inkernel and not wirelib.inkernel_wire_ok("fp8"):
            # no silent numerics switch to int8: auto keeps the exact
            # wire where the toolchain cannot carry fp8 in-kernel
            return None
        return w
    if inkernel:
        if w == "int8-mxu":
            wirelib.require_mxu("ag_gemm")
        else:
            wirelib.require_inkernel(w, "ag_gemm")
    if not wirelib.wire_blockable(slab_rows, k, w, strict):
        raise ValueError(
            f"ag_gemm wire_dtype={w!r}: slab ({slab_rows}, {k}) admits no "
            "legal wire chunking/blocking (a pinned wire format is a "
            "contract); use wire_dtype='auto' or the bf16 wire"
        )
    return w


def resolve_ag_gemm_method(
    a_mesh, axis, a, b, *, batch_axes=(), method=None, out_dtype=None,
    collective_id: int = 5, return_gathered: bool = False,
    dcn_axis: str | None = None, wire_dtype=None,
) -> AGGemmMethod:
    """The engine :func:`ag_gemm` will ACTUALLY run for these arguments:
    the explicit ``method``, else the tuned winner (when tuning is
    enabled and the args are concrete), else the topology/blockability
    heuristic — with the safety recheck demoting a fused winner that is
    not buildable in this environment. Exposed so callers that must act
    on the resolved engine (ops.overlap's save_gathered residual gate)
    agree with the entry instead of re-guessing."""
    if method is not None:
        return method
    from triton_distributed_tpu.tune.autotuner import tuned_method_or_none

    batch_axes = tuple(batch_axes)
    dp = mesh_axes_size(a_mesh, batch_axes)
    out_dtype = out_dtype or a.dtype
    m = tuned_method_or_none(
        lambda: _engine_tuner(
            a_mesh, axis, batch_axes, jnp.dtype(out_dtype), collective_id,
            return_gathered, dcn_axis, wirelib.normalize_wire(wire_dtype),
        ),
        a, b,
    )
    auto = functools.partial(
        auto_ag_gemm_method, a_mesh, axis, a, b, dp=dp, dcn_axis=dcn_axis
    )
    method = AGGemmMethod(m) if m else auto()
    if method == AGGemmMethod.PALLAS_FUSED and auto() != method:
        # a persisted winner from another environment (bigger VMEM
        # budget, non-DCN mesh) may no longer be buildable here; the
        # heuristic encodes exactly those safety constraints
        method = auto()
    return method


def ag_gemm(
    a,
    b,
    mesh,
    axis: str = "x",
    *,
    batch_axes: tuple = (),
    method: AGGemmMethod | None = None,
    out_dtype=None,
    collective_id: int = 5,
    return_gathered: bool = False,
    dcn_axis: str | None = None,
    wire_dtype=None,
    wq: str | None = None,
    b_quant=None,
    schedule=None,
):
    """Fused AllGather(A) @ B for column-parallel TP.

    ``wire_dtype``: what the ring ships (docs/PERF.md "Quantized wire").
    None/'bf16' — the raw compute dtype (default, today's numerics);
    'fp8'/'int8' — 1-byte payload + per-chunk f32 scales (lang.wire),
    quantized once at the source, dequantized on receive before the MXU
    (own shard consumed exact); 'int8-mxu' — the DEQUANT-FREE consumer
    wire: identical int8 rails, but every slab (local included) feeds
    the MXU's native s8×s8→s32 path against the per-out-channel
    quantized B, with the chunk·channel scale product folded into the
    accumulator epilogue — no per-arrival dequant pass, half the
    arrival VMEM, 2× the MXU rate; 'auto' — the measured wire tuner,
    else the perf model picks the compressed wire exactly when the bf16
    ring transfer is the per-step critical path (comm-bound shapes),
    preferring 'int8-mxu' there when ``wq='int8'``. With a compressed
    wire the gathered-A output (``return_gathered``) holds the
    dequantized remote slabs — inference-grade, like the MoE wire.

    ``wq``: the caller's weight-quantization intent ('int8' or None).
    It does not change B's storage here; it licenses the auto selector
    to pick 'int8-mxu', whose epilogue quantizes B per out-channel.

    ``b_quant``: PRE-QUANTIZED weight residency (ROADMAP carried-
    forward, closed by the serving engine's steady-state loop): a
    ``(bq (K, N) int8, bs per-out-channel f32)`` pair — or pass ``b``
    itself as a ``{"q", "scale"}`` dict (the
    ``quantize_grouped_weights`` convention) — and the int8-mxu
    consumers feed it straight to the s8×s8 epilogue with NO per-call
    ``quantize_cols`` of B. When the int8-mxu wire is not eligible
    (1-device mesh, hierarchical call, pinned other wire, slab without
    a wire layout), B is widened ONCE per call and the ordinary engine
    runs — the same degradation discipline as every other knob.

    ``a``: (M, K) with rows sharded over ``(*batch_axes, axis)`` — each
    device holds an M/(dp·n) row shard; the kernel gathers the ``axis``
    factor within each DP group (Megatron sequence-parallel layout).
    ``b``: (K, N) sharded P(None, axis) — column-parallel weight.
    Returns (M, N) with rows sharded over ``batch_axes``, cols over ``axis``.

    ``dcn_axis``: hierarchical TP spanning slices (≡ the reference's
    inter-node AG-GEMM, allgather.py:291-375). The TP factor is
    (axis, dcn_axis) with AXIS-MAJOR ordering — rows P((axis, dcn_axis)),
    weight cols likewise: the other slices' rows cross DCN as nd−1
    independent ``ppermute`` fetches feeding per-slice fused rings
    (local slice first), so the DCN legs fly under the Mosaic calls;
    a serial ``lax.all_gather`` rail feeding one nd×-slab ring is the
    fallback when the per-slice slab admits no blocking (see
    _build_fused and docs/PERF.md's DCN-overlap section).

    ``return_gathered=True`` additionally returns the gathered activations
    (the reference exposes them in its symmetric workspace; callers reuse
    them for subsequent ops). Only the fused engine produces them for free;
    other engines re-gather via ``lax.all_gather``.

    Host entry ≡ reference ``ag_gemm`` (allgather_gemm.py:539) +
    ``rowise_ag_gemm_dispatcher`` (:586-661).
    """
    n = mesh.shape[axis]
    nd = mesh.shape[dcn_axis] if dcn_axis else 1
    batch_axes = tuple(batch_axes)
    dp = mesh_axes_size(mesh, batch_axes)
    out_dtype = out_dtype or a.dtype
    if isinstance(b, dict):
        # quantized-dict weight (the serving layers' storage): implies
        # the resident int8-mxu consumer
        b_quant = (b["q"], b["scale"])
        b = None
    if b_quant is not None:
        bq = b_quant[0]
        bs = jnp.asarray(b_quant[1], jnp.float32).reshape(1, -1)
        assert a.shape[1] == bq.shape[0], (
            f"contract dim mismatch {a.shape} @ {bq.shape}"
        )
        slab_rows = a.shape[0] // (dp * n * nd)
        eligible = (
            n * nd > 1 and dcn_axis is None
            and wirelib.normalize_wire(wire_dtype) in (None, "int8-mxu",
                                                       "auto")
            and wirelib.make_wire_format(
                "int8-mxu", slab_rows * nd, strict=False
            ) is not None
        )
        if eligible:
            proxy = jax.ShapeDtypeStruct(bq.shape, a.dtype)
            try:
                method = resolve_ag_gemm_method(
                    mesh, axis, a, proxy, batch_axes=batch_axes,
                    method=method, out_dtype=out_dtype,
                    collective_id=collective_id,
                    return_gathered=return_gathered,
                    wire_dtype="int8-mxu",
                )
            except Exception:
                method = AGGemmMethod.XLA_RING
            if method == AGGemmMethod.PALLAS_FUSED:
                try:
                    from triton_distributed_tpu.tune.schedule import (
                        resolve_schedule,
                    )

                    fn = _build_fused(
                        mesh, axis, batch_axes, a.shape, bq.shape,
                        a.dtype, jnp.dtype(out_dtype), collective_id,
                        interp_key(), return_gathered, None, "int8-mxu",
                        True,
                        resolve_schedule(
                            "ag_gemm.fused", a.shape, (n * nd,),
                            "int8-mxu", schedule,
                        ),
                    )
                    out, gathered = fn(a, bq, bs)
                    return (out, gathered) if return_gathered else out
                except ValueError:
                    pass                       # unblockable: XLA ring
            fn = _build_xla_ring(
                mesh, axis, batch_axes, jnp.dtype(out_dtype), None,
                "int8-mxu", True,
            )
            out = fn(a, bq, bs)
            if return_gathered:
                return out, _build_gather(mesh, axis, batch_axes, None)(a)
            return out
        # ineligible for the resident consumer: widen ONCE per call and
        # run the ordinary engine (documented degradation)
        b = (bq.astype(jnp.float32) * bs).astype(a.dtype)
    assert a.shape[0] % (n * nd * dp) == 0 and b.shape[1] % (n * nd) == 0
    assert a.shape[1] == b.shape[0], f"contract dim mismatch {a.shape} @ {b.shape}"
    if n * nd == 1:
        out = jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)
        return (out, a) if return_gathered else out
    method = resolve_ag_gemm_method(
        mesh, axis, a, b, batch_axes=batch_axes, method=method,
        out_dtype=out_dtype, collective_id=collective_id,
        return_gathered=return_gathered, dcn_axis=dcn_axis,
        wire_dtype=wire_dtype,
    )
    wire = resolve_ag_gemm_wire(
        mesh, axis, a, b, batch_axes=batch_axes, method=method,
        wire_dtype=wire_dtype, dcn_axis=dcn_axis, dp=dp, wq=wq,
    )
    if method == AGGemmMethod.PALLAS_FUSED:
        from triton_distributed_tpu.tune.schedule import resolve_schedule

        sched = resolve_schedule(
            "ag_gemm.fused", a.shape, (n * nd,), wire, schedule
        )
        if (
            sched is not None and sched.dequant == "epilogue"
            and wire == "int8" and dcn_axis is None
            and wirelib.inkernel_s8_dot_ok()
        ):
            # a searched epilogue-dequant schedule means the winner was
            # gated on the MXU-consumer kernel twin: the int8 payload is
            # consumed straight by the s8×s8 epilogue, no dequant pass
            wire = "int8-mxu"
        fn = _build_fused(
            mesh, axis, batch_axes, a.shape, b.shape, a.dtype, out_dtype,
            collective_id, interp_key(), return_gathered, dcn_axis, wire,
            False, sched,
        )
        out, gathered = fn(a, b)
        return (out, gathered) if return_gathered else out
    if method == AGGemmMethod.XLA_RING:
        fn = _build_xla_ring(
            mesh, axis, batch_axes, out_dtype, dcn_axis, wire
        )
    else:
        fn = _build_xla_naive(mesh, axis, batch_axes, out_dtype, dcn_axis)
    out = fn(a, b)
    if return_gathered:
        return out, _build_gather(mesh, axis, batch_axes, dcn_axis)(a)
    return out
