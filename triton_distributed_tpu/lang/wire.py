"""Wire quantization for the streaming rings: fp8/int8 payloads with
per-chunk scales.

The MoE A2A transport already proved out fp8+scales wire compression
(kernels/moe_all_to_all.py; docs/PERF.md, "fp8+scales fused-chunked-dma");
this module generalizes the idea to the AG/RS streaming rings so the
fused TP engines (ag_gemm, gemm_rs, the moe_tp_fused pair) and the
standalone ring collectives can move 1-byte slabs on comm-bound shapes
— DeepEP-style low-latency transports in the reference compress their
dispatch payloads for exactly this reason (arXiv:2504.19442).

Layout contract (shared by the Pallas rings and their XLA twins, so
both ship byte-identical wire formats):

* payload: the (rows, cols) slab cast to the wire dtype — fp8 (e4m3)
  or int8, 1 byte/element;
* scales: ONE f32 scale per CHUNK of ``chunk_rows`` consecutive rows
  (symmetric quantization, scale = chunk amax / QMAX), shipped as a
  (rows // chunk_rows, 128) f32 plane with the scale replicated across
  the 128 lanes — the lane replication makes the plane a legal Mosaic
  block operand ((1, 128) blocks, the flash-decode scale-plane idiom)
  and costs 512 B per chunk, negligible against chunk_rows·cols wire
  bytes at ring-slab scale.

Semantics:

* AG-side rings quantize ONCE at the source and forward the quantized
  bytes unchanged; receivers dequantize to the compute dtype before
  the MXU consumes the shard (each rank's OWN shard is consumed exact
  — it never crosses the wire).
* RS-side rings must re-quantize at every hop (each hop's payload is a
  new partial sum); the receive side dequantizes and accumulates in
  f32 before casting back, so the reduction error stays bounded by
  (n-1) independent per-hop roundings rather than compounding through
  the accumulator.

The value-level transforms are gradient-opaque (quantize rounds), so
the forward ops treat the wire knob as a transport option, mirroring
the MoE transport. Gradient RINGS ride the wire too — via the seeded
stochastic-rounding twin :func:`quantize_slab_sr` plus the per-hop
error feedback in ``train.grad_wire``, which together keep the
accumulated backward error bounded instead of compounding.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

#: accepted wire_dtype spellings. None and "bf16" both mean "raw wire"
#: (ship the compute dtype, today's behavior); "int8-mxu" ships the
#: int8 payload AND ends the wire at the MXU — the consumer runs an
#: s8×s8→s32 matmul on the arriving slab and folds the chunk scale into
#: the f32 accumulator epilogue (no per-arrival dequant pass); "auto"
#: defers to the perf-model / autotuner selection at the op entry.
WIRE_DTYPES = (None, "bf16", "fp8", "int8", "int8-mxu", "auto")

_QMAX = {"fp8": 448.0, "int8": 127.0}
_WDT = {"fp8": jnp.float8_e4m3fn, "int8": jnp.int8}

#: lane width of the scale planes (one f32 scale replicated per lane).
SCALE_LANES = 128


def _lint_recorder():
    """The active shmemlint recorder, or None (the overwhelmingly
    common case). The wire transforms are hookable the same way the
    ``lang.shmem`` primitives are: under symbolic execution they emit
    Quant/Dequant events carrying their ref regions (the provenance
    edges the SL008–SL010 data-correctness passes replay) instead of
    running the value-level pipelines."""
    from triton_distributed_tpu.analysis import events

    return events.active_recorder()


def paired_scale_ok(q_rows: int, s_shape: tuple) -> bool:
    """THE wire layout contract, exported for the static checker: a
    payload slab of ``q_rows`` rows pairs with an ``(s_rows,
    SCALE_LANES)`` f32 scale plane whose rows evenly chunk the payload
    (chunk_rows = q_rows / s_rows). shmemlint's SL009 validates every
    payload/scale RDMA pair against this instead of re-deriving layout
    from kernel internals."""
    if len(s_shape) != 2:
        return False
    s_rows, s_cols = s_shape
    return (
        s_cols == SCALE_LANES and s_rows > 0 and q_rows > 0
        and q_rows % s_rows == 0
    )


def normalize_wire(wire_dtype) -> str | None:
    """Canonical wire spelling: None for raw bf16 wire, 'fp8'/'int8'
    for compressed, 'int8-mxu' for the epilogue-dequant consumer wire,
    'auto' passed through for the selectors."""
    if wire_dtype in (None, "bf16"):
        return None
    if wire_dtype in ("fp8", "int8", "int8-mxu", "auto"):
        return wire_dtype
    raise ValueError(
        f"wire_dtype must be one of {WIRE_DTYPES}, got {wire_dtype!r}"
    )


def wire_payload(wire: str | None) -> str | None:
    """The PAYLOAD format a wire spelling puts on the rails. 'int8-mxu'
    ships byte-identical rails to 'int8' — the difference is entirely on
    the consumer side (epilogue-folded dequant instead of a dequant
    pass) — so ops with no MXU consumer (standalone AG/RS rings, the
    DCN rail legs, which dequantize before any compute) carry it as a
    plain int8 wire."""
    return "int8" if wire == "int8-mxu" else wire


@dataclass(frozen=True)
class WireFormat:
    """Static geometry of one quantized ring wire.

    ``quant``: 'fp8' | 'int8'; ``chunk_rows``: rows per f32 scale
    (must divide the slab rows it is used with).
    """

    quant: str
    chunk_rows: int

    @property
    def wire_dtype(self):
        return jnp.dtype(_WDT[self.quant])

    @property
    def qmax(self) -> float:
        return _QMAX[self.quant]

    def chunks(self, rows: int) -> int:
        assert rows % self.chunk_rows == 0, (rows, self.chunk_rows)
        return rows // self.chunk_rows

    def scale_shape(self, rows: int) -> tuple:
        return (self.chunks(rows), SCALE_LANES)

    def slab_bytes(self, rows: int, cols: int) -> int:
        """Wire bytes of one (rows, cols) slab: payload + scale plane."""
        return rows * cols * self.wire_dtype.itemsize \
            + self.chunks(rows) * SCALE_LANES * 4


def pick_chunk_rows(rows: int, strict: bool, target: int = 64) -> int | None:
    """Scale-chunk granularity for a slab of ``rows`` rows: the largest
    divisor ≤ ``target`` that keeps an interior (chunk_rows, bn) wire
    block Mosaic-lowerable (int8 sublane granule 32), or the whole slab
    as a single chunk. None only for pathological strict shapes."""
    from triton_distributed_tpu.kernels.ag_gemm import _divisor_block

    return _divisor_block(rows, min(target, rows), 32, strict)


def make_wire_format(quant: str, rows: int, *, strict: bool = False,
                     chunk_rows: int | None = None) -> WireFormat | None:
    """WireFormat for a slab of ``rows`` rows, or None when no legal
    chunking exists (callers then stay on the bf16 wire)."""
    cr = chunk_rows or pick_chunk_rows(rows, strict)
    if cr is None or rows % cr:
        return None
    return WireFormat(quant=wire_payload(quant), chunk_rows=cr)


# ------------------------------------------------------- XLA-side helpers

def quantize_slab(x, fmt: WireFormat):
    """(rows, cols) → (wire-dtype payload, (chunks, 128) f32 scales).

    Symmetric per-chunk quantization (scale = chunk amax / QMAX) — the
    per-token scales of the MoE wire (moe_all_to_all.quantize_rows),
    coarsened to ring-chunk granularity."""
    rows, cols = x.shape
    ch = fmt.chunks(rows)
    xf = x.astype(jnp.float32).reshape(ch, fmt.chunk_rows * cols)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / fmt.qmax
    q = xf / scale[:, None]
    if fmt.quant == "int8":
        q = jnp.clip(jnp.round(q), -127, 127)
    q = q.reshape(rows, cols).astype(fmt.wire_dtype)
    scales = jnp.broadcast_to(scale[:, None], (ch, SCALE_LANES))
    return q, scales.astype(jnp.float32)


def dequantize_slab(q, scales, fmt: WireFormat, out_dtype):
    """Inverse of :func:`quantize_slab` back to ``out_dtype``."""
    rows, cols = q.shape
    ch = fmt.chunks(rows)
    y = q.astype(jnp.float32).reshape(ch, fmt.chunk_rows * cols)
    y = y * scales[:, :1]
    return y.reshape(rows, cols).astype(out_dtype)


def quantize_slab_sr(x, fmt: WireFormat, key):
    """:func:`quantize_slab` with SEEDED STOCHASTIC ROUNDING — the
    gradient-ring quantizer (``train.grad_wire`` and the quantized
    backward duals of ``ops.overlap``).

    Same scale convention as the deterministic twin (symmetric
    per-chunk, scale = amax / QMAX clamped at 1e-12), but the int8 grid
    rounds ``floor(y + u)`` with ``u ~ U[0, 1)`` drawn from ``key`` —
    unbiased per element (``E[q·s] = x``), so the ring's reduction
    error averages out instead of accumulating a systematic
    round-to-nearest bias across hops. The fp8 grid is non-uniform (no
    uniform-offset SR exists for it), so fp8 keeps round-to-nearest and
    the grad ring's error feedback carries the bias instead.

    Deterministic under a fixed ``key``: same seed, same bits — the
    trainer derives keys from ``config.interp_key()``-stable seeds so a
    replayed step requantizes identically."""
    import jax

    rows, cols = x.shape
    ch = fmt.chunks(rows)
    xf = x.astype(jnp.float32).reshape(ch, fmt.chunk_rows * cols)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / fmt.qmax
    q = xf / scale[:, None]
    if fmt.quant == "int8":
        u = jax.random.uniform(key, q.shape, dtype=jnp.float32)
        q = jnp.clip(jnp.floor(q + u), -127, 127)
    q = q.reshape(rows, cols).astype(fmt.wire_dtype)
    scales = jnp.broadcast_to(scale[:, None], (ch, SCALE_LANES))
    return q, scales.astype(jnp.float32)


# -------------------------------------------------- in-kernel pipelines
#
# HBM-streaming twins of the helpers above, for the fused engines whose
# slabs never fit VMEM whole. Blocks stream through VMEM double-buffered
# (the ew_add_pipeline idiom); the scale plane rides as (1, 128) blocks.

def _wire_cols_block(cols: int, itemsize: int) -> int | None:
    """Column block of the dequant pipelines. Pinned to the scale
    plane's lane width: the inner multiply is then a (chunk_rows, 128)
    payload block against the (1, 128) scale block — a plain sublane
    broadcast, the flash-decode scale-fold idiom. A scalar extraction
    (``s_ref[0, 0]``) instead lowers to a ``vector.shape_cast 1x1 →
    scalar`` this Mosaic rejects (caught by the AOT suite)."""
    from triton_distributed_tpu.config import compiling_for_tpu
    from triton_distributed_tpu.kernels.ag_gemm import _divisor_block

    del itemsize
    if cols % SCALE_LANES == 0:
        return SCALE_LANES
    return _divisor_block(cols, SCALE_LANES, 128, compiling_for_tpu())


def quant_pipeline(rows: int, cols: int, fmt: WireFormat):
    """Streaming quantizer over HBM refs: callable(src, q, s).

    Two passes (both tiled emit_pipelines): the scale pass reduces each
    (chunk_rows, cols) chunk to its lane-replicated (1, 128) scale row
    via keepdims reductions + a lane broadcast — never materializing a
    scalar, because Mosaic rejects the ``vector<1x1> → scalar``
    shape_cast that ``jnp.max(x)`` / ``s_ref[0, 0]`` would emit (AOT
    suite finding) — and the quantize pass divides (chunk_rows, 128)
    payload blocks by the (1, 128) scale row (sublane broadcast, the
    flash-decode scale-fold idiom). Costs one extra read of the source
    slab; the wire, not HBM, is the bottleneck where this engages."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    ch = fmt.chunks(rows)
    qmax = fmt.qmax
    bn = _wire_cols_block(cols, 1)

    def scale_inner(src_ref, s_ref):
        x = jnp.abs(src_ref[...].astype(jnp.float32))
        row = jnp.max(x, axis=1, keepdims=True)         # (cr, 1)  lanes
        chunk = jnp.max(row, axis=0, keepdims=True)     # (1, 1) sublanes
        s_ref[...] = jnp.broadcast_to(
            jnp.maximum(chunk, 1e-12) / qmax, (1, SCALE_LANES)
        ).astype(jnp.float32)

    def quant_inner(src_ref, s_ref, q_ref):
        y = src_ref[...].astype(jnp.float32) / s_ref[:, :bn]
        if fmt.quant == "int8":
            y = jnp.clip(jnp.round(y), -127, 127)
        q_ref[...] = y.astype(q_ref.dtype)

    scale_pipe = pltpu.emit_pipeline(
        scale_inner,
        grid=(ch,),
        in_specs=[pl.BlockSpec((fmt.chunk_rows, cols), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, SCALE_LANES), lambda i: (i, 0))],
    )
    quant_pipe = pltpu.emit_pipeline(
        quant_inner,
        grid=(ch, cols // bn),
        in_specs=[
            pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, SCALE_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j))],
    )

    def run(src_hbm, q_hbm, s_hbm):
        rec = _lint_recorder()
        if rec is not None:
            from triton_distributed_tpu.analysis import events as ev

            rec.emit(ev.QuantEvent(
                src_region=src_hbm.region(), q_region=q_hbm.region(),
                s_region=s_hbm.region(), chunk_rows=fmt.chunk_rows,
            ))
            return
        scale_pipe(src_hbm, s_hbm)
        quant_pipe(src_hbm, s_hbm, q_hbm)

    return run


def dequant_pipeline(rows: int, cols: int, fmt: WireFormat):
    """Streaming dequantizer over HBM refs: (q, scales) → dst."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    ch = fmt.chunks(rows)
    bn = _wire_cols_block(cols, fmt.wire_dtype.itemsize)

    def inner(q_ref, s_ref, o_ref):
        # (cr, bn) · (1, bn) — sublane broadcast (the scale is lane-
        # replicated across the plane, so any bn ≤ 128 window is valid)
        o_ref[...] = (
            q_ref[...].astype(jnp.float32) * s_ref[:, :bn]
        ).astype(o_ref.dtype)

    pipe = pltpu.emit_pipeline(
        inner,
        grid=(ch, cols // bn),
        in_specs=[
            pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, SCALE_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j))],
    )

    def run(q_hbm, s_hbm, dst_hbm):
        rec = _lint_recorder()
        if rec is not None:
            from triton_distributed_tpu.analysis import events as ev

            rec.emit(ev.DequantEvent(
                q_region=q_hbm.region(), s_region=s_hbm.region(),
                dst_region=dst_hbm.region(),
            ))
            return
        pipe(q_hbm, s_hbm, dst_hbm)

    return run


def dequant_add_pipeline(rows: int, cols: int, fmt: WireFormat):
    """Streaming fused dequant-accumulate: dst = a + dequant(q, s).

    The RS-ring fold with a quantized wire: the add runs in f32 (the
    dequantized operand never round-trips through the wire dtype), so
    per-hop error is one rounding, not a compounding cast chain."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    ch = fmt.chunks(rows)
    bn = _wire_cols_block(cols, fmt.wire_dtype.itemsize)

    def inner(a_ref, q_ref, s_ref, o_ref):
        o_ref[...] = (
            a_ref[...].astype(jnp.float32)
            + q_ref[...].astype(jnp.float32) * s_ref[:, :bn]
        ).astype(o_ref.dtype)

    spec = pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j))
    pipe = pltpu.emit_pipeline(
        inner,
        grid=(ch, cols // bn),
        in_specs=[
            spec,
            pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, SCALE_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j))],
    )

    def run(a_hbm, q_hbm, s_hbm, dst_hbm):
        rec = _lint_recorder()
        if rec is not None:
            from triton_distributed_tpu.analysis import events as ev

            rec.emit(ev.DequantEvent(
                q_region=q_hbm.region(), s_region=s_hbm.region(),
                dst_region=dst_hbm.region(), add_region=a_hbm.region(),
            ))
            return
        pipe(a_hbm, q_hbm, s_hbm, dst_hbm)

    return run


def dequant_add_requant_pipeline(rows: int, cols: int, fmt: WireFormat):
    """Fused RS-ring fold + wire requantize:
    ``dst = a + dequant(q, s)`` AND ``(wq, ws) = quant(dst)`` with the
    wire scale taken off the fold accumulator — the reduce ring's next
    hop must ship the ACCUMULATED partial, so a producer-quantized wire
    (gemm_rs int8-MXU) re-quantizes here, in the fold pass itself,
    instead of a separate ``quant_pipeline`` read-back over HBM (the
    fold writes dst + the scale row in one pass; only the payload
    quantize re-reads dst — one slab read saved per hop)."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    ch = fmt.chunks(rows)
    qmax = fmt.qmax
    bn = _wire_cols_block(cols, fmt.wire_dtype.itemsize)

    def fold_inner(a_ref, q_ref, s_ref, o_ref, ws_ref):
        # (1, 1) scale window → sublane+lane broadcast over the full
        # chunk (the scale row is lane-replicated; mm_q8_rs_pipeline's
        # ``as_ref[:, :1]`` idiom)
        t = (a_ref[...].astype(jnp.float32)
             + q_ref[...].astype(jnp.float32) * s_ref[:, :1])
        o_ref[...] = t.astype(o_ref.dtype)
        row = jnp.max(jnp.abs(t), axis=1, keepdims=True)
        chunk = jnp.max(row, axis=0, keepdims=True)
        ws_ref[...] = jnp.broadcast_to(
            jnp.maximum(chunk, 1e-12) / qmax, (1, SCALE_LANES)
        ).astype(jnp.float32)

    fold_pipe = pltpu.emit_pipeline(
        fold_inner,
        grid=(ch,),
        in_specs=[
            pl.BlockSpec((fmt.chunk_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((fmt.chunk_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, SCALE_LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((fmt.chunk_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec((1, SCALE_LANES), lambda i: (i, 0)),
        ],
    )

    def quant_inner(src_ref, s_ref, q_ref):
        y = src_ref[...].astype(jnp.float32) / s_ref[:, :bn]
        if fmt.quant == "int8":
            y = jnp.clip(jnp.round(y), -127, 127)
        q_ref[...] = y.astype(q_ref.dtype)

    quant_pipe = pltpu.emit_pipeline(
        quant_inner,
        grid=(ch, cols // bn),
        in_specs=[
            pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, SCALE_LANES), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((fmt.chunk_rows, bn), lambda i, j: (i, j))],
    )

    def run(a_hbm, q_hbm, s_hbm, dst_hbm, wq_hbm, ws_hbm):
        rec = _lint_recorder()
        if rec is not None:
            from triton_distributed_tpu.analysis import events as ev

            rec.emit(ev.DequantEvent(
                q_region=q_hbm.region(), s_region=s_hbm.region(),
                dst_region=dst_hbm.region(), add_region=a_hbm.region(),
            ))
            rec.emit(ev.QuantEvent(
                src_region=dst_hbm.region(), q_region=wq_hbm.region(),
                s_region=ws_hbm.region(), chunk_rows=fmt.chunk_rows,
            ))
            return
        fold_pipe(a_hbm, q_hbm, s_hbm, dst_hbm, ws_hbm)
        quant_pipe(dst_hbm, ws_hbm, wq_hbm)

    return run


# ------------------------------------------------- VMEM-resident helpers
#
# The standalone ring kernels (allgather._ring_ag_kernel_w,
# reduce_scatter._ring_rs_kernel_w) keep whole slabs VMEM-resident and
# (de)quantize with direct ref arithmetic rather than streamed
# pipelines. Routing that arithmetic through these helpers keeps ONE
# implementation of the per-row wire math and gives shmemlint the same
# Quant/Dequant provenance events the pipelines emit.

def quant_rows_into(q_ref, s_ref, src_ref, quant: str):
    """Per-row symmetric quantization (chunk_rows=1) of a VMEM slab:
    ``q = src / scale``, ``s`` the lane-replicated f32 scale plane."""
    rec = _lint_recorder()
    if rec is not None:
        from triton_distributed_tpu.analysis import events as ev

        rec.emit(ev.QuantEvent(
            src_region=src_ref.region(), q_region=q_ref.region(),
            s_region=s_ref.region(), chunk_rows=1,
        ))
        return
    af = src_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(af), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / _QMAX[quant]
    q = af / scale
    if quant == "int8":
        q = jnp.clip(jnp.round(q), -127, 127)
    q_ref[...] = q.astype(q_ref.dtype)
    s_ref[...] = jnp.broadcast_to(
        scale, (af.shape[0], SCALE_LANES)
    ).astype(jnp.float32)


def dequant_rows_into(dst_ref, q_ref, s_ref):
    """Per-row dequant of a VMEM slab: ``dst = q · s[:, :1]`` (the
    scale is lane-replicated, column 0 suffices)."""
    from jax.experimental import pallas as pl

    rec = _lint_recorder()
    if rec is not None:
        from triton_distributed_tpu.analysis import events as ev

        rec.emit(ev.DequantEvent(
            q_region=q_ref.region(), s_region=s_ref.region(),
            dst_region=dst_ref.region(),
        ))
        return
    sc = s_ref[:, pl.ds(0, 1)]
    dst_ref[...] = (
        q_ref[...].astype(jnp.float32) * sc
    ).astype(dst_ref.dtype)


def dequant_add_rows_into(dst_ref, q_ref, s_ref, add_ref):
    """Fused per-row dequant-accumulate: ``dst = add + q · s[:, :1]``
    in f32 (the RS-ring fold — one rounding per hop)."""
    from jax.experimental import pallas as pl

    rec = _lint_recorder()
    if rec is not None:
        from triton_distributed_tpu.analysis import events as ev

        rec.emit(ev.DequantEvent(
            q_region=q_ref.region(), s_region=s_ref.region(),
            dst_region=dst_ref.region(), add_region=add_ref.region(),
        ))
        return
    sc = s_ref[:, pl.ds(0, 1)]
    dst_ref[...] = (
        q_ref[...].astype(jnp.float32) * sc
        + add_ref[...].astype(jnp.float32)
    ).astype(dst_ref.dtype)


def inkernel_wire_ok(quant: str) -> bool:
    """Can a PALLAS ring dequantize/quantize this wire dtype in-kernel
    on the current toolchain?

    The 2024-12 Mosaic backend rejects fp8 float extensions ("Only
    16-bit to 32-bit extensions supported": ``arith.extf f8E4M3FN →
    f32`` — caught by tests/test_aot_topology.py), while int8 ↔ f32
    widening/narrowing lowers fine (the int8-KV decode kernels run on
    chip, round 5). So in-kernel wires are int8-only when compiling
    for real Mosaic; fp8 stays available on the XLA engines (XLA
    handles f8 natively) and under the interpreter. Set
    ``TDTPU_WIRE_FP8_INKERNEL=1`` on a newer toolchain whose Mosaic
    gained the f8 casts."""
    import os

    from triton_distributed_tpu.config import compiling_for_tpu

    if quant != "fp8":
        return True
    if os.environ.get("TDTPU_WIRE_FP8_INKERNEL") == "1":
        return True
    return not compiling_for_tpu()


def require_inkernel(quant: str, engine: str) -> None:
    """Raise the canonical diagnostic when an EXPLICIT wire format needs
    in-kernel casts the current Mosaic lacks (pinned = contract)."""
    if not inkernel_wire_ok(quant):
        raise ValueError(
            f"{engine}: wire_dtype='fp8' requires in-kernel f8 casts this "
            "Mosaic backend lacks ('Only 16-bit to 32-bit extensions "
            "supported'); use wire_dtype='int8', an XLA engine (which "
            "carries fp8 natively), or TDTPU_WIRE_FP8_INKERNEL=1 on a "
            "newer toolchain"
        )


def inkernel_s8_dot_ok() -> bool:
    """Can a PALLAS kernel on the current toolchain feed int8 operands
    straight into the MXU (``dot_general`` s8×s8 → s32)?

    This Mosaic backend lowers the native s8×s8→s32 path fine — the
    W8A8 grouped GEMM (kernels/group_gemm._ggemm_q8a_kernel) runs it on
    chip at ~2× the bf16 rate (round 5, docs/PERF.md) — so the default
    is True. ``TDTPU_WIRE_INT8_MXU=0`` force-disables the epilogue-
    dequant consumers on a toolchain whose Mosaic regresses (the
    mosaic_compat pre-flight's MC004 scan then also catches the
    rejected accumulator form at build time)."""
    import os

    return os.environ.get("TDTPU_WIRE_INT8_MXU") != "0"


def require_mxu(engine: str) -> None:
    """Raise the canonical clean-refusal diagnostic when an EXPLICIT
    'int8-mxu' wire is pinned but in-kernel s8 MXU consumption is
    disabled for this toolchain (pinned = contract; the mosaic_compat
    pre-flight treats this refusal as a pass, mirroring the fp8
    handling)."""
    if not inkernel_s8_dot_ok():
        raise ValueError(
            f"{engine}: wire_dtype='int8-mxu' requires in-kernel s8 "
            "MXU dots, disabled for this toolchain "
            "(TDTPU_WIRE_INT8_MXU=0); use wire_dtype='int8' "
            "(dequant-then-matmul) or the bf16 wire"
        )


def quantize_cols(b):
    """(K, N) matmul weight → ((K, N) int8, (1, N) f32 scales):
    symmetric per-out-channel weight quantization for the int8-MXU
    consumers (the stationary-operand half of the s8×s8 product; the
    moving half is the per-chunk wire quantization). Same convention as
    ``kernels.group_gemm.quantize_grouped_weights`` with E=1, kept 2-D
    so the (1, bn) scale block is a legal Mosaic operand."""
    bf = b.astype(jnp.float32)
    amax = jnp.max(jnp.abs(bf), axis=0, keepdims=True)        # (1, N)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(bf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def epilogue_consume(q_hbm, s_hbm, out_hbm):
    """Record (under an active shmemlint recorder) that a quantized
    payload slab is consumed by an MXU pipeline whose ACCUMULATOR
    EPILOGUE folds the paired scale plane — the provenance edge that
    lets the dataflow pass treat the slab as dequantized-on-consume
    (SL008) while still checking the scale pairing (SL009/SL010).
    Returns True when an event was emitted (the caller then skips its
    value-level pipeline). ``s_hbm=None`` records a consume WITHOUT the
    scale fold — the scale-fold-omitted bug SL009 pins."""
    rec = _lint_recorder()
    if rec is None:
        return False
    from triton_distributed_tpu.analysis import events as ev

    rec.emit(ev.DequantEvent(
        q_region=q_hbm.region(),
        s_region=None if s_hbm is None else s_hbm.region(),
        dst_region=None if out_hbm is None else out_hbm.region(),
        epilogue=True,
    ))
    return True


def wire_blockable(rows: int, cols: int, quant: str, strict: bool) -> bool:
    """Can a (rows, cols) slab carry this wire format at all? (legal
    chunking + lowerable column blocks + the scale overhead actually
    saves bytes — tiny-cols slabs where the 512 B/chunk plane eats the
    compression are rejected rather than silently shipped larger)."""
    fmt = make_wire_format(wire_payload(quant), rows, strict=strict)
    if fmt is None or _wire_cols_block(cols, 1) is None:
        return False
    return fmt.slab_bytes(rows, cols) < rows * cols * 2  # vs bf16 wire
