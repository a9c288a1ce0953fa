"""Grouped (per-expert) GEMM over block-aligned sorted tokens.

Reference: the consumer grouped-GEMM kernels
``kernel_consumer_m_parallel_scatter_group_gemm`` (python/triton_dist/
kernels/nvidia/allgather_group_gemm.py:420-498) and the producer grouped
GEMM of moe_reduce_rs.py:362-467 — tiles walk the block-aligned sorted
token list, each M-block owned by exactly one expert whose weight matrix
it multiplies.

TPU re-design: the expert-id-per-block indirection becomes a Mosaic
scalar-prefetch index map — ``block_expert`` rides in SMEM and the
weight BlockSpec selects expert ``be[m]``'s (K, N) matrix per M-block
(the canonical TPU grouped-matmul / Megablocks schedule). MXU does the
FLOPs in bf16 with f32 accumulation in VMEM scratch. The XLA twin is
``jax.lax.ragged_dot`` over the same layout (group_sizes = padded
per-expert counts), used as the correctness baseline and as the
fallback where a shape falls off the kernel's alignment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.config import local_interpret


def _mac_if_live(be_ref, dummy_expert, mac):
    """Run ``mac`` unless this M-block is a dummy one: a dummy block
    multiplies nothing, and the zeros of its accumulator are stored as
    they are (the scales of the quantized epilogues are finite)."""
    if dummy_expert is None:
        mac()
    else:
        pl.when(be_ref[pl.program_id(0)] < dummy_expert)(mac)


def _ggemm_kernel(nsteps_k, be_ref, x_ref, w_ref, o_ref, acc_ref,
                  dummy_expert=None):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _mac():
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _mac_if_live(be_ref, dummy_expert, _mac)

    @pl.when(kk == nsteps_k - 1)
    def _store():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _ggemm_q_kernel(nsteps_k, xdt, be_ref, x_ref, w_ref, s_ref, o_ref,
                    acc_ref, dummy_expert=None):
    """Weight-only-quantized variant: W rides HBM in its 1-byte wire
    dtype (int8 / fp8) and is widened tile-by-tile in VMEM; the
    per-(expert, out-channel) scale multiplies the f32 accumulator once
    at the final K step (dequantization is linear over the K reduction,
    so folding it into the epilogue is exact)."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _mac():
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[0].astype(xdt),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _mac_if_live(be_ref, dummy_expert, _mac)

    @pl.when(kk == nsteps_k - 1)
    def _store():
        o_ref[:] = (acc_ref[:] * s_ref[0, 0][None, :]).astype(o_ref.dtype)


def _ggemm_q8a_kernel(nsteps_k, be_ref, x_ref, w_ref, xs_ref, ws_ref,
                      o_ref, acc_ref, dummy_expert=None):
    """W8A8 variant: BOTH operands ride int8 and the MXU runs its
    native s8×s8→s32 path (measured 320–350 TOP/s on a v5e — 2× the
    bf16 rate), with the rank-1 scale correction
    ``x_scale[m] · w_scale[e, n]`` applied to the s32 accumulator at
    the last K step (exact: both scales are constant over the K
    reduction)."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _mac():
        acc_ref[:] += jax.lax.dot_general(
            x_ref[:], w_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    _mac_if_live(be_ref, dummy_expert, _mac)

    @pl.when(kk == nsteps_k - 1)
    def _store():
        o_ref[:] = (
            acc_ref[:].astype(jnp.float32)
            * xs_ref[:]                        # (block_m, 1)
            * ws_ref[0, 0][None, :]            # (block_n,)
        ).astype(o_ref.dtype)


def quantize_act_rows(x):
    """Per-row symmetric int8 activation quantization: (M, K) →
    ((M, K) int8, (M, 1) f32 scales). The activation-side half of the
    W8A8 decode path (weights come from :func:`quantize_grouped_weights`)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s), -127.0, 127.0).astype(jnp.int8)
    return q, s.astype(jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "vmem_limit_bytes",
                     "interpret", "out_dtype", "dummy_expert"),
)
def grouped_matmul(
    x_sorted, w, block_expert, *,
    w_scale=None, x_scale=None,
    block_m: int = 512, block_n: int = 2048, block_k: int = 512,
    vmem_limit_bytes: int | None = None,
    interpret=None,
    out_dtype=None,
    dummy_expert: int | None = None,
):
    """x_sorted (cap, K) @ w (E, K, N) → (cap, N), expert per M-block.

    ``cap`` must be a multiple of ``block_m`` and ``block_expert`` have
    ``cap // block_m`` entries (from moe_utils.moe_align_block_size).
    Defaults swept on a real v5e (8 experts, 1024 rows/expert,
    4096×2048 bf16): (512, 2048, 512) → 168 TFLOP/s (MFU 0.85) vs 121
    for the old (256, 512, 512). Smaller block_m trades MXU efficiency
    for less routing padding — contexts keep their own defaults.

    WEIGHT-RESIDENT mode (decode sizes): ``block_n``/``block_k`` ≥ the
    whole N/K dims (pass e.g. 1<<30; rounded down to the dims) keep an
    expert's ENTIRE weight matrix in VMEM — the W BlockSpec index
    (be[m], 0, 0) is unchanged across that expert's consecutive sorted
    M-blocks, so Mosaic's pipeline skips the re-fetch and weight
    traffic drops from #blocks× to #expert-runs× the matrix. That lets
    ``block_m`` shrink (less alignment padding → fewer padded-row
    FLOPs) without the weight re-streaming penalty that otherwise
    punishes small blocks — measured 1235 → 1130 µs on the serving
    decode pair at (64, whole, whole) vs (256, 2048, 512), docs/PERF.md.
    Whole-dim tiles exceed Mosaic's 16 MB default scoped VMEM — pass
    ``vmem_limit_bytes`` (the contexts use config.fused_vmem_budget()).

    WEIGHT-ONLY QUANTIZATION (serving decode, where weight HBM reads
    dominate): pass ``w`` in a 1-byte dtype (int8 / float8_e4m3fn) plus
    ``w_scale`` (E, N) f32 per-(expert, out-channel) scales (from
    :func:`quantize_grouped_weights`). The kernel widens W tiles in
    VMEM and folds the scale into the f32 accumulator at the last K
    step — HBM weight traffic halves vs bf16 while the MXU still runs
    the bf16 pipeline. Composes with the weight-resident schedule.

    ``out_dtype`` (default: x's dtype): the store casts the f32
    accumulator directly to this — pass f32 for logits-grade outputs
    (a post-hoc ``.astype`` after a bf16 store would re-widen
    already-rounded values).

    W8A8 (``x_scale`` given too, x int8 from :func:`quantize_act_rows`):
    the MXU runs its native s8×s8→s32 path at 2× the bf16 rate and the
    rank-1 ``x_scale[m]·w_scale[e, n]`` correction lands on the s32
    accumulator in the epilogue. Decode-size grouped GEMMs at bm=64
    are MXU-bound (the weight-resident schedule already minimized the
    HBM reads), so doubling the MXU rate is the remaining lever.
    ``out_dtype`` defaults to bf16 here (int8 out makes no sense).

    ``dummy_expert`` (all three kernels): M-blocks whose
    ``block_expert`` is ``>= dummy_expert`` hold no row of any expert
    (the trailing group of ``moe_align_block_size`` and its slack).
    They are stored as zeros without a multiply, whatever their rows
    hold, and every step of such a block names the SAME tile of the
    weight, the activations and both scales, so the pipeline fetches
    one tile for a whole run of them instead of streaming an expert's
    matrix per block. None: every block is multiplied (``block_expert``
    must name a real expert everywhere).
    """
    from triton_distributed_tpu.config import compiling_for_tpu
    from triton_distributed_tpu.kernels.ag_gemm import _divisor_block

    cap, kdim = x_sorted.shape
    e, _, ndim = w.shape
    assert cap % block_m == 0, f"cap={cap} not divisible by block_m={block_m}"
    # round the requested blocks DOWN to divisors (TPU-aligned when
    # possible): the sweep-tuned defaults must not assert on shapes like
    # N=3584 that 512 divides but 2048 does not
    block_n = _divisor_block(ndim, min(block_n, ndim), 128, compiling_for_tpu()) or ndim
    block_k = _divisor_block(kdim, min(block_k, kdim), 128, compiling_for_tpu()) or kdim
    nsteps_k = kdim // block_k

    if dummy_expert is None:
        kernel_kw = {}

        def owner(m, be):
            return be[m]

        def tile(m, be, *idx):
            return idx
    else:
        kernel_kw = {"dummy_expert": int(dummy_expert)}

        def owner(m, be):
            return jnp.minimum(be[m], e - 1)

        def tile(m, be, *idx):
            # every step of a dummy block names tile 0 of each operand:
            # a run of them fetches it once
            live = (be[m] < dummy_expert).astype(jnp.int32)
            return tuple(i * live for i in idx)

    in_specs = [
        pl.BlockSpec((block_m, block_k),
                     lambda m, n, k, be: tile(m, be, m, k)),
        pl.BlockSpec(
            (1, block_k, block_n),
            lambda m, n, k, be: (owner(m, be), *tile(m, be, k, n)),
        ),
    ]
    acc_dtype = jnp.float32
    if w_scale is None:
        assert x_scale is None, "x_scale requires w_scale (W8A8 mode)"
        kernel = functools.partial(_ggemm_kernel, nsteps_k, **kernel_kw)
        args = (block_expert, x_sorted, w)
    else:
        assert w.dtype.itemsize == 1, (
            f"w_scale given but w dtype {w.dtype} is not a 1-byte wire "
            "dtype (int8 / float8_e4m3fn)"
        )
        assert w_scale.shape == (e, ndim), (w_scale.shape, (e, ndim))
        # (E, 1, N): the unit sublane dim equals the array dim, which
        # Mosaic accepts where a (1, block_n) slice of (E, N) is rejected
        ws3 = w_scale.astype(jnp.float32)[:, None, :]
        ws_spec = pl.BlockSpec(
            (1, 1, block_n),
            lambda m, n, k, be: (owner(m, be), 0, *tile(m, be, n)),
        )
        if x_scale is None:
            in_specs.append(ws_spec)
            kernel = functools.partial(
                _ggemm_q_kernel, nsteps_k, x_sorted.dtype, **kernel_kw
            )
            args = (block_expert, x_sorted, w, ws3)
        else:
            assert x_sorted.dtype == jnp.int8, (
                f"W8A8 needs int8 activations, got {x_sorted.dtype}"
            )
            assert x_scale.shape == (cap, 1), (x_scale.shape, (cap, 1))
            in_specs.append(
                pl.BlockSpec((block_m, 1),
                             lambda m, n, k, be: (*tile(m, be, m), 0))
            )
            in_specs.append(ws_spec)
            kernel = functools.partial(
                _ggemm_q8a_kernel, nsteps_k, **kernel_kw)
            args = (
                block_expert, x_sorted, w,
                x_scale.astype(jnp.float32), ws3,
            )
            acc_dtype = jnp.int32
            if out_dtype is None:
                out_dtype = jnp.bfloat16
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cap // block_m, ndim // block_n, nsteps_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k, be: (m, n)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc_dtype)],
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (cap, ndim), out_dtype or x_sorted.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes
        ),
        interpret=local_interpret() if interpret is None else interpret,
    )
    return call(*args)


def grouped_matmul_xla(x_sorted, w, splits_padded):
    """``jax.lax.ragged_dot`` twin: group sizes are the block-aligned
    per-expert counts (they sum to cap; padding rows are zero)."""
    return jax.lax.ragged_dot(
        x_sorted, w, splits_padded.astype(jnp.int32)
    ).astype(x_sorted.dtype)


def quantize_grouped_weights(w, mode: str = "int8"):
    """(E, K, N) weights → ((E, K, N) wire-dtype, (E, N) f32 scales).

    Symmetric per-(expert, out-channel) weight-only quantization for the
    serving decode path (the grouped GEMM there is weight-HBM-bound, so
    1-byte weights halve its floor). Same scale convention as the token
    wire quant (kernels/moe_all_to_all.quantize_rows — ≡ the reference's
    WITH_SCALE fp8 transport, low_latency_all_to_all.py:82-90), applied
    to the stationary operand instead of the moving one.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=1)        # (E, N)
    if mode == "int8":
        scale = jnp.maximum(amax, 1e-30) / 127.0
        q = jnp.round(w.astype(jnp.float32) / scale[:, None, :])
        return jnp.clip(q, -127, 127).astype(jnp.int8), scale
    if mode == "fp8":
        scale = jnp.maximum(amax, 1e-30) / 448.0                  # e4m3 max
        return (
            (w.astype(jnp.float32) / scale[:, None, :]).astype(
                jnp.float8_e4m3fn
            ),
            scale,
        )
    raise ValueError(f"weight quant mode must be int8|fp8, got {mode!r}")


def resident_weight_itemsize(mode: str | None, dtype) -> int:
    """VMEM bytes/elem a weight-resident ``grouped_matmul`` schedule
    must budget per weight element — the kernel-lowering cost model the
    model layer's residency gate consumes (kept HERE so it tracks this
    kernel). int8 tiles are consumed at wire width; fp8 has no native
    v5e MXU form, so Mosaic materializes the widened copy (budget wire
    + f32 temp — measured: whole-dim fp8 tiles blow scoped VMEM where
    int8 fits, docs/PERF.md); None = the unquantized compute dtype."""
    if mode == "int8":
        return 1
    if mode == "fp8":
        return 5
    assert mode is None, f"unknown weight-quant mode {mode!r}"
    return jnp.dtype(dtype).itemsize


def dequantize_grouped_weights(q, scale, dtype=jnp.bfloat16):
    """Widen (E, K, N) wire-dtype weights back with their (E, N) scales
    — the XLA-twin path (ragged_dot has no quantized form) and the
    correctness reference for the in-kernel epilogue dequant."""
    return (q.astype(jnp.float32) * scale[:, None, :]).astype(dtype)


def padded_splits(splits, block_m: int, cap: int):
    """Block-aligned per-expert counts with the tail slack folded into the
    last group so the sizes sum to ``cap`` (ragged_dot requires it)."""
    from triton_distributed_tpu.kernels.moe_utils import round_up_to_block

    padded = round_up_to_block(splits, block_m)
    slack = cap - jnp.sum(padded)
    return padded.at[-1].add(slack)
