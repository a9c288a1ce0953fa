"""Distributed GQA flash-decode: split-KV attention + LSE-combine (SP/CP).

Reference: python/triton_dist/kernels/nvidia/flash_decode.py —
``kernel_gqa_fwd_batch_decode_split_kv`` (:130-280, online-softmax partial
attention over KV splits), intra-rank combine (:393-451), inter-rank
combine merging per-rank (out, lse) partials (:482-566), host entries
``gqa_fwd_batch_decode{,_intra_rank}`` (:763-930); the SP layer
sp_flash_decode_layer.py:78-184 shards the KV cache over ranks.

TPU re-design:

* The reference splits KV across SMs and re-combines to fill the GPU.
  On TPU one core runs the grid sequentially with VMEM-resident
  accumulators, so "split-KV + intra-rank combine" collapses into a
  single Pallas kernel whose innermost grid dimension walks KV blocks,
  carrying (m, l, acc) online-softmax state in scratch — the classic
  TPU flash-attention schedule. No intra-rank combine kernel is needed;
  the hardware pipeline plays the role of the split scheduler.
* What remains distributed is exactly the reference's inter-rank stage:
  each rank decodes over its local KV shard producing (out, lse), the
  partials are all-gathered (small payload — the LL-allgather regime),
  and a combine re-normalizes with the global LSE. Numerically this is
  the ring-attention / blockwise-softmax merge, done once over ranks
  (≡ kernel_inter_rank_gqa_fwd_batch_decode_combine_kv).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.config import interp_key, local_interpret
from triton_distributed_tpu.lang.launch import shmem_call
from triton_distributed_tpu.utils.testing import chaos_delay

NEG_INF = -1.0e30  # finite -inf stand-in: exp(NEG_INF - m) == 0 without NaNs


def _n_valid_blocks(kv_len, block_k):
    """ceil(kv_len / block_k), floored at 1 — even an empty row walks one
    block (its scores are fully masked; lse comes back NEG_INF)."""
    return jnp.maximum(jax.lax.div(kv_len + block_k - 1, block_k), 1)


def _decode_kernel(
    scale, soft_cap, block_k, kv_lens_ref, q_ref, k_ref, v_ref,
    out_ref, lse_ref, m_ref, l_ref, acc_ref,
    ks_ref=None, vs_ref=None,
):
    """One (batch, kv_head) group; grid dim 2 walks KV blocks sequentially.

    q_ref: (1, 1, G, D) — the GQA query group of this kv head.
    k_ref/v_ref: (1, block_k, D) — current KV block of this head, read
    directly from the cache viewed as (B, S, Hkv·D) (a free reshape of the
    native layout — no transposed copy; the block DMA slices the head's
    D-column window).
    Carries (m, l, acc) in f32 scratch across the KV walk (the online
    softmax of the reference's split_kv kernel, :207-258).

    This STATIC grid walks the cache CAPACITY: blocks past
    ceil(kv_lens[b]/block_k) skip their COMPUTE (the ``pl.when``
    below) but their DMA still lands — Mosaic's pipeline fetches every
    BlockSpec window, and index-map clamping does not reliably elide
    the copies (measured). Length-proportional HBM traffic lives in
    :func:`_decode_kernel_dyn` (the native-layout default); this
    kernel serves the reference-style bshd view and unaligned
    geometries, where capacity-proportional reads are the price of the
    strided window.

    ``ks_ref``/``vs_ref``: optional (…, 1, block_k) f32 per-row scale
    blocks — int8 KV mode, with the same exact per-column scale folds
    as ``_decode_kernel_dyn``'s quant path.
    """
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]

    @pl.when(ki < _n_valid_blocks(kv_len, block_k))
    def _compute():
        q = q_ref[0, 0]                        # (G, D), input dtype
        # KV blocks arrive as (1, block_k, D) [bshd view] or (1, 1,
        # block_k, D) [bhsd]; flatten the unit block dims either way.
        k = k_ref[...].reshape(block_k, q.shape[-1])
        v = v_ref[...].reshape(block_k, q.shape[-1])
        if ks_ref is not None:
            # widen WITHOUT the scale; fold per-column below (exact)
            k = k.astype(jnp.bfloat16)
            v = v.astype(jnp.bfloat16)

        # Inputs stay in their native (bf16) dtype so the MXU runs at
        # full rate; accumulation is f32 via preferred_element_type.
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                              # (G, block_k) f32
        if ks_ref is not None:
            s = s * ks_ref[...].reshape(1, block_k)
        if soft_cap > 0.0:
            s = soft_cap * jnp.tanh(s / soft_cap)

        pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < kv_len
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:]                      # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # mask p explicitly: in an ALL-masked block m_new == NEG_INF and
        # exp(s − m_new) degenerates to 1, which would make an empty
        # row's output depend on how many blocks were walked — with the
        # mask, l stays 0 and _finish emits exact zeros + NEG_INF lse
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)   # (G, block_k)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        if vs_ref is not None:
            # fold V's per-row scale into p (rank-1 exactness)
            p = p * vs_ref[...].reshape(1, block_k)
        acc_ref[:] = alpha * acc_ref[:] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[:]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0, 0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.where(
            l > 0.0, m_ref[:] + jnp.log(safe_l), jnp.full_like(l, NEG_INF)
        )


def _decode_kernel_dyn(
    scale, soft_cap, block_k, n_bufs, g, d, quant, *refs,
):
    """Dynamic-trip-count decode: grid is (B, Hkv) ONLY; the KV walk is
    an in-kernel ``fori_loop`` over ceil(kv_lens[b]/block_k) blocks with
    manually double-buffered HBM→VMEM DMAs.

    Why not a (B, Hkv, S/block_k) grid with index-map clamping: a grid
    walks the cache CAPACITY — every invalid tail block still costs a
    grid step (measured 0.6–1.4 µs each at serving shapes), and Mosaic's
    revisit-skip does not reliably elide the clamped copies. A dynamic
    loop bound issues exactly ceil(len/block_k) DMAs and zero extra
    steps — HBM reads and overhead both scale with the TRUE lengths
    (≡ the reference kernel's dynamic ``for`` over kv chunks,
    flash_decode.py:207-216; same discipline as the count-bounded MoE
    chunk transport, moe_dispatch.py).

    k_hbm/v_hbm: full (B, Hkv, S, D) refs in ANY space — one (block_k,
    D) contiguous run is DMA'd per loop step into the rotating VMEM
    slots. The pipeline runs ACROSS grid steps: each iteration issues
    the NEXT block's copy — the last iteration of a (b, h) group
    prefetches the next group's block 0 — and ``slot_ref`` (persistent
    SMEM) carries the slot rotation over the group boundary, so the DMA
    engine never drains between groups (without this, a one-block group
    exposes its full copy latency every grid step: measured 2.4 ms vs
    1.5 ms for the whole walk at B=128, Hkv=8, S=2048).

    ``quant``: int8 KV mode — k_hbm/v_hbm are int8 with per-(b, h, s)
    f32 scale planes. The scales fold EXACTLY into the softmax
    (per-column into s before soft-capping, per-column into p before
    the PV dot), so the only extra VPU work is two int8→bf16 widens
    and two (G, block_k)-sized multiplies — the D-sized dequant
    multiply never happens. Halves the KV bytes in HBM and on the DMA
    stream (2× the context per chip). The scale planes arrive as
    PIPELINED (1, 1, 1, S) VMEM blocks — Mosaic's grid pipeline
    prefetches each (b, h) row's whole scale vector (8 KB at S=2048)
    — NOT as per-block manual DMAs: at serving batch sizes the walk is
    DMA-COUNT bound (thousands of 0.1-µs-class issues), and the two
    4 KB scale copies per block doubled the count for 3% of the bytes
    (measured: see docs/PERF.md round-5 serving attention section).
    """
    if quant:
        (kv_lens_ref, q_ref, k_hbm, v_hbm, ks_ref, vs_ref,
         out_ref, lse_ref,
         kbuf, vbuf, sem_k, sem_v, slot_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (kv_lens_ref, q_ref, k_hbm, v_hbm, out_ref, lse_ref,
         kbuf, vbuf, sem_k, sem_v, slot_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    nb_total = pl.num_programs(0)
    nh = pl.num_programs(1)
    kv_len = kv_lens_ref[b]
    # clamp at capacity: a caller whose lens overran the cache (e.g.
    # append_kv increments past a full cache) must not DMA past the end
    nb = jnp.minimum(
        _n_valid_blocks(kv_len, block_k),
        k_hbm.shape[2] // block_k,
    )
    q = q_ref[0, 0]                            # (G, D)

    def dma(bb, hh, j, slot):
        win = pl.ds(j * block_k, block_k)
        return [
            pltpu.make_async_copy(
                k_hbm.at[bb, hh, win], kbuf.at[slot], sem_k.at[slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[bb, hh, win], vbuf.at[slot], sem_v.at[slot]
            ),
        ]

    @pl.when(jnp.logical_and(b == 0, h == 0))
    def _warmup():                             # first block of the run
        slot_ref[0] = 0
        for cp in dma(0, 0, 0, 0):
            cp.start()

    s0 = slot_ref[0]                           # this group's start slot
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(j, _):
        slot = jax.lax.rem(s0 + j, n_bufs)
        nxt = jax.lax.rem(s0 + j + 1, n_bufs)

        # issue the NEXT block's copy BEFORE waiting on this one: the
        # engine queues it behind the in-flight copy and rolls straight
        # into it when that completes — i.e. during this block's
        # compute. Starting after the wait leaves the engine idle for
        # the whole compute phase (measured: per-iter time = DMA +
        # compute instead of max(DMA, compute)).
        @pl.when(j + 1 < nb)
        def _prefetch_in_group():
            for cp in dma(b, h, j + 1, nxt):
                cp.start()

        # group's last block: prefetch the NEXT group's first block so
        # the copy flies while out/lse spill and the grid advances
        @pl.when(
            jnp.logical_and(
                j + 1 == nb,
                jnp.logical_or(h + 1 < nh, b + 1 < nb_total),
            )
        )
        def _prefetch_next_group():
            nb_ = jnp.where(h + 1 < nh, b, b + 1)
            nh_ = jnp.where(h + 1 < nh, h + 1, 0)
            for cp in dma(nb_, nh_, 0, nxt):
                cp.start()

        for cp in dma(b, h, j, slot):
            cp.wait()

        win = pl.ds(j * block_k, block_k)
        if quant:
            # widen WITHOUT the scale (the D-sized multiply is the
            # expensive dequant path) — scales fold per-column below
            k = kbuf[slot].astype(jnp.bfloat16)    # (block_k, D)
            v = vbuf[slot].astype(jnp.bfloat16)
            v_scale = vs_ref[0, 0, :, win]         # (1, block_k)
        else:
            k = kbuf[slot]                         # (block_k, D)
            v = vbuf[slot]
            v_scale = None
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                              # (G, block_k)
        if quant:
            # exact: scale_s is constant along each k column of the dot
            s = s * ks_ref[0, 0, :, win]           # (1, block_k) broadcast
        if soft_cap > 0.0:
            s = soft_cap * jnp.tanh(s / soft_cap)

        def update(s, p_mask):
            m = m_ref[:]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)             # (G, block_k)
            if p_mask is not None:
                # an all-masked block degenerates exp(s − m) to 1
                p = jnp.where(p_mask, p, 0.0)
            l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
            if v_scale is not None:
                # fold V's per-row scale into p (row r of V scales the
                # whole rank-1 term p[:, r]·v[r]) — exact
                pv = (p * v_scale).astype(v.dtype)
            else:
                pv = p.astype(v.dtype)
            acc_ref[:] = alpha * acc_ref[:] + jnp.dot(
                pv, v, preferred_element_type=jnp.float32
            )
            m_ref[:] = m_new

        # interior blocks (every position valid) skip the mask chain —
        # the iota/compare/select passes over (G, block_k) f32 cost as
        # much VPU time as the whole softmax update (the kernel is
        # compute-bound at bf16 blocks); only the ragged tail pays them
        is_tail = jnp.logical_and(
            j + 1 == nb, (j + 1) * block_k > kv_len
        )

        @pl.when(is_tail)
        def _masked():
            pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            valid = pos < kv_len
            update(jnp.where(valid, s, NEG_INF), valid)

        @pl.when(jnp.logical_not(is_tail))
        def _plain():
            update(s, None)

        return 0

    jax.lax.fori_loop(0, nb, body, 0)
    slot_ref[0] = jax.lax.rem(s0 + nb, n_bufs)  # hand the rotation on
    l = l_ref[:]
    safe_l = jnp.where(l > 0.0, l, 1.0)
    out_ref[0, 0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)
    lse_ref[0, 0] = jnp.where(
        l > 0.0, m_ref[:] + jnp.log(safe_l), jnp.full_like(l, NEG_INF)
    )


def _decode_kernel_dyn_mh(
    scale, soft_cap, block_k, n_bufs, hkv, g, d, *refs,
):
    """MULTIHEAD dynamic-trip INT8 decode: grid (B,) — every KV head of
    a batch row in ONE grid step.

    Round-5 measurement (docs/PERF.md): at serving batch sizes the
    per-(b, h) grid of ``_decode_kernel_dyn`` pays ~0.55 µs of
    per-group overhead (grid step, out/lse spill, q/scale pipeline
    fetch, state re-init) × B·Hkv = 1024 groups — roughly half the
    kernel's time at B=128, while the same kernel at B=4 (32 groups)
    runs at 97% of HBM SOL. Folding the Hkv heads into one step cuts
    the group count 8×: the K/V copies become single strided DMAs
    (Hkv contiguous (block_k, D) runs each), the softmax state blocks
    up to (Hkv·G, ·), and the per-head compute unrolls statically.
    Trip counts are per-ROW (all heads share kv_lens[b]) — which is
    what makes the merge natural.

    Same quant semantics as ``_decode_kernel_dyn``: int8 K/V widened
    without scales, per-column scale folds into s and p, pipelined
    (1, Hkv, 1, S) scale blocks, SMEM slot-rotation carry with
    cross-row prefetch.
    """
    (kv_lens_ref, q_ref, k_hbm, v_hbm, ks_ref, vs_ref,
     out_ref, lse_ref,
     kbuf, vbuf, sem_k, sem_v, slot_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    nb_total = pl.num_programs(0)
    kv_len = kv_lens_ref[b]
    nb = jnp.minimum(
        _n_valid_blocks(kv_len, block_k),
        k_hbm.shape[2] // block_k,
    )

    def dma(bb, j, slot):
        win = pl.ds(j * block_k, block_k)
        return [
            pltpu.make_async_copy(
                k_hbm.at[bb, :, win], kbuf.at[slot], sem_k.at[slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[bb, :, win], vbuf.at[slot], sem_v.at[slot]
            ),
        ]

    @pl.when(b == 0)
    def _warmup():
        slot_ref[0] = 0
        for cp in dma(0, 0, 0):
            cp.start()

    s0 = slot_ref[0]
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(j, _):
        slot = jax.lax.rem(s0 + j, n_bufs)
        nxt = jax.lax.rem(s0 + j + 1, n_bufs)

        @pl.when(j + 1 < nb)
        def _prefetch_in_group():
            for cp in dma(b, j + 1, nxt):
                cp.start()

        @pl.when(jnp.logical_and(j + 1 == nb, b + 1 < nb_total))
        def _prefetch_next_group():
            for cp in dma(b + 1, 0, nxt):
                cp.start()

        # chaos hook: widen the slot-rotation window between the
        # prefetch issues and this block's wait (the race-prone carry)
        chaos_delay(site="flash_decode", step=None, me=None, n=None)
        for cp in dma(b, j, slot):
            cp.wait()

        win = pl.ds(j * block_k, block_k)

        def heads(masked):
            if masked:
                pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k), 1
                )
                valid = pos < kv_len               # (1, block_k)
            for h in range(hkv):                   # static unroll
                q = q_ref[0, h]                    # (G, D) bf16
                k = kbuf[slot, h].astype(jnp.bfloat16)
                v = vbuf[slot, h].astype(jnp.bfloat16)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                          # (G, block_k)
                s = s * ks_ref[0, h, :, win]
                if soft_cap > 0.0:
                    s = soft_cap * jnp.tanh(s / soft_cap)
                if masked:
                    s = jnp.where(valid, s, NEG_INF)
                lo, hi = h * g, (h + 1) * g
                m = m_ref[lo:hi]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(valid, p, 0.0)
                l_ref[lo:hi] = alpha * l_ref[lo:hi] + jnp.sum(
                    p, axis=1, keepdims=True
                )
                pv = (p * vs_ref[0, h, :, win]).astype(v.dtype)
                acc_ref[lo:hi] = alpha * acc_ref[lo:hi] + jnp.dot(
                    pv, v, preferred_element_type=jnp.float32
                )
                m_ref[lo:hi] = m_new

        is_tail = jnp.logical_and(
            j + 1 == nb, (j + 1) * block_k > kv_len
        )

        @pl.when(is_tail)
        def _masked():
            heads(True)

        @pl.when(jnp.logical_not(is_tail))
        def _plain():
            heads(False)

        return 0

    jax.lax.fori_loop(0, nb, body, 0)
    slot_ref[0] = jax.lax.rem(s0 + nb, n_bufs)     # hand the rotation on
    for h in range(hkv):
        lo, hi = h * g, (h + 1) * g
        l = l_ref[lo:hi]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0, h] = (acc_ref[lo:hi] / safe_l).astype(out_ref.dtype)
        lse_ref[0, h] = jnp.where(
            l > 0.0, m_ref[lo:hi] + jnp.log(safe_l), jnp.full_like(l, NEG_INF)
        )


def _paged_kernel_dyn_mh(
    scale, soft_cap, page, n_bufs, hkv, g, d, *refs,
):
    """MULTIHEAD dynamic-trip INT8 PAGED decode: grid (B,), all heads
    per step, the page walk as in-kernel manual DMAs indexed through
    the SMEM block table (scalar-prefetch — ``table_ref[b, j]`` picks
    the pool slab for row b's j-th page). The paged twin of
    :func:`_decode_kernel_dyn_mh`, for the same reason: the static
    (B, Hkv, pages) grid pays per-group overhead ~B·Hkv× — after the
    contiguous kernel went multihead, the paged serving step measured
    1.39× contiguous (was 1.08× grid-vs-grid, docs/PERF.md r5).

    Scale pools ride as (npages, Hkv, 1, page) ANY refs with their own
    small manual DMAs per page block — a table-indexed fetch can't use
    the grid pipeline (index maps change per grid step, not per inner
    loop iteration)."""
    (table_ref, kv_lens_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
     out_ref, lse_ref,
     kbuf, vbuf, ksbuf, vsbuf, sem_k, sem_v, sem_ks, sem_vs,
     slot_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    nb_total = pl.num_programs(0)
    npages = k_hbm.shape[0]
    pps = table_ref.shape[1]
    kv_len = kv_lens_ref[b]
    nb = jnp.minimum(_n_valid_blocks(kv_len, page), pps)

    def dma(bb, j, slot):
        # row bb's j-th page; clamp to the valid range so a prefetch
        # into a short row's padding never addresses out of pool
        jc = jnp.minimum(
            j, jnp.maximum(_n_valid_blocks(kv_lens_ref[bb], page) - 1, 0)
        )
        pid = jnp.clip(table_ref[bb, jc], 0, npages - 1)
        return [
            pltpu.make_async_copy(
                k_hbm.at[pid], kbuf.at[slot], sem_k.at[slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[pid], vbuf.at[slot], sem_v.at[slot]
            ),
            pltpu.make_async_copy(
                ks_hbm.at[pid], ksbuf.at[slot], sem_ks.at[slot]
            ),
            pltpu.make_async_copy(
                vs_hbm.at[pid], vsbuf.at[slot], sem_vs.at[slot]
            ),
        ]

    @pl.when(b == 0)
    def _warmup():
        slot_ref[0] = 0
        for cp in dma(0, 0, 0):
            cp.start()

    s0 = slot_ref[0]
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(j, _):
        slot = jax.lax.rem(s0 + j, n_bufs)
        nxt = jax.lax.rem(s0 + j + 1, n_bufs)

        @pl.when(j + 1 < nb)
        def _prefetch_in_group():
            for cp in dma(b, j + 1, nxt):
                cp.start()

        @pl.when(jnp.logical_and(j + 1 == nb, b + 1 < nb_total))
        def _prefetch_next_group():
            for cp in dma(b + 1, 0, nxt):
                cp.start()

        for cp in dma(b, j, slot):
            cp.wait()

        def heads(masked):
            if masked:
                pos = j * page + jax.lax.broadcasted_iota(
                    jnp.int32, (1, page), 1
                )
                valid = pos < kv_len
            for h in range(hkv):                   # static unroll
                q = q_ref[0, h]                    # (G, D) bf16
                k = kbuf[slot, h].astype(jnp.bfloat16)
                v = vbuf[slot, h].astype(jnp.bfloat16)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                s = s * ksbuf[slot, h]             # (1, page)
                if soft_cap > 0.0:
                    s = soft_cap * jnp.tanh(s / soft_cap)
                if masked:
                    s = jnp.where(valid, s, NEG_INF)
                lo, hi = h * g, (h + 1) * g
                m = m_ref[lo:hi]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(valid, p, 0.0)
                l_ref[lo:hi] = alpha * l_ref[lo:hi] + jnp.sum(
                    p, axis=1, keepdims=True
                )
                pv = (p * vsbuf[slot, h]).astype(v.dtype)
                acc_ref[lo:hi] = alpha * acc_ref[lo:hi] + jnp.dot(
                    pv, v, preferred_element_type=jnp.float32
                )
                m_ref[lo:hi] = m_new

        is_tail = jnp.logical_and(j + 1 == nb, (j + 1) * page > kv_len)

        @pl.when(is_tail)
        def _masked():
            heads(True)

        @pl.when(jnp.logical_not(is_tail))
        def _plain():
            heads(False)

        return 0

    jax.lax.fori_loop(0, nb, body, 0)
    slot_ref[0] = jax.lax.rem(s0 + nb, n_bufs)
    for h in range(hkv):
        lo, hi = h * g, (h + 1) * g
        l = l_ref[lo:hi]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        out_ref[0, h] = (acc_ref[lo:hi] / safe_l).astype(out_ref.dtype)
        lse_ref[0, h] = jnp.where(
            l > 0.0, m_ref[lo:hi] + jnp.log(safe_l), jnp.full_like(l, NEG_INF)
        )


def pick_block_k(s_len: int, requested: int, *, head_dim: int = 128,
                 itemsize: int = 2) -> int:
    """Largest divisor of ``s_len`` ≤ ``requested``, preferring sublane
    multiples (16). Replaces the old hard divisibility assert: SP cache
    slices (S/tp) may not divide the caller's block_k (e.g. capacity 384
    with the default block), and nothing upstream enforces it.

    On real TPU an unaligned *interior* second-minor block is a Mosaic
    lowering error (see ``_divisor_block``'s contract), so strict mode
    applies and a length with no aligned divisor ≤ requested degrades to
    ONE whole-length block (ragged edges are padded, interiors never
    misalign) — not to the old pathological block_k=1. That whole-length
    fallback is CAPPED (ADVICE r3): a long prime-ish cache slice would
    otherwise materialize an (s_len, D) K and V block in VMEM and fail
    at Mosaic compile/run far less legibly — raise here with the fix
    (pad the cache to an aligned capacity) instead."""
    from triton_distributed_tpu.config import compiling_for_tpu
    from triton_distributed_tpu.kernels.ag_gemm import _divisor_block

    b = _divisor_block(s_len, requested, 16, strict=compiling_for_tpu())
    if b:
        return b
    # whole-length fallback: 2 KV blocks (K and V) double-buffered by
    # the pipeline ≈ 4·s_len·D·itemsize of VMEM
    est = 4 * s_len * head_dim * itemsize
    budget = 64 * 1024 * 1024   # leave headroom under the 128 MB v5e VMEM
    if compiling_for_tpu() and est > budget:
        raise ValueError(
            f"flash_decode: cache slice length {s_len} has no 16-aligned "
            f"divisor <= block_k={requested}, and a whole-length KV block "
            f"(~{est >> 20} MB VMEM) exceeds the safe budget — pad the KV "
            "cache capacity to a multiple of 16"
        )
    return s_len


@functools.partial(
    jax.jit,
    static_argnames=("scale", "soft_cap", "block_k", "kv_layout", "interpret"),
)
def gqa_fwd_batch_decode(
    q, k_cache, v_cache, kv_lens, *,
    scale: float | None = None, soft_cap: float = 0.0,
    block_k: int | None = 2048, kv_layout: str = "bhsd", interpret=None,
):
    """Local GQA decode over a (sharded or whole) KV cache → (out, lse).

    q: (B, Hq, D); k_cache/v_cache: (B, Hkv, S, D) (``kv_layout="bhsd"``,
    the framework's native decode layout: each KV block is one contiguous
    DMA run — measured 97% of HBM speed-of-light on a v5e vs 87% for the
    strided view at the same block size) or (B, S, Hkv, D) (``"bshd"``,
    the reference-style layout); kv_lens: (B,) int32 valid lengths.
    The layout default is "bhsd" EVERYWHERE in this stack (kernel, XLA
    twin, AOT twin, SP entries, layer, append_kv) — callers holding
    reference-style caches must pass kv_layout="bshd" explicitly. Returns out
    (B, Hq, D) in q.dtype and lse (B, Hq) f32 — the per-shard partials
    the SP combine consumes. ``lse`` is the natural-log sum-exp of
    ``scale * q·k`` over valid positions (≡ gqa_fwd_batch_decode,
    flash_decode.py:763-846, with the intra-rank combine folded into the
    kernel's sequential KV walk).
    """
    batch, hq, d = q.shape
    if kv_layout == "bshd":
        _, s_len, hkv, _ = k_cache.shape
    elif kv_layout == "bhsd":
        _, hkv, s_len, _ = k_cache.shape
    else:
        raise ValueError(f"kv_layout must be 'bshd' or 'bhsd', got {kv_layout!r}")
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if block_k is None:
        # auto: half the capacity, clamped to the measured sweet band
        # (v5e sweeps, docs/PERF.md — smaller blocks lose DMA depth,
        # larger ones lose length granularity against partial fills)
        block_k = min(max(s_len // 2, 1024), 4096)
    block_k = pick_block_k(
        s_len, block_k, head_dim=d, itemsize=k_cache.dtype.itemsize
    )

    qg = q.reshape(batch, hkv, g, d)
    # the manual-DMA path slices (block_k, d) runs out of the raw cache,
    # which needs native tile alignment (lane dim d ≡ 0 mod 128, sublane
    # offset ≡ 0 mod 8); unaligned geometries (tiny test heads) take the
    # static BlockSpec grid below, whose pipeline pads transparently
    if kv_layout == "bhsd" and d % 128 == 0 and block_k % 8 == 0:
        # native layout: dynamic-trip-count kernel — grid (B, Hkv),
        # in-kernel double-buffered KV DMAs, ceil(len/block_k) blocks
        # per row (HBM reads scale with TRUE lengths, not capacity)
        n_bufs = 2
        kernel = functools.partial(
            _decode_kernel_dyn, scale, soft_cap, block_k, n_bufs, g, d, False
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,              # kv_lens → trip counts
            grid=(batch, hkv),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, lens: (b, h, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, g, 1), lambda b, h, lens: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_bufs, block_k, d), k_cache.dtype),
                pltpu.VMEM((n_bufs, block_k, d), v_cache.dtype),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SMEM((1,), jnp.int32),    # slot rotation carry
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        )
        call = shmem_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((batch, hkv, g, d), q.dtype),
                jax.ShapeDtypeStruct((batch, hkv, g, 1), jnp.float32),
            ],
            collective_id=None,
            interpret=local_interpret() if interpret is None else interpret,
            name="gqa_decode_split_kv_dyn",
            # the slot-rotation carry (SMEM) and cross-step DMA prefetch
            # are only correct under SEQUENTIAL grid execution — pin it
            # so a parallel/Megacore default can't corrupt the pipeline
            dimension_semantics=("arbitrary", "arbitrary"),
        )
        out, lse = call(kv_lens.astype(jnp.int32), qg, k_cache, v_cache)
        return out.reshape(batch, hq, d), lse.reshape(batch, hq)

    # static (B, Hkv, S/block_k) grid: the reference-style bshd layout
    # (whose strided head window precludes the manual contiguous-run
    # DMA above) and unaligned-geometry bhsd fallbacks
    if kv_layout == "bshd":
        kf = k_cache.reshape(batch, s_len, hkv * d)   # free view, no copy
        vf = v_cache.reshape(batch, s_len, hkv * d)
        kv_spec = pl.BlockSpec((1, block_k, d), lambda b, h, k: (b, k, h))
    else:
        kf, vf = k_cache, v_cache
        kv_spec = pl.BlockSpec(
            (1, 1, block_k, d), lambda b, h, k: (b, h, k, 0)
        )
    kernel = functools.partial(_decode_kernel, scale, soft_cap, block_k)
    call = shmem_call(
        kernel,
        grid=(batch, hkv, s_len // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # kv_lens, whole (B,)
            pl.BlockSpec((1, 1, g, d), lambda b, h, k: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, k: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b, h, k: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hkv, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        collective_id=None,
        interpret=local_interpret() if interpret is None else interpret,
        name="gqa_decode_split_kv",
    )
    out, lse = call(kv_lens.astype(jnp.int32), qg, kf, vf)
    return out.reshape(batch, hq, d), lse.reshape(batch, hq)


def quantize_kv(x):
    """Per-(…, s) row int8 quantization of a (..., S, D) cache tensor:
    each length-D row gets one f32 scale (max-abs / 127). Returns
    (int8 values, f32 scales of shape x.shape[:-1]).

    TPU-first serving extension (the reference quantizes only the
    tokens moving through the MoE wire, low_latency_all_to_all.py:82-90;
    the stationary KV cache is the larger HBM consumer at decode —
    int8 halves both the cache footprint and the attention DMA bytes).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(amax > 0.0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(
        jnp.round(xf / s[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, s


def _mh_q8_vmem_plan(hkv, s_len, block_k, d, n_bufs, multihead):
    """(n_bufs, vmem_limit, multihead) for the multihead-q8 decode grid.

    The VMEM residents are the int8 KV slot buffers (2 · n_bufs · Hkv ·
    block_k · d) AND the grid-pipelined (1, Hkv, 1, S) f32 scale planes
    — 2 planes (K and V) × 2 Mosaic pipeline buffers × Hkv·S·4 B, which
    grow linearly in per-shard S and previously ate the fixed 8 MB
    headroom silently (ADVICE r5: compilation failures from ~64k
    per-shard S). Budgeting: shallower KV buffering first; then a
    scoped vmem_limit that counts BOTH terms; above the per-shard-S
    threshold where even minimal buffering cannot fit the configured
    budget, fall back to the per-(b, h) grid (multihead=False), whose
    scale blocks are Hkv× smaller."""
    from triton_distributed_tpu.config import fused_vmem_budget

    def kv_bytes(nb):
        return 2 * nb * hkv * block_k * d

    scale_bytes = 2 * 2 * hkv * s_len * 4
    while multihead and n_bufs > 2 and \
            kv_bytes(n_bufs) + scale_bytes > 12 * 1024 * 1024:
        n_bufs -= 1
    vmem_limit = None
    if multihead and kv_bytes(n_bufs) + scale_bytes > 12 * 1024 * 1024:
        vmem_limit = kv_bytes(n_bufs) + scale_bytes + 8 * 1024 * 1024
        if vmem_limit > fused_vmem_budget():
            # per-shard S too large for the multihead grid at any depth
            multihead = False
            vmem_limit = None
    return n_bufs, vmem_limit, multihead


def _q8_auto_block_k(batch, hkv, s_len):
    """Block size for the int8 walk — the r4 heuristic (half capacity
    clamped to [1024, 4096]) re-validated round 5 by a PAIRED sweep at
    the serving headline (B=128, Hkv=8, S=2048, mixed lens U[S/8,
    3S/4], v5e): 1024 best; 512 +20%, 256 +57% (per-block overhead),
    2048 +5% (over-read on partial rows). The walk is bytes/BW bound
    (~0.17 µs/block fixed + ~470-580 GB/s effective on 131-262 KB
    contiguous runs) — NOT DMA-count bound: moving the per-block scale
    copies onto the grid pipeline and deepening n_bufs 2→4 measured
    neutral at 1024 (docs/PERF.md round-5 serving attention)."""
    del batch, hkv
    return min(max(s_len // 2, 1024), 4096)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "soft_cap", "block_k", "n_bufs", "multihead",
                     "interpret"),
)
def gqa_fwd_batch_decode_q8(
    q, k_q, k_scale, v_q, v_scale, kv_lens, *,
    scale: float | None = None, soft_cap: float = 0.0,
    block_k: int | None = None, n_bufs: int = 4, multihead: bool = True,
    interpret=None,
):
    """Local GQA decode over an INT8 KV cache → (out, lse).

    q: (B, Hq, D) bf16/f32; k_q/v_q: (B, Hkv, S, D) int8 [bhsd];
    k_scale/v_scale: (B, Hkv, S) f32 per-token-per-head scales (from
    :func:`quantize_kv`). Same contract as :func:`gqa_fwd_batch_decode`
    — dynamic per-row trip counts, reads scale with TRUE lengths — at
    half the KV bytes; the scales fold exactly into the softmax and
    ride the grid pipeline, not per-block DMAs (see
    ``_decode_kernel_dyn``'s quant mode). ``n_bufs``: KV slot depth —
    4 keeps the DMA engine fed across short (1-2 block) rows where
    double buffering drains at every group boundary. ``multihead``
    (default): grid (B,) with all Hkv heads per step — 8× fewer grid
    groups, the round-5 fix for the per-group overhead that dominated
    the serving shape (``_decode_kernel_dyn_mh``); False keeps the
    per-(b, h) grid (comparison/debug).
    """
    batch, hq, d = q.shape
    _, hkv, s_len, _ = k_q.shape
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if block_k is None:
        block_k = _q8_auto_block_k(batch, hkv, s_len)
    block_k = pick_block_k(s_len, block_k, head_dim=d, itemsize=1)

    if d % 128 != 0 or block_k % 128 != 0:
        # unaligned geometry (the in-kernel scale slice works at lane
        # granules): widen via XLA and take the dense path
        k = (k_q.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
        v = (v_q.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
        return gqa_fwd_batch_decode(
            q, k, v, kv_lens, scale=scale, soft_cap=soft_cap,
            block_k=block_k, kv_layout="bhsd", interpret=interpret,
        )

    qg = q.reshape(batch, hkv, g, d).astype(jnp.bfloat16)
    ks4 = k_scale.astype(jnp.float32).reshape(batch, hkv, 1, s_len)
    vs4 = v_scale.astype(jnp.float32).reshape(batch, hkv, 1, s_len)
    n_bufs, vmem_limit, multihead = _mh_q8_vmem_plan(
        hkv, s_len, block_k, d, n_bufs, multihead
    )
    if multihead:
        kernel = functools.partial(
            _decode_kernel_dyn_mh, scale, soft_cap, block_k, n_bufs,
            hkv, g, d,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch,),
            in_specs=[
                pl.BlockSpec((1, hkv, g, d), lambda b, lens: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                # whole per-row scale planes on the grid pipeline (see
                # _decode_kernel_dyn's quant note)
                pl.BlockSpec(
                    (1, hkv, 1, s_len), lambda b, lens: (b, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, hkv, 1, s_len), lambda b, lens: (b, 0, 0, 0)
                ),
            ],
            out_specs=[
                pl.BlockSpec((1, hkv, g, d), lambda b, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, hkv, g, 1), lambda b, lens: (b, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_bufs, hkv, block_k, d), jnp.int8),
                pltpu.VMEM((n_bufs, hkv, block_k, d), jnp.int8),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((hkv * g, 1), jnp.float32),
                pltpu.VMEM((hkv * g, 1), jnp.float32),
                pltpu.VMEM((hkv * g, d), jnp.float32),
            ],
        )
        dims = ("arbitrary",)
    else:
        kernel = functools.partial(
            _decode_kernel_dyn, scale, soft_cap, block_k, n_bufs, g, d, True
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, hkv),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, lens: (b, h, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(
                    (1, 1, 1, s_len), lambda b, h, lens: (b, h, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, 1, s_len), lambda b, h, lens: (b, h, 0, 0)
                ),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, g, d), lambda b, h, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, g, 1), lambda b, h, lens: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_bufs, block_k, d), jnp.int8),
                pltpu.VMEM((n_bufs, block_k, d), jnp.int8),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SemaphoreType.DMA((n_bufs,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        )
        dims = ("arbitrary", "arbitrary")
    call = shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hkv, g, 1), jnp.float32),
        ],
        collective_id=None,
        vmem_limit_bytes=vmem_limit,
        interpret=local_interpret() if interpret is None else interpret,
        name="gqa_decode_split_kv_q8" + ("_mh" if multihead else ""),
        # slot-rotation carries + cross-step DMA prefetch require
        # SEQUENTIAL grid execution
        dimension_semantics=dims,
    )
    out, lse = call(kv_lens.astype(jnp.int32), qg, k_q, v_q, ks4, vs4)
    return out.reshape(batch, hq, d), lse.reshape(batch, hq)


def gqa_fwd_batch_decode_q8_xla(
    q, k_q, k_scale, v_q, v_scale, kv_lens, *, scale=None, soft_cap=0.0,
):
    """Dense-XLA twin of :func:`gqa_fwd_batch_decode_q8` (correctness
    reference): widen the int8 cache and run the dense reference."""
    k = (k_q.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
    v = (v_q.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    return gqa_fwd_batch_decode_xla(
        q, k, v, kv_lens, scale=scale, soft_cap=soft_cap, kv_layout="bhsd"
    )


def _paged_decode_kernel(
    scale, soft_cap, page, table_ref, kv_lens_ref, q_ref, k_ref, v_ref,
    out_ref, lse_ref, m_ref, l_ref, acc_ref,
):
    """Scalar-prefetch adapter over :func:`_decode_kernel`: the page
    table is consumed by the BlockSpec index maps (which page to DMA
    next), not by the compute body."""
    del table_ref
    _decode_kernel(
        scale, soft_cap, page, kv_lens_ref, q_ref, k_ref, v_ref,
        out_ref, lse_ref, m_ref, l_ref, acc_ref,
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "soft_cap", "interpret")
)
def paged_gqa_fwd_batch_decode(
    q, k_pool, v_pool, kv_lens, block_table, *,
    scale: float | None = None, soft_cap: float = 0.0, interpret=None,
):
    """PAGED GQA decode: the KV cache lives in a shared page pool and
    each batch row walks its own page list (≡ the reference's paged
    entries — gqa_fwd_batch_decode takes (num_pages, page_size, Hkv, D)
    caches + a block_table, flash_decode.py:763-846, and the SP layer
    forwards one, sp_flash_decode_layer.py:78-84).

    q: (B, Hq, D); k_pool/v_pool: (num_pages, Hkv, page_size, D) —
    "phsd", the paged analogue of the bhsd fast layout: one (page,
    head) block is a single contiguous DMA run. block_table:
    (B, pages_per_seq) int32 page ids (entries past the valid length
    may be any in-range id — their scores are masked by ``kv_lens``);
    kv_lens: (B,) valid lengths. Returns (out (B, Hq, D), lse (B, Hq)).

    The page table rides as a scalar-prefetch operand so the KV
    BlockSpec index maps read it directly — the kernel's sequential
    page walk is physically gather-free (the DMA engine fetches page
    ``table[b, j]`` while page ``j-1`` computes), the TPU translation
    of the reference's in-kernel ``tl.load(block_table + ...)``.

    Page-size guidance (measured on a v5e, docs/PERF.md): per-page
    pipeline overhead makes small GPU-style pages slow — use ≥1024-row
    pages (757 GB/s at 2048, matching the contiguous kernel; 149 GB/s
    at 128).
    """
    batch, hq, d = q.shape
    npages, hkv, page, _ = k_pool.shape
    assert v_pool.shape == k_pool.shape, (k_pool.shape, v_pool.shape)
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    qg = q.reshape(batch, hkv, g, d)
    pages_per_seq = block_table.shape[1]
    grid = (batch, hkv, pages_per_seq)

    def kv_map(b, h, j, table_ref, lens_ref):
        # length-aware page skipping (same trick as the dense kernel's
        # block clamp): steps past row b's last valid page revisit it,
        # so Mosaic skips their DMA — reads scale with true lengths.
        # Also doubles as the -1-padding guard: clamped steps never
        # consult the (possibly -1) padded table entries.
        jc = jnp.minimum(j, _n_valid_blocks(lens_ref[b], page) - 1)
        # clamp BOTH ways: padded table entries (-1 padding included)
        # must never address out of pool
        return (jnp.clip(table_ref[b, jc], 0, npages - 1), h, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, page, d), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, g, d), lambda b, h, j, t_, l_: (b, h, 0, 0)
            ),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, j, t_, l_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b, h, j, t_, l_: (b, h, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale, soft_cap, page),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hkv, g, 1), jnp.float32),
        ],
        interpret=local_interpret() if interpret is None else interpret,
        name="gqa_decode_paged",
    )
    out, lse = call(
        block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
        qg, k_pool, v_pool,
    )
    return out.reshape(batch, hq, d), lse.reshape(batch, hq)


@functools.partial(
    jax.jit, static_argnames=("scale", "soft_cap", "interpret")
)
def paged_gqa_fwd_batch_decode_q8(
    q, k_pool, k_scale, v_pool, v_scale, kv_lens, block_table, *,
    scale: float | None = None, soft_cap: float = 0.0, interpret=None,
):
    """PAGED GQA decode over an INT8 page pool.

    k_pool/v_pool: (num_pages, Hkv, page, D) int8; k_scale/v_scale:
    (num_pages, Hkv, page) f32 per-row scales (reshaped internally to
    the lane-aligned (num_pages, Hkv, 1, page) DMA layout). Same
    contract as :func:`paged_gqa_fwd_batch_decode` at half the KV pool
    bytes — the int8 composition of the paged and quantized serving
    modes (block-table page walk + exact in-softmax scale folds).
    """
    batch, hq, d = q.shape
    npages, hkv, page, _ = k_pool.shape
    assert v_pool.shape == k_pool.shape, (k_pool.shape, v_pool.shape)
    assert hq % hkv == 0, f"GQA needs Hq % Hkv == 0, got {hq} % {hkv}"
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    if d % 128 != 0 or page % 128 != 0:
        # unaligned geometry (the (…, 1, page) scale windows slice the
        # lane dim at page granules): widen and take the full-precision
        # paged path — the SAME fallback discipline (and precision) as
        # the contiguous q8 entry, so mixed-geometry callers see one
        # numerical behavior across cache layouts
        kp = (k_pool.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
        vp = (v_pool.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
        return paged_gqa_fwd_batch_decode(
            q, kp, vp, kv_lens, block_table, scale=scale,
            soft_cap=soft_cap, interpret=interpret,
        )

    qg = q.reshape(batch, hkv, g, d).astype(jnp.bfloat16)

    # MULTIHEAD page walk (grid (B,), manual table-indexed DMAs): 8×
    # fewer grid groups than the static (B, Hkv, pages) grid — the
    # per-group overhead fix of _decode_kernel_dyn_mh applied to the
    # paged mode (see _paged_kernel_dyn_mh)
    n_bufs = 4
    while n_bufs > 2 and 2 * n_bufs * hkv * page * d > 12 * 1024 * 1024:
        n_bufs -= 1
    vmem_limit = None
    if 2 * n_bufs * hkv * page * d > 12 * 1024 * 1024:
        vmem_limit = 2 * n_bufs * hkv * page * d + 8 * 1024 * 1024
    mh_kernel = functools.partial(
        _paged_kernel_dyn_mh, scale, soft_cap, page, n_bufs, hkv, g, d
    )
    mh_grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table, kv_lens
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda b, t_, l_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda b, t_, l_: (b, 0, 0, 0)),
            pl.BlockSpec((1, hkv, g, 1), lambda b, t_, l_: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_bufs, hkv, page, d), jnp.int8),
            pltpu.VMEM((n_bufs, hkv, page, d), jnp.int8),
            pltpu.VMEM((n_bufs, hkv, 1, page), jnp.float32),
            pltpu.VMEM((n_bufs, hkv, 1, page), jnp.float32),
            pltpu.SemaphoreType.DMA((n_bufs,)),
            pltpu.SemaphoreType.DMA((n_bufs,)),
            pltpu.SemaphoreType.DMA((n_bufs,)),
            pltpu.SemaphoreType.DMA((n_bufs,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv * g, 1), jnp.float32),
            pltpu.VMEM((hkv * g, 1), jnp.float32),
            pltpu.VMEM((hkv * g, d), jnp.float32),
        ],
    )
    mh_call = shmem_call(
        mh_kernel,
        grid_spec=mh_grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, hkv, g, d), q.dtype),
            jax.ShapeDtypeStruct((batch, hkv, g, 1), jnp.float32),
        ],
        collective_id=None,
        vmem_limit_bytes=vmem_limit,
        interpret=local_interpret() if interpret is None else interpret,
        name="gqa_decode_paged_q8_mh",
        dimension_semantics=("arbitrary",),   # slot carry is sequential
    )
    out, lse = mh_call(
        block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
        qg, k_pool, v_pool,
        k_scale.astype(jnp.float32).reshape(npages, hkv, 1, page),
        v_scale.astype(jnp.float32).reshape(npages, hkv, 1, page),
    )
    return out.reshape(batch, hq, d), lse.reshape(batch, hq)


def paged_gqa_fwd_batch_decode_q8_xla(
    q, k_pool, k_scale, v_pool, v_scale, kv_lens, block_table, *,
    scale=None, soft_cap=0.0,
):
    """Dense-XLA twin: widen the int8 pools and take the dense paged
    reference."""
    kp = (k_pool.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
    vp = (v_pool.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
    return paged_gqa_fwd_batch_decode_xla(
        q, kp, vp, kv_lens, block_table, scale=scale, soft_cap=soft_cap
    )


def paged_gqa_fwd_batch_decode_xla(
    q, k_pool, v_pool, kv_lens, block_table, *, scale=None, soft_cap=0.0,
):
    """Dense-XLA twin of :func:`paged_gqa_fwd_batch_decode`: gather the
    pages into a contiguous bhsd cache and reuse the dense reference."""
    npages, hkv, page, d = k_pool.shape
    safe = jnp.clip(block_table.astype(jnp.int32), 0, npages - 1)
    # (B, P, Hkv, page, D) → (B, Hkv, P·page, D)
    kc = k_pool[safe].transpose(0, 2, 1, 3, 4).reshape(
        block_table.shape[0], hkv, -1, d
    )
    vc = v_pool[safe].transpose(0, 2, 1, 3, 4).reshape(
        block_table.shape[0], hkv, -1, d
    )
    return gqa_fwd_batch_decode_xla(
        q, kc, vc, kv_lens, scale=scale, soft_cap=soft_cap,
        kv_layout="bhsd",
    )


def _local_paged_shard_decode(
    q, k_pool, v_pool, global_kv_lens, block_table, axis, *,
    scale, soft_cap, use_pallas, interpret=None,
):
    """Rank-local PAGED decode over this rank's sequence slice — the ONE
    definition of the per-rank lens/dispatch logic (shared by the device
    body and the jitted SP entry, mirroring _local_shard_decode)."""
    r = jax.lax.axis_index(axis)
    page = k_pool.shape[2]
    s_loc = block_table.shape[1] * page
    local_lens = jnp.clip(
        global_kv_lens - r * s_loc, 0, s_loc
    ).astype(jnp.int32)
    decode = (
        paged_gqa_fwd_batch_decode if use_pallas
        else paged_gqa_fwd_batch_decode_xla
    )
    kwargs = dict(scale=scale, soft_cap=soft_cap)
    if use_pallas:
        kwargs.update(interpret=interpret)
    return decode(q, k_pool, v_pool, local_lens, block_table, **kwargs)


def sp_paged_gqa_fwd_batch_decode_device(
    q, k_pool, v_pool, global_kv_lens, block_table, axis, *,
    scale=None, soft_cap=0.0, use_pallas=True, interpret=None,
):
    """Per-device SP PAGED decode body — callable inside any shard_map.

    Each rank owns a page pool and the page table of ITS contiguous
    sequence slice (≡ "each rank's kv shard's kv_table",
    sp_flash_decode_layer.py:84): local paged decode over the slice,
    then the usual AG(out, lse) + inter-rank combine.
    """
    out, lse = _local_paged_shard_decode(
        q, k_pool, v_pool, global_kv_lens, block_table, axis,
        scale=scale, soft_cap=soft_cap, use_pallas=use_pallas,
        interpret=interpret,
    )
    return _merge_shard_partials(out, lse, axis)


def gqa_fwd_batch_decode_aot(
    *, scale: float | None = None, soft_cap: float = 0.0,
    block_k: int = 2048, kv_layout: str = "bhsd", cache_dir=".aot_cache",
):
    """AOT twin of :func:`gqa_fwd_batch_decode` (≡ the ``*_aot`` entries
    calling pre-compiled kernels, flash_decode.py:1007-1160): returns a
    shape-dispatching artifact library — ``.compile(q, k, v, lens)``
    serializes one shape point, calls reload it without retracing."""
    from triton_distributed_tpu.tools.aot import AotLibrary

    def entry(q, k_cache, v_cache, kv_lens):
        return gqa_fwd_batch_decode(
            q, k_cache, v_cache, kv_lens,
            scale=scale, soft_cap=soft_cap, block_k=block_k,
            kv_layout=kv_layout,
        )

    # hyperparameters are part of the artifact identity — two libraries
    # sharing a cache_dir must never reuse each other's kernels
    name = f"gqa_decode-bk{block_k}-sc{soft_cap}-s{scale}-{kv_layout}"
    return AotLibrary(entry, name=name, cache_dir=cache_dir)


def gqa_fwd_batch_decode_xla(
    q, k_cache, v_cache, kv_lens, *, scale=None, soft_cap=0.0,
    kv_layout: str = "bhsd",
):
    """Dense-XLA twin of :func:`gqa_fwd_batch_decode` (correctness
    reference, ≡ the torch baselines in test_decode_attn.py)."""
    batch, hq, d = q.shape
    if kv_layout == "bshd":
        s_len = k_cache.shape[1]
        kt = k_cache.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,Hkv,S,D)
        vt = v_cache.transpose(0, 2, 1, 3).astype(jnp.float32)
    else:
        s_len = k_cache.shape[2]
        kt = k_cache.astype(jnp.float32)
        vt = v_cache.astype(jnp.float32)
    hkv = kt.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qg = q.reshape(batch, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, kt) * scale
    if soft_cap > 0.0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    mask = jnp.arange(s_len)[None, None, None, :] < kv_lens[:, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # explicit mask: an empty row has m == NEG_INF and exp degenerates
    # to 1 — mask so l stays 0 and the output is exact zeros (matching
    # the kernel's block-skipping-independent semantics)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhgs,bhsd->bhgd", p / jnp.maximum(l, 1e-30), vt)
    lse = jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30)), NEG_INF)
    return out.reshape(batch, hq, d).astype(q.dtype), lse.reshape(batch, hq)


def combine_partials(outs, lses, out_dtype=None):
    """Merge per-shard (out, lse) partials along axis 0.

    outs: (R, B, Hq, D); lses: (R, B, Hq). The blockwise-softmax /
    ring-attention merge (≡ kernel_inter_rank_gqa_fwd_batch_decode_
    combine_kv, flash_decode.py:482-566): weight each shard by
    exp(lse_r − lse_max) and renormalize. Shards with empty KV carry
    lse == NEG_INF and contribute exactly zero.
    """
    out_dtype = out_dtype or outs.dtype
    lses = lses.astype(jnp.float32)
    m = jnp.max(lses, axis=0, keepdims=True)                 # (1, B, Hq)
    w = jnp.exp(lses - m)                                    # (R, B, Hq)
    denom = jnp.maximum(jnp.sum(w, axis=0), 1e-30)           # (B, Hq)
    merged = jnp.einsum("rbh,rbhd->bhd", w, outs.astype(jnp.float32)) / denom[..., None]
    lse = m[0] + jnp.log(denom)
    return merged.astype(out_dtype), lse


def combine_gqa_partials(outs, lses, out_dtype=None):
    """Merge cp-rank partials in the ragged-kernel layout.

    outs: (R, Hkv, TG, D); lses: (R, Hkv, TG) — the (out, lse) pair
    :func:`~triton_distributed_tpu.kernels.ragged_paged_attention.
    ragged_paged_attention` returns, stacked along the cp axis. Same
    softmax merge as :func:`combine_partials`; the explicit where()
    guard keeps rows every shard masked out (all lses at NEG_INF —
    padding tokens, empty shards) at exactly zero weight instead of
    degenerating exp(NEG_INF − NEG_INF) to 1. For a row fully resident
    on one shard the merge is the identity on that shard's out
    (weights 1/1 in f32 — bit-exact through the round trip), which is
    what makes short-request streams byte-identical to the cp-free
    engine.
    """
    out_dtype = out_dtype or outs.dtype
    lses = lses.astype(jnp.float32)
    m = jnp.max(lses, axis=0, keepdims=True)                 # (1, Hkv, TG)
    w = jnp.where(lses > NEG_INF / 2, jnp.exp(lses - m), 0.0)
    denom = jnp.maximum(jnp.sum(w, axis=0), 1e-30)           # (Hkv, TG)
    merged = jnp.einsum(
        "rht,rhtd->htd", w, outs.astype(jnp.float32)
    ) / denom[..., None]
    lse = jnp.where(
        jnp.max(lses, axis=0) > NEG_INF / 2,
        m[0] + jnp.log(denom),
        NEG_INF,
    )
    return merged.astype(out_dtype), lse


def cp_lse_combine_xla(x, mesh, axis: str = "x"):
    """XLA body of the cp-decode LSE-combine — the degradation target
    declared for the ``cp_decode.lse_combine`` lint family.

    ``x``: per-rank (n·m, cols) contribution slabs stacked along
    ``axis`` (rows ``[dst·m, (dst+1)·m)`` = this rank's exp-weighted
    partial for destination shard ``dst``: numerator rows ``w_r·out_r``
    with the additive denominator row ``Σ w_r`` riding in the block —
    the weighting against the pre-agreed running max makes the merge a
    pure add over ranks, cf. :func:`combine_partials`). Returns each
    rank's (m, cols) reduced destination shard — ``psum_scatter``, the
    ring kernel's semantics on the raw f32 wire.
    """
    fn = jax.shard_map(
        lambda s: jax.lax.psum_scatter(
            s.astype(jnp.float32), axis, scatter_dimension=0, tiled=True
        ),
        mesh=mesh, in_specs=P(axis), out_specs=P(axis), check_vma=False,
    )
    return jax.jit(fn)(x)


def _local_shard_decode(
    q, k_shard, v_shard, global_kv_lens, axis, *,
    scale, soft_cap, block_k, use_pallas, kv_layout="bhsd", interpret=None,
):
    """Rank-local decode over this rank's contiguous KV slice → (out, lse)."""
    r = jax.lax.axis_index(axis)
    s_loc = k_shard.shape[1 if kv_layout == "bshd" else 2]
    local_lens = jnp.clip(global_kv_lens - r * s_loc, 0, s_loc).astype(jnp.int32)
    decode = gqa_fwd_batch_decode if use_pallas else gqa_fwd_batch_decode_xla
    kwargs = dict(scale=scale, soft_cap=soft_cap, kv_layout=kv_layout)
    if use_pallas:
        kwargs.update(block_k=block_k, interpret=interpret)
    return decode(q, k_shard, v_shard, local_lens, **kwargs)


def _merge_shard_partials(out, lse, axis):
    """AG of per-rank (out, lse) + inter-rank combine, inside shard_map.

    Small payload — the reference uses its LL allgather here
    (low_latency_allgather_layer.py); XLA's all_gather over ICI is the
    TPU fast path for this message size.
    """
    merged, _ = _merge_shard_partials_lse(out, lse, axis)
    return merged


def _merge_shard_partials_lse(out, lse, axis):
    """Like :func:`_merge_shard_partials` but returning (out, lse) —
    callers can merge FURTHER partials (e.g. the current decode step's
    just-produced token, ``layers.SpGQAFlashDecodeAttention.
    token_partial``: the softmax merge is associative, so the new token
    rides as an exact single-position partial with lse = its raw
    score, and the cache append need not feed the attention kernel)."""
    outs = jax.lax.all_gather(out, axis)
    lses = jax.lax.all_gather(lse, axis)
    return combine_partials(outs, lses, out_dtype=out.dtype)


def sp_gqa_fwd_batch_decode_device(
    q, k_shard, v_shard, global_kv_lens, axis, *,
    scale=None, soft_cap=0.0, block_k=2048, use_pallas=True,
    kv_layout="bhsd", interpret=None,
):
    """Per-device SP decode body — callable inside any shard_map.

    q: (B, Hq, D) replicated across ``axis``; k_shard/v_shard: this
    rank's contiguous slice of the sequence — (B, Hkv, S/R, D) for
    ``kv_layout="bhsd"`` (native, default) or (B, S/R, Hkv, D) for
    ``"bshd"``;
    global_kv_lens: (B,) TOTAL valid lengths. ≡ SpGQAFlashDecodeAttention
    .forward (sp_flash_decode_layer.py:78-184): local decode → AG of
    (out, lse) → inter-rank combine.
    """
    out, lse = _local_shard_decode(
        q, k_shard, v_shard, global_kv_lens, axis,
        scale=scale, soft_cap=soft_cap, block_k=block_k,
        use_pallas=use_pallas, kv_layout=kv_layout, interpret=interpret,
    )
    return _merge_shard_partials(out, lse, axis)


def _sp_specs(axis, batch_axes):
    """(batch-dim spec, rank-stacked partial spec, merged out spec) for
    the SP decode shard_maps. With ``batch_axes`` (e.g. a dp mesh axis)
    the batch dim 0 of q/lens/caches is SHARDED over them — the
    serving layout on a dp×tp mesh: batch over dp, sequence over tp.
    The per-rank partials stack rank-major into dim 0, so the stacked
    dim is sharded over (batch_axes..., axis)."""
    ba = tuple(batch_axes)
    b = ba if ba else None
    return b, ba + (axis,), b


@functools.lru_cache(maxsize=64)
def _sp_decode_fns(mesh, axis, scale, soft_cap, block_k, use_pallas,
                   kv_layout, batch_axes=(), ikey=()):
    """Jitted (local, merge) pair for :func:`sp_gqa_fwd_batch_decode`,
    cached so repeated decode steps don't retrace/recompile. ``ikey``
    is ``config.interp_key()`` — chaos/fault knobs are traced into the
    local decode kernel, so toggling them must rebuild (the same
    convention as every collective builder)."""
    # Two dispatches, not one: on the CPU-interpreter path, mixing the
    # io_callback-driven Pallas simulation and an XLA collective in a single
    # program can starve the collective rendezvous threads (deadlock). On
    # TPU the split costs one extra dispatch on a microseconds-scale op.
    def local(q, k_shard, v_shard, lens):
        return _local_shard_decode(
            q, k_shard, v_shard, lens, axis,
            scale=scale, soft_cap=soft_cap, block_k=block_k,
            use_pallas=use_pallas, kv_layout=kv_layout,
        )

    b, part, out = _sp_specs(axis, batch_axes)
    kv_spec = P(b, axis) if kv_layout == "bshd" else P(b, None, axis)
    local_fn = jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(b), kv_spec, kv_spec, P(b)),
            out_specs=(P(part), P(part)),
            check_vma=False,
        )
    )
    merge_fn = jax.jit(
        jax.shard_map(
            functools.partial(_merge_shard_partials_lse, axis=axis),
            mesh=mesh,
            in_specs=(P(part), P(part)),
            out_specs=(P(out), P(out)),
            check_vma=False,
        )
    )
    return local_fn, merge_fn


def sp_gqa_fwd_batch_decode(
    q, k_cache, v_cache, global_kv_lens, mesh, axis="x", *,
    scale=None, soft_cap=0.0, block_k=2048, use_pallas=True,
    kv_layout="bhsd", with_lse=False, batch_axes=(),
):
    """Host entry: sequence-parallel GQA decode on ``mesh``.

    k_cache/v_cache: (B, Hkv, S, D) [bhsd, native default] or
    (B, S, Hkv, D) [bshd] with S sharded over ``axis``; q and
    global_kv_lens replicated. Returns (B, Hq, D) replicated —
    plus the merged (B, Hq) lse with ``with_lse`` (for callers
    merging further partials via :func:`combine_partials`).
    With ``batch_axes`` (dp mesh axes), the batch dim of every
    operand and result is sharded over them instead — the serving
    layout on a dp×tp mesh (batch over dp, sequence over ``axis``).
    """
    local_fn, merge_fn = _sp_decode_fns(
        mesh, axis, scale, soft_cap, block_k, use_pallas, kv_layout,
        tuple(batch_axes), interp_key(),
    )
    out, lse = local_fn(q, k_cache, v_cache, global_kv_lens)
    out, lse = merge_fn(out, lse)
    return (out, lse) if with_lse else out


def _local_shard_decode_q8(
    q, k_q, k_scale, v_q, v_scale, global_kv_lens, axis, *,
    scale, soft_cap, block_k, interpret=None,
):
    """Rank-local INT8 decode over this rank's contiguous KV slice."""
    r = jax.lax.axis_index(axis)
    s_loc = k_q.shape[2]
    local_lens = jnp.clip(
        global_kv_lens - r * s_loc, 0, s_loc
    ).astype(jnp.int32)
    return gqa_fwd_batch_decode_q8(
        q, k_q, k_scale, v_q, v_scale, local_lens,
        scale=scale, soft_cap=soft_cap, block_k=block_k,
        interpret=interpret,
    )


def sp_gqa_fwd_batch_decode_q8_device(
    q, k_q, k_scale, v_q, v_scale, global_kv_lens, axis, *,
    scale=None, soft_cap=0.0, block_k=None, interpret=None,
):
    """Per-device SP decode body over an INT8 KV cache (composable
    inside any shard_map; quantized twin of
    :func:`sp_gqa_fwd_batch_decode_device`)."""
    out, lse = _local_shard_decode_q8(
        q, k_q, k_scale, v_q, v_scale, global_kv_lens, axis,
        scale=scale, soft_cap=soft_cap, block_k=block_k,
        interpret=interpret,
    )
    return _merge_shard_partials(out, lse, axis)


@functools.lru_cache(maxsize=64)
def _sp_q8_fns(mesh, axis, scale, soft_cap, block_k, batch_axes=(), ikey=()):
    """Jitted (local, merge) pair for the INT8 SP decode — split into
    two dispatches for the interpreter-deadlock reason documented at
    :func:`_sp_decode_fns`."""

    def local(q, kq, ks, vq, vs, lens):
        return _local_shard_decode_q8(
            q, kq, ks, vq, vs, lens, axis,
            scale=scale, soft_cap=soft_cap, block_k=block_k,
        )

    b, part, out = _sp_specs(axis, batch_axes)
    kv_spec = P(b, None, axis)                 # (B, Hkv, S[, D]) seq-sharded
    local_fn = jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(b), kv_spec, kv_spec, kv_spec, kv_spec, P(b)),
            out_specs=(P(part), P(part)),
            check_vma=False,
        )
    )
    merge_fn = jax.jit(
        jax.shard_map(
            functools.partial(_merge_shard_partials_lse, axis=axis),
            mesh=mesh,
            in_specs=(P(part), P(part)),
            out_specs=(P(out), P(out)),
            check_vma=False,
        )
    )
    return local_fn, merge_fn


def sp_gqa_fwd_batch_decode_q8(
    q, k_q, k_scale, v_q, v_scale, global_kv_lens, mesh, axis="x", *,
    scale=None, soft_cap=0.0, block_k=None, with_lse=False, batch_axes=(),
):
    """Host entry: sequence-parallel GQA decode over an INT8 KV cache.

    k_q/v_q: (B, Hkv, S, D) int8, k_scale/v_scale: (B, Hkv, S) f32 —
    all with S sharded over ``axis``; q and global_kv_lens replicated
    (batch dim sharded over ``batch_axes`` when given — the dp×tp
    serving layout). Returns (B, Hq, D) replicated (+ merged lse with
    ``with_lse``). Half the KV bytes of the bf16 entry both at rest
    and on the attention DMA stream.
    """
    local_fn, merge_fn = _sp_q8_fns(
        mesh, axis, scale, soft_cap, block_k, tuple(batch_axes), interp_key()
    )
    out, lse = local_fn(q, k_q, k_scale, v_q, v_scale, global_kv_lens)
    out, lse = merge_fn(out, lse)
    return (out, lse) if with_lse else out


def _local_paged_shard_decode_q8(
    q, k_pool, k_scale, v_pool, v_scale, global_kv_lens, block_table,
    axis, *, scale, soft_cap, interpret=None,
):
    """Rank-local INT8 paged decode over this rank's sequence slice."""
    r = jax.lax.axis_index(axis)
    page = k_pool.shape[2]
    s_loc = block_table.shape[1] * page
    local_lens = jnp.clip(
        global_kv_lens - r * s_loc, 0, s_loc
    ).astype(jnp.int32)
    return paged_gqa_fwd_batch_decode_q8(
        q, k_pool, k_scale, v_pool, v_scale, local_lens, block_table,
        scale=scale, soft_cap=soft_cap, interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _sp_paged_q8_fns(mesh, axis, scale, soft_cap, with_lse=False, ikey=()):
    """Jitted (local, merge) pair for the INT8 paged SP decode."""

    def local(q, kp, ks, vp, vs, lens, table):
        return _local_paged_shard_decode_q8(
            q, kp, ks, vp, vs, lens, table[0], axis,
            scale=scale, soft_cap=soft_cap,
        )

    local_fn = jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(axis), P(),
                      P(axis)),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )
    merge_fn = jax.jit(
        jax.shard_map(
            functools.partial(
                _merge_shard_partials_lse if with_lse
                else _merge_shard_partials,
                axis=axis,
            ),
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(), P()) if with_lse else P(),
            check_vma=False,
        )
    )
    return local_fn, merge_fn


def sp_paged_gqa_fwd_batch_decode_q8(
    q, k_pool, k_scale, v_pool, v_scale, global_kv_lens, block_table,
    mesh, axis="x", *, scale=None, soft_cap=0.0, with_lse=False,
):
    """Host entry: sequence-parallel INT8 PAGED GQA decode — the same
    per-rank pool/table contract as :func:`sp_paged_gqa_fwd_batch_decode`
    with int8 pools + (R·npages_local, Hkv, page) f32 scale pools, all
    sharded ``P(axis)`` on dim 0. ``with_lse``: also return the merged
    (B, Hq) lse so callers can fold further partials (a decode step's
    just-produced token, ``layers.SpGQAFlashDecodeAttention``)."""
    local_fn, merge_fn = _sp_paged_q8_fns(
        mesh, axis, scale, soft_cap, with_lse, interp_key()
    )
    out, lse = local_fn(
        q, k_pool, k_scale, v_pool, v_scale, global_kv_lens, block_table
    )
    return merge_fn(out, lse)


@functools.lru_cache(maxsize=64)
def _sp_paged_fns(mesh, axis, scale, soft_cap, use_pallas, with_lse=False,
                  ikey=()):
    """Jitted (local, merge) pair for the PAGED SP decode — split into
    two dispatches for the same interpreter-deadlock reason as
    :func:`_sp_decode_fns`."""

    def local(q, kp, vp, lens, table):
        return _local_paged_shard_decode(
            q, kp, vp, lens, table[0], axis,
            scale=scale, soft_cap=soft_cap, use_pallas=use_pallas,
        )

    local_fn = jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P(), P(axis)),
            out_specs=(P(axis), P(axis)),
            check_vma=False,
        )
    )
    merge_fn = jax.jit(
        jax.shard_map(
            functools.partial(
                _merge_shard_partials_lse if with_lse
                else _merge_shard_partials,
                axis=axis,
            ),
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(), P()) if with_lse else P(),
            check_vma=False,
        )
    )
    return local_fn, merge_fn


def sp_paged_gqa_fwd_batch_decode(
    q, k_pool, v_pool, global_kv_lens, block_table, mesh, axis="x", *,
    scale=None, soft_cap=0.0, use_pallas=True, with_lse=False,
):
    """Host entry: sequence-parallel PAGED GQA decode on ``mesh``.

    Each rank owns a page pool of its contiguous sequence slice and the
    table addressing it (≡ "each rank's kv shard's kv_table",
    sp_flash_decode_layer.py:78-84):

    * k_pool/v_pool: (R·npages_local, Hkv, page, D) sharded P(axis) on
      dim 0 — rank r's local pool is its shard.
    * block_table: (R, B, pages_per_slice) sharded P(axis), LOCAL page
      ids into each rank's own pool shard.
    * q, global_kv_lens replicated. Returns (B, Hq, D) replicated
      (+ the merged (B, Hq) lse with ``with_lse``).
    """
    local_fn, merge_fn = _sp_paged_fns(
        mesh, axis, scale, soft_cap, use_pallas, with_lse, interp_key()
    )
    out, lse = local_fn(q, k_pool, v_pool, global_kv_lens, block_table)
    return merge_fn(out, lse)
