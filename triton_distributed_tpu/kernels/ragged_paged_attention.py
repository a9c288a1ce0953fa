"""Ragged paged attention: mixed prefill-chunk + decode rows, ONE launch.

The continuous-batching serving engine (serving/engine.py) assembles a
per-step batch in which some requests contribute ONE decode token and
others contribute a CHUNK of prompt tokens — the "Ragged Paged
Attention" TPU kernel shape (arXiv:2604.15464, PAPERS.md): one kernel,
per-row ``(kv_len, q_len)`` metadata, per-request block tables into a
shared page pool, and NO rectangle padding — each row's KV walk is
``ceil(kv_len/page)`` pages of ITS true length, and each row's query
block is its true chunk (rounded to the sublane granule), packed into
one ragged token array.

Why not reuse the decode kernels (flash_decode.py): those are
one-query-per-row machines — ``q: (B, Hq, D)`` — so a prefill chunk
would need its own rectangle launch per step, which is exactly the
fixed-batch regime the engine exists to kill. This kernel walks BOTH
kinds of rows in one grid, so a step's cost is proportional to the
step's true token/KV volume regardless of the prefill/decode mix.

Layout contract (the "GQA-rows" packing):

* ``q``/``out``: ``(Hkv, T·G, D)`` — head-major, then token-major with
  the G query heads of one token adjacent. Row ``r``'s tokens occupy
  rows ``[q_starts[r]·G, (q_starts[r]+q_lens[r])·G)`` of dim 1. This
  makes each row's per-head query block ONE contiguous
  ``(block_q·G, D)`` DMA run — no in-kernel reshape that changes the
  lane dim (a construct this toolchain's Mosaic rejects; deny rule
  MC005). ``pack_gqa_rows`` / ``unpack_gqa_rows`` convert from/to the
  natural ``(T, Hq, D)``.
* ``q_starts`` must be 8-aligned token offsets (the engine packs rows
  at 8-token granularity — ragged, not rectangular: the pad between
  rows is < 8 tokens, not ``S - len``). 8 is all Mosaic asks: q/out
  are never int8, and an HBM bf16 array is tiled ``(8,128)(2,1)`` — 8
  rows per tile, pairs packed inside it (AOT-verified, jax 0.9.0).
* KV pools: ``(npages, Hkv, page, D)`` ["phsd"], int8 with
  ``(npages, Hkv, page)`` f32 scales (the serving default; compiled
  by Mosaic only at ``page % 128 == 0``) or bf16;
  ``block_table``: ``(R, pages_per_seq)`` pool page ids; ``kv_lens``:
  per-row TOTAL lengths INCLUDING this step's tokens (append-then-
  attend — the engine scatters the step's K/V into the pool first, so
  a chunk's tokens attend each other causally through the pool).
* Causality: token ``t`` of row ``r`` sits at global position
  ``kv_lens[r] - q_lens[r] + t`` and attends positions
  ``<= kv_lens[r] - q_lens[r] + t``. Decode rows (``q_lens[r] == 1``)
  degenerate to the flash-decode mask. Only FRONTIER pages (those
  crossing ``kv_len - q_len + 1``) pay the mask chain — interior pages
  run the unmasked fast path, the ``is_tail`` discipline of
  ``flash_decode._decode_kernel_dyn``.
* The ``block_q`` query block is a STATIC per-launch bound on
  ``max(q_lens)``; rows shorter than it over-read into the NEXT row's
  tokens and over-write garbage outputs there, which the ascending
  sequential grid self-heals (row r+1 re-writes its own rows after
  row r; the final row's tail needs ``q_starts[-1] + block_q <= T``
  of slack in the packed array — the CALLER's to give: the engine
  sizes each step's array to the largest such end over its batched
  rows, ``query_block_tokens`` says the block; a ``q_len == 0`` row
  needs none where ``topologies`` is given, its body is skipped).
  Out-DMAs are waited before the grid step ends so the self-heal
  ordering is real, not racy.

The kernel is LOCAL (no remote DMA): under tensor parallelism the
serving state shards the pools over the KV-HEAD dim (heads are
independent in GQA attention — no cross-rank LSE merge needed, unlike
the sequence-sharded decode path), so each rank runs this kernel on
its own head slice. It is registered in the kernel registry as the
``flash_decode.ragged_paged`` family with a ``local`` delivery
contract (every output element covered by locally computed writes, no
raw quantized bytes left) and covered by the Mosaic pre-flight.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.config import local_interpret
from triton_distributed_tpu.lang.launch import shmem_call
from triton_distributed_tpu.utils.testing import chaos_delay

NEG_INF = -1.0e30

#: lane width of the lse output's trailing dim (every lane carries the
#: same value; the wrapper reads lane 0)
LSE_LANES = 128

# ------------------------------------------------------- attention topology
#
# Per-row mask descriptor over the q×kv tile grid — the 5th scalar-
# prefetch operand (``topologies``). Row layout, ``W`` = descriptor
# width (ancestor-bitmask positions):
#
#   [kind, aux, anc[0..W-1], parent[0..W-1]]        (2 + 2·W) int32
#
# * ``kind``: TOPO_CAUSAL (today's causal-frontier mask — the default,
#   byte-identical outputs), TOPO_TREE (tree-speculation verify row:
#   ``anc[t]`` is the packed ancestor bitmask of q position ``t`` over
#   the row's speculative region ``[kv_len - q_len, kv_len)``, bit 0 =
#   the frontier token, self bit included — sibling branches never
#   attend each other), or TOPO_SHARED_PREFIX (positions below
#   ``aux = split`` tokens are a prefix page run ALIASED across rows'
#   block tables; the mask itself stays causal — the aliasing is a
#   table-level fact the engine's PagePool refcounts make safe), or
#   TOPO_CP (context-parallel KV shard: this row's pool walk covers one
#   cp rank's CONTIGUOUS slice of a longer global sequence, and the
#   causal frontier is shifted RIGHT by ``aux`` tokens — local position
#   ``p`` is visible to q token ``t`` iff
#   ``p < kv_len - q_len + t + 1 + aux`` AND ``p < kv_len``. The owner
#   shard (the one holding the frontier) runs ``aux = 0`` ≡ causal;
#   earlier, fully-covered shards run ``aux >= q_len`` and attend their
#   whole slice; a shard past the data runs ``kv_len = 0`` and masks
#   everything, so its LSE comes back NEG_INF and the cross-rank
#   LSE-combine weighs it zero).
# * ``aux``: TREE → occupied q positions (1 + draft nodes);
#   SHARED_PREFIX → the shared-prefix split in tokens; CP → the
#   frontier shift ``(global_kv - r·slice) - kv_len`` in tokens.
# * ``parent[t]``: q position of t's tree parent (-1 for the frontier)
#   — NOT read by the kernel (the anc bitmask is self-contained); it is
#   the analysis cross-check the masked-coverage SL008 facet validates
#   ``anc[t] == anc[parent[t]] | (1 << t)`` against, so a descriptor
#   that lets a TREE row attend a sibling branch cannot hide.
#
# The bitmask is int32, so a tree verify row carries at most
# TOPO_MAX_NODES q positions (bits 0..30 — bit 31 would overflow the
# signed lane).

TOPO_CAUSAL = 0
TOPO_TREE = 1
TOPO_SHARED_PREFIX = 2
TOPO_CP = 3
TOPO_MAX_NODES = 31


def topo_width(block_q: int) -> int:
    """Descriptor width for a ``block_q`` launch: one ancestor-bitmask
    slot per q position, capped at the int32 bitmask bound."""
    return min(int(block_q), TOPO_MAX_NODES)


def causal_topologies(r: int, width: int):
    """(R, 2+2W) all-CAUSAL descriptor block — the identity operand."""
    return np.zeros((r, 2 + 2 * width), np.int32)


def tree_topology_row(parents, width: int):
    """One TREE descriptor row from per-node parent indices.

    ``parents[i]`` is the parent DRAFT NODE of draft node ``i`` (-1 =
    the frontier token). q position 0 is the frontier; node ``i`` sits
    at q position ``i + 1``."""
    n = len(parents)
    if n + 1 > width:
        raise ValueError(
            f"tree of {n} nodes needs width >= {n + 1}, got {width}")
    row = np.zeros((2 + 2 * width,), np.int32)
    row[0] = TOPO_TREE
    row[1] = n + 1
    anc = np.zeros((width,), np.int64)
    par = np.full((width,), -1, np.int64)
    anc[0] = 1
    for i, p in enumerate(parents):
        t = i + 1
        pt = int(p) + 1
        if not 0 <= pt < t:
            raise ValueError(
                f"node {i}: parent {p} must be an earlier node or -1")
        anc[t] = anc[pt] | (np.int64(1) << t)
        par[t] = pt
    row[2:2 + width] = anc.astype(np.int32)
    row[2 + width:2 + 2 * width] = par.astype(np.int32)
    return row


def shared_prefix_topology_row(split: int, width: int):
    """One SHARED_PREFIX descriptor row (``split`` in tokens)."""
    row = np.zeros((2 + 2 * width,), np.int32)
    row[0] = TOPO_SHARED_PREFIX
    row[1] = int(split)
    return row


def cp_topology_row(shift: int, width: int):
    """One CP descriptor row. ``shift`` is the frontier shift in
    tokens: for cp rank r over a slice of ``s_loc`` positions serving a
    row at global length G, ``shift = max((G - r·s_loc) - kv_len, 0)``
    where ``kv_len = clip(G - r·s_loc, 0, s_loc)`` is the rank's local
    length — 0 on the shard that owns the frontier (pure causal),
    ``>= q_len`` on fully-covered earlier shards."""
    if shift < 0:
        raise ValueError(f"cp frontier shift must be >= 0, got {shift}")
    row = np.zeros((2 + 2 * width,), np.int32)
    row[0] = TOPO_CP
    row[1] = int(shift)
    return row


def _n_valid_pages(kv_len, page):
    """ceil(kv_len / page) floored at 1 (an empty row still walks one
    page; its scores are fully masked)."""
    return jnp.maximum(jax.lax.div(kv_len + page - 1, page), 1)


def _first_page(kv_len, q_len, page, pps, window):
    """First page of a sliding-window row's walk: the page of position
    ``kv_len - q_len - window + 1``, the oldest key the row's first
    query sees (0 while the window still reaches the sequence's start),
    kept inside the row's valid pages."""
    first_key = jnp.maximum(kv_len - q_len - window + 1, 0)
    last = jnp.minimum(_n_valid_pages(kv_len, page), pps) - 1
    return jnp.minimum(jax.lax.div(first_key, page), last)


def pack_gqa_rows(q, hkv):
    """(T, Hq, D) → (Hkv, T·G, D): the kernel's GQA-rows layout — one
    contiguous (q_len·G, D) run per (row, kv-head)."""
    t, hq, d = q.shape
    g = hq // hkv
    return q.reshape(t, hkv, g, d).transpose(1, 0, 2, 3).reshape(
        hkv, t * g, d
    )


def unpack_gqa_rows(o, hq):
    """(Hkv, T·G, D) → (T, Hq, D): inverse of :func:`pack_gqa_rows`."""
    hkv, tg, d = o.shape
    g = hq // hkv
    t = tg // g
    return o.reshape(hkv, t, g, d).transpose(1, 0, 2, 3).reshape(t, hq, d)


def _ragged_kernel(
    scale, soft_cap, page, n_bufs, hkv, g, d, block_q, quant, topo_w,
    with_lse, window, *refs,
):
    """Grid (R,): one request row per step; all local KV heads unrolled.

    Per row: a dynamic ``fori_loop`` over ``ceil(kv_len/page)`` pages
    with double-buffered table-indexed pool DMAs (the
    ``_paged_kernel_dyn_mh`` machinery), a per-row query block of
    ``block_q`` tokens DMA'd once (double-buffered across rows), and
    an online softmax whose state spans the row's ``block_q·G`` query
    rows per head. Slot rotation and the row-ahead prefetch ride an
    SMEM carry — SEQUENTIAL grid execution required (pinned via
    dimension_semantics).

    ``topo_w`` (static): 0 keeps the pre-topology kernel bit-for-bit
    (four scalar operands, every row causal, every row active); > 0
    adds the 5th scalar-prefetch topology operand of that descriptor
    width, the TREE ancestor-bitmask mask, and the ``q_len == 0`` row
    skip — inactive rows are hopped over by the cross-row q-prefetch
    (the prefetch targets the NEXT ACTIVE row, not ``r + 1``) and
    leave carries, buffers, and their stale out spans untouched.

    ``with_lse`` (static): False drops the lse output, its staging
    buffer and its DMA — the head-sharded serving step never reads it
    (only the cp shard merge does).

    ``window`` (static): None builds the kernel above bit for bit. An
    int makes every row a SLIDING-WINDOW row: query position ``i`` sees
    keys ``i - window < j <= i``. The page walk then starts at the
    first page that holds a key some valid query of the row can see
    (``_first_page``: the pages before it are never fetched), and a
    page that crosses the window's lower edge takes the masked path
    like a frontier page. Composes with CAUSAL descriptor rows (and
    their ``q_len == 0`` skip); the other topology kinds have no
    windowed meaning and the engine refuses them beside a window."""
    refs = iter(refs)

    def take(n):
        return [next(refs) for _ in range(n)]

    table_ref, kv_lens_ref, q_lens_ref, q_starts_ref = take(4)
    topo_ref = next(refs) if topo_w else None
    q_hbm, k_hbm, v_hbm = take(3)
    ks_hbm, vs_hbm = take(2) if quant else (None, None)
    out_hbm = next(refs)
    lse_hbm = next(refs) if with_lse else None
    qbuf, kbuf, vbuf = take(3)
    ksbuf, vsbuf = take(2) if quant else (None, None)
    obuf = next(refs)
    lbuf = next(refs) if with_lse else None
    sem_q, sem_k, sem_v = take(3)
    sem_ks, sem_vs = take(2) if quant else (None, None)
    sem_o, slot_ref, m_ref, l_ref, acc_ref = take(5)
    r = pl.program_id(0)
    nr = pl.num_programs(0)
    npages = k_hbm.shape[0]
    pps = table_ref.shape[1]
    nrows = table_ref.shape[0]
    rows = block_q * g

    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    nb = jnp.minimum(_n_valid_pages(kv_len, page), pps)

    def first_page(rr):
        # the first page of row rr's walk: 0, or under a window the
        # page of the oldest key its first query still sees
        if window is None:
            return 0
        return _first_page(kv_lens_ref[rr], q_lens_ref[rr], page, pps,
                           window)

    j0 = first_page(r)
    # pages walked before page j (no op at all without a window)
    walked = (lambda j: j) if window is None else (lambda j: j - j0)

    def dma(rr, j, slot):
        # row rr's j-th page; clamp so a prefetch into a short row's
        # padding never addresses out of pool (table pad entries incl.
        # -1 are clamped too)
        jc = jnp.minimum(
            j, jnp.maximum(_n_valid_pages(kv_lens_ref[rr], page) - 1, 0)
        )
        pid = jnp.clip(table_ref[rr, jc], 0, npages - 1)
        cps = [
            pltpu.make_async_copy(
                k_hbm.at[pid], kbuf.at[slot], sem_k.at[slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[pid], vbuf.at[slot], sem_v.at[slot]
            ),
        ]
        if quant:
            cps += [
                pltpu.make_async_copy(
                    ks_hbm.at[pid], ksbuf.at[slot], sem_ks.at[slot]
                ),
                pltpu.make_async_copy(
                    vs_hbm.at[pid], vsbuf.at[slot], sem_vs.at[slot]
                ),
            ]
        return cps

    def qdma(rr, qslot):
        # the row's whole query block, every local head, one strided
        # copy (hkv contiguous (rows, d) runs). Mosaic slices a tiled
        # HBM dim only at offsets it can PROVE tile-aligned: the
        # 8-aligned q_starts contract is that proof (deny rule MC008)
        start = pl.multiple_of(q_starts_ref[rr] * g, 8)
        return pltpu.make_async_copy(
            q_hbm.at[:, pl.ds(start, rows)], qbuf.at[qslot],
            sem_q.at[qslot],
        )

    if topo_w:
        # ---- q_len == 0 skip: the cross-row prefetch hop protocol ----
        # next_active(a): smallest active row index >= a (static unroll
        # over the R-sized scalar operand; nrows when none). The warmup
        # and the end-of-row prefetch both target the next ACTIVE row,
        # and an inactive row's entire body is skipped — its carries
        # pass through untouched, so the rotation the last active row
        # handed on still matches the buffers in flight.
        def next_active(after):
            na = jnp.int32(nrows)
            for rr in range(nrows - 1, -1, -1):
                na = jnp.where(
                    jnp.logical_and(rr >= after, q_lens_ref[rr] > 0),
                    jnp.int32(rr), na,
                )
            return na

        first_active = next_active(0)
        nxt_active = next_active(r + 1)
        nxt_clamped = jnp.minimum(nxt_active, nrows - 1)

        @pl.when(r == 0)
        def _warmup():
            slot_ref[0] = 0                   # KV slot rotation carry
            slot_ref[1] = 0                   # q double-buffer parity

            @pl.when(first_active < nr)
            def _start_first():
                fa = jnp.minimum(first_active, nrows - 1)
                qdma(fa, 0).start()
                for cp in dma(fa, first_page(fa), 0):
                    cp.start()
    else:
        @pl.when(r == 0)
        def _warmup():
            slot_ref[0] = 0                   # KV slot rotation carry
            slot_ref[1] = 0                   # q double-buffer parity
            qdma(0, 0).start()
            for cp in dma(0, first_page(0), 0):
                cp.start()

    def row_body():
        s0 = slot_ref[0]
        qslot = slot_ref[1]
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)
        qdma(r, qslot).wait()                 # warmed by the previous row

        # per-query-row causal limit: token t = row // g sits at global
        # position kv_len - q_len + t and may attend positions < limit =
        # that + 1. Rows past q_len (block padding) get limit > kv_len —
        # they attend whatever the pool holds and produce garbage the
        # packing contract discards (see module docstring).
        base = kv_len - q_len
        row_tok = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), g
        )
        limit = base + row_tok + 1            # (rows, 1)

        if topo_w:
            # the row's descriptor, materialized as per-query-row
            # columns via STATIC selects over the descriptor width —
            # the vector-indexed gather Mosaic rejects (MC006) is
            # exactly what this unroll avoids.
            kind = topo_ref[r, 0]
            aux = topo_ref[r, 1]
            anc_col = jnp.zeros((rows, 1), jnp.int32)
            for t in range(min(topo_w, block_q)):
                anc_col = jnp.where(
                    row_tok == t, topo_ref[r, 2 + t], anc_col
                )
            # every kind's mask is ONE form over two int32 limit
            # columns: valid = pos < hi  AND  (pos < lo  OR  anc bit).
            # CAUSAL / SHARED_PREFIX: lo = hi = limit (the bit term is
            # vacuous). CP: lo = hi = min(kv_len, limit + aux) — the
            # frontier shifted right by aux, clamped to the slice. TREE:
            # hi = kv_len, lo = base (everything below the speculative
            # region is causal-visible; inside it only ancestors are).
            # The kind select runs on the int32 columns: Mosaic cannot
            # legalize a select whose OPERANDS are i1 vectors (MC010).
            cp_lim = jnp.minimum(limit + aux, kv_len)
            flat = jnp.where(kind == TOPO_CP, cp_lim, limit)
            is_tree = kind == TOPO_TREE
            lim_hi = jnp.where(is_tree, kv_len, flat)
            lim_lo = jnp.where(is_tree, base, flat)

        def body(j, _):
            slot = jax.lax.rem(s0 + walked(j), n_bufs)
            nxt = jax.lax.rem(s0 + walked(j) + 1, n_bufs)

            @pl.when(j + 1 < nb)
            def _prefetch_in_row():
                for cp in dma(r, j + 1, nxt):
                    cp.start()

            if topo_w:
                @pl.when(jnp.logical_and(j + 1 == nb, nxt_active < nr))
                def _prefetch_next_row():
                    qdma(nxt_clamped, 1 - qslot).start()
                    for cp in dma(nxt_clamped, first_page(nxt_clamped),
                                  nxt):
                        cp.start()
            else:
                @pl.when(jnp.logical_and(j + 1 == nb, r + 1 < nr))
                def _prefetch_next_row():
                    qdma(r + 1, 1 - qslot).start()
                    for cp in dma(r + 1, first_page(r + 1), nxt):
                        cp.start()

            # chaos hook: widens the slot-rotation window between the
            # prefetch issues and this page's wait (the race-prone carry)
            chaos_delay(site="ragged_paged", step=None, me=None, n=None)
            for cp in dma(r, j, slot):
                cp.wait()

            # only pages crossing the causal frontier (or the length
            # tail) pay the mask chain; interior pages take the plain
            # path. TREE rows: the speculative region [base, kv_len) is
            # entirely frontier pages, so interior pages stay fast.
            is_frontier = (j + 1) * page > base + 1
            if window is not None:
                # ... or the window's lower edge: some valid query of
                # the row no longer sees this page's first key
                is_frontier = jnp.logical_or(
                    is_frontier, j * page < kv_len - window)

            def heads(masked):
                if masked:
                    pos = j * page + jax.lax.broadcasted_iota(
                        jnp.int32, (1, page), 1
                    )
                    if topo_w:
                        # bit t of anc[t'] set ⇔ position base + t is
                        # visible to query row t' (TREE rows only
                        # reach this term — see lim_lo above)
                        bit = jax.lax.shift_right_logical(
                            anc_col, jnp.clip(pos - base, 0, 31)
                        ) & 1
                        valid = jnp.logical_and(
                            pos < lim_hi,
                            jnp.logical_or(pos < lim_lo, bit > 0),
                        )                     # (rows, page)
                    else:
                        valid = pos < limit   # (rows, page)
                    if window is not None:
                        valid = jnp.logical_and(
                            valid, pos >= limit - window)
                for h in range(hkv):          # static unroll
                    q = qbuf[qslot, h]        # (rows, d)
                    k = kbuf[slot, h]
                    v = vbuf[slot, h]
                    if quant:
                        k = k.astype(jnp.bfloat16)
                        v = v.astype(jnp.bfloat16)
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale                 # (rows, page) f32
                    if quant:
                        s = s * ksbuf[slot, h]   # (1, page) — exact fold
                    if soft_cap > 0.0:
                        s = soft_cap * jnp.tanh(s / soft_cap)
                    if masked:
                        s = jnp.where(valid, s, NEG_INF)
                    lo, hi = h * rows, (h + 1) * rows
                    m = m_ref[lo:hi]
                    m_new = jnp.maximum(
                        m, jnp.max(s, axis=1, keepdims=True)
                    )
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new)
                    if masked:
                        # an all-masked row degenerates exp(s - m) to 1
                        p = jnp.where(valid, p, 0.0)
                    l_ref[lo:hi] = alpha * l_ref[lo:hi] + jnp.sum(
                        p, axis=1, keepdims=True
                    )
                    if quant:
                        pv = (p * vsbuf[slot, h]).astype(v.dtype)
                    else:
                        pv = p.astype(v.dtype)
                    acc_ref[lo:hi] = alpha * acc_ref[lo:hi] + jnp.dot(
                        pv, v, preferred_element_type=jnp.float32
                    )
                    m_ref[lo:hi] = m_new

            @pl.when(is_frontier)
            def _masked():
                heads(True)

            @pl.when(jnp.logical_not(is_frontier))
            def _plain():
                heads(False)

            return 0

        jax.lax.fori_loop(j0, nb, body, 0)
        slot_ref[0] = jax.lax.rem(s0 + walked(nb), n_bufs)  # hand the rotation on
        if topo_w:
            slot_ref[1] = jnp.where(nxt_active < nr, 1 - qslot, qslot)
        else:
            slot_ref[1] = jnp.where(r + 1 < nr, 1 - qslot, qslot)

        for h in range(hkv):
            lo, hi = h * rows, (h + 1) * rows
            l = l_ref[lo:hi]
            safe_l = jnp.where(l > 0.0, l, 1.0)
            obuf[h] = (acc_ref[lo:hi] / safe_l).astype(obuf.dtype)
            if with_lse:
                # lane-broadcast: a DMA slice must span whole 128-lane
                # tiles (a trailing dim of 1 is refused — MC009)
                lbuf[h] = jnp.broadcast_to(
                    jnp.where(
                        l > 0.0, m_ref[lo:hi] + jnp.log(safe_l),
                        jnp.full_like(l, NEG_INF)
                    ),
                    (rows, LSE_LANES),
                )
        start = pl.multiple_of(q_starts_ref[r] * g, 8)
        cps = [pltpu.make_async_copy(
            obuf, out_hbm.at[:, pl.ds(start, rows)], sem_o.at[0]
        )]
        if with_lse:
            cps.append(pltpu.make_async_copy(
                lbuf, lse_hbm.at[:, pl.ds(start, rows)], sem_o.at[1]
            ))
        for cp in cps:
            cp.start()
        # wait BEFORE the grid advances: overlapping rows' out regions
        # self-heal by write order, which async completions would break
        for cp in cps:
            cp.wait()

    if topo_w:
        @pl.when(q_len > 0)
        def _active_row():
            row_body()
    else:
        row_body()


@functools.lru_cache(maxsize=64)
def _build_ragged(
    r, pps, npages, t_tokens, hkv, g, d, page, block_q, q_dtype,
    quant, scale, soft_cap, n_bufs, interpret, token=(), topo_w=0,
    with_lse=True, window=None,
):
    """Construct the ragged-paged-attention pallas_call (lru-cached on
    the full static geometry; ``token`` busts the cache for lint/
    preflight builds). Returns the call taking
    ``(table, kv_lens, q_lens, q_starts[, topologies], q, k_pool,
    v_pool [, k_scale, v_scale])`` — the topology operand present iff
    ``topo_w > 0`` (its descriptor width; 0 = the pre-topology
    launch, bit-for-bit) — and returning ``[out, lse]`` (``[out]``
    without ``with_lse``); lse is ``(Hkv, T·G, LSE_LANES)``,
    lane-broadcast."""
    del token
    q_dtype = jnp.dtype(q_dtype)
    rows = block_q * g
    kernel = functools.partial(
        _ragged_kernel, scale, soft_cap, page, n_bufs, hkv, g, d,
        block_q, quant, topo_w, with_lse, window,
    )
    pool_dt = jnp.dtype(jnp.int8) if quant else q_dtype
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),    # q (head-major packed)
        pl.BlockSpec(memory_space=pl.ANY),    # k pool
        pl.BlockSpec(memory_space=pl.ANY),    # v pool
    ]
    scratch = [
        pltpu.VMEM((2, hkv, rows, d), q_dtype),          # qbuf
        pltpu.VMEM((n_bufs, hkv, page, d), pool_dt),     # kbuf
        pltpu.VMEM((n_bufs, hkv, page, d), pool_dt),     # vbuf
    ]
    sems = [
        pltpu.SemaphoreType.DMA((2,)),        # sem_q
        pltpu.SemaphoreType.DMA((n_bufs,)),   # sem_k
        pltpu.SemaphoreType.DMA((n_bufs,)),   # sem_v
    ]
    if quant:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),   # k scales
            pl.BlockSpec(memory_space=pl.ANY),   # v scales
        ]
        scratch += [
            pltpu.VMEM((n_bufs, hkv, 1, page), jnp.float32),  # ksbuf
            pltpu.VMEM((n_bufs, hkv, 1, page), jnp.float32),  # vsbuf
        ]
        sems += [
            pltpu.SemaphoreType.DMA((n_bufs,)),  # sem_ks
            pltpu.SemaphoreType.DMA((n_bufs,)),  # sem_vs
        ]
    scratch += [pltpu.VMEM((hkv, rows, d), q_dtype)]     # obuf
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]      # out
    out_shape = [jax.ShapeDtypeStruct((hkv, t_tokens * g, d), q_dtype)]
    if with_lse:
        scratch += [pltpu.VMEM((hkv, rows, LSE_LANES), jnp.float32)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)]
        out_shape += [jax.ShapeDtypeStruct(
            (hkv, t_tokens * g, LSE_LANES), jnp.float32
        )]
    sems += [pltpu.SemaphoreType.DMA((2,))]   # sem_o (out, lse)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # table, kv_lens, q_lens, starts [+ per-row topology]
        num_scalar_prefetch=5 if topo_w else 4,
        grid=(r,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch + sems + [
            pltpu.SMEM((2,), jnp.int32),                 # slot carries
            pltpu.VMEM((hkv * rows, 1), jnp.float32),    # m
            pltpu.VMEM((hkv * rows, 1), jnp.float32),    # l
            pltpu.VMEM((hkv * rows, d), jnp.float32),    # acc
        ],
    )
    # VMEM working set: the kv slot buffers + scale planes (one
    # (1, page) row pads to an 8-sublane tile) + q/out blocks +
    # softmax state (the (·, 1) m/l columns pad to full lanes) + the
    # lse staging block, with pipeline headroom
    kv_bytes = 2 * n_bufs * hkv * page * d * pool_dt.itemsize
    sc_bytes = 2 * n_bufs * hkv * 8 * page * 4 if quant else 0
    q_bytes = 3 * hkv * rows * d * q_dtype.itemsize
    st_bytes = hkv * rows * (d + 2 * 128) * 4
    if with_lse:
        st_bytes += hkv * rows * LSE_LANES * 4
    vmem_limit = None
    total = kv_bytes + sc_bytes + q_bytes + st_bytes
    if total > 12 * 1024 * 1024:
        vmem_limit = total + 8 * 1024 * 1024
    call = shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        collective_id=None,                   # purely local kernel
        vmem_limit_bytes=vmem_limit,
        interpret=local_interpret() if interpret is None else interpret,
        # a windowed launch has a name of its own: a profile tells the
        # sliding-window layers' kernel from the global layers'
        name="ragged_paged_attention"
        + ("" if window is None else f"_w{window}")
        + ("_q8" if quant else ""),
        # slot-rotation carries + cross-row prefetch + out self-heal
        # all require SEQUENTIAL grid execution
        dimension_semantics=("arbitrary",),
    )
    return call


# --------------------------------------------------------- selected walk
#
# ``selected``: block-sparse attention over a per-(row, KV head) list of
# pages. The selection (kernels/sparse_select.py) hands three arrays:
#
#   pages  (R, Hkv, W) int32   logical page indices of the row's walk,
#                              ascending, the first ``counts`` valid:
#                              the pages in which SOME query position
#                              of the row selected a block
#   counts (R, Hkv)    int32   pages walked (0 for a row not batched)
#   bits   (Hkv, T·G, SELECT_WORDS) int32
#                              per packed query row a bitmap over the
#                              sequence's blocks of ``select_block``
#                              tokens: bit ``b % 32`` of word ``b // 32``
#                              = the row's query position attends block
#                              ``b`` (the G heads of a token carry the
#                              same words)
#
# Key ``j`` is visible to a query at position ``i`` iff ``j <= i`` and
# bit ``j // select_block`` of the query's bitmap is set.
#
# A (row, KV head) pair is walked by the LENGTH of its row, three tiles
# in one launch (what a pair walks it also FETCHES and writes back: the
# query rows, their bitmap words, the out rows):
#
#   q_len == 1            ``select_token_rows`` tokens' G rows (ONE
#   (every decode row)    token's where G fills the query dtype's
#                         sublane tile) against a key block of
#                         ``SELECT_KV_PAGES`` listed pages an iteration:
#                         that many K and V copies into one buffer, one
#                         QK^T, one mask row (the token's own: built a
#                         page at a time from the page's index and its
#                         bitmap word), one softmax update, one PV
#   2 .. SELECT_SHORT     ``SELECT_SHORT`` tokens' rows against one page
#   (at block_q above it) an iteration
#   longer                the launch's ``block_q`` tokens' rows against
#                         one page an iteration
#
# Buffers: the key blocks' ``2 + n_bufs`` slots of ``SELECT_KV_PAGES``
# pages each — slots 0 / 1 hold the FIRST block of the pair of that
# parity, fetched with its query rows and bitmap words a whole pair
# ahead (under the walk of the pair before it); the rest are the ring a
# pair's further blocks go round, one block ahead. No state is carried
# from pair to pair but what those fetches left in the other parity's
# buffers.

#: int32 words of one query row's block bitmap (one lane tile)
SELECT_WORDS = 128
#: a row of 2 to this many tokens is walked (fetched, written back) as
#: a query block of this many tokens whatever the launch's ``block_q``;
#: a row of ONE token as ``select_token_rows`` tokens
SELECT_SHORT = 8
#: listed pages of one key block of a one-token row's walk (fetched
#: into one buffer, scored as one block)
SELECT_KV_PAGES = 4


def select_token_rows(g: int, q_dtype, block_q: int) -> int:
    """Tokens a one-token row of the selected walk is walked as: the
    fewest whose ``t · G`` query rows fill the dtype's sublane tile (8
    rows of 4 bytes, 16 of 2), never more than ``SELECT_SHORT`` or the
    launch's block."""
    tile = 8 * max(1, 4 // jnp.dtype(q_dtype).itemsize)
    return min(tile // math.gcd(g, tile), SELECT_SHORT, block_q)


def _selected_kernel(
    scale, page, n_bufs, hkv, g, d, block_q, block, tok, kb, *refs,
):
    """Grid (R·Hkv,): step ``i`` visits the i-th ACTIVE (row, KV head)
    pair of ``order`` (row-major, so rows are visited in ascending
    order and a short row's out block is healed by the next row's, as
    in ``_ragged_kernel``); steps past ``n_active`` do nothing. Per
    pair: the walk over ``pages[vr, :counts[vr]]`` by key blocks — one
    page, or ``kb`` pages for a row of one token (``tok`` tokens' rows:
    the layout notes above) — with table-indexed pool DMAs, every key
    masked causally and by block. A pair's FIRST key block, its query
    rows and their bitmap words are fetched a whole pair ahead (when
    the pair before it starts, at the size THIS pair walks) into the
    buffers of the pair's parity; its further blocks go round a ring
    of ``n_bufs`` slots, one block ahead."""
    (table_ref, kv_lens_ref, q_lens_ref, q_starts_ref, order_ref, n_ref,
     pages_ref, counts_ref, q_hbm, k_hbm, v_hbm, bits_hbm, out_hbm,
     qbuf, bbuf, kbuf, vbuf, obuf, sem_q, sem_b, sem_k, sem_v, sem_o,
     m_ref, l_ref, acc_ref) = refs
    i = pl.program_id(0)
    n_active = n_ref[0]
    npages = k_hbm.shape[0]
    rows = block_q * g
    bpp = page // block                       # blocks a page

    def pair(step):
        vr = order_ref[step]
        return vr, jax.lax.div(vr, hkv), jax.lax.rem(vr, hkv)

    def by_len(q_len, fn):
        """``fn(rows_c, token)`` under the one ``pl.when`` a row of
        ``q_len`` tokens falls in: the (static) query rows it walks,
        and whether as a row of ONE token (``kb`` listed pages an
        iteration) or a page an iteration."""
        pl.when(q_len == 1)(functools.partial(fn, tok * g, True))
        if block_q > SELECT_SHORT:
            # a row of at most SELECT_SHORT tokens (a prompt's tail, a
            # verify row) walks as a block of that many, not of block_q
            pl.when(jnp.logical_and(q_len > 1, q_len <= SELECT_SHORT))(
                functools.partial(fn, SELECT_SHORT * g, False))
            pl.when(q_len > SELECT_SHORT)(
                functools.partial(fn, rows, False))
        else:
            pl.when(q_len > 1)(functools.partial(fn, rows, False))

    def slot_of(parity, j):
        # the buffer of a pair's key block ``j``: its first in slot
        # ``parity`` (0 / 1: fetched a pair ahead), the rest round the
        # ring behind them
        return jnp.where(j == 0, parity, 2 + jax.lax.rem(j - 1, n_bufs))

    def part(u):
        # page ``u`` of a slot's buffer (static or traced ``u``)
        at = u * page
        return pl.ds(at if isinstance(u, int) else pl.multiple_of(at, page),
                     page)

    def kvdma(pr, at, slot, u=0):
        """The K and V copies of pair ``pr``'s ``at``-th listed page
        into page ``u`` of a slot (a page past the list's end is its
        last again: masked whole by the walk)."""
        vr, r, h = pr
        lp = pages_ref[vr, jnp.minimum(at, jnp.maximum(counts_ref[vr] - 1, 0))]
        pid = jnp.clip(table_ref[r, lp], 0, npages - 1)
        return [
            pltpu.make_async_copy(
                k_hbm.at[pid, h], kbuf.at[slot, part(u)], sem_k.at[slot, u]),
            pltpu.make_async_copy(
                v_hbm.at[pid, h], vbuf.at[slot, part(u)], sem_v.at[slot, u]),
        ]

    def each_page(fn):
        # a rolled loop: a step program traces (and lowers) one page's
        # copies, not ``kb`` of them, wherever a block starts or lands
        jax.lax.fori_loop(0, kb, lambda u, _: fn(u) or 0, 0)

    def start_block(pr, j, slot, token):
        """Start the copies of pair ``pr``'s key block ``j`` into a
        slot: a one-token row's listed pages ``[j·kb, (j+1)·kb)``, any
        other row's page ``j``."""
        def one(u):
            for cp in kvdma(pr, j * kb + u, slot, u):
                cp.start()

        if token:
            each_page(one)
        else:
            for cp in kvdma(pr, j, slot):
                cp.start()

    def wait_block(slot):
        """Wait for a one-token row's ``start_block`` into the slot:
        the same destinations and semaphores, no address of a source
        worked out again."""
        def one(u):
            pltpu.make_async_copy(
                k_hbm.at[0, 0], kbuf.at[slot, part(u)],
                sem_k.at[slot, u]).wait()
            pltpu.make_async_copy(
                v_hbm.at[0, 0], vbuf.at[slot, part(u)],
                sem_v.at[slot, u]).wait()

        each_page(one)

    def qdma(pr, parity, rows_c):
        _, r, h = pr
        start = pl.multiple_of(q_starts_ref[r] * g, 8)
        return [
            pltpu.make_async_copy(
                q_hbm.at[h, pl.ds(start, rows_c)],
                qbuf.at[parity, pl.ds(0, rows_c)], sem_q.at[parity]),
            pltpu.make_async_copy(
                bits_hbm.at[h, pl.ds(start, rows_c)],
                bbuf.at[parity, pl.ds(0, rows_c)], sem_b.at[parity]),
        ]

    def start_pair(step, parity):
        """Start what the pair of grid step ``step`` finds waiting: the
        query rows and bitmap words it will walk and its first key
        block, in the buffers of its parity."""
        pr = pair(step)

        def go(rows_c, token):
            for cp in qdma(pr, parity, rows_c):
                cp.start()
            start_block(pr, 0, parity, token)

        by_len(q_lens_ref[pr[1]], go)

    # the fetches of the NEXT pair run under the whole of this pair's
    # walk (the other parity's buffers are free since the pair before
    # this one ended); grid step 0 starts its own pair's first. One
    # rolled loop, so that a step program traces ``start_pair`` once
    jax.lax.fori_loop(
        jnp.where(i == 0, 0, i + 1), jnp.minimum(i + 2, n_active),
        lambda t, _: start_pair(t, jax.lax.rem(t, 2)) or 0, 0)

    @pl.when(i < n_active)
    def _pair():
        pr = vr, r, h = pair(i)
        parity = jax.lax.rem(i, 2)
        cnt = jnp.maximum(counts_ref[vr], 1)
        kv_len = kv_lens_ref[r]
        q_len = q_lens_ref[r]
        lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, SELECT_WORDS), 1)
        lane_p = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        lane_blk = jax.lax.div(lane_p, block)
        start = pl.multiple_of(q_starts_ref[r] * g, 8)

        def look_ahead(j, nblk, token):
            @pl.when(j + 1 < nblk)
            def _prefetch():
                start_block(pr, j + 1, slot_of(parity, j + 1), token)

        def write_out(rows_c):
            out = pltpu.make_async_copy(
                obuf.at[pl.ds(0, rows_c)],
                out_hbm.at[h, pl.ds(start, rows_c)], sem_o.at[0])
            out.start()
            # waited before the grid advances (the out self-heal's order)
            out.wait()

        def walk(rows_c):
            """The pair's walk over the first ``rows_c`` (static) rows
            of its query block, a page an iteration."""
            m_ref[:rows_c] = jnp.full((rows_c, 1), NEG_INF, jnp.float32)
            l_ref[:rows_c] = jnp.zeros((rows_c, 1), jnp.float32)
            acc_ref[:rows_c] = jnp.zeros((rows_c, d), jnp.float32)
            q = qbuf[parity, :rows_c]             # (rows_c, d)
            words = bbuf[parity, :rows_c]         # (rows_c, SELECT_WORDS)
            row_tok = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (rows_c, 1), 0), g)
            limit = kv_len - q_len + row_tok + 1  # (rows_c, 1)

            def body(j, _):
                slot = slot_of(parity, j)
                look_ahead(j, cnt, False)
                chaos_delay(site="ragged_paged", step=None, me=None, n=None)
                for cp in kvdma(pr, j, slot):
                    cp.wait()
                lp = pages_ref[vr, jnp.minimum(j, cnt - 1)]
                # the page's ``bpp`` bits of every query row: one word
                # of the row's bitmap, picked by a lane compare (no
                # gather)
                bit0 = lp * bpp
                word = jnp.sum(
                    jnp.where(lane_w == jax.lax.div(bit0, 32), words, 0),
                    axis=1, keepdims=True)        # (rows_c, 1)
                word = jax.lax.shift_right_logical(
                    word,
                    jnp.broadcast_to(jax.lax.rem(bit0, 32), word.shape))
                chosen = jnp.zeros((rows_c, page), jnp.int32)
                for b in range(bpp):              # static
                    chosen = jnp.where(
                        lane_blk == b,
                        jax.lax.shift_right_logical(
                            word, jnp.full_like(word, b)) & 1,
                        chosen)
                pos = lp * page + lane_p
                valid = jnp.logical_and(pos < limit, chosen > 0)
                s = jax.lax.dot_general(
                    q, kbuf[slot, :page], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                         # (rows_c, page) f32
                s = jnp.where(valid, s, NEG_INF)
                m = m_ref[:rows_c]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_ref[:rows_c] = alpha * l_ref[:rows_c] + jnp.sum(
                    p, axis=1, keepdims=True)
                v = vbuf[slot, :page]
                acc_ref[:rows_c] = alpha * acc_ref[:rows_c] + jnp.dot(
                    p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
                m_ref[:rows_c] = m_new
                return 0

            jax.lax.fori_loop(0, cnt, body, 0)
            l = l_ref[:rows_c]
            obuf[:rows_c] = (
                acc_ref[:rows_c] / jnp.where(l > 0.0, l, 1.0)
            ).astype(obuf.dtype)
            write_out(rows_c)

        def token_walk(rows_c):
            """The walk of a row of ONE token: its ``rows_c`` (static)
            query rows against ``kb`` listed pages an iteration. Every
            live row is the one token's, so the mask is ONE row: the
            token's bitmap words (row 0's) and its position
            ``kv_len - 1``."""
            nblk = jax.lax.div(cnt + kb - 1, kb)
            q = qbuf[parity, :rows_c]             # (rows_c, d)
            words = bbuf[parity, :1]              # (1, SELECT_WORDS)

            def body(j, carry):
                m, l, acc = carry
                slot = slot_of(parity, j)
                look_ahead(j, nblk, True)
                chaos_delay(site="ragged_paged", step=None, me=None, n=None)
                wait_block(slot)
                seen = []
                for u in range(kb):               # static: a page's lanes
                    at = j * kb + u
                    lp = pages_ref[vr, jnp.minimum(at, cnt - 1)]
                    bit0 = lp * bpp
                    word = jnp.sum(
                        jnp.where(lane_w == jax.lax.div(bit0, 32), words, 0),
                        axis=1, keepdims=True)    # (1, 1)
                    bit = jax.lax.shift_right_logical(
                        jnp.broadcast_to(word, (1, page)),
                        jax.lax.rem(bit0, 32) + lane_blk) & 1
                    # a page past the list's end (the last again) sees
                    # nothing
                    lim = jnp.where(at < cnt, kv_len, 0)
                    seen.append(jnp.where(lp * page + lane_p < lim, bit, 0))
                seen = jnp.concatenate(seen, axis=1)
                valid = jnp.broadcast_to(seen, (rows_c, kb * page)) > 0
                s = jax.lax.dot_general(
                    q, kbuf[slot], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                         # (rows_c, kb·page) f32
                s = jnp.where(valid, s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
                v = vbuf[slot]
                acc = alpha * acc + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                return m_new, l, acc

            _, l, acc = jax.lax.fori_loop(0, nblk, body, (
                jnp.full((rows_c, 1), NEG_INF, jnp.float32),
                jnp.zeros((rows_c, 1), jnp.float32),
                jnp.zeros((rows_c, d), jnp.float32)))
            obuf[:rows_c] = (
                acc / jnp.where(l > 0.0, l, 1.0)).astype(obuf.dtype)
            # the rest of the row's packing slot goes back as zeros: a
            # later layer's chunk form multiplies a padding token's
            # rows by 0 (kernels/lightning_attention.py), so they must
            # be finite, which rows nobody wrote need not be
            slot_rows = min(SELECT_SHORT, block_q) * g
            if slot_rows > rows_c:
                obuf[rows_c:slot_rows] = jnp.zeros(
                    (slot_rows - rows_c, d), obuf.dtype)
            write_out(slot_rows)

        def walk_pair(rows_c, token):
            for cp in qdma(pr, parity, rows_c):
                cp.wait()
            (token_walk if token else walk)(rows_c)

        by_len(q_len, walk_pair)


@functools.lru_cache(maxsize=64)
def _build_selected(
    r, pps, npages, t_tokens, hkv, g, d, page, block_q, block, width,
    q_dtype, scale, n_bufs, interpret,
):
    """The selected walk's pallas_call: takes ``(table, kv_lens, q_lens,
    q_starts, order, n_active, pages (R·Hkv, W), counts (R·Hkv,), q,
    k_pool, v_pool, bits)`` and returns ``[out]``."""
    q_dtype = jnp.dtype(q_dtype)
    rows = block_q * g
    tok, kb = select_token_rows(g, q_dtype, block_q), SELECT_KV_PAGES
    kernel = functools.partial(
        _selected_kernel, scale, page, n_bufs, hkv, g, d, block_q, block,
        tok, kb)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(r * hkv,),
        in_specs=[any_, any_, any_, any_],    # q, k pool, v pool, bits
        out_specs=[any_],
        scratch_shapes=[
            pltpu.VMEM((2, rows, d), q_dtype),               # qbuf
            pltpu.VMEM((2, rows, SELECT_WORDS), jnp.int32),  # bbuf
            # two first-block buffers (a pair's parity) + the ring
            pltpu.VMEM((2 + n_bufs, kb * page, d), q_dtype),  # kbuf
            pltpu.VMEM((2 + n_bufs, kb * page, d), q_dtype),  # vbuf
            pltpu.VMEM((rows, d), q_dtype),                  # obuf
            pltpu.SemaphoreType.DMA((2,)),                   # sem_q
            pltpu.SemaphoreType.DMA((2,)),                   # sem_b
            pltpu.SemaphoreType.DMA((2 + n_bufs, kb)),       # sem_k
            pltpu.SemaphoreType.DMA((2 + n_bufs, kb)),       # sem_v
            pltpu.SemaphoreType.DMA((1,)),                   # sem_o
            pltpu.VMEM((rows, 1), jnp.float32),              # m
            pltpu.VMEM((rows, 1), jnp.float32),              # l
            pltpu.VMEM((rows, d), jnp.float32),              # acc
        ],
    )
    # q/out blocks, the bitmap words, softmax state (the (·, 1) columns
    # pad to full lanes) and the score temporaries of the widest tile
    scores = max(rows * page, tok * g * kb * page)
    total = (3 * rows * d * q_dtype.itemsize + 2 * rows * SELECT_WORDS * 4
             + rows * (d + 2 * 128) * 4 + 6 * scores * 4
             + 2 * (2 + n_bufs) * kb * page * d * q_dtype.itemsize)
    return shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hkv, t_tokens * g, d), q_dtype)],
        collective_id=None,
        vmem_limit_bytes=(total + (8 << 20)) if total > (12 << 20) else None,
        interpret=local_interpret() if interpret is None else interpret,
        # a name of its own that a search for the kernel's still finds
        name="ragged_paged_attention_selected",
        dimension_semantics=("arbitrary",),
    )


# ----------------------------------------------------------- latent walk
#
# ``latent``: attention over a LATENT pool (multi-head latent attention,
# absorbed). The pool holds ONE entry a token for ALL the G query heads,
# ``(npages, 1, page, Dp)``: ``[c_kv (d_latent) | k_pe (d_rope) | zeros]``
# with ``Dp`` whole 128-lane tiles. A query row is ``[q_nope W_k^T
# (d_latent) | q_pe (d_rope) | zeros]``; its score against entry ``j``
# is their dot product over all ``Dp`` columns (the zero tail adds
# nothing) and its value is the entry's first ``d_latent`` columns. The
# caller applies W_kvb's value part to the output.
#
# G is the model's head count (128 at the published size): ONE token's
# heads already fill an MXU tile, so the query block is cut by TOKENS,
# not by the launch's ``block_q``: a decode row (``q_len == 1``) walks
# as a block of G rows, any other row as ``ceil(q_len / LATENT_TQ)``
# blocks of ``LATENT_TQ · G`` rows, each walking the pages up to its own
# last position. A block of ``block_q · G`` rows would be 32768 x Dp at
# the chunk rung (42 MB) and would give a decode row 8 x its work.

#: tokens of one query block of a row with more than one token
LATENT_TQ = 8
#: keys scored at a time: this many tokens of consecutive pages are
#: fetched into one buffer and multiplied as one block
LATENT_KV_BLOCK = 512


def _latent_kernel(
    scale, page, n_bufs, g, dl, kb, tq, *refs,
):
    """Grid (R,): one request row a step; a row outside the batch
    (``q_len == 0``) does nothing. Each query block starts its own
    fetches (no state is carried from block to block or row to row)
    and waits for its output before the next begins."""
    (table_ref, kv_lens_ref, q_lens_ref, q_starts_ref, q_hbm, k_hbm,
     out_hbm, qbuf, kbuf, obuf, sem_q, sem_k, sem_o, m_ref, l_ref,
     acc_ref) = refs
    r = pl.program_id(0)
    npages = k_hbm.shape[0]
    pps = table_ref.shape[1]
    kbp = kb * page
    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    q_start = q_starts_ref[r]
    last_page = jnp.minimum(_n_valid_pages(kv_len, page), pps) - 1

    def fetch(j, slot):
        """The copies of key block ``j`` (pages ``[j·kb, (j+1)·kb)``; a
        page past the row's last is its last again, masked by its
        positions)."""
        cps = []
        for u in range(kb):
            lp = jnp.minimum(j * kb + u, last_page)
            pid = jnp.clip(table_ref[r, lp], 0, npages - 1)
            cps.append(pltpu.make_async_copy(
                k_hbm.at[pid], kbuf.at[slot, pl.ds(u * page, page)],
                sem_k.at[slot, u]))
        return cps

    def walk(n_tok, i):
        """Query block ``i`` of the row: ``n_tok`` (static) tokens from
        the row's token ``i · n_tok`` on."""
        rows = n_tok * g
        first = i * n_tok
        at = (q_start + first) * g
        if g % 8 == 0:
            at = pl.multiple_of(at, 8)
        base = kv_len - q_len + first         # position of the first token
        nblk = jnp.maximum(
            jax.lax.div(jnp.minimum(base + n_tok, kv_len) + kbp - 1, kbp),
            1)
        q_in = pltpu.make_async_copy(
            q_hbm.at[pl.ds(at, rows)], qbuf.at[pl.ds(0, rows)],
            sem_q.at[0])
        q_in.start()
        for cp in fetch(0, 0):
            cp.start()
        m_ref[:rows] = jnp.full((rows, 1), NEG_INF, jnp.float32)
        l_ref[:rows] = jnp.zeros((rows, 1), jnp.float32)
        acc_ref[:rows] = jnp.zeros((rows, dl), jnp.float32)
        limit = base + 1 + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), g)
        q_in.wait()
        q = qbuf[:rows]                       # (rows, Dp)

        def body(j, _):
            slot = jax.lax.rem(j, n_bufs)

            @pl.when(j + 1 < nblk)
            def _prefetch():
                for cp in fetch(j + 1, jax.lax.rem(j + 1, n_bufs)):
                    cp.start()

            chaos_delay(site="ragged_paged", step=None, me=None, n=None)
            for cp in fetch(j, slot):
                cp.wait()

            def block(masked):
                k = kbuf[slot]                # (kbp, Dp)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                     # (rows, kbp) f32
                if masked:
                    valid = j * kbp + jax.lax.broadcasted_iota(
                        jnp.int32, (1, kbp), 1) < limit
                    s = jnp.where(valid, s, NEG_INF)
                m = m_ref[:rows]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                if masked:
                    p = jnp.where(valid, p, 0.0)
                l_ref[:rows] = alpha * l_ref[:rows] + jnp.sum(
                    p, axis=1, keepdims=True)
                acc_ref[:rows] = alpha * acc_ref[:rows] + jnp.dot(
                    p.astype(k.dtype), k[:, :dl],
                    preferred_element_type=jnp.float32)
                m_ref[:rows] = m_new

            # only a block that reaches past the first token's own
            # position (or the row's length) pays the mask
            frontier = (j + 1) * kbp > base + 1
            pl.when(frontier)(functools.partial(block, True))
            pl.when(jnp.logical_not(frontier))(
                functools.partial(block, False))
            return 0

        jax.lax.fori_loop(0, nblk, body, 0)
        l = l_ref[:rows]
        obuf[:rows] = (
            acc_ref[:rows] / jnp.where(l > 0.0, l, 1.0)).astype(obuf.dtype)
        out = pltpu.make_async_copy(
            obuf.at[pl.ds(0, rows)], out_hbm.at[pl.ds(at, rows)],
            sem_o.at[0])
        out.start()
        out.wait()

    @pl.when(q_len == 1)
    def _decode_row():
        walk(1, 0)

    @pl.when(q_len > 1)
    def _chunk_row():
        def one(i, _):
            walk(tq, i)
            return 0

        jax.lax.fori_loop(0, jax.lax.div(q_len + tq - 1, tq), one, 0)


@functools.lru_cache(maxsize=64)
def _build_latent(
    r, pps, npages, t_tokens, g, dp, dl, page, q_dtype, scale, n_bufs,
    interpret,
):
    """The latent walk's pallas_call: takes ``(table, kv_lens, q_lens,
    q_starts, q (T·G, Dp), pool (npages, page, Dp))`` and returns
    ``[out (T·G, d_latent)]``."""
    q_dtype = jnp.dtype(q_dtype)
    kb = max(1, LATENT_KV_BLOCK // page)
    tq = LATENT_TQ
    rows = tq * g
    kernel = functools.partial(
        _latent_kernel, scale, page, n_bufs, g, dl, kb, tq)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=[any_, any_],                # q, latent pool
        out_specs=[any_],
        scratch_shapes=[
            pltpu.VMEM((rows, dp), q_dtype),                 # qbuf
            pltpu.VMEM((n_bufs, kb * page, dp), q_dtype),    # kbuf
            pltpu.VMEM((rows, dl), q_dtype),                 # obuf
            pltpu.SemaphoreType.DMA((1,)),                   # sem_q
            pltpu.SemaphoreType.DMA((n_bufs, kb)),           # sem_k
            pltpu.SemaphoreType.DMA((1,)),                   # sem_o
            pltpu.VMEM((rows, 1), jnp.float32),              # m
            pltpu.VMEM((rows, 1), jnp.float32),              # l
            pltpu.VMEM((rows, dl), jnp.float32),             # acc
        ],
    )
    # q/out blocks, the key buffers, softmax state (the (·, 1) columns
    # pad to full lanes) and the (rows, kv block) score temporaries
    total = (rows * (dp + dl) * q_dtype.itemsize
             + n_bufs * kb * page * dp * q_dtype.itemsize
             + rows * (dl + 2 * 128) * 4 + 4 * rows * kb * page * 4)
    return shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t_tokens * g, dl), q_dtype)],
        collective_id=None,
        vmem_limit_bytes=(total + (8 << 20)) if total > (12 << 20) else None,
        interpret=local_interpret() if interpret is None else interpret,
        # a name of its own that a search for the kernel's still finds
        name="ragged_paged_attention_latent",
        # no state is carried from row to row
        dimension_semantics=("arbitrary",),
    )


def _check_latent(latent, dp, k_scale, v_pool, window, selected,
                  topologies, with_lse, soft_cap):
    """What the latent walk is built for, refused by name."""
    dl, dr = (int(x) for x in latent)
    if dl < 1 or dr < 0 or dl + dr > dp:
        raise ValueError(
            f"ragged_paged_attention: latent=({dl}, {dr}) does not fit "
            f"the pool's {dp} columns")
    for name, on in (("int8 pools (k_scale)", k_scale is not None),
                     ("a V pool", v_pool is not None),
                     ("window", window is not None),
                     ("selected", selected is not None),
                     ("topologies", topologies is not None),
                     ("with_lse", bool(with_lse)),
                     ("soft_cap", soft_cap > 0.0)):
        if on:
            raise ValueError(
                f"ragged_paged_attention: latent with {name} is not "
                "built")
    return dl


def active_rows(q_lens):
    """``(order (R,), n (1,))``: the rows with ``q_lens > 0`` in
    ascending order, the rest of ``order`` repeating the last of them
    (a grid step past ``n`` then re-visits a block it already holds:
    no fetch, no write)."""
    r = q_lens.shape[0]
    on = q_lens > 0
    n = jnp.sum(on.astype(jnp.int32))
    order = jnp.sort(jnp.where(on, jnp.arange(r, dtype=jnp.int32), r))
    last = order[jnp.maximum(n - 1, 0)]
    order = jnp.where(jnp.arange(r) < n, order, jnp.where(n > 0, last, 0))
    return order.astype(jnp.int32), n.reshape(1).astype(jnp.int32)


def query_block_tokens(q_lens, block_q: int, *, latent: bool = False):
    """Host side: per row, the packed tokens from ``q_starts[r]`` on
    that a launch at ``block_q`` MOVES for that row (its query block
    in, its out block back), so that ``q_starts[r] + this <= T`` is the
    whole of what the launch asks of the packed width; 0 for a row
    outside the batch (``q_lens == 0``: with ``topologies`` the
    contiguous walk skips its body, the other two never visit it). The
    contiguous walk moves the launch's ``block_q`` for every row it
    visits; the latent walk (``latent``) cuts a row into blocks of
    ``LATENT_TQ`` tokens and moves a decode row's one. The selected
    walk moves LESS than this says for a short row — a row of one token
    fetches ``select_token_rows`` tokens (one where G fills a sublane
    tile) and writes its 8-token packing slot back, a row of 2 to
    ``SELECT_SHORT`` tokens fetches and writes ``SELECT_SHORT`` — and
    ``block_q`` for a longer one: for it this is a safe over-estimate,
    not narrowed (the engine's widths were sized by it)."""
    q_lens = np.asarray(q_lens)
    if latent:
        block = np.where(q_lens == 1, 1, -(-q_lens // LATENT_TQ) * LATENT_TQ)
    else:
        block = np.full_like(q_lens, block_q)
    return np.where(q_lens > 0, block, 0)


def auto_block_q(max_q_len: int, g: int) -> int:
    """Smallest block from the {8, 16, 32, 64, 128, ...} ladder covering
    ``max_q_len`` whose GQA row count (block·G) is sublane-aligned —
    keeping the jit/kernel cache bounded while decode-dominated steps
    don't pay a prefill-sized MXU block."""
    b = 8
    while b < max_q_len:
        b *= 2
    while (b * g) % 8:
        b *= 2
    return b


@functools.partial(
    jax.jit,
    static_argnames=("group", "scale", "soft_cap", "block_q", "n_bufs",
                     "with_lse", "interpret", "window", "select_block",
                     "latent"),
)
def ragged_paged_attention(
    q, k_pool, v_pool, kv_lens, q_lens, q_starts, block_table, *,
    group: int, topologies=None, k_scale=None, v_scale=None,
    scale: float | None = None, soft_cap: float = 0.0, block_q: int = 8,
    n_bufs: int = 2, with_lse: bool = True, interpret=None,
    window: int | None = None, selected=None, select_block: int = 0,
    latent: tuple | None = None,
):
    """Mixed prefill-chunk/decode attention over a shared page pool.

    q: (Hkv, T·G, D) packed GQA rows (:func:`pack_gqa_rows`) with
    ``group`` = G = Hq // Hkv (not recoverable from the packed shape);
    k_pool/v_pool: (npages, Hkv, page, D) — int8 when ``k_scale``/
    ``v_scale`` ((npages, Hkv, page) f32) are given, else q.dtype;
    kv_lens/q_lens/q_starts: (R,) int32 per-row metadata (lengths
    INCLUDE this step's tokens; starts are 8-aligned token offsets
    with ``q_starts[r] + block_q <= T`` slack for every row);
    block_table: (R, pages_per_seq) int32 pool page ids. ``block_q``:
    static bound on max(q_lens) (see :func:`auto_block_q`).

    ``topologies``: optional (R, 2+2W) int32 per-row attention-topology
    descriptors (see the module-level layout notes) — None keeps the
    pre-topology launch bit-for-bit. When given, TREE rows mask by
    ancestor bitmask, SHARED_PREFIX rows read aliased prefix pages
    through their (deduplicated) block tables, and ``q_len == 0`` rows
    are skipped by the cross-row prefetch hop.

    ``window`` (static): None is full causal attention, today's launch
    bit for bit. An int is sliding-window attention: query position
    ``i`` sees keys ``i - window < j <= i``, and each row's walk skips
    the pages below its window (``block_table`` may then be a ring: a
    page's id is only read while the page is walked). Descriptor rows
    beside a window must be CAUSAL.

    ``selected``: None is the contiguous walk above, today's launch bit
    for bit. ``(pages, counts, bits)`` (the layout notes above
    ``_selected_kernel``) is BLOCK-SPARSE attention with blocks of
    ``select_block`` tokens: each (row, KV head) walks its listed pages
    only, and a key is visible where it is causal AND its block's bit
    is set in the query row's bitmap. A launch of its own
    (``ragged_paged_attention_selected``); bf16/f32 pools, no lse, no
    window, CAUSAL rows only (``topologies`` is not read).

    ``latent`` (static): None is attention over K and V pools, today's
    launch bit for bit. ``(d_latent, d_rope)`` is ABSORBED latent
    attention (the layout notes above ``_latent_kernel``): ``k_pool``
    ``(npages, 1, page, Dp)`` holds one entry a token for all ``group``
    heads, ``v_pool`` is None, ``q`` is ``(1, T·G, Dp)`` and the result
    ``(1, T·G, d_latent)``; ``scale`` is the caller's (the model's
    softmax scale is not ``Dp``'s). A launch of its own
    (``ragged_paged_attention_latent``) whose query blocks are cut by
    tokens whatever ``block_q``; bf16/f32 pools, no lse, CAUSAL rows
    only.

    Returns (out (Hkv, T·G, D) in q.dtype, lse (Hkv, T·G) f32 — None
    without ``with_lse``, which also drops the kernel's lse writes).
    Rows of dim 1 outside the per-row valid spans hold garbage (the
    packing contract; see the module docstring).
    """
    hkv, tg, d = q.shape
    g = group
    npages, _, page, _ = k_pool.shape
    if latent is not None:
        dl = _check_latent(latent, d, k_scale, v_pool, window, selected,
                           topologies, with_lse, soft_cap)
        if scale is None:
            raise ValueError(
                "ragged_paged_attention: latent needs scale= (the "
                "model's softmax scale)")
        r, pps = block_table.shape
        if g % 8 and not local_interpret(interpret):
            raise ValueError(
                f"ragged_paged_attention: latent needs group={g} to be "
                "sublane-aligned (a multiple of 8) under Mosaic")
        call = _build_latent(
            r, pps, npages, tg // g, g, d, dl, page,
            jnp.dtype(q.dtype).name, float(scale), n_bufs, interpret)
        (out,) = call(
            block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
            q_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
            q[0], k_pool[:, 0])
        return out[None], None
    if selected is not None:
        pages, counts, bits = selected
        r, pps = block_table.shape
        _check_selected(page, select_block, k_scale, window, with_lse,
                        soft_cap)
        if (block_q * g) % 8:
            raise ValueError(
                f"ragged_paged_attention: block_q·G = {block_q * g} must "
                "be sublane-aligned (multiple of 8)")
        call = _build_selected(
            r, pps, npages, tg // g, hkv, g, d, page, block_q,
            int(select_block), int(pages.shape[-1]),
            jnp.dtype(q.dtype).name,
            float(1.0 / math.sqrt(d) if scale is None else scale),
            n_bufs, interpret,
        )
        # the (row, KV head) pairs ``r·Hkv + h`` of the batched rows
        order, n_active = active_rows(jnp.repeat(q_lens, hkv))
        (out,) = call(
            block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
            q_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
            order, n_active,
            pages.astype(jnp.int32).reshape(r * hkv, -1),
            counts.astype(jnp.int32).reshape(r * hkv),
            q, k_pool, v_pool, bits.astype(jnp.int32),
        )
        return out, None
    assert v_pool.shape == k_pool.shape, (k_pool.shape, v_pool.shape)
    assert tg % g == 0, (tg, g)
    t_tokens = tg // g
    r, pps = block_table.shape
    quant = k_scale is not None
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if (block_q * g) % 8:
        raise ValueError(
            f"ragged_paged_attention: block_q·G = {block_q * g} must be "
            "sublane-aligned (multiple of 8) — pick block_q via "
            "auto_block_q"
        )
    if quant and page % 128 and not local_interpret(interpret):
        raise ValueError(
            f"ragged_paged_attention: int8 pools need page % 128 == 0 "
            f"under Mosaic (got page={page}) — the (1, page) scale "
            "plane of one page is a DMA window and must span whole "
            "128-lane tiles"
        )
    topo_w = 0
    if topologies is not None:
        tr, tw = topologies.shape
        topo_w = (tw - 2) // 2
        if tr != r or tw != 2 + 2 * topo_w or not (
            1 <= topo_w <= TOPO_MAX_NODES
        ):
            raise ValueError(
                f"ragged_paged_attention: topologies shape {(tr, tw)} "
                f"must be (R={r}, 2+2·W) with 1 <= W <= {TOPO_MAX_NODES}"
            )
    if window is not None and int(window) < 1:
        raise ValueError(
            f"ragged_paged_attention: window must be >= 1, got {window}")
    call = _build_ragged(
        r, pps, npages, t_tokens, hkv, g, d, page, block_q,
        jnp.dtype(q.dtype).name, quant, float(scale), float(soft_cap),
        n_bufs, interpret, (), topo_w, with_lse,
        None if window is None else int(window),
    )
    args = [
        block_table.astype(jnp.int32), kv_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
    ]
    if topo_w:
        args.append(topologies.astype(jnp.int32))
    args += [q, k_pool, v_pool]
    if quant:
        args += [
            k_scale.astype(jnp.float32).reshape(npages, hkv, 1, page),
            v_scale.astype(jnp.float32).reshape(npages, hkv, 1, page),
        ]
    if not with_lse:
        (out,) = call(*args)
        return out, None
    out, lse = call(*args)
    return out, lse[..., 0]


def _check_selected(page, block, k_scale, window, with_lse, soft_cap):
    """What the selected walk is built for, refused by name."""
    if block < 1 or page % block or 32 % (page // block):
        raise ValueError(
            f"ragged_paged_attention: selected needs select_block >= 1 "
            f"dividing the page into a power-of-two number of blocks <= "
            f"32 (got select_block={block}, page={page})")
    for name, on in (("int8 pools (k_scale)", k_scale is not None),
                     ("window", window is not None),
                     ("with_lse", bool(with_lse)),
                     ("soft_cap", soft_cap > 0.0)):
        if on:
            raise ValueError(
                f"ragged_paged_attention: selected with {name} is not "
                "built")


def selected_bits(chosen, group: int):
    """``chosen`` (T, Hkv, NB) bool, block ``b`` attended by token
    ``t``'s queries of KV head ``h`` -> the kernel's bitmap
    ``(Hkv, T·G, SELECT_WORDS)`` int32 (``NB <= 32 · SELECT_WORDS``)."""
    t, hkv, nb = chosen.shape
    cap = 32 * SELECT_WORDS
    if nb > cap:
        raise ValueError(
            f"selected_bits: {nb} blocks a sequence, the bitmap holds "
            f"{cap}")
    c = jnp.pad(chosen, ((0, 0), (0, 0), (0, cap - nb)))
    c = c.reshape(t, hkv, SELECT_WORDS, 32).astype(jnp.uint32)
    words = jnp.sum(c << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    words = jnp.broadcast_to(
        words.transpose(1, 0, 2)[:, :, None, :],
        (hkv, t, group, SELECT_WORDS))
    return words.reshape(hkv, t * group, SELECT_WORDS)


def ragged_paged_attention_xla(
    q, k_pool, v_pool, kv_lens, q_lens, q_starts, block_table, *,
    group: int, topologies=None, k_scale=None, v_scale=None, scale=None,
    soft_cap=0.0, window=None, selected=None, select_block: int = 0,
    latent=None,
):
    """Dense-XLA twin (correctness reference + degradation target):
    gather each row's pages into a contiguous cache and run the masked
    dense attention with the same causal-frontier semantics — including
    the per-row topology operand (TREE ancestor-bitmask masks; CAUSAL
    and SHARED_PREFIX rows mask causally). Same signature/garbage-rows
    contract as :func:`ragged_paged_attention`. With ``selected`` the
    same block mask from the same bitmap (the page list is the
    kernel's to walk: the twin gathers every page). With ``latent``
    the same scores over the entries' columns and the values their
    first ``d_latent``.
    """
    hkv, tg, d = q.shape
    g = group
    t_tokens = tg // g
    npages, _, page, _ = k_pool.shape
    r, pps = block_table.shape
    if latent is not None:
        dl = _check_latent(latent, d, k_scale, v_pool, window, selected,
                           topologies, False, soft_cap)
        v_pool = k_pool[..., :dl]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if selected is not None:
        _check_selected(page, select_block, k_scale, window, False,
                        soft_cap)
        topologies = None
    if k_scale is not None:
        k_pool = (k_pool.astype(jnp.float32)
                  * k_scale[..., None]).astype(q.dtype)
        v_pool = (v_pool.astype(jnp.float32)
                  * v_scale[..., None]).astype(q.dtype)
    safe = jnp.clip(block_table.astype(jnp.int32), 0, npages - 1)
    # (R, pps, Hkv, page, D) → (R, Hkv, pps·page, D)
    kc = k_pool[safe].transpose(0, 2, 1, 3, 4).reshape(r, hkv, -1, d)
    vc = v_pool[safe].transpose(0, 2, 1, 3, 4).reshape(
        r, hkv, -1, v_pool.shape[-1])
    s_cap = pps * page

    # token t of the packed array belongs to row rt with position
    # pt = kv_len[rt] - q_len[rt] + (t - q_start[rt]); tokens outside
    # every row's span keep row -1 (their outputs are garbage anyway —
    # compute them against row 0 with a full mask)
    tok = jnp.arange(t_tokens)
    row_of = jnp.full((t_tokens,), -1, jnp.int32)
    for rr in range(r):
        inside = (tok >= q_starts[rr]) & (tok < q_starts[rr] + q_lens[rr])
        row_of = jnp.where(inside, rr, row_of)
    row_c = jnp.clip(row_of, 0, r - 1)
    t_in_row = tok - q_starts[row_c]
    limit = jnp.where(
        row_of >= 0,
        kv_lens[row_c] - q_lens[row_c] + t_in_row + 1,
        0,
    )                                          # (T,)

    qg = q.reshape(hkv, t_tokens, g, d).astype(jnp.float32)
    kt = kc[row_c].astype(jnp.float32)         # (T, Hkv, S, D)
    vt = vc[row_c].astype(jnp.float32)
    s = jnp.einsum("htgd,thsd->htgs", qg, kt) * scale
    if soft_cap > 0.0:
        s = soft_cap * jnp.tanh(s / soft_cap)
    pos_s = jnp.arange(s_cap)
    ok = pos_s[None, :] < limit[:, None]       # (T, S) causal
    if window is not None:
        ok = ok & (pos_s[None, :] >= (limit - window)[:, None])
    if topologies is not None:
        topologies = jnp.asarray(topologies, jnp.int32)
        w = (topologies.shape[1] - 2) // 2
        kind_t = topologies[row_c, 0]          # (T,)
        anc_t = topologies[row_c, 2 + jnp.clip(t_in_row, 0, w - 1)]
        base_t = kv_lens[row_c] - q_lens[row_c]
        rel = pos_s[None, :] - base_t[:, None]             # (T, S)
        bit = jnp.right_shift(anc_t[:, None], jnp.clip(rel, 0, 31)) & 1
        tree_ok = (pos_s[None, :] < kv_lens[row_c][:, None]) & (
            (rel < 0) | (bit > 0)
        )
        ok = jnp.where(
            ((kind_t == TOPO_TREE) & (row_of >= 0))[:, None],
            tree_ok, ok,
        )
        aux_t = topologies[row_c, 1]           # (T,) cp frontier shift
        cp_ok = (pos_s[None, :] < kv_lens[row_c][:, None]) & (
            pos_s[None, :] < (limit + aux_t)[:, None]
        )
        ok = jnp.where(
            ((kind_t == TOPO_CP) & (row_of >= 0))[:, None],
            cp_ok, ok,
        )
    mask = ok[None, :, None, :]
    if selected is not None:
        # bit b of a token's bitmap: its queries attend block b
        words = selected[2].reshape(hkv, t_tokens, g, -1)[:, :, 0]
        blk = pos_s // select_block                          # (S,)
        at = words[:, :, blk // 32]                          # (H, T, S)
        bit = jax.lax.shift_right_logical(
            at, jnp.broadcast_to((blk % 32).astype(jnp.int32), at.shape)
        ) & 1
        mask = mask & (bit > 0)[:, :, None, :]               # (H, T, 1, S)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("htgs,thsd->htgd", p / jnp.maximum(l, 1e-30), vt)
    lse = jnp.where(
        l[..., 0] > 0,
        m[..., 0] + jnp.log(jnp.maximum(l[..., 0], 1e-30)),
        NEG_INF,
    )
    return (
        out.reshape(hkv, tg, -1).astype(q.dtype),
        lse.reshape(hkv, tg),
    )


# ------------------------------------------------------------ lint geometry
#
# The registry family builds the kernel at this small fixed geometry:
# 2 rows, 1-page walks, G=1, 8-token blocks packed with ZERO slack
# (q_starts = (0, 8), T = 16) so the `local` delivery contract can
# require FULL coverage of the out buffer by locally computed writes.

LINT_GEOM = dict(r=2, pps=2, npages=4, t=16, hkv=2, g=1, d=128, page=8,
                 block_q=8, topo_w=8)

#: parking-zone slack the GRID lint geometry reserves past each row's
#: packed span — the widest block_q a legal candidate may write into it.
#: A schedule whose block overruns even this slack spills into the next
#: row's delivered span (OOB on the zero-slack gate buffer → SL008).
GRID_BLOCK_CAP = 16


def grid_lint_geom(schedule=None) -> dict:
    """The :data:`LINT_GEOM`-shaped geometry a grid schedule gates at:
    the packing granularity ``pack_rows`` sets the per-row span, the
    schedule's ``block_q`` (0 = the :func:`auto_block_q` ladder) sets
    the query block, and the packed width reserves exactly
    ``min(block_q, GRID_BLOCK_CAP)`` tokens of tail slack — so the
    default schedule reproduces :data:`LINT_GEOM` exactly (byte-
    identity pin) while an over-wide block has nowhere legal to park
    its writes."""
    g = 1
    pack = 8 if schedule is None else int(schedule.pack_rows)
    bq = 0 if schedule is None else int(schedule.block_q)
    bq = bq or auto_block_q(pack, g)
    page = 8
    t = pack + min(bq, GRID_BLOCK_CAP)
    kv0 = pack + 4                        # row 0 crosses a page boundary
    pps = -(-kv0 // page)
    topo_w = topo_width(max(bq, 8))
    topo = causal_topologies(2, topo_w)
    tree_pack = 0 if schedule is None else int(
        getattr(schedule, "tree_pack", 0)
    )
    if tree_pack > 0:
        # exercise the TREE mask path at the gate: row 1 carries a
        # branchy verify tree (trunk chain + one sibling branch off the
        # frontier) of min(tree_pack, pack) nodes
        nd = max(min(tree_pack, pack) - 1, 1)
        parents = [-1] + list(range(nd - 1))
        if nd >= 3:
            parents[2] = -1               # sibling branch off the root
        topo[1] = tree_topology_row(parents[:nd], topo_w)
    return dict(
        r=2, pps=pps, npages=2 * pps, t=t, hkv=2, g=g, d=128, page=page,
        block_q=bq, n_bufs=2 if schedule is None else int(schedule.n_bufs),
        kv_lens=(kv0, pack), q_lens=(pack, pack), q_starts=(0, pack),
        topo_w=topo_w, topo=topo,
    )


def build_grid_lint_kernel(token=(), schedule=None, quant=True):
    """Grid-schedule gate entry: construct the ragged kernel at
    :func:`grid_lint_geom` with the schedule's ``block_q``/``n_bufs``
    threaded through the production builder. Returns the geometry dict
    so the gate can derive matching input shapes and scalar-prefetch
    init values."""
    gm = grid_lint_geom(schedule)
    _build_ragged(
        gm["r"], gm["pps"], gm["npages"], gm["t"], gm["hkv"], gm["g"],
        gm["d"], gm["page"], gm["block_q"], "float32", quant,
        1.0 / math.sqrt(gm["d"]), 0.0, gm["n_bufs"], False, token,
        gm["topo_w"],
    )
    return gm


#: the lint geometry's sliding window: one (lint) page, as the served
#: model's is one (serving) page
LINT_WINDOW = 8


def build_lint_kernel(token=(), quant=True, window=None):
    """Construct the ragged kernel exactly as production would (via
    shmem_call, so the LaunchSpec is captured under the family's
    launch name) at :data:`LINT_GEOM`. Used by the kernel registry and
    the Mosaic pre-flight. ``window``: the sliding-window variant
    (launch ``ragged_paged_attention_w<window>_q8``)."""
    gm = LINT_GEOM
    return _build_ragged(
        gm["r"], gm["pps"], gm["npages"], gm["t"], gm["hkv"], gm["g"],
        gm["d"], gm["page"], gm["block_q"], "float32", quant,
        1.0 / math.sqrt(gm["d"]), 0.0, 2, False, token, gm["topo_w"],
        window=window,
    )
