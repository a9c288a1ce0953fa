"""Block selection for block-sparse attention, and the compressed keys
it scores: XLA, on the device, inside the serving step.

A sparse attention layer keeps beside its K/V pools a pool of
COMPRESSED keys, per KV head

    kc_j = mean(k[stride·j : stride·j + kernel])        for stride·j + kernel <= n

stored in the page and row of its first token (``(npages, Hkv, page //
stride, D)``, addressed by the block table). ``append_compressed``
writes the ones a step's appended tokens complete, from the pool the
step has just appended to: incremental, never a pass over a context.

``select_blocks`` then chooses, for every packed query position ``i``
and KV head, the blocks of ``block`` tokens it attends:

    i <  dense_len:  every block that holds a key j <= i
    i >= dense_len:  p_h  = softmax_j(q_h . kc_j / sqrt(D)) over the j with
                            stride·j + kernel <= i + 1, per query head h
                     sc_j = sum of p_h over the KV head's query heads
                     B_b  = max of sc_j over the j whose span
                            [stride·j, stride·j + kernel) meets block b
                     B_b  = +inf for b < init_blocks and for the blocks
                            that meet [i - window + 1, i]
                     the ``topk`` largest B_b, ties to the lower b

and hands the ragged kernel's ``selected`` operand: per (row, KV head)
the pages some position of the row chose a block in, and per packed
query row the bitmap of its blocks
(``kernels/ragged_paged_attention.py``).

ONE PROGRAM WHATEVER THE CONTEXTS. Whether a row is past ``dense_len``
is data. The scores' extent is static, so the step holds the selection
at a short ladder of context caps (``dense_len``, then x4 up to the
block table's reach) and ``lax.switch`` takes the smallest that covers
the step's longest batched row: a step whose rows are all short pays
for no scoring, and a 30k-token row not for the table's 131k.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels.ragged_paged_attention import (
    selected_bits,
)


def append_compressed(kc_pool, k_pool, table, kv_lens, q_lens, *,
                      kernel: int, stride: int, block_q: int):
    """Write into ``kc_pool`` (npages, Hkv, page // stride, D) the
    compressed keys that the step's appended tokens complete. ``k_pool``
    (npages, Hkv, page, D) already holds them (append-then-read); row
    ``r`` appended positions ``[kv_lens[r] - q_lens[r], kv_lens[r])``,
    at most ``block_q`` of them. A compressed key is the mean of
    ``kernel // stride`` sums over ``stride`` tokens, each of which
    lies in one page (``page % stride == 0``)."""
    npages, hkv, page, d = k_pool.shape
    r, pps = table.shape
    per_page = page // stride
    m = kernel // stride
    assert page % stride == 0 and kernel % stride == 0, (page, kernel, stride)
    end = kv_lens
    start = kv_lens - q_lens
    # compressed key j is complete once position stride·j + kernel - 1
    # is in: the new ones have start < stride·j + kernel <= end
    j_lo = jnp.maximum(jnp.floor_divide(start - kernel + stride, stride), 0)
    j_hi = jnp.floor_divide(end - kernel, stride)
    cand = block_q // stride + 1
    j = j_lo[:, None] + jnp.arange(cand)[None, :]             # (R, C)
    new = (j <= j_hi[:, None]) & (q_lens > 0)[:, None]
    # the stride-token segments x = j .. j + m - 1 of every candidate
    x = j_lo[:, None] + jnp.arange(cand + m - 1)[None, :]     # (R, C+m-1)
    rows = jnp.arange(r)[:, None]
    seg_page = table[rows, jnp.clip(x * stride // page, 0, pps - 1)]
    segs = k_pool.reshape(npages, hkv, per_page, stride, d)[
        jnp.clip(seg_page, 0, npages - 1), :, x % per_page]   # (R, X, Hkv, s, D)
    sums = jnp.sum(segs.astype(jnp.float32), axis=3)          # (R, X, Hkv, D)
    kc = sum(sums[:, u:u + cand] for u in range(m)) / kernel  # (R, C, Hkv, D)
    dest = table[rows, jnp.clip(j * stride // page, 0, pps - 1)]
    dest = jnp.where(new & (dest >= 0), dest, npages)         # dropped
    return kc_pool.at[dest, :, j % per_page].set(
        kc.astype(kc_pool.dtype), mode="drop")


def block_scores(sc, visible, *, kernel, stride, block):
    """``sc`` (..., NC) per-compressed-key scores, ``visible`` (..., NC)
    bool -> (..., NB) block scores: the max over the visible compressed
    keys whose span meets the block, -inf where none. With ``r = block
    // stride`` and ``m = kernel // stride`` those are the ``r + m - 1``
    keys from ``b·r - m + 1`` on: as many strided slices, no gather."""
    n_keys = sc.shape[-1]
    r, m = block // stride, kernel // stride
    n_blocks = n_keys // r
    lead = [(0, 0)] * (sc.ndim - 1)
    padded = jnp.pad(jnp.where(visible, sc, -jnp.inf),
                     lead + [(m - 1, r)], constant_values=-jnp.inf)
    out = None
    for u in range(r + m - 1):
        part = padded[..., u:u + n_blocks * r:r]
        out = part if out is None else jnp.maximum(out, part)
    return out


def forced_blocks(pos, n_blocks: int, *, block, init_blocks, window):
    """(T, NB) bool: the blocks a query at ``pos`` always attends: the
    first ``init_blocks`` and those that meet [pos - window + 1, pos]."""
    b = jnp.arange(n_blocks)[None, :]
    lo = jnp.maximum(pos - window + 1, 0)[:, None] // block
    return (b < init_blocks) | ((b >= lo) & (b <= pos[:, None] // block))


def kth_largest_key(scores, topk: int):
    """``scores`` (..., N) float32 -> ``(key (..., N) uint32, kth (...,
    1) uint32)``: keys whose unsigned order is the floats', and the
    ``topk``-th largest of them (``topk < N``)."""
    # ``lax.top_k`` lowers to a full sort of every row (6 of the 9 ms a
    # chunk step's selection took on the v5e). The chosen SET needs only
    # the topk-th largest value: found exactly, four bits a pass, on keys
    # whose unsigned order is the floats' (``+ 0.0``: no negative zero)
    bits = jax.lax.bitcast_convert_type(
        scores.astype(jnp.float32) + 0.0, jnp.uint32)
    sign = jnp.uint32(1 << 31)
    key = jnp.where(bits >= sign, ~bits, bits | sign)
    # kth: the largest value that ``topk`` keys reach. A pass tries the
    # 15 non-zero digits at once: as many keys reach a larger candidate
    # as a smaller one or fewer, so the digit is a count
    kth = jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32)
    for shift in range(28, -1, -4):
        cands = kth | (jnp.arange(1, 16, dtype=jnp.uint32) << shift)
        reach = jnp.sum(key[..., None, :] >= cands[..., None], axis=-1,
                        dtype=jnp.int32)                     # (..., 15)
        digit = jnp.sum(reach >= topk, axis=-1, keepdims=True)
        kth = kth | (digit.astype(jnp.uint32) << shift)
    return key, kth


def choose_blocks(scores, forced, topk: int):
    """``scores`` (..., NB) with ``forced`` (..., NB) -> bool (..., NB):
    the ``topk`` largest with the forced ones at +inf, ties to the
    lower block."""
    n_blocks = scores.shape[-1]
    if topk >= n_blocks:
        return jnp.ones(scores.shape, bool)
    key, kth = kth_largest_key(jnp.where(forced, jnp.inf, scores), topk)
    above = key > kth
    tie = key == kth
    # ties to the lower block: a tie's rank among the ties, as a product
    # with a triangle of ones (0 / 1 in bfloat16, summed in float32: exact)
    before = jnp.einsum(
        "...b,bc->...c", tie.astype(jnp.bfloat16),
        jnp.triu(jnp.ones((n_blocks, n_blocks), jnp.bfloat16)),
        preferred_element_type=jnp.float32)
    room = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (tie & (before <= room.astype(jnp.float32)))


def _chosen(cap, q, slots, pos, kc_pool, table, *, tile, group, page,
            kernel, stride, block, init_blocks, window, topk, dense_len,
            scale):
    """(N, Hkv, NB) bool: the blocks each of ``N`` queries ``q`` (N, Hq,
    D) at positions ``pos`` attends by score, with contexts of at most
    ``cap`` tokens in view. Every ``tile`` consecutive queries are one
    slot's (``slots`` (N / tile,)): its compressed keys are gathered
    once a tile, page by page."""
    n, hq, d = q.shape
    hkv = hq // group
    n_pages, n_blocks = cap // page, cap // block
    n_keys = n_pages * (page // stride)
    held = jnp.clip(table[slots, :n_pages], 0, kc_pool.shape[0] - 1)
    kc = kc_pool[held].transpose(0, 2, 1, 3, 4).reshape(
        n // tile, hkv, n_keys, d)
    s = jnp.einsum(
        "xqhgd,xhcd->xqhgc", q.reshape(n // tile, tile, hkv, group, d), kc,
        preferred_element_type=jnp.float32,
    ).reshape(n, hkv, group, n_keys) * scale
    vis = (jnp.arange(n_keys)[None, :] * stride + kernel
           <= pos[:, None] + 1)                                      # (N, NC)
    s = jnp.where(vis[:, None, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(vis[:, None, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    sc = jnp.sum(p, axis=2)                                          # (N, Hkv, NC)
    scores = block_scores(
        sc, jnp.broadcast_to(vis[:, None, :], sc.shape),
        kernel=kernel, stride=stride, block=block)
    seen = jnp.arange(n_blocks)[None, :] <= (pos // block)[:, None]
    scores = jnp.where(seen[:, None, :], scores, -jnp.inf)
    forced = forced_blocks(pos, n_blocks, block=block,
                           init_blocks=init_blocks, window=window)
    return choose_blocks(scores, forced[:, None, :], topk)


def _select_at(cap, scored, single, q, kc_pool, table, token_rows,
               token_pos, kv_lens, q_starts, *, group, page, block,
               dense_len, width, **sizes):
    """The selection with contexts of at most ``cap`` tokens in view.
    ``scored`` False: no row is past ``dense_len`` (no scores at all).
    ``single``: every batched row holds ONE token (a decode-only step),
    so the scores are taken for ``R`` queries, one a slot, not for the
    packed width. Returns ``(pages (R, Hkv, width), counts (R, Hkv),
    bits)``."""
    t, hq, d = q.shape
    hkv = hq // group
    r, _ = table.shape
    n_pages, n_blocks = cap // page, cap // block
    live = token_pos >= 0
    pos = jnp.maximum(token_pos, 0)
    row_of = jnp.clip(token_rows, 0, r - 1)
    seen = jnp.arange(n_blocks)[None, :] <= (pos // block)[:, None]  # (T, NB)
    chosen = jnp.broadcast_to(seen[:, None, :], (t, hkv, n_blocks))
    if scored and single:
        by_row = _chosen(
            cap, q[jnp.clip(q_starts, 0, t - 1)], jnp.arange(r),
            jnp.maximum(kv_lens - 1, 0), kc_pool, table, tile=1,
            group=group, page=page, block=block, dense_len=dense_len,
            **sizes)
        sparse = by_row[row_of]
    elif scored:
        # a tile of 8 packed tokens is one row's (8-aligned spans)
        sparse = _chosen(
            cap, q, row_of[::8], pos, kc_pool, table, tile=8, group=group,
            page=page, block=block, dense_len=dense_len, **sizes)
    if scored:
        chosen = jnp.where((pos >= dense_len)[:, None, None], sparse, chosen)
    chosen = chosen & seen[:, None, :] & live[:, None, None]
    # the pages some position of a row chose a block in, ascending
    in_page = jnp.any(
        chosen.reshape(t, hkv, n_pages, page // block), axis=-1)
    of_row = (token_rows[:, None] == jnp.arange(r)[None, :]) & live[:, None]
    walked = jnp.einsum(
        "tr,thp->rhp", of_row.astype(jnp.float32),
        in_page.astype(jnp.float32)) > 0                             # (R, Hkv, NP)
    counts = jnp.sum(walked, axis=-1).astype(jnp.int32)
    pages = jnp.sort(
        jnp.where(walked, jnp.arange(n_pages), n_pages), axis=-1)
    pages = jnp.where(pages < n_pages, pages, 0).astype(jnp.int32)
    if n_pages < width:
        pages = jnp.pad(pages, ((0, 0), (0, 0), (0, width - n_pages)))
    else:
        pages = pages[..., :width]
    return pages, counts, selected_bits(chosen, group)


def context_caps(dense_len: int, capacity: int) -> list:
    """The ladder of context caps the step holds the selection at."""
    caps = [min(dense_len, capacity)]
    while caps[-1] < capacity:
        caps.append(min(capacity, caps[-1] * 4))
    return caps


def select_blocks(q, kc_pool, table, token_rows, token_pos, kv_lens,
                  q_lens, q_starts, *, group: int, page: int, kernel: int,
                  stride: int, block: int, init_blocks: int, window: int,
                  topk: int, dense_len: int):
    """q: (T, Hq, D) the step's (normed) queries; ``kc_pool``: the
    layer's compressed keys AFTER ``append_compressed``; ``table``: (R,
    pps) block table; ``token_rows`` / ``token_pos``: (T,) slot and
    sequence position of every packed token (pos < 0 = padding);
    ``kv_lens`` / ``q_lens`` / ``q_starts``: (R,). Returns ``(pages (R,
    Hkv, pps), counts (R, Hkv), bits (Hkv, T·G, SELECT_WORDS))``.

    One ``lax.switch`` over what the step holds: no row past
    ``dense_len`` (no scores), or the smallest context cap of the ladder
    that covers its longest batched row, each with every row holding
    one token (scores for R queries) or not (for the packed width)."""
    t, hq, d = q.shape
    r, pps = table.shape
    assert t % 8 == 0 and dense_len % page == 0, (t, dense_len, page)
    caps = context_caps(dense_len, pps * page)
    longest = jnp.max(jnp.where(q_lens > 0, kv_lens, 0))
    rung = sum((longest > c).astype(jnp.int32) for c in caps[:-1])
    single = (jnp.max(q_lens) <= 1).astype(jnp.int32)
    kw = dict(group=group, page=page, kernel=kernel, stride=stride,
              block=block, init_blocks=init_blocks, window=window,
              topk=topk, dense_len=dense_len, scale=1.0 / math.sqrt(d),
              width=pps)
    branches = [functools.partial(_select_at, caps[0], False, False, **kw)]
    for cap in caps[1:]:
        branches += [functools.partial(_select_at, cap, True, one, **kw)
                     for one in (False, True)]
    index = jnp.where(rung > 0, 2 * rung - 1 + single, 0)
    operands = (q, kc_pool, table, token_rows, token_pos, kv_lens, q_starts)
    if len(branches) == 1:
        return branches[0](*operands)
    return jax.lax.switch(index, branches, *operands)
