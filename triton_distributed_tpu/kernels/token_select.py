"""Token-level learned selection (the DeepSeek-Sparse-Attention form):
an INDEXER scores every cached token of a row, the row's queries keep
their ``topk`` best, and attention walks the kept tokens only.

Beside its K/V pools such a layer keeps a pool of INDEXER KEYS, one a
token for all heads, ``(npages, 1, page, index_stored)`` (the key's
``index_dim`` values, zeros up to whole 128-lane tiles), addressed by
the same block table and appended in the step that appends K/V. A query
at position ``t`` with indexer queries ``qI[t, j]`` (``J`` heads) and
head weights ``w[t, j]``::

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s] * scale)      s <= t
    S[t]    = every s <= t                      if t + 1 <= topk
              the topk largest I[t, s], ties to the lower s   otherwise
    o[t, h] = softmax over s in S[t] of (q[t, h] . k[s] / sqrt(D)) v[s]

ONE selection a query position, shared by every head.

Three stages, each a function here and its XLA twin:

``index_scores``   the SCAN. A Pallas kernel, grid over the rows: a
                   row's queries (one 8-token packing slot, or the
                   launch's ``block_q`` tokens) against its indexer
                   keys, a block of pages an iteration, ``I`` out in
                   float32. A row whose context is at most ``topk``
                   selects everything and is not scanned.
``select_tokens``  the SELECTION, XLA: the bitwise threshold search of
                   ``sparse_select.kth_largest_key`` on ``I``, ties by a
                   running count, under ``select_caps``' three context
                   caps (``lax.switch`` takes the smallest that covers
                   the longest row), once for the one-token rows' ``R``
                   queries and once for the longer rows' packed tokens,
                   so a chunk's choice is made at ITS context's cap,
                   not at the longest resident decode row's. Hands the
                   walk its MASK WORDS.
``token_walk``     the WALK. A Pallas kernel, grid over the rows: the
                   online-softmax page walk of ``ragged_paged_attention``
                   under a per-(query position, key) mask. It reads
                   every page of the row's context: with seeded weights
                   the kept tokens of a 20k-token context lie in every
                   one of its pages (~13 a page), so a walk by page has
                   nothing to skip; a gather by token is 2 x 4 copies of
                   256 bytes a token from a head-major pool.

THE MASK WORDS ``(PW, T, page)`` int32, ``PW = ceil(pps / 32)``: key
``s`` of a row, in its logical page ``p = s // page`` at offset ``k``,
is bit ``p // PW`` of word ``[p % PW, t, k]``. A page's ``page`` keys
are one bit of ``page`` consecutive words: the walk takes plane ``p %
PW`` (a dynamic index on a leading dimension), shifts by ``p // PW``
and has the page's mask with no gather and no lane shuffle. The width
does not depend on the context cap, so the walk is one launch outside
the ``lax.switch``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.config import local_interpret
from triton_distributed_tpu.kernels.ragged_paged_attention import (
    NEG_INF,
    _n_valid_pages,
)
from triton_distributed_tpu.kernels.sparse_select import kth_largest_key
from triton_distributed_tpu.lang.launch import shmem_call

#: a row of at most this many tokens is scanned and walked as ONE
#: 8-token packing slot, whatever the launch's ``block_q``
SHORT = 8
#: pages of one key block of such a row (fetched into one buffer,
#: scored as one block); a longer row goes a page at a time. 8: a
#: decode row holds 100-200 pages at the benchmark's contexts, and the
#: selected walk's probe (CHANGES.md, PR 45) read 0.131 us a page at 8
#: against 0.167 at 4 on lists a quarter as long
KV_PAGES = 8
#: lanes of a vreg: the stored width of an indexer key is whole tiles
LANES = 128
#: the middle rung of ``select_caps``, in ``topk``s: 32768 keys at the
#: published 2048, over the benchmark's longest context (27136)
STEP = 16


def index_stored(index_dim: int) -> int:
    """Values the indexer-key pool STORES a token: the key padded with
    zeros to whole 128-lane tiles."""
    return -(-index_dim // LANES) * LANES


def word_planes(pps: int) -> int:
    """``PW``: logical pages of one bit of the mask words."""
    return -(-pps // 32)


# ------------------------------------------------------------- mask words


def pack_words(chosen, *, page: int, pps: int):
    """``chosen`` (N, C) bool over the first ``C`` keys of each query's
    row (``C`` whole pages, at most ``pps``) -> the mask words ``(PW,
    N, page)`` int32."""
    n, c = chosen.shape
    pw = word_planes(pps)
    bits = -(-(c // page) // pw)
    x = jnp.pad(chosen, ((0, 0), (0, bits * pw * page - c)))
    x = x.reshape(n, bits, pw, page).astype(jnp.uint32)
    words = jnp.sum(
        x << jnp.arange(bits, dtype=jnp.uint32)[None, :, None, None],
        axis=1, dtype=jnp.uint32)                            # (N, PW, page)
    return jax.lax.bitcast_convert_type(words, jnp.int32).transpose(1, 0, 2)


def unpack_words(words, n_pages: int):
    """The mask words ``(PW, N, page)`` -> (N, n_pages · page) bool."""
    pw, n, page = words.shape
    p = jnp.arange(n_pages)
    w = jax.lax.bitcast_convert_type(words, jnp.uint32)[p % pw]
    bit = (w >> (p // pw).astype(jnp.uint32)[:, None, None]) & 1
    return bit.transpose(1, 0, 2).reshape(n, n_pages * page) > 0


# -------------------------------------------------------------- selection


def _running_count(x):
    """Inclusive running count of the True entries of ``x`` (..., C)
    along its last axis, float32 (exact: counts below 2^24). Two
    levels of products with triangles of ones where ``C`` is whole
    tiles, a plain cumulative sum elsewhere (the tests' sizes)."""
    c = x.shape[-1]
    if c % LANES:
        return jnp.cumsum(x.astype(jnp.float32), axis=-1)
    n = c // LANES
    xb = x.reshape(x.shape[:-1] + (n, LANES)).astype(jnp.bfloat16)
    inside = jnp.einsum(
        "...nk,kl->...nl", xb,
        jnp.triu(jnp.ones((LANES, LANES), jnp.bfloat16)),
        preferred_element_type=jnp.float32)
    # a tile's total is a sum of at most 128 ones: exact in bfloat16
    before = jnp.einsum(
        "...n,nm->...m", inside[..., -1].astype(jnp.bfloat16),
        jnp.triu(jnp.ones((n, n), jnp.bfloat16), k=1),
        preferred_element_type=jnp.float32)
    return (inside + before[..., None]).reshape(x.shape)


def choose_tokens(scores, topk: int):
    """``scores`` (..., C) float32, -inf where a key is not in view ->
    bool (..., C): the ``topk`` largest, ties to the lower key. A query
    with at most ``topk`` keys in view keeps them all (and may keep
    keys not in view: the caller masks)."""
    if topk >= scores.shape[-1]:
        return jnp.ones(scores.shape, bool)
    key, kth = kth_largest_key(scores, topk)
    above = key > kth
    tie = key == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (tie & (_running_count(tie) <= room.astype(jnp.float32)))


def select_caps(topk: int, capacity: int) -> list:
    """The ladder of context caps a choice is made at: ``topk`` (no
    score is read), ``STEP · topk`` and the block table's reach. Three
    rungs, not a fine ladder: every rung is a branch of every layer of
    every step program (PERF.md §6, PR 49: ten branches a layer made
    executables of ~36 MB and a cold start of 165 s)."""
    caps = [min(topk, capacity)]
    for cap in (STEP * topk, capacity):
        if caps[-1] < capacity:
            caps.append(min(cap, capacity))
    return caps


def _kept(scores, pos, on, cap, topk):
    """bool (N, cap): the keys each of ``N`` queries at ``pos`` keeps
    (none where not ``on``): every key in view up to ``topk`` of them,
    then the ``topk`` best by ``scores`` (N, >= cap)."""
    seen = (jnp.arange(cap)[None, :] <= pos[:, None]) & on[:, None]
    if cap == topk:
        return seen                         # no row past topk: no score read
    kept = choose_tokens(jnp.where(seen, scores[:, :cap], -jnp.inf), topk)
    return jnp.where((pos < topk)[:, None], seen, kept & seen)


def _rows_at(cap, scores, kv_lens, q_lens, q_starts, *, page, pps, topk):
    """Mask words ``(PW, R, page)`` of every ONE-TOKEN row's token, with
    contexts of at most ``cap`` keys in view (zeros for other rows)."""
    first = jnp.clip(q_starts, 0, scores.shape[0] - 1)
    return pack_words(
        _kept(scores[first], kv_lens - 1, q_lens == 1, cap, topk),
        page=page, pps=pps)


def _packed_at(cap, scores, token_pos, of_long, *, page, pps, topk):
    """Mask words ``(PW, T, page)`` of the packed tokens ``of_long``
    (those of the rows of more than one token), with contexts of at
    most ``cap`` keys in view (zeros for the others)."""
    return pack_words(_kept(scores, token_pos, of_long, cap, topk),
                      page=page, pps=pps)


def select_tokens(scores, token_rows, token_pos, kv_lens, q_lens,
                  q_starts, *, page: int, pps: int, topk: int):
    """``scores`` (T, >= pps · page) float32 from ``index_scores`` (read
    only where a live query past ``topk`` has a key in view) -> the
    mask words ``(PW, T, page)`` int32 of the step.

    Two choices, each one ``lax.switch`` over ``select_caps`` (the
    smallest cap that covers the longest row of its kind; the first
    rung reads no score): the ONE-TOKEN rows' (every decode row:
    ``R`` queries, whatever the step is wide) and the LONGER rows' (a
    chunk, a prompt's tail: every packed token, at a cap its own rows
    set, not the resident decode rows')."""
    assert topk % page == 0, (topk, page)
    t = token_pos.shape[0]
    r = kv_lens.shape[0]
    kw = dict(page=page, pps=pps, topk=topk)
    live = token_pos >= 0
    row_of = jnp.clip(token_rows, 0, r - 1)

    def pick(caps, longest, fn, *operands):
        branches = [functools.partial(fn, cap, **kw) for cap in caps]
        if len(branches) == 1:
            return branches[0](*operands)
        rung = sum((longest > c).astype(jnp.int32) for c in caps[:-1])
        return jax.lax.switch(rung, branches, *operands)

    one = q_lens == 1
    caps = select_caps(topk, pps * page)
    by_row = pick(
        caps,
        jnp.max(jnp.where(one, kv_lens, 0)),
        _rows_at, scores, kv_lens, q_lens, q_starts)          # (PW, R, page)
    packed = pick(
        caps,
        jnp.max(jnp.where(q_lens > 1, kv_lens, 0)),
        _packed_at, scores, token_pos, live & (q_lens[row_of] > 1))
    mine = live & one[row_of]
    return jnp.where(mine[None, :, None], by_row[:, row_of], packed)


# ------------------------------------------------------------------- scan


def scores_width(pps: int, page: int) -> int:
    """Keys a row of ``index_scores``'s output holds: the block table's
    reach in whole key blocks."""
    return -(-pps // KV_PAGES) * KV_PAGES * page


def _scan_kernel(scale, page, heads, block_q, topk, *refs):
    """Grid (R,): row ``r``'s queries against its indexer keys. A row
    outside the batch, or whose context is at most ``topk``, is
    skipped (nothing reads its scores)."""
    (table_ref, kv_lens_ref, q_lens_ref, q_starts_ref, qi_hbm, w_hbm,
     ik_hbm, out_hbm, qbuf, wbuf, kbuf, obuf, sem_q, sem_k, sem_o) = refs
    r = pl.program_id(0)
    npages = ik_hbm.shape[0]
    pps = table_ref.shape[1]
    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    nb = jnp.minimum(_n_valid_pages(kv_len, page), pps)
    start = pl.multiple_of(q_starts_ref[r], 8)

    def scan(tq, kb, one=False):
        """Row ``r`` as a block of ``tq`` tokens against ``kb`` pages an
        iteration. ``one``: the row holds ONE token, the first of its
        block: its ``heads`` query rows alone are scored (picked out of
        the block by a product with a 0 / 1 matrix: exact) and the
        block's other score rows are left as they are."""
        span = kb * page
        nblk = jax.lax.div(nb + kb - 1, kb)
        fetch = [
            pltpu.make_async_copy(
                src.at[:, pl.ds(start, tq)], dst.at[:, pl.ds(0, tq)],
                sem_q.at[i])
            for i, (src, dst) in enumerate(((qi_hbm, qbuf), (w_hbm, wbuf)))]

        def keys(j, slot):
            # a page past the row's last is its last again: its scores
            # land past the context, where no query has a key in view
            out = []
            for u in range(kb):
                p = jnp.minimum(j * kb + u, nb - 1)
                pid = jnp.clip(table_ref[r, p], 0, npages - 1)
                out.append(pltpu.make_async_copy(
                    ik_hbm.at[pid, 0], kbuf.at[slot, pl.ds(u * page, page)],
                    sem_k.at[slot, u]))
            return out

        def put(j):
            return pltpu.make_async_copy(
                obuf.at[pl.ds(0, tq), pl.ds(0, span)],
                out_hbm.at[pl.ds(start, tq),
                           pl.ds(pl.multiple_of(j * span, span), span)],
                sem_o.at[0])

        for cp in fetch + keys(0, 0):
            cp.start()
        for cp in fetch:
            cp.wait()
        q = qbuf[:, :tq].reshape(heads * tq, qbuf.shape[-1]).astype(
            kbuf.dtype)
        if one:
            pick = (jax.lax.broadcasted_iota(jnp.int32, (heads, heads * tq), 1)
                    == tq * jax.lax.broadcasted_iota(
                        jnp.int32, (heads, heads * tq), 0)).astype(q.dtype)
            q = jnp.dot(pick, q, preferred_element_type=jnp.float32
                        ).astype(q.dtype)                      # (heads, DS)
            w_one = jnp.concatenate(
                [wbuf[h, 0:1, 0:1] for h in range(heads)], axis=0)

        def body(j, _):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _ahead():
                for cp in keys(j + 1, 1 - slot):
                    cp.start()

            for cp in keys(j, slot):
                cp.wait()
            s = jax.lax.dot_general(
                q, kbuf[slot, :span], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if one:
                acc = jnp.sum(w_one * jnp.maximum(s, 0.0), axis=0,
                              keepdims=True)                   # (1, span)
            else:
                acc = jnp.zeros((tq, span), jnp.float32)
                for h in range(heads):        # static: aligned row slices
                    acc = acc + wbuf[h, :tq, 0:1] * jnp.maximum(
                        s[h * tq:(h + 1) * tq], 0.0)

            @pl.when(j > 0)
            def _landed():
                put(j - 1).wait()

            obuf[:1 if one else tq, :span] = acc
            put(j).start()
            return 0

        jax.lax.fori_loop(0, nblk, body, 0)
        put(nblk - 1).wait()

    scanned = kv_len > topk
    pl.when(jnp.logical_and(scanned, q_len == 1))(
        functools.partial(scan, SHORT, KV_PAGES, True))
    pl.when(jnp.logical_and(
        scanned, jnp.logical_and(q_len > 1, q_len <= SHORT)))(
        functools.partial(scan, SHORT, KV_PAGES))
    if block_q > SHORT:
        pl.when(jnp.logical_and(scanned, q_len > SHORT))(
            functools.partial(scan, block_q, 1))


@functools.lru_cache(maxsize=64)
def _build_scan(r, pps, npages, t, heads, stored, page, block_q, topk,
                key_dtype, scale, interpret):
    """The scan's pallas_call: ``(table, kv_lens, q_lens, q_starts, qI
    (J, T, stored) float32, w (J, T, 128) float32, key pool) -> [I (T,
    scores_width) float32]``."""
    key_dtype = jnp.dtype(key_dtype)
    tq = max(block_q, SHORT)
    span = KV_PAGES * page
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=[any_, any_, any_],
        out_specs=[any_],
        scratch_shapes=[
            pltpu.VMEM((heads, tq, stored), jnp.float32),    # qbuf
            pltpu.VMEM((heads, tq, LANES), jnp.float32),     # wbuf
            pltpu.VMEM((2, span, stored), key_dtype),        # kbuf
            pltpu.VMEM((tq, span), jnp.float32),             # obuf
            pltpu.SemaphoreType.DMA((2,)),                   # sem_q
            pltpu.SemaphoreType.DMA((2, KV_PAGES)),          # sem_k
            pltpu.SemaphoreType.DMA((1,)),                   # sem_o
        ],
    )
    # the widest tile's scores (J · tq, page) and their temporaries
    total = (heads * tq * (stored + LANES) * 4 + tq * span * 4
             + 2 * span * stored * key_dtype.itemsize
             + 4 * heads * max(tq * page, SHORT * span) * 4)
    return shmem_call(
        functools.partial(_scan_kernel, scale, page, heads, block_q, topk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(
            (t, scores_width(pps, page)), jnp.float32)],
        collective_id=None,
        vmem_limit_bytes=(total + (8 << 20)) if total > (12 << 20) else None,
        interpret=local_interpret() if interpret is None else interpret,
        name="dsa_index_scores",
        dimension_semantics=("arbitrary",),
    )


@functools.partial(
    jax.jit, static_argnames=("topk", "block_q", "scale", "interpret"))
def index_scores(qi, w, key_pool, kv_lens, q_lens, q_starts, table, *,
                 topk: int, block_q: int, scale: float, interpret=None):
    """``qi`` (T, J, Di) the step's indexer queries, ``w`` (T, J)
    float32 their head weights, ``key_pool`` (npages, 1, page, stored)
    AFTER the step's append -> ``I`` (T, scores_width) float32: row
    ``r``'s live queries against keys ``[0, kv_lens[r])`` (garbage
    elsewhere, and everywhere for a row whose context is at most
    ``topk``)."""
    t, heads, di = qi.shape
    npages, _, page, stored = key_pool.shape
    r, pps = table.shape
    qf = jnp.pad(qi.astype(jnp.float32).transpose(1, 0, 2),
                 ((0, 0), (0, 0), (0, stored - di)))
    wf = jnp.broadcast_to(
        w.astype(jnp.float32).T[:, :, None], (heads, t, LANES))
    call = _build_scan(r, pps, npages, t, heads, stored, page, block_q,
                       topk, key_pool.dtype, scale, interpret)
    return call(table, kv_lens, q_lens, q_starts, qf, wf, key_pool)[0]


def index_scores_xla(qi, w, key_pool, token_rows, table, *, scale: float):
    """The scan's twin: every query against every key its row's table
    reaches, ``(T, scores_width)`` float32, a head at a time."""
    t, heads, di = qi.shape
    npages, _, page, _ = key_pool.shape
    r, pps = table.shape
    held = jnp.clip(table, 0, npages - 1)
    keys = key_pool[held][:, :, 0, :, :di].reshape(r, pps * page, di)
    mine = keys[jnp.clip(token_rows, 0, r - 1)]              # (T, C, Di)

    def head(acc, qw):
        q, wj = qw                                           # (T, Di), (T,)
        s = jnp.einsum("td,tcd->tc", q.astype(mine.dtype), mine,
                       preferred_element_type=jnp.float32) * scale
        return acc + wj[:, None] * jnp.maximum(s, 0.0), None

    out, _ = jax.lax.scan(
        head, jnp.zeros((t, pps * page), jnp.float32),
        (qi.transpose(1, 0, 2), w.astype(jnp.float32).T))
    return jnp.pad(out, ((0, 0), (0, scores_width(pps, page) - pps * page)))


# ------------------------------------------------------------------- walk


def walk_token_rows(g: int, q_dtype) -> int:
    """Tokens a one-token row is walked as: the fewest whose ``t · G``
    query rows fill the dtype's sublane tile (8 rows of 4 bytes, 16 of
    2), never more than its packing slot."""
    tile = 8 * max(1, 4 // jnp.dtype(q_dtype).itemsize)
    return min(tile // math.gcd(g, tile), SHORT)


def _walk_kernel(scale, page, hkv, g, d, block_q, tok, *refs):
    """Grid (R,): row ``r``'s queries through every page of its
    context under the mask words, all KV heads a page (one copy of a
    page's K and of its V for the ``hkv`` heads; one mask for them
    all). By the row's length: ONE token walks ``tok`` tokens' rows
    against ``KV_PAGES`` pages an iteration, 2 to ``SHORT`` tokens its
    packing slot likewise, a longer row ``block_q`` tokens' rows a
    page an iteration."""
    (table_ref, kv_lens_ref, q_lens_ref, q_starts_ref, q_hbm, k_hbm,
     v_hbm, w_hbm, out_hbm, qbuf, wbuf, kbuf, vbuf, obuf, sem_q, sem_k,
     sem_v, sem_o, m_ref, l_ref, acc_ref) = refs
    r = pl.program_id(0)
    npages = k_hbm.shape[0]
    pps = table_ref.shape[1]
    pw = w_hbm.shape[0]
    kv_len = kv_lens_ref[r]
    q_len = q_lens_ref[r]
    nb = jnp.minimum(_n_valid_pages(kv_len, page), pps)
    start = pl.multiple_of(q_starts_ref[r], 8)

    def walk(tq, kb):
        rows, span = tq * g, kb * page
        tw = max(tq, SHORT)               # mask rows fetched: whole tiles
        nblk = jax.lax.div(nb + kb - 1, kb)
        fetch = [
            pltpu.make_async_copy(
                q_hbm.at[:, pl.ds(pl.multiple_of(start * g, 8), rows)],
                qbuf.at[:, pl.ds(0, rows)], sem_q.at[0]),
            pltpu.make_async_copy(
                w_hbm.at[:, pl.ds(start, tw)], wbuf.at[:, pl.ds(0, tw)],
                sem_q.at[1]),
        ]

        def pages(j, slot):
            out = []
            for u in range(kb):
                p = jnp.minimum(j * kb + u, nb - 1)
                pid = jnp.clip(table_ref[r, p], 0, npages - 1)
                at = pl.ds(u * page, page)
                out += [
                    pltpu.make_async_copy(
                        k_hbm.at[pid], kbuf.at[slot, :, at],
                        sem_k.at[slot, u]),
                    pltpu.make_async_copy(
                        v_hbm.at[pid], vbuf.at[slot, :, at],
                        sem_v.at[slot, u]),
                ]
            return out

        for cp in fetch + pages(0, 0):
            cp.start()
        for h in range(hkv):
            lo = h * rows
            m_ref[lo:lo + rows] = jnp.full((rows, 1), NEG_INF, jnp.float32)
            l_ref[lo:lo + rows] = jnp.zeros((rows, 1), jnp.float32)
            acc_ref[lo:lo + rows] = jnp.zeros((rows, d), jnp.float32)
        row_tok = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), g)
        if tq > SHORT:
            # a token's mask row to its G query rows, as a product with
            # a matrix of ones (0 / 1 both sides: exact)
            spread = (row_tok == jax.lax.broadcasted_iota(
                jnp.int32, (rows, tq), 1)).astype(qbuf.dtype)
        for cp in fetch:
            cp.wait()

        def seen(j):
            """(rows, span) bool: the block's keys each query row
            attends."""
            out = []
            for u in range(kb):
                at = j * kb + u
                p = jnp.minimum(at, nb - 1)
                plane = wbuf[jax.lax.rem(p, pw), :tw]        # (tw, page)
                bit = jax.lax.shift_right_logical(
                    plane, jnp.full_like(plane, jax.lax.div(p, pw))) & 1
                # a page past the row's last (its last again) is unseen
                bit = bit * (at < nb).astype(jnp.int32)
                if tq > SHORT:
                    out.append(jnp.dot(
                        spread, bit.astype(jnp.float32).astype(qbuf.dtype),
                        preferred_element_type=jnp.float32) > 0.5)
                    continue
                mine = jnp.zeros((rows, page), jnp.int32)
                for i in range(tq):       # static: a sublane broadcast each
                    mine = jnp.where(row_tok == i, bit[i:i + 1], mine)
                out.append(mine > 0)
            return out[0] if kb == 1 else jnp.concatenate(out, axis=1)

        def body(j, _):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _ahead():
                for cp in pages(j + 1, 1 - slot):
                    cp.start()

            for cp in pages(j, slot):
                cp.wait()
            valid = seen(j)
            for h in range(hkv):              # static unroll
                lo, hi = h * rows, (h + 1) * rows
                s = jax.lax.dot_general(
                    qbuf[h, :rows], kbuf[slot, h, :span],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, NEG_INF)
                m = m_ref[lo:hi]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_ref[lo:hi] = alpha * l_ref[lo:hi] + jnp.sum(
                    p, axis=1, keepdims=True)
                v = vbuf[slot, h, :span]
                acc_ref[lo:hi] = alpha * acc_ref[lo:hi] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                m_ref[lo:hi] = m_new
            return 0

        jax.lax.fori_loop(0, nblk, body, 0)
        for h in range(hkv):
            lo, hi = h * rows, (h + 1) * rows
            l = l_ref[lo:hi]
            # a query row that attends nothing (a padding token's)
            # writes zeros
            obuf[h, :rows] = (
                acc_ref[lo:hi] / jnp.where(l > 0.0, l, 1.0)
            ).astype(obuf.dtype)
        out = pltpu.make_async_copy(
            obuf.at[:, pl.ds(0, rows)],
            out_hbm.at[:, pl.ds(pl.multiple_of(start * g, 8), rows)],
            sem_o.at[0])
        out.start()
        # waited before the grid advances: a long row's block runs over
        # the rows behind it, which write their own after it
        out.wait()

    pl.when(q_len == 1)(functools.partial(walk, tok, KV_PAGES))
    pl.when(jnp.logical_and(q_len > 1, q_len <= SHORT))(
        functools.partial(walk, SHORT, KV_PAGES))
    if block_q > SHORT:
        pl.when(q_len > SHORT)(functools.partial(walk, block_q, 1))


@functools.lru_cache(maxsize=64)
def _build_walk(r, pps, npages, t, hkv, g, d, page, block_q, q_dtype,
                scale, interpret):
    """The walk's pallas_call: ``(table, kv_lens, q_lens, q_starts, q
    (Hkv, T·G, D), k pool, v pool, words (PW, T, page)) -> [out (Hkv,
    T·G, D)]``."""
    q_dtype = jnp.dtype(q_dtype)
    tq = max(block_q, SHORT)
    rows = tq * g
    span = KV_PAGES * page
    pw = word_planes(pps)
    tok = walk_token_rows(g, q_dtype)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(r,),
        in_specs=[any_, any_, any_, any_],
        out_specs=[any_],
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, d), q_dtype),             # qbuf
            pltpu.VMEM((pw, tq, page), jnp.int32),           # wbuf
            pltpu.VMEM((2, hkv, span, d), q_dtype),          # kbuf
            pltpu.VMEM((2, hkv, span, d), q_dtype),          # vbuf
            pltpu.VMEM((hkv, rows, d), q_dtype),             # obuf
            pltpu.SemaphoreType.DMA((2,)),                   # sem_q
            pltpu.SemaphoreType.DMA((2, KV_PAGES)),          # sem_k
            pltpu.SemaphoreType.DMA((2, KV_PAGES)),          # sem_v
            pltpu.SemaphoreType.DMA((1,)),                   # sem_o
            pltpu.VMEM((hkv * rows, 1), jnp.float32),        # m
            pltpu.VMEM((hkv * rows, 1), jnp.float32),        # l
            pltpu.VMEM((hkv * rows, d), jnp.float32),        # acc
        ],
    )
    # q/out blocks, the mask words, K/V slots, softmax state (the
    # (·, 1) columns pad to full lanes), the widest tile's scores
    scores = max(rows * page, SHORT * g * span)
    total = (2 * hkv * rows * d * q_dtype.itemsize + pw * tq * page * 4
             + 4 * hkv * span * d * q_dtype.itemsize
             + hkv * rows * (d + 2 * LANES) * 4 + 6 * scores * 4
             + rows * tq * q_dtype.itemsize)
    return shmem_call(
        functools.partial(_walk_kernel, scale, page, hkv, g, d, block_q,
                          tok),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hkv, t * g, d), q_dtype)],
        collective_id=None,
        vmem_limit_bytes=(total + (8 << 20)) if total > (12 << 20) else None,
        interpret=local_interpret() if interpret is None else interpret,
        name="ragged_paged_attention_tokens",
        dimension_semantics=("arbitrary",),
    )


@functools.partial(
    jax.jit, static_argnames=("group", "block_q", "scale", "interpret"))
def token_walk(q, k_pool, v_pool, words, kv_lens, q_lens, q_starts, table,
               *, group: int, block_q: int, scale: float | None = None,
               interpret=None):
    """``q`` (Hkv, T·G, D) in the GQA-rows packing of
    ``ragged_paged_attention``, the pools AFTER the step's append,
    ``words`` the step's mask words -> out (Hkv, T·G, D): each query
    row's softmax attention over the keys its token's mask keeps (zeros
    for a token that keeps none). Rows outside the batched rows' blocks
    are not written."""
    hkv, tg, d = q.shape
    npages, _, page, _ = k_pool.shape
    r, pps = table.shape
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    call = _build_walk(r, pps, npages, tg // group, hkv, group, d, page,
                       block_q, q.dtype, scale, interpret)
    return call(table, kv_lens, q_lens, q_starts, q, k_pool, v_pool,
                words)[0]


def token_walk_xla(q, k_pool, v_pool, words, token_rows, table, *,
                   group: int, scale: float | None = None):
    """The walk's twin: dense attention of every packed token over the
    keys its row's table reaches, under the mask the words hold."""
    hkv, tg, d = q.shape
    npages, _, page, _ = k_pool.shape
    r, pps = table.shape
    t = tg // group
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    held = jnp.clip(table, 0, npages - 1)
    row_of = jnp.clip(token_rows, 0, r - 1)

    def mine(pool):                                          # (T, Hkv, C, D)
        return pool[held].transpose(0, 2, 1, 3, 4).reshape(
            r, hkv, pps * page, d)[row_of]

    kept = unpack_words(words, pps)                          # (T, C)
    s = jnp.einsum("htgd,thcd->htgc", q.reshape(hkv, t, group, d),
                   mine(k_pool),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(kept[None, :, None, :], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(kept[None, :, None, :],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("htgc,thcd->htgd", p.astype(v_pool.dtype), mine(v_pool),
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(hkv, tg, d)
