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

Three stages, each a Pallas kernel here with its XLA twin (the twins
gather a row's whole table reach a token: the CPU references', the
tests' and the degraded engine's sizes), each a grid over the ROWS with
``kv_lens`` / ``q_lens`` / ``q_starts`` prefetched, each visiting a row
by what it holds: its own pages, a one-token row as its 8-token packing
slot, a longer row its tokens:

``index_scores``   the SCAN (launch ``dsa_index_scores``; twin
                   ``index_scores_xla``): a row's queries (one 8-token
                   packing slot, or the launch's ``block_q`` tokens)
                   against its indexer keys, a block of pages an
                   iteration, ``I`` out in float32. A row whose context
                   is at most ``topk`` selects everything and is not
                   scanned.
``select_tokens``  the SELECTION (launch ``dsa_select_tokens``; twin
                   ``select_tokens_xla``): a row's queries 8 at a time,
                   each tile among the scores of the row's OWN pages,
                   read into VMEM once as order-preserving integers. The
                   ``topk``-th largest key of every query is found bit
                   by bit (``BITS`` a pass, a pass a compare-and-count
                   over the resident keys), ties are kept from the lower
                   key by a count carried across the pages, and the
                   tile's MASK WORDS are written straight out. A query
                   below ``topk`` keeps every key in view and reads no
                   score.
``token_walk``     the WALK (launch ``ragged_paged_attention_tokens``;
                   twin ``token_walk_xla``): the online-softmax page walk
                   of ``ragged_paged_attention`` under a per-(query
                   position, key) mask. It reads every page of the row's
                   context: with seeded weights the kept tokens of a
                   20k-token context lie in every one of its pages (~13
                   a page), so a walk by page has nothing to skip; a
                   gather by token is 2 x 4 copies of 256 bytes a token
                   from a head-major pool.

THE MASK WORDS ``(PW, T, page)`` int32, ``PW = ceil(pps / 32)``: key
``s`` of a row, in its logical page ``p = s // page`` at offset ``k``,
is bit ``p // PW`` of word ``[p % PW, t, k]``. A page's ``page`` keys
are one bit of ``page`` consecutive words: the selection ORs a page's
shifted 0 / 1 mask into plane ``p % PW``, and the walk takes that plane
(a dynamic index on a leading dimension), shifts by ``p // PW`` and has
the page's mask with no gather and no lane shuffle.
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
#: scored as one block); a longer row goes a page at a time; the
#: selection's key planes of one ``kbuf`` entry, whatever the row. 8: a
#: decode row holds 100-200 pages at the benchmark's contexts, and the
#: selected walk's probe (CHANGES.md, PR 45) read 0.131 us a page at 8
#: against 0.167 at 4 on lists a quarter as long
KV_PAGES = 8
#: lanes of a vreg: the stored width of an indexer key is whole tiles
LANES = 128
#: bits of the key a pass of the selection's search settles (1, 2 or
#: 4: ``2^BITS - 1`` candidates a pass, up to ``32 / BITS`` passes). 2:
#: a pass ends in a reduction across lanes, a branch on its result and
#: the next candidates' broadcast, which a 16-row decode step pays 16
#: times a pass; on the v5e (PERF.md section 5, PR 50) a launch of 16
#: one-token rows at 13k-26k keys takes 143 us at 2 against 160 at 1, a
#: 256-token row at 8k / 21k keys 290 / 415 against 343 / 458; 4 bits
#: (15 candidates a pass) read 2304-21248 keys 1.3-2.2 x slower than 2
BITS = 2
#: entries of ``kbuf`` a step of the search's counting loop reads (the
#: loop's scalar work and register moves, once a step, cost as much as
#: one entry's 24 vector operations: 19 bundles for two entries against
#: 13 for one)
STRIDE = 2
I32_MIN = -(1 << 31)
I32_MAX = (1 << 31) - 1


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


def select_tokens_xla(scores, token_pos, *, page: int, pps: int, topk: int):
    """The selection's twin: every packed token's choice among ALL the
    keys its row's table reaches, ``scores`` (T, >= pps · page) float32
    -> the mask words ``(PW, T, page)`` int32: every key in view up to
    ``topk`` of them, then the ``topk`` best by score; zeros for a
    padding token."""
    cap = pps * page
    seen = ((jnp.arange(cap)[None, :] <= token_pos[:, None])
            & (token_pos >= 0)[:, None])
    kept = seen
    if cap > topk:                          # else no query chooses by score
        kept = choose_tokens(
            jnp.where(seen, scores[:, :cap], -jnp.inf), topk) & seen
        kept = jnp.where((token_pos < topk)[:, None], seen, kept)
    return pack_words(kept, page=page, pps=pps)


def _select_kernel(page, pps, topk, *refs):
    """Grid (R,): row ``r``'s queries, ``SHORT`` positions a TILE, each
    tile among the keys of the row's own pages.

    A tile's scores are copied into a slot of ``kbuf`` a block of
    ``KV_PAGES`` pages an entry, all copies in flight at once and
    started while the tile BEFORE it (of this row or of the row before)
    is searched, then turned, in place, into order-preserving integers
    (kept in the float32 they came as), MIN where a query does not see
    the key: ``KV_PAGES`` PLANES ``(SHORT, page)`` an entry. A row of
    more tokens than one: a plane is a page, a query a sublane. A
    ONE-TOKEN row: a plane holds eight pages of its one query, a page a
    sublane, packed to the front of the slot (no plane carries seven
    dead sublanes through the search). Between the two forms' loading
    and writing the SEARCH is shared: up to ``32 / BITS`` passes, each
    counting the keys that reach ``2^BITS - 1`` candidates, find every
    sublane's ``topk``-th largest key and how many keys lie above it;
    it stops at the pass after which EXACTLY ``topk`` keys reach every
    choosing query's candidate (then the keys that reach it are kept;
    only where more reach a fully settled kth are ties ranked). The
    tile's words leave ``wacc`` under the next tile's search."""
    (kv_lens_ref, q_lens_ref, q_starts_ref, sc_hbm, _, out_hbm, kbuf, wacc,
     tri, kth_ref, room_ref, flying, sem_s, sem_o) = refs
    r = pl.program_id(0)
    pw = wacc.shape[0]
    span = KV_PAGES * page
    plane = (SHORT, page)
    rows = pl.num_programs(0)

    def plan(row, j):
        """Tile ``j`` of ``row``: its first token and first position,
        the row's key blocks, and whether a live query of the tile
        chooses by score (else nothing of its scores is read)."""
        kv_len, q_len = kv_lens_ref[row], q_lens_ref[row]
        nb = jnp.minimum(_n_valid_pages(kv_len, page), pps)
        first = kv_len - q_len + j * SHORT
        need = first + jnp.minimum(q_len - j * SHORT, SHORT) > topk
        return (pl.multiple_of(q_starts_ref[row] + j * SHORT, SHORT), first,
                jax.lax.div(nb + KV_PAGES - 1, KV_PAGES), need)

    q_len = q_lens_ref[r]
    one = q_len == 1
    ntile = jax.lax.div(q_len + SHORT - 1, SHORT)
    nblk = plan(r, 0)[2]
    ngrp = jax.lax.div(nblk + KV_PAGES - 1, KV_PAGES)   # of a one-token row
    sub = jax.lax.broadcasted_iota(jnp.int32, plane, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, plane, 1)

    @pl.when(r == 0)
    def _first():
        flying[0] = 0                     # a tile's words are on their way out
        flying[1] = 0                     # the kbuf slot of the next tile
        flying[2] = -1                    # (row, tile) whose scores are on
        flying[3] = -1                    # their way into that slot
        tri[...] = (                      # tri[k, l] = k <= l
            jax.lax.broadcasted_iota(jnp.int32, (page, page), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (page, page), 1)
        ).astype(tri.dtype)

    def lanes(u):
        return slice(u * page, (u + 1) * page)

    def as_int(x):                        # kbuf is float32, as the copies are
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def as_float(x):
        return jax.lax.bitcast_convert_type(x, jnp.float32)

    def int_keys(b, seen):
        """A float32's bits -> the int32 of the same order (-0.0 as
        +0.0), MIN where not ``seen``."""
        b = jnp.where(b == I32_MIN, 0, b)
        return jnp.where(seen, jnp.where(b < 0, b ^ I32_MAX, b), I32_MIN)

    def scores(slot, t0, blocks, arrive):
        """The scores of the tile at token ``t0`` against its row's
        ``blocks`` key blocks into ``kbuf[slot]``: started, or awaited
        (a slice of fewer rows than a tile is no copy Mosaic makes: a
        one-token row's seven other rows come along)."""
        def copy(i, _):
            cp = pltpu.make_async_copy(
                sc_hbm.at[pl.ds(t0, SHORT),
                          pl.ds(pl.multiple_of(i * span, span), span)],
                kbuf.at[slot, i], sem_s.at[slot])
            cp.wait() if arrive else cp.start()
            return 0

        jax.lax.fori_loop(0, blocks, copy, 0)

    def rank_of(tie):
        """A tie's place among its sublane's ties: a product with a
        triangle of ones (0 / 1 both sides: exact)."""
        return jnp.dot(tie.astype(tri.dtype), tri[...],
                       preferred_element_type=jnp.float32)

    def tile(j, _):
        t0, first, _, need = plan(r, j)
        live = j * SHORT + sub < q_len
        pos = first + jnp.where(one, 0, sub)
        slot = flying[1]
        kb = kbuf.at[slot]

        @pl.when(need)
        def _scores():
            @pl.when(jnp.logical_or(flying[2] != r, flying[3] != j))
            def _now():
                scores(slot, t0, nblk, False)

            scores(slot, t0, nblk, True)

        # the next tile's scores, of this row or of the row after it,
        # come in under this tile's search
        last = j + 1 == ntile
        after = jnp.minimum(r + 1, rows - 1)
        row2, j2 = jnp.where(last, after, r), jnp.where(last, 0, j + 1)
        t2, _, blocks2, need2 = plan(row2, j2)
        ahead = jnp.logical_and(need2, jnp.logical_or(
            jnp.logical_not(last),
            jnp.logical_and(r + 1 < rows, q_lens_ref[after] > 0)))
        flying[1] = 1 - slot
        flying[2] = jnp.where(ahead, row2, -1)
        flying[3] = j2
        pl.when(ahead)(lambda: scores(1 - slot, t2, blocks2, False))

        def seen_one(q):
            """Plane ``q`` of a one-token row: pages ``8q .. 8q + 7``."""
            return (q * KV_PAGES + sub) * page + lane <= pos

        def seen_tile(p):
            return (p * page + lane <= pos) & live

        @pl.when(jnp.logical_and(need, one))
        def _keys_one():
            def group(g, _):
                # entries 8g .. 8g + 7, a plane each, into entry g
                planes = []
                for u in range(KV_PAGES):
                    q = g * KV_PAGES + u
                    at = jnp.minimum(q, nblk - 1)
                    dense = kb[at, :, lanes(0)]     # row 0 in sublane 0
                    for v in range(1, KV_PAGES):  # a sublane broadcast each
                        dense = jnp.where(
                            sub == v, kb[at, 0:1, lanes(v)], dense)
                    planes.append(int_keys(as_int(dense), seen_one(q)))
                for u, keys in enumerate(planes):
                    kb[g, :, lanes(u)] = as_float(keys)
                return 0

            jax.lax.fori_loop(0, ngrp, group, 0)

        @pl.when(jnp.logical_and(need, jnp.logical_not(one)))
        def _keys_tile():
            def block(g, _):
                for u in range(KV_PAGES):
                    kb[g, :, lanes(u)] = as_float(int_keys(
                        as_int(kb[g, :, lanes(u)]),
                        seen_tile(g * KV_PAGES + u)))
                return 0

            jax.lax.fori_loop(0, nblk, block, 0)

        # --- the search: a sublane's kth key, bit by bit from the top
        cands = range(1, 1 << BITS)
        chooses = (pos >= topk) & (jnp.where(
            one, need.astype(jnp.int32), live.astype(jnp.int32)) > 0)

        def one_pass(carry):
            i, kth, above, reach, _ = carry             # (SHORT, 1) each
            shift = 32 - BITS * (i + 1)
            tried = [jnp.broadcast_to(
                (kth | jax.lax.shift_left(jnp.int32(c), shift)) ^ I32_MIN,
                plane) for c in cands]

            def count(step, at, accs):
                # ``step`` entries from ``at``; KV_PAGES running counts a
                # candidate, a plane each: no sum waits for another
                for b in range(step):
                    keys = as_int(kb[at + b])
                    accs = tuple(
                        tuple(a + (keys[:, lanes(u)] >= c).astype(jnp.int32)
                              for u, a in enumerate(acc))
                        for acc, c in zip(accs, tried))
                return accs

            n = jnp.where(one, ngrp, nblk)
            whole = jax.lax.div(n, STRIDE)
            accs = jax.lax.fori_loop(
                0, whole, lambda i, a: count(STRIDE, i * STRIDE, a),
                tuple(tuple(jnp.zeros(plane, jnp.int32)
                            for _ in range(KV_PAGES)) for _ in cands))
            accs = jax.lax.fori_loop(
                whole * STRIDE, n, functools.partial(count, 1), accs)
            cnts = []                     # float32: exact below 2^24
            for acc in accs:
                acc = list(acc)
                while len(acc) > 1:           # a tree, not a chain
                    acc = [x + y for x, y in zip(acc[::2], acc[1::2])]
                cnt = jnp.sum(acc[0].astype(jnp.float32), axis=1,
                              keepdims=True)
                cnts.append(jnp.where(
                    one, jnp.sum(cnt, axis=0, keepdims=True), cnt))
            # ``topk`` keys reach the candidates up to ``digit``: kth is
            # the largest of them in the last pass that has one, kth + 1
            # the smallest of the others in the last pass that has one
            digit = jnp.zeros((SHORT, 1), jnp.int32)
            for cnt in cnts:
                reach = jnp.where(cnt >= topk, cnt, reach)
                digit = digit + (cnt >= topk).astype(jnp.int32)
            for cnt in reversed(cnts):
                above = jnp.where(cnt >= topk, above, cnt)
            kth = kth | jax.lax.shift_left(digit, jnp.full_like(digit, shift))
            # a query whose candidate EXACTLY topk keys reach is done:
            # it keeps the keys that reach it, whatever the lower bits
            todo = jnp.sum(jnp.where(
                chooses & (jnp.broadcast_to(reach, plane) != topk), 1, 0
            )[:, :1])
            return i + 1, kth, above, reach, todo

        kth = jnp.zeros((SHORT, 1), jnp.int32)
        none = jnp.zeros((SHORT, 1), jnp.float32)
        _, kth, above, _, todo = jax.lax.while_loop(
            lambda c: jnp.logical_and(c[0] < 32 // BITS, c[4] > 0),
            one_pass, (0, kth, none, none, need.astype(jnp.int32)))
        kth_ref[...] = jnp.broadcast_to(kth ^ I32_MIN, plane)
        room_ref[...] = jnp.broadcast_to(topk - above, plane)
        # every bit settled and still more than topk keys reach some
        # query's kth: its ties are kept by their place
        ranked = todo > 0

        # --- the mask words (the tile's before them, of this row or of
        # one before it, leave ``wacc`` under this tile's search)
        landed(t0)
        wacc[...] = jnp.zeros(wacc.shape, jnp.int32)
        low = pos < topk

        def kept_of(k, seen, before, ahead=None):
            """The plane's kept keys (int32 0 / 1) and the ties ahead
            of the next plane; ``before`` ties ahead of this one (None:
            every tie is kept); ``ahead(ties)``: the ties of the
            sublanes before each one, where the plane's sublanes are
            one query's."""
            kth = kth_ref[...]
            if before is None:
                return (seen & (low | (k >= kth))).astype(jnp.int32), None
            tie = k == kth
            rank = rank_of(tie)
            ties = rank[:, page - 1:page]
            if ahead is None:
                after = before + ties
            else:
                after = before + jnp.sum(ties, axis=0, keepdims=True)
                before = before + ahead(ties)
            kept = (k > kth) | (
                tie & (before + rank <= room_ref[...]))
            return (seen & (low | kept)).astype(jnp.int32), after

        def put(m, kept):
            """``kept``: KV_PAGES arrays (rows, page) of pages ``8m ..
            8m + 7`` into the words of the tile's first ``rows``
            tokens."""
            rows = kept[0].shape[0]
            if pw % KV_PAGES:                 # a narrow table: page by page
                for v, k in enumerate(kept):
                    p = m * KV_PAGES + v
                    q = jax.lax.rem(p, pw)
                    wacc[q, :rows] = wacc[q, :rows] | jax.lax.shift_left(
                        k, jnp.full_like(k, jax.lax.div(p, pw)))
                return
            # the block's pages are consecutive planes at one bit
            first = m * KV_PAGES
            at = pl.ds(pl.multiple_of(jax.lax.rem(first, pw), KV_PAGES),
                       KV_PAGES)
            new = jnp.stack(kept)
            wacc[at, :rows] = wacc[at, :rows] | jax.lax.shift_left(
                new, jnp.full_like(new, jax.lax.div(first, pw)))

        def ahead(ties):
            wide = jnp.broadcast_to(ties, plane)
            upto = wide                       # a running sum down the sublanes
            for d in (1, 2, 4):
                upto = upto + jnp.where(sub >= d, pltpu.roll(upto, d, 0), 0.0)
            return upto - wide

        def words_one(by_place):
            def group(g, before):
                for u in range(KV_PAGES):
                    q = g * KV_PAGES + u
                    kept, before = kept_of(
                        as_int(kb[g, :, lanes(u)]), seen_one(q), before,
                        ahead if by_place else None)
                    put(q, [kept[v:v + 1] for v in range(KV_PAGES)])
                return before

            jax.lax.fori_loop(
                0, ngrp, group,
                jnp.zeros((1, 1), jnp.float32) if by_place else None)

        def words_tile(by_place):
            def block(g, before):
                kept = []
                for u in range(KV_PAGES):
                    mine, before = kept_of(
                        as_int(kb[g, :, lanes(u)]),
                        seen_tile(g * KV_PAGES + u), before)
                    kept.append(mine)
                put(g, kept)
                return before

            jax.lax.fori_loop(
                0, nblk, block,
                jnp.zeros((SHORT, 1), jnp.float32) if by_place else None)

        for form, words in ((one, words_one),
                            (jnp.logical_not(one), words_tile)):
            for by_place in (False, True):
                pl.when(jnp.logical_and(form, ranked == by_place))(
                    functools.partial(words, by_place))

        out(t0).start()
        flying[0] = 1
        return 0

    def out(t0):
        return pltpu.make_async_copy(
            wacc, out_hbm.at[:, pl.ds(t0, SHORT)], sem_o.at[0])

    def landed(t0):
        @pl.when(flying[0] > 0)
        def _():
            out(t0).wait()                # (every tile's copy is as large)
            flying[0] = 0

    jax.lax.fori_loop(0, ntile, tile, 0)
    pl.when(r == rows - 1)(functools.partial(landed, 0))


@functools.lru_cache(maxsize=64)
def _build_select(r, pps, t, page, topk, interpret):
    """The selection's pallas_call: ``(kv_lens, q_lens, q_starts, I (T,
    width) float32, zeros (PW, T, page) int32) -> [words]``, the words
    written over the zeros (no row visits a token outside the batched
    rows' blocks)."""
    assert KV_PAGES == SHORT        # a one-token row's plane: a page a sublane
    pw = word_planes(pps)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(r,),
        in_specs=[any_, any_],
        out_specs=[any_],
        scratch_shapes=[
            pltpu.VMEM((2, -(-pps // KV_PAGES), SHORT, KV_PAGES * page),
                       jnp.float32),                               # kbuf
            pltpu.VMEM((pw, SHORT, page), jnp.int32),              # wacc
            pltpu.VMEM((page, page), jnp.float32),                 # tri
            pltpu.VMEM((SHORT, page), jnp.int32),                  # kth
            pltpu.VMEM((SHORT, page), jnp.float32),                # room
            pltpu.SMEM((4,), jnp.int32),                           # flying
            pltpu.SemaphoreType.DMA((2,)),                         # sem_s
            pltpu.SemaphoreType.DMA((1,)),                         # sem_o
        ],
    )
    return shmem_call(
        functools.partial(_select_kernel, page, pps, topk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((pw, t, page), jnp.int32)],
        collective_id=None,
        input_output_aliases={4: 0},
        interpret=local_interpret() if interpret is None else interpret,
        name="dsa_select_tokens",
        dimension_semantics=("arbitrary",),
    )


@functools.partial(
    jax.jit, static_argnames=("page", "pps", "topk", "interpret"))
def select_tokens(scores, kv_lens, q_lens, q_starts, *, page: int,
                  pps: int, topk: int, interpret=None):
    """``scores`` (T, >= pps · page) float32 from ``index_scores`` (read
    only where a live query past ``topk`` has a key in view: the pages
    of its OWN row's context) -> the mask words ``(PW, T, page)`` int32
    of the step: for each live query position the ``topk`` largest of
    its scores, ties to the lower key; every key in view for a query at
    a position below ``topk``; zeros for every other token."""
    assert topk % page == 0, (topk, page)
    t = scores.shape[0]
    call = _build_select(kv_lens.shape[0], pps, t, page, topk, interpret)
    return call(kv_lens, q_lens, q_starts, scores,
                jnp.zeros((word_planes(pps), t, page), jnp.int32))[0]


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
