"""ServingState: the explicit, donated, page-table-addressed decode state.

Per-layer page pools, the block table and the per-slot lengths are ONE
object with one placement story — what ``Transformer.serving_step``
takes, donates and returns, and what the continuous-batching engine
owns:

* **page pools** per layer — ``(npages, Hkv, page, D)`` (int8
  ``{"q","scale"}`` dicts under ``kv_quant``), sharded over the KV-HEAD
  dim on the tp axis. Head sharding (not sequence sharding) is the
  serving layout: GQA heads are independent, so ranks
  never exchange LSE partials, and a request's pages live wholly in the
  shared pool — any rank can serve any mix of requests, which is what
  admission/eviction over one free list requires.
* **block table** ``(slots, pages_per_seq)`` int32 — pool page ids per
  request slot, replicated (it is scheduler metadata, bytes-tiny).
* **kv_lens** ``(slots,)`` int32 — per-slot lengths *including* the
  step currently in flight (the ragged kernel attends append-then-
  attend).
* **cursors** ``(slots,)`` int32 — per-request progress (prompt tokens
  consumed + tokens generated); the device-side mirror of the
  scheduler's cursor so an evicted request's resume point travels with
  the state object.

* **ring pools** (PR 29) — a model with SLIDING-WINDOW attention
  layers (``TransformerConfig.layer_attn``) gives each of them a pool
  of its own, ``(slots · ring, Hkv, page, D)``: slot ``s`` owns pages
  ``[s·ring, (s+1)·ring)`` for good and writes logical page ``j`` of
  its sequence to page ``s·ring + j % ring``. ``ring_table`` states
  exactly that in the block table's shape, built once on the device;
  it is arithmetic, not allocation — no host allocator, no upload, and
  eviction or preemption owe it nothing, because a ring holds nothing
  a recompute does not rewrite before it reads. ``ring_pages`` is the
  least ``ring`` whose live positions never alias.

* **recurrent states and compressed keys** (PR 33) — a LIGHTNING
  (linear-attention) layer keeps no K/V pages: its entry of ``layers``
  is ``None`` and ``recurrent[i]`` is its state, ``(slots, heads,
  head_dim, head_dim)`` float32, one ``head_dim x head_dim`` matrix a
  slot and head. The step reads and writes the matrices of the slots it
  batches and starts a slot whose span begins at position 0 from zero,
  so admission, eviction and slot reuse owe it nothing: a recompute
  rebuilds it as it rebuilds pages. A BLOCK-SPARSE attention layer
  keeps, beside its K/V pools, ``ckeys[i]``: ``(npages, Hkv, page //
  stride, D)``, compressed key ``j`` (the mean of keys ``[j·stride,
  j·stride + kernel)``) in the page and row of its first token, so the
  block table addresses it and the allocator need not know of it. A
  compressed key is written in the step that appends its last token
  and never read before that.

* **a state of two parts** (PR 41) — a KDA (gated delta-rule linear
  attention) layer keeps no K/V pages either: its entry of ``layers``
  is ``None`` and ``recurrent[i]`` is the PAIR ``(matrix, tail)``: the
  delta rule's state ``(slots, kda_heads, head_dim, head_dim)`` float32
  (``kernels/kda_attention.py`` reads and writes the matrices of the
  slots a step batches, in place) and the short convolution's tail
  ``(slots, kda_conv - 1, 3 · kda_heads · head_dim)`` float32, the last
  pre-activation rows of [q | k | v] the slot's last step left, which
  the first tokens of its next span convolve with
  (``Transformer._kda_inputs``). Both follow the lightning contract: a
  span from position 0 starts from zeros whatever the slot held, a slot
  that is not batched keeps both as they are, and a recompute rebuilds
  both. What would need a SNAPSHOT of either (prefix cache, a rolled-
  back draft, page shipping) is refused by name
  (``serving/engine.py:REFUSED["recurrent"]``).

* **latent pools** (PR 35) — a model with LATENT attention
  (``TransformerConfig.kv_latent``) keeps, per layer, ONE array and no
  V pool: ``layers[i] = (pool, None)`` with ``pool`` ``(npages, 1,
  page, latent_stored)``, one entry a token for ALL the heads, the
  RMS-normed latent ``c_kv`` (``kv_latent`` values), the rotated key
  every head shares (``qk_rope_dim``) and zeros up to whole 128-lane
  tiles. The block table addresses it as it does K/V pages, so the
  allocator, admission, eviction and re-prefill need nothing new; the
  step appends one stream (``kv_append_latent``) and every head walks
  the same entries absorbed (``ragged_paged_attention(latent=)``).

The object is a pytree (``jax.tree_util``): the serving-step jit
donates it whole, and with the pool placements pinned the per-step
append aliases in place — no pool-sized copy per step.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, replace as _dc_replace

import jax
import numpy as np


@dataclass(frozen=True)
class ServingState:
    """One engine's device-resident serving state (see module docs)."""

    # per layer (k_pool, v_pool), dicts under kv_quant; (pool, None) a
    # latent layer; None a lightning or kda layer
    layers: tuple
    block_table: object  # (slots, pages_per_seq) int32
    kv_lens: object      # (slots,) int32 — includes the in-flight step
    cursors: object      # (slots,) int32
    page: int = 0        # static: rows per page
    # static: context-parallel shards of the pool. Under cp > 1 the
    # pool rows are ONE stacked allocation of cp per-shard pools (shard
    # r owns global page ids [r·npages/cp, (r+1)·npages/cp)) and the
    # block-table columns split the same way: logical page index p of a
    # sequence lives in shard min(p // (pages_per_seq/cp), cp-1), so a
    # long request's KV spreads over every shard while the table keeps
    # GLOBAL ids and the scatter-append stays shard-oblivious.
    cp: int = 1
    # sliding-window layers (static indices into ``layers``): their
    # pools are rings of ``ring`` pages a slot addressed by
    # ``ring_table`` (slots, pages_per_seq), not by ``block_table``
    ring_table: object = None
    window_layers: tuple = ()
    ring: int = 0
    # per layer (or ``()``): a lightning layer's recurrent state, a kda
    # layer's pair (state matrix, convolution tail), a block-sparse
    # layer's compressed-key pool, None elsewhere; a lightning or kda
    # layer's entry of ``layers`` is None
    recurrent: tuple = ()
    ckeys: tuple = ()

    def replace(self, **kw) -> "ServingState":
        return _dc_replace(self, **kw)

    @property
    def slots(self) -> int:
        return int(self.block_table.shape[0])

    @property
    def pages_per_seq(self) -> int:
        return int(self.block_table.shape[1])

    @property
    def pages_per_shard(self) -> int:
        """Block-table columns owned by one cp shard."""
        return self.pages_per_seq // max(self.cp, 1)

    @property
    def npages(self) -> int:
        """Pages of a GLOBAL layer's pool (what ``block_table`` and the
        host allocator address); a window layer's holds
        ``slots · ring``."""
        i = next((i for i in range(len(self.layers))
                  if i not in self.window_layers
                  and self.layers[i] is not None), 0)
        return self.layer_pages(i)

    def layer_pages(self, i: int) -> int:
        """Pages of layer ``i``'s pool."""
        k0 = self.layers[i][0]
        return int((k0["q"] if isinstance(k0, dict) else k0).shape[0])

    @property
    def capacity(self) -> int:
        """Max sequence positions one slot can hold."""
        return self.pages_per_seq * self.page


def _flatten(s: ServingState):
    return (
        (s.layers, s.block_table, s.kv_lens, s.cursors, s.ring_table,
         s.recurrent, s.ckeys),
        (s.page, s.cp, s.window_layers, s.ring),
    )


def _unflatten(aux, children):
    layers, table, lens, cursors, ring_table, recurrent, ckeys = children
    return ServingState(
        layers=layers, block_table=table, kv_lens=lens, cursors=cursors,
        page=aux[0], cp=aux[1], ring_table=ring_table,
        window_layers=aux[2], ring=aux[3], recurrent=recurrent,
        ckeys=ckeys,
    )


jax.tree_util.register_pytree_node(ServingState, _flatten, _unflatten)


def ring_pages(chunk: int, window: int, page: int) -> int:
    """Pages of one slot's ring: a step appends at most ``chunk``
    tokens and then attends back ``window - 1`` positions from the first
    of them, so ``chunk + window - 1`` consecutive positions are live at
    once; they lie in at most ``ceil((chunk + window - 1) / page) + 1``
    pages (a run that starts at a page's last row), and a ring that
    long maps them to distinct pages."""
    return -(-(chunk + window - 1) // page) + 1


def ring_table(slots: int, pages_per_seq: int, ring: int):
    """``(slots, pages_per_seq)`` int32, ``[s, j] = s·ring + j % ring``:
    the block table of a ring pool, made on the device."""
    import jax.numpy as jnp

    s = jnp.arange(slots, dtype=jnp.int32)[:, None]
    j = jnp.arange(pages_per_seq, dtype=jnp.int32)[None, :]
    return s * ring + j % ring


def fresh_table(slots: int, pages_per_seq: int) -> np.ndarray:
    """Host-side table template (-1 = unallocated; device consumers
    clamp, the allocator never reads a -1 back)."""
    return np.full((slots, pages_per_seq), -1, np.int32)


class PagePool:
    """Host-side page allocator with PER-PAGE REFCOUNTS and an optional
    prefix cache (the PR-6 follow-on the block tables already made
    expressible).

    Three page states:

    * **free** — on the free list, content garbage;
    * **held** — ``refs[pg] >= 1``: referenced by that many block-table
      rows (shared-prefix pages are held by several slots at once; the
      engine's eviction *decrements* instead of freeing);
    * **cached** — ``refs[pg] == 0`` but the page is registered in the
      prefix cache: its KV content (a pure function of the token prefix
      it froze under — the chain hash) stays resident so a re-admitted
      evicted request, or a new request sharing the prefix, can reattach
      it instead of recomputing. Cached pages are *reclaimable*: when
      the free list runs dry the least-recently-released cached page is
      unregistered and reused, so the cache never shrinks the pool.

    Only FULL pages are ever registered (a page's content is frozen the
    moment the owning request's cursor crosses its end — nothing writes
    a page below the cursor), so a cached page's bytes can never change
    while it sits in the cache.
    """

    def __init__(self, npages: int, page: int, *, prefix_cache: bool = False):
        self.npages = int(npages)
        self.page = int(page)
        self.prefix_cache = bool(prefix_cache)
        self.refs = np.zeros((npages,), np.int32)
        self.free: list = list(range(npages - 1, -1, -1))
        self._by_hash: dict = {}              # chain hash -> page id
        self._hash_of: dict = {}              # page id -> chain hash
        self._reclaim: OrderedDict = OrderedDict()   # refcount-0 cached, LRU

    @property
    def available(self) -> int:
        """Pages an allocation may claim: free + reclaimable-cached."""
        return len(self.free) + len(self._reclaim)

    @property
    def held_pages(self) -> int:
        """Pages some block-table row still references (refs >= 1).
        On an IDLE engine this must be 0 — anything else is a leak
        (the preemption/eviction invariant the multi-tenant chaos
        matrix pins: ``pool.held_pages == 0`` once every stream has
        completed, whatever was preempted mid-draft on the way)."""
        return int((self.refs >= 1).sum())

    def alloc(self, idx: int | None = None) -> int | None:
        """Claim one page (refcount 1), reclaiming the LRU cached page
        when the free list is dry. None when genuinely exhausted.
        ``idx`` — the logical page index within the owning sequence —
        is the cp routing key; a flat pool ignores it."""
        del idx
        if self.free:
            pg = self.free.pop()
        elif self._reclaim:
            pg, _ = self._reclaim.popitem(last=False)
            h = self._hash_of.pop(pg)
            if self._by_hash.get(h) == pg:
                del self._by_hash[h]
        else:
            return None
        assert self.refs[pg] == 0, (pg, self.refs[pg])
        self.refs[pg] = 1
        return pg

    def retain(self, pg: int) -> None:
        """One more block-table row references ``pg`` (prefix share, or
        resurrection of a cached page)."""
        if pg in self._reclaim:
            del self._reclaim[pg]
        self.refs[pg] += 1

    def release(self, pg: int) -> None:
        """Drop one reference; the page frees (or parks in the cache)
        only when the LAST reference drops — shared-prefix pages survive
        their co-holders' evictions."""
        assert self.refs[pg] >= 1, (pg, self.refs[pg])
        self.refs[pg] -= 1
        if self.refs[pg] == 0:
            if pg in self._hash_of:
                self._reclaim[pg] = None
            else:
                self.free.append(pg)

    def register(self, pg: int, chain_hash) -> None:
        """Publish a FROZEN full page under its prefix-chain hash. First
        registration wins; a second page with identical content simply
        stays private (no post-hoc dedup — the bytes are already paid)."""
        if not self.prefix_cache or chain_hash in self._by_hash:
            return
        self._by_hash[chain_hash] = pg
        self._hash_of[pg] = chain_hash

    def lookup(self, chain_hash, idx: int | None = None) -> int | None:
        """The resident page holding this prefix page, or None. ``idx``
        routes the probe to the owning cp shard; a flat pool ignores
        it."""
        del idx
        return self._by_hash.get(chain_hash)

    @property
    def headroom(self) -> int:
        """Pages ANY set of allocations can count on, wherever their
        logical indices fall (a cp pool: its fullest shard's)."""
        return self.available

    def can_hold(self, held: int, need: int) -> bool:
        """Whether growing a sequence from ``held`` to ``need`` pages
        can be satisfied — the allocation gate the protocol's ``alloc``
        verb asks before claiming anything (a cp pool answers per
        owning shard; a flat pool is a simple headroom check)."""
        return need - held <= self.available

    def clone(self) -> "PagePool":
        """Deep-copy the allocator state (servlint world forking)."""
        q = PagePool.__new__(PagePool)
        q.npages = self.npages
        q.page = self.page
        q.prefix_cache = self.prefix_cache
        q.refs = self.refs.copy()
        q.free = list(self.free)
        q._by_hash = dict(self._by_hash)
        q._hash_of = dict(self._hash_of)
        q._reclaim = OrderedDict(self._reclaim)
        return q


class CpPagePool:
    """Context-parallel page allocator: ``cp`` per-shard
    :class:`PagePool` instances behind ONE global page-id namespace.

    Shard ``s`` owns global page ids ``[s·npages_shard,
    (s+1)·npages_shard)`` — the same rows of the stacked device pool —
    and logical page index ``idx`` of any sequence is owned by shard
    ``min(idx // pages_per_shard, cp-1)``, mirroring the block-table
    column split. Appends therefore always land on the owning shard
    (``alloc`` routes by ``idx``), releases route by the global id's
    shard, and the prefix cache registers/looks up within the owning
    shard (a prefix page at logical index p re-attaches on the shard
    that held it — position determines owner, so the probe is exact).

    The combined read-only views (``refs``/``free``/``_reclaim``/
    ``_hash_of``/``_by_hash``, all in GLOBAL ids) exist for the
    invariant checkers (servlint SV001/SV002 and the engine's leak
    asserts), which see one coherent allocator regardless of cp.
    """

    def __init__(self, cp: int, npages: int, page: int,
                 pages_per_shard: int, *, prefix_cache: bool = False):
        assert cp >= 2, cp
        self.cp = int(cp)
        self.npages_shard = int(npages)
        self.npages = int(cp) * int(npages)     # TOTAL pages
        self.page = int(page)
        self.pages_per_shard = int(pages_per_shard)
        self.prefix_cache = bool(prefix_cache)
        self.shards = tuple(
            PagePool(npages, page, prefix_cache=prefix_cache)
            for _ in range(self.cp)
        )

    # ---- routing

    def owner_of(self, idx: int) -> int:
        """Logical page index within a sequence → owning shard."""
        return min(int(idx) // self.pages_per_shard, self.cp - 1)

    def shard_of(self, pg: int) -> int:
        """Global page id → owning shard."""
        return int(pg) // self.npages_shard

    # ---- combined views (global ids)

    @property
    def refs(self):
        return np.concatenate([s.refs for s in self.shards])

    @property
    def free(self) -> list:
        return [
            i * self.npages_shard + lp
            for i, s in enumerate(self.shards) for lp in s.free
        ]

    @property
    def _reclaim(self) -> OrderedDict:
        out = OrderedDict()
        for i, s in enumerate(self.shards):
            for lp in s._reclaim:
                out[i * self.npages_shard + lp] = None
        return out

    @property
    def _hash_of(self) -> dict:
        return {
            i * self.npages_shard + lp: h
            for i, s in enumerate(self.shards)
            for lp, h in s._hash_of.items()
        }

    @property
    def _by_hash(self) -> dict:
        return {
            h: i * self.npages_shard + lp
            for i, s in enumerate(self.shards)
            for h, lp in s._by_hash.items()
        }

    @property
    def available(self) -> int:
        """Total claimable pages across shards — an UPPER bound for any
        one sequence (growth routes to owners; :meth:`can_hold` is the
        exact per-shard gate)."""
        return sum(s.available for s in self.shards)

    @property
    def held_pages(self) -> int:
        return sum(s.held_pages for s in self.shards)

    # ---- allocator verbs

    def alloc(self, idx: int | None = None) -> int | None:
        """Claim one page ON THE SHARD OWNING logical index ``idx``
        (None routes to shard 0 — only correct for idx-agnostic
        callers that never coexist with cp, asserted away)."""
        assert idx is not None, "cp pool allocation needs the page index"
        s = self.owner_of(idx)
        lp = self.shards[s].alloc()
        return None if lp is None else s * self.npages_shard + lp

    def retain(self, pg: int) -> None:
        s = self.shard_of(pg)
        self.shards[s].retain(pg - s * self.npages_shard)

    def release(self, pg: int) -> None:
        s = self.shard_of(pg)
        self.shards[s].release(pg - s * self.npages_shard)

    def register(self, pg: int, chain_hash) -> None:
        s = self.shard_of(pg)
        self.shards[s].register(pg - s * self.npages_shard, chain_hash)

    def lookup(self, chain_hash, idx: int | None = None) -> int | None:
        assert idx is not None, "cp pool lookup needs the page index"
        s = self.owner_of(idx)
        lp = self.shards[s].lookup(chain_hash)
        return None if lp is None else s * self.npages_shard + lp

    @property
    def headroom(self) -> int:
        return min(s.available for s in self.shards)

    def can_hold(self, held: int, need: int) -> bool:
        """Exact per-shard gate: pages ``held..need-1`` route to their
        owners; every owner must have the headroom."""
        want = [0] * self.cp
        for p in range(held, need):
            want[self.owner_of(p)] += 1
        return all(
            w <= s.available for w, s in zip(want, self.shards)
        )

    def clone(self) -> "CpPagePool":
        q = CpPagePool.__new__(CpPagePool)
        q.cp = self.cp
        q.npages_shard = self.npages_shard
        q.npages = self.npages
        q.page = self.page
        q.pages_per_shard = self.pages_per_shard
        q.prefix_cache = self.prefix_cache
        q.shards = tuple(s.clone() for s in self.shards)
        return q


def page_chain_hash(prev_hash, tokens) -> int:
    """The prefix-cache key of one FULL page: chains the previous
    page's hash with this page's token ids. KV content of page ``p`` is
    a function of the ENTIRE prefix up to its end (attention mixes every
    earlier token into the residual stream), which is exactly what the
    chain covers."""
    return hash((prev_hash, tuple(int(t) for t in tokens)))
