"""Speculative decoding as a ragged-batch scenario.

Decode pays a full model step per emitted token; the ragged
paged-attention kernel already runs mixed ``q_len`` rows in one launch,
so the verify pass of draft-k speculation is literally a ``q_len=k+1``
row in the normal mixed batch — no new kernel (the Ragged Paged
Attention paper's stated point).

The pieces:

``Drafter``
    Proposes up to ``k`` provisional next tokens for one request from
    information the engine already holds. Implementations must be
    DETERMINISTIC functions of the request's token history — the accept
    rule below only preserves token-exactness because a draft can never
    inject randomness into the stream (wrong drafts are rejected, right
    drafts emit exactly what the keyed sampler would have drawn anyway).

``NGramDrafter``
    Prompt-lookup drafting: match the longest recent n-gram suffix of
    the request's own token history (prompt + generated) against an
    earlier occurrence and propose its continuation. Free (no extra
    model weights, no device work) and strong on motif-heavy traffic.

``DraftModelDrafter``
    A shared-weights TRUNCATED-DEPTH draft model: the target's own
    embedding, first ``depth`` decoder blocks, final norm and lm_head
    (parameter views — no second checkpoint) run as a real greedy
    autoregressive forward. Acceptance now measures how much of the
    target the early layers already determine, which is what makes
    acceptance rates and the adaptive-k budget meaningful.

``SpeculativeEngine``
    A :class:`~triton_distributed_tpu.serving.engine.ServingEngine`
    mode. Steady decode rows (one remaining sequence token) are widened
    to ``[frontier, d_1 .. d_k]`` — the drafts are appended as
    PROVISIONAL page content, verified by the same jitted step as every
    other row (the all-positions-logits twin), and accepted via the
    request-keyed sampler draws:

    for ``j = 0..nd``: sample ``t_j`` from the logits at packed index
    ``q_starts[s] + j`` with the request's draw key
    ``(seed, rid, n0 + j)`` (``n0`` = tokens generated before the
    step); emit ``t_j``; accept draft ``j+1`` iff ``t_j == d_{j+1}``,
    else stop — ``t_j`` is the correction. All drafts accepted → the
    last draw is the bonus token. Because the engine's sampler draws
    are deterministic keyed functions of (seed, rid, position), this
    exact-match rule IS the rejection-sampling identity: every emitted
    token is byte-identical to what the non-speculative engine would
    have produced at that position, so streams stay token-exact across
    chunking, eviction, tp sharding and disaggregation.

    Rejected drafts roll back through the recompute-eviction
    discipline: the cursor rewinds to the surviving prefix and pages
    past it return to the pool. KV above the cursor is garbage the
    same way post-eviction pool pages are — ``kv_lens`` is recomputed
    from host cursors every step, so it is never attended and is
    overwritten by the next append.
"""
from __future__ import annotations

import numpy as np

from triton_distributed_tpu.serving.engine import ServingEngine

# kernel families the speculative engine launches — identical to the
# plain engine's (the verify pass is the SAME ragged kernel; that is
# the point). bench --lint gates that each resolves a degradation
# target so a speculative fleet degrades exactly like a plain one.
SPEC_ENGINE_FAMILIES = ("flash_decode.ragged_paged",)


# ===================================================================
# Drafters
# ===================================================================

class Drafter:
    """Proposes provisional next tokens for one request.

    Contract: ``draft(req, k)`` returns an ``int32`` array of length
    ``<= k`` (empty is always legal — the row degrades to a plain
    decode step). The result must be a deterministic pure function of
    ``req.seq`` (prompt + generated so far): no RNG, no mutable state
    that scheduling order could perturb. ``observe`` is optional
    feedback (accepted/rejected counts) for adaptive drafters; the
    built-ins ignore it."""

    name = "null"

    def draft(self, req, k: int) -> np.ndarray:
        raise NotImplementedError

    def observe(self, req, accepted: int, rejected: int) -> None:
        pass


class NGramDrafter(Drafter):
    """Prompt-lookup drafting over the request's OWN token history.

    Matches the longest suffix n-gram (``max_ngram`` down to
    ``min_ngram``) of ``req.seq`` against its most recent earlier
    occurrence and proposes the tokens that followed it. Rightmost
    match wins — recency beats primacy on repetitive traffic, and the
    tie-break keeps the proposal deterministic."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError((min_ngram, max_ngram))
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def draft(self, req, k: int) -> np.ndarray:
        seq = [int(t) for t in req.seq]
        for n in range(self.max_ngram, self.min_ngram - 1, -1):
            if len(seq) <= n:
                continue
            tail = seq[-n:]
            for i in range(len(seq) - n - 1, -1, -1):
                if seq[i:i + n] == tail:
                    cont = seq[i + n:i + n + k]
                    if cont:
                        return np.asarray(cont, np.int32)
                    break               # suffix matched itself only
        return np.zeros((0,), np.int32)


class TreeDrafter(NGramDrafter):
    """Tree drafting over the request's OWN token history — the
    Medusa-style multi-path proposal the ragged kernel's TREE attention
    topology verifies in ONE row.

    The TRUNK is exactly :class:`NGramDrafter`'s proposal (the most
    recent matching continuation), packed first as a parent chain — so
    a tree row can never accept fewer trunk tokens than the linear
    drafter would have. DIVERGENT continuations from OLDER occurrences
    of the same suffix n-gram then graft sibling branches at their
    divergence points: where the history continues the motif more than
    one way, the tree hedges instead of committing, and the verify walk
    follows whichever child the keyed sample actually draws (the
    "sibling rescue" that beats linear draft-k on branchy traffic).

    ``draft_tree(req, budget)`` returns ``(tokens, parents)`` int32
    arrays of equal length ``<= budget``: ``tokens[i]`` is draft node
    ``i``'s token, ``parents[i]`` its parent NODE index (< i; -1 = the
    frontier). Trunk-first packing (node ``i`` of the trunk has parent
    ``i - 1``) is part of the contract — the engine rewinds the cursor
    to the accepted IN-PLACE prefix, and only trunk nodes sit at their
    true sequence offsets in the pool. Deterministic pure function of
    ``req.seq``, like every drafter."""

    name = "tree"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 branches: int = 2, branch_len: int = 2):
        super().__init__(max_ngram, min_ngram)
        if branches < 0 or branch_len < 1:
            raise ValueError((branches, branch_len))
        self.branches = branches
        self.branch_len = branch_len

    def _continuations(self, seq: list, k: int) -> list:
        """Continuations of the longest matched suffix n-gram, most
        recent occurrence first (the same scan order as
        :meth:`NGramDrafter.draft`, collecting every occurrence)."""
        for n in range(self.max_ngram, self.min_ngram - 1, -1):
            if len(seq) <= n:
                continue
            tail = seq[-n:]
            conts = []
            for i in range(len(seq) - n - 1, -1, -1):
                if seq[i:i + n] == tail:
                    cont = seq[i + n:i + n + k]
                    if cont:
                        conts.append(cont)
            if conts:
                return conts
        return []

    def draft_tree(self, req, budget: int):
        empty = (np.zeros((0,), np.int32), np.zeros((0,), np.int32))
        if budget <= 0:
            return empty
        seq = [int(t) for t in req.seq]
        conts = self._continuations(seq, budget)
        if not conts:
            return empty
        trunk = conts[0][:budget]
        tokens = list(trunk)
        parents = [-1] + list(range(len(trunk) - 1))
        grafted = 0
        for cont in conts[1:]:
            if grafted >= self.branches or len(tokens) >= budget:
                break
            dv = next(
                (d for d in range(min(len(cont), len(trunk)))
                 if cont[d] != trunk[d]),
                None,
            )
            if dv is None:
                continue               # same path — nothing to hedge
            if any(parents[t] == dv - 1 and tokens[t] == cont[dv]
                   for t in range(len(tokens))):
                continue               # this sibling already exists
            par = dv - 1               # divergence hangs off trunk[dv-1]
            added = False
            for tok in cont[dv:dv + self.branch_len]:
                if len(tokens) >= budget:
                    break
                tokens.append(tok)
                parents.append(par)
                par = len(tokens) - 1
                added = True
            grafted += int(added)
        return (np.asarray(tokens, np.int32),
                np.asarray(parents, np.int32))


class DraftModelDrafter(Drafter):
    """A genuinely smaller shared-weights draft model: the target's own
    embedding, its FIRST ``depth`` decoder blocks, final norm and
    lm_head — all parameter VIEWS into the target checkpoint (shared
    embeddings, truncated depth; no second checkpoint shipped) — run as
    a real autoregressive forward. Drafting k tokens is k greedy steps
    of that truncated model, so acceptance tracks how much of the
    target's computation the early layers already determine (the
    adaptive-k budget then has a real signal to walk), instead of the
    fixed bigram table this class used to be.

    Sequences are right-padded to a ``BUCKET``-aligned length so the
    jitted forward compiles once per bucket, not per length; causal
    attention keeps the padding out of every position that is read.
    Deterministic pure function of ``req.seq`` — the drafter contract
    token-exactness rests on."""

    name = "draft_model"

    BUCKET = 16

    def __init__(self, model, params, depth: int | None = None):
        n = len(params["blocks"])
        if depth is None:
            depth = max(1, n // 2)
        if not 1 <= depth <= n:
            raise ValueError(
                f"draft depth must be in [1, {n}], got {depth}")
        self.depth = int(depth)
        self._model = model
        # views, not copies: the draft checkpoint IS the target's
        self._params = {
            "embed": params["embed"],
            "norm_f": params["norm_f"],
            "lm_head": params["lm_head"],
            "blocks": list(params["blocks"][:depth]),
        }
        self._fwd = None

    def _forward(self):
        if self._fwd is None:
            import jax

            self._fwd = jax.jit(self._model.forward)
        return self._fwd

    def _next_token(self, seq: list) -> int:
        ln = len(seq)
        pad = -(-ln // self.BUCKET) * self.BUCKET
        toks = np.zeros((1, pad), np.int32)
        toks[0, :ln] = seq
        logits = np.asarray(self._forward()(self._params, toks))
        return int(np.argmax(logits[ln - 1]))

    def draft(self, req, k: int) -> np.ndarray:
        seq = [int(t) for t in req.seq]
        out = []
        for _ in range(k):
            tok = self._next_token(seq)
            out.append(tok)
            seq.append(tok)
        return np.asarray(out, np.int32)


def make_drafter(kind: str, model=None, params=None, **kw) -> Drafter:
    """Build a drafter by name (``"ngram"`` / ``"tree"`` /
    ``"draft_model"``) — the bench/CI entry point. ``draft_model``
    accepts ``depth`` (the truncated layer count; default half the
    target's); ``tree`` accepts ``branches``/``branch_len``."""
    if kind == "ngram":
        return NGramDrafter(**kw)
    if kind == "tree":
        return TreeDrafter(**kw)
    if kind == "draft_model":
        if model is None or params is None:
            raise ValueError("draft_model drafter needs model + params")
        return DraftModelDrafter(model, params, **kw)
    raise ValueError(f"unknown drafter kind: {kind!r}")


# ===================================================================
# SpeculativeEngine
# ===================================================================

class SpeculativeEngine(ServingEngine):
    """:class:`ServingEngine` with draft-k speculative decode rows.

    Scheduling, admission, eviction, prefix caching, health/probation
    and degradation are all inherited untouched — speculation only
    changes what a steady decode row PACKS (``1 + k`` tokens instead of
    1) and how its logits are consumed (the verify/accept loop in
    :meth:`_advance_row`). With ``spec_k <= 7`` the widened row costs
    no extra packed budget: ``_ceil8(k+1) == _ceil8(1)``.

    ``spec_tree > 0`` switches steady decode rows to TREE verification:
    the drafter's ``draft_tree`` packs up to ``spec_tree`` nodes of a
    draft TREE into one verify row, the row carries a TREE attention-
    topology descriptor (kernels/ragged_paged_attention.py) so sibling
    branches never attend each other, and the accept walk descends the
    tree by the same request-keyed draws — each emitted token is the
    keyed sample at its PATH-conditioned distribution, so streams stay
    byte-identical to the plain engine while branchy traffic accepts
    more tokens per step than any single linear path could."""

    # the verify / accept loop reads the logits after every draft token
    # on the host, and a rejected draft rolls the cursor back: a step's
    # result is down before the next is assembled
    host_logits = True

    def __init__(self, model, params, cfg, *, drafter: Drafter | None = None,
                 spec_k: int = 4, spec_tree: int = 0,
                 adaptive_k: bool = False, **kw):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if spec_k + 1 > cfg.chunk:
            # the chunk bound sizes the kernel's block_q cap and with
            # it the packed array's widest width — a verify row wider
            # than a prefill chunk would invalidate both
            raise ValueError(
                f"spec_k={spec_k} verify row exceeds chunk={cfg.chunk}")
        if spec_tree:
            from triton_distributed_tpu.kernels.ragged_paged_attention \
                import TOPO_MAX_NODES

            if spec_tree + 1 > cfg.chunk:
                raise ValueError(
                    f"spec_tree={spec_tree} verify row exceeds "
                    f"chunk={cfg.chunk}")
            if spec_tree + 1 > TOPO_MAX_NODES:
                raise ValueError(
                    f"spec_tree={spec_tree} exceeds the topology "
                    f"descriptor's {TOPO_MAX_NODES - 1}-node bound")
        self.spec_k = int(spec_k)
        self.spec_tree = int(spec_tree)
        # set before super().__init__: the traffic key (_spec_key) is
        # derived during the base constructor
        super().__init__(model, params, cfg, **kw)
        if drafter is None:
            drafter = TreeDrafter() if spec_tree else NGramDrafter()
        if spec_tree and not hasattr(drafter, "draft_tree"):
            raise ValueError(
                "spec_tree needs a drafter with draft_tree (TreeDrafter)")
        self.drafter = drafter
        # adaptive per-request draft budget: consume the observe()
        # feedback to walk each request's k inside [1, spec_k] — AIMD
        # over the verify outcomes (grow +1 on a clean sweep, shrink to
        # what the row actually earned on a rejection). A deterministic
        # pure function of the request's accept history, so two replays
        # of a trace budget identically; ``spec_k`` stays the admission
        # headroom bound (``_row_take_bound`` must assume the widest
        # row a request may ever pack).
        self.adaptive_k = bool(adaptive_k)
        self._req_k: dict = {}             # rid -> current draft budget
        # slot -> this step's proposed draft tail (cleared every
        # assembly: a deferred row's entry must not leak into a later
        # step where the slot packs something else)
        self._step_drafts: dict = {}
        # slot -> (tokens, parents) of this step's draft TREE (tree
        # mode only; cleared alongside _step_drafts)
        self._step_trees: dict = {}

    def _spec_key(self) -> tuple:
        # extends the engine's traffic-tuning key: a schedule searched
        # for draft-k=4 rows is the wrong answer for tree-packed rows
        return (self.spec_k, self.spec_tree)

    # ------------------------------------------------------- planning

    def _row_take_bound(self, req) -> int:
        take = super()._row_take_bound(req)
        if len(req.seq) - req.cursor == 1:
            # steady decode row: may widen by the draft budget —
            # admission headroom must assume the widest case
            take = min(1 + max(self.spec_k, self.spec_tree),
                       self.state.capacity - req.cursor)
        return take

    def _plan_row(self, req) -> np.ndarray:
        if len(req.seq) - req.cursor != 1:
            return super()._plan_row(req)     # prefill/chunk row
        if self.spec_tree:
            return self._plan_tree_row(req)
        # steady decode row: widen to [frontier, d_1 .. d_nd]. Drafting
        # past the request's remaining emission target is pure rollback
        # work, so nd is also capped by (max_new - generated - 1).
        budget = self.spec_k
        if self.adaptive_k:
            budget = self._req_k.setdefault(req.rid, self.spec_k)
            st = self.stats
            st.adaptive_k_rows[budget] = (
                st.adaptive_k_rows.get(budget, 0) + 1)
        if self.throttled_tiers:
            # brownout squeeze: a throttled tier drafts at most one
            # token — speculation's rollback work is the first compute
            # the fleet reclaims from batch traffic under overload
            pr = getattr(req, "priority", None)
            if pr is None:
                pr = self._tenant(req).priority
            if pr in self.throttled_tiers:
                budget = min(budget, 1)
        nd = min(budget,
                 self.state.capacity - (req.cursor + 1),
                 req.max_new - len(req.generated) - 1)
        drafts = (self.drafter.draft(req, nd) if nd > 0
                  else np.zeros((0,), np.int32))
        drafts = np.asarray(drafts, np.int32)[:max(nd, 0)]
        self._step_drafts[req.slot] = drafts
        return np.concatenate(
            [np.asarray(req.seq[req.cursor:], np.int32), drafts])

    def _plan_tree_row(self, req) -> np.ndarray:
        """Steady decode row, tree mode: pack [frontier, node_1 ..
        node_nd] where the nodes are a draft TREE in index order
        (``parents[t] < t``, ``-1`` = the frontier). The row's TREE
        topology descriptor (emitted by :meth:`_row_topology`) masks
        each node to attend only its root-to-node ancestry, so
        ``logits[base + t]`` is the PATH-conditioned next-token
        distribution — sibling branches never contaminate each other."""
        budget = self.spec_tree
        if self.throttled_tiers:
            pr = getattr(req, "priority", None)
            if pr is None:
                pr = self._tenant(req).priority
            if pr in self.throttled_tiers:
                budget = 1            # brownout: shed speculation first
        nd = min(budget,
                 self.state.capacity - (req.cursor + 1),
                 req.max_new - len(req.generated) - 1)
        if nd > 0:
            tokens, parents = self.drafter.draft_tree(req, nd)
            # parents[t] < t, so truncating the tail keeps a valid tree
            tokens = np.asarray(tokens, np.int32)[:nd]
            parents = np.asarray(parents, np.int32)[: len(tokens)]
        else:
            tokens = np.zeros((0,), np.int32)
            parents = np.zeros((0,), np.int32)
        self._step_trees[req.slot] = (tokens, parents)
        self._step_drafts[req.slot] = tokens
        return np.concatenate(
            [np.asarray(req.seq[req.cursor:], np.int32), tokens])

    def _row_topology(self, s: int, req, take: int):
        tree = self._step_trees.get(s)
        if tree is None or len(tree[0]) == 0:
            return None               # plain row stays CAUSAL
        from triton_distributed_tpu.kernels.ragged_paged_attention \
            import topo_width, tree_topology_row

        _, parents = tree
        return tree_topology_row(
            [int(p) for p in parents], topo_width(self._block_q_cap))

    def _assemble(self):
        self._step_drafts = {}
        self._step_trees = {}
        return super()._assemble()

    # ------------------------------------------------------- verify

    def _step_jit(self):
        # same batch contract, but logits at EVERY packed position —
        # the accept loop needs the next-token distribution after each
        # draft token, not just each slot's frontier
        return self.model._serving_all_logits_jit

    def _advance_row(self, s: int, req, take: int, logits,
                     q_starts, q_lens) -> tuple:
        drafts = self._step_drafts.get(s)
        base = int(q_starts[s])
        tree = self._step_trees.get(s)
        if tree is not None and len(tree[0]) > 0:
            return self._advance_tree_row(s, req, take, logits, base, tree)
        if drafts is None or len(drafts) == 0:
            # plain chunk/decode row — base bookkeeping, but the
            # frontier distribution lives at the row's LAST packed
            # index (logits here are per-token, not per-slot)
            self.ops.advance_cursor(self, s, req, take)
            if req.cursor == len(req.seq):
                tok = self._sample(logits[base + take - 1], req)
                req.generated.append(tok)
                self._maybe_complete(req, s)
                return 1, take - 1
            return 0, take
        # verify row: [frontier, d_1 .. d_nd] at positions
        # cursor .. cursor+nd. logits[base + j] is the next-token
        # distribution given seq[:cursor+1] + d_1..d_j — valid exactly
        # while every earlier draft was accepted, which is exactly how
        # far the loop below reads.
        nd = len(drafts)
        assert take == nd + 1, (take, nd)
        old_cursor = req.cursor
        emitted = accepted = 0
        for j in range(nd + 1):
            tok = self._sample(logits[base + j], req)
            req.generated.append(tok)
            emitted += 1
            if len(req.generated) >= req.max_new:
                break                  # stream length must match exactly
            if j < nd and tok == int(drafts[j]):
                accepted += 1          # draft j's provisional KV is real
                continue
            break                      # tok is the correction (j < nd)
            # ... or the bonus draw after a full accept (j == nd)
        # rollback: rewind to the surviving prefix and free the pages
        # the rejected tail claimed at assembly. Garbage KV above the
        # cursor is never attended (kv_lens is recomputed from host
        # cursors) and the next append overwrites it in place.
        self.ops.rollback_draft(self, s, req, old_cursor, take, accepted)
        st = self.stats
        st.spec_rows += 1
        st.draft_tokens += nd
        st.accepted_draft_tokens += accepted
        st.spec_tokens_out += emitted
        st.rolled_back_tokens += nd - accepted
        self.drafter.observe(req, accepted, nd - accepted)
        if self.adaptive_k:
            self._observe_k(req, accepted, nd - accepted, nd)
        self._maybe_complete(req, s)
        return emitted, 0

    def _advance_tree_row(self, s: int, req, take: int, logits,
                          base: int, tree) -> tuple:
        """Tree verify: walk the draft tree from the frontier, at each
        node drawing the request-keyed sample from that node's
        PATH-conditioned logits (the TREE mask guarantees position
        ``t+1`` attended exactly prefix + node ``t``'s ancestry).
        Accepting means descending to the child whose draft token
        matches the draw; the walk ends on a mismatch (the draw IS the
        correction) or at a leaf (the draw is the bonus token). Every
        draw keys on (seed, rid, generated-so-far) exactly as the plain
        engine's sequential draws would, so the stream is
        byte-identical to non-speculative decode.

        Only the leading IN-PLACE segment of the accepted path — nodes
        whose q position equals their linear packed position, i.e. the
        trunk — advances the cursor: off-trunk accepted tokens were
        written to the wrong pool offsets, so they are emitted into the
        stream now but re-packed (and their KV rewritten in place) as a
        chunk row next step."""
        tokens, parents = tree
        nd = len(tokens)
        assert take == nd + 1, (take, nd)
        old_cursor = req.cursor
        emitted = 0
        path = []                 # q positions of accepted nodes, root->leaf
        cur = 0                   # current q position (0 = frontier)
        while True:
            tok = self._sample(logits[base + cur], req)
            req.generated.append(tok)
            emitted += 1
            if len(req.generated) >= req.max_new:
                break             # stream length must match exactly
            nxt = -1
            for t in range(nd):   # child of cur whose draft matches the draw
                if int(parents[t]) + 1 == cur and int(tokens[t]) == tok:
                    nxt = t + 1
                    break
            if nxt < 0:
                break             # correction draw, or bonus past a leaf
            path.append(nxt)
            cur = nxt
        in_place = 0
        for i, qp in enumerate(path):
            if qp == i + 1:       # trunk: packed position == path position
                in_place += 1
            else:
                break
        self.ops.rollback_draft(self, s, req, old_cursor, take, in_place)
        st = self.stats
        st.spec_rows += 1
        st.draft_tokens += nd
        accepted = len(path)
        st.accepted_draft_tokens += accepted
        st.spec_tokens_out += emitted
        st.rolled_back_tokens += nd - in_place
        self.drafter.observe(req, accepted, nd - accepted)
        self._maybe_complete(req, s)
        return emitted, 0

    def _observe_k(self, req, accepted: int, rejected: int,
                   nd: int) -> None:
        """Walk the request's draft budget on one verify outcome:
        a clean sweep earns +1 (additive growth, capped at ``spec_k``),
        a rejection shrinks the budget to ``accepted + 1`` (what the
        row proved it could use, floor 1) — rejected drafts are pure
        rollback work, so the budget tracks the stream's measured
        compressibility instead of paying ``spec_k`` everywhere."""
        k = self._req_k.get(req.rid, self.spec_k)
        if rejected > 0:
            k = max(1, accepted + 1)
        elif nd > 0:
            k = min(self.spec_k, k + 1)
        self._req_k[req.rid] = k
