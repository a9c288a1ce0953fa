"""ProtocolOps: the serving/fleet protocol's transition functions
behind one narrow seam.

Everything that moves a request or a page through the serving state
machine — admission, allocation, eviction, preemption, the
transactional reserve/commit/abort KV ship, speculative rollback, and
the fleet's failover/drain requeue discipline — lives here as a verb on
:class:`ProtocolOps`. The production engines delegate
(``ServingEngine``, ``DisaggregatedEngine``, ``SpeculativeEngine``,
``ServingFleet`` each hold an ``ops`` instance), so there is exactly
ONE implementation of each transition.

The point of the seam is :mod:`triton_distributed_tpu.analysis.
servlint`: the bounded model checker drives THESE verbs — the real
scheduling/pool logic, not a re-implementation — over an abstract
2-replica fleet, and its seeded true-positive fixtures are built by
subclassing :class:`ProtocolOps` with one deliberate bug per rule
(mutated ops through the production seam). Every verb is pure host
bookkeeping: numpy tables, the :class:`~triton_distributed_tpu.serving.
state.PagePool` refcounts, request fields and deques — no device work,
which is what makes exhaustive interleaving exploration affordable.

Behavior contract: each verb's body IS the pre-seam engine/fleet method
body (PR 19 moved them verbatim); the trace-equality pin in
tests/test_fleet.py holds ``FleetStats.events`` byte-identical across
the refactor.
"""

from __future__ import annotations

import time
from collections import deque


class ProtocolOps:
    """The serving protocol's transition verbs. Engine-scoped verbs
    take the engine as their first argument (one stateless ops instance
    can serve every role engine of a deployment); fleet-scoped verbs
    take the pieces they move. Subclass and override a verb to build a
    deliberately-broken protocol for servlint's fixtures."""

    #: fixture metadata: the servlint rule a mutated subclass seeds
    #: (None on the production ops)
    seeds_rule: str | None = None

    # ---------------------------------------------------- page allocator

    def alloc(self, eng, slot: int, held: int, need: int) -> bool:
        """Grow ``slot``'s table from ``held`` to ``need`` pages;
        all-or-nothing (no partial growth to unwind). The logical page
        index rides into the pool so a cp-sharded pool can land each
        page on its owning shard (``can_hold`` is the matching exact
        per-shard gate; a flat pool degenerates both to the old
        headroom check)."""
        if not eng.pool.can_hold(held, need):
            return False
        for pg in range(held, need):
            eng.table[slot, pg] = eng.pool.alloc(pg)
        return True

    def free_slot(self, eng, slot: int) -> None:
        """Release the slot's page references — shared-prefix pages
        only truly free when their LAST holder lets go (the refcount
        discipline); privately-held pages return to the free list."""
        for pg in eng.table[slot]:
            if pg >= 0:
                eng.pool.release(int(pg))
        eng.table[slot] = -1
        eng.slot_req[slot] = None

    def ensure_pages(self, eng, slot: int, held: int, need: int,
                     batched: set) -> bool:
        """Batch assembly's allocation loop: claim the row's pages,
        evicting (priority-aware LIFO) until they fit or nothing
        evictable remains. False = the row defers this step."""
        while not self.alloc(eng, slot, held, need):
            if not self.evict_one(eng, batched | {slot}):
                return False
        return True

    # ------------------------------------------------ eviction/preemption

    def evict_one(self, eng, batched: set) -> bool:
        """Evict the lowest-tier, latest-arrived active request not
        already in this step's batch (priority-aware LIFO preemption);
        its pages return to the free list and the request re-queues AT
        THE FRONT with cursor 0 — the recompute prefix (prompt +
        generated) resumes it exactly. Parked requests (pages pinned by
        an in-flight KV ship) and already-completed holders are never
        victims."""
        victims = [
            (eng._rank(req), req.arrival, s)
            for s, req in enumerate(eng.slot_req)
            if req is not None and s not in batched
            and not req.parked and not req.done
        ]
        if not victims:
            return False
        _, _, s = max(victims)
        req = eng.slot_req[s]
        req.cursor = 0
        req.evictions += 1
        req.slot = None
        self.free_slot(eng, s)
        eng.waiting.appendleft(req)
        eng.stats.evictions += 1
        return True

    def preempt_for(self, eng, by_req) -> bool:
        """Priority preemption: evict the LOWEST-tier resident row
        strictly below ``by_req``'s effective rank through the
        recompute-eviction discipline (token-exact, cursor-resumable).
        False = no strictly-lower victim. Runs under the ``preempt``
        chaos site so a fault-plan Stall can wedge it visibly."""
        rank = eng._eff_rank(by_req)
        victims = [
            (eng._eff_rank(req), -int((eng.table[s] >= 0).sum()),
             req.arrival, s)
            for s, req in enumerate(eng.slot_req)
            if req is not None and not req.parked and not req.done
            and eng._eff_rank(req) > rank
        ]
        if not victims:
            return False
        from triton_distributed_tpu.lang.launch import maybe_instrument

        _, _, _, s = max(victims)

        def body():
            victim = eng.slot_req[s]
            victim.cursor = 0
            victim.evictions += 1
            victim.slot = None
            self.free_slot(eng, s)
            eng.waiting.append(victim)
            eng.stats.evictions += 1
            eng.stats.preemptions += 1
            t = getattr(victim, "tenant", "default")
            eng.stats.tenant_preemptions[t] = (
                eng.stats.tenant_preemptions.get(t, 0) + 1)
            if eng.on_preempt is not None:
                eng.on_preempt(by_req, victim)
            return True

        return maybe_instrument(
            body, axis=None, site="preempt",
            collective_id=("preempt", eng.step_count), n=1,
            step=eng.step_count,
        )()

    # ----------------------------------------------------------- admission

    def admit(self, eng) -> None:
        """Priority admission over the free slots: effective tier rank
        (tenant tier minus the aging bump), then FIFO, with preemption
        when a higher tier finds no slot or no page headroom and
        per-tenant fair-share deferrals."""
        while eng.pending and eng.pending[0].arrival <= eng.step_count:
            eng.waiting.append(eng.pending.popleft())
        if not eng.waiting:
            return
        eng.waiting = deque(sorted(
            eng.waiting,
            key=lambda r: (eng._eff_rank(r), r.arrival, r.rid)))
        deferred: list = []
        while eng.waiting:
            req = eng.waiting[0]
            free = [s for s, r in enumerate(eng.slot_req) if r is None]
            if not free:
                if not self.preempt_for(eng, req):
                    break                  # no slot, no lower-tier victim
                free = [s for s, r in enumerate(eng.slot_req)
                        if r is None]
            first = min(eng._chunk_for(req), len(req.seq))
            if (eng._pages_held(first)
                    > eng.pool.available - eng._committed_pages()):
                # pool exhausted: a higher tier may still claim pages
                # by preempting the lowest-tier resident
                if self.preempt_for(eng, req):
                    continue
                break                      # hold the queue
            if not eng._fair_share_ok(req, first):
                eng.waiting.popleft()
                deferred.append(req)
                t = getattr(req, "tenant", "default")
                eng.stats.fair_share_deferrals[t] = (
                    eng.stats.fair_share_deferrals.get(t, 0) + 1)
                continue
            eng.waiting.popleft()
            s = free[0]
            req.slot = s
            eng.slot_req[s] = req
            if req.t_admit is None:
                # the FIRST slot only: a re-admission after an eviction
                # is not a second queue wait. (A request handed over by
                # a fleet replica was never ``submit``-ted: no wait.)
                req.t_admit = time.perf_counter()
                if req.t_submit is not None:
                    eng.stats.queue_wait_s += req.t_admit - req.t_submit
                    eng.stats.admissions += 1
            if len(req.seq) > eng.state.capacity:
                # cannot ever fit — fail it loudly rather than wedging
                req.done = True
                self.free_slot(eng, s)
                raise ValueError(
                    f"request {req.rid}: sequence {len(req.seq)} exceeds "
                    f"slot capacity {eng.state.capacity}"
                )
            if eng.pool.prefix_cache and req.cursor == 0:
                eng._attach_prefix(req, s)
        for req in deferred:               # over-share: retry next step
            eng.waiting.append(req)

    # ------------------------------------------------------- row advance

    def advance_cursor(self, eng, s: int, req, take: int) -> int:
        """Move one batched row's cursor past its packed tokens and
        publish newly-frozen pages to the prefix cache. Returns the
        pre-advance cursor."""
        old_cursor = req.cursor
        req.cursor += take
        if eng.pool.prefix_cache:
            eng._register_frozen(req, s, old_cursor)
        return old_cursor

    def complete(self, eng, req, s: int) -> None:
        """Completion check after a row emitted into ``req.generated``;
        frees (or parks, via ``on_complete``) the slot when the request
        reaches its target."""
        target = 1 if eng.cfg.prefill_only else req.max_new
        if len(req.generated) >= target:
            req.completion_step = eng.step_count
            eng.stats.completed += 1
            eng.stats.generated_tokens += len(req.generated)
            if not eng.cfg.prefill_only:
                req.done = True
            if eng.on_complete is None or eng.on_complete(req, s):
                self.free_slot(eng, s)

    def rollback_draft(self, eng, s: int, req, old_cursor: int,
                       take: int, accepted: int) -> None:
        """Speculative rollback: rewind the cursor to the surviving
        prefix (frontier + accepted drafts) and free the pages the
        rejected tail claimed at assembly. Garbage KV above the cursor
        is never attended (kv_lens is recomputed from host cursors) and
        the next append overwrites it in place."""
        req.cursor = old_cursor + 1 + accepted
        keep = eng._pages_held(req.cursor)
        got = eng._pages_held(old_cursor + take)
        for pg in range(keep, got):
            if eng.table[s, pg] >= 0:
                eng.pool.release(int(eng.table[s, pg]))
                eng.table[s, pg] = -1
        if eng.pool.prefix_cache:
            # register AFTER the rewind — only pages below the FINAL
            # cursor are frozen (pure functions of the chained prefix)
            eng._register_frozen(req, s, old_cursor)

    # --------------------------------------------- transactional KV ship

    def reserve_shipped(self, eng, req) -> tuple | None:
        """Claim a slot + landing pages for a request whose first
        ``req.cursor`` tokens of KV will arrive by transfer. Returns
        (slot, page_ids) or None (no slot / pool pressure — the caller
        retries, leaving the source pages pinned)."""
        free = [s for s, r in enumerate(eng.slot_req) if r is None]
        if not free:
            return None
        if len(req.seq) > eng.state.capacity:
            raise ValueError(
                f"request {req.rid}: sequence {len(req.seq)} exceeds "
                f"slot capacity {eng.state.capacity}"
            )
        need = eng._pages_held(req.cursor)
        if (need > eng.pool.available - eng._committed_pages()
                or not eng.pool.can_hold(0, need)):
            return None
        s = free[0]
        pids = []
        for p in range(need):
            pg = eng.pool.alloc(p)
            eng.table[s, p] = pg
            pids.append(int(pg))
        req.slot = s
        req.parked = True
        eng.slot_req[s] = req
        return s, pids

    def commit_shipped(self, eng, req) -> None:
        """The transfer into this request's reserved pages has landed:
        the row becomes schedulable (and evictable) like any other."""
        req.parked = False

    def release_parked(self, eng, slot: int) -> None:
        """Free a parked slot (source-side handoff after its pages have
        shipped, or an abandoned reservation)."""
        req = eng.slot_req[slot]
        assert req is not None and req.parked, (slot, req)
        req.parked = False
        self.free_slot(eng, slot)

    def ship_commit(self, src_eng, pslot: int, dst_eng, req) -> None:
        """Land one ship/migration: handoff order matters (the
        ``_commit_ships`` discipline) — the SOURCE frees its pinned
        pages FIRST, then the row becomes schedulable at the
        destination. The reverse order would leave a window where both
        pools claim the request's KV."""
        self.release_parked(src_eng, pslot)
        self.commit_shipped(dst_eng, req)

    def ship_abort(self, dst_eng, dslot: int, req, pslot: int) -> None:
        """Transport exhausted: roll the destination reservation back
        (landing pages return to the pool) and restore the request to
        its source slot, schedulable in place — the degradation target
        of every ship is finish-where-you-are / re-prefill."""
        self.release_parked(dst_eng, dslot)
        req.slot = pslot
        req.parked = False

    def migrate_live_core(self, req, src_role, dst_role, pslot: int,
                          npg: int, transport):
        """The transactional core of a replica→replica live migration:
        reserve landing pages at the destination, gather+transport the
        committed pages, then commit (source releases first) — or roll
        back on transport exhaustion. Returns None (no reservation —
        try another destination), False (transport failed, rolled
        back), or ``(dslot, dpids)`` on success."""
        got = self.reserve_shipped(dst_role, req)
        if got is None:
            return None                # no slot/pages there; try next
        dslot, dpids = got
        src_pids = [int(p) for p in src_role.table[pslot, :npg]]
        payload = src_role.gather_pages(src_pids)
        shipped = transport(payload)
        if shipped is None:
            # roll the reservation back; the row stays at src and
            # can still finish in place (or requeue on a kill)
            self.ship_abort(dst_role, dslot, req, pslot)
            return False
        dst_role.land_pages(dpids, *shipped)
        self.ship_commit(src_role, pslot, dst_role, req)
        return dslot, dpids

    # ------------------------------------------------- fleet requeue verbs

    def failover_requeue(self, held: list, queue, stats=None) -> list:
        """The ReplicaDeath drain discipline: everything the dead
        replica held re-enters the fleet queue at cursor 0 (the
        recompute-eviction discipline — re-prefilling prompt+generated
        resumes the exact cursor), arrival-ordered at the FRONT — zero
        lost requests, and the request-keyed sampler keeps the streams
        byte-identical."""
        drained = sorted(held, key=lambda r: r.arrival)
        for req in drained:
            if stats is not None:
                stats.failover_re_prefill_tokens += req.cursor
            if req.cursor > 0:
                req.evictions += 1
            req.cursor = 0
            req.slot = None
            req.parked = False
        for req in reversed(drained):
            queue.appendleft(req)
        return drained

    def drain_requeue(self, role, queue) -> list:
        """Planned-drain requeue: one role's queued-but-not-resident
        work re-enters the fleet queue now (residents migrate or finish
        in place)."""
        moved = [r for r in list(role.waiting) + list(role.pending)
                 if not r.done]
        role.waiting.clear()
        role.pending.clear()
        for req in moved:
            req.slot = None
            queue.append(req)
        return moved
