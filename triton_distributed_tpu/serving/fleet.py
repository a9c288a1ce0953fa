"""Fleet-scale serving: N engine replicas behind a health- and
cache-aware router.

The north star says millions of users; one :class:`~triton_distributed_
tpu.serving.engine.ServingEngine` (or one disaggregated pair) is the
wrong unit for that. This module is the first layer that AGGREGATES
engines: ``n`` replicas — colocated engines or
:class:`~triton_distributed_tpu.serving.engine.DisaggregatedEngine`
pairs, each on its own mesh slice carved by
:func:`~triton_distributed_tpu.runtime.topology.carve_replica_meshes` —
behind a :class:`FleetRouter` that scores admission per replica on

    score(r, req) = (1 + w_prefix · overlap_pages(r, req))
                    · health_factor(r)
                    / (1 + w_load · load_ms(r) / mean_load)

* ``overlap_pages`` — consecutive full prompt pages already RESIDENT in
  the replica's :class:`~triton_distributed_tpu.serving.state.PagePool`
  prefix registry (chain-hash lookups, the PR 7 machinery): routing a
  request where its prefix lives skips recomputing it.
* ``health_factor`` — the fleet :class:`~triton_distributed_tpu.runtime.
  health.HealthLedger` state of peer ``"replica:k"``: HEALTHY 1.0,
  SUSPECT 0.5, PROBATION probe-only, UNHEALTHY excluded — the same
  signals :func:`~triton_distributed_tpu.runtime.topology.replan_mesh`
  consumes, so the rotation grows and shrinks exactly when a replan
  would.
* ``load_ms`` — :func:`~triton_distributed_tpu.tune.perf_model.
  replica_load_ms`: the analytic step time of the replica's resident
  occupancy scaled by its queue depth, normalized by the fleet-mean
  load so the knob is scale-free (the same ``w_load`` works for
  microsecond CPU-sim steps and millisecond TPU steps). No
  measurement, so scores are reproducible.

Session affinity pins a ``req.session`` to the replica that served it
last (its KV prefix lives there); when that replica is full AND its
score (cache value vs queue depth) no longer justifies queueing, the
request SPILLS to the best-scoring replica with room and the affinity
follows the pages. Every tie-break hashes through the fleet seed (folded into
``config.interp_key`` like the fault-plan identity), so same seed ⇒
identical placement.

Robustness headline — :class:`~triton_distributed_tpu.runtime.faults.
ReplicaDeath`: when the active fault plan kills replica ``k`` at a
tick, the fleet records the fatal ``replica_death`` signal, drains
EVERYTHING the dead replica held (slots, queues, in-flight ships) back
through the router onto the survivors at cursor 0 — the recompute-
eviction discipline: re-prefilling prompt+generated resumes each
stream at its exact cursor — and, because sampling is keyed
``(seed, rid, n_generated)``, the re-placed streams are byte-identical
to the fault-free run. Zero requests are lost. A revived replica
re-enters rotation only through the PR 10 probation-probe path: clean
idle ticks earn PROBATION, seeded probes earn traffic, enough clean
probes earn HEALTHY — never a blind re-add. All replicas dead is a
loud refusal, not a hang.

Multi-tenancy (docs/SERVING.md § Multi-tenant serving): requests carry
a ``tenant`` + priority tier (interactive / batch / background, from
:class:`~triton_distributed_tpu.serving.engine.TenantConfig`), and the
fleet enforces them end to end — a deadline **slack** term in the
router score (``slack = slo_ms − modeled completion``; negative slack
outranks prefix affinity), tier-priced retry-after (a tier-r retry
waits only on the queue at rank ≤ r), engine-level **priority
preemption** through the recompute-eviction discipline, and a
:class:`BrownoutController` that sheds overload in strict
reverse-priority order (background rejected first, batch spec/chunk
budgets squeezed next, interactive last) with hysteretic recovery.
Every shed/preempt/brownout transition lands in ``stats.events`` —
the same replay-determinism contract as scale/drain/migrate.
"""

from __future__ import annotations

import time
import zlib
from collections import deque
from dataclasses import dataclass, field


def _u(*parts) -> float:
    """crc32-seeded uniform in [0, 1) — the FaultPlan/HealthLedger
    determinism idiom, reused for router tie-breaks."""
    return (zlib.crc32(repr(parts).encode()) & 0xFFFFFFFF) / 2**32


#: Kernel families a fleet replica's engines launch. ``bench.py
#: --lint`` verifies each is registered with a RESOLVABLE degradation
#: target: a replica whose engines cannot degrade cannot be safely
#: failed over onto, so the fleet inherits the engine-level
#: degradation-matrix guarantee by construction.
FLEET_ENGINE_FAMILIES = (
    "flash_decode.ragged_paged",   # every replica's serving step
    "kv_ship.pages",               # disaggregated replicas' KV wire
    "cp_decode.lse_combine",       # cp replicas' cross-rank LSE merge
)

#: Kernel families the replica→replica KV-page MIGRATION wire rides —
#: the kv_ship machinery routed fleet-internally instead of
#: prefill→decode. ``bench.py --lint`` gates that each resolves a
#: degradation target (``migration_gaps == 0``): the migration path's
#: own fallback is re-prefill at the destination, but the wire it
#: prefers must inherit the engine-level degradation guarantee or a
#: drain would wedge on the first transport fault.
MIGRATION_ENGINE_FAMILIES = (
    "kv_ship.pages",
)


# ------------------------------------------------------------- replica

@dataclass
class Replica:
    """One fleet member: an engine (colocated ``ServingEngine`` or a
    ``DisaggregatedEngine`` pair) plus its carved mesh. Duck-typed over
    both engine shapes — ``_roles`` is the flat engine list."""

    index: int
    engine: object
    mesh: object = None

    @property
    def peer(self) -> str:
        return f"replica:{self.index}"

    @property
    def _roles(self) -> tuple:
        e = self.engine
        if hasattr(e, "prefill"):          # DisaggregatedEngine
            return (e.prefill, e.decode)
        return (e,)

    @property
    def admit_role(self):
        """The engine new requests enter (the prefill half of a pair)."""
        return self._roles[0]

    def submit(self, req) -> None:
        # straight into `waiting`: the request already passed the
        # fleet-level arrival gate, the engine must not re-gate it
        self.admit_role.waiting.append(req)

    def step(self):
        e = self.engine
        if hasattr(e, "tick"):
            return e.tick()
        # the fleet reads and moves a replica's slots between its steps
        # (routing, failover, drain, migration): none stays in flight
        # (the contract of ``ServingEngine.drain``)
        rep = e.step()
        e.drain()
        return rep

    @property
    def idle(self) -> bool:
        return self.engine.idle

    def held(self) -> list:
        """Every not-done request this replica currently owns (slots,
        queues, both roles; parked/shipping requests sit in slots)."""
        out, seen = [], set()
        for role in self._roles:
            for req in (list(role.slot_req) + list(role.waiting)
                        + list(role.pending)):
                if req is not None and not req.done \
                        and id(req) not in seen:
                    seen.add(id(req))
                    out.append(req)
        return out

    def neutralize(self) -> None:
        """The replica's device state died with its slice: host mirrors
        must read empty so nothing ever schedules into it again."""
        for role in self._roles:
            role.slot_req = [None] * role.cfg.slots
            role.table[:] = -1
            role.waiting.clear()
            role.pending.clear()
        e = self.engine
        if hasattr(e, "_ready"):
            e._ready.clear()
            e._inflight.clear()

    # ------------------------------------------------- router signals

    def overlap_pages(self, req) -> int:
        """Consecutive full prompt pages resident in this replica's
        prefix registry — the cache term of the router score."""
        from triton_distributed_tpu.serving.state import page_chain_hash

        best = 0
        for role in self._roles:
            pool = role.pool
            if not pool.prefix_cache:
                continue
            page = role.cfg.page
            seq = req.seq
            h, n = 0, 0
            for p in range((len(seq) - 1) // page):
                h = page_chain_hash(h, seq[p * page:(p + 1) * page])
                if pool.lookup(h, p) is None:
                    break
                n += 1
            best = max(best, n)
        return best

    @property
    def cp(self) -> int:
        """Context-parallel factor of this replica's mesh (1 = no cp
        axis) — the long-context capability the router places by."""
        return max(
            getattr(role.model, "cp", 1) for role in self._roles)

    def fits_context(self, req) -> bool:
        """Can this replica EVER hold ``req`` end-to-end — the
        request's full KV (prompt plus every token it may generate)
        within the pool AND the per-slot table width? False means
        routing here can never admit it, whatever drains: the router's
        long-context placement filter."""
        role = self.admit_role
        tokens = len(req.seq) + int(getattr(req, "max_new", 0) or 0)
        need = max(-(-tokens // role.cfg.page), 1)
        return (need <= role.state.pages_per_seq
                and need <= role.pool.npages)

    def load_ms(self) -> float:
        """Queue-depth/step-time estimate — the perf term."""
        from triton_distributed_tpu.tune import perf_model

        return sum(perf_model.replica_load_ms(r) for r in self._roles)

    def step_model_ms(self) -> float:
        """Analytic cost of the step ABOUT to run (current occupancy)
        — the deterministic clock the fleet accumulates per replica.
        A prefilling slot bills its chunk, a decoding slot one token,
        so prefix hits (skipped prefill) show up as modeled time
        saved."""
        from triton_distributed_tpu.tune import perf_model

        return sum(perf_model.replica_step_ms(r) for r in self._roles
                   if not r.idle)

    def queue_depth(self, *, rank=None, rank_of=None) -> int:
        """Requests queued at the admission role (not yet in slots) —
        the quantity the router's ``queue_cap`` bounds. With ``rank``
        (and the fleet's ``rank_of``), only entries at rank <= rank
        count: priority admission sorts a tier-r arrival ahead of
        everything below it, so lower-tier backlog is not depth a
        tier-r client ever stands behind."""
        role = self.admit_role
        queued = list(role.waiting) + list(role.pending)
        if rank is None or rank_of is None:
            return len(queued)
        return sum(1 for q in queued if rank_of(q) <= rank)

    def can_accept(self, req) -> bool:
        """Would the admission role admit ``req`` NOW (free slot + page
        headroom)? False means routing here queues the request."""
        role = self.admit_role
        if all(r is not None for r in role.slot_req):
            return False
        first = min(role.cfg.chunk, len(req.seq))
        return (role._pages_held(first)
                <= role.pool.available - role._committed_pages())


# -------------------------------------------------------------- router

@dataclass(frozen=True)
class RouterConfig:
    """Router knobs (see docs/SERVING.md § Fleet)."""

    w_prefix: float = 1.0       # weight of the prefix-overlap term
    w_load: float = 1.0         # weight of the fleet-mean-relative load
    w_slack: float = 1.0        # weight of the deadline-deficit term
    policy: str = "scored"      # "scored" | "round_robin" (baseline)
    affinity: bool = True       # session stickiness
    # admission control: when EVERY routable replica already has this
    # many requests queued (waiting + pending on its admission role,
    # counted at the arrival's own tier — lower-tier backlog is
    # invisible to a higher-tier arrival), the fleet REJECTS the
    # arrival with a priced retry-after instead of letting `waiting`
    # grow without bound. None = unbounded (the pre-cap behavior).
    queue_cap: int | None = None


class FleetRouter:
    """Scores and places one request at a time. Stateless apart from
    the round-robin cursor and the session-affinity map; every
    tie-break is seeded, so same seed ⇒ identical placement."""

    def __init__(self, seed: int, cfg: RouterConfig | None = None):
        self.seed = seed
        self.cfg = cfg or RouterConfig()
        self._rr = 0
        self.affinity: dict = {}           # session -> replica index
        # tenant -> TenantConfig, assigned by the owning ServingFleet;
        # empty = single-tenant (no deadline term, pre-tier behavior)
        self.tenants: dict = {}

    def health_factor(self, state) -> float | None:
        """None = not routable. PROBATION returns None here — probe
        admission is the fleet's job (``ServingFleet._route_probe``),
        not a score."""
        from triton_distributed_tpu.runtime.health import PeerState

        if state is PeerState.HEALTHY:
            return 1.0
        if state is PeerState.SUSPECT:
            return 0.5
        return None                        # PROBATION / UNHEALTHY

    def slack_ms(self, replica: Replica, req) -> float | None:
        """Deadline slack of placing ``req`` at ``replica``:
        ``slo_ms − modeled completion``, where modeled completion is
        the queue already ahead (``replica.load_ms()``) plus the
        request's own remaining work (:func:`~triton_distributed_tpu.
        tune.perf_model.request_service_ms`). None when the request's
        tenant has no finite SLO — no deadline term at all."""
        import math

        tc = self.tenants.get(getattr(req, "tenant", None))
        if tc is None or not math.isfinite(tc.slo_ms):
            return None
        from triton_distributed_tpu.tune import perf_model

        return (tc.slo_ms - replica.load_ms()
                - perf_model.request_service_ms(replica.admit_role, req))

    def score(self, replica: Replica, req, state,
              mean_load: float = 0.0,
              slack: float | None = None) -> float | None:
        """The admission score. The load term enters RELATIVE to
        ``mean_load`` (the fleet mean, computed by :meth:`route`) so
        ``w_load`` is scale-free — the same knob balances microsecond
        CPU-sim steps and millisecond TPU steps. A NEGATIVE deadline
        ``slack`` divides the score by the (mean-normalized) deficit:
        the tighter a placement misses the tenant SLO, the harder it
        is penalized, so tight-deadline requests drift to the replica
        that still makes the deadline even when another holds their
        prefix."""
        hf = self.health_factor(state)
        if hf is None:
            return None
        c = self.cfg
        rel = replica.load_ms() / mean_load if mean_load > 0 else 0.0
        base = ((1.0 + c.w_prefix * replica.overlap_pages(req)) * hf
                / (1.0 + c.w_load * rel))
        if slack is not None and slack < 0:
            deficit = -slack / mean_load if mean_load > 0 else -slack
            base /= (1.0 + c.w_slack * deficit)
        return base

    def route(self, req, replicas: list, ledger) -> tuple:
        """Pick the replica for ``req`` among routable ``replicas``.
        Returns ``(replica, spilled)`` — ``spilled`` True when session
        affinity wanted a replica that is full (or gone) and the score
        said re-homing beats queueing there."""
        states = {r.index: ledger.state(r.peer) for r in replicas}
        routable = [r for r in replicas
                    if self.health_factor(states[r.index]) is not None]
        if not routable:
            raise RuntimeError(
                "fleet router: no routable replica (every replica is "
                "dead or condemned) — no survivor to fail over to")
        # long-context placement: a request whose end-to-end KV exceeds
        # a replica's pool can NEVER be admitted there — only replicas
        # whose mesh carries a cp axis wide enough stay candidates.
        # None left is a hard, priced refusal (capacity does not appear
        # by waiting), not a queue-and-hope.
        fits = [r for r in routable if r.fits_context(req)]
        if not fits:
            raise RuntimeError(
                "fleet router: no routable replica can hold this "
                "request's KV — "
                + self.long_context_refusal(req, routable))
        routable = fits
        if self.cfg.policy == "round_robin":
            r = routable[self._rr % len(routable)]
            self._rr += 1
            return r, False
        mean = sum(r.load_ms() for r in routable) / len(routable)
        slacks = {r.index: self.slack_ms(r, req) for r in routable}
        scored = [(r, self.score(r, req, states[r.index], mean,
                                 slack=slacks[r.index]))
                  for r in routable]
        # seeded tie-break: equal scores place identically under the
        # same fleet seed regardless of construction order
        scored.sort(key=lambda rs: (
            -rs[1], _u(self.seed, "tie", req.rid, rs[0].index)))
        best_with_room = next(
            ((r, s) for r, s in scored if r.can_accept(req)), None)
        sess = getattr(req, "session", None)
        spilled = False
        chosen = None
        if self.cfg.affinity and sess is not None \
                and sess in self.affinity:
            home = next((rs for rs in scored
                         if rs[0].index == self.affinity[sess]), None)
            hs = slacks.get(home[0].index) if home is not None else None
            if home is None:
                spilled = True       # home dead/condemned: re-home
            elif hs is not None and hs < 0 \
                    and not home[0].can_accept(req) \
                    and best_with_room is not None \
                    and (slacks.get(best_with_room[0].index) or 0.0) > hs:
                # deadline outranks prefix affinity: queueing at the
                # full home is MODELED to miss the tenant SLO while
                # another replica with room still makes (or misses it
                # by less) — re-home now, pages can follow the spill
                spilled = True
            elif home[0].can_accept(req) or best_with_room is None \
                    or home[1] >= best_with_room[1]:
                # queue at the home even when it is full, as long as
                # its score (resident prefix vs queue depth) still
                # beats the best replica with a free slot — waiting
                # where the pages live beats re-prefilling them
                chosen = home[0]
            else:
                spilled = True       # home full and outscored: spill
        if chosen is None:
            chosen = (best_with_room or scored[0])[0]
        if self.cfg.affinity and sess is not None:
            self.affinity[sess] = chosen.index   # affinity follows
        return chosen, spilled

    def long_context_refusal(self, req, replicas: list) -> str:
        """The priced reason no replica in ``replicas`` can hold
        ``req``: :func:`~triton_distributed_tpu.tune.perf_model.
        refuse_long_context` evaluated at the LARGEST-capacity
        candidate (the one that came closest), so the message names
        the cp factor that would have sufficed and its modeled
        per-step price."""
        from triton_distributed_tpu.tune import perf_model

        big = max(replicas, key=lambda r: min(
            r.admit_role.pool.npages,
            r.admit_role.state.pages_per_seq))
        role = big.admit_role
        tokens = len(req.seq) + int(getattr(req, "max_new", 0) or 0)
        need = max(-(-tokens // role.cfg.page), 1)
        return perf_model.refuse_long_context(
            role.model.config, role.cfg.page, need,
            pool_pages=role.pool.npages,
            pages_per_seq=role.state.pages_per_seq,
            cp=big.cp,
        ) or "long-context refusal with no over-capacity term (bug)"


# ---------------------------------------------------------- autoscaler

@dataclass(frozen=True)
class AutoscalerConfig:
    """Grow-side elasticity knobs (docs/SERVING.md § Elastic fleet).

    The pressure signal is PRICED, not counted: a tick is pressured
    when even the LIGHTEST routable replica's
    :func:`~triton_distributed_tpu.tune.perf_model.replica_load_ms`
    (the modeled wait the best possible placement pays — the projected
    p99 admission wait, since every other placement waits longer)
    exceeds ``slo_ms`` while work is actually backed up. ``window``
    consecutive pressured ticks trigger a grow; ``cooldown`` ticks must
    then pass before the next — together the flap damping that keeps a
    burst from oscillating the fleet."""

    slo_ms: float                  # projected-admission-wait SLO (model ms)
    window: int = 3                # consecutive pressured ticks to grow
    cooldown: int = 10             # min ticks between grows (flap damp)
    max_replicas: int | None = None


class FleetAutoscaler:
    """Watches the ledger-filtered routing set plus the windowed
    queue-depth/``replica_load_ms`` signal and decides WHEN the fleet
    should spawn from its reserve pool. Pure bookkeeping over
    deterministic inputs (the perf model and the tick clock), seeded
    like every fleet component — same seed and trace ⇒ identical grow
    ticks. The fleet owns HOW to grow (:meth:`ServingFleet.grow`:
    reserve mesh, probation warm-up, probe-gated admission)."""

    def __init__(self, cfg: AutoscalerConfig, seed: int = 0):
        self.cfg = cfg
        self.seed = seed
        self.pressured = 0             # consecutive pressured ticks
        self.last_grow: int | None = None
        self.history: list = []        # (tick, projected_ms, backlog)

    def pressure(self, fleet) -> bool:
        """Is THIS tick pressured? Projected wait at the lightest
        routable replica vs the SLO, gated on a real backlog."""
        routable = [
            r for r in fleet._route_candidates()
            if fleet.router.health_factor(
                fleet.health.state(r.peer)) is not None
        ]
        if not routable:
            return False
        projected = min(r.load_ms() for r in routable)
        backlog = (len(fleet.queue)
                   + sum(r.queue_depth() for r in routable))
        self.history.append((fleet.ticks, projected, backlog))
        return projected > self.cfg.slo_ms and backlog > 0

    def should_grow(self, fleet) -> bool:
        """One observation per fleet tick: update the sustained-pressure
        window, then apply the flap damps (window, cooldown,
        max_replicas)."""
        if self.pressure(fleet):
            self.pressured += 1
        else:
            self.pressured = 0
        if self.pressured < max(1, self.cfg.window):
            return False
        if self.last_grow is not None \
                and fleet.ticks - self.last_grow < self.cfg.cooldown:
            return False
        if self.cfg.max_replicas is not None \
                and len(fleet._alive()) >= self.cfg.max_replicas:
            return False
        return True


# ------------------------------------------------------------ brownout

#: Escalation ladder, strict reverse-priority order. Each level keeps
#: everything the previous one shed: ``shed_background`` bounces
#: background arrivals with a priced retry-after; ``squeeze_batch``
#: additionally throttles the batch tier's spec/chunk budgets on every
#: engine (``throttled_tiers``); ``shed_batch`` bounces batch arrivals
#: too. Interactive is NEVER shed — its protection is the whole point.
BROWNOUT_LEVELS = ("normal", "shed_background", "squeeze_batch",
                   "shed_batch")


@dataclass(frozen=True)
class BrownoutConfig:
    """Overload-controller knobs (docs/SERVING.md § Multi-tenant
    serving). Flap-damped like :class:`AutoscalerConfig`: ``window``
    consecutive pressured ticks escalate one level, ``cooldown``
    consecutive clean ticks de-escalate one level — hysteresis, so a
    border-line load doesn't oscillate the fleet between shedding and
    re-admitting every tick."""

    slo_ms: float                  # fleet-wide modeled-wait ceiling
    window: int = 3                # pressured ticks per escalation
    cooldown: int = 5              # clean ticks per de-escalation


class BrownoutController:
    """Fleet-level graceful degradation. Watches the same priced
    pressure signal as the autoscaler PLUS per-tier modeled slack (an
    arrived request whose tenant SLO is missed even at the lightest
    routable replica is pressure, whatever the absolute load), and
    sheds in strict reverse-priority order — see
    :data:`BROWNOUT_LEVELS`. Pure bookkeeping over deterministic
    inputs, seeded like every fleet component: same seed and trace ⇒
    identical shed ticks and transitions."""

    def __init__(self, cfg: BrownoutConfig, seed: int = 0):
        self.cfg = cfg
        self.seed = seed
        self.level = 0                 # index into BROWNOUT_LEVELS
        self.pressured = 0             # consecutive pressured ticks
        self.clean = 0                 # consecutive clean ticks
        self.history: list = []        # (tick, projected_ms, backlog)

    def pressure(self, fleet) -> bool:
        """Is THIS tick pressured? Backlog counts only ARRIVED fleet
        queue entries — a shed request parked at a future retry tick
        is the controller's own output, not input pressure (counting
        it would latch the brownout on forever)."""
        routable = [
            r for r in fleet._route_candidates()
            if fleet.router.health_factor(
                fleet.health.state(r.peer)) is not None
        ]
        if not routable:
            return False
        arrived = [q for q in fleet.queue if q.arrival <= fleet.ticks]
        backlog = (len(arrived)
                   + sum(r.queue_depth() for r in routable))
        projected = min(r.load_ms() for r in routable)
        self.history.append((fleet.ticks, projected, backlog))
        if backlog == 0:
            return False
        if projected > self.cfg.slo_ms:
            return True
        # per-tier slack: even a light fleet is pressured when some
        # arrived tenant's deadline is already un-meetable everywhere
        for q in arrived:
            slacks = [s for s in (fleet.router.slack_ms(r, q)
                                  for r in routable) if s is not None]
            if slacks and max(slacks) < 0:
                return True
        return False

    def observe(self, fleet) -> None:
        """One observation per fleet tick: escalate after ``window``
        pressured ticks, de-escalate after ``cooldown`` clean ticks,
        log every transition into the replay-determinism event
        stream."""
        if self.pressure(fleet):
            self.pressured += 1
            self.clean = 0
            if self.pressured >= max(1, self.cfg.window) \
                    and self.level < len(BROWNOUT_LEVELS) - 1:
                old = BROWNOUT_LEVELS[self.level]
                self.level += 1
                self.pressured = 0
                fleet._log_event(
                    "brownout", -1,
                    f"{old}->{BROWNOUT_LEVELS[self.level]}")
        else:
            self.clean += 1
            self.pressured = 0
            if self.clean >= max(1, self.cfg.cooldown) \
                    and self.level > 0:
                old = BROWNOUT_LEVELS[self.level]
                self.level -= 1
                self.clean = 0
                fleet._log_event(
                    "brownout", -1,
                    f"{old}->{BROWNOUT_LEVELS[self.level]}")

    def sheds(self, rank: int) -> bool:
        """Does the CURRENT level shed an arrival of this tier rank?
        Strict reverse priority: background (rank 2) from
        ``shed_background`` up, batch (rank 1) only at ``shed_batch``,
        interactive (rank 0) never."""
        if rank >= 2:
            return self.level >= 1
        if rank == 1:
            return self.level >= 3
        return False

    @property
    def squeezed(self) -> frozenset:
        """Tiers whose spec/chunk budgets every engine throttles at
        the current level (``ServingEngine.throttled_tiers``)."""
        return (frozenset({"batch"}) if self.level >= 2
                else frozenset())


# --------------------------------------------------------------- stats

@dataclass
class FleetStats:
    """Fleet-level accounting. Per-request ticks (TTFT/TPOT) use the
    deterministic tick clock; wall-time aggregates use the per-replica
    step time the fleet accumulates (replicas run concurrently on
    their own slices in production, so fleet wall = slowest replica)."""

    submitted: int = 0
    routed: dict = field(default_factory=dict)     # replica -> count
    affinity_hits: int = 0
    spills: int = 0
    probes: int = 0
    # admission control (RouterConfig.queue_cap): arrivals rejected
    # because every routable replica's queue was at cap, and the priced
    # retry-after each rejection was told to wait (perf-model ms)
    admission_rejections: int = 0
    retry_after_ms: list = field(default_factory=list)
    deaths: list = field(default_factory=list)     # (replica, tick)
    failover_requeued: int = 0
    failover_re_prefill_tokens: int = 0
    replica_time: dict = field(default_factory=dict)  # replica -> s
    # modeled (perf-model) step time per replica, ms — deterministic,
    # and sensitive to compute actually saved (prefix hits skip
    # prefill chunks), unlike host wall time on the CPU harness
    replica_model_ms: dict = field(default_factory=dict)
    # folded stats of engines that died/were replaced (revive swaps
    # the engine object; its counters must not vanish)
    retired_prefix_hits: int = 0
    retired_evictions: int = 0
    retired_generated: int = 0
    retired_preemptions: int = 0
    retired_tenant_preemptions: dict = field(default_factory=dict)
    # --- multi-tenant brownout / maintenance ---
    sheds: dict = field(default_factory=dict)         # tier -> count
    tenant_sheds: dict = field(default_factory=dict)  # tenant -> count
    retunes: list = field(default_factory=list)  # (tick, replica, n)
    records: dict = field(default_factory=dict)
    # rid -> {arrival, first_token_tick, completion_tick, n, tokens}
    # --- elastic fleet (grow / drain / migrate) ---
    # the replay-determinism object: every scale/drain/migration event
    # as (kind, replica, tick, detail) in occurrence order — same fleet
    # seed and trace ⇒ byte-identical list (test-pinned)
    events: list = field(default_factory=list)
    grows: list = field(default_factory=list)      # (replica, tick)
    drains: list = field(default_factory=list)     # (replica, start, done)
    drain_requeued: int = 0        # queued work handed back by a drain
    migrations: int = 0
    migrated_pages: int = 0
    migration_wire_bytes: int = 0
    # (migrate_ms, reprefill_ms) per migration — the perf_model.
    # migrate_vs_reprefill_ms verdict that justified each wire trip
    migration_priced: list = field(default_factory=list)
    migration_refusals: int = 0    # priced: re-prefill beat the wire
    migration_failures: int = 0    # wire exhausted; re-prefill fallback
    # --- long-context placement ---
    # (rid, priced reason) per arrival whose end-to-end KV fits NO
    # routable replica — refused outright (perf_model.
    # refuse_long_context prices the cp factor that would have held it)
    long_context_refusals: list = field(default_factory=list)

    @property
    def migrations_cheaper(self) -> int:
        """Migrations whose shipped wire priced UNDER the modeled
        re-prefill — by construction all of them (the fleet refuses the
        rest), so this equals ``migrations`` unless the pricing gate is
        broken; the CI smoke asserts it is nonzero."""
        return sum(1 for w, r in self.migration_priced if w < r)

    @property
    def completed(self) -> int:
        return sum(1 for r in self.records.values()
                   if r["completion_tick"] is not None)

    @property
    def lost_requests(self) -> int:
        return self.submitted - self.completed

    def _recs(self, tenant: str | None = None) -> list:
        if tenant is None:
            return list(self.records.values())
        return [r for r in self.records.values()
                if getattr(r["req"], "tenant", "default") == tenant]

    def _ttfts(self, tenant: str | None = None) -> list:
        return [r["first_token_tick"] - r["arrival"]
                for r in self._recs(tenant)
                if r["first_token_tick"] is not None]

    def _tpots(self, tenant: str | None = None) -> list:
        return [(r["completion_tick"] - r["first_token_tick"])
                / max(r["n"] - 1, 1)
                for r in self._recs(tenant)
                if r["completion_tick"] is not None]

    @property
    def p99_ttft_ticks(self) -> float:
        import numpy as np

        ts = self._ttfts()
        return float(np.percentile(np.asarray(ts), 99)) if ts else 0.0

    @property
    def p99_tpot_ticks(self) -> float:
        import numpy as np

        ts = self._tpots()
        return float(np.percentile(np.asarray(ts), 99)) if ts else 0.0

    def per_tenant(self, preemptions: dict | None = None) -> dict:
        """tenant -> goodput/latency/robustness view: submitted,
        completed, generated tokens, p99 TTFT/TPOT in fleet ticks,
        sheds, and (when the fleet passes its merged map) preemptions
        — the per-tenant observability surface the multi-tenant bench
        and CI smoke assert on."""
        import numpy as np

        out: dict = {}
        for rec in self.records.values():
            t = getattr(rec["req"], "tenant", "default")
            d = out.setdefault(t, {
                "submitted": 0, "completed": 0, "generated": 0,
                "p99_ttft_ticks": 0.0, "p99_tpot_ticks": 0.0,
                "sheds": 0, "preemptions": 0,
            })
            d["submitted"] += 1
            if rec["completion_tick"] is not None:
                d["completed"] += 1
                d["generated"] += rec["n"]
        for t, d in out.items():
            ts = self._ttfts(t)
            if ts:
                d["p99_ttft_ticks"] = float(
                    np.percentile(np.asarray(ts), 99))
            tp = self._tpots(t)
            if tp:
                d["p99_tpot_ticks"] = float(
                    np.percentile(np.asarray(tp), 99))
            d["sheds"] = self.tenant_sheds.get(t, 0)
            d["preemptions"] = (preemptions or {}).get(t, 0)
        return out


# --------------------------------------------------------------- fleet

class ServingFleet:
    """N replicas + a router + a fleet health ledger, driven on one
    deterministic tick clock. See the module docstring for the scoring
    and failover contracts.

    ``engines`` — list of built engines (one per replica; pair them
    with meshes from ``carve_replica_meshes`` on real topologies).
    ``seed`` — the fleet routing seed; installed via
    ``config.set_fleet_seed`` for the duration of :meth:`run` so cached
    kernel builds can't leak across differently-routed fleets.
    ``reserve`` — spare capacity the autoscaler may spawn from: a list
    of engines, zero-arg engine factories, or ``(factory, mesh)`` pairs
    (meshes from ``carve_replica_meshes(..., reserve=n)``). Factories
    defer building until the grow actually happens.
    ``autoscaler`` — an :class:`AutoscalerConfig`; None disables
    ledger-driven grow (the pre-elastic behavior).
    ``perf_spec`` — optional TpuSpec override for the migration pricing
    (tests flip the migrate-vs-reprefill verdict by shrinking
    ``dcn_gbps``).
    ``tenants`` — ``{tenant: TenantConfig}``; enables the deadline
    slack term, tier-priced retry-after, per-tenant fair share, and
    priority preemption (the map is pushed into every engine).
    ``brownout`` — a :class:`BrownoutConfig`; None disables
    load-shedding (the pre-brownout behavior).
    ``retune_every`` — run the grid-schedule ``background_retune`` in
    the fleet's own maintenance window every N ticks (low-pressure
    ticks only; suppressed during brownout). None disables.
    """

    def __init__(self, engines, *, seed: int = 0,
                 router: RouterConfig | None = None, health=None,
                 meshes=None, reserve=None, autoscaler=None,
                 perf_spec=None, tenants=None, brownout=None,
                 retune_every: int | None = None, ops=None):
        from triton_distributed_tpu.runtime.health import HealthLedger
        from triton_distributed_tpu.serving.protocol import ProtocolOps

        if not engines:
            raise ValueError("a fleet needs at least one replica")
        if router is not None and router.queue_cap is not None \
                and router.queue_cap < 1:
            raise ValueError(
                f"queue_cap must be >= 1 (got {router.queue_cap}) — "
                "a zero cap rejects every arrival forever")
        meshes = meshes or [None] * len(engines)
        self.replicas = [Replica(i, e, m)
                         for i, (e, m) in enumerate(zip(engines, meshes))]
        self.seed = seed
        # fleet-level protocol verbs live behind the same seam the
        # engines use, so servlint can drive (or mutate) them too
        self.ops = ops if ops is not None else ProtocolOps()
        self.health = health if health is not None else HealthLedger(
            seed=seed)
        self.router = FleetRouter(seed, router)
        self.queue: deque = deque()        # fleet arrivals, by time
        self.ticks = 0
        self.stats = FleetStats()
        self._dead: set = set()            # currently-dead replica idx
        self._death_handled: set = set()   # faults already consumed
        self._probing: dict = {}           # replica idx -> probe tick
        self._draining: dict = {}          # replica idx -> drain start
        self._retired: set = set()         # cleanly drained, gone
        self._reserve = list(reserve or [])
        self.autoscaler = (FleetAutoscaler(autoscaler, seed=seed)
                           if autoscaler is not None else None)
        self.perf_spec = perf_spec
        self.tenants = dict(tenants or {})
        self.router.tenants = self.tenants
        self.brownout = (BrownoutController(brownout, seed=seed)
                         if brownout is not None else None)
        self.retune_every = retune_every
        for r in self.replicas:
            self._wire_tenancy(r)

    def _wire_tenancy(self, replica: Replica) -> None:
        """Push the fleet tenant map into the replica's engines and
        hook engine preemptions into the replay-determinism event
        stream — called for every replica that enters the fleet
        (construction, grow, revive)."""
        for role in replica._roles:
            if self.tenants:
                role.tenants = self.tenants

            def on_preempt(by, victim, _idx=replica.index, _role=role):
                from triton_distributed_tpu.serving.engine import TIERS

                self._log_event(
                    "preempt", _idx,
                    f"rid={victim.rid} tier="
                    f"{TIERS[_role._rank(victim)]} by={by.rid}")

            role.on_preempt = on_preempt

    def _rank_of(self, req) -> int:
        """Fleet-side tier rank of a request — per-request priority
        first, then its tenant's tier, interactive (0) by default."""
        from triton_distributed_tpu.serving.engine import (
            DEFAULT_TENANT, tier_rank,
        )

        tc = self.tenants.get(getattr(req, "tenant", "default"),
                              DEFAULT_TENANT)
        return tier_rank(getattr(req, "priority", None) or tc.priority)

    # ---------------------------------------------------------- intake

    def submit(self, req) -> None:
        self.queue.append(req)
        self.stats.submitted += 1
        self.stats.records[req.rid] = {
            "arrival": req.arrival, "first_token_tick": None,
            "completion_tick": None, "n": 0, "tokens": None,
            "req": req,
        }

    def submit_trace(self, trace) -> None:
        for r in sorted(trace, key=lambda r: r.arrival):
            self.submit(r)

    @property
    def idle(self) -> bool:
        return (not self.queue
                and not self._draining
                and all(r.idle for r in self._alive()))

    def _alive(self) -> list:
        return [r for r in self.replicas
                if r.index not in self._dead
                and r.index not in self._retired]

    def _route_candidates(self) -> list:
        """Replicas the router may place NEW work on: alive and not
        draining — a draining replica finishes (or migrates) what it
        holds and admits nothing."""
        return [r for r in self._alive()
                if r.index not in self._draining]

    def rotation(self) -> tuple:
        """Replica indices currently receiving scored traffic — the
        ledger-driven grow/shrink surface (PROBATION members rejoin
        probe-first; UNHEALTHY members are out; draining members have
        stopped admitting)."""
        from triton_distributed_tpu.runtime.health import PeerState

        out = []
        for r in self._route_candidates():
            st = self.health.state(r.peer)
            if st not in (PeerState.UNHEALTHY, PeerState.PROBATION):
                out.append(r.index)
        return tuple(out)

    # -------------------------------------------------------- dispatch

    def _dispatch(self) -> int:
        """Route every arrived request. Runs under the
        ``router_dispatch`` chaos site: a fault-plan Stall there wedges
        the WHOLE fleet's admission (every replica starves at once) and
        an armed watchdog names it."""
        from triton_distributed_tpu.lang.launch import maybe_instrument

        body = maybe_instrument(
            self._dispatch_body, axis=None, site="router_dispatch",
            collective_id=("router_dispatch", self.ticks), n=1,
            step=self.ticks,
        )
        return body()

    def _dispatch_body(self) -> int:
        n = 0
        while self.queue and self.queue[0].arrival <= self.ticks:
            req = self.queue.popleft()
            if self._refuse_long_context(req):
                continue
            if self._shed_brownout(req):
                continue
            if self._reject_overload(req):
                continue
            target = self._route_probe(req)
            spilled = False
            if target is None:
                sess = getattr(req, "session", None)
                home_idx = (self.router.affinity.get(sess)
                            if sess is not None else None)
                target, spilled = self.router.route(
                    req, self._route_candidates(), self.health)
                if spilled and home_idx is not None \
                        and home_idx != target.index:
                    # the session re-homed but its prefix pages still
                    # live at the old home: ship them instead of
                    # letting admission re-prefill (when priced)
                    self._migrate_prefix(req, home_idx, target)
            target.submit(req)
            self.stats.routed[target.index] = (
                self.stats.routed.get(target.index, 0) + 1)
            if spilled:
                self.stats.spills += 1
            elif getattr(req, "session", None) is not None:
                self.stats.affinity_hits += 1
            n += 1
        return n

    def _refuse_long_context(self, req) -> bool:
        """Long-context placement gate: an arrival whose end-to-end KV
        fits NO routable replica is refused OUTRIGHT with the priced
        reason (``stats.long_context_refusals``). Unlike an overload
        bounce there is no retry-after — waiting cannot make pool
        capacity appear, so a priced retry would be a promise the
        fleet can never honor. The request is marked done with its
        ``refusal`` reason attached (the loud failure the client
        sees), and the event log records it for replay pins."""
        routable = self._routable()
        if not routable:
            return False       # route() raises the every-replica-dead error
        if any(r.fits_context(req) for r in routable):
            return False
        reason = self.router.long_context_refusal(req, routable)
        self.stats.long_context_refusals.append((req.rid, reason))
        self._log_event("long_context_refusal", -1, f"rid={req.rid}")
        req.refusal = reason
        req.done = True
        return True

    def _reject_overload(self, req) -> bool:
        """Admission control (``RouterConfig.queue_cap``): when every
        routable replica's queue is at cap, the arrival is REJECTED
        with a priced retry-after instead of deepening some replica's
        ``waiting`` without bound. The retry-after is the perf model's
        estimate of when the LIGHTEST ROUTABLE queue will have drained
        at the request's own tier (:meth:`_priced_retry`) — so a
        client backs off proportionally to the congestion its tier
        actually sees, not by a blind constant. The rejected request
        re-enters the fleet queue at the retry tick (the harness's
        stand-in for the client honoring Retry-After), so a flooded
        trace finishes with zero LOST requests — later, not never."""
        cap = self.router.cfg.queue_cap
        if cap is None:
            return False
        routable = self._routable()
        if not routable:
            return False       # route() raises the every-replica-dead error
        # depth at the arrival's OWN tier: a batch flood queued below
        # an interactive arrival is not depth it stands behind (the
        # same tier-visibility the priced retry uses) — single-tenant
        # fleets see the full queue, the pre-tier cap exactly
        rank = self._rank_of(req)
        if min(r.queue_depth(rank=rank, rank_of=self._rank_of)
               for r in routable) < cap:
            return False
        retry_ms, retry_ticks = self._priced_retry(req, routable)
        self.stats.admission_rejections += 1
        self._requeue_priced(req, retry_ms, retry_ticks)
        return True

    def _routable(self) -> list:
        """Route candidates the ledger actually admits traffic to —
        PROBATION and UNHEALTHY excluded. Every retry-after price MUST
        come off this set: a PROBATION replica's empty queue is not a
        wait any client can actually buy (it only takes seeded
        probes), so pricing off it would hand out retry-afters the
        fleet cannot honor (pinned by test)."""
        return [
            r for r in self._route_candidates()
            if self.router.health_factor(self.health.state(r.peer))
            is not None
        ]

    def _priced_retry(self, req, routable) -> tuple:
        """``(retry_ms, retry_ticks)`` for a bounced arrival: the
        modeled drain of the lightest ROUTABLE replica's queue AT THE
        REQUEST'S OWN TIER. Priority admission sorts tier-r retries
        ahead of every lower tier, so a tier-r client waits only on
        the queued work at rank ≤ r — per-tenant retry-after prices by
        the tenant's own tier, not the fleet mean. Single-tenant
        fleets price identically to the pre-tier behavior (every
        request is rank 0, the filter passes the whole queue)."""
        import math

        from triton_distributed_tpu.tune import perf_model

        light = min(routable, key=lambda r: (r.queue_depth(),
                                             r.load_ms(), r.index))
        rank = self._rank_of(req)
        role = light.admit_role
        ahead = sum(1 for q in list(role.waiting) + list(role.pending)
                    if self._rank_of(q) <= rank)
        retry_ms = perf_model.tiered_replica_load_ms(role, ahead)
        for other in light._roles:
            if other is not role:
                retry_ms += perf_model.replica_load_ms(other)
        step_ms = light.step_model_ms()
        retry_ticks = (max(1, math.ceil(retry_ms / step_ms))
                       if step_ms > 0 else 1)
        return retry_ms, retry_ticks

    def _requeue_priced(self, req, retry_ms: float,
                        retry_ticks: int) -> None:
        req.arrival = self.ticks + retry_ticks
        req.admission_retries = getattr(req, "admission_retries", 0) + 1
        self.stats.retry_after_ms.append(retry_ms)
        # re-enter in arrival order (stable sort keeps FIFO among ties)
        self.queue.append(req)
        self.queue = deque(sorted(self.queue, key=lambda r: r.arrival))

    def _shed_brownout(self, req) -> bool:
        """Brownout load-shedding: while the overload controller sits
        at a level that sheds this arrival's tier, bounce it with the
        same tier-priced retry-after as admission control — strict
        reverse-priority order (background first, batch only at the
        deepest level, interactive never) and zero lost requests (the
        retry re-enters the fleet queue and lands once the controller
        recovers)."""
        if self.brownout is None or self.brownout.level == 0:
            return False
        rank = self._rank_of(req)
        if not self.brownout.sheds(rank):
            return False
        routable = self._routable()
        if not routable:
            return False
        from triton_distributed_tpu.serving.engine import TIERS

        retry_ms, retry_ticks = self._priced_retry(req, routable)
        tier = TIERS[min(rank, len(TIERS) - 1)]
        self.stats.sheds[tier] = self.stats.sheds.get(tier, 0) + 1
        t = getattr(req, "tenant", "default")
        self.stats.tenant_sheds[t] = (
            self.stats.tenant_sheds.get(t, 0) + 1)
        self._log_event(
            "shed", -1,
            f"rid={req.rid} tier={tier} "
            f"level={BROWNOUT_LEVELS[self.brownout.level]} "
            f"retry@{self.ticks + retry_ticks}")
        self._requeue_priced(req, retry_ms, retry_ticks)
        return True

    def _route_probe(self, req):
        """A PROBATION replica whose seeded probe is due gets this
        request as its probe — traffic is the probe, exactly like the
        engine-level kernel probes."""
        from triton_distributed_tpu.runtime.health import PeerState

        for r in self._route_candidates():
            if r.index in self._probing:
                continue
            if self.health.state(r.peer) is PeerState.PROBATION \
                    and self.health.probe_due(r.peer, self.ticks):
                self._probing[r.index] = self.ticks
                self.stats.probes += 1
                return r
        return None

    # ------------------------------------------------------------ tick

    def tick(self) -> dict:
        """One fleet tick: consume replica deaths, maybe grow, route
        arrivals, advance drains (migrate-or-finish), step every live
        replica (concurrent slices in production; the host harness
        serializes them on one clock)."""
        from triton_distributed_tpu.runtime.health import PeerState

        self._check_replica_deaths()
        self._maybe_grow()
        self._observe_brownout()
        routed = self._dispatch()
        self._advance_drains()
        stepped = 0
        for r in self._alive():
            st = self.health.state(r.peer)
            if st is PeerState.UNHEALTHY:
                # a revived replica idles cleanly until the ledger
                # grants PROBATION — the gate before any probe traffic
                self.health.observe_clean(r.peer, step=self.ticks)
                continue
            if r.idle:
                continue
            self.stats.replica_model_ms[r.index] = (
                self.stats.replica_model_ms.get(r.index, 0.0)
                + r.step_model_ms())
            t0 = time.perf_counter()
            try:
                r.step()
            except Exception:
                if r.index in self._probing:
                    del self._probing[r.index]
                    self.health.probe_result(r.peer, False,
                                             step=self.ticks)
                    continue
                raise
            self.stats.replica_time[r.index] = (
                self.stats.replica_time.get(r.index, 0.0)
                + time.perf_counter() - t0)
            stepped += 1
            if r.index in self._probing:
                del self._probing[r.index]
                self.health.probe_result(r.peer, True, step=self.ticks)
        self._maybe_retune()
        self._update_records()
        self.ticks += 1
        return {"tick": self.ticks, "routed": routed,
                "stepped": stepped, "queued": len(self.queue)}

    def _observe_brownout(self) -> None:
        """One brownout observation per tick, then project the current
        squeeze set onto every live engine — ``throttled_tiers`` is
        what ``_chunk_for`` and the speculative ``_plan_row`` read to
        halve the batch tier's chunk and cap its draft budget."""
        if self.brownout is None:
            return
        self.brownout.observe(self)
        squeezed = self.brownout.squeezed
        for r in self._alive():
            for role in r._roles:
                role.throttled_tiers = squeezed

    def _maybe_retune(self) -> None:
        """Grid-schedule retuning inside the fleet's own MAINTENANCE
        WINDOW (PR-15 follow-on): every ``retune_every`` ticks, IF the
        tick is low-pressure — no arrived backlog, every routable
        queue empty, brownout at normal (an overloaded fleet has no
        business burning host time on schedule search). Retunes the
        hottest shape ledger among the routable replicas via
        ``background_retune`` (dryrun: perf-model priced, store
        persisted) and joins the thread inside the window — the next
        engine build resolves the winners for free."""
        if not self.retune_every or self.ticks == 0 \
                or self.ticks % self.retune_every:
            return
        if self.brownout is not None and self.brownout.level > 0:
            return
        if any(q.arrival <= self.ticks for q in self.queue):
            return
        routable = self._routable()
        if not routable or any(r.queue_depth() > 0 for r in routable):
            return

        def heat(replica):
            return sum(float(ent[1]) for role in replica._roles
                       for ent in role.stats.shape_ledger.values())

        target = max(routable, key=lambda r: (
            heat(r), -_u(self.seed, "retune", self.ticks, r.index)))
        role = target.admit_role
        if not role.stats.shape_ledger:
            return
        from triton_distributed_tpu.tune.traffic import (
            background_retune,
        )

        mc = role.model.config
        t = background_retune(
            role.stats, mesh_shape=(role.model.tp,),
            wire="int8" if getattr(mc, "kv_quant", None) is not None
            else None,
            dryrun=True)
        t.join()
        self.stats.retunes.append(
            (self.ticks, target.index, len(t.reports)))
        self._log_event("retune", target.index,
                        f"reports={len(t.reports)}")

    def _update_records(self) -> None:
        # the Request objects are shared with the engines (engines
        # mutate them in place), so the fleet reads progress directly
        for rec in self.stats.records.values():
            req = rec["req"]
            if req.generated and rec["first_token_tick"] is None:
                rec["first_token_tick"] = self.ticks
            if req.done and rec["completion_tick"] is None:
                rec["completion_tick"] = self.ticks
                rec["n"] = len(req.generated)
                rec["tokens"] = list(req.generated)

    def run(self, trace=None, max_ticks: int = 10_000) -> FleetStats:
        from triton_distributed_tpu import config as _config

        if trace is not None:
            self.submit_trace(trace)
        prev = _config.fleet_seed()
        _config.set_fleet_seed(self.seed)
        try:
            for _ in range(max_ticks):
                if self.idle:
                    break
                self.tick()
        finally:
            _config.set_fleet_seed(prev)
        return self.stats

    # -------------------------------------------------------- failover

    def _check_replica_deaths(self) -> None:
        """Consume the active plan's :class:`ReplicaDeath` faults —
        the fleet twin of ``DisaggregatedEngine._check_slice_deaths``."""
        from triton_distributed_tpu.runtime import faults as _faults

        plan = _faults.active_plan()
        if plan is None:
            return
        for k in plan.dead_replicas(self.ticks):
            if k in self._death_handled or k >= len(self.replicas):
                continue
            self._death_handled.add(k)
            self._kill(k)

    def _kill(self, k: int) -> None:
        self._dead.add(k)
        # a death interrupts any in-progress drain of the same replica:
        # the remaining resident rows take the failover path below
        # (cursor-0 requeue) instead of migrating — still zero lost
        interrupted = self._draining.pop(k, None)
        if not self._alive():
            raise RuntimeError(
                f"fault plan killed every fleet replica by tick "
                f"{self.ticks} — no survivor to fail over to")
        replica = self.replicas[k]
        self.health.record(
            "replica_death", replica.peer, step=self.ticks,
            detail=f"replica {k} died at tick {self.ticks}")
        self.stats.deaths.append((k, self.ticks))
        self._log_event(
            "death", k,
            f"mid-drain (started@{interrupted})"
            if interrupted is not None else "")
        self._retire_engine(replica)
        # drain: everything the replica held re-enters the FLEET queue
        # at cursor 0 (the recompute-eviction discipline: re-prefilling
        # prompt+generated resumes the exact cursor) and re-routes onto
        # the survivors this same tick — zero lost requests, and the
        # request-keyed sampler keeps the streams byte-identical
        drained = self.ops.failover_requeue(
            replica.held(), self.queue, self.stats)
        self.stats.failover_requeued += len(drained)
        replica.neutralize()
        # the dead replica's sessions must re-home on their next request
        for sess, idx in list(self.router.affinity.items()):
            if idx == k:
                del self.router.affinity[sess]
        # SV007 (servlint counterexample): if this death left ONLY
        # draining survivors, the fleet is permanently unroutable — the
        # backlog (including the rows just requeued above) waits on
        # replicas that admit no routed work, and drain completion
        # itself can wedge when the drain's migration target was the
        # replica that just died. Cancel the surviving drains: capacity
        # loss outranks the drain intent.
        if not self._route_candidates():
            for j in sorted(self._draining):
                self._draining.pop(j)
                self._log_event("drain_cancel", j, f"death@{k}")

    def _retire_engine(self, replica: Replica) -> None:
        for role in replica._roles:
            self.stats.retired_prefix_hits += role.stats.prefix_hits
            self.stats.retired_evictions += role.stats.evictions
            self.stats.retired_generated += role.stats.generated_tokens
            self.stats.retired_preemptions += role.stats.preemptions
            for t, n in role.stats.tenant_preemptions.items():
                self.stats.retired_tenant_preemptions[t] = (
                    self.stats.retired_tenant_preemptions.get(t, 0) + n)

    def revive(self, k: int, engine=None) -> None:
        """Bring replica ``k`` back with a FRESH engine (its old device
        state died with it). The ledger still holds the fatal
        ``replica_death`` record, so the replica re-enters rotation
        only through probation probes — never a blind re-add."""
        if k not in self._dead:
            raise ValueError(f"replica {k} is not dead")
        if engine is not None:
            self.replicas[k].engine = engine
        self._wire_tenancy(self.replicas[k])
        self._dead.discard(k)

    # ---------------------------------------------------------- elastic

    def _log_event(self, kind: str, replica: int,
                   detail: str = "") -> None:
        self.stats.events.append((kind, replica, self.ticks, detail))

    def _maybe_grow(self) -> None:
        if self.autoscaler is None or not self._reserve:
            return
        if self.autoscaler.should_grow(self):
            self.grow()

    def grow(self) -> int:
        """Spawn one replica from the reserve pool. The newcomer enters
        through the ledger, never blindly: the spawn is recorded as a
        fatal signal (UNHEALTHY), clean idle ticks earn PROBATION, and
        the router hands it traffic only as seeded probes until
        ``promote_after`` clean probes promote it to HEALTHY — the same
        PR 10 path a revived replica walks. Returns the new index."""
        if not self._reserve:
            raise ValueError("grow: the reserve pool is empty")
        spare = self._reserve.pop(0)
        mesh = None
        if isinstance(spare, tuple):
            spare, mesh = spare
        engine = spare() if callable(spare) else spare
        idx = len(self.replicas)
        replica = Replica(idx, engine, mesh)
        self.replicas.append(replica)
        self._wire_tenancy(replica)
        self.health.record(
            "autoscale_spawn", replica.peer, step=self.ticks,
            detail=f"replica {idx} spawned from the reserve pool",
            fatal=True)
        if self.autoscaler is not None:
            self.autoscaler.last_grow = self.ticks
            self.autoscaler.pressured = 0
        self.stats.grows.append((idx, self.ticks))
        self._log_event("grow", idx, "spawned from reserve")
        return idx

    def drain(self, k: int) -> None:
        """Planned retirement — the dual of :meth:`_kill`. Replica
        ``k`` stops admitting immediately (out of the routing set and
        the rotation); its queued-but-not-resident work re-enters the
        fleet queue now; resident rows either finish in place or
        MIGRATE their committed KV pages to a surviving replica (when
        :func:`~triton_distributed_tpu.tune.perf_model.
        migrate_vs_reprefill_ms` prices the wire under the recompute
        and a destination can reserve landing pages); once empty the
        replica retires cleanly. A chaos ``ReplicaDeath`` mid-drain
        falls back to the failover path — zero requests lost either
        way."""
        if k in self._dead or k in self._retired \
                or k >= len(self.replicas):
            raise ValueError(f"replica {k} is dead/retired/unknown")
        if k in self._draining:
            return
        others = [r for r in self._route_candidates()
                  if r.index != k and self.router.health_factor(
                      self.health.state(r.peer)) is not None]
        if not others:
            raise RuntimeError(
                f"cannot drain replica {k}: it is the last routable "
                "replica — grow or revive first")
        self._draining[k] = self.ticks
        replica = self.replicas[k]
        requeued = 0
        for role in replica._roles:
            requeued += len(self.ops.drain_requeue(role, self.queue))
        if requeued:
            self.queue = deque(sorted(self.queue,
                                      key=lambda r: r.arrival))
            self.stats.drain_requeued += requeued
        # session affinities stay pointed here until their next request
        # re-routes — the spill-migration path needs the old home
        self._log_event("drain_start", k, f"requeued={requeued}")

    def _advance_drains(self) -> None:
        """One drain step per draining replica: try to migrate every
        resident row off it (parked rows ride their own ship machinery
        and finish first), retire when nothing is left."""
        for k in sorted(self._draining):
            replica = self.replicas[k]
            for role in replica._roles:
                for req in list(role.slot_req):
                    if req is None or req.done or req.parked:
                        continue
                    self._try_migrate_live(req, replica, role)
            if not replica.held() and replica.idle:
                self._retire(k)

    def _retire(self, k: int) -> None:
        replica = self.replicas[k]
        start = self._draining.pop(k)
        self._retired.add(k)
        self._retire_engine(replica)
        replica.neutralize()
        for sess, idx in list(self.router.affinity.items()):
            if idx == k:
                del self.router.affinity[sess]
        self.stats.drains.append((k, start, self.ticks))
        self._log_event("drain_done", k, f"started@{start}")

    # ------------------------------------------------------- migration

    def _price_migration(self, role, n_pages: int) -> tuple:
        from triton_distributed_tpu.tune import perf_model

        mc = role.model.config
        hkv = mc.n_kv_heads
        return perf_model.migrate_vs_reprefill_ms(
            n_pages, page=role.cfg.page, hkv=hkv,
            g=mc.n_heads // max(hkv, 1), d=mc.head_dim,
            hidden=mc.hidden, n_layers=mc.n_layers,
            chunk=role.cfg.chunk,
            quant=getattr(mc, "kv_quant", None) is not None,
            spec=self.perf_spec)

    def _landing_shardings(self, role, with_scale: bool) -> tuple:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        # payload (L·2, P, Hkv, page[, D]): KV heads stay sharded over
        # the destination's tp axis, like the pools they land in — the
        # DisaggregatedEngine wire discipline
        q = NamedSharding(role.model.mesh, P(None, None, role.model.tp_axis))
        return q, (q if with_scale else None)

    def _migrate_transport(self, payload, dst_role):
        """The replica→replica wire: the kv_ship XLA transfer onto the
        destination mesh under the ``kv_migrate`` chaos site, with the
        PR 10 capped-jittered retry/backoff. Returns the landed payload
        or None when exhausted — the caller rolls back and the row
        falls back to re-prefill (this path's degradation target)."""
        import os as _os

        from triton_distributed_tpu.lang.launch import maybe_instrument
        from triton_distributed_tpu.tools.native import xla_kv_ship

        qpay, spay = payload
        shard = self._landing_shardings(dst_role, spay is not None)
        send = maybe_instrument(
            lambda: xla_kv_ship((qpay, spay), shard), axis=None,
            site="kv_migrate",
            collective_id=("kv_migrate", self.ticks), n=1,
            step=self.ticks)
        retries = max(1, int(_os.environ.get("TDTPU_SHIP_RETRIES", "3")))
        backoff = float(_os.environ.get("TDTPU_SHIP_BACKOFF", "0.2"))
        cap = float(_os.environ.get("TDTPU_SHIP_BACKOFF_CAP", "2.0"))
        for attempt in range(retries):
            try:
                return send()
            except Exception:
                if attempt == retries - 1:
                    self.health.record(
                        "migrate_transport_error", "site:kv_migrate",
                        step=self.ticks)
                    return None
                delay = min(cap, backoff * (2.0 ** attempt))
                delay *= 0.5 + self.health.uniform(
                    "migrate_backoff", self.ticks, attempt)
                time.sleep(delay)

    def _try_migrate_live(self, req, src: Replica, role) -> bool:
        """Migrate one RESIDENT row off ``src``: reserve landing pages
        at the best-scoring destination with room, ship the committed
        pages (everything below the cursor) in pool-native wire form,
        commit, release the source. Token-exact: the cursor survives
        the move and sampling is keyed ``(seed, rid, n_generated)``, so
        the stream continues as if it never moved. False = the row
        stays (priced against us, no destination room, or the wire
        failed) and finishes in place."""
        pslot = req.slot
        npg = role._pages_held(req.cursor)
        if npg == 0:
            # nothing committed yet: hand the request straight back to
            # the fleet queue instead of burning drain time on it
            if pslot is not None:
                role._free_slot(pslot)
            req.slot = None
            self.queue.append(req)
            self.queue = deque(sorted(self.queue,
                                      key=lambda r: r.arrival))
            self.stats.drain_requeued += 1
            return False
        wire_ms, reprefill_ms = self._price_migration(role, npg)
        if wire_ms >= reprefill_ms:
            self.stats.migration_refusals += 1
            return False
        cands = [r for r in self._route_candidates()
                 if r.index != src.index
                 and self.router.health_factor(
                     self.health.state(r.peer)) is not None]
        mean = (sum(r.load_ms() for r in cands) / len(cands)
                if cands else 0.0)
        cands.sort(key=lambda r: (
            -(self.router.score(r, req, self.health.state(r.peer),
                                mean) or 0.0),
            _u(self.seed, "migrate", req.rid, r.index)))
        for dst in cands:
            dst_role = dst.admit_role
            if dst_role.cfg.page != role.cfg.page:
                continue               # pages ship verbatim
            out = self.ops.migrate_live_core(
                req, role, dst_role, pslot, npg,
                lambda p, _d=dst_role: self._migrate_transport(p, _d))
            if out is None:
                continue               # no slot/pages there; try next
            if out is False:
                self.stats.migration_failures += 1
                self._log_event("migrate_failed", src.index,
                                f"rid={req.rid} dst={dst.index}")
                return False
            dslot, dpids = out
            self._warm_migrated_prefix(req, dst_role, dpids)
            sess = getattr(req, "session", None)
            if sess is not None:
                self.router.affinity[sess] = dst.index
            self._account_migration(role, npg, wire_ms, reprefill_ms)
            self._log_event(
                "migrate", src.index,
                f"rid={req.rid} pages={npg} -> replica {dst.index}")
            return True
        return False

    def _migrate_prefix(self, req, home_idx: int, dst: Replica) -> bool:
        """Spill-path migration: the request re-homed, but its prefix
        pages still live in the OLD home's pool (a draining, full, or
        outscored replica). Ship the resident full-page chain into
        destination CACHE pages — alloc, land, register under the same
        chain hashes, then release to the reclaimable cache — so
        admission at the new home attaches the pages instead of
        re-prefilling them. Priced like every migration; skipped
        whenever the wire loses."""
        from triton_distributed_tpu.serving.state import page_chain_hash

        if home_idx in self._dead or home_idx in self._retired \
                or home_idx >= len(self.replicas) \
                or home_idx == dst.index:
            return False
        src_role = self.replicas[home_idx].admit_role
        dst_role = dst.admit_role
        if not (src_role.pool.prefix_cache
                and dst_role.pool.prefix_cache):
            return False
        if src_role.cfg.page != dst_role.cfg.page:
            return False
        # cp-mismatched replicas shard their pools differently: a page
        # chain gathered in one layout does not land 1:1 in the other,
        # so the ship is refused here and admission re-prefills
        if getattr(src_role.pool, "cp", 1) \
                != getattr(dst_role.pool, "cp", 1):
            return False
        page = src_role.cfg.page
        seq = req.seq
        src_pids, hashes, h = [], [], 0
        for p in range((len(seq) - 1) // page):
            h = page_chain_hash(h, seq[p * page:(p + 1) * page])
            pg = src_role.pool.lookup(h, p)
            if pg is None:
                break
            src_pids.append(int(pg))
            hashes.append(h)
        npg = len(src_pids)
        if npg == 0 or dst.overlap_pages(req) >= npg:
            return False
        wire_ms, reprefill_ms = self._price_migration(src_role, npg)
        if wire_ms >= reprefill_ms:
            self.stats.migration_refusals += 1
            return False
        if npg > dst_role.pool.available - dst_role._committed_pages():
            return False
        dpids = [dst_role.pool.alloc(i) for i in range(npg)]
        if any(pg is None for pg in dpids):
            for pg in dpids:
                if pg is not None:
                    dst_role.pool.release(pg)
            return False
        payload = src_role.gather_pages(src_pids)
        shipped = self._migrate_transport(payload, dst_role)
        if shipped is None:
            for pg in dpids:
                dst_role.pool.release(pg)
            self.stats.migration_failures += 1
            self._log_event("migrate_failed", home_idx,
                            f"rid={req.rid} dst={dst.index}")
            return False
        dst_role.land_pages(dpids, *shipped)
        for pg, hh in zip(dpids, hashes):
            dst_role.pool.register(int(pg), hh)
        for pg in dpids:
            # refcount 0 + registered = reclaimable cache residency:
            # attachable by the arriving request, reclaimed under
            # pressure, never leaked
            dst_role.pool.release(int(pg))
        self._account_migration(src_role, npg, wire_ms, reprefill_ms)
        self._log_event(
            "migrate", home_idx,
            f"rid={req.rid} pages={npg} -> replica {dst.index} "
            f"(prefix)")
        return True

    def _account_migration(self, role, npg: int, wire_ms: float,
                           reprefill_ms: float) -> None:
        from triton_distributed_tpu.kernels.kv_ship import (
            ship_wire_bytes,
        )

        mc = role.model.config
        st = self.stats
        st.migrations += 1
        st.migrated_pages += npg
        st.migration_wire_bytes += ship_wire_bytes(
            npg, role.cfg.page, mc.n_kv_heads, mc.head_dim,
            mc.n_layers, getattr(mc, "kv_quant", None) is not None)
        st.migration_priced.append((wire_ms, reprefill_ms))

    def _warm_migrated_prefix(self, req, dst_role, dpids) -> None:
        """The landed pages below the cursor are frozen: register their
        chain hashes at the destination (the ``_warm_prefix_cache``
        discipline) so siblings sharing the prefix attach without
        another wire trip. Partial trailing pages stay private."""
        if not dst_role.pool.prefix_cache:
            return
        full = min(req.cursor // dst_role.cfg.page, len(dpids))
        if full <= 0:
            return
        hashes = dst_role._page_hashes(req, full)
        for p in range(full):
            dst_role.pool.register(int(dpids[p]), hashes[p])

    # ------------------------------------------------------ aggregates

    @property
    def prefix_hits(self) -> int:
        return self.stats.retired_prefix_hits + sum(
            role.stats.prefix_hits
            for r in self.replicas for role in r._roles
            if r.index not in self._dead)

    @property
    def evictions(self) -> int:
        return self.stats.retired_evictions + sum(
            role.stats.evictions
            for r in self.replicas for role in r._roles
            if r.index not in self._dead)

    @property
    def preemptions(self) -> int:
        return self.stats.retired_preemptions + sum(
            role.stats.preemptions
            for r in self.replicas for role in r._roles
            if r.index not in self._dead)

    def tenant_preemptions(self) -> dict:
        """tenant -> preemption count, live engines + retired."""
        out = dict(self.stats.retired_tenant_preemptions)
        for r in self.replicas:
            if r.index in self._dead:
                continue
            for role in r._roles:
                for t, n in role.stats.tenant_preemptions.items():
                    out[t] = out.get(t, 0) + n
        return out

    def per_tenant(self) -> dict:
        """:meth:`FleetStats.per_tenant` with the fleet's merged
        preemption map filled in — the one-call observability view."""
        return self.stats.per_tenant(self.tenant_preemptions())

    @property
    def generated_tokens(self) -> int:
        return sum(r["n"] for r in self.stats.records.values()
                   if r["completion_tick"] is not None)

    def token_streams(self) -> dict:
        """rid -> completed token list (None while incomplete) — what
        the bench diffs against the fault-free reference run."""
        return {rid: rec["tokens"]
                for rid, rec in self.stats.records.items()}
