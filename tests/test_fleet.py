"""ISSUE-11 fleet suite: the health- and cache-aware router over N
engine replicas and its ReplicaDeath failover discipline.

The tentpole under test is :mod:`triton_distributed_tpu.serving.fleet`:

* **scoring** — the admission score (prefix overlap × health factor /
  fleet-relative load) against hand-built expectations, and the
  affinity/spill rules (queue at the prefix home while its score beats
  the best replica with room; spill — and re-home — when it doesn't);
* **cache-aware routing** — a shared-prefix session trace lands more
  prefix-cache page hits under the scored router than under the
  round-robin baseline;
* **failover** — a :class:`ReplicaDeath` mid-trace drains the dead
  replica's requests back through the router onto survivors: zero lost
  requests, token streams byte-identical to the fault-free run
  (sampling is keyed ``(seed, rid, n_generated)``, so placement can
  never change tokens); both-replicas-dead is a loud refusal;
* **probation re-entry** — a revived replica earns PROBATION through
  clean ticks and re-enters rotation through seeded probe traffic,
  never a blind re-add;
* **determinism** — same fleet seed ⇒ identical placement, and the
  fleet seed folds into ``config.interp_key`` like the fault plan;
* **chaos sites** — the ``router_dispatch`` site and the XLA
  ``kv_ship`` fallback transport are heartbeated: a fault-plan Stall
  under an armed watchdog trips into the ledger instead of wedging.

All sim-free: the fleet/router layers are host code, the engines run
their CPU paths (XLA twins).
"""

import gc
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_distributed_tpu import config
from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.runtime import faults, health, watchdog
from triton_distributed_tpu.runtime.faults import (
    FaultPlan,
    ReplicaDeath,
    Stall,
    parse_plan,
)
from triton_distributed_tpu.runtime.health import HealthLedger, PeerState
from triton_distributed_tpu.runtime.watchdog import WatchdogTimeout
from triton_distributed_tpu.serving import (
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
)
from triton_distributed_tpu.serving.fleet import (
    FleetRouter,
    RouterConfig,
    ServingFleet,
)

#: tier-1 fast subset (ci/fast.sh): the fleet half of the robustness
#: story
pytestmark = pytest.mark.fast


@pytest.fixture(autouse=True)
def _isolated_ledgers():
    yield
    health.set_ledger(None)
    faults.set_fault_plan(None)
    watchdog.clear_trip()
    config.set_fleet_seed(None)
    gc.collect()


CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32, kv_quant="int8",
)

ECFG = dict(slots=4, token_budget=48, chunk=16, page=8, npages=32,
            prefix_cache=True, temperature=0.7, top_k=40, seed=11)


@pytest.fixture(scope="module")
def fleet_models():
    """Two replica models on their own 1-device meshes, same params."""
    devs = jax.devices()
    out = []
    params = None
    for k in range(2):
        mesh = Mesh(np.asarray(devs[k:k + 1]), ("tp",))
        model = Transformer(TransformerConfig(**CFG), mesh, "tp", ())
        if params is None:
            params = model.init(jax.random.PRNGKey(0))
        p = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                         model.shardings())
        out.append((model, p))
    return out


def _fast_ledger(seed=0):
    return HealthLedger(seed=seed, probation_after=1, promote_after=1,
                        probe_interval=2)


def _fleet(fleet_models, policy="scored", seed=1, ledger=None, **ecfg):
    kw = dict(ECFG, **ecfg)
    engines = [ServingEngine(m, p, EngineConfig(**kw), use_pallas=False)
               for m, p in fleet_models]
    return ServingFleet(engines, seed=seed,
                        router=RouterConfig(policy=policy),
                        health=ledger)


def _req(rid, arrival, session=None, plen=20, max_new=5, prefix=None):
    rng = np.random.default_rng(1000 + rid)
    prompt = rng.integers(0, CFG["vocab"], (plen,)).astype(np.int32)
    if prefix is not None:
        prompt = np.concatenate(
            [prefix, prompt[:6].astype(np.int32)])
    r = Request(rid=rid, prompt=prompt, max_new=max_new,
                arrival=arrival)
    if session is not None:
        r.session = session
    return r


def _trace(n=8, session_every=None, prefix=None, spread=1.0):
    out = []
    for i in range(n):
        sess = ("s" if session_every and i % session_every == 0
                else None)
        out.append(_req(i, arrival=i * spread, session=sess,
                        prefix=prefix if sess else None))
    return out


# ------------------------------------------------------------- scoring

class _StubReplica:
    def __init__(self, index, overlap=0, load=0.0, room=True):
        self.index = index
        self.peer = f"replica:{index}"
        self._overlap, self._load, self._room = overlap, load, room

    def overlap_pages(self, req):
        return self._overlap

    def load_ms(self):
        return self._load

    def can_accept(self, req):
        return self._room

    def fits_context(self, req):
        return True


class _StubLedger:
    def __init__(self, states=None):
        self._states = states or {}

    def state(self, peer):
        return self._states.get(peer, PeerState.HEALTHY)


class TestScoring:
    def test_score_matches_hand_formula(self):
        router = FleetRouter(seed=0)
        r = _StubReplica(0, overlap=4, load=2.0)
        # (1 + w_prefix*4) * hf / (1 + w_load * load/mean)
        assert router.score(r, None, PeerState.HEALTHY, 2.0) \
            == pytest.approx(5.0 / 2.0)
        assert router.score(r, None, PeerState.SUSPECT, 2.0) \
            == pytest.approx(5.0 / 4.0)
        assert router.score(r, None, PeerState.UNHEALTHY, 2.0) is None
        assert router.score(r, None, PeerState.PROBATION, 2.0) is None
        # no load anywhere -> pure prefix * health
        assert router.score(r, None, PeerState.HEALTHY, 0.0) \
            == pytest.approx(5.0)

    def test_route_picks_highest_score(self):
        router = FleetRouter(seed=0)
        cold = _StubReplica(0, overlap=0, load=1.0)
        warm = _StubReplica(1, overlap=5, load=1.0)
        chosen, spilled = router.route(
            _req(0, 0.0), [cold, warm], _StubLedger())
        assert chosen is warm and not spilled

    def test_route_excludes_condemned(self):
        router = FleetRouter(seed=0)
        sick = _StubReplica(0, overlap=9)
        ok = _StubReplica(1)
        led = _StubLedger({"replica:0": PeerState.UNHEALTHY})
        chosen, _ = router.route(_req(0, 0.0), [sick, ok], led)
        assert chosen is ok
        led = _StubLedger({"replica:0": PeerState.UNHEALTHY,
                           "replica:1": PeerState.PROBATION})
        with pytest.raises(RuntimeError, match="no survivor"):
            router.route(_req(0, 0.0), [sick, ok], led)

    def test_affinity_sticks_and_follows(self):
        router = FleetRouter(seed=0)
        a, b = _StubReplica(0), _StubReplica(1)
        req = _req(0, 0.0, session="s")
        router.affinity["s"] = 0
        chosen, spilled = router.route(req, [a, b], _StubLedger())
        assert chosen is a and not spilled
        assert router.affinity["s"] == 0

    def test_full_home_queues_while_score_justifies(self):
        """A full home with a resident prefix still wins: waiting where
        the pages live beats re-prefilling them elsewhere."""
        router = FleetRouter(seed=0)
        home = _StubReplica(0, overlap=10, load=1.0, room=False)
        other = _StubReplica(1, overlap=0, load=1.0, room=True)
        router.affinity["s"] = 0
        chosen, spilled = router.route(
            _req(0, 0.0, session="s"), [home, other], _StubLedger())
        assert chosen is home and not spilled

    def test_full_cold_home_spills_and_rehomes(self):
        router = FleetRouter(seed=0)
        home = _StubReplica(0, overlap=0, load=3.0, room=False)
        other = _StubReplica(1, overlap=0, load=1.0, room=True)
        router.affinity["s"] = 0
        chosen, spilled = router.route(
            _req(0, 0.0, session="s"), [home, other], _StubLedger())
        assert chosen is other and spilled
        assert router.affinity["s"] == 1   # affinity follows the spill


# ------------------------------------------------- cache-aware routing

class TestCacheAwareRouting:
    def test_prefix_routing_beats_round_robin(self, fleet_models):
        """A session's followers land where the leader's prefix pages
        are resident under the scored router; round-robin scatters them
        and pays the prefill once per replica."""
        rng = np.random.default_rng(7)
        prefix = rng.integers(0, CFG["vocab"], (80,)).astype(np.int32)

        def trace():
            # leader at 0, followers after its prefill completed,
            # poisson-ish fillers in between
            out = [_req(0, 0.0, session="s", prefix=prefix)]
            out += [_req(1 + j, 1.0 + j) for j in range(4)]
            out += [_req(5 + j, 8.0 + 1.5 * j, session="s",
                         prefix=prefix) for j in range(4)]
            return out

        scored = _fleet(fleet_models, "scored")
        scored.run(trace())
        rr = _fleet(fleet_models, "round_robin")
        rr.run(trace())
        assert scored.stats.lost_requests == 0
        assert rr.stats.lost_requests == 0
        assert scored.prefix_hits > rr.prefix_hits
        assert scored.stats.completed == len(trace())
        assert scored.generated_tokens > 0


# ------------------------------------------------------------ failover

class TestReplicaDeathFailover:
    def _session_trace(self):
        # session "s" pinned to replica 1 via the public affinity map,
        # so the step-4 death is guaranteed to catch in-flight work
        out = [_req(i, i * 0.7, session="s" if i % 2 else None,
                    max_new=6) for i in range(8)]
        return out

    def test_death_failover_token_exact(self, fleet_models):
        ref = _fleet(fleet_models, "scored")
        ref.router.affinity["s"] = 1
        ref.run(self._session_trace())
        assert ref.stats.lost_requests == 0
        ref_tokens = ref.token_streams()

        fleet = _fleet(fleet_models, "scored")
        fleet.router.affinity["s"] = 1
        plan = FaultPlan(seed=1,
                         faults=(ReplicaDeath(replica=1, step=4),))
        with faults.fault_plan(plan):
            stats = fleet.run(self._session_trace())
        assert stats.lost_requests == 0
        assert stats.completed == 8
        assert stats.deaths == [(1, 4)]
        assert stats.failover_requeued >= 1
        assert fleet.health.state("replica:1") is PeerState.UNHEALTHY
        assert fleet.rotation() == (0,)
        assert fleet.token_streams() == ref_tokens
        # run() restored the ambient fleet seed
        assert config.fleet_seed() is None

    def test_all_replicas_dead_refuses(self, fleet_models):
        fleet = _fleet(fleet_models)
        plan = FaultPlan(seed=1, faults=(
            ReplicaDeath(replica=0, step=2),
            ReplicaDeath(replica=1, step=2)))
        with faults.fault_plan(plan):
            with pytest.raises(RuntimeError, match="no survivor"):
                fleet.run(_trace())

    def test_probation_reentry_after_revive(self, fleet_models):
        """A revived replica re-enters rotation through the probation
        probe path: clean ticks earn PROBATION, a seeded probe carries
        real traffic, a clean probe earns HEALTHY — never a blind
        re-add."""
        fleet = _fleet(fleet_models, ledger=_fast_ledger())
        plan = FaultPlan(seed=1,
                         faults=(ReplicaDeath(replica=1, step=2),))
        with faults.fault_plan(plan):
            fleet.run(_trace())
        assert fleet.rotation() == (0,)

        m, p = fleet_models[1]
        fleet.revive(1, ServingEngine(m, p, EngineConfig(**ECFG),
                                      use_pallas=False))
        base = fleet.ticks
        second = [_req(100 + i, base + 1.0 + i, max_new=4)
                  for i in range(8)]
        fleet.run(second)
        assert fleet.stats.lost_requests == 0
        assert fleet.stats.probes >= 1
        assert fleet.health.state("replica:1") is PeerState.HEALTHY
        assert fleet.rotation() == (0, 1)
        assert fleet.stats.routed.get(1, 0) >= 1

    def test_revive_requires_dead(self, fleet_models):
        fleet = _fleet(fleet_models)
        with pytest.raises(ValueError, match="not dead"):
            fleet.revive(0)


# --------------------------------------------------------- determinism

class TestDeterminism:
    def _placements(self, fleet_models, seed):
        fleet = _fleet(fleet_models, seed=seed)
        placed = []
        orig = FleetRouter.route

        def spy(router, req, replicas, ledger):
            r, sp = orig(router, req, replicas, ledger)
            placed.append((req.rid, r.index, sp))
            return r, sp

        fleet.router.route = types.MethodType(spy, fleet.router)
        fleet.run(_trace(n=10, session_every=3))
        return placed, dict(fleet.stats.routed)

    def test_same_seed_identical_placement(self, fleet_models):
        p1, r1 = self._placements(fleet_models, seed=5)
        p2, r2 = self._placements(fleet_models, seed=5)
        assert p1 == p2
        assert r1 == r2

    def test_fleet_seed_in_interp_key(self):
        base = config.interp_key()
        config.set_fleet_seed(3)
        keyed = config.interp_key()
        assert keyed != base
        assert 3 in keyed
        config.set_fleet_seed(None)
        assert config.interp_key() == base

    def test_run_installs_fleet_seed(self, fleet_models):
        seen = {}
        fleet = _fleet(fleet_models, seed=9)
        orig_tick = fleet.tick

        def spy():
            seen["seed"] = config.fleet_seed()
            return orig_tick()

        fleet.tick = spy
        fleet.run(_trace(n=2))
        assert seen["seed"] == 9
        assert config.fleet_seed() is None

    def test_parse_plan_replica_death_roundtrip(self):
        plan = parse_plan("seed=2; ReplicaDeath(replica=1, step=8)")
        assert plan.seed == 2
        assert plan.faults == (ReplicaDeath(replica=1, step=8),)
        assert plan.dead_replicas(7) == ()
        assert plan.dead_replicas(8) == (1,)
        assert plan.dead_replicas() == (1,)


# --------------------------------------------------------- chaos sites

class TestChaosSites:
    def test_router_dispatch_stall_trips_watchdog(self, fleet_models):
        """A fault-plan Stall at the router_dispatch site wedges the
        WHOLE fleet's admission; an armed watchdog trips, names the
        site, releases the gate, and the trace still completes."""
        fleet = _fleet(fleet_models)
        plan = FaultPlan(seed=0,
                         faults=(Stall(site="router_dispatch", rank=0),))
        box = {}
        with faults.fault_plan(plan):
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.2):
                    box["stats"] = fleet.run(_trace(n=4))
        assert box["stats"].lost_requests == 0
        assert fleet.health.state("site:router_dispatch") \
            is PeerState.UNHEALTHY

    def test_xla_kv_ship_fallback_is_heartbeated(self):
        """Satellite pin: the XLA collective-fallback KV ship transport
        runs under the kv_ship watchdog instrument — the LAST
        unheartbeated fallback entry point. A Stall there trips into
        the ledger instead of wedging the transfer."""
        from triton_distributed_tpu.tools import native

        led = HealthLedger(seed=0)
        payload = {"pages": np.ones((2, 4), np.int8)}
        plan = FaultPlan(seed=0, faults=(Stall(site="kv_ship", rank=0),))
        with faults.fault_plan(plan):
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.2):
                    out = native.xla_kv_ship(
                        payload, {"pages": None})
                    # stall released by the trip; bytes still intact
                    assert np.array_equal(out["pages"],
                                          payload["pages"])
        assert led.state("site:kv_ship") is PeerState.UNHEALTHY


# -------------------------------------------- admission control (cap)

class TestAdmissionControl:
    """RouterConfig.queue_cap: a flooded trace is REJECTED with a
    priced retry-after once every routable replica's queue is at cap —
    `waiting` stops growing without bound, and nothing is lost (the
    harness re-enters rejected requests at their retry tick, standing
    in for a client honoring Retry-After)."""

    def _flooded_fleet(self, fleet_models, cap, slots=2):
        kw = dict(ECFG, slots=slots, npages=24)
        engines = [ServingEngine(m, p, EngineConfig(**kw),
                                 use_pallas=False)
                   for m, p in fleet_models]
        return ServingFleet(engines, seed=1,
                            router=RouterConfig(queue_cap=cap))

    def _flood(self, n):
        return [_req(i, arrival=0, plen=10, max_new=4)
                for i in range(n)]

    def test_flood_rejects_with_priced_retry_after(self, fleet_models):
        fleet = self._flooded_fleet(fleet_models, cap=2)
        stats = fleet.run(self._flood(14))
        assert stats.admission_rejections > 0
        assert stats.lost_requests == 0
        # the retry-after is PRICED (perf-model ms), never a blind 0
        assert len(stats.retry_after_ms) == stats.admission_rejections
        assert all(ms > 0 for ms in stats.retry_after_ms)
        # the cap held: no replica's queue ever exceeded cap + the
        # one-tick dispatch batch the cap is applied within
        assert all(r.queue_depth() == 0 for r in fleet.replicas)

    def test_cap_bounds_queue_depth_vs_uncapped(self, fleet_models):
        """The uncapped fleet buffers the whole flood in `waiting`; the
        capped fleet never queues deeper than cap at dispatch time."""
        kw = dict(ECFG, slots=2, npages=24)

        def depth_trace(router):
            engines = [ServingEngine(m, p, EngineConfig(**kw),
                                     use_pallas=False)
                       for m, p in fleet_models]
            fleet = ServingFleet(engines, seed=1, router=router)
            fleet.submit_trace(self._flood(14))
            depths = []
            for _ in range(200):
                if fleet.idle:
                    break
                fleet.tick()
                depths.append(max(r.queue_depth()
                                  for r in fleet.replicas))
            return fleet.stats, max(depths)

        un_stats, un_depth = depth_trace(RouterConfig())
        cap_stats, cap_depth = depth_trace(RouterConfig(queue_cap=2))
        assert un_stats.lost_requests == 0
        assert cap_stats.lost_requests == 0
        assert un_stats.admission_rejections == 0
        assert cap_stats.admission_rejections > 0
        assert cap_depth < un_depth, (cap_depth, un_depth)
        # dispatch admits into slots before queueing, so post-tick
        # depth stays bounded by the cap itself
        assert cap_depth <= 2

    def test_flood_with_replica_death_chaos(self, fleet_models):
        """Chaos pin: the cap keeps rejecting (on the survivor's queue
        alone) across a mid-flood ReplicaDeath, and the drain + retry
        paths compose — zero lost requests."""
        fleet = self._flooded_fleet(fleet_models, cap=2)
        plan = faults.parse_plan(
            "seed=1; ReplicaDeath(replica=1, step=3)")
        with faults.fault_plan(plan):
            stats = fleet.run(self._flood(12))
        assert stats.deaths == [(1, 3)]
        assert stats.admission_rejections > 0
        assert stats.lost_requests == 0
        assert stats.failover_requeued >= 0

    def test_zero_cap_refused(self, fleet_models):
        with pytest.raises(ValueError, match="queue_cap"):
            self._flooded_fleet(fleet_models, cap=0)


# ------------------------------------------- elastic fleet (ISSUE-13)

def _spare_factory(fleet_models, k=1, **ecfg):
    m, p = fleet_models[k]
    kw = dict(ECFG, **ecfg)
    return lambda: ServingEngine(m, p, EngineConfig(**kw),
                                 use_pallas=False)


class TestCarveReserve:
    def test_reserve_split_and_back_compat(self):
        from triton_distributed_tpu.runtime.topology import (
            carve_replica_meshes,
        )

        devs = jax.devices()
        active, spares = carve_replica_meshes(2, devs, reserve=1)
        assert len(active) == 2 and len(spares) == 1
        # reserve=0 keeps returning the pre-elastic flat list
        flat = carve_replica_meshes(2, devs)
        assert isinstance(flat, list) and len(flat) == 2
        with pytest.raises(ValueError, match="reserve"):
            carve_replica_meshes(2, devs, reserve=-1)


class _ScriptedScaler:
    """FleetAutoscaler with a scripted pressure signal — isolates the
    window/cooldown flap damping from the perf model."""

    def __init__(self, cfg, script):
        from triton_distributed_tpu.serving import FleetAutoscaler

        self.inner = FleetAutoscaler(cfg)
        self.inner.pressure = lambda fleet: bool(script.pop(0))

    def run(self, n):
        import types as _t

        decisions = []
        for t in range(n):
            fleet = _t.SimpleNamespace(ticks=t, _alive=lambda: [None])
            if self.inner.should_grow(fleet):
                decisions.append(t)
                self.inner.last_grow = t
                self.inner.pressured = 0
        return decisions


class TestAutoscaler:
    def test_window_and_cooldown_damping(self):
        from triton_distributed_tpu.serving import AutoscalerConfig

        cfg = AutoscalerConfig(slo_ms=1.0, window=2, cooldown=4)
        # pressure: sustained from t=1..9 with a one-tick dip at t=5
        script = [False, True, True, True, True, False,
                  True, True, True, True]
        grows = _ScriptedScaler(cfg, script).run(10)
        # first grow needs TWO consecutive pressured ticks (t=2); the
        # dip resets the window, then the second grow waits out BOTH
        # the rebuilt window (t=7) and the cooldown (7 - 2 >= 4)
        assert grows == [2, 7]

    def test_grow_spawns_probation_gated_replica(self, fleet_models):
        from triton_distributed_tpu.serving import AutoscalerConfig

        m0, p0 = fleet_models[0]
        engines = [ServingEngine(m0, p0, EngineConfig(**ECFG),
                                 use_pallas=False)]
        fleet = ServingFleet(
            engines, seed=1, router=RouterConfig(),
            health=_fast_ledger(),
            reserve=[_spare_factory(fleet_models)],
            autoscaler=AutoscalerConfig(slo_ms=0.0, window=2,
                                        cooldown=3, max_replicas=2))
        # staggered arrivals: the flood keeps arriving PAST the grow,
        # so the probe path has dispatch-time traffic to feed on
        trace = [_req(i, i * 0.5, plen=12, max_new=5)
                 for i in range(18)]
        stats = fleet.run(trace)
        assert stats.lost_requests == 0
        assert len(stats.grows) == 1          # max_replicas damped
        grown, at = stats.grows[0]
        assert grown == 1 and at >= 1         # window needed 2 ticks
        # the newcomer walked the PR-10 path: ledger entry, probes,
        # then real traffic — and ended HEALTHY in the rotation
        assert fleet.health.state("replica:1") is PeerState.HEALTHY
        assert stats.probes >= 1
        assert stats.routed.get(1, 0) >= 1
        assert 1 in fleet.rotation()
        kinds = [e[0] for e in stats.events]
        assert "grow" in kinds
        assert not fleet._reserve             # spare consumed

    def test_grow_then_drain_onto_the_newcomer(self, fleet_models):
        """The elastic composition (``ci/fast.sh``'s elastic smoke until
        PR 48): a 1-replica fleet grows under queue pressure, the grown
        replica earns admission through probation, THEN replica 0 is
        drained onto it — nothing lost, and at least one live KV-page
        migration priced cheaper than re-prefilling the same pages."""
        from triton_distributed_tpu.serving import AutoscalerConfig

        m0, p0 = fleet_models[0]
        ledger = _fast_ledger()
        fleet = ServingFleet(
            [ServingEngine(m0, p0, EngineConfig(**ECFG),
                           use_pallas=False)],
            seed=3, health=ledger,
            reserve=[_spare_factory(fleet_models)],
            autoscaler=AutoscalerConfig(slo_ms=0.0, window=2,
                                        cooldown=50, max_replicas=2))
        trace = [_req(i, i * 0.5, plen=12, max_new=5)
                 for i in range(18)]
        config.set_fleet_seed(fleet.seed)
        fleet.submit_trace(trace)
        drained = False
        for _ in range(500):
            if fleet.idle:
                break
            if (not drained and fleet.stats.grows
                    and ledger.state("replica:1") is PeerState.HEALTHY
                    and 1 in fleet.rotation()
                    and fleet.replicas[0].held()):
                fleet.drain(0)
                drained = True
            fleet.tick()
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.completed == len(trace)
        assert len(st.grows) == 1
        assert drained and len(st.drains) == 1
        assert st.migrations >= 1
        assert st.migrations_cheaper >= 1, st.migration_priced
        assert fleet.rotation() == (1,)

    def test_grow_without_reserve_refused(self, fleet_models):
        fleet = _fleet(fleet_models)
        with pytest.raises(ValueError, match="reserve"):
            fleet.grow()


class TestDrainMigration:
    def _pinned_trace(self, n_each=2, max_new=8):
        out = []
        for i in range(n_each):
            out.append(_req(i, 0.0, session="a", plen=20,
                            max_new=max_new))
        for i in range(n_each):
            out.append(_req(10 + i, 0.0, session="b", plen=20,
                            max_new=max_new))
        return out

    def _run_drained(self, fleet_models, drain_at=3, drain=1,
                     perf_spec=None, plan=None, death=None):
        fleet = _fleet(fleet_models, "scored")
        fleet.perf_spec = perf_spec
        fleet.router.affinity["a"] = 0
        fleet.router.affinity["b"] = 1
        fleet.submit_trace(self._pinned_trace())
        for t in range(400):
            if fleet.idle:
                break
            if t == drain_at:
                fleet.drain(drain)
            fleet.tick()
        return fleet

    def test_drain_migrates_pages_token_exact(self, fleet_models):
        ref = _fleet(fleet_models, "scored")
        ref.router.affinity["a"] = 0
        ref.router.affinity["b"] = 1
        ref.run(self._pinned_trace())
        assert ref.stats.lost_requests == 0

        fleet = self._run_drained(fleet_models)
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.completed == 4
        # resident rows moved their committed pages over the wire —
        # and every shipped migration priced under the re-prefill
        assert st.migrations >= 1
        assert st.migrated_pages >= 1
        assert st.migration_wire_bytes > 0
        assert st.migrations_cheaper == st.migrations
        assert all(w < r for w, r in st.migration_priced)
        # the drained replica retired cleanly and left the rotation
        assert len(st.drains) == 1
        k, start, done = st.drains[0]
        assert k == 1 and start == 3 and done >= start
        assert fleet.rotation() == (0,)
        assert 1 in fleet._retired
        kinds = [e[0] for e in st.events]
        assert "drain_start" in kinds and "drain_done" in kinds
        assert "migrate" in kinds
        # placement changed, bytes did not
        assert fleet.token_streams() == ref.token_streams()

    def test_pricing_flip_refuses_migration(self, fleet_models):
        """A DCN priced absurdly slow flips migrate_vs_reprefill: the
        drain REFUSES the wire, rows finish in place, and the streams
        stay byte-identical — the degradation is time, never tokens."""
        from triton_distributed_tpu.tune.perf_model import TpuSpec

        slow = TpuSpec(name="torture-dcn", bf16_tflops=200.0,
                       hbm_gbps=800.0, ici_gbps=50.0, ici_links=4,
                       dcn_gbps=1e-12)
        ref = self._run_drained(fleet_models)
        fleet = self._run_drained(fleet_models, perf_spec=slow)
        st = fleet.stats
        assert st.migrations == 0
        assert st.migration_refusals >= 1
        assert st.lost_requests == 0
        assert st.completed == 4
        assert 1 in fleet._retired
        assert fleet.token_streams() == ref.token_streams()

    def test_drain_last_routable_refused(self, fleet_models):
        fleet = _fleet(fleet_models)
        fleet.drain(1)
        with pytest.raises(RuntimeError, match="last routable"):
            fleet.drain(0)
        with pytest.raises(ValueError, match="dead/retired"):
            fleet.drain(7)

    def test_event_log_replays_deterministically(self, fleet_models):
        logs = []
        for _ in range(2):
            fleet = self._run_drained(fleet_models)
            logs.append(list(fleet.stats.events))
        assert logs[0] == logs[1]


# -------------------------------------------------- chaos soak (soak)

class TestChaosSoak:
    """The ISSUE-13 composition pin: a flood past ``queue_cap`` × a
    ReplicaDeath DURING an active drain × a migration-transport Stall,
    all in one run — lost_requests stays 0 and every stream is
    byte-exact against the fault-free fleet. Robustness features must
    compose, not merely pass alone."""

    def _soak_trace(self):
        out = []
        for i in range(3):
            out.append(_req(i, 0.0, session="a", plen=20, max_new=10))
        for i in range(3):
            out.append(_req(10 + i, 0.0, session="b", plen=20,
                            max_new=10))
        # late fillers: they flood the lone survivor after the death
        out += [_req(20 + i, 6.0, plen=10, max_new=4)
                for i in range(6)]
        return out

    def _soak_fleet(self, fleet_models):
        kw = dict(ECFG)
        engines = [ServingEngine(m, p, EngineConfig(**kw),
                                 use_pallas=False)
                   for m, p in fleet_models]
        fleet = ServingFleet(engines, seed=1,
                             router=RouterConfig(queue_cap=2))
        fleet.router.affinity["a"] = 0
        fleet.router.affinity["b"] = 1
        return fleet

    def test_flood_death_mid_drain_migration_stall(self, fleet_models):
        ref = self._soak_fleet(fleet_models)
        ref.run(self._soak_trace())
        assert ref.stats.lost_requests == 0

        fleet = self._soak_fleet(fleet_models)
        plan = FaultPlan(seed=1, faults=(
            ReplicaDeath(replica=0, step=5),
            Stall(site="kv_migrate", rank=0)))
        fleet.submit_trace(self._soak_trace())
        with faults.fault_plan(plan):
            # warm ticks before the watchdog arms: admission + first
            # chunks compile here, so only the STALL can look stalled
            for t in range(2):
                fleet.tick()
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.2):
                    for t in range(2, 400):
                        if fleet.idle:
                            break
                        if t == 3:
                            fleet.drain(0)
                        fleet.tick()
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.completed == 12
        # all three chaos ingredients actually fired
        assert st.admission_rejections > 0          # the cap rejected
        assert st.migrations >= 1                   # stalled, then shipped
        assert st.deaths == [(0, 5)]                # died MID-drain
        death = next(e for e in st.events if e[0] == "death")
        assert "mid-drain" in death[3]
        assert fleet.health.state("site:kv_migrate") \
            is PeerState.UNHEALTHY
        # the interrupted drain never completes; the failover path
        # finished the job instead — with zero lost work
        assert st.drains == []
        assert not fleet._draining
        assert st.failover_requeued >= 1
        assert fleet.token_streams() == ref.token_streams()


# ----------------------------------------- ship-window chaos (ISSUE-19)

class TestShipReservationWindowChaos:
    """The servlint-discovered interleaving as a concrete chaos case:
    ``ReplicaDeath`` landing BETWEEN ``reserve_shipped`` and
    ``commit_shipped`` on a disaggregated replica — the destination
    slot+pages are reserved, the payload is in flight, and the replica
    dies before the commit fence. The reservation must roll back with
    the replica (its pool died with the slice) and every mid-ship
    request must re-route onto the survivor: 0 lost requests, 0 leaked
    pages."""

    def _trace(self, n=4, max_new=6):
        return [_req(i, 0.0, session="s", plen=20, max_new=max_new)
                for i in range(n)]

    def _fleet_with_disagg(self, fleet_models, ship_delay_steps=3):
        (m0, p0), (m1, p1) = fleet_models
        colo = ServingEngine(m0, p0, EngineConfig(**ECFG),
                             use_pallas=False)
        # same model for both roles: transport="xla" needs no hybrid
        # mesh, and the window under test is the host-side reservation
        disagg = DisaggregatedEngine(
            m1, p1, m1, p1, EngineConfig(**ECFG), transport="xla",
            ship_delay_steps=ship_delay_steps, use_pallas=False)
        fleet = ServingFleet([colo, disagg], seed=1,
                             router=RouterConfig(policy="scored"))
        fleet.router.affinity["s"] = 1
        return fleet

    def test_death_in_reservation_window(self, fleet_models):
        ref = self._fleet_with_disagg(fleet_models)
        ref.run(self._trace())
        assert ref.stats.lost_requests == 0
        ref_streams = ref.token_streams()

        fleet = self._fleet_with_disagg(fleet_models)
        fleet.submit_trace(self._trace())
        eng = fleet.replicas[1].engine
        armed = None
        for t in range(400):
            if fleet.idle:
                break
            if armed is None and eng._inflight:
                # reserve_shipped ran (decode slot+pages reserved,
                # req parked) and nothing has committed yet: arm the
                # death so the NEXT tick's death check — which runs
                # before any step could commit — kills the replica
                # inside the reservation window
                assert eng.stats.ships == 0
                armed = [r.req.rid for r in eng._inflight]
                faults.set_fault_plan(FaultPlan(
                    seed=1,
                    faults=(ReplicaDeath(replica=1, step=fleet.ticks),)))
            fleet.tick()
        assert armed, "no ship ever entered the reservation window"
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.completed == 4
        assert [k for k, _ in st.deaths] == [1]
        assert st.failover_requeued >= len(armed)
        # the mid-ship payload never landed: the commit was rolled
        # back with the replica, not half-applied
        assert eng.stats.ships == 0
        # 0 leaked pages on the survivor: at idle every page is either
        # on the free list or parked in the reclaimable prefix cache,
        # and no refcount is live
        for role in fleet.replicas[0]._roles:
            assert int((np.asarray(role.pool.refs) > 0).sum()) == 0
            assert role.pool.available == role.pool.npages
        # placement changed (survivor finished the mid-ship rows),
        # bytes did not
        assert fleet.token_streams() == ref_streams


# ------------------------------------- drain-cancel on death (ISSUE-19)

class TestDrainCancelOnDeath:
    """servlint SV007 counterexample, regression-pinned: replica 0
    draining, replica 1 (the only other routable replica) dies — the
    backlog would wait forever on a fleet whose sole survivor admits no
    routed work. ``_kill`` now cancels the surviving drains (capacity
    loss outranks the drain intent)."""

    def test_death_of_last_routable_cancels_drain(self, fleet_models):
        from triton_distributed_tpu.tune.perf_model import TpuSpec

        # price the migration wire absurdly slow so the drain cannot
        # complete instantly — rows finish in place, holding the drain
        # open across the death tick
        slow = TpuSpec(name="slow-dcn", bf16_tflops=200.0,
                       hbm_gbps=800.0, ici_gbps=50.0, ici_links=4,
                       dcn_gbps=1e-12)

        def _trace():
            out = [_req(i, 0.0, session="a", plen=20, max_new=8)
                   for i in range(2)]
            out += [_req(10 + i, 0.0, session="b", plen=20, max_new=8)
                    for i in range(2)]
            out += [_req(20 + i, 4.0, plen=10, max_new=3)
                    for i in range(3)]
            return out

        ref = _fleet(fleet_models, "scored")
        ref.perf_spec = slow
        ref.router.affinity["a"] = 0
        ref.router.affinity["b"] = 1
        ref.run(_trace())
        assert ref.stats.lost_requests == 0

        fleet = _fleet(fleet_models, "scored")
        fleet.perf_spec = slow
        fleet.router.affinity["a"] = 0
        fleet.router.affinity["b"] = 1
        fleet.submit_trace(_trace())
        plan = FaultPlan(seed=1,
                         faults=(ReplicaDeath(replica=1, step=5),))
        with faults.fault_plan(plan):
            for t in range(400):
                if t == 3:
                    fleet.drain(0)
                if fleet.idle:
                    break
                fleet.tick()
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.completed == 7
        assert st.deaths == [(1, 5)]
        # the drain was CANCELED, not completed: replica 0 is back in
        # rotation serving the backlog, never retired
        cancels = [e for e in st.events if e[0] == "drain_cancel"]
        assert cancels and cancels[0][1] == 0
        assert "death@1" in cancels[0][3]
        assert st.drains == []
        assert not fleet._draining
        assert 0 not in fleet._retired
        assert fleet.rotation() == (0,)
        # the backlog drained through the de-drained survivor with the
        # streams still byte-identical to the fault-free run
        assert fleet.token_streams() == ref.token_streams()


# --------------------------------- ProtocolOps seam pin (ISSUE-19)

class TestProtocolSeamTraceEquality:
    """The ProtocolOps refactor is behavior-preserving: this golden
    fleet event trace was captured BEFORE the serving verbs moved
    behind the seam. Same seed ⇒ byte-identical ``FleetStats.events``;
    the token streams are held to an ORACLE run (the same trace through
    an undisturbed fleet), not to literals — token ids are a function
    of the installed jax's RNG, the protocol is not."""

    GOLDEN_EVENTS = [
        ("drain_start", 0, 6, "requeued=0"),
        ("migrate", 0, 6, "rid=5 pages=2 -> replica 1"),
        ("migrate", 0, 6, "rid=3 pages=3 -> replica 1"),
        ("migrate", 0, 6, "rid=4 pages=3 -> replica 1"),
        ("drain_done", 0, 6, "started@6"),
    ]

    def test_golden_fleet_trace_unchanged(self, fleet_models):
        def _trace():
            return [_req(i, float(i), plen=20, max_new=4)
                    for i in range(8)]

        oracle = _fleet(fleet_models, "scored", seed=3)
        oracle.run(_trace())
        assert oracle.stats.lost_requests == 0
        assert oracle.stats.completed == 8

        fleet = _fleet(fleet_models, "scored", seed=3)
        fleet.submit_trace(_trace())
        for t in range(60):
            if t == 6:
                fleet.drain(0)
            if fleet.idle:
                break
            fleet.tick()
        assert fleet.stats.lost_requests == 0
        assert list(fleet.stats.events) == self.GOLDEN_EVENTS
        streams = fleet.token_streams()
        assert len(streams) == 8
        assert all(len(v) == 4 for v in streams.values())
        assert streams == oracle.token_streams()
