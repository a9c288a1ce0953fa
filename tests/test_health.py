"""ISSUE-10 health-ledger suite: signal aggregation, mesh shrink,
serving failover and probation re-promotion.

The tentpole under test is :mod:`triton_distributed_tpu.runtime.health`
— one state machine fed by every failure signal the stack emits — and
the three action layers it drives:

* **signal aggregation** — fatal vs soft signals, flap damping (strikes
  survive a suspect-clear), deterministic seeded probe schedules (two
  replays of a trace probe at the same steps);
* **mesh shrink** — ``topology.replan_mesh`` maps the job onto the
  surviving n−1 (or surviving-slice) mesh, numerically identical to a
  hand-built mesh over the same devices, and feeds
  ``FaultPlan.unhealthy_peers`` automatically;
* **serving failover** — a :class:`SliceDeath` mid-trace re-queues the
  dead role's requests onto the survivor (exact-cursor re-prefill, the
  eviction recompute discipline), zero lost requests, token-exact; a
  transient kv_ship stall degrades the transport and probation probes
  re-promote it;
* **multi-slice watchdog aggregation** — per-slice trip summaries merge
  into one report naming the wedged slice, itself a ledger signal.

All sim-free: the ledger/topology layers are host code, the engines run
their CPU paths (the XLA twins and the interpreter kernels).
"""

import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.runtime import faults, health, watchdog
from triton_distributed_tpu.runtime.faults import (
    FaultPlan,
    SliceDeath,
    Stall,
)
from triton_distributed_tpu.runtime.health import (
    FATAL_KINDS,
    HealthLedger,
    PeerState,
)
from triton_distributed_tpu.runtime.topology import replan_mesh
from triton_distributed_tpu.runtime.watchdog import (
    TripSummary,
    WatchdogTimeout,
    merge_trip_summaries,
    report_merged_trip,
)
from triton_distributed_tpu.serving import (
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    poisson_trace,
)

#: tier-1 fast subset (ci/fast.sh): the health/failover half of the
#: robustness story
pytestmark = pytest.mark.fast


@pytest.fixture(autouse=True)
def _isolated_ledgers():
    """Ledgers register in a module-level WeakSet that the ops
    preflights consult — drop every ledger this test created so an
    UNHEALTHY verdict cannot leak into another test's preflight."""
    yield
    health.set_ledger(None)
    faults.set_fault_plan(None)
    watchdog.clear_trip()
    gc.collect()


# ----------------------------------------------------------- state machine


class TestLedgerStateMachine:
    def test_soft_signal_walks_through_suspect(self):
        led = HealthLedger(seed=0)
        assert led.state(3) is PeerState.HEALTHY
        assert led.record("transport_error", 3) is PeerState.SUSPECT
        assert led.record("transport_error", 3) is PeerState.UNHEALTHY

    @pytest.mark.parametrize("kind", sorted(FATAL_KINDS))
    def test_fatal_kinds_jump_straight_to_unhealthy(self, kind):
        led = HealthLedger(seed=0)
        assert led.record(kind, 1) is PeerState.UNHEALTHY

    def test_suspect_clears_but_strikes_persist(self):
        """Flap damping: a clean streak clears SUSPECT, but the strike
        count survives — the next failure condemns immediately instead
        of re-entering the suspect/clear livelock."""
        led = HealthLedger(seed=0, suspect_clears=2)
        led.record("transport_error", 5)
        assert led.observe_clean(5) is PeerState.SUSPECT
        assert led.observe_clean(5) is PeerState.HEALTHY
        assert led.record("transport_error", 5) is PeerState.UNHEALTHY

    def test_probation_and_probe_promotion(self):
        led = HealthLedger(seed=0, probation_after=2, promote_after=2,
                           probe_interval=3)
        led.record("watchdog_trip", 2)
        assert led.observe_clean(2) is PeerState.UNHEALTHY
        assert led.observe_clean(2) is PeerState.PROBATION
        # probes fire only in PROBATION, on the seeded schedule
        due = [s for s in range(12) if led.probe_due(2, s)]
        assert due and all(
            (s - due[0]) % 3 == 0 for s in due
        ), due
        assert led.probe_result(2, True) is PeerState.PROBATION
        assert led.probe_result(2, True) is PeerState.HEALTHY
        # promotion forgives strikes: one new soft failure is SUSPECT
        assert led.record("transport_error", 2) is PeerState.SUSPECT

    def test_probe_failure_drops_back_to_unhealthy(self):
        led = HealthLedger(seed=0, probation_after=1)
        led.record("slice_death", "slice:1")
        led.observe_clean("slice:1")
        assert led.state("slice:1") is PeerState.PROBATION
        assert led.probe_result("slice:1", False) is PeerState.UNHEALTHY
        assert not led.probe_due("slice:1", 0)

    def test_clean_observation_on_healthy_peer_is_identity(self):
        led = HealthLedger(seed=0)
        assert led.observe_clean("never-seen") is PeerState.HEALTHY
        assert "never-seen" not in led.peers()

    def test_unhealthy_queries_split_ranks_slices_and_sites(self):
        led = HealthLedger(seed=0)
        led.record("watchdog_trip", 3)
        led.record("watchdog_trip", 1)
        led.record("slice_death", "slice:1")
        led.record("kernel_error", "site:serving_step")
        assert led.unhealthy_peers() == (1, 3)
        assert led.unhealthy_slices() == (1,)
        snap = led.snapshot()
        assert snap["site:serving_step"]["state"] == "unhealthy"
        assert snap["3"]["last"] == "watchdog_trip"

    def test_to_fault_plan_fills_unhealthy_peers(self):
        led = HealthLedger(seed=7)
        led.record("watchdog_trip", 4)
        led.record("kernel_error", "site:serving_step")  # not a rank
        base = FaultPlan(seed=7, faults=(Stall(site="allgather", rank=1),),
                         unhealthy_peers=(2,))
        plan = led.to_fault_plan(base)
        assert plan.unhealthy_peers == (2, 4)
        assert plan.faults == base.faults  # faults preserved


class TestDeterminism:
    SIGNALS = [
        ("transport_error", "site:kv_ship", 1),
        ("watchdog_trip", 3, 4),
        ("transport_error", "site:kv_ship", 6),
        ("slice_death", "slice:1", 9),
    ]

    def _drive(self, led):
        for kind, peer, step in self.SIGNALS:
            led.record(kind, peer, step=step)
        for s in range(10, 16):
            led.observe_clean("site:kv_ship", step=s)

    def test_same_seed_same_story(self):
        """Two ledgers fed the identical signal sequence agree on every
        state, every snapshot field, and every probe step."""
        a, b = HealthLedger(seed=5), HealthLedger(seed=5)
        self._drive(a)
        self._drive(b)
        assert a.snapshot() == b.snapshot()
        sched_a = [s for s in range(40) if a.probe_due("site:kv_ship", s)]
        sched_b = [s for s in range(40) if b.probe_due("site:kv_ship", s)]
        assert sched_a == sched_b and sched_a

    def test_different_seed_different_probe_phase(self):
        """The probe phase is (seed, peer)-keyed: across a handful of
        peers two seeds cannot agree on every phase."""
        a, b = HealthLedger(seed=0), HealthLedger(seed=1)
        phases_a = [a._phase(p) for p in range(8)]
        phases_b = [b._phase(p) for p in range(8)]
        assert phases_a != phases_b

    def test_backoff_jitter_is_seeded(self):
        a, b = HealthLedger(seed=3), HealthLedger(seed=3)
        assert a.uniform("ship_backoff", 4, 1) == b.uniform(
            "ship_backoff", 4, 1)
        assert 0.0 <= a.uniform("x") < 1.0


# ------------------------------------------------------------- mesh shrink


class TestReplanMesh:
    def test_rank_removal_matches_handbuilt_mesh_numerically(self):
        """n−1 shrink: the replanned mesh runs a psum numerically equal
        to the same collective hand-built over the surviving devices —
        and the ledger's verdict rides along as the fault plan."""
        devs = jax.devices()
        assert len(devs) == 8
        mesh = Mesh(np.asarray(devs), ("x",))
        led = HealthLedger(seed=0)
        led.record("watchdog_trip", 3)
        rp = replan_mesh(mesh, led)
        assert rp.removed_ranks == (3,)
        assert rp.survivors == (0, 1, 2, 4, 5, 6, 7)
        assert rp.plan.unhealthy_peers == (3,)
        assert tuple(rp.mesh.devices.ravel()) == tuple(
            d for i, d in enumerate(devs) if i != 3)

        vals = np.arange(8.0, dtype=np.float32)
        surv_vals = vals[list(rp.survivors)]

        def total(x):
            return jax.lax.psum(x, "x")

        from jax.sharding import PartitionSpec as P

        out = jax.jit(jax.shard_map(
            total, mesh=rp.mesh, in_specs=P("x"), out_specs=P("x"),
        ))(jnp.asarray(surv_vals))
        twin = jax.jit(jax.shard_map(
            total, mesh=Mesh(np.asarray([devs[i] for i in rp.survivors]),
                             ("x",)),
            in_specs=P("x"), out_specs=P("x"),
        ))(jnp.asarray(surv_vals))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(twin))
        assert float(np.asarray(out)[0]) == surv_vals.sum()

    def test_slice_removal_on_hybrid_mesh(self):
        devs = jax.devices()
        hybrid = Mesh(np.asarray(devs).reshape(2, 4), ("dcn", "x"))
        led = HealthLedger(seed=0)
        led.record("slice_death", "slice:1")
        rp = replan_mesh(hybrid, led)
        assert rp.removed_slices == (1,)
        assert rp.removed_ranks == (4, 5, 6, 7)
        assert rp.survivors == (0, 1, 2, 3)
        assert rp.mesh.devices.shape == (1, 4)
        assert rp.mesh.axis_names == ("dcn", "x")

    def test_uncovered_rank_on_multiaxis_mesh_refuses(self):
        """A bad rank inside a surviving slice cannot be excised from a
        2-D mesh without leaving it ragged — replan refuses loudly."""
        devs = jax.devices()
        hybrid = Mesh(np.asarray(devs).reshape(2, 4), ("dcn", "x"))
        led = HealthLedger(seed=0)
        led.record("slice_death", "slice:1")
        led.record("watchdog_trip", 2)   # rank 2 lives in slice 0
        with pytest.raises(ValueError, match="containing slice"):
            replan_mesh(hybrid, led)
        led2 = HealthLedger(seed=0)
        led2.record("watchdog_trip", 5)  # rank 5 IS covered by slice 1
        led2.record("slice_death", "slice:1")
        rp = replan_mesh(hybrid, led2)
        assert rp.removed_ranks == (4, 5, 6, 7)

    def test_nothing_survives_refuses(self):
        devs = jax.devices()
        mesh = Mesh(np.asarray(devs[:2]).reshape(2, 1), ("dcn", "x"))
        led = HealthLedger(seed=0)
        led.record("slice_death", "slice:0")
        led.record("slice_death", "slice:1")
        with pytest.raises(ValueError, match="nothing survives"):
            replan_mesh(mesh, led)

    def test_preflight_refuses_on_live_unhealthy_ledger(self):
        """The ops preflight consults every live ledger: an UNHEALTHY
        collective rank anywhere refuses the fused path with a reason
        naming the re-plan escape hatch — no fault plan declared."""
        from triton_distributed_tpu.ops import (
            create_ag_gemm_context,
            preflight,
        )

        devs = jax.devices()
        mesh = Mesh(np.asarray(devs), ("x",))
        ctx = create_ag_gemm_context(mesh, "x")
        a = jnp.ones((64, 32), jnp.float32)
        b = jnp.ones((32, 128), jnp.float32)
        led = HealthLedger(seed=0)
        led.record("watchdog_trip", 2)
        reason = preflight(ctx, "ag_gemm", a, b)
        assert reason is not None and "health ledger" in reason
        assert "replan_mesh" in reason
        del led, reason
        gc.collect()
        assert not any(
            l.unhealthy_peers() for l in health.live_ledgers())


# ----------------------------------------------- multi-slice trip merging


class TestMultiSliceTripAggregation:
    def _summaries(self):
        clean = TripSummary(slice_index=0)
        waiting = TripSummary(
            slice_index=0, site="allgather", collective_id="('ag', 0)",
            n=4, entered=(0, 1, 2, 3), exited=(0, 1, 2, 3), gated=(),
            open_s=2.5,
        )
        wedged = TripSummary(
            slice_index=1, site="allgather", collective_id="('ag', 0)",
            n=4, entered=(0, 1, 2, 3), exited=(0, 1), gated=(2,),
            open_s=2.5,
        )
        return clean, waiting, wedged

    def test_merge_names_the_wedged_slice(self):
        clean, waiting, wedged = self._summaries()
        report, bad = merge_trip_summaries([clean, wedged])
        assert bad == (1,)
        assert "wedged slice [1]" in report and "slice 0: clean" in report

    def test_waiting_slice_is_not_wedged(self):
        """A slice whose ranks all exited (it tripped merely waiting on
        the wedged peer) is exonerated by the merge."""
        _, waiting, wedged = self._summaries()
        report, bad = merge_trip_summaries([waiting, wedged])
        assert bad == (1,)
        assert not waiting.wedged and wedged.wedged

    def test_report_merged_trip_feeds_the_ledger(self):
        led = HealthLedger(seed=0)
        clean, _, wedged = self._summaries()
        report = report_merged_trip([clean, wedged])
        assert "wedged slice [1]" in report
        assert led.unhealthy_slices() == (1,)
        assert led.state("slice:1") is PeerState.UNHEALTHY

    def test_summary_json_round_trip(self):
        _, _, wedged = self._summaries()
        back = TripSummary.from_json(wedged.to_json())
        assert back == wedged

    def test_exchange_is_identity_single_process(self):
        from triton_distributed_tpu.runtime.multislice import (
            exchange_trip_summaries,
        )

        _, _, wedged = self._summaries()
        assert exchange_trip_summaries(wedged) == [wedged]

    def test_host_instrument_trip_lands_in_ledger(self):
        """Satellite pin: a stalled kv_ship under an armed watchdog
        trips, and the trip report — parsed by every live ledger —
        condemns the ship site (n=1 host instrument: the site, not a
        mesh rank)."""
        from triton_distributed_tpu.lang.launch import maybe_instrument

        led = HealthLedger(seed=0)
        plan = FaultPlan(seed=0, faults=(Stall(site="kv_ship", rank=0),))
        with faults.fault_plan(plan):
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.2):
                    fn = maybe_instrument(
                        lambda: 1, axis=None, site="kv_ship",
                        collective_id=("kv_ship", 0), n=1,
                    )
                    assert fn() == 1   # stall released by the trip
        assert led.state("site:kv_ship") is PeerState.UNHEALTHY
        assert led.unhealthy_peers() == ()   # host rank 0 is not a peer


# -------------------------------------------------------- serving engines

CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32, kv_quant="int8",
)


@pytest.fixture(scope="module")
def roles1():
    devs = jax.devices()
    return (Mesh(np.asarray(devs[:1]), ("tp",)),
            Mesh(np.asarray(devs[1:2]), ("tp",)),
            Mesh(np.asarray(devs[:2]).reshape(2, 1), ("dcn", "tp")))


@pytest.fixture(scope="module")
def models1(roles1):
    mesh_p, mesh_d, _ = roles1
    mp = Transformer(TransformerConfig(**CFG), mesh_p, "tp", ())
    md = Transformer(TransformerConfig(**CFG), mesh_d, "tp", ())
    params = mp.init(jax.random.PRNGKey(0))
    pp = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                      mp.shardings())
    pd = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                      md.shardings())
    return mp, pp, md, pd


def _fast_ledger(seed=0):
    """Tight thresholds so probation/promotion fit a short trace."""
    return HealthLedger(seed=seed, probation_after=1, promote_after=1,
                        probe_interval=2)


class TestKernelProbation:
    def test_single_failure_degrades_then_probe_repromotes(
            self, models1, monkeypatch):
        """One injected Pallas failure is FATAL (kernel_error): the
        engine rides the XLA twin, earns probation with clean steps,
        and a seeded probe re-promotes it to the fused path — tokens
        identical to an untouched run throughout."""
        import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

        mp, pp, *_ = models1
        real = rpa.ragged_paged_attention
        calls = {"n": 0}

        def flaky(*a, **k):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected kernel failure")
            return real(*a, **k)

        monkeypatch.setattr(rpa, "ragged_paged_attention", flaky)
        eng = ServingEngine(
            mp, pp,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
            health=_fast_ledger(),
        )
        req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                      max_new=12, arrival=0.0)
        stats = eng.run([req], max_steps=80)
        assert calls["n"] >= 2
        assert stats.repromotions >= 1
        assert eng.use_pallas and not stats.degraded
        assert eng.health.state(eng.health_peer) is PeerState.HEALTHY
        # token-exact across degrade + re-promotion
        ref = ServingEngine(
            mp, pp,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        )
        ref_req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                          max_new=12, arrival=0.0)
        ref.run([ref_req], max_steps=80)
        assert req.generated == ref_req.generated

    def test_always_failing_kernel_stays_demoted(self, models1,
                                                 monkeypatch):
        """Probes against a still-broken kernel FAIL back to UNHEALTHY:
        the engine never flaps onto a path that keeps breaking."""
        import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

        mp, pp, *_ = models1
        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            raise RuntimeError("still broken")

        monkeypatch.setattr(rpa, "ragged_paged_attention", boom)
        # shapes distinct from the re-promotion test above (3 slots, and
        # a packed width of 32 against its 24): the model's step jit is
        # cached per (width, block) and a cache hit would replay the
        # REAL kernel captured at an earlier trace
        eng = ServingEngine(
            mp, pp,
            EngineConfig(slots=3, token_budget=24, chunk=6, page=8,
                         npages=16),
            health=_fast_ledger(),
        )
        req = Request(rid=0, prompt=np.arange(11, dtype=np.int32),
                      max_new=8, arrival=0.0)
        stats = eng.run([req], max_steps=60)
        assert stats.degraded and not eng.use_pallas
        assert stats.repromotions == 0
        assert calls["n"] >= 2   # the probe retried the broken path
        assert all(r.done for r in [req])


class TestTransportRetries:
    def test_transient_dcn_failures_absorbed_by_retries(
            self, models1, roles1, monkeypatch):
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        monkeypatch.setenv("TDTPU_SHIP_RETRIES", "3")
        monkeypatch.setenv("TDTPU_SHIP_BACKOFF", "0.001")
        eng = DisaggregatedEngine(
            mp, pp, md, pd,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ship_delay_steps=1, health=_fast_ledger(),
        )
        calls = {"n": 0}

        def flaky(qpay, spay):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("transient wire failure")
            return "landed"

        eng._transport_dcn = flaky
        assert eng._dcn_with_retries(None, None) == "landed"
        assert eng.stats.ship_retries == 2
        assert not eng.stats.degraded_transport

    def test_exhausted_retries_return_none(self, models1, roles1,
                                           monkeypatch):
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        monkeypatch.setenv("TDTPU_SHIP_RETRIES", "2")
        monkeypatch.setenv("TDTPU_SHIP_BACKOFF", "0.001")
        eng = DisaggregatedEngine(
            mp, pp, md, pd,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ship_delay_steps=1, health=_fast_ledger(),
        )

        def broken(qpay, spay):
            raise RuntimeError("wire down")

        eng._transport_dcn = broken
        assert eng._dcn_with_retries(None, None) is None
        assert eng.stats.ship_retries == 1   # attempts - 1


class TestServingFailover:
    ECFG = dict(slots=4, token_budget=48, chunk=16, page=8, npages=32)
    TRACE = dict(seed=9, n_requests=6, mean_interarrival=0.7,
                 len_lo=8, len_hi=30, max_new_lo=3, max_new_hi=6,
                 vocab=128)

    def _reference(self, models1):
        mp, pp, *_ = models1
        trace = poisson_trace(**self.TRACE)
        ServingEngine(mp, pp, EngineConfig(**self.ECFG)).run(
            trace, max_steps=500)
        return trace

    def _engine(self, models1, roles1, **kw):
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        return DisaggregatedEngine(
            mp, pp, md, pd, EngineConfig(**self.ECFG),
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ship_delay_steps=2, health=_fast_ledger(), **kw,
        )

    @pytest.mark.parametrize("dead_slice,role", [(1, "decode"),
                                                 (0, "prefill")])
    def test_slice_death_failover_token_exact(self, models1, roles1,
                                              dead_slice, role):
        """The acceptance pin: a role slice dies mid-trace; the
        survivor finishes the full Poisson trace — zero lost requests,
        token streams equal the fault-free colocated engine's."""
        ref = self._reference(models1)
        trace = poisson_trace(**self.TRACE)
        eng = self._engine(models1, roles1)
        plan = FaultPlan(
            seed=1, faults=(SliceDeath(slice=dead_slice, step=5),))
        with faults.fault_plan(plan):
            stats = eng.run(trace, max_ticks=800)
        assert stats.completed == self.TRACE["n_requests"]
        assert all(r.done for r in trace)
        fo = stats.failover
        assert fo is not None and fo["role"] == role
        assert fo["tick"] == 5 and fo["recovery_tick"] is not None
        assert eng.health.state(f"slice:{dead_slice}") \
            is PeerState.UNHEALTHY
        for a, b in zip(ref, trace):
            assert a.generated == b.generated, a.rid

    def test_decode_death_preserves_inflight_kv(self, models1, roles1):
        """Requests parked for (or inside) a ship when the decode slice
        dies keep their prefilled KV — it lives in the SURVIVOR's pool —
        so they resume decoding in place instead of re-prefilling."""
        trace = poisson_trace(**self.TRACE)
        eng = self._engine(models1, roles1)
        seen_inflight = {}

        real_check = eng._check_slice_deaths

        def spy():
            if eng._dead_role is None:
                seen_inflight["at_death"] = (
                    len(eng._inflight) + len(eng._ready))
            real_check()

        eng._check_slice_deaths = spy
        plan = FaultPlan(seed=1, faults=(SliceDeath(slice=1, step=4),))
        with faults.fault_plan(plan):
            stats = eng.run(trace, max_ticks=800)
        assert stats.completed == self.TRACE["n_requests"]
        # requeued counts only the re-prefill cohort; anything in a
        # ship at death decodes in place on the survivor
        assert stats.failover["requeued"] <= self.TRACE["n_requests"]
        assert stats.failover["re_prefill_tokens"] >= 0

    def test_transient_ship_stall_degrades_then_repromotes(
            self, models1, roles1):
        """Satellite 2+3 pin: a persistent kv_ship stall gate under an
        armed watchdog trips on the FIRST ship (releasing it), the
        transport degrades onto the XLA twin, and — the trip being
        stale for the rest of the arming — a probation probe re-promotes
        the DCN wire. Zero lost requests, final state un-degraded."""
        # every step and ship program this trace launches is compiled
        # BEFORE the 0.3 s deadline is armed: a first-use compile inside
        # it trips the watchdog at site serving_step, not at the stall
        self._engine(models1, roles1).run(
            poisson_trace(**self.TRACE), max_ticks=800)
        trace = poisson_trace(**self.TRACE)
        eng = self._engine(models1, roles1)
        plan = FaultPlan(seed=1, faults=(Stall(site="kv_ship", rank=0),))
        box = {}
        with faults.fault_plan(plan):
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.3):
                    box["stats"] = eng.run(trace, max_ticks=800)
        stats = box["stats"]
        assert stats.completed == self.TRACE["n_requests"]
        assert stats.transport_repromotions >= 1
        assert eng.transport == "dcn"
        assert not stats.degraded_transport
        assert eng.health.state("site:kv_ship") is PeerState.HEALTHY

    def test_both_slices_dead_refuses(self, models1, roles1):
        eng = self._engine(models1, roles1)
        trace = poisson_trace(**self.TRACE)
        plan = FaultPlan(seed=1, faults=(SliceDeath(slice=0, step=2),
                                         SliceDeath(slice=1, step=2)))
        with faults.fault_plan(plan):
            with pytest.raises(RuntimeError, match="no survivor"):
                eng.run(trace, max_ticks=800)

    def test_placement_refuses_condemned_slice(self, models1):
        """The perf-model placement gate consults the ledger: a split
        topology cannot place a role on a condemned slice."""
        from triton_distributed_tpu.tune.perf_model import (
            refuse_disaggregation,
        )

        mp, *_ = models1
        led = HealthLedger(seed=0)
        led.record("slice_death", "slice:1")
        reason = refuse_disaggregation(
            mp.config, 8, {"prompt_len": 64, "max_new": 8}, None,
            ledger=led,
        )
        assert reason is not None and "condemned slice" in reason


# ----------------------------------------------------------------- lint


class TestDegradationDeclarations:
    def test_every_family_declares_a_resolvable_target(self):
        """bench --lint's gate, asserted directly: every registered
        kernel family names a degradation target and every target
        resolves to a real callable."""
        from triton_distributed_tpu.kernels.registry import (
            families,
            missing_degradation_targets,
        )

        fams = families().values()
        assert fams and all(f.degrades_to for f in fams)
        assert missing_degradation_targets() == ()
