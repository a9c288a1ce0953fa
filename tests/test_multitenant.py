"""ISSUE-16 multi-tenant fleet suite: priority preemption,
deadline-aware routing, and brownout load-shedding.

The tentpole under test: every :class:`Request` carries a ``tenant`` +
priority tier (interactive / batch / background), and the stack
enforces it end to end —

* **deadline routing** — the router score gains a slack term
  (``slo_ms − modeled completion``); negative slack outranks prefix
  affinity, and retry-after prices by the request's OWN tier (only
  queued work at rank ≤ r is ahead of a tier-r retry);
* **priority preemption** — a higher-tier admission with no slot/page
  headroom evicts the lowest-tier resident through the recompute-
  eviction discipline: token-exact, cursor-resumable, zero pool-page
  leaks even mid-draft, with anti-starvation aging protecting both
  admission order AND residency;
* **brownout** — the fleet overload controller escalates through
  ``BROWNOUT_LEVELS`` in strict reverse-priority order (background
  shed first, batch squeezed then shed, interactive never) with
  hysteretic recovery;
* **fair share** — per-tenant page/token shares gate admission without
  head-of-line blocking, and the per-tenant stats surface
  goodput/p99/preemptions/sheds;
* **replay determinism** — tenant floods × ReplicaDeath × preemption
  produce byte-identical ``stats.events`` under the same seed (the
  PR-13 contract extended to preempt/shed/brownout events).

All sim-free: host-side scheduling over the engines' CPU (XLA) paths.
"""

import gc
from contextlib import nullcontext

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from triton_distributed_tpu import config
from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.runtime import faults, health, watchdog
from triton_distributed_tpu.runtime.faults import FaultPlan, ReplicaDeath
from triton_distributed_tpu.runtime.health import PeerState
from triton_distributed_tpu.serving import (
    TIERS,
    BrownoutConfig,
    BrownoutController,
    EngineConfig,
    Request,
    ServingEngine,
    ServingFleet,
    SpeculativeEngine,
    TenantConfig,
    effective_rank,
    tier_rank,
)
from triton_distributed_tpu.serving.fleet import (
    BROWNOUT_LEVELS,
    FleetRouter,
    RouterConfig,
)

#: tier-1 fast subset (ci/fast.sh): the multi-tenant robustness story
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
    dtype=jnp.float32, param_dtype=jnp.float32,
)

ECFG = dict(slots=4, token_budget=48, chunk=16, page=8, npages=32,
            prefix_cache=True, temperature=0.7, top_k=40, seed=11)

TEN = {
    "iact": TenantConfig(priority="interactive", slo_ms=0.05),
    "bat": TenantConfig(priority="batch"),
    "bg": TenantConfig(priority="background"),
}


@pytest.fixture(scope="module")
def fleet_models():
    """Two replica models on their own 1-device meshes, same params."""
    devs = jax.devices()
    out = []
    params = None
    for k in range(2):
        mesh = Mesh(np.asarray(devs[k:k + 1] or devs[:1]), ("tp",))
        model = Transformer(TransformerConfig(**CFG), mesh, "tp", ())
        if params is None:
            params = model.init(jax.random.PRNGKey(0))
        p = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                         model.shardings())
        out.append((model, p))
    return out


def _req(rid, arrival, tenant=None, priority=None, session=None,
         plen=20, max_new=5):
    rng = np.random.default_rng(1000 + rid)
    prompt = rng.integers(0, CFG["vocab"], (plen,)).astype(np.int32)
    r = Request(rid=rid, prompt=prompt, max_new=max_new,
                arrival=arrival)
    if tenant is not None:
        r.tenant = tenant
    if priority is not None:
        r.priority = priority
    if session is not None:
        r.session = session
    return r


def _engine(fleet_models, cls=ServingEngine, tenants=None, k=0,
            **kw):
    m, p = fleet_models[k]
    ecfg = {key: kw.pop(key, val) for key, val in ECFG.items()}
    kw.setdefault("use_pallas", False)
    return cls(m, p, EngineConfig(**ecfg), tenants=tenants, **kw)


def _fleet(fleet_models, tenants=None, brownout=None, queue_cap=None,
           seed=1, **kw):
    engines = [ServingEngine(m, p, EngineConfig(**ECFG),
                             use_pallas=False)
               for m, p in fleet_models]
    return ServingFleet(engines, seed=seed,
                        router=RouterConfig(queue_cap=queue_cap),
                        tenants=tenants, brownout=brownout, **kw)


def _mixed_trace(n_iact=4, n_bat=16, n_bg=4):
    out, rid = [], 0
    for i in range(n_iact):
        out.append(_req(rid, i * 3.0, "iact")); rid += 1
    for i in range(n_bat):
        out.append(_req(rid, 1.0 + i * 0.2, "bat")); rid += 1
    for i in range(n_bg):
        out.append(_req(rid, i * 1.5, "bg")); rid += 1
    return out


def _assert_no_leaks(owner):
    """Zero held pages once every stream completed — on a fleet, over
    the ALIVE replicas (a dead replica's pool is abandoned wholesale
    with its requeued requests, not unwound)."""
    if hasattr(owner, "replicas"):
        roles = [role for r in owner._alive() for role in r._roles]
    else:
        roles = (owner,)
    for role in roles:
        assert role.pool.held_pages == 0, (
            f"page leak: {role.pool.held_pages} pages still held")


# ------------------------------------------------------------- tiers

class TestTenantConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="unknown priority"):
            TenantConfig(priority="platinum")
        with pytest.raises(ValueError, match="page_share"):
            TenantConfig(page_share=0.0)
        with pytest.raises(ValueError, match="page_share"):
            TenantConfig(page_share=1.5)
        with pytest.raises(ValueError, match="token_budget"):
            TenantConfig(token_budget=4)

    def test_tier_rank_order(self):
        assert [tier_rank(t) for t in TIERS] == [0, 1, 2]
        # unknown/unset ranks interactive: the single-tenant default
        # must schedule exactly like the pre-tenancy engine
        assert tier_rank(None) == 0
        assert tier_rank("whatever") == 0

    def test_effective_rank_ages_toward_zero(self):
        r = _req(0, arrival=10.0, priority="background")
        assert effective_rank(r, now=10.0, aging_ticks=4) == 2
        assert effective_rank(r, now=14.0, aging_ticks=4) == 1
        assert effective_rank(r, now=18.0, aging_ticks=4) == 0
        assert effective_rank(r, now=99.0, aging_ticks=4) == 0  # floor
        # aging disabled: the static rank, forever
        assert effective_rank(r, now=99.0, aging_ticks=0) == 2


# -------------------------------------------------- deadline routing

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


class TestDeadlineRouting:
    def test_score_negative_slack_divides_by_deficit(self):
        router = FleetRouter(seed=0)
        r = _StubReplica(0, overlap=4, load=2.0)
        base = router.score(r, None, PeerState.HEALTHY, 2.0)
        # positive slack: no penalty
        assert router.score(r, None, PeerState.HEALTHY, 2.0,
                            slack=3.0) == pytest.approx(base)
        # negative slack: / (1 + w_slack * deficit/mean)
        assert router.score(r, None, PeerState.HEALTHY, 2.0,
                            slack=-4.0) \
            == pytest.approx(base / (1.0 + 4.0 / 2.0))

    def test_slack_ms_none_without_finite_slo(self, fleet_models):
        fleet = _fleet(fleet_models, tenants=dict(TEN))
        rep = fleet.replicas[0]
        # no tenant entry / infinite SLO -> no deadline term
        assert fleet.router.slack_ms(rep, _req(0, 0.0)) is None
        assert fleet.router.slack_ms(rep, _req(0, 0.0, "bat")) is None
        s = fleet.router.slack_ms(rep, _req(0, 0.0, "iact"))
        assert s is not None and s < TEN["iact"].slo_ms

    def test_negative_slack_outranks_prefix_affinity(self):
        """The full home holds the prefix, but queueing there is
        modeled to miss the SLO while the other replica still makes
        it: the deadline wins and the request spills."""
        router = FleetRouter(seed=0)
        router.tenants = {"t": TenantConfig(slo_ms=1.0)}
        home = _StubReplica(0, overlap=10, load=1.0, room=False)
        other = _StubReplica(1, overlap=0, load=1.0, room=True)
        router.slack_ms = lambda r, req: (
            -5.0 if r.index == 0 else 2.0)
        router.affinity["s"] = 0
        req = _req(0, 0.0, tenant="t", session="s")
        chosen, spilled = router.route(req, [home, other],
                                       _StubLedger())
        assert chosen is other and spilled
        assert router.affinity["s"] == 1

    def test_positive_slack_keeps_prefix_affinity(self):
        router = FleetRouter(seed=0)
        router.tenants = {"t": TenantConfig(slo_ms=1.0)}
        home = _StubReplica(0, overlap=10, load=1.0, room=False)
        other = _StubReplica(1, overlap=0, load=1.0, room=True)
        router.slack_ms = lambda r, req: 2.0
        router.affinity["s"] = 0
        req = _req(0, 0.0, tenant="t", session="s")
        chosen, spilled = router.route(req, [home, other],
                                       _StubLedger())
        assert chosen is home and not spilled


# ---------------------------------------------- tier-priced retry

class TestTierRetryPricing:
    def _loaded_fleet(self, fleet_models, n_queued=6):
        fleet = _fleet(fleet_models, tenants=dict(TEN), queue_cap=2)
        for k, rep in enumerate(fleet.replicas):
            for i in range(n_queued):
                rep.admit_role.waiting.append(
                    _req(100 * (k + 1) + i, 0.0, "bat"))
        return fleet

    def test_retry_prices_by_own_tier(self, fleet_models):
        """A batch queue ahead is invisible to an interactive retry:
        tier-r admission sorts ahead of every lower tier, so the
        interactive price counts zero queued-ahead while the batch
        price pays the whole flood."""
        fleet = self._loaded_fleet(fleet_models)
        routable = fleet._routable()
        iact_ms, _ = fleet._priced_retry(_req(0, 0.0, "iact"),
                                         routable)
        bat_ms, _ = fleet._priced_retry(_req(1, 0.0, "bat"), routable)
        bg_ms, _ = fleet._priced_retry(_req(2, 0.0, "bg"), routable)
        assert iact_ms < bat_ms
        assert bat_ms == pytest.approx(bg_ms)  # nothing queued below batch

    def test_retry_prices_off_lightest_routable_not_probation(
            self, fleet_models):
        """The PROBATION replica's empty queue is the lightest — but
        it is unroutable (it only takes seeded probes), so the
        retry-after MUST price off the loaded HEALTHY replica: a
        retry-after the fleet cannot honor is worse than a long one."""
        fleet = _fleet(fleet_models, tenants=dict(TEN), queue_cap=2)
        fleet.health = _StubLedger({"replica:0": PeerState.PROBATION})
        # replica 0: PROBATION, empty queue. replica 1: HEALTHY, at cap
        for i in range(4):
            fleet.replicas[1].admit_role.waiting.append(
                _req(100 + i, 0.0, "bat"))
        routable = fleet._routable()
        assert [r.index for r in routable] == [1]
        probe = _req(0, 0.0, "bat")
        want_ms, _ = fleet._priced_retry(probe, [fleet.replicas[1]])
        assert fleet._reject_overload(probe)
        assert fleet.stats.admission_rejections == 1
        assert fleet.stats.retry_after_ms[-1] == pytest.approx(want_ms)
        # the un-routable empty replica would have priced ~a bare step:
        # strictly below what the real routable queue costs
        bare_ms, _ = fleet._priced_retry(_req(9, 0.0, "bat"),
                                         [fleet.replicas[0]])
        assert want_ms > bare_ms

    def test_single_tenant_pricing_unchanged(self, fleet_models):
        """With no tenants map every request is rank 0 and the tier
        filter passes the whole queue: the price equals the pre-tier
        ``replica_load_ms`` of the lightest routable replica."""
        fleet = _fleet(fleet_models, queue_cap=2)
        for i in range(3):
            fleet.replicas[0].admit_role.waiting.append(
                _req(100 + i, 0.0))
        light = min(fleet._routable(),
                    key=lambda r: (r.queue_depth(), r.load_ms(),
                                   r.index))
        ms, _ = fleet._priced_retry(_req(0, 0.0), fleet._routable())
        assert ms == pytest.approx(light.load_ms())


# ------------------------------------------------------ preemption

class TestPreemption:
    def _solo_streams(self, fleet_models, trace_fn):
        eng = _engine(fleet_models)
        t = trace_fn()
        eng.run(t, max_steps=800)
        return {r.rid: list(r.generated) for r in t}

    def test_interactive_preempts_lowest_tier(self, fleet_models):
        eng = _engine(fleet_models, tenants=dict(TEN))
        bgs = [_req(i, 0.0, "bg", max_new=8) for i in range(4)]
        for r in bgs:
            eng.submit(r)
        for _ in range(2):
            eng.step()
        assert all(r.slot is not None for r in bgs)
        hi = _req(10, 2.0, "iact", max_new=4)
        eng.submit(hi)
        eng.step()
        assert eng.stats.preemptions == 1
        assert eng.stats.tenant_preemptions == {"bg": 1}
        assert hi.slot is not None
        victim = next(r for r in bgs if r.slot is None and not r.done)
        assert victim.cursor == 0 and victim.evictions == 1
        # run out: everyone completes, no pages leak
        for _ in range(200):
            if eng.idle:
                break
            eng.step()
        assert all(r.done for r in bgs + [hi])
        _assert_no_leaks(eng)

    def test_equal_rank_victim_is_fewest_committed_pages(
            self, fleet_models):
        """ISSUE-17 fleet satellite (ROADMAP #2 follow-on): preemption-
        aware victim COST. At equal effective rank the resident with
        the FEWEST committed pages is evicted — eviction is recompute-
        priced, so the cheapest re-prefill goes first. The short-prompt
        row sits in slot 0 on purpose: the pre-cost tie-break (latest
        arrival, then highest slot) would have picked a long row and
        thrown away 3x the materialized KV."""
        eng = _engine(fleet_models, tenants=dict(TEN))
        short = _req(0, 0.0, "bg", plen=8, max_new=8)
        longs = [_req(i, 0.0, "bg", plen=24, max_new=8)
                 for i in range(1, 4)]
        for r in [short] + longs:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        assert all(r.slot is not None for r in [short] + longs)
        pages = {r.rid: int((eng.table[r.slot] >= 0).sum())
                 for r in [short] + longs}
        assert pages[0] == min(pages.values())
        assert pages[0] < min(pages[r.rid] for r in longs)
        hi = _req(10, 2.0, "iact", max_new=4)
        eng.submit(hi)
        eng.step()
        assert eng.stats.preemptions == 1
        assert short.slot is None and short.cursor == 0
        assert all(r.slot is not None for r in longs)
        for _ in range(300):
            if eng.idle:
                break
            eng.step()
        assert all(r.done for r in [short, hi] + longs)
        _assert_no_leaks(eng)

    def test_preemption_token_exact(self, fleet_models):
        """Preempted streams are byte-identical to an unpreempted
        single-tenant run: sampling is keyed (seed, rid, n_generated),
        so the recompute-eviction resume cannot perturb a token."""
        def trace():
            out = [_req(i, 0.0, max_new=8) for i in range(4)]
            out.append(_req(10, 2.0, max_new=4))
            return out

        want = self._solo_streams(fleet_models, trace)
        eng = _engine(fleet_models, tenants=dict(TEN))
        t = [_req(i, 0.0, "bg", max_new=8) for i in range(4)]
        t.append(_req(10, 2.0, "iact", max_new=4))
        eng.run(t, max_steps=800)
        assert eng.stats.preemptions >= 1
        assert {r.rid: list(r.generated) for r in t} == want
        _assert_no_leaks(eng)

    def test_single_tenant_never_preempts(self, fleet_models):
        eng = _engine(fleet_models)
        t = [_req(i, 0.0, max_new=8) for i in range(4)]
        t.append(_req(10, 2.0, max_new=4))
        eng.run(t, max_steps=800)
        assert eng.stats.preemptions == 0

    def test_preempt_mid_draft_rolls_back_pages(self, fleet_models):
        """SpeculativeEngine: preemption lands while drafts are in
        flight — the victim's speculative pages roll back with the
        eviction, streams stay byte-identical to the PLAIN engine's
        (the rejection-sampling identity survives preemption), and the
        pool ends with zero held pages."""
        def trace():
            out = [_req(i, 0.0, max_new=8) for i in range(4)]
            out.append(_req(10, 3.0, max_new=4))
            return out

        want = self._solo_streams(fleet_models, trace)
        eng = _engine(fleet_models, cls=SpeculativeEngine,
                      tenants=dict(TEN), spec_k=4)
        t = [_req(i, 0.0, "bg", max_new=8) for i in range(4)]
        t.append(_req(10, 3.0, "iact", max_new=4))
        eng.run(t, max_steps=800)
        assert eng.stats.preemptions >= 1
        assert eng.stats.spec_rows > 0
        assert {r.rid: list(r.generated) for r in t} == want
        _assert_no_leaks(eng)

    def test_aging_prevents_background_starvation(self, fleet_models):
        """Sustained interactive flood vs one background request on a
        tiny engine. Without aging the background row is preempted or
        outsorted forever; with aging its effective rank reaches 0,
        where it can neither be outsorted NOR preempted — it completes
        while the flood is still arriving."""
        def run(aging_ticks):
            eng = _engine(fleet_models, tenants=dict(TEN), slots=2,
                          aging_ticks=aging_ticks)
            bg = _req(999, 0.0, "bg", max_new=4)
            eng.submit(bg)
            flood = [_req(i, i * 0.5, "iact", max_new=3)
                     for i in range(40)]
            for r in flood:
                eng.submit(r)
            done_at = None
            for s in range(120):
                eng.step()
                if bg.done and done_at is None:
                    done_at = s
            return bg, done_at, flood

        bg, done_at, flood = run(aging_ticks=4)
        last_arrival = max(r.arrival for r in flood)
        assert bg.done and done_at is not None
        assert done_at < last_arrival, (
            f"aged background finished at step {done_at}, after the "
            f"flood ended ({last_arrival}) — aging did not help")
        bg0, done0, _ = run(aging_ticks=0)
        assert done0 is None or done0 > done_at, (
            "disabling aging should starve the background request "
            "for longer")


# ------------------------------------------------------ fair share

class TestFairShare:
    def test_page_share_defers_without_blocking(self, fleet_models):
        tenants = {
            "bat": TenantConfig(priority="batch", page_share=0.25),
            "iact": TenantConfig(priority="interactive"),
        }
        eng = _engine(fleet_models, tenants=tenants)
        # two early batch residents fill the tenant's 8-page share
        # (24+12 tokens -> 4 pages each); the late pair must defer
        # until the early pair completes, while the late interactive
        # request sails through the free slots untouched
        t = [_req(i, 0.0, "bat", plen=24, max_new=12)
             for i in range(2)]
        t += [_req(2 + i, 4.0, "bat", plen=24, max_new=4)
              for i in range(2)]
        t.append(_req(10, 4.0, "iact", plen=24, max_new=4))
        eng.run(t, max_steps=800)
        assert eng.stats.fair_share_deferrals.get("bat", 0) > 0
        # deferred, not starved or lost — and no head-of-line block
        assert all(r.done for r in t)
        _assert_no_leaks(eng)

    def test_token_budget_caps_packed_rows(self, fleet_models):
        tenants = {"bat": TenantConfig(priority="batch",
                                       token_budget=16)}
        eng = _engine(fleet_models, tenants=tenants)
        t = [_req(i, 0.0, "bat", plen=24, max_new=4)
             for i in range(3)]
        eng.run(t, max_steps=800)
        assert eng.stats.fair_share_deferrals.get("bat", 0) > 0
        assert all(r.done for r in t)
        _assert_no_leaks(eng)


# -------------------------------------------------------- brownout

class TestBrownout:
    def test_level_ladder_sheds_reverse_priority(self):
        c = BrownoutController(BrownoutConfig(slo_ms=1.0))
        for level, (bg, bat) in enumerate(
                [(False, False), (True, False), (True, False),
                 (True, True)]):
            c.level = level
            assert c.sheds(tier_rank("background")) is bg
            assert c.sheds(tier_rank("batch")) is bat
            assert c.sheds(tier_rank("interactive")) is False
        c.level = 2
        assert c.squeezed == frozenset({"batch"})
        c.level = 1
        assert c.squeezed == frozenset()

    def test_hysteresis_window_and_cooldown(self, fleet_models):
        fleet = _fleet(fleet_models, tenants=dict(TEN),
                       brownout=BrownoutConfig(slo_ms=1.0, window=2,
                                               cooldown=3))
        c = fleet.brownout
        script = iter([True, True,            # escalate after 2
                       True,                  # 1 pressured (no move)
                       False, False, False,   # de-escalate after 3
                       False])
        c.pressure = lambda _fleet: next(script)
        c.observe(fleet)
        assert c.level == 0
        c.observe(fleet)
        assert c.level == 1                   # window hit
        c.observe(fleet)
        assert c.level == 1                   # needs window again
        for _ in range(3):
            c.observe(fleet)
        assert c.level == 0                   # cooldown hit
        trans = [e for e in fleet.stats.events if e[0] == "brownout"]
        assert [e[3] for e in trans] == [
            "normal->shed_background", "shed_background->normal"]

    def test_flood_sheds_in_strict_order_and_recovers(
            self, fleet_models):
        """A batch+background flood under a tight modeled SLO: the
        controller escalates, sheds land ONLY on background/batch with
        every background shed preceding the first batch shed, the
        squeeze clears on recovery, and zero requests are lost."""
        fleet = _fleet(fleet_models, tenants=dict(TEN), queue_cap=3,
                       brownout=BrownoutConfig(slo_ms=0.004, window=2,
                                               cooldown=3))
        st = fleet.run(_mixed_trace(n_bat=24, n_bg=6), max_ticks=800)
        assert st.lost_requests == 0
        shed_events = [e for e in st.events if e[0] == "shed"]
        assert shed_events, "flood never tripped the brownout"
        tiers = [e[3].split("tier=")[1].split()[0]
                 for e in shed_events]
        assert set(tiers) <= {"background", "batch"}
        assert "interactive" not in st.sheds
        if "batch" in tiers:
            assert "background" in tiers[:tiers.index("batch")]
        # recovered: back to normal, squeeze lifted everywhere
        assert fleet.brownout.level == 0
        for r in fleet._alive():
            for role in r._roles:
                assert role.throttled_tiers == frozenset()
        _assert_no_leaks(fleet)

    @pytest.mark.parametrize("death", [False, True],
                             ids=["no_fault", "replica_death"])
    def test_interactive_p99_protected_under_flood(self, fleet_models,
                                                   death):
        """The acceptance pin in miniature: interactive p99 TTFT under
        a batch flood (brownout armed) is no worse than without the
        flood — also when replica 1 dies at step 8 of BOTH runs (the
        composition ``ci/fast.sh``'s multi-tenant smoke held until
        PR 48): sheds land on background / batch only, nothing is lost,
        no page leaks."""
        plan = FaultPlan(
            seed=1, faults=(ReplicaDeath(replica=1, step=8),)
        ) if death else None

        def run(trace):
            fleet = _fleet(fleet_models, tenants=dict(TEN), queue_cap=3,
                           brownout=BrownoutConfig(slo_ms=0.004,
                                                   window=2, cooldown=3))
            with faults.fault_plan(plan) if plan else nullcontext():
                st = fleet.run(trace, max_ticks=800)
            assert st.lost_requests == 0
            assert st.deaths == ([(1, 8)] if death else [])
            return fleet

        base = run(_mixed_trace(n_bat=0, n_bg=0))
        p99_free = base.per_tenant()["iact"]["p99_ttft_ticks"]
        fleet = run(_mixed_trace(n_bat=24, n_bg=6))
        p99_flood = fleet.per_tenant()["iact"]["p99_ttft_ticks"]
        assert p99_flood <= p99_free, (
            f"interactive p99 degraded under flood: "
            f"{p99_flood} > {p99_free}")
        assert set(fleet.stats.sheds) <= {"background", "batch"}
        assert sum(fleet.stats.sheds.values()) >= 1
        _assert_no_leaks(fleet)


# ------------------------------------- drain × preemption interplay

class TestPreemptDuringDrain:
    def _trace(self):
        out = []
        for i in range(2):
            out.append(_req(i, 0.0, "bat", session="a", max_new=8))
        for i in range(2):
            out.append(_req(10 + i, 0.0, "bat", session="b",
                            max_new=8))
        # interactive burst while the drain migration is in flight
        out += [_req(20 + i, 4.0, "iact", max_new=4)
                for i in range(3)]
        return out

    def test_drain_migration_survives_preemption(self, fleet_models):
        """Drain replica 1 mid-run (its rows migrate to replica 0),
        then flood replica 0 with interactive admissions that preempt
        the migrated batch rows. The transactional reserve/land/commit
        handoff must stay intact: zero lost, token streams identical
        to the fault-free single-tenant fleet, no page leaks."""
        ref = _fleet(fleet_models)
        ref.router.affinity["a"] = 0
        ref.router.affinity["b"] = 1
        ref.run(self._trace())
        assert ref.stats.lost_requests == 0

        fleet = _fleet(fleet_models, tenants=dict(TEN))
        fleet.router.affinity["a"] = 0
        fleet.router.affinity["b"] = 1
        fleet.submit_trace(self._trace())
        for t in range(400):
            if fleet.idle:
                break
            if t == 3:
                fleet.drain(1)
            fleet.tick()
        st = fleet.stats
        assert st.lost_requests == 0
        assert st.migrations >= 1
        assert fleet.preemptions >= 1
        assert 1 in fleet._retired
        assert fleet.token_streams() == ref.token_streams()
        _assert_no_leaks(fleet)


# -------------------------------------------- maintenance retune

@pytest.fixture
def store_dir(tmp_path, monkeypatch):
    from triton_distributed_tpu.tune import schedule as S

    monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
    S.load_schedule.cache_clear()
    yield tmp_path
    S.load_schedule.cache_clear()


class TestMaintenanceRetune:
    def test_retune_fires_in_low_pressure_window(self, fleet_models,
                                                 store_dir):
        fleet = _fleet(fleet_models, retune_every=3)
        st = fleet.run(_mixed_trace(n_iact=3, n_bat=0, n_bg=0),
                       max_ticks=400)
        assert st.retunes, "no maintenance window found"
        assert any(e[0] == "retune" for e in st.events)
        tick, replica, n = st.retunes[0]
        assert tick % 3 == 0 and n >= 1

    def test_retune_suppressed_during_brownout(self, fleet_models,
                                               store_dir):
        fleet = _fleet(fleet_models, tenants=dict(TEN),
                       retune_every=3,
                       brownout=BrownoutConfig(slo_ms=1.0))
        fleet.run(_mixed_trace(n_iact=3, n_bat=0, n_bg=0),
                  max_ticks=400)
        before = len(fleet.stats.retunes)
        assert before > 0                  # normal level: retunes ran
        # force an overload level: the same low-pressure check must
        # now refuse the window
        fleet.brownout.level = 2
        fleet.ticks = 3 * fleet.retune_every
        fleet._maybe_retune()
        assert len(fleet.stats.retunes) == before
        fleet.brownout.level = 0
        fleet._maybe_retune()
        assert len(fleet.stats.retunes) == before + 1


# ------------------------------------------------ replay determinism

class TestReplayDeterminism:
    def _chaos_run(self, fleet_models):
        fleet = _fleet(fleet_models, tenants=dict(TEN), queue_cap=3,
                       brownout=BrownoutConfig(slo_ms=0.004, window=2,
                                               cooldown=3))
        plan = FaultPlan(seed=1,
                         faults=(ReplicaDeath(replica=1, step=8),))
        fleet.submit_trace(_mixed_trace(n_bat=16, n_bg=4))
        with faults.fault_plan(plan):
            for _ in range(600):
                if fleet.idle:
                    break
                fleet.tick()
        return fleet

    def test_flood_death_preemption_events_identical(
            self, fleet_models):
        """Tenant flood × ReplicaDeath × preemption/shed/brownout:
        same seed ⇒ byte-identical event logs (the PR-13 replay
        contract extended to the multi-tenant events), zero lost."""
        runs = [self._chaos_run(fleet_models) for _ in range(2)]
        for fleet in runs:
            assert fleet.stats.lost_requests == 0
            assert (1, 8) in fleet.stats.deaths
            _assert_no_leaks(fleet)
        assert runs[0].stats.events == runs[1].stats.events
        kinds = {e[0] for e in runs[0].stats.events}
        assert "death" in kinds
