"""Disaggregated prefill/decode serving: the KV-ship transport suite.

The ISSUE-7 satellite coverage, all sim-free (the transports under test
are XLA-side — the gather/scatter plumbing, the paired DCN ``ppermute``
rails, the device_put fallback — and the scheduling machinery is host
code; the Pallas ship kernel's correctness is pinned statically by the
``kv_ship.pages`` lint family in test_analysis.py):

* wire-layout round trip — int8 pages + per-row scale planes gathered,
  shipped and scattered BYTE-IDENTICALLY, across both the DCN rail and
  its XLA twin;
* in-flight-transfer vs eviction race — pages pinned by a mid-ship
  request are never eviction victims on either side;
* decode admission gating on SHIPPED pages (reserve → commit);
* 2×2 hybrid-mesh end-to-end token-exactness vs the colocated engine
  (int8 KV, tp=2 head sharding, evictions included);
* transport degradation onto ``tools.native.xla_kv_ship``;
* the perf model's `auto` placement refusal.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from oracle import assert_served_greedy

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import (
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    poisson_trace,
)

pytestmark = pytest.mark.fast

CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32, kv_quant="int8",
)
#: the pools hold int8 keys and values, the forward oracle none: a
#: served token is the oracle's choice up to that noise in its logits
INT8_EPS = 0.02


def _mesh(devs, axes):
    return Mesh(np.asarray(devs), axes)


@pytest.fixture(scope="module")
def roles1():
    """One device per role + the 2×1 hybrid mesh."""
    devs = jax.devices()
    return (_mesh(devs[:1], ("tp",)), _mesh(devs[1:2], ("tp",)),
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


class TestWireLayout:
    """The payload IS the pool's quantized bytes: every transport must
    move it bit-exactly."""

    def test_gather_scatter_round_trip_byte_identical(self, models1):
        """Pages gathered from a populated pool and scattered into a
        fresh pool at different slots hold byte-identical int8 payload
        AND scale planes."""
        from triton_distributed_tpu.kernels.kv_ship import (
            gather_kv_pages,
            scatter_kv_pages,
        )

        mp, pp, *_ = models1
        src = mp.init_serving_state(2, 16, 8)
        # populate a pool deterministically and PARK the finished
        # request (on_complete=False) so its table survives completion
        eng2 = ServingEngine(
            mp, pp, EngineConfig(slots=2, token_budget=32, chunk=8,
                                 page=8, npages=16),
            on_complete=lambda r, s: False,   # park: keep pages resident
        )
        req2 = Request(rid=0, prompt=np.arange(20, dtype=np.int32),
                       max_new=1, arrival=0.0)
        eng2.run([req2], max_steps=40)
        pids = eng2.table[req2.slot, :eng2._pages_held(req2.cursor)]
        assert (pids >= 0).all()
        qpay, spay = jax.jit(gather_kv_pages)(
            eng2.state.layers, jnp.asarray(pids.astype(np.int32))
        )
        assert qpay.dtype == jnp.int8 and spay is not None
        dst_pids = jnp.asarray(
            np.arange(len(pids), dtype=np.int32)[::-1].copy()
        )
        new_layers = jax.jit(scatter_kv_pages)(
            src.layers, dst_pids, qpay, spay
        )
        for li, (kp, vp) in enumerate(eng2.state.layers):
            nkp, nvp = new_layers[li]
            for pool, npool in ((kp, nkp), (vp, nvp)):
                np.testing.assert_array_equal(
                    np.asarray(pool["q"])[pids],
                    np.asarray(npool["q"])[np.asarray(dst_pids)],
                )
                np.testing.assert_array_equal(
                    np.asarray(pool["scale"])[pids],
                    np.asarray(npool["scale"])[np.asarray(dst_pids)],
                )

    def test_dcn_rail_byte_identical_to_xla_twin(self):
        """The paired ppermute rails land the exact payload+scale bytes
        on the destination role — byte-identical to what the XLA twin
        (device_put) moves — on a 2×4 hybrid mesh."""
        from triton_distributed_tpu.runtime.multislice import kv_ship_rail
        from triton_distributed_tpu.tools.native import xla_kv_ship

        devs = jax.devices()
        hybrid = Mesh(np.asarray(devs).reshape(2, 4), ("dcn", "x"))
        rng = np.random.default_rng(3)
        q = rng.integers(-127, 127, (4, 6, 2, 8, 16)).astype(np.int8)
        s = rng.standard_normal((4, 6, 2, 8)).astype(np.float32)
        stk_q = np.stack([q, np.zeros_like(q)])
        stk_s = np.stack([s, np.zeros_like(s)])
        out_q, out_s = kv_ship_rail(hybrid, "dcn", True)(stk_q, stk_s)
        np.testing.assert_array_equal(np.asarray(out_q)[1], q)
        np.testing.assert_array_equal(np.asarray(out_s)[1], s)
        # the XLA twin moves the same bytes (trivially — device_put)
        tq, ts = xla_kv_ship((q, s), (None, None))
        np.testing.assert_array_equal(np.asarray(tq), q)
        np.testing.assert_array_equal(np.asarray(ts), s)
        # raw wire (unquantized pools): payload-only rail
        (out_raw,) = kv_ship_rail(hybrid, "dcn", False)(stk_q)
        np.testing.assert_array_equal(np.asarray(out_raw)[1], q)

    def test_ship_wire_bytes_matches_perf_model(self):
        from triton_distributed_tpu.kernels.kv_ship import ship_wire_bytes
        from triton_distributed_tpu.tune.perf_model import (
            TPU_SPECS,
            kv_ship_ms,
        )

        b = ship_wire_bytes(4, 8, 2, 16, 2, True)
        # 2 layers × K,V × 4 pages × (2·8·16 int8 + 2·8·4 scale)
        assert b == 2 * 2 * 4 * (2 * 8 * 16 + 2 * 8 * 4)
        spec = TPU_SPECS["v5e"]
        ms = kv_ship_ms(4, 8, 2, 16, 2, True, spec)
        assert ms == pytest.approx(b / (spec.dcn_gbps * 1e9) * 1e3)


class TestDisaggregatedEngine:
    def test_end_to_end_token_exact_vs_colocated(self, models1, roles1):
        """Single-tp roles on the hybrid wire: every request's token
        stream equals the colocated engine's on the same trace."""
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        ecfg = EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                            npages=32)
        trace_c = poisson_trace(7, 6, 1.0, 5, 30, 3, 6, 128)
        trace_d = poisson_trace(7, 6, 1.0, 5, 30, 3, 6, 128)
        col = ServingEngine(mp, pp, ecfg)
        col.run(trace_c, max_steps=400)
        eng = DisaggregatedEngine(
            mp, pp, md, pd, ecfg, hybrid_mesh=hybrid, dcn_axis="dcn",
            transport="dcn", ship_delay_steps=1,
        )
        stats = eng.run(trace_d, max_ticks=600)
        assert stats.completed == 6
        assert stats.ships > 0 and not stats.degraded_transport
        assert stats.wire_compression > 1.0   # int8+scales vs bf16 pages
        for a, b in zip(trace_c, trace_d):
            assert a.generated == b.generated, a.rid

    def test_tp2_head_sharded_with_evictions_token_exact(self):
        """The acceptance pin: 2×2 hybrid mesh (tp=2 head sharding per
        role), int8 KV, decode pool small enough to force mid-stream
        evictions — token streams equal the colocated engine's."""
        devs = jax.devices()
        if len(devs) < 4:
            pytest.skip("needs 4 devices")
        mesh_p = _mesh(devs[:2], ("tp",))
        mesh_d = _mesh(devs[2:4], ("tp",))
        hybrid = Mesh(np.asarray(devs[:4]).reshape(2, 2), ("dcn", "tp"))
        mp = Transformer(TransformerConfig(**CFG), mesh_p, "tp", ())
        md = Transformer(TransformerConfig(**CFG), mesh_d, "tp", ())
        params = mp.init(jax.random.PRNGKey(0))
        pp = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                          mp.shardings())
        pd = jax.tree.map(lambda x, s: jax.device_put(x, s), params,
                          md.shardings())
        # decode pool far smaller than the prefill pool: decode-side
        # recompute-evictions fire while later ships are in flight
        ecfg = EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                            npages=32)
        dcfg = EngineConfig(slots=4, token_budget=32, chunk=16, page=8,
                            npages=14)
        trace_c = poisson_trace(9, 6, 0.7, 8, 30, 3, 6, 128)
        trace_d = poisson_trace(9, 6, 0.7, 8, 30, 3, 6, 128)
        col = ServingEngine(mp, pp, ecfg)
        col.run(trace_c, max_steps=500)
        eng = DisaggregatedEngine(
            mp, pp, md, pd, ecfg, decode_cfg=dcfg, hybrid_mesh=hybrid,
            dcn_axis="dcn", transport="dcn", ship_delay_steps=2,
        )
        stats = eng.run(trace_d, max_ticks=800)
        assert stats.completed == 6
        assert stats.decode.evictions > 0, (
            "config failed to force a decode-side eviction"
        )
        for a, b in zip(trace_c, trace_d):
            assert a.generated == b.generated, a.rid

    def test_admission_gates_on_shipped_pages(self, models1, roles1):
        """Between a ship's launch and its commit the decode slot is
        reserved-but-parked: its pages are claimed, its row is never
        batched; the first decode batch containing it happens only
        after the transfer commits."""
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        ecfg = EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                            npages=16)
        eng = DisaggregatedEngine(
            mp, pp, md, pd, ecfg, hybrid_mesh=hybrid, dcn_axis="dcn",
            transport="dcn", ship_delay_steps=3,
        )
        req = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                      max_new=4, arrival=0.0)
        eng.submit_trace([req])
        saw_parked_with_pages = False
        while not eng.idle and eng.ticks < 100:
            eng.tick()
            if eng._inflight:
                r = eng._inflight[0]
                assert req.parked
                # pages already claimed (admission gated on the SHIP,
                # not on promises) ...
                held = eng.decode.table[r.dslot]
                assert (held[:len(r.dpids)] >= 0).all()
                # ... but the row is not schedulable: no decode batch
                # has carried it while the transfer is in flight
                assert sum(eng.decode.stats.step_generated) == 0
                saw_parked_with_pages = True
        assert saw_parked_with_pages
        assert sum(eng.decode.stats.step_generated) > 0
        assert req.done
        assert_served_greedy(mp, pp, req, eps=INT8_EPS)

    def test_eviction_never_frees_pages_mid_ship(self, models1, roles1):
        """The race pin: while a transfer is in flight, neither role's
        eviction may pick the shipping request — its landing pages stay
        claimed and its table rows intact through the window."""
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        ecfg = EngineConfig(slots=3, token_budget=48, chunk=8, page=8,
                            npages=24)
        # decode pool with room for the ship but tight for decoders —
        # decode evictions fire during the in-flight windows
        dcfg = EngineConfig(slots=3, token_budget=24, chunk=8, page=8,
                            npages=10)
        eng = DisaggregatedEngine(
            mp, pp, md, pd, ecfg, decode_cfg=dcfg, hybrid_mesh=hybrid,
            dcn_axis="dcn", transport="dcn", ship_delay_steps=3,
        )
        trace = poisson_trace(5, 5, 0.5, 8, 22, 4, 7, 128)
        eng.submit_trace(trace)
        while not eng.idle and eng.ticks < 500:
            eng.tick()
            for r in eng._inflight:
                assert r.req.parked, "in-flight request lost its pin"
                table_row = eng.decode.table[r.dslot, :len(r.dpids)]
                assert list(table_row) == list(r.dpids), (
                    "eviction touched in-flight landing pages"
                )
                # the prefill-side source pages are still held too
                assert eng.prefill.slot_req[r.pslot] is r.req
        assert eng.stats.completed == 5
        for req in trace:
            assert_served_greedy(mp, pp, req, eps=INT8_EPS)

    def test_parked_requests_are_never_eviction_victims(self, models1):
        mp, pp, *_ = models1
        eng = ServingEngine(
            mp, pp, EngineConfig(slots=2, token_budget=32, chunk=8,
                                 page=8, npages=16),
        )
        req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                      max_new=2, arrival=0.0)
        eng._admit()   # no-op, just exercise the empty path
        eng.submit(req)
        eng._admit()
        req.parked = True
        assert eng._evict_one(set()) is False
        req.parked = False
        assert eng._evict_one(set()) is True

    def test_transport_degrades_to_xla_on_first_failure(
        self, models1, roles1, monkeypatch,
    ):
        """First DCN-wire failure flips the engine onto the
        device_put fallback (tools.native.xla_kv_ship) — results
        identical to the HEALTHY wire's (the oracle is the same engine
        without the injected failure: pages ship verbatim either way,
        so the degrade may not move a token), stats record the
        degradation and what failed."""
        import triton_distributed_tpu.serving.engine as engine_mod

        mp, pp, md, pd = models1
        _, _, hybrid = roles1

        def build():
            return DisaggregatedEngine(
                mp, pp, md, pd,
                EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                             npages=16),
                hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ), Request(rid=0, prompt=np.arange(11, dtype=np.int32),
                       max_new=3, arrival=0.0)

        healthy, want = build()
        assert healthy.run([want], max_ticks=100).completed == 1
        assert not healthy.stats.degraded_transport
        assert len(want.generated) == 3
        eng, req = build()

        def boom(self, qpay, spay):
            raise RuntimeError("injected wire failure")

        monkeypatch.setattr(
            engine_mod.DisaggregatedEngine, "_transport_dcn", boom
        )
        stats = eng.run([req], max_ticks=100)
        assert stats.degraded_transport
        assert eng.transport == "xla"
        assert stats.completed == 1
        assert req.generated == want.generated
        # the swallowed exception is kept: what failed, and when
        assert stats.transport_failures
        assert "injected wire failure" in \
            stats.transport_failures[0]["error"]
        assert stats.transport_failures[0]["site"] == "kv_ship"

    def test_transport_failure_propagates_on_request(
        self, models1, roles1, monkeypatch,
    ):
        """``propagate_failures=True``: the same injected failure is
        recorded and RAISED instead of degrading — for callers whose
        result must not come from the fallback."""
        import triton_distributed_tpu.serving.engine as engine_mod

        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        eng = DisaggregatedEngine(
            mp, pp, md, pd,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            propagate_failures=True,
        )

        def boom(self, qpay, spay):
            raise RuntimeError("injected wire failure")

        monkeypatch.setattr(
            engine_mod.DisaggregatedEngine, "_transport_dcn", boom
        )
        req = Request(rid=0, prompt=np.arange(11, dtype=np.int32),
                      max_new=3, arrival=0.0)
        with pytest.raises(RuntimeError, match="injected wire failure"):
            eng.run([req], max_ticks=100)
        assert eng.transport == "dcn"
        assert not eng.stats.degraded_transport
        assert len(eng.stats.transport_failures) == 1

    def test_max_new_1_completes_on_the_prefill_side(self, models1,
                                                     roles1):
        """A 1-token request is DONE when prefill finishes — no ship,
        no decode-slot churn."""
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        eng = DisaggregatedEngine(
            mp, pp, md, pd,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
            hybrid_mesh=hybrid, dcn_axis="dcn",
        )
        req = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                      max_new=1, arrival=0.0)
        stats = eng.run([req], max_ticks=50)
        assert stats.completed == 1 and stats.ships == 0
        assert_served_greedy(mp, pp, req, eps=INT8_EPS)

    def test_sampling_token_exact_across_topologies(self, models1,
                                                    roles1):
        """The satellite sampler is request-keyed: temperature/top-k
        streams are identical colocated vs disaggregated."""
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        ecfg = EngineConfig(slots=3, token_budget=48, chunk=16, page=8,
                            npages=24, temperature=0.8, top_k=12, seed=5)
        tc = poisson_trace(3, 4, 1.0, 5, 24, 3, 6, 128)
        td = poisson_trace(3, 4, 1.0, 5, 24, 3, 6, 128)
        ServingEngine(mp, pp, ecfg).run(tc, max_steps=300)
        DisaggregatedEngine(
            mp, pp, md, pd, ecfg, hybrid_mesh=hybrid, dcn_axis="dcn",
            transport="dcn", ship_delay_steps=1,
        ).run(td, max_ticks=500)
        assert [r.generated for r in tc] == [r.generated for r in td]
        assert all(len(r.generated) == r.max_new for r in tc)


class TestAutoPlacement:
    def test_perf_model_refuses_wire_dominated_traffic(self):
        from triton_distributed_tpu.tune.perf_model import (
            TPU_SPECS,
            refuse_disaggregation,
        )

        cfg = TransformerConfig(**CFG)
        spec = TPU_SPECS["v5e"]
        # long prompt, one decode step, fast decode: the ship cannot
        # hide — refused with the priced reason
        reason = refuse_disaggregation(
            cfg, 8,
            {"prompt_len": 4096, "max_new": 1, "decode_step_ms": 0.01},
            spec,
        )
        assert reason is not None and "kv_ship_ms" in reason
        # generous decode window: accepted
        assert refuse_disaggregation(
            cfg, 8,
            {"prompt_len": 64, "max_new": 256, "decode_step_ms": 5.0},
            spec,
        ) is None

    def test_engine_auto_placement_refusal_is_loud(self, models1,
                                                   roles1):
        mp, pp, md, pd = models1
        _, _, hybrid = roles1
        with pytest.raises(ValueError, match="refuses disaggregation"):
            DisaggregatedEngine(
                mp, pp, md, pd,
                EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                             npages=16),
                hybrid_mesh=hybrid, placement="auto",
                traffic={"prompt_len": 100_000, "max_new": 1,
                         "decode_step_ms": 1e-6},
            )
