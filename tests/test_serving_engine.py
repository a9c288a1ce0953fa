"""Continuous-batching serving engine: scheduler + serving-step tests.

The ISSUE-6 satellite suite: deterministic seeded Poisson traces,
admission blocking at pool exhaustion, eviction + re-admission resuming
from the exact cursor, chunked-prefill/decode interleave invariants —
and the end-to-end pin: every request served by the engine (under
contention, chunking and eviction) produces EXACTLY the tokens greedy
decoding by ``Transformer.forward`` produces (``tests/oracle.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from oracle import assert_served_greedy, greedy_tokens

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import (
    EngineConfig,
    Request,
    ServingEngine,
    ServingState,
    poisson_trace,
)

pytestmark = pytest.mark.fast

CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("tp",))


@pytest.fixture(scope="module")
def model_params(mesh1):
    model = Transformer(TransformerConfig(**CFG), mesh1, "tp", ())
    return model, model.init(jax.random.PRNGKey(0))


class TestServingEngine:
    def test_trace_is_deterministic(self, model_params):
        model, params = model_params
        outs = []
        for _ in range(2):
            eng = ServingEngine(
                model, params,
                EngineConfig(slots=4, token_budget=48, chunk=16,
                             page=8, npages=24),
            )
            trace = poisson_trace(9, 6, 1.0, 4, 24, 2, 5, 128)
            eng.run(trace, max_steps=300)
            outs.append([tuple(r.generated) for r in trace])
        assert outs[0] == outs[1]

    def test_matches_reference_under_contention(self, model_params):
        """Chunked prefill interleaved with other requests' decode —
        every request's tokens equal the forward oracle's."""
        model, params = model_params
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                         npages=32),
        )
        trace = poisson_trace(7, 6, 1.0, 5, 30, 3, 6, 128)
        stats = eng.run(trace, max_steps=400)
        assert stats.completed == 6
        for req in trace:
            assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid

    def test_admission_blocks_at_pool_exhaustion(self, model_params):
        """With pages for ~2 requests, a burst of 6 arrivals at t=0
        must NOT all be admitted at once — the queue drains as
        completions free pages, and everyone still completes."""
        model, params = model_params
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=6, token_budget=64, chunk=16, page=8,
                         npages=6),                  # ~2 × 24-token seqs
        )
        trace = [
            Request(rid=i, prompt=(np.arange(16) + i).astype(np.int32)
                    % 128, max_new=3, arrival=0.0)
            for i in range(6)
        ]
        eng.submit_trace(trace)
        eng._admit()
        admitted0 = sum(r is not None for r in eng.slot_req)
        assert admitted0 <= 3                        # pool-gated, not slot-gated
        assert len(eng.waiting) == 6 - admitted0
        stats = eng.run(max_steps=400)
        assert stats.completed == 6

    def test_eviction_resumes_from_exact_cursor(self, model_params):
        """Force mid-decode eviction (pool far smaller than the load):
        the evicted request re-prefills prompt+generated and completes
        with EXACTLY the uncontended reference tokens."""
        model, params = model_params
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                         npages=12),
        )
        trace = poisson_trace(7, 8, 1.0, 5, 30, 3, 6, 128)
        stats = eng.run(trace, max_steps=600)
        assert stats.completed == 8
        assert stats.evictions > 0, "config failed to force an eviction"
        evicted = [r for r in trace if r.evictions]
        assert evicted
        for req in evicted:
            assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), (
                f"evicted rid {req.rid} diverged after re-admission"
            )

    def test_interleave_invariants(self, model_params):
        """Per-step accounting: packed tokens within budget, prefill
        rows advance by at most `chunk`, decode rows by exactly 1, and
        at least one step genuinely mixes prefill and decode rows."""
        model, params = model_params
        cfg = EngineConfig(slots=4, token_budget=48, chunk=8, page=8,
                           npages=32)
        eng = ServingEngine(model, params, cfg)
        # request 0 decodes from step ~2 while 1 and 2 still prefill
        trace = [
            Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                    max_new=8, arrival=0.0),
            Request(rid=1, prompt=np.arange(30, dtype=np.int32) % 128,
                    max_new=2, arrival=1.0),
            Request(rid=2, prompt=np.arange(28, dtype=np.int32) % 128,
                    max_new=2, arrival=1.0),
        ]
        eng.submit_trace(trace)
        mixed_steps = 0
        cursors = {r.rid: 0 for r in trace}
        while not eng.idle and eng.step_count < 200:
            before = {
                r.rid: r.cursor for r in trace
            }
            rep = eng.step()
            assert rep["tokens"] <= cfg.token_budget
            decode_rows = prefill_rows = 0
            for r in trace:
                adv = r.cursor - before[r.rid]
                assert 0 <= adv <= cfg.chunk
                if adv == 1 and before[r.rid] >= len(r.prompt):
                    decode_rows += 1
                elif adv > 0 and before[r.rid] < len(r.prompt):
                    prefill_rows += 1
                    # prefill advances by the full chunk unless the
                    # prompt tail or budget ends it
                    assert adv == min(
                        cfg.chunk,
                        len(r.prompt) + len(r.generated) - before[r.rid],
                    ) or adv > 0
            if decode_rows and prefill_rows:
                mixed_steps += 1
            cursors.update({r.rid: r.cursor for r in trace})
        assert mixed_steps > 0, "trace never exercised a mixed batch"
        assert all(r.done for r in trace)

    def test_degrades_to_xla_twin_on_kernel_failure(self, model_params,
                                                    monkeypatch):
        """First Pallas failure flips the engine onto the XLA twin and
        the batch re-runs — results identical to a pallas-free run."""
        import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

        model, params = model_params
        real = rpa.ragged_paged_attention

        calls = {"n": 0}

        def boom(*a, **k):
            calls["n"] += 1
            raise RuntimeError("injected kernel failure")

        monkeypatch.setattr(rpa, "ragged_paged_attention", boom)
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        )
        req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                      max_new=3, arrival=0.0)
        stats = eng.run([req], max_steps=50)
        monkeypatch.setattr(rpa, "ragged_paged_attention", real)
        assert stats.degraded and calls["n"] >= 1
        assert eng.use_pallas is False
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new)
        # the absorbed exception is kept: what failed, at which step
        assert [f["site"] for f in stats.failures] == ["serving_step"]
        assert "injected kernel failure" in stats.failures[0]["error"]
        assert stats.failures[0]["step"] == 0

    def test_kernel_failure_propagates_on_request(self, model_params,
                                                  monkeypatch):
        """``propagate_failures=True`` records the failure and RAISES
        it instead of degrading (chip_smoke.py's contract: a result
        must not silently come from the XLA twin). Budget 40 keeps this
        trace off the step-jit cache of the degrade test above (a
        traced step captured the real kernel)."""
        import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

        model, params = model_params

        def boom(*a, **k):
            raise RuntimeError("injected kernel failure")

        monkeypatch.setattr(rpa, "ragged_paged_attention", boom)
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=40, chunk=8, page=8,
                         npages=16),
            propagate_failures=True,
        )
        req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                      max_new=3, arrival=0.0)
        with pytest.raises(RuntimeError, match="injected kernel failure"):
            eng.run([req], max_steps=50)
        assert eng.use_pallas is True and not eng.stats.degraded
        assert len(eng.stats.failures) == 1

    def test_serving_state_is_a_donatable_pytree(self, model_params):
        model, _ = model_params
        state = model.init_serving_state(slots=2, npages=8, page=8)
        assert isinstance(state, ServingState)
        leaves, tree = jax.tree.flatten(state)
        rebuilt = jax.tree.unflatten(tree, leaves)
        assert rebuilt.page == state.page
        assert rebuilt.slots == 2 and rebuilt.npages == 8
        assert state.capacity == state.pages_per_seq * 8

    def test_serving_rejects_unshardable_heads(self, mesh1):
        devs = jax.devices()
        if len(devs) < 8:
            pytest.skip("needs the 8-device test mesh")
        mesh8 = Mesh(np.asarray(devs), ("tp",))
        model = Transformer(
            TransformerConfig(**{**CFG, "n_kv_heads": 2, "n_heads": 4}),
            mesh8, "tp", (),
        )
        with pytest.raises(ValueError, match="KV heads"):
            model.init_serving_state(slots=2, npages=8, page=8)


class TestPrefixCache:
    """The PR-6 follow-on: per-page refcounts + chain-hash page reuse
    (serving/state.PagePool) — shared prefixes and re-admitted evicted
    requests reattach resident pages instead of recomputing, pinned
    token-exact."""

    def test_shared_prefix_reuses_pages_token_exact(self, model_params):
        model, params = model_params
        shared = (np.arange(24, dtype=np.int32) * 3) % 128
        r1 = Request(rid=0, prompt=shared.copy(), max_new=3, arrival=0.0)
        r2 = Request(
            rid=1,
            prompt=np.concatenate([shared, np.asarray([9, 4], np.int32)]),
            max_new=3, arrival=6.0,       # admitted after r1's pages froze
        )
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=4, token_budget=48, chunk=8, page=8,
                         npages=32, prefix_cache=True),
        )
        stats = eng.run([r1, r2], max_steps=300)
        assert stats.completed == 2
        assert stats.prefix_hits > 0, "shared prefix never reattached"
        for r in (r1, r2):
            assert r.generated == greedy_tokens(
                model, params, r.prompt, r.max_new), r.rid

    def test_evicted_request_reattaches_resident_pages(self, model_params):
        """Eviction decrements refcounts instead of freeing; the
        re-admitted request's recompute prefix reattaches the cached
        pages and still produces the exact reference tokens."""
        model, params = model_params
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                         npages=12, prefix_cache=True),
        )
        trace = poisson_trace(7, 8, 1.0, 5, 30, 3, 6, 128)
        stats = eng.run(trace, max_steps=600)
        assert stats.completed == 8
        assert stats.evictions > 0, "config failed to force an eviction"
        assert stats.prefix_hits > 0, "re-admission never reused a page"
        for req in trace:
            assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid

    def test_refcounted_release_keeps_shared_pages(self):
        from triton_distributed_tpu.serving.state import PagePool

        pool = PagePool(4, 8, prefix_cache=True)
        pg = pool.alloc()
        pool.register(pg, 1234)
        pool.retain(pg)                    # second holder
        pool.release(pg)                   # first lets go — still held
        assert pool.refs[pg] == 1
        assert pool.lookup(1234) == pg
        pool.release(pg)                   # last holder: parks in cache
        assert pool.refs[pg] == 0
        assert pool.lookup(1234) == pg     # resident, reattachable
        assert pool.available == 4         # and reclaimable under pressure
        # reclaim under pressure unregisters it
        got = {pool.alloc() for _ in range(4)}
        assert len(got) == 4
        assert pool.lookup(1234) is None
        assert pool.alloc() is None

    def test_prefix_cache_off_by_default(self, model_params):
        model, params = model_params
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        )
        assert eng.pool.prefix_cache is False


class TestSampling:
    """Engine-side temperature/top-k over the per-slot logits: draws
    are (seed, rid, n_generated)-keyed, so token streams are invariant
    to scheduling (chunking, contention, eviction replays)."""

    def test_greedy_default_unchanged(self, model_params):
        model, params = model_params
        req = Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                      max_new=3, arrival=0.0)
        ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        ).run([req], max_steps=50)
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new)

    def test_sampled_stream_invariant_to_chunking(self, model_params):
        model, params = model_params
        outs = []
        for chunk in (4, 16):
            req = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                          max_new=6, arrival=0.0)
            ServingEngine(
                model, params,
                EngineConfig(slots=2, token_budget=32, chunk=chunk,
                             page=8, npages=16, temperature=0.8,
                             top_k=16, seed=3),
            ).run([req], max_steps=80)
            outs.append(req.generated)
        assert outs[0] == outs[1]
        assert len(outs[0]) == 6

    def test_top_k_truncates_support(self, model_params):
        """With top_k=1 the sampler IS greedy regardless of
        temperature."""
        model, params = model_params
        req_g = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                        max_new=4, arrival=0.0)
        req_s = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                        max_new=4, arrival=0.0)
        base = dict(slots=2, token_budget=32, chunk=8, page=8, npages=16)
        ServingEngine(
            model, params, EngineConfig(**base),
        ).run([req_g], max_steps=60)
        ServingEngine(
            model, params,
            EngineConfig(**base, temperature=2.5, top_k=1, seed=9),
        ).run([req_s], max_steps=60)
        assert req_g.generated == req_s.generated


class TestServingStepTP:
    def test_tp2_head_sharded_matches_reference(self):
        """tp=2: pools shard over the KV-head dim; the engine's tokens
        equal the single-request reference on the same mesh."""
        devs = jax.devices()
        if len(devs) < 2:
            pytest.skip("needs >= 2 devices")
        mesh2 = Mesh(np.asarray(devs[:2]), ("tp",))
        cfg = TransformerConfig(
            **CFG, moe="ep", moe_layers=(1,), num_experts=4, topk=2,
        )
        model = Transformer(cfg, mesh2, "tp", ())
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, s),
            model.init(jax.random.PRNGKey(0)), model.shardings(),
        )
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        )
        req = Request(rid=0, prompt=(np.arange(10, dtype=np.int32) * 7)
                      % 128, max_new=3, arrival=0.0)
        stats = eng.run([req], max_steps=60)
        assert stats.completed == 1
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new)

    def test_int8_kv_pools_match_reference(self):
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
        cfg = TransformerConfig(**CFG, kv_quant="int8")
        model = Transformer(cfg, mesh, "tp", ())
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(
            model, params,
            EngineConfig(slots=2, token_budget=32, chunk=8, page=8,
                         npages=16),
        )
        req = Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                      max_new=3, arrival=0.0)
        eng.run([req], max_steps=50)
        # the pools hold int8 keys and values, the oracle none: a
        # served token is the oracle's choice up to that noise
        assert_served_greedy(model, params, req, eps=0.02)
