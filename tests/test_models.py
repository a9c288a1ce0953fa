"""Flagship transformer tests: training (dense + MoE) and the serving
step's transports and precisions.

The reference has no model zoo; these tests pin the framework-level
contract — every projection through the overlap ops, trainable
end-to-end — and, for ``serving_step``, the barrier-free LL MoE state
and each quantized precision against its full-precision twin
(``tests/test_serving_step.py`` holds the step against ``forward``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import force_fused_ctx, serve_all_logits
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import EngineConfig

CFG = dict(
    vocab=128, n_layers=2, hidden=128, ffn=256,
    n_heads=8, n_kv_heads=4, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32,
)


def _model(mesh, moe="none", dp=False):
    cfg = TransformerConfig(
        **CFG, moe=moe, moe_layers=(1,) if moe != "none" else (),
        num_experts=8, topk=2,
    )
    return Transformer(cfg, mesh, "tp", ("dp",) if dp else ())


def _sharded_params(model, key=0):
    params = model.init(jax.random.PRNGKey(key))
    return jax.tree.map(
        lambda p, s: jax.device_put(p, s), params, model.shardings()
    )


@pytest.fixture(scope="module")
def mesh_tp():
    devs = np.asarray(jax.devices())
    from jax.sharding import Mesh

    return Mesh(devs, ("tp",))


@pytest.fixture(scope="module")
def mesh_tp2():
    """Two devices: the smallest tp with a peer (the serving pools
    shard the 4 KV heads over it)."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:2]), ("tp",))


@pytest.fixture(scope="module")
def mesh_dp_tp():
    devs = np.asarray(jax.devices()).reshape(2, 4)
    from jax.sharding import Mesh

    return Mesh(devs, ("dp", "tp"))


class TestTraining:
    def test_dense_loss_decreases_dp_tp(self, mesh_dp_tp):
        model = _model(mesh_dp_tp, dp=True)
        params = _sharded_params(model)
        toks = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128),
            NamedSharding(mesh_dp_tp, P("dp")),
        )
        l1, params = model.train_step(params, toks, toks)
        l2, _ = model.train_step(params, toks, toks)
        assert np.isfinite(float(l1)) and float(l2) < float(l1)

    def test_moe_ep_loss_decreases(self, mesh_dp_tp):
        model = _model(mesh_dp_tp, moe="ep", dp=True)
        params = _sharded_params(model)
        toks = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128),
            NamedSharding(mesh_dp_tp, P("dp")),
        )
        l1, params = model.train_step(params, toks, toks)
        l2, _ = model.train_step(params, toks, toks)
        assert np.isfinite(float(l1)) and float(l2) < float(l1)


#: the engine the serving tests below drive: chunks of 8, pages of 8
SERVE = EngineConfig(slots=4, token_budget=32, chunk=8, page=8, npages=32)


def _serve(model, params, max_new=3, **engine_kw):
    """Two prompts (a chunked one, a short one) through ``SERVE``: the
    engine, its requests, and per request the logits at every sequence
    position."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, (n,)).astype(np.int32) for n in (12, 5)]
    return serve_all_logits(
        model, params, SERVE, prompts, max_new=max_new, **engine_kw)


_EP = dict(moe="ep", moe_layers=(1,), num_experts=8, topk=2)
_slow = pytest.mark.slow
#: precision → (fields of the full-precision twin, fields of the
#: quantized model, the quantizer both models' params go through, the
#: ``_moe_ep_ctx`` patch, tolerance as a share of the largest logit).
#: The three EP cases are ``slow`` (each serves two models over the
#: forced-fused interpreter transport); tier-1 keeps the two that need
#: no transport.
PRECISIONS = {
    "kv_quant": ({}, dict(kv_quant="int8"), None, None, 0.05),
    "dense_weight_quant": (
        {}, dict(dense_weight_quant="int8"), "quantize_dense_weights",
        None, 0.05),
    "dense_act_quant": (
        {}, dict(dense_weight_quant="int8", dense_act_quant="int8"),
        "quantize_dense_weights", None, 0.06),
    "moe_wire_quant": (
        _EP, dict(_EP, moe_wire_quant="fp8"), None, force_fused_ctx, 0.05),
    "moe_weight_quant": (
        _EP, dict(_EP, moe_weight_quant="int8"), "quantize_moe_weights",
        force_fused_ctx, 0.05),
    # W8A8 against W8A16: both serve the int8 expert matrices
    "moe_act_quant": (
        dict(_EP, moe_weight_quant="int8"),
        dict(_EP, moe_weight_quant="int8", moe_act_quant="int8"),
        "quantize_moe_weights",
        functools.partial(force_fused_ctx, use_pallas_gemm=True), 0.06),
}


class TestDecode:
    def test_decode_ll_state_matches_stateless(self, mesh_tp2, monkeypatch):
        """serving_step with the barrier-free LL MoE state EXECUTES (not
        just compiles) and matches the stateless step over consecutive
        parities. Off-TPU the model normally demotes the step to the
        XLA transport, so the fused context is forced here (tiny
        shapes, interpreter-safe)."""
        model = _model(mesh_tp2, moe="ep")
        monkeypatch.setattr(Transformer, "_moe_ep_ctx", force_fused_ctx())
        params = _sharded_params(model)
        eng_ll, _, ll = _serve(model, params)
        assert eng_ll.moe_state is not None
        state = eng_ll.moe_state[eng_ll._t_pad]     # SERVE: one width
        assert state[1] is not None                 # MoE layer 1
        eng_ref, _, ref = _serve(model, params, moe_state=None)
        assert eng_ref.moe_state is None
        np.testing.assert_allclose(
            np.concatenate(ll), np.concatenate(ref), atol=1e-5, rtol=1e-5)
        steps = len(eng_ll.stats.step_tokens)
        assert steps >= 4
        assert int(np.asarray(state[1].parity)[0]) == steps % 2

    def test_decode_fused_ll_real_ctx_executes(self, mesh_tp2):
        """The REAL ``_moe_ep_ctx`` path (no monkeypatch) under
        ``config.force_fused_transport`` serves through the fused-LL
        transport on a 2-device interpreter mesh (the smallest tp at
        which the transport has a peer) — chunked transport + donable
        functional state + pool append + ragged attention composed in
        the production step — and matches the XLA-transport logits
        (VERDICT r4 #4)."""
        from triton_distributed_tpu.config import config as tcfg

        model = _model(mesh_tp2, moe="ep")
        params = _sharded_params(model)
        _, _, ref = _serve(model, params)

        tcfg.force_fused_transport = True
        try:
            m_ll = _model(mesh_tp2, moe="ep")   # fresh ctx/jit caches
            ctx = m_ll._moe_ep_ctx(1, inference=True)
            assert ctx.transport == "fused"
            eng, _, ll = _serve(m_ll, params)
            assert eng.moe_state is not None
            state = eng.moe_state[eng._t_pad]
            assert state[1] is not None
            np.testing.assert_allclose(
                np.concatenate(ll), np.concatenate(ref),
                atol=1e-5, rtol=1e-5)
            steps = len(eng.stats.step_tokens)
            assert int(np.asarray(state[1].parity)[0]) == steps % 2
        finally:
            tcfg.force_fused_transport = False

    @pytest.mark.parametrize("precision", [
        pytest.param(k, marks=[_slow] if "moe" in k else [])
        for k in PRECISIONS])
    def test_decode_quant_close_to_full_precision(self, mesh_tp2,
                                                  monkeypatch, precision):
        """Each serving precision against its full-precision twin, the
        same prompts through ``_serving_all_logits_jit``: chunked
        prefill and decode rows alike stay within the quantization's
        tolerance at every position both were fed the same tokens for
        (a row is compared up to the first served token the two
        disagree on) — and differ (identical logits would mean the
        quantized path silently regressed to a no-op)."""
        full_over, quant_over, quantize, ctx_patch, tol = PRECISIONS[
            precision]
        if ctx_patch is not None:
            monkeypatch.setattr(Transformer, "_moe_ep_ctx", ctx_patch())
        full = Transformer(
            TransformerConfig(**CFG, **full_over), mesh_tp2, "tp", ())
        quant = Transformer(
            TransformerConfig(**CFG, **quant_over), mesh_tp2, "tp", ())
        params = _sharded_params(full)
        qparams = params if quantize is None else getattr(
            quant, quantize)(params)
        if quantize is not None:
            # idempotent: already-quantized params pass through
            again = getattr(quant, quantize)(qparams)
            assert all(a is b for a, b in zip(
                jax.tree.leaves(again), jax.tree.leaves(qparams)))
        eng_q, reqs_q, got = _serve(quant, qparams)
        if "kv_quant" in quant_over:
            assert eng_q.state.layers[0][0]["q"].dtype == jnp.int8
        # the twin serves the SAME stored weights where the precision
        # under test is not the weights' (W8A8 against W8A16)
        _, reqs_f, want = _serve(
            full, qparams if full_over.get("moe_weight_quant") else params)
        decode_rows = 0
        for rq, rf, g, w in zip(reqs_q, reqs_f, got, want):
            same = np.asarray(rq.generated) == np.asarray(rf.generated)
            fed = int(np.argmin(same)) if not same.all() else len(same) - 1
            decode_rows += fed
            n = len(rf.prompt) + fed
            assert g.dtype == w.dtype == np.float32
            err = np.abs(g[:n] - w[:n]).max()
            assert 0 < err < tol * np.abs(w[:n]).max()
        assert decode_rows > 0, "degenerate: no decode row was compared"

    def test_residency_gate_keys_on_actual_weights(self, mesh_tp):
        """A preset can default moe_weight_quant while the caller never
        ran quantize_moe_weights: the weight-residency VMEM gate must
        size from the REAL leaves (bf16), not the config's intent —
        sizing bf16 tiles at 1 B/elem would blow scoped VMEM at the
        first decode compile."""
        from triton_distributed_tpu.config import config, fused_vmem_budget

        cfg = TransformerConfig(
            vocab=128, n_layers=1, hidden=7168, ffn=2560, n_heads=8,
            n_kv_heads=4, head_dim=16, moe="ep", moe_layers=(0,),
            num_experts=8, topk=2, moe_weight_quant="int8",
        )
        budget = int(0.7 * fused_vmem_budget())
        if not (2 * cfg.hidden * cfg.ffn <= budget
                < 2 * cfg.hidden * cfg.ffn * 2):
            pytest.skip("vmem budget does not straddle this geometry")
        model = Transformer(cfg, mesh_tp, "tp", ())
        old = config.force_compile
        config.force_compile = True    # compiling_for_tpu() → True
        try:
            ctx_q = model._moe_ep_ctx(16, inference=True)
            ctx_raw = model._moe_ep_ctx(
                16, inference=True, weights_quantized=False
            )
        finally:
            config.force_compile = old
        # the gate decides the schedule; the block is the rule's at
        # this step's rows (16 a shard), each regime's own
        from triton_distributed_tpu.models.transformer import (
            expert_block_m,
        )

        rows = 16 * model.tp
        assert ctx_q.gg_block_n is not None and ctx_q.block_m == \
            expert_block_m(rows, 2, 8, resident=True, floor=16, cap=64)
        assert ctx_raw.gg_block_n is None and ctx_raw.block_m == \
            expert_block_m(rows, 2, 8, resident=False, floor=64, cap=256)
        assert ctx_q.block_m < ctx_raw.block_m


class TestRemat:
    def test_remat_matches_no_remat(self, mesh_dp_tp, monkeypatch):
        """jax.checkpoint must not change values or gradients. The
        interpreted Pallas engines carry io_callback effects that
        jax.checkpoint rejects, so this pins the XLA engines (what a
        remat run uses off-TPU; on hardware Mosaic kernels compose)."""
        from triton_distributed_tpu.config import config as tdtpu_config

        monkeypatch.setattr(tdtpu_config, "fused_vmem_budget", 0)
        toks = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128),
            NamedSharding(mesh_dp_tp, P("dp")),
        )
        losses, grads = {}, {}
        for remat in (False, True):
            cfg = TransformerConfig(**CFG, remat=remat)
            m = Transformer(cfg, mesh_dp_tp, "tp", ("dp",))
            params = jax.tree.map(
                lambda p, s: jax.device_put(p, s),
                m.init(jax.random.PRNGKey(0)), m.shardings(),
            )
            l, g = jax.value_and_grad(m.loss)(params, toks, toks)
            losses[remat], grads[remat] = float(l), g
        assert abs(losses[True] - losses[False]) < 1e-6
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
            ),
            grads[True], grads[False],
        )

    def test_remat_with_pallas_engines_rejected_off_tpu(self, mesh_dp_tp):
        cfg = TransformerConfig(**CFG, remat=True)
        m = Transformer(cfg, mesh_dp_tp, "tp", ("dp",))
        params = _sharded_params(m)
        toks = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 128),
            NamedSharding(mesh_dp_tp, P("dp")),
        )
        with pytest.raises(ValueError, match="TDTPU_FUSED_VMEM_BUDGET"):
            m.forward(params, toks)
