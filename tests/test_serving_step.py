"""``Transformer.serving_step`` — the one served path — against the plain
reference, ``Transformer.forward`` (``tests/oracle.py``).

The engine packs every step (the one packing contract); the device step
hands out logits at every packed position (``_serving_all_logits_jit``,
``conftest.serve_all_logits``). CPU sizes, the XLA twins of the kernels
(``use_pallas=False``); the kernels against their twins are
``test_ragged_attention`` / ``test_kv_append`` / ``test_window_share``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import force_fused_ctx, serve_all_logits
from jax.sharding import Mesh
from oracle import forward_logits, greedy_tokens

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import (
    EngineConfig,
    Request,
    ServingEngine,
)

pytestmark = pytest.mark.fast

CFG = dict(
    vocab=128, n_layers=2, hidden=128, ffn=256,
    n_heads=8, n_kv_heads=4, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ENGINE = EngineConfig(slots=4, token_budget=32, chunk=8, page=8, npages=32)
TOL = dict(atol=1e-4, rtol=1e-4)


def _model(tp=1, moe="none", **over):
    cfg = TransformerConfig(
        **CFG, moe=moe, moe_layers=(1,) if moe != "none" else (),
        num_experts=8, topk=2, **over)
    model = Transformer(
        cfg, Mesh(np.asarray(jax.devices()[:tp]), ("tp",)), "tp", ())
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, s),
        model.init(jax.random.PRNGKey(0)), model.shardings())
    return model, params


def _prompts(*lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab"], (n,)).astype(np.int32)
            for n in lens]


@pytest.mark.parametrize("moe", ["none", "ep", "tp"])
def test_chunked_prefill_logits_match_forward(moe):
    """A prompt fed in chunks of 8 through the step's three MLP kinds
    (dense, EP experts, the gathered-expert branch) gives ``forward``'s
    logits at EVERY position: one forward pass equals the chunks."""
    model, params = _model(tp=2, moe=moe)
    eng, (req,), (got,) = serve_all_logits(
        model, params, ENGINE, _prompts(21))
    assert len(eng.stats.step_tokens) == 3          # 8 + 8 + 5
    np.testing.assert_allclose(
        got, forward_logits(model, params, req.prompt), **TOL)


def test_ragged_rows_match_forward():
    """Rows of different lengths packed into ONE step: each row's
    logits equal ``forward`` on that row alone."""
    model, params = _model()
    eng, reqs, got = serve_all_logits(
        model, params, ENGINE, _prompts(5, 8, 3))
    assert eng.stats.step_tokens[0] == 16           # all three, one step
    for req, rows in zip(reqs, got):
        np.testing.assert_allclose(
            rows, forward_logits(model, params, req.prompt), **TOL)


@pytest.mark.parametrize("kv_quant", [None, "int8"])
def test_decode_rows_match_forward(kv_quant):
    """Token-by-token decode over the pages, across two page
    boundaries, equals ``forward`` over the whole sequence — exactly
    for float pools, within int8-KV tolerance (and not exactly: the
    quantized pool must have engaged) for int8 pools."""
    model, params = _model(kv_quant=kv_quant)
    _, reqs, got = serve_all_logits(
        model, params, ENGINE, _prompts(5, 6), max_new=14)
    for req, rows in zip(reqs, got):
        want = forward_logits(model, params, req.seq[:-1])
        if kv_quant is None:
            np.testing.assert_allclose(rows, want, **TOL)
            continue
        err = np.abs(rows - want).max()
        assert 0 < err < 0.05 * np.abs(want).max()


def test_row_past_table_capacity_is_refused():
    """A sequence longer than one slot's table can address is refused
    at admission, by name — not served with its tail dropped."""
    model, params = _model()
    ecfg = EngineConfig(slots=2, token_budget=32, chunk=8, page=8, npages=4)
    eng = ServingEngine(model, params, ecfg, use_pallas=False)
    assert eng.state.capacity == 32
    eng.submit(Request(rid=0, prompt=_prompts(40)[0], max_new=1,
                       arrival=0.0))
    with pytest.raises(ValueError, match="exceeds slot capacity 32"):
        eng.step()


def test_tp_step_matches_one_chip():
    """Head-sharded pools, column/row-sharded projections and experts
    over four chips give the one-chip step's logits."""
    model4, params4 = _model(tp=4, moe="ep")
    model1, _ = _model(tp=1, moe="ep")
    params1 = jax.tree.map(
        lambda p, s: jax.device_put(np.asarray(p), s),
        params4, model1.shardings())
    prompts = _prompts(13, 6)
    _, _, got4 = serve_all_logits(model4, params4, ENGINE, prompts,
                                  max_new=3)
    _, _, got1 = serve_all_logits(model1, params1, ENGINE, prompts,
                                  max_new=3)
    for a, b in zip(got4, got1):
        np.testing.assert_allclose(a, b, **TOL)


@pytest.mark.parametrize("moe", ["none", "ep"])
def test_engine_greedy_matches_forward_oracle(moe):
    """Many steps, chunked prefill beside decode rows, more requests
    than slots: every request's tokens are the forward oracle's."""
    model, params = _model(tp=2, moe=moe)
    eng = ServingEngine(model, params, ENGINE, use_pallas=False)
    reqs = [Request(rid=i, prompt=p, max_new=4 + i % 3, arrival=0.5 * i)
            for i, p in enumerate(_prompts(19, 4, 11, 26, 7, 9))]
    stats = eng.run(reqs)
    assert stats.completed == len(reqs) and len(stats.step_tokens) > 8
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid


def test_engine_threads_moe_state_across_steps(monkeypatch):
    """The barrier-free LL workspaces ride the engine from step to
    step (donated, returned, threaded): the parity rolls once a step
    and the tokens are the forward oracle's."""
    monkeypatch.setattr(Transformer, "_moe_ep_ctx", force_fused_ctx())
    model, params = _model(tp=2, moe="ep")
    eng = ServingEngine(model, params, ENGINE, use_pallas=False,
                        propagate_failures=True)
    state = eng.moe_state[eng._t_pad]          # ENGINE: one width
    assert list(eng.moe_state) == [eng._t_pad] and state[1] is not None
    reqs = [Request(rid=i, prompt=p, max_new=3, arrival=0.0)
            for i, p in enumerate(_prompts(10, 5))]
    stats = eng.run(reqs)
    steps = len(stats.step_tokens)
    assert stats.completed == 2 and steps >= 4
    assert int(np.asarray(eng.moe_state[eng._t_pad][1].parity)[0]) \
        == steps % 2
    monkeypatch.undo()                  # the oracle routes by forward
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid
