"""PR 41's mixer on the serving path, at CPU sizes: gated delta-rule
linear attention (KDA) layers with a state of two parts a slot (the
matrix and the convolution's tail) beside a gated NoPE GQA layer and an
expert share on every layer.

The oracle is the benchmark's plain reference of the architecture
(``benchmark/models/solar_open2.py``: float32, the recurrence token by
token, no cache, no kernel, nothing of the program) on the benchmark's
own seeded weights. The twin is one period ``attention, kda, kda, kda``
with four KDA heads of 16, rank 8, the published 4 taps and beta up to
2, 8 experts (top-2) and a shared expert on every layer.
"""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import weights  # noqa: E402
from benchmark.models import solar_open2 as ref  # noqa: E402
from conftest import serve_all_logits  # noqa: E402
from triton_distributed_tpu.kernels.kda_attention import (  # noqa: E402
    kda_attention,
    kda_attention_xla,
)
from triton_distributed_tpu.models import Transformer, presets  # noqa: E402
from triton_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
)
from triton_distributed_tpu.serving import (  # noqa: E402
    DisaggregatedEngine,
    EngineConfig,
    ServingEngine,
    SpeculativeEngine,
)
from triton_distributed_tpu.serving.engine import state_kinds  # noqa: E402

pytestmark = pytest.mark.fast

KINDS = ("attention", "kda", "kda", "kda")
SIZE_KEYS = (
    "vocab", "n_layers", "hidden", "ffn", "n_heads", "n_kv_heads",
    "head_dim", "layer_mixer", "kda_heads", "kda_conv", "kda_rank",
    "kda_beta_scale", "num_experts", "experts_held", "first_expert_held",
    "topk", "shared_experts", "routed_scale", "norm_eps")
#: chunk 16 over pages of 16: prompts of 70 and 45 tokens cross four
#: and two chunk boundaries, and their tails are shorter than a chunk
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=16, npages=64)
PROMPTS = (70, 9, 45, 23, 3)


def tiny_config(**over):
    kw = dict(n_layers=4, layer_mixer=KINDS, moe_layers=(0, 1, 2, 3),
              n_heads=8, n_kv_heads=2, vocab=96)
    kw.update(over)
    return presets.tiny(presets.solar_open2(n_layers=4), **kw)


def sizes_of(cfg) -> dict:
    out = {}
    for k in SIZE_KEYS:
        v = getattr(cfg, k)
        out[k] = list(v) if isinstance(v, tuple) else v
    out["experts_held"] = cfg.local_experts
    return out


def one_chip_model(cfg):
    return Transformer(cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)),
                       tp_axis="x")


def seeded(cfg, seed=7):
    model = one_chip_model(cfg)
    sizes = sizes_of(cfg)
    params = weights.make_params(
        ref.param_plan(sizes), seed, cfg.param_dtype, model.shardings())
    return model, sizes, params


def prompts_of(lengths, vocab=96, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in lengths]


def reference_rows(params, sizes, req):
    seq = np.concatenate([req.prompt,
                          np.asarray(req.generated[:-1], np.int32)])
    return np.asarray(ref.logits_at(params, sizes, seq, np.arange(len(seq))))


# ------------------------------------------------- (a) engine == reference


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_twins", "kernels_interpreted"])
def test_engine_through_matrix_and_tail_equals_the_reference(use_pallas):
    """Chunked prefill across several chunk boundaries, then decode,
    five requests of different lengths through four slots in packed
    steps (so requests share steps and a slot is used again): the
    logits at EVERY position equal the reference's full forward.
    Float32 both sides, so the tolerance is accumulation order only."""
    model, sizes, params = seeded(tiny_config())
    eng, reqs, logits = serve_all_logits(
        model, params, ENGINE, prompts_of(PROMPTS), max_new=6,
        use_pallas=use_pallas)
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req), atol=1e-4, rtol=1e-4)
    st = eng.stats
    assert st.kda_rows > st.kda_chunk_rows > 0
    assert st.state_rows == st.kda_rows
    assert st.moe_local_steps > 0
    assert eng._step_jit()._cache_size() <= len(eng._rungs()) + 1


def test_a_reused_slot_starts_from_zero_matrix_and_zero_tail():
    """One slot, two requests one after the other: the second finds the
    first's matrix, tail and pages in its slot and serves the
    reference's logits all the same."""
    model, sizes, params = seeded(tiny_config())
    ecfg = EngineConfig(slots=1, token_budget=32, chunk=16, page=16,
                        npages=8)
    eng, reqs, logits = serve_all_logits(
        model, params, ecfg, prompts_of((50, 37)), max_new=4)
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req), atol=1e-4, rtol=1e-4)


def test_param_plan_is_the_programs_init_tree():
    cfg = tiny_config()
    model = one_chip_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        ref.param_plan(sizes_of(cfg)), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert jax.tree.structure(model.shardings()) == jax.tree.structure(want)


def test_the_preset_states_the_published_model_and_its_cut():
    whole = presets.solar_open2()
    assert whole.n_layers == 48 and len(whole.kda_layers) == 36
    assert tuple(i for i in range(48) if i not in whole.kda_layers) == \
        tuple(range(0, 48, 4))
    assert whole.moe_layers == tuple(range(48)) and not whole.rope_layers
    cut = presets.solar_open2(n_layers=4, experts_held=40, vocab=24576)
    assert cut.layer_mixer == KINDS and cut.local_experts == 40
    assert (cut.hidden, cut.ffn, cut.n_heads, cut.n_kv_heads, cut.head_dim,
            cut.kda_heads, cut.kda_conv, cut.kda_rank, cut.kda_beta_scale,
            cut.num_experts, cut.topk, cut.shared_experts) == (
        4096, 1280, 64, 8, 128, 64, 4, 128, 2.0, 320, 8, 1)
    tiny = tiny_config()
    assert (tiny.kda_heads, tiny.kda_conv, tiny.kda_rank,
            tiny.kda_beta_scale) == (4, 4, 8, 2.0)
    state = one_chip_model(tiny).init_serving_state(3, 16, 16)
    assert state.layers[1] is None and state.recurrent[0] is None
    matrix, tail = state.recurrent[2]
    assert matrix.shape == (3, 4, 16, 16) and matrix.dtype == jnp.float32
    assert tail.shape == (3, 3, 3 * 4 * 16) and tail.dtype == jnp.float32


# ------------------------------ (b) kernel and twin == the recurrence


def _naive_recurrence(q, k, v, g, beta, state, kv_lens, q_lens, q_starts):
    """The delta rule a token at a time in float64."""
    h, t, d = q.shape
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    o = np.zeros((h, t, d))
    new = np.asarray(state, np.float64).copy()
    for r in range(len(q_lens)):
        n = int(q_lens[r])
        if not n:
            continue
        for hh in range(h):
            s = new[r, hh] if int(kv_lens[r]) > n else np.zeros((d, d))
            for i in range(int(q_starts[r]), int(q_starts[r]) + n):
                s = np.exp(g[hh, i])[:, None] * s
                u = beta[hh, i] * (v[hh, i] - s.T @ k[hh, i])
                s = s + np.outer(k[hh, i], u)
                o[hh, i] = s.T @ q[hh, i] / np.sqrt(d)
            new[r, hh] = s
    return o, new


def _inputs(seed, h, t, d, r, *, g_scale=1.5, beta_shift=0.0):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = jnp.asarray(unit(rng.normal(size=(h, t, d))), jnp.float32)
    k = jnp.asarray(unit(rng.normal(size=(h, t, d))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(h, t, d)), jnp.float32)
    g = jnp.asarray(-np.exp(rng.normal(size=(h, t, d)) * g_scale),
                    jnp.float32)
    beta = jnp.asarray(
        2.0 / (1.0 + np.exp(-(2.0 * rng.normal(size=(h, t)) + beta_shift))),
        jnp.float32)
    state = jnp.asarray(rng.normal(size=(r, h, d, d)), jnp.float32)
    return q, k, v, g, beta, state


def _check(mix, args, kv_lens, q_lens, q_starts, block_q):
    q, k, v, g, beta, state = args
    lens = [jnp.asarray(a, jnp.int32) for a in (kv_lens, q_lens, q_starts)]
    o, new = mix(q, k, v, g, beta, state, *lens, block_q=block_q)
    want_o, want_s = _naive_recurrence(q, k, v, g, beta, state, *lens)
    for rr in range(len(q_lens)):
        span = slice(q_starts[rr], q_starts[rr] + q_lens[rr])
        np.testing.assert_allclose(np.asarray(o)[:, span], want_o[:, span],
                                   atol=1e-4, rtol=1e-4)
        if not q_lens[rr]:
            np.testing.assert_array_equal(np.asarray(new)[rr],
                                          np.asarray(state)[rr])
    np.testing.assert_allclose(np.asarray(new), want_s, atol=1e-4, rtol=1e-4)


MIXES = pytest.mark.parametrize(
    "mix", [kda_attention_xla, kda_attention],
    ids=["xla_twin", "kernel_interpreted"])

#: (kv_lens, q_lens, q_starts, block_q) over five rows of 96 packed tokens
BATCHES = {
    # every row one token, one of them its sequence's first
    "decode_rows": ([9, 1, 40, 0, 77], [1, 1, 1, 0, 1],
                    [0, 8, 16, 88, 24], 8),
    # whole chunks, one from position 0, one that is not a multiple of
    # the sub-chunk
    "chunks": ([32, 0, 64, 19, 0], [32, 0, 32, 19, 0],
               [0, 88, 32, 64, 88], 32),
    # a ragged mix: a prompt's tail of 5, an idle slot, a decode row, a
    # chunk that continues a sequence, a chunk from position 0
    "ragged": ([5, 0, 9, 72, 19], [5, 0, 1, 32, 19],
               [0, 64, 8, 16, 48], 32),
    # a slot that held a state is reused from position 0 (rows 0 and
    # 3: the state they find must not be read)
    "reused_from_zero": ([3, 0, 0, 17, 0], [3, 0, 0, 17, 0],
                         [0, 88, 88, 8, 88], 32),
}


@MIXES
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_kernel_and_twin_are_the_token_by_token_recurrence(mix, batch):
    """Outputs and states equal the delta rule run a token at a time in
    float64, on decays a channel between ``e^-0.05`` and ``e^-30`` a
    token; a slot that is not batched keeps its matrix bit for bit."""
    kv_lens, q_lens, q_starts, block_q = BATCHES[batch]
    _check(mix, _inputs(0, 4, 96, 16, 5), kv_lens, q_lens, q_starts,
           block_q)


@MIXES
@pytest.mark.parametrize("extreme", ["decay_near_0", "beta_near_2",
                                     "no_decay_beta_near_2"])
def test_the_chunk_form_holds_at_the_ends_of_decay_and_beta(mix, extreme):
    """``alpha`` near 0 (``g`` down to ``-e^6`` a token: ``exp(-G)``
    would leave float32 inside one sub-chunk), ``beta`` within 1e-3 of 2
    (eigenvalues at -1), and both ends at once with no decay at all."""
    kw = {"decay_near_0": dict(g_scale=3.0),
          "beta_near_2": dict(beta_shift=8.0),
          "no_decay_beta_near_2": dict(g_scale=0.0, beta_shift=8.0)}
    args = list(_inputs(3, 4, 96, 16, 5, **kw[extreme]))
    if extreme == "no_decay_beta_near_2":
        args[3] = args[3] * 1e-6
    kv_lens, q_lens, q_starts, block_q = BATCHES["ragged"]
    _check(mix, args, kv_lens, q_lens, q_starts, block_q)


# ------------------------- (c) a chunk split anywhere: the tail carried


@pytest.mark.parametrize("cut", range(1, 12))
def test_a_span_split_at_any_offset_is_the_whole_span(cut):
    """One request's 12 tokens through one KDA layer as ONE span and as
    two spans cut at every offset (the second starting from the matrix
    AND the tail the first left): same outputs, same matrix, same
    tail; by the XLA twin, float32."""
    cfg = tiny_config(n_layers=2, layer_mixer=("attention", "kda"),
                      moe_layers=(0, 1))
    model, sizes, params = seeded(cfg)
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(cut), (32, cfg.hidden))
    state = model.init_serving_state(2, 16, 16)

    def span(state, start, n, at):
        """Tokens [start, start + n) of the request in slot 1, packed
        from offset ``at``."""
        q_lens = jnp.asarray([0, n], jnp.int32)
        q_starts = jnp.asarray([24, at], jnp.int32)
        st = state.replace(kv_lens=jnp.asarray([0, start + n], jnp.int32))
        xn = jnp.zeros((32, cfg.hidden)).at[at:at + n].set(x[start:start + n])
        qkv = xn @ blk["wqkv"]
        q, k, v, g, beta, tail = model._kda_inputs(
            blk, xn, qkv, st, 1, q_lens, q_starts)
        o, matrix = model._kda_mix(q, k, v, g, beta, st, 1, q_lens,
                                   q_starts, 16, False)
        rec = list(st.recurrent)
        rec[1] = (matrix, tail)
        return o[at:at + n], st.replace(recurrent=tuple(rec))

    whole, after = span(state, 0, 12, 8)
    first, mid = span(state, 0, cut, 0)
    second, split = span(mid, cut, 12 - cut, 8)
    np.testing.assert_allclose(
        np.concatenate([first, second]), whole, atol=1e-4, rtol=1e-4)
    for a, b in zip(split.recurrent[1], after.recurrent[1]):
        np.testing.assert_allclose(a[1], b[1], atol=1e-4, rtol=1e-4)
        # the idle slot's parts stay as they were
        np.testing.assert_array_equal(a[0], np.zeros_like(a[0]))
    # and the reference's own inputs from the same rows and tail
    rq, rk, rv, rg, rb, rtail = ref.kda_inputs(
        blk, x[:12], jnp.zeros((3, 3 * 4 * 16)), sizes)
    np.testing.assert_allclose(after.recurrent[1][1][1], rtail,
                               atol=1e-5, rtol=1e-5)
    _, ro = ref.kda_tokens(jnp.zeros((4, 16, 16)), rq, rk, rv, rg, rb)
    np.testing.assert_allclose(whole, ro.reshape(12, -1), atol=1e-4,
                               rtol=1e-4)


def test_the_references_blocked_evaluation_is_its_one_shot_evaluation(
        monkeypatch):
    """The reference in blocks of rows (state and tail carried from one
    to the next) and of queries gives what one block gives."""
    cfg = tiny_config()
    _, sizes, params = seeded(cfg)
    tokens = prompts_of([96])[0]
    rows = np.arange(96)
    whole = ref.logits_at(params, sizes, tokens, rows)
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    ref._logits.clear_cache()
    try:
        blocked = ref.logits_at(params, sizes, tokens, rows)
    finally:
        ref._logits.clear_cache()
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------- (d) the share


def test_eight_shares_of_two_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts, routes over all 16 and
    computes its own experts' part; the parts, with the shared expert
    counted ONCE, add up to the uncut reference's sparse layer, on a
    KDA layer's block, for the program's layer and the reference's."""
    whole = tiny_config(num_experts=16, topk=4)
    sizes = sizes_of(whole)
    params = weights.make_params(ref.param_plan(sizes), 7, jnp.float32)
    blk = params["blocks"][2]
    xn = jax.random.normal(jax.random.PRNGKey(3), (24, whole.hidden))
    shared = ref._gated(xn, blk["shared_up"], blk["shared_down"], None)
    uncut = ref.share_of_layer(blk, xn, sizes) + shared

    got_ref, got_prog = shared, shared
    for chip in range(8):
        cut = dataclasses.replace(
            whole, experts_held=2, first_expert_held=2 * chip)
        mine = dict(blk, moe_up=blk["moe_up"][2 * chip:2 * chip + 2],
                    moe_down=blk["moe_down"][2 * chip:2 * chip + 2])
        got_ref = got_ref + ref.share_of_layer(mine, xn, sizes_of(cut))
        y, _ = one_chip_model(cut)._decode_moe_ep(mine, xn)
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, uncut, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_prog, uncut, atol=1e-4, rtol=0)


# ------------------------------------------------------ (e) refusals


def _refusals():
    base = tiny_config()
    mesh1 = lambda: Mesh(np.asarray(jax.devices()[:1]), ("x",))  # noqa: E731
    mesh2 = lambda: Mesh(np.asarray(jax.devices()[:2]), ("x",))  # noqa: E731
    small = dict(slots=2, token_budget=32, chunk=16, page=16, npages=16)

    def engine(**kw):
        model, _, params = seeded(base)
        return ServingEngine(model, params, EngineConfig(**small, **kw))

    def speculative():
        model, _, params = seeded(base)
        return SpeculativeEngine(model, params, EngineConfig(**small),
                                 spec_k=2)

    def disaggregated():
        model, _, params = seeded(base)
        return DisaggregatedEngine(model, params, model, params,
                                   EngineConfig(**small))

    kda = "kda layers"
    return {
        "prefix_cache": (lambda: engine(prefix_cache=True),
                         f"{kda}.*prefix_cache / prefix_share"),
        "prefix_share": (
            lambda: engine(prefix_cache=True, prefix_share=True),
            f"{kda}.*prefix_cache / prefix_share"),
        "speculative": (speculative, f"{kda}.*SpeculativeEngine"),
        "prefill_only": (lambda: engine(prefill_only=True),
                         f"{kda}.*prefill_only"),
        "disaggregated": (disaggregated, f"DisaggregatedEngine.*{kda}"),
        "kv_ship": (lambda: engine().gather_pages([0]),
                    f"kv_ship / page migration.*{kda}"),
        "tp": (lambda: Transformer(
            dataclasses.replace(base, experts_held=0), mesh2(),
            tp_axis="x"), f"{kda}.*tp=2"),
        "cp": (lambda: Transformer(
            base, Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                       ("x", "c")), tp_axis="x", cp_axis="c"),
            f"{kda}.*cp=2"),
        "kv_quant": (lambda: tiny_config(kv_quant="int8"), "kv_quant"),
        "sliding_window": (
            lambda: tiny_config(
                layer_attn=("sliding", "full", "full", "full"), window=8),
            "sliding-window layers"),
        "lightning_beside": (
            lambda: tiny_config(
                layer_mixer=("attention", "kda", "lightning", "kda"),
                lightning_heads=4),
            "kda layers .* with lightning layers"),
        "dense_weight_quant": (
            lambda: tiny_config(dense_weight_quant="int8"),
            "dense_weight_quant"),
        "qk_norm": (lambda: tiny_config(qk_norm=True),
                    "kda layers .* with qk_norm"),
        "rope_on_kda": (
            lambda: tiny_config(rope_theta=1e4, rope_layers=(0, 1)),
            "rope_layers on a kda layer"),
        "forward": (
            lambda: Transformer(base, mesh1(), tp_axis="x").forward(
                None, jnp.zeros((1, 8), jnp.int32)),
            "layer_mixer, out_gate, out_norm"),
        "kda_heads": (
            lambda: TransformerConfig(n_layers=2, layer_mixer=(
                "attention", "kda")), "kda_heads >= 1"),
        "taps": (
            lambda: tiny_config(kda_conv=1), "kda_conv >= 2 taps"),
        "beta_scale": (
            lambda: tiny_config(kda_beta_scale=2.5), "kda_beta_scale <= 2"),
        "fields_without_layer": (
            lambda: TransformerConfig(kda_heads=4),
            "without a 'kda' layer"),
        "mixer_kind": (
            lambda: TransformerConfig(n_layers=2, layer_mixer=(
                "attention", "mamba")), "'attention', 'lightning' or 'kda'"),
        "block_q": (
            lambda: kda_attention(
                *_inputs(0, 4, 48, 16, 2), *(jnp.zeros((2,), jnp.int32),) * 3,
                block_q=24), "multiple of the sub-chunk"),
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_the_two_part_state_cannot_serve_is_refused_by_name(what):
    build, match = _refusals()[what]
    with pytest.raises(ValueError, match=match):
        build()


def test_state_kinds_names_the_kda_layers():
    kinds = state_kinds(tiny_config())
    assert list(kinds) == ["recurrent"]
    assert "kda layers" in kinds["recurrent"]
    assert "convolution tail" in kinds["recurrent"]
    assert state_kinds(presets.tiny(presets.mixtral_8x7b())) == {}
