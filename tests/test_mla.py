"""PR 35's latent attention (MLA) and group-limited router on the
serving path, at CPU sizes: a latent pool (ONE entry a token and layer
for every head), the absorbed walk of the ragged kernel, the one-pool
append, YaRN frequencies, and the choice of experts inside the best
groups.

The oracle is the benchmark's plain reference of the architecture
(``benchmark/models/dots_vlm1.py``: float32, EXPANDED, no cache, no
kernel, nothing of the program) on the benchmark's own seeded weights.
The twin is 1 dense + 2 sparse layers, 4 heads, ranks 24 / 16, q.k head
8 + 4, value head 8, 16 experts in 4 groups (top-2 groups, top-4), YaRN
x 4 over 16 positions.
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
from benchmark.models import dots_vlm1 as ref  # noqa: E402
from conftest import serve_all_logits  # noqa: E402
from triton_distributed_tpu.kernels import moe_utils as mu  # noqa: E402
from triton_distributed_tpu.kernels.kv_append import (  # noqa: E402
    _build_append,
)
from triton_distributed_tpu.kernels.ragged_paged_attention import (  # noqa: E402
    LATENT_TQ,
    _build_ragged,
    ragged_paged_attention,
    ragged_paged_attention_xla,
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
from triton_distributed_tpu.serving.engine import (  # noqa: E402
    REFUSED,
    state_kinds,
)

pytestmark = pytest.mark.fast

SIZE_KEYS = (
    "vocab", "n_layers", "hidden", "ffn", "dense_ffn", "n_heads",
    "n_kv_heads", "head_dim", "kv_latent", "q_latent", "qk_nope_dim",
    "qk_rope_dim", "v_head_dim", "rope_theta", "rope_yarn_factor",
    "rope_yarn_original", "rope_yarn_beta_fast", "rope_yarn_beta_slow",
    "rope_mscale_all_dim", "num_experts", "experts_held",
    "first_expert_held", "topk", "moe_layers", "shared_experts", "router",
    "routed_scale", "router_groups", "router_topk_groups", "norm_eps",
    "gated_ffn")
#: chunk 16 over pages of 16: prompts of 70 and 100 tokens cross five
#: and seven chunk boundaries, and every prompt but the 9-token one
#: the YaRN original length of 16
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=16, npages=64)
PROMPTS = (70, 9, 40, 100, 23)


def tiny_config(**over):
    """The twin: experts 4-7 of 16 are held here (a share of 4)."""
    kw = dict(n_layers=3, vocab=96, moe_layers=(1, 2), num_experts=16,
              topk=4, experts_held=4, first_expert_held=4)
    kw.update(over)
    return presets.tiny(
        presets.dots_vlm1(n_layers=3, n_dense_layers=1), **kw)


def sizes_of(cfg) -> dict:
    out = {}
    for k in SIZE_KEYS:
        v = getattr(cfg, k)
        out[k] = list(v) if isinstance(v, tuple) else v
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
def test_engine_through_the_latent_pool_equals_the_reference(use_pallas):
    """Chunked prefill across several chunk boundaries, then decode,
    five requests of different lengths through four slots in packed
    steps, positions on both sides of the YaRN original length: the
    logits at EVERY position equal the reference's full (expanded)
    forward. Float32 both sides, so the tolerance is accumulation order
    and the absorbed products' (1e-4 against logits of size ~4;
    measured 8e-6)."""
    model, sizes, params = seeded(tiny_config())
    eng, reqs, logits = serve_all_logits(
        model, params, ENGINE, prompts_of(PROMPTS), max_new=6,
        use_pallas=use_pallas)
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req), atol=1e-4, rtol=1e-4)
    st = eng.stats
    assert st.latent_rows > 0 and st.chunk_rows_expanded == 0
    # a decode row fetches its pages once, a chunk row once a query
    # block: more than one walk of every page held, never fewer
    assert st.latent_pages_walked >= st.global_pages_walked > 0
    assert st.append_runs > 0 if use_pallas else st.append_scatter_steps > 0
    # two rungs (decode-only and the cap), one program each (+ 1: the
    # first step sees the pools as init_serving_state placed them)
    assert eng._rungs() == [8, 16]
    assert eng._step_jit()._cache_size() <= len(eng._rungs()) + 1


def test_param_plan_is_the_programs_init_tree():
    cfg = tiny_config()
    want = jax.eval_shape(one_chip_model(cfg).init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        ref.param_plan(sizes_of(cfg)), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert a.shape == b.shape and a.dtype == b.dtype


# ------------------------------------------- (b) absorbed == expanded


def _one_row_step(n, page=16):
    """One request's first ``n`` positions as one packed step."""
    pps = -(-n // page)
    return dict(
        kv_lens=jnp.asarray([n], jnp.int32),
        q_lens=jnp.asarray([n], jnp.int32),
        q_starts=jnp.asarray([0], jnp.int32),
        table=jnp.arange(pps, dtype=jnp.int32)[None, ::-1],  # not identity
        npages=pps, page=page)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_twin", "kernel_interpreted"])
def test_absorbed_attention_equals_the_expanded_layer(use_pallas):
    """ONE latent layer on the same normed rows: the program's absorbed
    form (W_kvb's key part folded into the query, the walk over the
    cached [c_kv | k_pe] entries, W_kvb's value part after it, W_o)
    equals the reference's expanded layer, which up-projects every
    head's keys and values and caches nothing."""
    model, sizes, params = seeded(tiny_config())
    c, blk = model.config, params["blocks"][0]
    n = 40                                  # 5 query blocks of 8 tokens
    a = jax.random.normal(jax.random.PRNGKey(5), (n, c.hidden))
    want = ref._mla(blk, a, sizes, None)

    step = _one_row_step(n)
    qf, entry = model._latent_qkv(
        blk, a, model._rope_tables(jnp.arange(n)))
    assert qf.shape == (n, c.n_heads * c.latent_stored)
    assert entry.shape == (n, 1, c.latent_stored)
    # the stored entry is the needed one and a zero tail
    assert not np.asarray(entry[..., c.latent_width:]).any()
    pool = jnp.zeros((step["npages"], 1, step["page"], c.latent_stored))
    rows = (step["table"][0][jnp.arange(n) // 16] * 16 + jnp.arange(n) % 16)
    pool = pool.reshape(-1, c.latent_stored).at[rows].set(
        entry[:, 0]).reshape(pool.shape)
    attend = ragged_paged_attention if use_pallas \
        else ragged_paged_attention_xla
    kw = dict(block_q=8, with_lse=False) if use_pallas else {}
    o, _ = attend(
        qf.reshape(1, -1, c.latent_stored), pool, None, step["kv_lens"],
        step["q_lens"], step["q_starts"], step["table"], group=c.n_heads,
        scale=c.latent_softmax_scale, latent=(c.kv_latent, c.qk_rope_dim),
        **kw)
    assert o.shape == (1, n * c.n_heads, c.kv_latent)
    got = model._latent_out(blk, o[0]) @ blk["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_the_latent_kernel_equals_its_twin_on_a_mixed_step():
    """Decode rows, a chunk row longer than a query block and one that
    is not a whole number of blocks, an idle row, in one launch: the
    kernel's rows equal the XLA twin's inside every row's span."""
    g, dl, dr, dp, page = 8, 16, 4, 128, 16
    lens, takes = (37, 1, 64, 0, 19), (1, 1, 21, 0, 19)
    starts = (0, 8, 16, 40, 40)
    r, pps = len(lens), 5
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(key[0], (r * pps, 1, page, dp))
    pool = pool.at[..., dl + dr:].set(0.0)
    q = jax.random.normal(key[1], (1, 64 * g, dp))
    table = jax.random.permutation(key[2], r * pps).reshape(r, pps)
    args = (q, pool, None, jnp.asarray(lens), jnp.asarray(takes),
            jnp.asarray(starts), table.astype(jnp.int32))
    kw = dict(group=g, scale=0.3, latent=(dl, dr))
    got, _ = ragged_paged_attention(*args, block_q=8, with_lse=False, **kw)
    want, _ = ragged_paged_attention_xla(*args, **kw)
    assert max(takes) > 2 * LATENT_TQ and max(takes) % LATENT_TQ
    for s, t in zip(starts, takes):
        span = slice(s * g, (s + t) * g)
        np.testing.assert_allclose(got[0, span], want[0, span],
                                   atol=2e-5, rtol=1e-5)


# ------------------------------------------------ (c) what the pool holds


def test_the_pool_holds_one_entry_a_token_and_a_reused_slot_reads_its_own():
    """A layer's pool is ONE array, ``(npages, 1, page, latent_stored)``
    with no V beside it; after a request of n tokens exactly n of its
    rows are written, each the needed ``kv_latent + qk_rope_dim`` values
    and a zero tail. A second request through the same (only) slot
    reads none of the first's entries: its logits are those of the
    reference on its own sequence."""
    cfg = tiny_config()
    model, sizes, params = seeded(cfg)
    ecfg = EngineConfig(slots=1, token_budget=32, chunk=16, page=16,
                        npages=8)
    first, second = prompts_of((50, 21), seed=3)
    eng, (req,), _ = serve_all_logits(model, params, ecfg, [first],
                                      max_new=4)
    assert cfg.latent_width == 20 and cfg.latent_stored == 128
    written = len(first) + 4 - 1          # the last token is never fed
    for pool, v in eng.state.layers:
        assert v is None
        assert pool.shape == (8, 1, 16, cfg.latent_stored)
        rows = np.asarray(pool).reshape(-1, cfg.latent_stored)
        assert int(np.any(rows != 0, axis=1).sum()) == written
        assert not rows[:, cfg.latent_width:].any()
    assert eng.state.recurrent == () and eng.state.ckeys == ()

    # the same engine object cannot be re-run by the helper: serve both
    # through ONE slot, one after the other, and read the second's rows
    eng2, reqs, logits = serve_all_logits(
        model, params, ecfg, [first, second], max_new=4)
    assert {r.slot for r in reqs} <= {0, None}
    np.testing.assert_allclose(
        logits[1], reference_rows(params, sizes, reqs[1]),
        atol=1e-4, rtol=1e-4)


# ------------------------------------------------------- (d) the router


def _route_sizes(**over):
    return dict(dict(router_groups=4, router_topk_groups=2, topk=4), **over)


def test_the_router_keeps_groups_then_experts_as_the_reference_does():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    w, ids = mu.select_experts_sigmoid_bias(
        logits, bias, 4, scale=2.5, groups=4, topk_groups=2)
    ids = np.asarray(ids)
    # exactly topk distinct experts, in exactly topk_groups groups or
    # fewer (all four may lie in one kept group), none outside a kept
    # group as the reference keeps them
    s = jax.nn.sigmoid(logits)
    ref_ids, kept = ref.choose(s, bias, _route_sizes())
    np.testing.assert_array_equal(ids, np.asarray(ref_ids))
    kept = np.asarray(kept)
    assert kept.shape == (64, 2)
    for row, groups in zip(ids, kept):
        assert len(set(row)) == 4
        assert set(row // 4) <= set(groups) and len(set(groups)) == 2
    # the group limit binds: somewhere the unlimited top-4 differs
    _, free = mu.select_experts_sigmoid_bias(logits, bias, 4, scale=2.5)
    assert (np.sort(ids, 1) != np.sort(np.asarray(free), 1)).any()
    # weights: the chosen experts' own scores, renormalised, x scale
    mine = jnp.take_along_axis(s, jnp.asarray(ids), 1)
    np.testing.assert_allclose(
        w, 2.5 * mine / jnp.sum(mine, 1, keepdims=True), rtol=1e-6)
    # and the reference's gate over all experts is the same assignment
    gate = ref.route({"router": jnp.eye(16), "router_bias": bias}, logits,
                     dict(_route_sizes(), routed_scale=2.5))
    np.testing.assert_allclose(
        jnp.take_along_axis(gate, jnp.asarray(ids), 1), w, rtol=1e-5)
    assert int((np.asarray(gate) > 0).sum()) == 64 * 4


def test_the_bias_moves_the_choice_and_not_the_weights():
    logits = jax.random.normal(jax.random.PRNGKey(2), (32, 16))
    # a bias that lifts group 3's two best over every other group's
    bias = jnp.zeros((16,)).at[12:14].set(10.0)
    w, ids = mu.select_experts_sigmoid_bias(
        logits, bias, 4, scale=1.0, groups=4, topk_groups=2)
    ids = np.asarray(ids)
    assert ((ids == 12).any(1) & (ids == 13).any(1)).all()
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), jnp.asarray(ids), 1)
    np.testing.assert_allclose(w, s / jnp.sum(s, 1, keepdims=True),
                               rtol=1e-6)
    assert float(jnp.max(w)) < 1.0          # no 10 in any weight


def test_ties_go_to_the_lower_group_and_the_lower_expert():
    logits = jnp.zeros((3, 16))             # every score 0.5
    _, ids = mu.select_experts_sigmoid_bias(
        logits, jnp.zeros((16,)), 4, groups=4, topk_groups=2)
    np.testing.assert_array_equal(ids, np.tile([0, 1, 2, 3], (3, 1)))
    # with groups 0 and 1 kept and group 0 holding three ones
    logits = jnp.zeros((1, 16)).at[0, jnp.asarray([1, 2, 3, 6])].set(1.0)
    _, ids = mu.select_experts_sigmoid_bias(
        logits, jnp.zeros((16,)), 4, groups=4, topk_groups=2)
    np.testing.assert_array_equal(ids, [[1, 2, 3, 6]])
    ref_ids, kept = ref.choose(jax.nn.sigmoid(logits), jnp.zeros((16,)),
                               _route_sizes())
    np.testing.assert_array_equal(ref_ids, ids)
    np.testing.assert_array_equal(kept, [[0, 1]])


def test_one_group_is_the_router_that_was_there():
    """``groups=1`` is PR 29's router: the same jaxpr as the call
    without the arguments, and the same choice."""
    logits = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    old = jax.make_jaxpr(lambda a, b: mu.select_experts_sigmoid_bias(
        a, b, 2, scale=2.5))(logits, bias)
    new = jax.make_jaxpr(lambda a, b: mu.select_experts_sigmoid_bias(
        a, b, 2, scale=2.5, groups=1, topk_groups=1))(logits, bias)
    assert str(old) == str(new)
    assert "top_k" in str(old) and str(old).count("top_k") == 1


# --------------------------------------------------- (e) the shares add up


def test_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Each of 4 chips holds 4 of 16 experts (one routing group), routes
    over all 16 in groups and computes its own experts' part; the parts,
    with the shared expert counted ONCE, add up to the uncut reference's
    sparse layer — for the program's layer (``_decode_moe_ep``) and for
    the reference's ``share_of_layer`` alike."""
    whole = tiny_config(experts_held=0, first_expert_held=0)
    sizes = sizes_of(whole)
    sizes["experts_held"] = 16
    params = weights.make_params(ref.param_plan(sizes), 7, jnp.float32)
    blk = params["blocks"][1]
    xn = jax.random.normal(jax.random.PRNGKey(3), (24, whole.hidden))
    shared = ref._gated(xn, blk["shared_up"], blk["shared_down"], None)
    uncut = ref.share_of_layer(blk, xn, sizes) + shared
    # the uncut layer weights exactly topk experts a token, in two groups
    gate = np.asarray(ref.route(blk, xn, sizes))
    assert ((gate > 0).sum(1) == 4).all()
    assert all(len(set(np.nonzero(g)[0] // 4)) <= 2 for g in gate)

    got_ref, got_prog = shared, shared
    for first in (0, 4, 8, 12):
        cut = dataclasses.replace(
            whole, experts_held=4, first_expert_held=first)
        mine = dict(blk, moe_up=blk["moe_up"][first:first + 4],
                    moe_down=blk["moe_down"][first:first + 4])
        got_ref = got_ref + ref.share_of_layer(mine, xn, sizes_of(cut))
        y, _ = one_chip_model(cut)._decode_moe_ep(mine, xn)
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, uncut, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_prog, uncut, atol=1e-4, rtol=0)


# ------------------------------------------------------------- (f) YaRN


def test_yarn_frequencies_and_scale_are_the_published_ones():
    """dim 64, base 10000, factor 40 over 4096, beta 32 / 1: the
    correction range is dims [10, 23]; below it the plain frequencies,
    above it a fortieth, a linear blend between (values written out,
    computed by hand from the issue's lines). ``m = 0.1 ln 40 + 1``."""
    cfg = presets.dots_vlm1(n_layers=1, n_dense_layers=1)
    got = np.asarray(cfg.yarn_inv_freq, np.float64)
    assert got.shape == (32,)
    f = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40.0, rtol=1e-6)
    # i = 16: ramp (16 - 10) / 13, f = 10000^-0.5 = 0.01
    np.testing.assert_allclose(
        got[16], 0.01 * (1 - 6 / 13) + 0.01 / 40 * (6 / 13), rtol=1e-6)
    np.testing.assert_allclose(got[11], f[11] * (1 - 1 / 13 * 39 / 40),
                               rtol=1e-6)
    assert abs(cfg.latent_softmax_scale
               - 192 ** -0.5 * 1.3688879454113936 ** 2) < 1e-9
    # the reference computes its own, from the same lines
    sizes = {k: getattr(cfg, k) for k in SIZE_KEYS
             if k.startswith(("rope", "qk_"))}
    np.testing.assert_allclose(ref.yarn_inv_freq(sizes), got, rtol=1e-6)
    assert abs(ref.softmax_scale(sizes) - cfg.latent_softmax_scale) < 1e-9
    # the twin: factor 4 over 16 positions, dim 4 -> both of its two
    # frequencies blended or not by the same rule
    tiny = tiny_config()
    np.testing.assert_allclose(
        ref.yarn_inv_freq(sizes_of(tiny)), tiny.yarn_inv_freq, rtol=1e-6)
    # factor 1 is the plain rotation and the plain scale
    plain = dataclasses.replace(tiny, rope_yarn_factor=1.0)
    np.testing.assert_allclose(
        plain.yarn_inv_freq, 10000.0 ** (-np.arange(2) / 2.0), rtol=1e-6)
    assert abs(plain.latent_softmax_scale - 12 ** -0.5) < 1e-9


# ------------------------------------------------------ (g) refusals


def _refusals():
    base = tiny_config()
    whole = tiny_config(experts_held=0, first_expert_held=0)
    mesh1 = lambda: Mesh(np.asarray(jax.devices()[:1]), ("x",))  # noqa: E731
    mesh2 = lambda: Mesh(np.asarray(jax.devices()[:2]), ("x",))  # noqa: E731
    small = dict(slots=2, token_budget=32, chunk=16, page=16, npages=16)

    def engine(cls=ServingEngine, **kw):
        spec = {"spec_k": kw.pop("spec_k")} if "spec_k" in kw else {}
        return cls(one_chip_model(base), None, EngineConfig(**small, **kw),
                   use_pallas=False, **spec)

    def disaggregated():
        model = one_chip_model(base)
        return DisaggregatedEngine(model, None, model, None,
                                   EngineConfig(**small), use_pallas=False)

    return {
        "prefix_cache": (lambda: engine(prefix_cache=True),
                         r"latent pool \(kv_latent\) with prefix_cache"),
        "prefix_share": (
            lambda: engine(prefix_cache=True, prefix_share=True),
            r"latent pool \(kv_latent\) with prefix_cache / prefix_share"),
        "speculative": (lambda: engine(SpeculativeEngine, spec_k=2),
                        r"latent pool \(kv_latent\) under SpeculativeEngine"),
        "prefill_only": (lambda: engine(prefill_only=True),
                         r"latent pool \(kv_latent\) with prefill_only"),
        "disaggregated": (disaggregated,
                          "DisaggregatedEngine with a latent pool"),
        "kv_ship": (lambda: engine().gather_pages([0]),
                    "kv_ship / page migration with a latent pool"),
        "tp": (lambda: Transformer(whole, mesh2(), tp_axis="x"),
               r"latent pool \(kv_latent\) with tp=2"),
        "cp": (lambda: Transformer(
            whole, Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                       ("x", "c")), tp_axis="x", cp_axis="c"),
            r"latent pool \(kv_latent\) with cp=2"),
        "kv_quant": (lambda: tiny_config(kv_quant="int8"),
                     "kv_latent=16 with kv_quant"),
        "sliding_window": (
            lambda: tiny_config(layer_attn=("sliding", "full", "full"),
                                window=8, rope_layers=()),
            "sliding-window layers"),
        "lightning": (
            lambda: tiny_config(
                layer_mixer=("attention", "lightning", "attention"),
                lightning_heads=4),
            "lightning layers"),
        "sparse": (
            lambda: tiny_config(
                sparse_topk=4, sparse_block=8, sparse_kernel=4,
                sparse_stride=2, sparse_init_blocks=1, sparse_window=16,
                sparse_dense_len=32),
            "block-sparse attention"),
        "forward": (
            lambda: Transformer(base, mesh1(), tp_axis="x").forward(
                None, jnp.zeros((1, 8), jnp.int32)),
            "kv_latent, router_groups"),
        "latent_sizes": (lambda: TransformerConfig(kv_latent=16),
                         "kv_latent=16 needs"),
        "latent_fields_alone": (lambda: TransformerConfig(qk_rope_dim=4),
                                "qk_rope_dim without kv_latent"),
        "yarn_alone": (lambda: TransformerConfig(rope_yarn_factor=4.0),
                       "needs kv_latent"),
        "groups_router": (
            lambda: presets.tiny(presets.mixtral_8x7b(), router_groups=2),
            "sigmoid_bias router's"),
        "groups_hold_topk": (
            lambda: tiny_config(router_groups=8, router_topk_groups=1),
            "router_topk_groups=1 of them hold topk=4"),
        "kernel_v_pool": (
            lambda: ragged_paged_attention(
                jnp.zeros((1, 64, 128)), jnp.zeros((2, 1, 16, 128)),
                jnp.zeros((2, 1, 16, 128)), *[jnp.ones((1,), jnp.int32)] * 3,
                jnp.zeros((1, 2), jnp.int32), group=8, scale=1.0,
                latent=(16, 4), with_lse=False),
            "latent with a V pool"),
        "kernel_scale": (
            lambda: ragged_paged_attention(
                jnp.zeros((1, 64, 128)), jnp.zeros((2, 1, 16, 128)), None,
                *[jnp.ones((1,), jnp.int32)] * 3,
                jnp.zeros((1, 2), jnp.int32), group=8, latent=(16, 4),
                with_lse=False),
            "latent needs scale="),
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_the_latent_pool_cannot_serve_is_refused_by_name(what):
    build, match = _refusals()[what]
    with pytest.raises(ValueError, match=match):
        build()


def test_every_kind_of_state_answers_every_feature_from_one_table():
    """``REFUSED`` is the one table the window, recurrent and latent
    refusals read: every kind states every feature (a pair left out
    would be SERVED, and none is today)."""
    features = {"prefix_cache", "speculation", "prefill_only",
                "gather_pages", "disaggregated"}
    assert set(REFUSED) == {"window", "recurrent", "latent", "index"}
    for kind, row in REFUSED.items():
        assert set(row) == features, kind
        assert all("{what}" in why for why in row.values())
    assert state_kinds(tiny_config()) == {
        "latent": "a latent pool (kv_latent)"}
    assert state_kinds(presets.tiny(presets.mixtral_8x7b())) == {}
    assert list(state_kinds(presets.tiny(presets.k_exaone_236b()))) == [
        "window"]
    assert list(state_kinds(presets.tiny(presets.minicpm_sala()))) == [
        "recurrent"]


# --------------------------------------- (h) latent=None is today's


def test_latent_none_is_todays_kernel_and_append():
    """``latent=None`` builds the contiguous walk's launch under its
    old name from the old cache key; the latent walk is a launch of its
    own whose name a search for the kernel's still finds. Likewise the
    append: two pools are ``kv_append``, one is ``kv_append_latent``."""
    q = jnp.zeros((2, 16, 16))
    pools = [jnp.zeros((4, 2, 16, 16))] * 2
    rows = [jnp.asarray(x, jnp.int32) for x in ((9, 3), (8, 1), (0, 8))]
    table = jnp.zeros((2, 2), jnp.int32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda *a: ragged_paged_attention(
            *a, group=1, block_q=8, with_lse=False, **kw))(
                q, *pools, *rows, table))

    plain, none = text(), text(latent=None)
    assert plain == none
    assert "ragged_paged_attention_latent" not in plain
    assert 'name=ragged_paged_attention' in plain.replace('"', "")
    lat = str(jax.make_jaxpr(lambda *a: ragged_paged_attention(
        *a, group=8, scale=1.0, latent=(16, 4), with_lse=False))(
            jnp.zeros((1, 16 * 8, 128)), jnp.zeros((4, 1, 16, 128)), None,
            *rows, table))
    assert "ragged_paged_attention_latent" in lat
    # the builder's cache key did not grow: the same object both ways
    key = (2, 8, 16, 24, 2, 4, 16, 16, 8, "float32", False, 0.25, 0.0, 2,
           None, (), 0, False, None)
    assert _build_ragged(*key) is _build_ragged(*key)
    two = _build_append(4, 2, 16, 16, "float32", False, True)
    units = jnp.zeros((1 + 4 * 8,), jnp.int32)
    names = [str(jax.make_jaxpr(call)(units, *ops)) for call, ops in (
        (two, [jnp.zeros((4, 2, 16, 16)), jnp.zeros((24, 2, 16))] * 2),
        (_build_append(4, 1, 16, 128, "float32", False, True, (), 1),
         [jnp.zeros((4, 1, 16, 128)), jnp.zeros((24, 1, 128))]))]
    assert "kv_append_latent" not in names[0] and "kv_append" in names[0]
    assert "kv_append_latent" in names[1]


def test_the_published_preset_states_the_model():
    c = presets.dots_vlm1()
    assert (c.n_layers, c.hidden, c.n_heads, c.vocab) == (61, 7168, 128,
                                                           129280)
    assert (c.q_latent, c.kv_latent, c.qk_nope_dim, c.qk_rope_dim,
            c.v_head_dim, c.head_dim) == (1536, 512, 128, 64, 128, 192)
    assert c.moe_layers == tuple(range(3, 61)) and c.dense_ffn == 18432
    assert (c.num_experts, c.topk, c.router_groups, c.router_topk_groups,
            c.ffn, c.shared_experts, c.routed_scale) == (
                256, 8, 8, 4, 2048, 1, 2.5)
    assert (c.latent_width, c.latent_stored) == (576, 640)
    cut = presets.dots_vlm1(n_layers=5, n_dense_layers=1, experts_held=8,
                            vocab=16160)
    assert cut.moe_layers == (1, 2, 3, 4) and cut.experts_published == 256
    with pytest.raises(ValueError, match="n_dense_layers=6 of n_layers=5"):
        presets.dots_vlm1(n_layers=5, n_dense_layers=6)
