"""PR 49's token-level learned selection on the serving path, at CPU
sizes: a pool of indexer keys beside K/V (ONE key a token and layer),
the scan over a row's whole context, the top-k over it on the device,
and the attention walk under a per-(query position, key) mask.

The oracle is the benchmark's plain reference of the architecture
(``benchmark/models/keye_vl2.py``: float32, no cache, no kernel, nothing
of the program) on the benchmark's own seeded weights. The twin is 2
layers, GQA 8 query / 4 KV heads of 16, an indexer of 2 heads of 8 that
keeps 8 tokens, 16 experts (top-4) of which 4 are held; contexts run to
~50, so most positions select.
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

from benchmark.harness import metrics, weights  # noqa: E402
from benchmark.models import keye_vl2 as ref  # noqa: E402
from conftest import serve_all_logits  # noqa: E402
from triton_distributed_tpu.kernels import token_select as ts  # noqa: E402
from triton_distributed_tpu.kernels.ragged_paged_attention import (  # noqa: E402
    pack_gqa_rows,
    unpack_gqa_rows,
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
    "vocab", "n_layers", "hidden", "ffn", "n_heads", "n_kv_heads",
    "head_dim", "index_heads", "index_dim", "index_topk", "rope_theta",
    "num_experts", "experts_held", "first_expert_held", "topk",
    "norm_eps")
#: chunk 16 over pages of 8: a prompt of 43 crosses two chunk and five
#: page boundaries; every position from 8 on selects 8 of its keys; a
#: table of 16 pages reaches 128 tokens: context caps 8 / 32 / 128
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=8, npages=16)
PROMPTS = (33, 5, 43, 11)


def tiny_config(**over):
    """The twin: experts 4-7 of 16 are held here (a share of 4)."""
    kw = dict(vocab=96, num_experts=16, topk=4, experts_held=4,
              first_expert_held=4)
    kw.update(over)
    return presets.tiny(presets.keye_vl2_30b(n_layers=2), **kw)


def sizes_of(cfg) -> dict:
    out = {k: getattr(cfg, k) for k in SIZE_KEYS}
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
def test_engine_through_the_indexer_pool_equals_the_reference(use_pallas):
    """Chunked prefill across chunk and page boundaries, then decode,
    four requests through four slots in packed steps, contexts on both
    sides of ``index_topk``: the logits at EVERY position equal the
    reference's full forward pass. Float32 both sides, so the tolerance
    is accumulation order (1e-4 against logits of size ~4)."""
    model, sizes, params = seeded(tiny_config())
    eng, reqs, logits = serve_all_logits(
        model, params, ENGINE, prompts_of(PROMPTS), max_new=4,
        use_pallas=use_pallas)
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req), atol=1e-4, rtol=1e-4)
    st = eng.stats
    assert st.dsa_rows > st.dsa_sparse_rows > 0
    assert st.index_keys_scanned > 0 and st.dsa_selected_tokens > 0
    assert st.append_runs > 0 if use_pallas else st.append_scatter_steps > 0
    assert eng._rungs() == [8, 16]


def test_param_plan_is_the_programs_init_tree():
    cfg = tiny_config()
    want = jax.eval_shape(one_chip_model(cfg).init, jax.random.PRNGKey(0))
    plan = ref.param_plan(sizes_of(cfg))
    got = jax.tree.map(lambda leaf: leaf[0], plan,
                       is_leaf=lambda x: isinstance(x, tuple))
    assert jax.tree.structure(got, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree.structure(want)
    for a, b in zip(
            jax.tree.leaves(got, is_leaf=lambda x: isinstance(x, tuple)),
            jax.tree.leaves(want)):
        assert tuple(a) == b.shape


def test_the_preset_states_the_published_model():
    c = presets.keye_vl2_30b()
    assert (c.n_layers, c.hidden, c.n_heads, c.n_kv_heads, c.head_dim) == (
        48, 2048, 32, 4, 128)
    assert (c.index_heads, c.index_dim, c.index_topk) == (16, 64, 2048)
    assert (c.num_experts, c.topk, c.ffn, c.shared_experts) == (
        128, 8, 768, 0)
    assert c.vocab == 151936 and c.rope_theta == 1e7 and c.qk_norm
    assert c.moe_layers == c.rope_layers == tuple(range(48))
    assert c.index_width == 17 * 64 + 16 and c.index_stored == 128
    cut = presets.keye_vl2_30b(n_layers=8, experts_held=16, vocab=18992)
    assert cut.local_experts == 16 and cut.experts_published == 128
    # a layer's parameters as the configuration file reckons them
    tree = jax.eval_shape(one_chip_model(cut).init, jax.random.PRNGKey(0))
    layer = sum(int(np.prod(a.shape))
                for a in jax.tree.leaves(tree["blocks"][0]))
    assert abs(layer - 96.9e6) < 0.1e6


# ------------------------------------------------------- (b) the selection


def _reference_kept(scores, pos, topk):
    return np.asarray(ref.kept(
        jnp.asarray(scores), jnp.asarray(pos), {"index_topk": topk}))


@pytest.mark.parametrize("scores", ["random", "tied", "all_equal"])
def test_the_choice_is_the_references_top_k_ties_to_the_lower_key(scores):
    rng = np.random.default_rng(3)
    n, c, topk = 24, 256, 16
    sc = rng.normal(size=(n, c)).astype(np.float32)
    if scores == "tied":
        sc = np.round(sc * 2) / 2          # many equal values at the edge
    elif scores == "all_equal":
        sc = np.zeros_like(sc)
    pos = rng.integers(0, c, (n,)).astype(np.int32)
    seen = np.arange(c)[None, :] <= pos[:, None]
    got = np.asarray(ts.choose_tokens(
        jnp.where(seen, sc, -jnp.inf), topk)) & seen
    got = np.where((pos < topk)[:, None], seen, got)
    np.testing.assert_array_equal(got, _reference_kept(sc, pos, topk))
    assert (got.sum(1) == np.minimum(pos + 1, topk)).all()


def _step(rows, *, page=8, pps=16, seed=0, slots=None):
    """A packed step of ``rows`` = [(context before, tokens taken)]:
    the operands every stage reads."""
    slots = slots or len(rows)
    q_lens = np.zeros(slots, np.int32)
    q_starts = np.zeros(slots, np.int32)
    kv_lens = np.zeros(slots, np.int32)
    t = sum(-(-take // 8) * 8 for _, take in rows) + 16
    token_rows = np.zeros(t, np.int32)
    token_pos = np.full(t, -1, np.int32)
    at = 0
    for s, (cur, take) in enumerate(rows):
        q_starts[s], q_lens[s], kv_lens[s] = at, take, cur + take
        token_rows[at:at + take] = s
        token_pos[at:at + take] = np.arange(cur, cur + take)
        at += -(-take // 8) * 8
    table = np.random.default_rng(seed).permutation(
        slots * pps).reshape(slots, pps).astype(np.int32)
    return dict(
        t=t, page=page, pps=pps, npages=slots * pps,
        table=jnp.asarray(table), q_lens=jnp.asarray(q_lens),
        q_starts=jnp.asarray(q_starts), kv_lens=jnp.asarray(kv_lens),
        token_rows=jnp.asarray(token_rows), token_pos=jnp.asarray(token_pos))


def _select(how, scores, st, topk):
    """The step's mask words by the kernel (interpreted) or its twin."""
    sizes = dict(page=st["page"], pps=st["pps"], topk=topk)
    if how == "xla_twin":
        return ts.select_tokens_xla(scores, st["token_pos"], **sizes)
    return ts.select_tokens(
        scores, st["kv_lens"], st["q_lens"], st["q_starts"], **sizes)


def _assert_the_references_choice(words, scores, st, topk):
    cap = st["pps"] * st["page"]
    assert words.shape == (ts.word_planes(st["pps"]), st["t"], st["page"])
    pos = np.asarray(st["token_pos"])
    want = _reference_kept(np.asarray(scores)[:, :cap],
                           np.maximum(pos, 0), topk)
    np.testing.assert_array_equal(
        np.asarray(ts.unpack_words(words, st["pps"])),
        want & (pos >= 0)[:, None])


@pytest.mark.parametrize("how", ["xla_twin", "kernel_interpreted"])
@pytest.mark.parametrize("rows", [
    [(3, 1), (7, 1)],                       # no row past topk: no score read
    [(20, 1), (5, 1), (30, 1)],             # decode-only, 4 pages in view
    [(100, 1), (9, 1)],                     # decode-only, 13 pages in view
    [(0, 16), (40, 1), (8, 5)],             # a chunk beside decode rows
    [(3, 5), (90, 1), (20, 16)],            # a short tail beside both
    [(100, 16), (120, 1)],                  # the table's reach
], ids=["dense", "single32", "single128", "mixed", "tail", "top_cap"])
def test_the_selection_is_the_references_at_every_context_cap(rows, how):
    """The selection on random scores: every live query position's
    kept set is the reference's top-k of the same scores (a row at or
    under ``topk`` keeps all), however many pages its row holds and
    whether the step is decode-only or not; padding tokens and tokens
    of no batched row keep nothing. The kernel's words are the twin's
    bit for bit."""
    topk = 8
    st = _step(rows)
    scores = jax.random.normal(
        jax.random.PRNGKey(1), (st["t"], ts.scores_width(st["pps"], 8)))
    words = _select(how, scores, st, topk)
    _assert_the_references_choice(words, scores, st, topk)
    np.testing.assert_array_equal(
        np.asarray(words), np.asarray(_select("xla_twin", scores, st, topk)))


def _edge(case):
    """(rows, ``_step``'s other arguments, scores -> scores): what the
    kernel must get right where its tiles, pages and passes meet."""
    rng = np.random.default_rng(11)

    def ties(sc, pos):
        # five keys above all, then equal scores from key 5 on in every
        # other place, the rest far below: the ties cross page boundaries
        # (8) and a one-token row's plane boundary (64), and the topk-th
        # place falls among them
        sc -= 10.0
        sc[:, 5::2] = 0.5
        sc[:, :5] = 2.0 + np.arange(5)
        return sc

    def zeros(sc, pos):
        # -inf, -0.0, +0.0 and a few positive scores: fewer than topk
        # keys above zero, so the kth is a zero of either sign
        kind = rng.integers(0, 8, sc.shape)
        return np.select([kind == 0, kind < 3, kind < 7],
                         [-np.inf, -0.0, 0.0], np.abs(sc)).astype(np.float32)

    def garbage(sc, pos):
        # what the scan leaves where no query has a key in view
        return np.where(np.arange(sc.shape[1])[None, :] > pos[:, None],
                        3e38, sc).astype(np.float32)

    wide = dict(pps=256)          # 32-page planes a word bit: 8 planes
    return {
        "ties_across_pages": ([(70, 1), (20, 16), (9, 3)], {}, ties),
        "ties_in_a_chunk_from_zero": ([(0, 16), (100, 1)], {}, ties),
        "at_topk_and_one_past": ([(7, 1), (8, 1), (0, 8), (1, 8)], {}, None),
        "ends_mid_page": ([(42, 1), (50, 3), (27, 16)], {}, None),
        "zeros_of_both_signs": ([(60, 1), (30, 16), (12, 5)], {}, zeros),
        "garbage_out_of_view": ([(33, 1), (18, 16), (90, 2)], {}, garbage),
        "rows_outside_the_batch": ([(40, 1), (10, 16)], dict(slots=5),
                                   garbage),
        "a_wide_table": ([(1500, 1), (700, 16), (30, 5), (2040, 1)], wide,
                         garbage),
        "ties_in_a_wide_table": ([(1100, 1), (300, 16)], wide, ties),
    }[case]


@pytest.mark.parametrize("case", [
    "ties_across_pages", "ties_in_a_chunk_from_zero", "at_topk_and_one_past",
    "ends_mid_page", "zeros_of_both_signs", "garbage_out_of_view",
    "rows_outside_the_batch", "a_wide_table", "ties_in_a_wide_table"])
def test_the_selection_kernel_at_its_edges(case):
    """The kernel's words equal the twin's bit for bit, and both are the
    reference's choice: ties kept from the lower key across a page and
    a plane boundary, a row exactly at ``topk`` and one past it, a
    context that ends inside a page, -inf / -0.0 / equal zeros at the
    kth place, huge values where no query has a key in view (which
    change nothing), slots no row of the batch uses, a table wide
    enough (256 pages) that a key block is eight word planes at one
    bit."""
    topk = 8
    rows, step, plant = _edge(case)
    st = _step(rows, **step)
    base = np.asarray(jax.random.normal(
        jax.random.PRNGKey(2), (st["t"], ts.scores_width(st["pps"], 8))))
    pos = np.asarray(st["token_pos"])
    scores = jnp.asarray(plant(base.copy(), pos) if plant else base)
    words = _select("kernel_interpreted", scores, st, topk)
    np.testing.assert_array_equal(
        np.asarray(words), np.asarray(_select("xla_twin", scores, st, topk)))
    _assert_the_references_choice(words, scores, st, topk)
    if case.startswith("garbage"):
        np.testing.assert_array_equal(np.asarray(words), np.asarray(
            _select("kernel_interpreted", jnp.asarray(base), st, topk)))


def test_the_selections_counter_and_the_two_metric_files_that_read_it():
    """``EngineStats.dsa_select_pairs``: a row's query positions past
    ``topk`` x the keys of the pages it holds, summed over the device
    steps — a prompt of 43 in chunks of 16 (8 x 16, 16 x 32, 11 x 48)
    and three decode steps (1 x 48 each) at pages of 8, ``topk`` 8. The
    benchmark's two PR 50 metric files read it and the step times; a
    program without the counter reads 0 and nothing raises."""
    import json

    model, _, params = seeded(tiny_config())
    ecfg = EngineConfig(slots=1, token_budget=32, chunk=16, page=8,
                        npages=16)
    eng, _, _ = serve_all_logits(
        model, params, ecfg, prompts_of((43,), seed=3), max_new=4)
    assert eng.stats.dsa_select_pairs == 8 * 16 + 16 * 32 + 11 * 48 + 3 * 48
    assert eng.stats.index_keys_scanned == 16 + 32 + 43 + 44 + 45 + 46
    layer_metrics = ROOT / "benchmark" / "layer_metrics"
    read = {
        name: json.loads((layer_metrics / f"{name}.json").read_text())
        for name in ("dsa_select_pairs_per_step", "keyevl2_step_ms_p95")}
    rec = {"counters": {"stats.dsa_select_pairs": 1312, "device_steps": 6},
           "series": {"step_device_ms": [11.0] * 19 + [30.0]}}
    assert metrics.read_layer_metric(
        rec, read["dsa_select_pairs_per_step"]) == pytest.approx(1312 / 6)
    assert 11.0 < metrics.read_layer_metric(
        rec, read["keyevl2_step_ms_p95"]) <= 30.0
    parent = {"counters": {"device_steps": 6}, "series": {}}
    assert metrics.read_layer_metric(
        parent, read["dsa_select_pairs_per_step"]) == 0
    assert metrics.read_layer_metric(
        parent, read["keyevl2_step_ms_p95"]) is None
    entries = {m["name"]: m for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]}
    for name in read:
        assert entries[name]["workloads"] == ["keyevl2.docs16k"]
        assert entries[name]["moves"] == "itl_p95_ms"


# ------------------------------------------------------------ (c) the walk


def _dense_masked(q, k, v, keep):
    """q (T, Hq, D), k / v (T, C, Hkv, D) each token's own keys, keep
    (T, C) -> (T, Hq, D)."""
    t, hq, d = q.shape
    g = hq // k.shape[2]
    s = np.einsum("thgd,tchd->thgc", q.reshape(t, -1, g, d), k) / d ** 0.5
    s = np.where(keep[:, None, None, :], s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("thgc,tchd->thgd", p, v).reshape(t, hq, d)


@pytest.mark.parametrize("attend,block_q", [
    ("xla_twin", 16), ("kernel_interpreted", 8), ("kernel_interpreted", 16)])
def test_the_token_walk_is_masked_dense_attention(attend, block_q):
    """Decode rows of 9 to 100 keys before and after a chunk row (at
    the chunk rung), each query position under a random mask of its
    own: the walk equals dense attention under that mask."""
    hkv, g, d = 2, 2, 16
    rows = [(40, 1), (8, 1), (99, 1)] if block_q == 8 else \
        [(40, 1), (24, 16), (99, 1), (3, 5)]
    st = _step(rows, seed=2)
    t, pps, page = st["t"], st["pps"], st["page"]
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (t, hkv * g, d))
    kp = jax.random.normal(ks[1], (st["npages"], hkv, page, d))
    vp = jax.random.normal(ks[2], (st["npages"], hkv, page, d))
    pos = np.asarray(st["token_pos"])
    keys = np.arange(pps * page)[None, :]
    keep = np.asarray(jax.random.bernoulli(ks[3], 0.3, (t, pps * page)))
    keep = (keep | (keys == pos[:, None])) & (keys <= pos[:, None])
    words = ts.pack_words(jnp.asarray(keep), page=page, pps=pps)
    np.testing.assert_array_equal(
        np.asarray(ts.unpack_words(words, pps)), keep)
    qp = pack_gqa_rows(q, hkv)
    if attend == "xla_twin":
        o = ts.token_walk_xla(qp, kp, vp, words, st["token_rows"],
                              st["table"], group=g)
    else:
        o = ts.token_walk(qp, kp, vp, words, st["kv_lens"], st["q_lens"],
                          st["q_starts"], st["table"], group=g,
                          block_q=block_q)
    got = np.asarray(unpack_gqa_rows(o, hkv * g))
    table = np.asarray(st["table"])
    row_of = np.asarray(st["token_rows"])

    def mine(pool):                                        # (T, C, Hkv, D)
        return np.asarray(pool)[table].transpose(0, 1, 3, 2, 4).reshape(
            len(table), pps * page, hkv, d)[row_of]

    live = pos >= 0
    want = _dense_masked(np.asarray(q)[live], mine(kp)[live],
                         mine(vp)[live], keep[live])
    np.testing.assert_allclose(got[live], want, atol=2e-5, rtol=2e-5)


def test_the_scan_kernel_is_its_twin_where_a_query_has_a_key_in_view():
    """Rows on both sides of ``topk``, a chunk row and decode rows: the
    kernel's scores equal the twin's for every live query of a row past
    ``topk`` against every key of its context (a row at or under
    ``topk`` is not scanned: nothing reads its scores)."""
    st = _step([(40, 1), (24, 16), (3, 1), (99, 1)], seed=5)
    t, pps, page = st["t"], st["pps"], st["page"]
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    qi = jax.random.normal(ks[0], (t, 2, 8))
    w = jax.random.normal(ks[1], (t, 2))
    pool = jnp.pad(jax.random.normal(ks[2], (st["npages"], 1, page, 8)),
                   ((0, 0),) * 3 + ((0, ts.index_stored(8) - 8),))
    twin = np.asarray(ts.index_scores_xla(
        qi, w, pool, st["token_rows"], st["table"], scale=8 ** -0.5))
    got = np.asarray(ts.index_scores(
        qi, w, pool, st["kv_lens"], st["q_lens"], st["q_starts"],
        st["table"], topk=8, block_q=16, scale=8 ** -0.5))
    assert got.shape == twin.shape == (t, ts.scores_width(pps, page))
    pos = np.asarray(st["token_pos"])
    kv = np.asarray(st["kv_lens"])[np.asarray(st["token_rows"])]
    for i in np.nonzero((pos >= 0) & (kv > 8))[0]:
        np.testing.assert_allclose(
            got[i, :pos[i] + 1], twin[i, :pos[i] + 1], atol=1e-5, rtol=1e-5)


# ---------------------------------------------------- (d) the indexer keys


def test_the_pool_holds_one_key_a_token_and_a_reused_slot_reads_its_own():
    """A layer's indexer keys are ONE array, ``(npages, 1, page,
    index_stored)``; after a request of n tokens exactly n of its rows
    are written, across page boundaries, each the reference's key of
    that position and a zero tail. A second request through the same
    (only) slot reads none of the first's keys: its logits are those of
    the reference on its own sequence."""
    cfg = tiny_config()
    model, sizes, params = seeded(cfg)
    ecfg = EngineConfig(slots=1, token_budget=32, chunk=16, page=8,
                        npages=16)
    first, second = prompts_of((42, 13), seed=3)
    eng, (req,), _ = serve_all_logits(model, params, ecfg, [first],
                                      max_new=4)
    written = len(first) + 4 - 1          # the last token is never fed
    seq = np.concatenate([req.prompt, np.asarray(req.generated[:-1])])
    x = params["embed"][seq].astype(jnp.float32)
    want = ref.indexer(
        params["blocks"][0],
        ref._rmsnorm(x, params["blocks"][0]["norm_attn"], cfg.norm_eps),
        sizes)[1]
    assert len(eng.state.ckeys) == cfg.n_layers
    for pool in eng.state.ckeys:
        assert pool.shape == (16, 1, 8, cfg.index_stored)
        rows = np.asarray(pool).reshape(-1, cfg.index_stored)
        assert int(np.any(rows != 0, axis=1).sum()) == written
        assert not rows[:, cfg.index_dim:].any()
    # a one-slot engine's allocator hands its pages out ascending: the
    # first pages of the pool hold the first layer's keys in order
    rows = np.asarray(eng.state.ckeys[0])[:-(-written // 8)].reshape(
        -1, cfg.index_stored)
    np.testing.assert_allclose(
        rows[:written, :cfg.index_dim], want, atol=1e-5, rtol=1e-5)

    eng2, reqs, logits = serve_all_logits(
        model, params, ecfg, [first, second], max_new=4)
    assert {r.slot for r in reqs} <= {0, None}
    np.testing.assert_allclose(
        logits[1], reference_rows(params, sizes, reqs[1]),
        atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ (e) the share


def test_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Each of 4 chips holds 4 of 16 experts, routes over all 16 and
    computes its own experts' part; the parts add up to the uncut
    reference's expert layer (no shared expert to count once) — for the
    program's layer (``_decode_moe_ep``) and for the reference's
    ``share_of_layer`` alike."""
    whole = tiny_config(experts_held=0, first_expert_held=0)
    sizes = sizes_of(whole)
    params = weights.make_params(ref.param_plan(sizes), 7, jnp.float32)
    blk = params["blocks"][1]
    xn = jax.random.normal(jax.random.PRNGKey(3), (24, whole.hidden))
    uncut = ref.share_of_layer(blk, xn, sizes)
    got_ref = got_prog = 0.0
    for chip in range(4):
        cut = dataclasses.replace(
            whole, experts_held=4, first_expert_held=4 * chip)
        mine = dict(blk, moe_up=blk["moe_up"][4 * chip:4 * chip + 4],
                    moe_down=blk["moe_down"][4 * chip:4 * chip + 4])
        got_ref = got_ref + ref.share_of_layer(mine, xn, sizes_of(cut))
        y, _ = one_chip_model(cut)._decode_moe_ep(mine, xn)
        got_prog = got_prog + y
    np.testing.assert_allclose(got_ref, uncut, atol=1e-4, rtol=0)
    np.testing.assert_allclose(got_prog, uncut, atol=1e-4, rtol=0)


# ------------------------------------------------------------ (f) refusals


def _refusals():
    base = tiny_config()
    mesh1 = lambda: Mesh(np.asarray(jax.devices()[:1]), ("x",))  # noqa: E731
    mesh2 = lambda: Mesh(np.asarray(jax.devices()[:2]), ("x",))  # noqa: E731
    small = dict(slots=2, token_budget=32, chunk=16, page=8, npages=16)

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

    return {
        "prefix_cache": (lambda: engine(prefix_cache=True),
                         "prefix_cache / prefix_share"),
        "prefix_share": (
            lambda: engine(prefix_cache=True, prefix_share=True),
            "prefix_cache / prefix_share"),
        "speculative": (speculative, "SpeculativeEngine"),
        "prefill_only": (lambda: engine(prefill_only=True), "prefill_only"),
        "disaggregated": (disaggregated, "DisaggregatedEngine"),
        "kv_ship": (lambda: engine().gather_pages([0]),
                    "kv_ship / page migration"),
        "tp": (lambda: Transformer(
            dataclasses.replace(base, experts_held=0, first_expert_held=0),
            mesh2(), tp_axis="x"), "tp=2"),
        "cp": (lambda: Transformer(
            base, Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                       ("x", "c")), tp_axis="x", cp_axis="c"), "cp=2"),
        "kv_quant": (lambda: tiny_config(kv_quant="int8"), "kv_quant"),
        "sliding_window": (
            lambda: tiny_config(layer_attn=("sliding", "full"), window=8),
            "sliding-window layers"),
        "block_sparse": (
            lambda: tiny_config(
                sparse_kernel=4, sparse_stride=2, sparse_block=8,
                sparse_init_blocks=1, sparse_window=16, sparse_topk=4,
                sparse_dense_len=32), "index_topk=8 with block-sparse"),
        "forward": (
            lambda: Transformer(base, mesh1(), tp_axis="x").forward(
                None, jnp.zeros((1, 8), jnp.int32)), "index_topk"),
        "index_sizes": (
            lambda: TransformerConfig(index_topk=8), "index_topk=8 needs"),
        "index_heads": (
            lambda: TransformerConfig(index_heads=2), "without index_topk"),
        "page": (
            lambda: one_chip_model(base).init_serving_state(2, 8, 16),
            "needs a page"),
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_a_token_selection_cannot_serve_is_refused_by_name(what):
    build, match = _refusals()[what]
    with pytest.raises(ValueError, match=match):
        build()


def test_the_index_kind_answers_every_feature():
    features = {"prefix_cache", "speculation", "prefill_only",
                "gather_pages", "disaggregated"}
    assert set(REFUSED["index"]) == features
    assert all("{what}" in why for why in REFUSED["index"].values())
    assert list(state_kinds(tiny_config())) == ["index"]
    assert "index" not in state_kinds(presets.tiny(presets.mixtral_8x7b()))
