"""PR 29's architecture on the serving path, at CPU sizes: sliding-window
layers over ring pools beside a global paged pool, rotary embedding and
q/k norm, gated FFNs, a sigmoid router with a selection bias, a shared
expert, and an expert layer that holds a SHARE of the router's experts.

The oracle is the benchmark's plain reference of the architecture
(``benchmark/models/exaone_moe.py``: float32, no cache, no kernel,
nothing of the program) on the benchmark's own seeded weights.
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
from benchmark.models import exaone_moe as ref  # noqa: E402
from conftest import on_host  # noqa: E402
from triton_distributed_tpu.kernels import moe_utils as mu  # noqa: E402
from triton_distributed_tpu.kernels.group_gemm import (  # noqa: E402
    grouped_matmul,
    quantize_act_rows,
    quantize_grouped_weights,
)
from triton_distributed_tpu.kernels.ragged_paged_attention import (  # noqa: E402
    _build_ragged,
    causal_topologies,
    pack_gqa_rows,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    topo_width,
    unpack_gqa_rows,
)
from triton_distributed_tpu.models import Transformer, presets  # noqa: E402
from triton_distributed_tpu.serving import (  # noqa: E402
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    SpeculativeEngine,
)
from triton_distributed_tpu.serving.state import (  # noqa: E402
    ring_pages,
    ring_table,
)

pytestmark = pytest.mark.fast

KINDS = ("sliding", "sliding", "sliding", "full", "sliding")
SIZE_KEYS = (
    "vocab", "n_layers", "hidden", "ffn", "dense_ffn", "n_heads",
    "n_kv_heads", "head_dim", "num_experts", "experts_held",
    "first_expert_held", "topk", "moe_layers", "layer_attn", "window",
    "rope_theta", "rope_layers", "shared_experts", "routed_scale",
    "norm_eps")
#: chunk 24, window 16, page 8: ring = ceil(39 / 8) + 1 = 6 pages, 48
#: positions — the 70-token prompt wraps it, and chunks of 24 straddle
#: the window's edge at every step after the first
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=24, page=8, npages=64)
PROMPTS = (70, 5, 33)


def tiny_config(**over):
    """The published preset's twin at test sizes: the benchmark's five
    layers (a dense one, a whole sliding x 3 + full period, one more
    sliding), window 16, experts 2-5 of 8 held."""
    kw = dict(n_layers=5, layer_attn=KINDS, rope_layers=(0, 1, 2, 4),
              moe_layers=(1, 2, 3, 4), window=16, num_experts=8,
              experts_held=4, first_expert_held=2)
    kw.update(over)
    return presets.tiny(presets.k_exaone_236b(), **kw)


def sizes_of(cfg) -> dict:
    out = {}
    for k in SIZE_KEYS:
        v = getattr(cfg, k)
        out[k] = list(v) if isinstance(v, tuple) else v
    return out


def one_chip_model(cfg):
    return Transformer(cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)),
                       tp_axis="x")


def serve(model, params, use_pallas, ecfg=ENGINE, prompts=PROMPTS,
          max_new=6, seed=0):
    """``(requests, {rid: (max_new, vocab) logits})`` of one short run."""
    # ``keep`` below reads each row's logits
    eng = on_host(ServingEngine)(model, params, ecfg, use_pallas=use_pallas)
    seen, sample = {}, eng._sample

    def keep(row_logits, req):
        seen.setdefault(req.rid, []).append(np.asarray(row_logits))
        return sample(row_logits, req)

    eng._sample = keep
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, max_new=max_new, arrival=0,
                    prompt=rng.integers(0, model.config.vocab, (n,))
                    .astype(np.int32))
            for i, n in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    for _ in range(400):
        if eng.idle:
            break
        eng.step()
    assert all(r.done for r in reqs)
    return eng, reqs, {k: np.stack(v) for k, v in seen.items()}


# ------------------------------------------------- engine == reference


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_twins", "kernels_interpreted"])
def test_engine_through_ring_and_global_pools_equals_the_reference(
        use_pallas):
    """Chunked prefill then decode through four ring pools and one
    paged pool gives the reference's logits at every served position:
    float32 both sides, so the tolerance is accumulation order only
    (1e-4 against logits of size ~3; measured 5e-6)."""
    cfg = tiny_config()
    model = one_chip_model(cfg)
    sizes = sizes_of(cfg)
    plan = ref.param_plan(sizes)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(plan, cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(have)))
    params = weights.make_params(plan, 3300000001, cfg.param_dtype)
    eng, reqs, seen = serve(model, params, use_pallas)
    assert max(PROMPTS) > eng.state.ring * ENGINE.page      # wraps
    for r in reqs:
        seq = np.concatenate(
            [r.prompt, np.asarray(r.generated[:-1], np.int32)])
        rows = np.arange(len(r.prompt) - 1, len(seq))
        np.testing.assert_allclose(
            seen[r.rid], np.asarray(ref.logits_at(params, sizes, seq, rows)),
            atol=1e-4, rtol=0)
    st = eng.stats
    assert 0 < st.window_pages_walked < st.global_pages_walked


def test_int8_kv_rides_a_ring_with_its_scale_planes():
    """``kv_quant="int8"`` beside window layers is built: a ring pool
    is the same ``{"q", "scale"}`` pair at ``slots x ring`` pages, and
    the append kernel and the ragged kernel take it as they take the
    global pool. Kernels and XLA twins serve the same tokens."""
    cfg = tiny_config(kv_quant="int8")
    model = one_chip_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    eng, a, la = serve(model, params, True)
    _, b, lb = serve(model, params, False)
    k0 = eng.state.layers[0][0]
    assert k0["q"].shape[0] == k0["scale"].shape[0] == 4 * eng.state.ring
    for r in a:
        np.testing.assert_allclose(la[r.rid], lb[r.rid], atol=5e-2, rtol=0)


def test_window_layers_allocate_slots_times_ring_pages_and_no_more():
    cfg = tiny_config()
    eng = ServingEngine(one_chip_model(cfg), None, ENGINE,
                        use_pallas=False)
    st = eng.state
    assert st.window_layers == (0, 1, 2, 4) and st.ring == 6
    for i in range(cfg.n_layers):
        pages = ENGINE.slots * 6 if i in st.window_layers else ENGINE.npages
        assert st.layer_pages(i) == pages
        assert st.layers[i][0].shape == st.layers[i][1].shape == (
            pages, cfg.n_kv_heads, ENGINE.page, cfg.head_dim)
    assert st.npages == ENGINE.npages
    assert st.ring_table.shape == st.block_table.shape
    assert int(jnp.max(st.ring_table)) == ENGINE.slots * 6 - 1


# --------------------------------------------------------- the share


def test_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 2 of 16 experts, routes over all 16 and
    computes its own experts' part; the parts, with the shared expert
    counted ONCE, add up to the uncut reference's sparse layer — for
    the program's layer (``_decode_moe_ep`` + ``_dense_mlp``) and for
    the reference's ``share_of_layer`` alike."""
    whole = tiny_config(n_layers=2, layer_attn=("sliding", "full"),
                        rope_layers=(0,), moe_layers=(1,), num_experts=16,
                        topk=4, experts_held=0, first_expert_held=0)
    sizes = sizes_of(whole)
    sizes["experts_held"] = 16
    params = weights.make_params(ref.param_plan(sizes), 7, jnp.float32)
    blk = params["blocks"][1]
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
    model = one_chip_model(whole)
    np.testing.assert_allclose(
        model._dense_mlp(xn, blk["shared_up"], blk["shared_down"]), shared,
        atol=1e-4, rtol=0)


def test_sigmoid_router_and_held_assignments():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
    bias = jnp.zeros((16,)).at[5].set(10.0)         # always chosen ...
    w, ids = mu.select_experts_sigmoid_bias(logits, bias, 4, scale=2.5)
    assert bool(jnp.all(jnp.any(ids == 5, axis=1)))
    s = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(jnp.sum(w, axis=1), 2.5, rtol=1e-6)
    # ... but weighted by its own score, not by score + bias
    np.testing.assert_allclose(
        w, 2.5 * jnp.take_along_axis(s, ids, 1)
        / jnp.sum(jnp.take_along_axis(s, ids, 1), 1, keepdims=True),
        rtol=1e-6)
    flat_e, w_flat = mu.held_assignments(w, ids, first=4, held=4)
    mine = (ids >= 4) & (ids < 8)
    np.testing.assert_array_equal(
        flat_e.reshape(ids.shape), jnp.where(mine, ids - 4, 4))
    assert bool(jnp.all((w_flat.reshape(ids.shape) == 0) == ~mine))


@pytest.mark.parametrize("quant", [None, "w8a16", "w8a8"])
def test_dummy_blocks_are_stored_as_zeros_without_a_multiply(quant):
    """``grouped_matmul(dummy_expert=)``, all three kernels: the blocks
    of real experts bit for bit as without it, the trailing dummy
    blocks exact zeros whatever rows they hold."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32)
    be = jnp.asarray([0, 1, 1, 2, 2, 2], jnp.int32)
    kw = dict(block_m=8, block_n=32, block_k=16)
    if quant is not None:
        w, kw["w_scale"] = quantize_grouped_weights(w, "int8")
    if quant == "w8a8":
        x, kw["x_scale"] = quantize_act_rows(x)
    got = grouped_matmul(x, w, be, dummy_expert=2, **kw)
    want = grouped_matmul(x, w, jnp.minimum(be, 1), **kw)
    np.testing.assert_array_equal(got[:24], want[:24])
    assert not np.asarray(got[24:]).any() and np.asarray(want[24:]).any()


# --------------------------------------------------- windowed kernel

HKV, G, D, PAGE = 2, 2, 32, 8


def _window_batch(ring=4, pps=8):
    """A decode row deep in its sequence, a chunk that straddles the
    window's edge, an empty slot — over RING tables."""
    rng = np.random.default_rng(0)
    slots = 3
    kc, vc = (jnp.asarray(rng.standard_normal((slots * ring, HKV, PAGE, D)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(ring_table(slots, pps, ring))
    kv = jnp.asarray([37, 21, 0], jnp.int32)
    ql = jnp.asarray([1, 16, 0], jnp.int32)
    qs = jnp.asarray([0, 8, 32], jnp.int32)
    q = jnp.asarray(rng.standard_normal((48, HKV * G, D)), jnp.float32)
    return q, kc, vc, kv, ql, qs, table


def _spans(out, ql, qs):
    return [np.asarray(out)[:, int(s) * G:(int(s) + int(n)) * G]
            for s, n in zip(qs, ql) if int(n)]


@pytest.mark.parametrize("window", [5, 8, 13, 100])
@pytest.mark.parametrize("descriptors", [False, True],
                         ids=["no_topologies", "causal_topologies"])
def test_windowed_kernel_equals_its_twin_and_dense_masked_attention(
        window, descriptors):
    q, kc, vc, kv, ql, qs, table = _window_batch()
    qp = pack_gqa_rows(q, HKV)
    topo = jnp.asarray(causal_topologies(3, topo_width(16))) \
        if descriptors else None
    out, _ = ragged_paged_attention(
        qp, kc, vc, kv, ql, qs, table, group=G, block_q=16, window=window,
        topologies=topo)
    twin, _ = ragged_paged_attention_xla(
        qp, kc, vc, kv, ql, qs, table, group=G, window=window,
        topologies=topo)
    for a, b in zip(_spans(out, ql, qs), _spans(twin, ql, qs)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    # the twin against dense masked attention over the ring's LIVE
    # positions, gathered by hand (row 1: positions 5..20)
    r, n, L = 1, int(ql[1]), int(kv[1])
    pos = np.arange(L)
    kd = np.stack([np.asarray(kc)[int(table[r, p // PAGE]), :, p % PAGE]
                   for p in pos], 1)                     # (Hkv, L, D)
    vd = np.stack([np.asarray(vc)[int(table[r, p // PAGE]), :, p % PAGE]
                   for p in pos], 1)
    qd = np.asarray(q)[int(qs[r]):int(qs[r]) + n].reshape(n, HKV, G, D)
    s = np.einsum("thgd,hsd->thgs", qd, kd) / np.sqrt(D)
    at = L - n + np.arange(n)
    seen = (pos[None] <= at[:, None]) & (pos[None] > at[:, None] - window)
    s = np.where(seen[:, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    dense = np.einsum("thgs,hsd->thgd", p / p.sum(-1, keepdims=True), vd)
    got = unpack_gqa_rows(twin, HKV * G)[int(qs[r]):int(qs[r]) + n]
    np.testing.assert_allclose(got.reshape(n, HKV, G, D), dense,
                               atol=1e-5, rtol=1e-5)


def test_window_none_is_todays_kernel():
    """``window=None`` builds the launch under its old name and gives,
    byte for byte, what a window no key falls out of gives; a window
    changes the name, so a profile tells the kinds apart."""
    q, kc, vc, kv, ql, qs, _ = _window_batch()
    table = jnp.asarray(np.arange(3 * 8).reshape(3, 8) % 12, jnp.int32)
    qp = pack_gqa_rows(q, HKV)
    plain = ragged_paged_attention(
        qp, kc, vc, kv, ql, qs, table, group=G, block_q=16)
    wide = ragged_paged_attention(
        qp, kc, vc, kv, ql, qs, table, group=G, block_q=16, window=1 << 20)
    for a, b in zip(plain, wide):
        for x, y in zip(_spans(a, ql, qs), _spans(b, ql, qs)):
            np.testing.assert_array_equal(x, y)
    args = (2, 2, 4, 16, 2, 1, 128, 8, 8, "float32", False, 0.1, 0.0, 2,
            True)
    jaxprs = [str(jax.make_jaxpr(_build_ragged(*args, (tag,), 0, True, w))(
        jnp.zeros((2, 2), jnp.int32), *[jnp.zeros((2,), jnp.int32)] * 3,
        jnp.zeros((2, 16, 128)), *[jnp.zeros((4, 2, 8, 128))] * 2))
        for tag, w in (("a", None), ("b", 8))]
    assert "ragged_paged_attention" in jaxprs[0]
    assert "ragged_paged_attention_w" not in jaxprs[0]
    assert "ragged_paged_attention_w8" in jaxprs[1]
    with pytest.raises(ValueError, match="window must be >= 1"):
        ragged_paged_attention(qp, kc, vc, kv, ql, qs, table, group=G,
                               block_q=16, window=0)


# ------------------------------------------------------- ring tables


@pytest.mark.parametrize("chunk,window,page", [
    (256, 128, 128), (24, 16, 8), (16, 16, 8), (1, 1, 8), (8, 64, 8),
    (33, 7, 8), (256, 4096, 128), (100, 100, 16)])
def test_no_two_live_positions_of_a_slot_alias_in_its_ring(
        chunk, window, page):
    """At every cursor: the positions a step writes (up to ``chunk``)
    and those its queries still see (``window - 1`` back) map to
    distinct (page, row) places of the slot's ring."""
    ring = ring_pages(chunk, window, page)
    slots, pps = 3, 4 * ring + 3
    table = np.asarray(ring_table(slots, pps, ring))
    assert table.shape == (slots, pps)
    for s in range(slots):
        assert set(table[s]) == set(range(s * ring, (s + 1) * ring))
    for cursor in list(range(0, 3 * ring * page, max(1, page // 3))):
        for take in {1, chunk}:
            lo = max(cursor - window + 1, 0)
            hi = min(cursor + take, pps * page)
            places = {(table[1, p // page], p % page)
                      for p in range(lo, hi)}
            assert len(places) == hi - lo


# ---------------------------------------------------------- refusals


def _engine(cls=ServingEngine, cfg=None, ecfg=ENGINE, **kw):
    model = one_chip_model(cfg or tiny_config())
    return cls(model, None, ecfg, use_pallas=False, **kw)


REFUSED = {
    "prefix_cache": lambda: _engine(
        ecfg=dataclasses.replace(ENGINE, prefix_cache=True)),
    "SHARED_PREFIX": lambda: _engine(ecfg=dataclasses.replace(
        ENGINE, prefix_cache=True, prefix_share=True)),
    "SpeculativeEngine": lambda: _engine(SpeculativeEngine, spec_k=2),
    "prefill_only": lambda: _engine(
        ecfg=dataclasses.replace(ENGINE, prefill_only=True)),
    "DisaggregatedEngine": lambda: DisaggregatedEngine(
        one_chip_model(tiny_config()), None,
        one_chip_model(tiny_config()), None, ENGINE, use_pallas=False),
    "kv_ship": lambda: _engine().gather_pages([0]),
    "cp > 1": lambda: Transformer(
        tiny_config(), Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                            ("x", "cpx")), tp_axis="x", cp_axis="cpx"),
    "tp=2": lambda: Transformer(
        tiny_config(), Mesh(np.asarray(jax.devices()[:2]), ("x",)),
        tp_axis="x"),
    "needs chunk=": lambda: one_chip_model(
        tiny_config()).init_serving_state(4, 64, 8),
    "moe='ep' only": lambda: tiny_config(moe="none", moe_layers=()),
    "not built beside": lambda: tiny_config(moe_weight_quant="int8"),
    "go together": lambda: tiny_config(window=0),
    "rope_theta > 0": lambda: tiny_config(rope_theta=0.0),
    "for each of": lambda: tiny_config(layer_attn=("full",)),
    "do not lie in": lambda: tiny_config(first_expert_held=6),
    "Transformer.forward does not implement": lambda: one_chip_model(
        tiny_config()).forward(None, jnp.zeros((1, 8), jnp.int32)),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_is_not_built_raises_by_name(what):
    match = {"SHARED_PREFIX": "SHARED_PREFIX", "kv_ship": "kv_ship",
             "tp=2": "experts_held=4 with tp=2"}.get(what, what)
    with pytest.raises(ValueError, match=match):
        REFUSED[what]()


def test_the_published_preset_states_the_model():
    c = presets.k_exaone_236b()
    assert (c.n_layers, c.num_experts, c.vocab, c.topk) == (
        48, 128, 153600, 8)
    assert c.layer_attn[:4] == ("sliding", "sliding", "sliding", "full")
    assert c.layer_attn.count("full") == 12 and c.window == 128
    assert c.moe_layers == tuple(range(1, 48)) and c.dense_ffn == 18432
    assert c.rope_layers == c.window_layers and c.local_experts == 128
    cut = presets.k_exaone_236b(n_layers=5, experts_held=16, vocab=19200)
    assert cut.layer_attn == KINDS and cut.moe_layers == (1, 2, 3, 4)
    assert (cut.local_experts, cut.experts_published) == (16, 128)
    # both older presets keep every default: their programs are as before
    for old in (presets.mixtral_8x7b(), presets.deepseek_moe_16b()):
        assert old.beyond_plain == () and not old.routed_assignments
