"""PR 33's mixers on the serving path, at CPU sizes: lightning (linear-
attention) layers with a recurrent state beside the page pool, block-
sparse attention that walks selected pages, compressed keys kept as K is
appended, output gates and muP scalings.

The oracle is the benchmark's plain reference of the architecture
(``benchmark/models/minicpm_sala.py``: float32, no cache, no kernel,
nothing of the program) on the benchmark's own seeded weights. The twin
is four layers ``attention, lightning, lightning, lightning`` with
blocks of 8, top-4, a window of 16, a dense length of 32 and compressed
keys of kernel 4 / stride 2.
"""

import hashlib
import pathlib
import re
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
from benchmark.models import minicpm_sala as ref  # noqa: E402
from conftest import on_host, serve_all_logits  # noqa: E402
from triton_distributed_tpu.kernels import sparse_select as sel  # noqa: E402
from triton_distributed_tpu.kernels.lightning_attention import (  # noqa: E402
    SHORT,
    _span_update,
    decay_slopes,
    lightning_attention,
    lightning_attention_xla,
)
from triton_distributed_tpu.kernels.ragged_paged_attention import (  # noqa: E402
    SELECT_KV_PAGES,
    _build_ragged,
    pack_gqa_rows,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    select_token_rows,
    selected_bits,
)
from triton_distributed_tpu.models import Transformer, presets  # noqa: E402
from triton_distributed_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
)
from triton_distributed_tpu.serving import (  # noqa: E402
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    SpeculativeEngine,
)

pytestmark = pytest.mark.fast

KINDS = ("attention", "lightning", "lightning", "lightning")
SIZE_KEYS = (
    "vocab", "n_layers", "hidden", "ffn", "n_heads", "n_kv_heads",
    "head_dim", "layer_mixer", "lightning_heads", "sparse_kernel",
    "sparse_stride", "sparse_block", "sparse_init_blocks", "sparse_window",
    "sparse_topk", "sparse_dense_len", "rope_theta", "norm_eps",
    "embed_scale", "residual_scale", "logit_divisor")
#: chunk 16 over pages of 16: prompts of 70 and 100 tokens cross five
#: and seven chunk boundaries and the dense length of 32
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=16, npages=64)
PROMPTS = (70, 9, 40, 100, 23)


def tiny_config(**over):
    kw = dict(n_layers=4, layer_mixer=KINDS, rope_layers=(1, 2, 3),
              n_heads=8, n_kv_heads=2, vocab=96)
    kw.update(over)
    return presets.tiny(presets.minicpm_sala(n_layers=4), **kw)


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


def reference_rows(params, sizes, req, max_new):
    seq = np.concatenate([req.prompt,
                          np.asarray(req.generated[:-1], np.int32)])
    return np.asarray(ref.logits_at(
        params, sizes, seq, np.arange(len(seq)), blocked=False))


# ------------------------------------------------- (a) engine == reference


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla_twins", "kernels_interpreted"])
def test_engine_through_states_and_selected_pages_equals_the_reference(
        use_pallas):
    """Chunked prefill across several chunk boundaries, then decode,
    five requests of different lengths through four slots in packed
    steps, contexts on both sides of the dense length: the logits at
    EVERY position equal the reference's full forward. Float32 both
    sides, so the tolerance is accumulation order only (1e-4 against
    logits of size ~3; measured 5e-6)."""
    model, sizes, params = seeded(tiny_config())
    eng, reqs, logits = serve_all_logits(
        model, params, ENGINE, prompts_of(PROMPTS), max_new=6,
        use_pallas=use_pallas)
    for req, got in zip(reqs, logits):
        want = reference_rows(params, sizes, req, 6)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    st = eng.stats
    assert st.state_rows > 0 and st.sparse_rows > 0
    assert st.selected_pages_walked > 0
    # five prompts' chunks and tails, then one-token rows: five decode
    # steps a request
    assert st.selected_rows > st.selected_token_rows >= 5 * 5
    # chunks of 16 take the lightning launch's chunk form; tails of at
    # most SHORT tokens (70 = 4 x 16 + 6, 9 ...) and decode rows its
    # rank-1 form
    assert st.state_rows == st.selected_rows
    assert st.state_rows > st.state_token_rows > st.selected_token_rows
    # one program per rung and width, whatever the contexts
    # (+ 1: the first step sees the pools as init_serving_state placed
    # them, every later one as a step returned them: PERF.md section 7)
    assert eng._step_jit()._cache_size() <= len(eng._rungs()) + 1


def test_param_plan_is_the_programs_init_tree():
    cfg = tiny_config()
    model = one_chip_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        ref.param_plan(sizes_of(cfg)), cfg.param_dtype)
    assert jax.tree.structure(want) == jax.tree.structure(have)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_the_preset_states_the_published_model_and_its_cut():
    whole = presets.minicpm_sala()
    assert whole.n_layers == 32 and len(whole.lightning_layers) == 24
    assert whole.sparse_layers == (0, 9, 16, 17, 22, 29, 30, 31)
    assert whole.rope_layers == whole.lightning_layers
    cut = presets.minicpm_sala(n_layers=8, layer_stride=4)
    assert cut.layer_mixer == (
        "attention", "lightning", "lightning", "lightning") * 2
    # the published depth's scaling, whatever the cut
    assert cut.residual_scale == whole.residual_scale == 1.4 / 32 ** 0.5
    assert (cut.hidden, cut.ffn, cut.n_heads, cut.n_kv_heads, cut.vocab) \
        == (4096, 16384, 32, 2, 73448)
    with pytest.raises(ValueError, match="reaches past the 32 published"):
        presets.minicpm_sala(n_layers=9, layer_stride=4)


# ----------------------------------- (b) chunk form == the recurrence


def _naive_recurrence(q, k, v, state, kv_lens, q_lens, q_starts):
    h, t, d = q.shape
    lam = np.exp(-np.asarray(decay_slopes(h), np.float64))
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    o = np.zeros((h, t, d))
    new = np.asarray(state, np.float64).copy()
    for r in range(len(q_lens)):
        n = int(q_lens[r])
        if not n:
            continue
        for hh in range(h):
            s = new[r, hh] if int(kv_lens[r]) > n else np.zeros((d, d))
            for i in range(int(q_starts[r]), int(q_starts[r]) + n):
                s = lam[hh] * s + np.outer(k[hh, i], v[hh, i])
                o[hh, i] = q[hh, i] / np.sqrt(d) @ s
            new[r, hh] = s
    return o, new


MIXES = pytest.mark.parametrize(
    "mix", [lightning_attention_xla, lightning_attention],
    ids=["xla_twin", "kernel_interpreted"])


def _mixed(mix, seed, q_lens, q_starts, kv_lens, t, block_q, h=4, d=16):
    """One launch on seeded inputs against the float64 recurrence:
    every span's outputs and every state at 1e-4. Returns ``(o, new,
    inputs)``."""
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(h, t, d)), jnp.float32)
               for _ in range(3))
    state = jnp.asarray(rng.normal(size=(len(q_lens), h, d, d)), jnp.float32)
    lens = tuple(jnp.asarray(a, jnp.int32)
                 for a in (kv_lens, q_lens, q_starts))
    o, new = mix(q, k, v, state, *lens, block_q=block_q)
    want_o, want_s = _naive_recurrence(q, k, v, state, *lens)
    for start, n in zip(q_starts, q_lens):
        np.testing.assert_allclose(
            np.asarray(o)[:, start:start + n], want_o[:, start:start + n],
            atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(new), want_s, atol=1e-4, rtol=1e-4)
    for r, n in enumerate(q_lens):
        if not n:       # a slot not batched: untouched, bit for bit
            np.testing.assert_array_equal(np.asarray(new)[r],
                                          np.asarray(state)[r])
    return np.asarray(o), np.asarray(new), (q, k, v, state)


@MIXES
def test_the_chunk_form_is_the_token_by_token_recurrence(mix):
    """Spans of 1, 5 and a whole block, one starting at position 0
    (from zero whatever the slot held), one slot not batched (its state
    untouched): outputs and states equal the recurrence run a token at
    a time in float64 (1e-4: float32 accumulation over 16 positions)."""
    _mixed(mix, 0, (5, 0, 1, 16), (0, 32, 8, 16), (5, 0, 9, 40), 48, 16)


@MIXES
@pytest.mark.parametrize("n", range(1, SHORT + 1))
def test_a_short_span_is_the_recurrence_a_token_at_a_time(mix, n):
    """Spans of ``n`` <= SHORT tokens (the kernel's rank-1 form; the
    twin's chunk form): in a reused slot (the state carried), from
    position 0 (zero state whatever the slot held), beside a slot not
    batched, at the launch's one block (``block_q`` 8) and beside a
    longer block (16: two bodies): the float64 recurrence. The out
    rows past a span that no later row writes are finite (the next
    layer multiplies padding by 0)."""
    q_lens, q_starts, kv_lens = (n, n, 0, 1), (0, 8, 0, 16), (40 + n, n, 0, 7)
    for block_q in (8, 16):
        o, _, _ = _mixed(mix, n, q_lens, q_starts, kv_lens, 40, block_q)
        assert np.isfinite(o[:, :24]).all()


@MIXES
def test_lightning_decode_rows_before_and_after_a_chunk_row_at_block_q_256(
        mix):
    """The launch changes body (and fetch size) at both hand-overs: a
    one-token row, a chunk row of 40 at a block of 256, a one-token
    row, a tail of 5 from position 0, a one-token row; every short
    row's block lies inside the chunk row's and is written after it."""
    _mixed(mix, 3, (1, 40, 1, 5, 1), (0, 8, 48, 56, 64),
           (150, 256, 90, 5, 230), 8 + 256, 256)


def test_a_row_longer_than_short_is_span_update_bit_for_bit():
    """Rows of 9 and 16 tokens beside short rows take the chunk form:
    the launch returns the bits ``_span_update`` returns for the row's
    block, head by head (the function the parent ran for every row)."""
    q_lens, q_starts, kv_lens = (1, 9, 3, 16), (0, 8, 24, 32), (9, 30, 3, 16)
    o, new, (q, k, v, state) = _mixed(
        lightning_attention, 5, q_lens, q_starts, kv_lens, 48, 16)
    slopes = decay_slopes(4)
    chunk = jax.jit(_span_update, static_argnames="scale")
    for r in (1, 3):
        rows = slice(q_starts[r], q_starts[r] + 16)
        for h in range(4):
            want_o, want_s = chunk(
                q[h, rows], k[h, rows], v[h, rows], state[r, h], slopes[h],
                jnp.int32(q_lens[r]), kv_lens[r] == q_lens[r], scale=0.25)
            n = q_lens[r]
            np.testing.assert_array_equal(
                o[h, rows][:n], np.asarray(want_o)[:n])
            np.testing.assert_array_equal(new[r, h], np.asarray(want_s))


def test_the_references_blocked_evaluation_is_its_one_shot_evaluation(
        monkeypatch):
    """The reference's chunked recurrence, blocked queries and blocked
    rows give what its token-by-token, all-at-once lines give."""
    cfg = tiny_config()
    _, sizes, params = seeded(cfg)
    monkeypatch.setattr(ref, "ROW_BLOCK", 16)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    monkeypatch.setattr(ref, "SEQ_BUCKET", 32)
    ref._logits.clear_cache()
    tokens = prompts_of([83])[0]
    rows = np.arange(83)
    try:
        blocked = ref.logits_at(params, sizes, tokens, rows)
    finally:
        ref._logits.clear_cache()
    whole = ref.logits_at(params, sizes, tokens, rows, blocked=False)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------- (c) a slot used again


def test_a_reused_slot_starts_from_zero_state_and_fresh_compressed_keys():
    """One slot, two requests one after the other: the second finds the
    first's recurrent state, pages and compressed keys in its slot and
    serves the reference's logits all the same."""
    model, sizes, params = seeded(tiny_config())
    ecfg = EngineConfig(slots=1, token_budget=32, chunk=16, page=16,
                        npages=8)
    eng, reqs, logits = serve_all_logits(
        model, params, ecfg, prompts_of((75, 52)), max_new=4)
    assert eng.cfg.slots == 1
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req, 4), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("engine", ["ids", "all_positions"])
def test_the_device_arg_max_serves_the_tokens_the_host_argmax_serves(engine):
    """A greedy engine takes the arg-max of each logits row on the
    device and one token id a row comes down, not the logits
    (``EngineConfig.greedy_on_device`` decides nothing any more); an
    engine with ``host_logits`` fetches the logits and takes it on the
    host. The served streams are the same, also where the host-side
    engine's step hands out logits at every packed position."""
    import dataclasses

    model, _, params = seeded(tiny_config())

    Host = on_host(ServingEngine)
    if engine == "all_positions":
        class Host(Host):
            def _step_jit(self):
                return self.model._serving_all_logits_jit

            def _advance_row(self, s, req, take, logits, q_starts, q_lens):
                at = np.full((len(q_lens),), q_starts[s] + take - 1)
                return super()._advance_row(
                    s, req, take, logits[at], q_starts, q_lens)

    served = []
    for cls, flag in ((Host, False), (ServingEngine, False),
                      (ServingEngine, True)):
        eng = cls(
            model, params, dataclasses.replace(ENGINE, greedy_on_device=flag),
            use_pallas=False, propagate_failures=True)
        assert (eng._greedy is None) == (cls is Host)
        reqs = [Request(rid=i, prompt=p, max_new=5)
                for i, p in enumerate(prompts_of(PROMPTS))]
        assert eng.run(reqs).completed == len(reqs)
        assert (eng.stats.lookahead_steps > 0) == (cls is not Host)
        served.append([r.generated for r in reqs])
    assert served[0] == served[1] == served[2]
    # the global layers' walk is not counted where every attention
    # layer walks a selection
    assert eng.stats.global_pages_walked == 0
    assert eng.stats.selected_pages_walked > 0


def test_a_layout_that_repeats_is_uploaded_once():
    """Consecutive decode-only steps over the same rows hand the step
    the SAME device arrays for rows, starts, lengths and topology (an
    upload costs the host ~0.3 ms whatever its size); a step with
    another layout uploads anew, and the streams are what they were."""
    model, _, params = seeded(tiny_config())
    eng = ServingEngine(model, params, ENGINE, use_pallas=False,
                        propagate_failures=True)
    reqs = [Request(rid=i, prompt=p, max_new=12)
            for i, p in enumerate(prompts_of((9, 23)))]
    for r in reqs:
        eng.submit(r)
    seen = []
    while not eng.idle:
        eng.step()
        seen.append({k: id(v[1]) for k, v in eng._uploads.items()})
    # (a decode-only step's tokens all come from the device: the host's
    # upload is zeros and the slots they come from, which repeat too)
    assert set(seen[-1]) == {"tokens", "token_src", "token_rows",
                             "q_starts", "q_lens", "topo"}
    # the last steps are decode-only over both rows, then over one;
    # the last call of all launches nothing (it retires the step in
    # flight) and uploads nothing
    assert seen[-3] == seen[-4] == seen[-5] != seen[-2] == seen[-1]
    assert seen[0]["q_lens"] != seen[-1]["q_lens"]
    assert len({s["topo"] for s in seen}) <= 2       # one a packed width
    again = ServingEngine(model, params, ENGINE, use_pallas=False)
    again._uploaded = lambda name, host: jnp.asarray(host)
    fresh = [Request(rid=i, prompt=p, max_new=12)
             for i, p in enumerate(prompts_of((9, 23)))]
    again.run(fresh)
    assert [r.generated for r in fresh] == [r.generated for r in reqs]


def test_greedy_on_device_keeps_the_non_finite_check():
    from triton_distributed_tpu.serving.engine import _greedy_tokens

    rows = np.zeros((4, 7), np.float32)
    rows[0, 3] = rows[0, 5] = 2.0      # the first of equals
    rows[1, 2] = np.nan
    rows[2, 6] = np.inf
    rows[3, :] = -1.0
    assert np.asarray(_greedy_tokens(jnp.asarray(rows))).tolist() == [
        3, -1, -1, 0]
    model, _, params = seeded(tiny_config())
    # the flag decides nothing: sampling keeps the logits on the host
    assert ServingEngine(model, params, EngineConfig(
        slots=2, token_budget=32, chunk=16, page=16, npages=8,
        greedy_on_device=True, temperature=0.7))._greedy is None
    eng = ServingEngine(model, params, EngineConfig(
        slots=2, token_budget=32, chunk=16, page=16, npages=8),
        use_pallas=False)
    with pytest.raises(FloatingPointError, match="non-finite"):
        eng._sample(np.int32(-1), Request(rid=0, prompt=np.zeros(3, np.int32)))


def test_an_evicted_request_rebuilds_its_state_by_the_recompute():
    """A pool too small for the three requests together: the evicted
    one re-prefills from position 0, which zeroes its slot's recurrent
    state and rewrites its compressed keys; every served position still
    carries the reference's logits."""
    model, sizes, params = seeded(tiny_config())
    ecfg = EngineConfig(slots=3, token_budget=64, chunk=16, page=16,
                        npages=10)
    # 2 pages each at admission, 5 each by the end: growth evicts
    eng, reqs, logits = serve_all_logits(
        model, params, ecfg, prompts_of((30, 28, 26)), max_new=44)
    assert eng.stats.evictions > 0
    for req, got in zip(reqs, logits):
        np.testing.assert_allclose(
            got, reference_rows(params, sizes, req, 44), atol=1e-4,
            rtol=1e-4)


# ---------------------------------------------------- (d) the selection


def test_forced_blocks_are_in_exactly_topk_are_chosen_and_ties_go_low():
    scores = jnp.asarray([[0.5, 0.9, 0.9, 0.1, 0.9, 0.2, 0.0, 0.3]])
    forced = jnp.asarray([[True, False, False, False, False, False, False,
                           True]])
    chosen = np.asarray(sel.choose_blocks(scores, forced, 4))
    # the two forced, then the two LOWER of the three tied at 0.9
    assert chosen.tolist() == [[True, True, True, False, False, False,
                                False, True]]
    rng = np.random.default_rng(3)
    scores = jnp.asarray(rng.integers(0, 4, (50, 2, 24)), jnp.float32)
    forced = rng.random((50, 2, 24)) < 0.1
    forced = jnp.asarray(forced & (np.cumsum(forced, -1) <= 4))
    chosen = np.asarray(sel.choose_blocks(scores, forced, 6))
    assert (chosen.sum(-1) == 6).all()
    assert (chosen | ~np.asarray(forced)).all()
    # window and initial blocks of a query at position 70, blocks of 8
    f = np.asarray(sel.forced_blocks(jnp.asarray([70]), 12, block=8,
                                     init_blocks=1, window=16))[0]
    assert np.nonzero(f)[0].tolist() == [0, 6, 7, 8]


@pytest.mark.parametrize("scores", ["random", "tied", "all_equal",
                                    "half_unseen", "negative"])
def test_the_threshold_search_chooses_what_a_sort_chooses(scores):
    """``choose_blocks`` finds the topk-th largest value bit by bit and
    ranks its ties, without a sort: the chosen SET is ``lax.top_k``'s
    (stable: ties to the lower block), also where fewer than topk
    blocks hold a finite score."""
    rng = np.random.default_rng(11)
    sc = rng.random((9, 2, 64)).astype(np.float32)
    if scores == "tied":
        sc = np.round(sc * 4) / 4
    elif scores == "all_equal":
        sc[:] = 0.0
    elif scores == "half_unseen":
        sc[..., 5:] = -np.inf
    elif scores == "negative":
        sc = -sc
    forced = jnp.asarray(rng.random((9, 1, 64)) < 0.1)
    for topk in (1, 8, 63, 64, 100):
        _, ids = jax.lax.top_k(jnp.where(forced, jnp.inf, sc), min(topk, 64))
        want = np.zeros((9, 2, 64), bool)
        np.put_along_axis(want, np.asarray(ids), True, axis=-1)
        got = jax.jit(sel.choose_blocks, static_argnums=2)(
            jnp.asarray(sc), forced, topk)
        np.testing.assert_array_equal(np.asarray(got), want)


def _random_sparse_step(seed, lens, takes, *, page=16, pps=8, hkv=2, g=4,
                        d=16):
    """A packed step over random pools: rows of ``lens`` tokens of
    context of which the last ``takes`` are this step's."""
    cfg = tiny_config()
    rng = np.random.default_rng(seed)
    r = len(lens)
    npages = r * pps
    table = jnp.asarray(rng.permutation(npages).reshape(r, pps), jnp.int32)
    kp, vp = (jnp.asarray(rng.normal(size=(npages, hkv, page, d)),
                          jnp.float32) for _ in range(2))
    starts, at = [], 0
    for n in takes:
        starts.append(at)
        at += -(-n // 8) * 8
    t = at + max(takes)
    t = -(-t // 8) * 8
    rows = np.zeros((t,), np.int32)
    pos = np.full((t,), -1, np.int32)
    for i, (n, ln, s) in enumerate(zip(takes, lens, starts)):
        rows[s:s + n] = i
        pos[s:s + n] = np.arange(ln - n, ln)
    q = jnp.asarray(rng.normal(size=(t, hkv * g, d)), jnp.float32)
    kw = dict(kernel=cfg.sparse_kernel, stride=cfg.sparse_stride)
    # the compressed keys of each row's whole K, by the incremental path
    kc = jnp.zeros((npages, hkv, page // cfg.sparse_stride, d), jnp.float32)
    lens_a = jnp.asarray(lens, jnp.int32)
    kc = sel.append_compressed(kc, kp, table, lens_a, lens_a,
                               block_q=max(lens), **kw)
    return dict(cfg=cfg, table=table, kp=kp, vp=vp, kc=kc, q=q,
                rows=jnp.asarray(rows), pos=jnp.asarray(pos), lens=lens_a,
                takes=jnp.asarray(takes, jnp.int32),
                starts=jnp.asarray(starts, jnp.int32), page=page, g=g)


def _select(step, **over):
    c = step["cfg"]
    kw = dict(group=step["g"], page=step["page"], kernel=c.sparse_kernel,
              stride=c.sparse_stride, block=c.sparse_block,
              init_blocks=c.sparse_init_blocks, window=c.sparse_window,
              topk=c.sparse_topk, dense_len=c.sparse_dense_len)
    kw.update(over)
    return sel.select_blocks(
        step["q"], step["kc"], step["table"], step["rows"], step["pos"],
        step["lens"], step["takes"], step["starts"], **kw)


def _gathered_k(step, row):
    table = np.asarray(step["table"])[row]
    return np.asarray(step["kp"])[table].transpose(0, 2, 1, 3).reshape(
        -1, step["kp"].shape[1], step["kp"].shape[-1])


def test_the_selection_is_the_references_on_the_same_scores():
    """Rows at contexts 100 and 57 (past the dense length of 32), one
    at 20 (below), a chunk of 16 and two decode rows: each position's
    chosen blocks are those the reference chooses from the same keys."""
    step = _random_sparse_step(5, lens=(100, 20, 57), takes=(16, 1, 1))
    c = step["cfg"]
    pages, counts, bits = (np.asarray(a) for a in _select(step))
    sizes = sizes_of(c)
    hkv, g = 2, step["g"]
    words = bits.reshape(hkv, -1, g, bits.shape[-1])[:, :, 0]
    for row, (ln, n, s) in enumerate(zip((100, 20, 57), (16, 1, 1),
                                         np.asarray(step["starts"]))):
        k = jnp.asarray(_gathered_k(step, row)[:ln])
        kc = ref._compressed_keys(k, sizes)
        at = jnp.arange(ln - n, ln)
        qb = step["q"][s:s + n].reshape(n, hkv, g, -1)
        nb = -(-ln // c.sparse_block)
        want = np.asarray(ref._chosen_blocks(qb, at, kc, sizes, nb))
        got = np.stack([
            (words[:, s:s + n, b // 32] >> (b % 32)) & 1 for b in range(nb)
        ], axis=-1).transpose(1, 0, 2).astype(bool)          # (n, Hkv, nb)
        np.testing.assert_array_equal(got, want)
        # and the page list is the union of the positions' choices
        for h in range(hkv):
            union = sorted({b * c.sparse_block // step["page"]
                            for b in np.nonzero(want[:, h].any(0))[0]})
            assert pages[row, h, :counts[row, h]].tolist() == union
    assert (counts[1] == 2).all()        # 20 tokens, dense: both pages


@pytest.mark.parametrize("attend", ["xla_twin", "kernel_interpreted"])
def test_with_every_block_chosen_the_selected_walk_is_the_dense_walk(attend):
    """top-k >= the number of blocks: the selection is every visible
    block, and the selected walk gives the dense kernel's output."""
    step = _random_sparse_step(9, lens=(100, 20, 57), takes=(16, 1, 8))
    chosen = _select(step, topk=64)
    c = step["cfg"]
    qp = pack_gqa_rows(step["q"], 2)
    args = (qp, step["kp"], step["vp"], step["lens"], step["takes"],
            step["starts"], step["table"])
    dense, _ = ragged_paged_attention(
        *args, group=step["g"], block_q=16, with_lse=False)
    if attend == "xla_twin":
        got, _ = ragged_paged_attention_xla(
            *args, group=step["g"], selected=chosen,
            select_block=c.sparse_block)
    else:
        got, _ = ragged_paged_attention(
            *args, group=step["g"], block_q=16, with_lse=False,
            selected=chosen, select_block=c.sparse_block)
    g = step["g"]
    for n, s in zip((16, 1, 8), np.asarray(step["starts"])):
        np.testing.assert_allclose(
            np.asarray(got)[:, s * g:(s + n) * g],
            np.asarray(dense)[:, s * g:(s + n) * g], atol=2e-5, rtol=2e-5)


def test_the_selected_kernel_is_its_twin_on_a_sparse_selection():
    step = _random_sparse_step(11, lens=(100, 40, 57, 128),
                               takes=(16, 1, 8, 3))
    chosen = _select(step)
    c = step["cfg"]
    qp = pack_gqa_rows(step["q"], 2)
    args = (qp, step["kp"], step["vp"], step["lens"], step["takes"],
            step["starts"], step["table"])
    kw = dict(group=step["g"], selected=chosen, select_block=c.sparse_block)
    want, _ = ragged_paged_attention_xla(*args, **kw)
    got, _ = ragged_paged_attention(*args, block_q=16, with_lse=False, **kw)
    g = step["g"]
    for n, s in zip((16, 1, 8, 3), np.asarray(step["starts"])):
        np.testing.assert_allclose(
            np.asarray(got)[:, s * g:(s + n) * g],
            np.asarray(want)[:, s * g:(s + n) * g], atol=2e-5, rtol=2e-5)


# ------------------------- (d2) a one-token row's tile of the walk

KB = SELECT_KV_PAGES


def _listed_step(seed, lens, takes, n_listed, *, g=4, dtype=jnp.float32,
                 block_q=8, page=16, pps=16, hkv=2, d=16, block=8):
    """A packed step with a selection made by hand, so that a one-token
    row lists exactly ``n_listed[row]`` pages (None: every page it
    holds, the contiguous list of a row below the dense length; a row
    of more tokens chooses each position's own block and a random half
    of those before it). Returns the kernel's arguments, its
    ``selected`` and the rows' packed spans."""
    rng = np.random.default_rng(seed)
    r, npages, bpp = len(lens), len(lens) * pps, page // block
    table = jnp.asarray(rng.permutation(npages).reshape(r, pps), jnp.int32)
    kp, vp = (jnp.asarray(rng.normal(size=(npages, hkv, page, d)), dtype)
              for _ in range(2))
    starts, at = [], 0
    for n in takes:
        starts.append(at if n else 0)
        at += -(-n // 8) * 8
    # as wide as if every row were batched: steps of one ``block_q``
    # share their programs
    t = 8 * (r - 1) + -(-max(takes) // 8) * 8 + block_q
    chosen = np.zeros((t, hkv, pps * bpp), bool)
    pages = np.zeros((r, hkv, pps), np.int32)
    counts = np.zeros((r, hkv), np.int32)
    for i, (ln, n, s, want) in enumerate(zip(lens, takes, starts, n_listed)):
        for h in range(hkv if n else 0):
            held = -(-ln // page)
            if n == 1 and want is not None:
                # ``want`` distinct pages, the last held among them
                # (the token's own), one or both blocks of each
                listed = np.append(rng.choice(
                    held - 1, want - 1, replace=False), held - 1)
                for pg in listed:
                    chosen[s, h, pg * bpp + rng.choice(
                        bpp, 1 + rng.integers(bpp), replace=False)] = True
                chosen[s, h, (ln - 1) // block] = True
            for k in range(n if n > 1 or want is None else 0):
                own = (ln - n + k) // block
                chosen[s + k, h, :own + 1] = (
                    want is None or rng.random(own + 1) < 0.5)
                chosen[s + k, h, own] = True
            pg = np.unique(np.nonzero(chosen[s:s + n, h].any(0))[0] // bpp)
            pg = pg[pg < held]
            pages[i, h, :len(pg)], counts[i, h] = pg, len(pg)
    q = jnp.asarray(rng.normal(size=(t, hkv * g, d)), dtype)
    lens_a, takes_a, starts_a = (
        jnp.asarray(a, jnp.int32) for a in (lens, takes, starts))
    args = (pack_gqa_rows(q, hkv), kp, vp, lens_a, takes_a, starts_a, table)
    sel_ = (jnp.asarray(pages), jnp.asarray(counts),
            selected_bits(jnp.asarray(chosen), g))
    spans = [(s * g, (s + n) * g) for s, n in zip(starts, takes) if n]
    return args, dict(group=g, selected=sel_, select_block=block), spans


def _kernel_is_twin(args, kw, spans, block_q, tol):
    want, _ = ragged_paged_attention_xla(*args, **kw)
    got, _ = ragged_paged_attention(
        *args, block_q=block_q, with_lse=False, **kw)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    for lo, hi in spans:
        np.testing.assert_allclose(
            got[:, lo:hi], want[:, lo:hi], atol=tol, rtol=tol)
    return got


@pytest.mark.parametrize("g,dtype,tok", [
    (16, jnp.bfloat16, 1), (8, jnp.bfloat16, 2), (4, jnp.float32, 2)],
    ids=["g16_bf16", "g8_bf16", "g4_f32"])
def test_a_one_token_row_walks_its_tokens_rows_against_key_blocks(
        g, dtype, tok):
    """Five decode rows that list 1, kb - 1, kb, kb + 1 and 2 kb + 1
    pages (a block's masked tail, a pair handing over to the next at
    every fill of its last block) at the group sizes and dtypes that
    decide the tile: ``tok`` tokens' rows, the fewest that fill the
    dtype's sublane tile."""
    assert select_token_rows(g, dtype, 8) == tok
    n_listed = (1, KB - 1, KB, KB + 1, 2 * KB + 1)
    args, kw, spans = _listed_step(
        21, lens=(9, 100, 177, 250, 203), takes=(1,) * 5,
        n_listed=n_listed, g=g, dtype=dtype)
    assert np.asarray(kw["selected"][1]).tolist() == [[n, n] for n in n_listed]
    _kernel_is_twin(args, kw, spans, 8,
                    2e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("n_pages", [1, KB - 1, KB, KB + 1, 2 * KB + 1])
def test_a_pair_hands_over_to_the_next_at_any_fill_of_its_last_block(
        n_pages):
    """A decode row listing ``n_pages`` between two others: its first
    key block was started by the pair before it, and its last starts
    the next pair's query rows and first block."""
    args, kw, spans = _listed_step(
        30 + n_pages, lens=(120, 256, 77, 0, 0), takes=(1, 1, 1, 0, 0),
        n_listed=(KB + 2, n_pages, 2, None, None))
    _kernel_is_twin(args, kw, spans, 8, 2e-5)


def test_a_row_below_the_dense_length_beside_one_above_it():
    """A decode row that lists every page it holds, every block of it
    (the contiguous list the selection gives below the dense length),
    between two that list a choice."""
    args, kw, spans = _listed_step(
        13, lens=(100, 20, 200, 47, 31), takes=(1,) * 5,
        n_listed=(3, None, KB + 1, None, 2))
    assert np.asarray(kw["selected"][1])[:, 0].tolist() == [3, 2, KB + 1, 3, 2]
    _kernel_is_twin(args, kw, spans, 8, 2e-5)


def test_decode_rows_before_and_after_a_chunk_row_at_block_q_256():
    """The fetch ahead changes size at both hand-overs: a one-token
    pair starts a chunk pair's 256-token query block and single page,
    the chunk pair a one-token pair's rows and key block; a tail of 5
    tokens takes the short tile between them."""
    args, kw, spans = _listed_step(
        41, lens=(150, 256, 90, 61, 230), takes=(1, 40, 1, 5, 1),
        n_listed=(KB + 1, None, 3, None, 2 * KB), block_q=256)
    _kernel_is_twin(args, kw, spans, 256, 2e-5)


def test_a_batch_whose_first_rows_are_not_batched():
    """Rows outside the batch (``q_lens`` 0, ``counts`` 0) come first:
    the first ACTIVE pair is warmed, at its own size."""
    args, kw, spans = _listed_step(
        43, lens=(0, 0, 140, 0, 33), takes=(0, 0, 1, 0, 1),
        n_listed=(None, None, KB + 1, None, 2))
    assert np.asarray(kw["selected"][1]).tolist()[:2] == [[0, 0], [0, 0]]
    _kernel_is_twin(args, kw, spans, 8, 2e-5)


def test_rows_of_two_to_eight_tokens_keep_their_walk_bit_for_bit():
    """Rows of 2, 5 and 8 tokens beside decode rows: at ``block_q`` 16
    they walk (and now fetch) the short tile of 8 tokens, a page an
    iteration, and give the bits the launch's own block of 8 gives
    them at ``block_q`` 8: the walk PR 45 found, whatever the decode
    rows beside them do."""
    args, kw, spans = _listed_step(
        47, lens=(130, 64, 250, 99, 18), takes=(2, 1, 5, 1, 8),
        n_listed=(None, 3, None, KB + 1, None), block_q=16)
    at16 = _kernel_is_twin(args, kw, spans, 16, 2e-5)
    at8 = _kernel_is_twin(args, kw, spans, 8, 2e-5)
    for lo, hi in (spans[0], spans[2], spans[4]):
        np.testing.assert_array_equal(at16[:, lo:hi], at8[:, lo:hi])


def _assembled(model):
    """An engine of the tiny ``model`` kind (``sparse``: the lightning +
    block-sparse twin; ``dense``; ``kda``) after one ``_assemble`` of
    prompts of 1, 40, 1 and 9 tokens at a chunk of 16."""
    if model == "sparse":
        mdl, _, params = seeded(tiny_config())
    else:
        mdl = one_chip_model(
            presets.tiny() if model == "dense" else presets.tiny(
                presets.solar_open2(n_layers=4), n_layers=4,
                layer_mixer=("kda", "kda", "kda", "attention"),
                moe_layers=(0, 1, 2, 3), n_heads=8, n_kv_heads=2, vocab=96))
        params = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(mdl.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(mdl, params, ENGINE, use_pallas=False)
    for i, p in enumerate(prompts_of((1, 40, 1, 9))):
        eng.submit(Request(rid=i, prompt=p, max_new=2, arrival=0))
    eng._admit()
    eng._assemble()
    return eng


@pytest.mark.parametrize("model", ["sparse", "dense"])
def test_assemble_counts_the_rows_of_the_selected_walk(model):
    """``selected_rows`` counts the rows ``_assemble`` batches on a
    model with sparse layers and ``selected_token_rows`` those of ONE
    token (a prompt's tail of one token is one too); a dense model
    reads 0 / 0. (Booked into ``EngineStats`` with the step's other
    counts: the engine test above reads them there.)"""
    eng = _assembled(model)
    assert (*eng._selected_work, eng._state_work[0] > 0) == (
        (4, 2, True) if model == "sparse" else (0, 0, False))


@pytest.mark.parametrize("model,rows,short", [
    ("sparse", 4, 2), ("dense", 0, 0), ("kda", 4, 0)])
def test_assemble_counts_the_short_rows_of_the_lightning_launch(
        model, rows, short):
    """Of ``state_rows``, ``state_token_rows`` counts those a lightning
    layer's launch runs in its rank-1 form (at most SHORT tokens: the
    prompts of one token; the chunks of 16 and the 9-token prompt take
    the chunk form); a dense model has no state rows, a kda model's are
    another kernel's."""
    eng = _assembled(model)
    assert tuple(eng._state_work[2:]) == (rows, short)


# --------------------------------------------- (e) compressed keys


@pytest.mark.parametrize("chunks", [
    (100,), (16,) * 6 + (4,), (1,) * 40, (3, 13, 16, 1, 31, 2, 17),
    (15, 1, 16, 16, 33)], ids=["whole", "pages", "tokens", "ragged", "odd"])
def test_compressed_keys_kept_incrementally_are_those_of_the_whole_k(chunks):
    """Append in chunks that end before, on and after page boundaries
    (page 16, kernel 4, stride 2): after every chunk the pool holds the
    mean-pooled keys of exactly the windows that fit, equal to those
    computed from the whole K."""
    cfg = tiny_config()
    sizes = sizes_of(cfg)
    page, pps, hkv, d = 16, 8, 2, 16
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.permutation(2 * pps).reshape(2, pps), jnp.int32)
    k_all = rng.normal(size=(sum(chunks), hkv, d)).astype(np.float32)
    kp = np.zeros((2 * pps, hkv, page, d), np.float32)
    kc = jnp.full((2 * pps, hkv, page // 2, d), np.nan, jnp.float32)
    done = 0
    for n in chunks:
        for p in range(done, done + n):
            kp[int(table[1, p // page]), :, p % page] = k_all[p]
        done += n
        # slot 1 appends, slot 0 is not batched
        kc = sel.append_compressed(
            kc, jnp.asarray(kp), table, jnp.asarray([0, done], jnp.int32),
            jnp.asarray([0, n], jnp.int32), kernel=4, stride=2,
            block_q=max(chunks))
        want = np.asarray(ref._compressed_keys(jnp.asarray(k_all[:done]),
                                               sizes))
        held = np.asarray(kc)[np.asarray(table)[1]].transpose(0, 2, 1, 3) \
            .reshape(-1, hkv, d)
        np.testing.assert_allclose(held[:len(want)], want, atol=1e-6)
        # nothing beyond the windows that fit, nothing in slot 0's pages
        assert np.isnan(held[len(want):]).all()
        assert np.isnan(np.asarray(kc)[np.asarray(table)[0]]).all()


# -------------------------------------- (f) selected=None is today's


def test_selected_none_is_todays_kernel():
    """``selected=None`` builds the contiguous walk's launch, its old
    name and body; a selection is a launch of its own whose name a
    search for the kernel's still finds."""
    step = _random_sparse_step(4, lens=(40, 20), takes=(8, 1))
    qp = pack_gqa_rows(step["q"], 2)
    args = (qp, step["kp"], step["vp"], step["lens"], step["takes"],
            step["starts"], step["table"])

    def text(**kw):
        return str(jax.make_jaxpr(lambda *a: ragged_paged_attention(
            *a, group=step["g"], block_q=8, with_lse=False, **kw))(*args))

    plain, none = text(), text(selected=None)
    assert plain == none
    assert "ragged_paged_attention_selected" not in plain
    chosen = _select(step)
    assert "ragged_paged_attention_selected" in text(
        selected=chosen, select_block=step["cfg"].sparse_block)
    a = ragged_paged_attention(*args, group=step["g"], block_q=8)
    b = ragged_paged_attention(*args, group=step["g"], block_q=8,
                               selected=None)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the builder's cache key did not grow: the same object both ways
    key = (2, 8, 16, 24, 2, 4, 16, 16, 8, "float32", False, 0.25, 0.0, 2,
           None, (), 0, False, None)
    assert _build_ragged(*key) is _build_ragged(*key)
    for bad, match in ((dict(select_block=5), "select_block"),
                       (dict(select_block=8, window=4), "window"),
                       (dict(select_block=8, with_lse=True), "with_lse")):
        with pytest.raises(ValueError, match=match):
            ragged_paged_attention(
                *args, group=step["g"], block_q=8,
                **{"with_lse": False, "selected": chosen, **bad})


# ------------------------------------------------------ (g) refusals


def _refusals():
    base = tiny_config()
    mesh1 = lambda: Mesh(np.asarray(jax.devices()[:1]), ("x",))  # noqa: E731
    mesh2 = lambda: Mesh(np.asarray(jax.devices()[:2]), ("x",))  # noqa: E731

    def engine(**kw):
        model, _, params = seeded(base)
        return ServingEngine(model, params, EngineConfig(
            slots=2, token_budget=32, chunk=16, page=16, npages=16, **kw))

    def speculative():
        model, _, params = seeded(base)
        return SpeculativeEngine(model, params, EngineConfig(
            slots=2, token_budget=32, chunk=16, page=16, npages=16),
            spec_k=2)

    def disaggregated():
        model, _, params = seeded(base)
        return DisaggregatedEngine(model, params, model, params, EngineConfig(
            slots=2, token_budget=32, chunk=16, page=16, npages=16))

    def ship():
        model, _, params = seeded(base)
        return ServingEngine(model, params, EngineConfig(
            slots=2, token_budget=32, chunk=16, page=16, npages=16)
        ).gather_pages([0])

    return {
        "prefix_cache": (lambda: engine(prefix_cache=True),
                         "prefix_cache / prefix_share"),
        "prefix_share": (
            lambda: engine(prefix_cache=True, prefix_share=True),
            "prefix_cache / prefix_share"),
        "speculative": (speculative, "SpeculativeEngine"),
        "prefill_only": (lambda: engine(prefill_only=True), "prefill_only"),
        "disaggregated": (disaggregated, "DisaggregatedEngine"),
        "kv_ship": (ship, "kv_ship / page migration"),
        "tp": (lambda: Transformer(base, mesh2(), tp_axis="x"), "tp=2"),
        "cp": (lambda: Transformer(
            base, Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                       ("x", "c")), tp_axis="x", cp_axis="c"), "cp=2"),
        "kv_quant": (lambda: tiny_config(kv_quant="int8"), "kv_quant"),
        "sliding_window": (
            lambda: tiny_config(
                layer_attn=("sliding", "full", "full", "full"), window=8),
            "sliding-window layers"),
        "forward": (
            lambda: Transformer(base, mesh1(), tp_axis="x").forward(
                None, jnp.zeros((1, 8), jnp.int32)),
            "layer_mixer, sparse_topk, out_gate, out_norm, embed_scale, "
            "residual_scale, logit_divisor"),
        "lightning_heads": (
            lambda: TransformerConfig(n_layers=2, layer_mixer=(
                "attention", "lightning")), "lightning_heads"),
        "sparse_sizes": (
            lambda: TransformerConfig(sparse_topk=4), "sparse_topk=4 needs"),
        "page": (
            lambda: one_chip_model(base).init_serving_state(2, 8, 12),
            "needs a page"),
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_the_new_state_cannot_serve_is_refused_by_name(what):
    build, match = _refusals()[what]
    with pytest.raises(ValueError, match=match):
        build()


# ---------------------- (h) the accepted configurations' programs


def _step_texts(cfg, ecfg) -> tuple:
    """StableHLO of the rung-8 step of ``cfg`` by its XLA twins (no
    kernel body, so no source line is in it), debug locations off:
    lowered from ``_step_args`` as the engine calls it, and with the
    host's upload of the tokens in the place of the merged ``tokens``
    (what a step was handed before PR 34)."""
    model = one_chip_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
    if cfg.moe_weight_quant or cfg.dense_weight_quant:
        params = model.quantize_dense_weights(
            model.quantize_moe_weights(params))
    eng = ServingEngine(model, params, ecfg, use_pallas=False)
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new=2, arrival=0))
    eng._admit()
    *arrays, _, _ = eng._assemble()
    args = list(eng._step_args(tuple(arrays), 8))
    as_engine = eng._step_jit().lower(*args).as_text()
    args[2] = jnp.asarray(arrays[0])
    return as_engine, eng._step_jit().lower(*args).as_text()


# per configuration: the digest through the engine's path (PR 34), and
# with the uploaded tokens (taken on the tree before PR 33). RE-TAKEN IN
# PR 40, which changes these three programs on purpose: their expert
# layers, at one rank, sort once and exchange with nobody
# (``tests/test_moe_local.py`` holds the new block to the old results).
# Before: 82dab0446a04e3f4 / 57ca03e7507824f3, 8c8a6a0b5e338ae8 /
# ee0bb4b9c115ff09, 7ae6d2b45eac2673 / b82a79e936396471
ACCEPTED = {
    "dsmoe16b": (lambda: presets.tiny(presets.deepseek_moe_16b()),
                 "1cb5357bf36f5ab5", "620b044c34f39bba"),
    "mixtral8x7b": (lambda: presets.tiny(presets.mixtral_8x7b()),
                    "5b29d8790cd7dd07", "479209065905fea7"),
    "kexaone236b": (lambda: presets.tiny(
        presets.k_exaone_236b(), n_layers=5,
        layer_attn=("sliding", "sliding", "sliding", "full", "sliding"),
        rope_layers=(0, 1, 2, 4), moe_layers=(1, 2, 3, 4), window=16,
        num_experts=8, experts_held=4, first_expert_held=2),
        "fbd6fa9258d83387", "c03340e322b6ca17"),
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_the_accepted_configurations_lower_the_programs_they_lowered(name):
    """The new fields default to the model that was there: the step
    program of each accepted configuration's twin is, instruction for
    instruction, the one the tree before PR 33 lowered but for PR 40's
    expert layer (the second digest was taken there, with the host's
    upload as ``tokens``, and again under PR 40), and holds none of the
    new scopes or launches. As the ENGINE calls it
    (the first digest, taken under PR 34) ``tokens`` is the array merged
    on the device, a committed one: that argument of ``@main``, alone,
    carries a replicated-sharding attribute, the private functions are
    numbered from another start, and dsmoe's lowering shares three
    helper bodies (``_where``, ``round``, ``clip``) it printed twice."""
    build, engine_digest, upload_digest = ACCEPTED[name]
    as_engine, with_upload = _step_texts(build(), EngineConfig(
        slots=4, token_budget=64, chunk=16, page=16, npages=32))
    committed = " {sdy.sharding = #sdy.sharding<@mesh, [{}]>}"
    main = {t: next(ln for ln in t.splitlines() if "@main(" in ln)
            for t in (as_engine, with_upload)}
    changed = [(a, b) for a, b in zip(*(
        re.split(r", (?=%arg\d+:)", main[t])
        for t in (with_upload, as_engine))) if a != b]
    assert len(changed) == 1 and changed[0][1] == changed[0][0] + committed
    for text, digest in ((as_engine, engine_digest),
                         (with_upload, upload_digest)):
        for new in ("linear_attn", "sparse_select", "out_gate",
                    "lightning_attention",
                    "ragged_paged_attention_selected"):
            assert new not in text
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
