"""The packed width of a serving step follows its BATCH: a decode-only
step (every row fits the low rung's block) is ``live + 8`` rows wide, a
step that holds a chunk launches at the cap and takes the narrowest
width of a short ladder that covers ``q_starts[s] + block(s)`` of its
batched rows (``Transformer.step_rows_needed``); a slot outside the
batch sits at row 0 and is skipped. A rung's first launch builds every
width's program; the tokens served are the plain references' and the
widest step's.

CPU sizes, mostly the XLA twins (``use_pallas=False``); the kernels at
narrow widths, interpreted, in the cases marked so and in
``test_kv_append`` / ``test_window_share`` / ``test_mla`` /
``test_sala`` / ``test_kda``.
"""

import dataclasses
import json
import pathlib
import sys

import jax
import numpy as np
import pytest
from conftest import force_fused_ctx
from jax.sharding import Mesh
from oracle import greedy_tokens

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import program, weights  # noqa: E402
from benchmark.models import exaone_moe as ref  # noqa: E402
import test_kda  # noqa: E402
import test_mla  # noqa: E402
import test_sala  # noqa: E402
from conftest import serve_all_logits  # noqa: E402
from test_serving_step import CFG, _model  # noqa: E402
from test_window_share import sizes_of, tiny_config  # noqa: E402
from triton_distributed_tpu.kernels import moe_utils as mu  # noqa: E402
from triton_distributed_tpu.models import Transformer  # noqa: E402
from triton_distributed_tpu.models.transformer import (  # noqa: E402
    expert_block_m,
)
from triton_distributed_tpu.serving import (  # noqa: E402
    EngineConfig,
    Request,
    ServingEngine,
    SpeculativeEngine,
)
from triton_distributed_tpu.serving.engine import (  # noqa: E402
    chunk_widths,
    live_rows,
    packed_width,
)
from triton_distributed_tpu.serving.spec import TreeDrafter  # noqa: E402

pytestmark = pytest.mark.fast

#: cap 16: rung 8 is 4 x 8 + 8 = 40 rows wide, rung 16 the widest, 80
#: (a chunk behind the decode rows needs 56: too near 80 for a program)
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=8, npages=64)
NARROW, WIDE = 40, 80
#: cap 64: rung 8 is 72 wide, a step with a chunk 136, 224 or 320
LADDER = EngineConfig(slots=8, token_budget=256, chunk=64, page=8,
                      npages=320)
#: (prompt length, arrival in steps): chunks of 16 beside decode rows,
#: decode-only steps between and after them
TRAFFIC = ((21, 0.0), (5, 0.0), (40, 4.0), (3, 9.0))


def _requests(vocab, traffic=TRAFFIC, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=max_new, arrival=at,
                    prompt=rng.integers(0, vocab, (n,)).astype(np.int32))
            for i, (n, at) in enumerate(traffic)]


def _serve(model, params, reqs, ecfg=ENGINE, engine=ServingEngine, **kw):
    """Run ``reqs`` to the end; returns the engine and, per device step,
    ``(block_q, arrays)`` as ``_run_device`` was handed them."""
    eng = engine(model, params, ecfg, use_pallas=False,
                 propagate_failures=True, **kw)
    steps, run = [], eng._run_device

    def spy(arrays, block_q):
        steps.append((block_q, arrays))
        return run(arrays, block_q)

    eng._run_device = spy
    stats = eng.run(reqs, max_steps=200)
    assert stats.completed == len(reqs) and not stats.failures
    return eng, steps


# ------------------------------------------------------------ the rule


@pytest.mark.parametrize("slots, budget, chunk, low, ladder", [
    # the benchmark's cells: a decode-only step 264 rows, a chunk behind
    # every decode row 520, the midpoint's multiple of 128, the widest
    (32, 512, 256, 264, (520, 640, 768)),
    # chip_smoke.py's engine
    (16, 512, 256, 136, (392, 512, 768)),
    # the low rung IS the cap: its step is 40 wide, one program
    (4, 32, 8, 40, (40,)),
    # slots x 8 fill the budget: a decode-only step holds it and a
    # block, a chunk behind them all is past the widest
    (64, 512, 256, 520, (768,)),
    # DisaggregatedEngine's decode role (budget 8 x slots): likewise
    (8, 64, 16, 72, (80,)),
    # widths a few rows apart are not worth a program each
    (4, 64, 16, NARROW, (WIDE,)),
    (8, 256, 64, 72, (136, 224, 320)),
    (16, 256, 64, 136, (256, 320)),
    (32, 512, 128, 264, (392, 512, 640)),
])
def test_the_widths_are_a_function_of_slots_budget_and_chunk(
        slots, budget, chunk, low, ladder):
    model, params = _model()
    eng = ServingEngine(
        model, params,
        EngineConfig(slots=slots, token_budget=budget, chunk=chunk, page=8,
                     npages=8),
        use_pallas=False)
    cap = eng._block_q_cap
    assert cap == chunk and eng._rungs() == sorted({8, cap})
    assert packed_width(8, slots, budget) == low \
        == live_rows(8, slots, budget) + 8
    assert eng._widths(8) == ((low,) if cap > 8 else ladder)
    assert eng._widths(cap) == ladder and eng._t_pad == budget + cap
    assert ladder[-1] == eng._t_pad
    assert all(w % 8 == 0 for w in ladder) and sorted(ladder) == list(ladder)
    # one set of (absent) workspaces a distinct width, no EP layer: None
    assert eng.moe_state is None


@pytest.mark.parametrize("narrowest, widest, grain, ladder", [
    (520, 768, 128, (520, 640, 768)),
    (392, 768, 128, (392, 512, 768)),
    (56, 80, 8, (80,)),                    # 24 rows are no program's worth
    (700, 768, 128, (700, 768)),           # the midpoint is under it
    (776, 768, 128, (768,)),               # nothing under the widest
    (136, 320, 32, (136, 224, 320)),
])
def test_the_ladder_is_the_ends_and_a_midpoint_worth_their_programs(
        narrowest, widest, grain, ladder):
    assert chunk_widths(narrowest, widest, grain) == ladder


def test_an_engine_takes_the_rule_from_its_config_alone():
    model, params = _model()
    eng = ServingEngine(model, params, ENGINE, use_pallas=False)
    assert eng._rungs() == [8, 16] and eng._t_pad == WIDE
    assert [eng._widths(b) for b in eng._rungs()] == [(NARROW,), (WIDE,)]
    assert eng.moe_state is None                  # no EP expert layer
    # a tuned floor lifts the lowest rung to the cap: one rung
    from triton_distributed_tpu.tune.schedule import GridSchedule

    floor = ServingEngine(model, params, ENGINE, use_pallas=False,
                          grid_schedule=GridSchedule(block_q=16))
    assert floor._rungs() == [16] and floor._rung(1) == 16
    assert floor._widths(16) == (WIDE,)
    # the rungs between the low one and the cap are folded into the cap
    wide = ServingEngine(model, params, LADDER, use_pallas=False)
    assert wide._rungs() == [8, 64]
    assert [wide._rung(n) for n in (1, 8, 9, 16, 33, 64)] == [
        8, 8, 64, 64, 64, 64]


# ------------------------------------------------- what a step is handed


def test_a_decode_only_step_is_narrow_and_a_chunk_step_covers_its_rows():
    model, params = _model()
    eng, steps = _serve(model, params, _requests(CFG["vocab"]))
    seen = set()
    for block_q, (tokens, token_rows, token_pos, q_starts, q_lens,
                  *_) in steps:
        width = len(tokens)
        assert block_q == eng._rung(int(q_lens.max()))
        assert width == len(token_rows) == len(token_pos)
        assert width in eng._widths(block_q)
        out = q_lens == 0
        # a slot outside the batch stays at row 0: every launch skips it
        assert (q_starts[out] == 0).all()
        ends = (q_starts + -(-q_lens // 8) * 8)[~out]
        assert ends.max() <= ENGINE.token_budget
        assert (token_pos[ends.max():] == -1).all()
        assert (q_starts[~out] + block_q <= width).all()
        seen.add((block_q, width))
    # both kinds of step occurred, and each rung has ONE width here
    assert seen == {(8, NARROW), (16, WIDE)}
    decode_only = [len(a[0]) for b, a in steps if a[4].max() == 1]
    assert decode_only and set(decode_only) == {NARROW}


# ---------------------------- (a) every batch fits the narrowest that does


def _block(kind, q_len, rung):
    """What this file says a model of ``kind`` moves for a batched row
    of ``q_len`` tokens at ``rung``, in its own words: the latent walk
    a row's own tokens in blocks of 8; every other model here has a
    softmax layer whose (contiguous or selected) walk fetches the
    launch's whole block."""
    return -(-q_len // 8) * 8 if kind == "latent" else rung


def _model_of(kind, **over):
    """``(model, params)``: the tiny twin of the kind's architecture
    (a latent pool; block-sparse + lightning layers; kda layers), else
    the dense model."""
    twin = {"latent": test_mla, "selected": test_sala, "kda": test_kda}
    if kind in twin:
        model, _, params = twin[kind].seeded(twin[kind].tiny_config())
        return model, params
    return _model(**over)


def _engine_of(kind, ecfg):
    model, params = _model_of(kind)
    if kind == "tree":
        return SpeculativeEngine(
            model, params, ecfg, use_pallas=False, spec_tree=8,
            drafter=TreeDrafter(branches=3, branch_len=2))
    return ServingEngine(model, params, ecfg, use_pallas=False)


def _seat(eng, slot, rng, *, left):
    """Seat a request in ``slot`` as a step would find it, ``left``
    tokens of its sequence not yet in KV: 1 is a decode row (a motif
    prompt, so that a drafter drafts), more a prompt still prefilling."""
    vocab, page = eng.model.config.vocab, eng.cfg.page
    if left == 1:
        motif = rng.integers(0, vocab, (5,)).astype(np.int32)
        prompt = np.tile(motif, 4)[:int(rng.integers(11, 20))]
        req = Request(rid=slot, max_new=64, prompt=prompt)
        req.generated = [int(prompt[len(prompt) % 5])]
        req.cursor = len(prompt)
    else:
        done = int(rng.integers(0, 3)) * eng.cfg.chunk
        req = Request(rid=slot, max_new=64, prompt=rng.integers(
            0, vocab, (done + left,)).astype(np.int32))
        req.cursor = done
    req.slot = slot
    eng.slot_req[slot] = req
    assert eng._alloc(slot, 0, -(-req.cursor // page))


def _clear(eng):
    for s, req in enumerate(eng.slot_req):
        if req is not None:
            eng._free_slot(s)
            eng.slot_req[s] = None
    eng.table[:] = -1


#: slots -> an engine whose cap rung has more than one width
SIZES = {
    4: EngineConfig(slots=4, token_budget=256, chunk=64, page=8, npages=320),
    8: LADDER,
    16: EngineConfig(slots=16, token_budget=256, chunk=64, page=8,
                     npages=320),
    32: EngineConfig(slots=32, token_budget=512, chunk=128, page=8,
                     npages=640),
}


def _layouts(rng, slots, chunk):
    """Occupancies ``{slot: tokens left}``: a chunk first, last and in
    the middle of a full house, a prompt's tail, two chunks, four (the
    budget of every size here), decode rows alone, then random ones."""
    mid = slots // 2
    full = dict.fromkeys(range(slots), 1)
    yield {**full, 0: 3 * chunk}
    yield {**full, slots - 1: 3 * chunk}
    yield {**full, mid: 3 * chunk}
    yield {**full, mid: int(rng.integers(9, chunk))}
    yield {**full, 0: 2 * chunk, 1: 2 * chunk}
    yield dict.fromkeys(range(4), 2 * chunk)       # the budget, in chunks
    yield {s: 1 for s in range(0, slots, 2)}
    yield {mid: 3 * chunk}
    yield full
    for _ in range(24):
        yield {s: (1 if rng.random() < 0.75
                   else int(rng.integers(2, 3 * chunk)))
               for s in range(slots) if rng.random() < 0.8}


@pytest.mark.parametrize("slots", sorted(SIZES))
@pytest.mark.parametrize("kind",
                         ["dense", "tree", "latent", "selected", "kda"])
def test_every_batched_row_fits_the_narrowest_width_that_holds_them_all(
        kind, slots):
    eng = _engine_of(kind, SIZES[slots])
    cfg, cap = eng.cfg, eng._block_q_cap
    rng = np.random.default_rng(slots)
    seen = set()
    for layout in _layouts(rng, slots, cfg.chunk):
        _clear(eng)
        for slot, left in layout.items():
            _seat(eng, slot, rng, left=left)
        (tokens, _, token_pos, q_starts, q_lens, _, _, batched,
         _) = eng._assemble()
        if not batched:
            continue
        rung = eng._rung(int(q_lens.max()))
        ladder = eng._widths(rung)
        width = len(tokens)
        ends = [int(q_starts[s]) + _block(kind, int(q_lens[s]), rung)
                for s in batched]
        assert all(e <= width for e in ends), (layout, ends, width)
        assert width == min(w for w in ladder if w >= max(ends))
        # nothing of the batch lies past the budget, nor a token past
        # its row's own span
        spans = [(int(q_starts[s]), int(q_lens[s])) for s in batched]
        assert max(a + n for a, n in spans) <= cfg.token_budget
        assert (token_pos >= 0).sum() == sum(n for _, n in spans)
        assert (q_starts[q_lens == 0] == 0).all()
        seen.add((rung, width))
    assert (8, packed_width(8, slots, cfg.token_budget)) in seen
    at_cap = {w for r, w in seen if r == cap}
    # the layouts reach every width of the ladder
    ladder = eng._widths(cap)
    assert at_cap == set(ladder)
    if kind == "latent":
        # no batch of a latent model needs more than the budget: its
        # ladder ends at the first width that holds it
        assert [w >= cfg.token_budget for w in ladder] == [
            False] * (len(ladder) - 1) + [True]
    else:
        assert ladder[-1] == eng._t_pad and len(ladder) > 1
    if kind == "tree":
        assert max(int(n) for n in q_lens) > 1      # verify rows drafted


def test_each_kernel_says_what_it_moves_for_a_row():
    from triton_distributed_tpu.kernels import (
        kda_attention,
        lightning_attention,
        ragged_paged_attention,
    )

    q_lens = np.array([0, 1, 2, 8, 9, 64])
    blocks = ragged_paged_attention.query_block_tokens
    assert blocks(q_lens, 64).tolist() == [0, 64, 64, 64, 64, 64]
    assert blocks(q_lens[:4], 8).tolist() == [0, 8, 8, 8]
    assert blocks(q_lens, 64, latent=True).tolist() == [0, 1, 8, 8, 16, 64]
    for kernel in (lightning_attention, kda_attention):
        assert kernel.query_block_tokens(q_lens, 64).tolist() == [
            0, 8, 8, 8, 64, 64]
        assert kernel.query_block_tokens(q_lens[:4], 8).tolist() == [
            0, 8, 8, 8]
    # a model asks for the largest of its kinds of layer, 8-aligned
    dense, _ = _model()
    starts = np.array([0, 64, 72, 0])
    lens = np.array([64, 1, 1, 0])
    assert dense.step_rows_needed(starts, lens, 64) == 72 + 64
    latent = test_mla.one_chip_model(test_mla.tiny_config())
    assert latent.step_rows_needed(starts, lens, 64) == 80
    assert dense.step_rows_needed(starts, 0 * lens, 8) == 0


# ------------------------------------------------------- the same tokens


@pytest.mark.parametrize("moe", ["none", "ep"], ids=["dense", "softmax_ep"])
def test_streams_across_both_widths_are_the_forward_oracle_s(moe):
    model, params = _model(moe=moe)
    reqs = _requests(CFG["vocab"])
    _, steps = _serve(model, params, reqs)
    assert {len(a[0]) for _, a in steps} == {NARROW, WIDE}
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid


def test_a_sigmoid_share_s_streams_are_its_plain_reference_s():
    """``forward`` refuses this architecture by name (window layers, a
    sigmoid router, a share of the experts), so the oracle here is the
    benchmark's plain reference of it, as in ``test_window_share``."""
    cfg = tiny_config()
    model = Transformer(cfg, Mesh(np.asarray(jax.devices()[:1]), ("x",)),
                        tp_axis="x")
    sizes = sizes_of(cfg)
    params = weights.make_params(
        ref.param_plan(sizes), 3300000032, cfg.param_dtype)
    reqs = _requests(cfg.vocab, max_new=4)
    _, steps = _serve(model, params, reqs)
    assert {len(a[0]) for _, a in steps} == {NARROW, WIDE}
    for req in reqs:
        seq = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)])
        rows = np.arange(len(req.prompt) - 1, len(seq))
        logits = np.asarray(ref.logits_at(params, sizes, seq, rows))
        gaps = logits.max(-1) - logits[np.arange(len(rows)), req.generated]
        assert float(gaps.max()) <= 1e-4, (req.rid, gaps)


def test_speculative_rows_verify_the_same_stream_at_the_narrow_width():
    """``SpeculativeEngine`` reads ``logits[q_starts[s] + j]`` of the
    all-positions step: offsets under ``live``, whatever the width."""
    model, params = _model()
    rng = np.random.default_rng(5)
    motif = rng.integers(0, CFG["vocab"], (5,)).astype(np.int32)

    def reqs():
        return [Request(rid=i, max_new=16, arrival=at,
                        prompt=np.tile(motif, n)[:5 * n - i])
                for i, (n, at) in enumerate(((4, 0.0), (2, 0.0), (6, 3.0)))]

    plain, spec = reqs(), reqs()
    _serve(model, params, plain)
    eng, steps = _serve(model, params, spec, engine=SpeculativeEngine,
                        spec_k=4)
    assert eng.stats.spec_rows > 0
    assert {len(a[0]) for _, a in steps} == {NARROW, WIDE}
    assert [r.generated for r in spec] == [r.generated for r in plain]


# ----------------------- (b) the narrow steps' logits are the widest's


def _widest_only(monkeypatch):
    """Every step of a rung at the rung's widest width: the width
    function as it was before the ladder."""
    widths = ServingEngine._widths
    monkeypatch.setattr(ServingEngine, "_widths",
                        lambda self, block_q: widths(self, block_q)[-1:])


#: (prompt lengths, all due at once) through 8 slots: two chunks in one
#: step, a chunk first, last and between decode rows, tails
LADDER_PROMPTS = (150, 5, 70, 9, 200, 23, 3, 40, 100, 12)


@pytest.mark.parametrize("kind, use_pallas", [
    ("dense", False), ("dense", True), ("latent", False), ("latent", True),
    ("selected", False), ("selected", True), ("kda", False),
], ids=lambda v: {True: "kernels_interpreted", False: "xla_twins"}.get(v, v))
def test_logits_and_streams_are_those_of_the_widest_steps(
        kind, use_pallas, monkeypatch):
    """The same engine twice, its steps as wide as their batches need
    and every one the widest: float32 logits at EVERY position equal at
    1e-5, streams token for token. A narrower step drops only rows
    that are no token's."""
    model, params = _model_of(kind)
    ecfg = dataclasses.replace(LADDER, page=16, npages=160)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, model.config.vocab, (n,)).astype(np.int32)
               for n in LADDER_PROMPTS]

    def serve():
        eng, reqs, logits = serve_all_logits(
            model, params, ecfg, prompts, max_new=5, use_pallas=use_pallas)
        return eng, [r.generated for r in reqs], logits

    eng, streams, logits = serve()
    st = eng.stats
    assert st.chunk_narrow_steps > 0
    assert st.chunk_packed_rows < st.chunk_steps * eng._t_pad
    _widest_only(monkeypatch)
    wide_eng, wide_streams, wide_logits = serve()
    ws = wide_eng.stats
    assert ws.chunk_steps == st.chunk_steps
    assert ws.chunk_narrow_steps == (
        ws.chunk_steps if wide_eng._widths(64)[-1] < wide_eng._t_pad else 0)
    assert streams == wide_streams
    for got, want in zip(logits, wide_logits):
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ------------------- (c) every width's program is built in the warm-up


def _full_house_then_a_chunk_mid_slot(vocab, at):
    """Eight short prompts (two that end early in slots 0 and 1), then
    two long ones that take those slots: chunks in front of six decode
    rows, the ladder's widest; a third long one later takes a slot in
    the middle."""
    rng = np.random.default_rng(7)

    def req(rid, n, max_new, arrival):
        return Request(rid=rid, max_new=max_new, arrival=at + arrival,
                       prompt=rng.integers(0, vocab, (n,)).astype(np.int32))

    first = [req(i, 5 + i, 3 if i in (0, 1, 4) else 40, 0.0)
             for i in range(8)]
    return first + [req(8, 200, 4, 6.0), req(9, 190, 4, 6.0),
                    req(10, 150, 4, 7.0)]


def test_a_window_after_the_harness_warm_up_builds_no_program():
    """One lone request per block of the old ladder reaches only the
    narrowest width of the cap rung; the engine builds the others at
    that rung's first launch. A window with chunks in front of a full
    house of decode rows then launches the widest and builds nothing."""
    model, params = _model()
    eng = ServingEngine(model, params, LADDER, use_pallas=False,
                        propagate_failures=True)
    keys, run = [], eng._run_device

    def spy(arrays, block_q):
        keys.append((block_q, len(arrays[0]), int(arrays[4].sum())))
        return run(arrays, block_q)

    eng._run_device = spy
    warm = program.warm_up(eng, CFG["vocab"])
    assert warm["rungs"] == [8, 16, 32, 64]
    every = {(b, w) for b in eng._rungs() for w in eng._widths(b)}
    assert every == {(8, 72), (64, 136), (64, 224), (64, 320)}
    # the lone requests launched the narrowest of the cap rung alone;
    # the other two were built by empty batches, before its first step
    assert {k[:2] for k in keys if k[2]} == {(8, 72), (64, 136)}
    empties = [k[:2] for k in keys if not k[2]]
    assert empties == [(64, 224), (64, 320)]
    assert keys.index((64, 320, 0)) < [k[:2] for k in keys].index((64, 136))
    assert eng.stats.programs_built == len(every) == 4
    steps = len(eng.stats.step_times)
    assert steps == sum(1 for k in keys if k[2])    # an empty batch is no step

    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k:
        name.endswith("jaxpr_to_mlir_module_duration")
        and lowered.append(name))
    del keys[:]
    reqs = _full_house_then_a_chunk_mid_slot(CFG["vocab"], eng.step_count)
    stats = eng.run(reqs, max_steps=300)
    assert all(r.done for r in reqs) and not stats.failures
    seen = {k[:2] for k in keys}
    assert all(k[2] for k in keys)                  # no empty batch again
    assert {(64, 224), (64, 320)} <= seen <= every
    assert stats.programs_built == 4 and not lowered, lowered
    for r in reqs:
        assert r.generated == greedy_tokens(
            model, params, r.prompt, r.max_new), r.rid


@pytest.mark.parametrize("kind, use_pallas", [
    ("dense_int8", False), ("dense_int8", True), ("selected", False),
    ("selected", True), ("kda", False), ("latent", True),
], ids=lambda v: {True: "kernels_interpreted", False: "xla_twins"}.get(v, v))
def test_an_empty_batch_leaves_the_serving_state_bit_identical(
        kind, use_pallas):
    """What builds a rung's other widths: a step of that width with no
    row at all. Pools, scales, recurrent states, convolution tails and
    compressed keys come back bit for bit, the ids a later merge reads
    are not replaced, and no step is counted."""
    model, params = _model_of(kind, kv_quant="int8")
    ecfg = dataclasses.replace(LADDER, page=16, npages=160)
    eng = ServingEngine(model, params, ecfg, use_pallas=use_pallas,
                        propagate_failures=True)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, max_new=30, arrival=0.0,
                    prompt=rng.integers(0, model.config.vocab, (n,))
                    .astype(np.int32)) for i, n in enumerate((70, 9, 23))]
    eng.submit_trace(reqs)
    for _ in range(4):                  # chunks, tails and decode rows
        eng.step()
    eng.drain()

    def held():
        st = eng.state
        return [np.asarray(a) for a in jax.tree.leaves(
            (st.layers, st.recurrent, st.ckeys))]

    before, ids, steps = held(), eng._ids, len(eng.stats.step_times)
    assert any(a.any() for a in before)
    built = eng.stats.programs_built
    cap = eng._block_q_cap
    for w in eng._widths(cap):
        eng._token_src = np.full((w,), -1, np.int32)
        out = eng._run_device(eng._empty_batch(w), cap)
        assert np.asarray(out).shape == (ecfg.slots,)
    after = held()
    assert len(before) == len(after)
    for a, b in zip(before, after):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert len(eng.stats.step_times) == steps
    assert eng.stats.programs_built >= built
    # and the run goes on to its end
    eng._ids = ids
    eng.run()
    assert all(r.done for r in reqs) and not eng.stats.failures


# ------------------------------------- the expert layer's alignment block


@pytest.mark.parametrize("rows, topk, experts, resident, floor, cap, block", [
    # resident weights: half the even share (24.75 / 2 -> 16; 72 / 2 ->
    # 64), never under an int8 operand's tile
    (264, 6, 64, True, 32, 128, 32),
    (768, 6, 64, True, 32, 128, 64),
    # tiled weights: twice the share (33 -> 64; 96 -> 128; 132 -> 256)
    (264, 8, 128, False, 64, 256, 64),
    (768, 8, 128, False, 64, 256, 128),
    (264, 2, 8, False, 64, 256, 256),
    # an exact power of two stays (128 x 2 / 8 / 2 = 16)
    (128, 2, 8, True, 8, 64, 16),
    (128, 2, 8, False, 16, 256, 64),
    # never over the cap, never under the floor
    (4096, 6, 64, True, 32, 128, 128),
    (4096, 2, 8, False, 64, 256, 256),
    (264, 8, 256, False, 64, 256, 64),
    (8, 2, 256, True, 16, 64, 16),
    (1, 1, 8, True, 8, 64, 8),
])
def test_the_block_is_a_power_of_two_from_the_even_share(
        rows, topk, experts, resident, floor, cap, block):
    assert expert_block_m(rows, topk, experts, resident=resident,
                          floor=floor, cap=cap) == block


#: configuration file -> {width: (block_m, rows of the sorted buffer)}
#: on the chip; mixtral's are what every width took before PR 36
CELL_BLOCKS = {
    "dsmoe16b-d9": {264: (32, 3616), 520: (32, 5152), 640: (32, 5856),
                    768: (64, 8704)},
    "mixtral8x7b-d2": {264: (256, 3072), 520: (256, 3584),
                       640: (256, 3584), 768: (256, 3840)},
    "kexaone236b-ep8-d5": {264: (64, 3200), 520: (128, 6400),
                           640: (128, 7296), 768: (128, 8320)},
    "dotsvlm1-ep32-d5": {264: (64, 2688), 520: (64, 4736),
                         640: (64, 5696), 768: (64, 6720)},
    "solaropen2-ep8-d4": {264: (16, 2736), 520: (16, 4784),
                          640: (16, 5744), 768: (16, 6768)},
    "minicpmsala9b-d8": {264: None, 520: None, 640: None, 768: None},
}


@pytest.mark.parametrize("width", [264, 520, 640, 768])
@pytest.mark.parametrize("name", sorted(CELL_BLOCKS))
def test_the_cells_expert_blocks_follow_their_widths(
        name, width, monkeypatch):
    """The six configurations as the benchmark runs them, at the four
    widths of the cells' engine (a decode-only step's and the ladder of
    a step that holds a chunk): compiling for the chip the block is
    the rule's (a function of the width, the router and where the
    weights live, not of the preset), the counter's rows are the
    buffer ``moe_align_block_size`` builds; off the chip, and for
    training, the context is what it was."""
    from triton_distributed_tpu.config import config as tcfg

    conf = json.loads(
        (ROOT / "benchmark" / "configs" / f"{name}.json").read_text())
    c = program.model_config(conf)
    model = Transformer(c, Mesh(np.asarray(jax.devices()[:1]), ("x",)),
                        tp_axis="x")
    want = CELL_BLOCKS[name][width]
    if want is None:                  # no EP expert layer
        assert model.moe_aligned_rows(width) == 0
        return
    wq = c.moe_weight_quant is not None
    off = model._moe_ep_ctx(width, inference=True, weights_quantized=wq)
    assert (off.block_m, off.transport, off.use_pallas_gemm) == (
        128, "xla", False)
    monkeypatch.setattr(tcfg, "force_compile", True)  # compiling_for_tpu()
    ctx = model._moe_ep_ctx(width, inference=True, weights_quantized=wq)
    train = model._moe_ep_ctx(width)
    rows = model.moe_aligned_rows(width)
    assert (train.block_m, train.transport, train.use_pallas_gemm) == (
        128, "xla", False)
    assert ctx.transport == "fused" and ctx.use_pallas_gemm
    assert (ctx.block_m, ctx.aligned_rows) == want
    assert rows == ctx.aligned_rows == mu.moe_align_block_size(
        np.zeros((ctx.recv_rows, 1), np.int32), ctx.experts_per_rank + 1,
        ctx.block_m)[0].shape[0]
    # the resident regime: a 2.9 MB int8 expert, a 3.9 MB bf16 one
    assert (ctx.gg_block_n is not None) == (
        name in ("dsmoe16b-d9", "solaropen2-ep8-d4"))


# ----------------------------------------------------------- the counters


@pytest.mark.parametrize("moe", ["none", "ep"], ids=["dense", "softmax_ep"])
def test_packed_rows_sums_the_widths_and_masked_rows_the_rest(moe):
    model, params = _model(moe=moe)
    eng, steps = _serve(model, params, _requests(CFG["vocab"]))
    st = eng.stats
    widths = [len(a[0]) for _, a in steps]
    assert len(widths) == len(st.step_tokens)
    assert st.packed_rows == sum(widths) < len(widths) * eng._t_pad
    assert st.moe_masked_rows == (
        st.packed_rows - sum(st.step_tokens) if moe == "ep" else 0)
    # a step's program allocates its width's sorted buffer a layer
    aligned = {w: model.moe_aligned_rows(w, params) for w in set(widths)}
    assert st.moe_aligned_rows == sum(aligned[w] for w in widths)
    assert all((rows > w * model.config.topk) == (moe == "ep")
               for w, rows in aligned.items())


# ------------------------------------------------------- one program a key


def test_the_step_jit_holds_one_program_a_key_and_a_second_pass_none():
    model, params = _model()
    ecfg = EngineConfig(slots=4, token_budget=64, chunk=32, page=8,
                        npages=64)
    eng = ServingEngine(model, params, ecfg, use_pallas=False,
                        propagate_failures=True)
    rungs = eng._rungs()
    assert rungs == [8, 32]             # 16 is folded into the cap
    assert [eng._widths(b) for b in rungs] == [(40,), (96,)]
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k:
        name.endswith("jaxpr_to_mlir_module_duration")
        and lowered.append(name))

    def one_pass(first_rid):
        """A request alone per block of the old ladder (its prompt sets
        it), each decoding a few tokens: the benchmark's warm-up."""
        rng, launched = np.random.default_rng(0), set()
        run = eng._run_device

        def spy(arrays, block_q):
            launched.add((block_q, len(arrays[0])))
            return run(arrays, block_q)

        eng._run_device = spy
        for i, b in enumerate((8, 16, 32)):
            n = b // 2 + 1 if b > 8 else 2
            req = Request(rid=first_rid + i, max_new=3,
                          arrival=eng.step_count,
                          prompt=rng.integers(0, 128, (n,)).astype(np.int32))
            eng.run([req], max_steps=32)
            assert req.done
        eng._run_device = run
        return launched

    assert one_pass(0) == {(8, 40), (32, 96)}
    # one program a key, and the very first step's once more (its
    # fresh state is not yet the jit's own output)
    held = model._serving_jit._cache_size()
    assert len(rungs) <= held <= len(rungs) + 1
    n = len(lowered)
    assert one_pass(10) == {(8, 40), (32, 96)}
    assert model._serving_jit._cache_size() == held
    assert len(lowered) == n


# --------------------------------------------------------------- two chips


def test_a_tp2_engine_serves_at_the_narrow_width(monkeypatch):
    """Two devices, the fused EP transport: one set of workspaces per
    width (``m_local = width / 2``), ONE parity for all of them that
    rolls once a step whichever width the step has; the tokens are the
    forward oracle's."""
    monkeypatch.setattr(Transformer, "_moe_ep_ctx", force_fused_ctx())
    model, params = _model(tp=2, moe="ep")
    reqs = _requests(CFG["vocab"], traffic=((21, 0.0), (5, 0.0)),
                     max_new=4)
    eng, steps = _serve(model, params, reqs)
    widths = [len(a[0]) for _, a in steps]
    assert sorted(eng.moe_state) == [NARROW, WIDE] == sorted(set(widths))
    instances = set()
    for w, state in eng.moe_state.items():
        assert state[0] is None and state[1] is not None
        assert state[1].disp_tok.shape[0] % 2 == 0
        assert int(np.asarray(state[1].parity)[0]) == len(widths) % 2
        instances.add(state[1].instance)
    assert len(instances) == 2          # a state per compiled kernel
    monkeypatch.undo()                  # the oracle routes by forward
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid
