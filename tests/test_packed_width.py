"""The packed width of a serving step follows its ``block_q`` rung: a
step whose rows cannot fill the token budget at its rung is ``live +
block_q`` rows wide (``live = slots * block_q``), every other step the
widest, ``_t_pad``. One program per rung, as before; the tokens served
are the plain references'.

CPU sizes, the XLA twins (``use_pallas=False``); the kernels at a narrow
width are ``test_kv_append`` / ``test_window_share`` (their engines'
low rungs are narrow too).
"""

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
    live_rows,
    packed_width,
)

pytestmark = pytest.mark.fast

#: cap 16: rung 8 is 4 x 8 + 8 = 40 rows wide, rung 16 the widest, 80
ENGINE = EngineConfig(slots=4, token_budget=64, chunk=16, page=8, npages=64)
NARROW, WIDE = 40, 80
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


@pytest.mark.parametrize("slots, budget, cap, widths", [
    # the benchmark's three cells: two widths
    (32, 512, 256, {8: 264, 16: 768, 32: 768, 64: 768, 128: 768,
                    256: 768}),
    # chip_smoke.py's engine: three
    (16, 512, 256, {8: 136, 16: 272, 32: 768, 256: 768}),
    # slots x 8 fill the budget: always the widest
    (4, 32, 8, {8: 40}),
    (64, 512, 256, {8: 768, 256: 768}),
    # DisaggregatedEngine's decode role (budget 8 x slots): likewise
    (8, 64, 16, {8: 80, 16: 80}),
    (4, 64, 16, {8: NARROW, 16: WIDE}),
])
def test_the_width_is_a_function_of_rung_slots_and_budget(
        slots, budget, cap, widths):
    for rung, width in widths.items():
        assert packed_width(rung, slots, budget, cap) == width
        live = live_rows(rung, slots, budget)
        # every row's block fits: a batched row starts under live less
        # its own 8-aligned take, a parked one at live
        assert live + rung <= width <= budget + cap
        assert width % 8 == 0 and live <= budget


def test_an_engine_takes_the_rule_from_its_config_alone():
    model, params = _model()
    eng = ServingEngine(model, params, ENGINE, use_pallas=False)
    assert eng._rungs() == [8, 16] and eng._t_pad == WIDE
    assert [eng._width(b) for b in eng._rungs()] == [NARROW, WIDE]
    assert eng.moe_state is None                  # no EP expert layer
    # a tuned floor lifts the lowest rung, and with it the width
    from triton_distributed_tpu.tune.schedule import GridSchedule

    floor = ServingEngine(model, params, ENGINE, use_pallas=False,
                          grid_schedule=GridSchedule(block_q=16))
    assert floor._rungs() == [16] and floor._width(floor._rung(1)) == WIDE


# ------------------------------------------------- what a step is handed


def test_a_decode_only_step_is_narrow_and_a_chunk_step_the_widest():
    model, params = _model()
    eng, steps = _serve(model, params, _requests(CFG["vocab"]))
    seen = set()
    for block_q, (tokens, token_rows, token_pos, q_starts, q_lens,
                  *_) in steps:
        width = len(tokens)
        assert block_q == eng._rung(int(q_lens.max()))
        assert width == len(token_rows) == len(token_pos) \
            == eng._width(block_q)
        live = width - block_q if width < WIDE else ENGINE.token_budget
        out = q_lens == 0
        assert (q_starts[out] == live).all()
        ends = (q_starts + np.where(out, 0, -(-q_lens // 8) * 8))[~out]
        assert ends.max() <= live and (token_pos[ends.max():] == -1).all()
        assert (q_starts + block_q <= width).all()
        seen.add((block_q, width))
    # both kinds of step occurred, and each rung has ONE width
    assert seen == {(8, NARROW), (16, WIDE)}
    decode_only = [len(a[0]) for b, a in steps if a[4].max() == 1]
    assert decode_only and set(decode_only) == {NARROW}


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
    "dsmoe16b-d9": {264: (32, 3616), 768: (64, 8704)},
    "mixtral8x7b-d2": {264: (256, 3072), 768: (256, 3840)},
    "kexaone236b-ep8-d5": {264: (64, 3200), 768: (128, 8320)},
    "dotsvlm1-ep32-d5": {264: (64, 2688), 768: (64, 6720)},
    "minicpmsala9b-d8": {264: None, 768: None},
}


@pytest.mark.parametrize("width", [264, 768])
@pytest.mark.parametrize("name", sorted(CELL_BLOCKS))
def test_the_cells_expert_blocks_follow_their_two_widths(
        name, width, monkeypatch):
    """The five configurations as the benchmark runs them, at the two
    widths of the cells' engine: compiling for the chip the block is
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
    # the resident regime is dsmoe's alone (a 2.9 MB int8 expert)
    assert (ctx.gg_block_n is not None) == (name == "dsmoe16b-d9")


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


# ------------------------------------------------------ one program a rung


def test_the_step_jit_holds_one_program_a_rung_and_a_second_pass_none():
    model, params = _model()
    ecfg = EngineConfig(slots=4, token_budget=64, chunk=32, page=8,
                        npages=64)
    eng = ServingEngine(model, params, ecfg, use_pallas=False,
                        propagate_failures=True)
    rungs = eng._rungs()
    assert rungs == [8, 16, 32]
    assert [eng._width(b) for b in rungs] == [40, 96, 96]
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k:
        name.endswith("jaxpr_to_mlir_module_duration")
        and lowered.append(name))

    def one_pass(first_rid):
        """A request alone per rung (its prompt sets it), each decoding
        a few tokens: the benchmark's warm-up."""
        rng, launched = np.random.default_rng(0), set()
        run = eng._run_device

        def spy(arrays, block_q):
            launched.add((block_q, len(arrays[0])))
            return run(arrays, block_q)

        eng._run_device = spy
        for i, b in enumerate(rungs):
            n = b // 2 + 1 if b > rungs[0] else 2
            req = Request(rid=first_rid + i, max_new=3,
                          arrival=eng.step_count,
                          prompt=rng.integers(0, 128, (n,)).astype(np.int32))
            eng.run([req], max_steps=32)
            assert req.done
        eng._run_device = run
        return launched

    assert one_pass(0) == {(8, 40), (16, 96), (32, 96)}
    # one program a rung, and the very first step's once more (its
    # fresh state is not yet the jit's own output): as before the rule
    held = model._serving_jit._cache_size()
    assert len(rungs) <= held <= len(rungs) + 1
    n = len(lowered)
    assert one_pass(10) == {(8, 40), (16, 96), (32, 96)}
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
