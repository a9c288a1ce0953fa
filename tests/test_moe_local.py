"""An expert layer that ONE rank holds whole exchanges with nobody
(``EPMoEContext.local``: ``ops/moe.py::_local_assignments_device``): one
sort of the assignments, one gather into the sorted buffer, the grouped
GEMMs, one un-sort, the weighted sum — against the dense reference and
against the exchange protocol called directly at one rank; what the
lowered program holds; the workspaces that are no longer built; the
engine's counter.

CPU sizes; the ``ragged_dot`` twin of the grouped GEMM except where a
case says otherwise (the kernel interpreted).
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import dense_moe_ref, force_fused_ctx
from jax.sharding import Mesh, PartitionSpec as P
from oracle import greedy_tokens
from test_serving_step import ENGINE, _model, _prompts

from triton_distributed_tpu.kernels import moe_utils as mu
from triton_distributed_tpu.kernels.group_gemm import (
    dequantize_grouped_weights,
    quantize_grouped_weights,
)
from triton_distributed_tpu.models import Transformer
from triton_distributed_tpu.ops import (
    create_ep_moe_context,
    create_ep_moe_state,
    ep_moe,
)
from triton_distributed_tpu.ops import moe as moe_ops
from triton_distributed_tpu.serving import Request, ServingEngine

pytestmark = pytest.mark.fast

E, TOPK, H, F, T = 8, 2, 128, 256, 40


def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("x",))


def _ctx(**kw):
    kw = {"transport": "xla", "block_m": 8, "use_pallas_gemm": False,
          "dtype": jnp.float32, **kw}
    return create_ep_moe_context(
        _mesh1(), "x", num_experts=E, topk=TOPK, max_m=T * TOPK, hidden=H,
        **kw)


def _data(gated=False):
    ks = jax.random.split(jax.random.PRNGKey(40), 4)
    x = jax.random.normal(ks[0], (T, H), jnp.float32)
    logits = jax.random.normal(ks[1], (T, E), jnp.float32)
    w_up = jax.random.normal(
        ks[2], (E, H, F * (2 if gated else 1)), jnp.float32) * 0.05
    w_down = jax.random.normal(ks[3], (E, F, H), jnp.float32) * 0.05
    return x, logits, w_up, w_down


def _dense_assignments(x, flat_e, w_flat, w_up, w_down, gated):
    """The plain reference over pre-routed assignments: every expert's
    MLP on every row, the assignments' weights pick."""
    out = jnp.zeros((x.shape[0], H), jnp.float32)
    e_of = np.asarray(flat_e).reshape(-1, TOPK)
    w_of = jnp.asarray(w_flat).reshape(-1, TOPK)
    for e in range(E):
        h = x @ w_up[e]
        h = (jax.nn.silu(h[:, :F]) * h[:, F:]) if gated else jax.nn.silu(h)
        w_e = jnp.sum(jnp.where(e_of == e, w_of, 0.0), axis=1)
        out += w_e[:, None] * (h @ w_down[e])
    return out


def _exchange_body(ctx, rows: int):
    """The exchange protocol's body at ONE rank, to call directly (the
    dispatcher takes the local path there): ``f(x, flat_e, w_flat,
    w_up, w_down)``."""
    return jax.shard_map(
        lambda *a: moe_ops._exchange_assignments_device(
            ctx, a[0], a[1], a[2], rows, a[3], a[4]),
        mesh=ctx.mesh, in_specs=P(), out_specs=P(), check_vma=False)


#: id -> (gated, weights quantized, routing, grouped GEMM by the kernel)
CASES = {
    "logits": (False, False, "logits", False),
    "logits_kernel": (False, False, "logits", True),
    "sentinels": (False, False, "share", False),
    "row_mask": (False, False, "rows", False),
    "gated": (True, False, "logits", False),
    "gated_share_rows_kernel": (True, False, "share_rows", True),
    "int8_weights": (False, True, "logits", False),
    "int8_weights_rows_kernel": (False, True, "rows", True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_local_path_is_the_dense_reference_and_the_exchange_s(case):
    """One rank: ``ep_moe`` takes the local path; its result is the
    dense reference's and the one the exchange protocol (``xla``
    transport, a self-exchange) computes from the same assignments —
    plain logits, a held share's sentinels, padding rows masked, gated
    and two-matrix experts, weight-quantized dicts."""
    gated, quantized, routing, kernel = CASES[case]
    x, logits, w_up, w_down = _data(gated)
    ctx = _ctx(gated=gated, use_pallas_gemm=kernel)
    assert ctx.local and ctx.n == 1
    w, ids = mu.select_experts(logits, TOPK)
    rows = None
    if "rows" in routing:
        rows = jnp.arange(T) % 5 != 3          # every fifth row: padding
    if "share" in routing:
        # experts 2..7 of a router over 10: ids local to the 8 held
        first, held = 2, E
        wide = jax.random.normal(jax.random.PRNGKey(5), (T, 10))
        w, ids = mu.select_experts(wide, TOPK)
        flat_e, w_flat = mu.held_assignments(w, ids, first, held, rows=rows)
        assert int((flat_e == E).sum()) > 0
    else:
        flat_e, w_flat = mu.held_assignments(w, ids, 0, E, rows=rows)
    routed = logits if routing == "logits" else (flat_e, w_flat)
    wq_up, wq_down, tol = w_up, w_down, 1e-5
    if quantized:
        wq_up, wq_down = (
            dict(zip(("q", "scale"), quantize_grouped_weights(m, "int8")))
            for m in (w_up, w_down))
        # the reference multiplies what the dicts hold
        w_up, w_down = (
            dequantize_grouped_weights(m["q"], m["scale"], jnp.float32)
            for m in (wq_up, wq_down))
    got = np.asarray(ep_moe(x, routed, wq_up, wq_down, ctx))
    want = np.asarray(_dense_assignments(
        x, flat_e, w_flat, w_up, w_down, gated))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, atol=tol * 10, rtol=tol * 10)
    if routing == "logits" and not gated and not quantized:
        np.testing.assert_allclose(
            got, np.asarray(dense_moe_ref(x, logits, w_up, w_down, TOPK)),
            atol=1e-5, rtol=1e-5)
    if rows is not None:
        assert not got[~np.asarray(rows)].any()     # a masked row's y
    by_exchange = np.asarray(jax.jit(_exchange_body(
        replace(ctx, use_pallas_gemm=False), T))(
            x, flat_e, w_flat, wq_up, wq_down))
    np.testing.assert_allclose(got, by_exchange, atol=1e-5, rtol=1e-5)


def test_the_wire_quantization_goes_with_the_wire():
    """``quant="fp8"`` at one rank: accepted (a preset is written for
    any mesh), and nothing is quantized — the result is the
    full-precision context's, bit for bit."""
    x, logits, w_up, w_down = _data()
    plain = _ctx(transport="fused")
    fp8 = _ctx(transport="fused", quant="fp8")
    assert fp8.quant == "fp8" and fp8.local
    np.testing.assert_array_equal(
        np.asarray(ep_moe(x, logits, w_up, w_down, fp8)),
        np.asarray(ep_moe(x, logits, w_up, w_down, plain)))


def test_gradients_at_one_rank_match_dense():
    """The local path is differentiable end to end (sort, gather,
    ``ragged_dot``): what ``layers.EPMoEMLP`` trains through on a
    one-device mesh."""
    x, logits, w_up, w_down = _data()
    ctx = _ctx()

    def loss(fn):
        return lambda x, u, d: jnp.sum(fn(x, u, d) ** 2)

    got = jax.grad(loss(lambda x, u, d: ep_moe(x, logits, u, d, ctx)),
                   argnums=(0, 1, 2))(x, w_up, w_down)
    want = jax.grad(
        loss(lambda x, u, d: dense_moe_ref(x, logits, u, d, TOPK)),
        argnums=(0, 1, 2))(x, w_up, w_down)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-5, rtol=1e-4)


def _count(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in ``jaxpr``, every call site of
    every nested jaxpr counted (the lowered text holds a jitted
    function's body once however often it is called)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count(sub, name)
    return n


def _scopes(jaxpr, outer: str = "") -> set:
    """``(name stack, primitive)`` of every equation, nested jaxprs
    under their caller's stack."""
    out = set()
    for eqn in jaxpr.eqns:
        stack = "/".join(filter(None, (
            outer, str(eqn.source_info.name_stack))))
        out.add((stack, eqn.primitive.name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out |= _scopes(sub, stack)
    return out


@pytest.mark.parametrize("routed", [False, True], ids=["logits", "routed"])
def test_the_lowered_program_sorts_once_and_loops_and_exchanges_never(
        routed):
    """The n = 1 program of one expert layer (CPU lowering): no
    ``all_to_all``, no ``while`` (a block's owner is found by
    comparison), exactly ONE ``sort``, the four device scopes; the
    exchange protocol at one rank, lowered beside it, sorts twice,
    loops and exchanges."""
    x, logits, w_up, w_down = _data()
    ctx = _ctx()
    w, ids = mu.select_experts(logits, TOPK)
    flat_e, w_flat = mu.held_assignments(w, ids, 0, E)
    arg = (flat_e, w_flat) if routed else logits

    def local(x, a, u, d):
        return ep_moe(x, a, u, d, ctx)

    text = jax.jit(local).lower(x, arg, w_up, w_down).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "stablehlo.while" not in text and "all_to_all" not in text
    jaxpr = jax.make_jaxpr(local)(x, arg, w_up, w_down).jaxpr
    assert _count(jaxpr, "sort") == 1
    assert not any(_count(jaxpr, p)
                   for p in ("while", "scan", "all_to_all"))
    # every operation of the block lies under one of the four scopes
    # the per-layer metrics read (the gather under moe_dispatch, the
    # un-sort under moe_combine)
    stacks = _scopes(jaxpr)
    for scope, prim in (("moe_route", "sort"), ("moe_dispatch", "gather"),
                        ("moe_gemm", "ragged_dot_general"),
                        ("moe_combine", "gather")):
        assert any(scope in st.split("/") and p == prim
                   for st, p in stacks), (scope, prim)
    old = jax.make_jaxpr(_exchange_body(ctx, T))(
        x, flat_e, w_flat, w_up, w_down).jaxpr
    assert _count(old, "sort") == 2
    assert _count(old, "scan") >= 1 and _count(old, "all_to_all") == 3


@pytest.mark.parametrize("transport", ["fused", "xla"])
@pytest.mark.parametrize("block_m", [8, 32])
def test_aligned_rows_is_the_buffer_the_local_path_allocates(
        transport, block_m, monkeypatch):
    """``EPMoEContext.aligned_rows`` (the ``moe_aligned_rows`` counter)
    at one rank: ``max_m`` assignments in place — not a receive slot's
    chunk-rounded capacity — plus the alignment, which is the sorted
    buffer ``_grouped_mlp`` is handed."""
    x, logits, w_up, w_down = _data()
    ctx = _ctx(transport=transport, block_m=block_m)
    assert ctx.recv_rows == ctx.max_m == T * TOPK
    assert ctx.aligned_rows == mu.aligned_capacity(T * TOPK, E + 1, block_m)
    seen, mlp = [], moe_ops._grouped_mlp

    def spy(ctx, xs, *a):
        seen.append(xs.shape)
        return mlp(ctx, xs, *a)

    monkeypatch.setattr(moe_ops, "_grouped_mlp", spy)
    jax.eval_shape(lambda *a: moe_ops.ep_moe_device(*a, ctx=ctx),
                   x, logits, w_up, w_down)
    assert seen == [(ctx.aligned_rows, H)]


@pytest.mark.parametrize("block_m", [1, 8, 32])
def test_positions_invert_the_sorted_ids(block_m):
    """``moe_align_block_size(positions=True)``: each flat (row, slot)
    index's place in the padded order — the sorted ids read back at
    those places are the iota, every segment starts on a block, and
    the owners, counts and ids are what a plain numpy walk gives."""
    rng = np.random.default_rng(block_m)
    ids = rng.choice([0, 1, 3, 6], (37, 3)).astype(np.int32)  # 2,4,5: none
    sti, be, counts, pos = map(np.asarray, mu.moe_align_block_size(
        jnp.asarray(ids), 7, block_m, positions=True))
    flat = ids.reshape(-1)
    np.testing.assert_array_equal(sti[pos], np.arange(flat.size))
    np.testing.assert_array_equal(counts, np.bincount(flat, minlength=7))
    want, owners = [], []
    for e in range(7):
        mine = np.flatnonzero(flat == e)            # stable: source order
        pad = -len(mine) % block_m
        want += list(mine) + [flat.size] * pad
        owners += [e] * ((len(mine) + pad) // block_m)
    assert len(sti) == mu.aligned_capacity(flat.size, 7, block_m)
    np.testing.assert_array_equal(sti[:len(want)], want)
    assert (sti[len(want):] == flat.size).all()
    np.testing.assert_array_equal(be[:len(owners)], owners)
    assert (be[len(owners):] == 6).all()
    plain = mu.moe_align_block_size(jnp.asarray(ids), 7, block_m)
    assert len(plain) == 3
    np.testing.assert_array_equal(np.asarray(plain[0]), sti)


def test_one_rank_keeps_no_windows():
    """No exchange, no receive windows: a state is refused by name at
    one rank, where it is built and where it would be threaded."""
    ctx = _ctx(transport="fused")
    with pytest.raises(ValueError, match="exchanges with nobody"):
        create_ep_moe_state(ctx)
    x, logits, w_up, w_down = _data()
    with pytest.raises(ValueError, match="between ranks only"):
        moe_ops.ep_moe_device(x, logits, w_up, w_down, ctx, state={})


@pytest.mark.parametrize("tp", [1, 2])
def test_init_decode_state_is_none_at_one_rank_and_unchanged_across(
        tp, monkeypatch):
    """Under the fused transport ``init_decode_state`` builds one LL
    state per expert layer at tp = 2, as before, and nothing at tp = 1;
    ``moe_local`` says which."""
    monkeypatch.setattr(Transformer, "_moe_ep_ctx", force_fused_ctx())
    model, _ = _model(tp=tp, moe="ep")
    assert model._moe_ep_ctx(8, inference=True).transport == "fused"
    assert model.moe_local == (tp == 1)
    states = model.init_decode_state(ENGINE.token_budget)
    if tp == 1:
        assert states is None
        return
    assert [s is not None for s in states] == [False, True]
    assert states[1].disp_tok.shape[0] % 2 == 0
    assert int(np.asarray(states[1].parity)[0]) == 0


@pytest.mark.parametrize("moe, tp", [("none", 1), ("ep", 1), ("ep", 2)],
                         ids=["dense", "ep_one_rank", "ep_two_ranks"])
def test_the_engine_counts_the_steps_that_ran_the_local_path(moe, tp):
    """``EngineStats.moe_local_steps``: every device step of an engine
    whose expert layers have one rank, none of a dense model's or of an
    exchange between ranks; the workspaces are per width and absent
    where the step carries none; the tokens are the forward oracle's."""
    model, params = _model(tp=tp, moe=moe)
    eng = ServingEngine(model, params, ENGINE, use_pallas=False,
                        propagate_failures=True)
    reqs = [Request(rid=i, prompt=p, max_new=3, arrival=0.0)
            for i, p in enumerate(_prompts(10, 5))]
    stats = eng.run(reqs)
    steps = len(stats.step_tokens)
    assert stats.completed == 2 and steps >= 4
    assert model.moe_local == (moe == "ep" and tp == 1)
    assert stats.moe_local_steps == (steps if model.moe_local else 0)
    if moe == "none":
        assert eng.moe_state is None
    else:
        # off the chip the exchange rides the XLA transport: no
        # workspaces at either tp, one (absent) entry a width
        assert sorted(eng.moe_state) == sorted(
            {w for b in eng._rungs() for w in eng._widths(b)})
        assert set(eng.moe_state.values()) == {None}
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid
