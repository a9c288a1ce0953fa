"""The pool append by (slot, page) runs: ``kernels/kv_append``.

The kernel and its XLA twin — the row scatter ``serving_step`` keeps
for head-sharded pools and ``use_pallas=False`` — must leave the SAME
BYTES in the pools: over random ragged batches in every pool dtype, a
decode row at every offset of a tile, a chunk across three pages,
slots outside the batch, unallocated (-1) table entries, and pages no
run names. The kernel relies on the engine's packing contract (one
8-aligned contiguous span a slot, consecutive positions), which has
its own property test; and whole engines must serve the same token
streams by either path. Last, the kernel compiles for the chip at both
benchmark cells' real pool shapes (AOT, no chip: skipped where the
topology cannot be described) — and so does the grouped GEMM with a
dummy tail, which shares the fixture that loads libtpu.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_distributed_tpu.kernels.kv_append import (
    UNIT_FIELDS,
    append_rows_xla,
    append_units,
    kv_append,
    max_units,
)
from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import (
    EngineConfig,
    ServingEngine,
    SpeculativeEngine,
    TreeDrafter,
    poisson_trace,
)

pytestmark = pytest.mark.fast

SLOTS, PPS, NPAGES = 6, 8, 24


def _batch(page, t, plan, seed=0, drop=()):
    """Pack ``plan`` = [(slot, q_len, first_pos)] as the engine does:
    spans at 8-aligned starts in slot order, every page a span touches
    allocated (but the ``(slot, page index)`` pairs in ``drop``), slots
    outside the batch parked past the spans, everything else padding."""
    rng = np.random.default_rng(seed)
    token_rows = np.zeros((t,), np.int32)
    token_pos = np.full((t,), -1, np.int32)
    q_starts = np.full((SLOTS,), t - 8, np.int32)
    q_lens = np.zeros((SLOTS,), np.int32)
    table = np.full((SLOTS, PPS), -1, np.int32)
    perm = rng.permutation(NPAGES)
    nxt = start = 0
    for s, ln, p0 in plan:
        for pg in range(p0 // page, (p0 + ln - 1) // page + 1):
            if (s, pg) not in drop:
                table[s, pg] = perm[nxt]
            nxt += 1
        token_rows[start:start + ln] = s
        token_pos[start:start + ln] = np.arange(p0, p0 + ln)
        q_starts[s], q_lens[s] = start, ln
        start += -(-ln // 8) * 8
    assert start <= t - 8
    return token_rows, token_pos, q_starts, q_lens, table


def _scatter(pool, new, token_rows, token_pos, table, page):
    """``serving_step``'s row scatter (the tp == 1 form), addressed
    per token from ``token_rows`` / ``token_pos`` as it does."""
    npages, hkv = pool.shape[:2]
    valid = token_pos >= 0
    pos_c = jnp.maximum(token_pos, 0)
    local_page = table[jnp.clip(token_rows, 0, table.shape[0] - 1),
                       jnp.clip(pos_c // page, 0, table.shape[1] - 1)]
    pool_idx = jnp.where(valid & (local_page >= 0), local_page, npages)
    rows = ((pool_idx[:, None] * hkv + jnp.arange(hkv)[None, :]) * page
            + (pos_c % page)[:, None]).reshape(-1)
    return np.asarray(
        append_rows_xla(pool, new.reshape(-1, *new.shape[2:]), rows))


def _random(rng, shape, dtype):
    if dtype == jnp.int8:
        return jnp.asarray(rng.integers(-127, 128, shape).astype(np.int8))
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _check(dtype, hkv, page, plan, *, t=96, d=16, seed=0, drop=()):
    """Kernel == scatter, bit for bit, K and V (and both scale planes
    of an int8 pool); returns (before, after, table) of the K pool."""
    rng = np.random.default_rng(seed + 1)
    tr, tp, qs, ql, table = map(
        jnp.asarray, _batch(page, t, plan, seed, drop))
    quant = dtype == jnp.int8
    pools = [_random(rng, (NPAGES, hkv, page, d), dtype) for _ in "kv"]
    news = [_random(rng, (t, hkv, d), dtype) for _ in "kv"]
    units = append_units(qs, ql, tp[jnp.clip(qs, 0, t - 1)], table,
                         page=page, t=t)
    assert units.shape == (1 + UNIT_FIELDS * max_units(t, SLOTS, page),)
    want = [_scatter(p, n, tr, tp, table, page)
            for p, n in zip(pools, news)]
    if quant:
        planes = [_random(rng, (NPAGES, hkv, page), jnp.float32)
                  for _ in "kv"]
        snews = [_random(rng, (t, hkv), jnp.float32) for _ in "kv"]
        want += [_scatter(p, n, tr, tp, table, page)
                 for p, n in zip(planes, snews)]
        ko, vo = kv_append(
            units, *({"q": p, "scale": s} for p, s in zip(pools, planes)),
            *({"q": n, "scale": s} for n, s in zip(news, snews)))
        got = [ko["q"], vo["q"], ko["scale"], vo["scale"]]
    else:
        got = list(kv_append(units, *pools, *news))
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))
    return np.asarray(pools[0]), np.asarray(got[0]), np.asarray(table)


def _ragged_plan(rng, page, t):
    """A random mixed batch: decode rows and chunks at random cursors,
    some slots left out."""
    plan, room = [], t - 8
    for s in rng.permutation(SLOTS)[:rng.integers(2, SLOTS + 1)]:
        ln = int(rng.choice([1, 1, 1, rng.integers(2, 3 * page)]))
        ln = min(ln, room - 8)
        if ln <= 0:
            break
        p0 = int(rng.integers(0, PPS * page - ln + 1))
        plan.append((int(s), ln, p0))
        room -= -(-ln // 8) * 8
    return sorted(plan)


@pytest.mark.parametrize("hkv_g", [(16, 1), (8, 4), (2, 2)],
                         ids=lambda hg: f"hkv{hg[0]}g{hg[1]}")
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16, jnp.float32],
                         ids=["int8_scales", "bf16", "float32"])
def test_kernel_equals_scatter_on_random_ragged_batches(dtype, hkv_g):
    # G = Hq // Hkv is the attention kernel's; the pools hold KV heads
    hkv, _ = hkv_g
    rng = np.random.default_rng(hkv + 1000 * jnp.dtype(dtype).itemsize)
    for i in range(2):
        _check(dtype, hkv, 32, _ragged_plan(rng, 32, 96), seed=i)


@pytest.mark.parametrize("offset", [0, 1, 15, 16, 31, 63])
@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8_scales", "bf16"])
def test_decode_row_at_every_offset_of_a_tile(dtype, offset):
    """One token lands on row ``offset`` of its page (page 64: two
    int8 tiles, four bf16 tiles), beside a decode row elsewhere."""
    _check(dtype, 2, 64, [(1, 1, 64 + offset), (4, 1, 7)])


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8_scales", "bf16"])
def test_a_256_token_chunk_spans_three_pages(dtype):
    before, after, table = _check(
        dtype, 2, 128, [(0, 1, 300), (2, 256, 100)], t=288)
    pages = table[2, :3]
    assert (pages >= 0).all() and (table[2, 3:] < 0).all()
    # rows 100.. of the first page, the second whole, rows ..99 of the
    # third changed; the first page's rows below the cursor did not
    assert (after[pages[1]] != before[pages[1]]).any(axis=(0, 2)).all()
    np.testing.assert_array_equal(after[pages[0], :, :100],
                                  before[pages[0], :, :100])
    np.testing.assert_array_equal(after[pages[2], :, 100:],
                                  before[pages[2], :, 100:])


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float32],
                         ids=["int8_scales", "float32"])
def test_slots_outside_the_batch_and_padding_rows_write_nothing(dtype):
    """Only slots 1 and 3 are batched (``q_lens == 0`` elsewhere, their
    ``q_starts`` parked); every packed row outside the two spans is
    padding (``token_pos < 0``)."""
    before, after, table = _check(dtype, 2, 32, [(1, 3, 30), (3, 1, 0)])
    touched = set(table[table >= 0].tolist())
    assert len(touched) == 3                 # 30..32 crosses a page
    for pg in set(range(NPAGES)) - touched:
        np.testing.assert_array_equal(after[pg], before[pg])


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8_scales", "bf16"])
def test_an_unallocated_table_entry_drops_its_run_only(dtype):
    """The chunk's middle page has table entry -1: that run is dropped,
    the runs before and after it (and the neighbour slots) land."""
    before, after, table = _check(
        dtype, 2, 32, [(0, 1, 5), (2, 70, 20), (5, 2, 31)],
        drop={(2, 1)})
    assert table[2, 1] == -1 and table[2, 0] >= 0 and table[2, 2] >= 0
    assert (after[table[2, 0], :, 20:] != before[table[2, 0], :, 20:]).any()
    assert (after[table[2, 2], :, :26] != before[table[2, 2], :, :26]).any()


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16, jnp.float32],
                         ids=["int8_scales", "bf16", "float32"])
def test_page_of_eight(dtype):
    """The engine tests' page: smaller than a bf16 or int8 tile."""
    _check(dtype, 2, 8, [(0, 1, 5), (2, 20, 3), (3, 7, 8), (5, 1, 63)],
           t=64)


def test_pages_no_run_names_keep_their_bytes():
    before, after, table = _check(
        jnp.int8, 16, 32, [(0, 1, 33), (1, 40, 60), (4, 1, 255)])
    touched = set(table[table >= 0].tolist())
    assert 0 < len(touched) < NPAGES
    for pg in range(NPAGES):
        same = np.array_equal(after[pg], before[pg])
        assert same == (pg not in touched), pg


def test_unit_list_of_an_empty_step_is_empty():
    z = jnp.zeros((SLOTS,), jnp.int32)
    units = append_units(z + 88, z, z - 1,
                         jnp.full((SLOTS, PPS), -1, jnp.int32),
                         page=32, t=96)
    assert int(units[0]) == 0
    assert (np.asarray(units[1:]).reshape(-1, UNIT_FIELDS)[:, 0] == -1).all()


# ------------------------------------------------- the packing contract

CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
ECFG = dict(slots=4, token_budget=48, chunk=16, page=8, npages=40)


@pytest.fixture(scope="module")
def model_params():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    model = Transformer(
        TransformerConfig(**CFG, kv_quant="int8"), mesh, "tp", ())
    return model, model.init(jax.random.PRNGKey(0))


def _motif_trace(seed, n=6):
    """Prompts of repeated motifs, so that the drafters draft."""
    trace = poisson_trace(seed, n, 0.7, 5, 40, 6, 14, 128)
    rng = np.random.default_rng(seed + 1000)
    for r in trace:
        motif = rng.integers(0, 128, (5,)).astype(np.int32)
        r.prompt = np.tile(motif, -(-len(r.prompt) // 5))[:len(r.prompt)]
    return trace


def _engine(kind, model, params, **kw):
    if kind == "plain":
        return ServingEngine(model, params, EngineConfig(**ECFG), **kw)
    spec = dict(spec_k=4) if kind == "spec_linear" else dict(
        spec_tree=8, drafter=TreeDrafter(branches=3, branch_len=2))
    return SpeculativeEngine(model, params, EngineConfig(**ECFG),
                             **spec, **kw)


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("kind", ["plain", "spec_linear", "spec_tree"])
def test_assemble_packs_one_aligned_contiguous_span_a_slot(
        model_params, kind, seed):
    """What the append kernel (and ``serving_step``'s docstring) takes
    from ``_assemble``: a batched slot's tokens are one span at an
    8-aligned start, spans neither overlap nor pass the budget, they
    sit at consecutive positions from the slot's cursor, and every
    other packed row is padding."""
    model, params = model_params
    eng = _engine(kind, model, params, use_pallas=False)
    seen = []
    assemble = eng._assemble

    def checked():
        out = assemble()
        tokens, token_rows, token_pos, q_starts, q_lens = out[:5]
        batched = out[7]
        covered = np.zeros(len(tokens), bool)
        assert set(np.flatnonzero(q_lens)) == set(batched)
        for s in batched:
            a, ln = int(q_starts[s]), int(q_lens[s])
            assert a % 8 == 0 and ln > 0
            assert a + ln <= eng.cfg.token_budget
            assert not covered[a:a + ln].any()
            covered[a:a + ln] = True
            assert (token_rows[a:a + ln] == s).all()
            # (the launch-side cursor: the step in flight applied)
            first = eng._view(eng.slot_req[s])[0]
            np.testing.assert_array_equal(
                token_pos[a:a + ln], first + np.arange(ln))
        assert (token_pos[~covered] == -1).all()
        # the rest stay at row 0 (every launch skips them), and the
        # step is the narrowest width of its rung that holds the
        # launch's block of every batched row
        rung = eng._rung(int(q_lens.max()))
        assert len(tokens) in eng._widths(rung)
        assert (q_starts[q_lens == 0] == 0).all()
        need = model.step_rows_needed(q_starts, q_lens, rung)
        assert need == max((q_starts[s] + rung for s in batched), default=0)
        assert len(tokens) == min(
            w for w in eng._widths(rung) if w >= need)
        seen.append(len(batched))
        return out

    eng._assemble = checked
    eng.run(_motif_trace(seed), max_steps=400)
    assert sum(seen) > 20 and max(seen) > 1


# ------------------------------------------------------ whole engines


@pytest.mark.parametrize("kind", ["plain", "spec_tree"])
def test_engines_serve_the_same_streams_by_kernel_and_by_scatter(
        model_params, kind, monkeypatch):
    """``use_pallas=True`` twice, the append by the kernel and — the
    model told that its heads are sharded — by the scatter: the same
    token streams, and the counters say which path ran."""
    model, params = model_params
    streams, stats = [], []
    for by_kernel in (True, False):
        if not by_kernel:
            monkeypatch.setattr(
                Transformer, "kv_append_by_kernel",
                lambda self, use_pallas: False)
            # the traced steps captured the kernel: another jit
            monkeypatch.delitem(model.__dict__, "_serving_jit", False)
            monkeypatch.delitem(
                model.__dict__, "_serving_all_logits_jit", False)
        eng = _engine(kind, model, params)
        trace = _motif_trace(5)
        eng.run(trace, max_steps=400)
        assert eng.use_pallas and not eng.stats.degraded
        streams.append([tuple(r.generated) for r in trace])
        stats.append(eng.stats)
    assert streams[0] == streams[1]
    assert all(len(s) >= 6 for s in streams[0])
    steps = len(stats[0].step_times)
    assert stats[0].append_runs >= steps > 0
    assert stats[0].append_scatter_steps == 0
    assert stats[1].append_runs == 0
    assert stats[1].append_scatter_steps == len(stats[1].step_times)
    monkeypatch.undo()
    model.__dict__.pop("_serving_jit", None)
    model.__dict__.pop("_serving_all_logits_jit", None)


# ------------------------------------------------- compiles for the chip


@pytest.fixture(scope="module")
def one_chip():
    """An unattached v5e chip to compile for. Described here, inside a
    fixture, never at import: one process at a time may load libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("t", [768, 264], ids=["wide", "narrow"])
@pytest.mark.parametrize(
    "npages,hkv,dtype",
    [(640, 16, jnp.int8), (1024, 8, jnp.bfloat16)],
    ids=["dsmoe16b_s8_640x16", "mixtral8x7b_bf16_1024x8"])
def test_kernel_compiles_for_the_chip_at_the_cells_pool_shapes(
        one_chip, npages, hkv, dtype, t):
    """Mosaic accepts the kernel at both packed widths of the cells'
    engine (768, and a decode-only step's 264), page 128, head 128; the
    pools are updated in place: no pool-sized temporary."""
    from triton_distributed_tpu.config import config

    slots, page, d = 32, 128, 128

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    units = arg((1 + UNIT_FIELDS * max_units(t, slots, page),), jnp.int32)
    pool, new = arg((npages, hkv, page, d), dtype), arg((t, hkv, d), dtype)
    if dtype == jnp.int8:
        plane = arg((npages, hkv, page), jnp.float32)
        snew = arg((t, hkv), jnp.float32)

        def fn(u, kq, ks, vq, vs, kn, ksn, vn, vsn):
            return kv_append(
                u, {"q": kq, "scale": ks}, {"q": vq, "scale": vs},
                {"q": kn, "scale": ksn}, {"q": vn, "scale": vsn})

        args = (units, pool, plane, pool, plane, new, snew, new, snew)
        donate = (1, 2, 3, 4)
    else:
        fn, args, donate = kv_append, (units, pool, pool, new, new), (1, 2)
    old = config.force_compile
    config.force_compile = True
    try:
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        assert 'kernel_name = "kv_append' in lowered.as_text()
        compiled = lowered.compile()
    finally:
        config.force_compile = old
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize(
    "cap,bm,e,k,n,quant",
    [(12928, 128, 64, 2048, 1408, "w8a8"),
     (12928, 64, 64, 1408, 2048, "w8a16"),
     (3840, 256, 8, 4096, 14336, None),
     (9856, 128, 64, 2048, 1408, "w8a8")],
    ids=["dsmoe16b_w8a8_resident_up", "dsmoe16b_w8a16_resident_down",
         "mixtral8x7b_bf16_tiled_up", "dsmoe16b_w8a8_narrow_step_up"])
def test_grouped_matmul_with_a_dummy_tail_compiles_for_the_chip(
        one_chip, cap, bm, e, k, n, quant):
    """Mosaic accepts all three grouped-GEMM kernels with
    ``dummy_expert`` (a predicated multiply, index maps pinned by a
    prefetched block id) at the cells' expert-layer shapes. Here, not
    in a file of its own: one test file holds the fixture that loads
    libtpu."""
    from triton_distributed_tpu.config import config, fused_vmem_budget
    from triton_distributed_tpu.kernels.group_gemm import grouped_matmul

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kw = dict(block_m=bm, dummy_expert=e)
    wdt = jnp.bfloat16 if quant is None else jnp.int8
    args = [arg((cap, k), jnp.int8 if quant == "w8a8" else jnp.bfloat16),
            arg((e, k, n), wdt), arg((cap // bm,), jnp.int32)]
    names = []
    if quant is not None:            # the experts stay in fast memory
        kw.update(block_n=1 << 30, block_k=1 << 30,
                  vmem_limit_bytes=fused_vmem_budget())
        args.append(arg((e, n), jnp.float32))
        names.append("w_scale")
    if quant == "w8a8":
        args.append(arg((cap, 1), jnp.float32))
        names.append("x_scale")

    def fn(x, w, be, *scales):
        return grouped_matmul(x, w, be, **dict(zip(names, scales)), **kw)

    old = config.force_compile
    config.force_compile = True
    try:
        lowered = jax.jit(fn).lower(*args)
        assert "tpu_custom_call" in lowered.as_text()
        lowered.compile()
    finally:
        config.force_compile = old


def test_int8_dense_projection_compiles_at_the_narrow_width(one_chip):
    """``Transformer._dmm``'s W8A8 launch (ONE M-block, ``block_m`` the
    packed width) at a decode-only step's 264 rows: not a multiple of
    the int8 tile's 32 sublanes, and Mosaic takes it because the block
    is the whole dimension (dsmoe's ``wqkv``)."""
    from triton_distributed_tpu.config import config, fused_vmem_budget
    from triton_distributed_tpu.kernels.group_gemm import grouped_matmul

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    m, k, n = 264, 2048, 6144

    def fn(x, w, ws, xs):
        return grouped_matmul(
            x, w, jnp.zeros((1,), jnp.int32), w_scale=ws, x_scale=xs,
            block_m=m, vmem_limit_bytes=fused_vmem_budget(),
            out_dtype=jnp.bfloat16)

    old = config.force_compile
    config.force_compile = True
    try:
        lowered = jax.jit(fn).lower(
            arg((m, k), jnp.int8), arg((1, k, n), jnp.int8),
            arg((1, n), jnp.float32), arg((m, 1), jnp.float32))
        assert "tpu_custom_call" in lowered.as_text()
        lowered.compile()
    finally:
        config.force_compile = old

