"""KV pool append by page RUNS, not by rows: ONE launch a layer.

The continuous-batching step appends this step's K/V tokens to the
paged pools before it attends (append-then-attend,
``Transformer.serving_step``). Written as an XLA row scatter that
costs one serial update per packed row and KV head, valid or padding
(~65 ns each on a v5e: 12 288 rows a pool, 0.78 ms, 36 pools a dsmoe
step). But the engine packs a batched slot's tokens as ONE contiguous
span of the packed array (8-aligned ``q_starts[s]``, ``q_lens[s]``
long) that lands at CONSECUTIVE sequence positions ``first_pos[s] +
arange(q_lens[s])`` — so in at most ``ceil(q_len/page) + 1`` pages of
the slot's block-table row, and inside each page as one contiguous
``(len, D)`` run per KV head. A step's append is a few dozen such
(slot, page) UNITS, and this kernel's work is in proportion to them.

One unit = one pool page, read-modify-write:

* DMA the page ``pool[page_id]`` (all heads) and the window of the new
  rows that covers it, HBM → VMEM, K and V (and their two scale
  planes) at once;
* merge in VMEM: a run starts at ANY row of a tile and the new rows at
  any 8-aligned row of theirs, so the window is rotated by the dynamic
  difference (``pltpu.roll``, on 32-bit copies) and selected under the
  run's row mask, one tile group at a time, only the groups the run
  touches; scale planes the same along lanes;
* DMA the page back.

No two units of a step hold the same page (slots own their pages; a
shared prefix page lies below every cursor and is never written), so
units pipeline with no hazard: unit ``i+1``'s reads are in flight
while unit ``i`` merges and writes back (two VMEM slots).

The pools are aliased in place (``input_output_aliases``), under the
donation the step jit already has. The XLA row scatter stays in
``serving_step`` for head-sharded pools (``tp > 1``) and the engine's
degraded twin (``use_pallas=False``); it is this kernel's test oracle:
the pool after the kernel equals the pool after the scatter, bit for
bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.config import local_interpret
from triton_distributed_tpu.lang.launch import shmem_call

#: lane width of a vreg: the scale planes' rotate granule
LANES = 128

#: int32 fields of one unit descriptor: pool page id (-1: dropped),
#: source row of the page's row 0 (front pad included), first and
#: one-past-last row of the run inside the page
UNIT_FIELDS = 4


def row_tile(dtype, page: int) -> int:
    """Rows of one VMEM tile of ``dtype`` (8 for 32-bit, 16 for bf16,
    32 for int8), capped at the page: the merge's aligned granule."""
    native = 8 * (4 // jnp.dtype(dtype).itemsize)
    return min(native, page)


def lane_tile(page: int) -> int:
    """Lanes of one rotate granule of a scale plane: a vreg's 128, or
    the whole page where it is not whole vregs (interpreted only)."""
    return LANES if page % LANES == 0 else page


def max_units(t: int, slots: int, page: int) -> int:
    """Static bound on a step's units: every unit holds a token, and a
    span of ``L`` tokens lies in at most ``L // page + 2`` pages."""
    return min(t, t // page + 2 * slots)


def append_units(q_starts, q_lens, first_pos, block_table, *, page: int,
                 t: int):
    """The step's (slot, page) units as one int32 vector
    ``[n, (page_id, src_row, lo, hi) × max_units]`` — derived on the
    device from operands the step already has, once a step, shared by
    every layer. ``first_pos[s]``: sequence position of slot ``s``'s
    first packed token. A slot with ``q_len == 0`` has no unit; a unit
    whose table entry is -1 (or lies past the table) keeps its place
    with ``page_id = -1`` and is skipped by the kernel."""
    slots, pps = block_table.shape
    u_max = max_units(t, slots, page)
    q_lens = q_lens.astype(jnp.int32)
    p0 = jnp.maximum(first_pos.astype(jnp.int32), 0)
    g0 = p0 // page
    nseg = jnp.where(q_lens > 0, (p0 + q_lens - 1) // page - g0 + 1, 0)
    cum = jnp.cumsum(nseg)
    u = jnp.arange(u_max, dtype=jnp.int32)
    slot = jnp.minimum(
        jnp.sum(cum[None, :] <= u[:, None], axis=1), slots - 1
    ).astype(jnp.int32)
    seg = u - (cum[slot] - nseg[slot])
    pg = g0[slot] + seg
    live = (u < cum[-1]) & (pg < pps)
    page_id = jnp.where(
        live, block_table[slot, jnp.clip(pg, 0, pps - 1)], -1
    )
    base = pg * page - p0[slot]            # page row 0, relative to token 0
    lo = jnp.clip(-base, 0, page)
    hi = jnp.clip(q_lens[slot] - base, 0, page)
    # front pad of the new rows: ``page`` (see _pad_rows)
    src = q_starts.astype(jnp.int32)[slot] + base + page
    units = jnp.stack([page_id, src, lo, hi], axis=1)
    units = jnp.where(live[:, None], units, jnp.array([-1, 0, 0, 0]))
    n = jnp.minimum(cum[-1], u_max).astype(jnp.int32)
    return jnp.concatenate([n[None], units.reshape(-1).astype(jnp.int32)])


def append_rows_xla(pool, new, rows):
    """The kernel's XLA twin, and ``serving_step``'s append wherever
    the kernel does not run: ONE-index row scatters over the pool
    viewed as ``(npages·Hkv·page, ...)`` rows, ``rows[i]`` the flat row
    of ``new[i]`` (past the last row: dropped). One serial update a
    packed row and KV head, valid or padding."""
    flat = pool.reshape(-1, *pool.shape[3:])
    return flat.at[rows].set(new).reshape(pool.shape)


def _append_kernel(hkv, page, d, tile, lt, quant, n_pools, units, *refs):
    """Software-pipelined loop over the step's units (module docstring).
    ``refs``: the HBM operands ``[pool, new] × (K, V) [+ (scale, new
    scale) × (K, V)]``, the aliased HBM outputs, then per stream a
    page buffer and a window buffer (2 slots each) and the DMA
    semaphores. ``n_pools`` 1: ONE pool (a latent pool: one entry a
    token, no V), the same loop over half the streams."""
    n_streams = n_pools * (2 if quant else 1)
    ins = refs[:2 * n_streams]
    outs = refs[2 * n_streams:3 * n_streams]
    scratch = refs[3 * n_streams:]
    bufs = scratch[:2 * n_streams]
    sem_in, sem_win, sem_out = scratch[2 * n_streams:]
    streams = []
    for k in range(n_streams):
        streams.append(dict(
            pool=ins[2 * k], new=ins[2 * k + 1], out=outs[k],
            buf=bufs[2 * k], win=bufs[2 * k + 1], k=k,
            scale=k >= n_pools,
        ))
    n = units[0]
    n_lg = page // lt

    def field(i, f):
        return units[1 + i * UNIT_FIELDS + f]

    def live(i):
        return field(i, 0) >= 0

    def reads(i, b):
        """DMA descriptors of unit ``i``'s loads into slot ``b``."""
        page_id = field(i, 0)
        src = field(i, 1)
        out = []
        for st in streams:
            out.append(pltpu.make_async_copy(
                st["pool"].at[page_id], st["buf"].at[b],
                sem_in.at[st["k"], b]))
            if st["scale"]:
                # chunk-major new scales (nchunks, Hkv, lt): the
                # window is n_lg + 1 whole chunks
                c0 = src // lt
                window = st["new"].at[pl.ds(c0, n_lg + 1)]
            else:
                r0 = pl.multiple_of((src // tile) * tile, tile)
                window = st["new"].at[:, pl.ds(r0, page + tile), :]
            out.append(pltpu.make_async_copy(
                window, st["win"].at[b], sem_win.at[st["k"], b]))
        return out

    def writes(i, b):
        page_id = field(i, 0)
        return [
            pltpu.make_async_copy(
                st["buf"].at[b], st["out"].at[page_id],
                sem_out.at[st["k"], b])
            for st in streams
        ]

    def merge_rows(st, b, src, lo, hi):
        """Rows ``[lo, hi)`` of the page buffer take the window's rows
        ``[shift + lo, shift + hi)``: per touched tile group, the two
        window tiles under it rotated up by ``shift`` (< tile)."""
        buf, win = st["buf"], st["win"]
        shift = src % tile
        n_rows = hkv * 2 * tile
        rot = (n_rows - shift) % n_rows

        def group(tg, carry):
            r0 = pl.multiple_of(tg * tile, tile)
            old = buf[b, :, pl.ds(r0, tile), :]
            w = win[b, :, pl.ds(r0, 2 * tile), :]
            w = w.astype(jnp.float32).reshape(hkv * 2 * tile, d)
            # cyclic over the flattened (head, row) axis: row r < tile
            # of a head reads its row r + shift < 2·tile, same head
            w = pltpu.roll(w, rot, 0).reshape(hkv, 2 * tile, d)
            new = w[:, :tile, :]
            row = r0 + jax.lax.broadcasted_iota(
                jnp.int32, (hkv, tile, d), 1)
            keep = (row >= lo) & (row < hi)
            merged = jnp.where(keep, new, old.astype(jnp.float32))
            buf[b, :, pl.ds(r0, tile), :] = merged.astype(old.dtype)
            return carry

        jax.lax.fori_loop(lo // tile, (hi - 1) // tile + 1, group, 0)

    def merge_lanes(st, b, src, lo, hi):
        """The same along lanes for a ``(Hkv, page)`` scale plane:
        window chunks ``lg`` and ``lg + 1`` rotated left by ``shift``
        (< lt), each lane taking the chunk its source falls in."""
        buf, win = st["buf"], st["win"]
        shift = src % lt
        rot = (lt - shift) % lt
        lane = jax.lax.broadcasted_iota(jnp.int32, (hkv, lt), 1)
        for lg in range(n_lg):
            sl = slice(lg * lt, (lg + 1) * lt)
            c0 = pltpu.roll(win[b, lg], rot, 1)
            c1 = pltpu.roll(win[b, lg + 1], rot, 1)
            new = jnp.where(lane + shift < lt, c0, c1)
            pos = lane + lg * lt
            keep = (pos >= lo) & (pos < hi)
            buf[b, :, sl] = jnp.where(keep, new, buf[b, :, sl])

    @pl.when(jnp.logical_and(n > 0, live(0)))
    def _prologue():
        for c in reads(0, 0):
            c.start()

    def unit(i, carry):
        b = i % 2

        @pl.when(live(i))
        def _arrived():
            for c in reads(i, b):
                c.wait()

        @pl.when(i + 1 < n)
        def _prefetch():
            @pl.when(jnp.logical_and(i >= 1, live(jnp.maximum(i - 1, 0))))
            def _slot_free():
                for c in writes(jnp.maximum(i - 1, 0), 1 - b):
                    c.wait()

            @pl.when(live(i + 1))
            def _next():
                for c in reads(i + 1, 1 - b):
                    c.start()

        @pl.when(live(i))
        def _merge():
            src, lo, hi = field(i, 1), field(i, 2), field(i, 3)
            for st in streams:
                if st["scale"]:
                    merge_lanes(st, b, src, lo, hi)
                else:
                    merge_rows(st, b, src, lo, hi)
            for c in writes(i, b):
                c.start()

        return carry

    jax.lax.fori_loop(0, n, unit, 0)

    # the last two units' write-backs are still in flight
    for back in (2, 1):
        i = n - back

        @pl.when(jnp.logical_and(i >= 0, live(jnp.maximum(i, 0))))
        def _drain(i=i):
            for c in writes(jnp.maximum(i, 0), jnp.maximum(i, 0) % 2):
                c.wait()


@functools.lru_cache(maxsize=64)
def _build_append(npages, hkv, page, d, dtype, quant, interpret,
                  token=(), n_pools=2):
    """The pallas_call, cached on the static geometry: taking ``(units,
    k_pool, k_new, v_pool, v_new[, k_scale, k_snew, v_scale, v_snew])``
    and returning the pools (and scale planes) in place. ``n_pools``
    1: ``(units, pool, new)``, the launch ``kv_append_latent``."""
    del token
    dtype = jnp.dtype(dtype)
    tile = row_tile(dtype, page)
    lt = lane_tile(page)
    n_lg = page // lt
    kernel = functools.partial(
        _append_kernel, hkv, page, d, tile, lt, quant, n_pools)
    pool = jax.ShapeDtypeStruct((npages, hkv, page, d), dtype)
    plane = jax.ShapeDtypeStruct((npages, hkv, page), jnp.float32)
    out_shape = [pool] * n_pools + ([plane] * n_pools if quant else [])
    n_streams = len(out_shape)
    scratch = []
    for _ in range(n_pools):
        scratch += [pltpu.VMEM((2, hkv, page, d), dtype),
                    pltpu.VMEM((2, hkv, page + tile, d), dtype)]
    for _ in range(n_pools if quant else 0):
        scratch += [pltpu.VMEM((2, hkv, page), jnp.float32),
                    pltpu.VMEM((2, n_lg + 1, hkv, lt), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((n_streams, 2))] * 3
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[any_spec] * (2 * n_streams),
        out_specs=[any_spec] * n_streams,
        scratch_shapes=scratch,
    )
    return shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        collective_id=None,                   # purely local kernel
        interpret=local_interpret() if interpret is None else interpret,
        # operand 0 is the scalar-prefetched unit list; pools sit at
        # 1, 3[, 5, 7] and come back as outputs 0, 1[, 2, 3]
        input_output_aliases={1 + 2 * k: k for k in range(n_streams)},
        name="kv_append" + ("_latent" if n_pools == 1 else "")
        + ("_q8" if quant else ""),
        dimension_semantics=("arbitrary",),
    )


def _pad_rows(new, page, tile):
    """(T, Hkv, D) new rows → head-major ``(Hkv, page + T + page +
    tile, D)``: a unit's window starts up to ``page - 1`` rows before
    the span (a run that starts low in its page) and ends up to ``page
    + tile`` rows past it."""
    return jnp.pad(new.transpose(1, 0, 2),
                   ((0, 0), (page, page + tile), (0, 0)))


def _pad_lanes(news, page, lt):
    """(T, Hkv) new scales → chunk-major ``(nchunks, Hkv, lt)`` with
    the same front pad: a window is whole chunks on the leading dim."""
    t, hkv = news.shape
    total = -(-(2 * page + t + lt) // lt) * lt
    x = jnp.pad(news.T, ((0, 0), (page, total - page - t)))
    return x.reshape(hkv, total // lt, lt).transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_append(units, k_pool, v_pool, k_new, v_new, *, interpret=None):
    """Append one layer's new K/V rows to its pools, in place (jitted:
    a step's layers trace and lower it once, not once each).

    ``units``: :func:`append_units` of the step; ``k_pool``/``v_pool``:
    ``(npages, Hkv, page, D)`` arrays, or ``{"q": int8 pool, "scale":
    (npages, Hkv, page) f32}`` dicts; ``k_new``/``v_new``: ``(T, Hkv,
    D)`` rows in the pool's dtype, or ``{"q": int8 rows, "scale": (T,
    Hkv) f32}`` dicts. ``v_pool`` and ``v_new`` None: ``k_pool`` is a
    LATENT pool (one entry a token, no V), appended as the one stream.
    Returns the pools in the form given. Rows outside every unit's
    run, and whole pages no unit names, keep their bytes.
    """
    if v_pool is None:
        return _append_latent(units, k_pool, k_new, interpret)
    quant = isinstance(k_pool, dict)
    kq, vq, kn, vn = (
        x["q"] if quant else x for x in (k_pool, v_pool, k_new, v_new))
    npages, hkv, page, d = kq.shape
    tile = row_tile(kq.dtype, page)
    lt = lane_tile(page)
    if page % tile or (page % LANES and not local_interpret(interpret)):
        raise ValueError(
            f"kv_append: page={page} must be whole {tile}-row tiles, and "
            f"under Mosaic a multiple of {LANES} (its scale plane whole "
            "lanes) — what the ragged kernel asks of an int8 pool"
        )
    call = _build_append(
        npages, hkv, page, d, jnp.dtype(kq.dtype).name, quant, interpret,
    )
    args = [units, kq, _pad_rows(kn, page, tile),
            vq, _pad_rows(vn, page, tile)]
    if quant:
        args += [k_pool["scale"], _pad_lanes(k_new["scale"], page, lt),
                 v_pool["scale"], _pad_lanes(v_new["scale"], page, lt)]
    out = call(*args)
    if quant:
        return ({"q": out[0], "scale": out[2]},
                {"q": out[1], "scale": out[3]})
    return out[0], out[1]


def _append_latent(units, pool, new, interpret):
    """:func:`kv_append` for a LATENT pool ``(npages, 1, page, D)``:
    one entry a token for every head and no V pool, so one stream of
    the same unit loop (launch ``kv_append_latent``). ``new``: (T, 1,
    D). Returns ``(pool, None)``."""
    npages, hkv, page, d = pool.shape
    tile = row_tile(pool.dtype, page)
    if page % tile:
        raise ValueError(
            f"kv_append: page={page} must be whole {tile}-row tiles")
    call = _build_append(
        npages, hkv, page, d, jnp.dtype(pool.dtype).name, False,
        interpret, n_pools=1)
    (out,) = call(units, pool, _pad_rows(new, page, tile))
    return out, None


# ------------------------------------------------------------ lint surface

#: the registry / Mosaic pre-flight geometry: an int8 pool with its
#: scale planes; two batched slots — a decode row at position 5, and
#: positions 12..15, a run from the middle of a page to its end
LINT_GEOM = dict(npages=4, hkv=2, page=8, d=128, t=16, slots=2)
_LINT_BATCH = dict(q_starts=(0, 8), q_lens=(1, 4), first_pos=(5, 12),
                   table=((2, -1), (-1, 3)))


def build_lint_kernel(token=()):
    """Construct the kernel as production would (via ``shmem_call``, so
    the LaunchSpec is captured under its launch name) at
    :data:`LINT_GEOM`."""
    gm = LINT_GEOM
    return _build_append(
        gm["npages"], gm["hkv"], gm["page"], gm["d"], "int8", True,
        False, token,
    )


def lint_in_shapes():
    """``(shape, dtype)`` of the lint kernel's nine operands, the new
    rows and scales as :func:`kv_append` pads them."""
    gm = LINT_GEOM
    page, hkv = gm["page"], gm["hkv"]
    units = jax.eval_shape(lint_units)
    new = jax.eval_shape(
        lambda x: _pad_rows(x, page, row_tile(jnp.int8, page)),
        jax.ShapeDtypeStruct((gm["t"], hkv, gm["d"]), jnp.int8))
    snew = jax.eval_shape(
        lambda x: _pad_lanes(x, page, lane_tile(page)),
        jax.ShapeDtypeStruct((gm["t"], hkv), jnp.float32))
    pool = jax.ShapeDtypeStruct((gm["npages"], hkv, page, gm["d"]), jnp.int8)
    plane = jax.ShapeDtypeStruct((gm["npages"], hkv, page), jnp.float32)
    return [(x.shape, x.dtype) for x in (
        units, pool, new, pool, new, plane, snew, plane, snew)]


def lint_units():
    """The unit list of :data:`_LINT_BATCH`, as the step derives it."""
    b = {k: jnp.asarray(v, jnp.int32) for k, v in _LINT_BATCH.items()}
    return append_units(b["q_starts"], b["q_lens"], b["first_pos"],
                        b["table"], page=LINT_GEOM["page"], t=LINT_GEOM["t"])
