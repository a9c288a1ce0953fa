"""Lightning (linear) attention over the serving step's packed rows.

One layer's mixer for a ragged batch of prefill chunks and decode
tokens, each row with a recurrent state of its own: per head ``h`` a
``D x D`` float32 matrix ``S`` with

    S_t = lam_h S_{t-1} + k_t^T v_t        lam_h = exp(-slope_h)
    o_t = (q_t / sqrt(D)) S_t              slope_h = 2^(-8 (h + 1) / H)

Row ``r`` of the step holds the ``q_lens[r]`` tokens at packed offsets
``[q_starts[r], q_starts[r] + q_lens[r])``, which sit at sequence
positions ``[kv_lens[r] - q_lens[r], kv_lens[r])`` (the serving step's
packing contract, ``Transformer.serving_step``). A span longer than
``SHORT`` tokens is computed in the CHUNK FORM, exact for any span
length: with ``n = q_lens[r]``, ``t, j`` local indices and ``S`` the
slot's state before the span,

    o_t  = (q_t / sqrt D) ( lam^(t+1) S + sum_{j <= t} lam^(t-j) k_j^T v_j )
    S'   = lam^n S + sum_j lam^(n-1-j) k_j^T v_j

a prefill chunk being one ``(n x n)`` decay-masked product plus the
carried state. A span of at most ``SHORT`` tokens (a decode row, a
prompt's tail) is the RANK-1 FORM, the recurrence itself a token at a
time on the vector unit: ``k_t`` down the state's rows times ``v_t``
along them, ``o_t`` the sum down the rows of ``q_t``-scaled ``S_t``; no
product, no decay mask. The twin evaluates the chunk form for every
span: two evaluations of one recurrence. A span that starts at position
0 starts from ``S = 0`` whatever the slot held (a slot reused by another
request needs no reset); a row with ``q_lens == 0`` is not visited and
its state stays as it is.

``lightning_attention`` is the Pallas kernel: grid over the step's
ACTIVE rows (a compacted list, so an inactive slot costs no state
traffic), the state block of the visited slot pipelined in and out by
its BlockSpec and aliased in place, the row's q/k/v block fetched by
double-buffered DMAs one row ahead, everything in float32.
``lightning_attention_xla`` is its twin (``use_pallas=False`` and the
tests): the chunk form's lines as gathers and einsums.

Both take q, k, v HEAD-MAJOR, ``(H, T, D)`` float32, and return
``(o (H, T, D) float32, state')``. Rows of ``o`` outside every span
hold garbage, as the ragged attention kernel's do: a row shorter than
its block writes the whole block, and the ascending order of the visits
lets the next row write over it. The block is ``block_q`` tokens, or
``SHORT`` for a row of at most that many.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.config import local_interpret
from triton_distributed_tpu.kernels.ragged_paged_attention import active_rows
from triton_distributed_tpu.lang.launch import shmem_call

HI = jax.lax.Precision.HIGHEST


def decay_slopes(heads: int):
    """``slope_h = 2^(-8 (h + 1) / heads)``, (heads,) float32: head
    ``h`` decays its state by ``exp(-slope_h)`` a token."""
    return jnp.exp2(-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                    / heads)


def _span_update(q, k, v, s_prev, slope, n, first, scale):
    """One head of one row: q, k, v (B, D) float32 of which the first
    ``n`` rows are the span, ``s_prev`` (D, D), ``first`` = the span
    starts at position 0. Returns ``(o (B, D), s_new (D, D))``."""
    b = q.shape[0]
    t = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    s_prev = jnp.where(first, 0.0, s_prev)
    qs = q * scale
    lag = t - j                                            # (B, B)
    decay = jnp.where(
        jnp.logical_and(lag >= 0, j < n),
        jnp.exp(-slope * jnp.maximum(lag, 0).astype(jnp.float32)), 0.0)
    a = jax.lax.dot_general(
        qs, k, (((1,), (1,)), ((), ())), precision=HI,
        preferred_element_type=jnp.float32) * decay
    carried = qs * jnp.exp(-slope * (t + 1).astype(jnp.float32))
    o = jnp.dot(a, v, precision=HI, preferred_element_type=jnp.float32) \
        + jnp.dot(carried, s_prev, precision=HI,
                  preferred_element_type=jnp.float32)
    kd = k * jnp.where(
        t < n, jnp.exp(-slope * jnp.maximum(n - 1 - t, 0)
                       .astype(jnp.float32)), 0.0)
    s_new = jnp.exp(-slope * n.astype(jnp.float32)) * s_prev \
        + jax.lax.dot_general(
            kd, v, (((0,), (0,)), ((), ())), precision=HI,
            preferred_element_type=jnp.float32)
    return o, s_new


#: a row of at most this many tokens (a decode row, a prompt's tail)
#: runs the rank-1 form and is fetched and written as a block of this
#: many, whatever the step's ``block_q``
SHORT = 8
#: heads of a short row whose first tokens are computed side by side
#: (one straight-line body, so one head's loads and transposes run under
#: another's arithmetic; traced once): measured on a v5e at the decode
#: shape of minicpmsala9b.docbatch, PR 47 (``CHANGES.md`` has the table)
SHORT_HEADS = 4


def short_row(n):
    """Whether a row of ``n`` tokens runs the rank-1 form (and moves a
    block of ``SHORT`` tokens); host or device values."""
    return n <= SHORT


def across(row, d):
    """``row`` (1, D) over key channels as the matrix (D, D) whose
    every COLUMN it is (row ``c`` holds ``row[c]`` in every lane): what
    scales or fills the state's rows. One 128 x 128 transpose of the
    row repeated down the sublanes; measured on a v5e (PR 41) against
    the transpose of an (8, D) block and a lane broadcast of its column
    a state vreg: 0.87 against 1.42 ms for 32 decode rows of 64
    heads."""
    return jnp.broadcast_to(row, (d, d)).T


def _token_update(q_t, k_t, v_t, s, lam):
    """One token of one head, the recurrence itself: ``q_t`` (scaled),
    ``k_t``, ``v_t``, ``lam`` (1, D), ``s`` (D, D). Returns ``(o_t (1,
    D), s_t)``."""
    d = s.shape[0]
    s = lam * s + across(k_t, d) * v_t
    return jnp.sum(across(q_t, d) * s, axis=0, keepdims=True), s


def query_block_tokens(q_lens, block_q: int):
    """Host side: per row, the packed tokens from ``q_starts[r]`` on
    that a launch at ``block_q`` fetches and writes for that row:
    ``SHORT`` for a row of at most that many, else ``block_q``; 0 for a
    row outside the batch (never visited). ``q_starts[r] + this <= T``
    is all the launch asks of the packed width."""
    q_lens = np.asarray(q_lens)
    block = np.where(short_row(q_lens), min(SHORT, block_q), block_q)
    return np.where(q_lens > 0, block, 0)


def _lightning_kernel(heads, d, block_q, scale, order_ref, n_ref,
                      kv_lens_ref, q_lens_ref, q_starts_ref, slopes_ref,
                      q_hbm, k_hbm, v_hbm, s_in, o_hbm, s_out,
                      qbuf, kbuf, vbuf, obuf, sem_in, sem_o):
    i = pl.program_id(0)
    n_active = n_ref[0]
    sizes = (SHORT, block_q) if block_q > SHORT else (block_q,)

    def by_size(step, fn):
        """``fn(b)`` with ``b`` the static block of row ``order[step]``:
        SHORT if it holds at most SHORT tokens, else ``block_q``."""
        short = short_row(q_lens_ref[order_ref[step]])
        for b in sizes:
            if len(sizes) == 1:
                fn(b)
            else:
                pl.when(short if b == SHORT else jnp.logical_not(short))(
                    functools.partial(fn, b))

    def fetch(step, slot, b):
        r = order_ref[step]
        start = pl.multiple_of(q_starts_ref[r], 8)
        return [
            pltpu.make_async_copy(
                src.at[:, pl.ds(start, b)], buf.at[slot, :, pl.ds(0, b)],
                sem_in.at[slot, x])
            for x, (src, buf) in enumerate(
                ((q_hbm, qbuf), (k_hbm, kbuf), (v_hbm, vbuf)))
        ]

    def start_fetch(step, slot):
        def start(b):
            for cp in fetch(step, slot, b):
                cp.start()

        by_size(step, start)

    @pl.when(jnp.logical_and(i == 0, n_active > 0))
    def _warm():
        start_fetch(0, 0)

    @pl.when(n_active == 0)
    def _nothing():
        # no row at all: the one block this launch holds goes back as
        # it came
        s_out[...] = s_in[...]

    @pl.when(i < n_active)
    def _row():
        slot = jax.lax.rem(i, 2)
        r = order_ref[i]

        @pl.when(i + 1 < n_active)
        def _ahead():
            start_fetch(i + 1, 1 - slot)

        n = q_lens_ref[r]
        first = kv_lens_ref[r] - n == 0
        start = pl.multiple_of(q_starts_ref[r], 8)

        def chunk_head(b, h, _):
            o, s_new = _span_update(
                qbuf[slot, h, :b], kbuf[slot, h, :b], vbuf[slot, h, :b],
                s_in[0, h], slopes_ref[h], n, first, scale)
            obuf[h, :b] = o
            s_out[0, h] = s_new
            return 0

        def short_heads(b):
            """The rank-1 form: every head's first token, ``SHORT_HEADS``
            heads side by side; then, for the rare row that has more,
            every head's other tokens one at a time."""
            t8 = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)

            def lam_of(h):
                return jnp.exp(jnp.full((1, d), -slopes_ref[h], jnp.float32))

            def first_token(h):
                o, s = _token_update(
                    qbuf[slot, h, 0:1] * scale, kbuf[slot, h, 0:1],
                    vbuf[slot, h, 0:1],
                    jnp.where(first, 0.0, s_in[0, h]), lam_of(h))
                obuf[h, :b] = jnp.where(t8 == 0, o, 0.0)
                s_out[0, h] = s

            side = math.gcd(heads, SHORT_HEADS)

            def group(g, _):
                jax.lax.fori_loop(
                    0, side, lambda x, _: first_token(g * side + x), None,
                    unroll=True)

            jax.lax.fori_loop(0, heads // side, group, None)

            @pl.when(n > 1)
            def _tail():
                def head(h, _):
                    qb, kb, vb = (buf[slot, h, :b]
                                  for buf in (qbuf, kbuf, vbuf))
                    lam = lam_of(h)

                    def token(t, carry):
                        s, o = carry
                        at = t8 == t

                        def row(x):
                            return jnp.sum(jnp.where(at, x, 0.0), axis=0,
                                           keepdims=True)

                        o_t, s = _token_update(
                            row(qb) * scale, row(kb), row(vb), s, lam)
                        return s, jnp.where(at, o_t, o)

                    s_out[0, h], obuf[h, :b] = jax.lax.fori_loop(
                        1, n, token, (s_out[0, h], obuf[h, :b]))

                jax.lax.fori_loop(0, heads, head, None)

        def span(b):
            for cp in fetch(i, slot, b):
                cp.wait()
            if b <= SHORT:
                short_heads(b)
            else:
                jax.lax.fori_loop(
                    0, heads, functools.partial(chunk_head, b), 0)
            out = pltpu.make_async_copy(
                obuf.at[:, pl.ds(0, b)], o_hbm.at[:, pl.ds(start, b)],
                sem_o.at[0])
            out.start()
            # waited before the grid advances: a short row's block runs
            # over the next row's span, which that row then writes
            out.wait()

        by_size(i, span)


@functools.lru_cache(maxsize=32)
def _build(r, t, heads, d, block_q, interpret):
    kernel = functools.partial(
        _lightning_kernel, heads, d, block_q, 1.0 / math.sqrt(d))
    f32 = jnp.float32
    state_spec = pl.BlockSpec(
        (1, heads, d, d), lambda i, order, *_: (order[i], 0, 0, 0))
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # order, n_active, kv_lens, q_lens, q_starts
        num_scalar_prefetch=5,
        grid=(r,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),   # slopes
                  any_, any_, any_, state_spec],
        out_specs=[any_, state_spec],
        scratch_shapes=[
            pltpu.VMEM((2, heads, block_q, d), f32),       # qbuf
            pltpu.VMEM((2, heads, block_q, d), f32),       # kbuf
            pltpu.VMEM((2, heads, block_q, d), f32),       # vbuf
            pltpu.VMEM((heads, block_q, d), f32),          # obuf
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    # q/k/v double-buffered + o, the state block in and out (each
    # double-buffered by the pipeline), and the (B, B) products
    need = (7 * heads * block_q * d + 4 * heads * d * d
            + 4 * block_q * max(block_q, d)) * 4
    return shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, t, d), f32),
                   jax.ShapeDtypeStruct((r, heads, d, d), f32)],
        # the state is updated in place: a slot the step does not visit
        # keeps its matrix (operands: 5 scalars, slopes, q, k, v, state)
        input_output_aliases={9: 1},
        collective_id=None,
        vmem_limit_bytes=need + (16 << 20),
        interpret=local_interpret() if interpret is None else interpret,
        name="lightning_attention",
        # the out blocks of consecutive rows overlap: ascending order
        dimension_semantics=("arbitrary",),
    )


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def lightning_attention(q, k, v, state, kv_lens, q_lens, q_starts, *,
                        block_q: int, interpret=None):
    """q, k, v: (H, T, D) float32 packed tokens, head-major; ``state``:
    (R, H, D, D) float32; ``kv_lens`` / ``q_lens`` / ``q_starts``: (R,)
    int32 as the ragged attention kernel takes them (lengths INCLUDE
    the step's tokens, starts 8-aligned, ``q_starts + block_q <= T``).
    Returns ``(o (H, T, D) float32, state')``."""
    heads, t, d = q.shape
    r = state.shape[0]
    order, n = active_rows(q_lens)
    call = _build(r, t, heads, d, int(block_q), interpret)
    o, new = call(order, n, kv_lens.astype(jnp.int32),
                  q_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
                  decay_slopes(heads), q, k, v, state)
    return o, new


def lightning_attention_xla(q, k, v, state, kv_lens, q_lens, q_starts, *,
                            block_q: int):
    """The kernel's XLA twin: same arguments, same lines."""
    heads, t, d = q.shape
    r = state.shape[0]
    at = q_starts[:, None] + jnp.arange(block_q)[None, :]      # (R, B)
    rows = jnp.clip(at, 0, t - 1)
    first = (kv_lens - q_lens) == 0

    def row(qr, kr, vr, s_prev, n, first):
        return jax.vmap(
            lambda a, b, c, s, slope: _span_update(
                a, b, c, s, slope, n, first, 1.0 / math.sqrt(d))
        )(qr, kr, vr, s_prev, decay_slopes(heads))

    # (H, R, B, D) -> per row (H, B, D)
    qr, kr, vr = (x[:, rows].transpose(1, 0, 2, 3) for x in (q, k, v))
    o_rows, s_new = jax.vmap(row)(qr, kr, vr, state, q_lens, first)
    live = jnp.arange(block_q)[None, :] < q_lens[:, None]
    dest = jnp.where(live, at, t).reshape(-1)                  # t: dropped
    o = jnp.zeros((heads, t, d), jnp.float32).at[:, dest].set(
        o_rows.transpose(1, 0, 2, 3).reshape(heads, -1, d), mode="drop")
    return o, jnp.where((q_lens > 0)[:, None, None, None], s_new, state)
