"""Gated delta-rule linear attention (KDA) over the serving step's
packed rows.

One layer's mixer for a ragged batch of prefill chunks and decode
tokens, each row with a recurrent state of its own: per head a ``D x
D`` float32 matrix ``S`` (key channels x value channels) with a decay
PER CHANNEL AND TOKEN ``a_t = exp(g_t)`` (``g_t <= 0``, ``D`` values a
head) and a correction of what the state already holds for ``k_t``:

    S'  = Diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        b_t in [0, 2]
    o_t = S_t^T q_t / sqrt(D)

(``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``: the
delta rule; ``kernels/lightning_attention.py`` is the case of one
constant decay a head and no correction.)

Rows, spans and the state contract are the lightning kernel's: row
``r`` holds the ``q_lens[r]`` tokens at packed offsets ``[q_starts[r],
q_starts[r] + q_lens[r])`` at sequence positions ``[kv_lens[r] -
q_lens[r], kv_lens[r])``; a span that starts at position 0 starts from
``S = 0`` whatever the slot held, a row with ``q_lens == 0`` is not
visited and its state stays as it is.

A span of at most ``SHORT`` tokens (a decode row, a prompt's tail) is
the RANK-1 FORM, token by token: ``S'^T k`` and ``S'^T q`` as one
product ``[a k; a q] S`` on the MXU, the decay and the update ``S_t =
Diag(a) S + k u^T`` on the vector unit, ``S_t^T q = S'^T q + u (k .
q)``. A longer span is an EXACT CHUNK FORM in float32 over
sub-chunks of ``SUB`` tokens with the state carried from one to the
next. Inside a sub-chunk, ``G_t`` the running sum of ``g`` from its
start and ``S`` the state before it:

    A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])      j < t
    B_tj = sum_c q_t[c] k_j[c] exp(G_t[c] - G_j[c])      j <= t
    u_t  = v_t - S^T (exp(G_t) k_t) - sum_{j<t} A_tj b_j u_j
    o_t  = (S^T (exp(G_t) q_t) + sum_{j<=t} B_tj b_j u_j) / sqrt(D)
    S'   = Diag(exp(G_last)) S + sum_j (exp(G_last - G_j) k_j) b_j u_j^T

``u`` is the unit lower-triangular system ``(I + A Diag(b)) U = R``
solved by forward substitution (the UT transform of the delta rule,
row by row: backward stable where the Neumann product is not, at ``b``
near 2). Every pairwise decay is formed as ``exp(G_t - G_j)`` with ``t
>= j``, an exponent that is never positive, and never as ``exp(G_t) *
exp(-G_j)``: under seeded weights a channel decays by ``e^-5`` and more
a token, and ``exp(-G_j)`` leaves float32 inside one sub-chunk. The
exponent itself is summed from its own ``g`` (``G_t - G_j = g_{j+1} +
... + g_t``, grown a token at a time), not taken as the difference of
two running sums, which after one strongly decayed token has lost the
digits of every later one. The products with ``S`` ride the MXU at
``Precision.HIGHEST``.

``kda_attention`` is the Pallas kernel: grid over the step's ACTIVE
rows (a compacted list) x groups of ``heads_per_step`` heads, the state
block of the visited slot and group pipelined in and out by its
BlockSpec and aliased in place, the row's q/k/v/g block fetched by
double-buffered DMAs one grid step ahead. ``kda_attention_xla`` is its
twin (``use_pallas=False`` and the tests): the recurrence itself, token
by token under a mask, as gathers and einsums.

Both take q, k, v, g HEAD-MAJOR, ``(H, T, D)`` float32 (q and k as the
model hands them: L2-normed; the ``1 / sqrt(D)`` is applied here) and
``beta`` ``(H, T)`` float32, and return ``(o (H, T, D) float32,
state')``. Rows of ``o`` outside every span hold zeros or another row's
block, as the lightning kernel's: a row shorter than its block writes
the whole block, and the ascending order of the visits lets the next
row write over it.
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
from triton_distributed_tpu.kernels.lightning_attention import across
from triton_distributed_tpu.kernels.ragged_paged_attention import active_rows
from triton_distributed_tpu.lang.launch import shmem_call

HI = jax.lax.Precision.HIGHEST

#: a row of at most this many tokens runs the rank-1 form and is
#: fetched and written as a block of this many
SHORT = 8
#: tokens of one sub-chunk of the chunk form
SUB = 16


def query_block_tokens(q_lens, block_q: int):
    """Host side: per row, the packed tokens from ``q_starts[r]`` on
    that a launch at ``block_q`` fetches and writes for that row:
    ``SHORT`` for a row of at most that many, else ``block_q``; 0 for a
    row outside the batch (never visited). ``q_starts[r] + this <= T``
    is all the launch asks of the packed width."""
    q_lens = np.asarray(q_lens)
    block = np.where(q_lens <= SHORT, min(SHORT, block_q), block_q)
    return np.where(q_lens > 0, block, 0)


def _kda_kernel(groups, hg, d, block_q, scale, order_ref, n_ref,
                kv_lens_ref, q_lens_ref, q_starts_ref, beta_ref,
                q_hbm, k_hbm, v_hbm, g_hbm, s_in, o_hbm, s_out,
                qbuf, kbuf, vbuf, gbuf, obuf, sem_in, sem_o):
    i, j = pl.program_id(0), pl.program_id(1)
    n_active = n_ref[0]
    sizes = (SHORT, block_q) if block_q > SHORT else (block_q,)
    f32 = jnp.float32

    def by_size(row_step, fn):
        """``fn(b)`` with ``b`` the static block of row
        ``order[row_step]``: SHORT if it holds at most SHORT tokens,
        else ``block_q``."""
        short = q_lens_ref[order_ref[row_step]] <= SHORT
        for b in sizes:
            if len(sizes) == 1:
                fn(b)
            else:
                pl.when(short if b == SHORT else jnp.logical_not(short))(
                    functools.partial(fn, b))

    def fetch(row_step, group, slot, b):
        r = order_ref[row_step]
        start = pl.multiple_of(q_starts_ref[r], 8)
        heads = pl.ds(pl.multiple_of(group * hg, hg), hg)
        return [
            pltpu.make_async_copy(
                src.at[heads, pl.ds(start, b)],
                buf.at[slot, :, pl.ds(0, b)], sem_in.at[slot, x])
            for x, (src, buf) in enumerate(
                ((q_hbm, qbuf), (k_hbm, kbuf), (v_hbm, vbuf),
                 (g_hbm, gbuf)))
        ]

    def start_fetch(row_step, group, slot):
        def start(b):
            for cp in fetch(row_step, group, slot, b):
                cp.start()

        by_size(row_step, start)

    step = i * groups + j
    slot = jax.lax.rem(step, 2)

    @pl.when(jnp.logical_and(step == 0, n_active > 0))
    def _warm():
        start_fetch(0, 0, 0)

    @pl.when(n_active == 0)
    def _nothing():
        # no row at all: the one block this launch holds goes back as
        # it came
        s_out[...] = s_in[...]

    @pl.when(i < n_active)
    def _row():
        r = order_ref[i]
        last_group = j + 1 == groups

        @pl.when(jnp.logical_or(jnp.logical_not(last_group),
                                i + 1 < n_active))
        def _ahead():
            start_fetch(jnp.where(last_group, i + 1, i),
                        jnp.where(last_group, 0, j + 1), 1 - slot)

        n = q_lens_ref[r]
        first = kv_lens_ref[r] - n == 0
        start = pl.multiple_of(q_starts_ref[r], 8)
        lanes = jax.lax.broadcasted_iota(
            jnp.int32, (1, beta_ref.shape[1]), 1)

        def beta_of(h, at, b):
            """(b, 1): the betas of global head ``h`` for the ``b``
            tokens from packed offset ``at``."""
            rows = beta_ref[pl.ds(at, b), :]
            return jnp.sum(jnp.where(lanes == h, rows, 0.0), axis=1,
                           keepdims=True)

        def short_head(hl, _):
            """The rank-1 form, a token at a time."""
            h = j * hg + hl
            qb, kb, vb = (buf[slot, hl, :SHORT]
                          for buf in (qbuf, kbuf, vbuf))
            ab = jnp.exp(gbuf[slot, hl, :SHORT])
            bt = beta_of(h, start, SHORT)
            r8 = jax.lax.broadcasted_iota(jnp.int32, (SHORT, 1), 0)
            obuf[hl, :SHORT] = jnp.zeros((SHORT, d), f32)

            def token(t, s):
                a_t, k_t, q_t = ab[t:t + 1], kb[t:t + 1], qb[t:t + 1]
                # S'^T k and S'^T q as ONE product with the state as it
                # came: S' = Diag(a) S, so S'^T x = S^T (a x)
                p = jnp.dot(
                    jnp.where(r8 == 0, a_t * k_t,
                              jnp.where(r8 == 1, a_t * q_t, 0.0)),
                    s, precision=HI, preferred_element_type=f32)
                u = bt[t:t + 1] * (vb[t:t + 1] - p[0:1])
                # S^T q = S'^T q + u (k . q)
                obuf[hl, t:t + 1] = scale * (p[1:2] + u * jnp.sum(
                    k_t * q_t, axis=1, keepdims=True))
                return s * across(a_t, d) + across(k_t, d) * u

            s_out[0, hl] = token(0, jnp.where(first, 0.0, s_in[0, hl]))
            for t in range(1, SHORT):
                @pl.when(t < n)
                def _more(t=t):
                    s_out[0, hl] = token(t, s_out[0, hl])

            return 0

        def chunk_head(b, hl, _):
            """The chunk form, a sub-chunk at a time."""
            h = j * hg + hl
            s_out[0, hl] = jnp.where(first, 0.0, s_in[0, hl])
            it = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)
            ti = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
            tj = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
            upto, after = (ti >= tj).astype(f32), (tj > ti).astype(f32)
            n_sub = (n + SUB - 1) // SUB

            def sub(c, _):
                at = pl.multiple_of(c * SUB, SUB)
                rows = pl.ds(at, SUB)
                q_, k_, v_ = (buf[slot, hl, rows]
                              for buf in (qbuf, kbuf, vbuf))
                live = at + it < n
                # a token past the span: no decay, no update
                g_ = jnp.where(live, gbuf[slot, hl, rows], 0.0)
                bc = jnp.where(live, beta_of(h, start + at, SUB), 0.0)
                # G_t (from the sub-chunk's start) and G_last - G_t, each
                # a sum of its own terms: no difference of running sums
                gam = jnp.dot(upto, g_, precision=HI,
                              preferred_element_type=f32)
                rest = jnp.dot(after, g_, precision=HI,
                               preferred_element_type=f32)
                s = s_out[0, hl]
                eg = jnp.exp(gam)
                res = v_ - jnp.dot(k_ * eg, s, precision=HI,
                                   preferred_element_type=f32)
                carried = jnp.dot(q_ * eg, s, precision=HI,
                                  preferred_element_type=f32)
                u = jnp.zeros((SUB, d), f32)
                o = jnp.zeros((SUB, d), f32)
                lag = jnp.zeros((SUB, d), f32)
                for t in range(SUB):
                    # G_t - G_j for j <= t, grown by g_t a token (rows
                    # j > t: unused): exp's argument is <= 0
                    lag = jnp.where(it < t, lag + g_[t:t + 1], 0.0)
                    ke = k_ * jnp.exp(lag)
                    a_t = jnp.sum(k_[t:t + 1] * ke, axis=1, keepdims=True)
                    u_t = res[t:t + 1] - jnp.sum(
                        jnp.where(it < t, a_t * bc, 0.0) * u, axis=0,
                        keepdims=True)
                    u = jnp.where(it == t, u_t, u)
                    b_t = jnp.sum(q_[t:t + 1] * ke, axis=1, keepdims=True)
                    o_t = jnp.sum(
                        jnp.where(it <= t, b_t * bc, 0.0) * u, axis=0,
                        keepdims=True)
                    o = jnp.where(it == t, o_t, o)
                obuf[hl, rows] = scale * (carried + o)
                kw = k_ * jnp.exp(rest)
                s_out[0, hl] = across(jnp.exp(gam[SUB - 1:SUB]), d) * s \
                    + jax.lax.dot_general(
                        kw, bc * u, (((0,), (0,)), ((), ())),
                        precision=HI, preferred_element_type=f32)
                return 0

            jax.lax.fori_loop(0, n_sub, sub, 0)

            def blank(c, _):
                obuf[hl, pl.ds(pl.multiple_of(c * SUB, SUB), SUB)] = \
                    jnp.zeros((SUB, d), f32)
                return 0

            jax.lax.fori_loop(n_sub, b // SUB, blank, 0)
            return 0

        def span(b):
            for cp in fetch(i, j, slot, b):
                cp.wait()
            head = short_head if b <= SHORT else functools.partial(
                chunk_head, b)
            jax.lax.fori_loop(0, hg, head, 0)
            out = pltpu.make_async_copy(
                obuf.at[:, pl.ds(0, b)],
                o_hbm.at[pl.ds(pl.multiple_of(j * hg, hg), hg),
                         pl.ds(start, b)],
                sem_o.at[0])
            out.start()
            # waited before the grid advances: a long row's block runs
            # over the next row's span, which that row then writes
            out.wait()

        by_size(i, span)


@functools.lru_cache(maxsize=32)
def _build(r, t, heads, d, block_q, hg, interpret):
    groups = heads // hg
    kernel = functools.partial(
        _kda_kernel, groups, hg, d, block_q, 1.0 / math.sqrt(d))
    f32 = jnp.float32

    def state_index(i, j, order, n, *_):
        # a grid step past the last active row re-visits the block the
        # last one left: no fetch, no write
        return (order[i], jnp.where(i < n[0], j, groups - 1), 0, 0)

    state_spec = pl.BlockSpec((1, hg, d, d), state_index)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # order, n_active, kv_lens, q_lens, q_starts
        num_scalar_prefetch=5,
        grid=(r, groups),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),   # beta (T, H)
                  any_, any_, any_, any_, state_spec],
        out_specs=[any_, state_spec],
        scratch_shapes=[
            pltpu.VMEM((2, hg, block_q, d), f32),          # qbuf
            pltpu.VMEM((2, hg, block_q, d), f32),          # kbuf
            pltpu.VMEM((2, hg, block_q, d), f32),          # vbuf
            pltpu.VMEM((2, hg, block_q, d), f32),          # gbuf
            pltpu.VMEM((hg, block_q, d), f32),             # obuf
            pltpu.SemaphoreType.DMA((2, 4)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    # q/k/v/g double-buffered + o, the state block in and out (each
    # double-buffered by the pipeline), beta, a head's transposes
    need = (9 * hg * block_q * d + 4 * hg * d * d
            + 2 * t * max(heads, 128) + 8 * d * 128) * 4
    return shmem_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((heads, t, d), f32),
                   jax.ShapeDtypeStruct((r, heads, d, d), f32)],
        # the state is updated in place: a slot the step does not visit
        # keeps its matrix (operands: 5 scalars, beta, q, k, v, g, state)
        input_output_aliases={10: 1},
        collective_id=None,
        vmem_limit_bytes=need + (16 << 20),
        interpret=local_interpret() if interpret is None else interpret,
        name="kda_attention",
        # the out blocks of consecutive rows overlap: ascending order
        dimension_semantics=("arbitrary", "arbitrary"),
    )


def heads_per_step(heads: int) -> int:
    """Heads one grid step holds: 16 (1 MB of state a block at D =
    128), or all of a narrower layer."""
    return 16 if heads % 16 == 0 else heads


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def kda_attention(q, k, v, g, beta, state, kv_lens, q_lens, q_starts, *,
                  block_q: int, interpret=None):
    """q, k, v, g: (H, T, D) float32 packed tokens, head-major (``g``
    the log decay, <= 0); ``beta``: (H, T) float32; ``state``: (R, H,
    D, D) float32; ``kv_lens`` / ``q_lens`` / ``q_starts``: (R,) int32
    as the ragged attention kernel takes them (lengths INCLUDE the
    step's tokens, starts 8-aligned, ``q_starts + block_q <= T``).
    Returns ``(o (H, T, D) float32, state')``."""
    heads, t, d = q.shape
    r = state.shape[0]
    if block_q > SHORT and block_q % SUB:
        raise ValueError(
            f"kda_attention: block_q={block_q} past {SHORT} must be a "
            f"multiple of the sub-chunk {SUB}")
    order, n = active_rows(q_lens)
    call = _build(r, t, heads, d, int(block_q), heads_per_step(heads),
                  interpret)
    o, new = call(order, n, kv_lens.astype(jnp.int32),
                  q_lens.astype(jnp.int32), q_starts.astype(jnp.int32),
                  beta.T, q, k, v, g, state)
    return o, new


def kda_attention_xla(q, k, v, g, beta, state, kv_lens, q_lens, q_starts,
                      *, block_q: int):
    """The kernel's XLA twin: same arguments; the recurrence itself, a
    token of every row at a time, a token past its span changing
    nothing."""
    heads, t, d = q.shape
    at = q_starts[:, None] + jnp.arange(block_q)[None, :]      # (R, B)
    rows = jnp.clip(at, 0, t - 1)
    live = jnp.arange(block_q)[None, :] < q_lens[:, None]      # (R, B)
    first = (kv_lens - q_lens) == 0
    # (H, R, B, D) -> token-major (B, R, H, D)
    qr, kr, vr, gr = (x[:, rows].transpose(2, 1, 0, 3)
                      for x in (q, k, v, g))
    br = beta[:, rows].transpose(2, 1, 0)                      # (B, R, H)
    s0 = jnp.where(first[:, None, None, None], 0.0, state)

    def token(s, xs):
        qt, kt, vt, gt, bt, on = xs
        s1 = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum(
            "rhkv,rhk->rhv", s1, kt, precision=HI))
        s1 = s1 + kt[..., None] * u[..., None, :]
        s = jnp.where(on[:, None, None, None], s1, s)
        return s, jnp.einsum("rhkv,rhk->rhv", s, qt,
                             precision=HI) / math.sqrt(d)

    s_new, o_rows = jax.lax.scan(token, s0, (qr, kr, vr, gr, br, live.T))
    dest = jnp.where(live, at, t).reshape(-1)                  # t: dropped
    o = jnp.zeros((heads, t, d), jnp.float32).at[:, dest].set(
        o_rows.transpose(2, 1, 0, 3).reshape(heads, -1, d), mode="drop")
    return o, jnp.where((q_lens > 0)[:, None, None, None], s_new, state)
