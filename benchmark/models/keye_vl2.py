"""Keye-VL-2.0-30B-A3B's language model (``model_type: KeyeVL2``):
parameter plan and plain reference, the same two entry points as the
other architectures here, nothing of the program:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

One layer, residual stream ``x`` (T, hidden), every product in float32
at ``jax.lax.Precision.HIGHEST``; query position ``t``, key position
``s <= t``; ``J`` indexer heads of ``Di``, ``topk`` kept tokens::

    a = rmsnorm(x, norm_attn)
    q, k, v = split(a wqkv) as (T, n_heads | n_kv_heads | n_kv_heads, head_dim)
    q = rope(rmsnorm_over_head_dim(q, norm_q));  k = rope(rmsnorm_over_head_dim(k, norm_k))
    qI, kI, w = split(a w_index) as (T, J, Di | Di | J)
    qI = rope_over_Di(qI);  kI = rope_over_Di(layernorm_over_Di(kI) * norm_ki);  w = w / sqrt(J)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s] / sqrt(Di))
    S[t]    = every s <= t                      if t + 1 <= topk
              the topk largest I[t, s] (s <= t), ties to the lower s   otherwise
    x = x + (softmax over s in S[t] of (q[t, h] . k[s, h // G] / sqrt(head_dim)) v[s, h // G]) wo
    m = rmsnorm(x, norm_mlp)
    p = softmax(m router);  E = top_k(p);  g_e = p_e / sum_E p
    x = x + sum_{e in E, e held here} g_e (silu(m Wg_e) * (m Wu_e)) down_e   # moe_up = [Wg | Wu]
    at the end:    logits = rmsnorm(x, norm_f) lm_head

ONE selection a query position, shared by all the heads. Both
rotations are rotate-half over every dim of the head (128; 64 in the
indexer) at ``rope_theta``: M-RoPE's three position components are
equal on text. What the configuration file states and what it ASSUMES
(the q/k norm, the indexer's key norm with a gain and no bias, its
rotation, the two scales, ``w_index`` read from the layer's normed
input, tie order) is in the file, under ``assumed``. No departure: the
program computes these lines.

THE SHARE is ``exaone_moe``'s: the router keeps ``num_experts`` outputs
and ``topk``, only experts ``[first_expert_held, first_expert_held +
experts_held)`` exist here, and what the absent ones would have added
is left out. ``share_of_layer`` is the routed part for ANY such share,
which is what lets a test add the shares up to the whole.

Queries go through the indexer, the choice and attention in blocks of
``Q_BLOCK`` positions against all keys, so a sequence of 27136 tokens
needs (32 x 128 x 27136) scores at a time (a sequence is shorter than
``Q_BLOCK`` or a multiple of it: ``correct`` pads to 512). The kept
set is found from the ``topk``-th largest score (``lax.top_k``'s last
value) and a running count of the ties, not from sorted indices.

``bits`` is the control ``correct`` has to reject: every matmul input
rounded to a symmetric ``bits``-bit grid (weights per output channel,
activations, K/V and the indexer's keys per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.models.prenorm_moe import HI, _fq, _mm, _rmsnorm

#: query positions scored against all keys at a time
Q_BLOCK = 128


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` gives for these fields. Projections
    N(0, 1/sqrt(hidden)), gains 1, and the stream kept TOKEN-SPECIFIC:
    the embedding N(0, 1) (entries of unit size, as a scaled embedding
    gives) and ``wo`` / ``moe_down``, which write to the stream, scaled
    by 1/sqrt(2 x layers run) (the GPT-2 / Megatron rule).

    With the other plans' N(0, 0.02) embedding under unscaled
    projections THIS layer collapses: attention over >= 2048 seeded keys
    is an average; an average passes what the positions have in COMMON
    at full strength and what tells them apart at ~1/sqrt(keys), so the
    common part grows ~2.4 x a layer and after a few layers every
    position holds the same stream. Greedy decoding then serves ONE
    token over and over at a margin no rounding moves, and the served
    tokens tell int8 from bfloat16 no more than they tell a right mask
    from a wrong one (PERF.md section 6, PR 49: what the first readings
    of this cell were)."""
    h, f, d = sizes["hidden"], sizes["ffn"], sizes["head_dim"]
    qd, kvd = sizes["n_heads"] * d, sizes["n_kv_heads"] * d
    j, di = sizes["index_heads"], sizes["index_dim"]
    e_all, e = sizes["num_experts"], sizes["experts_held"]
    s_h = h ** -0.5
    s_out = (2 * sizes["n_layers"]) ** -0.5
    blk = {
        "norm_attn": ((h,), None),
        "norm_mlp": ((h,), None),
        "norm_q": ((d,), None),
        "norm_k": ((d,), None),
        "wqkv": ((h, qd + 2 * kvd), s_h),
        "wo": ((qd, h), s_h * s_out),
        "w_index": ((h, (j + 1) * di + j), s_h),
        "norm_ki": ((di,), None),
        "router": ((h, e_all), s_h),
        "moe_up": ((e, h, 2 * f), s_h),
        "moe_down": ((e, f, h), f ** -0.5 * s_out),
    }
    return {
        "embed": ((sizes["vocab"], h), 1.0),
        "norm_f": ((h,), None),
        "lm_head": ((h, sizes["vocab"]), s_h),
        "blocks": [dict(blk) for _ in range(sizes["n_layers"])],
    }


def _rope(x, pos, theta):
    """x (T, heads, D) rotated to positions ``pos`` (T,): rotate-half
    over all D dims."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def indexer(blk, xn, sizes, bits=None):
    """``(qI (T, J, Di), kI (T, Di), w (T, J))`` of normed rows ``xn``:
    queries and key rotated, the key layer-normed first, the head
    weights over sqrt(J)."""
    n = xn.shape[0]
    j, di = sizes["index_heads"], sizes["index_dim"]
    y = _mm(xn, blk["w_index"], bits)
    qi = y[:, :j * di].reshape(n, j, di)
    ki = y[:, j * di:(j + 1) * di]
    w = y[:, (j + 1) * di:] / (j ** 0.5)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(
        jnp.mean(ki * ki, axis=-1, keepdims=True) + sizes["norm_eps"]
    ) * blk["norm_ki"].astype(jnp.float32)
    pos = jnp.arange(n)
    qi = _rope(qi, pos, sizes["rope_theta"])
    ki = _rope(ki[:, None, :], pos, sizes["rope_theta"])[:, 0]
    return qi, _fq(ki, bits, -1), w


def index_scores(qi, ki, w, sizes):
    """``I`` (Tq, Tk) float32 of queries ``qi`` (Tq, J, Di), ``w`` (Tq,
    J) against keys ``ki`` (Tk, Di), no mask."""
    s = jnp.einsum("tjd,sd->tjs", qi, ki, precision=HI) \
        / (sizes["index_dim"] ** 0.5)
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1)


def kept(scores, at, sizes):
    """bool (Tq, Tk): the keys each query at position ``at`` (Tq,)
    attends, from ``scores`` (Tq, Tk) over keys 0 .. Tk - 1: every key
    ``s <= t`` while ``t + 1 <= topk``, then the ``topk`` largest
    scores among them, ties to the lower ``s``."""
    topk = sizes["index_topk"]
    n = scores.shape[1]
    seen = jnp.arange(n)[None, :] <= at[:, None]
    if topk >= n:
        return seen
    sc = jnp.where(seen, scores, -jnp.inf)
    kth = jax.lax.top_k(sc, topk)[0][:, -1:]
    above = sc > kth
    tie = sc == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (tie & (jnp.cumsum(tie, axis=-1) <= room))
    return jnp.where((at < topk)[:, None], seen, chosen & seen)


def _attention(blk, xn, sizes, bits):
    n, hq, hkv, d = (xn.shape[0], sizes["n_heads"], sizes["n_kv_heads"],
                     sizes["head_dim"])
    eps, theta = sizes["norm_eps"], sizes["rope_theta"]
    qkv = _mm(xn, blk["wqkv"], bits)
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    pos = jnp.arange(n)
    q = _rope(_rmsnorm(q.reshape(n, hq, d), blk["norm_q"], eps), pos, theta)
    k = _rope(_rmsnorm(k.reshape(n, hkv, d), blk["norm_k"], eps), pos, theta)
    k = _fq(k, bits, -1)
    v = _fq(v.reshape(n, hkv, d), bits, -1)
    qi, ki, w = indexer(blk, xn, sizes, bits)

    def block(part):
        qs, qis, ws, start = part
        at = start + jnp.arange(qs.shape[0])
        keep = kept(index_scores(qis, ki, ws, sizes), at, sizes)
        s = jnp.einsum("shgd,thd->hgst", qs.reshape(-1, hkv, hq // hkv, d),
                       k, precision=HI) / (d ** 0.5)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgst,thd->shgd", p, v, precision=HI) \
            .reshape(-1, hq * d)

    qb = min(Q_BLOCK, n)
    assert n % qb == 0, (n, qb)
    nb = n // qb
    o = jax.lax.map(block, (
        q.reshape(nb, qb, hq, d), qi.reshape((nb, qb) + qi.shape[1:]),
        w.reshape(nb, qb, -1), jnp.arange(0, n, qb)))
    return _mm(o.reshape(n, hq * d), blk["wo"], bits)


def _gated(xn, up, down, bits):
    h = _mm(xn, up, bits)
    f = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], down, bits)


def route(blk, xn, sizes):
    """(gate (T, num_experts)): each token's weight on every expert of
    the whole layer, 0 on those it did not choose."""
    p = jax.nn.softmax(_mm(xn, blk["router"]), axis=-1)
    w, ids = jax.lax.top_k(p, sizes["topk"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(xn.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, ids].set(w)


def share_of_layer(blk, xn, sizes, bits=None):
    """The routed part of one layer that the experts held here give:
    every held expert computed for every token and weighted by its
    gate."""
    first, held = sizes["first_expert_held"], sizes["experts_held"]
    gate = route(blk, xn, sizes)[:, first:first + held]

    def expert(y, e):
        up, down, g = e
        return y + g[:, None] * _gated(xn, up, down, bits), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(xn), (blk["moe_up"], blk["moe_down"], gate.T))
    return y


@functools.partial(jax.jit, static_argnames=("sizes", "bits"))
def _logits(params, tokens, rows, *, sizes, bits):
    sizes = dict(sizes)
    eps = sizes["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    for blk in params["blocks"]:
        x = x + _attention(
            blk, _rmsnorm(x, blk["norm_attn"], eps), sizes, bits)
        x = x + share_of_layer(
            blk, _rmsnorm(x, blk["norm_mlp"], eps), sizes, bits)
    x = _rmsnorm(x[rows], params["norm_f"], eps)
    return _mm(x, params["lm_head"], bits)


def logits_at(params, sizes: dict, tokens, rows, bits=None):
    """Next-token logits ``(len(rows), vocab)`` float32 after positions
    ``rows`` of ONE sequence ``tokens`` (1-D int32). The pass is causal,
    so tokens padded on at the end change nothing at earlier rows."""
    frozen = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sizes.items()))
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(rows, jnp.int32), sizes=frozen, bits=bits)
