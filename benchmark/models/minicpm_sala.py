"""MiniCPM-SALA (``model_type: minicpm_sala``): parameter plan and plain
reference, the two entry points every ``models/<arch>.py`` has and
nothing of the program:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

Residual stream ``x`` (T, hidden); every product float32 at
``jax.lax.Precision.HIGHEST``; ``r = residual_scale`` (the PUBLISHED
depth's ``scale_depth / sqrt(32)``, whatever the depth run)::

    x0 = embed_scale * embed[token]
    every layer:     a = rmsnorm(x, norm_attn);  x = x + r * mixer(a)
                     m = rmsnorm(x, norm_mlp);   x = x + r * (silu(m Wg) * (m Wu)) down     # up = [Wg | Wu]
    after the last:  logits = (rmsnorm(x, norm_f) / logit_divisor) lm_head

    lightning mixer (layer_mixer[i] == "lightning"; H = lightning_heads, D = head_dim):
      q, k, v = split(a wqkv) as (T, H, D);  q = rmsnorm_head(q, norm_q);  k = rmsnorm_head(k, norm_k)
      q, k = rope(q, k, position; rope_theta, rotate-half, every dim)
      per head h:  S_t = lam_h S_{t-1} + k_t^T v_t  (D x D, S_{-1} = 0);  o_t = (q_t / sqrt D) S_t
                   lam_h = exp(-2^(-8 (h + 1) / H))
      out = (rmsnorm_head(o, norm_o) * sigmoid(a wz)) wo

    sparse mixer (layer_mixer[i] == "attention"; n_heads query heads, n_kv_heads KV heads, no rotation):
      q = rmsnorm_head(q, norm_q) as (T, n_heads, D);  k = rmsnorm_head(k, norm_k), v as (T, n_kv_heads, D)
      kc_j = mean(k[stride j : stride j + kernel])  for stride j + kernel <= n, per KV head
      query at position i, blocks of ``block`` tokens:
        i <  dense_len:  every key j <= i
        i >= dense_len:  p_h  = softmax_j(q_h . kc_j / sqrt D) over the j with stride j + kernel <= i + 1
                         sc_j = sum of p_h over the KV head's query heads
                         B_b  = max of sc_j over the j whose span [stride j, stride j + kernel) meets block b
                         B_b  = +inf for b < init_blocks and for the blocks that meet [i - window + 1, i]
                         the topk largest B_b among the blocks b <= i // block, ties to the lower b
                         keys j <= i that lie in a chosen block
      o = softmax over the visible keys (q k_j / sqrt D) v;  out = (o * sigmoid(a wz)) wo

What of this the published config states and what is the family's
convention is in the configuration file, under ``assumed`` and
``departures``.

A sequence of 29184 tokens has to fit beside the masters, so nothing
is ever (T x T) or (T x ffn): the lightning recurrence runs in its
chunk form over blocks of ``ROW_BLOCK`` positions (exact: the carried
state times the decay, plus the block's own decay-masked products),
the sparse layers score ``Q_BLOCK`` queries at a time against all
keys, and projections and the FFN go ``ROW_BLOCK`` rows at a time.
``blocked=False`` evaluates the same lines in one shot (the token-by-
token recurrence, all queries at once): the tests hold the two equal.

``bits`` is the control ``correct`` has to reject, as in
``prenorm_moe``: every matmul input rounded to a symmetric ``bits``-bit
grid (weights per output channel, activations, q/k/v and compressed
keys per row). ``bits = STATE_BF16`` is a second control: everything
float32 but the lightning layers' recurrent state, which is rounded to
bfloat16 after every token (what a program that kept its state in
bfloat16 would carry).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.models.prenorm_moe import HI, _fq, _mm, _rmsnorm

#: positions a lightning block, a projection or the FFN take at a time
ROW_BLOCK = 512
#: query positions a sparse layer scores against all keys at a time
Q_BLOCK = 128
#: a sequence is evaluated at the next multiple of this many tokens (the
#: pass is causal: padding changes nothing before it), so that the
#: comparison's sequences share a few compiled shapes
SEQ_BUCKET = 8192
#: the ``bits`` of the control that keeps only the recurrent state low
STATE_BF16 = 16


def _heads(sizes, i):
    if sizes["layer_mixer"][i] == "lightning":
        return sizes["lightning_heads"], sizes["lightning_heads"]
    return sizes["n_heads"], sizes["n_kv_heads"]


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` gives for these fields. Projections
    N(0, 1/sqrt(fan_in)), embedding N(0, 0.02), gains 1; the head's std
    is times ``logit_divisor``, so that logits have unit scale as the
    other configurations' do (muP divides the head's input by it)."""
    h, f, d = sizes["hidden"], sizes["ffn"], sizes["head_dim"]
    s_h = h ** -0.5
    plan = {
        "embed": ((sizes["vocab"], h), 0.02),
        "norm_f": ((h,), None),
        "lm_head": ((h, sizes["vocab"]), s_h * sizes["logit_divisor"]),
        "blocks": [],
    }
    for i in range(sizes["n_layers"]):
        hq, hkv = _heads(sizes, i)
        blk = {
            "norm_attn": ((h,), None),
            "norm_mlp": ((h,), None),
            "norm_q": ((d,), None),
            "norm_k": ((d,), None),
            "wqkv": ((h, (hq + 2 * hkv) * d), s_h),
            "wz": ((h, hq * d), s_h),
            "wo": ((hq * d, h), (hq * d) ** -0.5),
            "up": ((h, 2 * f), s_h),
            "down": ((f, h), f ** -0.5),
        }
        if sizes["layer_mixer"][i] == "lightning":
            blk["norm_o"] = ((d,), None)
        plan["blocks"].append(blk)
    return plan


def _rope(x, pos, theta):
    """x (T, heads, D) rotated to positions ``pos`` (T,): rotate-half
    over all D dims."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _over_rows(fn, x, blocked):
    """``fn`` over the rows of ``x`` (T, ...), ``ROW_BLOCK`` at a time."""
    n = x.shape[0]
    if not blocked or n <= ROW_BLOCK:
        return fn(x)
    assert n % ROW_BLOCK == 0, (n, ROW_BLOCK)
    out = jax.lax.map(fn, x.reshape(n // ROW_BLOCK, ROW_BLOCK, *x.shape[1:]))
    return jax.tree.map(lambda y: y.reshape(n, *y.shape[2:]), out)


def _project(blk, x, sizes, i, bits):
    """rows of the residual stream -> (q (T, hq, D), k, v (T, hkv, D),
    gate (T, hq·D)), q and k normed per head."""
    hq, hkv = _heads(sizes, i)
    d, eps = sizes["head_dim"], sizes["norm_eps"]
    a = _rmsnorm(x, blk["norm_attn"], eps)
    qkv = _mm(a, blk["wqkv"], bits)
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    n = x.shape[0]
    q = _rmsnorm(q.reshape(n, hq, d), blk["norm_q"], eps)
    k = _rmsnorm(k.reshape(n, hkv, d), blk["norm_k"], eps)
    return q, k, v.reshape(n, hkv, d), jax.nn.sigmoid(_mm(a, blk["wz"], bits))


def _ffn(blk, x, sizes, bits):
    m = _rmsnorm(x, blk["norm_mlp"], sizes["norm_eps"])
    h = _mm(m, blk["up"], bits)
    f = h.shape[-1] // 2
    return x + sizes["residual_scale"] * _mm(
        jax.nn.silu(h[:, :f]) * h[:, f:], blk["down"], bits)


# ------------------------------------------------------------- lightning

def _decay(sizes):
    h = sizes["lightning_heads"]
    return jnp.exp(-jnp.exp2(
        -8.0 * (jnp.arange(h, dtype=jnp.float32) + 1.0) / h))       # (H,)


def _lightning_tokens(s, q, k, v, lam, carry=jnp.float32):
    """The recurrence token by token from state ``s`` (H, D, D), kept
    in ``carry``: q, k, v (T, H, D) -> (new state, o (T, H, D))."""
    d = q.shape[-1]

    def step(s, qkv):
        qt, kt, vt = qkv
        s = lam[:, None, None] * s + jnp.einsum("hd,he->hde", kt, vt,
                                                precision=HI)
        s = s.astype(carry).astype(jnp.float32)
        return s, jnp.einsum("hd,hde->he", qt / d ** 0.5, s, precision=HI)

    return jax.lax.scan(step, s, (q, k, v))


def _lightning_block(s, q, k, v, lam):
    """One block of positions in the chunk form: carried state ``s``
    (H, D, D), q, k, v (C, H, D) -> (new state, o (C, H, D))."""
    c, _, d = q.shape
    t = jnp.arange(c, dtype=jnp.float32)
    log_lam = jnp.log(lam)                                           # (H,)
    lag = t[:, None] - t[None, :]                                    # (C, C)
    decay = jnp.where(
        lag >= 0, jnp.exp(log_lam[:, None, None] * jnp.maximum(lag, 0.0)),
        0.0)                                                         # (H, C, C)
    qs = q / d ** 0.5
    a = jnp.einsum("thd,jhd->htj", qs, k, precision=HI) * decay
    carried = qs * jnp.exp(log_lam[None, :] * (t[:, None] + 1.0))[..., None]
    o = jnp.einsum("htj,jhe->the", a, v, precision=HI) + jnp.einsum(
        "thd,hde->the", carried, s, precision=HI)
    kd = k * jnp.exp(log_lam[None, :] * (c - 1.0 - t[:, None]))[..., None]
    s = jnp.exp(log_lam * c)[:, None, None] * s + jnp.einsum(
        "jhd,jhe->hde", kd, v, precision=HI)
    return s, o


def _lightning_layer(blk, x, sizes, i, bits, blocked):
    carry = jnp.bfloat16 if bits == STATE_BF16 else jnp.float32
    bits = None if bits == STATE_BF16 else bits
    d, eps = sizes["head_dim"], sizes["norm_eps"]
    heads = sizes["lightning_heads"]
    lam, r = _decay(sizes), sizes["residual_scale"]
    n = x.shape[0]

    def qkv_of(xb, pos):
        q, k, v, gate = _project(blk, xb, sizes, i, bits)
        q = _fq(_rope(q, pos, sizes["rope_theta"]), bits, -1)
        k = _fq(_rope(k, pos, sizes["rope_theta"]), bits, -1)
        return q, k, _fq(v, bits, -1), gate

    def finish(xb, o, gate):
        o = _rmsnorm(o, blk["norm_o"], eps).reshape(-1, heads * d) * gate
        return _ffn(blk, xb + r * _mm(o, blk["wo"], bits), sizes, bits)

    s0 = jnp.zeros((heads, d, d), jnp.float32)
    if not blocked or n <= ROW_BLOCK:
        q, k, v, gate = qkv_of(x, jnp.arange(n))
        return finish(x, _lightning_tokens(s0, q, k, v, lam, carry)[1], gate)
    assert n % ROW_BLOCK == 0, (n, ROW_BLOCK)

    def block(s, xb_and_start):
        xb, start = xb_and_start
        q, k, v, gate = qkv_of(xb, start + jnp.arange(ROW_BLOCK))
        if carry == jnp.float32:
            s, o = _lightning_block(s, q, k, v, lam)
        else:
            s, o = _lightning_tokens(s, q, k, v, lam, carry)
        return s, finish(xb, o, gate)

    _, out = jax.lax.scan(
        block, s0, (x.reshape(-1, ROW_BLOCK, x.shape[-1]),
                    jnp.arange(0, n, ROW_BLOCK)))
    return out.reshape(n, -1)


# ---------------------------------------------------------------- sparse

def _compressed_keys(k, sizes):
    """k (T, Hkv, D) -> kc (NC, Hkv, D): the mean of ``kernel`` keys
    every ``stride``, windows that fit the sequence only."""
    kernel, stride = sizes["sparse_kernel"], sizes["sparse_stride"]
    n = k.shape[0]
    nc = max((n - kernel) // stride + 1, 0)
    at = stride * jnp.arange(nc)[:, None] + jnp.arange(kernel)[None, :]
    return jnp.mean(k[at], axis=1)


def _softmax_where(s, seen):
    """softmax of ``s`` over the last axis among ``seen``; 0 where none
    is."""
    top = jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True)
    e = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def _chosen_blocks(qb, at, kc, sizes, nb):
    """qb (B, Hkv, G, D) queries at positions ``at`` (B,), kc (NC, Hkv,
    D) -> (B, Hkv, nb) bool, the blocks each query attends (before the
    causal mask)."""
    kernel, stride, block = (sizes["sparse_kernel"], sizes["sparse_stride"],
                             sizes["sparse_block"])
    d = qb.shape[-1]
    nc = kc.shape[0]
    blocks = jnp.arange(nb)
    last = at // block                                        # (B,)
    seen = blocks[None, :] <= last[:, None]                   # (B, NB)
    if nc == 0 or nc * stride + kernel <= sizes["sparse_dense_len"]:
        # no position of this sequence is past the dense length
        return jnp.broadcast_to(seen[:, None], (at.shape[0], qb.shape[1], nb))
    j = jnp.arange(nc)
    vis = stride * j[None, :] + kernel <= at[:, None] + 1     # (B, NC)
    s = jnp.einsum("bhgd,chd->bhgc", qb, kc, precision=HI) / d ** 0.5
    p = _softmax_where(s, vis[:, None, None])
    sc = jnp.where(vis[:, None], jnp.sum(p, axis=2), -jnp.inf)   # (B, Hkv, NC)
    # compressed key j spans tokens [stride j, stride j + kernel)
    meets = (stride * j[None, :] < (blocks[:, None] + 1) * block) & (
        stride * j[None, :] + kernel > blocks[:, None] * block)   # (NB, NC)
    score = jnp.max(
        jnp.where(meets[None, None], sc[:, :, None, :], -jnp.inf), axis=-1)
    lo = jnp.maximum(at - sizes["sparse_window"] + 1, 0) // block
    forced = (blocks[None, :] < sizes["sparse_init_blocks"]) | (
        (blocks[None, :] >= lo[:, None]) & seen)
    score = jnp.where(forced[:, None], jnp.inf,
                      jnp.where(seen[:, None], score, -jnp.inf))
    _, ids = jax.lax.top_k(score, min(sizes["sparse_topk"], nb))
    sparse = jnp.any(ids[..., None] == blocks, axis=-2)       # (B, Hkv, NB)
    dense = at < sizes["sparse_dense_len"]
    return jnp.where(dense[:, None, None], True, sparse) & seen[:, None]


def _sparse_layer(blk, x, sizes, i, bits, blocked):
    bits = None if bits == STATE_BF16 else bits
    hq, hkv = _heads(sizes, i)
    d, block = sizes["head_dim"], sizes["sparse_block"]
    r, n = sizes["residual_scale"], x.shape[0]
    q, k, v, gate = _over_rows(
        lambda xb: _project(blk, xb, sizes, i, bits), x, blocked)
    q, k, v = (_fq(a, bits, -1) for a in (q, k, v))
    kc = _fq(_compressed_keys(k, sizes), bits, -1)
    keys = jnp.arange(n)

    def attend(queries):
        qb, at, xb, gb = queries
        qb = qb.reshape(-1, hkv, hq // hkv, d)
        chosen = _chosen_blocks(qb, at, kc, sizes, -(-n // block))
        seen = (keys[None, :] <= at[:, None])[:, None] & chosen[
            :, :, keys // block]                              # (B, Hkv, T)
        s = jnp.einsum("bhgd,thd->bhgt", qb, k, precision=HI) / d ** 0.5
        p = _softmax_where(s, seen[:, :, None])
        o = jnp.einsum("bhgt,thd->bhgd", p, v, precision=HI)
        o = o.reshape(-1, hq * d) * gb
        return _ffn(blk, xb + r * _mm(o, blk["wo"], bits), sizes, bits)

    qb = Q_BLOCK if blocked and n > Q_BLOCK else n
    assert n % qb == 0, (n, qb)
    out = jax.lax.map(attend, (
        q.reshape(n // qb, qb, hq, d), keys.reshape(n // qb, qb),
        x.reshape(n // qb, qb, -1), gate.reshape(n // qb, qb, -1)))
    return out.reshape(n, -1)


@functools.partial(jax.jit, static_argnames=("sizes", "bits", "blocked"))
def _logits(params, tokens, rows, *, sizes, bits, blocked):
    sizes = dict(sizes)
    x = sizes["embed_scale"] * params["embed"][tokens].astype(jnp.float32)
    for i, blk in enumerate(params["blocks"]):
        layer = (_lightning_layer if sizes["layer_mixer"][i] == "lightning"
                 else _sparse_layer)
        x = layer(blk, x, sizes, i, bits, blocked)
    x = _rmsnorm(x[rows], params["norm_f"], sizes["norm_eps"])
    return _mm(x / sizes["logit_divisor"], params["lm_head"],
               None if bits == STATE_BF16 else bits)


def logits_at(params, sizes: dict, tokens, rows, bits=None, blocked=True):
    """Next-token logits ``(len(rows), vocab)`` float32 after positions
    ``rows`` of ONE sequence ``tokens`` (1-D int32). The pass is causal,
    so tokens padded on at the end change nothing at earlier rows."""
    frozen = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sizes.items()))
    tokens = jnp.asarray(tokens, jnp.int32)
    n = tokens.shape[0]
    if blocked and n > ROW_BLOCK:
        tokens = jnp.pad(tokens, (0, -n % SEQ_BUCKET))
    return _logits(params, tokens, jnp.asarray(rows, jnp.int32),
                   sizes=frozen, bits=bits, blocked=blocked)
