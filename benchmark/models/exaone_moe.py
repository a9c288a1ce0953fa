"""K-EXAONE (``model_type: exaone_moe``): parameter plan and plain
reference, as ``prenorm_moe`` gives them for the two older
configurations — the same two entry points, nothing of the program:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

One layer, residual stream ``x`` (T, hidden), every product in float32
at ``jax.lax.Precision.HIGHEST``::

    a = rmsnorm(x, norm_attn)
    q, k, v = split(a wqkv) as (T, n_heads | n_kv_heads | n_kv_heads, head_dim)
    q = rmsnorm_over_head_dim(q, norm_q);  k = rmsnorm_over_head_dim(k, norm_k)
    layer in rope_layers:  q, k = rope(q, k, position; rope_theta, rotate-half, every dim)
    key j visible to query i  iff  j <= i  and (full layer or j > i - window)
    x = x + softmax(q k^T / sqrt(head_dim) + mask) v wo
    m = rmsnorm(x, norm_mlp)
    dense layer:   x = x + (silu(m Wg) * (m Wu)) down          # up = [Wg | Wu]
    sparse layer:  s = sigmoid(m router);  ids = top_k(s + router_bias)
                   w = routed_scale * s[ids] / sum(s[ids])
                   x = x + sum_k w_k E_ids_k(m) [experts held here only] + E_shared(m)
    at the end:    logits = rmsnorm(x, norm_f) lm_head

Which of this the configuration file states and which it ASSUMES (the
pre-norm residual, q/k norm on every layer and rotation on the sliding
layers only, the selection bias) is in the file, under ``assumed``.

THE SHARE. ``sizes`` states one chip's share of a deployment that
divides each layer over several chips: the router keeps
``num_experts`` outputs and ``topk``, and only experts
``[first_expert_held, first_expert_held + experts_held)`` exist here
(``moe_up`` / ``moe_down`` hold just them). A token's routed result is
the part those experts give; what the absent ones would have added is
left out, and that partial sum goes on to the next layer. ``vocab`` is
the slice of the vocabulary held here: embedding rows, logits and the
traffic's ids are over the slice. ``share_of_layer`` below is the same
sparse layer for ANY such share, which is what lets a test add the
shares up to the whole.

Queries go through attention in blocks of ``Q_BLOCK`` positions against
all keys, so a sequence of 8704 tokens needs (8 x 8 x 256 x 8704)
scores at a time, not 8704 squared (a sequence is shorter than
``Q_BLOCK`` or a multiple of it: ``correct`` pads to 512).

``bits`` is the control ``correct`` has to reject, as in
``prenorm_moe``: every matmul input rounded to a symmetric ``bits``-bit
grid (weights per output channel, activations and K/V per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.models.prenorm_moe import HI, _fq, _mm, _rmsnorm

#: query positions scored against all keys at a time
Q_BLOCK = 256


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` gives for these fields. Projections
    N(0, 1/sqrt(fan_in)), embeddings N(0, 0.02), gains 1, the router's
    selection bias N(0, 0.01)."""
    h, f, fd = sizes["hidden"], sizes["ffn"], sizes["dense_ffn"]
    d = sizes["head_dim"]
    qd, kvd = sizes["n_heads"] * d, sizes["n_kv_heads"] * d
    e_all, e = sizes["num_experts"], sizes["experts_held"]
    fs = sizes["shared_experts"] * f
    s_h = h ** -0.5
    plan = {
        "embed": ((sizes["vocab"], h), 0.02),
        "norm_f": ((h,), None),
        "lm_head": ((h, sizes["vocab"]), s_h),
        "blocks": [],
    }
    for i in range(sizes["n_layers"]):
        blk = {
            "norm_attn": ((h,), None),
            "norm_mlp": ((h,), None),
            "norm_q": ((d,), None),
            "norm_k": ((d,), None),
            "wqkv": ((h, qd + 2 * kvd), s_h),
            "wo": ((qd, h), s_h),
        }
        if i in sizes["moe_layers"]:
            blk["router"] = ((h, e_all), s_h)
            blk["router_bias"] = ((e_all,), 0.01)
            blk["moe_up"] = ((e, h, 2 * f), s_h)
            blk["moe_down"] = ((e, f, h), f ** -0.5)
            blk["shared_up"] = ((h, 2 * fs), s_h)
            blk["shared_down"] = ((fs, h), fs ** -0.5)
        else:
            blk["up"] = ((h, 2 * fd), s_h)
            blk["down"] = ((fd, h), fd ** -0.5)
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


def _attention(blk, xn, sizes, bits, sliding, rotate):
    n, hq, hkv, d = (xn.shape[0], sizes["n_heads"], sizes["n_kv_heads"],
                     sizes["head_dim"])
    eps = sizes["norm_eps"]
    qkv = _mm(xn, blk["wqkv"], bits)
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    q = _rmsnorm(q.reshape(n, hq, d), blk["norm_q"], eps)
    k = _rmsnorm(k.reshape(n, hkv, d), blk["norm_k"], eps)
    if rotate:
        pos = jnp.arange(n)
        q = _rope(q, pos, sizes["rope_theta"])
        k = _rope(k, pos, sizes["rope_theta"])
    k = _fq(k, bits, -1)
    v = _fq(v.reshape(n, hkv, d), bits, -1)
    window = sizes["window"]
    keys = jnp.arange(n)

    def block(queries_and_start):
        qs, start = queries_and_start        # (B, hq, d), scalar
        at = start + jnp.arange(qs.shape[0])
        s = jnp.einsum("shgd,thd->hgst", qs.reshape(-1, hkv, hq // hkv, d),
                       k, precision=HI) / (d ** 0.5)
        seen = keys[None, :] <= at[:, None]
        if sliding:
            seen = seen & (keys[None, :] > at[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgst,thd->shgd", p, v, precision=HI) \
            .reshape(-1, hq * d)

    qb = min(Q_BLOCK, n)
    assert n % qb == 0, (n, qb)
    o = jax.lax.map(block, (q.reshape(n // qb, qb, hq, d),
                            jnp.arange(0, n, qb)))
    return _mm(o.reshape(n, hq * d), blk["wo"], bits)


def _gated(xn, up, down, bits):
    h = _mm(xn, up, bits)
    f = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], down, bits)


def route(blk, xn, sizes):
    """(gate (T, num_experts)): each token's weight on every expert of
    the whole layer, 0 on those it did not choose."""
    s = jax.nn.sigmoid(_mm(xn, blk["router"]))
    _, ids = jax.lax.top_k(
        s + blk["router_bias"].astype(jnp.float32), sizes["topk"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = sizes["routed_scale"] * w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(xn.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, ids].set(w)


def share_of_layer(blk, xn, sizes, bits=None):
    """The routed part of one sparse layer that the experts held here
    give: every held expert computed for every token and weighted by
    its gate (the shared expert is NOT in it: every chip computes that
    alike, and it is added once)."""
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
    for i, blk in enumerate(params["blocks"]):
        x = x + _attention(
            blk, _rmsnorm(x, blk["norm_attn"], eps), sizes, bits,
            sliding=sizes["layer_attn"][i] == "sliding",
            rotate=i in sizes["rope_layers"])
        xn = _rmsnorm(x, blk["norm_mlp"], eps)
        if "router" in blk:
            x = x + share_of_layer(blk, xn, sizes, bits) + _gated(
                xn, blk["shared_up"], blk["shared_down"], bits)
        else:
            x = x + _gated(xn, blk["up"], blk["down"], bits)
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
