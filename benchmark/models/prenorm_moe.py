"""A model the benchmark can run: its parameter plan and its plain
reference, the forward pass in float32. A configuration file names this
module under ``model``; a configuration of another architecture names a
module a later PR adds beside it, with the same two entry points:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

Straightforward ``jax.numpy``, every matmul at
``jax.lax.Precision.HIGHEST``, one whole sequence at a time: no kernel,
no cache, no batching, every expert computed for every token and
weighted by its gate. It imports nothing of the program and is handed
the benchmark's own weights (``harness.weights`` makes them from
``param_plan`` and ``--seed``), never anything the program made.
``harness.program.build`` refuses to go on if the program's own
``init`` would give another tree than ``param_plan``: a change of layout
in the program fails loudly instead of bending the yardstick.

The architecture is the one the configuration file states under
``as_run`` and ``departures``: pre-norm decoder blocks, RMSNorm,
causal grouped-query attention without positional rotation, an
un-gated SiLU feed-forward (``down(silu(up(x)))``), and on expert
layers a softmax router whose top-k probabilities are renormalised.

``bits`` computes the same pass in a LOWER precision — the control that
``correct`` has to reject: every matmul input is rounded (weights per
output channel, activations and K/V per row) before the float32
product, to a symmetric ``bits``-bit integer grid, or for ``"fp8"`` to
float8 e4m3 values scaled to the row's or channel's largest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` documents. Scales follow the family's
    usual initialisation: embeddings N(0, 0.02), projections
    N(0, 1/sqrt(fan_in)), norm gains 1."""
    h, f = sizes["hidden"], sizes["ffn"]
    qd = sizes["n_heads"] * sizes["head_dim"]
    kvd = sizes["n_kv_heads"] * sizes["head_dim"]
    e = sizes["num_experts"]
    s_h, s_f = h ** -0.5, f ** -0.5
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
            "wqkv": ((h, qd + 2 * kvd), s_h),
            "wo": ((qd, h), s_h),
        }
        if i in sizes["moe_layers"]:
            blk["router"] = ((h, e), s_h)
            blk["moe_up"] = ((e, h, f), s_h)
            blk["moe_down"] = ((e, f, h), s_f)
        else:
            blk["up"] = ((h, f), s_h)
            blk["down"] = ((f, h), s_f)
        plan["blocks"].append(blk)
    return plan


def _fq(x, bits, axis):
    """Round ``x`` to a symmetric ``bits``-bit grid along ``axis``."""
    if not bits:
        return x
    top = 448.0 if bits == "fp8" else float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if bits == "fp8":
        y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        y = jnp.clip(jnp.round(x / scale), -top, top)
    return y * scale


def _mm(x, w, bits=None):
    """(rows, K) @ (K, N) in float32."""
    x = _fq(x.astype(jnp.float32), bits, -1)
    w = _fq(w.astype(jnp.float32), bits, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, w, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * w.astype(jnp.float32)


def _attention(blk, xn, sizes, bits):
    n, hq, hkv, d = (xn.shape[0], sizes["n_heads"], sizes["n_kv_heads"],
                     sizes["head_dim"])
    qkv = _mm(xn, blk["wqkv"], bits)
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    q = q.reshape(n, hkv, hq // hkv, d)
    k = _fq(k.reshape(n, hkv, d), bits, -1)
    v = _fq(v.reshape(n, hkv, d), bits, -1)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HI) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hgst,thd->shgd", p, v, precision=HI)
    return _mm(o.reshape(n, hq * d), blk["wo"], bits)


def _moe(blk, xn, sizes, bits):
    probs = jax.nn.softmax(_mm(xn, blk["router"]), axis=-1)
    w, ids = jax.lax.top_k(probs, sizes["topk"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(xn.shape[0])[:, None]
    gate = jnp.zeros_like(probs).at[rows, ids].set(w)

    def expert(y, e):
        up, down, g = e
        h = jax.nn.silu(_mm(xn, up, bits))
        return y + g[:, None] * _mm(h, down, bits), None

    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(xn),
        (blk["moe_up"], blk["moe_down"], gate.T))
    return y


@functools.partial(jax.jit, static_argnames=("sizes", "bits"))
def _logits(params, tokens, rows, *, sizes, bits):
    sizes = dict(sizes)
    eps = sizes["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    for blk in params["blocks"]:
        x = x + _attention(
            blk, _rmsnorm(x, blk["norm_attn"], eps), sizes, bits)
        xn = _rmsnorm(x, blk["norm_mlp"], eps)
        if "router" in blk:
            x = x + _moe(blk, xn, sizes, bits)
        else:
            x = x + _mm(
                jax.nn.silu(_mm(xn, blk["up"], bits)), blk["down"], bits)
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
