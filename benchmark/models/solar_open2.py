"""Solar-Open2 (``model_type: solar_open2``): parameter plan and plain
reference, the two entry points every ``models/<arch>.py`` has and
nothing of the program:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

Residual stream ``x`` (T, hidden); every product float32 at
``jax.lax.Precision.HIGHEST``; no positional embedding anywhere
(``use_rope`` false)::

    x0 = embed[token]
    every layer:     a = rmsnorm(x, norm_attn);  x = x + mixer(a)
                     m = rmsnorm(x, norm_mlp)
                     s = sigmoid(m router);  ids = top_k(s + router_bias)
                     w = routed_scale * s[ids] / sum(s[ids])
                     x = x + sum_k w_k E_ids_k(m) [experts held here only] + E_shared(m)
                     E(m) = (silu(m Wg) * (m Wu)) down                        # up = [Wg | Wu]
    after the last:  logits = rmsnorm(x, norm_f) lm_head

    KDA mixer (layer_mixer[i] == "kda": gated delta-rule linear attention; H = kda_heads, D = head_dim):
      [q~ | k~ | v~] = a wqkv                                    (T, 3 H D), pre-activation
      conv(u)_t = sum_{i=0..taps-1} conv_w[i] * u_{t-(taps-1)+i}    depthwise, causal, zeros before position 0
      q = l2norm_head(silu(conv(q~)));  k = l2norm_head(silu(conv(k~)));  v = silu(conv(v~))
      l2norm(y) = y / sqrt(sum(y^2) + 1e-6) over the D values of a head
      g_t = -exp(a_log_h) * softplus((a wa_down) wa_up + dt_bias)      (H x D values a token), alpha_t = exp(g_t)
      b_t = kda_beta_scale * sigmoid(a wbeta)_h
      per head, S (D x D float32, S_{-1} = 0):
        S'  = Diag(alpha_t) S_{t-1};   S_t = S' + b_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t / sqrt(D)
      out = (rmsnorm_head(o, norm_o) * sigmoid((a wg_down) wg_up)) wo

    GQA mixer (layer_mixer[i] == "attention"; n_heads query / n_kv_heads KV heads, no rotation, no q/k norm):
      q, k, v = split(a wqkv);  o = softmax over keys j <= i (q k_j / sqrt D) v;  out = (o * sigmoid(a wz)) wo

The recurrence runs TOKEN BY TOKEN (``lax.scan``): no chunk form, no
inverse, nothing cached. Which of this the published config states and
which is the family's convention is in the configuration file, under
``assumed``.

THE SHARE. As ``exaone_moe``: the router keeps ``num_experts`` outputs
and ``topk``, and only experts ``[first_expert_held, first_expert_held
+ experts_held)`` exist here; what the absent ones would have added is
left out, and that partial sum goes on to the next layer. ``vocab`` is
the slice of the vocabulary held here. Routing and the experts' part
are ``exaone_moe``'s own lines (``route``, ``share_of_layer``), which a
test adds up over all shares to the whole layer.

A sequence of 12288 tokens has to fit beside the masters: a KDA layer
goes ``ROW_BLOCK`` positions at a time (projections, convolution with
the three pre-activation rows before the block, the token scan from the
carried state, gate and ``wo``), the GQA layer scores ``Q_BLOCK``
queries at a time against all keys. A sequence is at most ``ROW_BLOCK``
long or a multiple of it (``correct`` pads to 512).

``bits`` is the control ``correct`` has to reject, as in
``prenorm_moe``: every matmul input rounded to a symmetric ``bits``-bit
grid (weights per output channel, activations and q/k/v per row); the
recurrent state, the convolution and the decays stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.models.exaone_moe import _gated, share_of_layer
from benchmark.models.prenorm_moe import HI, _fq, _mm, _rmsnorm

#: positions a KDA layer takes at a time
ROW_BLOCK = 512
#: query positions the GQA layer scores against all keys at a time
Q_BLOCK = 256
#: under the square root of the KDA layers' L2 norm
L2_EPS = 1e-6


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` gives for these fields. Projections
    N(0, 1/sqrt(fan_in)), embedding N(0, 0.02), gains 1, the router's
    selection bias N(0, 0.01); the convolution's taps N(0, 1/sqrt(taps)),
    ``a_log`` N(0, 0.5) a head and ``dt_bias`` N(0, 1) a channel (all
    zero-mean: what ``harness/weights.py`` draws)."""
    h, f, d = sizes["hidden"], sizes["ffn"], sizes["head_dim"]
    qd, kvd = sizes["n_heads"] * d, sizes["n_kv_heads"] * d
    hk, taps, rank = (sizes["kda_heads"], sizes["kda_conv"],
                      sizes["kda_rank"])
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
            "router": ((h, e_all), s_h),
            "router_bias": ((e_all,), 0.01),
            "moe_up": ((e, h, 2 * f), s_h),
            "moe_down": ((e, f, h), f ** -0.5),
            "shared_up": ((h, 2 * fs), s_h),
            "shared_down": ((fs, h), fs ** -0.5),
        }
        if sizes["layer_mixer"][i] == "kda":
            blk.update(
                wqkv=((h, 3 * hk * d), s_h),
                conv_w=((taps, 3 * hk * d), taps ** -0.5),
                wa_down=((h, rank), s_h),
                wa_up=((rank, hk * d), rank ** -0.5),
                a_log=((hk,), 0.5),
                dt_bias=((hk * d,), 1.0),
                wbeta=((h, hk), s_h),
                wg_down=((h, rank), s_h),
                wg_up=((rank, hk * d), rank ** -0.5),
                norm_o=((d,), None),
                wo=((hk * d, h), (hk * d) ** -0.5),
            )
        else:
            blk.update(
                wqkv=((h, qd + 2 * kvd), s_h),
                wz=((h, qd), s_h),
                wo=((qd, h), qd ** -0.5),
            )
        plan["blocks"].append(blk)
    return plan


# ------------------------------------------------------------------ KDA

def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_inputs(blk, a, tail, sizes, bits=None):
    """Rows ``a`` (n, hidden) of a KDA layer's normed input, ``tail``
    the ``taps - 1`` pre-activation rows before them -> ``(q, k, v, g
    (n, H, D), beta (n, H), the new tail)``."""
    hk, d, taps = sizes["kda_heads"], sizes["head_dim"], sizes["kda_conv"]
    n = a.shape[0]
    ext = jnp.concatenate([tail, _mm(a, blk["wqkv"], bits)], axis=0)
    w = blk["conv_w"].astype(jnp.float32)
    conv = sum(w[i] * ext[i:i + n] for i in range(taps))
    q, k, v = (y.reshape(n, hk, d)
               for y in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    dt = _mm(_mm(a, blk["wa_down"], bits), blk["wa_up"], bits) \
        + blk["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(blk["a_log"].astype(jnp.float32))[None, :, None] \
        * jax.nn.softplus(dt).reshape(n, hk, d)
    beta = sizes["kda_beta_scale"] * jax.nn.sigmoid(
        _mm(a, blk["wbeta"], bits))
    return (_fq(_l2norm(q), bits, -1), _fq(_l2norm(k), bits, -1),
            _fq(v, bits, -1), g, beta, ext[n:])


def kda_tokens(s, q, k, v, g, beta):
    """The recurrence token by token from state ``s`` (H, D, D): q, k,
    v, g (n, H, D), beta (n, H) -> (new state, o (n, H, D))."""
    d = q.shape[-1]

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt,
                                           precision=HI))
        s = s + kt[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI) / d ** 0.5

    return jax.lax.scan(step, s, (q, k, v, g, beta))


def _kda_layer(blk, x, sizes, bits):
    hk, d, taps = sizes["kda_heads"], sizes["head_dim"], sizes["kda_conv"]
    eps, n = sizes["norm_eps"], x.shape[0]

    def block(carry, xb):
        s, tail = carry
        a = _rmsnorm(xb, blk["norm_attn"], eps)
        q, k, v, g, beta, tail = kda_inputs(blk, a, tail, sizes, bits)
        s, o = kda_tokens(s, q, k, v, g, beta)
        gate = jax.nn.sigmoid(
            _mm(_mm(a, blk["wg_down"], bits), blk["wg_up"], bits))
        o = _rmsnorm(o, blk["norm_o"], eps).reshape(-1, hk * d) * gate
        return (s, tail), xb + _mm(o, blk["wo"], bits)

    carry = (jnp.zeros((hk, d, d), jnp.float32),
             jnp.zeros((taps - 1, 3 * hk * d), jnp.float32))
    if n <= ROW_BLOCK:
        return block(carry, x)[1]
    assert n % ROW_BLOCK == 0, (n, ROW_BLOCK)
    _, out = jax.lax.scan(block, carry, x.reshape(-1, ROW_BLOCK, x.shape[-1]))
    return out.reshape(n, -1)


# ------------------------------------------------------------------ GQA

def _gqa_layer(blk, x, sizes, bits):
    n, hq, hkv, d = (x.shape[0], sizes["n_heads"], sizes["n_kv_heads"],
                     sizes["head_dim"])
    a = _rmsnorm(x, blk["norm_attn"], sizes["norm_eps"])
    qkv = _mm(a, blk["wqkv"], bits)
    q, k, v = jnp.split(qkv, [hq * d, (hq + hkv) * d], axis=-1)
    k = _fq(k.reshape(n, hkv, d), bits, -1)
    v = _fq(v.reshape(n, hkv, d), bits, -1)
    keys = jnp.arange(n)

    def block(queries_and_start):
        qs, start = queries_and_start        # (B, hq, d), scalar
        at = start + jnp.arange(qs.shape[0])
        s = jnp.einsum("shgd,thd->hgst", qs.reshape(-1, hkv, hq // hkv, d),
                       k, precision=HI) / (d ** 0.5)
        p = jax.nn.softmax(
            jnp.where(keys[None, :] <= at[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgst,thd->shgd", p, v, precision=HI) \
            .reshape(-1, hq * d)

    qb = min(Q_BLOCK, n)
    assert n % qb == 0, (n, qb)
    o = jax.lax.map(block, (q.reshape(n // qb, qb, hq, d),
                            jnp.arange(0, n, qb)))
    o = o.reshape(n, hq * d) * jax.nn.sigmoid(_mm(a, blk["wz"], bits))
    return x + _mm(o, blk["wo"], bits)


@functools.partial(jax.jit, static_argnames=("sizes", "bits"))
def _logits(params, tokens, rows, *, sizes, bits):
    sizes = dict(sizes)
    eps = sizes["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    for i, blk in enumerate(params["blocks"]):
        layer = _kda_layer if sizes["layer_mixer"][i] == "kda" \
            else _gqa_layer
        x = layer(blk, x, sizes, bits)
        m = _rmsnorm(x, blk["norm_mlp"], eps)
        x = x + share_of_layer(blk, m, sizes, bits) + _gated(
            m, blk["shared_up"], blk["shared_down"], bits)
    x = _rmsnorm(x[rows], params["norm_f"], eps)
    return _mm(x, params["lm_head"], bits)


def logits_at(params, sizes: dict, tokens, rows, bits=None):
    """Next-token logits ``(len(rows), vocab)`` float32 after positions
    ``rows`` of ONE sequence ``tokens`` (1-D int32). The pass is causal,
    so tokens padded on at the end change nothing at earlier rows."""
    frozen = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in sizes.items()))
    with jax.default_matmul_precision("highest"):
        return _logits(params, jnp.asarray(tokens, jnp.int32),
                       jnp.asarray(rows, jnp.int32), sizes=frozen,
                       bits=bits)
