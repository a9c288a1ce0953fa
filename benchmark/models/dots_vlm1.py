"""dots.vlm1's language model (``model_type: dots_vlm``; DeepSeek-V3's
block, whose keys its config carries one for one): parameter plan and
plain reference, the two entry points every configuration's ``model``
module has, and nothing of the program:

    param_plan(sizes) -> tree of ((shape), std) leaves (std None = ones)
    logits_at(params, sizes, tokens, rows, bits=None) -> (rows, vocab)

Residual stream ``x`` (T, hidden); every product in float32 at
``jax.lax.Precision.HIGHEST``; eps = ``norm_eps``::

    every layer:    a = rmsnorm(x, norm_attn);  x = x + mla(a, pos)
                    m = rmsnorm(x, norm_mlp);   x = x + ffn_l(m)
    after the last: logits = rmsnorm(x, norm_f) lm_head

    mla (n_heads heads; d_nope = qk_nope_dim, d_rope = qk_rope_dim,
         d_v = v_head_dim; ranks q_latent and kv_latent):
      cq = rmsnorm(a wq_a, norm_qa)                         # (T, q_latent)
      q  = cq wq_b  as (T, heads, d_nope + d_rope) = [q_nope | q_pe]
      [ckv | kpe] = a wkv_a                                 # (T, kv_latent | d_rope)
      ckv = rmsnorm(ckv, norm_kva);  kpe is ONE head, shared by all heads
      q_pe, kpe = rope(q_pe, kpe, pos)      # rotate-half over d_rope, YaRN frequencies
      [k_nope_h | v_h] = ckv wkv_b  as (heads, d_nope | d_v)
      s_h[t, j] = (q_nope_h[t] . k_nope_h[j] + q_pe_h[t] . kpe[j]) * scale,  j <= t
      scale = (d_nope + d_rope)^-0.5 * m^2,  m = 0.1 * rope_mscale_all_dim * ln(factor) + 1
      o_h = softmax_j(s_h) v_h;  out = concat_h(o_h) wo

    YaRN (dim d_rope, base rope_theta, factor, original, beta_fast, beta_slow):
      f_i = base^(-2i / dim), i < dim / 2
      cd(n) = dim ln(original / (2 pi n)) / (2 ln base)
      lo = max(floor(cd(beta_fast)), 0);  hi = min(ceil(cd(beta_slow)), dim - 1)
      r_i = clip((i - lo) / (hi - lo), 0, 1);  inv_freq_i = f_i (1 - r_i) + (f_i / factor) r_i
      (cos and sin times mscale / mscale_all_dim = 1)

    dense layer:   ffn(m) = (silu(m Wg) * (m Wu)) down       # up = [Wg | Wu], width dense_ffn
    sparse layer:  ffn(m) = shared(m) + sum over chosen e HELD HERE of w_e expert_e(m)
      s = sigmoid(m router)  (num_experts, float32);  c = s + router_bias
      group g = experts [g E/G, (g + 1) E/G):  G_g = sum of the 2 largest c in g
      keep the router_topk_groups groups with the largest G_g (ties to the lower g)
      chosen = the topk largest c among the kept groups' experts (ties to the lower e)
      w_e = routed_scale * s_e / sum_{chosen} s_e           # over all chosen, held or not

This is the EXPANDED form: keys and values are up-projected from the
latents for every head, nothing is absorbed, nothing is cached, one
sequence at a time. Which of it the configuration file states and which
it ASSUMES is in the file, under ``assumed``.

THE SHARE, as ``exaone_moe`` has it: the router keeps ``num_experts``
outputs, groups and ``topk``; only experts ``[first_expert_held,
first_expert_held + experts_held)`` exist here, and a token's routed
result is the part they give. ``vocab`` is the slice of the vocabulary
held here. ``share_of_layer`` is the sparse layer's routed part for ANY
such share, which lets a test add the shares up to the whole.

Attention runs ``H_BLOCK`` heads at a time (their keys and values
up-projected for those heads alone) and, inside, queries in blocks of
``Q_BLOCK`` positions against all keys; the feed-forward parts run
``Q_BLOCK`` rows at a time. A sequence of 19968 tokens at 128 heads then
needs (16 x 256 x 19968) scores and (19968 x 16 x 256) keys and values
at a time, beside the weights (a sequence is shorter than ``Q_BLOCK`` or
a multiple of it: ``correct`` pads to 512).

``bits`` is the control ``correct`` has to reject: every matmul input
rounded to a symmetric ``bits``-bit grid (weights per output channel,
activations, latents and the shared key per row).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.models.prenorm_moe import HI, _fq, _mm, _rmsnorm

#: query positions scored against all keys at a time
Q_BLOCK = 256
#: heads scored at a time
H_BLOCK = 16


def param_plan(sizes: dict) -> dict:
    """Tree of ``(shape, std)`` leaves (``std`` None = ones), in the
    layout ``Transformer.init`` gives for these fields. Projections
    N(0, 1/sqrt(fan_in)), embeddings N(0, 0.02), gains 1, the router's
    selection bias N(0, 0.01)."""
    h, f, fd = sizes["hidden"], sizes["ffn"], sizes["dense_ffn"]
    heads = sizes["n_heads"]
    dn, dr, dv = (sizes["qk_nope_dim"], sizes["qk_rope_dim"],
                  sizes["v_head_dim"])
    ql, kl = sizes["q_latent"], sizes["kv_latent"]
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
            "wq_a": ((h, ql), s_h),
            "norm_qa": ((ql,), None),
            "wq_b": ((ql, heads * (dn + dr)), ql ** -0.5),
            "wkv_a": ((h, kl + dr), s_h),
            "norm_kva": ((kl,), None),
            "wkv_b": ((kl, heads * (dn + dv)), kl ** -0.5),
            "wo": ((heads * dv, h), (heads * dv) ** -0.5),
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


def yarn_inv_freq(sizes: dict):
    """The rotation's ``qk_rope_dim / 2`` inverse frequencies (the
    docstring's YaRN lines), float32."""
    dim, base = sizes["qk_rope_dim"], float(sizes["rope_theta"])
    factor = float(sizes["rope_yarn_factor"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f = base ** (-2.0 * i / dim)
    if factor == 1.0:
        return f

    def cd(turns):
        return dim * math.log(sizes["rope_yarn_original"]
                              / (2 * math.pi * turns)) / (2 * math.log(base))

    lo = max(math.floor(cd(sizes["rope_yarn_beta_fast"])), 0)
    hi = min(math.ceil(cd(sizes["rope_yarn_beta_slow"])), dim - 1)
    r = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f * (1.0 - r) + f / factor * r


def softmax_scale(sizes: dict) -> float:
    m = 1.0
    if sizes["rope_yarn_factor"] > 1.0:
        m = 0.1 * sizes["rope_mscale_all_dim"] * math.log(
            sizes["rope_yarn_factor"]) + 1.0
    return (sizes["qk_nope_dim"] + sizes["qk_rope_dim"]) ** -0.5 * m * m


def _rope(x, pos, inv_freq):
    """x (T, heads, d_rope) rotated to positions ``pos`` (T,):
    rotate-half."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _rowwise(fn, x):
    """``fn`` over the rows of ``x`` in blocks of ``Q_BLOCK`` (it acts
    on every row alone): the widest intermediate is a block's."""
    n = x.shape[0]
    if n <= Q_BLOCK:
        return fn(x)
    assert n % Q_BLOCK == 0, (n, Q_BLOCK)
    y = jax.lax.map(fn, x.reshape(n // Q_BLOCK, Q_BLOCK, -1))
    return y.reshape(n, -1)


def _mla(blk, a, sizes, bits):
    n, heads = a.shape[0], sizes["n_heads"]
    dn, dr, dv = (sizes["qk_nope_dim"], sizes["qk_rope_dim"],
                  sizes["v_head_dim"])
    kl, eps = sizes["kv_latent"], sizes["norm_eps"]
    cq = _rmsnorm(_mm(a, blk["wq_a"], bits), blk["norm_qa"], eps)
    kv = _mm(a, blk["wkv_a"], bits)
    ckv = _rmsnorm(kv[:, :kl], blk["norm_kva"], eps)
    pos, inv_freq = jnp.arange(n), yarn_inv_freq(sizes)
    kpe = _rope(kv[:, None, kl:], pos, inv_freq)[:, 0]       # (n, dr)
    # the cached entry is what a lower precision rounds
    ckv, kpe = _fq(ckv, bits, -1), _fq(kpe, bits, -1)
    scale = softmax_scale(sizes)
    keys = jnp.arange(n)
    hb = min(H_BLOCK, heads)
    qb = min(Q_BLOCK, n)
    assert heads % hb == 0 and n % qb == 0, (heads, hb, n, qb)

    def some_heads(w):
        """``hb`` heads, from their columns of wq_b and wkv_b: keys and
        values up-projected for these heads only, queries in blocks."""
        wq, wkv = w                           # (ql, hb (dn+dr)), (kl, hb (dn+dv))
        q = _mm(cq, wq, bits).reshape(n, hb, dn + dr)
        q_pe = _rope(q[..., dn:], pos, inv_freq)
        kvb = _mm(ckv, wkv, bits).reshape(n, hb, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]

        def block(queries_and_start):
            qn, qp, start = queries_and_start  # (qb, hb, dn | dr), scalar
            seen = keys[None, :] <= (start + jnp.arange(qb))[:, None]
            s = (jnp.einsum("shd,thd->hst", qn, k_nope, precision=HI)
                 + jnp.einsum("shd,td->hst", qp, kpe, precision=HI)) * scale
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thd->shd", p, v, precision=HI)

        o = jax.lax.map(block, (q[..., :dn].reshape(n // qb, qb, hb, dn),
                                q_pe.reshape(n // qb, qb, hb, dr),
                                jnp.arange(0, n, qb)))
        return o.reshape(n, hb, dv)

    def columns(w, d):                        # (K, heads d) -> head groups
        return w.reshape(w.shape[0], heads // hb, hb * d).transpose(1, 0, 2)

    o = jax.lax.map(some_heads, (columns(blk["wq_b"], dn + dr),
                                 columns(blk["wkv_b"], dn + dv)))
    o = o.transpose(1, 0, 2, 3).reshape(n, heads * dv)
    return _mm(o, blk["wo"], bits)


def _gated(xn, up, down, bits):
    def rows(x):
        h = _mm(x, up, bits)
        f = h.shape[-1] // 2
        return _mm(jax.nn.silu(h[:, :f]) * h[:, f:], down, bits)

    return _rowwise(rows, xn)


def choose(scores, bias, sizes):
    """``(ids (T, topk), kept (T, router_topk_groups))`` of the
    group-limited choice from sigmoid scores ``scores`` (T, E)."""
    c = scores + bias.astype(jnp.float32)
    t, e = c.shape
    g, kg = sizes["router_groups"], sizes["router_topk_groups"]
    per = e // g
    best2, _ = jax.lax.top_k(c.reshape(t, g, per), 2)
    _, kept = jax.lax.top_k(best2.sum(-1), kg)
    group_of = jnp.arange(e) // per
    allowed = (group_of[None, :, None] == kept[:, None, :]).any(-1)
    _, ids = jax.lax.top_k(jnp.where(allowed, c, -jnp.inf), sizes["topk"])
    return ids, kept


def route(blk, xn, sizes):
    """(gate (T, num_experts)): each token's weight on every expert of
    the whole layer, 0 on those it did not choose."""
    s = jax.nn.sigmoid(_mm(xn, blk["router"]))
    ids, _ = choose(s, blk["router_bias"], sizes)
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
    for blk in params["blocks"]:
        x = x + _mla(blk, _rmsnorm(x, blk["norm_attn"], eps), sizes, bits)
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
