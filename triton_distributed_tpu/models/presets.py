"""Model-family presets on the north-star shapes.

The reference benchmarks its kernels on Llama-7B/70B TP GEMMs
(test_ag_gemm.py defaults, BASELINE.json) and DeepSeek-style MoE
AllToAll shapes (README.md:87); these presets pin the same families as
runnable model configs — full-size for deployment, "tiny" twins with
identical topology for tests/CI.
"""

from __future__ import annotations

import jax.numpy as jnp

from triton_distributed_tpu.models.transformer import TransformerConfig


def llama_7b(**overrides) -> TransformerConfig:
    """Llama-2-7B geometry (the reference's intra-node AG-GEMM bench
    family: hidden 4096, ffn 11008)."""
    cfg = dict(
        vocab=32000, n_layers=32, hidden=4096, ffn=11008,
        n_heads=32, n_kv_heads=32, head_dim=128,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def llama_70b(**overrides) -> TransformerConfig:
    """Llama-2-70B geometry (GQA 8 KV heads; the inter-node bench
    family: hidden 8192, ffn 28672)."""
    cfg = dict(
        vocab=32000, n_layers=80, hidden=8192, ffn=28672,
        n_heads=64, n_kv_heads=8, head_dim=128,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def mixtral_8x7b(**overrides) -> TransformerConfig:
    """Mixtral-style MoE: 8 experts topk 2 in every block (the EP a2a
    + grouped-GEMM family)."""
    cfg = dict(
        vocab=32000, n_layers=32, hidden=4096, ffn=14336,
        n_heads=32, n_kv_heads=8, head_dim=128,
        moe="ep", moe_layers=tuple(range(32)), num_experts=8, topk=2,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def deepseek_moe_16b(**overrides) -> TransformerConfig:
    """DeepSeek-MoE-16B-style geometry: many small experts, topk 6
    (the low-latency AllToAll headline family, README.md:87)."""
    cfg = dict(
        vocab=102400, n_layers=28, hidden=2048, ffn=1408,
        n_heads=16, n_kv_heads=16, head_dim=128,
        moe="ep", moe_layers=tuple(range(1, 28)), num_experts=64, topk=6,
        dtype=jnp.bfloat16,
        # the reference's headline dispatch for this family is fp8
        # WITH_SCALE (README.md:87) — decode tokens cross the EP a2a at
        # 1 byte/elem with per-token scales (models/transformer.py)
        moe_wire_quant="fp8",
        # decode grouped GEMMs are weight-HBM-bound — serve the expert
        # matrices int8 (per-out-channel scales, epilogue dequant;
        # run params through Transformer.quantize_moe_weights). int8,
        # not fp8: v5e has no native fp8 MXU path and the widening
        # lowers poorly (docs/PERF.md dead-end record)
        moe_weight_quant="int8",
        # W8A8 expert GEMMs at decode: the MXU's s8×s8 path runs 2× the
        # bf16 rate and the wire already quantized the tokens
        moe_act_quant="int8",
        # int8 KV cache: half the cache HBM (2× context per chip) and
        # 25–40% faster decode attention (docs/PERF.md)
        kv_quant="int8",
        # int8 dense projections (wqkv/wo/lm_head): decode-time dense
        # GEMMs are weight-HBM-bound like the expert GEMMs — run params
        # through Transformer.quantize_dense_weights
        dense_weight_quant="int8",
        # W8A8 dense projections (lm_head stays W8A16 for the logits)
        dense_act_quant="int8",
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def k_exaone_236b(**overrides) -> TransformerConfig:
    """K-EXAONE-236B-A23B (LGAI-EXAONE, ``model_type: exaone_moe``) as
    published: 48 layers in the pattern sliding, sliding, sliding, full
    (window 128; rotation at theta 1e6 and none on the full layers, q/k
    RMS norm on all), GQA 64 / 8 x 128, one dense layer (gated, 18432)
    then 47 sparse ones: 128 experts of width 2048, top-8 by sigmoid
    score + selection bias, weights renormalised and x 2.5, one shared
    expert; vocabulary 153600. Its multi-token-prediction head is not
    part of the forward pass and not stated here.

    The per-layer tuples follow ``n_layers``, so a depth cut needs that
    one override; ``experts_held`` (with ``first_expert_held``) makes
    the config one chip's share of an expert-parallel deployment, and
    ``vocab`` its slice of the vocabulary."""
    n = int(overrides.get("n_layers", 48))
    kinds = tuple("full" if i % 4 == 3 else "sliding" for i in range(n))
    cfg = dict(
        vocab=153600, n_layers=n, hidden=6144, ffn=2048, dense_ffn=18432,
        n_heads=64, n_kv_heads=8, head_dim=128, norm_eps=1e-5,
        layer_attn=kinds, window=128,
        rope_theta=1e6,
        rope_layers=tuple(i for i, k in enumerate(kinds) if k == "sliding"),
        qk_norm=True, gated_ffn=True,
        moe="ep", moe_layers=tuple(range(1, n)), num_experts=128, topk=8,
        shared_experts=1, router="sigmoid_bias", routed_scale=2.5,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


#: MiniCPM-SALA's published ``mixer_types``: 8 block-sparse attention
#: layers ("minicpm4") among 24 lightning layers
_SALA_SPARSE_LAYERS = (0, 9, 16, 17, 22, 29, 30, 31)


def minicpm_sala(**overrides) -> TransformerConfig:
    """MiniCPM-SALA (openbmb, ``model_type: minicpm_sala``) as
    published: 32 layers, hidden 4096, a gated FFN of 16384 on every
    layer, vocabulary 73448, untied head; 8 block-sparse attention
    layers (32 query / 2 KV heads x 128, q/k RMS norm, no rotation,
    sigmoid output gate) and 24 lightning linear-attention layers (32
    heads x 128, q/k norm, rotation at theta 1e4, output norm and
    gate); muP scalings: embedding x 12, every sub-layer's output x
    1.4 / sqrt(32), the final hidden state / (4096 / 256).

    The sparse layers' sizes are MiniCPM4's published ``sparse_config``
    (kernel 32, stride 16, block 64, 1 initial block, window 2048,
    top-64, dense below 8192): the config names the family, not the
    numbers.

    A depth cut is two integers: ``n_layers`` and ``layer_stride`` run
    the published layers ``0, layer_stride, 2 layer_stride, ...``
    (their kinds kept; ``residual_scale`` stays the PUBLISHED depth's,
    a constant of the model). The per-layer tuples are derived here."""
    n = int(overrides.pop("n_layers", 32))
    step = int(overrides.pop("layer_stride", 1))
    if n < 1 or step < 1 or (n - 1) * step >= 32:
        raise ValueError(
            f"minicpm_sala: n_layers={n} at layer_stride={step} reaches "
            "past the 32 published layers")
    kinds = tuple(
        "attention" if i * step in _SALA_SPARSE_LAYERS else "lightning"
        for i in range(n))
    cfg = dict(
        vocab=73448, n_layers=n, hidden=4096, ffn=16384,
        n_heads=32, n_kv_heads=2, head_dim=128, norm_eps=1e-6,
        layer_mixer=kinds, lightning_heads=32,
        sparse_kernel=32, sparse_stride=16, sparse_block=64,
        sparse_init_blocks=1, sparse_window=2048, sparse_topk=64,
        sparse_dense_len=8192,
        rope_theta=1e4,
        rope_layers=tuple(
            i for i, k in enumerate(kinds) if k == "lightning"),
        qk_norm=True, gated_ffn=True, out_gate=True, out_norm=True,
        embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5,
        logit_divisor=4096 / 256,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def dots_vlm1(**overrides) -> TransformerConfig:
    """dots.vlm1's language model (rednote-hilab, ``model_type:
    dots_vlm``; DeepSeek-V3's block) as published: 61 layers, hidden
    7168, 128 heads of latent attention (MLA: query rank 1536, cached
    latent 512 + one shared rotated key of 64, q.k head 128 + 64, value
    head 128) with YaRN x 40 over 4096 positions, 3 dense layers (gated,
    18432) then 58 sparse ones: 256 experts of width 2048 in 8 groups,
    top-8 inside the best 4 groups by sigmoid score + selection bias,
    weights renormalised and x 2.5, one shared expert; vocabulary
    129280, untied head. Its multi-token-prediction module and its
    vision encoder are not part of this forward pass and not stated
    here.

    The cut is five integers: ``n_layers`` with ``n_dense_layers`` of
    them dense at the front, ``experts_held`` (with
    ``first_expert_held``) one chip's share of an expert-parallel
    layer, and ``vocab`` its slice of the vocabulary."""
    n = int(overrides.pop("n_layers", 61))
    dense = int(overrides.pop("n_dense_layers", 3))
    if not 0 <= dense <= n:
        raise ValueError(
            f"dots_vlm1: n_dense_layers={dense} of n_layers={n}")
    cfg = dict(
        vocab=129280, n_layers=n, hidden=7168, ffn=2048, dense_ffn=18432,
        n_heads=128, n_kv_heads=128, head_dim=192, norm_eps=1e-6,
        kv_latent=512, q_latent=1536, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128,
        rope_theta=1e4, rope_yarn_factor=40.0, rope_yarn_original=4096,
        rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
        rope_mscale_all_dim=1.0,
        gated_ffn=True,
        moe="ep", moe_layers=tuple(range(dense, n)), num_experts=256,
        topk=8, shared_experts=1, router="sigmoid_bias",
        routed_scale=2.5, router_groups=8, router_topk_groups=4,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def solar_open2(**overrides) -> TransformerConfig:
    """Solar-Open2-250B (upstage, ``model_type: solar_open2``) as
    published: 48 layers, hidden 4096, in periods of four: one softmax
    GQA layer (``gqa_layers`` 0, 4, ..., 44: 64 query / 8 KV heads x
    128, no rotation, sigmoid output gate) then three gated delta-rule
    linear-attention layers (KDA: 64 heads x 128, a causal depthwise
    convolution of 4 taps on q, k and v, a per-channel decay and an
    output gate through rank-128 pairs, beta up to 2, output norm);
    every layer sparse (``first_k_dense_replace`` 0): 320 experts of
    width 1280, top-8 by sigmoid score + selection bias, weights
    renormalised (x 1), one shared expert; vocabulary 196608, untied
    head.

    The cut is four integers: ``n_layers`` (the published layers 0,
    1, ...: the pattern follows), ``experts_held`` (with
    ``first_expert_held``) one chip's share of an expert-parallel
    layer, and ``vocab`` its slice of the vocabulary."""
    n = int(overrides.get("n_layers", 48))
    kinds = tuple("attention" if i % 4 == 0 else "kda" for i in range(n))
    cfg = dict(
        vocab=196608, n_layers=n, hidden=4096, ffn=1280,
        n_heads=64, n_kv_heads=8, head_dim=128, norm_eps=1e-5,
        layer_mixer=kinds, kda_heads=64, kda_conv=4, kda_rank=128,
        kda_beta_scale=2.0,
        gated_ffn=True, out_gate=True, out_norm=True,
        moe="ep", moe_layers=tuple(range(n)), num_experts=320, topk=8,
        shared_experts=1, router="sigmoid_bias", routed_scale=1.0,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def keye_vl2_30b(**overrides) -> TransformerConfig:
    """Keye-VL-2.0-30B-A3B's language model (Kwai-Keye, ``model_type:
    KeyeVL2``) as published: 48 identical layers, hidden 2048, GQA 32
    query / 4 KV heads x 128 with q/k RMS norm, rotation at theta 1e7
    (M-RoPE, ``mrope_section`` [16, 24, 24]: on text its three position
    components are equal and the rotation is the ordinary one over all
    64 frequency pairs), and on every layer ``sa_config``'s token-level
    selection: an indexer of 16 heads x 64 with one key head keeps each
    query's 2048 best cached tokens; every layer sparse: 128 experts of
    width 768, top-8 of the softmax renormalised, no shared expert;
    vocabulary 151936, untied head. Its vision tower is not part of
    this forward pass and not stated here.

    The cut is three integers: ``n_layers``, ``experts_held`` (with
    ``first_expert_held``) one chip's share of an expert-parallel
    layer, and ``vocab`` its slice of the vocabulary."""
    n = int(overrides.get("n_layers", 48))
    cfg = dict(
        vocab=151936, n_layers=n, hidden=2048, ffn=768,
        n_heads=32, n_kv_heads=4, head_dim=128, norm_eps=1e-6,
        rope_theta=1e7, rope_layers=tuple(range(n)), qk_norm=True,
        index_heads=16, index_dim=64, index_topk=2048,
        gated_ffn=True,
        moe="ep", moe_layers=tuple(range(n)), num_experts=128, topk=8,
        dtype=jnp.bfloat16,
    )
    cfg.update(overrides)
    return TransformerConfig(**cfg)


def tiny(preset=None, **overrides) -> TransformerConfig:
    """CI-sized twin: same topology knobs as ``preset`` (or dense
    defaults), tiny dims — what the tests and the driver dryrun use."""
    cfg = dict(
        vocab=128, n_layers=2, hidden=128, ffn=256,
        n_heads=8, n_kv_heads=4, head_dim=16,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    if preset is not None:
        cfg.update(
            moe=preset.moe,
            moe_layers=tuple(i for i in preset.moe_layers if i < 2),
            num_experts=min(preset.num_experts, 8),
            topk=min(preset.topk, 2),
            attn=preset.attn,
            moe_wire_quant=preset.moe_wire_quant,
            moe_weight_quant=preset.moe_weight_quant,
            moe_act_quant=preset.moe_act_quant,
            kv_quant=preset.kv_quant,
            dense_weight_quant=preset.dense_weight_quant,
            dense_act_quant=preset.dense_act_quant,
            # the serving-path architecture knobs, at the twin's depth
            # and sizes: the first two layers' kinds, a window of one
            # (tiny) page, a shared expert, the router and its scale
            layer_attn=preset.layer_attn[:2],
            window=min(preset.window, 16),
            rope_theta=preset.rope_theta,
            rope_layers=tuple(i for i in preset.rope_layers if i < 2),
            qk_norm=preset.qk_norm,
            gated_ffn=preset.gated_ffn,
            dense_ffn=min(preset.dense_ffn, 384),
            shared_experts=preset.shared_experts,
            router=preset.router,
            routed_scale=preset.routed_scale,
            # the mixers of PR 33 at the twin's depth and sizes: the
            # first two layers' kinds, four heads a lightning layer,
            # blocks of 8 with stride 2 / kernel 4, top-4, a window of
            # 16 and a dense length of 32
            layer_mixer=preset.layer_mixer[:2],
            lightning_heads=min(preset.lightning_heads, 4),
            sparse_kernel=min(preset.sparse_kernel, 4),
            sparse_stride=min(preset.sparse_stride, 2),
            sparse_block=min(preset.sparse_block, 8),
            sparse_init_blocks=preset.sparse_init_blocks,
            sparse_window=min(preset.sparse_window, 16),
            sparse_topk=min(preset.sparse_topk, 4),
            sparse_dense_len=min(preset.sparse_dense_len, 32),
            # the token selection of PR 49 at the twin's sizes: an
            # indexer of two heads of 8 that keeps 8 tokens
            index_heads=min(preset.index_heads, 2),
            index_dim=min(preset.index_dim, 8),
            index_topk=min(preset.index_topk, 8),
            out_gate=preset.out_gate,
            out_norm=preset.out_norm,
            # the gated delta-rule layers of PR 41 at the twin's sizes:
            # four heads, the published taps, a rank of 8
            kda_heads=min(preset.kda_heads, 4),
            kda_conv=preset.kda_conv,
            kda_rank=min(preset.kda_rank, 8),
            kda_beta_scale=preset.kda_beta_scale,
            embed_scale=preset.embed_scale,
            residual_scale=preset.residual_scale,
            logit_divisor=preset.logit_divisor,
            # the group-limited router of PR 35: at most four groups
            # (two experts or more each), the kept ones holding topk
            router_groups=min(preset.router_groups, 4),
            router_topk_groups=min(preset.router_topk_groups, 2),
        )
        if preset.kv_latent:
            # latent attention at the twin's sizes: ranks 24 / 16,
            # q.k head 8 + 4, value head 8, four heads; YaRN over 16
            # positions at the preset's other numbers
            cfg.update(
                n_heads=4, n_kv_heads=4, head_dim=12,
                kv_latent=16, q_latent=24, qk_nope_dim=8, qk_rope_dim=4,
                v_head_dim=8,
                rope_yarn_factor=min(preset.rope_yarn_factor, 4.0),
                rope_yarn_original=min(preset.rope_yarn_original, 16),
                rope_yarn_beta_fast=preset.rope_yarn_beta_fast,
                rope_yarn_beta_slow=preset.rope_yarn_beta_slow,
                rope_mscale_all_dim=preset.rope_mscale_all_dim,
            )
    cfg.update(overrides)
    return TransformerConfig(**cfg)
