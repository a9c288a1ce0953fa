"""Flagship transformer: TP+SP(+DP) decoder built on the overlap ops.

The reference is a kernel library, not a training framework — its model
surface is the TP shapes its tests use (Llama-7B/70B GEMMs,
test_ag_gemm.py; DeepSeek MoE shapes, test_ep_moe_inference.py) and the
SP decode layer. This module is the framework-level completion: a
decoder whose every projection runs through the fused overlap ops, so
the reference's flagship patterns (AG-GEMM up/qkv, GEMM-RS down/out —
tutorials 07/08; MoE TP — ag_group_gemm/moe_reduce_rs) ARE the training
path's hot path.

Two entry points: :meth:`Transformer.forward` (training, and the plain
reference the tests compare with) and :meth:`Transformer.serving_step`
(everything served: one ragged step of prefill chunks and decode tokens
over a paged :class:`ServingState`, driven by ``serving.ServingEngine``).

Layout (Megatron sequence-parallel):

* Between blocks, activations are (B·S, H) row-sharded over
  (*dp_axes, tp) — the SP layout.
* qkv/up projections: AG-GEMM (gather rows, col-shard heads/ffn).
* out/down projections: GEMM-RS (row-shard K, scatter rows back).
* Attention runs with heads sharded over tp (plain jnp between the
  overlap ops — XLA keeps the head dim local, no resharding).
* MoE blocks: MoETPMLP (TP over experts' F dim) or EPMoEMLP (EP over
  the same axis) — selectable per config.
* LM head: weights replicated, rows stay sharded, loss is computed on
  the row shards (no logit gather).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from triton_distributed_tpu import ops, tracing
from triton_distributed_tpu.kernels import moe_utils as mu
from triton_distributed_tpu.layers import (
    ColumnParallelLinear,
    ParallelMLP,
    RowParallelLinear,
)


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    n_layers: int = 2
    hidden: int = 512
    ffn: int = 1024
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 64
    # Attention parallelism: "tp" = heads sharded via AG-GEMM/GEMM-RS
    # projections; "ring" / "ulysses" = context parallelism over the tp
    # axis (sequence-sharded attention, replicated projection weights) —
    # the long-context training modes
    attn: str = "tp"
    # MoE: "none" = dense MLP everywhere; "tp" / "ep" put a MoE MLP in
    # every block whose index is in moe_layers
    moe: str = "none"
    moe_layers: tuple = ()
    num_experts: int = 8
    topk: int = 2
    norm_eps: float = 1e-5
    # Quantized wire for the fused EP-MoE DECODE transport ("fp8" |
    # "int8" | None): tokens cross the a2a at 1 byte/elem with
    # per-token scales in the metadata (≡ the reference's headline fp8
    # WITH_SCALE dispatch). Halves the decode wire bytes at n>1. ONE
    # rank (tp = 1) exchanges with nobody and has no wire: nothing is
    # quantized for it there (``ops/moe.py::EPMoEContext.local``).
    # Training is unaffected (it rides the differentiable
    # full-precision transport).
    moe_wire_quant: str | None = None
    # Weight-only quantization of the EP expert matrices ("int8" |
    # "fp8" | None): serving-decode grouped GEMMs are weight-HBM-bound
    # (B·topk rows vs MB-scale matrices), so 1-byte weights halve the
    # dominant read. Takes effect when the caller runs params through
    # :meth:`Transformer.quantize_moe_weights` (after init/load);
    # training (``forward``) widens transparently. TPU-first extension —
    # the reference quantizes only the moving tokens (WITH_SCALE fp8,
    # low_latency_all_to_all.py:82-90), not the stationary weights.
    moe_weight_quant: str | None = None
    # W8A8 expert GEMMs ("int8" | None): with int8 expert weights, also
    # quantize the decode activations per row and run the MXU's native
    # s8×s8 path at 2× the bf16 rate (ops/moe.EPMoEContext.act_quant).
    # Adds one more per-row quantization step on the hidden activation;
    # logits stay within ~1% of the W8A16 path (tests). Decode-only.
    moe_act_quant: str | None = None
    # Weight-only quantization of the DENSE projections ("int8" |
    # None): wqkv / wo / dense-MLP up/down / lm_head stored int8 with
    # per-out-channel f32 scales, consumed at DECODE time by the
    # grouped-GEMM epilogue-dequant kernel (E=1) — at decode the M dim
    # is B, so these matmuls are weight-HBM-bound exactly like the
    # expert GEMMs and 1-byte weights halve the dominant read. Takes
    # effect after :meth:`Transformer.quantize_dense_weights`;
    # ``forward`` widens transparently. TPU-first extension.
    dense_weight_quant: str | None = None
    # W8A8 dense projections ("int8" | None): also quantize the B
    # activation rows per step so the dense decode matmuls ride the
    # s8×s8 MXU path. Requires dense_weight_quant="int8". The lm_head
    # stays W8A16 (logits want the f32 accumulator unperturbed by
    # input quantization); applies to wqkv/wo/up/down.
    dense_act_quant: str | None = None
    # INT8 KV cache ("int8" | None): the serving page pools store int8
    # values + per-(page, head, position) f32 scales and the ragged
    # paged-attention kernel folds the scales into the softmax — half the KV bytes at rest
    # (2× context per chip) and on the attention DMA stream (measured
    # 25–40% faster decode attention at serving shapes, docs/PERF.md).
    # TPU-first serving extension; training is unaffected.
    kv_quant: str | None = None
    # rematerialize each block in backward (jax.checkpoint): trades one
    # extra forward per block for O(n_layers) less activation memory —
    # the standard long-context / large-model training knob. Off-TPU the
    # INTERPRETED Pallas engines carry io_callback effects that
    # jax.checkpoint rejects — use the XLA engines there (e.g.
    # TDTPU_FUSED_VMEM_BUDGET=0); compiled Mosaic kernels compose fine.
    remat: bool = False
    dtype: object = jnp.bfloat16
    param_dtype: object = jnp.float32
    # ---- architecture fields of the SERVING path (PR 29). Every
    # default is the model above: full causal attention, no rotation,
    # an un-gated two-matrix FFN of one width, a softmax router over
    # experts that are all held here. ``serving_step`` implements them;
    # ``forward`` raises on them by name (``_plain_only``).
    # per-layer attention kind, "full" | "sliding" (() = all full), and
    # the sliding layers' window: query i sees keys i - window < j <= i
    layer_attn: tuple = ()
    window: int = 0
    # rotary embedding (rotate-half, every dim of the head) at base
    # ``rope_theta`` on the layers in ``rope_layers``; none elsewhere
    rope_theta: float = 0.0
    rope_layers: tuple = ()
    # RMS norm of q and of k over the head dim, gains (head_dim,)
    qk_norm: bool = False
    # three-matrix gated FFN silu(x Wg) * (x Wu) Wd, dense layers and
    # experts alike; [Wg | Wu] are stored as ONE matrix (gate first)
    gated_ffn: bool = False
    # width of the dense layers' FFN where it is not ``ffn`` (0 = ffn)
    dense_ffn: int = 0
    # shared experts beside the routed ones: one dense gated MLP of
    # width ``shared_experts · ffn`` whose output every token adds
    shared_experts: int = 0
    # "softmax": top-k of the softmax, renormalised. "sigmoid_bias":
    # sigmoid scores, top-k chosen by score + a per-expert bias
    # (parameter ``router_bias``), weights the chosen scores
    # renormalised and times ``routed_scale``
    router: str = "softmax"
    routed_scale: float = 1.0
    # this program's SHARE of a wider expert-parallel layer: the router
    # keeps ``num_experts`` outputs, the ``experts_held`` experts from
    # ``first_expert_held`` on have their weights here, and the layer
    # adds the part of the result they give (0 = all are held)
    experts_held: int = 0
    first_expert_held: int = 0
    # ---- mixers beside softmax attention on every key (PR 33); the
    # defaults are the model above. ``serving_step`` implements them,
    # ``forward`` raises on them by name.
    # per-layer mixer kind, "attention" | "lightning" (() = all
    # attention). A LIGHTNING layer is linear attention with a per-head
    # decay: ``lightning_heads`` query AND key/value heads of
    # ``head_dim``, state S_t = lam_h S_{t-1} + k_t^T v_t (head_dim x
    # head_dim, float32, one a slot and layer in ``ServingState``),
    # o_t = (q_t / sqrt(head_dim)) S_t, lam_h = exp(-2^(-8 (h+1) /
    # lightning_heads))
    layer_mixer: tuple = ()
    lightning_heads: int = 0
    # BLOCK-SPARSE attention on the attention layers (``sparse_topk``
    # 0 = dense): a query at position i >= ``sparse_dense_len`` attends
    # the keys j <= i of its ``sparse_topk`` best blocks of
    # ``sparse_block`` tokens, chosen per KV head from the scores of
    # its query heads against COMPRESSED keys (the mean of
    # ``sparse_kernel`` keys every ``sparse_stride``; kept in a pool
    # beside K/V), with the first ``sparse_init_blocks`` blocks and the
    # blocks of the last ``sparse_window`` positions always chosen
    # (kernels/sparse_select.py has the lines)
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_block: int = 0
    sparse_init_blocks: int = 0
    sparse_window: int = 0
    sparse_topk: int = 0
    sparse_dense_len: int = 0
    # TOKEN-LEVEL learned selection on the attention layers
    # (``index_topk`` 0 = none; a selection kind beside the block one,
    # not a mixer): an INDEXER of ``index_heads`` query heads of
    # ``index_dim`` and ONE key head scores every cached token, I[t, s]
    # = sum_j w[t, j] relu(qI[t, j] . kI[s] / sqrt(index_dim)), and a
    # query at position t >= index_topk attends its ``index_topk``
    # best keys s <= t (ties to the lower s; every key before that), one
    # choice for all heads. qI, kI (layer-normed) and w come from the
    # layer's normed input through ``w_index``; qI and kI are rotated at
    # ``rope_theta`` over all ``index_dim``; the keys live in a pool
    # beside K/V (kernels/token_select.py has the lines)
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # sigmoid output gate on every mixer: out = (o * sigmoid(a Wz)) Wo
    # (parameter ``wz``), and on lightning layers an RMS norm of o over
    # each head before it (gain ``norm_o``, (head_dim,))
    out_gate: bool = False
    out_norm: bool = False
    # muP scalings: the embedding times ``embed_scale``, every
    # sub-layer's output times ``residual_scale`` before it is added,
    # the final hidden state divided by ``logit_divisor`` before the head
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    # ---- latent attention (MLA) and group-limited routing (PR 35);
    # the defaults are the model above. ``serving_step`` implements
    # them, ``forward`` raises on them by name.
    # ``kv_latent`` > 0 (0 = K and V pages): every layer caches ONE
    # entry a token, the RMS-normed latent ``c_kv`` (``kv_latent``
    # values) and the rotated key ``k_pe`` (``qk_rope_dim``) that all
    # heads share; a head's key is [c_kv W_kvb's key part (
    # ``qk_nope_dim``) | k_pe], its value c_kv W_kvb's value part
    # (``v_head_dim``); queries come through a latent of ``q_latent``
    # with an RMS norm inside. ``head_dim`` is then the q.k head size
    # ``qk_nope_dim + qk_rope_dim`` and ``n_kv_heads == n_heads``. The
    # step attends ABSORBED: W_kvb's key part folded into the query,
    # its value part applied after the walk over the latents
    kv_latent: int = 0
    q_latent: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN frequencies for the rotation (``rope_yarn_factor`` 1 = the
    # plain ones): the ``qk_rope_dim`` / 2 frequencies of base
    # ``rope_theta`` blended towards ``f / factor`` between the
    # dimensions that turn ``beta_fast`` and ``beta_slow`` times in
    # ``rope_yarn_original`` positions, and the softmax scale times
    # ``(0.1 rope_mscale_all_dim ln(factor) + 1)^2``
    rope_yarn_factor: float = 1.0
    rope_yarn_original: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # group-limited choice of the ``sigmoid_bias`` router (1, 1 = no
    # limit): the experts lie in ``router_groups`` equal groups, a
    # group scores the sum of its two largest biased scores, and the
    # top-k is taken inside the ``router_topk_groups`` best groups
    router_groups: int = 1
    router_topk_groups: int = 1
    # ---- gated delta-rule linear attention (PR 41); the defaults are
    # the model above. ``serving_step`` implements it, ``forward``
    # raises on it by name.
    # ``layer_mixer[i] == "kda"``: ``kda_heads`` query AND key/value
    # heads of ``head_dim``; q, k, v through a causal depthwise
    # convolution of ``kda_conv`` taps + SiLU (its last ``kda_conv - 1``
    # pre-activation rows a slot kept beside the matrix), q and k
    # L2-normed a head; a decay PER CHANNEL ``alpha_t = exp(-exp(a_log_h)
    # softplus(x wa_down wa_up + dt_bias))`` and an output gate
    # ``sigmoid(x wg_down wg_up)`` (under ``out_gate``), both through a
    # rank-``kda_rank`` pair; ``beta_t = kda_beta_scale sigmoid(x
    # wbeta)`` (2: eigenvalues down to -1); state ``S_t = (I - beta_t
    # k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T`` (head_dim x
    # head_dim float32), ``o_t = S_t^T q_t / sqrt(head_dim)``
    # (kernels/kda_attention.py has the lines)
    kda_heads: int = 0
    kda_conv: int = 0
    kda_rank: int = 0
    kda_beta_scale: float = 1.0

    def __post_init__(self):
        if self.attn not in ("tp", "ring", "ulysses"):
            raise ValueError(
                f"attn must be 'tp', 'ring' or 'ulysses', got {self.attn!r}"
            )
        if self.moe not in ("none", "tp", "ep"):
            raise ValueError(
                f"moe must be 'none', 'tp' or 'ep', got {self.moe!r}"
            )
        if self.moe_wire_quant not in (None, "fp8", "int8"):
            raise ValueError(
                "moe_wire_quant must be None, 'fp8' or 'int8', got "
                f"{self.moe_wire_quant!r}"
            )
        if self.moe_weight_quant not in (None, "fp8", "int8"):
            raise ValueError(
                "moe_weight_quant must be None, 'fp8' or 'int8', got "
                f"{self.moe_weight_quant!r}"
            )
        if self.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {self.kv_quant!r}"
            )
        if self.dense_weight_quant not in (None, "int8"):
            raise ValueError(
                "dense_weight_quant must be None or 'int8', got "
                f"{self.dense_weight_quant!r}"
            )
        if self.moe_act_quant not in (None, "int8"):
            raise ValueError(
                "moe_act_quant must be None or 'int8', got "
                f"{self.moe_act_quant!r}"
            )
        if self.moe_act_quant is not None and self.moe_weight_quant != "int8":
            raise ValueError(
                "moe_act_quant (W8A8) needs moe_weight_quant='int8' — the "
                "s8×s8 MXU path consumes int8 weight dicts"
            )
        if self.dense_act_quant not in (None, "int8"):
            raise ValueError(
                "dense_act_quant must be None or 'int8', got "
                f"{self.dense_act_quant!r}"
            )
        if (self.dense_act_quant is not None
                and self.dense_weight_quant != "int8"):
            raise ValueError(
                "dense_act_quant (W8A8) needs dense_weight_quant='int8'"
            )
        if self.moe_weight_quant is not None and self.moe != "ep":
            raise ValueError(
                "moe_weight_quant targets the EP expert matrices — set "
                f"moe='ep' (got moe={self.moe!r})"
            )
        if self.layer_attn and (
            len(self.layer_attn) != self.n_layers
            or set(self.layer_attn) - {"full", "sliding"}
        ):
            raise ValueError(
                f"layer_attn must give 'full' or 'sliding' for each of "
                f"the {self.n_layers} layers, got {self.layer_attn!r}")
        if (self.window > 0) != bool(self.window_layers):
            raise ValueError(
                f"window={self.window} and layer_attn's sliding layers "
                f"{self.window_layers} go together")
        # (a latent model rotates its decoupled halves on every layer:
        # rope_theta is its base, rope_layers stays empty)
        if bool(self.rope_layers) != (
                self.rope_theta > 0 and not self.kv_latent) or any(
                not 0 <= i < self.n_layers for i in self.rope_layers):
            raise ValueError(
                f"rope_layers={self.rope_layers!r} need rope_theta > 0 "
                f"and lie in the {self.n_layers} layers (and the other "
                f"way round; got rope_theta={self.rope_theta})")
        if self.router not in ("softmax", "sigmoid_bias"):
            raise ValueError(
                f"router must be 'softmax' or 'sigmoid_bias', got "
                f"{self.router!r}")
        held = self.experts_held
        if held and not 0 <= self.first_expert_held <= (
                self.num_experts - held):
            raise ValueError(
                f"experts_held={held} from first_expert_held="
                f"{self.first_expert_held} do not lie in the router's "
                f"{self.num_experts} experts")
        routed = [k for k, on in (
            ("router", self.router != "softmax"),
            ("routed_scale", self.routed_scale != 1.0),
            ("experts_held", held > 0),
            ("shared_experts", self.shared_experts > 0),
        ) if on]
        if routed and self.moe != "ep":
            raise ValueError(
                f"{', '.join(routed)}: built for moe='ep' only "
                f"(got moe={self.moe!r})")
        if self.gated_ffn and self.moe == "tp":
            raise ValueError("gated_ffn is not built for moe='tp'")
        quantized = [k for k in (
            "moe_weight_quant", "moe_act_quant", "dense_weight_quant")
            if getattr(self, k) is not None]
        if quantized and (self.gated_ffn or self.shared_experts or held):
            raise ValueError(
                f"{', '.join(quantized)}: not built beside gated_ffn / "
                "shared_experts / experts_held (their matrices are "
                "served in param_dtype)")
        if self.layer_mixer and (
            len(self.layer_mixer) != self.n_layers
            or set(self.layer_mixer) - {"attention", "lightning", "kda"}
        ):
            raise ValueError(
                f"layer_mixer must give 'attention', 'lightning' or "
                f"'kda' for each of the {self.n_layers} layers, got "
                f"{self.layer_mixer!r}")
        if bool(self.lightning_layers) != (self.lightning_heads > 0):
            raise ValueError(
                f"lightning_heads={self.lightning_heads} and layer_mixer's "
                f"lightning layers {self.lightning_layers} go together")
        kda = ("kda_heads", "kda_conv", "kda_rank")
        if self.kda_layers:
            if self.kda_heads < 1 or self.kda_conv < 2 \
                    or self.kda_rank < 1 \
                    or not 0.0 < self.kda_beta_scale <= 2.0:
                raise ValueError(
                    f"layer_mixer's kda layers {self.kda_layers} need "
                    "kda_heads >= 1, kda_conv >= 2 taps, kda_rank >= 1 "
                    "and 0 < kda_beta_scale <= 2 (got "
                    f"{[getattr(self, f) for f in kda]}, "
                    f"{self.kda_beta_scale})")
            beside = [k for k, on in (
                ("lightning layers (layer_mixer)",
                 bool(self.lightning_layers)),
                ("block-sparse attention (sparse_topk)",
                 self.sparse_topk > 0),
                ("qk_norm", self.qk_norm),
                ("rope_layers on a kda layer",
                 bool(set(self.rope_layers) & set(self.kda_layers))),
                ("kv_latent", self.kv_latent > 0),
                ("dense_weight_quant",
                 self.dense_weight_quant is not None),
            ) if on]
            if beside:
                raise ValueError(
                    f"kda layers (layer_mixer) with {', '.join(beside)}: "
                    "not built")
        elif any(getattr(self, f) for f in kda) \
                or self.kda_beta_scale != 1.0:
            raise ValueError(
                "kda_heads / kda_conv / kda_rank / kda_beta_scale "
                "without a 'kda' layer in layer_mixer")
        sparse = ("sparse_kernel", "sparse_stride", "sparse_block",
                  "sparse_init_blocks", "sparse_window", "sparse_dense_len")
        if self.sparse_topk:
            k, st, b = (self.sparse_kernel, self.sparse_stride,
                        self.sparse_block)
            if min(getattr(self, f) for f in sparse) < 1 or b % st \
                    or k % st or self.sparse_dense_len % b:
                raise ValueError(
                    f"sparse_topk={self.sparse_topk} needs "
                    f"{', '.join(sparse)} >= 1, sparse_stride dividing "
                    "sparse_kernel and sparse_block, and sparse_block "
                    "dividing sparse_dense_len (got "
                    f"{[getattr(self, f) for f in sparse]})")
        elif any(getattr(self, f) for f in sparse):
            raise ValueError(
                f"{', '.join(f for f in sparse if getattr(self, f))} "
                "without sparse_topk")
        index = ("index_heads", "index_dim", "index_topk")
        if self.index_topk:
            if min(getattr(self, f) for f in index) < 1 \
                    or self.index_dim % 2 or self.rope_theta <= 0:
                raise ValueError(
                    f"index_topk={self.index_topk} needs {', '.join(index)} "
                    ">= 1, an even index_dim and rope_theta > 0 (the "
                    "indexer's rotation; got "
                    f"{[getattr(self, f) for f in index]}, rope_theta="
                    f"{self.rope_theta})")
            beside = [k for k, on in (
                ("block-sparse attention (sparse_topk)",
                 self.sparse_topk > 0),
                ("recurrent layers (layer_mixer)",
                 bool(self.recurrent_layers)),
                ("kv_latent", self.kv_latent > 0),
                ("out_gate", self.out_gate),
            ) if on]
            if beside:
                raise ValueError(
                    f"index_topk={self.index_topk} with "
                    f"{', '.join(beside)}: a token selection is built "
                    "over the K/V pools of plain GQA layers only")
        elif any(getattr(self, f) for f in index):
            raise ValueError(
                f"{', '.join(f for f in index if getattr(self, f))} "
                "without index_topk")
        stateful = [k for k, on in (
            ("layer_mixer", bool(self.recurrent_layers)),
            ("sparse_topk", self.sparse_topk > 0),
            ("index_topk", self.index_topk > 0)) if on]
        if stateful and self.window_layers:
            raise ValueError(
                f"{', '.join(stateful)} with sliding-window layers "
                "(layer_attn) in the same model: not built")
        if stateful and self.kv_quant is not None:
            raise ValueError(
                f"{', '.join(stateful)} with kv_quant={self.kv_quant!r}: "
                "compressed keys, indexer keys and the selected walks are "
                "built over bf16 pools only")
        if stateful and self.attn != "tp":
            raise ValueError(
                f"{', '.join(stateful)} with attn={self.attn!r}: built "
                "for attn='tp' only")
        if self.out_norm and not self.recurrent_layers:
            raise ValueError("out_norm is the recurrent (lightning, kda) "
                             "layers' output norm: no such layer in "
                             "layer_mixer")
        latent = ("kv_latent", "q_latent", "qk_nope_dim", "qk_rope_dim",
                  "v_head_dim")
        if self.kv_latent:
            if min(getattr(self, f) for f in latent) < 1 \
                    or self.qk_rope_dim % 2 or self.rope_theta <= 0 \
                    or self.head_dim != self.qk_nope_dim + self.qk_rope_dim \
                    or self.n_kv_heads != self.n_heads:
                raise ValueError(
                    f"kv_latent={self.kv_latent} needs {', '.join(latent)} "
                    ">= 1, an even qk_rope_dim, rope_theta > 0, head_dim "
                    "= qk_nope_dim + qk_rope_dim and n_kv_heads = n_heads "
                    f"(got {[getattr(self, f) for f in latent]}, head_dim="
                    f"{self.head_dim}, rope_theta={self.rope_theta}, "
                    f"n_kv_heads={self.n_kv_heads})")
            beside = [k for k, on in (
                ("kv_quant", self.kv_quant is not None),
                ("sliding-window layers (layer_attn)",
                 bool(self.window_layers)),
                ("lightning layers (layer_mixer)",
                 bool(self.lightning_layers)),
                ("block-sparse attention (sparse_topk)",
                 self.sparse_topk > 0),
                ("rope_layers", bool(self.rope_layers)),
                ("qk_norm", self.qk_norm),
                ("out_gate", self.out_gate),
                ("dense_weight_quant",
                 self.dense_weight_quant is not None),
                (f"attn={self.attn!r}", self.attn != "tp"),
            ) if on]
            if beside:
                raise ValueError(
                    f"kv_latent={self.kv_latent} with "
                    f"{', '.join(beside)}: a latent pool holds one "
                    "bf16 entry a token for every head and is walked "
                    "whole by every layer; not built beside these")
        elif any(getattr(self, f) for f in latent):
            raise ValueError(
                f"{', '.join(f for f in latent if getattr(self, f))} "
                "without kv_latent")
        yarn = self.rope_yarn_factor != 1.0
        if self.rope_yarn_factor < 1.0 or (yarn and (
                not self.kv_latent or self.rope_yarn_original < 1
                or self.rope_yarn_beta_fast <= self.rope_yarn_beta_slow)):
            raise ValueError(
                f"rope_yarn_factor={self.rope_yarn_factor} must be >= 1 "
                "and, past 1, needs kv_latent (the rotation it is built "
                "into), rope_yarn_original >= 1 and rope_yarn_beta_fast "
                "> rope_yarn_beta_slow")
        g, kg = self.router_groups, self.router_topk_groups
        if g < 1 or not 1 <= kg <= g:
            raise ValueError(
                f"router_groups={g}, router_topk_groups={kg}: need 1 <= "
                "router_topk_groups <= router_groups")
        if g > 1 and (self.router != "sigmoid_bias"
                      or self.num_experts % g
                      or self.num_experts // g < 2
                      or kg * (self.num_experts // g) < self.topk):
            raise ValueError(
                f"router_groups={g} is the sigmoid_bias router's "
                f"(got router={self.router!r}); the groups divide "
                f"num_experts={self.num_experts} into two experts or "
                f"more each, and router_topk_groups={kg} of them hold "
                f"topk={self.topk}")

    @property
    def window_layers(self) -> tuple:
        """Indices of the sliding-window attention layers."""
        return tuple(
            i for i, k in enumerate(self.layer_attn) if k == "sliding")

    @property
    def lightning_layers(self) -> tuple:
        """Indices of the lightning (linear-attention) layers."""
        return tuple(
            i for i, k in enumerate(self.layer_mixer) if k == "lightning")

    @property
    def kda_layers(self) -> tuple:
        """Indices of the gated delta-rule (KDA) layers."""
        return tuple(
            i for i, k in enumerate(self.layer_mixer) if k == "kda")

    @property
    def recurrent_layers(self) -> tuple:
        """Indices of the layers that keep a recurrent state a slot
        and no K/V pages: lightning and kda."""
        return tuple(i for i, k in enumerate(self.layer_mixer)
                     if k != "attention")

    @property
    def sparse_layers(self) -> tuple:
        """Indices of the block-sparse attention layers: every
        attention layer of a model with ``sparse_topk``."""
        if not self.sparse_topk:
            return ()
        return tuple(i for i in range(self.n_layers)
                     if i not in self.lightning_layers)

    @property
    def index_width(self) -> int:
        """Columns of ``w_index``: the indexer's queries, its one key
        and its head weights, side by side."""
        return (self.index_heads + 1) * self.index_dim + self.index_heads

    @property
    def index_stored(self) -> int:
        """Values the indexer-key pool STORES a token and layer
        (``kernels/token_select.py::index_stored``)."""
        from triton_distributed_tpu.kernels.token_select import (
            index_stored,
        )

        return index_stored(self.index_dim)

    def layer_heads(self, i: int) -> tuple:
        """``(query heads, key/value heads)`` of layer ``i``'s mixer."""
        if i in self.lightning_layers:
            return self.lightning_heads, self.lightning_heads
        if i in self.kda_layers:
            return self.kda_heads, self.kda_heads
        return self.n_heads, self.n_kv_heads

    @property
    def local_experts(self) -> int:
        """Experts whose weights this program holds."""
        return self.experts_held or self.num_experts

    @property
    def experts_published(self) -> int:
        """The router's width: the experts of the whole layer, of which
        ``local_experts`` are held here."""
        return self.num_experts

    @property
    def dense_ffn_width(self) -> int:
        return self.dense_ffn or self.ffn

    @property
    def routed_assignments(self) -> bool:
        """Does the serving step route in the model (and hand
        ``ops.ep_moe`` its assignments), not in the op?"""
        return self.router != "softmax" or self.experts_held > 0

    @property
    def beyond_plain(self) -> tuple:
        """Names of the set fields only ``serving_step`` implements
        (``forward`` refuses them)."""
        return tuple(k for k, on in (
            ("layer_attn", bool(self.window_layers)),
            ("rope_layers", bool(self.rope_layers)),
            ("qk_norm", self.qk_norm),
            ("gated_ffn", self.gated_ffn),
            ("dense_ffn", self.dense_ffn_width != self.ffn),
            ("shared_experts", self.shared_experts > 0),
            ("router", self.router != "softmax"),
            ("experts_held", self.experts_held > 0),
            ("layer_mixer", bool(self.recurrent_layers)),
            ("sparse_topk", self.sparse_topk > 0),
            ("index_topk", self.index_topk > 0),
            ("out_gate", self.out_gate),
            ("out_norm", self.out_norm),
            ("embed_scale", self.embed_scale != 1.0),
            ("residual_scale", self.residual_scale != 1.0),
            ("logit_divisor", self.logit_divisor != 1.0),
            ("kv_latent", self.kv_latent > 0),
            ("router_groups", self.router_groups > 1),
        ) if on)

    @property
    def latent_width(self) -> int:
        """Values a latent pool NEEDS a token and layer: ``c_kv`` and
        the shared rotated key."""
        return self.kv_latent + self.qk_rope_dim

    @property
    def latent_stored(self) -> int:
        """Values a latent pool STORES a token and layer: the entry
        padded with zeros to whole 128-lane tiles (one array a page:
        one DMA, one score product; a (page, qk_rope_dim) array of its
        own would occupy a whole lane tile all the same)."""
        return -(-self.latent_width // 128) * 128

    @property
    def yarn_inv_freq(self):
        """The rotation's ``qk_rope_dim / 2`` inverse frequencies,
        float32 (numpy): base ``rope_theta``, YaRN-blended where
        ``rope_yarn_factor`` > 1."""
        dim, base = self.qk_rope_dim, float(self.rope_theta)
        f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        factor = float(self.rope_yarn_factor)
        if factor == 1.0:
            return f.astype(np.float32)

        def correction_dim(turns):
            return dim * np.log(self.rope_yarn_original
                                / (turns * 2 * np.pi)) / (2 * np.log(base))

        lo = max(np.floor(correction_dim(self.rope_yarn_beta_fast)), 0)
        hi = min(np.ceil(correction_dim(self.rope_yarn_beta_slow)),
                 dim - 1)
        ramp = np.clip(
            (np.arange(dim // 2, dtype=np.float64) - lo)
            / max(hi - lo, 1e-3), 0, 1)
        return (f * (1 - ramp) + f / factor * ramp).astype(np.float32)

    @property
    def latent_softmax_scale(self) -> float:
        """``head_dim^-0.5 m^2``, ``m = 0.1 rope_mscale_all_dim
        ln(rope_yarn_factor) + 1`` (1 without YaRN)."""
        m = 1.0
        if self.rope_yarn_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * float(
                np.log(self.rope_yarn_factor)) + 1.0
        return float(self.head_dim ** -0.5 * m * m)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def qkv_dim(self) -> int:
        return self.q_dim + 2 * self.kv_dim


def expert_block_m(rows: int, topk: int, experts: int, *, resident: bool,
                   floor: int, cap: int) -> int:
    """The alignment block of an EP expert layer's sorted buffer for a
    step of ``rows`` packed rows whose router chooses ``topk`` of
    ``experts``.

    Every expert's segment of the sorted buffer is padded to a multiple
    of the block, so the buffer — and with it the sort, the gather into
    it, the re-quantization, the activation and the GEMMs' stores
    (``ops/moe.py::_grouped_mlp`` and the gather in front of it) — is
    ``received rows + (held experts
    + 1)·(block − 1)`` rows long whatever the step holds. A served
    step's grouped GEMM is weight-byte-bound (a decode-only step gives
    a touched expert 1–7 rows), so the block buys nothing on the MXU
    and is sized from the rows an expert gets from a FULL step under an
    even router, ``share = rows·topk / experts``, to a power of two:

    - ``resident`` weights (an expert's matrix stays in VMEM over its
      consecutive blocks, so a second block costs a grid step and no
      re-fetch): HALF the share;
    - tiled weights (every block re-streams the matrix): TWICE the
      share, so an expert at twice its even share still fits one block;

    never under ``floor`` nor over ``cap``. Measured on a v5e, one layer
    at the benchmark's shapes under a cell's load (PR 36; ``CHANGES.md``
    has the whole sweep, block x configuration x width): dsmoe (64
    experts, top-6, W8A8 resident) 0.70 ms at block 32 against 1.06 at
    128 on a 264-row step, 1.21 at 64 against 1.44 on a 768-row one;
    kexaone (a 16-of-128 share, top-8, tiled) 0.91 at 64 against 1.15
    at 256, and 3.27 at 128 against 3.79. Under 64 rows a tiled block
    loses again (dots 1.14 at 32, 0.97 at 64): a block that holds no
    row still walks its (N, K) grid steps, and there are more of them.
    mixtral (8 experts, top-2, tiled) keeps 256 at both of its widths.
    """
    share = rows * topk / experts * (0.5 if resident else 2)
    block = 1 << (max(1, math.ceil(share)) - 1).bit_length()
    return max(floor, min(cap, block))


def _rotate_half(x, cos, sin):
    """``x`` (T, heads, d) rotated by the tables of
    ``Transformer._rope_tables``, in float32, back in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


@dataclass(frozen=True)
class Transformer:
    """The model object: config + mesh/axes + derived contexts."""

    config: TransformerConfig
    mesh: Mesh
    tp_axis: str = "tp"
    dp_axes: tuple = ()
    # context-parallel axis for LONG-CONTEXT SERVING (None = no cp):
    # the serving page pool becomes cp stacked per-shard pools and each
    # shard's paged-attention partial merges through the cross-rank
    # LSE-combine (kernels/flash_decode.combine_gqa_partials; wire twin
    # cp_decode.lse_combine). Orthogonal to tp (head sharding) — a
    # tp×cp mesh shards heads within each cp group.
    cp_axis: str | None = None

    def __post_init__(self):
        # the build log's listeners, once a process: at the first model,
        # never at import
        tracing.install()
        with tracing.Span("setup.model"):
            self._check()

    def _check(self) -> None:
        """Refuse, by name, what this configuration on this mesh cannot
        run."""
        c = self.config
        if c.experts_held and self.tp > 1:
            raise ValueError(
                f"experts_held={c.experts_held} with tp={self.tp}: a "
                "share of an expert-parallel layer is what ONE chip "
                "holds; sharding it again over tp is not built")
        if c.window_layers and self.cp > 1:
            raise ValueError(
                "sliding-window layers (layer_attn) with cp > 1: a ring "
                "pool is one chip's, the cp shard walk is not built "
                "over it")
        stateful = [k for k, on in (
            ("lightning layers (layer_mixer)", bool(c.lightning_layers)),
            ("kda layers (layer_mixer)", bool(c.kda_layers)),
            ("block-sparse attention (sparse_topk)", c.sparse_topk > 0),
            ("a token selection (index_topk)", c.index_topk > 0),
        ) if on]
        for axis, n in (("tp", self.tp), ("cp", self.cp)):
            if stateful and n > 1:
                raise ValueError(
                    f"{', '.join(stateful)} with {axis}={n}: the "
                    "recurrent state, the compressed keys, the indexer's "
                    "keys and the selection are one chip's; sharding "
                    "them is not built")
            if c.kv_latent and n > 1:
                raise ValueError(
                    f"a latent pool (kv_latent) with {axis}={n}: the "
                    "latents are ONE head's, which a head-sharded pool "
                    "cannot split and the cp shard walk is not built "
                    "over")

    def _plain_only(self) -> None:
        """``forward`` runs the plain architecture; refuse, by name, a
        field only ``serving_step`` implements."""
        beyond = self.config.beyond_plain
        if beyond:
            raise ValueError(
                "Transformer.forward does not implement "
                f"{', '.join(beyond)}: serve this configuration through "
                "serving_step (ServingEngine)")

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def cp(self) -> int:
        """Context-parallel degree of the serving pool (1 = no cp)."""
        if not self.cp_axis:
            return 1
        return self.mesh.shape[self.cp_axis]

    @property
    def row_spec(self):
        """Sequence-parallel activation sharding: rows over (dp..., tp)."""
        return P(tuple(self.dp_axes) + (self.tp_axis,))

    @property
    def token_shards(self) -> int:
        """Number of row shards of the SP activation layout (tp × dp) —
        the single definition of the padding/shard-count arithmetic used
        by both forward (EPMoEMLP) and serving (_decode_moe_ep)."""
        return self.tp * int(
            np.prod([self.mesh.shape[a] for a in self.dp_axes]) or 1
        )

    @functools.cached_property
    def _ag_ctx(self):
        return ops.create_ag_gemm_context(
            self.mesh, self.tp_axis, batch_axes=tuple(self.dp_axes)
        )

    @functools.cached_property
    def _rs_ctx(self):
        return ops.create_gemm_rs_context(
            self.mesh, self.tp_axis, batch_axes=tuple(self.dp_axes)
        )

    @functools.cached_property
    def _mlp(self):
        return ParallelMLP(
            ColumnParallelLinear(self._ag_ctx),
            RowParallelLinear(self._rs_ctx),
            activation="silu",
        )

    @functools.cached_property
    def _moe_tp_ctx(self):
        c = self.config
        return ops.create_ag_group_gemm_context(
            self.mesh, self.tp_axis, num_experts=c.num_experts, topk=c.topk,
            dtype=c.dtype, use_pallas_gemm=False,
            batch_axes=tuple(self.dp_axes),
        )

    def _moe_ep_ctx(self, m_local: int, inference: bool = False,
                    weights_quantized: bool | None = None):
        """``weights_quantized``: whether the expert-weight leaves this
        context will consume are ACTUALLY quantized dicts — the
        residency gate must size VMEM from the real storage, not from
        the config's intent (a preset may default moe_weight_quant
        while the caller never ran quantize_moe_weights; sizing bf16
        tiles at 1 B/elem would blow scoped VMEM at compile). None →
        trust the config (callers without params in hand, e.g.
        init_decode_state — residency affects only GEMM tiling, not
        state geometry)."""
        c = self.config
        # training must stay on the differentiable XLA transport;
        # inference (decode) rides the fused window-DMA dispatch — the
        # low-latency path the reference's EP-MoE serving scenario is
        # built around (test_ep_moe_inference.py). Two fallbacks to the
        # XLA transport: a tp axis that crosses DCN (no Pallas remote
        # DMA there — fall back like every other op entry, don't raise),
        # and off-TPU runs (per-step interpreted dispatch kernels are
        # 100× slower and can wedge the interpreter's worker pool — the
        # fused decode path's compile/correctness coverage lives in
        # tests/test_ep_moe.py, test_races.py and test_aot_topology.py).
        from triton_distributed_tpu.config import (
            compiling_for_tpu,
            config as _cfg,
        )
        from triton_distributed_tpu.runtime import is_dcn_axis

        # force_fused_transport: bounded off-TPU execution of the fused
        # transport on the interpreter (the multi-device execution
        # evidence for the composed fused-LL step) — transport only;
        # the Mosaic-only grouped-GEMM/W8A8 paths still need real
        # lowering (pallas_ok below)
        fused_ok = (
            inference
            and (compiling_for_tpu() or _cfg.force_fused_transport)
            and not is_dcn_axis(self.mesh, self.tp_axis)
        )
        pallas_ok = fused_ok and compiling_for_tpu()
        # the scalar-prefetch grouped-GEMM kernel serves the expert MLP
        # on hardware (off-TPU / training keep the differentiable
        # ragged_dot path). WEIGHT-RESIDENT mode (whole-N/K tiles) needs
        # one expert's FULL (hidden, ffn) matrix double-buffered in
        # VMEM — gate on the budget (dsmoe's 2.9 MB int8 expert fits;
        # Mixtral's 117 MB one does not and takes the tiled schedule,
        # where every M-block of an expert re-streams its matrix)
        from triton_distributed_tpu.config import fused_vmem_budget
        from triton_distributed_tpu.kernels.group_gemm import (
            resident_weight_itemsize,
        )

        wq_mode = c.moe_weight_quant
        if weights_quantized is False:
            wq_mode = None               # raw bf16 leaves despite the config
        elif weights_quantized and wq_mode is None:
            # quantized dicts despite a None config (the explicit
            # mode= override of quantize_moe_weights): size the
            # residency gate from the 1-byte storage actually in hand
            wq_mode = "int8"
        w_itemsize = resident_weight_itemsize(wq_mode, c.dtype)
        wr_ok = pallas_ok and (
            2 * c.hidden * c.ffn * (2 if c.gated_ffn else 1) * w_itemsize
            <= int(0.7 * fused_vmem_budget())
        )
        # W8A8 engages only where its int8 weight dicts will exist
        a8 = c.moe_act_quant if (pallas_ok and wq_mode == "int8") else None
        if pallas_ok:
            # the alignment block follows the step's width
            # (``expert_block_m``). Caps: the blocks tuned for steps
            # whose every row is a token (``docs/PERF.md``, another
            # machine: 128 W8A8, 64 W8A16, 256 tiled); of the
            # benchmark's steps only mixtral's reach one. Floors: an
            # operand's sublane tile (32 rows int8, 16 bfloat16) where
            # the weights are resident, 64 where every M-block walks
            # its own (N, K) tiles
            tile = 32 // (1 if a8 else jnp.dtype(c.dtype).itemsize)
            bm = expert_block_m(
                m_local * self.tp, c.topk, c.num_experts, resident=wr_ok,
                floor=tile if wr_ok else 64,
                cap=(128 if a8 else 64) if wr_ok else 256)
        else:
            bm = 128
        return ops.create_ep_moe_context(
            self.mesh, self.tp_axis, num_experts=c.local_experts,
            topk=c.topk,
            max_m=m_local * c.topk, hidden=c.hidden, dtype=c.dtype,
            transport="fused" if fused_ok else "xla",
            use_pallas_gemm=pallas_ok,
            block_m=bm,
            gg_block_n=1 << 30 if wr_ok else None,
            gg_block_k=1 << 30 if wr_ok else None,
            quant=c.moe_wire_quant if fused_ok else None,
            act_quant=a8,
            batch_axes=tuple(self.dp_axes),
            gated=c.gated_ffn,
        )

    # ---------------------------------------------------------------- params

    def init(self, key):
        c = self.config
        keys = iter(jax.random.split(
            key, 4 + (16 if c.kda_layers else 11 if c.kv_latent else 8)
            * c.n_layers))
        # (a layer with every field of PR 33 draws 8 keys, a latent
        # layer 11, a kda layer with shared experts 16: the split holds)
        pd = c.param_dtype
        s = 1.0 / (c.hidden ** 0.5)

        def dense(k, shape, scale=None):
            return jax.random.normal(k, shape, pd) * (scale or s)

        params = {
            "embed": dense(next(keys), (c.vocab, c.hidden), 0.02),
            "norm_f": jnp.ones((c.hidden,), pd),
            "lm_head": dense(next(keys), (c.hidden, c.vocab)),
            "blocks": [],
        }
        # a gated FFN stores [gate | up] as one matrix of twice the width
        up_w = 2 if c.gated_ffn else 1
        e, fd = c.local_experts, c.dense_ffn_width
        for i in range(c.n_layers):
            # a lightning layer has its own head counts: as many
            # key/value heads as query heads
            hq, hkv = c.layer_heads(i)
            qd = hq * c.head_dim
            blk = {
                "norm_attn": jnp.ones((c.hidden,), pd),
                "norm_mlp": jnp.ones((c.hidden,), pd),
            }
            if c.kv_latent:
                # latent attention: two low-rank paths with a norm
                # inside each, W_kvb = per head [key part | value part]
                od = hq * c.v_head_dim
                blk.update(
                    wq_a=dense(next(keys), (c.hidden, c.q_latent)),
                    norm_qa=jnp.ones((c.q_latent,), pd),
                    wq_b=dense(next(keys), (c.q_latent, qd),
                               c.q_latent ** -0.5),
                    wkv_a=dense(next(keys), (c.hidden, c.latent_width)),
                    norm_kva=jnp.ones((c.kv_latent,), pd),
                    wkv_b=dense(
                        next(keys),
                        (c.kv_latent,
                         hq * (c.qk_nope_dim + c.v_head_dim)),
                        c.kv_latent ** -0.5),
                    wo=dense(next(keys), (od, c.hidden), od ** -0.5),
                )
            else:
                blk.update(
                    wqkv=dense(next(keys),
                               (c.hidden, qd + 2 * hkv * c.head_dim)),
                    wo=dense(next(keys), (qd, c.hidden)),
                )
            if i in c.kda_layers:
                # the convolution's taps over [q | k | v], the decay's
                # rank-kda_rank pair with its per-head and per-channel
                # terms, beta's projection
                blk.update(
                    conv_w=dense(next(keys), (c.kda_conv, 3 * qd),
                                 c.kda_conv ** -0.5),
                    wa_down=dense(next(keys), (c.hidden, c.kda_rank)),
                    wa_up=dense(next(keys), (c.kda_rank, qd),
                                c.kda_rank ** -0.5),
                    a_log=dense(next(keys), (hq,), 0.5),
                    dt_bias=dense(next(keys), (qd,), 1.0),
                    wbeta=dense(next(keys), (c.hidden, hq)),
                )
            if c.qk_norm:
                blk["norm_q"] = jnp.ones((c.head_dim,), pd)
                blk["norm_k"] = jnp.ones((c.head_dim,), pd)
            if c.index_topk:
                # the indexer: [queries | key | head weights] as ONE
                # matrix, and the gain of its key's layer norm
                blk["w_index"] = dense(next(keys), (c.hidden, c.index_width))
                blk["norm_ki"] = jnp.ones((c.index_dim,), pd)
            if c.out_gate and i in c.kda_layers:
                # a kda layer's gate goes through a rank-kda_rank pair
                blk["wg_down"] = dense(next(keys), (c.hidden, c.kda_rank))
                blk["wg_up"] = dense(next(keys), (c.kda_rank, qd),
                                     c.kda_rank ** -0.5)
            elif c.out_gate:
                blk["wz"] = dense(next(keys), (c.hidden, qd))
            if c.out_norm and i in c.recurrent_layers:
                blk["norm_o"] = jnp.ones((c.head_dim,), pd)
            if c.moe != "none" and i in c.moe_layers:
                blk["router"] = dense(next(keys), (c.hidden, c.num_experts))
                if c.router == "sigmoid_bias":
                    blk["router_bias"] = dense(
                        next(keys), (c.num_experts,), 0.01)
                blk["moe_up"] = dense(
                    next(keys), (e, c.hidden, up_w * c.ffn))
                blk["moe_down"] = dense(
                    next(keys), (e, c.ffn, c.hidden),
                    1.0 / (c.ffn ** 0.5),
                )
                if c.shared_experts:
                    fs = c.shared_experts * c.ffn
                    blk["shared_up"] = dense(
                        next(keys), (c.hidden, up_w * fs))
                    blk["shared_down"] = dense(
                        next(keys), (fs, c.hidden), 1.0 / (fs ** 0.5))
            else:
                blk["up"] = dense(next(keys), (c.hidden, up_w * fd))
                blk["down"] = dense(
                    next(keys), (fd, c.hidden), 1.0 / (fd ** 0.5)
                )
            params["blocks"].append(blk)
        return params

    def quantize_moe_weights(self, params, mode: str | None = None):
        """Replace every EP block's expert matrices with weight-only-
        quantized ``{"q": 1-byte, "scale": (E, N) f32}`` dicts (see
        group_gemm.quantize_grouped_weights). Run AFTER init/load and
        device placement — the quantized leaves inherit the expert
        sharding from the source arrays. ``mode`` defaults to
        ``config.moe_weight_quant``; returns ``params`` unchanged when
        both are None. Decode consumes the dicts in the grouped-GEMM
        epilogue; ``forward`` widens transparently."""
        mode = mode or self.config.moe_weight_quant
        if mode is None:
            return params
        if self.config.moe != "ep":
            raise ValueError("quantize_moe_weights targets EP expert weights")
        from triton_distributed_tpu.kernels.group_gemm import (
            quantize_grouped_weights,
        )

        out = dict(params)
        out["blocks"] = []
        for blk in params["blocks"]:
            blk = dict(blk)
            for name in ("moe_up", "moe_down"):
                if name in blk and not isinstance(blk[name], dict):
                    q, scale = quantize_grouped_weights(blk[name], mode)
                    blk[name] = {"q": q, "scale": scale}
            out["blocks"].append(blk)
        return out

    _DENSE_QUANT_KEYS = ("wqkv", "wo", "up", "down")

    def quantize_dense_weights(self, params, mode: str | None = None):
        """Replace the dense projection matrices (wqkv / wo / dense-MLP
        up/down per block, plus lm_head) with ``{"q": int8 (K, N),
        "scale": (N,) f32}`` dicts (per-out-channel, the same
        convention as the expert weights). Decode consumes them through
        the grouped-GEMM epilogue-dequant kernel; ``forward`` widens
        transparently. Run AFTER init/load + device placement;
        ``mode`` defaults to ``config.dense_weight_quant``."""
        mode = mode or self.config.dense_weight_quant
        if mode is None:
            return params
        from triton_distributed_tpu.kernels.group_gemm import (
            quantize_grouped_weights,
        )

        def q2d(w):
            if isinstance(w, dict):
                return w                       # already quantized
            q, scale = quantize_grouped_weights(w[None], mode)
            return {"q": q[0], "scale": scale[0]}

        out = dict(params)
        out["lm_head"] = q2d(params["lm_head"])
        out["blocks"] = []
        for blk in params["blocks"]:
            blk = dict(blk)
            for name in self._DENSE_QUANT_KEYS:
                if name in blk:
                    blk[name] = q2d(blk[name])
            out["blocks"].append(blk)
        return out

    def _dense_w(self, w):
        """Dense weight for a widening consumer (``forward``):
        dequantize a dict, cast a plain array to the compute dtype."""
        if isinstance(w, dict):
            from triton_distributed_tpu.kernels.group_gemm import (
                dequantize_grouped_weights,
            )

            return dequantize_grouped_weights(
                w["q"][None], w["scale"][None], self.config.dtype
            )[0]
        return w.astype(self.config.dtype)

    def _dmm(self, x, w, out_dtype=None, act_quant=True, shard=None):
        """Decode-time dense matmul dispatching on the weight storage:
        quantized dicts ride the grouped-GEMM kernel (E=1, tiled weight
        streaming with epilogue dequant — the decode GEMMs are
        weight-HBM-bound, so 1-byte weights halve the dominant read);
        plain arrays take the ordinary XLA dot. With
        ``config.dense_act_quant`` (and ``act_quant=True``), the B
        activation rows quantize per row and the kernel runs the
        s8×s8 MXU path (W8A8).

        ``shard``: the weight's tp layout per :meth:`shardings` —
        ``"col"`` (N sharded: wqkv/up), ``"row"`` (K sharded: wo/down,
        the per-rank partial products are psum'd) or None (replicated:
        lm_head). GSPMD cannot partition a Mosaic call, so the kernel
        runs per shard under ``shard_map``."""
        if not isinstance(w, dict):
            return x @ w.astype(out_dtype or self.config.dtype)
        from triton_distributed_tpu.config import fused_vmem_budget
        from triton_distributed_tpu.kernels.group_gemm import grouped_matmul

        b = x.shape[0]
        # ONE M-block (block_m = B): the grid iterates (m, n, k) with m
        # outermost, so a second M-block would re-stream every weight
        # tile — doubling the int8 reads back to bf16 volume (measured)
        if b > 1024:                             # huge M: decode never is
            y = x @ self._dense_w(w)
            return y.astype(out_dtype) if out_dtype is not None else y
        # sublane-odd B: pad rows up to the next multiple of 8 and slice
        # the result — the kernel path (f32 accumulator straight to the
        # store) then serves EVERY decode batch size; the old fallback
        # re-dequantized the full weight matrix in HBM per step and
        # rounded logits through bf16
        bp = -(-b // 8) * 8
        if bp != b:
            x = jnp.pad(x, ((0, bp - b), (0, 0)))
        # out_dtype reaches the kernel store: the f32 accumulator casts
        # straight to it (an astype after a bf16 store would re-widen
        # already-rounded values — logits want full f32)
        kw = dict(
            block_m=bp, vmem_limit_bytes=fused_vmem_budget(),
            out_dtype=out_dtype,
        )
        if (
            act_quant
            and self.config.dense_act_quant == "int8"
            and w["q"].dtype == jnp.int8
        ):
            from triton_distributed_tpu.kernels.group_gemm import (
                quantize_act_rows,
            )

            # per-row scales span the WHOLE K dim, so rows quantize
            # before any K split. Pin the out dtype: W8A8
            # grouped_matmul would otherwise default to bf16 (x is
            # int8), silently downcasting an f32 model's outputs
            x, xsc = quantize_act_rows(x)
            kw["out_dtype"] = out_dtype or self.config.dtype
            x_scale = (xsc,)
        else:
            x = x.astype(self.config.dtype)
            x_scale = ()
        t = self.tp_axis
        # specs of x (M, K), w (1, K, N), w_scale (1, N) and the output
        x_spec, w_spec, s_spec, o_spec = {
            "col": (P(), P(None, None, t), P(None, t), P(None, t)),
            "row": (P(None, t), P(None, t), P(), P()),
            None: (P(), P(), P(), P()),
        }[shard]

        def local(x, wq, ws, *xs):
            y = grouped_matmul(
                x, wq, jnp.zeros((1,), jnp.int32), w_scale=ws,
                x_scale=xs[0] if xs else None, **kw,
            )
            return jax.lax.psum(y, t) if shard == "row" else y

        y = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(x_spec, w_spec, s_spec) + (P(),) * len(x_scale),
            out_specs=o_spec, check_vma=False,
        )(x, w["q"][None], w["scale"][None], *x_scale)
        return y[:b] if bp != b else y

    @property
    def _attn_proj_shard(self):
        """``(wqkv, wo)`` tp layouts for :meth:`_dmm`, mirroring
        :meth:`shardings` (CP attention replicates the projections)."""
        return ("col", "row") if self.config.attn == "tp" else (None, None)

    def _expert_w(self, w):
        """Expert weights for a dense consumer: widen a quantized dict,
        cast a plain array."""
        if isinstance(w, dict):
            from triton_distributed_tpu.kernels.group_gemm import (
                dequantize_grouped_weights,
            )

            return dequantize_grouped_weights(
                w["q"], w["scale"], self.config.dtype
            )
        return w.astype(self.config.dtype)

    def shardings(self):
        """NamedSharding pytree matching :meth:`init` — TP dims sharded,
        the rest replicated (DP gradients reduce via batch_axes)."""
        c = self.config
        t = self.tp_axis

        def ns(*spec):
            return NamedSharding(self.mesh, P(*spec))

        rep = ns()
        out = {
            "embed": rep, "norm_f": rep, "lm_head": rep, "blocks": [],
        }
        for i in range(c.n_layers):
            if c.attn == "tp":
                attn_sh = {"wqkv": ns(None, t), "wo": ns(t, None)}
            else:
                # CP attention: projections replicated, sequence sharded
                attn_sh = {"wqkv": rep, "wo": rep}
            if c.kv_latent:
                # one chip's (tp > 1 is refused): every leaf whole
                attn_sh = dict.fromkeys(
                    ("wq_a", "norm_qa", "wq_b", "wkv_a", "norm_kva",
                     "wkv_b", "wo"), rep)
            blk = {
                "norm_attn": rep, "norm_mlp": rep, **attn_sh,
            }
            if c.qk_norm:
                blk.update(norm_q=rep, norm_k=rep)
            if c.index_topk:
                blk.update(w_index=rep, norm_ki=rep)
            if i in c.kda_layers:
                # one chip's (tp > 1 is refused): every leaf whole
                blk.update(dict.fromkeys(
                    ("conv_w", "wa_down", "wa_up", "a_log", "dt_bias",
                     "wbeta"), rep))
            if c.out_gate and i in c.kda_layers:
                blk.update(wg_down=rep, wg_up=rep)
            elif c.out_gate:
                blk.update(wz=ns(None, t))
            if c.out_norm and i in c.recurrent_layers:
                blk.update(norm_o=rep)
            if c.moe != "none" and i in c.moe_layers:
                if c.router == "sigmoid_bias":
                    blk.update(router_bias=rep)
                if c.shared_experts:
                    blk.update(shared_up=ns(None, t), shared_down=ns(t, None))
                if c.moe == "ep":
                    # experts sharded over tp (each rank owns E/tp experts)
                    blk.update(router=rep, moe_up=ns(t), moe_down=ns(t))
                else:
                    # TP flavour: the ffn dim sharded
                    blk.update(
                        router=rep,
                        moe_up=ns(None, None, t), moe_down=ns(None, t, None),
                    )
            else:
                blk.update(up=ns(None, t), down=ns(t, None))
            out["blocks"].append(blk)
        return out

    # --------------------------------------------------------------- forward

    def _rmsnorm(self, x, w):
        xf = x.astype(jnp.float32)
        r = jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.config.norm_eps
        )
        return (xf * r).astype(x.dtype) * w.astype(x.dtype)

    def _cp_attention(self, blk, x, b, s):
        """Context-parallel attention: sequence sharded over tp, heads
        whole, projection weights replicated (the long-context layout).
        x: (B·S, H) SP rows → (B·S, H) SP rows."""
        from triton_distributed_tpu.kernels.ring_attention import (
            ring_attention,
            ulysses_attention,
        )

        c = self.config
        ba = tuple(self.dp_axes)
        seq_sharding = NamedSharding(
            self.mesh, P(ba if ba else None, self.tp_axis)
        )
        xr = jax.lax.with_sharding_constraint(
            x.reshape(b, s, c.hidden), seq_sharding
        )
        qkv = xr @ self._dense_w(blk["wqkv"])                 # replicated W
        q, k, v = jnp.split(qkv, [c.q_dim, c.q_dim + c.kv_dim], axis=-1)
        q = q.reshape(b, s, c.n_heads, c.head_dim)
        k = k.reshape(b, s, c.n_kv_heads, c.head_dim)
        v = v.reshape(b, s, c.n_kv_heads, c.head_dim)
        attn = ring_attention if c.attn == "ring" else ulysses_attention
        o = attn(q, k, v, self.mesh, self.tp_axis, batch_axes=ba)
        o = o.reshape(b, s, c.q_dim) @ self._dense_w(blk["wo"])
        return jax.lax.with_sharding_constraint(
            o.reshape(b * s, c.hidden),
            NamedSharding(self.mesh, self.row_spec),
        )

    def _attention(self, blk, x, b, s):
        """x: (B·S, H) SP rows → (B·S, H) SP rows. Heads sharded tp;
        attn='ring'/'ulysses' take the context-parallel path."""
        c = self.config
        if c.attn != "tp":
            return self._cp_attention(blk, x, b, s)
        qkv = ops.ag_gemm(x, self._dense_w(blk["wqkv"]), self._ag_ctx)
        q, k, v = jnp.split(qkv, [c.q_dim, c.q_dim + c.kv_dim], axis=-1)
        hq, hkv, d = c.n_heads, c.n_kv_heads, c.head_dim
        q = q.reshape(b, s, hq, d)
        k = k.reshape(b, s, hkv, d)
        v = v.reshape(b, s, hkv, d)
        g = hq // hkv
        qg = q.reshape(b, s, hkv, g, d)
        logits = jnp.einsum(
            "bshgd,bthd->bhgst", qg.astype(jnp.float32), k.astype(jnp.float32)
        ) / (d ** 0.5)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(c.dtype)
        o = jnp.einsum("bhgst,bthd->bshgd", probs, v)
        o = o.reshape(b * s, hq * d)
        return ops.gemm_rs(o, self._dense_w(blk["wo"]), self._rs_ctx)

    def _mlp_block(self, blk, x):
        c = self.config
        if "up" in blk:
            p = {
                "up": {"w": self._dense_w(blk["up"])},
                "down": {"w": self._dense_w(blk["down"])},
            }
            return self._mlp(p, x)
        moe_params = {
            "router": blk["router"],
            "up": self._expert_w(blk["moe_up"]),
            "down": self._expert_w(blk["moe_down"]),
        }
        if c.moe == "ep":
            # EP flavour: experts sharded over tp, tokens stay row-sharded;
            # fully differentiable (XLA transport) — the training MoE.
            from triton_distributed_tpu.layers import EPMoEMLP

            return EPMoEMLP(
                self._moe_ep_ctx(x.shape[0] // self.token_shards)
            )(moe_params, x)
        # TP flavour
        logits = x.astype(jnp.float32) @ blk["router"]
        weights, ids = mu.select_experts(logits, c.topk)
        from triton_distributed_tpu.layers import MoETPMLP

        return MoETPMLP(self._moe_tp_ctx)(moe_params, x, ids, weights)

    def _embed_rows(self, params, tokens):
        """(B, S) int32 → (B·S, H) SP-row-sharded activations."""
        x = params["embed"][tokens.reshape(-1)].astype(self.config.dtype)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.row_spec)
        )

    def _head(self, params, x):
        x = self._rmsnorm(x, params["norm_f"])
        w = params["lm_head"]
        if isinstance(w, dict):
            w = self._dense_w(w)
        return x.astype(jnp.float32) @ w

    def forward(self, params, tokens):
        """tokens: (B, S) int32 → logits (B·S, vocab) SP-row-sharded."""
        self._plain_only()
        c = self.config
        b, s = tokens.shape
        x = self._embed_rows(params, tokens)

        def block(x, blk):
            x = x + self._attention(
                blk, self._rmsnorm(x, blk["norm_attn"]), b, s)
            return x + self._mlp_block(
                blk, self._rmsnorm(x, blk["norm_mlp"]))

        if c.remat:
            from triton_distributed_tpu.config import (
                _use_interpret,
                fused_vmem_budget,
            )

            if _use_interpret(None) and fused_vmem_budget() > 0:
                raise ValueError(
                    "remat=True off-TPU requires the XLA engines: the "
                    "interpreted Pallas engines carry io_callback effects "
                    "jax.checkpoint rejects. Set TDTPU_FUSED_VMEM_BUDGET=0 "
                    "(or config.config.fused_vmem_budget = 0) to pin them."
                )
            block = jax.checkpoint(block)
        for blk in params["blocks"]:
            x = block(x, blk)
        return self._head(params, x)

    def loss(self, params, tokens, targets):
        """Causal LM loss; logits stay row-sharded end to end."""
        logits = self.forward(params, tokens)
        tgt = targets.reshape(-1)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
        return jnp.mean(nll)

    def train_step(self, params, tokens, targets, lr=1e-3):
        """One SGD step (the driver's dryrun entry; real training would
        wrap this in optax — the grads are ordinary pytrees)."""
        l, g = jax.value_and_grad(self.loss)(params, tokens, targets)
        new = jax.tree.map(lambda p, d: p - lr * d.astype(p.dtype), params, g)
        return l, new

    # ------------------------------------------------------- ragged serving

    def init_decode_state(self, batch: int, abstract: bool = False):
        """Per-layer persistent workspaces for the BARRIER-FREE fused
        EP-MoE decode transport (ops.EPMoEState): one state per MoE
        layer, None elsewhere. Returns None when the model has no EP
        layers, when the exchange has ONE rank (tp = 1: no exchange,
        so no receive windows) or when decode would ride the XLA
        transport (off-TPU / DCN tp axis) — :meth:`serving_step` then
        needs no state at all.
        ``batch`` is a step's packed width (the engine builds one set
        per distinct width its steps take, ``ServingEngine._width``).
        ``abstract=True`` yields ShapeDtypeStruct leaves (topology
        compiles)."""
        c = self.config
        if c.moe != "ep" or not c.moe_layers:
            return None
        m_local = -(-batch // self.token_shards)
        ctx = self._moe_ep_ctx(m_local, inference=True)
        if ctx.local or ctx.transport != "fused":
            return None
        from triton_distributed_tpu.ops import create_ep_moe_state

        return [
            create_ep_moe_state(ctx, abstract=abstract)
            if i in c.moe_layers else None
            for i in range(c.n_layers)
        ]

    def moe_aligned_rows(self, batch: int, params=None) -> int:
        """Rows of the expert-sorted buffer ONE EP expert layer of a
        serving step ``batch`` packed rows wide allocates
        (``EPMoEContext.aligned_rows``: a static of that width's
        program, sized from ``params``' expert leaves as the step
        sizes it); 0 for a model with no EP expert layer."""
        c = self.config
        if c.moe != "ep" or not c.moe_layers:
            return 0
        wq = None if params is None else isinstance(
            params["blocks"][c.moe_layers[0]]["moe_up"], dict)
        return self._moe_ep_ctx(
            -(-batch // self.token_shards), inference=True,
            weights_quantized=wq).aligned_rows

    @property
    def moe_local(self) -> bool:
        """Whether a serving step's EP expert layers exchange with
        nobody (``EPMoEContext.local``: ONE rank on the tp axis, a
        static of the mesh): no dispatch, no combine, no workspaces.
        False for a model with no EP expert layer."""
        c = self.config
        return (c.moe == "ep" and bool(c.moe_layers)
                and self._moe_ep_ctx(1, inference=True).local)

    def _dense_mlp(self, xn, w_up, w_down):
        """The serving step's dense FFN on normed rows ``xn``:
        ``down(silu(up(x)))``, or gated (``config.gated_ffn``, ``w_up``
        = [gate | up]) ``down(silu(gate(x)) * up(x))``."""
        h = self._dmm(xn, w_up, shard="col")
        if self.config.gated_ffn:
            f = h.shape[-1] // 2
            h = jax.nn.silu(h[:, :f]) * h[:, f:]
        else:
            h = jax.nn.silu(h)
        return self._dmm(h, w_down, shard="row")

    def _decode_moe_ep(self, blk, xn, state=None, row_mask=None):
        """A serving step's EP MoE: the B packed-token activations ride
        the EP dispatch → sharded grouped expert MLP → combine machinery, so
        expert weights STAY sharded — no gathered (B, H, F) weight
        tensor ever materializes (the reference's EP-MoE inference
        headline: test_ep_moe_inference.py, decode-sized batches through
        low_latency_all_to_all.py:36-118). B is padded up to the token
        -shard count; pad rows are discarded after the combine. With
        ``state``, the transport runs barrier-free over the persistent
        workspaces; returns (y, state'). At tp = 1 there is no exchange
        and no state: the op sorts, gathers, multiplies and un-sorts in
        place (``EPMoEContext.local``). ``row_mask`` (B,) bool: the
        rows that are tokens of the step — the assignments of every
        other row (a serving step's padding) are handed to the op
        masked, so they are neither shipped nor multiplied; their ``y``
        is zero. None: every row is a token."""
        c = self.config
        b = xn.shape[0]
        shards = self.token_shards
        pad = (-b) % shards
        with jax.named_scope("moe_route"):
            xp = jnp.pad(xn, ((0, pad), (0, 0)))
            logits = xp.astype(jnp.float32) @ blk["router"]
            if c.routed_assignments or row_mask is not None:
                # the model routes (over ALL the router's experts) and
                # hands the op its assignments: local ids, masked where
                # the expert is not held here or the row is no token
                if c.router == "sigmoid_bias":
                    w, ids = mu.select_experts_sigmoid_bias(
                        logits, blk["router_bias"], c.topk,
                        scale=c.routed_scale, groups=c.router_groups,
                        topk_groups=c.router_topk_groups)
                else:
                    w, ids = mu.select_experts(logits, c.topk)
                logits = mu.held_assignments(
                    w, ids, c.first_expert_held, c.local_experts,
                    rows=None if row_mask is None
                    else jnp.pad(row_mask, (0, pad)))
        wq = isinstance(blk["moe_up"], dict)
        ctx = self._moe_ep_ctx(
            (b + pad) // shards, inference=True, weights_quantized=wq
        )
        # quantized dicts pass straight through — the ops layer consumes
        # them on both the grouped-GEMM (epilogue dequant) and XLA
        # (widen) paths; only plain arrays need the compute-dtype cast
        w_up, w_down = (
            w if isinstance(w, dict) else w.astype(c.dtype)
            for w in (blk["moe_up"], blk["moe_down"])
        )
        if state is not None and ctx.transport == "fused":
            y, state = ops.ep_moe(xp, logits, w_up, w_down, ctx, state=state)
        else:
            y = ops.ep_moe(xp, logits, w_up, w_down, ctx)
        return y[:b], state

    @property
    def _serving_pool_sharding(self):
        """Serving pool placement: KV HEADS (dim 1) over tp. Heads are
        independent in GQA attention, so the ragged serving step never
        exchanges LSE partials across ranks — and the whole page pool
        (dim 0) is one shared allocation any rank can serve any request
        from, which is what the engine's single free list requires."""
        return NamedSharding(self.mesh, P(None, self.tp_axis))

    def init_serving_state(self, slots: int, npages: int, page: int,
                           chunk: int | None = None):
        """Build a fresh :class:`~triton_distributed_tpu.serving.state.
        ServingState` — the serving-state object of the
        continuous-batching engine: per-layer head-sharded page pools,
        one shared (slots, pages_per_seq) block table (allocator-owned,
        -1 = unallocated), per-slot kv_lens and cursors. Every leaf
        gets its own buffer (the serving-step jit donates the state).
        ``pages_per_seq`` is ``npages`` capped at 1024 table columns —
        a slot may address the whole pool.

        Under ``cp > 1``, ``npages`` is the PER-SHARD pool size: the
        pool rows become one stacked allocation of ``cp·npages`` pages
        (shard r owns rows [r·npages, (r+1)·npages) — on a cp-sharded
        TPU mesh this dim would carry P(cp_axis); this reproduction
        keeps the stack replicated and shards the attention WALK), the
        table columns split the same way, and one slot's capacity
        grows to ``cp·pages_per_shard·page`` positions — the whole
        point of long-context serving.

        SLIDING-WINDOW layers (``config.layer_attn``) get ring pools of
        ``slots · ring`` pages and the state one ``ring_table`` for all
        of them (serving/state.py); ``chunk``, the most tokens a step
        appends to one slot, sizes the ring and is required then."""
        from triton_distributed_tpu.serving.state import (
            ServingState,
            fresh_table,
            ring_pages,
            ring_table,
        )

        c = self.config
        cp = self.cp
        if self.dp_axes:
            raise ValueError("ragged serving is tp-only (dp composes by "
                             "running one engine per dp group)")
        if c.n_kv_heads % self.tp:
            raise ValueError(
                f"serving pools shard the {c.n_kv_heads} KV heads over "
                f"tp={self.tp} — Hkv must divide"
            )
        pps = min(npages, max(1024 // cp, 1)) * cp
        npages = npages * cp
        spec = self._serving_pool_sharding
        windowed = c.window_layers
        ring = 0
        if windowed:
            if chunk is None:
                raise ValueError(
                    "init_serving_state: a model with sliding-window "
                    "layers needs chunk= (it sizes their ring pools)")
            ring = ring_pages(int(chunk), c.window, page)

        def pools(n, heads=c.n_kv_heads, width=c.head_dim):
            """``(make, ...)``: one template of an n-page pool on the
            device; every call of ``make`` an independent buffer (the
            step jit donates each leaf)."""
            if c.kv_quant is not None:
                zq = jax.device_put(
                    jnp.zeros((n, heads, page, width), jnp.int8), spec)
                zs = jax.device_put(
                    jnp.ones((n, heads, page), jnp.float32), spec
                )
                return lambda: {"q": zq + jnp.int8(0), "scale": zs + 0.0}
            z = jax.device_put(
                jnp.zeros((n, heads, page, width), c.dtype), spec)
            zero = jnp.zeros((), c.dtype)
            return lambda: z + zero

        lightning, sparse, kda = (c.lightning_layers, c.sparse_layers,
                                  c.kda_layers)
        if sparse and (page % c.sparse_block or page % c.sparse_stride
                       or c.sparse_dense_len % page):
            raise ValueError(
                f"block-sparse attention (sparse_topk) needs a page that "
                f"sparse_block={c.sparse_block} and sparse_stride="
                f"{c.sparse_stride} divide and that divides "
                f"sparse_dense_len={c.sparse_dense_len}, got page={page}")
        if c.index_topk and c.index_topk % page:
            raise ValueError(
                f"a token selection (index_topk) needs a page that "
                f"divides index_topk={c.index_topk}, got page={page}")
        # a latent pool: ONE entry a token and layer for all the heads,
        # ``(npages, 1, page, latent_stored)``, addressed by the same
        # block table; no V pool (serving/state.py)
        full = pools(npages, 1, c.latent_stored) if c.kv_latent \
            else pools(npages)
        recurrent = ckeys = ()
        if c.kv_latent:
            layers = tuple((full(), None) for _ in range(c.n_layers))
        elif lightning or sparse or kda:
            # a lightning layer keeps a state a slot and no pages, a kda
            # layer the pair (matrix, convolution tail); a sparse layer
            # a pool of compressed keys beside K/V (serving/state.py).
            # Each leaf its own buffer (donated)
            def matrix(heads):
                return jnp.zeros((slots, heads, c.head_dim, c.head_dim),
                                 jnp.float32)

            recurrent = tuple(
                matrix(c.lightning_heads) if i in lightning
                else (matrix(c.kda_heads),
                      jnp.zeros((slots, c.kda_conv - 1,
                                 3 * c.kda_heads * c.head_dim),
                                jnp.float32)) if i in kda
                else None for i in range(c.n_layers))
            ckeys = tuple(
                jax.device_put(
                    jnp.zeros((npages, c.n_kv_heads,
                               page // c.sparse_stride, c.head_dim),
                              c.dtype), spec)
                if i in sparse else None for i in range(c.n_layers))
            layers = tuple(
                None if i in c.recurrent_layers else (full(), full())
                for i in range(c.n_layers))
        elif c.index_topk:
            # every layer a pool of indexer keys beside K/V: ONE entry a
            # token for all the heads, ``(npages, 1, page,
            # index_stored)``, addressed by the same block table
            ckeys = tuple(
                jax.device_put(
                    jnp.zeros((npages, 1, page, c.index_stored), c.dtype),
                    spec)
                for _ in range(c.n_layers))
            layers = tuple((full(), full()) for _ in range(c.n_layers))
        elif windowed:
            # a window layer keeps slots · ring pages and no more
            ringed = pools(slots * ring)
            layers = tuple(
                (ringed(), ringed()) if i in windowed else (full(), full())
                for i in range(c.n_layers)
            )
        else:
            layers = tuple((full(), full()) for _ in range(c.n_layers))
        return ServingState(
            layers=layers,
            block_table=jnp.asarray(fresh_table(slots, pps)),
            kv_lens=jnp.zeros((slots,), jnp.int32),
            cursors=jnp.zeros((slots,), jnp.int32),
            page=page,
            cp=cp,
            ring_table=ring_table(slots, pps, ring) if windowed else None,
            window_layers=windowed,
            ring=ring,
            recurrent=recurrent,
            ckeys=ckeys,
        )

    def _ragged_attn(self, qp, k_pool, v_pool, state, q_lens, q_starts,
                     block_q, use_pallas, n_bufs=2, topologies=None,
                     with_lse=False, window=None):
        """One layer's ragged paged attention over the (updated) pools
        via the head-sharded serving layer. qp: (Hkv, T·G, D) packed
        GQA rows (already holding this step's tokens in the pools —
        append-then-attend). Returns (Hkv, T·G, D) — or the
        ``(out, lse)`` partial pair under ``with_lse`` (the cp shard
        loop merges those via ``combine_gqa_partials``)."""
        from triton_distributed_tpu.layers import RaggedPagedAttention

        c = self.config
        layer = RaggedPagedAttention(
            self.mesh, self.tp_axis, group=c.n_heads // c.n_kv_heads,
            use_pallas=use_pallas,
        )
        return layer(
            qp, k_pool, v_pool, state.kv_lens, q_lens, q_starts,
            state.block_table, topologies=topologies, block_q=block_q,
            n_bufs=n_bufs, with_lse=with_lse, window=window,
        )

    def _rope_tables(self, token_pos, dim=None):
        """``(cos, sin)``, each (T, 1, rotated dims) float32, of the
        packed tokens' sequence positions (padding tokens: position 0),
        the two halves alike (rotate-half): over ``head_dim`` (or
        ``dim``: an indexer's heads) at ``config.rope_theta``, or, in a
        latent model, over ``qk_rope_dim`` at the (YaRN)
        ``config.yarn_inv_freq``."""
        c = self.config
        if c.kv_latent:
            inv_freq = jnp.asarray(c.yarn_inv_freq)
        else:
            half = (dim or c.head_dim) // 2
            inv_freq = c.rope_theta ** (
                -jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.maximum(token_pos, 0).astype(jnp.float32)[:, None] \
            * inv_freq[None, :]
        ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
        return jnp.cos(ang), jnp.sin(ang)

    def _qk_norm_rope(self, blk, q, k, rope):
        """q (T, q_dim), k (T, kv_dim) → the same, each head RMS-normed
        over its dim (``config.qk_norm``: gains ``norm_q``, ``norm_k``)
        and, with ``rope`` (this layer rotates), rotated to its token's
        position. The rotation runs in float32."""
        c = self.config
        t = q.shape[0]

        def one(x, gain):
            x = x.reshape(t, -1, c.head_dim)
            if c.qk_norm:
                x = self._rmsnorm(x, blk[gain])
            if rope is not None:
                x = _rotate_half(x, *rope)
            return x.reshape(t, -1)

        return one(q, "norm_q"), one(k, "norm_k")

    def _latent_qkv(self, blk, xn, rope):
        """The latent layer's projections of normed rows ``xn`` (T, H):
        ``(q (T, heads · latent_stored), entry (T, 1, latent_stored))``
        in the compute dtype. ``q`` is ABSORBED, per head [q_nope
        W_kvb's key part^T (kv_latent) | rotated q_pe | zeros], and
        ``entry`` what the pool caches, [rmsnorm(c_kv) | rotated k_pe |
        zeros]: a head's score is their dot product."""
        c = self.config
        t = xn.shape[0]
        h, dn, dr, dl = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.kv_latent
        with jax.named_scope("mla_lowrank"):
            cq = self._rmsnorm(self._dmm(xn, blk["wq_a"]), blk["norm_qa"])
            q = self._dmm(cq, blk["wq_b"]).reshape(t, h, dn + dr)
            kv = self._dmm(xn, blk["wkv_a"])                  # (T, dl + dr)
            ckv = self._rmsnorm(kv[:, :dl], blk["norm_kva"])
        with jax.named_scope("qk_rope"):
            q_pe = _rotate_half(q[..., dn:], *rope)           # (T, h, dr)
            k_pe = _rotate_half(kv[:, None, dl:], *rope)      # (T, 1, dr)
        pad = c.latent_stored - c.latent_width
        with jax.named_scope("mla_absorb"):
            wk = blk["wkv_b"].astype(c.dtype).reshape(
                dl, h, dn + c.v_head_dim)[..., :dn]
            qa = jnp.einsum("thn,lhn->thl", q[..., :dn], wk,
                            preferred_element_type=jnp.float32)
            qf = jnp.concatenate(
                [qa.astype(c.dtype), q_pe,
                 jnp.zeros((t, h, pad), c.dtype)], axis=-1)
        entry = jnp.concatenate(
            [ckv[:, None, :], k_pe, jnp.zeros((t, 1, pad), c.dtype)],
            axis=-1)
        return qf.reshape(t, h * c.latent_stored), entry

    def _latent_out(self, blk, o):
        """The walk's output ``o`` (T · heads, kv_latent), each head's
        softmax-weighted latents, through W_kvb's value part: (T,
        heads · v_head_dim)."""
        c = self.config
        h, dn, dv = c.n_heads, c.qk_nope_dim, c.v_head_dim
        with jax.named_scope("mla_absorb"):
            wv = blk["wkv_b"].astype(c.dtype).reshape(
                c.kv_latent, h, dn + dv)[..., dn:]
            y = jnp.einsum("thl,lhv->thv",
                           o.reshape(-1, h, c.kv_latent), wv,
                           preferred_element_type=jnp.float32)
        return y.astype(c.dtype).reshape(-1, h * dv)

    def _latent_mix(self, qf, entry, pool, state, append, q_lens,
                    q_starts, block_q, use_pallas, n_bufs):
        """A latent layer's append and walk: the step's entries into
        the layer's pool, then every head's absorbed attention through
        it. Returns ``(o (T · heads, kv_latent), pool)``."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )

        c = self.config
        with jax.named_scope("kv_append"):
            pool = append(pool, entry.astype(pool.dtype))
        with jax.named_scope("attn"):
            attend = (ragged_paged_attention if use_pallas
                      else ragged_paged_attention_xla)
            kw = dict(block_q=block_q, n_bufs=n_bufs, with_lse=False) \
                if use_pallas else {}
            o, _ = attend(
                qf.reshape(1, -1, c.latent_stored), pool, None,
                state.kv_lens, q_lens, q_starts, state.block_table,
                group=c.n_heads, scale=c.latent_softmax_scale,
                latent=(c.kv_latent, c.qk_rope_dim), **kw)
        return o[0], pool

    def _cp_ragged_attn(self, qp, kp, vp, state, q_lens, q_starts,
                        block_q, use_pallas, n_bufs, topologies):
        """Context-parallel attention: walk each cp shard's slice of
        the stacked pool with a TOPO_CP row descriptor (the frontier
        shift makes each shard's local causal mask exact against the
        GLOBAL positions it holds), then merge the per-shard (out, lse)
        partials with the cross-rank LSE-combine — the XLA body of the
        ``cp_decode.lse_combine`` wire contract. Shard r of the table
        columns/pool rows is sliced statically; its local kv length and
        shift derive from the traced global ``state.kv_lens``. A row
        fully resident on shard 0 merges bit-exactly to shard 0's out
        (every other shard's lse is NEG_INF), which keeps short-request
        streams byte-identical to a cp-free engine."""
        from triton_distributed_tpu.kernels.flash_decode import (
            combine_gqa_partials,
        )
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            TOPO_CP,
            topo_width,
        )

        cp = state.cp
        pps_loc = state.pages_per_seq // cp
        pool0 = kp["q"] if isinstance(kp, dict) else kp
        nps = pool0.shape[0] // cp
        s_loc = pps_loc * state.page
        slots = state.slots
        if topologies is None:
            w = topo_width(block_q)
            topologies = jnp.zeros((slots, 2 + 2 * w), jnp.int32)
        outs, lses = [], []
        for r in range(cp):
            kp_r = jax.tree.map(lambda a: a[r * nps:(r + 1) * nps], kp)
            vp_r = jax.tree.map(lambda a: a[r * nps:(r + 1) * nps], vp)
            cols = state.block_table[:, r * pps_loc:(r + 1) * pps_loc]
            table_r = jnp.where(cols >= 0, cols - r * nps, -1)
            lens_r = jnp.clip(state.kv_lens - r * s_loc, 0, s_loc)
            shift_r = jnp.maximum(state.kv_lens - r * s_loc, 0) - lens_r
            topo_r = (
                topologies.at[:, 0].set(TOPO_CP).at[:, 1].set(shift_r)
            )
            o_r, l_r = self._ragged_attn(
                qp, kp_r, vp_r,
                state.replace(
                    layers=(), block_table=table_r, kv_lens=lens_r
                ),
                q_lens, q_starts, block_q, use_pallas, n_bufs, topo_r,
                with_lse=True,
            )
            outs.append(o_r)
            lses.append(l_r)
        out, _ = combine_gqa_partials(
            jnp.stack(outs), jnp.stack(lses), out_dtype=qp.dtype
        )
        return out

    def _lightning_mix(self, q, k, v, state, li, q_lens, q_starts,
                       block_q, use_pallas):
        """A lightning layer's mixing of the packed step: q, k, v (T,
        heads · D) -> ``(o (T, heads · D) float32, the layer's new
        recurrent state)``; kernel or XLA twin
        (kernels/lightning_attention.py)."""
        from triton_distributed_tpu.kernels.lightning_attention import (
            lightning_attention,
            lightning_attention_xla,
        )

        c = self.config
        t = q.shape[0]
        heads = c.lightning_heads
        qh, kh, vh = (
            a.reshape(t, heads, c.head_dim).transpose(1, 0, 2)
            .astype(jnp.float32) for a in (q, k, v))
        mix = lightning_attention if use_pallas else lightning_attention_xla
        o, new = mix(qh, kh, vh, state.recurrent[li], state.kv_lens,
                     q_lens, q_starts, block_q=block_q)
        return o.transpose(1, 0, 2).reshape(t, heads * c.head_dim), new

    def _kda_inputs(self, blk, xn, pre, state, li, q_lens, q_starts):
        """A kda layer's inputs to the recurrence from the packed
        step's pre-activation rows ``pre`` (T, 3 · heads · D) float32:
        ``(q, k, v, g (T, heads, D) float32, beta (T, heads) float32,
        the layer's new convolution tail)``. The first ``kda_conv - 1``
        tokens of a span convolve with the slot's tail (zeros where the
        span starts at position 0); the span's last ``kda_conv - 1``
        pre-activation rows are the new tail."""
        scope = jax.named_scope
        c = self.config
        f32 = jnp.float32
        t, taps = pre.shape[0], c.kda_conv
        heads, d = c.kda_heads, c.head_dim
        held = state.recurrent[li][1]              # (slots, taps - 1, 3HD)
        with scope("kda_conv"):
            first = (state.kv_lens - q_lens) == 0
            tail = jnp.where(first[:, None, None], 0.0, held)
            w = blk["conv_w"].astype(f32)
            # every token from the rows before it IN THE STEP ...
            conv = w[taps - 1] * pre
            for m in range(1, taps):
                conv = conv + w[taps - 1 - m] * jnp.roll(pre, m, axis=0)
            # ... but for a span's first taps - 1 tokens, whose rows
            # before lie in the slot's tail: those (slots x (taps - 1)
            # rows, not the step's width) from the tail and the span's
            # own first rows, written over the others
            ahead = jnp.arange(taps - 1)[None]
            at = q_starts[:, None] + ahead
            ext = jnp.concatenate(
                [tail, pre[jnp.clip(at, 0, t - 1)]], axis=1)
            head = sum(w[i] * ext[:, i:i + taps - 1] for i in range(taps))
            conv = conv.at[
                jnp.where((q_lens > 0)[:, None], at, t).reshape(-1)
            ].set(head.reshape(-1, head.shape[-1]), mode="drop")
            src = q_lens[:, None] - (taps - 1) + ahead
            new_tail = jnp.where(
                (src >= 0)[..., None],
                pre[jnp.clip(q_starts[:, None] + src, 0, t - 1)],
                jnp.take_along_axis(
                    tail, jnp.clip(src + taps - 1, 0, taps - 2)[..., None],
                    axis=1))
            new_tail = jnp.where((q_lens > 0)[:, None, None], new_tail,
                                 held)
            q, k, v = (a.reshape(t, heads, d) for a in jnp.split(
                jax.nn.silu(conv), 3, axis=-1))
        with scope("kda_gates"):
            def l2norm(a):
                return a * jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

            dt = jnp.dot(self._dmm(xn, blk["wa_down"]),
                         blk["wa_up"].astype(c.dtype),
                         preferred_element_type=f32) \
                + blk["dt_bias"].astype(f32)
            g = -jnp.exp(blk["a_log"].astype(f32))[None, :, None] \
                * jax.nn.softplus(dt).reshape(t, heads, d)
            beta = c.kda_beta_scale * jax.nn.sigmoid(jnp.dot(
                xn, blk["wbeta"].astype(c.dtype),
                preferred_element_type=f32))
            q, k = l2norm(q), l2norm(k)
        return q, k, v, g, beta, new_tail

    def _kda_layer(self, blk, xn, state, li, q_lens, q_starts, block_q,
                   use_pallas):
        """A kda layer's mixer on the packed step's normed input
        ``xn``: ``(o (T, heads · D) float32, the layer's new (matrix,
        tail))``."""
        scope = jax.named_scope
        with scope("attn_proj"):
            # the pre-activation rows come out of the product in
            # float32: the convolution's tail keeps them
            pre = jnp.dot(xn, blk["wqkv"].astype(self.config.dtype),
                          preferred_element_type=jnp.float32)
            q, k, v, g, beta, tail = self._kda_inputs(
                blk, xn, pre, state, li, q_lens, q_starts)
        with scope("attn"), scope("kda_attn"):
            o, matrix = self._kda_mix(
                q, k, v, g, beta, state, li, q_lens, q_starts, block_q,
                use_pallas)
        return o, (matrix, tail)

    def _kda_mix(self, q, k, v, g, beta, state, li, q_lens, q_starts,
                 block_q, use_pallas):
        """A kda layer's mixing of the packed step: q, k, v, g (T,
        heads, D), beta (T, heads) -> ``(o (T, heads · D) float32, the
        layer's new state matrix)``; kernel or XLA twin
        (kernels/kda_attention.py)."""
        from triton_distributed_tpu.kernels.kda_attention import (
            kda_attention,
            kda_attention_xla,
        )

        t = q.shape[0]
        mix = kda_attention if use_pallas else kda_attention_xla
        o, new = mix(*(a.transpose(1, 0, 2) for a in (q, k, v, g)), beta.T,
                     state.recurrent[li][0], state.kv_lens, q_lens,
                     q_starts, block_q=block_q)
        return o.transpose(1, 0, 2).reshape(t, -1), new

    def _gate_out(self, blk, o, xn, heads):
        """The mixer's output before ``wo``: on a recurrent (lightning,
        kda) layer RMS-normed over each head (``config.out_norm``), then
        times the sigmoid gate of the layer's normed input (a kda
        layer's through its rank-``kda_rank`` pair)."""
        c = self.config
        t = o.shape[0]
        o = o.astype(jnp.float32)
        if "norm_o" in blk:
            o = self._rmsnorm(o.reshape(t, heads, c.head_dim),
                              blk["norm_o"]).reshape(t, -1)
        if "wg_down" in blk:
            gate = self._dmm(self._dmm(xn, blk["wg_down"]), blk["wg_up"])
        else:
            gate = self._dmm(xn, blk["wz"],
                             shard=self._attn_proj_shard[0])
        return (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
            c.dtype)

    def _attention_mix(self, li, q, k, v, pools, state, kv_shape,
                       append_global, append_ring, token_rows, token_pos,
                       q_lens, q_starts, topologies, block_q, use_pallas,
                       n_bufs, new_ckeys, index=None):
        """A softmax-attention layer of the packed step: append the
        step's K/V to the layer's pools, then attend through them:
        full causal, sliding-window over a ring (``append_ring``),
        block-sparse over the selected pages (``config.sparse_layers``)
        or over the tokens an indexer keeps (``index``: its ``(queries,
        head weights, key entries, append)`` of the step, under
        ``config.index_topk``). Returns ``(o (T, q_dim), k_pool,
        v_pool)``."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            pack_gqa_rows,
            unpack_gqa_rows,
        )

        scope = jax.named_scope
        c = self.config
        t = q.shape[0]
        kp, vp = pools
        k = k.reshape(kv_shape)
        v = v.reshape(kv_shape)
        is_window = append_ring is not None
        append_layer = append_ring if is_window else append_global
        with scope("kv_append"):
            if isinstance(kp, dict):
                from triton_distributed_tpu.kernels.flash_decode \
                    import quantize_kv

                k_new, v_new = (
                    dict(zip(("q", "scale"), quantize_kv(x)))
                    for x in (k, v)
                )
            else:
                k_new, v_new = k.astype(kp.dtype), v.astype(vp.dtype)
            kp, vp = append_layer(kp, vp, k_new, v_new)
            kp = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, self._serving_pool_sharding
                ), kp,
            )
            vp = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, self._serving_pool_sharding
                ), vp,
            )
            if li in c.sparse_layers:
                from triton_distributed_tpu.kernels.sparse_select import (
                    append_compressed,
                )

                # the compressed keys the step's tokens complete, from
                # the pool they were just appended to
                new_ckeys[li] = append_compressed(
                    state.ckeys[li], kp, state.block_table, state.kv_lens,
                    q_lens, kernel=c.sparse_kernel, stride=c.sparse_stride,
                    block_q=block_q)
            if index is not None:
                # the indexer's keys of the step's tokens, beside their
                # K/V: same table, same rows
                qi, wi, entry, append_index = index
                new_ckeys[li] = append_index(
                    state.ckeys[li], entry.astype(state.ckeys[li].dtype))
        with scope("attn"):
            qp = pack_gqa_rows(
                q.reshape(t, c.n_heads, c.head_dim), c.n_kv_heads
            )
            if li in c.sparse_layers:
                o = self._selected_attn(
                    qp, q, kp, vp, new_ckeys[li], state, token_rows,
                    token_pos, q_lens, q_starts, block_q, use_pallas,
                    n_bufs)
            elif index is not None:
                o = self._token_selected_attn(
                    qp, qi, wi, kp, vp, new_ckeys[li], state, token_rows,
                    token_pos, q_lens, q_starts, block_q, use_pallas)
            elif is_window:
                # the ring table in the block table's place, and
                # the walk bounded below by the window
                o = self._ragged_attn(
                    qp, kp, vp,
                    state.replace(layers=(),
                                  block_table=state.ring_table),
                    q_lens, q_starts, block_q, use_pallas, n_bufs,
                    topologies, window=c.window,
                )
            else:
                attn = (self._cp_ragged_attn if state.cp > 1
                        else self._ragged_attn)
                o = attn(
                    qp, kp, vp, state.replace(layers=()), q_lens,
                    q_starts, block_q, use_pallas, n_bufs,
                    topologies,
                )
            o = unpack_gqa_rows(o, c.n_heads).reshape(t, c.q_dim)
        return o, kp, vp

    def _selected_attn(self, qp, q, kp, vp, kc, state, token_rows,
                       token_pos, q_lens, q_starts, block_q, use_pallas,
                       n_bufs):
        """Block-sparse attention of one layer: choose each query
        position's blocks from the compressed keys (scope
        ``sparse_select``), then the ragged kernel's walk over the
        chosen pages, or its XLA twin with the same mask."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )
        from triton_distributed_tpu.kernels.sparse_select import (
            select_blocks,
        )

        c = self.config
        g = c.n_heads // c.n_kv_heads
        with jax.named_scope("sparse_select"):
            chosen = select_blocks(
                q.reshape(-1, c.n_heads, c.head_dim), kc,
                state.block_table, token_rows, token_pos, state.kv_lens,
                q_lens, q_starts, group=g, page=state.page,
                kernel=c.sparse_kernel,
                stride=c.sparse_stride, block=c.sparse_block,
                init_blocks=c.sparse_init_blocks, window=c.sparse_window,
                topk=c.sparse_topk, dense_len=c.sparse_dense_len)
        kw = dict(group=g, selected=chosen, select_block=c.sparse_block)
        if use_pallas:
            o, _ = ragged_paged_attention(
                qp, kp, vp, state.kv_lens, q_lens, q_starts,
                state.block_table, block_q=block_q, n_bufs=n_bufs,
                with_lse=False, **kw)
        else:
            o, _ = ragged_paged_attention_xla(
                qp, kp, vp, state.kv_lens, q_lens, q_starts,
                state.block_table, **kw)
        return o

    def _index_proj(self, blk, xn, rope):
        """The indexer's projections of normed rows ``xn`` (T, H),
        scope ``dsa_index_proj``: ``(qI (T, index_heads, index_dim), w
        (T, index_heads) float32, entry (T, 1, index_stored))``. qI
        and the key are rotated to their token's position (``rope``:
        the step's tables over ``index_dim``), the key layer-normed
        first; ``w`` carries ``index_heads^-0.5``; ``entry`` is what
        the pool caches, [key | zeros]. One product in float32, queries
        and key then in the compute dtype."""
        c = self.config
        t = xn.shape[0]
        j, di = c.index_heads, c.index_dim
        with jax.named_scope("dsa_index_proj"):
            y = jnp.dot(xn, blk["w_index"].astype(c.dtype),
                        preferred_element_type=jnp.float32)
            qi = y[:, :j * di].reshape(t, j, di)
            ki = y[:, j * di:(j + 1) * di]
            w = y[:, (j + 1) * di:] * j ** -0.5
            ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
            ki = ki * jax.lax.rsqrt(
                jnp.mean(ki * ki, axis=-1, keepdims=True) + c.norm_eps
            ) * blk["norm_ki"].astype(jnp.float32)
            qi = _rotate_half(qi, *rope).astype(c.dtype)
            ki = _rotate_half(ki[:, None, :], *rope).astype(c.dtype)
            entry = jnp.pad(
                ki, ((0, 0), (0, 0), (0, c.index_stored - di)))
        return qi, w, entry

    def _token_selected_attn(self, qp, qi, wi, kp, vp, ki_pool, state,
                             token_rows, token_pos, q_lens, q_starts,
                             block_q, use_pallas):
        """Attention of one layer over the tokens its indexer keeps:
        score every cached key of every batched row (scope
        ``dsa_scan``), keep each query position's ``index_topk`` best
        (``dsa_select``), walk the kept tokens (``dsa_walk``): the
        kernels of ``kernels/token_select.py`` or their XLA twins."""
        from triton_distributed_tpu.kernels import token_select as ts

        c = self.config
        scope = jax.named_scope
        g = c.n_heads // c.n_kv_heads
        scale = c.index_dim ** -0.5
        with scope("dsa_scan"):
            if use_pallas:
                scores = ts.index_scores(
                    qi, wi, ki_pool, state.kv_lens, q_lens, q_starts,
                    state.block_table, topk=c.index_topk, block_q=block_q,
                    scale=scale)
            else:
                scores = ts.index_scores_xla(
                    qi, wi, ki_pool, token_rows, state.block_table,
                    scale=scale)
        with scope("dsa_select"):
            sizes = dict(page=state.page, pps=state.pages_per_seq,
                         topk=c.index_topk)
            if use_pallas:
                words = ts.select_tokens(
                    scores, state.kv_lens, q_lens, q_starts, **sizes)
            else:
                words = ts.select_tokens_xla(scores, token_pos, **sizes)
        with scope("dsa_walk"):
            if use_pallas:
                o = ts.token_walk(
                    qp, kp, vp, words, state.kv_lens, q_lens, q_starts,
                    state.block_table, group=g, block_q=block_q)
                # (a padding token's rows are written by no block)
                live = jnp.repeat(token_pos >= 0, g)
                o = jnp.where(live[None, :, None], o, 0)
            else:
                o = ts.token_walk_xla(
                    qp, kp, vp, words, token_rows, state.block_table,
                    group=g)
        return o

    def kv_append_by_kernel(self, use_pallas: bool) -> bool:
        """Does a serving step append by the ``kernels/kv_append``
        kernel (True) or by its XLA twin, the row scatter? Decided by
        what is there, no option: a head-sharded pool's rows do not
        merge into page runs, and ``use_pallas=False`` is the engine's
        degraded twin (and the CPU references')."""
        return bool(use_pallas) and self.tp == 1

    @functools.cached_property
    def _kv_append_kernel(self):
        """``(units, kp, vp, k_new, v_new) -> (kp, vp)``: one layer's
        pool append through ``kernels/kv_append`` on every device's own
        copy of the (head-unsharded) pools — the shard_map the
        attention layer launches its kernel in, under one jit so that
        a step's layers trace and lower it once."""
        from jax.sharding import PartitionSpec as P

        from triton_distributed_tpu.kernels.kv_append import kv_append

        pool, new = P(None, self.tp_axis), P()
        if self.config.kv_quant is not None:
            pool, new = ({"q": p, "scale": p} for p in (pool, new))
        return jax.jit(jax.shard_map(
            kv_append,
            mesh=self.mesh,
            in_specs=(P(), pool, pool, new, new),
            out_specs=(pool, pool),
            check_vma=False,
        ))

    @functools.cached_property
    def _latent_append_kernel(self):
        """``(units, pool, new) -> pool``: one latent layer's append
        through ``kernels/kv_append`` (its one-pool launch), under one
        jit so that a step's layers trace and lower it once."""
        from triton_distributed_tpu.kernels.kv_append import kv_append

        return jax.jit(
            lambda units, pool, new: kv_append(
                units, pool, None, new, None)[0])

    def _latent_appender(self, state, token_rows, token_pos, q_starts,
                         q_lens, use_pallas):
        """``(pool, entries (T, 1, latent_stored)) -> pool`` for the
        step: the kernel over the step's (slot, page) runs, or its XLA
        twin, the row scatter (``use_pallas=False``)."""
        from triton_distributed_tpu.kernels.kv_append import (
            append_rows_xla,
            append_units,
        )

        t, page = token_pos.shape[0], state.page
        if self.kv_append_by_kernel(use_pallas):
            return functools.partial(
                self._latent_append_kernel,
                append_units(
                    q_starts, q_lens,
                    token_pos[jnp.clip(q_starts, 0, t - 1)],
                    state.block_table, page=page, t=t))
        pos_c = jnp.maximum(token_pos, 0)
        local_page = state.block_table[
            jnp.clip(token_rows, 0, state.slots - 1),
            jnp.clip(pos_c // page, 0, state.pages_per_seq - 1)]
        # padding tokens and unallocated entries land past the last row
        pid = jnp.where((token_pos >= 0) & (local_page >= 0), local_page,
                        state.npages)
        rows = pid * page + pos_c % page
        return lambda pool, new: append_rows_xla(pool, new[:, 0], rows)

    def step_rows_needed(self, q_starts, q_lens, block_q: int) -> int:
        """Host side: the least packed width ``T`` at which
        :meth:`serving_step` can run this batch at ``block_q`` — the
        largest ``q_starts[s] + block(s)`` over the batched rows,
        8-aligned, ``block(s)`` the tokens the attention launches of
        this model's layers move for a row of ``q_lens[s]`` (each
        kernel's own ``query_block_tokens``; the largest of the kinds
        of layer the model has). 0 for an empty batch."""
        from triton_distributed_tpu.kernels import (
            kda_attention,
            lightning_attention,
            ragged_paged_attention,
        )

        c = self.config
        blocks = []
        if len(c.recurrent_layers) < c.n_layers:
            # the softmax layers: latent in a latent model, else the
            # contiguous, windowed or selected walk
            blocks.append(ragged_paged_attention.query_block_tokens(
                q_lens, block_q, latent=bool(c.kv_latent)))
        if c.lightning_layers:
            blocks.append(
                lightning_attention.query_block_tokens(q_lens, block_q))
        if c.kda_layers:
            blocks.append(
                kda_attention.query_block_tokens(q_lens, block_q))
        ends = np.where(np.asarray(q_lens) > 0,
                        np.asarray(q_starts) + np.maximum.reduce(blocks), 0)
        return -(-int(ends.max(initial=0)) // 8) * 8

    def serving_step(self, params, state, tokens, token_rows, token_pos,
                     q_starts, q_lens, topologies=None, moe_state=None, *,
                     block_q: int = 8, use_pallas: bool = True,
                     n_bufs: int = 2, all_logits: bool = False):
        """One CONTINUOUS-BATCHING step: a ragged mixed batch of prefill
        chunks and decode tokens through every layer in one program.

        ``state``: :class:`ServingState` whose ``kv_lens`` already
        INCLUDE this step's tokens (the engine advances lengths at
        batch-assembly time); ``tokens``: (T,) packed token ids;
        ``token_rows``/``token_pos``: (T,) per-token slot id and global
        sequence position (pos < 0 marks padding tokens — their K/V
        writes are dropped); ``q_starts``/``q_lens``: (slots,) per-slot
        spans into the packed array (8-aligned starts, ``q_lens == 0``
        for slots not in this batch). THE WIDTH ``T`` is the caller's:
        the step reads it off ``tokens`` and every row-sized operation
        (projections, routing, dispatch, the append's quantize) is that
        wide. What the step asks of it is ``q_starts[s] + block(s) <=
        T`` for every slot IN the batch, ``block(s)`` the packed tokens
        its attention launches move for that row
        (:meth:`step_rows_needed` says the least such ``T``; the engine
        takes the narrowest width of its ladder that covers it). A
        ``q_lens == 0`` slot asks nothing where ``topologies`` is given:
        every launch then skips it, whatever its ``q_starts`` (without
        the operand the contiguous walk writes a garbage block there,
        and the caller parks it past every span). THE PACKING CONTRACT, which the
        pool append relies on: slot ``s``'s tokens are the ONE
        contiguous span ``[q_starts[s], q_starts[s] + q_lens[s])`` of
        the packed array, spans do not overlap, and they sit at
        consecutive sequence positions — ``token_rows == s`` and
        ``token_pos == token_pos[q_starts[s]] + arange(q_lens[s])`` on
        the span, ``token_pos < 0`` everywhere else
        (``ServingEngine._assemble`` packs exactly this, tree-verify
        rows included). Returns ``(logits (slots, vocab),
        state')`` — logits at each slot's LAST packed token (the
        next-token distribution for rows that finished a chunk at their
        prompt end, garbage for q_lens == 0 slots), plus ``moe_state'``
        (from :meth:`init_decode_state`: per-layer LL workspaces, so
        EP-MoE blocks run the fused transport BARRIER-FREE) when given,
        to thread into the next step.

        ``topologies``: optional (slots, 2+2W) int32 per-row attention-
        topology descriptors (kernels/ragged_paged_attention.py layout)
        shared by every layer's attention — TREE verify rows, shared-
        prefix aliasing, and the ``q_lens == 0`` kernel-side row skip
        all ride this operand; None keeps the pre-topology launch.

        Every new K/V token is written into the page pools FIRST and
        attention reads the updated pools (append-then-attend) — by the
        ``kernels/kv_append`` kernel, one (slot, page) run at a time,
        when ``use_pallas`` and the heads are unsharded (``tp == 1``);
        by its XLA twin, a row scatter, for head-sharded pools and
        ``use_pallas=False``. Both leave the same bytes. A
        prefill chunk's tokens attend each other causally through the
        pool, and under ``kv_quant`` they are attended in their stored
        int8 form — bit-consistent with every later step by
        construction."""
        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            pack_gqa_rows,
            unpack_gqa_rows,
        )

        # device scopes (``jax.named_scope``: one component of every
        # operation's ``op_name``, trace-time only): embed, attn_proj,
        # kv_append, attn, dense_ffn, lm_head here; moe_route,
        # moe_dispatch, moe_gemm, moe_combine in ops/moe.py. Others are
        # NESTED in those, so that a reading of "under none of the ten"
        # still covers them: qk_rope (q/k norm + rotation), out_gate and
        # a kda layer's kda_conv / kda_gates inside attn_proj,
        # linear_attn / kda_attn inside attn, shared_expert inside
        # dense_ffn
        scope = jax.named_scope
        c = self.config
        t = tokens.shape[0]
        page = state.page
        npages = state.npages
        with scope("embed"):
            x = params["embed"][tokens].astype(c.dtype)      # (T, H)
            if c.embed_scale != 1.0:
                x = x * c.embed_scale

        def add(x, y):
            # the residual stream takes a sub-layer's output times
            # ``residual_scale`` (muP depth scaling; 1 = plain add)
            if c.residual_scale != 1.0:
                y = y * c.residual_scale
            return x + y.astype(x.dtype)

        def appender(table, pool_pages):
            """``(kv_shape, append_layer)`` for the pools ``table``
            addresses (the block table, or a ring table)."""
            if self.kv_append_by_kernel(use_pallas):
                # the kernel: one read-modify-write per (slot, page)
                # run of the packing contract, described ONCE a step
                # from operands the step already has
                from triton_distributed_tpu.kernels.kv_append import (
                    append_units,
                )

                return (t, c.n_kv_heads, c.head_dim), functools.partial(
                    self._kv_append_kernel,
                    append_units(
                        q_starts, q_lens,
                        token_pos[jnp.clip(q_starts, 0, t - 1)],
                        table, page=page, t=t,
                    ),
                )
            valid = token_pos >= 0
            pos_c = jnp.maximum(token_pos, 0)
            local_page = table[
                jnp.clip(token_rows, 0, state.slots - 1),
                jnp.clip(pos_c // page, 0, state.pages_per_seq - 1),
            ]
            # padding tokens (and unallocated -1 table entries)
            # scatter out of pool — JAX OOB-scatter drops them
            pool_idx = jnp.where(
                valid & (local_page >= 0), local_page, pool_pages
            )
            pi = pool_idx[:, None]
            hi = jnp.arange(c.n_kv_heads)[None, :]
            oi = (pos_c % page)[:, None]
            if self.tp == 1:
                # heads unsharded: append as ONE-index row scatters
                # over the pool viewed as (npages·Hkv·page, D) rows.
                # XLA flattens the three-index scatter to exactly
                # this anyway, but the scatter its pass creates
                # drops the operation's metadata (the append showed
                # in a profile with no op_name, nameless); the same
                # flattening done here compiles to the same two
                # in-place fusions and keeps ``kv_append`` on them.
                # An out-of-pool page still lands past the last row
                # and is dropped.
                from triton_distributed_tpu.kernels.kv_append import (
                    append_rows_xla,
                )

                kv_shape = (t * c.n_kv_heads, c.head_dim)
                append = functools.partial(
                    append_rows_xla,
                    rows=((pi * c.n_kv_heads + hi) * page + oi)
                    .reshape(-1),
                )
            else:
                # head-sharded pools keep the three-index form:
                # their rows do not merge into one sharded dimension
                kv_shape = (t, c.n_kv_heads, c.head_dim)

                def append(pool, new):
                    return pool.at[pi, hi, oi].set(new)

            def append_layer(kp, vp, k_new, v_new):
                # pools and new rows are arrays, or {"q", "scale"}
                return (jax.tree.map(append, kp, k_new),
                        jax.tree.map(append, vp, v_new))

            return kv_shape, append_layer

        windowed = state.window_layers
        # padding rows are no token's: an expert layer is handed their
        # assignments masked
        is_token = token_pos >= 0
        with scope("kv_append"):
            # one description of the step's append per KIND of pool:
            # the global layers' by the block table, the window layers'
            # by the ring table (same kernel, same packing contract),
            # a latent model's by the block table, one stream
            if c.kv_latent:
                append_latent = self._latent_appender(
                    state, token_rows, token_pos, q_starts, q_lens,
                    use_pallas)
            else:
                kv_shape, append_global = appender(
                    state.block_table, npages)
            if windowed:
                _, append_ring = appender(
                    state.ring_table, state.slots * state.ring)
            if c.index_topk:
                # the indexer keys' pool: one entry a token, the latent
                # pool's one-stream append by the block table
                append_index = self._latent_appender(
                    state, token_rows, token_pos, q_starts, q_lens,
                    use_pallas)
        if c.rope_layers or c.kv_latent:
            with scope("attn_proj"), scope("qk_rope"):
                rope = self._rope_tables(token_pos)
        if c.index_topk:
            with scope("attn_proj"), scope("dsa_index_proj"):
                index_rope = self._rope_tables(token_pos, c.index_dim)

        new_layers = []
        new_states = None if moe_state is None else list(moe_state)
        new_recurrent, new_ckeys = list(state.recurrent), list(state.ckeys)
        lightning, kda = c.lightning_layers, c.kda_layers
        qkv_sh, wo_sh = self._attn_proj_shard
        for li, (blk, pools) in enumerate(
            zip(params["blocks"], state.layers)
        ):
            hq, hkv = c.layer_heads(li)
            if c.kv_latent:
                # latent attention: project (absorbed), append the
                # step's entries, walk them, un-absorb
                with scope("attn_proj"):
                    xn = self._rmsnorm(x, blk["norm_attn"])
                    qf, entry = self._latent_qkv(blk, xn, rope)
                o, pool = self._latent_mix(
                    qf, entry, pools[0], state, append_latent, q_lens,
                    q_starts, block_q, use_pallas, n_bufs)
                new_layers.append((pool, None))
                with scope("attn_proj"):
                    x = add(x, self._dmm(self._latent_out(blk, o),
                                         blk["wo"]))
            else:
                with scope("attn_proj"):
                    xn = self._rmsnorm(x, blk["norm_attn"])
                    qkv = self._dmm(xn, blk["wqkv"], shard=qkv_sh)  # (T, qkv)
                    q, k, v = jnp.split(
                        qkv, [hq * c.head_dim, (hq + hkv) * c.head_dim],
                        axis=-1,
                    )
                    if c.qk_norm or li in c.rope_layers:
                        with scope("qk_rope"):
                            q, k = self._qk_norm_rope(
                                blk, q, k,
                                rope if li in c.rope_layers else None)
                if li in kda:
                    # (a kda layer makes its own inputs from ``xn``; the
                    # q, k, v above go unused and out of the program.
                    # The lines above stay the parent's to the letter: an
                    # ``if`` round them cost the accepted cells ~0.8 s
                    # of warm tracing on the chip: PERF.md section 6,
                    # PR 41)
                    new_layers.append(None)
                    o, new_recurrent[li] = self._kda_layer(
                        blk, xn, state, li, q_lens, q_starts, block_q,
                        use_pallas)
                elif li in lightning:
                    new_layers.append(None)
                    with scope("attn"), scope("linear_attn"):
                        o, new_recurrent[li] = self._lightning_mix(
                            q, k, v, state, li, q_lens, q_starts, block_q,
                            use_pallas)
                elif c.index_topk:
                    with scope("attn_proj"):
                        index = self._index_proj(blk, xn, index_rope) + (
                            append_index,)
                    o, kp, vp = self._attention_mix(
                        li, q, k, v, pools, state, kv_shape, append_global,
                        None, token_rows, token_pos, q_lens, q_starts,
                        topologies, block_q, use_pallas, n_bufs, new_ckeys,
                        index=index)
                    new_layers.append((kp, vp))
                else:
                    o, kp, vp = self._attention_mix(
                        li, q, k, v, pools, state, kv_shape, append_global,
                        append_ring if li in windowed else None, token_rows,
                        token_pos, q_lens, q_starts, topologies, block_q,
                        use_pallas, n_bufs, new_ckeys)
                    new_layers.append((kp, vp))
                with scope("attn_proj"):
                    if c.out_gate:
                        with scope("out_gate"):
                            o = self._gate_out(blk, o, xn, hq)
                    x = add(x, self._dmm(o.astype(c.dtype), blk["wo"],
                                         shard=wo_sh))
            if "up" in blk:
                with scope("dense_ffn"):
                    xn = self._rmsnorm(x, blk["norm_mlp"])
                    x = add(x, self._dense_mlp(xn, blk["up"], blk["down"]))
            elif c.moe == "ep":
                with scope("moe_route"):
                    xn = self._rmsnorm(x, blk["norm_mlp"])
                st = None if moe_state is None else moe_state[li]
                y, st = self._decode_moe_ep(blk, xn, st, row_mask=is_token)
                with scope("moe_combine"):
                    x = x + y.astype(x.dtype)
                if "shared_up" in blk:
                    # the shared expert: dense, every token, counted
                    # once whatever share of the routed experts is here
                    with scope("dense_ffn"), scope("shared_expert"):
                        x = x + self._dense_mlp(
                            xn, blk["shared_up"], blk["shared_down"])
                if new_states is not None:
                    new_states[li] = st
            else:
                # the non-EP expert branch is ``moe_gemm`` whole: router,
                # top-k and the gathered expert weights in one einsum each
                with scope("moe_gemm"):
                    xn = self._rmsnorm(x, blk["norm_mlp"])
                    logits_r = xn.astype(jnp.float32) @ blk["router"]
                    w, ids = mu.select_experts(logits_r, c.topk)
                    y = jnp.zeros_like(xn, dtype=jnp.float32)
                    for tt in range(c.topk):
                        hh = jax.nn.silu(jnp.einsum(
                            "bh,bhf->bf", xn,
                            blk["moe_up"][ids[:, tt]].astype(c.dtype),
                        ))
                        y += w[:, tt:tt + 1] * jnp.einsum(
                            "bf,bfh->bh", hh,
                            blk["moe_down"][ids[:, tt]].astype(c.dtype),
                        ).astype(jnp.float32)
                    x = x + y.astype(x.dtype)
        with scope("lm_head"):
            x = self._rmsnorm(x, params["norm_f"])
            if c.logit_divisor != 1.0:
                x = x / c.logit_divisor
            if all_logits:
                # logits at EVERY packed position — the speculative
                # verify pass needs the next-token distribution after
                # each draft token, not just each slot's frontier.
                # Per-token matmul rows are independent, so
                # logits[q_starts[s]+j] is bit-identical to what a
                # non-speculative step would have produced at that
                # sequence position.
                x_last = x                                   # (T, H)
            else:
                last_idx = jnp.clip(q_starts + q_lens - 1, 0, t - 1)
                x_last = x[last_idx]                         # (slots, H)
            if isinstance(params["lm_head"], dict):
                logits = self._dmm(
                    x_last, params["lm_head"], out_dtype=jnp.float32,
                    act_quant=False,
                )
            else:
                logits = x_last.astype(jnp.float32) @ params["lm_head"]
        new_state = state.replace(
            layers=tuple(new_layers), recurrent=tuple(new_recurrent),
            ckeys=tuple(new_ckeys))
        if moe_state is None:
            return logits, new_state
        return logits, new_state, new_states

    @functools.cached_property
    def _serving_jit(self):
        # donate the ServingState (the pool append aliases in place) and
        # the LL MoE workspaces (the barrier-free protocol needs the
        # SAME physical buffers across steps)
        @functools.partial(
            jax.jit, static_argnums=(9, 10, 11), donate_argnums=(1, 8)
        )
        def step(params, state, tokens, token_rows, token_pos, q_starts,
                 q_lens, topologies, moe_state, block_q, use_pallas,
                 n_bufs=2):
            return self.serving_step(
                params, state, tokens, token_rows, token_pos, q_starts,
                q_lens, topologies, moe_state, block_q=block_q,
                use_pallas=use_pallas, n_bufs=n_bufs,
            )

        return step

    @functools.cached_property
    def _serving_all_logits_jit(self):
        # the speculative engine's serving step: identical batch
        # contract, but logits come back for EVERY packed position
        # ((T, vocab), not (slots, vocab)) so the engine can read the
        # verify row's distribution after each draft token. Same
        # donation discipline as `_serving_jit`.
        @functools.partial(
            jax.jit, static_argnums=(9, 10, 11), donate_argnums=(1, 8)
        )
        def step(params, state, tokens, token_rows, token_pos, q_starts,
                 q_lens, topologies, moe_state, block_q, use_pallas,
                 n_bufs=2):
            return self.serving_step(
                params, state, tokens, token_rows, token_pos, q_starts,
                q_lens, topologies, moe_state, block_q=block_q,
                use_pallas=use_pallas, n_bufs=n_bufs, all_logits=True,
            )

        return step
