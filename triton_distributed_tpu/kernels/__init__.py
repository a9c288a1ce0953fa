"""Kernel library (L4): collective and compute-communication-overlap kernels.

Reference: python/triton_dist/kernels/nvidia/ (see SURVEY.md §2.3).

The serving step's mixers are imported where they are launched
(``models/transformer.py``), not re-exported here:
``ragged_paged_attention`` (softmax over paged K/V: causal, windowed,
selected pages, latent), ``kv_append``, ``sparse_select``,
``lightning_attention`` (linear attention, one scalar decay a head:
``S_t = lam S_{t-1} + k_t^T v_t``) and ``kda_attention`` (the gated
delta rule, a decay a channel and token and a correction term: ``S_t =
(I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``), each a Pallas
kernel with an XLA twin of the same arguments.
"""

from triton_distributed_tpu.kernels.ag_gemm import (
    AGGemmMethod,
    ag_gemm,
    resolve_ag_gemm_wire,
)
from triton_distributed_tpu.kernels.all_to_all import all_to_all, all_to_all_xla
from triton_distributed_tpu.kernels.allgather import (
    PersistentLLAllGather,
    all_gather,
)
from triton_distributed_tpu.kernels.flash_decode import (
    combine_partials,
    gqa_fwd_batch_decode,
    gqa_fwd_batch_decode_q8,
    gqa_fwd_batch_decode_q8_xla,
    gqa_fwd_batch_decode_xla,
    paged_gqa_fwd_batch_decode,
    paged_gqa_fwd_batch_decode_q8,
    paged_gqa_fwd_batch_decode_q8_xla,
    paged_gqa_fwd_batch_decode_xla,
    quantize_kv,
    sp_gqa_fwd_batch_decode,
    sp_gqa_fwd_batch_decode_device,
    sp_gqa_fwd_batch_decode_q8,
    sp_gqa_fwd_batch_decode_q8_device,
    sp_paged_gqa_fwd_batch_decode,
    sp_paged_gqa_fwd_batch_decode_device,
    sp_paged_gqa_fwd_batch_decode_q8,
)
from triton_distributed_tpu.kernels.gemm_rs import (
    GemmRSMethod,
    gemm_rs,
    resolve_gemm_rs_wire,
)
from triton_distributed_tpu.kernels.group_gemm import (
    grouped_matmul,
    grouped_matmul_xla,
)
from triton_distributed_tpu.kernels.moe_all_to_all import (
    MoEAllToAllContext,
    create_all_to_all_context,
    fast_all_to_all,
)
from triton_distributed_tpu.kernels.moe_utils import (
    moe_align_block_size,
    select_experts,
)
from triton_distributed_tpu.kernels.ring_attention import (
    ring_attention,
    ulysses_attention,
)
from triton_distributed_tpu.kernels.reduce_scatter import (
    reduce_scatter,
    reduce_scatter_xla,
)

__all__ = [
    "PersistentLLAllGather",
    "all_gather",
    "reduce_scatter",
    "reduce_scatter_xla",
    "all_to_all",
    "all_to_all_xla",
    "ag_gemm",
    "AGGemmMethod",
    "resolve_ag_gemm_wire",
    "gemm_rs",
    "GemmRSMethod",
    "resolve_gemm_rs_wire",
    "gqa_fwd_batch_decode",
    "gqa_fwd_batch_decode_xla",
    "paged_gqa_fwd_batch_decode",
    "paged_gqa_fwd_batch_decode_xla",
    "sp_gqa_fwd_batch_decode",
    "sp_gqa_fwd_batch_decode_device",
    "sp_paged_gqa_fwd_batch_decode",
    "sp_paged_gqa_fwd_batch_decode_device",
    "combine_partials",
    "select_experts",
    "moe_align_block_size",
    "grouped_matmul",
    "grouped_matmul_xla",
    "MoEAllToAllContext",
    "create_all_to_all_context",
    "fast_all_to_all",
    "ring_attention",
    "ulysses_attention",
]
