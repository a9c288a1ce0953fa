"""MoE routing utilities: expert selection, token sort, block alignment.

Reference: ``select_experts`` (python/triton_dist/kernels/nvidia/
moe_reduce_rs.py:180-213, softmax+topk routing), ``full_moe_align_block_
size`` (moe_reduce_rs.py:87-179) and the CUDA ``moe_ag_scatter_align_
block_size`` (csrc/lib/moe_utils.cu:61-356): sort the (token, expert)
pairs by expert and pad each expert's segment to a GEMM block boundary so
a grouped GEMM can walk whole blocks with a single expert id per block.

TPU re-design: the alignment is a handful of cumsums/scatters over a few
thousand int32s — XLA fuses it into the surrounding program, so it stays
jnp (no custom kernel needed; the reference needed CUDA because torch ops
for this were the bottleneck at sub-microsecond latencies). Shapes are
static: the padded capacity is the worst case ``M·k`` rounded up plus one
partial block per expert, and unused slots carry a sentinel row id that
gathers a zero row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def round_up_to_block(x, block: int):
    """Round ``x`` (int or int array) up to a multiple of ``block``."""
    return ((x + block - 1) // block) * block


def exclusive_cumsum(x):
    """[0, x0, x0+x1, ...] — segment start offsets from segment sizes."""
    return jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(x)[:-1].astype(jnp.int32)]
    )


def select_experts(gate_logits, topk: int, *, renormalize: bool = True):
    """Softmax router → (weights (M, k) f32, expert ids (M, k) int32).

    ≡ select_experts (moe_reduce_rs.py:180-213).
    """
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, topk)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


def select_experts_sigmoid_bias(gate_logits, bias, topk: int, *,
                                scale: float = 1.0, groups: int = 1,
                                topk_groups: int = 1):
    """Sigmoid router with a selection bias (the DeepSeek-V3 family's
    ``scoring_func: sigmoid``) → (weights (M, k) f32, expert ids (M, k)
    int32): scores ``s = sigmoid(logits)``; the top-k of ``s + bias``
    are SELECTED, and weighted by their own ``s`` renormalised over the
    k and times ``scale`` (``routed_scaling_factor``). The bias only
    moves the choice.

    ``groups`` > 1 is the GROUP-LIMITED choice (``topk_method:
    noaux_tc``): the experts lie in ``groups`` equal runs, a group
    scores the sum of its two largest ``s + bias``, and the top-k is
    taken among the experts of the ``topk_groups`` best groups only.
    Ties go to the lower group and the lower expert (``lax.top_k``)."""
    s = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    if groups > 1:
        m, e = c.shape
        best2, _ = jax.lax.top_k(c.reshape(m, groups, e // groups), 2)
        _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_groups)
        in_kept = jnp.any(
            jnp.arange(groups)[None, :, None] == kept[:, None, :], axis=-1)
        c = jnp.where(jnp.repeat(in_kept, e // groups, axis=1), c,
                      -jnp.inf)
    _, ids = jax.lax.top_k(c, topk)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return w, ids.astype(jnp.int32)


def held_assignments(weights, ids, first: int, held: int, rows=None):
    """One chip's SHARE of routed assignments: ``ids`` over the whole
    router become ids local to the ``held`` experts that start at
    expert ``first``; every other assignment becomes the sentinel
    ``held`` with weight exactly 0 — what ``ops.moe`` sorts to the tail
    and neither stages nor multiplies. ``rows`` (M,) bool: the rows that
    are tokens; every assignment of another row (a step's padding) is
    the sentinel too. Flat ``(M·k,)`` pairs."""
    local = ids.astype(jnp.int32) - first
    mine = (local >= 0) & (local < held)
    if rows is not None:
        mine &= rows[:, None]
    return (jnp.where(mine, local, held).reshape(-1),
            jnp.where(mine, weights.astype(jnp.float32), 0.0).reshape(-1))


def aligned_capacity(total: int, num_experts: int, block_m: int) -> int:
    """Static worst-case padded length: every expert wastes < block_m."""
    return round_up_to_block(total + num_experts * (block_m - 1), block_m)


def moe_align_block_size(topk_ids, num_experts: int, block_m: int, *,
                         positions: bool = False):
    """Sort (token, slot) pairs by expert and pad segments to block_m.

    topk_ids: (M, k) int32, every id in [0, num_experts). Returns:
      sorted_token_ids: (cap,) int32 — flat source index ``row*k + slot``
        per padded position, sentinel ``M*k`` for padding (gather a zero
        row there);
      block_expert: (cap//block_m,) int32 — owning expert of each block;
      splits: (num_experts,) int32 — true token count per expert;
      with ``positions``, also (M*k,) int32 — each flat source index's
        padded position (the inverse of ``sorted_token_ids``: what an
        un-sort gathers by).
    ≡ moe_ag_scatter_align_block_size (csrc/lib/moe_utils.cu:61-356).

    ONE sort; counts, offsets and block owners by COMPARISON against
    the expert ids (a fused compare-and-sum over pairs × experts,
    ~1 µs on the chip), not by a scatter-add, a gather from a table of
    offsets or a bisection: each of those is serialized there, 10–20 µs
    for a few thousand int32 and a ``while`` for the bisection —
    several times the sort itself (PERF.md §6, PR 40).
    """
    m, k = topk_ids.shape
    total = m * k
    cap = aligned_capacity(total, num_experts, block_m)
    flat = topk_ids.reshape(-1).astype(jnp.int32)
    experts = jnp.arange(num_experts, dtype=jnp.int32)

    splits = jnp.sum(flat[:, None] == experts[None, :], axis=0,
                     dtype=jnp.int32)
    padded = round_up_to_block(splits, block_m)
    # the one sort carries each id's flat source index (a stable argsort)
    sorted_experts, order = jax.lax.sort(
        (flat, jnp.arange(total, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    # a sorted pair's padded position: its sorted position plus the
    # alignment padding of the experts before its own
    pad_before = exclusive_cumsum(padded - splits)
    dest = jnp.arange(total, dtype=jnp.int32) + jnp.sum(
        jnp.where(sorted_experts[:, None] == experts[None, :],
                  pad_before[None, :], 0), axis=1, dtype=jnp.int32)

    sorted_token_ids = jnp.full((cap,), total, jnp.int32).at[dest].set(order)

    nblocks = cap // block_m
    block_start = jnp.arange(nblocks, dtype=jnp.int32) * block_m
    block_expert = jnp.searchsorted(
        jnp.cumsum(padded), block_start, side="right",
        method="compare_all",
    ).astype(jnp.int32)
    block_expert = jnp.clip(block_expert, 0, num_experts - 1)
    if not positions:
        return sorted_token_ids, block_expert, splits
    inverse = jnp.zeros((total,), jnp.int32).at[order].set(dest)
    return sorted_token_ids, block_expert, splits, inverse


def gather_sorted(x, sorted_token_ids, topk: int):
    """Rows of ``x`` (M, H) in padded-sorted order, zeros at padding.

    ``sorted_token_ids`` indexes the flattened (M·k) token-slot space;
    the row is ``id // k``.
    """
    total = x.shape[0] * topk
    rows = jnp.clip(sorted_token_ids // topk, 0, x.shape[0] - 1)
    valid = sorted_token_ids < total
    return jnp.where(valid[:, None], x[rows], 0)


def scatter_combine(y_sorted, sorted_token_ids, weights, m: int):
    """Weighted scatter-add of expert outputs back to token order.

    y_sorted: (cap, H) grouped-GEMM output in padded-sorted order;
    weights: (M, k) router weights. Returns (M, H) — each token is the
    weighted sum of its k expert outputs (≡ the topk-reduce stage of
    moe_reduce_rs.py:468-545).
    """
    k = weights.shape[1]
    total = m * k
    valid = sorted_token_ids < total
    safe = jnp.where(valid, sorted_token_ids, 0)
    w = weights.reshape(-1)[safe] * valid                      # (cap,)
    rows = jnp.where(valid, safe // k, m)                      # sentinel → m
    out = jnp.zeros((m + 1, y_sorted.shape[1]), jnp.float32)
    out = out.at[rows].add(y_sorted.astype(jnp.float32) * w[:, None])
    return out[:m]
