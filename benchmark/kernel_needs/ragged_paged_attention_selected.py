"""What one engine step needs of the ragged kernel's SELECTED walk
(block-sparse attention, ``as_run.sparse_*``) in the model's sparse
layers: bytes it has to read and operations it has to do, both lower
bounds. Signature and arguments as ``ragged_paged_attention.step_needs``.

A query at position ``i`` below ``sparse_dense_len`` attends every key
``j <= i``; from there on the keys ``j <= i`` of ``sparse_topk`` blocks
of ``sparse_block`` tokens, of which the last holds ``i`` itself: at
least ``(topk - 1) * block + i % block + 1`` pairs, in at least
``ceil(topk * block / page)`` pages (two chosen blocks may share a
page). A row's walk covers at least what its LAST position needs.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes, page = config["as_run"], int(config["engine"]["page"])
    block, topk = int(sizes["sparse_block"]), int(sizes["sparse_topk"])
    dense = int(sizes["sparse_dense_len"])
    layers = sum(1 for k in sizes["layer_mixer"] if k == "attention")
    page_bytes = (sizes["n_kv_heads"] * page * sizes["head_dim"] * 2
                  * int(config["kv_bytes_per_element"]))
    pair_ops = 4.0 * sizes["n_heads"] * sizes["head_dim"]
    pages = pairs = 0
    for take, cursor in rows:
        held = -(-cursor // page)
        pages += held if cursor <= dense else min(
            held, -(-topk * block // page))
        for i in range(cursor - take, cursor):
            pairs += i + 1 if i < dense else min(
                i + 1, (topk - 1) * block + i % block + 1)
    return layers * pages * page_bytes, layers * pairs * pair_ops
