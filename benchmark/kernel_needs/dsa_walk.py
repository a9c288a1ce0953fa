"""What one engine step needs of the attention WALK over the tokens an
indexer keeps (``as_run.index_*``): bytes it has to read and operations
it has to do, both lower bounds. Signature and arguments as
``ragged_paged_attention.step_needs``.

A query at position ``i`` attends ``min(i + 1, index_topk)`` keys (one
choice for all the heads): ``4 x n_heads x head_dim`` operations a pair.
A row has to read K and V of the tokens its positions attend; positions
of one row may share kept tokens, so a row is charged at least what its
LAST position needs and at most its context: ``min(cursor,
index_topk)`` tokens of ``2 x n_kv_heads x head_dim`` values. The needs
count the WORK, whatever launch does it: a walk that reads every page
of the context is charged its time against the kept tokens' bytes.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes = config["as_run"]
    topk, layers = int(sizes["index_topk"]), int(sizes["n_layers"])
    token_bytes = (2 * sizes["n_kv_heads"] * sizes["head_dim"]
                   * int(config["kv_bytes_per_element"]))
    pair_ops = 4.0 * sizes["n_heads"] * sizes["head_dim"]
    tokens = pairs = 0
    for take, cursor in rows:
        tokens += min(cursor, topk)
        for i in range(cursor - take, cursor):
            pairs += min(i + 1, topk)
    return layers * tokens * token_bytes, layers * pairs * pair_ops
