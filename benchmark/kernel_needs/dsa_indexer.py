"""What one engine step needs of the indexer's SCAN (a token-level
learned selection, ``as_run.index_*``): bytes it has to read and
operations it has to do, both lower bounds. Signature and arguments as
``ragged_paged_attention.step_needs``.

A batched row whose context is at most ``index_topk`` selects
everything and is not scanned. A longer row reads the indexer key of
every token of its context once a layer (``index_dim`` values as
needed: the pool's stored width, whole lane tiles, is the program's
choice and not charged), and every one of its query positions scores
every key at or before it: ``2 x index_heads x index_dim`` operations a
pair. The needs count the WORK, whatever launch does it.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes = config["as_run"]
    topk, heads, dim = (int(sizes["index_topk"]), int(sizes["index_heads"]),
                        int(sizes["index_dim"]))
    layers = int(sizes["n_layers"])
    key_bytes = dim * int(config["kv_bytes_per_element"])
    pair_ops = 2.0 * heads * dim
    keys = pairs = 0
    for take, cursor in rows:
        if cursor <= topk:
            continue
        keys += cursor
        first = cursor - take
        pairs += take * (first + 1) + take * (take - 1) // 2
    return layers * keys * key_bytes, layers * pairs * pair_ops
