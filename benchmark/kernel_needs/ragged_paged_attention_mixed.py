"""What one engine step needs of the ragged paged-attention kernel in a
model whose layers are of two kinds (``as_run.layer_attn``): bytes it
has to read and operations it has to do, both lower bounds. Signature
and arguments as ``ragged_paged_attention.step_needs``.

A ``full`` layer is counted as that file counts every layer: the row's
resident pages read once, each new position attending itself and all
before it. A ``sliding`` layer (window ``w``) reads only the pages in
which a key lies that some new position of the row still sees — from
the page of position ``cursor - take - w + 1`` on — and a new position
at ``p`` attends ``min(w, p + 1)`` keys.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes, page = config["as_run"], int(config["engine"]["page"])
    kinds, window = sizes["layer_attn"], int(sizes["window"])
    n_window = sum(1 for k in kinds if k == "sliding")
    n_full = len(kinds) - n_window
    # K and V of one resident page of one layer, scales not counted
    page_bytes = (sizes["n_kv_heads"] * page * sizes["head_dim"] * 2
                  * int(config["kv_bytes_per_element"]))
    # one multiply-add for the score and one for the value, per head
    # and element of the head, per attended (query, key) pair
    pair_ops = 4.0 * sizes["n_heads"] * sizes["head_dim"]
    pages = pairs = 0
    for take, cursor in rows:
        held = -(-cursor // page)
        first = cursor - take                 # the first new position
        pages += n_full * held
        pairs += n_full * (take * first + take * (take + 1) // 2)
        pages += n_window * (held - max(first - window + 1, 0) // page)
        pairs += n_window * sum(
            min(window, p + 1) for p in range(first, cursor))
    return pages * page_bytes, pairs * pair_ops
