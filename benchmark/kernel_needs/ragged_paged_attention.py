"""What one engine step needs of the ragged paged-attention kernel:
bytes it has to read and operations it has to do, both lower bounds.

A layer-metric file names ``step_needs`` under ``needs``; the roofline
reader calls it once per traced step. A roofline metric of another
kernel names a function of the same signature in a file a later PR adds
beside this one:

    step_needs(config, rows) -> (bytes, operations)

``config`` is the cell's configuration file (``as_run`` sizes,
``engine`` geometry, ``kv_bytes_per_element``); ``rows`` the step's
batched rows as the driver saw them, ``(take, cursor)``: the row
advanced ``take`` positions and stands at ``cursor`` after the step.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes, page = config["as_run"], int(config["engine"]["page"])
    # K and V of one resident page over all layers, scales not counted
    page_bytes = (sizes["n_kv_heads"] * page * sizes["head_dim"] * 2
                  * int(config["kv_bytes_per_element"]) * sizes["n_layers"])
    # one multiply-add for the score and one for the value, per head
    # and element of the head, per attended (query, key) pair
    pair_ops = 4.0 * sizes["n_heads"] * sizes["head_dim"] * sizes["n_layers"]
    pages = pairs = 0
    for take, cursor in rows:
        # the row's resident pages (those holding positions < cursor)
        # are read once; each new position attends itself and all before
        pages += -(-cursor // page)
        pairs += take * (cursor - take) + take * (take + 1) // 2
    return pages * page_bytes, pairs * pair_ops
