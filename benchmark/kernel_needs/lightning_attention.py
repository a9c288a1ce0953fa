"""What one engine step needs of the ``lightning_attention`` kernel in
the model's lightning layers (``as_run.layer_mixer``): bytes and
operations, both lower bounds. Signature and arguments as
``ragged_paged_attention.step_needs``.

A batched row reads and writes its slot's recurrent state (``heads x
head_dim x head_dim`` float32) and its q, k, v rows, and writes its o
rows (float32); the chunk form of a span of ``n`` positions does, a
head, the ``n x n`` scores and their product with v (``4 n^2 d``), the
carried state's share of the outputs and the state's update (``4 n
d^2``).
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes = config["as_run"]
    heads, d = int(sizes["lightning_heads"]), int(sizes["head_dim"])
    layers = sum(1 for k in sizes["layer_mixer"] if k == "lightning")
    by = ops = 0
    for take, _ in rows:
        by += 2 * heads * d * d * 4 + 4 * take * heads * d * 4
        ops += heads * (4.0 * take * take * d + 4.0 * take * d * d)
    return layers * by, layers * ops
