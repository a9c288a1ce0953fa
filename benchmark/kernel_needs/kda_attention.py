"""What one engine step needs of the ``kda_attention`` kernel in the
model's gated delta-rule layers (``as_run.layer_mixer`` == "kda"):
bytes and operations, both lower bounds. Signature and arguments as
``ragged_paged_attention.step_needs``.

The needs are the RECURRENCE's own, whatever form computes it: a
batched row reads and writes its slot's state matrix once a layer
(``kda_heads x head_dim x head_dim`` float32, twice), reads its q, k, v
and log-decay rows and its betas and writes its o rows (float32); a
token does, a head, the decay of the matrix (``d^2``), ``S'^T k`` (``2
d^2``), the rank-1 update (``2 d^2``) and ``S^T q`` (``2 d^2``): ``7
d^2`` operations, the least an exact form does. A chunk form does more
(the sub-chunk's pairwise decays, the triangular solve) and a form that
re-read the state more often would move more: either reads as a lower
share of this roofline, which so cannot pass 100 %. The convolution's
tail (three pre-activation rows a slot) is read and written outside
the kernel, under the ``kda_conv`` scope, and is not counted here.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes = config["as_run"]
    heads, d = int(sizes["kda_heads"]), int(sizes["head_dim"])
    layers = sum(1 for k in sizes["layer_mixer"] if k == "kda")
    by = ops = 0
    for take, _ in rows:
        by += 2 * heads * d * d * 4 + take * heads * (5 * d + 1) * 4
        ops += 7.0 * take * heads * d * d
    return layers * by, layers * ops
