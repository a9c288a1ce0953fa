"""What one engine step needs of the ragged kernel's LATENT walk
(multi-head latent attention over a latent pool, ``as_run.kv_latent``):
bytes it has to read and operations it has to do, both lower bounds.
Signature and arguments as ``ragged_paged_attention.step_needs``.

The needs are the MODEL's, whatever implements it. Bytes: each batched
row's resident latents once a layer, ``kv_latent + qk_rope_dim`` values
a token as the model needs them (not as the pool stores them, padded).
Operations: ``2 x heads x (qk_nope_dim + qk_rope_dim + v_head_dim)`` an
attended (query, key) pair, one multiply-add an element of the score
and of the value: what the EXPANDED form does inside attention, the
least any exact form does (the absorbed walk does ``2 x heads x
(kv_latent + qk_rope_dim + kv_latent)``, 3.4 times as much at the
published sizes, for keys and values it never up-projects). A change of
form or of padding is therefore read by the same yardstick, and the
share cannot pass 100 %.
"""

from __future__ import annotations


def step_needs(config: dict, rows: list) -> tuple:
    sizes = config["as_run"]
    layers = int(sizes["n_layers"])
    token_bytes = ((sizes["kv_latent"] + sizes["qk_rope_dim"])
                   * int(config["kv_bytes_per_element"]))
    pair_ops = 2.0 * sizes["n_heads"] * (
        sizes["qk_nope_dim"] + sizes["qk_rope_dim"] + sizes["v_head_dim"])
    tokens = pairs = 0
    for take, cursor in rows:
        # the row's resident entries (positions < cursor) are read
        # once; each new position attends itself and all before
        tokens += cursor
        pairs += take * (cursor - take) + take * (take + 1) // 2
    return layers * tokens * token_bytes, layers * pairs * pair_ops
