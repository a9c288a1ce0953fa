"""Tutorial 16: paged serving — page pools, block tables, on-device
multi-step decode.

Production serving doesn't keep one contiguous KV slab per request —
it allocates fixed-size PAGES from a pool and addresses them through a
block table (the reference's block-table path is its default decode
entry, flash_decode.py:763-846). Round 5 makes that a first-class
model mode:

* ``Transformer.init_paged_cache(batch, capacity, page)`` — per-layer
  int8/bf16 page pools, rank-major over tp (rank r owns its sequence
  slice's pages), plus ONE (R, B, pages_per_slice) table of LOCAL page
  ids shared by every layer.
* ``Transformer.paginate_caches(caches, page)`` — the prefill→decode
  bridge: a contiguous prefill-filled cache converts to pools with one
  reshape per plane (pages of the dense identity allocation ARE the
  page-aligned rows; no gather).
* ``decode_step(..., block_table=table)`` — attention walks the table
  (scalar-prefetch index maps: the DMA engine fetches page[j] while
  page[j-1] computes) and ``paged_append_kv`` writes the new token
  through the table in place.
* ``generate(..., block_table=...)`` / ``generate_scan(...)`` — the
  serving loops run unchanged on pools; generate_scan folds the whole
  decode into ONE jitted lax.scan (one dispatch per SEQUENCE, not
  one per token).
"""

from _common import get_mesh

mesh = get_mesh()

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models import Transformer, TransformerConfig

cfg = TransformerConfig(
    vocab=128, n_layers=2, hidden=128, ffn=256,
    n_heads=8, n_kv_heads=4, head_dim=16,
    moe="ep", moe_layers=(1,), num_experts=8, topk=2,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
model = Transformer(cfg, mesh, "x", ())
params = jax.tree.map(
    lambda p, s: jax.device_put(p, s),
    model.init(jax.random.PRNGKey(0)), model.shardings(),
)

B, PROMPT, STEPS, CAP, PAGE = 2, 16, 4, 64, 4  # 8 ranks × 2 pages × 4 rows

# ---- path A: contiguous prefill, then PAGINATE and decode from pools
prompt = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0, cfg.vocab)
caches = model.init_cache(B, CAP)
last, caches, lens = model._prefill_jit(params, caches, prompt)
first = jnp.argmax(last, axis=-1).astype(jnp.int32)

pools, table = model.paginate_caches(caches, page=PAGE)
# the decode jits DONATE caches and lens (in-place update) — hand each
# serving path its own lens buffer (`+ 0`), the same discipline as any
# state shared across donating calls
toks_paged, pools, lens_p = model.generate(
    params, pools, lens + 0, first, STEPS, block_table=table
)

# contiguous twin from the same state → identical tokens
toks_flat, _, _ = model.generate(params, caches, lens + 0, first, STEPS)
np.testing.assert_array_equal(np.asarray(toks_paged), np.asarray(toks_flat))
print(f"paged generate == contiguous generate over {STEPS} steps")

# ---- path B: pool-native session (no contiguous stage at all), decoded
# by the ON-DEVICE multi-step entry (one jitted lax.scan)
pools2, table2 = model.init_paged_cache(B, CAP, page=PAGE)
toks_scan, pools2, lens2 = model.generate_scan(
    params, pools2, jnp.zeros((B,), jnp.int32), first, STEPS,
    block_table=table2,
)
toks_loop, _, _ = model.generate(
    params, model.init_paged_cache(B, CAP, page=PAGE)[0],
    jnp.zeros((B,), jnp.int32), first, STEPS, block_table=table2,
)
np.testing.assert_array_equal(np.asarray(toks_scan), np.asarray(toks_loop))
print(f"generate_scan (one program, {STEPS} steps) == per-step generate")
print("tutorial 16 OK")
