"""Sequence-parallel flash-decode attention layer.

Reference: python/triton_dist/layers/nvidia/sp_flash_decode_layer.py —
``SpGQAFlashDecodeAttention(nn.Module)`` (:45-184): local split-kv
attention on the rank's KV shard → low-latency AG of per-rank partial
(out, lse) → inter-rank combine, with symmetric AG buffers grown on
demand (:60-77).

TPU re-design: the layer is a thin stateless callable over the
flash-decode kernels (kernels/flash_decode.py) — no buffer management
is needed because XLA owns allocation; the only state worth keeping is
the geometry + jit caches, which the kernel module already holds.
Exposes both the host entry (global arrays on a mesh) and the device
body (for composition inside a model's shard_map).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from triton_distributed_tpu.kernels.flash_decode import (
    quantize_kv,
    sp_paged_gqa_fwd_batch_decode,
    sp_paged_gqa_fwd_batch_decode_q8,
    sp_gqa_fwd_batch_decode,
    sp_gqa_fwd_batch_decode_device,
    sp_gqa_fwd_batch_decode_q8,
)


@dataclass(frozen=True)
class SpGQAFlashDecodeAttention:
    """SP/CP decode attention: KV cache sequence-sharded over ``axis``.

    q_heads/kv_heads/head_dim describe the GQA geometry; ``scale`` defaults
    to 1/sqrt(head_dim); ``soft_cap`` > 0 enables logit soft-capping
    (≡ the ctor args at sp_flash_decode_layer.py:45-59).
    """

    mesh: jax.sharding.Mesh
    axis: str = "x"
    q_heads: int = 32
    kv_heads: int = 8
    head_dim: int = 128
    # dp mesh axes the BATCH dim is sharded over (the dp×tp serving
    # layout: batch over dp, sequence over ``axis``); () = replicated.
    # Non-paged modes only — the paged pool layout is rank-major.
    batch_axes: tuple = ()
    scale: float | None = None
    soft_cap: float = 0.0
    # None → auto (kernel heuristic: shard_len/2 clamped to [1024, 4096])
    block_k: int | None = None
    use_pallas: bool = True
    # "bhsd" (B, Hkv, S, D) is the fast decode layout: each KV block is
    # one contiguous DMA run (97% of HBM SOL measured on v5e vs 87% for
    # the reference-style "bshd" strided view). "bshd" kept for callers
    # holding (B, S, Hkv, D) caches.
    kv_layout: str = "bhsd"
    # For serialized-artifact (AOT) deployment of the local decode, use
    # kernels.flash_decode.gqa_fwd_batch_decode_aot directly (≡ the
    # reference's USE_TRITON_DISTRIBUTED_AOT path picking *_aot entries,
    # sp_flash_decode_layer.py:32-39); this layer always dispatches the
    # jit-cached SP pipeline.

    def __call__(self, q, k_cache, v_cache, global_kv_lens,
                 block_table=None):
        """q: (B, Hq, D) replicated; k/v_cache: (B, S, Hkv, D) [bshd] or
        (B, Hkv, S, D) [bhsd] with S sharded over ``axis``;
        global_kv_lens: (B,) total lengths. Returns (B, Hq, D) replicated
        (≡ forward, sp_flash_decode_layer.py:78-184).

        PAGED mode (``block_table`` given, ≡ the reference layer's
        block_table arg + page_size ctor knob): k/v_cache are page POOLS
        (R·npages_local, Hkv, page, D) sharded over ``axis`` and
        block_table is (R, B, pages_per_slice) of local page ids.

        INT8 mode: pass each cache as a ``{"q": int8 (B, Hkv, S, D),
        "scale": f32 (B, Hkv, S)}`` dict (the same quantized-leaf
        convention as the expert weights; build with
        :func:`quantize_kv` / models' ``kv_quant`` config) — half the
        KV bytes at rest and on the attention DMA stream."""
        if block_table is not None:
            if isinstance(k_cache, dict):       # int8 page pools
                return sp_paged_gqa_fwd_batch_decode_q8(
                    q, k_cache["q"], k_cache["scale"],
                    v_cache["q"], v_cache["scale"], global_kv_lens,
                    block_table, self.mesh, self.axis,
                    scale=self.scale, soft_cap=self.soft_cap,
                )
            return sp_paged_gqa_fwd_batch_decode(
                q, k_cache, v_cache, global_kv_lens, block_table,
                self.mesh, self.axis, scale=self.scale,
                soft_cap=self.soft_cap, use_pallas=self.use_pallas,
            )
        return self._nonpaged(q, k_cache, v_cache, global_kv_lens, False)

    def _nonpaged(self, q, k_cache, v_cache, global_kv_lens, with_lse):
        """The ONE non-paged dispatch (dict → int8, array → bf16)."""
        if isinstance(k_cache, dict):
            return sp_gqa_fwd_batch_decode_q8(
                q, k_cache["q"], k_cache["scale"],
                v_cache["q"], v_cache["scale"], global_kv_lens,
                self.mesh, self.axis, scale=self.scale,
                soft_cap=self.soft_cap, block_k=self.block_k,
                with_lse=with_lse, batch_axes=self.batch_axes,
            )
        return sp_gqa_fwd_batch_decode(
            q, k_cache, v_cache, global_kv_lens, self.mesh, self.axis,
            scale=self.scale, soft_cap=self.soft_cap,
            block_k=self.block_k, use_pallas=self.use_pallas,
            kv_layout=self.kv_layout, with_lse=with_lse,
            batch_axes=self.batch_axes,
        )

    def partials(self, q, k_cache, v_cache, global_kv_lens,
                 block_table=None):
        """Like ``__call__`` but returning the merged ``(out, lse)``
        pair — the softmax merge is associative, so the caller can fold
        FURTHER partials (e.g. the decode step's just-produced token as
        an exact single-position partial via ``combine_partials``)
        without the cache append feeding the attention kernel. With
        ``block_table``, the caches are page POOLS (the paged serving
        mode; see ``__call__``)."""
        if block_table is not None:
            if isinstance(k_cache, dict):
                return sp_paged_gqa_fwd_batch_decode_q8(
                    q, k_cache["q"], k_cache["scale"],
                    v_cache["q"], v_cache["scale"], global_kv_lens,
                    block_table, self.mesh, self.axis,
                    scale=self.scale, soft_cap=self.soft_cap,
                    with_lse=True,
                )
            return sp_paged_gqa_fwd_batch_decode(
                q, k_cache, v_cache, global_kv_lens, block_table,
                self.mesh, self.axis, scale=self.scale,
                soft_cap=self.soft_cap, use_pallas=self.use_pallas,
                with_lse=True,
            )
        return self._nonpaged(q, k_cache, v_cache, global_kv_lens, True)

    def token_partial(self, q, k_new, v_new):
        """The (out, lse) partial of ONE just-produced KV position, in
        THIS layer's score convention (scale + soft_cap) so it can be
        merged with :meth:`partials` results without domain drift: a
        weight-1 softmax over a single position has out = v and
        lse = its (soft-capped, scaled) raw score.

        q: (B, Hq, D); k_new/v_new: (B, Hkv, D). Returns
        ((B, Hq, D) f32, (B, Hq) f32)."""
        b, hq, d = q.shape
        hkv = k_new.shape[1]
        g = hq // hkv
        scale = self.scale if self.scale is not None else 1.0 / (d ** 0.5)
        qg = q.reshape(b, hkv, g, d)
        s = jnp.einsum(
            "bhgd,bhd->bhg",
            qg.astype(jnp.float32), k_new.astype(jnp.float32),
        ) * scale
        if self.soft_cap > 0.0:
            s = self.soft_cap * jnp.tanh(s / self.soft_cap)
        out = jnp.broadcast_to(
            v_new[:, :, None].astype(jnp.float32), (b, hkv, g, d)
        ).reshape(b, hq, d)
        return out, s.reshape(b, hq)

    def device_body(self, q, k_shard, v_shard, global_kv_lens):
        """Per-device body for composition inside a model's shard_map."""
        return sp_gqa_fwd_batch_decode_device(
            q, k_shard, v_shard, global_kv_lens, self.axis,
            scale=self.scale, soft_cap=self.soft_cap,
            block_k=self.block_k, use_pallas=self.use_pallas,
            kv_layout=self.kv_layout,
        )


@dataclass(frozen=True)
class RaggedPagedAttention:
    """Serving-layout ragged paged attention: pools sharded over the
    KV-HEAD dim on ``axis`` (GQA heads are independent — no cross-rank
    LSE merge, unlike the sequence-sharded decode layer above), q/out
    in the head-major GQA-rows packing, metadata replicated. The layer
    the continuous-batching serving step composes
    (models/transformer.serving_step); see
    kernels/ragged_paged_attention.py for the kernel contract and
    docs/SERVING.md for the state layout."""

    mesh: jax.sharding.Mesh
    axis: str = "x"
    group: int = 4                 # G = Hq // Hkv
    scale: float | None = None
    soft_cap: float = 0.0
    use_pallas: bool = True

    def __call__(self, qp, k_pool, v_pool, kv_lens, q_lens, q_starts,
                 block_table, *, topologies=None, block_q: int = 8,
                 n_bufs: int = 2, with_lse: bool = False,
                 window: int | None = None):
        """qp: (Hkv, T·G, D) packed rows sharded P(axis) on dim 0;
        k_pool/v_pool: (npages, Hkv, page, D) arrays or int8
        ``{"q","scale"}`` dicts, sharded P(None, axis); metadata —
        including the optional (R, 2+2W) per-row attention-topology
        descriptors — replicated. Returns (Hkv, T·G, D) sharded like
        qp — or the ``((Hkv, T·G, D), (Hkv, T·G))`` partial pair under
        ``with_lse`` (the cp-decode path merges per-shard partials with
        ``flash_decode.combine_gqa_partials``; head sharding makes the
        LSE per-rank-local, so the pair shards exactly like qp).
        ``window``: sliding-window attention (the kernel's and its
        twin's ``window``); None is full causal."""
        from jax.sharding import PartitionSpec as P

        from triton_distributed_tpu.kernels.ragged_paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )

        quant = isinstance(k_pool, dict)
        g, block = self.group, block_q
        use_pallas = self.use_pallas
        has_topo = topologies is not None

        def local(qp, table, kv_lens, q_lens, q_starts, *rest):
            if has_topo:
                topo, *pools = rest
            else:
                topo, pools = None, rest
            fn = (ragged_paged_attention if use_pallas
                  else ragged_paged_attention_xla)
            kw = dict(group=g, scale=self.scale, soft_cap=self.soft_cap,
                      topologies=topo)
            if window is not None:
                kw["window"] = window
            if use_pallas:
                kw["block_q"] = block
                kw["n_bufs"] = n_bufs
                kw["with_lse"] = with_lse
            if quant:
                kq, ks, vq, vs = pools
                out, lse = fn(qp, kq, vq, kv_lens, q_lens, q_starts,
                              table, k_scale=ks, v_scale=vs, **kw)
            else:
                kc, vc = pools
                out, lse = fn(qp, kc, vc, kv_lens, q_lens, q_starts,
                              table, **kw)
            return (out, lse) if with_lse else out

        pools = (
            (k_pool["q"], k_pool["scale"], v_pool["q"], v_pool["scale"])
            if quant else (k_pool, v_pool)
        )
        meta = (P(),) if has_topo else ()
        sharded = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(), P(), P(), P()) + meta
            + tuple(P(None, self.axis) for _ in pools),
            out_specs=(
                (P(self.axis), P(self.axis)) if with_lse else P(self.axis)
            ),
            check_vma=False,
        )
        extra = (topologies,) if has_topo else ()
        return sharded(qp, block_table, kv_lens, q_lens, q_starts,
                       *extra, *pools)


def append_kv(k_cache, v_cache, kv_lens, k_new, v_new, kv_layout="bhsd",
              k_quant=None, v_quant=None):
    """Append one decode step's K/V at each batch row's current length.

    k_cache/v_cache: (B, Hkv, S, D) [``kv_layout="bhsd"``, native
    default] or (B, S, Hkv, D) [``"bshd"``]; k_new/v_new: (B, Hkv, D);
    kv_lens: (B,)
    lengths BEFORE the append. Returns updated caches and lengths.
    (The reference leaves cache management to the serving stack; provided
    here so the models package can run real decode loops.)

    A row whose length has reached the cache capacity S drops the write
    (JAX out-of-bounds scatter semantics) while the returned length
    still increments — callers must enforce capacity up front.

    INT8 caches (``{"q", "scale"}`` dicts, bhsd only): the new rows are
    quantized per (b, h) — one f32 scale per appended D-row — and both
    planes are scattered. ``k_quant``/``v_quant``: optional already-
    computed ``(int8 values, f32 scales)`` pairs (from
    :func:`~triton_distributed_tpu.kernels.flash_decode.quantize_kv`);
    passing them makes the cached token BIT-IDENTICAL to whatever the
    caller attended — re-quantizing a dequantized bf16 round-trip can
    shift ints by 1 LSB (ADVICE r5).
    """
    if isinstance(k_cache, dict):
        assert kv_layout == "bhsd", "int8 caches are bhsd-native"
        kq_new, ks_new = k_quant if k_quant is not None else quantize_kv(k_new)
        vq_new, vs_new = v_quant if v_quant is not None else quantize_kv(v_new)
        b = k_cache["q"].shape[0]
        heads = jnp.arange(k_cache["q"].shape[1])
        bi = jnp.arange(b)[:, None]
        hi = heads[None, :]
        li = kv_lens[:, None]
        k_cache = {
            "q": k_cache["q"].at[bi, hi, li].set(kq_new),
            "scale": k_cache["scale"].at[bi, hi, li].set(ks_new),
        }
        v_cache = {
            "q": v_cache["q"].at[bi, hi, li].set(vq_new),
            "scale": v_cache["scale"].at[bi, hi, li].set(vs_new),
        }
        return k_cache, v_cache, kv_lens + 1
    b = k_cache.shape[0]
    rows = jnp.arange(b)
    if kv_layout == "bshd":
        k_cache = k_cache.at[rows, kv_lens].set(k_new.astype(k_cache.dtype))
        v_cache = v_cache.at[rows, kv_lens].set(v_new.astype(v_cache.dtype))
    else:
        heads = jnp.arange(k_cache.shape[1])
        bi = rows[:, None]
        hi = heads[None, :]
        li = kv_lens[:, None]
        k_cache = k_cache.at[bi, hi, li].set(
            k_new.astype(k_cache.dtype)
        )
        v_cache = v_cache.at[bi, hi, li].set(
            v_new.astype(v_cache.dtype)
        )
    return k_cache, v_cache, kv_lens + 1


def paged_append_kv(k_pool, v_pool, block_table, kv_lens, k_new, v_new,
                    k_quant=None, v_quant=None):
    """Append one decode step's K/V into PAGE POOLS at each row's
    current length — the paged twin of :func:`append_kv` (≡ the
    reference kernels writing through the block table,
    flash_decode.py:763-846).

    k_pool/v_pool: (R·npages_local, Hkv, page, D) pools — or int8
    ``{"q", "scale"}`` dicts with (R·npages_local, Hkv, page) scale
    pools; block_table: (R, B, pages_per_slice) LOCAL page ids (rank
    r's pool shard is rows [r·npages_local, (r+1)·npages_local));
    kv_lens: (B,) GLOBAL lengths before the append. A row at global
    position L lives on sequence slice L // (pages_per_slice·page), in
    local page (L mod s_loc) // page, at offset L mod page. Rows at
    capacity drop the write (JAX OOB scatter semantics), like
    append_kv. Written at the global level — GSPMD partitions the
    scatter (on one device this is a plain in-place write; a rank-local
    shard_map twin is the multi-host optimization, same as the
    reference's per-rank table writes)."""
    r, b, pps = block_table.shape
    pool0 = k_pool["q"] if isinstance(k_pool, dict) else k_pool
    npages_local = pool0.shape[0] // r
    page = pool0.shape[2]
    s_loc = pps * page
    rows = jnp.arange(b)
    slice_idx = kv_lens // s_loc
    local = kv_lens % s_loc
    off = local % page
    local_id = block_table[
        jnp.clip(slice_idx, 0, r - 1), rows, local // page
    ]
    # rows past capacity get an out-of-range pool index on purpose —
    # the scatter drops them (same contract as append_kv)
    pool_idx = jnp.where(
        kv_lens < r * s_loc,
        slice_idx * npages_local + local_id,
        pool0.shape[0],
    )
    heads = jnp.arange(pool0.shape[1])
    pi = pool_idx[:, None]
    hi = heads[None, :]
    oi = off[:, None]
    if isinstance(k_pool, dict):
        # pre-quantized pairs keep the cache bit-identical to what the
        # caller attended (see append_kv)
        kq_new, ks_new = k_quant if k_quant is not None else quantize_kv(k_new)
        vq_new, vs_new = v_quant if v_quant is not None else quantize_kv(v_new)
        k_pool = {
            "q": k_pool["q"].at[pi, hi, oi].set(kq_new),
            "scale": k_pool["scale"].at[pi, hi, oi].set(ks_new),
        }
        v_pool = {
            "q": v_pool["q"].at[pi, hi, oi].set(vq_new),
            "scale": v_pool["scale"].at[pi, hi, oi].set(vs_new),
        }
        return k_pool, v_pool, kv_lens + 1
    k_pool = k_pool.at[pi, hi, oi].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[pi, hi, oi].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool, kv_lens + 1
