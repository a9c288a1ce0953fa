"""Ragged paged-attention kernel: numerics vs the XLA reference twin.

The serving tentpole's kernel contract (ISSUE 6): ONE launch processes
mixed prefill-chunk and decode rows against per-request block tables —
per-row (kv_len, q_len) metadata, causal frontier masking, int8 pools
with exact in-softmax scale folds, and the packed GQA-rows layout.
These tests pin the kernel to :func:`ragged_paged_attention_xla` (an
independently written dense reference) and the reference itself to
plain dense causal attention.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.kernels.flash_decode import quantize_kv
from triton_distributed_tpu.kernels.ragged_paged_attention import (
    auto_block_q,
    causal_topologies,
    pack_gqa_rows,
    ragged_paged_attention,
    ragged_paged_attention_xla,
    topo_width,
    tree_topology_row,
    unpack_gqa_rows,
)

pytestmark = pytest.mark.fast

HKV, G, D, PAGE, PPS, NPAGES = 2, 2, 32, 8, 4, 16


def _pools(rng, quant):
    kc = jnp.asarray(
        rng.standard_normal((NPAGES, HKV, PAGE, D)), jnp.float32
    )
    vc = jnp.asarray(
        rng.standard_normal((NPAGES, HKV, PAGE, D)), jnp.float32
    )
    if not quant:
        return (kc, vc), {}
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    return (kq, vq), dict(k_scale=ks, v_scale=vs)


def _mixed_batch(rng):
    """Three rows: steady decode, a mid-prompt chunk, a fresh prefill."""
    kv_lens = jnp.asarray([13, 21, 8], jnp.int32)   # incl. step tokens
    q_lens = jnp.asarray([1, 5, 8], jnp.int32)
    q_starts = jnp.asarray([0, 8, 16], jnp.int32)   # 8-aligned
    t = 32
    table = jnp.asarray(
        rng.permutation(NPAGES)[: 3 * PPS].reshape(3, PPS), jnp.int32
    )
    q = jnp.asarray(
        rng.standard_normal((t, HKV * G, D)), jnp.float32
    )
    return q, kv_lens, q_lens, q_starts, table


class TestRaggedKernel:
    @pytest.mark.parametrize("quant", [False, True])
    def test_matches_xla_twin_mixed_rows(self, quant):
        rng = np.random.default_rng(0)
        pools, scales = _pools(rng, quant)
        q, kv_lens, q_lens, q_starts, table = _mixed_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        bq = auto_block_q(int(q_lens.max()), G)
        out, lse = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=bq, **scales,
        )
        ref, rlse = ragged_paged_attention_xla(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            **scales,
        )
        # int8 tolerance: the kernel widens to bf16 before the dot, the
        # twin to f32 — same bound as the paged q8 decode tests
        tol = 2e-2 if quant else 1e-5
        for r in range(3):
            s = int(q_starts[r]) * G
            w = int(q_lens[r]) * G
            np.testing.assert_allclose(
                np.asarray(out)[:, s:s + w], np.asarray(ref)[:, s:s + w],
                atol=tol, rtol=tol,
            )
            np.testing.assert_allclose(
                np.asarray(lse)[:, s:s + w],
                np.asarray(rlse)[:, s:s + w], atol=tol, rtol=tol,
            )

    def test_xla_twin_matches_dense_causal(self):
        """The reference itself, pinned: one fresh-prefill row equals
        plain dense causal attention over the gathered pages."""
        rng = np.random.default_rng(1)
        (kc, vc), _ = _pools(rng, False)
        L = 11
        kv_lens = jnp.asarray([L], jnp.int32)
        q_lens = jnp.asarray([L], jnp.int32)
        q_starts = jnp.asarray([0], jnp.int32)
        table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        t = 16
        q = jnp.asarray(rng.standard_normal((t, HKV * G, D)), jnp.float32)
        qp = pack_gqa_rows(q, HKV)
        out, _ = ragged_paged_attention_xla(
            qp, kc, vc, kv_lens, q_lens, q_starts, table, group=G
        )
        got = unpack_gqa_rows(out, HKV * G)[:L]          # (L, Hq, D)

        # dense causal reference over the contiguous first-4-pages view
        kcat = kc[table[0]].transpose(1, 0, 2, 3).reshape(HKV, -1, D)[:, :L]
        vcat = vc[table[0]].transpose(1, 0, 2, 3).reshape(HKV, -1, D)[:, :L]
        qg = q[:L].reshape(L, HKV, G, D)
        s = jnp.einsum("thgd,hsd->thgs", qg, kcat) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("thgs,hsd->thgd", p, vcat).reshape(L, HKV * G, D)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_decode_row_matches_paged_decode_kernel(self):
        """A decode-only ragged batch (every q_len == 1) must agree
        with the existing paged decode kernel on the same pools —
        the ragged kernel subsumes the decode rectangle."""
        from triton_distributed_tpu.kernels.flash_decode import (
            paged_gqa_fwd_batch_decode,
        )

        rng = np.random.default_rng(2)
        (kc, vc), _ = _pools(rng, False)
        b = 3
        kv_lens = jnp.asarray([9, 17, 25], jnp.int32)
        q_lens = jnp.ones((b,), jnp.int32)
        q_starts = jnp.asarray([0, 8, 16], jnp.int32)
        table = jnp.asarray(
            rng.permutation(NPAGES)[: b * PPS].reshape(b, PPS), jnp.int32
        )
        t = 32
        q = jnp.asarray(rng.standard_normal((t, HKV * G, D)), jnp.float32)
        qp = pack_gqa_rows(q, HKV)
        out, _ = ragged_paged_attention(
            qp, kc, vc, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8,
        )
        got = unpack_gqa_rows(out, HKV * G)       # (T, Hq, D)
        q_dec = q[np.asarray(q_starts)]           # (b, Hq, D)
        ref, _ = paged_gqa_fwd_batch_decode(
            q_dec, kc, vc, kv_lens, table
        )
        np.testing.assert_allclose(
            np.asarray(got)[np.asarray(q_starts)], np.asarray(ref),
            atol=1e-5, rtol=1e-5,
        )

    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((10, HKV * G, D)), jnp.float32)
        assert np.array_equal(
            np.asarray(unpack_gqa_rows(pack_gqa_rows(q, HKV), HKV * G)),
            np.asarray(q),
        )

    def test_auto_block_q_ladder(self):
        assert auto_block_q(1, 7) == 8       # 8·7 = 56 ≡ 0 (mod 8)
        assert auto_block_q(1, 2) == 8
        assert auto_block_q(9, 2) == 16
        assert auto_block_q(16, 1) == 16
        for mx, g in ((1, 1), (3, 7), (100, 2)):
            b = auto_block_q(mx, g)
            assert b >= mx and (b * g) % 8 == 0

    def test_block_q_alignment_rejected(self):
        rng = np.random.default_rng(4)
        pools, _ = _pools(rng, False)
        q, kv_lens, q_lens, q_starts, table = _mixed_batch(rng)
        with pytest.raises(ValueError, match="sublane"):
            ragged_paged_attention(
                pack_gqa_rows(q, HKV), *pools, kv_lens, q_lens, q_starts,
                table, group=G, block_q=3,
            )

    @pytest.mark.parametrize("quant", [False, True])
    def test_all_causal_topologies_byte_identical(self, quant):
        """Acceptance: an all-CAUSAL topology operand changes NOTHING —
        valid spans byte-identical to the topology-less launch (the
        identity-operand contract; garbage spans excluded, per the
        packing contract). Dropping the lse output (``with_lse=False``,
        the head-sharded serving step) changes nothing in ``out``
        either."""
        rng = np.random.default_rng(6)
        pools, scales = _pools(rng, quant)
        q, kv_lens, q_lens, q_starts, table = _mixed_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        base, base_lse = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, **scales,
        )
        topo = jnp.asarray(causal_topologies(3, topo_width(8)))
        got, got_lse = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, topologies=topo, **scales,
        )
        bare, no_lse = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, topologies=topo, with_lse=False, **scales,
        )
        assert no_lse is None
        for r in range(3):
            s = int(q_starts[r]) * G
            w = int(q_lens[r]) * G
            np.testing.assert_array_equal(
                np.asarray(base)[:, s:s + w], np.asarray(got)[:, s:s + w]
            )
            np.testing.assert_array_equal(
                np.asarray(base)[:, s:s + w], np.asarray(bare)[:, s:s + w]
            )
            np.testing.assert_array_equal(
                np.asarray(base_lse)[:, s:s + w],
                np.asarray(got_lse)[:, s:s + w],
            )

    def _tree_batch(self, rng):
        """Row 0: a tree verify row — frontier + 5 draft nodes with a
        sibling fork (node 1 and node 2 both children of node 0).
        Row 1: a plain decode row (CAUSAL)."""
        parents = [-1, 0, 0, 2, 3]
        kv_lens = jnp.asarray([14, 21], jnp.int32)
        q_lens = jnp.asarray([6, 1], jnp.int32)
        q_starts = jnp.asarray([0, 8], jnp.int32)
        table = jnp.asarray(
            rng.permutation(NPAGES)[: 2 * PPS].reshape(2, PPS), jnp.int32
        )
        t = 16
        q = jnp.asarray(
            rng.standard_normal((t, HKV * G, D)), jnp.float32
        )
        w = topo_width(8)
        topo = causal_topologies(2, w)
        topo[0] = tree_topology_row(parents, w)
        return q, kv_lens, q_lens, q_starts, table, jnp.asarray(topo)

    @pytest.mark.parametrize("quant", [False, True])
    def test_tree_row_matches_xla_twin(self, quant):
        """Tentpole numerics: a TREE verify row (sibling fork) under the
        ancestor-bitmask mask agrees with the XLA twin given the same
        topology operand."""
        rng = np.random.default_rng(7)
        pools, scales = _pools(rng, quant)
        q, kv_lens, q_lens, q_starts, table, topo = self._tree_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        out, lse = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, topologies=topo, **scales,
        )
        ref, rlse = ragged_paged_attention_xla(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            topologies=topo, **scales,
        )
        tol = 2e-2 if quant else 1e-5
        for r in range(2):
            s = int(q_starts[r]) * G
            w = int(q_lens[r]) * G
            np.testing.assert_allclose(
                np.asarray(out)[:, s:s + w], np.asarray(ref)[:, s:s + w],
                atol=tol, rtol=tol,
            )
            np.testing.assert_allclose(
                np.asarray(lse)[:, s:s + w],
                np.asarray(rlse)[:, s:s + w], atol=tol, rtol=tol,
            )

    def test_twin_tree_mask_matches_manual_dense(self):
        """The twin's TREE semantics, pinned independently: each q
        position attends the full committed prefix plus exactly the
        speculative positions its ancestor bitmask names — node 3 (a
        child of node 2) must NOT see sibling node 1's position."""
        rng = np.random.default_rng(8)
        (kc, vc), _ = _pools(rng, False)
        q, kv_lens, q_lens, q_starts, table, topo = self._tree_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        out, _ = ragged_paged_attention_xla(
            qp, kc, vc, kv_lens, q_lens, q_starts, table, group=G,
            topologies=topo,
        )
        got = unpack_gqa_rows(out, HKV * G)
        L, nq = int(kv_lens[0]), int(q_lens[0])
        base = L - nq                        # committed prefix tokens
        anc = np.asarray(topo)[0, 2:2 + topo_width(8)]
        kcat = kc[table[0]].transpose(1, 0, 2, 3).reshape(HKV, -1, D)[:, :L]
        vcat = vc[table[0]].transpose(1, 0, 2, 3).reshape(HKV, -1, D)[:, :L]
        for t in range(nq):
            vis = np.zeros((L,), bool)
            vis[:base] = True
            for j in range(nq):
                if (int(anc[t]) >> j) & 1:
                    vis[base + j] = True
            if t >= 3:                       # deep chain excludes node 1
                assert not vis[base + 2]
            qt = np.asarray(q)[t].reshape(HKV, G, D)
            s = np.einsum(
                "hgd,hsd->hgs", qt, np.asarray(kcat)
            ) / math.sqrt(D)
            s = np.where(vis[None, None, :], s, -1e30)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            ref = np.einsum(
                "hgs,hsd->hgd", p, np.asarray(vcat)
            ).reshape(HKV * G, D)
            np.testing.assert_allclose(
                np.asarray(got)[t], ref, atol=1e-5, rtol=1e-5
            )

    def test_qlen_zero_rows_skipped_under_topology(self):
        """Satellite: with the topology operand present the kernel
        takes the cross-row q-prefetch hop over q_len == 0 rows —
        active rows' valid spans must match the batch without the
        inactive row byte-for-byte."""
        rng = np.random.default_rng(9)
        pools, scales = _pools(rng, True)
        q, kv_lens, q_lens, q_starts, table = _mixed_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        w = topo_width(8)
        a_out, _ = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, topologies=jnp.asarray(causal_topologies(3, w)),
            **scales,
        )
        # inactive row INSIDE the batch (skip hop must cross it)
        kv4 = jnp.asarray([13, 0, 21, 8], jnp.int32)
        ql4 = jnp.asarray([1, 0, 5, 8], jnp.int32)
        qs4 = jnp.asarray([0, 24, 8, 16], jnp.int32)
        tb4 = jnp.concatenate(
            [table[:1], jnp.zeros((1, PPS), jnp.int32), table[1:]]
        )
        b_out, _ = ragged_paged_attention(
            qp, *pools, kv4, ql4, qs4, tb4, group=G, block_q=8,
            topologies=jnp.asarray(causal_topologies(4, w)), **scales,
        )
        for r in range(3):
            s = int(q_starts[r]) * G
            w_ = int(q_lens[r]) * G
            np.testing.assert_array_equal(
                np.asarray(a_out)[:, s:s + w_],
                np.asarray(b_out)[:, s:s + w_],
            )

    def test_inactive_rows_leave_valid_spans_intact(self):
        """q_len == 0 rows write garbage at THEIR q_start only — parked
        past every valid span, they must not perturb active rows (the
        engine's parking-zone contract; regression for the clobber bug
        the sequential out-DMA ordering self-heals)."""
        rng = np.random.default_rng(5)
        pools, scales = _pools(rng, True)
        q, kv_lens, q_lens, q_starts, table = _mixed_batch(rng)
        qp = pack_gqa_rows(q, HKV)
        a_out, _ = ragged_paged_attention(
            qp, *pools, kv_lens, q_lens, q_starts, table, group=G,
            block_q=8, **scales,
        )
        # add an inactive 4th row parked at token 24 (the slack zone)
        kv4 = jnp.concatenate([kv_lens, jnp.zeros((1,), jnp.int32)])
        ql4 = jnp.concatenate([q_lens, jnp.zeros((1,), jnp.int32)])
        qs4 = jnp.concatenate([q_starts, jnp.asarray([24], jnp.int32)])
        tb4 = jnp.concatenate([table, jnp.zeros((1, PPS), jnp.int32)])
        b_out, _ = ragged_paged_attention(
            qp, *pools, kv4, ql4, qs4, tb4, group=G, block_q=8, **scales,
        )
        for r in range(3):
            s = int(q_starts[r]) * G
            w = int(q_lens[r]) * G
            np.testing.assert_array_equal(
                np.asarray(a_out)[:, s:s + w],
                np.asarray(b_out)[:, s:s + w],
            )
