"""Quantized-wire streaming rings (ISSUE 3): lang.wire layout, the
XLA-ring wire twins (byte-identical layout to the fused Pallas wire),
the standalone collectives' wire knobs, the perf-model/topology wire
auto-selection, and the collective-id rail ledger.

Accuracy tolerances are PINNED here (the acceptance contract):

* fp8 (e4m3) wire: one rounding per element ≤ 2^-3 relative → AG-side
  (quantize once) max error ≤ 6% of the output scale; RS-side (per-hop
  requant over n-1 hops) ≤ 15%.
* int8 wire with per-chunk scales: ≤ 2% AG-side / 4% RS-side on
  well-conditioned slabs; the worst-case OUTLIER slab test pins the
  known failure mode (one huge row inflates the chunk scale and
  flattens its neighbors) so the guidance in docs/PERF.md stays honest.

The fused Pallas wire engines themselves need the TPU-simulation
interpreter (skipped without it); their protocol is checked statically
for every jax by the registry families in test_analysis.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from triton_distributed_tpu.lang import wire as wirelib

#: tier-1 fast subset (ci/fast.sh): XLA wire twins and layout math
pytestmark = pytest.mark.fast


def _rel_err(got, ref):
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    scale = np.abs(ref).max() or 1.0
    return float(np.abs(got - ref).max() / scale)


# ------------------------------------------------------------- the layout

class TestWireFormat:
    def test_normalize(self):
        assert wirelib.normalize_wire(None) is None
        assert wirelib.normalize_wire("bf16") is None
        assert wirelib.normalize_wire("fp8") == "fp8"
        assert wirelib.normalize_wire("int8") == "int8"
        assert wirelib.normalize_wire("auto") == "auto"
        with pytest.raises(ValueError):
            wirelib.normalize_wire("fp4")

    def test_chunking_and_bytes(self):
        fmt = wirelib.make_wire_format("fp8", 128)
        assert fmt.chunk_rows == 64 and fmt.chunks(128) == 2
        # payload at 1 B/elem + one (128·4 B) scale row per chunk
        assert fmt.slab_bytes(128, 8192) == 128 * 8192 + 2 * 512
        # vs the bf16 wire: the acceptance ratio at ring-slab scale
        assert 128 * 8192 * 2 / fmt.slab_bytes(128, 8192) > 1.8

    def test_whole_slab_chunk_for_tiny_slabs(self):
        fmt = wirelib.make_wire_format("int8", 16)
        assert fmt.chunk_rows == 16 and fmt.chunks(16) == 1

    def test_wire_blockable_rejects_tiny_slabs(self):
        # an 8×32 slab: the 512 B scale row eats the compression → must
        # be rejected, not shipped larger than the bf16 wire
        assert not wirelib.wire_blockable(8, 32, "fp8", strict=False)
        assert wirelib.wire_blockable(64, 2048, "fp8", strict=False)

    @pytest.mark.parametrize("quant", ["fp8", "int8"])
    def test_roundtrip_tolerance(self, quant):
        fmt = wirelib.make_wire_format(quant, 128)
        x = jax.random.normal(jax.random.PRNGKey(0), (128, 1024), jnp.float32)
        q, s = wirelib.quantize_slab(x, fmt)
        assert q.dtype == fmt.wire_dtype
        assert s.shape == fmt.scale_shape(128)
        y = wirelib.dequantize_slab(q, s, fmt, jnp.float32)
        tol = 0.06 if quant == "fp8" else 0.02
        assert _rel_err(y, x) < tol

    def test_outlier_slab_worst_case(self):
        """One huge row per chunk inflates the shared scale: int8 must
        still round-trip the OUTLIER exactly-ish while its neighbors
        degrade gracefully (bounded by outlier/127 per element) — the
        documented worst case of per-chunk scales."""
        fmt = wirelib.make_wire_format("int8", 64)
        x = np.random.default_rng(1).normal(size=(64, 512)).astype(np.float32)
        x[0, :] *= 1000.0                       # the outlier row
        q, s = wirelib.quantize_slab(jnp.asarray(x), fmt)
        y = np.asarray(wirelib.dequantize_slab(q, s, fmt, jnp.float32))
        # outlier row: ~2 valid digits survive
        assert _rel_err(y[0], x[0]) < 0.01
        # neighbor rows: absolute error bounded by half a quantization
        # step of the inflated scale
        step = float(np.asarray(s)[0, 0])
        assert np.abs(y[1:] - x[1:]).max() <= 0.5 * step * 1.01
        # fp8 keeps per-element exponents: neighbors stay accurate even
        # under the inflated chunk scale
        fmt8 = wirelib.make_wire_format("fp8", 64)
        q8, s8 = wirelib.quantize_slab(jnp.asarray(x), fmt8)
        y8 = np.asarray(wirelib.dequantize_slab(q8, s8, fmt8, jnp.float32))
        assert _rel_err(y8[1:], x[1:]) < 0.06

    def test_quantize_matches_ring_wire_bytes_model(self):
        from triton_distributed_tpu.tune.perf_model import ring_wire_bytes

        fmt = wirelib.make_wire_format("fp8", 128)
        assert ring_wire_bytes(128, 8192, 2, "fp8", fmt.chunk_rows) == \
            fmt.slab_bytes(128, 8192)
        assert ring_wire_bytes(128, 8192, 2, None) == 128 * 8192 * 2


# ------------------------------------------------ XLA ring wire engines

class TestWireOverlapEngines:
    """fp8/int8-wire AG-GEMM and GEMM-RS vs their bf16-wire twins, at
    pinned tolerances (the XLA ring engines ship the same lang.wire
    bytes as the fused kernels and run on any backend)."""

    def _ab(self, m, k, n, seed):
        a = jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(seed + 1), (k, n), jnp.float32)
        return a, b

    @pytest.mark.parametrize("w,tol", [("fp8", 0.06), ("int8", 0.02)])
    def test_ag_gemm_wire_accuracy(self, mesh8, w, tol):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab(64, 1024, 128, 1)
        ref = ag_gemm(a, b, mesh8, "x", method=AGGemmMethod.XLA_RING)
        got = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING, wire_dtype=w
        )
        assert _rel_err(got, ref) < tol

    @pytest.mark.parametrize("w,tol", [("fp8", 0.15), ("int8", 0.04)])
    def test_gemm_rs_wire_accuracy(self, mesh8, w, tol):
        from triton_distributed_tpu.kernels.gemm_rs import (
            GemmRSMethod,
            gemm_rs,
        )

        a, b = self._ab(64, 1024, 256, 3)
        ref = gemm_rs(a, b, mesh8, "x", method=GemmRSMethod.XLA_RING)
        got = gemm_rs(
            a, b, mesh8, "x", method=GemmRSMethod.XLA_RING, wire_dtype=w
        )
        assert _rel_err(got, ref) < tol

    def test_bf16_wire_is_todays_numerics(self, mesh8):
        """wire_dtype=None and 'bf16' are the identical program."""
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab(64, 1024, 128, 5)
        x = ag_gemm(a, b, mesh8, "x", method=AGGemmMethod.XLA_RING)
        y = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING, wire_dtype="bf16"
        )
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_explicit_wire_on_ineligible_slab_raises(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        # 32 cols: the scale plane eats the compression — a pinned wire
        # format is a contract, so this must raise, not silently demote
        a, b = self._ab(64, 32, 128, 7)
        with pytest.raises(ValueError, match="wire"):
            ag_gemm(
                a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
                wire_dtype="fp8",
            )

    def test_auto_wire_demotes_to_none_on_ineligible(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        a, b = self._ab(64, 32, 128, 9)
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.XLA_RING, wire_dtype="auto"
        ) is None

    def test_naive_engine_never_ships_a_wire(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        a, b = self._ab(64, 1024, 128, 11)
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.XLA_NAIVE,
            wire_dtype="fp8",
        ) is None

    def test_overlap_ctx_wire_forward_only(self, mesh8):
        """ops.overlap threads ctx.wire_dtype into the forward; the
        VJP still runs (backward duals ship the bf16 wire)."""
        from triton_distributed_tpu.kernels.ag_gemm import AGGemmMethod
        from triton_distributed_tpu.ops.overlap import (
            ag_gemm,
            create_ag_gemm_context,
        )

        ctx = create_ag_gemm_context(
            mesh8, "x", method=AGGemmMethod.XLA_RING, wire_dtype="fp8",
        )
        a, b = self._ab(64, 1024, 128, 13)
        out, grads = jax.value_and_grad(
            lambda a, b: jnp.sum(ag_gemm(a, b, ctx) ** 2), argnums=(0, 1)
        )(a, b)
        assert np.isfinite(float(out))
        assert all(np.isfinite(np.asarray(g)).all() for g in grads)


# ------------------------------------------------ int8→MXU consumer wire

class TestInt8MXU:
    """ISSUE 5 acceptance: the dequant-free 'int8-mxu' wire — identical
    int8 rails, consumed by an s8×s8→s32 matmul with the chunk·channel
    scales folded in the accumulator epilogue. Pinned here: tolerance
    against the dequant-then-matmul twin (incl. the outlier-slab worst
    case), knob plumbing, the jaxpr proof that no per-arrival dequant
    pass exists in the traced fused kernel, and the auto-selection
    contract (int8-mxu on the comm-bound wq=int8 config, bf16 on the
    north-star)."""

    def _ab(self, m, k, n, seed):
        a = jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(seed + 1), (k, n), jnp.float32)
        return a, b

    def test_normalize_and_payload(self):
        assert wirelib.normalize_wire("int8-mxu") == "int8-mxu"
        assert wirelib.wire_payload("int8-mxu") == "int8"
        assert wirelib.wire_payload("fp8") == "fp8"
        assert wirelib.wire_payload(None) is None

    def test_quantize_cols_roundtrip(self):
        b = jax.random.normal(jax.random.PRNGKey(3), (256, 128), jnp.float32)
        bq, bs = wirelib.quantize_cols(b)
        assert bq.dtype == jnp.int8 and bs.shape == (1, 128)
        assert _rel_err(bq.astype(jnp.float32) * bs, b) < 0.02

    def test_ag_gemm_int8_mxu_accuracy(self, mesh8):
        """Output within pinned tolerance of BOTH the exact result and
        the dequant-then-matmul twin on the same wire (the twin gap is
        pure per-channel weight-quant error, ≲1/127 per element)."""
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab(64, 1024, 128, 21)
        ref = ag_gemm(a, b, mesh8, "x", method=AGGemmMethod.XLA_RING)
        mx = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            wire_dtype="int8-mxu",
        )
        twin = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            wire_dtype="int8",
        )
        assert _rel_err(mx, ref) < 0.04
        assert _rel_err(mx, np.asarray(twin)) < 0.03

    def test_outlier_slab_worst_case_vs_twin(self, mesh8):
        """One huge activation row inflates its chunk scale identically
        for both int8 consumers — the epilogue fold must not amplify
        the documented per-chunk-scale worst case beyond the twin's."""
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a = np.random.default_rng(5).normal(size=(64, 1024)).astype(np.float32)
        a[0, :] *= 1000.0                       # the outlier row
        a = jnp.asarray(a)
        b = jax.random.normal(jax.random.PRNGKey(6), (1024, 128), jnp.float32)
        mx = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            wire_dtype="int8-mxu",
        )
        twin = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            wire_dtype="int8",
        )
        assert np.isfinite(np.asarray(mx)).all()
        assert _rel_err(mx, np.asarray(twin)) < 0.03

    def test_explicit_on_ineligible_slab_raises(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab(64, 32, 128, 23)   # scale plane eats compression
        with pytest.raises(ValueError, match="wire"):
            ag_gemm(
                a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
                wire_dtype="int8-mxu",
            )

    def test_resolve_explicit_and_auto_wq(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        a, b = self._ab(64, 1024, 128, 25)
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.XLA_RING,
            wire_dtype="int8-mxu",
        ) == "int8-mxu"
        # auto + declared int8 weight intent on a comm-bound shard
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.XLA_RING,
            wire_dtype="auto", wq="int8",
        ) == "int8-mxu"
        # auto without the intent never silently picks int8 numerics
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.XLA_RING,
            wire_dtype="auto",
        ) in (None, "fp8")

    def test_toolchain_gate_demotes_auto_and_refuses_pinned(
        self, mesh8, monkeypatch
    ):
        """TDTPU_WIRE_INT8_MXU=0: auto+wq demotes to the
        dequant-then-matmul int8 wire on the fused engine (not a
        numerics-class switch — the caller declared int8); an explicit
        pinned 'int8-mxu' refuses with the canonical diagnostic."""
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        monkeypatch.setenv("TDTPU_WIRE_INT8_MXU", "0")
        a, b = self._ab(64, 1024, 128, 27)
        assert resolve_ag_gemm_wire(
            mesh8, "x", a, b, method=AGGemmMethod.PALLAS_FUSED,
            wire_dtype="auto", wq="int8",
        ) == "int8"
        with pytest.raises(ValueError, match="in-kernel s8"):
            resolve_ag_gemm_wire(
                mesh8, "x", a, b, method=AGGemmMethod.PALLAS_FUSED,
                wire_dtype="int8-mxu",
            )

    def test_wire_tuner_mxu_candidates(self):
        from triton_distributed_tpu.tune.autotuner import wire_tuner

        t = wire_tuner("t", lambda *a, **k: None, mxu=True)
        assert {"wire_dtype": "int8-mxu"} in t.configs
        t2 = wire_tuner("t2", lambda *a, **k: None)
        assert {"wire_dtype": "int8-mxu"} not in t2.configs

    def test_perf_model_projects_the_win(self):
        """Acceptance: the perf model projects int8→MXU as a per-step
        win on the comm-bound bench config (skipped dequant pass + the
        s8×s8 MXU rate), and auto picks it exactly there."""
        from triton_distributed_tpu.tune.perf_model import (
            TPU_SPECS,
            auto_wire_dtype,
            dequant_pass_ms,
            int8_mxu_step_ratio,
        )

        spec = TPU_SPECS["v5e"]
        assert int8_mxu_step_ratio(128, 8192, 512, spec) > 1.0
        assert dequant_pass_ms(128, 8192, 2, spec) > 0.0
        assert auto_wire_dtype(
            128, 8192, 512, 2, spec=spec, consumer_wq="int8"
        ) == "int8-mxu"
        # the north-star prefill shard stays on the exact wire
        assert auto_wire_dtype(
            1024, 8192, 3584, 2, spec=spec, consumer_wq="int8"
        ) == "bf16"
        # no declared intent → fp8, as before
        assert auto_wire_dtype(128, 8192, 512, 2, spec=spec) == "fp8"

    def test_fused_kernel_jaxpr_has_no_dequant_pass(self):
        """THE acceptance assertion: the traced int8-mxu fused kernel
        contains an s8×s8→s32 dot and NO int8→float convert (the
        signature of a per-arrival dequant pass) — the wire provably
        ends at the MXU. The dequant twin is the positive control."""
        from triton_distributed_tpu.analysis import mosaic_compat
        from triton_distributed_tpu.kernels.registry import families

        kjs = mosaic_compat.trace_family_kernels(
            families()["ag_gemm.fused_int8mxw"], 4
        )
        assert kjs
        casts, s8_dots = [], 0
        for kj in kjs:
            casts += mosaic_compat.i8_to_float_casts(kj)
            for eqn in mosaic_compat._walk_jaxprs(kj):
                if eqn.primitive.name != "dot_general":
                    continue
                dts = [str(v.aval.dtype) for v in eqn.invars[:2]]
                if dts == ["int8", "int8"]:
                    s8_dots += 1
                    assert "int32" in str(eqn.outvars[0].aval.dtype)
        assert s8_dots >= 1
        assert casts == [], casts
        # positive control: the grouped int8-mxu family likewise
        kjs = mosaic_compat.trace_family_kernels(
            families()["moe_tp.ag_group_gemm_int8mxw"], 4
        )
        assert all(
            mosaic_compat.i8_to_float_casts(kj) == [] for kj in kjs
        )

    def test_mc004_flags_f32_accumulate_of_int8(self):
        """The deny-list leg: an s8 dot asking for a float accumulator
        is MC004 (what this Mosaic actually rejects)."""
        import jax as _jax
        from triton_distributed_tpu.analysis import mosaic_compat

        def bad(aq, bq):
            return jax.lax.dot_general(
                aq, bq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        jaxpr = _jax.make_jaxpr(bad)(
            jnp.zeros((8, 128), jnp.int8), jnp.zeros((128, 64), jnp.int8)
        )
        f = mosaic_compat.scan_kernel_jaxpr(jaxpr.jaxpr, "fixture")
        assert [x.rule for x in f] == ["MC004"]

    def test_moe_tp_context_int8_mxu_builds(self, mesh8):
        """Knob plumbing: MoETPContext(wire_dtype='int8-mxu') reaches
        the grouped epilogue consumer's builder (the fused engines
        themselves need the TPU-sim interpreter; their protocol twin is
        the registry family)."""
        from triton_distributed_tpu.kernels.moe_tp_fused import (
            build_ag_group_gemm_call,
            pick_gg_blocks,
        )

        blocks = pick_gg_blocks(8, 16, 128, 128, 4)
        call = build_ag_group_gemm_call(
            8, ("x",), "x", 16, 128, 128, 2, blocks,
            jnp.dtype(jnp.float32), 13, wire="int8-mxu",
        )
        assert call is not None


# --------------------------------------------- standalone ring wire knobs

class TestStandaloneWire:
    def test_all_gather_wire_fp8(self, mesh8):
        from triton_distributed_tpu.kernels.allgather import all_gather

        x = jax.random.normal(jax.random.PRNGKey(0), (64, 1024), jnp.float32)
        got = all_gather(x, mesh8, "x", wire_dtype="fp8")
        assert got.shape == x.shape
        assert _rel_err(got, x) < 0.06

    def test_all_gather_wire_auto_small_stays_exact(self, mesh8):
        from triton_distributed_tpu.kernels.allgather import all_gather

        # 32 KiB shards sit under the auto threshold → bf16 wire, exact
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 1024), jnp.float32)
        got = all_gather(x, mesh8, "x", wire_dtype="auto")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x))

    def test_all_gather_explicit_wire_on_1d_raises(self, mesh8):
        from triton_distributed_tpu.kernels.allgather import all_gather

        with pytest.raises(ValueError, match="wire"):
            all_gather(jnp.zeros((64,)), mesh8, "x", wire_dtype="fp8")

    @pytest.mark.parametrize("w,tol", [("fp8", 0.15), ("int8", 0.04)])
    def test_reduce_scatter_wire(self, mesh8, w, tol):
        from triton_distributed_tpu.kernels.reduce_scatter import (
            reduce_scatter,
        )

        y = jax.random.normal(
            jax.random.PRNGKey(2), (8, 64, 1024), jnp.float32
        )
        ref = np.asarray(y).sum(0)
        got = reduce_scatter(y, mesh8, "x", stacked=True, wire_dtype=w)
        assert got.shape == ref.shape
        assert _rel_err(got, ref) < tol

    def test_reduce_scatter_bf16_wire_unchanged(self, mesh8):
        from triton_distributed_tpu.kernels.reduce_scatter import (
            reduce_scatter,
        )

        y = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 64), jnp.float32)
        a = reduce_scatter(y, mesh8, "x", stacked=True)
        b = reduce_scatter(y, mesh8, "x", stacked=True, wire_dtype="bf16")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------- streaming-RS wire (round 8)

class TestStreamRSWire:
    """The last bf16 leg of the standalone RS family: rs_ring_stream's
    quantized wire. The Pallas streaming engine needs the TPU-sim
    interpreter (its protocol twin is the reduce_scatter.stream_int8w
    registry family in test_analysis.py); what runs on any backend here
    is the entry routing, the builder, and the byte-identical XLA-twin
    numerics."""

    def test_stream_wire_builder_constructs(self, mesh8):
        from triton_distributed_tpu.kernels.reduce_scatter import (
            _build_rs_stream_w,
        )

        fn = _build_rs_stream_w(
            mesh8, "x", 64, 2048, jnp.dtype(jnp.float32), True, 3,
            ("test", 0), "int8",
        )
        assert fn is not None

    def test_resolve_maps_int8_mxu_to_payload(self):
        from triton_distributed_tpu.kernels.reduce_scatter import (
            _resolve_rs_wire,
        )

        # a reduce ring has no MXU consumer: the epilogue wire carries
        # its int8 payload
        assert _resolve_rs_wire("int8-mxu", 64, 2048, 8, 4) == "int8"

    @pytest.mark.parametrize("w,tol", [("fp8", 0.15), ("int8", 0.04)])
    def test_streaming_scale_payload_accuracy(self, mesh8, w, tol):
        """A payload sized past the VMEM ring: off-TPU the entry
        degrades to the XLA twin carrying the same wire; the reduction
        stays within the pinned RS tolerances."""
        from triton_distributed_tpu.kernels.reduce_scatter import (
            reduce_scatter,
        )

        y = jax.random.normal(
            jax.random.PRNGKey(8), (8, 256, 2048), jnp.float32
        )
        ref = np.asarray(y).sum(0)
        got = reduce_scatter(y, mesh8, "x", stacked=True, wire_dtype=w)
        assert got.shape == ref.shape
        assert _rel_err(got, ref) < tol


# ------------------------------------------------ DCN rail wire (round 8)

class TestDCNRailWire:
    """The hierarchical engines' DCN rail legs — the slowest transport
    in the system — now ship the quantized payload + scale planes
    (runtime.multislice.dcn_wire_*). The rail machinery is
    link-agnostic, so the 2×4 CPU mesh exercises the exact multi-slice
    numerics."""

    def _ab(self, m, k, n, seed):
        a = jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(seed + 1), (k, n), jnp.float32)
        return a, b

    def test_hier_ag_gemm_rail_wire_accuracy(self, mesh2x4):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab(64, 1024, 128, 31)
        ref = ag_gemm(
            a, b, mesh2x4, "tp", dcn_axis="dp",
            method=AGGemmMethod.XLA_RING,
        )
        got = ag_gemm(
            a, b, mesh2x4, "tp", dcn_axis="dp",
            method=AGGemmMethod.XLA_RING, wire_dtype="fp8",
        )
        assert _rel_err(got, np.asarray(ref)) < 0.08

    def test_hier_gemm_rs_rail_wire_accuracy(self, mesh2x4):
        from triton_distributed_tpu.kernels.gemm_rs import (
            GemmRSMethod,
            gemm_rs,
        )

        a, b = self._ab(64, 1024, 256, 33)
        ref = gemm_rs(
            a, b, mesh2x4, "tp", dcn_axis="dp",
            method=GemmRSMethod.XLA_RING,
        )
        got = gemm_rs(
            a, b, mesh2x4, "tp", dcn_axis="dp",
            method=GemmRSMethod.XLA_RING, wire_dtype="int8",
        )
        assert _rel_err(got, np.asarray(ref)) < 0.06

    def test_resolve_hier_returns_rail_payload(self, mesh2x4):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        a, b = self._ab(64, 1024, 128, 35)
        # explicit wires resolve to the rail payload; int8-mxu demotes
        # to int8 (the rail dequantizes before any MXU)
        assert resolve_ag_gemm_wire(
            mesh2x4, "tp", a, b, method=AGGemmMethod.XLA_RING,
            wire_dtype="int8-mxu", dcn_axis="dp",
        ) == "int8"
        assert resolve_ag_gemm_wire(
            mesh2x4, "tp", a, b, method=AGGemmMethod.XLA_RING,
            wire_dtype="fp8", dcn_axis="dp",
        ) == "fp8"

    def test_auto_rail_wire_compresses_big_payloads_only(self, mesh2x4):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            resolve_ag_gemm_wire,
        )

        big_a, big_b = self._ab(512, 2048, 128, 37)
        assert resolve_ag_gemm_wire(
            mesh2x4, "tp", big_a, big_b, method=AGGemmMethod.XLA_RING,
            wire_dtype="auto", dcn_axis="dp",
        ) == "fp8"
        small_a, small_b = self._ab(64, 256, 128, 39)
        assert resolve_ag_gemm_wire(
            mesh2x4, "tp", small_a, small_b, method=AGGemmMethod.XLA_RING,
            wire_dtype="auto", dcn_axis="dp",
        ) is None

    def test_dcn_wire_reduce_scatter_helper(self, mesh8):
        """The shared rail body (also the gemm_rs degradation twin's
        ring): per-hop quantized ppermute reduce over any axis."""
        from jax.sharding import PartitionSpec as P

        from triton_distributed_tpu.runtime.multislice import (
            dcn_wire_reduce_scatter,
        )

        fmt = wirelib.make_wire_format("int8", 8)
        x = jax.random.normal(jax.random.PRNGKey(9), (64, 256), jnp.float32)

        fn = jax.shard_map(
            lambda s: dcn_wire_reduce_scatter(s, "x", 8, fmt),
            mesh=mesh8, in_specs=P(None), out_specs=P("x"),
            check_vma=False,
        )
        got = np.asarray(jax.jit(fn)(x))
        ref = np.asarray(x) * 8
        assert _rel_err(got, ref) < 0.04


# ------------------------------------------------------ wire auto-selection

class TestWireSelection:
    def test_perf_model_comm_bound_picks_fp8(self):
        from triton_distributed_tpu.tune.perf_model import (
            TPU_SPECS,
            auto_wire_dtype,
        )

        spec = TPU_SPECS["v5e"]
        # decode-side small-M small-N shard: the A-slab ring transfer
        # dwarfs the per-step matmul → compressed wire
        assert auto_wire_dtype(128, 8192, 512, 2, spec=spec) == "fp8"
        # the north-star prefill shard is flops-bound → raw wire
        assert auto_wire_dtype(1024, 8192, 3584, 2, spec=spec) == "bf16"

    def test_topology_standalone_threshold(self):
        from triton_distributed_tpu.runtime.topology import (
            auto_allgather_wire,
        )

        assert auto_allgather_wire(1 << 20) == "fp8"
        assert auto_allgather_wire(1 << 12) is None

    def test_engine_tuner_keys_include_wire(self, mesh8):
        """Persisted engine winners must be per-wire-format: the tuner
        name (the disk key namespace) carries the wire."""
        from triton_distributed_tpu.kernels.ag_gemm import _engine_tuner

        t_raw = _engine_tuner(mesh8, "x", (), jnp.dtype(jnp.float32), 5,
                              False, None, None)
        t_fp8 = _engine_tuner(mesh8, "x", (), jnp.dtype(jnp.float32), 5,
                              False, None, "fp8")
        assert t_raw.name != t_fp8.name and "wfp8" in t_fp8.name

    def test_wire_tuner_candidates(self):
        from triton_distributed_tpu.tune.autotuner import wire_tuner

        t = wire_tuner("t", lambda *a, **k: None)
        assert t.configs == [
            {"wire_dtype": "bf16"}, {"wire_dtype": "fp8"}
        ]


# ------------------------------------------------- collective-id rails

class TestCollectiveRails:
    def test_shipped_rails_match_the_historical_offsets(self):
        from triton_distributed_tpu.kernels.registry import (
            rail_collective_id,
            reserved_rails,
        )

        rails = reserved_rails()
        assert rails["ag_gemm.dcn_chunks"] == (64, 32)
        assert rails["gemm_rs.dcn_chunks"] == (96, 32)
        # the ledger arithmetic reproduces the old ad-hoc ids exactly
        assert rail_collective_id("ag_gemm.dcn_chunks", 5, 3) == 5 + 64 + 3
        assert rail_collective_id("gemm_rs.dcn_chunks", 6, 2) == 6 + 96 + 2
        assert rail_collective_id("gemm_rs.dcn_chunks", None, 0) is None

    def test_overlapping_reservation_raises(self):
        from triton_distributed_tpu.kernels import registry

        with pytest.raises(ValueError, match="overlaps"):
            registry.reserve_collective_rail("rogue.family", 90, 16)
        assert "rogue.family" not in registry.reserved_rails()

    def test_out_of_range_chunk_raises(self):
        from triton_distributed_tpu.kernels.registry import (
            rail_collective_id,
        )

        with pytest.raises(ValueError, match="reserved length"):
            rail_collective_id("ag_gemm.dcn_chunks", 5, 32)

    def test_re_reservation_same_range_is_idempotent(self):
        from triton_distributed_tpu.kernels import registry

        registry.reserve_collective_rail("ag_gemm.dcn_chunks", 64, 32)
        with pytest.raises(ValueError, match="re-reserved"):
            registry.reserve_collective_rail("ag_gemm.dcn_chunks", 64, 16)


# ---------------------------------------------- fused engines (TPU sim)

class TestFusedWireEngines:
    """The fused Pallas wire rings, executed on the interpreter mesh
    (the static protocol twin lives in test_analysis.py)."""

    @pytest.mark.parametrize("w,tol", [("fp8", 0.06), ("int8", 0.02)])
    def test_fused_ag_gemm_wire(self, mesh8, w, tol):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a = jax.random.normal(jax.random.PRNGKey(1), (64, 1024), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(2), (1024, 128), jnp.float32)
        ref = np.asarray(jnp.dot(a, b))
        got = ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.PALLAS_FUSED, wire_dtype=w
        )
        assert _rel_err(got, ref) < tol

    @pytest.mark.parametrize("w,tol", [("fp8", 0.15), ("int8", 0.04)])
    def test_fused_gemm_rs_wire(self, mesh8, w, tol):
        from triton_distributed_tpu.kernels.gemm_rs import (
            GemmRSMethod,
            gemm_rs,
        )

        a = jax.random.normal(jax.random.PRNGKey(3), (64, 1024), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(4), (1024, 256), jnp.float32)
        ref = np.asarray(jnp.dot(a, b))
        got = gemm_rs(
            a, b, mesh8, "x", method=GemmRSMethod.PALLAS_FUSED, wire_dtype=w
        )
        assert _rel_err(got, ref) < tol

    def test_fused_ring_ag_standalone_wire(self, mesh8):
        from triton_distributed_tpu.kernels.allgather import all_gather
        from triton_distributed_tpu.runtime import AllGatherMethod

        x = jax.random.normal(jax.random.PRNGKey(5), (64, 1024), jnp.float32)
        got = all_gather(
            x, mesh8, "x", method=AllGatherMethod.RING_1D, wire_dtype="fp8"
        )
        assert _rel_err(got, x) < 0.06


class TestWeightResidency:
    """Pre-quantized weight residency for the int8-mxu consumers
    (ROADMAP carried-forward, closed by PR 6): serving layers holding
    quantize_grouped_weights-style dicts pass the (bq, bs) pair
    through — NO per-call quantize_cols of B — and ineligible calls
    widen once and degrade cleanly."""

    def _ab(self):
        a = jax.random.normal(jax.random.PRNGKey(31), (512, 256),
                              jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(32), (256, 512),
                              jnp.bfloat16)
        return a, b

    def test_resident_pair_matches_per_call_quantization(self, mesh8):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab()
        ref = np.asarray(ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            wire_dtype="int8-mxu",
        ), np.float32)
        bq, bs = wirelib.quantize_cols(b)
        got = np.asarray(ag_gemm(
            a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
            b_quant=(bq, bs),
        ), np.float32)
        got_dict = np.asarray(ag_gemm(
            a, {"q": bq, "scale": bs[0]}, mesh8, "x",
            method=AGGemmMethod.XLA_RING,
        ), np.float32)
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(got_dict, ref)

    def test_resident_path_never_requantizes_b(self, mesh8, monkeypatch):
        from triton_distributed_tpu.kernels.ag_gemm import (
            AGGemmMethod,
            ag_gemm,
        )

        a, b = self._ab()
        bq, bs = wirelib.quantize_cols(b)
        calls = {"n": 0}
        orig = wirelib.quantize_cols

        def counting(x):
            calls["n"] += 1
            return orig(x)

        monkeypatch.setattr(wirelib, "quantize_cols", counting)
        ag_gemm(a, b, mesh8, "x", method=AGGemmMethod.XLA_RING,
                b_quant=(bq, bs))
        assert calls["n"] == 0

    def test_ineligible_call_widens_and_degrades(self):
        """1-device mesh: the resident pair cannot ride a wire — B is
        widened once and the plain dot runs, within weight-quant
        error of the dense result."""
        from jax.sharding import Mesh

        from triton_distributed_tpu.kernels.ag_gemm import ag_gemm

        mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("x",))
        a, b = self._ab()
        bq, bs = wirelib.quantize_cols(b)
        ref = np.asarray(ag_gemm(a, b, mesh1, "x"), np.float32)
        got = np.asarray(
            ag_gemm(a, b, mesh1, "x", b_quant=(bq, bs)), np.float32
        )
        assert _rel_err(jnp.asarray(got), jnp.asarray(ref)) < 0.02
