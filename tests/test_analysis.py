"""shmemlint: static semaphore-protocol analysis (ISSUE 2 acceptance).

The properties pinned here:

* every registered kernel family lints CLEAN on an 8-rank abstract mesh
  (and the analyzer is shape/size-generic: a 3-rank mesh too);
* the seeded broken kernels each produce their expected rule ID with
  rank + site diagnostics — including the ``test_races.py`` caveat (a
  deliberately missing wait the dynamic race detector has MISSED under
  ``dma_execution_mode="on_wait"``): :func:`fixtures.missing_wait` is
  that bug and SL001 flags it statically, forever, on any jax;
* the CLI (``python -m triton_distributed_tpu.analysis.lint``) walks
  the registry and exits nonzero exactly when errors exist.

Everything here is static — no interpreter, no devices, no mesh: these
tests run identically on the 2-vCPU CI runner and a TPU host.
"""

import json

import numpy as np
import pytest

pytestmark = [pytest.mark.analysis, pytest.mark.fast]

from triton_distributed_tpu.analysis import (
    dataflow,
    events,
    fixtures,
    mosaic_compat,
)
from triton_distributed_tpu.analysis.checks import simulate
from triton_distributed_tpu.analysis.findings import (
    RULES,
    SCHEMA_VERSION,
    Severity,
)
from triton_distributed_tpu.analysis.lint import (
    _cross_family_checks,
    analyze_family,
    analyze_spec,
    lint_all,
    lint_family,
    main as lint_main,
)
from triton_distributed_tpu.kernels.registry import families


def _rules(findings):
    return sorted({f.rule for f in findings})


def _analyze_fixture(fx, n=8, site="fixture"):
    spec, in_shapes = fx()
    return analyze_spec(spec, in_shapes(n), n, kernel_name=fx.__name__,
                        site=site)


# ------------------------------------------------------------ registry clean

class TestRegistryClean:
    def test_all_registered_families_lint_clean_mesh8(self):
        """ISSUE acceptance: the full registry on --mesh 8 — protocol
        (SL001-007), delivery contracts (SL008) and wire rails
        (SL009/SL010) — no findings."""
        findings = lint_all(n=8)
        assert findings == [], [f.format() for f in findings]

    def test_all_registered_families_lint_clean_mesh4(self):
        """ISSUE acceptance: same at --mesh 4."""
        findings = lint_all(n=4)
        assert findings == [], [f.format() for f in findings]

    def test_registry_clean_on_odd_mesh(self):
        findings = lint_all(n=3)
        assert findings == [], [f.format() for f in findings]

    def test_every_family_produces_cross_rank_traffic(self):
        """A vacuously-clean analyzer is worthless: every family's
        symbolic execution must record real cross-rank events (puts to
        a different rank and/or remote signals) on every rank —
        except `local`-contract families (the ragged serving kernel),
        which must instead record real LOCAL DMA traffic."""
        for name, fam in families().items():
            rec, _ = analyze_family(fam, 4)
            is_local = (
                fam.contract is not None
                and getattr(fam.contract, "kind", None) == "local"
            )
            for r in range(4):
                if is_local:
                    local = [
                        e for e in rec.traces[r]
                        if isinstance(e, events.PutEvent) and e.local
                    ]
                    assert local, f"{name}: rank {r} recorded no DMAs"
                    continue
                cross = [
                    e for e in rec.traces[r]
                    if (isinstance(e, events.PutEvent) and e.dst_rank != r)
                    or (isinstance(e, events.SignalEvent) and e.target != r)
                ]
                assert cross, f"{name}: rank {r} recorded no remote traffic"

    def test_replay_completes_and_balances(self):
        """The replay simulation itself: the ring allgather completes
        with every semaphore exactly drained."""
        rec, _ = analyze_family(families()["allgather.ring_1d"], 4)
        sim = simulate(rec)
        assert sim.completed
        for k, total in sim.delivered.items():
            assert sim.consumed.get(k, 0) == total, k


# --------------------------------------------------------- seeded fixtures

class TestSeededFixtures:
    def test_missing_wait_flagged_with_rank_and_site(self):
        """The test_races.py caveat, covered forever: the deliberately
        removed wait the dynamic detector missed is SL001 here, naming
        the semaphore, the ranks and the site."""
        rec, findings = _analyze_fixture(fixtures.missing_wait)
        assert "SL001" in _rules(findings)
        f = next(f for f in findings if f.rule == "SL001")
        assert f.severity == Severity.ERROR
        assert f.site == "fixture"
        assert len(f.ranks) > 0
        assert f.sem
        # the unordered landing is also caught as a buffer hazard
        assert "SL004" in _rules(findings)

    def test_credit_imbalance_flagged(self):
        """Signal-1/wait-2 off-by-one → SL002 on every rank, with the
        available-vs-required credit arithmetic in the message."""
        rec, findings = _analyze_fixture(fixtures.credit_imbalance)
        sl2 = [f for f in findings if f.rule == "SL002"]
        assert sl2, _rules(findings)
        assert {r for f in sl2 for r in f.ranks} == set(range(8))
        assert "only 1 are available" in sl2[0].message

    def test_deadlock_cycle_flagged_with_full_chain(self):
        rec, findings = _analyze_fixture(fixtures.deadlock)
        f = next(f for f in findings if f.rule == "SL003")
        assert set(f.ranks) == set(range(8))
        for r in range(8):
            assert f"rank {r}" in f.message

    def test_duplicate_collective_id_flagged(self):
        (sa, ia), (sb, ib) = fixtures.duplicate_collective_id()
        ra, _ = analyze_spec(sa, ia(8), 8, kernel_name="dup_a",
                             site="site_a")
        rb, _ = analyze_spec(sb, ib(8), 8, kernel_name="dup_b",
                             site="site_b")
        findings = _cross_family_checks([ra, rb])
        assert _rules(findings) == ["SL005"]
        assert "45" in findings[0].message

    def test_same_site_engines_may_share_collective_id(self):
        """Engine variants of one op entry share its default id by
        design — no false positive."""
        fams = families()
        recs = [
            analyze_family(fams[n], 4)[0]
            for n in ("allgather.ring_1d", "allgather.ll_small")
        ]
        assert _cross_family_checks(recs) == []

    def test_barrier_sequence_mismatch_flagged(self):
        rec, findings = _analyze_fixture(fixtures.barrier_mismatch)
        f = next(f for f in findings if f.rule == "SL005")
        assert set(f.ranks) == set(range(1, 8))

    def test_undrained_dma_flagged(self):
        rec, findings = _analyze_fixture(fixtures.undrained_dma)
        assert _rules(findings) == ["SL007"]
        assert all("send_sem" in f.sem for f in findings)

    def test_vmem_overcommit_flagged(self):
        rec, findings = _analyze_fixture(fixtures.vmem_overcommit)
        f = next(f for f in findings if f.rule == "SL006")
        assert "big_ref" in f.message


# ---------------------------------------------------- dataflow provenance

def _analyze_df_fixture(fx, n=8):
    spec, in_shapes, contract = fx()
    return analyze_spec(
        spec, in_shapes(n), n, kernel_name=fx.__name__, site="fixture",
        contract=contract,
    )


class TestDataflowProvenance:
    """The symbolic payload-provenance engine itself — guard against a
    vacuously-clean pass."""

    def test_gather_provenance_single_marker_per_source(self):
        """The ring AG's workspace must end with each source's marker on
        exactly its slab, on every rank (not all-zeros, not mixed)."""
        from triton_distributed_tpu.analysis.checks import simulate

        rec, _ = analyze_family(families()["allgather.ring_1d"], 4)
        sim = simulate(rec)
        st = dataflow._State(rec)
        st.seed_inputs()
        dataflow._replay(rec, sim, st)
        for rank in range(4):
            c = st.get(rank, "out_ref")["contrib"]
            for s in range(4):
                slab = c[s * 8:(s + 1) * 8]
                assert (slab == np.int64(1) << (4 * s)).all(), (rank, s)

    def test_reduce_provenance_full_fold_mask(self):
        """gemm_rs's output: every element exactly one contribution per
        rank (the 0x1111 nibble mask at n=4)."""
        from triton_distributed_tpu.analysis.checks import simulate

        rec, _ = analyze_family(families()["gemm_rs.fused"], 4)
        sim = simulate(rec)
        st = dataflow._State(rec)
        st.seed_inputs()
        dataflow._replay(rec, sim, st)
        for rank in range(4):
            assert (st.get(rank, "out_hbm")["contrib"] == 0x1111).all()

    def test_wire_families_record_quant_dequant_events(self):
        """The wire hooks feed the evaluator: AG-side rings record
        dequants, RS-side rings record per-hop quantize + fused
        dequant-accumulate."""
        rec, _ = analyze_family(families()["ag_gemm.fused_fp8w"], 4)
        deq = [e for e in rec.events(events.DequantEvent)]
        assert deq and all(e.add_region is None for e in deq)
        rec, _ = analyze_family(families()["gemm_rs.fused_fp8w"], 4)
        assert any(True for _ in rec.events(events.QuantEvent))
        assert all(
            e.add_region is not None
            for e in rec.events(events.DequantEvent)
        )

    def test_wire_dst_ends_dequantized_never_quantized(self):
        """No registry family may leave raw wire bytes in its contract
        destination (the SL008 wire leg, asserted on the state)."""
        from triton_distributed_tpu.analysis.checks import simulate

        for name in ("ag_gemm.fused_fp8w", "reduce_scatter.ring_fp8w"):
            fam = families()[name]
            rec, _ = analyze_family(fam, 4)
            sim = simulate(rec)
            st = dataflow._State(rec)
            st.seed_inputs()
            dataflow._replay(rec, sim, st)
            dst = dataflow._resolve_dst(rec, fam.contract.dst)
            for rank in range(4):
                wire = st.get(rank, dst)["wire"]
                assert not (wire == dataflow.QUANTIZED).any(), (name, rank)
                assert (wire == dataflow.DEQUANTIZED).any(), (name, rank)


class TestSeededDataflowFixtures:
    """Each data-correctness rule pinned by a deliberately broken kernel
    that is PROTOCOL-CLEAN — the whole point: every semaphore balances
    and SL001-SL007 stay silent, yet the delivered bytes are wrong."""

    def test_skipped_chunk_is_sl008_only(self):
        rec, findings = _analyze_df_fixture(fixtures.skipped_chunk)
        assert _rules(findings) == ["SL008"], [f.format() for f in findings]
        f = next(f for f in findings if "never delivered" in f.message)
        assert f.severity == Severity.ERROR
        assert f.site == "fixture"
        assert len(f.ranks) >= 1
        # every rank is missing a chunk
        assert {fd.ranks[0] for fd in findings
                if "of source rank" in fd.message} == set(range(8))

    def test_dup_chunk_reports_duplicate_and_loss(self):
        rec, findings = _analyze_df_fixture(fixtures.dup_chunk)
        assert _rules(findings) == ["SL008"], [f.format() for f in findings]
        msgs = " | ".join(f.message for f in findings)
        assert "duplicated" in msgs
        assert "never delivered" in msgs
        # the duplicate names both the holder and source rank 0
        f = next(f for f in findings if "duplicated" in f.message)
        assert 0 in f.ranks

    def test_scale_on_payload_sem_is_sl009(self):
        rec, findings = _analyze_df_fixture(fixtures.scale_on_payload_sem)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]
        f = findings[0]
        assert "payload rail's semaphore" in f.message
        assert f.sem and "recv_sem" in f.sem
        assert len(f.ranks) == 2

    def test_stale_scale_is_sl010(self):
        rec, findings = _analyze_df_fixture(fixtures.stale_scale)
        assert _rules(findings) == ["SL010"], [f.format() for f in findings]
        f = findings[0]
        assert "scale group" in f.message
        assert f.site == "fixture"
        assert len(f.ranks) == 1

    def test_scale_fold_omitted_is_sl009(self):
        """The int8→MXU consumer bug (round 8): rails correctly paired,
        semaphores balanced, but the epilogue never folds the scale —
        the s8×s8 product is stored unrescaled. SL009 with rank + site."""
        rec, findings = _analyze_df_fixture(fixtures.scale_fold_omitted)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]
        f = findings[0]
        assert "NO scale folded" in f.message
        assert f.site == "fixture"
        assert len(f.ranks) == 1
        # every rank consumes unrescaled — one finding each
        assert {fd.ranks[0] for fd in findings} == set(range(8))

    def test_serialized_ring_is_sl011_with_projection(self):
        """The hop-critical-path feed-in (ROADMAP PR-4 follow-on): a
        protocol-clean, delivery-complete gather whose deepest chain
        rides n hops instead of n-1 — flagged with the perf model's
        projected wall-clock regression in the message."""
        rec, findings = _analyze_df_fixture(fixtures.serialized_ring)
        assert _rules(findings) == ["SL011"], [f.format() for f in findings]
        f = findings[0]
        assert "8 remote hops" in f.message and "ring-optimal <= 7" in f.message
        assert "ms critical path" in f.message
        assert f.site == "fixture"

    def test_epilogue_consume_families_flow(self):
        """The int8→MXU registry families record epilogue DequantEvents
        (q + scale regions, no dst copy) and their contract destination
        — the WIRE workspace itself — ends fully consumed: every
        arrival flipped to DEQUANTIZED by the epilogue fold, never raw."""
        from triton_distributed_tpu.analysis.checks import simulate

        for name in ("ag_gemm.fused_int8mxw",
                     "moe_tp.ag_group_gemm_int8mxw"):
            fam = families()[name]
            rec, findings = analyze_family(fam, 4)
            assert findings == [], [f.format() for f in findings]
            eps = [e for e in rec.events(events.DequantEvent) if e.epilogue]
            assert eps and all(e.s_region is not None for e in eps), name
            sim = simulate(rec)
            st = dataflow._State(rec)
            st.seed_inputs()
            dataflow._replay(rec, sim, st)
            dst = dataflow._resolve_dst(rec, fam.contract.dst)
            for rank in range(4):
                wire = st.get(rank, dst)["wire"]
                assert not (wire == dataflow.QUANTIZED).any(), (name, rank)
                assert (wire == dataflow.DEQUANTIZED).any(), (name, rank)

    def test_hop_histogram_ring_depth(self):
        """The per-element hop counters behind SL011: a clean 4-rank AG
        ring tops out at exactly n-1 = 3 hops."""
        from triton_distributed_tpu.analysis.checks import simulate

        fam = families()["allgather.ring_1d"]
        rec, _ = analyze_family(fam, 4)
        sim = simulate(rec)
        st = dataflow._State(rec)
        st.seed_inputs()
        dataflow._replay(rec, sim, st)
        hist = dataflow.hop_histogram(
            rec, st, dataflow._resolve_dst(rec, fam.contract.dst)
        )
        assert max(hist) == 3
        assert dataflow._check_hop_depth(rec, st, fam.contract) == []

    def test_contract_on_unknown_ref_is_loud(self):
        spec, in_shapes, _ = fixtures.skipped_chunk()
        with pytest.raises(KeyError, match="no_such_buffer"):
            analyze_spec(
                spec, in_shapes(4), 4, kernel_name="fx", site="fixture",
                contract=dataflow.DeliveryContract(
                    kind="gather", dst="no_such_buffer"
                ),
            )


# ------------------------------------------------------ mosaic pre-flight

class TestMosaicCompat:
    def test_registry_preflight_clean(self):
        """ISSUE acceptance: every family passes MC001-MC003 — scanned
        under the hardware build config, or refusing cleanly under the
        pinned-fp8 wire contract (the contract fires before Mosaic
        would)."""
        findings, report = mosaic_compat.preflight_all(n=4)
        assert findings == [], [f.format() for f in findings]
        assert set(report["scanned"]) | set(report["refused"]) == set(
            families()
        )
        # the fp8-pinned wire twins are exactly the clean refusals
        assert all("fp8w" in name for name in report["refused"])
        assert report["refused"], "no family exercised the wire contract"

    def test_preflight_is_seconds_fast(self):
        """The pre-flight must stay tier-1-cheap (< 60 s is the
        acceptance bound; warm it runs in single-digit seconds)."""
        import time

        t0 = time.time()
        mosaic_compat.preflight_all(n=4, kernels=["allgather"])
        assert time.time() - t0 < 60

    def test_f8_cast_fixture_flagged(self):
        spec, in_shapes = fixtures.f8_inkernel_cast()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx_f8", site="fixture"
        )
        assert _rules(f) == ["MC001"]
        assert "16-bit to 32-bit" in f[0].message

    def test_scalar_shape_cast_fixture_flagged(self):
        spec, in_shapes = fixtures.scalar_shape_cast()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx_sc", site="fixture"
        )
        assert _rules(f) == ["MC002"]

    def test_subbyte_broadcast_fixture_flagged(self):
        spec, in_shapes = fixtures.subbyte_broadcast()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx_sb", site="fixture"
        )
        assert _rules(f) == ["MC003"]

    def test_dynamic_gather_fixture_flagged(self):
        """MC006: jnp.take over a TRACED index vector — the anc[par]
        index chase the ragged kernel's static ancestor-bitmask unroll
        exists to avoid — is denied; the registry preflight above
        proves the real kernels never produce it."""
        spec, in_shapes = fixtures.dynamic_gather()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx_dg", site="fixture"
        )
        assert _rules(f) == ["MC006"]
        assert "traced indices" in f[0].message

    def test_sublane_dynamic_slice_fixture_flagged(self):
        """MC007 (the nightly-slow-run signature promoted to a static
        rule): lax.dynamic_slice with a TRACED start index on the
        sublane (second-minor) dim — this Mosaic only folds constant
        sublane offsets, so the 8-minute AOT refusal becomes a
        2-second lint finding."""
        spec, in_shapes = fixtures.sublane_dynamic_slice()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx_sds", site="fixture"
        )
        assert _rules(f) == ["MC007"]
        assert "sublane" in f[0].message

    @pytest.mark.parametrize("fixture, rule, phrase", [
        ("unproven_tile_slice", "MC008", "divisibility proof"),
        ("thin_lane_dma", "MC009", "trailing dim of 1"),
        ("i1_vector_select", "MC010", "i1 vector"),
    ])
    def test_ragged_bringup_refusals_flagged(self, fixture, rule, phrase):
        """MC008–MC010: the three constructs Mosaic refused on the
        ragged paged kernel's first real compile (jax 0.9.0, AOT vs
        v5e), each reproduced alone — the pre-flight must not call a
        kernel carrying one clean again."""
        spec, in_shapes = getattr(fixtures, fixture)()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(4), 4, kernel_name="fx", site="fixture"
        )
        assert _rules(f) == [rule]
        assert phrase in f[0].message

    def test_scan_enters_pl_when_bodies(self):
        """Every ``pl.when`` stages a ``cond`` whose branches ride a
        TUPLE param — the scan must walk them (it once did not, and
        reported the ragged kernel clean with its whole row body
        unscanned)."""
        kj, = mosaic_compat.trace_family_kernels(
            families()["flash_decode.ragged_paged"], 4
        )
        prims = {e.primitive.name for e in mosaic_compat._walk_jaxprs(kj)}
        assert {"dma_start", "dot_general", "multiple_of"} <= prims

    def test_fp8_wire_family_flags_mc001_when_forced(self, monkeypatch):
        """The KNOWN f8-cast construct, on a real registry family: with
        the toolchain override asserting in-kernel f8 support, the fp8
        wire twin builds — and the pre-flight still flags the extf cast
        this Mosaic rejects (the finding the 8-minute AOT suite would
        otherwise be the first to see)."""
        monkeypatch.setenv("TDTPU_WIRE_FP8_INKERNEL", "1")
        status, f = mosaic_compat.preflight_family(
            families()["ag_gemm.fused_fp8w"], 4
        )
        assert status == "scanned"
        assert "MC001" in _rules(f)

    def test_clean_kernels_not_flagged(self):
        """int8 widening and the (1, 128) scale-row idiom must NOT trip
        the scan — the non-wire and int8-capable families are clean."""
        status, f = mosaic_compat.preflight_family(
            families()["gemm_rs.fused"], 4
        )
        assert status == "scanned" and f == []

    def test_mosaic_cli(self):
        assert mosaic_compat.main(
            ["--mesh", "4", "--kernel", "allgather.ring_1d"]
        ) == 0


# ------------------------------------------------------------------ the CLI

class TestCLI:
    def test_cli_clean_registry_exits_zero(self, capsys):
        assert lint_main(["--mesh", "4"]) == 0
        err = capsys.readouterr().err
        assert "0 error(s)" in err

    def test_cli_kernel_filter_and_json(self, capsys):
        assert lint_main(["--mesh", "4", "--kernel", "allgather",
                          "--json"]) == 0

    def test_cli_json_schema_version_and_rule_counts(self, capsys):
        """Satellite contract: --json emits a schema_version header and
        a per-rule-count summary (machine-readable, all rules present
        with zeros)."""
        assert lint_main(["--mesh", "4", "--kernel", "allgather.ring_1d",
                          "--json"]) == 0
        lines = [json.loads(l) for l in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["schema_version"] == SCHEMA_VERSION
        assert "allgather.ring_1d" in lines[0]["families"]
        assert set(lines[-1]["rule_counts"]) == set(RULES)
        assert lines[-1]["errors"] == 0

    def test_cli_mosaic_flag(self, capsys):
        assert lint_main(["--mesh", "4", "--kernel", "allgather.ring_1d",
                          "--mosaic"]) == 0
        assert "mosaic-compat" in capsys.readouterr().err

    def test_cli_rejects_trivial_mesh(self):
        with pytest.raises(SystemExit):
            lint_main(["--mesh", "1"])

    def test_cli_list(self, capsys):
        assert lint_main(["--list"]) == 0
        out = capsys.readouterr().out
        for name in families():
            assert name in out

    def test_allow_demotes_severity(self):
        spec, in_shapes = fixtures.vmem_overcommit()
        _, findings = analyze_spec(spec, in_shapes(4), 4,
                                   kernel_name="fx", site="fixture")
        from triton_distributed_tpu.analysis.lint import _apply_allow

        demoted = _apply_allow(findings, {"SL006"})
        assert all(f.severity == Severity.INFO for f in demoted
                   if f.rule == "SL006")


# --------------------------------------------------------------- event model

class TestEventModel:
    def test_rule_catalog_is_stable(self):
        """Rule ids are load-bearing (docs, suppressions, this file):
        removing or renumbering one is a breaking change."""
        assert set(RULES) == {
            "SL001", "SL002", "SL003", "SL004", "SL005", "SL006", "SL007",
            "SL008", "SL009", "SL010", "SL011", "SL012", "SL013",
            "MC001", "MC002", "MC003", "MC004", "MC005", "MC006",
            "MC007", "MC008", "MC009", "MC010",
            "SV001", "SV002", "SV003", "SV004", "SV005", "SV006",
            "SV007",
        }

    def test_ring_trace_targets_right_neighbor(self):
        rec, _ = analyze_family(families()["allgather.ring_1d"], 4)
        for r in range(4):
            puts = [e for e in rec.traces[r]
                    if isinstance(e, events.PutEvent) and not e.local]
            assert puts and all(p.dst_rank == (r + 1) % 4 for p in puts)

    def test_region_overlap_semantics(self):
        a = events.Region("buf", (0, 0), (8, 128))
        b = events.Region("buf", (7, 0), (9, 128))
        c = events.Region("buf", (8, 0), (16, 128))
        d = events.Region("other", (0, 0), (8, 128))
        assert a.overlaps(b) and b.overlaps(c)
        assert not a.overlaps(c) and not a.overlaps(d)

    def test_lint_family_by_name(self):
        assert lint_family("gemm_rs.fused", n=4) == []
        with pytest.raises(KeyError):
            lint_family("no_such_kernel")


# --------------------------------------------------- quantized-wire bytes

#: bytes per element of each ring buffer the wire kernels ship, keyed by
#: the kernel parameter name the Region's root ref carries (the base
#: families move f32 lint payloads; the _fp8w twins move 1-byte slabs
#: plus f32 scale planes).
_REF_ITEMSIZE = {
    "x_hbm": 4, "ag_hbm": 4, "a_hbm": 4, "w0": 4, "w1": 4,
    "xs_hbm": 4, "y_hbm": 4,
    "xq_hbm": 1, "agq_hbm": 1, "wq0": 1, "wq1": 1, "xsc_hbm": 4,
    "xs_ref": 4, "xq_ref": 1, "x_ref": 4, "out_ref": 4,
    "outq_ref": 1, "outs_ref": 4, "qbuf_ref": 1, "sbuf_ref": 4,
    "ws0": 4, "ws1": 4, "ags_hbm": 4, "acc_ref": 4,
}


def _remote_put_bytes(rec, rank=0):
    """Total bytes rank ``rank`` RDMAs to peers in one symbolic run."""
    total = 0
    for e in rec.traces[rank]:
        if isinstance(e, events.PutEvent) and not e.local:
            r = e.src_region
            elems = 1
            for lo, hi in zip(r.lo, r.hi):
                elems *= hi - lo
            total += elems * _REF_ITEMSIZE[r.ref]
    return total


class TestWirePayloadBytes:
    """ISSUE 3 acceptance: shmemlint symbolically models the COMPRESSED
    payload byte counts — the _fp8w twins' recorded RDMA traffic is the
    lang.wire layout (1-byte payload + per-chunk f32 scale plane), not
    the raw-slab byte count, and the scale rail's semaphore protocol is
    part of the replayed trace."""

    @pytest.mark.parametrize(
        "base,wire", [
            ("ag_gemm.fused", "ag_gemm.fused_fp8w"),
            ("gemm_rs.fused", "gemm_rs.fused_fp8w"),
            ("moe_tp.ag_group_gemm", "moe_tp.ag_group_gemm_fp8w"),
            ("moe_tp.reduce_rs", "moe_tp.reduce_rs_fp8w"),
        ],
    )
    def test_wire_variant_ships_fewer_bytes(self, base, wire):
        fams = families()
        rec_b, f_b = analyze_family(fams[base], 4)
        rec_w, f_w = analyze_family(fams[wire], 4)
        assert f_b == [] and f_w == [], (
            [f.format() for f in f_b + f_w]
        )
        b_bytes = _remote_put_bytes(rec_b)
        w_bytes = _remote_put_bytes(rec_w)
        # lint payloads are f32 → the 1-byte wire + scale planes must
        # come in well under half (the bf16 acceptance ratio is 1.8×;
        # on f32 lint slabs the same layout gives ≥ 2×)
        assert w_bytes * 2 <= b_bytes, (base, b_bytes, wire, w_bytes)

    @pytest.mark.parametrize(
        "wire,rows,cols", [
            # standalone rings carry PER-ROW scale planes at wider lint
            # columns (their entries gate on cols·itemsize > cols+512)
            ("allgather.ring_1d_fp8w", 8, 2048),
            ("reduce_scatter.ring_fp8w", 8, 2048),
        ],
    )
    def test_standalone_wire_under_raw_bytes(self, wire, rows, cols):
        rec, f = analyze_family(families()[wire], 4)
        assert f == [], [x.format() for x in f]
        w_bytes = _remote_put_bytes(rec)
        raw = 3 * rows * cols * 4          # n-1 = 3 hops of the f32 slab
        expect = 3 * (rows * cols + rows * 128 * 4)   # 1-byte + scales
        assert w_bytes == expect
        assert w_bytes * 2 <= raw

    def test_rs_stream_wire_under_raw_bytes(self):
        """The HBM-streaming RS wire (round 8): per-hop quantized ring
        slabs + per-chunk scale planes, well under half the raw f32
        ring traffic the base streaming family ships."""
        rec_b, f_b = analyze_family(families()["reduce_scatter.stream"], 4)
        rec_w, f_w = analyze_family(
            families()["reduce_scatter.stream_int8w"], 4
        )
        assert f_b == [] and f_w == [], (
            [x.format() for x in f_b + f_w]
        )
        w = _remote_put_bytes(rec_w)
        # lint geometry differs (128 vs 2048 cols) — compare per-element
        b_per = _remote_put_bytes(rec_b) / (3 * 8 * 128)
        w_per = w / (3 * 8 * 2048)
        assert w_per * 2 <= b_per, (b_per, w_per)

    def test_int8_mxu_wire_ships_compressed_and_never_dequantizes(self):
        """The dequant-free consumer's traffic is the int8 wire layout
        (identical rails to the dequant twin) — the difference is all on
        the consume side, checked by the epilogue-event tests above."""
        rec_b, _ = analyze_family(families()["ag_gemm.fused"], 4)
        rec_w, f_w = analyze_family(families()["ag_gemm.fused_int8mxw"], 4)
        assert f_w == [], [x.format() for x in f_w]
        assert _remote_put_bytes(rec_w) * 2 <= _remote_put_bytes(rec_b)

    def test_ag_gemm_wire_bytes_match_the_layout_exactly(self):
        from triton_distributed_tpu.lang import wire as wirelib

        rec, _ = analyze_family(families()["ag_gemm.fused_fp8w"], 4)
        fmt = wirelib.make_wire_format("fp8", 16)
        # n-1 = 3 forwards of one 16×128 slab + its scale plane
        assert _remote_put_bytes(rec) == 3 * fmt.slab_bytes(16, 128)

    def test_wire_ring_has_a_scale_rail(self):
        """Every payload RDMA is paired with a scale-plane RDMA (the
        protocol shmemlint replays covers both rails)."""
        rec, _ = analyze_family(families()["ag_gemm.fused_fp8w"], 4)
        puts = [
            e for e in rec.traces[0]
            if isinstance(e, events.PutEvent) and not e.local
        ]
        payload = [p for p in puts if p.src_region.ref in ("xq_hbm", "agq_hbm")]
        scales = [p for p in puts if p.src_region.ref in ("xs_hbm", "ags_hbm")]
        assert len(payload) == len(scales) == 3


# ------------------------------------------------- KV-ship family

class TestKVShipFamily:
    """The `kv_ship.pages` family (ISSUE 7): the disaggregated-serving
    page transport — a PAIRWISE permute contract (src_only pins the
    role topology) with dual payload/scale DMA rails, and its two
    seeded fixtures."""

    def test_family_lints_clean_both_meshes(self):
        for n in (4, 8):
            findings = lint_family("kv_ship.pages", n=n)
            assert findings == [], [f.format() for f in findings]

    def test_family_is_preflighted(self):
        status, f = mosaic_compat.preflight_family(
            families()["kv_ship.pages"], 8
        )
        assert status == "scanned" and f == []

    def test_pages_land_from_exactly_the_partner(self):
        """Provenance: every rank's landing buffer holds its partner
        rank's marker on every element — nobody else's, no holes, and
        the landed bytes end DEQUANTIZED (installed with their scale
        planes), never raw."""
        from triton_distributed_tpu.analysis.checks import simulate

        fam = families()["kv_ship.pages"]
        rec, findings = analyze_family(fam, 4)
        assert findings == [], [f.format() for f in findings]
        sim = simulate(rec)
        st = dataflow._State(rec)
        st.seed_inputs()
        dataflow._replay(rec, sim, st)
        for rank in range(4):
            s = st.get(rank, "dst_q")
            partner = (rank - 2) % 4
            marker = np.int64(1) << (4 * partner)
            assert (s["contrib"] == marker).all(), rank
            assert not (s["wire"] == dataflow.QUANTIZED).any(), rank
        # the install edges are consume-with-scale epilogue events
        eps = [e for e in rec.events(events.DequantEvent) if e.epilogue]
        assert eps and all(e.s_region is not None for e in eps)

    def test_skipped_page_fixture_is_sl008(self):
        rec, findings = _analyze_df_fixture(fixtures.kv_ship_skipped_page)
        assert _rules(findings) == ["SL008"], [f.format() for f in findings]
        msgs = " | ".join(f.message for f in findings)
        assert "chunk missing" in msgs and "hole" in msgs
        assert all(f.severity == Severity.ERROR for f in findings)
        # every rank is short exactly its partner's page
        short = {f.ranks[0] for f in findings if "of source rank" in f.message}
        assert short == set(range(8))

    def test_unpaired_scale_fixture_is_sl009(self):
        rec, findings = _analyze_df_fixture(fixtures.kv_ship_unpaired_scale)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]
        msgs = " | ".join(f.message for f in findings)
        assert "no paired scale-plane RDMA" in msgs
        assert "NO scale folded" in msgs

    def test_src_only_flags_stray_sources(self):
        """The src_only extension itself: a delivery from OUTSIDE the
        declared sender set is flagged even when its byte count looks
        plausible — want is 0 for non-partners."""
        from triton_distributed_tpu.analysis.dataflow import (
            DeliveryContract,
        )

        spec, in_shapes, _ = fixtures.skipped_chunk()
        _, findings = analyze_spec(
            spec, in_shapes(4), 4, kernel_name="stray", site="fixture",
            contract=DeliveryContract(
                kind="gather", dst="out_ref",
                src_only=lambda rank, n: {rank},   # only own writes legal
            ),
        )
        dup = [f for f in findings if "duplicated" in f.message]
        assert dup, [f.format() for f in findings]


# ----------------------------------------------- ragged serving family

class TestRaggedFamily:
    """The `flash_decode.ragged_paged` family (ISSUE 6): a LOCAL
    grid kernel analyzed per grid point, its `local` delivery contract,
    and the MC005 lane-reshape deny rule its packing exists to avoid."""

    def test_family_lints_clean_both_meshes(self):
        for n in (4, 8):
            findings = lint_family("flash_decode.ragged_paged", n=n)
            assert findings == [], [f.format() for f in findings]

    def test_family_is_preflighted(self):
        from triton_distributed_tpu.analysis import mosaic_compat

        status, f = mosaic_compat.preflight_family(
            families()["flash_decode.ragged_paged"], 4
        )
        assert status == "scanned" and f == []

    def test_grid_walk_runs_every_row(self):
        """The symbolic evaluator executes one kernel run PER GRID
        POINT: both rows' out spans carry write events (a single-
        invocation evaluation would leave row 1's span untouched and
        the contract pass blind to it)."""
        rec, _ = analyze_family(families()["flash_decode.ragged_paged"], 4)
        writes = [
            e.dst_region for e in rec.traces[0]
            if isinstance(e, events.PutEvent) and e.local
            and e.dst_region.ref == "ref10"
        ]
        starts = sorted(r.lo[1] for r in writes)
        assert starts == [0, 8]            # one out-DMA per packed row

    def test_tree_sibling_fixture_is_sl008(self):
        """Seeded masked-coverage true-positive: a TREE row whose
        ancestry bitmask smuggles a SIBLING-branch bit (anc not closed
        under the parent pointers) — balanced semaphores, full byte
        coverage; only the contract's topology facet can reject it."""
        spec, in_shapes, contract, init = fixtures.ragged_tree_sibling()
        _, findings = analyze_spec(
            spec, in_shapes(4), 4, kernel_name="ragged_tree_sibling",
            site="fixture", contract=contract, init=init,
        )
        sib = [f for f in findings if f.rule == "SL008"]
        assert sib, [f.format() for f in findings]
        assert all("sibling" in f.message for f in sib)
        assert all(f.severity == Severity.ERROR for f in sib)

    def test_topo_meta_inferred_both_meshes(self):
        """The masked-coverage facet is INFERRED, not just declared:
        contract inference detects the topology operand from the
        scalar-prefetch profile at mesh 4 AND 8, agrees with the
        declared facet (no SL012), and carries the width."""
        from triton_distributed_tpu.analysis import contract_infer

        for n in (4, 8):
            res = contract_infer.infer_family(
                families()["flash_decode.ragged_paged"], n)
            assert res.findings == [], [f.format() for f in res.findings]
            assert res.contract.topo == {
                "ref": 4, "kv_lens": 1, "q_lens": 2, "width": 8}

    def test_ragged_hole_fixture_is_sl008(self):
        spec, in_shapes, contract = fixtures.ragged_hole()
        _, findings = analyze_spec(
            spec, in_shapes(4), 4, kernel_name="ragged_hole",
            site="fixture", contract=contract,
        )
        holes = [f for f in findings if f.rule == "SL008"]
        assert holes and all("hole" in f.message for f in holes)
        assert all(f.severity == Severity.ERROR for f in holes)

    def test_lane_reshape_fixture_is_mc005(self):
        from triton_distributed_tpu.analysis import mosaic_compat

        spec, in_shapes = fixtures.lane_reshape()
        f = mosaic_compat.preflight_spec(
            spec, in_shapes(8), 8, kernel_name="fixture_lane_reshape"
        )
        assert [x.rule for x in f] == ["MC005"]
        assert "lane" in f[0].message

    def test_unit_collapse_reshape_not_flagged(self):
        """The supported reshape form — unit dims dropped, lane dim
        kept — must pass MC005 (the existing kernels' idiom)."""
        from triton_distributed_tpu.analysis import mosaic_compat
        from triton_distributed_tpu.analysis.fixtures import _spec

        def kernel(x_ref, out_ref):
            import jax.numpy as jnp

            out_ref[...] = jnp.reshape(x_ref[...], (8, 128))  # (1,8,128)

        f = mosaic_compat.preflight_spec(
            _spec(kernel, "fixture_unit_collapse",
                  out_shapes=[((8, 128), np.dtype(np.float32))]),
            [((1, 8, 128), np.dtype(np.float32))], 8,
            kernel_name="unit_collapse",
        )
        assert [x.rule for x in f] == []


# -------------------------------------------- grid-schedule mutations

class TestGridScheduleMutations:
    """The PR-15 grid-schedule legality gate, pinned through its
    mutation fixtures: each is the REAL production builder under a
    mutated :class:`GridSchedule`, and each must land on its exact rule
    ID — the shapes of wrongness the grid enumerator's oracle exists to
    reject (a gate that cannot reject is not a gate)."""

    def test_overwide_block_q_is_sl008(self):
        """block_q=32 past the 16-token parking cap: the q-window and
        out-DMA overrun the zero-slack gate buffer — OOB + coverage
        SL008, nothing else (the protocol pass is blind to it)."""
        rec, findings = _analyze_df_fixture(
            fixtures.grid_ragged_overwide_block)
        assert _rules(findings) == ["SL008"], [f.format() for f in findings]
        assert all(f.severity == Severity.ERROR for f in findings)

    def test_coalesced_drop_rail_is_sl009(self):
        """coalesce=2 ticks shipping payload-only: every page lands at
        its slot but no scale plane accompanies it and the install has
        no fold — exactly SL009 (contract=None keeps the permute pass's
        SL008 for the missing scale deliveries out of the pin)."""
        rec, findings = _analyze_df_fixture(
            fixtures.grid_kv_ship_dropped_scale)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]
        msgs = " | ".join(f.message for f in findings)
        assert "scale" in msgs

    def test_gemm_rs_shared_rail_is_sl009(self):
        """rail='shared' on the int8-MXU fused GEMM-RS: scale arrivals
        signal the payload's recv semaphore — credits balance, only the
        rail-pairing replay can reject it."""
        rec, findings = _analyze_df_fixture(
            fixtures.grid_gemm_rs_shared_rail)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]

    def test_grid_families_lint_clean_default(self):
        """The other half of the oracle pin: the DEFAULT grid schedule
        gates clean for all three families at mesh 4 AND 8 (the
        candidate production actually runs must never be rejected)."""
        from triton_distributed_tpu.tune.schedule import (
            GRID_DEFAULT,
            check_schedule,
            grid_families,
        )

        for fam in grid_families():
            for n in (4, 8):
                findings = check_schedule(fam, GRID_DEFAULT, n)
                assert findings == [], (
                    fam, n, [f.format() for f in findings])


# -------------------------------------- CP + grad-ring train families

class TestCPTrainFamilies:
    """The training subsystem's lint families (ISSUE 14): the
    context-parallel attention rings (``cp.ring_attention`` KV
    rotation, ``cp.ulysses`` a2a) and the quantized gradient ring
    (``grad_ring.stream_int8w``), plus their seeded schedule-mutation
    fixtures."""

    FAMILIES = (
        "cp.ring_attention", "cp.ulysses", "grad_ring.stream_int8w",
    )

    def test_families_lint_clean_both_meshes(self):
        for name in self.FAMILIES:
            for n in (4, 8):
                findings = lint_family(name, n=n)
                assert findings == [], (name, [f.format() for f in findings])

    def test_families_are_preflighted(self):
        for name in self.FAMILIES:
            status, f = mosaic_compat.preflight_family(families()[name], 8)
            assert status == "scanned" and f == [], (name, f)

    def test_families_have_degradation_targets(self):
        from triton_distributed_tpu.kernels.registry import (
            missing_degradation_targets,
        )

        missing = {f.name for f in missing_degradation_targets()}
        assert not (missing & set(self.FAMILIES))

    def test_skipped_block_fixture_is_sl008(self):
        rec, findings = _analyze_df_fixture(fixtures.cp_ring_skipped_block)
        assert _rules(findings) == ["SL008"], [f.format() for f in findings]
        assert all(f.severity == Severity.ERROR for f in findings)

    def test_unpaired_scale_fixture_is_sl009(self):
        rec, findings = _analyze_df_fixture(fixtures.grad_ring_unpaired_scale)
        assert _rules(findings) == ["SL009"], [f.format() for f in findings]


# ------------------------------------------------- contract inference (17)

def _infer_fixture(fx, n=8):
    """Run one 4-tuple contract fixture (spec, in_shapes, declared,
    degrades_to) through the inference diff."""
    from triton_distributed_tpu.analysis import abstract, contract_infer

    spec, in_shapes, declared, twin = fx()
    rec = abstract.run_symbolic(
        spec, in_shapes(n), n, kernel_name=fx.__name__, site="fixture")
    return rec, contract_infer.infer_spec(
        rec, degrades_to=twin, declared=declared)


class TestContractInference:
    """ISSUE 17 tentpole: SL008 obligations derived from the XLA twin
    + replay provenance, hand-written contracts demoted to assertions.
    """

    def test_registry_complete_targets_and_contracts(self):
        """Satellite: every registered family resolves its degrades_to
        dotted path AND carries a declared-or-inferred delivery
        contract — the `bench.py --lint` silent-gap check, promoted to
        tier-1."""
        from triton_distributed_tpu.analysis import contract_infer
        from triton_distributed_tpu.kernels.registry import (
            resolve_degradation_target,
        )

        for name, fam in sorted(families().items()):
            assert fam.degrades_to, f"{name}: no degradation target"
            assert resolve_degradation_target(fam.degrades_to) is not None
            contract = fam.contract
            if contract is None:
                contract = contract_infer.infer_family(fam, 4).contract
            assert contract is not None, (
                f"{name}: neither a declared nor an inferable contract")

    @pytest.mark.parametrize("n", [4, 8])
    def test_inferred_agrees_with_declared_whole_registry(self, n):
        """Acceptance: inferred contracts agree with declared ones for
        ALL registered families at mesh 4 and 8 — no silent allow. Any
        SL012/SL013 here is either a real contract bug or twin drift;
        fix the declaration (or the kernel), don't relax this test."""
        findings = lint_all(n=n, infer_contracts=True)
        assert findings == [], [f.format() for f in findings]

    def test_twins_actually_execute(self):
        """The verdicts above must come from EXECUTED twins (conftest
        provides 8 host devices), not the static class table — a tabled
        profile can't measure payloads."""
        from triton_distributed_tpu.analysis import contract_infer

        for name in ("allgather.ring_1d", "reduce_scatter.ring",
                     "all_to_all.dense", "kv_ship.pages",
                     "flash_decode.ragged_paged", "moe_tp.reduce_rs",
                     "grad_ring.stream_int8w", "cp.ring_attention"):
            res = contract_infer.infer_family(families()[name], 4)
            assert res.profile.executed, (name, res.profile.detail)

    def test_sl012_on_declared_gather_that_reduces(self):
        """Seeded true-positive: the REAL reduce-scatter ring declared
        `kind='gather'`. The twin delivers class 'fold'; the kind-class
        diff names the declaration as the bug."""
        _, res = _infer_fixture(fixtures.contract_declares_gather_actually_reduces)
        assert "SL012" in _rules(res.findings), (
            [f.format() for f in res.findings])
        f = next(f for f in res.findings if f.rule == "SL012")
        assert "class 'fold'" in f.message and "gather" in f.message
        assert f.severity == Severity.ERROR

    def test_sl012_on_overdeclared_payload(self):
        """Seeded true-positive: the REAL AG ring declaring twice the
        per-source payload the kernel lands. Kind and dst are right —
        only the measured modal payload can catch it."""
        _, res = _infer_fixture(fixtures.contract_overdeclared_payload)
        rules = [f.rule for f in res.findings]
        assert rules == ["SL012"], [f.format() for f in res.findings]
        assert "over-declares" in res.findings[0].message
        assert "2048" in res.findings[0].message
        assert "1024" in res.findings[0].message

    def test_sl013_on_undeclared_contract_and_sl008_still_bites(self):
        """Acceptance: a family with contract=None draws SL013, AND the
        inferred contract keeps SL008 live — the skipped-chunk schedule
        mutation (a real AG ring one source short) is still caught with
        no declaration anywhere in sight."""
        from triton_distributed_tpu.analysis import (
            abstract,
            checks,
            contract_infer,
        )

        spec, in_shapes, _declared = fixtures.schedule_skipped_chunk()
        rec = abstract.run_symbolic(
            spec, in_shapes(8), 8, kernel_name="fx_skip", site="fixture")
        res = contract_infer.infer_spec(
            rec, degrades_to="jax.lax.all_gather", declared=None)
        assert _rules(res.findings) == ["SL013"]
        assert res.findings[0].severity == Severity.WARNING
        # the twin pins src_only=None (all sources) — the kernel's own
        # skip cannot launder itself into the inferred topology
        assert res.contract is not None and res.contract.src_only is None
        findings = checks.check_family(
            rec, contract=None, fallback_contract=res.contract)
        assert "SL008" in _rules(findings), [f.format() for f in findings]
        assert any("chunk missing" in f.message for f in findings
                   if f.rule == "SL008")

    def test_sl013_clean_family_passes_sl008_via_inferred(self):
        """The SL013 path on a CORRECT kernel: stripping a clean
        family's declaration yields exactly the warning — the inferred
        contract runs SL008 and it passes."""
        import dataclasses

        fam = dataclasses.replace(
            families()["allgather.ring_1d"], contract=None)
        _, findings = analyze_family(fam, 4, infer_contracts=True)
        assert _rules(findings) == ["SL013"], (
            [f.format() for f in findings])

    def test_inference_is_opt_in(self):
        """Without infer_contracts, a contract=None family draws no
        SL013 and no SL008 — exactly the pre-existing silent gap this
        subsystem exists to surface (pinned so the default path stays
        byte-identical for downstream consumers)."""
        import dataclasses

        fam = dataclasses.replace(
            families()["allgather.ring_1d"], contract=None)
        _, findings = analyze_family(fam, 4)
        assert findings == []

    def test_strict_registration_gate(self):
        """TDTPU_LINT_STRICT=1 re-verifies declared contracts at
        registration (memoized one-shot) — the current registry must
        pass it."""
        import os
        from triton_distributed_tpu.kernels import registry

        old = os.environ.get("TDTPU_LINT_STRICT")
        saved = registry._STRICT_VERIFIED
        registry._STRICT_VERIFIED = None
        os.environ["TDTPU_LINT_STRICT"] = "1"
        try:
            fams = registry.families()
            assert len(fams) >= 27
            assert registry._STRICT_VERIFIED is True
        finally:
            registry._STRICT_VERIFIED = saved
            if old is None:
                os.environ.pop("TDTPU_LINT_STRICT", None)
            else:
                os.environ["TDTPU_LINT_STRICT"] = old

    def test_cli_infer_contracts_flag(self, capsys):
        assert lint_main(["--mesh", "4", "--kernel", "allgather.ring_1d",
                          "--infer-contracts"]) == 0
        assert "0 error(s)" in capsys.readouterr().err


# ------------------------------------------------------- docs coverage (17)

class TestLintDocs:
    def test_every_emitted_code_is_documented(self):
        """Satellite: grep every finding code emitted anywhere under
        analysis/ (plus the full RULES catalog) and fail on any code
        docs/LINT.md does not carry a table row for."""
        import pathlib
        import re

        repo = pathlib.Path(__file__).resolve().parents[1]
        analysis_dir = (repo / "triton_distributed_tpu" / "analysis")
        emitted = set(RULES)
        pat = re.compile(r'["\'](SL\d{3}|MC\d{3}|SV\d{3})["\']')
        for py in analysis_dir.glob("*.py"):
            emitted |= set(pat.findall(py.read_text()))
        doc = (repo / "docs" / "LINT.md").read_text()
        documented = {
            m.group(1)
            for m in re.finditer(r"^\|\s*(SL\d{3}|MC\d{3}|SV\d{3})\s*\|",
                                 doc, re.MULTILINE)
        }
        undocumented = emitted - documented
        assert not undocumented, (
            f"finding codes emitted in analysis/ but missing a "
            f"docs/LINT.md table row: {sorted(undocumented)}")
        # and the table must not document codes the catalog disowns
        phantom = documented - set(RULES)
        assert not phantom, (
            f"docs/LINT.md documents codes not in the RULES catalog: "
            f"{sorted(phantom)}")
