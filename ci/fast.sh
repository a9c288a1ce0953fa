#!/usr/bin/env bash
# fast: the < 5-minute tier-1 subset (ROADMAP CI-budget item, closed
# round 7) and the lint-side gates.
#
# 1. The `fast`-marked test modules: the static analysis suite
#    (shmemlint + the Mosaic-compat pre-flight), the fault engine, the
#    host-level runtime/topology logic, the wire-layout/XLA-twin tests,
#    the lang-layer slices, the tools, and the serving / fleet /
#    training suites that hold what this script's inline "ISSUE N
#    acceptance" smokes used to repeat (PR 48 cut them):
#      fleet failover, elastic grow + drain   tests/test_fleet.py
#      speculation, tree drafts, prefix dedup tests/test_speculation.py
#      multi-tenant flood + ReplicaDeath      tests/test_multitenant.py
#      the dp×tp×cp train step on the EF ring tests/test_train.py
#      cp-sharded long-context decode         tests/test_longcontext.py
#      slice-death failover, probation        tests/test_health.py
# 2. The gates no timing run should start without: the schedule-search
#    oracle smokes, the degradation-target gates, contract inference and
#    servlint (`python bench.py --lint` runs the same gates before its
#    kernel timers).
#
# Everything that answers "did I just break a protocol, a contract, or
# the host plumbing?" without paying for the big interpreted model
# suites. Use it as the inner-loop gate; the full tier-1 run remains
# the merge gate, and serving speed is `benchmark/run.py`'s.
#
#   ci/fast.sh              # the subset
#   ci/fast.sh -x -k wire   # extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'fast and not slow' \
  -p no:cacheprovider "$@"

# Bounded schedule-search smoke: enumerate + mutate one ring family,
# replay every candidate through shmemlint + the Mosaic pre-flight, and
# require that the oracle rejected at least one mutation (stable rule
# IDs) AND produced a lint-clean pick. Exits 2 if the gate is unwired.
JAX_PLATFORMS=cpu python -m triton_distributed_tpu.tune.schedule \
  --family ag_gemm.fused --mesh 8

# Same oracle over the ISSUE-14 gradient ring: the scale_rail=payload
# mutation must be rejected with a stable rule ID (SL009 — scales must
# ride the sideband rail, never the int8 payload) and the clean
# schedule must win.
JAX_PLATFORMS=cpu python -m triton_distributed_tpu.tune.schedule \
  --family grad_ring.stream_int8w --mesh 8

# Bounded GRID-schedule smoke (PR-15): the three grid families —
# ragged paged attention (block_q/n_bufs/pack_rows), kv_ship page
# coalescing, and the GEMM-RS int8-MXU epilogue — each enumerate their
# freedom product + mutations through the same oracle. Exits 2 unless
# at least one candidate was rejected with a stable rule ID (the
# over-wide block's SL008, the dropped/shared scale rail's SL009) AND
# a lint-clean pick landed. Mesh 8 here; the pytest suite pins mesh 4.
JAX_PLATFORMS=cpu python -m triton_distributed_tpu.tune.schedule \
  --family flash_decode.ragged_paged --mesh 8
JAX_PLATFORMS=cpu python -m triton_distributed_tpu.tune.schedule \
  --family kv_ship.pages --mesh 8
JAX_PLATFORMS=cpu python -m triton_distributed_tpu.tune.schedule \
  --family gemm_rs.mx_epilogue --mesh 8

# Degradation-target gate (the `bench.py --lint` check, standalone):
# every registered kernel family must name a degradation target that
# resolves to a real callable — a family without a declared fallback
# is a robustness hole, not a style nit.
JAX_PLATFORMS=cpu python - <<'EOF'
from triton_distributed_tpu.kernels.registry import (
    missing_degradation_targets,
)

gaps = missing_degradation_targets()
assert not gaps, f"families without a resolvable degradation target: {gaps}"
print(f"degradation targets: all families declare a resolvable fallback")
EOF

# Training-family gate (the `bench.py --lint` train_gaps check,
# standalone): the train step's collective families — the CP attention
# rings and the quantized gradient ring — must be registered, lint
# clean, and declare a resolvable degradation target, or the trainer's
# ledger demotion (wire ring -> exact psum twin) rests on an unverified
# fallback. tests/test_train.py holds the same check in tier-1.
JAX_PLATFORMS=cpu python - <<'EOF'
from triton_distributed_tpu.analysis.lint import lint_family
from triton_distributed_tpu.kernels.registry import (
    missing_degradation_targets,
)
from triton_distributed_tpu.train import TRAIN_ENGINE_FAMILIES

gaps = {fam for fam, _ in missing_degradation_targets()}
for fam in TRAIN_ENGINE_FAMILIES:
    findings = lint_family(fam, n=8)
    assert findings == [], f"{fam} lints dirty: {findings}"
    assert fam not in gaps, f"{fam} has a degradation gap"
print(f"training families: {len(TRAIN_ENGINE_FAMILIES)} lint-clean "
      f"with declared fallbacks")
EOF

# Contract-inference smoke (ISSUE 17 acceptance): derive the delivery
# contract of one family per twin class from the XLA twin + replay
# provenance at mesh 4 and diff it against the declaration — a drifted
# declaration (SL012) or a silently missing one (SL013) fails CI in
# seconds. The full-registry sweep at mesh 4 AND 8 lives in the pytest
# suite; this step keeps the fast path to one family per class:
# gather (ring AG), reduce (ring RS), permute (dense a2a), local
# (ragged paged attention).
JAX_PLATFORMS=cpu python - <<'EOF'
import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

from triton_distributed_tpu.analysis import contract_infer
from triton_distributed_tpu.kernels.registry import families

fams = families()
drifted = []
for name in ("allgather.ring_1d", "reduce_scatter.ring",
             "all_to_all.dense", "flash_decode.ragged_paged"):
    res = contract_infer.infer_family(fams[name], 4)
    assert res.profile.executed, (
        f"{name}: twin not executed ({res.profile.detail})")
    if res.findings:
        drifted.append((name, [f.format() for f in res.findings]))
assert not drifted, f"contract inference drift: {drifted}"
print("contract inference: ring AG / ring RS / dense a2a / ragged "
      "local all agree with their declared contracts at mesh 4")
EOF

# Serving-protocol model-check smoke (ISSUE 19 acceptance): servlint's
# bounded exhaustive exploration over the production ProtocolOps seam
# must visit >= 1000 states with ZERO findings in <= 5 s, and every
# seeded mutated-ops fixture (SV001..SV007) must be caught — exit 2 —
# by exactly its rule.
JAX_PLATFORMS=cpu python - <<'EOF2'
import time

from triton_distributed_tpu.analysis import servlint

t0 = time.perf_counter()
findings, stats = servlint.lint_serving(max_states=2000)
dt = time.perf_counter() - t0
assert findings == [], (
    f"servlint smoke: production ops produced findings: "
    f"{[f.format() for f in findings]}")
assert stats["states"] >= 1000, (
    f"servlint smoke: only {stats['states']} states explored (< 1000)")
assert dt <= 5.0, (
    f"servlint smoke: exploration took {dt:.1f}s (> 5s budget)")
print(f"servlint smoke: {stats['states']} states / "
      f"{stats['transitions']} transitions clean in {dt:.2f}s")
EOF2
for rule in SV001 SV001cp SV002 SV003 SV004 SV005 SV006 SV007; do
  rc=0
  JAX_PLATFORMS=cpu python -m triton_distributed_tpu.analysis.lint \
    --serving-fixture "$rule" >/dev/null 2>&1 || rc=$?
  if [ "$rc" -ne 2 ]; then
    echo "servlint smoke: fixture $rule exited $rc (want 2)" >&2
    exit 1
  fi
done
echo "servlint smoke: all 8 seeded fixtures caught (exit 2 each)"
