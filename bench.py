"""Driver benchmark: fused AG-GEMM throughput on the north-star TP shape.

Measures the flagship overlap op (BASELINE.md north-star: fused AG-GEMM on
Llama-7B TP shapes, reference tutorial 07 / test_ag_gemm.py) on whatever
devices are present — the one real TPU chip under the driver, or the
virtual CPU mesh during development.

Methodology (the round-1 numbers were dispatch-overhead artifacts):

* Every timing is an **in-jit ``lax.fori_loop``** whose carry chains each
  iteration's output back into the next iteration's input, timed as the
  *difference* between a high and a low iteration count — the host's
  per-dispatch cost (launch, argument handling, the result fetch)
  cancels out.
* The loop dependency folds ``jnp.sum(out)`` into the carry so XLA cannot
  narrow the benched computation to the part feeding one element (it
  will happily turn ``dot(a, b)[0, 0]`` into a dot-product).
* The fence is a host fetch of the loop's scalar result: it cannot
  return before the last iteration has run.
* Numbers are reported with ``device_kind`` and MFU / %-of-SOL against
  ``tune.perf_model.detect_spec()`` so they are explainable as
  %-of-speed-of-light.

Prints ONE JSON line on stdout:
  {"metric": "ag_gemm_tflops_per_chip", "value": N, "unit": "TFLOP/s",
   "vs_baseline": speedup_vs_unoverlapped, ...}

``vs_baseline`` compares the fused flagship engine against the
unoverlapped baseline (all_gather → dot, ≡ the reference's torch_ag_gemm
cuBLAS+NCCL baseline, test_ag_gemm.py) measured the same way on the same
hardware; the baseline's own TFLOPs ride along so both sides are visible.
Secondary metrics (gemm_rs, grouped-GEMM MFU, MoE a2a transport,
flash-decode HBM%) go to stderr, one JSON line each.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

# CPU dev-box runs (JAX_PLATFORMS=cpu) get the same virtual 8-device
# mesh the test harness uses (tests/conftest.py): the multi-rank rows —
# the 2×(n/2) disaggregated serving split, the DCN rails, the ring
# engines — then exercise their real cross-device paths instead of
# degenerating to n=1. Real-TPU runs are untouched.
if os.environ.get("JAX_PLATFORMS") == "cpu":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _make_runner(step, state, iters):
    """Jitted (state → scalar) fori_loop runner, compiled and warmed —
    the one timing-runner construction both bench_loop and bench_paired
    use (the double float() is compile + steady-state warm; the host
    fetch of the scalar is the fence)."""

    @jax.jit
    def run(state):
        def body(i, carry):
            return step(*carry)

        return jax.lax.fori_loop(0, iters, body, (state, jnp.float32(0)))[1]

    float(run(state))
    float(run(state))
    return run


def _make_donating_runner(step, state, iters, donate_idx):
    """Runner that DONATES ``state[donate_idx]`` — a persistent-
    workspace carry (e.g. the barrier-free LL MoE state, whose protocol
    requires the SAME physical buffers across invocations: skewed peers'
    in-flight DMAs target the persistent addresses). Each invocation
    consumes the donated tree and returns the final carry's version, so
    callers THREAD it: ``d, s = call(d)`` — the run/donate protocol of
    the serving step (models/transformer._serving_jit). The float
    fetch is inside ``call`` (the fence, as in :func:`_make_runner`)."""
    state = tuple(state)
    rest = state[:donate_idx] + (None,) + state[donate_idx + 1:]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(rest_in, dstate):
        full = rest_in[:donate_idx] + (dstate,) + rest_in[donate_idx + 1:]

        def body(i, carry):
            return step(*carry)

        fstate, s = jax.lax.fori_loop(
            0, iters, body, (full, jnp.float32(0))
        )
        return fstate[donate_idx], s

    def call(dstate):
        d, s = run(rest, dstate)
        return d, float(s)

    return call


def bench_loop(step, state, *, lo=4, hi=20, reps=5, donate_idx=None):
    """Time ``step`` (state, s) -> (state, s) via in-jit fori_loop deltas.

    Returns seconds per iteration. ``s`` is a f32 scalar the step must
    fold a full-output reduction into (the anti-DCE / anti-narrowing
    dependency); fetching it on the host is the execution fence.

    A single (lo, hi) pair is noisy (the host shares its cores with
    whatever else runs there); each rep measures the pair back-to-back
    (slowly-varying interference hits both sides) and the median paired
    delta is used. Callers size (hi - lo) so the expected delta dwarfs
    dispatch jitter.

    ``donate_idx``: position in ``state`` of a persistent-workspace
    carry to donate-and-thread across every runner invocation (see
    :func:`_make_donating_runner`) — without it, re-invoking jitted
    programs with non-donated workspaces would break the LL persistent-
    buffer contract at n>1 (each invocation would get fresh placements
    while peers RDMA into the old addresses).
    """
    if donate_idx is not None:
        state = tuple(state)
        run_lo = _make_donating_runner(step, state, lo, donate_idx)
        run_hi = _make_donating_runner(step, state, hi, donate_idx)
        d = state[donate_idx]
        for r in (run_lo, run_lo, run_hi, run_hi):   # compile + steady warm
            d, _ = r(d)
        deltas = []
        for _ in range(reps):
            t0 = time.perf_counter()
            d, _ = run_lo(d)
            t1 = time.perf_counter()
            d, _ = run_hi(d)
            deltas.append((time.perf_counter() - t1) - (t1 - t0))
    else:
        run_lo = _make_runner(step, state, lo)
        run_hi = _make_runner(step, state, hi)
        deltas = []
        for _ in range(reps):
            t0 = time.perf_counter()
            float(run_lo(state))
            t1 = time.perf_counter()
            float(run_hi(state))
            deltas.append((time.perf_counter() - t1) - (t1 - t0))
    dt = float(np.median(deltas)) / (hi - lo)
    if dt <= 0:
        raise RuntimeError(
            f"bench_loop: non-positive median timing delta over {reps} reps "
            f"(lo={lo}, hi={hi}) — noise swamped the measurement; raise the "
            "iteration counts"
        )
    return dt


def perturb(a, s):
    """Tiny dynamic data dependency: keeps the loop carry live without
    changing values beyond an underflowing-to-zero epsilon."""
    return a + (s * jnp.float32(1e-30)).astype(a.dtype)


def bench_paired(step_a, step_b, state, *, lo=8, hi=40, reps=11):
    """Paired A-vs-B timing: per rep, A's and B's (lo, hi) fori_loop
    deltas run back-to-back IN SNAKE ORDER (A,B then B,A on alternating
    reps — a monotonic interference ramp hits whichever side runs later,
    so a fixed order would bias every pair's ratio the same way; the
    alternation makes the bias cancel across reps, the same fix
    autotuner._bench applies to config ranking). Returns (median t_a,
    median t_b, median of per-pair t_b/t_a ratios, (q25, q75) of the
    ratios)."""
    a_lo, a_hi = _make_runner(step_a, state, lo), _make_runner(step_a, state, hi)
    b_lo, b_hi = _make_runner(step_b, state, lo), _make_runner(step_b, state, hi)

    def delta(r_lo, r_hi):
        t0 = time.perf_counter()
        float(r_lo(state))
        t1 = time.perf_counter()
        float(r_hi(state))
        return ((time.perf_counter() - t1) - (t1 - t0)) / (hi - lo)

    ratios, tas, tbs = [], [], []
    for rep in range(reps):
        if rep % 2 == 0:
            ta = delta(a_lo, a_hi)
            tb = delta(b_lo, b_hi)
        else:
            tb = delta(b_lo, b_hi)
            ta = delta(a_lo, a_hi)
        if ta > 0 and tb > 0:
            ratios.append(tb / ta)
            tas.append(ta)
            tbs.append(tb)
    if not ratios:
        # every rep lost a side to noise (µs-scale CPU deltas): one
        # last-resort UNPAIRED attempt, reported as untrusted (NaN IQR
        # + stderr warning) — fabricated confidence would be worse than
        # aborting, and a still-negative delta does abort
        ta = delta(a_lo, a_hi)
        tb = delta(b_lo, b_hi)
        if ta <= 0 or tb <= 0:
            raise RuntimeError(
                "bench_paired: no positive paired deltas and the "
                "unpaired fallback is non-positive too — noise swamped "
                "the measurement; raise lo/hi"
            )
        print(
            json.dumps({
                "warning": "bench_paired fell back to a single UNPAIRED "
                "comparison (all paired reps lost a side to noise); "
                "ratio is order-biased and IQR is undefined",
            }),
            file=sys.stderr, flush=True,
        )
        return ta, tb, tb / ta, (float("nan"), float("nan"))
    tas, tbs, ratios = map(np.asarray, (tas, tbs, ratios))
    # outlier rejection: an interference burst on one side of a pair
    # collapses (or inflates) that delta and its ratio explodes — keep
    # pairs whose BOTH deltas sit within 2× of their medians, so the
    # reported IQR reflects the protocol, not the host's worst burst
    ma, mb = np.median(tas), np.median(tbs)
    keep = (
        (tas > 0.5 * ma) & (tas < 2 * ma)
        & (tbs > 0.5 * mb) & (tbs < 2 * mb)
    )
    if keep.any():
        tas, tbs, ratios = tas[keep], tbs[keep], ratios[keep]
    return (
        float(np.median(tas)),
        float(np.median(tbs)),
        float(np.median(ratios)),
        (float(np.percentile(ratios, 25)), float(np.percentile(ratios, 75))),
    )


def _parse_args(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="triton_distributed_tpu driver benchmark"
    )
    ap.add_argument(
        "--lint", action="store_true",
        help="run shmemlint (protocol SL001-007, delivery/wire dataflow "
        "SL008-010) plus the Mosaic-compat pre-flight (MC001-004) over "
        "the benched kernel families BEFORE any timing; abort (exit 2) "
        "on errors so a broken protocol — or a kernel Mosaic would "
        "reject mid-run — fails in seconds instead of hanging the "
        "timed run",
    )
    ap.add_argument(
        "--infer-contracts", action="store_true",
        help="with --lint: additionally derive each family's delivery "
        "contract from its XLA twin (rank-tagged execution + replay "
        "provenance) and diff it against the declared one — SL012 on "
        "drift, SL013 on a family registered without a declaration "
        "(SL008 runs on the inferred contract there). Needs enough "
        "host devices to execute the twins; falls back to the static "
        "class table otherwise",
    )
    ap.add_argument(
        "--dryrun", action="store_true",
        help="hardware-free engine exercise: run ONLY the "
        "serving_continuous bench at interpreter-tiny shapes (whatever "
        "the platform) and exit — with --faults, the fault plan is "
        "active inside the ragged kernel and the scheduler's "
        "eviction/degradation behavior runs under it (the robustness "
        "follow-on: chaos-line replay without a TPU)",
    )
    ap.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="replay a nightly chaos line on real hardware: a "
        "(seed, faults) spec, e.g. \"seed=7; Delay(site=allgather, "
        "rank=2, cycles=50000)\" or the JSON twin (see "
        "runtime.faults.parse_plan). The plan is active for every "
        "benched collective.",
    )
    ap.add_argument(
        "--n-layers", type=int, default=None, metavar="L",
        help="serving benches: override the model depth (default 1). "
        "The serving-state donation path only shows its cost at depth "
        "> 1 — per-layer pool bytes are reported so the sweep is "
        "explainable (ISSUE-12 satellite / ISSUE-6 follow-on)",
    )
    ap.add_argument(
        "--tree", action="store_true",
        help="serving_speculative: the tree-speculation paired row — "
        "spec_tree verify trees (TreeDrafter sibling branches) vs "
        "linear draft-k on a branchy SAMPLED motif trace (token "
        "mismatches must be 0 and accepted/step strictly above "
        "linear), plus the in-batch shared-prefix dedup row "
        "(deduped pages > 0, token-exact; ISSUE-18)",
    )
    ap.add_argument(
        "--spec-k", type=int, default=None, metavar="K",
        help="serving_fleet: run SPECULATIVE replicas (draft-k K, "
        "ngram drafter) against a non-speculative fleet on the "
        "IDENTICAL trace — per-replica accepted-tokens/step and the "
        "goodput ratio are reported (ISSUE-13 satellite)",
    )
    ap.add_argument(
        "scenario", nargs="?", default=None,
        help="run ONLY this named scenario (currently: serving_fleet "
        "— the multi-replica router bench, --spec-k K for speculative "
        "replicas — serving_speculative — the draft-k speculative "
        "engine vs the plain engine, colocated AND disaggregated — or "
        "serving_elastic — autoscale grow from a reserve mesh, a "
        "mid-trace drain with live KV-page migration — or "
        "serving_multitenant — priority preemption, deadline routing "
        "and brownout shedding under a 4x batch flood — or "
        "serving_longcontext — context-parallel decode over the "
        "cp-sharded page pool: a request k× one pool shard served "
        "token-exact vs the single-slice oracle, the short-request "
        "goodput tax, and the priced long-context placement verdict "
        "(ISSUE-20); all compose "
        "with --dryrun and --faults, e.g. the ISSUE-16 acceptance "
        "line 'serving_multitenant --dryrun --faults \"seed=1; "
        "ReplicaDeath(replica=1, step=8)\"' — or train_step — the "
        "dp×tp×cp train step on the int8 EF gradient ring vs the "
        "single-device reference and the exact psum twin, ISSUE-14)",
    )
    return ap.parse_args(argv)


def _run_lint(infer_contracts: bool = False) -> None:
    """bench --lint: static protocol + dataflow + Mosaic-compat passes
    over the benched kernel set (exit 2 on errors — unchanged
    contract; the dataflow rules ride inside lint_all, the pre-flight
    is its own sweep). ``infer_contracts`` additionally diffs every
    declared delivery contract against the twin-inferred one (SL012 /
    SL013 ride inside the findings stream like any other rule)."""
    from triton_distributed_tpu.analysis import lint as shmemlint
    from triton_distributed_tpu.analysis import mosaic_compat
    from triton_distributed_tpu.analysis.findings import (
        Severity,
        rule_counts,
    )

    findings = shmemlint.lint_all(n=8, infer_contracts=infer_contracts)
    if infer_contracts:
        print(
            json.dumps({"lint_contract_inference": {
                "mesh": 8,
                "drift": sum(f.rule == "SL012" for f in findings),
                "undeclared": sum(f.rule == "SL013" for f in findings),
            }}),
            file=sys.stderr, flush=True,
        )
    mc, report = mosaic_compat.preflight_all(n=8)
    findings += mc
    for f in findings:
        print(json.dumps({"lint": f.to_json()}), file=sys.stderr, flush=True)
    # re-gate every persisted schedule-search winner: a cached schedule
    # is trusted by the op resolve paths with zero checks at load time,
    # so --lint is where a stale/corrupt entry gets caught
    from triton_distributed_tpu.tune import schedule as sched_lib

    for key, entry in sched_lib.stored_entries().items():
        fam = entry.get("family")
        try:
            # kind-aware rebuild: grid winners replay as GridSchedule
            # through the same gate as ring winners
            sched = sched_lib.schedule_from_entry(entry)
            if sched is None:
                raise ValueError(f"unparseable store entry {key!r}")
            extra = sched_lib.check_schedule(fam, sched, 8)
        except Exception as e:
            print(
                json.dumps({"lint_schedule_cache": key,
                            "error": f"{type(e).__name__}: {e}"[:200]}),
                file=sys.stderr, flush=True,
            )
            continue
        findings += extra
        print(
            json.dumps({"lint_schedule_cache": key,
                        "findings": [f.rule for f in extra]}),
            file=sys.stderr, flush=True,
        )

    # degradation-target gate: every registered family must declare a
    # resolvable XLA twin to fall onto (the health ledger's demotion
    # needs somewhere to go — an undeclared target is the silent-gap
    # class docs/ROBUSTNESS.md's matrix documents)
    from triton_distributed_tpu.kernels.registry import (
        missing_degradation_targets,
    )

    gaps = missing_degradation_targets()
    for fam, problem in gaps:
        print(
            json.dumps({"lint_degradation_gap":
                        {"family": fam, "problem": problem}}),
            file=sys.stderr, flush=True,
        )

    # fleet gate (ISSUE 11): every kernel family a fleet replica's
    # engines launch must be REGISTERED with a resolvable degradation
    # target — a replica whose engines cannot degrade is not a safe
    # failover destination, so the router's whole health story would
    # rest on an unverified fallback
    from triton_distributed_tpu.kernels import registry as _registry
    from triton_distributed_tpu.serving.fleet import (
        FLEET_ENGINE_FAMILIES,
    )

    fams = _registry.families()
    gap_names = {f for f, _ in gaps}
    fleet_gaps = []
    for fam in FLEET_ENGINE_FAMILIES:
        if fam not in fams:
            fleet_gaps.append((fam, "fleet replica family not registered"))
        elif fam in gap_names:
            fleet_gaps.append(
                (fam, "fleet replica family has a degradation gap"))
    for fam, problem in fleet_gaps:
        print(
            json.dumps({"lint_fleet_gap":
                        {"family": fam, "problem": problem}}),
            file=sys.stderr, flush=True,
        )

    # speculative gate (ISSUE 12): the kernel families the speculative
    # engine launches — by design the SAME ragged family as the plain
    # engine — must be registered with a resolvable degradation target,
    # so a speculative deployment degrades onto the XLA twin exactly
    # like a plain one (verify rows are ordinary ragged rows there too)
    from triton_distributed_tpu.serving.spec import SPEC_ENGINE_FAMILIES

    spec_gaps = []
    for fam in SPEC_ENGINE_FAMILIES:
        if fam not in fams:
            spec_gaps.append(
                (fam, "speculative engine family not registered"))
        elif fam in gap_names:
            spec_gaps.append(
                (fam, "speculative engine family has a degradation gap"))
    for fam, problem in spec_gaps:
        print(
            json.dumps({"lint_spec_gap":
                        {"family": fam, "problem": problem}}),
            file=sys.stderr, flush=True,
        )

    # migration gate (ISSUE 13): the fleet's replica→replica KV-page
    # migration rides the kv_ship wire families — they must stay
    # registered with a resolvable degradation target, or a drain's
    # migrate-or-finish path would rest on an unverified transport
    # (the fallback when the wire is refused is re-prefill, which is
    # exactly the degradation target story this gate keeps honest)
    from triton_distributed_tpu.serving.fleet import (
        MIGRATION_ENGINE_FAMILIES,
    )

    migration_gaps = []
    for fam in MIGRATION_ENGINE_FAMILIES:
        if fam not in fams:
            migration_gaps.append(
                (fam, "migration wire family not registered"))
        elif fam in gap_names:
            migration_gaps.append(
                (fam, "migration wire family has a degradation gap"))
    for fam, problem in migration_gaps:
        print(
            json.dumps({"lint_migration_gap":
                        {"family": fam, "problem": problem}}),
            file=sys.stderr, flush=True,
        )

    # training gate (ISSUE 14): the train step's collective families —
    # the CP attention rings and the quantized gradient ring — must be
    # registered with a resolvable degradation target, or the trainer's
    # ledger demotion (wire ring → exact psum twin) would rest on an
    # unverified fallback
    from triton_distributed_tpu.train import TRAIN_ENGINE_FAMILIES

    train_gaps = []
    for fam in TRAIN_ENGINE_FAMILIES:
        if fam not in fams:
            train_gaps.append(
                (fam, "training family not registered"))
        elif fam in gap_names:
            train_gaps.append(
                (fam, "training family has a degradation gap"))
    for fam, problem in train_gaps:
        print(
            json.dumps({"lint_train_gap":
                        {"family": fam, "problem": problem}}),
            file=sys.stderr, flush=True,
        )

    # serving-protocol gate (ISSUE 19): servlint's bounded model check
    # of the host-side serving/fleet protocol — page conservation,
    # transactional ships, request safety (SV001–SV007) — over the
    # production ProtocolOps seam. The same exit-2 contract: a protocol
    # counterexample refuses the timing run.
    from triton_distributed_tpu.analysis import servlint

    sv_findings, sv_stats = servlint.lint_serving(max_states=3000)
    findings += sv_findings
    for f in sv_findings:
        print(json.dumps({"lint": f.to_json()}), file=sys.stderr,
              flush=True)
    print(
        json.dumps({"metric": "servlint",
                    "states": sv_stats["states"],
                    "transitions": sv_stats["transitions"],
                    "complete": sv_stats["complete"],
                    "errors": sum(f.severity >= Severity.ERROR
                                  for f in sv_findings)}),
        file=sys.stderr, flush=True,
    )

    errs = (sum(f.severity >= Severity.ERROR for f in findings)
            + len(gaps) + len(fleet_gaps) + len(spec_gaps)
            + len(migration_gaps) + len(train_gaps))
    print(
        json.dumps({"metric": "shmemlint", "errors": errs,
                    "findings": len(findings),
                    "rule_counts": rule_counts(findings),
                    "degradation_gaps": len(gaps),
                    "fleet_gaps": len(fleet_gaps),
                    "spec_gaps": len(spec_gaps),
                    "migration_gaps": len(migration_gaps),
                    "train_gaps": len(train_gaps),
                    "mosaic_scanned": len(report["scanned"]),
                    "mosaic_refused": len(report["refused"])}),
        file=sys.stderr, flush=True,
    )
    if errs:
        print(
            json.dumps({
                "metric": "ag_gemm_tflops_per_chip", "value": 0.0,
                "unit": "TFLOP/s", "vs_baseline": 0.0,
                "error": f"shmemlint found {errs} protocol error(s); "
                "refusing to time broken kernels",
            }),
            flush=True,
        )
        sys.exit(2)


def main(argv=None) -> None:
    from triton_distributed_tpu.config import enable_compile_cache

    args = _parse_args(argv)
    enable_compile_cache()
    if args.lint:
        _run_lint(infer_contracts=args.infer_contracts)
    if args.faults:
        from triton_distributed_tpu.runtime import faults as _rt_faults

        plan = _rt_faults.parse_plan(args.faults)
        _rt_faults.set_fault_plan(plan)
        print(
            json.dumps({"metric": "fault_replay", "plan": repr(plan)}),
            file=sys.stderr, flush=True,
        )

    if args.scenario is not None:
        from triton_distributed_tpu.tune.perf_model import detect_spec

        scenarios = {
            "serving_fleet": _bench_serving_fleet,
            "serving_speculative": _bench_serving_speculative,
            "serving_elastic": _bench_serving_elastic,
            "serving_multitenant": _bench_serving_multitenant,
            "serving_longcontext": _bench_serving_longcontext,
            "train_step": _bench_train_step,
        }
        bench_fn = scenarios.get(args.scenario)
        if bench_fn is None:
            print(json.dumps({"error":
                              f"unknown scenario {args.scenario!r}"}),
                  file=sys.stderr, flush=True)
            sys.exit(2)
        devs = jax.devices()
        mesh = Mesh(np.asarray(devs), ("x",))
        on_tpu = jax.default_backend() == "tpu"
        if not (on_tpu or args.dryrun):
            # the full-size scenario needs the chip; the toy shapes are
            # an explicit request, never a silent substitute
            raise RuntimeError(
                f"scenario {args.scenario!r} found no TPU "
                f"(backend={jax.default_backend()!r}): pass --dryrun "
                "for the CPU-sized shapes"
            )
        kw = {}
        if args.scenario == "serving_fleet" and args.spec_k:
            kw["spec_k"] = args.spec_k
        if args.scenario == "serving_speculative" and args.tree:
            kw["tree"] = True
        out = bench_fn(
            mesh, len(devs), on_tpu, detect_spec(),
            tiny=args.dryrun, **kw,
        )
        out["faults"] = args.faults
        print(json.dumps(out), flush=True)
        return

    if args.dryrun:
        from triton_distributed_tpu.tune.perf_model import detect_spec

        devs = jax.devices()
        mesh = Mesh(np.asarray(devs), ("x",))
        on_tpu = jax.default_backend() == "tpu"
        out = _bench_serving_continuous(
            mesh, len(devs), on_tpu, detect_spec(), tiny=True,
            n_layers=args.n_layers,
        )
        out["faults"] = args.faults
        print(json.dumps(out), flush=True)
        # the disaggregated twin at the same interpreter shapes: the
        # split-role engine, the DCN wire rails and the perf-model
        # placement gate all run hardware-free too
        out2 = _bench_serving_disaggregated(
            mesh, len(devs), on_tpu, detect_spec(), tiny=True,
        )
        out2["faults"] = args.faults
        print(json.dumps(out2), flush=True)
        return

    from triton_distributed_tpu.kernels.ag_gemm import (
        _build_fused,
        _build_xla_naive,
    )
    from triton_distributed_tpu.tune.perf_model import (
        detect_spec,
        overlap_efficiency,
    )

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    on_tpu = jax.default_backend() == "tpu"
    spec = detect_spec()
    device_kind = getattr(devs[0], "device_kind", "cpu")

    # Llama-7B TP8 up-projection (reference test_ag_gemm defaults
    # 8192×8192×28672): each chip's work is the full gathered A against
    # its N/8 weight shard. On one chip we bench exactly that per-chip
    # work; off-TPU (CPU dev runs) shapes shrink to keep CI fast.
    tp = 8
    if on_tpu:
        m, k, n_shard = 8192, 8192, 28672 // tp
    else:
        m, k, n_shard = 256, 256, 512 // tp
    nn = n_shard * n  # global N for the n-device mesh
    dtype = jnp.bfloat16

    key = jax.random.PRNGKey(0)
    a = jax.device_put(
        jax.random.normal(key, (m, k), dtype), NamedSharding(mesh, P("x", None))
    )
    b = jax.device_put(
        jax.random.normal(key, (k, nn), dtype), NamedSharding(mesh, P(None, "x"))
    )

    fused = _build_fused(
        mesh, "x", (), (m, k), (k, nn), jnp.dtype(dtype), jnp.dtype(dtype), 5,
        False, False,  # return_gathered=False: the production default path
    )
    naive = _build_xla_naive(mesh, "x", (), jnp.dtype(dtype))

    def fused_step(state, s):
        a, b = state
        out, _ag = fused(a, b)
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(a, s), b), s

    def naive_step(state, s):
        a, b = state
        out = naive(a, b)
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(a, s), b), s

    lo, hi = (8, 40) if on_tpu else (1, 3)
    reps = 11 if on_tpu else 5  # CPU deltas are µs-scale; keep headroom
    # PAIRED protocol (r4 settle, docs/PERF.md): each rep measures the
    # fused and baseline lo/hi deltas back-to-back and vs_baseline is
    # the MEDIAN OF PER-PAIR RATIOS — slowly-varying chip interference
    # hits both sides of a pair, so the recorded ratio is stable where
    # two independent medians drift apart by the run spread (±2%).
    t_fused, t_naive, ratio_med, ratio_iqr = bench_paired(
        fused_step, naive_step, (a, b), lo=lo, hi=hi, reps=reps
    )

    flops = 2.0 * m * k * nn
    tflops_per_chip = flops / t_fused / n / 1e12
    tflops_naive = flops / t_naive / n / 1e12
    mfu = tflops_per_chip / spec.bf16_tflops
    if n > 1:
        # MEASURED overlap (VERDICT r2 #7): fused vs compute-only vs
        # comm-only on the same shapes, same methodology —
        # (t_comm + t_compute - t_fused) / t_comm is the fraction of the
        # comm time the fused engine actually hid.
        compute_only = jax.jit(
            jax.shard_map(
                lambda af, bl: jnp.dot(af, bl, preferred_element_type=jnp.float32).astype(dtype),
                mesh=mesh, in_specs=(P(None, None), P(None, "x")),
                out_specs=P(None, "x"), check_vma=False,
            )
        )
        comm_only = jax.jit(
            jax.shard_map(
                lambda al: jax.lax.all_gather(al, "x", tiled=True),
                mesh=mesh, in_specs=P("x", None), out_specs=P(None, None),
                check_vma=False,
            )
        )
        a_rep = jax.device_put(
            jax.random.normal(key, (m, k), dtype), NamedSharding(mesh, P(None, None))
        )

        def compute_step(state, s):
            af, bl = state
            out = compute_only(af, bl)
            s = s + jnp.sum(out.astype(jnp.float32))
            return (perturb(af, s), bl), s

        def comm_step(state, s):
            al = state
            out = comm_only(al)
            s = s + jnp.sum(out.astype(jnp.float32))
            return perturb(al, s), s

        t_compute = bench_loop(compute_step, (a_rep, b), lo=lo, hi=hi)
        t_comm = bench_loop(comm_step, a, lo=lo, hi=hi)
        # a comm leg within noise of zero cannot anchor the ratio — say
        # so instead of reporting a clamped artifact as "measured"
        if t_comm > 0.05 * t_fused:
            overlap = max(0.0, min(1.0, (t_comm + t_compute - t_fused) / t_comm))
            overlap_kind = "measured"
        else:
            overlap = 0.0
            overlap_kind = "comm_below_noise_floor"
    else:
        # n=1: no comm exists to measure — project the TP8 ring
        # analytically from the measured per-chip compute. Per ring step
        # the fused kernel hides ONE shard transfer (m/tp·k bytes,
        # unidirectional, one ICI link) under ONE shard matmul (1/tp of
        # the whole per-chip job).
        compute_step_ms = t_fused / tp * 1e3
        shard_bytes = (m // tp) * k * jnp.dtype(dtype).itemsize
        comm_step_ms = shard_bytes / (spec.ici_gbps * 1e9) * 1e3
        overlap = overlap_efficiency(compute_step_ms, comm_step_ms)
        overlap_kind = "projected_tp8"

    print(
        json.dumps(
            {
                "metric": "ag_gemm_tflops_per_chip",
                "value": round(tflops_per_chip, 2),
                "unit": "TFLOP/s",
                # fused vs unoverlapped AG→dot, median of PER-PAIR
                # ratios (paired protocol). At n=1 the baseline's gather
                # leg is free, so this isolates raw engine efficiency —
                # the settled ~2-3% streaming-pipeline overhead
                # (docs/PERF.md; the op entry short-circuits n=1 to the
                # XLA engine, so users never pay it); the overlap
                # advantage appears where there is comm to hide (n>1).
                "vs_baseline": round(ratio_med, 4),
                # NaN (the unpaired-fallback sentinel) is not valid
                # JSON — emit null so the headline line stays parseable
                "vs_baseline_iqr": [
                    None if np.isnan(v) else round(v, 4) for v in ratio_iqr
                ],
                "baseline_tflops_per_chip": round(tflops_naive, 2),
                "device_kind": device_kind,
                "n_chips": n,
                "mfu": round(mfu, 4),
                "overlap_pct": round(100 * overlap, 1),
                "overlap_kind": overlap_kind,
                "config": f"M={m} K={k} N={nn} bf16 fused-streaming",
            }
        ),
        flush=True,
    )

    failed = []
    for fn in (_bench_gemm_rs, _bench_wire_rings, _bench_schedule_search,
               _bench_group_gemm,
               _bench_moe_a2a, _bench_flash_decode,
               _bench_serving_continuous, _bench_serving_disaggregated):
        try:
            print(json.dumps(fn(mesh, n, on_tpu, spec)), file=sys.stderr, flush=True)
        except Exception as e:
            failed.append(fn.__name__)
            print(
                json.dumps({"metric": fn.__name__, "error": f"{type(e).__name__}: {e}"[:300]}),
                file=sys.stderr,
                flush=True,
            )
    if failed:
        # every phase still reports, but a failed one fails the run
        raise RuntimeError(f"bench phases failed: {failed}")


def _bench_gemm_rs(mesh, n, on_tpu, spec):
    """North-star GEMM-RS (Llama-7B down-projection 8192×28672×8192 TP8):
    per-chip K shard against the full output."""
    from triton_distributed_tpu.kernels.gemm_rs import _build_fused

    tp = 8
    m, k_shard, nn = (8192, 28672 // tp, 8192) if on_tpu else (128, 64, 256)
    k = k_shard * n
    dtype = jnp.bfloat16
    a = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1), (m, k), dtype),
        NamedSharding(mesh, P(None, "x")),
    )
    b = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(2), (k, nn), dtype),
        NamedSharding(mesh, P("x", None)),
    )
    fused = _build_fused(
        mesh, "x", (), (m, k), (k, nn), jnp.dtype(dtype), jnp.dtype(dtype), 6, False
    )

    def step(state, s):
        a, b = state
        out = fused(a, b)
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(a, s), b), s

    lo, hi = (4, 16) if on_tpu else (1, 3)
    t = bench_loop(step, (a, b), lo=lo, hi=hi)
    tflops = 2.0 * m * k * nn / t / n / 1e12
    return {
        "metric": "gemm_rs_tflops_per_chip",
        "value": round(tflops, 2),
        "unit": "TFLOP/s",
        "mfu": round(tflops / spec.bf16_tflops, 4),
        "config": f"n={n} M={m} K={k} N={nn} bf16 fused-streaming",
    }


def _bench_wire_rings(mesh, n, on_tpu, spec):
    """Quantized-wire streaming rings on COMM-BOUND shapes (ISSUE 3):
    decode-side small-M AG-GEMM and GEMM-RS shards where the bf16 ring
    transfer, not the shard matmul, is the per-step critical path.
    Reports per-step wire bytes bf16 vs fp8 (the ≥1.8× acceptance
    check), projected overlap_pct for both wires from the perf model,
    the auto-selector's picks on the comm-bound AND the compute-bound
    north-star configs (must be fp8 resp. bf16), and measured accuracy
    deltas of the fp8/int8 wire vs the bf16-wire twin (XLA ring engines
    — byte-identical wire layout to the fused kernels, runnable at any
    n)."""
    from triton_distributed_tpu.kernels.ag_gemm import AGGemmMethod, ag_gemm
    from triton_distributed_tpu.kernels.gemm_rs import GemmRSMethod, gemm_rs
    from triton_distributed_tpu.lang import wire as wirelib
    from triton_distributed_tpu.tune.perf_model import (
        auto_wire_dtype,
        estimate_gemm_ms,
        overlap_efficiency,
        ring_wire_ms,
    )

    tp = 8
    # comm-bound: decode-scale M (batch rows), Llama-7B K, a small
    # per-shard N (qkv-head-scale projection) — the weight fetch no
    # longer hides the A-slab ring transfer, so the wire IS the
    # per-step critical path
    m_cb, k_cb, nl_cb = 1024, 8192, 512
    slab_cb = m_cb // tp
    # compute-bound: the north-star prefill shard
    m_ns, k_ns, nl_ns = 8192, 8192, 28672 // tp
    slab_ns = m_ns // tp

    from triton_distributed_tpu.tune.perf_model import (
        dequant_pass_ms,
        estimate_s8_gemm_ms,
        int8_mxu_step_ratio,
    )

    fmt = wirelib.make_wire_format("fp8", slab_cb, strict=False)
    bf16_bytes = slab_cb * k_cb * 2
    fp8_bytes = fmt.slab_bytes(slab_cb, k_cb)
    compute_cb = estimate_gemm_ms(slab_cb, k_cb, nl_cb, spec)
    out = {
        "metric": "wire_quantized_rings",
        "wire_reduction_fp8": round(bf16_bytes / fp8_bytes, 3),
        "wire_bytes_per_step": {"bf16": bf16_bytes, "fp8": fp8_bytes},
        "overlap_pct_bf16": round(
            100 * overlap_efficiency(compute_cb, ring_wire_ms(bf16_bytes, spec)), 1
        ),
        "overlap_pct_fp8": round(
            100 * overlap_efficiency(compute_cb, ring_wire_ms(fp8_bytes, spec)), 1
        ),
        "auto_pick_comm_bound": auto_wire_dtype(slab_cb, k_cb, nl_cb, 2, spec=spec),
        "auto_pick_north_star": auto_wire_dtype(slab_ns, k_ns, nl_ns, 2, spec=spec),
        # int8→MXU (round 8): the dequant-free consumer vs
        # dequant-then-matmul on the same int8 wire — the skipped
        # per-arrival pass plus the s8×s8 MXU rate, per ring step
        "auto_pick_comm_bound_wq_int8": auto_wire_dtype(
            slab_cb, k_cb, nl_cb, 2, spec=spec, consumer_wq="int8"
        ),
        "auto_pick_north_star_wq_int8": auto_wire_dtype(
            slab_ns, k_ns, nl_ns, 2, spec=spec, consumer_wq="int8"
        ),
        "int8_mxu_skipped_dequant_ms": round(
            dequant_pass_ms(slab_cb, k_cb, 2, spec), 5
        ),
        "int8_mxu_step_ms": round(
            estimate_s8_gemm_ms(slab_cb, k_cb, nl_cb, spec), 5
        ),
        "int8_mxu_vs_dequant_step_ratio": round(
            int8_mxu_step_ratio(slab_cb, k_cb, nl_cb, spec), 3
        ),
        "config": (
            f"comm-bound M={m_cb} K={k_cb} N/tp={nl_cb} tp={tp} "
            f"(slab {slab_cb}×{k_cb}) vs north-star M={m_ns}"
        ),
    }

    # measured accuracy deltas vs the bf16-wire twin (small shapes off
    # TPU; the wire layout is identical to the fused engines')
    if n == 1:
        # a 1-device mesh short-circuits the rings — no wire is crossed
        # and a 0.0 delta would be vacuous, not evidence
        out["accuracy"] = (
            "n=1: no wire crossed; pinned tolerances in tests/test_wire.py"
        )
        return out
    ma, ka, na = (512, 2048, 512) if not on_tpu else (1024, 8192, 512)
    a = jax.random.normal(jax.random.PRNGKey(21), (ma, ka), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(22), (ka, na), jnp.bfloat16)
    ref = np.asarray(
        ag_gemm(a, b, mesh, "x", method=AGGemmMethod.XLA_RING), np.float32
    )
    scale = float(np.abs(ref).max()) or 1.0
    pair = {}
    for w in ("fp8", "int8", "int8-mxu"):
        got = np.asarray(
            ag_gemm(a, b, mesh, "x", method=AGGemmMethod.XLA_RING,
                    wire_dtype=w),
            np.float32,
        )
        pair[w] = got
        key = w.replace("-", "_")
        out[f"ag_{key}_rel_err"] = round(
            float(np.abs(got - ref).max()) / scale, 5
        )
    # the paired row the acceptance pins: epilogue-folded dequant vs
    # the dequant-then-matmul twin on the SAME int8 wire bytes (their
    # gap is pure weight-quantization error, bounded by ~1/127)
    out["ag_int8_mxu_vs_dequant_delta"] = round(
        float(np.abs(pair["int8-mxu"] - pair["int8"]).max()) / scale, 5
    )
    a2 = jax.random.normal(jax.random.PRNGKey(23), (ma, ka), jnp.bfloat16)
    b2 = jax.random.normal(jax.random.PRNGKey(24), (ka, na), jnp.bfloat16)
    ref2 = np.asarray(
        gemm_rs(a2, b2, mesh, "x", method=GemmRSMethod.XLA_RING), np.float32
    )
    scale2 = float(np.abs(ref2).max()) or 1.0
    for w in ("fp8", "int8"):
        got = np.asarray(
            gemm_rs(a2, b2, mesh, "x", method=GemmRSMethod.XLA_RING,
                    wire_dtype=w),
            np.float32,
        )
        out[f"rs_{w}_rel_err"] = round(
            float(np.abs(got - ref2).max()) / scale2, 5
        )

    # rs_ring_stream wire row (round 8): the standalone RS's
    # HBM-streaming engine now carries the quantized wire; off-TPU the
    # entry degrades to the byte-identical XLA twin, so this measures
    # the same per-hop quantize / f32 dequant-accumulate numerics the
    # streaming kernel ships on chip
    from triton_distributed_tpu.kernels.reduce_scatter import (
        reduce_scatter,
    )

    ys = jax.random.normal(
        jax.random.PRNGKey(27), (n, 32 * n, 2048), jnp.bfloat16
    )
    ref_s = np.asarray(ys, np.float32).sum(0)
    scale_s = float(np.abs(ref_s).max()) or 1.0
    got_s = np.asarray(
        reduce_scatter(ys, mesh, "x", stacked=True, wire_dtype="int8"),
        np.float32,
    )
    out["rs_stream_int8_rel_err"] = round(
        float(np.abs(got_s - ref_s).max()) / scale_s, 5
    )

    # DCN rail row (round 8): hierarchical ag_gemm at dcn_axis>1 — the
    # rail legs (the slowest transport) ship the quantized payload +
    # scale planes; measured against the raw-rail twin on a 2×(n/2)
    # mesh (the rail machinery is link-agnostic, so the numbers are the
    # DCN numerics even off a real multi-slice pod)
    if n >= 4 and n % 2 == 0:
        from jax.sharding import Mesh

        mesh2 = Mesh(
            np.asarray(mesh.devices).reshape(2, n // 2), ("rail", "x")
        )
        tp2, nd2 = n // 2, 2
        md, kd, nld = 32 * tp2 * nd2, 2048, 64 * tp2 * nd2
        ad = jax.random.normal(jax.random.PRNGKey(28), (md, kd), jnp.bfloat16)
        bd = jax.random.normal(jax.random.PRNGKey(29), (kd, nld), jnp.bfloat16)
        ref_d = np.asarray(
            ag_gemm(ad, bd, mesh2, "x", dcn_axis="rail",
                    method=AGGemmMethod.XLA_RING),
            np.float32,
        )
        got_d = np.asarray(
            ag_gemm(ad, bd, mesh2, "x", dcn_axis="rail",
                    method=AGGemmMethod.XLA_RING, wire_dtype="fp8"),
            np.float32,
        )
        out["dcn_rail_fp8_rel_err"] = round(
            float(np.abs(got_d - ref_d).max())
            / (float(np.abs(ref_d).max()) or 1.0),
            5,
        )
        m_dev = md // (tp2 * nd2)
        fmt_d = wirelib.make_wire_format("fp8", m_dev, strict=False)
        out["dcn_rail_wire_reduction"] = round(
            m_dev * kd * 2 / fmt_d.slab_bytes(m_dev, kd), 3
        )

    if on_tpu and n > 1:
        # real multi-chip: time the fused wire vs bf16 twin, paired.
        # int8 wire — the in-kernel wire this Mosaic can lower
        # (lang.wire.inkernel_wire_ok; fp8 extf is rejected)
        from triton_distributed_tpu.kernels.ag_gemm import _build_fused

        dtype = jnp.bfloat16
        av = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(25), (m_cb, k_cb), dtype),
            NamedSharding(mesh, P("x", None)),
        )
        bv = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(26), (k_cb, nl_cb * n), dtype),
            NamedSharding(mesh, P(None, "x")),
        )
        raw = _build_fused(
            mesh, "x", (), av.shape, bv.shape, jnp.dtype(dtype),
            jnp.dtype(dtype), 5, False, False,
        )
        comp = _build_fused(
            mesh, "x", (), av.shape, bv.shape, jnp.dtype(dtype),
            jnp.dtype(dtype), 5, False, False, None, "int8",
        )

        def mk(fn):
            def step(state, s):
                a, b = state
                o, _ = fn(a, b)
                s = s + jnp.sum(o.astype(jnp.float32))
                return (perturb(a, s), b), s
            return step

        t_raw, t_q, ratio, iqr = bench_paired(
            mk(raw), mk(comp), (av, bv), lo=8, hi=40, reps=11
        )
        out["fused_int8_vs_bf16_ratio"] = round(ratio, 4)
        out["fused_int8_vs_bf16_iqr"] = [round(v, 4) for v in iqr]
        # int8-mxu vs dequant-then-matmul, paired on the SAME wire: the
        # measured counterpart of int8_mxu_vs_dequant_step_ratio above
        mxc = _build_fused(
            mesh, "x", (), av.shape, bv.shape, jnp.dtype(dtype),
            jnp.dtype(dtype), 5, False, False, None, "int8-mxu",
        )
        _, _, ratio_mx, iqr_mx = bench_paired(
            mk(comp), mk(mxc), (av, bv), lo=8, hi=40, reps=11
        )
        out["fused_int8mxu_vs_int8_ratio"] = round(ratio_mx, 4)
        out["fused_int8mxu_vs_int8_iqr"] = [round(v, 4) for v in iqr_mx]
    return out


def _bench_schedule_search(mesh, n, on_tpu, spec):
    """Schedule-space search on the comm-bound config (the tentpole's
    paired row): enumerate ring schedules for the AG-GEMM family, gate
    every candidate through shmemlint+Mosaic (rejections carry rule
    IDs — at least one mutation MUST be rejected or the oracle is
    dead), price the survivors on the perf model, and report the
    searched winner against the canonical default. On TPU the top-k
    survivors are also timed end to end (fused engine, int8 wire);
    off-TPU the row is perf-model-only (``timed: 0``). The winner
    persists keyed by (family, shape, mesh, wire) — the second bench
    run reloads it with zero search cost (``cached: true``)."""
    from triton_distributed_tpu.kernels.ag_gemm import _build_fused
    from triton_distributed_tpu.tune import schedule as sched_lib
    from triton_distributed_tpu.tune.autotuner import search_ring_schedule

    tp = 8
    m_cb, k_cb, nl_cb = 1024, 8192, 512   # _bench_wire_rings' comm-bound
    slab_cb = m_cb // tp

    time_fn = None
    if on_tpu and n == tp:
        dtype = jnp.bfloat16
        av = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(30), (m_cb, k_cb), dtype),
            NamedSharding(mesh, P("x", None)),
        )
        bv = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(31), (k_cb, nl_cb * n), dtype),
            NamedSharding(mesh, P(None, "x")),
        )

        def time_fn(sched):
            wire = "int8-mxu" if sched.dequant == "epilogue" else "int8"
            fn = _build_fused(
                mesh, "x", (), av.shape, bv.shape, jnp.dtype(dtype),
                jnp.dtype(dtype), 5, False, False, None, wire, False, sched,
            )

            def step(state, s):
                a, b = state
                o, _ = fn(a, b)
                s = s + jnp.sum(o.astype(jnp.float32))
                return (perturb(a, s), b), s

            return bench_loop(step, (av, bv), lo=8, hi=40) * 1e3

    rep = search_ring_schedule(
        "ag_gemm.fused", rows=slab_cb, cols=k_cb, mesh_shape=(tp,),
        wire="int8", shape=(m_cb, k_cb), itemsize=2,
        dryrun=not on_tpu, top_k=2, time_fn=time_fn,
    )
    winner = sched_lib.RingSchedule.from_dict(rep["winner"])
    out = {
        "metric": "schedule_search",
        "family": rep["family"],
        "config": f"comm-bound M={m_cb} K={k_cb} N/tp={nl_cb} tp={tp}",
        "cached": rep["cached"],
        "candidates": rep["candidates"],
        "timed": rep.get("timed", 0),
        # the paired row: canonical default vs searched winner, same
        # perf model, same shapes — searched must be no worse
        "default": sched_lib.DEFAULT.to_dict(),
        "default_ms": round(rep["default_ms"], 5),
        "searched": rep["winner"],
        "searched_ms": round(rep["winner_ms"], 5),
        "searched_no_worse": rep["winner_ms"] <= rep["default_ms"] + 1e-9,
        "rejected": [
            {"schedule": s, "rules": rules} for s, rules in rep["rejected"]
        ],
        "winner_is_default": winner.is_default(),
    }
    return out


def _bench_group_gemm(mesh, n, on_tpu, spec):
    """Grouped-GEMM MFU proxy (the MoE expert-compute hot loop)."""
    from triton_distributed_tpu.kernels.group_gemm import grouped_matmul

    if on_tpu:
        e, m_per, h, f, block_m = 8, 1024, 4096, 2048, 512
    else:
        e, m_per, h, f, block_m = 4, 64, 128, 128, 64
    m_total = e * m_per
    x = jax.random.normal(jax.random.PRNGKey(3), (m_total, h), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(4), (e, h, f), jnp.bfloat16)
    block_expert = jnp.repeat(jnp.arange(e, dtype=jnp.int32), m_per // block_m)

    def step(state, s):
        x, w = state
        out = grouped_matmul(x, w, block_expert, block_m=block_m)
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(x, s), w), s

    lo, hi = (8, 80) if on_tpu else (1, 3)
    t = bench_loop(step, (x, w), lo=lo, hi=hi)
    tflops = 2.0 * m_total * h * f / t / 1e12
    return {
        "metric": "group_gemm_tflops",
        "value": round(tflops, 2),
        "unit": "TFLOP/s",
        "mfu": round(tflops / spec.bf16_tflops, 4),
        "config": f"experts={e} m/e={m_per} {h}x{f} bf16",
    }


def _bench_moe_a2a(mesh, n, on_tpu, spec):
    """MoE dispatch leg on the reference's headline config (128 tok/rank,
    topk 8, hidden 7168 — README.md:87), through the FUSED count-bounded
    chunked transport (kernels/moe_dispatch): one aligned staging pass
    over the true M·topk rows + per-peer chunked DMAs sized by the true
    counts (r4; the r3 windows shipped worst-case bytes). With one chip
    there is no wire to cross; what is measured (and labeled) is the
    full dispatch machinery — aligned staging, quantize/bitcast, the
    compiled chunked-DMA kernel, receive unpack."""
    from triton_distributed_tpu.kernels import moe_all_to_all as ma
    from triton_distributed_tpu.kernels import moe_dispatch as md

    epr, hidden, tok, topk = (8, 7168, 128, 8) if on_tpu else (2, 256, 16, 2)
    max_m = tok * topk
    # fp8 wire with in-row per-token scales — the reference's headline
    # config is fp8 WITH_SCALE (README.md:87)
    ctx = ma.create_all_to_all_context(
        mesh, "x", max_m=max_m, hidden=hidden,
        experts_per_rank=epr, dtype=jnp.bfloat16, quant="fp8",
    )
    rng = np.random.default_rng(5)
    sorted_e = np.sort(
        rng.integers(0, ctx.num_experts, (n, max_m)), axis=1
    ).astype(np.int32)
    splits_np = np.stack(
        [np.bincount(a, minlength=ctx.num_experts) for a in sorted_e]
    ).astype(np.int32)
    x = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(5), (n * max_m, hidden), jnp.bfloat16),
        NamedSharding(mesh, P("x")),
    )
    se = jax.device_put(jnp.asarray(sorted_e).reshape(-1), NamedSharding(mesh, P("x")))
    splits = jax.device_put(jnp.asarray(splits_np), NamedSharding(mesh, P("x")))

    def device_leg(x_loc, se_loc, spl_loc):
        spl_loc = spl_loc.reshape(-1)
        counts, offs, offs_al, sendk = md.send_plan(ctx, spl_loc)
        peer, dest = md.assignment_dest(ctx, se_loc, offs, offs_al)
        payload, scales = md.stage_aligned(
            ctx, x_loc, jnp.arange(x_loc.shape[0], dtype=jnp.int32), dest,
            x_loc.shape[0],
        )
        meta = md.meta_payload(ctx, spl_loc, scales, offs_al, sendk)
        recv_tok, recv_meta = md.dispatch_device(
            ctx, payload, offs_al, sendk, meta
        )
        toks, rspl = md.recv_view(ctx, recv_tok, recv_meta)
        return toks.reshape(n * md.slot_pad(ctx), hidden)

    leg = jax.jit(
        jax.shard_map(
            device_leg, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
            out_specs=P("x"), check_vma=False,
        )
    )

    def device_stage_only(x_loc, se_loc, spl_loc):
        """The staging half alone (plan, gather, quantize, meta pack) —
        total − stage ≈ the transport kernel + receive unpack."""
        spl_loc = spl_loc.reshape(-1)
        counts, offs, offs_al, sendk = md.send_plan(ctx, spl_loc)
        peer, dest = md.assignment_dest(ctx, se_loc, offs, offs_al)
        payload, scales = md.stage_aligned(
            ctx, x_loc, jnp.arange(x_loc.shape[0], dtype=jnp.int32), dest,
            x_loc.shape[0],
        )
        meta = md.meta_payload(ctx, spl_loc, scales, offs_al, sendk)
        return (
            jnp.sum(payload.astype(jnp.float32), axis=1, keepdims=True)
            + jnp.sum(meta.astype(jnp.float32)).reshape(1, 1)
        )

    stage = jax.jit(
        jax.shard_map(
            device_stage_only, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
            out_specs=P("x"), check_vma=False,
        )
    )

    def step(state, s):
        x = state
        out = leg(x, se, splits)
        s = s + jnp.sum(out.astype(jnp.float32))
        return perturb(x, s), s

    def stage_step(state, s):
        x = state
        out = stage(x, se, splits)
        s = s + jnp.sum(out)
        return perturb(x, s), s

    lo, hi = (16, 400) if on_tpu else (1, 3)
    t = bench_loop(step, x, lo=lo, hi=hi)
    t_stage = bench_loop(stage_step, x, lo=lo, hi=hi)
    return {
        "metric": "moe_a2a_dispatch_latency",
        "value": round(t * 1e6, 1),
        "unit": "us",
        "stage_us": round(t_stage * 1e6, 1),
        "kernel_unpack_us": round((t - t_stage) * 1e6, 1),
        "config": (
            f"n={n} tok/rank={tok} topk={topk} hidden={hidden} fp8+scales "
            "fused-chunked-dma "
            + ("self-transport(no wire)" if n == 1 else "ring")
        ),
    }


def _serving_continuous_config(n, on_tpu, tiny=False, n_layers=None):
    """(model config, engine config, trace knobs) for the continuous
    bench. TPU: the serving headline model (hidden 7168, EP-MoE, every
    int8 knob) under the ISSUE-6 traffic shape — B≫128 requests,
    lengths ~U[S/8, 3S/4] against S=2048. Off-TPU (and --dryrun):
    interpreter-sized shapes, same shape of traffic. ``n_layers``
    overrides the model depth (the ``--n-layers`` donation sweep —
    depth > 1 exercises the per-layer serving-state donation path the
    default depth-1 bench never touches)."""
    import jax.numpy as jnp

    from triton_distributed_tpu.models import TransformerConfig
    from triton_distributed_tpu.serving import EngineConfig

    # KV heads shard over tp in the serving state — keep divisible
    n_kv = n if n > 4 else 4
    if on_tpu and not tiny:
        s_cap = 2048
        cfg = TransformerConfig(
            vocab=4096, n_layers=1, hidden=7168, ffn=2048, n_heads=7 * n_kv,
            n_kv_heads=n_kv, head_dim=128, moe="ep", moe_layers=(0,),
            num_experts=max(8, n), topk=8, param_dtype=jnp.bfloat16,
            moe_weight_quant="int8", moe_act_quant="int8", kv_quant="int8",
            dense_weight_quant="int8", dense_act_quant="int8",
        )
        ecfg = EngineConfig(
            slots=160, token_budget=512, chunk=256, page=1024,
            npages=352, max_steps=200_000,
        )
        trace_kw = dict(
            n_requests=256, mean_interarrival=0.25,
            len_lo=s_cap // 8, len_hi=3 * s_cap // 4,
            max_new_lo=16, max_new_hi=64, vocab=4096,
        )
    else:
        s_cap = 64
        cfg = TransformerConfig(
            vocab=256, n_layers=1, hidden=128, ffn=128, n_heads=2 * n_kv,
            n_kv_heads=n_kv, head_dim=32, moe="ep", moe_layers=(0,),
            num_experts=max(4, n), topk=2, param_dtype=jnp.bfloat16,
            dtype=jnp.float32,
        )
        ecfg = EngineConfig(
            slots=6, token_budget=48, chunk=16, page=8,
            npages=40, max_steps=5_000,
        )
        trace_kw = dict(
            n_requests=24, mean_interarrival=0.6,
            len_lo=s_cap // 8, len_hi=3 * s_cap // 4,
            max_new_lo=3, max_new_hi=8, vocab=256,
        )
    if n_layers is not None and n_layers != cfg.n_layers:
        from dataclasses import replace as _rep2

        # keep the MoE layer set valid at the new depth (drop layers
        # past it; added depth is dense — the donation path under test
        # is per-layer KV state, not expert count)
        moe_layers = tuple(l for l in cfg.moe_layers if l < n_layers)
        cfg = _rep2(cfg, n_layers=int(n_layers), moe_layers=moe_layers)
    return cfg, ecfg, trace_kw, s_cap


def _bench_serving_continuous(mesh, n, on_tpu, spec, tiny=False,
                              n_layers=None):
    """CONTINUOUS-BATCHING serving on the ragged paged-attention kernel
    (ISSUE 6 tentpole acceptance): a seeded Poisson arrival trace with
    ~U[S/8, 3S/4] prompt lengths drives the ServingEngine — admission/
    eviction over the page pool, chunked prefill interleaved into
    decode batches, one ragged mixed kernel launch per step. Reports
    sustained tok/s, p50/p99 step time and GOODPUT (completed requests'
    generated tokens per wall second)."""
    import jax

    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.serving import ServingEngine, poisson_trace
    from triton_distributed_tpu.tune.perf_model import (
        ragged_serving_step_ms,
    )

    cfg, ecfg, trace_kw, _ = _serving_continuous_config(
        n, on_tpu, tiny, n_layers=n_layers
    )
    model = Transformer(cfg, mesh, tp_axis="x")
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        model.init(jax.random.PRNGKey(7)), model.shardings(),
    )
    params = model.quantize_moe_weights(params)
    params = model.quantize_dense_weights(params)

    def fresh_trace():
        return poisson_trace(seed=11, **trace_kw)

    # ---- continuous engine (run twice; first run pays the compiles)
    for _warm in (False, True):
        trace = fresh_trace()
        eng = ServingEngine(model, params, ecfg)
        stats = eng.run(trace)
    assert stats.completed == trace_kw["n_requests"], (
        stats.completed, stats.deferrals)
    # per-layer KV pool footprint: at depth > 1 the engine carries one
    # (k_pool, v_pool) pair PER LAYER, all donated through the jitted
    # step — the `--n-layers` sweep's reported quantity
    per_layer_pool_bytes = sum(
        int(x.nbytes) for x in jax.tree.leaves(eng.state.layers[0])
    )
    # donation precondition at depth: every layer's pool leaves carry
    # their own buffers (the step jit donates the whole ServingState —
    # a buffer shared across layers would alias the in-place appends).
    # Verified at ANY depth, but only depth > 1 exercises it.
    _leaves = jax.tree.leaves(eng.state.layers)
    _ptrs = {
        x.addressable_shards[0].data.unsafe_buffer_pointer()
        for x in _leaves
    }
    donation_distinct = len(_ptrs) == len(_leaves)

    # ---- traffic-tuned grid schedules: the run's shape ledger feeds a
    # dryrun schedule search per hot key (oracle-gated, perf-model
    # priced); winners persist in the store and the REBUILT engine
    # resolves them with zero search cost on its build path
    from triton_distributed_tpu.tune import traffic as traffic_lib

    wire_key = "int8" if cfg.kv_quant is not None else None
    tune_reports = traffic_lib.retune_hot_shapes(
        stats, mesh_shape=(model.tp,), wire=wire_key, dryrun=True,
    )
    tuned_vs_default = [
        {
            "key": str(rep.get("key", "")),
            "default_ms": round(rep["default_ms"], 4),
            "tuned_ms": round(rep["winner_ms"], 4),
            "winner": rep["winner"],
            "cached": rep["cached"],
        }
        for rep in tune_reports if "error" not in rep
    ]
    eng_tuned = ServingEngine(model, params, ecfg)
    resolved_schedule = eng_tuned.grid_schedule.to_dict()

    # model term: a representative steady step (every slot decoding at
    # the mean trace length)
    mean_len = (trace_kw["len_lo"] + trace_kw["len_hi"]) // 2
    page = ecfg.page
    from triton_distributed_tpu.tune.perf_model import (
        measured_page_issue_ms,
    )

    model_ms = ragged_serving_step_ms(
        [mean_len] * ecfg.slots, [1] * ecfg.slots, page=page,
        hkv=cfg.n_kv_heads // n, g=cfg.n_heads // cfg.n_kv_heads,
        d=cfg.head_dim, hidden=cfg.hidden, n_layers=cfg.n_layers,
        spec=spec, quant=cfg.kv_quant is not None,
        # the backend's MEASURED per-page issue cost (ROADMAP
        # follow-on): off-TPU the interpreter pays milliseconds per
        # page, not the v5e's 0.17 µs — the model term should track
        # the machine the measurement next to it ran on
        issue_ms=measured_page_issue_ms(),
    )
    return {
        "metric": "serving_continuous",
        "value": round(stats.goodput_tok_per_s, 1),
        "unit": "tok/s goodput",
        "sustained_tok_per_s": round(stats.sustained_tok_per_s, 1),
        "p50_step_ms": round(stats.p50_step_ms, 2),
        "p99_step_ms": round(stats.p99_step_ms, 2),
        "steps": len(stats.step_times),
        "completed": stats.completed,
        "evictions": stats.evictions,
        "deferrals": stats.deferrals,
        "degraded_to_xla": stats.degraded,
        "model_steady_step_ms": round(model_ms, 3),
        "n_layers": cfg.n_layers,
        "per_layer_pool_bytes": per_layer_pool_bytes,
        "pool_bytes_total": per_layer_pool_bytes * cfg.n_layers,
        "donation_distinct_buffers": donation_distinct,
        "tuned_vs_default": tuned_vs_default,
        "tuned_strictly_better": sum(
            1 for r in tuned_vs_default
            if r["tuned_ms"] < r["default_ms"]
        ),
        "resolved_grid_schedule": resolved_schedule,
        "config": (
            f"n={n} slots={ecfg.slots} budget={ecfg.token_budget} "
            f"chunk={ecfg.chunk} page={page} npages={ecfg.npages} "
            f"requests={trace_kw['n_requests']} "
            f"lens~U[{trace_kw['len_lo']},{trace_kw['len_hi']}] "
            f"poisson(seed=11) hidden={cfg.hidden} "
            f"kvq={cfg.kv_quant} "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_serving_longcontext(mesh, n, on_tpu, spec, tiny=False):
    """LONG-CONTEXT serving (ISSUE 20 tentpole acceptance): a tp×cp
    mesh replica whose page-table walk is context-parallel — each cp
    rank walks only its own pool shard and the per-rank (out, lse)
    partials merge through the LSE-combine contract — serves a request
    whose KV need is a MULTIPLE of one pool shard (inadmissible on any
    cp-free replica of the same per-slice pool), token-exact against a
    single-slice oracle engine given one pool of the combined size.
    The paired row: (a) the capacity ratio the cp axis bought with
    ``token_mismatches == 0``, (b) short-request goodput on the SAME
    cp engine vs the cp-free engine (the hop tax short traffic pays),
    (c) the PRICED placement verdict — what the fleet router tells a
    cp-free replica refusing the long request, and the modeled
    cp-vs-flat step cost crossover behind it."""
    import jax

    from triton_distributed_tpu.models import Transformer, TransformerConfig
    from triton_distributed_tpu.serving import (
        EngineConfig,
        Request,
        ServingEngine,
        poisson_trace,
    )
    from triton_distributed_tpu.tune.perf_model import (
        cp_decode_step_ms,
        ragged_serving_step_ms,
        refuse_long_context,
    )

    devs = jax.devices()
    if len(devs) < 2:
        return {"metric": "serving_longcontext",
                "error": "needs >= 2 devices for a cp=2 axis"}
    cp = 2
    tp = 2 if len(devs) >= 4 else 1
    mesh_cp = Mesh(
        np.asarray(devs[:tp * cp]).reshape(tp, cp), ("x", "cp"))
    mesh_flat = Mesh(np.asarray(devs[:tp]), ("x",))

    import jax.numpy as jnp

    n_kv = max(tp, 2)
    cfg = TransformerConfig(
        vocab=256, n_layers=2, hidden=128, ffn=128, n_heads=2 * n_kv,
        n_kv_heads=n_kv, head_dim=32, dtype=jnp.float32,
    )
    # one pool shard: 8 pages of 8 tokens. The long request needs
    # ~12 pages — inadmissible on one shard, admitted under cp=2.
    npages_shard, page = 8, 8
    ecfg = EngineConfig(slots=4, token_budget=32, chunk=16, page=page,
                        npages=npages_shard, max_steps=5_000,
                        temperature=0.0)
    ecfg_oracle = EngineConfig(
        slots=4, token_budget=32, chunk=16, page=page,
        npages=cp * npages_shard, max_steps=5_000, temperature=0.0)

    def build(m, cp_axis, use_pallas):
        model = Transformer(cfg, m, tp_axis="x", cp_axis=cp_axis)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        return model, params, use_pallas

    model_cp, params_cp, _ = build(mesh_cp, "cp", False)
    model_fl, params_fl, _ = build(mesh_flat, None, False)
    use_pallas = bool(on_tpu)

    # ---- (a) capacity: long requests k× one pool shard, cp vs oracle
    rng = np.random.default_rng(23)
    long_prompt = rng.integers(1, 255, size=84).astype(np.int32)
    short_prompts = [rng.integers(1, 255, size=12).astype(np.int32)
                     for _ in range(3)]

    def long_trace():
        reqs = [Request(rid=0, prompt=long_prompt.copy(), max_new=10,
                        arrival=0)]
        reqs += [Request(rid=i + 1, prompt=p.copy(), max_new=4,
                         arrival=0) for i, p in enumerate(short_prompts)]
        return reqs

    t_cp = long_trace()
    eng_cp = ServingEngine(model_cp, params_cp, ecfg,
                           use_pallas=use_pallas)
    stats_cp = eng_cp.run(t_cp)
    t_or = long_trace()
    eng_or = ServingEngine(model_fl, params_fl, ecfg_oracle,
                           use_pallas=use_pallas)
    eng_or.run(t_or)
    streams_cp = {r.rid: list(r.generated) for r in t_cp}
    streams_or = {r.rid: list(r.generated) for r in t_or}
    mismatches = sum(
        1 for rid in streams_or
        for a, b in zip(streams_cp.get(rid, []), streams_or[rid])
        if a != b
    ) + sum(
        1 for rid in streams_or
        if len(streams_cp.get(rid, [])) != len(streams_or[rid])
    )
    need_pages = -(-(len(long_prompt) + 10) // page)
    leaked = int(np.asarray(eng_cp.pool.refs).sum())

    # ---- (b) short-request goodput: cp engine vs cp-free engine on
    # an identical short-only Poisson trace (both warmed once)
    trace_kw = dict(n_requests=12, mean_interarrival=0.6, len_lo=8,
                    len_hi=40, max_new_lo=3, max_new_hi=6, vocab=256)

    def short_goodput(model, params, cfg_e):
        for _warm in (False, True):
            eng = ServingEngine(model, params, cfg_e,
                                use_pallas=use_pallas)
            st = eng.run(poisson_trace(seed=11, **trace_kw))
        return st

    st_cp = short_goodput(model_cp, params_cp, ecfg)
    st_fl = short_goodput(model_fl, params_fl, ecfg)
    ratio = (st_cp.goodput_tok_per_s / st_fl.goodput_tok_per_s
             if st_fl.goodput_tok_per_s > 0 else float("inf"))

    # ---- (c) priced placement verdict: what a cp-free replica of one
    # pool shard says when refusing the long request, and the modeled
    # cp-vs-flat step-cost pair behind the router's choice
    verdict = refuse_long_context(
        cfg, page, need_pages,
        pool_pages=npages_shard,
        pages_per_seq=min(npages_shard, 1024),
        cp=1, spec=spec,
    )
    kv = need_pages * page
    hkv = cfg.n_kv_heads // tp
    g = cfg.n_heads // cfg.n_kv_heads
    cp_ms = cp_decode_step_ms(
        kv, cp=cp, page=page, hkv=hkv, g=g, d=cfg.head_dim,
        hidden=cfg.hidden, n_layers=cfg.n_layers, spec=spec,
        quant=cfg.kv_quant is not None)
    flat_ms = ragged_serving_step_ms(
        [kv], [1], page=page, hkv=hkv, g=g, d=cfg.head_dim,
        hidden=cfg.hidden, n_layers=cfg.n_layers, spec=spec,
        quant=cfg.kv_quant is not None)
    return {
        "metric": "serving_longcontext",
        "value": round(need_pages / npages_shard, 3),
        "unit": "x one-pool capacity served",
        "token_mismatches": int(mismatches),
        "leaked_pages": leaked,
        "long_request_pages": need_pages,
        "pool_pages_per_shard": npages_shard,
        "cp": cp,
        "tp": tp,
        "completed_long": stats_cp.completed,
        "evictions": stats_cp.evictions,
        "short_goodput_cp_tok_per_s": round(
            st_cp.goodput_tok_per_s, 1),
        "short_goodput_flat_tok_per_s": round(
            st_fl.goodput_tok_per_s, 1),
        "short_goodput_ratio": round(ratio, 3),
        "placement_verdict": verdict,
        "model_cp_step_ms": round(cp_ms, 4),
        "model_flat_step_ms": round(flat_ms, 4),
        "config": (
            f"tp={tp} cp={cp} slots={ecfg.slots} page={page} "
            f"npages/shard={npages_shard} long={len(long_prompt)}+10 "
            f"hidden={cfg.hidden} "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_serving_disaggregated(mesh, n, on_tpu, spec, tiny=False):
    """DISAGGREGATED prefill/decode (ISSUE 7 tentpole acceptance): the
    PR-6 Poisson trace served by a two-role topology on a 2×(n/2)
    hybrid mesh — a prefill slice runs chunked prefill, each finished
    request's int8 KV pages ship slice→slice on the quantized DCN wire
    (payload + per-row scale planes, the pool's native bytes), landing
    in the decode slice's pool overlapped with its decode steps — vs
    the COLOCATED PR-6 engine on the same n/2-chip slice serving the
    same trace. The number disaggregation must win is DECODE p99 step
    time: colocated decode steps carry interleaved prefill chunks (the
    contention), the decode role's steps never do. Both engines run the
    satellite temperature/top-k sampler (request-keyed draws — the two
    topologies still produce identical token streams, asserted here)."""
    import jax

    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.serving import (
        DisaggregatedEngine,
        ServingEngine,
        poisson_trace,
    )
    from triton_distributed_tpu.tune.perf_model import (
        kv_ship_ms,
        measured_page_issue_ms,
        refuse_disaggregation,
    )

    devs = jax.devices()
    if len(devs) < 2:
        return {"metric": "serving_disaggregated",
                "error": "needs >= 2 devices for a 2x(n/2) role split"}
    half = len(devs) // 2
    mesh_p = Mesh(np.asarray(devs[:half]), ("x",))
    mesh_d = Mesh(np.asarray(devs[half:2 * half]), ("x",))
    hybrid = Mesh(
        np.asarray(devs[:2 * half]).reshape(2, half), ("dcn", "x")
    )

    cfg, ecfg, trace_kw, s_cap = _serving_continuous_config(
        half, on_tpu, tiny
    )
    from dataclasses import replace as _rep

    if not on_tpu or tiny:
        # the CONTENDED shape of the comparison: prefill chunks much
        # wider than a decode batch (budget ≫ 8·slots), prompts many
        # chunks long, arrivals dense enough that colocated decode
        # steps almost always carry a prefill chunk. The decode role's
        # engine auto-narrows to an 8·slots packed width, so its steps
        # never pay the prefill-sized rectangle — the width gap that
        # IS the interference, visible even on the dev box where the
        # XLA-twin step cost is rectangle-shaped.
        s_cap = 256
        # int8 KV pools even at interpreter shapes: the ship's payload
        # is then the pool's native int8 bytes + per-row scale planes —
        # the quantized wire (and its compression) under test
        cfg = _rep(cfg, kv_quant="int8")
        ecfg = _rep(
            ecfg, slots=6, token_budget=256, chunk=128, page=8,
            npages=192,
        )
        trace_kw = dict(
            n_requests=24, mean_interarrival=0.8,
            len_lo=64, len_hi=192, max_new_lo=4, max_new_hi=10,
            vocab=trace_kw["vocab"],
        )
    ecfg = _rep(ecfg, temperature=0.7, top_k=40, seed=11)

    def build(mesh_role):
        model = Transformer(cfg, mesh_role, tp_axis="x")
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        params = model.quantize_moe_weights(params)
        params = model.quantize_dense_weights(params)
        return model, params

    model_p, params_p = build(mesh_p)
    model_d, params_d = build(mesh_d)

    def fresh_trace():
        return poisson_trace(seed=11, **trace_kw)

    import os as _os

    from triton_distributed_tpu.runtime import faults as _rt_faults
    from triton_distributed_tpu.runtime import watchdog as _rt_watchdog

    wd_trips = []

    def _guarded(run_fn):
        """Under --faults, arm the collective watchdog around the run
        (the serving_step / kv_ship host heartbeats are live) so a
        stalled ship or step TRIPS — the trip feeds the health ledger
        and releases the stall gates — instead of wedging the bench.
        Trips are reported, not fatal: the run's recovery behavior is
        the thing under test."""
        if _rt_faults.active_plan() is None:
            return run_fn()
        # generous default: the first guarded run pays jit compiles,
        # which can take seconds on the dev box — only a real stall
        # (or a wedged slice) should out-wait this
        deadline = float(_os.environ.get("TDTPU_BENCH_WATCHDOG", "10.0"))
        box = {}
        try:
            with _rt_watchdog.collective_watchdog(deadline=deadline):
                box["stats"] = run_fn()
        except _rt_watchdog.WatchdogTimeout as e:
            wd_trips.append(str(e).splitlines()[0])
        finally:
            _rt_watchdog.clear_trip()
        return box.get("stats")

    # ---- colocated baseline on the SAME n/2-chip slice (run twice;
    # the first run pays the compiles). Under a SliceDeath fault plan
    # this engine is untouched (no slice roles), so its token streams
    # stay the fault-free reference the failover must reproduce.
    for _warm in (False, True):
        trace_c = fresh_trace()
        eng_c = ServingEngine(model_p, params_p, ecfg)
        stats_c = _guarded(lambda: eng_c.run(trace_c))
    assert stats_c is not None and (
        stats_c.completed == trace_kw["n_requests"]
    ), (stats_c and stats_c.completed, wd_trips)

    # ---- disaggregated, KV on the quantized DCN wire
    for _warm in (False, True):
        trace_d = fresh_trace()
        eng = DisaggregatedEngine(
            model_p, params_p, model_d, params_d, ecfg,
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ship_delay_steps=1,
        )
        stats = _guarded(lambda: eng.run(trace_d))
    assert stats is not None and (
        stats.completed == trace_kw["n_requests"]
    ), (stats and stats.completed, len(eng._ready), len(eng._inflight),
        wd_trips)
    # token-exactness across topologies (int8 KV pages shipped
    # verbatim + request-keyed sampling): the split changes WHERE work
    # runs, never what it computes
    mismatches = sum(
        a.generated != b.generated for a, b in zip(trace_c, trace_d)
    )

    mean_len = (trace_kw["len_lo"] + trace_kw["len_hi"]) // 2
    pages_per_req = -(-mean_len // ecfg.page)
    hkv_l = cfg.n_kv_heads // half
    ship_model_ms = kv_ship_ms(
        pages_per_req, ecfg.page, hkv_l, cfg.head_dim, cfg.n_layers,
        cfg.kv_quant is not None, spec,
    )
    refusal = refuse_disaggregation(
        cfg, ecfg.page,
        {"prompt_len": mean_len,
         "max_new": (trace_kw["max_new_lo"] + trace_kw["max_new_hi"]) // 2},
        spec,
    )
    # the measured per-page issue cost (ROADMAP follow-on): steady-state
    # decode walks ~ceil(len/page) pages per active row, so the decode
    # role's p50 step over its typical row count prices one page walk
    steady_rows = max(
        1, min(ecfg.slots, int(np.median(
            [t for t in stats.decode.step_tokens if t > 0] or [1]
        )))
    )
    measured_issue = (
        stats.decode.p50_step_ms / (steady_rows * pages_per_req)
        if pages_per_req else 0.0
    )

    p99_c = stats_c.decode_p99_step_ms
    p99_d = stats.decode_p99_step_ms
    return {
        "metric": "serving_disaggregated",
        "value": round(p99_d, 2),
        "unit": "ms decode p99",
        "colocated_decode_p99_ms": round(p99_c, 2),
        "decode_p99_vs_colocated": round(p99_d / p99_c, 3) if p99_c else None,
        "decode_p99_improved": bool(p99_d < p99_c),
        "goodput_tok_per_s": round(stats.goodput_tok_per_s, 1),
        "colocated_goodput": round(stats_c.goodput_tok_per_s, 1),
        "goodput_vs_colocated": round(
            stats.goodput_tok_per_s / stats_c.goodput_tok_per_s, 3
        ) if stats_c.goodput_tok_per_s else None,
        "ships": stats.ships,
        "ship_p50_ms": round(float(np.median(stats.ship_ms)), 2)
        if stats.ship_ms else 0.0,
        "shipped_wire_bytes": stats.shipped_wire_bytes,
        "wire_compression_vs_raw": round(stats.wire_compression, 3),
        "degraded_transport": stats.degraded_transport,
        "final_transport": eng.transport,
        "ship_retries": stats.ship_retries,
        "transport_repromotions": stats.transport_repromotions,
        "kernel_repromotions": (
            stats.prefill.repromotions + stats.decode.repromotions
        ),
        # failover outcome (ISSUE 10): under a SliceDeath plan the
        # colocated run above is the fault-free token reference, so
        # token_mismatches_vs_colocated == 0 IS the token-exactness
        # acceptance; lost_requests must be 0
        "failover": stats.failover,
        "lost_requests": trace_kw["n_requests"] - stats.completed,
        "watchdog_trips": wd_trips,
        "health": eng.health.snapshot(),
        "token_mismatches_vs_colocated": mismatches,
        "prefill_evictions": stats.prefill.evictions,
        "decode_evictions": stats.decode.evictions,
        "kv_ship_model_ms_per_req": round(ship_model_ms, 4),
        "auto_placement": ("refused: " + refusal) if refusal else "accepted",
        "measured_page_issue_ms": round(measured_issue, 4),
        "model_page_issue_ms": measured_page_issue_ms(),
        "config": (
            f"2x{half} hybrid mesh, slots={ecfg.slots} "
            f"budget={ecfg.token_budget} chunk={ecfg.chunk} "
            f"page={ecfg.page} npages={ecfg.npages} "
            f"requests={trace_kw['n_requests']} "
            f"lens~U[{trace_kw['len_lo']},{trace_kw['len_hi']}] "
            f"temp=0.7 top_k=40 kvq={cfg.kv_quant} "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_serving_speculative_tree(mesh, n, on_tpu, spec, tiny=False):
    """The --tree paired row (ISSUE-18 acceptance): tree speculation
    (spec_tree verify trees under the kernel's TREE topology, the
    TreeDrafter's trunk + sibling branches) against linear draft-k on
    a BRANCHY SAMPLED motif trace — small top_k temperature sampling
    makes the prompt self-history genuinely ambiguous, the regime
    where sibling rescue branches accept tokens the single linear
    draft loses. Both engines must reproduce the plain engine's
    streams byte-identically; the tree row must land strictly more
    accepted tokens per verify step. Rides the pinned small recipe
    (the acceptance comparison is about scheduling, not FLOPs) so the
    row is deterministic on CPU and TPU alike. Also emits the
    in-batch shared-prefix dedup paired row: requests sharing a long
    prompt prefix served with ``prefix_share`` fold their duplicate
    frozen prefix pages onto one canonical page (deduped pages > 0,
    token-exact, goodput no worse)."""
    import jax
    from dataclasses import replace as _sp_rep

    from triton_distributed_tpu.models import Transformer, TransformerConfig
    from triton_distributed_tpu.serving import (
        EngineConfig,
        NGramDrafter,
        Request,
        ServingEngine,
        SpeculativeEngine,
        TreeDrafter,
        poisson_trace,
    )
    from triton_distributed_tpu.tune.perf_model import (
        DEFAULT_SPEC_ACCEPTANCE,
        expected_accepted_per_step,
        expected_accepted_per_step_tree,
    )

    cfg = TransformerConfig(
        vocab=128, n_layers=2, hidden=64, ffn=128, n_heads=4,
        n_kv_heads=2, head_dim=16, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    model = Transformer(cfg, mesh1, tp_axis="x")
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                        npages=40, temperature=1.0, top_k=4, seed=5)
    spec_tree, spec_k = 8, 4

    def branchy_trace():
        base = poisson_trace(13, 6, 0.5, 8, 30, 16, 24, 128)
        rng = np.random.default_rng(13 + 1000)
        for r in base:
            ln = len(r.prompt)
            motif = rng.integers(0, 128, (5,)).astype(np.int32)
            r.prompt = np.tile(motif, -(-ln // 5))[:ln]
        return base

    t_ref = branchy_trace()
    stats_ref = ServingEngine(model, params, ecfg).run(
        t_ref, max_steps=800)
    t_tree = branchy_trace()
    stats_tree = SpeculativeEngine(
        model, params, ecfg, spec_tree=spec_tree,
        drafter=TreeDrafter(branches=3, branch_len=2),
    ).run(t_tree, max_steps=800)
    t_lin = branchy_trace()
    stats_lin = SpeculativeEngine(
        model, params, ecfg, spec_k=spec_k, drafter=NGramDrafter(),
    ).run(t_lin, max_steps=800)
    assert (stats_ref.completed == stats_tree.completed
            == stats_lin.completed == len(t_ref))
    mism_tree = sum(
        a.generated != b.generated for a, b in zip(t_ref, t_tree))
    mism_lin = sum(
        a.generated != b.generated for a, b in zip(t_ref, t_lin))
    tree_acc = stats_tree.accepted_tokens_per_step
    lin_acc = stats_lin.accepted_tokens_per_step

    # ---- shared-prefix dedup paired row: one long common prefix
    def shared_trace():
        rng = np.random.default_rng(21)
        prefix = rng.integers(0, 128, (24,)).astype(np.int32)
        return [
            Request(rid=i,
                    prompt=np.concatenate(
                        [prefix,
                         rng.integers(0, 128, (4,)).astype(np.int32)]),
                    max_new=6, arrival=0.1 * i)
            for i in range(6)
        ]

    dcfg = _sp_rep(ecfg, slots=3, npages=64)
    for _warm in (False, True):            # warm run pays the compiles
        t_base = shared_trace()
        stats_base = ServingEngine(model, params, dcfg).run(
            t_base, max_steps=800)
    for _warm in (False, True):
        t_dd = shared_trace()
        stats_dd = ServingEngine(
            model, params,
            _sp_rep(dcfg, prefix_cache=True, prefix_share=True),
        ).run(t_dd, max_steps=800)
    assert stats_base.completed == stats_dd.completed == len(t_base)
    mism_dd = sum(
        a.generated != b.generated for a, b in zip(t_base, t_dd))

    return {
        "metric": "serving_speculative_tree",
        "value": round(tree_acc, 3),
        "unit": "accepted tok/verify-step",
        "accepted_tokens_per_step": round(tree_acc, 3),
        "linear_accepted_tokens_per_step": round(lin_acc, 3),
        "tree_beats_linear": bool(tree_acc > lin_acc),
        "token_mismatches_vs_nonspeculative": mism_tree,
        "linear_token_mismatches_vs_nonspeculative": mism_lin,
        "spec_rows": stats_tree.spec_rows,
        "draft_tokens": stats_tree.draft_tokens,
        "rolled_back_tokens": stats_tree.rolled_back_tokens,
        "steps": len(stats_tree.step_times),
        "steps_linear": len(stats_lin.step_times),
        "steps_nonspeculative": len(stats_ref.step_times),
        "model_accepted_per_step_linear_prior": round(
            expected_accepted_per_step(spec_k, DEFAULT_SPEC_ACCEPTANCE),
            3),
        "model_accepted_per_step_tree_prior": round(
            expected_accepted_per_step_tree(
                spec_tree, DEFAULT_SPEC_ACCEPTANCE, branches=3), 3),
        # the shared-prefix dedup row
        "shared_prefix_rows": stats_dd.shared_prefix_rows,
        "deduped_pages": stats_dd.deduped_pages,
        "dedup_token_mismatches": mism_dd,
        # scheduler-level goodput (generated tokens per STEP): the
        # deterministic "no worse" pin — dedup changes page aliasing,
        # never the step count or the streams. Wall-clock goodput rides
        # alongside; at interpreter-tiny shapes it sees the host-side
        # table rewrite but not the KV reads dedup saves, so it is
        # reported, not gated on.
        "dedup_goodput_ratio": round(
            (stats_dd.generated_tokens / len(stats_dd.step_times))
            / (stats_base.generated_tokens / len(stats_base.step_times)),
            3),
        "dedup_wallclock_goodput_ratio": round(
            stats_dd.goodput_tok_per_s / stats_base.goodput_tok_per_s, 3
        ) if stats_base.goodput_tok_per_s else None,
        "config": (
            f"spec_tree={spec_tree} TreeDrafter(branches=3, "
            f"branch_len=2) vs spec_k={spec_k} ngram, top_k=4 "
            f"temperature=1.0 branchy motif trace; dedup: 6 requests "
            f"sharing a 24-token prefix, page=8 "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_serving_speculative(mesh, n, on_tpu, spec, tiny=False,
                               tree=False):
    if tree:
        return _bench_serving_speculative_tree(mesh, n, on_tpu, spec,
                                               tiny=tiny)
    """SPECULATIVE decoding (ISSUE 12 tentpole acceptance): the PR-6
    Poisson trace with MOTIF-HEAVY prompts (repeated 5-token motifs —
    the traffic shape prompt-lookup speculation exists for) served
    three ways: (1) the plain colocated engine — the token-stream
    reference; (2) the colocated SpeculativeEngine (n-gram drafter,
    spec_k=4) — must reproduce the reference streams byte-identically
    while emitting >1 accepted token per verify row; (3) the
    disaggregated engine with a speculative decode role — same streams
    again, with KV still shipping on the quantized DCN wire at the
    CHANGED cadence (fewer, wider decode steps). Reports the decode
    p50/p99 deltas speculation buys and the perf-model rows that price
    the cadence change for placement (`spec_step_ms`, the truncated-
    geometric accepted/step prior, and `refuse_disaggregation` with
    and without `spec_k` in the traffic dict)."""
    import jax

    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.serving import (
        DisaggregatedEngine,
        NGramDrafter,
        ServingEngine,
        SpeculativeEngine,
        poisson_trace,
    )
    from triton_distributed_tpu.tune.perf_model import (
        DEFAULT_SPEC_ACCEPTANCE,
        expected_accepted_per_step,
        measured_page_issue_ms,
        ragged_serving_step_ms,
        refuse_disaggregation,
        spec_step_ms,
    )

    devs = jax.devices()
    if len(devs) < 2:
        return {"metric": "serving_speculative",
                "error": "needs >= 2 devices for the disaggregated leg"}
    half = len(devs) // 2
    mesh_p = Mesh(np.asarray(devs[:half]), ("x",))
    mesh_d = Mesh(np.asarray(devs[half:2 * half]), ("x",))
    hybrid = Mesh(
        np.asarray(devs[:2 * half]).reshape(2, half), ("dcn", "x")
    )

    cfg, ecfg, trace_kw, s_cap = _serving_continuous_config(
        half, on_tpu, tiny
    )
    from dataclasses import replace as _rep

    # GREEDY decode: at temperature 0 every engine argmaxes the same
    # logits, so acceptance is purely "did the drafter guess the
    # model's next token" — the honest accepted/step for prompt-lookup
    ecfg = _rep(ecfg, temperature=0.0, seed=11)
    if not on_tpu or tiny:
        # decode-heavy traffic: long generation tails (greedy decode on
        # a tiny model settles into repetitive continuations — the
        # regime prompt-lookup drafting feeds on) and pool headroom for
        # the provisional draft pages
        trace_kw = dict(
            trace_kw, len_lo=8, len_hi=32,
            max_new_lo=16, max_new_hi=32,
        )
        ecfg = _rep(ecfg, npages=64)
    spec_k = 4

    def build(mesh_role):
        model = Transformer(cfg, mesh_role, tp_axis="x")
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        params = model.quantize_moe_weights(params)
        params = model.quantize_dense_weights(params)
        return model, params

    model_p, params_p = build(mesh_p)
    model_d, params_d = build(mesh_d)

    def fresh_trace():
        """The Poisson arrivals/max_new, with every prompt rewritten
        into a repeated 5-token motif (fresh Request objects per call —
        engines mutate them in place). Deterministic."""
        base = poisson_trace(seed=11, **trace_kw)
        rng = np.random.default_rng(29)
        for r in base:
            ln = len(r.prompt)
            motif = rng.integers(
                0, trace_kw["vocab"], (5,)).astype(np.int32)
            r.prompt = np.tile(motif, -(-ln // 5))[:ln]
        return base

    # ---- (1) plain colocated reference (warm run pays compiles)
    for _warm in (False, True):
        trace_ref = fresh_trace()
        eng_ref = ServingEngine(model_p, params_p, ecfg)
        stats_ref = eng_ref.run(trace_ref)
    assert stats_ref.completed == trace_kw["n_requests"], (
        stats_ref.completed, stats_ref.deferrals)

    # ---- (2) colocated speculative, n-gram drafter
    for _warm in (False, True):
        trace_s = fresh_trace()
        eng_s = SpeculativeEngine(
            model_p, params_p, ecfg, spec_k=spec_k,
            drafter=NGramDrafter(),
        )
        stats_s = eng_s.run(trace_s)
    assert stats_s.completed == trace_kw["n_requests"], (
        stats_s.completed, stats_s.deferrals)
    mism_coloc = sum(
        a.generated != b.generated for a, b in zip(trace_ref, trace_s)
    )

    # ---- (3) disaggregated with a speculative decode role
    for _warm in (False, True):
        trace_d = fresh_trace()
        eng_d = DisaggregatedEngine(
            model_p, params_p, model_d, params_d, ecfg,
            hybrid_mesh=hybrid, dcn_axis="dcn", transport="dcn",
            ship_delay_steps=1, spec_k=spec_k, drafter=NGramDrafter(),
        )
        stats_d = eng_d.run(trace_d)
    assert stats_d.completed == trace_kw["n_requests"], (
        stats_d.completed, len(eng_d._ready), len(eng_d._inflight))
    mism_disagg = sum(
        a.generated != b.generated for a, b in zip(trace_ref, trace_d)
    )

    # ---- perf-model: the priced ship-cadence change. Speculation
    # widens each decode row to q=1+k and shrinks the decode window to
    # max_new/accepted steps — the rows placement reasons with.
    mean_len = (trace_kw["len_lo"] + trace_kw["len_hi"]) // 2
    hkv_l = max(1, cfg.n_kv_heads // half)
    g = cfg.n_heads // cfg.n_kv_heads
    plain_ms = ragged_serving_step_ms(
        [mean_len] * ecfg.slots, [1] * ecfg.slots, page=ecfg.page,
        hkv=hkv_l, g=g, d=cfg.head_dim, hidden=cfg.hidden,
        n_layers=cfg.n_layers, spec=spec,
        quant=cfg.kv_quant is not None,
        issue_ms=measured_page_issue_ms(),
    )
    spec_ms = spec_step_ms(
        [mean_len] * ecfg.slots, spec_k=spec_k, page=ecfg.page,
        hkv=hkv_l, g=g, d=cfg.head_dim, hidden=cfg.hidden,
        n_layers=cfg.n_layers, spec=spec,
        quant=cfg.kv_quant is not None,
        issue_ms=measured_page_issue_ms(),
    )
    prior_acc = expected_accepted_per_step(
        spec_k, DEFAULT_SPEC_ACCEPTANCE
    )
    measured_acc = stats_s.accepted_tokens_per_step
    traffic = {
        "prompt_len": mean_len,
        "max_new": (trace_kw["max_new_lo"]
                    + trace_kw["max_new_hi"]) // 2,
    }
    refusal_plain = refuse_disaggregation(cfg, ecfg.page, traffic, spec)
    p_meas = (min(1.0, stats_s.draft_acceptance_rate)
              if stats_s.draft_tokens else DEFAULT_SPEC_ACCEPTANCE)
    refusal_spec = refuse_disaggregation(
        cfg, ecfg.page,
        dict(traffic, spec_k=spec_k, spec_acceptance=p_meas),
        spec,
    )

    p50_ref, p99_ref = (stats_ref.decode_p50_step_ms,
                        stats_ref.decode_p99_step_ms)
    p50_s, p99_s = (stats_s.decode_p50_step_ms,
                    stats_s.decode_p99_step_ms)
    return {
        "metric": "serving_speculative",
        "value": round(measured_acc, 3),
        "unit": "accepted tok/verify-step",
        "accepted_tokens_per_step": round(measured_acc, 3),
        "draft_acceptance_rate": round(
            stats_s.draft_acceptance_rate, 3),
        "token_mismatches_vs_nonspeculative": mism_coloc,
        "token_mismatches_disaggregated": mism_disagg,
        "spec_rows": stats_s.spec_rows,
        "draft_tokens": stats_s.draft_tokens,
        "rolled_back_tokens": stats_s.rolled_back_tokens,
        "steps": len(stats_s.step_times),
        "steps_nonspeculative": len(stats_ref.step_times),
        "decode_p50_step_ms": round(p50_s, 2),
        "decode_p99_step_ms": round(p99_s, 2),
        "decode_p50_delta_ms": round(p50_s - p50_ref, 2),
        "decode_p99_delta_ms": round(p99_s - p99_ref, 2),
        "goodput_tok_per_s": round(stats_s.goodput_tok_per_s, 1),
        "goodput_vs_nonspeculative": round(
            stats_s.goodput_tok_per_s / stats_ref.goodput_tok_per_s, 3
        ) if stats_ref.goodput_tok_per_s else None,
        "disagg_accepted_tokens_per_step": round(
            stats_d.decode.accepted_tokens_per_step, 3),
        "disagg_ships": stats_d.ships,
        "disagg_decode_p99_ms": round(stats_d.decode_p99_step_ms, 2),
        # the priced cadence change: ms per EMITTED token, before and
        # after speculation — what replica_load_ms and auto placement
        # now reason with
        "model_plain_step_ms": round(plain_ms, 4),
        "model_spec_step_ms": round(spec_ms, 4),
        "model_accepted_per_step_prior": round(prior_acc, 3),
        "model_ms_per_token_plain": round(plain_ms, 4),
        "model_ms_per_token_spec": round(
            spec_ms / max(measured_acc, 1.0), 4),
        "auto_placement_plain": (
            ("refused: " + refusal_plain) if refusal_plain
            else "accepted"),
        "auto_placement_spec": (
            ("refused: " + refusal_spec) if refusal_spec
            else "accepted"),
        "config": (
            f"2x{half} hybrid mesh, spec_k={spec_k} ngram drafter "
            f"slots={ecfg.slots} budget={ecfg.token_budget} "
            f"chunk={ecfg.chunk} page={ecfg.page} "
            f"npages={ecfg.npages} requests={trace_kw['n_requests']} "
            f"motif-prompts lens~U[{trace_kw['len_lo']},"
            f"{trace_kw['len_hi']}] greedy "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _fleet_trace(trace_kw, page):
    """The serving_fleet traffic: the seeded Poisson base PLUS two
    session bursts, each sharing its OWN 10-page prompt prefix — a
    leader arrives early and its followers arrive after the leader's
    prefill has published the prefix pages. A cache-aware router lands
    every follower on resident pages (one prefill per session);
    round-robin scatters each session across replicas and pays the
    prefill once per replica. Deterministic; fresh Request objects per
    call (engines mutate them in place)."""
    from triton_distributed_tpu.serving import poisson_trace
    from triton_distributed_tpu.serving.engine import Request

    base = poisson_trace(seed=13, **trace_kw)
    rng = np.random.default_rng(17)
    out = list(base)
    rid = len(base)
    for s in range(2):
        prefix = rng.integers(
            0, trace_kw["vocab"], (10 * page,)).astype(np.int32)
        # leader at 1.0/2.0 (prefilled well before the acceptance
        # plan's step-8 death); followers straddle the death
        arrivals = [1.0 + s] + [8.0 + s + 1.5 * j for j in range(5)]
        for a in arrivals:
            tail = rng.integers(
                0, trace_kw["vocab"], (int(rng.integers(4, 12)),)
            ).astype(np.int32)
            req = Request(
                rid=rid,
                prompt=np.concatenate([prefix, tail]),
                max_new=int(rng.integers(trace_kw["max_new_lo"],
                                         trace_kw["max_new_hi"])),
                arrival=a,
            )
            req.session = f"burst-{s}"
            out.append(req)
            rid += 1
    return out


def _bench_serving_fleet(mesh, n, on_tpu, spec, tiny=False,
                         spec_k=None):
    """FLEET serving (ISSUE 11 tentpole acceptance): 3 engine replicas,
    each on its own mesh slice carved by ``carve_replica_meshes``,
    behind the scored ``FleetRouter`` (prefix overlap × health × load
    estimate, session affinity, spill) vs a ROUND-ROBIN baseline on
    the same Poisson + shared-prefix-burst trace. Under a --faults
    ``ReplicaDeath`` plan the dead replica's in-flight requests drain
    back through the router onto the survivor: ``lost_requests`` must
    be 0 and the token streams byte-identical to the fault-free
    reference run (request-keyed sampling — placement cannot change
    tokens).

    ``--spec-k K`` (ISSUE 13 satellite) swaps every replica for a
    :class:`SpeculativeEngine` at draft budget K (ngram drafter, motif
    prompts so prompt-lookup drafting has something to accept): the
    NON-speculative scored fleet becomes the reference run, so the
    token oracle simultaneously proves fleet-level speculative
    token-exactness, and the output adds per-replica accepted
    tokens/step plus the spec-vs-plain goodput ratio on the identical
    trace."""
    import os as _os

    import jax

    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.runtime import faults as _rt_faults
    from triton_distributed_tpu.runtime import watchdog as _rt_watchdog
    from triton_distributed_tpu.runtime.topology import (
        carve_replica_meshes,
    )
    from triton_distributed_tpu.serving import (
        NGramDrafter,
        ServingEngine,
        SpeculativeEngine,
    )
    from triton_distributed_tpu.serving.fleet import (
        RouterConfig,
        ServingFleet,
    )

    devs = jax.devices()
    # 3 replicas: the acceptance plan kills replica 1 mid-trace, and
    # with TWO survivors the router keeps being a router afterwards —
    # a 2-replica fleet degenerates to "route everything to the lone
    # survivor" where every policy is equal
    n_replicas = 3
    meshes = carve_replica_meshes(n_replicas, devs)
    w = int(meshes[0].devices.size)
    cfg, ecfg, trace_kw, s_cap = _serving_continuous_config(
        w, on_tpu, tiny
    )
    from dataclasses import replace as _rep

    if not on_tpu or tiny:
        # small enough for the CI smoke, big enough that the burst's
        # shared prefix (10 pages, ~5 prefill chunks) dominates the
        # routing decision
        trace_kw = dict(
            n_requests=12, mean_interarrival=1.0,
            len_lo=8, len_hi=40,
            # spec fleets need decode room for the drafter to earn
            # accepts; the plain fleet headline keeps short tails
            max_new_lo=8 if spec_k else 3,
            max_new_hi=14 if spec_k else 7,
            vocab=trace_kw["vocab"],
        )
        ecfg = _rep(ecfg, slots=4, token_budget=48, chunk=16, page=8,
                    npages=64)
    ecfg = _rep(ecfg, prefix_cache=True, temperature=0.7, top_k=40,
                seed=11)

    models = []
    for m in meshes:
        model = Transformer(cfg, m, tp_axis="x")
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        params = model.quantize_moe_weights(params)
        params = model.quantize_dense_weights(params)
        models.append((model, params))

    def fresh_trace():
        out = _fleet_trace(trace_kw, ecfg.page)
        if spec_k:
            # motif prompts: prompt-lookup drafting needs repeats to
            # accept — without them the spec fleet degenerates to a
            # k=0 fleet and the ratio measures only verify overhead
            rng = np.random.default_rng(23)
            for r in out:
                ln = len(r.prompt)
                motif = rng.integers(
                    0, trace_kw["vocab"], (5,)).astype(np.int32)
                r.prompt = np.tile(motif, -(-ln // 5))[:ln]
        return out

    n_total = len(fresh_trace())

    def build_fleet(policy, k=None):
        if k:
            engines = [SpeculativeEngine(model, params, ecfg,
                                         spec_k=k,
                                         drafter=NGramDrafter())
                       for model, params in models]
        else:
            engines = [ServingEngine(model, params, ecfg)
                       for model, params in models]
        return ServingFleet(
            engines, seed=1, router=RouterConfig(policy=policy),
            meshes=meshes,
        )

    wd_trips = []

    def _guarded(run_fn):
        # same contract as the disaggregated bench: under --faults the
        # collective watchdog is armed so a stalled router_dispatch /
        # serving_step trips into the ledger instead of wedging
        if _rt_faults.active_plan() is None:
            return run_fn()
        deadline = float(_os.environ.get("TDTPU_BENCH_WATCHDOG", "10.0"))
        box = {}
        try:
            with _rt_watchdog.collective_watchdog(deadline=deadline):
                box["out"] = run_fn()
        except _rt_watchdog.WatchdogTimeout as e:
            wd_trips.append(str(e).splitlines()[0])
        finally:
            _rt_watchdog.clear_trip()
        return box.get("out")

    # ---- fault-free reference (the token oracle; run twice — the
    # first run pays every jit compile for both replica models)
    plan = _rt_faults.active_plan()
    _rt_faults.set_fault_plan(None)
    try:
        for _warm in (False, True):
            ref_fleet = build_fleet("scored")
            ref_fleet.run(fresh_trace())
    finally:
        _rt_faults.set_fault_plan(plan)
    ref_tokens = ref_fleet.token_streams()
    assert ref_fleet.stats.lost_requests == 0, ref_fleet.stats

    # ---- the routed fleet under the active plan (the headline run;
    # with --spec-k these replicas are SPECULATIVE and the non-spec
    # reference above doubles as the goodput baseline)
    fleet = build_fleet("scored", k=spec_k)
    stats = _guarded(lambda: fleet.run(fresh_trace()))
    assert stats is not None, wd_trips

    # ---- round-robin baseline under the SAME plan
    rr = build_fleet("round_robin", k=spec_k)
    rr_stats = _guarded(lambda: rr.run(fresh_trace()))
    assert rr_stats is not None, wd_trips

    tokens = fleet.token_streams()
    mismatches = sum(
        1 for rid, t in ref_tokens.items() if tokens.get(rid) != t
    )

    def hit_rate(fl):
        total_pages = sum(
            len(rec["req"].prompt) // ecfg.page
            for rec in fl.stats.records.values())
        return fl.prefix_hits / total_pages if total_pages else 0.0

    goodput = fleet.goodput_tok_per_s
    rr_goodput = rr.goodput_tok_per_s
    out = {
        "metric": "serving_fleet",
        "value": round(goodput, 1),
        "unit": "tok/s fleet goodput (modeled wall)",
        "rr_goodput": round(rr_goodput, 1),
        "goodput_vs_round_robin": round(goodput / rr_goodput, 3)
        if rr_goodput else None,
        "ticks": fleet.ticks,
        "rr_ticks": rr.ticks,
        "p99_ttft_ticks": round(stats.p99_ttft_ticks, 2),
        "p99_tpot_ticks": round(stats.p99_tpot_ticks, 2),
        "rr_p99_ttft_ticks": round(rr_stats.p99_ttft_ticks, 2),
        "prefix_hit_rate": round(hit_rate(fleet), 3),
        "rr_prefix_hit_rate": round(hit_rate(rr), 3),
        "completed": stats.completed,
        "lost_requests": stats.lost_requests,
        "rr_lost_requests": rr_stats.lost_requests,
        "token_mismatches_vs_fault_free": mismatches,
        "deaths": stats.deaths,
        "failover_requeued": stats.failover_requeued,
        "failover_re_prefill_tokens": stats.failover_re_prefill_tokens,
        "routed": {str(k): v for k, v in sorted(stats.routed.items())},
        "spills": stats.spills,
        "affinity_hits": stats.affinity_hits,
        "probes": stats.probes,
        "rotation": list(fleet.rotation()),
        "watchdog_trips": wd_trips,
        "health": fleet.health.snapshot(),
        "config": (
            f"replicas={n_replicas}x{w} slots={ecfg.slots} "
            f"budget={ecfg.token_budget} chunk={ecfg.chunk} "
            f"page={ecfg.page} npages={ecfg.npages} "
            f"requests={n_total} temp=0.7 top_k=40 "
            f"prefix_cache=on fleet_seed=1 "
            + (f"spec_k={spec_k} ngram-drafter " if spec_k else "")
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }
    if spec_k:
        nonspec_goodput = ref_fleet.goodput_tok_per_s
        out.update({
            "spec_k": spec_k,
            # per-replica accepted tokens per verify step — the spec
            # win the router's load term prices replicas by
            "accepted_tokens_per_step": {
                str(r.index): round(
                    r.engine.stats.accepted_tokens_per_step, 3)
                for r in fleet.replicas},
            "spec_rows": {
                str(r.index): r.engine.stats.spec_rows
                for r in fleet.replicas},
            "nonspec_goodput": round(nonspec_goodput, 1),
            "goodput_vs_nonspec": round(goodput / nonspec_goodput, 3)
            if nonspec_goodput else None,
        })
    return out


def _bench_serving_elastic(mesh, n, on_tpu, spec, tiny=False):
    """ELASTIC fleet (ISSUE 13 tentpole acceptance): 2 active replicas
    plus one RESERVE slice carved by ``carve_replica_meshes(...,
    reserve=1)``, a seeded :class:`FleetAutoscaler` that spawns from
    the reserve under sustained priced pressure (the newcomer earns
    admission through the PR-10 probation-probe path), then a planned
    ``drain`` of replica 0 once the newcomer is HEALTHY — its resident
    rows MIGRATE their committed KV pages over the kv_ship wire when
    ``perf_model.migrate_vs_reprefill_ms`` prices the wire under the
    recompute. Composes with the --faults acceptance plan
    ``ReplicaDeath(replica=1, step=N)``: the death, the grow and the
    drain all land in one run, and still lost_requests == 0 with every
    stream byte-identical to the fault-free reference. The whole
    grow/drain/migrate event log is replayed twice under the same
    fleet seed and must come back identical."""
    import os as _os

    import jax

    from triton_distributed_tpu import config as _config
    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.runtime import faults as _rt_faults
    from triton_distributed_tpu.runtime import watchdog as _rt_watchdog
    from triton_distributed_tpu.runtime.health import (
        HealthLedger,
        PeerState,
    )
    from triton_distributed_tpu.runtime.topology import (
        carve_replica_meshes,
    )
    from triton_distributed_tpu.serving import ServingEngine
    from triton_distributed_tpu.serving.fleet import (
        AutoscalerConfig,
        RouterConfig,
        ServingFleet,
    )

    devs = jax.devices()
    n_active = 2
    active_meshes, spare_meshes = carve_replica_meshes(
        n_active, devs, reserve=1)
    w = int(active_meshes[0].devices.size)
    cfg, ecfg, trace_kw, s_cap = _serving_continuous_config(
        w, on_tpu, tiny
    )
    from dataclasses import replace as _rep

    if not on_tpu or tiny:
        trace_kw = dict(
            n_requests=14, mean_interarrival=0.6,
            len_lo=8, len_hi=40, max_new_lo=4, max_new_hi=8,
            vocab=trace_kw["vocab"],
        )
        ecfg = _rep(ecfg, slots=4, token_budget=48, chunk=16, page=8,
                    npages=64)
    ecfg = _rep(ecfg, prefix_cache=True, temperature=0.7, top_k=40,
                seed=11)

    models = []
    for m in list(active_meshes) + list(spare_meshes):
        model = Transformer(cfg, m, tp_axis="x")
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        params = model.quantize_moe_weights(params)
        params = model.quantize_dense_weights(params)
        models.append((model, params))

    def fresh_trace():
        return _fleet_trace(trace_kw, ecfg.page)

    n_total = len(fresh_trace())
    grown_peer = f"replica:{n_active}"

    def build_fleet(elastic=True):
        engines = [ServingEngine(model, params, ecfg)
                   for model, params in models[:n_active]]
        spare_model, spare_params = models[n_active]
        if not elastic:
            return ServingFleet(
                engines, seed=1, router=RouterConfig(),
                meshes=list(active_meshes))
        return ServingFleet(
            engines, seed=1,
            router=RouterConfig(queue_cap=4),
            # fast probation so the grown replica earns admission
            # within the trace (the PR-10 knobs, not a blind add)
            health=HealthLedger(seed=1, probation_after=1,
                                promote_after=1, probe_interval=2),
            meshes=list(active_meshes),
            reserve=[(lambda: ServingEngine(spare_model, spare_params,
                                            ecfg),
                      spare_meshes[0])],
            autoscaler=AutoscalerConfig(slo_ms=0.0, window=2,
                                        cooldown=50, max_replicas=3),
        )

    def drive(fleet, max_ticks=2000):
        """fleet.run plus the drain trigger: once the grown replica is
        HEALTHY, replica 0 is drained — the planned-retirement half of
        the elastic story, with the autoscaler's grow and the fault
        plan's death composing around it."""
        fleet.submit_trace(fresh_trace())
        prev = _config.fleet_seed()
        _config.set_fleet_seed(fleet.seed)
        drained = False
        try:
            for _ in range(max_ticks):
                if fleet.idle:
                    break
                if (not drained and fleet.stats.grows
                        and fleet.health.state(grown_peer)
                        is PeerState.HEALTHY):
                    fleet.drain(0)
                    drained = True
                fleet.tick()
        finally:
            _config.set_fleet_seed(prev)
        return fleet.stats

    wd_trips = []

    def _guarded(run_fn):
        if _rt_faults.active_plan() is None:
            return run_fn()
        deadline = float(_os.environ.get("TDTPU_BENCH_WATCHDOG", "10.0"))
        box = {}
        try:
            with _rt_watchdog.collective_watchdog(deadline=deadline):
                box["out"] = run_fn()
        except _rt_watchdog.WatchdogTimeout as e:
            wd_trips.append(str(e).splitlines()[0])
        finally:
            _rt_watchdog.clear_trip()
        return box.get("out")

    # ---- fault-free static reference (the token oracle; run twice —
    # the first run pays every jit compile for the replica models)
    plan = _rt_faults.active_plan()
    _rt_faults.set_fault_plan(None)
    try:
        for _warm in (False, True):
            ref_fleet = build_fleet(elastic=False)
            ref_fleet.run(fresh_trace())
    finally:
        _rt_faults.set_fault_plan(plan)
    ref_tokens = ref_fleet.token_streams()
    assert ref_fleet.stats.lost_requests == 0, ref_fleet.stats

    # ---- the elastic run under the active plan (grow + drain +
    # migrate + whatever the plan throws at it)
    fleet = build_fleet()
    stats = _guarded(lambda: drive(fleet))
    assert stats is not None, wd_trips

    # ---- replay determinism: the same fleet seed and trace must
    # produce the byte-identical grow/drain/migration event log
    fleet2 = build_fleet()
    stats2 = _guarded(lambda: drive(fleet2))
    assert stats2 is not None, wd_trips
    events_deterministic = list(stats.events) == list(stats2.events)

    tokens = fleet.token_streams()
    mismatches = sum(
        1 for rid, t in ref_tokens.items() if tokens.get(rid) != t
    )
    goodput = fleet.goodput_tok_per_s
    priced = [(round(wms, 6), round(rms, 6))
              for wms, rms in stats.migration_priced]
    return {
        "metric": "serving_elastic",
        "value": round(goodput, 1),
        "unit": "tok/s fleet goodput (modeled wall)",
        "ticks": fleet.ticks,
        "completed": stats.completed,
        "lost_requests": stats.lost_requests,
        "token_mismatches_vs_fault_free": mismatches,
        "grows": stats.grows,
        "drains": stats.drains,
        "drain_requeued": stats.drain_requeued,
        "migrations": stats.migrations,
        "migrations_cheaper_than_reprefill": stats.migrations_cheaper,
        "migrated_pages": stats.migrated_pages,
        "migration_wire_bytes": stats.migration_wire_bytes,
        "migration_priced_ms": priced[:8],
        "migration_refusals": stats.migration_refusals,
        "migration_failures": stats.migration_failures,
        "deaths": stats.deaths,
        "failover_requeued": stats.failover_requeued,
        "admission_rejections": stats.admission_rejections,
        "probes": stats.probes,
        "routed": {str(k): v for k, v in sorted(stats.routed.items())},
        "rotation": list(fleet.rotation()),
        "event_log": [list(e) for e in stats.events[:24]],
        "event_log_deterministic": events_deterministic,
        "watchdog_trips": wd_trips,
        "health": fleet.health.snapshot(),
        "config": (
            f"active={n_active}x{w} reserve=1x{w} slots={ecfg.slots} "
            f"budget={ecfg.token_budget} chunk={ecfg.chunk} "
            f"page={ecfg.page} npages={ecfg.npages} "
            f"requests={n_total} queue_cap=4 slo_ms=0.0 window=2 "
            f"temp=0.7 top_k=40 prefix_cache=on fleet_seed=1 "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_serving_multitenant(mesh, n, on_tpu, spec, tiny=False):
    """MULTI-TENANT fleet (ISSUE 16 tentpole acceptance): 3 replicas,
    an interactive trickle under a 4x BATCH FLOOD plus a background
    drip, per-tenant :class:`TenantConfig` (tight interactive SLO →
    the router's deadline-slack term is live), the seeded
    :class:`BrownoutController` armed and ``queue_cap`` admission
    counting tier-visible depth. Four runs:

    1. fault-free SINGLE-TENANT oracle over the identical trace — the
       token-exactness reference (sampling is request-keyed, so
       preemption/shed/retry may reorder WHEN a token appears, never
       WHICH token);
    2. flood-free interactive-only run under the SAME fault plan —
       the p99 baseline the brownout + preemption must protect;
    3. the headline multi-tenant run under the plan (the acceptance
       line adds ``--faults "seed=1; ReplicaDeath(replica=1,
       step=8)"``): interactive p99 no worse than (2), every shed on
       background/batch with background shed strictly first,
       preemptions > 0, zero pool-page leaks on live replicas, zero
       lost requests;
    4. a same-seed replay of (3) — the event log (placement,
       preemption, shed, brownout transition, retune) must come back
       byte-identical (the PR-13 replay contract extended to the
       multi-tenant events)."""
    import os as _os

    import jax

    from triton_distributed_tpu import config as _config
    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.runtime import faults as _rt_faults
    from triton_distributed_tpu.runtime import watchdog as _rt_watchdog
    from triton_distributed_tpu.runtime.topology import (
        carve_replica_meshes,
    )
    from triton_distributed_tpu.serving import (
        BrownoutConfig,
        Request,
        ServingEngine,
        TenantConfig,
    )
    from triton_distributed_tpu.serving.fleet import (
        RouterConfig,
        ServingFleet,
    )

    devs = jax.devices()
    n_replicas = 3
    meshes = carve_replica_meshes(n_replicas, devs)
    w = int(meshes[0].devices.size)
    cfg, ecfg, _trace_kw, _s_cap = _serving_continuous_config(
        w, on_tpu, tiny
    )
    from dataclasses import replace as _rep

    if not on_tpu or tiny:
        ecfg = _rep(ecfg, slots=4, token_budget=48, chunk=16, page=8,
                    npages=64)
    ecfg = _rep(ecfg, prefix_cache=True, temperature=0.7, top_k=40,
                seed=11)
    # SLOs scale with the perf model's step cost: interpreter-tiny
    # models step in ~microseconds of MODEL time, headline in ms
    slo_iact = 0.05 if (tiny or not on_tpu) else 50.0
    slo_brownout = 0.004 if (tiny or not on_tpu) else 4.0
    tenants = {
        "iact": TenantConfig(priority="interactive", slo_ms=slo_iact),
        "bat": TenantConfig(priority="batch"),
        "bg": TenantConfig(priority="background"),
    }

    models = []
    for m in meshes:
        model = Transformer(cfg, m, tp_axis="x")
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s),
            model.init(jax.random.PRNGKey(7)), model.shardings(),
        )
        params = model.quantize_moe_weights(params)
        params = model.quantize_dense_weights(params)
        models.append((model, params))

    import numpy as _np

    n_iact, n_bat, n_bg = 6, 24, 6       # the 4x batch flood

    def fresh_trace(only_interactive=False):
        out, rid = [], 0

        def mk(rid, arrival, tenant, plen):
            rng = _np.random.default_rng(5000 + rid)
            prompt = rng.integers(
                0, cfg.vocab, (plen,)).astype(_np.int32)
            r = Request(rid=rid, prompt=prompt, max_new=5,
                        arrival=arrival)
            r.tenant = tenant
            return r

        for i in range(n_iact):
            out.append(mk(rid, i * 3.0, "iact", 20)); rid += 1
        for i in range(n_bat):
            r = mk(rid, 1.0 + i * 0.2, "bat", 24); rid += 1
            if not only_interactive:
                out.append(r)
        for i in range(n_bg):
            r = mk(rid, i * 1.5, "bg", 16); rid += 1
            if not only_interactive:
                out.append(r)
        return out

    def build_fleet(multitenant=True):
        engines = [ServingEngine(model, params, ecfg)
                   for model, params in models]
        if not multitenant:
            return ServingFleet(engines, seed=1,
                                router=RouterConfig(),
                                meshes=list(meshes))
        return ServingFleet(
            engines, seed=1,
            router=RouterConfig(queue_cap=3),
            meshes=list(meshes),
            tenants=tenants,
            brownout=BrownoutConfig(slo_ms=slo_brownout, window=2,
                                    cooldown=3),
        )

    def drive(fleet, trace, max_ticks=2000):
        fleet.submit_trace(trace)
        prev = _config.fleet_seed()
        _config.set_fleet_seed(fleet.seed)
        try:
            for _ in range(max_ticks):
                if fleet.idle:
                    break
                fleet.tick()
        finally:
            _config.set_fleet_seed(prev)
        return fleet.stats

    wd_trips = []

    def _guarded(run_fn):
        if _rt_faults.active_plan() is None:
            return run_fn()
        deadline = float(_os.environ.get("TDTPU_BENCH_WATCHDOG",
                                         "10.0"))
        box = {}
        try:
            with _rt_watchdog.collective_watchdog(deadline=deadline):
                box["out"] = run_fn()
        except _rt_watchdog.WatchdogTimeout as e:
            wd_trips.append(str(e).splitlines()[0])
        finally:
            _rt_watchdog.clear_trip()
        return box.get("out")

    # ---- (1) fault-free single-tenant oracle (run twice — the first
    # pays every jit compile for the replica models)
    plan = _rt_faults.active_plan()
    _rt_faults.set_fault_plan(None)
    try:
        for _warm in (False, True):
            oracle = build_fleet(multitenant=False)
            drive(oracle, fresh_trace())
    finally:
        _rt_faults.set_fault_plan(plan)
    ref_tokens = oracle.token_streams()
    assert oracle.stats.lost_requests == 0, oracle.stats

    # ---- (2) flood-free interactive-only baseline, SAME fault plan:
    # the p99 the flood must not degrade
    base = build_fleet()
    base_stats = _guarded(
        lambda: drive(base, fresh_trace(only_interactive=True)))
    assert base_stats is not None, wd_trips
    p99_free = base.per_tenant()["iact"]["p99_ttft_ticks"]

    # ---- (3) the headline multi-tenant flood under the plan
    fleet = build_fleet()
    stats = _guarded(lambda: drive(fleet, fresh_trace()))
    assert stats is not None, wd_trips

    # ---- (4) same-seed replay: byte-identical event log
    fleet2 = build_fleet()
    stats2 = _guarded(lambda: drive(fleet2, fresh_trace()))
    assert stats2 is not None, wd_trips
    events_deterministic = list(stats.events) == list(stats2.events)

    per_tenant = fleet.per_tenant()
    p99_flood = per_tenant["iact"]["p99_ttft_ticks"]
    tokens = fleet.token_streams()
    mismatches = sum(
        1 for rid, t in ref_tokens.items() if tokens.get(rid) != t
    )
    shed_tiers = [e[3].split("tier=")[1].split()[0]
                  for e in stats.events if e[0] == "shed"]
    bg_shed_first = ("batch" not in shed_tiers
                     or "background" in
                     shed_tiers[:shed_tiers.index("batch")])
    leaked = sum(role.pool.held_pages
                 for r in fleet._alive() for role in r._roles)

    # the acceptance pins — loud here, and ci/fast.sh re-derives them
    # from the JSON so the smoke exits nonzero on any regression
    assert stats.lost_requests == 0, stats
    assert mismatches == 0, (
        f"{mismatches} admitted streams diverged from the fault-free "
        "single-tenant oracle")
    assert set(shed_tiers) <= {"background", "batch"}, shed_tiers
    assert bg_shed_first, shed_tiers
    assert fleet.preemptions > 0, "flood never forced a preemption"
    assert leaked == 0, f"{leaked} pool pages leaked on live replicas"
    assert p99_flood <= p99_free, (
        f"interactive p99 degraded under flood: "
        f"{p99_flood} > {p99_free}")

    return {
        "metric": "serving_multitenant",
        "value": round(fleet.goodput_tok_per_s, 1),
        "unit": "tok/s fleet goodput (modeled wall)",
        "ticks": fleet.ticks,
        "completed": stats.completed,
        "lost_requests": stats.lost_requests,
        "token_mismatches_vs_single_tenant_oracle": mismatches,
        "interactive_p99_ttft_ticks_flood": p99_flood,
        "interactive_p99_ttft_ticks_flood_free": p99_free,
        "preemptions": fleet.preemptions,
        "tenant_preemptions": fleet.tenant_preemptions(),
        "sheds_by_tier": dict(stats.sheds),
        "background_shed_before_batch": bg_shed_first,
        "brownout_transitions": [
            e[3] for e in stats.events if e[0] == "brownout"],
        "pool_pages_leaked": leaked,
        "deaths": stats.deaths,
        "failover_requeued": stats.failover_requeued,
        "admission_rejections": stats.admission_rejections,
        "per_tenant": per_tenant,
        "routed": {str(k): v for k, v in sorted(stats.routed.items())},
        "event_log": [list(e) for e in stats.events[:24]],
        "event_log_deterministic": events_deterministic,
        "watchdog_trips": wd_trips,
        "config": (
            f"replicas={n_replicas}x{w} slots={ecfg.slots} "
            f"budget={ecfg.token_budget} chunk={ecfg.chunk} "
            f"page={ecfg.page} npages={ecfg.npages} "
            f"trace={n_iact}iact+{n_bat}bat+{n_bg}bg queue_cap=3 "
            f"slo_iact={slo_iact} brownout_slo={slo_brownout} "
            f"window=2 cooldown=3 temp=0.7 top_k=40 fleet_seed=1 "
            + ("tiny-dryrun" if tiny or not on_tpu else "headline")
        ),
    }


def _bench_flash_decode(mesh, n, on_tpu, spec):
    from triton_distributed_tpu.kernels.flash_decode import gqa_fwd_batch_decode

    b, hq, hkv, d, s_len = (4, 32, 8, 128, 8192) if on_tpu else (2, 8, 2, 128, 1024)
    q = jax.random.normal(jax.random.PRNGKey(0), (b, hq, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s_len, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s_len, d), jnp.bfloat16)
    lens = jnp.full((b,), s_len, jnp.int32)

    def step(state, s):
        q, k, v = state
        out, _lse = gqa_fwd_batch_decode(
            q, k, v, lens, kv_layout="bhsd", block_k=4096 if on_tpu else 256
        )
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(q, s), k, v), s

    lo, hi = (16, 300) if on_tpu else (1, 3)
    t = bench_loop(step, (q, k, v), lo=lo, hi=hi)
    kv_bytes = 2 * b * s_len * hkv * d * 2
    gbps = kv_bytes / t / 1e9

    # int8 KV twin at the same shape (half the cache bytes; scales fold
    # in-softmax — kernels/flash_decode.py q8 mode)
    from triton_distributed_tpu.kernels.flash_decode import (
        gqa_fwd_batch_decode_q8,
        quantize_kv,
    )

    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)

    def step_q8(state, s):
        q, kq, ks, vq, vs = state
        out, _ = gqa_fwd_batch_decode_q8(
            q, kq, ks, vq, vs, lens, block_k=4096 if on_tpu else 256
        )
        s = s + jnp.sum(out.astype(jnp.float32))
        return (perturb(q, s), kq, ks, vq, vs), s

    t_q8 = bench_loop(step_q8, (q, kq, ks, vq, vs), lo=lo, hi=hi)
    return {
        "metric": "flash_decode_step",
        "value": round(t * 1e6, 1),
        "unit": "us",
        "kv_gbps": round(gbps, 1),
        "hbm_pct": round(100 * gbps / spec.hbm_gbps, 1),
        "int8_kv_us": round(t_q8 * 1e6, 1),
        "config": f"B={b} Hq={hq} Hkv={hkv} D={d} S={s_len} bf16 (+int8-KV twin)",
    }


def _bench_train_step(mesh, n, on_tpu, spec, tiny=False):
    """TRAINING (ISSUE 14 acceptance): the dp2×tp2×cp2 train step on
    the int8 EF gradient ring — CP ring attention over "cp", Megatron
    MLP over "tp", the wire-quantized dp all-reduce — vs the
    single-device dense reference and the exact psum twin. One row
    reports: the ring's wire bytes vs the bf16 baseline (~2× down),
    the final-loss delta against its pinned tolerance, and the EF
    link-aggregate error strictly below the no-EF control."""
    import numpy as _np

    from jax.sharding import Mesh as _Mesh, PartitionSpec as _P

    from triton_distributed_tpu import train
    from triton_distributed_tpu.train import grad_wire, step as _stepmod

    steps = 5 if tiny else 20
    cfg = train.TrainConfig()
    trainer = train.Trainer(cfg)
    batches = [trainer.make_batch(k) for k in range(steps)]
    t0 = time.perf_counter()
    dist = [trainer.step(tok, tgt)["loss"] for tok, tgt in batches]
    dt = time.perf_counter() - t0

    params = _stepmod.init_params(cfg)
    opt = _stepmod.init_opt_state(params)
    ref = []
    for tok, tgt in batches:
        params, opt, loss = train.train_step_reference(
            params, opt, tok, tgt, cfg)
        ref.append(float(loss))
    loss_tol = 0.05
    delta = abs(dist[-1] - ref[-1])

    # EF vs the no-EF control on the metric EF bounds: the
    # link-aggregate (stripe-summed) reduce-scatter error (see
    # train/grad_wire.py — per-element error is the SR noise floor
    # either way)
    nring, srows, cols = 4, 8, 128
    ring_mesh = _Mesh(_np.asarray(jax.devices()[:nring]), ("x",))

    def agg_err(ef):
        errs = []
        for seed in (0, 1, 2):
            rng = _np.random.RandomState(seed)
            x = rng.standard_normal(
                (nring * nring * srows, cols)).astype(_np.float32)
            exact = x.reshape(nring, nring * srows, cols).sum(axis=0)
            fn = jax.shard_map(
                lambda v: grad_wire.ef_ring_reduce_scatter(
                    v, "x", n=nring, wire="int8", seed=seed + 7, ef=ef),
                mesh=ring_mesh, in_specs=_P("x", None),
                out_specs=_P("x", None), check_vma=False,
            )
            err = _np.asarray(jax.jit(fn)(x)) - exact
            errs.append(
                float(_np.abs(
                    err.reshape(nring, srows, cols).sum(axis=0)).mean()))
        return float(_np.mean(errs))

    ef_err, ctl_err = agg_err(True), agg_err(False)
    wires = trainer.wire_report()
    ok = (delta < loss_tol and ef_err < ctl_err
          and wires["ratio"] > 1.9)
    return {
        "metric": "train_step",
        "value": round(dt / steps * 1e3, 2),
        "unit": "ms/step",
        "config": (f"dp{cfg.dp}×tp{cfg.tp}×cp{cfg.cp} "
                   f"attn={cfg.attn} wire={trainer.wire} "
                   f"microbatches={cfg.microbatches}"),
        "steps": steps,
        "final_loss": round(dist[-1], 6),
        "final_loss_ref": round(ref[-1], 6),
        "final_loss_delta": round(delta, 6),
        "loss_tol": loss_tol,
        "grad_ring_bytes": wires["wire_bytes"],
        "grad_ring_bf16_bytes": wires["bf16_bytes"],
        "grad_ring_byte_ratio": round(wires["ratio"], 3),
        "ef_agg_err": round(ef_err, 6),
        "no_ef_agg_err": round(ctl_err, 6),
        "ef_below_control": ef_err < ctl_err,
        "ok": ok,
    }


if __name__ == "__main__":
    try:
        main()
    except Exception as e:
        # the error rides a JSON line AND the exit code: a failed run
        # must not read as a zero-valued measurement
        import traceback

        traceback.print_exc()
        print(json.dumps({"error": f"{type(e).__name__}: {e}"[:300]}))
        sys.exit(1)
