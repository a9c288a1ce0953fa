"""Kernel-alone timers, the lint gate and the chaos replay.

NOT the benchmark. Serving speed — and training speed, when it gets a
cell — is ``benchmark/run.py``'s: ``BENCHMARK.json`` declares its cells
and metrics, the driver runs it on the chip, and every number in
``PERF.md`` and ``PERF_LEDGER.jsonl`` comes from it. This file keeps
what nothing there duplicates:

* **The in-jit runners** (``bench_loop``, ``bench_paired``,
  ``_make_donating_runner``) and the timers of the overlap kernels
  ALONE: fused AG-GEMM on the Llama-7B TP shape (in ``main``), GEMM-RS,
  the quantized wire rings, the schedule search, the grouped GEMM, the
  MoE all-to-all transport and flash-decode. No ledger line rests on
  them yet (no cell runs more than one chip); they wait for the first
  4-chip cell.
* ``--lint``: shmemlint, the Mosaic pre-flight, the degradation-target
  gates and servlint before any timing (exit 2 on errors;
  ``docs/ANALYSIS.md``, ``docs/LINT.md``).
* ``--dryrun [--faults SPEC]``: the hardware-free replay of a nightly
  chaos line through ``ServingEngine`` at interpreter-tiny shapes
  (``docs/ROBUSTNESS.md`` §1). It prints COUNTS — requests completed,
  evictions, degradations, watchdog trips — and no rate.

Methodology of the timers (the round-1 numbers were dispatch-overhead
artifacts):

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
The other timers (gemm_rs, wire rings, schedule search, grouped-GEMM MFU,
MoE a2a transport, flash-decode HBM%) go to stderr, one JSON line each.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import time

# CPU dev-box runs (JAX_PLATFORMS=cpu) get the same virtual 8-device
# mesh the test harness uses (tests/conftest.py): the multi-rank rows —
# the DCN rails, the ring engines — then exercise their real
# cross-device paths instead of degenerating to n=1. Real-TPU runs are
# untouched.
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
        description="triton_distributed_tpu kernel-alone timers, lint gate "
        "and chaos replay (serving speed: benchmark/run.py)"
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
        help="hardware-free chaos replay: serve a seeded trace through "
        "ServingEngine at interpreter-tiny shapes (whatever the "
        "platform), print its counts and exit — with --faults, the "
        "fault plan is active inside the ragged kernel and the "
        "scheduler's eviction/degradation behavior runs under it, a "
        "watchdog armed round the run (TDTPU_BENCH_WATCHDOG seconds, "
        "default 10)",
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
        help="--dryrun: override the model depth (default 1). The "
        "serving-state donation path is only exercised at depth > 1; "
        "per-layer pool bytes are reported",
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

    if args.dryrun:
        devs = jax.devices()
        out = _bench_serving_continuous(
            Mesh(np.asarray(devs), ("x",)), len(devs),
            n_layers=args.n_layers,
        )
        out["faults"] = args.faults
        print(json.dumps(out), flush=True)
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
               _bench_group_gemm, _bench_moe_a2a, _bench_flash_decode):
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


def _serving_continuous_config(n, n_layers=None):
    """(model config, engine config, trace knobs) of the ``--dryrun``
    replay: interpreter-sized shapes under the ISSUE-6 shape of traffic
    (lengths ~U[S/8, 3S/4] against S=64). ``n_layers`` overrides the
    model depth (``--n-layers``: depth > 1 exercises the per-layer
    serving-state donation path the default depth 1 never touches)."""
    from dataclasses import replace

    from triton_distributed_tpu.models import TransformerConfig
    from triton_distributed_tpu.serving import EngineConfig

    # KV heads shard over tp in the serving state — keep divisible
    n_kv = n if n > 4 else 4
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
        # keep the MoE layer set valid at the new depth (drop layers
        # past it; added depth is dense — the donation path under test
        # is per-layer KV state, not expert count)
        moe_layers = tuple(l for l in cfg.moe_layers if l < n_layers)
        cfg = replace(cfg, n_layers=int(n_layers), moe_layers=moe_layers)
    return cfg, ecfg, trace_kw


def _bench_serving_continuous(mesh, n, n_layers=None):
    """The ``--dryrun`` replay: a seeded Poisson arrival trace drives
    the ServingEngine at interpreter-tiny shapes — admission/eviction
    over the page pool, chunked prefill interleaved into decode
    batches, one ragged mixed kernel launch per step — under whatever
    fault plan ``--faults`` activated. Reports what the replay is for:
    requests completed, evictions, deferrals, degradations and the
    failures behind them, watchdog trips. No rate: serving speed is
    ``benchmark/run.py``'s."""
    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.runtime import faults, watchdog
    from triton_distributed_tpu.serving import ServingEngine, poisson_trace

    cfg, ecfg, trace_kw = _serving_continuous_config(n, n_layers=n_layers)
    model = Transformer(cfg, mesh, tp_axis="x")
    params = jax.tree.map(
        jax.device_put, model.init(jax.random.PRNGKey(7)), model.shardings()
    )
    params = model.quantize_moe_weights(params)
    params = model.quantize_dense_weights(params)

    # under --faults, arm the collective watchdog around the run (the
    # serving_step host heartbeat is live) so a stalled step TRIPS — the
    # trip feeds the health ledger and releases the stall gates —
    # instead of wedging the replay. Trips are reported, not fatal: the
    # run's recovery is the thing under test. Generous default: the run
    # pays its jit compiles, only a real stall should out-wait it
    guard = contextlib.nullcontext()
    if faults.active_plan() is not None:
        guard = watchdog.collective_watchdog(
            deadline=float(os.environ.get("TDTPU_BENCH_WATCHDOG", "10.0"))
        )
    wd_trips = []
    eng = ServingEngine(model, params, ecfg)
    try:
        with guard:
            eng.run(poisson_trace(seed=11, **trace_kw))
    except watchdog.WatchdogTimeout as e:
        wd_trips.append(str(e).splitlines()[0])
    finally:
        watchdog.clear_trip()
    stats = eng.stats
    if stats.completed != trace_kw["n_requests"] and not wd_trips:
        raise RuntimeError(
            f"dryrun lost requests: {stats.completed} of "
            f"{trace_kw['n_requests']} completed, {stats.deferrals} deferrals"
        )
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
    leaves = jax.tree.leaves(eng.state.layers)
    ptrs = {
        x.addressable_shards[0].data.unsafe_buffer_pointer() for x in leaves
    }
    return {
        "metric": "serving_continuous",
        "requests": trace_kw["n_requests"],
        "completed": stats.completed,
        "steps": len(stats.step_times),
        "evictions": stats.evictions,
        "deferrals": stats.deferrals,
        "degraded_to_xla": stats.degraded,
        "repromotions": stats.repromotions,
        "failures": stats.failures,
        "watchdog_trips": wd_trips,
        "n_layers": cfg.n_layers,
        "per_layer_pool_bytes": per_layer_pool_bytes,
        "pool_bytes_total": per_layer_pool_bytes * cfg.n_layers,
        "donation_distinct_buffers": len(ptrs) == len(leaves),
        "config": (
            f"n={n} slots={ecfg.slots} budget={ecfg.token_budget} "
            f"chunk={ecfg.chunk} page={ecfg.page} npages={ecfg.npages} "
            f"lens~U[{trace_kw['len_lo']},{trace_kw['len_hi']}] "
            f"poisson(seed=11) hidden={cfg.hidden} tiny-dryrun"
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
