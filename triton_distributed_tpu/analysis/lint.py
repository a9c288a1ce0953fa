"""shmemlint public API and CLI.

API::

    from triton_distributed_tpu import analysis
    findings = analysis.lint_all(n=8)                  # whole registry
    findings = analysis.lint_family("ag_gemm.fused", n=8)

CLI (exits nonzero when any ERROR-severity finding survives)::

    python -m triton_distributed_tpu.analysis.lint [--mesh 8]
        [--kernel ag_gemm] [--json] [--list]

No devices are required: kernel builders are constructed over a
``jax.sharding.AbstractMesh`` (nothing executes — the analyzer runs the
kernel *bodies* symbolically), so the lint pass runs identically on a
dev laptop, a CI runner and a TPU host, including on a jax without the
TPU-simulation interpreter where the dynamic race/chaos suites cannot
run at all.

Suppressing an intentional violation: pass ``allow={"SL007", ...}`` to
the API (or ``--allow SL007`` on the CLI) — the finding is still
printed, demoted to INFO. See docs/ANALYSIS.md for the rule catalog.
"""

from __future__ import annotations

import itertools

from triton_distributed_tpu.analysis import abstract, checks
from triton_distributed_tpu.analysis.findings import (
    Finding,
    Severity,
    has_errors,
)

_TOKENS = itertools.count()


def lint_mesh(n: int = 8, axis: str = "x"):
    """An abstract n-device 1D mesh for kernel construction. Builders
    only read ``shape``/``axis_names`` at build time, so no physical
    devices back it."""
    import jax

    return jax.sharding.AbstractMesh((int(n),), (axis,))


def analyze_spec(spec, in_shapes, n, *, kernel_name, site=None, init=None,
                 axis="x", mesh_axes=("x",), contract=None):
    """Symbolically execute one captured/hand-built LaunchSpec and run
    the checker passes — protocol (SL001–SL007), wire-rail consistency
    (SL009/SL010), and, when a ``contract`` is given, delivery
    completeness (SL008). Returns (recorder, findings)."""
    rec = abstract.run_symbolic(
        spec, in_shapes, n, axis=axis, mesh_axes=mesh_axes, init=init,
        kernel_name=kernel_name, site=site,
    )
    return rec, checks.check_family(rec, contract=contract)


def analyze_family(fam, n: int = 8, mesh=None, *, infer_contracts=False):
    """Build one registry family over an abstract mesh, read back the
    captured LaunchSpec, and analyze it (the family's declared delivery
    contract drives the SL008 pass). Returns (recorder, findings).

    ``infer_contracts=True`` additionally derives the family's delivery
    obligation from its XLA twin (:mod:`.contract_infer`): declared
    contracts are diffed against the inferred one (SL012 on drift), and
    a family with ``contract=None`` gets the inferred contract as the
    SL008 fallback plus an SL013 surfacing the gap."""
    from triton_distributed_tpu.lang.launch import captured_launch

    mesh = mesh if mesh is not None else lint_mesh(n, fam.axis)
    fam.build(mesh, n, ("shmemlint", next(_TOKENS)))
    spec = captured_launch(fam.launch_name)
    if spec is None:
        raise RuntimeError(
            f"family {fam.name!r}: builder did not construct a "
            f"shmem_call named {fam.launch_name!r}"
        )
    rec = abstract.run_symbolic(
        spec, fam.in_shapes(n), n,
        axis=fam.axis, mesh_axes=fam.mesh_axes,
        init=fam.init(n) if fam.init else None,
        kernel_name=fam.name, site=fam.site,
    )
    fallback, inferred = None, []
    if infer_contracts and fam.degrades_to:
        from triton_distributed_tpu.analysis import contract_infer

        result = contract_infer.infer_spec(
            rec, degrades_to=fam.degrades_to, declared=fam.contract)
        inferred = result.findings
        fallback = result.contract
    findings = checks.check_family(
        rec, contract=fam.contract, fallback_contract=fallback)
    return rec, findings + inferred


def _apply_allow(findings, allow):
    allow = set(allow or ())
    for f in findings:
        if f.rule in allow:
            f.severity = Severity.INFO
    return findings


def lint_family(name: str, n: int = 8, mesh=None, allow=None,
                infer_contracts=False):
    """Lint one registry family by name; returns the findings."""
    from triton_distributed_tpu.kernels.registry import families

    fam = families()[name]
    _, findings = analyze_family(fam, n, mesh,
                                 infer_contracts=infer_contracts)
    return _apply_allow(findings, allow)


def _cross_family_checks(recorders) -> list:
    """SL005 across the registry: two DIFFERENT-site families sharing a
    collective_id share one barrier-semaphore rendezvous — interleaved
    launches would satisfy each other's barriers. Engine variants of
    one op entry (same fault-plan site) deliberately share their op's
    default id: only one of them runs per call."""
    findings = []
    by_id: dict = {}
    for rec in recorders:
        cid = rec.info.collective_id
        if cid is None or not rec.barrier_sem_used:
            continue
        by_id.setdefault(cid, {}).setdefault(
            rec.info.site, []).append(rec.info.kernel)
    for cid, sites in sorted(by_id.items(), key=lambda kv: str(kv[0])):
        if len(sites) > 1:
            kernels = sorted(k for ks in sites.values() for k in ks)
            findings.append(Finding(
                "SL005", "+".join(kernels),
                f"collective_id {cid!r} is shared by kernel families of "
                f"different sites {sorted(map(str, sites))} "
                f"({kernels}) — their barrier rendezvous collide when "
                "launched in one program",
            ))
    return findings


def lint_all(n: int = 8, mesh=None, kernels=None, allow=None,
             infer_contracts=False):
    """Lint every registered kernel family (optionally filtered by the
    ``kernels`` substring list) plus the cross-family hygiene checks.
    Returns the combined findings list."""
    from triton_distributed_tpu.kernels.registry import families

    fams = families()
    if kernels:
        fams = {
            name: f for name, f in fams.items()
            if any(k in name for k in kernels)
        }
        if not fams:
            raise ValueError(f"no registered kernel matches {kernels}")
    findings, recorders = [], []
    for name in sorted(fams):
        rec, f = analyze_family(fams[name], n, mesh,
                                infer_contracts=infer_contracts)
        recorders.append(rec)
        findings += f
    findings += _cross_family_checks(recorders)
    return _apply_allow(findings, allow)


# ---------------------------------------------------------------------- CLI

def _main_serving(args, json, sys) -> int:
    """The ``--serving`` mode: servlint's bounded model check of the
    serving/fleet protocol (SV001–SV007). Exits 2 on any error finding
    — the bench/CI abort convention — 0 when the exploration is
    clean."""
    from triton_distributed_tpu.analysis import servlint
    from triton_distributed_tpu.analysis.findings import (
        SCHEMA_VERSION,
        rule_counts,
    )

    findings, stats = servlint.lint_serving(
        fixture=args.serving_fixture, max_states=args.serving_states)
    _apply_allow(findings, args.allow)
    errs = sum(f.severity >= Severity.ERROR for f in findings)
    warns = sum(f.severity == Severity.WARNING for f in findings)
    if args.json:
        print(json.dumps({
            "schema_version": SCHEMA_VERSION, "mode": "serving",
            "fixture": args.serving_fixture,
            "states": stats["states"],
            "transitions": stats["transitions"],
            "complete": stats["complete"],
        }))
        for f in findings:
            print(json.dumps(f.to_json()))
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "rule_counts": rule_counts(findings),
            "errors": errs, "warnings": warns,
        }))
    else:
        for f in findings:
            print(f.format())
        kind = "exhaustive" if stats["complete"] else "state-capped"
        print(
            f"servlint: {stats['states']} states, "
            f"{stats['transitions']} transitions ({kind}): "
            f"{errs} error(s), {warns} warning(s)",
            file=sys.stderr)
    return 2 if has_errors(findings) else 0


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m triton_distributed_tpu.analysis.lint",
        description="shmemlint: static semaphore-protocol and deadlock "
        "analysis over the registered SHMEM kernel families",
    )
    ap.add_argument("--mesh", type=int, default=8, metavar="N",
                    help="abstract mesh size to analyze on (default 8)")
    ap.add_argument("--kernel", action="append", default=None,
                    metavar="SUBSTR",
                    help="only families whose name contains SUBSTR "
                    "(repeatable); e.g. --kernel ag_gemm")
    ap.add_argument("--allow", action="append", default=None,
                    metavar="RULE",
                    help="demote RULE (e.g. SL007) to info severity")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object per line on stdout: a "
                    "schema_version header, each finding, and a "
                    "rule_counts summary")
    ap.add_argument("--infer-contracts", action="store_true",
                    help="derive each family's delivery contract from "
                    "its XLA twin and diff it against the declared one "
                    "(SL012 on drift, SL013 on a missing declaration; "
                    "SL008 runs on the inferred contract when none is "
                    "declared)")
    ap.add_argument("--mosaic", action="store_true",
                    help="also run the Mosaic-compat pre-flight (rules "
                    "MC001-MC010: trace each family's kernel jaxpr and "
                    "scan for constructs this toolchain's Mosaic "
                    "rejects)")
    ap.add_argument("--serving", action="store_true",
                    help="model-check the serving/fleet protocol "
                    "instead of the kernel families (rules SV001-SV007: "
                    "bounded exhaustive interleaving over a 2-replica "
                    "abstract fleet driven by the production ProtocolOps "
                    "seam); exits 2 on any error finding")
    ap.add_argument("--serving-fixture", default=None, metavar="RULE",
                    help="run the --serving exploration against the "
                    "seeded mutated-ops fixture for RULE (e.g. SV003) "
                    "instead of the production ops")
    ap.add_argument("--serving-states", type=int, default=6000,
                    metavar="N",
                    help="distinct-state cap for the --serving "
                    "exploration (default 6000; 0 = uncapped, the "
                    "nightly exhaustive run — the human label and "
                    "--json 'complete' field then report whether the "
                    "full reachable graph was walked)")
    ap.add_argument("--list", action="store_true",
                    help="list registered kernel families and exit")
    args = ap.parse_args(argv)

    if args.mesh < 2:
        ap.error("--mesh must be >= 2 (a 1-rank mesh has no protocol)")

    if args.serving or args.serving_fixture:
        return _main_serving(args, json, sys)

    from triton_distributed_tpu.kernels.registry import families

    if args.list:
        for name, fam in sorted(families().items()):
            print(f"{name:24s} site={fam.site} launch={fam.launch_name}")
        return 0

    findings = lint_all(n=args.mesh, kernels=args.kernel, allow=args.allow,
                        infer_contracts=args.infer_contracts)
    if args.mosaic:
        from triton_distributed_tpu.analysis import mosaic_compat

        mc, report = mosaic_compat.preflight_all(
            n=args.mesh, kernels=args.kernel
        )
        findings += _apply_allow(mc, args.allow)
        if not args.json:
            print(
                "mosaic-compat: "
                f"{len(report['scanned'])} scanned, "
                f"{len(report['refused'])} refused cleanly "
                f"({sorted(report['refused'])})",
                file=sys.stderr,
            )
    checked = sorted(
        name for name in families()
        if not args.kernel or any(k in name for k in args.kernel)
    )
    errs = sum(f.severity >= Severity.ERROR for f in findings)
    warns = sum(f.severity == Severity.WARNING for f in findings)
    if args.json:
        from triton_distributed_tpu.analysis.findings import (
            SCHEMA_VERSION,
            rule_counts,
        )

        print(json.dumps({
            "schema_version": SCHEMA_VERSION, "mesh": args.mesh,
            "families": checked,
        }))
        for f in findings:
            print(json.dumps(f.to_json()))
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "rule_counts": rule_counts(findings),
            "errors": errs, "warnings": warns,
        }))
    else:
        for f in sorted(findings, key=lambda f: -f.severity):
            print(f.format())
        print(
            f"shmemlint: {len(checked)} kernel families on a "
            f"{args.mesh}-rank mesh: {errs} error(s), {warns} warning(s)",
            file=sys.stderr,
        )
    return 1 if has_errors(findings) else 0


if __name__ == "__main__":
    raise SystemExit(main())
