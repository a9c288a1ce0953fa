"""Mosaic-compat pre-flight: seconds-fast compile-shaped coverage.

The only static check that the SHMEM kernels actually *lower* on this
toolchain used to be ``tests/test_aot_topology.py`` — a full XLA+Mosaic
compile against an unattached v5e topology whose module fixture alone
cost ~8 minutes of the tier-1 budget (it is ``slow``-marked since
round 6, leaving tier-1 with zero Mosaic-lowering coverage). This
module restores a cheap approximation: every registry family is built
exactly as it would be FOR HARDWARE (``config.force_compile`` — the
strict divisor/blocking paths, the in-kernel wire contracts), its
``pallas_call`` is traced to a kernel jaxpr on CPU (tracing runs no
platform code — an abstract mesh suffices), and the jaxpr is scanned
for the constructs this toolchain's Mosaic backend is KNOWN to reject:

* **MC001** — f8 casts inside the kernel (``arith.extf f8E4M3FN →
  f32``: "Only 16-bit to 32-bit extensions supported"; the finding the
  AOT suite catches at minute 8, here at second 2);
* **MC002** — collapsing a loaded ``(1, 1)`` float vector to a scalar
  (the ``vector.shape_cast 1x1 → scalar`` Mosaic rejects — the reason
  lang.wire keeps lane-replicated ``(1, 128)`` scale rows);
* **MC003** — broadcasting a sub-byte (4-bit) vector;
* **MC004** — a dot over 1-byte operands with an unsupported
  accumulator form. The int8→MXU consumers (ag_gemm/moe_tp
  ``wire_dtype='int8-mxu'``) ride the NATIVE s8×s8→s32 path — proven
  on this toolchain by the W8A8 grouped GEMM running on chip
  (kernels/group_gemm, round 5) and re-verified by this pre-flight's
  force-compile scan of those families; what Mosaic rejects is asking
  the MXU for a FLOAT accumulate of int8 operands, or any fp8 dot
  (no f8 MXU form here, see MC001). A family whose builder refuses
  cleanly under ``lang.wire.require_mxu`` (TDTPU_WIRE_INT8_MXU=0) is a
  pass — the contract fires before Mosaic ever would, mirroring the
  MC001 fp8 handling;
* **MC006** — a gather with traced (runtime) indices: no dynamic
  vector-indexed gather lowering here — the reason the ragged
  kernel's tree-topology mask is a STATIC per-position
  ancestor-bitmask unroll rather than an ``anc[par]`` index chase.

* **MC008 / MC009 / MC010** — the three refusals the ragged paged
  kernel hit on its first real Mosaic compile (jax 0.9.0 / libtpu
  0.0.34, AOT against v5e): a DMA window sliced at a TRACED
  second-minor offset with no ``pl.multiple_of`` proof ("Failed to
  prove that a tile index in dimension 1 is divisible by the tiling");
  a sliced DMA endpoint with a trailing dim of 1 ("Slice shape along
  dimension 2 must be aligned to tiling (128), but is 1" — thin minor
  dims > 1 are not flagged: the lint geometries are deliberately
  tiny); and a ``select`` whose OPERANDS are i1 vectors ("failed
  to legalize operation 'arith.select'").

A family whose builder REFUSES cleanly under the hardware contract
(``require_inkernel`` raising for a pinned fp8 wire) is a pass: the
contract fires before Mosaic ever would, which is the designed
behavior. What this does NOT prove: full backend legality (layouts,
alignment, semaphore rules) — that remains the nightly/slow AOT
suite's job. The scan is a deny-list of known-rejected constructs, not
an emulation of the Mosaic verifier.

CLI::

    python -m triton_distributed_tpu.analysis.mosaic_compat
        [--mesh 8] [--kernel SUBSTR] [--json]
"""

from __future__ import annotations

import contextlib
import itertools

from triton_distributed_tpu.analysis.findings import Finding

_TOKENS = itertools.count()

#: substrings of the canonical clean-refusal diagnostics
#: (lang.wire.require_inkernel / require_mxu) — a build that raises one
#: never reaches Mosaic, so there is nothing to scan and nothing to
#: flag.
_CLEAN_REFUSALS = ("in-kernel f8", "in-kernel s8")


@contextlib.contextmanager
def _force_compile():
    """Build for HARDWARE (strict Mosaic paths) from this CPU process.
    Builders key their caches on explicit tokens here, so flipping the
    knob cannot leak stale builds into other callers."""
    from triton_distributed_tpu.config import config

    old = config.force_compile
    config.force_compile = True
    try:
        yield
    finally:
        config.force_compile = old


def _is_f8(dtype) -> bool:
    return "float8" in str(dtype)


def _is_subbyte(dtype) -> bool:
    s = str(dtype)
    return ("int4" in s) or ("float4" in s) or ("int2" in s)


def _walk_jaxprs(jaxpr):
    """Yield every eqn of a jaxpr and (recursively) of the sub-jaxprs
    carried in eqn params (scan/while bodies, pipeline loops, and the
    ``cond`` branch tuples every ``pl.when`` stages)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _walk_jaxprs(inner)


def _kernel_jaxprs(jaxpr):
    """The pallas_call kernel jaxprs reachable from an outer jaxpr —
    the scan looks ONLY inside them (host-side XLA ops may legally use
    every construct Mosaic lacks, e.g. the XLA-side fp8 quantize)."""
    out = []
    for eqn in _walk_jaxprs(jaxpr):
        if eqn.primitive.name == "pallas_call":
            kj = eqn.params.get("jaxpr")
            if kj is not None:
                out.append(kj)
    return out


def _dma_windows(eqn):
    """``(ref shape, NDIndexer)`` for each indexed src/dst endpoint of
    one ``dma_start`` eqn (whole-ref endpoints carry no indexer)."""
    import jax

    parts = jax.tree.unflatten(eqn.params["tree"], eqn.invars)
    for ref, transforms in ((parts[0], parts[1]), (parts[2], parts[3])):
        for tr in transforms or ():
            if hasattr(tr, "indices") and len(tr.shape) >= 2:
                yield tuple(tr.shape), tr


def scan_kernel_jaxpr(kjaxpr, kernel_name, site=None) -> list:
    """MC001–MC010 over one kernel jaxpr."""
    findings = []
    seen = set()

    def add(rule, msg):
        if (rule, msg) not in seen:
            seen.add((rule, msg))
            findings.append(Finding(rule, kernel_name, msg, site=site))

    eqns = list(_walk_jaxprs(kjaxpr))
    producer = {id(v): e for e in eqns for v in e.outvars}

    def divisor(v, depth=0):
        """Largest factor PROVABLY dividing a traced offset, the way
        Mosaic's own index analysis sees it: constants, products with a
        constant, sums of such, and ``pl.multiple_of`` hints (MC008).
        Any other op — notably a scalar-prefetch load — proves
        nothing (1); a value entering from an enclosing jaxpr (loop
        carry, cond operand) is out of this scan's sight and assumed
        aligned, so the rule only fires on offsets it fully sees."""
        import math

        if isinstance(v, int) or hasattr(v, "val"):
            c = abs(int(getattr(v, "val", v)))
            return c if c else 1 << 30
        e = producer.get(id(v))
        if e is None:
            return 1 << 30
        if depth > 16:
            return 1
        op = e.primitive.name
        if op == "multiple_of":
            return int(e.params["values"][0])
        if op == "mul":
            return (divisor(e.invars[0], depth + 1)
                    * divisor(e.invars[1], depth + 1))
        if op in ("add", "sub"):
            return math.gcd(divisor(e.invars[0], depth + 1),
                            divisor(e.invars[1], depth + 1))
        if op == "convert_element_type":
            return divisor(e.invars[0], depth + 1)
        return 1

    for eqn in eqns:
        name = eqn.primitive.name
        if name == "convert_element_type" and eqn.invars and eqn.outvars:
            src = getattr(eqn.invars[0].aval, "dtype", None)
            dst = getattr(eqn.outvars[0].aval, "dtype", None)
            if src is not None and (_is_f8(src) or _is_f8(dst)):
                add("MC001",
                    f"in-kernel cast {src} -> {dst}: this Mosaic rejects "
                    "f8 extensions ('Only 16-bit to 32-bit extensions "
                    "supported') — carry int8 in-kernel or keep fp8 on "
                    "the XLA engines (lang.wire.inkernel_wire_ok)")
        elif name in ("reshape", "squeeze") and eqn.invars and eqn.outvars:
            ia = eqn.invars[0].aval
            oa = eqn.outvars[0].aval
            ishape = getattr(ia, "shape", None)
            oshape = getattr(oa, "shape", None)
            if (
                ishape and len(ishape) >= 2 and all(d == 1 for d in ishape)
                and oshape == ()
                and "float" in str(getattr(ia, "dtype", ""))
            ):
                add("MC002",
                    f"{tuple(ishape)} float vector collapsed to a scalar "
                    "in-kernel: Mosaic rejects the vector<1x1> -> scalar "
                    "shape_cast — keep a (1, lanes) row and broadcast "
                    "(the lang.wire scale-plane idiom)")
            elif (
                ishape is not None and oshape is not None
                and len(ishape) >= 2 and len(oshape) >= 2
                and ishape[-1] != oshape[-1]
                and ishape[-1] > 1 and oshape[-1] > 1
            ):
                # MC005: a reshape that CHANGES the lane (minor)
                # dimension between two >1-lane vectors — this
                # Mosaic's vector shape_cast cannot re-lay lanes (the
                # construct a naive (T, G·D) → (T·G, D) GQA-row
                # flatten produces; the ragged kernel's head-major
                # packing exists to avoid it). Unit-collapse reshapes
                # (lane dim kept) are the supported form and pass.
                add("MC005",
                    f"in-kernel reshape {tuple(ishape)} -> "
                    f"{tuple(oshape)} changes the lane (minor) "
                    "dimension: this Mosaic's vector shape_cast cannot "
                    "re-lay lanes — restructure the buffer so the lane "
                    "dim survives (e.g. the head-major (Hkv, T*G, D) "
                    "GQA-rows packing of kernels/"
                    "ragged_paged_attention) or reshape on the XLA "
                    "side")
        elif name == "broadcast_in_dim" and eqn.outvars:
            dt = getattr(eqn.outvars[0].aval, "dtype", None)
            if dt is not None and _is_subbyte(dt):
                add("MC003",
                    f"in-kernel broadcast of sub-byte dtype {dt}: this "
                    "Mosaic backend has no sub-byte broadcast layout — "
                    "widen to int8 first")
        elif name == "dot_general" and len(eqn.invars) >= 2 and eqn.outvars:
            dts = [getattr(v.aval, "dtype", None) for v in eqn.invars[:2]]
            out_dt = getattr(eqn.outvars[0].aval, "dtype", None)
            onebyte = [
                d for d in dts
                if d is not None and getattr(d, "itemsize", 0) == 1
            ]
            if len(onebyte) == 2:
                if any(_is_f8(d) for d in onebyte):
                    add("MC004",
                        f"in-kernel dot over fp8 operands ({dts[0]} x "
                        f"{dts[1]}): this Mosaic has no f8 MXU form — "
                        "carry int8 (the s8*s8->s32 path) or keep fp8 "
                        "on the XLA engines")
                elif "int32" not in str(out_dt):
                    add("MC004",
                        f"in-kernel s8 dot accumulating to {out_dt}: "
                        "Mosaic lowers int8 dots only on the native "
                        "s8*s8->s32 path — set preferred_element_type="
                        "int32 and fold the scales on the accumulator "
                        "in the epilogue (the lang.wire int8-mxu "
                        "contract)")
        elif name == "gather" and len(eqn.invars) >= 2:
            # MC006: a gather whose index operand is a TRACED value
            # (a Var, not a Literal constant) — dynamic vector-indexed
            # gathers have no lowering on this Mosaic backend. The
            # construct a naive topology-mask build produces
            # (anc[par[q]] with runtime par): the ragged kernel's
            # static per-position ancestor-bitmask unroll exists to
            # avoid it. Constant-index gathers fold at trace time and
            # pass.
            idx = eqn.invars[1]
            if not hasattr(idx, "val"):        # jax.core.Literal has .val
                ishape = getattr(idx.aval, "shape", ())
                add("MC006",
                    f"in-kernel gather with traced indices (index "
                    f"shape {tuple(ishape)}): this Mosaic has no "
                    "dynamic vector-indexed gather lowering — unroll "
                    "over the index set with static masks (the ragged "
                    "kernel's ancestor-bitmask unroll) or gather on "
                    "the XLA side")
        elif name == "dma_start":
            for shape, ix in _dma_windows(eqn):
                sub = ix.indices[-2]
                start = getattr(sub, "start", None)
                # whole-tile windows only: the sub-tile (1–2 row)
                # metadata windows of moe_dispatch compile as they are
                if (start is not None and sub.size != shape[-2]
                        and sub.size % 8 == 0 and divisor(start) % 8):
                    add("MC008",
                        f"DMA window of {shape} sliced at a traced "
                        f"second-minor offset (size {sub.size}) with no "
                        "divisibility proof: Mosaic must PROVE a tile "
                        "index divisible by the sublane tiling — wrap "
                        "the offset in pl.multiple_of(offset, 8) (and "
                        "keep the packing contract that makes it true)")
                sliced = any(
                    not hasattr(i, "size") or i.size != d
                    for i, d in zip(ix.indices, shape)
                )
                if sliced and shape[-1] == 1:
                    add("MC009",
                        f"sliced DMA endpoint over {shape}: a trailing "
                        "dim of 1 pads to a 128-lane tile and Mosaic "
                        "refuses the memref slice as not lane-tile "
                        "aligned — make the minor dim lane-dense "
                        "(broadcast the (rows, 1) column to (rows, "
                        "128)) or DMA the whole ref")
        elif name == "select_n" and len(eqn.invars) >= 2:
            case = eqn.invars[1].aval
            if (str(getattr(case, "dtype", "")) == "bool"
                    and len(getattr(case, "shape", ())) >= 1):
                add("MC010",
                    f"select over i1 vector operands {tuple(case.shape)}"
                    ": Mosaic cannot legalize arith.select on mask "
                    "vectors — select the int32/float values the masks "
                    "are compared against, or combine the masks with "
                    "logical and/or")
        elif name == "dynamic_slice" and len(eqn.invars) >= 2:
            # MC007: a dynamic_slice whose start index on the SUBLANE
            # (second-minor) dimension is a TRACED value while the
            # slice is proper on that dimension — this Mosaic can only
            # fold dynamic sublane offsets that are compile-time
            # constants (traced LANE offsets and full-size sublane
            # "slices" at a traced zero both lower fine). Promoted
            # from the nightly slow run's jaxpr signature so the
            # 8-minute finding is a 2-second one.
            op = eqn.invars[0]
            oshape = getattr(op.aval, "shape", ())
            sizes = tuple(eqn.params.get("slice_sizes", ()))
            if (len(oshape) >= 2
                    and len(eqn.invars) == 1 + len(oshape)
                    and len(sizes) == len(oshape)
                    and sizes[-2] != oshape[-2]):
                sub = eqn.invars[1 + len(oshape) - 2]
                if not hasattr(sub, "val"):   # Literal has .val
                    add("MC007",
                        f"in-kernel dynamic_slice of {tuple(oshape)} "
                        f"with a traced start index on the sublane "
                        f"(second-minor) dimension (slice_sizes="
                        f"{sizes}): this Mosaic only folds constant "
                        "sublane offsets — unroll over the candidate "
                        "offsets with static masks or hoist the slice "
                        "to the XLA side")
    return findings


def i8_to_float_casts(kjaxpr) -> list:
    """Every in-kernel ``convert_element_type`` that widens an int8
    array to a float type — the signature of a per-arrival DEQUANT
    pass. The int8→MXU acceptance check (tests/test_wire.py) asserts
    this list is EMPTY for the ``*_int8mxw`` families' traced kernels:
    their wire ends at the s8×s8 dot, whose only float conversion is
    the s32 accumulator's epilogue widening."""
    out = []
    for eqn in _walk_jaxprs(kjaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        if not (eqn.invars and eqn.outvars):
            continue
        src = getattr(eqn.invars[0].aval, "dtype", None)
        dst = getattr(eqn.outvars[0].aval, "dtype", None)
        if (src is not None and dst is not None
                and "int8" in str(src) and "float" in str(dst)):
            out.append((str(src), str(dst),
                        tuple(getattr(eqn.invars[0].aval, "shape", ()))))
    return out


# ------------------------------------------------------------------ tracing

def trace_spec(spec, in_shapes, n, *, mesh=None, axis="x"):
    """Trace one LaunchSpec's pallas_call to a jaxpr on an abstract
    n-rank mesh. Nothing executes and no TPU platform code runs —
    tracing only stages the kernel body out, which is exactly the input
    of the Python-side Mosaic lowering."""
    import jax
    from jax.experimental import pallas as pl
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.analysis.lint import lint_mesh

    mesh = mesh if mesh is not None else lint_mesh(n, axis)
    kw = {}
    scratch = list(spec.scratch_shapes)
    if getattr(spec, "grid_spec", None) is not None:
        # scalar-prefetch families (PrefetchScalarGridSpec): re-invoke
        # with the captured spec object — it already carries the
        # scratch (the capture mirrors it into spec.scratch_shapes for
        # the abstract evaluator), and in_shapes lists the scalar-
        # prefetch operands FIRST, exactly the call convention
        kw["grid_spec"] = spec.grid_spec
        scratch = []
    else:
        if spec.grid is not None:
            kw["grid"] = spec.grid
        if spec.in_specs is not None:
            kw["in_specs"] = spec.in_specs
        if spec.out_specs is not None:
            kw["out_specs"] = spec.out_specs
    call = pl.pallas_call(
        spec.kernel,
        out_shape=spec.out_shape,
        scratch_shapes=scratch,
        interpret=False,
        **kw,
    )
    nout = len(jax.tree.leaves(jax.eval_shape(lambda: spec.out_shape)))
    avals = [jax.ShapeDtypeStruct(s, d) for s, d in in_shapes]
    wrapped = jax.shard_map(
        lambda *a: jax.tree.leaves(call(*a)),
        mesh=mesh,
        in_specs=tuple(P() for _ in avals),
        out_specs=[P()] * nout,
        check_vma=False,
    )
    return jax.make_jaxpr(wrapped)(*avals)


def preflight_spec(spec, in_shapes, n, *, kernel_name, site=None,
                   axis="x") -> list:
    """Trace one spec under the hardware config and scan it."""
    with _force_compile():
        jaxpr = trace_spec(spec, in_shapes, n, axis=axis)
    findings = []
    for kj in _kernel_jaxprs(jaxpr.jaxpr):
        findings += scan_kernel_jaxpr(kj, kernel_name, site=site)
    return findings


def trace_family_kernels(fam, n: int = 8) -> list:
    """Build one registry family FOR HARDWARE and return its traced
    kernel jaxprs — the raw material of the deny-list scan, and of
    ad-hoc jaxpr assertions in tests (e.g. the int8→MXU acceptance
    check that no per-arrival dequant pass exists in the traced
    kernel). Raises the builder's clean-refusal ValueError through."""
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.analysis.lint import lint_mesh

    with _force_compile():
        mesh = lint_mesh(n, fam.axis)
        fam.build(mesh, n, ("mosaic_compat", next(_TOKENS)))
        spec = captured_launch(fam.launch_name)
        if spec is None:
            raise RuntimeError(
                f"family {fam.name!r}: builder did not construct a "
                f"shmem_call named {fam.launch_name!r}"
            )
        jaxpr = trace_spec(spec, fam.in_shapes(n), n, mesh=mesh,
                           axis=fam.axis)
    return _kernel_jaxprs(jaxpr.jaxpr)


def preflight_family(fam, n: int = 8):
    """Build one registry family FOR HARDWARE and scan its kernel.
    Returns (status, findings): status 'scanned', or 'refused' when the
    builder raised a canonical pinned-wire contract error (a pass —
    the contract fires before Mosaic ever would)."""
    try:
        kernel_jaxprs = trace_family_kernels(fam, n)
    except ValueError as e:
        if any(s in str(e) for s in _CLEAN_REFUSALS):
            return "refused", []
        raise
    findings = []
    for kj in kernel_jaxprs:
        findings += scan_kernel_jaxpr(kj, fam.name, site=fam.site)
    return "scanned", findings


def preflight_all(n: int = 8, kernels=None):
    """Pre-flight every registry family (optionally filtered by name
    substrings). Returns (findings, report) where report maps
    'scanned'/'refused' to the family-name lists."""
    from triton_distributed_tpu.kernels.registry import families

    fams = families()
    if kernels:
        fams = {
            name: f for name, f in fams.items()
            if any(k in name for k in kernels)
        }
        if not fams:
            raise ValueError(f"no registered kernel matches {kernels}")
    findings = []
    report = {"scanned": [], "refused": []}
    for name in sorted(fams):
        status, f = preflight_family(fams[name], n)
        report[status].append(name)
        findings += f
    return findings, report


# ---------------------------------------------------------------------- CLI

def main(argv=None) -> int:
    import argparse
    import json
    import sys

    from triton_distributed_tpu.analysis.findings import (
        SCHEMA_VERSION,
        Severity,
        rule_counts,
    )

    ap = argparse.ArgumentParser(
        prog="python -m triton_distributed_tpu.analysis.mosaic_compat",
        description="Mosaic-compat pre-flight: trace each registered "
        "kernel family's jaxpr (built for hardware) and scan for "
        "constructs this toolchain's Mosaic backend rejects "
        "(MC001-MC010)",
    )
    ap.add_argument("--mesh", type=int, default=8, metavar="N")
    ap.add_argument("--kernel", action="append", default=None,
                    metavar="SUBSTR")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if args.mesh < 2:
        ap.error("--mesh must be >= 2")

    findings, report = preflight_all(n=args.mesh, kernels=args.kernel)
    errs = sum(f.severity >= Severity.ERROR for f in findings)
    if args.json:
        print(json.dumps({
            "schema_version": SCHEMA_VERSION, "mesh": args.mesh,
            "scanned": report["scanned"], "refused": report["refused"],
        }))
        for f in findings:
            print(json.dumps(f.to_json()))
        print(json.dumps({
            "schema_version": SCHEMA_VERSION,
            "rule_counts": rule_counts(findings), "errors": errs,
        }))
    else:
        for f in findings:
            print(f.format())
        print(
            f"mosaic-compat: {len(report['scanned'])} kernel families "
            f"scanned, {len(report['refused'])} refused cleanly under "
            f"the hardware wire contract: {errs} error(s)",
            file=sys.stderr,
        )
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
