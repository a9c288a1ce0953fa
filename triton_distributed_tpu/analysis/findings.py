"""Finding model and the SL rule catalog.

Rule IDs are STABLE — tests, suppression annotations and docs refer to
them by name (docs/ANALYSIS.md is the human-facing catalog). Adding a
rule appends; renumbering is a breaking change.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

#: version of the machine-readable finding schema (``--json`` output and
#: :meth:`Finding.to_json`). Bump when a field is added/renamed so
#: downstream consumers (CI dashboards, bench parsers) can dispatch.
SCHEMA_VERSION = 3


class Severity(enum.IntEnum):
    INFO = 0
    WARNING = 1
    ERROR = 2


#: rule id -> (slug, default severity, one-line description)
RULES = {
    "SL001": (
        "credit-imbalance",
        Severity.ERROR,
        "semaphore credits left unconsumed at kernel exit (signals/DMA "
        "arrivals exceed waits) — the next launch reusing the semaphore "
        "inherits stale credits and releases a wait early",
    ),
    "SL002": (
        "unsatisfiable-wait",
        Severity.ERROR,
        "a semaphore wait whose required credits never arrive on any "
        "rank — at runtime this is a silent hang the watchdog must catch",
    ),
    "SL003": (
        "deadlock-cycle",
        Severity.ERROR,
        "cross-rank wait-for cycle: every rank in the chain is parked in "
        "a wait whose credit is behind another parked rank's wait",
    ),
    "SL004": (
        "unsynchronized-buffer-write",
        Severity.ERROR,
        "a remote DMA lands in a symmetric-buffer region that a local "
        "access also touches, with no wait/fence ordering the two "
        "(write-after-read / write-after-write over RDMA)",
    ),
    "SL005": (
        "barrier-hygiene",
        Severity.ERROR,
        "collective_id misuse: duplicate id across kernel families, "
        "barrier-semaphore use without a collective_id, or ranks "
        "disagreeing on the barrier sequence",
    ),
    "SL006": (
        "vmem-overcommit",
        Severity.ERROR,
        "the kernel's VMEM-resident working set (inputs + outputs + "
        "scratch) exceeds the per-core VMEM budget",
    ),
    "SL007": (
        "undrained-dma",
        Severity.WARNING,
        "a started DMA whose send (local completion) semaphore is never "
        "waited — the kernel can exit with the transfer in flight "
        "(missing quiet()/wait_send())",
    ),
    "SL008": (
        "delivery-incompleteness",
        Severity.ERROR,
        "the kernel terminates without satisfying its declared delivery "
        "contract: a gather/permute destination missing a source chunk "
        "or holding one twice, a reduction folding a rank's contribution "
        "zero or multiple times, or raw quantized wire bytes left in the "
        "output — caught even when every semaphore balances",
    ),
    "SL009": (
        "wire-rail-divergence",
        Severity.ERROR,
        "the quantized payload rail and its scale-plane rail diverge: a "
        "payload RDMA with no paired scale RDMA, the two rails guarded "
        "by the same semaphore credits (a scale arrival can release the "
        "payload wait), a scale plane whose layout drifts from the "
        "lang.wire contract, or a scale plane consumed before its "
        "arrival is ordered",
    ),
    "SL010": (
        "stale-scale-read",
        Severity.ERROR,
        "a dequantize consumes a scale plane from a different "
        "quantization than its payload slab (e.g. hop h's bytes "
        "dequantized with hop h-1's scales in a double-buffered "
        "workspace) — silently wrong values, no protocol violation",
    ),
    "SL011": (
        "hop-critical-path",
        Severity.ERROR,
        "the deepest delivery chain into the contract destination rides "
        "more remote hops than the ring-optimal n-1 — the schedule "
        "serializes or detours transfers; the replay's per-element hop "
        "counters are fed to tune.perf_model.hop_critical_path_ms to "
        "project the wall-clock regression before any hardware run",
    ),
    "SL012": (
        "contract-drift",
        Severity.ERROR,
        "the hand-declared DeliveryContract disagrees with the one "
        "inferred from the family's XLA twin + replay provenance: wrong "
        "kind class (gather/permute vs reduce vs local), a dst root "
        "that never exhibits the twin's delivery pattern, "
        "over/under-declared payload_per_src, missing or stray source "
        "ranks, or full/own-absent drift — the declaration would make "
        "SL008 check the wrong obligation",
    ),
    "SL013": (
        "undeclared-contract",
        Severity.WARNING,
        "a registered family carries no declared DeliveryContract; "
        "contract inference derived one from the XLA twin so the SL008 "
        "completeness pass still runs, but the gap should be closed by "
        "declaring the contract in kernels/registry.py",
    ),
    "MC001": (
        "mosaic-f8-cast",
        Severity.ERROR,
        "the kernel body casts to/from an 8-bit float inside the Pallas "
        "kernel; this toolchain's Mosaic backend rejects f8 extensions "
        "('Only 16-bit to 32-bit extensions supported') — carry int8 "
        "in-kernel or dequantize on the XLA side",
    ),
    "MC002": (
        "mosaic-scalar-shape-cast",
        Severity.ERROR,
        "the kernel body collapses a loaded (1, 1) float vector to a "
        "scalar (jnp.reshape(x, ()) / x[0, 0] on a loaded block); "
        "Mosaic rejects the vector<1x1> -> scalar shape_cast — keep a "
        "(1, lanes) row and broadcast instead (the scale-plane idiom)",
    ),
    "MC003": (
        "mosaic-subbyte-broadcast",
        Severity.ERROR,
        "the kernel body broadcasts a sub-byte (4-bit) vector; this "
        "Mosaic backend has no layout for sub-byte broadcasts — widen "
        "to int8 before broadcasting",
    ),
    "MC004": (
        "mosaic-s8-dot-accumulator",
        Severity.ERROR,
        "an in-kernel dot over 1-byte operands with an unsupported "
        "accumulator form: int8 dots must run the native s8*s8->s32 "
        "path (preferred_element_type=int32, scales folded on the "
        "accumulator afterwards), and fp8 operands have no MXU form on "
        "this toolchain at all — quantize the scale fold into the "
        "epilogue, don't ask the MXU for a float accumulate of int8",
    ),
    "MC005": (
        "mosaic-lane-reshape",
        Severity.ERROR,
        "an in-kernel reshape changes the lane (minor) dimension "
        "between two >1-lane vectors; this Mosaic's vector shape_cast "
        "cannot re-lay lanes — restructure the buffer so the lane dim "
        "survives (the ragged kernel's head-major GQA-rows packing) "
        "or reshape on the XLA side",
    ),
    "MC006": (
        "mosaic-dynamic-gather",
        Severity.ERROR,
        "an in-kernel gather with TRACED (runtime) indices; this "
        "Mosaic backend has no dynamic vector-indexed gather lowering "
        "— unroll over the index set with static masks (the ragged "
        "kernel's per-position ancestor-bitmask unroll) or gather on "
        "the XLA side",
    ),
    "MC007": (
        "mosaic-sublane-dynamic-slice",
        Severity.ERROR,
        "an in-kernel dynamic_slice with a TRACED start index on the "
        "sublane (second-minor) dimension of a >=2-D vector; this "
        "Mosaic backend can only fold dynamic sublane offsets that are "
        "compile-time constants — slice the sublane dim with a static "
        "offset (unroll over the candidate offsets with masks) or hoist "
        "the slice to the XLA side",
    ),
    "MC008": (
        "mosaic-unproven-tile-slice",
        Severity.ERROR,
        "a DMA window is sliced at a TRACED offset on the second-minor "
        "(sublane) dimension with no pl.multiple_of proof; Mosaic "
        "refuses any tiled-dim slice whose index it cannot prove "
        "divisible by the tiling ('Failed to prove that a tile index "
        "in dimension 1 is divisible by the tiling (8)') — state the "
        "alignment the packing contract guarantees with "
        "pl.multiple_of(offset, 8)",
    ),
    "MC009": (
        "mosaic-thin-lane-dma",
        Severity.ERROR,
        "a sliced DMA endpoint with a trailing dim of 1 ('Slice shape "
        "along dimension 2 must be aligned to tiling (128), but is "
        "1'); a (rows, 1) column cannot be DMA'd through a sliced ref "
        "— stage it lane-dense (broadcast to 128 lanes) or move the "
        "whole ref",
    ),
    "MC010": (
        "mosaic-i1-select",
        Severity.ERROR,
        "a select whose OPERANDS are i1 (mask) vectors; Mosaic fails "
        "to legalize arith.select on vector<i1> — select between the "
        "integer/float quantities the masks derive from, or fold the "
        "choice into logical and/or of the masks",
    ),
    "SV001": (
        "serving-page-leak",
        Severity.ERROR,
        "a reachable serving state holds a page that no slot table, "
        "ship reservation, or prefix-cache entry references and that is "
        "not on the pool free list — the pool permanently shrinks and "
        "admission eventually wedges",
    ),
    "SV002": (
        "serving-double-free",
        Severity.ERROR,
        "a protocol transition releases a page more times than it was "
        "retained (negative refcount) or allocates a page whose "
        "refcount is still live — two rows now share KV that one of "
        "them will overwrite",
    ),
    "SV003": (
        "serving-freed-while-shipped",
        Severity.ERROR,
        "a page pinned by an in-flight KV ship or live migration was "
        "freed (eviction/preemption of a parked row, or source release "
        "before the transport resolved) — the transfer lands into (or "
        "reads from) reallocated pages",
    ),
    "SV004": (
        "serving-request-conservation",
        Severity.ERROR,
        "a request was lost or duplicated across "
        "failover/drain/preemption: the multiset of live requests "
        "(queued + resident + parked + shipped + completed) no longer "
        "matches the admitted set",
    ),
    "SV005": (
        "serving-cursor-regression",
        Severity.ERROR,
        "a resident request's cursor moved backwards past a committed "
        "prefix without the recompute-eviction discipline (cursor reset "
        "to 0 off-slot) — the stream-exactness precondition breaks and "
        "re-emitted tokens diverge",
    ),
    "SV006": (
        "serving-nontransactional-ship",
        Severity.ERROR,
        "a KV ship/migration violated the transactional discipline: "
        "the destination commit became observable before the source "
        "released its pinned pages, or a transport-exhausted ship "
        "leaked its destination reservation instead of rolling back",
    ),
    "SV007": (
        "serving-unroutable-livelock",
        Severity.ERROR,
        "a reachable state with a nonempty backlog from which no "
        "sequence of transitions ever admits a request (no replica can "
        "free the pages/slots it would need) — the fleet livelocks "
        "with work queued",
    ),
}


@dataclass
class Finding:
    """One lint finding, with enough coordinates to act on it:
    ``kernel`` (registry family), ``site`` (fault-plan site name),
    ``ranks`` involved, the semaphore ``sem`` (name + slot), and the
    barrier-``phase`` index the event sat in (number of ``barrier_all``
    calls the rank had passed)."""

    rule: str
    kernel: str
    message: str
    site: str | None = None
    ranks: tuple = ()
    sem: str | None = None
    phase: int | None = None
    severity: Severity = field(default=None)  # type: ignore[assignment]

    def __post_init__(self):
        if self.rule not in RULES:
            raise ValueError(f"unknown rule id: {self.rule}")
        if self.severity is None:
            self.severity = RULES[self.rule][1]

    @property
    def slug(self) -> str:
        return RULES[self.rule][0]

    def format(self) -> str:
        loc = self.kernel + (f" [site={self.site}]" if self.site else "")
        bits = []
        if self.ranks:
            bits.append(f"ranks={list(self.ranks)}")
        if self.sem:
            bits.append(f"sem={self.sem}")
        if self.phase is not None:
            bits.append(f"phase={self.phase}")
        tail = (" (" + ", ".join(bits) + ")") if bits else ""
        return (
            f"{self.rule} {self.severity.name.lower()} {self.slug} "
            f"@ {loc}: {self.message}{tail}"
        )

    def to_json(self) -> dict:
        return {
            "schema_version": SCHEMA_VERSION,
            "rule": self.rule,
            "slug": self.slug,
            "severity": self.severity.name.lower(),
            "kernel": self.kernel,
            "site": self.site,
            "ranks": list(self.ranks),
            "sem": self.sem,
            "phase": self.phase,
            "message": self.message,
        }


def rule_counts(findings) -> dict:
    """Per-rule finding counts (every catalog rule, zero included) —
    the ``--json`` summary object's payload."""
    counts = {rule: 0 for rule in RULES}
    for f in findings:
        counts[f.rule] += 1
    return counts


def worst(findings) -> Severity | None:
    sevs = [f.severity for f in findings]
    return max(sevs) if sevs else None


def has_errors(findings) -> bool:
    return any(f.severity >= Severity.ERROR for f in findings)
