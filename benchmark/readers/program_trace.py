"""Readers of what the PROGRAM writes into a profiler trace: the host
spans it opens inside ``ServingEngine.step`` (``engine.<phase>``) and
the scopes of its jitted step (``jax.named_scope``: one ``/``-separated
component of a device operation's scope path).

A metric file names one as ``"benchmark.readers.program_trace:<function>"``;
each is given the run's record and returns ``None`` where there is
nothing to read (no trace, no traced step, a program that opens no such
span), so the same files run against a program from before the spans.

All three work on intervals, not on sums of durations: a gap is split
among the spans that were open during it, and device time is counted
once where a container (a ``while``) and its body overlap.
"""

from __future__ import annotations

from benchmark.harness.trace import _union


def _overlap(xs: list, ys: list) -> int:
    """Total length of the intersection of two ``_union`` results."""
    tot = i = j = 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _traced_steps(rec) -> int:
    if rec.get("trace") is None:
        return 0
    return len(rec["series"].get("traced_steps") or ())


def idle_overlap_ms_per_step(rec, span: str):
    """Device-idle ms during which host span ``span`` was open, per
    engine step of the traced part: over the gaps between device
    operations on the first chip, the part of each gap that an instance
    of ``span`` covers, summed. Sibling spans are disjoint, so what the
    phases of a step read adds up to the gaps they cover between them
    (``idle_ms_per_step`` gives each gap WHOLE to the one child that
    holds its majority, or to the parent)."""
    steps = _traced_steps(rec)
    trace = rec.get("trace")
    if not steps or not trace.device_ops:
        return None
    opened = _union((a, b) for name, _, a, b in trace.host_events
                    if name == span)
    if not opened:
        return None
    first_chip = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = _union((s, s + d) for _, s, d, _ in first_chip)
    gaps = [[a[1], b[0]] for a, b in zip(busy, busy[1:])]
    return _overlap(gaps, opened) / 1e6 / steps


def _named(op, scopes, result_types) -> bool:
    """Is this device event under one of ``scopes`` — or, having no
    scope path at all, of one of ``result_types`` (below)?"""
    label, _, _, path = op
    if path:
        return not scopes.isdisjoint(path.split("/"))
    return label.partition(" ")[2] in result_types


def unscoped_share(rec, scopes: list, result_types: list = ()):
    """% of device busy time (mean over the chips) during which no
    operation ran that is under one of ``scopes``: busy time less the
    union of those operations' intervals, over busy time. A path-less
    container whose body is scoped (XLA's ``while``) is covered by its
    body; events ``pathless_ms_per_step`` would count for
    ``result_types`` count as named."""
    trace = rec.get("trace")
    if trace is None or not trace.device_ops:
        return None
    scopes, result_types = set(scopes), set(result_types)
    busy = named = 0
    for ops in trace.device_ops.values():
        all_ = _union((s, s + d) for _, s, d, _ in ops)
        busy += sum(b - a for a, b in all_)
        named += _overlap(all_, _union(
            (op[1], op[1] + op[2]) for op in ops
            if _named(op, scopes, result_types)))
    return 100.0 * (busy - named) / busy if busy else None


def pathless_ms_per_step(rec, result_types: list):
    """Device ms, per engine step of the traced part (mean over the
    chips), of the events that carry NO scope path and whose result
    type is one of ``result_types`` (``"s8[1310720,128]"``, as a device
    operation's label has it after the instruction name).

    For an operation the compiler rebuilds without its metadata, known
    only by what it writes: the page-pool append of a HEAD-SHARDED pool
    (tp > 1) stays a three-index scatter, which XLA flattens into a new
    scatter with no ``op_name`` (PERF.md §6, PR 26); the metric file
    then names the pool's per-chip type, and ``unscoped_share`` takes
    the same list. The one-chip cells do not need it: there the program
    flattens the append itself and ``kv_append`` stays on it."""
    steps = _traced_steps(rec)
    trace = rec.get("trace")
    if not steps or not trace.device_ops:
        return None
    types = set(result_types)
    hit = [d for ops in trace.device_ops.values()
           for label, _, d, path in ops
           if not path and label.partition(" ")[2] in types]
    if not hit:
        return None
    return sum(hit) / len(trace.device_ops) / 1e6 / steps
