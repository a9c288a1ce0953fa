"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. A device is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
that ran on the chip, named by the whole HLO instruction
(``%ragged_paged_attention_q8.4 = bf16[16,768,128]{...} custom-call(...)``:
a Pallas kernel shows under its kernel name). Events are kept under a
short label, ``<instruction name> <result type>``; a kernel's time is
the sum of the durations of the events whose INSTRUCTION NAME contains
its name (operands that merely mention it do not count). Busy time is
the union of the events' intervals (a ``while`` and the operations of
its body overlap, and count once).
The harness's own host spans (``jax.profiler.TraceAnnotation``) sit on
the host plane, on the same clock, and say what the host was doing in
each gap between device operations.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the host spans ``driver.serve`` opens, and the only ones read
SPANS = ("loadgen", "idle_wait", "engine_step", "stamp")


def op_label(text: str) -> str:
    """``%name = type{layout} op(operands)`` -> ``name type``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    return f"{head.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def _union(intervals):
    """Merged, sorted copy of ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _overlap(a, b, spans):
    return sum(max(0, min(b, e) - max(a, s)) for s, e in spans)


class TraceSummary:
    """Device operations per chip and the harness's host spans, in ns."""

    def __init__(self, device_ops: dict, host_spans: dict):
        self.device_ops = device_ops    # plane -> [(name, start, dur)]
        self.host_spans = host_spans    # span name -> [(start, end)]

    @classmethod
    def from_file(cls, path: str) -> "TraceSummary":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, data) -> "TraceSummary":
        device_ops, host_spans = {}, {}
        wanted = set(SPANS)
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE):
                ops = device_ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        ops.append((op_label(ev.name), int(ev.start_ns),
                                    int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in wanted:
                            s = int(ev.start_ns)
                            host_spans.setdefault(ev.name, []).append(
                                (s, s + int(ev.duration_ns)))
        return cls(device_ops, host_spans)

    # ------------------------------------------------------------ device
    def chips(self) -> int:
        return len(self.device_ops)

    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, mean over the chips."""
        if not self.device_ops:
            return 0.0
        tot = 0
        for ops in self.device_ops.values():
            tot += sum(b - a for a, b in _union(
                (s, s + d) for _, s, d in ops))
        return tot / len(self.device_ops) / 1e9

    def matched_seconds(self, match: str):
        """Device seconds of the events whose name contains ``match``,
        mean over the chips; None where no event matches."""
        tot, hit = 0, False
        for ops in self.device_ops.values():
            for name, _, d in ops:
                if match in name.split(" ", 1)[0]:
                    tot += d
                    hit = True
        return tot / len(self.device_ops) / 1e9 if hit else None

    def top_ops(self, n: int = 10) -> list:
        """``[[name, seconds], ...]``: the operations that took most
        device time (summed by name, mean over the chips)."""
        by_name: dict = {}
        for ops in self.device_ops.values():
            for name, _, d in ops:
                by_name[name] = by_name.get(name, 0) + d
        k = max(len(self.device_ops), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, d / k / 1e9] for name, d in ranked]

    # -------------------------------------------------------------- host
    def idle_by_host_span(self, n: int = 10) -> list:
        """``[[what the host was doing, idle seconds], ...]``: every gap
        between device operations on the first chip, given to the
        harness span that covers most of it."""
        if not self.device_ops:
            return []
        ops = self.device_ops[sorted(self.device_ops)[0]]
        busy = _union((s, s + d) for _, s, d in ops)
        idle: dict = {}
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b <= a:
                continue
            share = {name: _overlap(a, b, self.host_spans.get(name, ()))
                     for name in SPANS}
            who = max(share, key=share.get)
            if share[who] <= 0:
                who = "outside the harness's spans"
            idle[who] = idle.get(who, 0) + (b - a)
        ranked = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
        return [[name, d / 1e9] for name, d in ranked]


def newest_xplane(trace_dir: str) -> str:
    """The ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def describe(path: str, top: int = 25) -> dict:
    """Planes, lines and the heaviest event names of a trace: what to
    look at by hand before trusting a reduction written against it."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            by_name: dict = {}
            n = 0
            for ev in line.events:
                n += 1
                by_name[ev.name] = by_name.get(ev.name, 0) \
                    + int(ev.duration_ns)
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
            lines[line.name] = {"events": n, "top": ranked[:top]}
        out[plane.name] = lines
    return out
