"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

A device is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation that ran on the chip, named by the whole HLO
instruction (``%ragged_paged_attention_q8.4 = bf16[16,768,128]{...}
custom-call(...)``: a Pallas kernel shows under its kernel name). Events
are kept under a short label, ``<instruction name> <result type>``; a
kernel's time is the sum of the durations of the events whose INSTRUCTION
NAME contains its name (operands that merely mention it do not count).
Busy time is the union of the events' intervals (a ``while`` and the
operations of its body overlap, and count once).

Each device event also keeps its SCOPE PATH: the ``op_name`` JAX gave
the operation (``jit(step)/kv_append/scatter``: every ``jax.named_scope``
the program opens is one ``/``-separated component of it). The profiler
writes it as the ``tf_op`` stat of the event's METADATA, which
``jax.profiler.ProfileData`` does not hand out (it gives an event's own
stats only), so ``scope_paths`` reads that one table from the file's
protobuf wire format; everything else is read with ``ProfileData``.

Every event of the host plane is kept, on the device's clock: the
harness's and the program's spans (``jax.profiler.TraceAnnotation``), and
the runtime's own events (transfers, execute, the Python tracer's
calls). A host SPAN is an event whose name has a file
``host_spans/<name>.json``; spans nest where one's interval lies inside
another's on the same line (thread), and every gap between device
operations goes to the span that covers it (``idle_by_path``).
"""

from __future__ import annotations

import glob
import json
import os
import pathlib

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: the benchmark's own directory (the one that holds ``harness/``)
BENCH = pathlib.Path(__file__).resolve().parents[1]
OUTSIDE = "outside the harness's spans"


def op_label(text: str) -> str:
    """``%name = type{layout} op(operands)`` -> ``name type``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    return f"{head.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def known_spans(bench=BENCH) -> dict:
    """``{name: {"layer": ..., "what": ...}}``: one entry per file
    ``<bench>/host_spans/<name>.json``. No list of them exists in code."""
    out = {}
    for path in sorted(pathlib.Path(bench).glob("host_spans/*.json")):
        with open(path) as f:
            out[path.name[:-len(".json")]] = json.load(f)
    return out


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


# ------------------------------------------------ the protobuf, by hand
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
# stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (tsl/profiler/protobuf/xplane.proto).

def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")


def _one(buf, number, default=None):
    return next((v for k, v in _fields(buf) if k == number), default)


def scope_paths(path: str) -> dict:
    """``{device plane: {event name: scope path}}`` from the ``tf_op``
    stat of every event metadata of the device planes (``<op_name>:<op
    type>``, the type dropped). Lines, and the host plane, are skipped
    unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for k, plane in _fields(space):
        if k != 1:
            continue
        name = bytes(_one(plane, 2, b"")).decode()
        if not name.startswith(DEVICE_PLANE):
            continue
        stat_names, events = {}, []
        for k, entry in _fields(plane):
            if k == 5:
                meta = _one(entry, 2, b"")
                stat_names[_one(entry, 1, 0)] = bytes(
                    _one(meta, 2, b"")).decode()
            elif k == 4:
                events.append(_one(entry, 2, b""))
        scopes = out.setdefault(name, {})
        for meta in events:
            for k, stat in _fields(meta):
                if k != 5 or stat_names.get(_one(stat, 1)) != "tf_op":
                    continue
                ref = _one(stat, 7)
                text = (stat_names.get(ref, "") if ref is not None
                        else bytes(_one(stat, 5, b"")).decode())
                scopes[bytes(_one(meta, 2, b"")).decode()] = \
                    text.rpartition(":")[0] if ":" in text else text
    return out


class TraceSummary:
    """Device operations per chip and the host's events, in ns.

    ``device_ops``  plane -> ``[(name, start, dur[, scope path])]``
    ``host``        every host event, ``[(name, line, start, end)]``; or,
                    as a hand-made record has it, ``{span: [(start,
                    end)]}`` (one line)
    ``spans``       the names that are host spans (``known_spans``);
                    default: every name of a hand-made ``host`` dict,
                    else the files beside the harness
    """

    def __init__(self, device_ops: dict, host, spans=None):
        self.device_ops = {
            plane: [(*op, "")[:4] for op in ops]
            for plane, ops in device_ops.items()}
        if isinstance(host, dict):
            if spans is None:
                spans = list(host)
            host = [(name, "", a, b)
                    for name, ivs in host.items() for a, b in ivs]
        self.host_events = list(host)
        self.spans = set(known_spans() if spans is None else spans)
        self._paths = self._idle = None
        self._idle_of: dict = {}

    @classmethod
    def from_file(cls, path: str, spans=None) -> "TraceSummary":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path), spans,
                                scope_paths(path))

    @classmethod
    def from_profile(cls, data, spans=None, scopes=None) -> "TraceSummary":
        device_ops, host = {}, []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE):
                ops = device_ops.setdefault(plane.name, [])
                scope = (scopes or {}).get(plane.name, {})
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        ops.append((op_label(ev.name), int(ev.start_ns),
                                    int(ev.duration_ns),
                                    scope.get(ev.name, "")))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        host.append((ev.name, line.name, s,
                                     s + int(ev.duration_ns)))
        return cls(device_ops, host, spans)

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
                (s, s + d) for _, s, d, _ in ops))
        return tot / len(self.device_ops) / 1e9

    def matched_seconds(self, match: str | None = None, *,
                        scope: str | None = None):
        """Device seconds of the events whose instruction name contains
        ``match``, or whose scope path has ``scope`` as one of its
        ``/``-separated components (``attn`` is not ``attn_out``); mean
        over the chips; None where no event matches."""
        if (match is None) == (scope is None):
            raise ValueError("one of match and scope")
        tot, hit = 0, False
        for ops in self.device_ops.values():
            for name, _, d, path in ops:
                if (match in name.split(" ", 1)[0] if scope is None
                        else scope in path.split("/")):
                    tot += d
                    hit = True
        return tot / len(self.device_ops) / 1e9 if hit else None

    def top_ops(self, n: int = 10) -> list:
        """``[[name, seconds], ...]``: the operations that took most
        device time (summed by name, mean over the chips)."""
        by_name: dict = {}
        for ops in self.device_ops.values():
            for name, _, d, _ in ops:
                by_name[name] = by_name.get(name, 0) + d
        k = max(len(self.device_ops), 1)
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, d / k / 1e9] for name, d in ranked]

    # -------------------------------------------------------------- host
    def span_paths(self) -> list:
        """Every instance of a known span as ``(path, start, end,
        line)``, sorted by start; ``path`` is the tuple of span names
        from the outermost down to this one. Nesting is read from the
        intervals: a span's parent is the innermost span instance on the
        same line (thread) whose interval holds it."""
        if self._paths is None:
            by_line: dict = {}
            for name, line, a, b in self.host_events:
                if name in self.spans:
                    by_line.setdefault(line, []).append((a, b, name))
            out = []
            for line, evs in by_line.items():
                stack = []              # [(end, path)], outermost first
                for a, b, name in sorted(
                        evs, key=lambda e: (e[0], -e[1])):
                    while stack and b > stack[-1][0]:
                        stack.pop()
                    path = (stack[-1][1] if stack else ()) + (name,)
                    stack.append((b, path))
                    out.append((path, a, b, line))
            self._paths = sorted(out, key=lambda p: p[1])
        return self._paths

    def span_seconds(self, span: str, self_time: bool = False):
        """Seconds inside the host events named ``span`` (any host
        event, a known span or not); with ``self_time``, less what the
        known spans inside each on its line cover. None where the trace
        has no such event."""
        tot, hit = 0, False
        for name, line, a, b in self.host_events:
            if name != span:
                continue
            hit = True
            tot += b - a
            if self_time:
                tot -= sum(e - s for s, e in _union(
                    (s, e) for path, s, e, ln in self.span_paths()
                    if ln == line and a <= s and e <= b
                    and (path[-1], s, e) != (name, a, b)))
        return tot / 1e9 if hit else None

    def idle_by_path(self) -> dict:
        """``{span path: idle ns}``: every gap between device operations
        on the first chip goes to the outermost span that covers most
        of it, then down: to the deepest span that covers more than half
        of the gap, else to that span's parent. A path reads
        ``engine_step/engine.fetch``. (Each gap is also booked on the
        one instance of every span of its path that covers most of it:
        ``idle_per_instance``.)"""
        if self._idle is not None or not self.device_ops:
            return self._idle or {}
        ops = self.device_ops[sorted(self.device_ops)[0]]
        busy = _union((s, s + d) for _, s, d, _ in ops)
        spans = self.span_paths()
        idle = self._idle = {}
        self._idle_of = {}              # (path, start, end) -> idle ns
        nxt, active = 0, []
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b <= a:
                continue
            while nxt < len(spans) and spans[nxt][1] < b:
                active.append(spans[nxt])
                nxt += 1
            active = [p for p in active if p[2] > a]
            share: dict = {}
            for path, s, e, _ in active:
                cover = min(b, e) - max(a, s)
                if cover > 0:
                    share[path] = share.get(path, 0) + cover
            who = ()
            roots = {p: c for p, c in share.items() if len(p) == 1}
            if roots:
                who = max(roots, key=roots.get)
                while True:
                    below = {p: c for p, c in share.items()
                             if len(p) == len(who) + 1
                             and p[:-1] == who}
                    best = max(below, key=below.get, default=None)
                    if best is None or 2 * below[best] <= b - a:
                        break
                    who = best
            key = "/".join(who) if who else OUTSIDE
            idle[key] = idle.get(key, 0) + (b - a)
            for depth in range(1, len(who) + 1):
                inst = max((p for p in active if p[0] == who[:depth]),
                           key=lambda p: min(b, p[2]) - max(a, p[1]))
                self._idle_of[inst[:3]] = \
                    self._idle_of.get(inst[:3], 0) + (b - a)
        return idle

    def idle_by_host_span(self, n: int = 10) -> list:
        """``[[what the host was doing, idle seconds], ...]``, the
        ``n`` largest of ``idle_by_path``."""
        ranked = sorted(self.idle_by_path().items(),
                        key=lambda kv: -kv[1])[:n]
        return [[name, d / 1e9] for name, d in ranked]

    def idle_seconds(self, span: str):
        """Device-idle seconds given to ``span`` or to a span nested in
        it; None where the trace has no such span."""
        if not any(span in p[0] for p in self.span_paths()):
            return None
        return sum(d for path, d in self.idle_by_path().items()
                   if span in path.split("/")) / 1e9

    def idle_per_instance(self, span: str) -> list:
        """Device-idle seconds inside each instance of ``span`` (its
        nested spans included), in order of start; one stalled step
        is one entry here, and moves ``idle_seconds`` whole."""
        self.idle_by_path()
        return [self._idle_of.get(p[:3], 0) / 1e9
                for p in self.span_paths() if p[0][-1] == span]


def newest_xplane(trace_dir: str) -> str:
    """The ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def describe(path: str, top: int = 25) -> dict:
    """Planes, lines, the heaviest event names and the names of the
    events' own stats: what to look at by hand before trusting a
    reduction written against it."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            by_name: dict = {}
            stats: set = set()
            n = 0
            for ev in line.events:
                n += 1
                by_name[ev.name] = by_name.get(ev.name, 0) \
                    + int(ev.duration_ns)
                stats.update(k for k, _ in ev.stats)
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
            lines[line.name] = {"events": n, "top": ranked[:top],
                                "event_stats": sorted(stats)}
        out[plane.name] = lines
    out["scope_paths"] = {
        plane: sorted(set(scopes.values()))[:4 * top]
        for plane, scopes in scope_paths(path).items()}
    return out
