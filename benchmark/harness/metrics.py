"""From a served window to numbers: the end-to-end metrics, the
failures, and the generic readers that per-layer metric files name.

A per-layer metric is ``layer_metrics/<name>.json``:
``{"reader": <kind>, "args": {...}}``. ``reader`` is one of the kinds
below or, for a number none of them gives, a function in a file a later
PR adds (``readers/`` is the place), named ``"package.module:function"``
and called as ``function(rec, **args) -> float | None``. The kinds:

``percentile_of``          ``series``, ``q``, ``scale`` (default 1):
                           the q-th percentile of a named series of the
                           window, times ``scale``.
``span_minus_counter``     ``span``, ``counter``: per engine step, the
                           harness's span minus the program's own
                           timing of the same step; the mean.
``counter_ratio``          ``num``, ``den``, ``scale``: a ratio of two
                           counts of the window.
``trace_events_ms_per_step`` ``match`` or ``scope``: device time of
                           the trace's events whose instruction name
                           contains ``match``, or whose scope path (the
                           operation's ``op_name``) has ``scope`` as
                           one ``/``-separated component, per engine
                           step of the traced part.
``host_span_ms_per_step``  ``span``, ``self_time`` (default false):
                           host time inside the trace's host events of
                           that name — with ``self_time``, less what
                           the known spans nested in them cover — per
                           engine step of the traced part.
``idle_ms_per_step``       ``span``, ``q`` (optional): device-idle time
                           of the trace that ``TraceSummary.idle_by_path``
                           gives to that host span or to a span nested
                           in it; with ``q`` the q-th percentile over
                           the span's instances (one stalled step does
                           not move a median), else the sum per
                           engine step of the traced part.
``trace_roofline_share``   ``match``, ``needs``, ``peak_bytes``,
                           ``peak_flops``: per traced step the least
                           time the chip could take for what the step
                           NEEDS of the kernel — ``needs`` names a
                           function ``"package.module:function"``,
                           ``(config, rows) -> (bytes, operations)``,
                           both lower bounds; the larger of bytes over
                           the peak byte rate and operations over the
                           peak operation rate — summed, over the
                           matched kernel time, in %.

A reader that finds nothing to read returns ``None`` and the metric is
left out of the result line.
"""

from __future__ import annotations

import math

from benchmark.harness.spec import named


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in 0..100."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def failures(window, vocab: int) -> list:
    """Requests that count as failed, with the reason."""
    out = []
    for a in window.arrivals:
        r = a.request
        if r is None or not r.done:
            out.append((a.rid, "not finished when the drain closed"))
        elif len(r.generated) != a.max_new:
            out.append((a.rid, f"{len(r.generated)} of {a.max_new} "
                               "tokens"))
        elif not all(0 <= t < vocab for t in r.generated):
            out.append((a.rid, "a token outside the vocabulary"))
    return out


def series(window) -> dict:
    """Named per-request and per-step series of one window, in ms.
    Per-step series are aligned with each other; ``traced_steps``
    holds the indices of the steps inside the traced part and
    ``traced_rows`` their batched rows. ``stats.<field>`` are the
    engine's own per-step lists as the program wrote them (no unit
    implied: ``percentile_of`` takes a ``scale``)."""
    arr = window.arrivals
    first = [a.token_times[0] - a.due for a in arr if a.token_times]
    gaps = [b - a for r in arr
            for a, b in zip(r.token_times, r.token_times[1:])]
    s = {
        "ttft_ms": [1e3 * x for x in first],
        "itl_ms": [1e3 * x for x in gaps],
        "gen_late_ms": [1e3 * (a.submitted - a.due) for a in arr
                        if a.submitted is not None],
        "queue_wait_ms": [1e3 * (a.admitted - a.due) for a in arr
                          if a.admitted is not None],
        "step_wall_ms": [1e3 * (st[1] - st[0]) for st in window.steps],
        "step_device_ms": [1e3 * st[2] for st in window.steps],
        **window.stats_series,
    }
    if window.traced:
        lo, hi = window.traced
        inside = [i for i, st in enumerate(window.steps)
                  if st[0] >= lo and st[1] <= hi]
        s["traced_steps"] = inside
        s["traced_rows"] = [window.steps[i][3] for i in inside]
    return s


def end_to_end(window, setup_s: float) -> dict:
    """Every end-to-end metric the harness can compute, by name."""
    s = series(window)
    done = [a for a in window.arrivals
            if a.request is not None and a.request.done]
    out = {"setup_s": setup_s}
    if s["ttft_ms"]:
        out["ttft_p95_ms"] = percentile(s["ttft_ms"], 95)
        out["ttft_p50_ms"] = percentile(s["ttft_ms"], 50)
    if s["itl_ms"]:
        out["itl_p50_ms"] = percentile(s["itl_ms"], 50)
        out["itl_p95_ms"] = percentile(s["itl_ms"], 95)
    if done:
        # over all the work and all the time of the WINDOW: tokens
        # emitted before it closed. (Tokens of completed requests over
        # first-due-to-last-completion read 5 % apart from run to run:
        # the denominator's end is wherever the seed put the last long
        # answer; kept as drain_tok_s on the record line.)
        out["out_tok_s"] = sum(
            1 for a in window.arrivals for t in a.token_times
            if t <= window.seconds) / window.seconds
        first_due = min(a.due for a in window.arrivals)
        last = max(a.token_times[-1] for a in done)
        out["drain_tok_s"] = (
            sum(len(a.request.generated) for a in done)
            / (last - first_due))
    return out


# ---------------------------------------------------------------- readers
# record = {"series": {...}, "counters": {...}, "trace": TraceSummary |
#           None, "peaks": {...}, "chips": n, "config": {...}}

def _percentile_of(rec, series, q, scale=1.0):
    xs = rec["series"].get(series)
    return scale * percentile(xs, q) if xs else None


def _span_minus_counter(rec, span, counter):
    a, b = rec["series"].get(span), rec["series"].get(counter)
    if not a or not b or len(a) != len(b):
        return None
    return sum(x - y for x, y in zip(a, b)) / len(a)


def _counter_ratio(rec, num, den, scale=1.0):
    c = rec["counters"]
    if not c.get(den):
        return None
    return scale * c.get(num, 0) / c[den]


def _per_traced_step(rec, seconds):
    """``seconds(trace)`` in ms per engine step of the traced part."""
    tr, steps = rec.get("trace"), rec["series"].get("traced_steps")
    if tr is None or not steps:
        return None
    secs = seconds(tr)
    return None if secs is None else 1e3 * secs / len(steps)


def _trace_events_ms_per_step(rec, match=None, scope=None):
    return _per_traced_step(
        rec, lambda tr: tr.matched_seconds(match, scope=scope))


def _host_span_ms_per_step(rec, span, self_time=False):
    return _per_traced_step(
        rec, lambda tr: tr.span_seconds(span, self_time))


def _idle_ms_per_step(rec, span, q=None):
    if q is None:
        return _per_traced_step(rec, lambda tr: tr.idle_seconds(span))
    tr = rec.get("trace")
    per = tr.idle_per_instance(span) if tr is not None else None
    return 1e3 * percentile(per, q) if per else None


def _trace_roofline_share(rec, match, needs, peak_bytes, peak_flops):
    tr, steps = rec.get("trace"), rec["series"].get("traced_rows")
    if tr is None or not steps:
        return None
    secs = tr.matched_seconds(match)
    if not secs:
        return None
    step_needs = named(needs)
    # per chip: a step's bytes and operations are spread over the
    # cell's chips, and matched_seconds is already the mean over chips
    bw, peak = float(rec["peaks"][peak_bytes]), float(
        rec["peaks"][peak_flops])
    least = sum(max(b / bw, f / peak) for b, f in (
        step_needs(rec["config"], rows) for rows in steps))
    return 100.0 * least / rec["chips"] / secs


READERS = {
    "percentile_of": _percentile_of,
    "span_minus_counter": _span_minus_counter,
    "counter_ratio": _counter_ratio,
    "trace_events_ms_per_step": _trace_events_ms_per_step,
    "trace_roofline_share": _trace_roofline_share,
    "host_span_ms_per_step": _host_span_ms_per_step,
    "idle_ms_per_step": _idle_ms_per_step,
}


def layer_record(window, trace, peaks, cell) -> dict:
    """What every reader is given of one traced run."""
    return {"series": series(window), "counters": window.counters,
            "trace": trace, "peaks": peaks, "chips": cell.chips,
            "config": cell.config}


def per_layer(cell, rec: dict) -> dict:
    """The cell's per-layer metrics that found something to read, as
    the result line has them."""
    out = {}
    for m in cell.per_layer:
        v = read_layer_metric(rec, cell.layer_metrics[m["name"]])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def read_layer_metric(rec: dict, definition: dict):
    """One metric from the run's record, or None."""
    kind = definition["reader"]
    if kind not in READERS and ":" not in kind:
        raise KeyError(f"unknown reader kind {kind!r} (has: "
                       f"{', '.join(READERS)}, or "
                       "'package.module:function')")
    reader = READERS.get(kind) or named(kind)
    return reader(rec, **definition.get("args", {}))
