"""Readers of the program's own account of its START-UP: the ``setup.*``
spans it opens round the phases of set-up and its log of every program
built under a span (``triton_distributed_tpu/tracing.py``; PR 39).

Set-up lies before the window, and the record a reader is given holds
the window's deltas only; so these reach the program's log through its
one function, ``tracing.startup_log()``. A program without it (the
parent of PR 39) is an ``ImportError`` here, and every reader returns
``None``: the metric is left out of the line, not reported as 0. No cut
by time is needed: a ``setup.*`` span closes during set-up, and a run
that builds a program inside its window has failed.

The log is a dict: ``spans`` (closed ``setup.*`` spans: ``name``,
``seconds``, ``gc_s``, and a ``setup.program``'s ``step``, ``block_q``,
``width``) and ``programs`` (one record a program JAX built while a span
was open: ``fun_name``, the innermost open ``span``, ``trace_s``,
``lower_s``, ``compile_s`` — the backend's compile LESS the persistent
cache's ``retrieval_s`` —, ``cache`` ``hit`` | ``miss`` | ``off``,
``inside``: the program in whose trace or lowering it was built, else
None). Sums of record seconds take the records with ``inside`` None:
intervals are booked by containment, never added to what holds them.
"""

from __future__ import annotations

PROGRAM = "setup.program"


def _log():
    """The program's log, or None: no such function in this program, or
    no engine was ever built under it."""
    try:
        from triton_distributed_tpu.tracing import startup_log
    except ImportError:
        return None
    log = startup_log()
    if not any(s["name"] == "setup.engine" for s in log["spans"]):
        return None
    return log


def _spans(log, span: str) -> list:
    return [s for s in log["spans"] if s["name"] == span]


def span_seconds(rec, span: str):
    """Seconds inside the spans named ``span``, summed."""
    log = _log()
    return None if log is None else sum(
        s["seconds"] for s in _spans(log, span))


def span_seconds_max(rec, span: str):
    """The longest single span named ``span`` (0 with none)."""
    log = _log()
    return None if log is None else max(
        (s["seconds"] for s in _spans(log, span)), default=0.0)


def span_count(rec, span: str):
    log = _log()
    return None if log is None else len(_spans(log, span))


def gc_seconds(rec):
    """Collector pauses inside the ``setup.*`` spans: each booked on the
    innermost span open round it, so the sum counts a pause once."""
    log = _log()
    return None if log is None else sum(s["gc_s"] for s in log["spans"])


def program_seconds(rec, fields: list, span: str = PROGRAM):
    """Σ of ``fields`` over the top-level records built under ``span``
    (``["compile_s", "retrieval_s"]``: the backend's whole compile, a
    load from the cache included)."""
    log = _log()
    return None if log is None else sum(
        p[f] for p in log["programs"]
        if p["span"] == span and p["inside"] is None for f in fields)


def rebuilt_programs(rec):
    """Programs built by a dispatch whose key the engine had dispatched
    before: records under ``engine.dispatch`` and under no
    ``setup.program``. A sound engine reads 0."""
    log = _log()
    return None if log is None else sum(
        p["span"] == "engine.dispatch" and p["inside"] is None
        for p in log["programs"])


def cache_misses(rec):
    """Programs built under any ``setup.*`` span that the persistent
    compile cache did not hold. 0 says set-up was WARM."""
    log = _log()
    return None if log is None else sum(
        p["span"].startswith("setup.") and p["cache"] == "miss"
        for p in log["programs"])
