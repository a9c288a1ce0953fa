"""The wall-clock open-loop driver around ``submit`` / ``step``.

Load is offered at the times the generator fixed, in seconds, whatever
the engine is doing: every request that is due is handed over before
each step, and a request's clock starts when it was DUE, not when the
loop got round to it. One thread does both, because the engine's
interface is one synchronous ``step()``: a request that falls due
inside a step waits for the step's end, and that wait is reported
(``gen_late_ms``) and counted in its time to first token.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

from benchmark.harness import program


@dataclasses.dataclass
class Window:
    """What one served window left behind, times in seconds from its
    start."""

    arrivals: list
    seconds: float
    steps: list = dataclasses.field(default_factory=list)
    # one entry per engine step that ran the device:
    #   (start, end, device_s, rows) with rows = [(take, cursor), ...]:
    #   each batched row advanced ``take`` positions to ``cursor``
    closed_at: float = 0.0
    counters: dict = dataclasses.field(default_factory=dict)
    # the engine's own per-step lists over the window, as
    # ``stats.<field>``: one entry per entry of ``steps``
    stats_series: dict = dataclasses.field(default_factory=dict)
    traced: tuple | None = None     # (start, end) of the traced part
    stalled: float = 0.0            # seconds the profiler's start/stop took


def no_span(name):
    return contextlib.nullcontext()


def serve(engine, arrivals: list, seconds: float, drain_s: float, *,
          span=no_span, tracer=None) -> Window:
    """Offer ``arrivals`` at their due times and step the engine until
    all are done or ``drain_s`` past the window. ``span(name)`` is a
    context manager put around the loop's phases; ``tracer`` is
    ``(start_at_s, stop_at_s, start_fn, stop_fn)`` for a traced run.
    The spans are the loop's own phases and the engine's public
    ``step()`` (each a file in ``host_spans/``); spans inside the
    program are the program's to add, with a file each."""
    clock = time.perf_counter
    stats = engine.stats
    todo = deque(sorted(arrivals, key=lambda a: a.due))
    inflight: dict = {}
    cursors: dict = {}
    win = Window(arrivals=arrivals, seconds=seconds)
    base = {k: getattr(stats, k) for k in
            ("prefill_tokens", "generated_tokens", "completed",
             "evictions", "deferrals")}
    tokens0 = len(stats.step_tokens)
    before = program.stats_snapshot(engine)
    lowered0 = program.programs_lowered()
    tracing = False
    t0 = clock()
    while True:
        now = clock() - t0
        if tracer is not None:
            # starting and stopping the profiler blocks this thread for
            # seconds; that time is kept out of the drain's allowance
            if not tracing and win.traced is None and now >= tracer[0]:
                tracer[2]()
                tracing = True
                trace_from = clock() - t0
                win.stalled += trace_from - now
            elif tracing and now >= tracer[1]:
                tracer[3]()
                tracing = False
                win.traced = (trace_from, now)
                win.stalled += clock() - t0 - now
        with span("loadgen"):
            while todo and todo[0].due <= now:
                a = todo.popleft()
                a.request = program.new_request(
                    a.rid, a.prompt, a.max_new, engine.step_count)
                engine.submit(a.request)
                a.submitted = clock() - t0
                inflight[a.rid] = a
        if not inflight:
            if not todo:
                break
            with span("idle_wait"):
                time.sleep(max(0.0, todo[0].due - (clock() - t0)))
            continue
        if now - win.stalled > seconds + drain_s:
            break
        n_dev = len(stats.step_times)
        ts = clock() - t0
        with span("engine_step"):
            engine.step()
        te = clock() - t0
        with span("stamp"):
            rows = []
            for a in list(inflight.values()):
                r = a.request
                if a.admitted is None and (
                        r.slot is not None or r.generated):
                    a.admitted = ts
                a.token_times.extend(
                    [te] * (len(r.generated) - len(a.token_times)))
                take = r.cursor - cursors.get(a.rid, 0)
                if take > 0:                # the row was batched
                    cursors[a.rid] = r.cursor
                    rows.append((take, r.cursor))
                if r.done:
                    del inflight[a.rid]
            if len(stats.step_times) > n_dev:
                win.steps.append((ts, te, stats.step_times[-1], rows))
    if tracing:
        tracer[3]()
        win.traced = (trace_from, clock() - t0)
    win.closed_at = clock() - t0
    win.counters = {
        k: getattr(stats, k) - v for k, v in base.items()}
    win.counters["step_tokens"] = int(sum(stats.step_tokens[tokens0:]))
    win.counters["device_steps"] = len(win.steps)
    win.counters["programs_lowered"] = (
        program.programs_lowered() - lowered0)
    # whatever else the engine counts or times, by its field's name:
    # numbers as deltas, lists that grew by one entry per device step
    # as per-step series
    after = program.stats_snapshot(engine)
    for k, v in after["numbers"].items():
        win.counters[f"stats.{k}"] = v - before["numbers"].get(k, 0)
    for k, (entries, n) in after["lists"].items():
        n0 = before["lists"].get(k, (None, 0))[1]
        grown = entries[n0:n]
        if win.steps and len(grown) == len(win.steps) and all(
                isinstance(x, (int, float)) for x in grown):
            win.stats_series[f"stats.{k}"] = [float(x) for x in grown]
    return win
