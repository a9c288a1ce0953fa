"""The program's one span, and the log of what start-up built.

:class:`Span` is what the model and the engine open round their work: a
``jax.profiler.TraceAnnotation`` (so it lies on the device trace's
clock whenever a profiler runs, and costs ~0.6 us when none does) and a
``perf_counter`` pair booked where an untraced run can read it. The six
``engine.<phase>`` spans of ``ServingEngine.step`` add their seconds to
the step's own dict; a ``setup.<what>`` span is appended to the
process-wide log below when it closes.

Beside the spans, a BUILD LOG: one record for every program JAX lowers
while a span is open on the lowering thread (traced, lowered, compiled
or loaded from the persistent cache, with its seconds), kept by ONE
process-wide pair of ``jax.monitoring`` listeners that
:func:`install` registers at the first ``Transformer`` construction —
never at import. The listeners fire only when JAX builds a program; a
steady serving step builds none. :func:`startup_log` returns spans and
records as a dict, :func:`ready_line` one line an operator can print at
readiness. No switch, no environment variable: always on.
"""

from __future__ import annotations

import functools
import gc
import threading
import time

import jax

#: spans whose name starts so are set-up: logged when they close, and
#: the collector's pauses inside them are summed on them as ``gc_s``
SETUP = "setup."

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
#: the persistent cache's events carry no name: each belongs to the
#: program whose lowering precedes it on that thread. A request that
#: used the cache is a miss until the cache says it hit
_CACHE = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
          "/jax/compilation_cache/cache_misses": "miss",
          "/jax/compilation_cache/cache_hits": "hit"}


class _Thread(threading.local):
    """What is open on one thread."""

    def __init__(self):
        self.stack: list = []       # open spans, innermost last
        self.traces: dict = {}      # name -> its newest trace, unlowered
        self.last: dict | None = None   # the newest record


_open = _Thread()
_spans: list = []       # closed ``setup.*`` spans, in closing order
_programs: list = []    # build records, in lowering order
_setup_open = 0         # ``setup.*`` spans open now, on any thread
_setup_lock = threading.Lock()      # set-up may run on several threads
_gc_t0 = None           # start of the collection running now
_installed = False


class Span:
    """One span of the program, measured twice: as the host span
    ``name`` of a profiler's trace (``attrs`` beside it: ``step=`` is
    what the spans of one engine step share; a no-op while no profiler
    runs) and as a ``perf_counter`` pair — added to ``acc[key]`` where
    one is given (a step's phases), else logged with ``attrs`` when a
    ``setup.*`` span closes. While open it is on its thread's stack:
    a program built meanwhile is booked on the innermost open span."""

    __slots__ = ("name", "ann", "acc", "key", "attrs", "t0", "seconds",
                 "gc_s")

    def __init__(self, name: str, acc: dict | None = None, key=None,
                 **attrs):
        self.ann = jax.profiler.TraceAnnotation(name, **attrs)
        self.name, self.acc, self.key, self.attrs = name, acc, key, attrs
        self.gc_s = 0.0

    def __enter__(self):
        global _setup_open
        self.ann.__enter__()
        _open.stack.append(self)
        if self.acc is None:
            with _setup_lock:
                _setup_open += 1
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        global _setup_open
        self.seconds = time.perf_counter() - self.t0
        _open.stack.pop()
        if self.acc is not None:
            self.acc[self.key] += self.seconds
        else:
            with _setup_lock:
                _setup_open -= 1
            _spans.append({"name": self.name, "t0": self.t0,
                           "seconds": self.seconds, "gc_s": self.gc_s,
                           **self.attrs})
        self.ann.__exit__(*exc)


def spanned(name: str):
    """Decorator: the whole of each call under a ``Span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kw):
            with Span(name):
                return fn(*args, **kw)
        return inner
    return wrap


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: a collection's pause, summed on the innermost
    ``setup.*`` span open on the collecting thread. One comparison a
    collection while no such span is open anywhere."""
    global _gc_t0
    if not _setup_open:
        return
    if phase == "start":
        _gc_t0 = time.perf_counter()
    elif _gc_t0 is not None:
        pause, _gc_t0 = time.perf_counter() - _gc_t0, None
        for span in reversed(_open.stack):
            if span.acc is None:
                span.gc_s += pause
                break


def _on_duration(event: str, duration: float, **kw) -> None:
    """A phase of a program's build has ended on this thread: JAX hands
    its seconds; its interval is those seconds back from now.

    Booked by CONTAINMENT of the intervals, never by addition: a jit
    called inside a traced function fires its own trace event inside
    the outer one (``ragged_paged_attention`` under ``step``; every
    ``jnp`` call: thousands a step program), and the outer's seconds
    hold it. A function's outermost trace ENDS LAST, so the newest
    trace of each name is kept — one dict entry an event, nothing to
    search — and the lowering of ``jit(step)`` takes ``step``'s. A
    record made inside a trace or a lowering (a program built eagerly
    meanwhile) is marked ``inside`` and summed by no reader."""
    st = _open
    if not st.stack:
        if event == _LOWER:
            # a program no span holds: its cache events are not the
            # newest record's
            st.last = None
        return
    if event == _TRACE:
        start = time.perf_counter() - duration
        name = kw.get("fun_name")
        st.traces[name] = (start, duration)
        _hold(start, name)
    elif event == _LOWER:
        # one program, one record, on the innermost open span. Its
        # trace is the newest of its name (``step`` lowers as
        # ``jit(step)``); traces nothing lowered (nested ones, an
        # ``eval_shape``) go with it
        name = kw.get("fun_name", "")
        start = time.perf_counter() - duration
        _hold(start, name)
        traced = st.traces.get(name.partition("(")[2][:-1], (start, 0.0))
        st.traces.clear()
        span = st.stack[-1]
        st.last = {
            "fun_name": name, "span": span.name,
            "step": span.attrs.get("step"),
            # the program key, under ``setup.program``
            "block_q": span.attrs.get("block_q"),
            "width": span.attrs.get("width"),
            "t0": traced[0], "trace_s": traced[1], "lower_s": duration,
            # the backend's compile less the cache's retrieval: on a
            # hit, what hashing the module for its key took
            "compile_s": 0.0, "cache": "off", "retrieval_s": 0.0,
            "inside": None,
        }
        _programs.append(st.last)
    elif event == _COMPILE:
        # the backend's compile, a load from the cache included
        rec = st.last
        if rec is not None and rec["fun_name"] == kw.get("fun_name"):
            rec["compile_s"] = max(0.0, duration - rec["retrieval_s"])
    elif event == _RETRIEVAL and st.last is not None:
        st.last["retrieval_s"] += duration


def _hold(start: float, name: str) -> None:
    """The interval from ``start`` to now holds every record made in
    it: mark them (newest first; the first older one ends the walk)."""
    for rec in reversed(_programs):
        if rec["t0"] < start:
            break
        rec["inside"] = name


def _on_event(event: str, **kw) -> None:
    state = _CACHE.get(event)
    rec = _open.last
    if state is not None and rec is not None and rec["cache"] != "hit":
        rec["cache"] = state


def install() -> None:
    """Register the pair of listeners and the collector's callback,
    once a process (``Transformer`` calls this when it is built)."""
    global _installed
    if _installed:
        return
    _installed = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    gc.callbacks.append(_on_gc)


def startup_log() -> dict:
    """What this process's set-up did, so far: ``spans`` — every closed
    ``setup.*`` span (``name``, ``seconds``, ``gc_s``, ``t0`` on the
    ``perf_counter`` clock, and its attributes: a ``setup.program``'s
    ``step``, ``block_q``, ``width``) — and ``programs`` — one record a
    program built while a span was open (``fun_name``, the innermost
    open ``span``, its ``step`` and program key, ``trace_s``,
    ``lower_s``, ``compile_s``, ``cache`` ``hit`` | ``miss`` | ``off``,
    ``retrieval_s``; ``inside``: the program in whose trace or lowering
    it was built, else None)."""
    return {"spans": list(_spans), "programs": list(_programs)}


def summary(log: dict | None = None) -> dict:
    """The log's sums: what :func:`ready_line` prints and the
    benchmark's set-up metrics read, each under the metric's name less
    ``setup_``. ``*_s`` of a span that was never opened is 0."""
    log = startup_log() if log is None else log
    spans, programs = log["spans"], log["programs"]

    def seconds(name):
        return sum(s["seconds"] for s in spans if s["name"] == name)

    built = [s["seconds"] for s in spans if s["name"] == "setup.program"]
    top = [p for p in programs if p["inside"] is None]
    step = [p for p in top if p["span"] == "setup.program"]
    return {
        "model_s": seconds("setup.model"),
        "engine_s": seconds("setup.engine"),
        "state_s": seconds("setup.state"),
        "workspaces_s": seconds("setup.workspaces"),
        "step_programs": len(built),
        "step_programs_s": sum(built),
        "step_program_s_max": max(built, default=0.0),
        "trace_s": sum(p["trace_s"] for p in step),
        "lower_s": sum(p["lower_s"] for p in step),
        "compile_s": sum(p["compile_s"] + p["retrieval_s"] for p in step),
        # the key had been dispatched before, yet JAX built again
        "rebuilt_programs": sum(p["span"] == "engine.dispatch"
                                for p in top),
        # 0 says set-up was warm
        "cache_misses": sum(p["span"].startswith(SETUP)
                            and p["cache"] == "miss" for p in programs),
        "gc_s": sum(s["gc_s"] for s in spans),
    }


def ready_line(log: dict | None = None) -> str:
    """One line for an operator at readiness."""
    s = summary(log)
    return (
        f"ready: model {s['model_s']:.1f} s, engine {s['engine_s']:.1f} s "
        f"(state {s['state_s']:.1f}, workspaces {s['workspaces_s']:.1f}), "
        f"{s['step_programs']} step programs {s['step_programs_s']:.1f} s "
        f"(trace {s['trace_s']:.1f}, lower {s['lower_s']:.1f}, "
        f"compile/load {s['compile_s']:.1f}; "
        f"{s['cache_misses']} cache misses, "
        f"{s['rebuilt_programs']} rebuilt), gc {s['gc_s']:.1f} s")
