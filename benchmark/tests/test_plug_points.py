"""What a later PR adds without editing the harness: readers, host
spans, device scopes and the engine's own counters, found by name.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import gzip
import json
import pathlib
import shutil
import time

import numpy as np
import pytest

import benchmark
import benchmark.readers
from benchmark.harness import driver, loadgen, metrics, program, trace
from benchmark.harness.spec import Spec

HERE = pathlib.Path(__file__).resolve().parent
DATA_ROOT = HERE / "data" / "root"
REPO = HERE.parents[1]
DEV = "/device:TPU:0"


# --------------------------------------------- the trace keeps everything

def test_scopes_and_host_events_of_a_recorded_trace(tmp_path):
    """``data/v5e_scoped_steps.xplane.pb.gz``: two engine steps cut
    WITH the events' stats, their metadata's stats and every host
    event; the expected numbers were worked out from the protobuf
    directly (``data/README``)."""
    want = json.loads(
        (HERE / "data" / "v5e_scoped_steps.expected.json").read_text())
    path = tmp_path / "steps.xplane.pb"
    with gzip.open(HERE / "data" / "v5e_scoped_steps.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    s = trace.TraceSummary.from_file(str(path), spans=want["spans"])
    near = lambda x: pytest.approx(x, rel=1e-3)
    assert s.chips() == want["chips"]
    assert len(s.device_ops[DEV]) == want["device_events"]
    assert len(s.host_events) == want["host_events"]
    assert s.busy_seconds() == near(want["busy_s"])
    # the scope path: one whole component, never a part of one
    for scope, secs in want["scope_s"].items():
        assert s.matched_seconds(scope=scope) == near(secs), scope
    assert s.matched_seconds(scope="ragged_paged_attention") is None
    assert s.matched_seconds("ragged_paged_attention") == near(
        want["match_ragged_paged_attention_s"])
    assert sum(d for _, _, d, p in s.device_ops[DEV] if not p) / 1e9 == \
        near(want["unscoped_s"])
    # any host event by name; self time against the spans given
    for name, secs in want["span_s"].items():
        assert s.span_seconds(name) == near(secs), name
    assert s.span_seconds("engine_step", self_time=True) == near(
        want["self_s"]["engine_step"])
    assert s.span_seconds("no such event") is None
    # the program's functions as spans: the gaps go down to them (the
    # harness reads whole ns: the sub-us gaps agree to 100 ns in all)
    got = {k: v / 1e9 for k, v in s.idle_by_path().items()}
    assert got == {k: pytest.approx(v, rel=1e-3, abs=1e-7)
                   for k, v in want["idle_s"].items()}
    assert s.idle_seconds("engine_step") == near(sum(want["idle_s"].values()))
    # with the span files beside the harness alone, as a run reads it:
    # the same seconds, all under engine_step
    plain = trace.TraceSummary.from_file(str(path))
    assert plain.idle_by_host_span() == [
        ["engine_step", near(sum(want["idle_s"].values()))]]


def test_the_four_spans_the_driver_opens_are_four_files(fake_requests):
    names = set(trace.known_spans(REPO / "benchmark"))
    assert names == {"loadgen", "idle_wait", "engine_step", "stamp"}
    assert names == set(trace.known_spans(DATA_ROOT / "benchmark"))
    opened = set()
    driver.serve(_Engine(), _arrivals(1), 0.05, 5.0,
                 span=lambda name: opened.add(name) or driver.no_span(name))
    assert opened <= names
    for entry in trace.known_spans(REPO / "benchmark").values():
        assert entry["layer"] and entry["what"]


def _nested():
    """One step 0..100: ``a`` 10..40 and ``b`` 40..90 inside it, ``c``
    50..60 inside ``b`` (and an ``a`` on another thread, a root of its
    own); device busy 0..10, 30..44, 62..64, 96..100."""
    ops = {DEV: [("k", 0, 10, "jit(step)/attn/dot_general"),
                 ("k", 30, 14, "jit(step)/attn_out/dot_general"),
                 ("f", 62, 2, "jit(step)/kv_append/scatter"),
                 ("f", 96, 4)]}
    host = [("engine_step", "py", 0, 100), ("a", "py", 10, 40),
            ("b", "py", 40, 90), ("c", "py", 50, 60),
            ("a", "other thread", 5, 8), ("runtime", "rt", 44, 62)]
    return ops, host


def test_a_gap_goes_to_the_child_above_half_and_to_the_parent_below():
    ops, host = _nested()
    s = trace.TraceSummary(ops, host, spans=["engine_step", "a", "b", "c"])
    assert [p for p, *_ in s.span_paths()] == [
        ("engine_step",), ("a",), ("engine_step", "a"),
        ("engine_step", "b"), ("engine_step", "b", "c")]
    # gap 10..30 lies in a; 44..62 in b, c covers 10 of its 18: more
    # than half; 64..96: b covers 26 of 32, no child of b any of it
    assert s.idle_by_path() == {"engine_step/a": 20,
                                "engine_step/b/c": 18,
                                "engine_step/b": 32}
    assert s.idle_seconds("engine_step") == pytest.approx(70e-9)
    assert s.idle_seconds("b") == pytest.approx(50e-9)
    assert s.idle_seconds("c") == pytest.approx(18e-9)
    assert s.idle_seconds("nope") is None
    # c at exactly half, or below: the gap stays with b
    ops[DEV][2] = ("f", 64, 2, "")          # gap 44..64, c covers 10
    s = trace.TraceSummary(ops, host, spans=["engine_step", "a", "b", "c"])
    assert s.idle_by_path()["engine_step/b"] == 20 + 30
    # an unknown child does not exist: today's names and seconds
    s = trace.TraceSummary(ops, host, spans=["engine_step"])
    assert s.idle_by_host_span() == [["engine_step", pytest.approx(70e-9)]]


def test_one_stalled_step_moves_the_mean_and_not_the_median():
    """Five steps of 100 with the device idle for the last 10 of each;
    the third stalls for 1000 more."""
    ops, host, t = [], [], 0
    for i in range(5):
        wall = 1100 if i == 2 else 100
        host.append(("engine_step", "py", t, t + wall))
        ops.append(("k", t, 90))
        t += wall
    ops.append(("k", t, 90))
    s = trace.TraceSummary({DEV: ops}, host, spans=["engine_step"])
    assert s.idle_per_instance("engine_step") == pytest.approx(
        [10e-9, 10e-9, 1010e-9, 10e-9, 10e-9])
    assert s.idle_seconds("engine_step") == pytest.approx(1050e-9)
    rec = {"series": {"traced_steps": [0, 1, 2, 3, 4]}, "trace": s}
    idle = lambda **a: metrics.read_layer_metric(
        rec, {"reader": "idle_ms_per_step", "args": a})
    assert idle(span="engine_step") == pytest.approx(210e-6)
    assert idle(span="engine_step", q=50) == pytest.approx(10e-6)


def test_the_three_new_readers_on_a_hand_made_record():
    ops, host = _nested()
    rec = {"series": {"traced_steps": [0, 1], "x": [1.0, 2.0, 3.0]},
           "counters": {}, "chips": 1,
           "trace": trace.TraceSummary(
               ops, host, spans=["engine_step", "a", "b", "c"])}
    read = metrics.read_layer_metric
    per_step = lambda ns: pytest.approx(1e3 * ns / 1e9 / 2)

    def events(**args):
        return read(rec, {"reader": "trace_events_ms_per_step",
                          "args": args})
    assert events(scope="attn") == per_step(10)
    assert events(scope="attn_out") == per_step(14)
    assert events(scope="dot_general") == per_step(24)
    assert events(scope="kv") is None
    assert events(match="k") == per_step(24)
    with pytest.raises(ValueError):
        events(match="k", scope="attn")

    def span(**args):
        return read(rec, {"reader": "host_span_ms_per_step", "args": args})
    assert span(span="engine_step") == per_step(100)
    assert span(span="engine_step", self_time=True) == per_step(20)
    assert span(span="b", self_time=True) == per_step(40)
    # ``a`` twice: inside the step and on the other thread
    assert span(span="a", self_time=True) == per_step(30 + 3)
    assert span(span="runtime") == per_step(18)     # not a known span
    assert span(span="absent") is None

    def idle(name):
        return read(rec, {"reader": "idle_ms_per_step",
                          "args": {"span": name}})
    assert idle("engine_step") == per_step(70)
    assert idle("b") == per_step(50) and idle("c") == per_step(18)
    assert idle("absent") is None
    # the same gaps booked on the span's instances: one of each, but
    # two of ``a`` (the other thread's covers no gap: 0 and 20)
    for name, ns in (("engine_step", 70), ("b", 50), ("a", 10)):
        assert read(rec, {"reader": "idle_ms_per_step", "args": {
            "span": name, "q": 50}}) == pytest.approx(1e3 * ns / 1e9)
    assert read(rec, {"reader": "idle_ms_per_step", "args": {
        "span": "absent", "q": 50}}) is None
    assert read(rec, {"reader": "percentile_of", "args": {
        "series": "x", "q": 50, "scale": 1e3}}) == 2000.0
    rec["trace"] = None
    assert idle("engine_step") is None and span(span="b") is None


def test_a_reader_is_a_kind_or_a_function_named_in_the_file():
    rec = {"series": {"x": [1.0, 5.0]}, "counters": {}}
    assert metrics.read_layer_metric(rec, {
        "reader": "benchmark.harness.metrics:_percentile_of",
        "args": {"series": "x", "q": 100}}) == 5.0
    with pytest.raises(ImportError):
        metrics.read_layer_metric(
            rec, {"reader": "benchmark.readers.no_such_module:read"})
    with pytest.raises(AttributeError):
        metrics.read_layer_metric(
            rec, {"reader": "benchmark.harness.metrics:no_such_function"})
    with pytest.raises(KeyError):
        metrics.read_layer_metric(rec, {"reader": "no_such_kind"})


# ------------------------------------------ the engine's own numbers

class _Request:
    def __init__(self, prompt, max_new):
        self.prompt, self.max_new = prompt, max_new
        self.generated, self.cursor, self.slot, self.done = [], 0, None, False


class _Stats:
    """The fields the driver names, and three it has never heard of."""

    def __init__(self):
        self.step_times, self.step_tokens = [], []
        self.prefill_tokens = self.generated_tokens = self.completed = 0
        self.evictions = self.deferrals = 0
        self.fetch_times = []       # one entry per device step
        self.pages_walked = 0
        self.failures = []          # a list that is no per-step series
        self.degraded = False


class _Engine:
    """A slot on the first step, one token per step after; a step with
    nothing to run does not touch the device."""

    def __init__(self):
        self.stats, self.step_count, self.reqs = _Stats(), 0, []

    def submit(self, r):
        self.reqs.append(r)

    def step(self):
        live = [r for r in self.reqs if not r.done]
        self.step_count += 1
        if not live:
            return
        time.sleep(0.002)
        for r in live:
            if r.slot is None:
                r.slot, r.cursor = 0, len(r.prompt)
            else:
                r.generated.append(1)
                r.cursor += 1
                r.done = len(r.generated) >= r.max_new
        st = self.stats
        st.step_times.append(0.0015)
        st.step_tokens.append(len(live))
        st.fetch_times.append(0.0005 * len(st.step_times))
        st.pages_walked += 3


def _arrivals(n, max_new=3):
    return [loadgen.Arrival(rid=i, due=0.01 * i, max_new=max_new,
                            prompt=np.zeros((4,), np.int32))
            for i in range(n)]


@pytest.fixture
def fake_requests(monkeypatch):
    monkeypatch.setattr(
        program, "new_request",
        lambda rid, prompt, max_new, arrival: _Request(prompt, max_new))


def test_engine_fields_become_counters_and_per_step_series(fake_requests):
    eng = _Engine()
    # what ran before the window (warm-up) is not the window's
    eng.stats.fetch_times += [9.0, 9.0]
    eng.stats.step_times += [9.0, 9.0]
    eng.stats.pages_walked = 100
    snap = program.stats_snapshot(eng)
    assert snap["numbers"]["pages_walked"] == 100
    assert "degraded" not in snap["numbers"]
    assert set(snap["lists"]) == {"step_times", "step_tokens",
                                  "fetch_times", "failures"}
    win = driver.serve(eng, _arrivals(3), 0.05, 5.0)
    n = len(win.steps)
    assert n >= 4 and win.counters["device_steps"] == n
    assert win.counters["stats.pages_walked"] == 3 * n
    assert win.counters["stats.completed"] == 0 == win.counters["completed"]
    s = metrics.series(win)
    assert s["stats.fetch_times"] == pytest.approx(
        [0.0005 * (3 + i) for i in range(n)])
    assert s["stats.step_times"] == [0.0015] * n
    assert len(s["stats.fetch_times"]) == len(s["step_wall_ms"])
    assert "stats.failures" not in s
    # the eight series and eight counters that were there keep their names
    assert {"ttft_ms", "itl_ms", "gen_late_ms", "queue_wait_ms",
            "step_wall_ms", "step_device_ms"} <= set(s)
    assert {"prefill_tokens", "generated_tokens", "completed", "evictions",
            "deferrals", "step_tokens", "device_steps",
            "programs_lowered"} <= set(win.counters)


def test_stats_snapshot_reads_the_program_s_own_engine_stats():
    from triton_distributed_tpu.serving.engine import EngineStats

    st = EngineStats(prefill_tokens=7, degraded=True)
    st.step_times.append(0.5)
    snap = program.stats_snapshot(type("E", (), {"stats": st})())
    assert snap["numbers"]["prefill_tokens"] == 7
    assert "degraded" not in snap["numbers"]
    assert snap["lists"]["step_times"] == ([0.5], 1)
    assert "shape_ledger" not in snap["lists"]


# ------------------------------- a rehearsal of the tracing PR, as files

def test_the_tracing_pr_adds_its_metrics_as_files(
        tmp_path, monkeypatch, fake_requests):
    """A reader module, a host-span file, a ``stats.<field>`` series
    metric and a ``scope`` metric, added to a copy of ``data/root`` as
    NEW FILES and entries; the unchanged harness reports all of them."""
    root = tmp_path / "root"
    shutil.copytree(DATA_ROOT, root)
    bench = root / "benchmark"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "readers").mkdir()
    (bench / "readers" / "worst.py").write_text(
        "def worst_over_median(rec, series):\n"
        "    xs = sorted(rec['series'].get(series) or [])\n"
        "    return xs[-1] / xs[len(xs) // 2] if xs else None\n")
    monkeypatch.setattr(benchmark.readers, "__path__",
                        list(benchmark.readers.__path__)
                        + [str(bench / "readers")])
    (bench / "host_spans" / "engine.fetch.json").write_text(json.dumps(
        {"layer": "device step", "what": "np.asarray(logits)"}))
    new = {
        "fetch_worst_over_median": {
            "reader": "benchmark.readers.worst:worst_over_median",
            "args": {"series": "stats.fetch_times"}},
        "fetch_ms_p50": {
            "reader": "percentile_of",
            "args": {"series": "stats.fetch_times", "q": 50,
                     "scale": 1000.0}},
        "kv_append_ms_per_step": {
            "reader": "trace_events_ms_per_step",
            "args": {"scope": "kv_append"}},
        "fetch_idle_ms_per_step": {
            "reader": "idle_ms_per_step", "args": {"span": "engine.fetch"}},
        "fetch_self_ms_per_step": {
            "reader": "host_span_ms_per_step",
            "args": {"span": "engine.fetch", "self_time": True}},
    }
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for name, definition in new.items():
        (bench / "layer_metrics" / f"{name}.json").write_text(
            json.dumps(definition))
        doc["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "device step",
            "moves": "ttft_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")

    spec = Spec(root)
    cell = spec.cell("tiny.chat")
    win = driver.serve(_Engine(), _arrivals(3), 0.05, 5.0)
    win.traced = (0.0, win.closed_at)
    n = len(win.steps)
    # a hand-made trace of those steps, 1 us a tick: the step's span
    # with the program's engine.fetch inside, a scoped append, a gap
    # under the fetch and one before it
    ops, host = [], []
    for i in range(n):
        t = 1000 * i
        host += [("engine_step", "python3", t, t + 900),
                 ("engine.fetch", "python3", t + 500, t + 800),
                 ("TransferFromDevice", "runtime", t + 520, t + 700)]
        ops += [("fusion.1 s8[64,128]", t, 100,
                 "jit(step)/layer/kv_append/scatter"),
                ("fusion.2 bf16[8]", t + 100, 300,
                 "jit(step)/layer/kv_append_scale/mul"),
                ("k.3 bf16[8]", t + 840, 160, "jit(step)/lm_head/dot")]
    summary = trace.TraceSummary({DEV: ops}, host,
                                 spans=trace.known_spans(spec.bench))
    rec = metrics.layer_record(win, summary, None, cell)
    got = metrics.per_layer(cell, rec)
    value = lambda name: got[name]["value"]
    assert set(new) | {"gen_late_p95_ms", "step_ms_p50"} == set(got)
    assert value("fetch_worst_over_median") == pytest.approx(
        n / (n // 2 + 1))
    assert value("fetch_ms_p50") == pytest.approx(0.5 * (n + 1) / 2)
    assert value("kv_append_ms_per_step") == pytest.approx(100e-6)
    # the gap 400..840 of each step: engine.fetch covers 300 of 440
    assert value("fetch_idle_ms_per_step") == pytest.approx(
        440e-6 * n / n)
    assert value("fetch_self_ms_per_step") == pytest.approx(300e-6)
    assert summary.idle_by_host_span()[0] == [
        "engine_step/engine.fetch", pytest.approx(440e-9 * n)]
