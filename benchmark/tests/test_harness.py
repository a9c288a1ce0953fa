"""The benchmark harness, checked on the CPU at a tiny size (seconds).

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import copy
import json
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import benchmark
from benchmark.harness import (
    cell_run, correct, driver, loadgen, metrics, program, trace,
)
from benchmark.harness.spec import Spec

HERE = pathlib.Path(__file__).resolve().parent
DATA_ROOT = HERE / "data" / "root"
REPO = HERE.parents[1]


def last_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


# ------------------------------------------------------------- load generator

MIX = json.loads((REPO / "benchmark" / "mixes" / "chat.json").read_text())


def test_loadgen_is_deterministic_under_seed():
    a = loadgen.generate(MIX, 10.0, 20.0, 2**31 + 11, 1000)
    b = loadgen.generate(MIX, 10.0, 20.0, 2**31 + 11, 1000)
    assert [x.due for x in a] == [x.due for x in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))


def test_loadgen_gives_every_seed_the_same_work_in_another_order():
    a = loadgen.generate(MIX, 10.0, 20.0, 1, 1000)
    b = loadgen.generate(MIX, 10.0, 20.0, 2, 1000)
    assert len(a) == len(b) == 200
    for key in (lambda x: len(x.prompt), lambda x: x.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = [np.diff([x.due for x in t]) for t in (a, b)]
    np.testing.assert_allclose(np.sort(gaps[0]), np.sort(gaps[1]))
    assert a[-1].due == pytest.approx(b[-1].due)
    # due times are SECONDS inside the window, the first at its start
    assert a[0].due == 0.0 and 15.0 < a[-1].due < 20.0
    lens = [len(x.prompt) for x in a]
    assert min(lens) >= 32 and max(lens) == 2048
    assert 330 < np.median(lens) < 440
    assert max(x.max_new for x in a) == 384


def test_loadgen_arrivals_are_as_bursty_as_a_poisson_stream():
    """Nothing smooths the order: over seeds, the count of arrivals in
    a 5 s stretch varies as that of a Poisson stream conditioned on
    the run's count and span (variance n p (1 - p)), and the gaps of
    one run are not sorted into any pattern."""
    mix = {**MIX, "prompt": {**MIX["prompt"], "median": 33, "max": 40}}
    rate, seconds, w = 7.0, 51.0, 5.0
    counts, spans = [], []
    for seed in range(200):
        due = np.array([a.due for a in
                        loadgen.generate(mix, rate, seconds, seed, 100)])
        spans.append(due[-1])
        counts += [int(((due >= i * w) & (due < (i + 1) * w)).sum())
                   for i in range(int(due[-1] // w))]
    n, p = round(rate * seconds), w / np.mean(spans)
    assert np.mean(counts) == pytest.approx(rate * w, rel=0.03)
    assert np.var(counts) == pytest.approx(n * p * (1 - p), rel=0.15)
    # and far above what evenly spread arrivals would give
    assert np.var(counts) > 0.8 * rate * w


def test_loadgen_refuses_a_kind_it_does_not_have():
    with pytest.raises(ValueError):
        loadgen.generate({**MIX, "arrivals": "uniform"}, 5.0, 2.0, 1, 100)


class _FakeRequest:
    def __init__(self):
        self.generated, self.cursor, self.slot, self.done = [], 0, None, False


class _FakeEngine:
    """Two-step service: a slot on the first step, one token per step
    after that; each step takes 20 ms of wall clock."""

    def __init__(self):
        self.stats = type("S", (), dict(
            step_times=[], step_tokens=[], prefill_tokens=0,
            generated_tokens=0, completed=0, evictions=0, deferrals=0))()
        self.step_count, self.reqs = 0, []

    def submit(self, r):
        self.reqs.append(r)

    def step(self):
        time.sleep(0.02)
        for r in self.reqs:
            if r.done:
                continue
            if r.slot is None:
                r.slot, r.cursor = 0, len(r.prompt)
            else:
                r.generated.append(1)
                r.cursor += 1
                r.done = len(r.generated) >= r.max_new
        self.stats.step_times.append(0.015)
        self.stats.step_tokens.append(1)
        self.step_count += 1


def test_driver_times_requests_from_when_they_were_due(monkeypatch):
    monkeypatch.setattr(
        program, "new_request",
        lambda rid, prompt, max_new, arrival: _with(prompt, max_new))
    arr = [loadgen.Arrival(rid=i, due=d, max_new=2,
                           prompt=np.zeros((8,), np.int32))
           for i, d in enumerate((0.0, 0.005, 0.25))]
    win = driver.serve(_FakeEngine(), arr, 0.3, 5.0)
    s = metrics.series(win)
    # request 1 fell due inside request 0's first step: it was handed
    # over late, and its clock still started when it was due
    assert arr[1].submitted - arr[1].due > 0.010
    assert s["gen_late_ms"][1] > 10.0
    ttft = [a.token_times[0] - a.due for a in arr]
    assert ttft[0] == pytest.approx(0.040, abs=0.012)
    assert ttft[1] == pytest.approx(0.055, abs=0.012)
    assert ttft[1] > arr[1].token_times[0] - arr[1].submitted
    # the engine was idle before request 2: the driver slept to its due
    assert arr[2].submitted - arr[2].due < 0.010
    assert len(s["itl_ms"]) == 3 and all(15 < g < 35 for g in s["itl_ms"])
    assert not metrics.failures(win, vocab=10)
    assert s["step_device_ms"] == [15.0] * len(win.steps)


def _with(prompt, max_new):
    r = _FakeRequest()
    r.prompt, r.max_new = prompt, max_new
    return r


# ------------------------------------------------------------------ metrics

def test_percentile_arithmetic_on_a_hand_made_sample():
    xs = [10, 20, 30, 40, 50]
    assert metrics.percentile(xs, 50) == 30
    assert metrics.percentile(xs, 95) == pytest.approx(48.0)
    assert metrics.percentile(xs, 0) == 10
    assert metrics.percentile(xs, 100) == 50
    assert metrics.percentile([7], 95) == 7
    ys = list(np.random.default_rng(0).normal(size=101))
    for q in (5, 50, 95, 99):
        assert metrics.percentile(ys, q) == pytest.approx(
            float(np.percentile(ys, q)))
    with pytest.raises(ValueError):
        metrics.percentile([], 50)


def test_readers_on_a_hand_made_record():
    rec = {"series": {"a": [5.0, 7.0, 9.0], "b": [1.0, 2.0, 3.0],
                      "traced_steps": [0, 1],
                      "traced_rows": [[(4, 20)], [(1, 21), (1, 33)]]},
           "counters": {"n": 30, "d": 120}, "trace": None, "chips": 1}
    read = metrics.read_layer_metric
    assert read(rec, {"reader": "span_minus_counter",
                      "args": {"span": "a", "counter": "b"}}) == 5.0
    assert read(rec, {"reader": "counter_ratio", "args": {
        "num": "n", "den": "d", "scale": 100.0}}) == 25.0
    assert read(rec, {"reader": "percentile_of",
                      "args": {"series": "a", "q": 50}}) == 7.0
    # nothing to read -> nothing reported
    assert read(rec, {"reader": "percentile_of",
                      "args": {"series": "absent", "q": 50}}) is None
    assert read(rec, {"reader": "trace_events_ms_per_step",
                      "args": {"match": "x"}}) is None
    with pytest.raises(KeyError):
        read(rec, {"reader": "no_such_reader"})


def test_roofline_share_from_a_needs_function_named_in_the_file():
    """The kernel's bytes and operations come from the function the
    layer-metric file names; the share is the least time over the
    kernel's time."""
    definition = json.loads(
        (REPO / "benchmark" / "layer_metrics"
         / "ragged_paged_attention_roofline.json").read_text())
    config = {"as_run": {"n_kv_heads": 2, "n_heads": 4, "head_dim": 8,
                         "n_layers": 3},
              "engine": {"page": 16}, "kv_bytes_per_element": 2}
    from benchmark.kernel_needs import ragged_paged_attention as need

    # a row that took 4 positions to cursor 20: 2 resident pages, and
    # 4 x 16 earlier + (4 + 3 + 2 + 1) own attended pairs
    by, ops = need.step_needs(config, [(4, 20)])
    assert by == 2 * (2 * 16 * 8 * 2 * 2 * 3)
    assert ops == (4 * 16 + 10) * (4.0 * 4 * 8 * 3)
    tr = trace.TraceSummary(
        {"/device:TPU:0": [("ragged_paged_attention.1 bf16[8]", 0, 1000),
                           ("fusion.2 f32[8]", 1000, 500)]}, {})
    rec = {"series": {"traced_rows": [[(4, 20)]]}, "counters": {},
           "trace": tr, "chips": 1, "config": config,
           "peaks": {"hbm_bytes_per_s": by / 0.5e-6,
                     "bf16_flops_per_s": ops / 0.1e-6}}
    # bytes need 0.5 us, operations 0.1 us, the kernel took 1 us
    assert metrics.read_layer_metric(rec, definition) == \
        pytest.approx(50.0)
    rec["trace"] = None
    assert metrics.read_layer_metric(rec, definition) is None


# -------------------------------------------------------------------- trace

def test_trace_reduction_on_a_recorded_trace():
    """``data/v5e_steps.xplane.pb``: a few engine steps cut from a
    trace recorded on the v5e by ``sweep.py --trace-probe``; the
    expected numbers are in ``data/v5e_steps.expected.json``, worked
    out from the protobuf directly (``data/README``)."""
    want = json.loads((HERE / "data" / "v5e_steps.expected.json")
                      .read_text())
    s = trace.TraceSummary.from_file(
        str(HERE / "data" / "v5e_steps.xplane.pb"))
    assert s.chips() == want["chips"]
    assert s.busy_seconds() == pytest.approx(want["busy_s"], rel=1e-3)
    assert s.matched_seconds("ragged_paged_attention") == pytest.approx(
        want["ragged_paged_attention_s"], rel=1e-3)
    assert s.matched_seconds("no_such_kernel") is None
    top = s.top_ops(3)
    assert [n for n, _ in top] == want["top3_names"]
    assert sum(d for _, d in s.idle_by_host_span()) == pytest.approx(
        want["idle_s"], rel=1e-3)
    assert s.idle_by_host_span()[0][0] == want["idle_mostly_in"]


def test_idle_gaps_go_to_the_span_that_covers_them():
    ops = {"/device:TPU:0": [("k", 0, 10), ("k", 30, 10), ("j", 35, 15),
                             ("k", 100, 10)]}
    spans = {"engine_step": [(0, 60)], "stamp": [(60, 61)],
             "idle_wait": [(62, 98)]}
    s = trace.TraceSummary(ops, spans)
    assert s.busy_seconds() == pytest.approx(40e-9)
    assert s.matched_seconds("k") == pytest.approx(30e-9)
    assert dict(map(tuple, s.idle_by_host_span())) == {
        "engine_step": pytest.approx(20e-9),
        "idle_wait": pytest.approx(50e-9)}


# ------------------------------------------------- a run, end to end, on CPU

@pytest.fixture(scope="module")
def tiny():
    """The tiny cell, built once: (spec, cell, program)."""
    import tempfile

    spec = Spec(DATA_ROOT)
    cell = spec.cell("tiny.chat")
    program.hermetic_tuning(tempfile.mkdtemp())
    program.enable_compile_cache()
    prog = program.build(cell.config, cell.mix, 1, seed=5)
    program.warm_up(prog.engine, cell.config["as_run"]["vocab"])
    return spec, cell, prog


def _window(cell, prog, seed, seconds=1.5):
    arr = loadgen.generate(cell.mix, 6.0, seconds, seed,
                           cell.config["as_run"]["vocab"])
    return driver.serve(prog.engine, arr, seconds, 60.0)


def test_same_seed_twice_gives_the_same_token_streams(tiny):
    _, cell, prog = tiny
    a, b = _window(cell, prog, 9), _window(cell, prog, 9)
    assert not metrics.failures(a, 128) and not metrics.failures(b, 128)
    assert [x.request.generated for x in a.arrivals] == \
        [x.request.generated for x in b.arrivals]
    assert a.counters["programs_lowered"] == 0 == \
        b.counters["programs_lowered"]


def test_control_in_lower_precision_comes_out_not_correct(tiny):
    """The control is the reference itself computed at the
    configuration's ``control_bits`` and put in the program's place: its
    tokens must fail the limits the program's own tokens pass."""
    _, cell, prog = tiny
    tol, sizes = cell.config["tolerance"], cell.config["as_run"]
    for seed in (5, 6, 7):
        prog.load_weights(seed)
        win = _window(cell, prog, seed)
        picked = correct.sample(win.arrivals, seed, tol["sample"])
        assert picked[0] is max(
            win.arrivals, key=lambda a: len(a.prompt) + a.max_new)
        gaps = correct.served_gaps(
            prog.reference.logits_at, prog.masters(seed), sizes, picked,
            cell.mix["output"]["max"], control_bits=tol["control_bits"])
        ok, rows = correct.decide(correct.numbers(gaps["program"]), tol)
        assert ok, rows
        ok, rows = correct.decide(correct.numbers(gaps["control"]), tol)
        assert not ok, rows
    prog.load_weights(5)


def _fresh_root(tmp_path) -> pathlib.Path:
    root = tmp_path / "root"
    shutil.copytree(DATA_ROOT, root)
    return root


def test_new_config_mix_cell_and_metric_are_found_as_files(
        tmp_path, capsys, monkeypatch):
    """A later PR's move: new files and new BENCHMARK.json entries,
    no edit to any file that was there. Also the ``chips: 4`` path, on
    four virtual devices: the mesh comes from the data."""
    root = _fresh_root(tmp_path)
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "tiny-moe.json").read_text())
    cfg["chips"] = 4
    (bench / "configs" / "tiny-moe-tp4.json").write_text(json.dumps(cfg))
    # a mix with an arrivals kind of its own: a function in a new file
    (bench / "traffic_kinds").mkdir()
    (bench / "traffic_kinds" / "even.py").write_text(
        "import numpy as np\n\n"
        "def gaps(spec, n, rate_rps):\n"
        "    return np.full((n,), spec['stretch'] / rate_rps)\n")
    monkeypatch.setattr(
        benchmark, "__path__", list(benchmark.__path__) + [str(bench)])
    mix = json.loads((bench / "mixes" / "tiny.json").read_text())
    mix["arrivals"] = {"kind": "benchmark.traffic_kinds.even:gaps",
                       "stretch": 1.0}
    (bench / "mixes" / "steady.json").write_text(json.dumps(mix))
    (bench / "cells" / "tiny.steady_tp4.json").write_text(
        json.dumps({"rate_rps": 5.0}))
    (bench / "layer_metrics" / "queue_wait_p50_ms.json").write_text(
        json.dumps({"reader": "percentile_of",
                    "args": {"series": "queue_wait_ms", "q": 50}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    before = copy.deepcopy(doc)
    doc["configs"].append({
        "name": "tiny-moe-tp4", "source": "benchmark/tests",
        "file": "benchmark/configs/tiny-moe-tp4.json", "reduced": [],
        "why": "four chips"})
    doc["workloads"].append({
        "name": "tiny.steady_tp4", "config": "tiny-moe-tp4",
        "traffic": "steady", "chips": 4, "why": "test"})
    doc["per_layer"].append({
        "name": "queue_wait_p50_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "scheduler",
        "moves": "ttft_p95_ms", "workloads": ["tiny.steady_tp4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = Spec(root)
    cell = spec.cell("tiny.steady_tp4")
    assert cell.chips == 4
    due = [a.due for a in loadgen.generate(cell.mix, 5.0, 1.6, 3, 128)]
    np.testing.assert_allclose(np.diff(due), 0.2)
    assert "queue_wait_p50_ms" in cell.layer_metrics
    # the new metric is this cell's only; the old cell does not get it
    assert [m["name"] for m in spec.cell("tiny.chat").per_layer] == \
        [m["name"] for m in before["per_layer"]]
    rc = cell_run.run_cell(spec, "tiny.steady_tp4", 2**31 + 3, 1.5, False,
                           t_start=time.perf_counter(), rehearse=True)
    out = capsys.readouterr().out
    line = last_line(out)
    assert rc == 0 and line["correct"] is True, out
    assert line["attempted"] == 8 and line["failed"] == 0
    first = json.loads(out.splitlines()[0])
    assert first["device"]["platform"] == "cpu"
    # a rehearsal line never carries a metric
    assert line["metrics"] == {} and line["rehearsal"] is True


def test_broken_timed_path_comes_out_not_correct(capsys):
    """The rest of a run (the look for a chip skipped), with a token
    altered where it is produced: ``correct`` must be false."""
    def flip_tokens(prog):
        eng, sample = prog.engine, prog.engine._sample
        vocab = eng.model.config.vocab

        def wrong(row_logits, req):
            tok = sample(row_logits, req)
            return (tok + 1) % vocab if len(req.generated) % 3 == 2 else tok
        eng._sample = wrong

    rc = cell_run.run_cell(Spec(DATA_ROOT), "tiny.chat", 11, 1.5, False,
                           t_start=time.perf_counter(), rehearse=True,
                           break_program=flip_tokens)
    out = capsys.readouterr().out
    line = last_line(out)
    assert rc == 0 and line["correct"] is False, out
    assert line["failed"] == 0          # every request still completed
    compared = next(json.loads(ln)["compared"] for ln in out.splitlines()
                    if ln.startswith('{"compared"'))
    assert not all(n["ok"] for n in compared["numbers"])


def test_run_refuses_to_measure_off_the_chip():
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dsmoe16b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stdout + p.stderr
    assert "not a TPU" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_benchmark_json_names_only_files_that_exist():
    spec = Spec(REPO)
    for name in spec.workloads():
        cell = spec.cell(name)
        assert cell.load["rate_rps"] > 0
        assert set(cell.layer_metrics) == {m["name"] for m in cell.per_layer}
        assert {m["moves"] for m in cell.per_layer} <= \
            {m["name"] for m in cell.end_to_end}
        assert cell.config["tolerance"]["limits"]
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9")


def test_benchmark_json_keeps_to_the_contract_s_limits():
    import re

    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda t: 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    assert 1 <= doc["run_seconds"] <= 51
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["why"]) and line(c["source"])
        assert c["file"].startswith("benchmark/") and (REPO / c["file"]).exists()
        assert all(name.match(k) for k in c["reduced"])
        assert c["reduced"] == json.loads(
            (REPO / c["file"]).read_text())["reduced"]
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) == len(doc["end_to_end"])
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
