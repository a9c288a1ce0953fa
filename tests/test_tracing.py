"""The engine's step, measured from inside (ISSUE 26): the six host
spans and per-step lists of ``ServingEngine.step``, the device scopes of
the jitted serving step, and the request stamps with their counters.
And start-up (ISSUE 39): the ``setup.*`` spans, the log of every program
built under a span, ``EngineStats.programs_built``.

All on the CPU at a tiny size: what is opened, when, how often and
under which name — never a time (times come from the chip).
"""

import gc
import json
import os
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring     # the listeners' getters are not public
from jax.sharding import Mesh

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.serving import (
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    SpeculativeEngine,
    make_drafter,
    poisson_trace,
)
from conftest import drained
from triton_distributed_tpu import tracing
from triton_distributed_tpu.serving.engine import PHASES

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.harness import metrics, program  # noqa: E402

pytestmark = pytest.mark.fast

CFG = dict(
    vocab=128, n_layers=2, hidden=64, ffn=128,
    n_heads=4, n_kv_heads=2, head_dim=16,
    dtype=jnp.float32, param_dtype=jnp.float32,
)
#: the three block kinds of ``serving_step``: dense FFN, EP experts
#: (ops/moe.py) and the non-EP expert branch
BLOCKS = {
    "dense": {},
    "ep": dict(moe="ep", moe_layers=(1,), num_experts=4, topk=2),
    "tp": dict(moe="tp", moe_layers=(1,), num_experts=4, topk=2),
}
#: the device scopes each block kind must show (``embed`` ... ``lm_head``
#: are every step's; layer 0 is dense in all three)
COMMON = {"embed", "attn_proj", "kv_append", "attn", "dense_ffn", "lm_head"}
SCOPES = {
    "dense": COMMON,
    "ep": COMMON | {"moe_route", "moe_dispatch", "moe_gemm", "moe_combine"},
    "tp": COMMON | {"moe_gemm"},
}
SPANS = [f"engine.{p}" for p in PHASES]
ECFG = EngineConfig(slots=4, token_budget=48, chunk=16, page=8, npages=32)


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("tp",))


def _model(mesh, kind):
    model = Transformer(TransformerConfig(**CFG, **BLOCKS[kind]), mesh,
                        "tp", ())
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def models(mesh1):
    return {kind: _model(mesh1, kind) for kind in BLOCKS}


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what is
    opened, with its ``step``, and that it is closed again."""

    log: list = []
    open_now: list = []
    #: every ``setup.*`` span as opened: (name, attributes, the spans
    #: open round it, outermost first)
    setup: list = []

    def __init__(self, name, **kw):
        self.name, self.step, self.kw = name, kw.get("step"), kw

    def __enter__(self):
        if self.name.startswith("setup."):
            # set-up has a list of its own: ``log`` is the engine's
            # phases, as before there was a ``setup.*`` span
            _Annotation.setup.append(
                (self.name, self.kw, tuple(_Annotation.open_now)))
        else:
            _Annotation.log.append((self.name, self.step))
        _Annotation.open_now.append(self.name)

    def __exit__(self, *exc):
        assert _Annotation.open_now.pop() == self.name
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Annotation.log, _Annotation.open_now, _Annotation.setup = [], [], []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    return _Annotation


def _per_step(log):
    """The recorded spans cut into one list per ``step()`` call."""
    calls = []
    for name, step in log:
        if name == "engine.admit":
            calls.append([])
        calls[-1].append((name, step))
    return calls


def _lists(stats):
    return {p: getattr(stats, f"{p}_times") for p in PHASES}


# ------------------------------------------------- the six per-step lists

@pytest.mark.parametrize("order", ["ahead", "drained"])
@pytest.mark.parametrize("kind", ["dense", "ep"])
def test_each_list_grows_by_one_per_device_step_and_sums_to_the_wall(
        models, kind, order):
    """One entry a device step in every list, appended in the call that
    RETIRES the step. A step's six entries are its own phases: retired
    in the call that launched it (``drained``) they lie inside that
    call; launched ahead, its admit .. dispatch ran in one call and its
    fetch and advance in the next, and only the totals meet."""
    model, params = models[kind]
    eng = (ServingEngine if order == "ahead"
           else drained(ServingEngine))(model, params, ECFG)
    # nothing submitted: admit and assemble run, the device does not
    eng.step()
    assert all(v == [] for v in _lists(eng.stats).values())
    assert eng.stats.step_times == []
    trace = poisson_trace(7, 6, 1.0, 5, 30, 3, 6, 128)
    eng.submit_trace(trace)
    walls, all_walls, device_steps = [], [], 0
    while not eng.idle:
        before = len(eng.stats.step_times)
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        all_walls.append(wall)
        ran = len(eng.stats.step_times) - before
        assert ran in (0, 1)
        device_steps += ran
        # every list has exactly one entry per step that ran the device
        assert {len(v) for v in _lists(eng.stats).values()} == {device_steps}
        if ran:
            walls.append(wall)
    assert device_steps == len(walls) > 5
    assert eng.stats.lookahead_steps == (
        device_steps - 1 if order == "ahead" else 0)
    lists = _lists(eng.stats)
    sums = [sum(lists[p][i] for p in PHASES) for i in range(device_steps)]
    assert all(v >= 0.0 for vs in lists.values() for v in vs)
    if order == "drained":
        # the spans lie inside step(): never more than its wall time,
        # and (the typical step) within a few % of it
        assert all(s <= w for s, w in zip(sums, walls))
        assert np.median(np.asarray(sums) / np.asarray(walls)) > 0.9
    else:
        # launched ahead: the first call retires nothing and the last
        # launches nothing, so the calls' walls hold every span once
        assert len(all_walls) == device_steps + 1
        assert 0.9 * sum(all_walls) < sum(sums) <= sum(all_walls)
    # step_times keeps its meaning: uploads + dispatch + fetch
    for i, dt in enumerate(eng.stats.step_times):
        assert dt == pytest.approx(
            lists["upload"][i] + lists["dispatch"][i] + lists["fetch"][i],
            rel=1e-12)


# ------------------------------------------------------ the six host spans

@pytest.mark.parametrize("order", ["ahead", "drained"])
def test_six_annotations_open_once_per_device_step_in_order(
        models, annotations, order):
    model, params = models["dense"]
    eng = (ServingEngine if order == "ahead"
           else drained(ServingEngine))(model, params, ECFG)
    eng.step()                                  # an empty step
    eng.submit_trace(poisson_trace(3, 4, 1.0, 5, 20, 2, 4, 128))
    while not eng.idle:
        eng.step()
    assert not annotations.open_now
    calls = _per_step(annotations.log)
    assert calls[0] == [("engine.admit", 0), ("engine.assemble", 0)]
    assert [c[0][1] for c in calls] == list(range(len(calls)))
    full = [c for c in calls if len(c) > 2]
    n = len(eng.stats.step_times)
    assert n > 3
    # the six spans of a DEVICE STEP share its number, each opened once
    by_step = {}
    for call in calls:
        for name, step in call:
            by_step.setdefault(step, []).append(name)
    stepped = [k for k, names in by_step.items() if len(names) > 2]
    assert len(stepped) == n
    if order == "drained":
        assert len(full) == n
        for call in calls:
            # one step number on every span of a call, counting up
            assert len({step for _, step in call}) == 1
            assert [n for n, _ in call] in (SPANS[:2], SPANS)
        return
    # launched ahead: a call opens admit .. dispatch of ITS step, then
    # fetch and advance of the step BEFORE; the first call of a busy
    # stretch retires nothing, the last launches nothing
    assert len(full) == n + 1
    assert [name for name, _ in full[0]] == SPANS[:4]
    assert [name for name, _ in full[-1]] == SPANS[:2] + SPANS[4:]
    for call in full[1:-1]:
        assert [name for name, _ in call] == SPANS
    for call in full[1:]:
        k = call[0][1]
        assert [step for _, step in call[-2:]] == [k - 1, k - 1]
        assert {step for _, step in call[:-2]} == {k}
    launched = set(stepped)
    assert all(sorted(by_step[k]) == sorted(SPANS) for k in launched)


def test_a_degraded_step_re_runs_upload_and_dispatch_into_one_entry(
        mesh1, models, annotations, monkeypatch):
    import triton_distributed_tpu.kernels.ragged_paged_attention as rpa

    # a model of its own: a step program traced with the real kernel
    # would be served from the jit cache and never meet the mock
    model = Transformer(TransformerConfig(**CFG), mesh1, "tp", ())
    params = models["dense"][1]

    def boom(*a, **k):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setattr(rpa, "ragged_paged_attention", boom)
    eng = ServingEngine(
        model, params,
        EngineConfig(slots=2, token_budget=32, chunk=8, page=8, npages=16))
    req = Request(rid=0, prompt=np.arange(9, dtype=np.int32), max_new=3,
                  arrival=0.0)
    stats = eng.run([req], max_steps=50)
    assert stats.degraded and req.done
    calls = _per_step(annotations.log)
    # the failing launch dies inside engine.dispatch; the XLA twin's
    # re-run opens upload and dispatch again, then fetch and advance
    assert [n for n, _ in calls[0]] == [
        "engine.admit", "engine.assemble", "engine.upload",
        "engine.dispatch", "engine.upload", "engine.dispatch",
        "engine.fetch", "engine.advance"]
    assert all([n for n, _ in c] == SPANS for c in calls[1:]
               if len(c) > 2)
    # still ONE entry per device step, the two runs summed into it
    assert {len(v) for v in _lists(stats).values()} == {
        len(stats.step_times)}


def test_the_spans_through_the_speculative_engine(models, annotations):
    model, params = models["dense"]
    eng = SpeculativeEngine(model, params, ECFG, spec_k=2,
                            drafter=make_drafter("ngram"),
                            use_pallas=False)
    trace = poisson_trace(5, 4, 1.0, 5, 20, 6, 10, 128)
    stats = eng.run(trace, max_steps=300)
    assert stats.completed == 4
    full = [c for c in _per_step(annotations.log) if len(c) > 2]
    assert len(full) == len(stats.step_times)
    assert all([n for n, _ in c] == SPANS for c in full)
    assert {len(v) for v in _lists(stats).values()} == {len(full)}
    # stamped in step(), so the override of _advance_row is covered
    assert all(r.t_submit < r.t_admit < r.t_first for r in trace)


def test_the_spans_through_both_roles_of_a_disaggregated_engine(
        annotations):
    devs = jax.devices()
    mesh_p = Mesh(np.asarray(devs[:1]), ("tp",))
    mesh_d = Mesh(np.asarray(devs[1:2]), ("tp",))
    hybrid = Mesh(np.asarray(devs[:2]).reshape(2, 1), ("dcn", "tp"))
    cfg = TransformerConfig(**CFG, kv_quant="int8")
    mp = Transformer(cfg, mesh_p, "tp", ())
    md = Transformer(cfg, mesh_d, "tp", ())
    params = mp.init(jax.random.PRNGKey(0))
    pp = jax.tree.map(jax.device_put, params, mp.shardings())
    pd = jax.tree.map(jax.device_put, params, md.shardings())
    eng = DisaggregatedEngine(
        mp, pp, md, pd, ECFG, hybrid_mesh=hybrid, dcn_axis="dcn",
        transport="dcn", ship_delay_steps=1)
    trace = poisson_trace(7, 4, 1.0, 5, 20, 3, 5, 128)
    stats = eng.run(trace, max_ticks=400)
    assert stats.completed == 4 and stats.ships > 0
    full = [c for c in _per_step(annotations.log) if len(c) > 2]
    assert all([n for n, _ in c] == SPANS for c in full)
    assert len(full) == (len(stats.prefill.step_times)
                         + len(stats.decode.step_times))
    for role in (stats.prefill, stats.decode):
        assert {len(v) for v in _lists(role).values()} == {
            len(role.step_times)}
    # the prefill role admits and emits the first token; the decode
    # role takes the row by reserve_shipped and counts neither again
    assert stats.prefill.admissions == stats.prefill.first_tokens == 4
    assert stats.decode.admissions == stats.decode.first_tokens == 0


# ------------------------------------------------------ the device scopes

def lowered_scope_components(eng) -> set:
    """Every ``/``-separated component of every operation name in the
    step program this engine would launch now (its lowered text with
    debug info)."""
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        auto_block_q,
    )

    eng._admit()
    *arrays, batched, _ = eng._assemble()
    assert batched
    text = eng._step_jit().lower(*eng._step_args(
        tuple(arrays), auto_block_q(1, eng._g))).as_text(debug_info=True)
    parts = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        parts.update(path.split("/"))
    return parts


@pytest.mark.parametrize("kind", list(BLOCKS))
def test_the_lowered_step_holds_every_scope_as_a_whole_component(
        models, kind):
    model, params = models[kind]
    eng = ServingEngine(model, params, ECFG, use_pallas=False)
    eng.submit(Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                       max_new=2))
    parts = lowered_scope_components(eng)
    assert SCOPES[kind] <= parts, SCOPES[kind] - parts
    # a block kind the model does not have leaves no scope behind
    assert not (SCOPES["ep"] - SCOPES[kind]) & parts


# ------------------------------------------- request stamps and counters

def test_a_request_submitted_to_a_full_engine_waits_then_is_stamped(models):
    model, params = models["dense"]
    eng = ServingEngine(
        model, params,
        EngineConfig(slots=2, token_budget=32, chunk=8, page=8, npages=16))
    reqs = [Request(rid=i, prompt=(np.arange(9) + i).astype(np.int32),
                    max_new=3) for i in range(4)]
    for r in reqs:
        assert r.t_submit is r.t_admit is r.t_first is None
        eng.submit(r)
    eng.step()
    # two slots: two admitted in the first step, two still waiting
    assert [r.t_admit is not None for r in reqs] == [True, True, False,
                                                     False]
    eng.run(max_steps=100)
    assert all(r.done for r in reqs)
    assert all(r.t_submit < r.t_admit < r.t_first for r in reqs)
    # the late two waited for a slot, through whole steps
    assert min(r.t_admit - r.t_submit for r in reqs[2:]) > max(
        r.t_admit - r.t_submit for r in reqs[:2])
    st = eng.stats
    assert st.admissions == st.first_tokens == 4
    assert st.queue_wait_s == pytest.approx(
        sum(r.t_admit - r.t_submit for r in reqs))
    assert st.first_token_s == pytest.approx(
        sum(r.t_first - r.t_admit for r in reqs))


def test_an_evicted_and_readmitted_request_is_counted_once(models):
    model, params = models["dense"]
    eng = ServingEngine(
        model, params,
        EngineConfig(slots=4, token_budget=48, chunk=16, page=8,
                     npages=12))
    trace = poisson_trace(7, 8, 1.0, 5, 30, 3, 6, 128)
    for r in trace:
        r.arrival = 0.0
    eng.submit_trace(trace)
    stamps = {}
    while not eng.idle:
        eng.step()
        for r in trace:
            if r.t_admit is not None:
                # the first admission's stamp never moves
                assert stamps.setdefault(r.rid, r.t_admit) == r.t_admit
    st = eng.stats
    assert st.completed == 8 and st.evictions > 0
    assert any(r.evictions for r in trace)
    assert st.admissions == st.first_tokens == 8
    assert st.queue_wait_s == pytest.approx(
        sum(r.t_admit - r.t_submit for r in trace))
    assert st.first_token_s == pytest.approx(
        sum(r.t_first - r.t_admit for r in trace))


# ----------------------------------------------- profiler on, profiler off

def test_token_streams_are_identical_with_the_profiler_on_and_off(
        models, tmp_path):
    from jax.profiler import ProfileData

    from triton_distributed_tpu.tools import group_profile

    model, params = models["ep"]

    def serve():
        eng = ServingEngine(model, params, ECFG)
        trace = poisson_trace(11, 5, 1.0, 5, 24, 3, 6, 128)
        eng.run(trace, max_steps=300)
        return [tuple(r.generated) for r in trace], eng.stats

    plain, _ = serve()
    with group_profile(tmp_path) as where:
        traced, stats = serve()
    assert traced == plain and all(len(t) >= 3 for t in plain)
    # and the spans really are in the profile, on the host plane, once
    # per device step each
    files = list(where.rglob("*.xplane.pb"))
    assert len(files) == 1
    seen = {}
    for plane in ProfileData.from_file(str(files[0])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    seen[ev.name] = seen.get(ev.name, 0) + 1
    assert set(seen) == set(SPANS)
    assert {seen[n] for n in SPANS[2:]} == {len(stats.step_times)}


# ------------------------------------------------- start-up (ISSUE 39)

BENCH = ROOT / "benchmark"
SETUP_METRICS = sorted(p.name[:-len(".json")] for p in
                       (BENCH / "layer_metrics").glob("setup_*.json"))


def _log_since(mark):
    """The process-wide log's entries made since ``mark`` (the lengths
    ``_mark()`` took): the suite shares one process."""
    log = tracing.startup_log()
    return {k: log[k][mark[k]:] for k in log}


def _mark():
    return {k: len(v) for k, v in tracing.startup_log().items()}


def _serve(eng, first_rid, lengths=(3, 9)):
    """One request alone per prompt length (its chunk sets the rung),
    each decoding a few tokens: the shape of the benchmark's warm-up."""
    for i, n in enumerate(lengths):
        req = Request(rid=first_rid + i, max_new=3,
                      prompt=np.arange(n, dtype=np.int32))
        eng.run([req], max_steps=64)
        assert req.done


@pytest.mark.parametrize("kind", ["dense", "ep"])
def test_the_setup_spans_open_once_a_construction_children_inside(
        mesh1, models, annotations, kind):
    model, params = _model(mesh1, kind)
    assert annotations.setup == [("setup.model", {}, ())]
    ServingEngine(model, params, ECFG, use_pallas=False)
    assert not annotations.open_now
    assert [(n, held) for n, _, held in annotations.setup[1:]] == [
        ("setup.engine", ()),
        ("setup.state", ("setup.engine",)),
        ("setup.workspaces", ("setup.engine",))]
    # a second construction: each once more, never a fifth name
    ServingEngine(model, params, ECFG, use_pallas=False)
    assert [n for n, _, _ in annotations.setup[4:]] == [
        "setup.engine", "setup.state", "setup.workspaces"]
    assert annotations.log == []        # no phase of a step was opened


@pytest.mark.parametrize("order", ["ahead", "drained"])
def test_setup_program_opens_once_a_program_key_inside_its_dispatch(
        models, annotations, order):
    model, params = models["dense"]
    eng = (ServingEngine if order == "ahead"
           else drained(ServingEngine))(model, params, ECFG,
                                        use_pallas=False)
    mark = _mark()
    _serve(eng, 0)
    built = [(kw, held) for n, kw, held in annotations.setup
             if n == "setup.program"]
    rungs = eng._rungs()
    assert len(rungs) == 2
    assert [(kw["block_q"], kw["width"]) for kw, _ in built] == [
        (b, w) for b in rungs for w in eng._widths(b)]
    for kw, held in built:
        # nested in the dispatch of the step it names
        assert held == ("engine.dispatch",) and kw["step"] >= 0
    assert eng.stats.programs_built == len(rungs)
    # a second pass over the same rungs opens none and builds nothing
    _serve(eng, 10)
    assert sum(n == "setup.program" for n, _, _ in annotations.setup) \
        == len(rungs) == eng.stats.programs_built
    new = _log_since(mark)
    spans = [s for s in new["spans"] if s["name"] == "setup.program"]
    assert [(s["block_q"], s["width"]) for s in spans] == [
        (b, w) for b in rungs for w in eng._widths(b)]
    assert eng.stats.program_build_s == sum(s["seconds"] for s in spans)
    # the six phases are as before (admit .. advance, in order)
    assert all([n for n, _ in call] in (SPANS[:2], SPANS[:4], SPANS,
                                        SPANS[:2] + SPANS[4:])
               for call in _per_step(annotations.log))


def test_programs_built_is_one_per_key_the_harness_warm_up_visits(mesh1):
    model, params = _model(mesh1, "ep")
    eng = ServingEngine(
        model, params, EngineConfig(slots=4, token_budget=64, chunk=32,
                                    page=8, npages=64),
        use_pallas=False, propagate_failures=True)
    keys, run = set(), eng._run_device

    def spy(arrays, block_q):
        keys.add((block_q, len(arrays[0])))
        return run(arrays, block_q)

    eng._run_device = spy
    mark = _mark()
    warm = program.warm_up(eng, CFG["vocab"])
    # the warm-up sends a request per block of the old ladder; the
    # engine launches at two rungs, one width each at this size
    assert len(warm["rungs"]) == 3 and eng._rungs() == [8, 32]
    assert keys == {(b, w) for b in eng._rungs() for w in eng._widths(b)}
    assert len(keys) == 2
    assert eng.stats.programs_built == len(keys)
    new = _log_since(mark)
    assert {(s["block_q"], s["width"]) for s in new["spans"]} == keys
    steps = [p for p in new["programs"] if p["span"] == "setup.program"
             and p["fun_name"] == "jit(step)"]
    assert [(p["block_q"], p["width"]) for p in steps] == sorted(keys)
    # what the benchmark's window must read: a second warm-up builds
    # nothing, so both counters are still over it
    before = program.stats_snapshot(eng)["numbers"]
    program.warm_up(eng, CFG["vocab"])
    after = program.stats_snapshot(eng)["numbers"]
    assert after["programs_built"] == before["programs_built"] == len(keys)
    assert after["program_build_s"] == before["program_build_s"]


def test_a_build_under_a_key_seen_before_is_a_rebuild_not_a_step_program(
        mesh1, models):
    model, params = models["dense"]
    eng = ServingEngine(model, params, ECFG, use_pallas=False)
    _serve(eng, 0)
    built, mark = eng.stats.programs_built, _mark()
    rebuilt = tracing.summary()["rebuilt_programs"]
    # the same keys, through a jit that has not built them: a twin
    # model's (its ``_serving_jit`` is its own)
    eng.model = _model(mesh1, "dense")[0]
    _serve(eng, 10)
    assert eng.stats.programs_built == built
    new = _log_since(mark)
    assert [s for s in new["spans"] if s["name"] == "setup.program"] == []
    again = [p for p in new["programs"] if p["fun_name"] == "jit(step)"]
    assert len(again) >= 2
    assert {p["span"] for p in again} == {"engine.dispatch"}
    assert all(p["block_q"] is None and p["step"] is not None
               for p in again)
    assert tracing.summary()["rebuilt_programs"] == rebuilt + len(again)


def test_a_nested_jit_is_booked_once_by_containment():
    seen = []

    def listen(event, duration, **kw):
        if event.endswith("jaxpr_trace_duration"):
            seen.append((kw["fun_name"], duration))

    @jax.jit
    def inner_of_the_nest(x):
        return x * 2

    @jax.jit
    def outer_of_the_nest(x):
        return inner_of_the_nest(x) + inner_of_the_nest(x + 1)

    tracing.install()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        mark = _mark()
        with tracing.Span("setup.test_nest"):
            outer_of_the_nest(jnp.ones((3,), jnp.float32))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    traced = dict(seen)
    assert {"inner_of_the_nest", "outer_of_the_nest"} <= set(traced)
    new = _log_since(mark)["programs"]
    nest = [p for p in new if "of_the_nest" in p["fun_name"]]
    # ONE program: the inner jit was traced inside the outer one and
    # lowered with it; the record's trace is the outer's alone
    assert [p["fun_name"] for p in nest] == ["jit(outer_of_the_nest)"]
    assert nest[0]["trace_s"] == traced["outer_of_the_nest"]
    assert nest[0]["span"] == "setup.test_nest"
    assert nest[0]["inside"] is None
    assert tracing._open.traces == {} and tracing._open.stack == []


@pytest.mark.parametrize("cache", ["hit", "miss", "off"])
def test_a_record_reads_its_cache_state_from_the_events_after_its_lowering(
        cache):
    """JAX's own order, replayed: trace, lowering, then inside the
    backend's compile the cache's nameless events."""
    say, dur = jax.monitoring.record_event, \
        jax.monitoring.record_event_duration_secs
    core, pc = "/jax/core/compile/", "/jax/compilation_cache/"
    tracing.install()
    mark = _mark()
    with tracing.Span("setup.test_cache"):
        dur(core + "jaxpr_trace_duration", 0.5, fun_name="replayed")
        # JAX traces small things between a trace and its lowering:
        # the record takes the trace of ITS name
        dur(core + "jaxpr_trace_duration", 0.0625, fun_name="a_cast")
        dur(core + "jaxpr_to_mlir_module_duration", 0.25,
            fun_name="jit(replayed)")
        if cache != "off":
            say(pc + "compile_requests_use_cache")
        if cache == "hit":
            say(pc + "cache_hits")
            dur(pc + "compile_time_saved_sec", 9.0)
            dur(pc + "cache_retrieval_time_sec", 0.125)
        if cache == "miss":
            say(pc + "cache_misses")
        dur(core + "backend_compile_duration", 1.0,
            fun_name="jit(replayed)")
    # outside every span: no record, and its cache events are nobody's
    dur(core + "jaxpr_to_mlir_module_duration", 0.25, fun_name="jit(late)")
    say(pc + "cache_misses")
    new = _log_since(mark)
    assert [p["fun_name"] for p in new["programs"]] == ["jit(replayed)"]
    rec = new["programs"][0]
    assert rec["cache"] == cache
    assert (rec["trace_s"], rec["lower_s"]) == (0.5, 0.25)
    # the backend's second holds the retrieval: never added twice
    assert rec["compile_s"] + rec["retrieval_s"] == 1.0
    assert rec["retrieval_s"] == (0.125 if cache == "hit" else 0.0)
    summed = tracing.summary(new)
    assert summed["cache_misses"] == (cache == "miss")
    assert summed["step_programs"] == 0 == summed["rebuilt_programs"]


def test_collector_pauses_go_to_the_innermost_open_setup_span_only():
    tracing.install()
    mark = _mark()
    acc = {"dispatch": 0.0}
    with tracing.Span("setup.test_outer"):
        with tracing.Span("setup.test_inner"):
            # a phase of a step is no set-up: it collects nothing
            with tracing.Span("engine.dispatch", acc, "dispatch"):
                gc.collect()
    assert tracing._setup_open == 0
    gc.collect()                          # no span open: one comparison
    spans = {s["name"]: s for s in _log_since(mark)["spans"]}
    assert set(spans) == {"setup.test_outer", "setup.test_inner"}
    assert spans["setup.test_inner"]["gc_s"] > 0.0
    assert spans["setup.test_outer"]["gc_s"] == 0.0
    assert acc["dispatch"] > 0.0


def test_a_second_model_and_engine_register_no_second_listener(
        mesh1, models):
    def ours():
        return (
            sum(f is tracing._on_duration for f in
                monitoring.get_event_duration_listeners()),
            sum(f is tracing._on_event for f in
                monitoring.get_event_listeners()),
            sum(f is tracing._on_gc for f in gc.callbacks))

    assert ours() == (1, 1, 1)            # ``models`` built the first
    model, params = _model(mesh1, "dense")
    ServingEngine(model, params, ECFG)
    ServingEngine(model, params, ECFG)
    assert ours() == (1, 1, 1)


def test_importing_the_package_registers_no_listener():
    code = (
        "import gc\n"
        "from jax._src import monitoring\n"
        "import triton_distributed_tpu.models, "
        "triton_distributed_tpu.serving, triton_distributed_tpu.tracing\n"
        "assert not monitoring.get_event_duration_listeners()\n"
        "assert not monitoring.get_event_listeners()\n"
        "assert triton_distributed_tpu.tracing._on_gc not in gc.callbacks\n")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_benchmarks_readers_read_a_tiny_engines_start_up(mesh1):
    """Every ``setup_*`` metric file through the benchmark's own
    ``read_layer_metric``, on this process's log; the sums of
    ``benchmark/README-pr39.md`` hold."""
    model, params = _model(mesh1, "ep")
    eng = ServingEngine(model, params, ECFG, use_pallas=False)
    program.warm_up(eng, CFG["vocab"])
    assert len(SETUP_METRICS) == 13
    read = {}
    for name in SETUP_METRICS:
        definition = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        read[name] = metrics.read_layer_metric({}, definition)
        assert read[name] is not None and read[name] >= 0, name
    # the log is the process's: at least this engine's programs
    assert read["setup_step_programs"] >= eng.stats.programs_built == 2
    assert (read["setup_trace_s"] + read["setup_lower_s"]
            + read["setup_compile_s"]) <= read["setup_step_programs_s"]
    assert read["setup_state_s"] + read["setup_workspaces_s"] \
        <= read["setup_engine_s"]
    assert read["setup_step_program_s_max"] <= read["setup_step_programs_s"]
    # and they agree with the program's own sums, name for name
    summed = tracing.summary()
    assert {f"setup_{k}": v for k, v in summed.items()} == read
    line = tracing.ready_line()
    assert line.startswith("ready: model ") and "step programs" in line
