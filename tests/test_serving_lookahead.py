"""PR 34: the engine keeps ONE device step in flight. ``step()`` admits,
assembles, uploads and dispatches step k and only then fetches step
k - 1's token ids and advances its rows; a decode row's token of the
step in flight stays on the device (``_merge_tokens``).

What is held here, all on the CPU at tiny sizes: (a) the token streams
are those of the same engine with its launch-ahead question forced to
"no" (``conftest.drained``) and the plain reference's, on every kind of
model the benchmark serves; (b) how often the order engages
(``EngineStats.lookahead_steps``) and that every engine that must have
a step's result on the host first serves the streams it served before;
(c) what is public after ``step()`` describes retired steps only, every
per-step list aligned; (d) a non-finite logits row raises at the fetch
of its step and delivers nothing.
"""

import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import drained
from oracle import greedy_tokens

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import test_sala  # noqa: E402
import test_window_share  # noqa: E402
from benchmark.harness import program, weights  # noqa: E402
from benchmark.models import exaone_moe, minicpm_sala  # noqa: E402
from test_serving_step import CFG, _model  # noqa: E402
from triton_distributed_tpu.serving import (  # noqa: E402
    DisaggregatedEngine,
    EngineConfig,
    Request,
    ServingEngine,
    SpeculativeEngine,
    make_drafter,
)
from triton_distributed_tpu.serving.engine import (  # noqa: E402
    PHASES,
    _greedy_jit,
    _merge_tokens,
)

pytestmark = pytest.mark.fast

#: three slots under five requests: chunks of 16 beside decode rows,
#: decode-only steps between them, a request that ends at its first
#: token (``max_new`` 1), rows that end at different steps, and two
#: requests admitted into slots that a completion freed
ENGINE = EngineConfig(slots=3, token_budget=64, chunk=16, page=8, npages=64)
#: (prompt length, max_new, arrival in steps)
TRAFFIC = ((21, 5, 0.0), (5, 3, 0.0), (40, 4, 2.0), (3, 6, 5.0),
           (17, 1, 6.0))
KINDS = ("dense", "ep", "window", "sala")


def _requests(vocab, traffic=TRAFFIC, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new=m, arrival=a,
                    prompt=rng.integers(0, vocab, (n,)).astype(np.int32))
            for i, (n, m, a) in enumerate(traffic)]


@functools.lru_cache(maxsize=None)
def _kind(kind):
    """``(model, params, engine config, oracle)`` of one kind of model;
    ``oracle(req)`` holds a finished request to the plain reference."""
    if kind in ("dense", "ep"):
        model, params = _model(moe="none" if kind == "dense" else "ep")

        def oracle(req):
            assert req.generated == greedy_tokens(
                model, params, req.prompt, req.max_new), req.rid

        return model, params, ENGINE, oracle
    # ``forward`` refuses these by name: the benchmark's plain reference
    if kind == "window":
        mod, ref, ecfg = test_window_share, exaone_moe, dataclasses.replace(
            test_window_share.ENGINE, slots=3)
    else:
        mod, ref, ecfg = test_sala, minicpm_sala, dataclasses.replace(
            test_sala.ENGINE, slots=3)
    cfg = mod.tiny_config()
    model, sizes = mod.one_chip_model(cfg), mod.sizes_of(cfg)
    params = weights.make_params(
        ref.param_plan(sizes), 3300000034, cfg.param_dtype,
        model.shardings())

    def oracle(req):
        seq = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)])
        rows = np.arange(len(req.prompt) - 1, len(seq))
        logits = np.asarray(ref.logits_at(params, sizes, seq, rows))
        gaps = logits.max(-1) - logits[np.arange(len(rows)), req.generated]
        assert float(gaps.max()) <= 1e-4, (req.rid, gaps)

    return model, params, ecfg, oracle


def _run(engine_cls, kind="dense", ecfg=None, traffic=TRAFFIC, **kw):
    model, params, cfg, _ = _kind(kind)
    eng = engine_cls(model, params, ecfg or cfg, use_pallas=False,
                     propagate_failures=True, **kw)
    reqs = _requests(model.config.vocab, traffic)
    stats = eng.run(reqs, max_steps=400)
    assert stats.completed == len(reqs) and not stats.failures
    assert all(len(r.generated) == r.max_new for r in reqs)
    return eng, reqs


def _streams(reqs):
    return [r.generated for r in reqs]


# ------------------------------------------- (a) the same tokens, any order

@pytest.mark.parametrize("kind", KINDS)
def test_streams_are_the_drained_order_s_and_the_reference_s(kind):
    ahead, reqs = _run(ServingEngine, kind)
    sync, want = _run(drained(ServingEngine), kind)
    assert _streams(reqs) == _streams(want)
    oracle = _kind(kind)[3]
    for req in reqs:
        oracle(req)
    # the order engaged on all but the first step of a busy stretch,
    # and never in the drained engine
    n = len(ahead.stats.step_times)
    assert 0.8 * n <= ahead.stats.lookahead_steps < n
    assert sync.stats.lookahead_steps == 0
    # the same work: every prompt token prefilled once, every token
    # generated once, in steps of the same widths
    for k in ("prefill_tokens", "generated_tokens", "completed",
              "evictions"):
        assert getattr(ahead.stats, k) == getattr(sync.stats, k), k
    assert sum(ahead.stats.step_tokens) == sum(sync.stats.step_tokens)


def test_the_merge_takes_a_slot_s_token_from_the_device_and_clamps():
    tokens = jnp.asarray([7, 0, 9, 0, 0], jnp.int32)
    src = jnp.asarray([-1, 2, -1, 0, 1], jnp.int32)
    ids = jnp.asarray([5, -1, 3], jnp.int32)    # slot 1: a non-finite row
    assert np.asarray(_merge_tokens(tokens, src, ids)).tolist() == [
        7, 3, 9, 5, 0]


def test_a_mixed_step_after_the_warm_up_lowers_no_program():
    """The harness's warm-up serves one request alone per rung; the
    merge program in front of the step is compiled there at every
    width, also the wide one, where the warm-up never had a token on
    the device to merge: a window's chunk beside decode rows whose
    tokens are in flight lowers nothing."""
    model, params, ecfg, _ = _kind("dense")
    eng = ServingEngine(model, params, ecfg, use_pallas=False,
                        propagate_failures=True)
    widths = {w for b in eng._rungs() for w in eng._widths(b)}
    wide = max(widths)
    assert len(widths) == 2
    program.warm_up(eng, model.config.vocab)
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k:
        name.endswith("jaxpr_to_mlir_module_duration")
        and lowered.append(name))
    reqs = _requests(model.config.vocab)
    for r in reqs:
        r.arrival += eng.step_count
    widths, run = set(), eng._run_device

    def spy(arrays, block_q):
        src = eng._token_src
        widths.add((len(arrays[0]), bool((src >= 0).any()),
                    bool((arrays[4] > 1).any())))
        return run(arrays, block_q)

    eng._run_device = spy
    assert eng.run(reqs, max_steps=200).completed >= len(reqs)
    # a wide step that merged a token in flight beside a chunk
    assert any(w == wide and merged and chunk
               for w, merged, chunk in widths), widths
    assert not lowered, lowered


def test_the_warm_up_lowers_each_rung_s_step_program_once():
    """An engine's FIRST step merges its tokens like every other (ids
    of zeros, no slot named): the step program is handed one kind of
    ``tokens`` a rung (a committed array off the merge, never the
    host's uncommitted upload beside it), so the warm-up compiles each
    rung once, not its first rung twice."""
    model, params = _model()                # a step jit of its own
    eng = ServingEngine(model, params, ENGINE, use_pallas=False,
                        propagate_failures=True)
    steps = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, fun_name=None, **k:
        name.endswith("jaxpr_to_mlir_module_duration")
        and fun_name == "jit(step)" and steps.append(name))
    program.warm_up(eng, model.config.vocab)
    assert len(steps) == len(eng._rungs()) > 1
    n = eng.stats.completed
    assert eng.run(_requests(model.config.vocab, seed=4)).completed == n + 5
    assert len(steps) == len(eng._rungs())


# ---------------------------------------------- (b) how often it engages

def test_one_busy_stretch_launches_every_step_but_its_first_ahead():
    eng, _ = _run(ServingEngine, traffic=((21, 6, 0.0), (5, 9, 0.0)))
    n = len(eng.stats.step_times)
    assert n > 8 and eng.stats.lookahead_steps == n - 1
    # one more call of step() than device steps: the last retires only
    assert eng.step_count == n + 1


#: what the parent commit (PR 33) served for ``TRAFFIC`` at temperature
#: 0.8, top_k 16, seed 5 on the dense model: the seeded streams
SAMPLED = [[107, 76, 28, 15, 114], [87, 126, 126], [26, 100, 82, 100],
           [97, 117, 92, 69, 117, 96], [44]]


def test_a_sampling_engine_never_launches_ahead_and_keeps_its_streams():
    eng, reqs = _run(ServingEngine, ecfg=dataclasses.replace(
        ENGINE, temperature=0.8, top_k=16, seed=5))
    assert eng._greedy is None and eng.stats.lookahead_steps == 0
    assert _streams(reqs) == SAMPLED
    assert eng.step_count == len(eng.stats.step_times) == 11


@pytest.mark.parametrize("tree", [0, 4], ids=["linear", "tree"])
def test_a_speculative_engine_never_launches_ahead(tree):
    eng, reqs = _run(SpeculativeEngine, spec_k=2, spec_tree=tree,
                     drafter=make_drafter("tree" if tree else "ngram"))
    assert eng.stats.lookahead_steps == 0 and eng.stats.spec_rows > 0
    assert _streams(reqs) == _streams(_run(ServingEngine)[1])


def test_an_eviction_is_chosen_with_nothing_in_flight():
    """A pool too small for the residents: ``_launch_ahead`` sees that
    ``ensure_pages`` may have to evict and retires the step in flight
    first, the victim replays from its committed tokens, and the order
    resumes once the pool has room."""
    ecfg = dataclasses.replace(ENGINE, npages=9)
    traffic = ((30, 12, 0.0), (28, 12, 0.0), (26, 12, 0.0))
    eng, reqs = _run(ServingEngine, ecfg=ecfg, traffic=traffic)
    sync, want = _run(drained(ServingEngine), ecfg=ecfg, traffic=traffic)
    st = eng.stats
    assert st.evictions == sync.stats.evictions > 0
    assert 0 < st.lookahead_steps < len(st.step_times) - 1
    assert _streams(reqs) == _streams(want)
    oracle = _kind("dense")[3]
    for req in reqs:
        oracle(req)


@pytest.mark.parametrize("share", [False, True],
                         ids=["prefix_cache", "prefix_share"])
def test_a_prefix_cache_is_read_and_written_with_nothing_in_flight(share):
    """The registry is written when a step that freezes a page retires
    and read by an admission and by the dedup: the order drains around
    both (a step in flight that freezes a page, a request waiting or
    due) and engages on the decode steps between, with the hits, the
    folded pages and the streams of the drained order."""
    ecfg = dataclasses.replace(ENGINE, prefix_cache=True,
                               prefix_share=share)
    model = _kind("dense")[0]
    rng = np.random.default_rng(2)
    motif = rng.integers(0, model.config.vocab, (24,)).astype(np.int32)

    def reqs():
        return [Request(rid=i, max_new=14, arrival=float(i),
                        prompt=np.concatenate(
                            [motif, np.full((3 + i,), i + 1, np.int32)]))
                for i in range(4)]

    served = []
    for cls in (ServingEngine, drained(ServingEngine)):
        eng = cls(model, _kind("dense")[1], ecfg, use_pallas=False,
                  propagate_failures=True)
        frozen = []
        retire = eng._retire

        def spy(flight, eng=eng, retire=retire, frozen=frozen):
            # nothing else is in flight when a page is published
            frozen.append((flight.freezes, eng._flight is None
                           or eng._flight is flight))
            return retire(flight)

        eng._retire = spy
        rs = reqs()
        assert eng.run(rs, max_steps=200).completed == 4
        assert eng.stats.prefix_hits > 0
        assert all(alone for freezes, alone in frozen if freezes)
        assert any(freezes for freezes, _ in frozen)
        served.append((_streams(rs), eng.stats.prefix_hits,
                       eng.stats.deduped_pages))
        n = len(eng.stats.step_times)
        if cls is ServingEngine:
            assert 0 < eng.stats.lookahead_steps < n - 1, (
                eng.stats.lookahead_steps, n)
        else:
            assert eng.stats.lookahead_steps == 0
    assert served[0] == served[1]
    oracle = _kind("dense")[3]
    for req in rs:
        oracle(req)


@pytest.mark.parametrize("role", ["full", "prefill_only"])
def test_a_completion_hook_is_called_from_a_retirement(role):
    """``on_complete`` is handed a request by the retirement that
    completes it, with all its tokens, while the step launched
    meanwhile holds no row of that slot (the launch-side view knows
    what a ``prefill_only`` role completes at: the first token), so the
    hook may keep the slot; whoever then MOVES it drains first
    (``ServingEngine.drain``)."""
    seen = []

    def hook(req, slot):
        seen.append((req.rid, len(req.generated), slot, eng._flight
                     is None or slot not in eng._flight.takes))
        return True

    model, params, ecfg, _ = _kind("dense")
    ecfg = dataclasses.replace(ecfg, prefill_only=role == "prefill_only")
    served = []
    for cls in (ServingEngine, drained(ServingEngine)):
        seen.clear()
        eng = cls(model, params, ecfg, use_pallas=False,
                  propagate_failures=True, on_complete=hook)
        reqs = _requests(model.config.vocab)
        assert eng.run(reqs, max_steps=400).completed == len(reqs)
        want = {r.rid: 1 if ecfg.prefill_only else r.max_new for r in reqs}
        assert {rid: n for rid, n, _, _ in seen} == want
        assert all(apart for *_, apart in seen)
        assert (eng.stats.lookahead_steps > 0) == (cls is ServingEngine)
        served.append(_streams(reqs))
    assert served[0] == served[1]


def test_both_roles_of_a_disaggregated_engine_keep_the_synchronous_order():
    """The hook's parking, the ships' reservations and commits and a
    failover move both roles' slots between their steps: a tick drains
    each role after its step (the contract of ``ServingEngine.drain``)."""
    devs = jax.devices()
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(**CFG)
    mp = Transformer(cfg, Mesh(np.asarray(devs[:1]), ("tp",)), "tp", ())
    md = Transformer(cfg, Mesh(np.asarray(devs[1:2]), ("tp",)), "tp", ())
    params = mp.init(jax.random.PRNGKey(0))
    pp = jax.tree.map(jax.device_put, params, mp.shardings())
    pd = jax.tree.map(jax.device_put, params, md.shardings())
    eng = DisaggregatedEngine(mp, pp, md, pd, ENGINE, transport="xla",
                              ship_delay_steps=1, use_pallas=False)
    reqs = _requests(cfg.vocab)
    stats = eng.run(reqs, max_ticks=400)
    assert stats.completed == len(reqs) and stats.ships > 0
    assert stats.prefill.lookahead_steps == stats.decode.lookahead_steps == 0
    assert eng.prefill._flight is None and eng.decode._flight is None
    for req in reqs:
        assert req.generated == greedy_tokens(
            mp, pp, req.prompt, req.max_new), req.rid


def test_a_fleet_drains_a_replica_after_each_of_its_steps():
    """The fleet reads and moves its replicas' slots between their steps
    (routing, failover, drain, migration): ``Replica.step`` retires what
    it launched."""
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer, TransformerConfig
    from triton_distributed_tpu.serving.fleet import ServingFleet

    cfg, engines = TransformerConfig(**CFG), []
    for dev in jax.devices()[:2]:
        model = Transformer(cfg, Mesh(np.asarray([dev]), ("tp",)), "tp", ())
        params = jax.tree.map(jax.device_put,
                              model.init(jax.random.PRNGKey(0)),
                              model.shardings())
        engines.append(ServingEngine(model, params, ENGINE,
                                     use_pallas=False))
    fleet = ServingFleet(engines, seed=1)
    reqs = _requests(cfg.vocab)
    fleet.submit_trace(reqs)
    for _ in range(400):
        if fleet.idle:
            break
        fleet.tick()
        assert all(e._flight is None for e in engines)
    assert all(r.done for r in reqs)
    assert all(e.stats.lookahead_steps == 0 and e.stats.completed
               for e in engines)
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid


def test_an_admission_that_forces_an_eviction_on_one_cp_shard_drains_first():
    """A cp pool promises an admission's first chunk out of the SUM of
    its shards and claims each page on the shard that owns its index:
    every sequence's first pages on shard 0. With shard 0 nearly full,
    shard 1 empty, slot 0 freed by a completion and two residents
    decoding in the slots above, a late arrival is admitted into slot 0
    and ``ensure_pages`` evicts for it: ``_launch_ahead`` counts the
    admission's pages against the fullest shard and retires the step in
    flight first."""
    import test_longcontext

    from triton_distributed_tpu.models import Transformer

    model = Transformer(test_longcontext._tcfg(),
                        test_longcontext._mesh_cp_only(), tp_axis="x",
                        cp_axis="cpx")
    params = model.init(jax.random.PRNGKey(0))
    ecfg = EngineConfig(slots=3, token_budget=48, chunk=16, page=8,
                        npages=5)
    traffic = ((5, 2, 0.0), (10, 12, 0.0), (11, 12, 0.0), (12, 4, 5.0))
    served = []
    for cls in (ServingEngine, drained(ServingEngine)):
        eng = cls(model, params, ecfg, use_pallas=False,
                  propagate_failures=True)
        assert eng.pool.cp == 2
        reqs = _requests(model.config.vocab, traffic)
        short, free = [], []
        admit = eng._admit

        def spy(eng=eng, admit=admit, short=short, free=free):
            late = [r for r in (*eng.waiting, *eng.pending)
                    if r.rid == 3 and r.arrival <= eng.step_count]
            if late and not short:
                # the case: the sum would hold the first chunk, shard 0
                # cannot, and nothing is in flight when it is admitted
                short.append((eng.pool.available, eng.pool.headroom,
                              eng._pages_held(12), eng._flight))
                free.append(eng.slot_req.index(None))
            return admit()

        eng._admit = spy
        assert eng.run(reqs, max_steps=200).completed == 4
        (available, headroom, first, flight), = short
        assert headroom < first <= available and flight is None
        assert free == [0] and eng.stats.evictions > 0
        served.append((_streams(reqs), eng.stats.evictions))
        n = len(eng.stats.step_times)
        assert (0 < eng.stats.lookahead_steps < n - 1) \
            == (cls is ServingEngine)
    assert served[0] == served[1]
    for req in reqs:
        assert req.generated == greedy_tokens(
            model, params, req.prompt, req.max_new), req.rid


class _FailsAtFetch:
    """A step's result whose failure surfaces as it comes down, as an
    asynchronous device error does."""

    def __array__(self, *a, **k):
        raise RuntimeError("device step failed")


@pytest.mark.parametrize("propagate", [False, True])
def test_a_failure_at_the_fetch_of_a_step_launched_ahead_is_booked(
        propagate):
    """The fetch of a step launched ahead runs in the next call, outside
    the guarded run: its failure cannot be re-run (the pools went with
    the program), but it is booked in ``stats.failures`` and told to the
    health ledger before it is raised, so every later step is drained
    and on the twin."""
    from triton_distributed_tpu.runtime.health import PeerState

    model, params, ecfg, _ = _kind("dense")
    eng = ServingEngine(model, params, ecfg, use_pallas=True,
                        propagate_failures=propagate)
    reqs = _requests(model.config.vocab)
    eng.submit_trace(reqs)
    for _ in range(3):
        eng.step()
    failed = eng._flight
    assert failed.ahead and not eng.stats.failures
    cursors = [r.cursor for r in reqs]
    failed.out = _FailsAtFetch()
    with pytest.raises(RuntimeError, match="device step failed"):
        eng.step()
    assert eng.stats.failures == [{
        "step": failed.step, "site": "serving_step",
        "error": "RuntimeError: device step failed"}]
    # nothing of the failed step was delivered
    assert [r.cursor for r in reqs] == cursors
    assert len(eng.stats.step_times) == failed.step
    state = eng.health.state(eng.health_peer)
    if propagate:
        assert state is PeerState.HEALTHY and eng.use_pallas
    else:
        assert state is PeerState.UNHEALTHY
        assert eng.stats.degraded and not eng.use_pallas
        assert not eng._launch_ahead()


def test_a_path_the_ledger_does_not_call_healthy_is_not_launched_ahead():
    """A probing or degraded step re-runs the batch it just launched on
    a failure, and the ledger hears of a clean step once its result is
    down: from the first signal on, every step is retired where it was
    launched."""
    model, params, ecfg, oracle = _kind("dense")
    eng = ServingEngine(model, params, ecfg, use_pallas=False)
    reqs = _requests(model.config.vocab)
    eng.submit_trace(reqs)
    for _ in range(4):
        eng.step()
    # steps 1, 2 and 3 were launched ahead; 3 is still in flight
    assert eng.stats.lookahead_steps == 2 and eng._flight.ahead
    eng.health.record("kernel_error", eng.health_peer, step=eng.step_count)
    while not eng.idle:
        eng.step()
        assert eng._flight is None
    assert eng.stats.lookahead_steps == 3 < len(eng.stats.step_times) - 4
    for req in reqs:
        oracle(req)


# ------------------------------- (c) public state describes retired steps

@pytest.mark.parametrize("kind", ["dense", "sala"])
def test_what_is_public_after_a_step_describes_retired_steps_only(kind):
    model, params, ecfg, _ = _kind(kind)
    eng = ServingEngine(model, params, ecfg, use_pallas=False,
                        propagate_failures=True)
    reqs = _requests(model.config.vocab)
    eng.submit_trace(reqs)
    lists = [f.name for f in dataclasses.fields(eng.stats)
             if f.name.endswith("_times") or f.name in (
                 "step_tokens", "step_generated")]
    assert {f"{p}_times" for p in PHASES} < set(lists)
    in_flight_seen = 0
    for _ in range(400):
        if eng.idle:
            break
        n0 = len(eng.stats.step_times)
        eng.step()
        st = eng.stats
        n = len(st.step_times)
        assert n - n0 in (0, 1)             # a call retires one step
        assert {len(getattr(st, k)) for k in lists} == {n}
        assert sum(st.step_generated) == sum(len(r.generated) for r in reqs)
        for r in reqs:
            # cursor and generated are of ONE retired step: everything
            # but the newest token is in the pool, and no placeholder
            # stands in ``seq`` for a token still on the device
            if r.generated:
                assert r.cursor == len(r.prompt) + len(r.generated) - 1 \
                    or r.done
                assert len(r.seq) == len(r.prompt) + len(r.generated)
                assert r.t_first is not None
            else:
                assert r.cursor <= len(r.prompt) and r.t_first is None
            assert r.done == (len(r.generated) == r.max_new)
            # a slot is freed by the retirement that completes its row
            assert any(q is r for q in eng.slot_req) == (
                r.t_admit is not None and not r.done)
        if eng._flight is not None:
            in_flight_seen += 1
            assert not eng.idle
    assert in_flight_seen > 5 and eng.idle and eng._flight is None
    assert all(r.done for r in reqs)


def test_run_and_a_step_with_nothing_to_launch_retire_the_step_in_flight():
    model, params, ecfg, _ = _kind("dense")
    eng = ServingEngine(model, params, ecfg, use_pallas=False)
    reqs = _requests(model.config.vocab)
    # run() cut short: it ends with nothing in flight, and every step
    # it dispatched is in the lists
    stats = eng.run(reqs, max_steps=5)
    assert eng._flight is None and eng.step_count == 5
    assert len(stats.step_times) == 5 and not eng.idle
    eng.run()
    assert eng.idle and all(r.done for r in reqs)
    # one request alone: the step after its last token launches
    # nothing, retires what is in flight and reports that step
    one = Request(rid=9, prompt=np.arange(5, dtype=np.int32), max_new=2,
                  arrival=float(eng.step_count))
    eng.submit(one)
    reports = []
    while not eng.idle:
        reports.append(dict(eng.step()))
        assert (eng._flight is None) == eng.idle
    assert one.done and len(reports) == 3
    # (the first call launched a step and retired none: its report)
    assert "ms" not in reports[0] and reports[0]["batched"] == 1
    assert reports[1]["step"] + 1 == reports[2]["step"]
    assert reports[2]["generated"] == 1 and "ms" in reports[2]


# ----------------------------------------------- (d) a non-finite row

def test_a_non_finite_row_raises_at_its_fetch_and_delivers_nothing():
    model, params, ecfg, _ = _kind("dense")
    eng = ServingEngine(model, params, ecfg, use_pallas=False,
                        propagate_failures=True)
    a, b = (Request(rid=i, prompt=np.arange(5 + i, dtype=np.int32),
                    max_new=8, arrival=0.0) for i in range(2))
    eng.submit_trace([a, b])
    poisoned = []

    def greedy(logits):
        if eng.step_count == 3:             # slot 1's row of step 3
            poisoned.append(len(b.generated))
            logits = logits.at[1, 7].set(jnp.nan)
        return _greedy_jit(logits)

    eng._greedy = greedy
    for _ in range(4):
        eng.step()                          # step 3 is launched: no raise
    assert poisoned == [2] and len(b.generated) == 3
    with pytest.raises(FloatingPointError, match="non-finite logits for "
                       "request 1"):
        eng.step()                          # ... and fetched by the next
    # slot 0's row of that step was delivered, nothing of slot 1's
    assert len(a.generated) == 4 and len(b.generated) == 3
    # the step launched meanwhile fed slot 1 a clamped 0; whoever
    # steps on never sees a token of the poisoned row
    for _ in range(3):
        eng.step()
    assert len(b.generated) == 3 and not b.done
    assert len(a.generated) > 4
