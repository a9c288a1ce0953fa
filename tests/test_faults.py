"""Robustness subsystem: fault-plan engine, collective watchdog,
graceful degradation, bootstrap retry.

The acceptance properties (ISSUE 1):

* a single-peer stall on the ring allgather is DETECTED by the watchdog
  within its deadline and raises with rank/collective_id/semaphore
  diagnostics — no hang;
* the same ``FaultPlan`` seed reproduces the identical injected fault
  sequence across two runs, and delay-injected collectives stay
  bit-correct;
* a forced preflight failure on ``ag_gemm`` demotes to the XLA-native
  path and returns numerically identical results.

Tests that need the Pallas TPU-simulation interpreter are split from
those that run anywhere (the watchdog, stall gates and degradation
layer are host-side and engine-agnostic — on a jax without the
simulator they are exercised through the instrumented XLA fallback
engines instead).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from triton_distributed_tpu.runtime import (
    AllGatherMethod,
    Corrupt,
    Delay,
    FaultPlan,
    SignalFault,
    Stall,
    WatchdogTimeout,
    collective_watchdog,
    fault_plan,
)
from triton_distributed_tpu.runtime import faults, watchdog
from triton_distributed_tpu.utils import assert_allclose

#: tier-1 fast subset (ci/fast.sh): the fault-engine half of the robustness story
pytestmark = pytest.mark.fast


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """No plan/trip state may leak between tests (the trip record is
    deliberately sticky for the degradation probe)."""
    yield
    faults.set_fault_plan(None)
    watchdog.clear_trip()


# ------------------------------------------------------------- plan engine


class TestFaultPlan:
    def test_schedule_deterministic_under_seed(self):
        mk = lambda seed: FaultPlan(seed=seed, faults=(
            Delay(site="allgather", jitter=0.75, cycles=50_000),
            SignalFault(site="allgather", rank=2, kind="dup"),
            Corrupt(site="allgather", rank=5, word=7, value=9.0),
            Stall(site="allgather", rank=3),
        ))
        s1 = mk(7).schedule("allgather", n=8, steps=7)
        s2 = mk(7).schedule("allgather", n=8, steps=7)
        s3 = mk(8).schedule("allgather", n=8, steps=7)
        assert s1 == s2, "same seed must replay the identical schedule"
        assert s1 != s3, "a different seed must draw different delays"
        # structural faults are seed-independent but present
        kinds = {e[0] for e in s1}
        assert kinds == {"delay", "signal", "corrupt", "stall"}

    def test_site_and_rank_matching(self):
        plan = FaultPlan(seed=0, faults=(
            Delay(site="gemm_rs", rank=1, step=2, cycles=1000, jitter=0.0),
        ))
        assert plan.delay_cycles("gemm_rs", 2, 4) == (0, 1000, 0, 0)
        assert plan.delay_cycles("gemm_rs", 1, 4) == (0, 0, 0, 0)
        assert plan.delay_cycles("allgather", 2, 4) == (0, 0, 0, 0)
        assert plan.signal_factor("gemm_rs", 1) == 1  # no signal faults

    def test_signal_and_corrupt_queries(self):
        plan = FaultPlan(seed=0, faults=(
            SignalFault(site="*", rank=3, kind="drop"),
            Corrupt(site="all_to_all", rank=1, word=4, value=2.5),
        ))
        assert plan.signal_factor("reduce_scatter", 3) == 0
        assert plan.signal_factor("reduce_scatter", 2) == 1
        assert plan.corruption("all_to_all", 1) == (4, 2.5)
        assert plan.corruption("all_to_all", 2) is None

    def test_invalid_faults_rejected(self):
        with pytest.raises(TypeError):
            FaultPlan(faults=("not a fault",))
        with pytest.raises(ValueError):
            FaultPlan(faults=(SignalFault(kind="replay"),))

    def test_plan_participates_in_trace_cache_key(self):
        from triton_distributed_tpu.config import interp_key

        base = interp_key()
        with fault_plan(FaultPlan(seed=1)):
            armed = interp_key()
        assert armed != base, "activating a plan must invalidate builds"
        assert interp_key() == base, "deactivation must restore the key"

    def test_nested_plans_rejected(self):
        with fault_plan(FaultPlan(seed=1)):
            with pytest.raises(RuntimeError, match="already active"):
                with fault_plan(FaultPlan(seed=2)):
                    pass


# ------------------------------------------------------------- stall cap


class TestStallCap:
    def test_matrix_larger_than_cap_skips_excess(self, monkeypatch):
        """ISSUE satellite: a 5-stall matrix under
        ``max_concurrent_stalls=2`` holds at most 2 gates; the other 3
        stall_wait calls return immediately instead of parking worker
        threads (the 2-vCPU CI wedge the cap exists to prevent)."""
        monkeypatch.setenv("TDTPU_STALL_TIMEOUT", "20")
        plan = FaultPlan(
            seed=0,
            faults=tuple(Stall(site=f"cap{i}", rank=0) for i in range(5)),
            max_concurrent_stalls=2,
        )
        done: list = []

        def worker(i):
            faults.stall_wait(f"cap{i}", 0)
            done.append(i)

        with fault_plan(plan):
            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(5)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10
            while len(done) < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            # 3 of 5 skipped promptly; exactly the cap's worth held
            assert len(done) == 3, f"over-cap stalls did not skip: {done}"
            assert faults.held_stalls() == 2
            faults.release_stalls()
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads)
        assert faults.held_stalls() == 0, "held count must drain to zero"

    def test_uncapped_plan_holds_all(self, monkeypatch):
        """Without a cap every matching stall parks (the pre-cap
        behaviour chaos tests rely on)."""
        monkeypatch.setenv("TDTPU_STALL_TIMEOUT", "20")
        plan = FaultPlan(
            seed=0,
            faults=tuple(Stall(site=f"unc{i}", rank=0) for i in range(3)),
        )
        with fault_plan(plan):
            threads = [
                threading.Thread(
                    target=faults.stall_wait, args=(f"unc{i}", 0),
                    daemon=True,
                )
                for i in range(3)
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 10
            while faults.held_stalls() < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert faults.held_stalls() == 3
            faults.release_stalls()
            for t in threads:
                t.join(timeout=10)
        assert faults.held_stalls() == 0

    def test_cap_in_trace_key(self):
        """Changing the cap must invalidate cached kernel builds, same
        as any other plan field."""
        a = FaultPlan(seed=1, max_concurrent_stalls=2)
        b = FaultPlan(seed=1, max_concurrent_stalls=3)
        assert a.key() != b.key()


# ------------------------------------------------------------ plan replay


class TestParsePlan:
    """bench --faults replay: a nightly chaos line round-trips back
    into the plan that produced it."""

    def test_compact_format(self):
        plan = faults.parse_plan(
            "seed=7; Delay(site=allgather, rank=2, cycles=50000); "
            "Stall(site=ag_gemm, rank=3); max_concurrent_stalls=2"
        )
        assert plan == FaultPlan(
            seed=7,
            faults=(
                Delay(site="allgather", rank=2, cycles=50000),
                Stall(site="ag_gemm", rank=3),
            ),
            max_concurrent_stalls=2,
        )

    def test_json_format(self):
        plan = faults.parse_plan(
            '{"seed": 7, "faults": [{"kind": "Delay", "site": '
            '"allgather", "cycles": 50000}], "max_concurrent_stalls": 2}'
        )
        assert plan == FaultPlan(
            seed=7,
            faults=(Delay(site="allgather", cycles=50000),),
            max_concurrent_stalls=2,
        )

    def test_repr_roundtrip(self):
        """The compact format is the dataclass reprs joined by ';' —
        exactly what a nightly log line carries."""
        plan = FaultPlan(
            seed=11,
            faults=(
                SignalFault(site="allgather", rank=1, kind="drop"),
                Corrupt(site="gemm_rs", rank=2, word=3, value=5.0),
            ),
            max_concurrent_stalls=1,
        )
        line = "seed=11; " + "; ".join(
            repr(f) for f in plan.faults
        ) + "; max_concurrent_stalls=1"
        assert faults.parse_plan(line) == plan

    def test_unknown_fault_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse_plan("Frob(site=allgather)")

    def test_garbage_segment_rejected(self):
        with pytest.raises(ValueError, match="cannot parse"):
            faults.parse_plan("seed=1; what even is this")


# ---------------------------------------------------------------- watchdog


@pytest.mark.chaos
class TestWatchdog:
    def test_detects_single_peer_stall_and_raises(self, mesh8):
        """ISSUE acceptance: a stalled peer on the allgather is detected
        within the deadline and the raise carries rank, collective_id
        and semaphore expected-vs-observed diagnostics — the test
        completes (bounded) instead of wedging."""
        from triton_distributed_tpu.kernels import all_gather

        x = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
        plan = FaultPlan(seed=0, faults=(Stall(site="allgather", rank=3),))
        t0 = time.monotonic()
        with fault_plan(plan):
            with pytest.raises(WatchdogTimeout) as exc:
                with collective_watchdog(deadline=1.5):
                    y = all_gather(
                        x, mesh8, "x", method=AllGatherMethod.RING_1D, collective_id=2
                    )
                    np.asarray(y)       # force completion inside the guard
        elapsed = time.monotonic() - t0
        msg = str(exc.value)
        assert "collective_id=2" in msg
        assert "rank" in msg and "[3]" in msg          # the stalled rank
        assert "semaphore" in msg and "expected 7" in msg
        assert "FaultPlan" in msg and "Stall" in msg   # active plan dumped
        assert elapsed < 30, f"watchdog did not bound the stall: {elapsed}s"
        # the trip is sticky for the degradation probe until cleared
        assert watchdog.last_trip() is not None

    def test_stall_released_run_completes_correctly(self, mesh8):
        """After the watchdog releases the stall gate, the collective
        itself completes with correct data (the stall delays, it does
        not corrupt)."""
        from triton_distributed_tpu.kernels import all_gather

        x = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
        plan = FaultPlan(seed=0, faults=(Stall(site="allgather", rank=1),))
        got = {}
        with fault_plan(plan):
            try:
                with collective_watchdog(deadline=1.0):
                    got["y"] = np.asarray(all_gather(
                        x, mesh8, "x", method=AllGatherMethod.RING_1D, collective_id=2
                    ))
            except WatchdogTimeout:
                pass
        np.testing.assert_array_equal(got["y"], np.asarray(x))

    def test_clean_run_does_not_trip(self, mesh8):
        from triton_distributed_tpu.kernels import all_gather

        x = jnp.ones((64, 128), jnp.float32)
        with collective_watchdog(deadline=30.0):
            y = np.asarray(all_gather(x, mesh8, "x", method=AllGatherMethod.RING_1D))
        np.testing.assert_array_equal(y, np.ones((64, 128), np.float32))
        assert watchdog.last_trip() is None

    def test_double_arming_rejected(self):
        with collective_watchdog(deadline=30.0):
            with pytest.raises(RuntimeError, match="already armed"):
                with collective_watchdog(deadline=30.0):
                    pass

    def test_hostlevel_trip_without_any_engine(self):
        """Watchdog core without jax in the loop: heartbeats driven by
        hand, a stalled rank held on the plan gate from a worker thread.
        The monitor must trip, dump diagnostics and release the gate."""
        plan = FaultPlan(seed=0, faults=(Stall(site="unit", rank=2),))
        with fault_plan(plan):
            with pytest.raises(WatchdogTimeout) as exc:
                with collective_watchdog(deadline=0.3, poll=0.02):
                    for r in (0, 1):
                        watchdog._hb_enter("unit", 99, 4, r)
                        watchdog._hb_exit("unit", 99, 4, r, None)
                    t = threading.Thread(
                        target=watchdog._hb_enter, args=("unit", 99, 4, 2)
                    )
                    t.start()
                    t.join(timeout=20)
                    assert not t.is_alive(), "gate was never released"
        msg = str(exc.value)
        assert "'unit'" in msg and "collective_id=99" in msg
        assert "stalled at fault-plan entry gate" in msg and "[2]" in msg

    def test_stall_timeout_backstop_without_watchdog(self, monkeypatch):
        """A stall with NO watchdog armed must not wedge forever: the
        TDTPU_STALL_TIMEOUT backstop lets the rank proceed."""
        monkeypatch.setenv("TDTPU_STALL_TIMEOUT", "0.2")
        plan = FaultPlan(seed=0, faults=(Stall(site="unit2", rank=0),))
        t0 = time.monotonic()
        with fault_plan(plan):
            faults.stall_wait("unit2", 0)      # blocks ~0.2s, then returns
        assert 0.1 < time.monotonic() - t0 < 5.0


# ---------------------------------------------------- injection end-to-end


@pytest.mark.chaos
class TestInjectionEndToEnd:
    def test_delay_plan_bit_correct_and_deterministic(self, mesh8):
        """Seeded per-(rank, step) delays widen race windows without
        changing results, twice over (ISSUE acceptance: same seed →
        identical sequence; collectives stay bit-correct)."""
        from triton_distributed_tpu.kernels import all_gather

        x = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128)
        plan = FaultPlan(seed=11, faults=(
            Delay(site="allgather", jitter=0.9, cycles=80_000),
        ))
        outs = []
        for _ in range(2):
            with fault_plan(plan):
                outs.append(np.asarray(all_gather(
                    x, mesh8, "x", method=AllGatherMethod.RING_1D
                )))
        np.testing.assert_array_equal(outs[0], np.asarray(x))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_corruption_deterministic_under_seed(self, mesh8):
        """A corruption fault visibly lands (the result differs from
        truth at the targeted shard) and is bit-identical across two
        runs of the same plan — injected faults replay exactly."""
        from triton_distributed_tpu.kernels import all_gather

        x = jnp.ones((64, 128), jnp.float32)
        plan = FaultPlan(seed=3, faults=(
            Corrupt(site="allgather", rank=3, word=5, value=123.0),
        ))
        runs = []
        for _ in range(2):
            with fault_plan(plan):
                runs.append(np.asarray(all_gather(
                    x, mesh8, "x", method=AllGatherMethod.LL_SMALL
                )))
        assert not np.array_equal(runs[0], np.ones((64, 128), np.float32)), \
            "corruption fault never landed"
        # rank 3's shard head word is the corrupted one
        assert runs[0][3 * 8, 5] == 123.0
        np.testing.assert_array_equal(runs[0], runs[1])


# ------------------------------------------------------------- degradation


class TestGracefulDegradation:
    def test_ag_gemm_demotes_on_unhealthy_peer(self, mesh8):
        """ISSUE acceptance: a forced preflight failure demotes ag_gemm
        to the XLA-native path with allclose-identical results."""
        from triton_distributed_tpu.ops import (
            ag_gemm, ag_gemm_safe, create_ag_gemm_context, preflight,
        )

        a = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(2), (32, 128), jnp.float32)
        ctx = create_ag_gemm_context(mesh8, "x")
        healthy = np.asarray(ag_gemm(a, b, ctx), np.float32)

        plan = FaultPlan(seed=0, unhealthy_peers=(3,))
        with fault_plan(plan):
            reason = preflight(ctx, "ag_gemm", a, b)
            assert reason is not None and "unhealthy" in reason
            demoted = np.asarray(ag_gemm_safe(a, b, ctx), np.float32)
        assert_allclose(demoted, healthy, atol=1e-5, rtol=1e-5)
        # and the demotion is transient: plan cleared -> fused again
        assert preflight(ctx, "ag_gemm", a, b) is None

    def test_gemm_rs_demotes_on_watchdog_trip(self, mesh8):
        from triton_distributed_tpu.ops import (
            create_gemm_rs_context, gemm_rs, gemm_rs_safe, preflight,
        )

        a = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(4), (32, 128), jnp.float32)
        ctx = create_gemm_rs_context(mesh8, "x")
        healthy = np.asarray(gemm_rs(a, b, ctx), np.float32)

        watchdog._LAST_TRIP = "synthetic trip (test)"
        try:
            assert "watchdog" in preflight(ctx, "gemm_rs", a, b)
            tripped = np.asarray(gemm_rs_safe(a, b, ctx), np.float32)
        finally:
            watchdog.clear_trip()
        assert_allclose(tripped, healthy, atol=1e-5, rtol=1e-5)

    def test_ep_moe_transport_demotes_and_matches_dense(self, mesh8):
        """The fused MoE transport demotes to the XLA a2a under an
        unhealthy-peer plan and still matches the dense reference."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from conftest import dense_moe_ref
        from triton_distributed_tpu.ops import create_ep_moe_context, ep_moe
        from triton_distributed_tpu.ops.moe import _transport_degrade_reason

        n, E, topk, H, F, Mtok = 8, 16, 2, 128, 256, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (n * Mtok, H), jnp.float32)
        logits = jax.random.normal(jax.random.PRNGKey(1), (n * Mtok, E))
        w_up = jax.random.normal(jax.random.PRNGKey(2), (E, H, F), jnp.float32) * 0.05
        w_down = jax.random.normal(jax.random.PRNGKey(3), (E, F, H), jnp.float32) * 0.05
        ref = dense_moe_ref(x, logits, w_up, w_down, topk)
        sh = NamedSharding(mesh8, P("x"))
        ctx = create_ep_moe_context(
            mesh8, "x", num_experts=E, topk=topk, max_m=Mtok * topk,
            hidden=H, dtype=jnp.float32, transport="fused", block_m=8,
        )
        plan = FaultPlan(seed=0, unhealthy_peers=(5,))
        with fault_plan(plan):
            assert "unhealthy" in _transport_degrade_reason(ctx)
            out = ep_moe(
                jax.device_put(x, sh), jax.device_put(logits, sh),
                jax.device_put(w_up, sh), jax.device_put(w_down, sh), ctx,
            )
        assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


# --------------------------------------------------------- bootstrap retry


class TestBootstrapRetry:
    def test_retries_then_succeeds(self):
        from triton_distributed_tpu.runtime.bootstrap import (
            _initialize_with_retry,
        )

        calls, sleeps = [], []

        def flaky(**kw):
            calls.append(kw)
            if len(calls) < 3:
                raise RuntimeError("connection refused")

        _initialize_with_retry(
            "coord:1234", 4, 1, retries=5, backoff=0.5, cap=8.0,
            sleep=sleeps.append, initialize=flaky,
        )
        assert len(calls) == 3
        assert calls[0] == dict(
            coordinator_address="coord:1234", num_processes=4, process_id=1
        )
        # exponential envelope with ±50% jitter: attempt k in
        # [0.5, 1.5] * base * 2^k
        assert len(sleeps) == 2
        for k, s in enumerate(sleeps):
            assert 0.5 * 0.5 * 2 ** k <= s <= 1.5 * 0.5 * 2 ** k

    def test_backoff_capped(self):
        from triton_distributed_tpu.runtime.bootstrap import (
            _initialize_with_retry,
        )

        sleeps = []

        def always_fail(**kw):
            raise RuntimeError("nope")

        with pytest.raises(RuntimeError):
            _initialize_with_retry(
                "c:1", 2, 0, retries=8, backoff=1.0, cap=2.0,
                sleep=sleeps.append, initialize=always_fail,
            )
        assert len(sleeps) == 7
        assert all(s <= 2.0 * 1.5 for s in sleeps)

    def test_terminal_error_names_coordinator(self):
        from triton_distributed_tpu.runtime.bootstrap import (
            _initialize_with_retry,
        )

        def always_fail(**kw):
            raise ConnectionError("rendezvous timed out")

        with pytest.raises(RuntimeError) as exc:
            _initialize_with_retry(
                "10.0.0.9:8476", 16, 3, retries=2, backoff=0.0, cap=0.0,
                sleep=lambda s: None, initialize=always_fail,
            )
        msg = str(exc.value)
        assert "10.0.0.9:8476" in msg
        assert "2 attempt(s)" in msg
        assert "num_processes=16" in msg and "process_id=3" in msg
        assert "rendezvous timed out" in msg
        assert isinstance(exc.value.__cause__, ConnectionError)


# ----------------------------------------------------------- legacy chaos


def test_legacy_chaos_delay_untouched_by_engine(monkeypatch):
    """Without an active plan, chaos_delay keeps the reference-style
    global-boolean behaviour (and stays a no-op when disabled)."""
    from triton_distributed_tpu.config import config
    from triton_distributed_tpu.utils.testing import chaos_delay

    monkeypatch.setattr(config, "chaos_delay", False)
    chaos_delay(site="allgather", step=0, me=None, n=8)  # host no-op
