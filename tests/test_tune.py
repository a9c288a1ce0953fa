"""Autotuner + perf-model tests.

Mirrors the reference's autotuner contract (autotuner.py:97-253):
thunk-level benching, failed-config skip, caching, consensus.
"""

import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.tune import (
    TPU_SPECS,
    contextual_autotune,
    detect_spec,
    estimate_all_gather_ms,
    estimate_all_to_all_ms,
    estimate_gemm_ms,
    estimate_reduce_scatter_ms,
    overlap_efficiency,
)


class TestAutotuner:
    def test_picks_and_caches(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        bench_calls = []

        @contextual_autotune(configs=[{"s": 2.0}, {"s": 3.0}])
        def op(x, *, s):
            bench_calls.append(s)
            return x * s

        x = jnp.ones((4, 4))
        y1 = op(x)
        n_bench = len(bench_calls)
        assert n_bench >= 2                     # both configs benched
        y2 = op(x)                              # cache hit: exactly 1 call
        assert len(bench_calls) == n_bench + 1
        assert float(y1[0, 0]) == float(y2[0, 0])
        log = (tmp_path / "process-0.jsonl").read_text()
        assert "best" in log

    def test_failed_config_skipped(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))

        @contextual_autotune(configs=[{"ok": False}, {"ok": True}])
        def op(x, *, ok):
            if not ok:
                raise ValueError("broken config")
            return x + 1

        out = op(jnp.zeros((2,)))
        np.testing.assert_allclose(np.asarray(out), 1.0)

    def test_all_configs_failing_raises(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))

        @contextual_autotune(configs=[{"a": 1}, {"a": 2}])
        def op(x, *, a):
            raise ValueError("nope")

        with pytest.raises(RuntimeError, match="every config failed"):
            op(jnp.zeros((2,)))

    def test_distinct_shapes_tuned_separately(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        seen = []

        @contextual_autotune(configs=[{"s": 1.0}])
        def op(x, *, s):
            seen.append(x.shape)
            return x

        op(jnp.ones((2, 2)))
        op(jnp.ones((4, 4)))
        log = (tmp_path / "process-0.jsonl").read_text().strip().splitlines()
        assert len([l for l in log if "best" in l]) == 2


class TestPairedBench:
    """VERDICT r3 #8: the paired (snake-order + within-round
    normalization) ranking must stay stable under a monotonic
    interference ramp that flips the naive independent ranking."""

    def test_paired_ranking_survives_drift(self, tmp_path, monkeypatch):
        import triton_distributed_tpu.tune.autotuner as at

        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        # true costs: config B is 2% FASTER; background interference
        # ramps +5% per measurement window — larger than the real gap
        true_ms = {1: 1.00, 2: 0.98}
        step = [0]
        schedule = []

        def fake_perf(fn, warmup=0, iters=1):
            out = fn()       # the thunk returns its config's `a`
            a = int(out)
            ms = true_ms[a] * (1.0 + 0.05 * step[0])
            schedule.append((a, ms))
            step[0] += 1
            return out, ms

        monkeypatch.setattr(at, "perf_func", fake_perf)

        tuner = at.ContextualAutoTuner(
            lambda *, a: a, [{"a": 1}, {"a": 2}],
            name="paired", rounds=2, warmup=0, iters=1, log=False,
            persist=False,
        )
        best = tuner.pick()
        assert best == {"a": 2}, f"paired ranking picked {best}"

        # the same scripted measurements mislead the INDEPENDENT
        # (forward-order, median-of-absolute) ranking: A is measured
        # first in every round, so the ramp penalizes B systematically
        fwd = {1: [], 2: []}
        t = 0
        for _ in range(2):
            for a in (1, 2):
                fwd[a].append(true_ms[a] * (1.0 + 0.05 * t))
                t += 1
        assert np.median(fwd[1]) < np.median(fwd[2]), (
            "drift scenario no longer flips the independent ranking — "
            "strengthen the ramp"
        )


class TestWinnerValidation:
    """Persisted winners are TTL'd and re-validated against the recorded
    runner-up (VERDICT r2 #8): a noise-artifact winner heals instead of
    persisting forever."""

    @staticmethod
    def _sleep_op():
        import time as _t

        def op(x, *, d):
            _t.sleep(d)
            return x

        return op

    def test_stale_wrong_winner_recovers(self, tmp_path, monkeypatch):
        from triton_distributed_tpu.tune.autotuner import (
            ContextualAutoTuner,
            _shape_key,
        )

        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        fast, slow = {"d": 0.0}, {"d": 0.05}
        tuner = ContextualAutoTuner(
            self._sleep_op(), [fast, slow], name="heal", warmup=0, iters=1,
        )
        x = jnp.ones((2,))
        key = ("heal", _shape_key((x,), {}))
        # inject the SLOW config as the persisted winner (a noisy sweep's
        # artifact), fast one recorded as runner-up
        tuner._disk_put(key, slow, fast)
        assert tuner.pick(x) == fast            # re-validated → re-tuned
        assert tuner._disk_get(key)["best"] == fast   # store healed

    def test_valid_winner_accepted_without_full_sweep(self, tmp_path, monkeypatch):
        from triton_distributed_tpu.tune.autotuner import (
            ContextualAutoTuner,
            _shape_key,
        )

        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        fast, slow = {"d": 0.0}, {"d": 0.05}
        calls = []

        def op(x, *, d):
            calls.append(d)
            import time as _t

            _t.sleep(d)
            return x

        tuner = ContextualAutoTuner(op, [fast, slow], name="ok",
                                    warmup=0, iters=1)
        x = jnp.ones((2,))
        tuner._disk_put(("ok", _shape_key((x,), {})), fast, slow)
        assert tuner.pick(x) == fast
        # validation benched exactly winner+runner once each (no sweep,
        # which here would be indistinguishable by count — assert order:
        # best first, runner second, nothing else)
        assert calls == [0.0, 0.05]

    def test_ttl_expiry_rebenches(self, tmp_path, monkeypatch):
        from triton_distributed_tpu.tune.autotuner import (
            ContextualAutoTuner,
            _shape_key,
        )

        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        tuner = ContextualAutoTuner(
            self._sleep_op(), [{"d": 0.0}, {"d": 0.02}], name="ttl",
            warmup=0, iters=1, ttl_s=0,
        )
        x = jnp.ones((2,))
        key = ("ttl", _shape_key((x,), {}))
        tuner._disk_put(key, {"d": 0.02}, {"d": 0.0})
        assert tuner._disk_get(key) is None     # ttl 0 → instantly stale
        assert tuner.pick(x) == {"d": 0.0}      # full re-bench found fast

    def test_legacy_v1_entry_rebenches(self, tmp_path, monkeypatch):
        import json as _json

        from triton_distributed_tpu.tune.autotuner import (
            ContextualAutoTuner,
            _shape_key,
        )

        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        tuner = ContextualAutoTuner(
            self._sleep_op(), [{"d": 0.0}, {"d": 0.02}], name="v1",
            warmup=0, iters=1,
        )
        x = jnp.ones((2,))
        key = ("v1", _shape_key((x,), {}))
        # hand-write a pre-v2 store entry (bare config dict)
        (tmp_path / "cache.json").write_text(
            _json.dumps({repr(key): {"d": 0.02}})
        )
        assert tuner._disk_get(key) is None     # schema drift → miss
        assert tuner.pick(x) == {"d": 0.0}
        assert tuner._disk_get(key)["v"] == 2   # store upgraded


class TestPerfModel:
    def test_specs_and_detection(self):
        assert set(TPU_SPECS) == {"v4", "v5e", "v5p", "v6e"}
        # the CPU test mesh stands in for the AOT target, by name
        assert detect_spec() is TPU_SPECS["v5e"]

        class Dev:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        assert detect_spec(Dev("TPU v5 lite")) is TPU_SPECS["v5e"]
        assert detect_spec(Dev("TPU v5p")) is TPU_SPECS["v5p"]
        # an accelerator with no row is an error, never a borrowed row
        with pytest.raises(ValueError, match="no TpuSpec row"):
            detect_spec(Dev("TPU v9 mega"))

    def test_estimates_scale_sanely(self):
        spec = TPU_SPECS["v5e"]
        small = estimate_gemm_ms(1024, 1024, 1024, spec)
        big = estimate_gemm_ms(8192, 8192, 8192, spec)
        assert big > small * 100        # cubic flops growth dominates
        ag = estimate_all_gather_ms(2**20, 8, spec)
        rs = estimate_reduce_scatter_ms(2**20, 8, spec)
        assert ag == rs > 0
        a2a = estimate_all_to_all_ms(2**20, 8, spec)
        assert 0 < a2a < ag             # torus bisection beats ring wire time
        assert overlap_efficiency(2.0, 1.0) == 1.0
        assert overlap_efficiency(1.0, 2.0) == 0.5

    def test_migrate_vs_reprefill_pricing(self):
        """The fleet's migration gate (ISSUE-13): shipping pages over a
        fast DCN beats recomputing the prefix; a slow DCN flips the
        verdict while the re-prefill side (DCN-independent) holds."""
        from triton_distributed_tpu.tune.perf_model import (
            TpuSpec,
            migrate_vs_reprefill_ms,
        )

        kw = dict(page=8, hkv=2, g=2, d=16, hidden=64, n_layers=2)
        fast = TpuSpec(name="fast-dcn", bf16_tflops=200.0,
                       hbm_gbps=800.0, ici_gbps=50.0, ici_links=4,
                       dcn_gbps=100.0)
        w, r = migrate_vs_reprefill_ms(4, spec=fast, **kw)
        assert 0 < w < r
        slow = TpuSpec(name="slow-dcn", bf16_tflops=200.0,
                       hbm_gbps=800.0, ici_gbps=50.0, ici_links=4,
                       dcn_gbps=1e-9)
        w2, r2 = migrate_vs_reprefill_ms(4, spec=slow, **kw)
        assert w2 > r2
        assert r2 == pytest.approx(r)
        # both sides grow with the prefix length
        w3, r3 = migrate_vs_reprefill_ms(8, spec=fast, **kw)
        assert w3 > w and r3 > r


class TestTunedEngineSelection:
    """method=None consults the measured tuner with a persistent on-disk
    cache (VERDICT r1 #7): miss → bench+store, hit → no bench."""

    def _env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TDTPU_AUTOTUNE", "1")
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))

    def test_ag_gemm_tuned_and_disk_cached(self, mesh8, tmp_path, monkeypatch):
        import jax

        import importlib

        mod = importlib.import_module("triton_distributed_tpu.kernels.ag_gemm")
        from triton_distributed_tpu.tune.autotuner import ContextualAutoTuner

        self._env(tmp_path, monkeypatch)
        mod._engine_tuner.cache_clear()
        a = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 128))
        ref = np.asarray(jnp.dot(a, b))
        out = mod.ag_gemm(a, b, mesh8, "x")            # miss → bench + store
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)
        store = json.loads((tmp_path / "cache.json").read_text())
        assert any("ag_gemm" in k for k in store)

        # fresh tuner (new process simulation): must hit the DISK cache —
        # a full sweep is forbidden. Winner re-validation (the cheap
        # 2-config re-bench) is pinned to "accept" here: on this noisy
        # time-shared host a legitimate rejection would trigger a full
        # sweep and flake the test; the validation logic itself is
        # covered deterministically by TestWinnerValidation.
        mod._engine_tuner.cache_clear()
        validated = []
        monkeypatch.setattr(
            ContextualAutoTuner, "_validate_entry",
            lambda self, entry, args, kwargs: (
                validated.append(entry), entry["best"]
            )[1],
        )
        monkeypatch.setattr(
            ContextualAutoTuner, "_bench",
            lambda self, *a, **k: (_ for _ in ()).throw(
                AssertionError("full sweep ran on a disk hit")
            ),
        )
        out2 = mod.ag_gemm(a, b, mesh8, "x")
        np.testing.assert_allclose(np.asarray(out2), ref, atol=1e-4, rtol=1e-4)
        assert validated, "disk entry never reached winner re-validation"

    def test_gemm_rs_and_all_gather_tuned(self, mesh8, tmp_path, monkeypatch):
        import jax

        import importlib

        agmod = importlib.import_module("triton_distributed_tpu.kernels.allgather")
        rsmod = importlib.import_module("triton_distributed_tpu.kernels.gemm_rs")

        self._env(tmp_path, monkeypatch)
        rsmod._engine_tuner.cache_clear()
        agmod._engine_tuner.cache_clear()
        a = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
        b = jax.random.normal(jax.random.PRNGKey(3), (32, 48))
        out = rsmod.gemm_rs(a, b, mesh8, "x")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jnp.dot(a, b)), atol=1e-4, rtol=1e-4
        )
        x = jax.random.normal(jax.random.PRNGKey(4), (64, 16))
        full = agmod.all_gather(x, mesh8, "x")
        np.testing.assert_allclose(np.asarray(full), np.asarray(x), atol=0)
        store = json.loads((tmp_path / "cache.json").read_text())
        assert any("gemm_rs" in k for k in store)
        assert any("all_gather" in k for k in store)

    def test_heuristic_when_disabled(self, mesh8, tmp_path, monkeypatch):
        """TDTPU_AUTOTUNE=0 → static heuristics, no cache file."""
        import jax

        import importlib

        mod = importlib.import_module("triton_distributed_tpu.kernels.ag_gemm")
        monkeypatch.setenv("TDTPU_AUTOTUNE", "0")
        monkeypatch.setenv("TDTPU_AUTOTUNE_LOG_DIR", str(tmp_path))
        mod._engine_tuner.cache_clear()
        a = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 128))
        mod.ag_gemm(a, b, mesh8, "x")
        assert not (tmp_path / "cache.json").exists()
