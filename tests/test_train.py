"""The training subsystem (ISSUE 14): EF/SR quantized gradient rings,
the dp×tp×cp train step, and its ledger-driven wire degradation.

The reference repo trains on raw NCCL; the properties pinned here are
the ones this port's wire stack adds:

* the gradient ring's error feedback telescopes the LINK-AGGREGATE
  (stripe-summed) error — strictly below the no-EF control for > 1 hop
  and sublinear in hop count (per-element error is the unbiased SR
  noise floor either way; see train/grad_wire.py's module docstring),
* seeded stochastic rounding is bit-deterministic and rank-identical,
* the wire resolve contract is loud (pinned raises, auto demotes),
* the dp2×tp2×cp2 step tracks the single-device dense reference within
  a pinned tolerance on both the quantized ring and the psum twin,
* a chaos Stall on the grad ring trips the watchdog, demotes the step
  to the XLA twin through the HealthLedger, and probation re-promotes.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.fast

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from triton_distributed_tpu import train  # noqa: E402
from triton_distributed_tpu.train import grad_wire, step as stepmod  # noqa: E402


def _submesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def _allreduce(mesh, n, wire, seed, ef=True):
    """Per-rank (rows, cols) partials → stacked per-rank sums
    (n·rows, cols): rank r's result slab at rows [r·rows, (r+1)·rows)."""
    fn = jax.shard_map(
        lambda x: grad_wire.grad_allreduce_device(
            x, "x", n=n, wire=wire, seed=seed, ef=ef),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
        check_vma=False,
    )
    return jax.jit(fn)


def _reduce_scatter(mesh, n, wire, seed, ef):
    """Per-rank (n·srows, cols) partials → the reduced slab
    (n·srows, cols): stripe s is rank s's owned output."""
    fn = jax.shard_map(
        lambda x: grad_wire.ef_ring_reduce_scatter(
            x, "x", n=n, wire=wire, seed=seed, ef=ef),
        mesh=mesh, in_specs=P("x", None), out_specs=P("x", None),
        check_vma=False,
    )
    return jax.jit(fn)


def _partials(n, srows, cols, seed):
    """Per-rank partial slabs: rank r's (n·srows, cols) block of the
    returned (n·n·srows, cols) array."""
    rng = np.random.RandomState(seed)
    return rng.standard_normal((n * n * srows, cols)).astype(np.float32)


def _rs_errors(n, seed, ef, srows=8, cols=128):
    """(per-element |err| mean, link-aggregate |err| mean) of the
    quantized reduce-scatter vs the exact f32 reduction."""
    mesh = _submesh(n)
    x = _partials(n, srows, cols, seed)
    exact = x.reshape(n, n * srows, cols).sum(axis=0)
    out = np.asarray(
        _reduce_scatter(mesh, n, "int8", seed=seed + 7, ef=ef)(x))
    err = out - exact                           # (n·srows, cols)
    agg = err.reshape(n, srows, cols).sum(axis=0)   # stripe-summed
    return float(np.abs(err).mean()), float(np.abs(agg).mean())


# ------------------------------------------------- ring numerics + EF


class TestGradRing:
    def test_allreduce_matches_psum_and_is_rank_identical(self):
        n, rows, cols = 4, 16, 128
        mesh = _submesh(n)
        rng = np.random.RandomState(0)
        x = rng.standard_normal((n * rows, cols)).astype(np.float32)
        exact = x.reshape(n, rows, cols).sum(axis=0)
        out = np.asarray(_allreduce(mesh, n, "int8", seed=3)(x))
        blocks = out.reshape(n, rows, cols)
        # every rank consumed the same shipped bytes: bit-identical
        for r in range(1, n):
            assert (blocks[r] == blocks[0]).all(), r
        # per-element error bounded vs the exact reduction
        tol = 3e-2 * np.abs(exact).max()
        assert np.abs(blocks[0] - exact).max() < tol

    def test_wire_none_is_exact_psum(self):
        n, rows, cols = 4, 8, 128
        mesh = _submesh(n)
        x = np.random.RandomState(1).standard_normal(
            (n * rows, cols)).astype(np.float32)
        exact = x.reshape(n, rows, cols).sum(axis=0)
        out = np.asarray(_allreduce(mesh, n, None, seed=0)(x))
        np.testing.assert_allclose(
            out.reshape(n, rows, cols)[0], exact, rtol=1e-6, atol=1e-5)

    def test_same_seed_bit_identical_different_seed_not(self):
        n = 4
        mesh = _submesh(n)
        x = np.random.RandomState(2).standard_normal(
            (n * 16, 128)).astype(np.float32)
        a = np.asarray(_allreduce(mesh, n, "int8", seed=11)(x))
        b = np.asarray(_allreduce(mesh, n, "int8", seed=11)(x))
        c = np.asarray(_allreduce(mesh, n, "int8", seed=12)(x))
        assert (a == b).all()
        assert (a != c).any()

    @pytest.mark.parametrize("n", [4, 8])
    def test_ef_aggregate_error_below_no_ef_control(self, n):
        """The EF claim, measured on the metric EF actually bounds: the
        stripe-summed (link-aggregate) error. Per hop, EF folds the
        previous rounding's residual into the next message, so a rank's
        shipped total telescopes to ONE residual; the no-EF control
        accumulates n-1 independent roundings. (Per-element error is
        the unbiased SR noise floor either way — deliberately NOT the
        metric here.) Averaged over seeds for stability."""
        ef_aggs, ctl_aggs = [], []
        for seed in (0, 1, 2):
            _, agg_ef = _rs_errors(n, seed, ef=True)
            _, agg_ctl = _rs_errors(n, seed, ef=False)
            ef_aggs.append(agg_ef)
            ctl_aggs.append(agg_ctl)
        assert np.mean(ef_aggs) < np.mean(ctl_aggs), (ef_aggs, ctl_aggs)

    def test_ef_aggregate_error_sublinear_in_hops(self):
        """Hop growth: 3 hops (n=4) → 7 hops (n=8). With EF the
        aggregate error must grow SLOWER than the hop count; the no-EF
        control is free to grow at (or beyond) √hops."""
        ef4 = np.mean([_rs_errors(4, s, ef=True)[1] for s in (0, 1, 2)])
        ef8 = np.mean([_rs_errors(8, s, ef=True)[1] for s in (0, 1, 2)])
        assert ef8 / ef4 < 7.0 / 3.0, (ef4, ef8)


# ------------------------------------------------------ wire resolve


class TestResolveContract:
    def test_auto_demotes_silently(self):
        # 6 rows over an 8-ring: no legal chunking → exact wire
        assert grad_wire.resolve_grad_wire("auto", 6, 128, 8) is None

    def test_pinned_ineligible_raises(self):
        with pytest.raises(ValueError, match="pinned wire format"):
            grad_wire.resolve_grad_wire("int8", 6, 128, 8)

    def test_eligible_resolves(self):
        assert grad_wire.resolve_grad_wire("auto", 64, 128, 8) == "int8"
        assert grad_wire.resolve_grad_wire("fp8", 64, 128, 8) == "fp8"

    def test_bf16_and_none_are_exact(self):
        assert grad_wire.resolve_grad_wire(None, 64, 128, 8) is None
        assert grad_wire.resolve_grad_wire("bf16", 64, 128, 8) is None

    def test_trainer_pinned_config_refuses_at_init(self):
        # a vocab-1 model's slab is too small for an int8 ring over dp=8
        with pytest.raises(ValueError):
            grad_wire.resolve_grad_wire("int8", 2, 128, 8)


# ------------------------------------------------------- train step


def _reference_losses(cfg, batches):
    params = stepmod.init_params(cfg)
    opt = stepmod.init_opt_state(params)
    losses = []
    for tok, tgt in batches:
        params, opt, loss = train.train_step_reference(
            params, opt, tok, tgt, cfg)
        losses.append(float(loss))
    return losses


class TestTrainStep:
    STEPS = 4
    TOL = 0.05          # pinned |loss_dist - loss_ref| per step

    def _trainer_losses(self, cfg):
        tr = train.Trainer(cfg)
        batches = [tr.make_batch(k) for k in range(self.STEPS)]
        dist = [tr.step(tok, tgt)["loss"] for tok, tgt in batches]
        return tr, dist, _reference_losses(cfg, batches)

    def test_wire_step_tracks_reference(self):
        cfg = train.TrainConfig()          # dp2×tp2×cp2, int8 ring
        tr, dist, ref = self._trainer_losses(cfg)
        assert tr.wire == "int8"
        assert abs(dist[0] - ref[0]) < 1e-4     # identical initial params
        for d, r in zip(dist, ref):
            assert abs(d - r) < self.TOL, (dist, ref)
        # the wire actually halves the ring bytes
        assert tr.wire_report()["ratio"] > 1.9

    def test_psum_twin_tracks_reference(self):
        cfg = train.TrainConfig(wire_dtype=None)
        tr, dist, ref = self._trainer_losses(cfg)
        assert tr.wire is None
        for d, r in zip(dist, ref):
            assert abs(d - r) < self.TOL, (dist, ref)

    def test_ulysses_attention_step(self):
        cfg = train.TrainConfig(attn="ulysses")
        tr, dist, ref = self._trainer_losses(cfg)
        for d, r in zip(dist, ref):
            assert abs(d - r) < self.TOL, (dist, ref)

    def test_step_is_deterministic(self):
        cfg = train.TrainConfig()
        a = [r["loss"] for r in train.Trainer(cfg).run(3)]
        b = [r["loss"] for r in train.Trainer(cfg).run(3)]
        assert a == b


# -------------------------------------- the families the step launches


@pytest.mark.parametrize("fam", train.TRAIN_ENGINE_FAMILIES)
def test_train_family_registered_lint_clean_with_fallback(fam):
    """The ``bench.py --lint`` train gate, in tier-1 (it was an inline
    block of ``ci/fast.sh`` until PR 48): every family the train step
    launches is registered, lints clean at mesh 8 and declares a
    degradation target that resolves — the ledger's demotion (wire ring
    → exact psum twin) needs somewhere to go."""
    from triton_distributed_tpu.analysis.lint import lint_family
    from triton_distributed_tpu.kernels.registry import (
        missing_degradation_targets,
    )

    assert lint_family(fam, n=8) == []
    assert fam not in {f for f, _ in missing_degradation_targets()}


# ------------------------------------------------- chaos + probation


@pytest.mark.chaos
class TestGradRingDegradation:
    def test_stall_trips_degrades_and_reprobes(self):
        """The full degradation loop: a fault-plan Stall at site
        ``grad_ring`` wedges the wire step mid-run; the armed watchdog
        trips, names the site, and broadcasts ``site:grad_ring`` FATAL
        into the trainer's ledger; the next step demotes to the exact
        psum twin; clean degraded steps earn PROBATION; seeded probes
        re-promote the ring — and it STAYS promoted."""
        from triton_distributed_tpu.runtime import faults, watchdog
        from triton_distributed_tpu.runtime.faults import FaultPlan, Stall
        from triton_distributed_tpu.runtime.health import PeerState
        from triton_distributed_tpu.runtime.watchdog import WatchdogTimeout

        tr = train.Trainer(train.TrainConfig())
        assert tr.step()["wire"] == "int8"      # warm compile first

        plan = FaultPlan(seed=0, faults=(Stall(site="grad_ring", rank=0),))
        with faults.fault_plan(plan):
            with pytest.raises(WatchdogTimeout):
                with watchdog.collective_watchdog(deadline=0.2):
                    tr.step()
        assert tr.health.state("site:grad_ring") is PeerState.UNHEALTHY

        post = tr.step()
        assert post["wire"] is None and post["degraded"]

        reports = [tr.step() for _ in range(40)]
        assert any(r["probing"] for r in reports)
        assert tr.repromotions >= 1
        tail = tr.step()
        assert tail["wire"] == "int8" and not tail["degraded"]

    def test_probe_failure_falls_back_to_unhealthy(self):
        """A probe that raises drops the ring straight back to
        UNHEALTHY (no partial credit), and the step still completes on
        the twin."""
        from triton_distributed_tpu.runtime.health import PeerState

        tr = train.Trainer(train.TrainConfig())
        tr.step()
        tr.health.record("watchdog_trip", "site:grad_ring", fatal=True)
        assert tr.step()["wire"] is None        # demoted

        # walk to PROBATION, then sabotage exactly the probe step
        real_run = tr._run
        while not tr.health.probe_due("site:grad_ring", tr.step_count):
            tr.step()
            assert tr.health.state("site:grad_ring") is not None

        def boom(tokens, targets):
            if tr.use_wire:
                raise RuntimeError("injected ring failure")
            return real_run(tokens, targets)

        tr._run = boom
        r = tr.step()
        assert r["wire"] is None                # completed on the twin
        assert tr.health.state("site:grad_ring") is PeerState.UNHEALTHY
        tr._run = real_run


# --------------------------------------------------- overlap bwd wire


class TestOverlapBackwardWire:
    def test_ag_gemm_quantized_duals_track_exact(self, mesh8):
        from triton_distributed_tpu.ops import overlap

        a = np.random.RandomState(1).standard_normal(
            (64, 32)).astype(np.float32)
        b = np.random.RandomState(2).standard_normal(
            (32, 128)).astype(np.float32)

        def grads(ctx):
            f = lambda a_, b_: jnp.sum(overlap.ag_gemm(a_, b_, ctx) ** 2)
            da, db = jax.grad(f, argnums=(0, 1))(jnp.asarray(a),
                                                 jnp.asarray(b))
            return np.asarray(da), np.asarray(db)

        da0, db0 = grads(overlap.create_ag_gemm_context(mesh8, "x"))
        da8, db8 = grads(overlap.create_ag_gemm_context(
            mesh8, "x", bwd_wire_dtype="int8"))
        assert np.abs(da8 - da0).max() < 5e-2 * np.abs(da0).max()
        assert np.abs(db8 - db0).max() < 5e-2 * max(np.abs(db0).max(), 1.0)

    def test_gemm_rs_quantized_duals_track_exact(self, mesh8):
        from triton_distributed_tpu.ops import overlap

        a = np.random.RandomState(3).standard_normal(
            (64, 256)).astype(np.float32)
        b = np.random.RandomState(4).standard_normal(
            (256, 128)).astype(np.float32)

        def grads(ctx):
            f = lambda a_, b_: jnp.sum(overlap.gemm_rs(a_, b_, ctx) ** 2)
            da, db = jax.grad(f, argnums=(0, 1))(jnp.asarray(a),
                                                 jnp.asarray(b))
            return np.asarray(da), np.asarray(db)

        da0, db0 = grads(overlap.create_gemm_rs_context(mesh8, "x"))
        da8, db8 = grads(overlap.create_gemm_rs_context(
            mesh8, "x", bwd_wire_dtype="int8"))
        assert np.abs(da8 - da0).max() < 5e-2 * np.abs(da0).max()
        assert np.abs(db8 - db0).max() < 5e-2 * np.abs(db0).max()

    def test_pinned_bwd_wire_refuses_uncarryable_cotangent(self, mesh8):
        from triton_distributed_tpu.ops import overlap

        ctx = overlap.create_ag_gemm_context(
            mesh8, "x", bwd_wire_dtype="int8")
        g = jnp.ones((6, 32), jnp.float32)      # 6 rows % 8 ranks != 0
        with pytest.raises(ValueError, match="pinned wire format"):
            overlap._resolve_bwd(ctx, g, 32)

    def test_auto_bwd_wire_demotes_silently(self, mesh8):
        from triton_distributed_tpu.ops import overlap

        ctx = overlap.create_ag_gemm_context(
            mesh8, "x", bwd_wire_dtype="auto")
        g = jnp.ones((6, 32), jnp.float32)
        assert overlap._resolve_bwd(ctx, g, 32) is None
