"""Shard-resident serving state: the donated ``ServingState`` pools of
``Transformer._serving_jit`` on a multi-chip mesh.

The serving contract (≡ the reference's SP decode layer, whose per-rank
KV shard keeps one placement for the life of the session —
sp_flash_decode_layer.py:45-184):

* ONE canonical pool placement (KV heads over tp,
  ``Transformer._serving_pool_sharding``) from ``init_serving_state``
  through every step;
* the step jit DONATES the state, and the pinned output placements let
  XLA alias the pools — the per-step append is in place, not a
  pool-sized copy;
* the shardguard utilities turn a violation (the round-4 "[SPMD]
  Involuntary full rematerialization" compile-log failure mode) into
  a loud CI failure.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import serve_all_logits
from jax.sharding import NamedSharding, PartitionSpec as P

from triton_distributed_tpu.models import Transformer, TransformerConfig
from triton_distributed_tpu.runtime import (
    assert_args_aliased,
    assert_no_involuntary_resharding,
    find_involuntary_resharding,
    input_output_aliased_params,
)
from triton_distributed_tpu.serving import (
    EngineConfig,
    Request,
    ServingEngine,
)

ENGINE = EngineConfig(slots=4, token_budget=32, chunk=8, page=8, npages=32)
#: the pools are the state's only leaves of this size (tables and
#: lengths are a few hundred bytes and pass through the step)
POOL_BYTES = 1 << 12


def _model(mesh, kv_quant=None):
    """The dryrun mesh's serving model: tp over its ``tp`` axis, the
    step replicated over ``dp`` (ragged serving is tp-only: dp composes
    by one engine a dp group)."""
    cfg = TransformerConfig(
        vocab=128, n_layers=2, hidden=128, ffn=256,
        n_heads=8, n_kv_heads=4, head_dim=16,
        moe="ep", moe_layers=(1,), num_experts=8, topk=2,
        kv_quant=kv_quant,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    model = Transformer(cfg, mesh, "tp", ())
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, s),
        model.init(jax.random.PRNGKey(0)), model.shardings(),
    )
    return model, params


def _first_step(model, params):
    """``(engine, args)``: the argument tuple of an engine's first
    device step (a chunk of 8 and a whole prompt of 5), its three
    static arguments left off — what the shard guards pair with the
    compiled program's parameters."""
    eng = ServingEngine(model, params, ENGINE, use_pallas=False)
    rng = np.random.default_rng(1)
    for i, n in enumerate((11, 5)):
        eng.submit(Request(rid=i, prompt=rng.integers(0, 128, (n,))
                           .astype(np.int32), max_new=2, arrival=0.0))
    eng._admit()
    return eng, eng._step_args(eng._assemble()[:7], 8)[:9]


def _compiled_step(model, args):
    """``_serving_jit`` lowered from ABSTRACT arguments carrying the
    canonical placements (params on ``shardings()``, pools on
    ``_serving_pool_sharding``, the rest replicated) — lowering from
    the live arrays would echo their shardings back and make the
    boundary check vacuous."""
    rep = NamedSharding(model.mesh, P())

    def abstract(tree, sharding_of):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding_of(x)), tree)

    params, state, *rest = args
    pools = abstract(state.layers, lambda _: model._serving_pool_sharding)
    return model._serving_jit.lower(
        abstract(params, lambda x: x.sharding),
        abstract(state.replace(layers=()), lambda _: rep)
        .replace(layers=pools),
        *abstract(rest, lambda _: rep), 8, False, 2,
    ).compile()


def _off_placement(model, args):
    """The same arguments with the pools living replicated."""
    params, state, *rest = args
    bad = jax.tree.map(
        lambda x: jax.device_put(
            np.asarray(x), NamedSharding(model.mesh, P())),
        state.layers)
    return (params, state.replace(layers=bad), *rest)


class TestServingShardResidency:
    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_decode_no_reshard_and_aliased(self, mesh2x4, kv_quant):
        """Compile the serving step on the 2×4 dryrun mesh: (i) every
        argument arrives in the placement the program compiled for (no
        involuntary reshard at the call boundary), (ii) the compiled
        program aliases the donated pools to its outputs — the in-place
        append survived donation — and a step's output pools keep the
        placement."""
        model, params = _model(mesh2x4, kv_quant)
        eng, args = _first_step(model, params)
        pool_sh = model._serving_pool_sharding
        for leaf in jax.tree.leaves(args[1].layers):  # init placement
            assert leaf.sharding.is_equivalent_to(pool_sh, leaf.ndim)
        comp = _compiled_step(model, args)
        # (the step's nine small host arrays are uploaded to one device
        # and broadcast: a few hundred bytes, under the guard's floor)
        assert find_involuntary_resharding(
            comp, args, min_bytes=POOL_BYTES) == []
        # ... and the check is NON-vacuous: the same program must flag
        # pools living in a non-canonical placement
        assert find_involuntary_resharding(
            comp, _off_placement(model, args), min_bytes=POOL_BYTES)
        assert_args_aliased(comp, args, lambda a: a[1],
                            min_bytes=POOL_BYTES)
        logits, state = comp(*args)
        for leaf in jax.tree.leaves(state.layers):    # the step kept it
            assert leaf.sharding.is_equivalent_to(pool_sh, leaf.ndim)
        assert logits.shape == (ENGINE.slots, 128)
        assert bool(jnp.isfinite(logits[:2]).all())

    def test_decode_matches_replicated_reference(self, mesh2x4):
        """The serving step over the dryrun mesh (heads and experts
        over tp, replicated over dp) must produce the same logits as
        the same model with everything on one device."""
        model, params = _model(mesh2x4)
        mesh1 = jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1, 1), ("dp", "tp")
        )
        model1, _ = _model(mesh1)
        params1 = jax.device_put(
            jax.tree.map(np.asarray, params),
            NamedSharding(mesh1, P()),
        )
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, (n,)) for n in (11, 5)]
        _, _, got = serve_all_logits(model, params, ENGINE, prompts,
                                     max_new=2)
        _, _, want = serve_all_logits(model1, params1, ENGINE, prompts,
                                      max_new=2)
        np.testing.assert_allclose(
            np.concatenate(got), np.concatenate(want),
            atol=2e-4, rtol=2e-4
        )

    def test_guard_trips_on_seeded_mismatch(self, mesh2x4):
        """A program compiled for one placement, fed an array living in
        another, must fail the guard loudly."""
        want = NamedSharding(mesh2x4, P("dp", None))
        have = NamedSharding(mesh2x4, P(None, "tp"))
        comp = jax.jit(lambda a: a * 2).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=want)
        ).compile()
        x = jax.device_put(jnp.zeros((64, 64), jnp.float32), have)
        bad = find_involuntary_resharding(comp, (x,), min_bytes=0)
        assert len(bad) == 1
        with pytest.raises(AssertionError, match="involuntary resharding"):
            assert_no_involuntary_resharding(comp, (x,), min_bytes=0)
        # the matching placement passes
        ok = jax.device_put(jnp.zeros((64, 64), jnp.float32), want)
        assert_no_involuntary_resharding(comp, (ok,), min_bytes=0)

    def test_alias_guard_trips_on_dropped_donation(self, mesh2x4):
        """A donated argument whose output placement diverges cannot be
        aliased — the guard must say so (instead of the program paying
        a silent copy per call)."""
        x = jax.device_put(
            jnp.zeros((64, 64), jnp.float32),
            NamedSharding(mesh2x4, P("dp", None)),
        )

        def resharded(a):
            return jax.lax.with_sharding_constraint(
                a + 1, NamedSharding(mesh2x4, P("tp", None))
            )

        comp = jax.jit(resharded, donate_argnums=(0,)).lower(x).compile()
        with pytest.raises(AssertionError, match="NOT input/output-aliased"):
            assert_args_aliased(comp, (x,), lambda a: a[0])

        def inplace(a):
            return jax.lax.with_sharding_constraint(
                a.at[0].set(1.0), NamedSharding(mesh2x4, P("dp", None))
            )

        comp2 = jax.jit(inplace, donate_argnums=(0,)).lower(x).compile()
        assert_args_aliased(comp2, (x,), lambda a: a[0])
        assert 0 in input_output_aliased_params(comp2)

    def test_decode_boundary_violation_raises(self, mesh2x4):
        """The ISSUE-1 negative path on the REAL serving program (not a
        synthetic lambda): pools living off the canonical placement
        must make ``assert_no_involuntary_resharding`` raise with the
        offending leaf paths in the message."""
        model, params = _model(mesh2x4)
        _, args = _first_step(model, params)
        comp = _compiled_step(model, args)
        with pytest.raises(AssertionError, match="involuntary resharding"):
            assert_no_involuntary_resharding(
                comp, _off_placement(model, args), min_bytes=POOL_BYTES
            )

    def test_reshard_guard_min_bytes_filters_small_leaves(self, mesh2x4):
        """Leaves below ``min_bytes`` are exempt: resharding a few KB per
        call is noise, and flagging it would make the guard uninhabitable
        for scalar step counters and lens vectors."""
        want = NamedSharding(mesh2x4, P("dp", None))
        have = NamedSharding(mesh2x4, P(None, "tp"))
        comp = jax.jit(lambda a: a * 2).lower(
            jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=want)
        ).compile()
        x = jax.device_put(jnp.zeros((8, 8), jnp.float32), have)
        # 256 bytes: flagged at min_bytes=0, exempt at the 1 MiB default
        assert find_involuntary_resharding(comp, (x,), min_bytes=0)
        assert_no_involuntary_resharding(comp, (x,))

    def test_guard_rejects_mismatched_arg_tree(self, mesh2x4):
        """Passing a different argument tree than the program was
        lowered with must be a loud ValueError, not a silent mispairing
        of leaves with parameter shardings."""
        sh = NamedSharding(mesh2x4, P())
        comp = jax.jit(lambda a, b: a + b).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32, sharding=sh),
            jax.ShapeDtypeStruct((8,), jnp.float32, sharding=sh),
        ).compile()
        x = jax.device_put(jnp.zeros((8,), jnp.float32), sh)
        with pytest.raises(ValueError, match="does not match the compiled"):
            find_involuntary_resharding(comp, (x,), min_bytes=0)

    def test_leaf_range_rejects_foreign_selector(self):
        from triton_distributed_tpu.runtime.shardguard import leaf_range

        args = (jnp.zeros((4,)), jnp.zeros((8,)))
        assert leaf_range(args, lambda a: a[1]) == range(1, 2)
        with pytest.raises(ValueError, match="top-level args"):
            leaf_range(args, lambda a: "not an arg")

    def test_alias_guard_handles_dropped_unused_args(self, mesh2x4):
        """jit(keep_unused=False) drops unused argument leaves from the
        compiled signature — the guards must renumber through the kept
        set instead of false-failing (or false-passing) on the shift."""
        f = jax.jit(lambda a, b: b.at[0].set(1.0), donate_argnums=(1,))
        a = jnp.zeros((8,))
        b = jax.device_put(
            jnp.zeros((64,)), NamedSharding(mesh2x4, P())
        )
        comp = f.lower(a, b).compile()
        # b IS aliased even though it is HLO parameter 0 (a was dropped)
        assert_args_aliased(comp, (a, b), lambda t: t[1])
        # the dropped leaf itself reports as not-aliased
        with pytest.raises(AssertionError, match="NOT input/output"):
            assert_args_aliased(comp, (a, b), lambda t: t[0])
        # and the reshard guard still pairs the kept leaves correctly
        assert_no_involuntary_resharding(comp, (a, b), min_bytes=0)
