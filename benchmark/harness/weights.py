"""The benchmark's own weights: made on the device from ``--seed``.

The program under test and the plain reference are both handed this
tree; neither makes it. The layout (names, shapes, scales) is the
``param_plan`` of the module the configuration names under ``model``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def seed_key(seed: int):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def abstract_params(plan: dict, dtype) -> dict:
    return jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf[0], jnp.dtype(dtype)),
        plan, is_leaf=_is_leaf,
    )


def make_params(plan: dict, seed: int, dtype, shardings=None) -> dict:
    """Every leaf in ONE jitted call, straight onto ``shardings`` and
    in ``dtype`` (the type the weights are stored in)."""
    leaves, treedef = jax.tree.flatten(plan, is_leaf=_is_leaf)

    def gen(key):
        out, last = [], None
        for i, (shape, std) in enumerate(leaves):
            if std is None:
                out.append(jnp.ones(shape, dtype))
                continue
            k = jax.random.key_data(jax.random.fold_in(key, i))
            if last is not None:
                # one leaf after the other: without the barrier the
                # compiler draws every leaf's float32 values at once
                # (15.8 GB at four layers of 64 experts, a whole chip)
                k, last = jax.lax.optimization_barrier((k, last))
            k = jax.random.wrap_key_data(k, impl="rbg")
            last = (jax.random.normal(k, shape, jnp.float32) * std) \
                .astype(dtype)
            out.append(last)
        return jax.tree.unflatten(treedef, out)

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))
