"""Fail-loud guards over a compiled program's argument placements.

Two silent performance killers on a multi-chip mesh:

* **Involuntary resharding at a phase boundary** — a program compiled
  with parameter shardings that differ from the placements of the
  arrays the caller will actually pass (e.g. a serving state built in
  one layout while the step compiles wanting another). XLA
  "fixes" it with a full copy/reshard of the argument every call —
  pool-sized traffic per serving step at pod scale. The round-4
  dryrun's compile log caught exactly this by accident ("[SPMD]
  Involuntary full rematerialization" over the cache params); these
  guards make it a CI failure instead of a log tail.
* **A dropped donation** — a serving step whose page pools were
  donated but whose in/out placements diverged, so XLA allocates a
  fresh pool-sized buffer per step instead of aliasing in place (≡
  the reference kernels mutating their persistent caches,
  flash_decode.py:763-846).

Use with any ``jax.jit``-compiled callable::

    compiled = jitted.lower(*args).compile()
    assert_no_involuntary_resharding(compiled, args)
    aliased = input_output_aliased_params(compiled)

The checks read ``compiled.input_shardings`` and the optimized HLO
header, plus (best-effort) the executable's kept-argument set — jit
with the default ``keep_unused=False`` DROPS unused argument leaves
from the compiled signature, shifting parameter numbers.

IMPORTANT: lower the program from **abstract arguments carrying the
intended placements** (``jax.ShapeDtypeStruct(..., sharding=canon)``:
for ``Transformer._serving_jit`` the params on ``shardings()`` and the
``ServingState`` pools on ``_serving_pool_sharding``, as
``tests/test_serving_shard.py`` does), not from the live arrays —
a program lowered from committed arrays reports those arrays' own
shardings back, so a boundary check against it can never fail.
"""

from __future__ import annotations

import re

import jax


def _kept_indices(compiled, n_flat):
    """Flat argument-leaf indices that survived into the compiled
    signature, in HLO parameter order. jit(keep_unused=False) drops
    unused leaves; the executable records which (private attr,
    best-effort — absent means all kept)."""
    kept = getattr(
        getattr(compiled, "_executable", None), "_kept_var_idx", None
    )
    if kept is None:
        return list(range(n_flat))
    return sorted(kept)


def _leaf_pairs(compiled, args):
    """Flattened (path, arg leaf, compiled parameter sharding) triples
    over the KEPT argument leaves.

    ``compiled.input_shardings`` is a (args, kwargs) pair of pytrees
    mirroring the call signature after unused-leaf dropping; pairing it
    with the kept subset of the argument leaves lines every leaf up
    with the sharding the compiled program expects for it.
    """
    arg_sh, kw_sh = compiled.input_shardings
    assert not kw_sh, "keyword arguments are not supported by the guard"
    flat_args = jax.tree_util.tree_leaves_with_path(args)
    flat_sh = jax.tree_util.tree_leaves(
        arg_sh, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
    )
    kept = _kept_indices(compiled, len(flat_args))
    if len(kept) != len(flat_sh) or (kept and kept[-1] >= len(flat_args)):
        raise ValueError(
            f"argument tree ({len(flat_args)} leaves, {len(kept)} kept) "
            f"does not match the compiled signature ({len(flat_sh)} "
            "parameter shardings) — pass exactly the args the program "
            "was lowered with"
        )
    return [
        (jax.tree_util.keystr(flat_args[i][0]), flat_args[i][1], sh)
        for i, sh in zip(kept, flat_sh)
    ]


def find_involuntary_resharding(compiled, args, *, min_bytes=1 << 20):
    """List of (path, nbytes, arg sharding, program sharding) for every
    argument leaf of at least ``min_bytes`` whose current placement
    differs from the placement the compiled program expects — each one
    is a full reshard/copy XLA will silently insert at EVERY call."""
    bad = []
    for path, leaf, want in _leaf_pairs(compiled, args):
        if not isinstance(leaf, jax.Array) or leaf.nbytes < min_bytes:
            continue
        have = leaf.sharding
        if not have.is_equivalent_to(want, leaf.ndim):
            bad.append((path, leaf.nbytes, have, want))
    return bad


def assert_no_involuntary_resharding(compiled, args, *, min_bytes=1 << 20):
    """Fail loudly when calling ``compiled`` with ``args`` would
    reshard any argument of at least ``min_bytes`` (see
    :func:`find_involuntary_resharding`)."""
    bad = find_involuntary_resharding(compiled, args, min_bytes=min_bytes)
    if bad:
        lines = "\n".join(
            f"  {p} ({n} bytes): have {h.spec if hasattr(h, 'spec') else h}"
            f" -> program wants {w.spec if hasattr(w, 'spec') else w}"
            for p, n, h, w in bad
        )
        raise AssertionError(
            f"involuntary resharding of {len(bad)} argument(s) at every "
            f"call of this compiled program:\n{lines}\n"
            "Pin the producer's output shardings (or the consumer's "
            "in_shardings) so the placements agree across the boundary."
        )


_ALIAS_ENTRY = re.compile(
    r"\{([\d,\s]*)\}\s*:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)"
)


def _alias_table_text(text: str) -> str:
    """The brace-balanced body of the HLO header's
    ``input_output_alias={...}`` table ('' when absent) — the entries
    themselves contain nested ``{}`` so a regex-to-first-brace won't
    do."""
    key = "input_output_alias={"
    start = text.find(key)
    if start < 0:
        return ""
    i, depth = start + len(key), 1
    while i < len(text) and depth:
        depth += {"{": 1, "}": -1}.get(text[i], 0)
        i += 1
    return text[start + len(key):i - 1]


def input_output_aliased_params(compiled) -> dict:
    """Parse the optimized HLO header's ``input_output_alias`` table →
    ``{parameter_number: output_index_tuple}``. A donated argument that
    XLA actually aliases (updates in place) appears here; a donation
    XLA had to drop (placement/layout mismatch) does not."""
    out = {}
    for om in _ALIAS_ENTRY.finditer(_alias_table_text(compiled.as_text())):
        out_idx = tuple(
            int(t) for t in om.group(1).replace(" ", "").split(",") if t
        )
        out[int(om.group(2))] = out_idx
    return out


def leaf_range(args, selector) -> range:
    """Flat parameter-index range covered by ``selector(args)`` — e.g.
    ``leaf_range((params, caches, lens), lambda a: a[1])`` is the cache
    leaves' positions in the compiled program's parameter numbering
    (jit flattens positional args in order)."""
    flat_before = 0
    found = None
    target = selector(args)
    # walk the top-level args in order, counting leaves
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        if a is target:
            found = range(flat_before, flat_before + n)
        flat_before += n
    if found is None:
        raise ValueError("selector must return one of the top-level args")
    return found


def selfcheck() -> None:
    """Pin the jax/XLA introspection formats the guards depend on.

    The guards read two PRIVATE surfaces — the executable's
    ``_kept_var_idx`` set (which argument leaves survived
    ``keep_unused=False``) and the optimized HLO header's
    ``input_output_alias={...}`` table — and a jax/XLA version change
    renaming the attribute or reformatting the table would otherwise
    surface as spurious "dropped donation" / "resharding" errors on
    correct programs. This self-test runs two trivially known programs
    through the real pipeline and raises one CLEAR diagnostic when the
    expectations no longer hold (ADVICE r5); call it from CI (the
    test-suite does) or before trusting a guard verdict on a new jax.
    """
    import jax.numpy as jnp

    # 1) a donated, genuinely-aliasable argument must round-trip
    #    through input_output_aliased_params
    f = jax.jit(lambda s, x: (s + x, jnp.float32(0.0)), donate_argnums=(0,))
    x = jnp.zeros((128, 128), jnp.float32)
    y = jnp.ones((128, 128), jnp.float32)
    compiled = f.lower(x, y).compile()
    aliased = input_output_aliased_params(compiled)
    if 0 not in aliased:
        raise AssertionError(
            "shardguard.selfcheck: a trivially-donated jit argument did "
            "not appear in the parsed input_output_alias table "
            f"(got {aliased!r}) — the optimized-HLO header format has "
            "drifted; update shardguard._ALIAS_ENTRY/_alias_table_text "
            "before trusting assert_args_aliased on this jax"
        )
    if assert_args_aliased(compiled, (x, y), lambda a: a[0]) is not None:
        raise AssertionError("assert_args_aliased returned unexpectedly")

    # 2) an UNUSED argument leaf must be visibly dropped from the kept
    #    set (or all leaves reported kept — the documented best-effort
    #    fallback when the private attr is absent), and the kept/
    #    sharding pairing must stay consistent
    g = jax.jit(lambda used, unused: used * 2.0)
    compiled2 = g.lower(x, x).compile()
    kept = _kept_indices(compiled2, 2)
    flat_sh = jax.tree_util.tree_leaves(
        compiled2.input_shardings[0],
        is_leaf=lambda s: isinstance(s, jax.sharding.Sharding),
    )
    if len(kept) != len(flat_sh):
        raise AssertionError(
            "shardguard.selfcheck: the kept-argument set "
            f"({kept!r}) does not line up with the compiled parameter "
            f"shardings ({len(flat_sh)} entries) — the _kept_var_idx "
            "attribute has drifted; _leaf_pairs would misattribute "
            "shardings to the wrong leaves on this jax"
        )
    # the consistency check above is the load-bearing one; additionally
    # pin today's exact behavior so a silent semantic change is visible
    if kept not in ([0], [0, 1]):
        raise AssertionError(
            f"shardguard.selfcheck: unexpected kept set {kept!r} for a "
            "2-arg program with one unused arg"
        )


def assert_args_aliased(compiled, args, selector, *, min_bytes=0):
    """Assert every leaf of ``selector(args)`` (≥ ``min_bytes``) is
    input/output-aliased in ``compiled`` — i.e. its donation survived
    and the program updates it in place. A selected leaf the program
    dropped as unused also fails (a serving-state buffer the program
    never reads is its own bug)."""
    aliased = input_output_aliased_params(compiled)
    flat_n = len(jax.tree_util.tree_leaves(args))
    # flat leaf index → HLO parameter number (unused leaves dropped)
    param_of = {flat: p for p, flat in enumerate(_kept_indices(compiled, flat_n))}
    idxs = leaf_range(args, selector)
    leaves = jax.tree_util.tree_leaves(selector(args))
    missing = [
        i for i, leaf in zip(idxs, leaves)
        if getattr(leaf, "nbytes", 0) >= min_bytes
        and param_of.get(i) not in aliased
    ]
    if missing:
        raise AssertionError(
            f"argument leaves {missing} (of {list(idxs)}) are NOT input/"
            "output-aliased — their donation was dropped (or the leaf is "
            "unused), so the program copies them instead of updating in "
            "place. Check that the output placements equal the input "
            "placements (with_sharding_constraint) and that "
            "donate_argnums covers them."
        )
