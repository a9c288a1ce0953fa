"""Contract inference: derive SL008 delivery obligations from the twin.

Delivery contracts used to be hand-declared per registry family
(``KernelFamily.contract``) — the one structural hole in the analyzer:
a new family can under-declare and the SL008 completeness pass goes
silently blind, and every machine-generated schedule axis widens that
gap. This module closes it by *deriving* each family's
:class:`~triton_distributed_tpu.analysis.dataflow.DeliveryContract`
from two independent witnesses and diffing the declaration against
them:

1. **The XLA twin** (``degrades_to`` — every family has one,
   lint-enforced). The twin is executed for real on a small CPU mesh
   with rank-tagged inputs: rank ``r``'s payload carries the value
   ``2**r``, identity/ones untagged operands keep the twin linear, so
   every output element's value IS a bitmask of the source ranks that
   contributed to it. Decoding the bitmasks classifies the twin's
   delivery semantics into one of three classes — ``single`` (every
   nonzero element traces to exactly one source: the gather / permute
   shapes), ``fold`` (elements sum contributions from every rank: the
   reduce shapes) or ``local`` (a per-rank function with no mesh
   operand at all). Twins whose public signature is local because the
   transport is composed *around* them in the degraded op path (dense
   attention behind a KV gather, the grouped GEMM behind the MoE token
   all-gather / ahead of the reduce-scatter) are run inside exactly
   that documented composition (ops/moe_tp.py, ops/cp.py) — the class
   measures the degraded data path, not just the inner callable.

2. **The replay's provenance arrays** (``dataflow._State``). Given the
   twin's class, the kernel's own replayed ``contrib`` nibbles
   *realize* the concrete contract: which root buffer exhibits the
   class's delivery pattern (the ``dst``), how many elements each
   source lands per rank (``payload_per_src``), whether every element
   is covered (``full``), whether the local rank's own chunk is
   legitimately absent (``own_absent_ok``), and which sources actually
   deliver into each rank (``src_only`` — only trusted for
   topology-agnostic transports; mesh collectives pin all-sources from
   the twin so a kernel that silently skips a source cannot launder
   the skip into its own inferred topology).

Hand-written contracts become assertions checked against the inferred
ones:

* **SL012** — declared ≠ inferred: wrong kind class, a dst that does
  not exhibit the twin's delivery pattern, over/under-declared
  payload, missing or stray sources, full/own-absent drift.
* **SL013** — a registered family with NO declared contract: inference
  supplies one (so SL008 never goes blind) and surfaces the gap.

Gather and permute intentionally compare as ONE kind class: SL008
checks them with the same branch (every element single-sourced,
per-source counts exact), and sharded twin outputs cannot distinguish
replicated from partitioned landings in general. The inferred
contract's label is chosen from the replay realization and only
affects which (identical) SL008 branch runs.

Twin execution needs ``n`` real (host-platform) devices. When fewer
are available the profile falls back to a static class table keyed by
the twin path — realization and the SL012/SL013 diffs still run, with
``TwinProfile.executed = False`` recorded in every finding's message
so a CI log can tell a measured verdict from a tabled one.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import numpy as np

from triton_distributed_tpu.analysis import checks, dataflow
from triton_distributed_tpu.analysis.dataflow import (
    _NIBBLE,
    DeliveryContract,
)
from triton_distributed_tpu.analysis.findings import Finding

#: twin delivery classes
SINGLE, FOLD, LOCAL = "single", "fold", "local"

#: DeliveryContract.kind → twin class (gather and permute are one
#: class: SL008 checks them with the same branch)
_KIND_CLASS = {
    "gather": SINGLE, "permute": SINGLE, "reduce": FOLD, "local": LOCAL,
}


@dataclass(frozen=True)
class TwinProfile:
    """What the executed twin revealed about the degraded data path.

    ``sources`` is "all" when the twin is a mesh collective over the
    full axis (every rank must deliver — the inferred contract may NOT
    narrow the topology from the replay, or a skipped source would
    launder itself into the inferred ``src_only``); None means the
    transport is topology-agnostic (kv_ship's device_put) and the
    observed sender sets are the contract.
    """

    cls: str                       # single | fold | local
    sources: str | None            # "all" | None
    executed: bool
    detail: str = ""


@dataclass
class InferenceResult:
    """One family's inference: the twin profile, the realized dst root,
    the synthesized contract (usable as the SL008 fallback when the
    family declares none), the SL012/SL013 findings, and the raw
    per-rank observation table for diagnostics."""

    profile: TwinProfile
    dst: str | None
    contract: DeliveryContract | None
    findings: list = field(default_factory=list)
    observed: dict = field(default_factory=dict)


# ------------------------------------------------------------ twin execution

def _mesh(n):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("x",))


def _tags(n):
    """Power-of-two per-rank tags: exact in f32 up to 16 ranks, and a
    sum of any subset is a unique bitmask of the contributing ranks."""
    return 2.0 ** np.arange(n)


def _decode_class(out, n) -> str:
    """Classify a tag-carrying twin output: every nonzero value must be
    an exact subset-sum of the rank tags; one bit set everywhere is
    ``single``, any multi-bit value is ``fold``."""
    v = np.asarray(out, np.float64).ravel()
    iv = np.rint(v).astype(np.int64)
    if not np.allclose(v, iv, atol=1e-6):
        raise ValueError(
            f"twin output is not tag-linear (values {v[:4]}...) — the "
            "provenance decode only holds for linear data movement"
        )
    if (iv < 0).any() or (iv >= (1 << n)).any():
        raise ValueError(
            f"twin output {iv.min()}..{iv.max()} outside the {n}-rank "
            "tag space"
        )
    nz = iv[iv != 0]
    if nz.size == 0:
        raise ValueError("twin output all-zero — tags never arrived")
    multi = (nz & (nz - 1)) != 0
    return FOLD if multi.any() else SINGLE


def _shmap(body, mesh, in_specs, out_specs):
    import jax

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    ))


def _h_all_gather(twin, n):
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    x = np.repeat(_tags(n), 4)[:, None] * np.ones((1, 128), np.float32)
    out = _shmap(lambda a: twin(a, "x", tiled=True),
                 mesh, P("x"), P("x"))(x.astype(np.float32))
    cls = _decode_class(out, n)
    if cls != SINGLE:
        raise ValueError(f"all_gather twin decoded as {cls}")
    return TwinProfile(SINGLE, "all", True,
                       "tags replicate, one source per element")


def _h_psum_scatter(twin, n):
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    # rank r's whole slab carries tag r; the scatter's output elements
    # must decode to the full-mesh bitmask (one fold per rank)
    x = (_tags(n)[:, None, None]
         * np.ones((1, 4 * n, 128), np.float32)).astype(np.float32)
    out = _shmap(
        lambda a: twin(a[0], "x", scatter_dimension=0, tiled=True),
        mesh, P("x"), P("x"),
    )(x)
    cls = _decode_class(out, n)
    if cls != FOLD:
        raise ValueError(f"psum_scatter twin decoded as {cls}")
    if not np.allclose(np.asarray(out), _tags(n).sum()):
        raise ValueError("psum_scatter twin missed a contribution")
    return TwinProfile(FOLD, "all", True, "full-mesh fold per element")


def _h_all_to_all(twin, n):
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    x = (_tags(n)[:, None, None]
         * np.ones((1, 4 * n, 128), np.float32)).astype(np.float32)
    out = _shmap(
        lambda a: twin(a[0], "x", split_axis=0, concat_axis=0,
                       tiled=True),
        mesh, P("x"), P("x"),
    )(x)
    cls = _decode_class(out, n)
    if cls != SINGLE:
        raise ValueError(f"all_to_all twin decoded as {cls}")
    return TwinProfile(SINGLE, "all", True,
                       "one block per source redistributed")


def _h_ag_gemm(twin, n):
    # B = identity passes the row tags straight through the GEMM: the
    # output provenance is the gathered-A workspace's provenance
    mesh = _mesh(n)
    k = 8
    a = np.repeat(_tags(n), 2)[:, None] * np.ones((1, k), np.float32)
    b = np.eye(k, dtype=np.float32)
    out = twin(a.astype(np.float32), b, mesh, "x")
    cls = _decode_class(out, n)
    if cls != SINGLE:
        raise ValueError(f"ag_gemm twin decoded as {cls}")
    return TwinProfile(SINGLE, "all", True,
                       "row tags survive B=I; gathered-A provenance")


def _h_gemm_rs(twin, n):
    # A's K-columns carry the owner rank's tag, B = ones/(K/n): each
    # rank's partial is exactly its tag, the scatter folds all of them
    mesh = _mesh(n)
    kc, m, nn = 2, 2 * n, 8
    a = np.repeat(_tags(n), kc)[None, :] * np.ones((m, 1), np.float32)
    b = np.full((n * kc, nn), 1.0 / kc, np.float32)
    out = twin(a.astype(np.float32), b, mesh, "x")
    if not np.allclose(np.asarray(out), _tags(n).sum()):
        raise ValueError("gemm_rs twin is not the exact sum of tags")
    return TwinProfile(FOLD, "all", True,
                       "partial per rank = tag, scatter folds all")


def _h_kv_ship(twin, n):
    # topology-agnostic device_put tree: values pass through unchanged
    # (single-source by construction); WHICH pairs ship is the caller's
    # placement choice, so the topology comes from the replay
    payload = {"q": (_tags(n)[:, None]
                     * np.ones((1, 8), np.float32)).astype(np.float32)}
    out = twin(payload, {"q": None})
    if not np.allclose(out["q"], payload["q"]):
        raise ValueError("kv_ship twin altered the payload")
    return TwinProfile(SINGLE, None, True,
                       "pass-through transport; topology from replay")


def _h_grouped_ag(twin, n):
    # the degraded MoE dispatch path (ops/moe_tp.ag_group_gemm_device):
    # all_gather the sorted token slab, then the grouped GEMM locally —
    # W = one identity expert keeps the gathered tags intact
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    k, rows = 8, 2
    x = np.repeat(_tags(n), rows)[:, None] * np.ones((1, k), np.float32)
    w = np.eye(k, dtype=np.float32)[None]
    splits = np.asarray([rows * n], np.int32)

    def body(a):
        g = jax.lax.all_gather(a, "x", tiled=True)
        return twin(g, w, splits)

    out = _shmap(body, mesh, P("x"), P("x"))(x.astype(np.float32))
    cls = _decode_class(out, n)
    if cls != SINGLE:
        raise ValueError(f"grouped AG twin decoded as {cls}")
    return TwinProfile(SINGLE, "all", True,
                       "gather-then-grouped-GEMM (degraded dispatch)")


def _h_grouped_rs(twin, n):
    # the degraded MoE combine path: grouped GEMM on the local partial,
    # then the reduce-scatter folds one contribution per rank
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    k, rows = 8, 2 * n
    x = (_tags(n)[:, None, None]
         * np.ones((1, rows, k), np.float32)).astype(np.float32)
    w = np.eye(k, dtype=np.float32)[None]
    splits = np.asarray([rows], np.int32)

    def body(a):
        y = twin(a[0], w, splits)
        return jax.lax.psum_scatter(y, "x", scatter_dimension=0,
                                    tiled=True)

    out = _shmap(body, mesh, P("x"), P("x"))(x)
    if not np.allclose(np.asarray(out), _tags(n).sum()):
        raise ValueError("grouped RS twin is not the exact sum of tags")
    return TwinProfile(FOLD, "all", True,
                       "grouped-GEMM-then-scatter (degraded combine)")


def _h_cp_attention(twin, n):
    # both CP schemes degrade onto dense attention over GATHERED kv
    # (registry: "gather KV, attend locally") — the transport leg is
    # the all_gather; the attention itself must run and stay finite
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n)
    s, d = 4, 8
    kv = (_tags(n)[:, None, None, None]
          * np.ones((1, s, 1, d), np.float32)).astype(np.float32)
    q = np.ones((1, n * s, 1, d), np.float32)

    def body(k_loc):
        k_full = jax.lax.all_gather(k_loc, "x", axis=1, tiled=True)
        o = twin(q, k_full, k_full, causal=True)
        return k_full, o

    k_full, o = _shmap(body, mesh, P("x", None, None, None),
                       (P("x"), P("x")))(kv)
    cls = _decode_class(k_full, n)
    if cls != SINGLE or not np.isfinite(np.asarray(o)).all():
        raise ValueError("cp twin's gathered-KV leg failed to decode")
    return TwinProfile(SINGLE, "all", True,
                       "KV gathered, attended locally (degraded CP)")


def _h_grad_ring(twin, n):
    # grad_allreduce_xla takes a REPLICATED operand (in_specs P()), so
    # per-rank tags cannot ride through it; the fold class is proved by
    # the exact ×n psum of a replicated unit slab instead
    mesh = _mesh(n)
    out = twin(np.ones((8, 128), np.float32), mesh, "x")
    if not np.allclose(np.asarray(out), float(n)):
        raise ValueError("grad ring twin is not the exact n-way psum")
    return TwinProfile(FOLD, "all", True,
                       "replicated psum = exact x n fold")


def _h_cp_decode(twin, n):
    # cp_lse_combine_xla shards its stacked slab operand over the cp
    # axis (in_specs P(axis)); rank r's whole contribution slab carries
    # tag r, so every reduced destination element must decode to the
    # full-mesh fold — a dropped rank is a token decoded against a
    # silently missing KV shard
    mesh = _mesh(n)
    m = 8
    x = (np.repeat(_tags(n), n * m)[:, None]
         * np.ones((1, 128), np.float32)).astype(np.float32)
    out = twin(x, mesh, "x")
    cls = _decode_class(out, n)
    if cls != FOLD:
        raise ValueError(f"cp decode combine twin decoded as {cls}")
    if not np.allclose(np.asarray(out), _tags(n).sum()):
        raise ValueError("cp decode combine twin missed a contribution")
    return TwinProfile(FOLD, "all", True,
                       "one weighted partial folded per cp rank")


def _h_ragged_local(twin, n):
    # a per-rank function: no mesh/axis operand at all. Execute at the
    # registry's lint geometry on one device so path rot still fails
    # loudly, then assert finiteness — INCLUDING the per-row topology
    # operand with a full TREE row, so a twin that dropped or broke
    # the masked path fails the profile instead of silently agreeing
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_GEOM as g,
        causal_topologies,
        tree_topology_row,
    )

    pool = np.ones((g["npages"], g["hkv"], g["page"], g["d"]), np.float32)
    topo = causal_topologies(g["r"], g["topo_w"])
    # row 1: frontier + 7 nodes, two branches off the frontier — every
    # packed position occupied, so the tree row stays finite
    topo[1] = tree_topology_row([-1, 0, 0, 2, 3, 4, 5], g["topo_w"])
    out = twin(
        np.ones((g["hkv"], g["t"] * g["g"], g["d"]), np.float32),
        pool, pool,
        np.asarray([12, 8], np.int32), np.asarray([8, 8], np.int32),
        np.asarray([0, 8], np.int32),
        np.arange(g["r"] * g["pps"], dtype=np.int32)
        .reshape(g["r"], g["pps"]),
        group=g["g"], topologies=topo,
    )
    out, _lse = out                        # (attention out, per-row LSE)
    if not np.isfinite(np.asarray(out)).all():
        raise ValueError("ragged local twin produced non-finite output")
    return TwinProfile(LOCAL, None, True,
                       "per-rank function, no mesh operand")


def _h_kv_append_local(twin, n):
    """The pool append's twin is a per-rank row scatter over the
    rank's own pool: rows named land, every other row keeps its bytes,
    nothing crosses ranks."""
    del n
    import jax.numpy as jnp

    pool = jnp.zeros((2, 2, 8, 16), jnp.float32)
    out = np.asarray(twin(pool, jnp.ones((3, 16), jnp.float32),
                          jnp.asarray([0, 9, pool[..., 0].size])))
    if out.shape != pool.shape or out.sum() != 2 * 16:
        raise ValueError("kv_append local twin dropped or invented rows")
    return TwinProfile(LOCAL, None, True,
                       "per-rank function, no mesh operand")


#: harness key → runner. Keys are the DEGRADATION_TARGETS dotted paths,
#: except where one twin serves families of different classes (the
#: grouped GEMM) — those disambiguate through _twin_key.
_NATIVE = "triton_distributed_tpu.tools.native."
_HARNESSES = {
    "jax.lax.all_gather": _h_all_gather,
    "jax.lax.psum_scatter": _h_psum_scatter,
    "jax.lax.all_to_all": _h_all_to_all,
    _NATIVE + "xla_ag_gemm": _h_ag_gemm,
    _NATIVE + "xla_gemm_rs": _h_gemm_rs,
    _NATIVE + "xla_kv_ship": _h_kv_ship,
    "grouped_matmul_xla:ag": _h_grouped_ag,
    "grouped_matmul_xla:rs": _h_grouped_rs,
    "triton_distributed_tpu.kernels.ring_attention."
    "dense_attention_reference": _h_cp_attention,
    "triton_distributed_tpu.train.grad_wire.grad_allreduce_xla":
        _h_grad_ring,
    "triton_distributed_tpu.kernels.flash_decode.cp_lse_combine_xla":
        _h_cp_decode,
    "triton_distributed_tpu.kernels.ragged_paged_attention."
    "ragged_paged_attention_xla": _h_ragged_local,
    "triton_distributed_tpu.kernels.kv_append.append_rows_xla":
        _h_kv_append_local,
}

#: fallback class table for hosts without n devices (profile marked
#: executed=False; realization and the SL012/SL013 diffs still run)
_STATIC_CLASS = {
    "jax.lax.all_gather": (SINGLE, "all"),
    "jax.lax.psum_scatter": (FOLD, "all"),
    "jax.lax.all_to_all": (SINGLE, "all"),
    _NATIVE + "xla_ag_gemm": (SINGLE, "all"),
    _NATIVE + "xla_gemm_rs": (FOLD, "all"),
    _NATIVE + "xla_kv_ship": (SINGLE, None),
    "grouped_matmul_xla:ag": (SINGLE, "all"),
    "grouped_matmul_xla:rs": (FOLD, "all"),
    "triton_distributed_tpu.kernels.ring_attention."
    "dense_attention_reference": (SINGLE, "all"),
    "triton_distributed_tpu.train.grad_wire.grad_allreduce_xla":
        (FOLD, "all"),
    "triton_distributed_tpu.kernels.flash_decode.cp_lse_combine_xla":
        (FOLD, "all"),
    "triton_distributed_tpu.kernels.ragged_paged_attention."
    "ragged_paged_attention_xla": (LOCAL, None),
    "triton_distributed_tpu.kernels.kv_append.append_rows_xla":
        (LOCAL, None),
}


def _twin_key(path: str, family_name: str | None) -> str:
    """The grouped GEMM backs both MoE pipeline stages; the degraded
    op path composed around it differs (gather-then-GEMM vs
    GEMM-then-scatter, ops/moe_tp.py), so the harness key carries the
    stage."""
    if path.endswith("group_gemm.grouped_matmul_xla"):
        stage = "rs" if "reduce_rs" in (family_name or "") else "ag"
        return f"grouped_matmul_xla:{stage}"
    return path


@functools.lru_cache(maxsize=None)
def _run_twin(key: str, n: int) -> TwinProfile:
    import jax

    from triton_distributed_tpu.kernels.registry import (
        resolve_degradation_target,
    )

    if key not in _HARNESSES:
        raise ValueError(
            f"no twin harness for degradation target {key!r} — contract "
            "inference cannot profile it (add a harness in "
            "analysis/contract_infer.py)"
        )
    path = key.split(":")[0]
    if ":" in key:
        path = ("triton_distributed_tpu.kernels.group_gemm."
                "grouped_matmul_xla")
    twin = resolve_degradation_target(path)   # existence proof either way
    if len(jax.devices()) < n:
        cls, sources = _STATIC_CLASS[key]
        return TwinProfile(
            cls, sources, False,
            f"{len(jax.devices())} device(s) < mesh {n}: static class "
            "table (twin resolved but not executed)",
        )
    return _HARNESSES[key](twin, n)


def twin_profile(degrades_to: str, n: int,
                 family_name: str | None = None) -> TwinProfile:
    """Execute (or table-classify) the twin behind a DEGRADATION_TARGETS
    dotted path on an ``n``-rank mesh with rank-tagged inputs."""
    return _run_twin(_twin_key(degrades_to, family_name), n)


# ----------------------------------------------------------- realization

def _observe_root(rec, state, root):
    """Per-rank classification of one root's contrib nibbles: exact
    per-source single-marker counts, full-fold counts, empties."""
    n = rec.n
    full_mask = sum(np.int64(1) << (_NIBBLE * s) for s in range(n))
    per_rank = []
    for rank in range(n):
        st = state.get(rank, root)
        c = st["contrib"]
        counts = {
            s: int((c == (np.int64(1) << (_NIBBLE * s))).sum())
            for s in range(n)
        }
        per_rank.append({
            "counts": counts,
            "fold": int((c == full_mask).sum()) if n > 1
            else int((c != 0).sum()),
            "empty": int((c == 0).sum()),
            "total": int(c.size),
        })
    return per_rank


def _class_mass(per_rank, cls, n) -> int:
    """How many elements of a root exhibit the twin class's delivery
    pattern, summed over ranks. ``single`` counts FOREIGN singles only
    (own-written compute buffers must not outscore the transport dst);
    ``local`` counts own singles on roots no foreign byte ever
    touched."""
    if cls == FOLD:
        return sum(o["fold"] for o in per_rank)
    if cls == SINGLE:
        return sum(
            c for rank, o in enumerate(per_rank)
            for s, c in o["counts"].items() if s != rank
        )
    for rank, o in enumerate(per_rank):
        if any(c for s, c in o["counts"].items() if s != rank):
            return 0
    return sum(o["counts"][rank] for rank, o in enumerate(per_rank))


def _modal(values):
    vals = [v for v in values if v > 0]
    if not vals:
        return 0
    uniq, counts = np.unique(np.asarray(vals), return_counts=True)
    return int(uniq[np.argmax(counts)])


def _realize(rec, state, profile, declared):
    """Pick the dst root that exhibits the twin class and read the
    concrete contract quantities off its provenance. Returns
    (dst_root or None, observation dict, dst-mismatch findings)."""
    n = rec.n
    findings = []
    kernel, site = rec.info.kernel, rec.info.site
    cands = [
        root for root, meta in rec.ref_meta.items()
        if meta.dtype is not None and not meta.is_input
        and int(np.prod(meta.shape)) > 0
    ]
    obs = {root: _observe_root(rec, state, root) for root in cands}
    declared_root = None
    if declared is not None:
        declared_root = dataflow._resolve_dst(rec, declared.dst)
        if declared_root not in obs:
            obs[declared_root] = _observe_root(rec, state, declared_root)
    scores = {
        root: _class_mass(per, profile.cls, n) for root, per in obs.items()
    }
    # ties broken toward the widest dtype: a quantized wire workspace
    # matches the delivery pattern element-for-element with the
    # dequantized destination, but the contract belongs to the latter
    best = max(
        (root for root in scores if scores[root] > 0),
        key=lambda r: (scores[r],
                       np.dtype(rec.ref_meta[r].dtype).itemsize, r),
        default=None,
    )
    # the declared dst wins as long as it realizes the class at all —
    # secondary roots (landed metadata, scale planes) can carry MORE
    # pattern-matching elements without being the payload destination
    if declared_root is not None and scores.get(declared_root, 0) > 0:
        dst = declared_root
    elif declared_root is not None and best is not None:
        dst = best
        findings.append(Finding(
            "SL012", kernel,
            f"declared contract dst {declared_root!r} exhibits none of "
            f"the twin's '{profile.cls}' delivery pattern, but "
            f"{best!r} does ({scores[best]} element(s)) — the declared "
            "destination is wrong"
            + ("" if profile.executed else " [twin class from static "
               "table; no devices to execute it]"),
            site=site,
        ))
    else:
        dst = best
    return dst, obs, findings


def _infer_single(rec, per_rank, dst, profile):
    """Concrete gather/permute quantities at the chosen dst."""
    n = rec.n
    dst_elems = int(np.prod(rec.ref_meta[dst].shape))
    senders = {
        rank: {s for s, c in o["counts"].items() if c > 0}
        for rank, o in enumerate(per_rank)
    }
    payload = _modal(
        c for o in per_rank for c in o["counts"].values()
    )
    own_absent = (
        all(o["counts"][rank] == 0 for rank, o in enumerate(per_rank))
        and any(senders.values())
    )
    full = all(
        o["empty"] == 0 or (own_absent and o["empty"] == payload)
        for o in per_rank
    )
    all_sources = all(
        senders[rank] >= (set(range(n)) - ({rank} if own_absent else set()))
        for rank in range(n)
    )
    kind = "gather" if (all_sources and full) else "permute"
    src_only = None
    if profile.sources is None:
        observed = {r: frozenset(s) for r, s in senders.items()}
        if any(s != set(range(n)) for s in senders.values()):
            src_only = (lambda m: lambda rank, n_: m[rank])(observed)
    payload_fn = None
    if payload and payload != dst_elems // n:
        payload_fn = (lambda v: lambda n_: v)(payload)
    contract = DeliveryContract(
        kind=kind, dst=dst, payload_per_src=payload_fn, full=full,
        own_absent_ok=own_absent, src_only=src_only,
    )
    return contract, {
        "senders": senders, "payload": payload,
        "own_absent": own_absent, "full": full,
    }


def _diff_single(rec, declared, per_rank, dst, profile, q):
    """SL012 facets of a single-class (gather/permute) realization
    against the declaration."""
    n = rec.n
    findings = []
    kernel, site = rec.info.kernel, rec.info.site
    tabled = ("" if profile.executed
              else " [twin class from static table]")
    dst_elems = int(np.prod(rec.ref_meta[dst].shape))
    expect = (
        declared.payload_per_src(n) if declared.payload_per_src
        else dst_elems // n
    )
    if q["payload"] and expect != q["payload"]:
        findings.append(Finding(
            "SL012", kernel,
            f"declared payload_per_src={expect} but the replay lands "
            f"{q['payload']} element(s) per (rank, source) in {dst} — "
            f"the contract {'over' if expect > q['payload'] else 'under'}"
            f"-declares each source's delivery{tabled}",
            site=site,
        ))
    for rank in range(n):
        declared_set = (
            set(declared.src_only(rank, n))
            if declared.src_only is not None else set(range(n))
        )
        got = q["senders"][rank]
        extra = got - declared_set
        allow_own = {rank} if (declared.own_absent_ok
                               or q["own_absent"]) else set()
        missing = declared_set - got - allow_own
        if extra:
            findings.append(Finding(
                "SL012", kernel,
                f"source rank(s) {sorted(extra)} deliver into rank "
                f"{rank}'s {dst} but sit OUTSIDE the declared source "
                f"topology {sorted(declared_set)}{tabled}",
                site=site, ranks=(rank,),
            ))
        if missing:
            findings.append(Finding(
                "SL012", kernel,
                f"declared source rank(s) {sorted(missing)} never "
                f"deliver into rank {rank}'s {dst} — the declared "
                f"topology over-promises{tabled}",
                site=site, ranks=(rank,),
            ))
    if declared.full != q["full"]:
        findings.append(Finding(
            "SL012", kernel,
            f"declared full={declared.full} but the replay shows "
            f"full={q['full']} coverage of {dst} "
            f"({'holes remain' if declared.full else 'every element is covered'})"
            f"{tabled}",
            site=site,
        ))
    # own-absence only drifts when the declared topology actually
    # expects own delivery — a src_only that already excludes the own
    # rank (kv_ship's disjoint pairs) declares the absence structurally,
    # which is exactly how SL008's want=0 branch reads it
    own_expected = any(
        rank in (set(declared.src_only(rank, n))
                 if declared.src_only is not None else {rank})
        for rank in range(n)
    )
    if q["own_absent"] and own_expected and not declared.own_absent_ok:
        findings.append(Finding(
            "SL012", kernel,
            f"no rank ever publishes its OWN chunk into {dst} yet the "
            "declared contract does not set own_absent_ok — the "
            f"declaration and the kernel disagree{tabled}",
            site=site,
        ))
    return findings


def _infer_topo_meta(rec) -> dict | None:
    """Detect a per-row attention-topology operand from the replay's
    input signature. The ragged family's scalar-prefetch block is a
    leading run of int32 inputs — table ``(R, pps)``, then the three
    per-row vectors ``kv_lens``/``q_lens``/``q_starts`` of length R.
    When a FIFTH leading int32 input follows with shape
    ``(R, 2 + 2W)``, it is the topology descriptor: the inferred LOCAL
    contract carries the masked-coverage facet so an UNDECLARED family
    still gets its descriptors value-checked."""
    metas = sorted(
        (m for m in rec.ref_meta.values() if m.is_input),
        key=lambda m: m.index,
    )
    if len(metas) < 5:
        return None
    lead = metas[:5]
    if not all(m.dtype == np.dtype(np.int32) for m in lead):
        return None
    if len(lead[1].shape) != 1:
        return None
    rows = lead[1].shape[0]
    tshape = lead[4].shape
    if len(tshape) != 2 or tshape[0] != rows:
        return None
    w = (tshape[1] - 2) // 2
    if w < 1 or tshape[1] != 2 + 2 * w:
        return None
    return {"ref": 4, "kv_lens": 1, "q_lens": 2, "width": int(w)}


def infer_from_replay(rec, sim, state, *, degrades_to,
                      declared=None) -> InferenceResult:
    """The core diff: profile the twin, realize the contract from the
    replayed provenance, and compare against the declaration (SL012) or
    synthesize the missing one (SL013)."""
    kernel, site = rec.info.kernel, rec.info.site
    profile = twin_profile(degrades_to, rec.n, family_name=kernel)
    tabled = "" if profile.executed else " [twin class from static table]"
    findings: list = []

    if declared is not None:
        declared_cls = _KIND_CLASS.get(declared.kind)
        if declared_cls != profile.cls:
            findings.append(Finding(
                "SL012", kernel,
                f"declared kind {declared.kind!r} is class "
                f"{declared_cls!r} but the XLA twin ({degrades_to}) "
                f"delivers class {profile.cls!r} ({profile.detail}) — "
                f"the declared contract checks the wrong shape{tabled}",
                site=site,
            ))
            # realize against the twin's class anyway: the synthesized
            # contract (not the wrong declaration) is what SL008 needs

    dst, obs, dst_findings = _realize(rec, state, profile, declared)
    findings += dst_findings
    if dst is None:
        findings.append(Finding(
            "SL012" if declared is not None else "SL013", kernel,
            f"no root buffer exhibits the twin's '{profile.cls}' "
            f"delivery pattern ({profile.detail}) — the kernel's replay "
            f"and its degradation target disagree entirely{tabled}",
            site=site,
        ))
        return InferenceResult(profile, None, None, findings, obs)

    per_rank = obs[dst]
    if profile.cls == FOLD:
        contract = DeliveryContract(kind="reduce", dst=dst)
        quantities = {}
    elif profile.cls == LOCAL:
        full = all(o["empty"] == 0 for o in per_rank)
        topo_meta = _infer_topo_meta(rec)
        contract = DeliveryContract(kind="local", dst=dst, full=full,
                                    topo=topo_meta)
        quantities = {"full": full, "topo": topo_meta}
        if declared is not None and _KIND_CLASS.get(declared.kind) == LOCAL:
            if declared.full != full:
                findings.append(Finding(
                    "SL012", kernel,
                    f"declared full={declared.full} but the replay shows "
                    f"full={full} own-write coverage of {dst}{tabled}",
                    site=site,
                ))
            dt = getattr(declared, "topo", None)
            if (dt is None) != (topo_meta is None):
                have = "a" if topo_meta else "no"
                want = "one" if dt else "none"
                findings.append(Finding(
                    "SL012", kernel,
                    f"the replay's input signature shows {have} per-row "
                    f"attention-topology operand but the declared "
                    f"contract carries {want} — the masked-coverage "
                    f"facet would check the wrong operand set{tabled}",
                    site=site,
                ))
            elif dt is not None and topo_meta is not None and \
                    int(dt.get("width", -1)) != topo_meta["width"]:
                findings.append(Finding(
                    "SL012", kernel,
                    f"declared topology width {dt.get('width')} drifted "
                    f"from the replay's descriptor width "
                    f"{topo_meta['width']}{tabled}",
                    site=site,
                ))
    else:
        contract, quantities = _infer_single(rec, per_rank, dst, profile)
        if declared is not None \
                and _KIND_CLASS.get(declared.kind) == SINGLE:
            findings += _diff_single(
                rec, declared, per_rank, dst, profile, quantities)

    if declared is None:
        findings.append(Finding(
            "SL013", kernel,
            f"family registered with NO declared DeliveryContract — "
            f"inferred a {contract.kind!r} contract on {dst!r} from the "
            f"XLA twin ({degrades_to}: {profile.detail}) so SL008 "
            "still runs; declare the contract in kernels/registry.py "
            f"to pin it{tabled}",
            site=site,
        ))
    return InferenceResult(profile, dst, contract, findings,
                           {"roots": obs, "quantities": quantities})


def infer_spec(rec, *, degrades_to, declared=None) -> InferenceResult:
    """Inference over an already-recorded symbolic run (fixtures and
    tests): simulate, replay provenance, then diff."""
    sim = checks.simulate(rec)
    if not sim.completed:
        # a wedged protocol has no terminal provenance to realize; the
        # SL002/SL003 findings from the protocol pass own this case
        profile = twin_profile(degrades_to, rec.n,
                               family_name=rec.info.kernel)
        return InferenceResult(profile, None, None, [], {})
    state, _puts, _wire = dataflow.replay_provenance(rec, sim)
    return infer_from_replay(
        rec, sim, state, degrades_to=degrades_to, declared=declared)


def infer_family(fam, n: int = 8, rec=None) -> InferenceResult:
    """Infer one registry family's contract at mesh ``n``. ``rec`` can
    reuse the recorder lint already produced; otherwise the family is
    re-analyzed symbolically."""
    if not fam.degrades_to:
        raise ValueError(
            f"family {fam.name!r} declares no degradation target — "
            "nothing to infer from (missing_degradation_targets() "
            "polices this)"
        )
    if rec is None:
        from triton_distributed_tpu.analysis import lint

        rec, _ = lint.analyze_family(fam, n)
    return infer_spec(rec, degrades_to=fam.degrades_to,
                      declared=fam.contract)


def verify_declared_contracts(n: int = 4, kernels=None) -> list:
    """Run inference over every registered family and return the
    SL012/SL013 findings — the TDTPU_LINT_STRICT registration gate and
    the ci/fast.sh smoke step both call this."""
    from triton_distributed_tpu.kernels.registry import families

    findings = []
    for name, fam in sorted(families().items()):
        if kernels and not any(k in name for k in kernels):
            continue
        findings += infer_family(fam, n).findings
    return findings
