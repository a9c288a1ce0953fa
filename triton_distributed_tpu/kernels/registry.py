"""Registry of SHMEM kernel families for static analysis (shmemlint).

Each :class:`KernelFamily` names one protocol the kernel library ships
and knows how to *construct* it through the real builder (so the
analyzer sees the exact kernel partial, scratch semaphores,
collective_id and VMEM limits production uses — captured by the
``lang.launch.shmem_call`` hook) plus the per-device input shapes the
capture cannot know. Shapes are small lint shapes: the protocol under
analysis (signal/wait structure, slot indexing, barrier usage) is
shape-generic; only the region arithmetic needs concrete numbers.

Builders are lru-cached, so every build call gets a fresh
``("shmemlint", token)`` in an unused key argument — guaranteeing the
captured LaunchSpec was produced by THIS build, not a stale cache hit
from another configuration.

Central collective-id ledger: the ids below are the ones the op entries
default to. ``analysis.lint`` cross-checks uniqueness across families
(rule SL005) — a new family colliding with an existing id fails lint
instead of deadlocking a rendezvous at runtime (ADVICE r5: gemm_rs's
+96 chunk rail vs ag_gemm's +64 rail).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


# ------------------------------------------------- collective-id offset rails
#
# Chunked engines (ag_gemm's DCN rail, gemm_rs's column chunks) need one
# DISTINCT collective_id per chunk ring — a skewed neighbor's chunk-s+1
# barrier signal must not satisfy a chunk-s wait. Offsets used to be
# allocated ad hoc (+64 here, +96 there) with disjointness maintained by
# comment (ADVICE r5); this ledger makes it a checked invariant: every
# rail reserves an [offset, offset+length) range at import, overlapping
# reservations raise immediately, and the id arithmetic goes through
# :func:`rail_collective_id` so no call site can silently stray outside
# its reservation.

_RAILS: dict = {}


def reserve_collective_rail(name: str, offset: int, length: int) -> None:
    """Reserve the offset range [offset, offset+length) for ``name``'s
    per-chunk collective ids. Overlap with any existing reservation is a
    programming error and raises at import time — the static twin of the
    SL005 runtime-collision rule."""
    assert length > 0
    for other, (off, ln) in _RAILS.items():
        if other == name:
            continue
        if offset < off + ln and off < offset + length:
            raise ValueError(
                f"collective-id rail {name!r} [{offset}, {offset + length}) "
                f"overlaps {other!r} [{off}, {off + ln}) — chunk barriers "
                "of the two families would satisfy each other's rendezvous"
            )
    prev = _RAILS.get(name)
    if prev is not None and prev != (offset, length):
        raise ValueError(
            f"collective-id rail {name!r} re-reserved with a different "
            f"range: {prev} vs {(offset, length)}"
        )
    _RAILS[name] = (offset, length)


def rail_collective_id(name: str, collective_id, chunk: int):
    """The collective_id of chunk ring ``chunk`` on rail ``name``
    (None passes through — the degenerate no-barrier path)."""
    off, length = _RAILS[name]
    if not 0 <= chunk < length:
        raise ValueError(
            f"rail {name!r}: chunk {chunk} outside the reserved length "
            f"{length} — widen the reservation, don't improvise offsets"
        )
    return None if collective_id is None else collective_id + off + chunk


def reserved_rails() -> dict:
    """Snapshot of the ledger (name → (offset, length)), for tests."""
    return dict(_RAILS)


#: the rails the fused engines ship with. Bases are the op entries'
#: default collective_ids (single digits), so offsets start high enough
#: that base ids can never land inside a rail.
reserve_collective_rail("ag_gemm.dcn_chunks", 64, 32)
reserve_collective_rail("gemm_rs.dcn_chunks", 96, 32)


@dataclass(frozen=True)
class KernelFamily:
    """One analyzable kernel family.

    ``build(mesh, n, token)`` constructs the kernel via its real
    builder (mesh may be a ``jax.sharding.AbstractMesh`` — nothing is
    executed); ``launch_name`` is the ``shmem_call`` name to read the
    captured :class:`~triton_distributed_tpu.lang.launch.LaunchSpec`
    back under; ``in_shapes(n)`` gives per-device input (shape, dtype)
    pairs; ``init(n)`` optionally seeds ref contents by name or
    positional index (count-carrying protocols need representative
    values to steer their receive loops).

    ``contract`` declares the family's DELIVERY contract (gather /
    reduce / all-to-all permutation — see ``analysis.dataflow.
    DeliveryContract``): what the destination buffer must provably hold
    at termination. The SL008 pass is driven entirely by this table —
    a family with no contract still gets the protocol and wire-rail
    passes, but delivery completeness is only as strong as what is
    declared here.
    """

    name: str
    site: str | None
    launch_name: str
    build: callable
    in_shapes: callable
    init: callable = None
    axis: str = "x"
    mesh_axes: tuple = ("x",)
    contract: object = None
    # dotted path of the XLA twin this family degrades onto (the
    # with_fallback / health-probation target). Filled from
    # DEGRADATION_TARGETS in families(); a registered family without
    # one is a silent-gap lint error (bench.py --lint).
    degrades_to: str | None = None


#: family name → dotted path of its declared degradation target. Every
#: registered family MUST appear here (or set degrades_to directly):
#: ``bench.py --lint`` fails on a family whose degraded path is
#: undeclared or unresolvable — the silent-gap class where a fused
#: engine has no tested place to fall when the health ledger demotes it.
DEGRADATION_TARGETS = {
    "allgather.ring_1d": "jax.lax.all_gather",
    "allgather.ring_bidir": "jax.lax.all_gather",
    "allgather.ll_small": "jax.lax.all_gather",
    "allgather.ll_persist": "jax.lax.all_gather",
    "allgather.ring_1d_fp8w": "jax.lax.all_gather",
    "reduce_scatter.ring": "jax.lax.psum_scatter",
    "reduce_scatter.stream": "jax.lax.psum_scatter",
    "reduce_scatter.ring_fp8w": "jax.lax.psum_scatter",
    "reduce_scatter.stream_int8w": "jax.lax.psum_scatter",
    "all_to_all.dense": "jax.lax.all_to_all",
    "ag_gemm.fused": "triton_distributed_tpu.tools.native.xla_ag_gemm",
    "ag_gemm.fused_fp8w": "triton_distributed_tpu.tools.native.xla_ag_gemm",
    "ag_gemm.fused_int8mxw":
        "triton_distributed_tpu.tools.native.xla_ag_gemm",
    "gemm_rs.fused": "triton_distributed_tpu.tools.native.xla_gemm_rs",
    "gemm_rs.fused_fp8w": "triton_distributed_tpu.tools.native.xla_gemm_rs",
    "moe_tp.ag_group_gemm":
        "triton_distributed_tpu.kernels.group_gemm.grouped_matmul_xla",
    "moe_tp.ag_group_gemm_fp8w":
        "triton_distributed_tpu.kernels.group_gemm.grouped_matmul_xla",
    "moe_tp.ag_group_gemm_int8mxw":
        "triton_distributed_tpu.kernels.group_gemm.grouped_matmul_xla",
    "moe_tp.reduce_rs":
        "triton_distributed_tpu.kernels.group_gemm.grouped_matmul_xla",
    "moe_tp.reduce_rs_fp8w":
        "triton_distributed_tpu.kernels.group_gemm.grouped_matmul_xla",
    "flash_decode.ragged_paged":
        "triton_distributed_tpu.kernels.ragged_paged_attention."
        "ragged_paged_attention_xla",
    "flash_decode.ragged_paged_window":
        "triton_distributed_tpu.kernels.ragged_paged_attention."
        "ragged_paged_attention_xla",
    "kv_ship.pages": "triton_distributed_tpu.tools.native.xla_kv_ship",
    # the pool append's XLA twin is the row scatter serving_step keeps
    # for head-sharded pools and use_pallas=False
    "kv_append.pages":
        "triton_distributed_tpu.kernels.kv_append.append_rows_xla",
    "moe_dispatch.a2a": "jax.lax.all_to_all",
    "moe_combine.a2a": "jax.lax.all_to_all",
    # training: both CP schemes degrade onto dense attention (gather KV,
    # attend locally — exact, no ring to deadlock); the grad ring onto
    # the plain-psum all-reduce (exact bf16 wire, no quantization)
    "cp.ring_attention":
        "triton_distributed_tpu.kernels.ring_attention."
        "dense_attention_reference",
    "cp.ulysses":
        "triton_distributed_tpu.kernels.ring_attention."
        "dense_attention_reference",
    "grad_ring.stream_int8w":
        "triton_distributed_tpu.train.grad_wire.grad_allreduce_xla",
    "cp_decode.lse_combine":
        "triton_distributed_tpu.kernels.flash_decode.cp_lse_combine_xla",
}


def resolve_degradation_target(path: str):
    """Import the object behind a DEGRADATION_TARGETS dotted path (or
    raise) — the lint gate's existence proof that the declared fallback
    is real, not a typo."""
    import importlib

    mod_name, _, attr = path.rpartition(".")
    mod = importlib.import_module(mod_name)
    return getattr(mod, attr)


def missing_degradation_targets() -> tuple:
    """(family, problem) pairs for every registered family whose
    degradation target is undeclared or fails to import. Empty means
    the bidirectional degradation matrix (docs/ROBUSTNESS.md) has no
    silent gaps; ``bench.py --lint`` and ci/fast.sh enforce empty."""
    out = []
    for name, fam in families().items():
        if not fam.degrades_to:
            out.append((name, "no declared degradation target"))
            continue
        try:
            resolve_degradation_target(fam.degrades_to)
        except Exception as e:  # noqa: BLE001 — report, don't crash lint
            out.append(
                (name, f"target {fam.degrades_to!r} unresolvable: {e}"))
    return tuple(out)


_F32 = np.dtype(np.float32)
_I32 = np.dtype(np.int32)
_I8 = np.dtype(np.int8)


def _f8():
    import ml_dtypes

    return np.dtype(ml_dtypes.float8_e4m3fn)


# ----------------------------------------------------------------- builders

def _ag(method):
    def build(mesh, n, token):
        import jax.numpy as jnp

        from triton_distributed_tpu.kernels.allgather import (
            _build_all_gather,
        )

        _build_all_gather(
            mesh, "x", method, (8 * n, 128), jnp.dtype(jnp.float32), 2,
            token,
        )

    return build


def _ag_ll_persist(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.allgather import _build_ll_persist

    _build_ll_persist(
        mesh, "x", 8, 128, jnp.dtype(jnp.float32), 12, token,
    )


def _rs_ring(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_reduce_scatter,
    )

    _build_reduce_scatter(
        mesh, "x", (8 * n, 128), jnp.dtype(jnp.float32), False, 3, token
    )


def _rs_stream(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_rs_stream,
    )

    _build_rs_stream(
        mesh, "x", 8 * n, 128, jnp.dtype(jnp.float32), False, 3, token
    )


def _rs_stream_w(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_rs_stream_w,
    )

    # wide lint columns: the streaming wire's per-chunk scale planes
    # only compress when the chunk payload dwarfs them (entry gate)
    _build_rs_stream_w(
        mesh, "x", 8 * n, 2048, jnp.dtype(jnp.float32), False, 3, token,
        "int8",
    )


def _a2a(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.all_to_all import _build_a2a_call

    _build_a2a_call(
        ("x",), "x", n, (8 * n, 128), jnp.dtype(jnp.float32), 4, token
    )


def _ag_gemm(mesh, n, token, wire=None):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.ag_gemm import _build_fused

    _build_fused(
        mesh, "x", (), (16 * n, 128), (128, 64 * n),
        jnp.dtype(jnp.float32), jnp.dtype(jnp.float32), 5, token,
        return_gathered=True, wire=wire,
    )


def _gemm_rs(mesh, n, token, wire=None):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.gemm_rs import _build_fused

    _build_fused(
        mesh, "x", (), (16 * n, 128 * n), (128 * n, 64),
        jnp.dtype(jnp.float32), jnp.dtype(jnp.float32), 6, token,
        wire=wire,
    )


def _ag_ring_w(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.allgather import _build_all_gather
    from triton_distributed_tpu.runtime import AllGatherMethod

    # wider lint columns than the raw twin: the standalone rings carry
    # PER-ROW scale planes (512 B/row), which only compress when the
    # row payload dwarfs them — exactly the entry's eligibility gate
    _build_all_gather(
        mesh, "x", AllGatherMethod.RING_1D, (8 * n, 2048),
        jnp.dtype(jnp.float32), 2, token, wire="fp8",
    )


def _rs_ring_w(mesh, n, token):
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_reduce_scatter_w,
    )

    _build_reduce_scatter_w(
        mesh, "x", (8 * n, 2048), jnp.dtype(jnp.float32), False, 3, token,
        "fp8",
    )


#: lint geometry for the moe_tp fused pair: 8-row routing blocks, 16-row
#: per-shard sorted slabs, tiny K/N/F/H of 128, 2 experts per rank.
_MOE_TP_GEOM = dict(bm=8, cap=16, k=128, nl=128, fl=128, h=128, e=2)


def _moe_tp_blocks():
    from triton_distributed_tpu.kernels.moe_tp_fused import pick_gg_blocks

    g = _MOE_TP_GEOM
    return pick_gg_blocks(g["bm"], g["cap"], g["k"], g["nl"], 4)


def _moe_ag_gg(wire):
    def build(mesh, n, token):
        import jax.numpy as jnp

        from triton_distributed_tpu.kernels.moe_tp_fused import (
            build_ag_group_gemm_call,
        )

        g = _MOE_TP_GEOM
        build_ag_group_gemm_call(
            n, ("x",), "x", g["cap"], g["k"], g["nl"], g["e"],
            _moe_tp_blocks(), jnp.dtype(jnp.float32), 13, wire=wire,
        )
        _capture_token(token)

    return build


def _moe_rs(wire):
    def build(mesh, n, token):
        import jax.numpy as jnp

        from triton_distributed_tpu.kernels.moe_tp_fused import (
            build_moe_reduce_rs_call,
        )

        g = _MOE_TP_GEOM
        build_moe_reduce_rs_call(
            n, ("x",), "x", g["cap"], g["fl"], g["h"], g["e"],
            _moe_tp_blocks(), jnp.dtype(jnp.float32), 12, wire=wire,
        )
        _capture_token(token)

    return build


def _capture_token(token):
    """The moe_tp builders are not lru-cached (shmem_call is constructed
    directly), so the freshness token is consumed here only to keep the
    build signature uniform."""
    del token


def _moe_ag_gg_shapes(wire):
    def in_shapes(n):
        g = _MOE_TP_GEOM
        if wire == "int8-mxu":
            # no bf16 slab at all: quantized tokens + per-routing-block
            # scale plane + per-(expert, out-channel) quantized weights
            return [
                ((n, g["cap"] // g["bm"]), _I32),      # be (SMEM)
                ((g["cap"], g["k"]), _I8),             # quantized slab
                ((g["cap"] // g["bm"], 128), _F32),    # scale plane
                ((g["e"], g["k"], g["nl"]), _I8),      # quantized weights
                ((g["e"], 1, g["nl"]), _F32),          # weight scales
            ]
        shapes = [
            ((n, g["cap"] // g["bm"]), _I32),          # be (SMEM)
            ((g["cap"], g["k"]), _F32),                # sorted slab
        ]
        if wire:
            shapes += [
                ((g["cap"], g["k"]), _f8()),           # quantized slab
                ((1, 128), _F32),                      # scale plane
            ]
        shapes.append(((g["e"], g["k"], g["nl"]), _F32))   # expert weights
        return shapes

    return in_shapes


def _moe_rs_shapes(n):
    g = _MOE_TP_GEOM
    return [
        ((n, g["cap"] // g["bm"]), _I32),              # be (SMEM)
        ((n * g["cap"], g["fl"]), _F32),               # per-shard sorted y
        ((g["e"], g["fl"], g["h"]), _F32),             # expert weights
    ]


def _kv_ship(mesh, n, token):
    """The disaggregated-serving KV page ship (kernels/kv_ship.py):
    pairwise prefill→decode page transfers on the quantized wire —
    int8 page payloads + per-row f32 scale planes as dual DMA rails,
    landing at the receiver's block-table-assigned slots."""
    from triton_distributed_tpu.kernels.kv_ship import build_lint_kernel

    build_lint_kernel(mesh, n, token=(token, n))


def _kv_ship_in_shapes(n):
    from triton_distributed_tpu.kernels.kv_ship import KV_SHIP_GEOM as g

    del n
    rows = g["pages"] * g["rows"]
    return [
        ((g["pages"],), _I32),               # landing page table (SMEM)
        ((rows, g["cols"]), _I8),            # staged page payload
        ((rows, 128), _F32),                 # per-row scale planes
    ]


def _kv_ship_init(n):
    from triton_distributed_tpu.kernels.kv_ship import KV_SHIP_GEOM as g

    del n
    # landing slots: a permutation of the destination pool (zero slack,
    # so the permute contract demands full exactly-once coverage) —
    # identical on every rank, as the reserve→ship handshake guarantees
    return {0: np.asarray(
        list(reversed(range(g["pages"]))), np.int32
    )}


def _kv_ship_elems() -> int:
    """Elements ONE partner rank delivers into a pool: the whole staged
    page set (pages · rows · cols)."""
    from triton_distributed_tpu.kernels.kv_ship import KV_SHIP_GEOM as g

    return g["pages"] * g["rows"] * g["cols"]


def _cp_kv_rotate(mesh, n, token):
    """The ring-attention KV-rotation ring (kernels/cp_ring.py): the
    training CP transport's Pallas twin on the shared AG forward-ring
    harness, schedule-threaded so PR 9's search applies."""
    from triton_distributed_tpu.kernels.cp_ring import build_kv_rotate_lint

    build_kv_rotate_lint(mesh, n, token=(token, n))


def _cp_ulysses(mesh, n, token):
    """The Ulysses head-scatter a2a (kernels/cp_ring.py)."""
    from triton_distributed_tpu.kernels.cp_ring import build_ulysses_lint

    build_ulysses_lint(mesh, n, token=(token, n))


def _grad_ring(mesh, n, token):
    """The wire-quantized gradient ring (kernels/cp_ring.py): streaming
    reduce ring on the int8 wire — the Pallas protocol twin of
    ``train.grad_wire``'s EF reduce-scatter."""
    from triton_distributed_tpu.kernels.cp_ring import build_grad_ring_lint

    build_grad_ring_lint(mesh, n, token=(token, n))


def _cp_lse_combine(mesh, n, token):
    """The long-context decode merge (kernels/cp_ring.py): cross-rank
    LSE-combine as an f32 add-reduce ring — the Pallas protocol twin of
    ``flash_decode.cp_lse_combine_xla``."""
    from triton_distributed_tpu.kernels.cp_ring import (
        build_cp_lse_combine_lint,
    )

    build_cp_lse_combine_lint(mesh, n, token=(token, n))


def _ragged_paged(mesh, n, token):
    """The ragged paged-attention family is LOCAL (no remote DMA): the
    serving state shards pools over the KV-head dim, so each rank runs
    the same kernel on its head slice. Built at the kernel module's
    LINT_GEOM (zero-slack packing → the `local` contract can demand
    FULL own-write coverage of the out buffer)."""
    del mesh
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        build_lint_kernel,
    )

    build_lint_kernel(token=(token, n))


def _ragged_paged_window(mesh, n, token):
    """The same kernel with a static sliding window (the launch a
    model's window layers make, over their ring pools): same geometry,
    same operands, same `local` contract — the window moves where a
    row's page walk starts and adds the mask's lower edge, not what
    the row writes."""
    del mesh
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_WINDOW,
        build_lint_kernel,
    )

    build_lint_kernel(token=(token, n), window=LINT_WINDOW)


def _kv_append(mesh, n, token):
    """The pool append (kernels/kv_append.py) is LOCAL like the ragged
    kernel it feeds: every rank read-modify-writes pages of its own
    pools."""
    del mesh
    from triton_distributed_tpu.kernels.kv_append import build_lint_kernel

    build_lint_kernel(token=(token, n))


def _kv_append_in_shapes(n):
    from triton_distributed_tpu.kernels.kv_append import lint_in_shapes

    del n
    return [(shape, np.dtype(dtype)) for shape, dtype in lint_in_shapes()]


def _kv_append_init(n):
    from triton_distributed_tpu.kernels.kv_append import lint_units

    del n
    return {0: np.asarray(lint_units())}


def _ragged_in_shapes(n):
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_GEOM as g,
    )

    del n
    pool = (g["npages"], g["hkv"], g["page"], g["d"])
    return [
        ((g["r"], g["pps"]), _I32),                   # block table
        ((g["r"],), _I32),                            # kv_lens
        ((g["r"],), _I32),                            # q_lens
        ((g["r"],), _I32),                            # q_starts
        ((g["r"], 2 + 2 * g["topo_w"]), _I32),        # topologies
        ((g["hkv"], g["t"] * g["g"], g["d"]), _F32),  # packed q
        (pool, _I8),                                  # k pool
        (pool, _I8),                                  # v pool
        ((g["npages"], g["hkv"], 1, g["page"]), _F32),  # k scales
        ((g["npages"], g["hkv"], 1, g["page"]), _F32),  # v scales
    ]


def _ragged_init(n):
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_GEOM as g,
    )

    del n
    # two active rows, zero-slack packing: row 0 walks 2 pages (len 12
    # over 8-row pages), row 1 walks 1; both contribute 8 tokens at
    # 8-aligned starts tiling the whole (t, g) out span
    return {
        0: np.arange(g["r"] * g["pps"], dtype=np.int32).reshape(
            g["r"], g["pps"]
        ),
        1: np.asarray([12, 8], np.int32),             # kv_lens
        2: np.asarray([8, 8], np.int32),              # q_lens
        3: np.asarray([0, 8], np.int32),              # q_starts
        4: np.zeros((g["r"], 2 + 2 * g["topo_w"]), np.int32),  # CAUSAL
    }


#: lint geometry for the chunked MoE a2a: 8-row alignment tiles, 1 chunk
#: of 8 rows per peer, 2-chunk slots, a 1-row meta block whose chunk
#: count sits at (row 0, lane 1).
_MOE_GEOM = dict(a=8, chunk_u=1, slot_u=2, mr=1, nck_row=0, nck_lane=1,
                 kmax=2, cap=16, hidden=128)


def _moe_a2a(know_recv, collective_id):
    def build(mesh, n, token):
        import jax.numpy as jnp

        from triton_distributed_tpu.kernels.moe_dispatch import (
            _build_chunked_a2a,
        )

        g = _MOE_GEOM
        _build_chunked_a2a(
            ("x",), "x", n, g["a"], g["chunk_u"], g["slot_u"], g["mr"],
            g["nck_row"], g["nck_lane"], g["kmax"], g["cap"], g["hidden"],
            jnp.dtype(jnp.float32), know_recv, collective_id, token,
        )

    return build


def _moe_in_shapes(n):
    g = _MOE_GEOM
    return [
        ((1,), _I32),                       # parity
        ((n,), _I32),                       # offs (a-units)
        ((n,), _I32),                       # sendk
        ((n,), _I32),                       # recvk
        ((n * g["slot_u"] * g["a"], g["hidden"]), _F32),   # payload
        ((n * g["mr"], 128), _I32),         # meta
    ]


def _moe_init(know_recv):
    def init(n):
        g = _MOE_GEOM
        seed = {
            "offs_ref": np.arange(n, dtype=np.int32) * g["slot_u"],
            "sendk_ref": np.ones((n,), np.int32),
            "recvk_ref": np.ones((n,), np.int32),
        }
        if not know_recv:
            # the dispatch leg reads incoming chunk counts from the
            # landed metadata head; per-rank symbolic execution has no
            # peer memory, so seed the receive metadata with the counts
            # a symmetric peer would send (1 chunk each)
            meta = np.zeros((n * g["mr"], 128), np.int32)
            meta[:, g["nck_lane"]] = 1
            seed[6 + 1] = meta              # output ref: dst_meta
            src = np.zeros((n * g["mr"], 128), np.int32)
            src[:, g["nck_lane"]] = 1
            seed["meta_hbm"] = src
        return seed

    return init


#: every analyzable kernel family, keyed by registry name. Each family
#: declares its DELIVERY contract (the SL008 table): what the kernel
#: must provably have delivered when every semaphore has balanced.
def families() -> dict:
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.runtime import AllGatherMethod

    def gather(dst, **kw):
        return DeliveryContract(kind="gather", dst=dst, **kw)

    def reduce(dst, **kw):
        return DeliveryContract(kind="reduce", dst=dst, **kw)

    #: the chunked MoE a2a is capacity-padded: with the seeded routing
    #: (1 chunk per peer) each source delivers exactly one chunk of
    #: chunk_u·a rows into its slot; the rest of the slot stays empty.
    _g = _MOE_GEOM
    moe_contract = DeliveryContract(
        kind="permute", dst=6,        # dst_tok, behind the *refs splat
        payload_per_src=lambda n: _g["chunk_u"] * _g["a"] * _g["hidden"],
        full=False,
    )

    fams = [
        KernelFamily(
            "allgather.ring_1d", "allgather", "ag_ring_1d",
            _ag(AllGatherMethod.RING_1D),
            lambda n: [((8, 128), _F32)],
            contract=gather("out_ref"),
        ),
        KernelFamily(
            "allgather.ring_bidir", "allgather", "ag_ring_bidir",
            _ag(AllGatherMethod.RING_BIDIR),
            lambda n: [((8, 128), _F32)],
            contract=gather("out_ref"),
        ),
        KernelFamily(
            "allgather.ll_small", "allgather", "ag_ll_small",
            _ag(AllGatherMethod.LL_SMALL),
            lambda n: [((8, 128), _F32)],
            contract=gather("out_ref"),
        ),
        KernelFamily(
            "allgather.ll_persist", "allgather", "ag_ll_persist",
            _ag_ll_persist,
            lambda n: [((1,), _I32), ((8, 128), _F32),
                       ((2 * n * 8, 128), _F32)],
            contract=gather("out_ref"),
        ),
        KernelFamily(
            "reduce_scatter.ring", "reduce_scatter", "rs_ring",
            _rs_ring,
            lambda n: [((8 * n, 128), _F32)],
            contract=reduce("out_ref"),
        ),
        KernelFamily(
            "reduce_scatter.stream", "reduce_scatter", "rs_ring_stream",
            _rs_stream,
            lambda n: [((8 * n, 128), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "all_to_all.dense", "all_to_all", "a2a_dense",
            _a2a,
            lambda n: [((8 * n, 128), _F32)],
            contract=DeliveryContract(kind="permute", dst="out_ref"),
        ),
        KernelFamily(
            "ag_gemm.fused", "ag_gemm", "ag_gemm_fused",
            _ag_gemm,
            lambda n: [((16, 128), _F32), ((128, 64), _F32)],
            contract=gather("ag_hbm"),
        ),
        KernelFamily(
            # quantized-wire twin: payload rides as fp8 + a per-chunk f32
            # scale plane; shmemlint checks the changed byte counts and
            # the scale rail's semaphore protocol alongside the original
            "ag_gemm.fused_fp8w", "ag_gemm", "ag_gemm_fused_fp8w",
            lambda mesh, n, token: _ag_gemm(mesh, n, token, wire="fp8"),
            lambda n: [((16, 128), _F32), ((16, 128), _f8()),
                       ((1, 128), _F32), ((128, 64), _F32)],
            contract=gather("ag_hbm"),
        ),
        KernelFamily(
            # dequant-free int8→MXU twin: identical int8 rails, but the
            # contract destination is the WIRE workspace itself — every
            # arriving slab must be epilogue-consumed (the provenance
            # edge lang.wire.epilogue_consume emits flips it to
            # dequantized; raw bytes left over are SL008, a consume
            # without the scale fold is SL009)
            "ag_gemm.fused_int8mxw", "ag_gemm", "ag_gemm_fused_int8mxw",
            lambda mesh, n, token: _ag_gemm(mesh, n, token,
                                            wire="int8-mxu"),
            lambda n: [((16, 128), _I8), ((1, 128), _F32),
                       ((128, 64), _I8), ((1, 64), _F32)],
            # no local-slab publish: the local slab is consumed straight
            # from the quantized input and never enters the workspace
            contract=gather("agq_hbm", own_absent_ok=True),
        ),
        KernelFamily(
            "gemm_rs.fused", "gemm_rs", "gemm_rs_fused",
            _gemm_rs,
            # A rows are unsharded (each device holds all M rows of its
            # K-column shard); B is row-sharded
            lambda n: [((16 * n, 128), _F32), ((128, 64), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "gemm_rs.fused_fp8w", "gemm_rs", "gemm_rs_fused_fp8w",
            lambda mesh, n, token: _gemm_rs(mesh, n, token, wire="fp8"),
            lambda n: [((16 * n, 128), _F32), ((128, 64), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "allgather.ring_1d_fp8w", "allgather", "ag_ring_1d_fp8w",
            _ag_ring_w,
            lambda n: [((8, 2048), _F32), ((8, 2048), _f8()),
                       ((8, 128), _F32)],
            contract=gather("out_ref"),
        ),
        KernelFamily(
            "reduce_scatter.ring_fp8w", "reduce_scatter", "rs_ring_fp8w",
            _rs_ring_w,
            lambda n: [((8 * n, 2048), _F32)],
            contract=reduce("out_ref"),
        ),
        KernelFamily(
            # the HBM-streaming RS's quantized wire (the last bf16 leg
            # of the standalone RS family): per-hop quant pipelines +
            # scale rail, dequant-accumulate in f32 — the fused gemm_rs
            # wire protocol on the streaming engine
            "reduce_scatter.stream_int8w", "reduce_scatter",
            "rs_ring_stream_int8w",
            _rs_stream_w,
            lambda n: [((8 * n, 2048), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "moe_tp.ag_group_gemm", "moe_tp", "ag_group_gemm_fused",
            _moe_ag_gg(None),
            _moe_ag_gg_shapes(None),
            # no local-slab publish: slab `me` is consumed straight from
            # the sorted input and legitimately absent from the workspace
            contract=gather("ag_hbm", own_absent_ok=True),
        ),
        KernelFamily(
            "moe_tp.ag_group_gemm_fp8w", "moe_tp", "ag_group_gemm_fused_fp8w",
            _moe_ag_gg("fp8"),
            _moe_ag_gg_shapes("fp8"),
            contract=gather("ag_hbm", own_absent_ok=True),
        ),
        KernelFamily(
            # dequant-free int8→MXU grouped twin: sorted int8 slabs feed
            # the s8×s8 grouped GEMM against per-(expert, out-channel)
            # quantized weights; the wire workspace is the contract dst
            "moe_tp.ag_group_gemm_int8mxw", "moe_tp",
            "ag_group_gemm_fused_int8mxw",
            _moe_ag_gg("int8-mxu"),
            _moe_ag_gg_shapes("int8-mxu"),
            contract=gather("agq_hbm", own_absent_ok=True),
        ),
        KernelFamily(
            "moe_tp.reduce_rs", "moe_tp", "moe_reduce_rs_fused",
            _moe_rs(None),
            _moe_rs_shapes,
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "moe_tp.reduce_rs_fp8w", "moe_tp", "moe_reduce_rs_fused_fp8w",
            _moe_rs("fp8"),
            _moe_rs_shapes,
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            # the serving engine's mixed prefill/decode attention — a
            # LOCAL kernel (head-sharded pools, no cross-rank merge):
            # the contract demands every out element be the rank's own
            # computed write (full coverage, no holes, no raw
            # quantized bytes surviving the scale folds)
            "flash_decode.ragged_paged", "ragged_paged",
            "ragged_paged_attention_q8",
            _ragged_paged,
            _ragged_in_shapes,
            init=_ragged_init,
            contract=DeliveryContract(
                kind="local", dst=10,
                topo={"ref": 4, "kv_lens": 1, "q_lens": 2, "width": 8},
            ),
        ),
        KernelFamily(
            # the sliding-window launch of the same kernel (window
            # layers over ring pools): every out element still the
            # rank's own computed write
            "flash_decode.ragged_paged_window", "ragged_paged",
            "ragged_paged_attention_w8_q8",
            _ragged_paged_window,
            _ragged_in_shapes,
            init=_ragged_init,
            contract=DeliveryContract(
                kind="local", dst=10,
                topo={"ref": 4, "kv_lens": 1, "q_lens": 2, "width": 8},
            ),
        ),
        KernelFamily(
            # the serving step's pool append: a LOCAL read-modify-write
            # of the pages this step's (slot, page) runs name — the
            # pools are aliased in place and mostly keep their bytes:
            # own writes only, not full coverage
            "kv_append.pages", "kv_append", "kv_append_q8",
            _kv_append,
            _kv_append_in_shapes,
            init=_kv_append_init,
            contract=DeliveryContract(kind="local", dst=9, full=False),
        ),
        KernelFamily(
            # the disaggregated-serving page ship: a PAIRWISE permute —
            # each decode rank's pool must hold exactly its partner
            # prefill rank's pages, each exactly once at its assigned
            # slot (src_only pins the topology; a skipped or doubled
            # page is SL008), with the scale rail paired per page on
            # its own semaphores (SL009) and the landed pair recorded
            # installed-as-quantized (epilogue_consume — the pool keeps
            # int8+scales, the attention kernel folds at read time)
            "kv_ship.pages", "kv_ship", "kv_ship_pages",
            _kv_ship,
            _kv_ship_in_shapes,
            init=_kv_ship_init,
            contract=DeliveryContract(
                kind="permute", dst="dst_q",
                payload_per_src=lambda n: (
                    _kv_ship_elems()
                ),
                src_only=lambda rank, n: {(rank - n // 2) % n},
            ),
        ),
        KernelFamily(
            # training CP: the KV-rotation ring under ring attention.
            # The local KV block is consumed at step 0 straight from
            # the input (the XLA body's peeled step 0) and never enters
            # the workspace — own_absent_ok, like the int8-MXU gathers.
            # A skip_last schedule mutation drops one block entirely;
            # only this gather contract (SL008) can see the hole.
            "cp.ring_attention", "cp_ring", "cp_ring_kv_rotate",
            _cp_kv_rotate,
            lambda n: [((8, 128), _F32)],
            contract=gather("ag_ref", own_absent_ok=True),
        ),
        KernelFamily(
            # training CP: the Ulysses seq→heads re-shard's dense a2a
            "cp.ulysses", "cp_ring", "cp_ulysses_a2a",
            _cp_ulysses,
            lambda n: [((8 * n, 128), _F32)],
            contract=DeliveryContract(kind="permute", dst="out_ref"),
        ),
        KernelFamily(
            # the gradient ring: streaming reduce on the int8 wire (wide
            # lint columns — scale planes only compress when the stripe
            # payload dwarfs them). The EF/stochastic-rounding numerics
            # live in train.grad_wire; this twin pins the PROTOCOL
            # (slot/ack discipline, paired scale rail → SL009).
            "grad_ring.stream_int8w", "grad_ring", "grad_ring_stream_int8w",
            _grad_ring,
            lambda n: [((8 * n, 2048), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            # long-context serving: each cp rank's paged-attention
            # partial rides as exp-weighted numerator rows + an
            # additive denominator row, so the softmax merge is a pure
            # add-reduce and the ring stays on the raw f32 wire (a
            # quantized denominator would drift the final normalize).
            # The reduce contract (SL008) is what sees a dropped or
            # double-folded rank — a token decoded against a silently
            # missing KV shard.
            "cp_decode.lse_combine", "cp_decode", "cp_decode_lse_combine",
            _cp_lse_combine,
            lambda n: [((8 * n, 128), _F32)],
            contract=reduce("out_hbm"),
        ),
        KernelFamily(
            "moe_dispatch.a2a", "moe_dispatch", "moe_chunked_a2a",
            _moe_a2a(False, 10),
            _moe_in_shapes,
            init=_moe_init(False),
            contract=moe_contract,
        ),
        KernelFamily(
            "moe_combine.a2a", "moe_dispatch", "moe_chunked_a2a",
            _moe_a2a(True, 11),
            _moe_in_shapes,
            init=_moe_init(True),
            contract=moe_contract,
        ),
    ]
    from dataclasses import replace as _replace

    out = {
        f.name: (
            f if f.degrades_to
            else _replace(f, degrades_to=DEGRADATION_TARGETS.get(f.name))
        )
        for f in fams
    }
    _strict_verify_contracts()
    return out


#: one-shot flag for the TDTPU_LINT_STRICT registration gate: None =
#: not yet run, True = verified clean. A failure leaves it None so a
#: fixed environment can re-verify.
_STRICT_VERIFIED = None


def _strict_verify_contracts():
    """Under ``TDTPU_LINT_STRICT=1``, re-verify every hand-declared
    delivery contract against the one inferred from its XLA twin at
    registration time (mesh 4, memoized — one pass per process). Any
    SL012 drift raises: a declaration that would make SL008 check the
    wrong obligation must not register."""
    import os

    global _STRICT_VERIFIED
    if _STRICT_VERIFIED or os.environ.get("TDTPU_LINT_STRICT") != "1":
        return
    # mark before running: verification itself calls families()
    _STRICT_VERIFIED = True
    try:
        from triton_distributed_tpu.analysis import contract_infer
        from triton_distributed_tpu.analysis.findings import Severity

        findings = contract_infer.verify_declared_contracts(n=4)
        errs = [f for f in findings if f.severity >= Severity.ERROR]
        if errs:
            raise RuntimeError(
                "TDTPU_LINT_STRICT: declared delivery contracts drift "
                "from the twin-inferred obligations:\n"
                + "\n".join(f.format() for f in errs)
            )
    except BaseException:
        _STRICT_VERIFIED = None
        raise
