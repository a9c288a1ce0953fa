"""Expert-parallel MoE MLP op: AllToAll dispatch → grouped GEMMs → combine.

Reference: the EP layer ``EPAll2AllLayer`` (python/triton_dist/layers/
nvidia/ep_a2a_layer.py:40-240 — preprocess splits/indices → dispatch →
caller's expert compute → combine) over the low-latency AllToAll
(low_latency_all_to_all.py) and the grouped GEMMs of
allgather_group_gemm.py:420 / moe_reduce_rs.py:362; routing ≡
select_experts (moe_reduce_rs.py:180).

TPU re-design: one ``shard_map`` body does route → expert-sort →
dispatch → local grouped GEMM MLP over the owned experts → return a2a →
weighted combine. Three transports:

* ``transport="fused"`` (flat-mesh default): in-kernel per-peer window
  DMAs straight from the aligned expert-sorted payload
  (kernels/moe_dispatch) — the low-latency inference path.
* ``transport="pallas"``: staged padded-slot in-kernel a2a
  (kernels/all_to_all.all_to_all_device) — the hierarchical-capable
  transport (default when ``dcn_axis`` is set).
* ``transport="xla"``: ``lax.all_to_all`` — differentiable end-to-end
  (sort/gather/scatter/topk-softmax all have transpose rules), which is
  what makes EP *training* possible; the reference is inference-only.

ONE rank (``EPMoEContext.local``: a one-chip mesh axis) exchanges with
nobody: the body sorts its own assignments once, gathers once, runs the
same grouped GEMMs and un-sorts — no transport of any kind, no wire
quantization, no receive windows (``_local_assignments_device``).
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from triton_distributed_tpu.kernels import moe_all_to_all as ma
from triton_distributed_tpu.kernels import moe_utils as mu
from triton_distributed_tpu.kernels.all_to_all import all_to_all_device
from triton_distributed_tpu.kernels.group_gemm import grouped_matmul, padded_splits


@dataclass(frozen=True)
class EPMoEContext:
    """Static geometry of the EP MoE layer (≡ EPAll2AllLayer's ctor state
    + AllToAllContext). Experts are sharded over ``axis``: rank r owns
    experts [r*epr, (r+1)*epr)."""

    mesh: Mesh
    axis: str
    num_experts: int
    topk: int
    # Transport capacity. Staged ("pallas"/"xla") transports read it as
    # PER-PEER slot capacity (overflow beyond it is clamped); the fused
    # transport needs TOTAL-assignment capacity (max_m ≥ M·topk, the
    # standard worst-case sizing) and degrades to the staged path with a
    # warning when sized smaller.
    max_m: int
    hidden: int
    dtype: jnp.dtype = jnp.bfloat16
    activation: str = "silu"        # silu | gelu | none
    # "fused": in-kernel per-peer window DMAs straight from the aligned
    #   expert-sorted payload — the low-latency inference path
    #   (kernels/moe_dispatch, ≡ the reference's on-device range
    #   computation, low_latency_all_to_all.py:36-80). Flat meshes only;
    #   requires max_m ≥ M·topk (the worst-case total, the standard
    #   sizing).
    # "pallas": staged padded-slot a2a (kernels/moe_all_to_all) — the
    #   hierarchical-capable in-kernel transport.
    # "xla": lax.all_to_all — differentiable end to end (training).
    # None (default): "fused" on flat meshes, "pallas" hierarchical.
    transport: str | None = None    # fused | pallas | xla
    block_m: int = 128
    use_pallas_gemm: bool = True
    # Grouped-GEMM N/K tiles (None → kernel defaults). Setting both to
    # a huge value (whole-dim) enables the WEIGHT-RESIDENT schedule:
    # each expert's full weight matrix stays in VMEM across its
    # consecutive sorted blocks, so block_m can shrink (less alignment
    # padding) without re-streaming weights per block — the decode-size
    # optimum (group_gemm.grouped_matmul docstring).
    gg_block_n: int | None = None
    gg_block_k: int | None = None
    collective_id: int = 10
    batch_axes: tuple = ()          # extra (DP) axes sharding token rows
    # Hierarchical (multi-slice) EP: experts span (dcn_axis × axis) and
    # the exchange decomposes into a same-local-rank DCN rail leg +
    # intra-slice ICI leg (≡ ep_a2a.py:36-150's node rotation with
    # same-local-rank rail puts). None → flat single-slice exchange.
    dcn_axis: str | None = None
    # Quantized token transport ("fp8" | "int8"): tokens ride the a2a at
    # 1 byte/elem with per-token scales in the wire metadata (≡ the
    # reference's headline fp8 WITH_SCALE dispatch). Carried by the
    # "fused" and "pallas" transports; the XLA transport is the
    # differentiable path and stays full-precision. It is the encoding
    # of bytes that cross a link: ONE rank (``local``) has no wire, so
    # the field is accepted there (a preset is written for any mesh)
    # and nothing is quantized for it.
    quant: str | None = None
    # W8A8 expert GEMMs ("int8"): quantize the ACTIVATIONS per row too
    # and run the MXU's native s8×s8→s32 path (2× the bf16 rate, the
    # remaining lever once the weight-resident schedule has minimized
    # HBM reads). Requires int8 weight dicts + the Pallas GEMM. Its
    # block_m is the caller's: every array of ``_expert_mlp`` (sorted
    # rows, their int8 copy, the hidden, the output) is
    # ``aligned_rows`` long, so the block is sized to the rows an
    # expert is expected to get, not to the MXU (a served step's
    # grouped GEMM is weight-byte-bound: ``models/transformer.py::
    # expert_block_m`` has the rule and its chip numbers); an int8
    # operand's sublane tile, 32 rows, is the least.
    act_quant: str | None = None
    # gated expert MLP: ``w_up`` is (epr, H, 2F), gate columns first —
    # ONE grouped GEMM gives both halves and the hidden activation is
    # ``act(gate) * up`` (three matrices an expert, SwiGLU). False: the
    # two-matrix ``down(act(up(x)))``.
    gated: bool = False

    @property
    def n(self) -> int:
        """Total EP ranks (dcn × local when hierarchical)."""
        n = self.mesh.shape[self.axis]
        if self.dcn_axis is not None:
            n *= self.mesh.shape[self.dcn_axis]
        return n

    @property
    def epl(self) -> int:
        """EP ranks per slice (the ICI leg width)."""
        return self.mesh.shape[self.axis]

    @property
    def dcn(self) -> int:
        """Number of slices on the DCN leg (1 when flat)."""
        return self.mesh.shape[self.dcn_axis] if self.dcn_axis else 1

    @property
    def ep_axes(self) -> tuple:
        """Mesh axes the experts are sharded over, DCN-major — global EP
        rank g = slice·epl + local matches P(ep_axes) dim-0 sharding."""
        return (self.dcn_axis, self.axis) if self.dcn_axis else (self.axis,)

    @property
    def experts_per_rank(self) -> int:
        return self.num_experts // self.n

    @property
    def local(self) -> bool:
        """ONE rank holds every expert of the exchange (a static of the
        mesh): its assignments are sorted and multiplied where they
        are, whatever ``transport`` says."""
        return self.n == 1

    @property
    def recv_rows(self) -> int:
        """Rows the expert MLP is handed: every peer's receive slot —
        ONE rank's own ``max_m`` assignments, in place."""
        if self.local:
            return self.max_m
        if self.transport == "fused":
            from triton_distributed_tpu.kernels import moe_dispatch as md

            return self.n * md.slot_pad(self.a2a)
        return self.n * self.max_m

    @property
    def aligned_rows(self) -> int:
        """Rows of the expert-sorted buffer (and of every array of
        ``_grouped_mlp`` behind it): the received rows plus up to
        ``block_m - 1`` rows of alignment an expert and the dummy
        group."""
        return mu.aligned_capacity(
            self.recv_rows, self.experts_per_rank + 1, self.block_m)

    @property
    def a2a(self) -> ma.MoEAllToAllContext:
        return ma.create_all_to_all_context(
            self.mesh, self.axis, max_m=self.max_m, hidden=self.hidden,
            experts_per_rank=self.experts_per_rank, dtype=self.dtype,
            collective_id=self.collective_id, num_ranks=self.n,
            quant=self.quant,
        )


def create_ep_moe_context(
    mesh, axis, *, num_experts, topk, max_m, hidden, **kw
) -> EPMoEContext:
    ctx = EPMoEContext(
        mesh=mesh, axis=axis, num_experts=num_experts, topk=topk,
        max_m=max_m, hidden=hidden, **kw,
    )
    if ctx.transport is None:
        ctx = replace(
            ctx,
            transport="pallas" if ctx.dcn_axis is not None else "fused",
        )
    assert num_experts % ctx.n == 0, f"{num_experts} experts over {ctx.n} ranks"
    ctx.a2a  # fail fast on bad quant/hidden geometry, not at trace time
    if ctx.quant is not None and ctx.transport == "xla":
        raise ValueError(
            "quantized transport rides the Pallas slot payload; the XLA "
            "transport is the differentiable full-precision path"
        )
    if ctx.act_quant not in (None, "int8"):
        raise ValueError(f"act_quant must be None or 'int8', got {ctx.act_quant!r}")
    if ctx.act_quant is not None and ctx.gated:
        raise ValueError(
            "gated expert MLPs are built for bf16 and "
            "weight-only-quantized GEMMs, not W8A8 (act_quant)")
    if ctx.transport == "fused" and ctx.dcn_axis is not None:
        raise ValueError(
            "the fused window-DMA transport is flat (single-slice) only; "
            "use transport='pallas' for the hierarchical exchange"
        )
    if ctx.transport in ("pallas", "fused"):
        # Pallas remote DMA cannot cross DCN: a multi-slice EP axis must
        # be declared as dcn_axis so the exchange takes the hierarchical
        # rail path (≡ the reference's CommScope INTER_NODE dispatch).
        from triton_distributed_tpu.runtime import is_dcn_axis

        if ctx.dcn_axis is None and is_dcn_axis(mesh, axis):
            raise ValueError(
                f"EP axis {axis!r} crosses DCN; pass dcn_axis= for the "
                "hierarchical exchange or transport='xla'"
            )
        if ctx.dcn_axis is not None and is_dcn_axis(mesh, ctx.axis):
            raise ValueError(
                f"intra-slice EP axis {ctx.axis!r} itself crosses DCN — "
                "swap the axes (dcn_axis must be the cross-slice one)"
            )
    return ctx


@dataclass
class EPMoEState:
    """Persistent workspaces of the BARRIER-FREE fused transport (≡ the
    reference AllToAllContext's symmetric buffers + call_count,
    low_latency_all_to_all.py:125-187). Owns the double-buffered
    receive windows for both legs and the parity counter; thread the
    returned state through successive ``ep_moe(..., state=)`` calls
    (the arrays are donated — always use the returned state).

    ``instance`` keys the compiled kernels per live state so two states
    never share physical per-parity semaphores (see
    moe_dispatch._build_chunked_a2a_ll)."""

    parity: jax.Array       # (1,) int32, replicated
    disp_tok: jax.Array     # dispatch windows, P(batch+ep) sharded
    disp_meta: jax.Array
    comb_tok: jax.Array     # combine windows
    comb_meta: jax.Array
    instance: int = 0       # static (pytree aux data)

    def as_dict(self):
        return {
            "parity": self.parity,
            "disp_tok": self.disp_tok, "disp_meta": self.disp_meta,
            "comb_tok": self.comb_tok, "comb_meta": self.comb_meta,
        }


jax.tree_util.register_dataclass(
    EPMoEState,
    data_fields=["parity", "disp_tok", "disp_meta", "comb_tok", "comb_meta"],
    meta_fields=["instance"],
)

_NEXT_LL_INSTANCE = [0]


def create_ep_moe_state(ctx: EPMoEContext, abstract: bool = False) -> EPMoEState:
    """Allocate zeroed persistent LL workspaces for ``ctx`` (fused flat
    transport only). Each call consumes TWO kernel instances (dispatch,
    combine). ``abstract=True`` returns ShapeDtypeStruct leaves instead
    of device arrays — for lowering/compiling against an unattached
    topology mesh (tests/test_aot_topology.py)."""
    import numpy as np
    from jax.sharding import NamedSharding

    from triton_distributed_tpu.kernels import moe_dispatch as md

    if ctx.transport != "fused" or ctx.dcn_axis is not None:
        raise ValueError(
            "EPMoEState rides the flat fused transport "
            f"(got transport={ctx.transport!r}, dcn_axis={ctx.dcn_axis!r})"
        )
    if ctx.local:
        raise ValueError(
            "EPMoEState: one EP rank exchanges with nobody, so there are "
            "no receive windows to keep — call ep_moe without state"
        )
    a2a = ctx.a2a
    (tok_shape, tok_dt), (meta_shape, meta_dt) = md.ll_workspace_shapes(a2a)
    row_axes = tuple(ctx.batch_axes) + ctx.ep_axes
    shards = int(np.prod([ctx.mesh.shape[ax] for ax in row_axes]))
    sh = NamedSharding(ctx.mesh, P(row_axes))
    rep = NamedSharding(ctx.mesh, P())

    if abstract:
        def ws(shape, dt, sharding=sh):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

        tok_shape = (shards * tok_shape[0],) + tok_shape[1:]
        meta_shape = (shards * meta_shape[0],) + meta_shape[1:]
        inst = _NEXT_LL_INSTANCE[0]
        _NEXT_LL_INSTANCE[0] += 2
        return EPMoEState(
            parity=ws((1,), jnp.int32, rep),
            disp_tok=ws(tok_shape, tok_dt),
            disp_meta=ws(meta_shape, meta_dt),
            comb_tok=ws(tok_shape, tok_dt),
            comb_meta=ws(meta_shape, meta_dt),
            instance=inst,
        )

    def ws(shape, dt):
        return jax.device_put(
            jnp.zeros((shards * shape[0],) + shape[1:], dt), sh
        )

    inst = _NEXT_LL_INSTANCE[0]
    _NEXT_LL_INSTANCE[0] += 2
    return EPMoEState(
        parity=jax.device_put(jnp.zeros((1,), jnp.int32), rep),
        disp_tok=ws(tok_shape, tok_dt),
        disp_meta=ws(meta_shape, meta_dt),
        comb_tok=ws(tok_shape, tok_dt),
        comb_meta=ws(meta_shape, meta_dt),
        instance=inst,
    )


def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    return x


def _a2a(ctx: EPMoEContext, x):
    """Transpose the leading (n, ...) slot dim across EP ranks — the
    FLAT exchange over ``ctx.axis`` (hierarchical meshes never reach
    here: ``_ep_moe_hier_device`` decomposes into a dedup'd DCN rail +
    a flat intra-slice exchange before any slot staging happens)."""
    if ctx.transport == "pallas":
        flat = x.reshape(ctx.n * x.shape[1], -1)
        out = all_to_all_device(
            flat, ctx.n, ctx.axis, ctx.mesh.axis_names,
            collective_id=ctx.collective_id,
        )
        return out.reshape(x.shape)
    return jax.lax.all_to_all(x, ctx.axis, 0, 0, tiled=False)


def _dispatch(ctx: EPMoEContext, x_sorted, splits):
    """Stage + exchange → ((n, max_m, H) tokens, clamped (n, epr) splits).

    Pallas: one bitcast int32 payload per peer (inference fast path).
    XLA: tokens and splits ride two ``lax.all_to_all`` calls so the
    float tokens never cross a gradient-opaque bitcast (training path).
    """
    a2a = ctx.a2a
    toks, spl = ma.dispatch_stage(a2a, x_sorted, splits)
    if ctx.transport == "pallas":
        recv = _a2a(ctx, ma.pack_slots(a2a, toks, spl).reshape(
            ctx.n, a2a.slot_rows, a2a.ints_per_row))
        return ma.recv_tokens_view(a2a, recv)
    rtoks = _a2a(ctx, toks)
    rspl = _a2a(ctx, spl[:, None, :])[:, 0, :]
    return rtoks, ma.clamp_recv_splits(a2a, rspl)


def _combine(ctx: EPMoEContext, y_slots, splits, total):
    """Return-leg exchange + unstage → (total, H) in sorted order."""
    a2a = ctx.a2a
    if ctx.transport == "pallas":
        comb = _a2a(ctx, ma.combine_stage(a2a, y_slots).reshape(
            ctx.n, a2a.slot_rows, a2a.ints_per_row))
        toks = ma.combine_unpack(a2a, comb)
    else:
        toks = _a2a(ctx, y_slots)
    return ma.combine_unstage(a2a, toks, splits, total)


def _grouped_mlp(ctx: EPMoEContext, xs, be, counts, w_up, w_down):
    """The two grouped GEMMs over this rank's experts.

    xs: (cap, H) rows sorted by local expert, each expert's segment
    padded to ``ctx.block_m`` (``moe_utils.moe_align_block_size``: ``be``
    the blocks' owners, ``counts`` the true rows an expert and of the
    trailing DUMMY group ``epr``). w_up: (epr, H, F); w_down: (epr, F,
    H). The dummy group's rows and the padding are zero and contribute
    zeros; the Pallas GEMM stores that group's blocks (and the capacity
    no row fills) as zeros without fetching or multiplying a weight
    (``grouped_matmul(dummy_expert=)``), the ``ragged_dot`` twin folds
    them into the last expert's group. Returns (cap, H), sorted.

    Either weight may instead be a WEIGHT-QUANTIZED dict
    ``{"q": (epr, K, N) int8/fp8, "scale": (epr, N) f32}`` (from
    group_gemm.quantize_grouped_weights): the Pallas path folds the
    scale into the GEMM epilogue, halving the weight HBM reads that
    dominate decode-size grouped GEMMs; the XLA twin widens first.
    """
    epr = ctx.experts_per_rank
    cap = xs.shape[0]

    def act(h):
        # gated: the GEMM gave [gate | up]; the hidden is act(gate)·up
        if not ctx.gated:
            return _act(ctx.activation, h)
        f = h.shape[1] // 2
        return _act(ctx.activation, h[:, :f]) * h[:, f:]

    if ctx.use_pallas_gemm:
        # the dummy tail (be == epr: the rows of no expert, and the
        # capacity a step does not use) keeps its own id: the kernel
        # neither fetches a weight for such a block nor multiplies it
        gg_kw = {"block_m": ctx.block_m, "dummy_expert": epr}
        if ctx.gg_block_n is not None:
            gg_kw["block_n"] = ctx.gg_block_n
        if ctx.gg_block_k is not None:
            gg_kw["block_k"] = ctx.gg_block_k
        if ctx.gg_block_n is not None or ctx.gg_block_k is not None:
            from triton_distributed_tpu.config import fused_vmem_budget

            gg_kw["vmem_limit_bytes"] = fused_vmem_budget()

        def gg(inp, w):
            if isinstance(w, dict):
                return grouped_matmul(
                    inp, w["q"], be, w_scale=w["scale"], **gg_kw)
            return grouped_matmul(inp, w, be, **gg_kw)

        if (
            ctx.act_quant == "int8"
            and isinstance(w_up, dict) and isinstance(w_down, dict)
            and w_up["q"].dtype == jnp.int8 and w_down["q"].dtype == jnp.int8
        ):
            # W8A8: per-row int8 activations into the s8×s8 MXU path
            # (2× rate); the hidden activation re-quantizes after the
            # nonlinearity (its own per-row scale — the only extra
            # quantization step beyond what the int8 wire already did)
            from triton_distributed_tpu.kernels.group_gemm import (
                quantize_act_rows,
            )

            def gg8(q_in, s_in, w):
                return grouped_matmul(
                    q_in, w["q"], be, w_scale=w["scale"], x_scale=s_in,
                    out_dtype=ctx.dtype, **gg_kw,
                )

            xq, xsc = quantize_act_rows(xs)
            h = _act(ctx.activation, gg8(xq, xsc, w_up))
            hq, hsc = quantize_act_rows(h)
            y = gg8(hq, hsc, w_down)
        else:
            h = gg(xs, w_up)
            h = act(h).astype(ctx.dtype)
            y = gg(h, w_down)
    else:
        from triton_distributed_tpu.kernels.group_gemm import (
            dequantize_grouped_weights,
        )

        if isinstance(w_up, dict):
            w_up = dequantize_grouped_weights(
                w_up["q"], w_up["scale"], ctx.dtype
            )
        if isinstance(w_down, dict):
            w_down = dequantize_grouped_weights(
                w_down["q"], w_down["scale"], ctx.dtype
            )
        # aligned group sizes; the dummy group and tail slack are zero
        # rows — fold them into the last real expert
        gs_all = padded_splits(counts, ctx.block_m, cap)
        gs = gs_all[:epr].at[-1].add(gs_all[epr])
        h = jax.lax.ragged_dot(xs, w_up, gs)
        h = act(h).astype(ctx.dtype)
        y = jax.lax.ragged_dot(h, w_down, gs)
    # no post-GEMM re-masking: invalid/slack rows entered the GEMMs as
    # exact zeros (the caller's ``xs``), so their outputs are exact
    # zeros — the old (cap, H) `where` pass was a full ~23 MB r+w of
    # dead HBM bandwidth at serving shapes.
    return y


def _expert_mlp(ctx: EPMoEContext, rows, eid, valid, w_up, w_down):
    """Grouped MLP over RECEIVED rows (the exchange's receive side).

    rows: (R, H) received tokens; eid: (R,) local expert ids; valid: (R,)
    bool. Invalid rows are zero and sorted into the trailing dummy group
    of :func:`_grouped_mlp`. Returns (R, H) in receive order."""
    epr = ctx.experts_per_rank
    r = rows.shape[0]
    # sort received rows by local expert, invalid rows to a dummy tail
    # group — the align-block trick over receive-side data
    ids = jnp.where(valid, eid, epr).astype(jnp.int32)[:, None]
    sti, be, counts = mu.moe_align_block_size(ids, epr + 1, ctx.block_m)
    safe = jnp.clip(sti, 0, r - 1)
    ok = (sti < r) & valid[safe]
    xs = jnp.where(ok[:, None], rows[safe], 0).astype(ctx.dtype)
    y = _grouped_mlp(ctx, xs, be, counts, w_up, w_down)
    # un-sort via inverse-permutation GATHER: every received row index
    # appears exactly once in sti (it is a sort of all r rows), so the
    # inverse is total — scatter only the (cap,) int32 iota (trivial;
    # padding entries drop out of bounds), then move the big array with
    # one gather instead of scattering (cap, H) rows.
    inv = jnp.zeros((r,), jnp.int32).at[sti].set(
        jnp.arange(sti.shape[0], dtype=jnp.int32), mode="drop"
    )
    return y[inv]


def _weighted(y, w, live):
    """Expert outputs ``y`` (.., H) times their f32 combine weights ``w``
    (the same shape less H), in f32, where ``live`` (= ``w != 0``).
    Masked assignments carry weight exactly 0, but their y rows may be
    garbage (untransported window slack) — zero them before the MAC so
    a stray inf/nan cannot poison the sum. Under debug_checksum the
    poison NaNs ride rows with nonzero weight, so they stay loud."""
    return jnp.where(
        live[..., None], y.astype(jnp.float32) * w[..., None], 0.0)


def _local_assignments_device(ctx: EPMoEContext, x, flat_e, w_flat,
                              out_rows, w_up, w_down):
    """:func:`_ep_assignments_device` where the exchange has ONE rank
    (``ctx.local``): every expert of it is held here, so nothing is
    staged, shipped, received or returned — one sort of the assignments
    by expert with block alignment, one gather of their rows into the
    sorted buffer, the grouped GEMMs, one un-sort gather, the weighted
    sum. No wire, so no wire quantization either (``ctx.quant`` encodes
    bytes that cross a link); no loop: a block's owner is looked up by
    comparison. The four device scopes stay, over what took the
    exchange's place."""
    epr = ctx.experts_per_rank
    total = flat_e.shape[0]
    with jax.named_scope("moe_route"):
        # a masked assignment (the sentinel, or any id past the held
        # experts) sorts into the dummy group
        ids = jnp.minimum(flat_e.astype(jnp.int32), epr)[:, None]
        sti, be, counts, inv = mu.moe_align_block_size(
            ids, epr + 1, ctx.block_m, positions=True)
    with jax.named_scope("moe_dispatch"):
        # the dummy group's rows (its blocks are the trailing ones) and
        # every segment's padding enter the GEMMs as zeros: they gather
        # a zero row appended to ``x`` (a select behind the gather is a
        # second pass over the whole sorted buffer where the compiler
        # does not fuse the two: 156 µs a layer at 8320 x 6144)
        ok = (sti < total) & jnp.repeat(be < epr, ctx.block_m)
        rows = jnp.where(ok, sti // ctx.topk, x.shape[0])
        xs = jnp.concatenate(
            [x, jnp.zeros((1, ctx.hidden), x.dtype)])[rows].astype(ctx.dtype)
    with jax.named_scope("moe_gemm"):
        y = _grouped_mlp(ctx, xs, be, counts, w_up, w_down)
    with jax.named_scope("moe_combine"):
        # un-sort TOP-K-MAJOR, one gather to (topk, rows, H), and add
        # the k weighted (rows, H) slabs in one fused pass. Summing the
        # middle dim of a (rows, topk, H) un-sort reduces across
        # sublanes (81 µs a layer at kexaone's 264 x 8 x 6144), and a
        # ``sum`` over the leading dim made the compiler write the whole
        # f32 copy out first (360 µs at 768 rows): PERF.md §6, PR 40
        by_k = (out_rows, ctx.topk)
        y_k, w_k = y[inv.reshape(by_k).T], w_flat.reshape(by_k).T
        live = w_k != 0
        return functools.reduce(jnp.add, (
            _weighted(y_k[k], w_k[k], live[k]) for k in range(ctx.topk)))


def _slot_tables(ctx: EPMoEContext, rspl, slot_m: int, shift=None):
    """(eid, valid) for (n, slot_m) receive slots from clamped counts.
    ``shift`` (n,): per-slot row offset of the segment inside the window
    (fused transport under extreme skew; None → 0)."""
    pos = jnp.arange(slot_m, dtype=jnp.int32)
    cum = jnp.cumsum(rspl, axis=1)                     # (n, epr)
    rel = pos[None, :] - (
        jnp.zeros((rspl.shape[0], 1), jnp.int32) if shift is None
        else shift[:, None]
    )
    eid = jax.vmap(
        lambda c, r: jnp.searchsorted(c, r, side="right")
    )(cum, rel)
    eid = jnp.clip(eid, 0, ctx.experts_per_rank - 1).reshape(-1)
    valid = ((rel >= 0) & (rel < cum[:, -1][:, None])).reshape(-1)
    return eid, valid


def _ep_assignments_device(ctx: EPMoEContext, x, flat_e, w_flat, out_rows,
                           w_up, w_down, state=None, instance=0):
    """Dispatch pre-routed assignments → grouped MLP → combine →
    weighted scatter, on a FLAT exchange over ``ctx.axis``.

    x: (R, H) token rows; flat_e: (T,) exchange-local expert id per
    assignment (T = R·topk; the SENTINEL ``ctx.num_experts`` marks a
    masked assignment — sorted to the tail, never shipped); w_flat:
    (T,) f32 combine weights, exactly 0 for masked assignments.
    Returns (out_rows, H) f32 weighted sums (out_rows == R) — plus the
    updated workspace dict when ``state`` is given (the barrier-free LL
    transport; fused only). ONE rank (``ctx.local``) takes
    :func:`_local_assignments_device`: the same result with no exchange.
    """
    # device scopes (``jax.named_scope``, one component of each
    # operation's ``op_name``; trace-time only): moe_route, moe_dispatch,
    # moe_gemm, moe_combine — what a profile of any step that runs this
    # block (serving, decode, training) is read by
    if ctx.local:
        # (never with a ``state``: ``ep_moe_device`` refuses one)
        return _local_assignments_device(
            ctx, x, flat_e, w_flat, out_rows, w_up, w_down)
    return _exchange_assignments_device(
        ctx, x, flat_e, w_flat, out_rows, w_up, w_down, state, instance)


def _exchange_assignments_device(ctx: EPMoEContext, x, flat_e, w_flat,
                                 out_rows, w_up, w_down, state=None,
                                 instance=0):
    """:func:`_ep_assignments_device` between ranks: the whole protocol
    (sort and split counts, stage, dispatch, receive-side tables, the
    expert MLP over the received rows, the return leg, the weighted
    sum) over ``ctx.transport``."""
    total = flat_e.shape[0]
    new_state = None
    with jax.named_scope("moe_route"):
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        valid_a = flat_e < ctx.num_experts
        n_valid = jnp.sum(valid_a.astype(jnp.int32))
        splits = jnp.zeros((ctx.num_experts,), jnp.int32).at[
            jnp.clip(flat_e, 0, ctx.num_experts - 1)
        ].add(valid_a.astype(jnp.int32))

    transport = ctx.transport
    if transport == "fused" and ctx.max_m < total:
        if state is not None:
            raise ValueError(
                f"ep_moe LL state: max_m={ctx.max_m} < M·topk={total} — "
                "the fused transport needs full-assignment capacity and "
                "the persistent workspaces are sized by it"
            )
        # the fused aligned payload must hold EVERY assignment; a
        # per-peer-capacity max_m (< M·topk — the documented sizing the
        # staged transport clamps against) degrades to the padded-slot
        # path instead of failing, preserving the old overflow semantics
        from triton_distributed_tpu.kernels.ag_gemm import _warn_once

        _warn_once(
            ("ep_moe", "fused_cap", ctx.max_m, total),
            f"ep_moe: max_m={ctx.max_m} < M·topk={total}; the fused "
            "window transport needs full-assignment capacity — using "
            "the padded-slot transport (overflow-clamping) instead",
        )
        transport = "pallas"
        ctx = replace(ctx, transport="pallas")

    if transport == "fused":
        from triton_distributed_tpu.kernels import moe_dispatch as md

        a2a = ctx.a2a
        with jax.named_scope("moe_dispatch"):
            # single staging pass: gather straight from x into the
            # aligned per-peer segments (no x_sorted materialization, no
            # slot inflation — the reference's on-device range
            # computation)
            counts, offs, offs_al, sendk = md.send_plan(a2a, splits)
            peer, dest = md.assignment_dest(
                a2a, flat_e[order], offs, offs_al)
            payload, scales = md.stage_aligned(
                a2a, x, order // ctx.topk, dest, n_valid
            )
            meta = md.meta_payload(a2a, splits, scales, offs_al, sendk)
            if state is None:
                recv_tok, recv_meta = md.dispatch_device(
                    a2a, payload, offs_al, sendk, meta
                )
            else:
                dtok, dmeta = md.dispatch_ll_device(
                    a2a, payload, offs_al, sendk, meta,
                    state["parity"], state["disp_tok"],
                    state["disp_meta"], instance,
                )
                recv_tok, recv_meta = md.ll_window(a2a, dtok, dmeta,
                                                   state["parity"])
            toks, rspl = md.recv_view(a2a, recv_tok, recv_meta)

            slot_m = md.slot_pad(a2a)
            eid, valid = _slot_tables(ctx, rspl, slot_m)
        with jax.named_scope("moe_gemm"):
            y = _expert_mlp(
                ctx, toks.reshape(ctx.recv_rows, ctx.hidden), eid, valid,
                w_up, w_down,
            )
        with jax.named_scope("moe_combine"):
            # return leg: slot-regular — the same chunked kernel with
            # static slot offsets carries back exactly the received row
            # ranges
            y_tok, y_meta = md.stage_return(
                a2a, y.reshape(ctx.n, slot_m, ctx.hidden)
            )
            retk = -(-jnp.sum(rspl, axis=1) // md.chunk_rows(a2a))
            if state is None:
                comb_tok, comb_meta = md.combine_device(
                    a2a, y_tok, y_meta, retk, sendk
                )
            else:
                ctok, cmeta = md.combine_ll_device(
                    a2a, y_tok, y_meta, retk, sendk,
                    state["parity"], state["comb_tok"],
                    state["comb_meta"], instance + 1,
                )
                comb_tok, comb_meta = md.ll_window(a2a, ctok, cmeta,
                                                   state["parity"])
                new_state = {
                    "parity": (state["parity"] + 1) % 2,
                    "disp_tok": dtok, "disp_meta": dmeta,
                    "comb_tok": ctok, "comb_meta": cmeta,
                }
            y_sorted = md.combine_view(
                a2a, comb_tok, comb_meta, peer, dest, offs_al, n_valid
            )
    else:
        with jax.named_scope("moe_dispatch"):
            x_sorted = x[order // ctx.topk].astype(ctx.dtype)
            # dispatch: tokens to the ranks owning their experts
            toks, rspl = _dispatch(ctx, x_sorted, splits)
            eid, valid = _slot_tables(ctx, rspl, ctx.max_m)
        with jax.named_scope("moe_gemm"):
            y = _expert_mlp(
                ctx, toks.reshape(ctx.recv_rows, ctx.hidden), eid,
                valid, w_up, w_down,
            )
        with jax.named_scope("moe_combine"):
            # combine: processed tokens back to their owners
            y_sorted = _combine(
                ctx, y.reshape(ctx.n, ctx.max_m, ctx.hidden), splits,
                total
            )

    # back to assignment order via inverse-permutation GATHER (scatter
    # only the (T,) iota; total-coverage since ``order`` is a
    # permutation), then reduce the topk groups with a segmented sum
    with jax.named_scope("moe_combine"):
        inv_order = jnp.zeros((total,), jnp.int32).at[order].set(
            jnp.arange(total, dtype=jnp.int32)
        )
        # assignment t belongs to token t // topk, so the (T, H) array
        # IS (out_rows, topk, H) row-major. One gather + one reduction
        # pass instead of a full-width f32 select pass + an f32
        # scatter-add.
        by_k = (out_rows, ctx.topk)
        w_k = w_flat.reshape(by_k)
        out = _weighted(
            y_sorted[inv_order].reshape(by_k + (ctx.hidden,)),
            w_k, w_k != 0).sum(axis=1)
    return (out, new_state) if state is not None else out


def _rail_stage(ctx: EPMoEContext, x, ids, weights):
    """Dedup rail staging: ONE row per unique (token, target-slice) pair.

    Returns (tok_slot (dcn, M, H), ids_slot (dcn, M, topk) [-1 pad],
    w_slot (dcn, M, topk) [0 pad], hit (M, dcn), u_counts (dcn,)).
    Capacity is M rows per slice — DCN payload scales with unique
    tokens, never with topk duplicates (≡ the reference's once-per-node
    put + local scatter, ep_a2a.py:74-80, :120-150)."""
    m = x.shape[0]
    slice_experts = ctx.epl * ctx.experts_per_rank
    e_slice = ids // slice_experts                       # (m, topk)
    d_idx = jnp.arange(ctx.dcn, dtype=jnp.int32)
    hit = (e_slice[:, :, None] == d_idx[None, None, :]).any(axis=1)  # (m,dcn)
    u_counts = hit.sum(axis=0).astype(jnp.int32)
    tok_of_slot = jnp.argsort(
        jnp.where(hit.T, jnp.arange(m, dtype=jnp.int32)[None, :], m),
        axis=1, stable=True,
    ).astype(jnp.int32)                                  # (dcn, m)
    valid_u = jnp.arange(m, dtype=jnp.int32)[None, :] < u_counts[:, None]
    safe = jnp.clip(tok_of_slot, 0, m - 1)
    tok_slot = jnp.where(valid_u[..., None], x[safe], 0).astype(ctx.dtype)
    ids_slot = jnp.where(valid_u[..., None], ids[safe], -1).astype(jnp.int32)
    w_slot = jnp.where(
        valid_u[..., None], weights[safe].astype(jnp.float32), 0.0
    )
    return tok_slot, ids_slot, w_slot, hit, u_counts


def _ep_moe_hier_device(x, logits, w_up, w_down, ctx: EPMoEContext):
    """Hierarchical EP with RAIL DEDUP: each token crosses DCN at most
    ONCE per target slice (not once per assignment), is expanded to its
    per-expert assignments INSIDE the slice, and its per-slice weighted
    partial crosses back as ONE row (≡ the reference's once-per-node
    put + intra-node scatter, ep_a2a.py:36-150; DCN is exactly the link
    where duplicate bytes hurt most)."""
    m = x.shape[0]
    dcn, epl, epr = ctx.dcn, ctx.epl, ctx.experts_per_rank
    with jax.named_scope("moe_route"):
        weights, ids = mu.select_experts(logits, ctx.topk)
        ids = ids.astype(jnp.int32)

    with jax.named_scope("moe_dispatch"):
        tok_slot, ids_slot, w_slot, hit, _ = _rail_stage(
            ctx, x, ids, weights)
        # DCN rail (same-local-rank by mesh construction): unique tokens
        # out
        rtok = jax.lax.all_to_all(
            tok_slot, ctx.dcn_axis, 0, 0, tiled=False)
        rids = jax.lax.all_to_all(
            ids_slot, ctx.dcn_axis, 0, 0, tiled=False)
        rw = jax.lax.all_to_all(w_slot, ctx.dcn_axis, 0, 0, tiled=False)

    # intra-slice flat EP over the railed set: keep only assignments
    # whose expert lives in MY slice, sentinel the rest
    my_slice = jax.lax.axis_index(ctx.dcn_axis)
    slice_experts = epl * epr
    rows = rtok.reshape(dcn * m, ctx.hidden)
    aids = rids.reshape(dcn * m, ctx.topk)
    local_e = aids - my_slice * slice_experts
    amask = (aids >= 0) & (local_e >= 0) & (local_e < slice_experts)
    flat_e = jnp.where(amask, local_e, slice_experts).reshape(-1)
    w_flat = jnp.where(amask, rw.reshape(dcn * m, ctx.topk), 0.0).reshape(-1)

    sub = replace(
        ctx,
        num_experts=slice_experts,
        max_m=ctx.max_m * dcn,
        dcn_axis=None,
        # honor the caller's transport on the intra-slice leg: "pallas"
        # keeps the padded-slot semantics (per-peer capacity with
        # overflow clamping); "fused"/"xla" pass through
        transport=ctx.transport,
    )
    part = _ep_assignments_device(
        sub, rows, flat_e, w_flat, dcn * m, w_up, w_down
    )                                                    # (dcn·m, H) f32

    # rail back: ONE weighted partial row per unique (token, slice) pair
    # — in ctx.dtype, not the f32 accumulator (DCN is exactly the link
    # where bytes hurt; the cross-slice sum still runs in f32 below)
    with jax.named_scope("moe_combine"):
        back = jax.lax.all_to_all(
            part.astype(ctx.dtype).reshape(dcn, m, ctx.hidden),
            ctx.dcn_axis, 0, 0, tiled=False,
        )
        # source side: sum each token's per-slice partials
        pos = jnp.cumsum(hit, axis=0) - 1                # (m, dcn)
        safe_pos = jnp.clip(pos, 0, m - 1)
        d_idx = jnp.arange(dcn)
        gathered = back[d_idx[None, :], safe_pos]        # (m, dcn, H)
        out = jnp.sum(
            jnp.where(hit[..., None], gathered.astype(jnp.float32), 0.0),
            axis=1,
        )
    return out.astype(x.dtype)


def ep_moe_device(x, logits, w_up, w_down, ctx: EPMoEContext, state=None,
                  instance=0):
    """Per-device EP MoE body — callable inside any shard_map.

    x: (M, H) this rank's tokens; logits: (M, E) — or, pre-routed, the
    pair ``(flat_e (M·topk,) int32, w_flat (M·topk,) f32)`` of
    :func:`_ep_assignments_device`; w_up: (epr, H, F) ((epr, H, 2F)
    gated), w_down: (epr, F, H) — this rank's experts. Returns (M, H),
    plus the updated LL workspace dict when ``state`` is given.
    """
    assert ctx.transport in ("fused", "pallas", "xla"), (
        f"unresolved transport {ctx.transport!r} — build contexts via "
        "create_ep_moe_context"
    )
    if state is not None and (
            ctx.transport != "fused" or ctx.dcn_axis or ctx.local):
        # reject here (not just in the ep_moe host entry): a state
        # silently dropped on a downgraded transport would surface as
        # None['parity'] a step later, far from the cause
        raise ValueError(
            "ep_moe_device state= rides the flat fused transport between "
            f"ranks only (got transport={ctx.transport!r}, "
            f"dcn_axis={ctx.dcn_axis!r}, ranks={ctx.n})"
        )
    if isinstance(logits, tuple):
        # PRE-ROUTED (``ep_moe(routed=)``): the caller's router chose;
        # flat exchange-local ids, the sentinel ``ctx.num_experts`` with
        # weight exactly 0 for an assignment that is not this layer's
        if ctx.dcn_axis is not None:
            raise ValueError("pre-routed ep_moe rides the flat exchange")
        flat_e, w_flat = logits
    else:
        if ctx.dcn_axis is not None:
            return _ep_moe_hier_device(x, logits, w_up, w_down, ctx)
        with jax.named_scope("moe_route"):
            weights, ids = mu.select_experts(logits, ctx.topk)
        flat_e = ids.reshape(-1).astype(jnp.int32)
        w_flat = weights.reshape(-1).astype(jnp.float32)
    res = _ep_assignments_device(
        ctx, x, flat_e, w_flat, x.shape[0], w_up, w_down,
        state=state, instance=instance,
    )
    # (the cast is the root of the fusion that holds the weighted sum:
    # under the scope, so that a profile books that fusion on it)
    with jax.named_scope("moe_combine"):
        if state is not None:
            out, new_state = res
            return out.astype(x.dtype), new_state
        return res.astype(x.dtype)


@functools.lru_cache(maxsize=64)
def _build_ep_moe(ctx: EPMoEContext, ikey: tuple = (), instance=None):
    # ikey: config.interp_key() — chaos/race knobs are baked in at trace
    # time, so they must participate in the cache identity (like every
    # other kernel builder; del keeps the signature honest about usage).
    # instance: the EPMoEState identity (None → stateless barrier mode).
    del ikey
    rows = P(tuple(ctx.batch_axes) + ctx.ep_axes)
    experts = P(ctx.ep_axes)
    if instance is None:
        fn = jax.shard_map(
            functools.partial(ep_moe_device, ctx=ctx),
            mesh=ctx.mesh,
            in_specs=(rows, rows, experts, experts),
            out_specs=rows,
            check_vma=False,
        )
        return jax.jit(fn)
    ws_specs = {
        "parity": P(),
        "disp_tok": rows, "disp_meta": rows,
        "comb_tok": rows, "comb_meta": rows,
    }
    def body(x, logits, w_up, w_down, ws):
        return ep_moe_device(
            x, logits, w_up, w_down, ctx, state=ws, instance=instance
        )

    fn = jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=(rows, rows, experts, experts, ws_specs),
        out_specs=(rows, ws_specs),
        check_vma=False,
    )
    # donate the workspaces: the LL protocol REQUIRES the same physical
    # buffers to carry every call (skewed peers' in-flight DMAs target
    # the persistent addresses)
    return jax.jit(fn, donate_argnums=(4,))


def ep_moe(x, logits, w_up, w_down, ctx: EPMoEContext, state=None):
    """Host entry: EP MoE MLP on ``ctx.mesh``.

    Global shapes: x (M, H) and logits (M, E) token-sharded over
    ``ctx.axis``; w_up (E, H, F) / w_down (E, F, H) expert-sharded over
    ``ctx.axis``. Returns (M, H) token-sharded.

    PRE-ROUTED: pass ``logits`` as the pair ``(flat_e (M·topk,) int32,
    w_flat (M·topk,) f32)`` — the caller's own router (a sigmoid router
    with a selection bias, a chip's share of a wider layer:
    ``moe_utils.held_assignments``); ``flat_e`` are ids local to
    ``ctx.num_experts`` experts, the sentinel ``ctx.num_experts`` (weight
    exactly 0) for an assignment that is not this layer's to compute.

    With ``state`` (an :class:`EPMoEState` from
    :func:`create_ep_moe_state`): the fused transport runs BARRIER-FREE
    over the state's persistent double-buffered workspaces and the call
    returns ``(out, state')`` — thread ``state'`` into the next call
    (the reference's call_count protocol, low_latency_all_to_all.py:
    97-118, as a functional carry usable inside jitted decode loops).
    """
    from triton_distributed_tpu.config import interp_key

    reason = _transport_degrade_reason(ctx)
    if reason is not None:
        from triton_distributed_tpu.ops.overlap import _log_demotion_once

        _log_demotion_once("ep_moe", reason)
        demoted = replace(ctx, transport="xla")
        out = _build_ep_moe(demoted, interp_key())(x, logits, w_up, w_down)
        if state is not None:
            # the LL workspaces carry no obligations while the fused
            # transport is demoted — return them untouched so the caller's
            # state threading survives the degradation window
            return out, state
        return out
    if state is None:
        return _build_ep_moe(ctx, interp_key())(x, logits, w_up, w_down)
    if ctx.transport != "fused":
        raise ValueError("ep_moe state= requires transport='fused'")
    fn = _build_ep_moe(ctx, interp_key(), state.instance)
    out, ws = fn(x, logits, w_up, w_down, state.as_dict())
    return out, EPMoEState(instance=state.instance, **ws)


def _transport_degrade_reason(ctx: EPMoEContext) -> str | None:
    """Should the Pallas/fused MoE transport demote to the XLA a2a for
    this call? Same probe family as ``ops.overlap.preflight``: an
    unhealthy peer in the active fault plan or a prior watchdog trip.
    Quantized wire payloads cannot demote (the XLA transport is
    full-precision only) — those keep the fused path and surface
    whatever the fault is. ONE rank has no transport to demote."""
    if (ctx.local or ctx.transport not in ("fused", "pallas")
            or ctx.quant is not None):
        return None
    from triton_distributed_tpu.runtime import faults, watchdog

    plan = faults.active_plan()
    if plan is not None and plan.unhealthy_peers:
        return (
            f"fault plan marks peer(s) {plan.unhealthy_peers} unhealthy "
            f"(plan seed={plan.seed})"
        )
    if watchdog.last_trip() is not None:
        return "collective watchdog tripped on a prior step"
    from triton_distributed_tpu.runtime import health

    for ledger in health.live_ledgers():
        bad = ledger.unhealthy_peers()
        if bad:
            return f"health ledger marks peer(s) {bad} unhealthy"
    return None


_EP_MOE_TUNERS: OrderedDict = OrderedDict()
_EP_MOE_TUNERS_MAX = 64          # bounded like the sibling _build caches


def ep_moe_tuned(x, logits, w_up, w_down, ctx: EPMoEContext,
                 candidates: tuple = (64, 128, 256)):
    """``ep_moe`` with ``block_m`` autotuned per input shape.

    The L6→L4 integration the reference gets from wrapping kernels in
    ``contextual_autotune`` (autotuner.py:97): the whole thunk is
    benchmarked per block size (alignment capacity changes with it, so
    the tuning unit must be the op, not the inner GEMM), the winner is
    cached per shape, and on multi-process meshes the MAX-consensus
    keeps every process on the same config.
    """
    from triton_distributed_tpu.tune import ContextualAutoTuner  # cycle: tune→ops is none, but keep ops importable without tune at module load

    key = (ctx, tuple(candidates))
    tuner = _EP_MOE_TUNERS.get(key)
    if tuner is None:
        def run(x, logits, up, down, *, block_m):
            return ep_moe(x, logits, up, down, replace(ctx, block_m=block_m))

        # ctx is part of the tuner identity: the persistent winner store
        # keys on (name, arg shapes), and two contexts with identical
        # token shapes but different transport/quant/geometry must not
        # share winners
        ctx_tag = (
            f"{dict(ctx.mesh.shape)}|{ctx.axis}|{ctx.dcn_axis}|"
            f"E{ctx.num_experts}k{ctx.topk}m{ctx.max_m}|{ctx.transport}|"
            f"{ctx.quant}|{jnp.dtype(ctx.dtype).name}"
        )
        tuner = ContextualAutoTuner(
            run, [{"block_m": b} for b in candidates],
            name=f"ep_moe[{ctx_tag}]",
        )
        _EP_MOE_TUNERS[key] = tuner
        while len(_EP_MOE_TUNERS) > _EP_MOE_TUNERS_MAX:
            _EP_MOE_TUNERS.popitem(last=False)
    else:
        _EP_MOE_TUNERS.move_to_end(key)
    return tuner(x, logits, w_up, w_down)
