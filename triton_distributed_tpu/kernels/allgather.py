"""AllGather engines (TPU-native re-design of the reference AG family).

Reference: python/triton_dist/kernels/nvidia/allgather.py — copy-engine
full-mesh push/pull (:79-135), 1D ring push (:138), NUMA-aware 2D ring
(:194), inter-node NVSHMEM variants (:291-468), with ``AllGatherMethod``
auto-selection (:44-69); low-latency variants in low_latency_allgather.py.

TPU re-design: the torus makes rings the bandwidth-optimal method over
ICI, so the workhorses are a unidirectional ring and a bidirectional ring
(each direction carries half of every shard → 2× bandwidth). For small
messages a direct all-to-all push minimizes hops (the role the reference's
LL-packed protocol plays; TPU needs no flag packing because the RDMA recv
semaphore is ordered after payload arrival). DCN / no-Pallas paths fall
back to ``jax.lax.all_gather``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import lang
from triton_distributed_tpu.config import interp_key
from triton_distributed_tpu.lang import wire as wirelib
from triton_distributed_tpu.runtime import (
    AllGatherMethod,
    auto_allgather_method,
    detect_topology,
    ring_neighbors,
)
from triton_distributed_tpu.runtime import faults as _faults
from triton_distributed_tpu.utils.testing import chaos_delay

_SITE = "allgather"     # fault-plan / watchdog site for every AG engine


def _ring_ag_kernel(
    n, axis, mesh_axes, schedule, x_ref, out_ref, send_sem, recv_sem
):
    """Unidirectional ring: at step s forward shard (me-s) to the right
    neighbor; after n-1 steps everyone holds everything. The traversal
    (direction, chunk order) is the :class:`RingSchedule`'s to choose;
    ``schedule=None`` is the canonical forward ring, byte-identical to
    the pre-schedule kernel."""
    direction = "fwd" if schedule is None else schedule.direction
    order = "ring" if schedule is None else schedule.chunk_order
    me = lang.my_pe(axis)
    m = x_ref.shape[0]
    left, right = ring_neighbors(me, n)
    left, right = lang.pe_flat(axis, left, mesh_axes), lang.pe_flat(axis, right, mesh_axes)
    to = right if direction == "fwd" else left

    out_ref[pl.ds(me * m, m)] = x_ref[:]
    # payload-corruption hook: the local slab is both what the ring
    # forwards and what lands in the result, so a corrupted word here
    # propagates exactly like a corrupted wire payload would
    _faults.maybe_corrupt(out_ref, _SITE, me, n, row_off=me * m)
    lang.neighbor_barrier(axis, left, right, site=_SITE, me=me, n=n)

    # One semaphore slot per step: a slot's credit can then only come from
    # that step's DMA, so a wait being satisfied proves that *specific*
    # transfer landed (slot reuse would let a later step's credit release an
    # earlier wait while its data is still in flight).
    last = n - 1 if order != "skip_last" else n - 2
    for s in range(last):
        if direction == "fwd":
            src = jax.lax.rem(me + n - s, n) if s > 0 else me
        else:
            src = jax.lax.rem(me + s, n)
        chaos_delay(site=_SITE, step=s, me=me, n=n)
        dma = lang.remote_copy(
            out_ref.at[pl.ds(src * m, m)],
            out_ref.at[pl.ds(src * m, m)],
            send_sem.at[s],
            recv_sem.at[s],
            to,
        )
        dma.start()
        dma.wait()  # drains send + the symmetric incoming recv


def _ring_ag_kernel_w(
    n, axis, mesh_axes, schedule,
    x_ref, xq_ref, xs_ref, out_ref, outq_ref, outs_ref,
    send_sem, recv_sem, s_send_sem, s_recv_sem,
):
    """Quantized-wire twin of :func:`_ring_ag_kernel`: the ring forwards
    the host-quantized slab (1 byte/elem) plus a per-ROW f32 scale plane
    (lang.wire with chunk_rows=1 — the VMEM-resident engines afford
    row-granular scales), dequantizing each arrival into ``out_ref``.
    The own slab is written exact from ``x_ref`` (it never crosses the
    wire), matching the fused engines' wire contract."""
    direction = "fwd" if schedule is None else schedule.direction
    order = "ring" if schedule is None else schedule.chunk_order
    rail = "own" if schedule is None else schedule.scale_rail
    me = lang.my_pe(axis)
    m = x_ref.shape[0]
    left, right = ring_neighbors(me, n)
    left = lang.pe_flat(axis, left, mesh_axes)
    right = lang.pe_flat(axis, right, mesh_axes)
    to = right if direction == "fwd" else left
    sr_sem = s_recv_sem if rail == "own" else recv_sem

    out_ref[pl.ds(me * m, m)] = x_ref[:]
    outq_ref[pl.ds(me * m, m)] = xq_ref[:]
    outs_ref[pl.ds(me * m, m)] = xs_ref[:]
    _faults.maybe_corrupt(out_ref, _SITE, me, n, row_off=me * m)
    lang.neighbor_barrier(axis, left, right, site=_SITE, me=me, n=n)

    last = n - 1 if order != "skip_last" else n - 2
    for s in range(last):
        if direction == "fwd":
            src = jax.lax.rem(me + n - s, n) if s > 0 else me
        else:
            src = jax.lax.rem(me + s, n)
        chaos_delay(site=_SITE, step=s, me=me, n=n)
        dma_q = lang.remote_copy(
            outq_ref.at[pl.ds(src * m, m)],
            outq_ref.at[pl.ds(src * m, m)],
            send_sem.at[s], recv_sem.at[s], to,
        )
        dma_s = lang.remote_copy(
            outs_ref.at[pl.ds(src * m, m)],
            outs_ref.at[pl.ds(src * m, m)],
            s_send_sem.at[s], sr_sem.at[s], to,
        )
        dma_q.start()
        dma_s.start()
        dma_q.wait()   # drains send + the symmetric incoming recv
        dma_s.wait()
        # the slab that just LANDED came from the upstream neighbor:
        # its step-s source — shard (me∓1∓s) — dequantize it for the
        # caller (the wire copy stays resident for the next forward)
        if direction == "fwd":
            arr = jax.lax.rem(me + 2 * n - 1 - s, n)
        else:
            arr = jax.lax.rem(me + 1 + s, n)
        wirelib.dequant_rows_into(
            out_ref.at[pl.ds(arr * m, m)],
            outq_ref.at[pl.ds(arr * m, m)],
            outs_ref.at[pl.ds(arr * m, m)],
        )


def _ring_bidir_ag_kernel(
    n, axis, mesh_axes, schedule, x_ref, out_ref, send_sem, recv_sem
):
    """Bidirectional ring: clockwise carries the left split8/8 columns of
    every shard, counter-clockwise the rest → each link moves a fraction
    of the bytes, halving AG time on a torus at the default even split."""
    me = lang.my_pe(axis)
    m = x_ref.shape[0]
    k = x_ref.shape[1]
    if schedule is None:
        kh = k // 2
    else:
        # lane-align the split point so both column slices stay Mosaic-
        # friendly; at split8=4 on lane-multiple widths this is k // 2
        kh = (k * int(schedule.split8)) // 8
        if k >= 256:
            kh = max(128, min(k - 128, (kh // 128) * 128))
    left, right = ring_neighbors(me, n)
    left, right = lang.pe_flat(axis, left, mesh_axes), lang.pe_flat(axis, right, mesh_axes)

    out_ref[pl.ds(me * m, m)] = x_ref[:]
    lang.neighbor_barrier(axis, left, right, site=_SITE, me=me, n=n)

    # Per-step distinct semaphore slots (see _ring_ag_kernel): cw uses
    # slots [0, n-1), ccw uses [n-1, 2(n-1)).
    for s in range(n - 1):
        cw_src = jax.lax.rem(me + n - s, n)   # shard forwarded clockwise
        ccw_src = jax.lax.rem(me + s, n)      # shard forwarded counter-clockwise
        chaos_delay(site=_SITE, step=s, me=me, n=n)
        cw = lang.remote_copy(
            out_ref.at[pl.ds(cw_src * m, m), pl.ds(0, kh)],
            out_ref.at[pl.ds(cw_src * m, m), pl.ds(0, kh)],
            send_sem.at[s],
            recv_sem.at[s],
            right,
        )
        ccw = lang.remote_copy(
            out_ref.at[pl.ds(ccw_src * m, m), pl.ds(kh, k - kh)],
            out_ref.at[pl.ds(ccw_src * m, m), pl.ds(kh, k - kh)],
            send_sem.at[n - 1 + s],
            recv_sem.at[n - 1 + s],
            left,
        )
        cw.start()
        ccw.start()
        cw.wait()
        ccw.wait()


def _ll_push_ag_kernel(n, axis, mesh_axes, x_ref, out_ref, send_sem, recv_sem):
    """Small-message path: push the local shard straight to every peer
    (one hop, n-1 concurrent RDMAs), then wait for the n-1 arrivals.
    ≡ the role of the reference's LL/multimem fast-allgather
    (low_latency_allgather.py:532-624) — flag packing is unnecessary
    because TPU recv semaphores fire after payload arrival."""
    me = lang.my_pe(axis)
    m = x_ref.shape[0]

    out_ref[pl.ds(me * m, m)] = x_ref[:]
    _faults.maybe_corrupt(out_ref, _SITE, me, n, row_off=me * m)
    lang.barrier_all(axis, mesh_axes)

    handles = []
    for i in range(n - 1):
        peer = lang.pe_flat(axis, jax.lax.rem(me + 1 + i, n), mesh_axes)
        chaos_delay(site=_SITE, step=i, me=me, n=n)
        handles.append(
            lang.putmem_signal_nbi_block(
                out_ref.at[pl.ds(me * m, m)],
                out_ref.at[pl.ds(me * m, m)],
                send_sem.at[i],
                recv_sem.at[i],
                peer,
            )
        )
    lang.quiet(*handles)
    # wait for the n-1 incoming shards (equal-size, any order)
    for i, h in enumerate(handles):
        h.wait_recv()


def _ll_persist_kernel(
    n, axis, mesh_axes, parity_ref, x_ref, ws_in, out_ref, ws_out,
    send_sem, recv_sem, local_sem,
):
    """Barrier-free small-message AG over a PERSISTENT double-buffered
    workspace (≡ the reference's LL protocol: persistent symmetric
    buffers + call_count double buffering, low_latency_allgather.py:
    532-569 — no entry barrier at all).

    Why no barrier is needed: a rank finishes call N only after
    receiving every peer's call-N push, so inter-rank skew is bounded
    by ONE call. Writes for call N land in parity window N%2; the only
    other traffic a lagging peer can have outstanding is for call N-1
    in window (N-1)%2 — disjoint. The workspace aliases input→output
    (pallas input_output_aliases + jit donation), so the SAME physical
    buffer carries every call; the per-call recv DMA semaphore (n-1
    credits) replaces the reference's packed flag words.

    Semaphores are PER-PARITY rows (2, n-1): Mosaic reuses the same
    physical semaphores across calls of a kernel, so a skewed peer's
    call-N+1 credit must not be able to satisfy my call-N wait — with
    parity rows it lands in the other row, and a same-parity mix-up
    (call N vs N+2) is impossible because skew > 1 contradicts the
    recv dependency. This is the counting-semaphore translation of the
    reference's exact-value ``signal_wait_until(EQ, call_count)``.

    parity_ref: SMEM (1,) = call_idx % 2; ws_in/ws_out: the aliased
    (2·n·m, k) persistent workspace; out_ref: (n·m, k) fresh output
    (the parity window is drained into it — the window is overwritten
    two calls later)."""
    del ws_in  # aliased with ws_out — one buffer, two names
    me = lang.my_pe(axis)
    m = x_ref.shape[0]
    parity = parity_ref[0]
    base = parity * (n * m)

    # my own slot: local VMEM→HBM copy into the window (the drain below
    # reads the whole window, mine included)
    cp_self = pltpu.make_async_copy(
        x_ref, ws_out.at[pl.ds(base + me * m, m)], local_sem
    )
    cp_self.start()

    handles = []
    for i in range(n - 1):
        peer = lang.pe_flat(axis, jax.lax.rem(me + 1 + i, n), mesh_axes)
        chaos_delay(site=_SITE, step=i, me=me, n=n)
        handles.append(
            lang.putmem_signal_nbi_block(
                ws_out.at[pl.ds(base + me * m, m)],   # peer's slot `me`
                x_ref,
                send_sem.at[parity, i],
                recv_sem.at[parity, i],
                peer,
            )
        )
    lang.quiet(*handles)
    for h in handles:
        h.wait_recv()
    cp_self.wait()
    drain = pltpu.make_async_copy(
        ws_out.at[pl.ds(base, n * m)], out_ref, local_sem
    )
    drain.start()
    drain.wait()


_KERNELS = {
    # (kernel, number of semaphore slots as fn of n)
    AllGatherMethod.RING_1D: (_ring_ag_kernel, lambda n: n - 1),
    AllGatherMethod.RING_BIDIR: (_ring_bidir_ag_kernel, lambda n: 2 * (n - 1)),
    AllGatherMethod.LL_SMALL: (_ll_push_ag_kernel, lambda n: n - 1),
}


@functools.lru_cache(maxsize=256)
def _build_all_gather(mesh, axis, method, shape, dtype, collective_id, chaos,
                      wire=None, schedule=None):
    """Compile-once factory: the jitted collective for one (mesh, shape)
    configuration. lru_cache gives call-site reuse — without it every
    invocation would rebuild pallas_call+shard_map+jit and retrace.

    ``wire`` ('fp8'/'int8'): quantized ring wire (lang.wire, per-row
    scales). Supported on RING_1D (the Pallas wire kernel) and
    XLA_FALLBACK (quantize → gather payload+scales → dequantize, the
    numerics twin that also genuinely halves DCN bytes); the entry
    demotes other methods to the raw wire."""
    n = mesh.shape[axis]
    m = shape[0] // n
    fmt = (
        wirelib.WireFormat(quant=wire, chunk_rows=1)
        if wire is not None else None
    )
    if method == AllGatherMethod.XLA_FALLBACK:
        if fmt is None:
            inner = lambda s: jax.lax.all_gather(s, axis, tiled=True)  # noqa: E731
        else:
            def inner(s):
                q, sc = wirelib.quantize_slab(s, fmt)
                qg = jax.lax.all_gather(q, axis, tiled=True)
                sg = jax.lax.all_gather(sc, axis, tiled=True)
                out = wirelib.dequantize_slab(qg, sg, fmt, s.dtype)
                # own slab exact, like the ring wire kernels
                me = jax.lax.axis_index(axis)
                return jax.lax.dynamic_update_slice(
                    out, s, (me * m,) + (0,) * (s.ndim - 1)
                )
        # instrumented like the Pallas engines: an XLA collective can
        # wedge too (DCN partner loss), and the watchdog/stall hooks are
        # pure host callbacks — no Pallas machinery needed
        body = lang.maybe_instrument(
            inner,
            axis=axis, site=_SITE, collective_id=collective_id, n=n,
        )
        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(None),
            check_vma=False,
        )
        return jax.jit(fn)

    if fmt is not None:
        assert method == AllGatherMethod.RING_1D, method
        wirelib.require_inkernel(wire, "all_gather")
        nsem = max(n - 1, 1)
        call = lang.shmem_call(
            functools.partial(
                _ring_ag_kernel_w, n, axis, mesh.axis_names, schedule
            ),
            out_shape=[
                jax.ShapeDtypeStruct(shape, dtype),
                jax.ShapeDtypeStruct(shape, fmt.wire_dtype),
                jax.ShapeDtypeStruct(
                    (shape[0], wirelib.SCALE_LANES), jnp.float32
                ),
            ],
            in_specs=lang.vmem_specs(3),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((nsem,)),
                pltpu.SemaphoreType.DMA((nsem,)),
                pltpu.SemaphoreType.DMA((nsem,)),   # scale rail
                pltpu.SemaphoreType.DMA((nsem,)),
            ],
            collective_id=collective_id,
            name=f"ag_ring_1d_{wire}w",
        )
        call = lang.maybe_instrument(
            call, axis=axis, site=_SITE, collective_id=collective_id, n=n
        )

        def body(x_loc):
            q, sc = wirelib.quantize_slab(x_loc, fmt)
            return call(x_loc, q, sc)[0]

        fn = jax.shard_map(
            body, mesh=mesh, in_specs=P(axis), out_specs=P(None),
            check_vma=False,
        )
        return jax.jit(fn)

    kernel_fn, nsem_fn = _KERNELS[method]
    nsem = max(nsem_fn(n), 1)
    if method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR):
        kernel = functools.partial(
            kernel_fn, n, axis, mesh.axis_names, schedule
        )
    else:
        kernel = functools.partial(kernel_fn, n, axis, mesh.axis_names)
    call = lang.shmem_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=lang.vmem_specs(1),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((nsem,)),
            pltpu.SemaphoreType.DMA((nsem,)),
        ],
        collective_id=collective_id,
        name=f"ag_{method.value}",
    )
    call = lang.maybe_instrument(
        call, axis=axis, site=_SITE, collective_id=collective_id, n=n
    )
    fn = jax.shard_map(
        call, mesh=mesh, in_specs=P(axis), out_specs=P(None), check_vma=False
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _build_ll_persist(mesh, axis, m_local, k, dtype, collective_id, chaos,
                      instance=0):
    """Jitted barrier-free LL AG: (parity, x, ws) → (gathered, ws') with
    the workspace donated/aliased straight through.

    ``instance`` keys the build per PersistentLLAllGather INSTANCE: two
    live contexts with identical configs must not share one compiled
    kernel — its physical per-parity DMA semaphores would be shared too,
    and interleaved calls could satisfy each other's waits while the
    data sits in the *other* instance's workspace."""
    n = mesh.shape[axis]
    call = lang.shmem_call(
        functools.partial(_ll_persist_kernel, n, axis, mesh.axis_names),
        out_shape=[
            jax.ShapeDtypeStruct((n * m_local, k), dtype),
            jax.ShapeDtypeStruct((2 * n * m_local, k), dtype),
        ],
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2, max(n - 1, 1))),
            pltpu.SemaphoreType.DMA((2, max(n - 1, 1))),
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={2: 1},
        # barrier-FREE by design: the kernel never touches the barrier
        # semaphore, and Mosaic rejects a collective_id on one that
        # doesn't (collective_id arg kept for the state cache key only)
        collective_id=None,
        name="ag_ll_persist",
    )
    call = lang.maybe_instrument(
        call, axis=axis, site=_SITE, collective_id=collective_id, n=n
    )
    fn = jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(None), P(axis)),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(2,))


class PersistentLLAllGather:
    """Context-owned barrier-free LL allgather (≡ the reference's
    ``AllGatherLayer`` owning persistent symmetric buffers with per-call
    signal bookkeeping, low_latency_allgather_layer.py:31-195).

    Owns the double-buffered workspace and the call counter; each call
    runs the barrier-free kernel (no ``barrier_all`` before the pushes —
    for the small-message regime that barrier IS the latency). Stateful
    by design: use it where the reference layer is used (decode-step
    loops), not inside a larger jit trace.
    """

    _next_instance = [0]

    def __init__(self, mesh, axis, shard_shape, dtype=jnp.bfloat16,
                 collective_id: int = 12):
        from jax.sharding import NamedSharding

        m, k = shard_shape
        self.mesh, self.axis = mesh, axis
        self.n = mesh.shape[axis]
        self.m, self.k = m, k
        self.dtype = jnp.dtype(dtype)
        self.collective_id = collective_id
        self.call_idx = 0
        # per-instance kernel identity — see _build_ll_persist
        self.instance = PersistentLLAllGather._next_instance[0]
        PersistentLLAllGather._next_instance[0] += 1
        self.ws = jax.device_put(
            jnp.zeros((self.n * 2 * self.n * m, k), self.dtype),
            NamedSharding(mesh, P(axis)),
        )

    def __call__(self, x):
        """x: (n·m, k) sharded P(axis) → (n·m, k) replicated gathered."""
        fn = _build_ll_persist(
            self.mesh, self.axis, self.m, self.k, self.dtype,
            self.collective_id, interp_key(), self.instance,
        )
        parity = jnp.full((1,), self.call_idx % 2, jnp.int32)
        out, self.ws = fn(parity, x, self.ws)
        self.call_idx += 1
        return out


@functools.lru_cache(maxsize=64)
def _engine_tuner(mesh, axis, collective_id):
    """Measured engine selection for ``method=None`` — replaces the
    static 64 KiB LL threshold with a per-shape measurement (the
    reference's contextual_autotune wrapping, autotuner.py:97); winners
    persist on disk and the MAX consensus aligns processes."""
    from triton_distributed_tpu.tune.autotuner import method_tuner

    def run(x, *, method):
        return all_gather(
            x, mesh, axis, method=AllGatherMethod(method),
            collective_id=collective_id,
        )

    # LL_PERSIST is excluded: inside jit traces all_gather silently
    # demotes it to LL_SMALL (the persistent workspace is module state),
    # so a persisted 'll_persist' winner would not be the engine that
    # actually runs at traced call sites — the measured winner must
    # always match the executed engine (ADVICE r3). Callers wanting the
    # barrier-free protocol opt in explicitly (method=LL_PERSIST eager,
    # or PersistentLLAllGather / the MoE LL transport in jitted loops).
    candidates = [
        m for m in AllGatherMethod if m != AllGatherMethod.LL_PERSIST
    ]
    return method_tuner(
        f"all_gather[{dict(mesh.shape)}|{axis}|{collective_id}]",
        run, candidates,
    )


def _resolve_ag_wire(wire_dtype, method, x, n):
    """The wire :func:`all_gather` will actually ship: None unless the
    payload is 2-D, the method carries a wire (RING_1D / XLA_FALLBACK),
    and the per-row scale plane actually saves bytes. 'auto' defers to
    :func:`runtime.topology.auto_allgather_wire`; an explicit 'fp8' /
    'int8' on an ineligible payload raises (pinned = contract)."""
    w = wirelib.normalize_wire(wire_dtype)
    if w is None:
        return None
    cols = x.shape[-1] if x.ndim == 2 else 0
    eligible = (
        x.ndim == 2
        and method in (AllGatherMethod.RING_1D, AllGatherMethod.XLA_FALLBACK)
        and x.shape[0] % n == 0
        and cols * x.dtype.itemsize > cols + wirelib.SCALE_LANES * 4
    )
    inkernel = method == AllGatherMethod.RING_1D
    if w == "auto":
        if not eligible:
            return None
        if inkernel and not wirelib.inkernel_wire_ok("fp8"):
            return None  # Mosaic lacks in-kernel f8 casts; stay exact
        from triton_distributed_tpu.runtime.topology import (
            auto_allgather_wire,
        )

        shard_bytes = (x.size // n) * x.dtype.itemsize
        return auto_allgather_wire(shard_bytes)
    if inkernel:
        wirelib.require_inkernel(w, "all_gather")
    if not eligible:
        raise ValueError(
            f"all_gather wire_dtype={w!r} needs a 2-D payload with "
            f"cols·itemsize > cols + {wirelib.SCALE_LANES * 4} on a "
            "ring_1d/xla method (a pinned wire format is a contract); "
            f"got shape {x.shape} {x.dtype} on {method}"
        )
    return w


def all_gather(
    x,
    mesh,
    axis: str = "x",
    *,
    method: AllGatherMethod | None = None,
    collective_id: int = 2,
    wire_dtype=None,
    schedule=None,
):
    """AllGather ``x`` (sharded on dim 0 along ``axis``) → replicated full array.

    Host entry ≡ reference ``fast_allgather`` dispatcher
    (low_latency_allgather.py:971) + method auto-selection (allgather.py:54-69).

    ``wire_dtype``: quantized ring wire ('fp8'/'int8' — 1-byte payload +
    per-row f32 scales, own slab exact; 'auto' — compressed above the
    topology helper's byte threshold). Carried by the RING_1D and
    XLA_FALLBACK engines; with an explicit compressed wire a bidir/LL
    method resolution is demoted to RING_1D so the wire request wins.
    """
    n = mesh.shape[axis]
    if n == 1:
        return x
    if method is None:
        from triton_distributed_tpu.runtime.topology import LinkKind
        from triton_distributed_tpu.tune.autotuner import tuned_method_or_none

        topo = detect_topology(mesh, axis)
        if topo.link_kind == LinkKind.DCN:
            # Pallas remote DMA cannot cross DCN: never bench Pallas
            # candidates here (a failure may hang, not raise) and never
            # apply a disk winner persisted on an ICI mesh — the same
            # environment re-validation ag_gemm/gemm_rs do before using
            # a tuned method.
            method = AllGatherMethod.XLA_FALLBACK
        else:
            m = tuned_method_or_none(
                lambda: _engine_tuner(mesh, axis, collective_id), x
            )
            if m is not None:
                method = AllGatherMethod(m)
            else:
                shard_bytes = (x.size // n) * x.dtype.itemsize
                method = auto_allgather_method(topo, shard_bytes)
    if method == AllGatherMethod.RING_BIDIR and (x.ndim < 2 or x.shape[1] < 2):
        # bidir splits dim 1 between the two directions — impossible on
        # rank-1 / single-column inputs; fall back to the plain ring.
        method = AllGatherMethod.RING_1D
    if wirelib.normalize_wire(wire_dtype) in ("fp8", "int8") and method in (
        AllGatherMethod.RING_BIDIR, AllGatherMethod.LL_SMALL,
        AllGatherMethod.LL_PERSIST,
    ):
        # an explicit compressed wire outranks the method heuristic —
        # only the plain ring (and the XLA fallback) carry the wire
        method = AllGatherMethod.RING_1D
    if method == AllGatherMethod.LL_PERSIST:
        if isinstance(x, jax.core.Tracer) or x.ndim != 2:
            # the persistent workspace is module state — unreachable from
            # inside a trace (and the context is 2-D); the barrier'd LL
            # push is the stateless equivalent
            method = AllGatherMethod.LL_SMALL
        else:
            return _persist_state(
                mesh, axis, (x.shape[0] // n, x.shape[1]), x.dtype,
                collective_id,
            )(x)
    wire = _resolve_ag_wire(wire_dtype, method, x, n)
    if method in (AllGatherMethod.RING_1D, AllGatherMethod.RING_BIDIR):
        from triton_distributed_tpu.tune.schedule import resolve_schedule

        family = (
            "allgather.ring_1d"
            if method == AllGatherMethod.RING_1D
            else "allgather.ring_bidir"
        )
        schedule = resolve_schedule(family, x.shape, (n,), wire, schedule)
    else:
        schedule = None
    fn = _build_all_gather(
        mesh, axis, method, x.shape, x.dtype, collective_id, interp_key(),
        wire=wire, schedule=schedule,
    )
    return fn(x)


from collections import OrderedDict

_PERSIST_STATES: OrderedDict = OrderedDict()
_PERSIST_STATES_MAX = 8   # each entry PINS a 2× gathered-array HBM
                          # workspace per device — keep the LRU small


def _persist_state(mesh, axis, shard_shape, dtype, collective_id):
    """Module-owned PersistentLLAllGather per configuration — the
    context the reference keeps in its AllGatherLayer, surfaced through
    the stateless ``all_gather(method=LL_PERSIST)`` entry so the engine
    tuner can bench it like any other method. LRU-bounded: evicting an
    entry only frees its workspace (the protocol carries no cross-call
    obligations beyond the buffer — a fresh context restarts at call 0).
    """
    key = (mesh, axis, tuple(shard_shape), jnp.dtype(dtype), collective_id)
    st = _PERSIST_STATES.get(key)
    if st is None:
        st = _PERSIST_STATES[key] = PersistentLLAllGather(
            mesh, axis, shard_shape, dtype, collective_id
        )
        while len(_PERSIST_STATES) > _PERSIST_STATES_MAX:
            _PERSIST_STATES.popitem(last=False)
    else:
        _PERSIST_STATES.move_to_end(key)
    return st
