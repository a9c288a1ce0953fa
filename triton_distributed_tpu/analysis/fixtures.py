"""Deliberately broken SHMEM kernels — one per shmemlint rule.

These exist so every rule is pinned by a real kernel body forever, and
specifically to close the caveat ``tests/test_races.py`` documents: the
TPU interpreter's dynamic race detector has MISSED a deliberately
removed wait under ``dma_execution_mode="on_wait"``. The
:func:`missing_wait` fixture is exactly that bug, and
``tests/test_analysis.py`` asserts shmemlint flags it (SL001) with
rank + semaphore diagnostics — statically, on any jax, no interpreter
required.

Each fixture returns a hand-built
:class:`~triton_distributed_tpu.lang.launch.LaunchSpec` plus the
per-device input shapes, ready for
:func:`triton_distributed_tpu.analysis.lint.analyze_spec`.
"""

from __future__ import annotations

import numpy as np

from triton_distributed_tpu import lang
from triton_distributed_tpu.lang import wire as wirelib
from triton_distributed_tpu.lang.launch import LaunchSpec

_F32 = np.dtype(np.float32)


def _f8():
    import ml_dtypes

    return np.dtype(ml_dtypes.float8_e4m3fn)


def _spec(kernel, name, out_shapes=(), scratch=(), collective_id=None,
          vmem_limit_bytes=None):
    import jax

    return LaunchSpec(
        name=name,
        kernel=kernel,
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_shapes],
        in_specs=None,
        out_specs=None,
        scratch_shapes=tuple(scratch),
        collective_id=collective_id,
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _sems(*shapes):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.SemaphoreType.DMA(s) if s else pltpu.SemaphoreType.REGULAR(())
            for s in shapes]


def missing_wait(axis="x"):
    """The test_races caveat, seeded: every rank pushes its shard to
    every peer and signals arrival, but the consuming
    ``signal_wait_until`` was "forgotten" — the kernel reads the
    gathered buffer with nothing ordering the landings. Dynamically
    this is a probabilistic wrong-answer; statically it is SL001
    (unconsumed flag credits) + SL004 (unordered landing vs the read).
    """

    def kernel(x_ref, out_ref, chk_ref, send_sem, recv_sem, flag_sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        m = x_ref.shape[0]
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        out_ref[pl.ds(me * m, m)] = x_ref[:]
        lang.barrier_all(axis)
        handles = []
        for i in range(n - 1):
            peer = (me + 1 + i) % n
            handles.append(lang.putmem_signal_nbi_block(
                out_ref.at[pl.ds(me * m, m)],
                x_ref,
                send_sem.at[i],
                recv_sem.at[i],
                peer,
            ))
            lang.signal_op(flag_sem, 1, pe=peer, site="fixture")
        lang.quiet(*handles)
        # BUG: no `for i in range(n-1): lang.signal_wait_until(flag_sem, 1)`
        # and no recv waits — the landings are unordered with this read:
        chk_ref[0, 0] = jnp.sum(out_ref[:])

    return (
        _spec(
            kernel, "fixture_missing_wait",
            out_shapes=[((8 * 8, 128), _F32), ((1, 1), _F32)],
            scratch=_sems((8,), (8,), None),
            collective_id=40,
        ),
        lambda n: [((8, 128), _F32)],
    )


def credit_imbalance(axis="x"):
    """Off-by-one credit accounting: each rank sends ONE barrier credit
    (to its right neighbor) but waits for TWO — the classic symptom
    that today only shows up as a hang the watchdog must catch. SL002.
    """

    def kernel(x_ref, out_ref, sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        lang.signal_op(sem, 1, pe=(me + 1) % n, site="fixture")
        lang.signal_wait_until(sem, 2)     # BUG: only 1 credit ever comes
        out_ref[:] = x_ref[:]

    return (
        _spec(
            kernel, "fixture_credit_imbalance",
            out_shapes=[((8, 128), _F32)],
            scratch=_sems(None),
            collective_id=41,
        ),
        lambda n: [((8, 128), _F32)],
    )


def deadlock(axis="x"):
    """Wait-before-signal around the ring: every rank parks in a wait
    whose credit is behind the next rank's identical wait. SL003 with
    the full rank cycle."""

    def kernel(x_ref, out_ref, sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        lang.signal_wait_until(sem, 1)     # BUG: nobody signals first
        lang.signal_op(sem, 1, pe=(me + 1) % n, site="fixture")
        out_ref[:] = x_ref[:]

    return (
        _spec(
            kernel, "fixture_deadlock",
            out_shapes=[((8, 128), _F32)],
            scratch=_sems(None),
            collective_id=42,
        ),
        lambda n: [((8, 128), _F32)],
    )


def barrier_mismatch(axis="x"):
    """Rank 0 runs an extra ``barrier_all`` the other ranks don't —
    diverging collective sequences across ranks. SL005 (and the missing
    peers make the extra barrier an SL002 hang)."""

    def kernel(x_ref, out_ref):
        me = lang.my_pe(axis)
        lang.barrier_all(axis)
        if me == 0:                        # BUG: rank-dependent barrier
            lang.barrier_all(axis)
        out_ref[:] = x_ref[:]

    return (
        _spec(
            kernel, "fixture_barrier_mismatch",
            out_shapes=[((8, 128), _F32)],
            collective_id=43,
        ),
        lambda n: [((8, 128), _F32)],
    )


def undrained_dma(axis="x"):
    """Puts whose local completion is never drained (missing ``quiet``/
    ``wait_send``) — the kernel can exit with transfers in flight.
    SL007."""

    def kernel(x_ref, out_ref, send_sem, recv_sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        m = x_ref.shape[0]
        from jax.experimental import pallas as pl

        out_ref[pl.ds(me * m, m)] = x_ref[:]
        lang.barrier_all(axis)
        handles = []
        for i in range(n - 1):
            peer = (me + 1 + i) % n
            handles.append(lang.putmem_signal_nbi_block(
                out_ref.at[pl.ds(me * m, m)], x_ref,
                send_sem.at[i], recv_sem.at[i], peer,
            ))
        for h in handles:
            h.wait_recv()
        # BUG: no lang.quiet(*handles) — send semaphores never drained

    return (
        _spec(
            kernel, "fixture_undrained_dma",
            out_shapes=[((8 * 8, 128), _F32)],
            scratch=_sems((8,), (8,)),
            collective_id=44,
        ),
        lambda n: [((8, 128), _F32)],
    )


def vmem_overcommit(axis="x"):
    """VMEM working set exceeding the launch's declared budget. SL006."""

    def kernel(x_ref, out_ref, big_ref, sem):
        out_ref[:] = x_ref[:]
        lang.signal_op(sem, 1, site="fixture")
        lang.signal_wait_until(sem, 1)

    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp

    return (
        _spec(
            kernel, "fixture_vmem_overcommit",
            out_shapes=[((8, 128), _F32)],
            scratch=[pltpu.VMEM((64, 128), jnp.float32)] + _sems(None),
            collective_id=None,
            vmem_limit_bytes=16 * 1024,   # 16 KiB budget vs ~40 KiB set
        ),
        lambda n: [((8, 128), _F32)],
    )


def skipped_chunk(axis="x"):
    """An AG ring one hop SHORT (``range(n - 2)`` instead of
    ``n - 1``): every semaphore balances — each step is a matched
    start/wait pair — but each rank terminates missing exactly one
    source's chunk. Undetectable by the protocol rules by construction;
    SL008 against the declared gather contract."""
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract

    def kernel(x_ref, out_ref, send_sem, recv_sem):
        import jax
        from jax.experimental import pallas as pl

        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        m = x_ref.shape[0]

        out_ref[pl.ds(me * m, m)] = x_ref[:]
        lang.barrier_all(axis)
        for s in range(n - 2):             # BUG: one ring hop short
            src = jax.lax.rem(me + n - s, n) if s > 0 else me
            dma = lang.remote_copy(
                out_ref.at[pl.ds(src * m, m)],
                out_ref.at[pl.ds(src * m, m)],
                send_sem.at[s], recv_sem.at[s], (me + 1) % n,
            )
            dma.start()
            dma.wait()

    return (
        _spec(
            kernel, "fixture_skipped_chunk",
            out_shapes=[((8 * 8, 128), _F32)],
            scratch=_sems((8,), (8,)),
            collective_id=46,
        ),
        lambda n: [((8, 128), _F32)],
        DeliveryContract(kind="gather", dst="out_ref"),
    )


def dup_chunk(axis="x"):
    """A correct LL-push allgather followed by rank 0 RE-delivering its
    shard into slot 1 on every peer — the duplicate overwrites source
    1's chunk. Every semaphore balances (the dup arrivals are waited),
    every landing is barrier-ordered; the data is still wrong: source 0
    held twice, source 1 lost. SL008."""
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract

    def kernel(x_ref, out_ref, send_sem, recv_sem, dsend_sem, drecv_sem):
        from jax.experimental import pallas as pl

        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        m = x_ref.shape[0]

        out_ref[pl.ds(me * m, m)] = x_ref[:]
        lang.barrier_all(axis)
        handles = []
        for i in range(n - 1):
            peer = (me + 1 + i) % n
            handles.append(lang.putmem_signal_nbi_block(
                out_ref.at[pl.ds(me * m, m)], x_ref,
                send_sem.at[i], recv_sem.at[i], peer,
            ))
        lang.quiet(*handles)
        for h in handles:
            h.wait_recv()
        lang.barrier_all(axis)
        if me == 0:
            # BUG: shard 0 delivered AGAIN, into slot 1, on every peer
            dups = [
                lang.putmem_signal_nbi_block(
                    out_ref.at[pl.ds(1 * m, m)], x_ref,
                    dsend_sem.at[i], drecv_sem.at[i], i + 1,
                )
                for i in range(n - 1)
            ]
            lang.quiet(*dups)
        else:
            lang.signal_wait_until(drecv_sem.at[me - 1], 1)

    return (
        _spec(
            kernel, "fixture_dup_chunk",
            out_shapes=[((8 * 8, 128), _F32)],
            scratch=_sems((8,), (8,), (8,), (8,)),
            collective_id=47,
        ),
        lambda n: [((8, 128), _F32)],
        DeliveryContract(kind="gather", dst="out_ref"),
    )


def scale_on_payload_sem(axis="x"):
    """A quantized one-hop wire whose scale rail is signaled on the
    PAYLOAD's recv semaphore. The credits balance (the receiver waits
    twice), but credits count — they don't tag: the payload wait can be
    released by the scale arrival while the 1-byte slab is still in
    flight. SL009."""

    def kernel(x_ref, xq_ref, xs_ref, out_ref, outq_ref, outs_ref,
               send_sem, recv_sem, s_send_sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)

        lang.barrier_all(axis)
        peer = (me + 1) % n
        dq = lang.remote_copy(
            xq_ref, outq_ref, send_sem.at[0], recv_sem.at[0], peer
        )
        # BUG: the scale rail rides the payload's recv semaphore
        dsc = lang.remote_copy(
            xs_ref, outs_ref, s_send_sem.at[0], recv_sem.at[0], peer
        )
        dq.start()
        dsc.start()
        dq.wait()
        dsc.wait_send()
        lang.signal_wait_until(recv_sem.at[0], 1)   # the second credit
        wirelib.dequant_rows_into(out_ref, outq_ref, outs_ref)

    return (
        _spec(
            kernel, "fixture_scale_on_payload_sem",
            out_shapes=[((8, 2048), _F32), ((8, 2048), _f8()),
                        ((8, 128), _F32)],
            scratch=_sems((1,), (1,), (1,)),
            collective_id=48,
        ),
        lambda n: [((8, 2048), _F32), ((8, 2048), _f8()),
                   ((8, 128), _F32)],
        None,
    )


def stale_scale(axis="x"):
    """Two correctly-railed quantized hops into a double-buffered
    workspace; the receiver then dequantizes slot 0's payload with slot
    1's scale plane. Protocol-clean, rails paired, values silently
    wrong. SL010."""

    def kernel(x_ref, out_ref, qbuf_ref, sbuf_ref, recvq_ref, recvs_ref,
               send_sem, recv_sem, s_send_sem, s_recv_sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)

        lang.barrier_all(axis)
        peer = (me + 1) % n
        for slot in range(2):
            wirelib.quant_rows_into(
                qbuf_ref.at[slot], sbuf_ref.at[slot], x_ref, "fp8"
            )
            dq = lang.remote_copy(
                qbuf_ref.at[slot], recvq_ref.at[slot],
                send_sem.at[slot], recv_sem.at[slot], peer,
            )
            dsc = lang.remote_copy(
                sbuf_ref.at[slot], recvs_ref.at[slot],
                s_send_sem.at[slot], s_recv_sem.at[slot], peer,
            )
            dq.start()
            dsc.start()
            dq.wait()
            dsc.wait()
        # BUG: slot 0's bytes, slot 1's scales
        wirelib.dequant_rows_into(
            out_ref, recvq_ref.at[0], recvs_ref.at[1]
        )

    from jax.experimental.pallas import tpu as pltpu
    import jax.numpy as jnp
    import ml_dtypes

    f8 = jnp.dtype(ml_dtypes.float8_e4m3fn)
    return (
        _spec(
            kernel, "fixture_stale_scale",
            out_shapes=[((8, 2048), _F32)],
            scratch=[
                pltpu.VMEM((2, 8, 2048), f8),            # qbuf
                pltpu.VMEM((2, 8, 128), jnp.float32),    # sbuf
                pltpu.VMEM((2, 8, 2048), f8),            # recvq
                pltpu.VMEM((2, 8, 128), jnp.float32),    # recvs
            ] + _sems((2,), (2,), (2,), (2,)),
            collective_id=49,
        ),
        lambda n: [((8, 2048), _F32)],
        None,
    )


def scale_fold_omitted(axis="x"):
    """An int8→MXU consumer whose epilogue NEVER folds the scale: the
    rails are correctly paired (payload + scale plane on their own
    semaphores — the SL009 structural legs stay silent), every
    semaphore balances, but the arriving s8 slab is fed to the MXU and
    stored without its chunk-scale rescale. The values are silently off
    by the quantization scale. SL009 (scale-fold omitted), with rank +
    site diagnostics."""

    def kernel(xq_ref, xs_ref, out_ref, outq_ref, outs_ref,
               send_sem, recv_sem, s_send_sem, s_recv_sem):
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)

        lang.barrier_all(axis)
        peer = (me + 1) % n
        dq = lang.remote_copy(
            xq_ref, outq_ref, send_sem.at[0], recv_sem.at[0], peer
        )
        dsc = lang.remote_copy(
            xs_ref, outs_ref, s_send_sem.at[0], s_recv_sem.at[0], peer
        )
        dq.start()
        dsc.start()
        dq.wait()
        dsc.wait()
        # BUG: the s8×s8 pipeline consumes the payload with NO scale
        # plane — the epilogue stores the unrescaled accumulator
        wirelib.epilogue_consume(outq_ref, None, out_ref)

    return (
        _spec(
            kernel, "fixture_scale_fold_omitted",
            out_shapes=[((8, 128), _F32), ((8, 2048), np.dtype(np.int8)),
                        ((8, 128), _F32)],
            scratch=_sems((1,), (1,), (1,), (1,)),
            collective_id=50,
        ),
        lambda n: [((8, 2048), np.dtype(np.int8)), ((8, 128), _F32)],
        None,
    )


def serialized_ring(axis="x"):
    """A gather ring that runs ``n`` hops instead of ``n-1`` — every
    chunk is still delivered exactly once everywhere (the extra lap
    re-delivers each rank's OWN shard on top of its already-correct
    local copy), every semaphore balances, SL008 is clean... but the
    deepest delivery chain is now ``n`` sequential hops. The hop
    counters the replay tracks expose the detour and the perf model
    prices it: SL011."""
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract

    def kernel(x_ref, out_ref, send_sem, recv_sem):
        import jax
        from jax.experimental import pallas as pl

        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        m = x_ref.shape[0]

        out_ref[pl.ds(me * m, m)] = x_ref[:]
        lang.barrier_all(axis)
        for s in range(n):                 # BUG: one lap too many
            src = jax.lax.rem(me + n - s, n) if s > 0 else me
            dma = lang.remote_copy(
                out_ref.at[pl.ds(src * m, m)],
                out_ref.at[pl.ds(src * m, m)],
                send_sem.at[s], recv_sem.at[s], (me + 1) % n,
            )
            dma.start()
            dma.wait()

    return (
        _spec(
            kernel, "fixture_serialized_ring",
            out_shapes=[((8 * 8, 128), _F32)],
            scratch=_sems((8,), (8,)),
            collective_id=51,
        ),
        lambda n: [((8, 128), _F32)],
        DeliveryContract(kind="gather", dst="out_ref"),
    )


_SCHED_TOKENS = None


def _schedule_token():
    global _SCHED_TOKENS
    if _SCHED_TOKENS is None:
        import itertools

        _SCHED_TOKENS = itertools.count()
    return ("fixture-schedule", next(_SCHED_TOKENS))


def schedule_skipped_chunk(axis="x"):
    """A schedule-search MUTATION executed by the REAL ring kernel (not
    a hand-written replica): ``chunk_order='skip_last'`` threaded
    through the production allgather builder drops the final hop's
    start+wait+consume — every remaining semaphore balances, the rails
    stay paired, but each rank terminates one source short. SL008 is
    the only rule that can see it, which is exactly why the schedule
    enumerator's legality gate is shmemlint."""
    import jax.numpy as jnp

    from triton_distributed_tpu.analysis import lint
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.allgather import _build_all_gather
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.runtime import AllGatherMethod
    from triton_distributed_tpu.tune.schedule import RingSchedule

    n = 8
    _build_all_gather(
        lint.lint_mesh(n), axis, AllGatherMethod.RING_1D, (8 * n, 2048),
        jnp.dtype(jnp.float32), 53, _schedule_token(), wire="int8",
        schedule=RingSchedule(chunk_order="skip_last"),
    )
    spec = captured_launch("ag_ring_1d_int8w")
    return (
        spec,
        lambda _n: [((8, 2048), _F32), ((8, 2048), np.dtype(np.int8)),
                    ((8, 128), _F32)],
        DeliveryContract(kind="gather", dst="out_ref"),
    )


def schedule_scale_on_payload(axis="x"):
    """The other mutation family: ``scale_rail='payload'`` threaded
    through the production streaming-RS builder signals the quantized
    wire's scale arrivals on the PAYLOAD's recv semaphore. Credits
    balance (reduce_ring still waits the right totals) — only the SL009
    rail-pairing replay can reject it."""
    import jax.numpy as jnp

    from triton_distributed_tpu.analysis import lint
    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_rs_stream_w,
    )
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import RingSchedule

    n = 8
    _build_rs_stream_w(
        lint.lint_mesh(n), axis, 8 * n, 2048, jnp.dtype(jnp.float32),
        False, 54, _schedule_token(), "int8",
        schedule=RingSchedule(scale_rail="payload"),
    )
    spec = captured_launch("rs_ring_stream_int8w")
    return (
        spec,
        lambda _n: [((8 * n, 2048), _F32)],
        DeliveryContract(kind="reduce", dst="out_hbm"),
    )


def kv_ship_skipped_page(axis="x"):
    """The KV page ship one page SHORT: the sender's loop walks
    ``range(pages - 1)``, so the last staged page never leaves the
    prefill pool — every semaphore balances (each started rail pair is
    waited), the rails stay paired, but the decode pool terminates with
    that page's slot unwritten and its source's delivered element count
    short. SL008 against the pairwise permute contract (the bug a
    protocol pass cannot see: an admission gate reading kv_lens would
    happily walk the hole)."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.kv_ship import (
        KV_SHIP_GEOM,
        _kv_ship_kernel,
    )
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.kernels.kv_ship import build_lint_kernel
    from triton_distributed_tpu.analysis.lint import lint_mesh

    g = KV_SHIP_GEOM
    n = 8
    build_lint_kernel(lint_mesh(n, axis), n,
                      token=("fixture_kv_ship_skip",))
    real = captured_launch("kv_ship_pages")
    import functools as _ft

    short = _ft.partial(
        _kv_ship_kernel, n, axis, (axis,),
        g["pages"] - 1,                      # BUG: one page never ships
        g["rows"], 1, "paired",
    )

    def kernel(dstpg_ref, src_q, src_s, dst_q, dst_s,
               send_sem, recv_sem, s_send_sem, s_recv_sem):
        dstpg_ref[...] = np.asarray(
            list(reversed(range(g["pages"]))), np.int32
        )
        short(dstpg_ref, src_q, src_s, dst_q, dst_s,
              send_sem, recv_sem, s_send_sem, s_recv_sem)

    def in_shapes(n):
        del n
        rows = g["pages"] * g["rows"]
        return [
            ((g["pages"],), np.dtype(np.int32)),
            ((rows, g["cols"]), np.dtype(np.int8)),
            ((rows, 128), _F32),
        ]

    return (
        replace(real, kernel=kernel, name="fixture_kv_ship_skipped_page"),
        in_shapes,
        DeliveryContract(
            kind="permute", dst="dst_q",
            payload_per_src=lambda n: g["pages"] * g["rows"] * g["cols"],
            src_only=lambda rank, n: {(rank - n // 2) % n},
        ),
    )


def kv_ship_unpaired_scale(axis="x"):
    """A KV page ship whose SCALE RAIL was dropped: the int8 page
    payloads fly and land at their assigned slots (the permute contract
    is satisfied — every page exactly once), but no per-row scale plane
    accompanies them and the landing is installed without a scale fold.
    The decode pool now holds int8 bytes whose scales are whatever the
    pool's scale plane last held — silently wrong logits. SL009 (no
    paired scale-plane RDMA before the next wait, and the
    scale-fold-omitted consume)."""

    from triton_distributed_tpu.kernels.kv_ship import KV_SHIP_GEOM

    g = KV_SHIP_GEOM
    pages, rows = g["pages"], g["rows"]

    def kernel(dstpg_ref, src_q, src_s, dst_q, dst_s,
               send_sem, recv_sem, s_send_sem, s_recv_sem):
        from jax.experimental import pallas as pl

        dstpg_ref[...] = np.asarray(
            list(reversed(range(pages))), np.int32
        )
        me = lang.my_pe(axis)
        n = lang.n_pes(axis)
        to = (me + n // 2) % n

        lang.barrier_all(axis)
        handles = []
        for i in range(pages):
            slot = dstpg_ref[i]
            dq = lang.remote_copy(
                src_q.at[pl.ds(i * rows, rows)],
                dst_q.at[pl.ds(slot * rows, rows)],
                send_sem.at[i], recv_sem.at[i], to,
            )
            # BUG: the scale plane never ships — payload rail only
            dq.start()
            handles.append(dq)
        lang.quiet(*handles)
        for dq in handles:
            dq.wait_recv()
        for i in range(pages):
            slot = dstpg_ref[i]
            # BUG: installed with NO scale fold (s=None)
            wirelib.epilogue_consume(
                dst_q.at[pl.ds(slot * rows, rows)], None, None
            )

    total = pages * rows
    return (
        _spec(
            kernel, "fixture_kv_ship_unpaired_scale",
            out_shapes=[((total, g["cols"]), np.dtype(np.int8)),
                        ((total, 128), _F32)],
            scratch=_sems((pages,), (pages,), (pages,), (pages,)),
            collective_id=52,
        ),
        lambda n: [
            ((pages,), np.dtype(np.int32)),
            ((total, g["cols"]), np.dtype(np.int8)),
            ((total, 128), _F32),
        ],
        None,
    )


def grid_ragged_overwide_block(axis="x"):
    """GRID-schedule MUTATION through the production ragged builder:
    ``block_q=32`` against the gate geometry's 16-token parking cap.
    The packed buffer reserves exactly ``min(block_q, GRID_BLOCK_CAP)``
    tokens of tail slack, so a 32-wide query block's q-window reads and
    out-DMA writes overrun the buffer — the evaluator's OOB events and
    the zero-slack local contract both land on SL008. Every semaphore
    balances and the page walk is protocol-clean: only the dataflow
    pass can reject this candidate, which is why it sits in the
    schedule enumerator's mutation set."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        build_grid_lint_kernel,
    )
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import GridSchedule

    g = build_grid_lint_kernel(
        token=_schedule_token(), schedule=GridSchedule(block_q=32)
    )
    real = captured_launch("ragged_paged_attention_q8")

    def kernel(*refs):
        table, kv_lens, q_lens, q_starts, topo = refs[:5]
        table[...] = np.arange(
            g["r"] * g["pps"], dtype=np.int32
        ).reshape(g["r"], g["pps"])
        kv_lens[...] = np.asarray(g["kv_lens"], np.int32)
        q_lens[...] = np.asarray(g["q_lens"], np.int32)
        q_starts[...] = np.asarray(g["q_starts"], np.int32)
        topo[...] = np.asarray(g["topo"], np.int32)
        real.kernel(*refs)

    def in_shapes(n):
        del n
        pool = (g["npages"], g["hkv"], g["page"], g["d"])
        return [
            ((g["r"], g["pps"]), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"], 2 + 2 * g["topo_w"]), np.dtype(np.int32)),
            ((g["hkv"], g["t"] * g["g"], g["d"]), _F32),
            (pool, np.dtype(np.int8)),
            (pool, np.dtype(np.int8)),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
        ]

    return (
        replace(real, kernel=kernel,
                name="fixture_grid_ragged_overwide_block"),
        in_shapes,
        DeliveryContract(kind="local", dst=10),
    )


def grid_kv_ship_dropped_scale(axis="x"):
    """GRID-schedule MUTATION through the production kv_ship builder:
    a 2-page coalesced tick whose scale rail is DROPPED
    (``coalesce=2, rail='drop'``). The int8 page payloads fly and land
    coalesced (the permute is still exact), but no per-row scale plane
    ships and the landing installs with no scale fold — SL009, the
    same silent-wrong-logits bug as :func:`kv_ship_unpaired_scale`,
    produced by the real builder under a mutated schedule instead of a
    hand-written replica."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.kv_ship import (
        KV_SHIP_GEOM,
        build_lint_kernel,
        coalesced_landing_table,
    )
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import GridSchedule

    g = KV_SHIP_GEOM
    n = 8
    build_lint_kernel(
        lint_mesh(n, axis), n, token=_schedule_token(),
        schedule=GridSchedule(coalesce=2, rail="drop"),
    )
    real = captured_launch("kv_ship_pages")
    table = np.asarray(coalesced_landing_table(g["pages"], 2), np.int32)

    def kernel(dstpg_ref, *refs):
        dstpg_ref[...] = table
        real.kernel(dstpg_ref, *refs)

    def in_shapes(n):
        del n
        rows = g["pages"] * g["rows"]
        return [
            ((g["pages"],), np.dtype(np.int32)),
            ((rows, g["cols"]), np.dtype(np.int8)),
            ((rows, 128), _F32),
        ]

    # contract=None (the kv_ship_unpaired_scale precedent): the rail
    # pairing is the bug under test, so the pin is EXACTLY ["SL009"] —
    # the permute contract would add its own SL008 for the missing
    # scale-plane deliveries and blur the rule pin
    return (
        replace(real, kernel=kernel,
                name="fixture_grid_kv_ship_dropped_scale"),
        in_shapes,
        None,
    )


def grid_gemm_rs_shared_rail(axis="x"):
    """GRID-schedule MUTATION through the production fused GEMM-RS
    builder on the int8-MXU wire: ``rail='shared'`` signals the scale
    plane's arrival on the PAYLOAD's recv semaphore. Credits balance —
    the reduce ring waits the right totals — but a rank can fold a
    stale scale against a fresh payload; only the SL009 rail-pairing
    replay rejects it."""
    import jax.numpy as jnp

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.gemm_rs import _build_fused
    from triton_distributed_tpu.lang import wire as wirelib
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import GridSchedule

    n = 8
    _build_fused(
        lint_mesh(n, axis), axis, (), (16 * n, 128 * n), (128 * n, 64),
        jnp.dtype(jnp.float32), jnp.dtype(jnp.float32), 6,
        _schedule_token(), wire="int8-mxu",
        schedule=GridSchedule(rail="shared"),
    )
    spec = captured_launch("gemm_rs_fused_int8mxw")

    def in_shapes(n):
        return [((16 * n, 128), np.dtype(np.int8)),
                ((n, wirelib.SCALE_LANES), _F32),
                ((128, 64), np.dtype(np.int8)),
                ((1, 64), _F32)]

    return (
        spec,
        in_shapes,
        DeliveryContract(kind="reduce", dst="out_hbm"),
    )


# ------------------------------------------------ Mosaic-compat fixtures
#
# These are consumed by analysis.mosaic_compat.preflight_spec (real jax
# tracing, not the abstract evaluator): each kernel contains exactly one
# construct this toolchain's Mosaic backend rejects.

def f8_inkernel_cast(axis="x"):
    """arith.extf f8E4M3FN → f32 inside the kernel ('Only 16-bit to
    32-bit extensions supported'). MC001."""

    def kernel(xq_ref, out_ref):
        import jax.numpy as jnp

        out_ref[...] = xq_ref[...].astype(jnp.float32) * 2.0

    return (
        _spec(kernel, "fixture_f8_cast", out_shapes=[((8, 128), _F32)]),
        lambda n: [((8, 128), _f8())],
    )


def scalar_shape_cast(axis="x"):
    """A loaded (1, 1) float block collapsed to a scalar — the
    vector<1x1> → scalar shape_cast Mosaic rejects. MC002."""

    def kernel(x_ref, out_ref):
        import jax.numpy as jnp

        blk = x_ref[...]
        s = jnp.reshape(blk[0:1, 0:1], ())    # BUG: scalar shape_cast
        out_ref[...] = blk * s

    return (
        _spec(kernel, "fixture_scalar_cast", out_shapes=[((8, 128), _F32)]),
        lambda n: [((8, 128), _F32)],
    )


def subbyte_broadcast(axis="x"):
    """An int4 vector broadcast — no sub-byte broadcast layout in this
    Mosaic backend. MC003."""

    def kernel(x_ref, out_ref):
        import jax.numpy as jnp

        nib = jnp.broadcast_to(
            jnp.zeros((1, 1), jnp.int4), x_ref.shape
        )
        out_ref[...] = x_ref[...] + nib.astype(jnp.float32)

    return (
        _spec(kernel, "fixture_subbyte", out_shapes=[((8, 128), _F32)]),
        lambda n: [((8, 128), _F32)],
    )


def duplicate_collective_id(axis="x"):
    """TWO kernel families at DIFFERENT sites sharing one
    collective_id — their barrier rendezvous collide when both are
    launched in a program (the ad-hoc id-rail hazard ADVICE.md flagged
    on gemm_rs's +96 range). The cross-family SL005 check catches it;
    returns both (spec, in_shapes) pairs."""

    def mk(name, site):
        def kernel(x_ref, out_ref):
            lang.barrier_all(axis)
            out_ref[:] = x_ref[:]

        return _spec(
            kernel, name,
            out_shapes=[((8, 128), _F32)],
            collective_id=45,              # BUG: shared across sites
        )

    return (
        (mk("fixture_dup_cid_a", "site_a"), lambda n: [((8, 128), _F32)]),
        (mk("fixture_dup_cid_b", "site_b"), lambda n: [((8, 128), _F32)]),
    )


def ragged_hole(axis="x"):
    """The REAL ragged paged-attention kernel with mis-addressed row
    packing: both rows' ``q_starts`` park at 0, so the second row's
    out-DMA overwrites the first row's span and rows [8:16) of the
    packed output are never written — every semaphore balances, the
    page walk is protocol-clean, but the `local` delivery contract
    terminates with a hole. SL008 (kind='local')."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_GEOM,
        build_lint_kernel,
    )
    from triton_distributed_tpu.lang.launch import captured_launch

    g = LINT_GEOM
    build_lint_kernel(token=("fixture_ragged_hole",))
    real = captured_launch("ragged_paged_attention_q8")

    def kernel(*refs):
        table, kv_lens, q_lens, q_starts = refs[:4]
        table[...] = np.arange(
            g["r"] * g["pps"], dtype=np.int32
        ).reshape(g["r"], g["pps"])
        kv_lens[...] = np.asarray([12, 8], np.int32)
        q_lens[...] = np.asarray([8, 8], np.int32)
        q_starts[...] = np.asarray([0, 0], np.int32)   # BUG: both park at 0
        real.kernel(*refs)

    def in_shapes(n):
        del n
        pool = (g["npages"], g["hkv"], g["page"], g["d"])
        return [
            ((g["r"], g["pps"]), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"], 2 + 2 * g["topo_w"]), np.dtype(np.int32)),
            ((g["hkv"], g["t"] * g["g"], g["d"]), _F32),
            (pool, np.dtype(np.int8)),
            (pool, np.dtype(np.int8)),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
        ]

    return (
        replace(real, kernel=kernel, name="fixture_ragged_hole"),
        in_shapes,
        DeliveryContract(kind="local", dst=10),
    )


def ragged_tree_sibling(axis="x"):
    """The REAL ragged kernel fed a MALFORMED tree descriptor: row 1's
    node at q position 2 carries an ancestry bitmask that includes its
    SIBLING branch (bit 1) — the bitmasks are not closed under the
    packed parent pointers, so that node's scores admit keys from a
    path it does not descend from and the verify walk samples from a
    contaminated distribution. Coverage is perfect (every out element
    is the rank's own write), so only the contract's masked-coverage
    facet can reject it. SL008 (kind='local', value-level)."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        LINT_GEOM,
        TOPO_TREE,
        build_lint_kernel,
        causal_topologies,
    )
    from triton_distributed_tpu.lang.launch import captured_launch

    g = LINT_GEOM
    w = g["topo_w"]
    build_lint_kernel(token=("fixture_ragged_tree_sibling",))
    real = captured_launch("ragged_paged_attention_q8")

    topo = causal_topologies(g["r"], w)
    # row 1: frontier + 7 nodes filling the packed span; q1 and q2 are
    # SIBLING branches off the frontier, q3..q7 chain off q2. A
    # well-formed q2 mask is {0, 2}; this one smuggles in bit 1 (its
    # sibling q1) — and every descendant inherits the leak, but the
    # closure breaks exactly at q2, the graft point.
    topo[1, 0] = TOPO_TREE
    topo[1, 1] = 8
    anc = [1, 3, 7, 15, 31, 63, 127, 255]   # anc[2] holds bit 1: BUG
    par = [-1, 0, 0, 2, 3, 4, 5, 6]
    topo[1, 2:2 + 8] = anc
    topo[1, 2 + w:2 + w + 8] = par

    def in_shapes(n):
        del n
        pool = (g["npages"], g["hkv"], g["page"], g["d"])
        return [
            ((g["r"], g["pps"]), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"],), np.dtype(np.int32)),
            ((g["r"], 2 + 2 * w), np.dtype(np.int32)),
            ((g["hkv"], g["t"] * g["g"], g["d"]), _F32),
            (pool, np.dtype(np.int8)),
            (pool, np.dtype(np.int8)),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
            ((g["npages"], g["hkv"], 1, g["page"]), _F32),
        ]

    init = {
        0: np.arange(g["r"] * g["pps"], dtype=np.int32).reshape(
            g["r"], g["pps"]),
        1: np.asarray([12, 8], np.int32),
        2: np.asarray([8, 8], np.int32),
        3: np.asarray([0, 8], np.int32),
        4: topo,
    }
    return (
        replace(real, kernel=real.kernel,
                name="fixture_ragged_tree_sibling"),
        in_shapes,
        DeliveryContract(
            kind="local", dst=10,
            topo={"ref": 4, "kv_lens": 1, "q_lens": 2, "width": w},
        ),
        init,
    )


def lane_reshape(axis="x"):
    """An in-kernel reshape that CHANGES the lane (minor) dimension —
    (8, 256) → (16, 128) — the vector shape_cast this Mosaic cannot
    re-lay (the naive GQA-row flatten the ragged kernel's head-major
    packing exists to avoid). MC005."""

    def kernel(x_ref, out_ref):
        import jax.numpy as jnp

        blk = x_ref[...]                       # (8, 256)
        out_ref[...] = jnp.reshape(blk, (16, 128))   # BUG: lane change

    return (
        _spec(kernel, "fixture_lane_reshape",
              out_shapes=[((16, 128), _F32)]),
        lambda n: [((8, 256), _F32)],
    )


def dynamic_gather(axis="x"):
    """An in-kernel gather with TRACED indices — the ``anc[par]``
    index chase a naive tree-topology mask build would produce
    (``jnp.take`` over a runtime int vector). This Mosaic has no
    dynamic vector-indexed gather lowering; the ragged kernel's
    static ancestor-bitmask unroll exists to avoid it. MC006."""

    def kernel(idx_ref, x_ref, out_ref):
        import jax.numpy as jnp

        idx = idx_ref[...]                     # (8,) traced int32
        out_ref[...] = jnp.take(x_ref[...], idx, axis=0)   # BUG

    return (
        _spec(kernel, "fixture_dynamic_gather",
              out_shapes=[((8, 128), _F32)]),
        lambda n: [((8,), np.dtype(np.int32)), ((8, 128), _F32)],
    )


def sublane_dynamic_slice(axis="x"):
    """An in-kernel ``dynamic_slice`` whose SUBLANE (second-minor)
    start index is a traced runtime value — the jaxpr signature the
    nightly slow run surfaced (a KV-window slice ``x[start:start+8]``
    with a per-step ``start``). This Mosaic only folds constant
    sublane offsets; traced LANE offsets are fine. MC007."""

    def kernel(idx_ref, x_ref, out_ref):
        import jax.lax as lax

        i = idx_ref[0]                         # traced scalar int32
        out_ref[...] = lax.dynamic_slice(
            x_ref[...], (i, 0), (8, 128))      # BUG: traced sublane start

    return (
        _spec(kernel, "fixture_sublane_dynamic_slice",
              out_shapes=[((8, 128), _F32)]),
        lambda n: [((1,), np.dtype(np.int32)), ((16, 128), _F32)],
    )


def unproven_tile_slice(axis="x"):
    """A DMA window sliced at a TRACED second-minor offset Mosaic
    cannot prove tile-aligned — the ragged kernel's pre-repair
    ``q_hbm.at[:, pl.ds(q_starts[r] * g, rows)]`` with ``g == 1``
    ('Failed to prove that a tile index in dimension 1 is divisible by
    the tiling (8)'). ``pl.multiple_of`` is the repair. MC008."""

    def kernel(idx_ref, x_ref, out_ref, sem):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        start = idx_ref[0] * 1                 # BUG: no divisibility proof
        cp = pltpu.make_async_copy(
            x_ref.at[pl.ds(start, 8)], out_ref, sem.at[0])
        cp.start()
        cp.wait()

    return (
        _spec(kernel, "fixture_unproven_tile_slice",
              out_shapes=[((8, 128), _F32)], scratch=_sems((1,))),
        lambda n: [((1,), np.dtype(np.int32)), ((32, 128), _F32)],
    )


def thin_lane_dma(axis="x"):
    """A (rows, 1) column DMA'd into a sliced window — the ragged
    kernel's pre-repair ``(hkv, rows, 1)`` lse staging block ('Slice
    shape along dimension 2 must be aligned to tiling (128), but is
    1'). The offset carries its proof; only the lane extent is wrong.
    MC009."""

    def kernel(idx_ref, x_ref, out_ref, sem):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        start = pl.multiple_of(idx_ref[0] * 8, 8)
        cp = pltpu.make_async_copy(
            x_ref, out_ref.at[pl.ds(start, 8)], sem.at[0])  # BUG: (·, 1)
        cp.start()
        cp.wait()

    return (
        _spec(kernel, "fixture_thin_lane_dma",
              out_shapes=[((32, 1), _F32)], scratch=_sems((1,))),
        lambda n: [((1,), np.dtype(np.int32)), ((8, 1), _F32)],
    )


def i1_vector_select(axis="x"):
    """A select whose OPERANDS are mask vectors — the ragged kernel's
    pre-repair ``jnp.where(kind == TOPO_TREE, tree_valid, valid)``
    ('failed to legalize operation arith.select' on vector<i1>).
    MC010."""

    def kernel(kind_ref, x_ref, out_ref):
        import jax.numpy as jnp

        x = x_ref[...]
        a, b = x > 0.0, x > 1.0
        valid = jnp.where(kind_ref[0] == 1, a, b)   # BUG: i1 operands
        out_ref[...] = jnp.where(valid, x, 0.0)

    return (
        _spec(kernel, "fixture_i1_vector_select",
              out_shapes=[((8, 128), _F32)]),
        lambda n: [((1,), np.dtype(np.int32)), ((8, 128), _F32)],
    )


def cp_ring_skipped_block(axis="x"):
    """The context-parallel KV rotation ring one BLOCK short: the
    schedule mutation ``chunk_order='skip_last'`` threaded through the
    production cp.ring_attention builder drops the final hop's
    start+wait+consume, so each rank's rotated-KV workspace terminates
    one source block short — an attention output silently missing one
    rank's keys/values. Semaphores balance, rails stay paired; only the
    SL008 delivery replay against the gather contract can reject it
    (``own_absent_ok``: the harness never copies the local block, ring
    attention consumes it straight from the operand)."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.cp_ring import build_kv_rotate_lint
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import RingSchedule

    n = 8
    build_kv_rotate_lint(
        lint_mesh(n, axis), n, token=_schedule_token(),
        schedule=RingSchedule(chunk_order="skip_last"),
    )
    spec = captured_launch("cp_ring_kv_rotate")
    return (
        replace(spec, name="fixture_cp_ring_skipped_block"),
        lambda _n: [((8, 128), _F32)],
        DeliveryContract(kind="gather", dst="ag_ref", own_absent_ok=True),
    )


def grad_ring_unpaired_scale(axis="x"):
    """The gradient ring's quantized wire with the scale rail riding the
    PAYLOAD semaphore: ``scale_rail='payload'`` threaded through the
    production grad_ring.stream_int8w builder signals scale arrivals on
    the payload's recv semaphore. Credits balance (reduce_ring waits the
    right totals) — a gradient can dequantize against a scale from the
    WRONG hop; only the SL009 rail-pairing replay can reject it."""
    from dataclasses import replace

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.cp_ring import build_grad_ring_lint
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.tune.schedule import RingSchedule

    n = 8
    build_grad_ring_lint(
        lint_mesh(n, axis), n, token=_schedule_token(),
        schedule=RingSchedule(scale_rail="payload"),
    )
    spec = captured_launch("grad_ring_stream_int8w")
    return (
        replace(spec, name="fixture_grad_ring_unpaired_scale"),
        lambda _n: [((8 * _n, 2048), _F32)],
        DeliveryContract(kind="reduce", dst="out_hbm"),
    )


def contract_declares_gather_actually_reduces(axis="x"):
    """A seeded SL012 true-positive for contract inference: the REAL
    reduce-scatter ring kernel registered with a hand-written contract
    that declares ``kind='gather'``. Every semaphore balances and the
    kernel genuinely delivers — but it FOLDS (every output element sums
    a contribution from all ranks) while the declaration promises
    single-sourced chunks, so plain SL008 would check the wrong shape
    and judge a correct reduction 'incomplete' (or a broken gather
    complete). Only the twin diff (``jax.lax.psum_scatter`` delivers
    class 'fold', the declared kind is class 'single') can name the
    declaration itself as the bug. Returns (spec, in_shapes, declared
    contract, degrades_to path)."""
    from dataclasses import replace

    import jax.numpy as jnp

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.reduce_scatter import (
        _build_reduce_scatter,
    )
    from triton_distributed_tpu.lang.launch import captured_launch

    n = 8
    _build_reduce_scatter(
        lint_mesh(n, axis), axis, (8 * n, 128), jnp.dtype(jnp.float32),
        False, 55, _schedule_token(),
    )
    spec = captured_launch("rs_ring")
    return (
        replace(spec, name="fixture_contract_gather_actually_reduces"),
        lambda _n: [((8 * _n, 128), _F32)],
        DeliveryContract(kind="gather", dst="out_ref"),
        "jax.lax.psum_scatter",
    )


def contract_overdeclared_payload(axis="x"):
    """A seeded SL012 true-positive for contract inference: the REAL
    1-D all-gather ring with a declared ``payload_per_src`` of TWICE
    what the twin (and the kernel) actually deliver per source. The
    kind and dst are right, so the drift is purely quantitative — a
    declaration like this would make SL008 flag every correct run as
    half-delivered (and, declared the other way, bless a half-delivered
    one). The inference pass measures the modal per-source landing off
    the replay's provenance nibbles and names the over-declaration.
    Returns (spec, in_shapes, declared contract, degrades_to path)."""
    from dataclasses import replace

    import jax.numpy as jnp

    from triton_distributed_tpu.analysis.dataflow import DeliveryContract
    from triton_distributed_tpu.analysis.lint import lint_mesh
    from triton_distributed_tpu.kernels.allgather import _build_all_gather
    from triton_distributed_tpu.lang.launch import captured_launch
    from triton_distributed_tpu.runtime import AllGatherMethod

    n = 8
    _build_all_gather(
        lint_mesh(n, axis), axis, AllGatherMethod.RING_1D, (8 * n, 128),
        jnp.dtype(jnp.float32), 56, _schedule_token(),
    )
    spec = captured_launch("ag_ring_1d")
    return (
        replace(spec, name="fixture_contract_overdeclared_payload"),
        lambda _n: [((8, 128), _F32)],
        DeliveryContract(
            kind="gather", dst="out_ref",
            payload_per_src=lambda _n: 2 * 8 * 128,
        ),
        "jax.lax.all_gather",
    )
