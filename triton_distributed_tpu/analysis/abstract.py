"""Abstract evaluator: run SHMEM kernel bodies symbolically, per rank.

A kernel body is an ordinary Python function over Pallas refs. Under
the evaluator it runs *eagerly* with:

* concrete rank values — ``lang.my_pe`` returns the rank currently
  being executed (the ``lang.shmem`` hook layer consults
  :func:`events.active_recorder`);
* :class:`AbsRef` stand-ins for refs — real numpy storage, so index
  arithmetic and compute (``jnp.dot`` on loaded blocks, fold-in adds)
  execute concretely, while every read/write is recorded with its
  element region;
* :class:`AbsSem`/:class:`AbsDMA` stand-ins for semaphores and DMA
  descriptors — starts, waits and signals become trace events instead
  of hardware ops;
* a patched Pallas/lax environment (:func:`patched_pallas`):
  ``pl.when`` evaluates its concrete predicate, ``lax.fori_loop``
  becomes a Python loop, ``emit_pipeline`` records the hull of its
  block accesses, delays are no-ops.

One execution per rank yields the per-rank event traces
(:class:`events.Recorder`) that :mod:`checks` replays cross-rank.

Heuristic, documented: a remote put also copies its source values into
the *local* instance of the destination buffer. Per-rank execution has
no peer memory; for rank-symmetric inputs (the registry's lint shapes)
this models "the peer sends what I would send", which is what
count-carrying protocols (the MoE metadata heads) need to steer their
receive loops correctly.
"""

from __future__ import annotations

import contextlib
import functools
import inspect
import itertools

import numpy as np

from triton_distributed_tpu.analysis import events as ev


def _as_int(x) -> int:
    """Concretize an index/count that may be a 0-d jax array."""
    return int(x)


# ------------------------------------------------------------------- refs

class TaggedArray(np.ndarray):
    """Value read out of an :class:`AbsRef`, remembering *where* it was
    read from so a subsequent store can be recognized as a copy
    (``dst[...] = src[...]``) or a two-operand fold
    (``dst[...] = a[...] + b[...]``) — the provenance edges the SL008
    delivery pass follows. Any other arithmetic strips the tag: the
    result is then locally computed data, which is exactly what the
    dataflow model wants."""

    def __array_finalize__(self, obj):
        # never inherit a tag through views/copies/astype — a tag is
        # only valid on the exact array a read returned
        self.src_region = None
        self.add_srcs = None

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        tags = [getattr(i, "src_region", None) for i in inputs]
        plain = tuple(
            i.view(np.ndarray) if isinstance(i, TaggedArray) else i
            for i in inputs
        )
        out = getattr(ufunc, method)(*plain, **kwargs)
        if (
            ufunc is np.add and method == "__call__" and len(inputs) == 2
            and all(t is not None for t in tags)
            and isinstance(out, np.ndarray)
        ):
            out = out.view(TaggedArray)
            out.add_srcs = (tags[0], tags[1])
        return out


class AbsRef:
    """Ref stand-in with numpy storage. Views (``.at[...]`` and the
    evaluator's slicing) share the parent storage and keep ROOT-buffer
    coordinates: ``origin`` spans every root dim (including ones a
    scalar index dropped) and ``dims`` maps each remaining data dim to
    its root dim, so recorded regions always index the root buffer."""

    def __init__(self, name, data, space="vmem", rec=None, origin=None,
                 root=None, dims=None):
        self.name = name
        self.data = data                      # np.ndarray (possibly a view)
        self.space = space
        self.rec = rec
        self.origin = tuple(origin or (0,) * data.ndim)
        self.root = root or name
        self.dims = tuple(range(data.ndim)) if dims is None else tuple(dims)

    # -- python surface the kernels use ------------------------------------
    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def at(self):
        return _AtIndexer(self)

    def __getitem__(self, idx):
        view = self._slice(idx)
        if self.rec is not None:
            self.rec.emit(ev.ReadEvent(region=view.region()))
        out = np.array(view.data).view(TaggedArray)  # copy — refs mutable
        out.src_region = view.region()
        return out

    def __setitem__(self, idx, value):
        view = self._slice(idx)
        copy_src = getattr(value, "src_region", None)
        add_srcs = getattr(value, "add_srcs", None)
        if np.shape(value) != view.data.shape:
            copy_src = add_srcs = None    # broadcast/partial store: no edge
        if self.rec is not None:
            self.rec.emit(ev.WriteEvent(
                region=view.region(), copy_src=copy_src, add_srcs=add_srcs,
            ))
        view.data[...] = np.broadcast_to(
            np.asarray(value, dtype=self.data.dtype), view.data.shape
        )

    # -- internals ---------------------------------------------------------
    def _slice(self, idx) -> "AbsRef":
        if not isinstance(idx, tuple):
            idx = (idx,)
        np_idx, origin, dims, squeeze = [], list(self.origin), [], []
        req_ext, oob = {}, False
        for d in range(self.ndim):
            rd = self.dims[d]
            dim = self.data.shape[d]
            i = idx[d] if d < len(idx) else slice(None)
            if i is Ellipsis:
                i = slice(None)
            if isinstance(i, slice):
                start = 0 if i.start is None else _as_int(i.start)
                stop = dim if i.stop is None else _as_int(i.stop)
            elif hasattr(i, "start") and hasattr(i, "size"):  # pl.Slice
                start = _as_int(i.start)
                stop = start + _as_int(i.size)
            else:                        # scalar index: slice + squeeze so
                start = _as_int(i)       # the result stays a writable VIEW
                stop = start + 1
                origin[rd] += start
                np_idx.append(slice(start, stop))
                squeeze.append(d)
                continue
            if start < 0 or stop > dim:
                oob = True                # numpy will clip silently —
            req_ext[rd] = stop - start    # remember the REQUESTED window
            np_idx.append(slice(start, stop))
            origin[rd] += start
            dims.append(rd)
        sub = self.data[tuple(np_idx)]
        if squeeze:
            sub = np.squeeze(sub, axis=tuple(squeeze))
        res = AbsRef(
            self.name, sub, self.space, self.rec,
            origin=origin, root=self.root, dims=dims,
        )
        if oob and self.rec is not None:
            lo = tuple(res.origin)
            hi = tuple(
                o + req_ext.get(rd, 1)
                for rd, o in enumerate(res.origin)
            )
            self.rec.emit(ev.OobEvent(region=ev.Region(self.root, lo, hi)))
        return res

    def region(self) -> ev.Region:
        extent = {rd: s for rd, s in zip(self.dims, self.data.shape)}
        lo = tuple(self.origin)
        hi = tuple(
            o + extent.get(rd, 1) for rd, o in enumerate(self.origin)
        )
        return ev.Region(self.root, lo, hi)

    def set_values(self, values) -> None:
        """Raw store WITHOUT a Write event (used by the evaluator's
        local data-propagation for puts — the write is carried by the
        PutEvent itself)."""
        self.data[...] = np.broadcast_to(
            np.asarray(values, dtype=self.data.dtype), self.data.shape
        )

    def __repr__(self):
        return f"AbsRef({self.root}{list(self.origin)}, {self.data.shape})"


class _AtIndexer:
    def __init__(self, ref):
        self._ref = ref

    def __getitem__(self, idx):
        return self._ref._slice(idx)


class AbsSem:
    """Semaphore stand-in. ``.at[idx]`` selects a slot; the (name, slot)
    pair is the identity credits and waits are matched on."""

    def __init__(self, name, shape=(), slot=()):
        self.name = name
        self.shape = tuple(shape)
        self.slot = tuple(slot)

    @property
    def at(self):
        return _SemIndexer(self)

    @property
    def key(self):
        return (self.name, self.slot)

    def __repr__(self):
        return f"AbsSem({self.name}{list(self.slot)})"


class _SemIndexer:
    def __init__(self, sem):
        self._sem = sem

    def __getitem__(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        slot = tuple(_as_int(i) for i in idx)
        return AbsSem(self._sem.name, self._sem.shape, self._sem.slot + slot)


class AbsDMA:
    """DMA-descriptor stand-in. ``start`` emits a PutEvent and locally
    propagates source values into the destination view (see module
    docstring). Wait methods emit consuming waits on the matching
    semaphore slots — including the Pallas idiom of rebuilding a
    descriptor (or a dummy local copy) purely to wait on its semaphore,
    which is why waits do not require a preceding ``start``."""

    def __init__(self, rec, src, dst, send_sem, recv_sem=None, dst_rank=None,
                 local=False):
        self.rec = rec
        self.src, self.dst = src, dst
        self.send_sem, self.recv_sem = send_sem, recv_sem
        self.dst_rank = rec.me if dst_rank is None else _as_int(dst_rank)
        self.local = local

    def start(self):
        self.rec.emit(ev.PutEvent(
            src_region=self.src.region(),
            dst_region=self.dst.region(),
            dst_rank=self.dst_rank,
            send_key=self.send_sem.key,
            recv_key=self.recv_sem.key if self.recv_sem else None,
            local=self.local,
        ))
        if self.src.data.shape == self.dst.data.shape:
            self.dst.set_values(self.src.data)
        return self

    def wait_send(self):
        self.rec.emit(ev.WaitEvent(key=self.send_sem.key, value=1))

    def wait_recv(self):
        key = (self.recv_sem or self.send_sem).key
        self.rec.emit(ev.WaitEvent(key=key, value=1))

    def wait(self):
        if self.local:
            self.rec.emit(ev.WaitEvent(key=self.send_sem.key, value=1))
        else:
            self.wait_send()
            self.wait_recv()


# --------------------------------------------------------- patched pallas

_BARRIER_SEM = "barrier_sem"


def _space_str(ms) -> str:
    s = str(ms).lower()
    for known in ("vmem", "smem", "semaphore", "any"):
        if known in s:
            return "semaphore" if known == "semaphore" else known
    return "any"


@contextlib.contextmanager
def patched_pallas(rec: ev.Recorder):
    """Swap the Pallas/lax entry points kernels actually use for
    evaluator equivalents, for the dynamic extent of one symbolic
    execution. Single-threaded by design (lint runs are not concurrent
    with tracing)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def mk_remote(src_ref, dst_ref, send_sem, recv_sem, device_id,
                  device_id_type=None, **kw):
        return AbsDMA(rec, src_ref, dst_ref, send_sem, recv_sem,
                      dst_rank=device_id)

    def mk_local(src_ref, dst_ref, sem, **kw):
        return AbsDMA(rec, src_ref, dst_ref, sem, None, local=True)

    def sem_signal(sem, inc=1, device_id=None, device_id_type=None, **kw):
        target = rec.me if device_id is None else _as_int(device_id)
        rec.emit(ev.SignalEvent(key=sem.key, target=target,
                                inc=_as_int(inc)))

    def sem_wait(sem, value=1):
        rec.emit(ev.WaitEvent(key=sem.key, value=_as_int(value)))

    def barrier_sem():
        rec.barrier_sem_used = True
        return AbsSem(_BARRIER_SEM)

    def when(pred):
        def deco(fn):
            if bool(pred):
                fn()
            return fn
        return deco

    def fori_loop(lo, hi, body, init, **kw):
        carry = init
        for i in range(_as_int(lo), _as_int(hi)):
            carry = body(i, carry)
        return carry

    def emit_pipeline(body, *, grid, in_specs=None, out_specs=None, **kw):
        in_specs = list(in_specs or [])
        out_specs = list(out_specs or [])

        def hull(spec, ref):
            bs = tuple(_as_int(b) for b in spec.block_shape)
            dims = tuple(_as_int(g) for g in grid)
            pts = itertools.product(*(range(g) for g in dims))
            if int(np.prod(dims)) > 4096:   # affine maps: corners suffice
                pts = itertools.product(*({0, g - 1} for g in dims))
            lo = [None] * len(bs)
            hi = [None] * len(bs)
            for pt in pts:
                blk = spec.index_map(*pt)
                if not isinstance(blk, tuple):
                    blk = (blk,)
                for d, b in enumerate(blk):
                    b = _as_int(b)
                    lo[d] = b * bs[d] if lo[d] is None else min(lo[d], b * bs[d])
                    hi[d] = max(hi[d] or 0, (b + 1) * bs[d])
            hi = [min(h, s) for h, s in zip(hi, ref.data.shape)]
            return ref._slice(tuple(slice(l, h) for l, h in zip(lo, hi)))

        def run(*refs):
            ins, outs = refs[: len(in_specs)], refs[len(in_specs):]
            for spec, ref in zip(in_specs, ins):
                rec.emit(ev.ReadEvent(region=hull(spec, ref).region()))
            for spec, ref in zip(out_specs, outs):
                rec.emit(ev.WriteEvent(region=hull(spec, ref).region()))

        return run

    # mutated by run_symbolic's grid walk (one kernel execution per
    # grid point, ids advancing row-major — the sequential-grid
    # semantics every registered grid kernel pins)
    grid_env = {"ids": (0,) * 8, "dims": (1,) * 8}

    patches = [
        (pltpu, "make_async_remote_copy", mk_remote),
        (pltpu, "make_async_copy", mk_local),
        (pltpu, "semaphore_signal", sem_signal),
        (pltpu, "semaphore_wait", sem_wait),
        (pltpu, "get_barrier_semaphore", barrier_sem),
        (pltpu, "emit_pipeline", emit_pipeline),
        (pl, "when", when),
        (pl, "delay", lambda cycles: None),
        # the in-register rotate has no eager rule: numpy's is the same
        (pltpu, "roll", lambda x, shift, axis, **kw: np.roll(
            np.asarray(x), _as_int(shift), axis)),
        (pl, "program_id", lambda d: grid_env["ids"][d]),
        (pl, "num_programs", lambda d: grid_env["dims"][d]),
        (jax.lax, "fori_loop", fori_loop),
    ]
    saved = []
    for mod, attr, repl in patches:
        saved.append((mod, attr, getattr(mod, attr, None)))
        setattr(mod, attr, repl)
    try:
        yield grid_env
    finally:
        for mod, attr, orig in reversed(saved):
            if orig is None:
                try:
                    delattr(mod, attr)
                except AttributeError:
                    pass
            else:
                setattr(mod, attr, orig)


# ------------------------------------------------------ ref construction

def _ref_names(kernel, count) -> list:
    """Best-effort ref names from the kernel callable's signature (the
    params left unbound by functools.partial), for readable findings."""
    fn, bound = kernel, 0
    while isinstance(fn, functools.partial):
        bound += len(fn.args)
        fn = fn.func
    try:
        params = [
            p for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD,
                          p.VAR_POSITIONAL)
        ]
    except (TypeError, ValueError):
        params = []
    names, i = [], 0
    for p in params[bound:]:
        if p.kind == p.VAR_POSITIONAL:
            break
        names.append(p.name)
    while len(names) < count:
        names.append(f"ref{len(names)}")
    return names[:count]


def build_refs(launch, in_shapes, rec: ev.Recorder, init=None):
    """Materialize the abstract refs for one captured launch:
    ``in_shapes`` — per-device input (shape, dtype) pairs (the one thing
    the capture cannot know); outputs and scratch come from the captured
    ``out_shape``/``scratch_shapes``. ``init`` maps ref NAME -> initial
    ndarray (default zeros). Returns the positional ref list and tallies
    the VMEM working set into ``rec.info``."""
    import jax

    init = dict(init or {})
    specs: list[tuple] = []                  # (kind, shape, dtype, space)
    in_specs = launch.in_specs or []
    for i, (shape, dtype) in enumerate(in_shapes):
        space = _space_str(
            getattr(in_specs[i], "memory_space", "vmem")
        ) if i < len(in_specs) else "vmem"
        specs.append(("ref", shape, np.dtype(dtype), space))
    out_shape = launch.out_shape
    if isinstance(out_shape, (jax.ShapeDtypeStruct,)):
        out_shape = [out_shape]
    out_specs = launch.out_specs
    if out_specs is not None and not isinstance(out_specs, (list, tuple)):
        out_specs = [out_specs]
    for i, o in enumerate(out_shape):
        space = _space_str(
            getattr(out_specs[i], "memory_space", "vmem")
        ) if out_specs and i < len(out_specs) else "vmem"
        specs.append(("ref", tuple(o.shape), np.dtype(o.dtype), space))
    for s in launch.scratch_shapes or ():
        space = _space_str(getattr(s, "memory_space", ""))
        if space == "semaphore" or "SemaphoreType" in type(s).__name__:
            specs.append(("sem", tuple(getattr(s, "shape", ()) or ()),
                          None, "semaphore"))
        else:
            specs.append(("ref", tuple(s.shape), np.dtype(s.dtype), space))

    names = _ref_names(launch.kernel, len(specs))
    n_in = len(in_shapes)
    refs, vmem, breakdown = [], 0, []
    for i, (name, (kind, shape, dtype, space)) in enumerate(
        zip(names, specs)
    ):
        rec.ref_meta.setdefault(name, ev.RefMeta(
            shape=tuple(shape), dtype=dtype, space=space,
            is_input=(kind == "ref" and i < n_in), index=i,
        ))
        if kind == "sem":
            refs.append(AbsSem(name, shape))
            continue
        data = init.get(name, init.get(i))
        data = (np.zeros(shape, dtype) if data is None
                else np.array(data, dtype).reshape(shape))
        if kind == "ref" and i < n_in:
            # value-level contract facets (the ragged topology check)
            # read input OPERANDS at replay time
            rec.input_values.setdefault(i, np.array(data, copy=True))
        refs.append(AbsRef(name, data, space, rec))
        if space in ("vmem", "smem"):
            vmem += data.nbytes
            breakdown.append((name, data.nbytes))
    rec.info.vmem_bytes = vmem
    rec.info.vmem_breakdown = tuple(breakdown)
    return refs


def run_symbolic(launch, in_shapes, n: int, *, axis="x", mesh_axes=None,
                 init=None, kernel_name=None, site=None) -> ev.Recorder:
    """Symbolically execute ``launch.kernel`` once per rank on an
    abstract ``n``-rank mesh; returns the filled recorder."""
    info = ev.LaunchInfo(
        kernel=kernel_name or launch.name or "?",
        site=site,
        collective_id=launch.collective_id,
        vmem_limit_bytes=launch.vmem_limit_bytes,
    )
    rec = ev.Recorder(n, axis, mesh_axes, info)
    # grid kernels (the ragged serving family) execute once PER GRID
    # POINT, row-major, with persistent refs/scratch across steps —
    # the sequential-grid semantics their SMEM slot carries and
    # cross-step DMA prefetches rely on. Gridless launches (every
    # collective family) run exactly once, as before.
    grid = launch.grid
    gs = getattr(launch, "grid_spec", None)
    if grid is None and gs is not None:
        grid = getattr(gs, "grid", None)
    points = (
        list(itertools.product(*(range(int(d)) for d in grid)))
        if grid else [()]
    )
    for me in range(n):
        refs = build_refs(launch, in_shapes, rec, init=init)
        rec.start_rank(me)
        old = ev.set_recorder(rec)
        try:
            with patched_pallas(rec) as grid_env:
                if grid:
                    grid_env["dims"] = tuple(int(d) for d in grid) + (
                        (1,) * (8 - len(grid))
                    )
                for ids in points:
                    if ids:
                        grid_env["ids"] = tuple(ids) + (0,) * (8 - len(ids))
                    launch.kernel(*refs)
        finally:
            ev.set_recorder(old)
    rec.me = None
    return rec
