"""Native runtime: ctypes bindings + XLA-native degradation targets.

Two kinds of "native" live here:

* ctypes bindings for the native host library (csrc/tdtpu_native.cpp).
  Reference: csrc/{op_pybind.cc,registry.cc} expose CUDA host utilities
  into Python via pybind11/torch; here the binding layer is ctypes over a
  plain C ABI (pybind11 is not in this toolchain) and the library is
  built on first use with g++ (cached under csrc/build/, keyed by the
  source's content hash). Every entry point has a pure-python fallback
  so the package works where no compiler exists — the native path is
  the fast path, not a hard dependency — but a FAILED build is logged
  with the compiler's output, never swallowed.
* **XLA-native collective equivalents** (bottom of the module): the
  degradation targets of ``ops.overlap.with_fallback`` — pure
  ``lax.all_gather``/``psum_scatter`` + ``jnp.dot`` twins of the fused
  Pallas engines, one per engine in the degradation matrix
  (docs/ROBUSTNESS.md). Numerically equivalent (same f32 accumulation),
  strictly slower (no compute/communication overlap), and dependent on
  nothing but XLA — the floor the serving stack can always stand on.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import struct
import subprocess
import threading

import numpy as np

_ART_MAGIC = 0x5452415550544454          # "TDTPUART" little-endian
_FNV_OFF, _FNV_PRIME = 1469598103934665603, 1099511628211


def _fnv1a(data: bytes) -> int:
    h = _FNV_OFF
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h

_ROOT = pathlib.Path(__file__).resolve().parents[2]
_SRC = _ROOT / "csrc" / "tdtpu_native.cpp"
_lock = threading.Lock()
_lib_cache: list = []          # [lib or None] once resolved
_log = logging.getLogger(__name__)


def _so_path() -> pathlib.Path:
    """Library path keyed by the SOURCE's content hash: a build left
    in csrc/build/ by another checkout or an older source can never be
    loaded for this one (mtimes say nothing across copies of a tree)."""
    tag = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _ROOT / "csrc" / "build" / f"libtdtpu_native-{tag}.so"


def _build(so: pathlib.Path) -> bool:
    so.parent.mkdir(parents=True, exist_ok=True)
    tmp = so.with_suffix(f".{os.getpid()}.tmp")
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           "-o", str(tmp), str(_SRC)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)        # a half-written library never lands
        return True
    except (subprocess.SubprocessError, FileNotFoundError) as e:
        _log.warning(
            "native host library build failed — using the pure-python "
            "fallbacks: %s\n%s", e,
            (getattr(e, "stderr", b"") or b"").decode(errors="replace")[-2000:],
        )
        return False


def native_lib():
    """The loaded library, or None (build failed / disabled)."""
    with _lock:
        if _lib_cache:
            return _lib_cache[0]
        lib = None
        if os.environ.get("TDTPU_NO_NATIVE") != "1":
            so = _so_path()
            if so.exists() or _build(so):
                try:
                    lib = ctypes.CDLL(str(so))
                    u8p = ctypes.POINTER(ctypes.c_uint8)
                    lib.tdtpu_artifact_write.argtypes = [
                        ctypes.c_char_p, u8p, ctypes.c_uint64]
                    lib.tdtpu_artifact_size.restype = ctypes.c_int64
                    lib.tdtpu_artifact_size.argtypes = [ctypes.c_char_p]
                    lib.tdtpu_artifact_read.argtypes = [
                        ctypes.c_char_p, u8p, ctypes.c_uint64]
                    lib.tdtpu_moe_align_block_size.restype = ctypes.c_int64
                    lib.tdtpu_dataset_open.restype = ctypes.c_void_p
                    lib.tdtpu_dataset_len.restype = ctypes.c_uint64
                    lib.tdtpu_dataset_close.argtypes = [ctypes.c_void_p]
                    lib.tdtpu_dataset_len.argtypes = [ctypes.c_void_p]
                except OSError as e:
                    _log.warning("native host library %s failed to "
                                 "load: %s", so, e)
                    lib = None
        _lib_cache.append(lib)
        return lib


# ------------------------------------------------------------------ artifact

def artifact_write(path: str, blob: bytes) -> None:
    """Atomic checksummed write. Both paths emit the SAME on-disk format
    (magic | len | payload | fnv1a) so artifacts stay readable across
    hosts with and without the native library."""
    lib = native_lib()
    if lib is not None:
        buf = (ctypes.c_uint8 * len(blob)).from_buffer_copy(blob)
        rc = lib.tdtpu_artifact_write(path.encode(), buf, len(blob))
        if rc == 0:
            return
    framed = (
        struct.pack("<QQ", _ART_MAGIC, len(blob)) + blob
        + struct.pack("<Q", _fnv1a(blob))
    )
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(framed)
    os.replace(tmp, path)


def artifact_read(path: str) -> bytes:
    lib = native_lib()
    if lib is not None:
        size = lib.tdtpu_artifact_size(path.encode())
        if size >= 0:
            out = (ctypes.c_uint8 * size)()
            rc = lib.tdtpu_artifact_read(path.encode(), out, size)
            if rc == -3:
                raise IOError(f"artifact checksum mismatch: {path}")
            if rc == 0:
                return bytes(out)
    raw = pathlib.Path(path).read_bytes()
    if len(raw) >= 8 and struct.unpack_from("<Q", raw, 0)[0] == _ART_MAGIC:
        # Magic present → this IS a framed artifact; a bad length or
        # checksum is corruption/truncation, not a legacy file (returning
        # the raw bytes would hand garbage to a downstream parser —
        # mirror the native rc=-3 error path instead; ADVICE r1).
        if len(raw) < 24:
            raise IOError(f"artifact truncated: {path}")
        _, length = struct.unpack_from("<QQ", raw, 0)
        if len(raw) != 24 + length:
            raise IOError(
                f"artifact length mismatch: {path} ({len(raw)} bytes, "
                f"frame says {24 + length})"
            )
        payload = raw[16 : 16 + length]
        (stored,) = struct.unpack_from("<Q", raw, 16 + length)
        if _fnv1a(payload) != stored:
            raise IOError(f"artifact checksum mismatch: {path}")
        return payload
    return raw                     # pre-framing legacy file: raw payload


# ----------------------------------------------------------------- moe align

def moe_align_block_size_host(topk_ids, num_experts: int, block_m: int):
    """Host (numpy) twin of kernels/moe_utils.moe_align_block_size —
    native-accelerated token sort/pad for CPU-side preprocessing
    (≡ moe_ag_scatter_align_block_size, csrc/lib/moe_utils.cu:61-356).
    Returns (sorted_token_ids, block_expert, splits) numpy arrays."""
    ids = np.ascontiguousarray(topk_ids, dtype=np.int32)
    if ids.size and (ids.min() < 0 or ids.max() >= num_experts):
        raise ValueError(
            f"expert ids out of range [0, {num_experts}): "
            f"[{ids.min()}, {ids.max()}]"
        )
    m, k = ids.shape
    total = m * k
    cap = int(np.ceil((total + num_experts * (block_m - 1)) / block_m)) * block_m
    lib = native_lib()
    if lib is not None:
        sti = np.empty((cap,), np.int32)
        be = np.empty((cap // block_m,), np.int32)
        splits = np.empty((num_experts,), np.int32)
        rc = lib.tdtpu_moe_align_block_size(
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int64(m), ctypes.c_int64(k),
            ctypes.c_int64(num_experts), ctypes.c_int64(block_m),
            sti.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            be.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            splits.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int64(cap),
        )
        if rc < 0:
            raise RuntimeError(
                f"tdtpu_moe_align_block_size failed (rc={rc})"
            )
        return sti, be, splits
    # numpy fallback — same layout contract
    flat = ids.reshape(-1)
    splits = np.bincount(flat, minlength=num_experts).astype(np.int32)
    padded = (splits + block_m - 1) // block_m * block_m
    padded_offs = np.concatenate([[0], np.cumsum(padded)[:-1]]).astype(np.int64)
    offs = np.concatenate([[0], np.cumsum(splits)[:-1]]).astype(np.int64)
    order = np.argsort(flat, kind="stable").astype(np.int32)
    se = flat[order]
    dest = padded_offs[se] + (np.arange(total) - offs[se])
    sti = np.full((cap,), total, np.int32)
    sti[dest] = order
    starts = np.arange(cap // block_m) * block_m
    be = np.searchsorted(np.cumsum(padded), starts, side="right").astype(np.int32)
    be = np.clip(be, 0, num_experts - 1)
    return sti, be, splits


# -------------------------------------------------------------- token dataset

class TokenDataset:
    """mmap'd uint32 token file with seeded random-window sampling — the
    native IO path of the training loop. ``sample`` returns
    (batch, seqlen+1) uint32: inputs = [:, :-1], targets = [:, 1:]."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lib = native_lib()
        self._handle = None
        if self._lib is not None:
            self._handle = self._lib.tdtpu_dataset_open(self.path.encode())
        if self._handle is None:
            self._mm = np.memmap(self.path, dtype=np.uint32, mode="r")

    def __len__(self):
        if self._handle is not None:
            return int(self._lib.tdtpu_dataset_len(self._handle))
        return int(self._mm.shape[0])

    def sample(self, batch: int, seqlen: int, seed: int):
        out = np.empty((batch, seqlen + 1), np.uint32)
        if self._handle is not None:
            rc = self._lib.tdtpu_dataset_sample(
                ctypes.c_void_p(self._handle), ctypes.c_uint64(seed),
                ctypes.c_int64(batch), ctypes.c_int64(seqlen),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            )
            if rc == 0:
                return out
            raise ValueError(f"dataset shorter than seqlen+1={seqlen + 1}")
        n = len(self)
        if n < seqlen + 1:
            raise ValueError(f"dataset shorter than seqlen+1={seqlen + 1}")
        rng = np.random.default_rng(seed)
        offs = rng.integers(0, n - seqlen, size=batch)
        for b, off in enumerate(offs):
            out[b] = self._mm[off : off + seqlen + 1]
        return out

    def close(self):
        if self._handle is not None:
            self._lib.tdtpu_dataset_close(ctypes.c_void_p(self._handle))
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------- XLA-native degradation targets
# The fused-engine fallbacks used by ops.overlap.with_fallback and the
# EP-MoE transport demotion. Deliberately the *simplest correct* XLA
# programs (gather → dot, dot → psum_scatter): when a preflight probe
# has already failed, predictability beats cleverness.
#
# Instrumented like the Pallas engines (lang.maybe_instrument): an XLA
# collective can wedge too — a dead host mid-rendezvous hangs
# all_gather/psum_scatter exactly like a lost DMA credit — and the
# degradation path being the UNINSTRUMENTED one would mean the watchdog
# goes blind at the moment it is most needed (ROADMAP: "watchdog
# coverage for the XLA collective paths"). The builders key on
# config.interp_key() so arming a watchdog / activating a plan rebuilds
# with the heartbeat hooks traced in, same contract as the kernels.

import functools as _functools


@_functools.lru_cache(maxsize=128)
def _xla_ag_gemm_fn(mesh, axis, batch_axes, out_dtype, ikey=None,
                    wire=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu import lang
    from triton_distributed_tpu.lang import wire as wirelib

    ba = tuple(batch_axes)
    mx = wire == "int8-mxu"

    def body(a_loc, b_loc):
        fmt = (
            wirelib.make_wire_format(
                wirelib.wire_payload(wire), a_loc.shape[0], strict=False
            )
            if wire is not None else None
        )
        if fmt is None:
            a_full = jax.lax.all_gather(a_loc, axis, tiled=True)
            return jnp.dot(
                a_full, b_loc, preferred_element_type=jnp.float32
            ).astype(out_dtype)
        # byte-identical lang.wire rails over the XLA gather: the
        # degradation target preserves the wire layout (and for
        # int8-mxu the epilogue-fold numerics) so accuracy tests run on
        # any backend
        q, sc = wirelib.quantize_slab(a_loc, fmt)
        qg = jax.lax.all_gather(q, axis, tiled=True)
        sg = jax.lax.all_gather(sc, axis, tiled=True)
        if mx:
            bq, bs = wirelib.quantize_cols(b_loc)
            row_scale = jnp.repeat(sg[:, :1], fmt.chunk_rows, axis=0)
            acc = jax.lax.dot_general(
                qg, bq, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return (
                acc.astype(jnp.float32) * row_scale * bs
            ).astype(out_dtype)
        a_full = wirelib.dequantize_slab(qg, sg, fmt, a_loc.dtype)
        me = jax.lax.axis_index(axis)
        a_full = jax.lax.dynamic_update_slice(
            a_full, a_loc, (me * a_loc.shape[0], 0)
        )
        return jnp.dot(
            a_full, b_loc, preferred_element_type=jnp.float32
        ).astype(out_dtype)

    body = lang.maybe_instrument(
        body, axis=axis, site="ag_gemm", collective_id="xla_fallback",
        n=mesh.shape[axis],
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(ba + (axis,) if ba else axis, None), P(None, axis)),
        out_specs=P(ba if ba else None, axis),
        check_vma=False,
    )
    return jax.jit(fn)


def xla_ag_gemm(a, b, mesh, axis, *, batch_axes=(), out_dtype=None,
                wire_dtype=None):
    """AllGather(A) @ B via plain XLA — the ag_gemm degradation target.
    Same layout contract as ``kernels.ag_gemm`` (rows sharded over
    ``(*batch_axes, axis)``, B cols sharded over ``axis``).
    ``wire_dtype`` ('fp8'/'int8'/'int8-mxu'): the degraded path keeps
    shipping the byte-identical lang.wire payload+scale rails — and for
    'int8-mxu' the epilogue-fold numerics — so a demotion never changes
    the wire format mid-flight."""
    import jax.numpy as jnp

    from triton_distributed_tpu.config import interp_key
    from triton_distributed_tpu.lang import wire as wirelib

    out_dtype = jnp.dtype(out_dtype or a.dtype)
    return _xla_ag_gemm_fn(
        mesh, axis, tuple(batch_axes), out_dtype, interp_key(),
        wirelib.normalize_wire(wire_dtype),
    )(a, b)


@_functools.lru_cache(maxsize=128)
def _xla_gemm_rs_fn(mesh, axis, batch_axes, out_dtype, ikey=None,
                    wire=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu import lang
    from triton_distributed_tpu.lang import wire as wirelib

    ba = tuple(batch_axes)
    n = mesh.shape[axis]

    def body(a_loc, b_loc):
        part = jnp.dot(a_loc, b_loc, preferred_element_type=jnp.float32)
        fmt = (
            wirelib.make_wire_format(
                wirelib.wire_payload(wire), part.shape[0] // n,
                strict=False,
            )
            if wire is not None and part.shape[0] % n == 0 else None
        )
        if fmt is not None:
            # quantized ppermute reduce ring — the same per-hop
            # payload+scale rails and f32 dequant-accumulate as the
            # Pallas wire ring (runtime.multislice shares the body with
            # the hierarchical DCN legs)
            from triton_distributed_tpu.runtime.multislice import (
                dcn_wire_reduce_scatter,
            )

            return dcn_wire_reduce_scatter(
                part.astype(out_dtype), axis, n, fmt
            )
        return jax.lax.psum_scatter(
            part, axis, scatter_dimension=0, tiled=True
        ).astype(out_dtype)

    body = lang.maybe_instrument(
        body, axis=axis, site="gemm_rs", collective_id="xla_fallback",
        n=n,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(ba if ba else None, axis), P(axis, None)),
        out_specs=P(ba + (axis,) if ba else axis, None),
        check_vma=False,
    )
    return jax.jit(fn)


def xla_gemm_rs(a, b, mesh, axis, *, batch_axes=(), out_dtype=None,
                wire_dtype=None):
    """(A @ B) → ReduceScatter via plain XLA — the gemm_rs degradation
    target. Same layout contract as ``kernels.gemm_rs``. ``wire_dtype``
    keeps the demoted path on the byte-identical quantized reduce ring
    (per-hop payload+scale rails, f32 dequant-accumulate)."""
    import jax.numpy as jnp

    from triton_distributed_tpu.config import interp_key
    from triton_distributed_tpu.lang import wire as wirelib

    out_dtype = jnp.dtype(out_dtype or a.dtype)
    return _xla_gemm_rs_fn(
        mesh, axis, tuple(batch_axes), out_dtype, interp_key(),
        wirelib.normalize_wire(wire_dtype),
    )(a, b)


def xla_kv_ship(payload, shardings):
    """KV-page transfer via plain XLA data movement — the kv_ship
    degradation target: a ``device_put`` of the (already wire-shaped)
    payload pytree onto the decode mesh's placements. No collective, no
    rails, nothing to deadlock — XLA/the runtime route the bytes over
    whatever link connects the meshes (DCN across slices, ICI within
    one), which is exactly the predictability a degraded path wants.
    The payload stays in its quantized pool form (int8 pages + f32
    per-row scale planes), so even the fallback never widens the wire
    — a demotion changes the transport, never the bytes.

    Heartbeated like every other transport: the ``device_put`` is a
    cross-mesh transfer that can wedge exactly like a collective (a
    peer slice going away mid-flight hangs the runtime's copy), so the
    body runs under the host-mode ``kv_ship`` watchdog instrument —
    this was the LAST unheartbeated fallback entry point (``xla_ag_gemm``
    and ``xla_gemm_rs`` instrument inside their shard_map bodies)."""
    import jax

    from triton_distributed_tpu import lang

    def body():
        return jax.tree.map(
            lambda x, s: x if s is None else jax.device_put(x, s),
            payload, shardings,
            is_leaf=lambda x: x is None,
        )

    return lang.maybe_instrument(
        body, axis=None, site="kv_ship", collective_id="xla_fallback",
        n=1,
    )()
