"""Global configuration and platform detection.

The reference framework configures itself through env vars
(``NVSHMEM_*``, ``USE_TRITON_DISTRIBUTED_AOT``; reference:
python/triton_dist/layers/nvidia/sp_flash_decode_layer.py:32-39). Here the
switches that matter are: which backend are we on (TPU vs CPU-simulated
mesh), whether Pallas kernels should run under the TPU interpreter (the
CPU path used by the test-suite), and test-only chaos/race knobs.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


def backend() -> str:
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def compiling_for_tpu() -> bool:
    """Will Pallas kernels built now lower through Mosaic? True on real
    TPU and under ``force_compile`` (AOT lowering for an unattached TPU
    topology from a CPU-backed process). Strict Mosaic constraints
    (block alignment) key on this, not on :func:`on_tpu`."""
    return config.force_compile or on_tpu()


@dataclass
class Config:
    # Force Pallas interpreter mode even on TPU (debugging).
    force_interpret: bool = field(
        default_factory=lambda: os.environ.get("TDTPU_FORCE_INTERPRET", "0") == "1"
    )
    # Force real Mosaic compilation even off-TPU — the AOT-lowering path:
    # building kernels against an unattached multi-chip TPU *topology*
    # (jax.experimental.topologies) from a CPU-backed process must lower
    # through Mosaic, not the interpreter (tests/test_aot_topology.py).
    force_compile: bool = field(
        default_factory=lambda: os.environ.get("TDTPU_FORCE_COMPILE", "0") == "1"
    )
    # Enable the interpreter's DMA race detector (CPU test runs only).
    # TPU-native answer to the reference's chaos-delay substitute for a race
    # detector (reference: python/triton_dist/kernels/nvidia/allgather.py:72-77).
    detect_races: bool = field(
        default_factory=lambda: os.environ.get("TDTPU_DETECT_RACES", "0") == "1"
    )
    # Inject randomized delays into comm paths to widen race windows
    # ("for_correctness" testing in the reference).
    chaos_delay: bool = field(
        default_factory=lambda: os.environ.get("TDTPU_CHAOS_DELAY", "0") == "1"
    )
    # Debug-mode integrity verification of the fused MoE transport's
    # wire metadata (kernels/moe_dispatch): senders always stamp a
    # checksum word into the meta head; with this flag on, receivers
    # re-verify it and POISON failing slots with NaN instead of
    # silently masking tokens by (possibly corrupted) counts.
    debug_checksum: bool = field(
        default_factory=lambda: os.environ.get("TDTPU_DEBUG_CHECKSUM", "0") == "1"
    )
    # Per-core VMEM working-set budget (bytes) used to gate fused single
    # -kernel engines (ag_gemm, gemm_rs) vs the streaming XLA ring paths.
    fused_vmem_budget: int = field(
        default_factory=lambda: int(
            float(os.environ.get("TDTPU_FUSED_VMEM_BUDGET", str(96 * 1024 * 1024)))
        )
    )
    # Run the fused MoE decode TRANSPORT (chunked window DMAs + LL
    # state) off-TPU on the interpreter instead of demoting decode to
    # the XLA a2a (Transformer._moe_ep_ctx's off-TPU default, kept
    # because per-step interpreted dispatch can wedge the io_callback
    # worker pool on small hosts). Turn on for BOUNDED runs — the
    # multi-device execution evidence for the composed fused-LL decode
    # step (VERDICT r4 #4): tests/test_models.py and the dryrun run 3
    # consecutive steps under it. Expert GEMMs stay on the XLA path
    # off-TPU (Mosaic-only kernels still require real lowering).
    force_fused_transport: bool = field(
        default_factory=lambda: os.environ.get(
            "TDTPU_FORCE_FUSED_TRANSPORT", "0"
        ) == "1"
    )


config = Config()


def fused_vmem_budget() -> int:
    return config.fused_vmem_budget


_FLEET_SEED: int | None = None


def set_fleet_seed(seed: int | None) -> None:
    """Install (or clear, with ``None``) the fleet routing seed.

    Every routing/spill/affinity tie-break in
    :mod:`~triton_distributed_tpu.serving.fleet` hashes through this
    seed, and like the :class:`~triton_distributed_tpu.runtime.faults.
    FaultPlan` identity it is folded into :func:`interp_key` so cached
    kernel builds cannot leak across fleets routed differently."""
    global _FLEET_SEED
    _FLEET_SEED = seed


def fleet_seed() -> int | None:
    """The active fleet routing seed (None outside a fleet)."""
    return _FLEET_SEED


def interp_key() -> tuple:
    """Hashable key of the config state captured at pallas BUILD time
    (chaos delays are traced in; detect_races is baked into the
    interpreter params; force_compile flips interpret→Mosaic) —
    lru-cached kernel builders must include it so toggling any knob
    rebuilds instead of reusing a stale build.

    Includes the fault-engine trace key (runtime.faults.trace_key):
    the active :class:`~triton_distributed_tpu.runtime.faults.FaultPlan`
    identity and the collective-watchdog armed flag — both are traced
    into kernels (seeded delay/corruption ops; heartbeat callbacks), so
    activating/changing/clearing either must invalidate cached builds.
    The fleet routing seed (:func:`set_fleet_seed`) rides along for the
    same reason.
    """
    from triton_distributed_tpu.runtime import faults

    return (
        config.chaos_delay, config.detect_races, config.force_compile,
        config.debug_checksum, _FLEET_SEED,
    ) + faults.trace_key()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a place a caller
    OUTSIDE the process can choose; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no path
    is set in code. Otherwise ``.jax_cache/`` at the checkout root
    (gitignored) — a FIXED path, because the path is part of the cache
    key: a temp name, pid or timestamp never hits twice. Every
    executable is kept, however quick its compile — an entry point's
    set-up is hundreds of small programs beside the few big ones."""
    import pathlib

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(
            pathlib.Path(__file__).resolve().parents[1] / ".jax_cache"
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def autotune_enabled() -> bool:
    """Should ``method=None`` op entries consult the measured autotuner
    (vs the static heuristics)? Default: on real hardware yes, on the CPU
    interpreter no (benching simulated kernels is meaningless and slow).
    Override with TDTPU_AUTOTUNE=1/0.
    """
    env = os.environ.get("TDTPU_AUTOTUNE")
    if env is not None:
        return env == "1"
    return on_tpu()


def _use_interpret(force: bool | None) -> bool:
    """Shared should-we-interpret policy: forced, or running off-TPU.
    ``config.force_compile`` overrides the off-TPU default (AOT lowering
    against an unattached TPU topology needs real Mosaic)."""
    if force is not None:
        return bool(force)
    if config.force_interpret:
        # the explicit debugging knob wins over force_compile: someone
        # asking for the interpreter (race detector, chaos) must get it
        return True
    if config.force_compile:
        return False
    return not on_tpu()


def local_interpret(force: bool | None = None):
    """Pallas ``interpret=`` argument for kernels with NO cross-device ops.

    Off-TPU these run under the *plain* Pallas interpreter (True), not the
    TPU state machine: the simulation's io_callback threads starve XLA's
    CPU thread pool on small hosts (observed as a flaky deadlock with 8
    virtual devices on 1 core), and a kernel without remote DMA/semaphores
    gains nothing from the heavyweight simulation.
    """
    return _use_interpret(force)


_io_callback_patched = False
_pipeline_shim_applied = False


def ensure_pipeline_shim():
    """Make ``pltpu.emit_pipeline`` traceable off-TPU.

    The pipeline helper's ragged-edge DMA tiling asks the *runtime* for the
    TPU generation (jax._src.pallas.mosaic.pipeline._get_tpu_generation) at
    trace time, which raises on the CPU interpreter mesh. The generation
    only picks the second-minor tile multiple used to round up ragged tail
    blocks — our streaming kernels use even blockings and the interpreter
    ignores tiling entirely, so answering a fixed modern generation is
    semantically inert here.

    Guarded: applied only off-TPU, only when the private helper still has
    the expected zero-arg shape; if jax internals drift, raises a clear
    error instead of silently patching (set TDTPU_NO_INTERPRETER_SHIMS=1
    to skip the shim and run without emit_pipeline-based kernels).
    """
    global _pipeline_shim_applied
    if _pipeline_shim_applied or on_tpu():
        return
    if os.environ.get("TDTPU_NO_INTERPRETER_SHIMS") == "1":
        return
    import inspect

    try:
        import jax._src.pallas.mosaic.pipeline as _pipe

        fn = _pipe._get_tpu_generation
        if len(inspect.signature(fn).parameters) != 0:
            raise AttributeError("unexpected _get_tpu_generation signature")
    except (AttributeError, ImportError) as e:
        raise RuntimeError(
            "triton_distributed_tpu interpreter shim: jax internals have "
            "drifted (jax._src.pallas.mosaic.pipeline._get_tpu_generation "
            f"not patchable: {e}). Pin jax to a tested version or set "
            "TDTPU_NO_INTERPRETER_SHIMS=1."
        ) from e
    _pipe._get_tpu_generation = lambda: 5
    _pipeline_shim_applied = True


def ensure_interpreter_unblocked():
    """Unblock the TPU-simulation interpreter on small hosts.

    jax's ``io_callback_impl`` device_puts callback args onto cpu:0 and the
    interpreter's callbacks then force that pending cross-device copy
    (``np.array(val)`` in ``_allocate_buffer``). When every client thread is
    already parked inside a device's blocked callback — guaranteed here,
    where N virtual devices rendezvous through DMA waits on a 1-core host —
    the copy can never be scheduled and the process deadlocks (observed
    deterministically for buffers over ~128 KB/device). The interpreter's
    callback args are always materialized host buffers, so converting them
    in place with ``np.asarray`` needs no client thread at all.

    Process-wide (affects all jax io_callbacks); applied only off-TPU,
    opt-out via TDTPU_NO_IO_CALLBACK_PATCH=1.
    """
    global _io_callback_patched
    if _io_callback_patched or on_tpu():
        return
    if os.environ.get("TDTPU_NO_IO_CALLBACK_PATCH") == "1":
        return
    import inspect
    import logging

    import numpy as np
    import jax._src.callback as _cb
    from jax import tree_util
    from jax._src import config as _jax_config
    from jax._src import xla_bridge as _xb

    try:
        expected = {"result_avals", "callback", "sharding", "ordered"}
        params = inspect.signature(_cb.io_callback_impl).parameters
        if not expected.issubset(params) or not hasattr(_cb, "io_callback_p"):
            raise AttributeError(f"io_callback_impl params {set(params)}")
    except AttributeError as e:
        raise RuntimeError(
            "triton_distributed_tpu interpreter shim: jax internals have "
            f"drifted (jax._src.callback.io_callback_impl not patchable: {e})."
            " Pin jax to a tested version or set TDTPU_NO_IO_CALLBACK_PATCH=1"
            " (large interpreted kernels may then deadlock on small hosts)."
        ) from e

    logger = logging.getLogger("jax._src.callback")

    def io_callback_impl(*args, result_avals, callback, sharding, ordered):
        # Same contract as the original impl, minus the device_put of args
        # onto cpu:0 (the deadlock); callbacks still run under a cpu
        # default_device and failures are still logged.
        del result_avals, sharding, ordered
        args = tuple(np.asarray(a) for a in args)
        cpu_device, *_ = _xb.local_devices(backend="cpu")
        with _jax_config.default_device(cpu_device):
            try:
                return tree_util.tree_map(np.asarray, callback(*args))
            except BaseException:
                logger.exception("jax.io_callback failed")
                raise

    _cb.io_callback_impl = io_callback_impl
    _cb.io_callback_p.def_impl(io_callback_impl)
    _io_callback_patched = True


def interpret_params(force: bool | None = None):
    """Pallas ``interpret=`` argument for the current platform.

    On TPU hardware: ``False`` (compile with Mosaic). Anywhere else (the
    8-virtual-device CPU mesh the tests run on): ``InterpretParams`` so that
    remote DMA + semaphore semantics are simulated faithfully.
    """
    from jax.experimental.pallas import tpu as pltpu

    if not _use_interpret(force):
        if not on_tpu():
            # force_compile from a CPU-backed process (AOT lowering for a
            # TPU topology): emit_pipeline still asks the *runtime* for
            # the TPU generation at trace time — answer for the target
            ensure_pipeline_shim()
        return False
    ensure_interpreter_unblocked()
    ensure_pipeline_shim()
    return pltpu.InterpretParams(
        detect_races=config.detect_races,
        dma_execution_mode="on_wait",
    )
