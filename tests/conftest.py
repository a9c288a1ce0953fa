"""Test harness: 8 virtual CPU devices simulating a TPU slice.

The reference tests are torchrun multi-process scripts on real GPUs
(SURVEY.md §4). Here every test runs single-process on a virtual 8-device
CPU mesh; Pallas kernels execute under the TPU interpreter
(InterpretParams), which faithfully simulates remote DMA + semaphores.
Whether the same kernels COMPILE is tests/test_aot_topology.py's job
(real Mosaic, unattached v5e topology); that they RUN on the chip is
chip_smoke.py's.
"""

import faulthandler
import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import Mesh  # noqa: E402


def pytest_collection_modifyitems(items):
    """Run the tuned-engine-selection tests LAST. They bench many
    interpreted kernels in rapid succession, which can leave the TPU
    interpreter's io_callback worker pool wedged on this 1-core host;
    an interpreted kernel running after them in the same process then
    deadlocks in the ordered-effects chain (observed as a hang in
    Token.block_until_ready). The full suite's alphabetical order
    already put test_tune last — this makes that load-bearing ordering
    explicit so subset runs are safe too."""
    items.sort(key=lambda it: "TestTunedEngineSelection" in it.nodeid)


@pytest.fixture(autouse=True)
def _fresh_interpreter_state():
    """Isolate tests: the TPU interpreter keeps global shared memory /
    semaphore state per process; stale state from a failed kernel must not
    leak into the next test."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.reset_tpu_interpret_mode_state()
    yield


@pytest.fixture(autouse=True)
def _test_deadline():
    """Per-test wall-clock ceiling: a hung collective (wedged semaphore
    wait, starved io_callback pool) must fail the suite in seconds, not
    eat the full tier-1 budget. ``faulthandler.dump_traceback_later``
    fires from a watchdog thread even when the main thread is blocked
    inside a C++ wait (where ``signal.alarm`` would never be delivered):
    it dumps every thread's stack and hard-exits. Override the ceiling
    with ``TDTPU_TEST_TIMEOUT`` (seconds; 0 disables)."""
    ceiling = float(os.environ.get("TDTPU_TEST_TIMEOUT", "300"))
    if ceiling > 0:
        faulthandler.dump_traceback_later(ceiling, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session")
def mesh8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return Mesh(np.asarray(devs), ("x",))


@pytest.fixture(scope="session")
def mesh2x4():
    devs = np.asarray(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("dp", "tp"))


# ---------------------------------------------------------------- MoE helpers
# Shared by test_ep_moe / test_moe / test_chaos so the dense reference and
# the routed-data construction exist exactly once.

def dense_moe_ref(x, logits, w_up, w_down, topk, activation="silu"):
    """Per-token dense MoE reference: topk-weighted expert MLPs."""
    import jax
    import jax.numpy as jnp

    from triton_distributed_tpu.kernels import moe_utils as mu

    weights, ids = mu.select_experts(logits, topk)
    act = jax.nn.silu if activation == "silu" else jax.nn.gelu
    out = jnp.zeros((x.shape[0], w_down.shape[-1]))
    for t in range(topk):
        h = act(jnp.einsum("mh,mhf->mf", x, w_up[ids[:, t]]))
        out += weights[:, t : t + 1] * jnp.einsum(
            "mf,mfh->mh", h, w_down[ids[:, t]]
        )
    return out


def moe_splits_data(n, m, num_experts, hidden, seed=0):
    """Random expert-sorted tokens + per-device splits (numpy)."""
    rng = np.random.default_rng(seed)
    assign = np.sort(rng.integers(0, num_experts, (n, m)), axis=1)
    splits = np.stack(
        [np.bincount(a, minlength=num_experts) for a in assign]
    ).astype(np.int32)
    toks = rng.standard_normal((n, m, hidden)).astype(np.float32)
    return toks, splits


# ------------------------------------------------------------ serving helper
# Shared by test_models / test_serving_step: the engine packs the steps
# (the one packing contract), the device step hands out every position.

def force_fused_ctx(use_pallas_gemm=False):
    """Monkeypatch body for ``Transformer._moe_ep_ctx``: the serving
    step rides the fused EP transport even off-TPU (tiny
    interpreter-safe geometry), honoring the config's moe_wire_quant and
    moe_act_quant (W8A8 lives in the Pallas grouped GEMM:
    ``use_pallas_gemm``)."""
    from triton_distributed_tpu import ops

    def fused_ctx(self, m_local, inference=False, weights_quantized=None):
        c = self.config
        return ops.create_ep_moe_context(
            self.mesh, self.tp_axis, num_experts=c.num_experts,
            topk=c.topk, max_m=m_local * c.topk, hidden=c.hidden,
            dtype=c.dtype, transport="fused" if inference else "xla",
            use_pallas_gemm=use_pallas_gemm, block_m=8,
            quant=c.moe_wire_quant if inference else None,
            act_quant=c.moe_act_quant if inference else None,
            batch_axes=tuple(self.dp_axes),
        )

    return fused_ctx


def drained(engine_cls):
    """``engine_cls`` with its launch-ahead question forced to "no":
    every step is retired in the ``step()`` call that launched it (the
    synchronous order) — what the order that keeps one step in flight
    is compared with."""
    return type("Drained" + engine_cls.__name__, (engine_cls,),
                {"_launch_ahead": lambda self: False})


def on_host(engine_cls):
    """``engine_cls`` with ``host_logits``: every step's logits come
    down (a tap that reads or compares them needs that), so no step is
    launched ahead."""
    return type("Host" + engine_cls.__name__, (engine_cls,),
                {"host_logits": True})


def serve_all_logits(model, params, ecfg, prompts, *, max_new=1,
                     use_pallas=False, **engine_kw):
    """Serve ``prompts`` (arriving together) through a ``ServingEngine``
    whose device step is ``Transformer._serving_all_logits_jit``.
    Returns ``(engine, requests, logits)``: ``logits[i]`` is
    ``(len(prompt) + max_new - 1, vocab)`` float32, row p the
    next-token distribution the step that batched sequence position p
    of request i computed there."""
    from triton_distributed_tpu.serving import Request, ServingEngine

    class AllLogitsEngine(on_host(ServingEngine)):
        def _step_jit(self):
            return self.model._serving_all_logits_jit

        def _run_device(self, arrays, block_q):
            full = np.asarray(
                super()._run_device(arrays, block_q))       # (T, vocab)
            _, _, token_pos, q_starts, q_lens = arrays[:5]
            for s in np.nonzero(q_lens)[0]:
                span = slice(q_starts[s], q_starts[s] + q_lens[s])
                rows = seen.setdefault(self.slot_req[s].rid, {})
                rows.update(zip(token_pos[span].tolist(), full[span]))
            return full[np.clip(q_starts + q_lens - 1, 0, len(full) - 1)]

    seen: dict = {}
    eng = AllLogitsEngine(model, params, ecfg, use_pallas=use_pallas,
                          propagate_failures=True, **engine_kw)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32), max_new=max_new,
                    arrival=0.0) for i, p in enumerate(prompts)]
    stats = eng.run(reqs)
    assert stats.completed == len(reqs) and not stats.failures, stats.failures
    logits = [np.stack([seen[r.rid][p]
                        for p in range(len(r.prompt) + max_new - 1)])
              for r in reqs]
    return eng, reqs, logits
