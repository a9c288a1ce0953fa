"""The system under test, and nothing else: everything the benchmark
takes from the program is imported here.

Build sequence, engine with failures propagating, the lowering counter
and the hermetic tuning store are copies of what ``chip_smoke.py``
proved on the chip (PR 21) — copies, so that a later PR may change the
program and not the yardstick.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import time

import numpy as np

from benchmark.harness import loadgen, weights


class HarnessFailure(Exception):
    """A run that must not report a result: exit non-zero, no line."""


_lowered: list | None = None


def programs_lowered() -> int:
    """Programs JAX has lowered in this process so far (tiny eager ones
    included); the first call installs the listener."""
    global _lowered
    if _lowered is None:
        import jax

        _lowered = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **k:
            name.endswith("jaxpr_to_mlir_module_duration")
            and _lowered.append(name)
        )
    return len(_lowered)


def hermetic_tuning(store_dir: str) -> None:
    """No measuring autotuner, and a schedule store this run creates
    empty: an earlier run's stored winners must not steer the build."""
    os.environ["TDTPU_AUTOTUNE"] = "0"
    os.environ["TDTPU_AUTOTUNE_LOG_DIR"] = store_dir


def enable_compile_cache() -> str:
    from triton_distributed_tpu.config import enable_compile_cache

    return enable_compile_cache()


def model_config(config: dict):
    """The program's ``TransformerConfig`` for a configuration file:
    its ``preset`` with its ``overrides``; refuses to go on where that
    is not the ``as_run`` sizes the yardstick computes with."""
    import jax.numpy as jnp

    from triton_distributed_tpu.models import presets

    over = dict(config.get("overrides", {}))
    for k in ("dtype", "param_dtype"):
        if k in over:
            over[k] = jnp.dtype(over[k]).type
    if "moe_layers" in over:
        over["moe_layers"] = tuple(over["moe_layers"])
    cfg = getattr(presets, config["preset"])(**over)
    for k, want in config["as_run"].items():
        got = getattr(cfg, k)
        got = list(got) if isinstance(got, tuple) else got
        if got != want:
            raise HarnessFailure(
                f"configuration file says as_run.{k} = {want!r}, the "
                f"program's preset {config['preset']!r} gives {got!r}")
    return cfg


@dataclasses.dataclass
class Program:
    model: object
    engine: object
    reference: object       # the configuration's ``model`` module
    sizes: dict
    param_dtype: object
    shardings: object
    devices: list

    def served_params(self, seed: int):
        """The benchmark's weights for ``seed`` in the form the
        configuration serves them (the program's own quantizers)."""
        import jax

        masters = self.masters(seed)
        c = self.model.config
        if c.moe_weight_quant is None and c.dense_weight_quant is None:
            return jax.block_until_ready(masters)

        # the program's two quantize passes under ONE jit that consumes
        # the masters: run eagerly they queue ~9 GB of float32
        # temporaries at four layers of 64 experts (15.4 GB peak of 16)
        def quantize(m):
            return self.model.quantize_dense_weights(
                self.model.quantize_moe_weights(m))

        return jax.block_until_ready(
            jax.jit(quantize, donate_argnums=0)(masters))

    def load_weights(self, seed: int) -> None:
        """Another seed's weights into the (one) engine."""
        self.engine.params = None
        self.engine.params = self.served_params(seed)

    def masters(self, seed: int):
        """The same weights again, as made (for the reference)."""
        return weights.make_params(
            self.reference.param_plan(self.sizes), seed, self.param_dtype,
            self.shardings)

    def drop_state(self) -> None:
        """Free the program's device state (weights as served, page
        pools, MoE workspaces); the engine cannot step afterwards."""
        eng = self.engine
        eng.params = eng.state = eng.moe_state = None


def build(config: dict, mix: dict, chips: int, seed: int) -> Program:
    """Mesh, model, the benchmark's weights as served, ONE engine."""
    import jax
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer
    from triton_distributed_tpu.serving import EngineConfig, ServingEngine
    from triton_distributed_tpu.tune.schedule import GRID_DEFAULT

    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise HarnessFailure(
            f"the cell asks for {chips} chip(s), JAX sees {len(devices)}")
    cfg = model_config(config)
    mesh = Mesh(np.asarray(devices), ("x",))
    model = Transformer(cfg, mesh, tp_axis="x")
    sizes = dict(config["as_run"])
    reference = importlib.import_module(config["model"])
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = weights.abstract_params(
        reference.param_plan(sizes), cfg.param_dtype)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise HarnessFailure(
            "the program's Transformer.init gives another parameter "
            f"tree than {config['model']}.param_plan")
    ecfg = EngineConfig(**config["engine"])
    need = loadgen.worst_case_tokens(mix)
    if ecfg.slots * need > ecfg.npages * ecfg.page:
        raise HarnessFailure(
            f"{ecfg.slots} slots x {need} tokens do not fit "
            f"{ecfg.npages} pages of {ecfg.page}: this mix would evict")
    prog = Program(model=model, engine=None, reference=reference,
                   sizes=sizes,
                   param_dtype=cfg.param_dtype,
                   shardings=model.shardings(), devices=devices)
    # weights first, then the pools: the peak is the larger of the
    # two pairs, not all three at once. ONE engine per process (a
    # second one recompiles every step program); load_weights gives it
    # another seed's weights
    prog.engine = ServingEngine(model, prog.served_params(seed), ecfg,
                                propagate_failures=True)
    if prog.engine.grid_schedule is not GRID_DEFAULT:
        raise HarnessFailure(
            f"engine resolved a stored schedule "
            f"({prog.engine.grid_schedule}) from a store this run did "
            "not write")
    if devices[0].platform == "tpu" and cfg.moe == "ep":
        ctx = model._moe_ep_ctx(
            -(-prog.engine._t_pad // model.token_shards), inference=True,
            weights_quantized=cfg.moe_weight_quant is not None,
        )
        if not (ctx.transport == "fused" and ctx.use_pallas_gemm
                and prog.engine.moe_state is not None):
            raise HarnessFailure(
                f"EP context resolved transport={ctx.transport!r} "
                f"use_pallas_gemm={ctx.use_pallas_gemm}: not the fused "
                "path this cell is about")
    return prog


def new_request(rid: int, prompt, max_new: int, arrival: float):
    from triton_distributed_tpu.serving import Request

    return Request(rid=rid, prompt=prompt, max_new=max_new,
                   arrival=arrival)


def block_q_rungs(engine) -> list:
    """Every ``block_q`` the engine's steps can be compiled at."""
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        auto_block_q,
    )

    rungs, b = [], auto_block_q(1, engine._g)
    while b <= engine._block_q_cap:
        rungs.append(b)
        b *= 2
    return rungs


def warm_up(engine, vocab: int) -> dict:
    """A fixed short trace that compiles every step program this
    engine can launch: one request alone per ``block_q`` rung (its
    prefill chunk sets the rung), each decoding a few tokens (the
    decode-only step is the lowest rung)."""
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    rungs = block_q_rungs(engine)
    for i, b in enumerate(rungs):
        n = max(2, b // 2 + 1) if b > rungs[0] else 2
        req = new_request(-1 - i, rng.integers(0, vocab, (n,))
                          .astype(np.int32), 3, engine.step_count)
        engine.submit(req)
        for _ in range(64):
            if engine.idle:
                break
            engine.step()
        if not req.done:
            raise HarnessFailure(f"warm-up request at block_q {b} "
                                 "did not finish")
    return {"rungs": rungs, "seconds": time.perf_counter() - t0}


def stats_snapshot(engine) -> dict:
    """``{field: value}`` of every numeric field of ``engine.stats`` and
    ``{field: (list, length now)}`` of every list field, whatever the
    fields are: a counter or a per-step timing the program adds later
    is found here by its name."""
    st = engine.stats
    if dataclasses.is_dataclass(st):
        names = [f.name for f in dataclasses.fields(st)]
    else:
        names = [k for k, v in {**vars(type(st)), **vars(st)}.items()
                 if not k.startswith("_") and not callable(v)
                 and not isinstance(v, property)]
    numbers, lists = {}, {}
    for k in names:
        v = getattr(st, k)
        if isinstance(v, list):
            lists[k] = (v, len(v))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            numbers[k] = v
    return {"numbers": numbers, "lists": lists}


def check_health(engine) -> None:
    st = engine.stats
    if st.degraded or st.repromotions or st.failures:
        raise HarnessFailure(
            f"engine degraded={st.degraded} "
            f"repromotions={st.repromotions} failures={st.failures}")
