#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the serving engine starts on the chip.

Drives the serving main path once, through the entry points a user
calls::

    ServingEngine.run(trace) -> ServingEngine.step -> Transformer._serving_jit
      -> layers.RaggedPagedAttention -> the compiled ragged paged kernel
      -> the fused EP-MoE dispatch + Pallas grouped GEMMs

Model: ``presets.deepseek_moe_16b`` at every width the preset carries
(hidden 2048, 16 heads x 128, 16 KV heads, 64 experts top-6 at expert
width 1408, vocab 102400, fp8 MoE wire, int8 weights/activations/KV).
Depth is the ONLY cut: 28 layers -> 4 (the leading dense layer + three
expert layers). Weights are random, from ``SEED``. Traffic: a seeded
Poisson trace of 24 requests (prompts 256-1536, 16-32 new tokens)
through chunked prefill over an int8 page pool.

The one-chip leg always runs; with four devices visible the same leg
runs again at tp=4 (4 KV heads and 16 experts per chip, the EP
all-to-all over ICI), plus one pinned PALLAS_FUSED ``ops.ag_gemm`` and
``ops.gemm_rs`` against their XLA twins. One process drives every chip.

It FAILS (non-zero exit, reason on the last line, no result line)
unless JAX's first device is a TPU, every request completes with finite
logits, the engine never degraded / re-promoted / swallowed an
exception, the model resolved the FUSED EP transport with the Pallas
grouped GEMM, the lowered step carries the ragged kernel's Mosaic
custom call (and, on one chip, the pool append kernel's: no hidden
scatter), and one mixed prefill+decode batch agrees between the
kernel and its XLA twin on the same ServingState (logits, not tokens;
once as served, once with every activation-side quantization off —
the sharper instrument, see ``PARITY_VIEWS``), and the engine launched
steps ahead of the one before (``EngineStats.lookahead_steps`` > 0; PR
34) with the streams of the same trace served once more in the drained
order (every step retired in the call that launched it).

Set-up/compile seconds are printed apart from run seconds; no speed is
claimed. The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import gc
import json
import os
import sys
import tempfile
import time

SEED = 0
MODEL_CUT = dict(n_layers=4, moe_layers=(1, 2, 3))
ENGINE = dict(slots=16, token_budget=512, chunk=256, page=128, npages=1024)
TRACE = dict(n_requests=24, mean_interarrival=2.0, len_lo=256,
             len_hi=1537, max_new_lo=16, max_new_hi=33)
#: the parity batch runs the dense-gather XLA twin, whose cost is
#: tokens x pages_per_seq x page — so it gets its own small engine
#: (same model, same params, same page size)
PARITY_ENGINE = dict(slots=4, token_budget=64, chunk=32, page=128, npages=8)
PARITY_PROMPTS = (40, 70, 50)           # arrivals 0, 1, 3; 6 new tokens
#: Parity views: config overrides over the SAME params, and the bound on
#: rms(kernel - twin) / rms(twin) over the batched rows' logits. Kernel
#: and twin differ only inside attention, at bf16 rounding level (the
#: kernel feeds the MXU bf16 probabilities and folds the int8 K/V scales
#: in f32; the twin rounds dequantized K/V to bf16). What the logits
#: show of that depends on how often the layers downstream re-quantize:
#: every lossy step (int8 activation rows, the fp8 MoE wire) turns a
#: sub-LSB difference into whole-LSB flips. "exact_acts" switches those
#: off — int8 weights, bf16 activations and wire — so a wrong mask, page
#: or scale fold in the kernel (one misattended position is ~1/20 of a
#: row's softmax mass) cannot hide; "served" is the preset as it ships
#: and only catches a path that is grossly off (unrelated logits score
#: ~1.4). Measured on the v5e (my chip runs, PR 21; CHANGES.md has the
#: table): exact_acts 0.0075 at tp=4; served 0.047 (tp=1), 0.064
#: (tp=4), up to 0.069 across depth 2-4 variants. Bounds are 2-3x that.
PARITY_VIEWS = {
    "exact_acts": (dict(dense_act_quant=None, moe_act_quant=None,
                        moe_wire_quant=None), 0.02),
    "served": ({}, 0.15),
}
MAX_STEPS = 4000
DEADLINE_S = 1150
#: the window leg (PR 29): ``presets.k_exaone_236b`` cut in WIDTH to a
#: twin that compiles in seconds — every kind of layer, the head size,
#: the window and the page as served (128 each), a share of the experts
#: held (4 of 32, from expert 8 on) — and, compiled but not run, one
#: rung of the step at the published widths as the benchmark serves it
WINDOW_TWIN = dict(n_layers=5, hidden=256, ffn=256, dense_ffn=512,
                   n_heads=8, n_kv_heads=2, vocab=1024, num_experts=32,
                   experts_held=4, first_expert_held=8)
WINDOW_ENGINE = dict(slots=4, token_budget=512, chunk=256, page=128,
                     npages=16)
WINDOW_PROMPTS = (700, 40, 300)         # 700 > ring (4) x page = 512
WINDOW_TOL = 0.03                       # rms(kernel - twin) / rms(twin)
PUBLISHED_CUT = dict(n_layers=5, experts_held=16, vocab=19200)
PUBLISHED_ENGINE = dict(slots=32, token_budget=512, chunk=256, page=128,
                        npages=2176)
#: the sala leg: the benchmark's cut of ``presets.minicpm_sala`` (the
#: published layers 0, 4, ..., 28) and its engine; bounds on
#: rms(kernel - twin) / rms(twin): the selected walk over bf16 pools
#: differs from its twin as the contiguous walk does (bf16
#: probabilities into the MXU), the lightning mixer is float32 both ways
SALA_CUT = dict(n_layers=8, layer_stride=4)
SALA_ENGINE = dict(slots=32, token_budget=512, chunk=256, page=128,
                   npages=7296)
SALA_TOL = {"selected": 0.03, "lightning": 1e-3}
#: the mla leg (PR 35): ``presets.dots_vlm1`` cut in WIDTH to a twin
#: that compiles in seconds: a dense and two sparse layers, the latent
#: entry, both head sizes, YaRN and the page as served (512 + 64, 192 /
#: 128, x 40 over 4096, 128), 8 heads, a share of the experts held (4
#: of 32 in 8 groups, from expert 8 on). Prompts on both sides of a
#: chunk and of a page; bound on rms(kernel - twin) / rms(twin) over the
#: rows both paths answered for the same tokens
MLA_TWIN = dict(n_layers=3, n_dense_layers=1, hidden=256, ffn=256,
                dense_ffn=512, n_heads=8, n_kv_heads=8, q_latent=128,
                vocab=1024, num_experts=32, experts_held=4,
                first_expert_held=8)
MLA_ENGINE = dict(slots=4, token_budget=512, chunk=256, page=128, npages=16)
MLA_PROMPTS = (300, 40, 130)
MLA_TOL = 0.03


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


#: one entry per program JAX lowered in this process (None until
#: ``leg`` hooks the listener) — how "nothing compiled in the warm
#: pass" is counted, tiny eager programs included
_lowered: list | None = None


def programs_lowered() -> int:
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


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def need(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def model_config():
    from triton_distributed_tpu.models import presets

    return presets.deepseek_moe_16b(**MODEL_CUT)


def build(devices):
    """Mesh, model and quantized params on ``devices`` — the repo's own
    init -> shardings -> quantize_moe_weights -> quantize_dense_weights
    sequence, with init jitted straight onto its shardings so the f32
    tree never sits whole on device 0."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer

    mesh = Mesh(np.asarray(devices), ("x",))
    model = Transformer(model_config(), mesh, tp_axis="x")
    params = jax.jit(model.init, out_shardings=model.shardings())(
        jax.random.PRNGKey(SEED)
    )
    params = model.quantize_moe_weights(params)
    params = model.quantize_dense_weights(params)
    return model, jax.block_until_ready(params)


def parity(model, params, on_chip: bool, tol: float) -> dict:
    """One mixed prefill+decode batch through the kernel AND its XLA
    twin from copies of the same ServingState, rms-compared within
    ``tol``; also lowers that step and looks for the ragged kernel's
    Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_distributed_tpu.serving import (
        EngineConfig,
        Request,
        ServingEngine,
    )

    class Probe(ServingEngine):
        host_logits = True      # the logits are what is compared
        pair = None
        lowered = None

        def _run_device(self, arrays, block_q):
            q_lens = arrays[4]
            decode = [s for s in np.flatnonzero(q_lens == 1)
                      if self.slot_req[s].generated]
            if self.pair is not None or not decode \
                    or not (q_lens > 1).any():
                return super()._run_device(arrays, block_q)
            self.lowered = self._step_jit().lower(
                *self._step_args(arrays, block_q)
            ).as_text()
            before = jax.tree.map(jnp.copy, (self.state, self.moe_state))
            got = np.asarray(super()._run_device(arrays, block_q))
            after = (self.state, self.moe_state)
            self.state, self.moe_state = before
            self.use_pallas = False
            try:
                want = np.asarray(super()._run_device(arrays, block_q))
            finally:
                self.use_pallas = True
            self.state, self.moe_state = after
            rows = q_lens > 0
            self.pair = (got[rows], want[rows], q_lens[rows].tolist())
            return got

    rng = np.random.default_rng(SEED)
    trace = [
        Request(rid=i, max_new=6, arrival=float(t),
                prompt=rng.integers(0, model.config.vocab, (n,))
                .astype(np.int32))
        for i, (n, t) in enumerate(zip(PARITY_PROMPTS, (0, 1, 3)))
    ]
    eng = Probe(model, params, EngineConfig(**PARITY_ENGINE),
                propagate_failures=True)
    stats = eng.run(trace, max_steps=MAX_STEPS)
    need(stats.completed == len(trace), "parity trace did not complete")
    need(eng.pair is not None, "parity trace never formed a mixed batch")
    got, want, q_lens = eng.pair
    need(np.isfinite(got).all() and np.isfinite(want).all(),
         "non-finite logits in the parity batch")
    rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    need(rms <= tol,
         f"kernel vs XLA twin logits differ by rms {rms:.4f} of "
         f"rms|logit| (tolerance {tol})")
    if on_chip:
        need(any("tpu_custom_call" in ln
                 and 'kernel_name = "ragged_paged_attention' in ln
                 for ln in eng.lowered.splitlines()),
             "the lowered serving step holds no ragged_paged_attention "
             "tpu_custom_call")
        # the pool append: by its kernel wherever the heads are
        # unsharded, and by no kernel (the row scatter) where they are
        by_kernel = model.kv_append_by_kernel(True)
        need(any("tpu_custom_call" in ln
                 and 'kernel_name = "kv_append' in ln
                 for ln in eng.lowered.splitlines()) == by_kernel,
             "the lowered serving step's pool append is not the path "
             f"tp={model.tp} takes (kv_append tpu_custom_call "
             f"expected: {by_kernel})")
    return {"rows_q_lens": q_lens, "rms_rel_err": rms, "tol": tol,
            "max_abs_err": float(np.abs(got - want).max()),
            "logit_absmax": float(np.abs(want).max())}


def engine(model, params, on_chip: bool):
    """The ServingEngine both passes run on, failures propagating."""
    from triton_distributed_tpu.serving import EngineConfig, ServingEngine
    from triton_distributed_tpu.tune.schedule import GRID_DEFAULT

    eng = ServingEngine(model, params, EngineConfig(**ENGINE),
                        propagate_failures=True)
    need(eng.grid_schedule is GRID_DEFAULT,
         f"engine resolved a stored schedule ({eng.grid_schedule}) from "
         "a store this run did not write")
    if on_chip:
        ctx = model._moe_ep_ctx(
            -(-eng._t_pad // model.token_shards), inference=True,
            weights_quantized=True,
        )
        # one chip exchanges with nobody: no workspaces; across chips
        # every width's step carries its own
        need(ctx.transport == "fused" and ctx.use_pallas_gemm
             and all((ws is None) == ctx.local
                     for ws in eng.moe_state.values()),
             f"EP context resolved transport={ctx.transport!r} "
             f"use_pallas_gemm={ctx.use_pallas_gemm} ranks={ctx.n}")
    return eng


def serve(eng):
    """One pass of the seeded trace through ``eng``, arrivals counted
    from the engine's clock (an idle engine replays the same schedule);
    returns (trace, wall seconds, engine steps)."""
    import numpy as np

    from triton_distributed_tpu.serving import poisson_trace

    vocab = eng.model.config.vocab
    trace = poisson_trace(seed=SEED, vocab=vocab, **TRACE)
    for r in trace:
        r.arrival += eng.step_count
    done, steps = eng.stats.completed, len(eng.stats.step_times)
    t0 = time.perf_counter()
    stats = eng.run(trace, max_steps=MAX_STEPS)
    wall = time.perf_counter() - t0
    need(stats.completed - done == len(trace)
         and all(r.done for r in trace),
         f"{stats.completed - done}/{len(trace)} requests completed")
    need(all(len(r.generated) == r.max_new for r in trace),
         "a request finished short of max_new")
    toks = np.concatenate([r.generated for r in trace])
    need(((toks >= 0) & (toks < vocab)).all(),
         "generated token outside the vocabulary")
    # non-finite logits raise inside ServingEngine._sample
    need(not stats.degraded and stats.repromotions == 0
         and not stats.failures,
         f"engine degraded={stats.degraded} "
         f"repromotions={stats.repromotions} failures={stats.failures}")
    return trace, wall, len(stats.step_times) - steps


def spread(eng, params, n: int) -> dict:
    """Params, page pools and LL state must each sit 1/n per device."""
    big = {
        "moe_up": params["blocks"][1]["moe_up"]["q"],
        "kv_pool": eng.state.layers[1][0]["q"],
    }
    if eng.moe_state[eng._t_pad] is not None:   # across chips (engine())
        big["ll_dispatch"] = eng.moe_state[eng._t_pad][1].disp_tok
    out = {}
    for name, x in big.items():
        devs = {s.device.id for s in x.addressable_shards}
        share = x.addressable_shards[0].data.nbytes / x.nbytes
        need(len(devs) == n and abs(share - 1 / n) < 1e-9,
             f"{name} is not spread 1/{n} per device: devices "
             f"{sorted(devs)}, shard share {share:.3f}")
        out[name] = list(x.addressable_shards[0].data.shape)
    return out


def overlap_ops(mesh) -> dict:
    """Pinned PALLAS_FUSED ag_gemm / gemm_rs (the first remote DMAs
    this repo issues on silicon) against their XLA twins, at the
    model's projection widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu import ops
    from triton_distributed_tpu.kernels.ag_gemm import AGGemmMethod
    from triton_distributed_tpu.kernels.gemm_rs import GemmRSMethod
    from triton_distributed_tpu.tools.native import xla_ag_gemm, xla_gemm_rs

    def rnd(i, shape, *spec):
        x = jax.random.normal(jax.random.PRNGKey(SEED + i), shape,
                              jnp.bfloat16)
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    m, k, n_qkv, n_o = 2048, 2048, 6144, 2048
    cases = {
        "ag_gemm": (
            ops.ag_gemm, xla_ag_gemm,
            ops.create_ag_gemm_context(
                mesh, "x", method=AGGemmMethod.PALLAS_FUSED),
            rnd(1, (m, k), "x"), rnd(2, (k, n_qkv), None, "x"),
        ),
        "gemm_rs": (
            ops.gemm_rs, xla_gemm_rs,
            ops.create_gemm_rs_context(
                mesh, "x", method=GemmRSMethod.PALLAS_FUSED),
            rnd(3, (m, k), None, "x"), rnd(4, (k, n_o), "x"),
        ),
    }
    out = {}
    for name, (op, twin, ctx, a, b) in cases.items():
        got = np.asarray(op(a, b, ctx), np.float32)
        want = np.asarray(twin(a, b, mesh, "x"), np.float32)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        # bf16 outputs of one f32-accumulated K=2048 product: the two
        # engines may round the last bit apart, no more
        need(np.isfinite(got).all() and err <= 2.0 ** -6,
             f"{name} PALLAS_FUSED vs XLA twin: rel err {err:.5f}")
        out[name] = {"shape": list(got.shape), "rel_err": err}
    return out


def lower_published_rung(big, ecfg):
    """``(lowered, width)``: the lowest rung's step of ``big`` at the
    width ``ecfg``'s engine gives it, lowered from shapes alone (no
    weight, no pool is made)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        auto_block_q,
        topo_width,
    )
    from triton_distributed_tpu.serving.engine import packed_width

    cfg = big.config
    rep = NamedSharding(big.mesh, P())

    def arg(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    abstract = jax.tree.map(
        lambda a, sh: arg(a.shape, a.dtype, sh),
        jax.eval_shape(big.init, jax.random.PRNGKey(0)), big.shardings())
    state = jax.eval_shape(
        lambda: big.init_serving_state(
            ecfg.slots, ecfg.npages, ecfg.page, chunk=ecfg.chunk))
    pool_sh = big._serving_pool_sharding
    state = state.replace(layers=jax.tree.map(
        lambda a: arg(a.shape, a.dtype, pool_sh), state.layers))
    cap = auto_block_q(ecfg.chunk, cfg.n_heads // cfg.n_kv_heads)
    slots = ecfg.slots
    width = packed_width(8, slots, ecfg.token_budget)
    ints = [arg((width,), jnp.int32)] * 3 + [arg((slots,), jnp.int32)] * 2
    lowered = big._serving_jit.lower(
        abstract, state, *ints,
        arg((slots, 2 + 2 * topo_width(cap)), jnp.int32),
        big.init_decode_state(width, abstract=True), 8, True, 2)
    return lowered, width


def serve_rows(model, params, ecfg: dict, prompts, use_pallas: bool,
               what: str):
    """``prompts`` (six new tokens each, arriving a step apart) through
    a ``ServingEngine`` whose logits come down. Returns ``(engine,
    {rid: [each served row's float32 logits]}, [each request's tokens],
    stats)``; fails the smoke unless every request completed cleanly."""
    import numpy as np

    from triton_distributed_tpu.serving import (
        EngineConfig,
        Request,
        ServingEngine,
    )

    class HostLogits(ServingEngine):
        host_logits = True      # ``keep`` below records each row's

    eng = HostLogits(model, params, EngineConfig(**ecfg),
                     use_pallas=use_pallas, propagate_failures=True)
    rows, sample = {}, eng._sample

    def keep(row_logits, req):
        rows.setdefault(req.rid, []).append(
            np.asarray(row_logits, np.float32))
        return sample(row_logits, req)

    eng._sample = keep
    reqs = [Request(rid=i, prompt=p, max_new=6, arrival=float(i))
            for i, p in enumerate(prompts)]
    stats = eng.run(reqs, max_steps=MAX_STEPS)
    need(stats.completed == len(prompts) and not stats.failures
         and not stats.degraded,
         f"{what} (use_pallas={use_pallas}) did not complete cleanly: "
         f"{stats.failures}")
    return eng, rows, [r.generated for r in reqs], stats


def window_leg(devices, on_chip: bool = True) -> dict:
    """Sliding-window layers over ring pools, a sigmoid-routed share of
    an expert layer, a shared expert: the width-cut twin served once by
    the kernels and once by their XLA twins (logits compared), then one
    rung of the published-width step compiled for this device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer, presets
    from triton_distributed_tpu.serving import EngineConfig
    from triton_distributed_tpu.serving.state import ring_pages

    mesh = Mesh(np.asarray(devices), ("x",))
    t0 = time.perf_counter()
    model = Transformer(
        presets.k_exaone_236b(param_dtype=jnp.bfloat16, **WINDOW_TWIN),
        mesh, tp_axis="x")
    ring = ring_pages(WINDOW_ENGINE["chunk"], model.config.window,
                      WINDOW_ENGINE["page"])
    params = jax.block_until_ready(jax.jit(
        model.init, out_shardings=model.shardings())(
            jax.random.PRNGKey(SEED)))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.config.vocab, (n,)).astype(np.int32)
               for n in WINDOW_PROMPTS]
    served = {}
    for use_pallas in (True, False):
        eng, rows, _, stats = serve_rows(
            model, params, WINDOW_ENGINE, prompts, use_pallas,
            "window twin")
        need(eng.state.ring == ring and all(
            eng.state.layer_pages(i) == WINDOW_ENGINE["slots"] * ring
            for i in eng.state.window_layers),
            "a window layer holds more than slots x ring pages")
        served[use_pallas] = (
            np.stack([r for rid in sorted(rows) for r in rows[rid]]),
            stats)
    got, want = served[True][0], served[False][0]
    need(np.isfinite(got).all(), "window twin: logits not finite")
    rel = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    need(rel <= WINDOW_TOL,
         f"window twin: kernels and XLA twins disagree, rel rms {rel:.4f}"
         f" > {WINDOW_TOL}")
    st = served[True][1]
    t_twin = time.perf_counter() - t0

    # ---- one published-width rung, compiled for this device, not run
    t0 = time.perf_counter()
    cfg = presets.k_exaone_236b(param_dtype=jnp.bfloat16, **PUBLISHED_CUT)
    big = Transformer(cfg, mesh, tp_axis="x")
    lowered, width = lower_published_rung(
        big, EngineConfig(**PUBLISHED_ENGINE))
    text = lowered.as_text()
    for kernel in (f"ragged_paged_attention_w{cfg.window}",
                   "ragged_paged_attention", "kv_append"):
        need(not on_chip or f'kernel_name = "{kernel}"' in text,
             f"published-width step lowered without the {kernel} kernel")
    mem = lowered.compile().memory_analysis()
    return {
        "leg": "window", "twin_rel_rms": round(rel, 5),
        "twin_s": round(t_twin, 2),
        "ring_pages_per_slot": ring,
        "global_pages_walked": st.global_pages_walked,
        "window_pages_walked": st.window_pages_walked,
        "published_rung_width": width,
        "published_rung_compile_s": round(time.perf_counter() - t0, 2),
        "published_rung_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes},
    }


def sala_leg(devices, on_chip: bool = True) -> dict:
    """PR 33's two launches at MiniCPM-SALA's published widths against
    their XLA twins on one mixed step each (outputs compared, rel rms):
    the ragged kernel's selected walk (2 KV heads x 16 query heads x
    128, pages of 128, blocks of 64, top-64 from compressed keys; two
    decode rows past the dense length, one below it, a chunk of 16 at
    12k) and the lightning mixer (32 heads x 128; spans of 1, 37 and
    256, one from position 0, one slot not batched). Then the
    published-width step of the benchmark's cut, lowered with both
    launches in it and compiled for this device."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.kernels import sparse_select as sel
    from triton_distributed_tpu.kernels.lightning_attention import (
        lightning_attention,
        lightning_attention_xla,
    )
    from triton_distributed_tpu.kernels.ragged_paged_attention import (
        pack_gqa_rows,
        ragged_paged_attention,
        ragged_paged_attention_xla,
    )
    from triton_distributed_tpu.models import Transformer, presets
    from triton_distributed_tpu.serving import EngineConfig

    def rel_rms(got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        return float(np.sqrt(np.mean((got - want) ** 2))
                     / np.sqrt(np.mean(want ** 2)))

    cfg = presets.minicpm_sala(param_dtype=jnp.bfloat16, **SALA_CUT)
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    # ---- the selected walk
    hkv, g, d, page = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, 128, 128
    lens, takes, pps = (9000, 20000, 3000, 12288), (1, 1, 1, 16), 192
    r = len(lens)
    table = jnp.asarray(rng.permutation(r * pps).reshape(r, pps), jnp.int32)
    kp, vp = (jnp.asarray(rng.normal(size=(r * pps, hkv, page, d)),
                          jnp.bfloat16) for _ in range(2))
    starts = np.cumsum([0] + [-(-n // 8) * 8 for n in takes[:-1]])
    t = int(starts[-1]) + 32
    rows, pos = np.zeros((t,), np.int32), np.full((t,), -1, np.int32)
    for i, (n, ln, s) in enumerate(zip(takes, lens, starts)):
        rows[s:s + n], pos[s:s + n] = i, np.arange(ln - n, ln)
    q = jnp.asarray(rng.normal(size=(t, hkv * g, d)), jnp.bfloat16)
    lens_a, takes_a = (jnp.asarray(a, jnp.int32) for a in (lens, takes))
    kc = sel.append_compressed(
        jnp.zeros((r * pps, hkv, page // cfg.sparse_stride, d),
                  jnp.bfloat16),
        kp, table, lens_a, lens_a, kernel=cfg.sparse_kernel,
        stride=cfg.sparse_stride, block_q=max(lens))
    chosen = sel.select_blocks(
        q, kc, table, jnp.asarray(rows), jnp.asarray(pos), lens_a, takes_a,
        jnp.asarray(starts, jnp.int32), group=g, page=page, kernel=cfg.sparse_kernel,
        stride=cfg.sparse_stride, block=cfg.sparse_block,
        init_blocks=cfg.sparse_init_blocks, window=cfg.sparse_window,
        topk=cfg.sparse_topk, dense_len=cfg.sparse_dense_len)
    counts = np.asarray(chosen[1])
    need((counts[:2] <= cfg.sparse_topk).all() and (counts[:2] >= 32).all()
         and (counts[2] == -(-3000 // page)).all(),
         f"selected walk: page counts {counts.tolist()} are not a top-"
         f"{cfg.sparse_topk} selection's")
    args = (pack_gqa_rows(q, hkv), kp, vp, lens_a, takes_a,
            jnp.asarray(starts, jnp.int32), table)
    kw = dict(group=g, selected=chosen, select_block=cfg.sparse_block)
    got, _ = ragged_paged_attention(*args, block_q=16, with_lse=False, **kw)
    want, _ = ragged_paged_attention_xla(*args, **kw)
    live = np.concatenate([np.arange(s * g, (s + n) * g)
                           for n, s in zip(takes, starts)])
    rel_sel = rel_rms(np.asarray(got, np.float32)[:, live],
                      np.asarray(want, np.float32)[:, live])
    need(np.isfinite(rel_sel) and rel_sel <= SALA_TOL["selected"],
         f"selected walk and its XLA twin disagree, rel rms {rel_sel:.5f}"
         f" > {SALA_TOL['selected']}")
    del kp, vp, kc, got, want, chosen
    # ---- the lightning mixer
    heads, bq = cfg.lightning_heads, 256
    q_lens = jnp.asarray([1, 37, 0, 256, 1], jnp.int32)
    q_starts = jnp.asarray([0, 8, 560, 48, 304], jnp.int32)
    kv_lens = jnp.asarray([5000, 37, 0, 12288, 9001], jnp.int32)
    tl = 560 + bq
    lq, lk, lv = (jnp.asarray(rng.normal(size=(heads, tl, d)), jnp.float32)
                  for _ in range(3))
    state = jnp.asarray(rng.normal(size=(5, heads, d, d)), jnp.float32)
    want_o, want_s = lightning_attention_xla(
        lq, lk, lv, state, kv_lens, q_lens, q_starts, block_q=bq)
    got_o, got_s = lightning_attention(
        lq, lk, lv, state, kv_lens, q_lens, q_starts, block_q=bq)
    live = np.concatenate([np.arange(int(s), int(s + n))
                           for n, s in zip(q_lens, q_starts)])
    rel_o = rel_rms(np.asarray(got_o)[:, live], np.asarray(want_o)[:, live])
    rel_s = rel_rms(got_s, want_s)
    need(max(rel_o, rel_s) <= SALA_TOL["lightning"],
         f"lightning mixer and its XLA twin disagree, rel rms outputs "
         f"{rel_o:.2e}, states {rel_s:.2e} > {SALA_TOL['lightning']}")
    need(np.array_equal(np.asarray(got_s)[2], np.asarray(state)[2]),
         "lightning mixer touched the state of a slot it did not batch")
    t_twins = time.perf_counter() - t0

    # ---- the published-width step, lowered and compiled, not run
    t0 = time.perf_counter()
    mesh = Mesh(np.asarray(devices), ("x",))
    big = Transformer(cfg, mesh, tp_axis="x")
    lowered, width = lower_published_rung(
        big, EngineConfig(**SALA_ENGINE))
    text = lowered.as_text()
    for kernel in ("ragged_paged_attention_selected", "lightning_attention",
                   "kv_append"):
        need(not on_chip or f'kernel_name = "{kernel}"' in text,
             f"published-width step lowered without the {kernel} kernel")
    mem = lowered.compile().memory_analysis()
    return {
        "leg": "sala", "selected_rel_rms": round(rel_sel, 5),
        "selected_pages": counts.tolist(),
        "lightning_rel_rms": {"outputs": rel_o, "states": rel_s},
        "twins_s": round(t_twins, 2),
        "published_rung_width": width,
        "published_rung_compile_s": round(time.perf_counter() - t0, 2),
        "published_rung_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes},
    }


def keye_leg() -> dict:
    """PR 50's selection kernel against its XLA twin at the shapes of
    the benchmark's ``keyevl2.docs16k`` (16 slots, a block table of
    1024 pages of 128, top-2048): a decode-only step (16 one-token rows
    at 13k-26k keys) and a step that holds a chunk (one 256-token row
    at 16k keys, a 5-token tail and a row under ``topk`` beside 13
    one-token rows, one slot not batched), each once on random scores
    (no tie at the kth place) and once on scores rounded to 1/64 (ties
    at every kth place, kept by their place). The mask words must be
    EQUAL, bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_distributed_tpu.kernels import token_select as ts

    t0 = time.perf_counter()
    slots, pps, page, topk = 16, 1024, 128, 2048
    sizes = dict(page=page, pps=pps, topk=topk)
    decode = np.linspace(13000, 26273, slots).astype(np.int32)
    steps = {
        "decode": (np.ones(slots, np.int32), decode),
        "chunk": (np.asarray([256, 5, 1, 0] + [1] * 12, np.int32),
                  np.concatenate([[16384 + 256, 9001, 1500, 4000],
                                  decode[4:]]).astype(np.int32)),
    }
    equal = {}
    for name, (q_lens, kv_lens) in steps.items():
        blocks = -(-q_lens // 8) * 8
        q_starts = (np.cumsum(blocks) - blocks) * (q_lens > 0)
        t = int(blocks.sum()) + 8
        pos = np.full((t,), -1, np.int32)
        for n, ln, s in zip(q_lens, kv_lens, q_starts):
            pos[s:s + n] = np.arange(ln - n, ln)
        scores = jax.random.normal(
            jax.random.PRNGKey(SEED), (t, ts.scores_width(pps, page)),
            jnp.float32)
        # what the scan leaves where no query has a key in view
        scores = jnp.where(
            jnp.arange(scores.shape[1])[None, :] > jnp.asarray(pos)[:, None],
            3e38, scores)
        for kind, sc in (("random", scores),
                         ("tied", jnp.round(scores * 64) / 64)):
            got = ts.select_tokens(
                sc, *(jnp.asarray(a, jnp.int32)
                      for a in (kv_lens, q_lens, q_starts)), **sizes)
            want = ts.select_tokens_xla(sc, jnp.asarray(pos), **sizes)
            same = bool(jnp.array_equal(got, want))
            kept = int(jnp.sum(jax.lax.population_count(got)))
            need(same, f"the selection kernel's mask words differ from "
                       f"its XLA twin's on the {name} step, {kind} scores")
            need(kept == int(np.minimum(pos + 1, topk)[pos >= 0].sum()),
                 f"{name} step, {kind} scores: {kept} keys kept")
            equal[f"{name}.{kind}"] = same
    return {"leg": "keye", "words_equal": equal,
            "twins_s": round(time.perf_counter() - t0, 2)}


def mla_leg(devices) -> dict:
    """PR 35's latent pool on the chip: a width-cut twin of
    ``presets.dots_vlm1`` serves three short requests through
    ``ServingEngine`` by the kernels (the latent walk, the one-pool
    append) and again by their XLA twins; every served row's logits
    are compared (rel rms)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.models import Transformer, presets

    t0 = time.perf_counter()
    model = Transformer(
        presets.dots_vlm1(param_dtype=jnp.bfloat16, **MLA_TWIN),
        Mesh(np.asarray(devices), ("x",)), tp_axis="x")
    params = jax.block_until_ready(jax.jit(
        model.init, out_shardings=model.shardings())(
            jax.random.PRNGKey(SEED)))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.config.vocab, (n,)).astype(np.int32)
               for n in MLA_PROMPTS]

    served = {}
    for use_pallas in (True, False):
        eng, rows, tokens, stats = serve_rows(
            model, params, MLA_ENGINE, prompts, use_pallas, "mla twin")
        pool, v = eng.state.layers[0]
        need(v is None and pool.shape == (
            MLA_ENGINE["npages"], 1, MLA_ENGINE["page"],
            model.config.latent_stored),
            "a latent layer holds more than one entry a token")
        served[use_pallas] = (rows, tokens, stats)
    # a request's rows are comparable while both paths fed it the same
    # tokens: up to and with the first row whose arg-max parts them (on
    # seeded random weights a near-tie flips on bf16 rounding, and
    # every later row then answers another sequence)
    got, want = [], []
    for rid, (a, b) in enumerate(zip(served[True][1], served[False][1])):
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                    len(a) - 1) + 1
        got += served[True][0][rid][:same]
        want += served[False][0][rid][:same]
    got, want = np.stack(got), np.stack(want)
    need(len(got) >= 2 * len(prompts),
         f"mla twin: only {len(got)} comparable rows: the paths part at "
         "once")
    need(np.isfinite(got).all(), "mla twin: logits not finite")
    rel = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    need(rel <= MLA_TOL,
         f"mla twin: kernels and XLA twins disagree, rel rms {rel:.4f}"
         f" > {MLA_TOL}")
    st = served[True][2]
    need(st.append_runs > 0 and st.latent_rows > 0,
         "mla twin: the kernel path did not book its append or its walk")
    return {"leg": "mla", "twin_rel_rms": round(rel, 5),
            "rows_compared": len(got),
            "twin_s": round(time.perf_counter() - t0, 2),
            "latent_pages_walked": st.latent_pages_walked,
            "latent_rows": st.latent_rows, "append_runs": st.append_runs}


def leg(devices, on_chip: bool = True) -> dict:
    """The whole smoke on one device set."""
    from triton_distributed_tpu import tracing

    n = len(devices)
    built = [programs_lowered()]
    t0 = time.perf_counter()
    model, params = build(devices)
    t_build = time.perf_counter() - t0
    rec = {"leg": f"{n}chip", "tp": model.tp, "model": "deepseek_moe_16b",
           "cut": MODEL_CUT, "engine": ENGINE, "build_s": round(t_build, 2)}

    t0 = time.perf_counter()
    rec["parity"] = {
        view: parity(
            type(model)(dataclasses.replace(model.config, **overrides),
                        model.mesh, tp_axis=model.tp_axis),
            params, on_chip, tol,
        )
        for view, (overrides, tol) in PARITY_VIEWS.items()
    }
    rec["parity_s"] = round(time.perf_counter() - t0, 2)

    # pass 1 compiles every block_q rung the trace touches; pass 2 is
    # the same trace again with nothing left to compile. ONE engine
    # serves both: a new engine's LL MoE state carries a fresh static
    # ``instance`` id, so every step program would compile again
    built.append(programs_lowered())
    eng = engine(model, params, on_chip)
    cold, t_cold, _ = serve(eng)
    built.append(programs_lowered())
    # readiness, from inside: where this process's set-up went
    print(tracing.ready_line(), flush=True)
    step_programs = eng.stats.programs_built
    warm, t_warm, steps = serve(eng)
    built.append(programs_lowered())
    need(built[3] == built[2],
         f"the warm pass lowered {built[3] - built[2]} new program(s)")
    need(eng.stats.programs_built == step_programs,
         "the warm pass dispatched a step program a first time: "
         f"{tracing.startup_log()['spans'][-1]}")
    need([r.generated for r in cold] == [r.generated for r in warm],
         "the same seeded trace produced different token streams twice")
    # the passes above launched step k + 1 before step k's tokens came
    # down; the same trace once more in the DRAINED order (every step
    # retired in the call that launched it) serves the same streams
    ahead = eng.stats.lookahead_steps
    need(ahead > 0, "no step was launched ahead of the one before")
    eng._launch_ahead = lambda: False
    drained, _, drained_steps = serve(eng)
    del eng._launch_ahead
    need(eng.stats.lookahead_steps == ahead,
         "a step was launched ahead in the drained order")
    need([r.generated for r in drained] == [r.generated for r in warm],
         "launched ahead, the trace's token streams are not the drained "
         "order's")
    rec["lookahead"] = {
        "steps_ahead": ahead, "steps": len(eng.stats.step_times),
        "drained_pass_steps": drained_steps}
    if n > 1:
        rec["spread"] = spread(eng, params, n)
        rec["overlap_ops"] = overlap_ops(model.mesh)
    st = eng.stats
    rec.update(
        schedule=str(eng.grid_schedule), schedule_source="default",
        programs_lowered={"setup": built[1] - built[0],
                          "cold_pass": built[2] - built[1],
                          "warm_pass": built[3] - built[2]},
        requests=len(warm),
        steps=steps,
        prompt_tokens=sum(len(r.prompt) for r in warm),
        generated_tokens=sum(len(r.generated) for r in warm),
        evictions=st.evictions,
        degraded=st.degraded, repromotions=st.repromotions,
        failures=st.failures,
        setup_compile_s=round(t_build + rec["parity_s"] + t_cold, 2),
        run_s=round(t_warm, 2),
        hbm_bytes_in_use=[
            (d.memory_stats() or {}).get("bytes_in_use") for d in devices
        ],
    )
    del eng, params, model
    gc.collect()                        # hand the HBM to the next leg
    return rec


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default="window,dsmoe",
                    help="comma-separated: window (the sliding-window / "
                         "expert-share twin + one published rung "
                         "compiled), dsmoe (the served trace), sala (the "
                         "selected walk and the lightning mixer against "
                         "their twins + one published rung compiled), mla "
                         "(a width-cut latent-attention twin served by "
                         "kernels and by XLA twins), keye (the token "
                         "selection's kernel against its twin at the "
                         "benchmark's shapes)")
    legs = ap.parse_args(argv).legs.split(",")
    t_start = time.perf_counter()
    # a wedged collective must end as a failure with every thread's
    # stack on stderr, inside the smoke's 1200 s allowance — not as a
    # hang (fires from a watchdog thread even under a blocked C++ wait)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: FAIL: JAX's first device is "
              f"{device['platform']!r} ({device['kind']}), not a TPU",
              flush=True)
        return 2

    from triton_distributed_tpu.config import enable_compile_cache

    # hermetic tuning state: no measuring autotuner, and a schedule
    # store this run creates empty (the cwd-relative .autotune_logs/ of
    # an earlier run must not steer the engine build)
    with tempfile.TemporaryDirectory() as store:
        os.environ["TDTPU_AUTOTUNE"] = "0"
        os.environ["TDTPU_AUTOTUNE_LOG_DIR"] = store
        say(device=device, compile_cache=enable_compile_cache(),
            jax=jax.__version__, seed=SEED)
        try:
            if "window" in legs:
                say(**window_leg(devs[:1]))
            if "sala" in legs:
                say(**sala_leg(devs[:1]))
            if "mla" in legs:
                say(**mla_leg(devs[:1]))
            if "keye" in legs:
                say(**keye_leg())
            if "dsmoe" in legs:
                say(**leg(devs[:1]))
                if len(devs) >= 4:
                    say(**leg(devs[:4]))
        except Exception as e:
            import traceback

            traceback.print_exc()
            print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", flush=True)
            return 1
    say(total_s=round(time.perf_counter() - t_start, 2),
        speed="not measured")
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
