"""Serving CLI: prefill a prompt batch, then SP flash-decode generate.

The reference leaves serving orchestration to the caller (its surface
is the SP decode layer); this CLI completes the loop at L7: build a
preset model on the available mesh, run the one-pass prompt prefill
into the sequence-sharded KV caches, and greedy-decode through the
distributed flash-decode layer, reporting decode throughput.

Usage (any host; model sizes default to the tiny CI twins)::

    python -m triton_distributed_tpu.tools.generate \
        --preset tiny:llama_7b --batch 4 --prompt-len 64 --steps 32

On a multi-chip mesh run one process per host via launch.sh; the tp
axis spans all devices (decode KV is sequence-sharded over it).
"""

from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="tiny",
                   help="models.presets factory name (tiny, llama_7b, "
                        "llama_70b, mixtral_8x7b, deepseek_moe_16b; "
                        "tiny:<name> = the CI twin of <name>'s topology)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--capacity", type=int, default=None,
                   help="KV cache capacity (default prompt+steps rounded up)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--watchdog-deadline", type=float, default=0.0,
                   help="seconds before a wedged collective launch aborts "
                        "the run with rank/semaphore diagnostics instead of "
                        "hanging (0 = watchdog off). Armed around the WHOLE "
                        "run so every build traces the heartbeat hooks in.")
    args = p.parse_args(argv)

    import contextlib

    from triton_distributed_tpu.runtime.watchdog import collective_watchdog

    # arm BEFORE any build: arming participates in config.interp_key, so
    # kernels built inside the context carry the heartbeat instrumentation
    # the deadline monitor needs
    guard = (
        collective_watchdog(deadline=args.watchdog_deadline)
        if args.watchdog_deadline > 0 else contextlib.nullcontext()
    )
    with guard:
        _run(args)


def _run(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.config import enable_compile_cache
    from triton_distributed_tpu.models import Transformer, presets

    enable_compile_cache()

    import inspect

    def _factories():
        return {
            n: f for n, f in vars(presets).items()
            if inspect.isfunction(f) and f.__module__ == presets.__name__
        }

    def _resolve(name):
        f = _factories().get(name)
        if f is None:
            raise SystemExit(
                f"unknown preset {name!r}; available: "
                f"{sorted(_factories())} (or tiny:<name>)"
            )
        return f

    if args.preset.startswith("tiny:"):
        cfg = presets.tiny(_resolve(args.preset.split(":", 1)[1])())
    else:
        cfg = _resolve(args.preset)()

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("tp",))
    model = Transformer(cfg, mesh, "tp", ())
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        model.init(jax.random.PRNGKey(args.seed)),
        model.shardings(),
    )
    # serving weight quantization (preset-gated): expert matrices and
    # dense projections to int8 + per-channel scales, consumed in the
    # grouped-GEMM epilogue (the KV cache quantizes via init_cache
    # when the preset sets kv_quant)
    params = model.quantize_moe_weights(params)
    params = model.quantize_dense_weights(params)

    cap = args.capacity or -(-(args.prompt_len + args.steps) // 128) * 128
    prompt = jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len),
        0, cfg.vocab,
    )

    # compile-warm both phases on throwaway state so the timings below
    # measure execution, not trace+compile
    warm = model._prefill_jit(params, model.init_cache(args.batch, cap), prompt)
    jax.block_until_ready(warm[0])
    del warm  # cache-sized pytree — free it before the timed phases

    caches = model.init_cache(args.batch, cap)
    t0 = time.perf_counter()
    last_logits, caches, lens = model._prefill_jit(params, caches, prompt)
    jax.block_until_ready(last_logits)
    t_prefill = time.perf_counter() - t0

    first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    # LL workspaces for EP-MoE decode (None for dense presets / off-TPU)
    moe_state = model.init_decode_state(args.batch)
    # one warm step to exclude decode compile from the timing — on
    # THROWAWAY cache/lens buffers: the decode jits donate their cache
    # and lens arguments (in-place update), so warming on the live ones
    # would delete the buffers the timed run needs
    warm_c = model.init_cache(args.batch, cap)
    if moe_state is None:
        _, caches_w, lens_w = model._decode_jit(params, warm_c, lens + 0, first)
    else:
        # the state is donated per step — keep threading the returned one
        _, caches_w, lens_w, moe_state = model._decode_jit_state(
            params, warm_c, lens + 0, first, moe_state
        )
    jax.block_until_ready(lens_w)
    del warm_c, caches_w

    t0 = time.perf_counter()
    res = model.generate(
        params, caches, lens, first, args.steps, moe_state=moe_state
    )
    toks, caches, lens = res[:3]
    toks = np.asarray(toks)  # host fetch = the reliable fence
    t_decode = time.perf_counter() - t0

    tps = args.batch * args.steps / t_decode
    print(f"preset={args.preset} devices={len(devs)} "
          f"B={args.batch} prompt={args.prompt_len} steps={args.steps}")
    print(f"prefill: {t_prefill * 1e3:.1f} ms "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode * 1e3:.1f} ms "
          f"({tps:.0f} tok/s, {t_decode / args.steps * 1e3:.2f} ms/step)")
    print("sample completion ids:", toks[0, : min(8, args.steps)].tolist())


if __name__ == "__main__":
    main()
