"""Serving CLI: a batch of prompts through ``serving.ServingEngine``.

Builds a preset model on the available devices, submits ``--batch``
random prompts of ``--prompt-len`` tokens to one continuous-batching
engine (chunked prefill and decode share each ``Transformer.
serving_step``), greedy-decodes ``--steps`` tokens each and reports the
throughput of the second, compiled pass.

Usage (any host; model sizes default to the tiny CI twins)::

    python -m triton_distributed_tpu.tools.generate \
        --preset tiny:llama_7b --batch 4 --prompt-len 64 --steps 32

The tp axis spans as many devices as divide the model's KV heads (and
experts): the serving pools shard heads over it.
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
    p.add_argument("--batch", type=int, default=4,
                   help="requests submitted together (= engine slots)")
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--steps", type=int, default=32,
                   help="tokens generated per request")
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
    import inspect

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from triton_distributed_tpu.config import (
        compiling_for_tpu,
        enable_compile_cache,
    )
    from triton_distributed_tpu.models import Transformer, presets
    from triton_distributed_tpu.serving import (
        EngineConfig,
        Request,
        ServingEngine,
    )

    enable_compile_cache()
    factories = {
        n: f for n, f in vars(presets).items()
        if inspect.isfunction(f) and f.__module__ == presets.__name__
    }
    tiny, _, name = args.preset.rpartition(":")
    if name not in factories or tiny not in ("", "tiny"):
        raise SystemExit(
            f"unknown preset {args.preset!r}; available: "
            f"{sorted(factories)} (or tiny:<name>)"
        )
    cfg = factories[name]()
    if tiny:
        cfg = presets.tiny(cfg)

    devs = jax.devices()
    tp = max(
        d for d in range(1, len(devs) + 1)
        if cfg.n_kv_heads % d == 0
        and (cfg.moe == "none" or cfg.local_experts % d == 0)
    )
    mesh = Mesh(np.asarray(devs[:tp]), ("tp",))
    model = Transformer(cfg, mesh, "tp", ())
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s),
        model.init(jax.random.PRNGKey(args.seed)),
        model.shardings(),
    )
    # serving weight quantization (preset-gated): expert matrices and
    # dense projections to int8 + per-channel scales (the KV pools
    # quantize when the preset sets kv_quant)
    params = model.quantize_dense_weights(model.quantize_moe_weights(params))

    page = 128                      # int8 pools need page % 128 == 0
    chunk = -(-min(args.prompt_len, 256) // 8) * 8
    per_req = -(-(args.prompt_len + args.steps) // page)
    ecfg = EngineConfig(
        slots=args.batch, token_budget=max(chunk, 8 * args.batch),
        chunk=chunk, page=page, npages=args.batch * per_req, seed=args.seed,
    )
    # the kernels on the TPU, their XLA twins elsewhere (the Pallas
    # interpreter would serve the same tokens, minutes later)
    eng = ServingEngine(model, params, ecfg,
                        use_pallas=compiling_for_tpu())
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len),
        0, cfg.vocab,
    ), np.int32)

    def serve():
        """One pass of the batch through the (one) engine."""
        reqs = [Request(rid=i, prompt=row, max_new=args.steps,
                        arrival=eng.step_count)
                for i, row in enumerate(prompts)]
        first_step = eng.step_count
        t0 = time.perf_counter()
        eng.run(reqs)
        return reqs, time.perf_counter() - t0, eng.step_count - first_step

    serve()                         # compiles every step program used
    reqs, t_serve, n_steps = serve()
    stats = eng.stats
    if not all(r.done for r in reqs) or stats.failures or stats.degraded:
        raise SystemExit(
            f"engine did not serve the batch cleanly: "
            f"done={[r.done for r in reqs]} failures={stats.failures} "
            f"degraded={stats.degraded}")

    tps = args.batch * args.steps / t_serve
    print(f"preset={args.preset} devices={tp} "
          f"B={args.batch} prompt={args.prompt_len} steps={args.steps}")
    print(f"decode:  {t_serve * 1e3:.1f} ms, prefill included "
          f"({tps:.0f} tok/s, {n_steps} engine steps, "
          f"{t_serve / n_steps * 1e3:.2f} ms/step)")
    print("sample completion ids:",
          [int(t) for t in reqs[0].generated[: min(8, args.steps)]])


if __name__ == "__main__":
    main()
