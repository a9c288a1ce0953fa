"""Tutorial 13: serving — one engine, one step, the int8 stack on.

The reference leaves serving orchestration to the caller (its surface
is the SP decode layer, sp_flash_decode_layer.py); here the flagship
model is served by ``serving.ServingEngine``: requests of any length
arrive at any time, each engine step packs prefill CHUNKS and decode
tokens into one ragged batch, and ``Transformer.serving_step`` runs it
as one program — pool append, ragged paged attention over a block
table, EP-MoE block — over a donated ``ServingState`` of page pools.
There is no other decode path.

Three things are shown on a tiny twin of the DeepSeek-MoE preset:

1. **The int8 serving stack** (every heavy plane of the step, each
   with exact scale folds): KV pools (``kv_quant``: int8 values + one
   f32 scale per (page, head, position); the kernel folds K's scale
   into the scores and V's into p), expert matrices
   (``moe_weight_quant``: per-(expert, out-channel) scales in the
   grouped-GEMM epilogue), expert activations (``moe_act_quant``:
   W8A8, the MXU's s8×s8 path) and dense projections
   (``dense_weight_quant``, the same kernel with E=1).
2. **Continuous batching over pages**: more requests than slots,
   prompts longer than a chunk, a pool of 8-token pages; every request
   completes with exactly ``max_new`` tokens, and the first token's
   logits agree with the full-precision model's ``forward`` within
   int8 noise (median over the requests).
3. **The barrier-free LL MoE carry** (≡ the reference's call_count
   protocol, low_latency_all_to_all.py:97-118): where the fused EP
   transport engages, ``Transformer.init_decode_state`` allocates
   persistent double-buffered workspaces, the engine threads them
   through every step (donated, returned, handed to the next), and the
   parity rolls once a step with no barrier.
"""

from _common import get_mesh

mesh = get_mesh(4)          # the tiny twin's 4 KV heads shard over tp

import jax
import numpy as np
from jax.sharding import Mesh

from triton_distributed_tpu.config import config
from triton_distributed_tpu.models import Transformer, presets
from triton_distributed_tpu.serving import (
    EngineConfig,
    Request,
    ServingEngine,
)

ENGINE = EngineConfig(slots=4, token_budget=48, chunk=16, page=8, npages=48)
PROMPTS = (37, 9, 21, 5, 14, 30)        # six requests, four slots
MAX_NEW = 4


def build(cfg, mesh, key=0):
    """Model + weights placed on their shardings, then quantized as
    the configuration says (AFTER placement: the quantized leaves
    inherit the sharding of their sources)."""
    model = Transformer(cfg, mesh, mesh.axis_names[0], ())
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, s),
        model.init(jax.random.PRNGKey(key)), model.shardings(),
    )
    return model, model.quantize_dense_weights(
        model.quantize_moe_weights(params))


def serve(model, params, prompts):
    """All ``prompts`` through one engine. Returns the engine, the
    requests, and each request's FIRST-token logits row."""
    class HostLogits(ServingEngine):
        # the logits come down (a greedy engine otherwise keeps each
        # row's arg-max on the device and fetches token ids, one step
        # in flight): ``keep`` below reads a row's
        host_logits = True

    eng = HostLogits(model, params, ENGINE)
    first, sample = {}, eng._sample

    def keep(row_logits, req):
        first.setdefault(req.rid, np.asarray(row_logits, np.float32))
        return sample(row_logits, req)

    eng._sample = keep
    reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW, arrival=0.5 * i)
            for i, p in enumerate(prompts)]
    stats = eng.run(reqs)
    assert stats.completed == len(reqs) and not stats.degraded
    return eng, reqs, first


def main():
    # ---- 1. the int8 stack: the DeepSeek serving preset ships all four
    # planes on; the tiny() twin keeps the same quantization topology
    cfg = presets.tiny(presets.deepseek_moe_16b())
    assert (cfg.kv_quant, cfg.moe_weight_quant, cfg.moe_act_quant,
            cfg.dense_weight_quant) == ("int8",) * 4
    model, params = build(cfg, mesh)
    assert params["blocks"][0]["wqkv"]["q"].dtype == np.int8
    assert params["lm_head"]["q"].dtype == np.int8

    # ---- 2. continuous batching over pages
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in PROMPTS]
    eng, reqs, first = serve(model, params, prompts)
    assert eng.state.layers[0][0]["q"].dtype == np.int8   # int8 pools
    st = eng.stats
    for r in reqs:
        assert len(r.generated) == MAX_NEW
        print(f"request {r.rid}: prompt {len(r.prompt):2d} tokens -> "
              f"{r.generated}")
    print(f"{len(st.step_tokens)} engine steps, {st.prefill_tokens} prefill "
          f"tokens in chunks of <= {ENGINE.chunk}, "
          f"{sum(st.step_generated)} tokens generated, "
          f"{st.evictions} evictions")

    # the full-precision model (same weights before quantization) agrees
    # within int8 noise: its plain ``forward`` over each prompt, last
    # position, against the logits the engine sampled the first token from
    cfg_f = presets.tiny(presets.deepseek_moe_16b(), kv_quant=None,
                         moe_weight_quant=None, moe_act_quant=None,
                         dense_weight_quant=None, dense_act_quant=None,
                         moe_wire_quant=None)
    model_f, params_f = build(cfg_f, mesh)
    width = -(-max(PROMPTS) // 8) * 8             # rows shard over tp
    padded = np.zeros((len(reqs), width), np.int32)
    for r in reqs:
        padded[r.rid, :len(r.prompt)] = r.prompt  # causal: padding is inert
    want = np.asarray(jax.jit(model_f.forward)(params_f, padded)).reshape(
        len(reqs), width, cfg.vocab)
    errs = []
    for r in reqs:
        ref = want[r.rid, len(r.prompt) - 1]
        errs.append(float(
            np.abs(first[r.rid] - ref).max() / np.abs(ref).max()))
    # (a token whose top-k experts flip under the quantization noise
    # shows as an outlier of 0.2-0.3: the median is the noise itself)
    print("int8 stack vs full-precision forward, first-token logits: "
          f"rel err median {np.median(errs):.4f}, max {max(errs):.4f}")
    assert np.median(errs) < 0.05, errs

    # ---- 3. the barrier-free LL carry. Off-TPU the model demotes the
    # EP transport to the XLA all-to-all; force the fused transport on a
    # 2-device mesh (interpreter-sized) to watch the state ride the
    # engine. On a TPU slice it engages by itself.
    mesh2 = Mesh(mesh.devices.reshape(-1)[:2], ("x",))
    cfg_ll = presets.tiny(presets.mixtral_8x7b())
    model_x, params_ll = build(cfg_ll, mesh2, key=2)
    _, reqs_x, _ = serve(model_x, params_ll, prompts[:2])
    config.force_fused_transport = True
    try:
        model_ll, _ = build(cfg_ll, mesh2, key=2)     # fresh jit caches
        eng_ll, reqs_ll, _ = serve(model_ll, params_ll, prompts[:2])
    finally:
        config.force_fused_transport = False
    # one set of workspaces per packed width the engine's steps take (a
    # decode-only step is narrower than a chunk step), one parity for
    # all of them: it rolls once a step, whichever width the step has
    states = eng_ll.moe_state
    assert states is not None and None not in states.values(), \
        "the fused transport did not engage"
    parity = {
        w: int(np.asarray(next(s for s in st if s is not None).parity)[0])
        for w, st in states.items()}
    steps = len(eng_ll.stats.step_tokens)
    assert set(parity.values()) == {steps % 2}
    assert [r.generated for r in reqs_ll] == [r.generated for r in reqs_x]
    print(f"LL carry: {steps} barrier-free steps, parity by width -> "
          f"{parity}, tokens == XLA transport")
    print("tutorial 13 OK")


if __name__ == "__main__":
    main()
