"""What one engine step needs of the grouped GEMM where the expert
layer holds a SHARE of a wider layer's experts (``as_run.experts_held``
of ``num_experts``) and every expert is a gated three-matrix MLP:
bytes it has to move and operations it has to do. Signature and
arguments as ``ragged_paged_attention.step_needs``.

Per expert layer and step, with ``n`` = the step's batched tokens:

* kept assignments: each token chooses ``topk`` distinct experts of
  ``num_experts``; ``n · topk · held / num_experts`` of the choices
  fall on an expert held here, on average;
* weights: gate, up ``(hidden, ffn)`` and down ``(ffn, hidden)`` of
  every held expert a token chose, read once. Which experts a step
  touches is the router's to say and is not recorded (a device-to-host
  read every step), so this is the EXPECTED number under a router that
  spreads evenly, ``held · (1 - (1 - topk / num_experts) ** n)`` — an
  expectation, not a bound: right over the ~100 steps of a traced
  part with seeded random weights, too high or low on any one step;
* activations: the kept rows in (hidden), the two hidden halves out
  and the gated product back in (3 · ffn), the result out (hidden);
* operations: ``6 · kept · hidden · ffn`` (three matrices).

Weights at ``overrides.param_dtype``, activations at ``overrides.dtype``
(bfloat16 where not given). Byte-bound at every batch this cell sees.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_needs(config: dict, rows: list) -> tuple:
    sizes, over = config["as_run"], config.get("overrides", {})
    w_bytes = _BYTES[over["param_dtype"]]
    x_bytes = _BYTES[over.get("dtype", "bfloat16")]
    hidden, ffn = sizes["hidden"], sizes["ffn"]
    held, experts, topk = (sizes["experts_held"], sizes["num_experts"],
                           sizes["topk"])
    layers = len(sizes["moe_layers"])
    n = sum(take for take, _ in rows)
    kept = n * topk * held / experts
    touched = held * (1.0 - (1.0 - topk / experts) ** n)
    weights = touched * 3 * hidden * ffn * w_bytes
    activations = kept * (2 * hidden + 3 * ffn) * x_bytes
    return (layers * (weights + activations),
            layers * 6.0 * kept * hidden * ffn)
