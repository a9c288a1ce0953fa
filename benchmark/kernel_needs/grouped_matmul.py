"""What one engine step needs of the grouped GEMM (``kernels/group_gemm``
``grouped_matmul``), as the expert layers use it: bytes it has to move
and operations it has to do, both lower bounds. Signature and arguments
as ``ragged_paged_attention.step_needs``.

Per expert layer and step, with ``n`` = the step's batched tokens and
``a = n · topk`` assignments:

* weights: the up ``(hidden, ffn)`` and down ``(ffn, hidden)`` matrices
  of every expert TOUCHED, read once. Which experts a step touches is
  the router's to say and is not recorded (a device-to-host read every
  step); ``min(experts, a)`` is the most it can be, so on a step that
  touches fewer this counts from ABOVE — with 8 experts and >= 50
  assignments that is under 1 % of steps (all 8 are hit), which is why
  the metric lists only such cells;
* activations: ``a`` rows in and out of each GEMM (hidden -> ffn ->
  hidden), padding rows left out;
* operations: one multiply-add per row, input and output channel of
  each GEMM: ``4 · a · hidden · ffn``.

Weights are taken at the width the configuration stores them in
(``overrides.param_dtype``) and activations at the compute width
(``overrides.dtype``, bfloat16 where not given): for UN-QUANTIZED
experts. Where the program quantizes the experts (int8 weights and
rows) the same kernel also runs the dense projections and ``lm_head``,
the events cannot be told apart by name, and this function is not the
one to use.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def step_needs(config: dict, rows: list) -> tuple:
    sizes, over = config["as_run"], config.get("overrides", {})
    w_bytes = _BYTES[over["param_dtype"]]
    x_bytes = _BYTES[over.get("dtype", "bfloat16")]
    hidden, ffn = sizes["hidden"], sizes["ffn"]
    layers = len(sizes["moe_layers"])
    assigned = sum(take for take, _ in rows) * sizes["topk"]
    touched = min(sizes["num_experts"], assigned)
    weights = touched * 2 * hidden * ffn * w_bytes
    activations = assigned * 2 * (hidden + ffn) * x_bytes
    return (layers * (weights + activations),
            layers * 4.0 * assigned * hidden * ffn)
