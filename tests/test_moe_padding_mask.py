"""A serving step's padding rows are no token's: their expert
assignments reach ``ops.ep_moe`` masked (the sentinel, weight 0), so
the op neither ships nor multiplies them — and the step's real rows
read the same numbers as when every row was routed.

CPU sizes, the XLA twins (``use_pallas=False``): there the masked and
the unmasked step must agree bit for bit on every real row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from triton_distributed_tpu import ops
from triton_distributed_tpu.models import Transformer, presets
from triton_distributed_tpu.serving import (
    EngineConfig,
    Request,
    ServingEngine,
)

pytestmark = pytest.mark.fast

ENGINE = EngineConfig(slots=4, token_budget=32, chunk=8, page=8, npages=32)
#: a softmax router over experts all held here, and a sigmoid router
#: whose layer holds experts 2-5 of 8 (``held_assignments`` masks too)
CONFIGS = {
    "softmax": dict(moe="ep", moe_layers=(1,), num_experts=4, topk=2),
    "sigmoid_share": dict(
        moe="ep", moe_layers=(1,), num_experts=8, topk=2,
        router="sigmoid_bias", experts_held=4, first_expert_held=2),
}


def one_chip_model(**over):
    return Transformer(presets.tiny(**over),
                       Mesh(np.asarray(jax.devices()[:1]), ("x",)),
                       tp_axis="x")


def engine_with(model, prompts, params=None, max_new=3):
    eng = ServingEngine(
        model, model.init(jax.random.PRNGKey(0)) if params is None
        else params, ENGINE, use_pallas=False)
    rng = np.random.default_rng(0)
    for i, n in enumerate(prompts):
        eng.submit(Request(
            rid=i, max_new=max_new, arrival=0,
            prompt=rng.integers(0, model.config.vocab, (n,))
            .astype(np.int32)))
    return eng


def first_step_args(eng):
    """The argument tuple of the first device step: a chunk of 8, a
    whole prompt of 5, two empty slots; the rest of the packed width
    is padding."""
    eng._admit()
    arrays = eng._assemble()[:7]
    return eng._step_args(arrays, 8), arrays


def spy_on_ep_moe(monkeypatch):
    seen, real = [], ops.ep_moe

    def spy(x, logits, *a, **kw):
        seen.append(logits)
        return real(x, logits, *a, **kw)

    monkeypatch.setattr(ops, "ep_moe", spy)
    return seen


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_padding_rows_reach_the_op_masked_and_real_rows_are_untouched(
        kind, monkeypatch):
    model = one_chip_model(**CONFIGS[kind])
    c = model.config
    eng = engine_with(model, (11, 5))
    args, arrays = first_step_args(eng)
    token_pos, q_lens = arrays[2], arrays[4]
    t, tokens = token_pos.shape[0], int(q_lens.sum())
    assert tokens == 8 + 5 and (t,) == eng._widths(8)

    seen = spy_on_ep_moe(monkeypatch)
    step = dict(block_q=8, use_pallas=False)
    masked, _ = model.serving_step(*args[:9], **step)
    (flat_e, w_flat), = seen
    flat_e = np.asarray(flat_e).reshape(t, c.topk)
    w_flat = np.asarray(w_flat).reshape(t, c.topk)
    real = token_pos >= 0
    assert real.sum() == tokens
    # every assignment of a padding row is the sentinel, weight exactly 0
    assert (flat_e[~real] == c.local_experts).all()
    assert not w_flat[~real].any()

    # the parent's routing: every packed row routed like a token
    del seen[:]
    mask_blind = Transformer._decode_moe_ep
    monkeypatch.setattr(
        Transformer, "_decode_moe_ep",
        lambda self, blk, xn, state=None, row_mask=None:
        mask_blind(self, blk, xn, state))
    routed, _ = model.serving_step(*args[:9], **step)
    old, = seen
    if isinstance(old, tuple):              # the share routed already
        np.testing.assert_array_equal(
            flat_e[real], np.asarray(old[0]).reshape(t, c.topk)[real])
        np.testing.assert_array_equal(
            w_flat[real], np.asarray(old[1]).reshape(t, c.topk)[real])
        sentinels = (np.asarray(old[0]) == c.local_experts).sum()
    else:                                   # the op routed, nothing masked
        assert old.shape == (t, c.num_experts)
        sentinels = 0
        assert (flat_e == c.local_experts).sum() == (t - tokens) * c.topk
    assert (flat_e == c.local_experts).sum() > sentinels
    batched = np.asarray(q_lens) > 0
    np.testing.assert_array_equal(np.asarray(masked)[batched],
                                  np.asarray(routed)[batched])


def test_moe_masked_rows_counts_what_assemble_left_empty():
    model = one_chip_model(**CONFIGS["softmax"])
    eng = engine_with(model, (11, 5, 20))
    for _ in range(40):
        if eng.idle:
            break
        eng.step()
    st = eng.stats
    assert eng.idle and st.completed == 3
    assert st.moe_masked_rows == (
        st.packed_rows - sum(st.step_tokens)) > 0
    # a model with no EP expert layer masks nothing
    dense = engine_with(one_chip_model(), (11, 5))
    for _ in range(20):
        dense.step()
    assert dense.stats.completed == 2
    assert dense.stats.moe_masked_rows == 0


def test_no_row_mask_hands_the_op_its_logits_unmasked(monkeypatch):
    """A caller with no mask behaves as before: ``_decode_moe_ep``
    gives ``ops.ep_moe`` the router's logits, and the op routes."""
    model = one_chip_model(**CONFIGS["softmax"])
    params = model.init(jax.random.PRNGKey(0))
    seen = spy_on_ep_moe(monkeypatch)
    xn = jax.random.normal(jax.random.PRNGKey(1),
                           (2, model.config.hidden), jnp.float32)
    y, state = model._decode_moe_ep(params["blocks"][1], xn, row_mask=None)
    logits, = seen
    assert logits.shape == (2, model.config.num_experts)
    assert y.shape == xn.shape and state is None
