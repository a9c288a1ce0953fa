"""The one oracle the serving tests compare with: ``Transformer.forward``
over the whole sequence — the plain reference, independent of pages,
chunks, slots and the engine.

``forward`` is causal, so a sequence is right-padded to a bucket (one
compile a bucket, not one a length) and read at its own positions.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 16


@functools.lru_cache(maxsize=None)
def _forward_jit(model):
    return jax.jit(model.forward)


def forward_logits(model, params, seq) -> np.ndarray:
    """(len(seq), vocab) float32 logits of ``model.forward`` on one
    sequence: row i is the next-token distribution after ``seq[:i+1]``."""
    seq = np.asarray(seq, np.int32)
    n = len(seq)
    step = int(np.lcm(BUCKET, model.token_shards))
    padded = np.zeros((-(-n // step) * step,), np.int32)
    padded[:n] = seq
    logits = _forward_jit(model)(params, jnp.asarray(padded)[None])
    return np.asarray(logits, np.float32)[:n]


def greedy_tokens(model, params, prompt, max_new: int) -> list:
    """``max_new`` greedy tokens after ``prompt`` by repeated
    ``model.forward`` over the whole sequence so far."""
    seq = [int(t) for t in np.asarray(prompt)]
    for _ in range(max_new):
        seq.append(int(np.argmax(forward_logits(model, params, seq)[-1])))
    return seq[len(prompt):]


def served_gaps(model, params, prompt, generated) -> np.ndarray:
    """How far each SERVED token's reference logit lies below the
    reference's best at its position (0 where the served token is the
    reference's argmax), prompt + served tokens teacher-forced through
    ``forward`` — what ``benchmark/harness/correct.py`` compares."""
    prompt = np.asarray(prompt, np.int32)
    generated = np.asarray(generated, np.int32)
    logits = forward_logits(
        model, params, np.concatenate([prompt, generated]))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(generated)]
    return rows.max(-1) - rows[np.arange(len(generated)), generated]


def assert_served_greedy(model, params, req, eps: float = 1e-4) -> None:
    """``req`` (a finished ``serving.Request``) holds ``max_new`` tokens,
    each the reference's greedy choice up to a gap of ``eps`` in the
    reference's own logits (a rounding tie; a quantized pool's noise
    where ``eps`` says so)."""
    assert len(req.generated) == req.max_new, (
        req.rid, len(req.generated), req.max_new)
    gaps = served_gaps(model, params, req.prompt, req.generated)
    assert float(gaps.max()) <= eps, (
        f"rid {req.rid}: served tokens {list(req.generated)} lie "
        f"{gaps.tolist()} below the forward reference's best (eps {eps})")
