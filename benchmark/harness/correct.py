"""How ``correct`` is decided: the served tokens against the plain
reference, on requests the timed window itself finished.

Once the window has closed, a sample of its finished requests (drawn
from the seed, the longest always in it) goes through the reference
once each: the prompt with the tokens the engine served, teacher-
forced. At every served position the reference has a best token; the
number read is how far the SERVED token's reference logit lies below
that best one (0 where the engine served the reference's own choice).
Greedy decoding in a lower precision than the configuration states
serves tokens that lie further below, and that is what the limits in
the configuration file's ``tolerance`` reject.

The same readings for the CONTROL — the reference itself computed in
the next lower precision (``logits_at(bits=...)``), put in
the program's place — come from ``served_gaps(control_bits=...)``; the benchmark's own
runs never compute them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: sequences are padded to a multiple of this many tokens, and served
#: rows to the mix's longest output: a handful of compiled shapes
PAD = 512


def sample(arrivals: list, seed: int, k: int) -> list:
    """``k`` finished requests drawn from the seed, the longest (prompt
    + served tokens) always among them."""
    done = [a for a in arrivals
            if a.request is not None and a.request.done
            and len(a.request.generated) > 0]
    if not done:
        return []
    longest = max(done, key=lambda a: (len(a.prompt) + a.max_new, -a.rid))
    rest = [a for a in done if a is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _gap(ref, chosen):
    """How far each ``chosen`` token's logit lies below the row's best."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(ref, axis=-1).astype(jnp.int32)


def _padded(prompt, served, max_rows: int):
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    n = -(-len(seq) // PAD) * PAD
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return (np.pad(seq, (0, n - len(seq))).astype(np.int32),
            np.pad(rows, (0, max_rows - len(rows))).astype(np.int32),
            np.pad(np.asarray(served, np.int32),
                   (0, max_rows - len(served))))


def served_gaps(logits_at, masters, sizes: dict, picked: list,
                max_rows: int, control_bits=None) -> dict:
    """Per served token of ``picked``: the program's gap, whether it is
    the reference's own choice and, with ``control_bits``, the gap of
    the token the lower precision puts first at the same position.
    ``logits_at`` is the plain reference of the configuration's
    ``model`` module."""
    prog, agree, ctl = [], [], []
    for a in picked:
        served = a.request.generated
        seq, rows, toks = _padded(a.prompt, served, max_rows)
        ref = logits_at(masters, sizes, seq, rows)
        gap, best = _gap(ref, jnp.asarray(toks))
        n = len(served)
        prog.append(np.asarray(gap)[:n])
        agree.append(np.asarray(best)[:n] == toks[:n])
        if control_bits:
            low = logits_at(masters, sizes, seq, rows, bits=control_bits)
            gap_c, _ = _gap(ref, jnp.argmax(low, axis=-1))
            ctl.append(np.asarray(gap_c)[:n])
    out = {"program": np.concatenate(prog), "agree": np.concatenate(agree)}
    if control_bits:
        out["control"] = np.concatenate(ctl)
    return out


def numbers(gaps: np.ndarray) -> dict:
    """The numbers a tolerance may name, from per-token gaps."""
    return {
        "gap_max": float(np.max(gaps)),
        "gap_p99": float(np.percentile(gaps, 99)),
        "gap_p90": float(np.percentile(gaps, 90)),
        "gap_mean": float(np.mean(gaps)),
    }


def decide(read: dict, tolerance: dict) -> tuple:
    """``(correct, [(name, value, limit, ok), ...])`` for the numbers
    the configuration's ``tolerance`` names."""
    rows = []
    for name, limit in tolerance["limits"].items():
        value = read[name]
        rows.append((name, value, float(limit),
                     bool(np.isfinite(value) and value <= limit)))
    return all(r[3] for r in rows), rows
