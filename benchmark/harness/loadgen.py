"""The one general traffic generator: a mix file's parameters + a rate
+ a window + ``--seed`` -> the requests of one run, due in SECONDS.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps — the quantiles of the mix's distributions at the
run's request count — each in a uniformly random order of its own drawn
from the seed, with other token ids. So every run of a cell offers the
same work (the same tokens to prefill and to generate, the last request
due at the same time), and which request meets which, and where the
short gaps fall side by side, is the seed's. Nothing shapes the order:
exponential gaps in a random order are a Poisson stream conditioned on
its count and span, bursts included (``tests``: the count of arrivals
in a 5 s stretch varies from seed to seed as a Poisson stream's does).
(Lengths and gaps drawn anew per seed would make the seed change the
amount of work: the spread between seeds would measure the draw.)

Mix parameters (``mixes/<name>.json``):

    arrivals   "poisson": exponential gaps at the cell's rate
    prompt     {"dist": "lognormal", "median", "sigma", "min", "max"}
    output     same keys
    token_ids  "uniform": every id of the vocabulary alike
    drain_s    seconds after the window in which a request may finish

Each of ``arrivals``, ``dist`` and ``token_ids`` names a kind below, or
— for a mix that needs another — a function in a file a later PR adds
under the benchmark's directory, as ``"package.module:function"`` with
the signature of the built-in of its table. ``arrivals`` may be an
object ``{"kind": ..., <parameters>}`` where the kind takes parameters.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

from benchmark.harness.spec import named

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Arrival:
    """One request of a run, with the driver's stamps (seconds from the
    window's start; ``None`` until it happens)."""

    rid: int
    due: float
    prompt: np.ndarray              # (L,) int32 token ids
    max_new: int
    submitted: float | None = None  # handed to the engine
    admitted: float | None = None   # start of the step that gave a slot
    token_times: list = dataclasses.field(default_factory=list)
    request: object = None          # the program's request object


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantiles of a clipped lognormal, ascending."""
    z = np.array([_NORMAL.inv_cdf(float(p)) for p in _quantile_points(n)])
    x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])) \
        .astype(np.int64)


def poisson_gaps(spec: dict, n: int, rate_rps: float) -> np.ndarray:
    """The ``n`` quantiles of the exponential gap at ``rate_rps``
    (seconds, ascending); they sum to about ``n / rate_rps``."""
    return -np.log1p(-_quantile_points(n)) / rate_rps


def uniform_token_ids(spec: dict, rng, lens, vocab: int) -> list:
    return [rng.integers(0, vocab, (int(n),)).astype(np.int32)
            for n in lens]


LENGTHS = {"lognormal": lognormal_lengths}      # (spec, n) -> ascending
ARRIVALS = {"poisson": poisson_gaps}            # (spec, n, rate) -> gaps
TOKEN_IDS = {"uniform": uniform_token_ids}      # (spec, rng, lens, vocab)


def resolve(kind: str, table: dict):
    """A built-in of ``table`` by name, or ``"package.module:function"``
    from a file added beside the harness."""
    if kind in table:
        return table[kind]
    if ":" not in kind:
        raise ValueError(f"unknown kind {kind!r} (has: "
                         f"{', '.join(table)}, or 'package.module:function')")
    return named(kind)


def _kind(entry) -> tuple:
    """``"name"`` or ``{"kind": "name", ...}`` -> (name, parameters)."""
    if isinstance(entry, dict):
        return entry["kind"], entry
    return entry, {}


def generate(mix: dict, rate_rps: float, seconds: float, seed: int,
             vocab: int) -> list:
    """The run's requests, ordered by due time. Their number is fixed
    by rate and window (``round(rate * seconds)``, at least 1)."""
    n = max(1, int(round(rate_rps * seconds)))
    rng = np.random.default_rng(int(seed))
    prompt_len, out_len = (
        rng.permutation(resolve(mix[k]["dist"], LENGTHS)(mix[k], n))
        for k in ("prompt", "output"))
    # the first request is due at 0 and n - 1 gaps follow: the window
    # starts with traffic, and the last request is due at the same
    # time whatever the seed
    kind, params = _kind(mix["arrivals"])
    gap = rng.permutation(resolve(kind, ARRIVALS)(params, n - 1, rate_rps))
    due = np.concatenate([[0.0], np.cumsum(gap)])
    kind, params = _kind(mix.get("token_ids", "uniform"))
    prompts = resolve(kind, TOKEN_IDS)(params, rng, prompt_len, vocab)
    return [
        Arrival(rid=i, due=float(due[i]), max_new=int(out_len[i]),
                prompt=prompts[i])
        for i in range(n)
    ]


def worst_case_tokens(mix: dict) -> int:
    """Longest sequence (prompt + output) the mix can produce."""
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def offered(arrivals: list) -> dict:
    """What one run offers, for the record line."""
    return {
        "requests": len(arrivals),
        "prompt_tokens": int(sum(len(a.prompt) for a in arrivals)),
        "output_tokens": int(sum(a.max_new for a in arrivals)),
        "last_due_s": float(max(a.due for a in arrivals)),
    }
