"""The ``backlog`` arrivals kind: every request due when the window
opens.

A mix names it as ``"arrivals": {"kind":
"benchmark.arrivals.backlog:gaps"}``. The generator
(``harness/loadgen.py:generate``) fixes the run's request count as
``round(rate_rps * seconds)`` and asks the kind for the ``n - 1`` gaps
between consecutive due times; here they are all 0, so the first
request and every other are due at t = 0 and the rate only sets how
many there are. For a fixed set replayed at saturation: what the
program completes inside the window is then its own speed, not an
offered rate.
"""

from __future__ import annotations

import numpy as np


def gaps(spec: dict, n: int, rate_rps: float) -> np.ndarray:
    """``n`` gaps of 0 seconds (signature of ``loadgen.poisson_gaps``:
    the mix's ``arrivals`` object, the number of gaps, the cell's
    rate, which is not read)."""
    return np.zeros((int(n),), np.float64)
