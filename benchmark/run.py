#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Build -> warm up -> measure for ``--seconds`` in a wall-clock open loop
-> compare the served tokens with the plain reference -> print the
result as the last line of standard output (one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a run of its own.

Exits non-zero and prints no result line when JAX's first device is not
a TPU (unless ``--rehearse``: control flow only, no metric), when the
device is not in ``peaks.json``, when the engine degraded or recorded a
failure, or when any program was lowered inside the measured window.
See benchmark/README.md.
"""

import time

T_START = time.perf_counter()      # process start, for setup_s

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off-chip: control flow only, no metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark.harness.cell_run import run_cell
    from benchmark.harness.spec import Spec

    return run_cell(Spec(ROOT), args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START,
                    rehearse=args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
