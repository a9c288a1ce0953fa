#!/usr/bin/env python3
"""The builder's readings, taken once on the chip: NOT run by the
driver, and never part of a benchmark run.

One process, one set-up, then any of:

``--rates a,b,c``        the knee: a window at each rising rate (once
                         per ``--sweep-seeds`` seed), the engine
                         drained between them. A rate is sustained
                         while, when its window closes, fewer than 1 in
                         20 of the requests offered still wait for a
                         slot, on every seed; the knee is the highest
                         sustained rate before the first that is not,
                         and the sweep stops there.
``--limit-seeds s,...``  the readings a ``tolerance`` is set from: for
                         each seed its own weights, a short window at
                         ``--rate`` (default: the cell file's), then the
                         program's gaps and the CONTROL's (the reference
                         at the configuration's ``control_bits``) on the
                         same prompts and served tokens.
``--trace-probe``        a traced stretch of a 20 s window; the trace's
                         planes, lines, heaviest events, stat names and
                         scope paths are written to ``chiprun_out/``
                         with the ``.xplane.pb`` (``--python-tracer 0``:
                         without the profiler's Python tracer).

    chiprun -- python3 benchmark/sweep.py --workload dsmoe16b.chat \\
        --rates 6,8,10,12,14,17,20 --limit-seeds 1,2,3 --trace-probe
"""

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.harness import (  # noqa: E402
    correct, driver, loadgen, metrics, program,
)
from benchmark.harness.spec import Spec  # noqa: E402


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def one_window(eng, mix, rate, seconds, seed, vocab, drain_s=120.0):
    arr = loadgen.generate(mix, rate, seconds, seed, vocab)
    win = driver.serve(eng, arr, seconds, drain_s)
    program.check_health(eng)
    e2e = metrics.end_to_end(win, 0.0)
    waiting = sum(1 for a in arr
                  if a.admitted is None or a.admitted > seconds)
    unfinished = sum(1 for a in arr
                     if not a.token_times or a.token_times[-1] > seconds)
    s = metrics.series(win)
    return win, {
        "rate_rps": rate, "offered": len(arr),
        "waiting_at_close": waiting, "unfinished_at_close": unfinished,
        "drained_at_s": win.closed_at,
        "failed": len(metrics.failures(win, vocab)),
        "lowered": win.counters["programs_lowered"],
        "ttft_p50_ms": e2e.get("ttft_p50_ms"),
        "ttft_p95_ms": e2e.get("ttft_p95_ms"),
        "ttft_p90_ms": metrics.percentile(s["ttft_ms"], 90),
        "ttft_p99_ms": metrics.percentile(s["ttft_ms"], 99),
        "ttft_mean_ms": sum(s["ttft_ms"]) / len(s["ttft_ms"]),
        "ttft_tail10_ms": (lambda t: sum(t) / len(t))(
            sorted(s["ttft_ms"])[-max(1, len(s["ttft_ms"]) // 10):]),
        "itl_p99_ms": metrics.percentile(s["itl_ms"], 99),
        "itl_p50_ms": e2e.get("itl_p50_ms"),
        "itl_p95_ms": e2e.get("itl_p95_ms"),
        "out_tok_s": e2e.get("out_tok_s"),
        "drain_tok_s": e2e.get("drain_tok_s"),
        "step_ms_p50": metrics.percentile(s["step_device_ms"], 50),
        "host_ms_per_step": metrics.read_layer_metric(
            {"series": s}, {"reader": "span_minus_counter", "args": {
                "span": "step_wall_ms", "counter": "step_device_ms"}}),
        "steps": win.counters["device_steps"],
        "deferrals": win.counters["deferrals"],
        "longest_step_ms": max(s["step_wall_ms"]),
        "steps_over_100ms": sum(1 for w in s["step_wall_ms"] if w > 100),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--rates", default="")
    ap.add_argument("--sweep-seeds", default="",
                    help="run every rate once per seed (default: --seed)")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--limit-seeds", default="")
    ap.add_argument("--limit-seconds", type=float, default=8.0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control-bits", default=None,
                    type=lambda v: v if v == "fp8" else int(v),
                    help="read another control than the configuration's")
    ap.add_argument("--trace-probe", action="store_true")
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1,
                    help="0: the probe's trace without the profiler's "
                         "Python tracer (what it costs the host loop)")
    ap.add_argument("--root", default=str(ROOT), help=argparse.SUPPRESS)
    args = ap.parse_args()

    import jax

    spec = Spec(args.root)
    cell = spec.cell(args.workload)
    cfg, mix, sizes = cell.config, cell.mix, cell.config["as_run"]
    vocab = sizes["vocab"]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    with tempfile.TemporaryDirectory() as store:
        program.hermetic_tuning(store)
        program.enable_compile_cache()
        program.programs_lowered()
        t0 = time.perf_counter()
        prog = program.build(cfg, mix, cell.chips, args.seed)
        eng = prog.engine
        t_build = time.perf_counter() - t0
        warm = program.warm_up(eng, vocab)
        say(device=str(jax.devices()[0].device_kind), build_s=t_build,
            warm_up_s=warm["seconds"], rungs=warm["rungs"],
            lowered=program.programs_lowered(),
            memory=[{k: (d.memory_stats() or {}).get(k) for k in
                     ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
                    for d in prog.devices])

        rate = args.rate or float(cell.load["rate_rps"])
        if args.rates:
            knee = None
            seeds = [int(x) for x in args.sweep_seeds.split(",") if x] \
                or [args.seed]
            for r in (float(x) for x in args.rates.split(",")):
                held = True
                for sd in seeds:
                    _, row = one_window(eng, mix, r, args.seconds, sd, vocab)
                    row["seed"] = sd
                    row["sustained"] = (
                        row["waiting_at_close"] < 0.05 * row["offered"])
                    held = held and row["sustained"]
                    say(sweep=row)
                if not held:
                    break
                knee = r
            if knee is not None:
                rate = 0.8 * knee
            say(knee_rps=knee, rate_rps=rate)

        tol = cfg["tolerance"]
        for seed in (int(x) for x in args.limit_seeds.split(",") if x):
            t0 = time.perf_counter()
            prog.load_weights(seed)
            win, row = one_window(eng, mix, rate, args.limit_seconds,
                                  seed, vocab)
            picked = correct.sample(win.arrivals, seed, int(tol["sample"]))
            eng.params = None
            masters = prog.masters(seed)
            t1 = time.perf_counter()
            gaps = correct.served_gaps(
                prog.reference.logits_at, masters, sizes, picked,
                int(mix["output"]["max"]),
                control_bits=args.control_bits or tol["control_bits"])
            del masters
            np.savez(out_dir / f"{cell.name}.gaps.{seed}.npz", **gaps)
            say(limits={
                "seed": seed, "rate_rps": rate, "offered": row["offered"],
                "failed": row["failed"], "lowered": row["lowered"],
                "ttft_p95_ms": row["ttft_p95_ms"],
                "served_tokens": int(len(gaps["program"])),
                "top1_served": float(gaps["agree"].mean()),
                "program": correct.numbers(gaps["program"]),
                "control": correct.numbers(gaps["control"]),
                "reference_and_control_s": time.perf_counter() - t1,
                "seed_s": time.perf_counter() - t0,
            })

        if args.trace_probe:
            from benchmark.harness import trace as tracelib

            prog.load_weights(args.seed)
            tdir = str(ROOT / ".profiles" / "bench" / "probe")
            shutil.rmtree(tdir, ignore_errors=True)
            span = jax.profiler.TraceAnnotation
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = args.python_tracer
            # long enough for the slots to fill as in a full run
            arr = loadgen.generate(mix, rate, 20.0, args.seed, vocab)
            win = driver.serve(
                eng, arr, 20.0, 60.0, span=span,
                tracer=(16.0, 18.0,
                        lambda: jax.profiler.start_trace(
                            tdir, profiler_options=options),
                        jax.profiler.stop_trace))
            path = tracelib.newest_xplane(tdir)
            size = os.path.getsize(path)
            with open(out_dir / f"{cell.name}.trace_describe.json",
                      "w") as f:
                json.dump(tracelib.describe(path), f, indent=1)
            if size < 48 << 20:
                shutil.copy(path, out_dir / f"{cell.name}.xplane.pb")
            summ = tracelib.TraceSummary.from_file(path)
            inside = metrics.series(win)["traced_steps"]
            say(trace_probe={
                "xplane_bytes": size, "traced": win.traced,
                "python_tracer": args.python_tracer,
                "traced_steps": len(inside),
                "idle_ms_per_step": {
                    k: 1e3 * v / len(inside)
                    for k, v in summ.idle_by_host_span(10)},
                "chips": summ.chips(), "busy_s": summ.busy_seconds(),
                "host_spans": sorted({"/".join(p[0])
                                      for p in summ.span_paths()}),
                "host_events": len(summ.host_events),
                "top_ops": summ.top_ops(10),
                "idle": summ.idle_by_host_span(10)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
