"""One process, one cell, one run: build -> warm up -> measure ->
compare with the reference -> the contract's last line."""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

from benchmark.harness import correct, driver, loadgen, metrics, program
from benchmark.harness.program import HarnessFailure


#: how much of a traced run's window the profiler records (its end)
TRACE_SECONDS = 3.0


def say(**kw) -> None:
    print(json.dumps(kw), flush=True)


def device_record(devices, all_devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(all_devices)}


def memory_peak(devices):
    """Peak bytes in use on the fullest chip, or None off-chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def memory_now(devices) -> dict:
    """In use and peak so far on the first chip (None off-chip)."""
    st = devices[0].memory_stats() or {}
    return {"in_use": st.get("bytes_in_use"),
            "peak": st.get("peak_bytes_in_use")}


def run_cell(spec, workload: str, seed: int, seconds: float, trace: bool,
             *, t_start: float, rehearse: bool = False,
             break_program=None) -> int:
    """Returns the process's exit code; prints the result line last.

    ``break_program(prog)`` is for the harness's own tests: it is called
    on the built program before warm-up, to break the timed path
    underneath a run that is otherwise whole."""
    import jax

    cell = spec.cell(workload)
    all_devices = jax.devices()
    platform = all_devices[0].platform
    if platform != "tpu" and not rehearse:
        print(f"benchmark: FAIL: JAX's first device is {platform!r} "
              f"({all_devices[0].device_kind}), not a TPU "
              "(--rehearse runs the control flow off-chip and reports "
              "no metric)", flush=True)
        return 2
    if len(all_devices) < cell.chips:
        print(f"benchmark: FAIL: cell {workload} needs {cell.chips} "
              f"chip(s), JAX sees {len(all_devices)}", flush=True)
        return 2
    peaks = None if rehearse else spec.peaks(all_devices[0].device_kind)

    with tempfile.TemporaryDirectory() as store:
        program.hermetic_tuning(store)
        cache = program.enable_compile_cache()
        program.programs_lowered()
        say(workload=workload, seed=seed, seconds=seconds, trace=trace,
            rehearse=rehearse, compile_cache=cache, jax=jax.__version__,
            device=device_record(all_devices[:cell.chips], all_devices))
        try:
            return _run(spec, cell, seed, seconds, trace, t_start,
                        rehearse, peaks, all_devices, break_program)
        except HarnessFailure as e:
            print(f"benchmark: FAIL: {e}", flush=True)
            return 1


def _run(spec, cell, seed, seconds, trace, t_start, rehearse, peaks,
         all_devices, break_program) -> int:
    import jax

    cfg, mix = cell.config, cell.mix
    sizes = cfg["as_run"]
    t0 = time.perf_counter()
    prog = program.build(cfg, mix, cell.chips, seed)
    if break_program is not None:
        break_program(prog)
    eng = prog.engine
    t_build = time.perf_counter() - t0
    mem_build = memory_now(prog.devices)
    lowered_build = program.programs_lowered()
    warm = program.warm_up(eng, sizes["vocab"])
    program.check_health(eng)
    say(build_s=t_build, warm_up_s=warm["seconds"],
        block_q_rungs=warm["rungs"], memory_after_build=mem_build,
        memory_after_warm_up=memory_now(prog.devices),
        programs_lowered={"build": lowered_build,
                          "warm_up": program.programs_lowered()
                          - lowered_build})

    rate = float(cell.load["rate_rps"])
    arrivals = loadgen.generate(mix, rate, seconds, seed, sizes["vocab"])
    say(offered=loadgen.offered(arrivals), rate_rps=rate,
        knee_rps=cell.load.get("knee_rps"))

    span, tracer, trace_dir = driver.no_span, None, None
    if trace:
        trace_dir = os.path.join(spec.root, ".profiles", "bench", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        span = jax.profiler.TraceAnnotation
        # the LAST seconds of the window: stopping the profiler blocks
        # the loop for seconds, and there no request falls due in it
        span_s = min(TRACE_SECONDS, seconds)
        # without the profiler's Python tracer: it records every Python
        # call of the host loop (3k events an engine step) and the
        # device waits while it does, 1.4-1.9 ms a step (PERF.md §6, PR
        # 25). Spans are TraceAnnotations and need the host tracer only
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        tracer = (seconds - span_s, seconds,
                  lambda: jax.profiler.start_trace(
                      trace_dir, profiler_options=options),
                  jax.profiler.stop_trace)

    setup_s = time.perf_counter() - t_start
    win = driver.serve(eng, arrivals, seconds, float(mix["drain_s"]),
                       span=span, tracer=tracer)
    program.check_health(eng)
    if win.counters["programs_lowered"]:
        raise HarnessFailure(
            f"{win.counters['programs_lowered']} program(s) were lowered "
            "inside the measured window: warm-up missed a shape")
    devices = prog.devices
    mem_peak = memory_peak(devices)
    failed = metrics.failures(win, sizes["vocab"])
    e2e = metrics.end_to_end(win, setup_s)
    walls = sorted((te - ts, ts) for ts, te, *_ in win.steps)
    say(window={"closed_at_s": win.closed_at,
                **{k: v for k, v in win.counters.items()
                   if v or not k.startswith("stats.")},
                "profiler_stall_s": win.stalled,
                "longest_steps_ms_at_s": [
                    [round(1e3 * w, 1), round(ts, 2)]
                    for w, ts in walls[-3:]]},
        samples={"ttft": len(win.arrivals) - sum(
                     1 for a in win.arrivals if not a.token_times),
                 "itl": sum(max(len(a.token_times) - 1, 0)
                            for a in win.arrivals)},
        beside={k: e2e.get(k) for k in
                ("ttft_p95_ms", "ttft_p50_ms", "drain_tok_s")},
        failed=[list(f) for f in failed[:8]])

    # ---- correct: after the window, the program's state freed first
    t0 = time.perf_counter()
    tol = cfg["tolerance"]
    picked = correct.sample(win.arrivals, seed, int(tol["sample"]))
    prog.drop_state()
    ok, rows = False, []
    if picked:
        masters = prog.masters(seed)
        gaps = correct.served_gaps(
            prog.reference.logits_at, masters, sizes, picked,
            int(mix["output"]["max"]))
        read = correct.numbers(gaps["program"])
        ok, rows = correct.decide(read, tol)
        del masters
        say(compared={
            "requests": [a.rid for a in picked],
            "served_tokens": int(len(gaps["program"])),
            "longest_sequence": len(picked[0].prompt) + picked[0].max_new,
            "reference_top1_served": float(gaps["agree"].mean()),
            "reference_s": time.perf_counter() - t0,
            "numbers": [{"name": n, "value": v, "limit": lim, "ok": k}
                        for n, v, lim, k in rows]})
    is_correct = bool(ok and not failed)

    device = device_record(devices, all_devices)
    device["memory_peak_bytes"] = mem_peak
    line = {"correct": is_correct, "attempted": len(win.arrivals),
            "failed": len(failed), "metrics": {}, "device": device}
    if rehearse:
        # a rehearsal proves control flow; it never carries a metric
        line["rehearsal"] = True
        say(**line)
        return 0

    if not trace:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                line["metrics"][m["name"]] = {
                    "value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from benchmark.harness import trace as tracelib

        summary = tracelib.TraceSummary.from_file(
            tracelib.newest_xplane(trace_dir),
            spans=tracelib.known_spans(spec.bench))
        rec = metrics.layer_record(win, summary, peaks, cell)
        line["metrics"] = metrics.per_layer(cell, rec)
        device["busy_s"] = summary.busy_seconds()
        device["window_s"] = win.traced[1] - win.traced[0]
        line["breakdown"] = {"device_ops": summary.top_ops(10),
                             "idle_gaps": summary.idle_by_host_span(10)}
        if device["busy_s"] <= 0:
            raise HarnessFailure("the trace holds no device operation")
    say(**line)
    return 0
