"""Profiling tools: per-process traces gathered into one timeline.

Reference: utils.group_profile (python/triton_dist/utils.py:417-502) —
torch.profiler per rank → chrome traces → gather to rank0 over
torch.distributed → pid/tid remap by rank*1e8 → one merged, gzipped
timeline (merge machinery :282-414).

TPU re-design: ``group_profile`` wraps ``jax.profiler.trace`` writing
one subdir per process (the profiler is already whole-device — every
TPU op lands in the trace, no per-kernel hooks needed), and
``merge_chrome_traces`` performs the same pid-offset merge over any
chrome-format ``*.trace.json(.gz)`` the runs produced. On multi-host
deployments each host writes to the shared log dir when one exists;
pods WITHOUT shared storage run ``gather_traces`` first — an IN-BAND
gather of every host's trace files to process 0 (≡ the reference's
torch.distributed gather, utils.py:417-502). ``merge_chrome_traces``
refuses (loudly) to produce a partial merge when it can see that other
processes' traces are missing.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import pathlib

import jax

PID_STRIDE = 10**8   # ≡ the reference's rank*1e8 remap (utils.py:330)


@contextlib.contextmanager
def group_profile(log_dir=".profiles", *, enabled: bool = True,
                  create_perfetto_trace: bool = False):
    """Trace the enclosed block on every process (≡ group_profile,
    utils.py:417). Writes ``<log_dir>/process-<i>/``."""
    if not enabled:
        yield None
        return
    path = pathlib.Path(log_dir) / f"process-{jax.process_index()}"
    path.mkdir(parents=True, exist_ok=True)
    # without the profiler's Python tracer: it records every Python call
    # of the host loop and the device waits while it does (1.4-1.9 ms a
    # serving step, PERF.md). The program opens its own host spans
    # (``engine.<phase>``), which need the host tracer only.
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(
        str(path), create_perfetto_trace=create_perfetto_trace,
        profiler_options=options,
    ):
        yield path


def _load_trace(fname):
    op = gzip.open if fname.endswith(".gz") else open
    with op(fname, "rt") as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def gather_traces(log_dir=".profiles"):
    """IN-BAND gather of every process's trace directory to process 0
    (≡ the reference gathering per-rank chrome traces to rank 0 over
    torch.distributed, utils.py:417-502) — for multi-host runs WITHOUT
    a shared log dir. Every process tars its ``process-<i>`` subdir and
    the blobs ride ``multihost_utils.process_allgather`` (padded to the
    max size — trace volume, not a hot path); process 0 unpacks all of
    them under its ``log_dir`` so :func:`merge_chrome_traces` sees the
    full set. Single-process: no-op. Returns ``log_dir``."""
    if jax.process_count() == 1:
        return pathlib.Path(log_dir)
    import io
    import tarfile

    import numpy as np
    from jax.experimental import multihost_utils

    log_dir = pathlib.Path(log_dir)
    mine = log_dir / f"process-{jax.process_index()}"
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w:gz") as tar:
        if mine.is_dir():
            tar.add(mine, arcname=mine.name)
    blob = np.frombuffer(buf.getvalue(), np.uint8)
    sizes = np.asarray(
        multihost_utils.process_allgather(np.array([blob.size], np.int64))
    ).reshape(-1)
    cap = int(sizes.max())
    padded = np.zeros((cap,), np.uint8)
    padded[: blob.size] = blob
    blobs = np.asarray(multihost_utils.process_allgather(padded))
    if jax.process_index() == 0:
        for i, (b, s) in enumerate(zip(blobs, sizes)):
            if i == jax.process_index() or s == 0:
                continue
            with tarfile.open(
                fileobj=io.BytesIO(b[: int(s)].tobytes()), mode="r:gz"
            ) as tar:
                tar.extractall(log_dir, filter="data")
    return log_dir


def merge_chrome_traces(log_dir=".profiles", out="merged_trace.json.gz"):
    """Merge every chrome trace under ``log_dir`` into one timeline,
    remapping pids by process index (≡ utils.py:282-414). Returns the
    output path, or None if no traces were found.

    On a multi-process run the merge REFUSES to cover only the local
    host's traces: if fewer process dirs are present than
    ``jax.process_count()``, it raises and names the fix (shared log
    dir, or :func:`gather_traces` first) instead of silently producing
    a partial timeline that reads as complete."""
    log_dir = pathlib.Path(log_dir)
    merged = []
    found = False
    procs_seen = set()
    for proc_dir in sorted(log_dir.glob("process-*")):
        try:
            idx = int(proc_dir.name.split("-")[1])
        except (IndexError, ValueError):
            continue
        pats = ("**/*.trace.json.gz", "**/*.trace.json", "**/trace.json.gz")
        files = sorted({f for p in pats for f in glob.glob(
            str(proc_dir / p), recursive=True)})
        for fname in files:
            found = True
            procs_seen.add(idx)
            for ev in _load_trace(fname):
                ev = dict(ev)
                if "pid" in ev:
                    try:
                        ev["pid"] = int(ev["pid"]) + idx * PID_STRIDE
                    except (TypeError, ValueError):
                        pass
                merged.append(ev)
    if not found:
        return None
    if jax.process_count() > 1 and len(procs_seen) < jax.process_count():
        raise RuntimeError(
            f"merge_chrome_traces: traces found for processes "
            f"{sorted(procs_seen)} but this run has "
            f"{jax.process_count()} — no shared log dir? Run "
            "tools.gather_traces(log_dir) before merging (in-band "
            "gather to process 0), or point every host at shared "
            "storage. Refusing to write a partial merge that would "
            "read as complete."
        )
    out_path = log_dir / out
    with gzip.open(out_path, "wt") as f:
        json.dump({"traceEvents": merged}, f)
    return out_path
