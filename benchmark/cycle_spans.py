"""Per-cycle time of the scheduling cycle's own spans, for the readers of
the queue, upload, sync, preempt, status and uninstrumented metrics.

A program that opens no ``queue`` span (every cycle opens one) predates
these spans: the readers then return None and the metric is left out.
Imports nothing of the program."""

from __future__ import annotations

from benchmark import stats


def instrumented(roots) -> bool:
    return any(root.find("queue") is not None for root in roots)


def self_ms_per_cycle(run, names):
    """Summed self time of the spans named in ``names`` per cycle of the
    window (ms)."""
    if not run.spans or not instrumented(run.spans):
        return None
    return 1000.0 * stats.self_seconds(run.spans, names) / len(run.spans)


def duration_ms_per_cycle(run, name: str):
    """Summed duration of the spans named ``name`` per cycle of the
    window (ms)."""
    if not run.spans or not instrumented(run.spans):
        return None
    total = sum(sp.duration_seconds for root in run.spans
                for sp in root.find_all(name))
    return 1000.0 * total / len(run.spans)
