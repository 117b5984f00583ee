"""Preemption: self time of the preempt spans (PostFilter preemption
dry-runs and evictions) per cycle of the window (ms)."""

from benchmark import cycle_spans

NAMES = {"preempt"}


def read(run):
    return cycle_spans.self_ms_per_cycle(run, NAMES)
