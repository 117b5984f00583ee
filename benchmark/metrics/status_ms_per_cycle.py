"""Unschedulable status: self time of the status and diagnose spans
(error handlers, PodScheduled=False diagnosis and writes, gang status)
per cycle of the window (ms)."""

from benchmark import cycle_spans

NAMES = {"status", "diagnose"}


def read(run):
    return cycle_spans.self_ms_per_cycle(run, NAMES)
