"""Dispatch policy: time the host spent in blocking readbacks outside
overlap_wait (the sync spans) per cycle of the window (ms)."""

from benchmark import cycle_spans


def read(run):
    return cycle_spans.duration_ms_per_cycle(run, "sync")
