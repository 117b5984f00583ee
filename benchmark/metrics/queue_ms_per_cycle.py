"""Queue and pack: self time of the queue, prefilter, plan and
reservation_prepass spans per cycle of the window (ms)."""

from benchmark import cycle_spans

NAMES = {"queue", "prefilter", "plan", "reservation_prepass"}


def read(run):
    return cycle_spans.self_ms_per_cycle(run, NAMES)
