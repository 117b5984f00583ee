"""Whole served path: the cycle roots' own time, outside every child span,
per cycle of the window (ms). Read on any program with a cycle root."""

from benchmark import stats

NAMES = {"cycle"}


def read(run):
    if not run.spans:
        return None
    return 1000.0 * stats.self_seconds(run.spans, NAMES) / len(run.spans)
