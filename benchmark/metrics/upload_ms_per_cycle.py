"""Upload: time of the upload spans (DeviceSnapshot upload of the packed
and side arrays) per cycle of the window (ms)."""

from benchmark import cycle_spans


def read(run):
    return cycle_spans.duration_ms_per_cycle(run, "upload")
