"""The readers of the cycle's own spans (queue, upload, sync, preempt,
status, uninstrumented) on a synthetic span tree: self time with children,
durations, the division by the window's cycles, and a program that
predates the spans."""

import pytest

from benchmark import run as R
from koordinator_tpu.obs import Span

_ids = iter(range(1, 10**6))


def _sp(name, dur, *children):
    sp = Span(name=name, trace_id=0, span_id=next(_ids), parent_id=None,
              start_unix=0.0, start_mono=0.0, duration_seconds=dur)
    sp.children = list(children)
    return sp


def _cycle():
    """One 0.9 s cycle; its children cover 0.88 s, so 20 ms is the root's
    own time."""
    return _sp(
        "cycle", 0.9,
        _sp("queue", 0.05, _sp("diagnose", 0.01)),
        _sp("prefilter", 0.01),
        _sp("reservation_prepass", 0.03),
        _sp("plan", 0.02),
        _sp("snapshot", 0.2),
        _sp("kernel", 0.3, _sp("upload", 0.04), _sp("sync", 0.1)),
        _sp("replay_drain", 0.13,
            _sp("wave_replay", 0.12, _sp("preempt", 0.05))),
        _sp("preempt", 0.06),
        _sp("status", 0.08, _sp("diagnose", 0.03),
            _sp("store_flush", 0.01)),
    )


class _Run:
    def __init__(self, spans):
        self.spans = spans


# per cycle, in ms: self times add the spans' own time less their children
WANT = {
    "queue_ms_per_cycle": 40 + 10 + 30 + 20,
    "upload_ms_per_cycle": 40,
    "sync_ms_per_cycle": 100,
    "preempt_ms_per_cycle": 50 + 60,
    "status_ms_per_cycle": 40 + 30 + 10,
    "uninstrumented_ms_per_cycle": 20,
}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_a_synthetic_tree(metric):
    read = R.reader_for(metric + ".steady")
    assert read(_Run([_cycle(), _cycle()])) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_of_no_cycles_reads_nothing(metric):
    assert R.reader_for(metric + ".backlog")(_Run([])) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_a_program_without_the_spans(metric):
    """A cycle tree from before these spans (no queue span): every reader
    but the root's own time leaves its metric out, and none raises."""
    old = _sp("cycle", 1.0, _sp("snapshot", 0.3),
              _sp("kernel", 0.5), _sp("bind", 0.1))
    got = R.reader_for(metric + ".steady")(_Run([old]))
    if metric == "uninstrumented_ms_per_cycle":
        assert got == pytest.approx(100)
    else:
        assert got is None


def test_instrumented_program_without_a_sync_reads_zero():
    """A window whose cycles opened no sync span still reports the metric
    (0), so a traced run's line always carries it."""
    cyc = _sp("cycle", 0.5, _sp("queue", 0.1), _sp("kernel", 0.2))
    assert R.reader_for("sync_ms_per_cycle.backlog")(_Run([cyc])) == 0.0
