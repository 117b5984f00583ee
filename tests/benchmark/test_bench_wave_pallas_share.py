"""The reader of ``wave_pallas_share.backlog`` on synthetic span trees:
chained ``kernel`` spans by their ``backend`` attribute, single-round
spans left out, and nothing read from a program whose chained spans
carry no ``backend``."""

import pytest

from benchmark import run as R
from koordinator_tpu.obs import Span

_ids = iter(range(1, 10**6))


def _sp(name, *children, **attrs):
    sp = Span(name=name, trace_id=0, span_id=next(_ids), parent_id=None,
              start_unix=0.0, start_mono=0.0, duration_seconds=0.1,
              attributes={k: str(v) for k, v in attrs.items()})
    sp.children = list(children)
    return sp


class _Run:
    def __init__(self, spans):
        self.spans = spans


@pytest.mark.parametrize("backends,want", [
    (("pallas", "pallas"), 100.0),
    (("pallas", "xla", "xla", "pallas"), 50.0),
    (("xla",), 0.0),
])
def test_wave_pallas_share_on_a_synthetic_tree(backends, want):
    read = R.reader_for("wave_pallas_share.backlog")
    roots = [_sp("cycle", _sp("kernel", _sp("upload"), overlap=1,
                              waves=8, backend=b))
             for b in backends]
    # a single-round dispatch is no chained wave: it never counts
    roots.append(_sp("cycle", _sp("kernel", backend="xla")))
    assert read(_Run(roots)) == pytest.approx(want)


def test_wave_pallas_share_of_a_program_without_backend():
    read = R.reader_for("wave_pallas_share.backlog")
    assert read(_Run([_sp("cycle", _sp("kernel", overlap=1))])) is None
    assert read(_Run([_sp("cycle", _sp("kernel", backend="pallas"))])) \
        is None
    assert read(_Run([])) is None
