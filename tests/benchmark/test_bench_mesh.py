"""The mesh cell (mesh-50k.steady) at a small size on virtual CPU devices,
and the reader of its dispatch share.

- ``mesh_dispatch_share`` on a synthetic span tree: kernel spans by their
  ``shards`` attribute, and nothing read from a program without it.
- mesh-50k shrunk by ``bench_sizes.small`` through the harness with the
  mesh pinned to 4 devices: every count of the reference reads 0.
- One closed batch on that world binds byte for byte as the single-device
  scheduler does at the same seed (as tests/test_mesh_dispatch.py pins for
  the program's own churn worlds).
"""

import time

import pytest

from benchmark import faults
from benchmark import harness as H
from benchmark import reference
from benchmark import run as R
from benchmark import world as W
from koordinator_tpu.obs import Span

from bench_sizes import small

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


@pytest.mark.parametrize("shards,want", [
    ((4, 4, 4), 100.0),
    ((4, 1, 4, 0), 50.0),
    ((1, 1), 0.0),
])
def test_mesh_dispatch_share_on_a_synthetic_tree(shards, want):
    read = R.reader_for("mesh_dispatch_share.mesh")
    roots = [_sp("cycle", _sp("kernel", _sp("upload", shards=n), shards=n))
             for n in shards]
    assert read(_Run(roots)) == pytest.approx(want)


def test_mesh_dispatch_share_of_a_program_without_shards():
    read = R.reader_for("mesh_dispatch_share.mesh")
    assert read(_Run([_sp("cycle", _sp("kernel"), _sp("kernel"))])) is None
    assert read(_Run([])) is None


def _pinned(n):
    """The harness's Scheduler with its mesh pinned to ``n`` devices."""
    return faults.Fault(f"mesh{n}", scheduler_kwargs={"mesh": n})


def test_mesh_cell_small_on_four_devices_is_correct(cpu_devices):
    keep = {}
    res = R.run("mesh-50k.steady", 4242, 2.0, False, need_chip=False,
                cache_dir="", cfg_override=small, fault=_pinned(4),
                keep=keep)
    assert res["correct"], res["checks"]
    assert {k: v["value"] for k, v in res["checks"].items()
            if k != "score_gap"} == {k: 0 for k in reference.LIMITS
                                     if k != "score_gap"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert keep["log"].binds


def _closed_batch(n):
    """One burst of the small mesh-50k world through a scheduler pinned to
    ``n`` devices (``"off"``: one), cycled until a cycle binds none."""
    bench = R.load_json(R.os.path.join(R.ROOT, "BENCHMARK.json"))
    _cell, cfg, traffic = R.find_cell(bench, "mesh-50k.steady")
    cfg, traffic = small(cfg, traffic)
    h = H.Cell(cfg, traffic, 31337, 1.0, scheduler_kwargs={"mesh": n})
    assert (h.sched.mesh.devices.size if h.sched.mesh is not None
            else 1) == (1 if n == "off" else n)
    jobs = W.draw_jobs(cfg, cfg["pod_mix"], 120, salt=7,
                       leaf_quotas=W.leaf_quota_names(h.world.quotas),
                       gangs=h.world.gangs, prefix="c")
    h.writer.add_job([p for j in jobs for p in j], time.perf_counter())
    for _ in range(6):
        if not h.cycle("window")["bound"]:
            break
    return [(name, node, status) for _t, name, node, status in h.log.binds]


def test_mesh_cell_closed_batch_binds_as_one_device(cpu_devices):
    mesh, one = _closed_batch(4), _closed_batch("off")
    assert len(mesh) > 60
    assert mesh == one
