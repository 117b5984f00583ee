"""The default path's mesh choice (parallel/mesh.size_rule_shards): shard
the node axis over every chip only where the one-chip Pallas kernel cannot
hold the cluster's node axis. Pure arithmetic over the kernel's VMEM
estimate with v5e's budget, the start-up and resize-gate readings in
``Scheduler`` (the node bucket alone moves them), and the explicit
overrides (``KOORD_TPU_MESH``, ``Scheduler(mesh=...)``)."""

import json
import os
import time

import pytest

from koordinator_tpu.models.full_chain import (
    full_chain_vmem_bytes,
    node_axis_vmem_bytes,
)
from koordinator_tpu.ops import pallas_common
from koordinator_tpu.client.store import KIND_NODE, KIND_POD
from koordinator_tpu.parallel.mesh import size_rule_shards

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = pallas_common.vmem_budget_bytes("TPU v5 lite")


def _config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _config_vmem(name: str) -> int:
    """The size rule's reading of a benchmark configuration's fleet."""
    return node_axis_vmem_bytes(_config(name)["nodes"]["count"])


@pytest.mark.parametrize("config,chips,shards", [
    ("fullchain-5k", 4, 1),
    ("fullchain-5k", 1, 1),
    ("mesh-50k", 4, 4),
    ("mesh-50k", 1, 1),
])
def test_size_rule_on_a_v5e_host(config, chips, shards):
    assert size_rule_shards(_config_vmem(config), chips, V5E) == shards


def test_size_rule_off_tpu_stays_single_device():
    # no budget: the CPU (and any backend without the Pallas kernel)
    assert pallas_common.default_vmem_budget() is None
    assert size_rule_shards(_config_vmem("mesh-50k"), 8, None) == 1


def _world(nodes: int, small: bool = True):
    """A benchmark world of fullchain-5k's shapes in a fresh store; small:
    ``nodes`` nodes and few quotas, gangs and running pods."""
    from benchmark import objects as O
    from benchmark import world as W

    cfg = _config("fullchain-5k")
    if small:
        cfg["nodes"]["count"] = nodes
        cfg["quotas"]["count"] = 12
        cfg["gangs"]["count"] = 8
        cfg["running_pods"]["count"] = 16
    w = W.build_world(cfg, 7)
    return w, O.build_store(w, time.time())


def _store(nodes: int):
    return _world(nodes)[1]


def _devices(sched) -> int:
    return sched.mesh.devices.size if sched.mesh is not None else 1


def test_default_scheduler_on_cpu_stays_single_device(cpu_devices,
                                                      monkeypatch):
    from koordinator_tpu.scheduler.cycle import Scheduler

    monkeypatch.delenv("KOORD_TPU_MESH", raising=False)
    assert Scheduler(_store(48)).mesh is None


def test_overrides_win_over_the_size_rule(cpu_devices, monkeypatch):
    """A budget every cluster is over makes the rule shard on all eight
    virtual devices; KOORD_TPU_MESH and the argument still pin it."""
    from koordinator_tpu.scheduler.cycle import Scheduler

    monkeypatch.setattr(pallas_common, "default_vmem_budget", lambda: 1)
    monkeypatch.delenv("KOORD_TPU_MESH", raising=False)
    store = _store(48)
    assert _devices(Scheduler(store)) == 8
    assert Scheduler(store, mesh="off").mesh is None
    assert _devices(Scheduler(store, mesh=2)) == 2
    monkeypatch.setenv("KOORD_TPU_MESH", "off")
    assert Scheduler(store).mesh is None
    monkeypatch.setenv("KOORD_TPU_MESH", "2")
    assert _devices(Scheduler(store)) == 2


def _add_jobs(w, stores, count: int, salt: int, aff_terms: int = 0):
    """``count`` pods of the configuration's mix into every store; the
    first ``aff_terms`` each carry an anti-affinity term of their own, so
    the batch packs that many affinity columns."""
    from benchmark import objects as O
    from benchmark import world as W
    from koordinator_tpu.api.objects import PodAffinityTerm

    jobs = W.draw_jobs(w.config, w.config["pod_mix"], count, salt=salt,
                       leaf_quotas=W.leaf_quota_names(w.quotas),
                       gangs=w.gangs, prefix=f"p{salt}")
    specs = [p for j in jobs for p in j]
    for store in stores:
        now = time.time()
        for i, p in enumerate(specs):
            pod = O.pod_object(p, w.namespace, now + i * 1e-6)
            if i < aff_terms:
                pod.spec.pod_anti_affinity.append(PodAffinityTerm(
                    selector={"uniq": f"u{salt}-{i}"},
                    topology_key="kubernetes.io/hostname"))
            store.add(KIND_POD, pod)


def _kernel_shards(sched) -> tuple:
    root = [r for r in sched.tracer.roots(limit=4) if r.name == "cycle"][-1]
    return ({k.attributes["shards"] for k in root.find_all("kernel")},
            {u.attributes["shards"] for u in root.find_all("upload")})


def _binds(store):
    return sorted((p.meta.key, p.spec.node_name)
                  for p in store.list(KIND_POD)
                  if p.spec.node_name and "/p" in p.meta.key)


def test_resize_gate_follows_the_packed_shape(cpu_devices, monkeypatch):
    """The rule is read again when the packed node bucket changes: a
    cluster that grows past the budget shards from its next dispatch,
    one that shrinks below it returns to one device, and the binds equal
    a pinned single-device twin's over the same stores."""
    from benchmark import objects as O
    from koordinator_tpu.scheduler import metrics as M
    from koordinator_tpu.scheduler.cycle import Scheduler

    # 64 nodes sit at the budget (not over it), 200 are past it
    monkeypatch.setattr(pallas_common, "default_vmem_budget",
                        lambda: node_axis_vmem_bytes(64))
    monkeypatch.delenv("KOORD_TPU_MESH", raising=False)
    w, a = _world(200)
    b = _world(200)[1]
    busy = {p.node for p in w.running}
    spare = [n for n in w.nodes if n.name not in busy][:136]

    def shrink():
        for store in (a, b):
            for n in spare:
                store.delete(KIND_NODE, "/" + n.name)

    def grow():
        for store in (a, b):
            for n in spare:
                store.add(KIND_NODE, O.node_object(n))

    shrink()
    sched, twin = Scheduler(a), Scheduler(b, mesh="off")
    assert sched.mesh is None

    def cycle(k: int):
        _add_jobs(w, (a, b), 40, k)
        sched.run_cycle(now=1000.0 + k)
        twin.run_cycle(now=1000.0 + k)
        return _kernel_shards(sched)

    assert cycle(1) == ({"1"}, {"1"}) and sched.mesh is None
    # grown past the budget: every chip, and the counter counts it
    grow()
    before = M.MESH_DISPATCHES.get(shards="8") or 0.0
    assert cycle(2) == ({"8"}, {"8"}) and _devices(sched) == 8
    assert (M.MESH_DISPATCHES.get(shards="8") or 0.0) > before
    # shrunk below it again: back to one device
    shrink()
    assert cycle(3) == ({"1"}, {"1"}) and sched.mesh is None
    assert _binds(a) == _binds(b)
    assert len(_binds(a)) > 60


def test_batch_axes_do_not_move_the_mesh(cpu_devices, monkeypatch):
    """At a fixed node count, a batch whose own axes (here 16 affinity
    columns) put its kernel estimate past the budget stays on the mesh
    the node axis chose: the size rule reads the node bucket alone."""
    from koordinator_tpu.scheduler import snapshot as S
    from koordinator_tpu.scheduler import cycle as C

    monkeypatch.setattr(pallas_common, "default_vmem_budget",
                        lambda: node_axis_vmem_bytes(64))
    monkeypatch.delenv("KOORD_TPU_MESH", raising=False)
    packed = []

    def reduce(fc):
        out = S.reduce_to_active_axes(fc)
        packed.append(out[0])
        return out

    monkeypatch.setattr(C, "reduce_to_active_axes", reduce)
    w, a = _world(64)
    sched = C.Scheduler(a)
    for k, terms in ((1, 0), (2, 16), (3, 0)):
        _add_jobs(w, (a,), 60, k, aff_terms=terms)
        sched.run_cycle(now=1000.0 + k)
        assert _kernel_shards(sched) == ({"1"}, {"1"})
        assert sched.mesh is None
    big = max(packed, key=lambda fc: fc.aff_dom.shape[1])
    assert big.aff_dom.shape[1] >= 16
    assert full_chain_vmem_bytes(big) > node_axis_vmem_bytes(64)


def test_fullchain_5k_packs_onto_one_chip(monkeypatch):
    """fullchain-5k's world, packed through the cycle at its full size:
    on a four-chip v5e the size rule keeps it on one chip, and the packed
    batch itself fits the Pallas kernel's budget there, so the steady
    cell keeps ``jit_full_chain_pallas``."""
    from koordinator_tpu.scheduler import snapshot as S
    from koordinator_tpu.scheduler import cycle as C

    monkeypatch.delenv("KOORD_TPU_MESH", raising=False)
    packed = []

    def reduce(fc):
        out = S.reduce_to_active_axes(fc)
        packed.append(out[0])
        return out

    monkeypatch.setattr(C, "reduce_to_active_axes", reduce)
    w, store = _world(0, small=False)
    sched = C.Scheduler(store)
    _add_jobs(w, (store,), 200, 1)
    sched.run_cycle(now=time.time())
    fc = packed[-1]
    n = fc.base.allocatable.shape[0]
    assert n == 5120 and sched._size_rule_bucket == n
    assert size_rule_shards(node_axis_vmem_bytes(n), 4, V5E) == 1
    assert size_rule_shards(full_chain_vmem_bytes(fc), 4, V5E) == 1
