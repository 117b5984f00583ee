"""Pipelined cycle (scheduler/cycle.CyclePipeline) semantics.

The pipeline reorders WHEN host work runs (non-blocking kernel dispatch,
condition writes deferred into the next cycle's kernel window); these
tests pin that it never changes WHAT the scheduler produces — the
serial-vs-pipelined parity harness (scheduler/pipeline_parity.py, also a
hack/lint.sh gate) plus targeted deferral/flush/env-gate behaviors."""

import numpy as np

from koordinator_tpu.api.objects import Node, ObjectMeta, Pod, PodSpec
from koordinator_tpu.api.resources import ResourceList
from koordinator_tpu.client.store import KIND_NODE, KIND_POD, ObjectStore
from koordinator_tpu.scheduler.cycle import (
    CyclePipeline,
    Scheduler,
    pipeline_enabled_from_env,
)
from koordinator_tpu.scheduler.pipeline_parity import run_pipeline_parity

GIB = 1024 ** 3
NOW = 1_000_000.0


def make_store(num_nodes=3, cpu=8000):
    store = ObjectStore()
    for i in range(num_nodes):
        store.add(KIND_NODE, Node(
            meta=ObjectMeta(name=f"n{i}", namespace=""),
            allocatable=ResourceList.of(cpu=cpu, memory=32 * GIB, pods=20)))
    return store


def pend_pod(store, name, cpu=1000):
    pod = Pod(
        meta=ObjectMeta(name=name, uid=name, creation_timestamp=NOW),
        spec=PodSpec(requests=ResourceList.of(cpu=cpu, memory=GIB)))
    store.add(KIND_POD, pod)
    return pod


def cond(store, key):
    return store.get(KIND_POD, key).get_condition("PodScheduled")


def test_serial_vs_pipelined_parity_fixture():
    """The lint-gate fixture: identical bindings, failure sets and
    PodScheduled conditions through churn rounds (flush included)."""
    report = run_pipeline_parity()
    assert report["ok"], report["mismatches"]
    assert report["conditions_checked"] > 0


def test_condition_writes_defer_until_flush():
    store = make_store(num_nodes=1, cpu=2000)
    pend_pod(store, "fits", cpu=1000)
    pend_pod(store, "too-big", cpu=64000)  # no node can hold it
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    res = pipeline.run_cycle(now=NOW)
    # the verdict itself is computed in-cycle...
    assert "default/too-big" in res.failed
    assert [b.pod_key for b in res.bound] == ["default/fits"]
    # ...but the condition write is deferred (no kernel window ran after)
    assert cond(store, "default/too-big") is None
    assert len(sched._deferred_diagnose) == 1
    pipeline.flush()
    c = cond(store, "default/too-big")
    assert c is not None and c.status == "False"
    assert c.reason == "Unschedulable"
    assert not sched._deferred_diagnose


def test_deferred_flush_runs_in_next_kernel_window():
    store = make_store(num_nodes=1, cpu=2000)
    pend_pod(store, "too-big", cpu=64000)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    pipeline.run_cycle(now=NOW)
    assert cond(store, "default/too-big") is None
    # next cycle has a kernel pass (a new pod arrives): the deferred write
    # lands during its overlap window without an explicit flush
    pend_pod(store, "late", cpu=500)
    pipeline.run_cycle(now=NOW + 2)
    c = cond(store, "default/too-big")
    assert c is not None and c.status == "False"
    # the condition carries cycle N's timestamp, not the flush time
    assert c.last_transition_time == NOW


def test_deferred_verdict_superseded_by_bind_is_skipped():
    """A pod that fails cycle N but binds in cycle N+1 must end with
    PodScheduled=True — the deferred False write never clobbers it."""
    store = make_store(num_nodes=1, cpu=2000)
    pend_pod(store, "wants-cap", cpu=4000)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    res = pipeline.run_cycle(now=NOW)
    assert "default/wants-cap" in res.failed
    # capacity arrives; N+1 binds the pod, then flush drains N's verdict
    store.add(KIND_NODE, Node(
        meta=ObjectMeta(name="big", namespace=""),
        allocatable=ResourceList.of(cpu=64000, memory=64 * GIB, pods=20)))
    res2 = pipeline.run_cycle(now=NOW + 2)
    assert [b.pod_key for b in res2.bound] == ["default/wants-cap"]
    pipeline.flush()
    c = cond(store, "default/wants-cap")
    assert c is not None and c.status == "True"


def test_env_gate_disables_pipeline(monkeypatch):
    monkeypatch.setenv("KOORD_TPU_PIPELINE", "0")
    assert pipeline_enabled_from_env() is False
    store = make_store()
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched)  # enabled=None -> env decides
    assert pipeline.enabled is False
    assert sched.pipeline_mode is False
    # serial fallback writes conditions inline, exactly the old behavior
    pend_pod(store, "too-big", cpu=64000)
    pipeline.run_cycle(now=NOW)
    c = cond(store, "default/too-big")
    assert c is not None and c.status == "False"
    monkeypatch.delenv("KOORD_TPU_PIPELINE")
    assert pipeline_enabled_from_env() is True


def test_pipeline_spans_and_device_busy():
    store = make_store()
    pend_pod(store, "a", cpu=500)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    res = pipeline.run_cycle(now=NOW)
    # device busy time comes from a profiler trace, not the host clock
    assert not hasattr(res, "device_busy_seconds")
    root = sched.tracer.roots(limit=1)[0]
    assert root.find("pack_incremental") is not None
    kernel = root.find("kernel")
    assert kernel is not None
    assert kernel.find("overlap_wait") is not None


def test_pack_incremental_counters_and_upload_gauges():
    from koordinator_tpu.scheduler import metrics as m

    store = make_store()
    for i in range(4):
        pend_pod(store, f"p{i}", cpu=500)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    pipeline.run_cycle(now=NOW)
    # steady state: a carried-over pending pod must reuse its packed row
    reused_before = sched.snapshot_cache.stats["pod_row_hits"]
    pend_pod(store, "fresh", cpu=64000)  # stays pending across cycles
    pipeline.run_cycle(now=NOW + 2)
    pipeline.run_cycle(now=NOW + 4)
    assert sched.snapshot_cache.stats["pod_row_hits"] > reused_before
    # pack counters + upload gauges land in the Prometheus exposition
    text = m.REGISTRY.expose()
    assert "koord_scheduler_pack_rows_reused_total" in text
    assert "koord_scheduler_pack_rows_repacked_total" in text
    assert "koord_scheduler_upload_fields_reused_total" in text
    assert "koord_scheduler_upload_bytes_put_total" in text
    pipeline.flush()


def test_carried_deferred_drains_on_kernel_less_cycle():
    """A cycle with no kernel window (empty pending queue) must drain
    carried-over deferred writes instead of letting them linger."""
    store = make_store(num_nodes=1, cpu=2000)
    pend_pod(store, "too-big", cpu=64000)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    pipeline.run_cycle(now=NOW)
    assert len(sched._deferred_diagnose) == 1
    # the failed pod leaves the queue entirely; the next cycle has nothing
    # to schedule and therefore no overlap window
    store.delete(KIND_POD, "default/too-big")
    pipeline.run_cycle(now=NOW + 2)
    assert not sched._deferred_diagnose, (
        "kernel-less cycles must not strand deferred writes")


def test_deferred_write_skips_recreated_pod_with_new_uid():
    """Delete + recreate under the same key between cycles: the old
    incarnation's deferred verdict must not stamp the new pod."""
    store = make_store(num_nodes=1, cpu=2000)
    pend_pod(store, "stateful-0", cpu=64000)
    sched = Scheduler(store)
    pipeline = CyclePipeline(sched, enabled=True)
    res = pipeline.run_cycle(now=NOW)
    assert "default/stateful-0" in res.failed
    store.delete(KIND_POD, "default/stateful-0")
    fresh = Pod(
        meta=ObjectMeta(name="stateful-0", uid="reborn",
                        creation_timestamp=NOW + 1),
        spec=PodSpec(requests=ResourceList.of(cpu=64000, memory=GIB)))
    store.add(KIND_POD, fresh)
    # flush the OLD verdict explicitly: the uid guard must skip the write
    pipeline.flush()
    assert cond(store, "default/stateful-0") is None
    # the recreated pod earns its OWN verdict with its own timestamp
    pipeline.run_cycle(now=NOW + 4)
    pipeline.flush()
    c = cond(store, "default/stateful-0")
    assert c is not None and c.status == "False"
    assert c.last_transition_time == NOW + 4
