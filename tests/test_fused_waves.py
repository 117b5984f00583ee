"""Fused multi-wave scheduling (models/fused_waves.py + the cycle
driver's per-wave replay): K rounds per device dispatch must be
byte-identical to K sequential single-round cycles, with compacted
readback and carried on-device state.

The kernel-level contract (wave 1 == the serial step, bit-exact) plus
the driver-level contract (fuzz parity through churn, the genuine
multi-wave retry channel, truncation semantics, auto-K policy and its
demotions, metrics/spans)."""

import numpy as np
import pytest

from koordinator_tpu.api.objects import (
    Node,
    ObjectMeta,
    Pod,
    PodGroup,
    PodSpec,
    Reservation,
    TopologySpreadConstraint,
)
from koordinator_tpu.api.resources import ResourceList
from koordinator_tpu.client.store import (
    KIND_NODE,
    KIND_POD,
    KIND_POD_GROUP,
    KIND_RESERVATION,
    ObjectStore,
)
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.scheduler.cycle import CyclePipeline, Scheduler
from koordinator_tpu.scheduler.pipeline_parity import run_fused_wave_parity

GIB = 1024 ** 3
NOW = 1_000_000.0
GANG_LABEL = "pod-group.scheduling.sigs.k8s.io"


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

def _packed_fixture(num_nodes=24, num_pods=70, seed=11):
    from koordinator_tpu.scheduler.snapshot import (
        build_full_chain_inputs,
        reduce_to_active_axes,
    )
    from koordinator_tpu.testing import synth_full_cluster

    la = LoadAwareArgs()
    _cluster, state = synth_full_cluster(
        num_nodes, num_pods, seed=seed, num_quotas=3, num_gangs=4,
        topology_fraction=0.5, lsr_fraction=0.2)
    fc, pods, nodes, _tree, _gi, ng, ngroups = build_full_chain_inputs(
        state, la)
    ex = nodes.extras
    fc, active = reduce_to_active_axes(fc)
    idx = np.asarray(active)
    est = np.take(ex["la_est_nonprod"], idx, axis=-1)
    adj = np.take(ex["la_adj_nonprod"], idx, axis=-1)
    return la, fc, pods, ng, ngroups, active, est, adj


def test_la_term_split_is_exact():
    """la_term_nonprod == la_est_nonprod + la_adj_nonprod bit-for-bit —
    the invariant the fused kernel's carried est_sum rests on."""
    from koordinator_tpu.testing import synth_full_cluster
    from koordinator_tpu.ops.loadaware import build_loadaware_node_state

    _cluster, state = synth_full_cluster(16, 40, seed=3)
    ex = build_loadaware_node_state(
        state.nodes, state.node_metrics, state.pods_by_key, state.assigned,
        LoadAwareArgs(), state.now, pad_to=16)
    assert np.array_equal(
        ex["la_term_nonprod"],
        ex["la_est_nonprod"] + ex["la_adj_nonprod"])


def test_fused_wave1_matches_serial_step_bitwise():
    """K=1 fused bindings == the serial single-round step, row for row
    (the evaluator and commit path are shared code — this pins it)."""
    from koordinator_tpu.models.full_chain import build_full_chain_step
    from koordinator_tpu.models.fused_waves import build_fused_wave_step

    from koordinator_tpu.models.fused_waves import plain_sides

    la, fc, pods, ng, ngroups, active, est, adj = _packed_fixture()
    chosen = np.asarray(
        build_full_chain_step(la, ng, ngroups, active_axes=active)(fc)[0])
    out = build_fused_wave_step(la, ng, ngroups, waves=1,
                                active_axes=active)(fc,
                                                    plain_sides(est, adj))
    n = int(np.asarray(out.wave_counts)[0])
    fused = np.full_like(chosen, -1)
    fused[np.asarray(out.bind_pods)[:n]] = np.asarray(out.bind_nodes)[:n]
    assert int(out.waves_run) == 1
    assert np.array_equal(fused, chosen)


def test_fused_kernel_early_exits_on_fixpoint():
    """A wave that commits nothing proves the fixpoint: waves_run stops
    there instead of burning the full K on device."""
    from koordinator_tpu.models.fused_waves import (
        build_fused_wave_step,
        plain_sides,
    )

    la, fc, pods, ng, ngroups, active, est, adj = _packed_fixture()
    out = build_fused_wave_step(la, ng, ngroups, waves=8,
                                active_axes=active)(fc,
                                                    plain_sides(est, adj))
    counts = np.asarray(out.wave_counts)
    waves_run = int(out.waves_run)
    assert waves_run < 8
    assert counts[waves_run - 1] == 0  # the exit wave committed nothing
    assert (counts[waves_run:] == 0).all()


def test_fused_step_rejects_bad_waves_and_prod_mismatch():
    from koordinator_tpu.models.fused_waves import build_fused_wave_step

    with pytest.raises(ValueError):
        build_fused_wave_step(LoadAwareArgs(), 1, 1, waves=0)
    with pytest.raises(ValueError):
        build_fused_wave_step(LoadAwareArgs(), 1, 1, waves=9)
    # prod-mode args REQUIRE the prod side split (and vice versa): the
    # carry's est_sum_prod slot presence must match prod_mode
    with pytest.raises(ValueError):
        build_fused_wave_step(
            LoadAwareArgs(score_according_prod_usage=True), 1, 1, waves=2)
    with pytest.raises(ValueError):
        build_fused_wave_step(LoadAwareArgs(), 1, 1, waves=2, prod=True)


# ---------------------------------------------------------------------------
# driver level: parity through churn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 8])
def test_fused_k_equals_k_serial_cycles_through_churn(k):
    """The pipeline_parity gate fixture (quotas, gangs, NUMA topology,
    cpuset pods, per-round arrival/metric churn): fused-K bound
    sequences, failure/rejection lists, PodScheduled conditions and final
    assignments must be byte-identical to K sequential single-round
    cycles. hack/lint.sh runs all of K in {1,2,4,8}."""
    report = run_fused_wave_parity(k)
    assert report["ok"], report["mismatches"]
    assert report["conditions_checked"] > 0


# ---------------------------------------------------------------------------
# driver level: the genuine multi-wave retry channel
# ---------------------------------------------------------------------------

def _spread_retry_store():
    """Two zones; gang member b1 (Permit always fails -> reverts every
    round) holds n0 in wave 1 and shadows p; kept pod c raises zone za's
    spread count, so wave 2's re-evaluation pushes b1 to zone zb and p
    binds n0 — the topology-spread channel is non-additive, which is what
    makes a LATER round differ from re-running the first."""
    store = ObjectStore()
    for name, zone in (("n0", "za"), ("n1", "zb")):
        store.add(KIND_NODE, Node(
            meta=ObjectMeta(name=name, namespace="", labels={"zone": zone}),
            allocatable=ResourceList.of(cpu=6000, memory=32 * GIB, pods=20)))
    store.add(KIND_POD_GROUP, PodGroup(
        meta=ObjectMeta(name="gb", namespace="default"), min_member=2))
    store.add(KIND_POD, Pod(
        meta=ObjectMeta(name="b1", uid="b1", creation_timestamp=NOW,
                        labels={GANG_LABEL: "gb", "app": "red"}),
        spec=PodSpec(priority=9000,
                     requests=ResourceList.of(cpu=3000, memory=GIB, pods=1),
                     topology_spread=[TopologySpreadConstraint(
                         max_skew=1, topology_key="zone",
                         selector={"app": "red"})])))
    store.add(KIND_POD, Pod(
        meta=ObjectMeta(name="b2", uid="b2", creation_timestamp=NOW,
                        labels={GANG_LABEL: "gb"}),
        spec=PodSpec(priority=9000,
                     requests=ResourceList.of(cpu=900_000, memory=GIB,
                                              pods=1))))
    store.add(KIND_POD, Pod(
        meta=ObjectMeta(name="c", uid="c", creation_timestamp=NOW + 1,
                        labels={"app": "red"}),
        spec=PodSpec(priority=5000, node_selector={"zone": "za"},
                     requests=ResourceList.of(cpu=1000, memory=GIB,
                                              pods=1))))
    store.add(KIND_POD, Pod(
        meta=ObjectMeta(name="p", uid="p", creation_timestamp=NOW + 2),
        spec=PodSpec(priority=1000, node_selector={"zone": "za"},
                     requests=ResourceList.of(cpu=3000, memory=GIB,
                                              pods=1))))
    return store


def test_wave2_binds_pod_rejected_in_wave1():
    """One fused dispatch does what took two serial cycles: p fails the
    first round (capacity held by the reverting gang member), binds in
    the second (the kept commit moved the gang member's choice)."""
    sched = Scheduler(_spread_retry_store(), waves=4)
    res = sched.run_cycle(now=NOW)
    bound = [(b.pod_key, b.node_name) for b in res.bound]
    assert bound == [("default/c", "n0"), ("default/p", "n0")]
    # logical cycle 1 recorded p's transient failure, like serial c1 did
    assert "default/p" in res.failed
    assert res.waves >= 2


def test_fused_spread_scenario_matches_serial_exactly():
    """The same store through 3 serial cycles vs one fused K=3 cycle:
    concatenated bound/failed/rejected and final store state identical."""
    s_ser = Scheduler(_spread_retry_store(), waves=1)
    ser_bound, ser_failed, ser_rejected = [], [], []
    for _ in range(3):
        r = s_ser.run_cycle(now=NOW)
        ser_bound += [(b.pod_key, b.node_name) for b in r.bound]
        ser_failed += r.failed
        ser_rejected += r.rejected
    s_f = Scheduler(_spread_retry_store(), waves=3)
    rf = s_f.run_cycle(now=NOW)
    assert [(b.pod_key, b.node_name) for b in rf.bound] == ser_bound
    assert rf.failed == ser_failed
    assert rf.rejected == ser_rejected
    assert rf.waves == 3
    for key in ("default/c", "default/p", "default/b1"):
        a = s_ser.store.get(KIND_POD, key)
        b = s_f.store.get(KIND_POD, key)
        assert a.spec.node_name == b.spec.node_name
        ca, cb = (x.get_condition("PodScheduled") for x in (a, b))
        assert (ca is None) == (cb is None)
        if ca is not None:
            assert (ca.status, ca.reason, ca.message) == (
                cb.status, cb.reason, cb.message)


# ---------------------------------------------------------------------------
# driver level: waves policy
# ---------------------------------------------------------------------------

def _plain_store(num_nodes=2):
    store = ObjectStore()
    for i in range(num_nodes):
        store.add(KIND_NODE, Node(
            meta=ObjectMeta(name=f"n{i}", namespace=""),
            allocatable=ResourceList.of(cpu=64000, memory=64 * GIB,
                                        pods=500)))
    return store


def _pend(store, name, cpu=500, pvcs=()):
    pod = Pod(
        meta=ObjectMeta(name=name, uid=name, creation_timestamp=NOW),
        spec=PodSpec(requests=ResourceList.of(cpu=cpu, memory=GIB, pods=1),
                     pvc_names=list(pvcs)))
    store.add(KIND_POD, pod)
    return pod


def test_auto_waves_policy_scales_with_queue_depth():
    from koordinator_tpu.scheduler.cycle import _auto_waves

    assert _auto_waves(10) == 1
    assert _auto_waves(256) == 2
    assert _auto_waves(1024) == 4
    assert _auto_waves(4096) == 8


def test_effective_waves_demotions():
    """The PR-14 burn-down: reservations, claims and prod scoring no
    longer demote; only the narrow residues do."""
    store = _plain_store()
    sched = Scheduler(store, waves=8)
    pods = [_pend(store, f"p{i}") for i in range(4)]
    assert sched._effective_waves(pods, {}) == 8
    # pending Reservation CRs: carried as reservation rows + in-kernel
    # nomination — fused stays on
    res = Reservation(meta=ObjectMeta(name="r", namespace="__reservation__"))
    assert sched._effective_waves(pods, {"__reservation__/r": res}) == 8
    # claim-carrying pods: the hot-claim factorization carries the
    # volume-group regrouping (opaque-token mode: nothing is entangled)
    pvc_pod = _pend(store, "with-claim", pvcs=["claim-a"])
    assert sched._effective_waves(pods + [pvc_pod], {}) == 8
    # prod-usage scoring rides the est/adj prod split
    prod_sched = Scheduler(
        _plain_store(), args=LoadAwareArgs(score_according_prod_usage=True),
        waves=8)
    assert prod_sched._effective_waves(pods, {}) == 8
    # explicit K=1 and env-auto shallow queues stay serial
    assert Scheduler(_plain_store(), waves=1)._effective_waves(
        pods, {}) == 1
    assert Scheduler(_plain_store(), waves="auto")._effective_waves(
        pods, {}) == 1


def test_effective_waves_residual_demotions():
    """The remaining data-driven demotions: host-only ScoreTransformers
    and claim entanglement; retired reasons raise at the chokepoint."""
    from koordinator_tpu.api.objects import (
        PersistentVolumeClaim,
        StorageClass,
    )
    from koordinator_tpu.client.store import KIND_PVC, KIND_STORAGECLASS
    from koordinator_tpu.scheduler.frameworkext import ScoreTransformer

    store = _plain_store()
    sched = Scheduler(store, waves=8)
    pods = [_pend(store, f"p{i}") for i in range(4)]

    class HostOnly(ScoreTransformer):
        name = "host-only"

    sched.extender.register_transformer(HostOnly())
    assert sched._effective_waves(pods, {}) == 1
    assert "non-expressible-transformer" in sched._cycle_demotions

    # volume-aware store + two pods with unbound WFFC claims: entangled
    store2 = _plain_store()
    sched2 = Scheduler(store2, waves=8)
    store2.add(KIND_STORAGECLASS, StorageClass(
        meta=ObjectMeta(name="sc", namespace=""),
        provisioner="csi.example", volume_binding_mode="WaitForFirstConsumer"))
    for i in range(2):
        store2.add(KIND_PVC, PersistentVolumeClaim(
            meta=ObjectMeta(name=f"c{i}", namespace="default"),
            storage_class_name="sc"))
    claim_pods = [_pend(store2, f"q{i}", pvcs=[f"c{i}"]) for i in range(2)]
    filler = [_pend(store2, f"f{i}") for i in range(2)]
    assert sched2._effective_waves(claim_pods + filler, {}) == 1
    assert "claim-entangled" in sched2._cycle_demotions
    # ONE unbound-claim pod is carriable (its own bind removes it)
    sched3 = Scheduler(store2, waves=8)
    assert sched3._effective_waves([claim_pods[0]] + filler, {}) == 8

    # the chokepoint refuses retired reasons loudly
    with pytest.raises(ValueError):
        sched._note_demotion("claim-pods", 1)
    with pytest.raises(ValueError):
        sched._note_demotion("not-a-registered-reason", 1)


def test_waves_env_spec(monkeypatch):
    from koordinator_tpu.scheduler.cycle import waves_from_env

    monkeypatch.setenv("KOORD_TPU_WAVES", "4")
    assert waves_from_env() == 4
    monkeypatch.setenv("KOORD_TPU_WAVES", "99")
    assert waves_from_env() == 8  # clamped to MAX_WAVES
    monkeypatch.setenv("KOORD_TPU_WAVES", "auto")
    assert waves_from_env() == "auto"
    monkeypatch.setenv("KOORD_TPU_WAVES", "bogus")
    assert waves_from_env() == "auto"
    monkeypatch.delenv("KOORD_TPU_WAVES")
    assert waves_from_env() == "auto"


# ---------------------------------------------------------------------------
# driver level: observability
# ---------------------------------------------------------------------------

def test_fused_cycle_metrics_and_wave_spans():
    """Default (overlapped-replay) trace layout: the kernel span carries
    the wave budget + overlap marker, and the per-wave host replay rides
    wave_replay[i] spans under replay_drain."""
    from koordinator_tpu.scheduler import metrics as m

    store = _spread_retry_store()
    sched = Scheduler(store, waves=4)
    assert sched.replay_overlap  # the default
    res = sched.run_cycle(now=NOW)
    assert res.waves >= 2
    text = m.REGISTRY.expose()
    assert "koord_scheduler_waves_per_dispatch_bucket" in text
    assert "koord_scheduler_readback_bytes_total" in text
    # the host-clock occupancy gauge is retired
    assert "koord_scheduler_pipeline_occupancy" not in text
    root = sched.tracer.roots(limit=1)[0]
    kernel = root.find("kernel")
    assert kernel is not None
    assert kernel.attributes.get("waves") == "4"
    assert kernel.attributes.get("overlap") == "1"
    drain = root.find("replay_drain")
    assert drain is not None
    waves = [s for s in drain.children if s.name == "wave_replay"]
    assert len(waves) >= 2
    assert waves[0].attributes.get("index") == "0"
    assert "bound" in waves[0].attributes


def test_fused_cycle_wave_spans_serial_replay_twin():
    """KOORD_TPU_REPLAY_OVERLAP=0: the single-program fused dispatch
    keeps the original retrospective wave markers under the kernel span
    — the parity twin's trace shape is part of 'today's exact path'."""
    store = _spread_retry_store()
    sched = Scheduler(store, waves=4, replay_overlap=False)
    res = sched.run_cycle(now=NOW)
    assert res.waves >= 2
    root = sched.tracer.roots(limit=1)[0]
    kernel = root.find("kernel")
    assert kernel is not None
    assert kernel.attributes.get("waves") == "4"
    assert kernel.attributes.get("overlap") is None
    waves = [s for s in kernel.children if s.name == "wave"]
    assert len(waves) >= 2
    assert waves[0].attributes.get("index") == "0"
    assert "bound" in waves[0].attributes


def test_serial_path_reports_one_wave():
    store = _plain_store()
    _pend(store, "a")
    sched = Scheduler(store)  # auto -> shallow queue -> serial
    res = sched.run_cycle(now=NOW)
    assert res.waves == 1
    assert [b.pod_key for b in res.bound] == ["default/a"]


# ---------------------------------------------------------------------------
# PR 14 carried state: reservations/claims through the fused dispatch
# ---------------------------------------------------------------------------

def test_reservation_consumed_by_wave2_of_same_dispatch():
    """The ISSUE-14 headline: a Reservation CR bound in wave 1 is
    consumed by an owner pod in wave 2 of the SAME dispatch (in-kernel
    nomination), with the consume annotation and the allocate-once
    Succeeded transition at the next reconcile."""
    from koordinator_tpu.api.objects import (
        ANNOTATION_RESERVATION_ALLOCATED,
    )
    from koordinator_tpu.scheduler.pipeline_parity import (
        _reservation_world,
    )

    now, store = _reservation_world()
    sched = Scheduler(store, waves=4)
    res = sched.run_cycle(now=now)
    assert res.demotions == []
    assert res.waves >= 2
    bound = {b.pod_key: b for b in res.bound}
    # the pseudo-pod bound its CR in wave 1...
    assert "__reservation__/resv-a" in bound
    r = store.get(KIND_RESERVATION, "/resv-a")
    assert r.phase == "Available"
    # ... and the selector-blocked owner consumed it IN THE SAME CYCLE
    assert bound["default/own-a"].node_name == bound[
        "__reservation__/resv-a"].node_name
    assert bound["default/own-a"].annotations[
        ANNOTATION_RESERVATION_ALLOCATED] == "resv-a"
    # multi-consumer (allocate_once=False): both owners rode resv-b
    for key in ("default/own-b1", "default/own-b2"):
        assert bound[key].annotations[
            ANNOTATION_RESERVATION_ALLOCATED] == "resv-b"
    # next cycle's reconcile retires the consumed allocate-once CR
    sched.run_cycle(now=now + 1)
    assert store.get(KIND_RESERVATION, "/resv-a").phase == "Succeeded"
    assert store.get(KIND_RESERVATION, "/resv-b").phase == "Available"


def test_carried_dispatch_ladder_demotion_lands_serial_identical():
    """Satellite: a fused dispatch carrying reservations + claims whose
    device window faults down the ladder mid-dispatch must land on the
    serial path with binds identical to a fault-free serial twin (the
    FusedDispatchDemoted re-run), and the transitions flight-dump."""
    from koordinator_tpu.scheduler.pipeline_parity import (
        _reservation_world,
    )

    def twin(inject: bool):
        now, store = _reservation_world()
        # a claim pod rides along so BOTH carried features are in play
        _pend(store, "claimer", pvcs=["c-x"])
        sched = Scheduler(store, waves=4)
        if inject:
            calls = {"n": 0}

            def inj(stage):
                calls["n"] += 1
                if stage == "fused" and calls["n"] <= 2:
                    raise RuntimeError("injected fused fault")

            sched.fault_injector = inj
        seq = []
        for c in range(4):
            r = sched.run_cycle(now=now + c)
            seq.extend((b.pod_key, b.node_name) for b in r.bound)
        return sched, seq

    sched_f, seq_f = twin(inject=True)
    _sched_c, seq_c = twin(inject=False)
    # the faulted world demoted below fused waves (retry once, then the
    # ladder's serial rung — later clean cycles may re-promote, so pin
    # the DEMOTED-cycle accounting, not the final level) and re-ran the
    # SAME pass serially with identical binds
    demoted = [r for r in sched_f.flight.snapshot()
               if "ladder-serial-waves" in r.get("demotions", [])]
    assert demoted, "the injected faults never demoted the fused dispatch"
    assert seq_f == seq_c


def test_crash_restart_rederives_reservation_state_from_replay():
    """Satellite: a fresh Scheduler on a surviving store (the koordguard
    crash-restart shape) re-derives reservation carry state — Available
    rows, consumed remainders via consumer annotations — purely from
    subscribe-replay, and the next fused dispatch nominates within the
    REMAINING capacity only."""
    from koordinator_tpu.api.objects import (
        ANNOTATION_RESERVATION_ALLOCATED,
        ReservationOwner,
    )

    store = _plain_store(num_nodes=1)
    node = store.list(KIND_NODE)[0]
    # an Available reservation with one PRE-CRASH consumer recorded only
    # through the consumer pod's annotation (the store truth)
    res = Reservation(
        meta=ObjectMeta(name="surv", namespace="",
                        creation_timestamp=NOW - 50),
        template=PodSpec(requests=ResourceList.of(cpu=2000, memory=GIB,
                                                   pods=2)),
        owners=[ReservationOwner(label_selector={"app": "w"})],
        allocate_once=False,
        phase="Available",
        node_name=node.meta.name,
        allocatable=ResourceList.of(cpu=2000, memory=GIB, pods=2),
        allocated=ResourceList.of(cpu=1500, pods=1),
        current_owners=["default/old-consumer"])
    store.add(KIND_RESERVATION, res)
    old = Pod(
        meta=ObjectMeta(name="old-consumer", uid="old",
                        creation_timestamp=NOW - 40, labels={"app": "w"},
                        annotations={
                            ANNOTATION_RESERVATION_ALLOCATED: "surv"}),
        spec=PodSpec(node_name=node.meta.name,
                     requests=ResourceList.of(cpu=1500, memory=GIB,
                                              pods=1)))
    store.add(KIND_POD, old)
    # two fresh owner pods, selector-blocked: only the reservation's
    # REMAINDER (500m) can host them — exactly one fits
    for name in ("w1", "w2"):
        pod = Pod(meta=ObjectMeta(name=name, uid=name,
                                  creation_timestamp=NOW,
                                  labels={"app": "w"}),
                  spec=PodSpec(requests=ResourceList.of(
                      cpu=400, memory=GIB, pods=1)))
        pod.spec.node_selector = {"reserved-only": "true"}
        store.add(KIND_POD, pod)
    # the RESTARTED scheduler: fresh object graph over the old store
    sched = Scheduler(store, waves=4)
    plugin = sched.extender.plugin("Reservation")
    assert "surv" in plugin.by_name  # subscribe-replay rebuilt the cache
    r = sched.run_cycle(now=NOW)
    bound = {b.pod_key for b in r.bound}
    # the host pre-pass (cycle start: already Available) nominates w1
    # within the replayed remainder; w2 (400 > 100 left) cannot fit
    assert "default/w1" in bound
    assert "default/w2" not in bound
    assert store.get(KIND_RESERVATION, "/surv").allocated.to_vector()[
        0] > 0


def test_opaque_claim_pods_bind_and_count_csi_slots():
    """The VolumeBinding opaque-token mode fix: pvc_names without any
    PVC/PV/StorageClass objects are CSI count tokens — pods BIND (no
    Reserve veto) and the attachable-volume limit still gates them.
    Pre-PR-14 these pods were immortal queue residents, which is why
    claim-pods dominated the soak demotion profile."""
    store = ObjectStore()
    node = Node(meta=ObjectMeta(name="n0", namespace=""),
                allocatable=ResourceList.of(cpu=64000, memory=64 * GIB,
                                            pods=50))
    node.attachable_volume_limit = 2
    store.add(KIND_NODE, node)
    for i in range(3):
        _pend(store, f"q{i}", pvcs=[f"c{i}"])
    sched = Scheduler(store, waves=4)
    res = sched.run_cycle(now=NOW)
    assert res.demotions == []
    bound = [b.pod_key for b in res.bound]
    # two claims fill the CSI limit; the third pod stays pending on the
    # volume filter — in BOTH the fused and next serial cycles
    assert len(bound) == 2
    assert len(res.failed) >= 1
    res2 = sched.run_cycle(now=NOW + 1)
    assert not res2.bound


def test_pipeline_defers_conditions_across_fused_cycle():
    """Fused cycles compose with the CyclePipeline: a transient wave-1
    failure that a later wave resolves must end PodScheduled=True after
    flush (the deferred False verdict is superseded by the bind)."""
    store = _spread_retry_store()
    pipeline = CyclePipeline(Scheduler(store, waves=4), enabled=True)
    res = pipeline.run_cycle(now=NOW)
    assert ("default/p", "n0") in [
        (b.pod_key, b.node_name) for b in res.bound]
    pipeline.flush()
    cond = store.get(KIND_POD, "default/p").get_condition("PodScheduled")
    assert cond is not None and cond.status == "True"
