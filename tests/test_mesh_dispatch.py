"""Mesh-backed production dispatch (KOORD_TPU_MESH): the scheduling cycle
sharded over the device mesh must be byte-identical to the single-device
path, the sharding helpers must absorb non-divisible axis sizes, and the
mesh path must be observable (devices/shard gauges, shard spans).

The heavyweight matrix (1/2/4/8 devices x serial/fused x explain) runs in
hack/lint.sh via scheduler/pipeline_parity.run_mesh_parity; tier-1 pins a
representative slice plus the unit seams (DeviceSnapshot sharded upload/
scatter, put_on_mesh padding + multi-host branch, metrics)."""

import numpy as np
import pytest

from koordinator_tpu.scheduler import metrics as scheduler_metrics
from koordinator_tpu.scheduler.pipeline_parity import run_mesh_parity


# ---------------------------------------------------------------------------
# production-path parity (the tentpole gate, tier-1 slice)
# ---------------------------------------------------------------------------

def test_mesh_parity_serial_2dev(cpu_devices):
    rep = run_mesh_parity(2)
    assert rep["ok"], rep["mismatches"]
    assert rep["conditions_checked"] > 0


def test_mesh_parity_fused_8dev(cpu_devices):
    rep = run_mesh_parity(8, waves=4)
    assert rep["ok"], rep["mismatches"]


def test_mesh_parity_explain_counts(cpu_devices):
    rep = run_mesh_parity(4, explain="counts")
    assert rep["ok"], rep["mismatches"]


def test_mesh_parity_non_divisible_mesh(cpu_devices):
    """3 devices never divide the pow2/256-granule node buckets, so every
    upload exercises pad_for_sharding inside put_on_mesh — the production
    regression for the non-divisible-axis satellite."""
    rep = run_mesh_parity(3)
    assert rep["ok"], rep["mismatches"]


# ---------------------------------------------------------------------------
# DeviceSnapshot: sharded upload + shard-aware scatter
# ---------------------------------------------------------------------------

def _mesh_of(devs, n):
    from koordinator_tpu.parallel import make_mesh

    return make_mesh(devs[:n])


def test_device_snapshot_mesh_upload_shards_node_axis(cpu_devices):
    from koordinator_tpu.scheduler.snapshot_cache import DeviceSnapshot

    mesh = _mesh_of(cpu_devices, 8)
    ds = DeviceSnapshot(mesh=mesh)
    node_arr = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
    pod_arr = np.ones((16, 3), np.float32)
    dev_node = ds._one("allocatable", node_arr)
    dev_pod = ds._one("fit_requests", pod_arr)
    # node-axis field sharded over all devices; pod field replicated
    assert len({sh.device.id for sh in dev_node.addressable_shards}) == 8
    assert dev_node.addressable_shards[0].data.shape[0] == 8  # 64 / 8
    assert np.asarray(dev_pod).shape == pod_arr.shape
    for sh in dev_pod.addressable_shards:
        assert sh.data.shape == pod_arr.shape  # replicated: full copy


def test_device_snapshot_mesh_pads_non_divisible(cpu_devices):
    from koordinator_tpu.scheduler.snapshot_cache import DeviceSnapshot

    mesh = _mesh_of(cpu_devices, 8)
    ds = DeviceSnapshot(mesh=mesh)
    node_arr = np.random.default_rng(0).random((30, 3)).astype(np.float32)
    dev = ds._one("allocatable", node_arr)
    assert dev.shape == (32, 3)  # padded to the mesh factor
    host = np.asarray(dev)
    np.testing.assert_array_equal(host[:30], node_arr)
    assert not host[30:].any()  # zero pad rows
    # unchanged re-upload reuses the buffer (pad rows never look dirty)
    before = dict(ds.stats)
    dev2 = ds._one("allocatable", node_arr)
    assert dev2 is dev
    assert ds.stats["reused"] == before["reused"] + 1


def test_device_snapshot_mesh_scatter_keeps_sharding(cpu_devices):
    from koordinator_tpu.scheduler.snapshot_cache import DeviceSnapshot

    mesh = _mesh_of(cpu_devices, 8)
    ds = DeviceSnapshot(mesh=mesh)
    rng = np.random.default_rng(1)
    node_arr = rng.random((64, 4)).astype(np.float32)
    dev = ds._one("requested", node_arr)
    sharding = dev.sharding
    # dirty two rows on different shards -> scatter path, sharding kept
    node_arr2 = node_arr.copy()
    node_arr2[3] += 1.0
    node_arr2[60] += 2.0
    dev2 = ds._one("requested", node_arr2)
    assert ds.stats["scattered"] == 1
    assert dev2.sharding == sharding
    np.testing.assert_array_equal(np.asarray(dev2), node_arr2)


def test_device_snapshot_mesh_scatter_respects_dispatch_guard(cpu_devices):
    from koordinator_tpu.scheduler.snapshot_cache import DeviceSnapshot

    mesh = _mesh_of(cpu_devices, 2)
    ds = DeviceSnapshot(mesh=mesh)
    node_arr = np.zeros((64, 4), np.float32)
    ds._one("requested", node_arr)
    ds.begin_dispatch()
    try:
        node_arr2 = node_arr.copy()
        node_arr2[5] = 1.0
        ds._one("requested", node_arr2)
    finally:
        ds.end_dispatch()
    assert ds.stats["scattered_safe"] == 1  # non-donating double-buffer


# ---------------------------------------------------------------------------
# mesh observability
# ---------------------------------------------------------------------------

def _mesh_world(num_nodes=16, num_pods=40, ndev=4, **kw):
    from koordinator_tpu.scheduler.cycle import Scheduler
    from koordinator_tpu.scheduler.pipeline_parity import (
        build_store_from_state,
    )
    from koordinator_tpu.testing import synth_full_cluster

    _cluster, state = synth_full_cluster(
        num_nodes, num_pods, seed=5, num_quotas=2, num_gangs=2)
    store = build_store_from_state(state)
    return Scheduler(store, mesh=ndev, **kw), state


def test_mesh_cycle_emits_shard_spans_and_gauges(cpu_devices):
    sched, state = _mesh_world(ndev=4, waves=1)
    assert scheduler_metrics.MESH_DEVICES.get() == 4.0
    res = sched.run_cycle(now=state.now)
    assert res.bound  # the fixture must actually schedule
    root = sched.tracer.roots(limit=1)[0]
    kernel = root.find("kernel")
    # the per-shard markers ride the readback's sync span in the kernel
    shards = kernel.find("sync").find_all("shard")
    assert len(shards) == 4
    assert [s.attributes["index"] for s in shards] == ["0", "1", "2", "3"]
    total_rows = sum(int(s.attributes["rows"]) for s in shards)
    assert total_rows == 16  # real rows split across shards
    imb = scheduler_metrics.MESH_SHARD_IMBALANCE.get()
    assert imb is not None and imb >= 1.0
    assert any(
        scheduler_metrics.MESH_SHARD_READBACK_BYTES.get(shard=str(i))
        for i in range(4))


def test_mesh_off_reports_zero_devices():
    from koordinator_tpu.client.store import ObjectStore
    from koordinator_tpu.scheduler.cycle import Scheduler

    Scheduler(ObjectStore(), mesh="off")
    assert scheduler_metrics.MESH_DEVICES.get() == 0.0


def test_mesh_from_env_parsing(cpu_devices, monkeypatch):
    from koordinator_tpu.parallel import mesh_from_env

    assert mesh_from_env(env_value="off") is None
    assert mesh_from_env(env_value="0") is None
    assert mesh_from_env(env_value="auto").devices.size == 8
    assert mesh_from_env(env_value=4).devices.size == 4
    assert mesh_from_env(env_value="1").devices.size == 1
    assert mesh_from_env(env_value="bogus") is None  # warn, stay off
    with pytest.raises(ValueError):
        mesh_from_env(env_value=99)
    monkeypatch.setenv("KOORD_TPU_MESH", "2")
    assert mesh_from_env().devices.size == 2


def test_mesh_demoted_with_sidecar(cpu_devices):
    from koordinator_tpu.client.store import ObjectStore
    from koordinator_tpu.scheduler.cycle import Scheduler

    sched = Scheduler(ObjectStore(), mesh=2,
                      sidecar_address="localhost:1")
    assert sched.mesh is None  # the sidecar protocol is single-device


# ---------------------------------------------------------------------------
# batched per-dispatch condition writes (fused replay satellite)
# ---------------------------------------------------------------------------

def test_fused_dispatch_single_condition_flush(cpu_devices):
    """A non-pipelined fused dispatch must drain ALL its logical cycles'
    PodScheduled writes in one flush after the wave replay — no condition
    write may interleave with a later wave's bind writes."""
    from koordinator_tpu.client.store import KIND_POD
    from koordinator_tpu.scheduler.cycle import Scheduler
    from koordinator_tpu.scheduler.pipeline_parity import (
        build_store_from_state,
    )
    from koordinator_tpu.testing import synth_full_cluster

    _cluster, state = synth_full_cluster(24, 70, seed=11, num_quotas=3,
                                         num_gangs=4, topology_fraction=0.5,
                                         lsr_fraction=0.2)
    store = build_store_from_state(state)
    events = []

    def on_pod(ev, pod, old):
        cond = pod.get_condition("PodScheduled")
        if cond is not None and cond.status == "False":
            events.append(("cond", pod.meta.key))
        elif pod.is_assigned and (old is None or not old.is_assigned):
            events.append(("bind", pod.meta.key))

    store.subscribe(KIND_POD, on_pod)
    sched = Scheduler(store, waves=4, mesh="off")
    assert sched.pipeline_mode is False
    res = sched.run_cycle(now=state.now)
    assert res.waves >= 1
    conds = [i for i, e in enumerate(events) if e[0] == "cond"]
    binds = [i for i, e in enumerate(events) if e[0] == "bind"]
    assert conds, "fixture produced no unschedulable pods"
    assert binds, "fixture produced no bindings"
    # one flush per dispatch: every condition write lands after the last
    # bind of the whole dispatch, not interleaved per wave
    assert min(conds) > max(binds)
    assert not sched._deferred_diagnose  # drained, not leaked
    assert sched._defer_condition_writes is False


# ---------------------------------------------------------------------------
# sharding helpers: padding + multi-host branch + dtype preservation
# ---------------------------------------------------------------------------

def test_put_on_mesh_pads_1023_node_snapshot(cpu_devices):
    """1023 nodes on 8 devices: the helpers pad to the mesh factor
    internally; bindings must match the single-device step bit-for-bit."""
    from koordinator_tpu.models.scheduler_model import (
        build_schedule_step,
        make_inputs,
    )
    from koordinator_tpu.ops.loadaware import (
        LoadAwareArgs,
        build_loadaware_node_state,
    )
    from koordinator_tpu.ops.packing import pack_nodes, pack_pods
    from koordinator_tpu.parallel import (
        build_sharded_schedule_step,
        make_mesh,
        shard_inputs_nodewise,
    )
    from koordinator_tpu.testing import synth_cluster

    args = LoadAwareArgs()
    cluster = synth_cluster(num_nodes=1023, num_pods=64, seed=2)
    pods = pack_pods(cluster.pods, args.resource_weights,
                     args.estimated_scaling_factors)
    nodes = pack_nodes(cluster.nodes, pad_to=1023)  # forced odd axis
    nodes.extras = build_loadaware_node_state(
        cluster.nodes, cluster.node_metrics, cluster.pods_by_key,
        cluster.assigned, args, cluster.now, pad_to=1023)
    inputs = make_inputs(pods, nodes, args)
    assert inputs.allocatable.shape[0] == 1023

    chosen_1, _ = build_schedule_step(args)(inputs)
    mesh = make_mesh(cpu_devices)
    sharded = shard_inputs_nodewise(inputs, mesh)
    assert sharded.allocatable.shape[0] == 1024  # padded inside the helper
    assert sharded.pod_valid.shape == inputs.pod_valid.shape  # replicated
    chosen_8, _ = build_sharded_schedule_step(args, mesh)(sharded)
    np.testing.assert_array_equal(np.asarray(chosen_1),
                                  np.asarray(chosen_8))
    assert (np.asarray(chosen_1)[: pods.num_valid] >= 0).sum() > 0


def test_shard_inputs_2d_pads_both_axes(cpu_devices):
    from jax.sharding import Mesh

    from koordinator_tpu.parallel import make_mesh, shard_inputs_2d
    from koordinator_tpu.models.scheduler_model import make_inputs
    from koordinator_tpu.ops.loadaware import (
        LoadAwareArgs,
        build_loadaware_node_state,
    )
    from koordinator_tpu.ops.packing import pack_nodes, pack_pods
    from koordinator_tpu.testing import synth_cluster

    args = LoadAwareArgs()
    cluster = synth_cluster(num_nodes=29, num_pods=17, seed=4)
    pods = pack_pods(cluster.pods, args.resource_weights,
                     args.estimated_scaling_factors, pad_to=17)
    nodes = pack_nodes(cluster.nodes, pad_to=29)
    nodes.extras = build_loadaware_node_state(
        cluster.nodes, cluster.node_metrics, cluster.pods_by_key,
        cluster.assigned, args, cluster.now, pad_to=29)
    inputs = make_inputs(pods, nodes, args)
    mesh = make_mesh(cpu_devices)  # 2 x 4: pods x 2, nodes x 4
    assert isinstance(mesh, Mesh)
    sharded = shard_inputs_2d(inputs, mesh)
    assert sharded.fit_requests.shape[0] % 2 == 0   # pods axis padded
    assert sharded.allocatable.shape[0] % 4 == 0    # nodes axis padded
    assert sharded.weights.shape == inputs.weights.shape  # replicated


def test_shard_inputs_preserve_dtypes(cpu_devices):
    """Every field of shard_inputs_nodewise / shard_inputs_2d /
    shard_full_chain_inputs keeps its host dtype — an implicit upcast
    would silently change kernel numerics on the mesh only."""
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.parallel import (
        make_mesh,
        shard_full_chain_inputs,
        shard_inputs_2d,
        shard_inputs_nodewise,
    )
    from koordinator_tpu.scheduler.snapshot import build_full_chain_inputs
    from koordinator_tpu.testing import synth_full_cluster

    args = LoadAwareArgs()
    _cluster, state = synth_full_cluster(12, 24, seed=6)
    fc, *_rest = build_full_chain_inputs(state, args)
    mesh = make_mesh(cpu_devices)
    for sharder, val in (
        (shard_inputs_nodewise, fc.base),
        (shard_inputs_2d, fc.base),
        (shard_full_chain_inputs, fc),
    ):
        out = sharder(val, mesh)
        for name in type(val)._fields:
            host = getattr(val, name)
            dev = getattr(out, name)
            if name == "base":
                continue  # covered by the nodewise pass above
            assert np.asarray(dev).dtype == np.asarray(host).dtype, (
                sharder.__name__, name)


def test_put_on_mesh_multihost_branch(cpu_devices):
    """The make_array_from_callback path (taken when the mesh spans
    processes): a fake non-fully-addressable sharding must still produce
    an array whose shard-local slices match the host array exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from koordinator_tpu.parallel import make_mesh, put_on_mesh

    class FakeMultiHost(NamedSharding):
        """Claims not-fully-addressable, forcing the callback path."""

        @property
        def is_fully_addressable(self):
            return False

    mesh = make_mesh(cpu_devices)
    sharding = FakeMultiHost(mesh, P(("pods", "nodes")))
    rng = np.random.default_rng(7)
    for dtype in (np.float32, np.int32, bool):
        host = (rng.random((42, 3)) * 10).astype(dtype)  # 42 -> pad 48
        arr = put_on_mesh(host, sharding)
        assert arr.shape == (48, 3)
        assert arr.dtype == host.dtype
        padded = np.zeros((48, 3), dtype)
        padded[:42] = host
        for sh in arr.addressable_shards:
            np.testing.assert_array_equal(np.asarray(sh.data),
                                          padded[sh.index])


def test_pad_for_sharding_noop_when_divisible(cpu_devices):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from koordinator_tpu.parallel import make_mesh, pad_for_sharding

    mesh = make_mesh(cpu_devices)
    sharding = NamedSharding(mesh, P(("pods", "nodes")))
    arr = np.ones((64, 3), np.float32)
    out = pad_for_sharding(arr, sharding)
    assert out is arr  # divisible: pass-through, no copy
    rep = NamedSharding(mesh, P())
    odd = np.ones((7, 3), np.float32)
    assert pad_for_sharding(odd, rep) is odd  # replicated: never padded


def test_mesh_row_layout_imbalance(cpu_devices):
    from koordinator_tpu.parallel import make_mesh, mesh_row_layout

    mesh = make_mesh(cpu_devices)
    rows = mesh_row_layout(mesh, n_real=30, n_padded=32)
    assert rows == [4, 4, 4, 4, 4, 4, 4, 2]
    assert sum(rows) == 30
