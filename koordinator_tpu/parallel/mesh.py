"""Mesh layout and sharded scheduling steps.

Two shardings cover the framework's compute:

  1. serial-parity step: node-state arrays ([N, R], [N]) sharded over ALL devices
     on the "nodes" axis; pod arrays replicated. Each fori_loop iteration's
     filter/score row is computed shard-locally; the argmax reduces across shards
     (XLA all-reduce over ICI). This preserves exact serial semantics at any mesh
     size — the distributed analog of kube-scheduler's per-node fan-out.

  2. score-matrix / rebalance: 2-D mesh ("pods", "nodes"); the [P, N] score matrix
     shards over both axes — full SPMD for the descheduler's 50k-pod global
     rebalance (BASELINE.md config 5) and throughput mode.

Multi-host: the same code runs under `jax.distributed.initialize()`; mesh axes laid
out so "nodes" stays within a slice (ICI) and "pods" may span slices (DCN), since
the pods axis only needs its collectives at the final argmax/top-k. Exercised by
`tests/test_multihost.py`: two OS processes x 4 virtual CPU devices federate into
one 8-device mesh and run the sharded full-chain step with gloo collectives
crossing the process boundary, bit-identical to single-device.
"""

from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.models.scheduler_model import (
    ScheduleInputs,
    build_schedule_step,
    build_score_matrix,
)
from koordinator_tpu.ops.loadaware import LoadAwareArgs

logger = logging.getLogger(__name__)


def make_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D mesh ("pods", "nodes"); the nodes axis gets the larger factor (node
    count exceeds pending-pod count in the target configs)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    pods_dim = 1
    for f in range(int(np.sqrt(n)), 0, -1):
        if n % f == 0:
            pods_dim = f
            break
    nodes_dim = n // pods_dim
    dev_array = np.array(devices).reshape(pods_dim, nodes_dim)
    return Mesh(dev_array, axis_names=("pods", "nodes"))


def size_rule_shards(vmem_bytes: int, n_devices: int,
                     budget: Optional[int]) -> int:
    """The default path's mesh choice: shard the node axis over every
    visible chip only where the one-chip Pallas kernel cannot hold the
    cluster, i.e. its VMEM estimate at the cluster's node bucket
    (``models.full_chain.node_axis_vmem_bytes``) is over the device
    kind's budget — the selector's own test, at the fewest axes a batch
    keeps. No budget (off TPU) or one chip: 1, the single-device path."""
    if budget is None or n_devices < 2 or vmem_bytes <= budget:
        return 1
    return n_devices


def mesh_env_value() -> str:
    """KOORD_TPU_MESH as set, "" when unset: an explicit value overrides
    the size rule (``size_rule_shards``)."""
    import os

    return os.environ.get("KOORD_TPU_MESH", "").strip().lower()


def mesh_from_env(env_value: Optional[str] = None) -> Optional[Mesh]:
    """KOORD_TPU_MESH=<ndev>|auto pins the production mesh-backed
    dispatch path (scheduler/cycle.py): "auto" takes every visible device,
    an integer takes a prefix of `jax.devices()`. Unset/0/1-device-visible
    "auto"/"off" return None — the single-device path (unset, the
    scheduler applies the size rule instead). A request for more
    devices than exist fails loudly (a silently-smaller mesh would make
    capacity planning lie)."""
    raw = (mesh_env_value() if env_value is None
           else str(env_value)).strip().lower()
    if raw in ("", "0", "off", "false", "no"):
        return None
    devices = jax.devices()
    if raw == "auto":
        if len(devices) < 2:
            return None
        return make_mesh(devices)
    try:
        n = int(raw)
    except ValueError:
        logger.warning("KOORD_TPU_MESH=%r not an int or 'auto'; "
                       "mesh dispatch stays off", raw)
        return None
    if n <= 1:
        # a 1-device mesh is still a valid mesh (the parity gates use it);
        # pin it explicitly with KOORD_TPU_MESH=1
        if raw == "1":
            return make_mesh(devices[:1])
        return None
    if n > len(devices):
        raise ValueError(
            f"KOORD_TPU_MESH={n} but only {len(devices)} devices visible")
    return make_mesh(devices[:n])


def surviving_submesh(mesh: Mesh, lost_device_ids) -> Optional[Mesh]:
    """The partial-mesh rung's submesh (koordguard): the configured mesh
    minus the devices a dispatch fault was attributed to, re-factored
    2-D by ``make_mesh``. Non-divisible node axes re-pad through the
    existing ``pad_for_sharding`` on upload, so any survivor count is a
    valid mesh. The scheduler records losses only while survivors
    remain, so its calls never see the defensive None (returned when
    nothing survives) — a caller that can reach it must drop to its
    single-device rung itself."""
    lost = {int(i) for i in lost_device_ids}
    survivors = [d for d in mesh.devices.flat if d.id not in lost]
    if not survivors:
        return None
    return make_mesh(survivors)


def mesh_program(raw):
    """``raw`` (a builder's unjitted step) renamed ``<its name>_mesh``, so
    the sharded program is told apart from the single-device one in a
    device trace's ``XLA Modules``."""
    def program(*operands):
        return raw(*operands)

    program.__name__ = program.__qualname__ = raw.__name__ + "_mesh"
    return program


def _node_axis_spec(mesh: Mesh, flat: bool) -> P:
    # serial mode shards nodes over every device (both mesh axes)
    return P(("pods", "nodes")) if flat else P("nodes")


def _shard_counts(sharding: NamedSharding, ndim: int) -> Tuple[int, ...]:
    """Shards per dimension a NamedSharding splits an ndim-array into."""
    spec = sharding.spec
    sizes = dict(sharding.mesh.shape)
    counts = []
    for d in range(ndim):
        entry = spec[d] if d < len(spec) else None
        if entry is None:
            counts.append(1)
        elif isinstance(entry, (tuple, list)):
            f = 1
            for ax in entry:
                f *= sizes[ax]
            counts.append(f)
        else:
            counts.append(sizes[entry])
    return tuple(counts)


def pad_for_sharding(arr: np.ndarray, sharding: NamedSharding) -> np.ndarray:
    """Zero-pad each sharded dimension up to the next multiple of its shard
    count, so callers never pre-quantize axis sizes to the mesh factor.

    Zero rows reproduce the snapshot build's own bucket-pad semantics
    exactly (node_ok/allocatable/pod_valid all zero -> the row is
    infeasible for every kernel), which is why padding here cannot perturb
    bindings — the regression gate is test_parallel's 1023-node fixture.
    Divisible shapes pass through untouched (no copy)."""
    arr = np.asarray(arr)
    counts = _shard_counts(sharding, arr.ndim)
    widths = []
    needs = False
    for size, c in zip(arr.shape, counts):
        pad = (-size) % c
        widths.append((0, pad))
        needs = needs or pad > 0
    if not needs:
        return arr
    return np.pad(arr, widths)


def put_on_mesh(arr, sharding: NamedSharding):
    """Place host data on a (possibly multi-host) sharding, zero-padding
    non-divisible sharded axes (`pad_for_sharding`). Single-process meshes
    take the fast `device_put` path; when the mesh spans processes
    (`jax.distributed.initialize()`), each process materializes only its
    addressable shards from the (identically computed) host array."""
    arr = pad_for_sharding(np.asarray(arr), sharding)
    if sharding.is_fully_addressable:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def merge_readback(*arrays) -> Tuple[List[np.ndarray], Dict[int, int]]:
    """Materialize kernel outputs to host numpy, merging from the per-shard
    device buffers, and account the bytes each mesh device actually holds
    for them.

    The sharded steps pin their compacted readback outputs (chosen /
    bind_pods / bind_nodes / bind_zones / wave_counts) to a REPLICATED
    sharding, so every shard holds the full buffer in the same packed order
    the serial driver replays; the merge reads one addressable copy and the
    per-shard byte map feeds the `koord_scheduler_mesh_readback_bytes`
    gauges (shard-imbalance regressions must be visible, not inferred).
    Blocking is intended: this IS the mesh path's designated sync point."""
    out: List[np.ndarray] = []
    per_shard: Dict[int, int] = {}
    for arr in arrays:
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            for sh in shards:
                per_shard[sh.device.id] = (
                    per_shard.get(sh.device.id, 0) + int(sh.data.nbytes))
        # koordlint: disable=unsharded-transfer-in-mesh-path
        out.append(np.asarray(arr))
    return out, per_shard


def mesh_row_layout(mesh: Mesh, n_real: int, n_padded: int) -> List[int]:
    """REAL (unpadded) node rows owned by each shard of the flat node
    sharding, in device order — the shard-imbalance observability input.
    With the node axis padded to `n_padded` over D devices each shard owns
    `n_padded // D` rows; trailing shards may hold only pad rows."""
    ndev = mesh.devices.size
    per = n_padded // ndev if ndev else 0
    return [max(0, min(per, n_real - i * per)) for i in range(ndev)]


def shard_inputs_nodewise(inputs: ScheduleInputs, mesh: Mesh) -> ScheduleInputs:
    """Sharding for the serial-parity step: node arrays sharded over all devices,
    pod arrays + weights replicated."""
    node_spec = _node_axis_spec(mesh, flat=True)
    pod_fields = {
        "fit_requests",
        "estimated",
        "is_prod",
        "is_daemonset",
        "pod_valid",
        "weights",
    }

    def put(name, arr):
        spec = P() if name in pod_fields else node_spec
        return put_on_mesh(arr, NamedSharding(mesh, spec))

    return ScheduleInputs(**{k: put(k, v) for k, v in inputs._asdict().items()})


def shard_inputs_2d(inputs: ScheduleInputs, mesh: Mesh) -> ScheduleInputs:
    """Sharding for the one-shot matrix: pods over "pods", nodes over "nodes"."""
    pod_fields = {"fit_requests", "estimated", "is_prod", "is_daemonset", "pod_valid"}

    def put(name, arr):
        if name == "weights":
            spec = P()
        elif name in pod_fields:
            spec = P("pods")
        else:
            spec = P("nodes")
        return put_on_mesh(arr, NamedSharding(mesh, spec))

    return ScheduleInputs(**{k: put(k, v) for k, v in inputs._asdict().items()})


def build_sharded_schedule_step(args: LoadAwareArgs, mesh: Mesh):
    """Serial-parity step jitted with node-sharded in/out shardings."""
    raw = build_schedule_step(args, jit=False)
    node_spec = _node_axis_spec(mesh, flat=True)
    out_shardings = (
        NamedSharding(mesh, P()),          # chosen [P] replicated
        NamedSharding(mesh, node_spec),    # requested [N, R]
    )
    return jax.jit(mesh_program(raw), out_shardings=out_shardings)


def build_sharded_score_matrix(args: LoadAwareArgs, mesh: Mesh):
    """One-shot [P, N] matrix jitted over the 2-D mesh."""
    raw = build_score_matrix(args, jit=False)
    out = NamedSharding(mesh, P("pods", "nodes"))
    return jax.jit(mesh_program(raw), out_shardings=(out, out))
