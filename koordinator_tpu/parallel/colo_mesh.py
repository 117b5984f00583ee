"""Mesh-sharded colo pass: the control plane's production promotion.

``build_colo_step`` (colo/step.py) jitted over the device mesh — the
THIRD consumer of the mesh-backed DeviceSnapshot. Node-axis inputs (the
NodeResource pipeline columns + the degrade mask) arrive SHARDED flat
over every device — the DeviceSnapshot places them via ``put_on_mesh``
under the same NamedShardings the scheduler's node arrays use
(snapshot_cache._mesh_node_fields includes the ``colo_*`` node fields)
— the quota-tree arrays replicate (control-plane scale), and every
output pins REPLICATED so the batch/mid columns, the runtime matrix and
the revoke mask read back whole on every shard. Same program, same
math: decision parity with the single-device pass AND the host oracles
is gated by ``pipeline_parity.run_colo_parity`` at 1/2/4/8 devices.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.colo.step import build_colo_step
from koordinator_tpu.parallel.mesh import mesh_program


def build_sharded_colo_step(cpu_policy: str, memory_policy: str,
                            mesh: Mesh):
    """The colo pass jitted with replicated out_shardings over ``mesh``.
    Inputs keep whatever placement the DeviceSnapshot upload committed
    them to (node fields sharded, quota fields replicated); XLA lowers
    the node-axis batch/mid math shard-locally and inserts the
    column-sum / segment-op collectives for the predicted total and the
    quota fold."""
    raw = build_colo_step(cpu_policy, memory_policy, jit=False)
    rep = NamedSharding(mesh, P())
    return jax.jit(mesh_program(raw), out_shardings=rep)
