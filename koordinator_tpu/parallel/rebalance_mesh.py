"""Mesh-sharded rebalance pass: the descheduler's production promotion.

The ROADMAP's "teach the descheduler's 2-D score-matrix mode the same
production promotion": ``build_rebalance_step`` (balance/step.py) jitted
over the device mesh. Node-axis inputs (usage/metric columns + the rhs
limbs) arrive SHARDED flat over every device — the DeviceSnapshot
places them via ``put_on_mesh`` under the same NamedShardings the
scheduler's node arrays use (snapshot_cache._mesh_node_fields includes
the ``rb_*`` node fields) — pod arrays replicate, and every output pins
REPLICATED so the compacted (node_idx, pod_idx, score) readback holds
the host victim order on every shard. Same program, same math: byte
parity with the single-device pass is gated by
``pipeline_parity.run_rebalance_parity`` at 1/2/4/8 devices.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.balance.step import build_rebalance_step
from koordinator_tpu.parallel.mesh import mesh_program


def build_sharded_rebalance_step(max_evict_per_node: int, mesh: Mesh):
    """The rebalance pass jitted with replicated out_shardings over
    ``mesh``. Inputs keep whatever placement the DeviceSnapshot upload
    committed them to (node fields sharded, pod fields replicated);
    XLA lowers the node-axis classification shard-locally and inserts
    the candidate-sort collectives."""
    raw = build_rebalance_step(max_evict_per_node, jit=False)
    rep = NamedSharding(mesh, P())
    return jax.jit(mesh_program(raw), out_shardings=rep)
