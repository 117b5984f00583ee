"""Sharded FULL plugin-chain step: the flagship kernel over a device mesh.

Distributes the fused chain of models/full_chain.py — gang PreFilter, quota
admission, Fit/LoadAware/cpuset/NUMA filters, LoadAware+NUMA scoring, serial
Reserve, gang Permit barrier — the same way the base serial-parity step is
distributed (parallel/mesh.py): the distributed analog of the reference's
per-node goroutine fan-out at
/root/reference/pkg/scheduler/frameworkext/framework_extender.go:204.

Layout:
  * node-axis state sharded over ALL mesh devices ("pods"+"nodes" axes flat):
    allocatable/requested/usage [N, R], NUMA free/capacity [N, K, R], cpuset
    bind state [N] — each fori_loop iteration's filter+score row is computed
    shard-locally and the argmax reduces across shards (ICI all-reduce).
  * pod arrays replicated ([P, ...] is small: the batch, not the cluster).
  * quota tree replicated ([G, R] is tiny); the order-dependent admission check
    and used-rollup run identically on every shard, so the carried quota state
    never needs a collective.
  * gang arrays replicated; the Permit barrier is a segment reduction over the
    replicated `chosen` vector, computed post-loop on every shard.

Bindings are bit-identical to the single-device step at any mesh size: the
per-shard score rows are the same values the unsharded kernel computes, and
argmax tie-breaking (lowest node index) is preserved by XLA's cross-shard
argmax reduction over the global index space.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from koordinator_tpu.models.full_chain import (
    ExplainOut,
    FullChainInputs,
    build_full_chain_step,
)
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.parallel.mesh import (
    _node_axis_spec,
    mesh_program,
    put_on_mesh,
    shard_inputs_nodewise,
)

# FullChainInputs fields indexed [N, ...] (sharded); everything else (pods,
# quota tree, gangs) is replicated.
_FC_NODE_FIELDS = frozenset(
    {
        "numa_free",
        "numa_capacity",
        "numa_policy",
        "has_topology",
        "bind_free",
        "cpus_per_core",
        "node_taint_group",
        "aff_dom",
        "aff_count",
        "anti_cover",
        "pref_scores",
        "port_used",
        "vol_free",
        "node_vol_group",
        "img_scores",
    }
)


def shard_full_chain_inputs(fc: FullChainInputs, mesh: Mesh) -> FullChainInputs:
    """Place FullChainInputs on the mesh: node state sharded over all devices,
    pod/quota/gang state replicated."""
    node_spec = _node_axis_spec(mesh, flat=True)
    base = shard_inputs_nodewise(fc.base, mesh)

    def put(name, arr):
        spec = node_spec if name in _FC_NODE_FIELDS else P()
        return put_on_mesh(arr, NamedSharding(mesh, spec))

    rest = {k: put(k, v) for k, v in fc._asdict().items() if k != "base"}
    return FullChainInputs(base=base, **rest)


def build_sharded_full_chain_step(
    args: LoadAwareArgs,
    num_gangs: int,
    num_groups: int,
    mesh: Mesh,
    active_axes=None,
    explain=None,
):
    """Full-chain step jitted with node-sharded in/out shardings.

    Same contract as build_full_chain_step:
    FullChainInputs -> (chosen[P], requested[N, R], quota_used[G, R]),
    plus the ExplainOut 4th output (and the extra ``n_real`` operand) when
    ``explain`` is "counts"/"full" — attribution arrays are pod-axis and
    come back replicated, so the readback merge sees one packed buffer.
    """
    raw = build_full_chain_step(
        args, num_gangs, num_groups, jit=False, active_axes=active_axes,
        explain=explain,
    )
    node_spec = _node_axis_spec(mesh, flat=True)
    rep = NamedSharding(mesh, P())
    out_shardings = (
        rep,                               # chosen [P] replicated
        NamedSharding(mesh, node_spec),    # requested [N, R] node-sharded
        rep,                               # quota_used [G, R] replicated
    )
    if explain is not None:
        # ExplainOut(stage_counts[P, S], terms[P, T] | None): pod-axis,
        # replicated. terms is None below "full" — a pytree NON-leaf, so
        # its sharding slot must be None too or the structures mismatch.
        out_shardings = out_shardings + (
            ExplainOut(rep, rep if explain == "full" else None),)
    return jax.jit(mesh_program(raw), out_shardings=out_shardings)


def build_sharded_fused_wave_step(
    args: LoadAwareArgs,
    num_gangs: int,
    num_groups: int,
    waves: int,
    mesh: Mesh,
    active_axes=None,
    explain=None,
    prod: bool = False,
    claims: bool = False,
    res: bool = False,
    score_passes=(),
):
    """Fused multi-wave step (models/fused_waves.py) jitted over the mesh.

    Same contract as build_fused_wave_step — (FullChainInputs,
    WaveSideInputs) -> FusedWaveOut (+ ExplainOut under koordexplain) —
    with the node-axis carried state sharded exactly like the serial mesh
    step: each wave's filter/score rows compute shard-locally, the argmax
    reduces over ICI, and `commit_pod_state`'s node-row updates stay on
    the owning shard. The compacted (pod, node, zone, res) readback
    buffers are pod-axis and pinned REPLICATED, so the host merge sees
    one packed buffer identical on every shard
    (parallel/mesh.merge_readback). The PR 14 carried extensions follow
    the same split: prod est/adj and hot-claim coverage are node-axis,
    claim membership and reservation rows replicate
    (``wave_side_shardings``).
    """
    from koordinator_tpu.models.fused_waves import (
        FusedWaveOut,
        build_fused_wave_step,
    )

    # no VMEM budget: the XLA search pass (build_sharded_chained_wave_step)
    raw = build_fused_wave_step(
        args, num_gangs, num_groups, waves=waves, jit=False,
        active_axes=active_axes, explain=explain,
        prod=prod, claims=claims, res=res, score_passes=score_passes,
    )
    rep = NamedSharding(mesh, P())
    fw_out = FusedWaveOut(rep, rep, rep, rep, rep, rep)
    if explain is None:
        out_shardings = fw_out
    else:
        out_shardings = (
            fw_out, ExplainOut(rep, rep if explain == "full" else None))
    return jax.jit(mesh_program(raw), out_shardings=out_shardings)


def wave_carry_shardings(mesh: Mesh, explain=None, prod: bool = False,
                         claims: bool = False, res: bool = False):
    """Shardings for the chained wave step's carry tuple: node-axis state
    slots sharded flat over the mesh (the same layout the fused carry has
    inside the sharded while_loop), pod/quota/gang/reservation/term slots
    replicated, feature-absent slots None (matching the carry's leafless
    pytree holes). Used both for the step's out_shardings (so the carried
    state never leaves its shard between wave dispatches) and by the
    driver to place the few host-created wave-0 slots (put_on_mesh)."""
    from koordinator_tpu.models.fused_waves import (
        NUM_WAVE_STATE,
        WAVE_STATE_FIELDS,
        WAVE_STATE_NODE_SLOTS,
    )

    node = NamedSharding(mesh, _node_axis_spec(mesh, flat=True))
    rep = NamedSharding(mesh, P())
    present = {
        "est_sum_prod": prod,
        "claim_new": claims,
        "vol_new": claims,
        "res_avail": res,
        "res_remain": res,
        "res_node": res,
        "res_succ": res,
    }
    carry = tuple(
        (None if not present.get(WAVE_STATE_FIELDS[i], True)
         else node if i in WAVE_STATE_NODE_SLOTS else rep)
        for i in range(NUM_WAVE_STATE))
    if explain == "full":
        carry = carry + (rep,)  # per-pod score-term rows
    return carry


def wave_side_shardings(mesh: Mesh, prod: bool = False,
                        claims: bool = False, res: bool = False):
    """Sharding pytree for WaveSideInputs: [N, ...] operands follow the
    flat node sharding, pod-axis/reservation operands replicate."""
    from koordinator_tpu.models.fused_waves import (
        ClaimSides,
        ProdSides,
        ResSides,
        WaveSideInputs,
    )

    node = NamedSharding(mesh, _node_axis_spec(mesh, flat=True))
    rep = NamedSharding(mesh, P())
    return WaveSideInputs(
        la_est=node,
        la_adj=node,
        prod=ProdSides(est=node, adj=node) if prod else None,
        claims=(ClaimSides(pod_claim=rep, pod_nonhot=rep, covered0=node)
                if claims else None),
        res=(ResSides(owner_match=rep, rank=rep, alloc=rep, once=rep,
                      row_of=rep, pod_slot=rep, nominate_ok=rep)
             if res else None),
    )


def build_sharded_chained_wave_step(
    args: LoadAwareArgs,
    num_gangs: int,
    num_groups: int,
    mesh: Mesh,
    active_axes=None,
    explain=None,
    prod: bool = False,
    claims: bool = False,
    res: bool = False,
    score_passes=(),
):
    """One chained wave (models/fused_waves.build_chained_wave_step)
    jitted over the mesh: the overlapped-replay dispatch unit.

    The carry's node-axis slots are pinned to the flat node sharding on
    OUTPUT, so chaining dispatches keeps every wave's filter/score rows
    shard-local with no resharding between waves; the per-wave compacted
    (pod, node, zone, res) rows come back replicated for the host merge
    (parallel/mesh.merge_readback), exactly like the fused step's
    buffers."""
    from koordinator_tpu.models.fused_waves import (
        WaveChainOut,
        build_chained_wave_step,
    )

    # no VMEM budget: the search pass stays on the XLA evaluator, since
    # a pallas_call does not partition over the node-sharded carry
    raw = build_chained_wave_step(
        args, num_gangs, num_groups, jit=False,
        active_axes=active_axes, explain=explain,
        prod=prod, claims=claims, res=res, score_passes=score_passes,
    )
    rep = NamedSharding(mesh, P())
    rows = WaveChainOut(rep, rep, rep, rep, rep)
    out_shardings = (
        wave_carry_shardings(mesh, explain=explain, prod=prod,
                             claims=claims, res=res),
        rows,
    )
    if explain is not None:
        out_shardings = out_shardings + (rep,)  # this wave's counts row
    return jax.jit(mesh_program(raw), out_shardings=out_shardings)
