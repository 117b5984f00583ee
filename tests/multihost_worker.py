"""Worker process for the jax.distributed multi-host tests (2 and 4
processes).

Each process owns `local_devices` virtual CPU devices;
`jax.distributed.initialize()` federates them into one global mesh (the
DCN analog — process boundary == host boundary). Every process builds the
identical synthetic cluster, runs the sharded full-chain step over the
GLOBAL mesh (gloo collectives across the process boundary), and diffs the
bindings against a locally-computed single-device run. In the 4-process
shape the mesh is 2-D (pods x nodes), so the one-shot score matrix shards
BOTH batch axes across the process boundary. Prints ``MULTIHOST_OK
<digest>`` so the parent test can also assert all processes agree.

Usage: python multihost_worker.py <process_id> <num_processes> <port>
       [local_devices=4]
"""

import hashlib
import os
import re
import sys


def main() -> None:
    proc_id, num_procs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    local_devices = int(sys.argv[4]) if len(sys.argv) > 4 else 4
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={local_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # pin the CPU in jax.config too, before any backend initializes
    # (the same order as conftest.py)
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        f"127.0.0.1:{port}", num_processes=num_procs, process_id=proc_id
    )
    assert jax.device_count() == local_devices * num_procs, jax.devices()
    assert jax.local_device_count() == local_devices

    import numpy as np

    from koordinator_tpu.models.full_chain import build_full_chain_step
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.parallel import (
        build_sharded_full_chain_step,
        make_mesh,
        shard_full_chain_inputs,
    )
    from koordinator_tpu.scheduler.snapshot import build_full_chain_inputs
    from koordinator_tpu.testing import synth_full_cluster

    args = LoadAwareArgs()
    _, state = synth_full_cluster(30, 60, seed=0)
    fc, pods, _, _, _, ng, ngroups = build_full_chain_inputs(state, args)

    # single-device reference on this process's local device
    chosen_1, requested_1, quota_1 = build_full_chain_step(args, ng, ngroups)(fc)
    chosen_1 = np.asarray(chosen_1)

    # global mesh spanning both processes
    mesh = make_mesh(jax.devices())
    step = build_sharded_full_chain_step(args, ng, ngroups, mesh)
    chosen_g, requested_g, quota_g = step(shard_full_chain_inputs(fc, mesh))
    chosen_g = np.asarray(chosen_g)  # replicated -> locally addressable

    np.testing.assert_array_equal(chosen_1, chosen_g)
    np.testing.assert_array_equal(np.asarray(quota_1), np.asarray(quota_g))
    assert (chosen_1[: len(pods.keys)] >= 0).sum() > 0, "vacuous schedule"

    # second pass at a bucketed-with-PADDING shape (500 pods x 250 nodes
    # pad to 512 x 256, so pad rows actually cross the shard boundary):
    # bucket/pad/shard interplay across the real process boundary, not
    # just the toy fixture (the single-process dryrun covers 2048x1024;
    # gloo collectives over CPU bound what is CI-affordable here). Runs
    # through reduce_to_active_axes like the production cycle, and checks
    # the quota rollup parity on the reduced axes too.
    from koordinator_tpu.scheduler.snapshot import reduce_to_active_axes

    _, big_state = synth_full_cluster(250, 500, seed=1)
    big_fc, big_pods, _, _, _, bng, bngroups = build_full_chain_inputs(
        big_state, args)
    big_fc, big_axes = reduce_to_active_axes(big_fc)
    assert big_fc.base.fit_requests.shape[0] > len(big_pods.keys)  # padded
    big_ref, _, big_quota_ref = build_full_chain_step(
        args, bng, bngroups, active_axes=big_axes)(big_fc)
    big_ref = np.asarray(big_ref)
    big_step = build_sharded_full_chain_step(
        args, bng, bngroups, mesh, active_axes=big_axes)
    big_g, _, big_quota_g = big_step(shard_full_chain_inputs(big_fc, mesh))
    big_g = np.asarray(big_g)
    np.testing.assert_array_equal(big_ref, big_g)
    np.testing.assert_array_equal(
        np.asarray(big_quota_ref), np.asarray(big_quota_g))
    assert (big_g[: len(big_pods.keys)] >= 0).sum() > 100

    # third pass: the one-shot [P, N] score matrix sharded over BOTH mesh
    # axes (pods x nodes) at the same padded 512 x 256 shape — with >= 2
    # processes per axis (the 4-process shape), every shard boundary of
    # both batch axes crosses a process boundary. Feasibility and score
    # must match the local single-device matrix bit-for-bit.
    from koordinator_tpu.models.scheduler_model import build_score_matrix
    from koordinator_tpu.parallel import (
        build_sharded_score_matrix,
        shard_inputs_2d,
    )

    matrix = build_sharded_score_matrix(args, mesh)
    feas_g, score_g = matrix(shard_inputs_2d(big_fc.base, mesh))
    # the matrix outputs stay sharded across processes (unlike the
    # replicated chosen vector): assemble the global arrays via the DCN
    # allgather before host comparison
    from jax.experimental import multihost_utils

    feas_g = np.asarray(multihost_utils.process_allgather(feas_g, tiled=True))
    score_g = np.asarray(
        multihost_utils.process_allgather(score_g, tiled=True))
    feas_1, score_1 = build_score_matrix(args)(big_fc.base)
    np.testing.assert_array_equal(np.asarray(feas_1), feas_g)
    np.testing.assert_array_equal(np.asarray(score_1), score_g)
    assert feas_g.shape[0] > len(big_pods.keys)  # padding crossed shards

    digest = hashlib.sha256(
        chosen_g.tobytes() + big_g.tobytes() + feas_g.tobytes()
        + score_g.tobytes()).hexdigest()[:16]
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    print(f"MULTIHOST_OK {digest} mesh={mesh_shape}", flush=True)


if __name__ == "__main__":
    main()
