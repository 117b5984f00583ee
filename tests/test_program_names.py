"""Every jitted device program carries a name of its own, so a profiler
trace's ``XLA Modules`` line tells the programs apart: the step builders
lowered on the CPU at tiny shapes give distinct module names, none of
them the anonymous ``jit_step``, ``jit_raw`` or ``jit__lambda``."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def batch():
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.scheduler.snapshot import (
        build_full_chain_inputs,
        reduce_to_active_axes,
    )
    from koordinator_tpu.testing import synth_full_cluster

    _, state = synth_full_cluster(20, 30, seed=0, num_quotas=4,
                                  num_gangs=2)
    fc, _pods, _nodes, _tree, _gi, ng, ngroups = build_full_chain_inputs(
        state, LoadAwareArgs())
    fc, active = reduce_to_active_axes(fc)
    return fc, active, ng, ngroups


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split(" ", 1)[0]


def _full_chain_programs(batch):
    from koordinator_tpu.models.full_chain import build_full_chain_step
    from koordinator_tpu.models.fused_waves import (
        build_chained_wave_step,
        build_fused_wave_step,
        initial_wave_carry,
        plain_sides,
    )
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.ops.pallas_full_chain import (
        build_pallas_full_chain_step,
    )

    fc, active, ng, ngroups = batch
    args = LoadAwareArgs()
    n = fc.base.allocatable.shape[0]
    n_real = np.int32(n)
    est = np.zeros(fc.base.allocatable.shape, np.float32)
    sides = plain_sides(est, est)
    carry = initial_wave_carry(fc, sides)
    kw = dict(active_axes=active)
    yield build_full_chain_step(args, ng, ngroups, **kw), (fc,)
    yield (build_full_chain_step(args, ng, ngroups, explain="counts", **kw),
           (fc, n_real))
    yield (build_pallas_full_chain_step(args, ng, ngroups, interpret=True,
                                        **kw), (fc,))
    yield build_fused_wave_step(args, ng, ngroups, 2, **kw), (fc, sides)
    yield (build_fused_wave_step(args, ng, ngroups, 2, explain="counts",
                                 **kw), (fc, sides, n_real))
    yield build_chained_wave_step(args, ng, ngroups, **kw), (fc, carry, sides)
    yield (build_chained_wave_step(args, ng, ngroups, explain="counts", **kw),
           (fc, carry, sides, n_real))


def _mesh_programs(batch):
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.parallel import (
        build_sharded_full_chain_step,
        make_mesh,
    )

    fc, active, ng, ngroups = batch
    mesh = make_mesh()
    yield (build_sharded_full_chain_step(LoadAwareArgs(), ng, ngroups, mesh,
                                         active_axes=active), (fc,))


def _other_programs():
    import jax

    from koordinator_tpu.api.resources import NUM_RESOURCES as R
    from koordinator_tpu.balance.step import build_rebalance_step
    from koordinator_tpu.colo.step import build_colo_step
    from koordinator_tpu.scheduler.snapshot_cache import snapshot_row_scatter

    f32, i32 = np.float32, np.int32
    rows = np.zeros((16, 3), f32)
    yield (jax.jit(snapshot_row_scatter),
           (rows, np.zeros(8, i32), np.zeros((8, 3), f32)))
    n, p = 8, 64
    nr = np.zeros((n, R), f32)
    yield build_rebalance_step(4), (
        nr, np.zeros(n, bool), np.zeros(R, f32), np.zeros(R, f32), nr, nr,
        np.zeros(p, i32), np.zeros(p, i32), np.zeros(p, f32),
        np.zeros((p, R), i32), np.zeros(p, bool))
    g = 8
    gr = np.zeros((g, R), f32)
    yield build_colo_step("dynamic", "static"), (
        (nr,) * 11 + (np.zeros(n, bool), np.zeros(g, i32), np.zeros(g, i32))
        + (gr,) * 6 + (np.zeros(g, bool),) * 3 + (np.zeros(R, f32),))


def test_step_builders_lower_to_distinct_named_modules(batch):
    names = []
    for fn, args in (list(_full_chain_programs(batch))
                     + list(_mesh_programs(batch))
                     + list(_other_programs())):
        names.append(_module_name(fn.lower(*args)))
    assert len(set(names)) == len(names), names
    assert not {"jit_step", "jit_raw", "jit__lambda"} & set(names), names
    assert {"jit_full_chain_xla", "jit_full_chain_pallas", "jit_fused_waves",
            "jit_chained_wave", "jit_full_chain_xla_mesh",
            "jit_snapshot_row_scatter", "jit_rebalance_step",
            "jit_colo_step"} <= set(names), names
