"""The main-path kernels compiled for a described TPU v5e (nothing runs).

A compile for a chip that is described and not attached raises what that
chip's compiler would raise: a kernel that needs more scoped VMEM than the
chip allows is refused here, at no chip time. These tests pin that the
Pallas selectors never route a shape to a kernel the v5e compiler refuses
(ops/pallas_common.VMEM_LIMIT_BYTES, the estimate_vmem_bytes bounds), that
BASELINE config 4 (10,240 x 5,120 padded) stays on Pallas, and that the
LoadAware Pallas step and the chained wave step (its search pass on the
Pallas kernel) compile at that shape,
and that the sharded steps the size rule picks for the mesh-50k cell
(50,000 nodes, 57,344 padded, over a 2x2 host) compile with their
cross-chip collectives.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, and the driver's workers import
every test file. The persistent compile cache is off around these
compiles (a cache entry written for a described chip cannot be read back
without one).
"""

import os

import numpy as np
import pytest

R_FULL, K_FULL = 14, 8  # synth_full_cluster without axis reduction


@pytest.fixture(scope="module")
def v5e_2x2():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def template():
    """A tiny reduced full-chain batch: P=128, N=64, R=3, K=2 — every
    other axis has another size, so shapes rescale by value."""
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.scheduler.snapshot import (
        build_full_chain_inputs,
        reduce_to_active_axes,
    )
    from koordinator_tpu.testing import synth_full_cluster

    _, state = synth_full_cluster(60, 100, seed=0, num_quotas=8,
                                  num_gangs=4)
    fc, _pods, _nodes, _tree, _gi, ng, ngroups = build_full_chain_inputs(
        state, LoadAwareArgs())
    fc, _active = reduce_to_active_axes(fc)
    assert fc.base.fit_requests.shape == (128, 3)
    assert fc.numa_free.shape == (64, 2, 3)
    return fc, ng, ngroups


def _shapes(tree, sharding, P, N, R, K):
    import jax

    size = {128: P, 64: N, 3: R, 2: K}

    def leaf(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(tuple(size.get(d, d) for d in x.shape),
                                    x.dtype, sharding=sharding)

    return jax.tree.map(leaf, tree)


def _on_chip(tree, sharding):
    import jax

    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _active(R):
    return None if R == R_FULL else tuple(range(R))


def _compile_pallas_full_chain(template, one_chip, P, N, R, K):
    import jax

    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.ops.pallas_full_chain import (
        build_pallas_full_chain_step,
    )

    fc, ng, ngroups = template
    step = build_pallas_full_chain_step(
        LoadAwareArgs(), ng, ngroups, jit=False, active_axes=_active(R),
        enable_volumes=True)
    return jax.jit(step).lower(_shapes(fc, one_chip, P, N, R, K)).compile()


def _v5e_budget():
    from koordinator_tpu.ops import pallas_common as pc

    return pc.vmem_budget_bytes("TPU v5 lite")


def test_full_chain_pallas_compiles_at_baseline_config4(template, one_chip):
    from koordinator_tpu.ops.pallas_full_chain import estimate_vmem_bytes

    # BASELINE config 4 with every resource axis and NUMA slot: the
    # tightest shape the selector keeps on Pallas (14.1 MiB on v5e)
    assert estimate_vmem_bytes(5_120, R_FULL, K_FULL, 8) \
        <= _v5e_budget()
    compiled = _compile_pallas_full_chain(template, one_chip, 10_240,
                                          5_120, R_FULL, K_FULL)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P,N,R,K", [
    (2_048, 4_096, R_FULL, K_FULL),
    (2_048, 6_144, R_FULL, K_FULL),   # the compiler refuses it: 16.91 MiB
    (2_048, 10_240, R_FULL, K_FULL),  # refused at 28.12 MiB
    (2_048, 8_192, 5, 4),             # zone slices off the 8-row tile
    (2_048, 10_240, 3, 2),            # the production axis reduction
])
def test_full_chain_selector_admits_only_what_compiles(template, one_chip,
                                                       P, N, R, K):
    """estimate <= budget must imply the kernel compiles; a shape past
    the budget is routed to the XLA step and is not compiled here."""
    from koordinator_tpu.ops.pallas_full_chain import estimate_vmem_bytes

    if estimate_vmem_bytes(N, R, K, 8) > _v5e_budget():
        assert (P, N, R, K) != (2_048, 4_096, R_FULL, K_FULL)
        return  # routed to XLA
    _compile_pallas_full_chain(template, one_chip, P, N, R, K)


def test_loadaware_pallas_step_compiles_at_10k_x_5k(template, one_chip):
    import jax

    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.ops.pallas_step import (
        build_pallas_schedule_step,
        estimate_vmem_bytes,
    )

    assert estimate_vmem_bytes(5_120, R_FULL) <= _v5e_budget()
    fc, _ng, _ngroups = template
    step = build_pallas_schedule_step(LoadAwareArgs(), jit=False)
    base = _shapes(fc.base, one_chip, 10_240, 5_120, R_FULL, K_FULL)
    jax.jit(step).lower(base).compile()


def test_chained_wave_step_compiles_at_10k_x_5k(template, one_chip):
    """The chained wave program at the backlog cell's padded shape, built
    as the scheduler builds it on a v5e (its VMEM budget, a volume-less
    batch): the search pass is the Pallas full-chain kernel's custom
    call inside the wave program."""
    import jax

    from koordinator_tpu.models.fused_waves import (
        build_chained_wave_step,
        initial_wave_carry,
        plain_sides,
        wave_search_backend,
    )
    from koordinator_tpu.ops.loadaware import LoadAwareArgs

    fc, ng, ngroups = template
    R = fc.base.fit_requests.shape[1]
    fc_s = _shapes(fc, one_chip, 10_240, 5_120, R, 2)
    nr = jax.ShapeDtypeStruct((5_120, R), np.float32, sharding=one_chip)
    sides = plain_sides(nr, nr)
    carry = _on_chip(jax.eval_shape(initial_wave_carry, fc_s, sides),
                     one_chip)
    assert wave_search_backend(fc_s, _v5e_budget(), False) == "pallas"
    step = build_chained_wave_step(LoadAwareArgs(), ng, ngroups, jit=False,
                                   active_axes=tuple(range(R)),
                                   vmem_budget_bytes=_v5e_budget(),
                                   volumes=False)
    text = jax.jit(step).lower(fc_s, carry, sides).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def mesh_50k(v5e_2x2, template):
    """The template at mesh-50k's padded shape (P=256, N=57,344) placed
    as the scheduler's DeviceSnapshot places it over a described 2x2
    host: node-axis fields flat over the four chips, the rest
    replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from koordinator_tpu.parallel.mesh import make_mesh
    from koordinator_tpu.scheduler.snapshot_cache import _mesh_node_fields

    mesh = make_mesh(list(v5e_2x2.devices))
    node_fields = _mesh_node_fields()
    node = NamedSharding(mesh, P(("pods", "nodes")))
    rep = NamedSharding(mesh, P())
    fc, ng, ngroups = template
    size = {128: 256, 64: 57_344}

    def put(name, x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(
            tuple(size.get(d, d) for d in x.shape), x.dtype,
            sharding=node if name in node_fields else rep)

    base = type(fc.base)(**{k: put(k, v)
                            for k, v in fc.base._asdict().items()})
    fc_s = type(fc)(base=base, **{k: put(k, v) for k, v in
                                  fc._asdict().items() if k != "base"})
    return mesh, fc_s, ng, ngroups, node


def test_sharded_single_round_step_compiles_at_mesh_50k(mesh_50k):
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.ops.pallas_full_chain import estimate_vmem_bytes
    from koordinator_tpu.parallel.full_chain_mesh import (
        build_sharded_full_chain_step,
    )

    mesh, fc_s, ng, ngroups, _node = mesh_50k
    assert estimate_vmem_bytes(57_344, 3, 2, 1_000) > _v5e_budget()
    step = build_sharded_full_chain_step(LoadAwareArgs(), ng, ngroups, mesh,
                                         active_axes=(0, 1, 2))
    text = step.lower(fc_s).compile().as_text()
    assert "all-reduce" in text


def test_sharded_chained_wave_step_compiles_at_mesh_50k(mesh_50k):
    import jax

    from koordinator_tpu.models.fused_waves import (
        initial_wave_carry,
        plain_sides,
    )
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.parallel.full_chain_mesh import (
        build_sharded_chained_wave_step,
        wave_carry_shardings,
    )

    mesh, fc_s, ng, ngroups, node = mesh_50k
    R = fc_s.base.fit_requests.shape[1]
    nr = jax.ShapeDtypeStruct((57_344, R), np.float32, sharding=node)
    sides = plain_sides(nr, nr)
    carry = tuple(
        None if sh is None else jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                     sharding=sh)
        for x, sh in zip(jax.eval_shape(initial_wave_carry, fc_s, sides),
                         wave_carry_shardings(mesh)))
    step = build_sharded_chained_wave_step(LoadAwareArgs(), ng, ngroups,
                                           mesh, active_axes=(0, 1, 2))
    text = step.lower(fc_s, carry, sides).compile().as_text()
    assert "all-reduce" in text
    # the sharded chain keeps the XLA search pass: no Pallas call
    assert "tpu_custom_call" not in text
