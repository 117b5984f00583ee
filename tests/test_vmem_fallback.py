"""VMEM-budget fallback: the backend selectors must degrade to the XLA step
when the Pallas kernel's VMEM-resident state would not fit on-chip, instead
of failing to compile (the kernels pin all node/NUMA/quota state in VMEM —
ops/pallas_step.py documents ~20k nodes at R=16 as the reach)."""

import jax
import numpy as np
import pytest

import koordinator_tpu.models.full_chain as fc_mod
import koordinator_tpu.models.scheduler_model as sm_mod
from koordinator_tpu.ops import pallas_common as pc
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.pallas_full_chain import (
    estimate_vmem_bytes as fc_vmem,
)
from koordinator_tpu.ops.pallas_step import estimate_vmem_bytes as step_vmem
from koordinator_tpu.scheduler.snapshot import build_full_chain_inputs
from koordinator_tpu.testing import synth_full_cluster


V5E = pc.VMEM_LIMIT_BYTES["TPU v5 lite"]


class TestEstimates:
    def test_flagship_shape_fits_default_budget(self):
        # the headline bench config (10k pods x 5k nodes, R=16, K=2,
        # G=64) must stay on the Pallas path, and so must BASELINE config
        # 4 with no axis reduction (R=14, K=8), which the v5e compiler
        # accepts at 14.1 MiB (tests/test_chip_compile.py)
        assert fc_vmem(5_000, 16, 2, 64) <= V5E
        assert fc_vmem(5_120, 14, 8, 8) <= V5E
        assert step_vmem(5_000, 16) <= V5E

    def test_50k_nodes_exceeds_default_budget(self):
        assert fc_vmem(50_000, 16, 2, 64) > V5E
        # the v5e compiler refuses R=14, K=8 at 6,144 nodes (16.91 MiB)
        assert fc_vmem(6_144, 14, 8, 8) > V5E

    def test_monotonic_in_every_dim(self):
        base = fc_vmem(1_000, 16, 2, 64)
        assert fc_vmem(2_000, 16, 2, 64) > base
        assert fc_vmem(1_000, 32, 2, 64) > base
        assert fc_vmem(1_000, 16, 4, 64) > base
        assert fc_vmem(1_000, 16, 2, 300) > base
        assert fc_vmem(1_000, 16, 2, 64, T=4) > base

    def test_budget_table_rejects_unknown_kinds(self):
        assert pc.vmem_budget_bytes("TPU v5 lite") == 16 * 1024 * 1024
        with pytest.raises(ValueError, match="no scoped-VMEM limit"):
            pc.vmem_budget_bytes("TPU v99")


class TestDispatch:
    """Force the TPU selection path on CPU and check which step runs."""

    def _inputs(self):
        args = LoadAwareArgs()
        _, state = synth_full_cluster(16, 24, seed=3)
        fc, *_, ng, ngroups = build_full_chain_inputs(state, args)
        return args, fc, ng, ngroups

    def test_over_budget_uses_xla_and_matches(self, monkeypatch):
        args, fc, ng, ngroups = self._inputs()
        monkeypatch.setattr(fc_mod.jax, "default_backend", lambda: "tpu")
        step = fc_mod.build_best_full_chain_step(
            args, ng, ngroups, vmem_budget_bytes=0)
        chosen, req, qused = step(fc)
        assert step.last_backend == "xla"
        ref_chosen, ref_req, ref_qused = fc_mod.build_full_chain_step(
            args, ng, ngroups)(fc)
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(ref_chosen))
        np.testing.assert_allclose(np.asarray(req), np.asarray(ref_req),
                                   atol=1e-3)

    def test_under_budget_selects_pallas(self, monkeypatch):
        args, fc, ng, ngroups = self._inputs()
        monkeypatch.setattr(fc_mod.jax, "default_backend", lambda: "tpu")
        import koordinator_tpu.ops.pallas_full_chain as pfc

        calls = []
        real_build = pfc.build_pallas_full_chain_step

        def fake_build(*a, **kw):
            real = real_build(*a, interpret=True, **kw)
            return lambda x: calls.append(1) or real(x)

        monkeypatch.setattr(
            "koordinator_tpu.ops.pallas_full_chain."
            "build_pallas_full_chain_step", fake_build)
        step = fc_mod.build_best_full_chain_step(
            args, ng, ngroups, vmem_budget_bytes=1 << 40)
        step(fc)
        assert step.last_backend == "pallas" and calls

    def test_schedule_step_over_budget_uses_xla(self, monkeypatch):
        from koordinator_tpu.ops.loadaware import build_loadaware_node_state
        from koordinator_tpu.ops.packing import pack_nodes, pack_pods
        from koordinator_tpu.testing import synth_cluster

        args = LoadAwareArgs()
        cluster = synth_cluster(num_nodes=16, num_pods=24, seed=7)
        pods = pack_pods(cluster.pods, args.resource_weights,
                         args.estimated_scaling_factors)
        nodes = pack_nodes(cluster.nodes)
        nodes.extras = build_loadaware_node_state(
            cluster.nodes, cluster.node_metrics, cluster.pods_by_key,
            cluster.assigned, args, cluster.now, pad_to=nodes.padded_size)
        inputs = sm_mod.make_inputs(pods, nodes, args)
        monkeypatch.setattr(sm_mod.jax, "default_backend", lambda: "tpu")
        step = sm_mod.build_best_schedule_step(args, vmem_budget_bytes=0)
        chosen, req = step(inputs)
        assert step.last_backend == "xla"
        ref_chosen, _ = sm_mod.build_schedule_step(args)(inputs)
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(ref_chosen))


def test_smem_estimate_guards_high_vg_batches():
    """The flattened volume-group SMEM rows grow with VG; the estimator
    must admit the measured-good shapes (10k pods, VG=1) and reject the
    combination that would blow the 1 MB Mosaic budget (10k pods, VG=16)
    so the dispatch degrades to XLA instead of failing to compile."""
    from koordinator_tpu.ops.pallas_full_chain import (
        SMEM_BUDGET_BYTES,
        estimate_smem_bytes,
    )

    assert estimate_smem_bytes(10_000, VG=1, T=8) <= SMEM_BUDGET_BYTES
    assert estimate_smem_bytes(10_000, VG=16, T=8) > SMEM_BUDGET_BYTES
    # small batches afford the full group budget
    assert estimate_smem_bytes(1_000, VG=16, T=8) <= SMEM_BUDGET_BYTES


def test_volume_less_high_vg_batch_stays_on_pallas_budget():
    """A high-VG batch whose pods mount no new PVCs compiles the volume
    machinery out (1-float placeholder), so the SMEM estimate must admit
    it where the volume-carrying shape would not."""
    from koordinator_tpu.ops.pallas_full_chain import (
        SMEM_BUDGET_BYTES,
        estimate_smem_bytes,
    )

    assert estimate_smem_bytes(10_000, VG=0, T=8) <= SMEM_BUDGET_BYTES
    assert estimate_smem_bytes(10_000, VG=16, T=8) > SMEM_BUDGET_BYTES


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v99"])
def test_scheduler_looks_up_the_budget_at_construction(monkeypatch, kind):
    """The budget is looked up when the Scheduler is built: an unknown TPU
    kind fails there, before any cycle, and never reaches the dispatch
    ladder (which would retry and demote it to host-fallback)."""
    import types

    from koordinator_tpu.client.store import ObjectStore
    from koordinator_tpu.scheduler.cycle import Scheduler

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        jax, "devices", lambda *a: [types.SimpleNamespace(device_kind=kind)])
    if kind in pc.VMEM_LIMIT_BYTES:
        sched = Scheduler(ObjectStore(), mesh="off", warmup="off")
        assert sched._vmem_budget == pc.VMEM_LIMIT_BYTES[kind]
    else:
        with pytest.raises(ValueError, match="no scoped-VMEM limit"):
            Scheduler(ObjectStore(), mesh="off", warmup="off")
