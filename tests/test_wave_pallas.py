"""The fused wave body's search pass on the Pallas full-chain kernel
(models/fused_waves.wave_search_backend).

- Parity: the chained step with the Pallas pass 1 gives the XLA chained
  step's WaveChainOut rows and carries, byte for byte, wave after wave;
  the fused while_loop twin likewise. On the CPU the kernel runs in the
  Pallas interpreter, and the builders get a VMEM budget, which only a
  TPU supplies to the scheduler.
- Selection: one rule (models/full_chain.pallas_full_chain_fits) for the
  single-round selector and the wave body, read from shapes and static
  flags; the scheduler's mesh keeps the XLA pass.
"""

import jax
import numpy as np
import pytest

from koordinator_tpu.api.objects import PodAffinityTerm
from koordinator_tpu.models.full_chain import pallas_full_chain_fits
from koordinator_tpu.models.fused_waves import (
    ProdSides,
    WaveSideInputs,
    build_chained_wave_step,
    build_fused_wave_step,
    initial_wave_carry,
    wave_search_backend,
)
from koordinator_tpu.ops import pallas_common as pc
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.scheduler.snapshot import (
    build_full_chain_inputs,
    reduce_to_active_axes,
)
from koordinator_tpu.testing import synth_full_cluster

WAVES = 4
V5E = pc.vmem_budget_bytes("TPU v5 lite")


def _gang_retry(state, la):
    """A two-member gang (min 2) whose first member requires pod affinity
    to ``app=db`` over a cluster-wide zone, and the batch's last non-gang
    pod labeled ``app=db``: wave 1 places the second member alone and
    Permit reverts it; the db pod is kept, so wave 2 places both. Returns
    the gang's pod keys."""
    fc, pods, *_ = build_full_chain_inputs(state, la)
    gid = np.asarray(fc.gang_id)[:len(pods.objs)]
    gmin = np.asarray(fc.gang_min_member)
    gang = next(g for g in sorted(set(gid[gid >= 0].tolist()))
                if (gid == g).sum() == 2 and gmin[g] == 2)
    rows = np.nonzero(gid == gang)[0]
    db = pods.objs[np.nonzero(gid < 0)[0][-1]]
    pods.objs[rows[0]].spec.pod_affinity = [
        PodAffinityTerm(selector={"app": "db"}, topology_key="zone")]
    db.meta.labels["app"] = "db"
    for node in state.nodes:
        node.meta.labels["zone"] = "z"
    return [pods.keys[r] for r in rows]


# the configurations tests/test_pallas_full_chain.py diffs; gang_retry
# adds the gang that Permit reverts in wave 1 and keeps in wave 2, so
# the carried state feeds a second search pass
CASES = {
    "numa-quota-gangs": dict(seed=1, gang_retry=True),
    "second-seed": dict(seed=0),
    "all-topology-lsr": dict(seed=1, topology_fraction=1.0,
                             lsr_fraction=0.4, gang_retry=True),
    "no-quota-no-gang": dict(seed=9, num_quotas=0, num_gangs=0),
    "taints": dict(seed=1, taint_fraction=0.4, gang_retry=True),
    "crosses-pod-block": dict(seed=6, num_nodes=40, num_pods=160),
    "prod-mode": dict(seed=1, prod=True, gang_retry=True),
    "all-axes": dict(seed=1, reduce=False, gang_retry=True),
}


def _batch(seed, num_nodes=24, num_pods=70, prod=False, reduce=True,
           gang_retry=False, **kw):
    la = LoadAwareArgs(score_according_prod_usage=prod)
    _cluster, state = synth_full_cluster(num_nodes, num_pods, seed=seed, **kw)
    watch = _gang_retry(state, la) if gang_retry else []
    fc, pods, nodes, _tree, _gi, ng, ngroups = build_full_chain_inputs(
        state, la)
    ex = nodes.extras
    active = None
    if reduce:
        fc, active = reduce_to_active_axes(fc)

    def side(name):
        arr = ex[name]
        return arr if active is None else np.take(
            arr, np.asarray(active), axis=-1)

    sides = WaveSideInputs(
        la_est=side("la_est_nonprod"), la_adj=side("la_adj_nonprod"),
        prod=(ProdSides(side("la_est_prod"), side("la_adj_prod"))
              if prod else None))
    watch_rows = [pods.keys.index(k) for k in watch]
    return la, fc, sides, ng, ngroups, active, watch_rows


def _pallas_kw(pallas: bool):
    return (dict(vmem_budget_bytes=V5E, volumes=True, interpret=True)
            if pallas else {})


def _chain(batch, pallas: bool):
    la, fc, sides, ng, ngroups, active, _w = batch
    step = build_chained_wave_step(la, ng, ngroups, active_axes=active,
                                   prod=sides.prod is not None,
                                   **_pallas_kw(pallas))
    carry = initial_wave_carry(fc, sides)
    waves = []
    for _ in range(WAVES):
        carry, rows = step(fc, carry, sides)
        waves.append((jax.tree.map(np.asarray, rows),
                      [None if c is None else np.asarray(c)
                       for c in carry]))
    return waves


@pytest.mark.parametrize("case", list(CASES))
def test_chained_pallas_pass_matches_xla_chain(case):
    batch = _batch(**CASES[case])
    la, fc, _sides, *_ = batch
    assert wave_search_backend(fc, V5E, True) == "pallas"
    xla, pallas = _chain(batch, False), _chain(batch, True)
    for w, ((rows_x, carry_x), (rows_p, carry_p)) in enumerate(
            zip(xla, pallas)):
        for field, a, b in zip(rows_x._fields, rows_x, rows_p):
            assert np.array_equal(a, b), (w, field)
        for slot, (a, b) in enumerate(zip(carry_x, carry_p)):
            assert (a is None) == (b is None), (w, slot)
            if a is not None:
                assert a.dtype == b.dtype and np.array_equal(a, b), (w, slot)
    counts = [int(rows.count) for rows, _ in xla]
    assert counts[0] > 0
    watch = batch[-1]
    if watch:
        # the gang stays out of wave 1 (Permit reverted its placed
        # member) and binds whole in wave 2
        def bound(w):
            rows = xla[w][0]
            return set(rows.bind_pods[:int(rows.count)].tolist())

        assert not bound(0) & set(watch)
        assert set(watch) <= bound(1)


@pytest.mark.parametrize("case", ["numa-quota-gangs", "all-topology-lsr",
                                  "prod-mode"])
def test_fused_twin_pallas_pass_matches_xla(case):
    la, fc, sides, ng, ngroups, active, _w = _batch(**CASES[case])
    outs = [build_fused_wave_step(la, ng, ngroups, waves=WAVES,
                                  active_axes=active,
                                  prod=sides.prod is not None,
                                  **_pallas_kw(p))(fc, sides)
            for p in (False, True)]
    for field, a, b in zip(outs[0]._fields, *outs):
        assert np.array_equal(np.asarray(a), np.asarray(b)), field


def test_pallas_pass_is_in_the_wave_program():
    la, fc, sides, ng, ngroups, active, _w = _batch(**CASES["second-seed"])
    carry = initial_wave_carry(fc, sides)
    for pallas in (False, True):
        step = build_chained_wave_step(la, ng, ngroups, jit=False,
                                       active_axes=active,
                                       **_pallas_kw(pallas))
        text = str(jax.make_jaxpr(step)(fc, carry, sides))
        assert ("pallas_call" in text) == pallas


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def template():
    """A small reduced batch (P=128, N=64, R=3, K=2, no affinity terms)
    whose shapes are rescaled without allocating: the rule reads shapes
    and static flags alone."""
    _, state = synth_full_cluster(60, 100, seed=0, num_quotas=8,
                                  num_gangs=4)
    fc, *_rest = build_full_chain_inputs(state, LoadAwareArgs())
    fc, _active = reduce_to_active_axes(fc)
    assert fc.base.fit_requests.shape == (128, 3)
    assert fc.numa_free.shape == (64, 2, 3)
    return fc


def _shaped(fc, P, N, VG=None, vol=0.0):
    size = {128: P, 64: N}

    def leaf(x):
        x = np.asarray(x)
        return np.broadcast_to(np.zeros((), x.dtype),
                               tuple(size.get(d, d) for d in x.shape))

    out = jax.tree.map(leaf, fc)
    vg = out.vol_needed.shape[1] if VG is None else VG
    return out._replace(vol_needed=np.broadcast_to(
        np.asarray(vol, np.float32), (P, vg)))


# (P, N, VG, volumes) -> the kernel the rule picks on the v5e budget
RULE = [
    ((10_240, 5_120, None, False), True),    # the backlog cell's shape
    ((10_240, 5_120, None, True), True),
    ((12_288, 5_120, None, False), True),
    ((16_384, 5_120, None, False), False),   # 20 SMEM rows a pod: 1.3 MB
    ((2_048, 131_072, None, False), False),  # over the VMEM budget
    ((10_240, 5_120, 64, True), False),      # volume rows past SMEM
    ((10_240, 5_120, 64, False), True),      # compiled out when unused
]


@pytest.mark.parametrize("shape,fits", RULE)
def test_rule_on_the_v5e_budget(template, shape, fits):
    P, N, VG, vol = shape
    fc = _shaped(template, P, N, VG)
    assert pallas_full_chain_fits(fc, V5E, vol) is fits
    assert wave_search_backend(fc, V5E, vol) == ("pallas" if fits
                                                  else "xla")
    # explain, a claims batch and no budget (off TPU) keep the XLA pass
    for explain in ("counts", "full"):
        assert wave_search_backend(fc, V5E, vol, explain=explain) == "xla"
    assert wave_search_backend(fc, V5E, vol, claims=True) == "xla"
    assert wave_search_backend(fc, None, vol) == "xla"


def test_single_round_selector_keeps_its_decisions(template, monkeypatch):
    """The single-round selector on a TPU backend (stubbed steps, so
    nothing compiles): the decisions it made before the rule was shared,
    the volume flag resolved only where a variant could fit."""
    from koordinator_tpu.models import full_chain as FC
    from koordinator_tpu.ops import pallas_full_chain as PFC

    monkeypatch.setattr(FC.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(FC, "build_full_chain_step",
                        lambda *a, **k: (lambda fc: "xla"))
    monkeypatch.setattr(PFC, "build_pallas_full_chain_step",
                        lambda *a, **k: (lambda fc: (
                            "pallas", k["enable_volumes"])))
    step = FC.build_best_full_chain_step(LoadAwareArgs(), 4, 4,
                                         vmem_budget_bytes=V5E)
    for (P, N, VG, vol), fits in RULE:
        fc = _shaped(template, P, N, VG, vol=1.0 if vol else 0.0)
        out = step(fc)
        assert step.last_backend == ("pallas" if fits else "xla")
        assert out == (("pallas", vol) if fits else "xla")


def test_scheduler_mesh_keeps_the_xla_pass(template):
    """The scheduler's reading of the rule: a TPU budget and a one-chip
    scheduler pick the Pallas pass; the mesh and no budget keep XLA."""
    from koordinator_tpu.client.store import ObjectStore
    from koordinator_tpu.scheduler.cycle import Scheduler

    fc = _shaped(template, 10_240, 5_120)
    one = Scheduler(ObjectStore())
    assert one._wave_search(fc, None, False) == ("xla", True)  # no budget
    one._vmem_budget = V5E
    assert one._wave_search(fc, None, False) == ("pallas", False)
    assert one._wave_search(fc, "counts", False) == ("xla", True)
    assert one._wave_search(fc, None, True) == ("xla", True)
    meshed = Scheduler(ObjectStore(), mesh=2)
    meshed._vmem_budget = V5E
    assert meshed.mesh is not None
    assert meshed._wave_search(fc, None, False) == ("xla", True)
