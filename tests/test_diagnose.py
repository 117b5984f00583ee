"""Direct coverage for scheduler/diagnose.py: golden messages per reason
branch, the counts/formatter split, and the kernel attribution pass
(models/full_chain.explain_stage_counts) against the host oracle on each
crafted branch — previously this module was only exercised indirectly
through cycle tests."""

import numpy as np
import pytest

from koordinator_tpu.models.full_chain import (
    EXPLAIN_STAGE_GANG,
    EXPLAIN_STAGE_QUOTA,
    EXPLAIN_STAGES,
    NUM_EXPLAIN_STAGES,
    FullChainInputs,
    explain_stage_counts,
    make_pod_evaluator,
    resolve_weight_idx,
)
from koordinator_tpu.models.scheduler_model import ScheduleInputs
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.scheduler.cycle import _WaveStateMirror
from koordinator_tpu.scheduler.diagnose import (
    GANG_MESSAGE,
    QUOTA_MESSAGE,
    diagnose_unbound,
    format_stage_counts,
    host_stage_counts,
    shared_state,
)

N, P, R, T, K, PT = 3, 1, 2, 1, 2, 1


def make_fc(N=N, P=P, T=T, PT=PT, **over) -> FullChainInputs:
    """A minimal 1-pod x 3-node batch where EVERY stage passes; each test
    flips exactly the arrays that trigger its reason branch. The sizes
    default to the module's and may be raised for batch tests."""
    f32, i32 = np.float32, np.int32

    base = ScheduleInputs(
        fit_requests=np.ones((P, R), f32),
        estimated=np.ones((P, R), f32),
        is_prod=np.zeros(P, bool),
        is_daemonset=np.zeros(P, bool),
        pod_valid=np.ones(P, bool),
        allocatable=np.full((N, R), 10.0, f32),
        requested=np.zeros((N, R), f32),
        node_ok=np.ones(N, bool),
        la_filter_usage=np.zeros((N, R), f32),
        la_has_filter_usage=np.zeros(N, bool),
        la_filter_thresholds=np.zeros((N, R), f32),
        la_prod_thresholds=np.zeros((N, R), f32),
        la_prod_pod_usage=np.zeros((N, R), f32),
        la_term_nonprod=np.zeros((N, R), f32),
        la_term_prod=np.zeros((N, R), f32),
        la_score_valid=np.ones(N, bool),
        la_filter_skip=np.zeros(N, bool),
        weights=np.asarray(LoadAwareArgs().weight_vector()[:R], f32),
    )
    base = base._replace(**{k: np.asarray(v)
                            for k, v in over.items()
                            if k in base._fields})
    fc_over = {k: np.asarray(v) for k, v in over.items()
               if k not in base._fields}
    fc = FullChainInputs(
        base=base,
        requests=np.ones((P, R), f32),
        gang_id=np.full(P, -1, i32),
        quota_id=np.full(P, -1, i32),
        needs_numa=np.zeros(P, bool),
        needs_bind=np.zeros(P, bool),
        cores_needed=np.zeros(P, f32),
        full_pcpus=np.zeros(P, bool),
        pod_taint_mask=np.ones(P, f32),       # bit 0 set
        pod_aff_req=np.zeros((P, T), bool),
        pod_anti_req=np.zeros((P, T), bool),
        pod_aff_match=np.zeros((P, T), bool),
        pod_spread_skew=np.zeros((P, T), f32),
        pod_pref_id=np.full(P, -1, i32),
        pod_ppref_id=np.full(P, -1, i32),
        pod_ppref_mask=np.zeros((P, T), bool),
        pod_port_wants=np.zeros((P, PT), bool),
        vol_needed=np.zeros((P, 1), f32),
        pod_img_id=np.full(P, -1, i32),
        node_taint_group=np.zeros(N, i32),    # group 0 -> bit 0
        aff_dom=np.zeros((N, T), f32),        # all nodes in domain 0
        aff_count=np.zeros((N, T), f32),
        anti_cover=np.zeros((N, T), f32),
        aff_exists=np.zeros(T, bool),
        pref_scores=np.zeros((N, 0), f32),
        port_used=np.zeros((N, PT), f32),
        vol_free=np.full(N, np.inf, f32),
        node_vol_group=np.zeros(N, i32),
        img_scores=np.zeros((N, 1), f32),
        ppref_w=np.zeros((1, T), f32),
        numa_free=np.full((N, K, R), 10.0, f32),
        numa_capacity=np.full((N, K, R), 10.0, f32),
        numa_policy=np.zeros(N, i32),
        has_topology=np.ones(N, bool),
        bind_free=np.full(N, 8.0, f32),
        cpus_per_core=np.ones(N, f32),
        quota_ancestors=np.asarray([[0, -1]], i32),
        quota_used=np.zeros((1, R), f32),
        quota_runtime=np.full((1, R), 100.0, f32),
        gang_min_member=np.ones(1, f32),
        gang_assumed=np.zeros(1, f32),
        gang_valid=np.ones(1, bool),
        gang_group_id=np.zeros(1, i32),
    )
    return fc._replace(**fc_over)


def kernel_counts(fc: FullChainInputs) -> np.ndarray:
    """The on-device attribution pass at cycle-start state, unjitted."""
    import jax
    import jax.numpy as jnp

    # vmap indexes pod rows with tracers: inputs must be device arrays
    # (inside the jitted production step they already are)
    fc = jax.tree_util.tree_map(jnp.asarray, fc)
    evaluate = make_pod_evaluator(
        fc, resolve_weight_idx(LoadAwareArgs(), list(range(R))), False)
    state = (fc.base.requested, fc.numa_free, fc.bind_free, fc.quota_used,
             fc.aff_count, fc.anti_cover, jnp.asarray(fc.aff_exists, bool),
             fc.port_used, fc.vol_free)
    return np.asarray(explain_stage_counts(fc, evaluate, state,
                                           np.int32(N)))


# every reason branch: (name, fc overrides, expected exact message)
BRANCHES = [
    ("gang", dict(gang_id=[0], gang_valid=[False]), GANG_MESSAGE),
    ("quota", dict(quota_id=[0], quota_runtime=[[1.0, 1.0]],
                   requests=[[2.0, 2.0]]), QUOTA_MESSAGE),
    ("unschedulable_node", dict(node_ok=[False] * 3),
     "0/3 nodes are available: 3 node not schedulable."),
    ("taint_selector", dict(pod_taint_mask=[0.0]),
     "0/3 nodes are available: "
     "3 taint/selector/volume-topology mismatch."),
    ("insufficient_resources", dict(fit_requests=[[100.0, 1.0]]),
     "0/3 nodes are available: 3 insufficient resources."),
    ("load_threshold", dict(la_has_filter_usage=[True] * 3,
                            la_filter_usage=[[9.0, 9.0]] * 3,
                            la_filter_thresholds=[[50.0, 50.0]] * 3),
     "0/3 nodes are available: 3 node load over threshold."),
    ("host_port", dict(pod_port_wants=[[True]],
                       port_used=[[1.0]] * 3),
     "0/3 nodes are available: 3 hostPort in use."),
    ("csi_limit", dict(vol_needed=[[2.0]], vol_free=[1.0] * 3),
     "0/3 nodes are available: 3 CSI volume limit exceeded."),
    ("bindable_cpus", dict(needs_bind=[True], cores_needed=[4.0],
                           bind_free=[2.0] * 3),
     "0/3 nodes are available: 3 insufficient bindable CPUs."),
    ("numa_topology", dict(needs_numa=[True], numa_policy=[1] * 3,
                           requests=[[5.0, 5.0]],
                           numa_free=[[[2.0, 2.0]] * K] * 3),
     "0/3 nodes are available: 3 NUMA topology cannot fit."),
    ("affinity", dict(pod_aff_req=[[True]], aff_exists=[True]),
     "0/3 nodes are available: "
     "3 affinity/anti-affinity/spread mismatch."),
]


@pytest.mark.parametrize("name,over,expected",
                         BRANCHES, ids=[b[0] for b in BRANCHES])
def test_golden_message_per_branch(name, over, expected):
    fc = make_fc(**over)
    assert diagnose_unbound(fc, 0, N) == expected


@pytest.mark.parametrize("name,over,expected",
                         BRANCHES, ids=[b[0] for b in BRANCHES])
def test_kernel_counts_match_host_per_branch(name, over, expected):
    """The on-device attribution must agree with the host oracle on every
    crafted branch — and format to the same golden message."""
    fc = make_fc(**over)
    host = host_stage_counts(fc, 0, N)
    kern = kernel_counts(fc)[0]
    assert np.array_equal(host, kern), (host, kern)
    assert format_stage_counts(kern, N) == expected


def test_in_batch_contention_fallback():
    """All stages pass at cycle-start state -> the contention message."""
    fc = make_fc()
    assert diagnose_unbound(fc, 0, N) == (
        "0/3 nodes available after in-batch placements: "
        "capacity consumed by earlier pods this cycle")
    assert not host_stage_counts(fc, 0, N).any()


def test_gang_short_circuits_quota_and_filters():
    """The legacy early-return order: gang wins over quota and over any
    filter-stage counts riding the same vector."""
    fc = make_fc(gang_id=[0], gang_valid=[False], quota_id=[0],
                 quota_runtime=[[1.0, 1.0]], requests=[[2.0, 2.0]],
                 node_ok=[False] * 3)
    counts = host_stage_counts(fc, 0, N)
    assert counts[EXPLAIN_STAGE_GANG] == 1
    assert counts[EXPLAIN_STAGE_QUOTA] == 1
    assert counts[0] == 3  # node not schedulable still counted
    assert diagnose_unbound(fc, 0, N) == GANG_MESSAGE
    fc2 = make_fc(quota_id=[0], quota_runtime=[[1.0, 1.0]],
                  requests=[[2.0, 2.0]], node_ok=[False] * 3)
    assert diagnose_unbound(fc2, 0, N) == QUOTA_MESSAGE


def test_multi_reason_sorted_by_count_then_taxonomy():
    """Counts sort descending; equal counts keep taxonomy order (the
    legacy dict-insertion tie-break via stable sort)."""
    # 3 taint mismatches everywhere, 1 node cordoned -> taint first
    fc = make_fc(pod_taint_mask=[0.0], node_ok=[False, True, True])
    assert diagnose_unbound(fc, 0, N) == (
        "0/3 nodes are available: "
        "3 taint/selector/volume-topology mismatch, "
        "1 node not schedulable.")
    # tie at 3: taxonomy order (node not schedulable before taint)
    fc = make_fc(pod_taint_mask=[0.0], node_ok=[False] * 3)
    assert diagnose_unbound(fc, 0, N) == (
        "0/3 nodes are available: 3 node not schedulable, "
        "3 taint/selector/volume-topology mismatch.")


def test_format_stage_counts_vector_contract():
    counts = np.zeros(NUM_EXPLAIN_STAGES, np.uint32)
    counts[2] = 5  # insufficient resources
    assert format_stage_counts(counts, 7) == (
        "0/7 nodes are available: 5 insufficient resources.")
    assert len(EXPLAIN_STAGES) + 2 == NUM_EXPLAIN_STAGES


def test_stage_taxonomy_matches_legacy_labels():
    """The kernel/host shared taxonomy IS the legacy message vocabulary;
    renaming a stage is a message-format change and must be deliberate."""
    assert EXPLAIN_STAGES == (
        "node not schedulable",
        "taint/selector/volume-topology mismatch",
        "insufficient resources",
        "node load over threshold",
        "hostPort in use",
        "CSI volume limit exceeded",
        "insufficient bindable CPUs",
        "NUMA topology cannot fit",
        "affinity/anti-affinity/spread mismatch",
    )


# ---------------------------------------------------------------------------
# the per-state memo of node-level counts, keyed on the pod's filter row
# ---------------------------------------------------------------------------

BN, BP, BT, BPT = 64, 200, 2, 2


def _shape(fit=(2.0, 4.0), req=None, taint=1.0, prod=False, ds=False,
           ports=(0, 0), vol=0.0, bind=False, cores=0.0, full_pcpus=False,
           numa=False, aff_match=(0, 0), anti=(0, 0), aff_req=(0, 0),
           skew=(0, 0)):
    return dict(
        fit_requests=fit, requests=fit if req is None else req,
        pod_taint_mask=taint, is_prod=prod, is_daemonset=ds,
        pod_port_wants=ports, vol_needed=[vol], needs_bind=bind,
        cores_needed=cores, full_pcpus=full_pcpus, needs_numa=numa,
        pod_aff_match=aff_match, pod_anti_req=anti, pod_aff_req=aff_req,
        pod_spread_skew=skew)


# Distinct filter rows. For every field the node-level masks read, two
# shapes differ in that field alone and reject different node counts,
# so a memo key that missed the field would serve one shape the other's
# counts: plain, prod, daemonset, hostPort, CSI volume, a taint miss,
# more resources, anti-affinity, required affinity with and without a
# self match, spread with and without a skew, LSR cpusets (cores, full
# pcpus, no bind) and NUMA pods (same fit, other requests)
SHAPES = [
    _shape(),
    _shape(prod=True),
    _shape(ds=True),
    _shape(ports=(1, 0)),
    _shape(vol=2.0),
    _shape(taint=2.0),
    _shape(fit=(12.0, 4.0), req=(2.0, 4.0)),
    _shape(anti=(0, 1)),
    _shape(aff_req=(1, 0)),
    _shape(aff_req=(1, 0), aff_match=(1, 0)),
    _shape(aff_match=(0, 1)),
    _shape(aff_match=(0, 1), skew=(0, 1.0)),
    _shape(cores=4.0),
    _shape(bind=True, cores=4.0),
    _shape(bind=True, cores=2.0),
    _shape(bind=True, cores=3.0),
    _shape(bind=True, cores=3.0, full_pcpus=True),
    _shape(numa=True),
    _shape(numa=True, req=(6.0, 6.0)),
    # only ever diagnosed with a PreFilter verdict (quota 0): the message
    # path must not count it
    _shape(fit=(1.0, 1.0)),
]


def _batch(seed: int = 7):
    """200 pods of the SHAPES on 64 random nodes. Gangs 1 and 3 are
    invalid; quota 0 has no room, quota 2 sits under it (exhausted by
    its ancestor), quota 1 is ample. Every shape but the last has pods
    free of both flags; the last has quota-0 pods alone. Returns (fc,
    shape index per pod, PreFilter-flagged mask)."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    shape_of = rng.integers(0, len(SHAPES), BP)
    gang_id = np.where(rng.random(BP) < 0.3, rng.integers(0, 4, BP), -1)
    quota_id = np.where(rng.random(BP) < 0.5, rng.integers(0, 3, BP), -1)
    # every shape has quota-exhausted, gang-invalid and free pods
    for s in range(len(SHAPES)):
        shape_of[3 * s:3 * s + 3] = s
        gang_id[3 * s:3 * s + 3] = (-1, -1, 1)
        quota_id[3 * s:3 * s + 3] = (-1, 0, -1)
    quota_id[shape_of == len(SHAPES) - 1] = 0
    rows = {k: np.asarray([SHAPES[s][k] for s in shape_of])
            for k in SHAPES[0]}
    gang_valid = np.asarray([True, False, True, False])
    flagged = (((gang_id >= 0) & ~gang_valid[np.maximum(gang_id, 0)])
               | (quota_id == 0) | (quota_id == 2))
    alloc = rng.choice([16.0, 32.0, 64.0], (BN, 2)).astype(f32)
    fc = make_fc(
        N=BN, P=BP, T=BT, PT=BPT,
        fit_requests=rows["fit_requests"].astype(f32),
        is_prod=rows["is_prod"], is_daemonset=rows["is_daemonset"],
        allocatable=alloc,
        requested=(alloc * rng.random((BN, 2))).astype(f32),
        node_ok=rng.random(BN) > 0.1,
        la_has_filter_usage=rng.random(BN) < 0.5,
        la_filter_usage=(alloc * rng.random((BN, 2))).astype(f32),
        la_filter_thresholds=np.full((BN, 2), 70.0, f32),
        la_prod_thresholds=np.full((BN, 2), 50.0, f32),
        la_prod_pod_usage=(alloc * rng.random((BN, 2))).astype(f32),
        requests=rows["requests"].astype(f32),
        gang_id=gang_id.astype(np.int32),
        quota_id=quota_id.astype(np.int32),
        needs_numa=rows["needs_numa"], needs_bind=rows["needs_bind"],
        cores_needed=rows["cores_needed"].astype(f32),
        full_pcpus=rows["full_pcpus"],
        pod_taint_mask=rows["pod_taint_mask"].astype(f32),
        pod_aff_req=rows["pod_aff_req"].astype(bool),
        pod_anti_req=rows["pod_anti_req"].astype(bool),
        pod_aff_match=rows["pod_aff_match"].astype(bool),
        pod_spread_skew=rows["pod_spread_skew"].astype(f32),
        pod_ppref_mask=np.zeros((BP, BT), bool),
        pod_port_wants=rows["pod_port_wants"].astype(bool),
        vol_needed=rows["vol_needed"].astype(f32),
        node_taint_group=rng.integers(0, 2, BN).astype(np.int32),
        aff_dom=rng.integers(-1, 4, (BN, BT)).astype(f32),
        aff_count=rng.integers(0, 3, (BN, BT)).astype(f32),
        anti_cover=(rng.random((BN, BT)) < 0.3).astype(f32),
        aff_exists=np.asarray([True, False]),
        ppref_w=np.zeros((1, BT), f32),
        port_used=(rng.random((BN, BPT)) < 0.3).astype(f32),
        vol_free=rng.integers(0, 4, BN).astype(f32),
        numa_free=rng.choice([2.0, 4.0, 8.0], (BN, K, 2)).astype(f32),
        numa_capacity=np.full((BN, K, 2), 8.0, f32),
        numa_policy=rng.integers(0, 3, BN).astype(np.int32),
        has_topology=rng.random(BN) > 0.2,
        bind_free=rng.choice([2.0, 4.0, 8.0], BN).astype(f32),
        cpus_per_core=rng.choice([1.0, 2.0], BN).astype(f32),
        quota_ancestors=np.asarray([[0, -1], [1, -1], [2, 0]], np.int32),
        quota_used=np.zeros((3, 2), f32),
        quota_runtime=np.asarray([[0.0, 0.0], [1e6, 1e6], [1e6, 1e6]],
                                 f32),
        gang_min_member=np.ones(4, f32),
        gang_assumed=np.zeros(4, f32),
        gang_valid=gang_valid,
        gang_group_id=np.arange(4, dtype=np.int32),
    )
    return fc, shape_of, flagged


def test_memo_counts_match_oracle_per_row():
    """One memo across every row of a state gives the oracle's counts for
    each row, gang and quota slots included, and computes one node-level
    vector per distinct filter row; the message path computes none for
    PreFilter-flagged pods and formats the oracle's message for all."""
    fc, shape_of, flagged = _batch()
    assert flagged.any() and (~flagged).any()
    shared = shared_state(fc, BN)
    oracle = [host_stage_counts(fc, i, BN) for i in range(BP)]
    stages = np.stack(oracle)[:, :len(EXPLAIN_STAGES)]
    # the batch reaches every Filter stage and both PreFilter flags
    assert (stages.sum(axis=0) > 0).all(), stages.sum(axis=0)
    assert any(c[EXPLAIN_STAGE_GANG] for c in oracle)
    assert any(c[EXPLAIN_STAGE_QUOTA] for c in oracle)

    memo = {}
    for i in range(BP):
        got = host_stage_counts(fc, i, BN, shared=shared, memo=memo)
        assert np.array_equal(got, oracle[i]), (i, got, oracle[i])
    assert len(memo) == len(set(shape_of.tolist())) == len(SHAPES)

    memo = {}
    for i in range(BP):
        msg = diagnose_unbound(fc, i, BN, shared=shared, memo=memo)
        assert msg == format_stage_counts(oracle[i], BN), i
    assert len(memo) == len(set(shape_of[~flagged].tolist())) == (
        len(SHAPES) - 1)


def test_memo_is_per_state():
    """Two states of one batch that differ by a committed placement, each
    diagnosed with its own memo, give each state's oracle counts; the
    placement moves some row's counts, so a memo carried from one state
    into the other would serve stale ones."""
    fc_a, _shape_of, _flagged = _batch(seed=11)
    mirror = _WaveStateMirror(fc_a)
    for i, node in ((3, 5), (9, 5), (12, 17), (15, 17)):
        mirror.commit(i, node, 0)
    fc_b = mirror.patched_fc()
    for fc in (fc_a, fc_b):
        shared, memo = shared_state(fc, BN), {}
        for i in range(BP):
            got = host_stage_counts(fc, i, BN, shared=shared, memo=memo)
            assert np.array_equal(got, host_stage_counts(fc, i, BN)), i
    moved = sum(not np.array_equal(host_stage_counts(fc_a, i, BN),
                                   host_stage_counts(fc_b, i, BN))
                for i in range(BP))
    assert moved > 0
