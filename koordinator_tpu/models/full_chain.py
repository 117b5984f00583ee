"""Full plugin-chain scheduling step (BASELINE config 4).

Fuses the reference's whole hot loop (SURVEY.md section 3.1) into one compiled
program per batch:

  PreFilter   gang validity (host precompute) + quota admission (in-loop, order
              dependent) + NUMA/cpuset prechecks
  Filter      NodeResourcesFit + LoadAware thresholds + NodeNUMAResource admit
              (cpuset capacity, SMT alignment, NUMA topology policy)
  Score       LoadAware least-allocated + NodeNUMAResource least-allocated,
              equal plugin weights, summed (frameworkext RunScorePlugins
              normalize+weighted-sum)
  Reserve     on-device state updates: Fit requested, LoadAware assign-cache
              deltas, NUMA zone free, bindable-cpu free, quota used
  Permit      gang barrier as a segment-reduction post-pass (ops/gang.py)

Reservation consumption and concrete device/cpuset assignment remain host-side in
the cycle driver (scheduler/cycle.py): they run once per actual binding, not per
pod x node. The serial parity emulator (scheduler/parity.py serial_schedule_full)
implements the identical chain scalar-wise; bindings must match exactly.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.models.scheduler_model import ScheduleInputs, _score_row
from koordinator_tpu.ops import loadaware as la_ops
from koordinator_tpu.ops.fit import fit_ok_row
from koordinator_tpu.ops.gang import gang_permit_mask
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.numa import (
    cpuset_filter_row,
    numa_admit_row,
    numa_score_row,
    numa_spread_fill,
)
from koordinator_tpu.ops.quota import quota_admit_row, quota_used_add_row

# ---------------------------------------------------------------------------
# koordexplain: the per-filter-stage reject taxonomy shared by the on-device
# attribution pass (explain_stage_counts), the host-numpy oracle
# (scheduler/diagnose.py host_stage_counts) and the /explain surfaces.
# ORDER IS LOAD-BEARING: it is the insertion order of diagnose.py's legacy
# reasons dict, and format_stage_counts relies on a stable sort over it to
# reproduce the legacy message tie-break byte-for-byte.
# ---------------------------------------------------------------------------
EXPLAIN_STAGES = (
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
# prometheus-safe stage keys for koord_scheduler_filter_rejections_total
EXPLAIN_STAGE_KEYS = (
    "node_not_schedulable",
    "taint_selector_volume_topology",
    "insufficient_resources",
    "node_load_over_threshold",
    "host_port_in_use",
    "csi_volume_limit",
    "insufficient_bindable_cpus",
    "numa_topology",
    "affinity_spread",
    "gang_not_satisfied",
    "quota_exhausted",
)
# pod-level PreFilter verdict slots appended after the per-node stages
# (0/1 flags, not node counts — they reproduce diagnose.py's early returns)
EXPLAIN_STAGE_GANG = len(EXPLAIN_STAGES)
EXPLAIN_STAGE_QUOTA = len(EXPLAIN_STAGES) + 1
NUM_EXPLAIN_STAGES = len(EXPLAIN_STAGES) + 2

# per-plugin score-term slots of ExplainOut.terms rows (the "full" level)
EXPLAIN_TERMS = ("LoadAware", "NodeNUMAResource", "Preferred",
                 "best_score", "runner_up")


class ExplainOut(NamedTuple):
    """Attribution readback riding the scheduling dispatch.

    ``stage_counts``: [P, NUM_EXPLAIN_STAGES] uint32 per-pod rejected-node
    counts over the REAL (unpadded) nodes, evaluated at cycle-start state —
    the same state scheduler/diagnose.py reads — plus the two pod-level
    PreFilter verdict slots. The fused wave step emits [K, P, ...], one row
    per wave at wave-start state. ``terms``: [P, len(EXPLAIN_TERMS)] f32
    decision-time score attribution (None below the "full" level)."""

    stage_counts: jnp.ndarray
    terms: jnp.ndarray  # or None


class FullChainInputs(NamedTuple):
    base: ScheduleInputs
    # pods
    requests: jnp.ndarray       # [P, R] raw requests (quota/NUMA accounting)
    gang_id: jnp.ndarray        # [P] int32
    quota_id: jnp.ndarray       # [P] int32
    needs_numa: jnp.ndarray     # [P] bool — subject to NUMA admission
    needs_bind: jnp.ndarray     # [P] bool — requires cpuset binding
    cores_needed: jnp.ndarray   # [P] float — whole cpus for cpuset pods
    full_pcpus: jnp.ndarray     # [P] bool — resolved FullPCPUs policy
    pod_taint_mask: jnp.ndarray  # [P] f32 bitmask of admitted node groups
    #     (taints tolerated AND node selector/affinity satisfied —
    #     ops/taints.py)
    pod_aff_req: jnp.ndarray    # [P, T] bool — required pod-affinity terms
    pod_anti_req: jnp.ndarray   # [P, T] bool — required anti-affinity terms
    pod_aff_match: jnp.ndarray  # [P, T] bool — pod's labels match term
    pod_spread_skew: jnp.ndarray  # [P, T] f32 — DoNotSchedule topology
    #     spread maxSkew over term t's domains (0 = no constraint)
    pod_pref_id: jnp.ndarray    # [P] int32 preferred-affinity profile (-1)
    pod_ppref_id: jnp.ndarray   # [P] int32 preferred POD-affinity profile
    pod_ppref_mask: jnp.ndarray  # [P, T] bool — terms the profile weighs
    #     (the wave kernel's conflict rule)
    pod_port_wants: jnp.ndarray  # [P, PT] bool — hostPort slots requested
    #     (ops/ports.py NodePorts factorization)
    vol_needed: jnp.ndarray     # [P, VG] f32 — NEW PVC attachments the pod
    #     adds on a node of volume-group g: distinct claims minus claims
    #     already attached there (upstream NodeVolumeLimits counts only new
    #     attachments). VG==1 ("no pending claim attached anywhere") is the
    #     common case and collapses to the plain per-pod count.
    pod_img_id: jnp.ndarray     # [P] int32 ImageLocality profile (-1)
    # nodes
    node_taint_group: jnp.ndarray  # [N] int32 admission-signature group
    aff_dom: jnp.ndarray        # [N, T] f32 topology domain id (-1 invalid)
    aff_count: jnp.ndarray      # [N, T] f32 matching pods in n's domain
    anti_cover: jnp.ndarray     # [N, T] f32 pods CARRYING term t as required
    #     anti-affinity in n's domain (symmetric anti-affinity — upstream
    #     existingAntiAffinityCounts); blocks incoming pods MATCHING t
    aff_exists: jnp.ndarray     # [T] bool — any matching pod anywhere
    #     (domain-labeled or not; drives the first-replica bootstrap)
    pref_scores: jnp.ndarray    # [N, S] f32 preferred-node-affinity score
    #     rows (0..100 per profile, static — ops/podaffinity.py)
    port_used: jnp.ndarray      # [N, PT] f32 — hostPort slot in use on n
    vol_free: jnp.ndarray       # [N] f32 — attachable CSI volumes left
    #     (+inf when the node reports no limit)
    node_vol_group: jnp.ndarray  # [N] int32 — volume-group id: nodes whose
    #     attached-claim sets intersect the pending batch's claims
    #     identically share a group (group 0 = empty intersection)
    img_scores: jnp.ndarray     # [N, max(SI,1)] f32 ImageLocality rows
    ppref_w: jnp.ndarray        # [max(S2,1), max(T,1)] f32 per-profile term
    #     weights for preferred pod affinity (negative = anti preference)
    numa_free: jnp.ndarray      # [N, K, R]
    numa_capacity: jnp.ndarray  # [N, K, R]
    numa_policy: jnp.ndarray    # [N] int32
    has_topology: jnp.ndarray   # [N] bool
    bind_free: jnp.ndarray      # [N] float
    cpus_per_core: jnp.ndarray  # [N] float
    # quota tree
    quota_ancestors: jnp.ndarray  # [G, D]
    quota_used: jnp.ndarray       # [G, R]
    quota_runtime: jnp.ndarray    # [G, R]
    # gangs
    gang_min_member: jnp.ndarray  # [NG]
    gang_assumed: jnp.ndarray     # [NG]
    gang_valid: jnp.ndarray       # [NG] bool (PreFilter validity)
    gang_group_id: jnp.ndarray    # [NG] int32


def resolve_weight_idx(args: LoadAwareArgs, active_axes):
    """Weight-axis resolution shared by every full-chain kernel, so the serial
    and wave kernels can never trace different weight sets."""
    full_weights = args.weight_vector()
    if active_axes is not None:
        full_weights = full_weights[list(active_axes)]
    return tuple(int(i) for i in np.nonzero(full_weights)[0])


def resolve_balance_idx(active_axes):
    """(cpu_axis, mem_axis) positions after active-axes slicing, for the
    NodeResourcesBalancedAllocation score; (-1, -1) when either axis was
    sliced away (score contributes 0 then — upstream needs both)."""
    from koordinator_tpu.api.resources import RESOURCE_INDEX, ResourceName

    cpu = RESOURCE_INDEX[ResourceName.CPU]
    mem = RESOURCE_INDEX[ResourceName.MEMORY]
    if active_axes is None:
        return cpu, mem
    axes = [int(a) for a in active_axes]
    if cpu in axes and mem in axes:
        return axes.index(cpu), axes.index(mem)
    return -1, -1


def make_pod_evaluator(fc: FullChainInputs, weight_idx, prod_mode,
                       bal_idx=(-1, -1), explain_terms=False):
    """The per-pod PreFilter+Filter+Score+select math, factored so the serial
    kernel and the wave kernel (models/wave_chain.py) trace the IDENTICAL
    computation — binding parity between them is by construction.

    Returns evaluate(i, requested, delta_np, delta_pr, numa_free, bind_free,
    quota_used, ...) -> (found, best, zone_at_best, admit, score_row,
    bal_row, best_score) where admit is the pod-level PreFilter verdict
    (gang validity AND quota admission); vmap-able over i at frozen
    state. score_row is the feasibility-masked [N] score vector and
    bal_row the unmasked balanced-allocation term (both consumed by the
    wave kernel's conflict bound; the serial loop drops them).

    With ``explain_terms`` (the KOORD_TPU_EXPLAIN=full kernels) evaluate
    appends the per-plugin score-term rows (la_score, numa_score, pref) so
    the loop body can record the winning node's attribution.

    The returned callable carries ``evaluate.filter_chain`` — the
    PreFilter+Filter verdicts alone, at any frozen state — which the
    attribution pass (explain_stage_counts) vmaps to produce the per-stage
    reject counts diagnose.py formats. It is the SAME closure evaluate
    itself combines into ``feasible``, so counts can never drift from the
    decisions."""
    inputs = fc.base
    reject_np, reject_prod = la_ops.loadaware_node_reject(
        inputs.allocatable,
        inputs.la_filter_usage,
        inputs.la_has_filter_usage,
        inputs.la_filter_thresholds,
        inputs.la_prod_thresholds,
        inputs.la_prod_pod_usage,
        inputs.la_filter_skip,
    )
    gang_pod_ok = jnp.where(
        fc.gang_id >= 0, fc.gang_valid[jnp.maximum(fc.gang_id, 0)], True
    )

    T = fc.aff_dom.shape[1]
    PT = fc.port_used.shape[1]

    # balanced-allocation reciprocals hoisted out of the pod loop
    # (ops/pallas_common.safe_reciprocal documents the cross-kernel
    # bit-parity contract)
    if bal_idx[0] >= 0:
        from koordinator_tpu.ops.pallas_common import safe_reciprocal

        bal_inv_c, bal_inv_m = (
            safe_reciprocal(inputs.allocatable[:, axis]) for axis in bal_idx)

    def filter_chain(i, requested, numa_free, bind_free, quota_used,
                     aff_count, anti_cover, aff_exists, port_used, vol_free):
        """PreFilter + Filter verdicts for pod ``i`` at the given frozen
        state: (gang_ok, quota_ok, fit, la_ok, cpuset_ok, numa_ok, zone,
        taint_ok, affinity_ok, ports_ok, vol_ok). The single home of every
        filter predicate — evaluate combines these into ``feasible`` and
        the attribution pass counts their complements."""
        req_fit = inputs.fit_requests[i]
        req = fc.requests[i]
        is_prod_i = inputs.is_prod[i]

        # ---- PreFilter: gang validity + quota admission (order-dependent)
        quota_ok = quota_admit_row(
            req, fc.quota_id[i], fc.quota_ancestors, quota_used, fc.quota_runtime
        )

        # ---- Filter chain
        fit = fit_ok_row(req_fit, inputs.allocatable, requested)
        la_reject = jnp.where(is_prod_i, reject_prod, reject_np)
        la_ok = inputs.is_daemonset[i] | ~la_reject
        cpuset_ok = cpuset_filter_row(
            fc.needs_bind[i], fc.cores_needed[i], fc.full_pcpus[i],
            fc.has_topology, bind_free, fc.cpus_per_core,
        )
        numa_ok, zone = numa_admit_row(
            req, fc.needs_numa[i], numa_free, fc.numa_policy
        )
        # TaintToleration (vendored default plugin): pod tolerates the node's
        # taint-set group (ops/taints.py bit test)
        taint_ok = (
            jnp.right_shift(
                fc.pod_taint_mask[i].astype(jnp.int32), fc.node_taint_group
            )
            & 1
        ) == 1
        # InterPodAffinity (vendored default plugin, ops/podaffinity.py):
        # every required anti term has a zero count in the node's domain;
        # every required affinity term has a match in a VALID domain, or
        # bootstraps (self-match with no matching pod anywhere)
        affinity_ok = jnp.ones(aff_count.shape[0], bool)
        for t in range(T):
            count_t = aff_count[:, t]
            dom_valid = fc.aff_dom[:, t] >= 0
            anti_ok = ~fc.pod_anti_req[i, t] | (count_t <= 0)
            # symmetric anti-affinity: a pod MATCHING term t may not land
            # where any CARRIER of anti term t lives (anti_cover > 0 only
            # on domain-labeled nodes, so dom_valid is implied)
            sym_ok = ~fc.pod_aff_match[i, t] | (anti_cover[:, t] <= 0)
            bootstrap = fc.pod_aff_match[i, t] & ~aff_exists[t]
            aff_ok = ~fc.pod_aff_req[i, t] | (
                dom_valid & (count_t > 0)) | bootstrap
            # PodTopologySpread (DoNotSchedule): count + self - min over
            # ELIGIBLE domains must stay within maxSkew. Eligibility is the
            # pod's admission bit test (node selector/affinity + taints) —
            # upstream NodeAffinityPolicy=Honor + NodeTaintsPolicy=Honor —
            # so an empty domain the pod could never use cannot pin the
            # minimum at zero. A node without the topology label cannot
            # host the constrained pod.
            skew = fc.pod_spread_skew[i, t]
            self_match = jnp.where(fc.pod_aff_match[i, t], 1.0, 0.0)
            min_count = jnp.min(
                jnp.where(dom_valid & taint_ok, count_t, jnp.inf))
            spread_ok = (skew <= 0) | (
                dom_valid & (count_t + self_match - min_count <= skew))
            affinity_ok = affinity_ok & anti_ok & sym_ok & aff_ok & spread_ok
        # NodePorts (vendored default plugin, ops/ports.py): no requested
        # hostPort slot may already be bound on the node
        ports_ok = jnp.ones(port_used.shape[0], bool)
        for s in range(PT):
            ports_ok = ports_ok & (
                ~fc.pod_port_wants[i, s] | (port_used[:, s] <= 0))
        # NodeVolumeLimits (CSI attachable count): nodes without a reported
        # limit carry vol_free = +inf and always pass; the per-node volume
        # group resolves "claims already attached here don't count again"
        # (upstream's already-attached exemption)
        vn = fc.vol_needed[i][fc.node_vol_group]
        vol_ok = (vn <= 0) | (vol_free >= vn)
        return (gang_pod_ok[i], quota_ok, fit, la_ok, cpuset_ok, numa_ok,
                zone, taint_ok, affinity_ok, ports_ok, vol_ok)

    def evaluate(i, requested, delta_np, delta_pr, numa_free, bind_free,
                 quota_used, aff_count, anti_cover, aff_exists, port_used,
                 vol_free):
        req_fit = inputs.fit_requests[i]
        req = fc.requests[i]
        est = inputs.estimated[i]
        is_prod_i = inputs.is_prod[i]

        with jax.named_scope("filter"):
            (gang_ok, quota_ok, fit, la_ok, cpuset_ok, numa_ok, zone,
             taint_ok, affinity_ok, ports_ok, vol_ok) = filter_chain(
                i, requested, numa_free, bind_free, quota_used, aff_count,
                anti_cover, aff_exists, port_used, vol_free)
            admit = gang_ok & quota_ok
            feasible = (
                inputs.node_ok & fit & la_ok & cpuset_ok & numa_ok
                & taint_ok & affinity_ok & ports_ok & vol_ok & admit
            )

        with jax.named_scope("score"):
            # ---- Score chain (equal plugin weights, each already 0..100)
            la_score = _score_row(
                est, is_prod_i, inputs, delta_np, delta_pr, weight_idx,
                prod_mode
            )
            numa_score = numa_score_row(
                req, requested, inputs.allocatable, inputs.weights,
                weight_idx,
            )
            # NodeResourcesBalancedAllocation (vendored default scoring):
            # for the two balanced axes the upstream std reduces to
            # |fc - fm| / 2 (no sqrt — the bit-parity discipline holds);
            # fractions clamp to 1 and a zero-capacity axis contributes
            # fraction 0
            if bal_idx[0] >= 0:
                ci, mi = bal_idx
                def _frac(axis, inv):
                    return jnp.minimum(
                        (requested[:, axis] + req_fit[axis]) * inv, 1.0)
                std = jnp.abs(
                    _frac(ci, bal_inv_c) - _frac(mi, bal_inv_m)) * 0.5
                bal_row = jnp.floor((1.0 - std) * 100.0)
                numa_score = numa_score + bal_row
            else:
                bal_row = jnp.zeros(requested.shape[0], jnp.float32)
            # preferred node affinity (soft NodeAffinity score): a static,
            # profile-bucketed 0..100 row — pods without preferences add 0.
            # Zero-column tables mean NO pod carries the feature: skip the
            # gather entirely (snapshot emits true empties)
            if fc.pref_scores.shape[1]:
                pid = fc.pod_pref_id[i]
                pref = jnp.where(
                    pid >= 0, fc.pref_scores[:, jnp.maximum(pid, 0)], 0.0)
            else:
                pref = jnp.zeros(aff_count.shape[0], jnp.float32)
            # preferred POD affinity (soft InterPodAffinity score):
            # weighted sum of matching-pod counts over the shared term
            # space, max-min normalized to 0..100 per pod (upstream
            # NormalizeScore semantics)
            sid2 = fc.pod_ppref_id[i]
            if T and fc.ppref_w.shape[0]:  # zero rows == no profiles: no work
                w_row = fc.ppref_w[jnp.maximum(sid2, 0), :T]          # [T]
                # elementwise+reduce, not matmul: TPU matmuls default to bf16
                # passes and the products must stay exact integers
                raw = jnp.sum(aff_count * w_row[None, :], axis=1)     # [N]
                # max-min over node_ok nodes only (upstream NormalizeScore
                # spans the candidate set — padded/cordoned rows must not
                # anchor the scale and shift weights across bucket sizes)
                mx = jnp.max(jnp.where(inputs.node_ok, raw, -jnp.inf))
                mn = jnp.min(jnp.where(inputs.node_ok, raw, jnp.inf))
                norm = jnp.where(
                    mx > mn,
                    jnp.floor((raw - mn) * 100.0 / (mx - mn)), 0.0)
                pref = pref + jnp.where(sid2 >= 0, norm, 0.0)
            # ImageLocality (vendored default plugin, ops/ports.py): static
            # profile-bucketed 0..100 row, like preferred node affinity
            if fc.img_scores.shape[1]:
                iid = fc.pod_img_id[i]
                pref = pref + jnp.where(
                    iid >= 0, fc.img_scores[:, jnp.maximum(iid, 0)], 0.0)
            score = la_score + numa_score + pref
            score = jnp.where(feasible, score, -1.0)

        # ---- select
        with jax.named_scope("select"):
            best = jnp.argmax(score)
            found = (score[best] >= 0.0) & inputs.pod_valid[i]
        # score/bal rows + best value ride along for the wave kernel's
        # balanced-allocation conflict bound; the serial loop ignores them
        # (XLA dead-code-eliminates the unused outputs)
        if explain_terms:
            return (found, best, zone[best], admit, score, bal_row,
                    score[best], la_score, numa_score, pref)
        return found, best, zone[best], admit, score, bal_row, score[best]

    evaluate.filter_chain = filter_chain
    return evaluate


def explain_stage_counts(fc: FullChainInputs, evaluate, filter_state,
                         n_real):
    """[P, NUM_EXPLAIN_STAGES] uint32: per-pod rejected-node counts at the
    frozen ``filter_state`` — the (requested, numa_free, bind_free,
    quota_used, aff_count, anti_cover, aff_exists, port_used, vol_free)
    9-tuple ``evaluate.filter_chain`` takes — over the first ``n_real``
    (unpadded) nodes, plus the two pod-level PreFilter verdict flags.
    Vmapped reuse of the SAME filter_chain the decisions ran through, so a
    count here is exactly "nodes this stage rejected for this pod", in the
    state scheduler/diagnose.py diagnoses against."""
    inputs = fc.base
    N = inputs.allocatable.shape[0]
    P = inputs.fit_requests.shape[0]
    valid = jnp.arange(N, dtype=jnp.int32) < n_real

    def row(i):
        (gang_ok, quota_ok, fit, la_ok, cpuset_ok, numa_ok, _zone, taint_ok,
         affinity_ok, ports_ok, vol_ok) = evaluate.filter_chain(
            i, *filter_state)
        # EXPLAIN_STAGES order (diagnose.py's legacy insertion order)
        bads = (~inputs.node_ok, ~taint_ok, ~fit, ~la_ok, ~ports_ok,
                ~vol_ok, ~cpuset_ok, ~numa_ok, ~affinity_ok)
        counts = [jnp.sum(b & valid).astype(jnp.uint32) for b in bads]
        counts.append(jnp.where(gang_ok, 0, 1).astype(jnp.uint32))
        counts.append(jnp.where(quota_ok, 0, 1).astype(jnp.uint32))
        return jnp.stack(counts)

    return jax.vmap(row)(jnp.arange(P, dtype=jnp.int32))


def commit_pod_state(fc: FullChainInputs, prod_mode: bool, state, i, found,
                     best, zone_at_best):
    """Apply pod ``i``'s tentative binding to the in-round device state.

    ``state`` is the 11-tuple (requested, delta_np, delta_pr, numa_free,
    bind_free, quota_used, aff_count, anti_cover, aff_exists, port_used,
    vol_free) every full-chain kernel carries. Factored out of the serial
    loop so the fused wave kernel (models/fused_waves.py) traces the
    IDENTICAL update sequence — both its in-wave pass and its kept-only
    replay pass call this function, so carried state can never drift from
    what the serial kernel would have produced."""
    with jax.named_scope("commit"):
        inputs = fc.base
        (requested, delta_np, delta_pr, numa_free, bind_free, quota_used,
         aff_count, anti_cover, aff_exists, port_used, vol_free) = state
        T = fc.aff_dom.shape[1]
        PT = fc.port_used.shape[1]
        req_fit = inputs.fit_requests[i]
        req = fc.requests[i]
        est = inputs.estimated[i]
        is_prod_i = inputs.is_prod[i]
        fnd = found.astype(jnp.float32)

        def upd_row(mat, add_row):
            new_row = mat[best] + fnd * add_row
            return jax.lax.dynamic_update_slice(mat, new_row[None], (best, 0))

        requested = upd_row(requested, req_fit)
        delta_np = upd_row(delta_np, est)
        if prod_mode:
            delta_pr = upd_row(
                delta_pr, jnp.where(is_prod_i, 1.0, 0.0) * est
            )
        new_zone_free = numa_spread_fill(numa_free[best], req, zone_at_best)
        apply_numa = (found & fc.needs_numa[i]).astype(jnp.float32)
        mixed = (apply_numa * new_zone_free
                 + (1.0 - apply_numa) * numa_free[best])
        numa_free = jax.lax.dynamic_update_slice(
            numa_free, mixed[None], (best, 0, 0)
        )
        bind_free = bind_free.at[best].add(
            -fnd * jnp.where(fc.needs_bind[i], fc.cores_needed[i], 0.0)
        )
        # NodePorts: the placed pod binds its wanted slots on the node
        if PT:
            port_row = jnp.maximum(
                port_used[best],
                fnd * fc.pod_port_wants[i].astype(jnp.float32))
            port_used = jax.lax.dynamic_update_slice(
                port_used, port_row[None], (best, 0))
        vol_free = vol_free.at[best].add(
            -fnd * fc.vol_needed[i][fc.node_vol_group[best]])
        quota_used = quota_used_add_row(
            quota_used, req, fc.quota_id[i], fc.quota_ancestors, found
        )
        # inter-pod affinity: the placed pod raises the count of every
        # term it matches across the chosen node's whole domain, flips
        # the term's exists flag even on an unlabeled node, and — for
        # terms it CARRIES as anti-affinity — raises the domain's
        # anti_cover (symmetric anti-affinity for later pods)
        for t in range(T):
            chosen_dom = fc.aff_dom[best, t]
            in_dom = (chosen_dom >= 0) & (fc.aff_dom[:, t] == chosen_dom)
            inc = found & fc.pod_aff_match[i, t] & in_dom
            aff_count = aff_count.at[:, t].add(inc.astype(jnp.float32))
            inc_cov = found & fc.pod_anti_req[i, t] & in_dom
            anti_cover = anti_cover.at[:, t].add(
                inc_cov.astype(jnp.float32))
            aff_exists = aff_exists.at[t].set(
                aff_exists[t] | (found & fc.pod_aff_match[i, t]))
        return (requested, delta_np, delta_pr, numa_free, bind_free,
                quota_used, aff_count, anti_cover, aff_exists, port_used,
                vol_free)


def build_full_chain_step(args: LoadAwareArgs, num_gangs: int, num_groups: int,
                          jit: bool = True, active_axes=None, explain=None):
    """FullChainInputs -> (chosen[P], requested[N, R], quota_used[G, R]).

    num_gangs/num_groups are static (gang arrays are padded to them).
    active_axes: when the inputs were sliced to the active resource axes
    (snapshot.reduce_to_active_axes), the original axis ids, so weight indices
    map correctly.

    explain: None (the default, the exact historical step), "counts", or
    "full" (koordexplain attribution). An explain step takes an extra
    ``n_real`` int32 scalar (real node count — padding must not inflate
    counts) and returns a 4th output, ExplainOut. The decision computation
    is untouched: attribution is extra outputs only, so bindings stay
    byte-identical to the explain=None step.
    """
    weight_idx = resolve_weight_idx(args, active_axes)
    bal_idx = resolve_balance_idx(active_axes)
    prod_mode = args.score_according_prod_usage
    explain_full = explain == "full"

    def _step_impl(fc: FullChainInputs, n_real):
        inputs = fc.base
        P = inputs.fit_requests.shape[0]
        N = inputs.allocatable.shape[0]
        evaluate = make_pod_evaluator(fc, weight_idx, prod_mode, bal_idx,
                                      explain_terms=explain_full)

        T = fc.aff_dom.shape[1]
        PT = fc.port_used.shape[1]

        def body(i, state):
            if explain_full:
                chain_state, terms, chosen = state[:-2], state[-2], state[-1]
                (found, best, zone_at_best, _admit, score, _b, best_v,
                 la_row, numa_row, pref_row) = evaluate(i, *chain_state)
                # decision-time attribution: the winning node's per-plugin
                # terms + the runner-up score (margin = best - runner_up);
                # -1 marks "no feasible runner-up", matching the score
                # vector's infeasible sentinel
                runner = jnp.maximum(jnp.max(jnp.where(
                    jnp.arange(N, dtype=jnp.int32) == best,
                    -jnp.inf, score)), -1.0)
                terms = terms.at[i].set(jnp.stack([
                    la_row[best], numa_row[best], pref_row[best],
                    best_v, runner]))
            else:
                chain_state, chosen = state[:-1], state[-1]
                found, best, zone_at_best, _admit, _s, _b, _mv = evaluate(
                    i, *chain_state,
                )
            chain_state = commit_pod_state(
                fc, prod_mode, chain_state, i, found, best, zone_at_best)
            chosen = chosen.at[i].set(jnp.where(found, best.astype(jnp.int32), -1))
            if explain_full:
                return chain_state + (terms, chosen)
            return chain_state + (chosen,)

        R = inputs.fit_requests.shape[-1]
        init = (
            inputs.requested,
            jnp.zeros((N, R), jnp.float32),
            jnp.zeros((N, R), jnp.float32),
            fc.numa_free,
            fc.bind_free,
            fc.quota_used,
            fc.aff_count,
            fc.anti_cover,
            jnp.asarray(fc.aff_exists, bool),
            fc.port_used,
            fc.vol_free,
        )
        if explain_full:
            init = init + (jnp.zeros((P, len(EXPLAIN_TERMS)), jnp.float32),)
        init = init + (jnp.full(P, -1, jnp.int32),)
        out = jax.lax.fori_loop(0, P, body, init)
        requested, quota_used, chosen = out[0], out[5], out[-1]
        terms = out[-2] if explain_full else None

        # ---- Permit barrier (gang group all-or-nothing)
        with jax.named_scope("select"):
            keep = gang_permit_mask(
                chosen, fc.gang_id, fc.gang_min_member, fc.gang_assumed,
                fc.gang_group_id, num_gangs, num_groups,
            )
            chosen = jnp.where(keep, chosen, -1)
        if explain is None:
            return chosen, requested, quota_used
        # attribution counts at CYCLE-START state — diagnose.py's contract
        # (its legacy messages are computed against the packed batch before
        # in-batch placements)
        filter_state = (init[0], init[3], init[4], init[5], init[6],
                        init[7], init[8], init[9], init[10])
        counts = explain_stage_counts(fc, evaluate, filter_state, n_real)
        return chosen, requested, quota_used, ExplainOut(counts, terms)

    if explain is None:
        def full_chain_xla(fc: FullChainInputs):
            return _step_impl(fc, None)

        return jax.jit(full_chain_xla) if jit else full_chain_xla

    def full_chain_xla_explain(fc: FullChainInputs, n_real):
        return _step_impl(fc, n_real)

    return jax.jit(full_chain_xla_explain) if jit else full_chain_xla_explain


def full_chain_vmem_bytes(fc: FullChainInputs) -> int:
    """The Pallas full-chain kernel's scoped-VMEM estimate at ``fc``'s
    shapes (ops/pallas_full_chain.estimate_vmem_bytes). Over the device
    kind's budget, the selector below keeps the batch on the XLA step."""
    from koordinator_tpu.ops.pallas_full_chain import estimate_vmem_bytes

    return estimate_vmem_bytes(
        fc.base.allocatable.shape[0], fc.base.fit_requests.shape[1],
        fc.numa_free.shape[1], fc.quota_used.shape[0], fc.aff_dom.shape[1],
        fc.pref_scores.shape[1], fc.port_used.shape[1],
        fc.img_scores.shape[1])


def pallas_full_chain_fits(fc: FullChainInputs, vmem_budget_bytes,
                           volumes: bool, explain=None) -> bool:
    """Whether the Pallas full-chain kernel serves ``fc``: the one rule
    the single-round selector (build_best_full_chain_step) and the fused
    wave body's search pass (models/fused_waves.wave_search_backend) both
    read. Shapes and static flags only, so it folds at trace time.

    ``vmem_budget_bytes`` is the device kind's scoped-VMEM budget
    (ops/pallas_common.default_vmem_budget), which only the TPU backend
    has: None keeps the XLA step. The kernel's VMEM estimate must fit it
    and its SMEM estimate the Mosaic limit; ``volumes`` (any pod of the
    batch mounts new volumes) sizes the per-pod volume rows, which the
    volume-less variant compiles out. koordexplain attribution runs on
    the XLA step alone."""
    from koordinator_tpu.ops.pallas_full_chain import (
        SMEM_BUDGET_BYTES,
        estimate_smem_bytes,
    )

    if explain is not None or vmem_budget_bytes is None:
        return False
    if full_chain_vmem_bytes(fc) > vmem_budget_bytes:
        return False
    P = fc.base.fit_requests.shape[0]
    T = fc.aff_dom.shape[1]
    VG = fc.vol_needed.shape[1]
    S2 = fc.ppref_w.shape[0] if T else 0
    return (estimate_smem_bytes(P, VG if volumes else 0, T, S2)
            <= SMEM_BUDGET_BYTES)


def node_axis_vmem_bytes(n_nodes: int) -> int:
    """``full_chain_vmem_bytes`` at ``n_nodes``' bucket with the fewest
    axes a pack keeps (scheduler/snapshot.reduce_to_active_axes: the
    pods, cpu and memory axes, one NUMA zone; one quota row, no affinity,
    preference, port or image columns). The estimate grows with every
    axis, so this is a lower bound for each batch over that many nodes:
    over the budget, no batch of the cluster fits the Pallas kernel on
    one chip. The scheduler's size rule (parallel/mesh.size_rule_shards)
    reads it, so the mesh follows the node axis alone."""
    from koordinator_tpu.ops.packing import bucket_size
    from koordinator_tpu.ops.pallas_full_chain import estimate_vmem_bytes

    return estimate_vmem_bytes(bucket_size(n_nodes), 3, 1, 1)


def build_best_full_chain_step(args: LoadAwareArgs, num_gangs: int,
                               num_groups: int, active_axes=None,
                               vmem_budget_bytes=None, kernel: str = "auto",
                               explain=None):
    """Backend-aware selector: the VMEM-resident Pallas kernel on TPU
    (ops/pallas_full_chain.py), the XLA step elsewhere. Same contract,
    bit-identical bindings.

    The Pallas kernel pins all node/NUMA/quota state in VMEM, so its reach
    is bounded by the device kind's scoped-VMEM limit
    (ops/pallas_common.VMEM_LIMIT_BYTES; an unknown TPU kind raises): a
    shape whose estimate exceeds it runs the XLA step, so the selector
    never dispatches a kernel the chip's compiler refuses. The dispatch
    reads shapes plus one host-side numpy flag (any volumes?), so it
    never syncs the device; under jit the shape checks fold at trace time
    and the volume variant stays conservative.

    ``kernel`` forces an implementation: "serial" (XLA fori_loop), "pallas",
    or "wave" (models/wave_chain.py); "auto" is the default selection above.

    ``explain`` (koordexplain attribution) pins the XLA serial step — the
    Pallas/wave kernels do not emit attribution; the cycle driver documents
    the demotion via ``last_backend``.
    """
    def _forced(step_fn, name):
        # plain wrapper: jitted callables reject attribute assignment
        # (varargs: explain steps take an extra n_real operand)
        def step(*fc_args):
            return step_fn(*fc_args)

        step.last_backend = name
        return step

    if explain is not None:
        return _forced(
            build_full_chain_step(args, num_gangs, num_groups,
                                  active_axes=active_axes, explain=explain),
            "xla",
        )
    if kernel == "serial":
        return _forced(
            build_full_chain_step(args, num_gangs, num_groups,
                                  active_axes=active_axes),
            "serial",
        )
    if kernel == "wave":
        from koordinator_tpu.models.wave_chain import (
            build_wave_full_chain_step,
        )

        return _forced(
            build_wave_full_chain_step(args, num_gangs, num_groups,
                                       active_axes=active_axes),
            "wave",
        )
    xla_step = build_full_chain_step(args, num_gangs, num_groups,
                                     active_axes=active_axes)
    if kernel == "pallas" and jax.default_backend() != "tpu":
        raise ValueError("kernel='pallas' requires the TPU backend")
    if jax.default_backend() != "tpu":
        return xla_step
    from koordinator_tpu.ops import pallas_common as pc
    from koordinator_tpu.ops.pallas_full_chain import (
        build_pallas_full_chain_step,
    )

    budget = (pc.default_vmem_budget() if vmem_budget_bytes is None
              else vmem_budget_bytes)
    # two lazily-built pallas variants: volume-less batches (the common
    # case) compile out the CSI volume machinery entirely
    pallas_steps = {}

    def _pallas(enable_volumes: bool):
        if enable_volumes not in pallas_steps:
            pallas_steps[enable_volumes] = build_pallas_full_chain_step(
                args, num_gangs, num_groups, active_axes=active_axes,
                enable_volumes=enable_volumes)
        return pallas_steps[enable_volumes]

    def step(fc: FullChainInputs):
        # the volume-less variant first: a batch no variant admits (the
        # VMEM budget, or SMEM without volume rows) must not pay the
        # vol-flag resolution (which can cost a D2H readback for fresh
        # device-resident arrays)
        if not pallas_full_chain_fits(fc, budget, volumes=False):
            step.last_backend = "xla"
            return xla_step(fc)
        # the snapshot builder hands HOST (numpy) arrays, so this check
        # is sync-free; CONCRETE device arrays (device-resident snapshot
        # state) are checked once per buffer and memoized — only tracers
        # conservatively keep the volume machinery. Resolved BEFORE the
        # SMEM guard: a volume-less batch compiles the machinery out (a
        # 1-float placeholder rides the input slot), so high-VG batches
        # with no new PVCs still fit the Pallas budget.
        vn = fc.vol_needed
        if isinstance(vn, np.ndarray):
            vol = bool((vn > 0).any())
        elif isinstance(vn, jax.Array) and not isinstance(
                vn, jax.core.Tracer):
            import weakref

            # memoized per live array object: the weakref guards
            # against id() reuse after GC handing back a stale flag
            cache = step._vol_flags
            hit = cache.get(id(vn))
            if hit is not None and hit[0]() is vn:
                vol = hit[1]
            else:
                vol = bool((np.asarray(vn) > 0).any())
                if len(cache) > 64:
                    cache.clear()
                cache[id(vn)] = (weakref.ref(vn), vol)
        else:
            vol = True
        if pallas_full_chain_fits(fc, budget, volumes=vol):
            step.last_backend = "pallas"
            return _pallas(vol)(fc)
        step.last_backend = "xla"
        return xla_step(fc)

    step.last_backend = None
    step._vol_flags = {}
    return step
