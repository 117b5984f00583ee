"""Pallas TPU kernel for the FULL plugin-chain scheduling step.

Extends ops/pallas_step.py's VMEM-resident sequential loop to the whole chain
(models/full_chain.py): Fit + LoadAware + NodeNUMAResource (cpuset capacity,
SMT alignment, topology-policy admit, zone accounting) + ElasticQuota
admission — all state carried in VMEM across the (P,) grid. The gang Permit
barrier remains an XLA post-pass (one segment reduction per batch).

Layout choices (TPU lanes are 128 wide; f32 tile (8, 128)):
  * node arrays transposed [R, N] — nodes on lanes;
  * NUMA free state as one [K*R, N] buffer; zone k is the static row slice
    [k*R:(k+1)*R] (no 3D reductions needed — K is a static python loop);
  * quota tree in [R, G] lane layout — groups on lanes — so the per-pod
    request column [R, 1] broadcasts against (used, runtime) directly, and
    the ancestor-chain walk becomes one dynamic-sublane row slice of a
    host-precomputed [G, G] ancestor-closure matrix;
  * per-pod scalars (quota id, flags) in SMEM; per-pod vectors extracted from
    [R, P] arrays by a lane one-hot reduce.

Bindings are bit-identical to the XLA step — tests/test_pallas_full_chain.py
diffs them across NUMA/quota/gang configs, including the explicit
lowest-index-max tie-break Mosaic's argmax does not guarantee.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from koordinator_tpu.models.full_chain import (
    FullChainInputs,
    resolve_balance_idx,
)
from koordinator_tpu.ops import loadaware as la_ops
from koordinator_tpu.ops import pallas_common as pc
from koordinator_tpu.ops.gang import gang_permit_mask
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.numa import POLICY_NONE, POLICY_SINGLE_NUMA_NODE

from koordinator_tpu.ops.pallas_common import POD_BLOCK, UNROLL


def estimate_vmem_bytes(N: int, R: int, K: int, G: int, T: int = 0,
                        S: int = 0, PT: int = 0, SI: int = 0) -> int:
    """Upper bound of the scoped VMEM the compiler allocates for one
    pallas_call of the full-chain kernel (volumes on or off), mirroring
    the specs below: every blocked input and output is double-buffered,
    scratch is single; an [r, N] buffer takes pad8(r) rows of lanes(N);
    each NUMA zone slice that starts off an 8-row tile costs a relayout of
    2 * pad8(R) rows; 24 rows cover the other body temporaries. Fitted
    against the v5e compiler's reported allocation over R in 3..16, K in
    1..8 and 1k..8k nodes (PR 21: within 0-7% above it where R and the
    zone slices are tile-aligned, more above it where they are not).
    Independent of the pod count: pod columns stream in fixed blocks.
    Used by
    models.full_chain.build_best_full_chain_step to keep a shape whose
    state would not fit on the XLA step."""
    Rp, KR = pc.pad8(R), pc.pad8(K * R)
    extras_in = 3 * pc.pad8(T) + pc.pad8(S) + pc.pad8(PT) + pc.pad8(SI)
    rows_in = 4 * Rp + KR + 11 + extras_in
    rows_scratch = 3 * Rp + KR + 2 + 2 * pc.pad8(T) + pc.pad8(PT)
    misaligned = sum(1 for k in range(1, K) if (k * R) % 8)
    rows = (2 * rows_in + 2 * Rp + rows_scratch
            + 2 * Rp * misaligned + 24 + 4)  # + 4: the volume rows' temps
    G_lane = max(128, pc.lanes(max(G, 1)))
    fixed = (2 * 3 * Rp * POD_BLOCK               # pod column blocks
             + 7 * Rp * G_lane + 2 * UNROLL * G_lane  # quota state
             + 2 * UNROLL * 128)                  # chosen blocks
    return 4 * (rows * pc.lanes(N) + fixed)


# measured Mosaic SMEM allocation limit on v5e (the compile error reports
# "would exceed memory (size=1048576)")
SMEM_BUDGET_BYTES = 1 << 20


def estimate_smem_bytes(P: int, VG: int = 1, T: int = 0,
                        S2: int = 0) -> int:
    """Upper-bound SMEM footprint: 20 per-pod [P_pad] f32 scalar arrays,
    the flattened [P_pad * VG] volume-group rows (VG == 0 means the
    volume machinery is compiled out — a 1-float placeholder rides the
    input slot), the [max(T,1)] exists seed + scratch, and the
    [max(S2,1), max(T,1)] pod-pref weights. Used alongside
    estimate_vmem_bytes to degrade to the XLA step before Mosaic rejects
    the allocation (a high-VG batch is the only way past the budget at
    the shapes the VMEM check admits)."""
    P_pad = -(-P // POD_BLOCK) * POD_BLOCK
    vol_floats = VG * P_pad if VG else 1
    floats = (20 * P_pad + vol_floats + 2 * max(T, 1)
              + max(S2, 1) * max(T, 1))
    return 4 * floats


def _make_kernel(weights: np.ndarray, prod_mode: bool, N: int, R: int,
                 K: int, G: int, T: int = 0, S: int = 0, S2: int = 0,
                 PT: int = 0, SI: int = 0, VOL: bool = True,
                 VG: int = 1, BAL=(-1, -1)):
    wsum = float(max(weights.sum(), 1.0))
    consts = pc.weight_consts(weights)

    def kernel(
        # --- SMEM per-pod scalars
        prod_ref, valid_ref, ds_ref, gangok_ref,
        needsnuma_ref, needsbind_ref, fullpcpus_ref, cores_ref,  # f32 [P]
        taintmask_ref,                                            # f32 [P]
        affreq_ref, antireq_ref, affmatch_ref,   # f32 [P] term bitmasks
        skew0_ref, skew1_ref, skew2_ref,         # f32 [P] skew bit-planes
        affexists0_ref,                          # f32 [max(T,1)] host seed
        prefid_ref,                              # int32 [P] pref profile
        pprefid_ref,                             # int32 [P] pod-pref profile
        pprefw_ref,                              # f32 [max(S2,1), max(T,1)]
        portwants_ref,                           # f32 [P] port-slot bitmask
        volneeded_ref,                           # f32 [P * VG] new-PVC
        #     counts per node volume-group, FLATTENED row-major (pod p,
        #     group g at [p * VG + g]): a 2-D SMEM window lane-pads each
        #     row to 128 floats — 5 MB at 10k pods, over the 1 MB SMEM
        #     budget — so the per-pod rows stay 1-D
        imgid_ref,                               # int32 [P] image profile
        qid_ref,                                                  # int32 [P]
        # --- VMEM pod column blocks [R, POD_BLOCK]
        fitreq_ref, rawreq_ref, est_ref,
        # --- VMEM node state [R, N]
        alloc_ref, req0_ref, term_np_ref, term_pr_ref,
        # --- VMEM node rows [1, N]
        lafeas_np_ref, lafeas_pr_ref, node_ok_ref, score_valid_ref,
        has_topo_ref, bindfree0_ref, cpc_ref, policy_ref,
        taintpow_ref,                                  # [1, N] f32 2^group
        # --- VMEM numa [K*R, N] / per-pod ancestor rows [UNROLL, G_lane]
        #     (pre-gathered host-side: no in-kernel dynamic slice) / quota
        numafree0_ref, ancpod_ref, qused0_ref, qruntime_ref,
        # --- VMEM inter-pod affinity [max(T,1), N] + preferred-affinity
        #     profile score rows [max(S,1), N] + NodePorts slots
        #     [max(PT,1), N] + volume headroom [1, N] + ImageLocality rows
        affdom_ref, affcount0_ref, anticover0_ref, prefrows_ref,
        portused0_ref, volfree0_ref, volgrp_ref, imgrows_ref,
        # --- outputs
        chosen_ref,                 # (UNROLL, 1) int32 block, one per step
        requested_ref,              # [R, N] (carried)
        qused_ref,                  # [R, G] (carried)
        # --- scratch
        dnp_ref, dpr_ref,           # [R, N] (alloc - LoadAware base)
        numa_ref,                   # [K*R, N]
        bindfree_ref,               # [1, N]
        headroom_ref,               # [R, N] (alloc - requested)
        qacc_ref,                   # [R, G] quota-used accumulator
        affcount_ref,               # [max(T,1), N] carried term counts
        anticover_ref,              # [max(T,1), N] carried anti carriers
        portused_ref,               # [max(PT,1), N] carried port slots
        volfree_ref,                # [1, N] carried volume headroom
        affexists_ref,              # SMEM [max(T,1)] carried exists flags
    ):
        i = pl.program_id(0)
        alloc = alloc_ref[:]

        # Mutable chain state lives in VMEM scratch and is carried in
        # HEADROOM form — headroom_ref holds alloc - requested, dnp/dpr hold
        # alloc - (term + delta) — so the per-pod Fit check and
        # least-requested remainders are single compares/subtracts instead
        # of add-then-compare. The requested/quota-used OUTPUT buffers are
        # written only on the last grid step: output blocks round-trip to
        # HBM, so storing them per step would serialize the pipeline. All
        # quantities are packed integers < 2^24, so f32 arithmetic is exact
        # and the re-association preserves bit-parity with the XLA step.
        @pl.when(i == 0)
        def _init():
            headroom_ref[:] = alloc - req0_ref[:]
            dnp_ref[:] = alloc - term_np_ref[:]
            if prod_mode:
                dpr_ref[:] = alloc - term_pr_ref[:]
            numa_ref[:] = numafree0_ref[:]
            bindfree_ref[:] = bindfree0_ref[:]
            qacc_ref[:] = qused0_ref[:]
            if T:
                affcount_ref[:] = affcount0_ref[:]
                anticover_ref[:] = anticover0_ref[:]
                for t in range(T):
                    affexists_ref[t] = affexists0_ref[t]
            if PT:
                portused_ref[:] = portused0_ref[:]
            if VOL:
                volfree_ref[:] = volfree0_ref[:]

        # read-only node state: load once per grid step
        lafeas_np = lafeas_np_ref[0, :]
        lafeas_pr = lafeas_pr_ref[0, :]
        node_ok_row = node_ok_ref[0, :] > 0
        score_valid_row = score_valid_ref[0, :] > 0
        has_topo_row = has_topo_ref[0, :] > 0
        cpc = jnp.maximum(cpc_ref[0, :], 1.0)
        policy = policy_ref[0, :]
        taintpow = taintpow_ref[0, :]
        qruntime = qruntime_ref[:]
        w_col = pc.weight_col(consts, R)
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]
        safe_cap = jnp.where(alloc > 0, alloc, 1.0)
        cap_pos = alloc > 0
        if BAL[0] >= 0:
            # balanced-allocation reciprocals once per grid step (8 pods)
            # instead of two division rows per pod (pc.safe_reciprocal
            # documents the cross-kernel bit-parity contract)
            bal_inv_c, bal_inv_m = (
                pc.safe_reciprocal(alloc[axis:axis + 1, :]) for axis in BAL)
        single_node = policy == POLICY_SINGLE_NUMA_NODE              # [N]
        fitreq_blk = fitreq_ref[:]
        rawreq_blk = rawreq_ref[:]
        est_blk = est_ref[:]

        # mutable chain state: carried in registers across the UNROLL pods,
        # stored back to the scratch refs once per grid step
        headroom = headroom_ref[:]                      # alloc - requested
        headla_np = dnp_ref[:]                          # alloc - np base
        headla_pr = dpr_ref[:] if prod_mode else headla_np
        numa = [numa_ref[k * R:(k + 1) * R, :] for k in range(K)]
        bindfree = bindfree_ref[0, :]
        qused = qacc_ref[:]                                          # [R, G]
        aff_dom = [affdom_ref[t:t + 1, :] for t in range(T)]         # [1, N]
        aff_count = [affcount_ref[t:t + 1, :] for t in range(T)]
        anti_cover = [anticover_ref[t:t + 1, :] for t in range(T)]
        port_used = [portused_ref[s:s + 1, :] for s in range(PT)]
        vol_free = volfree_ref[0, :] if VOL else None
        volgrp = volgrp_ref[0, :] if VOL else None  # [N] f32 group ids

        for j in range(UNROLL):
            p = i * UNROLL + j
            prod = prod_ref[p] > 0
            needs_numa = needsnuma_ref[p] > 0
            needs_bind = needsbind_ref[p] > 0
            full_pcpus = fullpcpus_ref[p] > 0
            cores = cores_ref[p]
            gid = qid_ref[p]
            has_quota = gid >= 0

            lane = (i * UNROLL) % POD_BLOCK + j
            pod_mask = pc.make_pod_mask(lane, POD_BLOCK)
            fit_need = pc.pod_column(fitreq_blk, pod_mask)
            raw_req = pc.pod_column(rawreq_blk, pod_mask)
            est = pc.pod_column(est_blk, pod_mask)                   # [R, 1]
            # effective requests: rows with no demand compare true against
            # anything, so (req <= 0) | (req <= free) is one compare
            fit_eff = jnp.where(fit_need > 0, fit_need, pc.NEG_F32)
            raw_eff = jnp.where(raw_req > 0, raw_req, pc.NEG_F32)

            # ---- PreFilter: quota admission along the ancestor closure row
            anc_row = ancpod_ref[j:j + 1, :]                         # [1, G]
            # f32 throughout: Mosaic can't truncate narrow bool vectors
            viol = jnp.max(
                jnp.where((raw_req > 0) & (qused + raw_req > qruntime),
                          1.0, 0.0),
                axis=0, keepdims=True)                               # [1, G]
            quota_ok = jnp.sum(anc_row * viol) <= 0.0
            admit = (gangok_ref[p] > 0) & (quota_ok | ~has_quota)

            # ---- Filter: Fit
            fit = jnp.all(headroom >= fit_eff, axis=0)               # [N]
            # ---- Filter: LoadAware thresholds
            la_feas = jnp.where(prod, lafeas_pr, lafeas_np) > 0
            la_ok = la_feas | (ds_ref[p] > 0)
            # ---- Filter: cpuset capacity + SMT alignment
            smt_ok = (~full_pcpus) | (
                jnp.abs(jnp.remainder(cores, cpc)) < 0.5)
            # f32-valued selects throughout the filter chain: Mosaic cannot
            # truncate/select narrow bool vectors
            cpuset_ok_f = jnp.where(
                has_topo_row & smt_ok & (cores <= bindfree), 1.0, 0.0)
            cpuset_ok = jnp.where(needs_bind, cpuset_ok_f, 1.0) > 0
            # ---- Filter: NUMA topology admit (ops/numa.numa_admit_row):
            # per-zone fits (ascending cumulative free kept for the
            # waterfall), lowest fitting zone wins
            fits = []
            cumfree = []
            run = jnp.zeros((R, N), jnp.float32) if K == 0 else None
            for k in range(K):
                fits.append(jnp.all(numa[k] >= raw_eff, axis=0))
                run = numa[k] if run is None else run + numa[k]
                cumfree.append(run)
            zone = jnp.full((N,), K, jnp.int32)
            for k in range(K - 1, -1, -1):
                zone = jnp.where(fits[k], jnp.int32(k), zone)        # lowest k
            fits_total = jnp.all(run >= raw_eff, axis=0)
            any_zone_f = jnp.where(zone < K, 1.0, 0.0)
            fits_total_f = jnp.where(fits_total, 1.0, 0.0)
            numa_ok_f = jnp.where(single_node, any_zone_f, fits_total_f)
            numa_ok_f = jnp.where(policy == POLICY_NONE, 1.0, numa_ok_f)
            numa_ok = jnp.where(needs_numa, numa_ok_f, 1.0) > 0

            # ---- Filter: TaintToleration — bit test in exact f32 arithmetic
            # (floor/mod; Mosaic has no shift-by-vector): bit g of mask is
            # floor(mask / 2^g) mod 2
            taint_ok = jnp.remainder(
                jnp.floor(taintmask_ref[p] / taintpow), 2.0) >= 1.0
            # ---- Filter: NodePorts (wanted slot free) + CSI volume limit
            # (VOL statically gates the volume machinery: volume-less
            # batches — the common case — pay nothing per pod)
            feasible = (node_ok_row & fit & la_ok & cpuset_ok
                        & numa_ok & taint_ok & admit)
            if VOL:
                # per-node NEW attachments: the pod's [VG] row gathered by
                # the node's volume group (select over static VG; group ids
                # are exact small-integer f32; flattened SMEM indexing)
                vol_needed = jnp.where(
                    volgrp == 0.0, volneeded_ref[p * VG], 0.0)
                for g in range(1, VG):
                    vol_needed = jnp.where(
                        volgrp == float(g), volneeded_ref[p * VG + g],
                        vol_needed)
                feasible = feasible & (
                    (vol_needed <= 0.0) | (vol_free >= vol_needed))
            for s in range(PT):
                want_s = jnp.remainder(
                    jnp.floor(portwants_ref[p] / float(1 << s)), 2.0) >= 1.0
                feasible = feasible & (
                    (~want_s) | (port_used[s][0, :] <= 0))
            # ---- Filter: InterPodAffinity (ops/podaffinity.py). Term
            # membership rides per-pod SMEM bitmasks; 2^t is a static
            # Python constant, so the bit tests are scalar ops.
            for t in range(T):
                aff_t = jnp.remainder(
                    jnp.floor(affreq_ref[p] / float(1 << t)), 2.0) >= 1.0
                anti_t = jnp.remainder(
                    jnp.floor(antireq_ref[p] / float(1 << t)), 2.0) >= 1.0
                match_t = jnp.remainder(
                    jnp.floor(affmatch_ref[p] / float(1 << t)), 2.0) >= 1.0
                count_t = aff_count[t][0, :]
                empty_t = count_t <= 0                              # [N]
                anti_ok = (~anti_t) | empty_t
                # symmetric anti-affinity: carriers of anti term t in this
                # node's domain block any pod matching t
                sym_ok = (~match_t) | (anti_cover[t][0, :] <= 0)
                boot = match_t & (affexists_ref[t] <= 0.0)
                dom_valid_t = aff_dom[t][0, :] >= 0
                aff_ok = (~aff_t) | boot | (dom_valid_t & ~empty_t)
                feasible = feasible & anti_ok & sym_ok & aff_ok
                # PodTopologySpread: skew reconstructed from 3 bit-planes
                bit = lambda ref: jnp.remainder(  # noqa: E731
                    jnp.floor(ref[p] / float(1 << t)), 2.0)
                skew = (bit(skew0_ref) + 2.0 * bit(skew1_ref)
                        + 4.0 * bit(skew2_ref))
                self_m = jnp.where(match_t, 1.0, 0.0)
                # min over domains the pod is ELIGIBLE for (admission test)
                min_count = jnp.min(
                    jnp.where(dom_valid_t & taint_ok, count_t, jnp.inf))
                spread_ok = (skew <= 0.0) | (
                    dom_valid_t & (count_t + self_m - min_count <= skew))
                feasible = feasible & spread_ok

            # ---- Score: LoadAware + NodeNUMAResource least-allocated
            headla = jnp.where(prod, headla_pr, headla_np) if prod_mode \
                else headla_np
            la_per_r = pc.least_requested_rem(headla - est, safe_cap, cap_pos)
            nu_per_r = pc.least_requested_rem(headroom - raw_req, safe_cap,
                                              cap_pos)
            la_score = pc.weighted_floor_score_col(la_per_r, w_col, wsum)
            la_score = jnp.where(score_valid_row, la_score, 0.0)
            score = la_score + pc.weighted_floor_score_col(nu_per_r, w_col,
                                                           wsum)
            # NodeResourcesBalancedAllocation: 2-axis std == |fc - fm| / 2.
            # requested = alloc - headroom (exact integers < 2^24, so the
            # re-association matches the XLA evaluator bit-for-bit)
            if BAL[0] >= 0:
                ci, mi = BAL

                def _frac(axis, inv):
                    cap = alloc[axis:axis + 1, :]
                    used = (cap - headroom[axis:axis + 1, :]
                            + fit_need[axis, 0])
                    return jnp.minimum(used * inv, 1.0)

                bal_std = jnp.abs(
                    _frac(ci, bal_inv_c) - _frac(mi, bal_inv_m)) * 0.5
                score = score + jnp.floor(
                    (1.0 - bal_std) * 100.0)[0, :]
            # preferred node affinity: static profile row one-hot select
            if S:
                sid = prefid_ref[p]
                for s in range(S):
                    score = score + jnp.where(
                        sid == s, prefrows_ref[s:s + 1, :][0, :], 0.0)
            # ImageLocality: static profile rows, same select pattern
            if SI:
                iid = imgid_ref[p]
                for s in range(SI):
                    score = score + jnp.where(
                        iid == s, imgrows_ref[s:s + 1, :][0, :], 0.0)
            # preferred POD affinity: weighted count sum, max-min normalized
            # per pod (weights read as SMEM scalars by traced profile id)
            if T and S2:
                sid2 = pprefid_ref[p]
                s2c = jnp.maximum(sid2, 0)
                raw = jnp.zeros((N,), jnp.float32)
                for t in range(T):
                    raw = raw + pprefw_ref[s2c, t] * aff_count[t][0, :]
                # max-min over node_ok only (upstream NormalizeScore spans
                # the candidate set; padded rows must not anchor the scale)
                mx = jnp.max(jnp.where(node_ok_row, raw, -jnp.inf))
                mn = jnp.min(jnp.where(node_ok_row, raw, jnp.inf))
                norm = jnp.where(
                    mx > mn,
                    jnp.floor((raw - mn) * 100.0 / (mx - mn)), 0.0)
                score = score + jnp.where(sid2 >= 0, norm, 0.0)
            score = jnp.where(feasible, score, -1.0)

            best, maxv, _ = pc.lowest_index_max(score, N, iota)
            found = (maxv >= 0.0) & (valid_ref[p] > 0)
            sel = ((iota == best) & found).astype(jnp.float32)       # [N]

            # ---- Reserve: state updates
            headroom = headroom - sel[None, :] * fit_need
            est_add = sel[None, :] * est
            headla_np = headla_np - est_add
            if prod_mode:
                headla_pr = headla_pr - jnp.where(prod, 1.0, 0.0) * est_add
            bindfree = bindfree - sel * jnp.where(needs_bind, cores, 0.0)
            # ports/volumes: bind wanted slots, debit volume headroom
            for s in range(PT):
                want_s = jnp.remainder(
                    jnp.floor(portwants_ref[p] / float(1 << s)), 2.0) >= 1.0
                port_used[s] = jnp.maximum(
                    port_used[s],
                    (sel * jnp.where(want_s, 1.0, 0.0))[None, :])
            if VOL:
                vol_free = vol_free - sel * vol_needed
            # numa: single-zone subtract + lowest-zones-first waterfall
            # (disjoint). Only the SingleNUMANode policy pins a zone
            # (numa_admit_row returns zone = -1 otherwise); every other
            # policy spread-fills. The waterfall take is the closed form
            # take_k = clip(D - cumfree_{<k}, 0, free_k): exact for packed
            # integers, identical to the sequential remaining-carry.
            apply_numa = sel * jnp.where(needs_numa, 1.0, 0.0)       # [N]
            single_m = apply_numa * jnp.where(
                single_node & (zone < K), 1.0, 0.0)
            spread_m = apply_numa - single_m
            demand = raw_req * spread_m[None, :]                     # [R, N]
            for k in range(K):
                zone_m = (single_m * jnp.where(zone == k, 1.0, 0.0))[None, :]
                free_k = numa[k] - raw_req * zone_m
                # cumfree >= 0, so off-demand columns clamp to 0 unmasked
                rem = demand if k == 0 else \
                    jnp.maximum(demand - cumfree[k - 1], 0.0)
                numa[k] = free_k - jnp.minimum(free_k, rem)
            # quota: add along the ancestor closure
            q_apply = jnp.where(found & has_quota, 1.0, 0.0)
            qused = qused + raw_req * anc_row * q_apply
            # affinity: raise matched terms' counts over the chosen domain
            # and latch the exists flag (even on an unlabeled node)
            for t in range(T):
                match_t = jnp.remainder(
                    jnp.floor(affmatch_ref[p] / float(1 << t)), 2.0) >= 1.0
                anti_t = jnp.remainder(
                    jnp.floor(antireq_ref[p] / float(1 << t)), 2.0) >= 1.0
                dom_row = aff_dom[t][0, :]
                chosen_dom = jnp.sum(sel * dom_row)
                in_dom = (chosen_dom >= 0) & (dom_row == chosen_dom)
                inc = jnp.where((found & match_t) & in_dom, 1.0, 0.0)
                aff_count[t] = aff_count[t] + inc[None, :]
                inc_cov = jnp.where((found & anti_t) & in_dom, 1.0, 0.0)
                anti_cover[t] = anti_cover[t] + inc_cov[None, :]
                affexists_ref[t] = jnp.where(
                    found & match_t, 1.0, affexists_ref[t])

            picked = jnp.where(found, best, jnp.int32(-1))
            chosen_ref[j:j + 1, :] = picked.reshape(1, 1)

        headroom_ref[:] = headroom
        dnp_ref[:] = headla_np
        if prod_mode:
            dpr_ref[:] = headla_pr
        for k in range(K):
            numa_ref[k * R:(k + 1) * R, :] = numa[k]
        bindfree_ref[:] = bindfree[None, :]
        qacc_ref[:] = qused
        for t in range(T):
            affcount_ref[t:t + 1, :] = aff_count[t]
            anticover_ref[t:t + 1, :] = anti_cover[t]
        for s in range(PT):
            portused_ref[s:s + 1, :] = port_used[s]
        if VOL:
            volfree_ref[:] = vol_free[None, :]

        @pl.when(i == pl.num_programs(0) - 1)
        def _emit():
            requested_ref[:] = alloc - headroom
            qused_ref[:] = qused

    return kernel


def pallas_full_chain_search(fc: FullChainInputs, args: LoadAwareArgs,
                             active_axes=None, enable_volumes: bool = True,
                             interpret: bool = False
                             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The kernel's serial round over ``fc``, before the gang Permit
    post-pass: (chosen[P], requested[N, R], quota_used[G, R]), where
    ``chosen`` is each pod's tentative node (-1 none) under in-batch state
    feedback. Traceable and unjitted: build_pallas_full_chain_step applies
    Permit against ``fc.gang_assumed``, the fused wave body
    (models/fused_waves.py) against its carried counters.

    enable_volumes=False compiles OUT the CSI volume-limit machinery (the
    per-pod [N] compare/select/update) — valid only for batches where no
    pod mounts volumes."""
    weights = args.weight_vector()  # static float32, baked in
    if active_axes is not None:
        weights = weights[list(active_axes)]
    prod_mode = args.score_according_prod_usage
    inputs = fc.base
    P, R = inputs.fit_requests.shape
    N = inputs.allocatable.shape[0]
    K = fc.numa_free.shape[1]
    G = fc.quota_used.shape[0]
    G_eff = max(G, 1)
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
    # ancestor closure computed traceably (inputs may be tracers under jit):
    # closure[g, a] = 1 iff a appears in g's chain (-1 padding never matches)
    if G:
        anc = jnp.any(
            fc.quota_ancestors[:, :, None]
            == jnp.arange(G, dtype=fc.quota_ancestors.dtype)[None, None, :],
            axis=1,
        ).astype(jnp.float32)
    else:
        anc = jnp.zeros((1, 1), jnp.float32)

    f32, row = pc.f32, pc.row
    P_pad, pad_p = pc.pad_pods(P, POD_BLOCK)
    spad = lambda x: jnp.pad(f32(x), pad_p)  # noqa: E731

    def pods_t(x):  # [P, R] -> [R, P_pad]
        return jnp.pad(f32(x), pad_p + [(0, 0)]).T

    # numa [N, K, R] -> [K*R, N]
    numa0 = jnp.transpose(f32(fc.numa_free), (1, 2, 0)).reshape(K * R, N)
    # quota lane axis padded to >= 128: Mosaic cannot truncate the narrow
    # bool vectors that comparisons on a (R, G<128) block would produce.
    # Padding runtime with +inf keeps phantom groups from ever violating.
    G_lane = max(128, -(-G_eff // 128) * 128)
    if G:
        qused0 = jnp.pad(f32(fc.quota_used).T, [(0, 0), (0, G_lane - G)])
        qruntime = jnp.pad(f32(fc.quota_runtime).T,
                           [(0, 0), (0, G_lane - G)],
                           constant_values=jnp.inf)
        qid = jnp.asarray(fc.quota_id, jnp.int32)
    else:
        qused0 = jnp.zeros((R, G_lane), jnp.float32)
        qruntime = jnp.full((R, G_lane), jnp.inf, jnp.float32)
        qid = jnp.full(P, -1, jnp.int32)
    # pre-gather each pod's ancestor-closure row: [P_pad, G_lane] in HBM,
    # streamed as [UNROLL, G_lane] blocks (quota-less pods hit row 0 of
    # an all-zeros closure or carry has_quota == False, so the row is
    # never applied)
    qid_pad = jnp.pad(qid, pad_p, constant_values=-1)
    anc = jnp.pad(anc, [(0, 0), (0, G_lane - anc.shape[1])])
    anc_pod = jnp.take(anc, jnp.maximum(qid_pad, 0), axis=0)

    # inter-pod affinity: per-pod term rows become [P] f32 bitmasks
    # (exact: T <= 24 < 2^24), node state transposes to [T, N]
    T = fc.aff_dom.shape[1]
    T_eff = max(T, 1)
    pow_t = jnp.asarray(
        [float(1 << t) for t in range(T)], jnp.float32)
    if T:
        def bitmask(rows):  # [P, T] bool -> [P_pad] f32
            return jnp.pad(
                jnp.sum(f32(rows) * pow_t[None, :], axis=1), pad_p)

        affreq_m = bitmask(fc.pod_aff_req)
        antireq_m = bitmask(fc.pod_anti_req)
        affmatch_m = bitmask(fc.pod_aff_match)
        skew_i = jnp.asarray(fc.pod_spread_skew, jnp.int32)
        skew0_m = bitmask((skew_i & 1) > 0)
        skew1_m = bitmask((skew_i & 2) > 0)
        skew2_m = bitmask((skew_i & 4) > 0)
        affexists0 = f32(fc.aff_exists)
        affdom0 = f32(fc.aff_dom).T
        affcount0 = f32(fc.aff_count).T
        anticover0 = f32(fc.anti_cover).T
    else:
        affreq_m = antireq_m = affmatch_m = jnp.zeros(P_pad, jnp.float32)
        skew0_m = skew1_m = skew2_m = affreq_m
        affexists0 = jnp.zeros(1, jnp.float32)
        affdom0 = jnp.full((1, N), -1.0, jnp.float32)
        affcount0 = jnp.zeros((1, N), jnp.float32)
        anticover0 = jnp.zeros((1, N), jnp.float32)

    # preference-less batches carry ZERO profile columns (snapshot emits
    # true empties); the kernel skips the profile loops and the input
    # slot gets one placeholder row
    S = fc.pref_scores.shape[1]
    S_eff = max(S, 1)
    prefrows0 = (f32(fc.pref_scores).T if S
                 else jnp.zeros((1, N), jnp.float32))
    prefid_pad = jnp.pad(jnp.asarray(fc.pod_pref_id, jnp.int32), pad_p,
                         constant_values=-1)
    S2 = fc.ppref_w.shape[0] if T else 0  # zero rows == no profiles
    pprefid_pad = jnp.pad(jnp.asarray(fc.pod_ppref_id, jnp.int32), pad_p,
                          constant_values=-1)
    pprefw0 = (f32(fc.ppref_w) if S2
               else jnp.zeros((1, max(T, 1)), jnp.float32))

    # NodePorts slots as per-pod f32 bitmasks (PT <= 16 < 2^24, exact),
    # node state transposed [PT, N]; volume headroom as one [1, N] row;
    # ImageLocality rows like the preference profiles
    PT = fc.port_used.shape[1]
    PT_eff = max(PT, 1)
    if PT:
        pow_s = jnp.asarray(
            [float(1 << s) for s in range(PT)], jnp.float32)
        portwants_m = jnp.pad(jnp.sum(
            f32(fc.pod_port_wants) * pow_s[None, :], axis=1), pad_p)
        portused0 = f32(fc.port_used).T
    else:
        portwants_m = jnp.zeros(P_pad, jnp.float32)
        portused0 = jnp.zeros((1, N), jnp.float32)
    VG = fc.vol_needed.shape[1]
    if enable_volumes:
        volneeded_pad = jnp.pad(
            f32(fc.vol_needed), pad_p + [(0, 0)]).reshape(-1)
    else:
        # volume machinery compiled out: the kernel never reads the
        # ref, so a 1-float placeholder keeps high-VG volume-less
        # batches inside the SMEM budget
        volneeded_pad = jnp.zeros(1, jnp.float32)
    volfree0 = f32(fc.vol_free)[None, :]
    volgrp0 = f32(fc.node_vol_group)[None, :]
    SI = fc.img_scores.shape[1]
    SI_eff = max(SI, 1)
    imgrows0 = (f32(fc.img_scores).T if SI
                else jnp.zeros((1, N), jnp.float32))
    imgid_pad = jnp.pad(jnp.asarray(fc.pod_img_id, jnp.int32), pad_p,
                        constant_values=-1)

    kernel = _make_kernel(weights, prod_mode, N, R, K, G_eff, T, S, S2,
                          PT, SI, VOL=enable_volumes, VG=VG,
                          BAL=resolve_balance_idx(active_axes))
    grid_inputs = (
        spad(inputs.is_prod), spad(inputs.pod_valid),
        spad(inputs.is_daemonset), spad(gang_pod_ok),
        spad(fc.needs_numa), spad(fc.needs_bind),
        spad(fc.full_pcpus), spad(fc.cores_needed),
        jnp.pad(f32(fc.pod_taint_mask), pad_p, constant_values=1.0),
        affreq_m, antireq_m, affmatch_m,
        skew0_m, skew1_m, skew2_m, affexists0,
        prefid_pad, pprefid_pad, pprefw0,
        portwants_m, volneeded_pad, imgid_pad,
        qid_pad,
        pods_t(inputs.fit_requests), pods_t(fc.requests),
        pods_t(inputs.estimated),
        f32(inputs.allocatable).T, f32(inputs.requested).T,
        f32(inputs.la_term_nonprod).T, f32(inputs.la_term_prod).T,
        row(~reject_np), row(~reject_prod),
        row(inputs.node_ok), row(inputs.la_score_valid),
        row(fc.has_topology), row(fc.bind_free), row(fc.cpus_per_core),
        jnp.asarray(fc.numa_policy, jnp.int32)[None, :],
        jnp.exp2(f32(fc.node_taint_group))[None, :],
        numa0, anc_pod, qused0, qruntime,
        affdom0, affcount0, anticover0, prefrows0,
        portused0, volfree0, volgrp0, imgrows0,
    )
    smem, full = pc.smem_spec, pc.full_spec
    pod_spec = pc.pod_block_spec(R)
    chosen, requested_t, qused_t = pl.pallas_call(
        kernel,
        grid=(P_pad // UNROLL,),
        in_specs=(
            [smem()] * 23
            + [pod_spec] * 3
            + [full((R, N))] * 4
            + [full((1, N))] * 9
            + [full((K * R, N)),
               pl.BlockSpec((UNROLL, G_lane), lambda i: (i, 0)),
               full((R, G_lane)), full((R, G_lane))]
            + [full((T_eff, N))] * 3
            + [full((S_eff, N))]
            + [full((PT_eff, N)), full((1, N)), full((1, N)),
               full((SI_eff, N))]
        ),
        out_specs=[
            pc.chosen_block_spec(),
            full((R, N)),
            full((R, G_lane)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((R, N), jnp.float32),
            jax.ShapeDtypeStruct((R, G_lane), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, N), jnp.float32),
            pltpu.VMEM((R, N), jnp.float32),
            pltpu.VMEM((K * R, N), jnp.float32),
            pltpu.VMEM((1, N), jnp.float32),
            pltpu.VMEM((R, N), jnp.float32),
            pltpu.VMEM((R, G_lane), jnp.float32),
            pltpu.VMEM((T_eff, N), jnp.float32),
            pltpu.VMEM((T_eff, N), jnp.float32),
            pltpu.VMEM((PT_eff, N), jnp.float32),
            pltpu.VMEM((1, N), jnp.float32),
            pltpu.SMEM((T_eff,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(*grid_inputs)
    quota_used = qused_t[:, :G].T if G else fc.quota_used
    return chosen[:P, 0], requested_t.T, quota_used


def build_pallas_full_chain_step(args: LoadAwareArgs, num_gangs: int,
                                 num_groups: int, interpret: bool = False,
                                 jit: bool = True, active_axes=None,
                                 enable_volumes: bool = True):
    """FullChainInputs -> (chosen[P], requested[N, R], quota_used[G, R]);
    same contract as models.full_chain.build_full_chain_step.

    enable_volumes=False compiles OUT the CSI volume-limit machinery
    (pallas_full_chain_search) — valid only for batches where no pod
    mounts volumes; the backend selector checks the concrete inputs and
    picks the variant."""

    def full_chain_pallas(
            fc: FullChainInputs
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        chosen, requested, quota_used = pallas_full_chain_search(
            fc, args, active_axes=active_axes,
            enable_volumes=enable_volumes, interpret=interpret)

        # ---- Permit barrier (XLA post-pass, once per batch)
        keep = gang_permit_mask(
            chosen, fc.gang_id, fc.gang_min_member, fc.gang_assumed,
            fc.gang_group_id, num_gangs, num_groups,
        )
        return jnp.where(keep, chosen, -1), requested, quota_used

    return jax.jit(full_chain_pallas) if jit else full_chain_pallas
