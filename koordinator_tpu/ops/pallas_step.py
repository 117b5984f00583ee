"""Pallas TPU kernel for the serial-parity scheduling step.

The XLA path (models/scheduler_model.build_schedule_step) expresses the
sequential pod loop as `lax.fori_loop`; every iteration re-reads the [N, R]
node state from wherever XLA materialized it. This kernel instead runs the
WHOLE pod loop inside one `pallas_call` with the node state pinned in VMEM:

  * grid = (P_pad / UNROLL,) — TPU grids are sequential, so scratch buffers
    carry the running state (headroom, LoadAware assign-cache deltas) from
    step to step with zero HBM round-trips; each step walks UNROLL pods in
    order with the state held in registers;
  * node arrays are laid out transposed [R, N] so the N axis rides the
    128-wide lanes (R <= 16 sublanes, f32 min tile is (8, 128));
  * pod columns stream in as [R, POD_BLOCK] blocks; per-pod scalars sit in
    SMEM.

Semantics are bit-identical to the XLA step (same go_round / floor-division
helpers, same first-max tie-break); tests/test_pallas_step.py diffs the two
paths on randomized clusters. VMEM budget: ~8 [R, N] f32 arrays — N up to
~20k fits the 16 MB/core budget at R = 16.

Reference anchor: the loop this replaces is the scheduleOne Filter+Score
fan-out (SURVEY.md section 3.1); state carried corresponds to the Fit
`requested` cache and LoadAware's podAssignCache estimates
(plugins/loadaware/pod_assign_cache.go).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from koordinator_tpu.ops import loadaware as la_ops
from koordinator_tpu.ops import pallas_common as pc
from koordinator_tpu.ops.loadaware import LoadAwareArgs


from koordinator_tpu.ops.pallas_common import POD_BLOCK, UNROLL


def _make_kernel(weights: np.ndarray, prod_mode: bool, N: int, R: int):
    wsum = float(max(weights.sum(), 1.0))
    consts = pc.weight_consts(weights)

    def kernel(
        prod_ref, valid_ref, ds_ref,                     # [P] SMEM scalars
        req_ref, est_ref,                                # [R, POD_BLOCK] blocks
        alloc_ref, req0_ref, term_np_ref, term_pr_ref,   # [R, N] VMEM
        lafeas_np_ref, lafeas_pr_ref, node_ok_ref, score_valid_ref,  # [1, N]
        chosen_ref,                                      # [UNROLL, 1] out block
        requested_ref,                                   # [R, N] f32 out
        dnp_ref, dpr_ref,                                # [R, N] scratch
        headroom_ref,                                    # [R, N] scratch
    ):
        i = pl.program_id(0)
        alloc = alloc_ref[:]                             # [R, N]

        # state carried in headroom form (alloc - requested, alloc - base):
        # Fit and least-requested become single compares/subtracts; exact
        # f32 integer arithmetic keeps bindings bit-identical (see
        # ops/pallas_full_chain.py)
        @pl.when(i == 0)
        def _init():
            headroom_ref[:] = alloc - req0_ref[:]
            dnp_ref[:] = alloc - term_np_ref[:]
            if prod_mode:
                dpr_ref[:] = alloc - term_pr_ref[:]

        lafeas_np = lafeas_np_ref[0, :]
        lafeas_pr = lafeas_pr_ref[0, :]
        node_ok_row = node_ok_ref[0, :] > 0
        score_valid_row = score_valid_ref[0, :] > 0
        safe_cap = jnp.where(alloc > 0, alloc, 1.0)
        cap_pos = alloc > 0
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]
        w_col = pc.weight_col(consts, R)
        req_blk = req_ref[:]
        est_blk = est_ref[:]

        headroom = headroom_ref[:]
        headla_np = dnp_ref[:]
        headla_pr = dpr_ref[:] if prod_mode else headla_np

        for j in range(UNROLL):
            p = i * UNROLL + j
            prod = prod_ref[p] > 0
            lane = (i * UNROLL) % POD_BLOCK + j
            pod_mask = pc.make_pod_mask(lane, POD_BLOCK)
            need = pc.pod_column(req_blk, pod_mask)      # [R, 1]
            est = pc.pod_column(est_blk, pod_mask)       # [R, 1]
            need_eff = jnp.where(need > 0, need, pc.NEG_F32)
            fit = jnp.all(headroom >= need_eff, axis=0)  # [N]

            # LoadAware least-allocated score with in-batch deltas
            headla = jnp.where(prod, headla_pr, headla_np) if prod_mode \
                else headla_np
            per_r = pc.least_requested_rem(headla - est, safe_cap, cap_pos)
            score = pc.weighted_floor_score_col(per_r, w_col, wsum)
            score = jnp.where(score_valid_row, score, 0.0)

            la_feas = jnp.where(prod, lafeas_pr, lafeas_np) > 0
            la_ok = la_feas | (ds_ref[p] > 0)
            feasible = node_ok_row & fit & la_ok
            score = jnp.where(feasible, score, -1.0)

            best, maxv, _ = pc.lowest_index_max(score, N, iota)
            found = (maxv >= 0.0) & (valid_ref[p] > 0)
            sel = ((iota == best) & found).astype(jnp.float32)   # [N]

            headroom = headroom - sel[None, :] * need
            est_add = sel[None, :] * est
            headla_np = headla_np - est_add
            if prod_mode:
                headla_pr = headla_pr - jnp.where(prod, 1.0, 0.0) * est_add
            picked = jnp.where(found, best, jnp.int32(-1))
            chosen_ref[j:j + 1, :] = picked.reshape(1, 1)

        headroom_ref[:] = headroom
        dnp_ref[:] = headla_np
        if prod_mode:
            dpr_ref[:] = headla_pr

        @pl.when(i == pl.num_programs(0) - 1)
        def _emit():
            requested_ref[:] = alloc - headroom

    return kernel


def estimate_vmem_bytes(N: int, R: int) -> int:
    """Upper bound of the scoped VMEM the compiler allocates for one
    pallas_call of the schedule kernel: the 4 [R, N] and 4 [1, N] inputs
    and the [R, N] output double-buffered, 3 [R, N] scratch buffers, and
    2 * pad8(R) rows of body temporaries, rows padded to the 8-row tile
    and N to 128 lanes; pod columns stream, so the pod count does not
    enter. Fitted against the v5e compiler's reported
    allocation (PR 21: 9% above it at R=14; the compiler single-buffers
    the node inputs on short grids, so small batches sit further below).
    Used by models.scheduler_model.build_best_schedule_step to keep a
    shape that would not fit on the XLA step."""
    Rp = pc.pad8(R)
    rows = 2 * (4 * Rp + 4) + 2 * Rp + 3 * Rp + 2 * Rp
    fixed = 2 * 2 * Rp * POD_BLOCK + 2 * UNROLL * 128
    return 4 * (rows * pc.lanes(N) + fixed)


def build_pallas_schedule_step(args: LoadAwareArgs, interpret: bool = False,
                               jit: bool = True):
    """ScheduleInputs -> (chosen [P] int32, requested [N, R] f32), same
    contract as models.scheduler_model.build_schedule_step, computed by the
    VMEM-resident Pallas kernel. `interpret=True` runs the kernel in the
    Pallas interpreter (CPU parity tests)."""
    prod_mode = args.score_according_prod_usage
    weights = np.asarray(args.weight_vector(), np.float32)

    def schedule_pallas(inputs) -> Tuple[jnp.ndarray, jnp.ndarray]:
        P, R = inputs.fit_requests.shape
        N = inputs.allocatable.shape[0]
        reject_np, reject_prod = la_ops.loadaware_node_reject(
            inputs.allocatable,
            inputs.la_filter_usage,
            inputs.la_has_filter_usage,
            inputs.la_filter_thresholds,
            inputs.la_prod_thresholds,
            inputs.la_prod_pod_usage,
            inputs.la_filter_skip,
        )
        f32, row = pc.f32, pc.row
        P_pad, pad_p = pc.pad_pods(P, POD_BLOCK)

        def pods_t(x):  # [P, R] -> [R, P_pad]
            return jnp.pad(f32(x), pad_p + [(0, 0)]).T

        kernel = _make_kernel(weights, prod_mode, N, R)
        grid_inputs = (
            jnp.pad(f32(inputs.is_prod), pad_p),
            jnp.pad(f32(inputs.pod_valid), pad_p),  # padding invalid => -1
            jnp.pad(f32(inputs.is_daemonset), pad_p),
            pods_t(inputs.fit_requests), pods_t(inputs.estimated),
            f32(inputs.allocatable).T, f32(inputs.requested).T,
            f32(inputs.la_term_nonprod).T, f32(inputs.la_term_prod).T,
            row(~reject_np), row(~reject_prod),
            row(inputs.node_ok), row(inputs.la_score_valid),
        )
        smem, full = pc.smem_spec, pc.full_spec
        pod_spec = pc.pod_block_spec(R)
        chosen, requested_t = pl.pallas_call(
            kernel,
            grid=(P_pad // UNROLL,),
            in_specs=[
                smem(), smem(), smem(),
                pod_spec, pod_spec,
                full((R, N)), full((R, N)), full((R, N)), full((R, N)),
                full((1, N)), full((1, N)), full((1, N)), full((1, N)),
            ],
            out_specs=[
                pc.chosen_block_spec(),
                full((R, N)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
                jax.ShapeDtypeStruct((R, N), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, N), jnp.float32),
                pltpu.VMEM((R, N), jnp.float32),
                pltpu.VMEM((R, N), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(*grid_inputs)
        return chosen[:P, 0], requested_t.T

    return jax.jit(schedule_pallas) if jit else schedule_pallas
