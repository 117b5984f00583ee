"""Wave-parallel full-chain scheduling step: serial bindings, parallel waves.

The serial kernel (models/full_chain.py) walks pods one at a time because each
binding mutates node/quota state. But the chain's state updates are MONOTONE:
committing a pod only ever (a) raises a node's requested/estimated usage and
shrinks its NUMA/bindable-CPU headroom — so that node's feasibility and score
for later pods can only get WORSE — and (b) raises quota usage along one
ancestor chain — so quota admission can only flip admit -> reject. Under
monotone decay, a pod's serial decision is EXACTLY its decision against the
wave-start state unless something it depends on was touched earlier in the
wave:

  * its argmax node was also chosen by an earlier wave pod (untouched nodes
    only decayed elsewhere, so the argmax — lowest-index tie-break included —
    cannot move), or
  * in-wave quota usage along its ancestor chain flips its admission (checked
    EXACTLY via an in-wave exclusive prefix-sum of ancestor-chain additions,
    not conservatively by chain overlap — sharing the tree root costs
    nothing while headroom lasts).

So each device step evaluates a WINDOW of W pods in parallel against frozen
state (vmapping the IDENTICAL per-pod evaluator the serial kernel uses —
parity is by construction), finds the first conflict, commits the clean
prefix in one batch of matmul/scatter updates, and advances. Conflict-free
prefixes average ~sqrt(N) pods, so the 10k x 5k trace collapses from 10k
serial iterations into ~100 wave iterations of MXU/VPU-friendly [W, N] work.

Same contract and bindings as build_full_chain_step, validated by
tests/test_wave_chain.py across the parity configs (CPU). State rollups run
at Precision.HIGHEST; node-side rollups are EXACT (committed pods occupy
distinct nodes, so each matmul row has a single non-zero term), and the
quota commit reuses the same cumsum the admission pass saw, so the wave is
internally consistent. The one theoretical divergence from the serial kernel
is f32 summation order for a quota group whose packed usage exceeds 2^24
while sitting within one ULP of its runtime — the full-batch binding diff
against the serial step (run on-chip when the selector adopts this kernel)
is the empirical gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from koordinator_tpu.models.full_chain import (
    FullChainInputs,
    make_pod_evaluator,
    resolve_balance_idx,
    resolve_weight_idx,
)
from koordinator_tpu.ops.gang import gang_permit_mask
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.numa import numa_spread_fill
from koordinator_tpu.ops.quota import quota_admit_row

DEFAULT_WAVE = 256


def build_wave_full_chain_step(args: LoadAwareArgs, num_gangs: int,
                               num_groups: int, jit: bool = True,
                               active_axes=None, wave: int = DEFAULT_WAVE):
    """FullChainInputs -> (chosen[P], requested[N, R], quota_used[G, R])."""
    weight_idx = resolve_weight_idx(args, active_axes)
    bal_idx = resolve_balance_idx(active_axes)
    prod_mode = args.score_according_prod_usage

    def full_chain_wave(fc: FullChainInputs):
        inputs = fc.base
        P, R = inputs.fit_requests.shape
        N = inputs.allocatable.shape[0]
        G, D = fc.quota_ancestors.shape
        W = min(wave, P)
        evaluate = make_pod_evaluator(fc, weight_idx, prod_mode, bal_idx)

        # [G, G] ancestor membership: anc_mask[g, a] == a is on g's chain
        anc_valid = fc.quota_ancestors >= 0                      # [G, D]
        anc_onehot_gd = jax.nn.one_hot(
            jnp.maximum(fc.quota_ancestors, 0), G, dtype=jnp.float32
        ) * anc_valid[..., None].astype(jnp.float32)             # [G, D, G]
        anc_mask = anc_onehot_gd.sum(axis=1)                     # [G, G] 0/1

        warange = jnp.arange(W, dtype=jnp.int32)

        def cond(state):
            return state[-1] < P

        T = fc.aff_dom.shape[1]

        def wave_body(state):
            (requested, delta_np, delta_pr, numa_free, bind_free,
             quota_used, aff_count, anti_cover, aff_exists, port_used,
             vol_free, chosen, pos) = state
            idx = pos + warange
            valid_w = idx < P
            idxc = jnp.minimum(idx, P - 1)

            (found_w, best_w, zone_w, admit_w, score_w, bal_w,
             maxv_w) = jax.vmap(
                lambda i: evaluate(i, requested, delta_np, delta_pr,
                                   numa_free, bind_free, quota_used,
                                   aff_count, anti_cover, aff_exists,
                                   port_used, vol_free)
            )(idxc)
            found_w = found_w & valid_w

            req_w = fc.requests[idxc]                 # [W, R]
            req_fit_w = inputs.fit_requests[idxc]     # [W, R]
            est_w = inputs.estimated[idxc]            # [W, R]
            qid_w = fc.quota_id[idxc]                 # [W]
            has_quota_w = qid_w >= 0

            # ---- exact in-wave quota re-admission: usage each pod would see
            # serially = wave-start usage + additions of all found pods before
            # it (exclusive prefix over the window)
            pod_anc_w = anc_mask[jnp.maximum(qid_w, 0)] * (
                (found_w & has_quota_w).astype(jnp.float32)[:, None]
            )                                          # [W, G]
            adds = pod_anc_w[:, :, None] * req_w[:, None, :]       # [W, G, R]
            incl = jnp.cumsum(adds, axis=0)                        # inclusive
            prefix = incl - adds                                   # exclusive
            admit_prefix_w = jax.vmap(
                lambda req, qid, pre: quota_admit_row(
                    req, qid, fc.quota_ancestors, quota_used + pre,
                    fc.quota_runtime,
                )
            )(req_w, qid_w, prefix)
            quota_flip_w = found_w & admit_w & ~admit_prefix_w

            # ---- node collision: an earlier wave pod already took this argmax
            sel_w = jax.nn.one_hot(best_w, N, dtype=jnp.float32) * (
                found_w.astype(jnp.float32)[:, None]
            )                                          # [W, N]
            taken_before = jnp.cumsum(sel_w, axis=0) - sel_w       # exclusive
            node_coll_w = found_w & (
                jnp.take_along_axis(
                    taken_before, best_w[:, None], axis=1
                )[:, 0] > 0.5
            )

            # ---- affinity conflict: an earlier in-wave pod MATCHING a term
            # this pod REQUIRES changes the term's counts, so the frozen
            # evaluation may diverge from serial. Anti terms only decay
            # (found -> infeasible), so only found pods conflict; required
            # affinity can FLIP INFEASIBLE -> FEASIBLE (non-monotone), so
            # any pod carrying the term conflicts once a match committed.
            if T:
                match_w = (fc.pod_aff_match[idxc]
                           & found_w[:, None])                     # [W, T]
                matched_before = (jnp.cumsum(
                    match_w.astype(jnp.float32), axis=0) - match_w) > 0.5
                anti_conf = found_w & jnp.any(
                    fc.pod_anti_req[idxc] & matched_before, axis=1)
                # required affinity, topology spread, AND weighted
                # preferences are all count-sensitive (a committed match
                # changes feasibility or the score), so any referenced
                # term with an earlier in-wave match conflicts
                aff_conf = jnp.any(
                    (fc.pod_aff_req[idxc]
                     | (fc.pod_spread_skew[idxc] > 0)
                     | fc.pod_ppref_mask[idxc]) & matched_before,
                    axis=1) & valid_w
                # symmetric anti-affinity: an earlier committed CARRIER of
                # anti term t raises anti_cover, so a later pod MATCHING t
                # may lose nodes the frozen evaluation still offered
                carried_w = (fc.pod_anti_req[idxc]
                             & found_w[:, None])                   # [W, T]
                carried_before = (jnp.cumsum(
                    carried_w.astype(jnp.float32), axis=0) - carried_w) > 0.5
                sym_conf = found_w & jnp.any(
                    fc.pod_aff_match[idxc] & carried_before, axis=1)
                affinity_conf_w = anti_conf | aff_conf | sym_conf
            else:
                affinity_conf_w = jnp.zeros_like(found_w)

            # ---- balanced-allocation conflict: the one NON-monotone score
            # term — committing pod e can make node n_e MORE balanced and so
            # RAISE its score for a later pod w, moving w's serial argmax to
            # a node the frozen evaluation under-scored. Sound pairwise
            # bound: every other term only decays, so w's post-commit score
            # on n_e is at most frozen_score_w(n_e) - frozen_bal_w(n_e) +
            # exact_post_bal_w(n_e) (node collisions guarantee at most ONE
            # in-wave commit per node, so the post state of n_e is frozen +
            # fit_e). Conflict when that bound could reach w's frozen best.
            if bal_idx[0] >= 0:
                ci, mi = bal_idx
                alloc = inputs.allocatable
                cap_c = alloc[best_w, ci]                          # [W] (e)
                cap_m = alloc[best_w, mi]
                base_c = requested[best_w, ci] + req_fit_w[:, ci]  # n_e + e
                base_m = requested[best_w, mi] + req_fit_w[:, mi]

                def _pair_frac(base_e, cap_e, waxis):
                    # reciprocal-multiply form, identical to the evaluator's
                    # _frac so the post-commit bal value is exact
                    from koordinator_tpu.ops.pallas_common import (
                        safe_reciprocal,
                    )

                    inv = safe_reciprocal(cap_e)                       # [W]
                    f = (base_e[None, :] + waxis[:, None]) * inv[None, :]
                    return jnp.minimum(f, 1.0)

                fpc = _pair_frac(base_c, cap_c, req_fit_w[:, ci])  # [W, W]
                fpm = _pair_frac(base_m, cap_m, req_fit_w[:, mi])
                bal_pair = jnp.floor(
                    (1.0 - jnp.abs(fpc - fpm) * 0.5) * 100.0)      # w x e
                score_at_ne = score_w[:, best_w]                   # [W, W]
                bal_at_ne = bal_w[:, best_w]
                bound = score_at_ne - bal_at_ne + bal_pair
                tri_e_before_w = (warange[None, :] < warange[:, None])
                # found_w gate: the bal term moves scores, never
                # feasibility, so a not-found pod stays not-found
                # post-commit and must not cut the wave
                bal_conf_w = found_w & jnp.any(
                    tri_e_before_w & found_w[None, :]
                    & (bound >= maxv_w[:, None]), axis=1)
            else:
                bal_conf_w = jnp.zeros_like(found_w)

            conflict_w = (quota_flip_w | node_coll_w | affinity_conf_w
                          | bal_conf_w)
            cut = jnp.where(
                conflict_w.any(), jnp.argmax(conflict_w), W
            ).astype(jnp.int32)

            commit_w = (warange < cut) & found_w
            cm = commit_w.astype(jnp.float32)
            sel_c = sel_w * cm[:, None]                            # [W, N]

            # HIGHEST precision keeps these f32 (TPU matmuls default to bf16
            # passes); each output row has at most ONE non-zero term — the
            # node-collision cut guarantees distinct nodes per wave — so the
            # rollup equals the serial kernel's add exactly
            hi = jax.lax.Precision.HIGHEST
            mm = lambda a, b: jnp.matmul(a, b, precision=hi)  # noqa: E731
            requested = requested + mm(sel_c.T, req_fit_w)
            delta_np = delta_np + mm(sel_c.T, est_w)
            if prod_mode:
                delta_pr = delta_pr + mm(
                    sel_c.T,
                    inputs.is_prod[idxc].astype(jnp.float32)[:, None] * est_w,
                )
            bind_free = bind_free - mm(
                sel_c.T,
                jnp.where(fc.needs_bind[idxc], fc.cores_needed[idxc], 0.0),
            )
            # NodePorts/volumes: same-node conflicts are impossible within a
            # wave (the node-collision cut commits distinct nodes), so the
            # frozen evaluation is exact and the rollup scatters cleanly
            if fc.port_used.shape[1]:
                port_used = jnp.maximum(
                    port_used,
                    mm(sel_c.T,
                       fc.pod_port_wants[idxc].astype(jnp.float32)))
            # per-pod NEW attachments at the chosen node (volume-group
            # gather — the already-attached exemption), one nonzero per
            # output row as above so the rollup equals the serial add
            vn_at_best = jnp.take_along_axis(
                fc.vol_needed[idxc],
                fc.node_vol_group[best_w][:, None], axis=1)[:, 0]  # [W]
            vol_free = vol_free - mm(sel_c.T, vn_at_best)
            # committed pods occupy DISTINCT nodes (node_coll cut), so the
            # per-pod NUMA fills scatter without aliasing
            new_rows_w = jax.vmap(numa_spread_fill)(
                numa_free[best_w], req_w, zone_w
            )                                          # [W, K, R]
            numa_idx = jnp.where(
                commit_w & fc.needs_numa[idxc], best_w, N
            )
            numa_free = numa_free.at[numa_idx].set(
                new_rows_w, mode="drop"
            )
            # quota commit from the SAME inclusive cumsum the admission pass
            # consumed: the committed total is incl[cut-1] (zero when the cut
            # lands on the first pod), so admission and commit can never see
            # differently-associated sums
            committed_total = jnp.where(
                cut > 0, incl[jnp.maximum(cut - 1, 0)], jnp.zeros_like(incl[0])
            )
            quota_used = quota_used + committed_total

            # affinity commit: every committed pod raises its matched terms'
            # counts over the chosen node's whole domain (exact: 0/1
            # indicator matmul at HIGHEST precision on small integers)
            for t in range(T):
                dom_col = fc.aff_dom[:, t]                         # [N]
                chosen_dom_w = dom_col[best_w]                     # [W]
                inc_w = (cm * fc.pod_aff_match[idxc, t]
                         * (chosen_dom_w >= 0))                    # [W]
                eq = (dom_col[None, :] == chosen_dom_w[:, None]
                      ).astype(jnp.float32)                        # [W, N]
                aff_count = aff_count.at[:, t].add(mm(inc_w[None, :], eq)[0])
                # committed CARRIERS raise anti_cover over their domain
                inc_cov_w = (cm * fc.pod_anti_req[idxc, t]
                             * (chosen_dom_w >= 0))                # [W]
                anti_cover = anti_cover.at[:, t].add(
                    mm(inc_cov_w[None, :], eq)[0])
                aff_exists = aff_exists.at[t].set(
                    aff_exists[t]
                    | jnp.any(commit_w & fc.pod_aff_match[idxc, t]))

            value_w = jnp.where(found_w, best_w.astype(jnp.int32), -1)
            chosen_idx = jnp.where((warange < cut) & valid_w, idx, P)
            chosen = chosen.at[chosen_idx].set(value_w, mode="drop")
            return (requested, delta_np, delta_pr, numa_free, bind_free,
                    quota_used, aff_count, anti_cover, aff_exists, port_used,
                    vol_free, chosen, pos + cut)

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
            jnp.full(P, -1, jnp.int32),
            jnp.int32(0),
        )
        (requested, _, _, _, _, quota_used, _, _, _, _, _, chosen,
         _pos) = jax.lax.while_loop(cond, wave_body, init)

        # ---- Permit barrier (gang group all-or-nothing)
        keep = gang_permit_mask(
            chosen, fc.gang_id, fc.gang_min_member, fc.gang_assumed,
            fc.gang_group_id, num_gangs, num_groups,
        )
        chosen = jnp.where(keep, chosen, -1)
        return chosen, requested, quota_used

    return jax.jit(full_chain_wave) if jit else full_chain_wave
