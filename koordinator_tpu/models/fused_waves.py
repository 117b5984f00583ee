"""Fused multi-wave scheduling: K dependent rounds in ONE device dispatch.

Every device dispatch pays a fixed dispatch + result-readback cost on top
of its kernel; a scheduling round that dispatches alone pays it once per
round. Chaining K rounds inside one jit pays it once per K rounds. How
large that fixed cost is on a locally attached chip is not measured yet
(chip_smoke.py prints the per-sync figure); this module gives the
production cycle the capability either way.

One fused dispatch runs up to K WAVES, where wave w is exactly the
scheduling round serial cycle w would run:

  wave body =
    0. carried-transition pre-passes — the host work serial cycle w runs
       BEFORE its kernel, expressed as carried state: the reservation
       reconcile's consumed-allocate-once transition (requested loses the
       reservation's held capacity, its consumer falls back to direct
       accounting) and the nomination pre-pass (owner pods bind onto
       reservations that became Available in an EARLIER wave of this
       dispatch, consuming carried remainders — so a migration-created
       Reservation is consumable by wave 2 of the same dispatch);
    1. evaluation pass — the serial full-chain round over the
       still-pending pods, producing tentative bindings with in-round
       state feedback: the Pallas full-chain kernel where the single-
       round selector's rule admits the batch (``wave_search_backend``),
       else the same ``make_pod_evaluator`` + ``commit_pod_state`` the
       single-round XLA step traces (models/full_chain.py);
    2. gang Permit against the CARRIED assumed counters;
    3. kept-only replay pass — the next wave's state is rebuilt from the
       wave-start state by committing ONLY the pods that survived Permit,
       in bind order. This mirrors what the host does between serial
       cycles: reverted gang members never reach the store, so their
       in-round reservations must not leak into the next round's state
       (and NUMA zone choices are re-picked under the kept-only state,
       the same way the host plugin allocates at Reserve). Reservation
       pseudo-pod rows commit their CARRY form here: the allocatable
       vector the restore transformer would add (no pod-count slot, no
       LoadAware estimate, no NUMA/affinity footprint) — the bound CR
       holds capacity, it is not a pod.

Carried device state (``WAVE_STATE_FIELDS``): node requested/NUMA-free/
bindable-cpu/port/volume state, quota used along the ancestor chains,
gang assumed counters, the pod assigned-mask, the LoadAware assigned-
estimate sums (non-prod ``est_sum`` AND, under scoreAccordingProdUsage,
the prod split ``est_sum_prod``), the hot-claim attachment matrix +
non-hot attachment counter (ops/volumes.py), and the reservation rows'
availability/remainder/node state. The LoadAware terms are recomputed
per wave as ``est_sum + adjusted`` — the SAME two-operand association a
next-cycle host rebuild produces (ops/loadaware.py exports both splits),
so carried state is bit-identical to what serial cycle w's snapshot
would contain. A pod rejected in wave i because a node filled up (or a
gang's quota was transiently held) retries in wave i+1 on-device, with
no host round-trip. Feature-absent slots carry ``None`` (a leafless
pytree), so a batch without claims/reservations/prod scoring traces the
exact historical program.

The ONE wave body (``_make_wave_body``) backs two dispatch shapes:

  * ``build_fused_wave_step`` — all K waves under ``lax.while_loop`` in
    one program, compacted (pod_idx, node_idx, zone, res_idx) readback at
    the end. Early exit: a wave that commits nothing (and has no pending
    carried transition) proves the fixpoint. This is the
    ``KOORD_TPU_REPLAY_OVERLAP=0`` path: the host replay of every wave
    runs serially after the single readback.
  * ``build_chained_wave_step`` — ONE wave per dispatch with the carried
    state staying on device between dispatches. The cycle driver
    (scheduler/cycle.py) dispatches wave w+1 asynchronously BEFORE
    syncing wave w's rows, so the host-side replay of wave w overlaps
    device execution of wave w+1 — the replay queue architecture. The
    step is K-independent, so every wave depth shares one compiled
    program. Tracing the SAME wave body keeps the chain bit-identical
    to the fused while_loop (pipeline_parity.run_replay_overlap_parity
    gates it).

Readback is COMPACTED: a (pod_idx, node_idx, zone, res_idx) binding
buffer plus per-wave bound counts — not K full assignment vectors and
none of the score/state matrices. ``res_idx >= 0`` marks a nomination
(the driver replays it as a via-reservation bind — Reserve hooks +
consume — FIRST in the logical cycle, the pre-pass position). The driver
replays the waves host-side as logical cycles; pipeline_parity gates
that a fused-K cycle is byte-identical to K sequential single-round
cycles.

Registered ``ScoreTransformer``s that implement the device-expressible
protocol (``device_pass``, scheduler/frameworkext.py) run as tensor
passes over the rebuilt per-wave inputs — the same rewrite their host
``before_score`` applies to the packed batch each serial cycle.

Remaining demotions (the driver falls back to K=1, the exact serial
path): the degradation ladder's serial rung, the gRPC sidecar (the
remote protocol is single-round), ScoreTransformers WITHOUT a device
pass, and ``claim-entangled`` batches (unbound WaitForFirstConsumer
claims on several pods, or claim-factorization budget overflows — see
ops/volumes.py). The four data-driven reasons this module used to force
— pending-reservations, claim-pods, prod-usage-score, score-transformer
— are retired (PR 14) and pinned retired by the demotion registry.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from koordinator_tpu.models.full_chain import (
    EXPLAIN_TERMS,
    NUM_EXPLAIN_STAGES,
    ExplainOut,
    FullChainInputs,
    commit_pod_state,
    explain_stage_counts,
    make_pod_evaluator,
    pallas_full_chain_fits,
    resolve_balance_idx,
    resolve_weight_idx,
)
from koordinator_tpu.ops.gang import gang_permit_mask
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.numa import numa_zone_for_node
from koordinator_tpu.ops.volumes import (
    advance_claim_state,
    effective_vol_needed,
)

MAX_WAVES = 8  # bounds the compile-cache key space; auto-K never exceeds it

# carried wave state (the chain step's explicit carry): index layout of
# the leading slots of the while_loop carry — scheduler/cycle.py builds
# the initial tuple via initial_wave_carry and threads the chain's output
# carry back in unchanged. Slots whose feature is off for the dispatch
# (no prod scoring / no hot claims / no pending reservation CRs) carry
# None — a leafless pytree, so the compiled program is the featureless
# trace exactly.
WAVE_STATE_FIELDS = (
    "assigned", "requested", "est_sum", "numa_free", "bind_free",
    "quota_used", "aff_count", "anti_cover", "aff_exists", "port_used",
    "vol_free", "gang_assumed",
    # PR 14 (demotion burn-down) carried extensions:
    "est_sum_prod",   # [N, R] prod assigned-estimate sum (prod mode only)
    "claim_new",      # [N, NC] hot claims newly attached per node
    "vol_new",        # [N] non-hot new attachments per node
    "res_avail",      # [NRES] reservation row became Available in-dispatch
    "res_remain",     # [NRES, R] packed allocatable remainder
    "res_node",       # [NRES] int32 node the row bound to (-1 pending)
    "res_succ",       # [NRES] int32 consumer pod row whose allocate-once
                      # consumption must apply the Succeeded transition at
                      # the NEXT wave boundary (-1 none)
)
NUM_WAVE_STATE = len(WAVE_STATE_FIELDS)
# wave-state slots indexed [N, ...] (node axis): sharded over the mesh in
# the sharded chain step; everything else (pod/quota/gang/reservation/term
# axes) replicated. est_sum (slot 2) is the node-axis LoadAware estimate
# sum; 12..14 are the PR 14 node-axis extensions.
WAVE_STATE_NODE_SLOTS = frozenset({1, 2, 3, 4, 6, 7, 9, 10, 12, 13, 14})

# out-block offsets relative to the carry start
_OUT_PODS = NUM_WAVE_STATE
_OUT_NODES = NUM_WAVE_STATE + 1
_OUT_ZONES = NUM_WAVE_STATE + 2
_OUT_RES = NUM_WAVE_STATE + 3
_N_OUT = NUM_WAVE_STATE + 4
_WAVE_COUNTS = NUM_WAVE_STATE + 5
_EX_COUNTS = NUM_WAVE_STATE + 6
_EX_TERMS = NUM_WAVE_STATE + 7

# nomination rank sentinel (plain int: no device array at import time)
_RANK_INF = 2**31 - 1


class ProdSides(NamedTuple):
    """scoreAccordingProdUsage term split (ops/loadaware.py exports)."""

    est: Any   # [N, R] la_est_prod — prod assigned-estimate sum at start
    adj: Any   # [N, R] la_adj_prod — non-estimated prod usage, static


class ClaimSides(NamedTuple):
    """Hot-claim factorization (ops/volumes.build_claim_pack)."""

    pod_claim: Any   # [P, NC] f32 0/1 — pod references hot claim c
    pod_nonhot: Any  # [P] f32 — the pod's non-hot distinct-claim count
    covered0: Any    # [N, NC] f32 0/1 — attached on node at dispatch start


class ResSides(NamedTuple):
    """Pending-reservation rows riding the batch (one per Reservation CR
    pseudo-pod; scheduler/cycle.py builds these in packed order)."""

    owner_match: Any  # [P, NRES] bool — res.matches(pod), host precompute
    rank: Any         # [NRES] int32 nomination preference (creation order)
    alloc: Any        # [NRES, R] f32 packed template requests (the
                      # restore-transformer add vector; no pod-count slot)
    once: Any         # [NRES] f32 0/1 allocate_once
    row_of: Any       # [NRES] int32 pseudo-pod row of each reservation
    pod_slot: Any     # [P] int32 reservation slot of a pseudo-pod row (-1)
    nominate_ok: Any  # [P] bool — host pre-pass eligibility class


class WaveSideInputs(NamedTuple):
    """Per-dispatch side operands of the fused/chained wave steps.

    ``prod``/``claims``/``res`` are None when the feature is absent from
    the batch — the pytree then has no leaves there and the compiled
    program is the featureless trace."""

    la_est: Any                     # [N, R] la_est_nonprod
    la_adj: Any                     # [N, R] la_adj_nonprod
    prod: Optional[ProdSides] = None
    claims: Optional[ClaimSides] = None
    res: Optional[ResSides] = None


class FusedWaveOut(NamedTuple):
    """Compacted readback of one fused dispatch."""

    bind_pods: jnp.ndarray    # [P] int32 pod row indices in bind order, -1 pad
    bind_nodes: jnp.ndarray   # [P] int32 node index per binding
    bind_zones: jnp.ndarray   # [P] int32 replay-state NUMA zone (-1 = spread)
    bind_res: jnp.ndarray     # [P] int32 reservation slot consumed via
    #     in-kernel nomination (-1 = plain kernel bind)
    wave_counts: jnp.ndarray  # [K] int32 bindings committed per wave
    waves_run: jnp.ndarray    # scalar int32 wave bodies actually executed


class WaveChainOut(NamedTuple):
    """Compacted readback of ONE chained wave dispatch."""

    bind_pods: jnp.ndarray   # [P] int32 this wave's pod rows in bind order
    bind_nodes: jnp.ndarray  # [P] int32 node index per binding
    bind_zones: jnp.ndarray  # [P] int32 replay-state NUMA zone (-1 = spread)
    bind_res: jnp.ndarray    # [P] int32 nomination reservation slot (-1)
    count: jnp.ndarray       # scalar int32 bindings this wave (0 = fixpoint)


def plain_sides(la_est, la_adj) -> WaveSideInputs:
    """The featureless side tuple (tests, benches): nonprod split only."""
    return WaveSideInputs(la_est=la_est, la_adj=la_adj)


def wave_search_backend(fc: FullChainInputs, vmem_budget_bytes,
                        volumes: bool, explain=None,
                        claims: bool = False) -> str:
    """The kernel of the wave body's search pass (pass 1): "pallas" where
    the single-round selector's rule (pallas_full_chain_fits) admits the
    batch, else "xla" (the ``fori_loop`` evaluator). A hot-claims batch
    keeps the XLA pass: each wave rewrites its vol_needed to a [P, N]
    view (effective_vol_needed), a volume row per node that the kernel's
    SMEM cannot hold at a deployed size."""
    if not claims and pallas_full_chain_fits(fc, vmem_budget_bytes,
                                             volumes, explain):
        return "pallas"
    return "xla"


def _pallas_search_pass(args: LoadAwareArgs, active_axes,
                        fc: FullChainInputs, vmem_budget_bytes,
                        volumes: bool, explain, claims: bool,
                        interpret: bool):
    """Pass 1 on the Pallas kernel, ``fc -> chosen[P]`` before Permit, or
    None where ``wave_search_backend`` keeps the XLA pass."""
    if wave_search_backend(fc, vmem_budget_bytes, volumes, explain,
                           claims) != "pallas":
        return None
    from koordinator_tpu.ops.pallas_full_chain import (
        pallas_full_chain_search,
    )

    def search(fc_start: FullChainInputs):
        return pallas_full_chain_search(
            fc_start, args, active_axes=active_axes,
            enable_volumes=volumes, interpret=interpret)[0]

    return search


def _check_wave_args(args: LoadAwareArgs, sides_prod: bool) -> None:
    if args.score_according_prod_usage != sides_prod:
        # the carry's est_sum_prod slot presence must equal prod_mode or
        # the while_loop carry structure would flip between iterations
        raise ValueError(
            "WaveSideInputs.prod must be supplied exactly when "
            "score_according_prod_usage is on (the prod term split "
            "la_est_prod/la_adj_prod rides the carry)")


def _carry_fc_variants(fc: FullChainInputs, sides: WaveSideInputs):
    """The per-row-kind input variants of the kept-only replay and the
    nomination pre-pass (static per dispatch, hoisted out of the loop).

    ``fc_carry``: reservation pseudo-pod rows commit their CARRY form —
    the packed allocatable vector (what the restore transformer adds at
    the next serial rebuild: no pod-count slot), no LoadAware estimate,
    no NUMA fill, no affinity footprint — a bound CR holds capacity but
    is not a pod. ``fc_nom``: nominated pods commit everything EXCEPT the
    node's requested row — a consumer's usage lives inside the
    reservation's already-counted footprint (the restore transformer's
    double-count subtraction, expressed as never-adding)."""
    inputs = fc.base
    if sides.res is None:
        fc_carry = fc
    else:
        slot = sides.res.pod_slot
        is_res = slot >= 0
        alloc_rows = sides.res.alloc[jnp.maximum(slot, 0)]
        fc_carry = fc._replace(
            base=inputs._replace(
                fit_requests=jnp.where(is_res[:, None], alloc_rows,
                                       inputs.fit_requests),
                estimated=jnp.where(is_res[:, None], 0.0,
                                    inputs.estimated),
            ),
            needs_numa=fc.needs_numa & ~is_res,
            pod_aff_match=fc.pod_aff_match & ~is_res[:, None],
            pod_anti_req=fc.pod_anti_req & ~is_res[:, None],
        )
    fc_nom = fc._replace(
        base=inputs._replace(fit_requests=jnp.zeros_like(inputs.fit_requests)))
    return fc_carry, fc_nom


def _make_wave_body(fc: FullChainInputs, sides: WaveSideInputs, n_real,
                    weight_idx, bal_idx, num_gangs: int, num_groups: int,
                    explain, prod_mode: bool, score_passes=(),
                    search=None):
    """The ONE wave body both dispatch shapes trace.

    ``carry`` layout: WAVE_STATE_FIELDS (NUM_WAVE_STATE slots, None where
    the feature is off), then out_pods / out_nodes / out_zones / out_res /
    n_out / wave_counts, then [ex_counts] [ex_terms] under koordexplain,
    then (w, done). Returns the same layout with w+1 and the fixpoint
    flag. Extracted verbatim from the original while_loop body so the
    fused step and the chained step cannot drift — byte parity between
    them is by construction of the trace, and pipeline_parity gates it
    empirically.

    ``search`` (``_pallas_search_pass``) runs pass 1 on the Pallas
    kernel; None runs it on the ``fori_loop`` evaluator.
    """
    inputs = fc.base
    P, R = inputs.fit_requests.shape
    N = inputs.allocatable.shape[0]
    explain_full = explain == "full"
    has_claims = sides.claims is not None
    has_res = sides.res is not None
    fc_carry, fc_nom = _carry_fc_variants(fc, sides)

    def wave_body(carry):
        (assigned, requested, est_sum, numa_free, bind_free, quota_used,
         aff_count, anti_cover, aff_exists, port_used, vol_free,
         gang_assumed, est_sum_prod, claim_new, vol_new, res_avail,
         res_remain, res_node, res_succ
         ) = carry[:NUM_WAVE_STATE]
        (out_pods, out_nodes, out_zones, out_res, n_out,
         wave_counts) = carry[_OUT_PODS:_WAVE_COUNTS + 1]
        w, done = carry[-2], carry[-1]
        if explain is not None:
            ex_counts = carry[_EX_COUNTS]
            ex_terms = carry[_EX_TERMS] if explain_full else None

        nom_count = jnp.int32(0)
        if has_res:
            # ---- pass 0a: the reservation reconcile's Succeeded
            # transition, one wave after an allocate-once consumption
            # (serial cycle w runs reconcile BEFORE its pre-pass): the
            # reservation stops being counted, so its held capacity
            # leaves the node and its consumer falls back to direct
            # accounting — (requested - alloc) + consumer_fit, the exact
            # event order the host restore recompute produces. All
            # integer-valued packed units: exact regardless of grouping.
            nres = res_succ.shape[0]

            def succ_body(r, req_state):
                p = res_succ[r]
                apply = (p >= 0).astype(jnp.float32)
                noden = jnp.maximum(res_node[r], 0)
                delta = (inputs.fit_requests[jnp.maximum(p, 0)]
                         - sides.res.alloc[r])
                new_row = req_state[noden] + apply * delta
                return jax.lax.dynamic_update_slice(
                    req_state, new_row[None], (noden, 0))

            requested = jax.lax.fori_loop(0, nres, succ_body, requested)
            res_succ = jnp.full_like(res_succ, -1)

            # ---- pass 0b: the nomination pre-pass over carried
            # reservation state — owner pods bind onto rows that became
            # Available in an EARLIER wave of this dispatch (rows
            # pre-dating the dispatch were already host-nominated before
            # the kernel pass). Walks pods in packed (queue) order, picks
            # the earliest-created fitting candidate (the host
            # nominator's sort), and commits everything EXCEPT the
            # node's requested row (fc_nom): the consumer lives inside
            # the reservation's counted footprint.
            est_pr_state = (est_sum_prod if prod_mode
                            else jnp.zeros_like(est_sum))

            def nom_body(i, st):
                (chain, res_avail_, res_remain_, res_succ_,
                 out_p, out_n, out_z, out_r, cnt, assigned_, ncnt) = st
                req = fc.requests[i]
                elig = (sides.res.nominate_ok[i] & ~assigned_[i]
                        & inputs.pod_valid[i])
                fits = jnp.all(
                    (req[None, :] <= 0) | (req[None, :] <= res_remain_),
                    axis=1)
                cand = (res_avail_ > 0.5) & sides.res.owner_match[i] & fits
                r = jnp.argmin(jnp.where(cand, sides.res.rank, _RANK_INF))
                found = elig & jnp.any(cand)
                noden = jnp.maximum(res_node[r], 0)
                zone = numa_zone_for_node(
                    req, fc.needs_numa[i], chain[3][noden],
                    fc.numa_policy[noden])
                chain = commit_pod_state(fc_nom, prod_mode, chain, i,
                                         found, noden, zone)
                fnd = found.astype(jnp.float32)
                res_remain_ = res_remain_.at[r].add(-fnd * req)
                # allocate-once: consumed rows leave the candidate set
                # (the nominator's allocate_once && current_owners skip)
                # and arm next wave's Succeeded transition
                once_hit = found & (sides.res.once[r] > 0.5)
                res_avail_ = res_avail_.at[r].add(
                    -once_hit.astype(jnp.float32) * res_avail_[r])
                res_succ_ = res_succ_.at[r].set(
                    jnp.where(once_hit, i, res_succ_[r]))
                slot = jnp.where(found, cnt, P)
                out_p = out_p.at[slot].set(i, mode="drop")
                out_n = out_n.at[slot].set(res_node[r], mode="drop")
                out_z = out_z.at[slot].set(zone, mode="drop")
                out_r = out_r.at[slot].set(r, mode="drop")
                assigned_ = assigned_.at[i].set(assigned_[i] | found)
                return (chain, res_avail_, res_remain_, res_succ_,
                        out_p, out_n, out_z, out_r,
                        cnt + found.astype(jnp.int32), assigned_,
                        ncnt + found.astype(jnp.int32))

            nom_init = (
                (requested, est_sum, est_pr_state, numa_free, bind_free,
                 quota_used, aff_count, anti_cover, aff_exists, port_used,
                 vol_free),
                res_avail, res_remain, res_succ,
                out_pods, out_nodes, out_zones, out_res, n_out, assigned,
                nom_count,
            )
            nom_out = jax.lax.fori_loop(0, P, nom_body, nom_init)
            (chain0, res_avail, res_remain, res_succ,
             out_pods, out_nodes, out_zones, out_res, n_out, assigned,
             nom_count) = nom_out
            (requested, est_sum, est_pr_state, numa_free, bind_free,
             quota_used, aff_count, anti_cover, aff_exists, port_used,
             vol_free) = chain0
            if prod_mode:
                est_sum_prod = est_pr_state

        # the round's LoadAware base term, rebuilt-association exact:
        # est_sum folds committed estimates in bind order onto the
        # host's initial sum, then ONE add of the adjusted usage
        term = est_sum + sides.la_adj
        active = inputs.pod_valid & ~assigned
        base_w = inputs._replace(la_term_nonprod=term, pod_valid=active)
        if prod_mode:
            base_w = base_w._replace(
                la_term_prod=est_sum_prod + sides.prod.adj)
        fc_w = fc._replace(base=base_w)
        if has_claims:
            # the per-(pod, node) volume view at wave-start claim state:
            # what the next serial cycle's regrouped [P, VG'] gather
            # would produce (ops/volumes.py)
            fc_w = fc_w._replace(
                vol_needed=effective_vol_needed(
                    fc.vol_needed, fc.node_vol_group,
                    sides.claims.pod_claim, claim_new),
                node_vol_group=jnp.arange(N, dtype=jnp.int32))
        for tf in score_passes:
            # device-expressible ScoreTransformers (frameworkext.py): the
            # same rewrite their host before_score applies to the packed
            # batch, re-applied to each wave's rebuilt inputs
            fc_w = tf(fc_w)
        evaluate = make_pod_evaluator(fc_w, weight_idx, prod_mode,
                                      bal_idx,
                                      explain_terms=explain_full)

        if explain is not None:
            # per-wave attribution at wave-START state (post pre-pass,
            # exactly the state serial cycle w's packed batch holds): the
            # counts the driver's logical cycle w formats for pods it
            # leaves unbound (diagnose.py reads wave-start state, see
            # _WaveStateMirror)
            filter_state = (requested, numa_free, bind_free, quota_used,
                            aff_count, anti_cover, aff_exists,
                            port_used, vol_free)
            counts_w = explain_stage_counts(fc_w, evaluate, filter_state,
                                            n_real)
            ex_counts = jax.lax.dynamic_update_slice(
                ex_counts, counts_w[None], (w, 0, 0))

        if search is not None:
            # ---- pass 1 on the Pallas full-chain kernel: the same
            # serial round (bit-identical bindings, tests/
            # test_wave_pallas.py) over the wave-start carried state
            chosen = search(fc_w._replace(
                base=fc_w.base._replace(requested=requested),
                numa_free=numa_free, bind_free=bind_free,
                quota_used=quota_used, aff_count=aff_count,
                anti_cover=anti_cover, aff_exists=aff_exists,
                port_used=port_used, vol_free=vol_free))
            wave_terms = None
        else:
            # ---- pass 1: the serial round (identical tracing to
            # build_full_chain_step's body — decisions are by construction
            # what serial cycle w's kernel would decide)
            def body(i, state):
                if explain_full:
                    chain_state, wterms, chosen = (state[:-2], state[-2],
                                                   state[-1])
                    (found, best, zone_at_best, _adm, score, _b, best_v,
                     la_row, numa_row, pref_row) = evaluate(i, *chain_state)
                    runner = jnp.maximum(jnp.max(jnp.where(
                        jnp.arange(N, dtype=jnp.int32) == best,
                        -jnp.inf, score)), -1.0)
                    wterms = wterms.at[i].set(jnp.stack([
                        la_row[best], numa_row[best], pref_row[best],
                        best_v, runner]))
                else:
                    chain_state, chosen = state[:-1], state[-1]
                    found, best, zone_at_best, _adm, _s, _b, _mv = evaluate(
                        i, *chain_state)
                chain_state = commit_pod_state(
                    fc_w, prod_mode, chain_state, i, found, best,
                    zone_at_best)
                chosen = chosen.at[i].set(
                    jnp.where(found, best.astype(jnp.int32), -1))
                if explain_full:
                    return chain_state + (wterms, chosen)
                return chain_state + (chosen,)

            init = (
                requested,
                jnp.zeros((N, R), jnp.float32),
                jnp.zeros((N, R), jnp.float32),
                numa_free,
                bind_free,
                quota_used,
                aff_count,
                anti_cover,
                aff_exists,
                port_used,
                vol_free,
            )
            if explain_full:
                init = init + (
                    jnp.zeros((P, len(EXPLAIN_TERMS)), jnp.float32),)
            init = init + (jnp.full(P, -1, jnp.int32),)
            pass1 = jax.lax.fori_loop(0, P, body, init)
            chosen = pass1[-1]
            wave_terms = pass1[-2] if explain_full else None

        # ---- Permit barrier against the CARRIED assumed counters
        with jax.named_scope("select"):
            keep = gang_permit_mask(
                chosen, fc.gang_id, fc.gang_min_member, gang_assumed,
                fc.gang_group_id, num_gangs, num_groups,
            )
            kept = (chosen >= 0) & keep
            kept_count = jnp.sum(kept.astype(jnp.int32))
        if explain_full:
            # the wave that finally KEEPS a pod owns its attribution
            # row (a Permit-reverted choice never persisted host-side)
            ex_terms = jnp.where(kept[:, None], wave_terms, ex_terms)

        # ---- pass 2: kept-only replay from the WAVE-START state.
        # Reverted gang reservations never persisted host-side, so the
        # next wave's base state commits only survivors, in bind
        # order; est_sum rides the delta_np slot (est_sum_prod the
        # delta_pr slot) so the fold order matches the assign-cache
        # append order, and the NUMA zone is re-picked under replay
        # state (= what the host plugin's Reserve sees). Reservation
        # pseudo-pod rows commit their carry form (fc_carry).
        est_pr_rinit = (est_sum_prod if prod_mode
                        else jnp.zeros((N, R), jnp.float32))

        def rbody(i, st):
            chain_state = st[:11]
            out_p, out_n, out_z, out_r, cnt = st[11:]
            k = kept[i]
            best = jnp.maximum(chosen[i], 0)
            zone = numa_zone_for_node(
                fc.requests[i], fc_carry.needs_numa[i],
                chain_state[3][best], fc.numa_policy[best])
            chain_state = commit_pod_state(
                fc_carry, prod_mode, chain_state, i, k, best, zone)
            slot = jnp.where(k, cnt, P)
            out_p = out_p.at[slot].set(i, mode="drop")
            out_n = out_n.at[slot].set(chosen[i], mode="drop")
            out_z = out_z.at[slot].set(zone, mode="drop")
            out_r = out_r.at[slot].set(-1, mode="drop")
            return chain_state + (out_p, out_n, out_z, out_r,
                                  cnt + k.astype(jnp.int32))

        rinit = (
            requested,
            est_sum,                       # delta_np slot: the carry
            est_pr_rinit,                  # delta_pr slot: prod carry
            numa_free,
            bind_free,
            quota_used,
            aff_count,
            anti_cover,
            aff_exists,
            port_used,
            vol_free,
            out_pods, out_nodes, out_zones, out_res, n_out,
        )
        rout = jax.lax.fori_loop(0, P, rbody, rinit)
        (requested, est_sum, est_pr_out, numa_free, bind_free, quota_used,
         aff_count, anti_cover, aff_exists, port_used, vol_free,
         out_pods, out_nodes, out_zones, out_res, n_out) = rout
        if prod_mode:
            est_sum_prod = est_pr_out

        # the vol_needed consumed by pass 1/2 above is FROZEN wave-start
        # state (serial in-cycle semantics); the boundary rebuilds the
        # claim columns + the attachable count set-wise — what the next
        # serial cycle's attached-set recompute yields (ops/volumes.py)
        if has_claims:
            claim_new, vol_new, vol_free = advance_claim_state(
                chosen, kept, sides.claims.pod_claim,
                sides.claims.pod_nonhot, sides.claims.covered0,
                claim_new, vol_new, fc.vol_free)

        if has_res:
            # a KEPT reservation pseudo-pod row turned its CR Available
            # on its chosen node: consumable by the NEXT wave's
            # nomination pre-pass (pass 0b) — the closed rebalance
            # loop's migration Reservation lands here
            rows = sides.res.row_of
            rowc = jnp.maximum(rows, 0)
            became = ((rows >= 0) & kept[rowc]).astype(jnp.float32)
            res_avail = res_avail + became
            res_node = jnp.where(became > 0.5, chosen[rowc], res_node)

        in_gang = fc.gang_id >= 0
        gang_assumed = gang_assumed + jax.ops.segment_sum(
            (kept & in_gang).astype(jnp.float32),
            jnp.maximum(fc.gang_id, 0), num_segments=num_gangs)
        assigned = assigned | kept
        bound_count = kept_count + nom_count
        wave_counts = wave_counts.at[w].set(bound_count)
        # a zero-commit wave with no pending carried transition is a
        # fixpoint: the next wave would see identical state and commit
        # nothing again
        done = bound_count == 0
        if has_res:
            done = done & ~jnp.any(res_succ >= 0)
        new_carry = (assigned, requested, est_sum, numa_free, bind_free,
                     quota_used, aff_count, anti_cover, aff_exists,
                     port_used, vol_free, gang_assumed,
                     est_sum_prod if prod_mode else None,
                     claim_new if has_claims else None,
                     vol_new if has_claims else None,
                     res_avail if has_res else None,
                     res_remain if has_res else None,
                     res_node if has_res else None,
                     res_succ if has_res else None,
                     out_pods, out_nodes, out_zones, out_res, n_out,
                     wave_counts)
        if explain is not None:
            new_carry = new_carry + (ex_counts,)
            if explain_full:
                new_carry = new_carry + (ex_terms,)
        return new_carry + (w + 1, done)

    return wave_body


def initial_wave_carry(fc: FullChainInputs, sides: WaveSideInputs,
                       explain=None):
    """The chain step's wave-0 carry (WAVE_STATE_FIELDS layout), built
    from the same (possibly device-resident/sharded) arrays the fused
    init consumes. Feature-absent slots are None. Under koordexplain
    "full" the carry also holds the per-pod score-term rows
    (kept-wave-wins across the chain)."""
    P = fc.base.fit_requests.shape[0]
    N = fc.base.allocatable.shape[0]
    has_claims = sides.claims is not None
    has_res = sides.res is not None
    if has_claims:
        nc = sides.claims.pod_claim.shape[1]
        claim_new0 = jnp.zeros((N, nc), jnp.float32)
        vol_new0 = jnp.zeros(N, jnp.float32)
    else:
        claim_new0 = vol_new0 = None
    if has_res:
        nres = sides.res.rank.shape[0]
        res_avail0 = jnp.zeros(nres, jnp.float32)
        # a Pending CR entering the batch has nothing allocated yet: the
        # full packed template is the remainder
        res_remain0 = jnp.asarray(sides.res.alloc, jnp.float32)
        res_node0 = jnp.full(nres, -1, jnp.int32)
        res_succ0 = jnp.full(nres, -1, jnp.int32)
    else:
        res_avail0 = res_remain0 = None
        res_node0 = res_succ0 = None
    carry = (
        jnp.zeros(P, bool),
        fc.base.requested,
        sides.la_est,
        fc.numa_free,
        fc.bind_free,
        fc.quota_used,
        fc.aff_count,
        fc.anti_cover,
        jnp.asarray(fc.aff_exists, bool),
        fc.port_used,
        fc.vol_free,
        fc.gang_assumed,
        sides.prod.est if sides.prod is not None else None,
        claim_new0,
        vol_new0,
        res_avail0,
        res_remain0,
        res_node0,
        res_succ0,
    )
    if explain == "full":
        carry = carry + (
            jnp.zeros((P, len(EXPLAIN_TERMS)), jnp.float32),)
    return carry


def build_fused_wave_step(args: LoadAwareArgs, num_gangs: int,
                          num_groups: int, waves: int, jit: bool = True,
                          active_axes=None, explain=None,
                          prod: bool = False, claims: bool = False,
                          res: bool = False, score_passes=(),
                          vmem_budget_bytes=None, volumes: bool = True,
                          interpret: bool = False):
    """(FullChainInputs, WaveSideInputs) -> FusedWaveOut.

    ``sides`` carries the LoadAware nonprod score-term split
    (build_loadaware_node_state's ``la_est_nonprod``/``la_adj_nonprod``),
    sliced to the same active axes as the rest of the batch, plus the
    optional prod split, hot-claim factorization and reservation rows —
    the ``prod``/``claims``/``res`` flags pin which optional blocks the
    trace expects (the driver keys its step cache on them).

    ``explain`` (None | "counts" | "full", koordexplain): the step takes an
    extra ``n_real`` int32 operand and returns (FusedWaveOut, ExplainOut)
    with per-WAVE stage counts [waves, P, NUM_EXPLAIN_STAGES], each wave's
    row computed at wave-START state — exactly the state the driver's
    legacy host mirror (_WaveStateMirror) would hand diagnose.py for that
    logical cycle. "full" additionally carries the winning node's score
    terms for each pod across waves (the wave that finally kept the pod
    wins the row). Decisions are untouched: attribution is extra carried
    outputs only.

    ``vmem_budget_bytes`` (the device kind's, None off TPU) and
    ``volumes`` (any pod mounts new volumes, resolved on the host) feed
    ``wave_search_backend``, read at trace time: where it says "pallas",
    every wave's search pass runs the Pallas full-chain kernel
    (``interpret`` runs it in the Pallas interpreter, for tests).
    """
    if not 1 <= waves <= MAX_WAVES:
        raise ValueError(f"waves must be in [1, {MAX_WAVES}], got {waves}")
    _check_wave_args(args, prod)
    weight_idx = resolve_weight_idx(args, active_axes)
    bal_idx = resolve_balance_idx(active_axes)
    prod_mode = args.score_according_prod_usage
    explain_full = explain == "full"

    def _step_impl(fc: FullChainInputs, sides: WaveSideInputs, n_real):
        inputs = fc.base
        P, _R = inputs.fit_requests.shape

        wave_body = _make_wave_body(
            fc, sides, n_real, weight_idx, bal_idx, num_gangs, num_groups,
            explain, prod_mode, score_passes=score_passes,
            search=_pallas_search_pass(
                args, active_axes, fc, vmem_budget_bytes, volumes, explain,
                claims, interpret))

        def cond(carry):
            w, done = carry[-2], carry[-1]
            return (w < waves) & ~done

        # the parity-critical wave-state slots come from the SAME
        # builder the chain's wave-0 carry uses — the two dispatch
        # shapes cannot desynchronize their initial state
        init = initial_wave_carry(fc, sides) + (
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.int32(0),
            jnp.zeros(waves, jnp.int32),
        )
        if explain is not None:
            init = init + (
                jnp.zeros((waves, P, NUM_EXPLAIN_STAGES), jnp.uint32),)
            if explain_full:
                init = init + (
                    jnp.zeros((P, len(EXPLAIN_TERMS)), jnp.float32),)
        init = init + (jnp.int32(0), jnp.bool_(False))
        out = jax.lax.while_loop(cond, wave_body, init)
        fw = FusedWaveOut(
            bind_pods=out[_OUT_PODS], bind_nodes=out[_OUT_NODES],
            bind_zones=out[_OUT_ZONES], bind_res=out[_OUT_RES],
            wave_counts=out[_WAVE_COUNTS], waves_run=out[-2])
        if explain is None:
            return fw
        return fw, ExplainOut(out[_EX_COUNTS],
                              out[_EX_TERMS] if explain_full else None)

    if explain is None:
        def fused_waves(fc: FullChainInputs, sides: WaveSideInputs):
            return _step_impl(fc, sides, None)

        return jax.jit(fused_waves) if jit else fused_waves

    def fused_waves_explain(fc: FullChainInputs, sides: WaveSideInputs,
                            n_real):
        return _step_impl(fc, sides, n_real)

    return jax.jit(fused_waves_explain) if jit else fused_waves_explain


def build_chained_wave_step(args: LoadAwareArgs, num_gangs: int,
                            num_groups: int, jit: bool = True,
                            active_axes=None, explain=None,
                            prod: bool = False, claims: bool = False,
                            res: bool = False, score_passes=(),
                            vmem_budget_bytes=None, volumes: bool = True,
                            interpret: bool = False):
    """ONE wave per dispatch, carried state on device between dispatches.

    (FullChainInputs, carry, WaveSideInputs) -> (carry', WaveChainOut),
    where ``carry`` is the initial_wave_carry tuple (or a previous
    dispatch's output carry — the arrays never leave the device between
    waves). Under koordexplain the step takes the extra ``n_real``
    operand and returns (carry', WaveChainOut, counts_row[P, S]) — this
    wave's attribution at wave-START state, the exact row the fused
    step's [K, P, S] buffer holds at index w.

    K-independent by construction: the cycle driver chains as many
    dispatches as the wave budget needs, so every K shares one compiled
    program, and — the point of the chain — wave w+1 can be dispatched
    BEFORE wave w's rows are read back, overlapping the host replay of
    wave w with device execution of wave w+1. A zero ``count`` readback
    is the fixpoint signal (the fused while_loop's early exit); the
    driver stops consuming there (tracking the pending-transition flag
    host-side — a consumed allocate-once reservation arms one more
    wave, see scheduler/cycle.py).

    ``vmem_budget_bytes``/``volumes``/``interpret``: the search pass's
    kernel, as build_fused_wave_step takes them.
    """
    _check_wave_args(args, prod)
    weight_idx = resolve_weight_idx(args, active_axes)
    bal_idx = resolve_balance_idx(active_axes)
    prod_mode = args.score_according_prod_usage
    explain_full = explain == "full"

    def _step_impl(fc: FullChainInputs, carry, sides: WaveSideInputs,
                   n_real):
        P = fc.base.fit_requests.shape[0]
        wave_body = _make_wave_body(
            fc, sides, n_real, weight_idx, bal_idx, num_gangs, num_groups,
            explain, prod_mode, score_passes=score_passes,
            search=_pallas_search_pass(
                args, active_axes, fc, vmem_budget_bytes, volumes, explain,
                claims, interpret))
        full = tuple(carry[:NUM_WAVE_STATE]) + (
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.full(P, -1, jnp.int32),
            jnp.int32(0),
            jnp.zeros(1, jnp.int32),
        )
        if explain is not None:
            full = full + (
                jnp.zeros((1, P, NUM_EXPLAIN_STAGES), jnp.uint32),)
            if explain_full:
                full = full + (carry[NUM_WAVE_STATE],)
        full = full + (jnp.int32(0), jnp.bool_(False))
        out = wave_body(full)
        new_carry = tuple(out[:NUM_WAVE_STATE])
        if explain_full:
            new_carry = new_carry + (out[_EX_TERMS],)
        rows = WaveChainOut(bind_pods=out[_OUT_PODS],
                            bind_nodes=out[_OUT_NODES],
                            bind_zones=out[_OUT_ZONES],
                            bind_res=out[_OUT_RES],
                            count=out[_N_OUT])
        if explain is None:
            return new_carry, rows
        return new_carry, rows, out[_EX_COUNTS][0]

    if explain is None:
        def chained_wave(fc: FullChainInputs, carry, sides: WaveSideInputs):
            return _step_impl(fc, carry, sides, None)

        return jax.jit(chained_wave) if jit else chained_wave

    def chained_wave_explain(fc: FullChainInputs, carry,
                             sides: WaveSideInputs, n_real):
        return _step_impl(fc, carry, sides, n_real)

    return jax.jit(chained_wave_explain) if jit else chained_wave_explain
