"""The fused batched scheduling step.

Replaces the reference's scheduleOne hot loop (SURVEY.md section 3.1): instead of
per-pod Go plugin dispatch with a per-node goroutine fan-out, one compiled XLA
program processes an entire pending-pod batch against the packed node state.

Two execution modes:

  * serial-parity (default): a `lax.fori_loop` walks pods in queue order; each
    iteration filters+scores that pod against ALL nodes in one fused vector pass,
    picks argmax, and applies the assignment to on-device state (Fit `requested`,
    LoadAware assign-cache deltas) before the next pod — bit-matching the
    reference's sequential contract (pod i+1 sees pod i's Reserve). Tie-break is
    lowest node index (the reference randomizes among max-score nodes,
    selectHost; the parity emulator uses the same deterministic rule).

  * score-matrix: one shot [P, N] feasibility + scores for all pods, no
    assignment feedback — used by the descheduler's global rebalance and by
    diagnostics (top-N score dump, frameworkext/debug.go analog).

State layout (all float32/bool, static shapes):
  requested[N, R]   NodeResourcesFit accumulated requests
  delta_np[N, R]    in-batch LoadAware assign-cache estimates (all pods)
  delta_pr[N, R]    same, prod pods only (scoreAccordingProdUsage branch)
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from koordinator_tpu.ops import loadaware as la_ops
from koordinator_tpu.ops.common import least_requested_score
from koordinator_tpu.ops.fit import fit_ok_matrix, fit_ok_row, with_pod_count
from koordinator_tpu.ops.loadaware import LoadAwareArgs
from koordinator_tpu.ops.packing import NodeBatch, PodBatch


class ScheduleInputs(NamedTuple):
    """Device-ready pytree for one scheduling step (LoadAware chain)."""

    # pods [P, ...]
    fit_requests: jnp.ndarray   # [P, R] requests with pods-axis = 1
    estimated: jnp.ndarray      # [P, R]
    is_prod: jnp.ndarray        # [P]
    is_daemonset: jnp.ndarray   # [P]
    pod_valid: jnp.ndarray      # [P]
    # nodes [N, ...]
    allocatable: jnp.ndarray    # [N, R]
    requested: jnp.ndarray      # [N, R]
    node_ok: jnp.ndarray        # [N] valid & schedulable
    la_filter_usage: jnp.ndarray
    la_has_filter_usage: jnp.ndarray
    la_filter_thresholds: jnp.ndarray
    la_prod_thresholds: jnp.ndarray
    la_prod_pod_usage: jnp.ndarray
    la_term_nonprod: jnp.ndarray
    la_term_prod: jnp.ndarray
    la_score_valid: jnp.ndarray
    la_filter_skip: jnp.ndarray
    weights: jnp.ndarray        # [R]


def make_inputs(pods: PodBatch, nodes: NodeBatch, args: LoadAwareArgs) -> ScheduleInputs:
    # host numpy throughout: the jitted step does the single H2D transfer;
    # eager jnp.asarray here would round-trip via reduce_to_active_axes
    ex = nodes.extras
    node_ok = np.asarray(nodes.valid)
    return ScheduleInputs(
        fit_requests=np.asarray(with_pod_count(pods.requests)),
        estimated=np.asarray(pods.estimated),
        is_prod=np.asarray(pods.is_prod),
        is_daemonset=np.asarray(pods.is_daemonset),
        pod_valid=np.asarray(pods.valid),
        allocatable=np.asarray(nodes.allocatable),
        requested=np.asarray(nodes.requested),
        node_ok=np.asarray(node_ok),
        la_filter_usage=np.asarray(ex["la_filter_usage"]),
        la_has_filter_usage=np.asarray(ex["la_has_filter_usage"]),
        la_filter_thresholds=np.asarray(ex["la_filter_thresholds"]),
        la_prod_thresholds=np.asarray(ex["la_prod_thresholds"]),
        la_prod_pod_usage=np.asarray(ex["la_prod_pod_usage"]),
        la_term_nonprod=np.asarray(ex["la_term_nonprod"]),
        la_term_prod=np.asarray(ex["la_term_prod"]),
        la_score_valid=np.asarray(ex["la_score_valid"]),
        la_filter_skip=np.asarray(ex["la_filter_skip"]),
        weights=np.asarray(args.weight_vector()),
    )


def _score_row(
    est_row: jnp.ndarray,       # [R]
    is_prod_i: jnp.ndarray,     # scalar bool
    inputs: ScheduleInputs,
    delta_np: jnp.ndarray,      # [N, R]
    delta_pr: jnp.ndarray,      # [N, R]
    weight_idx: Tuple[int, ...],
    prod_mode: bool,
) -> jnp.ndarray:
    """LoadAware score of one pod against all nodes, honoring in-batch deltas."""
    acc = jnp.zeros(inputs.allocatable.shape[0], jnp.float32)
    wsum = jnp.sum(inputs.weights)
    for r in weight_idx:
        base = (
            jnp.where(
                is_prod_i,
                inputs.la_term_prod[:, r] + delta_pr[:, r],
                inputs.la_term_nonprod[:, r] + delta_np[:, r],
            )
            if prod_mode
            else inputs.la_term_nonprod[:, r] + delta_np[:, r]
        )
        used = est_row[r] + base
        acc = acc + inputs.weights[r] * least_requested_score(
            used, inputs.allocatable[:, r]
        )
    score = jnp.floor(acc / jnp.maximum(wsum, 1.0))
    return jnp.where(inputs.la_score_valid, score, 0.0)


def build_schedule_step(args: LoadAwareArgs, jit: bool = True):
    """Return a jittable step: ScheduleInputs -> (chosen[P] int32, requested[N, R]).

    chosen[i] is the node index assigned to queue-position-i pod, or -1.
    With jit=False the raw traceable fn is returned (for re-jitting under a Mesh
    with explicit shardings, see parallel/).
    """
    weight_idx = tuple(int(i) for i in np.nonzero(args.weight_vector())[0])
    prod_mode = args.score_according_prod_usage

    def schedule_xla(inputs: ScheduleInputs):
        P = inputs.fit_requests.shape[0]
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

        def body(i, state):
            requested, delta_np, delta_pr, chosen = state
            req = inputs.fit_requests[i]
            est = inputs.estimated[i]
            is_prod_i = inputs.is_prod[i]
            fit = fit_ok_row(req, inputs.allocatable, requested)
            la_reject = jnp.where(is_prod_i, reject_prod, reject_np)
            la_ok = inputs.is_daemonset[i] | ~la_reject
            feasible = inputs.node_ok & fit & la_ok
            score = _score_row(
                est, is_prod_i, inputs, delta_np, delta_pr, weight_idx, prod_mode
            )
            score = jnp.where(feasible, score, -1.0)
            best = jnp.argmax(score)  # first occurrence -> lowest index tie-break
            found = (score[best] >= 0.0) & inputs.pod_valid[i]
            sel = (jnp.arange(N, dtype=jnp.int32) == best) & found
            requested = requested + sel[:, None] * req[None, :]
            est_add = sel[:, None] * est[None, :]
            delta_np = delta_np + est_add
            if prod_mode:
                delta_pr = delta_pr + jnp.where(is_prod_i, 1.0, 0.0) * est_add
            chosen = chosen.at[i].set(jnp.where(found, best.astype(jnp.int32), -1))
            return requested, delta_np, delta_pr, chosen

        R = inputs.fit_requests.shape[-1]
        init = (
            inputs.requested,
            jnp.zeros((N, R), jnp.float32),
            jnp.zeros((N, R), jnp.float32),
            jnp.full(P, -1, jnp.int32),
        )
        requested, _, _, chosen = jax.lax.fori_loop(0, P, body, init)
        return chosen, requested

    return jax.jit(schedule_xla) if jit else schedule_xla


def build_best_schedule_step(args: LoadAwareArgs, vmem_budget_bytes=None):
    """Backend-aware selector: the VMEM-resident Pallas kernel on TPU
    (ops/pallas_step.py), the XLA step elsewhere. Same contract,
    bit-identical bindings. Past the device kind's VMEM budget the
    per-call dispatch runs the XLA step (see build_best_full_chain_step)."""
    xla_step = build_schedule_step(args)
    if jax.default_backend() != "tpu":
        return xla_step
    from koordinator_tpu.ops import pallas_common as pc
    from koordinator_tpu.ops.pallas_step import (
        build_pallas_schedule_step,
        estimate_vmem_bytes,
    )

    budget = (pc.default_vmem_budget() if vmem_budget_bytes is None
              else vmem_budget_bytes)
    pallas_step = build_pallas_schedule_step(args)

    def step(inputs):
        R = inputs.fit_requests.shape[1]
        N = inputs.allocatable.shape[0]
        if estimate_vmem_bytes(N, R) <= budget:
            step.last_backend = "pallas"
            return pallas_step(inputs)
        step.last_backend = "xla"
        return xla_step(inputs)

    step.last_backend = None
    return step


def build_score_matrix(args: LoadAwareArgs, jit: bool = True):
    """One-shot [P, N] (feasible, score) with no assignment feedback."""
    prod_mode = args.score_according_prod_usage
    weight_idx = tuple(int(i) for i in np.nonzero(args.weight_vector())[0])

    def score_matrix(inputs: ScheduleInputs):
        reject_np, reject_prod = la_ops.loadaware_node_reject(
            inputs.allocatable,
            inputs.la_filter_usage,
            inputs.la_has_filter_usage,
            inputs.la_filter_thresholds,
            inputs.la_prod_thresholds,
            inputs.la_prod_pod_usage,
            inputs.la_filter_skip,
        )
        la_ok = la_ops.loadaware_filter(
            inputs.is_prod, inputs.is_daemonset, reject_np, reject_prod
        )
        fit = fit_ok_matrix(inputs.fit_requests, inputs.allocatable, inputs.requested)
        feasible = (
            la_ok
            & fit
            & inputs.node_ok[None, :]
            & inputs.pod_valid[:, None]
        )
        score = la_ops.loadaware_score_terms(
            inputs.estimated,
            inputs.is_prod,
            inputs.la_term_nonprod,
            inputs.la_term_prod,
            inputs.allocatable,
            inputs.la_score_valid,
            inputs.weights,
            prod_mode,
            weight_idx,
        )
        return feasible, score

    return jax.jit(score_matrix) if jit else score_matrix
