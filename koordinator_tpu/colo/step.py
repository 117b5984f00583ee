"""The device colo pass: the control plane's resource model as ONE program.

``build_colo_step`` compiles the two koord-manager reconciler families the
device mirror never touched into a single jitted pass:

  * the slo-controller's NodeResource pipeline — the batch/mid overcommit
    formula of ``slocontroller/noderesource._batch_mid_kernel`` reproduced
    verbatim over the packed per-node columns (colo/pack.py), with the
    staleness degrade folded in as ``degraded -> zero batch/mid rows``
    exactly like the host controller's gather;
  * the quota-controller's elastic-quota runtime fairness — the
    ``ops/quota.compute_runtime_quotas`` level fold (auto-scaled mins +
    water-filling redistribution per (parent, resource) segment) expressed
    as segment ops over the packed tree, plus the over-runtime
    revoke-candidate mask the overuse loop consumes.

Decision-parity discipline (gated by ``pipeline_parity.run_colo_parity``
at single-device and mesh 1/2/4/8):

  * the batch/mid arithmetic is the exact f32 op sequence of the host
    kernel — both sides run IEEE f32 elementwise ops on bit-identical
    packed rows, so the ``int()`` writeback truncation lands on the same
    integers;
  * the water-filling rounds are the host's own f32 arithmetic
    (``go_round_np`` is ``floor(x + 0.5)`` on f32 arrays), transcribed
    op-for-op; segment sums are order-free because every packed quota
    quantity is integer-valued (milli-cores / MiB) and the reconciler's
    eligibility guard bounds per-parent sums under 2^24 — the f32
    integer-exact envelope;
  * the ONE float64 site in the host fold — ``scaled_min_level``'s
    ``floor(avail * min / en_sum)`` — is an exact integer floor-division
    for in-envelope operands, reproduced on device through an f32 quotient
    candidate plus an int32 MODULAR correction (the same wraparound trick
    balance/step.py uses for its freed-prefix cumsum: ``a*m - q*s`` is
    exact in int32 arithmetic while the true remainder stays < 2^31).

Everything here is jnp on traced values — no host loops, no store reads
(koordlint rule 18 ``host-reconcile-in-colo-path`` pins that for this
package).
"""

from __future__ import annotations

from typing import NamedTuple

from koordinator_tpu.api.resources import RESOURCE_INDEX, ResourceName
from koordinator_tpu.ops.quota import MAX_QUOTA_DEPTH
from koordinator_tpu.utils.sloconfig import (
    POLICY_MAX_USAGE_REQUEST,
    POLICY_REQUEST,
)

CPU = RESOURCE_INDEX[ResourceName.CPU]
MEM = RESOURCE_INDEX[ResourceName.MEMORY]
BATCH_CPU_AXIS = RESOURCE_INDEX[ResourceName.BATCH_CPU]
BATCH_MEM_AXIS = RESOURCE_INDEX[ResourceName.BATCH_MEMORY]
MID_CPU_AXIS = RESOURCE_INDEX[ResourceName.MID_CPU]
MID_MEM_AXIS = RESOURCE_INDEX[ResourceName.MID_MEMORY]


class ColoOut(NamedTuple):
    """Device outputs of one colo pass (device values until the driver's
    readback sync). Node columns are the 4 allocatable vectors the
    writeback publishes; quota rows carry the runtime matrix and the
    revoke-candidate mask the overuse loop consumes."""

    batch_cpu: object     # [N] f32 — batch-cpu allocatable (milli)
    batch_mem: object     # [N] f32 — batch-memory allocatable (MiB)
    mid_cpu: object       # [N] f32
    mid_mem: object       # [N] f32
    n_degraded: object    # scalar i32 — staleness-degraded real nodes
    runtime: object       # [G, R] f32 — runtime quota per group
    revoke_over: object   # [G, R] f32 — max(used - runtime, 0)
    revoke_mask: object   # [G] bool  — any axis over runtime
    predicted_total: object  # [R] f32 — post-writeback cluster total
    #                          the runtime fold divided (verified by
    #                          the reconciler against the store)


def _exact_floordiv(a, m, s):
    """``floor(a * m / s)`` computed EXACTLY for integer-valued f32
    operands with ``a, m < 2^24`` and ``m <= s`` wherever the result is
    consumed: an f32 quotient candidate (absolute error <= 3 after
    floor), then an int32 modular correction — ``a*m`` and ``q*s`` wrap
    identically mod 2^32, so their difference is the true remainder
    whenever it stays < 2^31, which the +-3 candidate window guarantees.
    Rows violating the preconditions are masked off by the caller (the
    reconciler's eligibility guard demotes out-of-envelope trees to the
    host oracle before this runs)."""
    import jax.numpy as jnp

    s1 = jnp.maximum(s, 1.0)
    q0 = jnp.floor(a * m / s1)
    ai = a.astype(jnp.int32)
    mi = m.astype(jnp.int32)
    si = s1.astype(jnp.int32)
    am = ai * mi  # wraps mod 2^32 — exactness lives in the difference
    best = jnp.zeros_like(q0)
    # static 7-candidate unroll at trace time, not a host data loop
    # koordlint: disable=host-reconcile-in-colo-path
    for off in range(-3, 4):
        q = jnp.maximum(q0 + off, 0.0)
        k = am - q.astype(jnp.int32) * si
        best = jnp.where(k >= 0, jnp.maximum(best, q), best)
    return best


def _scaled_min_level(total, parent, min_, enable, level, cur_level, gp):
    """Device twin of ops/quota.scaled_min_level: AutoScaleMin for the
    groups at ``cur_level``. The host's float64 segment sums are exact
    f32 under the eligibility envelope; the one genuine f64 computation
    (the proportional floor-division) goes through _exact_floordiv."""
    import jax.numpy as jnp

    R = min_.shape[1]
    active = level == cur_level
    seg = jnp.where(parent >= 0, parent, gp)

    def seg_sum(mask):
        contrib = jnp.where((active & mask)[:, None], min_, 0.0)
        return jnp.zeros((gp + 1, R), jnp.float32).at[seg].add(contrib)

    en_sum = seg_sum(enable)
    dis_sum = seg_sum(~enable)
    seg_total = jnp.full((gp + 1, R), -jnp.inf, jnp.float32).at[seg].max(
        jnp.where(active[:, None], total, -jnp.inf))
    seg_total = jnp.where(jnp.isfinite(seg_total), seg_total, 0.0)

    need_scale = (en_sum + dis_sum) > seg_total
    avail = jnp.maximum(seg_total - dis_sum, 0.0)
    scaled = _exact_floordiv(avail[seg], min_, en_sum[seg])
    use = active[:, None] & enable[:, None] & need_scale[seg]
    return jnp.where(use, scaled, min_).astype(jnp.float32)


def _water_fill_level(total, parent, min_, guarantee, request, weight,
                      allow_lent, level, cur_level, gp):
    """Device twin of ops/quota.water_fill_level: one level of the
    iterated redistribution, the host's f32 op sequence transcribed with
    the data-dependent break as a lax.while_loop predicate (the body is
    idempotent once no group stays adjustable, so the padded bound never
    changes the fixpoint)."""
    import jax.numpy as jnp
    from jax import lax

    active = (level == cur_level)[:, None]
    eff_min = jnp.maximum(min_, guarantee)
    over = request > eff_min
    base = jnp.where(over, eff_min,
                     jnp.where(allow_lent[:, None], request, eff_min))
    base = jnp.where(active, base, 0.0)
    seg = jnp.where(parent >= 0, parent, gp)
    adjustable = over & active & (weight > 0)

    def seg_sum(x):
        return jnp.zeros((gp + 1, x.shape[1]), x.dtype).at[seg].add(x)

    spent = seg_sum(base)
    seg_total = jnp.full((gp + 1, total.shape[1]), -jnp.inf,
                         jnp.float32).at[seg].max(
        jnp.where(active, total, -jnp.inf))
    leftover = jnp.maximum(seg_total - spent, 0.0)
    leftover = jnp.where(jnp.isfinite(leftover), leftover, 0.0)

    def cond(carry):
        i, _runtime, adj, left = carry
        return (i < gp + 2) & jnp.any(adj) & jnp.any(left > 0)

    def body(carry):
        i, runtime, adj, left = carry
        w = jnp.where(adj, weight, 0.0)
        wsum = seg_sum(w)[seg]
        delta = jnp.where(
            (wsum > 0) & adj,
            jnp.floor(weight * left[seg] / jnp.maximum(wsum, 1e-9) + 0.5),
            0.0)
        new_rt = runtime + delta
        overshoot = jnp.maximum(new_rt - request, 0.0)
        # only adjustable rows clamp to request; a non-lent sibling sits
        # at eff_min > request and must keep it (host comment verbatim)
        new_rt = jnp.where(adj, jnp.minimum(new_rt, request), runtime)
        still = adj & (new_rt < request)
        left = seg_sum(jnp.where(adj, overshoot, 0.0))
        return i + 1, new_rt, still, left

    _, runtime, _, _ = lax.while_loop(
        cond, body, (0, base, adjustable, leftover))
    return jnp.where(active, runtime, 0.0).astype(jnp.float32)


def device_runtime_quotas(parent, level, q_min, q_max, weight, guarantee,
                          request, enable_scale, allow_lent, q_valid,
                          cluster_total, scale_min_enabled: bool = True):
    """Device twin of ops/quota.compute_runtime_quotas: the top-down
    level fold. Levels are a static Python loop over the bounded tree
    depth (MAX_QUOTA_DEPTH); levels past the real depth have no active
    rows and are no-ops, so ONE compiled program serves every tree."""
    import jax.numpy as jnp

    gp = parent.shape[0]
    total_row = cluster_total.astype(jnp.float32)
    runtime = jnp.zeros_like(q_min)
    # static bounded-depth unroll at trace time (the host fold's level
    # loop); every op inside is a traced array op
    # koordlint: disable=host-reconcile-in-colo-path
    for lvl in range(MAX_QUOTA_DEPTH + 1):
        total = jnp.where(
            (parent >= 0)[:, None],
            runtime[jnp.clip(parent, 0, gp - 1)],
            total_row[None, :])
        min_eff = (
            _scaled_min_level(total, parent, q_min, enable_scale, level,
                              lvl, gp)
            if scale_min_enabled else q_min)
        rt_lvl = _water_fill_level(total, parent, min_eff, guarantee,
                                   request, weight, allow_lent, level,
                                   lvl, gp)
        runtime = jnp.where((level == lvl)[:, None], rt_lvl, runtime)
    runtime = jnp.minimum(runtime, q_max).astype(jnp.float32)
    return jnp.where(q_valid[:, None], runtime, 0.0)


def build_colo_step(cpu_policy: str, memory_policy: str,
                    scale_min_enabled: bool = True, jit: bool = True):
    """Compile the colo tensor pass for a (cpu, memory) calculate-policy
    pair (the slo-config scalars — static so the policy pick lowers to a
    column select, exactly like the host kernel's static_argnames).

    The returned step takes padded arrays (pad nodes: all-zero rows with
    ``degraded`` False — batch/mid formula yields 0; pad quota rows:
    ``level`` -1 and ``q_valid`` False — never active at any level):

      node axis [N, R] f32: capacity, node_reserved, system_reserved,
        node_used, pod_all_used, hp_used, hp_request, hp_max,
        prod_reclaimable, reclaim_pct, mid_pct; degraded [N] bool
      quota axis: q_parent/q_level [G] i32, q_min/q_max/q_weight/
        q_guarantee/q_request/q_used [G, R] f32, q_allow_lent/
        q_enable_scale/q_valid [G] bool

    ``q_total_base`` is the cluster allocatable total with the four
    overcommit axes ZEROED: the runtime fold divides the PREDICTED
    post-writeback total — base axes from the store, batch/mid axes
    re-derived from this pass's own truncated columns — because in the
    host world the noderesource writeback lands BEFORE the revoke loop
    computes runtime, and the device pass must match that ordering
    inside one program.
    """
    import jax
    import jax.numpy as jnp

    def pick(by_usage, by_request, by_max, policy):
        if policy == POLICY_REQUEST:
            return by_request
        if policy == POLICY_MAX_USAGE_REQUEST:
            return by_max
        return by_usage

    def colo_step(capacity, node_reserved, system_reserved, node_used,
                  pod_all_used, hp_used, hp_request, hp_max, prod_reclaimable,
                  reclaim_pct, mid_pct, degraded,
                  q_parent, q_level, q_min, q_max, q_weight, q_guarantee,
                  q_request, q_used, q_allow_lent, q_enable_scale, q_valid,
                  q_total_base):
        # ---- batch/mid: slocontroller/noderesource._batch_mid_kernel,
        # the identical f32 op sequence (parity is bit-level)
        reclaimable_capacity = capacity * reclaim_pct / 100.0
        system_used = jnp.maximum(node_used - pod_all_used, 0.0)
        system_used = jnp.maximum(system_used, system_reserved)
        by_usage = jnp.maximum(
            reclaimable_capacity - node_reserved - system_used - hp_used,
            0.0)
        by_request = jnp.maximum(
            reclaimable_capacity - node_reserved - system_reserved
            - hp_request, 0.0)
        by_max = jnp.maximum(
            reclaimable_capacity - node_reserved - system_used - hp_max,
            0.0)
        batch = by_usage
        batch = batch.at[:, CPU].set(
            pick(by_usage, by_request, by_max, cpu_policy)[:, CPU])
        batch = batch.at[:, MEM].set(
            pick(by_usage, by_request, by_max, memory_policy)[:, MEM])
        batch = jnp.where(degraded[:, None], 0.0, batch)
        mid = jnp.minimum(prod_reclaimable, capacity * mid_pct / 100.0)
        mid = jnp.where(degraded[:, None], 0.0, jnp.maximum(mid, 0.0))

        # ---- predicted post-writeback cluster total: the writeback
        # publishes int(column) per node (truncation = floor for these
        # nonnegative values), so the new overcommit-axis totals are the
        # floored column sums — exact f32 under the eligibility envelope
        predicted_total = q_total_base
        # static 4-axis unroll at trace time
        # koordlint: disable=host-reconcile-in-colo-path
        for axis, col in ((BATCH_CPU_AXIS, batch[:, CPU]),
                          (BATCH_MEM_AXIS, batch[:, MEM]),
                          (MID_CPU_AXIS, mid[:, CPU]),
                          (MID_MEM_AXIS, mid[:, MEM])):
            predicted_total = predicted_total.at[axis].set(
                jnp.sum(jnp.floor(col)))

        # ---- quota runtime fold + the revoke-candidate mask
        runtime = device_runtime_quotas(
            q_parent, q_level, q_min, q_max, q_weight, q_guarantee,
            q_request, q_enable_scale, q_allow_lent, q_valid,
            predicted_total, scale_min_enabled=scale_min_enabled)
        revoke_over = jnp.maximum(q_used - runtime, 0.0) * jnp.where(
            q_valid[:, None], 1.0, 0.0)
        revoke_mask = jnp.any(revoke_over > 0, axis=-1) & q_valid

        return ColoOut(
            batch_cpu=batch[:, CPU], batch_mem=batch[:, MEM],
            mid_cpu=mid[:, CPU], mid_mem=mid[:, MEM],
            n_degraded=jnp.sum(degraded.astype(jnp.int32)),
            runtime=runtime, revoke_over=revoke_over,
            revoke_mask=revoke_mask, predicted_total=predicted_total)

    return jax.jit(colo_step) if jit else colo_step
