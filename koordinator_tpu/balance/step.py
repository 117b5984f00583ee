"""The device rebalance pass: LowNodeLoad as ONE batched tensor program.

``build_rebalance_step`` compiles node classification, per-node overload
margins, and the greedy victim selection into a single jitted pass over
the packed arrays (balance/pack.py), with compacted
(node_idx, pod_idx, score) readback — the device twin of the host oracle
``LowNodeLoad.select_victims_host`` (descheduler/lownodeload.py), which
stays as the diagnose-style reference exactly the way
``host_stage_counts`` is for koordexplain.

Decision-parity discipline (gated by
``pipeline_parity.run_rebalance_parity`` at mesh 1/2/4/8):

  * the victim ORDER is the host's stable lexsort (node, priority asc,
    cpu desc, slot order as the tiebreak), reproduced as three chained
    stable argsorts plus a candidates-first pass — a stable sort of the
    full padded axis restricted to candidate rows IS the stable sort of
    the compressed candidate array;
  * the freed-requests prefix runs as an int32 cumsum: the packed
    request rows are integer-valued by the repo's f32-exactness
    discipline (milli-cores / MiB), a global int32 cumsum may wrap, but
    per-segment DIFFERENCES of prefix sums are exact in modular
    arithmetic while each segment's freed total stays < 2^31 — the
    device-side analog of the host's float64 accumulation;
  * the still-over threshold compare reproduces the host's float64
    comparison bit-for-bit through a two-limb split: the host
    precomputes rhs = (usage_pct - high_thr) * alloc in float64 per node
    (tiny [N, R]) and ships (hi, lo) float32 limbs; the device tests
    ``X < hi  or  (X == hi and lo > 0)``, which for the exactly-
    representable integer X = freed*100 decides ``X < rhs_f64`` exactly.

Everything here is jnp on traced values — no host loops, no store reads
(koordlint rule 16 pins that for this package).
"""

from __future__ import annotations

from typing import NamedTuple


class RebalanceOut(NamedTuple):
    """Device outputs of one rebalance pass (device values until the
    driver's readback sync). ``sel_*`` are compacted: the first
    ``sel_count`` entries are the selected victims in host victim order;
    the tail is -1/0 padding."""

    is_low: object       # [N] bool — below low thresholds on every axis
    is_high: object      # [N] bool — above high thresholds on any axis
    margin: object       # [N] f32  — max checked-axis overload (>= 0)
    cand_count: object   # scalar i32 — movable pods on overloaded nodes
    sel_count: object    # scalar i32 — victims selected
    sel_pod: object      # [P] i32  — pack slot index of victim j (-1 pad)
    sel_node: object     # [P] i32  — node index of victim j (-1 pad)
    sel_score: object    # [P] f32  — victim-order key (cpu request)


def build_rebalance_step(max_evict_per_node: int, jit: bool = True):
    """Compile the rebalance tensor pass for a per-node eviction cap.

    The returned step takes padded arrays (pad pods: ``pod_ok`` False;
    pad nodes: ``has_metric`` False — both make the row inert, the same
    bucket-pad semantics the scheduler kernels use):

      usage_pct [N, R] f32, has_metric [N] bool,
      low_thr [R] f32, high_thr [R] f32,
      rhs_hi [N, R] f32, rhs_lo [N, R] f32   (host float64 limb split),
      pod_node [P] i32, pod_prio [P] i32, pod_cpu [P] f32,
      pod_req_i [P, R] i32, pod_ok [P] bool  (alive & movable)
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    cap = int(max_evict_per_node)

    def rebalance_step(usage_pct, has_metric, low_thr, high_thr, rhs_hi,
                       rhs_lo, pod_node, pod_prio, pod_cpu, pod_req_i,
                       pod_ok):
        N = usage_pct.shape[0]
        P = pod_node.shape[0]
        # ---- classification (classify_nodes, vectorized identically)
        checked_l = low_thr > 0
        low = jnp.all(~checked_l | (usage_pct < low_thr),
                      axis=-1) & has_metric
        checked_h = high_thr > 0
        over = usage_pct - high_thr
        high = jnp.any(checked_h & (over > 0.0), axis=-1) & has_metric
        is_low = low & ~high
        is_high = high
        margin = jnp.where(
            has_metric,
            jnp.max(over, axis=-1, initial=0.0,
                    where=jnp.broadcast_to(checked_h, over.shape)),
            0.0).astype(jnp.float32)
        # host early-outs become a kernel-wide gate: no high or no low
        # nodes -> zero candidates -> empty selection
        active = jnp.any(is_high) & jnp.any(is_low)
        # the host's over_gate spans ALL axes (unchecked thresholds are
        # 0, so any positive usage passes) — replicate verbatim
        over_gate = jnp.any(over > 0.0, axis=-1)
        node_ok = is_high & over_gate
        cand = (pod_ok & (pod_node >= 0)
                & node_ok[jnp.maximum(pod_node, 0)] & active)
        cand_count = jnp.sum(cand.astype(jnp.int32))

        # ---- victim order: stable lexsort (node, prio asc, cpu desc)
        # over the candidate rows. Least-significant key first, then a
        # candidates-first pass pushes pad/non-candidate rows to the
        # tail without perturbing the candidates' relative order.
        idx = jnp.arange(P, dtype=jnp.int32)
        order = jnp.argsort(-pod_cpu, stable=True)
        order = order[jnp.argsort(pod_prio[order], stable=True)]
        order = order[jnp.argsort(pod_node[order], stable=True)]
        order = order[jnp.argsort(
            jnp.where(cand[order], 0, 1).astype(jnp.int32), stable=True)]
        cs = cand[order]
        node_s = pod_node[order]

        # ---- per-node segments over the sorted candidate prefix
        seg_start = cs & ((idx == 0) | (node_s != jnp.roll(node_s, 1)))
        start_pos = lax.cummax(jnp.where(seg_start, idx, -1))
        sp = jnp.maximum(start_pos, 0)
        rank = idx - start_pos

        # ---- exclusive freed-requests prefix per segment: int32
        # modular cumsum (see module doc); non-candidate rows contribute
        # zero so the candidate prefix matches the compressed host array
        reqs_s = jnp.where(cs[:, None], pod_req_i[order], 0)
        gcum = jnp.cumsum(reqs_s, axis=0, dtype=jnp.int32)
        excl = gcum - reqs_s
        freed = excl - excl[sp]
        X = freed.astype(jnp.float32) * 100.0

        # ---- still-over: the host's float64 "freed*100 < rhs" compare,
        # decided exactly via the (hi, lo) limb split
        ns = jnp.clip(node_s, 0, N - 1)
        rh = rhs_hi[ns]
        rl = rhs_lo[ns]
        lt = (X < rh) | ((X == rh) & (rl > 0.0))
        still_over = jnp.any(lt & checked_h, axis=-1)

        # ---- greedy selection: candidate k is taken iff every earlier
        # candidate in its segment (and k itself) kept the node over,
        # and its rank is under the per-node cap — the prefix-AND as a
        # cumsum-of-failures == 0 test, exactly the host formulation
        fail_i = jnp.where(cs, (~still_over).astype(jnp.int32), 0)
        fails_g = jnp.cumsum(fail_i)
        seg_base = fails_g[sp] - fail_i[sp]
        prefix_ok = (fails_g - seg_base) == 0
        selected = cs & prefix_ok & (rank < cap)

        # ---- compacted readback: scatter the selected triples to the
        # front (drop-mode scatter; non-selected rows target index P)
        sel_rank = jnp.cumsum(selected.astype(jnp.int32)) - 1
        sel_count = jnp.sum(selected.astype(jnp.int32))
        tgt = jnp.where(selected, sel_rank, P)
        sel_pod = jnp.full(P, -1, jnp.int32).at[tgt].set(
            order.astype(jnp.int32), mode="drop")
        sel_node = jnp.full(P, -1, jnp.int32).at[tgt].set(
            node_s.astype(jnp.int32), mode="drop")
        sel_score = jnp.zeros(P, jnp.float32).at[tgt].set(
            pod_cpu[order], mode="drop")
        return RebalanceOut(is_low, is_high, margin, cand_count,
                            sel_count, sel_pod, sel_node, sel_score)

    return jax.jit(rebalance_step) if jit else rebalance_step


def split_rhs_limbs(usage_pct, alloc, high_thr):
    """Host-side float64 rhs = (usage_pct - high_thr) * max(alloc, 1e-9)
    per node, split into (hi, lo) float32 limbs for the exact device
    compare. Vectorized numpy — tiny [N, R] work, no per-node loop."""
    import numpy as np

    rhs = ((usage_pct.astype(np.float64) - high_thr.astype(np.float64))
           * np.maximum(alloc, np.float32(1e-9)).astype(np.float64))
    hi = rhs.astype(np.float32)
    lo = (rhs - hi.astype(np.float64)).astype(np.float32)
    return hi, lo
