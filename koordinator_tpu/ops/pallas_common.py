"""Shared fragments for the VMEM-resident Pallas scheduling kernels.

Both kernels (ops/pallas_step.py LoadAware-only, ops/pallas_full_chain.py
full chain) carry the bit-identical-bindings contract against the XLA steps;
the logic they share lives here as plain-Python helpers called from inside
the kernel bodies, so a fix lands in both at once.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_NODE_SCORE = 100.0

# Shared unroll/streaming scheme for the sequential kernels: UNROLL pods are
# walked per grid step (grid bookkeeping and state load/store amortize), pod
# columns stream in as [R, POD_BLOCK] blocks, and the chosen output block is
# (UNROLL, 1) — written by exactly one step. UNROLL must divide POD_BLOCK.
UNROLL = 8
POD_BLOCK = 128

# Effective-request sentinel: rows with no demand compare true against any
# headroom, making (req <= 0) | (req <= free) a single compare.
NEG_F32 = -3.0e38

# Scoped-VMEM limit Mosaic gives one kernel, keyed by jax's
# ``device_kind``. The backend selectors route a shape to a Pallas kernel
# only when that kernel's ``estimate_vmem_bytes`` (an upper bound of the
# compiler's scoped allocation, checked by tests/test_chip_compile.py)
# fits. An unknown kind is an error: a limit guessed for a chip the code
# was never compiled for lets the selector dispatch a program the chip's
# compiler refuses.
VMEM_LIMIT_BYTES = {
    # v5e: the compiler's RESOURCE_EXHAUSTED for the full chain at
    # 2,048 x 6,144 reads "Scoped allocation with size 16.91M and limit
    # 16.00M" (jax 0.9.0 / libtpu 0.0.34, compiled for a described
    # v5e:2x2 — PR 21)
    "TPU v5 lite": 16 * 1024 * 1024,
}


def vmem_budget_bytes(device_kind: str) -> int:
    try:
        return VMEM_LIMIT_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no scoped-VMEM limit known for TPU kind {device_kind!r}: "
            "compile the Pallas kernels for it and add its limit to "
            "ops/pallas_common.VMEM_LIMIT_BYTES") from None


def default_vmem_budget() -> Optional[int]:
    """The scoped-VMEM budget of jax's default device: None off TPU; an
    unknown TPU kind raises (vmem_budget_bytes)."""
    if jax.default_backend() != "tpu":
        return None
    return vmem_budget_bytes(jax.devices()[0].device_kind)


def pad8(rows: int) -> int:
    """Sublane rows an [rows, N] f32 VMEM buffer occupies: one row stays
    one row, more round up to the 8-row tile."""
    return 1 if rows <= 1 else -(-rows // 8) * 8


def lanes(n: int) -> int:
    """Lane width an [*, n] buffer occupies (128-lane tiles)."""
    return -(-n // 128) * 128


def weight_consts(weights: np.ndarray) -> List[Tuple[int, float]]:
    """Static (axis, weight) pairs baked into the kernel as Python floats —
    SMEM only serves scalars, so weights can't ride a vector input."""
    return [(r, float(v)) for r, v in enumerate(weights) if v]


def pod_column(ref, pod_mask) -> jnp.ndarray:
    """Extract pod i's [R, 1] column from an [R, P] array via the lane
    one-hot `pod_mask` ([1, P]). TPU block shapes can't carve a [1, R] row
    and dynamic lane slicing relayouts; the masked reduce is a few hundred
    VPU flops."""
    return jnp.sum(ref[:] * pod_mask, axis=1, keepdims=True)


def make_pod_mask(i, P_pad: int) -> jnp.ndarray:
    return (jax.lax.broadcasted_iota(jnp.int32, (1, P_pad), 1) == i
            ).astype(jnp.float32)


def weight_col(consts, R: int) -> jnp.ndarray:
    """[R, 1] weight column built from a sublane iota — Pallas kernels
    cannot capture array constants, so the static weights are encoded as a
    chain of iota selects."""
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    col = jnp.zeros((R, 1), jnp.float32)
    for r, wv in consts:
        col = jnp.where(r_iota == r, jnp.float32(wv), col)
    return col


def safe_reciprocal(cap) -> jnp.ndarray:
    """f32 1/cap with 0 for cap <= 0. The balanced-allocation score in every
    implementation (XLA evaluator, Pallas kernel, wave kernel, numpy oracle,
    C++ floor) computes f = min(used * safe_reciprocal(cap), 1) — the SAME
    f32 reciprocal-multiply — so bit-parity across kernels holds while the
    per-pod division rows disappear. The JAX sites all call this helper; the
    numpy/C++ forms transcribe it (1.0f/cap guarded by cap > 0)."""
    return jnp.where(cap > 0, 1.0 / jnp.where(cap > 0, cap, 1.0), 0.0)


def least_requested_rem(rem, safe_cap, cap_pos) -> jnp.ndarray:
    """least_requested with the remainder (alloc - used) precomputed and
    safe_cap/cap_pos hoisted out of the per-pod loop: rem >= 0 is exactly
    used <= alloc for the packed-integer values the kernels carry."""
    per_r = jnp.floor(rem * MAX_NODE_SCORE / safe_cap)
    return jnp.where(cap_pos & (rem >= 0), per_r, 0.0)


def weighted_floor_score_col(per_r, w_col, wsum: float) -> jnp.ndarray:
    """weighted_floor_score as one [R, 1]-broadcast multiply + sublane
    reduce — per-row slicing of an [R, N] array relayouts on Mosaic, so the
    loop form costs ~3x. Same f32 product/sum values, so the floor parity
    holds (per-axis products are exact for packed integers * small weights,
    and the sum order over R is ascending in both forms)."""
    return jnp.floor(jnp.sum(per_r * w_col, axis=0) / wsum)


def lowest_index_max(score, N: int, iota=None):
    """(best, maxv, iota): lowest-index max, computed explicitly — Mosaic's
    argmax does not guarantee first-occurrence on ties, and the binding
    contract (reference selectHost determinism) hangs on this tie-break.
    Pass a precomputed [N] iota to hoist it out of a per-pod loop."""
    maxv = jnp.max(score)
    if iota is None:
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)[0]
    best = jnp.min(jnp.where(score == maxv, iota, jnp.int32(N))
                   ).astype(jnp.int32)
    return best, maxv, iota


# ---- wrapper-side packing helpers ----------------------------------------

smem_spec = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)


def full_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def pod_block_spec(R: int):
    """[R, POD_BLOCK] streaming spec for pod-column arrays: a block serves
    POD_BLOCK // UNROLL consecutive grid steps."""
    return pl.BlockSpec((R, POD_BLOCK), lambda i: (0, (i * UNROLL) // POD_BLOCK))


def chosen_block_spec():
    """(UNROLL, 1) chosen-output block, one per grid step."""
    return pl.BlockSpec((UNROLL, 1), lambda i: (i, 0))


def f32(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.float32)


def row(x) -> jnp.ndarray:
    return f32(x)[None, :]


def pad_pods(P: int, multiple: int = 8):
    """(P_pad, pad_spec): pods padded to a multiple (8 so the (8, 1) chosen
    blocks divide the grid; the unrolled full-chain kernel asks for its
    POD_BLOCK). Padded entries have pod_valid == 0."""
    P_pad = -(-P // multiple) * multiple
    return P_pad, [(0, P_pad - P)]
