"""Scoped VMEM the v5e compiler allocates for the Pallas kernels, against
their estimate_vmem_bytes bounds (PERF.md appendix A).

Compiles for a described v5e (nothing runs, no chip needed) and bisects
``vmem_limit_bytes`` in the kernels' CompilerParams: the allocation lies in
(lo, hi], where a compile under hi succeeds and one under lo is refused.
Prints one markdown row per shape: kernel, P, N, R, K, X (the T/S/PT/SI
extras), V (volumes on), allocation, estimate and estimate / hi.

    JAX_PLATFORMS=cpu python hack/vmem_fit.py            # the 30 shapes
    JAX_PLATFORMS=cpu python hack/vmem_fit.py fc:1024x1024x3x2x0x0

A shape is kernel:PxNxRxKxXxV; kernel is fc (full chain) or la (LoadAware
schedule step). About 30 minutes on the CPU for all 30.
"""

from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SHAPES = [
    "fc:1024x1024x3x2x0x0", "fc:1024x4096x3x2x0x0", "fc:10240x5120x3x2x0x0",
    "fc:1024x1024x14x8x0x0", "fc:1024x4096x14x8x0x0",
    "fc:10240x5120x14x8x0x0", "fc:2048x6144x14x8x0x0",
    "fc:1024x4096x3x8x0x0", "fc:1024x4096x14x2x0x0", "fc:1024x4096x8x2x0x0",
    "fc:1024x4096x3x2x4x0", "fc:1024x4096x3x2x0x1", "fc:1024x8192x3x2x0x0",
    "fc:1024x4096x16x8x0x0", "fc:1024x4096x14x8x4x1", "fc:1024x4096x8x4x0x0",
    "fc:1024x4096x3x4x0x0", "fc:1024x4096x5x4x0x0", "fc:1024x4096x5x2x0x0",
    "fc:1024x4096x3x1x0x0",
    "la:4096x1024x14x2x0x0", "la:16384x1024x14x2x0x0",
    "la:65536x1024x14x2x0x0", "la:1024x4096x8x2x0x0",
    "la:1024x16384x3x2x0x0", "la:1024x1024x14x8x0x0",
    "la:1024x4096x14x8x0x0", "la:4096x4096x14x8x0x0", "la:1024x4096x3x2x0x0",
    "la:10240x5120x14x8x0x0",
]

MIB = 1 << 20


def main(argv) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import SingleDeviceSharding

    import koordinator_tpu.ops.pallas_full_chain as pfc
    import koordinator_tpu.ops.pallas_step as pst
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.scheduler.snapshot import (
        build_full_chain_inputs,
        reduce_to_active_axes,
    )
    from koordinator_tpu.testing import synth_full_cluster

    jax.config.update("jax_enable_compilation_cache", False)
    limit = [0]
    params = pltpu.CompilerParams

    def limited(**kw):
        kw["vmem_limit_bytes"] = limit[0]
        return params(**kw)

    pfc.pltpu.CompilerParams = pst.pltpu.CompilerParams = limited
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    # tiny reduced template: P=128, N=64, R=3, K=2 and zero-width extras;
    # every other axis has another size, so shapes rescale by value
    _, state = synth_full_cluster(60, 100, seed=0, num_quotas=8, num_gangs=4)
    fc, _p, _n, _t, _g, ng, ngroups = build_full_chain_inputs(
        state, LoadAwareArgs())
    fc, active = reduce_to_active_axes(fc)
    G = fc.quota_used.shape[0]

    def size(mb: str) -> float:
        return float(mb[:-1]) * {"K": 1 << 10, "M": MIB, "G": 1 << 30}[
            mb[-1]] if mb[-1] in "KMG" else float(mb)

    def allocation(step, shapes):
        lo, hi, at = 0, 64 * MIB, MIB
        while hi - lo > max(hi // 100, 16384):
            limit[0] = int(at)
            jax.clear_caches()
            try:
                jax.jit(step).lower(shapes).compile()
                hi = at
            except Exception as e:
                m = re.search(r"size ([0-9.]+[KMG]?) and limit", str(e))
                if not m:
                    raise
                lo, wanted = at, size(m.group(1))
                if at < wanted and wanted * 1.01 < hi:
                    at = wanted * 1.01  # jump to just above the report
                    continue
            at = (lo + hi) / 2
        return lo, hi

    print("| kernel | P | N | R | K | X | V | allocated MiB | "
          "estimate MiB | est / alloc |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for spec in argv or SHAPES:
        kind, dims = spec.split(":")
        P, N, R, K, X, V = map(int, dims.split("x"))
        scale = {128: P, 64: N, 3: R, 2: K, 0: X}

        def leaf(x):
            x = np.asarray(x)
            return jax.ShapeDtypeStruct(
                tuple(scale.get(d, d) for d in x.shape), x.dtype,
                sharding=dev)

        if kind == "fc":
            step = pfc.build_pallas_full_chain_step(
                LoadAwareArgs(), ng, ngroups, jit=False,
                active_axes=None if R == 14 else active,
                enable_volumes=bool(V))
            shapes = jax.tree.map(leaf, fc)
            est = pfc.estimate_vmem_bytes(N, R, K, G, X, X, X, X)
        else:
            step = pst.build_pallas_schedule_step(LoadAwareArgs(), jit=False)
            shapes = jax.tree.map(leaf, fc.base)
            est = pst.estimate_vmem_bytes(N, R)
        lo, hi = allocation(step, shapes)
        print(f"| {kind} | {P} | {N} | {R} | {K} | {X} | {V} | "
              f"{lo / MIB:.3f}–{hi / MIB:.3f} | {est / MIB:.3f} | "
              f"{est / hi:.2f} |", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
