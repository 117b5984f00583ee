#!/usr/bin/env python3
"""chip_smoke.py — the koord-scheduler's main path, once, on the chip.

    python chip_smoke.py              # one chip: the four phases below
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Phases (one process; no subprocess once JAX is imported; each phase prints
one informational line, and any failed check exits non-zero):

  device   — jax.devices()[0].platform must be "tpu"; never continues on
             the CPU.
  kernels  — BASELINE config 4 (10k pending x 5k nodes, full chain,
             synth_full_cluster seed 0 with bench.py run_churn's quota and
             gang counts): the Pallas full chain the backend selector
             dispatches on TPU, the XLA full chain and the C++ serial floor
             (native/floor.py) must bind identically.
  served   — Scheduler(store) with every default on (auto waves, replay
             overlap, pack overlap, compile cache) over an ObjectStore
             world at 10k x 5k: cycle 0, then churn cycles with ~1,000
             arrivals each. Every cycle binds, bindings equal the serial
             reference twin (Scheduler(waves=1), the parity gates' twin)
             on an identical store, and the ladder stays at "full" with
             zero retries, zero demotions and no host-fallback pass.
  sync     — a compiled no-op jit plus readback, median of 20: the fixed
             per-sync cost the fused-wave design amortizes.
  mesh     — (--chips 4 only) Scheduler(store, mesh=4) on the 100k pods x
             50k nodes mesh-scale world (2,048 pending) against a
             single-device twin on an identical store, with the served
             phase's ladder, retry, demotion and host-fallback checks.

Timings printed here are bring-up observations, not benchmark metrics.
The last stdout line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
The compile cache lives in JAX_COMPILATION_CACHE_DIR, else in
<checkout>/.jax_compile_cache.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(Exception):
    """A failed check: the script exits non-zero and prints no result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def _median_seconds(fn, iters: int) -> float:
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _config4_inputs(num_pods: int, num_nodes: int, seed: int):
    from koordinator_tpu.ops.loadaware import LoadAwareArgs
    from koordinator_tpu.scheduler.snapshot import (
        build_full_chain_inputs,
        reduce_to_active_axes,
    )
    from koordinator_tpu.testing import synth_full_cluster

    la = LoadAwareArgs()
    _cluster, state = synth_full_cluster(
        num_nodes, num_pods, seed=seed,
        num_quotas=max(8, num_pods // 100), num_gangs=max(4, num_pods // 50))
    fc, pods, _nodes, _tree, _gi, ng, ngroups = build_full_chain_inputs(
        state, la)
    fc, active = reduce_to_active_axes(fc)
    return la, fc, pods, ng, ngroups, active


def phase_kernels(num_pods: int = 10_000, num_nodes: int = 5_000,
                  seed: int = 0, interpret: bool = False,
                  iters: int = 10) -> dict:
    """Pallas vs XLA vs the C++ floor at BASELINE config 4. With
    ``interpret=False`` the Pallas step is the one
    build_best_full_chain_step dispatches (it must pick Pallas); a test
    on the CPU passes ``interpret=True`` to run the kernel body in the
    Pallas interpreter instead."""
    import jax
    import numpy as np

    from koordinator_tpu.models.full_chain import (
        build_best_full_chain_step,
        build_full_chain_step,
    )
    from koordinator_tpu.native import floor as native_floor
    from koordinator_tpu.ops.pallas_full_chain import (
        build_pallas_full_chain_step,
    )

    la, fc, pods, ng, ngroups, active = _config4_inputs(
        num_pods, num_nodes, seed)
    if interpret:
        vol = bool((np.asarray(fc.vol_needed) > 0).any())
        pallas = build_pallas_full_chain_step(
            la, ng, ngroups, interpret=True, active_axes=active,
            enable_volumes=vol)
    else:
        pallas = build_best_full_chain_step(la, ng, ngroups,
                                            active_axes=active)
    xla = build_full_chain_step(la, ng, ngroups, active_axes=active)

    out = {}
    fc_dev = jax.block_until_ready(jax.device_put(fc))
    for name, step in (("pallas", pallas), ("xla", xla)):
        t0 = time.perf_counter()
        chosen = np.asarray(jax.block_until_ready(step(fc)[0]))
        first = time.perf_counter() - t0
        if not interpret and name == "pallas":
            check(getattr(step, "last_backend", None) == "pallas",
                  f"selector dispatched {step.last_backend!r}, not pallas, "
                  f"at {pods.padded_size} x {fc.base.allocatable.shape[0]}")
        steady = _median_seconds(
            lambda: jax.block_until_ready(step(fc_dev)[0]), iters)
        out[name] = (chosen, first, steady)

    if not native_floor.available():
        raise SmokeFailure("libkoordfloor.so is not built")
    t0 = time.perf_counter()
    floor = native_floor.serial_schedule_full_native(
        fc, la, num_groups=ngroups, active_axes=active)
    t_floor = time.perf_counter() - t0

    n = pods.num_valid
    pal, xl = out["pallas"][0][:n], out["xla"][0][:n]
    check((pal == xl).all(),
          f"pallas vs xla: {int((pal != xl).sum())} bindings differ")
    check((xl == floor[:n]).all(),
          f"xla vs C++ floor: {int((xl != floor[:n]).sum())} bindings differ")
    bound = int((xl >= 0).sum())
    check(bound > 0, "full chain bound no pod")
    info = {
        "shape": f"{pods.padded_size}x{fc.base.allocatable.shape[0]}",
        "bound": f"{bound}/{n}",
        "identical": "pallas=xla=floor",
        "pallas_first_s": round(out["pallas"][1], 4),
        "pallas_steady_s": round(out["pallas"][2], 6),
        "xla_first_s": round(out["xla"][1], 4),
        "xla_steady_s": round(out["xla"][2], 6),
        "floor_s": round(t_floor, 4),
    }
    say("kernels", **info)
    return info


def _store_world(num_nodes: int, num_pods: int, seed: int,
                 total_pods: int = 0):
    from koordinator_tpu.scheduler.pipeline_parity import (
        build_store_from_state,
    )
    from koordinator_tpu.testing import (
        synth_full_cluster,
        top_up_running_pods,
    )

    _cluster, state = synth_full_cluster(
        num_nodes, num_pods, seed=seed,
        num_quotas=max(8, num_pods // 100), num_gangs=max(4, num_pods // 50))
    store = build_store_from_state(state)
    if total_pods:
        top_up_running_pods(store, total_pods, num_nodes)
    return state, store


def _kernels_by_cycle(sched) -> list:
    """Which kernel each dispatch used, per cycle root: the selector's
    backend for a single-round dispatch, the wave depth for a fused or
    chained one."""
    used = []
    for root in sched.tracer.roots():
        if root.name != "cycle":
            continue
        for ks in root.find_all("kernel"):
            a = ks.attributes
            if "waves" in a:
                kind = "chain" if a.get("overlap") == "1" else "fused"
                used.append(f"{kind}(K={a['waves']})")
            else:
                used.append(a.get("backend", "?"))
    return used


def _count(names: list) -> str:
    counts = {}
    for n in names:
        counts[n] = counts.get(n, 0) + 1
    return ",".join(f"{n}x{c}" for n, c in counts.items())


def _host_fallback_ran(sched) -> bool:
    return any(ks.attributes.get("host_fallback") == "1"
               for root in sched.tracer.roots()
               for ks in root.find_all("kernel"))


def _warmup_summary(sched) -> str:
    """The background ladder's outcome (its zero-calls count in
    compile_s): rungs by outcome and seconds, or "off"."""
    if sched.warmup is None:
        return "off"
    sched.warmup.join()
    st = sched.warmup.stats
    return ",".join(
        [f"{k}={st[k]}" for k in ("rungs", "warmed", "skipped", "failed",
                                  "invalidated")]
        + [f"s={st['seconds']:.3f}"])


def _dispatch_retries() -> float:
    from koordinator_tpu.scheduler import metrics as scheduler_metrics

    return sum(v for _l, v in scheduler_metrics.DISPATCH_RETRIES.samples())


def phase_served(num_pods: int = 10_000, num_nodes: int = 5_000,
                 cycles: int = 3, arrivals: int = 1_000, seed: int = 0,
                 fault_injector=None, warmup: str = "off") -> dict:
    """Scheduler(store) with every default on vs the serial reference
    twin. ``fault_injector`` (tests) is installed on the scheduler under
    test; an absorbed fault must fail the phase. ``warmup`` is the
    scheduler under test's warm-up mode: main() runs the ladder in the
    background, as a restart with a compile cache does (a test must not,
    since the cache dir is process-global); the twin is the reference
    and never runs one, so it never competes with cycle 0."""
    from koordinator_tpu.scheduler.cycle import Scheduler
    from koordinator_tpu.scheduler.pipeline_parity import apply_round_delta

    state, store = _store_world(num_nodes, num_pods, seed)
    _state, store_twin = _store_world(num_nodes, num_pods, seed)
    sched = Scheduler(store, warmup=warmup)
    twin = Scheduler(store_twin, waves=1, warmup="off")
    sched.fault_injector = fault_injector
    retries0 = _dispatch_retries()
    now = state.now
    walls, bound_per_cycle = [], []
    for r in range(cycles + 1):
        if r:
            apply_round_delta(store, r, now, arrivals)
            apply_round_delta(store_twin, r, now, arrivals)
        t = now + 2 * r
        t0 = time.perf_counter()
        res = sched.run_cycle(now=t)
        walls.append(time.perf_counter() - t0)
        check(res.demotions == [], f"cycle {r} demoted: {res.demotions}")
        got = [(b.pod_key, b.node_name) for b in res.bound]
        check(got, f"cycle {r} bound no pod")
        want = []
        # a fused dispatch of K waves is K serial cycles
        for _ in range(max(1, res.waves)):
            tres = twin.run_cycle(now=t)
            want.extend((b.pod_key, b.node_name) for b in tres.bound)
        check(got == want,
              f"cycle {r}: {len(got)} bindings vs the serial twin's "
              f"{len(want)}, sequences differ")
        bound_per_cycle.append(len(got))
    check(sched.ladder.level_name == "full",
          f"ladder at {sched.ladder.level_name!r}, not 'full'")
    retries = _dispatch_retries() - retries0
    check(retries == 0, f"{retries:g} dispatch retries")
    check(not _host_fallback_ran(sched), "the host-fallback pass ran")
    info = {
        "world": f"{num_pods}x{num_nodes}",
        "cycles": cycles + 1,
        "bound": bound_per_cycle,
        "kernels": _kernels_by_cycle(sched),
        "twin": "identical",
        "twin_kernels": _count(_kernels_by_cycle(twin)),
        "ladder": sched.ladder.level_name,
        "retries": int(retries),
        "compile_s": round(sched.compile_wall_seconds, 3),
        "cycle0_s": round(walls[0], 3),
        "steady_cycle_s": (round(statistics.median(walls[1:]), 4)
                           if len(walls) > 1 else None),
        "warmup": _warmup_summary(sched),
    }
    say("served", **info)
    return info


def phase_sync(iters: int = 20) -> dict:
    """A compiled no-op jit followed by readback: the fixed per-sync
    cost every dispatch pays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    noop = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.zeros((8, 128), jnp.int32))
    np.asarray(noop(x))  # compile + warm
    med = _median_seconds(lambda: np.asarray(noop(x)), iters)
    info = {"noop_jit_readback_median_ms": round(med * 1000.0, 4),
            "iters": iters}
    say("sync", **info)
    return info


def phase_mesh(ndev: int = 4, num_nodes: int = 50_000, pending: int = 2_048,
               total_pods: int = 100_000, cycles: int = 1, seed: int = 7,
               fault_injector=None, warmup: str = "off") -> dict:
    """Mesh dispatch over ``ndev`` devices vs a single-device twin on an
    identical store (the MULTICHIP_r06 mesh-scale world).
    ``fault_injector`` and ``warmup`` apply to the mesh scheduler, as in
    phase_served."""
    from koordinator_tpu.scheduler.cycle import Scheduler
    from koordinator_tpu.scheduler.pipeline_parity import apply_round_delta

    state, store_m = _store_world(num_nodes, pending, seed, total_pods)
    _state, store_1 = _store_world(num_nodes, pending, seed, total_pods)
    sched_m = Scheduler(store_m, mesh=ndev, warmup=warmup)
    sched_1 = Scheduler(store_1, mesh="off", warmup="off")
    sched_m.fault_injector = fault_injector
    check(sched_m.mesh is not None and sched_m.mesh.devices.size == ndev,
          f"mesh={ndev} did not resolve to a {ndev}-device mesh")
    retries0 = _dispatch_retries()
    now = state.now
    walls_m, walls_1, bound = [], [], []
    for r in range(cycles + 1):
        if r:
            for store in (store_m, store_1):
                apply_round_delta(store, r, now, max(4, pending // 100),
                                  metric_touches=max(2, num_nodes // 100),
                                  prefix="mesh", namespace="meshsmoke")
        t = now + 2 * r
        t0 = time.perf_counter()
        res_m = sched_m.run_cycle(now=t)
        walls_m.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        res_1 = sched_1.run_cycle(now=t)
        walls_1.append(time.perf_counter() - t0)
        for res in (res_m, res_1):
            check(res.demotions == [],
                  f"mesh cycle {r} demoted: {res.demotions}")
        got = [(b.pod_key, b.node_name) for b in res_m.bound]
        want = [(b.pod_key, b.node_name) for b in res_1.bound]
        check(got, f"mesh cycle {r} bound no pod")
        check(got == want,
              f"mesh cycle {r}: {len(got)} bindings vs single-device "
              f"{len(want)}, sequences differ")
        bound.append(len(got))
    rows = [int(s.attributes["rows"])
            for root in sched_m.tracer.roots()
            for s in root.find_all("shard")]
    check(rows, "no shard[i] span recorded under the mesh dispatch")
    check(all(n > 0 for n in rows), f"a shard held no rows: {rows}")
    for sched in (sched_m, sched_1):
        check(sched.ladder.level_name == "full",
              f"ladder at {sched.ladder.level_name!r}, not 'full'")
        check(not _host_fallback_ran(sched), "the host-fallback pass ran")
    retries = _dispatch_retries() - retries0
    check(retries == 0, f"{retries:g} dispatch retries")
    info = {
        "world": f"{total_pods or pending}x{num_nodes}",
        "pending": pending,
        "mesh_devices": int(sched_m.mesh.devices.size),
        "bound": bound,
        "bindings": "mesh=single",
        "kernels": _count(_kernels_by_cycle(sched_m)),
        "shard_rows": rows[:ndev],
        "mesh_cycle_s": [round(w, 3) for w in walls_m],
        "single_cycle_s": [round(w, 3) for w in walls_1],
        "retries": int(retries),
        "warmup": _warmup_summary(sched_m),
    }
    say("mesh", **info)
    return info


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _ensure_floor() -> None:
    """The C++ floor is built from tracked sources with make, before JAX
    is imported (no subprocess may start once it is)."""
    from koordinator_tpu.native import floor as native_floor

    if not native_floor.available():
        native_floor.build()


def _count_cache_events() -> dict:
    import jax

    counts = {"cache_hits": 0, "cache_misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def listener(event, **_kw):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phase only (a four-chip host)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "koordinator_tpu")):
        print("chip_smoke: FAIL: no koordinator_tpu package next to this "
              "script", file=sys.stderr)
        return 2
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    try:
        if args.chips == 1:
            _ensure_floor()
        dev = phase_device()
        check(dev["platform"] == "tpu",
              f"JAX found no TPU (platform {dev['platform']!r})")
        check(dev["count"] >= args.chips,
              f"--chips {args.chips} but JAX has {dev['count']} devices")
        say("device", **dev)
        from koordinator_tpu.scheduler.warmup import (
            configure_compile_cache,
            default_compile_cache_dir,
        )

        cache_dir = configure_compile_cache(default_compile_cache_dir())
        cache = _count_cache_events()
        if args.chips == 4:
            phase_mesh(ndev=4, warmup="background")
        else:
            phase_kernels()
            phase_served(warmup="background")
            phase_sync()
        say("cache", dir=cache_dir, **cache)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
