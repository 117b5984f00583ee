#!/usr/bin/env python
"""Coldstart gate (PR 15): the persistent compile cache must pay for
itself across a crash-restart, without moving a single decision.

Runs the crash-restart scenario (waves pinned to 4, the fused-chain
compile ladder) as a process pair:

  * COLD — no compile-cache dir: the restart pays the full on-demand
    compile ladder before its first bind (the pre-PR-15 world);
  * WARM — JAX_COMPILATION_CACHE_DIR armed + KOORD_TPU_WARMUP=sync:
    the restart replays the rung index recorded by its own pre-restart
    cycles, XLA compiles disk-served, the first cycle an in-memory
    step-cache HIT.

Asserts (all from the report JSON):

  * binding logs BYTE-IDENTICAL across the pair — the cache is a
    latency lever, never a decision change;
  * zero invariant breaches in both worlds;
  * the warm restart binds its first pod with ZERO steady-state
    recompiles (restart.steady_state_compiles == [0]) and a complete
    warm-up ladder with every valid rung warmed;
  * warm restart-to-first-bind wall-clock strictly below cold. Wall
    clocks on a noisy box can invert at sim scale (the margin is the
    XLA-backend share of the compile, which silicon-scale programs
    dominate but ~1s sim programs do not), so a single inversion
    re-measures the pair once before failing.

Usage: check_coldstart.py [--cache-dir DIR] [--retries 1] [--json OUT]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile


def run_crash_restart(env_extra, label):
    """Run the crash-restart scenario (waves pinned to 4) in a fresh
    subprocess under a scrubbed cache env + ``env_extra``. Returns
    (report dict | None, process wall seconds). ONE implementation for
    this gate AND bench.py --coldstart (which imports it), so the
    cold/warm subprocess protocol can never drift between the two."""
    import time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("KOORD_TPU_WARMUP", None)
    env.update(env_extra)
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    try:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "koordinator_tpu.sim", "crash-restart",
             "--waves", "4", "--quiet", "--max-breaches", "0",
             "--out", out_path],
            capture_output=True, text=True,
            cwd=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".."),
            env=env)
        wall = time.perf_counter() - t0
        if proc.returncode != 0:
            print(f"FAIL {label} run exited {proc.returncode}:\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            return None, wall
        with open(out_path) as f:
            return json.load(f), wall
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass


def warm_env(cache_dir):
    return {"JAX_COMPILATION_CACHE_DIR": cache_dir,
            "KOORD_TPU_WARMUP": "sync"}


def report_restart_wall(rep):
    walls = rep["restart"]["to_first_bind_wall_seconds"]
    return max(walls) if walls else 0.0


def measure_pair(cache_dir):
    cold, _w = run_crash_restart({}, "cold")
    warm, _w = run_crash_restart(warm_env(cache_dir), "warm")
    return cold, warm


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cache-dir", default=None,
                    help="compile-cache dir for the warm run (default: "
                    "a fresh temp dir)")
    ap.add_argument("--retries", type=int, default=1,
                    help="wall-clock inversion re-measures this many "
                    "times before failing (default 1)")
    ap.add_argument("--json", default=None,
                    help="write the pair summary JSON here")
    args = ap.parse_args(argv)

    restart_wall = report_restart_wall

    def validate(cold, warm):
        """The STRUCTURAL contract — checked on every measured pair,
        retries included (a re-measured pair must re-prove everything,
        not just the wall ordering)."""
        errors = []
        if cold["binding_log_sha256"] != warm["binding_log_sha256"]:
            errors.append(
                f"binding logs differ: cold "
                f"{cold['binding_log_sha256'][:16]} vs warm "
                f"{warm['binding_log_sha256'][:16]} — the compile "
                f"cache moved a decision")
        for label, rep in (("cold", cold), ("warm", warm)):
            if rep["invariant_breaches"]:
                errors.append(
                    f"{label} run had {rep['invariant_breaches']} "
                    f"invariant breaches")
        wu = warm.get("warmup", {})
        if not wu.get("complete"):
            errors.append("warm run's warm-up ladder did not complete")
        elif wu.get("failed", 0) or wu.get("invalidated", 0):
            errors.append(f"warm-up rungs failed/invalidated: {wu}")
        elif (wu.get("warmed", 0) + wu.get("built", 0)
              != wu.get("rungs", -1)):
            errors.append(f"not every recorded rung was warmed: {wu}")
        steady = warm["restart"].get("steady_state_compiles", [])
        if steady != [0] * warm["restart"]["count"]:
            errors.append(
                f"warm restart compiled in steady state: {steady} — "
                f"the first bind must be an in-memory step-cache hit")
        return errors

    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="koord_cc_")
    tries = 0
    while True:
        cold, warm = measure_pair(cache_dir)
        if cold is None or warm is None:
            return 1
        errors = validate(cold, warm)
        cold_wall, warm_wall = restart_wall(cold), restart_wall(warm)
        if errors or warm_wall < cold_wall or tries >= args.retries:
            break
        # structural contract held but the wall ordering inverted: a
        # noisy-box artifact at sim scale — re-measure the whole pair
        tries += 1
        print(f"coldstart: wall inversion (cold {cold_wall:.2f}s vs "
              f"warm {warm_wall:.2f}s); re-measuring pair "
              f"({tries}/{args.retries})", file=sys.stderr)
        import shutil

        shutil.rmtree(cache_dir, ignore_errors=True)
        os.makedirs(cache_dir, exist_ok=True)
    if not errors and warm_wall >= cold_wall:
        errors.append(
            f"warm restart-to-first-bind wall ({warm_wall:.2f}s) not "
            f"below cold ({cold_wall:.2f}s) after {tries} retries")
    wu = warm.get("warmup", {})
    steady = warm["restart"].get("steady_state_compiles", [])

    summary = {
        "cold_restart_wall_seconds": cold_wall,
        "warm_restart_wall_seconds": warm_wall,
        "cold_restart_compile_seconds":
            cold["restart"]["restart_wall_compile_seconds"],
        "warm_restart_compile_seconds":
            warm["restart"]["restart_wall_compile_seconds"],
        "warm_restart_pack_seconds":
            warm["restart"]["restart_wall_pack_seconds"],
        "warmup": wu,
        "steady_state_compiles": steady,
        "binding_log_sha256": cold["binding_log_sha256"],
        "pair_deterministic":
            cold["binding_log_sha256"] == warm["binding_log_sha256"],
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
            f.write("\n")
    if errors:
        for e in errors:
            print(f"FAIL coldstart: {e}", file=sys.stderr)
        return 1
    print(f"ok coldstart: logs identical "
          f"({cold['binding_log_sha256'][:16]}…), warm restart "
          f"{warm_wall:.2f}s < cold {cold_wall:.2f}s, warm-up "
          f"{wu.get('warmed', 0)}/{wu.get('rungs', 0)}"
          f" rungs, 0 steady-state recompiles", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
