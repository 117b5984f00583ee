#!/usr/bin/env bash
# Repo lint gate: koordlint (AST static analysis, see README "Static
# analysis") + a bytecode-compile sweep + the koordtrace JSONL schema pin.
# Mirrors what tier-1 enforces via tests/test_static_analysis.py and
# tests/test_obs.py so it can run pre-push without pytest.
set -euo pipefail

cd "$(dirname "$0")/.."

echo "== koordlint =="
python -m koordinator_tpu.analysis koordinator_tpu bench.py

echo "== koordlint guard map + orphan-lock self-check =="
# the whole-program lock-discipline pass (analysis/guards.py): dumps the
# inferred guard map and fails on any Lock/RLock attribute that guards
# nothing — every shipped lock must earn its place in the map (or carry
# a `# koordlint: guards(<resource>)` declaration)
python -m koordinator_tpu.analysis --guards --check-locks koordinator_tpu \
    > /dev/null

echo "== compileall =="
python -m compileall -q koordinator_tpu bench.py tests hack/microbench.py \
    hack/check_metrics_catalog.py hack/check_races.py

echo "== serial-vs-pipelined + fused-wave + explain + mesh cycle parity =="
# same store fixture through the strictly serial path, the CyclePipeline,
# AND the fused multi-wave path at K in {1,2,4,8}: bindings, failure sets
# and PodScheduled conditions must be byte-identical — a fused-K cycle is
# K sequential single-round cycles (tier-1 runs the same fixtures via
# tests/test_cycle_pipeline.py and tests/test_fused_waves.py; the
# readback-in-wave-body rule above keeps the wave kernels device-pure).
# Also gates koordexplain: the kernel-counts formatter must reproduce the
# legacy diagnose messages string-for-string, and the pipeline/fused
# parity properties must hold with KOORD_TPU_EXPLAIN=counts enabled.
# Also gates the mesh-backed dispatch (KOORD_TPU_MESH): the production
# cycle sharded over 1/2/4/8-device meshes — serial, fused K=4, and with
# explain=counts on top — must be byte-identical to single-device (the
# harness forces the 8-way virtual CPU device split itself).
# Also gates the overlapped wave replay (KOORD_TPU_REPLAY_OVERLAP):
# run_replay_overlap_parity diffs the chained in-flight replay against
# the serial-replay twin at K in {1,2,4,8}; the env pin below makes the
# fused-wave + mesh gates run WITH overlap enabled (both worlds), so
# every parity property above holds under the overlap architecture too.
# Also gates koordcolo (colo/): run_colo_parity runs the device
# control-plane pass (slo-controller batch/mid overcommit + the
# elastic-quota runtime fold as ONE jitted program over the shared
# DeviceSnapshot) against the retained host oracles — batch/mid
# allocatable vectors, degraded-node sets, runtime-quota matrices,
# revoke-victim lists (order included) and binding logs must be
# decision-identical at single-device and mesh 1/2/4/8.
# PR 15: the pack-overlap parity gates ride this run (overlap is
# default-on, and run_pack_overlap_parity diffs the twin at the
# ScheduleInputs level), AND the whole suite runs with the persistent
# compile cache armed — every parity property must hold with on-disk
# executables serving the deserialized side. The warm-up ladder is
# pinned OFF here: the suite builds dozens of differently-configured
# schedulers in one process and each would redundantly replay the
# shared rung index; the ladder has its own gate (check_coldstart.py).
_KOORD_CC_DIR="$(mktemp -d)"
KOORD_TPU_REPLAY_OVERLAP=1 JAX_COMPILATION_CACHE_DIR="$_KOORD_CC_DIR" \
    KOORD_TPU_WARMUP=off JAX_PLATFORMS=cpu \
    python -m koordinator_tpu.scheduler.pipeline_parity
rm -rf "$_KOORD_CC_DIR"

echo "== obs trace schema (golden fixture) =="
# the CLI exits non-zero on any schema drift against the checked-in trace;
# a deliberate format change must regenerate the fixture AND bump
# TRACE_SCHEMA_VERSION in koordinator_tpu/obs/__init__.py
python -m koordinator_tpu.obs tests/fixtures/trace_golden.jsonl > /dev/null

echo "== flight-recorder bundle schema (golden fixture) =="
# same pin for the koordexplain flight recorder (obs/flight.py): schema
# drift against the checked-in bundle must be a conscious
# FLIGHT_SCHEMA_VERSION bump + fixture regeneration
python -m koordinator_tpu.obs flight tests/fixtures/flight_golden.jsonl > /dev/null

echo "== koordwatch timeline bundle schema (golden fixture) =="
# the koordwatch device-timeline JSONL (obs/timeline.py, the
# /debug/timeline body): drift must be a conscious
# TIMELINE_SCHEMA_VERSION bump + fixture regeneration
python -m koordinator_tpu.obs timeline tests/fixtures/timeline_golden.jsonl > /dev/null

echo "== koordwatch slo bundle schema (golden fixture) =="
# the koordwatch SLO registry JSONL (obs/slo.py, the /debug/slo body)
python -m koordinator_tpu.obs slo tests/fixtures/slo_golden.jsonl > /dev/null

echo "== README metric-catalog drift gate =="
# every metric name registered in code must appear in the README metric
# catalog and vice versa (hack/check_metrics_catalog.py) — the catalog
# can never rot again
python hack/check_metrics_catalog.py > /dev/null

echo "== demotion-budget gate (fused-wave burn-down, PR 14) =="
# the soak-derived seeded scenario through the REAL Scheduler: the
# demoted-cycle fraction must stay <= 15% (pre-PR-14 soak demoted 61.1%
# of cycles, CHURN_r04/r05 — claim-pods/reservations/prod/transformer
# are carried device state now). A PR reintroducing a data-driven
# demotion branch fails here fast, with the per-reason profile printed.
KOORD_TPU_REPLAY_OVERLAP=1 JAX_PLATFORMS=cpu \
    python hack/check_demotion_budget.py --budget 0.15 --cycles 150

echo "== koordsim seeded smoke scenario (determinism + invariants) =="
# the fixed-seed smoke scenario through the REAL Scheduler (~50 cycles:
# Poisson churn, a gang storm cadence, a node drain, metric flips, and a
# dispatch-fault burst that demotes the degradation ladder to the host
# fallback and back). --check-determinism runs it TWICE and requires a
# byte-identical binding log; --max-breaches 0 fails the gate on ANY
# store-level invariant breach (koordinator_tpu/sim/invariants.py). This
# keeps the gate structural — wall-clock numbers stay in bench.py.
# overlap pinned on: the byte-stability of the seeded scenario must hold
# under the overlapped-replay architecture (decisions are parity-gated
# identical, so the binding log cannot move)
KOORD_TPU_REPLAY_OVERLAP=1 JAX_PLATFORMS=cpu python -m koordinator_tpu.sim smoke \
    --check-determinism --max-breaches 0 --quiet > /dev/null

echo "== koordrace deterministic interleaving gate (two fixed seeds) =="
# the dynamic half of the lock-discipline pass (sim/racecheck.py): the
# smoke scenario with pipeline overlap, an armed dispatch watchdog and
# background warm-up, under seeded thread preemption at every
# guarded-field touchpoint from the static guard map. Two fixed
# preemption seeds; binding logs must be byte-identical across them,
# with zero unguarded-touch witnesses, zero canonical-lock-order
# inversions, zero torn /metrics or /debug/timeline scrapes, and
# static/dynamic agreement (a runtime witness the analyzer missed is
# its own failure class).
python hack/check_races.py

echo "== koordsim crash-restart scenario (recovery determinism + invariants) =="
# koordguard's crash-restart gate: the scheduler is torn down mid-run
# (device state, step caches, pack memo dropped; its store watches
# severed) and rebuilt against the surviving store. Run TWICE with
# --check-determinism: the binding logs must be byte-identical across
# the restart boundary, with zero invariant breaches (the double-booking
# and gang checks see both sides of the boundary every cycle). The
# restart-to-first-bind SLO verdict rides the report JSON; bench.py
# --churn fault-ladder is the citable wall-clock pair.
KOORD_TPU_REPLAY_OVERLAP=1 JAX_PLATFORMS=cpu python -m koordinator_tpu.sim crash-restart \
    --check-determinism --max-breaches 0 --quiet > /dev/null

echo "== coldstart gate (persistent compile cache + warm-up ladder) =="
# PR 15: the crash-restart scenario as a cold/warm process pair — cold
# pays the full on-demand compile ladder at restart, warm replays the
# recorded rung index against the persistent cache (KOORD_TPU_WARMUP=
# sync). Binding logs must be byte-identical, the warm restart must
# bind its first pod with ZERO steady-state recompiles, and the warm
# restart-to-first-bind wall must be strictly below cold (one noise
# re-measure allowed; the margin is the XLA-backend share, which real
# silicon-scale programs dominate). bench.py --coldstart is the citable
# number pair (COLDSTART_r01).
python hack/check_coldstart.py

echo "== koordsim overcommit-shift scenario (colo closed loop) =="
# koordcolo's soak gate: a co-located koord-manager recomputes batch/mid
# overcommit on device every cycle while batch-class pods consume it and
# prod-usage surges shrink/restore it mid-run. Run TWICE with
# --check-determinism (byte-identical binding logs) and zero breaches —
# the batch-bind discipline (new binds never exceed the CURRENT
# overcommit) and the metric-write-to-observing-dispatch staleness SLO
# both count as invariants here. The device-vs-host-oracle engine pair
# (logs must also be identical ACROSS engines) is bench.py --colo.
KOORD_TPU_REPLAY_OVERLAP=1 JAX_PLATFORMS=cpu python -m koordinator_tpu.sim overcommit-shift \
    --check-determinism --max-breaches 0 --quiet > /dev/null

echo "lint OK"
