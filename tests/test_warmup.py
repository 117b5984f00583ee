"""Persistent compile cache + AOT warm-up ladder (scheduler/warmup.py).

Covers the PR 15 cold-start contract:

  * record -> restart -> replay: a fresh Scheduler against the same
    cache dir rebuilds every recorded rung through the keyed step-cache
    chokepoints, so its first cycle is an in-memory HIT — zero
    steady-state recompiles up to (and past) the first bind;
  * fingerprint discipline: a simulated code-version bump
    (KOORD_TPU_PROGRAM_FINGERPRINT) must MISS — rungs count
    ``invalidated``, nothing replays, and the on-demand compile still
    works;
  * corruption: a truncated/garbage index and truncated XLA cache
    entries must degrade to a clean compile — the ladder never crashes
    the scheduler;
  * the aval-spec roundtrip the index records call shapes with.

The cache config is process-global in jax, so this module owns ONE
session dir; decision determinism under the armed cache is separately
pinned by the parity gates (hack/lint.sh runs them with the cache on).
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from koordinator_tpu.scheduler import metrics as scheduler_metrics
from koordinator_tpu.scheduler import warmup as wu
from koordinator_tpu.scheduler.cycle import CyclePipeline, Scheduler
from koordinator_tpu.scheduler.pipeline_parity import (
    apply_round_delta,
    build_store_from_state,
)
from koordinator_tpu.testing import synth_full_cluster


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("compile_cache"))
    wu.configure_compile_cache(d)
    # jax's cache config is process-global: later configure calls with a
    # different dir are ignored (first wins), so every test here shares
    # this one dir
    assert wu.configure_compile_cache(d) == wu._configured_dir
    return wu._configured_dir


def _world(seed=7, pods=40):
    _cluster, state = synth_full_cluster(
        16, pods, seed=seed, num_quotas=2, num_gangs=2,
        topology_fraction=0.5, lsr_fraction=0.2)
    return state, build_store_from_state(state)


def _run_rounds(sched, store, now, rounds=3, arrivals=7):
    pipe = CyclePipeline(sched, enabled=True)
    for r in range(rounds):
        if r:
            apply_round_delta(store, r, now, arrivals)
        pipe.run_cycle(now=now + 2 * r)
    pipe.flush()


class TestAvalSpec:
    def test_roundtrip_arrays_tuples_none_values(self):
        from koordinator_tpu.models.scheduler_model import ScheduleInputs

        spec = wu.aval_spec((np.zeros((3, 4), np.float32), None,
                             np.int32(5), (np.ones(2, bool),)))
        out = wu.zeros_from_spec(spec)
        assert out[1] is None
        assert out[0].shape == (3, 4) and out[0].dtype == np.float32
        assert out[2] == 5  # scalars record BY VALUE
        assert out[3][0].dtype == bool
        # namedtuples rebuild through the registry
        n_fields = len(ScheduleInputs._fields)
        si = ScheduleInputs(*([np.zeros((2, 2), np.float32)] * n_fields))
        out = wu.zeros_from_spec(wu.aval_spec(si))
        assert isinstance(out, ScheduleInputs)
        assert out[0].shape == (2, 2)

    def test_unregistered_namedtuple_rejected(self):
        import collections

        Odd = collections.namedtuple("OddTuple", "x")
        with pytest.raises(TypeError):
            wu.aval_spec(Odd(x=np.zeros(1)))


class TestIndex:
    def test_corrupt_index_loads_empty(self, cache_dir, tmp_path):
        idx = wu.CompileCacheIndex(str(tmp_path))
        with open(idx.path, "w") as f:
            f.write('{"v": 1, "entries": {"x"')  # truncated JSON
        assert idx.load() == {}
        # a record after the corruption rewrites a clean index
        idx.record("serial", {"signature": [16, 16, 1]}, [])
        assert len(idx.load()) == 1

    def test_stale_fingerprint_purged_on_write(self, tmp_path,
                                               monkeypatch):
        idx = wu.CompileCacheIndex(str(tmp_path))
        monkeypatch.setenv("KOORD_TPU_PROGRAM_FINGERPRINT", "v1")
        idx.record("serial", {"signature": [16, 16, 1]}, [])
        assert len(idx.load()) == 1
        monkeypatch.setenv("KOORD_TPU_PROGRAM_FINGERPRINT", "v2")
        idx.record("serial", {"signature": [32, 16, 1]}, [])
        entries = idx.load()
        # the v1 entry is gone; only the v2 rung remains
        assert len(entries) == 1
        assert all(e["fp"] == "v2" for e in entries.values())


class TestWarmupLadder:
    def test_record_then_restart_replays_with_zero_steady_misses(
            self, cache_dir):
        state, store = _world()
        sched = Scheduler(store, waves=4, explain="off", warmup="off")
        _run_rounds(sched, store, state.now)
        entries = wu.CompileCacheIndex(cache_dir).load()
        assert entries, "dispatch compiles must record rungs"
        assert {e["kind"] for e in entries.values()} <= {
            "serial", "fused", "chain", "rebalance", "colo", "scatter"}

        # the "restarted" scheduler: same store world, sync warm-up
        state2, store2 = _world()
        sched2 = Scheduler(store2, waves=4, explain="off", warmup="sync")
        stats = sched2.warmup.stats
        assert stats["complete"] is True
        assert stats["warmed"] == stats["rungs"] > 0  # every rung HIT
        assert stats["failed"] == stats["invalidated"] == 0
        assert sched2._steady_state is True

        # first cycle binds with ZERO steady-state recompiles: the
        # in-memory step cache already holds every rung
        flagged = []
        sched2.compile_miss_hook = flagged.append
        m0 = scheduler_metrics.COMPILE_CACHE_MISSES.get()
        pipe = CyclePipeline(sched2, enabled=True)
        res = pipe.run_cycle(now=state2.now)
        pipe.flush()
        assert res.bound, "the warm scheduler must actually bind"
        assert scheduler_metrics.COMPILE_CACHE_MISSES.get() == m0
        assert flagged == []

    @pytest.mark.parametrize("waves", [1, 4])
    def test_mesh_rungs_and_scatters_warm_on_the_mesh(
            self, cache_dir, cpu_devices, waves):
        """A restarted mesh scheduler runs its step rungs on operands
        placed as a cycle places them, and compiles the row-delta
        programs its predecessor met as ``scatter`` rungs: the same
        rounds then compile neither a step nor a scatter."""
        state, store = _world(seed=31)
        sched = Scheduler(store, waves=waves, explain="off", warmup="off",
                          mesh=4)
        _run_rounds(sched, store, state.now)
        tag = [int(d.id) for d in sched.mesh.devices.flat]
        kinds = {e["kind"] for e in
                 wu.CompileCacheIndex(cache_dir).load().values()
                 if e["meta"].get("mesh_tag") == tag}
        assert "scatter" in kinds and kinds & {"serial", "fused", "chain"}

        state2, store2 = _world(seed=31)
        sched2 = Scheduler(store2, waves=waves, explain="off",
                           warmup="sync", mesh=4)
        stats = sched2.warmup.stats
        assert stats["warmed"] > 0
        assert stats["failed"] == 0
        scatters = len(sched2.device_snapshot._scatter_cache)
        assert scatters > 0
        # each warmed step holds the executable of the cycle's own
        # operand shardings: the rounds add none
        steps = dict(sched2._step_cache)
        held = {k: steps[k]._cache_size() for k in steps}
        assert held and all(n >= 1 for n in held.values())
        m0 = scheduler_metrics.COMPILE_CACHE_MISSES.get()
        _run_rounds(sched2, store2, state2.now)
        assert scheduler_metrics.COMPILE_CACHE_MISSES.get() == m0
        assert len(sched2.device_snapshot._scatter_cache) == scatters
        assert {k: steps[k]._cache_size() for k in steps} == held

    def test_fingerprint_bump_invalidates_and_recompiles(
            self, cache_dir, monkeypatch):
        state, store = _world(seed=9)
        sched = Scheduler(store, waves=1, explain="off", warmup="off")
        _run_rounds(sched, store, state.now, rounds=2)
        # simulated code-version bump: every recorded rung must MISS
        monkeypatch.setenv("KOORD_TPU_PROGRAM_FINGERPRINT",
                           "bumped-version")
        state2, store2 = _world(seed=9)
        sched2 = Scheduler(store2, waves=1, explain="off", warmup="sync")
        stats = sched2.warmup.stats
        assert stats["warmed"] == 0
        assert stats["invalidated"] == stats["rungs"] > 0
        # ...and the on-demand compile path still works (recompiled)
        m0 = scheduler_metrics.COMPILE_CACHE_MISSES.get()
        res = sched2.run_cycle(now=state2.now)
        assert res.bound
        assert scheduler_metrics.COMPILE_CACHE_MISSES.get() > m0

    def test_corrupted_cache_entries_fall_back_cleanly(self, cache_dir):
        state, store = _world(seed=13)
        sched = Scheduler(store, waves=4, explain="off", warmup="off")
        _run_rounds(sched, store, state.now, rounds=2)
        # truncate every on-disk XLA entry AND garbage the index tail:
        # warm-up must still complete and the scheduler must still bind
        for name in os.listdir(cache_dir):
            if name.endswith("-cache"):
                path = os.path.join(cache_dir, name)
                with open(path, "r+b") as f:
                    f.truncate(64)
        state2, store2 = _world(seed=13)
        sched2 = Scheduler(store2, waves=4, explain="off", warmup="sync")
        stats = sched2.warmup.stats
        assert stats["complete"] is True  # never crashes the ladder
        res = sched2.run_cycle(now=state2.now)
        assert res.bound

        # a fully garbage index degrades to an empty ladder
        idx_path = os.path.join(cache_dir, wu.INDEX_NAME)
        with open(idx_path, "w") as f:
            f.write("\x00not json at all")
        state3, store3 = _world(seed=13)
        sched3 = Scheduler(store3, waves=4, explain="off", warmup="sync")
        assert sched3.warmup.stats["rungs"] == 0
        assert sched3.warmup.stats["complete"] is True
        assert sched3.run_cycle(now=state3.now).bound

    def test_ladder_transition_drops_steady_state_guard(self, cache_dir):
        state, store = _world(seed=21)
        sched = Scheduler(store, waves=1, explain="off", warmup="off")
        sched.note_warmup_complete(
            {"warmed": 2, "rungs": 2, "seconds": 0.1,
             "skipped": 0, "failed": 0, "invalidated": 0})
        assert sched._steady_state is True
        sched._on_ladder_transition(
            {"from": "full", "to": "no-mesh", "from_level": 0,
             "to_level": 2, "reason": "test"})
        assert sched._steady_state is False

    def test_empty_ladder_never_arms_the_guard(self, cache_dir):
        """A first boot against an index that covered nothing (empty,
        or all rungs invalidated) promised nothing — its legitimate
        cold compiles must not be flagged as steady-state misses."""
        state, store = _world(seed=25)
        sched = Scheduler(store, waves=1, explain="off", warmup="off")
        sched.note_warmup_complete(
            {"warmed": 0, "rungs": 0, "seconds": 0.0,
             "skipped": 0, "failed": 0, "invalidated": 0})
        assert sched._steady_state is False
        flagged = []
        sched.compile_miss_hook = flagged.append
        assert sched.run_cycle(now=state.now).bound
        assert flagged == []


_CACHE_PROBE = """
import jax
from koordinator_tpu.scheduler import warmup as wu
from koordinator_tpu.scheduler.cycle import Scheduler
from koordinator_tpu.client.store import ObjectStore
lib = Scheduler(ObjectStore()).compile_cache_dir
got = wu.configure_compile_cache(wu.default_compile_cache_dir())
print(lib, got, jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_dir_rule(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and no other dir is set; unset, a
    library Scheduler stays cache-off and the entry points fall back to
    <checkout>/.jax_compile_cache. A fresh process: jax's cache config is
    process-global."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=120).stdout.split()
    default = os.path.join(root, ".jax_compile_cache")
    if env_dir:
        assert out == [str(tmp_path)] * 3
    else:
        assert out == ["None", default, default]
