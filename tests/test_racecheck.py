"""koordrace, dynamic half: the deterministic interleaving harness
(sim/racecheck.py) and the three pinned interleavings the ISSUE calls
out — a watchdog overrun racing its own clean sync, the background
warm-up ladder racing the first cycle's ``_get_*step`` probes, and a
pack-overlap dispatch window racing a late dirty-row scatter.

Every interleaving is pinned through :meth:`RaceCheck.add_hook` (a
callback fired ON the touching thread at a guarded-field touchpoint
from the static guard map) — never through sleeps. The full-scenario
two-seed determinism contract lives in ``hack/check_races.py`` (wired
into hack/lint.sh); these tests cover the harness mechanics and the
specific races at unit scale.
"""

import os
import threading

import numpy as np
import pytest

from koordinator_tpu.api.objects import (
    Node,
    NodeMetric,
    NodeMetricInfo,
    ObjectMeta,
    Pod,
    PodSpec,
)
from koordinator_tpu.api.resources import ResourceList
from koordinator_tpu.client.store import (
    KIND_NODE,
    KIND_NODE_METRIC,
    KIND_POD,
    ObjectStore,
)
from koordinator_tpu.scheduler.deadline import (
    DeadlineWatchdog,
    DispatchDeadlineExceeded,
)
from koordinator_tpu.scheduler.snapshot_cache import DeviceSnapshot
from koordinator_tpu.sim import racecheck as racecheck_mod
from koordinator_tpu.sim.racecheck import (
    RaceCheck,
    _TracedLock,
    validate_metrics_body,
    validate_timeline_body,
)

GIB = 1024 ** 3
NOW = 1_000_000.0


def make_store(num_nodes=3):
    store = ObjectStore()
    for i in range(num_nodes):
        store.add(KIND_NODE, Node(
            meta=ObjectMeta(name=f"node-{i}", namespace=""),
            allocatable=ResourceList.of(
                cpu=16_000, memory=64 * GIB, pods=110)))
        store.add(KIND_NODE_METRIC, NodeMetric(
            meta=ObjectMeta(name=f"node-{i}", namespace=""),
            update_time=NOW - 10,
            node_metric=NodeMetricInfo(
                node_usage=ResourceList.of(cpu=1000, memory=2 * GIB))))
    return store


def pend_pod(store, name, **spec_kwargs):
    pod = Pod(
        meta=ObjectMeta(name=name, creation_timestamp=NOW - 30),
        spec=PodSpec(priority=9500,
                     requests=ResourceList.of(cpu=500, memory=GIB),
                     **spec_kwargs),
    )
    store.add(KIND_POD, pod)
    return pod


@pytest.fixture
def rc():
    """A RaceCheck with preemption off (tests pin interleavings through
    hooks; random yields would only add noise) — uninstalled on exit
    even when the test dies mid-install."""
    rc = RaceCheck(preempt_seed=0, preempt_permille=0)
    yield rc
    rc.uninstall()


# ---------------------------------------------------------------------------
# harness mechanics
# ---------------------------------------------------------------------------

class TestTracedLock:
    def test_ownership_tracking(self):
        lk = _TracedLock(threading.Lock(), "Lock", "X._lock")
        assert not lk.held_by_me()
        with lk:
            assert lk.held_by_me()
            assert lk.locked()
        assert not lk.held_by_me()

    def test_rlock_reentrancy(self):
        lk = _TracedLock(threading.RLock(), "RLock", "X._lock")
        with lk:
            with lk:
                assert lk.held_by_me()
            assert lk.held_by_me()
        assert not lk.held_by_me()

    def test_condition_over_wrapper_keeps_wait_semantics(self):
        """threading.Event builds Condition(Lock()) internally; a
        wrapped lock must keep exact wait/notify semantics AND balanced
        ownership books across the wait's release/reacquire."""
        lk = _TracedLock(threading.RLock(), "RLock", "")
        cond = threading.Condition(lk)
        hits = []

        def waiter():
            with cond:
                cond.wait(timeout=10)
                hits.append(lk.held_by_me())

        t = threading.Thread(target=waiter)
        t.start()
        # let the waiter reach wait() (it RELEASES the lock there)
        for _ in range(1000):
            if lk.acquire(blocking=False):
                break
        cond.notify()
        lk.release()
        t.join(timeout=10)
        assert hits == [True]
        assert not lk.held_by_me()

    def test_install_wraps_new_locks_and_event_roundtrip(self, rc):
        rc.install()
        lk = threading.Lock()
        assert isinstance(lk, _TracedLock)
        ev = threading.Event()
        done = []
        t = threading.Thread(target=lambda: done.append(ev.wait(10)))
        t.start()
        ev.set()
        t.join(timeout=10)
        assert done == [True]
        rc.uninstall()
        assert not isinstance(threading.Lock(), _TracedLock)

    def test_factory_labels_from_lockdef_site(self, rc):
        """A lock constructed at a LockDef line the static map knows
        self-identifies — DeviceSnapshot's mirror lock gets the label
        the canonical order (obs/lockorder.py) declares."""
        rc.install()
        snap = DeviceSnapshot()
        assert isinstance(snap._lock, _TracedLock)
        assert snap._lock.label == "DeviceSnapshot._lock"

    def test_sweep_wraps_import_time_singletons(self, rc):
        from koordinator_tpu.scheduler import metrics as scheduler_metrics

        rc.install()
        assert isinstance(scheduler_metrics.REGISTRY._lock, _TracedLock)
        assert scheduler_metrics.REGISTRY._lock.label == "Registry._lock"
        rc.uninstall()
        # the sweep restores the raw lock on uninstall
        assert not isinstance(scheduler_metrics.REGISTRY._lock, _TracedLock)


class TestOrderTracking:
    def test_declared_order_violation_recorded(self, rc, monkeypatch):
        monkeypatch.setattr(racecheck_mod, "_ACTIVE", rc)
        outer = _TracedLock(threading.Lock(), "Lock",
                            rc.canonical_order[0])
        inner = _TracedLock(threading.Lock(), "Lock",
                            rc.canonical_order[1])
        with outer:
            with inner:
                pass
        assert rc.order_violations == []
        with inner:
            with outer:  # inner-then-outer: the declared inversion
                pass
        assert len(rc.order_violations) == 1
        v = rc.order_violations[0]
        assert v["held"] == rc.canonical_order[1]
        assert v["acquired"] == rc.canonical_order[0]

    def test_unlisted_locks_are_not_order_checked(self, rc, monkeypatch):
        monkeypatch.setattr(racecheck_mod, "_ACTIVE", rc)
        a = _TracedLock(threading.Lock(), "Lock", "NotCanonical._a")
        b = _TracedLock(threading.Lock(), "Lock", "NotCanonical._b")
        with b:
            with a:
                pass
        assert rc.order_violations == []


class TestScrapeValidators:
    def test_metrics_validator_accepts_real_exposition(self):
        from koordinator_tpu.scheduler import metrics as scheduler_metrics

        validate_metrics_body(scheduler_metrics.REGISTRY.expose())

    def test_metrics_validator_rejects_torn_line(self):
        with pytest.raises(ValueError):
            validate_metrics_body("koord_good 1.0\nkoord_torn 12.3torn\n")

    def test_timeline_validator_rejects_torn_bundle(self):
        from koordinator_tpu.obs.timeline import DeviceTimeline

        t = DeviceTimeline()
        t.close(t.open("scheduler", "serial"), "clean")
        body = t.export_jsonl()
        validate_timeline_body(body)
        with pytest.raises(ValueError):
            validate_timeline_body(body[: len(body) // 2])


class TestStaticIndex:
    def test_touchpoints_come_from_the_guard_map(self, rc):
        """The trace fires exactly where the static map says guarded
        fields are touched — the suppressed warmup snapshot line is
        excluded (the pragma holds for the dynamic half too)."""
        specs = [s for lines in rc._touch_files.values()
                 for s in lines.values()]
        owners = {s.owner for s in specs}
        assert "DeviceSnapshot" in owners
        assert "Registry" in owners
        assert "DeadlineWatchdog" in owners
        import koordinator_tpu.scheduler.warmup as warmup_mod

        with open(warmup_mod.__file__) as f:
            pragma_lines = [
                i for i, ln in enumerate(f.read().splitlines(), start=1)
                if "koordlint: disable=unguarded-shared-field" in ln]
        assert pragma_lines, "warmup.py lost its documented pragma"
        suppressed = [s for s in specs
                      if s.path.endswith("scheduler/warmup.py")
                      and s.line in pragma_lines]
        assert suppressed == []

    def test_canonical_order_is_the_declared_one(self, rc):
        from koordinator_tpu.obs.lockorder import CANONICAL_LOCK_ORDER

        assert rc.canonical_order == CANONICAL_LOCK_ORDER


# ---------------------------------------------------------------------------
# the three pinned interleavings
# ---------------------------------------------------------------------------

class TestWatchdogOverrunRace:
    def test_overrun_races_clean_sync(self, rc):
        """Pin the nastiest watchdog interleaving: the worker's sync
        completes EXACTLY while the overrun is being accounted. The hook
        fires on the waiter thread at the ``overruns += 1`` touchpoint
        (under DeadlineWatchdog._lock) and releases the worker there —
        the overrun must still raise, the counter must read exactly 1,
        and the late worker must drain cleanly in the background."""
        release = threading.Event()
        finished = threading.Event()
        rc.add_hook(
            lambda spec: (spec.owner == "DeadlineWatchdog"
                          and spec.field == "overruns" and spec.write),
            lambda spec, frame: release.set())
        rc.install()
        wd = DeadlineWatchdog(deadline_seconds=0.05)

        def slow_sync():
            release.wait(10)
            finished.set()
            return "late"

        with pytest.raises(DispatchDeadlineExceeded):
            wd.run(slow_sync, "test-path")
        assert release.is_set(), "hook never fired at the overrun touch"
        assert finished.wait(10), "abandoned worker never drained"
        with wd._lock:
            assert wd.overruns == 1
        assert rc.witnesses == []
        assert rc.order_violations == []

    def test_clean_sync_within_deadline_untouched(self, rc):
        rc.install()
        wd = DeadlineWatchdog(deadline_seconds=5.0)
        assert wd.run(lambda: "fast", "test-path") == "fast"
        with wd._lock:
            assert wd.overruns == 0
        assert rc.witnesses == []


class TestWarmupRacesFirstCycle:
    def test_background_ladder_races_step_cache(self, rc, tmp_path,
                                                monkeypatch):
        """The background warm-up ladder replays recorded rungs through
        ``_get_*step`` from its own thread while the first cycle
        dispatches — both threads probe the shared ``_step_cache`` memo
        (guarded by ``_step_lock`` since this PR) and the harness must
        observe zero unguarded touches. Phase 1 records rungs with the
        ladder off; phase 2 rebuilds under instrumentation."""
        from koordinator_tpu.scheduler.cycle import Scheduler
        from koordinator_tpu.scheduler.warmup import (
            _join_live_ladders,
            configure_compile_cache,
        )

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        cache_dir = configure_compile_cache()
        if cache_dir is None:  # pragma: no cover - config is first-wins
            pytest.skip("compile cache unavailable in this process")

        store1 = make_store()
        sched1 = Scheduler(store1, waves=2, warmup="off")
        pend_pod(store1, "record-a")
        sched1.run_cycle(now=NOW)

        touch_threads = set()
        rc.add_hook(
            lambda spec: (spec.owner == "Scheduler"
                          and spec.field == "_step_cache"),
            lambda spec, frame: touch_threads.add(
                threading.current_thread().name))
        rc.install()
        store2 = make_store()
        sched2 = Scheduler(store2, waves=2, warmup="background")
        assert sched2.warmup is not None, "background ladder never armed"
        pend_pod(store2, "race-a")
        result = sched2.run_cycle(now=NOW)
        _join_live_ladders()
        rc.uninstall()

        assert result.bound, "first cycle under the ladder bound nothing"
        assert any(n.startswith("koord-warmup") for n in touch_threads), \
            f"warm-up thread never probed the step cache: {touch_threads}"
        assert any(not n.startswith("koord-warmup")
                   for n in touch_threads), \
            "cycle thread never probed the step cache"
        assert rc.witnesses == []
        assert rc.order_violations == []


class TestPrepackRacesLateScatter:
    def test_scatter_under_open_dispatch_window_never_donates(self, rc):
        """Pin the pack-overlap donation hazard: a dirty-row scatter
        lands while another consumer's dispatch window is open. The
        window opens on a separate thread; the scatter proceeds only
        after the harness OBSERVED the ``_in_flight`` ledger write (the
        hook fires at the guarded touchpoint — no sleeps), so the
        ``donate = self._in_flight == 0`` read deterministically sees
        the open window and must take the non-donating path."""
        import jax.numpy as jnp

        window_open = threading.Event()
        rc.add_hook(
            lambda spec: (spec.owner == "DeviceSnapshot"
                          and spec.field == "_in_flight" and spec.write),
            lambda spec, frame: window_open.set())
        rc.install()
        snap = DeviceSnapshot()
        dev = jnp.zeros((8, 4), jnp.float32)

        t = threading.Thread(target=snap.begin_dispatch,
                             name="koordrace-dispatcher")
        t.start()
        assert window_open.wait(10), "ledger write touchpoint never fired"
        t.join(timeout=10)

        idx = np.array([2], np.int32)
        rows = np.full((1, 4), 7.0, np.float32)
        out = snap._scatter(dev, idx, rows)
        assert snap.stats["scattered_safe"] == 1, \
            "scatter donated into an open dispatch window"
        np.testing.assert_array_equal(np.asarray(out)[2], rows[0])

        snap.end_dispatch()
        out2 = snap._scatter(out, np.array([5], np.int32), rows)
        assert snap.stats["scattered_safe"] == 1, \
            "closed window must restore the donating fast path"
        np.testing.assert_array_equal(np.asarray(out2)[5], rows[0])
        assert rc.witnesses == []
        assert rc.order_violations == []


# ---------------------------------------------------------------------------
# the gate entrypoint (cheap pieces only; the two-seed run is lint.sh's)
# ---------------------------------------------------------------------------

class TestCheckRacesPlumbing:
    def test_static_race_findings_empty_on_shipped_tree(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_races", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "hack", "check_races.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.static_race_findings() == []
