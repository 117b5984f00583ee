"""koordtrace + histogram metrics: the observability layer's contracts.

Four layers:
  * Histogram — exposition validity (TYPE histogram, cumulative `_bucket`
    series ending in `le="+Inf"`, `_sum`/`_count` consistency, label
    escaping through the shared `_escape_label`);
  * Tracer — nesting, thread isolation, ring wraparound, JSONL schema;
  * instrumentation — one synthetic scheduling cycle produces the
    {cycle -> snapshot, encode, kernel, bind} span tree with nonzero
    monotonic durations, and the compile-cache counters distinguish the
    first compile from steady state;
  * surfaces — ObsServer/KoordletServer routing and the replay CLI's
    golden-fixture exit-code contract (mirrored by hack/lint.sh).
"""

import json
import re
import subprocess
import sys
import threading
from pathlib import Path

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
from koordinator_tpu.koordlet.metrics import Histogram, Registry
from koordinator_tpu.obs import TRACE_SCHEMA_VERSION, Tracer, validate_record
from koordinator_tpu.obs.server import ObsServer
from koordinator_tpu.scheduler import metrics as scheduler_metrics
from koordinator_tpu.scheduler.cycle import Scheduler

REPO_ROOT = Path(__file__).resolve().parent.parent
GOLDEN = REPO_ROOT / "tests" / "fixtures" / "trace_golden.jsonl"
GIB = 1024**3
NOW = 1_000_000.0


# ---------------------------------------------------------------------------
# histogram exposition
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_exposition_shape(self):
        reg = Registry()
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.expose()
        assert "# HELP lat_seconds latency" in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text
        assert "lat_seconds_sum 5.55" in text

    def test_buckets_cumulative_and_consistent(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(0.01, 0.1, 1.0, 10.0))
        for v in (0.005, 0.005, 0.05, 0.5, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        text = reg.expose()
        counts = [float(m.group(2)) for m in re.finditer(
            r'h_bucket\{le="([^"]+)"\} (\S+)', text)]
        # cumulative: each bucket includes everything below it
        assert counts == sorted(counts)
        assert counts == [2.0, 3.0, 6.0, 7.0, 8.0]
        # +Inf bucket == _count, and _sum matches the observations
        assert counts[-1] == h.count() == 8.0
        assert h.sum() == pytest.approx(56.56)
        # boundary semantics: le is inclusive (value == bound lands in it)
        h2 = Histogram("h2", buckets=(1.0,))
        h2.observe(1.0)
        _, cum, _, _ = h2.snapshot()
        assert cum == [1.0]

    def test_label_escaping_interplay(self):
        """Histogram series carry their labels through the same
        `_escape_label` path as every other kind — including on the
        synthesized `le` label lines."""
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(0.5, pod='a"b\\c\nd')
        text = reg.expose()
        escaped = 'pod="a\\"b\\\\c\\nd"'
        bucket_lines = [ln for ln in text.splitlines() if "_bucket" in ln]
        assert len(bucket_lines) == 2  # le="1" and le="+Inf"
        for line in bucket_lines:
            assert escaped in line and 'le="' in line
        assert f"h_sum{{{escaped}}} 0.5" in text
        assert f"h_count{{{escaped}}} 1" in text

    def test_per_labelset_series_are_independent(self):
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(0.5, node="a")
        h.observe(0.5, node="a")
        h.observe(2.0, node="b")
        assert h.count(node="a") == 2.0
        assert h.count(node="b") == 1.0
        assert h.count(node="nope") == 0.0

    def test_large_counts_expose_full_precision(self):
        """%g would round counters past ~1e6 to 6 significant digits,
        making small increments invisible between scrapes."""
        reg = Registry()
        c = reg.counter("big_total")
        c.inc(1_234_567)
        h = reg.histogram("h", buckets=(1.0,))
        for _ in range(3):
            h.observe(0.5)
        text = reg.expose()
        assert "big_total 1234567" in text
        assert "e+" not in text
        c.inc()
        assert "big_total 1234568" in reg.expose()

    def test_scalar_api_rebound_not_silent(self):
        """Histogram inherits the scalar _Metric surface; clear()/get()
        must act on the real series storage and set-style mutation must
        refuse loudly instead of writing to the unused scalar dict."""
        reg = Registry()
        h = reg.histogram("h", buckets=(1.0,))
        h.observe(0.5, node="a")
        assert h.get(node="a") == 1.0
        assert h.get(node="zzz") is None
        h.clear(node="a")
        assert h.get(node="a") is None
        assert "h_bucket" not in reg.expose()
        with pytest.raises(TypeError):
            h._set({}, 1.0)
        with pytest.raises(TypeError):
            h._add({}, 1.0)

    def test_non_finite_samples_do_not_poison_exposition(self):
        """One inf/NaN sample must degrade to Prometheus' non-finite
        spellings on its own line, not crash every future scrape."""
        reg = Registry()
        g = reg.gauge("ratio")
        g.set(float("inf"), node="a")
        g.set(float("-inf"), node="b")
        g.set(float("nan"), node="c")
        g.set(0.5, node="d")
        text = reg.expose()
        assert 'ratio{node="a"} +Inf' in text
        assert 'ratio{node="b"} -Inf' in text
        assert 'ratio{node="c"} NaN' in text
        assert 'ratio{node="d"} 0.5' in text

    def test_kind_conflict_rejected(self):
        reg = Registry()
        reg.histogram("h")
        with pytest.raises(ValueError):
            reg.counter("h")
        # same-kind, same-bucket re-registration returns the existing
        # instance; a DIFFERENT bucket spec must refuse rather than
        # silently hand back mismatched buckets
        assert reg.histogram("h") is reg.get("h")
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(30.0, 60.0, 300.0))
        # an explicit +Inf bound is stripped (the +Inf series is
        # synthesized); all-non-finite buckets refuse
        h2 = reg.histogram("h2", buckets=(1.0, float("inf")))
        h2.observe(0.5)
        assert reg.expose().count('h2_bucket{le="+Inf"}') == 1
        with pytest.raises(ValueError):
            reg.histogram("h3", buckets=(float("inf"),))


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_and_ids(self):
        t = Tracer()
        with t.span("cycle") as root:
            with t.span("kernel", compiled="1") as k:
                pass
            with t.span("bind"):
                with t.span("reserve"):
                    pass
        roots = t.roots()
        assert [r.name for r in roots] == ["cycle"]
        r = roots[0]
        assert [c.name for c in r.children] == ["kernel", "bind"]
        assert r.children[1].children[0].name == "reserve"
        # ids: children share the root's trace id and link to their parent
        for span in r.walk():
            assert span.trace_id == r.span_id
            if span is not r:
                assert span.parent_id is not None
        assert k.attributes == {"compiled": "1"}
        assert root.find("reserve") is not None

    def test_durations_monotonic_nonzero(self):
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                sum(range(1000))
        root = t.roots()[0]
        assert root.duration_seconds > 0
        assert root.children[0].duration_seconds > 0
        # parent covers the child
        assert root.duration_seconds >= root.children[0].duration_seconds

    def test_ring_wraparound(self):
        t = Tracer(capacity=4)
        for i in range(10):
            with t.span(f"r{i}"):
                pass
        assert len(t) == 4
        assert [r.name for r in t.roots()] == ["r6", "r7", "r8", "r9"]
        assert t.seq == 10  # total committed survives the wraparound
        assert [r.name for r in t.roots(limit=2)] == ["r8", "r9"]

    def test_per_trace_span_budget(self):
        """A 10k-pod cycle must not pin 30k spans per retained root:
        per-item spans (depth >= 2) beyond the per-trace budget are timed
        but dropped, the root says how many went missing — and the
        depth-1 stage skeleton survives even after the budget burns."""
        t = Tracer(max_spans_per_trace=3)
        with t.span("root"):
            with t.span("prepass"):
                for i in range(10):
                    with t.span(f"item{i}") as sp:
                        pass
            # stage spans opened AFTER the budget is exhausted still land
            with t.span("kernel"):
                pass
        assert sp.duration_seconds > 0  # dropped spans still time
        root = t.roots()[0]
        assert [c.name for c in root.children] == ["prepass", "kernel"]
        # skeleton spans (root + depth-1) don't consume the budget: with
        # max=3, exactly 3 per-item spans are retained and 7 dropped
        assert [c.name for c in root.children[0].children] == [
            "item0", "item1", "item2"]
        assert root.attributes["dropped_spans"] == "7"
        # the budget resets per trace
        with t.span("root2"):
            with t.span("stage"):
                with t.span("kept"):
                    pass
        root2 = t.roots()[1]
        assert root2.find("kept") is not None
        assert "dropped_spans" not in root2.attributes

    def test_exception_marks_span_and_propagates(self):
        t = Tracer()
        with pytest.raises(ValueError):
            with t.span("cycle"):
                with t.span("kernel"):
                    raise ValueError("boom")
        root = t.roots()[0]
        assert root.attributes["error"] == "ValueError"
        assert root.children[0].attributes["error"] == "ValueError"
        # the tracer stack unwound: the next span is a fresh root
        with t.span("next"):
            pass
        assert [r.name for r in t.roots()] == ["cycle", "next"]

    def test_thread_isolation(self):
        """Each thread traces its own tree; concurrent spans never nest
        across threads and every root lands in the shared ring."""
        t = Tracer()
        barrier = threading.Barrier(4)

        def work(i):
            with t.span(f"thread-{i}"):
                barrier.wait(timeout=10)  # all spans open simultaneously
                with t.span("child"):
                    pass

        threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        roots = t.roots()
        assert sorted(r.name for r in roots) == [
            f"thread-{i}" for i in range(4)]
        for r in roots:
            assert [c.name for c in r.children] == ["child"]

    def test_export_jsonl_schema(self):
        t = Tracer()
        with t.span("cycle", mode="test"):
            with t.span("kernel"):
                pass
        lines = t.export_jsonl().strip().splitlines()
        assert len(lines) == 2
        for line in lines:
            rec = json.loads(line)
            assert validate_record(rec) == []
            assert rec["v"] == TRACE_SCHEMA_VERSION
        assert t.export_jsonl(limit=5) == t.export_jsonl()

    def test_validate_record_rejects_drift(self):
        good = json.loads(
            '{"v": 1, "trace": 1, "span": 1, "parent": null, "name": "x", '
            '"start_unix": 1.0, "start_mono": 1.0, "duration_ms": 1.0, '
            '"attrs": {}}')
        assert validate_record(good) == []
        for mutation in (
            {"v": 99},
            {"name": ""},
            {"duration_ms": "fast"},
            {"parent": "root"},
            {"parent": True},
            {"trace": True},
            {"attrs": {"k": 1}},
            {"start_mono": -1.0},
        ):
            assert validate_record({**good, **mutation}), mutation
        assert validate_record([1, 2, 3])


# ---------------------------------------------------------------------------
# cycle instrumentation
# ---------------------------------------------------------------------------

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


def pend_pod(store, name):
    pod = Pod(
        meta=ObjectMeta(name=name, creation_timestamp=NOW),
        spec=PodSpec(priority=9500,
                     requests=ResourceList.of(cpu=1000, memory=GIB)),
    )
    store.add(KIND_POD, pod)
    return pod


def _counter(metric):
    return metric.get() or 0.0


class TestCycleInstrumentation:
    def test_span_tree_and_compile_cache(self):
        store = make_store()
        sched = Scheduler(store)
        for i in range(4):
            pend_pod(store, f"p{i}")

        hits0 = _counter(scheduler_metrics.COMPILE_CACHE_HITS)
        misses0 = _counter(scheduler_metrics.COMPILE_CACHE_MISSES)
        cycles0 = scheduler_metrics.CYCLE_SECONDS.count()
        result = sched.run_cycle(now=NOW)
        assert len(result.bound) == 4

        # --- the acceptance span tree: cycle -> snapshot/encode/kernel/bind
        root = sched.tracer.roots()[-1]
        assert root.name == "cycle"
        child_names = {c.name for c in root.children}
        assert {"snapshot", "encode", "kernel", "bind"} <= child_names
        for name in ("cycle", "snapshot", "encode", "kernel", "bind"):
            span = root.find(name)
            assert span.duration_seconds > 0, name
        # first cycle is a cold compile: the step cache missed, the kernel
        # span says so, and a `compile` span recorded the build
        assert root.find("kernel").attributes["compiled"] == "1"
        assert root.find("compile") is not None
        assert _counter(scheduler_metrics.COMPILE_CACHE_MISSES) == misses0 + 1
        assert _counter(scheduler_metrics.COMPILE_CACHE_HITS) == hits0
        # per-binding Reserve/PreBind time rides the bind span as summed
        # attributes, not as per-pod spans
        assert root.find_all("bind_pod") == []
        bind = root.find("bind")
        assert bind.attributes["bound"] == "4"
        assert float(bind.attributes["reserve_ms"]) > 0
        assert float(bind.attributes["prebind_ms"]) > 0
        assert "vetoes" not in bind.attributes
        # duration consolidated through the root span, and the latency
        # histogram observed the cycle
        assert result.duration_seconds == root.duration_seconds > 0
        assert scheduler_metrics.CYCLE_SECONDS.count() == cycles0 + 1
        assert scheduler_metrics.KERNEL_SECONDS.count() >= 1

        # --- steady state: same shape signature -> cache hit, no recompile
        for i in range(4):
            pend_pod(store, f"q{i}")
        result2 = sched.run_cycle(now=NOW + 1)
        assert len(result2.bound) == 4
        assert _counter(scheduler_metrics.COMPILE_CACHE_MISSES) == misses0 + 1
        assert _counter(scheduler_metrics.COMPILE_CACHE_HITS) > hits0
        root2 = sched.tracer.roots()[-1]
        assert root2.find("kernel").attributes["compiled"] == "0"
        assert root2.find("compile") is None

    @pytest.mark.parametrize("waves", [1, 4], ids=["serial", "chained"])
    def test_cycle_skeleton_spans(self, waves):
        """The root's own work sits under named depth-1 spans: queue,
        plan and status; upload and sync under a kernel; a preempt span
        where a no-fit pod evicts a lower-priority victim (under
        wave_replay on the chained path); no per-pod bind spans."""
        store = make_store(num_nodes=1)
        for i in range(4):
            running = Pod(
                meta=ObjectMeta(name=f"low{i}", uid=f"low{i}",
                                creation_timestamp=NOW),
                spec=PodSpec(priority=100, node_name="node-0",
                             requests=ResourceList.of(cpu=4000,
                                                      memory=GIB)))
            running.phase = "Running"
            store.add(KIND_POD, running)
        store.add(KIND_POD, Pod(
            meta=ObjectMeta(name="vip", uid="vip", creation_timestamp=NOW),
            spec=PodSpec(priority=9000,
                         requests=ResourceList.of(cpu=4000, memory=GIB))))
        sched = Scheduler(store, waves=waves)
        result = sched.run_cycle(now=NOW)
        assert [b.pod_key for b in result.bound] == ["default/vip"]
        assert len(result.preempted_victims) == 1

        root = sched.tracer.roots()[-1]
        depth1 = [c.name for c in root.children]
        assert {"queue", "prefilter", "plan", "status"} <= set(depth1)
        assert root.find("queue").attributes["pods"] == "1"
        assert root.find("plan").attributes["waves"] == str(waves)
        kernels = root.find_all("kernel")
        assert any(k.find("upload") is not None for k in kernels)
        assert any(k.find("sync") is not None for k in kernels)
        up = root.find("upload")
        assert int(up.attributes["bytes"]) > 0
        assert int(up.attributes["fields_put"]) > 0
        evicting = [p for p in root.find_all("preempt")
                    if p.attributes["victims"] != "0"]
        assert len(evicting) == 1
        assert evicting[0].attributes["pods"] == "1"
        assert int(evicting[0].attributes["nodes_scanned"]) >= 1
        if waves > 1:
            replays = root.find_all("wave_replay")
            assert any(evicting[0] in list(w.walk()) for w in replays)
            assert "preempt" not in depth1
        else:
            assert evicting[0] in root.children
        assert root.find_all("bind_pod") == []
        assert root.find_all("reserve") == []
        assert root.find_all("prebind") == []

    def test_skeleton_annotations_on_the_profiler_clock(self, tmp_path):
        """A jax.profiler capture of one cycle holds the root and its
        depth-1 spans as host-plane annotations, at offsets from an
        anchor that match the spans' start_mono within 1 ms; deeper
        spans are not annotated."""
        import glob
        import time

        import jax
        from jax.profiler import ProfileData

        store = make_store()
        sched = Scheduler(store)
        pend_pod(store, "warm")
        sched.run_cycle(now=NOW)  # compile outside the capture
        for i in range(3):
            pend_pod(store, f"p{i}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("test_obs_anchor"):
                t_anchor = time.perf_counter()
            sched.run_cycle(now=NOW + 1)
        finally:
            jax.profiler.stop_trace()
        root = sched.tracer.roots()[-1]
        files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        pd = ProfileData.from_file(files[0])
        starts = {}
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    starts.setdefault(ev.name, []).append(int(ev.start_ns))
        (a_ns,) = starts["test_obs_anchor"]
        skeleton = [root] + root.children
        assert {"cycle", "queue", "kernel", "bind", "status"} <= {
            s.name for s in skeleton}
        for sp in skeleton:
            want = sp.start_mono - t_anchor
            got = [(s - a_ns) / 1e9 for s in starts.get(sp.name, [])]
            assert got, sp.name
            assert min(abs(g - want) for g in got) < 1e-3, sp.name
        assert root.find("upload") is not None
        assert "upload" not in starts

    def test_empty_cycle_still_stamps_duration(self):
        """The old three-site duration assignment shipped 0.0 whenever a
        return path forgot the stamp; the root span makes that
        structurally impossible — even a no-pending cycle reports how
        long the queue scan took."""
        sched = Scheduler(make_store(num_nodes=1))
        result = sched.run_cycle(now=NOW)
        assert result.bound == []
        assert result.duration_seconds > 0
        root = sched.tracer.roots()[-1]
        assert result.duration_seconds == root.duration_seconds

    def test_traces_jsonl_round_trips_through_validator(self):
        sched = Scheduler(make_store())
        pend_pod(sched.store, "p0")
        sched.run_cycle(now=NOW)
        for line in sched.tracer.export_jsonl().strip().splitlines():
            assert validate_record(json.loads(line)) == []


# ---------------------------------------------------------------------------
# component metrics
# ---------------------------------------------------------------------------

def test_descheduler_cycle_metrics():
    from koordinator_tpu.descheduler import metrics as dmetrics
    from koordinator_tpu.descheduler.descheduler import Descheduler

    before = dmetrics.CYCLE_SECONDS.count()
    Descheduler(make_store()).run_once(now=NOW)
    assert dmetrics.CYCLE_SECONDS.count() == before + 1
    # standby replicas observe nothing
    class _Standby:
        def tick(self, now):
            return False

    Descheduler(make_store(), elector=_Standby()).run_once(now=NOW)
    assert dmetrics.CYCLE_SECONDS.count() == before + 1


def test_registries_expose_histograms():
    from koordinator_tpu.descheduler import metrics as dmetrics
    from koordinator_tpu.koordlet import metrics as kmetrics

    for registry, name in (
        (scheduler_metrics.REGISTRY, "koord_scheduler_cycle_seconds"),
        (dmetrics.REGISTRY, "koord_descheduler_cycle_seconds"),
        (kmetrics.REGISTRY, "koordlet_qosmanager_cycle_seconds"),
    ):
        assert f"# TYPE {name} histogram" in registry.expose()


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------

class TestObsServer:
    def _tracer(self):
        t = Tracer()
        with t.span("cycle"):
            with t.span("kernel"):
                pass
        return t

    def test_routes(self):
        reg = Registry()
        reg.histogram("x_seconds").observe(0.5)
        srv = ObsServer(reg, self._tracer())
        status, ctype, body = srv.handle("/metrics")
        assert status == 200 and "version=0.0.4" in ctype
        assert 'x_seconds_bucket{le="+Inf"} 1' in body
        status, ctype, body = srv.handle("/traces")
        assert status == 200
        lines = body.strip().splitlines()
        assert len(lines) == 2
        assert all(validate_record(json.loads(ln)) == [] for ln in lines)
        assert srv.handle("/healthz")[0] == 200
        assert srv.handle("/nope")[0] == 404
        assert srv.handle("/traces", {"limit": "x"})[0] == 400

    def test_traces_limit(self):
        t = Tracer()
        for i in range(3):
            with t.span(f"c{i}"):
                pass
        srv = ObsServer(tracer=t)
        _, _, body = srv.handle("/traces", {"limit": "1"})
        assert [json.loads(ln)["name"]
                for ln in body.strip().splitlines()] == ["c2"]
        # explicit limit=0 means zero roots, not "unset"
        assert srv.handle("/traces", {"limit": "0"})[2] == ""
        assert len(srv.handle("/traces")[2].strip().splitlines()) == 3

    def test_disabled_surfaces_404(self):
        srv = ObsServer()  # neither registry nor tracer
        assert srv.handle("/metrics")[0] == 404
        assert srv.handle("/traces")[0] == 404

    def test_koordlet_server_exposes_traces(self):
        from koordinator_tpu.koordlet.audit import Auditor
        from koordinator_tpu.koordlet.server import KoordletServer

        reg = Registry()
        reg.counter("c_total").inc()
        srv = KoordletServer(Auditor(), metrics_registry=reg,
                             tracer=self._tracer())
        assert "c_total 1" in srv.handle("/metrics")[2]
        status, _, body = srv.handle("/traces")
        assert status == 200 and '"name": "cycle"' in body
        # without a tracer the route stays dark (pre-existing behavior)
        assert KoordletServer(Auditor()).handle("/traces")[0] == 404

    def test_live_server(self):
        import urllib.request

        srv = ObsServer(Registry(), self._tracer())
        server, _thread = srv.serve(port=0)
        try:
            port = server.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/traces") as resp:
                assert resp.status == 200
                assert b'"name": "cycle"' in resp.read()
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# replay CLI (the hack/lint.sh golden-fixture contract)
# ---------------------------------------------------------------------------

def _run_cli(*args, stdin=None):
    return subprocess.run(
        [sys.executable, "-m", "koordinator_tpu.obs", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, input=stdin,
        timeout=120)


class TestReplayCLI:
    def test_golden_fixture_renders(self):
        proc = _run_cli(str(GOLDEN))
        assert proc.returncode == 0, proc.stderr
        assert "cycle" in proc.stdout and "█" in proc.stdout
        # nesting is visible: bind_pod indents under bind
        assert re.search(r"^\s+bind\b", proc.stdout, re.M)
        assert re.search(r"^\s+bind_pod\b", proc.stdout, re.M)

    def test_stdin_input(self):
        proc = _run_cli("-", stdin=GOLDEN.read_text())
        assert proc.returncode == 0, proc.stderr

    def test_schema_drift_fails(self, tmp_path):
        lines = GOLDEN.read_text().strip().splitlines()
        rec = json.loads(lines[0])
        del rec["duration_ms"]
        bad = tmp_path / "drift.jsonl"
        bad.write_text("\n".join([json.dumps(rec)] + lines[1:]) + "\n")
        proc = _run_cli(str(bad))
        assert proc.returncode == 1
        assert "duration_ms" in proc.stderr

    def test_dangling_parent_fails(self, tmp_path):
        rec = json.loads(GOLDEN.read_text().splitlines()[1])
        rec["parent"] = 9999
        orphan = tmp_path / "orphan.jsonl"
        orphan.write_text(
            GOLDEN.read_text().splitlines()[0] + "\n" + json.dumps(rec) + "\n")
        proc = _run_cli(str(orphan))
        assert proc.returncode == 1
        assert "dangling parent" in proc.stderr

    def test_missing_file_is_usage_error(self):
        assert _run_cli("no/such/trace.jsonl").returncode == 2
