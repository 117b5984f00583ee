"""koordexplain (PR 5): on-device decision attribution, the per-pod
explain surfaces and the cycle flight recorder.

The acceptance gates live here: formatter-over-kernel-counts must match
the legacy host-numpy diagnose_unbound string-for-string on a churn
workload (serial AND fused), attribution must not perturb a single
decision, and the flight-recorder bundle must validate against its
schema — plus the HTTP/CLI surfaces and the new metrics."""

import json

import numpy as np
import pytest

from koordinator_tpu.client.store import KIND_POD
from koordinator_tpu.obs.flight import (
    FLIGHT_SCHEMA_VERSION,
    FlightRecorder,
    load_bundle,
    validate_cycle_record,
    validate_header,
)
from koordinator_tpu.obs.server import ObsServer
from koordinator_tpu.scheduler import metrics as scheduler_metrics
from koordinator_tpu.scheduler.cycle import (
    CyclePipeline,
    Scheduler,
    cycle_deadline_from_env,
    explain_from_env,
)
from koordinator_tpu.scheduler.pipeline_parity import (
    apply_round_delta,
    build_store_from_state,
    run_explain_parity,
    run_fused_wave_parity,
    run_pipeline_parity,
)
from koordinator_tpu.testing import synth_full_cluster

NOW = 1_000_000.0


def make_world(nodes=16, pods=50, seed=5):
    _cluster, state = synth_full_cluster(
        nodes, pods, seed=seed, num_quotas=3, num_gangs=4,
        topology_fraction=0.5, lsr_fraction=0.2)
    return state, build_store_from_state(state)


# ---------------------------------------------------------------------------
# acceptance gates: kernel counts vs legacy diagnosis, byte-for-byte
# ---------------------------------------------------------------------------


def test_explain_parity_serial_churn():
    """Formatter-over-kernel-counts == legacy host-numpy diagnose_unbound
    string-for-string on a churn workload (the tier-1 pin)."""
    report = run_explain_parity()
    assert report["ok"], report["mismatches"]
    assert report["conditions_checked"] > 0


def test_explain_parity_fused_waves():
    report = run_explain_parity(waves=4, rounds=2)
    assert report["ok"], report["mismatches"]


def test_pipeline_parity_with_explain_enabled():
    """The PR 3 gate must stay byte-identical with explain=counts on."""
    report = run_pipeline_parity(rounds=2, explain="counts")
    assert report["ok"], report["mismatches"]


def test_fused_wave_parity_with_explain_enabled():
    """The PR 4 gate must stay byte-identical with explain=counts on."""
    report = run_fused_wave_parity(4, explain="counts")
    assert report["ok"], report["mismatches"]


# ---------------------------------------------------------------------------
# attribution content: /explain records, terms, metrics
# ---------------------------------------------------------------------------


def _run_rounds(sched, store, state, rounds=3, arrivals=8):
    results = []
    for r in range(rounds):
        if r:
            apply_round_delta(store, r, state.now, arrivals)
        results.append(sched.run_cycle(now=state.now + 2 * r))
    return results


def test_explain_index_and_full_terms():
    state, store = make_world()
    sched = Scheduler(store, waves=1, explain="full")
    results = _run_rounds(sched, store, state)
    assert any(r.bound for r in results)
    bound_recs = [v for v in sched.explain_index.values()
                  if v["verdict"] == "bound"]
    assert bound_recs, "bound pods must be attributed"
    with_terms = [v for v in bound_recs if "terms" in v]
    assert with_terms, "full level must attach score terms"
    terms = with_terms[0]["terms"]
    assert set(terms) == {"LoadAware", "NodeNUMAResource", "Preferred",
                          "best_score", "runner_up"}
    # the plugin terms must reconstruct the winning score exactly
    assert terms["best_score"] == pytest.approx(
        terms["LoadAware"] + terms["NodeNUMAResource"] + terms["Preferred"])
    assert with_terms[0]["margin"] == pytest.approx(
        terms["best_score"] - terms["runner_up"])
    # per-pod lookup API (the /explain provider)
    key = next(k for k, v in sched.explain_index.items()
               if v["verdict"] == "bound")
    rec = sched.explain_record(key)
    assert rec is not None and rec["node"]
    assert sched.explain_record("no/such-pod") is None


def test_unschedulable_attribution_and_rejection_metric():
    state, store = make_world(nodes=6, pods=40, seed=9)
    before = {}
    sched = Scheduler(store, waves=1, explain="counts")
    m = scheduler_metrics.FILTER_REJECTIONS
    before = {lbl["stage"]: v for lbl, v in m.samples()}
    results = _run_rounds(sched, store, state)
    assert any(r.failed or r.rejected for r in results), \
        "fixture must leave pods unbound"
    unbound = [v for v in sched.explain_index.values()
               if v["verdict"] == "unschedulable"]
    assert unbound
    with_stages = [v for v in unbound if v.get("stages")]
    assert with_stages, "kernel counts must back unschedulable records"
    assert any(v.get("message", "").startswith("0/")
               or "PreFilter" in v.get("message", "")
               for v in with_stages)
    after = {lbl["stage"]: v for lbl, v in m.samples()}
    grew = {s for s in after
            if after[s] > before.get(s, 0.0)}
    assert grew, "filter_rejections_total must grow for some stage"


def test_explain_off_records_nothing():
    state, store = make_world(nodes=6, pods=20, seed=2)
    sched = Scheduler(store, waves=1, explain="off")
    _run_rounds(sched, store, state, rounds=2)
    assert sched.explain_index == {}
    assert sched.explain_record("anything") is None


def test_deferred_diagnose_metrics():
    gauge = scheduler_metrics.DIAGNOSE_DEFERRED_DEPTH
    total = scheduler_metrics.DIAGNOSE_DEFERRED_TOTAL
    t0 = total.get() or 0.0
    state, store = make_world(nodes=6, pods=40, seed=9)
    sched = Scheduler(store, waves=1, explain="off")
    pipeline = CyclePipeline(sched, enabled=True)
    _run_rounds(sched, store, state, rounds=2)
    pipeline.flush()
    assert (total.get() or 0.0) > t0, "pipeline must defer diagnose items"
    assert gauge.get() == 0.0, "flush must drain the deferred queue"


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["deferred", "direct"])
def test_diagnose_memo_metrics(pipelined):
    """The memo's hit and miss counters and each diagnose span's
    computed= agree with the pods the flushes diagnosed: one miss per
    node-level vector computed, one hit per other diagnosed pod. A
    saturated world leaves every unbound pod to the host diagnosis."""
    hits = scheduler_metrics.DIAGNOSE_MEMO_HITS
    misses = scheduler_metrics.DIAGNOSE_MEMO_MISSES
    h0, m0 = hits.get() or 0.0, misses.get() or 0.0
    state, store = make_world(nodes=6, pods=200, seed=9)
    sched = Scheduler(store, waves=1, explain="off")
    pipeline = CyclePipeline(sched, enabled=pipelined)
    _run_rounds(sched, store, state, rounds=2)
    pipeline.flush()
    spans = []

    def walk(sp):
        if sp.name == "diagnose":
            spans.append(sp.attributes)
        for child in sp.children:
            walk(child)

    for root in sched.tracer.roots():
        walk(root)
    assert spans and all(("deferred" in a) == pipelined for a in spans)
    pods = sum(int(a["pods"]) for a in spans)
    computed = sum(int(a["computed"]) for a in spans)
    assert all(int(a["computed"]) <= int(a["pods"]) for a in spans)
    assert (misses.get() or 0.0) - m0 == computed > 0
    assert (hits.get() or 0.0) - h0 == pods - computed > 0


# ---------------------------------------------------------------------------
# env plumbing
# ---------------------------------------------------------------------------


def test_explain_from_env(monkeypatch):
    for raw, want in [("off", None), ("", None), ("0", None),
                      ("counts", "counts"), ("on", "counts"),
                      ("full", "full"), ("bogus", None)]:
        monkeypatch.setenv("KOORD_TPU_EXPLAIN", raw)
        assert explain_from_env() == want, raw
    monkeypatch.delenv("KOORD_TPU_EXPLAIN")
    assert explain_from_env() is None


def test_cycle_deadline_from_env(monkeypatch):
    monkeypatch.delenv("KOORD_TPU_CYCLE_DEADLINE_MS", raising=False)
    assert cycle_deadline_from_env() is None
    monkeypatch.setenv("KOORD_TPU_CYCLE_DEADLINE_MS", "250")
    assert cycle_deadline_from_env() == pytest.approx(0.25)
    monkeypatch.setenv("KOORD_TPU_CYCLE_DEADLINE_MS", "0")
    assert cycle_deadline_from_env() is None
    monkeypatch.setenv("KOORD_TPU_CYCLE_DEADLINE_MS", "nope")
    assert cycle_deadline_from_env() is None


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_and_dump_validates(tmp_path):
    fr = FlightRecorder(capacity=4, dump_dir=str(tmp_path))
    for seq in range(7):
        fr.record_cycle({
            "v": FLIGHT_SCHEMA_VERSION, "kind": "cycle", "seq": seq,
            "ts": float(seq), "duration_ms": 1.0, "waves": 1,
            "bound": [], "failed": [], "rejected": [], "preempted": [],
            "metrics": {}, "spans": [],
        })
    assert len(fr) == 4
    body = fr.dump("unit")
    header, records, errors = load_bundle(body.splitlines())
    assert not errors, errors
    assert header["reason"] == "unit" and header["cycles"] == 4
    assert [r["seq"] for r in records] == [3, 4, 5, 6]
    assert fr.dumps == 1
    assert fr.last_dump_path and fr.last_dump_path.startswith(str(tmp_path))
    with open(fr.last_dump_path) as f:
        assert f.read() == body


def test_flight_schema_rejects_drift():
    assert validate_header({"v": 99}), "bad header must fail"
    good = {"v": FLIGHT_SCHEMA_VERSION, "kind": "cycle", "seq": 1,
            "ts": 0.0, "duration_ms": 1.0, "waves": 1, "bound": [],
            "failed": [], "rejected": [], "preempted": [], "metrics": {},
            "spans": []}
    assert validate_cycle_record(good) == []
    for mutate in [
        {"waves": -1}, {"bound": [{"pod": 1}]}, {"preempted": [1]},
        {"metrics": {"x": "y"}}, {"spans": [{"bogus": True}]},
        {"failed": [{"pod": "a", "stages": {"s": "notint"}}]},
    ]:
        assert validate_cycle_record({**good, **mutate}), mutate


def test_scheduler_cycles_land_in_flight_ring():
    state, store = make_world(nodes=6, pods=20, seed=2)
    sched = Scheduler(store, waves=1, explain="counts")
    _run_rounds(sched, store, state, rounds=2)
    assert len(sched.flight) == 2
    body = sched.flight.dump("unit")
    header, records, errors = load_bundle(body.splitlines())
    assert not errors, errors
    rec = records[0]
    assert rec["bound"] and {"pod", "node"} <= set(rec["bound"][0])
    assert any(s["name"] == "cycle" for s in rec["spans"])
    assert rec["metrics"]["pods_bound"] == len(rec["bound"])


def test_cycle_exception_triggers_dump(monkeypatch):
    state, store = make_world(nodes=6, pods=10, seed=2)
    sched = Scheduler(store, waves=1, explain="off")
    sched.run_cycle(now=state.now)
    dumps_before = sched.flight.dumps

    def boom(*a, **k):
        raise RuntimeError("kaboom")

    monkeypatch.setattr(sched, "_run_cycle_traced", boom)
    with pytest.raises(RuntimeError, match="kaboom"):
        sched.run_cycle(now=state.now + 2)
    assert sched.flight.dumps == dumps_before + 1
    records = sched.flight.snapshot()
    assert records[-1]["error"].startswith("RuntimeError")
    # the wreck record still validates against the bundle schema
    _h, recs, errors = load_bundle(
        sched.flight.dump("post_mortem").splitlines())
    assert not errors, errors


def test_deadline_overrun_triggers_dump():
    state, store = make_world(nodes=6, pods=10, seed=2)
    sched = Scheduler(store, waves=1, explain="off")
    sched.cycle_deadline_seconds = 0.0  # every real cycle overruns
    before = sched.flight.dumps
    sched.run_cycle(now=state.now)
    assert sched.flight.dumps == before + 1


def test_golden_fixture_validates():
    with open("tests/fixtures/flight_golden.jsonl") as f:
        header, records, errors = load_bundle(f.readlines())
    assert not errors, errors
    assert header["cycles"] == len(records) > 0


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------


def test_obs_server_explain_and_flight_routes():
    state, store = make_world(nodes=6, pods=20, seed=2)
    sched = Scheduler(store, waves=1, explain="full")
    _run_rounds(sched, store, state, rounds=2)
    srv = ObsServer(metrics_registry=scheduler_metrics.REGISTRY,
                    tracer=sched.tracer,
                    health_provider=sched.health_snapshot,
                    explain_provider=sched.explain_record,
                    flight=sched.flight)
    # healthz: liveness payload, not a bare ok
    status, ctype, body = srv.handle("/healthz")
    assert status == 200 and ctype == "application/json"
    health = json.loads(body)
    assert health["cycles"] == 2
    assert health["last_cycle_age_seconds"] >= 0.0
    assert health["last_cycle_waves"] == 1
    # explain: found / not found / missing param
    key = next(k for k, v in sched.explain_index.items()
               if v["verdict"] == "bound")
    status, ctype, body = srv.handle("/explain", {"pod": key})
    assert status == 200 and json.loads(body)["node"]
    assert srv.handle("/explain", {"pod": "no/such"})[0] == 404
    assert srv.handle("/explain")[0] == 400
    # flight recorder: GET status, POST dumps
    status, _ctype, body = srv.handle("/debug/flightrecorder")
    assert status == 200 and json.loads(body)["cycles"] == 2
    status, ctype, body = srv.handle("/debug/flightrecorder",
                                     method="POST")
    assert status == 200 and ctype == "application/x-ndjson"
    _h, recs, errors = load_bundle(body.splitlines())
    assert not errors and len(recs) == 2
    # metrics exposition carries the new series
    body = srv.handle("/metrics")[2]
    assert "koord_flight_recorder_dumps_total" in body
    assert "koord_scheduler_diagnose_deferred_depth" in body


def test_obs_server_healthz_default_unchanged():
    srv = ObsServer()
    assert srv.handle("/healthz") == (200, "text/plain", "ok")
    # no providers: the explain/flight routes stay 404
    assert srv.handle("/explain", {"pod": "x"})[0] == 404
    assert srv.handle("/debug/flightrecorder")[0] == 404


def test_obs_server_post_over_http():
    state, store = make_world(nodes=6, pods=10, seed=2)
    sched = Scheduler(store, waves=1, explain="counts")
    sched.run_cycle(now=state.now)
    srv = ObsServer(flight=sched.flight,
                    health_provider=sched.health_snapshot)
    server, _thread = srv.serve(0)
    try:
        import urllib.request

        port = server.server_address[1]
        with urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/debug/flightrecorder",
                    method="POST"), timeout=10) as resp:
            lines = resp.read().decode().splitlines()
        _h, recs, errors = load_bundle(lines)
        assert not errors and len(recs) == 1
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_flight_and_explain(capsys):
    from koordinator_tpu.obs.__main__ import main

    assert main(["flight", "tests/fixtures/flight_golden.jsonl"]) == 0
    out = capsys.readouterr().out
    assert "flight bundle" in out and "cycle 1" in out
    with open("tests/fixtures/flight_golden.jsonl") as f:
        rec = json.loads(f.readlines()[1])
    pod = rec["bound"][0]["pod"]
    assert main(["explain", "tests/fixtures/flight_golden.jsonl", pod]) == 0
    out = capsys.readouterr().out
    assert "verdict: bound" in out
    assert main(["explain", "tests/fixtures/flight_golden.jsonl",
                 "no/such-pod"]) == 1
    assert main(["flight", "/does/not/exist.jsonl"]) == 2


def test_cli_flight_rejects_bad_bundle(tmp_path, capsys):
    from koordinator_tpu.obs.__main__ import main

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "header"}\n')
    assert main(["flight", str(bad)]) == 1
    assert "schema error" in capsys.readouterr().err
