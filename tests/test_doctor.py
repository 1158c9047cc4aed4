"""Query doctor (runtime/doctor.py): additive critical-path breakdowns,
the rule catalog on synthetic run records, byte-identical determinism
over exported artifacts (clean and under a supervised chaos cell),
schema-version tolerance for PR-9-era ledger/history lines, and the
per-tenant SLO tracker (runtime/service.SloTracker + blaze_slo_*
gauges)."""

import json
import os

import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import doctor, faults, history, monitor, service, \
    trace


@pytest.fixture(autouse=True)
def _clean_doctor_conf():
    saved = {k: getattr(conf, k) for k in (
        "trace_enabled", "trace_export_dir", "monitor_enabled",
        "doctor_enabled", "doctor_skew_ratio", "history_dir",
        "fault_injection_spec", "tenant_slo_spec", "slo_window_queries",
        "slo_burn_alert_rate", "enable_supervisor",
        "max_concurrent_tasks", "max_task_retries", "retry_backoff_ms")}
    trace.reset()
    monitor.reset()
    history.reset()
    service.reset_slo()
    yield
    for k, v in saved.items():
        setattr(conf, k, v)
    faults.install(None)
    trace.reset()
    monitor.reset()
    history.reset()
    service.reset_slo()


# ---------------------------------------------------------------------------
# synthetic run records / span records
# ---------------------------------------------------------------------------


def _rec(total=1000.0, admission=0.0, counters=None, stages=None,
         outcome="admitted", resil=None):
    return {"schema_version": trace.SCHEMA_VERSION, "query_id": "qD",
            "tenant_id": "t1", "admission_outcome": outcome,
            "admission_wait_ms": admission, "duration_ms": total,
            "stages": stages or [], "resilience_events": resil or {},
            "counters": counters or {}}


def _stage_span(sid, dur_ms):
    return {"type": "span", "kind": "stage", "stage_id": sid,
            "dur": int(dur_ms * 1e6), "attrs": {}}


def _task_span(sid, tid, dur_ms, attrs=None, error=None):
    rec = {"type": "span", "kind": "task_attempt", "stage_id": sid,
           "task_id": tid, "dur": int(dur_ms * 1e6),
           "attrs": attrs or {}}
    if error:
        rec["error"] = error
    return rec


# ---------------------------------------------------------------------------
# critical path
# ---------------------------------------------------------------------------


def test_breakdown_sums_to_wall_exactly():
    cp = doctor.compute_critical_path(_rec(
        total=1000.0, admission=500.0,
        counters={"serde_encode_ms": 100.0, "fused_dispatch_ms": 300.0,
                  "compile_ms": 50.0}))
    assert cp["total_ms"] == 1500.0
    assert abs(sum(cp["terms"].values()) - cp["total_ms"]) < 0.01
    # un-attributed execution time is NAMED, not hidden
    assert cp["terms"]["residual"] == pytest.approx(550.0, abs=0.01)
    assert cp["parallel_scale"] == 1.0
    assert cp["top_term"] == "admission_wait"


def test_concurrent_terms_scale_into_the_span():
    # 4 pool threads each billed ~700ms of compute inside a 1s query:
    # raw attribution oversums, so it is scaled to fit — and the
    # breakdown STILL sums to the measured wall time
    cp = doctor.compute_critical_path(_rec(
        total=1000.0,
        counters={"fused_dispatch_ms": 2800.0, "serde_decode_ms": 200.0}))
    assert cp["parallel_scale"] == pytest.approx(1000.0 / 3000.0, rel=1e-3)
    assert abs(sum(cp["terms"].values()) - cp["total_ms"]) < 0.01
    assert cp["terms"]["residual"] == 0.0
    assert cp["top_term"] == "fused_dispatch"


def test_longest_chain_per_stage_is_deterministic():
    recs = [_stage_span(0, 500.0),
            _task_span(0, "map[0:0]", 120.0),
            _task_span(0, "map[0:1]", 480.0),
            _task_span(0, "map[0:1]", 15.0)]  # retry attempt, same task
    cp = doctor.compute_critical_path(_rec(total=500.0), recs)
    (ch,) = cp["chains"]
    assert ch["task_id"] == "map[0:1]"
    assert ch["attempts"] == 2
    assert ch["ms"] == pytest.approx(495.0)


# ---------------------------------------------------------------------------
# rule catalog
# ---------------------------------------------------------------------------


def test_serde_bound_fires_on_dominant_serde():
    findings = doctor.diagnose(_rec(
        total=1000.0,
        counters={"serde_encode_ms": 400.0, "serde_decode_ms": 100.0,
                  "bytes_copied_serde": 1 << 20}))
    assert findings[0].code == "serde_bound"
    assert findings[0].score == pytest.approx(0.5)
    assert findings[0].evidence["bytes_copied_serde"] == 1 << 20


def test_small_clean_queries_stay_finding_free():
    # everything under the absolute floors: a fast healthy query must
    # never page the oncall
    findings = doctor.diagnose(_rec(
        total=90.0,
        counters={"serde_encode_ms": 30.0, "fused_dispatch_ms": 40.0,
                  "compile_ms": 10.0}))
    assert findings == []


def test_skew_vs_straggler_split_on_environmental_events():
    base = [_stage_span(1, 800.0),
            _task_span(1, "r[1:0]", 60.0),
            _task_span(1, "r[1:1]", 70.0),
            _task_span(1, "r[1:2]", 790.0)]
    rec = _rec(total=1000.0)
    skew = doctor.diagnose(rec, records=base)
    assert skew[0].code == "skewed_partition"
    assert skew[0].evidence["task_id"] == "r[1:2]"
    assert skew[0].evidence["ratio"] > conf.doctor_skew_ratio

    # same imbalance + a hang/speculation event on the stage: the slow
    # task is environmental, not a data problem
    env = base + [{"type": "event", "kind": "speculation_launch",
                   "stage_id": 1, "task_id": "r[1:2]", "attrs": {}}]
    strag = doctor.diagnose(rec, records=env)
    assert strag[0].code == "straggler_dominated"
    assert strag[0].evidence["env_events"] == ["speculation_launch"]


def test_admission_rules():
    shed = doctor.diagnose(_rec(total=0.0, admission=80.0,
                                outcome="rejected"))
    assert shed[0].code == "admission_starved"
    assert shed[0].score == 1.0  # a shed query IS the worst outcome

    parked = doctor.diagnose(_rec(total=500.0, admission=500.0))
    assert parked[0].code == "admission_starved"
    assert parked[0].score == pytest.approx(0.5)

    quick = doctor.diagnose(_rec(total=1000.0, admission=60.0))
    assert not any(f.code == "admission_starved" for f in quick)


def test_compile_storm_needs_cache_misses():
    hot = {"compile_ms": 600.0, "compile_cache_misses": 9,
           "compile_cache_hits": 1}
    assert doctor.diagnose(_rec(total=1000.0, counters=hot))[0].code \
        == "compile_storm"
    warm = {"compile_ms": 600.0, "compile_cache_misses": 1,
            "compile_cache_hits": 9}
    assert not any(f.code == "compile_storm" for f in
                   doctor.diagnose(_rec(total=1000.0, counters=warm)))


def test_spill_queue_breaker_rules():
    fs = doctor.diagnose(_rec(
        total=1000.0,
        counters={"spill_ms": 300.0, "spill_bytes": 1 << 24,
                  "spill_count": 3, "sched_queue_ms": 400.0},
        resil={"breaker_trip": 2, "degrade": 1}))
    codes = [f.code for f in fs]
    assert "spill_bound" in codes
    assert "queue_contended" in codes
    assert "breaker_degraded" in codes
    # ranked by explained share: queue (0.4) > spill (0.3) > breaker
    assert codes.index("queue_contended") < codes.index("spill_bound")


def test_pipeline_underlap_has_absolute_floor():
    def stats(busy, wait):
        return [{"type": "event", "kind": "pipeline_stats",
                 "attrs": {"producer_busy_ms": busy,
                           "consumer_wait_ms": wait}}]

    # tiny absolute numbers on a small query: no finding even at 0% overlap
    assert not any(f.code == "pipeline_underlap" for f in doctor.diagnose(
        _rec(total=100.0), records=stats(20.0, 25.0)))
    slow = doctor.diagnose(_rec(total=1000.0), records=stats(400.0, 380.0))
    assert slow[0].code == "pipeline_underlap"
    assert slow[0].evidence["overlap_pct"] < 40


def test_regression_vs_history_uses_feed():
    class FakeFeed:
        def observed_stage_cost(self, fp):
            return {"n": 5, "ms_p50": 100.0}

    rec = _rec(total=1000.0, stages=[
        {"stage_id": 0, "fingerprint": "abc", "kind": "shuffle_map",
         "ms": 700.0}])
    fs = doctor.diagnose(rec, feed=FakeFeed())
    assert fs[0].code == "regression_vs_history"
    assert fs[0].evidence["fingerprint"] == "abc"
    # 2x + 100ms grace: 250ms over a 100ms median is NOT a regression
    rec["stages"][0]["ms"] = 250.0
    assert doctor.diagnose(rec, feed=FakeFeed()) == []


# ---------------------------------------------------------------------------
# artifact loading + schema-version tolerance
# ---------------------------------------------------------------------------


def test_load_ledger_tolerates_pr9_era_lines(tmp_path):
    old_line = {"query_id": "q-old", "duration_ms": 800.0,
                "counters": {"serde_encode_ms": 400.0}}  # no schema_version
    p = tmp_path / "ledger.jsonl"
    p.write_text("not json at all\n"
                 + json.dumps(old_line) + "\n"
                 + json.dumps(_rec()) + "\n")
    recs = doctor.load_ledger(str(p))
    assert [r["query_id"] for r in recs] == ["q-old", "qD"]
    entries = doctor.diagnose_dir(str(tmp_path))
    # a missing schema_version reads as version 1 and still diagnoses
    assert entries[0]["schema_version"] == 1
    assert entries[0]["findings"][0]["code"] == "serde_bound"
    assert entries[1]["schema_version"] == trace.SCHEMA_VERSION


def test_history_store_aggregates_old_and_new_records(tmp_path):
    # a PR-9-era shard line (no schema_version, no critical_path) next
    # to a record written by today's record_run
    shard = tmp_path / "history-000001.jsonl"
    old = {"query_id": "q-old", "duration_ms": 120.0,
           "plan_fingerprint": "fp1",
           "stages": [{"stage_id": 0, "fingerprint": "sfp",
                       "kind": "shuffle_map", "ms": 80.0, "tasks": 2,
                       "bytes": 1024, "copied_bytes": 512,
                       "moved_bytes": 0}]}
    shard.write_text(json.dumps(old) + "\n")
    conf.update(history_dir=str(tmp_path), trace_enabled=True,
                doctor_enabled=True)
    trace.reset()
    with trace.span("query", query_id="q-new"):
        pass
    history.record_run("q-new", {"plan_fingerprint": "fp1"})
    records = history.store(str(tmp_path)).records()
    assert len(records) == 2
    assert "schema_version" not in records[0]
    assert records[1]["schema_version"] == trace.SCHEMA_VERSION
    assert records[1]["critical_path"]["total_ms"] >= 0
    feed = history.StatisticsFeed(records)
    cost = feed.observed_stage_cost("sfp")
    assert cost and cost["n"] == 1  # the old line still feeds statistics


# ---------------------------------------------------------------------------
# determinism over real exported artifacts
# ---------------------------------------------------------------------------


def _run_mini_query(tmp_path, export_dir, spec=None, supervised=False):
    import numpy as np
    import pandas as pd
    import pyarrow.parquet as pq

    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs.ir import col
    from blaze_tpu.spark import plan_model as P
    from blaze_tpu.spark.local_runner import run_plan
    from blaze_tpu.spark.validator import _to_arrow_typed

    schema = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])
    rng = np.random.default_rng(5)
    df = pd.DataFrame({"k": rng.integers(0, 50, 4000),
                       "v": rng.random(4000)})
    path = str(tmp_path / "mini.parquet")
    pq.write_table(_to_arrow_typed(df, schema), path)
    plan = P.sort(P.shuffle_exchange(P.scan(schema, [(path, [])]),
                                     [col("k")], 4),
                  [(col("k"), True, True), (col("v"), True, True)])
    conf.update(trace_enabled=True, monitor_enabled=True,
                doctor_enabled=True, trace_export_dir=str(export_dir),
                fault_injection_spec=None)
    if supervised:
        conf.update(enable_supervisor=True, max_concurrent_tasks=4,
                    max_task_retries=3, retry_backoff_ms=1)
    if spec:
        faults.install(spec)
    try:
        run_plan(plan, num_partitions=4, mesh_exchange="off")
    finally:
        faults.install(None)


def _blob(export_dir):
    return json.dumps(doctor.diagnose_dir(str(export_dir)),
                      sort_keys=True)


def test_diagnosis_is_byte_identical_across_runs(tmp_path):
    export = tmp_path / "export"
    _run_mini_query(tmp_path, export)
    blobs = {_blob(export) for _ in range(3)}
    assert len(blobs) == 1, "same artifacts must diagnose identically"


def test_diagnosis_deterministic_under_supervised_chaos(tmp_path):
    export = tmp_path / "export"
    spec = {"seed": 3,
            "points": {"op": {"kind": "io", "fail_times": 1}}}
    _run_mini_query(tmp_path, export, spec=spec, supervised=True)
    recs = doctor.load_ledger(os.path.join(str(export), "ledger.jsonl"))
    assert recs, "chaos run must still export a ledger line"
    blobs = {_blob(export) for _ in range(3)}
    assert len(blobs) == 1


def test_explain_analyze_renders_critical_path(tmp_path):
    _run_mini_query(tmp_path, tmp_path / "export")
    from blaze_tpu.ops.basic import MemorySourceExec
    from blaze_tpu.columnar import types as T

    root = MemorySourceExec([], T.Schema([T.Field("x", T.INT64)]))
    out = trace.explain_analyze(root, None)
    assert "-- critical path --" in out


# ---------------------------------------------------------------------------
# SLO tracker + gauges
# ---------------------------------------------------------------------------


def test_slo_tracker_attainment_and_burn():
    conf.update(tenant_slo_spec={"a": {"latency_ms": 100.0,
                                       "target": 0.9}},
                slo_window_queries=100, slo_burn_alert_rate=1e9)
    t = service.SloTracker()
    for _ in range(8):
        t.observe("a", 50.0)
    t.observe("a", 500.0)
    t.observe("a", 700.0)
    s = t.stats()["a"]
    assert s["attainment"] == pytest.approx(0.8)
    # miss rate 0.2 against a 0.1 error budget: burning at 2x
    assert s["burn_rate"] == pytest.approx(2.0)
    assert s["breaches"] == 2
    assert s["window"] == 10


def test_slo_shed_queries_count_as_misses():
    conf.update(tenant_slo_spec={"a": {"latency_ms": 1000.0,
                                       "target": 0.5}},
                slo_window_queries=10, slo_burn_alert_rate=1e9)
    t = service.SloTracker()
    t.observe("a", 1.0)
    t.observe("a", 1.0, rejected=True)  # fast rejection is still a miss
    s = t.stats()["a"]
    assert s["attainment"] == pytest.approx(0.5)
    assert s["breaches"] == 1


def test_slo_untracked_tenant_ignored_and_spec_seeded():
    conf.update(tenant_slo_spec={"a": {"latency_ms": 10.0}})
    t = service.SloTracker()
    t.observe("nobody", 5.0)
    s = t.stats()
    # spec tenants appear (seeded, perfect) even before any arrival —
    # that is what makes the gauges visible mid-query; non-spec tenants
    # never do
    assert list(s) == ["a"]
    assert s["a"]["attainment"] == 1.0 and s["a"]["window"] == 0


def test_slo_burn_event_emitted_over_alert_rate():
    conf.update(trace_enabled=True,
                tenant_slo_spec={"a": {"latency_ms": 1.0,
                                       "target": 0.5}},
                slo_window_queries=10, slo_burn_alert_rate=1.0)
    trace.reset()
    t = service.SloTracker()
    t.observe("a", 50.0)  # 100% miss rate, burn 2.0 > alert 1.0
    kinds = [r["kind"] for r in trace.TRACE.snapshot()]
    assert "slo_burn" in kinds


def test_prometheus_slo_gauges_present_with_spec_only():
    conf.update(monitor_enabled=True,
                tenant_slo_spec={"acme": {"latency_ms": 250.0,
                                          "target": 0.99}})
    service.reset_slo()
    text = monitor.prometheus_text()
    assert 'blaze_slo_objective_ms{tenant="acme"} 250' in text
    assert 'blaze_slo_attainment{tenant="acme"} 1.0' in text
    assert 'blaze_slo_burn_rate{tenant="acme"} 0.0' in text
    assert 'blaze_slo_breaches_total{tenant="acme"} 0' in text


def test_prometheus_histogram_exposition():
    conf.update(trace_enabled=True, monitor_enabled=True)
    trace.reset()
    for v in (1, 3, 200):
        trace.record_value("batch_rows", v)
    text = monitor.prometheus_text()
    assert "# TYPE blaze_hist_batch_rows histogram" in text
    assert 'blaze_hist_batch_rows_bucket{le="+Inf"} 3' in text
    assert "blaze_hist_batch_rows_sum 204" in text
    assert "blaze_hist_batch_rows_count 3" in text
    # cumulative le buckets, monotone non-decreasing
    cums = [float(ln.rsplit(" ", 1)[-1]) for ln in text.splitlines()
            if ln.startswith("blaze_hist_batch_rows_bucket")]
    assert cums == sorted(cums)
