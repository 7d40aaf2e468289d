"""Observability subsystem tests: metrics registry + Prometheus rendering,
distributed tracing (TraceContext propagation, span recording, the
controller's timeline assembly), structured logging, the slow-query log —
and the end-to-end acceptance path: a groupby through an in-process
controller+worker cluster whose waterfall comes back via rpc.trace() and
whose metrics come back via rpc.metrics()."""

import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from tests.conftest import wait_until

from bqueryd_tpu import obs


# -- metrics primitives ------------------------------------------------------

def test_counter_and_gauge_render():
    reg = obs.MetricsRegistry()
    c = reg.counter("bqueryd_tpu_things_total", "things seen")
    c.inc()
    c.inc(2)
    reg.gauge("bqueryd_tpu_depth", "queue depth", fn=lambda: 7)
    text = reg.render()
    assert "# HELP bqueryd_tpu_things_total things seen" in text
    assert "# TYPE bqueryd_tpu_things_total counter" in text
    assert "bqueryd_tpu_things_total 3" in text
    assert "bqueryd_tpu_depth 7" in text


def test_gauge_callback_failure_is_nan_not_crash():
    reg = obs.MetricsRegistry()
    reg.gauge("bqueryd_tpu_broken", "always raises", fn=lambda: 1 / 0)
    assert "bqueryd_tpu_broken nan" in reg.render()


def test_histogram_buckets_cumulative_and_sum():
    reg = obs.MetricsRegistry()
    h = reg.histogram("bqueryd_tpu_lat_seconds", "latency")
    for v in (0.0002, 0.0002, 0.3, 1e9):  # two tiny, one mid, one overflow
        h.observe(v)
    text = reg.render()
    # cumulative counts: everything <= 0.5 except the overflow
    assert 'bqueryd_tpu_lat_seconds_bucket{le="0.5"} 3' in text
    assert 'bqueryd_tpu_lat_seconds_bucket{le="+Inf"} 4' in text
    assert "bqueryd_tpu_lat_seconds_count 4" in text
    assert h.count == 4
    # non-cumulative snapshot merges by vector add
    snap = h.snapshot()
    assert sum(snap["counts"]) == 4
    assert snap["buckets"] == list(obs.LATENCY_BUCKETS_S)


def test_histogram_family_labels():
    reg = obs.MetricsRegistry()
    reg.histogram(
        "bqueryd_tpu_phase_seconds", "per phase", labels={"phase": "kernel"}
    ).observe(0.01)
    reg.histogram(
        "bqueryd_tpu_phase_seconds", "per phase", labels={"phase": "merge"}
    ).observe(0.02)
    text = reg.render()
    assert text.count("# TYPE bqueryd_tpu_phase_seconds histogram") == 1
    assert 'phase="kernel"' in text and 'phase="merge"' in text


def test_merge_histogram_snapshots_vector_add():
    reg_a, reg_b = obs.MetricsRegistry(), obs.MetricsRegistry()
    for reg, values in ((reg_a, (0.001, 0.3)), (reg_b, (0.001,))):
        h = reg.histogram(
            "bqueryd_tpu_phase_seconds", "x", labels={"phase": "kernel"}
        )
        for v in values:
            h.observe(v)
    merged = obs.merge_histogram_snapshots(
        [reg_a.histogram_snapshot(), reg_b.histogram_snapshot()]
    )
    (entry,) = merged["bqueryd_tpu_phase_seconds"]
    assert sum(entry["counts"]) == 3
    assert entry["sum"] == pytest.approx(0.302)
    assert "_skipped" not in merged


def test_merge_histogram_snapshots_rejects_mismatched_buckets():
    good = {
        "bqueryd_tpu_x_seconds": [
            {"labels": {}, "buckets": [1.0, 2.0], "counts": [1, 0, 0], "sum": 0.5}
        ]
    }
    bad = {
        "bqueryd_tpu_x_seconds": [
            {"labels": {}, "buckets": [1.0, 5.0], "counts": [0, 1, 0], "sum": 3.0}
        ]
    }
    merged = obs.merge_histogram_snapshots([good, bad])
    (entry,) = merged["bqueryd_tpu_x_seconds"]
    assert entry["counts"] == [1, 0, 0]  # mismatch skipped, not mis-added
    assert merged["_skipped"] == ["bqueryd_tpu_x_seconds"]


def test_registry_counters_dict_compat():
    """The controller's counters surface: plain-dict reads/writes, every
    write mirrored into a typed Prometheus counter."""
    reg = obs.MetricsRegistry()
    counters = obs.RegistryCounters(reg, {"plan_pruned_shards": "help here"})
    assert counters["plan_pruned_shards"] == 0
    counters["plan_pruned_shards"] += 3
    assert counters["plan_pruned_shards"] == 3
    assert dict(counters) == {"plan_pruned_shards": 3}
    assert "bqueryd_tpu_plan_pruned_shards_total 3" in reg.render()


def test_registry_lint_clean_and_violations():
    reg = obs.MetricsRegistry()
    reg.counter("bqueryd_tpu_good_total", "fine")
    assert reg.lint() == []
    reg.counter("bqueryd_tpu_BAD", "casing")
    reg.gauge("bqueryd_tpu_nohelp", "")
    reg.histogram("bqueryd_tpu_odd_seconds", "buckets", buckets=(1.0, 2.0))
    problems = "\n".join(reg.lint())
    assert "bqueryd_tpu_BAD" in problems
    assert "missing help" in problems
    assert "merge precondition" in problems


# -- PhaseTimer satellites ---------------------------------------------------

def test_phase_timer_total_is_monotonic(monkeypatch):
    """total() must survive a wall-clock step backwards (NTP): both the
    anchor and the reading use perf_counter now."""
    from bqueryd_tpu.utils.tracing import PhaseTimer

    timer = PhaseTimer()
    with timer.phase("work"):
        pass
    # a wall-clock step back must not affect perf_counter-based totals
    monkeypatch.setattr(time, "time", lambda: 0.0)
    assert timer.total() >= 0.0
    assert timer.total() >= timer.timings["work"] - 1e-9


def test_phase_timer_total_key_never_collides():
    from bqueryd_tpu.utils.tracing import TOTAL_KEY, PhaseTimer

    timer = PhaseTimer()
    with timer.phase("total"):  # a REAL phase named "total"
        pass
    out = timer.as_dict()
    assert TOTAL_KEY == "_total"
    assert "total" in out and TOTAL_KEY in out
    assert out["total"] is not out[TOTAL_KEY]
    assert out[TOTAL_KEY] >= out["total"]


def test_phase_timer_records_spans_with_mapped_names():
    from bqueryd_tpu.utils.tracing import PhaseTimer

    recorder = obs.SpanRecorder(trace_id="t" * 32, node="w1")
    timer = PhaseTimer(recorder=recorder, span_names=obs.PHASE_SPAN_NAMES)
    with timer.phase("open"):
        pass
    with timer.phase("aggregate"):
        pass
    spans = recorder.export()
    names = [s["name"] for s in spans]
    assert names[0] == "calc"  # root first
    assert "storage_decode" in names and "kernel" in names
    for child in spans[1:]:
        assert child["parent_span_id"] == recorder.root_span_id
        assert child["trace_id"] == "t" * 32


# -- trace_span env gating (satellite: zero tests imported
#    utils/tracing before) ---------------------------------------------------

def test_trace_span_noop_when_profile_unset(monkeypatch):
    from bqueryd_tpu.utils import tracing

    monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    entered = []
    monkeypatch.setitem(
        __import__("sys").modules, "jax.profiler", None
    )  # would raise if touched
    with tracing.trace_span("off"):
        entered.append(True)
    assert entered == [True]


def test_trace_span_enabled_with_jax(monkeypatch):
    from bqueryd_tpu.utils import tracing

    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    with tracing.trace_span("on"):
        pass  # enters a real jax.profiler.TraceAnnotation


def test_trace_span_enabled_tags_trace_id(monkeypatch):
    import jax.profiler

    from bqueryd_tpu.utils import tracing

    seen = {}

    class FakeAnnotation:
        def __init__(self, name, **kwargs):
            seen["name"] = name
            seen.update(kwargs)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    ctx = obs.TraceContext.new_root()
    with obs.use_trace(ctx):
        with tracing.trace_span("kernel"):
            pass
    assert seen == {"name": "kernel", "trace_id": ctx.trace_id}


def test_trace_span_enabled_without_jax_is_noop(monkeypatch):
    """BQUERYD_TPU_PROFILE=1 but jax.profiler unimportable -> still a
    working no-op (downloader/controller processes without JAX)."""
    import sys

    from bqueryd_tpu.utils import tracing

    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # ImportError
    entered = []
    with tracing.trace_span("no-jax"):
        entered.append(True)
    assert entered == [True]


# -- trace model -------------------------------------------------------------

def test_trace_context_wire_roundtrip():
    ctx = obs.TraceContext.new_root()
    wire = ctx.to_wire()
    back = obs.TraceContext.from_wire(json.loads(json.dumps(wire)))
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    child = back.child()
    assert child.parent_span_id == back.span_id
    assert child.trace_id == back.trace_id
    assert obs.TraceContext.from_wire(None) is None
    assert obs.TraceContext.from_wire({"trace_id": 5}) is None


def test_trace_store_ring_eviction():
    store = obs.TraceStore(capacity=2)
    for i in range(3):
        store.put(f"t{i}", {"trace_id": f"t{i}"})
    assert store.get("t0") is None
    assert store.get("t2")["trace_id"] == "t2"
    assert len(store) == 2


# -- logs --------------------------------------------------------------------

def test_json_log_formatter_carries_context():
    formatter = obs.JsonLogFormatter(node_id="w-123")
    record = logging.LogRecord(
        "bqueryd_tpu.test", logging.INFO, __file__, 1, "hello %s", ("x",), None
    )
    with obs.bind_log_context(trace_id="abc", query_id="q1"):
        line = json.loads(formatter.format(record))
    assert line["msg"] == "hello x"
    assert line["node_id"] == "w-123"
    assert line["trace_id"] == "abc"
    assert line["query_id"] == "q1"
    # outside the bind, no correlation fields leak
    line2 = json.loads(formatter.format(record))
    assert "trace_id" not in line2


def test_slow_query_log_threshold_and_capacity(monkeypatch):
    log = obs.SlowQueryLog(capacity=2)
    monkeypatch.setenv("BQUERYD_TPU_SLOW_QUERY_MS", "100")
    assert not log.maybe_record(0.05, {"trace_id": "fast"})
    assert log.maybe_record(0.2, {"trace_id": "slow1"})
    assert log.maybe_record(0.2, {"trace_id": "slow2"})
    assert log.maybe_record(0.2, {"trace_id": "slow3"})
    entries = log.entries()
    assert [e["trace_id"] for e in entries] == ["slow2", "slow3"]
    assert entries[-1]["wall_ms"] == pytest.approx(200.0)


# -- /metrics HTTP endpoint --------------------------------------------------

def test_metrics_http_endpoint_serves_registry():
    from bqueryd_tpu.obs.http import MetricsServer

    reg = obs.MetricsRegistry()
    reg.counter("bqueryd_tpu_scraped_total", "scrapes").inc()
    server = MetricsServer(reg, port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5).read()
        assert b"bqueryd_tpu_scraped_total 1" in body
        health = urllib.request.urlopen(f"{base}/healthz", timeout=5).read()
        assert health == b"ok\n"
    finally:
        server.close()


def test_metrics_http_maybe_start_off_by_default(monkeypatch):
    from bqueryd_tpu.obs import http as obs_http

    monkeypatch.delenv("BQUERYD_TPU_METRICS_PORT", raising=False)
    assert obs_http.maybe_start(obs.MetricsRegistry()) is None


# -- end-to-end: the acceptance path ----------------------------------------

NR_SHARDS = 3


def _taxi_df(n=3_000, seed=11):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "total_amount": rng.gamma(2.5, 8.0, n),
            "trip_distance": rng.exponential(3.0, n),
        }
    )


@pytest.fixture(scope="module")
def obs_cluster(tmp_path_factory):
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = _taxi_df()
    root = tmp_path_factory.mktemp("obs_cluster")
    ctable.fromdataframe(df, str(root / "taxi.bcolz"))
    for i in range(NR_SHARDS):
        ctable.fromdataframe(
            df.iloc[i::NR_SHARDS], str(root / f"taxi-{i}.bcolzs")
        )
    url = f"mem://obs-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=str(root),
        heartbeat_interval=0.2,
        dead_worker_timeout=10.0,
    )
    worker = WorkerNode(
        coordination_url=url,
        data_dir=str(root),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.2,
        poll_timeout=0.1,
    )
    threads = [
        threading.Thread(target=node.go, daemon=True)
        for node in (controller, worker)
    ]
    for t in threads:
        t.start()
    wait_until(
        lambda: controller.files_map.get("taxi.bcolz"),
        desc="worker registration",
    )
    rpc = RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
    yield {
        "rpc": rpc,
        "controller": controller,
        "worker": worker,
        "df": df,
    }
    for node in (controller, worker):
        node.running = False
    for t in threads:
        t.join(timeout=5)


def _groupby(rpc):
    return rpc.groupby(
        ["taxi.bcolz"],
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        [],
    )


def test_trace_waterfall_covers_required_spans(obs_cluster):
    """ACCEPTANCE: groupby through controller+worker, then rpc.trace()
    returns a timeline covering admission, plan, dispatch, kernel, merge —
    with parent/child links intact."""
    rpc = obs_cluster["rpc"]
    _groupby(rpc)
    trace_id = rpc.last_trace_id
    assert trace_id
    timeline = rpc.trace(trace_id)
    assert timeline is not None
    assert timeline["trace_id"] == trace_id
    assert timeline["ok"] is True
    spans = timeline["spans"]
    names = {s["name"] for s in spans}
    assert {"admission", "plan", "dispatch", "kernel", "merge"} <= names, names
    # worker-side phases came along too
    assert {"calc", "storage_decode", "h2d_transfer"} <= names, names
    # parent/child links: every span's parent is another span in the
    # timeline, except the spans under the CLIENT's root span (not part of
    # the timeline): the controller's root "groupby" span, its
    # request_decode before and finalize after it, and the client's own
    # spans, which rpc.trace() merges in
    by_id = {s["span_id"]: s for s in spans}
    orphans = [
        s for s in spans if s["parent_span_id"] not in by_id
    ]
    assert sorted(s["name"] for s in orphans) == [
        "client_decode", "client_encode", "finalize", "groupby",
        "request_decode",
    ]
    assert len({s["parent_span_id"] for s in orphans}) == 1
    # chain: kernel -> calc -> dispatch -> groupby
    kernel = next(s for s in spans if s["name"] == "kernel")
    calc = by_id[kernel["parent_span_id"]]
    assert calc["name"] == "calc"
    dispatch = by_id[calc["parent_span_id"]]
    assert dispatch["name"] == "dispatch"
    assert by_id[dispatch["parent_span_id"]]["name"] == "groupby"
    for name in ("admission", "plan"):
        span = next(s for s in spans if s["name"] == name)
        assert by_id[span["parent_span_id"]]["name"] == "groupby"
    # every span is trace-consistent and non-negative
    for s in spans:
        assert s["trace_id"] == trace_id
        assert s["duration_s"] >= 0.0


def test_rpc_metrics_prometheus_exposition(obs_cluster):
    """ACCEPTANCE: rpc.metrics() returns valid Prometheus text including the
    migrated plan_pruned_shards counter and a latency histogram whose bucket
    counts sum to the query count."""
    rpc = obs_cluster["rpc"]
    controller = obs_cluster["controller"]
    _groupby(rpc)
    text = rpc.metrics()
    assert isinstance(text, str)
    assert "# TYPE bqueryd_tpu_plan_pruned_shards_total counter" in text
    assert "bqueryd_tpu_plan_pruned_shards_total" in text
    # the latency histogram: +Inf cumulative == _count == queries completed
    inf_line = next(
        line for line in text.splitlines()
        if line.startswith("bqueryd_tpu_groupby_seconds_bucket")
        and 'le="+Inf"' in line
    )
    count_line = next(
        line for line in text.splitlines()
        if line.startswith("bqueryd_tpu_groupby_seconds_count")
    )
    inf_value = int(float(inf_line.rsplit(" ", 1)[1]))
    count_value = int(float(count_line.rsplit(" ", 1)[1]))
    assert inf_value == count_value
    assert count_value == controller.counters["queries_completed"]
    assert count_value >= 1


def test_slow_query_log_over_rpc(obs_cluster):
    rpc = obs_cluster["rpc"]
    os.environ["BQUERYD_TPU_SLOW_QUERY_MS"] = "0"  # everything is slow
    try:
        _groupby(rpc)
        trace_id = rpc.last_trace_id
        entries = rpc.slow_queries()
    finally:
        os.environ.pop("BQUERYD_TPU_SLOW_QUERY_MS", None)
    assert entries, "threshold 0 must record every query"
    entry = next(e for e in entries if e["trace_id"] == trace_id)
    assert entry["ok"] is True
    assert entry["filenames"] == 1
    assert entry["plan_signature"]
    assert entry["wall_ms"] > 0
    # phase breakdown present, with the namespaced total key
    (timings,) = entry["phase_timings"].values()
    assert "_total" in timings


def test_worker_histograms_aggregate_into_info(obs_cluster):
    """Worker WRMs carry histogram snapshots; the controller merges them by
    bucket-vector addition into get_info."""
    rpc = obs_cluster["rpc"]
    worker = obs_cluster["worker"]
    _groupby(rpc)
    assert worker.groupby_queries.value >= 1

    def aggregated():
        info = obs_cluster["controller"].get_info()
        hists = info.get("worker_histograms", {})
        series = hists.get("bqueryd_tpu_worker_groupby_seconds")
        # snapshots ride periodic WRMs, and a pre-groupby WRM legitimately
        # carries the family with all-zero counts — wait for the heartbeat
        # that reflects the observation, not just for the family to exist
        if not series or sum(sum(e["counts"]) for e in series) < 1:
            return None
        return series

    series = wait_until(aggregated, desc="worker histogram snapshot in WRM")
    total = sum(sum(e["counts"]) for e in series)
    assert total >= 1
    # phase family made it too, with mapped span names as labels
    info = obs_cluster["controller"].get_info()
    phases = info["worker_histograms"]["bqueryd_tpu_query_phase_seconds"]
    labels = {e["labels"]["phase"] for e in phases}
    assert {"kernel", "storage_decode"} <= labels


def test_live_registries_pass_lint(obs_cluster):
    """Satellite: the registry self-check runs clean on REAL node
    registries (names, help text, identical bucket vectors)."""
    assert obs_cluster["controller"].metrics.lint() == []
    assert obs_cluster["worker"].metrics.lint() == []


def test_metrics_kill_switch_disables_hot_path(obs_cluster):
    rpc = obs_cluster["rpc"]
    controller = obs_cluster["controller"]
    before = controller.query_seconds.count
    obs.set_enabled(False)
    try:
        _groupby(rpc)
        trace_id = rpc.last_trace_id
    finally:
        obs.set_enabled(True)
    # no histogram observation, no timeline — but the query itself worked
    # and the logic counters still moved
    assert controller.query_seconds.count == before
    assert rpc.trace(trace_id) is None
    assert controller.counters["queries_completed"] >= 1


def test_last_call_duration_uses_perf_counter(obs_cluster):
    rpc = obs_cluster["rpc"]
    assert rpc.ping() == "pong"
    assert rpc.last_call_duration is not None
    assert rpc.last_call_duration >= 0.0
