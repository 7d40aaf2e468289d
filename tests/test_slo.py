"""PR 10 observability tier: critical-path attribution (rpc.autopsy), SLO
accounting (per-class margin histograms + burn rates), the controller
timeline ring (rpc.timeline), per-member bundle shares, and the
span-coverage lint — plus the e2e acceptance path: a live cluster whose
queries autopsy with >= 95% coverage and whose client folds its own
deserialize wall into the fetched record."""

import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from conftest import wait_until

from bqueryd_tpu import obs
from bqueryd_tpu.obs import slo
from bqueryd_tpu.obs.metrics import MetricsRegistry, quantile_from_snapshot


def span(name, start, dur, tags=None, trace_id="t1"):
    return obs.make_span(trace_id, name, start, dur, tags=tags)


def timeline(spans, trace_id="t1", ok=True):
    return {"trace_id": trace_id, "ok": ok, "spans": spans}


def total_of(record):
    return sum(record["segments"].values()) + record["unattributed_s"]


# -- attribution sweep --------------------------------------------------------

def test_attribute_simple_decomposition():
    t0 = 1000.0
    record = slo.attribute(timeline([
        span("groupby", t0, 1.0),
        span("admission", t0, 0.1),
        span("dispatch", t0 + 0.1, 0.1),
        span("calc", t0 + 0.2, 0.7),
        span("h2d_transfer", t0 + 0.25, 0.05),
        span("kernel", t0 + 0.3, 0.3),
    ]))
    segments = record["segments"]
    assert record["wall_s"] == pytest.approx(1.0)
    assert segments["admission_wait"] == pytest.approx(0.1)
    assert segments["dispatch"] == pytest.approx(0.1)
    assert segments["h2d_transfer"] == pytest.approx(0.05)
    assert segments["kernel"] == pytest.approx(0.3)
    # calc residue outside its phases: 0.2-0.25 and 0.6-0.9
    assert segments["worker_other"] == pytest.approx(0.35)
    # 0.9-1.0 only the root is active
    assert record["unattributed_s"] == pytest.approx(0.1)
    assert record["coverage"] == pytest.approx(0.9)
    # the invariant the chaos tests re-assert: segments + unattributed
    # always sum to the wall (non-overlap by construction)
    assert total_of(record) == pytest.approx(record["wall_s"], abs=1e-5)


def test_attribute_overlapping_concurrent_shards_never_double_count():
    """Two concurrent shard executions overlap on the wall clock; the sweep
    charges each instant once (most-specific wins), so the total never
    exceeds the wall."""
    t0 = 50.0
    record = slo.attribute(timeline([
        span("groupby", t0, 1.0),
        span("calc", t0, 0.8),
        span("calc", t0 + 0.1, 0.9),
        span("kernel", t0 + 0.2, 0.4),
        span("kernel", t0 + 0.3, 0.5),   # overlaps the other kernel
    ]))
    assert record["segments"]["kernel"] == pytest.approx(0.6)  # union
    assert total_of(record) == pytest.approx(1.0, abs=1e-6)
    assert record["coverage"] == pytest.approx(1.0)


def test_attribute_splits_backoff_out_of_retry_dispatch():
    t0 = 10.0
    record = slo.attribute(timeline([
        span("groupby", t0, 2.0),
        span("dispatch", t0, 0.2, tags={"worker": "w1", "retries": 0}),
        span("dispatch", t0 + 0.2, 0.8,
             tags={"worker": "w1", "retries": 0,
                   "failed": "dispatch timeout"}),
        span("dispatch", t0 + 1.0, 0.5,
             tags={"worker": "w2", "retries": 1, "backoff_s": 0.3,
                   "excluded": ["w1"]}),
        span("calc", t0 + 1.5, 0.5),
    ]))
    segments = record["segments"]
    assert segments["retry_backoff"] == pytest.approx(0.3)
    # 0.2 first queue + 0.8 failed wait + 0.2 post-backoff queue
    assert segments["dispatch"] == pytest.approx(1.2)
    assert total_of(record) == pytest.approx(2.0, abs=1e-6)
    attempts = record["attempts"]
    # ONE entry per physical attempt: the failed in-flight span annotates
    # attempt 1 (failed reason + how long it sat) instead of listing twice
    assert len(attempts) == 2
    assert attempts[0]["failed"] == "dispatch timeout"
    assert attempts[0]["inflight_s"] == pytest.approx(0.8)
    assert attempts[1]["excluded"] == ["w1"]
    assert attempts[1]["backoff_s"] == pytest.approx(0.3)


def test_attribute_hedge_dispatch_tagged():
    """The controller emits a zero-length hedge MARKER at dispatch time
    (listed in attempts) plus the hedge-race window at reply time (an
    inflight span tagged hedge: a segment, not an attempt) — mirror both
    here."""
    t0 = 0.0
    record = slo.attribute(timeline([
        span("groupby", t0, 1.0),
        span("dispatch", t0, 0.4, tags={"worker": "w1"}),
        span("dispatch", t0 + 0.4, 0.0,
             tags={"worker": "w2", "hedge": True}),
        span("inflight", t0 + 0.4, 0.2,
             tags={"worker": "w2", "hedge": True}),
        span("calc", t0 + 0.7, 0.3),
    ]))
    assert record["segments"]["hedge_dispatch"] == pytest.approx(0.2)
    hedges = [a for a in record["attempts"] if a["hedge"]]
    assert len(hedges) == 1 and hedges[0]["worker"] == "w2"


def test_attribute_bundle_share_reports_member_slice():
    t0 = 5.0
    record = slo.attribute(timeline([
        span("groupby", t0, 1.0),
        span("calc", t0, 1.0, tags={"bundle_share": 0.25}),
        span("kernel", t0 + 0.2, 0.8),
    ]))
    # true-wall segments stay untouched...
    assert record["segments"]["kernel"] == pytest.approx(0.8)
    # ...and the member's accountable slice is reported beside them
    assert record["bundle"]["share"] == pytest.approx(0.25)
    assert record["bundle"]["member_segments"]["kernel"] == pytest.approx(0.2)


def test_attribute_unknown_span_name_stays_visible():
    """An undeclared span name (the lint prevents shipping one, but a
    version-skewed worker may still send it) keeps its own segment instead
    of silently vanishing into unattributed."""
    record = slo.attribute(timeline([
        span("groupby", 0.0, 1.0),
        span("mystery_phase", 0.2, 0.5),
    ]))
    assert record["segments"]["mystery_phase"] == pytest.approx(0.5)
    assert record["coverage"] == pytest.approx(0.5)


def test_attribute_malformed_inputs_never_raise():
    assert slo.attribute(None)["wall_s"] == 0.0
    assert slo.attribute({})["coverage"] == 0.0
    record = slo.attribute(timeline([
        {"name": "kernel", "start_ts": "garbage", "duration_s": 1},
        {"not": "a span"},
        span("groupby", 0.0, 1.0),
    ]))
    assert record["wall_s"] == pytest.approx(1.0)


def test_attribute_without_root_uses_span_envelope():
    record = slo.attribute(timeline([
        span("calc", 10.0, 1.0),
        span("kernel", 10.2, 0.5),
    ]))
    assert record["wall_s"] == pytest.approx(1.0)
    assert record["segments"]["kernel"] == pytest.approx(0.5)


def test_summarize_compacts_record():
    record = slo.attribute(timeline([
        span("groupby", 0.0, 1.0),
        span("calc", 0.0, 0.9),
        span("kernel", 0.1, 0.6),
    ]))
    summary = slo.summarize(record, top=1)
    assert summary["segments"] == {"kernel": record["segments"]["kernel"]}
    assert summary["coverage"] == record["coverage"]
    assert slo.summarize(None) is None


def test_every_public_span_name_has_priority():
    """SPAN_CATEGORIES segments must all rank in SEGMENT_PRIORITY — an
    unranked segment would fall back to dispatch priority silently."""
    for segment in slo.SPAN_CATEGORIES.values():
        assert segment in slo.SEGMENT_PRIORITY
    for segment in slo.SYNTHETIC_SEGMENTS:
        assert segment in slo.SEGMENT_PRIORITY or segment == "unattributed"


# -- SLO tracker --------------------------------------------------------------

def test_parse_classes_formats_and_default():
    classes = slo.parse_classes("interactive:0.5:0.999,batch:30,junk:,bad:x")
    assert classes["interactive"] == {"target_s": 0.5, "objective": 0.999}
    assert classes["batch"]["target_s"] == 30.0
    assert classes["batch"]["objective"] == slo.DEFAULT_OBJECTIVE
    assert "junk" not in classes and "bad" not in classes
    assert "default" in classes
    assert slo.parse_classes("")["default"]["target_s"] == (
        slo.DEFAULT_TARGET_S
    )


def test_slo_tracker_records_margins_and_violations():
    registry = MetricsRegistry()
    tracker = slo.SLOTracker(
        registry, classes=slo.parse_classes("fast:0.5")
    )
    # on-target query: positive margin, no violation
    cls, violated = tracker.record("fast", wall_s=0.1)
    assert (cls, violated) == ("fast", False)
    # past-target query (no deadline): violation, margin clamps to 0
    cls, violated = tracker.record("fast", wall_s=0.9)
    assert violated
    # explicit deadline margin wins over the class target
    _, violated = tracker.record("fast", wall_s=0.1, margin_s=-0.2)
    assert violated
    # unknown class folds into default
    cls, _ = tracker.record("nope", wall_s=0.1)
    assert cls == "default"
    # errors violate regardless of wall
    _, violated = tracker.record("fast", wall_s=0.01, ok=False)
    assert violated
    hist = tracker._hist["fast"]
    assert hist.count == 4
    assert tracker._violations["fast"].value == 3
    assert tracker._queries["fast"].value == 4
    snapshot = tracker.snapshot()
    assert snapshot["fast"]["violations"] == 3
    assert snapshot["default"]["queries"] == 1


def test_slo_burn_rate_windows():
    registry = MetricsRegistry()
    tracker = slo.SLOTracker(
        registry, classes=slo.parse_classes("c:1.0:0.99")
    )
    now = 10_000.0
    # 2 of 4 violated inside the 5m window -> rate 0.5 over budget 0.01
    for offset, violated in ((-10, True), (-8, False), (-6, True), (-4, False)):
        tracker.record(
            "c", wall_s=2.0 if violated else 0.1, now=now + offset
        )
    assert tracker.burn_rate("c", 300.0, now=now) == pytest.approx(50.0)
    # nothing in a tiny window -> 0.0, not a division error
    assert tracker.burn_rate("c", 0.001, now=now + 100) == 0.0
    # gauges render without error and carry the labels
    text = registry.render()
    assert 'bqueryd_tpu_slo_burn_rate{slo_class="c",window="5m"}' in text
    assert registry.lint() == []


def test_slo_burn_window_survives_high_qps():
    """Burn bookkeeping is bucketed counts, not raw events: 50 minutes of
    heavy violations followed by a clean recovery must still dominate the
    1h rate at any QPS (a raw-event cap used to shrink the window to
    seconds under load), and memory stays bounded by bucket count."""
    tracker = slo.SLOTracker(
        MetricsRegistry(), classes=slo.parse_classes("c:1.0:0.99")
    )
    now = 100_000.0
    for i in range(5000):   # ~83 qpm for 50 minutes, all violating
        tracker.record("c", wall_s=2.0, now=now - 3600.0 + i * 0.6)
    for i in range(1000):   # clean last 10 minutes
        tracker.record("c", wall_s=0.1, now=now - 600.0 + i * 0.6)
    # 5000/6000 violated over the hour -> rate ~0.83 over budget 0.01
    assert tracker.burn_rate("c", 3600.0, now=now) == pytest.approx(
        83.3, rel=0.05
    )
    # the clean 5m window reads clean
    assert tracker.burn_rate("c", 300.0, now=now) == 0.0
    # memory: at most window/bucket + 1 buckets, regardless of QPS
    assert len(tracker._events["c"]) <= 3600.0 / slo._BURN_BUCKET_S + 2
    # buckets older than the largest window are trimmed on record
    tracker.record("c", wall_s=0.1, now=now + 7200.0)
    assert len(tracker._events["c"]) == 1


# -- timeline ring ------------------------------------------------------------

def test_snapshot_timeline_paces_and_bounds(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_TIMELINE_INTERVAL_S", "10")
    monkeypatch.setenv("BQUERYD_TPU_TIMELINE_ENTRIES", "3")
    ring = slo.SnapshotTimeline()
    taken = [
        ring.maybe_snapshot(lambda: {"n": i}, now=1000.0 + i * 6.0)
        for i in range(10)
    ]
    # 6 s apart at a 10 s interval: every other tick snapshots
    assert sum(taken) == 5
    entries = ring.entries()
    assert len(entries) == 3  # capacity trim, newest kept
    assert entries[-1]["n"] == 8 and "ts" in entries[-1]


def test_snapshot_timeline_disabled_and_builder_failure(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_TIMELINE_INTERVAL_S", "0")
    ring = slo.SnapshotTimeline()
    assert not ring.maybe_snapshot(lambda: {"x": 1}, now=1.0)
    monkeypatch.setenv("BQUERYD_TPU_TIMELINE_INTERVAL_S", "1")

    def boom():
        raise RuntimeError("builder broke")

    assert not ring.maybe_snapshot(boom, now=100.0)
    assert len(ring) == 0
    # the failure is counted (and logged), never invisible
    assert ring.failures == 1


def test_quantile_from_snapshot():
    from bqueryd_tpu.obs.metrics import Histogram

    h = Histogram("bqueryd_tpu_test_seconds", "t")
    for v in (0.001, 0.001, 0.04, 8.0):
        h.observe(v)
    snap = h.snapshot()
    assert quantile_from_snapshot(snap, 0.5) == pytest.approx(0.001)
    assert quantile_from_snapshot(snap, 0.99) == pytest.approx(10.0)
    assert quantile_from_snapshot({"buckets": [], "counts": []}, 0.5) is None
    assert quantile_from_snapshot({}, 0.5) is None


# -- bundle member shares -----------------------------------------------------

def test_member_shares_proportional_and_equal():
    from bqueryd_tpu.plan import bundle as bundlemod

    assert bundlemod.member_shares([]) == {}
    shares = bundlemod.member_shares(
        ["a", "b"], walls={"a": 0.3, "b": 0.1}
    )
    assert shares["a"] == pytest.approx(0.75)
    assert shares["b"] == pytest.approx(0.25)
    # missing/zero walls degrade to the equal split
    shares = bundlemod.member_shares(["a", "b"], walls={"a": 0.3})
    assert shares == {"a": 0.5, "b": 0.5}
    assert bundlemod.member_shares(["a", "b", "c"])["a"] == pytest.approx(
        1 / 3, abs=1e-4
    )


# -- span-coverage lint -------------------------------------------------------

def _span_project(tmp_path, extra_site="", schema_extra="", categories_extra=""):
    from tests.test_analysis import make_project

    return make_project(tmp_path, {
        "messages.py": (
            "SPAN_SCHEMA = {\n"
            "    'groupby': 'root',\n"
            "    'calc': 'worker root',\n"
            "    'open': 'raw name of storage_decode',\n"
            "    'storage_decode': 'decode',\n"
            f"{schema_extra}"
            "}\n"
        ),
        "obs/trace.py": (
            "PHASE_SPAN_NAMES = {'open': 'storage_decode'}\n"
        ),
        "obs/slo.py": (
            "SPAN_CATEGORIES = {\n"
            "    'groupby': 'query',\n"
            "    'calc': 'worker_other',\n"
            "    'storage_decode': 'storage_decode',\n"
            f"{categories_extra}"
            "}\n"
            "SYNTHETIC_SEGMENTS = ('unattributed',)\n"
        ),
        "worker.py": (
            "def handle(timer, recorder, make_span):\n"
            "    with timer.phase('open'):\n"
            "        pass\n"
            "    make_span('t', 'groupby', 0, 1)\n"
            "    SpanRecorder(root_name='calc')\n"
            f"{extra_site}"
            "def SpanRecorder(root_name=None):\n"
            "    return root_name\n"
        ),
    })


def _run_spans(project):
    from bqueryd_tpu.analysis.core import run_suite as core_run_suite
    from bqueryd_tpu.analysis.spans import SpanSchemaAnalyzer

    return core_run_suite(project=project, analyzers=[SpanSchemaAnalyzer()])


def test_span_lint_clean_project(tmp_path):
    result = _run_spans(_span_project(tmp_path))
    assert [f.render() for f in result.new] == []


def test_span_lint_flags_undeclared_site(tmp_path):
    result = _run_spans(_span_project(
        tmp_path, extra_site="    timer.phase('rogue_phase')\n"
    ))
    assert {
        (f.rule, f.symbol) for f in result.new
    } == {("span-undeclared-name", "rogue_phase")}


def test_span_lint_flags_unattributed_name(tmp_path):
    # declared + used, but no SPAN_CATEGORIES entry for its public form
    result = _run_spans(_span_project(
        tmp_path,
        extra_site="    timer.phase('warp')\n",
        schema_extra="    'warp': 'new phase',\n",
    ))
    assert {
        (f.rule, f.symbol) for f in result.new
    } == {("span-unattributed-name", "warp")}


def test_span_lint_flags_dead_name(tmp_path):
    result = _run_spans(_span_project(
        tmp_path, schema_extra="    'ghost': 'never recorded',\n",
        categories_extra="    'ghost': 'query',\n",
    ))
    assert {
        (f.rule, f.symbol) for f in result.new
    } == {("span-dead-name", "ghost")}


def test_span_lint_flags_unranked_segment(tmp_path):
    from tests.test_analysis import make_project

    project = make_project(tmp_path, {
        "messages.py": "SPAN_SCHEMA = {'groupby': 'root'}\n",
        "obs/trace.py": "PHASE_SPAN_NAMES = {}\n",
        "obs/slo.py": (
            "SPAN_CATEGORIES = {'groupby': 'query'}\n"
            "SYNTHETIC_SEGMENTS = ('retry_backoff', 'unattributed')\n"
            # 'retry_backoff' missing: the sweep would rank it silently
            "SEGMENT_PRIORITY = ('query',)\n"
        ),
        "worker.py": "def f(make_span):\n    make_span('t', 'groupby', 0, 1)\n",
    })
    result = _run_spans(project)
    assert {
        (f.rule, f.symbol) for f in result.new
    } == {("span-unranked-segment", "retry_backoff")}


def test_span_lint_raw_name_resolves_through_phase_map(tmp_path):
    # 'open' is used at a phase site and maps to storage_decode, which has
    # a category: no findings despite 'open' itself not being a category
    result = _run_spans(_span_project(tmp_path))
    assert not [f for f in result.new if f.symbol == "open"]


# -- e2e: cluster autopsy / timeline / slo ------------------------------------

def _start(*nodes):
    threads = [
        threading.Thread(target=node.go, daemon=True) for node in nodes
    ]
    for t in threads:
        t.start()
    return threads


def _stop(nodes, threads):
    for node in nodes:
        if node is not None:
            node.running = False
    for t in threads:
        t.join(timeout=5)


@pytest.fixture(scope="module")
def slo_cluster(tmp_path_factory):
    """Controller + one worker over two shards, with declared SLO classes,
    a fast timeline ring, and an everything-is-slow slow-query threshold."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.coordination import coordination_store
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    tmp_path = tmp_path_factory.mktemp("slo_cluster")
    url = "mem://slo_cluster"
    coordination_store(url).flushdb()
    env_overrides = {
        "BQUERYD_TPU_SLO_CLASSES": "interactive:0.5:0.999,batch:30",
        "BQUERYD_TPU_TIMELINE_INTERVAL_S": "0.2",
        "BQUERYD_TPU_SLOW_QUERY_MS": "0",
    }
    saved = {k: os.environ.get(k) for k in env_overrides}
    os.environ.update(env_overrides)

    rng = np.random.default_rng(23)
    df = pd.DataFrame({
        "g": rng.integers(0, 6, 4000).astype(np.int64),
        "v": rng.integers(-1000, 1000, 4000).astype(np.int64),
        "w": rng.random(4000),
    })
    shards = ["slo_0.bcolzs", "slo_1.bcolzs"]
    for i, name in enumerate(shards):
        ctable.fromdataframe(
            df.iloc[i::2].reset_index(drop=True), str(tmp_path / name)
        )
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.05,
    )
    worker = WorkerNode(
        coordination_url=url, data_dir=str(tmp_path),
        loglevel=logging.WARNING, restart_check=False,
        heartbeat_interval=0.1, poll_timeout=0.05,
    )
    threads = _start(controller, worker)
    wait_until(
        lambda: all(name in controller.files_map for name in shards),
        desc="shards advertised",
    )
    yield {
        "controller": controller, "worker": worker, "df": df,
        "shards": shards, "url": url,
    }
    _stop([controller, worker], threads)
    for key, value in saved.items():
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value


def test_autopsy_roundtrip_with_coverage(slo_cluster):
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING,
    )
    rpc.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    # warm second query: the attribution the bench gates on
    rpc.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    trace_id = rpc.last_trace_id  # every rpc call re-mints last_trace_id
    record = rpc.autopsy(trace_id)
    assert record["trace_id"] == trace_id
    assert record["ok"] is True
    # a warm ~10 ms micro-query's coverage is dominated by the sub-ms
    # finalize tail (fixed cost); the >= 0.95 contract is gated on the
    # bench's 400k-row sharded config where walls are real
    assert record["coverage"] >= 0.8
    segments = record["segments"]
    assert "kernel" in segments or "worker_other" in segments
    # the client folded its own deserialize wall in
    assert "client_deserialize" in segments
    assert total_of(record) == pytest.approx(record["wall_s"], abs=1e-3)
    assert record["attempts"] and record["attempts"][0]["worker"]
    # SLOW_QUERY_MS=0 records everything: the ring entry rides along, with
    # the compact attribution summary
    assert record["slow_query"]["trace_id"] == trace_id
    assert record["slow_query"]["attribution"]["coverage"] >= 0.8
    # autopsy() with no trace id serves the newest timeline
    assert rpc.autopsy()["trace_id"] == trace_id


def test_autopsy_unknown_trace_returns_none(slo_cluster):
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING,
    )
    assert rpc.autopsy("no_such_trace") is None


def test_slo_classes_and_margins_e2e(slo_cluster):
    from bqueryd_tpu.rpc import RPC

    controller = slo_cluster["controller"]
    before = controller.slo.snapshot()
    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING, slo_class="interactive",
    )
    rpc.groupby(
        slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [],
        deadline=30,
    )
    # the SLO record and the slow-query entry are the controller's
    # finalize, which runs after the client's reply was sent
    wait_until(
        lambda: controller.slow_queries.entry_for(rpc.last_trace_id),
        timeout=10, desc="the query's finalize",
    )
    after = controller.slo.snapshot()
    assert after["interactive"]["queries"] == (
        before["interactive"]["queries"] + 1
    )
    # a 30 s deadline on a sub-second query: margin positive, no violation
    assert after["interactive"]["violations"] == (
        before["interactive"]["violations"]
    )
    hist = controller.slo._hist["interactive"]
    assert hist.count >= 1
    # the slow-query entry carries the resolved class
    entry = controller.slow_queries.entry_for(rpc.last_trace_id)
    assert entry["slo_class"] == "interactive"
    # undeclared classes fold into default (no accidental cardinality)
    rpc2 = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING, slo_class="not_a_class",
    )
    rpc2.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    wait_until(
        lambda: controller.slo.snapshot()["default"]["queries"]
        > before["default"]["queries"],
        timeout=10, desc="the default class's SLO record",
    )


def test_timeline_ring_e2e(slo_cluster):
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING,
    )
    rpc.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    # wait for a snapshot taken AFTER the query completed (tests may run
    # in any order within the module)
    wait_until(
        lambda: len(slo_cluster["controller"].timeline_ring) >= 2
        and slo_cluster["controller"].timeline_ring.entries()[-1][
            "counters"
        ]["queries_completed"] >= 1,
        desc="timeline snapshot reflecting the completed query",
    )
    entries = rpc.timeline()
    assert len(entries) >= 2
    newest = entries[-1]
    assert newest["workers"] == 1
    assert newest["counters"]["queries_completed"] >= 1
    assert newest["groupby_p99_s"] is not None
    assert "default" in newest["slo"]
    assert entries[0]["ts"] <= newest["ts"]
    # PR 12: the ring doubles as capacity history — every entry carries
    # the fleet utilization/saturation slice
    capacity = newest["capacity"]
    for key in (
        "utilization", "state", "arrival_qps", "knee_qps",
        "headroom_qps", "model_drift",
    ):
        assert key in capacity
    assert capacity["state"] in ("ok", "warm", "saturated", "overloaded")


def test_debug_bundle_carries_new_sections(slo_cluster):
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING,
    )
    rpc.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    trace_id = rpc.last_trace_id  # every rpc call re-mints last_trace_id
    bundle = rpc.debug_bundle(trace_id)
    assert bundle["schema"] == "bqueryd_tpu.debug_bundle/4"
    controller_section = bundle["controller"]
    # the autopsy of the bundled trace rides inline
    assert controller_section["autopsy"]["trace_id"] == trace_id
    # micro-query coverage (see test_autopsy_roundtrip_with_coverage):
    # the sub-ms finalize tail dominates a ~10 ms warm wall
    assert controller_section["autopsy"]["coverage"] >= 0.8
    # PR 8/9 surfaces the artifact previously omitted
    assert controller_section["chaos"]["armed"] is False
    assert "injected_total" in controller_section["chaos"]
    # PR 12: the fleet capacity model rides the bundle, freshly evaluated
    capacity = controller_section["capacity"]
    assert capacity["enabled"] is True
    assert capacity["fleet"]["state"] in (
        "ok", "warm", "saturated", "overloaded"
    )
    assert "recommendations" in capacity
    assert "shards_by_holders" in controller_section["replication"]
    assert controller_section["batch_window"]["window_ms"] == 0
    assert "default" in controller_section["slo"]
    assert isinstance(controller_section["timeline_ring"], list)
    import json

    json.dumps(bundle, default=str)  # still one JSON-safe artifact


def test_bundle_member_shares_scale_slow_query_timings(
    slo_cluster, monkeypatch
):
    """A fused window's members land in the slow-query ring with
    share-scaled phase timings (not the whole bundle's wall) and their
    autopsies report the member slice."""
    from bqueryd_tpu.rpc import RPC

    controller = slo_cluster["controller"]
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "300")
    shards, url = slo_cluster["shards"], slo_cluster["url"]
    queries = [
        (shards, ["g"], [["v", "sum", "s"]], [["w", ">", 0.3]]),
        (shards, ["g"], [["v", "sum", "s"]], [["w", ">", 0.6]]),
    ]
    results, errors, trace_ids = {}, {}, {}

    def run(i, query):
        try:
            rpc = RPC(
                coordination_url=url, timeout=60, loglevel=logging.WARNING
            )
            results[i] = rpc.groupby(*query)
            trace_ids[i] = rpc.last_trace_id
        except Exception as exc:  # noqa: BLE001 - surfaced below
            errors[i] = exc

    bundles_before = controller.counters["plan_bundles"]
    threads = [
        threading.Thread(target=run, args=(i, q), daemon=True)
        for i, q in enumerate(queries)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(90)
    assert not errors
    assert controller.counters["plan_bundles"] > bundles_before
    shares = []
    for i in trace_ids:
        # the entry is the controller's finalize, after the reply
        entry = wait_until(
            lambda i=i: controller.slow_queries.entry_for(trace_ids[i]),
            timeout=10, desc="the member's finalize",
        )
        for timings in entry["phase_timings"].values():
            assert "_member_share" in timings
            shares.append(timings["_member_share"])
            # the scaled member wall is a fraction of the bundle wall
            assert timings["_total"] <= entry["wall_ms"] / 1000.0 + 1e-3
        record = controller.build_autopsy(trace_ids[i])
        assert record["bundle"]["share"] == pytest.approx(
            shares[-1], abs=1e-6
        )
        assert "bundle_demux" in record["segments"]
    # two executed members split the shared scan
    assert sum(shares) == pytest.approx(1.0, abs=1e-3)


def test_window_flight_events_recorded(slo_cluster, monkeypatch):
    """The flight ring explains staging decisions: window_open on first
    stage, window_flush with the fused-group census."""
    from bqueryd_tpu.rpc import RPC

    controller = slo_cluster["controller"]
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "100")
    rpc = RPC(
        coordination_url=slo_cluster["url"], timeout=60,
        loglevel=logging.WARNING,
    )
    rpc.groupby(slo_cluster["shards"], ["g"], [["v", "sum", "s"]], [])
    kinds = [e["kind"] for e in controller.flight.events()]
    assert "window_open" in kinds
    assert "window_flush" in kinds
    flush = [
        e for e in controller.flight.events() if e["kind"] == "window_flush"
    ][-1]
    assert flush["staged"] >= 1 and flush["groups"] >= 1
    # a solo flush fused nothing
    assert flush["fused"] == 0
    # the staged member's autopsy shows the window wait as its own segment
    record = wait_until(   # stored by the finalize, after the reply
        lambda: controller.build_autopsy(rpc.last_trace_id),
        timeout=10, desc="the query's stored timeline",
    )
    assert "batch_window_wait" in record["segments"]
