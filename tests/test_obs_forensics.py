"""PR 3 observability tier: compile/device profiling, the flight recorder +
debug bundle, and worker health scoring fed back into dispatch — plus the
end-to-end acceptance path: a two-worker cluster where one worker's fake
wedge flips ``rpc.health()``, dispatch routes around it, and the pulled
``rpc.debug_bundle()`` carries the wedge event in the flight ring and a
compile-registry cache hit for the second identical query."""

import functools
import json
import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from tests.conftest import wait_until

from bqueryd_tpu import obs
from bqueryd_tpu.obs import flightrec, health, profile


# -- flight recorder ----------------------------------------------------------

def test_flight_recorder_entry_bound_and_tail():
    ring = obs.FlightRecorder(capacity=4, max_bytes=1 << 20)
    for i in range(10):
        ring.record("tick", i=i)
    events = ring.events()
    assert len(events) == 4
    assert [e["i"] for e in events] == [6, 7, 8, 9]  # oldest first
    assert ring.evictions == 6
    tail = ring.tail(limit=2)
    assert [e["i"] for e in tail] == [8, 9]
    # seq is monotonic across evictions
    assert events[-1]["seq"] == 10


def test_flight_recorder_byte_bound():
    ring = obs.FlightRecorder(capacity=10_000, max_bytes=2048)
    for i in range(100):
        ring.record("blob", data="x" * 200)
    assert ring.nbytes <= 2048
    assert ring.evictions > 0
    assert len(ring) >= 1  # never evicts down to empty


def test_flight_recorder_events_are_json_safe():
    ring = obs.FlightRecorder(capacity=8)
    ring.record("envelope", verb="groupby", token="abc", parent=None)
    json.dumps(ring.events())


def test_approx_json_bytes_tracks_size():
    small = flightrec.approx_json_bytes({"a": 1})
    big = flightrec.approx_json_bytes({"a": "x" * 1000, "b": list(range(50))})
    assert big > small
    assert big >= 1000


# -- redaction ----------------------------------------------------------------

def test_redact_paths_outside_data_root():
    obj = {
        "ok": "/srv/bcolz/taxi.bcolz",
        "bad": "traceback File \"/home/alice/secret/app.py\" line 1",
        "url": "tcp://10.1.2.3:14300",
        "rel": "taxi.bcolz",
        "nested": ["/usr/lib/python3.11/site.py", {"k": "/srv/bcolz/x"}],
    }
    out = flightrec.redact_paths(obj, ["/srv/bcolz"])
    assert out["ok"] == "/srv/bcolz/taxi.bcolz"
    assert "/home/alice" not in out["bad"]
    assert "<redacted>/app.py" in out["bad"]
    assert out["url"] == "tcp://10.1.2.3:14300"  # URLs are not paths
    assert out["rel"] == "taxi.bcolz"
    assert out["nested"][0] == "<redacted>/site.py"
    assert out["nested"][1]["k"] == "/srv/bcolz/x"


def test_redact_paths_redacts_dict_keys():
    out = flightrec.redact_paths({"/etc/passwd/shadow": 1}, [])
    assert out == {"<redacted>/shadow": 1}


def test_redact_paths_allows_prefix_not_substring():
    # /srv/bcolz-evil must NOT ride the /srv/bcolz allowance
    out = flightrec.redact_paths(
        {"a": "/srv/bcolz-evil/file.bin"}, ["/srv/bcolz"]
    )
    assert out["a"] == "<redacted>/file.bin"


# -- bundle assembly ----------------------------------------------------------

def test_build_bundle_schema_partial_and_roundtrip():
    now = 1000.0
    bundle = flightrec.build_bundle(
        {"address": "tcp://x", "flight": []},
        {
            "w-live": {"data": {"flight": []}, "ts": now - 1.0,
                       "registered": True},
            "w-stale": {"data": {"flight": []}, "ts": now - 500.0,
                        "registered": False},
            "w-silent": {"data": None, "ts": None, "registered": True},
        },
        trace_id="t1",
        now=now,
    )
    assert list(bundle) == [
        "schema", "generated_ts", "trace_id", "controller", "workers",
        "partial",
    ]
    assert bundle["schema"] == flightrec.BUNDLE_SCHEMA
    assert bundle["trace_id"] == "t1"
    assert bundle["partial"] == ["w-silent"]
    assert bundle["workers"]["w-live"]["stale"] is False
    assert bundle["workers"]["w-stale"]["stale"] is True
    assert bundle["workers"]["w-stale"]["registered"] is False
    assert bundle["workers"]["w-silent"]["snapshot"] is None
    # round-trips through json
    assert json.loads(json.dumps(bundle)) == bundle


def test_build_bundle_redacts_foreign_paths():
    bundle = flightrec.build_bundle(
        {"flight": [{"kind": "error", "error": "File \"/root/app/x.py\""}]},
        {},
        allowed_path_prefixes=["/srv/data"],
    )
    assert "/root/app" not in json.dumps(bundle)
    assert "<redacted>/x.py" in bundle["controller"]["flight"][0]["error"]


# -- compile profiling --------------------------------------------------------

def test_instrument_counts_hits_misses_and_cost():
    import jax
    import jax.numpy as jnp

    prof = profile._reset_for_tests()
    try:

        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            return x * n

        g = profile.instrument("test.scale", f)
        # explicit dtype: the suite enables x64 (ops import), which would
        # otherwise shift the default dtype and the signature under test
        g(jnp.zeros(16, dtype=jnp.float32), n=3)
        g(jnp.zeros(16, dtype=jnp.float32), n=3)
        g(jnp.zeros(32, dtype=jnp.float32), n=3)
        snap = prof.snapshot()
        assert snap["jit_cache_misses"] == 2
        assert snap["jit_cache_hits"] == 1
        assert sum(snap["compile_seconds"]["counts"]) == 2
        by_sig = {p["signature"]: p for p in snap["programs"]}
        entry = by_sig["test.scale(float32[16];3)"]
        assert entry["calls"] == 2
        assert entry["compiles"] == 1
        assert entry["jit_cache_hits"] == 1
        assert entry["flops"] is not None and entry["flops"] > 0
        assert entry["bytes_accessed"] is not None
        json.dumps(snap)
    finally:
        profile._reset_for_tests()


def test_instrument_respects_kill_switch_and_traces():
    import jax
    import jax.numpy as jnp

    prof = profile._reset_for_tests()
    try:
        g = profile.instrument("test.inc", jax.jit(lambda x: x + 1))
        obs.set_enabled(False)
        try:
            g(jnp.zeros(4))
        finally:
            obs.set_enabled(True)
        assert prof.snapshot()["programs_tracked"] == 0
        # under an outer trace the wrapper passes straight through
        outer = jax.jit(lambda x: g(x))
        outer(jnp.zeros(4))
        assert prof.snapshot()["programs_tracked"] == 0
        # a plain (non-jitted) callable is also a passthrough
        plain = profile.instrument("test.plain", lambda x: x)
        assert plain(5) == 5
    finally:
        profile._reset_for_tests()


def test_program_registry_evicts_least_recently_called(monkeypatch):
    """Past MAX_PROGRAMS the registry drops the LRU shape, not the one that
    just arrived (the regression would freeze it at the first 256 shapes)."""
    prof = profile._reset_for_tests()
    try:
        monkeypatch.setattr(profile, "MAX_PROGRAMS", 4)

        class FakeJit:
            def lower(self, *a, **k):
                raise RuntimeError("no cost analysis in this test")

        fake = FakeJit()
        for i in range(6):
            prof.record_call(
                f"prog{i}", fake, (), {}, compiled=True, duration_s=0.01
            )
        sigs = {p["name"] for p in prof.snapshot()["programs"]}
        assert sigs == {"prog2", "prog3", "prog4", "prog5"}
        assert prof.programs_evicted == 2
        # re-calling a survivor keeps it fresh; the next new shape evicts
        # the actual LRU instead
        prof.record_call("prog2", fake, (), {}, compiled=False,
                         duration_s=0.0)
        prof.record_call("prog6", fake, (), {}, compiled=True,
                         duration_s=0.01)
        sigs = {p["name"] for p in prof.snapshot()["programs"]}
        assert "prog2" in sigs and "prog3" not in sigs
    finally:
        profile._reset_for_tests()


def test_compile_cache_info_reads_jax_config(tmp_path):
    """compile_cache_info() reports what jax.config HOLDS (ops/__init__.py
    placed it at import; tests/conftest.py pins the cache off) — it does
    not re-derive the decision from the environment."""
    import jax

    from bqueryd_tpu import ops  # noqa: F401  (places the cache config)

    assert profile.compile_cache_info() == {
        "enabled": False, "path": None, "writable": False,
    }
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_enable_compilation_cache,
    )
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        assert profile.compile_cache_info() == {
            "enabled": True, "path": str(tmp_path), "writable": True,
        }
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_enable_compilation_cache", saved[1])


def test_runtime_versions_reports_jax():
    import jax

    versions = profile.runtime_versions()
    assert versions["jax"] == jax.__version__
    assert "jaxlib" in versions


# -- health scoring -----------------------------------------------------------

def _snap(count, total):
    return {
        health.LATENCY_FAMILY: [
            {"labels": {}, "buckets": [1.0], "counts": [count], "sum": total}
        ]
    }


def test_health_scorer_error_rate_degrades():
    scorer = obs.HealthScorer(min_errors=3, error_rate_threshold=0.25)
    scorer.observe("w1", _snap(0, 0.0), errors=0, now=100.0)
    scorer.observe("w1", _snap(0, 0.0), errors=10, now=110.0)
    statuses = scorer.statuses()
    assert statuses["w1"]["status"] == obs.STATUS_DEGRADED
    assert "error rate" in statuses["w1"]["reason"]


def test_health_scorer_wedged_flag_wins():
    scorer = obs.HealthScorer()
    scorer.observe("w1", _snap(5, 0.1), wedged=True, now=100.0)
    assert scorer.status("w1") == obs.STATUS_WEDGED


def test_health_scorer_latency_outlier_vs_fleet():
    scorer = obs.HealthScorer(min_samples=5, latency_factor=3.0)
    for wid, per_query in (("fast1", 0.01), ("fast2", 0.012), ("slow", 0.5)):
        scorer.observe(wid, _snap(0, 0.0), now=100.0)
        scorer.observe(wid, _snap(10, 10 * per_query), now=110.0)
    statuses = scorer.statuses()
    assert statuses["fast1"]["status"] == obs.STATUS_OK
    assert statuses["slow"]["status"] == obs.STATUS_DEGRADED
    assert "fleet median" in statuses["slow"]["reason"]


def test_health_scorer_young_worker_is_ok_and_remove():
    scorer = obs.HealthScorer()
    scorer.observe("w1", _snap(1, 5.0), now=100.0)  # one sample: no window
    assert scorer.status("w1") == obs.STATUS_OK
    assert scorer.status("unknown") == obs.STATUS_OK
    scorer.remove("w1")
    assert scorer.statuses() == {}


def test_health_scorer_statuses_memo_invalidates_on_observe():
    """statuses() is memoized for the dispatch hot path; a new observation
    must invalidate the cache, not serve the stale verdict."""
    scorer = obs.HealthScorer()
    scorer.observe("w1", _snap(0, 0.0), now=100.0)
    first = scorer.statuses()
    assert scorer.statuses() is first  # cache hit between observations
    scorer.observe("w1", _snap(0, 0.0), wedged=True, now=110.0)
    assert scorer.statuses()["w1"]["status"] == obs.STATUS_WEDGED


def test_health_routing_env_gate(monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_HEALTH_ROUTING", raising=False)
    assert health.routing_enabled()
    monkeypatch.setenv("BQUERYD_TPU_HEALTH_ROUTING", "0")
    assert not health.routing_enabled()


# -- byte-bounded rings (satellite) -------------------------------------------

def test_trace_store_byte_bound_and_latest():
    store = obs.TraceStore(capacity=1000, max_bytes=4096)
    for i in range(50):
        store.put(f"t{i}", {"trace_id": f"t{i}", "pad": "x" * 300})
    assert store.nbytes <= 4096
    assert store.evictions > 0
    assert len(store) < 50
    assert store.get("t0") is None
    assert store.latest()["trace_id"] == "t49"


def test_trace_store_update_does_not_leak_bytes():
    store = obs.TraceStore(capacity=10, max_bytes=1 << 20)
    for _ in range(20):
        store.put("same", {"trace_id": "same", "pad": "x" * 100})
    assert len(store) == 1
    assert store.nbytes == flightrec.approx_json_bytes(store.get("same"))


def test_slow_query_log_byte_bound(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_SLOW_QUERY_MS", "0")
    log = obs.SlowQueryLog(capacity=1000, max_bytes=4096)
    for i in range(50):
        log.maybe_record(1.0, {"trace_id": f"t{i}", "pad": "y" * 300})
    assert log.nbytes <= 4096
    assert log.evictions > 0
    assert 1 <= len(log) < 50


# -- registry adoption + README-coverage lint (satellite) ---------------------

def test_registry_register_adopts_shared_instance():
    from bqueryd_tpu.obs.metrics import Histogram

    shared = Histogram("bqueryd_tpu_shared_seconds", "shared")
    reg_a, reg_b = obs.MetricsRegistry(), obs.MetricsRegistry()
    assert reg_a.register(shared) is shared
    assert reg_a.register(shared) is shared  # idempotent
    reg_b.register(shared)
    shared.observe(0.01)
    assert "bqueryd_tpu_shared_seconds_count 1" in reg_a.render()
    assert "bqueryd_tpu_shared_seconds_count 1" in reg_b.render()
    with pytest.raises(ValueError):
        reg_a.register(Histogram("bqueryd_tpu_shared_seconds", "other"))


def test_readme_coverage_lint_flags_undocumented():
    from bqueryd_tpu.obs.metrics import readme_coverage_problems

    reg = obs.MetricsRegistry()
    reg.counter("bqueryd_tpu_documented_total", "x")
    reg.counter("bqueryd_tpu_mystery_total", "x")
    problems = readme_coverage_problems(
        [reg], "docs mention `bqueryd_tpu_documented_total` only"
    )
    assert problems == [
        "bqueryd_tpu_mystery_total: registered but missing from the README "
        "metrics table"
    ]


# -- end-to-end: the acceptance path ------------------------------------------

NR_SHARDS = 3


def _taxi_df(n=3_000, seed=7):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "total_amount": rng.gamma(2.5, 8.0, n),
        }
    )


@pytest.fixture(scope="module")
def forensics_cluster(tmp_path_factory):
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = _taxi_df()
    root = tmp_path_factory.mktemp("forensics_cluster")
    ctable.fromdataframe(df, str(root / "taxi.bcolz"))
    for i in range(NR_SHARDS):
        ctable.fromdataframe(
            df.iloc[i::NR_SHARDS], str(root / f"taxi-{i}.bcolzs")
        )
    url = f"mem://forensics-{os.urandom(4).hex()}"
    # the result cache would serve the second identical query without any
    # kernel dispatch — the compile-registry acceptance check needs the
    # program to actually run twice
    old_cache = os.environ.get("BQUERYD_TPU_RESULT_CACHE_BYTES")
    os.environ["BQUERYD_TPU_RESULT_CACHE_BYTES"] = "0"
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=str(root),
        heartbeat_interval=0.2,
        dead_worker_timeout=2.0,
    )
    workers = [
        WorkerNode(
            coordination_url=url,
            data_dir=str(root),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.1,
        )
        for _ in range(2)
    ]
    threads = [
        threading.Thread(target=node.go, daemon=True)
        for node in [controller] + workers
    ]
    for t in threads:
        t.start()
    wait_until(
        lambda: len(controller.files_map.get("taxi.bcolz", ())) == 2,
        desc="both workers advertising",
    )
    rpc = RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
    yield {
        "rpc": rpc,
        "controller": controller,
        "workers": workers,
        "df": df,
    }
    for node in [controller] + workers:
        node.running = False
    for t in threads:
        t.join(timeout=5)
    if old_cache is None:
        os.environ.pop("BQUERYD_TPU_RESULT_CACHE_BYTES", None)
    else:
        os.environ["BQUERYD_TPU_RESULT_CACHE_BYTES"] = old_cache


def _groupby(rpc):
    return rpc.groupby(
        ["taxi.bcolz"],
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        [],
    )


def test_e2e_wedge_health_routing_and_debug_bundle(forensics_cluster):
    """ACCEPTANCE: wedge a fake worker -> rpc.health() flips it off ok ->
    dispatch routes around it -> the debug bundle carries the wedge event in
    the flight ring and a compile-registry cache hit for the second
    identical query."""
    rpc = forensics_cluster["rpc"]
    controller = forensics_cluster["controller"]
    healthy, wedged = forensics_cluster["workers"]

    # fake-wedge ONE worker of the in-process cluster: its WRMs advertise
    # the latch without touching the process-global devicehealth state
    wedged._backend_wedged = lambda: True

    def wedged_status():
        statuses = rpc.health()["workers"]
        return statuses.get(wedged.worker_id, {}).get("status")

    status = wait_until(
        lambda: wedged_status() in ("wedged", "degraded") and wedged_status(),
        desc="health scorer flags the wedged worker",
    )
    assert status == "wedged"
    assert rpc.health()["workers"][healthy.worker_id]["status"] == "ok"

    # the controller's flight ring logged the latch the moment the fleet
    # view learned it (never gated)
    assert any(
        e["kind"] == "worker_wedged" and e["worker"] == wedged.worker_id
        for e in controller.flight.events()
    )

    # dispatch routes around the wedged worker: sequential identical
    # queries all land on the healthy one
    base_healthy = healthy.groupby_queries.value
    base_wedged = wedged.groupby_queries.value
    snap_before = profile.profiler().snapshot(max_programs=1_000_000)
    hits_before = snap_before["jit_cache_hits"]
    # per-signature baseline: the process-global registry carries history
    # from every earlier test in this process; the acceptance claim is that
    # OUR second identical query registers as a hit on ITS program shape
    hits_by_sig = {
        p["signature"]: p["jit_cache_hits"] for p in snap_before["programs"]
    }
    expected = (
        forensics_cluster["df"]
        .groupby("payment_type")["total_amount"]
        .sum()
    )
    for _ in range(3):
        result = _groupby(rpc)
        got = result.set_index("payment_type")["total_amount"]
        assert np.allclose(got.sort_index(), expected.sort_index())
    trace_id = rpc.last_trace_id
    assert healthy.groupby_queries.value - base_healthy == 3
    assert wedged.groupby_queries.value == base_wedged
    assert controller.counters["health_avoided_dispatches"] >= 1

    # the repeat queries hit the jit cache (result cache is disabled in
    # this fixture, so the program really ran each time)
    assert profile.profiler().snapshot()["jit_cache_hits"] > hits_before

    # pull the bundle once the workers' WRM debug slices (with the fresh
    # compile registry numbers) have been absorbed
    def bundle_ready():
        bundle = rpc.debug_bundle(trace_id)
        workers = bundle["workers"]
        snap = (workers.get(healthy.worker_id) or {}).get("snapshot")
        if not snap:
            return None
        if snap["compile"]["jit_cache_hits"] <= hits_before:
            return None
        return bundle

    bundle = wait_until(bundle_ready, desc="bundle with fresh debug slices")
    assert bundle["schema"] == "bqueryd_tpu.debug_bundle/4"
    assert bundle["trace_id"] == trace_id
    # flight ring: the wedge event is in the artifact, alongside the
    # normal-flow envelope/dispatch/outcome events
    kinds = {
        (e["kind"], e.get("worker"))
        for e in bundle["controller"]["flight"]
    }
    assert ("worker_wedged", wedged.worker_id) in kinds
    bare_kinds = {k for k, _ in kinds}
    assert {"rpc", "dispatch", "query_done"} <= bare_kinds
    # compile registry: cache hit on the repeated identical query — some
    # program shape's hit count moved past its pre-query baseline
    compile_snap = bundle["workers"][healthy.worker_id]["snapshot"]["compile"]
    assert compile_snap["jit_cache_hits"] > hits_before
    assert any(
        p["jit_cache_hits"] > hits_by_sig.get(p["signature"], 0)
        for p in compile_snap["programs"]
    )
    # trace timeline rode along, spans intact
    assert bundle["controller"]["trace"]["trace_id"] == trace_id
    assert any(
        s["name"] == "kernel" for s in bundle["controller"]["trace"]["spans"]
    )
    # health section agrees with rpc.health()
    assert (
        bundle["controller"]["health"][wedged.worker_id]["status"] == "wedged"
    )
    # the whole artifact is one JSON file
    json.dumps(bundle)
    # both workers reported: nothing partial
    assert bundle["partial"] == []
    # worker flight rings carry the envelope/work events
    worker_kinds = {
        e["kind"]
        for e in bundle["workers"][healthy.worker_id]["snapshot"]["flight"]
    }
    assert {"envelope", "work_done"} <= worker_kinds


def test_e2e_info_reports_runtime_and_compile_cache(forensics_cluster):
    import jax

    rpc = forensics_cluster["rpc"]
    info = rpc.info()
    assert info["runtime"]["jax"] == jax.__version__
    assert set(info["compile_cache"]) == {"enabled", "path", "writable"}
    # per-worker versions gossiped via WRM debug slices
    wait_until(
        lambda: any(
            (v or {}).get("jax") == jax.__version__
            for v in rpc.info()["worker_runtime"].values()
        ),
        desc="worker runtime versions absorbed",
    )


def test_e2e_live_registries_pass_lints(forensics_cluster):
    """Registry lint + the README-coverage extension run clean on REAL node
    registries — every registered family is documented."""
    controller = forensics_cluster["controller"]
    workers = forensics_cluster["workers"]
    registries = [controller.metrics] + [w.metrics for w in workers]
    for registry in registries:
        assert registry.lint() == []
    from bqueryd_tpu.obs.metrics import readme_coverage_problems

    readme = open(
        os.path.join(os.path.dirname(__file__), "..", "README.md")
    ).read()
    assert readme_coverage_problems(registries, readme) == []


def test_e2e_trace_carries_device_memory_tags_when_available(
    forensics_cluster,
):
    """On backends with memory_stats (TPU) the calc root span is tagged with
    per-query device memory; on CPU the tags are simply absent — assert the
    span schema stays intact either way."""
    rpc = forensics_cluster["rpc"]
    _groupby(rpc)
    timeline = rpc.trace(rpc.last_trace_id)
    calc = next(s for s in timeline["spans"] if s["name"] == "calc")
    tags = calc.get("tags")
    if tags is not None and "device_hbm_watermark_bytes" in tags:
        assert tags["device_hbm_watermark_bytes"] >= 0
        assert tags["device_peak_delta_bytes"] >= 0


def test_e2e_sigusr1_dump_writes_bundle(forensics_cluster, tmp_path,
                                        monkeypatch):
    controller = forensics_cluster["controller"]
    monkeypatch.setenv("BQUERYD_TPU_DEBUG_DIR", str(tmp_path))
    controller._dump_debug_signal()
    dumps = list(tmp_path.glob("bqueryd_tpu_debug_controller_*.json"))
    assert len(dumps) == 1
    bundle = json.loads(dumps[0].read_text())
    assert bundle["schema"] == "bqueryd_tpu.debug_bundle/4"


def test_e2e_partial_bundle_after_worker_death(forensics_cluster):
    """A dead peer degrades the bundle, never fails it: its last absorbed
    snapshot still ships, marked unregistered."""
    rpc = forensics_cluster["rpc"]
    controller = forensics_cluster["controller"]
    wedged = forensics_cluster["workers"][1]
    wedged.running = False
    wait_until(
        lambda: wedged.worker_id not in controller.worker_map,
        desc="dead worker culled",
    )
    bundle = rpc.debug_bundle()
    entry = bundle["workers"][wedged.worker_id]
    assert entry["registered"] is False
    assert entry["snapshot"] is not None  # last words survive
    json.dumps(bundle)
