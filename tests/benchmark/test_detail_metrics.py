"""The seven per-layer metrics that read the worker's detail spans
(``utils.tracing.detail``; PR 27): each is a data file over a reader the
benchmark had.  A rehearsed traced run on the CPU backend gives each a
number; evidence from a program without the spans (the parent) gives 0.0,
or for ``worker_unnamed_ms`` the parent's unnamed time, and raises nothing.
"""

import json
import os

import pandas as pd
import pytest
from test_perf_benchmark import HEAVY, REPO, rehearse, time_limit  # noqa: F401

from benchmark import harness, readers

DETAIL_METRICS = {
    "worker_unnamed_ms": "worker", "worker_open_ms": "worker",
    "worker_cache_ms": "worker", "worker_post_ms": "worker",
    "layout_fold_ms": "mesh executor", "layout_pack_ms": "mesh executor",
    "aggregate_wait_ms": "mesh executor",
}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced rehearsal of the heavy cell, children at the program's
    defaults and not at the suite's pins."""
    patch = pytest.MonkeyPatch()
    for name in ("BQUERYD_TPU_SERVE", "BQUERYD_TPU_HOST_KERNEL_ROWS",
                 "BQUERYD_TPU_FORCE_MATMUL", "JAX_COMPILATION_CACHE_DIR"):
        patch.delenv(name, raising=False)
    pd.set_option("future.infer_string", False)
    try:
        result = rehearse(tmp_path_factory.mktemp("detail"), HEAVY, trace=True)
    finally:
        patch.undo()
    return json.loads(json.dumps(result))


@pytest.mark.parametrize("name", sorted(DETAIL_METRICS))
def test_a_traced_rehearsal_gives_the_metric_a_number(traced, name):
    assert traced["correct"] is True and traced["failed"] == 0
    metric = traced["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] >= 0.0
    # a steady query packs nothing on the loop thread since PR 28 (0.0 on the
    # chip too: ledger, PR 34), and the unnamed time is a remainder
    if name not in ("worker_unnamed_ms", "layout_pack_ms"):
        assert metric["value"] > 0.0   # the span was there to read
    # the accepted metric of the phase round the new spans keeps its meaning
    if name == "aggregate_wait_ms":
        assert traced["metrics"]["executor_aggregate_ms"]["value"] >= metric["value"]


def test_a_rehearsal_says_where_its_own_seconds_went(traced):
    """``observed["run_s"]``: the run's parts in the order it passes them,
    summing to its total, and the check's two counts beside each other."""
    run_s = dict(traced["observed"]["run_s"])
    total = run_s.pop("total")
    assert list(run_s) == ["worker_ready", "cold_pass", "warm_up", "window", "stop", "frames", "reference"]
    assert all(seconds >= 0.0 for seconds in run_s.values())
    assert sum(run_s.values()) == pytest.approx(total) and total > run_s["window"] >= 2.0
    recorded, compared = (traced["check"][k][0] for k in ("answers_recorded", "answers_compared"))
    assert recorded >= compared >= 1 and compared <= 48
    assert list(traced["check"])[-2:] == ["answers_recorded", "answers_compared"]


def test_the_unnamed_time_of_a_traced_rehearsal_is_a_small_part_of_calc(traced):
    host = traced["metrics"]
    named = sum(host[n]["value"] for n in ("worker_open_ms", "worker_cache_ms"))
    assert host["worker_unnamed_ms"]["value"] < named + host["executor_aggregate_ms"]["value"]


@pytest.mark.parametrize("name", sorted(DETAIL_METRICS))
def test_a_program_without_the_spans_reads_zero_and_raises_nothing(name):
    metric = json.load(open(os.path.join(REPO, "benchmark", "layer_metrics", name + ".json")))
    entry = next(m for m in harness.load_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]
                 if m["name"] == name)
    assert entry["layer"] == metric["layer"] == DETAIL_METRICS[name]
    assert entry["moves"] == "query_ms" and entry["workloads"] == [HEAVY]
    parent_spans = [
        {"name": "groupby", "duration_s": 0.500}, {"name": "calc", "duration_s": 0.480},
        {"name": "storage_decode", "duration_s": 0.010}, {"name": "filter", "duration_s": 0.030},
        {"name": "h2d_transfer", "duration_s": 0.060}, {"name": "kernel", "duration_s": 0.360},
        {"name": "d2h_fetch", "duration_s": 0.002}, {"name": "merge", "duration_s": 0.004},
        {"name": "reply_serialization", "duration_s": 0.001},
    ]
    ev = {
        "records": [{"ok": True, "trace_id": "t", "wall_s": 0.51,
                     "timings": {"g": {"open": 0.010, "aggregate": 0.358, "_total": 0.480}}}],
        "traces": {"t": {"spans": parent_spans}},
    }
    value = readers.read(metric, ev)
    if name == "worker_unnamed_ms":
        assert value == pytest.approx(1000 * (0.480 - 0.010 - 0.030 - 0.060 - 0.360 - 0.004 - 0.001))
    elif name == "worker_open_ms":
        assert value == pytest.approx(10.0)   # the coarse span it reads is the parent's too
    else:
        assert value == 0.0
    assert readers.read(metric, {"records": []}) is None
