"""The cell ``taxi-1chip-distinct.adhoc-distinct``: the walkthrough's month
on one chip asked how many distinct dropoff zones each pickup zone has
(265 groups, with the int64 fare sum beside it) and how many distinct
fares each zone pair has (70 225 groups; its (group, value) space is past
2^31).  Files over what the harness had: a configuration, its mix, one
data file over the ``span_self_time`` reader, and its tiny twin, found by
the twin's own ``twin_of``.  Rehearsed, traced and with the float32
control, on the CPU backend at the twin's size on ONE virtual device, so
the rehearsal takes the path the chip takes: one dispatch group, one
launch over the unit, counts on the wire.

Only the cell's own ``end_to_end`` is pinned exactly; everything else is
held by membership, so a later PR can add a cell to a shared metric, or a
metric to this cell, with new files alone.
"""

import json
import os

import pandas as pd
import pytest
from test_perf_benchmark import BENCH, DATA, HERE, TINY, rehearse, time_limit  # noqa: F401

from benchmark import harness, readers, reference, traffic

DISTINCT = "taxi-1chip-distinct.adhoc-distinct"
SHARED = ["client_ms", "controller_ms", "route_changes", "worker_host_ms",
          "compiles_in_window", "executor_align_ms", "executor_aggregate_ms",
          "groupby_roofline", "device_idle_share"]
SHAPES = {"zone_dropoffs": (["PULocationID"], ["count_distinct", "sum"]),
          "zonepair_fares": (["PULocationID", "DOLocationID"], ["count_distinct"])}
#: what reads the device's busy time is silent on the CPU backend
SILENT_ON_CPU = {"groupby_roofline", "device_idle_share", "worker_host_ms"}


def entries():
    return {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}


def test_the_distinct_cell_finds_its_files():
    cell = harness.load_cell(DISTINCT)
    config, mix = cell["config"], cell["mix"]
    assert cell["chips"] == 1 and config["name"] == "taxi-1chip-distinct"
    assert mix["name"] == "adhoc-distinct" and mix["check_at_most"] == 48
    assert (mix["check_every"], mix["settle_allow"], mix["warmup_max_s"]) == (3, 0, 120)
    assert [s["repeat_share"] for s in mix["streams"]] == [0.0]
    assert set(cell["end_to_end"]) == {"query_ms", "setup_s"}
    assert set(SHARED) | {"distinct_wait_ms"} <= set(cell["per_layer"])
    assert not any(m["args"].get("over") == "cold" for m in cell["per_layer"].values())
    assert list(traffic.shapes_of(mix)) == list(SHAPES) == list(config["queries"])
    for shape, (keys, ops) in SHAPES.items():
        query = config["queries"][shape]
        assert query["files"] == "all" and query["groupby"] == keys
        assert [a[1] for a in query["aggs"]] == ops and query["where"] == []
    # taxi-1chip's deployment but for what is asked of it
    base = harness.load_cell("taxi-1chip.adhoc-heavy")["config"]
    for key in ("columns", "published_columns", "rows", "shards", "shards_per_month",
                "months", "assumed", "slot", "chips", "workers", "reduced"):
        assert config[key] == base[key], key
    guarantees = dict(config["guarantees"])
    assert "once however many shard files hold it" in guarantees.pop("distinct_counts")
    assert guarantees == base["guarantees"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "taxi-1chip-distinct")
    assert set(config["reduced"]) == set(entry["reduced"]) == {"columns", "column_types"}
    metrics = entries()
    for name in ["query_ms", "distinct_wait_ms"] + SHARED:
        assert DISTINCT in metrics[name]["workloads"]
    assert DISTINCT not in metrics["cold_query_s"]["workloads"]
    assert all(metrics[name]["moves"] == "query_ms" for name in SHARED + ["distinct_wait_ms"])


def test_the_reference_answers_every_query_of_the_cell():
    for path in (os.path.join(DATA, "configs", "taxi-1chip-distinct.json"),
                 os.path.join(HERE, TINY["taxi-1chip-distinct"])):
        assert reference.unanswerable(harness.load_json(path)) == []


def test_the_new_metric_is_a_file_over_a_reader_the_benchmark_had():
    metric = harness.load_json(os.path.join(DATA, "layer_metrics", "distinct_wait_ms.json"))
    assert (metric["reader"], metric["args"]) == ("span_self_time", {"span": "distinct_wait"})
    assert metric["reader"] in readers.READERS
    entry = entries()["distinct_wait_ms"]
    assert entry["layer"] == metric["layer"] == "kernels"
    assert entry["unit"] == metric["unit"] == "ms" and entry["source"] == "program_span"
    assert entry["workloads"] == [DISTINCT]
    # a program without the span (the parent) reads 0.0 and raises nothing;
    # no evidence, nothing
    ev = {"records": [{"ok": True, "trace_id": "t", "wall_s": 0.3,
                       "timings": {"g": {"aggregate": 0.04, "_total": 0.28}}}],
          "traces": {"t": {"spans": [{"name": "calc", "duration_s": 0.28},
                                     {"name": "aggregate_wait", "duration_s": 0.03}]}}}
    assert readers.read(metric, ev) == 0.0
    assert readers.read(metric, {"records": []}) is None
    ev["traces"]["t"]["spans"].append({"name": "distinct_wait", "duration_s": 0.029})
    assert readers.read(metric, ev) == pytest.approx(29.0)


def _shipped_home(tmp_path):
    """The benchmark as shipped, only each configuration's file swapped for
    its tiny twin."""
    bench = json.loads(json.dumps(BENCH))
    for config in bench["configs"]:
        config["file"] = os.path.join(HERE, TINY[config["name"]])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.makedirs(tmp_path / "benchmark")
    for sub in ("traffic", "layer_metrics"):
        os.symlink(os.path.join(DATA, sub), tmp_path / "benchmark" / sub)
    return str(tmp_path)


def _rehearse_on_one_device(tmp_path, **kw):
    patch = pytest.MonkeyPatch()
    for name in ("BQUERYD_TPU_SERVE", "BQUERYD_TPU_HOST_KERNEL_ROWS",
                 "BQUERYD_TPU_FORCE_MATMUL", "JAX_COMPILATION_CACHE_DIR"):
        patch.delenv(name, raising=False)
    pd.set_option("future.infer_string", False)
    try:
        home = _shipped_home(tmp_path)
        result = rehearse(tmp_path, DISTINCT, home=home, devices=1, **kw)
    finally:
        patch.undo()
    return json.loads(json.dumps(result))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced rehearsal of the cell as shipped, with the float32
    control, children at the program's defaults."""
    return _rehearse_on_one_device(
        tmp_path_factory.mktemp("distinct"), trace=True, control=True)


def test_the_traced_rehearsal_is_correct_by_one_sort_over_the_unit(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["device"]["count"] == 1
    assert traced["attempted"] > 3 and traced["check"]["answers_compared"][0] > 0
    assert traced["observed"]["answer_source"] == {"recompute": traced["attempted"]}
    assert set(traced["observed"]["routes"]) == {f"{shape}:auto>sort" for shape in SHAPES}
    check = traced["check"]
    assert check["int_mismatch"] == [0, 0] and check["unanswered"] == [0, 0]
    assert set(traced["metrics"]) >= (set(SHARED) | {"distinct_wait_ms"}) - SILENT_ON_CPU
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert traced["metrics"]["route_changes"]["value"] == 0


def test_the_traced_rehearsal_gives_the_new_metric_a_number(traced):
    metric = traced["metrics"]["distinct_wait_ms"]
    assert metric["unit"] == "ms" and metric["value"] > 0.0
    assert traced["metrics"]["executor_aggregate_ms"]["value"] >= metric["value"]


def test_the_float32_control_fails_the_check(traced):
    """The distinct counts pass through the control unchanged; the int64
    fare sum beside them does not."""
    assert traced["control"]["fails"] is True
    rows = {name: (number, limit) for name, number, limit in traced["control"]["rows"]}
    assert rows["int_mismatch"][0] > rows["int_mismatch"][1] == 0


def _one_distinct_count_off(rpc, args):
    answer = rpc.groupby(*args)
    if args[2][0][1] == "count_distinct" and len(answer):
        answer = answer.copy()
        column = args[2][0][2]
        answer.loc[answer.index[0], column] += 1
    return answer


def test_a_rehearsal_with_one_distinct_count_off_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "ask", _one_distinct_count_off)
    result = _rehearse_on_one_device(tmp_path)
    assert result["correct"] is False and result["failed"] == 0
    assert result["check"]["int_mismatch"][0] > 0
