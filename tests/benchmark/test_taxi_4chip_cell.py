"""The cell ``taxi-4chip.adhoc-heavy`` (PR 34) as ``BENCHMARK.json`` ships
it: four chips, ``query_ms`` and ``setup_s`` and no cold pass, the nine
per-layer metrics it shares with the one-chip cell and ten of its own,
each a data file over a reader the benchmark had.  Seven of the ten are
the detail-span metrics of PR 27 under a second name (the originals are
pinned to the one-chip cell by ``test_detail_metrics.py``); the pairs are
held together here.  The shipped cell is rehearsed, traced, on four
virtual devices of the CPU backend at the tiny configuration's size.

Only the cell's own ``end_to_end`` is pinned exactly.  Everything else is
held by membership, so a later PR can add a cell to a shared metric, or a
metric to this cell, with new files alone.
"""

import json
import os

import pandas as pd
import pytest
from test_perf_benchmark import BENCH, DATA, HERE, TINY, rehearse, time_limit  # noqa: F401

from benchmark import harness, readers

CELL = "taxi-4chip.adhoc-heavy"
SHARED = ["client_ms", "controller_ms", "route_changes", "worker_host_ms",
          "compiles_in_window", "executor_align_ms", "executor_aggregate_ms",
          "groupby_roofline", "device_idle_share"]
DOUBLES = {name + ".mesh": name for name in (
    "worker_open_ms", "worker_cache_ms", "worker_unnamed_ms", "worker_post_ms",
    "layout_fold_ms", "layout_pack_ms", "aggregate_wait_ms")}
#: name -> (reader, args, layer, unit)
NEW = {
    "worker_table_keys_ms": ("span_self_time", {"span": "table_keys"}, "worker", "ms"),
    "executor_fetch_ms": ("phase_mean", {"phase": "fetch"}, "mesh executor", "ms"),
    # the one-chip cell's peak-bytes reading, moving ``query_ms`` here: the
    # cell makes no cold pass, and a working set that thrashes (PR 29) shows
    # in the steady query
    "hbm_peak_gb.mesh": ("counter_value", {"counter": "peak_bytes_in_use", "scale": 1e-09},
                         "device", "GB"),
}
OWN = sorted(set(DOUBLES) | set(NEW))
#: what reads the device's busy time or its memory is silent on the CPU backend
SILENT_ON_CPU = {"groupby_roofline", "device_idle_share", "worker_host_ms", "hbm_peak_gb.mesh"}


def metric_file(name):
    return harness.load_json(os.path.join(DATA, "layer_metrics", name + ".json"))


def test_the_shipped_cell_takes_four_chips_and_makes_no_cold_pass():
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 4 and cell["config"]["name"] == "taxi-4chip"
    assert cell["mix"]["name"] == "adhoc-heavy"
    assert set(cell["end_to_end"]) == {"query_ms", "setup_s"}
    assert set(SHARED) | set(OWN) <= set(cell["per_layer"])
    # nothing of the cell asks ``run_cell`` for a cold pass (``wants_cold``)
    assert not any(m["args"].get("over") == "cold" for m in cell["per_layer"].values())
    entry = next(c for c in BENCH["configs"] if c["name"] == "taxi-4chip")
    # every cut the file states is listed, and what else the entry lists (the
    # equal months) the file states under ``assumed``
    assert set(cell["config"]["reduced"]) <= set(entry["reduced"])
    assert set(entry["reduced"]) - set(cell["config"]["reduced"]) <= set(cell["config"]["assumed"])
    assert cell["config"]["rows"] == 4 * 10_906_858 and cell["config"]["shards"] == 40
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ["query_ms"] + SHARED + OWN:
        assert CELL in metrics[name]["workloads"]
    for name in ("cold_query_s", "storage_decode_s", "hbm_peak_gb"):
        assert CELL not in metrics[name]["workloads"]
    assert all(metrics[name]["moves"] == "query_ms" for name in SHARED + OWN)


@pytest.mark.parametrize("double", sorted(DOUBLES))
def test_a_mesh_double_is_its_original_but_for_the_name(double):
    original, mine = metric_file(DOUBLES[double]), metric_file(double)
    assert mine.pop("name") == double and original.pop("name") == DOUBLES[double]
    assert mine == original
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    keys = ("unit", "better", "source", "layer", "moves")
    assert [entries[double][k] for k in keys] == [entries[DOUBLES[double]][k] for k in keys]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_a_file_over_a_reader_the_benchmark_had(name):
    reader, args, layer, unit = NEW[name]
    metric = metric_file(name)
    assert (metric["reader"], metric["args"], metric["layer"]) == (reader, args, layer)
    assert reader in readers.READERS
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert entry["layer"] == layer and entry["unit"] == metric["unit"] == unit
    # a program without the span or the phase reads 0.0 and raises nothing;
    # no evidence at all, or a backend that keeps no memory statistics,
    # reads nothing
    ev = {"records": [{"ok": True, "trace_id": "t", "wall_s": 0.3,
                       "timings": {"g": {"aggregate": 0.04, "_total": 0.28}}}],
          "traces": {"t": {"spans": [{"name": "calc", "duration_s": 0.28}]}},
          "counters_after": {"peak_bytes_in_use": 0}}
    assert readers.read(metric, ev) == (None if reader == "counter_value" else 0.0)
    assert readers.read(metric, {"records": []}) is None
    ev["counters_after"]["peak_bytes_in_use"] = 320_898_560
    if reader == "counter_value":
        assert readers.read(metric, ev) == pytest.approx(0.32089856)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced rehearsal of the cell as shipped (only each
    configuration's file swapped for its tiny twin), on four virtual
    devices, children at the program's defaults and not at the suite's pins."""
    home = tmp_path_factory.mktemp("taxi4")
    bench = json.loads(json.dumps(BENCH))
    for config in bench["configs"]:
        config["file"] = os.path.join(HERE, TINY[config["name"]])
    (home / "BENCHMARK.json").write_text(json.dumps(bench))
    os.makedirs(home / "benchmark")
    for sub in ("traffic", "layer_metrics"):
        os.symlink(os.path.join(DATA, sub), home / "benchmark" / sub)
    patch = pytest.MonkeyPatch()
    for name in ("BQUERYD_TPU_SERVE", "BQUERYD_TPU_HOST_KERNEL_ROWS",
                 "BQUERYD_TPU_FORCE_MATMUL", "JAX_COMPILATION_CACHE_DIR"):
        patch.delenv(name, raising=False)
    pd.set_option("future.infer_string", False)
    try:
        result = rehearse(home, CELL, trace=True, devices=4, home=str(home))
    finally:
        patch.undo()
    return json.loads(json.dumps(result))


def test_the_traced_rehearsal_on_four_devices_is_correct(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] > 3 and traced["check"]["answers_compared"][0] >= 1
    assert traced["device"]["count"] == 4 and traced["device"]["platform"] == "cpu"
    assert traced["observed"]["answer_source"] == {"recompute": traced["attempted"]}
    assert set(traced["metrics"]) >= (set(SHARED) | set(OWN)) - SILENT_ON_CPU


@pytest.mark.parametrize("name", sorted(set(OWN) - SILENT_ON_CPU))
def test_the_traced_rehearsal_gives_each_new_metric_a_number(traced, name):
    metric = traced["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] >= 0.0
    if name not in ("layout_pack_ms.mesh", "layout_fold_ms.mesh", "worker_unnamed_ms.mesh"):
        assert metric["value"] > 0.0   # the span or the phase was there to read
    if name == "aggregate_wait_ms.mesh":
        assert traced["metrics"]["executor_aggregate_ms"]["value"] >= metric["value"]
