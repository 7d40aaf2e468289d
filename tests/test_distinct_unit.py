"""``count_distinct`` over a whole unit: one two-key device sort where one
payload is the whole answer, value sets everywhere else.

The kernel (``ops.groupby_count_distinct``, ``partial_tables``' distinct
part) is held to an ``np.unique`` oracle; the path — controller dispatch
group, worker, one-device mesh executor — to the benchmark's plain
reference over a ten-shard tiny taxi month; and the wire rule to the
placements and meshes that decide it: counts only where the controller
dispatched exactly one group holding every file the query keeps and the
mesh is one device, sets (unioned exactly downstream) in every other case.
"""

import json
import logging
import os
import threading

import numpy as np
import pytest
from conftest import wait_until

from benchmark import data, reference, traffic
from bqueryd_tpu import ops
from bqueryd_tpu.messages import CalcMessage
from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
TWIN = os.path.join(HERE, "benchmark", "taxi-tiny-distinct-cell.json")
SEED = 2**31 + 45


def oracle(codes, values, n_groups, mask=None):
    """Distinct non-negative values per group among the valid rows."""
    valid = (codes >= 0) & (values >= 0)
    if mask is not None:
        valid &= mask
    pairs = np.unique(np.stack([codes[valid], values[valid]]), axis=1)
    return np.bincount(pairs[0], minlength=n_groups)[:n_groups]


def _case(name):
    rng = np.random.default_rng(45)
    n = 3000
    codes = rng.integers(0, 50, n)
    values = rng.integers(0, 40, n)
    mask = None
    n_groups = 50
    if name == "nulls":
        codes[rng.random(n) < 0.1] = -1
        values[rng.random(n) < 0.1] = -1
    elif name == "masked":
        mask = rng.random(n) < 0.6
    elif name == "empty_groups":
        codes = rng.integers(0, 10, n) * 5          # 40 of 50 groups empty
    elif name == "one_group":
        codes = np.zeros(n, dtype=np.int64)
        n_groups = 1
    elif name == "past_2_31":
        # 70 225 groups x 50 001 values: a packed (group, value) composite
        # would pass 2^31 at a few thousand rows
        n_groups = 70_225
        codes = rng.integers(0, n_groups, n)
        values = rng.integers(0, 50_001, n)
        codes[:50] = n_groups - 1                   # the last group: many
        values[:50] = np.arange(50) % 7             # ... with repeats
    elif name == "one_value":
        # every group ends and starts on the same value: a new pair is told
        # by its group alone
        values = np.full(n, 5)
    elif name == "no_rows":
        codes, values = codes[:0], values[:0]
    return codes.astype(np.int32), values.astype(np.int32), n_groups, mask


@pytest.mark.parametrize(
    "name",
    ["nulls", "masked", "empty_groups", "one_group", "past_2_31", "one_value",
     "no_rows"],
)
def test_the_two_key_sort_counts_what_np_unique_counts(name):
    codes, values, n_groups, mask = _case(name)
    got = ops.groupby_count_distinct(
        codes, values, n_groups, None if mask is None else mask
    )
    assert np.asarray(got).dtype == np.int64
    np.testing.assert_array_equal(
        np.asarray(got), oracle(codes, values, n_groups, mask)
    )


@pytest.mark.parametrize("strategy", [None, "sort", "scatter"])
def test_partial_tables_carry_sums_and_two_distinct_counts_through_the_sort(
        strategy):
    """A distinct count takes the scatter entry whatever the hint (the MXU
    route has no form of it); the query's integer sum and count ride the
    sort, and a second distinct column takes a sort of its own."""
    codes, values, n_groups, mask = _case("nulls")
    rng = np.random.default_rng(7)
    other = rng.integers(-1, 9, len(codes)).astype(np.int32)
    fares = rng.integers(-500, 500, len(codes)).astype(np.int64)
    ops_ = ("count_distinct", "sum", "count_distinct", "count")
    route = ops.kernel_route(
        strategy, (values, fares, other, fares), ops_, len(codes), n_groups
    )
    assert route == ("scatter" if strategy == "scatter" else "sort")
    partials = ops.partial_tables(
        codes, (values, fares, other, fares), ops_, n_groups,
        strategy=strategy,
    )
    aggs = partials["aggs"]
    np.testing.assert_array_equal(
        np.asarray(aggs[0]["distinct"]), oracle(codes, values, n_groups)
    )
    np.testing.assert_array_equal(
        np.asarray(aggs[2]["distinct"]), oracle(codes, other, n_groups)
    )
    valid = codes >= 0
    np.testing.assert_array_equal(
        np.asarray(aggs[1]["sum"]),
        np.bincount(codes[valid], fares[valid], n_groups).astype(np.int64),
    )
    np.testing.assert_array_equal(
        np.asarray(partials["rows"]), np.bincount(codes[valid], None, n_groups)
    )


# -- the one-device mesh executor -------------------------------------------------------

@pytest.fixture(scope="module")
def month(tmp_path_factory):
    """The tiny twin's ten shards on disk, and the reference over them."""
    from bqueryd_tpu.storage import ctable

    config = json.load(open(TWIN))
    root = tmp_path_factory.mktemp("distinct_month")
    names = [data.shard_name(i) for i in range(config["shards"])]
    frames = data.frames(config, SEED)
    for name, frame in zip(names, frames):
        ctable.fromdataframe(frame, str(root / name))
    return {
        "config": config, "root": root, "names": names, "frames": frames,
        "reference": reference.Reference(dict(zip(names, frames))),
    }


def _tables(month):
    from bqueryd_tpu.storage.ctable import ctable

    return [ctable(str(month["root"] / n), mode="r") for n in month["names"]]


def _numbers(month, args, got):
    expected = month["reference"].answer(args)
    return reference.compare(args, got, expected, month["config"]["columns"])


def test_the_executor_takes_distinct_counts_only_whole_and_on_one_device():
    query = GroupByQuery(
        ["PULocationID"], [["DOLocationID", "count_distinct", "d"],
                           ["fare_amount", "sum", "s"]], sole_payload=True,
    )
    assert MeshQueryExecutor(mesh=make_mesh(1)).takes(query)
    assert not MeshQueryExecutor(mesh=make_mesh(4)).takes(query)
    part = GroupByQuery(query.groupby_cols, query.agg_list)
    assert not MeshQueryExecutor(mesh=make_mesh(1)).takes(part)
    runs = GroupByQuery(["PULocationID"],
                        [["DOLocationID", "sorted_count_distinct", "r"]],
                        sole_payload=True)
    assert not MeshQueryExecutor(mesh=make_mesh(1)).takes(runs)
    with pytest.raises(ValueError, match="whole answer"):
        MeshQueryExecutor(mesh=make_mesh(4)).execute([], query)


@pytest.mark.parametrize("shape", ["zone_dropoffs", "zonepair_fares"])
def test_one_launch_over_the_unit_matches_the_reference(month, shape):
    """Both shapes of the cell, each with its fresh filter, in one launch
    of the unit's global codes: counts, by the route ``sort``."""
    executor = MeshQueryExecutor(mesh=make_mesh(1))
    args = traffic.query_args(month["config"], shape, month["names"], 1.2345)
    files, gcols, aggs, where = args
    query = GroupByQuery(gcols, aggs, [tuple(w) for w in where],
                         sole_payload=True)
    payload = executor.execute(_tables(month), query)
    assert executor.last_effective_strategy == "sort"
    assert "distinct" in payload["aggs"][0]
    got = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload]))
    numbers = _numbers(month, args, got)
    assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0


# -- through rpc.groupby ---------------------------------------------------------------------

def _start(url, data_dirs, devices, root):
    """A controller and one calc worker per data dir, each worker's mesh
    ``devices`` wide."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(root), heartbeat_interval=0.1,
    )
    workers = []
    for data_dir in data_dirs:
        worker = WorkerNode(
            coordination_url=url, data_dir=str(data_dir),
            loglevel=logging.WARNING, restart_check=False,
            heartbeat_interval=0.1, poll_timeout=0.05,
        )
        worker.mesh_executor._mesh = make_mesh(devices)
        workers.append(worker)
    nodes = [controller] + workers
    threads = [threading.Thread(target=n.go, daemon=True) for n in nodes]
    for thread in threads:
        thread.start()
    rpc = RPC(coordination_url=url, timeout=120, loglevel=logging.WARNING)
    return {"controller": controller, "workers": workers, "rpc": rpc,
            "nodes": nodes, "threads": threads}


def _stop(cluster):
    for node in cluster["nodes"]:
        node.running = False
    for thread in cluster["threads"]:
        thread.join(timeout=5)


def _wires(cluster):
    return {
        wire: sum(w._distinct_payloads[wire].value for w in cluster["workers"])
        for wire in ("counts", "sets")
    }


@pytest.fixture(scope="module")
def one_worker(month, tmp_path_factory):
    """One worker holding the whole month on a one-device mesh: the
    deployment of the cell."""
    patch = pytest.MonkeyPatch()
    patch.setenv("BQUERYD_TPU_WARMUP", "0")
    patch.setenv("BQUERYD_TPU_PROFILE", "1")
    cluster = _start(f"mem://distinct-one-{os.urandom(4).hex()}",
                     [month["root"]], 1, tmp_path_factory.mktemp("run1"))
    wait_until(lambda: all(n in cluster["controller"].files_map
                           for n in month["names"]), desc="shards")
    yield cluster
    _stop(cluster)
    patch.undo()


def _ask(cluster, month, args):
    got = cluster["rpc"].groupby(*args)
    return got, _numbers(month, args, got)


def _wait_spans(rpc, name):
    trace_id = rpc.last_trace_id
    timeline = wait_until(
        lambda: (lambda t: t if t and any(
            s["name"] == "finalize" for s in t["spans"]) else None
        )(rpc.trace(trace_id)),
        timeout=10, desc="the stored timeline",
    )
    return [s for s in timeline["spans"] if s["name"] == name]


@pytest.mark.parametrize("shape", ["zone_dropoffs", "zonepair_fares"])
def test_one_worker_holding_every_file_ships_counts_in_one_payload(
        one_worker, month, shape):
    rpc = one_worker["rpc"]
    before = _wires(one_worker)
    args = traffic.query_args(month["config"], shape, month["names"], 0.4321)
    _got, numbers = _ask(one_worker, month, args)
    assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
    # one dispatch group, one payload, by the sort
    assert len(rpc.last_call_timings) == 1
    assert set(rpc.last_call_strategies["effective"].values()) == {"sort"}
    after = _wires(one_worker)
    assert (after["counts"] - before["counts"], after["sets"] - before["sets"]) == (1, 0)
    (span,) = _wait_spans(rpc, "distinct_wait")
    assert span["tags"]["wire"] == "counts"
    assert span["tags"]["rows"] == month["config"]["rows"]


def test_a_value_in_every_shard_is_counted_once_and_an_emptied_group_is_absent(
        one_worker, month):
    config, names = month["config"], month["names"]
    # a (pickup zone, dropoff zone) pair that every shard holds: the
    # answer has to count it once, not ten times
    pairs = [set(zip(f["PULocationID"], f["DOLocationID"])) for f in month["frames"]]
    zone, dropoff = sorted(set.intersection(*pairs))[0]
    files, gcols, aggs, where = traffic.query_args(config, "zone_dropoffs", names, 0.0)
    where = where + [["PULocationID", "not in", [int(zone) + 1]]]
    args = (files, gcols, aggs, where)
    got, numbers = _ask(one_worker, month, args)
    assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
    assert int(zone) + 1 not in set(got["PULocationID"])
    row = got[got["PULocationID"] == zone].iloc[0]
    full = np.concatenate([f["DOLocationID"][(f["PULocationID"] == zone)
                                             & (f["trip_distance"] > 0.0)]
                           for f in month["frames"]])
    assert row["dropoff_zones"] == len(np.unique(full)) < sum(
        len(np.unique(f["DOLocationID"][(f["PULocationID"] == zone)
                                        & (f["trip_distance"] > 0.0)]))
        for f in month["frames"])
    assert dropoff in full


def test_a_query_naming_one_shard_still_ships_counts(one_worker, month):
    before = _wires(one_worker)
    args = traffic.query_args(month["config"], "zonepair_fares", month["names"][:1], 2.5)
    _got, numbers = _ask(one_worker, month, args)
    assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
    assert _wires(one_worker)["counts"] == before["counts"] + 1


def test_two_workers_with_split_placement_ship_sets_and_stay_exact(month, tmp_path):
    """Half the month on each worker: the controller cannot dispatch one
    group holding every file, so each shard ships its value sets and the
    client unions them."""
    halves = [tmp_path / "a", tmp_path / "b"]
    for i, name in enumerate(month["names"]):
        half = halves[i % 2]
        half.mkdir(exist_ok=True)
        os.symlink(month["root"] / name, half / name)
    patch = pytest.MonkeyPatch()
    patch.setenv("BQUERYD_TPU_WARMUP", "0")
    cluster = _start(f"mem://distinct-two-{os.urandom(4).hex()}", halves, 1, tmp_path)
    try:
        wait_until(lambda: all(n in cluster["controller"].files_map
                               for n in month["names"]), desc="shards")
        for shape in ("zone_dropoffs", "zonepair_fares"):
            args = traffic.query_args(month["config"], shape, month["names"], 1.0)
            _got, numbers = _ask(cluster, month, args)
            assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
            assert len(cluster["rpc"].last_call_timings) == len(month["names"])
        assert _wires(cluster) == {"counts": 0, "sets": 2 * len(month["names"])}
    finally:
        _stop(cluster)
        patch.undo()


def test_a_four_device_mesh_takes_the_per_shard_route_and_stays_exact(month, tmp_path):
    """One worker holds every file, so the unit is flagged as the whole
    answer; a mesh of four devices cannot add per-device counts, so the
    worker splits the unit per shard, each shard's query without the flag,
    and ships the union of the sets."""
    patch = pytest.MonkeyPatch()
    patch.setenv("BQUERYD_TPU_WARMUP", "0")
    cluster = _start(f"mem://distinct-four-{os.urandom(4).hex()}",
                     [month["root"]], 4, tmp_path)
    try:
        wait_until(lambda: all(n in cluster["controller"].files_map
                               for n in month["names"]), desc="shards")
        args = traffic.query_args(month["config"], "zone_dropoffs", month["names"], 3.0)
        _got, numbers = _ask(cluster, month, args)
        assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
        assert len(cluster["rpc"].last_call_timings) == 1
        assert _wires(cluster) == {"counts": 0, "sets": 1}
    finally:
        _stop(cluster)
        patch.undo()


# -- the controller's dispatch groups and the flag ---------------------------------------

@pytest.mark.parametrize("ops_,placement,kwargs,groups", [
    (["count_distinct", "sum"], "one", {}, 1),
    (["count_distinct"], "split", {}, 4),
    (["sorted_count_distinct"], "one", {}, 4),
    (["count_distinct"], "one", {"batch": False}, 4),
    (["sum"], "split", {}, 2),
])
def test_a_distinct_count_is_one_group_only_where_one_placement_holds_every_file(
        ops_, placement, kwargs, groups):
    from bqueryd_tpu.controller import ControllerNode

    controller = ControllerNode.__new__(ControllerNode)
    files = [f"s{i}.bcolzs" for i in range(4)]
    controller.files_map = {
        f: {"w0"} if placement == "one" else {f"w{i % 2}"}
        for i, f in enumerate(files)
    }
    aggs = [["v", op, f"o{i}"] for i, op in enumerate(ops_)]
    got = controller._shard_groups(files, ["k"], aggs, kwargs)
    assert len(got) == groups and sorted(sum(got, [])) == files


def test_a_split_unit_is_never_the_whole_answer():
    """A flagged unit re-split per shard (its co-locating worker gone)
    hands every child a query without the flag, fragment included."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.plan import fragment_for, fragment_to_query, plan_groupby

    files = ["s0.bcolzs", "s1.bcolzs"]
    plan = plan_groupby(files, ["k"], [["v", "count_distinct", "d"]], [])
    msg = CalcMessage({"payload": "groupby", "sole_payload": True,
                       "filename": files, "token": "t"})
    msg.set_args_kwargs([files, ["k"], [["v", "count_distinct", "d"]], []], {})
    msg.add_as_binary("plan", fragment_for(plan, files, sole=True))
    children = ControllerNode.__new__(ControllerNode)._split_batch(msg)
    assert [c["filename"] for c in children] == files
    for child in children:
        assert "sole_payload" not in child
        assert fragment_to_query(child.get_from_binary("plan")).sole_payload is False
    assert msg["sole_payload"] is True   # the batch itself is untouched


def test_the_span_and_the_counter_are_registered():
    from bqueryd_tpu import messages
    from bqueryd_tpu.obs import metrics, slo

    assert "distinct_wait" in messages.SPAN_SCHEMA
    assert slo.SPAN_CATEGORIES["distinct_wait"] == "kernel"
    registry = metrics.MetricsRegistry()
    counters = metrics.distinct_payload_counters(registry)
    assert set(counters) == set(metrics.DISTINCT_WIRES) == {"counts", "sets"}
    assert sorted(m.labels["wire"] for m in registry.metrics()) == ["counts", "sets"]
    assert registry.lint() == []
