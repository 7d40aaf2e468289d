"""The plain reference answers every aggregation op and filter operator
that ``rpc.groupby`` takes, under the program's own names: hand-worked
frames for each, a wrong answer caught for each, the accepted
configurations' answers pinned byte for byte, a configuration the reference
cannot answer refused before a run starts, and a distinct-count deployment
(``taxi-tiny-distinct.json``, in no cell) rehearsed through the whole run.
"""

import ast
import hashlib
import json
import os
import shutil

import numpy as np
import pandas as pd
import pytest
from test_perf_benchmark import HERE, REPO, deployment_defaults, rehearse, tiny_home, time_limit  # noqa: F401

from benchmark import cluster, data, harness, reference, traffic

LIMITS = {"int_mismatch": 0, "unanswered": 0, "f32_mean_rel": 1e-6, "f64_mean_rel": 1e-7}
COLUMNS = {"g": "int64", "v": "float64", "i": "int64", "f": "float32", "t": "datetime64[ns]",
           "d": "float64"}
NaT = np.datetime64("NaT", "ns")


def ts(day):
    return np.datetime64(f"2016-01-{day:02d}", "ns")


#: two files; ``d`` is the filter column
FILES = {
    "a": pd.DataFrame({
        "g": np.array([1, 1, 1, 2, 2, 3], dtype=np.int64),
        "v": np.array([1.0, 1.0, np.nan, 5.0, 6.0, 7.0]),
        "i": np.array([10, -4, 11, 20, 20, 30], dtype=np.int64),
        "f": np.array([0.5, 2.25, -1.5, 3.0, 3.0, 9.0], dtype=np.float32),
        "t": np.array([ts(1), NaT, ts(3), ts(4), ts(5), ts(6)]),
        "d": np.array([0.5, 1.5, 2.5, 3.5, 4.5, 0.1]),
    }),
    "b": pd.DataFrame({
        "g": np.array([1, 2, 2, 4], dtype=np.int64),
        "v": np.array([1.0, 5.0, np.nan, np.nan]),
        "i": np.array([12, 19, 21, 40], dtype=np.int64),
        "f": np.array([-2.5, 1.0, 4.0, 0.25], dtype=np.float32),
        "t": np.array([NaT, NaT, ts(9), ts(10)]),
        "d": np.array([2.0, 0.2, 3.0, 4.0]),
    }),
}
#: group 3 has only a row with d 0.1: the filter leaves it empty
OVER_ONE = [["d", ">", 1.0]]


def ask(aggs, where=(), files=("a", "b"), frames=FILES, gcols=("g",), control=False):
    args = (list(files), list(gcols), [list(a) for a in aggs], [list(w) for w in where])
    ref = reference.Reference(frames)
    return args, ref.answer(args, accumulate="float32" if control else None)


def frame(**columns):
    return pd.DataFrame({k: np.asarray(v) for k, v in columns.items()})


# -- each op and operator, by hand -------------------------------------------------------

def test_count_distinct_drops_nulls_and_counts_a_value_in_two_files_once():
    _args, got = ask([["v", "count_distinct", "n"]])
    # group 1: 1.0 in both files and a NaN; group 2: 5.0 in both, 6.0, a NaN;
    # group 4: a NaN alone
    pd.testing.assert_frame_equal(got, frame(g=[1, 2, 3, 4], n=np.array([1, 2, 1, 0], dtype=np.int64)))
    _args, got = ask([["v", "count_distinct", "n"]], OVER_ONE)
    # after the filter group 3 is empty and so absent; group 2 keeps 5.0 of
    # file a (its 5.0 of file b has d 0.2) and 6.0
    pd.testing.assert_frame_equal(got, frame(g=[1, 2, 4], n=np.array([1, 2, 0], dtype=np.int64)))
    _args, got = ask([["i", "count_distinct", "n"], ["v", "sum", "s"]])
    pd.testing.assert_frame_equal(got, frame(g=[1, 2, 3, 4], n=np.array([4, 3, 1, 1], dtype=np.int64),
                                             s=[3.0, 16.0, 7.0, 0.0]))


def test_count_na_counts_nan_and_nat_and_nothing_in_an_int_column():
    _args, got = ask([["v", "count_na", "nv"], ["t", "count_na", "nt"], ["i", "count_na", "ni"]])
    expected = frame(g=[1, 2, 3, 4], nv=np.array([1, 1, 0, 1], dtype=np.int64),
                     nt=np.array([2, 1, 0, 0], dtype=np.int64), ni=np.zeros(4, dtype=np.int64))
    pd.testing.assert_frame_equal(got, expected)
    _args, got = ask([["t", "count_na", "nt"]], OVER_ONE)
    pd.testing.assert_frame_equal(got, frame(g=[1, 2, 4], nt=np.array([2, 0, 0], dtype=np.int64)))


RUNS = {
    # runs of file a: (1,1) (1,1) | (1,2) (1,2) -> 2; file b starts with (1,2)
    # again, where file a ended, and counts it anew: (1,2) | (1,3) -> 2, (2,3) -> 1
    "a": pd.DataFrame({"g": np.array([1, 1, 1, 1], dtype=np.int64), "v": [1.0, 1.0, 2.0, 2.0],
                       "d": [2.0, 2.0, 2.0, 2.0]}),
    "b": pd.DataFrame({"g": np.array([1, 1, 2], dtype=np.int64), "v": [2.0, 3.0, 3.0], "d": [2.0, 2.0, 2.0]}),
    # a row the filter drops does not split a run; a row of another group
    # does; a NaN starts a run of its own, and so does the next NaN
    "c": pd.DataFrame({"g": np.array([1, 1, 1, 2, 1, 2, 2], dtype=np.int64),
                       "v": [4.0, 9.0, 4.0, 4.0, 4.0, np.nan, np.nan],
                       "d": [2.0, 0.0, 2.0, 2.0, 2.0, 2.0, 2.0]}),
}


@pytest.mark.parametrize("files,where,expected", [
    (["a", "b"], [], {1: 4, 2: 1}),
    (["a"], [], {1: 2}),
    (["b", "a"], [], {1: 4, 2: 1}),
    (["c"], OVER_ONE, {1: 2, 2: 3}),
    (["c"], [], {1: 4, 2: 3}),
])
def test_sorted_count_distinct_counts_runs_in_each_files_stored_order(files, where, expected):
    _args, got = ask([["v", "sorted_count_distinct", "runs"]], where, files, RUNS)
    pd.testing.assert_frame_equal(got, frame(g=list(expected), runs=np.array(list(expected.values()),
                                                                             dtype=np.int64)))


def test_min_and_max_of_int64_float32_and_float64_keep_the_column_type():
    aggs = [[c, op, f"{op}_{c}"] for c in ("i", "f", "v") for op in ("min", "max")]
    _args, got = ask(aggs, OVER_ONE)
    expected = pd.DataFrame({
        "g": np.array([1, 2, 4], dtype=np.int64),
        "min_i": np.array([-4, 20, 40], dtype=np.int64), "max_i": np.array([12, 21, 40], dtype=np.int64),
        "min_f": np.array([-2.5, 3.0, 0.25], dtype=np.float32),
        "max_f": np.array([2.25, 4.0, 0.25], dtype=np.float32),
        "min_v": [1.0, 5.0, np.nan], "max_v": [1.0, 6.0, np.nan],
    })
    pd.testing.assert_frame_equal(got, expected)


@pytest.mark.parametrize("op,value,kept", [
    ("in", [2, 4], {2: 4, 4: 1}),
    ("in", [5], {}),
    ("not in", [2, 4], {1: 4, 3: 1}),
    ("not in", [], {1: 4, 2: 4, 3: 1, 4: 1}),
])
def test_in_and_not_in_take_a_list(op, value, kept):
    _args, got = ask([["i", "count", "n"]], [["g", op, value]])
    assert dict(zip(got["g"], got["n"])) == kept
    _args, got = ask([["i", "count", "n"]], [["i", op, [20, 19]]])
    assert dict(zip(got["g"], got["n"])) == ({2: 3} if op == "in" else {1: 4, 2: 1, 3: 1, 4: 1})


# -- a wrong answer is caught, op by op ------------------------------------------------------

OFF_BY_ONE = {
    # op: (column, where the answer is altered)
    "count_distinct": "v", "count_na": "v", "sorted_count_distinct": "v", "count": "v",
    "min": "i", "max": "f", "sum": "v", "mean": "f",
}


@pytest.mark.parametrize("op", sorted(OFF_BY_ONE))
def test_an_answer_off_in_one_group_fails_the_check(op):
    column = OFF_BY_ONE[op]
    args, expected = ask([[column, op, "out"]], OVER_ONE)
    same = reference.compare(args, expected.sample(frac=1.0, random_state=3), expected, COLUMNS)
    assert reference.verdict(reference.worst([same]), LIMITS)[0]
    values = expected["out"].to_numpy()
    off = np.arange(len(values)) == 1
    if values.dtype.kind == "i":
        wrong = expected.assign(out=values + off)
    else:
        wrong = expected.assign(out=(values * np.where(off, 1 + 1e-5, 1.0)).astype(values.dtype))
    numbers = reference.compare(args, wrong, expected, COLUMNS)
    if values.dtype.kind == "i":
        assert numbers["int_mismatch"] == 1
    else:
        assert numbers["f64_mean_rel" if COLUMNS[column] == "float64" else "f32_mean_rel"] > 1e-6
    assert not reference.verdict(reference.worst([numbers]), LIMITS)[0]


def test_an_extreme_of_a_datetime_column_is_compared_bit_for_bit():
    args, expected = ask([["t", "max", "last"]])
    assert expected["last"].dtype.kind == "M"
    assert reference.compare(args, expected, expected, COLUMNS)["int_mismatch"] == 0
    wrong = expected.copy()
    wrong.loc[0, "last"] += pd.Timedelta(1, "ns")
    assert reference.compare(args, wrong, expected, COLUMNS)["int_mismatch"] == 1
    as_ints = expected.assign(last=expected["last"].to_numpy().view(np.int64))
    assert reference.compare(args, as_ints, expected, COLUMNS)["int_mismatch"] == len(expected)


# -- the control ---------------------------------------------------------------------------

def test_the_control_passes_through_every_op_that_accumulates_nothing():
    aggs = [["v", op, op] for op in reference.CONTROL_EXACT] + [["f", "mean", "m"], ["i", "sum", "s"]]
    args, exact = ask(aggs, OVER_ONE)
    _args, control = ask(aggs, OVER_ONE, control=True)
    for op in reference.CONTROL_EXACT:
        np.testing.assert_array_equal(control[op].to_numpy(), exact[op].to_numpy())
        assert control[op].dtype == exact[op].dtype
    np.testing.assert_array_equal(control["g"].to_numpy(), exact["g"].to_numpy())
    with pytest.raises(ValueError, match="no rule for the op 'median'"):
        reference._lower_precision_groupby(FILES["a"], ["g"], [["v", "median", "x"]], np.dtype("float32"),
                                           exact)


# -- the program's names, copied -------------------------------------------------------------

def program_tuple(path, name):
    """A tuple of strings the program defines at the top of ``path``, read
    without importing the program (``bqueryd_tpu.ops`` imports JAX)."""
    tree = ast.parse(open(os.path.join(REPO, "bqueryd_tpu", path)).read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(getattr(t, "id", None) == name for t in node.targets):
            return ast.literal_eval(node.value)
    raise LookupError(f"{path} defines no {name}")


def test_the_reference_has_a_rule_for_every_op_and_operator_of_the_program():
    assert set(program_tuple("models/query.py", "AGG_OPS")) <= set(reference.AGGS)
    assert set(program_tuple("ops/predicates.py", "WHERE_OPS")) <= set(reference.OPS)
    assert set(reference.CONTROL_EXACT) <= set(reference.AGGS)
    source = open(reference.__file__).read()
    assert "bqueryd_tpu" not in "".join(
        line for line in source.splitlines() if line.startswith(("import", "from")))


# -- the accepted configurations' answers, byte for byte ------------------------------------------

PINS = json.load(open(os.path.join(HERE, "reference_pins.json")))


def digest(answer):
    h = hashlib.sha256()
    h.update(answer.index.to_numpy().tobytes())
    for col in answer.columns:
        values = answer[col].to_numpy()
        h.update(f"{col}|{values.dtype.str}|{len(values)};".encode())
        h.update(np.ascontiguousarray(values).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("twin", ["taxi-tiny.json", "taxi-tiny4.json", "taxi-tiny-dollars.json"])
def test_the_accepted_configurations_get_the_parents_answers_byte_for_byte(twin):
    """``reference_pins.json`` holds the digests of the answers (and the
    float32 control's) that the reference of the tree before the op table
    gave to every query of each accepted configuration's tiny twin, bare and
    with a fresh constant, at seed 2**31 + 43."""
    config = harness.load_json(os.path.join(HERE, twin))
    names = [data.shard_name(i) for i in range(config["shards"])]
    ref = reference.Reference(dict(zip(names, data.frames(config, PINS["seed"]))))
    for shape in config["queries"]:
        for value in (None, 1.23455):
            args = traffic.query_args(config, shape, names, value)
            pin = PINS["answers"][f"{twin}:{shape}:{value}"]
            assert digest(ref.answer(args)) == pin["answer"], (shape, value)
            assert digest(ref.answer(args, accumulate="float32")) == pin["control"], (shape, value)


# -- refused at load ----------------------------------------------------------------------------------

@pytest.mark.parametrize("query,term", [
    ("highcard", {"aggs": [["fare_amount", "median", "fare_median"]]}),
    ("filtered", {"where": [["trip_distance", "between", [1.0, 2.0]]]}),
])
def test_a_configuration_the_reference_cannot_answer_is_refused_before_a_run_starts(
        tmp_path, monkeypatch, query, term):
    config = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))
    config["queries"][query].update(term)
    path = tmp_path / "taxi-odd.json"
    path.write_text(json.dumps(config))

    def extra(bench):
        bench["configs"].append({"name": "taxi-odd", "file": str(path)})
        bench["workloads"].append({"name": "taxi-odd.adhoc-heavy", "config": "taxi-odd",
                                   "traffic": "adhoc-heavy", "chips": 1})

    home = tiny_home(tmp_path, extra)
    started = []
    monkeypatch.setattr(cluster.Cluster, "__init__", lambda *a: started.append(a))
    monkeypatch.setattr(data, "build_dataset", lambda *a: started.append(a))
    op = (term.get("aggs") or term.get("where"))[0][1]
    with pytest.raises(cluster.RunFailure) as refused:
        rehearse(tmp_path, "taxi-odd.adhoc-heavy", home=home)
    message = str(refused.value)
    assert str(path) in message and repr(op) in message and repr(query) in message, message
    assert started == [] and not (tmp_path / "benchmark" / ".work").exists()
    # the accepted cells load as before
    harness.load_cell("taxi-1chip.adhoc-heavy", home)


# -- a distinct-count deployment, rehearsed as data files ---------------------------------------

DISTINCT = "taxi-tiny-distinct.adhoc-distinct"


def distinct_home(tmp_path):
    """A home whose BENCHMARK.json adds ``taxi-tiny-distinct`` and its cell
    to the accepted ones, its mix beside the benchmark's own."""
    def extra(bench):
        bench["configs"].append({"name": "taxi-tiny-distinct",
                                 "file": os.path.join(HERE, "taxi-tiny-distinct.json")})
        bench["workloads"].append({"name": DISTINCT, "config": "taxi-tiny-distinct",
                                   "traffic": "adhoc-distinct", "chips": 1})
        for metric in bench["end_to_end"]:
            if metric["name"] == "query_ms":
                metric["workloads"].append(DISTINCT)

    home = tiny_home(tmp_path, extra, copy=True)
    shutil.copy(os.path.join(HERE, "adhoc-distinct.json"), tmp_path / "benchmark" / "traffic")
    return home


def test_the_distinct_configuration_asks_for_every_op_beyond_the_accepted_cells():
    config = harness.load_json(os.path.join(HERE, "taxi-tiny-distinct.json"))
    ops = {a[1] for q in config["queries"].values() for a in q["aggs"]}
    assert ops == set(reference.AGGS) - {"sum", "mean", "count"}
    assert {w[1] for q in config["queries"].values() for w in q["where"]} == {"in", "not in"}
    assert reference.unanswerable(config) == []
    shipped = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert "taxi-tiny-distinct" not in {c["name"] for c in shipped["configs"]}


def _distinct_off_by_one(rpc, args):
    answer = rpc.groupby(*args)
    if args[2][0][1] == "count_distinct":
        answer = answer.copy()
        answer.loc[answer.index[0], answer.columns[-1]] += 1
    return answer


@pytest.mark.parametrize("fault", [None, "a_distinct_count_off_by_one"])
def test_a_distinct_count_deployment_is_rehearsed_through_the_whole_run(
        tmp_path, deployment_defaults, monkeypatch, fault):
    if fault:
        monkeypatch.setattr(harness, "ask", _distinct_off_by_one)
    result = rehearse(tmp_path, DISTINCT, home=distinct_home(tmp_path))
    assert result["correct"] is (fault is None), result["check"]
    # every answer is kept, and each shape gets its share of the compared
    assert result["failed"] == 0 and result["check"]["answers_compared"][0] >= 3
    assert {route.split(":")[0] for route in result["observed"]["routes"]} == {
        "zone_dropoffs", "zonepair_fares", "vendor_runs"}
    if fault:
        assert result["check"]["int_mismatch"][0] > 0
