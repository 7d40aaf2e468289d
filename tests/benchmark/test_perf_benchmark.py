"""The benchmark's own tests: the yardstick under ``benchmark/`` held to
hand-worked cases, and the whole run rehearsed at a tiny size on the CPU
backend (the look for a chip skipped: ``expect_platform="cpu"``).

No test here loads libtpu, and every child runs with ``JAX_PLATFORMS=cpu``.
"""

import json
import os
import re
import shutil
import signal
import sys

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import data, harness, in_worker, readers, reference, roofline, traffic  # noqa: E402

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def tiny_twins():
    """The tiny twin of each configuration, by its name: the configuration
    files here that name it under ``twin_of``.  A new configuration's twin
    is registered by adding its file."""
    twins = {}
    for name in sorted(os.listdir(HERE)):
        if name.endswith(".json"):
            twin_of = json.load(open(os.path.join(HERE, name))).get("twin_of")
            if twin_of is not None:
                assert twin_of not in twins, f"{name} and {twins[twin_of]} both twin {twin_of}"
                twins[twin_of] = name
    return twins


TINY = tiny_twins()


#: a repeating mix (the shipped mixes never repeat): three streams over two
#: tiles, three requests in four as the tile stands
DASHBOARD = {
    "name": "dashboard-3x2", "why": "a repeating mix at a tiny size",
    "check_every": 4, "check_at_most": 48, "warmup_max_s": 30, "settle_allow": 0,
    "streams": [{"count": 3, "repeat_share": 0.75,
                 "shapes": [{"shape": "sharded", "weight": 1}, {"shape": "highcard", "weight": 1}]}],
}
DATA = os.path.join(REPO, "benchmark")
HEAVY = "taxi-1chip.adhoc-heavy"
#: cells that BENCHMARK.json does not hold (PERF.md, Open questions) and a
#: rehearsal on the CPU backend can: their end-to-end and per-layer metrics
REHEARSED = {
    "taxi-1chip.adhoc-lowcard": (1, ["query_ms", "query_p95_ms", "cold_query_s"], None),
    "taxi-1chip.dashboard-8": (1, ["query_p95_ms", "rows_per_s"],
                               ["controller_ms.dash", "cache_answer_share.dash", "device_idle_share.dash"]),
    "taxi-4chip.adhoc-heavy": (4, ["query_ms", "cold_query_s"], None),
}


@pytest.fixture(autouse=True)
def time_limit():
    """Each test has its own limit."""
    def expired(_signum, _frame):
        raise TimeoutError("a benchmark test ran past its 240 s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(240)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def deployment_defaults(monkeypatch):
    """Children run at the program's defaults, not at the suite's pins."""
    for name in ("BQUERYD_TPU_SERVE", "BQUERYD_TPU_HOST_KERNEL_ROWS",
                 "BQUERYD_TPU_FORCE_MATMUL", "JAX_COMPILATION_CACHE_DIR"):
        monkeypatch.delenv(name, raising=False)
    pd.set_option("future.infer_string", False)


def tiny_home(tmp_path, extra=None, copy=False):
    """A home for a rehearsal: BENCHMARK.json with each configuration's file
    swapped for its tiny twin and the cells of ``REHEARSED`` added, beside
    the benchmark's own traffic and metric files."""
    bench = json.loads(json.dumps(BENCH))
    for config in bench["configs"]:
        config["file"] = os.path.join(HERE, TINY[config["name"]])
    heavy_layers = [m["name"] for m in bench["per_layer"]]
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for cell, (chips, end_to_end, layers) in REHEARSED.items():
        config, mix = cell.split(".")
        bench["workloads"].append({"name": cell, "config": config, "traffic": mix, "chips": chips})
        for name in end_to_end:
            if name not in metrics:
                metrics[name] = {"name": name, "unit": "x", "workloads": []}
                bench["end_to_end"].append(metrics[name])
        for name in layers or []:
            metrics[name] = {"name": name, "unit": "x", "workloads": []}
            bench["per_layer"].append(metrics[name])
        for name in end_to_end + (layers or heavy_layers):
            metrics[name]["workloads"].append(cell)
    if extra:
        extra(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    if not (tmp_path / "benchmark").exists():
        os.makedirs(tmp_path / "benchmark")
        for sub in ("traffic", "layer_metrics"):
            if copy:
                shutil.copytree(os.path.join(DATA, sub), tmp_path / "benchmark" / sub)
            else:
                os.symlink(os.path.join(DATA, sub), tmp_path / "benchmark" / sub)
    return str(tmp_path)


def rehearse(tmp_path, workload, trace=False, seconds=2.0, seed=2**31 + 7, home=None,
             devices=None, control=False, platform="cpu", worker_env=()):
    env = {"BQUERYD_TPU_COMPILE_CACHE": "0", **dict(worker_env)}
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return harness.run_cell(
        workload, seed, seconds, trace, control=control, home=home or tiny_home(tmp_path),
        # the CPU backend declines the explored hints for ever, so the
        # planner never stops exploring and the warm-up never settles
        rehearsal={"platform": platform, "worker_env": env, "warmup_max_s": 3.0},
    )


# -- BENCHMARK.json ----------------------------------------------------------------

def test_every_name_and_unit_is_made_of_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"] for k in ("name", "config", "traffic")]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for text in [w["why"] for w in BENCH["workloads"]] + [
        c[k] for c in BENCH["configs"] for k in ("why", "source")
    ] + [m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_finds_its_files_and_reports_what_the_contract_asks():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for workload in sorted(cells):
        cell = harness.load_cell(workload)
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) >= 2
        assert cell["per_layer"], workload
        assert all(m["reader"] in readers.READERS for m in cell["per_layer"].values())
    assert set(harness.load_cell(HEAVY)["end_to_end"]) == {"query_ms", "cold_query_s", "setup_s"}
    for metric in BENCH["per_layer"]:
        assert metric["moves"] in e2e and set(metric["workloads"]) <= cells
        movers = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(movers.get("workloads", cells))


def test_every_configuration_has_a_tiny_twin_that_asks_what_it_asks():
    """The twin differs in its size alone, so a rehearsal of the twin runs
    the configuration's own queries under its own limits."""
    assert set(TINY) >= {c["name"] for c in BENCH["configs"]}
    for entry in BENCH["configs"]:
        config = harness.load_json(os.path.join(REPO, entry["file"]))
        twin = harness.load_json(os.path.join(HERE, TINY[entry["name"]]))
        for key in ("queries", "columns", "slot", "months", "shards", "chips", "workers"):
            assert twin[key] == config[key], (entry["name"], key)
        assert twin["guarantees"]["check_limits"] == config["guarantees"]["check_limits"]
        assert twin["rows"] < config["rows"]


# -- traffic and data ----------------------------------------------------------------

def mix_plans(mix_name, seed, length=600):
    config = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))
    mix = DASHBOARD if mix_name == "dashboard-3x2" else traffic.read_mix(
        os.path.join(DATA, "traffic", mix_name + ".json"))
    names = [data.shard_name(i) for i in range(config["shards"])]
    rows_of = {n: data.shard_rows(config["rows"], config["shards"], i) for i, n in enumerate(names)}
    return config, mix, traffic.plans(config, mix, seed, names, rows_of, length)


@pytest.mark.parametrize("mix_name", ["adhoc-lowcard", "adhoc-heavy", "dashboard-8", "dashboard-3x2"])
def test_the_same_seed_gives_the_same_queries_and_another_seed_the_same_work(mix_name):
    _c, mix, a = mix_plans(mix_name, 3_000_000_019)
    _c, _m, b = mix_plans(mix_name, 3_000_000_019)
    _c, _m, other = mix_plans(mix_name, 5)
    assert [[q.args for q in p] for p in a] == [[q.args for q in p] for p in b]
    assert [q.args for q in a[0]] != [q.args for q in other[0]]
    assert len(a) == sum(s["count"] for s in mix["streams"])
    # the same shapes in the same shares whatever the seed, cycle by cycle
    cycle = len(a[0]) // 50
    for plan_a, plan_o in zip(a, other):
        count = lambda plan: sorted((q.shape, q.fresh) for q in plan[:cycle * 16])  # noqa: E731
        assert count(plan_a) == count(plan_o)
    assert any(q.check for q in a[0]) and not all(q.check for q in a[0])


@pytest.mark.parametrize("mix_name", ["adhoc-lowcard", "adhoc-heavy"])
def test_no_two_window_queries_of_an_adhoc_mix_are_equal(mix_name):
    config, mix, (plan,) = mix_plans(mix_name, 11, length=3000)
    keys = [json.dumps(q.args) for q in plan]
    assert len(set(keys)) == len(keys)
    assert all(q.fresh and q.args[3] for q in plan)
    # nor does a window query repeat a warm-up query: they use other lanes
    warm = traffic.Constants(config["slot"], np.random.default_rng(1), lane=1)
    assert not {warm.next() for _ in range(2000)} & {q.value for q in plan}
    values = [q.value for q in plan if q.shape == plan[0].shape][:10]
    assert sorted(int(v // 0.5) for v in values) == list(range(10))   # stratified


def test_a_mix_is_held_to_the_keys_the_generator_reads(tmp_path):
    """A mix that asks for an open loop or a think time is refused, not
    silently given the closed loop, and so is one that states no cap on the
    check; the shipped mixes are as ISSUE.md has them."""
    uncapped = {k: v for k, v in DASHBOARD.items() if k != "check_at_most"}
    for name, wrong in (("loop", dict(DASHBOARD, loop="open")), ("check_at_most", uncapped),
                        ("think_s", dict(DASHBOARD, streams=[dict(DASHBOARD["streams"][0], think_s=1)]))):
        path = tmp_path / f"{name}.json"
        path.write_text(json.dumps(wrong))
        with pytest.raises(ValueError, match=name):
            traffic.read_mix(str(path))
    (tmp_path / "whole.json").write_text(json.dumps(DASHBOARD))
    assert traffic.read_mix(str(tmp_path / "whole.json")) == DASHBOARD
    shapes = {name: [e["shape"] for s in traffic.read_mix(os.path.join(DATA, "traffic", name + ".json"))["streams"]
                     for e in s["shapes"]] for name in ("adhoc-lowcard", "adhoc-heavy", "dashboard-8")}
    assert shapes == {"adhoc-lowcard": ["single", "filtered", "multikey"], "adhoc-heavy": ["highcard", "f64mean"],
                      "dashboard-8": ["sharded", "multikey", "filtered", "highcard"]}
    assert {traffic.read_mix(os.path.join(DATA, "traffic", name + ".json"))["check_at_most"] for name in shapes} == {48}


def test_a_repeating_mix_repeats_three_in_four():
    _c, _m, plans = mix_plans("dashboard-3x2", 9, length=160)
    assert len(plans) == 3
    for plan in plans:
        assert sum(q.fresh for q in plan) == 40
        fixed = {json.dumps(q.args) for q in plan if not q.fresh}
        assert len(fixed) == 2   # two tiles, each sent as it stands
    assert [q.args for q in plans[0]] != [q.args for q in plans[1]]


def test_the_same_seed_gives_the_same_frames_with_the_skew_the_config_states():
    config = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))
    a, b = data.shard_frame(config, 2**31 + 5, 3, 20_000), data.shard_frame(config, 2**31 + 5, 3, 20_000)
    pd.testing.assert_frame_equal(a, b)
    assert not a.equals(data.shard_frame(config, 2**31 + 5, 4, 20_000))
    assert {c: str(a[c].dtype) for c in a} == config["columns"]
    assert (a["passenger_count"] == 1).mean() > 0.6
    assert a["payment_type"].isin([1, 2]).mean() > 0.95
    top = a["PULocationID"].value_counts(normalize=True).iloc[0]
    assert 0.1 < top < 0.25 and a["PULocationID"].between(1, 265).all()   # Zipf, not uniform
    assert sum(data.shard_rows(10_906_858, 10, i) for i in range(10)) == 10_906_858


# -- the reference and the comparison ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_reference():
    config = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))
    names = [data.shard_name(i) for i in range(config["shards"])]
    return config, names, reference.Reference(dict(zip(names, data.frames(config, 77))))


@pytest.mark.parametrize("shape", ["single", "multikey", "filtered", "highcard", "f64mean"])
def test_the_comparison_passes_the_reference_and_fails_a_perturbed_aggregate(tiny_reference, shape):
    config, names, ref = tiny_reference
    limits = config["guarantees"]["check_limits"]
    args = traffic.query_args(config, shape, names, 1.23455)
    expected = ref.answer(args)
    assert len(expected) > 1
    same = reference.compare(args, expected.sample(frac=1.0, random_state=1), expected, config["columns"])
    assert reference.verdict(reference.worst([same]), limits)[0]
    out = args[2][-1][2]
    wrong = expected.copy()
    if wrong[out].dtype.kind == "i":
        wrong.loc[wrong.index[0], out] += 1
    else:
        wrong.loc[wrong.index[0], out] *= 1 + 1e-5
    numbers = reference.worst([reference.compare(args, wrong, expected, config["columns"])])
    assert not reference.verdict(numbers, limits)[0]
    assert not reference.verdict(reference.worst([reference.compare(
        args, expected.iloc[1:], expected, config["columns"])]), limits)[0]
    assert not reference.verdict(reference.worst([reference.compare(
        args, None, expected, config["columns"])]), limits)[0]


@pytest.mark.parametrize("shape", ["multikey", "highcard", "f64mean"])
def test_the_control_in_float32_fails_the_comparison(tiny_reference, shape):
    """The reference put in the program's place, accumulating in float32,
    is not correct: the limits would catch a lower-precision path."""
    config, names, ref = tiny_reference
    args = traffic.query_args(config, shape, names, 0.51235)
    control = ref.answer(args, accumulate="float32")
    numbers = reference.worst([reference.compare(args, control, ref.answer(args), config["columns"])])
    assert not reference.verdict(numbers, config["guarantees"]["check_limits"])[0], numbers


# -- the cap on the check: what a run does after its window ------------------------------------

class CountingReference:
    """Stands where ``reference.Reference`` does and keeps what it was asked."""

    def __init__(self):
        self.asked, self.asked_as_control = [], []

    def answer(self, args, accumulate=None):
        (self.asked if accumulate is None else self.asked_as_control).append(args[3][0][2])
        return hand_made_answer(args[3][0][2], off=0 if accumulate is None else 1)


def hand_made_answer(value, off=0):
    return pd.DataFrame({"passenger_count": [1, 2], "s": np.array([value, value + 1 + off], dtype=np.int64)})


def hand_made_records(recorded, unanswered=0):
    """A window's records in send order, the shapes taking turns while each
    lasts: every third marked for the check and holding its answer, the
    last ``unanswered`` of the marked ones holding none."""
    left, records = dict(recorded), []
    while any(left.values()):
        for shape in [s for s, n in left.items() if n]:
            left[shape] -= 1
            for marked in (True, False, False):
                value = len(records)
                args = (["f"], ["passenger_count"], [["fare_amount", "sum", "s"]], [["trip_distance", ">", value]])
                records.append({"shape": shape, "args": args, "check": marked, "ok": True, "t_send": float(value)})
                if marked:
                    records[-1]["answer"] = hand_made_answer(value)
    for record in [r for r in records if r["check"]][len(records) // 3 - unanswered:]:
        record.update(ok=False, answer=None)
    return records


THREE_SHAPES = dict(DASHBOARD, check_at_most=50, streams=[{"count": 1, "repeat_share": 0.0, "shapes": [
    {"shape": s, "weight": 1} for s in ("single", "filtered", "multikey")]}])
CAPPED = {
    # case: (mix, recorded answers by shape, marked queries with no answer, compared by shape)
    "300_recorded_of_two_shapes_compare_24_of_each": ("adhoc-heavy", {"highcard": 150, "f64mean": 150}, 0, [24, 24]),
    "30_recorded_compare_30": ("adhoc-heavy", {"highcard": 15, "f64mean": 15}, 0, [15, 15]),
    "5_of_one_shape_and_200_of_the_other_compare_5_and_43": ("adhoc-heavy", {"highcard": 5, "f64mean": 200}, 0, [5, 43]),
    "a_remainder_goes_to_the_shapes_first_in_file_order": (THREE_SHAPES, {"multikey": 90, "single": 80, "filtered": 70}, 0, [17, 17, 16]),
    "a_marked_query_with_no_answer_is_unanswered_though_never_chosen": ("adhoc-heavy", {"highcard": 150, "f64mean": 150}, 2, [24, 24]),
    "the_same_records_give_the_same_choice_twice": ("adhoc-heavy", {"highcard": 101, "f64mean": 77}, 0, [24, 24]),
    "the_control_sees_the_chosen_set": ("adhoc-heavy", {"highcard": 40, "f64mean": 9}, 0, [39, 9]),
}


@pytest.mark.parametrize("case", sorted(CAPPED))
def test_the_check_compares_at_most_what_the_mix_states_of_the_recorded_answers(case):
    mix, recorded, unanswered, compared = CAPPED[case]
    if isinstance(mix, str):
        mix = traffic.read_mix(os.path.join(DATA, "traffic", mix + ".json"))
    cell = {"config": harness.load_json(os.path.join(HERE, "taxi-tiny.json")), "mix": mix}
    records = hand_made_records(recorded, unanswered)
    ref, again = CountingReference(), CountingReference()
    out = harness.check(cell, ref, records, control=True)
    rows = {name: number for name, number, _limit in out["rows"]}
    assert rows["answers_recorded"] == sum(recorded.values()) - unanswered
    assert rows["answers_compared"] == sum(compared) == len(ref.asked) <= mix["check_at_most"]
    assert rows["answers_compared"] == min(rows["answers_recorded"], mix["check_at_most"])
    assert rows["unanswered"] == unanswered and rows["int_mismatch"] == 0
    assert out["correct"] is (unanswered == 0)
    by_value = {r["args"][3][0][2]: r for r in records}
    assert all(by_value[v]["check"] and by_value[v]["answer"] is not None for v in ref.asked)
    assert ref.asked == sorted(ref.asked)   # in send order
    for shape, count in zip(traffic.shapes_of(mix), compared):
        mine = [r["args"][3][0][2] for r in records if r["shape"] == shape and r.get("answer") is not None]
        asked = [v for v in ref.asked if by_value[v]["shape"] == shape]
        assert len(asked) == count and asked[0] == mine[0] and asked[-1] == mine[-1]
        # evenly spaced: no two gaps differ by more than one answer of the shape
        gaps = {mine.index(b) - mine.index(a) for a, b in zip(asked, asked[1:])}
        assert not gaps or max(gaps) - min(gaps) <= 1
    # the control is the reference in float32's place over the same chosen answers, and fails
    assert ref.asked_as_control == ref.asked and out["control"][0] is False
    # nothing is drawn: the same records give the same choice, with the control or without
    assert harness.check(cell, again, records)["rows"] == out["rows"] and again.asked == ref.asked
    assert again.asked_as_control == []
    # a wrong answer among the chosen is found as before
    by_value[ref.asked[-1]]["answer"] = hand_made_answer(ref.asked[-1], off=1)
    assert harness.check(cell, CountingReference(), records)["correct"] is False


# -- the yardstick's arithmetic ------------------------------------------------------------

def test_bytes_needed_against_hand_worked_shapes():
    columns = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))["columns"]
    bare = (["f"], ["passenger_count"], [["fare_amount", "sum", "s"]], [])
    assert roofline.bytes_needed(columns, bare, 1000) == 1000 * (8 + 8)
    filtered = (["f"], ["passenger_count"], [["fare_amount", "sum", "s"]], [["trip_distance", ">", 1.0]])
    assert roofline.bytes_needed(columns, filtered, 1000) == 1000 * (8 + 8 + 4)
    multikey = (["f"], ["VendorID", "payment_type"],
                [["fare_amount", "sum", "a"], ["fare_amount", "count", "n"], ["trip_distance", "mean", "m"]],
                [["trip_distance", ">", 1.0]])
    assert roofline.bytes_needed(columns, multikey, 10) == 10 * (8 + 8 + 8 + 4)   # each column once
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")


def test_trace_reduction_on_hand_made_planes():
    planes = {
        "/device:TPU:0": {"XLA Ops": [["fusion.1", 1000, 1000], ["fusion.2", 1500, 1000], ["sort", 6000, 2000]],
                          "Steps": [["0", 0, 9000]]},
        "/host:CPU": {"worker": [["align", 2600, 3000], ["inner", 3000, 500], ["aggregate", 5900, 2200]]},
    }
    out = in_worker.reduce_planes(planes)
    assert out["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(3500e-9)            # the union, not the sum
    assert out["window_s"] == pytest.approx(7100e-9)          # 1000 .. 8100
    assert dict(out["device_ops"])["sort"] == pytest.approx(2000e-9)
    assert dict(out["idle_gaps"]) == {"align": pytest.approx(3500e-9), "aggregate": pytest.approx(100e-9)}
    planes["/host:CPU"]["python"] = [["$posix stat", 2500, 3600], ["layout", 2550, 3100]]
    assert dict(in_worker.reduce_planes(planes)["idle_gaps"])["layout"] == pytest.approx(3500e-9)
    assert in_worker.reduce_planes({"/host:CPU": {"t": [["x", 0, 5]]}}) == {"device_planes": 0}


def test_trace_reduction_on_a_recorded_trace():
    """A slice of a TPU v5 lite trace of the lowcard mix (chip run, PR 25),
    cut to the first events of each line."""
    path = os.path.join(HERE, "recorded_planes.json")
    out = in_worker.reduce_planes(json.load(open(path)))
    assert out["device_planes"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert len(out["device_ops"]) == 10 and out["device_ops"][0][1] >= out["device_ops"][-1][1]
    assert 1 <= len(out["idle_gaps"]) <= 10
    idle = 1 - out["busy_s"] / out["window_s"]
    assert sum(s for _n, s in out["idle_gaps"]) <= idle * out["window_s"] * 1.001


def test_readers_return_nothing_where_there_is_nothing_to_read():
    ev = {"records": [], "column_dtypes": {}, "device_kind": "TPU v5 lite", "chips": 1}
    for name, m in harness.load_cell(HEAVY)["per_layer"].items():
        assert readers.read(m, ev) is None, name
    record = {"shape": "s", "fresh": True, "ok": True, "answer_source": "cached", "effective": ["matmul"],
              "timings": {"g": {"align": 0.002, "aggregate": 0.004}}, "wall_s": 0.010, "trace_id": "t",
              "t_send": 1.0, "t_reply": 1.01, "rows": 1000,
              "args": (["f"], ["passenger_count"], [["fare_amount", "sum", "s"]], [])}
    ev.update(records=[record], warm_routes={"s": ["scatter"]}, slice=[0.5, 2.0],
              column_dtypes={"passenger_count": "int64", "fare_amount": "int64"},
              traces={"t": {"spans": [{"name": "groupby", "duration_s": 0.008},
                                      {"name": "calc", "duration_s": 0.005}]}},
              device_trace={"busy_s": 0.001, "window_s": 1.5},
              counters_before={"jit_cache_misses": 3}, counters_after={"jit_cache_misses": 5, "peak_bytes_in_use": 2e9})
    assert readers.phase_mean(ev, "align") == pytest.approx(2.0)
    assert readers.span_self_time(ev, "groupby", ["calc"]) == pytest.approx(3.0)
    assert readers.client_minus_span(ev, "groupby") == pytest.approx(2.0)
    assert readers.span_minus_device(ev, "calc") == pytest.approx(4.0)
    assert readers.counter_delta(ev, "jit_cache_misses") == 2
    assert readers.counter_value(ev, "peak_bytes_in_use", 1e-9) == pytest.approx(2.0)
    assert readers.reply_field_share(ev, "answer_source", ["recompute"]) == 100.0
    assert readers.route_changes(ev) == 1.0
    assert readers.trace_idle(ev) == pytest.approx(100 * (1 - 0.001 / 1.5))
    assert readers.trace_roofline(ev) == pytest.approx(100 * (16000 / 819e9) / 0.001)
    # a query that lies partly in the slice counts by that part, in bytes and in spans
    ev["slice"] = [1.0075, 2.0]
    assert readers.trace_roofline(ev) == pytest.approx(100 * (0.25 * 16000 / 819e9) / 0.001)
    assert readers.span_minus_device(ev, "calc") == pytest.approx((0.25 * 5.0 - 1.0) / 0.25)
    ev["slice"] = [1.5, 2.0]
    assert readers.trace_roofline(ev) is None and readers.span_minus_device(ev, "calc") is None


# -- the whole run, at a tiny size on the CPU backend ------------------------------------------

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,trace", [
    ("taxi-1chip.adhoc-lowcard", False), ("taxi-1chip.adhoc-heavy", True),
    ("taxi-1chip.dashboard-8", True),
])
def test_rehearse_one_cell_of_each_mix(tmp_path, deployment_defaults, workload, trace):
    result = json.loads(json.dumps(rehearse(tmp_path, workload, trace=trace, control=True)))
    assert CONTRACT_KEYS <= set(result) and list(result)[-1] == "check"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 3
    assert result["device"]["platform"] == "cpu"
    assert result["check"]["answers_recorded"][0] >= result["check"]["answers_compared"][0] >= 1
    assert result["control"]["fails"] is True
    cell = harness.load_cell(workload, str(tmp_path))
    if trace:
        # the CPU backend has no device plane: the trace readers stay silent
        assert set(result["metrics"]) <= set(cell["per_layer"])
        assert {"controller_ms", "executor_aggregate_ms", "compiles_in_window"} <= set(result["metrics"]) or {
            "controller_ms.dash", "cache_answer_share.dash"} == set(result["metrics"])
        assert "groupby_roofline" not in result["metrics"]
    else:
        assert set(result["metrics"]) == set(cell["end_to_end"])
        assert all(m["value"] > 0 for m in result["metrics"].values())
    assert not os.listdir(tmp_path / "benchmark" / ".work")   # nothing left behind


def test_rehearse_the_four_chip_cell_on_four_virtual_devices(tmp_path, deployment_defaults):
    result = rehearse(tmp_path, "taxi-4chip.adhoc-heavy", devices=4)
    assert result["correct"] is True and result["device"]["count"] == 4
    assert set(result["metrics"]) == {"query_ms", "cold_query_s", "setup_s"}
    assert result["observed"]["answer_source"] == {"recompute": result["attempted"]}


def _plus_one(answer):
    answer = answer.copy()
    column = answer.columns[-1]
    if answer[column].dtype.kind == "i":
        answer.loc[answer.index[0], column] += 1
    else:
        answer.loc[answer.index[0], column] *= 1 + 1e-5
    return answer


FAULTS = {
    # an aggregate altered where the answer is produced
    "altered_answer": lambda rpc, args: _plus_one(rpc.groupby(*args)),
    # half of the shards left out, the aggregate taken over the rest
    "half_the_shards": lambda rpc, args: rpc.groupby(args[0][: max(1, len(args[0]) // 2)], *args[1:])
    if len(args[0]) > 1 else rpc.groupby(*args),
    # the answer of another query (the filter dropped)
    "filter_dropped": lambda rpc, args: rpc.groupby(args[0], args[1], args[2], []),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_run_with_the_timed_path_broken_is_not_correct(tmp_path, deployment_defaults, monkeypatch, fault):
    monkeypatch.setattr(harness, "ask", FAULTS[fault])
    result = rehearse(tmp_path, HEAVY)
    assert result["correct"] is False, result["check"]


def test_the_run_fails_without_a_result_where_the_worker_finds_no_tpu(tmp_path, deployment_defaults):
    """The look for a chip is the worker's: with ``JAX_PLATFORMS=cpu`` in
    its place the run ends in a failure, not in a CPU number."""
    from benchmark.cluster import RunFailure

    with pytest.raises(RunFailure, match="cpu"):
        rehearse(tmp_path, HEAVY, seconds=1.0, seed=5, platform="tpu", worker_env={"JAX_PLATFORMS": "cpu"})


# -- a later PR adds a deployment, a mix and a metric as files plus one entry each ---------------

def test_a_configuration_a_mix_and_a_metric_are_added_as_data(tmp_path, deployment_defaults):
    config = harness.load_json(os.path.join(HERE, "taxi-tiny.json"))
    config.update(name="taxi-half", rows=60_000, shards=5)
    (tmp_path / "taxi-half.json").write_text(json.dumps(config))

    def extra(bench):
        cell = "taxi-half.adhoc-sharded"
        bench["configs"].append({"name": "taxi-half", "file": str(tmp_path / "taxi-half.json")})
        bench["workloads"].append({"name": cell, "config": "taxi-half", "traffic": "adhoc-sharded", "chips": 1})
        bench["per_layer"].append({"name": "executor_layout_ms", "unit": "ms", "workloads": [cell]})
        for metric in bench["end_to_end"]:
            if metric["name"] == "query_ms":
                metric["workloads"].append(cell)

    TINY["taxi-half"] = str(tmp_path / "taxi-half.json")
    try:
        home = tiny_home(tmp_path, extra, copy=True)
    finally:
        TINY.pop("taxi-half")
    (tmp_path / "benchmark" / "traffic" / "adhoc-sharded.json").write_text(json.dumps({
        "name": "adhoc-sharded", "why": "two streams that repeat: what a dashboard mix needs of the generator",
        "check_every": 2, "check_at_most": 48, "warmup_max_s": 30, "settle_allow": 0,
        "streams": [{"count": 2, "repeat_share": 0.5, "shapes": [{"shape": "sharded", "weight": 1}]}],
    }))
    (tmp_path / "benchmark" / "layer_metrics" / "executor_layout_ms.json").write_text(json.dumps({
        "name": "executor_layout_ms", "layer": "mesh executor", "unit": "ms", "better": "lower",
        "source": "program_span", "moves": "query_ms", "reader": "phase_mean", "args": {"phase": "layout"},
    }))
    e2e = rehearse(tmp_path, "taxi-half.adhoc-sharded", home=home)
    assert e2e["correct"] is True and set(e2e["metrics"]) == {"query_ms", "setup_s"}
    assert e2e["observed"]["answer_source"].get("recompute", 0) < e2e["attempted"]   # repeats hit a cache
    traced = rehearse(tmp_path, "taxi-half.adhoc-sharded", trace=True, home=home)
    assert set(traced["metrics"]) == {"executor_layout_ms"}
