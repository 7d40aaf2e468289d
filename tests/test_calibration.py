"""Measured-cost kernel-strategy calibration (plan.calibrate).

The contract under test, in the ISSUE's terms:

* COLD START — a store with no samples makes every decision bit-identical
  to the PR-5 heuristic (`select_for_group`), and `BQUERYD_TPU_CALIB=0`
  restores that behaviour even against a warm (or poisoned) store;
* MEASUREMENT — warm cells rank the legal candidates; a measured-best
  matmul is promoted to the binding-inside-guards `matmul!` form, which
  `ops.partial_tables` honours ONLY when the backend guard and the
  groups/cells value guards pass (the forced-matmul regression stays
  unreachable through any hint);
* PERSISTENCE & GOSSIP — save/load round-trips, WRM summaries absorb
  n-weighted into the controller's model, and malformed gossip is dropped
  cell by cell;
* FEEDBACK — the mesh executor and the engine record effective-route
  kernel walls into the process store and report `effective_strategy`.
"""

import logging
import os
import pickle
import time

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.plan import calibrate
from bqueryd_tpu.plan.strategy import (
    STRATEGY_MATMUL_BINDING,
    candidate_strategies,
    choose_strategy,
    select_calibrated,
    select_for_group,
)


def shard_stats(rows, cards, lo=0, hi=100):
    return {
        "rows": rows,
        "cols": {
            col: {"kind": "numeric", "min": lo, "max": hi, "card": card}
            for col, card in cards.items()
        },
    }


def warm(store, strategy, wall_s, rows=10_000_000, groups=9, dtype="int",
         backend="cpu", n=None):
    for _ in range(n if n is not None else calibrate.min_samples()):
        store.record(rows, groups, dtype, backend, strategy, wall_s)


# -- cold start ---------------------------------------------------------------

def test_cold_start_is_bit_identical_to_heuristic():
    store = calibrate.CalibrationStore()
    cases = [
        ({"a": shard_stats(10_000_000, {"k": 9})}, ["a"], ["k"]),
        ({"a": shard_stats(10_000_000, {"k": 70_000})}, ["a"], ["k"]),
        ({"a": shard_stats(10_000_000, {"k": 1_000_000})}, ["a"], ["k"]),
        ({"a": shard_stats(0, {"k": 5})}, ["a"], ["k"]),
        ({}, ["missing"], ["k"]),
    ]
    for stats, files, cols in cases:
        heuristic = select_for_group(stats, files, cols)
        calibrated = select_calibrated(stats, files, cols, calibration=store)
        assert calibrated[:3] == heuristic
        assert calibrated[3] == "cold"


def test_choose_cold_bucket_never_explores(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_CALIB_EPSILON", "1.0")
    store = calibrate.CalibrationStore()
    for _ in range(50):
        choice, reason = store.choose(
            10_000_000, 9, None, ("matmul", "scatter", "sort"), "matmul"
        )
        assert (choice, reason) == ("matmul", "cold")


def test_kill_switch_restores_heuristic_exactly(monkeypatch):
    store = calibrate.CalibrationStore()
    # poisoned model: scatter "measured" as 100x faster than anything
    warm(store, "scatter", 0.001)
    warm(store, "matmul", 1.0)
    stats = {"a": shard_stats(10_000_000, {"k": 9})}
    with_calib = select_calibrated(stats, ["a"], ["k"], calibration=store)
    assert with_calib[0] == "scatter"  # calibration is live before the flip
    monkeypatch.setenv("BQUERYD_TPU_CALIB", "0")
    killed = select_calibrated(stats, ["a"], ["k"], calibration=store)
    assert killed[:3] == select_for_group(stats, ["a"], ["k"])
    assert killed[3] == "cold"
    # recording and gossip shut off with the same switch
    store.record(10_000_000, 9, "int", "cpu", "scatter", 0.5)
    assert store.stats()["samples_total"] == 2 * calibrate.min_samples()
    assert calibrate.summary_for_wire() is None


# -- measured decisions -------------------------------------------------------

def test_measured_override_and_promotion():
    store = calibrate.CalibrationStore()
    stats = {"a": shard_stats(10_000_000, {"k": 9})}
    # heuristic says matmul at 9 groups; measurement says scatter wins
    warm(store, "scatter", 0.01)
    warm(store, "matmul", 0.10)
    strat, est, rows, reason = select_calibrated(
        stats, ["a"], ["k"], calibration=store
    )
    assert (strat, reason) == ("scatter", "measured")
    # ...and the other way around: measured-best matmul becomes BINDING
    store2 = calibrate.CalibrationStore()
    warm(store2, "scatter", 0.10)
    warm(store2, "matmul", 0.01)
    strat2, _est, _rows, reason2 = select_calibrated(
        stats, ["a"], ["k"], calibration=store2
    )
    assert strat2 == STRATEGY_MATMUL_BINDING
    assert reason2 in ("measured", "agree")


def test_agree_keeps_heuristic_within_hysteresis():
    store = calibrate.CalibrationStore()
    # scatter nominally faster, but within the 10% hysteresis band
    warm(store, "matmul", 0.100)
    warm(store, "scatter", 0.095)
    choice, reason = store.choose(
        10_000_000, 9, None, ("matmul", "scatter", "sort"), "matmul"
    )
    assert (choice, reason) == ("matmul", "agree")


def test_candidates_exclude_matmul_past_guards():
    assert "matmul" not in candidate_strategies(10_000_000, 70_000)
    assert "matmul" in candidate_strategies(10_000_000, 9)
    # the cells budget guard: rows x groups beyond 2^36
    assert "matmul" not in candidate_strategies(1 << 33, 8192)


def test_promotion_never_offered_outside_guards():
    """Even a poisoned store claiming matmul is instant cannot promote past
    the value guards: matmul is not a CANDIDATE there."""
    store = calibrate.CalibrationStore()
    warm(store, "matmul", 0.000001, groups=70_000)
    warm(store, "scatter", 10.0, groups=70_000)
    stats = {"a": shard_stats(10_000_000, {"k": 70_000})}
    strat, _est, _rows, _reason = select_calibrated(
        stats, ["a"], ["k"], calibration=store
    )
    assert strat in ("scatter", "sort")


def test_unmeasured_candidate_scored_by_analytic_prior():
    """sort is unmeasured; its analytic units at extreme cardinality are
    far below scatter's blocks x groups table, so the learned
    seconds-per-unit scale must rank it first."""
    store = calibrate.CalibrationStore()
    rows, groups = 10_000_000, 2_000_000
    warm(store, "scatter", 5.0, rows=rows, groups=groups)
    choice, reason = store.choose(
        rows, groups, None, ("scatter", "sort"), "scatter"
    )
    # prior-extrapolated winner: advisory-strength evidence only
    assert (choice, reason) == ("sort", "prior")


def test_prior_extrapolation_never_promotes_matmul():
    """A bucket with only scatter walls where the analytic prior ranks the
    (unmeasured) matmul cheaper must yield the ADVISORY matmul hint — the
    binding promotion requires real matmul measurements."""
    store = calibrate.CalibrationStore()
    rows, groups = 1_000_000, 4  # matmul units rows*4 << scatter rows*8
    warm(store, "scatter", 0.5, rows=rows, groups=groups)
    choice, reason = store.choose(
        rows, groups, None, ("matmul", "scatter", "sort"), "matmul"
    )
    assert (choice, reason) == ("matmul", "prior")
    stats = {"a": shard_stats(rows, {"k": groups})}
    strat, _e, _r, sreason = select_calibrated(
        stats, ["a"], ["k"], calibration=store
    )
    assert strat == "matmul"          # advisory, NOT "matmul!"
    assert sreason == "prior"


def test_binding_promotion_never_rides_the_wire():
    """Mixed-version safety: fragments ship the advisory 'matmul' plus a
    strategy_binding flag old workers ignore — never the 'matmul!' literal
    their KERNEL_STRATEGIES validation would reject."""
    from bqueryd_tpu.plan import fragment_for, plan_groupby

    plan = plan_groupby(["a.bcolzs"], ["k"], [["v", "sum", "v"]], [])
    fragment = fragment_for(plan, ["a.bcolzs"], strategy="matmul!")
    assert fragment["strategy"] == "matmul"
    assert fragment["strategy_binding"] is True
    advisory = fragment_for(plan, ["a.bcolzs"], strategy="matmul")
    assert advisory["strategy"] == "matmul"
    assert advisory["strategy_binding"] is False


def test_exploration_is_bounded_deterministic_and_advisory(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_CALIB_EPSILON", "0.5")
    store = calibrate.CalibrationStore()
    warm(store, "matmul", 0.01)  # heuristic route measured; others not
    stats = {"a": shard_stats(10_000_000, {"k": 9})}
    seen = []
    for _ in range(8):
        strat, _e, _r, reason = select_calibrated(
            stats, ["a"], ["k"], calibration=store
        )
        seen.append((strat, reason))
        assert strat != STRATEGY_MATMUL_BINDING or reason != "explore"
    explored = [s for s, r in seen if r == "explore"]
    assert explored, "eps=0.5 must explore within 8 warm decisions"
    assert len(explored) == 4  # deterministic every-2nd slot, not random
    assert set(explored) <= {"scatter", "sort"}
    monkeypatch.setenv("BQUERYD_TPU_CALIB_EPSILON", "0")
    post = [
        select_calibrated(stats, ["a"], ["k"], calibration=store)[3]
        for _ in range(4)
    ]
    assert "explore" not in post


# -- persistence & gossip -----------------------------------------------------

def test_persistence_round_trip(tmp_path):
    path = str(tmp_path / "calib.json")
    store = calibrate.CalibrationStore(path=path)
    warm(store, "scatter", 0.02, n=7)
    warm(store, "matmul", 0.01, n=4)
    assert store.save()
    reloaded = calibrate.CalibrationStore(path=path)
    assert reloaded.load() == 2
    assert reloaded.summary()["cells"] == store.summary()["cells"]
    # and the reloaded model decides like the original
    assert reloaded.choose(
        10_000_000, 9, "int", ("matmul", "scatter", "sort"), "scatter"
    )[0] == "matmul"


def test_load_missing_or_corrupt_is_cold(tmp_path):
    store = calibrate.CalibrationStore(path=str(tmp_path / "absent.json"))
    assert store.load() == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert calibrate.CalibrationStore(path=str(bad)).load() == 0


def test_absorb_merges_and_drops_garbage():
    a = calibrate.CalibrationStore()
    warm(a, "scatter", 0.04, n=5)
    wire = a.summary()
    # vandalize: malformed keys/cells must be dropped one by one
    wire["cells"]["not-a-key"] = {"n": 3, "ewma_s": 0.1}
    wire["cells"]["r23|g3|int|cpu|matmul"] = {"n": "nan", "ewma_s": "x"}
    wire["cells"]["r23|g3|int|cpu|sort"] = {"n": 2, "ewma_s": -1.0}
    b = calibrate.CalibrationStore()
    assert b.absorb(wire) == 1
    assert b.absorb("nonsense") == 0
    assert b.absorb({"cells": 7}) == 0
    merged = b.summary()["cells"]
    assert list(merged) == list(a.summary()["cells"])
    # n-weighted re-absorb accumulates counts (capped)
    assert b.absorb(wire) == 1
    (cell,) = b.summary()["cells"].values()
    assert cell["n"] == 10


def test_worker_summary_rides_the_wrm(monkeypatch):
    calibrate._reset_for_tests()
    assert calibrate.summary_for_wire() is None  # cold worker advertises nothing
    calibrate.record_sample(
        1_000_000, 16, [np.dtype(np.int64)], "cpu", "scatter", 0.02
    )
    wire = calibrate.summary_for_wire()
    assert wire and "r19|g4|int|cpu|scatter" in wire["cells"]


def test_controller_absorbs_calibration_gossip(tmp_path):
    from bqueryd_tpu.controller import ControllerNode

    node = ControllerNode(
        coordination_url=f"mem://calib-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
    )
    try:
        peer = calibrate.CalibrationStore()
        peer.record(10_000_000, 9, "int", "cpu", "scatter", 0.03)  # ONE wall
        wrm = {"worker_id": "w1", "calibration": peer.summary()}
        node._absorb_shard_stats(wrm)
        assert node.calibration.stats()["cells"] == 1
        # heartbeat re-gossip of the same cumulative summary must NOT
        # double-count: one measured wall stays one sample however many
        # WRMs repeat it, so it can never clear the min-samples floor by
        # repetition alone
        for _ in range(calibrate.min_samples() + 2):
            node._absorb_shard_stats(wrm)
        choice, reason = node.calibration.choose(
            10_000_000, 9, None, ("matmul", "scatter", "sort"), "matmul"
        )
        assert (choice, reason) == ("matmul", "cold")
        # malformed gossip is inert
        node._absorb_shard_stats({"worker_id": "w2", "calibration": "junk"})
        node._absorb_shard_stats(
            {"worker_id": "w2", "calibration": {"cells": ["x"]}}
        )
        assert node.calibration.stats()["cells"] == 1
        # two DISTINCT workers' samples do merge n-weighted
        peer2 = calibrate.CalibrationStore()
        warm(peer2, "scatter", 0.03, n=5)
        node._absorb_shard_stats(
            {"worker_id": "w2", "calibration": peer2.summary()}
        )
        assert node.calibration.stats()["sources"] == 2
        choice, reason = node.calibration.choose(
            10_000_000, 9, None, ("matmul", "scatter", "sort"), "matmul"
        )
        assert reason in ("measured", "prior")  # floor now genuinely met
    finally:
        node.socket.close()


# -- kernel guards under the binding hint ------------------------------------

@pytest.fixture
def mm_counter(monkeypatch):
    """Counts dispatches into the MXU path without changing results."""
    from bqueryd_tpu.ops import groupby as gb

    calls = {"n": 0}
    real = gb._partial_tables_mm

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(gb, "_partial_tables_mm", counting)
    return calls


def _run_partials(strategy, n=4096, groups=9, op="min"):
    from bqueryd_tpu import ops

    rng = np.random.default_rng(3)
    codes = rng.integers(0, groups, n).astype(np.int32)
    values = rng.integers(-50, 50, n).astype(np.int64)
    import jax

    return jax.device_get(
        ops.partial_tables(codes, (values,), (op,), groups,
                           strategy=strategy)
    )


def test_binding_matmul_bypasses_only_profitability(mm_counter):
    """A min-only query fails the op/dtype profitability heuristic (min
    scatters regardless), so auto and advisory 'matmul' both scatter —
    while 'matmul!' takes the MXU path, bit-identically."""
    auto = _run_partials(None)
    assert mm_counter["n"] == 0
    advisory = _run_partials("matmul")
    assert mm_counter["n"] == 0  # advisory == auto, by definition
    bound = _run_partials("matmul!")
    assert mm_counter["n"] == 1
    for a, b in zip(
        (auto["rows"], *auto["aggs"][0].values()),
        (bound["rows"], *bound["aggs"][0].values()),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(auto["aggs"][0]["min"]),
        np.asarray(advisory["aggs"][0]["min"]),
    )


def test_binding_matmul_demotes_past_group_ceiling(mm_counter, monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", "8")
    _run_partials("matmul!", groups=9)
    assert mm_counter["n"] == 0  # value guard stands under promotion


def test_binding_matmul_demotes_on_cpu_backend(mm_counter, monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    bound = _run_partials("matmul!", op="sum")
    assert mm_counter["n"] == 0  # backend guard stands under promotion
    ref = _run_partials("scatter", op="sum")
    np.testing.assert_array_equal(
        np.asarray(bound["aggs"][0]["sum"]),
        np.asarray(ref["aggs"][0]["sum"]),
    )


def test_kernel_route_predictions(monkeypatch):
    from bqueryd_tpu import ops

    ints = [np.zeros(8, np.int64)]
    assert ops.kernel_route("scatter", ints, ("sum",), 10_000, 9) == "scatter"
    assert ops.kernel_route("sort", ints, ("sum",), 10_000, 9) == "sort"
    assert ops.kernel_route(None, ints, ("sum",), 10_000, 9) == "matmul"
    assert ops.kernel_route(None, ints, ("min",), 10_000, 9) == "scatter"
    assert ops.kernel_route("matmul!", ints, ("min",), 10_000, 9) == "matmul"
    # past the blocks x groups budget the adaptive scatter sorts
    assert ops.kernel_route(
        None, ints, ("sum",), 10_000_000, 1_000_000
    ) == "sort"
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    assert ops.kernel_route(
        "matmul!", ints, ("sum",), 10_000, 9
    ) == "scatter"  # backend guard


# -- feedback: executor + engine record and report ---------------------------

def taxi_like_df(n=9_000, seed=11):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "k": rng.integers(1, 7, n).astype(np.int64),
            "v": rng.integers(0, 1000, n).astype(np.int64),
        }
    )


@pytest.fixture
def shard_tables(tmp_path):
    from bqueryd_tpu.storage import ctable

    df = taxi_like_df()
    tables = []
    bounds = np.linspace(0, len(df), 4).astype(int)
    for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        root = str(tmp_path / f"t{i}.bcolzs")
        ctable.fromdataframe(df.iloc[lo:hi].reset_index(drop=True), root)
        tables.append(ctable(root, mode="r"))
    return tables


def test_mesh_executor_reports_route_and_records_samples(shard_tables):
    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh

    store = calibrate._reset_for_tests()
    executor = MeshQueryExecutor(mesh=make_mesh())
    query = GroupByQuery(["k"], [["v", "sum", "v"]])
    executor.execute(shard_tables, query)   # may compile: sample skipped
    executor.execute(shard_tables, query)   # warm: sample recorded
    assert executor.last_effective_strategy == "matmul"  # FORCE_MATMUL=1
    stats = store.stats()
    assert stats["samples_total"] >= 1
    key = calibrate.cell_key(
        calibrate.rows_bucket(sum(t.nrows for t in shard_tables)),
        calibrate.groups_bucket(6), "int", "cpu", "matmul",
    )
    assert key in store.summary(max_cells=512)["cells"]


def test_engine_reports_route(shard_tables):
    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine

    engine = QueryEngine()
    query = GroupByQuery(["k"], [["v", "sum", "v"]])
    engine.execute_local(shard_tables[0], query)
    assert engine.last_effective_strategy == "matmul"
    engine.execute_local(shard_tables[0], query, strategy="host")
    assert engine.last_effective_strategy == "host"
    engine.execute_local(shard_tables[0], query, strategy="scatter")
    assert engine.last_effective_strategy == "scatter"


def test_effective_strategy_reaches_the_client_envelope(tmp_path):
    """Controller folds the workers' effective_strategy replies into the
    result envelope's `strategies` key (RESULT_ENVELOPE_SCHEMA)."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, RPCMessage

    node = ControllerNode(
        coordination_url=f"mem://calib-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
    )
    replies = []
    node.reply_rpc_raw = lambda token, payload: replies.append(payload)
    try:
        node.worker_map["w1"] = {
            "worker_id": "w1", "workertype": "calc", "busy": False,
            "last_seen": time.time(), "node": node.node_name,
        }
        node.files_map["a.bcolzs"] = {"w1"}
        msg = RPCMessage({"payload": "groupby", "token": "00"})
        msg.set_args_kwargs(
            [["a.bcolzs"], ["k"], [["v", "sum", "v"]], []], {}
        )
        node.rpc_groupby(msg)
        (shard,) = [m for q in node.worker_out_messages.values() for m in q]
        reply = CalcMessage(dict(shard))
        reply["data"] = b"payload"
        reply["effective_strategy"] = "scatter"
        node.process_worker_result(reply)
        (payload,) = replies
        envelope = pickle.loads(payload)
        assert envelope["ok"]
        assert envelope["strategies"]["effective"] == {
            "a.bcolzs": "scatter"
        }
        assert "hints" in envelope["strategies"]
    finally:
        node.socket.close()
