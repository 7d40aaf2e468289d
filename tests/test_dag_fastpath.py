"""DAG fast path (PR 15): batched mesh dispatch + device-resident merge.

Covers the acceptance criteria:

* device-vs-host merge parity of the extended part kinds across the fuzz
  surface — top-k largest/smallest x int/float/datetime-NaT ties, sketch
  zero/negative/clamp buckets, mixed classic+extended agg lists — ints,
  top-k multisets and sketch BUCKETS bit-identical, floats within
  reassociation ulps;
* the working-set sharing contract (join-probe gathers, window-bucket
  derived keys, folded composite codes content-keyed: a different-measure
  repeat skips the whole derivation);
* fallback routing: count_distinct / raw rows / over-budget sketch grids
  raise DagFastPathUnsupported (the worker then serves via the PR-13
  per-shard pipeline), query-shape validation errors raise identically on
  both routes;
* the BQUERYD_TPU_DAG_BATCH kill switch: batch gating at the plan layer
  and cluster-level bit-identity vs the per-shard PR-13 path.
"""

import logging
import threading

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import QueryEngine
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import (
    DagFastPathUnsupported,
    MeshQueryExecutor,
)
from bqueryd_tpu.parallel.opexec import DagExecutor
from bqueryd_tpu.plan import dag as dagmod
from bqueryd_tpu.storage.ctable import ctable

from conftest import wait_until

N_SHARDS = 3
ROWS = 2_500
ALPHA = 0.01


def _dataset(seed=515):
    rng = np.random.default_rng(seed)
    frames = []
    for _ in range(N_SHARDS):
        n = ROWS
        ts = pd.to_datetime(
            rng.integers(1_400_000_000, 1_400_050_000, n), unit="s"
        ).to_series().reset_index(drop=True)
        ts[pd.Series(rng.random(n) < 0.07)] = pd.NaT
        frames.append(
            pd.DataFrame(
                {
                    "g": rng.integers(0, 6, n).astype(np.int64),
                    "cust": rng.integers(0, 40, n).astype(np.int64),
                    "k_str": rng.choice(["a", "b", "c"], n),
                    "t": ts.to_numpy(),
                    "v_int": rng.integers(-8, 8, n).astype(np.int64),
                    "v_big": rng.integers(-(2**50), 2**50, n),
                    "u64": rng.integers(0, 2**63, n).astype(np.uint64),
                    "v_float": np.where(
                        rng.random(n) < 0.08,
                        np.nan,
                        rng.random(n) * 200 - 100,
                    ),
                    # zero / negative / past-the-clamp magnitudes: the
                    # sketch's zero bucket, sign handling, and both clamp
                    # edges all get populated
                    "v_ext": np.where(
                        rng.random(n) < 0.2,
                        0.0,
                        np.where(
                            rng.random(n) < 0.5,
                            -rng.random(n) * 1e16,
                            rng.random(n) * 1e-14,
                        ),
                    ),
                }
            )
        )
    return frames


@pytest.fixture(scope="module")
def shards(tmp_path_factory):
    frames = _dataset()
    root = tmp_path_factory.mktemp("dagfast")
    tables = []
    for i, df in enumerate(frames):
        p = str(root / f"fp_{i}.bcolzs")
        ctable.fromdataframe(df, p)
        tables.append(ctable(p, mode="r"))
    return frames, tables


def _dim():
    cust = np.arange(30, dtype=np.int64)
    return {
        "cust": cust,
        "region": np.array(["r%d" % (c % 4) for c in cust], dtype=object),
        "weight": (cust % 7).astype(np.int64),
    }


def _slow(tables, dag):
    """The PR-13 per-shard route (what BQUERYD_TPU_DAG_BATCH=0 restores)."""
    executor = DagExecutor(QueryEngine())
    payloads = [executor.execute_shard(t, dag) for t in tables]
    return hostmerge.merge_payloads(payloads)


def _fast(tables, dag, mex=None):
    from bqueryd_tpu.parallel.executor import _codes_dtype

    mex = mex or MeshQueryExecutor()
    out = dict(mex.execute_dag(tables, dag))
    # every DAG alignment holds its folded dense codes at the width they
    # are packed at (PR 30), for one key and for composites alike
    for key, entry in list(mex._align_cache._data.items()):
        if key[1] == "dagalign":
            dense, combo_cols, _key_values = entry
            want = _codes_dtype(max(len(combo_cols), 1))
            assert [d.dtype for d in dense] == [want] * len(dense)
    return out


def _frames(payload_a, payload_b, sort_cols):
    a = hostmerge.payload_to_dataframe(payload_a)
    b = hostmerge.payload_to_dataframe(payload_b)
    return (
        a.sort_values(sort_cols).reset_index(drop=True),
        b.sort_values(sort_cols).reset_index(drop=True),
    )


# ---------------------------------------------------------------------------
# merge parity: device fast path vs the per-shard host route
# ---------------------------------------------------------------------------

def test_mixed_classic_and_extended_with_join_and_window(shards):
    """The full pipeline in one query — join + window + pushdown + post
    filter + classic + top-k + sketch: ints bit-exact, floats within
    reassociation, top-k lists identical, sketch estimates bit-equal."""
    _frames_src, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"],
        "groupby": ["g", {"window": {"on": "t", "every": "1h",
                                     "alias": "hr"}}],
        "aggs": [
            ["v_int", "sum", "s"],
            ["v_int", "min", "mn"],
            ["v_float", "mean", "m"],
            ["weight", "max", "wmax"],
            ["v_int", "topk", "t3", {"k": 3}],
            ["v_float", "quantile", "p50", {"q": 0.5, "alpha": ALPHA}],
        ],
        "where": [["v_int", ">", -7], ["weight", "<=", 5]],
        "join": {"table": _dim(), "on": "cust",
                 "select": ["region", "weight"]},
    })
    mex = MeshQueryExecutor()
    fast = _fast(tables, dag, mex)
    assert mex.last_merge_mode == "device"
    a, b = _frames(fast, _slow(tables, dag), ["g", "hr"])
    assert len(a) == len(b) and len(a) > 0
    for col in ("g", "hr", "s", "mn", "wmax"):
        assert a[col].tolist() == b[col].tolist(), col
    np.testing.assert_allclose(
        a["m"].to_numpy(), b["m"].to_numpy(), rtol=1e-12
    )
    for x, y in zip(a["t3"], b["t3"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(
        a["p50"].to_numpy(), b["p50"].to_numpy()
    )


@pytest.mark.parametrize("col,largest", [
    ("v_int", True),      # heavy ties: multiset semantics
    ("v_int", False),
    ("v_big", True),
    ("v_float", False),   # NaN skipping + float sort key
    ("t", True),          # datetime: NaT sentinel + int64 bitwise-not sort
])
def test_topk_parity_matrix(shards, col, largest):
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [[col, "topk", "tk", {"k": 5, "largest": largest}]],
    })
    a, b = _frames(_fast(tables, dag), _slow(tables, dag), ["g"])
    assert a["g"].tolist() == b["g"].tolist()
    for x, y in zip(a["tk"], b["tk"]):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype
        np.testing.assert_array_equal(xa, ya)


def test_sketch_buckets_bit_equal_including_clamps(shards):
    """The device-merged grid converts to EXACTLY the flat sketch part the
    host merge produces — zero bucket, negative keys, and both clamp
    edges included — so estimates are bit-equal, not just within alpha."""
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [
            ["v_ext", "quantile", "q1", {"q": 0.1, "alpha": 0.02}],
            ["v_ext", "quantile", "q9", {"q": 0.9, "alpha": 0.02}],
        ],
    })
    fast, slow = _fast(tables, dag), _slow(tables, dag)
    # align groups by key value, then compare the flat sketch parts
    fast_order = np.argsort(np.asarray(fast["keys"]["g"]))
    slow_order = np.argsort(np.asarray(slow["keys"]["g"]))
    for ai in range(2):
        fa, sa = fast["aggs"][ai], slow["aggs"][ai]
        fo = np.asarray(fa["sketch_offsets"])
        so = np.asarray(sa["sketch_offsets"])
        for gf, gs in zip(fast_order, slow_order):
            np.testing.assert_array_equal(
                np.asarray(fa["sketch_keys"])[fo[gf]:fo[gf + 1]],
                np.asarray(sa["sketch_keys"])[so[gs]:so[gs + 1]],
            )
            np.testing.assert_array_equal(
                np.asarray(fa["sketch_counts"])[fo[gf]:fo[gf + 1]],
                np.asarray(sa["sketch_counts"])[so[gs]:so[gs + 1]],
            )
    a, b = _frames(fast, slow, ["g"])
    np.testing.assert_array_equal(a["q1"].to_numpy(), b["q1"].to_numpy())
    np.testing.assert_array_equal(a["q9"].to_numpy(), b["q9"].to_numpy())


def test_uint64_and_string_keys_parity(shards):
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["k_str"],
        "aggs": [
            ["u64", "sum", "us"],
            ["u64", "max", "umax"],
            ["v_int", "topk", "tk", {"k": 2}],
        ],
    })
    a, b = _frames(_fast(tables, dag), _slow(tables, dag), ["k_str"])
    assert a["k_str"].tolist() == b["k_str"].tolist()
    assert a["us"].tolist() == b["us"].tolist()
    assert a["us"].dtype == b["us"].dtype  # mod-2^64 unsigned view kept
    assert a["umax"].tolist() == b["umax"].tolist()
    for x, y in zip(a["tk"], b["tk"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_topk_emission_routes_agree_directly():
    """All three dense emissions — matrix-argmax, segment k-pass, lexsort —
    produce the same flat partials as the numpy host twin (the k-pass and
    lexsort routes are only reachable via routing at >4096 groups / k >
    TOPK_KPASS_MAX_K, so they get direct coverage here)."""
    import jax
    import jax.numpy as jnp

    from bqueryd_tpu.ops import relops
    from bqueryd_tpu.parallel import opexec

    rng = np.random.default_rng(9)
    n, G, k = 3000, 7, 4
    codes = rng.integers(-1, G, n)
    for vals, drop_nan, float_neg in (
        (rng.integers(-5, 5, n).astype(np.int64), False, False),
        (np.where(rng.random(n) < 0.1, np.nan, rng.random(n)), True, True),
    ):
        for largest in (True, False):
            expected = opexec.topk_flat(codes, vals, k, largest, G)
            for emit in (
                relops.topk_matrix_block,
                relops.topk_kpass_block,
            ):
                dense, cnt = jax.device_get(
                    emit(
                        jnp.asarray(codes), jnp.asarray(vals), None,
                        k, largest, G, drop_nan, None,
                    )
                )
                got = opexec.dense_topk_to_flat(
                    np.asarray(dense), np.asarray(cnt)
                )
                np.testing.assert_array_equal(expected[1], got[1])
                np.testing.assert_array_equal(expected[0], got[0])
            dense, cnt = jax.device_get(
                relops.topk_dense_block(
                    jnp.asarray(codes), jnp.asarray(vals), None,
                    k, largest, G, drop_nan, None, float_neg,
                )
            )
            got = opexec.dense_topk_to_flat(
                np.asarray(dense), np.asarray(cnt)
            )
            np.testing.assert_array_equal(expected[1], got[1])
            np.testing.assert_array_equal(expected[0], got[0])


def test_topk_kpass_and_sort_routes_agree(shards):
    """The k-pass segment route (k <= TOPK_KPASS_MAX_K) and the lexsort
    route emit identical flat partials — both against each other (k
    straddling the crossover) and against the numpy host twin."""
    from bqueryd_tpu.ops import relops
    from bqueryd_tpu.parallel import opexec

    frames, _tables = shards
    rng = np.random.default_rng(3)
    codes = rng.integers(-1, 5, 4000)
    for col_vals in (
        rng.integers(-6, 6, 4000).astype(np.int64),        # ties
        np.where(rng.random(4000) < 0.1, np.nan, rng.random(4000)),
    ):
        for largest in (True, False):
            for k in (3, relops.TOPK_KPASS_MAX_K + 8):  # both routes
                host = opexec.topk_flat(
                    codes, col_vals, k, largest, 5
                )
                dev = relops.topk_partials(
                    codes, col_vals, k, largest, 5
                )
                np.testing.assert_array_equal(host[1], dev[1])
                np.testing.assert_array_equal(host[0], dev[0])


# ---------------------------------------------------------------------------
# the shared decode/align/H2D pass (working-set contract)
# ---------------------------------------------------------------------------

def test_different_measures_share_derivations(shards):
    """A second DAG query with DIFFERENT aggs over the same derivation
    pipeline (same join/window/filter/keys) hits the cached alignment and
    folded codes — the decode/align/H2D pass is shared, like folded group
    codes always were for classic queries."""
    _f, tables = shards
    mex = MeshQueryExecutor()
    base = {
        "table": ["x"],
        "groupby": ["g", {"window": {"on": "t", "every": "1h",
                                     "alias": "hr"}}],
        "where": [["v_int", ">", -7]],
        "join": {"table": _dim(), "on": "cust", "select": ["region"]},
    }
    _fast(tables, dagmod.compile_query(
        {**base, "aggs": [["v_int", "sum", "s"]]}
    ), mex)
    align_hits = mex.workingset.stats()["align"]["hits"]
    codes_hits = mex.workingset.stats()["codes"]["hits"]
    _fast(tables, dagmod.compile_query(
        {**base, "aggs": [["v_float", "mean", "m"],
                          ["v_float", "quantile", "p9", {"q": 0.9}]]}
    ), mex)
    stats = mex.workingset.stats()
    assert stats["align"]["hits"] > align_hits
    assert stats["codes"]["hits"] > codes_hits


# ---------------------------------------------------------------------------
# fallback routing + kill switch
# ---------------------------------------------------------------------------

def test_count_distinct_and_raw_rows_not_batchable():
    cd = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v", "count_distinct", "cd"]],
    })
    assert not dagmod.dag_batchable(cd)
    _plan, kw = dagmod.groupby_equivalent(cd)
    assert kw["batch"] is False
    ext = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v", "topk", "t", {"k": 2}]],
    })
    assert dagmod.dag_batchable(ext)
    _plan, kw = dagmod.groupby_equivalent(ext)
    assert kw["batch"] is True


def test_dag_batch_env_kill_switch(monkeypatch):
    ext = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v", "quantile", "q", {"q": 0.5}]],
    })
    monkeypatch.setenv("BQUERYD_TPU_DAG_BATCH", "0")
    assert not dagmod.dag_batchable(ext)
    _plan, kw = dagmod.groupby_equivalent(ext)
    assert kw["batch"] is False


def test_count_distinct_dag_raises_fast_path_unsupported(shards):
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v_int", "count_distinct", "cd"],
                 ["v_int", "topk", "t", {"k": 2}]],
    })
    with pytest.raises(DagFastPathUnsupported):
        MeshQueryExecutor().execute_dag(tables, dag)


def test_sketch_grid_budget_falls_back(shards, monkeypatch):
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v_float", "quantile", "p5", {"q": 0.5}]],
    })
    monkeypatch.setenv("BQUERYD_TPU_SKETCH_GRID_CELLS", "16")
    with pytest.raises(DagFastPathUnsupported):
        MeshQueryExecutor().execute_dag(tables, dag)


def test_validation_errors_identical_on_both_routes(shards):
    """A top-k over a dict (string) column raises the SAME DagValidationError
    on the fast path as on the per-shard route — the fast path never masks
    or reclassifies a query-shape error as a silent fallback."""
    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["k_str", "topk", "t", {"k": 2}]],
    })
    with pytest.raises(dagmod.DagValidationError) as fast_err:
        MeshQueryExecutor().execute_dag(tables, dag)
    with pytest.raises(dagmod.DagValidationError) as slow_err:
        DagExecutor(QueryEngine()).execute_shard(tables[0], dag)
    assert str(fast_err.value) == str(slow_err.value)


def test_worker_falls_back_when_unsupported(shards):
    """The worker-level router serves an ineligible DAG via the per-shard
    pipeline instead of failing the query."""
    from bqueryd_tpu.plan.dag import parse_op  # noqa: F401 - import check

    _f, tables = shards
    dag = dagmod.compile_query({
        "table": ["x"], "groupby": ["g"],
        "aggs": [["v_int", "count_distinct", "cd"]],
    })
    # dag_batchable is False -> the worker path goes straight per-shard;
    # emulate the routing condition the worker applies
    assert not dagmod.dag_batchable(dag)
    merged = _slow(tables, dag)
    df = hostmerge.payload_to_dataframe(merged)
    full = pd.concat(_f, ignore_index=True)
    exp = full.groupby("g")["v_int"].nunique().to_dict()
    assert dict(zip(df["g"], df["cd"])) == exp


# ---------------------------------------------------------------------------
# cluster e2e: batched dispatch + kill-switch bit-identity
# ---------------------------------------------------------------------------

def _start(*nodes):
    threads = [
        threading.Thread(target=node.go, daemon=True) for node in nodes
    ]
    for t in threads:
        t.start()
    return threads


@pytest.fixture
def fp_cluster(tmp_path, mem_store_url):
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    frames = _dataset(seed=77)[:2]
    for i, df in enumerate(frames):
        ctable.fromdataframe(df, str(tmp_path / f"fpc_{i}.bcolzs"))
    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.1,
    )
    worker = WorkerNode(
        coordination_url=mem_store_url, data_dir=str(tmp_path),
        loglevel=logging.WARNING, restart_check=False,
        heartbeat_interval=0.1, poll_timeout=0.05,
    )
    threads = _start(controller, worker)
    wait_until(
        lambda: all(
            controller.files_map.get(f"fpc_{i}.bcolzs") for i in range(2)
        ),
        desc="shards advertised",
    )
    rpc = RPC(
        coordination_url=mem_store_url, timeout=30, loglevel=logging.WARNING
    )
    yield {
        "rpc": rpc, "controller": controller, "worker": worker,
        "frames": frames,
        "shards": [f"fpc_{i}.bcolzs" for i in range(2)],
    }
    controller.running = False
    worker.running = False
    for t in threads:
        t.join(timeout=5)


def test_cluster_batched_dag_dispatch_and_kill_switch(
    fp_cluster, monkeypatch
):
    """A batched DAG query ships ONE CalcMessage for the co-located shard
    group and replies merge_mode 'device'; under BQUERYD_TPU_DAG_BATCH=0
    the same spec dispatches per shard (PR-13 shape), merges host-side,
    and the answers are bit-identical (ints) across the two paths."""
    rpc = fp_cluster["rpc"]
    controller = fp_cluster["controller"]
    spec = {
        "table": fp_cluster["shards"], "groupby": ["g"],
        "aggs": [
            ["v_int", "sum", "s"],
            ["v_int", "topk", "t3", {"k": 3}],
            ["v_float", "quantile", "p50", {"q": 0.5, "alpha": ALPHA}],
        ],
        "where": [["v_int", ">", -7]],
    }
    before = controller.counters["dispatched_shards"]
    batched = rpc.query(spec)
    assert controller.counters["dispatched_shards"] - before == 1
    assert "device" in (rpc.last_call_merge_modes or {}).values()

    monkeypatch.setenv("BQUERYD_TPU_DAG_BATCH", "0")
    before = controller.counters["dispatched_shards"]
    per_shard = rpc.query(spec)
    assert controller.counters["dispatched_shards"] - before == 2
    modes = set((rpc.last_call_merge_modes or {}).values())
    assert "device" not in modes

    a = batched.sort_values("g").reset_index(drop=True)
    b = per_shard.sort_values("g").reset_index(drop=True)
    assert a["g"].tolist() == b["g"].tolist()
    assert a["s"].tolist() == b["s"].tolist()
    for x, y in zip(a["t3"], b["t3"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(
        a["p50"].to_numpy(), b["p50"].to_numpy()
    )
