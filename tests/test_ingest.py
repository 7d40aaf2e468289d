"""Streaming ingest (PR 14): append path, chunk-granular zone-map pruning,
and delta-maintained hot aggregates.

Three layers of coverage:

* storage — per-chunk zone maps, snapshot-consistent mid-append reads,
  torn-append repair, append-safe column-cache keys, ChunkView decode;
* engine/executor — chunk pruning parity (engine, mesh, raw rows, DAG
  pushdown) vs the unpruned path, gates and kill switches;
* cluster — ``rpc.append`` fan-out (replica dedup by (node, data_dir)),
  delta-refreshed repeat queries, incremental stats re-advertisement,
  structured errors (unknown file, disabled, mixed-version).
"""

import logging
import os
import threading

import numpy as np
import pandas as pd
import pytest

from conftest import wait_until

from bqueryd_tpu.models.query import GroupByQuery, QueryEngine
from bqueryd_tpu.ops import predicates
from bqueryd_tpu.ops.workingset import (
    DeltaAggCache,
    growth_since,
    table_growth_base,
)
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.plan.stats import (
    StatsCollector,
    gather_table_stats,
    zone_can_match,
)
from bqueryd_tpu.storage.ctable import ChunkView, ctable, table_cache_key


def _frame(n, seed=0, offset=0):
    rng = np.random.RandomState(seed)
    return pd.DataFrame(
        {
            "g": rng.randint(0, 5, n).astype(np.int64),
            "v": rng.randint(-100, 100, n).astype(np.int64),
            "f": rng.random(n).astype(np.float32),
            "s": (rng.randint(0, 3, n)).astype(str),
            "seq": np.arange(offset, offset + n, dtype=np.int64),
            "ts": (
                np.int64(1_700_000_000_000_000_000)
                + np.arange(offset, offset + n, dtype=np.int64)
                * np.int64(60_000_000_000)
            ).view("datetime64[ns]"),
        }
    )


def _finalize(payloads):
    return hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads(list(payloads))
    )


def _sorted(df, keys):
    return df.sort_values(keys).reset_index(drop=True)


# ---------------------------------------------------------------------------
# storage: zone maps, snapshots, cache keys, views
# ---------------------------------------------------------------------------

def test_append_writes_chunk_zone_maps(tmp_path):
    df = _frame(1000)
    t = ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"), chunklen=100)
    maps = t.chunk_zone_maps("seq")
    assert len(maps) == 10
    assert maps[0] == (0, 99) and maps[9] == (900, 999)
    # datetime zone maps are physical int64 ns
    ts_maps = t.chunk_zone_maps("ts")
    assert ts_maps[0][0] == int(df["ts"].iloc[0].value)
    # dict columns carry none
    assert t.chunk_zone_maps("s") is None
    # column-level stats agree with the folded zone maps
    assert t.col_stats("seq") == (0, 999)


def test_zone_maps_skip_nan_and_nat(tmp_path):
    df = pd.DataFrame(
        {
            "f": np.array([np.nan, 1.5, 2.5, np.nan], dtype=np.float64),
            "ts": pd.to_datetime(
                [None, "2024-01-01", "2024-01-02", None]
            ),
        }
    )
    t = ctable.fromdataframe(df, str(tmp_path / "n.bcolzs"), chunklen=2)
    assert t.chunk_zone_maps("f")[0] == (1.5, 1.5)
    # all-NaT chunk carries no zone map (conservatively matches)
    df2 = pd.DataFrame({"f": [np.nan, np.nan], "ts": pd.to_datetime([None, None])})
    ctable(str(tmp_path / "n.bcolzs"), mode="a").append_dataframe(df2)
    t2 = ctable(str(tmp_path / "n.bcolzs"))
    assert t2.chunk_zone_maps("ts")[-1] is None


def test_mid_append_reader_keeps_snapshot(tmp_path):
    """A reader opened mid-append (column index grown, meta.json not yet
    renamed) decodes exactly its committed row-count snapshot."""
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(300), root, chunklen=100)
    torn = ctable(root, mode="a")
    # simulate the torn window: chunk data + column meta written for one
    # column, meta.json row count NOT yet committed
    torn._append_physical("v", np.arange(50, dtype=np.int64))
    reader = ctable(root, mode="r")
    assert reader.nrows == 300
    assert len(reader.column_raw("v")) == 300
    assert len(reader.committed_chunks("v")) == 3


def test_torn_append_repaired_on_next_append(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(300), root, chunklen=100)
    torn = ctable(root, mode="a")
    torn._append_physical("v", np.arange(50, dtype=np.int64))
    # the next real append truncates the uncommitted index entries, so the
    # chunk grid stays synchronized across columns
    appender = ctable(root, mode="a")
    extra = _frame(40, seed=1, offset=300)
    appender.append_dataframe(extra)
    t = ctable(root)
    assert t.nrows == 340
    assert t.chunk_rows() is not None  # consistent grid
    np.testing.assert_array_equal(
        t.column_raw("v")[-40:], extra["v"].to_numpy()
    )
    # every column ends on the same chunk count
    counts = {len(t.committed_chunks(c)) for c in t.names}
    assert len(counts) == 1


def test_column_cache_never_serves_stale_after_append(tmp_path):
    """Satellite: content keys incorporate chunk/row counts, so a reader
    opened pre-append never poisons the cache for post-append readers (and
    vice versa) even though both stat the same grown data file."""
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(200), root, chunklen=100)
    old_reader = ctable(root)
    ctable(root, mode="a").append_dataframe(_frame(100, seed=2, offset=200))
    # the OLD instance decodes (and caches) its 200-row snapshot while the
    # file on disk already holds 300 rows
    assert len(old_reader.column_raw("v")) == 200
    new_reader = ctable(root)
    assert len(new_reader.column_raw("v")) == 300
    # and reading through the old instance again still yields its snapshot
    assert len(old_reader.column_raw("v")) == 200


def test_chunk_view_values_stats_and_identity(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    df = _frame(1000)
    t = ctable.fromdataframe(df, root, chunklen=100)
    view = t.chunk_view([2, 7])
    assert view.nrows == 200
    np.testing.assert_array_equal(
        view.column_raw("seq"),
        np.concatenate([np.arange(200, 300), np.arange(700, 800)]),
    )
    # zone-folded stats over the selection only
    assert view.col_stats("seq") == (200, 299) or view.col_stats("seq") == (
        200, 799,
    )
    assert view.col_stats("seq")[0] == 200
    # dict + datetime logical decode round-trips
    np.testing.assert_array_equal(
        view.column("s"), df["s"].to_numpy(dtype=object)[
            np.r_[200:300, 700:800]
        ],
    )
    assert view.column("ts").dtype == np.dtype("datetime64[ns]")
    # distinct cache identity per selection, parent, and parent growth
    k1 = table_cache_key(view)
    assert k1 != table_cache_key(t.chunk_view([2, 8]))
    assert k1 == table_cache_key(t.chunk_view([2, 7]))
    ctable(root, mode="a").append_dataframe(_frame(10, seed=3, offset=1000))
    t2 = ctable(root)
    assert table_cache_key(t2.chunk_view([2, 7])) != k1


def test_tail_view_boundaries(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(250), root, chunklen=100)
    ctable(root, mode="a").append_dataframe(_frame(70, seed=4, offset=250))
    t = ctable(root)
    tail = t.tail_view(250)
    assert tail is not None and tail.nrows == 70
    np.testing.assert_array_equal(
        tail.column_raw("seq"), np.arange(250, 320)
    )
    assert t.tail_view(240) is None       # not a chunk boundary
    assert t.tail_view(320).nrows == 0    # end-of-table tail is empty


# ---------------------------------------------------------------------------
# stats: zone_can_match + incremental gather
# ---------------------------------------------------------------------------

def test_zone_can_match_matrix():
    assert zone_can_match(10, 20, "==", 15)
    assert not zone_can_match(10, 20, "==", 25)
    assert zone_can_match(10, 20, ">", 15)
    assert not zone_can_match(10, 20, ">", 20)
    assert zone_can_match(10, 20, ">=", 20)
    assert not zone_can_match(10, 20, ">=", 21)
    assert zone_can_match(10, 20, "<", 11)
    assert not zone_can_match(10, 20, "<", 10)
    assert zone_can_match(10, 20, "<=", 10)
    assert not zone_can_match(10, 20, "<=", 9)
    assert zone_can_match(10, 20, "in", [1, 15])
    assert not zone_can_match(10, 20, "in", [1, 25])
    assert zone_can_match(10, 20, "in", [])            # conservative
    # != never prunes (NaN rows satisfy it but are invisible to zone maps)
    assert zone_can_match(10, 10, "!=", 10)
    # incomparable values conservatively match
    assert zone_can_match(10, 20, "==", "oops")


def test_gather_stats_incremental_on_append(tmp_path, monkeypatch):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(400), root, chunklen=100)
    t1 = ctable(root)
    prev = gather_table_stats(t1)
    assert prev["cols"]["v"]["chunks"] == 4
    ctable(root, mode="a").append_dataframe(
        pd.DataFrame(
            {
                "g": [1], "v": [5000], "f": [0.5], "s": ["zz"],
                "seq": [9999],
                "ts": _frame(1)["ts"],
            }
        )
    )
    t2 = ctable(root)
    # the incremental path folds the new chunk's zone map into the previous
    # bounds: it must not re-read the whole column's stats
    calls = []
    real = t2.col_stats
    monkeypatch.setattr(
        t2, "col_stats", lambda name: calls.append(name) or real(name),
    )
    fresh = gather_table_stats(t2, prev=prev)
    assert "v" not in calls, "a grown column must fold, not re-gather"
    assert fresh["rows"] == 401
    assert fresh["cols"]["v"]["max"] == 5000     # folded from the new chunk
    assert fresh["cols"]["v"]["min"] == prev["cols"]["v"]["min"]
    assert fresh["cols"]["v"]["chunks"] == 5
    # parity with the full gather
    full = gather_table_stats(t2)
    assert fresh["cols"]["v"]["min"] == full["cols"]["v"]["min"]
    assert fresh["cols"]["v"]["max"] == full["cols"]["v"]["max"]


def test_gather_stats_rejects_in_place_replacement(tmp_path):
    """An in-place shard replacement with same-or-more chunks must NOT
    pass as an append: the per-column prefix fingerprint fails and the
    gather falls back to full stats — stale min/max folded into fresh
    advertisements would let the controller prune shards whose new rows
    match."""
    root = str(tmp_path / "t.bcolzs")
    old = _frame(400, seed=40)
    old["v"] += 100_000  # old bounds far from the replacement's
    ctable.fromdataframe(old, root, chunklen=100)
    prev = gather_table_stats(ctable(root))
    assert prev["cols"]["v"]["min"] >= 99_000
    # replace in place: same name, MORE chunks, completely different values
    ctable.fromdataframe(_frame(500, seed=41), root, chunklen=100)
    fresh = gather_table_stats(ctable(root), prev=prev)
    full = gather_table_stats(ctable(root))
    assert fresh["cols"]["v"]["min"] == full["cols"]["v"]["min"] < 0
    assert fresh["cols"]["v"]["max"] == full["cols"]["v"]["max"]
    assert fresh == full


def test_stats_collector_invalidate_drops_window(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(100), root)
    collector = StatsCollector(min_refresh_s=3600.0)
    first = collector.collect(str(tmp_path), ["t.bcolzs"])
    assert first["t.bcolzs"]["rows"] == 100
    ctable(root, mode="a").append_dataframe(_frame(20, seed=5, offset=100))
    # inside the refresh window: the stale snapshot object is returned
    assert collector.collect(str(tmp_path), ["t.bcolzs"]) is first
    collector.invalidate()
    fresh = collector.collect(str(tmp_path), ["t.bcolzs"])
    assert fresh["t.bcolzs"]["rows"] == 120


# ---------------------------------------------------------------------------
# pruning: selection, gates, parity
# ---------------------------------------------------------------------------

def test_chunk_selection_ops(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    df = _frame(1000)
    t = ctable.fromdataframe(df, root, chunklen=100)
    keep = predicates.chunk_selection(t, [["seq", ">", 850]])
    np.testing.assert_array_equal(keep, np.arange(10) >= 8)
    keep = predicates.chunk_selection(t, [["seq", "==", 250]])
    assert keep.sum() == 1 and keep[2]
    keep = predicates.chunk_selection(t, [["seq", "in", [50, 750]]])
    np.testing.assert_array_equal(np.flatnonzero(keep), [0, 7])
    # conjunction intersects
    keep = predicates.chunk_selection(
        t, [["seq", ">", 450], ["seq", "<=", 650]]
    )
    np.testing.assert_array_equal(np.flatnonzero(keep), [4, 5, 6])
    # datetime terms translate to ns before the zone compare
    cut = pd.Timestamp(df["ts"].iloc[900])
    keep = predicates.chunk_selection(t, [["ts", ">=", cut]])
    np.testing.assert_array_equal(np.flatnonzero(keep), [9])
    # dict columns and != contribute no pruning
    assert predicates.chunk_selection(t, [["s", "==", "1"]]) is None
    assert predicates.chunk_selection(t, [["seq", "!=", 5]]) is None
    # a non-selective term prunes nothing
    assert predicates.chunk_selection(t, [["seq", ">=", 0]]) is None


def test_chunk_pruned_table_gates(tmp_path, monkeypatch):
    root = str(tmp_path / "t.bcolzs")
    t = ctable.fromdataframe(_frame(1000), root, chunklen=100)
    terms = [["seq", ">", 850]]
    view, decoded, skipped = predicates.chunk_pruned_table(t, terms)
    assert isinstance(view, ChunkView) and (decoded, skipped) == (2, 8)
    # kill switch
    monkeypatch.setenv("BQUERYD_TPU_CHUNK_PRUNE", "0")
    same, decoded, skipped = predicates.chunk_pruned_table(t, terms)
    assert same is t and decoded == 0 and skipped == 0
    monkeypatch.delenv("BQUERYD_TPU_CHUNK_PRUNE")
    # selectivity floor: a near-full selection stays unpruned (counted)
    monkeypatch.setenv("BQUERYD_TPU_CHUNK_PRUNE_SELECTIVITY", "0.5")
    same, decoded, skipped = predicates.chunk_pruned_table(
        t, [["seq", ">", 150]]
    )
    assert same is t and (decoded, skipped) == (10, 0)
    # under the floor it prunes again
    view2, decoded, skipped = predicates.chunk_pruned_table(
        t, [["seq", ">", 850]]
    )
    assert isinstance(view2, ChunkView) and (decoded, skipped) == (2, 8)


@pytest.mark.parametrize(
    "terms",
    [
        [["seq", ">", 820]],
        [["seq", "<=", 120], ["v", ">", 0]],
        [["seq", "in", [10, 470, 980]]],
    ],
)
def test_engine_parity_with_chunk_pruning(tmp_path, terms):
    """Pruned execution is bit-identical to the full-table pass: zone maps
    are proofs, and surviving rows keep their order (float reductions see
    the same operand sequence)."""
    root = str(tmp_path / "t.bcolzs")
    df = _frame(2000, seed=7)
    t = ctable.fromdataframe(df, root, chunklen=128)
    query = GroupByQuery(
        ["g"],
        [
            ["v", "sum", "vs"], ["f", "mean", "fm"],
            ["v", "min", "vmin"], ["v", "max", "vmax"],
            ["f", "count", "n"],
        ],
        terms,
    )
    engine = QueryEngine()
    full = engine.execute_local(t, query, strategy="host")
    view, decoded, skipped = predicates.chunk_pruned_table(t, terms)
    assert skipped > 0
    pruned = engine.execute_local(view, query, strategy="host")
    a = _sorted(_finalize([full]), ["g"])
    b = _sorted(_finalize([pruned]), ["g"])
    pd.testing.assert_frame_equal(a, b)
    for col in ("vs", "vmin", "vmax", "n"):
        np.testing.assert_array_equal(
            a[col].to_numpy(), b[col].to_numpy()
        )


def test_raw_rows_chunk_prune_parity(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    df = _frame(1000, seed=8)
    t = ctable.fromdataframe(df, root, chunklen=100)
    terms = [["seq", ">=", 870]]
    query = GroupByQuery(["g"], [["v", "sum", "v"]], terms, aggregate=False)
    engine = QueryEngine()
    full = engine.execute_local(t, query)
    view, _, skipped = predicates.chunk_pruned_table(t, terms)
    assert skipped > 0
    pruned = engine.execute_local(view, query)
    for col in full["order"]:
        np.testing.assert_array_equal(
            np.asarray(full["columns"][col]),
            np.asarray(pruned["columns"][col]),
        )


def test_mesh_executor_accepts_chunk_views(tmp_path):
    """The mesh path runs over views: alignment, wire narrowing and the
    device caches key on the view's own identity."""
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor

    roots = []
    frames = []
    for i in range(2):
        df = _frame(600, seed=20 + i, offset=600 * i)
        root = str(tmp_path / f"s{i}.bcolzs")
        ctable.fromdataframe(df, root, chunklen=100)
        roots.append(root)
        frames.append(df)
    tables = [ctable(r) for r in roots]
    terms = [["seq", ">=", 1000]]
    query = GroupByQuery(
        ["g"], [["v", "sum", "vs"], ["f", "mean", "fm"]], terms
    )
    executor = MeshQueryExecutor()
    full = executor.execute(tables, query)
    pruned_tables = []
    skipped_total = 0
    for t in tables:
        view, _, skipped = predicates.chunk_pruned_table(t, terms)
        pruned_tables.append(view)
        skipped_total += skipped
    assert skipped_total > 0
    pruned = executor.execute(pruned_tables, query)
    a = _sorted(_finalize([full]), ["g"])
    b = _sorted(_finalize([pruned]), ["g"])
    np.testing.assert_array_equal(a["vs"].to_numpy(), b["vs"].to_numpy())
    np.testing.assert_allclose(
        a["fm"].to_numpy(), b["fm"].to_numpy(), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# delta maintenance
# ---------------------------------------------------------------------------

def test_growth_since_validation(tmp_path):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(300), root, chunklen=100)
    base = table_growth_base(ctable(root))
    # no growth -> empty id list
    assert growth_since(base, ctable(root)) == []
    ctable(root, mode="a").append_dataframe(_frame(150, seed=9, offset=300))
    grown = ctable(root)
    assert growth_since(base, grown) == [3, 4]
    # a rewrite (same rows, different bytes) must NOT validate
    ctable.fromdataframe(
        pd.concat(
            [_frame(300, seed=31), _frame(150, seed=32, offset=300)],
            ignore_index=True,
        ),
        root, chunklen=100,
    )
    assert growth_since(base, ctable(root)) is None
    # shrink must not validate either
    small = str(tmp_path / "small.bcolzs")
    ctable.fromdataframe(_frame(100), small, chunklen=100)
    assert growth_since(base, ctable(small)) is None


def test_delta_cache_refresh_parity(tmp_path):
    """delta = merge(cached partial, tail partial) must equal the full
    recompute: ints bit-exact, float means within reassociation ulps."""
    from bqueryd_tpu.models.query import ResultPayload

    root = str(tmp_path / "t.bcolzs")
    df = _frame(2000, seed=11)
    ctable.fromdataframe(df, root, chunklen=256)
    query = GroupByQuery(
        ["g"],
        [
            ["v", "sum", "vs"], ["f", "mean", "fm"],
            ["v", "min", "vmin"], ["v", "max", "vmax"],
        ],
        [["v", ">", -50]],
    )
    engine = QueryEngine()
    t1 = ctable(root)
    base_payload = engine.execute_local(t1, query, strategy="host")
    cache = DeltaAggCache()
    key = ("k",)
    assert cache.store(key, [t1], ResultPayload(base_payload).to_bytes())
    extra = _frame(180, seed=12, offset=2000)
    ctable(root, mode="a").append_dataframe(extra)
    t2 = ctable(root)
    entry = cache.get(key)
    ids = cache.refresh_ids(entry, [t2])
    assert ids == [[8]]
    tail = t2.chunk_view(ids[0])
    assert tail.nrows == 180
    tail_payload = engine.execute_local(tail, query, strategy="host")
    merged = _sorted(
        _finalize(
            [ResultPayload.from_bytes(entry["data"]), tail_payload]
        ),
        ["g"],
    )
    expected_df = pd.concat([df, extra], ignore_index=True)
    expected_df = expected_df[expected_df["v"] > -50]
    expected = _sorted(
        expected_df.groupby("g", as_index=False).agg(
            vs=("v", "sum"), fm=("f", "mean"),
            vmin=("v", "min"), vmax=("v", "max"),
        ),
        ["g"],
    )
    for col in ("vs", "vmin", "vmax"):
        np.testing.assert_array_equal(
            merged[col].to_numpy(), expected[col].to_numpy()
        )
    np.testing.assert_allclose(
        merged["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
    )


def _worker_for(tmp_path, mem_store_url):
    from bqueryd_tpu.worker import WorkerNode

    return WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
    )


def _groupby_msg(filenames, aggs=None, where=None, payload="groupby"):
    from bqueryd_tpu.messages import CalcMessage

    msg = CalcMessage({"payload": payload, "token": "00"})
    msg.set_args_kwargs(
        [
            filenames, ["g"],
            aggs or [["v", "sum", "vs"], ["f", "mean", "fm"]],
            where or [],
        ],
        {},
    )
    return msg


def test_worker_delta_serves_after_append(tmp_path, mem_store_url):
    """The worker path end to end: fresh compute records the delta base; an
    append makes the repeat a delta refresh (effective_strategy 'delta'),
    bit-identical to a from-scratch recompute."""
    root = str(tmp_path / "t.bcolzs")
    df = _frame(1500, seed=13)
    ctable.fromdataframe(df, root, chunklen=256)
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        first = worker.handle_work(_groupby_msg(["t.bcolzs"]))
        assert first.get("effective_strategy") != "delta"
        extra = _frame(120, seed=14, offset=1500)
        ctable(root, mode="a").append_dataframe(extra)
        second = worker.handle_work(_groupby_msg(["t.bcolzs"]))
        assert second.get("effective_strategy") == "delta"
        assert worker.delta_refreshes_total.value == 1
        # parity vs recomputing with delta serving disabled
        os.environ["BQUERYD_TPU_DELTA_SERVE"] = "0"
        try:
            third = worker.handle_work(_groupby_msg(["t.bcolzs"]))
        finally:
            os.environ.pop("BQUERYD_TPU_DELTA_SERVE")
        from bqueryd_tpu.models.query import ResultPayload

        got = _sorted(
            _finalize([ResultPayload.from_bytes(second["data"])]), ["g"]
        )
        want = _sorted(
            _finalize([ResultPayload.from_bytes(third["data"])]), ["g"]
        )
        np.testing.assert_array_equal(
            got["vs"].to_numpy(), want["vs"].to_numpy()
        )
        np.testing.assert_allclose(
            got["fm"].to_numpy(), want["fm"].to_numpy(), rtol=1e-9
        )
    finally:
        worker.socket.close()


def test_worker_delta_ineligible_shapes_recompute(tmp_path, mem_store_url):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(800, seed=15), root, chunklen=128)
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        aggs = [["v", "count_distinct", "vd"]]
        worker.handle_work(_groupby_msg(["t.bcolzs"], aggs=aggs))
        ctable(root, mode="a").append_dataframe(
            _frame(50, seed=16, offset=800)
        )
        reply = worker.handle_work(_groupby_msg(["t.bcolzs"], aggs=aggs))
        assert reply.get("effective_strategy") != "delta"
        assert worker.delta_refreshes_total.value == 0
    finally:
        worker.socket.close()


def test_worker_chunk_prune_counters_and_span(tmp_path, mem_store_url):
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(1200, seed=17), root, chunklen=100)
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        msg = _groupby_msg(["t.bcolzs"], where=[["seq", ">", 1050]])
        reply = worker.handle_work(msg)
        assert worker.chunks_skipped_total.value >= 9
        assert worker.chunks_decoded_total.value >= 1
        spans = reply.get("spans") or []
        prune = [s for s in spans if s.get("name") == "prune"]
        assert prune and prune[0]["tags"]["chunks_skipped"] >= 9
    finally:
        worker.socket.close()


# ---------------------------------------------------------------------------
# cluster: rpc.append fan-out + serving behaviour
# ---------------------------------------------------------------------------

def _start(*nodes):
    threads = [
        threading.Thread(target=node.go, daemon=True) for node in nodes
    ]
    for t in threads:
        t.start()
    return threads


def _stop(nodes, threads):
    for node in nodes:
        node.running = False
    for t in threads:
        t.join(timeout=5)


@pytest.fixture
def ingest_cluster(tmp_path, mem_store_url):
    """Controller + one calc worker serving one chunked shard."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC

    df = _frame(3000, seed=18)
    ctable.fromdataframe(
        df, str(tmp_path / "t.bcolzs"), chunklen=256
    )
    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
    )
    worker = _worker_for(tmp_path, mem_store_url)
    worker.heartbeat_interval = 0.1
    worker.poll_timeout = 0.05
    threads = _start(controller, worker)
    wait_until(
        lambda: "t.bcolzs" in controller.files_map,
        desc="shard registration",
    )
    rpc = RPC(
        coordination_url=mem_store_url, timeout=30, loglevel=logging.WARNING
    )
    yield {
        "rpc": rpc, "controller": controller, "worker": worker,
        "df": df, "tmp_path": tmp_path,
    }
    _stop([controller, worker], threads)


def test_rpc_append_end_to_end(ingest_cluster):
    rpc = ingest_cluster["rpc"]
    controller = ingest_cluster["controller"]
    worker = ingest_cluster["worker"]
    df = ingest_cluster["df"]
    q = (
        ["t.bcolzs"], ["g"],
        [["v", "sum", "vs"], ["f", "mean", "fm"], ["v", "min", "vmin"]],
        [],
    )
    r1 = rpc.groupby(*q)
    extra = _frame(240, seed=19, offset=3000)
    res = rpc.append("t.bcolzs", extra)
    assert res["appended"] == 240
    assert len(res["holders"]) == 1
    assert controller.counters["append_requests"] == 1
    assert controller.counters["append_dispatches"] == 1
    # the repeat query reflects the appended rows via a delta refresh
    r2 = rpc.groupby(*q)
    assert rpc.last_call_strategies["effective"]["t.bcolzs"] == "delta"
    assert worker.delta_refreshes_total.value == 1
    full = pd.concat([df, extra], ignore_index=True)
    expected = _sorted(
        full.groupby("g", as_index=False).agg(
            vs=("v", "sum"), fm=("f", "mean"), vmin=("v", "min")
        ),
        ["g"],
    )
    got = _sorted(r2, ["g"])
    np.testing.assert_array_equal(
        got["vs"].to_numpy(), expected["vs"].to_numpy()
    )
    np.testing.assert_allclose(
        got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
    )
    assert len(r1) == len(r2)
    # fresh stats re-advertise with the grown row count
    wait_until(
        lambda: (controller.shard_stats.get("t.bcolzs") or {}).get("rows")
        == 3240,
        desc="post-append stats re-advertisement",
    )


def test_rpc_append_unknown_file(ingest_cluster):
    from bqueryd_tpu.rpc import RPCError

    with pytest.raises(RPCError, match="not served by any worker"):
        ingest_cluster["rpc"].append("nope.bcolzs", _frame(5))


def test_rpc_append_disabled_worker(ingest_cluster, monkeypatch):
    from bqueryd_tpu.rpc import RPCError

    monkeypatch.setenv("BQUERYD_TPU_APPEND", "0")
    with pytest.raises(RPCError, match="streaming append disabled"):
        ingest_cluster["rpc"].append("t.bcolzs", _frame(5))


def test_rpc_append_mixed_version_rejected(ingest_cluster, monkeypatch):
    """A pre-PR-14 worker rejects the verb with its base unhandled-payload
    traceback; the controller rewrites it into the structured
    UnsupportedVerb error."""
    from bqueryd_tpu.rpc import RPCError
    from bqueryd_tpu.worker import WorkerNode

    def legacy(self, msg):
        raise ValueError(
            f"unhandled message payload {msg.get('payload')!r}"
        )

    monkeypatch.setattr(WorkerNode, "_append_rows", legacy)
    with pytest.raises(RPCError, match="UnsupportedVerb"):
        ingest_cluster["rpc"].append("t.bcolzs", _frame(5))


def test_rpc_append_dedupes_shared_datadir(tmp_path, mem_store_url):
    """Two workers serving the SAME (node, data_dir) are one physical
    replica: the append applies once, not twice."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC

    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(_frame(500, seed=21), root, chunklen=100)
    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
    )
    w1 = _worker_for(tmp_path, mem_store_url)
    w2 = _worker_for(tmp_path, mem_store_url)
    for w in (w1, w2):
        w.heartbeat_interval = 0.1
        w.poll_timeout = 0.05
    threads = _start(controller, w1, w2)
    try:
        wait_until(
            lambda: len(controller.files_map.get("t.bcolzs") or ()) == 2,
            desc="both workers advertising",
        )
        rpc = RPC(
            coordination_url=mem_store_url, timeout=30,
            loglevel=logging.WARNING,
        )
        res = rpc.append("t.bcolzs", _frame(50, seed=22, offset=500))
        assert len(res["holders"]) == 1, "shared data_dir = one append"
        assert ctable(root).nrows == 550
    finally:
        _stop([controller, w1, w2], threads)


def test_dag_query_chunk_prune_parity(ingest_cluster):
    """Satellite: rpc.query pushdown predicates ride the same chunk mask;
    results match the unpruned path exactly."""
    rpc = ingest_cluster["rpc"]
    worker = ingest_cluster["worker"]
    spec = {
        "table": ["t.bcolzs"],
        "groupby": ["g"],
        "aggs": [["v", "sum", "vs"], ["v", "topk", "top2", {"k": 2}]],
        "where": [["seq", ">", 2700]],
    }
    before = worker.chunks_skipped_total.value
    pruned = rpc.query(spec)
    assert worker.chunks_skipped_total.value > before
    os.environ["BQUERYD_TPU_CHUNK_PRUNE"] = "0"
    try:
        full = rpc.query(spec)
    finally:
        os.environ.pop("BQUERYD_TPU_CHUNK_PRUNE")
    a = _sorted(pruned, ["g"])
    b = _sorted(full, ["g"])
    np.testing.assert_array_equal(
        a["vs"].to_numpy(), b["vs"].to_numpy()
    )
    for x, y in zip(a["top2"], b["top2"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
