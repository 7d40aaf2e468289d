"""Wire narrowing, exact int64 limb sums, and executor cache behavior."""

import logging

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import GroupByQuery, freeze_value as _freeze
from bqueryd_tpu.parallel.executor import (
    MeshQueryExecutor,
    _codes_dtype,
    _where_signature,
    _wire_dtype,
    make_mesh,
)
from bqueryd_tpu.storage.ctable import ctable


@pytest.fixture
def shard_tables(tmp_path):
    rng = np.random.RandomState(9)
    frames, tables = [], []
    for i in range(3):
        df = pd.DataFrame(
            {
                "g": rng.randint(0, 6, 500).astype(np.int64),
                "v": rng.randint(-30000, 30000, 500).astype(np.int64),
                "big": rng.randint(-(2**62), 2**62, 500).astype(np.int64),
                "f": rng.random(500).astype(np.float32),
            }
        )
        root = str(tmp_path / f"s{i}.bcolzs")
        ctable.fromdataframe(df, root)
        frames.append(df)
        tables.append(ctable(root))
    return frames, tables


@pytest.mark.parametrize("path", ["mxu_matmul", "scatter"])
def test_int64_sum_bit_exact_full_range(path, monkeypatch):
    """Exact int64 sums across the full value range on BOTH kernel paths:
    the 8-bit-limb MXU matmul (default) and the 16-bit-limb blocked scatter
    (high-cardinality fallback, forced via BQUERYD_TPU_MATMUL_GROUPS=0)."""
    import jax

    from bqueryd_tpu import ops

    if path == "scatter":
        monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", "0")
    rng = np.random.RandomState(0)
    for dtype in (np.int8, np.int16, np.int32, np.int64):
        info = np.iinfo(dtype)
        vals = rng.randint(
            info.min, info.max, 5000, dtype=np.int64
        ).astype(dtype)
        codes = rng.randint(0, 7, 5000).astype(np.int32)
        out = jax.device_get(
            ops.partial_tables(codes, (vals,), ("sum",), 7)
        )["aggs"][0]["sum"]
        expect = np.zeros(7, dtype=np.int64)
        np.add.at(expect, codes, vals.astype(np.int64))
        np.testing.assert_array_equal(np.asarray(out), expect)


@pytest.mark.parametrize("op", ["sum", "mean", "count", "count_na", "min", "max"])
def test_mm_and_scatter_paths_agree(op, monkeypatch):
    """The MXU and scatter kernels must be interchangeable: identical results
    for every mergeable op, with nulls, masks and negative (dropped) codes."""
    import jax

    from bqueryd_tpu import ops

    rng = np.random.RandomState(5)
    n, g = 20_000, 23
    codes = rng.randint(-1, g, n).astype(np.int32)
    mask = rng.random(n) < 0.8
    vals = (rng.random(n) * 1000 - 500).astype(np.float32)
    vals[rng.random(n) < 0.05] = np.nan

    def run():
        return jax.device_get(
            ops.partial_tables(codes, (vals,), (op,), g, mask=mask)
        )

    mm = run()
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", "0")
    scatter = run()
    np.testing.assert_array_equal(mm["rows"], scatter["rows"])
    # float32 sums cancel heavily here (values in ±500, group sums ~1e2), so
    # compare with an absolute floor scaled to the summed magnitude instead of
    # pure rtol: both kernels carry ~1e-7 relative accumulation noise.
    atol = 1e-6 * float(np.nansum(np.abs(vals)))
    for key in scatter["aggs"][0]:
        np.testing.assert_allclose(
            mm["aggs"][0][key], scatter["aggs"][0][key], rtol=1e-4, atol=atol,
            err_msg=f"op={op} partial={key}",
        )


@pytest.mark.parametrize("op,g", [
    ("sum", 150),        # small-G: 2048 tile
    ("sum", 1300),       # pads to 1408 lanes: non-pow2 G, 1024 tile, two
                         # blocks — the shapes that once truncated the block
                         # loop when tiles weren't forced to divide BLOCK_K
    ("mean", 150),
    ("count_na", 150),
])
def test_pallas_kernel_matches_xla_path(op, g, monkeypatch):
    """BQUERYD_TPU_PALLAS=1 routes the one-hot contraction through the Pallas
    kernel (interpreted off-TPU); results must be bit-identical to the XLA
    path, which shares the limb plan and differs only in who forms the
    one-hot.  The flag is a static jit arg read per call in the un-jitted
    dispatcher, so the two runs trace distinct executables."""
    import jax

    from bqueryd_tpu import ops
    from bqueryd_tpu.ops import pallas_groupby

    if g == 1300:  # regression guard: this landing must use a dividing tile
        assert pallas_groupby.BLOCK_K % pallas_groupby._tile_k(1408) == 0

    rng = np.random.RandomState(9)
    n = 40_000  # pads to two 32768 blocks
    codes = rng.randint(-1, g, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    ivals = rng.randint(-(2**40), 2**40, n).astype(np.int64)
    fvals = (rng.random(n) * 100).astype(np.float32)
    fvals[rng.random(n) < 0.03] = np.nan
    vals = fvals if op == "count_na" else ivals

    def run():
        return jax.device_get(
            ops.partial_tables(codes, (vals,), (op,), g, mask=mask)
        )

    monkeypatch.delenv("BQUERYD_TPU_PALLAS", raising=False)
    xla = run()
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    pallas = run()
    np.testing.assert_array_equal(xla["rows"], pallas["rows"])
    for key in xla["aggs"][0]:
        np.testing.assert_array_equal(
            xla["aggs"][0][key], pallas["aggs"][0][key],
            err_msg=f"op={op} partial={key}",
        )


def test_pallas_high_cardinality_tile_shrinks(monkeypatch):
    """Towards the route's group ceiling the one-hot tile must shrink to
    _MIN_TILE instead of overflowing the VMEM budget — and no further:
    Mosaic refuses a 1-D codes load off the 1024-element tile grid (seen on
    the v5e), so the floor is that tile and the ceiling follows from it."""
    import jax

    from bqueryd_tpu import ops
    from bqueryd_tpu.ops import pallas_groupby as pg

    g = 1_700  # pads to 1792 lanes: the budget no longer fits a 2048 tile
    assert g <= pg.pallas_groups_limit()
    tile = pg._tile_k(-(-g // 128) * 128)
    assert tile == pg._MIN_TILE == pg._CODES_TILE
    assert tile * g <= pg._ONEHOT_BUDGET
    assert pg.BLOCK_K % tile == 0
    assert pg._hicard_kt() % pg._CODES_TILE == 0

    rng = np.random.RandomState(3)
    n = pg.BLOCK_K  # one grid block keeps interpret mode fast
    codes = rng.randint(-1, g, n).astype(np.int32)
    vals = rng.randint(-(2**40), 2**40, n).astype(np.int64)

    def run():
        return jax.device_get(ops.partial_tables(codes, (vals,), ("sum",), g))

    monkeypatch.delenv("BQUERYD_TPU_PALLAS", raising=False)
    xla = run()
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    pallas = run()
    np.testing.assert_array_equal(xla["rows"], pallas["rows"])
    np.testing.assert_array_equal(
        xla["aggs"][0]["sum"], pallas["aggs"][0]["sum"]
    )


def test_pallas_route_capped_at_groups_limit(monkeypatch):
    """Past pallas_groups_limit() the dispatcher must keep the XLA dot even
    with BQUERYD_TPU_PALLAS=1 (no VMEM-overflowing kernel launch)."""
    from bqueryd_tpu.ops import groupby as gbm
    from bqueryd_tpu.ops import pallas_groupby as pg

    g = pg.pallas_groups_limit() + 1
    seen = {}
    real = gbm._partial_tables_mm

    def spy(codes, measures, ops_, n_groups, mask=None, use_pallas=False,
            **kw):
        seen["use_pallas"] = use_pallas
        return real(codes, measures, ops_, n_groups, mask,
                    use_pallas=use_pallas, **kw)

    monkeypatch.setattr(gbm, "_partial_tables_mm", spy)
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", str(g))
    codes = np.arange(64, dtype=np.int32) % g
    vals = np.ones(64, dtype=np.int64)
    gbm.partial_tables(codes, (vals,), ("sum",), g)
    assert seen["use_pallas"] is False


def _worker_for(tmp_path, mem_store_url):
    from bqueryd_tpu.worker import WorkerNode

    return WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
    )


def _calc_msg(filenames):
    from bqueryd_tpu.messages import CalcMessage

    msg = CalcMessage({"payload": "groupby", "token": "00"})
    msg.set_args_kwargs(
        [filenames, ["g"], [["v", "sum", "v"]], []], {}
    )
    return msg


def test_result_cache_hit_and_activation_invalidation(
    tmp_path, mem_store_url, monkeypatch
):
    """A repeated identical query is served from the worker's result cache
    (no engine execution); rewriting the shard (two-phase activation bumps
    meta.json's mtime) invalidates the entry."""
    df = pd.DataFrame({"g": np.arange(20) % 3, "v": np.arange(20)})
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"))
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        calls = []
        real_execute = worker._execute
        monkeypatch.setattr(
            worker, "_execute",
            lambda *a, **kw: calls.append(1) or real_execute(*a, **kw),
        )
        first = worker.handle_work(_calc_msg(["t.bcolzs"]))
        second = worker.handle_work(_calc_msg(["t.bcolzs"]))
        assert calls == [1], "second query must be served from cache"
        assert first["data"] == second["data"]

        # activation rewrites the table: meta.json is written atomically
        # (temp + rename), so the table identity changes via the fresh inode
        # even within filesystem timestamp granularity — no mtime bump needed
        df2 = pd.DataFrame({"g": np.arange(20) % 3, "v": np.arange(20) * 10})
        import shutil

        shutil.rmtree(str(tmp_path / "t.bcolzs"))
        ctable.fromdataframe(df2, str(tmp_path / "t.bcolzs"))
        third = worker.handle_work(_calc_msg(["t.bcolzs"]))
        assert calls == [1, 1], "rewritten shard must recompute"
        assert third["data"] != first["data"]
    finally:
        worker.socket.close()


def test_result_cache_disabled_by_env(tmp_path, mem_store_url, monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_RESULT_CACHE_BYTES", "0")
    df = pd.DataFrame({"g": np.arange(6) % 2, "v": np.arange(6)})
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"))
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        calls = []
        real_execute = worker._execute
        monkeypatch.setattr(
            worker, "_execute",
            lambda *a, **kw: calls.append(1) or real_execute(*a, **kw),
        )
        worker.handle_work(_calc_msg(["t.bcolzs"]))
        worker.handle_work(_calc_msg(["t.bcolzs"]))
        assert calls == [1, 1], "cache disabled: every query executes"
    finally:
        worker.socket.close()


@pytest.mark.parametrize(
    "older_keys",
    [
        pytest.param({"strategy": "scatter"}, id="strategy=scatter"),
        pytest.param({"strategy": "sort"}, id="strategy=sort"),
        pytest.param({"strategy": "matmul", "strategy_binding": True},
                     id="strategy=matmul+binding"),
        pytest.param({"strategy": "host"}, id="strategy=host"),
        pytest.param(None, id="wrm-calibration"),
    ],
)
def test_older_peers_keys_are_ignored(
    tmp_path, mem_store_url, monkeypatch, older_keys
):
    """Mixed versions (MIGRATION.md, PR 32).  A fragment from an older
    controller may still carry a route: the worker ignores it, runs what it
    runs for every query, reports that route and echoes no hint.  A WRM from
    an older worker may still carry its measured-cost cells: the controller
    registers the worker, keeps no model of them and dispatches as ever."""
    from bqueryd_tpu.plan import fragment_for, plan_groupby

    monkeypatch.setenv("BQUERYD_TPU_RESULT_CACHE_BYTES", "0")
    rng = np.random.RandomState(3)
    df = pd.DataFrame({
        "g": rng.randint(0, 5, 3000).astype(np.int64),
        "v": rng.randint(-9, 9, 3000).astype(np.int64),
    })
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"))
    plan = plan_groupby(["t.bcolzs"], ["g"], [["v", "sum", "v"]], [])
    fragment = fragment_for(plan, ["t.bcolzs"], sole=True)

    if older_keys is None:
        from bqueryd_tpu.controller import ControllerNode
        from bqueryd_tpu.messages import RPCMessage, WorkerRegisterMessage

        node = ControllerNode(
            coordination_url=mem_store_url, loglevel=logging.WARNING,
            runfile_dir=str(tmp_path),
        )
        try:
            node.handle_worker(b"w-old", WorkerRegisterMessage({
                "worker_id": "w-old", "workertype": "calc",
                "data_files": ["t.bcolzs"],
                "calibration": {"v": 1, "cells": {
                    "r24|g4|int|tpu|sort": {"n": 9, "wall_s": 1e-9}}},
            }))
            assert "t.bcolzs" in node.files_map
            assert not hasattr(node, "calibration")
            msg = RPCMessage({"payload": "groupby", "token": "00"})
            msg.set_args_kwargs(
                [["t.bcolzs"], ["g"], [["v", "sum", "v"]], []], {})
            node.rpc_groupby(msg)
            (shard,) = [
                m for q in node.worker_out_messages.values() for m in q]
            assert shard.get_from_binary("plan") == fragment
        finally:
            node.socket.close()
        return

    def calc(frag):
        msg = _calc_msg(["t.bcolzs"])
        msg.add_as_binary("plan", frag)
        return worker.handle_work(msg)

    worker = _worker_for(tmp_path, mem_store_url)
    try:
        plain = calc(fragment)
        older = calc({**fragment, **older_keys})
        assert older["data"] == plain["data"]
        assert older["effective_strategy"] == plain["effective_strategy"]
        assert plain["effective_strategy"] == "matmul"  # FORCE_MATMUL=1
        assert "strategy" not in older
        assert "calibration" not in worker.prepare_wrm()
    finally:
        worker.socket.close()


def test_wire_dtype_narrows_by_stats(shard_tables):
    _, tables = shard_tables
    assert _wire_dtype(tables, "v") == np.dtype(np.int16)
    assert _wire_dtype(tables, "big") is None  # full-range int64 can't narrow
    assert _wire_dtype(tables, "f") is None    # floats ship as stored
    assert _codes_dtype(6) == np.dtype(np.int8)
    assert _codes_dtype(1000) == np.dtype(np.int16)
    assert _codes_dtype(100_000) == np.dtype(np.int32)


def test_narrowed_query_matches_pandas(shard_tables):
    frames, tables = shard_tables
    q = GroupByQuery(
        ["g"],
        [["v", "sum", "vs"], ["v", "min", "vmin"], ["big", "sum", "bs"],
         ["f", "mean", "fm"]],
    )
    ex = MeshQueryExecutor(mesh=make_mesh())
    r = ex.execute(tables, q)
    full = pd.concat(frames, ignore_index=True)
    expect = full.groupby("g").agg(
        vs=("v", "sum"), vmin=("v", "min"), bs=("big", "sum"),
        fm=("f", "mean"),
    )
    order = np.argsort(r["keys"]["g"])
    np.testing.assert_array_equal(
        r["aggs"][0]["sum"][order], expect["vs"].to_numpy()
    )
    got_min = r["aggs"][1]["min"][order]
    assert got_min.dtype == np.int64  # restored to the stored dtype
    np.testing.assert_array_equal(got_min, expect["vmin"].to_numpy())
    # int64 sums wrap mod 2^64 exactly like numpy; compare against numpy
    np.testing.assert_array_equal(
        r["aggs"][2]["sum"][order], expect["bs"].to_numpy()
    )
    np.testing.assert_allclose(
        r["aggs"][3]["sum"][order] / r["aggs"][3]["count"][order],
        expect["fm"].to_numpy(),
        rtol=1e-6,
    )


def test_set_and_array_where_terms_cacheable(shard_tables):
    frames, tables = shard_tables
    ex = MeshQueryExecutor(mesh=make_mesh())
    q = GroupByQuery(
        ["g"], [["v", "sum", "vs"]], where_terms=[["g", "in", {1, 2}]]
    )
    r = ex.execute(tables, q)  # must not crash on the set-valued term
    full = pd.concat(frames, ignore_index=True)
    expect = full[full["g"].isin([1, 2])].groupby("g")["v"].sum()
    order = np.argsort(r["keys"]["g"])
    np.testing.assert_array_equal(
        r["aggs"][0]["sum"][order], expect.to_numpy()
    )
    # distinct arrays with identical truncated reprs must not collide
    a = np.arange(2000)
    b = a.copy()
    b[1000] = -1
    sig_a = _freeze(a)
    sig_b = _freeze(b)
    assert sig_a != sig_b
    assert _freeze({1, 2}) == _freeze({2, 1})


def test_repeat_query_hits_caches(shard_tables):
    frames, tables = shard_tables
    ex = MeshQueryExecutor(mesh=make_mesh())
    q = GroupByQuery(["g"], [["v", "sum", "vs"]])
    ex.execute(tables, q)
    assert len(ex._codes_cache) == 1  # folded group codes
    assert len(ex._hbm_cache) == 1    # one measure block
    assert len(ex._align_cache) == 1
    before = (len(ex._codes_cache), len(ex._hbm_cache))
    ex.execute(tables, q)
    # no new blocks on repeat
    assert (len(ex._codes_cache), len(ex._hbm_cache)) == before
    ex.clear_caches()
    assert len(ex._hbm_cache) == 0 and ex._hbm_cache.nbytes == 0
    assert len(ex._codes_cache) == 0 and len(ex._align_cache) == 0


def test_where_signature_distinguishes_filters():
    q1 = GroupByQuery(["g"], [["v", "sum", "v"]], where_terms=[["v", ">", 1]])
    q2 = GroupByQuery(["g"], [["v", "sum", "v"]], where_terms=[["v", ">", 2]])
    assert _where_signature(q1) != _where_signature(q2)
