"""Device-resident distributed merge over the ICI mesh (ISSUE 7).

Covers the span partitioner and bucketized partial emission, parity of the
span-owned collective merge against the ``BQUERYD_TPU_DEVICE_MERGE=0``
hostmerge fallback across the fuzz-shaped dtype mix (limb-straddling int64,
narrow-wire min/max, float32 mean, float64 sum), the kill switch actually
routing through ``hostmerge.merge_payloads``, the D2H byte accounting, the
``merge_mode`` reply/envelope key end to end through a real cluster, and
the per-leaf (unpacked) fetch variant.
"""

import logging
import os
import threading

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.parallel import devicemerge, hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh
from bqueryd_tpu.storage.ctable import ctable

N_SHARDS = 3


# -- span partitioner + bucketized emission ----------------------------------

def test_bucket_span_math():
    assert devicemerge.bucket_span(24, 8) == (3, 24)
    assert devicemerge.bucket_span(9, 8) == (2, 16)
    assert devicemerge.bucket_span(1, 8) == (1, 8)
    assert devicemerge.bucket_span(0, 8) == (1, 8)   # empty table: 1 slot
    assert devicemerge.bucket_span(7, 1) == (7, 7)
    # every group lands in exactly one device's contiguous span
    for n_groups, n_dev in ((9, 8), (70_225, 8), (5, 3)):
        span, padded = devicemerge.bucket_span(n_groups, n_dev)
        assert padded >= n_groups
        assert span * n_dev == padded
        owners = [g // span for g in range(n_groups)]
        assert max(owners) < n_dev


def test_bucketize_partials_pads_past_real_groups():
    from bqueryd_tpu import ops

    codes = np.array([0, 1, 2, 2, 4, 1], dtype=np.int32)
    vals = np.array([10, -3, 7, 1, 2, 5], dtype=np.int64)
    n_groups = 5
    padded, span = ops.bucketize_partials(
        ops.partial_tables(codes, (vals,), ("sum",), n_groups), n_groups, 8
    )
    assert span == 1
    rows = np.asarray(padded["rows"])
    assert rows.shape == (8,)
    np.testing.assert_array_equal(rows[:5], [1, 2, 2, 0, 1])
    np.testing.assert_array_equal(rows[5:], 0)  # pad tail: no real group
    sums = np.asarray(padded["aggs"][0]["sum"])
    np.testing.assert_array_equal(sums[:5], [10, 2, 8, 0, 2])
    np.testing.assert_array_equal(sums[5:], 0)


def test_partial_tables_bucketized_matches_flat_emission():
    from bqueryd_tpu import ops

    rng = np.random.default_rng(5)
    codes = rng.integers(-1, 11, 4_000).astype(np.int32)
    vals = rng.integers(-(2**60), 2**60, 4_000).astype(np.int64)
    flat = ops.partial_tables(codes, (vals,), ("sum",), 11)
    bucketized, span = ops.partial_tables_bucketized(
        codes, (vals,), ("sum",), 11, 8
    )
    assert span == 2
    np.testing.assert_array_equal(
        np.asarray(bucketized["aggs"][0]["sum"])[:11],
        np.asarray(flat["aggs"][0]["sum"]),
    )


# -- device merge vs host fallback parity ------------------------------------

@pytest.fixture(scope="module")
def merge_shards(tmp_path_factory):
    """Fuzz-shaped dtype mix: limb-straddling int64 sums, narrow-wire
    (int8) min/max, float32 NaN means, float64 sums, string keys."""
    rng = np.random.default_rng(17)
    n = 9_000
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 23, n).astype(np.int64),
            "k_str": rng.choice(["a", "b", "c", None], n,
                                p=[0.4, 0.3, 0.2, 0.1]),
            "big": rng.integers(-(2**60), 2**60, n).astype(np.int64),
            "small": rng.integers(-100, 100, n).astype(np.int64),
            "f32": np.where(
                rng.random(n) < 0.05, np.nan, rng.random(n) * 100
            ).astype(np.float32),
            "f64": rng.random(n).astype(np.float64),
            "sel": rng.random(n).astype(np.float64),
        }
    )
    base = tmp_path_factory.mktemp("devmerge")
    tables = []
    for i in range(N_SHARDS):
        root = str(base / f"dm{i}.bcolzs")
        ctable.fromdataframe(df.iloc[i::N_SHARDS].reset_index(drop=True), root)
        tables.append(ctable(root))
    return df, tables


MERGE_CASES = [
    (["g"], [["big", "sum", "s"]], []),
    (["g"], [["small", "min", "lo"], ["small", "max", "hi"],
             ["big", "count", "n"]], []),
    (["g"], [["f32", "mean", "m32"], ["f64", "sum", "s64"]], []),
    (["k_str"], [["big", "sum", "s"], ["f32", "mean", "m"]], []),
    (["g"], [["big", "sum", "s"]], [["sel", ">", 0.5]]),
]


def _run_mode(tables, query, enabled, monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1" if enabled else "0")
    ex = MeshQueryExecutor(mesh=make_mesh())
    payload = ex.execute(tables, query)
    assert ex.last_merge_mode == ("device" if enabled else "host")
    df = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    )
    return df.sort_values(query.groupby_cols).reset_index(drop=True)


def _assert_mode_parity(dev, host, query):
    assert list(dev.columns) == list(host.columns)
    assert len(dev) == len(host)
    for col in dev.columns:
        a, b = dev[col].to_numpy(), host[col].to_numpy()
        if np.asarray(a).dtype.kind in "iub" or col in query.groupby_cols:
            # integer aggregates (the north-star axis) and keys: bit-exact
            np.testing.assert_array_equal(a, b)
        else:
            # float sums may reassociate between the device merge and the
            # host merge's sequential fold: equal to reassociation ulps
            np.testing.assert_allclose(
                np.asarray(a, dtype=np.float64),
                np.asarray(b, dtype=np.float64), rtol=1e-9, equal_nan=True,
            )


@pytest.mark.parametrize("case", range(len(MERGE_CASES)))
def test_device_merge_matches_host_fallback(merge_shards, monkeypatch, case):
    """The span-owned collective merge and the BQUERYD_TPU_DEVICE_MERGE=0
    hostmerge fallback must agree: bit-identical integers, reassociation
    ulps on floats — and both must match pandas."""
    df, tables = merge_shards
    gcols, aggs, where = MERGE_CASES[case]
    query = GroupByQuery(gcols, aggs, where, aggregate=True)
    dev = _run_mode(tables, query, True, monkeypatch)
    host = _run_mode(tables, query, False, monkeypatch)
    _assert_mode_parity(dev, host, query)

    sel = df
    for col, op, val in where:
        assert op == ">"
        sel = sel[sel[col] > val]
    g = sel.groupby(gcols[0], dropna=True)
    in_col, op, out_col = aggs[0]
    expect = getattr(g[in_col], {"sum": "sum", "min": "min", "max": "max",
                                 "mean": "mean", "count": "count"}[op])()
    got = dev.set_index(gcols[0])[out_col]
    if expect.dtype.kind in "iu" and op != "mean":
        np.testing.assert_array_equal(
            got.to_numpy(), expect.loc[got.index].to_numpy()
        )
    else:
        np.testing.assert_allclose(
            got.to_numpy(dtype=np.float64),
            expect.loc[got.index].to_numpy(dtype=np.float64),
            rtol=1e-5, equal_nan=True,
        )


def test_kill_switch_routes_through_hostmerge(merge_shards, monkeypatch):
    """=0 must actually call hostmerge.merge_payloads (per-device payloads);
    =1 must not touch it inside the executor."""
    _df, tables = merge_shards
    query = GroupByQuery(["g"], [["big", "sum", "s"]])
    calls = []
    real = hostmerge.merge_payloads

    def spy(payloads):
        calls.append(len(payloads))
        return real(payloads)

    monkeypatch.setattr(hostmerge, "merge_payloads", spy)

    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1")
    MeshQueryExecutor(mesh=make_mesh()).execute(tables, query)
    assert calls == [], "device merge must not host-merge anything"

    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "0")
    MeshQueryExecutor(mesh=make_mesh()).execute(tables, query)
    assert calls and calls[0] == 8, (
        "kill switch must merge one payload per mesh device via hostmerge"
    )


def test_device_merge_byte_accounting(merge_shards, monkeypatch):
    """Device mode fetches a fraction of the host-gather bytes and records
    the saving; host mode fetches every device's full table."""
    _df, tables = merge_shards
    query = GroupByQuery(["g"], [["big", "sum", "s"]])
    stats = devicemerge.stats()

    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1")
    before = stats.snapshot()
    MeshQueryExecutor(mesh=make_mesh()).execute(tables, query)
    mid = stats.snapshot()
    dev_fetched = (
        mid["bytes_fetched"]["device"] - before["bytes_fetched"]["device"]
    )
    dev_saved = mid["d2h_bytes_saved"] - before["d2h_bytes_saved"]
    assert mid["queries"]["device"] == before["queries"]["device"] + 1
    assert dev_fetched > 0
    assert dev_saved > 0, "an 8-device span merge must save per-device bytes"

    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "0")
    MeshQueryExecutor(mesh=make_mesh()).execute(tables, query)
    after = stats.snapshot()
    host_fetched = (
        after["bytes_fetched"]["host"] - mid["bytes_fetched"]["host"]
    )
    assert after["queries"]["host"] == mid["queries"]["host"] + 1
    # host-gather moves every device's table: ~n_dev x the span fetch
    assert host_fetched > 4 * dev_fetched


def test_device_merge_per_leaf_fetch(merge_shards, monkeypatch):
    """BQUERYD_TPU_PACKED_FETCH=0 (per-leaf device_get) under device merge
    must produce the identical table."""
    _df, tables = merge_shards
    query = GroupByQuery(
        ["g"], [["big", "sum", "s"], ["small", "min", "lo"]]
    )
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1")
    packed = _run_mode(tables, query, True, monkeypatch)
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "0")
    unpacked = _run_mode(tables, query, True, monkeypatch)
    for col in packed.columns:
        np.testing.assert_array_equal(
            packed[col].to_numpy(), unpacked[col].to_numpy()
        )


def test_packed_fetch_f64_as_f32_pair(merge_shards, monkeypatch):
    """On a TPU a float64 leaf has no bytes to cast (the compiler refuses
    ``bitcast-convert`` on f64), so it crosses the packed fetch as its
    float32 (hi, lo) split.  Forced on here: the packed table must agree
    with the per-leaf fetch to the pair's precision — float64 sums, a
    float mean, and the +-inf extrema fills of groups a filter emptied."""
    from bqueryd_tpu.parallel import executor as ex_mod

    _df, tables = merge_shards
    query = GroupByQuery(
        ["g"],
        [["f64", "sum", "s"], ["f64", "min", "lo"], ["f64", "max", "hi"],
         ["f32", "mean", "m"], ["big", "sum", "b"]],
        [["sel", ">", 0.97]],
    )
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "0")
    per_leaf = _run_mode(tables, query, True, monkeypatch)
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    monkeypatch.setattr(ex_mod, "_f64_rides_as_f32_pair", lambda: True)
    # the encoding is traced into the program: no stale bitcast variant
    ex_mod._mesh_program.cache_clear()
    try:
        packed = _run_mode(tables, query, True, monkeypatch)
    finally:
        ex_mod._mesh_program.cache_clear()
    assert len(packed) == len(per_leaf) > 0
    np.testing.assert_array_equal(
        packed["b"].to_numpy(), per_leaf["b"].to_numpy()
    )
    for col in ("s", "lo", "hi", "m"):
        np.testing.assert_allclose(
            packed[col].to_numpy(), per_leaf[col].to_numpy(),
            rtol=2.0**-45, equal_nan=True,
        )


def test_resolve_mode_contract(monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_DEVICE_MERGE", raising=False)
    assert devicemerge.resolve_mode() == devicemerge.MODE_DEVICE
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "0")
    assert devicemerge.resolve_mode() == devicemerge.MODE_HOST
    # multi-host pods pin the replicated-psum contract regardless
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1")
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert devicemerge.resolve_mode() == devicemerge.MODE_PSUM


def test_merge_stats_thread_safety():
    stats = devicemerge.MergeStats()

    def pound():
        for _ in range(500):
            stats.record("device", 100, saved=700)
            stats.record("host", 800)

    threads = [threading.Thread(target=pound) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = stats.snapshot()
    assert snap["bytes_fetched"]["device"] == 4 * 500 * 100
    assert snap["bytes_fetched"]["host"] == 4 * 500 * 800
    assert snap["d2h_bytes_saved"] == 4 * 500 * 700
    stats.reset()
    assert stats.snapshot()["queries"] == {"device": 0, "host": 0}


# -- merge_mode on the wire, end to end --------------------------------------

@pytest.fixture(scope="module")
def merge_cluster(tmp_path_factory):
    """Controller + one calc worker over real zmq (the reference's own test
    topology), with a sharded table set."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    from tests.conftest import wait_until

    rng = np.random.default_rng(23)
    n = 6_000
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 9, n).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
        }
    )
    root = tmp_path_factory.mktemp("devmerge_cluster")
    names = []
    for i in range(4):
        name = f"dm-{i}.bcolzs"
        ctable.fromdataframe(df.iloc[i::4].reset_index(drop=True),
                             str(root / name))
        names.append(name)

    url = f"mem://devmerge-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(root), heartbeat_interval=0.2,
    )
    worker = WorkerNode(
        coordination_url=url, data_dir=str(root), loglevel=logging.WARNING,
        restart_check=False, heartbeat_interval=0.2, poll_timeout=0.1,
    )
    threads = [
        threading.Thread(target=node.go, daemon=True)
        for node in (controller, worker)
    ]
    for t in threads:
        t.start()
    wait_until(
        lambda: len(controller.files_map) >= len(names),
        desc="worker shard registration",
    )
    rpc = RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
    yield df, names, rpc, controller, worker
    for node in (controller, worker):
        node.running = False
    for t in threads:
        t.join(timeout=5)


def test_merge_mode_rides_the_wire(merge_cluster, monkeypatch):
    """A batched groupby reports merge_mode=device per shard group; the
    kill switch flips every (now per-shard) reply to host/none, results
    stay identical, and the controller counts reply payload bytes."""
    df, names, rpc, controller, worker = merge_cluster
    monkeypatch.delenv("BQUERYD_TPU_DEVICE_MERGE", raising=False)
    expect = (
        df.groupby("g", as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
    )

    got_dev = rpc.groupby(names, ["g"], [["v", "sum", "s"]], [])
    modes = rpc.last_call_merge_modes
    assert modes and all(m == "device" for m in modes.values()), modes
    got_dev = got_dev.sort_values("g").reset_index(drop=True)
    np.testing.assert_array_equal(
        got_dev["s"].to_numpy(), expect["s"].to_numpy()
    )

    bytes_before = controller.counters["reply_payload_bytes"]
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "0")
    got_host = rpc.groupby(names, ["g"], [["v", "sum", "s"]], [])
    host_bytes = controller.counters["reply_payload_bytes"] - bytes_before
    modes = rpc.last_call_merge_modes
    # the kill switch un-batches: one reply per shard, merged host-side
    assert modes and len(modes) == len(names), modes
    assert all(m in ("host", "none") for m in modes.values()), modes
    got_host = got_host.sort_values("g").reset_index(drop=True)
    np.testing.assert_array_equal(
        got_host["s"].to_numpy(), expect["s"].to_numpy()
    )
    assert host_bytes > 0
    # the worker-side histogram twin observed the same replies
    snap = worker.metrics.histogram_snapshot()["bqueryd_tpu_reply_bytes"]
    assert sum(sum(e["counts"]) for e in snap) >= len(names)
