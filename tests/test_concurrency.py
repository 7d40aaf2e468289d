"""Shared-scan multi-query fusion (PR 9): the admission micro-batch window,
plan compatibility signatures, bundle execution parity, and per-member fault
isolation.

The executor-level tests prove the stacked-mask shared scan is bit-identical
to solo execution (the kernels fold a mask exactly like pre-folded codes);
the cluster tests prove the window end to end: distinct-but-compatible
concurrent queries fuse into one dispatch, every member keeps its own reply
identity, and a member's deadline expiry / quota rejection / shape error
never disturbs its bundle-mates.  Window 0 (the default) stages nothing.
"""

import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from conftest import wait_until

from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh
from bqueryd_tpu.plan import bundle as bundlemod
from bqueryd_tpu.plan import plan_groupby
from bqueryd_tpu.storage import ctable

N_SHARDS = 3


def swarm_df(n=9_000, seed=23):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "k": rng.integers(0, 8, n).astype(np.int64),
            "k2": rng.integers(0, 3, n).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
            "w": rng.random(n) * 10.0,
        }
    )


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    df = swarm_df()
    base = tmp_path_factory.mktemp("bundles")
    tables = []
    for i in range(N_SHARDS):
        root = str(base / f"b_{i}.bcolzs")
        ctable.fromdataframe(
            df.iloc[i::N_SHARDS].reset_index(drop=True), root
        )
        tables.append(ctable(root, mode="r"))
    return df, tables


def frame(payload):
    return hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    )


def assert_same(got, expected, key_cols, exact_ints=True):
    got = got.sort_values(key_cols).reset_index(drop=True)
    expected = expected.sort_values(key_cols).reset_index(drop=True)
    expected = expected[list(got.columns)]
    assert len(got) == len(expected)
    for col in got.columns:
        a, b = got[col].to_numpy(), expected[col].to_numpy()
        if a.dtype.kind in "iub" and exact_ints:
            assert np.array_equal(a, b), col
        else:
            np.testing.assert_allclose(
                a.astype(np.float64), b.astype(np.float64), rtol=1e-9
            )


# ---------------------------------------------------------------------------
# plan.bundle: compatibility signatures + fragments
# ---------------------------------------------------------------------------

def _plan(files, gcols, aggs, where=None, **kw):
    return plan_groupby(files, gcols, aggs, where or [], **kw)


def test_compat_key_fuses_across_measures_and_filters():
    keep = ["a.bcolzs", "b.bcolzs"]
    p1 = _plan(keep, ["k"], [["v", "sum", "v"]], [["w", ">", 1.0]])
    p2 = _plan(keep, ["k"], [["w", "mean", "m"]], [["w", "<", 9.0]])
    k1 = bundlemod.compat_key(p1, keep, {})
    k2 = bundlemod.compat_key(p2, keep, {})
    assert k1 is not None and k1 == k2


def test_compat_key_separates_incompatible_queries():
    keep = ["a.bcolzs", "b.bcolzs"]
    base = _plan(keep, ["k"], [["v", "sum", "v"]])
    key = bundlemod.compat_key(base, keep, {})
    # different group keys -> different signature
    other = _plan(keep, ["k2"], [["v", "sum", "v"]])
    assert bundlemod.compat_key(other, keep, {}) != key
    # different post-prune shard set -> different signature
    assert bundlemod.compat_key(base, keep[:1], {}) != key
    # raw-rows, basket expansion, non-mergeable aggs, batch=False and
    # fully-pruned plans cannot ride a bundle at all
    raw = _plan(keep, ["k"], [["v", "sum", "v"]], aggregate=False)
    assert bundlemod.compat_key(raw, keep, {}) is None
    basket = _plan(
        keep, ["k"], [["v", "sum", "v"]], expand_filter_column="k2"
    )
    assert bundlemod.compat_key(basket, keep, {}) is None
    distinct = _plan(keep, ["k"], [["v", "count_distinct", "nd"]])
    assert bundlemod.compat_key(distinct, keep, {}) is None
    assert bundlemod.compat_key(base, keep, {"batch": False}) is None
    assert bundlemod.compat_key(base, [], {}) is None
    # affinity is part of the identity (a pinned query must not fuse away)
    assert bundlemod.compat_key(base, keep, {"affinity": "w1"}) != key


def test_bundle_fragment_round_trip():
    keep = ["a.bcolzs"]
    p1 = _plan(keep, ["k"], [["v", "sum", "v"]], [["w", ">", 2.0]])
    p2 = _plan(keep, ["k"], [["v", "mean", "m"]])
    fragment = bundlemod.bundle_fragment(
        p1, keep, [("m1", p1, None), ("m2", p2, 123.0)],
    )
    members = bundlemod.bundle_to_queries(fragment)
    assert [m[0] for m in members] == ["m1", "m2"]
    assert members[0][1] is None and members[1][1] == 123.0
    q1, q2 = members[0][2], members[1][2]
    assert q1.where_terms == [("w", ">", 2.0)]
    assert q1.agg_list == [["v", "sum", "v"]]
    # mean decomposition round-trips through the physical form
    assert q2.ops == ("mean",)
    # a bundle names its members' work, never the kernel
    assert set(fragment) == {
        "v", "filenames", "groupby_cols", "sole", "members"}
    with pytest.raises(ValueError):
        bundlemod.bundle_to_queries({"v": 99, "members": []})


def test_window_knobs_default_off(monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_BATCH_WINDOW_MS", raising=False)
    assert bundlemod.batch_window_ms() == 0.0
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "25.5")
    assert bundlemod.batch_window_ms() == 25.5
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "garbage")
    assert bundlemod.batch_window_ms() == 0.0
    monkeypatch.setenv("BQUERYD_TPU_BATCH_MAX", "1")
    assert bundlemod.batch_max() == 2  # floor: a bundle needs two members


# ---------------------------------------------------------------------------
# ops.bundle_partial_tables: stacked-mask emission vs solo kernels
# ---------------------------------------------------------------------------

def test_bundle_partial_tables_matches_solo_kernels():
    import jax.numpy as jnp

    from bqueryd_tpu import ops

    rng = np.random.default_rng(5)
    n, n_groups = 4096, 11
    codes = rng.integers(-1, n_groups, n).astype(np.int32)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    w = rng.random(n)
    mask_a = rng.random(n) > 0.4
    mask_b = rng.random(n) > 0.7
    member_specs = (
        (0, ((0, "sum"), (0, "count"))),   # masked by mask_a, over v
        (None, ((1, "mean"),)),            # unfiltered, over w
        (1, ((1, "min"), (0, "max"))),     # masked by mask_b, mixed cols
    )
    out = ops.bundle_partial_tables(
        jnp.asarray(codes),
        jnp.stack([jnp.asarray(mask_a), jnp.asarray(mask_b)]),
        (jnp.asarray(v), jnp.asarray(w)),
        member_specs,
        n_groups,
    )
    assert len(out) == 3
    solos = [
        ops.partial_tables(
            jnp.asarray(codes), (jnp.asarray(v), jnp.asarray(v)),
            ("sum", "count"), n_groups, mask=jnp.asarray(mask_a),
        ),
        ops.partial_tables(
            jnp.asarray(codes), (jnp.asarray(w),), ("mean",), n_groups,
        ),
        ops.partial_tables(
            jnp.asarray(codes), (jnp.asarray(w), jnp.asarray(v)),
            ("min", "max"), n_groups, mask=jnp.asarray(mask_b),
        ),
    ]
    import jax

    for got, want in zip(out, solos):
        got_leaves = jax.tree_util.tree_leaves(got)
        want_leaves = jax.tree_util.tree_leaves(want)
        assert len(got_leaves) == len(want_leaves)
        for g, w_ in zip(got_leaves, want_leaves):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


# ---------------------------------------------------------------------------
# executor.execute_bundle: shared-scan parity on the 8-device mesh
# ---------------------------------------------------------------------------

def test_execute_bundle_matches_solo_execution(sharded):
    _df, tables = sharded
    ex = MeshQueryExecutor(mesh=make_mesh())
    queries = [
        GroupByQuery(["k"], [["v", "sum", "v_sum"]], [("w", ">", 6.0)]),
        GroupByQuery(["k"], [["v", "sum", "v_sum"]], [("w", ">", 1.5)]),
        GroupByQuery(["k"], [["w", "mean", "w_mean"]], []),
        GroupByQuery(
            ["k"], [["v", "min", "v_min"], ["v", "max", "v_max"]],
            [("w", "<", 8.0)],
        ),
        GroupByQuery(
            ["k"], [["v", "sum", "s"], ["v", "count", "n"],
                    ["w", "mean", "m"]],
            [("w", ">", 3.0)],
        ),
    ]
    bundled = ex.execute_bundle(tables, queries)
    assert len(bundled) == len(queries)
    for query, payload in zip(queries, bundled):
        solo = ex.execute(tables, query)
        assert_same(frame(payload), frame(solo), ["k"])


def test_execute_bundle_matches_pandas(sharded):
    df, tables = sharded
    ex = MeshQueryExecutor(mesh=make_mesh())
    queries = [
        GroupByQuery(["k"], [["v", "sum", "v_sum"]], [("w", ">", 5.0)]),
        GroupByQuery(["k"], [["v", "count", "n"]], [("w", "<", 5.0)]),
    ]
    got = [frame(p) for p in ex.execute_bundle(tables, queries)]
    exp0 = (
        df[df["w"] > 5.0].groupby("k")["v"].sum().reset_index()
        .rename(columns={"v": "v_sum"})
    )
    exp1 = (
        df[df["w"] < 5.0].groupby("k")["v"].count().reset_index()
        .rename(columns={"v": "n"})
    )
    assert_same(got[0], exp0, ["k"])
    assert_same(got[1], exp1, ["k"], exact_ints=False)


def test_execute_bundle_shares_scan_work(sharded):
    """The whole point: one alignment, one codes upload, one union measure
    upload for N members — solo repeats would multiply those misses."""
    _df, tables = sharded
    ex = MeshQueryExecutor(mesh=make_mesh())
    queries = [
        GroupByQuery(["k"], [["v", "sum", "a"]], [("w", ">", t)])
        for t in (1.0, 2.0, 3.0, 4.0)
    ]
    ex.execute_bundle(tables, queries)
    stats = ex.workingset.stats()
    assert stats["align"]["misses"] == 1
    assert stats["codes"]["misses"] == 1   # ONE unmasked codes entry
    assert stats["blocks"]["misses"] == 1  # v uploaded once for 4 members
    before = ex.workingset.stats()["codes"]["hits"]
    # a second bundle over the same tables is fully warm on the scan side
    ex.execute_bundle(tables, queries[:2])
    stats = ex.workingset.stats()
    assert stats["align"]["misses"] == 1
    assert stats["codes"]["hits"] > before
    # ... and the unmasked codes entry is the SAME one an unfiltered solo
    # query uses (shared key): no new codes miss
    ex.execute(tables, GroupByQuery(["k"], [["v", "sum", "a"]]))
    assert ex.workingset.stats()["codes"]["misses"] == 1


def test_execute_bundle_rejects_mixed_group_keys(sharded):
    _df, tables = sharded
    ex = MeshQueryExecutor(mesh=make_mesh())
    with pytest.raises(ValueError, match="group-key"):
        ex.execute_bundle(
            tables,
            [
                GroupByQuery(["k"], [["v", "sum", "a"]]),
                GroupByQuery(["k2"], [["v", "sum", "a"]]),
            ],
        )


# ---------------------------------------------------------------------------
# cluster: the admission window end to end
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
def swarm_cluster(tmp_path, mem_store_url):
    """Controller + one calc worker serving two shards of swarm_df."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.worker import WorkerNode

    df = swarm_df(n=6_000, seed=31)
    shards = ["c_0.bcolzs", "c_1.bcolzs"]
    for i, name in enumerate(shards):
        ctable.fromdataframe(
            df.iloc[i::2].reset_index(drop=True), str(tmp_path / name)
        )
    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
    )
    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.1,
        poll_timeout=0.05,
    )
    threads = _start(controller, worker)
    wait_until(
        lambda: all(name in controller.files_map for name in shards),
        desc="shards advertised",
    )
    yield {
        "controller": controller,
        "worker": worker,
        "df": df,
        "shards": shards,
        "url": mem_store_url,
    }
    _stop([controller, worker], threads)


def _concurrent_groupby(url, queries, timeout=60, client_ids=None):
    """One thread + one RPC socket per query; returns results/errors by
    index."""
    from bqueryd_tpu.rpc import RPC

    results, errors = {}, {}

    def run(i, query):
        try:
            rpc = RPC(
                coordination_url=url, timeout=timeout,
                loglevel=logging.WARNING,
                client_id=(client_ids or {}).get(i),
            )
            kwargs = {}
            if len(query) == 5:
                kwargs["deadline"] = query[4]
            results[i] = rpc.groupby(*query[:4], **kwargs)
        except Exception as exc:  # noqa: BLE001 - surfaced via errors dict
            errors[i] = exc

    threads = [
        threading.Thread(target=run, args=(i, q), daemon=True)
        for i, q in enumerate(queries)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout + 30)
    return results, errors


def test_window_zero_stages_nothing(swarm_cluster, monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_BATCH_WINDOW_MS", raising=False)
    cluster = swarm_cluster
    results, errors = _concurrent_groupby(
        cluster["url"],
        [(cluster["shards"], ["k"], [["v", "sum", "s"]], [])],
    )
    assert not errors
    assert cluster["controller"].counters["plan_bundles"] == 0
    assert not cluster["controller"]._pending_window


def test_window_fuses_compatible_queries_with_parity(
    swarm_cluster, monkeypatch
):
    """Distinct-but-compatible concurrent queries fuse into one bundle;
    every member's result is bit-identical to its window-0 run."""
    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    queries = [
        (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 7.0]]),
        (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 2.0]]),
        (shards, ["k"], [["w", "mean", "m"]], []),
    ]
    # window 0 reference first (and it must not bundle)
    monkeypatch.delenv("BQUERYD_TPU_BATCH_WINDOW_MS", raising=False)
    ref, errors = _concurrent_groupby(url, queries)
    assert not errors
    assert cluster["controller"].counters["plan_bundles"] == 0

    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "300")
    fused, errors = _concurrent_groupby(url, queries)
    assert not errors
    counters = cluster["controller"].counters
    assert counters["plan_bundles"] >= 1
    assert counters["plan_bundled_queries"] >= 3
    assert counters["plan_shared_dispatches"] >= 2
    for i in range(len(queries)):
        assert_same(fused[i], ref[i], ["k"])
    # pandas cross-check on one member (ints bit-exact end to end)
    expected = (
        df[df["w"] > 7.0].groupby("k")["v"].sum().reset_index()
        .rename(columns={"v": "s"})
    )
    assert_same(fused[0], expected, ["k"])


def test_window_keeps_incompatible_queries_separate(
    swarm_cluster, monkeypatch
):
    """One window, two signatures (different group keys): both complete
    correctly, unfused."""
    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "300")
    before = cluster["controller"].counters["plan_bundles"]
    results, errors = _concurrent_groupby(
        url,
        [
            (shards, ["k"], [["v", "sum", "s"]], []),
            (shards, ["k2"], [["v", "sum", "s"]], []),
        ],
    )
    assert not errors
    assert cluster["controller"].counters["plan_bundles"] == before
    for i, gcol in enumerate(["k", "k2"]):
        expected = (
            df.groupby(gcol)["v"].sum().reset_index()
            .rename(columns={"v": "s"})
        )
        assert_same(results[i], expected, [gcol])


def test_bundle_member_deadline_isolation(swarm_cluster, monkeypatch):
    """A member whose deadline expires inside the window is dropped from
    the stack with ITS error; bundle-mates answer normally, and nothing is
    re-executed (one dispatch total)."""
    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    controller = cluster["controller"]
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "600")
    dispatched_before = controller.counters["dispatched_shards"]
    queries = [
        (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 4.0]]),
        # 0.1 s deadline expires inside the 0.6 s window
        (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 5.0]], 0.1),
        (shards, ["k"], [["v", "sum", "s"]], []),
    ]
    results, errors = _concurrent_groupby(url, queries)
    assert set(errors) == {1}
    assert "deadline" in str(errors[1]).lower()
    assert set(results) == {0, 2}
    for i, term in ((0, 4.0), (2, None)):
        sel = df if term is None else df[df["w"] > term]
        expected = (
            sel.groupby("k")["v"].sum().reset_index()
            .rename(columns={"v": "s"})
        )
        assert_same(results[i], expected, ["k"])
    # the expired member triggered no re-dispatch of its bundle-mates
    assert (
        controller.counters["dispatched_shards"] - dispatched_before == 1
    )
    wait_until(
        lambda: not controller.inflight and not controller.rpc_segments,
        desc="bundle fully settled",
    )


def test_bundle_member_quota_rejection_isolation(swarm_cluster, monkeypatch):
    """A quota-rejected query (client over BQUERYD_TPU_ADMIT_CLIENT_QUOTA
    while its first query sits staged) gets BUSY; the staged bundle
    completes undisturbed."""
    from bqueryd_tpu.rpc import RPCBusyError

    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    controller = cluster["controller"]
    controller.admission.client_quota = 1
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "800")
    try:
        queries = [
            (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 3.0]]),
            (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 6.0]]),
            # same client_id as 0: over quota while 0 is staged -> BUSY
            (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 1.0]]),
        ]

        def fire():
            # 0 and 1 (distinct quota buckets) land in the window; 2
            # shares client 0's bucket and must bounce without touching
            # the staged bundle
            results, errors = {}, {}

            def one(i, client_id, delay):
                from bqueryd_tpu.rpc import RPC

                time.sleep(delay)
                try:
                    rpc = RPC(
                        coordination_url=url, timeout=60,
                        loglevel=logging.WARNING, client_id=client_id,
                        retries=1,
                    )
                    results[i] = rpc.groupby(*queries[i])
                except Exception as exc:  # noqa: BLE001
                    errors[i] = exc

            threads = [
                threading.Thread(
                    target=one, args=(0, "app-a", 0.0), daemon=True
                ),
                threading.Thread(
                    target=one, args=(1, "app-b", 0.0), daemon=True
                ),
                threading.Thread(
                    target=one, args=(2, "app-a", 0.25), daemon=True
                ),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(90)
            return results, errors

        results, errors = fire()
        assert set(errors) == {2}
        assert isinstance(errors[2], RPCBusyError)
        assert set(results) == {0, 1}
        for i, term in ((0, 3.0), (1, 6.0)):
            expected = (
                df[df["w"] > term].groupby("k")["v"].sum().reset_index()
                .rename(columns={"v": "s"})
            )
            assert_same(results[i], expected, ["k"])
    finally:
        controller.admission.client_quota = 0


def test_bundle_member_error_isolation(swarm_cluster, monkeypatch):
    """A member whose query fails per-member (unknown column) errors alone;
    its bundle-mate completes."""
    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "400")
    results, errors = _concurrent_groupby(
        url,
        [
            (shards, ["k"], [["v", "sum", "s"]], []),
            (shards, ["k"], [["no_such_column", "sum", "s"]], []),
        ],
    )
    assert set(errors) == {1}
    assert set(results) == {0}
    expected = (
        df.groupby("k")["v"].sum().reset_index().rename(columns={"v": "s"})
    )
    assert_same(results[0], expected, ["k"])


def test_identical_queries_share_dispatch_at_window_zero(swarm_cluster):
    """The PR-1 path the bench probe exercises: two concurrent IDENTICAL
    queries at window 0 fuse into one dispatch via the work-key index."""
    cluster = swarm_cluster
    df, shards, url = cluster["df"], cluster["shards"], cluster["url"]
    controller = cluster["controller"]
    os.environ.pop("BQUERYD_TPU_BATCH_WINDOW_MS", None)
    shared_before = controller.counters["plan_shared_dispatches"]
    dispatched_before = controller.counters["dispatched_shards"]
    query = (shards, ["k"], [["v", "sum", "s"]], [["w", ">", 4.44]])
    results, errors = _concurrent_groupby(url, [query, query])
    assert not errors
    assert (
        controller.counters["plan_shared_dispatches"] - shared_before >= 1
    )
    assert (
        controller.counters["dispatched_shards"] - dispatched_before == 1
    )
    expected = (
        df[df["w"] > 4.44].groupby("k")["v"].sum().reset_index()
        .rename(columns={"v": "s"})
    )
    for i in (0, 1):
        assert_same(results[i], expected, ["k"])
