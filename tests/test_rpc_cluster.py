"""Full-cluster integration tests: controller + workers + client in one
process (threads as nodes — the reference's own test topology, reference
tests/test_simple_rpc.py:42-74, with condition polling instead of sleeps)."""

import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from tests.conftest import wait_until

NR_SHARDS = 5


def taxi_like_df(n=12_000, seed=4):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "passenger_count": rng.integers(0, 7, n).astype(np.int64),
            "trip_distance": rng.exponential(3.0, n),
            "total_amount": rng.gamma(2.5, 8.0, n),
        }
    )


@pytest.fixture(scope="module")
def taxi_df():
    return taxi_like_df()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory, taxi_df):
    from bqueryd_tpu.storage import ctable

    root = tmp_path_factory.mktemp("cluster_data")
    ctable.fromdataframe(taxi_df, str(root / "taxi.bcolz"))
    for i in range(NR_SHARDS):
        ctable.fromdataframe(
            taxi_df.iloc[i::NR_SHARDS], str(root / f"taxi-{i}.bcolzs")
        )
    return str(root)


@pytest.fixture(scope="module")
def cluster(data_dir):
    import bqueryd_tpu
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.worker import DownloaderNode, WorkerNode

    url = f"mem://cluster-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=data_dir,
        heartbeat_interval=0.2,
        dead_worker_timeout=10.0,
    )
    worker = WorkerNode(
        coordination_url=url,
        data_dir=data_dir,
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.2,
        poll_timeout=0.1,
    )

    class DummyDownloader(DownloaderNode):
        """Fakes the blob fetch but stages a real file so the movebcolz
        two-phase commit runs (the reference's DummyDownloader seam,
        reference tests/test_simple_rpc.py:36-39)."""

        def download_file(self, ticket, fileurl, lock=None):
            from bqueryd_tpu.download import incoming_dir

            staging = incoming_dir(self, ticket)
            name = os.path.basename(fileurl)
            os.makedirs(os.path.join(staging, name), exist_ok=True)
            self.file_downloader_progress(ticket, fileurl, "DONE")

    downloader = DummyDownloader(
        coordination_url=url,
        data_dir=data_dir,
        loglevel=logging.WARNING,
        heartbeat_interval=0.2,
        poll_timeout=0.1,
    )
    downloader.download_interval = 0.2

    from bqueryd_tpu.worker import MoveBcolzNode

    mover = MoveBcolzNode(
        coordination_url=url,
        data_dir=data_dir,
        loglevel=logging.WARNING,
        heartbeat_interval=0.2,
        poll_timeout=0.1,
    )
    mover.download_interval = 0.2

    threads = [
        threading.Thread(target=node.go, daemon=True)
        for node in (controller, worker, downloader, mover)
    ]
    for t in threads:
        t.start()

    wait_until(
        lambda: controller.files_map.get("taxi.bcolz"),
        desc="worker registration with data files",
    )
    wait_until(
        lambda: len(controller.worker_map) >= 3,
        desc="all workers registered",
    )
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
    yield {
        "rpc": rpc,
        "controller": controller,
        "worker": worker,
        "downloader": downloader,
        "mover": mover,
        "url": url,
    }
    for node in (controller, worker, downloader, mover):
        node.running = False
    for t in threads:
        t.join(timeout=5)


def test_ping(cluster):
    assert cluster["rpc"].ping() == "pong"


def test_info_shape(cluster):
    info = cluster["rpc"].info()
    assert info["address"] == cluster["controller"].address
    workers = info["workers"]
    types = sorted(w["workertype"] for w in workers.values())
    assert types == ["calc", "download", "movebcolz"]
    node_names = {w["node"] for w in workers.values()}
    assert node_names == {cluster["worker"].node_name}
    assert info["others"] == {}
    assert cluster["rpc"].last_call_duration is not None


def test_groupby_single_file_vs_pandas(cluster, taxi_df):
    rpc = cluster["rpc"]
    for op, pandas_fn in [("sum", "sum"), ("mean", "mean"), ("count", "count")]:
        got = rpc.groupby(
            ["taxi.bcolz"],
            ["payment_type"],
            [["total_amount", op, "total_amount"]],
            [],
        )
        got = got.sort_values("payment_type").reset_index(drop=True)
        expected = (
            getattr(taxi_df.groupby("payment_type")["total_amount"], pandas_fn)()
            .reset_index()
        )
        pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False)


def test_groupby_sharded_matches_full(cluster):
    rpc = cluster["rpc"]
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    full = rpc.groupby(
        ["taxi.bcolz"], ["payment_type"],
        [["passenger_count", "count", "passenger_count"]], [],
    )
    parts = rpc.groupby(
        shard_names, ["payment_type"],
        [["passenger_count", "count", "passenger_count"]], [],
    )
    full = full.sort_values("payment_type").reset_index(drop=True)
    parts = parts.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(full, parts, check_dtype=False, check_column_type=False)


def test_effective_strategy_reaches_the_client_envelope(cluster):
    """A dispatch carries no route and the reply reports the one the
    kernel rule took: ``hints`` counts the shards sent ({"auto": n}),
    ``effective`` names the route per shard group (FORCE_MATMUL=1 here).
    A fresh filter constant keeps the result cache out of it."""
    rpc = cluster["rpc"]
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    rpc.groupby(
        shard_names, ["payment_type"],
        [["passenger_count", "sum", "passenger_count"]],
        [["trip_distance", ">", 0.123456]],
    )
    strategies = rpc.last_call_strategies
    assert strategies["hints"] == {"auto": NR_SHARDS}
    assert set(strategies["effective"].values()) == {"matmul"}


def test_groupby_with_filter(cluster, taxi_df):
    got = cluster["rpc"].groupby(
        ["taxi.bcolz"],
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        [("trip_distance", ">", 5.0)],
    )
    expected = (
        taxi_df[taxi_df.trip_distance > 5.0]
        .groupby("payment_type")["total_amount"].sum().reset_index()
    )
    got = got.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False)


def test_count_distinct_sharded(cluster, taxi_df):
    """Distinct counts can't psum-merge; the cluster must route them through
    the per-shard gather path and still agree with pandas nunique."""
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    got = cluster["rpc"].groupby(
        shard_names,
        ["payment_type"],
        [["passenger_count", "count_distinct", "nuniq"]],
        [],
    )
    expected = (
        taxi_df.groupby("payment_type")["passenger_count"]
        .nunique()
        .reset_index(name="nuniq")
    )
    got = got.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False)


def test_count_distinct_single_file_device_path(cluster, taxi_df):
    """A single-file count_distinct query gets the controller's sole-shard
    hint and finalizes on device (counts, no value sets) — same answer as
    pandas nunique."""
    got = cluster["rpc"].groupby(
        ["taxi.bcolz"],
        ["payment_type"],
        [["passenger_count", "count_distinct", "nuniq"]],
        [],
    )
    expected = (
        taxi_df.groupby("payment_type")["passenger_count"]
        .nunique()
        .reset_index(name="nuniq")
    )
    got = got.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False)


def test_count_distinct_string_column_across_shards(tmp_path, mem_store_url):
    """Per-shard dictionaries encode the same string with different codes;
    the distinct-set merge must union VALUES, not codes."""
    import threading

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage import ctable as storage_ctable
    from bqueryd_tpu.worker import WorkerNode

    # shard 0 sees 'cash' first, shard 1 sees 'credit' first -> code spaces
    # deliberately disagree
    s0 = pd.DataFrame({"g": [1, 1, 2], "pay": ["cash", "credit", "cash"]})
    s1 = pd.DataFrame({"g": [1, 2, 2], "pay": ["credit", "cash", "credit"]})
    storage_ctable.fromdataframe(s0, str(tmp_path / "p0.bcolzs"))
    storage_ctable.fromdataframe(s1, str(tmp_path / "p1.bcolzs"))

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.1,
    )
    worker = WorkerNode(
        coordination_url=mem_store_url, data_dir=str(tmp_path),
        loglevel=logging.WARNING, restart_check=False,
        heartbeat_interval=0.1, poll_timeout=0.05,
    )
    threads = [
        threading.Thread(target=n.go, daemon=True)
        for n in (controller, worker)
    ]
    for t in threads:
        t.start()
    try:
        wait_until(lambda: len(controller.files_map) >= 2, desc="shards")
        rpc = RPC(coordination_url=mem_store_url, timeout=30,
                  loglevel=logging.WARNING)
        got = rpc.groupby(
            ["p0.bcolzs", "p1.bcolzs"], ["g"],
            [["pay", "count_distinct", "nuniq"]], [],
        ).sort_values("g").reset_index(drop=True)
        full = pd.concat([s0, s1], ignore_index=True)
        exp = full.groupby("g")["pay"].nunique().reset_index(name="nuniq")
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, check_column_type=False)
    finally:
        for n in (controller, worker):
            n.running = False
        for t in threads:
            t.join(timeout=5)


def test_raw_rows_mode_sharded(cluster, taxi_df):
    """aggregate=False returns the filtered rows themselves, concatenated
    across shards (reference bqueryd/worker.py:316-323 raw path)."""
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    got = cluster["rpc"].groupby(
        shard_names,
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        [("trip_distance", ">", 20.0)],
        aggregate=False,
    )
    expected = taxi_df[taxi_df.trip_distance > 20.0]
    assert len(got) == len(expected)
    # same multiset of rows (shard order differs from source order)
    got_s = got.sort_values(
        ["payment_type", "total_amount"]
    ).reset_index(drop=True)
    exp_s = expected[["payment_type", "total_amount"]].sort_values(
        ["payment_type", "total_amount"]
    ).reset_index(drop=True)
    pd.testing.assert_frame_equal(got_s, exp_s, check_dtype=False, check_column_type=False)


def test_groupby_unknown_file_errors(cluster):
    from bqueryd_tpu.rpc import RPCError

    with pytest.raises(RPCError, match="not found"):
        cluster["rpc"].groupby(["nope.bcolz"], ["payment_type"], [["x", "sum", "x"]], [])


def test_unknown_verb_errors(cluster):
    from bqueryd_tpu.rpc import RPCError

    with pytest.raises(RPCError, match="unknown verb"):
        cluster["rpc"].frobnicate()


def test_sleep_roundtrip(cluster):
    result = cluster["rpc"].sleep(0.01)
    assert "slept" in result


def test_download_ticket_registration(cluster):
    import bqueryd_tpu

    rpc = cluster["rpc"]
    ticket = rpc.download(filenames=["test_download.bcolz"], bucket="bcolz", wait=False)
    store = cluster["controller"].store
    entries = store.hgetall(bqueryd_tpu.REDIS_TICKET_KEY_PREFIX + ticket)
    assert len(entries) == 1
    ((slot, value),) = entries.items()
    assert slot.partition("_")[2] == "s3://bcolz/test_download.bcolz"
    # the cluster's dummy downloader may legitimately claim the ticket and
    # advance it between registration and this read — assert the slot value
    # is a well-formed progress state, not specifically the initial -1
    state = value.rpartition("_")[2]
    assert state == "-1" or state == "DONE" or state.isdigit()


def test_download_wait_released_by_dummy_downloader(cluster):
    result = cluster["rpc"].download(
        filenames=["some_file.newdata"], bucket="bcolz", wait=True
    )
    assert result == "DONE"


def test_worker_error_aborts_query(cluster, data_dir):
    """A shard whose table is corrupt must abort the whole query with the
    worker's error forwarded (reference bqueryd/controller.py:157-168)."""
    import shutil

    from bqueryd_tpu.rpc import RPCError

    from tests.conftest import wait_until

    bad = os.path.join(data_dir, "bad.bcolz")
    os.makedirs(bad, exist_ok=True)
    with open(os.path.join(bad, "meta.json"), "w") as f:
        f.write("{}")
    try:
        wait_until(
            lambda: "bad.bcolz" in cluster["controller"].files_map,
            desc="bad.bcolz discovery",
        )
        with pytest.raises(RPCError):
            cluster["rpc"].groupby(
                ["bad.bcolz"], ["payment_type"], [["x", "sum", "x"]], []
            )
    finally:
        shutil.rmtree(bad)


def test_loglevel_fanout(cluster):
    import bqueryd_tpu

    # the verb fans out asynchronously (controller applies it synchronously,
    # workers on their next poll tick), and every node shares this process's
    # root logger — poll until the last fan-out echo settles
    assert cluster["rpc"].loglevel("debug") == "OK"
    wait_until(
        lambda: bqueryd_tpu.logger.level == logging.DEBUG,
        desc="loglevel debug applied",
    )
    cluster["rpc"].loglevel("info")
    # stability, not a fixed sleep: every fan-out echo (controller + 3
    # worker roles) must have applied 'info' — poll until the level has
    # held INFO continuously for half a second
    stable_since = [None]

    def held_info():
        if bqueryd_tpu.logger.level != logging.INFO:
            stable_since[0] = None
            return False
        if stable_since[0] is None:
            stable_since[0] = time.time()
        return time.time() - stable_since[0] >= 0.5
    wait_until(held_info, desc="loglevel info applied and stable")


def test_batched_dispatch_merges_on_worker(cluster, taxi_df):
    """Co-located mergeable shards travel as ONE CalcMessage and come back as
    ONE psum-merged payload (the TPU redesign of per-shard fan-out)."""
    rpc = cluster["rpc"]
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    got = rpc.groupby(
        shard_names, ["payment_type"],
        [["total_amount", "mean", "m"], ["total_amount", "sum", "s"]], [],
    )
    # one timing entry covering all shards == one worker round-trip,
    # labelled compactly as "<first-file>+<n-1>more"
    assert len(rpc.last_call_timings) == 1
    (key,) = rpc.last_call_timings
    first, _, rest = key.partition("+")
    assert first in shard_names
    assert rest == f"{NR_SHARDS - 1}more"
    g = taxi_df.groupby("payment_type")["total_amount"]
    expected = pd.DataFrame({"m": g.mean(), "s": g.sum()}).reset_index()
    got = got.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False)


def test_batch_false_restores_pershard_dispatch(cluster):
    rpc = cluster["rpc"]
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    rpc.groupby(
        shard_names, ["payment_type"], [["total_amount", "sum", "s"]], [],
        batch=False,
    )
    assert len(rpc.last_call_timings) == NR_SHARDS


def test_legacy_merge_sum_of_shard_means(cluster, taxi_df):
    """legacy_merge reproduces the reference's sum-of-shard-means quirk
    (reference bqueryd/rpc.py:171), which requires per-shard payloads."""
    from bqueryd_tpu.rpc import RPC

    legacy = RPC(
        coordination_url=cluster["url"], timeout=60,
        loglevel=logging.WARNING, legacy_merge=True,
    )
    shard_names = [f"taxi-{i}.bcolzs" for i in range(NR_SHARDS)]
    got = legacy.groupby(
        shard_names, ["payment_type"], [["total_amount", "mean", "m"]], [],
    )
    assert len(legacy.last_call_timings) == NR_SHARDS  # batching disabled
    expected = sum(
        taxi_df.iloc[i::NR_SHARDS].groupby("payment_type")["total_amount"]
        .mean()
        for i in range(NR_SHARDS)
    ).reset_index(name="m")
    got = got.sort_values("payment_type").reset_index(drop=True)
    pd.testing.assert_frame_equal(
        got, expected.rename(columns={"total_amount": "m"}),
        check_dtype=False, check_column_type=False,
    )


def test_readfile_returns_bytes(cluster, data_dir):
    """The reference's readfile verb (reference bqueryd/worker.py:216-220)
    end to end: client -> controller -> worker -> file bytes back."""
    with open(os.path.join(data_dir, "probe.txt"), "wb") as f:
        f.write(b"hello readfile")
    assert cluster["rpc"].readfile("probe.txt") == b"hello readfile"


def test_readfile_rejects_path_traversal(cluster):
    """The traversal guard is a deliberate behavior change vs the reference
    (which would serve any path joined under data_dir): escaping paths must
    error, not leak files."""
    from bqueryd_tpu.rpc import RPCError

    with pytest.raises(RPCError, match="escapes data_dir"):
        cluster["rpc"].readfile("../../etc/hostname")


def test_replacement_worker_first_query_rides_disk_sidecars(tmp_path):
    """A replacement worker's FIRST query on shards a previous worker served
    must come back exact and be answered from the on-disk factorize
    sidecars (bquery auto_cache parity across worker restarts): the
    sidecars' mtimes must not change — a store only happens on a load
    miss, so unchanged files mean the cold alignment truly loaded."""
    import glob

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage import ctable as CT
    from bqueryd_tpu.worker import WorkerNode

    df = taxi_like_df(n=6_000, seed=9)
    for i in range(3):
        CT.fromdataframe(
            df.iloc[i::3].reset_index(drop=True),
            str(tmp_path / f"side-{i}.bcolzs"),
        )
    url = f"mem://sidecar-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
        dead_worker_timeout=2.0,
    )

    def new_worker():
        return WorkerNode(
            coordination_url=url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.1,
            poll_timeout=0.05,
        )

    files = [f"side-{i}.bcolzs" for i in range(3)]
    expected = (
        df.groupby("payment_type")["total_amount"].sum().to_dict()
    )
    w1 = new_worker()
    nodes = [controller, w1]
    threads = [
        threading.Thread(target=n.go, daemon=True) for n in nodes
    ]
    for t in threads:
        t.start()
    try:
        wait_until(
            lambda: all(f in controller.files_map for f in files),
            desc="registration",
        )
        rpc = RPC(coordination_url=url, timeout=30,
                  loglevel=logging.WARNING)
        got = rpc.groupby(
            files, ["payment_type"], [["total_amount", "sum", "s"]], []
        )
        assert dict(
            zip(got["payment_type"], got["s"])
        ) == pytest.approx(expected)

        sidecars = sorted(
            glob.glob(str(tmp_path / "side-*" / "cols" / "*" / "*.npz"))
        )
        assert sidecars, "first worker must have persisted factorizations"
        stamps_before = [os.stat(p).st_mtime_ns for p in sidecars]

        # hard restart: silence + replacement (fresh engine, empty caches)
        w1.send = lambda *a, **k: None
        w1._hb_stop.set()
        w1.running = False
        w2 = new_worker()
        nodes.append(w2)
        t2 = threading.Thread(target=w2.go, daemon=True)
        threads.append(t2)
        t2.start()
        wait_until(
            lambda: w2.worker_id in controller.worker_map
            and w1.worker_id not in controller.worker_map,
            timeout=20,
            desc="replacement adopted, old culled",
        )
        # a DIFFERENT aggregation over the same key column: no result
        # cache anywhere can serve it, so it must run on the replacement —
        # while key alignment still rides the same factorize sidecars
        got2 = rpc.groupby(
            files, ["payment_type"], [["total_amount", "mean", "m"]], []
        )
        expected_mean = (
            df.groupby("payment_type")["total_amount"].mean().to_dict()
        )
        assert dict(
            zip(got2["payment_type"], got2["m"])
        ) == pytest.approx(expected_mean)
        stamps_after = [os.stat(p).st_mtime_ns for p in sidecars]
        assert stamps_after == stamps_before, (
            "replacement worker re-factorized instead of loading sidecars"
        )
    finally:
        for n in nodes:
            n.running = False
        for t in threads:
            t.join(timeout=5)
