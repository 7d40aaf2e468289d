"""Controller scheduling edge cases: batched-group re-split when placement
changes, fail-fast on vanished files, duplicate-filename dedup, and the
download-failure path of the two-phase commit."""

import logging
import os
import time

import pytest

import bqueryd_tpu
from bqueryd_tpu.controller import ControllerNode
from bqueryd_tpu.messages import RPCMessage


@pytest.fixture
def controller(tmp_path):
    node = ControllerNode(
        coordination_url=f"mem://sched-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
    )
    yield node
    node.socket.close()


def register(controller, worker_id, files, busy=True):
    controller.worker_map[worker_id] = {
        "worker_id": worker_id,
        "workertype": "calc",
        "busy": busy,
        "last_seen": time.time(),
        "node": controller.node_name,
    }
    for f in files:
        controller.files_map.setdefault(f, set()).add(worker_id)


def enqueue_groupby(controller, filenames):
    msg = RPCMessage({"payload": "groupby", "token": "00"})
    msg.set_args_kwargs(
        [filenames, ["k"], [["v", "sum", "v"]], []], {}
    )
    controller.rpc_groupby(msg)
    return msg


def queued(controller):
    return [m for q in controller.worker_out_messages.values() for m in q]


def test_colocated_shards_batch_into_one_message(controller):
    register(controller, "w1", ["a.bcolzs", "b.bcolzs", "c.bcolzs"])
    enqueue_groupby(controller, ["a.bcolzs", "b.bcolzs", "c.bcolzs"])
    msgs = queued(controller)
    assert len(msgs) == 1
    assert msgs[0]["filename"] == ["a.bcolzs", "b.bcolzs", "c.bcolzs"]


def test_split_placement_batches_per_worker_set(controller):
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    register(controller, "w2", ["c.bcolzs"])
    enqueue_groupby(controller, ["a.bcolzs", "b.bcolzs", "c.bcolzs"])
    names = sorted(
        str(m["filename"]) for m in queued(controller)
    )
    assert names == ["['a.bcolzs', 'b.bcolzs']", "c.bcolzs"]


def test_duplicate_filenames_deduplicated(controller):
    register(controller, "w1", ["a.bcolzs"])
    enqueue_groupby(controller, ["a.bcolzs", "a.bcolzs", "a.bcolzs"])
    (msg,) = queued(controller)
    assert msg["filename"] == "a.bcolzs"
    (segment,) = controller.rpc_segments.values()
    assert segment["filenames"] == ["a.bcolzs"]


def test_unservable_batch_resplits_to_per_shard(controller):
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    enqueue_groupby(controller, ["a.bcolzs", "b.bcolzs"])
    # placement changes: the co-locating worker dies, two new (busy) workers
    # each hold one shard
    controller.remove_worker("w1")
    register(controller, "w2", ["a.bcolzs"], busy=True)
    register(controller, "w3", ["b.bcolzs"], busy=True)
    controller.dispatch_pending()
    msgs = queued(controller)
    assert sorted(m["filename"] for m in msgs) == ["a.bcolzs", "b.bcolzs"]
    parent_tokens = {m["parent_token"] for m in msgs}
    assert len(parent_tokens) == 1  # still the same query
    assert len({m["token"] for m in msgs}) == 2  # fresh per-shard tokens


def test_vanished_file_aborts_parent_fast(controller):
    register(controller, "w1", ["a.bcolzs"])
    enqueue_groupby(controller, ["a.bcolzs"])
    controller.remove_worker("w1")  # file gone from every worker
    assert controller.rpc_segments
    controller.dispatch_pending()
    assert not queued(controller)
    assert not controller.rpc_segments  # aborted, client answered


def test_batch_respects_non_mergeable_ops(controller):
    """Run counts are per file, so a sorted_count_distinct keeps per-shard
    dispatch.  A count_distinct whose files one worker holds is one group,
    flagged as the whole answer; where the placement splits its files, per
    shard, and no payload is flagged."""
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    register(controller, "w2", ["c.bcolzs"])

    def dispatched(files, op):
        controller.worker_out_messages.clear()
        msg = RPCMessage({"payload": "groupby", "token": os.urandom(2).hex()})
        msg.set_args_kwargs([files, ["k"], [["v", op, "v"]], []], {})
        controller.rpc_groupby(msg)
        return sorted(
            (str(m["filename"]), bool(m.get("sole_payload")))
            for m in queued(controller)
        )

    ab = ["a.bcolzs", "b.bcolzs"]
    assert dispatched(ab, "sorted_count_distinct") == [
        ("a.bcolzs", False), ("b.bcolzs", False),
    ]
    assert dispatched(ab, "count_distinct") == [(str(ab), True)]
    assert dispatched(ab + ["c.bcolzs"], "count_distinct") == [
        ("a.bcolzs", False), ("b.bcolzs", False), ("c.bcolzs", False),
    ]


# -- download failure path --------------------------------------------------


class _Worker:
    """Minimal downloader-shaped stand-in for download.py functions."""

    def __init__(self, store, data_dir):
        self.store = store
        self.data_dir = data_dir
        self.node_name = "testnode"
        self.failed = []
        import logging as _l

        self.logger = _l.getLogger("test.download")

    def download_file(self, ticket, fileurl):
        raise IOError("bucket on fire")

    def run_download(self, ticket, fileurl, lock):
        """Synchronous version of DownloaderNode.run_download (no pool)."""
        try:
            self.download_file(ticket, fileurl)
        except Exception as exc:
            self.fail_ticket(ticket, fileurl, str(exc))
        finally:
            lock.release()

    def fail_ticket(self, ticket, fileurl, error):
        from bqueryd_tpu import download

        download.fail_ticket(self, ticket, fileurl, error)
        self.failed.append((ticket, fileurl, error))


def test_failed_download_poisons_ticket(tmp_path, mem_store_url):
    from bqueryd_tpu import download
    from bqueryd_tpu.coordination import coordination_store

    store = coordination_store(mem_store_url)
    worker = _Worker(store, str(tmp_path))
    ticket = "t1"
    download.set_progress(store, "testnode", ticket, "s3://b/f.zip", -1)
    download.set_progress(store, "othernode", ticket, "s3://b/f.zip", "DONE")

    download.check_downloads(worker)
    assert worker.failed and worker.failed[0][0] == ticket
    err = download.ticket_error(store, ticket)
    assert err and err.startswith("ERROR")
    # slots survive (observable state), underscore-free reason parses cleanly
    assert "_" not in err.partition(":")[2]

    # movebcolz must NOT activate and must clear its staging
    staging = download.incoming_dir(worker, ticket)
    os.makedirs(os.path.join(staging, "f.bcolz"), exist_ok=True)
    download.check_moves(worker)
    assert not os.path.exists(staging)
    assert not os.path.exists(os.path.join(worker.data_dir, "f.bcolz"))

    # a second poll cycle skips the ERROR slot instead of retrying forever
    worker.failed.clear()
    download.check_downloads(worker)
    assert not worker.failed


def test_ticket_error_released_to_waiting_client(controller):
    """TicketDoneMessage with an error must answer wait=True clients with the
    failure, not DONE."""
    from bqueryd_tpu.messages import TicketDoneMessage

    controller.rpc_segments["ticket_t9"] = {
        "client_token": "00",
        "msg": RPCMessage({"payload": "download", "token": "00"}),
        "created": time.time(),
    }
    sent = []
    controller.reply_rpc_message = lambda tok, m: sent.append((tok, m))
    controller.release_ticket_waiters("t9", "bucket on fire")
    ((_tok, reply),) = sent
    assert reply["msg_type"] == "error"
    assert "bucket on fire" in reply["payload"]
    assert "ticket_t9" not in controller.rpc_segments


def test_concurrent_clients_all_get_correct_results(tmp_path, mem_store_url):
    """Four client threads interleaving two query shapes against a
    two-worker cluster: every reply must be the bit-correct answer for ITS
    query (exercises the affinity queues, busy/done flow control, and sink
    bookkeeping under real concurrency)."""
    import logging
    import threading

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode
    from tests.conftest import wait_until

    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(0)
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 9, 20_000).astype(np.int64),
            "h": rng.integers(0, 4, 20_000).astype(np.int64),
            "v": rng.integers(-(10**10), 10**10, 20_000).astype(np.int64),
            "f": rng.random(20_000).astype(np.float32) * 50,
        }
    )
    for i in range(4):
        ctable.fromdataframe(
            df.iloc[i::4], str(tmp_path / f"s{i}.bcolzs")
        )
    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.2,
    )
    workers = [
        WorkerNode(
            coordination_url=mem_store_url, data_dir=str(tmp_path),
            loglevel=logging.WARNING, restart_check=False,
            heartbeat_interval=0.2, poll_timeout=0.1,
        )
        for _ in range(2)
    ]
    nodes = [controller] + workers
    threads = [threading.Thread(target=n.go, daemon=True) for n in nodes]
    for t in threads:
        t.start()
    try:
        wait_until(
            lambda: len(controller.files_map) >= 4, desc="shards registered"
        )
        shards = [f"s{i}.bcolzs" for i in range(4)]
        exp_sum = df.groupby("g")["v"].sum().sort_index().tolist()
        exp_multi = (
            df[df.f > 25].groupby(["g", "h"])["v"].sum().sort_index().tolist()
        )
        errors = []

        def client(ci):
            try:
                rpc = RPC(
                    coordination_url=mem_store_url, timeout=60,
                    loglevel=logging.WARNING,
                )
                for q in range(8):
                    if (ci + q) % 2 == 0:
                        got = rpc.groupby(
                            shards, ["g"], [["v", "sum", "s"]], []
                        ).sort_values("g")
                        assert got["s"].tolist() == exp_sum
                    else:
                        got = rpc.groupby(
                            shards, ["g", "h"], [["v", "sum", "s"]],
                            [["f", ">", 25.0]],
                        ).sort_values(["g", "h"])
                        assert got["s"].tolist() == exp_multi
            except Exception as exc:  # surfaced below with client id
                errors.append(f"client {ci}: {exc!r}")

        cts = [
            threading.Thread(target=client, args=(i,)) for i in range(4)
        ]
        for t in cts:
            t.start()
        for t in cts:
            t.join(timeout=120)
        assert not errors, errors
    finally:
        for n in nodes:
            n.running = False
        for t in threads:
            t.join(timeout=5)


def test_wide_fanout_64_shards_two_workers(tmp_path, mem_store_url):
    from tests.conftest import wait_until

    """Scale check on the fan-out machinery: 64 shards served by 2 workers
    through one query must batch into shard groups, keep the sink's
    bookkeeping straight, and produce the pandas answer — the widest
    shard count in the suite (the bench uses 10)."""
    import logging
    import os
    import threading
    import time

    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(11)
    frames = []
    for i in range(64):
        df = pd.DataFrame(
            {
                "g": rng.integers(0, 9, 500).astype(np.int64),
                "v": rng.integers(-(2**45), 2**45, 500).astype(np.int64),
            }
        )
        frames.append(df)
        ctable.fromdataframe(df, str(tmp_path / f"w_{i:02d}.bcolzs"))
    names = [f"w_{i:02d}.bcolzs" for i in range(64)]

    url = mem_store_url
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.2,
    )
    workers = [
        WorkerNode(
            coordination_url=url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.05,
        )
        for _ in range(2)
    ]
    threads = [
        threading.Thread(target=n.go, daemon=True)
        for n in [controller] + workers
    ]
    for t in threads:
        t.start()
    try:
        wait_until(
            lambda: len(controller.files_map) >= 64,
            timeout=60,
            desc="64 shards registered",
        )
        rpc = RPC(
            coordination_url=url, timeout=120, loglevel=logging.WARNING
        )
        got = rpc.groupby(names, ["g"], [["v", "sum", "s"]], [])
        got = got.sort_values("g").reset_index(drop=True)
        expected = (
            pd.concat(frames).groupby("g")["v"].sum().reset_index(name="s")
        )
        assert got["g"].tolist() == expected["g"].tolist()
        assert got["s"].tolist() == expected["s"].tolist()
    finally:
        for n in [controller] + workers:
            n.stop()
        for t in threads:
            t.join(timeout=10)
