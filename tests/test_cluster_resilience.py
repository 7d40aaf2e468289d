"""Failure-path and control-plane coverage the reference never had.

SURVEY.md §4 lists the reference's test blind spots: multi-controller
peering, worker death/cull, execute_code, and the memory watchdog.  These
tests close them, using the same threads-as-nodes topology as
tests/test_rpc_cluster.py (the reference's own fixture style, reference
tests/test_simple_rpc.py:42-74) with condition polling instead of sleeps.
"""

import logging
import os
import threading

import pytest

from conftest import wait_until


def _start(*nodes):
    threads = [
        threading.Thread(target=node.go, daemon=True) for node in nodes
    ]
    for t in threads:
        t.start()
    return threads


def _stop(nodes, threads):
    for node in nodes:
        if node is not None:  # a test may fail before creating late nodes
            node.running = False
    for t in threads:
        t.join(timeout=5)


@pytest.fixture
def small_cluster(tmp_path, mem_store_url):
    """One controller + one calc worker, fast heartbeats, no data files."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
        dead_worker_timeout=10.0,
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
    wait_until(lambda: controller.worker_map, desc="worker registration")
    rpc = RPC(
        coordination_url=mem_store_url, timeout=30, loglevel=logging.WARNING
    )
    yield {"rpc": rpc, "controller": controller, "worker": worker}
    _stop([controller, worker], threads)


def test_execute_code_roundtrip(small_cluster, monkeypatch):
    """The reference's deliberate remote-execution verb (reference
    bqueryd/worker.py:250-267) — here gated behind an explicit env flag."""
    monkeypatch.setenv("BQUERYD_TPU_ENABLE_EXECUTE_CODE", "1")
    result = small_cluster["rpc"].execute_code(
        function="math.gcd", args=[12, 18], wait=True
    )
    assert result == 6


def test_execute_code_direct_kwargs(small_cluster, monkeypatch):
    """Keywords other than function/args/kwargs/wait go to the function."""
    monkeypatch.setenv("BQUERYD_TPU_ENABLE_EXECUTE_CODE", "1")
    result = small_cluster["rpc"].execute_code(
        function="fnmatch.fnmatch", name="shard_3.bcolzs", pat="shard_*",
        wait=True,
    )
    assert result is True


def test_execute_code_disabled_by_default(small_cluster, monkeypatch):
    from bqueryd_tpu.rpc import RPCError

    monkeypatch.delenv("BQUERYD_TPU_ENABLE_EXECUTE_CODE", raising=False)
    with pytest.raises(RPCError, match="execute_code disabled"):
        small_cluster["rpc"].execute_code(
            function="math.gcd", args=[12, 18], wait=True
        )


def test_dead_worker_culled_and_rejoins(tmp_path, mem_store_url):
    """A worker that dies silently (no StopMessage) is culled after
    dead_worker_timeout and dropped from files_map (reference
    bqueryd/controller.py:548-552); a later heartbeat re-registers it."""
    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = pd.DataFrame({"g": np.arange(10), "v": np.arange(10)})
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"))

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=0.5,
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
    try:
        wait_until(
            lambda: "t.bcolzs" in controller.files_map, desc="registration"
        )
        # crash the worker: no StopMessage, no heartbeats, just silence
        worker.stop = lambda: None
        worker.running = False
        wait_until(
            lambda: not controller.worker_map,
            timeout=10,
            desc="silent worker culled",
        )
        assert not controller.files_map.get("t.bcolzs")

        # a restarted worker (fresh identity, same files) is picked up again
        worker2 = WorkerNode(
            coordination_url=mem_store_url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.1,
            poll_timeout=0.05,
        )
        threads += _start(worker2)
        wait_until(
            lambda: "t.bcolzs" in controller.files_map
            and controller.files_map["t.bcolzs"],
            desc="replacement worker registered",
        )
    finally:
        _stop([controller, worker, locals().get("worker2")], threads)


def test_controller_peering_and_killall(tmp_path, mem_store_url):
    """Two controllers on one store discover each other via the membership
    set + gossip (reference bqueryd/controller.py:77-106) and killall fans
    out to peers (reference bqueryd/controller.py:510-516)."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC

    a = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path / "a"),
        heartbeat_interval=0.1,
    )
    b = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path / "b"),
        heartbeat_interval=0.1,
    )
    threads = _start(a, b)
    try:
        wait_until(
            lambda: b.address in a.others and a.address in b.others,
            desc="mutual peer discovery",
        )
        rpc = RPC(
            coordination_url=mem_store_url,
            address=a.address,
            timeout=30,
            loglevel=logging.WARNING,
        )
        info = rpc.info()
        assert b.address in info["others"]
        rpc.killall()
        wait_until(
            lambda: not a.running and not b.running,
            desc="killall reached both controllers",
        )
        # both unregistered from the membership set
        from bqueryd_tpu import REDIS_SET_KEY
        from bqueryd_tpu.coordination import coordination_store

        wait_until(
            lambda: not coordination_store(mem_store_url).smembers(
                REDIS_SET_KEY
            ),
            desc="membership set emptied",
        )
    finally:
        _stop([a, b], threads)


def test_busy_worker_outliving_dead_timeout_not_culled(tmp_path, mem_store_url):
    """Work that outlives dead_worker_timeout still completes: the liveness
    thread keeps heartbeating while handle_work blocks the event loop, so the
    controller must neither cull the busy worker nor drop its files_map
    entries mid-query (the round-1 benchmark failure: 'file(s) no longer on
    any worker')."""
    import time as time_mod

    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = pd.DataFrame(
        {"g": np.arange(20) % 4, "v": np.arange(20, dtype=np.int64)}
    )
    ctable.fromdataframe(df, str(tmp_path / "slow.bcolzs"))

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=1.0,   # far below the query's runtime
        dispatch_timeout=30.0,
    )
    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.3,
        poll_timeout=0.05,
    )
    # make the query block the worker's event loop well past the cull timeout
    orig_handle_work = worker.handle_work

    def slow_handle_work(msg):
        time_mod.sleep(2.5)
        return orig_handle_work(msg)

    worker.handle_work = slow_handle_work

    threads = _start(controller, worker)
    try:
        wait_until(
            lambda: "slow.bcolzs" in controller.files_map, desc="registration"
        )
        rpc = RPC(
            coordination_url=mem_store_url, timeout=30, loglevel=logging.WARNING
        )
        result = rpc.groupby(
            ["slow.bcolzs"], ["g"], [["v", "sum", "v_sum"]], []
        )
        got = dict(zip(result["g"].tolist(), result["v_sum"].tolist()))
        expect = df.groupby("g")["v"].sum().to_dict()
        assert got == expect
        # the worker survived: still registered, file still advertised
        assert worker.worker_id in controller.worker_map
        assert "slow.bcolzs" in controller.files_map
    finally:
        _stop([controller, worker], threads)


def test_shard_retry_lands_on_replacement_worker(tmp_path, mem_store_url):
    """A worker that dies mid-flight (work dispatched, no reply, silence)
    gets its shard requeued after dispatch_timeout and the retry completes on
    a replacement worker — the dispatch-tracking behaviour the reference left
    as a TODO (reference bqueryd/controller.py:265)."""
    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = pd.DataFrame(
        {"g": np.arange(30) % 3, "v": np.arange(30, dtype=np.int64)}
    )
    ctable.fromdataframe(df, str(tmp_path / "r.bcolzs"))

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=1.0,
        dispatch_timeout=1.5,
    )
    worker_a = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.2,
        poll_timeout=0.05,
    )
    a_got_work = threading.Event()

    def crash_mid_work(msg):
        """Simulate a hard crash: stop heartbeating, never reply."""
        a_got_work.set()
        worker_a.stop = lambda: None       # no StopMessage: silent death
        worker_a._hb_stop.set()            # liveness thread dies too
        worker_a.running = False
        return None

    worker_a.handle_work = crash_mid_work

    worker_b = None
    threads = _start(controller, worker_a)
    try:
        wait_until(
            lambda: "r.bcolzs" in controller.files_map, desc="registration"
        )
        rpc = RPC(
            coordination_url=mem_store_url, timeout=45, loglevel=logging.WARNING
        )
        result_box = {}

        def ask():
            result_box["df"] = rpc.groupby(
                ["r.bcolzs"], ["g"], [["v", "sum", "v_sum"]], []
            )

        asker = threading.Thread(target=ask, daemon=True)
        asker.start()
        wait_until(a_got_work.is_set, desc="worker A received the shard")
        # bring up the replacement holding the same shard file
        worker_b = WorkerNode(
            coordination_url=mem_store_url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.05,
        )
        threads += _start(worker_b)
        asker.join(timeout=40)
        assert not asker.is_alive(), "query never completed after retry"
        result = result_box["df"]
        got = dict(zip(result["g"].tolist(), result["v_sum"].tolist()))
        assert got == df.groupby("g")["v"].sum().to_dict()
        # the retry really happened on B: A is gone from the worker map
        wait_until(
            lambda: worker_a.worker_id not in controller.worker_map,
            desc="dead worker culled",
        )
        assert worker_b.worker_id in controller.worker_map
    finally:
        _stop([controller, worker_a, worker_b], threads)


def test_memory_watchdog_stops_over_limit_worker(tmp_path, mem_store_url):
    """RSS above the limit (and caches shed without relief) stops the loop so
    a supervisor can restart the process (reference bqueryd/worker.py:232-241,
    2 GB cap)."""
    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=True,
        memory_limit_mb=1,  # any real process RSS exceeds this
    )
    worker.running = True
    worker._check_mem()
    assert worker.running is False
    worker.socket.close()


def test_memory_watchdog_unmeasurable_shed_still_stops(
    tmp_path, mem_store_url, monkeypatch
):
    """If the post-shed RSS read fails, the pre-shed over-limit reading wins
    and the worker still restarts (no silent disable of the safety net)."""
    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=True,
        memory_limit_mb=1,
    )
    monkeypatch.setattr(worker, "_shed_caches", lambda: None)
    worker.running = True
    worker._check_mem()
    assert worker.running is False
    worker.socket.close()


def test_memory_watchdog_shed_recovery_keeps_running(
    tmp_path, mem_store_url, monkeypatch
):
    """If shedding caches brings RSS back under the limit, the worker keeps
    serving instead of restarting."""
    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=True,
        memory_limit_mb=1,
    )
    monkeypatch.setattr(worker, "_shed_caches", lambda: 0.5)
    worker.running = True
    worker._check_mem()
    assert worker.running is True
    worker.socket.close()


def test_memory_watchdog_excludes_the_accelerator_runtime(
    tmp_path, mem_store_url, monkeypatch
):
    """A process that has initialised the TPU backend shows ~13.5 GB of RSS
    before serving a row (device mappings + premapped staging buffers; seen
    on the v5e, where the 2048 MB default stopped the worker after its
    first query).  The limit bounds what the WORKER holds above that."""
    import psutil

    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=True,
        memory_limit_mb=2048,
    )
    rss = {"mb": 14_611}
    monkeypatch.setattr(
        psutil.Process, "memory_info",
        lambda self: type("m", (), {"rss": int(rss["mb"] * 1e6)})(),
    )
    monkeypatch.setattr(worker, "_shed_caches", lambda: rss["mb"])
    worker.running = True
    worker._check_mem()
    assert worker.running is False, "raw RSS over the limit stops it"
    worker._runtime_rss_mb = 13_557.0   # measured around backend init
    worker.running = True
    worker._check_mem()
    assert worker.running is True, "1054 MB of its own is under the limit"
    rss["mb"] = 13_557 + 2_500
    worker._check_mem()
    assert worker.running is False, "2500 MB of its own is not"
    worker.socket.close()


def test_two_controllers_both_get_heartbeats_during_long_work(
    tmp_path, mem_store_url
):
    """Per-controller ADDRESSED heartbeat delivery: with two controllers and
    the worker's event loop blocked in a long handle_work, BOTH controllers'
    last_seen must keep refreshing (a single shared DEALER round-robins its
    sends across peers, making per-controller delivery probabilistic)."""
    import time as time_mod

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    a = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path / "a"),
        heartbeat_interval=0.05,
        dead_worker_timeout=10.0,
    )
    b = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path / "b"),
        heartbeat_interval=0.05,
        dead_worker_timeout=10.0,
    )
    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.1,
        poll_timeout=0.05,
    )
    threads = _start(a, b, worker)
    try:
        wid = worker.worker_id
        wait_until(
            lambda: wid in a.worker_map and wid in b.worker_map,
            desc="worker registered on both controllers",
        )
        rpc = RPC(
            coordination_url=mem_store_url,
            address=a.address,
            timeout=30,
            loglevel=logging.WARNING,
        )
        done = threading.Event()

        def ask():
            rpc.sleep(2.0)
            done.set()

        threading.Thread(target=ask, daemon=True).start()
        wait_until(
            lambda: a.worker_map.get(wid, {}).get("busy"),
            desc="worker busy in long work",
        )
        # while the event loop is blocked, sample last_seen on BOTH
        seen_a0 = a.worker_map[wid]["last_seen"]
        seen_b0 = b.worker_map[wid]["last_seen"]
        time_mod.sleep(0.6)  # several heartbeat ticks
        assert not done.is_set(), "work finished too early to measure"
        assert a.worker_map[wid]["last_seen"] > seen_a0
        assert b.worker_map[wid]["last_seen"] > seen_b0
        wait_until(done.is_set, desc="sleep verb completed")
    finally:
        _stop([a, b, worker], threads)


def test_hb_only_adoption_is_busy_until_main_socket_speaks(mem_store_url):
    """A worker adopted from a liveness-only heartbeat (controller restarted
    while the worker is deep in handle_work) must not be dispatchable: the
    ROUTER may only hold a route for the '.hb' identity, and dispatching
    would EHOSTUNREACH -> remove -> re-adopt in a loop that burns the
    shard's retry budget.  The first main-socket WRM clears the flag."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import WorkerRegisterMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        wrm = WorkerRegisterMessage(
            {
                "worker_id": "w1",
                "workertype": "calc",
                "data_files": ["s.bcolzs"],
                "liveness_only": True,
            }
        )
        controller.handle_worker(b"w1.hb", wrm)
        info = controller.worker_map["w1"]
        assert info["busy"] is True and info.get("hb_only")
        assert "s.bcolzs" in controller.files_map
        # not dispatchable while hb_only
        assert controller.find_free_worker(filename="s.bcolzs") is None

        # main-socket WRM proves the route: busy resets, flag clears
        full = WorkerRegisterMessage(
            {
                "worker_id": "w1",
                "workertype": "calc",
                "data_files": ["s.bcolzs"],
            }
        )
        controller.handle_worker(b"w1", full)
        info = controller.worker_map["w1"]
        assert info["busy"] is False and not info.get("hb_only")
        assert controller.find_free_worker(filename="s.bcolzs") == "w1"
    finally:
        controller.socket.close()


def test_unroutable_dispatch_does_not_charge_retry_budget(mem_store_url):
    """An EHOSTUNREACH send (missing ROUTER route) requeues the shard WITHOUT
    incrementing _retries: routing facts are not evidence against the shard,
    and charging them aborts the query after MAX_DISPATCH_RETRIES re-adopts."""
    from bqueryd_tpu.controller import MAX_DISPATCH_RETRIES, ControllerNode
    from bqueryd_tpu.messages import CalcMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        msg = CalcMessage(
            {
                "payload": "groupby",
                "token": "t1",
                "parent_token": "p1",
                "filename": "s.bcolzs",
                "_retries": MAX_DISPATCH_RETRIES,  # budget already exhausted
            }
        )
        # no such route on the ROUTER -> ZMQError (ROUTER_MANDATORY) path
        controller._send_to_worker("no-such-worker", msg)
        queue = controller.worker_out_messages.get(None, [])
        assert [m.get("token") for m in queue] == ["t1"], (
            "shard must be requeued, not aborted"
        )
        assert queue[0].get("_retries") == MAX_DISPATCH_RETRIES
    finally:
        controller.socket.close()


def test_hb_only_adoption_expires_after_hard_timeout(mem_store_url):
    """A worker whose main loop is permanently wedged but whose heartbeat
    thread stays alive must not block its shards forever: the adoption
    expires after dispatch_hard_timeout and the worker is reclaimed, letting
    queries fail fast instead of hanging to the client timeout."""
    import time as time_mod

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import WorkerRegisterMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent", dispatch_hard_timeout=0.2,
        dispatch_timeout=0.1,
    )
    try:
        wrm = WorkerRegisterMessage(
            {
                "worker_id": "w1",
                "workertype": "calc",
                "data_files": ["s.bcolzs"],
                "liveness_only": True,
            }
        )
        controller.handle_worker(b"w1.hb", wrm)
        assert "w1" in controller.worker_map
        time_mod.sleep(0.25)
        # heartbeats keep arriving (last_seen fresh) but main loop is silent
        controller.handle_worker(b"w1.hb", wrm.copy())
        controller.free_dead_workers()
        assert "w1" not in controller.worker_map
        assert "s.bcolzs" not in controller.files_map
        # the still-ticking heartbeat thread must NOT re-adopt it (quarantine)
        controller.handle_worker(b"w1.hb", wrm.copy())
        assert "w1" not in controller.worker_map
        # ...until the main socket proves the loop recovered
        full = WorkerRegisterMessage(
            {
                "worker_id": "w1",
                "workertype": "calc",
                "data_files": ["s.bcolzs"],
            }
        )
        controller.handle_worker(b"w1", full)
        assert "w1" in controller.worker_map
        controller.handle_worker(b"w1.hb", wrm.copy())  # liveness works again
        assert controller.worker_map["w1"]["last_seen"]
    finally:
        controller.socket.close()


def test_stop_is_a_shutdown_request_and_deregisters(
    tmp_path, mem_store_url, monkeypatch
):
    """Calling stop() from OUTSIDE the node loop (tests, embedders,
    signal handlers) must end the loop promptly and deregister the
    controller from the coordination store — previously the loop kept
    polling the closed socket forever and external teardown hung on
    thread joins."""
    import logging
    import threading
    import time

    import bqueryd_tpu
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.coordination import coordination_store
    from bqueryd_tpu.worker import WorkerNode

    monkeypatch.setenv("BQUERYD_TPU_WARMUP", "0")
    url = mem_store_url
    controller = ControllerNode(
        coordination_url=url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
    )
    worker = WorkerNode(
        coordination_url=url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.1,
        poll_timeout=0.05,
    )
    threads = [
        threading.Thread(target=n.go, daemon=True)
        for n in (controller, worker)
    ]
    for t in threads:
        t.start()
    store = coordination_store(url)
    wait_until(
        lambda: store.smembers(bqueryd_tpu.REDIS_SET_KEY),
        desc="controller registration",
    )
    # stop() before go() starts is a different race; wait the loops in
    wait_until(
        lambda: controller.running and worker.running, desc="loops running"
    )

    t0 = time.time()
    worker.stop()
    controller.stop()
    for t in threads:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in threads), "node loops did not exit"
    assert time.time() - t0 < 5, "external stop() took too long"
    assert store.smembers(bqueryd_tpu.REDIS_SET_KEY) == set()


def test_groupby_through_either_controller(tmp_path, mem_store_url, monkeypatch):
    """A worker registers with every controller in the store; the same
    query asked through EACH controller must produce the same
    pandas-checked answer (the reference's operational model: clients
    may point at any controller, reference bqueryd/rpc.py:62-78)."""
    import logging
    import threading

    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    monkeypatch.setenv("BQUERYD_TPU_WARMUP", "0")
    rng = np.random.default_rng(21)
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 6, 4_000).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, 4_000).astype(np.int64),
        }
    )
    ctable.fromdataframe(df, str(tmp_path / "s0.bcolzs"))
    expected = df.groupby("g")["v"].sum()

    controllers = [
        ControllerNode(
            coordination_url=mem_store_url,
            loglevel=logging.WARNING,
            runfile_dir=str(tmp_path),
            heartbeat_interval=0.1,
        )
        for _ in range(2)
    ]
    worker = WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
        heartbeat_interval=0.1,
        poll_timeout=0.05,
    )
    nodes = controllers + [worker]
    threads = [
        threading.Thread(target=n.go, daemon=True) for n in nodes
    ]
    for t in threads:
        t.start()
    try:
        for c in controllers:
            wait_until(
                lambda c=c: "s0.bcolzs" in c.files_map,
                desc=f"shard registered at {c.address}",
            )
        results = []
        for c in controllers:
            rpc = RPC(
                address=c.address,
                coordination_url=mem_store_url,
                loglevel=logging.WARNING,
                timeout=30,
            )
            got = rpc.groupby(
                ["s0.bcolzs"], ["g"], [["v", "sum", "s"]], []
            )
            got = got.sort_values("g").reset_index(drop=True)
            assert got["g"].tolist() == expected.index.tolist()
            assert got["s"].tolist() == expected.tolist()
            results.append(got)
        pd.testing.assert_frame_equal(results[0], results[1])
    finally:
        for n in nodes:
            n.stop()
        for t in threads:
            t.join(timeout=5)


def test_concurrent_clients_survive_worker_churn(tmp_path, mem_store_url):
    """N concurrent clients with mixed shard affinities keep getting exact
    answers while workers are hard-killed and replaced mid-stream — the
    redesign's dispatch tracking (tracked inflight + bounded retries +
    cull/requeue) under real concurrency, which the reference (retry TODO at
    reference bqueryd/controller.py:265) never attempted.

    Asserts: no lost replies (every call returns), bit-exact sums on every
    reply (any retry that re-merged, double-dispatched, or mixed stale
    partials into a result would corrupt them), bounded retries (every
    requeue stays under MAX_DISPATCH_RETRIES, none poisoned), churn really
    overlapped the query stream, and no leaked inflight entries once the
    stream drains."""
    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import MAX_DISPATCH_RETRIES, ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(42)
    n_shards, rows = 6, 400
    frames = {}
    for i in range(n_shards):
        df = pd.DataFrame(
            {
                "g": rng.integers(0, 5, rows).astype(np.int64),
                "v": rng.integers(-(2**40), 2**40, rows).astype(np.int64),
            }
        )
        frames[f"churn_{i}.bcolzs"] = df
        ctable.fromdataframe(df, str(tmp_path / f"churn_{i}.bcolzs"))

    # mixed affinities: each client sticks to its own file subset
    subsets = [
        [f"churn_{i}.bcolzs" for i in idx]
        for idx in ([0, 1], [2, 3], [4, 5], [0, 2, 4], [1, 3, 5],
                    list(range(n_shards)))
    ]
    expected = {
        tuple(sub): pd.concat([frames[f] for f in sub])
        .groupby("g")["v"].sum().to_dict()
        for sub in map(tuple, subsets)
    }

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=1.0,
        dispatch_timeout=1.5,
    )
    requeues = []
    real_requeue = controller._requeue

    def counting_requeue(entry, charge_retry=True, **kw):
        requeues.append(entry.get("retries", 0))
        return real_requeue(entry, charge_retry=charge_retry, **kw)

    controller._requeue = counting_requeue

    def spawn_worker():
        return WorkerNode(
            coordination_url=mem_store_url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.05,
        )

    workers = [spawn_worker() for _ in range(3)]
    threads = _start(controller, *workers)
    all_nodes = [controller] + list(workers)
    try:
        wait_until(
            lambda: len(controller.files_map.get("churn_0.bcolzs", ())) >= 1
            and len(controller.worker_map) >= 3,
            desc="initial registration",
        )

        stop_churn = threading.Event()
        errors = []
        results = []  # (subset, got_dict) — appended under a lock
        res_lock = threading.Lock()

        def client(sub, n_queries=4):
            try:
                rpc = RPC(
                    coordination_url=mem_store_url,
                    timeout=60,
                    loglevel=logging.WARNING,
                    retries=3,
                )
                for _ in range(n_queries):
                    df = rpc.groupby(
                        list(sub), ["g"], [["v", "sum", "s"]], []
                    )
                    got = dict(zip(df["g"].tolist(), df["s"].tolist()))
                    with res_lock:
                        results.append((tuple(sub), got))
            except Exception as exc:  # lost reply shows up here
                errors.append((sub, repr(exc)))

        kills_mid_stream = []

        def churn():
            """Hard-kill a worker mid-stream, start a replacement, twice."""
            try:
                for round_i in range(2):
                    if stop_churn.wait(0.6):
                        return
                    victim = workers[round_i]
                    # silent death: no goodbye StopMessage, no replies —
                    # but the loop thread still runs its own socket
                    # teardown on exit (stop() itself must stay intact)
                    victim.send = lambda *a, **k: None
                    victim._hb_stop.set()
                    victim.running = False
                    kills_mid_stream.append(
                        any(t.is_alive() for t in clients)
                    )
                    replacement = spawn_worker()
                    workers.append(replacement)
                    all_nodes.append(replacement)
                    threads.extend(_start(replacement))
            except Exception as exc:
                errors.append(("churn", repr(exc)))

        clients = [
            threading.Thread(target=client, args=(sub,), daemon=True)
            for sub in subsets
        ]
        churner = threading.Thread(target=churn, daemon=True)
        for t in clients:
            t.start()
        churner.start()
        for t in clients:
            t.join(timeout=120)
            assert not t.is_alive(), "client wedged: lost reply"
        stop_churn.set()
        churner.join(timeout=10)

        assert not errors, f"client/churn failures: {errors}"
        # the scenario must actually have happened: both kills landed while
        # clients were still querying (else this test silently stops
        # covering churn — tune the client/churn pacing if this fires)
        assert kills_mid_stream == [True, True], kills_mid_stream
        assert len(results) == len(subsets) * 4, "lost replies"
        for sub, got in results:
            assert got == expected[sub], f"wrong/duplicated sums for {sub}"
        # bounded retries: every requeue stayed under budget (none poisoned)
        assert all(r < MAX_DISPATCH_RETRIES for r in requeues), requeues
        # generous bound: kills can requeue at most the shards each victim
        # held inflight, twice, plus timeout-driven strays
        assert len(requeues) <= 4 * n_shards, requeues
        wait_until(
            lambda: not controller.inflight, desc="inflight drained"
        )
    finally:
        _stop(all_nodes, threads)


# ---------------------------------------------------------------------------
# fault-plan-driven chaos cases (PR 8): the failover paths exercised on
# purpose through bqueryd_tpu.chaos instead of hand-rolled monkeypatching
# ---------------------------------------------------------------------------

def _replica_cluster(tmp_path, mem_store_url, df_seed=11, n_workers=2,
                     dispatch_timeout=1.5, dispatch_hard_timeout=None,
                     shards=("rep_0.bcolzs", "rep_1.bcolzs")):
    """Controller + N workers ALL holding the same shard files (replica
    topology), small timeouts so failover happens in test time."""
    import numpy as np
    import pandas as pd

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(df_seed)
    frames = {}
    for name in shards:
        df = pd.DataFrame(
            {
                "g": rng.integers(0, 4, 300).astype(np.int64),
                "v": rng.integers(-(2**40), 2**40, 300).astype(np.int64),
            }
        )
        frames[name] = df
        ctable.fromdataframe(df, str(tmp_path / name))

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=1.0,
        dispatch_timeout=dispatch_timeout,
        dispatch_hard_timeout=dispatch_hard_timeout,
    )
    workers = [
        WorkerNode(
            coordination_url=mem_store_url,
            data_dir=str(tmp_path),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.05,
        )
        for _ in range(n_workers)
    ]
    threads = _start(controller, *workers)
    wait_until(
        lambda: all(
            len(controller.files_map.get(name, ())) >= n_workers
            for name in shards
        ),
        desc="every shard advertised by every worker (replica topology)",
    )
    import pandas as pd

    expected = (
        pd.concat(frames.values()).groupby("g")["v"].sum().to_dict()
    )
    return controller, workers, threads, expected, list(shards)


def _ask_sum(mem_store_url, shards, timeout=45):
    from bqueryd_tpu.rpc import RPC

    rpc = RPC(
        coordination_url=mem_store_url, timeout=timeout,
        loglevel=logging.WARNING,
    )
    df = rpc.groupby(list(shards), ["g"], [["v", "sum", "s"]], [])
    return rpc, dict(zip(df["g"].tolist(), df["s"].tolist()))


def test_die_after_ack_fails_over_to_replica_holder(tmp_path, mem_store_url):
    """A worker that hard-crashes after accepting work (die_after_ack: Busy
    sent, then silence — no reply, no heartbeats) must not fail the query:
    the dispatch timeout re-queues the shard onto the OTHER holder, the
    result is bit-identical, and the failover counter proves the path ran."""
    from bqueryd_tpu import chaos

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        chaos.arm({
            "seed": 1,
            "faults": [{
                "site": "worker.execute",
                "action": "die_after_ack",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected
        assert controller.counters["failover_dispatches"] >= 1
        assert chaos.injected_total() >= 1
        # exactly one worker died; the survivor still serves
        wait_until(
            lambda: len(controller.worker_map) == 1,
            desc="dead worker culled",
        )
        chaos.disarm()
        _, again = _ask_sum(mem_store_url, shards)
        assert again == expected
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_dag_topk_quantile_fails_over_under_kill_worker(
    tmp_path, mem_store_url
):
    """PR-13 acceptance: an operator-DAG query (top-k + quantile sketch)
    survives the PR-8 kill-worker chaos plan with ZERO failed queries —
    the DAG rides the same dispatch/failover machinery as plain groupbys,
    so the shard re-queues onto the replica holder and the merged answer
    matches the fault-free run exactly."""
    import numpy as np

    from bqueryd_tpu import chaos
    from bqueryd_tpu.rpc import RPC

    controller, workers, threads, _expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    spec = {
        "table": list(shards),
        "groupby": ["g"],
        "aggs": [
            ["v", "sum", "s"],
            ["v", "topk", "t3", {"k": 3}],
            ["v", "quantile", "p50", {"q": 0.5, "alpha": 0.01}],
        ],
    }
    try:
        rpc = RPC(
            coordination_url=mem_store_url, timeout=45,
            loglevel=logging.WARNING,
        )
        baseline = rpc.query(spec)  # fault-free reference run
        chaos.arm({
            "seed": 7,
            "faults": [{
                "site": "worker.execute",
                "action": "die_after_ack",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        got = rpc.query(spec)
        assert chaos.injected_total() >= 1
        assert controller.counters["failover_dispatches"] >= 1
        # zero failed queries: the chaos run answered, and EXACTLY —
        # int sums bit-equal, top-k lists identical, sketch estimates
        # bit-equal (same buckets, same counts, whoever served the shard)
        assert got["g"].tolist() == baseline["g"].tolist()
        assert got["s"].tolist() == baseline["s"].tolist()
        for a, b in zip(got["t3"], baseline["t3"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            got["p50"].to_numpy(), baseline["p50"].to_numpy()
        )
        wait_until(
            lambda: len(controller.worker_map) == 1,
            desc="dead worker culled",
        )
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_batched_dag_fails_over_as_whole_group_under_kill_worker(
    tmp_path, mem_store_url
):
    """PR-15 acceptance: a BATCHED DAG query (top-k + quantile sketch over
    one shard-group CalcMessage, the DAG fast path) survives the kill-worker
    chaos plan with ZERO failed queries — the whole group fails over to the
    replica holder (the PR-8/PR-9 bundle precedent), and the answer —
    including the sketch buckets behind the quantile estimates — is
    bit-equal to the fault-free baseline."""
    import numpy as np

    from bqueryd_tpu import chaos
    from bqueryd_tpu.rpc import RPC

    controller, workers, threads, _expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    spec = {
        "table": list(shards),
        "groupby": ["g"],
        "aggs": [
            ["v", "sum", "s"],
            ["v", "topk", "t3", {"k": 3}],
            ["v", "quantile", "p50", {"q": 0.5, "alpha": 0.01}],
        ],
    }
    try:
        rpc = RPC(
            coordination_url=mem_store_url, timeout=45,
            loglevel=logging.WARNING,
        )
        before = controller.counters["dispatched_shards"]
        baseline = rpc.query(spec)  # fault-free reference run
        # the whole replica-held shard set rode ONE batched CalcMessage
        assert controller.counters["dispatched_shards"] - before == 1
        assert "device" in (rpc.last_call_merge_modes or {}).values()
        chaos.arm({
            "seed": 17,
            "faults": [{
                "site": "worker.execute",
                "action": "die_after_ack",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        got = rpc.query(spec)
        assert chaos.injected_total() >= 1
        assert controller.counters["failover_dispatches"] >= 1
        # zero failed queries, bit-equal to the fault-free run: int sums,
        # top-k lists, and sketch estimates (same buckets, same counts,
        # whichever holder served the whole group)
        assert got["g"].tolist() == baseline["g"].tolist()
        assert got["s"].tolist() == baseline["s"].tolist()
        for a, b in zip(got["t3"], baseline["t3"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            got["p50"].to_numpy(), baseline["p50"].to_numpy()
        )
        wait_until(
            lambda: len(controller.worker_map) == 1,
            desc="dead worker culled",
        )
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_transient_device_fault_retries_on_other_holder(
    tmp_path, mem_store_url
):
    """A transient DeviceBusyError (wedge action: the worker latches
    backend_wedged and raises the transient class) triggers failover to the
    healthy replica holder — the query succeeds, nothing aborts, and the
    wedged worker is still alive (advertised wedged) afterwards."""
    from bqueryd_tpu import chaos

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        chaos.arm({
            "seed": 2,
            "faults": [{
                "site": "worker.execute",
                "action": "wedge",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected
        assert controller.counters["transient_faults"] >= 1
        assert controller.counters["failover_dispatches"] >= 1
        # both workers still registered: a transient fault must not cull
        assert len(controller.worker_map) == 2
        wedged = [w for w in workers if w._chaos_wedged]
        assert len(wedged) == 1
        # the wedge is advertised like the real device-health latch
        wait_until(
            lambda: any(
                controller._worker_wedged.get(w.worker_id)
                for w in wedged
            ),
            desc="wedge advertised in WRMs",
        )
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_autopsy_attributes_failover_backoff(tmp_path, mem_store_url):
    """A query that survives a transient device fault (wedge -> failover to
    the other holder) must autopsy with the recovery visible: a failed
    attempt, a retry whose backoff window appears as a retry_backoff
    segment, and segments that still sum consistently with the wall."""
    from bqueryd_tpu import chaos

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        chaos.arm({
            "seed": 2,
            "faults": [{
                "site": "worker.execute",
                "action": "wedge",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        rpc, got = _ask_sum(mem_store_url, shards)
        assert got == expected
        assert controller.counters["failover_dispatches"] >= 1
        record = rpc.autopsy(rpc.last_trace_id)
        assert record is not None and record["ok"] is True
        # the wedged attempt + the failover retry are both listed; the
        # retry excludes the faulted holder and charged a backoff window
        assert len(record["attempts"]) >= 2
        retries = [a for a in record["attempts"] if a["retries"] >= 1]
        assert retries and retries[0]["backoff_s"] > 0
        failed = [a for a in record["attempts"] if a.get("failed")]
        assert failed and failed[0]["worker"]
        assert record["segments"]["retry_backoff"] > 0
        # the non-overlap invariant holds under faults too
        total = sum(record["segments"].values()) + record["unattributed_s"]
        assert abs(total - record["wall_s"]) < 1e-3
        # recovery time is attributed, not mystery wall
        assert record["coverage"] >= 0.8
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_duplicated_reply_is_deduped_by_query_token(tmp_path, mem_store_url):
    """A reply the chaos plan duplicates at the controller must be counted
    (duplicate_replies) and not double-merged: sums stay bit-identical."""
    from bqueryd_tpu import chaos

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        chaos.arm({
            "seed": 3,
            "faults": [{
                "site": "controller.reply",
                "action": "duplicate",
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected, "duplicated reply must not double-merge"
        # the replay runs after the first copy completed the query and its
        # reply went out: the client may return before it is counted
        wait_until(
            lambda: controller.counters["duplicate_replies"] >= 1,
            timeout=10, desc="the duplicate counted",
        )
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_dropped_reply_recovers_via_failover(tmp_path, mem_store_url):
    """A result lost on the wire (controller.reply drop) is recovered by
    the dispatch timeout + failover re-queue; the answer stays exact."""
    from bqueryd_tpu import chaos

    # the dropping worker stays alive and heartbeating, so recovery runs
    # through the HARD timeout (live-but-silent reclaim) — shrink it
    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, dispatch_timeout=1.0,
        dispatch_hard_timeout=1.0,
    )
    try:
        chaos.arm({
            "seed": 4,
            "faults": [{
                "site": "controller.reply",
                "action": "drop",
                "times": 1,
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected
        assert controller.counters["failover_dispatches"] >= 1
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_redis_partitioned_worker_is_culled_and_inflight_requeued(
    tmp_path, mem_store_url
):
    """The redis-partition scenario: ONE worker loses the coordination
    store (heartbeats stop — its WRM broadcast path reads the store every
    tick) while its zmq sockets stay up.  With its event loop also blocked
    mid-query, the controller must time the dispatch out, re-queue the
    in-flight shard onto the surviving holder, cull the silent worker, and
    answer exactly."""
    import time as time_mod

    from bqueryd_tpu import chaos

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, dispatch_timeout=1.0
    )
    victim = workers[0]
    # pin the first dispatch onto the victim AND block it there long enough
    # for the partition + dispatch timeout to play out
    got_work = threading.Event()
    orig_handle_work = victim.handle_work

    def slow_handle_work(msg):
        got_work.set()
        time_mod.sleep(4.0)
        return orig_handle_work(msg)

    victim.handle_work = slow_handle_work
    # the other worker must not win the first dispatch: mark it busy until
    # the victim has the work
    survivor_id = workers[1].worker_id
    try:
        chaos.arm({
            "seed": 5,
            "faults": [{
                "site": "coordination.store",
                "action": "partition",
                "match": {"node": victim.worker_id},
                "window_s": 30.0,
            }],
        })
        wait_until(
            lambda: controller.worker_map.get(survivor_id) is not None,
            desc="survivor registered",
        )
        controller.worker_map[survivor_id]["busy"] = True
        result_box = {}

        def ask():
            _, result_box["got"] = _ask_sum(mem_store_url, shards)

        asker = threading.Thread(target=ask, daemon=True)
        asker.start()
        wait_until(got_work.is_set, desc="victim received the dispatch")
        controller.worker_map[survivor_id]["busy"] = False
        asker.join(timeout=40)
        assert not asker.is_alive(), "query never completed after partition"
        assert result_box["got"] == expected
        # the partitioned worker was culled (no heartbeats reached the
        # controller once the store access started raising StorePartitioned)
        wait_until(
            lambda: victim.worker_id not in controller.worker_map,
            timeout=15,
            desc="partitioned worker culled",
        )
        assert controller.counters["failover_dispatches"] >= 1
        assert chaos.site_stats().get("coordination.store", 0) >= 1
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_dispatch_exhaustion_returns_structured_error(
    tmp_path, mem_store_url
):
    """With every holder persistently faulting (transient raises, no
    replica left to absorb them), the retry budget exhausts and the client
    gets the STRUCTURED envelope: error_class DispatchExhausted + the
    per-attempt worker/fault history — not a blind timeout."""
    import numpy as np
    import pandas as pd

    from bqueryd_tpu import chaos
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC, RPCError
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    df = pd.DataFrame(
        {"g": np.arange(20) % 4, "v": np.arange(20, dtype=np.int64)}
    )
    ctable.fromdataframe(df, str(tmp_path / "x.bcolzs"))
    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=10.0,
        dispatch_timeout=10.0,
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
    try:
        wait_until(
            lambda: "x.bcolzs" in controller.files_map, desc="registration"
        )
        chaos.arm({
            "seed": 6,
            "faults": [{
                "site": "worker.execute",
                "action": "raise",
                "match": {"verb": "groupby"},
                "args": {"error": "DeviceBusyError"},
            }],
        })
        rpc = RPC(
            coordination_url=mem_store_url, timeout=30,
            loglevel=logging.WARNING,
        )
        with pytest.raises(RPCError) as excinfo:
            rpc.groupby(["x.bcolzs"], ["g"], [["v", "sum", "s"]], [])
        err = excinfo.value
        assert getattr(err, "error_class", None) == "DispatchExhausted"
        attempts = getattr(err, "attempts", [])
        assert len(attempts) >= 1
        assert all(a.get("worker") == worker.worker_id for a in attempts)
        assert any("DeviceBusyError" in str(a.get("reason")) for a in attempts)
        assert "DispatchExhausted" in str(err)
        # the sole holder was retried (never excluded outright) and the
        # abort is structural, not a client timeout
        assert controller.counters["transient_faults"] >= 1
    finally:
        chaos.disarm()
        _stop([controller, worker], threads)


def test_hedged_dispatch_first_reply_wins(tmp_path, mem_store_url):
    """BQUERYD_TPU_HEDGE_MS: a shard stuck on a slow holder past the
    threshold is duplicated onto the other holder; the fast duplicate's
    reply answers the query (hedge_wins), the slow original's late reply
    is deduplicated by token (duplicate_replies), sums stay exact."""
    import time as time_mod

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.worker import WorkerNode

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, dispatch_timeout=30.0,
        shards=("hedge_0.bcolzs",),
    )
    controller.hedge_ms = 300.0
    slow = workers[0]
    orig_handle_work = slow.handle_work
    slowed = threading.Event()

    def slow_handle_work(msg):
        if msg.isa("groupby"):
            slowed.set()
            time_mod.sleep(2.0)
        return orig_handle_work(msg)

    slow.handle_work = slow_handle_work
    fast_id = workers[1].worker_id
    try:
        wait_until(
            lambda: controller.worker_map.get(fast_id) is not None,
            desc="fast worker registered",
        )
        # force the first dispatch onto the slow worker
        controller.worker_map[fast_id]["busy"] = True
        result_box = {}

        def ask():
            _, result_box["got"] = _ask_sum(mem_store_url, shards)

        asker = threading.Thread(target=ask, daemon=True)
        asker.start()
        wait_until(slowed.is_set, desc="slow worker holds the shard")
        controller.worker_map[fast_id]["busy"] = False
        asker.join(timeout=30)
        assert not asker.is_alive(), "hedged query never completed"
        assert result_box["got"] == expected
        assert controller.counters["hedged_dispatches"] >= 1
        assert controller.counters["hedge_wins"] >= 1
        # the slow original eventually replies too: deduped, not re-merged
        wait_until(
            lambda: controller.counters["duplicate_replies"] >= 1,
            desc="late original reply deduplicated",
        )
    finally:
        _stop([controller] + workers, threads)


def test_late_reply_from_superseded_worker_wins_and_keeps_reclaim_handle(
    tmp_path, mem_store_url
):
    """A worker hung past the hard timeout is removed and its shard
    re-queued onto the other holder; its LATE valid reply then wins (replica
    holders compute identical payloads) — and the controller must keep a
    hard-timeout reclaim handle on the superseded attempt's worker, which is
    still computing: without one, a wedged holder sits busy-and-advertised
    forever with no watchdog."""
    import time as time_mod

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, dispatch_timeout=0.4,
        dispatch_hard_timeout=2.0, shards=("late_0.bcolzs",),
    )
    first, second = workers
    started = threading.Event()

    def wrap(worker, delay, evt=None):
        orig = worker.handle_work

        def wrapped(msg):
            if msg.isa("groupby"):
                if evt is not None:
                    evt.set()
                time_mod.sleep(delay)
            return orig(msg)

        worker.handle_work = wrapped

    # first: outlives the 2s hard timeout, replies at 3.5s; second picks up
    # the failover ~2.1-2.6s in and computes for 3s more — so the first
    # worker's late reply lands while the second is still mid-computation
    wrap(first, 3.5, started)
    wrap(second, 3.0)
    second_id = second.worker_id
    try:
        wait_until(
            lambda: controller.worker_map.get(second_id) is not None,
            desc="second worker registered",
        )
        # force the first dispatch onto the first worker
        controller.worker_map[second_id]["busy"] = True
        result_box = {}

        def ask():
            _, result_box["got"] = _ask_sum(mem_store_url, shards)

        asker = threading.Thread(target=ask, daemon=True)
        asker.start()
        wait_until(started.is_set, desc="first worker holds the shard")
        controller.worker_map[second_id]["busy"] = False
        asker.join(timeout=30)
        assert not asker.is_alive(), "query never completed"
        assert result_box["got"] == expected
        # the hard timeout really failed the shard over to the second holder
        assert controller.counters["failover_dispatches"] >= 1
        # ...and the first worker's late reply won while the second is still
        # computing: its reclaim handle must survive the inflight-entry pop
        assert any(
            second_id in rec["workers"]
            for rec in controller._hedge_losers.values()
        ), "no reclaim handle kept on the superseded attempt's worker"
        # the handle resolves: the loser answers (deduped by token) or is
        # reclaimed past the hard cap — either way tracking drains
        wait_until(
            lambda: not controller._hedge_losers,
            desc="superseded attempt deduplicated or reclaimed",
        )
    finally:
        _stop([controller] + workers, threads)


def test_requeue_of_hedged_entry_collapses_onto_surviving_duplicate(
    mem_store_url,
):
    """A hedged flight whose original side times out (or is culled) must
    NOT requeue a third execution — and must not leave the token in the
    hedge dedup ring, where the surviving duplicate's valid reply would be
    discarded as a 'duplicate' while the shard is still unanswered.  The
    inflight entry collapses onto the survivor with a rebased timeout
    clock and the failed side excluded."""
    import time

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, WorkerRegisterMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent", dispatch_timeout=0.01,
        dispatch_hard_timeout=0.02,
    )
    try:
        for wid in ("wa", "wb"):
            controller.handle_worker(
                wid.encode(),
                WorkerRegisterMessage({
                    "worker_id": wid, "workertype": "calc",
                    "data_files": ["s.bcolzs"],
                }),
            )
        msg = CalcMessage({
            "payload": "groupby", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        now = time.time()
        controller.inflight["t1"] = {
            "worker": "wa", "sent_at": now - 60, "msg": msg,
            "parent": "p1", "retries": 0,
            "hedged": "wb", "hedged_at": now,
        }
        controller._hedged_tokens["t1"] = now
        controller.retry_stale_dispatches()
        entry = controller.inflight["t1"]
        assert entry["worker"] == "wb" and "hedged" not in entry
        assert entry["sent_at"] == now, "survivor clock rebased to the hedge"
        assert "t1" not in controller._hedged_tokens, (
            "dedup ring entry would discard the survivor's valid reply"
        )
        assert not any(controller.worker_out_messages.values()), (
            "redundant third execution queued"
        )
        assert msg.get("_excluded_workers") == ["wa"]
        # the hung-but-heartbeating original was reclaimed like any other
        # hung dispatch; the survivor's entry was left alone
        assert "wa" not in controller.worker_map
        assert controller.inflight["t1"]["worker"] == "wb"

        # cull of the HEDGE side: the original attempt stands alone again
        msg2 = CalcMessage({
            "payload": "groupby", "token": "t2", "parent_token": "p2",
            "filename": "s.bcolzs",
        })
        controller.inflight["t2"] = {
            "worker": "wb", "sent_at": now, "msg": msg2,
            "parent": "p2", "retries": 0,
            "hedged": "wc", "hedged_at": now,
        }
        controller._hedged_tokens["t2"] = now
        controller.remove_worker("wc")
        entry2 = controller.inflight["t2"]
        assert entry2["worker"] == "wb" and "hedged" not in entry2
        assert "t2" not in controller._hedged_tokens
        assert msg2.get("_excluded_workers") == ["wc"]
    finally:
        controller.socket.close()


def test_stale_replies_while_retry_parked_neither_abort_nor_reexecute(
    mem_store_url,
):
    """While a timed-out shard's retry is still parked in the dispatch
    queue (backoff window / no free holder), a late reply from the FAILED
    attempt must not abort the query — the parked retry stands for a
    stale ERROR — and a late VALID result wins outright, withdrawing the
    queued retry instead of burning a worker on a finished shard."""
    import time

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, ErrorMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        aborted = []
        controller.abort_parent = (
            lambda parent, *a, **k: aborted.append(parent)
        )
        msg = CalcMessage({
            "payload": "groupby", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        entry = {
            "worker": "wa", "sent_at": time.time() - 60, "msg": msg,
            "parent": "p1", "retries": 0,
        }
        controller._requeue(entry, reason="test: dispatch timeout")
        assert "t1" in controller._requeued_tokens
        # late NON-transient error from the failed attempt: dropped, the
        # parked retry stands (the old path aborted the parent here)
        err = ErrorMessage({
            "payload": "boom", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        controller.handle_worker(b"wa", err)
        assert aborted == [], (
            "stale fault aborted a query with a healthy retry parked"
        )
        assert controller.counters["duplicate_replies"] == 1
        queued = controller.worker_out_messages.get(None, [])
        assert [m.get("token") for m in queued] == ["t1"]
        # late VALID result from the failed attempt: delivered (first
        # reply wins) and the queued retry is withdrawn
        reply = CalcMessage({
            "payload": "groupby", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        controller.handle_worker(b"wa", reply)
        assert aborted == []
        assert "t1" not in controller._requeued_tokens
        assert not any(controller.worker_out_messages.values()), (
            "answered shard left queued for a redundant execution"
        )
        # the win leaves a dedup-ring marker: ANOTHER superseded attempt
        # may still be computing the token, and its later non-transient
        # error must be counted and dropped — not reach the orphan
        # fall-through and abort the answered parent
        assert "t1" in controller._hedged_tokens
        late_err = ErrorMessage({
            "payload": "boom", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        dups_before = controller.counters["duplicate_replies"]
        controller.handle_worker(b"wb", late_err)
        assert aborted == [], (
            "late error from a second superseded attempt aborted the "
            "answered query"
        )
        assert controller.counters["duplicate_replies"] == dups_before + 1
    finally:
        controller.socket.close()


def test_orphan_loser_error_after_ring_eviction_does_not_abort(
    mem_store_url,
):
    """A late NON-transient ErrorMessage from a hedge loser whose
    dedup-ring marker was evicted by the 256-entry cap must not abort the
    parent: ``_hedge_losers`` outlives the ring and proves the token was
    already answered, so the reply is counted and dropped like the ring
    branch would have."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import ErrorMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        aborted = []
        controller.abort_parent = (
            lambda parent, *a, **k: aborted.append(parent)
        )
        # token answered long ago: the winning reply noted the loser, then
        # 256+ newer hedges evicted the ring marker
        controller._note_losers("t1", ["wa"])
        assert "t1" not in controller._hedged_tokens
        err = ErrorMessage({
            "payload": "shard file vanished", "token": "t1",
            "parent_token": "p1", "filename": "s.bcolzs",
        })
        controller.handle_worker(b"wa", err)
        assert aborted == [], (
            "orphan loser error aborted a query whose shard was merged"
        )
        assert controller.counters["duplicate_replies"] == 1
        assert "t1" not in controller._hedge_losers, (
            "answered loser left holding a hard-timeout reclaim handle"
        )
    finally:
        controller.socket.close()


def test_hedged_nontransient_error_defers_to_survivor(mem_store_url):
    """A NON-transient ErrorMessage from one side of a hedged pair must
    not abort the query (nor count a hedge win) while the other side is
    still computing: the inflight entry collapses onto the survivor, whose
    answer decides."""
    import time

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, ErrorMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        aborted = []
        controller.abort_parent = (
            lambda parent, *a, **k: aborted.append(parent)
        )
        msg = CalcMessage({
            "payload": "groupby", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        now = time.time()
        controller.inflight["t1"] = {
            "worker": "wa", "sent_at": now, "msg": msg, "parent": "p1",
            "retries": 0, "hedged": "wb", "hedged_at": now,
        }
        controller._hedged_tokens["t1"] = now
        err = ErrorMessage({
            "payload": "corrupt shard copy", "token": "t1",
            "parent_token": "p1", "filename": "s.bcolzs",
        })
        controller.handle_worker(b"wb", err)
        assert aborted == [], (
            "hedge-side permanent error aborted a query whose original "
            "attempt is healthy and still computing"
        )
        entry = controller.inflight["t1"]
        assert entry["worker"] == "wa" and "hedged" not in entry
        assert controller.counters["hedge_wins"] == 0, (
            "an error reply counted as a hedge win"
        )
        assert controller.counters["transient_faults"] == 0
        assert "t1" not in controller._hedged_tokens, (
            "survivor's valid reply would be deduplicated away"
        )
        assert msg.get("_excluded_workers") == ["wb"]
    finally:
        controller.socket.close()


def test_segment_completion_tolerates_overlapping_batch_and_children(
    mem_store_url,
):
    """A re-split batch can leave BOTH the late batch payload and its
    per-shard children in a segment's results: overlapping keys must
    neither complete the segment early (sum-of-key-lengths said 4/4 with
    half the files uncovered) nor merge a shard's payload twice."""
    import pickle

    from bqueryd_tpu.controller import ControllerNode

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        replies = []
        controller.reply_rpc_raw = (
            lambda tok, data: replies.append((tok, data))
        )
        controller._finalize_query_obs = lambda *a, **k: None
        segment = {
            "client_token": "c1",
            "filenames": ["f1", "f2", "f3", "f4"],
            # children (f1,) (f2,) answered, then the original batch's
            # late valid reply was delivered too
            "results": {
                ("f1",): b"c1", ("f2",): b"c2", ("f1", "f2"): b"b12",
            },
            "timings": {},
            "admission_ticket": None,
            "pruned": [],
            "obs": None,
            "strategies": {},
            "effective": {},
        }
        controller.rpc_segments["p1"] = segment
        controller._maybe_complete_segment("p1")
        assert "p1" in controller.rpc_segments and not replies, (
            "overlapping keys double-counted into premature completion"
        )
        segment["results"][("f3", "f4")] = b"b34"
        controller._maybe_complete_segment("p1")
        assert "p1" not in controller.rpc_segments and replies
        payloads = pickle.loads(replies[0][1])["payloads"]
        assert payloads == [b"b12", b"b34"], (
            "per-shard children merged alongside their own batch payload"
        )
    finally:
        controller.socket.close()


def test_maybe_hedge_skips_entries_requeued_mid_loop(mem_store_url):
    """Culling a gone hedge target mid-loop requeues that worker's OTHER
    inflight entries: the stale snapshot items must be skipped, not
    hedged — a ring marker for a parked token would discard the retry's
    valid reply as a duplicate and burn a redundant execution."""
    import time

    import zmq as zmq_mod

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, WorkerRegisterMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        controller.hedge_ms = 1.0
        for wid in ("wa", "wx", "wb"):
            controller.handle_worker(
                wid.encode(),
                WorkerRegisterMessage({
                    "worker_id": wid, "workertype": "calc",
                    "data_files": ["s.bcolzs"],
                }),
            )
        now = time.time()
        for token, worker in (("t1", "wa"), ("t2", "wx")):
            m = CalcMessage({
                "payload": "groupby", "token": token,
                "parent_token": f"p-{token}", "filename": "s.bcolzs",
            })
            controller.inflight[token] = {
                "worker": worker, "sent_at": now - 60, "msg": m,
                "parent": f"p-{token}", "retries": 0,
            }
        picks = iter(["wx", "wb"])
        controller.find_free_worker = (
            lambda *a, **k: next(picks)
        )

        def dead_route(target, msg):
            raise zmq_mod.ZMQError()

        controller._dispatch_wire = dead_route
        controller.maybe_hedge()
        # hedging t1 onto gone wx culled wx, requeueing t2 mid-loop: the
        # snapshot item for t2 must be skipped, not hedged
        assert "t2" in controller._requeued_tokens
        assert "t2" not in controller._hedged_tokens, (
            "parked token marked in the dedup ring — its retry's valid "
            "reply would be discarded as a duplicate"
        )
        assert controller.counters["hedged_dispatches"] == 0
    finally:
        controller.socket.close()


def test_replayed_transient_error_counts_once(mem_store_url):
    """A chaos-duplicated transient ErrorMessage must count ONE
    transient_fault: the replay enters process_worker_result with no
    inflight entry and is a duplicate of the fault, not a new one."""
    import time

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.messages import CalcMessage, ErrorMessage

    controller = ControllerNode(
        coordination_url=mem_store_url, loglevel=logging.WARNING,
        runfile_dir="/nonexistent",
    )
    try:
        msg = CalcMessage({
            "payload": "groupby", "token": "t1", "parent_token": "p1",
            "filename": "s.bcolzs",
        })
        entry = {
            "worker": "wa", "sent_at": time.time(), "msg": msg,
            "parent": "p1", "retries": 0,
        }
        err = ErrorMessage({
            "payload": "DeviceBusyError: chaos", "token": "t1",
            "parent_token": "p1", "filename": "s.bcolzs",
            "transient": True,
        })
        controller.process_worker_result(err, entry)   # the real fault
        controller.process_worker_result(err, None)    # the chaos replay
        assert controller.counters["transient_faults"] == 1, (
            "one injected duplicate inflated the transient-fault rate"
        )
        assert controller.counters["duplicate_replies"] == 1
    finally:
        controller.socket.close()


def test_hedged_transient_fault_defers_to_outstanding_duplicate(
    tmp_path, mem_store_url
):
    """A transient fault from one side of a hedged pair must NOT requeue or
    abort the shard while the duplicate is still computing: the inflight
    entry is re-keyed to the survivor, whose reply answers the query — no
    redundant third execution (failover_dispatches stays 0) and no
    DispatchExhausted abort with a correct answer in flight."""
    from bqueryd_tpu import chaos as chaos_mod

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, dispatch_timeout=30.0,
        shards=("hedtr_0.bcolzs",),
    )
    controller.hedge_ms = 200.0
    faulty, steady = workers
    faulty_started = threading.Event()
    fault_now = threading.Event()
    steady_go = threading.Event()

    orig_faulty = faulty.handle_work

    def faulty_work(msg):
        if msg.isa("groupby"):
            faulty_started.set()
            fault_now.wait(timeout=20)
            raise chaos_mod.DeviceBusyError("injected: hedged-pair fault")
        return orig_faulty(msg)

    faulty.handle_work = faulty_work
    orig_steady = steady.handle_work

    def steady_work(msg):
        if msg.isa("groupby"):
            steady_go.wait(timeout=20)
        return orig_steady(msg)

    steady.handle_work = steady_work
    steady_id = steady.worker_id
    try:
        wait_until(
            lambda: controller.worker_map.get(steady_id) is not None,
            desc="steady worker registered",
        )
        # force the first dispatch onto the faulty worker
        controller.worker_map[steady_id]["busy"] = True
        result_box = {}

        def ask():
            _, result_box["got"] = _ask_sum(mem_store_url, shards)

        asker = threading.Thread(target=ask, daemon=True)
        asker.start()
        wait_until(faulty_started.is_set, desc="faulty worker holds the shard")
        controller.worker_map[steady_id]["busy"] = False
        wait_until(
            lambda: controller.counters["hedged_dispatches"] >= 1,
            desc="tail shard hedged onto the steady holder",
        )
        fault_now.set()
        wait_until(
            lambda: controller.counters["transient_faults"] >= 1,
            desc="transient fault from the hedged pair processed",
        )
        # no requeue happened: the entry now rides the surviving duplicate
        assert controller.counters["failover_dispatches"] == 0
        assert [
            e["worker"] for e in controller.inflight.values()
        ] == [steady_id]
        steady_go.set()
        asker.join(timeout=30)
        assert not asker.is_alive(), "query never completed"
        assert result_box["got"] == expected
        assert controller.counters["failover_dispatches"] == 0
        assert not controller.inflight
    finally:
        _stop([controller] + workers, threads)


def test_bundle_shared_scan_fails_over_as_one_unit(
    tmp_path, mem_store_url, monkeypatch
):
    """Bundle x PR-8 failover: two distinct-but-compatible concurrent
    queries fuse into ONE shared-scan bundle inside the admission window;
    a chaos transient fault (wedge -> DeviceBusyError) on the first holder
    fails the WHOLE bundle over to the replica holder — both members get
    bit-exact answers, neither aborts, and no member is executed twice for
    one successful attempt (one bundle token end to end)."""
    from bqueryd_tpu import chaos
    from bqueryd_tpu.rpc import RPC

    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url, df_seed=17
    )
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "400")
    try:
        chaos.arm({
            "seed": 9,
            "faults": [{
                "site": "worker.execute",
                "action": "wedge",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        # distinct signatures (different filter conjunctions over the full
        # value range), identical answers: both cover every row
        lo = -(2**41)
        queries = [
            (list(shards), ["g"], [["v", "sum", "s"]], [["v", ">", lo]]),
            (list(shards), ["g"], [["v", "sum", "s"]], [["v", ">=", lo]]),
        ]
        results, errors = {}, {}

        def ask(i):
            try:
                rpc = RPC(
                    coordination_url=mem_store_url, timeout=60,
                    loglevel=logging.WARNING,
                )
                df = rpc.groupby(*queries[i])
                results[i] = dict(zip(df["g"].tolist(), df["s"].tolist()))
            except Exception as exc:  # noqa: BLE001
                errors[i] = exc

        askers = [
            threading.Thread(target=ask, args=(i,), daemon=True)
            for i in range(2)
        ]
        for t in askers:
            t.start()
        for t in askers:
            t.join(90)
        assert not errors, errors
        assert results[0] == expected
        assert results[1] == expected
        # the two queries rode ONE bundle...
        assert controller.counters["plan_bundles"] >= 1
        assert controller.counters["plan_bundled_queries"] >= 2
        # ...which failed over as one unit on the transient fault
        assert controller.counters["transient_faults"] >= 1
        assert controller.counters["failover_dispatches"] >= 1
        # a transient fault never culls: both holders still registered,
        # exactly one latched its chaos wedge
        assert len(controller.worker_map) == 2
        assert sum(1 for w in workers if w._chaos_wedged) == 1
        wait_until(
            lambda: not controller.inflight and not controller.rpc_segments,
            desc="bundle settled after failover",
        )
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def _warm_capacity_model(controller, workers, mem_store_url, shards,
                         expected, max_queries=20):
    """Query until every worker has a measured μ in the capacity model
    (random dispatch placement reaches both holders within a few tries)."""
    import time as _time

    def measured():
        ws = controller.capacity.evaluate().get("workers", {})
        return all(
            ws.get(w.worker_id, {}).get("mu") is not None for w in workers
        )

    deadline = _time.time() + 30
    for _ in range(max_queries):
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected
        if measured():
            return
        if _time.time() > deadline:
            break
        _time.sleep(0.2)  # let a WRM carry the bumped totals
    wait_until(measured, desc="every worker measured by the capacity model")


def test_capacity_kill_worker_shrinks_fleet_mu_and_advises_scale_up(
    tmp_path, mem_store_url, monkeypatch
):
    """PR-8 kill-worker chaos under the PR-12 capacity model: the dead
    worker's μ leaves the fleet aggregate, no query fails (replica
    failover), and with load still arriving the shadow advisor flips to
    scale_up.  Thresholds are pinned low so the micro-queries' utilization
    registers — the test targets the mechanism, not the default knobs."""
    from bqueryd_tpu import chaos

    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_HYSTERESIS_S", "0")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_WINDOW_S", "20")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_RHO_SATURATED", "0.005")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_RHO_WARM", "0.002")
    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        _warm_capacity_model(
            controller, workers, mem_store_url, shards, expected
        )
        before = controller.capacity.evaluate()["fleet"]
        assert before["measured_workers"] == 2
        chaos.arm({
            "seed": 5,
            "faults": [{
                "site": "worker.execute",
                "action": "die_after_ack",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected  # failover: ZERO failed queries
        chaos.disarm()
        wait_until(
            lambda: len(controller.worker_map) == 1,
            desc="dead worker culled",
        )
        # keep load arriving so the advisor has evidence post-kill
        for _ in range(3):
            _, again = _ask_sum(mem_store_url, shards)
            assert again == expected
        result = controller.capacity.evaluate()
        fleet = result["fleet"]
        assert fleet["workers"] == 1
        assert fleet["measured_workers"] == 1
        # the dead worker's μ left the aggregate: the model dropped it
        # entirely, and fleet capacity is now the survivor's μ alone (the
        # raw sum comparison would race the survivor's own EWMA drifting
        # as warm micro-queries speed up)
        dead = [w for w in workers if w.worker_id not in
                controller.worker_map]
        assert len(dead) == 1
        assert dead[0].worker_id not in result["workers"]
        survivor_mu = [
            w["mu"] for wid, w in result["workers"].items()
        ]
        assert len(survivor_mu) == 1 and survivor_mu[0] is not None
        assert fleet["mu_dispatches_per_s"] == pytest.approx(
            survivor_mu[0], rel=0.01
        )
        actions = [r["action"] for r in result["recommendations"]]
        assert "scale_up" in actions, result["recommendations"]
        assert controller.counters["capacity_scale_up_advised"] >= 1
        assert controller.counters["failover_dispatches"] >= 1
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_capacity_wedge_device_shrinks_fleet_mu_and_advises_scale_up(
    tmp_path, mem_store_url, monkeypatch
):
    """Wedge-device chaos: the wedged worker stays registered (transient
    failover serves its queries from the replica holder) but its
    advertised latch excludes its μ from fleet capacity — fleet μ shrinks,
    queries keep succeeding, and the advisor flips to scale_up."""
    from bqueryd_tpu import chaos

    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_HYSTERESIS_S", "0")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_WINDOW_S", "20")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_RHO_SATURATED", "0.005")
    monkeypatch.setenv("BQUERYD_TPU_CAPACITY_RHO_WARM", "0.002")
    controller, workers, threads, expected, shards = _replica_cluster(
        tmp_path, mem_store_url
    )
    try:
        _warm_capacity_model(
            controller, workers, mem_store_url, shards, expected
        )
        before = controller.capacity.evaluate()["fleet"]
        assert before["measured_workers"] == 2
        chaos.arm({
            "seed": 6,
            "faults": [{
                "site": "worker.execute",
                "action": "wedge",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        _, got = _ask_sum(mem_store_url, shards)
        assert got == expected  # transient failover: ZERO failed queries
        chaos.disarm()
        wedged = [w for w in workers if w._chaos_wedged]
        assert len(wedged) == 1
        # the latch must ride a WRM into the capacity model
        wait_until(
            lambda: controller.capacity.evaluate()
            .get("workers", {})
            .get(wedged[0].worker_id, {})
            .get("wedged") is True,
            desc="wedge latch absorbed by the capacity model",
        )
        for _ in range(3):
            _, again = _ask_sum(mem_store_url, shards)
            assert again == expected
        result = controller.capacity.evaluate()
        fleet = result["fleet"]
        # both workers still registered — but the wedged one is no longer
        # counted as capacity: fleet μ is the healthy worker's alone (the
        # raw before/after sum comparison would race the healthy worker's
        # own EWMA drift on warm micro-queries)
        assert len(controller.worker_map) == 2
        assert fleet["workers"] == 2
        assert fleet["measured_workers"] == 1
        healthy_mu = [
            w["mu"] for w in result["workers"].values()
            if not w["wedged"] and w["mu"] is not None
        ]
        assert len(healthy_mu) == 1
        assert fleet["mu_dispatches_per_s"] == pytest.approx(
            healthy_mu[0], rel=0.01
        )
        actions = [r["action"] for r in result["recommendations"]]
        assert "scale_up" in actions, result["recommendations"]
        assert controller.counters["transient_faults"] >= 1
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)


def test_append_delta_failover_chaos(tmp_path, mem_store_url):
    """PR-14 acceptance: append + kill-worker during a delta-refresh burst
    leaves ZERO failed queries with results bit-exact vs a full recompute.

    True replica topology (each worker owns its own data_dir copy of the
    shard): rpc.append fans the batch to BOTH holders; a die_after_ack
    chaos kill mid-burst fails the in-flight query over to the surviving
    replica; post-cull appends route to the survivor alone and its repeat
    queries keep being served by delta refreshes."""
    import shutil

    import numpy as np
    import pandas as pd

    from bqueryd_tpu import chaos
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(77)

    def batch(n, offset):
        return pd.DataFrame(
            {
                "g": rng.integers(0, 4, n).astype(np.int64),
                "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
                "seq": np.arange(offset, offset + n, dtype=np.int64),
            }
        )

    frame = batch(1200, 0)
    dirs = [tmp_path / "a", tmp_path / "b"]
    dirs[0].mkdir()
    ctable.fromdataframe(
        frame, str(dirs[0] / "t.bcolzs"), chunklen=256
    )
    shutil.copytree(str(dirs[0] / "t.bcolzs"), str(tmp_path / "b" / "t.bcolzs"))

    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.05,
        dead_worker_timeout=1.0,
        dispatch_timeout=1.5,
    )
    workers = [
        WorkerNode(
            coordination_url=mem_store_url,
            data_dir=str(d),
            loglevel=logging.WARNING,
            restart_check=False,
            heartbeat_interval=0.2,
            poll_timeout=0.05,
        )
        for d in dirs
    ]
    threads = _start(controller, *workers)
    q = (["t.bcolzs"], ["g"], [["v", "sum", "s"]], [])

    def expect(df):
        return df.groupby("g")["v"].sum().to_dict()

    try:
        wait_until(
            lambda: len(controller.files_map.get("t.bcolzs", ())) == 2,
            desc="both replica holders advertising",
        )
        rpc = RPC(
            coordination_url=mem_store_url, timeout=45,
            loglevel=logging.WARNING,
        )
        got = rpc.groupby(*q)
        assert dict(zip(got["g"], got["s"])) == expect(frame)

        # append #1 lands on BOTH replicas
        extra1 = batch(150, 1200)
        res = rpc.append("t.bcolzs", extra1)
        assert res["appended"] == 150 and len(res["holders"]) == 2
        assert all(
            ctable(str(d / "t.bcolzs")).nrows == 1350 for d in dirs
        )
        frame = pd.concat([frame, extra1], ignore_index=True)
        got = rpc.groupby(*q)
        assert dict(zip(got["g"], got["s"])) == expect(frame)
        # (which holder serves each query is a scheduling choice, so the
        # "delta" route is asserted deterministically below, once a single
        # survivor serves everything)

        # kill one holder mid-burst: the in-flight query fails over
        chaos.arm({
            "seed": 5,
            "faults": [{
                "site": "worker.execute",
                "action": "die_after_ack",
                "match": {"verb": "groupby"},
                "times": 1,
            }],
        })
        extra2 = batch(150, 1350)
        # the dying side may or may not have applied extra2 before the
        # kill fires on the next groupby — the SURVIVOR's state is what
        # queries answer from, so append first, then query through chaos
        failed = 0
        try:
            rpc.append("t.bcolzs", extra2, deadline=20)
        except Exception:
            # a holder that died mid-append reports a structured error;
            # the surviving replica applied it (asserted via parity below)
            pass
        frame = pd.concat([frame, extra2], ignore_index=True)
        try:
            got = rpc.groupby(*q)
        except Exception:
            failed += 1
        assert failed == 0, "chaos burst must leave zero failed queries"
        assert dict(zip(got["g"], got["s"])) == expect(frame)
        assert chaos.injected_total() >= 1
        chaos.disarm()

        wait_until(
            lambda: len(controller.worker_map) == 1,
            desc="dead worker culled",
        )
        survivor = [
            w for w in workers
            if w.worker_id in controller.worker_map
        ][0]

        # the survivor serves everything now: establish its delta base,
        # append (routes to it alone), and the repeat MUST delta-refresh
        got = rpc.groupby(*q)
        assert dict(zip(got["g"], got["s"])) == expect(frame)
        extra3 = batch(100, 1500)
        res = rpc.append("t.bcolzs", extra3)
        assert len(res["holders"]) == 1
        frame = pd.concat([frame, extra3], ignore_index=True)
        refreshes_before = survivor.delta_refreshes_total.value
        got = rpc.groupby(*q)
        assert dict(zip(got["g"], got["s"])) == expect(frame)
        assert survivor.delta_refreshes_total.value > refreshes_before
        assert (
            rpc.last_call_strategies["effective"]["t.bcolzs"] == "delta"
        )
        assert controller.counters["failover_dispatches"] >= 1
    finally:
        chaos.disarm()
        _stop([controller] + workers, threads)
