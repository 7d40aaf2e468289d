"""The controller and the client traced from inside (PR 40): the
controller's ``request_decode``, ``reply_absorb``, ``reply_encode`` and
``finalize`` spans, the ``inflight`` window that holds the worker's calc,
and the client's ``client_encode`` / ``client_decode``, which
``rpc.trace()`` merges into the controller's timeline."""

import logging
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pandas as pd
import pytest
from conftest import wait_until

from bqueryd_tpu import rpc as rpcmod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the controller's own spans inside the groupby root, disjoint by
#: construction; with the worker's calc they tile the root but for gaps
CONTROLLER_CHILDREN = (
    "admission", "batch_window", "dispatch", "reply_absorb", "reply_encode",
)
ROUNDING_S = 2e-6   # make_span rounds start and duration to the microsecond


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.coordination import coordination_store
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode

    tmp_path = tmp_path_factory.mktemp("host_spans")
    url = "mem://host_spans"
    coordination_store(url).flushdb()
    rng = np.random.default_rng(40)
    df = pd.DataFrame({
        "g": rng.integers(0, 5, 3000).astype(np.int64),
        "v": rng.integers(-100, 100, 3000).astype(np.int64),
    })
    shards = ["hs_0.bcolzs", "hs_1.bcolzs"]
    for i, name in enumerate(shards):
        ctable.fromdataframe(
            df.iloc[i::2].reset_index(drop=True), str(tmp_path / name)
        )
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.05,
    )
    worker = WorkerNode(
        coordination_url=url, data_dir=str(tmp_path),
        loglevel=logging.WARNING, restart_check=False,
        heartbeat_interval=0.1, poll_timeout=0.05,
    )
    nodes = [controller, worker]
    threads = [threading.Thread(target=n.go, daemon=True) for n in nodes]
    for thread in threads:
        thread.start()
    wait_until(
        lambda: all(name in controller.files_map for name in shards),
        desc="shards advertised",
    )
    rpc = rpcmod.RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
    query = (shards, ["g"], [["v", "sum", "s"]], [])
    rpc.groupby(*query)   # the first query compiles
    yield {"controller": controller, "rpc": rpc, "query": query, "url": url}
    for node in nodes:
        node.running = False
    for thread in threads:
        thread.join(timeout=5)


def _traced_query(cluster):
    rpc = cluster["rpc"]
    rpc.groupby(*cluster["query"])
    trace_id = rpc.last_trace_id
    return wait_until(   # the controller stores it after the reply
        lambda: (lambda t: t if t and any(
            s["name"] == "finalize" for s in t["spans"]) else None
        )(rpc.trace(trace_id)),
        timeout=10, desc="the stored timeline",
    )


def _named(timeline, name):
    return [s for s in timeline["spans"] if s["name"] == name]


def _interval(span):
    return span["start_ts"], span["start_ts"] + span["duration_s"]


def test_every_new_span_is_on_the_timeline_rpc_trace_returns(cluster):
    timeline = _traced_query(cluster)
    for name in ("request_decode", "reply_absorb", "reply_encode",
                 "client_encode", "client_decode"):
        spans = _named(timeline, name)
        assert len(spans) == 1, name
        assert spans[0]["duration_s"] > 0.0, name
        assert spans[0]["trace_id"] == timeline["trace_id"]
    assert len(_named(timeline, "finalize")) == 1
    starts = [s["start_ts"] for s in timeline["spans"]]
    assert starts == sorted(starts)
    # the in-flight window is its own span, holds the worker's calc, and
    # the dispatch spans hold no worker time
    (inflight,), (calc,) = _named(timeline, "inflight"), _named(timeline, "calc")
    lo, hi = _interval(inflight)
    assert lo - ROUNDING_S <= calc["start_ts"] and _interval(calc)[1] <= hi + ROUNDING_S
    for dispatch in _named(timeline, "dispatch"):
        assert _interval(dispatch)[1] <= calc["start_ts"] + ROUNDING_S


def test_the_spans_lie_in_the_order_of_the_query(cluster):
    timeline = _traced_query(cluster)
    one = {name: _named(timeline, name)[0] for name in (
        "client_encode", "request_decode", "groupby", "inflight",
        "reply_absorb", "reply_encode", "finalize", "client_decode")}
    root_lo, root_hi = _interval(one["groupby"])
    # before the root: the client's encode, then the controller's decode
    assert _interval(one["client_encode"])[1] <= one["request_decode"]["start_ts"] + ROUNDING_S
    assert _interval(one["request_decode"])[1] <= root_lo + ROUNDING_S
    # the reply's pickup ends the in-flight window and starts the absorb;
    # the absorb's end starts the encode; the encode ends the root
    assert _interval(one["inflight"])[1] == pytest.approx(
        one["reply_absorb"]["start_ts"], abs=ROUNDING_S)
    assert _interval(one["reply_absorb"])[1] == pytest.approx(
        one["reply_encode"]["start_ts"], abs=ROUNDING_S)
    assert root_hi - ROUNDING_S <= one["finalize"]["start_ts"]
    assert _interval(one["reply_encode"])[1] <= root_hi + ROUNDING_S
    # the client decodes what the controller sent (the encode span holds
    # the send, so the client may begin before the controller's span ends)
    assert one["reply_encode"]["start_ts"] <= one["client_decode"]["start_ts"]


def test_the_controller_children_do_not_overlap(cluster):
    """``admission``, ``batch_window``, ``dispatch``, ``reply_absorb`` and
    ``reply_encode`` neither overlap each other nor the worker's calc, and
    lie in the groupby root: what the root holds besides them and calc is
    the unnamed remainder, so subtracting their sums subtracts disjoint
    time (``controller_unnamed_ms``)."""
    for _ in range(3):
        timeline = _traced_query(cluster)
        root_lo, root_hi = _interval(_named(timeline, "groupby")[0])
        spans = [s for s in timeline["spans"]
                 if s["name"] in CONTROLLER_CHILDREN + ("calc",)]
        assert {"admission", "dispatch", "reply_absorb", "reply_encode", "calc"} <= {
            s["name"] for s in spans}
        intervals = sorted(_interval(s) for s in spans)
        for (lo, hi), (next_lo, _next_hi) in zip(intervals, intervals[1:]):
            assert hi <= next_lo + ROUNDING_S
        assert root_lo - ROUNDING_S <= intervals[0][0]
        assert intervals[-1][1] <= root_hi + ROUNDING_S
        named = sum(hi - lo for lo, hi in intervals)
        assert named <= root_hi - root_lo + len(intervals) * ROUNDING_S


def test_reply_encode_starts_where_reply_absorb_ends_on_the_wall_clock():
    """The instant ``_record_reply_absorb`` hands to ``reply_encode`` is the
    absorb span's end, read off the pickup's wall time and the elapsed
    ``perf_counter``: a second read of ``time.time()`` lies later wherever
    the thread was switched out between the two reads, and the spans
    overlapped.  A pickup whose wall stamp is far from ``time.time()``
    shows it: the handed instant follows the pickup, not the wall clock."""
    import time
    from types import SimpleNamespace

    from bqueryd_tpu.controller import ControllerNode

    segment = {"obs": {"trace_id": "t", "qspan_id": "q", "spans": []}}
    picked_up = (time.time() - 100.0, time.perf_counter() - 0.01)
    began = ControllerNode._record_reply_absorb(
        SimpleNamespace(address="controller"), segment, picked_up)
    (absorb,) = segment["obs"]["spans"]
    assert absorb["name"] == "reply_absorb"
    assert began[0] == pytest.approx(_interval(absorb)[1], abs=ROUNDING_S)
    assert began[0] - picked_up[0] == pytest.approx(began[1] - picked_up[1], abs=1e-6)


def test_the_client_keeps_its_spans_for_every_call_and_by_trace_for_queries(cluster):
    rpc = cluster["rpc"]
    rpc.info()
    assert [s["name"] for s in rpc.last_call_spans] == ["client_encode", "client_decode"]
    assert rpcmod.call_spans(rpc.last_trace_id) == []   # not a query verb
    rpc.groupby(*cluster["query"])
    spans = rpcmod.call_spans(rpc.last_trace_id)
    assert spans == rpc.last_call_spans and len(spans) == 2
    assert all(s["duration_s"] >= 0.0 for s in spans)


def test_the_autopsy_folds_in_the_client_decode_span(cluster):
    rpc = cluster["rpc"]
    timeline = _traced_query(cluster)
    (decode,) = _named(timeline, "client_decode")
    record = rpc.autopsy(timeline["trace_id"])
    assert record["segments"]["client_deserialize"] == pytest.approx(
        decode["duration_s"], abs=1e-6)
    for segment in ("reply_absorb", "reply_encode"):
        assert record["segments"].get(segment, 0.0) > 0.0, segment


def test_the_per_trace_record_is_bounded(monkeypatch):
    monkeypatch.setattr(rpcmod, "_call_spans", type(rpcmod._call_spans)())
    monkeypatch.setattr(rpcmod, "CALL_SPANS_KEPT", 3)
    for i in range(5):
        rpcmod._keep_call_spans(f"t{i}", [{"name": "client_decode", "duration_s": i}])
    assert list(rpcmod._call_spans) == ["t2", "t3", "t4"]
    assert rpcmod.call_spans("t0") == []


def test_the_pickup_stamp_is_never_sent_on(cluster):
    """``_picked_up`` rides a query's request inside the controller only:
    the verb pops it before anything is forwarded, and a worker's message
    (a reply, a registration that ``rpc.info`` returns) never carries it."""
    controller = cluster["controller"]
    sent = []
    real = controller.reply_rpc_message

    def spy(token, msg):
        sent.append(dict(msg))
        return real(token, msg)

    controller.reply_rpc_message = spy
    try:
        cluster["rpc"].groupby(*cluster["query"])
        cluster["rpc"].info()
    finally:
        del controller.reply_rpc_message
    assert sent and all("_picked_up" not in m for m in sent)


def test_a_controller_and_a_client_serve_a_query_without_jax(tmp_path):
    """The controller and the client, spans and all, in one process that
    never imports jax; the worker is a process of its own."""
    code = textwrap.dedent(f"""
        import logging, os, subprocess, sys, threading, time
        import numpy as np, pandas as pd
        pd.set_option("future.infer_string", False)
        from bqueryd_tpu.controller import ControllerNode
        from bqueryd_tpu.rpc import RPC
        from bqueryd_tpu.storage.ctable import ctable

        root = {str(tmp_path)!r}
        url = "file://" + os.path.join(root, "coordination")
        data = os.path.join(root, "data")
        os.makedirs(data)
        df = pd.DataFrame({{"g": np.arange(600) % 4, "v": np.arange(600)}})
        ctable.fromdataframe(df, os.path.join(data, "one.bcolzs"))
        os.environ["BQUERYD_TPU_RUNFILE_DIR"] = root
        controller = ControllerNode(coordination_url=url, loglevel=logging.WARNING,
                                    runfile_dir=root, heartbeat_interval=0.05)
        threading.Thread(target=controller.go, daemon=True).start()
        env = dict(os.environ, JAX_PLATFORMS="cpu", BQUERYD_TPU_WARMUP="0",
                   BQUERYD_TPU_COMPILE_CACHE="0", BQUERYD_TPU_IP="127.0.0.1")
        worker = subprocess.Popen(
            [sys.executable, "-m", "bqueryd_tpu.node", "worker",
             "--coordination=" + url, "--data_dir=" + data],
            env=env, cwd=root, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        try:
            deadline = time.time() + 90
            while "one.bcolzs" not in controller.files_map:
                assert time.time() < deadline, "the worker never advertised"
                time.sleep(0.1)
            rpc = RPC(coordination_url=url, timeout=60, loglevel=logging.WARNING)
            out = rpc.groupby(["one.bcolzs"], ["g"], [["v", "sum", "s"]], [])
            assert sorted(out["s"]) == sorted(df.groupby("g")["v"].sum()), out
            time.sleep(0.2)
            names = {{s["name"] for s in rpc.trace(rpc.last_trace_id)["spans"]}}
            assert {{"request_decode", "reply_absorb", "reply_encode", "finalize",
                    "client_encode", "client_decode"}} <= names, names
            assert "jax" not in sys.modules, "the controller or the client imported jax"
            print("JAX_FREE_OK")
        finally:
            worker.terminate()
            worker.wait(timeout=30)
            controller.running = False
    """)
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path), env=env,
                          capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0 and "JAX_FREE_OK" in proc.stdout, proc.stderr[-3000:]
