"""Accelerator-backend wedge detection and host-served degraded mode.

The failure mode: an accelerator that stops answering makes any dispatch
block forever inside native code (no signal can interrupt it).  These tests simulate the wedge through the
probe seam — no real hangs — and pin that the cluster keeps serving
exact results from the host kernels while latched, and resumes device
routing when a probe succeeds.
"""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.utils import devicehealth


@pytest.fixture(autouse=True)
def _reset_latch():
    devicehealth.force_state(False)
    yield
    devicehealth.force_state(False)


def test_latch_flips_when_probe_overdue_and_recovers_without_release(
    monkeypatch,
):
    """An in-flight probe past the deadline latches wedged without the
    caller ever blocking.  Recovery must NOT require the hung thread to
    return (a real wedge never does): the overdue probe is written off and
    a FRESH probe launched on the interval clock unlatches."""
    hang_forever = threading.Event()  # never set: a true wedge
    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        if calls["n"] == 1:
            hang_forever.wait(5)  # parked (bounded for test hygiene)

    monkeypatch.setattr(devicehealth, "_probe_fn", probe)
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_PROBE_TIMEOUT_S", "0.05")
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_PROBE_INTERVAL_S", "0.05")
    # arrange a fresh probe launch
    devicehealth._last_probe_start = 0.0
    t0 = time.perf_counter()
    assert devicehealth.backend_wedged() is False  # probe just launched
    assert time.perf_counter() - t0 < 1.0, "must never block"
    time.sleep(0.1)
    assert devicehealth.backend_wedged() is True  # overdue -> latched
    # the hung probe is written off; the interval clock launches probe #2
    # ("device recovered": it succeeds) and the latch clears
    deadline = time.time() + 5
    while devicehealth.backend_wedged() and time.time() < deadline:
        time.sleep(0.02)
    assert devicehealth.backend_wedged() is False
    assert calls["n"] >= 2, "a fresh probe must have been launched"
    hang_forever.set()


def test_probe_error_latches_and_recovers(monkeypatch):
    """A probe that ERRORS (backend dead but answering) latches too."""
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_PROBE_INTERVAL_S", "0.05")
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("backend gone")

    monkeypatch.setattr(devicehealth, "_probe_fn", flaky)
    devicehealth._last_probe_start = 0.0
    devicehealth.backend_wedged()  # launches the erroring probe
    deadline = time.time() + 5
    while not devicehealth.backend_wedged() and time.time() < deadline:
        time.sleep(0.02)
    assert devicehealth.backend_wedged() is True
    # the interval clock keeps re-probes coming; the second succeeds
    deadline = time.time() + 5
    while devicehealth.backend_wedged() and time.time() < deadline:
        time.sleep(0.05)
    assert devicehealth.backend_wedged() is False


def test_run_with_deadline_abandons_hung_fn():
    ev = threading.Event()
    t0 = time.perf_counter()
    done, result = devicehealth.run_with_deadline(ev.wait, 0.05)
    assert not done and result is None
    assert time.perf_counter() - t0 < 1.0
    ev.set()  # release the parked thread
    done, result = devicehealth.run_with_deadline(lambda: 41 + 1, 5)
    assert done and result == 42


def test_host_kernel_rows_wedged_overrides_env(monkeypatch):
    """While latched, host routing is unbounded — even over an operator
    device-only pin (survival beats performance)."""
    from bqueryd_tpu.models import query as q

    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    assert q.host_kernel_rows() == 0
    devicehealth.force_state(True)
    assert q.host_kernel_rows() == 1 << 62


def test_dispatch_floor_deadline_miss_latches(monkeypatch):
    from bqueryd_tpu.models import query as q

    monkeypatch.setattr(q, "_measured_floor", None)
    monkeypatch.setattr(
        devicehealth, "run_with_deadline", lambda fn, t: (False, None)
    )
    floor = q.device_dispatch_floor(remeasure=True)
    assert floor == devicehealth.probe_timeout_s()
    assert devicehealth.backend_wedged() is True
    # the garbage floor is NOT cached: recovery remeasures
    assert q._measured_floor is None


def test_wedged_engine_serves_exact_results(monkeypatch, tmp_path):
    """With the backend latched, a mergeable groupby, a count_distinct,
    and a basket filter all answer exactly from the host kernels."""
    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.storage.ctable import ctable

    # make sure the engine would OTHERWISE route to the device
    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    rng = np.random.default_rng(5)
    n = 30_000
    df = pd.DataFrame(
        {
            "k": rng.integers(0, 9, n).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
            "basket": rng.integers(0, 500, n).astype(np.int64),
        }
    )
    root = str(tmp_path / "w.bcolzs")
    ctable.fromdataframe(df, root)
    tbl = ctable(root, mode="r")
    devicehealth.force_state(True)
    engine = QueryEngine()

    def run(query):
        payload = engine.execute_local(tbl, query)
        return hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([payload])
        ).sort_values(query.groupby_cols).reset_index(drop=True)

    got = run(GroupByQuery(["k"], [["v", "sum", "s"]], [], aggregate=True))
    exp = (
        df.groupby("k", as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
    )
    np.testing.assert_array_equal(got["s"].to_numpy(), exp["s"].to_numpy())

    # WITH a where filter: the mask must compute on host while wedged
    # (this was the gap a review pass caught — term_mask dispatched jnp)
    got = run(
        GroupByQuery(
            ["k"], [["v", "sum", "s"]], [["v", ">", 0]], aggregate=True
        )
    )
    sel = df[df["v"] > 0]
    exp = sel.groupby("k", as_index=False)["v"].sum()
    np.testing.assert_array_equal(got["s"].to_numpy(), exp["v"].to_numpy())

    # formerly the one device-only op: the numpy run-leader twin serves it
    got = run(
        GroupByQuery(
            ["k"],
            [["basket", "sorted_count_distinct", "d"]],
            [],
            aggregate=True,
        )
    )
    b = df["basket"].to_numpy()
    k = df["k"].to_numpy()
    # run-leader ground truth: a row starts a run unless the ADJACENT
    # previous row has the same (group, value) — the kernel's semantics
    prev_same = np.concatenate(
        [[False], (b[1:] == b[:-1]) & (k[1:] == k[:-1])]
    )
    exp = (
        pd.DataFrame({"k": k, "new": ~prev_same})
        .groupby("k")["new"].sum().sort_index()
    )
    np.testing.assert_array_equal(got["d"].to_numpy(), exp.to_numpy())

    got = run(
        GroupByQuery(
            ["k"], [["basket", "count_distinct", "d"]], [], aggregate=True
        )
    )
    exp = df.groupby("k")["basket"].nunique()
    np.testing.assert_array_equal(
        got["d"].to_numpy(), exp.sort_index().to_numpy()
    )

    # basket expansion path (expand_mask_by_group host fallback)
    from bqueryd_tpu import ops

    codes = df["basket"].to_numpy()
    mask = df["v"].to_numpy() > 0
    got_mask = np.asarray(
        ops.expand_mask_by_group(codes, mask, n_groups=500)
    )
    sel_groups = set(codes[mask])
    exp_mask = np.array([c in sel_groups for c in codes])
    np.testing.assert_array_equal(got_mask, exp_mask)


def test_wedged_worker_routes_around_mesh(monkeypatch, tmp_path):
    """The worker must not touch the mesh executor while latched."""
    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.utils.tracing import PhaseTimer
    from bqueryd_tpu.worker import WorkerNode

    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    rng = np.random.default_rng(6)
    n = 60_000
    frames, tables = [], []
    for s in range(2):
        df = pd.DataFrame(
            {
                "k": rng.integers(0, 9, n).astype(np.int64),
                "v": rng.integers(-100, 100, n).astype(np.int64),
            }
        )
        frames.append(df)
        root = str(tmp_path / f"wm{s}.bcolzs")
        ctable.fromdataframe(df, root)
        tables.append(ctable(root, mode="r"))

    worker = WorkerNode.__new__(WorkerNode)
    worker._engine = None
    worker._result_cache = None

    class _MustNotRun:
        timer = None

        def execute(self, tables, query):
            raise AssertionError("mesh executor touched while wedged")

    worker._mesh_executor = _MustNotRun()
    import logging

    worker.logger = logging.getLogger("test-wedge")
    devicehealth.force_state(True)
    q = GroupByQuery(["k"], [["v", "sum", "s"]], [], aggregate=True)
    payload = worker._execute(tables, q, PhaseTimer())
    got = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    ).sort_values("k").reset_index(drop=True)
    all_df = pd.concat(frames, ignore_index=True)
    exp = all_df.groupby("k")["v"].sum()
    np.testing.assert_array_equal(
        got["s"].to_numpy(), exp.sort_index().to_numpy()
    )


def test_prepare_wrm_carries_backend_wedged():
    """The worker's register/heartbeat message surfaces the latch so
    rpc.info() gives operators degraded-mode visibility."""
    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode.__new__(WorkerNode)
    worker.worker_id = "w1"
    worker.node_name = "n1"
    worker.data_dir = "/tmp"
    worker.data_files = []
    worker.workertype = "calc"
    worker.start_time = time.time()
    worker.msg_count = 0
    devicehealth.force_state(False)
    assert worker.prepare_wrm()["backend_wedged"] is False
    devicehealth.force_state(True)
    assert worker.prepare_wrm()["backend_wedged"] is True


def test_wedged_cluster_serves_via_rpc(tmp_path, monkeypatch):
    """Full-stack degraded mode: a live (threads-as-nodes) cluster with the
    backend latched answers an RPC groupby exactly, and rpc.info() shows
    the worker advertising backend_wedged."""
    import logging
    import os

    # the JAX warmup daemon thread is pointless here (the backend is
    # latched) and a thread mid-compile at this short session's interpreter
    # exit aborts pthread teardown ("FATAL: exception not rethrown" —
    # the known gotcha; same pin as test_cluster_resilience)
    monkeypatch.setenv("BQUERYD_TPU_WARMUP", "0")

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage.ctable import ctable
    from bqueryd_tpu.worker import WorkerNode
    from tests.conftest import wait_until

    rng = np.random.default_rng(9)
    n = 40_000
    df = pd.DataFrame(
        {
            "k": rng.integers(0, 9, n).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
        }
    )
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"))
    url = f"mem://wedge-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.2,
    )
    worker = WorkerNode(
        coordination_url=url, data_dir=str(tmp_path),
        loglevel=logging.WARNING, restart_check=False,
        heartbeat_interval=0.2, poll_timeout=0.1,
    )
    threads = [
        threading.Thread(target=controller.go, daemon=True),
        threading.Thread(target=worker.go, daemon=True),
    ]
    for t in threads:
        t.start()
    try:
        devicehealth.force_state(True)
        wait_until(lambda: controller.worker_map, desc="worker registration")
        rpc = RPC(coordination_url=url, timeout=30,
                  loglevel=logging.WARNING)
        wait_until(
            lambda: any(
                w.get("data_files")
                for w in rpc.info().get("workers", {}).values()
            ),
            desc="worker registered",
        )
        got = rpc.groupby(
            ["t.bcolzs"], ["k"], [["v", "sum", "s"]], []
        ).sort_values("k").reset_index(drop=True)
        exp = df.groupby("k")["v"].sum()
        np.testing.assert_array_equal(
            got["s"].to_numpy(), exp.sort_index().to_numpy()
        )
        # heartbeats advertise the latch within an interval
        wait_until(
            lambda: any(
                w.get("backend_wedged")
                for w in rpc.info().get("workers", {}).values()
            ),
            desc="wedged flag visible in info()",
        )
    finally:
        devicehealth.force_state(False)
        worker.stop()
        controller.stop()
        for t in threads:
            t.join(timeout=10)


def test_wedge_marker_catches_transient_wedge():
    """A wedge that latches and recovers INSIDE a window must dirty the
    window even though both endpoint reads say not-wedged."""
    clean_start = devicehealth.wedge_marker()
    assert not devicehealth.window_dirty(clean_start)
    devicehealth.latch_wedged()
    devicehealth.force_state(False)  # recovered before the end read
    assert devicehealth.backend_wedged(launch=False) is False
    assert devicehealth.window_dirty(clean_start), (
        "transient wedge inside the window must dirty it"
    )


def test_detection_disabled_by_zero_timeout(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_PROBE_TIMEOUT_S", "0")
    # even a forced latch reads False while disabled, and no probe launches
    devicehealth.force_state(True)
    assert devicehealth.backend_wedged() is False
