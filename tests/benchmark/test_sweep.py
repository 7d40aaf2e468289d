"""The sweep of the device's idle time (``benchmark/sweep.py``; PR 40):
every idle interval cut at the worker's annotations, the profiler's clock
mapped onto the spans' by the ``calc`` annotation's ``wall_ts``, and the
pieces the worker spent waiting for work booked to the controller's and
the client's spans.  Plain Python over plain lists, like the tests of
``reduce_planes``, but for one profile the CPU backend records."""

import json
import os
import time

import pytest
from test_perf_benchmark import HERE, time_limit  # noqa: F401

from benchmark import in_worker, sweep

#: the wall clock at the profiler's zero, in ns, for the hand-made planes
OFFSET_NS = 1_700_000_000 * 10**9
#: the hand-made planes' unit, 1 ms in ns: well above a float64 wall
#: clock's grain (about 0.24 us at 1.7e9 s)
US = 10**6


def gap_planes():
    """One device gap, 2..8 ms, that runs from the worker's ``serialize``
    through ``wait_for_work`` into the next query's ``open``; the ``calc``
    annotations carry the clock."""
    return {
        "/device:TPU:0": {"XLA Ops": [["sort", US, US], ["fusion", 8 * US, US]]},
        "/host:CPU": {"python": [[name, start * US // 1000, dur * US // 1000] for name, start, dur in (
            ["calc", 1000, 1500], ["serialize", 1500, 1000],
            ["wait_for_work", 2500, 4000],
            ["calc", 6500, 2500], ["open", 6500, 2000],
        )]},
    }


def gap_walls():
    return [[1000 * US // 1000, (OFFSET_NS + 1000 * US // 1000) / 1e9],
            [6500 * US // 1000, (OFFSET_NS + 6500 * US // 1000) / 1e9]]


def test_a_gap_is_cut_at_the_annotations_and_not_booked_by_its_middle():
    planes = gap_planes()
    out = sweep.sweep(planes, gap_walls())
    assert dict(out["idle_swept"]) == {
        "serialize": pytest.approx(0.5e-3), "wait_for_work": pytest.approx(4e-3),
        "open": pytest.approx(1.5e-3),
    }
    assert sum(s for _n, s in out["idle_swept"]) == pytest.approx(6e-3)
    # booked by its middle, the same gap goes wholly to wait_for_work
    assert dict(in_worker.reduce_planes(planes)["idle_gaps"]) == {
        "wait_for_work": pytest.approx(6e-3)}
    # the piece the worker spent waiting, on the wall clock
    ((start, end, name),) = out["idle_pieces"]
    assert name == "wait_for_work"
    assert start == pytest.approx(OFFSET_NS / 1e9 + 2.5e-3, abs=1e-6)
    assert end - start == pytest.approx(4e-3, abs=1e-6)


def test_the_clock_offset_is_recovered_from_the_calc_events():
    offset, spread = sweep.clock_offset(gap_walls())
    assert offset == pytest.approx(OFFSET_NS, abs=512)   # a float64 of ~1.7e18
    assert spread < 1000
    jittered = gap_walls() + [[9 * US, (OFFSET_NS + 9 * US + 40_000) / 1e9]]
    offset, spread = sweep.clock_offset(jittered)
    assert offset == pytest.approx(OFFSET_NS, abs=512)   # the median holds
    assert spread == pytest.approx(40_000, abs=1024)
    assert sweep.clock_offset([]) == (None, None)
    assert "idle_pieces" not in sweep.sweep(gap_planes())   # no clock, no pieces


def test_the_innermost_annotation_takes_each_instant():
    host = [(0, 100, "calc"), (10, 50, "open"), (20, 30, "decode"), (40, 120, "other")]
    assert sweep._tile(sorted(host), 0, 130) == [
        [0, 10, "calc"], [10, 20, "open"], [20, 30, "decode"], [30, 40, "open"],
        [40, 120, "other"], [120, 130, sweep.NO_SPAN]]


def test_the_recorded_trace_keeps_every_number_of_the_midpoint_table_and_the_pieces_sum_to_idle():
    """A slice of a TPU v5 lite trace of the lowcard mix (chip run, PR 25)."""
    planes = json.load(open(os.path.join(HERE, "recorded_planes.json")))
    before = json.dumps(in_worker.reduce_planes(planes))
    out = dict(in_worker.reduce_planes(planes), **sweep.sweep(planes))
    assert json.dumps({k: out[k] for k in json.loads(before)}) == before
    idle = out["window_s"] - out["busy_s"]
    assert sum(s for _n, s in out["idle_swept"]) == pytest.approx(idle, abs=1e-6)
    assert len(out["idle_swept"]) > 1 and all(s > 0 for _n, s in out["idle_swept"])
    assert sweep.sweep({"/host:CPU": {"t": [["x", 0, 5]]}}) == {}


def test_the_waiting_pieces_are_booked_to_the_controller_and_the_client():
    """The worker waits 2.5..6.5 ms on the profiler's clock; on the wall
    clock the controller encodes the reply, the client decodes it, nothing
    runs for a while, the controller decodes the next request and has it
    in flight to the worker."""
    out = sweep.sweep(gap_planes(), gap_walls())
    out["device_planes"] = 1
    t0 = OFFSET_NS / 1e9

    def span(name, lo_us, hi_us):
        return {"name": name, "start_ts": t0 + lo_us / 1e6, "duration_s": (hi_us - lo_us) / 1e6}

    timelines = [
        {"spans": [span("groupby", 0, 3000), span("reply_encode", 2000, 3000),
                   span("client_decode", 3000, 4000), span("finalize", 3000, 3500),
                   span("calc", 500, 2500)]},
        {"spans": [span("client_encode", 4500, 5000), span("request_decode", 5000, 5500),
                   span("groupby", 5500, 9000), span("inflight", 6000, 9000)]},
    ]
    table = dict(sweep.idle_by_host(out, timelines, top=20))
    expect = {
        "controller:reply_encode": 500, "client:client_decode": 500,
        "controller:finalize": 500, "between": 500, "client:client_encode": 500,
        "controller:request_decode": 500, "controller:groupby": 500,
        "controller:inflight": 500, "worker:open": 1500, "worker:serialize": 500,
    }
    # what the wall clock's float64 grain leaves with wait_for_work is dust
    dust = {k: v for k, v in table.items() if k not in expect}
    assert set(dust) <= {"worker:wait_for_work"} and sum(dust.values()) < 1e-6
    assert {k: table[k] for k in expect} == {
        k: pytest.approx(v * 1e-6, abs=1e-6) for k, v in expect.items()}
    # nothing is lost: the table still sums to the idle time
    assert sum(table.values()) == pytest.approx(6e-3, abs=1e-6)
    assert sweep.idle_by_host({}, timelines) is None


def test_the_reader_reads_the_wait_per_query_of_the_slice_and_nothing_from_a_parent():
    record = {"ok": True, "t_send": 1.0, "t_reply": 1.01, "trace_id": "t"}
    ev = {"records": [record, dict(record, t_send=1.01, t_reply=1.02)], "slice": [1.005, 2.0],
          "device_trace": {"busy_s": 0.001, "window_s": 1.0,
                           "idle_swept": [["wait_for_work", 0.003], ["open", 0.001]]}}
    assert sweep.trace_idle_booked(ev) == pytest.approx(1000 * 0.003 / 1.5)
    assert sweep.trace_idle_booked(ev, name="open") == pytest.approx(1000 * 0.001 / 1.5)
    assert sweep.trace_idle_booked(ev, name="parse") == 0.0
    ev["device_trace"] = {"busy_s": 0.001, "window_s": 1.0, "idle_gaps": []}   # the parent's
    assert sweep.trace_idle_booked(ev) is None
    assert sweep.trace_idle_booked({"records": []}) is None


def test_the_clock_is_read_off_a_profile_the_backend_records(tmp_path):
    """``load_walls`` on a real ``.xplane.pb``: the annotation the worker
    makes (``tracing.detail("calc", wall_ts=...)``), as the profiler keeps it."""
    import jax
    import jax.numpy as jnp

    logdir = str(tmp_path / "trace")
    jax.profiler.start_trace(logdir)
    before = time.time()
    try:
        with jax.profiler.TraceAnnotation("calc", trace_id="x", wall_ts=time.time()):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _s, fs in os.walk(logdir) for f in fs
               if f.endswith(".xplane.pb")]
    ((start_ns, wall_ts),) = sweep.load_walls(path)
    assert before <= wall_ts <= time.time() and start_ns >= 0
