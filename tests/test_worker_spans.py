"""The two levels of the worker's tracing.

With ``BQUERYD_TPU_PROFILE`` unset a calc reply is the parent's, key for
key: the coarse phases, their spans, nothing else (the lists pinned here
were read off the tree before the detail spans existed).  With it set, the
detail spans of ``utils.tracing.detail`` appear nested in the phase they
belong to, as spans only — ``phase_timings`` gains ``post_prev`` and no
other key — and the loop thread's annotations cover a unit end to end.
The compile mark (``compiled``) is always on and absent in steady state.
"""

import logging
import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.messages import CalcMessage
from bqueryd_tpu.utils import tracing
from conftest import wait_until

TRACE_ID = "a" * 32
SLACK_S = 0.05

# -- what the parent's reply holds (switch unset, steady state) ---------------
ENVELOPE = {"created", "msg_type", "params", "payload", "token", "trace",
            "version"}
REPLY_KEYS = {
    "solo": ENVELOPE | {"data", "effective_strategy", "merge_mode",
                        "phase_timings", "spans"},
    "bundle": ENVELOPE | {"bundle", "bundle_members", "data",
                          "effective_strategy", "filename", "member_shares",
                          "merge_mode", "phase_timings", "spans"},
    "dag": ENVELOPE | {"data", "effective_strategy", "merge_mode",
                       "phase_timings", "spans"},
}
PHASE_KEYS = {
    "solo": {"_total", "aggregate", "align", "collect", "fetch", "layout",
             "mask", "open", "prune", "serialize"},
    "bundle": {"_total", "aggregate", "align", "collect", "fetch", "layout",
               "mask", "open", "serialize"},
    "dag": {"_total", "aggregate", "align", "collect", "fetch", "layout",
            "open", "prune", "serialize"},
}
SPAN_NAMES = {
    "solo": {"calc", "storage_decode", "prune", "align", "filter",
             "h2d_transfer", "kernel", "d2h_fetch", "merge",
             "reply_serialization"},
    "bundle": {"calc", "storage_decode", "align", "filter", "h2d_transfer",
               "kernel", "d2h_fetch", "merge", "reply_serialization"},
    "dag": {"calc", "storage_decode", "prune", "align", "h2d_transfer",
            "kernel", "d2h_fetch", "merge", "reply_serialization"},
}

# -- the detail names (ISSUE 27, Tentpole 2) and the phase round each ---------
IN_CALC = ("parse", "cache_probe", "mem_sample", "table_keys")
IN_LAYOUT = ("layout_fold", "layout_pack", "layout_h2d", "layout_columns")
IN_KERNEL = ("aggregate_launch", "aggregate_wait")
ENCLOSING = {
    **{name: ("calc",) for name in IN_CALC},
    **{name: ("h2d_transfer",) for name in IN_LAYOUT},
    **{name: ("kernel",) for name in IN_KERNEL},
    # a bundle packs its members' stacked masks inside its ``mask`` phase
    "layout_pack": ("h2d_transfer", "filter"),
}


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    """One calc worker driven directly (no loop thread): ``run(kind)``
    hands ``handle_work`` a solo, a bundle or an extended-DAG unit with a
    filter constant of its own, so no cache answers."""
    from bqueryd_tpu.plan import bundle as bundlemod
    from bqueryd_tpu.plan import dag as dagmod
    from bqueryd_tpu.plan import plan_groupby
    from bqueryd_tpu.storage import ctable
    from bqueryd_tpu.worker import WorkerNode

    patch = pytest.MonkeyPatch()
    # small tables go by the mesh executor, not by the host kernels
    patch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    patch.setenv("BQUERYD_TPU_WARMUP", "0")
    patch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    root = tmp_path_factory.mktemp("spans")
    rng = np.random.default_rng(27)
    names = []
    for i in range(3):
        n = 4000
        frame = pd.DataFrame({
            "k": rng.integers(0, 7, n).astype(np.int64),
            "v": rng.integers(0, 100, n).astype(np.int64),
            "u": rng.integers(0, 100, n).astype(np.int64),
            "w": rng.random(n) * 10,
            # past _DENSE_SUM_GROUPS groups: a float64 sum goes segmented
            "k3000": rng.integers(0, 3000, n).astype(np.int64),
            # one measure column per test that needs one no query has read
            **{f"cold_{kind}": rng.integers(0, 100, n).astype(np.int64)
               for kind in ("solo", "bundle", "dag", "loop")},
        })
        names.append(f"s{i}.bcolzs")
        ctable.fromdataframe(frame, str(root / names[-1]))
    worker = WorkerNode(
        coordination_url=f"mem://spans-{os.urandom(4).hex()}",
        data_dir=str(root), loglevel=logging.WARNING, restart_check=False,
    )
    sent = []
    worker.send = lambda addr, msg: sent.append(msg)
    worker.send_to_all = lambda msg: None
    constants = iter(np.linspace(0.5, 9.5, 400))

    def message(kind, measure="v", key="k"):
        n = float(next(constants))
        msg = CalcMessage({"payload": "groupby", "token": os.urandom(4).hex()})
        msg.set_trace({"trace_id": TRACE_ID, "span_id": "b" * 16})
        if kind == "solo":
            msg.set_args_kwargs(
                [names, [key], [[measure, "sum", "s"]], [["w", ">", n]]], {}
            )
        elif kind == "bundle":
            plans = [
                plan_groupby(names, [key], [[measure, "sum", "s"]],
                             [["w", ">", n + d]])
                for d in (0.0, 0.01)
            ]
            msg["filename"] = names
            msg.add_as_binary("bundle", bundlemod.bundle_fragment(
                plans[0], names, [("m0", plans[0], None), ("m1", plans[1], None)],
            ))
            msg.set_args_kwargs([names, [], [], []], {})
        else:
            dag = dagmod.compile_query({
                "table": names, "groupby": [key],
                "aggs": [[measure, "sum", "s"],
                         [measure, "topk", "top", {"k": 2}]],
                "where": [["w", ">", n]],
            })
            msg.add_as_binary("dag", dag.to_wire())
            msg.set_args_kwargs([names, [], [], []], {})
        return msg

    def run(kind, measure="v", key="k"):
        return worker.handle_work(message(kind, measure, key))

    yield {"worker": worker, "run": run, "message": message, "sent": sent,
           "names": names}
    worker.socket.close()
    patch.undo()


def span_names(reply):
    return [s["name"] for s in reply["spans"]]


def interval(span):
    return span["start_ts"], span["start_ts"] + span["duration_s"]


# -- (a) switch unset: the parent's reply --------------------------------------

@pytest.mark.parametrize("kind", ["solo", "bundle", "dag"])
def test_with_the_switch_unset_the_reply_is_the_parents(node, monkeypatch, kind):
    monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    node["run"](kind)   # whatever compiles, compiles here
    reply = node["run"](kind)
    assert set(reply) == REPLY_KEYS[kind]
    assert set(reply["phase_timings"]) == PHASE_KEYS[kind]
    assert set(span_names(reply)) == SPAN_NAMES[kind]
    assert reply["merge_mode"] == "device"


def test_with_the_switch_unset_every_detail_site_gets_one_shared_no_op(monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    timer = tracing.PhaseTimer()
    first = tracing.detail("layout_fold", timer)
    assert first is tracing.detail("calc", trace_id="x", wall_ts=1.0)
    assert first is tracing.detail("aggregate_wait", None)
    with first, first:   # re-entrant: sites nest
        pass
    assert timer.timings == {}
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "0")
    assert tracing.detail("parse", timer) is first


# -- (b) switch set: the detail spans, nested, spans only ------------------------

@pytest.mark.parametrize("kind", ["solo", "bundle", "dag"])
def test_with_the_switch_set_detail_spans_nest_in_their_phase(node, monkeypatch, kind):
    from bqueryd_tpu.obs.trace import SpanRecorder

    node["run"](kind, measure="v")
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    threads = set()
    record = SpanRecorder.record

    def noting(self, *args, **kwargs):
        threads.add(threading.get_ident())
        return record(self, *args, **kwargs)

    monkeypatch.setattr(SpanRecorder, "record", noting)
    # a measure column no query has read: decode + pack on this very pass.
    # (A bundle's codes carry no mask, so they pack once per key: it groups
    # by a key no bundle has had.  It takes no memory sample.)
    reply = node["run"](kind, measure=f"cold_{kind}",
                        key="u" if kind == "bundle" else "k")
    names = span_names(reply)
    assert set(names) - set(ENCLOSING) == SPAN_NAMES[kind]
    # a bundle's and a DAG's codes carry no mask of ``execute``'s kind and
    # come from the alignment at their width (PR 30): nothing folds there
    expected = set(ENCLOSING) - {
        "solo": set(), "bundle": {"mem_sample", "layout_fold"},
        "dag": {"layout_fold"},
    }[kind]
    assert "layout_fold" not in set(names) - expected
    assert expected <= set(names), sorted(expected - set(names))
    # the look-ups (result cache; the delta cache's own, for a plain
    # mergeable shape), then the stores; a bundle probes once
    assert names.count("cache_probe") == {"solo": 3, "dag": 2, "bundle": 1}[kind]
    if kind != "bundle":
        assert names.count("mem_sample") == 2
    for span in reply["spans"]:
        assert span["trace_id"] == TRACE_ID
        outer = ENCLOSING.get(span["name"])
        if outer is None:
            continue
        start, end = interval(span)
        # (a span's start is wall clock, its length perf_counter: room for a
        # loaded machine to come between the two readings)
        assert any(
            interval(o)[0] - SLACK_S <= start and end <= interval(o)[1] + SLACK_S
            for o in reply["spans"] if o["name"] in outer
        ), (span["name"], "outside every", outer)
    # spans only: no phase key but post_prev, no debit
    assert set(reply["phase_timings"]) - {"post_prev"} == PHASE_KEYS[kind]
    assert threads == {threading.get_ident()}   # nothing from a pool thread
    if kind != "bundle":   # a bundle's kernel span carries no route tag either
        wait = next(s for s in reply["spans"] if s["name"] == "aggregate_wait")
        assert wait["tags"]["effective_strategy"] == reply["effective_strategy"]


def test_the_detail_spans_take_nothing_off_the_aggregate_phase(node, monkeypatch):
    import jax

    node["run"]("solo")
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    ready = jax.block_until_ready

    def slow(out):   # the device's time, as the host waits for it
        time.sleep(0.2)
        return ready(out)

    monkeypatch.setattr(jax, "block_until_ready", slow)
    reply = node["run"]("solo")
    seconds = {
        name: sum(s["duration_s"] for s in reply["spans"] if s["name"] == name)
        for name in ("aggregate_launch", "aggregate_wait", "d2h_fetch", "kernel")
    }
    inside = (seconds["aggregate_launch"] + seconds["aggregate_wait"]
              + seconds["d2h_fetch"])
    assert seconds["aggregate_wait"] >= 0.2
    # ``aggregate`` is the kernel span less the fetch (the one debit there
    # is, the parent's): the detail spans lie inside it and leave it whole
    assert reply["phase_timings"]["aggregate"] == pytest.approx(inside, rel=0.1)
    assert reply["phase_timings"]["aggregate"] >= (
        seconds["aggregate_launch"] + seconds["aggregate_wait"])
    assert seconds["kernel"] >= inside


@pytest.mark.parametrize("profile", ["1", None], ids=["traced", "untraced"])
def test_the_float_sum_tag_and_counter_exist_under_the_switch_only(
        node, groupby_as_accelerator, monkeypatch, profile):
    """PR 31: which form the float64 sum took (``dense`` here: seven
    groups) is detail — a tag on the ``aggregate_wait`` span beside
    ``effective_strategy`` and one labelled counter, both only on a worker
    under the switch; the reply gains no key either way."""
    if profile:
        monkeypatch.setenv("BQUERYD_TPU_PROFILE", profile)
    else:
        monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    worker = node["worker"]

    def counted():
        return {
            tuple(m.labels.items()): m.value for m in worker.metrics.metrics()
            if m.name == "bqueryd_tpu_float_sum_total"
        }

    node["run"]("solo", measure="w")   # the fixture emptied the jit caches
    before = counted()
    reply = node["run"]("solo", measure="w")   # a float64 sum
    assert reply["effective_strategy"] == "matmul"
    assert set(reply) == REPLY_KEYS["solo"]
    tags = [s.get("tags", {}) for s in reply["spans"]
            if s["name"] == "aggregate_wait"]
    if profile:
        assert [t["float_sum"] for t in tags] == ["dense"]
        assert tags[0]["effective_strategy"] == "matmul"
        key = (("form", "dense"),)
        assert counted()[key] == before.get(key, 0.0) + 1
        node["run"]("solo", measure="v")   # an int64 sum: nothing to name
        assert counted()[key] == before.get(key, 0.0) + 1
    else:
        assert tags == []
        assert counted() == before
    kernel = next(s for s in reply["spans"] if s["name"] == "kernel")
    assert "float_sum" not in kernel.get("tags", {})


@pytest.mark.parametrize("profile", ["1", None], ids=["traced", "untraced"])
def test_a_segmented_float_sum_gets_a_wait_span_of_its_own(
        node, groupby_as_accelerator, monkeypatch, profile):
    """PR 38: above ``_DENSE_SUM_GROUPS`` groups a float64 sum on an
    accelerator takes the segmented form, and the wait for that launch is
    also the detail span ``float_sum_wait`` — inside ``aggregate_wait``,
    tagged ``form``, under the switch only; the ``float_sum`` tag and the
    counter carry the form's name.  A dense or integer sum has no such
    span."""
    if profile:
        monkeypatch.setenv("BQUERYD_TPU_PROFILE", profile)
    else:
        monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    worker = node["worker"]

    def counted(form):
        return sum(
            m.value for m in worker.metrics.metrics()
            if m.name == "bqueryd_tpu_float_sum_total" and m.labels == {"form": form}
        )

    node["run"]("solo", measure="w", key="k3000")   # compiles here
    before = counted("segmented")
    reply = node["run"]("solo", measure="w", key="k3000")
    assert reply["effective_strategy"] == "matmul"
    assert set(reply) == REPLY_KEYS["solo"]
    spans = {name: [s for s in reply["spans"] if s["name"] == name]
             for name in ("aggregate_wait", "float_sum_wait")}
    if profile:
        (outer,), (inner,) = spans["aggregate_wait"], spans["float_sum_wait"]
        assert inner["tags"] == {"form": "segmented"}
        assert outer["tags"]["float_sum"] == "segmented"
        assert interval(outer)[0] - SLACK_S <= interval(inner)[0]
        assert interval(inner)[1] <= interval(outer)[1] + SLACK_S
        assert counted("segmented") == before + 1
        for measure, key in (("w", "k"), ("v", "k3000")):   # dense; an int64 sum
            other = node["run"]("solo", measure=measure, key=key)
            assert "float_sum_wait" not in span_names(other)
    else:
        assert spans == {"aggregate_wait": [], "float_sum_wait": []}
        assert counted("segmented") == before


@pytest.mark.parametrize("kind", ["solo", "dag"])
@pytest.mark.parametrize("profile", ["1", None], ids=["traced", "untraced"])
def test_the_merge_tags_exist_under_the_switch_only(node, monkeypatch, profile, kind):
    """PR 34: which merge a launch ran and over how many devices is detail
    — two tags on the ``aggregate_wait`` span, the ``merge_mode`` the reply
    already sends and the mesh's size; without the switch there is no such
    span, and the reply gains no key either way."""
    if profile:
        monkeypatch.setenv("BQUERYD_TPU_PROFILE", profile)
    else:
        monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    node["run"](kind)   # steady state: a first pass marks its compile
    reply = node["run"](kind)
    assert set(reply) == REPLY_KEYS[kind]
    tags = [s.get("tags", {}) for s in reply["spans"]
            if s["name"] == "aggregate_wait"]
    if profile:
        mesh = node["worker"].mesh_executor.mesh
        assert reply["merge_mode"] == "device"
        assert [(t["merge_mode"], t["devices"]) for t in tags] == [
            ("device", mesh.devices.size)]
        assert "float_sum" not in tags[0]   # an int64 sum: nothing to name
    else:
        assert tags == []
    kernel = next(s for s in reply["spans"] if s["name"] == "kernel")
    assert not {"merge_mode", "devices"} & set(kernel.get("tags", {}))


def test_the_merge_tag_names_the_host_merge_under_its_kill_switch(node, monkeypatch):
    """A launch whose partial tables were merged on the host does not pass
    for the device merge in a timeline."""
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "0")
    reply = node["run"]("solo")
    assert reply["merge_mode"] == "host"
    tags = [s["tags"] for s in reply["spans"] if s["name"] == "aggregate_wait"]
    assert [t["merge_mode"] for t in tags] == ["host"]
    assert tags[0]["devices"] == node["worker"].mesh_executor.mesh.devices.size


# -- (c) the loop thread's annotations --------------------------------------------

class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: every annotation
    opened, with its thread, its arguments and its interval."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **kwargs):
        book = self

        class Annotation:
            def __enter__(self):
                self.event = {"name": name, "args": kwargs,
                              "thread": threading.get_ident(),
                              "start": time.perf_counter()}
                book.events.append(self.event)
                return self

            def __exit__(self, *exc):
                self.event["end"] = time.perf_counter()
                return False

        return Annotation()


def test_the_loop_threads_annotations_cover_one_unit_end_to_end(node, monkeypatch):
    import jax.profiler

    worker = node["worker"]
    node["run"]("solo")
    book = Annotations()
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", book)
    msg = node["message"]("solo", measure="cold_loop")
    wire = [b"controller", msg.to_json().encode()]

    class Socket:
        closed = False

        def recv_multipart(self):
            return wire

    monkeypatch.setattr(worker, "socket", Socket())
    del node["sent"][:]
    wall_before = time.time()
    worker.handle_in()
    assert len(node["sent"]) == 1 and node["sent"][0]["msg_type"] != "error"
    me = threading.get_ident()
    assert {e["thread"] for e in book.events} == {me}
    names = [e["name"] for e in book.events]
    assert names[0] == "calc" and names[-1] == "post" and names[-2] == "send"
    expected = (
        {"calc", "send", "post", "open", "prune", "align", "mask", "layout",
         "aggregate", "collect", "serialize"} | set(ENCLOSING)
    )
    assert expected <= set(names), sorted(expected - set(names))
    calc = book.events[0]
    assert calc["args"]["trace_id"] == TRACE_ID
    assert wall_before <= calc["args"]["wall_ts"] <= time.time()
    for event in book.events[1:]:
        assert calc["start"] <= event["start"] and event["end"] <= calc["end"]
        if event["name"] not in ("send", "post"):
            assert event["args"].get("trace_id") == TRACE_ID, event["name"]
    # the seconds after the reply ride the next one
    reply = node["run"]("solo")
    after = [e for e in book.events if e["name"] in ("send", "post")][:2]
    assert reply["phase_timings"]["post_prev"] == pytest.approx(
        sum(e["end"] - e["start"] for e in after), abs=SLACK_S)
    assert "post_prev" not in node["run"]("solo")["phase_timings"]


def test_the_worker_loop_annotates_its_wait_and_its_heartbeat(tmp_path, monkeypatch):
    import jax.profiler

    from bqueryd_tpu.worker import WorkerBase

    book = Annotations()
    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", book)
    worker = WorkerBase(
        coordination_url=f"mem://loop-{os.urandom(4).hex()}",
        data_dir=str(tmp_path), loglevel=logging.WARNING,
        restart_check=False, poll_timeout=0.02,
    )
    thread = threading.Thread(target=worker.go, daemon=True)
    thread.start()
    try:
        wait_until(
            lambda: {"wait_for_work", "heartbeat"} <= {e["name"] for e in book.events},
            timeout=10, desc="the loop's annotations",
        )
    finally:
        worker.running = False
        thread.join(timeout=5)
    assert not thread.is_alive()
    assert {e["thread"] for e in book.events} == {thread.ident}


# -- (d) the compile mark ---------------------------------------------------------

def test_a_unit_that_compiled_says_so_and_the_repeat_does_not(node, monkeypatch):
    monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    # a shape no test here has run: two keys, a mean
    msg = node["message"]("solo")
    msg.set_args_kwargs(
        [node["names"], ["k", "u"], [["w", "mean", "m"]], [["w", ">", 0.25]]], {}
    )
    first = node["worker"].handle_work(msg)
    assert first["compiled"] >= 1
    msg.set_args_kwargs(
        [node["names"], ["k", "u"], [["w", "mean", "m"]], [["w", ">", 0.35]]], {}
    )
    assert "compiled" not in node["worker"].handle_work(msg)


def test_the_client_reads_the_compile_mark(tmp_path, monkeypatch):
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.storage import ctable
    from bqueryd_tpu.worker import WorkerNode

    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    monkeypatch.setenv("BQUERYD_TPU_WARMUP", "0")
    rng = np.random.default_rng(5)
    n = 3000
    frame = pd.DataFrame({
        "g": rng.integers(0, 11, n).astype(np.int64),
        "x": rng.integers(0, 50, n).astype(np.int64),
        "y": rng.random(n),
    })
    ctable.fromdataframe(frame, str(tmp_path / "t.bcolzs"))
    url = f"mem://mark-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING,
        runfile_dir=str(tmp_path), heartbeat_interval=0.2,
    )
    worker = WorkerNode(
        coordination_url=url, data_dir=str(tmp_path), loglevel=logging.WARNING,
        restart_check=False, heartbeat_interval=0.2, poll_timeout=0.1,
    )
    threads = [threading.Thread(target=n_.go, daemon=True) for n_ in (controller, worker)]
    for thread in threads:
        thread.start()
    try:
        wait_until(lambda: controller.files_map.get("t.bcolzs"), desc="registration")
        rpc = RPC(coordination_url=url, timeout=120, loglevel=logging.WARNING)
        assert rpc.last_call_compiled is None
        # 13 groups of a product no other test's program has: min + max
        args = (["t.bcolzs"], ["g"], [["x", "min", "lo"], ["x", "max", "hi"]])
        rpc.groupby(*args, [["y", ">", 0.125]])
        assert rpc.last_call_compiled >= 1
        rpc.groupby(*args, [["y", ">", 0.375]])
        assert rpc.last_call_compiled == 0
    finally:
        for n_ in (controller, worker):
            n_.running = False
        for thread in threads:
            thread.join(timeout=5)
