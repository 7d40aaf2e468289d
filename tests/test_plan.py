"""Query planning & admission subsystem: plan compilation round-trips,
rewrite rules, stats-based shard pruning, cost-based kernel-strategy
selection, admission backpressure (BUSY), deadline propagation, and
multi-query shared dispatch."""

import logging
import os
import pickle
import time

import numpy as np
import pytest

from bqueryd_tpu import plan as planmod
from bqueryd_tpu.controller import ControllerNode
from bqueryd_tpu.messages import CalcMessage, Message, RPCMessage, msg_factory
from bqueryd_tpu.plan import (
    AdmissionController,
    LogicalPlan,
    compile_groupby,
    fragment_for,
    fragment_to_query,
    plan_groupby,
    stats_can_match,
)


# -- logical plans -----------------------------------------------------------

def test_compile_normalizes_and_dedups():
    plan = plan_groupby(
        ["a.bcolzs", "a.bcolzs", "b.bcolzs"],
        ["k"],
        ["v", ["w", "count"], ["v", "mean", "m"]],
        [["x", ">", 5]],
    )
    assert plan.filenames == ["a.bcolzs", "b.bcolzs"]
    assert plan.physical_agg_list() == [
        ["v", "sum", "v"], ["w", "count", "w"], ["v", "mean", "m"],
    ]
    # predicate pushdown moved the filter into the scan node
    assert plan.scan.pushdown == [("x", ">", 5)]
    assert plan.filter.terms == []
    assert "predicate_pushdown" in plan.rewrites
    # every touched column appears exactly once in the scan
    assert plan.scan.columns == ["k", "v", "w", "x"]


def test_mean_decomposition_rewrite():
    plan = plan_groupby(
        ["a.bcolzs"], ["k"],
        [["v", "mean", "m"], ["v", "sum", "s"], ["v", "count", "n"]],
        [],
    )
    assert "mean_decomposition" in plan.rewrites
    # primitives are deduplicated: mean's sum+count share the explicit ones
    assert [(a[0], a[1]) for a in plan.aggregate.aggs] == [
        ("v", "sum"), ("v", "count"),
    ]
    exprs = dict(plan.project.exprs)
    assert exprs["m"][0] == "div"
    # physical reconstruction restores the original output list in order
    assert plan.physical_agg_list() == [
        ["v", "mean", "m"], ["v", "sum", "s"], ["v", "count", "n"],
    ]


def test_plan_wire_roundtrip():
    plan = plan_groupby(
        ["a.bcolzs"], ["k", "j"],
        [["v", "mean", "m"]],
        [["x", "in", [1, 2]]],
        aggregate=True,
        expand_filter_column="basket",
    )
    back = LogicalPlan.from_wire(plan.to_wire())
    assert back.physical_agg_list() == plan.physical_agg_list()
    assert back.where_terms == plan.where_terms
    assert back.signature() == plan.signature()
    assert "Scan" in back.explain()


def test_fragment_roundtrip_to_query():
    plan = plan_groupby(
        ["a.bcolzs", "b.bcolzs"], ["k"],
        [["v", "mean", "m"]], [["x", "<=", 9]],
    )
    frag = fragment_for(plan, ["a.bcolzs"], sole=True)
    query = fragment_to_query(frag)
    assert query.groupby_cols == ["k"]
    assert query.agg_list == [["v", "mean", "m"]]
    assert query.where_terms == [("x", "<=", 9)]
    assert query.sole_payload is True
    assert "strategy" not in frag  # a fragment names the work, not the kernel
    # fragments survive the message binary-field transport
    msg = CalcMessage({"payload": "groupby"})
    msg.add_as_binary("plan", frag)
    again = msg_factory(msg.to_json()).get_from_binary("plan")
    assert again == frag


def test_identical_plans_share_a_signature():
    a = plan_groupby(["a.bcolzs"], ["k"], [["v", "sum", "v"]], [["x", ">", 1]])
    b = plan_groupby(["b.bcolzs"], ["k"], [["v", "sum", "v"]], [["x", ">", 1]])
    c = plan_groupby(["a.bcolzs"], ["k"], [["v", "sum", "v"]], [["x", ">", 2]])
    assert a.signature() == b.signature()  # shard set is not part of it
    assert a.signature() != c.signature()


# -- stats pruning -----------------------------------------------------------

STATS = {
    "rows": 1000,
    "cols": {
        "x": {"kind": "numeric", "min": 10, "max": 20},
        "d": {"kind": "dict"},
    },
}


@pytest.mark.parametrize(
    "term,expected",
    [
        (("x", "==", 15), True),
        (("x", "==", 25), False),
        (("x", ">", 20), False),
        (("x", ">", 19), True),
        (("x", ">=", 21), False),
        (("x", "<", 10), False),
        (("x", "<=", 9), False),
        (("x", "<=", 10), True),
        (("x", "in", [1, 2, 3]), False),
        (("x", "in", [1, 15]), True),
        (("y", "==", 1), True),       # unknown column: conservative match
        (("d", "==", "blue"), True),  # dict column: no controller pruning
        (("x", "==", "oops"), True),  # non-numeric value: conservative
    ],
)
def test_stats_can_match(term, expected):
    assert stats_can_match(STATS, [term]) is expected


def test_stats_can_match_conjunction():
    assert not stats_can_match(STATS, [("x", ">", 12), ("x", ">", 99)])
    assert stats_can_match(STATS, [("x", ">", 12), ("x", "<", 19)])


def test_garbage_stats_never_prune_and_never_raise():
    """A version-skewed worker can advertise any shape; every consumer must
    degrade (conservative match), never raise mid-launch."""
    assert stats_can_match(5, [("x", ">", 1)]) is True
    assert stats_can_match({"cols": 3}, [("x", ">", 1)]) is True
    bad_bounds = {"cols": {"x": {"kind": "numeric", "min": "a", "max": "b"}}}
    assert stats_can_match(bad_bounds, [("x", ">", 1)]) is True


def shard_stats(rows, cols, lo=0, hi=100):
    return {
        "rows": rows,
        "cols": {c: {"kind": "numeric", "min": lo, "max": hi} for c in cols},
    }


# -- forced routes -----------------------------------------------------------

def _route_shape(shape):
    """(codes, measures, ops, n_groups, mask) of one query shape."""
    rng = np.random.RandomState(7)
    n = 5000
    vals = rng.randint(-(10**12), 10**12, n).astype(np.int64)
    fvals = rng.random(n).astype(np.float64)
    if shape == "two_keys":
        # the composite code of two key columns, radix-packed as the engine
        # packs them
        n_groups = 37 * 11
        codes = (rng.randint(0, 37, n) * 11 + rng.randint(0, 11, n))
    else:
        n_groups = 37
        codes = rng.randint(0, 37, n)
    codes = codes.astype(np.int32)
    mask = rng.random(n) > 0.3 if shape == "filtered" else None
    if shape == "float64_mean":
        return codes, (fvals,), ("mean",), n_groups, mask
    return codes, (vals, fvals), ("sum", "mean"), n_groups, mask


@pytest.mark.parametrize("strategy", ["scatter", "sort", "matmul"])
@pytest.mark.parametrize(
    "shape", ["single_key", "two_keys", "float64_mean", "filtered"])
def test_strategy_hints_are_bit_exact(monkeypatch, shape, strategy):
    """``auto`` computes what every forced route computes: rows, counts and
    integer sums bit for bit, float64 sums to reassociation."""
    import jax

    from bqueryd_tpu import ops

    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    codes, measures, agg_ops, n_groups, mask = _route_shape(shape)

    def run(forced):
        return jax.device_get(
            ops.partial_tables(
                codes, measures, agg_ops, n_groups, mask, strategy=forced,
            )
        )

    base, got = run(None), run(strategy)
    assert jax.tree_util.tree_structure(base) == \
        jax.tree_util.tree_structure(got)
    for want, have in zip(jax.tree_util.tree_leaves(base),
                          jax.tree_util.tree_leaves(got)):
        if np.issubdtype(np.asarray(want).dtype, np.floating):
            np.testing.assert_allclose(have, want, rtol=1e-12)
        else:
            np.testing.assert_array_equal(have, want)
    with pytest.raises(ValueError):
        run("matmul!")  # no longer a route (PR 32); unknown names raise


# -- admission controller ----------------------------------------------------

def test_admission_backpressure_and_release():
    adm = AdmissionController(max_active=1, queue_depth=1, client_quota=0)
    assert adm.submit("t1", "c1", payload="p1") == planmod.ADMIT
    assert adm.submit("t2", "c2", payload="p2") == planmod.QUEUED
    assert adm.submit("t3", "c3", payload="p3") == planmod.BUSY  # queue full
    assert adm.stats()["active"] == 1 and adm.stats()["queued"] == 1
    # resubmission of a live ticket is flagged, never double-counted or
    # re-launched (a client retry must not double the fan-out)
    assert adm.submit("t1", "c1", payload="p1") == planmod.DUPLICATE
    assert adm.submit("t2", "c2", payload="p2") == planmod.DUPLICATE
    assert adm.stats()["active"] == 1 and adm.stats()["queued"] == 1
    adm.release("t1")
    launch, expired = adm.pop_ready()
    assert launch == ["p2"] and expired == []


def test_admission_client_quota():
    adm = AdmissionController(max_active=8, queue_depth=8, client_quota=1)
    assert adm.submit("t1", "same", payload="p1") == planmod.ADMIT
    assert adm.submit("t2", "same", payload="p2") == planmod.BUSY
    assert adm.submit("t3", "other", payload="p3") == planmod.ADMIT
    adm.release("t1")
    assert adm.submit("t4", "same", payload="p4") == planmod.ADMIT


def test_admission_deadline_expiry_in_queue():
    adm = AdmissionController(max_active=1, queue_depth=4)
    assert adm.submit("t1", "c1", payload="p1") == planmod.ADMIT
    assert (
        adm.submit("t2", "c2", deadline=time.time() - 1, payload="p2")
        == planmod.QUEUED
    )
    launch, expired = adm.pop_ready()
    assert launch == [] and expired == ["p2"]
    adm.release("t1")
    assert adm.stats()["active"] == 0 and adm.stats()["queued"] == 0


def test_admission_priority_order():
    adm = AdmissionController(max_active=1, queue_depth=8)
    adm.submit("t0", "c", payload="p0")
    adm.submit("tlow", "c1", priority=5, payload="low")
    adm.submit("thigh", "c2", priority=1, payload="high")
    adm.release("t0")
    launch, _ = adm.pop_ready()
    assert launch == ["high"]


# -- deadline message helpers ------------------------------------------------

def test_message_deadline_helpers():
    msg = Message({"payload": "x"})
    assert msg.deadline_remaining() is None
    assert not msg.deadline_expired()
    msg.set_deadline(seconds=100)
    assert 99 < msg.deadline_remaining() <= 100
    assert not msg.deadline_expired()
    msg.set_deadline(at=time.time() - 1)
    assert msg.deadline_expired()
    # survives serialization and copy
    again = msg_factory(msg.to_json())
    assert again.deadline_expired()
    assert again.copy().deadline_expired()


def test_worker_refuses_expired_work(tmp_path):
    from bqueryd_tpu.worker import WorkerBase

    worker = WorkerBase(
        coordination_url=f"mem://plan-{os.urandom(4).hex()}",
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
    )
    sent = []
    worker.send = lambda addr, m: sent.append(m)
    worker.send_to_all = lambda m: None
    try:
        msg = CalcMessage({"payload": "sleep"})
        msg.set_args_kwargs([0.0], {})
        msg.set_deadline(at=time.time() - 5)
        worker.handle(msg, b"ctrl")
        (reply,) = sent
        assert reply["msg_type"] == "error"
        assert "deadline exceeded" in reply["payload"]
    finally:
        worker.socket.close()


# -- controller integration --------------------------------------------------

@pytest.fixture
def controller(tmp_path):
    node = ControllerNode(
        coordination_url=f"mem://plan-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
    )
    node._replies = []
    node.reply_rpc_raw = (
        lambda client_token, payload: node._replies.append(
            (client_token, payload)
        )
    )
    yield node
    node.socket.close()


def register(controller, worker_id, files, busy=True, stats=None):
    controller.worker_map[worker_id] = {
        "worker_id": worker_id,
        "workertype": "calc",
        "busy": busy,
        "last_seen": time.time(),
        "node": controller.node_name,
    }
    for f in files:
        controller.files_map.setdefault(f, set()).add(worker_id)
        if stats is not None:
            controller.shard_stats[f] = stats.get(f) or stats


def groupby_msg(filenames, where=None, token="00", deadline=None,
                client_id=None, **kwargs):
    msg = RPCMessage({"payload": "groupby", "token": token})
    msg.set_args_kwargs(
        [filenames, ["k"], [["v", "sum", "v"]], where or []], kwargs
    )
    if deadline is not None:
        msg["deadline"] = deadline
    if client_id is not None:
        msg["client_id"] = client_id
    return msg


def queued(controller):
    return [m for q in controller.worker_out_messages.values() for m in q]


def decode_reply(payload):
    return pickle.loads(payload)


def test_plan_time_pruning_skips_excluded_shards(controller):
    stats = {
        "a.bcolzs": shard_stats(100, ["k"], lo=0, hi=50),
        "b.bcolzs": shard_stats(100, ["k"], lo=1000, hi=2000),
    }
    register(
        controller, "w1", ["a.bcolzs", "b.bcolzs"], stats=stats
    )
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs", "b.bcolzs"], where=[["x", ">", 100]])
    )
    # x is unknown in stats -> no pruning on it; prune on k instead
    msgs = queued(controller)
    assert len(msgs) == 1  # batched: both shards still dispatched

    # now a term on k that b's range excludes but a's allows (fresh client
    # token: the first ticket is still active, a reuse would be a DUPLICATE)
    for q in controller.worker_out_messages.values():
        q.clear()
    controller.rpc_segments.clear()
    before = controller.counters["plan_pruned_shards"]
    controller.rpc_groupby(
        groupby_msg(
            ["a.bcolzs", "b.bcolzs"], where=[["k", "<", 60]], token="01"
        )
    )
    (msg,) = queued(controller)
    assert msg["filename"] == "a.bcolzs"  # b pruned, never dispatched
    assert controller.counters["plan_pruned_shards"] - before == 1
    (segment,) = controller.rpc_segments.values()
    assert segment["results"] == {("b.bcolzs",): b""}  # pre-filled empty


def test_all_shards_pruned_replies_immediately(controller):
    stats = {"a.bcolzs": shard_stats(100, ["k"], lo=0, hi=50)}
    register(controller, "w1", ["a.bcolzs"], stats=stats)
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], where=[["k", ">", 99]], token="aa")
    )
    assert not queued(controller)
    assert not controller.rpc_segments  # completed instantly
    ((client, payload),) = controller._replies
    envelope = decode_reply(payload)
    assert envelope["ok"] is True
    assert envelope["payloads"] == [b""]  # one empty payload per shard


def test_planner_disabled_restores_static_fanout(controller, monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_PLANNER", "0")
    stats = {"a.bcolzs": shard_stats(100, ["k"], lo=0, hi=50)}
    register(controller, "w1", ["a.bcolzs"], stats=stats)
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], where=[["k", ">", 99]])
    )
    (msg,) = queued(controller)  # no pruning: dispatched anyway
    assert msg.get_from_binary("plan")["agg_list"] == [["v", "sum", "v"]]


_ROUTE_KEYS = {"strategy", "strategy_binding"}


@pytest.mark.parametrize("path", ["classic", "bundle", "dag"])
def test_dispatch_carries_no_route(controller, monkeypatch, path):
    """Whatever the advertised stats say — here the shape the planner used
    to hint ``matmul`` for — a dispatch names the work and never the kernel:
    no route key on the message nor in its fragment, no hint counter, and
    the segment counts its shards under the one route a dispatch asks for."""
    stats = {"a.bcolzs": shard_stats(10_000_000, ["k"])}
    register(controller, "w1", ["a.bcolzs"], stats=stats)
    if path == "bundle":
        monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "60000")
        controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
        controller.rpc_groupby(
            groupby_msg(["a.bcolzs"], where=[["k", ">", 1]], token="bb")
        )
        controller._flush_window(force=True)
        assert controller.counters["plan_bundles"] == 1
    elif path == "dag":
        msg = RPCMessage({"payload": "query", "token": "00"})
        msg.set_args_kwargs(
            [{"table": "a.bcolzs", "groupby": ["k"],
              "aggs": [["v", "topk", "top", {"k": 2}]]}], {}
        )
        controller.rpc_query(msg)
    else:
        controller.rpc_groupby(groupby_msg(["a.bcolzs"]))
    (shard,) = queued(controller)
    assert bool(shard.get("dag")) == (path == "dag")
    fragment = shard.get_from_binary("bundle" if path == "bundle" else "plan")
    assert not _ROUTE_KEYS & set(shard)
    assert not _ROUTE_KEYS & set(fragment)
    assert fragment["groupby_cols"] == ["k"]
    assert not [c for c in controller.counters if "hint" in c or "calib" in c]
    for segment in controller.rpc_segments.values():
        assert segment["strategies"] == {"auto": 1}


def test_shared_dispatch_fuses_identical_queries(controller):
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="bb"))
    msgs = queued(controller)
    assert len(msgs) == 1  # second query joined the first's work unit
    assert controller.counters["plan_shared_dispatches"] == 1
    assert len(controller.rpc_segments) == 2
    token = msgs[0]["token"]
    assert len(controller._work_subscribers[token]) == 2

    # one worker result completes BOTH clients
    reply = CalcMessage(dict(msgs[0]))
    reply["data"] = b"payload-bytes"
    controller.process_worker_result(reply)
    assert not controller.rpc_segments
    clients = sorted(c for c, _ in controller._replies)
    assert clients == ["aa", "bb"]
    for _, payload in controller._replies:
        envelope = decode_reply(payload)
        assert envelope["ok"] and envelope["payloads"] == [b"payload-bytes"]
    assert not controller._work_subscribers and not controller._work_index


def test_client_resend_does_not_duplicate_fanout(controller):
    """A client retrying after its own timeout resends the same identity;
    the controller must not launch a second fan-out for the live ticket."""
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))  # resend
    assert len(queued(controller)) == 1
    assert len(controller.rpc_segments) == 1
    assert controller.admission.stats()["active"] == 1
    # the single run answers the identity once; completion frees the slot
    (msg,) = queued(controller)
    reply = CalcMessage(dict(msg))
    reply["data"] = b"x"
    controller.process_worker_result(reply)
    assert [c for c, _ in controller._replies] == ["aa"]
    assert controller.admission.stats()["active"] == 0
    # the NEXT query from that client admits fresh
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    assert controller.admission.stats()["active"] == 1


def test_retry_with_fresh_deadline_joins_inflight_run(controller):
    """An application-level retry restamps a fresh absolute deadline; it
    must still read as a RESEND (join the in-flight run), or every retry
    of a long query would cancel and restart it — a livelock."""
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], token="aa", deadline=time.time() + 60)
    )
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], token="aa", deadline=time.time() + 90)
    )
    assert controller.counters["admission_superseded"] == 0
    assert len(queued(controller)) == 1
    assert controller.admission.stats()["active"] == 1


def test_new_query_on_live_identity_supersedes(controller):
    """A DIFFERENT query arriving on a live identity means the client gave
    up on the old one (REQ is lockstep): the abandoned run is retired with
    no reply and the new query is admitted in its place."""
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    old_msgs = queued(controller)
    controller.rpc_groupby(
        groupby_msg(["b.bcolzs"], where=[["k", ">", 1]], token="aa")
    )
    assert controller.counters["admission_superseded"] == 1
    # still exactly one live ticket for the identity, one live segment,
    # and the live segment is the NEW query's
    assert controller.admission.stats()["active"] == 1
    (segment,) = controller.rpc_segments.values()
    assert segment["filenames"] == ["b.bcolzs"]
    # the abandoned dispatch no longer owns a work unit; a late worker
    # reply for it must not reach the client
    for msg in old_msgs:
        assert msg["token"] not in controller._work_subscribers
    new_msg = next(
        m for m in queued(controller)
        if m["token"] in controller._work_subscribers
    )
    reply = CalcMessage(dict(new_msg))
    reply["data"] = b"x"
    controller.process_worker_result(reply)
    assert [c for c, _ in controller._replies] == ["aa"]


def test_different_deadlines_do_not_fuse(controller):
    """Fusing across deadlines would expire one client's work on another
    client's budget (or never enforce the tighter deadline at all)."""
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], token="aa", deadline=time.time() + 0.05)
    )
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="bb"))
    msgs = queued(controller)
    assert len(msgs) == 2
    assert controller.counters["plan_shared_dispatches"] == 0
    # the deadline-free query survives the other one's expiry
    time.sleep(0.1)
    controller.dispatch_pending()
    (remaining,) = queued(controller)
    assert remaining.get("deadline") is None
    ((client, payload),) = controller._replies
    assert client == "aa" and not decode_reply(payload)["ok"]


def test_different_queries_do_not_fuse(controller):
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], where=[["k", ">", 1]], token="bb")
    )
    assert len(queued(controller)) == 2
    assert controller.counters["plan_shared_dispatches"] == 0


def test_aborted_subscriber_does_not_kill_shared_work(controller):
    register(controller, "w1", ["a.bcolzs"])
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="bb"))
    (msg,) = queued(controller)
    # find aa's parent and abort it
    aa_parent = next(
        p for p, s in controller.rpc_segments.items()
        if s["client_token"] == "aa"
    )
    controller.abort_parent(aa_parent, "client gave up")
    assert queued(controller) == [msg]  # bb still owns the work unit
    reply = CalcMessage(dict(msg))
    reply["data"] = b"x"
    controller.process_worker_result(reply)
    done = {c: decode_reply(p) for c, p in controller._replies}
    assert done["aa"]["ok"] is False
    assert done["bb"]["ok"] is True


def test_malformed_stats_advertisement_is_quarantined(controller):
    """One bad WRM poisons at most its own shard's stats entry — and a
    well-shaped entry full of garbage still cannot fail a query."""
    register(controller, "w1", ["a.bcolzs"])
    controller._absorb_shard_stats({"shard_stats": 5})
    controller._absorb_shard_stats({"shard_stats": {"a.bcolzs": 7}})
    assert "a.bcolzs" not in controller.shard_stats
    controller._absorb_shard_stats({"shard_stats": {"a.bcolzs": {
        "rows": "many",
        "cols": {"k": {"kind": "numeric", "min": "lo", "max": 3}},
    }}})
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], where=[["k", ">", 1]], token="aa")
    )
    assert len(queued(controller)) == 1  # dispatched: not pruned, no raise
    assert controller.counters["plan_pruned_shards"] == 0


def test_failed_launch_leaves_no_zombie_segment(controller, monkeypatch):
    """If dispatch raises after SOME shard groups queued, the half-launched
    parent must be fully retired: a segment whose later groups never queued
    can never complete, and its queued work would burn worker time for a
    reply nobody can assemble."""
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    orig = controller._register_work
    calls = {"n": 0}

    def flaky(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("mid-launch failure")
        return orig(*args, **kwargs)

    monkeypatch.setattr(controller, "_register_work", flaky)
    with pytest.raises(RuntimeError):
        controller.rpc_groupby(
            groupby_msg(["a.bcolzs", "b.bcolzs"], token="aa", batch=False)
        )
    assert not controller.rpc_segments
    assert not controller._work_subscribers and not controller._work_index
    assert not queued(controller)
    assert controller.admission.stats()["active"] == 0


def test_admission_busy_reply(tmp_path):
    node = ControllerNode(
        coordination_url=f"mem://plan-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        admit_max_active=1,
        admit_queue_depth=1,
    )
    node._replies = []
    node.reply_rpc_raw = (
        lambda client_token, payload: node._replies.append(
            (client_token, payload)
        )
    )
    try:
        register(node, "w1", ["a.bcolzs"])
        node.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))  # active
        node.rpc_groupby(groupby_msg(["a.bcolzs"], token="bb"))  # queued
        node.rpc_groupby(groupby_msg(["a.bcolzs"], token="cc"))  # BUSY
        assert node.counters["admission_busy"] == 1
        assert node.counters["admission_queued"] == 1
        ((client, payload),) = node._replies
        assert client == "cc"
        envelope = decode_reply(payload)
        assert envelope["busy"] is True and envelope["ok"] is False
        # bb sat in the ADMISSION queue (not launched), so it could not
        # fuse with aa's in-flight work: completing aa frees the slot and
        # _admit_ready launches bb's own dispatch
        (msg,) = queued(node)
        node.worker_out_messages[None].clear()  # simulate the dispatch
        reply = CalcMessage(dict(msg))
        reply["data"] = b"x"
        node.process_worker_result(reply)
        assert {c for c, _ in node._replies} == {"aa", "cc"}
        (msg2,) = queued(node)  # bb launched into the freed capacity
        reply2 = CalcMessage(dict(msg2))
        reply2["data"] = b"y"
        node.process_worker_result(reply2)
        answered = {c for c, _ in node._replies}
        assert answered == {"aa", "bb", "cc"}
        assert node.admission.stats()["active"] == 0
    finally:
        node.socket.close()


def test_client_quota_binds_across_sockets(tmp_path):
    """Sockets declaring the same client_id share one quota bucket: the
    second concurrent query from the same application gets BUSY even
    though it arrives on a fresh REQ identity."""
    node = ControllerNode(
        coordination_url=f"mem://plan-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        admit_client_quota=1,
    )
    node._replies = []
    node.reply_rpc_raw = (
        lambda client_token, payload: node._replies.append(
            (client_token, payload)
        )
    )
    try:
        register(node, "w1", ["a.bcolzs"])
        node.rpc_groupby(
            groupby_msg(["a.bcolzs"], token="aa", client_id="app1")
        )
        node.rpc_groupby(
            groupby_msg(["a.bcolzs"], token="bb", client_id="app1")
        )
        assert node.counters["admission_busy"] == 1
        ((client, payload),) = node._replies
        assert client == "bb" and decode_reply(payload)["busy"] is True
        # a different application is not throttled by app1's quota
        node.rpc_groupby(
            groupby_msg(["a.bcolzs"], token="cc", client_id="app2")
        )
        assert node.counters["admission_busy"] == 1
    finally:
        node.socket.close()


def test_different_affinity_does_not_fuse(controller):
    """Fusing identical queries across affinity pins would silently run a
    pinned query on whichever worker the first query targeted."""
    register(controller, "w1", ["a.bcolzs"])
    register(controller, "w2", ["a.bcolzs"])
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], token="aa", affinity="w1")
    )
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], token="bb", affinity="w2")
    )
    assert controller.counters["plan_shared_dispatches"] == 0
    assert len(controller.worker_out_messages.get("w1", [])) == 1
    assert len(controller.worker_out_messages.get("w2", [])) == 1


def test_admission_queue_launches_after_release(tmp_path):
    node = ControllerNode(
        coordination_url=f"mem://plan-{os.urandom(4).hex()}",
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        admit_max_active=1,
        admit_queue_depth=4,
    )
    node._replies = []
    node.reply_rpc_raw = (
        lambda client_token, payload: node._replies.append(
            (client_token, payload)
        )
    )
    try:
        register(node, "w1", ["a.bcolzs", "b.bcolzs"])
        node.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
        # different shard set -> not fused; waits in the admission queue
        node.rpc_groupby(groupby_msg(["b.bcolzs"], token="bb"))
        assert len(queued(node)) == 1  # only aa launched
        (msg,) = queued(node)
        reply = CalcMessage(dict(msg))
        reply["data"] = b"x"
        node.process_worker_result(reply)  # completes aa, admits bb
        msgs = queued(node)
        assert any(m["filename"] == "b.bcolzs" for m in msgs)
    finally:
        node.socket.close()


def test_queued_dispatch_expires_past_deadline(controller):
    register(controller, "w1", ["a.bcolzs"], busy=True)
    controller.rpc_groupby(
        groupby_msg(
            ["a.bcolzs"], token="aa", deadline=time.time() + 0.05
        )
    )
    (msg,) = queued(controller)
    assert msg.get("deadline") is not None  # propagated onto the shard
    time.sleep(0.1)
    controller.dispatch_pending()
    assert not queued(controller)
    assert controller.counters["deadline_expired"] == 1
    ((client, payload),) = controller._replies
    envelope = decode_reply(payload)
    assert not envelope["ok"] and "deadline" in envelope["error"]


def test_wrm_shard_stats_absorbed(controller):
    from bqueryd_tpu.messages import WorkerRegisterMessage

    wrm = WorkerRegisterMessage(
        {
            "worker_id": "w9",
            "workertype": "calc",
            "data_files": ["a.bcolzs"],
            "shard_stats": {"a.bcolzs": {"rows": 42, "cols": {}}},
        }
    )
    controller.handle_worker(b"w9", wrm)
    assert controller.shard_stats["a.bcolzs"]["rows"] == 42
    # un-advertising the file drops its stats
    wrm2 = WorkerRegisterMessage(
        {"worker_id": "w9", "workertype": "calc", "data_files": []}
    )
    controller.handle_worker(b"w9", wrm2)
    assert "a.bcolzs" not in controller.shard_stats


def test_supersede_drops_staged_window_plan(controller, monkeypatch):
    """A plan still STAGED in the admission micro-batch window when its
    identity sends a DIFFERENT query must be dropped before the flush can
    launch it — a launched stale run would queue a mis-pairing reply for
    the identity's next request (the same contract as superseding an
    active run, one stage earlier)."""
    register(controller, "w1", ["a.bcolzs", "b.bcolzs"])
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "60000")
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    assert len(controller._pending_window) == 1
    assert not controller.rpc_segments  # staged, not launched
    controller.rpc_groupby(
        groupby_msg(["b.bcolzs"], where=[["k", ">", 1]], token="aa")
    )
    assert controller.counters["admission_superseded"] == 1
    # only the NEW query remains staged, and the identity holds ONE ticket
    (staged_entry,) = controller._pending_window
    assert staged_entry[1].filenames == ["b.bcolzs"]
    assert controller.admission.stats()["active"] == 1
    controller._flush_window(force=True)
    (segment,) = controller.rpc_segments.values()
    assert segment["filenames"] == ["b.bcolzs"]
    # no reply was emitted for the abandoned staged plan
    assert controller._replies == []


def test_bundle_reply_without_members_aborts_not_misdelivers(
    controller, monkeypatch
):
    """A bundle answered WITHOUT bundle_members (a pre-PR-9 worker ran only
    the positional params) must abort every member with the mixed-version
    error — falling through to the shared-dispatch sink would hand one
    member's payload to every member as ok=True."""
    register(controller, "w1", ["a.bcolzs"])
    monkeypatch.setenv("BQUERYD_TPU_BATCH_WINDOW_MS", "60000")
    controller.rpc_groupby(groupby_msg(["a.bcolzs"], token="aa"))
    controller.rpc_groupby(
        groupby_msg(["a.bcolzs"], where=[["k", ">", 1]], token="bb")
    )
    controller._flush_window(force=True)
    assert controller.counters["plan_bundles"] == 1
    (msg,) = queued(controller)
    assert msg.get("bundle") and msg.get("_bundle_parents")
    reply = CalcMessage(dict(msg))
    reply["data"] = b"member0-payload"  # no bundle_members key
    controller.process_worker_result(reply)
    assert sorted(c for c, _ in controller._replies) == ["aa", "bb"]
    for _client, payload in controller._replies:
        envelope = pickle.loads(payload)
        assert envelope["ok"] is False
        assert "BQUERYD_TPU_BATCH_WINDOW_MS=0" in envelope["error"]
    assert not controller.rpc_segments


def test_advertised_stats_hold_rows_and_bounds_only(tmp_path):
    """What a worker advertises per shard is what pruning reads: ``rows``
    and per-column kind, chunk identity and min/max — no cardinality, and
    no probe of a factorize sidecar (writing one changes nothing)."""
    import pandas as pd

    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine
    from bqueryd_tpu.plan.stats import StatsCollector, gather_table_stats
    from bqueryd_tpu.storage.ctable import ctable

    df = pd.DataFrame({
        "k": np.arange(600, dtype=np.int64) % 7,
        "v": np.arange(600, dtype=np.int64) - 5,
        "s": np.array(["a", "b", "c"])[np.arange(600) % 3],
    })
    root = str(tmp_path / "t.bcolzs")
    ctable.fromdataframe(df, root)
    table = ctable(root, mode="r")
    stats = gather_table_stats(table)
    assert stats["rows"] == 600 and set(stats["cols"]) == {"k", "v", "s"}
    for name, entry in stats["cols"].items():
        assert set(entry) <= {"kind", "chunks", "sig", "min", "max"}, name
    assert (stats["cols"]["v"]["min"], stats["cols"]["v"]["max"]) == (-5, 594)
    collector = StatsCollector(min_refresh_s=0.0)
    first = collector.collect(str(tmp_path), ["t.bcolzs"])
    assert first == {"t.bcolzs": stats}
    # a query factorizes the key and may store its sidecar: the advertised
    # stats are the same object, so the WRM does not re-advertise them
    QueryEngine().execute_local(
        table, GroupByQuery(["k", "s"], [["v", "sum", "v"]]))
    assert collector.collect(str(tmp_path), ["t.bcolzs"]) is first
