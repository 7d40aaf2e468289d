"""A unit of work reads each shard's identity once, at open (PR 37).

``WorkerNode._open_identified`` makes the one filesystem pass per shard
(``rootdir_cache_key``: a stat of meta.json and a realpath) and the unit
hands ``key + (nrows,)`` down to every consumer: the result-cache key, the
delta store's key and the mesh executor's cache keys.  Pinned here:

* one pass per shard per unit, for a solo, a bundle and an extended-DAG
  unit; ``bqueryd_tpu_table_identity_total{source="recomputed"}`` stays 0;
* the value handed down is ``table_cache_key(table)`` letter for letter,
  so no cache key changes in value;
* nothing is kept between units: a meta.json rewrite misses, an append is
  delta-served from its tail, an unchanged repeat hits the result cache;
* (PR 39) the pass makes the shard's canonical path from the unit's one
  ``realpath`` of ``data_dir`` and one ``lstat`` a plain shard
  (``bqueryd_tpu_identity_path_total{form="joined"}``), and a symlinked or
  nested shard's by its own ``realpath`` (``form="resolved"``), to the
  same value.
"""

import collections
import importlib
import logging
import os
import threading

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.messages import CalcMessage
from bqueryd_tpu.models.query import GroupByQuery, ResultPayload
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, view_identities
from bqueryd_tpu.storage.ctable import ctable, table_cache_key

# (the package re-exports the class under the module's name)
ctable_mod = importlib.import_module("bqueryd_tpu.storage.ctable")

SHARDS = 3
ROWS = 4000
KINDS = ["solo", "bundle", "dag"]


def _frame(rng, n, offset=0):
    return pd.DataFrame({
        "k": rng.integers(0, 7, n).astype(np.int64),
        "v": rng.integers(-100, 100, n).astype(np.int64),
        "w": rng.random(n) * 10,
        "seq": np.arange(offset, offset + n, dtype=np.int64),
    })


@pytest.fixture
def make_worker(monkeypatch):
    """Calc workers driven directly (no loop thread, so no heartbeat opens
    a table behind the test's back); small tables go by the mesh executor,
    not by the host kernels."""
    from bqueryd_tpu.worker import WorkerNode

    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    monkeypatch.setenv("BQUERYD_TPU_WARMUP", "0")
    monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    made = []

    def make(data_dir):
        made.append(WorkerNode(
            coordination_url=f"mem://identity-{os.urandom(4).hex()}",
            data_dir=str(data_dir), loglevel=logging.WARNING,
            restart_check=False,
        ))
        return made[-1]

    yield make
    for worker in made:
        worker.socket.close()


@pytest.fixture
def node(tmp_path, make_worker):
    """One calc worker over ``SHARDS`` shard files."""
    rng = np.random.default_rng(37)
    names, frames = [], []
    for i in range(SHARDS):
        names.append(f"s{i}.bcolzs")
        frames.append(_frame(rng, ROWS, offset=i * ROWS))
        ctable.fromdataframe(
            frames[-1], str(tmp_path / names[-1]), chunklen=500
        )
    yield {"worker": make_worker(tmp_path), "names": names,
           "frames": frames, "root": tmp_path}


def _message(names, kind="solo", where=None, above=2.5, topk=2):
    from bqueryd_tpu.plan import bundle as bundlemod
    from bqueryd_tpu.plan import dag as dagmod
    from bqueryd_tpu.plan import plan_groupby

    where = [["w", ">", above]] if where is None else where
    msg = CalcMessage({"payload": "groupby", "token": os.urandom(4).hex()})
    if kind == "solo":
        msg.set_args_kwargs([names, ["k"], [["v", "sum", "s"]], where], {})
        return msg
    if kind == "bundle":
        plans = [
            plan_groupby(names, ["k"], [["v", "sum", "s"]],
                         [["w", ">", above + d]])
            for d in (0.0, 0.25)
        ]
        msg["filename"] = names
        msg.add_as_binary("bundle", bundlemod.bundle_fragment(
            plans[0], names,
            [("m0", plans[0], None), ("m1", plans[1], None)],
        ))
    else:
        dag = dagmod.compile_query({
            "table": names, "groupby": ["k"],
            "aggs": [["v", "sum", "s"], ["v", "topk", "top", {"k": topk}]],
            "where": where,
        })
        msg.add_as_binary("dag", dag.to_wire())
    msg.set_args_kwargs([names, [], [], []], {})
    return msg


def _passes(worker, source):
    return worker._identity_passes[source].value


def _counting(monkeypatch):
    """Count the calls of ``rootdir_cache_key``, whoever makes them: the
    worker's open, ``table_cache_key``, a ``ChunkView``."""
    calls = []
    real = ctable_mod.rootdir_cache_key

    def counted(rootdir, canonical=None):
        calls.append(rootdir)
        return real(rootdir, canonical)

    monkeypatch.setattr(ctable_mod, "rootdir_cache_key", counted)
    return calls


def _table(reply):
    frame = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([ResultPayload.from_bytes(reply["data"])])
    )
    return frame.sort_values("k").reset_index(drop=True)


# -- (a) one pass per shard per unit ------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_served_unit_asks_the_filesystem_once_per_shard(
    node, monkeypatch, kind
):
    worker, names = node["worker"], node["names"]
    # the group's first unit aligns its keys, and the engine that
    # factorizes them files its own cache by ``table_cache_key`` (the
    # engine path's calls: not this PR's).  From then on no cache answers:
    # a fresh filter a unit — for the DAG, whose filter is part of its
    # per-shard derivation (so a fresh one factorizes again), a fresh k
    worker.handle_work(_message(names, kind))
    calls = _counting(monkeypatch)
    for fresh in (1, 2):
        del calls[:]
        opened = _passes(worker, "open")
        reply = worker.handle_work(_message(
            names, kind, **(
                {"topk": 2 + fresh} if kind == "dag"
                else {"above": 2.5 + fresh / 2}
            )
        ))
        assert reply["merge_mode"] == "device"   # the mesh executor ran
        assert reply["effective_strategy"] not in ("cached", "delta")
        assert len(calls) == SHARDS, (fresh, calls)
        assert sorted(os.path.basename(c) for c in calls) == names
        assert _passes(worker, "open") == opened + SHARDS
        assert _passes(worker, "recomputed") == 0


def test_the_rollup_verb_hands_its_one_identity_down(node, monkeypatch):
    """A rollup of an extended DAG goes by ``_execute_dag`` over the one
    shard it opened: the mesh executor is handed that open's identity."""
    from bqueryd_tpu.plan import dag as dagmod

    worker, names = node["worker"], node["names"]

    def rollup(topk):
        msg = CalcMessage({"payload": "rollup", "token": "00"})
        msg.set_args_kwargs([names[0], [], [], []], {})
        msg.add_as_binary("dag", dagmod.compile_query({
            "table": names[:1], "groupby": ["k"],
            "aggs": [["v", "topk", "top", {"k": topk}]],
        }).to_wire())
        return worker.handle_work(msg)

    rollup(2)   # aligns the shard's keys (the engine's own cache key)
    calls = _counting(monkeypatch)
    opened = _passes(worker, "open")
    assert rollup(3)["rollup_mode"] == "rebuild"
    assert calls == [str(node["root"] / names[0])]
    assert _passes(worker, "open") == opened + 1
    assert _passes(worker, "recomputed") == 0


# -- (b) the value handed down is table_cache_key's ----------------------------

def test_the_identity_handed_down_is_table_cache_key(node):
    worker, names = node["worker"], node["names"]
    tables, identities = worker._open_unit(names)
    assert isinstance(identities, tuple) and len(identities) == SHARDS
    for table, identity, name in zip(tables, identities, names):
        assert identity == table_cache_key(table)
        rootdir = os.path.realpath(str(node["root"] / name))
        assert identity[0] == rootdir and identity[-1] == ROWS
    query = GroupByQuery(["k"], [["v", "sum", "s"]], [["w", ">", 1.0]])
    assert worker._delta_key(identities, query) == (
        tuple(os.path.realpath(t.rootdir) for t in tables),
        query.signature(),
    )
    # nothing is kept on the instance, and the instance is reused
    again, _ = worker._open_unit(names)
    assert all(a is b for a, b in zip(again, tables))
    assert not any("ident" in attr for t in tables for attr in vars(t))


@pytest.mark.parametrize("kind", KINDS)
def test_bare_tables_build_the_same_executor_cache_keys(node, kind):
    """``execute*`` called with bare tables (tests, a caller with no open
    of its own) computes the identities at the call, and files everything
    under the same keys as a unit that was handed them."""
    from bqueryd_tpu.plan import dag as dagmod

    worker, names = node["worker"], node["names"]
    tables, identities = worker._open_unit(names)
    query = GroupByQuery(["k"], [["v", "sum", "s"]], [["w", ">", 2.5]])
    dag = dagmod.compile_query({
        "table": names, "groupby": ["k"],
        "aggs": [["v", "sum", "s"], ["v", "topk", "top", {"k": 2}]],
        "where": [["w", ">", 2.5]],
    })

    def run(executor, **kw):
        if kind == "solo":
            return [executor.execute(tables, query, **kw)]
        if kind == "bundle":
            return executor.execute_bundle(tables, [query, query], **kw)
        return [executor.execute_dag(tables, dag, **kw)]

    recomputed = []
    bare = MeshQueryExecutor(on_identity_recomputed=recomputed.append)
    handed = MeshQueryExecutor(on_identity_recomputed=recomputed.append)
    got_bare = run(bare)
    assert recomputed == [SHARDS]
    got_handed = run(handed, identities=identities)
    assert recomputed == [SHARDS]   # handed down: nothing asked again
    for segment in ("_align_cache", "_codes_cache", "_hbm_cache"):
        keys = set(getattr(bare, segment)._data)
        assert keys and keys == set(getattr(handed, segment)._data), segment
        assert all(key[0] == identities or key[0] in identities
                   for key in keys), segment
    for a, b in zip(got_bare, got_handed):
        assert a.to_bytes() == b.to_bytes()
    # and a second call, bare, on the executor that was handed them: warm
    misses = handed._align_cache.misses
    run(handed)
    assert handed._align_cache.misses == misses


def test_a_pruned_view_is_filed_under_its_own_token(node):
    worker, names = node["worker"], node["names"]
    tables, identities = worker._open_unit(names)
    views = [tables[0].chunk_view([0, 1]), tables[1], tables[2]]
    told = []
    out = view_identities(identities, tables, views, told.append)
    assert told == [1]
    assert out[0] == table_cache_key(views[0]) != identities[0]
    assert out[1:] == identities[1:]
    assert view_identities(None, tables, views, told.append) is None
    assert told == [1]
    # through the worker: a selective filter on a monotonic column prunes
    # every shard to a view; the answer is the unpruned one
    where = [["seq", ">=", SHARDS * ROWS - 400]]
    reply = worker.handle_work(_message(names, "solo", where=where))
    assert worker.chunks_skipped_total.value > 0
    assert _passes(worker, "recomputed") >= 1   # the view read its parent
    frame = pd.concat(node["frames"], ignore_index=True)
    want = (
        frame[frame["seq"] >= SHARDS * ROWS - 400]
        .groupby("k", as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
    )
    got = _table(reply)
    np.testing.assert_array_equal(got["k"].to_numpy(), want["k"].to_numpy())
    np.testing.assert_array_equal(got["s"].to_numpy(), want["s"].to_numpy())


# -- (c) nothing outlives the unit: invalidation through the worker -----------

def _rewrite_meta(rootdir):
    """What an activation does to meta.json: the same bytes under a fresh
    inode (tempfile + rename)."""
    path = os.path.join(rootdir, "meta.json")
    before = os.stat(path).st_ino
    with open(path, "rb") as f:
        data = f.read()
    tmp = path + ".new"
    with open(tmp, "wb") as f:
        f.write(data)
    os.replace(tmp, path)
    assert os.stat(path).st_ino != before


def test_an_unchanged_repeat_hits_and_a_rewritten_meta_misses(node):
    worker, names = node["worker"], node["names"]
    first = worker.handle_work(_message(names))
    assert first["effective_strategy"] not in ("cached", "delta")
    repeat = worker.handle_work(_message(names))
    assert repeat["effective_strategy"] == "cached"
    assert repeat["data"] == first["data"]

    _, before = worker._open_unit(names)
    _rewrite_meta(str(node["root"] / names[1]))
    _, after = worker._open_unit(names)
    assert after[0] == before[0] and after[2] == before[2]
    assert after[1] != before[1] and after[1][0] == before[1][0]

    executor = worker.mesh_executor
    align_misses = executor._align_cache.misses
    codes_entries = len(executor._codes_cache)
    third = worker.handle_work(_message(names))
    # neither the result cache nor the delta store (no growth) answers,
    # and the executor files the group under its new identity
    assert third["effective_strategy"] not in ("cached", "delta")
    assert executor._align_cache.misses > align_misses
    assert len(executor._codes_cache) > codes_entries
    assert third["data"] == first["data"]
    assert _passes(worker, "recomputed") == 0


def test_an_append_between_two_units_is_served_from_its_tail(
    node, monkeypatch
):
    worker, names = node["worker"], node["names"]
    rng = np.random.default_rng(38)
    first = worker.handle_work(_message(names))
    assert first["effective_strategy"] not in ("cached", "delta")
    extra = _frame(rng, 300, offset=SHARDS * ROWS)
    ctable(str(node["root"] / names[2]), mode="a").append_dataframe(extra)

    second = worker.handle_work(_message(names))
    assert second["effective_strategy"] == "delta"
    assert worker.delta_refreshes_total.value == 1
    assert worker.delta_cache().delta_rows == 300   # the tail only

    # bit-exact against a recompute with delta serving (and the result
    # cache, which now holds the refreshed bytes) out of the way
    monkeypatch.setenv("BQUERYD_TPU_DELTA_SERVE", "0")
    worker.result_cache.clear()
    third = worker.handle_work(_message(names))
    assert third["effective_strategy"] not in ("cached", "delta")
    got, want = _table(second), _table(third)
    np.testing.assert_array_equal(got["k"].to_numpy(), want["k"].to_numpy())
    np.testing.assert_array_equal(got["s"].to_numpy(), want["s"].to_numpy())
    frame = pd.concat(node["frames"] + [extra], ignore_index=True)
    truth = (
        frame[frame["w"] > 2.5].groupby("k", as_index=False)["v"].sum()
    )
    np.testing.assert_array_equal(got["s"].to_numpy(), truth["v"].to_numpy())

    # and the refreshed entry is found again: a second append, a second tail
    monkeypatch.delenv("BQUERYD_TPU_DELTA_SERVE")
    more = _frame(rng, 200, offset=SHARDS * ROWS + 300)
    ctable(str(node["root"] / names[0]), mode="a").append_dataframe(more)
    fourth = worker.handle_work(_message(names))
    assert fourth["effective_strategy"] == "delta"
    assert worker.delta_refreshes_total.value == 2


# -- (d) the canonical path: one realpath a unit, one lstat a plain shard -----

#: how each layout's shard is opened: ``joined`` = ``realpath(data_dir)``
#: and the name, proven by an lstat; ``resolved`` = a realpath of its own
LAYOUTS = {
    "plain": "joined",
    "symlinked_shard": "resolved",
    "symlinked_data_dir": "joined",
    "nested_name": "resolved",
}


def _write_shard(rootdir, seed=39):
    frame = _frame(np.random.default_rng(seed), ROWS)
    ctable.fromdataframe(frame, str(rootdir), chunklen=500)
    return frame


def _lay_out(root, layout):
    """``(data_dir, name)``: a data dir whose one shard, ``name``, is laid
    out as ``layout`` says."""
    data_dir = root / "data"
    if layout == "symlinked_data_dir":
        (root / "real").mkdir()
        os.symlink(root / "real", data_dir)
    else:
        data_dir.mkdir()
    name = "x.bcolzs"
    if layout == "symlinked_shard":
        (root / "store").mkdir()
        _write_shard(root / "store" / "x.v1.bcolzs")
        os.symlink(root / "store" / "x.v1.bcolzs", data_dir / name)
    elif layout == "nested_name":
        name = os.path.join("sub", name)
        (data_dir / "sub").mkdir()
        _write_shard(data_dir / name)
    else:
        _write_shard(data_dir / name)
    return data_dir, name


def _forms(worker):
    return {form: c.value for form, c in worker._identity_paths.items()}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_layout_hands_down_table_cache_key(
    tmp_path, make_worker, layout
):
    data_dir, name = _lay_out(tmp_path, layout)
    worker = make_worker(data_dir)
    (table,), (identity,) = worker._open_unit([name])
    assert identity == table_cache_key(table)
    assert identity[0] == os.path.realpath(os.path.join(data_dir, name))
    assert identity[-1] == ROWS
    want = {"joined": 0, "resolved": 0}
    want[LAYOUTS[layout]] = 1
    assert _forms(worker) == want


@pytest.mark.parametrize("repointed", ["shard", "data_dir"])
def test_a_repointed_link_misses_at_the_next_unit(
    tmp_path, make_worker, repointed
):
    """A shard link re-pointed (resolved form), or the data dir's (joined
    form: ``data_dir`` is resolved once a unit and kept no longer), between
    two units: the second opens the new table and answers from it."""
    layout = {"shard": "symlinked_shard", "data_dir": "symlinked_data_dir"}
    data_dir, name = _lay_out(tmp_path, layout[repointed])
    worker = make_worker(data_dir)
    first = worker.handle_work(_message([name]))
    assert first["effective_strategy"] not in ("cached", "delta")
    _, (before,) = worker._open_unit([name])

    if repointed == "shard":
        target, link = tmp_path / "store" / "x.v2.bcolzs", data_dir / name
        frame = _write_shard(target, seed=40)
    else:
        target, link = tmp_path / "real2", data_dir
        target.mkdir()
        frame = _write_shard(target / name, seed=40)
    os.symlink(target, f"{link}.new")
    os.replace(f"{link}.new", link)   # swapped in at once

    _, (after,) = worker._open_unit([name])
    assert after[0] == os.path.realpath(os.path.join(data_dir, name))
    assert after[0] != before[0]
    second = worker.handle_work(_message([name]))
    assert second["effective_strategy"] not in ("cached", "delta")
    got = _table(second)
    truth = frame[frame["w"] > 2.5].groupby("k", as_index=False)["v"].sum()
    np.testing.assert_array_equal(got["k"].to_numpy(), truth["k"].to_numpy())
    np.testing.assert_array_equal(got["s"].to_numpy(), truth["v"].to_numpy())


@pytest.mark.parametrize("absent", ["missing", "dangling"])
def test_an_absent_shard_raises_as_before(tmp_path, make_worker, absent):
    data_dir, name = _lay_out(tmp_path, "plain")
    if absent == "dangling":
        os.symlink(tmp_path / "gone.bcolzs", data_dir / "y.bcolzs")
    worker = make_worker(data_dir)
    with pytest.raises(ValueError) as err:
        worker._open_unit([name, "y.bcolzs"])
    rootdir = os.path.join(str(data_dir), "y.bcolzs")
    assert str(err.value) == f"Path {rootdir} does not exist"
    assert _forms(worker) == {"joined": 1, "resolved": 0}


@pytest.mark.parametrize("kind", ["solo", "dag"])
def test_a_served_unit_makes_one_realpath_and_two_calls_a_shard(
    node, monkeypatch, kind
):
    """The filesystem calls of a whole served unit over plain shards: the
    unit's one ``realpath`` (of ``data_dir``, an lstat a component), then
    an lstat of each shard and the stat of its meta.json.  (A bundle builds
    its filter masks on the host, and each column read there keys the
    storage cache by its own exists + stat + realpath: not the open's.)"""
    worker, names = node["worker"], node["names"]
    worker.handle_work(_message(names, kind))   # warm, as in (a)
    calls = collections.Counter()
    unit_thread = threading.get_ident()   # not a thread left by another test

    def counting(call, real):
        def counted(*args, **kwargs):
            if threading.get_ident() == unit_thread:
                calls[call] += 1
            return real(*args, **kwargs)
        return counted

    for call in ("lstat", "stat"):
        monkeypatch.setattr(os, call, counting(call, getattr(os, call)))
    monkeypatch.setattr(
        os.path, "realpath", counting("realpath", os.path.realpath)
    )
    os.path.realpath(str(node["root"]))
    components = calls["lstat"]
    calls.clear()
    joined = _forms(worker)["joined"]
    reply = worker.handle_work(_message(
        names, kind, **({"topk": 3} if kind == "dag" else {"above": 3.0})
    ))
    seen = dict(calls)
    assert reply["effective_strategy"] not in ("cached", "delta")
    assert seen.get("realpath") == 1, seen
    assert seen.get("stat") == SHARDS, seen   # meta.json's, the guarantee
    assert seen["lstat"] + seen["stat"] <= 2 * SHARDS + components, seen
    assert _forms(worker) == {"joined": joined + SHARDS, "resolved": 0}
