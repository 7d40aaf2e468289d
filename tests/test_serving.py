"""Semantic serving layer (PR 16): plan subsumption + materialized rollups.

Three layers of coverage:

* lattice — eligibility refusals, exact / key-fold / window-fold / zone-proof
  matching, transform application parity vs pandas, the costed source
  choice (tiny tables refuse on cost);
* manager — heat threshold decay, build/absorb lifecycle, append-epoch
  staleness (including an append racing a build), delta refresh, retention
  sweeps (count cap, byte cap, build timeout);
* worker + cluster — the ``rollup`` verb end to end (build, delta refresh,
  census), rollup/subsume answers through ``rpc.groupby`` with provenance
  on the result envelope, append invalidation (never serve stale), the
  ``BQUERYD_TPU_SERVE=0`` kill switch, mixed-version worker rejection, and
  the debug-bundle ``serving`` section + flight events.
"""

import logging
import os
import pickle
import threading

import numpy as np
import pandas as pd
import pytest

from conftest import wait_until

from bqueryd_tpu.models.query import GroupByQuery, QueryEngine, ResultPayload
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.serve import rollup as rollupmod
from bqueryd_tpu.serve import subsume
from bqueryd_tpu.storage.ctable import ctable


def _frame(n, seed=0, offset=0):
    rng = np.random.RandomState(seed)
    return pd.DataFrame(
        {
            "g": rng.randint(0, 5, n).astype(np.int64),
            "g2": rng.randint(0, 3, n).astype(np.int64),
            "v": rng.randint(-100, 100, n).astype(np.int64),
            "f": rng.random(n).astype(np.float32),
            "s": (rng.randint(0, 3, n)).astype(str),
            "seq": np.arange(offset, offset + n, dtype=np.int64),
        }
    )


def _finalize(payloads):
    return hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads(list(payloads))
    )


def _sorted(df, keys):
    return df.sort_values(keys).reset_index(drop=True)


def _view(keys=("g",), aggs=(("v", "sum", "vs"),), where=(),
          filenames=("t.bcolzs",), dag_sig=None, aggregate=True, expand=None):
    return {
        "filenames": tuple(filenames),
        "keys": tuple(keys),
        "aggs": tuple(tuple(a) for a in aggs),
        "where": tuple(subsume._freeze_term(t) for t in where),
        "aggregate_rows": aggregate,
        "expand": expand,
        "dag_sig": dag_sig,
    }


def _census(**cols):
    """{col: (kind, zones)} -> one file's census dict."""
    return {
        name: {"kind": kind, "zones": zones, "nulls": kind != "int"}
        for name, (kind, zones) in cols.items()
    }


# ---------------------------------------------------------------------------
# lattice: eligibility
# ---------------------------------------------------------------------------

def test_plan_eligibility_refusals():
    ok, why = subsume.plan_eligible(_view())
    assert ok and why is None
    assert subsume.plan_eligible(_view(aggregate=False)) == (False, "raw-rows")
    assert subsume.plan_eligible(_view(expand="basket")) == (
        False, "expand-filter"
    )
    assert subsume.plan_eligible(
        _view(aggs=(("v", "count_distinct", "vd"),))
    ) == (False, "op:count_distinct")
    assert subsume.plan_eligible(
        _view(aggs=(("v", "top_k", "t"),))
    ) == (False, "op:top_k")
    # joins never serve; plain DAGs pass (exact-only), windowed DAGs pass
    sig = [0] * 8
    sig[subsume._DAG_JOIN_IDX] = ("j",)
    sig[subsume._DAG_WINDOW_IDX] = None
    assert subsume.plan_eligible(_view(dag_sig=tuple(sig))) == (False, "join")
    sig[subsume._DAG_JOIN_IDX] = None
    assert subsume.plan_eligible(_view(dag_sig=tuple(sig)))[0]


def test_plan_view_and_key_from_logical_plan(tmp_path):
    from bqueryd_tpu import plan as planmod

    plan = planmod.plan_groupby(
        ["t.bcolzs"], ["g"], [["v", "sum", "vs"]], [["seq", ">", 5]],
        aggregate=True, expand_filter_column=None,
    )
    view = subsume.plan_view(plan)
    assert view["keys"] == ("g",)
    assert view["where"] == (("seq", ">", 5),)
    key = subsume.view_key(view)
    assert key.startswith("rollup:g:")
    # deterministic, and sensitive to the filter
    assert key == subsume.view_key(subsume.plan_view(plan))
    plan2 = planmod.plan_groupby(
        ["t.bcolzs"], ["g"], [["v", "sum", "vs"]], [],
        aggregate=True, expand_filter_column=None,
    )
    assert subsume.view_key(subsume.plan_view(plan2)) != key


# ---------------------------------------------------------------------------
# lattice: matching
# ---------------------------------------------------------------------------

def test_match_exact_and_filename_refusal():
    v = _view()
    t, why = subsume.match(v, dict(v))
    assert t == {"kind": "exact"} and why is None
    t, why = subsume.match(v, _view(filenames=("other.bcolzs",)))
    assert t is None and why == "filenames"
    t, why = subsume.match(v, _view(aggregate=False))
    assert t is None and why == "shape"


def test_key_fold_match_and_null_refusal():
    cand = _view(keys=("g", "g2"))
    query = _view(keys=("g",))
    meta = {"t.bcolzs": _census(g2=("int", [(0, 2)]))}
    t, why = subsume.match(cand, query, meta)
    assert why is None and t == {"kind": "fold", "keys": ("g",)}
    # the dropped key column must be proven null-free: float/dict refuse
    for kind in ("float", "dict", "datetime"):
        bad = {"t.bcolzs": _census(g2=(kind, None))}
        t, why = subsume.match(cand, query, bad)
        assert t is None and why == "key-nullable:g2"
    # a query keyed outside the candidate refuses
    t, why = subsume.match(cand, _view(keys=("s",)), meta)
    assert t is None and why == "keys"


def test_agg_projection_and_missing_agg():
    cand = _view(aggs=(("v", "sum", "vs"), ("f", "mean", "fm")))
    query = _view(aggs=(("f", "mean", "fm"),))
    t, why = subsume.match(cand, query, {})
    assert why is None and t == {"kind": "fold", "aggs": (1,)}
    t, why = subsume.match(
        cand, _view(aggs=(("v", "max", "vx"),)), {}
    )
    assert t is None and why == "agg-missing:vx"


def test_zone_proof_filter_match_and_partial_refusal():
    cand = _view()
    meta = {"t.bcolzs": _census(seq=("int", [(0, 255), (256, 511)]))}
    # full-select proof on every chunk: serve the stored bytes verbatim
    t, why = subsume.match(cand, _view(where=((("seq", ">=", 0)),)), meta)
    assert why is None and t == {"kind": "zone"}
    # partial chunk overlap: chunk (0, 255) is not wholly selected
    t, why = subsume.match(cand, _view(where=(("seq", ">", 100),)), meta)
    assert t is None and why == "filter-partial:seq"
    # a float column can never prove full selection (NaNs skip zone maps)
    fmeta = {"t.bcolzs": _census(f=("float", [(0.0, 1.0)]))}
    t, why = subsume.match(cand, _view(where=(("f", ">=", 0.0),)), fmeta)
    assert t is None and why == "filter-partial:f"
    # candidate filtered more strictly than the query can never serve it
    t, why = subsume.match(_view(where=(("seq", ">", 5),)), _view(), meta)
    assert t is None and why == "filter-weaker"


def test_zone_full_select_table():
    cases = [
        ((5, 5), "==", 5, True), ((4, 5), "==", 5, False),
        ((4, 9), "!=", 10, True), ((4, 9), "!=", 5, False),
        ((6, 9), ">", 5, True), ((5, 9), ">", 5, False),
        ((5, 9), ">=", 5, True), ((4, 9), ">=", 5, False),
        ((1, 4), "<", 5, True), ((1, 5), "<", 5, False),
        ((1, 5), "<=", 5, True), ((1, 6), "<=", 5, False),
        ((3, 3), "in", [3, 7], True), ((3, 4), "in", [3, 4], False),
    ]
    for zone, op, value, want in cases:
        assert subsume.zone_full_select(zone[0], zone[1], op, value) is want, (
            zone, op, value
        )
    # incomparable values are a conservative refusal, not a crash
    assert subsume.zone_full_select(1, 5, ">", None) is False
    # a chunk with no zone map (all-null) refuses
    meta = _census(seq=("int", [(0, 9), None]))
    assert not subsume.term_full_selects(meta, ("seq", ">=", 0))


def test_window_fold_alignment_rules():
    def sig(every, origin=0, col="ts", alias="w"):
        s = ["node"] * 8
        s[subsume._DAG_JOIN_IDX] = None
        s[subsume._DAG_WINDOW_IDX] = (col, every, alias, origin)
        return tuple(s)

    minute, hour = 60_000_000_000, 3_600_000_000_000
    cand, query = _view(dag_sig=sig(minute)), _view(dag_sig=sig(hour))
    t, why = subsume.match(cand, query)
    assert why is None
    assert t == {"kind": "fold", "window": ("w", hour, 0)}
    # coarse grid not a multiple of the fine one
    t, why = subsume.match(cand, _view(dag_sig=sig(90_000_000_000)))
    assert t is None and why == "window-misaligned"
    # origins incongruent modulo the fine width
    t, why = subsume.match(cand, _view(dag_sig=sig(hour, origin=30)))
    assert t is None and why == "window-origin"
    # a different window column (or alias) never folds
    t, why = subsume.match(cand, _view(dag_sig=sig(hour, col="ts2")))
    assert t is None and why == "window-column"
    # any other DAG node difference refuses
    other = list(sig(hour))
    other[0] = "different"
    t, why = subsume.match(cand, _view(dag_sig=tuple(other)))
    assert t is None and why == "dag-shape"
    # the fine rollup can never be answered FROM the coarse one
    t, why = subsume.match(_view(dag_sig=sig(hour)), _view(dag_sig=sig(minute)))
    assert t is None and why == "window-misaligned"


# ---------------------------------------------------------------------------
# lattice: transform application parity
# ---------------------------------------------------------------------------

def _partials(tmp_path, df, keys, aggs, name="p.bcolzs"):
    t = ctable.fromdataframe(df, str(tmp_path / name), chunklen=256)
    query = GroupByQuery(list(keys), [list(a) for a in aggs], [],
                         aggregate=True)
    return dict(QueryEngine().execute_local(t, query))


def test_apply_transform_key_fold_parity(tmp_path):
    df = _frame(2000, seed=3)
    aggs = [["v", "sum", "vs"], ["f", "mean", "fm"], ["v", "min", "vmin"]]
    fine = _partials(tmp_path, df, ["g", "g2"], aggs)
    folded = subsume.apply_transform(
        fine, {"kind": "fold", "keys": ("g",)}
    )
    got = _sorted(_finalize([ResultPayload(folded)]), ["g"])
    expected = _sorted(
        df.groupby("g", as_index=False).agg(
            vs=("v", "sum"), fm=("f", "mean"), vmin=("v", "min")
        ),
        ["g"],
    )
    np.testing.assert_array_equal(got["g"], expected["g"])
    np.testing.assert_array_equal(got["vs"], expected["vs"])
    np.testing.assert_array_equal(got["vmin"], expected["vmin"])
    np.testing.assert_allclose(
        got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
    )


def test_apply_transform_agg_projection_parity(tmp_path):
    df = _frame(1200, seed=4)
    fine = _partials(
        tmp_path, df, ["g"],
        [["v", "sum", "vs"], ["f", "mean", "fm"], ["v", "count", "n"]],
    )
    # project out the middle slot only (fm), no re-keying
    sliced = subsume.apply_transform(fine, {"kind": "fold", "aggs": (1,)})
    got = _sorted(_finalize([ResultPayload(sliced)]), ["g"])
    assert list(got.columns) == ["g", "fm"]
    expected = _sorted(
        df.groupby("g", as_index=False).agg(fm=("f", "mean")), ["g"]
    )
    np.testing.assert_allclose(
        got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
    )


def test_apply_transform_window_refloor_parity(tmp_path):
    minute, hour = 60_000_000_000, 3_600_000_000_000
    n = 1500
    rng = np.random.RandomState(7)
    df = pd.DataFrame(
        {
            "b": (np.arange(n, dtype=np.int64) * minute // 7) // minute
            * minute,
            "v": rng.randint(-50, 50, n).astype(np.int64),
        }
    )
    fine = _partials(tmp_path, df, ["b"], [["v", "sum", "vs"]])
    # re-key the minute buckets onto the hour grid and collapse
    folded = subsume.apply_transform(
        fine, {"kind": "fold", "window": ("b", hour, 0)}
    )
    got = _sorted(_finalize([ResultPayload(folded)]), ["b"])
    expected = _sorted(
        df.assign(b=(df["b"] // hour) * hour)
        .groupby("b", as_index=False).agg(vs=("v", "sum")),
        ["b"],
    )
    np.testing.assert_array_equal(got["b"], expected["b"])
    np.testing.assert_array_equal(got["vs"], expected["vs"])


def test_apply_transform_window_preserves_datetime_dtype(tmp_path):
    minute, hour = 60_000_000_000, 3_600_000_000_000
    df = pd.DataFrame(
        {
            "b": np.arange(0, 360, 3, dtype=np.int64) * minute,
            "v": np.ones(120, dtype=np.int64),
        }
    )
    fine = _partials(tmp_path, df, ["b"], [["v", "sum", "vs"]])
    fine["keys"] = dict(fine["keys"])
    fine["keys"]["b"] = np.asarray(
        fine["keys"]["b"], dtype=np.int64
    ).view("datetime64[ns]")
    folded = subsume.apply_transform(
        fine, {"kind": "fold", "window": ("b", hour, 0)}
    )
    out = np.asarray(folded["keys"]["b"])
    assert out.dtype == np.dtype("datetime64[ns]")
    want = np.sort(
        pd.Series(
            df["b"].to_numpy().view("datetime64[ns]")
        ).dt.floor("h").unique()
    )
    np.testing.assert_array_equal(np.sort(out), want)


def test_collapse_partials_passthrough_and_exact():
    rows_payload = {"kind": "rows", "data": [1, 2]}
    assert hostmerge.collapse_partials(rows_payload) is rows_payload
    p = {"kind": "partials", "rows": []}
    assert hostmerge.collapse_partials(p) is p
    # exact / zone transforms never touch the payload
    marker = {"kind": "partials", "rows": [1]}
    assert subsume.apply_transform(marker, {"kind": "exact"}) is marker


# ---------------------------------------------------------------------------
# lattice: source choice (cost)
# ---------------------------------------------------------------------------

def test_choose_source_prefers_cheapest_and_refuses_tiny_tables():
    matches = [
        ("rollup:a", {"kind": "exact"}, 5_000),
        ("rollup:b", {"kind": "fold"}, 50),
    ]
    choice = subsume.choose_source(matches, total_rows=1_000_000)
    assert choice is not None and choice[0] == "rollup:b"
    # a table barely bigger than the partials: recompute wins
    assert subsume.choose_source(matches, total_rows=40) is None
    assert subsume.choose_source([], total_rows=1_000_000) is None


# ---------------------------------------------------------------------------
# manager lifecycle
# ---------------------------------------------------------------------------

def _manager_entry(mgr, key="k1", filenames=("a.bcolzs", "b.bcolzs"), now=0.0):
    view = _view(filenames=filenames)
    spec = {"args": [["g"], [["v", "sum", "vs"]], []], "dag_wire": None}
    for _ in range(3):
        mgr.note_query(key, view, spec, now)
    return mgr.start_build(key, now)


def test_heat_threshold_decays():
    mgr = rollupmod.RollupManager()
    view, spec = _view(), {"args": [[], [], []], "dag_wire": None}
    # three instantaneous hits cross the default threshold of 3.0 ...
    assert not mgr.note_query("k", view, spec, 0.0)
    assert not mgr.note_query("k", view, spec, 0.0)
    assert mgr.note_query("k", view, spec, 0.0)
    # ... but spaced hits decay below it (hl 300s: 3 hits over 600s ~= 2.2)
    mgr2 = rollupmod.RollupManager()
    assert not mgr2.note_query("k", view, spec, 0.0)
    assert not mgr2.note_query("k", view, spec, 300.0)
    assert not mgr2.note_query("k", view, spec, 600.0)


def test_entry_lifecycle_ready_stale_refresh():
    mgr = rollupmod.RollupManager()
    entry = _manager_entry(mgr)
    assert entry is not None and entry.state == "building"
    assert mgr.start_build("k1", 0.0) is None  # idempotent
    info = {"data": b"x" * 10, "payload": {}, "base": b"b", "zones": {},
            "groups": 4, "mode": "rebuild"}
    assert mgr.absorb("k1", "a.bcolzs", dict(info), 1.0) == "building"
    assert mgr.absorb("k1", "b.bcolzs", dict(info), 1.0) == "ready"
    assert [e.key for e in mgr.candidates(("a.bcolzs", "b.bcolzs"))] == ["k1"]
    # wrong filename set: no candidates
    assert mgr.candidates(("a.bcolzs",)) == []
    # an append on EITHER file stales the entry out synchronously
    assert mgr.note_append("b.bcolzs", 2.0) == ["k1"]
    assert entry.state == "stale" and mgr.candidates(
        ("a.bcolzs", "b.bcolzs")
    ) == []
    # delta refresh hands back the prior partials and re-arms the epochs
    res = mgr.begin_refresh("k1", 3.0)
    assert res is not None
    refreshed, prior = res
    assert refreshed.state == "building" and set(prior) == {
        "a.bcolzs", "b.bcolzs"
    }
    assert mgr.absorb("k1", "a.bcolzs", dict(info), 4.0) == "building"
    assert mgr.absorb("k1", "b.bcolzs", dict(info), 4.0) == "ready"
    assert [e.key for e in mgr.candidates(("a.bcolzs", "b.bcolzs"))] == ["k1"]


def test_append_racing_a_build_never_serves():
    mgr = rollupmod.RollupManager()
    _manager_entry(mgr)
    info = {"data": b"x", "payload": {}, "base": b"b", "zones": {},
            "groups": 1, "mode": "rebuild"}
    mgr.absorb("k1", "a.bcolzs", dict(info), 1.0)
    # the append dispatch lands between the two shard replies: the epoch
    # snapshot no longer matches, so completion flips to stale, not ready
    assert mgr.note_append("b.bcolzs", 1.5) == []  # building: not "flipped"
    assert mgr.absorb("k1", "b.bcolzs", dict(info), 2.0) == "stale"
    assert mgr.candidates(("a.bcolzs", "b.bcolzs")) == []


def test_sweep_caps_and_build_timeout(monkeypatch):
    monkeypatch.setenv("BQUERYD_TPU_ROLLUP_MAX", "1")
    mgr = rollupmod.RollupManager()
    info = {"data": b"x" * 100, "payload": {}, "base": b"b", "zones": {},
            "groups": 1, "mode": "rebuild"}
    for i, key in enumerate(("cold", "hot")):
        view = _view(filenames=(f"{key}.bcolzs",))
        spec = {"args": [[], [], []], "dag_wire": None}
        for _ in range(3):
            mgr.note_query(key, view, spec, float(i))
        mgr.start_build(key, float(i))
        mgr.absorb(key, f"{key}.bcolzs", dict(info), float(i))
    mgr.note_hit("hot", 10.0)
    dropped = mgr.sweep(11.0)
    assert dropped == [("cold", "count-cap")]
    assert set(mgr.entries) == {"hot"} and mgr.evictions == 1
    # byte cap evicts the same way
    monkeypatch.setenv("BQUERYD_TPU_ROLLUP_MAX", "16")
    monkeypatch.setenv("BQUERYD_TPU_ROLLUP_CACHE_BYTES", "10")
    assert mgr.sweep(12.0) == [("hot", "byte-cap")]
    # a wedged build is abandoned after the timeout
    monkeypatch.delenv("BQUERYD_TPU_ROLLUP_CACHE_BYTES")
    view = _view(filenames=("w.bcolzs",))
    spec = {"args": [[], [], []], "dag_wire": None}
    for _ in range(3):
        mgr.note_query("wedge", view, spec, 100.0)
    mgr.start_build("wedge", 100.0)
    assert mgr.sweep(100.0 + rollupmod.BUILD_TIMEOUT_S + 1) == [
        ("wedge", "build-timeout")
    ]


# ---------------------------------------------------------------------------
# worker: the rollup verb
# ---------------------------------------------------------------------------

def _worker_for(tmp_path, mem_store_url):
    from bqueryd_tpu.worker import WorkerNode

    return WorkerNode(
        coordination_url=mem_store_url,
        data_dir=str(tmp_path),
        loglevel=logging.WARNING,
        restart_check=False,
    )


def _rollup_msg(fname, keys=("g",), aggs=None, where=None,
                prior=None, base=None):
    from bqueryd_tpu.messages import CalcMessage

    msg = CalcMessage({"payload": "rollup", "token": "rollup_test"})
    msg.set_args_kwargs(
        [
            fname, list(keys),
            aggs or [["v", "sum", "vs"], ["f", "mean", "fm"]],
            where or [],
        ],
        {"aggregate": True},
    )
    if prior is not None:
        msg.add_as_binary("rollup_prior", prior)
        msg.add_as_binary("rollup_base", base)
    return msg


def test_worker_rollup_build_census_and_parity(tmp_path, mem_store_url):
    df = _frame(1500, seed=11)
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"), chunklen=256)
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        reply = worker.handle_work(_rollup_msg("t.bcolzs", keys=("g", "g2")))
        assert reply.get("rollup_mode") == "rebuild"
        payload = ResultPayload.from_bytes(reply["data"])
        assert payload["kind"] == "partials"
        got = _sorted(_finalize([payload]), ["g", "g2"])
        expected = _sorted(
            df.groupby(["g", "g2"], as_index=False).agg(
                vs=("v", "sum"), fm=("f", "mean")
            ),
            ["g", "g2"],
        )
        np.testing.assert_array_equal(got["vs"], expected["vs"])
        np.testing.assert_allclose(
            got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
        )
        # the census carries exactly what the lattice proofs need
        zones = reply.get_from_binary("rollup_zones")
        assert zones["g"]["kind"] == "int" and not zones["g"]["nulls"]
        assert zones["f"]["kind"] == "float" and zones["f"]["nulls"]
        assert zones["s"]["kind"] == "dict" and zones["s"]["zones"] is None
        assert [z[0] for z in zones["seq"]["zones"]][:2] == [0, 256]
        assert reply.get("rollup_base")  # growth fingerprint for refreshes
    finally:
        worker.socket.close()


def test_worker_rollup_refresh_delta_and_fresh(tmp_path, mem_store_url):
    root = str(tmp_path / "t.bcolzs")
    df = _frame(1500, seed=12)
    ctable.fromdataframe(df, root, chunklen=256)
    worker = _worker_for(tmp_path, mem_store_url)
    try:
        first = worker.handle_work(_rollup_msg("t.bcolzs"))
        base = first.get_from_binary("rollup_base")
        # no growth: the prior partials round-trip untouched
        again = worker.handle_work(
            _rollup_msg("t.bcolzs", prior=first["data"], base=base)
        )
        assert again.get("rollup_mode") == "fresh"
        assert again["data"] == first["data"]
        # append, then refresh: only the tail is aggregated and hostmerged
        extra = _frame(300, seed=13, offset=1500)
        ctable(root, mode="a").append_dataframe(extra)
        refreshed = worker.handle_work(
            _rollup_msg("t.bcolzs", prior=first["data"], base=base)
        )
        assert refreshed.get("rollup_mode") == "delta"
        full = pd.concat([df, extra], ignore_index=True)
        got = _sorted(
            _finalize([ResultPayload.from_bytes(refreshed["data"])]), ["g"]
        )
        expected = _sorted(
            full.groupby("g", as_index=False).agg(
                vs=("v", "sum"), fm=("f", "mean")
            ),
            ["g"],
        )
        np.testing.assert_array_equal(got["vs"], expected["vs"])
        np.testing.assert_allclose(
            got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
        )
        # a stale fingerprint (or rewrite) falls back to a full rebuild
        rebuilt = worker.handle_work(
            _rollup_msg("t.bcolzs", prior=first["data"], base=b"bogus")
        )
        assert rebuilt.get("rollup_mode") == "rebuild"
    finally:
        worker.socket.close()


# ---------------------------------------------------------------------------
# cluster: serving end to end
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
def serving_cluster(tmp_path, mem_store_url, monkeypatch):
    """Controller + one calc worker, serving enabled with the heat
    threshold lowered to 1 so the FIRST eligible query materializes
    (decay makes spaced repeat counts wall-clock dependent)."""
    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC

    monkeypatch.setenv("BQUERYD_TPU_SERVE", "1")
    monkeypatch.setenv("BQUERYD_TPU_ROLLUP_HEAT_MIN", "1")
    df = _frame(3000, seed=21)
    ctable.fromdataframe(df, str(tmp_path / "t.bcolzs"), chunklen=256)
    controller = ControllerNode(
        coordination_url=mem_store_url,
        loglevel=logging.WARNING,
        runfile_dir=str(tmp_path),
        heartbeat_interval=0.1,
    )
    worker = _worker_for(tmp_path, mem_store_url)
    worker.heartbeat_interval = 0.1
    worker.poll_timeout = 0.05
    threads = _start(controller, worker)

    # the cost model refuses to serve without advertised stats; stats ride
    # the WRM one-shot with a 60s re-send window, so a first WRM that beats
    # the controller's socket would otherwise stall the fixture
    def _stats_known():
        if (controller.shard_stats.get("t.bcolzs") or {}).get("rows") == 3000:
            return True
        worker._stats_sent_ts = 0.0
        return False

    wait_until(_stats_known, desc="shard stats advertisement")
    rpc = RPC(
        coordination_url=mem_store_url, timeout=30, loglevel=logging.WARNING
    )
    yield {
        "rpc": rpc, "controller": controller, "worker": worker,
        "df": df, "tmp_path": tmp_path,
    }
    _stop([controller, worker], threads)


def _ready_keys(controller):
    return [
        e.key for e in controller.serving.manager.entries.values()
        if e.state == "ready"
    ]


Q = (
    ["t.bcolzs"], ["g"],
    [["v", "sum", "vs"], ["f", "mean", "fm"]], [],
)


def _expected(df, keys=("g",)):
    return _sorted(
        df.groupby(list(keys), as_index=False).agg(
            vs=("v", "sum"), fm=("f", "mean")
        ),
        list(keys),
    )


def _assert_parity(got, expected, keys=("g",)):
    got = _sorted(got, list(keys))
    np.testing.assert_array_equal(got["vs"], expected["vs"])
    np.testing.assert_allclose(
        got["fm"].to_numpy(), expected["fm"].to_numpy(), rtol=1e-6
    )


def test_rollup_materializes_and_serves(serving_cluster):
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    df = serving_cluster["df"]
    r1 = rpc.groupby(*Q)
    assert rpc.last_call_answer_source in ("recompute", "cached")
    assert rpc.last_call_subsumed_from is None
    wait_until(lambda: _ready_keys(controller), desc="rollup materialization")
    r2 = rpc.groupby(*Q)
    assert rpc.last_call_answer_source == "rollup"
    assert rpc.last_call_subsumed_from in _ready_keys(controller)
    expected = _expected(df)
    _assert_parity(r1, expected)
    _assert_parity(r2, expected)
    assert controller.counters["rollup_builds"] >= 1
    assert controller.serving.served >= 1


def test_key_fold_and_zone_subsumption_end_to_end(serving_cluster):
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    df = serving_cluster["df"]
    fine = (
        ["t.bcolzs"], ["g", "g2"],
        [["v", "sum", "vs"], ["f", "mean", "fm"]], [],
    )
    rpc.groupby(*fine)
    wait_until(lambda: _ready_keys(controller), desc="fine rollup")
    fine_key = _ready_keys(controller)[0]
    # the coarser groupby folds the finer rollup's partials (g2 is a
    # null-free int column, proven by the build census)
    r = rpc.groupby(*Q)
    assert rpc.last_call_answer_source == "subsume"
    assert rpc.last_call_subsumed_from == fine_key
    _assert_parity(r, _expected(df))
    # a filter the zone maps prove selects every chunk whole serves the
    # stored bytes verbatim
    rz = rpc.groupby(
        ["t.bcolzs"], ["g", "g2"],
        [["v", "sum", "vs"], ["f", "mean", "fm"]], [["seq", ">=", 0]],
    )
    assert rpc.last_call_answer_source == "rollup"
    _assert_parity(rz, _expected(df, keys=("g", "g2")), keys=("g", "g2"))
    # a partial-chunk filter overlap is NEVER subsumed: recompute, exact
    rp = rpc.groupby(
        ["t.bcolzs"], ["g", "g2"],
        [["v", "sum", "vs"], ["f", "mean", "fm"]], [["seq", ">", 1000]],
    )
    assert rpc.last_call_answer_source in ("recompute", "cached")
    _assert_parity(
        rp, _expected(df[df["seq"] > 1000], keys=("g", "g2")),
        keys=("g", "g2"),
    )
    decisions = list(controller.serving.decisions)
    assert any(
        r2[1].startswith("filter-partial")
        for d in decisions for r2 in d["rejected"]
    )


def test_append_invalidates_then_delta_refreshes(serving_cluster):
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    df = serving_cluster["df"]
    rpc.groupby(*Q)
    wait_until(lambda: _ready_keys(controller), desc="rollup materialization")
    extra = _frame(240, seed=22, offset=3000)
    res = rpc.append("t.bcolzs", extra)
    assert res["appended"] == 240
    # the entry staled out the moment the append was dispatched: the
    # repeat query recomputes against the grown table, never serves stale
    full = pd.concat([df, extra], ignore_index=True)
    r = rpc.groupby(*Q)
    assert rpc.last_call_answer_source in ("recompute", "cached", "delta")
    _assert_parity(r, _expected(full))
    # the heartbeat sweep delta-refreshes the entry back to ready
    wait_until(
        lambda: _ready_keys(controller)
        and controller.counters["rollup_refreshes"] >= 1,
        desc="delta refresh",
    )
    entry = controller.serving.manager.entries[_ready_keys(controller)[0]]
    assert entry.per_file["t.bcolzs"]["mode"] == "delta"
    # stats must re-advertise before the cost model will serve again
    wait_until(
        lambda: (controller.shard_stats.get("t.bcolzs") or {}).get("rows")
        == 3240,
        desc="post-append stats re-advertisement",
    )
    r2 = rpc.groupby(*Q)
    assert rpc.last_call_answer_source == "rollup"
    _assert_parity(r2, _expected(full))


def test_kill_switch_restores_dispatch_path(serving_cluster, monkeypatch):
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    df = serving_cluster["df"]
    rpc.groupby(*Q)
    wait_until(lambda: _ready_keys(controller), desc="rollup materialization")
    monkeypatch.setenv("BQUERYD_TPU_SERVE", "0")
    r = rpc.groupby(*Q)
    assert rpc.last_call_answer_source in ("recompute", "cached")
    _assert_parity(r, _expected(df))
    assert controller.serving.snapshot()["enabled"] is False
    # flipping it back re-enables serving from the still-ready entry
    monkeypatch.setenv("BQUERYD_TPU_SERVE", "1")
    r2 = rpc.groupby(*Q)
    assert rpc.last_call_answer_source == "rollup"
    _assert_parity(r2, _expected(df))


def test_mixed_version_worker_degrades_to_recompute(
    serving_cluster, monkeypatch
):
    """A pre-PR-16 worker rejects the rollup verb with its base
    unhandled-payload error: the entry is dropped and serving stays on
    the (always correct) recompute path."""
    worker = serving_cluster["worker"]
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    df = serving_cluster["df"]

    def _old_worker(msg):
        raise ValueError(f"unhandled message payload: {msg.get('payload')}")

    monkeypatch.setattr(worker, "_rollup_build", _old_worker)
    r = rpc.groupby(*Q)
    _assert_parity(r, _expected(df))
    wait_until(
        lambda: any(
            e.get("kind") == "rollup_build_failed"
            and "UnsupportedVerb" in str(e.get("reason"))
            for e in controller.flight.events()
        ),
        desc="rollup build rejection",
    )
    assert controller.serving.manager.entries == {}
    r2 = rpc.groupby(*Q)
    assert rpc.last_call_answer_source in ("recompute", "cached")
    _assert_parity(r2, _expected(df))


def test_debug_bundle_serving_section_and_flight_events(serving_cluster):
    rpc = serving_cluster["rpc"]
    controller = serving_cluster["controller"]
    rpc.groupby(*Q)
    wait_until(lambda: _ready_keys(controller), desc="rollup materialization")
    rpc.groupby(*Q)
    assert rpc.last_call_answer_source == "rollup"
    bundle = rpc.debug_bundle()
    assert bundle["schema"] == "bqueryd_tpu.debug_bundle/4"
    serving = bundle["controller"]["serving"]
    assert serving["enabled"] is True and serving["served"] >= 1
    states = {e["state"] for e in serving["rollups"]["entries"]}
    assert "ready" in states
    assert any(
        d["source"] == "rollup" for d in serving["recent_decisions"]
    )
    kinds = {e["kind"] for e in controller.flight.events()}
    assert {"rollup_dispatch", "rollup_materialized", "serve_decision"} \
        <= kinds
    # provenance counter carries the per-source labels
    metrics = controller.metrics.render()
    assert 'bqueryd_tpu_serve_answers_total{source="rollup"}' in metrics
