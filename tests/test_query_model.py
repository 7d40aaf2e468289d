import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import GroupByQuery, QueryEngine, ResultPayload
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.storage import ctable


def taxi_like_df(n=15_000, seed=2):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "VendorID": rng.integers(1, 3, n).astype(np.int64),
            "passenger_count": rng.integers(0, 7, n).astype(np.int64),
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "trip_distance": rng.exponential(3.0, n),
            "fare_amount": rng.gamma(2.0, 7.0, n),
            "total_amount": rng.gamma(2.5, 8.0, n),
            "flag": rng.choice(["Y", "N"], n),
            "basket_id": np.sort(rng.integers(0, n // 4, n)).astype(np.int64),
        }
    )


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    df = taxi_like_df()
    root = str(tmp_path_factory.mktemp("qm") / "taxi.bcolz")
    ctable.fromdataframe(df, root)
    return df, ctable(root, mode="r")


def run_query(table, *args, **kw):
    df, ct = table
    query = GroupByQuery(*args, **kw)
    payload = QueryEngine().execute_local(ct, query)
    wire = ResultPayload.from_bytes(payload.to_bytes())  # exercise wire hop
    return df, hostmerge.payload_to_dataframe(hostmerge.merge_payloads([wire]))


def assert_frames_match(got, expected, key_cols):
    got = got.sort_values(key_cols).reset_index(drop=True)
    expected = expected.sort_values(key_cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, expected, check_dtype=False, check_column_type=False,
                                  check_index_type=False)


def test_single_key_sum(table):
    df, got = run_query(
        table, ["payment_type"], [["total_amount", "sum", "total_amount"]]
    )
    expected = df.groupby("payment_type")["total_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["payment_type"])


def test_multi_key_multi_agg(table):
    df, got = run_query(
        table,
        ["VendorID", "payment_type"],
        [
            ["fare_amount", "sum", "fare_sum"],
            ["fare_amount", "mean", "fare_mean"],
            ["passenger_count", "count", "n"],
        ],
    )
    g = df.groupby(["VendorID", "payment_type"])
    expected = pd.DataFrame(
        {
            "fare_sum": g["fare_amount"].sum(),
            "fare_mean": g["fare_amount"].mean(),
            "n": g["passenger_count"].count(),
        }
    ).reset_index()
    assert_frames_match(got, expected, ["VendorID", "payment_type"])


def test_string_key(table):
    df, got = run_query(table, ["flag"], [["fare_amount", "sum", "fare_amount"]])
    expected = df.groupby("flag")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["flag"])


def test_where_filter(table):
    df, got = run_query(
        table,
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        where_terms=[("trip_distance", ">", 4.0)],
    )
    expected = (
        df[df.trip_distance > 4.0]
        .groupby("payment_type")["total_amount"].sum().reset_index()
    )
    assert_frames_match(got, expected, ["payment_type"])


def test_unmatchable_filter_prunes_to_empty(table):
    df, got = run_query(
        table,
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        where_terms=[("payment_type", "==", 999)],
    )
    assert got.empty


def test_count_distinct(table):
    df, got = run_query(
        table,
        ["payment_type"],
        [["passenger_count", "count_distinct", "nuniq"]],
    )
    expected = (
        df.groupby("payment_type")["passenger_count"].nunique()
        .reset_index().rename(columns={"passenger_count": "nuniq"})
    )
    assert_frames_match(got, expected, ["payment_type"])


def test_count_distinct_sole_payload_device_kernel(table):
    """sole_payload=True routes count_distinct through the device sort
    kernel (final counts, no sets); results must match the sets path and
    pandas nunique, including under a filter and on a string column."""
    df, ct = table
    for value_col, where in [
        ("passenger_count", []),
        ("passenger_count", [("trip_distance", ">", 4.0)]),
        ("flag", []),
    ]:
        query = GroupByQuery(
            ["payment_type"],
            [[value_col, "count_distinct", "nuniq"]],
            where_terms=where,
            sole_payload=True,
        )
        payload = QueryEngine().execute_local(ct, query)
        # the device path ships counts, not value sets
        assert "distinct" in payload["aggs"][0]
        assert "distinct_offsets" not in payload["aggs"][0]
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([ResultPayload.from_bytes(payload.to_bytes())])
        )
        sub = df if not where else df[df.trip_distance > 4.0]
        expected = (
            sub.groupby("payment_type")[value_col].nunique()
            .reset_index().rename(columns={value_col: "nuniq"})
        )
        assert_frames_match(got, expected, ["payment_type"])


def test_distinct_values_payload_cap(table, monkeypatch):
    """The configurable cap rejects count_distinct payloads whose (group,
    value) pairs would exhaust memory, with an actionable error."""
    df, ct = table
    monkeypatch.setenv("BQUERYD_TPU_DISTINCT_VALUES_LIMIT", "3")
    query = GroupByQuery(
        ["payment_type"], [["passenger_count", "count_distinct", "nuniq"]]
    )
    with pytest.raises(ValueError, match="DISTINCT_VALUES_LIMIT"):
        QueryEngine().execute_local(ct, query)


def test_raw_rows_mode(table):
    df, got = run_query(
        table,
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        where_terms=[("trip_distance", ">", 8.0)],
        aggregate=False,
    )
    expected = df.loc[
        df.trip_distance > 8.0, ["payment_type", "total_amount"]
    ].reset_index(drop=True)
    pd.testing.assert_frame_equal(
        got.reset_index(drop=True), expected, check_dtype=False,
        check_column_type=False,
    )


def test_basket_expansion(table):
    df, got = run_query(
        table,
        ["payment_type"],
        [["total_amount", "sum", "total_amount"]],
        where_terms=[("trip_distance", ">", 10.0)],
        expand_filter_column="basket_id",
    )
    hit_baskets = df.loc[df.trip_distance > 10.0, "basket_id"].unique()
    expanded = df[df.basket_id.isin(hit_baskets)]
    expected = expanded.groupby("payment_type")["total_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["payment_type"])


def test_cross_worker_merge_matches_full(table):
    """Payloads computed on disjoint row sets (as different workers would)
    must merge into exactly the unsharded result."""
    df, _ = table
    query = GroupByQuery(
        ["payment_type"],
        [
            ["fare_amount", "sum", "s"],
            ["fare_amount", "mean", "m"],
            ["fare_amount", "min", "lo"],
            ["fare_amount", "max", "hi"],
        ],
    )
    engine = QueryEngine()
    payloads = []
    import tempfile

    for i in range(3):
        part = df.iloc[i::3]
        root = tempfile.mkdtemp() + "/part.bcolzs"
        ctable.fromdataframe(part, root)
        payloads.append(engine.execute_local(ctable(root, "r"), query))
    merged = hostmerge.merge_payloads(payloads)
    got = hostmerge.payload_to_dataframe(merged)
    g = df.groupby("payment_type")["fare_amount"]
    expected = pd.DataFrame(
        {"s": g.sum(), "m": g.mean(), "lo": g.min(), "hi": g.max()}
    ).reset_index()
    assert_frames_match(got, expected, ["payment_type"])


def test_merge_empty_payloads():
    merged = hostmerge.merge_payloads([ResultPayload.empty(), ResultPayload.empty()])
    assert merged["kind"] == "empty"
    assert hostmerge.payload_to_dataframe(merged).empty


def test_agg_list_normalization():
    q = GroupByQuery(["k"], ["v", ["w", "mean"], ["x", "sum", "y"]])
    assert q.agg_list == [["v", "sum", "v"], ["w", "mean", "w"], ["x", "sum", "y"]]


def test_basket_expansion_null_baskets_are_one_group(tmp_path):
    """Dict-encoded basket columns with nulls: the null rows form ONE
    ordinary basket (the factorize runs over the physical codes, so -1 is
    a value like any other — the engine's long-standing semantics, kept
    when the factorize cache was introduced)."""
    from bqueryd_tpu.storage.ctable import ctable as CT

    df = pd.DataFrame(
        {
            "g": [1, 1, 2, 2, 1, 2],
            "v": [10, 20, 30, 40, 50, 60],
            "basket": ["a", None, None, "b", "a", None],
            "d": [0.0, 99.0, 0.0, 0.0, 0.0, 0.0],
        }
    )
    root = str(tmp_path / "nb.bcolz")
    CT.fromdataframe(df, root)
    query = GroupByQuery(
        ["g"],
        [["v", "sum", "s"]],
        [["d", ">", 50.0]],
        aggregate=True,
        expand_filter_column="basket",
    )
    payload = QueryEngine().execute_local(CT(root), query)
    from bqueryd_tpu.parallel import hostmerge

    got = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload]))
    # the matching row (d=99) has a NULL basket -> every null-basket row is
    # selected: rows v=20 (g=1), v=30 and v=60 (g=2)
    got = got.sort_values("g").reset_index(drop=True)
    assert got["g"].tolist() == [1, 2]
    assert got["s"].tolist() == [20, 90]


def test_null_dict_key_group_is_dropped(tmp_path):
    """A dict-encoded groupby key with nulls (code -1) must NOT produce a
    group: null-key rows vanish from the aggregation (pandas dropna
    semantics, and the mesh executor's convention).  Regression test — the
    old single-shard path re-factorized -1 into a real group whose collect
    then indexed key_values[-1], emitting a duplicate of the LAST key with
    the null rows' sum."""
    from bqueryd_tpu.storage.ctable import ctable as CT

    df = pd.DataFrame(
        {"k": ["a", None, "b", "a", None], "v": [1, 2, 3, 4, 5]}
    )
    root = str(tmp_path / "nullkey.bcolz")
    CT.fromdataframe(df, root)
    query = GroupByQuery(["k"], [["v", "sum", "s"]], [], aggregate=True)
    payload = QueryEngine().execute_local(CT(root), query)
    got = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload]))
    got = got.sort_values("k").reset_index(drop=True)
    assert got["k"].tolist() == ["a", "b"]
    assert got["s"].tolist() == [5, 3]


def test_null_dict_key_multikey_both_paths(tmp_path, monkeypatch):
    """Multi-key composites poison null keys to -1; both the dense-combos
    path (small composite space) and the compaction path (forced via a
    zero cap) must drop them and agree with pandas."""
    from bqueryd_tpu.models import query as qmod
    from bqueryd_tpu.storage.ctable import ctable as CT

    df = pd.DataFrame(
        {
            "k": ["a", None, "b", "a", None, "b", "a"],
            "g": [1, 1, 2, 2, 1, 2, 1],
            "v": [1, 2, 3, 4, 5, 6, 7],
        }
    )
    root = str(tmp_path / "nullmk.bcolz")
    CT.fromdataframe(df, root)
    expected = (
        df.groupby(["k", "g"])["v"].sum().reset_index(name="s")
    )
    for cap in (qmod._DENSE_COMBO_CAP, 0):
        monkeypatch.setattr(qmod, "_DENSE_COMBO_CAP", cap)
        query = GroupByQuery(
            ["k", "g"], [["v", "sum", "s"]], [], aggregate=True
        )
        payload = QueryEngine().execute_local(CT(root), query)
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([payload])
        )
        got = got.sort_values(["k", "g"]).reset_index(drop=True)
        assert got["k"].tolist() == expected["k"].tolist(), f"cap={cap}"
        assert got["g"].tolist() == expected["g"].tolist(), f"cap={cap}"
        assert got["s"].tolist() == expected["s"].tolist(), f"cap={cap}"


def test_mixed_width_unsigned_shards_merge(tmp_path):
    """One shard stores a column as uint64, a sibling as uint32: the
    engine tags them 'uint64' and None respectively, and the merge must
    reconcile to the unsigned view instead of rejecting the payloads."""
    from bqueryd_tpu.storage.ctable import ctable as CT

    a = pd.DataFrame(
        {"g": [1, 2], "v": np.array([2**63, 7], dtype=np.uint64)}
    )
    b = pd.DataFrame(
        {"g": [1, 2], "v": np.array([5, 9], dtype=np.uint32)}
    )
    pa, pb = str(tmp_path / "a.bcolzs"), str(tmp_path / "b.bcolzs")
    CT.fromdataframe(a, pa)
    CT.fromdataframe(b, pb)
    query = GroupByQuery(
        ["g"],
        [["v", "sum", "s"], ["v", "min", "lo"], ["v", "max", "hi"]],
        [],
        aggregate=True,
    )
    engine = QueryEngine()
    payloads = [
        engine.execute_local(CT(p), query) for p in (pa, pb)
    ]
    for order in (payloads, payloads[::-1]):  # order independence
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads(list(order))
        )
        got = got.sort_values("g").reset_index(drop=True)
        assert got["s"].tolist() == [2**63 + 5, 16]
        assert str(got["s"].dtype) == "uint64"
        # extrema must widen across payload dtypes, not truncate into
        # the narrower first payload's range
        assert got["lo"].tolist() == [5, 7]
        assert got["hi"].tolist() == [2**63, 9]

    # the same mixed-width shards on ONE worker (mesh executor) widen via
    # result_type and must tag the unsigned view the same way
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor

    q2 = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)
    payload = MeshQueryExecutor().execute([CT(pa), CT(pb)], q2)
    got3 = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    )
    got3 = got3.sort_values("g").reset_index(drop=True)
    assert got3["s"].tolist() == [2**63 + 5, 16]
    assert str(got3["s"].dtype) == "uint64"


def test_uint64_mixed_with_float_shard_is_refused(tmp_path):
    """A uint64 shard merging with a FLOAT sibling of the same column
    cannot keep the unsigned reinterpretation (the widened float total is
    not mod-2^64 bits); the merge must refuse loudly, not corrupt."""
    import pytest as _pytest

    from bqueryd_tpu.storage.ctable import ctable as CT

    a = pd.DataFrame(
        {"g": [1], "v": np.array([2**63], dtype=np.uint64)}
    )
    b = pd.DataFrame({"g": [1], "v": np.array([0.5], dtype=np.float64)})
    pa, pb = str(tmp_path / "a.bcolzs"), str(tmp_path / "b.bcolzs")
    CT.fromdataframe(a, pa)
    CT.fromdataframe(b, pb)
    query = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)
    engine = QueryEngine()
    payloads = [engine.execute_local(CT(p), query) for p in (pa, pb)]
    with _pytest.raises(ValueError, match="disagree"):
        hostmerge.merge_payloads(payloads)


def test_merge_tolerates_payload_without_value_kinds(tmp_path):
    """A payload missing ``value_kinds`` entirely (a worker still running a
    pre-kinds build during a rolling restart) must merge with a new-build
    payload for plain numeric measures — only genuinely incompatible kinds
    (uint64/datetime finalize next to kindless data) may refuse."""
    import pytest as _pytest

    from bqueryd_tpu.storage.ctable import ctable as CT

    a = pd.DataFrame({"g": [1, 2], "v": np.array([3, 4], dtype=np.int64)})
    b = pd.DataFrame({"g": [2, 3], "v": np.array([5, 6], dtype=np.int64)})
    pa, pb = str(tmp_path / "a.bcolzs"), str(tmp_path / "b.bcolzs")
    CT.fromdataframe(a, pa)
    CT.fromdataframe(b, pb)
    query = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)
    engine = QueryEngine()
    payloads = [engine.execute_local(CT(p), query) for p in (pa, pb)]
    assert "value_kinds" in payloads[0]
    del payloads[0]["value_kinds"]  # simulate the old-build worker
    for order in (payloads, payloads[::-1]):
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads(list(order))
        ).sort_values("g").reset_index(drop=True)
        assert got["g"].tolist() == [1, 2, 3]
        assert got["s"].tolist() == [3, 9, 6]

    # but a uint64-kind payload next to a kindless one is ambiguous (the
    # kindless sum may be a wrapped int64): still refused
    u = pd.DataFrame(
        {"g": [1], "v": np.array([2**63 + 1], dtype=np.uint64)}
    )
    pu = str(tmp_path / "u.bcolzs")
    CT.fromdataframe(u, pu)
    p_old = engine.execute_local(CT(pa), query)
    del p_old["value_kinds"]
    p_new = engine.execute_local(CT(pu), query)
    assert "uint64" in p_new["value_kinds"]
    with _pytest.raises(ValueError, match="disagree"):
        hostmerge.merge_payloads([p_old, p_new])


def test_engine_reports_route(table):
    _df, ct = table
    engine = QueryEngine()
    query = GroupByQuery(["passenger_count"], [["payment_type", "sum", "s"]])
    engine.execute_local(ct, query)
    assert engine.last_effective_strategy == "matmul"
    engine.execute_local(ct, query, strategy="host")
    assert engine.last_effective_strategy == "host"
    engine.execute_local(ct, query, strategy="scatter")
    assert engine.last_effective_strategy == "scatter"
