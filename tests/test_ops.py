import numpy as np
import pandas as pd
import pytest

# one namespace for all kernel entry points (module names are shadowed by the
# function re-exports in bqueryd_tpu.ops, so don't import submodules directly)
from bqueryd_tpu import ops as fz
from bqueryd_tpu import ops as gb
from bqueryd_tpu import ops as pred
from bqueryd_tpu.storage import ctable


def taxi_like_df(n=20_000, seed=1):
    rng = np.random.default_rng(seed)
    fare = rng.gamma(2.0, 7.0, n)
    fare[rng.random(n) < 0.01] = np.nan  # exercise NaN skipping
    return pd.DataFrame(
        {
            "VendorID": rng.integers(1, 3, n).astype(np.int64),
            "passenger_count": rng.integers(0, 7, n).astype(np.int64),
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "trip_distance": rng.exponential(3.0, n),
            "fare_amount": fare,
            "total_amount": rng.gamma(2.5, 8.0, n),
        }
    )


# ---------------------------------------------------------------------------
# factorize
# ---------------------------------------------------------------------------

def test_factorize_int_matches_pandas():
    values = np.array([5, 2, 5, 9, 2, 5, -3], dtype=np.int64)
    codes, uniques = fz.factorize(values)
    pd_codes, pd_uniques = pd.factorize(values)
    np.testing.assert_array_equal(codes, pd_codes)
    np.testing.assert_array_equal(uniques, pd_uniques)


def test_factorize_float():
    values = np.array([1.5, 0.5, 1.5, 2.5])
    codes, uniques = fz.factorize(values)
    np.testing.assert_array_equal(uniques[codes], values)
    assert uniques.tolist() == [1.5, 0.5, 2.5]


def test_factorize_device_fixed_capacity():
    import jax.numpy as jnp

    keys = jnp.array([7, 3, 7, 7, 1], dtype=jnp.int64)
    uniques, codes, n = fz.factorize_device(keys, capacity=8)
    assert int(n) == 3
    np.testing.assert_array_equal(np.asarray(uniques)[codes], np.asarray(keys))


def test_pack_unpack_codes_roundtrip():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 5, 100).astype(np.int64)
    b = rng.integers(0, 7, 100).astype(np.int64)
    c = rng.integers(0, 3, 100).astype(np.int64)
    packed = fz.pack_codes([a, b, c], [5, 7, 3])
    ua, ub, uc = fz.unpack_codes(packed, [5, 7, 3])
    np.testing.assert_array_equal(ua, a)
    np.testing.assert_array_equal(ub, b)
    np.testing.assert_array_equal(uc, c)


def test_pack_codes_null_poisons():
    packed = fz.pack_codes(
        [np.array([0, -1, 2]), np.array([1, 1, -1])], [3, 2]
    )
    assert packed.tolist() == [1, -1, -1]


# ---------------------------------------------------------------------------
# groupby kernels vs pandas
# ---------------------------------------------------------------------------

def run_groupby(df, key, measure, op, mask=None):
    codes, uniques = fz.factorize(df[key].to_numpy())
    tables, rows = gb.groupby_aggregate(
        codes,
        (df[measure].to_numpy(),),
        (op,),
        n_groups=len(uniques),
        mask=None if mask is None else np.asarray(mask),
    )
    return uniques, np.asarray(tables[0]), np.asarray(rows)


@pytest.mark.parametrize("op,pandas_op", [
    ("sum", "sum"), ("mean", "mean"), ("count", "count"),
    ("min", "min"), ("max", "max"),
])
def test_groupby_matches_pandas(op, pandas_op):
    df = taxi_like_df()
    uniques, got, _rows = run_groupby(df, "payment_type", "fare_amount", op)
    expected = getattr(df.groupby("payment_type")["fare_amount"], pandas_op)()
    got_series = pd.Series(got, index=uniques).sort_index()
    pd.testing.assert_series_equal(
        got_series, expected.sort_index(), check_names=False,
        check_index_type=False, check_dtype=False,
    )


def test_groupby_int64_sum_bit_exact():
    """North-star criterion: int64 sums agree bit-for-bit with a CPU
    reference (numpy bincount accumulation)."""
    rng = np.random.default_rng(11)
    n = 100_000
    keys = rng.integers(0, 50, n).astype(np.int64)
    # large values to exercise 64-bit range (sums far beyond int32)
    values = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    codes, uniques = fz.factorize(keys)
    tables, _ = gb.groupby_aggregate(codes, (values,), ("sum",), len(uniques))
    got = np.asarray(tables[0])
    expected = np.zeros(len(uniques), dtype=np.int64)
    np.add.at(expected, codes, values)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, expected)


def _groupby_module():
    # module names are shadowed by the function re-exports in bqueryd_tpu.ops
    import sys

    import bqueryd_tpu.ops.groupby  # noqa: F401

    return sys.modules["bqueryd_tpu.ops.groupby"]


def test_highcard_bench_shape_stays_on_blocked_path(request, monkeypatch):
    """The kernel route of BASELINE config 5 (10 M rows x 70,225 groups) and
    of the benchmark's ``highcard`` (11 010 048 rows x 73 728): on a CPU
    backend the bucket count stays inside ``_MAX_BLOCK_SEGMENTS``, so the
    exact int32 blocked scatter handles it — not the emulated-s64 fallback
    that cost ~3 s in round 3; on an accelerator, where that scatter retires
    an update every 8.8 ns, the one carried-payload sort does (PR 33)."""
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    m = _groupby_module()
    ints = (np.zeros(1, np.int32),)
    for n, n_groups in ((10_000_000, 70_225), (11_010_048, 73_728)):
        assert -(-n // m._SUM_BLOCK) * n_groups <= m._MAX_BLOCK_SEGMENTS
        assert not m._int_sums_sort(n, n_groups)
        assert m.kernel_route(None, ints, ("sum",), n, n_groups) == "scatter"
    m = request.getfixturevalue("groupby_as_accelerator")
    for n, n_groups in ((10_000_000, 70_225), (11_010_048, 73_728)):
        assert m._int_sums_sort(n, n_groups)
        assert m.kernel_route(None, ints, ("sum",), n, n_groups) == "sort"
        # the binding hints still name their own forms
        assert m.kernel_route(
            "scatter", ints, ("sum",), n, n_groups) == "scatter"


def test_groupby_highcard_int64_sum_bit_exact():
    """>=64k groups on the blocked-scatter path, full int64 range (block
    limb sums exercise the mod-2^32 wrap recovery)."""
    rng = np.random.default_rng(5)
    n, n_groups = 300_000, 70_000
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    info = np.iinfo(np.int64)
    values = rng.integers(info.min // 4, info.max // 4, n).astype(np.int64)
    values[:100] = info.max
    values[100:200] = info.min
    m = _groupby_module()
    assert -(-n // m._SUM_BLOCK) * n_groups <= m._MAX_BLOCK_SEGMENTS
    tables, _ = gb.groupby_aggregate(codes, (values,), ("sum",), n_groups)
    expected = np.zeros(n_groups, dtype=np.int64)
    with np.errstate(over="ignore"):
        np.add.at(expected, codes, values)
    np.testing.assert_array_equal(np.asarray(tables[0]), expected)


def test_groupby_uint16_blocked_wrap_recovery(monkeypatch):
    """A 64 Ki block of max uint16 values sums to 2^32 - 2^16: the int32
    scatter wraps negative and the uint32 bitcast must recover it exactly.
    The MXU route is disabled so the blocked scatter actually runs (at
    n_groups=1 the matmul path would otherwise absorb this case)."""
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", "0")
    n = 70_000  # > one block
    codes = np.zeros(n, dtype=np.int32)
    values = np.full(n, np.iinfo(np.uint16).max, dtype=np.uint16)
    tables, _ = gb.groupby_aggregate(codes, (values,), ("sum",), 1)
    assert int(np.asarray(tables[0])[0]) == n * 65535


def test_sorted_segment_sum_bit_exact():
    """The extreme-cardinality sort-based path, directly and via the public
    API (forced by shrinking the bucket budget)."""
    import jax.numpy as jnp

    m = _groupby_module()
    rng = np.random.default_rng(17)
    n, n_groups = 50_000, 4_096
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    info = np.iinfo(np.int64)
    values = rng.integers(info.min // 2, info.max // 2, n).astype(np.int64)
    expected = np.zeros(n_groups, dtype=np.int64)
    with np.errstate(over="ignore"):
        np.add.at(expected, codes, values)
    got = m._sorted_segment_sum(jnp.asarray(values), jnp.asarray(codes), n_groups)
    np.testing.assert_array_equal(np.asarray(got), expected)


def test_int64_segment_sum_routes_to_sorted_past_budget(monkeypatch):
    m = _groupby_module()
    # disable the MXU route (37 groups would otherwise take the matmul path
    # and never reach the scatter/sorted routing being pinned here) and
    # shrink the bucket budget so the sorted path must serve the query
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_GROUPS", "0")
    monkeypatch.setattr(m, "_MAX_BLOCK_SEGMENTS", 0)
    rng = np.random.default_rng(23)
    n, n_groups = 9_973, 37  # unique shape: avoids a stale jit cache entry
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    values = rng.integers(-(2**60), 2**60, n).astype(np.int64)
    tables, rows = gb.groupby_aggregate(codes, (values,), ("sum",), n_groups)
    expected = np.zeros(n_groups, dtype=np.int64)
    np.add.at(expected, codes, values)
    np.testing.assert_array_equal(np.asarray(tables[0]), expected)
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(codes, minlength=n_groups)
    )


_I64 = np.iinfo(np.int64)


def _sorted_form_case(name):
    """(codes, measures, ops, n_groups, mask, sentinels) of one case of the
    sorted form's test."""
    rng = np.random.default_rng(33)
    n, n_groups, mask, sentinels = 5_000, 40, None, None
    if name == "one_row":
        n = 1
    elif name == "groups_past_65536":
        n, n_groups = 90_000, 70_001
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    dtypes = {"int8": np.int8, "int16": np.int16, "int32": np.int32,
              "int64": np.int64, "uint16": np.uint16}
    ops = ("sum",)
    if name in dtypes:
        info = np.iinfo(dtypes[name])
        values = rng.integers(
            info.min, info.max, n, dtype=np.int64, endpoint=True
        ).astype(dtypes[name])
        values[:2] = info.min, info.max
        measures = (values,)
    elif name == "bool":
        measures = (rng.random(n) < 0.4,)
    elif name == "full_int64_range_wraps":
        # extremes in one group: the true sum leaves int64 and wraps
        values = rng.integers(_I64.min, _I64.max, n, dtype=np.int64)
        values[:64] = _I64.max
        codes[:64] = 3
        values[64:96] = _I64.min
        codes[64:96] = 4
        measures = (values,)
    elif name == "two_sums_and_a_count":
        measures = (
            rng.integers(_I64.min // 2, _I64.max // 2, n).astype(np.int64),
            rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),
            rng.integers(0, 9, n).astype(np.int16),
        )
        ops = ("sum", "sum", "count")
    elif name == "datetime_count_with_nat":
        stamps = rng.integers(0, 10**15, n).astype(np.int64)
        stamps[::3] = _I64.min  # NaT
        measures = (stamps, stamps)
        ops = ("count", "count_na")
        sentinels = (_I64.min, _I64.min)
    else:
        measures = (rng.integers(-(10**12), 10**12, n).astype(np.int64),)
    if name == "mask":
        mask = rng.random(n) < 0.6
    elif name == "negative_codes":
        codes[::5] = -1
    elif name == "empty_groups":
        codes[(codes % 3 == 0) | (codes < 2) | (codes > n_groups - 3)] = 7
    elif name == "every_row_invalid":
        codes[:] = -1
    return codes, measures, ops, n_groups, mask, sentinels


@pytest.mark.parametrize(
    "case",
    ["int8", "int16", "int32", "int64", "uint16", "bool",
     "full_int64_range_wraps", "mask", "negative_codes", "empty_groups",
     "every_row_invalid", "one_row", "groups_past_65536",
     "two_sums_and_a_count", "datetime_count_with_nat"],
)
def test_sorted_form_matches_add_at_bit_for_bit(case):
    """The one carried-payload sort and its prefix differences (a binding
    ``sort`` hint reaches it on this backend) against ``np.add.at``: every
    table int64 and equal bit for bit, wrapping mod 2^64 like the blocked
    scatter, whose own answer is compared too."""
    import jax

    codes, measures, ops, n_groups, mask, sentinels = _sorted_form_case(case)
    out = jax.device_get(gb.partial_tables(
        codes, measures, ops, n_groups, mask=mask, null_sentinels=sentinels,
        strategy="sort",
    ))
    keep = codes >= 0
    if mask is not None:
        keep &= mask
    rows = np.bincount(codes[keep], minlength=n_groups)
    assert out["rows"].dtype == np.int64
    np.testing.assert_array_equal(out["rows"], rows)
    for values, op, agg in zip(measures, ops, out["aggs"]):
        if op == "sum":
            want = np.zeros(n_groups, dtype=np.int64)
            with np.errstate(over="ignore"):
                np.add.at(want, codes[keep], values[keep].astype(np.int64))
            got = agg["sum"]
        else:
            null = values == _I64.min if sentinels else np.zeros(len(values), bool)
            counted = keep & (null if op == "count_na" else ~null)
            want = np.bincount(codes[counted], minlength=n_groups)
            got = agg["count"]
        assert got.dtype == np.int64 and got.shape == (n_groups,)
        np.testing.assert_array_equal(got, want)
    blocked = jax.device_get(gb.partial_tables(
        codes, measures, ops, n_groups, mask=mask, null_sentinels=sentinels,
        strategy="scatter",
    ))
    assert jax.tree_util.tree_structure(out) == \
        jax.tree_util.tree_structure(blocked)
    for got, want in zip(jax.tree_util.tree_leaves(out),
                         jax.tree_util.tree_leaves(blocked)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_sorted_form_lowers_to_one_sort_and_no_scatter():
    """What the form is for, read off the lowered module of a two-sum
    integer query under ``sort``: exactly one sort, whatever the number of
    sums, no scatter, and no gather but of one element or one row of sorted
    keys a group (the boundary search, ``_group_ends``)."""
    import re

    import jax

    n, n_groups = 3 * 65536 + 17, 1_000
    codes = np.zeros(n, np.int32)
    measures = (np.zeros(n, np.int64), np.zeros(n, np.int32))
    text = jax.jit(
        lambda c, m, k: gb.partial_tables(
            c, m, ("sum", "sum"), n_groups, mask=k, strategy="sort")
    ).lower(codes, measures, np.ones(n, bool)).as_text()
    assert text.count('"stablehlo.sort"(') == 1
    assert "stablehlo.scatter" not in text
    gathered = re.findall(r"stablehlo\.gather.*->\s*tensor<(\d+)x", text)
    assert gathered and {int(g) for g in gathered} == {n_groups}
    assert "dynamic_slice" not in text and "dynamic_gather" not in text
    # the blocked form of the same query, for contrast: a scatter a limb
    blocked = jax.jit(
        lambda c, m, k: gb.partial_tables(
            c, m, ("sum", "sum"), n_groups, mask=k, strategy="scatter")
    ).lower(codes, measures, np.ones(n, bool)).as_text()
    assert "stablehlo.sort" not in blocked
    assert blocked.count('"stablehlo.scatter"(') == 1 + 4 + 2


def test_groupby_count_na():
    df = taxi_like_df()
    uniques, got, _ = run_groupby(df, "payment_type", "fare_amount", "count_na")
    expected = df["fare_amount"].isna().groupby(df["payment_type"]).sum()
    got_series = pd.Series(got, index=uniques).sort_index()
    pd.testing.assert_series_equal(
        got_series, expected.sort_index(), check_names=False,
        check_index_type=False, check_dtype=False,
    )


def test_groupby_multikey_via_packed_codes():
    df = taxi_like_df()
    c1, u1 = fz.factorize(df["VendorID"].to_numpy())
    c2, u2 = fz.factorize(df["payment_type"].to_numpy())
    packed = fz.pack_codes([c1, c2], [len(u1), len(u2)])
    dense, combos = fz.factorize(packed)
    tables, rows = gb.groupby_aggregate(
        dense, (df["total_amount"].to_numpy(),), ("sum",), len(combos)
    )
    got = {}
    for combo, value in zip(combos, np.asarray(tables[0])):
        i1, i2 = divmod(int(combo), len(u2))
        got[(u1[i1], u2[i2])] = value
    expected = df.groupby(["VendorID", "payment_type"])["total_amount"].sum()
    assert set(got) == set(expected.index)
    for key, value in expected.items():
        assert got[key] == pytest.approx(value)


def test_groupby_mask_pushdown_matches_filtered_pandas():
    df = taxi_like_df()
    mask = (df["trip_distance"] > 5.0).to_numpy()
    uniques, got, rows = run_groupby(df, "payment_type", "total_amount", "sum", mask)
    expected = df[mask].groupby("payment_type")["total_amount"].sum()
    got_series = pd.Series(got, index=uniques)[rows > 0].sort_index()
    pd.testing.assert_series_equal(
        got_series, expected.sort_index(), check_names=False,
        check_index_type=False, check_dtype=False,
    )


def test_groupby_negative_codes_dropped():
    codes = np.array([0, -1, 1, 0], dtype=np.int32)
    values = np.array([10.0, 99.0, 20.0, 30.0])
    tables, rows = gb.groupby_aggregate(codes, (values,), ("sum",), 2)
    assert np.asarray(tables[0]).tolist() == [40.0, 20.0]
    assert np.asarray(rows).tolist() == [2, 1]


def test_partials_merge_equals_full():
    """Merging per-shard partials must equal the unsharded result — the
    invariant the psum merge relies on (shard-vs-full equivalence, reference
    tests/test_simple_rpc.py:175-190)."""
    df = taxi_like_df(n=9_000)
    shards = [df.iloc[i::3] for i in range(3)]
    key_uniques = np.unique(df["payment_type"].to_numpy())
    n_groups = len(key_uniques)
    ops = ("sum", "mean", "count", "min", "max")

    def shard_partials(part):
        codes = np.searchsorted(key_uniques, part["payment_type"].to_numpy())
        measures = tuple(part["fare_amount"].to_numpy() for _ in ops)
        return gb.partial_tables(
            codes.astype(np.int32), measures, ops, n_groups
        )

    merged = shard_partials(shards[0])
    for s in shards[1:]:
        merged = gb.combine_partials(merged, shard_partials(s))
    merged_tables = gb.finalize(merged, ops)

    full = shard_partials(df)
    full_tables = gb.finalize(full, ops)
    for m, f in zip(merged_tables, full_tables):
        np.testing.assert_allclose(np.asarray(m), np.asarray(f), rtol=1e-12)


def test_weighted_mean_not_sum_of_means():
    """The reference merges shard means by summing them (reference
    bqueryd/rpc.py:171); the partial representation must produce the true
    weighted mean instead."""
    a = pd.DataFrame({"k": [1, 1, 1], "v": [1.0, 1.0, 1.0]})   # mean 1, n=3
    b = pd.DataFrame({"k": [1], "v": [5.0]})                    # mean 5, n=1
    ops = ("mean",)

    def partials(df):
        codes = np.zeros(len(df), dtype=np.int32)
        return gb.partial_tables(codes, (df["v"].to_numpy(),), ops, 1)

    merged = gb.combine_partials(partials(a), partials(b))
    mean = float(gb.finalize(merged, ops)[0][0])
    assert mean == pytest.approx(2.0)      # (3*1 + 5)/4, NOT 1+5=6


def test_count_distinct_matches_pandas():
    df = taxi_like_df()
    gcodes, guniques = fz.factorize(df["payment_type"].to_numpy())
    vcodes, vuniques = fz.factorize(df["passenger_count"].to_numpy())
    got = gb.groupby_count_distinct(gcodes, vcodes, n_groups=len(guniques))
    expected = df.groupby("payment_type")["passenger_count"].nunique()
    got_series = pd.Series(np.asarray(got), index=guniques).sort_index()
    pd.testing.assert_series_equal(
        got_series, expected.sort_index(), check_names=False,
        check_index_type=False, check_dtype=False,
    )


def test_sorted_count_distinct_on_sorted_data():
    df = taxi_like_df().sort_values(["payment_type", "passenger_count"])
    gcodes, guniques = fz.factorize(df["payment_type"].to_numpy())
    got = gb.groupby_sorted_count_distinct(
        gcodes, df["passenger_count"].to_numpy(), n_groups=len(guniques)
    )
    expected = df.groupby("payment_type")["passenger_count"].nunique()
    got_series = pd.Series(np.asarray(got), index=guniques).sort_index()
    pd.testing.assert_series_equal(
        got_series, expected.sort_index(), check_names=False,
        check_index_type=False, check_dtype=False,
    )


# ---------------------------------------------------------------------------
# predicates
# ---------------------------------------------------------------------------

@pytest.fixture
def taxi_table(tmp_path):
    df = taxi_like_df(n=5_000)
    df["store_and_fwd_flag"] = np.where(df["VendorID"] == 1, "Y", "N")
    root = str(tmp_path / "taxi.bcolz")
    ctable.fromdataframe(df, root)
    return df, ctable(root, mode="r")


@pytest.mark.parametrize("term,pandas_expr", [
    (("trip_distance", ">", 5.0), lambda d: d.trip_distance > 5.0),
    (("trip_distance", "<=", 1.0), lambda d: d.trip_distance <= 1.0),
    (("payment_type", "==", 2), lambda d: d.payment_type == 2),
    (("payment_type", "!=", 2), lambda d: d.payment_type != 2),
    (("payment_type", "in", [1, 3]), lambda d: d.payment_type.isin([1, 3])),
    (("payment_type", "not in", [1, 3]), lambda d: ~d.payment_type.isin([1, 3])),
    (("store_and_fwd_flag", "==", "Y"), lambda d: d.store_and_fwd_flag == "Y"),
])
def test_term_masks_match_pandas(taxi_table, term, pandas_expr):
    df, table = taxi_table
    mask = pred.build_mask(table, [term])
    np.testing.assert_array_equal(np.asarray(mask), pandas_expr(df).to_numpy())


def test_multi_term_conjunction(taxi_table):
    df, table = taxi_table
    mask = pred.build_mask(
        table, [("trip_distance", ">", 2.0), ("payment_type", "==", 1)]
    )
    expected = (df.trip_distance > 2.0) & (df.payment_type == 1)
    np.testing.assert_array_equal(np.asarray(mask), expected.to_numpy())


def test_unknown_dict_value_semantics(taxi_table):
    _df, table = taxi_table
    assert not np.asarray(
        pred.build_mask(table, [("store_and_fwd_flag", "==", "MISSING")])
    ).any()
    assert np.asarray(
        pred.build_mask(table, [("store_and_fwd_flag", "!=", "MISSING")])
    ).all()


def test_empty_terms_is_none(taxi_table):
    _df, table = taxi_table
    assert pred.build_mask(table, []) is None


def test_shard_can_match_pruning(taxi_table):
    _df, table = taxi_table
    # trip_distance >= 0 always; a > max(col) filter can never match
    hi = table.col_stats("trip_distance")[1]
    assert not pred.shard_can_match(table, [("trip_distance", ">", hi + 1)])
    assert pred.shard_can_match(table, [("trip_distance", ">", hi - 1)])
    assert not pred.shard_can_match(table, [("payment_type", "==", 99)])
    assert not pred.shard_can_match(
        table, [("store_and_fwd_flag", "==", "MISSING")]
    )
    assert pred.shard_can_match(table, [("store_and_fwd_flag", "==", "Y")])


def test_sorted_count_distinct_masked_run_leader():
    """A mask dropping the first row of a run must not hide the run
    (regression: boundary detection vs previous *valid* row)."""
    codes = np.array([0, 0], dtype=np.int32)
    values = np.array([5.0, 5.0])
    got = gb.groupby_sorted_count_distinct(
        codes, values, n_groups=1, mask=np.array([False, True])
    )
    assert int(got[0]) == 1


def test_unpack_codes_preserves_null():
    out = fz.unpack_codes(np.array([-1, 3]), [3, 2])
    assert out[0].tolist() == [-1, 1]
    assert out[1].tolist() == [-1, 1]


def test_in_with_set_on_numeric_column(tmp_path):
    df = pd.DataFrame({"payment_type": np.array([1, 2, 3, 4], dtype=np.int64)})
    root = str(tmp_path / "t.bcolz")
    ctable.fromdataframe(df, root)
    table = ctable(root, mode="r")
    mask = pred.build_mask(table, [("payment_type", "in", {1, 3})])
    assert np.asarray(mask).tolist() == [True, False, True, False]


def test_min_preserves_true_negative_infinity():
    codes = np.array([0, 0], dtype=np.int32)
    values = np.array([-np.inf, 1.0])
    (table,), rows = gb.groupby_aggregate(codes, (values,), ("min",), 1)
    assert np.isneginf(np.asarray(table)[0])


def test_nat_does_not_poison_datetime_stats(tmp_path):
    ts = pd.Series(pd.to_datetime(["2016-01-02", None, "2016-01-05"]))
    root = str(tmp_path / "t.bcolz")
    ctable.fromdataframe(pd.DataFrame({"t": ts}), root)
    table = ctable(root, mode="r")
    lo, hi = table.col_stats("t")
    assert lo == pd.Timestamp("2016-01-02").value
    assert hi == pd.Timestamp("2016-01-05").value


# ---------------------------------------------------------------------------
# host kernel (latency-aware routing)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["sum", "mean", "count", "count_na", "min", "max"])
def test_host_partial_tables_matches_device(op):
    """ops.host_partial_tables is the numpy twin of partial_tables: same
    pytree, bit-exact ints, matching floats — the property that makes the
    latency-aware host route interchangeable with the device path."""
    import jax

    rng = np.random.default_rng(41)
    n, g = 30_000, 19
    codes = rng.integers(-1, g, n).astype(np.int32)
    mask = rng.random(n) < 0.85
    if op in ("count_na",):
        vals = (rng.random(n) * 100).astype(np.float64)
        vals[rng.random(n) < 0.04] = np.nan
    else:
        vals = rng.integers(-(2**60), 2**60, n).astype(np.int64)
    host = gb.host_partial_tables(codes, (vals,), (op,), g, mask=mask)
    dev = jax.device_get(gb.partial_tables(codes, (vals,), (op,), g, mask=mask))
    np.testing.assert_array_equal(host["rows"], dev["rows"])
    for key in dev["aggs"][0]:
        np.testing.assert_array_equal(
            np.asarray(host["aggs"][0][key]), np.asarray(dev["aggs"][0][key]),
            err_msg=f"op={op} partial={key}",
        )


def test_host_partial_tables_float_sum_close():
    import jax

    rng = np.random.default_rng(43)
    n, g = 20_000, 7
    codes = rng.integers(0, g, n).astype(np.int32)
    vals = (rng.random(n) * 100 - 50).astype(np.float32)
    host = gb.host_partial_tables(codes, (vals,), ("mean",), g)
    dev = jax.device_get(gb.partial_tables(codes, (vals,), ("mean",), g))
    np.testing.assert_array_equal(
        host["aggs"][0]["count"], dev["aggs"][0]["count"]
    )
    np.testing.assert_allclose(
        host["aggs"][0]["sum"], dev["aggs"][0]["sum"], rtol=1e-5
    )


def test_float_matmul_split_uses_reduce_precision(monkeypatch):
    """The bf16 Dekker split on the MXU path must round via
    lax.reduce_precision, never an f32->bf16->f32 astype round-trip: on
    TPU the XLA excess-precision pass elides the round-trip, zeroing the
    mid/lo limbs (~0.9% relative error on float sums — caught on real
    hardware, TPU_VALIDATE_r5_prefix.json case5/case10).  The elision
    never happens on the CPU test backend, so pin the structural
    property instead: the traced program of a float-measure matmul
    groupby must contain reduce_precision ops."""
    import jax

    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    g = _groupby_module()
    rng = np.random.default_rng(3)
    n, ng = 4_096, 9
    codes = rng.integers(0, ng, n).astype(np.int32)
    vals = rng.standard_normal(n).astype(np.float32)
    jaxpr = jax.make_jaxpr(
        lambda c, v: g._partial_tables_mm(c, (v,), ("sum",), ng)
    )(codes, vals)
    assert "reduce_precision" in str(jaxpr), (
        "float matmul limbs no longer rounded via reduce_precision; "
        "the TPU excess-precision elision bug can return"
    )
    # and the split is still a lossless representation end-to-end
    out = jax.device_get(g.partial_tables(codes, (vals,), ("sum",), ng))
    expected = np.zeros(ng)
    np.add.at(expected, codes, vals.astype(np.float64))
    np.testing.assert_allclose(
        np.asarray(out["aggs"][0]["sum"], dtype=np.float64),
        expected,
        rtol=2e-6,
    )


def test_host_kernel_rows_env_and_cap(monkeypatch):
    from bqueryd_tpu.models import query as q

    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "12345")
    assert q.host_kernel_rows() == 12345
    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    assert q.host_kernel_rows() == 0
    monkeypatch.delenv("BQUERYD_TPU_HOST_KERNEL_ROWS")
    monkeypatch.setattr(q, "_measured_floor", 10.0)  # pathological link
    assert q.host_kernel_rows() == q._HOST_ROUTE_CAP


def test_engine_routes_small_queries_to_host(monkeypatch, tmp_path):
    """Below the threshold execute_local must use the host kernel (no
    device dispatch); above, the device path."""
    import pandas as pd

    from bqueryd_tpu import ops as ops_pkg
    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine

    df = pd.DataFrame(
        {
            "g": np.arange(500, dtype=np.int64) % 5,
            "v": np.arange(500, dtype=np.int64),
        }
    )
    root = str(tmp_path / "t.bcolz")
    ctable.fromdataframe(df, root)
    table = ctable(root)
    query = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)

    calls = {"host": 0}
    real = ops_pkg.host_partial_tables

    def spy(*a, **k):
        calls["host"] += 1
        return real(*a, **k)

    monkeypatch.setattr(ops_pkg, "host_partial_tables", spy)
    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "1000")
    payload_host = QueryEngine().execute_local(table, query)
    assert calls["host"] == 1
    monkeypatch.setenv("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
    payload_dev = QueryEngine().execute_local(table, query)
    assert calls["host"] == 1  # unchanged: device path taken
    from bqueryd_tpu.parallel import hostmerge

    df_h = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload_host]))
    df_d = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload_dev]))
    pd.testing.assert_frame_equal(
        df_h.sort_values("g").reset_index(drop=True),
        df_d.sort_values("g").reset_index(drop=True),
        check_column_type=False,
    )


def test_matmul_route_auto_disables_on_cpu_backend(monkeypatch):
    """Without the force flag, a CPU backend must take the scatter path
    (the bf16 one-hot matmul emulates ~7x slower there)."""
    m = _groupby_module()
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    assert not m._matmul_profitable(
        (np.ones(64, dtype=np.int64),), ("sum",), 64, 8
    )
    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    assert m._matmul_profitable(
        (np.ones(64, dtype=np.int64),), ("sum",), 64, 8
    )


def test_host_int_sum_fast_path_bit_exact():
    """Small-range int64 sums take the single-bincount fast path; values
    straddling the 2^53 partial-sum bound take the 16-bit-limb fallback.
    Both must equal the python-int ground truth (mod-2^64 semantics)."""
    rng = np.random.default_rng(44)
    n, g = 50_000, 13
    codes = rng.integers(0, g, n).astype(np.int32)
    for lo, hi in [(-20_000, 20_000), (-(2**62), 2**62)]:
        vals = rng.integers(lo, hi, n).astype(np.int64)
        out = gb.host_partial_tables(codes, (vals,), ("sum",), g)
        totals = [0] * g  # python ints: no overflow, wrap applied at the end
        for c, v in zip(codes, vals):
            totals[c] += int(v)
        expect = np.array(
            [(t % (1 << 64)) - (1 << 64) if (t % (1 << 64)) >= (1 << 63)
             else t % (1 << 64) for t in totals],
            dtype=np.int64,
        )
        np.testing.assert_array_equal(
            out["aggs"][0]["sum"], expect, err_msg=f"range=({lo},{hi})",
        )


def test_host_partial_tables_all_valid_fast_path():
    """No mask + no negative codes takes the unweighted-bincount fast path;
    results must match the masked general path run on the same data."""
    rng = np.random.default_rng(45)
    n, g = 40_000, 11
    codes = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    fast = gb.host_partial_tables(codes, (vals,), ("mean",), g)
    general = gb.host_partial_tables(
        codes, (vals,), ("mean",), g, mask=np.ones(n, dtype=bool)
    )
    np.testing.assert_array_equal(fast["rows"], general["rows"])
    for key in fast["aggs"][0]:
        np.testing.assert_array_equal(
            fast["aggs"][0][key], general["aggs"][0][key]
        )


def test_count_na_int_measure_zero_on_all_paths(monkeypatch):
    """count_na over an integer measure is structurally zero (ints have no
    NaN); the scatter path, the forced-MXU path (zero_count plan — no
    matmul row spent), and the host kernel must all return zeros while
    float count_na still counts NaNs."""
    import jax

    rng = np.random.default_rng(46)
    n, g = 20_000, 7
    codes = rng.integers(-1, g, n).astype(np.int32)
    ivals = rng.integers(0, 100, n).astype(np.int64)
    fvals = rng.random(n).astype(np.float32)
    fvals[rng.random(n) < 0.1] = np.nan

    def run():
        return jax.device_get(
            gb.partial_tables(
                codes, (ivals, fvals), ("count_na", "count_na"), g
            )
        )

    scatter = run()
    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    mm = run()
    host = gb.host_partial_tables(
        codes, (ivals, fvals), ("count_na", "count_na"), g
    )
    for out, label in [(scatter, "scatter"), (mm, "mm"), (host, "host")]:
        np.testing.assert_array_equal(
            np.asarray(out["aggs"][0]["count"]), np.zeros(g, dtype=np.int64),
            err_msg=f"{label}: int count_na must be zero",
        )
        np.testing.assert_array_equal(
            np.asarray(out["aggs"][1]["count"]),
            np.asarray(scatter["aggs"][1]["count"]),
            err_msg=f"{label}: float count_na disagrees",
        )
    assert int(np.asarray(scatter["aggs"][1]["count"]).sum()) > 0


def test_host_ns_estimate_routes_slow_measures(tmp_path):
    """The routing cost estimate reads column metadata only: small-range
    int sums get the fast rate; min/max, stats-less columns, and int sums
    whose n x max|v| bound crosses 2^53 get the ~4x slow rate (so the
    derived row threshold shrinks instead of host-routing into the limb
    fallback)."""
    import os

    from bqueryd_tpu.models import query as qmod
    from bqueryd_tpu.storage.ctable import ctable as CT

    df = pd.DataFrame(
        {
            "small": np.array([1, -5, 9], dtype=np.int64),
            "huge": np.array([2**40, -(2**40), 7], dtype=np.int64),
            "f": np.array([0.5, 1.5, np.nan]),
            "u": np.array([1, 2, 3], dtype=np.uint64),
        }
    )
    root = str(tmp_path / "est.bcolz")
    CT.fromdataframe(df, root)
    ct = CT(root)

    fast = qmod._HOST_NS_PER_ROW
    slow = qmod._HOST_NS_PER_ROW_SLOW
    est = qmod._host_ns_estimate
    from bqueryd_tpu.storage import native as _native

    assert est(ct, [["small", "sum", "s"]], 1_000_000) == fast
    assert est(ct, [["f", "sum", "s"]], 1_000_000) == fast  # float: 1 bincount
    assert est(ct, [["small", "min", "s"]], 1_000) == slow  # ufunc.at
    # 2^40 bound x 150k rows >= 2^53 AND below the native row floor -> the
    # numpy limb fallback would run: slow rate
    assert est(ct, [["huge", "sum", "s"]], 150_000) == slow
    # same column, few rows -> partial sums stay exact, fast path
    assert est(ct, [["huge", "sum", "s"]], 1_000) == fast
    # above the native floor the C++ kernel sums exactly at any magnitude,
    # so the same huge-bound query rates fast (when the lib is built)
    if _native.groupby_available():
        assert est(ct, [["huge", "sum", "s"]], 1_048_576) == fast
    # extrema rate fast only when the DEDICATED min/max kernel will take
    # them; unsigned dtypes decline it (signed i64 accumulator) and must
    # keep the slow ufunc.at rate even above the native row floor
    if _native.groupby_available() and _native.groupby_minmax_available():
        assert est(ct, [["small", "min", "s"]], 1_048_576) == fast
        assert est(ct, [["u", "min", "s"]], 1_048_576) == slow
    # the slow estimate shrinks the derived threshold proportionally
    # (conftest pins BQUERYD_TPU_HOST_KERNEL_ROWS=0 for determinism, so
    # lift it here to exercise the derived-threshold path)
    qmod._measured_floor = 0.016  # low enough that the 4M cap never binds
    env_prior = os.environ.pop("BQUERYD_TPU_HOST_KERNEL_ROWS", None)
    try:
        assert qmod.host_kernel_rows(slow) * 3 < qmod.host_kernel_rows(fast)
    finally:
        qmod._measured_floor = None
        if env_prior is not None:
            os.environ["BQUERYD_TPU_HOST_KERNEL_ROWS"] = env_prior


def test_native_host_groupby_matches_numpy_paths(monkeypatch):
    """The striped C++ host kernels must agree with the numpy paths exactly:
    bit-equal int sums (any magnitude — the native path has no 2^53 bound),
    equal counts, allclose float sums with identical NaN-skip counts."""
    from bqueryd_tpu.storage import native

    if not native.groupby_available():
        pytest.skip("native groupby kernels not built")
    m = _groupby_module()
    rng = np.random.default_rng(48)
    n, g = 300_000, 37
    codes = rng.integers(-1, g, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    ivals = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    fvals = rng.random(n).astype(np.float64) * 100 - 50
    fvals[rng.random(n) < 0.05] = np.nan

    def run():
        return gb.host_partial_tables(
            codes,
            (ivals, fvals, ivals, fvals, ivals, fvals),
            ("sum", "mean", "count", "count_na", "min", "max"),
            g,
            mask=mask,
        )

    assert n >= m._NATIVE_GROUPBY_MIN_ROWS  # native path engages
    native_out = run()
    monkeypatch.setattr(m, "_NATIVE_GROUPBY_MIN_ROWS", n + 1)
    numpy_out = run()

    np.testing.assert_array_equal(native_out["rows"], numpy_out["rows"])
    for ai, (na, npy) in enumerate(
        zip(native_out["aggs"], numpy_out["aggs"])
    ):
        assert set(na) == set(npy), f"agg {ai} partial keys differ"
        for key in na:
            a, b = np.asarray(na[key]), np.asarray(npy[key])
            if a.dtype.kind in "iu":
                np.testing.assert_array_equal(a, b, err_msg=f"{ai}/{key}")
            else:
                np.testing.assert_allclose(
                    a, b, rtol=1e-12, err_msg=f"{ai}/{key}"
                )


def test_native_host_groupby_no_mask_fast_case(monkeypatch):
    """All-valid rows (mask=None, no negative codes) hit the native kernels
    with a null mask pointer; results still match numpy."""
    from bqueryd_tpu.storage import native

    if not native.groupby_available():
        pytest.skip("native groupby kernels not built")
    m = _groupby_module()
    rng = np.random.default_rng(49)
    n, g = 250_000, 11
    codes = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    native_out = gb.host_partial_tables(codes, (vals,), ("sum",), g)
    monkeypatch.setattr(m, "_NATIVE_GROUPBY_MIN_ROWS", n + 1)
    numpy_out = gb.host_partial_tables(codes, (vals,), ("sum",), g)
    np.testing.assert_array_equal(native_out["rows"], numpy_out["rows"])
    np.testing.assert_array_equal(
        native_out["aggs"][0]["sum"], numpy_out["aggs"][0]["sum"]
    )


def test_native_minmax_unsigned_stays_on_numpy_path():
    """uint64 values above 2^63 would wrap in the signed i64 minmax kernel,
    so unsigned measures must keep the numpy ufunc.at path — results must
    stay correct at native-route row counts."""
    rng = np.random.default_rng(50)
    n, g = 250_000, 7
    codes = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(2**62, 2**64 - 1, n, dtype=np.uint64)
    out = gb.host_partial_tables(
        codes, (vals, vals), ("min", "max"), g
    )
    for gi in range(g):
        sel = codes == gi
        assert int(out["aggs"][0]["min"][gi]) == int(vals[sel].min()), gi
        assert int(out["aggs"][1]["max"][gi]) == int(vals[sel].max()), gi


def test_native_minmax_shares_one_pass(monkeypatch):
    """min and max over the SAME measure must issue one native kernel call."""
    from bqueryd_tpu.storage import native

    if not native.groupby_minmax_available():
        pytest.skip("native minmax kernels not built")
    calls = []
    real = native.groupby_minmax

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(native, "groupby_minmax", spy)
    rng = np.random.default_rng(51)
    n, g = 250_000, 5
    codes = rng.integers(0, g, n).astype(np.int32)
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    out = gb.host_partial_tables(codes, (vals, vals), ("min", "max"), g)
    assert len(calls) == 1, f"expected one shared pass, saw {len(calls)}"
    for gi in range(g):
        sel = codes == gi
        assert int(out["aggs"][0]["min"][gi]) == vals[sel].min()
        assert int(out["aggs"][1]["max"][gi]) == vals[sel].max()


def test_compile_cache_placed_from_outside(tmp_path):
    """The persistent compile cache is placed from OUTSIDE the code:
    ``JAX_COMPILATION_CACHE_DIR`` set -> jax reads it and ops sets no
    directory; unset -> one fixed path inside the checkout, whatever
    ``JAX_PLATFORMS`` holds; ``BQUERYD_TPU_COMPILE_CACHE=0`` -> off.
    Subprocesses: the config is process-wide and latched at ops import."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a sitecustomize that fails the run if CODE sets the directory
    hook = tmp_path / "hook"
    hook.mkdir()
    (hook / "sitecustomize.py").write_text(
        "import os, jax\n"
        "_update = jax.config.update\n"
        "def update(name, value):\n"
        "    if name == 'jax_compilation_cache_dir' and os.environ.get(\n"
        "            'FORBID_CACHE_DIR_UPDATE'):\n"
        "        raise SystemExit('code set jax_compilation_cache_dir')\n"
        "    return _update(name, value)\n"
        "jax.config.update = update\n"
    )

    def probe(extra_env):
        env = dict(os.environ, **extra_env)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(hook), repo, env.get("PYTHONPATH", "")]
        )
        for leak in (
            "JAX_COMPILATION_CACHE_DIR",
            "BQUERYD_TPU_COMPILE_CACHE",
        ):
            if leak not in extra_env:
                env.pop(leak, None)
        out = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax\n"
                "from bqueryd_tpu import ops\n"
                "from bqueryd_tpu.obs import profile\n"
                "print(repr((profile.compile_cache_info()['path'],\n"
                "    jax.config.jax_persistent_cache_min_compile_time_secs)))",
            ],
            capture_output=True,
            text=True,
            env=env,
            timeout=120,
        )
        assert out.returncode == 0, out.stderr[-500:]
        return eval(out.stdout.strip().splitlines()[-1])

    fixed = os.path.join(repo, ".jax_cache")
    for platforms in ("cpu", "tpu,cpu", ""):
        # (nothing here touches a backend, so naming tpu is harmless)
        assert probe({"JAX_PLATFORMS": platforms}) == (fixed, 0)
    outside = str(tmp_path / "cc")
    assert probe({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": outside,
        "FORBID_CACHE_DIR_UPDATE": "1",
    }) == (outside, 0)
    assert probe({
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": outside,
        "BQUERYD_TPU_COMPILE_CACHE": "0",
    })[0] is None


def test_pack_codes_refuses_int64_overflow():
    """A composite key space past 2^63 must raise CompositeOverflow (a
    wrapped radix pack silently merges unrelated groups) — computed in
    python ints so the check itself cannot wrap."""
    from bqueryd_tpu import ops

    small = np.zeros(3, dtype=np.int64)
    with pytest.raises(ops.CompositeOverflow, match="exceeds int64"):
        ops.pack_codes([small] * 4, [3_000_000] * 4)
    # just under the line is fine
    ops.pack_codes([small] * 2, [2**31, 2**31 - 1])


def test_engine_tuple_fallback_on_composite_overflow(tmp_path):
    """Four near-unique key columns overflow the radix space; the engine
    must serve the query exactly via tuple factorization (the reference's
    bquery factorized key tuples and never had this limit)."""
    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.storage.ctable import ctable as CT

    rng = np.random.default_rng(5)
    n = 2_000
    df = pd.DataFrame(
        {f"k{i}": rng.integers(0, 10**9, n).astype(np.int64)
         for i in range(6)}
    )
    # duplicate some rows so real multi-row groups exist
    df = pd.concat([df, df.iloc[: n // 4]], ignore_index=True)
    df["v"] = rng.integers(-1000, 1000, len(df)).astype(np.int64)
    root = str(tmp_path / "of.bcolzs")
    CT.fromdataframe(df, root)
    ct = CT(root, mode="r")
    import math

    cards = [df[f"k{i}"].nunique() for i in range(6)]
    assert math.prod(cards) >= 2**63, "fixture no longer overflows"
    gcols = [f"k{i}" for i in range(6)]
    q = GroupByQuery(gcols, [["v", "sum", "s"]], [], aggregate=True)
    got = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([QueryEngine().execute_local(ct, q)])
    ).sort_values(gcols).reset_index(drop=True)
    exp = (
        df.groupby(gcols, as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
        .sort_values(gcols).reset_index(drop=True)
    )
    assert len(got) == len(exp)
    for c in got.columns:
        np.testing.assert_array_equal(got[c].to_numpy(), exp[c].to_numpy())


def test_worker_degrades_mesh_overflow_to_engine(tmp_path, caplog):
    """The worker's routing: a psum-mergeable query whose key space
    overflows the mesh alignment's radix pack must degrade to the engine
    path and still answer exactly."""
    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.storage.ctable import ctable as CT
    from bqueryd_tpu.utils.tracing import PhaseTimer
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(6)
    n = 2_000
    frames = []
    tables = []
    for s in range(2):
        df = pd.DataFrame(
            {f"k{i}": rng.integers(0, 10**9, n).astype(np.int64)
             for i in range(6)}
        )
        df["v"] = rng.integers(-100, 100, n).astype(np.int64)
        frames.append(df)
        root = str(tmp_path / f"of{s}.bcolzs")
        CT.fromdataframe(df, root)
        tables.append(CT(root, mode="r"))

    worker = WorkerNode.__new__(WorkerNode)  # routing only: no sockets
    worker._engine = None
    worker._mesh_executor = None
    worker._result_cache = None
    worker.memory_limit_mb = 2048   # what bounds the executor's align segment
    from bqueryd_tpu.obs.metrics import Counter

    # bare tables: the executor reads their identities itself, and says so
    worker._identity_passes = {"recomputed": Counter("recomputed", "")}
    import logging as _logging

    worker.logger = _logging.getLogger("test-overflow")
    gcols = [f"k{i}" for i in range(6)]
    q = GroupByQuery(gcols, [["v", "sum", "s"]], [], aggregate=True)
    import logging as _logging2

    with caplog.at_level(_logging2.INFO, logger="test-overflow"):
        payload = worker._execute(tables, q, PhaseTimer())
    # the MESH path must have been attempted and degraded — not routed
    # around: otherwise this test silently stops covering the fallback
    assert any("composite key space" in r.message for r in caplog.records)
    got = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    ).sort_values(gcols).reset_index(drop=True)
    all_df = pd.concat(frames, ignore_index=True)
    exp = (
        all_df.groupby(gcols, as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
        .sort_values(gcols).reset_index(drop=True)
    )
    assert len(got) == len(exp)
    for c in got.columns:
        np.testing.assert_array_equal(got[c].to_numpy(), exp[c].to_numpy())


def test_worker_degrades_mesh_runtime_error_to_engine(tmp_path, caplog):
    """A JaxRuntimeError out of the mesh executor must degrade to the
    per-shard engine path and still answer exactly, not fail the query —
    and the firing is counted (devicehealth.degrade_counts), because the
    same catch also answers a program the compiler rejected."""
    import jax

    from bqueryd_tpu.utils import devicehealth

    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.storage.ctable import ctable as CT
    from bqueryd_tpu.utils.tracing import PhaseTimer
    from bqueryd_tpu.worker import WorkerNode

    rng = np.random.default_rng(8)
    n = 50_000  # large enough that routing picks the mesh path
    frames = []
    tables = []
    for s in range(2):
        df = pd.DataFrame(
            {
                "k": rng.integers(0, 9, n).astype(np.int64),
                "v": rng.integers(-100, 100, n).astype(np.int64),
            }
        )
        frames.append(df)
        root = str(tmp_path / f"rt{s}.bcolzs")
        CT.fromdataframe(df, root)
        tables.append(CT(root, mode="r"))

    worker = WorkerNode.__new__(WorkerNode)  # routing only: no sockets
    worker._engine = None
    worker._result_cache = None

    class _FailingMesh:
        timer = None

        def execute(self, tables, query, strategy=None, identities=None):
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic failed to compile TPU kernel"
            )

    worker._mesh_executor = _FailingMesh()
    import logging as _logging

    worker.logger = _logging.getLogger("test-mesh-rt")
    q = GroupByQuery(["k"], [["v", "sum", "s"]], [], aggregate=True)
    fired_before = devicehealth.degrade_counts()["mesh_to_engine"]
    with caplog.at_level(_logging.WARNING, logger="test-mesh-rt"):
        payload = worker._execute(tables, q, PhaseTimer())
    assert (
        devicehealth.degrade_counts()["mesh_to_engine"] == fired_before + 1
    )
    # the mesh path must have been attempted and degraded — not routed
    # around: otherwise this test silently stops covering the fallback
    assert any("mesh executor failed" in r.message for r in caplog.records)
    got = hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([payload])
    ).sort_values("k").reset_index(drop=True)
    all_df = pd.concat(frames, ignore_index=True)
    exp = (
        all_df.groupby("k", as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
        .sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["k"].to_numpy(), exp["k"].to_numpy())
    np.testing.assert_array_equal(got["s"].to_numpy(), exp["s"].to_numpy())


def test_hicard_pallas_path_bit_exact(monkeypatch):
    """The group-tiled Pallas MXU path (BQUERYD_TPU_PALLAS=1 past
    matmul_groups_limit) must agree bit-for-bit with numpy: int64 sums
    with negatives, unsigned means, null codes, and ragged padding in
    both the row-block and group-tile dimensions (40k rows -> 2 blocks;
    9k groups -> 5 group tiles of 2048)."""
    import jax

    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    g = _groupby_module()
    rng = np.random.default_rng(1)
    n, ng = 40_000, 9_000
    codes = rng.integers(-1, ng, n).astype(np.int64)
    v64 = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    vu8 = rng.integers(0, 250, n).astype(np.uint8)
    assert g._hicard_matmul_profitable((v64, vu8), ("sum", "mean"), n, ng)
    out = jax.device_get(
        g.partial_tables(
            np.asarray(codes), (v64, vu8), ("sum", "mean"), n_groups=ng
        )
    )
    valid = codes >= 0
    truth_s = np.zeros(ng, dtype=np.int64)
    np.add.at(truth_s, codes[valid], v64[valid])
    got_s = np.asarray(out["aggs"][0]["sum"])
    assert got_s.dtype == np.int64
    np.testing.assert_array_equal(got_s, truth_s)
    truth_u = np.zeros(ng, dtype=np.uint64)
    np.add.at(truth_u, codes[valid], vu8[valid].astype(np.uint64))
    cnt = np.bincount(codes[valid], minlength=ng)
    np.testing.assert_array_equal(
        np.asarray(out["aggs"][1]["sum"]).astype(np.uint64), truth_u
    )
    np.testing.assert_array_equal(np.asarray(out["aggs"][1]["count"]), cnt)
    np.testing.assert_array_equal(np.asarray(out["rows"]), cnt)


def test_hicard_gate_declines_incompatible_queries(monkeypatch):
    """Floats (no wrap-free limb encoding), min/max (scatter anyway),
    out-of-range cardinalities, and the default flag state must all stay
    off the high-cardinality Pallas path."""
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    g = _groupby_module()
    n, ng = 40_000, 9_000
    i64 = np.ones(n, dtype=np.int64)
    f32 = np.ones(n, dtype=np.float32)
    assert g._hicard_matmul_profitable((i64,), ("sum",), n, ng)
    assert not g._hicard_matmul_profitable((f32,), ("sum",), n, ng)
    assert not g._hicard_matmul_profitable((i64,), ("min",), n, ng)
    # inside matmul_groups_limit the classic path owns it
    assert not g._hicard_matmul_profitable((i64,), ("sum",), n, 100)
    # past the hicard ceiling the sort/scatter path owns it
    from bqueryd_tpu.ops import pallas_groupby as pg

    over = pg.hicard_groups_limit() + 1
    assert not g._hicard_matmul_profitable((i64,), ("sum",), n, over)
    # default flag state: off
    monkeypatch.delenv("BQUERYD_TPU_PALLAS")
    assert not g._hicard_matmul_profitable((i64,), ("sum",), n, ng)


def test_hicard_kernel_rejects_wrap_risk():
    """Past HICARD_MAX_ROWS a limb total could wrap uint32 twice; the
    kernel must refuse (and the dispatcher gate declines the same bound)."""
    import jax.numpy as jnp

    from bqueryd_tpu.ops import pallas_groupby as pg

    g = _groupby_module()
    fake_n = pg.HICARD_MAX_ROWS + 1
    assert not g._hicard_matmul_profitable(
        (np.ones(8, dtype=np.int64),), ("sum",), fake_n, 9_000
    )
    with pytest.raises(ValueError, match="HICARD_MAX_ROWS"):
        pg.onehot_rows_dot_hicard(
            jnp.zeros(fake_n, jnp.int32),
            jnp.zeros((1, fake_n), jnp.bfloat16),
            n_rows=1,
            n_groups=9_000,
            interpret=True,
        )


def test_hicard_kernel_rejects_row_tile_off_the_codes_grid(monkeypatch):
    """Mosaic refused the hicard kernel on the v5e at its old 512-row tile
    (a 1-D int32 load must start on a 1024-element tile); the interpreter
    accepts any tile, so the knob check is what keeps CPU runs honest."""
    import jax.numpy as jnp

    from bqueryd_tpu.ops import pallas_groupby as pg

    monkeypatch.setenv("BQUERYD_TPU_PALLAS_HICARD_KT", "512")
    with pytest.raises(ValueError, match="multiple of 1024"):
        pg.onehot_rows_dot_hicard(
            jnp.zeros(4096, jnp.int32),
            jnp.zeros((1, 4096), jnp.bfloat16),
            n_rows=1,
            n_groups=9_001,  # a shape no other test traces
            interpret=True,
        )


def test_count_distinct_refuses_composite_overflow():
    """The sort keys are int32: groups past them are refused, never
    wrapped (the engine then ships value sets); values are never packed
    with the groups, so no bound applies to their product."""
    from bqueryd_tpu import ops

    with pytest.raises(ops.CompositeOverflow, match="int32 sort key"):
        ops.groupby_count_distinct(
            np.zeros(4, dtype=np.int32),
            np.zeros(4, dtype=np.int32),
            2**32,
        )


def test_host_sorted_count_distinct_matches_device():
    """The numpy run-leader twin must agree with the device kernel on
    adversarial layouts: masked rows bridging runs, null group codes,
    NaN values (NaN != NaN starts a new run), and empty input."""
    from bqueryd_tpu import ops

    rng = np.random.default_rng(17)
    n, g = 5_000, 37
    codes = rng.integers(-1, g, n).astype(np.int32)
    # sorted-ish values with repeats so real runs exist
    values = np.sort(rng.integers(0, 50, n)).astype(np.float64)
    values[rng.random(n) < 0.02] = np.nan
    mask = rng.random(n) < 0.8
    for m in (None, mask):
        dev = np.asarray(
            ops.groupby_sorted_count_distinct(codes, values, g, m)
        )
        host = ops.host_sorted_count_distinct(codes, values, g, m)
        np.testing.assert_array_equal(host, dev)
    # empty input
    np.testing.assert_array_equal(
        ops.host_sorted_count_distinct(
            np.empty(0, np.int32), np.empty(0), 5
        ),
        np.zeros(5, np.int64),
    )


def test_expand_mask_host_twin_out_of_range_parity(monkeypatch):
    """ADVICE r5 low #2: the wedged numpy twin of expand_mask_by_group must
    mirror the device twin's edge semantics for codes >= n_groups — the jit
    scatter silently DROPS out-of-range ids and the jit gather CLAMPS, where
    an unguarded fancy index raised IndexError instead."""
    from bqueryd_tpu.ops.groupby import _expand_mask_jit
    from bqueryd_tpu.utils import devicehealth

    n_groups = 4
    codes = np.array([0, 1, 7, 3, -1, 9, 3], dtype=np.int64)  # 7, 9 OOB
    mask = np.array([True, False, True, True, False, True, False])

    device = np.asarray(_expand_mask_jit(codes, mask, n_groups))
    monkeypatch.setattr(devicehealth, "backend_wedged", lambda **kw: True)
    host = np.asarray(gb.expand_mask_by_group(codes, mask, n_groups=n_groups))
    np.testing.assert_array_equal(host, device)
    # and the baseline in-range case still matches pandas-style semantics:
    # any selected row selects its whole group, null groups never selected
    codes2 = np.array([0, 0, 1, 2, -1, 2], dtype=np.int64)
    mask2 = np.array([True, False, False, False, True, True])
    host2 = np.asarray(
        gb.expand_mask_by_group(codes2, mask2, n_groups=3)
    )
    np.testing.assert_array_equal(
        host2, [True, True, False, True, False, True]
    )


def test_term_mask_wedged_rejects_device_arrays(monkeypatch):
    """ADVICE r5 low #1: the wedged branch must fail fast on a jax Array
    instead of np.asarray-ing it (a blocking device transfer — the exact
    hang the branch exists to avoid)."""
    import jax.numpy as jnp

    from bqueryd_tpu.utils import devicehealth

    monkeypatch.setattr(devicehealth, "backend_wedged", lambda **kw: True)
    host = pred.term_mask(np.array([1, 2, 3]), "==", 2)
    np.testing.assert_array_equal(np.asarray(host), [False, True, False])
    with pytest.raises(TypeError, match="wedged"):
        pred.term_mask(jnp.array([1, 2, 3]), "==", 2)


# ---------------------------------------------------------------------------
# float64 sums on an accelerator: dense at few groups, sorted above (PR 31)
# ---------------------------------------------------------------------------

def _float_case(name, n_groups=10):
    """(codes, float64 values) for one named shape of input."""
    m = _groupby_module()
    rng = np.random.default_rng(31)
    n = 3 * m._SUM_BLOCK
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    values = np.round(rng.gamma(1.2, 2.0, n), 2)
    if name == "nan_measures":
        values[rng.random(n) < 0.05] = np.nan
    elif name == "null_codes":
        codes[rng.random(n) < 0.1] = -1
    elif name == "empty_group":
        codes[codes == 3] = 4
    elif name == "one_group":
        codes[:] = n_groups - 1
    elif name == "ragged_rows":  # not a multiple of the block
        n = 2 * m._SUM_BLOCK + 12_345
        codes, values = codes[:n], values[:n]
    elif name == "mixed_magnitudes":
        # both signs, 1e-6 to 1e9: group 0 holds the largest values, the
        # last group the smallest, three decades a group
        low = 6.0 - 12.0 * codes / max(n_groups - 1, 1)
        values = rng.choice([-1.0, 1.0], n) * 10.0 ** rng.uniform(low, low + 3)
    else:
        assert name == "plain", name
    return codes, values


def _pandas_mean(codes, values, n_groups, mask=None):
    frame = pd.DataFrame({"g": codes, "v": values})
    if mask is not None:
        frame = frame[mask]
    frame = frame[frame["g"] >= 0]
    return frame.groupby("g")["v"].mean().reindex(range(n_groups))


def _group_count(n_groups):
    """A parametrised group count: "C" is the module's boundary, "C+1" the
    first count past it (read when the test runs, not when it is collected)."""
    constant = _groupby_module()._DENSE_SUM_GROUPS
    return {"C": constant, "C+1": constant + 1}.get(n_groups, n_groups)


@pytest.mark.parametrize("n_groups", [1, 9, 10, "C", "C+1"])
def test_dense_sum_matches_add_at_and_the_sorted_sum(n_groups):
    """The helper itself, at every group count round its boundary: the
    float64 ``np.add.at`` and ``_sorted_segment_sum`` on the same input."""
    import jax
    import jax.numpy as jnp

    m = _groupby_module()
    n_groups = _group_count(n_groups)
    codes, values = _float_case("plain", n_groups)
    if n_groups > 100:   # the boundary: fewer rows, still more than a block
        codes, values = codes[:m._SUM_BLOCK + 4321], values[:m._SUM_BLOCK + 4321]
    expect = np.zeros(n_groups)
    np.add.at(expect, codes, values)
    # jitted, as every caller has it: the [groups, rows] select is fused
    # into the reduction and never materialised
    dense = jax.jit(m._dense_segment_sum, static_argnums=2)(
        jnp.asarray(values), jnp.asarray(codes), n_groups)
    assert dense.dtype == jnp.float64 and dense.shape == (n_groups,)
    np.testing.assert_allclose(np.asarray(dense), expect, rtol=1e-12)
    by_sort = m._sorted_segment_sum(
        jnp.asarray(values), jnp.asarray(codes), n_groups,
        acc_dtype=jnp.float64,
    )
    np.testing.assert_allclose(
        np.asarray(dense), np.asarray(by_sort), rtol=1e-9
    )


@pytest.mark.parametrize("strategy", [None, "scatter"])
@pytest.mark.parametrize(
    "case",
    ["plain", "nan_measures", "null_codes", "empty_group", "one_group",
     "ragged_rows"],
)
def test_dense_mean_through_partial_tables_matches_pandas(
        groupby_as_accelerator, case, strategy):
    """``mean`` = sum / count of the accelerator's trace equals pandas to
    1e-12 on either route: NaN measures and null keys skipped, an empty
    group NaN, a row count off the block grid, a filter on top."""
    import jax

    m = groupby_as_accelerator
    codes, values = _float_case(case)
    n_groups = 10
    mask = np.random.default_rng(7).random(len(codes)) < 0.8
    assert m.float_sum_route(
        strategy, (values,), ("mean",), len(codes), n_groups
    ) == "dense"
    out = jax.device_get(m.partial_tables(
        codes, (values,), ("mean",), n_groups, mask=mask, strategy=strategy,
    ))
    agg = out["aggs"][0]
    with np.errstate(invalid="ignore", divide="ignore"):
        mean = np.asarray(agg["sum"]) / np.asarray(agg["count"])
    expect = _pandas_mean(codes, values, n_groups, mask)
    np.testing.assert_allclose(mean, expect.to_numpy(), rtol=1e-12)
    kept = mask & (codes >= 0)
    np.testing.assert_array_equal(
        np.asarray(out["rows"]),
        np.bincount(codes[kept], minlength=n_groups),
    )
    np.testing.assert_array_equal(
        np.asarray(agg["count"]),
        np.bincount(codes[kept & ~np.isnan(values)], minlength=n_groups),
    )


@pytest.mark.parametrize("strategy", [None, "scatter", "sort"])
def test_float64_mean_matches_pandas_on_the_cpu_backend(strategy):
    """Unpatched: this backend's own forms (a segment sum; the sort under
    its binding hint) against pandas to 1e-12, NaNs and null keys in."""
    import jax

    codes, values = _float_case("nan_measures")
    codes[::17] = -1
    out = jax.device_get(gb.partial_tables(
        codes, (values,), ("mean",), 10, strategy=strategy,
    ))
    agg = out["aggs"][0]
    mean = np.asarray(agg["sum"]) / np.asarray(agg["count"])
    np.testing.assert_allclose(
        mean, _pandas_mean(codes, values, 10).to_numpy(), rtol=1e-12
    )


def test_dense_sum_is_no_less_accurate_than_the_sorted_sum():
    """Values of both signs over fifteen decades, the small ones in the
    later groups: against the exactly rounded sum (``math.fsum``, scaled
    by the group's sum of magnitudes) the dense form — a group's sum only
    meets its own values — errs no more than the prefix difference, which
    carries the earlier groups' totals through the later ones."""
    import math

    import jax
    import jax.numpy as jnp

    m = _groupby_module()
    n_groups = 10
    codes, values = _float_case("mixed_magnitudes")
    exact = np.array(
        [math.fsum(values[codes == g]) for g in range(n_groups)]
    )
    scale = np.array(
        [math.fsum(np.abs(values[codes == g])) for g in range(n_groups)]
    )
    dense = np.asarray(jax.jit(m._dense_segment_sum, static_argnums=2)(
        jnp.asarray(values), jnp.asarray(codes), n_groups))
    by_sort = np.asarray(m._sorted_segment_sum(
        jnp.asarray(values), jnp.asarray(codes), n_groups,
        acc_dtype=jnp.float64))
    dense_err = np.max(np.abs(dense - exact) / scale)
    sort_err = np.max(np.abs(by_sort - exact) / scale)
    assert dense_err <= sort_err, (dense_err, sort_err)
    assert dense_err < 1e-13


@pytest.mark.parametrize("strategy", [None, "scatter"])
def test_integer_mean_takes_the_dense_sum(groupby_as_accelerator, strategy):
    """An integer mean accumulates in float64 like pandas, so it is the
    same plan and the same helper as a float64 one."""
    import unittest.mock as mock

    import jax

    m = groupby_as_accelerator
    rng = np.random.default_rng(32)
    n, n_groups = m._SUM_BLOCK + 777, 9
    codes = rng.integers(-1, n_groups, n).astype(np.int32)
    values = rng.integers(-(2**40), 2**40, n).astype(np.int64)
    assert m.float_sum_route(
        strategy, (values,), ("mean",), n, n_groups) == "dense"
    with mock.patch.object(
        m, "_dense_segment_sum", wraps=m._dense_segment_sum
    ) as dense:
        out = jax.device_get(m.partial_tables(
            codes, (values,), ("mean",), n_groups, strategy=strategy))
    assert [call.args[2] for call in dense.call_args_list] == [n_groups]
    agg = out["aggs"][0]
    mean = np.asarray(agg["sum"]) / np.asarray(agg["count"])
    np.testing.assert_allclose(
        mean, _pandas_mean(codes, values, n_groups).to_numpy(), rtol=1e-12
    )


@pytest.mark.parametrize(
    "n_groups, strategy, form",
    [
        pytest.param(10, None, "dense", id="few_groups"),
        pytest.param("C", None, "dense", id="at_the_constant"),
        pytest.param("C+1", None, "segmented", id="one_past_the_constant"),
        pytest.param("C+1", "scatter", "segmented", id="one_past_by_scatter"),
        pytest.param(10, "sort", "segmented", id="binding_sort_hint"),
    ],
)
def test_the_group_count_decides_the_form_of_the_float64_sum(
        groupby_as_accelerator, n_groups, strategy, form):
    """Dense up to ``_DENSE_SUM_GROUPS`` groups, the sorted rows' segmented
    scan above and under the binding sort hint (PR 38; never the prefix
    difference of the whole table, whatever the hint): what the kernels
    trace is what ``float_sum_route`` says from the host side, and both are
    right."""
    import unittest.mock as mock

    import jax

    m = groupby_as_accelerator
    n_groups = _group_count(n_groups)
    rng = np.random.default_rng(33)
    n = m._SUM_BLOCK + 99
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    values = rng.random(n) * 40
    assert m.float_sum_route(
        strategy, (values,), ("sum",), n, n_groups) == form
    with mock.patch.object(
        m, "_dense_segment_sum", wraps=m._dense_segment_sum
    ) as dense, mock.patch.object(
        m, "_segmented_sums", wraps=m._segmented_sums
    ) as segmented, mock.patch.object(
        m, "_sorted_segment_sum", wraps=m._sorted_segment_sum
    ) as prefix_diff:
        out = jax.device_get(m.partial_tables(
            codes, (values,), ("sum",), n_groups, strategy=strategy))
    assert (dense.called, segmented.called) == (
        form == "dense", form == "segmented")
    assert not prefix_diff.called
    expect = np.zeros(n_groups)
    np.add.at(expect, codes, values)
    np.testing.assert_allclose(
        np.asarray(out["aggs"][0]["sum"]), expect, rtol=1e-12)


# -- the route of a float64 mean ---------------------------------------------

def _mesh_cache_key(strategy, n_groups=10, width=4096):
    from bqueryd_tpu.parallel.executor import _effective_mesh_strategy

    return _effective_mesh_strategy(
        strategy, ("mean",), n_groups, (np.zeros(8, np.float64),), width
    )


@pytest.mark.parametrize("hint", [None, "auto", "matmul"])
def test_a_float64_mean_goes_by_matmul_under_every_matmul_hint(
        monkeypatch, hint):
    """Where ``matmul_route_allowed`` holds, ``rows`` and the mean's count
    are two rows of the stacked dot: auto and the advisory "matmul" are ONE
    route and ONE traced program (one mesh cache key)."""
    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    m = _groupby_module()
    floats = (np.zeros(8, np.float64),)
    assert m._matmul_profitable(floats, ("mean",), 4096, 10)
    assert m._matmul_profitable(floats, ("sum",), 4096, 10)
    assert gb.kernel_route(hint, floats, ("mean",), 4096, 10) == "matmul"
    assert _mesh_cache_key(hint) is None


@pytest.mark.parametrize("hint", ["scatter", "sort"])
def test_scatter_and_sort_stay_binding_for_a_float64_mean(monkeypatch, hint):
    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    floats = (np.zeros(8, np.float64),)
    assert gb.kernel_route(hint, floats, ("mean",), 4096, 10) == hint
    assert _mesh_cache_key(hint) == hint


@pytest.mark.parametrize("hint", [None, "matmul"])
def test_a_float64_mean_still_scatters_on_the_cpu_backend(monkeypatch, hint):
    """The backend guard stands: without the force flag nothing changes
    route here, and the sum is this backend's own segment sum (no tag)."""
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    floats = (np.zeros(8, np.float64),)
    assert gb.kernel_route(hint, floats, ("mean",), 4096, 10) == "scatter"
    assert _mesh_cache_key(hint) is None
    assert gb.float_sum_route(hint, floats, ("mean",), 4096, 10) is None


def test_a_query_of_extrema_alone_is_still_not_matmul_profitable(monkeypatch):
    """What the profitability rule still declines: min/max scatter on
    either route, and alone they do not pay for the one-hot."""
    monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    m = _groupby_module()
    floats = (np.zeros(8, np.float64),)
    assert not m._matmul_profitable(floats, ("min",), 4096, 10)
    assert not m._matmul_profitable(floats * 2, ("min", "max"), 4096, 10)
    assert m._matmul_profitable(floats * 2, ("min", "mean"), 4096, 10)
    assert m._matmul_profitable((), (), 4096, 10)


@pytest.mark.parametrize(
    "dtype, op, route, expect",
    [
        (np.float64, "mean", "matmul", "dense"),
        (np.float64, "sum", "scatter", "dense"),
        (np.float32, "sum", "matmul", None),      # bf16 limbs on the MXU
        (np.float32, "sum", "scatter", "dense"),  # accumulates in float64
        (np.int64, "mean", "matmul", "dense"),
        (np.int64, "sum", "matmul", None),
        (np.float64, "min", "sort", None),  # auto: its counts by the sort
        (np.float64, "count", "matmul", None),
    ],
)
def test_float_sum_route_names_the_float64_sums_only(
        groupby_as_accelerator, dtype, op, route, expect):
    strategy = "scatter" if route == "scatter" else None
    measures = (np.zeros(8, dtype),)
    assert gb.kernel_route(strategy, measures, (op,), 4096, 10) == route
    assert gb.float_sum_route(strategy, measures, (op,), 4096, 10) == expect


# -- the ONE route rule --------------------------------------------------------

def test_kernel_route_predictions(monkeypatch):
    ints = [np.zeros(8, np.int64)]
    assert gb.kernel_route("scatter", ints, ("sum",), 10_000, 9) == "scatter"
    assert gb.kernel_route("sort", ints, ("sum",), 10_000, 9) == "sort"
    assert gb.kernel_route(None, ints, ("sum",), 10_000, 9) == "matmul"
    assert gb.kernel_route(None, ints, ("min",), 10_000, 9) == "scatter"
    # past the blocks x groups budget the adaptive scatter sorts
    assert gb.kernel_route(
        None, ints, ("sum",), 10_000_000, 1_000_000
    ) == "sort"
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    assert gb.kernel_route(
        "matmul", ints, ("sum",), 10_000, 9
    ) == "scatter"  # backend guard: the advisory route cannot force it


@pytest.mark.parametrize(
    "n, n_groups, ops_",
    [(5000, 8193, ("sum",)), (11_010_048, 73_728, ("sum",)),
     (5000, 10, ("min", "max")), (1_048_576, 262_144, ("sum", "count"))],
    ids=["groups=8193", "highcard", "min-max-only", "groups=262144"],
)
def test_kernel_route_says_sort_wherever_an_accelerator_scatters(
        groupby_as_accelerator, monkeypatch, n, n_groups, ops_):
    """Whatever reaches the scatter entry on an accelerator counts and sums
    its integers by the one sort (``rows`` at the least: the extrema of a
    min/max-only query keep their own scatters), so ``auto`` reads ``sort``
    there; the binding hints read as themselves on any backend."""
    monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
    m = groupby_as_accelerator
    stubs = (np.zeros(1, np.int32),) * len(ops_)
    for spelling in (None, "auto", "matmul"):
        assert m.kernel_route(spelling, stubs, ops_, n, n_groups) == "sort"
    assert m.kernel_route("scatter", stubs, ops_, n, n_groups) == "scatter"
    assert m.kernel_route("sort", stubs, ops_, n, n_groups) == "sort"


#: a case of the route table: the backend ``ops.groupby`` reads ("cpu" is
#: this backend without the force flag, "cpu+force" with it, "tpu" the
#: accelerator reading of ``groupby_as_accelerator``), rows, groups, the
#: measures' (dtype, op) pairs, the route ``auto`` takes, and whether the
#: shape is small enough to run
_SUM = ((np.int64, "sum"),)
_BLOCK = 65536  # ops.groupby._SUM_BLOCK
_ROUTE_TABLE = [
    pytest.param("cpu+force", 5000, 1, _SUM, "matmul", True, id="groups=1"),
    pytest.param("cpu+force", 5000, 8192, _SUM, "matmul", True,
                 id="groups=8192:the-ceiling"),
    pytest.param("cpu+force", 5000, 8193, _SUM, "scatter", True,
                 id="groups=8193:past-the-ceiling"),
    pytest.param("cpu+force", (1 << 36) // 8192, 8192, _SUM, "matmul", False,
                 id="cells=2^36:the-cap"),
    pytest.param("cpu+force", (1 << 36) // 8192 + 1, 8192, _SUM, "scatter",
                 False, id="cells=2^36+8192:past-the-cap"),
    pytest.param("cpu+force", 5000, 10,
                 ((np.float64, "min"), (np.int64, "max")), "scatter", True,
                 id="min-max-only"),
    pytest.param("cpu+force", 5000, 10,
                 ((np.float64, "min"), (np.int64, "count")), "matmul", True,
                 id="min-beside-a-count"),
    pytest.param("cpu+force", 5000, 10, (), "matmul", True, id="rows-only"),
    pytest.param("cpu+force", 5000, 10, ((np.float64, "mean"),), "matmul",
                 True, id="float64-mean"),
    pytest.param("cpu+force", 5000, 8193, ((np.float64, "mean"),), "scatter",
                 True, id="float64-mean:past-the-ceiling"),
    pytest.param("cpu+force", 5000, 37, _SUM, "matmul", True,
                 id="int64-sum:cpu-with-FORCE_MATMUL"),
    pytest.param("cpu", 5000, 37, _SUM, "scatter", True,
                 id="int64-sum:cpu-without-FORCE_MATMUL"),
    pytest.param("tpu", 5000, 37, _SUM, "matmul", True,
                 id="int64-sum:accelerator"),
    pytest.param("tpu", 5000, 8193, _SUM, "sort", True,
                 id="groups=8193:accelerator"),
    pytest.param("tpu", 150_000, 73_728, ((np.int32, "sum"),), "sort", True,
                 id="groups=73728:accelerator"),
    pytest.param("tpu", 11_010_048, 73_728, ((np.int32, "sum"),), "sort",
                 False, id="highcard:accelerator"),
    pytest.param("cpu", 11_010_048, 73_728, ((np.int32, "sum"),), "scatter",
                 False, id="highcard:cpu"),
    pytest.param("tpu", 5000, 10,
                 ((np.float64, "min"), (np.int64, "max")), "sort", True,
                 id="min-max-only:accelerator"),
    pytest.param("tpu", 5000, 8193,
                 ((np.int64, "sum"), (np.float64, "min"), (np.int16, "count")),
                 "sort", True, id="sum-min-count:accelerator"),
    pytest.param("cpu", 1024 * _BLOCK, (1 << 25) // 1024, _SUM, "scatter",
                 False, id="blocks*groups=2^25:the-budget"),
    pytest.param("cpu", 1024 * _BLOCK, (1 << 25) // 1024 + 1, _SUM, "sort",
                 False, id="blocks*groups=2^25+1024:past-the-budget"),
    pytest.param("tpu", 1024 * _BLOCK + 1, (1 << 25) // 1024, _SUM, "sort",
                 False, id="one-row-more-is-one-block-more:accelerator"),
]


@pytest.mark.parametrize("backend, n, n_groups, aggs, route, runs",
                         _ROUTE_TABLE)
def test_route_table(request, monkeypatch, backend, n, n_groups, aggs, route,
                     runs):
    """The ONE rule that routes a served query, a case each side of every
    boundary: ``kernel_route`` under ``auto`` names the route, the
    dispatcher enters that route's kernel, the traced program has the named
    form (a sort and no integer scatter, or the reverse), and the answer is
    the named route's own, bit for bit."""
    import unittest.mock as mock

    import jax

    if backend == "tpu":
        monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
        m = request.getfixturevalue("groupby_as_accelerator")
    else:
        m = _groupby_module()
        if backend == "cpu":
            monkeypatch.delenv("BQUERYD_TPU_FORCE_MATMUL", raising=False)
        else:
            monkeypatch.setenv("BQUERYD_TPU_FORCE_MATMUL", "1")
    assert m._SUM_BLOCK == _BLOCK and m._MAX_BLOCK_SEGMENTS == 1 << 25
    ops_ = tuple(op for _dt, op in aggs)
    stubs = tuple(np.zeros(1, dt) for dt, _op in aggs)
    for spelling in (None, "auto"):
        assert m.kernel_route(spelling, stubs, ops_, n, n_groups) == route
    if not runs:
        return
    rng = np.random.default_rng(32)
    codes = rng.integers(0, n_groups, n).astype(np.int32)
    codes[::97] = -1  # null keys drop on every route
    measures = tuple(
        (rng.random(n) * 50).astype(dt) if np.issubdtype(dt, np.floating)
        else rng.integers(-(10**12), 10**12, n).astype(dt)
        for dt, _op in aggs
    )
    mask = rng.random(n) > 0.25
    with mock.patch.object(
        m, "_partial_tables_mm", wraps=m._partial_tables_mm
    ) as mm, mock.patch.object(
        m, "_partial_tables_scatter", wraps=m._partial_tables_scatter
    ) as scatter:
        auto = jax.device_get(
            m.partial_tables(codes, measures, ops_, n_groups, mask))
    # ("sort" is a form of the scatter entry, not an entry of its own)
    assert (mm.called, scatter.called) == (
        route == "matmul", route != "matmul")
    if route != "matmul":
        text = jax.jit(
            lambda c, ms, k: m.partial_tables(c, ms, ops_, n_groups, k)
        ).lower(codes, measures, mask).as_text()
        extrema = sum(op in ("min", "max") for op in ops_)
        assert text.count('"stablehlo.sort"(') == (route == "sort")
        if route == "sort":  # only the extrema still scatter
            assert text.count('"stablehlo.scatter"(') == extrema
        else:
            assert text.count('"stablehlo.scatter"(') > extrema
    if route == "matmul":
        own = m._partial_tables_mm(
            codes, measures, ops_, n_groups, mask, use_pallas=False,
            null_sentinels=(None,) * len(measures))
    else:
        own = m.partial_tables(
            codes, measures, ops_, n_groups, mask, strategy=route)
    own = jax.device_get(own)
    assert jax.tree_util.tree_structure(auto) == \
        jax.tree_util.tree_structure(own)
    for got, want in zip(jax.tree_util.tree_leaves(auto),
                         jax.tree_util.tree_leaves(own)):
        np.testing.assert_array_equal(got, want)
    present = codes >= 0
    np.testing.assert_array_equal(
        auto["rows"],
        np.bincount(codes[present & mask], minlength=n_groups),
    )
