"""Mesh executor: multi-shard queries merged on a virtual 8-device CPU mesh.

Covers SURVEY.md §7.2 step 5 (shard fan-out + mesh merge): equivalence of the
psum-merged result against both pandas ground truth and the per-shard
QueryEngine + host-merge path, for single/multi key, filters, string keys,
and shard counts above/below the device count.
"""

import os

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import GroupByQuery, QueryEngine, ResultPayload
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh
from bqueryd_tpu.storage import ctable

N_SHARDS = 5


def taxi_like_df(n=12_000, seed=7):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "VendorID": rng.integers(1, 3, n).astype(np.int64),
            "passenger_count": rng.integers(0, 7, n).astype(np.int64),
            "payment_type": rng.integers(1, 5, n).astype(np.int64),
            "trip_distance": rng.exponential(3.0, n),
            "fare_amount": rng.gamma(2.0, 7.0, n),
            "flag": rng.choice(["Y", "N", "M"], n),
            "PULocationID": rng.integers(1, 266, n).astype(np.int64),
            "DOLocationID": rng.integers(1, 266, n).astype(np.int64),
        }
    )


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """Unevenly sized shards (so bucket packing + padding is exercised)."""
    df = taxi_like_df()
    base = tmp_path_factory.mktemp("mesh")
    cuts = np.array([0, 1_000, 4_200, 6_000, 9_500, len(df)])
    tables = []
    for i in range(N_SHARDS):
        part = df.iloc[cuts[i] : cuts[i + 1]].reset_index(drop=True)
        root = str(base / f"taxi_{i}.bcolzs")
        ctable.fromdataframe(part, root)
        tables.append(ctable(root, mode="r"))
    return df, tables


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()  # all 8 virtual CPU devices


def mesh_result(tables, *args, **kw):
    query = GroupByQuery(*args, **kw)
    payload = MeshQueryExecutor(mesh=make_mesh()).execute(tables, query)
    wire = ResultPayload.from_bytes(payload.to_bytes())
    return hostmerge.payload_to_dataframe(hostmerge.merge_payloads([wire]))


def pershard_result(tables, *args, **kw):
    query = GroupByQuery(*args, **kw)
    engine = QueryEngine()
    payloads = [engine.execute_local(t, query) for t in tables]
    return hostmerge.payload_to_dataframe(hostmerge.merge_payloads(payloads))


def assert_frames_match(got, expected, key_cols, **kw):
    got = got.sort_values(key_cols).reset_index(drop=True)
    expected = expected.sort_values(key_cols).reset_index(drop=True)
    expected = expected[list(got.columns)]
    pd.testing.assert_frame_equal(
        got, expected, check_dtype=False, check_index_type=False,
        check_column_type=False, **kw
    )


def assert_dense_at_the_codes_dtype(dense, combos):
    """The alignment hands its dense codes over at the width every consumer
    packs them at (PR 30): no per-row int64 copy is kept."""
    from bqueryd_tpu.parallel.executor import _codes_dtype

    want = _codes_dtype(max(len(combos), 1))
    assert [d.dtype for d in dense] == [want] * len(dense)


def aligned(ex, tables, gcols):
    from bqueryd_tpu.parallel.executor import _table_key

    entry = ex._align_cache.get(
        (tuple(_table_key(t) for t in tables), tuple(gcols))
    )
    return entry[0], entry[1]


def test_mesh_uses_all_devices(mesh):
    assert mesh.devices.size == 8


def test_single_key_sum_matches_pandas(sharded, mesh):
    df, tables = sharded
    got = mesh_result(
        tables, ["passenger_count"], [["fare_amount", "sum", "fare_amount"]]
    )
    expected = df.groupby("passenger_count")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["passenger_count"])


def test_int64_sum_bit_exact(sharded, mesh):
    """North-star bit-for-bit int64: sums of int64 columns across the psum
    merge equal pandas exactly (no tolerance)."""
    df, tables = sharded
    got = mesh_result(
        tables, ["VendorID"], [["passenger_count", "sum", "s"]]
    ).sort_values("VendorID").reset_index(drop=True)
    expected = (
        df.groupby("VendorID")["passenger_count"].sum().reset_index(name="s")
    )
    assert got["s"].dtype == np.int64
    assert (got["s"].to_numpy() == expected["s"].to_numpy()).all()


def test_multi_key_multi_agg(sharded, mesh):
    df, tables = sharded
    args = (
        ["VendorID", "payment_type"],
        [
            ["fare_amount", "sum", "fare_sum"],
            ["fare_amount", "mean", "fare_mean"],
            ["trip_distance", "max", "dist_max"],
            ["passenger_count", "count", "n"],
        ],
    )
    got = mesh_result(tables, *args)
    g = df.groupby(["VendorID", "payment_type"])
    expected = pd.DataFrame(
        {
            "fare_sum": g["fare_amount"].sum(),
            "fare_mean": g["fare_amount"].mean(),
            "dist_max": g["trip_distance"].max(),
            "n": g["passenger_count"].count(),
        }
    ).reset_index()
    assert_frames_match(got, expected, ["VendorID", "payment_type"])


def test_string_key_across_shard_dictionaries(sharded, mesh):
    """Dict-encoded key columns have *different* per-shard dictionaries;
    alignment must merge by value, not by local code."""
    df, tables = sharded
    got = mesh_result(tables, ["flag"], [["fare_amount", "sum", "fare_amount"]])
    expected = df.groupby("flag")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["flag"])


def test_where_filter_pushdown(sharded, mesh):
    df, tables = sharded
    where = [["trip_distance", ">", 2.0], ["payment_type", "!=", 1]]
    got = mesh_result(
        tables,
        ["payment_type"],
        [["fare_amount", "sum", "fare_amount"]],
        where,
    )
    sel = df[(df.trip_distance > 2.0) & (df.payment_type != 1)]
    expected = sel.groupby("payment_type")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["payment_type"])


def test_high_cardinality_two_key(sharded, mesh):
    """The BASELINE.json stress config: PULocationID x DOLocationID."""
    df, tables = sharded
    got = mesh_result(
        tables,
        ["PULocationID", "DOLocationID"],
        [["fare_amount", "sum", "fare_amount"]],
    )
    expected = (
        df.groupby(["PULocationID", "DOLocationID"])["fare_amount"]
        .sum()
        .reset_index()
    )
    assert_frames_match(got, expected, ["PULocationID", "DOLocationID"])


def test_matches_pershard_hostmerge_path(sharded, mesh):
    """Device psum merge and host value-keyed merge are the same function."""
    df, tables = sharded
    args = (
        ["payment_type"],
        [["fare_amount", "mean", "m"], ["fare_amount", "min", "lo"]],
    )
    got = mesh_result(tables, *args)
    expected = pershard_result(tables, *args)
    assert_frames_match(got, expected, ["payment_type"])


def test_fewer_shards_than_devices(sharded, mesh):
    df, tables = sharded
    got = mesh_result(
        tables[:2], ["VendorID"], [["fare_amount", "sum", "fare_amount"]]
    )
    expected = (
        pd.concat([t.todataframe() for t in tables[:2]])
        .groupby("VendorID")["fare_amount"]
        .sum()
        .reset_index()
    )
    assert_frames_match(got, expected, ["VendorID"])


def test_more_shards_than_devices(tmp_path, mesh):
    df = taxi_like_df(n=3_000, seed=11)
    bounds = np.linspace(0, len(df), 14, dtype=int)  # 13 shards > 8 devices
    parts = [df.iloc[a:b] for a, b in zip(bounds[:-1], bounds[1:])]
    tables = []
    for i, part in enumerate(parts):
        root = str(tmp_path / f"s{i}.bcolzs")
        ctable.fromdataframe(part.reset_index(drop=True), root)
        tables.append(ctable(root, mode="r"))
    got = mesh_result(
        tables, ["payment_type"], [["fare_amount", "sum", "fare_amount"]]
    )
    expected = df.groupby("payment_type")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["payment_type"])


def test_prunes_unmatchable_shards_to_empty(sharded, mesh):
    _df, tables = sharded
    payload = MeshQueryExecutor(mesh=mesh).execute(
        tables,
        GroupByQuery(
            ["VendorID"],
            [["fare_amount", "sum", "s"]],
            [["trip_distance", ">", 1e9]],
        ),
    )
    # min/max pruning drops every shard before any device work
    assert payload["kind"] == "empty"


def test_rejects_non_mergeable_ops(sharded, mesh):
    _df, tables = sharded
    with pytest.raises(ValueError, match="mergeable"):
        MeshQueryExecutor(mesh=mesh).execute(
            tables,
            GroupByQuery(["VendorID"], [["payment_type", "count_distinct", "d"]]),
        )
    assert not MeshQueryExecutor.supports(
        GroupByQuery(["VendorID"], [["fare_amount", "sum", "s"]], aggregate=False)
    )


def test_packed_fetch_matches_unpacked(tmp_path, monkeypatch):
    """The single-buffer packed fetch (bitcast-to-uint64 concat inside the
    mesh program) must be lossless for every partial dtype: int64 sums,
    float32/float64 sums, counts, and min/max carried on narrowed wire
    dtypes (int8/int16)."""
    import pandas as pd

    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel import executor as ex
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor
    from bqueryd_tpu.storage.ctable import ctable

    rng = np.random.default_rng(21)
    n = 4000
    df = pd.DataFrame(
        {
            "g": rng.integers(0, 9, n).astype(np.int64),
            "big": rng.integers(-(2**60), 2**60, n).astype(np.int64),
            "small": rng.integers(-100, 100, n).astype(np.int64),  # int8 wire
            "f32": (rng.random(n) * 100).astype(np.float32),
            "f64": rng.random(n).astype(np.float64),
        }
    )
    tables = []
    for i in range(3):
        root = str(tmp_path / f"p{i}.bcolzs")
        ctable.fromdataframe(df.iloc[i::3], root)
        tables.append(ctable(root))
    query = GroupByQuery(
        ["g"],
        [
            ["big", "sum", "s"],
            ["small", "min", "lo"],
            ["small", "max", "hi"],
            ["f32", "mean", "m32"],
            ["f64", "sum", "s64"],
            ["big", "count", "n"],
        ],
        [],
        aggregate=True,
    )

    def run():
        ex._mesh_program.cache_clear()
        return MeshQueryExecutor().execute(tables, query)

    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    packed = run()
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "0")
    unpacked = run()
    from bqueryd_tpu.parallel import hostmerge

    df_p = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([packed]))
    df_u = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([unpacked]))
    pd.testing.assert_frame_equal(
        df_p.sort_values("g").reset_index(drop=True),
        df_u.sort_values("g").reset_index(drop=True),
        check_column_type=False,
    )
    expect = df.groupby("g")["big"].sum().sort_index()
    np.testing.assert_array_equal(
        df_p.sort_values("g")["s"].to_numpy(), expect.to_numpy()
    )


def test_packed_fetch_spec_stable_across_kernel_routes(tmp_path, monkeypatch):
    """Two row counts can route the SAME query shape through different
    kernels (MXU vs scatter past BQUERYD_TPU_MATMUL_CELLS), whose float
    partial dtypes differ (f64 vs f32).  Each width must decode with its
    own trace's spec — re-running the small query after the large one must
    not corrupt its float aggregates (the shared-spec retrace bug)."""
    import pandas as pd

    from bqueryd_tpu.models.query import GroupByQuery
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor
    from bqueryd_tpu.storage.ctable import ctable

    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    # rows*groups above this forces the scatter route for the LARGE table
    monkeypatch.setenv("BQUERYD_TPU_MATMUL_CELLS", str(5000 * 7))

    rng = np.random.default_rng(31)

    def build(name, n):
        df = pd.DataFrame(
            {
                "g": rng.integers(0, 7, n).astype(np.int64),
                "v": (rng.random(n) * 100).astype(np.float32),
            }
        )
        root = str(tmp_path / name)
        ctable.fromdataframe(df, root)
        return df, [ctable(root)]

    df_small, small = build("small.bcolz", 2000)
    df_large, large = build("large.bcolz", 60_000)
    query = GroupByQuery(["g"], [["v", "mean", "m"]], [], aggregate=True)
    executor = MeshQueryExecutor()

    def result_means(tables):
        payload = executor.execute(tables, query)
        df = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([payload])
        )
        return df.sort_values("g")["m"].to_numpy()

    def expect_means(df):
        return df.groupby("g")["v"].mean().sort_index().to_numpy()

    np.testing.assert_allclose(
        result_means(small), expect_means(df_small), rtol=1e-6
    )
    np.testing.assert_allclose(
        result_means(large), expect_means(df_large), rtol=1e-6
    )
    # the hazard: small again, after large's trace populated the cache
    np.testing.assert_allclose(
        result_means(small), expect_means(df_small), rtol=1e-6
    )


def test_sorted_form_over_four_devices_matches_host_and_the_default_spec(
        sharded, monkeypatch):
    """A two-key integer sum whose per-device partials come from the one
    carried-payload sort (a binding ``sort`` hint on this backend): the
    merged answer equals the host reference bit for bit, and what the packed
    fetch carries — leaves, dtypes, shapes — is what the default route's
    program carries, so the device merge and the fetch see no difference."""
    from bqueryd_tpu.parallel import executor as executor_mod

    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    df, tables = sharded
    specs = {}
    build = executor_mod._mesh_program

    def spy(*args, **kwargs):
        program, spec = build(*args, **kwargs)
        specs[kwargs.get("strategy")] = spec
        return program, spec

    monkeypatch.setattr(executor_mod, "_mesh_program", spy)
    query = GroupByQuery(
        ["PULocationID", "DOLocationID"],
        [["passenger_count", "sum", "riders"], ["payment_type", "sum", "pay"],
         ["VendorID", "count", "n"]],
    )
    executor = MeshQueryExecutor(mesh=make_mesh(4))
    frames = {}
    for strategy in (None, "sort"):
        payload = executor.execute(tables, query, strategy=strategy)
        assert executor.last_effective_strategy == (strategy or "scatter")
        frames[strategy] = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads(
                [ResultPayload.from_bytes(payload.to_bytes())]))
    assert specs[None]["leaves"] and specs["sort"] == specs[None]
    keys = ["PULocationID", "DOLocationID"]
    expected = df.groupby(keys, as_index=False).agg(
        riders=("passenger_count", "sum"), pay=("payment_type", "sum"),
        n=("VendorID", "count"),
    )
    assert len(expected) > 8192   # past the MXU route's ceiling
    for frame in frames.values():
        got = frame.sort_values(keys).reset_index(drop=True)
        for col in ("riders", "pay", "n"):
            assert got[col].dtype == np.int64
            np.testing.assert_array_equal(
                got[col].to_numpy(), expected[col].to_numpy())


def test_cold_path_hits_disk_sidecars_and_matches(sharded, mesh):
    """Warm query -> clear every process cache (the bench's cold reset) ->
    re-query: the alignment must come back from the on-disk factorize /
    composite sidecars bit-identically, for both single- and multi-key."""
    from bqueryd_tpu.storage.ctable import free_cachemem

    df, tables = sharded
    ex = MeshQueryExecutor(mesh=make_mesh())
    for gcols in (["passenger_count"], ["VendorID", "payment_type"]):
        query = GroupByQuery(
            gcols, [["fare_amount", "sum", "s"]], [], aggregate=True
        )
        warm = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([ex.execute(tables, query)])
        )
        # one key; two keys factorized (a composite-sidecar miss)
        assert_dense_at_the_codes_dtype(*aligned(ex, tables, gcols))
        # sidecars must exist next to the first shard now
        first = tables[0].rootdir
        assert os.path.isfile(
            os.path.join(first, "cols", gcols[0], "factor.npz")
        )
        ex.clear_caches()
        free_cachemem()
        # poison the factorizer: the cold query must be served entirely by
        # the sidecars, or an always-miss load regression could hide behind
        # a bit-identical recompute
        from bqueryd_tpu import ops as ops_mod

        real_factorize = ops_mod.factorize
        ops_mod.factorize = lambda *a, **k: (_ for _ in ()).throw(
            AssertionError("cold align recomputed instead of sidecar hit")
        )
        try:
            cold = hostmerge.payload_to_dataframe(
                hostmerge.merge_payloads([ex.execute(tables, query)])
            )
        finally:
            ops_mod.factorize = real_factorize
        # one key; two keys read back (a composite-sidecar hit)
        assert_dense_at_the_codes_dtype(*aligned(ex, tables, gcols))
        assert_frames_match(cold, warm, gcols)
        expected = (
            df.groupby(gcols, as_index=False)["fare_amount"]
            .sum()
            .rename(columns={"fare_amount": "s"})
        )
        assert_frames_match(cold, expected, gcols)


def test_program_bucket_properties():
    from bqueryd_tpu import ops

    for n in (1, 9, 16, 17, 100, 1000, 70225, 10_000_000):
        for fine in (False, True):
            b = ops.program_bucket(n, fine=fine)
            assert b >= n
            # bounded padding: <=12.5% coarse, <=3.2% fine (+1 step slack)
            limit = 1.032 if fine else 1.13
            assert n <= 16 or b <= int(n * limit) + 1, (n, fine, b)
            # stability: the whole step maps to one bucket
            assert ops.program_bucket(b, fine=fine) == b


def test_group_drift_reuses_compiled_program(tmp_path, mesh):
    """Two queries whose group counts differ but land in the same bucket
    must share one compiled mesh program — the point of shape bucketing
    (every exact cardinality is otherwise its own compile)."""
    from bqueryd_tpu.parallel import executor as ex_mod

    dfs = []
    for n_vals in (900, 905):  # both bucket to the same grid point
        rng = np.random.default_rng(n_vals)
        dfs.append(
            pd.DataFrame(
                {
                    "g": rng.integers(0, n_vals, 20_000).astype(np.int64),
                    "v": rng.integers(-100, 100, 20_000).astype(np.int64),
                }
            )
        )
    tables = []
    for i, df in enumerate(dfs):
        root = str(tmp_path / f"drift_{i}.bcolzs")
        ctable.fromdataframe(df, root)
        tables.append(ctable(root, mode="r"))

    ex = MeshQueryExecutor(mesh=make_mesh())
    query = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)
    before = ex_mod._mesh_program.cache_info()
    for df, t in zip(dfs, tables):
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads([ex.execute([t], query)])
        ).sort_values("g").reset_index(drop=True)
        expected = (
            df.groupby("g", as_index=False)["v"].sum()
            .rename(columns={"v": "s"})
        )
        assert_frames_match(got, expected, ["g"])
    after = ex_mod._mesh_program.cache_info()
    assert after.misses == before.misses + 1, (
        "group-count drift within one bucket must not recompile "
        f"(before={before}, after={after})"
    )
    assert after.hits >= before.hits + 1


def test_threaded_alignment_matches_sequential(sharded, mesh, monkeypatch):
    """BQUERYD_TPU_ALIGN_THREADS>1 must produce the identical alignment as
    the sequential path (single-core CI degrades to sequential silently, so
    force the pool on)."""
    df, tables = sharded
    for gcols in (["passenger_count"], ["VendorID", "payment_type"]):
        query = GroupByQuery(
            gcols, [["fare_amount", "sum", "s"]], [], aggregate=True
        )
        monkeypatch.setenv("BQUERYD_TPU_ALIGN_THREADS", "1")
        seq = MeshQueryExecutor(mesh=make_mesh())._global_key_space(
            tables, query, QueryEngine()
        )
        monkeypatch.setenv("BQUERYD_TPU_ALIGN_THREADS", "4")
        par = MeshQueryExecutor(mesh=make_mesh())._global_key_space(
            tables, query, QueryEngine()
        )
        s_dense, s_combos, s_cards, s_vals = seq
        p_dense, p_combos, p_cards, p_vals = par
        assert_dense_at_the_codes_dtype(s_dense, s_combos)
        assert_dense_at_the_codes_dtype(p_dense, p_combos)
        assert s_cards == p_cards
        np.testing.assert_array_equal(s_combos, p_combos)
        for a, b in zip(s_dense, p_dense):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for col in s_vals:
            np.testing.assert_array_equal(s_vals[col], p_vals[col])


def test_transient_runtime_error_retried_once(sharded, mesh, monkeypatch):
    """One transient JaxRuntimeError out of the merged-program dispatch
    (a transiently-classed INTERNAL/UNAVAILABLE status) must be retried in
    place so the mesh path still answers; a second failure propagates (the
    worker then degrades to the engine path).  Every retry is counted
    where a client can read it (devicehealth.degrade_counts)."""
    import jax

    from bqueryd_tpu.parallel import executor as ex_mod
    from bqueryd_tpu.utils import devicehealth

    retries_before = devicehealth.degrade_counts()["inplace_retry"]

    df, tables = sharded
    real = ex_mod._mesh_partials
    calls = {"n": 0}

    def flaky(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: device preempted"
            )
        return real(*args, **kw)

    monkeypatch.setattr(ex_mod, "_mesh_partials", flaky)
    got = mesh_result(
        tables, ["passenger_count"], [["fare_amount", "sum", "fare_amount"]]
    )
    assert calls["n"] == 2, "first failure must be retried exactly once"
    assert (
        devicehealth.degrade_counts()["inplace_retry"] == retries_before + 1
    )
    expected = df.groupby("passenger_count")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["passenger_count"])

    # persistent failure propagates after the single retry
    calls["n"] = 0

    def always_fail(*args, **kw):
        calls["n"] += 1
        raise jax.errors.JaxRuntimeError("INTERNAL: device preempted")

    monkeypatch.setattr(ex_mod, "_mesh_partials", always_fail)
    with pytest.raises(jax.errors.JaxRuntimeError):
        mesh_result(
            tables, ["VendorID"], [["fare_amount", "sum", "fare_amount"]]
        )
    assert calls["n"] == 2


def test_internal_error_does_not_latch_packed_fetch_off(
    sharded, mesh, monkeypatch
):
    """A transient INTERNAL JaxRuntimeError during the packed-fetch program
    must NOT set the process-lifetime _packed_fetch_broken latch (that
    would put every later query on per-leaf fetch — one D2H round-trip
    per result leaf); only a deterministic
    rejection (non-INTERNAL) is evidence against packing."""
    import jax

    from bqueryd_tpu.parallel import executor as ex_mod

    df, tables = sharded
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    monkeypatch.setattr(ex_mod, "_packed_fetch_broken", False)
    monkeypatch.setattr(ex_mod, "_packed_transient_count", 0)
    real_program = ex_mod._mesh_program
    calls = {"n": 0}

    def flaky_program(*args, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: remote_compile: HTTP 500"
            )
        return real_program(*args, **kw)

    monkeypatch.setattr(ex_mod, "_mesh_program", flaky_program)
    got = mesh_result(
        tables, ["passenger_count"], [["fare_amount", "sum", "fare_amount"]]
    )
    assert not ex_mod._packed_fetch_broken, (
        "transient INTERNAL error must not disable packed fetch for the "
        "process"
    )
    expected = df.groupby("passenger_count")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["passenger_count"])

    # a deterministic rejection DOES latch (and the query still answers
    # via per-leaf fetch, not an engine degrade)
    monkeypatch.setattr(ex_mod, "_packed_fetch_broken", False)
    state = {"first": True}

    def rejecting_program(*args, **kw):
        # reject only the packed variant (pack flag is positional arg 6)
        if args[6] and state["first"]:
            state["first"] = False
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: bitcast not supported"
            )
        return real_program(*args, **kw)

    monkeypatch.setattr(ex_mod, "_mesh_program", rejecting_program)
    from bqueryd_tpu.utils import devicehealth

    before = devicehealth.degrade_counts()
    got2 = mesh_result(
        tables, ["VendorID"], [["fare_amount", "sum", "fare_amount"]]
    )
    assert ex_mod._packed_fetch_broken, (
        "deterministic packed-program rejection must latch per-leaf fetch"
    )
    # a right answer from the per-leaf path is visible, not silent
    after = devicehealth.degrade_counts()
    assert after["packed_to_perleaf"] == before["packed_to_perleaf"] + 1
    assert after["packed_latched"] == before["packed_latched"] + 1
    expected2 = df.groupby("VendorID")["fare_amount"].sum().reset_index()
    assert_frames_match(got2, expected2, ["VendorID"])


def test_repeated_transient_failures_latch_past_cap(
    sharded, mesh, monkeypatch
):
    """A deterministic failure that carries a transient status (an XLA
    lowering bug classed INTERNAL) must not dodge the per-leaf latch
    forever: past _PACKED_TRANSIENT_LIMIT consecutive packed failures the
    latch sets anyway and the query answers via per-leaf fetch."""
    import jax

    from bqueryd_tpu.parallel import executor as ex_mod

    df, tables = sharded
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    monkeypatch.setattr(ex_mod, "_packed_fetch_broken", False)
    monkeypatch.setattr(ex_mod, "_packed_transient_count", 0)
    real_program = ex_mod._mesh_program

    def always_internal_on_packed(*args, **kw):
        if args[6]:  # the packed program variant
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: Mosaic lowering failed (deterministic)"
            )
        return real_program(*args, **kw)

    monkeypatch.setattr(ex_mod, "_mesh_program", always_internal_on_packed)
    # first query: both packed attempts raise transiently -> propagates
    with pytest.raises(jax.errors.JaxRuntimeError):
        mesh_result(
            tables, ["passenger_count"],
            [["fare_amount", "sum", "fare_amount"]],
        )
    # second query: cap reached -> latch sets, per-leaf fetch answers
    got = mesh_result(
        tables, ["VendorID"], [["fare_amount", "sum", "fare_amount"]]
    )
    assert ex_mod._packed_fetch_broken
    expected = df.groupby("VendorID")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["VendorID"])


def test_backend_outage_does_not_latch_packed_fetch(
    sharded, mesh, monkeypatch
):
    """When packed AND per-leaf both fail (whole backend down), the failure
    carries no packed-specific signal: the per-leaf latch must stay unset
    so packing resumes once the backend recovers."""
    import jax

    from bqueryd_tpu.parallel import executor as ex_mod

    df, tables = sharded
    monkeypatch.setenv("BQUERYD_TPU_PACKED_FETCH", "1")
    monkeypatch.setattr(ex_mod, "_packed_fetch_broken", False)
    # at the cap: the next packed failure takes the latch-pending path
    monkeypatch.setattr(
        ex_mod, "_packed_transient_count", ex_mod._PACKED_TRANSIENT_LIMIT
    )
    real_program = ex_mod._mesh_program
    down = {"is": True}

    def outage_program(*args, **kw):
        if down["is"]:
            raise jax.errors.JaxRuntimeError("UNAVAILABLE: device down")
        return real_program(*args, **kw)

    monkeypatch.setattr(ex_mod, "_mesh_program", outage_program)
    with pytest.raises(jax.errors.JaxRuntimeError):
        mesh_result(
            tables, ["passenger_count"],
            [["fare_amount", "sum", "fare_amount"]],
        )
    assert not ex_mod._packed_fetch_broken, (
        "an outage that also kills per-leaf fetch must not latch packing off"
    )
    # backend recovers: packed fetch resumes and the query answers
    down["is"] = False
    got = mesh_result(
        tables, ["VendorID"], [["fare_amount", "sum", "fare_amount"]]
    )
    assert not ex_mod._packed_fetch_broken
    expected = df.groupby("VendorID")["fare_amount"].sum().reset_index()
    assert_frames_match(got, expected, ["VendorID"])


def test_route_flag_flip_rebuilds_mesh_program(sharded, mesh, monkeypatch):
    """The kernel route is decided at TRACE time inside the cached mesh
    program: flipping a route flag (the bench's pallas variants, live
    re-tuning) must be a cache MISS that re-traces, not a silent hit that
    keeps serving the old route (the r4 bench's sharded_pallas number was
    exactly that sham, on the CPU side)."""
    from bqueryd_tpu.parallel import executor as ex_mod

    df, tables = sharded
    monkeypatch.delenv("BQUERYD_TPU_PALLAS", raising=False)
    args = (["passenger_count"], [["passenger_count", "sum", "s"]])
    mesh_result(tables, *args)
    before = ex_mod._mesh_program.cache_info()
    # same query, same flags: cache hit
    mesh_result(tables, *args)
    mid = ex_mod._mesh_program.cache_info()
    assert mid.misses == before.misses, "same-flags repeat must not re-trace"
    # flipped flag: cache miss (fresh trace through the dispatcher)
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    got = mesh_result(tables, *args)
    after = ex_mod._mesh_program.cache_info()
    assert after.misses > mid.misses, "flag flip must rebuild the program"
    got = got.sort_values("passenger_count").reset_index(drop=True)
    truth = df.groupby("passenger_count")["passenger_count"].sum()
    np.testing.assert_array_equal(
        got["s"].to_numpy(), truth.sort_index().to_numpy()
    )


def test_hicard_pallas_route_through_mesh(tmp_path, monkeypatch):
    """The group-tiled hicard Pallas kernel inside the full mesh program
    (shard_map + psum + packed fetch) — the exact composition the TPU
    bench's highcard+pallas variant executes — must stay bit-exact."""
    monkeypatch.setenv("BQUERYD_TPU_PALLAS", "1")
    rng = np.random.default_rng(31)
    n, ng = 30_000, 14_000  # observed uniques safely past matmul_groups_limit
    df = pd.DataFrame(
        {
            "k": rng.integers(0, ng, n).astype(np.int64),
            "v": rng.integers(-(2**40), 2**40, n).astype(np.int64),
        }
    )
    tables = []
    for i in range(3):
        root = str(tmp_path / f"hc{i}.bcolzs")
        ctable.fromdataframe(df.iloc[i::3].reset_index(drop=True), root)
        tables.append(ctable(root, mode="r"))
    from bqueryd_tpu.ops import groupby as gb

    # the executor routes on OBSERVED combos, not the fixture's nominal
    # cardinality: guard with the value the gate actually sees, so a
    # fixture drift below matmul_groups_limit cannot silently demote the
    # test to the non-Pallas route
    observed = df["k"].nunique()
    assert observed > gb.matmul_groups_limit(), (
        f"fixture drifted: {observed} observed groups no longer clears "
        f"matmul_groups_limit ({gb.matmul_groups_limit()})"
    )
    assert gb._hicard_matmul_profitable(
        (df["v"].to_numpy(),), ("sum",), n, observed
    ), "fixture must hit the hicard gate"
    got = mesh_result(tables, ["k"], [["v", "sum", "s"]])
    got = got.sort_values("k").reset_index(drop=True)
    exp = (
        df.groupby("k", as_index=False)["v"].sum()
        .rename(columns={"v": "s"})
        .sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_array_equal(got["k"].to_numpy(), exp["k"].to_numpy())
    np.testing.assert_array_equal(got["s"].to_numpy(), exp["s"].to_numpy())


@pytest.mark.parametrize("n_devices", [1, 4])
@pytest.mark.parametrize("profile", ["1", None], ids=["traced", "untraced"])
def test_float64_mean_rides_the_matmul_route_with_a_dense_sum(
        sharded, groupby_as_accelerator, monkeypatch, n_devices, profile):
    """PR 31: a float64 mean over few groups goes by the MXU route (its
    counts are rows of the dot) and sums densely, per device of the mesh,
    under auto and under the advisory "matmul" alike; the form is reported
    only under the profile switch."""
    df, tables = sharded
    if profile:
        monkeypatch.setenv("BQUERYD_TPU_PROFILE", profile)
    else:
        monkeypatch.delenv("BQUERYD_TPU_PROFILE", raising=False)
    query = GroupByQuery(
        ["passenger_count"], [["fare_amount", "mean", "m"]],
        [["trip_distance", ">", 1.5]],
    )
    executor = MeshQueryExecutor(mesh=make_mesh(n_devices))
    kept = df[df["trip_distance"] > 1.5]
    expected = (
        kept.groupby("passenger_count")["fare_amount"].mean()
        .rename("m").reset_index()
    )
    from bqueryd_tpu.parallel import executor as ex_mod

    traces = set()
    for hint in (None, "auto", "matmul"):
        payload = executor.execute(tables, query, strategy=hint)
        traces.add(ex_mod._mesh_program.cache_info().misses)
        assert executor.last_effective_strategy == "matmul"
        assert executor.last_float_sum == ("dense" if profile else None)
        got = hostmerge.payload_to_dataframe(
            hostmerge.merge_payloads(
                [ResultPayload.from_bytes(payload.to_bytes())]
            )
        )
        assert_frames_match(got, expected, ["passenger_count"], rtol=1e-12)
    assert len(traces) == 1, "three spellings of auto, ONE traced program"
    executor.execute(tables, query, strategy="scatter")
    assert ex_mod._mesh_program.cache_info().misses not in traces
    assert executor.last_effective_strategy == "scatter"
    assert executor.last_float_sum == ("dense" if profile else None)


def test_mesh_executor_reports_route(sharded):
    """``last_effective_strategy`` is the route the kernel rule took: the
    MXU route under auto (conftest's FORCE_MATMUL=1), the forced route
    where a test forces one."""
    _df, tables = sharded
    executor = MeshQueryExecutor(mesh=make_mesh())
    query = GroupByQuery(["passenger_count"], [["payment_type", "sum", "s"]])
    executor.execute(tables, query)
    assert executor.last_effective_strategy == "matmul"
    executor.execute(tables, query, strategy="scatter")
    assert executor.last_effective_strategy == "scatter"
