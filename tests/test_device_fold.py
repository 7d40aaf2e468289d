"""The device-side fold of ``MeshQueryExecutor.execute`` (PR 28): a filter of
scalar compares on numeric / datetime columns folds into the group codes on
the device, from resident unmasked codes and resident filter columns, by
one small jitted program — bit for bit what the host's ``build_mask`` +
``np.where`` + ``_pack`` gives, pad rows included; every other filter
folds on the host as it did.  The folded codes of a device fold are not
kept (PR 30): the two resident arrays are the state.
"""

import numpy as np
import pandas as pd
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bqueryd_tpu import ops
from bqueryd_tpu.models.query import GroupByQuery, ResultPayload
from bqueryd_tpu.obs import profile as obs_profile
from bqueryd_tpu.parallel import executor as executor_mod
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import (
    MeshQueryExecutor,
    _codes_dtype,
    _table_key,
    _where_signature,
    make_mesh,
)
from bqueryd_tpu.ops.predicates import build_mask as BUILD_MASK
from bqueryd_tpu.storage import ctable

PACK = MeshQueryExecutor._pack   # the spies below stand in for both
OPS = ("==", "!=", "<", "<=", ">", ">=")
CUTS = (0, 700, 2900, 4000)   # uneven shards: the pack pads


def frame(n=4000, seed=28):
    rng = np.random.default_rng(seed)
    f32 = (rng.random(n) * 10).round(2).astype(np.float32)
    f32[rng.random(n) < 0.05] = np.nan
    narrow = rng.integers(0, 100, n).astype(np.int64)
    return pd.DataFrame({
        "k": rng.integers(0, 9, n).astype(np.int64),          # int8 codes
        "k2": rng.integers(0, 300, n).astype(np.int64),       # int16 codes
        "v": rng.integers(-50, 50, n).astype(np.int64),
        "f32": f32,
        # a range ``_wire_dtype`` narrows to int8, and one it cannot
        "narrow": narrow,
        "wide": rng.integers(-2**40, 2**40, n).astype(np.int64),
        "ts": pd.Timestamp("2016-01-01")
        + pd.to_timedelta(rng.integers(0, 31 * 86400, n), unit="s"),
        # the same values as ``narrow``, dictionary-encoded: the host path
        "narrow_dict": narrow.astype(str),
        "basket": rng.integers(0, 500, n).astype(np.int64),
    })


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    df = frame()
    base = tmp_path_factory.mktemp("fold")
    tables = []
    for i in range(len(CUTS) - 1):
        root = str(base / f"s{i}.bcolzs")
        ctable.fromdataframe(
            df.iloc[CUTS[i]:CUTS[i + 1]].reset_index(drop=True), root
        )
        tables.append(ctable(root, mode="r"))
    assert tables[0].kind("narrow_dict") == "dict"
    assert tables[0].kind("ts") == "datetime"
    assert tables[0].physical_dtype("f32") == np.float32
    assert executor_mod._wire_dtype(tables, "narrow") == np.int8
    assert executor_mod._wire_dtype(tables, "wide") is None
    return df, tables


def constants(df):
    """One constant per filter column, inside its range and, for ``==``,
    one the column holds."""
    return {
        "f32": float(df["f32"].dropna().iloc[7]),
        "narrow": 41,
        "wide": int(df["wide"].iloc[11]),
        "ts": str(df["ts"].sort_values().iloc[len(df) // 2]),
    }


def run(ex, tables, groupby, where, aggs=(("v", "sum", "s"),), **kw):
    query = GroupByQuery(list(groupby), [list(a) for a in aggs],
                         where_terms=[list(t) for t in where], **kw)
    payload = ex.execute(tables, query)
    wire = ResultPayload.from_bytes(payload.to_bytes())
    return query, hostmerge.payload_to_dataframe(
        hostmerge.merge_payloads([wire])
    )


def codes_key(ex, tables, query):
    return (
        tuple(_table_key(t) for t in tables), "codes",
        tuple(query.groupby_cols), _where_signature(query),
        ex.mesh.devices.size,
    )


def cached_codes(ex, tables, query):
    key = codes_key(ex, tables, query)
    assert key in ex._codes_cache
    return ex._codes_cache.get(key)


def host_folded(ex, tables, query):
    """The parent's fold, statement for statement, over the aligned dense
    codes the executor itself cached."""
    n_dev = ex.mesh.devices.size
    tables_key = tuple(_table_key(t) for t in tables)
    dense, combos, _cards, _kv = ex._align_cache.get(
        (tables_key, tuple(query.groupby_cols))
    )
    cdt = _codes_dtype(max(len(combos), 1))
    folded = [
        np.where(np.asarray(BUILD_MASK(t, query.where_terms)), d, -1)
        .astype(cdt)
        for t, d in zip(tables, dense)
    ]
    return PACK(folded, n_dev, cdt.type(-1), dtype=cdt)


def assert_folded_like_the_host(spies, ex, tables, query):
    """``spies["device"].last``: what the last ``_fold_on_device`` returned,
    which no cache holds."""
    assert codes_key(ex, tables, query) not in ex._codes_cache
    got = spies["device"].last
    want = host_folded(ex, tables, query)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.sharding == NamedSharding(ex.mesh, P(ex.axis_name, None))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want == -1).any() and (want != -1).any()   # a filter that cuts


class Spy:
    """Counts the calls of a function it stands in for."""

    def __init__(self, fn):
        self.fn, self.calls, self.last = fn, 0, None

    def __call__(self, *args, **kwargs):
        self.calls += 1
        self.last = self.fn(*args, **kwargs)
        return self.last


@pytest.fixture
def spies(monkeypatch):
    """``pack`` (every ``_pack``), ``host`` (every ``build_mask``: the host
    path's mask-making) and ``device`` (every ``_fold_on_device``)."""
    pack = Spy(MeshQueryExecutor._pack)
    host = Spy(ops.build_mask)
    device = Spy(MeshQueryExecutor._fold_on_device)
    monkeypatch.setattr(MeshQueryExecutor, "_pack", staticmethod(pack))
    monkeypatch.setattr(ops, "build_mask", host)
    monkeypatch.setattr(
        MeshQueryExecutor, "_fold_on_device",
        lambda self, *a, **kw: device(self, *a, **kw),
    )
    return {"pack": pack, "host": host, "device": device}


# -- bit for bit the host's codes ----------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("column", ["f32", "narrow", "wide", "ts"])
@pytest.mark.parametrize("op", OPS)
def test_device_folded_codes_are_the_hosts(data, spies, op, column, n_dev):
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(n_dev))
    query, _ = run(ex, tables, ["k2"], [[column, op, constants(df)[column]]])
    assert spies["device"].calls == 1 and spies["host"].calls == 0
    assert_folded_like_the_host(spies, ex, tables, query)


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("op,constant", [
    ("<", 300), ("<=", 300), ("!=", 300), (">", -200), (">=", -200),
    ("!=", -200), ("<", 128), (">", -129),
])
def test_a_constant_outside_the_narrowed_range_compares_in_the_stored_dtype(
        data, spies, op, constant, n_dev):
    """``narrow`` rides the wire as int8 when it is a measure; the filter
    copy stays int64, so 300 is 300 and every row passes."""
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(n_dev))
    query, got = run(ex, tables, ["k"], [["narrow", op, constant]],
                     aggs=[("narrow", "sum", "s")])
    assert spies["device"].calls == 1 and spies["host"].calls == 0
    np.testing.assert_array_equal(
        np.asarray(spies["device"].last),
        np.asarray(cached_codes(ex, tables, GroupByQuery(["k"], []))),
    )
    want = df.groupby("k")["narrow"].sum()
    assert got.sort_values("k")["s"].tolist() == want.tolist()


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("where", [
    [["f32", ">", 2.5], ["wide", "<=", 2**39]],
    [["f32", ">", 2.5], ["f32", "<", 7.25]],
    [["ts", ">=", "2016-01-10"], ["narrow", "!=", 41], ["f32", "<=", 9.0]],
], ids=["two-columns", "one-column-twice", "three-terms"])
def test_and_ed_terms_fold_like_the_hosts(data, spies, where, n_dev):
    _df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(n_dev))
    query, _ = run(ex, tables, ["k"], where)
    assert spies["device"].calls == 1 and spies["host"].calls == 0
    assert_folded_like_the_host(spies, ex, tables, query)


# -- the answers ---------------------------------------------------------------

PANDAS_OPS = {
    "==": lambda s, c: s == c, "!=": lambda s, c: s != c,
    "<": lambda s, c: s < c, "<=": lambda s, c: s <= c,
    ">": lambda s, c: s > c, ">=": lambda s, c: s >= c,
}


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("op", OPS)
def test_a_filtered_groupby_equals_pandas(data, op, n_dev):
    """float32 with NaNs: a NaN row passes ``!=`` alone, as in pandas."""
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(n_dev))
    c = constants(df)["f32"]
    _, got = run(ex, tables, ["k"], [["f32", op, c]],
                 aggs=[("v", "sum", "s"), ("v", "count", "n")])
    kept = df[PANDAS_OPS[op](df["f32"], np.float32(c))]
    want = kept.groupby("k")["v"].agg(["sum", "count"])
    got = got.sort_values("k")
    assert got["k"].tolist() == want.index.tolist()
    assert got["s"].tolist() == want["sum"].tolist()
    assert got["n"].tolist() == want["count"].tolist()


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("op", ["==", "!="])
def test_the_device_fold_equals_the_host_fold_over_a_dict_column(
        data, spies, op, n_dev):
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(n_dev))
    _, device = run(ex, tables, ["k2"], [["narrow", op, 41]])
    assert (spies["device"].calls, spies["host"].calls) == (1, 0)
    _, host = run(ex, tables, ["k2"], [["narrow_dict", op, "41"]])
    assert spies["device"].calls == 1 and spies["host"].calls == len(tables)
    want = df[PANDAS_OPS[op](df["narrow"], 41)].groupby("k2")["v"].sum()
    for got in (device, host):
        got = got.sort_values("k2")
        assert got["k2"].tolist() == want.index.tolist()
        assert got["s"].tolist() == want.tolist()


# -- which filters fold where ----------------------------------------------------

@pytest.mark.parametrize("where,kwargs", [
    ([["narrow_dict", "==", "41"]], {}),
    ([["narrow", "in", [3, 41, 77]]], {}),
    ([["narrow", "not in", [3, 41, 77]]], {}),
    ([["f32", ">", 2.5], ["narrow_dict", "!=", "41"]], {}),
    ([["f32", ">", 2.5]], {"expand_filter_column": "basket"}),
    ([], {}),
], ids=["dict", "in", "not-in", "one-dict-term-of-two", "basket-expansion",
        "no-filter"])
def test_every_other_filter_folds_on_the_host(data, spies, where, kwargs):
    _df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    run(ex, tables, ["k"], where, **kwargs)
    assert spies["device"].calls == 0
    assert spies["host"].calls == len(tables)


def test_shards_of_differing_stored_dtypes_fold_on_the_host(
        tmp_path, spies):
    """A float32 shard compares a Python float in float32 and a float64
    shard in float64: one packed column could not give both."""
    rng = np.random.default_rng(3)
    tables, frames = [], []
    for i, dtype in enumerate((np.float32, np.float64)):
        part = pd.DataFrame({
            "k": rng.integers(0, 5, 500).astype(np.int64),
            "v": rng.integers(0, 9, 500).astype(np.int64),
            "x": rng.random(500).round(1).astype(dtype),
        })
        ctable.fromdataframe(part, str(tmp_path / f"m{i}.bcolzs"))
        tables.append(ctable(str(tmp_path / f"m{i}.bcolzs"), mode="r"))
        frames.append(part)
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    _, got = run(ex, tables, ["k"], [["x", ">", 0.1]])
    assert spies["device"].calls == 0 and spies["host"].calls == 2
    want = sum(
        part[part["x"] > part["x"].dtype.type(0.1)].groupby("k")["v"].sum()
        for part in frames
    )
    assert got.sort_values("k")["s"].tolist() == want.tolist()


@pytest.mark.parametrize("value", [None, "abc", [1, 2], np.float64(2.5)],
                         ids=["none", "str", "list", "numpy-scalar-is-strong"])
def test_only_a_python_number_is_a_traced_constant(data, value):
    _df, tables = data
    query = GroupByQuery(["k"], [["v", "sum", "s"]],
                         where_terms=[["narrow", "==", value]])
    want = None if not isinstance(value, float) else [("narrow", "==", 2.5)]
    assert executor_mod._device_fold_terms(tables, query) == want


# -- what a fresh constant costs ---------------------------------------------------

def test_a_fresh_constant_compiles_nothing_and_packs_nothing(data, spies):
    _df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    run(ex, tables, ["k2"], [["f32", ">", 1.25]])
    profiler = obs_profile.profiler()
    misses, packs = profiler.jit_cache_misses, spies["pack"].calls
    names = set(profiler.programs)
    for constant in (2.5, 3.75, 0.015625, 9.0):
        query, _ = run(ex, tables, ["k2"], [["f32", ">", constant]])
        assert_folded_like_the_host(spies, ex, tables, query)
    assert profiler.jit_cache_misses == misses
    assert spies["pack"].calls == packs
    assert spies["device"].calls == 5
    # the registry keys a traced scalar by its type, like the jit cache
    assert set(profiler.programs) == names
    fold = [p for p in profiler.programs.values()
            if p["name"] == "executor.fold_program"
            and "float32[4," in p["signature"]]
    assert fold and all("'float'" in p["signature"] for p in fold)


@pytest.mark.parametrize("budget", ["roomy", "tight", "one-entry"])
def test_twenty_fresh_filters_build_the_resident_arrays_once(
        data, spies, budget):
    """Alternating two key sets.  ``tight``: the codes segment holds the two
    unmasked entries and not a byte more; ``one-entry`` (one key set): the
    one.  Nothing else enters the segment, so nothing is ever evicted."""
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    width = int(ops.program_bucket(-(-CUTS[-1] // 4), fine=True))
    k_bytes, k2_bytes = 4 * width, 4 * width * 2      # int8, int16
    key_sets = [["k"], ["k2"]]
    if budget == "tight":
        ex._codes_cache.max_bytes = k_bytes + k2_bytes
    elif budget == "one-entry":
        ex._codes_cache.max_bytes = k2_bytes
        key_sets = [["k2"]]
    for i in range(20):
        keys = key_sets[i % len(key_sets)]
        constant = 0.5 + 0.37 * i
        query, got = run(ex, tables, keys, [["f32", ">", constant]])
        if budget == "one-entry":
            want = df[df["f32"] > np.float32(constant)].groupby("k2")["v"].sum()
            assert got.sort_values("k2")["s"].tolist() == want.tolist()
        else:
            assert_folded_like_the_host(spies, ex, tables, query)
    # an unmasked codes array a key set, the filter column, the measure
    assert spies["pack"].calls == len(key_sets) + 2
    assert spies["device"].calls == 20 and spies["host"].calls == 0
    stats = ex._codes_cache.stats()
    assert stats["evictions"] == 0 and stats["rejected"] == 0
    assert stats["entries"] == len(key_sets)
    assert ex._hbm_cache.stats()["entries"] == 2


def test_the_same_filter_twice_folds_twice_from_one_resident_entry(
        data, spies):
    """A repeat is the worker's result cache's to answer, before the
    executor runs; here it is one more dispatch."""
    _df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    _, first = run(ex, tables, ["k"], [["wide", "<", 12345]])
    packs = spies["pack"].calls
    _, again = run(ex, tables, ["k"], [["wide", "<", 12345]])
    assert spies["device"].calls == 2 and spies["pack"].calls == packs
    assert ex._codes_cache.stats()["entries"] == 1
    pd.testing.assert_frame_equal(first, again)


@pytest.mark.parametrize("first", ["filtered", "unfiltered"])
def test_one_unmasked_entry_serves_the_unfiltered_query_too(
        data, spies, first):
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(4))
    order = [[["f32", ">", 5.0]], []]
    if first == "unfiltered":
        order.reverse()
    for where in order:
        _, got = run(ex, tables, ["k"], where)
        kept = df[df["f32"] > 5.0] if where else df
        assert got.sort_values("k")["s"].tolist() == (
            kept.groupby("k")["v"].sum().tolist())
    # the unmasked codes once, the filter column, the measure column
    assert spies["pack"].calls == 3
    assert ex._codes_cache.stats()["entries"] == 1


# -- the tracing: one name, two sites ------------------------------------------------

@pytest.mark.parametrize("where,site", [
    ([["f32", ">", 2.5]], "device"),
    ([["narrow_dict", "==", "41"]], "host"),
])
def test_layout_fold_names_the_site_that_folded(data, monkeypatch, where, site):
    import jax.profiler

    from bqueryd_tpu.obs.trace import SpanRecorder
    from bqueryd_tpu.utils import tracing

    _df, tables = data
    opened = []

    class Annotation:
        def __init__(self, name, **kwargs):
            opened.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setenv("BQUERYD_TPU_PROFILE", "1")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    recorder = SpanRecorder("c" * 32, "d" * 16, "calc")
    ex = MeshQueryExecutor(
        mesh=make_mesh(4), timer=tracing.PhaseTimer(recorder=recorder)
    )
    run(ex, tables, ["k"], where)
    folds = [kwargs for name, kwargs in opened if name == "layout_fold"]
    assert [f["site"] for f in folds] == [site]
    names = [name for name, _ in opened]
    # inside ``layout``, after ``mask``: where ENCLOSING expects it
    assert names.index("mask") < names.index("layout") < names.index("layout_fold")
    spans = [s["name"] for s in recorder.spans]
    assert spans.count("layout_fold") == 1


def test_instrument_keys_the_registry_by_what_signature_args_gives():
    import jax

    profiler = obs_profile._reset_for_tests()
    program = obs_profile.instrument(
        "test.scaled", jax.jit(lambda x, c: x * c),
        signature_args=lambda x, c: (x, type(c).__name__),
    )
    x = np.arange(4, dtype=np.float32)
    for c in (1.5, 2.5, 3.5):
        program(x, c)
    assert profiler.jit_cache_misses == 1 and profiler.jit_cache_hits == 2
    assert list(profiler.programs) == ["test.scaled(float32[4];'float')"]
