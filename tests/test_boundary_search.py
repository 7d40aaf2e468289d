"""The sorted groups' boundary search (``ops.groupby._group_ends``): where
each group ends in the rows sorted by group key, read a row of keys at a
time (a compare of the few top-level keys with every group, then one gather
of a row per group and level) in place of ``jnp.searchsorted``'s dependent
one-element gathers.

Held to ``np.searchsorted(key_s, arange(G), side="right")`` element for
element over row counts at and around a row and a block of rows, group
counts from one to more than the rows, and key sets that leave groups
empty, put every row in one group or none, and run one key across rows;
with the shipped row width and with a narrow one that makes many levels.
Through ``partial_tables`` the sorted route's counts and int64 sums match
the blocked scatter's bit for bit.  Two structural guards, cheap on the
CPU: the sorted route's program holds no ``while`` (the old search's
loop), and the ``f64mean`` shape, which never sorts, lowers to the text it
lowered to before.  One compile at the real size for a described TPU v5e.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

from test_float_sum_forms import skewed


def _gb():
    import bqueryd_tpu.ops.groupby  # noqa: F401

    return sys.modules["bqueryd_tpu.ops.groupby"]


# -- exactness -------------------------------------------------------------------

def _runs_across_rows(n, n_groups, rng):
    """Runs of one key whose lengths straddle a row of 128 keys and a block
    of 16 384 (the second level's row of rows), then the invalid key."""
    pattern = [1, 127, 130, 16_390, 3, 255, 16_384, 129]
    lengths = np.tile(pattern, -(-n // sum(pattern)))
    key = np.repeat(np.arange(len(lengths)), lengths)[:n]
    return np.minimum(key, n_groups)


KEY_SETS = {
    "all_invalid": lambda n, g, rng: np.full(n, g),
    "one_group": lambda n, g, rng: np.full(n, g // 2),
    "every_third_empty": lambda n, g, rng: np.where(
        rng.random(n) < 0.05, g,
        rng.choice(np.arange(g)[np.arange(g) % 3 != 2] if g > 2 else np.arange(g), n)),
    # the float sums' skewed codes (nulls the invalid key), folded onto a
    # group count too small for the generator
    "skewed": lambda n, g, rng: (lambda c: np.where(c >= 0, c % g, g))(
        rng.choice(skewed(max(g, 40))[0], n)),
    "runs_across_rows": _runs_across_rows,
}

ROW_COUNTS = [1, 127, 128, 129, 16_383, 16_384, 16_385, 100_003]


@pytest.mark.parametrize("row", [None, 4], ids=["shipped", "row_4"])
@pytest.mark.parametrize("keys", sorted(KEY_SETS))
@pytest.mark.parametrize("n", ROW_COUNTS)
def test_the_row_search_equals_searchsorted(n, keys, row):
    """Every group count of the list, and one more group than rows (groups
    no row can reach): ``ends`` is ``searchsorted``'s, int32, element for
    element.  ``row_4`` reads rows of 4 keys, so 100 003 rows take eight
    levels."""
    import jax
    import jax.numpy as jnp

    m = _gb()
    search = jax.jit(m._group_ends, static_argnums=(1, 2))
    rng = np.random.default_rng([41, n])
    for n_groups in (1, 2, 40, 2304, n + 5):
        key_s = np.sort(KEY_SETS[keys](n, n_groups, rng)).astype(np.int32)
        assert key_s.min() >= 0 and key_s.max() <= n_groups
        got = search(jnp.asarray(key_s), n_groups, row)
        assert got.dtype == jnp.int32 and got.shape == (n_groups,)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.searchsorted(key_s, np.arange(n_groups), side="right"),
            err_msg=f"{n_groups} groups")


@pytest.mark.parametrize("n_groups", [9000, 70_225])
def test_the_sorted_route_matches_the_blocked_scatter_bit_for_bit(
        groupby_as_accelerator, n_groups):
    """``auto`` on an accelerator above the MXU's groups: the sorted route
    (one sort, the row search, prefix differences); ``scatter``: the
    blocked limb scatter.  Rows, counts and int64 sums (values at both ends
    of the range, so they wrap) agree bit for bit, a filter on top."""
    import jax

    m = groupby_as_accelerator
    rng = np.random.default_rng(n_groups)
    n = 150_000
    codes = rng.choice(skewed(n_groups)[0], n).astype(np.int32)
    values = rng.integers(-(2**62), 2**62, n, dtype=np.int64)
    values[::7] = np.iinfo(np.int64).max
    mask = rng.random(n) < 0.8
    measures, ops = (values, values), ("sum", "count")
    assert m.kernel_route(None, measures, ops, n, n_groups) == "sort"
    got, want = (
        jax.device_get(m.partial_tables(
            codes, measures, ops, n_groups, mask=mask, strategy=strategy))
        for strategy in (None, "scatter"))
    np.testing.assert_array_equal(got["rows"], want["rows"])
    np.testing.assert_array_equal(got["aggs"][0]["sum"], want["aggs"][0]["sum"])
    np.testing.assert_array_equal(got["aggs"][1]["count"], want["aggs"][1]["count"])
    kept = mask & (codes >= 0)
    np.testing.assert_array_equal(
        got["rows"], np.bincount(codes[kept], minlength=n_groups))


# -- structure -------------------------------------------------------------------

def test_the_sorted_route_lowers_to_one_sort_and_no_loop():
    """The search is straight-line: the program of the binding ``sort``
    hint (the accelerator's form of the scatter route) at 4 096 rows x 500
    groups holds its one sort and no ``while`` — ``searchsorted``'s default
    form was a loop of 12 dependent one-element gathers here, 24 at the
    cells' 11 M rows."""
    import jax
    import jax.numpy as jnp

    m = _gb()
    codes = jax.ShapeDtypeStruct((4096,), jnp.int32)
    values = jax.ShapeDtypeStruct((4096,), jnp.int64)
    text = m._partial_tables_scatter.__wrapped__.lower(
        codes, (values, values), ("sum", "count"), 500, force_sort=True
    ).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "stablehlo.while" not in text


#: sha256 of the StableHLO text of the ``f64mean`` shape's mesh program
#: (10 groups, one float64 mean, one device, the accelerator's forms: the
#: one-hot dot and the dense float64 sum) as the tree lowered it before the
#: row search came in.  That program never builds ``_SortedGroups``, so a
#: change to the search leaves it byte for byte and the compile cache gains
#: no entry for it; a change that alters it on purpose updates this line.
F64MEAN_PROGRAM_SHA256 = (
    "36d9f8a0724466c8da5d4ee8d21400d914c51b4fb2b3bc7f41b1e619a6d5a21d")


def test_the_f64mean_program_is_the_text_it_was(groupby_as_accelerator):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from bqueryd_tpu.parallel import devicemerge, executor

    mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
    program, _spec = executor._mesh_program(
        mesh, "shards", ("mean",), 10, ("int32", "float64"), 4096, True,
        route=executor._route_key(), merge_mode=devicemerge.MODE_DEVICE)
    text = program.__wrapped__.lower(
        jax.ShapeDtypeStruct((1, 4096), jnp.int32),
        jax.ShapeDtypeStruct((1, 4096), jnp.float64),
    ).as_text()
    assert "stablehlo.sort" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == F64MEAN_PROGRAM_SHA256


# -- the real size, compiled for a described TPU v5e ------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A device of a described v5e 2x2 host: nothing is attached, the TPU
    compiler compiles for it.  Described here, when a test of this module
    first asks, never at import."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_row_search_compiles_for_a_v5e_at_the_cells_size(one_chip):
    """11 010 048 sorted keys (a device's padded rows in every cell) and
    73 728 groups (``highcard``'s and ``zonepair_tips``' program groups):
    the TPU compiler takes it, and what it makes holds no loop."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    m = _gb()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        key_s = jax.ShapeDtypeStruct((11_010_048,), jnp.int32, sharding=one_chip)
        compiled = jax.jit(m._group_ends, static_argnums=1).lower(
            key_s, 73_728).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert "while" not in compiled.as_text()
    assert compiled.memory_analysis() is not None
