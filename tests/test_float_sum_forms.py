"""Every form a float64 per-group sum can take (PR 38), held to the one
promise they share: a group's sum meets only its own values, so a group of
one row of cents beside groups of millions is as exact as alone.

The rule (``ops.groupby._float_sum_form``) chooses from the backend and the
group count: ``dense`` up to ``_DENSE_SUM_GROUPS`` groups on an accelerator,
``segmented`` above it and under the binding ``sort`` hint, the plain
scatter-add (``None``) on a CPU backend.  Each is reached directly and
through ``partial_tables`` — by the ``sort`` seam on this CPU backend and by
the ``groupby_as_accelerator`` fixture, which lets the kernels read "tpu"
— on skewed data, against a float64 ``np.add.at``.  The structural test
runs the accelerator's form with a float32 accumulator: what it keeps and
the prefix difference of the whole table loses is the reason it exists.
One served-path test asks both shapes of the dollars configuration of an
in-process cluster at the tiny size and compares with the benchmark's
plain reference.
"""

import json
import logging
import os
import sys
import threading

import numpy as np
import pandas as pd
import pytest

from conftest import wait_until

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gb():
    import bqueryd_tpu.ops.groupby  # noqa: F401

    return sys.modules["bqueryd_tpu.ops.groupby"]


def skewed(n_groups, seed=38):
    """(codes, values, expect): every third group is ONE row of 1 to 99
    cents; the others hold thousands of rows of 10 000 to 1 000 000 dollars
    each (sums of 1e7 to 1e10), so in sorted order a one-row group sits
    between two groups a billion times its size.  Rows come shuffled, a
    few with a null key."""
    rng = np.random.default_rng(seed)
    big = np.arange(n_groups)[np.arange(n_groups) % 3 != 1]
    small = np.arange(n_groups)[np.arange(n_groups) % 3 == 1]
    rows_of_big = rng.integers(1, 60, len(big))
    rows_of_big[:5] = (3000, 2049, 1024, 1023, 5000)   # span the scan's blocks
    codes = np.concatenate([np.repeat(big, rows_of_big), small])
    values = np.concatenate([
        np.round(rng.uniform(1e4, 1e6, int(rows_of_big.sum())), 2),
        rng.integers(1, 100, len(small)) / 100.0,
    ])
    order = rng.permutation(len(codes))
    codes, values = codes[order].astype(np.int32), values[order]
    codes[rng.random(len(codes)) < 0.01] = -1
    kept = codes >= 0
    expect = np.zeros(n_groups)
    np.add.at(expect, codes[kept], values[kept])
    return codes, values, expect


def rel_error(got, expect):
    """Widest relative gap over the groups that have a sum; the others
    have to read exactly 0."""
    got = np.asarray(got, np.float64)
    has = expect != 0
    assert (got[~has] == 0).all()
    return float(np.max(np.abs(got[has] - expect[has]) / np.abs(expect[has])))


#: float64 on this backend: every form is a balanced or sequential sum of a
#: group's own values, at most 5 000 of them: well under 1e-12 of the sum
F64_RTOL = 1e-12


# -- each form, directly ---------------------------------------------------------

def sorted_inputs(codes, values, n_groups, dtype):
    """The rows sorted by group key as ``_SortedGroups`` sorts them (null
    keys past the last group), the values in ``dtype``, and the ends."""
    key = np.where(codes >= 0, codes, n_groups).astype(np.int32)
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    ends = np.searchsorted(key_s, np.arange(n_groups), side="right").astype(np.int32)
    return key_s, np.where(codes >= 0, values, 0.0)[order].astype(dtype), ends


@pytest.mark.parametrize("block", [128, 1024, 65536, None],
                         ids=["128", "1024", "65536", "the_constant"])
@pytest.mark.parametrize("n_groups", [40, 3000])
def test_the_segmented_scan_sums_each_group_alone(n_groups, block):
    """Whatever the block: groups inside a block, groups over many blocks,
    one-row groups, empty groups, the null keys' rows past the last end."""
    import jax
    import jax.numpy as jnp

    m = _gb()
    codes, values, expect = skewed(n_groups)
    key_s, v_s, ends = sorted_inputs(codes, values, n_groups, np.float64)
    got = jax.jit(m._segmented_sums, static_argnums=(3, 4))(
        jnp.asarray(key_s), jnp.asarray(v_s), jnp.asarray(ends), n_groups, block)
    assert got.dtype == jnp.float64 and got.shape == (n_groups,)
    assert rel_error(got, expect) < F64_RTOL


def test_the_segmented_scan_of_no_rows_and_of_one_group():
    import jax.numpy as jnp

    m = _gb()
    none = m._segmented_sums(
        jnp.full(2000, 5, jnp.int32), jnp.ones(2000), jnp.zeros(5, jnp.int32), 5)
    np.testing.assert_array_equal(np.asarray(none), np.zeros(5))
    # one group over every block and a pad: 2 500 rows of 0.1
    one = m._segmented_sums(
        jnp.zeros(2500, jnp.int32), jnp.full(2500, 0.1),
        jnp.array([2500, 2500, 2500], jnp.int32), 3)
    np.testing.assert_allclose(np.asarray(one), [250.0, 0.0, 0.0], rtol=1e-13)


@pytest.mark.parametrize("form", ["dense", "scatter_add"])
def test_the_other_two_forms_sum_each_group_alone(form):
    import jax
    import jax.numpy as jnp

    m = _gb()
    n_groups = 40 if form == "dense" else 3000
    codes, values, expect = skewed(n_groups)
    safe = jnp.asarray(np.where(codes >= 0, codes, 0).astype(np.int32))
    contrib = jnp.asarray(np.where(codes >= 0, values, 0.0))
    if form == "dense":
        got = jax.jit(m._dense_segment_sum, static_argnums=2)(contrib, safe, n_groups)
    else:
        got = jax.ops.segment_sum(contrib, safe, num_segments=n_groups)
    assert rel_error(got, expect) < F64_RTOL


# -- each form, through partial_tables -------------------------------------------

@pytest.mark.parametrize(
    "as_accelerator, strategy, n_groups, form, route",
    [
        pytest.param(False, "sort", 3000, "segmented", "sort", id="cpu_sort_seam"),
        pytest.param(False, "sort", 40, "segmented", "sort", id="cpu_sort_seam_few_groups"),
        pytest.param(False, None, 3000, None, "matmul", id="cpu_auto_scatter_add"),
        pytest.param(True, None, 3000, "segmented", "matmul", id="accelerator_auto_matmul_counts"),
        pytest.param(True, None, 9000, "segmented", "sort", id="accelerator_auto_sorted_counts"),
        pytest.param(True, "scatter", 3000, "segmented", "scatter", id="accelerator_scatter_own_sort"),
        pytest.param(True, None, 40, "dense", "matmul", id="accelerator_auto_dense"),
    ],
)
def test_every_form_the_rule_can_choose_through_partial_tables(
        request, as_accelerator, strategy, n_groups, form, route):
    """sum, mean and count of one float64 column, a filter on top: the
    form is what ``float_sum_route`` names, the route's counts are exact,
    and no group's sum is off by more than float64 rounding of its own
    values — the one-row groups of cents included."""
    import jax

    m = request.getfixturevalue("groupby_as_accelerator") if as_accelerator else _gb()
    codes, values, _all = skewed(n_groups)
    mask = np.random.default_rng(3).random(len(codes)) < 0.8
    values = values.copy()
    values[::97] = np.nan
    measures, ops = (values, values, values), ("sum", "mean", "count")
    assert m.kernel_route(strategy, measures, ops, len(codes), n_groups) == route
    assert m.float_sum_route(strategy, measures, ops, len(codes), n_groups) == form
    out = jax.device_get(m.partial_tables(
        codes, measures, ops, n_groups, mask=mask, strategy=strategy))
    kept = mask & (codes >= 0)
    present = kept & ~np.isnan(values)
    expect = np.zeros(n_groups)
    np.add.at(expect, codes[present], values[present])
    for agg in out["aggs"][:2]:
        assert rel_error(agg["sum"], expect) < F64_RTOL
    np.testing.assert_array_equal(
        np.asarray(out["rows"]), np.bincount(codes[kept], minlength=n_groups))
    for agg in out["aggs"][1:]:
        np.testing.assert_array_equal(
            np.asarray(agg["count"]), np.bincount(codes[present], minlength=n_groups))


def test_two_float_sums_of_the_matmul_route_share_one_sort(groupby_as_accelerator):
    """The MXU route sorts only for its segmented float sums, once a query."""
    import unittest.mock as mock

    import jax
    from jax import lax

    m = groupby_as_accelerator
    codes, values, expect = skewed(3000)
    with mock.patch.object(lax, "sort", wraps=lax.sort) as sort:
        out = jax.device_get(m.partial_tables(
            codes, (values, values * 2), ("sum", "mean"), 3000))
    assert sort.call_count == 1 and len(sort.call_args.args[0]) == 3
    assert rel_error(out["aggs"][0]["sum"], expect) < F64_RTOL
    assert rel_error(out["aggs"][1]["sum"], 2 * expect) < F64_RTOL


# -- the structural test: the same forms a precision down ------------------------

def test_in_float32_the_segmented_form_keeps_a_one_row_group_and_the_prefix_loses_it():
    """What the chip's emulated float64 (about 2^-47) does to dollars,
    shown where this backend can show it, a precision down: with a FLOAT32
    accumulator the segmented scan returns a one-row group's sum exactly
    (it met no other value) and every group's within 1e-6 (float32's 6e-8
    times the log of a group's rows), while the difference of a running
    float32 prefix of the whole table — ``_sorted_segment_sum``, the form
    an accelerator took above 2 048 groups until PR 38 — misses a group of
    cents by far more than its sum: the prefix is about 1e12 where the
    group sits, one float32 rounding of it about 6e4."""
    import jax.numpy as jnp

    m = _gb()
    n_groups = 3000
    codes, values, expect = skewed(n_groups)
    key_s, v32, ends = sorted_inputs(codes, values, n_groups, np.float32)
    # the reference sums the float32-rounded values in float64
    expect32 = np.zeros(n_groups)
    np.add.at(expect32, key_s[key_s < n_groups], v32[key_s < n_groups].astype(np.float64))
    segmented = np.asarray(m._segmented_sums(
        jnp.asarray(key_s), jnp.asarray(v32), jnp.asarray(ends), n_groups))
    assert segmented.dtype == np.float32
    one_row = (np.arange(n_groups) % 3 == 1) & (expect != 0)
    assert one_row.sum() > 900
    np.testing.assert_array_equal(segmented[one_row], expect32[one_row].astype(np.float32))
    assert rel_error(segmented, expect32) < 1e-6
    safe = np.where(codes >= 0, codes, 0).astype(np.int32)
    contrib = np.where(codes >= 0, values, 0.0).astype(np.float32)
    prefix_diff = np.asarray(m._sorted_segment_sum(
        jnp.asarray(contrib), jnp.asarray(safe), n_groups, acc_dtype=jnp.float32))
    gaps = np.abs(prefix_diff - expect32)[one_row] / expect32[one_row]
    assert np.median(gaps) > 1e-3 and gaps.max() > 1.0


# -- the served path, both shapes of the dollars configuration -------------------

@pytest.fixture(scope="module")
def dollars_cluster(tmp_path_factory):
    """Controller, one calc worker and a client as threads of this process
    over the tiny dollars configuration's ten shards."""
    sys.path.insert(0, REPO)
    from benchmark import data

    from bqueryd_tpu.controller import ControllerNode
    from bqueryd_tpu.rpc import RPC
    from bqueryd_tpu.worker import WorkerNode

    config = json.load(open(os.path.join(
        REPO, "tests", "benchmark", "taxi-tiny-dollars.json")))
    root = str(tmp_path_factory.mktemp("dollars"))
    seed = 3_380_000_777
    names = data.build_dataset(config, seed, root)
    url = f"mem://dollars-{os.urandom(4).hex()}"
    controller = ControllerNode(
        coordination_url=url, loglevel=logging.WARNING, runfile_dir=root,
        heartbeat_interval=0.2, dead_worker_timeout=10.0)
    worker = WorkerNode(
        coordination_url=url, data_dir=root, loglevel=logging.WARNING,
        restart_check=False, heartbeat_interval=0.2, poll_timeout=0.1)
    for node in (controller, worker):
        threading.Thread(target=node.go, daemon=True).start()
    wait_until(lambda: all(controller.files_map.get(n) for n in names),
               desc="the ten shards registered")
    rpc = RPC(coordination_url=url, timeout=120, loglevel=logging.WARNING)
    yield config, names, dict(zip(names, data.frames(config, seed))), rpc, worker
    controller.running = worker.running = False


@pytest.mark.parametrize("shape", ["zonepair_tips", "zonepax_tipmean"])
@pytest.mark.parametrize("as_accelerator", [False, True], ids=["cpu_forms", "accelerator_forms"])
def test_the_served_path_answers_both_dollar_shapes_like_the_reference(
        request, dollars_cluster, shape, as_accelerator):
    """``rpc.groupby`` -> controller -> the calc worker -> the mesh
    executor at deployment defaults, with a fresh ``trip_distance > N``:
    keys and counts bit for bit, every float64 sum and mean within 1e-7 of
    pandas per group (the configuration's limits; float64 here reads about
    1e-15) — by this backend's own forms, and by the accelerator's
    (``zonepair_tips`` by ``sort`` with the tips carried through its one
    sort, ``zonepax_tipmean`` by ``matmul`` with a sort for the tips)."""
    from benchmark import reference, traffic

    if as_accelerator:
        request.getfixturevalue("groupby_as_accelerator")
    config, names, frames, rpc, worker = dollars_cluster
    args = traffic.query_args(config, shape, names, 1.23455 if as_accelerator else 2.34565)
    got = rpc.groupby(*args)
    assert rpc.last_call_answer_source == "recompute"
    effective = set((rpc.last_call_strategies or {}).get("effective", {}).values())
    if as_accelerator:
        assert effective == {"sort" if shape == "zonepair_tips" else "matmul"}
        assert worker.mesh_executor.last_effective_strategy in effective
    numbers = reference.compare(
        args, got, reference.Reference(frames).answer(args), config["columns"])
    limits = config["guarantees"]["check_limits"]
    assert numbers["int_mismatch"] == 0 and numbers["unanswered"] == 0
    assert numbers["f64_mean_rel"] <= min(limits["f64_mean_rel"], 1e-12)
    assert len(got) > (2000 if shape == "zonepair_tips" else 300)
