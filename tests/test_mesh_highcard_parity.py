"""The kernel and the merge that ``taxi-4chip.adhoc-heavy`` runs, tied
together at a small size on four virtual devices: a ``highcard``-shaped
query (two keys, more than 8 192 groups, an int64 sum) under a binding
``sort`` (PR 33's carried-payload sort, ``ops.groupby._SortedGroups``, under
``partial_tables_bucketized``) and under a binding ``scatter``, merged on
the devices (``MODE_DEVICE``: all-gather over the ``shards`` axis, own key
span) and on the host (``MODE_HOST``), equals a pandas groupby over the
same seeded frames bit for bit; and the per-device partial tables of
``MODE_HOST`` add up to the whole.
"""

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.ops import groupby as gb
from bqueryd_tpu.parallel import executor as executor_mod
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor, make_mesh
from bqueryd_tpu.storage.ctable import ctable

DEVICES = 4
SHARDS = 10          # uneven over four devices, as 40 files over a 2 x 2 mesh
ZONES = 120          # 14 400 key pairs: past the MXU route's 8 192 groups
KEYS = ["pu", "do"]
merge_payloads = hostmerge.merge_payloads   # the executor's is spied on below


@pytest.fixture(scope="module")
def frames(tmp_path_factory):
    rng = np.random.default_rng(34)
    base = tmp_path_factory.mktemp("highcard4")
    shards, tables = [], []
    for i in range(SHARDS):
        n = 2_000 + 37 * i
        frame = pd.DataFrame({
            "pu": rng.integers(1, ZONES + 1, n).astype(np.int64),
            "do": rng.integers(1, ZONES + 1, n).astype(np.int64),
            # int64 cents, a few of them wide enough that a group's sum
            # carries between the 16-bit limbs and the two 32-bit halves
            "fare": np.where(rng.random(n) < 0.02,
                             rng.integers(-(2**58), 2**58, n),
                             rng.integers(250, 50_000, n)).astype(np.int64),
            "dist": (rng.random(n) * 5).astype(np.float32),
        })
        root = str(base / f"hc{i}.bcolzs")
        ctable.fromdataframe(frame, root)
        shards.append(frame)
        tables.append(ctable(root))
    return pd.concat(shards, ignore_index=True), tables


def as_frame(payload):
    frame = hostmerge.payload_to_dataframe(merge_payloads([payload]))
    return frame.sort_values(KEYS).reset_index(drop=True)


@pytest.mark.parametrize("mode", ["device", "host"])
@pytest.mark.parametrize("route", ["sort", "scatter"])
def test_highcard_over_four_devices_equals_pandas_bit_for_bit(
        frames, monkeypatch, route, mode):
    whole, tables = frames
    monkeypatch.setenv("BQUERYD_TPU_DEVICE_MERGE", "1" if mode == "device" else "0")
    per_device = []

    def spy(payloads):
        per_device.append(list(payloads))
        return merge_payloads(payloads)

    monkeypatch.setattr(hostmerge, "merge_payloads", spy)
    sorts = []
    sorted_groups = gb._SortedGroups.__init__

    def noting(self, *args, **kwargs):
        sorts.append(1)
        return sorted_groups(self, *args, **kwargs)

    monkeypatch.setattr(gb._SortedGroups, "__init__", noting)
    # the trace is this test's: no program of an earlier case answers
    executor_mod._mesh_program.cache_clear()
    gb._partial_tables_scatter.__wrapped__.clear_cache()
    query = GroupByQuery(KEYS, [["fare", "sum", "fare"]],
                         [["dist", ">", 1.25]], aggregate=True)
    executor = MeshQueryExecutor(mesh=make_mesh(DEVICES))
    payload = executor.execute(tables, query, strategy=route)

    assert executor.last_merge_mode == mode
    assert executor.last_effective_strategy == route
    assert bool(sorts) == (route == "sort")   # the sorted form traced, or not
    got = as_frame(payload)
    kept = whole[whole["dist"] > np.float32(1.25)]
    expect = kept.groupby(KEYS, as_index=False)["fare"].sum()
    expect = expect.sort_values(KEYS).reset_index(drop=True)
    assert len(expect) > 8192
    assert got["fare"].dtype == np.int64
    for column in KEYS + ["fare"]:
        np.testing.assert_array_equal(got[column].to_numpy(), expect[column].to_numpy())

    if mode == "device":
        assert per_device == []   # nothing merged on the host
        return
    # host mode: one partial table a device, and they add up to the whole
    (payloads,) = per_device
    assert len(payloads) == DEVICES
    parts = pd.concat([as_frame(p) for p in payloads], ignore_index=True)
    assert len(parts) > len(expect)   # a pair's rows lie on several devices
    summed = parts.groupby(KEYS, as_index=False)["fare"].sum()
    summed = summed.sort_values(KEYS).reset_index(drop=True)
    for column in KEYS + ["fare"]:
        np.testing.assert_array_equal(summed[column].to_numpy(), expect[column].to_numpy())
