"""The working set's policy (PR 30): each thing held once, at the width it
is used, in segments bounded by memory the worker measures.

* a deployment whose key sets' int64 dense codes would not fit the ``align``
  segment together, and whose narrow ones do, rebuilds nothing between
  queries: no ``align`` miss, no ``_pack``, no ``codes`` eviction — on
  ``execute``, ``execute_bundle`` and ``execute_dag``;
* the segments are sized from the memory sample (one device, four) and the
  worker's RSS limit, never from the environment;
* the four names that sized them are gone from the registry, the README's
  table, the package and ``deploy/``.
"""

import os
import types

import numpy as np
import pandas as pd
import pytest

from bqueryd_tpu import ops
from bqueryd_tpu.analysis.configreg import ENV_REGISTRY, registry_markdown_rows
from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.ops import workingset
from bqueryd_tpu.ops.workingset import SHARES, WorkingSet
from bqueryd_tpu.parallel.executor import (
    MeshQueryExecutor,
    _codes_dtype,
    make_mesh,
)
from bqueryd_tpu.plan import dag as dagmod
from bqueryd_tpu.storage import ctable

ROWS, N_DEV = 6000, 4
CUTS = (0, 1100, 4300, ROWS)
#: group counts of the two key sets: int8 and int16 codes
KEY_SETS = {"k": 9, "k2": 300}
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GONE = ("BQUERYD_TPU_ALIGN_CACHE_BYTES", "BQUERYD_TPU_CODES_CACHE_BYTES",
        "BQUERYD_TPU_HBM_CACHE_BYTES", "BQUERYD_TPU_HBM_EVICT_WATERMARK")


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    rng = np.random.default_rng(30)
    df = pd.DataFrame({
        "k": rng.integers(0, KEY_SETS["k"], ROWS).astype(np.int64),
        "k2": rng.integers(0, KEY_SETS["k2"], ROWS).astype(np.int64),
        "v": rng.integers(-50, 50, ROWS).astype(np.int64),
        "f32": (rng.random(ROWS) * 10).round(2).astype(np.float32),
    })
    df.loc[:KEY_SETS["k2"] - 1, "k2"] = np.arange(KEY_SETS["k2"])   # all seen
    base = tmp_path_factory.mktemp("policy")
    tables = []
    for i in range(len(CUTS) - 1):
        root = str(base / f"s{i}.bcolzs")
        ctable.fromdataframe(
            df.iloc[CUTS[i]:CUTS[i + 1]].reset_index(drop=True), root
        )
        tables.append(ctable(root, mode="r"))
    return df, tables


def answer(payload, key):
    keys, sums = payload["keys"][key], payload["aggs"][0]["sum"]
    order = np.argsort(keys)
    return keys[order].tolist(), sums[order].tolist()


def reference(df, key, constant):
    want = df[df["f32"] > np.float32(constant)].groupby(key)["v"].sum()
    return want.index.tolist(), want.tolist()


def solo(ex, df, tables, key, constant):
    payload = ex.execute(tables, GroupByQuery(
        [key], [["v", "sum", "s"]], where_terms=[["f32", ">", constant]]
    ))
    assert answer(payload, key) == reference(df, key, constant)


def bundle(ex, df, tables, key, constant):
    constants = (constant, constant + 0.01)
    payloads = ex.execute_bundle(tables, [
        GroupByQuery([key], [["v", "sum", "s"]],
                     where_terms=[["f32", ">", c]])
        for c in constants
    ])
    for payload, c in zip(payloads, constants):
        assert answer(payload, key) == reference(df, key, c)


def dag(ex, df, tables, key, constant):
    """A DAG's filter is part of what it derives, so a fresh constant is a
    fresh derivation by design; what alternates here is the key set, under
    one filter, with the measure's op taking turns."""
    op = ("sum", "count")[int(constant * 100) % 2]
    payload = dict(ex.execute_dag(tables, dagmod.compile_query({
        "table": ["x"], "groupby": [key], "where": [["f32", ">", 2.5]],
        "aggs": [["v", op, "s"]],
    })))
    kept = df[df["f32"] > np.float32(2.5)].groupby(key)["v"]
    want = kept.sum() if op == "sum" else kept.count()
    keys, got = payload["keys"][key], payload["aggs"][0][op]
    order = np.argsort(keys)
    assert keys[order].tolist() == want.index.tolist()
    assert got[order].tolist() == want.tolist()


def dag_derivation_bytes(ex):
    """What the DAG path keeps per table beside the dense codes (masks and
    per-key codes, as the per-shard route derives them)."""
    cache = ex._align_cache
    return sum(
        size for key, size in cache._sizes.items() if key[1] == "dagderive"
    )


@pytest.mark.parametrize("path", [solo, bundle, dag],
                         ids=["execute", "execute_bundle", "execute_dag"])
def test_two_key_sets_alternate_and_nothing_is_rebuilt(
        data, monkeypatch, path):
    df, tables = data
    ex = MeshQueryExecutor(mesh=make_mesh(N_DEV))
    narrow = sum(ROWS * _codes_dtype(g).itemsize for g in KEY_SETS.values())
    wide = len(KEY_SETS) * ROWS * 8
    # combos + dictionaries: 16 bytes a group
    small = 16 * sum(KEY_SETS.values())
    assert narrow + small < (narrow + wide) // 2 < wide
    ex._align_cache.max_bytes = (narrow + wide) // 2 + small
    width = int(ops.program_bucket(-(-ROWS // N_DEV), fine=True))
    # the two unmasked entries and not a byte more
    ex._codes_cache.max_bytes = sum(
        N_DEV * width * _codes_dtype(g).itemsize for g in KEY_SETS.values()
    )
    constants = iter(np.arange(0.5, 9.5, 0.13).round(2).tolist())
    for key in KEY_SETS:                      # the first pass of each
        path(ex, df, tables, key, next(constants))
    if path is dag:
        ex._align_cache.max_bytes += dag_derivation_bytes(ex)
        for key in KEY_SETS:                  # again, now that both may stay
            path(ex, df, tables, key, next(constants))

    packs = []
    pack = MeshQueryExecutor._pack

    def spy(arrays, n_devices, pad, dtype=None):
        packs.append(np.dtype(dtype) if dtype is not None else None)
        return pack(arrays, n_devices, pad, dtype=dtype)

    monkeypatch.setattr(MeshQueryExecutor, "_pack", staticmethod(spy))
    before = ex.workingset.stats()
    for _ in range(5):
        for key in KEY_SETS:
            path(ex, df, tables, key, next(constants))
    after = ex.workingset.stats()
    assert after["align"]["misses"] == before["align"]["misses"]
    for segment in ("align", "codes", "blocks"):
        assert after[segment]["evictions"] == before[segment]["evictions"]
        assert after[segment]["rejected"] == 0
        # the DAG's first passes ran before its derivations were counted in
        assert before[segment]["evictions"] == 0 or path is dag
    assert after["codes"]["entries"] == len(KEY_SETS)
    assert after["blocks"]["entries"] == before["blocks"]["entries"]
    # a bundle member's filter rides as a packed mask of its own, an input
    # of the kernel; codes and columns are packed by nobody
    assert [d for d in packs if d != np.bool_] == []
    assert (packs != []) == (path is bundle)


# -- sized by what is measured -------------------------------------------------

#: one v5e chip, as ``memory_stats()`` reports it
CHIP_LIMIT = 16_909_336_576


def sample(n_devices, in_use=0):
    return {"bytes_in_use": in_use, "peak_bytes_in_use": in_use,
            "bytes_limit": n_devices * CHIP_LIMIT}


@pytest.mark.parametrize("n_devices", [1, 4])
def test_segments_are_sized_from_the_sample_and_the_workers_limit(
        monkeypatch, n_devices):
    def no_environment(*args, **kwargs):
        raise AssertionError(f"the working set read the environment: {args}")

    # the module's own view of ``os``: the process's environment stays
    environ = type("Env", (), {
        "get": no_environment, "__getitem__": no_environment,
        "__contains__": no_environment,
    })()
    monkeypatch.setattr(workingset, "os", types.SimpleNamespace(
        environ=environ, sysconf=os.sysconf,
    ))
    host = 20_480 * 10**6
    ws = WorkingSet(host_limit_bytes=host)
    # no device has reported memory yet (CPU; a backend before its first
    # kernel call): the device arrays are host memory
    for name, share in SHARES.items():
        assert ws.segment(name).max_bytes == int(share * host)
    assert ws.evict_under_pressure(sample=sample(n_devices)) == 0
    limit = n_devices * CHIP_LIMIT
    assert ws.segment("blocks").max_bytes == int(SHARES["blocks"] * limit)
    assert ws.segment("codes").max_bytes == int(SHARES["codes"] * limit)
    assert ws.segment("align").max_bytes == int(SHARES["align"] * host)
    # full segments still leave a program its scratch under the watermark
    assert SHARES["blocks"] + SHARES["codes"] < workingset.EVICT_WATERMARK < 1


def test_a_budget_given_by_name_holds_whatever_is_measured():
    ws = WorkingSet(budgets={"codes": 1 << 14}, host_limit_bytes=1 << 30)
    ws.evict_under_pressure(sample=sample(4))
    assert ws.segment("codes").max_bytes == 1 << 14
    assert ws.segment("blocks").max_bytes == int(
        SHARES["blocks"] * 4 * CHIP_LIMIT
    )
    assert ws.segment("align").max_bytes == int(SHARES["align"] * (1 << 30))


def test_a_sample_over_the_watermark_sheds_blocks_first():
    ws = WorkingSet(host_limit_bytes=1 << 30)
    for name in ("blocks", "codes"):
        for i in range(4):
            ws.segment(name).put((name, i), b"", nbytes=100)
    limit = 4 * CHIP_LIMIT
    over = int(workingset.EVICT_WATERMARK * limit) + 250
    assert ws.evict_under_pressure(sample=sample(4, in_use=over)) == 300
    assert len(ws.segment("blocks")) == 1 and len(ws.segment("codes")) == 4
    assert ws.stats()["pressure_evictions"] == 3


def test_the_worker_hands_its_rss_limit_to_the_executor(tmp_path):
    import logging

    from bqueryd_tpu.worker import WorkerNode

    worker = WorkerNode(
        coordination_url=f"mem://policy-{os.urandom(4).hex()}",
        data_dir=str(tmp_path), loglevel=logging.WARNING,
        restart_check=False, memory_limit_mb=4096,
    )
    align = worker.mesh_executor.workingset.segment("align")
    assert align.max_bytes == int(SHARES["align"] * 4096 * 10**6)


# -- the four names ---------------------------------------------------------------

@pytest.mark.parametrize("name", GONE)
def test_the_name_is_gone(name):
    assert name not in ENV_REGISTRY
    assert not any(name in row for row in registry_markdown_rows())
    holders = []
    for root in ("bqueryd_tpu", "deploy", "README.md"):
        path = os.path.join(REPO, root)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _dirs, fs in os.walk(path) for f in fs
            if not f.endswith((".pyc", ".so"))
        ]
        for file in files:
            with open(file, errors="replace") as fh:
                if name in fh.read():
                    holders.append(os.path.relpath(file, REPO))
    assert holders == []
