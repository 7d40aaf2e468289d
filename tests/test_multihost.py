"""Multi-host execution path, simulated with two OS processes on CPU.

The mesh executor claims to scale to multi-host pods (``maybe_init_distributed``
+ the ``make_array_from_callback`` placement in ``executor._put``) the way the
reference scales by adding worker boxes (reference misc/supervisor.conf:19-20,
README.md:125).  Until a real pod exists, this is the executable evidence:
two ``jax.distributed``-joined CPU processes (4 virtual devices each → one
8-device global mesh) run the same groupby through MeshQueryExecutor and must
both produce the psum-merged global answer, bit-exact vs pandas.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

_WORKER_SCRIPT = r"""
import json, os, sys
proc_id, data_dir, out_path = int(sys.argv[1]), sys.argv[2], sys.argv[3]

from bqueryd_tpu import ops
assert ops.maybe_init_distributed() is True, "distributed init did not run"
import jax
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, jax.devices()

from bqueryd_tpu.models.query import GroupByQuery
from bqueryd_tpu.parallel import hostmerge
from bqueryd_tpu.parallel.executor import MeshQueryExecutor
from bqueryd_tpu.storage.ctable import ctable

names = sorted(n for n in os.listdir(data_dir) if n.endswith(".bcolzs"))
tables = [ctable(os.path.join(data_dir, n)) for n in names]
query = GroupByQuery(["g"], [["v", "sum", "s"]], [], aggregate=True)
executor = MeshQueryExecutor()
payload = executor.execute(tables, query)
df = hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload]))
df = df.sort_values("g").reset_index(drop=True)
with open(f"{out_path}.{proc_id}", "w") as f:
    json.dump({"g": df["g"].tolist(), "s": df["s"].tolist()}, f)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_mesh_psum_merge(tmp_path):
    # bounded by the communicate(timeout=240) below
    from bqueryd_tpu.storage.ctable import ctable

    rng = np.random.default_rng(9)
    frames = []
    for i in range(4):
        df = pd.DataFrame(
            {
                "g": rng.integers(0, 11, 5_000).astype(np.int64),
                "v": rng.integers(-(2**50), 2**50, 5_000).astype(np.int64),
            }
        )
        frames.append(df)
        ctable.fromdataframe(df, str(tmp_path / f"shard_{i}.bcolzs"))
    expect = (
        pd.concat(frames).groupby("g")["v"].sum().sort_index()
    )

    script = tmp_path / "worker.py"
    script.write_text(_WORKER_SCRIPT)
    out_path = str(tmp_path / "result.json")
    port = _free_port()
    import bqueryd_tpu

    pkg_root = os.path.dirname(os.path.dirname(bqueryd_tpu.__file__))
    env = dict(os.environ)
    env.update(
        {
            # the worker script lives in tmp_path, so the package root must
            # be importable explicitly — python puts the script's directory
            # on sys.path, not the parent's cwd
            "PYTHONPATH": os.pathsep.join(
                p for p in (pkg_root, env.get("PYTHONPATH")) if p
            ),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "BQUERYD_TPU_DIST_COORDINATOR": f"127.0.0.1:{port}",
            "BQUERYD_TPU_DIST_NPROCS": "2",
        }
    )
    procs = []
    for proc_id in (0, 1):
        penv = dict(env, BQUERYD_TPU_DIST_PROC_ID=str(proc_id))
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), str(proc_id), str(tmp_path),
                 out_path],
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
        )
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outputs.append(out.decode(errors="replace"))
    finally:
        for p in procs:  # a hung barrier must not leak into later tests
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outputs):
        assert p.returncode == 0, f"worker process failed:\n{out}"

    for proc_id in (0, 1):
        with open(f"{out_path}.{proc_id}") as f:
            got = json.load(f)
        assert got["g"] == expect.index.tolist()
        assert got["s"] == expect.tolist()
