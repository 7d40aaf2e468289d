"""chip_smoke.py's phases on the CPU backend.

The smoke itself demands the chip; its phases are importable, so tier-1
drives the cluster phase — controller + one worker as OS processes, a
JAX-free client — at 200 k rows on CPU, and checks that each thing the
smoke exists to catch (a degraded path, a host or cached answer, a child
that exits, a worker that will not let go) fails it.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: the worker's environment for a CPU run.  tests/conftest.py already pins
#: the CPU platform with 8 virtual devices, the MXU route on, host routing
#: off and the serving layer off, and children inherit it; the compile
#: cache is pinned OFF there, so the restart phase turns it back on at a
#: directory placed from outside (never the checkout's .jax_cache).
def cpu_worker_env(tmp_path):
    return {
        "BQUERYD_TPU_COMPILE_CACHE": "1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "compile_cache"),
    }


@pytest.fixture
def cluster(tmp_path):
    made = []

    def make(worker_env=None):
        workdir = tmp_path / f"cluster{len(made)}"
        workdir.mkdir()
        made.append(chip_smoke.Cluster(
            str(workdir),
            worker_env={**cpu_worker_env(tmp_path), **(worker_env or {})},
        ))
        return made[-1]

    yield make
    for c in made:
        c.stop()


def test_cluster_phase_on_cpu_with_a_jax_free_client(tmp_path):
    """The whole cluster phase — first line, 200 k rows in 10 shards, six
    queries against pandas, device-route and degrade checks, worker restart
    with a persistent-cache hit — in a child whose process (the smoke's
    parent + RPC client) must never import jax."""
    code = (
        "import json, sys, pandas as pd\n"
        "pd.set_option('future.infer_string', False)\n"
        "import chip_smoke\n"
        f"cluster = chip_smoke.Cluster({str(tmp_path / 'wd')!r},\n"
        f"    worker_env={cpu_worker_env(tmp_path)!r})\n"
        "try:\n"
        "    summary = chip_smoke.cluster_phase(\n"
        "        cluster, rows=200_000, expect_platform='cpu',\n"
        "        warm_repeats=1)\n"
        "finally:\n"
        "    cluster.stop()\n"
        "assert 'jax' not in sys.modules, 'the client imported jax'\n"
        "print(json.dumps(summary))\n"
    )
    (tmp_path / "wd").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(os.environ, PYTHONPATH=REPO), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("platform=cpu device_kind=cpu devices=8 jax=")
    assert "jaxlib=" in lines[0] and "libtpu=" in lines[0]
    summary = json.loads(lines[-1])
    assert summary["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": 8,
    }
    assert set(summary["queries"]) == set(chip_smoke.EXPECTED_ROUTES)
    for name in ("single", "sharded", "multikey", "filtered"):
        assert summary["queries"][name]["route"] == "matmul"
    restart = [l for l in lines if l.startswith("restart ")][0]
    assert f"compile_cache={tmp_path / 'compile_cache'}" in restart
    # the worker released everything it started
    assert not [l for l in lines if "outlived" in l]


def test_host_route_fails_the_smoke(cluster):
    """A worker that answers from the NumPy host kernels (here: forced by
    the host-routing threshold) is a wrong device, not a right answer."""
    c = cluster({"BQUERYD_TPU_HOST_KERNEL_ROWS": str(10**9)})
    c.start_controller()
    c.start_worker()
    rpc = chip_smoke.connect(c)
    names, frames = chip_smoke.build_dataset(c.data_dir, rows=20_000, shards=2)
    chip_smoke.wait_registered(rpc, c, names)
    with pytest.raises(chip_smoke.SmokeFailure, match=r"route\(s\) \['host'\]"):
        chip_smoke.run_queries(
            rpc, c, names, frames, queries=("sharded",), warm_repeats=0
        )


def test_worker_that_cannot_take_its_backend_fails_the_smoke(cluster):
    """A calc worker whose backend cannot be initialised exits non-zero
    (worker.warmup) instead of advertising shards it would serve from
    NumPy; the smoke notices the dead child before any data is built."""
    c = cluster({"JAX_PLATFORMS": "nochip"})
    with pytest.raises(
        chip_smoke.SmokeFailure, match="worker-1 exited with status 1"
    ) as failure:
        chip_smoke.cluster_phase(c, rows=20_000, shards=2)
    assert "could not be initialised" in str(failure.value)
    assert not os.listdir(c.data_dir), "data was built for a dead worker"


def test_worker_outliving_its_stop_fails_the_smoke(cluster, monkeypatch):
    """A stopped worker still alive at the deadline holds the chip: the
    smoke kills its process group and fails."""
    monkeypatch.setattr(chip_smoke, "STOP_DEADLINE_S", 0.5)
    c = cluster()
    with open(os.devnull, "wb") as sink:
        stubborn = subprocess.Popen(
            [sys.executable, "-c",
             "import signal, time\n"
             "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
             "print('up', flush=True)\n"
             "time.sleep(120)\n"],
            stdout=subprocess.PIPE, stderr=sink, start_new_session=True,
        )
    assert stubborn.stdout.readline() == b"up\n"  # handler installed
    c._starts = 1
    c.procs["worker-1"] = stubborn
    with pytest.raises(chip_smoke.SmokeFailure, match="outlived its stop"):
        c.stop_worker()
    assert stubborn.poll() is not None, "the stubborn worker was not reaped"


def _snapshot(**over):
    snap = {
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "count": 2, "memory": [
                       {"device": 0, "bytes_in_use": 1 << 20},
                       {"device": 1, "bytes_in_use": 1 << 20}]},
        "device_health": {"wedged": 0, "abandoned_probes": 0,
                          "wedge_generation": 0},
        "degrades": dict.fromkeys(
            ("mesh_to_engine", "dag_to_pershard", "bundle_to_members",
             "inplace_retry", "packed_to_perleaf", "packed_latched"), 0),
    }
    snap.update(over)
    return snap


def test_worker_report_checks():
    """What the worker's own report must say, case by case."""
    chip_smoke.check_worker_state(_snapshot(), "tpu")
    assert "dev1=" in chip_smoke.check_devices_used(_snapshot())
    with pytest.raises(chip_smoke.SmokeFailure, match="platform='tpu'"):
        chip_smoke.check_worker_state(_snapshot(), "cpu")
    fired = _snapshot()
    fired["degrades"]["mesh_to_engine"] = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="mesh_to_engine"):
        chip_smoke.check_worker_state(fired, "tpu")
    with pytest.raises(chip_smoke.SmokeFailure, match="wedged 1 time"):
        chip_smoke.check_worker_state(
            _snapshot(device_health={"wedged": 0, "wedge_generation": 1}),
            "tpu",
        )
    idle = _snapshot()
    idle["device"]["memory"][1]["bytes_in_use"] = 0
    with pytest.raises(chip_smoke.SmokeFailure, match=r"devices \[1\] hold"):
        chip_smoke.check_devices_used(idle)
    silent = _snapshot()
    silent["device"]["memory"].pop()
    with pytest.raises(chip_smoke.SmokeFailure, match="1 of 2 devices"):
        chip_smoke.check_devices_used(silent)


def test_last_line_is_exactly_the_chip_checks_object(monkeypatch, capsys):
    """``main()`` ends stdout with ``{"ok", "device": {"platform", "kind",
    "count"}}`` and nothing more; the observations ride the ``summary``
    line above it, which ends with ``"claim": null``."""
    device = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "build_native", lambda: "codec")
    monkeypatch.setattr(chip_smoke, "run_pallas_child", lambda: None)
    monkeypatch.setattr(
        chip_smoke, "cluster_phase",
        lambda cluster: {"device": device, "rows": 1, "queries": {}},
    )
    # main() installs a SIGTERM handler and pins a pandas option: neither
    # may leak into this test process; nor does a jax another test imported
    # count against the smoke's parent
    monkeypatch.setattr(chip_smoke.signal, "signal", lambda *a: None)
    monkeypatch.setattr("pandas.set_option", lambda *a: None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert lines[-2].startswith("summary {")
    assert lines[-2].endswith('"claim": null}')


def _reply(effective, merge="device"):
    return types.SimpleNamespace(
        last_call_strategies={
            "hints": {"auto": 1},
            "effective": {"taxi_0.bcolzs+9more": effective},
        },
        last_call_merge_modes={"taxi_0.bcolzs+9more": merge},
    )


def test_reply_checks():
    """A reply must name a device route and the device merge."""
    assert chip_smoke.check_reply("sharded", _reply("matmul")) == "matmul"
    assert chip_smoke.check_reply("highcard", _reply("sort")) == "sort"
    for route in ("cached", "host", "delta"):
        with pytest.raises(chip_smoke.SmokeFailure, match=route):
            chip_smoke.check_reply("highcard", _reply(route))
    # "scatter" where the MXU route is the default means the backend was
    # misread
    with pytest.raises(chip_smoke.SmokeFailure, match="scatter"):
        chip_smoke.check_reply("sharded", _reply("scatter"))
    with pytest.raises(chip_smoke.SmokeFailure, match="merge mode"):
        chip_smoke.check_reply("sharded", _reply("matmul", merge="host"))


def test_answers_are_held_to_pandas():
    """int64 aggregates bit for bit; float means by relative error."""
    frames = [chip_smoke.shard_frame(7, i, 500) for i in range(2)]
    for query in chip_smoke.EXPECTED_ROUTES:
        expected = chip_smoke.reference_answer(query, frames)
        assert chip_smoke.compare_answer(query, expected, expected) == 0.0
    expected = chip_smoke.reference_answer("sharded", frames)
    off_by_one = expected.copy()
    off_by_one.loc[0, "fare_amount"] += 1
    with pytest.raises(chip_smoke.SmokeFailure, match="not bit-exact"):
        chip_smoke.compare_answer("sharded", off_by_one, expected)
    expected = chip_smoke.reference_answer("f64mean", frames)
    drifted = expected.copy()
    drifted["tip_mean"] *= 1 + 1e-8
    with pytest.raises(chip_smoke.SmokeFailure, match="tip_mean"):
        chip_smoke.compare_answer("f64mean", drifted, expected)


def test_pallas_phase_under_the_interpreter():
    """The Pallas child's checks hold (interpret mode: Mosaic is the chip's)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.pallas_phase(40_000, True)"],
        env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert [l.split()[1] for l in lines] == [
        "kernel=onehot_rows_dot", "kernel=onehot_rows_dot_hicard",
    ]
    assert all("matches_numpy=True" in l for l in lines)
