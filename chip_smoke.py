#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Run from the root of a checkout, with no arguments, on a machine with a TPU::

    python chip_smoke.py

It does what a user of the system does, at the upstream's documented size
(BASELINE.md: NYC-taxi, ~10 M rows, 10 shard files):

1. builds ``native/build/libtpucolz.so`` from ``native/tpucolz.cpp`` (what
   runs is built from what git commits) and says which codec served;
2. starts ``python -m bqueryd_tpu.node controller`` and ONE
   ``python -m bqueryd_tpu.node worker`` as OS processes (``file://``
   coordination in a fresh directory), the worker with ``JAX_PLATFORMS=tpu``
   so JAX raises instead of falling back when it cannot take the chip.  One
   process holds the chip; this parent, the controller and the client never
   import ``jax``;
3. writes 10 000 000 seeded rows in 10 ``.bcolzs`` shards (parallel JAX-free
   processes) and moves them into the worker's data directory;
4. through ``bqueryd_tpu.RPC`` runs the five BASELINE queries and a sixth
   over a float64 column, each once cold and five times warm, and compares
   every answer with a plain pandas groupby over the same frames: int64
   aggregates bit for bit, float means within 1e-6 relative (f64: 1e-9);
5. asserts the DEVICE did the work, from what the reply and the worker
   report: the kernel route, ``merge_mode == "device"``, no wedge, every
   degrade counter 0, platform ``tpu``, every local device holding data;
6. stops the worker (gone within 10 s per chip or the run fails), starts a
   fresh one and repeats ``sharded``: same answer, persistent compile cache hit;
7. with the chip free again, compiles both Pallas kernels with Mosaic
   (``interpret=False``) at 10 M rows in one child and checks them against
   NumPy.

Any failed phase, any child's non-zero exit, any assertion: exit status
non-zero and no result line.  On success the last two lines of stdout are
``summary {..., "claim": null}`` (what the run observed, as JSON) and the one
JSON object the chip check reads, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Walls are printed as observations, each with the device they were taken on;
nothing here is a speed-up claim.

The phases are importable: ``tests/test_chip_smoke.py`` drives the cluster
phase at 200 k rows on the CPU backend.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

ROWS = 10_000_000
SHARDS = 10
SEED = 20160101          # the reference dataset: NYC yellow taxi 2016-01
WARM_REPEATS = 5
#: a stopped worker must be gone within this, per chip it held: what the
#: TPU runtime unmaps on exit grows with its chips (13.8 GB of RSS on one,
#: 40.6 GB on four) — observed 3.9-4.8 s on one chip, 6.6 s and > 10 s on four
STOP_DEADLINE_S = 10.0
RPC_TIMEOUT_S = 900      # one call; the f64 sort path compiles for minutes
HICARD_GROUPS = 70_225   # PULocationID x DOLocationID (265 x 265)
SLICE_SETTLE_S = 25.0    # > the worker's slowest heartbeat (20 s main loop)

#: kernel routes that ran a device program (ops.kernel_route); anything else
#: in a reply's ``effective`` — "host", "cached", "delta" — did not
DEVICE_ROUTES = frozenset({"matmul", "scatter", "sort"})
#: query -> the route its reply must name.  The MXU limb matmul is the TPU
#: default for these four ("scatter" there means the backend was misread);
#: 70 225 groups and float64 sums take another device route
EXPECTED_ROUTES = {
    "single": {"matmul"},
    "sharded": {"matmul"},
    "multikey": {"matmul"},
    "filtered": {"matmul"},
    "highcard": DEVICE_ROUTES,
    "f64mean": DEVICE_ROUTES,
}


class SmokeFailure(AssertionError):
    """A phase of the smoke did not hold; the run exits non-zero."""


def check(condition, message):
    # not ``assert``: the checks must survive ``python -O``
    if not condition:
        raise SmokeFailure(message)


def log(message):
    """Progress goes to stderr; stdout carries only result lines."""
    print(f"[chip_smoke] {message}", file=sys.stderr, flush=True)


def out(line):
    print(line, flush=True)


def device_tag(device):
    return f"{device['platform']}/{device['device_kind']}/{device['count']}"


def child_env(**extra):
    """Every child's environment: this checkout first on the import path."""
    return dict(
        os.environ,
        PYTHONPATH=os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH")) if p
        ),
        **extra,
    )


# -- 1. native codec ---------------------------------------------------------

def build_native():
    """Rebuild libtpucolz from source (a ``.so`` lying in the tree is not
    what git commits) and load it; returns the name of the serving codec."""
    from bqueryd_tpu.storage import native

    shutil.rmtree(os.path.join(REPO, "native", "build"), ignore_errors=True)
    native.build(check=True)
    check(native.available(), "libtpucolz.so was built but does not load")
    return "native-lz4 (libtpucolz, built from native/tpucolz.cpp)"


# -- 2. dataset ---------------------------------------------------------------

def shard_name(index):
    return f"taxi_{index}.bcolzs"


def shard_frame(seed, index, rows):
    """One shard's rows: the schema and value ranges of bench.py's
    ``build_dataset`` plus one float64 column (``tip_amount``), drawn from
    ``(seed, index)`` so any process regenerates the same frame."""
    import numpy as np
    import pandas as pd

    rng = np.random.RandomState([seed, index])
    return pd.DataFrame(
        {
            "passenger_count": rng.randint(1, 10, rows).astype(np.int64),
            # integer cents: int64 end to end, the bit-exactness axis
            "fare_amount": rng.randint(250, 20000, rows).astype(np.int64),
            "VendorID": rng.randint(1, 3, rows).astype(np.int64),
            "payment_type": rng.randint(1, 6, rows).astype(np.int64),
            "PULocationID": rng.randint(1, 266, rows).astype(np.int64),
            "DOLocationID": rng.randint(1, 266, rows).astype(np.int64),
            "trip_distance": (rng.random(rows) * 30).astype(np.float32),
            "pickup_ts": (
                np.int64(1_700_000_000_000_000_000)
                + rng.randint(0, 86_400, rows).astype(np.int64)
                * np.int64(1_000_000_000)
            ).view("datetime64[ns]"),
            # dollars as float64: puts the f64 path on the menu
            "tip_amount": np.round(rng.random(rows) * 20.0, 2),
        }
    )


def shard_rows(rows, shards, index):
    per = rows // shards
    return per + (rows % shards if index == shards - 1 else 0)


def _write_shard(job):
    """Pool worker (a fresh JAX-free process): write one shard."""
    seed, index, rows, rootdir = job
    from bqueryd_tpu.storage import native
    from bqueryd_tpu.storage.ctable import ctable

    ctable.fromdataframe(shard_frame(seed, index, rows), rootdir)
    check("jax" not in sys.modules, "a shard writer imported jax")
    return native.available()


def build_dataset(data_dir, rows=ROWS, shards=SHARDS, seed=SEED):
    """Write ``shards`` shard directories in parallel JAX-free processes and
    move each into ``data_dir`` whole (a worker scanning the directory never
    sees a half-written shard).  Returns ``(names, frames)``: the frames are
    regenerated here from the same seed, for the pandas reference."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    staging = os.path.join(data_dir, ".staging")
    os.makedirs(staging)
    names = [shard_name(i) for i in range(shards)]
    jobs = [
        (seed, i, shard_rows(rows, shards, i), os.path.join(staging, name))
        for i, name in enumerate(names)
    ]
    workers = max(1, min(shards, (os.cpu_count() or 2) - 1))
    with ProcessPoolExecutor(
        max_workers=workers,
        mp_context=multiprocessing.get_context("spawn"),
    ) as pool:
        native_served = list(pool.map(_write_shard, jobs))
    check(all(native_served), "a shard was written without the native codec")
    for name in names:
        os.rename(os.path.join(staging, name), os.path.join(data_dir, name))
    os.rmdir(staging)
    frames = [
        shard_frame(seed, i, shard_rows(rows, shards, i))
        for i in range(shards)
    ]
    return names, frames


# -- 3. queries and their plain reference --------------------------------------

def query_args(query, names):
    """(filenames, groupby_cols, agg_list, where_terms): the five BASELINE
    queries of bench.py's ``config_query`` and the float64 sixth."""
    fare_sum = [["fare_amount", "sum", "fare_amount"]]
    return {
        "single": (names[:1], ["passenger_count"], fare_sum, []),
        "sharded": (names, ["passenger_count"], fare_sum, []),
        "multikey": (
            names,
            ["VendorID", "payment_type"],
            [
                ["fare_amount", "sum", "fare_sum"],
                ["fare_amount", "count", "n"],
                ["trip_distance", "mean", "dist_mean"],
            ],
            [],
        ),
        "filtered": (
            names, ["passenger_count"], fare_sum,
            [["trip_distance", ">", 5.0]],
        ),
        "highcard": (names, ["PULocationID", "DOLocationID"], fare_sum, []),
        "f64mean": (
            names, ["passenger_count"],
            [["tip_amount", "mean", "tip_mean"]], [],
        ),
    }[query]


def reference_answer(query, frames):
    """The same query as a plain pandas groupby over the same frames."""
    import pandas as pd

    _files, gcols, aggs, where = query_args(query, list(range(len(frames))))
    df = pd.concat(frames[:1] if query == "single" else frames)
    for col, op, value in where:
        check(op == ">", f"reference has no operator {op!r}")
        df = df[df[col] > value]
    named = {out_col: (in_col, op) for in_col, op, out_col in aggs}
    return df.groupby(gcols, as_index=False).agg(**named)


def compare_answer(query, got, expected):
    """int64 aggregates bit for bit, float means within 1e-6 relative
    (float64 inputs: 1e-9).  Returns the worst relative float error seen."""
    import numpy as np

    _files, gcols, aggs, _where = query_args(query, [])
    check(
        len(got) == len(expected),
        f"{query}: {len(got)} groups, reference has {len(expected)}",
    )
    got = got.sort_values(gcols).reset_index(drop=True)
    expected = expected.sort_values(gcols).reset_index(drop=True)
    worst = 0.0
    for col in gcols + [out_col for _in, _op, out_col in aggs]:
        g, e = got[col].to_numpy(), expected[col].to_numpy()
        if np.issubdtype(e.dtype, np.integer):
            check(
                np.issubdtype(g.dtype, np.integer) and np.array_equal(g, e),
                f"{query}: int column {col!r} is not bit-exact",
            )
            continue
        check(
            bool(np.isfinite(g).all()), f"{query}: {col!r} is not finite"
        )
        rel = float(np.max(np.abs(g - e) / np.maximum(np.abs(e), 1e-300)))
        bound = 1e-9 if query == "f64mean" else 1e-6
        check(
            rel <= bound,
            f"{query}: float column {col!r} off by {rel:.3e} (> {bound})",
        )
        worst = max(worst, rel)
    return worst


# -- 4. the cluster: controller + one chip-owning worker, as OS processes ------

class Cluster:
    """A controller and one calc worker as child processes, started through
    the CLI a supervisor would use, each in its own process group so every
    exit path can reap it."""

    def __init__(self, workdir, worker_env):
        self.workdir = workdir
        self.data_dir = os.path.join(workdir, "data")
        self.log_dir = os.path.join(workdir, "logs")
        for path in (self.data_dir, self.log_dir,
                     os.path.join(workdir, "run")):
            os.makedirs(path)
        self.url = "file://" + os.path.join(workdir, "coordination")
        self.env = child_env(
            BQUERYD_TPU_IP="127.0.0.1",
            BQUERYD_TPU_RUNFILE_DIR=os.path.join(workdir, "run"),
            # repeats must run the device program, not a cache: the worker's
            # result cache and the controller's serving layer are off
            # (bench.py start_cluster does the same)
            BQUERYD_TPU_RESULT_CACHE_BYTES="0",
            BQUERYD_TPU_SERVE="0",
        )
        self.worker_env = dict(worker_env)
        self.procs = {}       # name -> Popen
        self._starts = 0
        self.devices = 1      # chips the worker holds, once it has said so

    def _spawn(self, name, role_args, extra_env=()):
        log_path = os.path.join(self.log_dir, f"{name}.log")
        with open(log_path, "ab") as log_file:
            proc = subprocess.Popen(
                [sys.executable, "-m", "bqueryd_tpu.node", *role_args,
                 f"--coordination={self.url}"],
                cwd=self.workdir,
                env=dict(self.env, **dict(extra_env)),
                stdout=log_file,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        proc.log_path = log_path
        self.procs[name] = proc
        log(f"started {name} (pid {proc.pid}), log {log_path}")
        return proc

    def start_controller(self):
        return self._spawn("controller", ["controller"])

    def start_worker(self):
        self._starts += 1
        return self._spawn(
            f"worker-{self._starts}",
            ["worker", f"--data_dir={self.data_dir}"],
            self.worker_env,
        )

    def worker(self):
        return self.procs[f"worker-{self._starts}"]

    def check_alive(self):
        """A child that exited — with any status — fails the run."""
        for name, proc in self.procs.items():
            code = proc.poll()
            if code is not None:
                raise SmokeFailure(
                    f"{name} exited with status {code}; last log lines:\n"
                    + log_tail(proc.log_path)
                )

    def stop_deadline_s(self):
        return STOP_DEADLINE_S * self.devices

    def stop_worker(self):
        """SIGTERM the worker; it must be gone within the stop deadline."""
        name = f"worker-{self._starts}"
        proc = self.procs.pop(name)
        deadline_s = self.stop_deadline_s()
        started = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        try:
            code = proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            kill_group(proc)
            raise SmokeFailure(
                f"{name} outlived its stop by {deadline_s:.0f}s "
                "(killed); the chip would still be held"
            ) from None
        took = time.monotonic() - started
        check(code == 0, f"{name} exited with status {code} on SIGTERM")
        return took

    def stop(self):
        """Reap everything, on every exit path."""
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + self.stop_deadline_s()
        for proc in self.procs.values():
            try:
                proc.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                pass
            kill_group(proc)
        self.procs.clear()


def kill_group(proc):
    """SIGKILL whatever is left of a child's process group."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def log_tail(path, lines=25):
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-lines:])


def poll(cluster, what, probe, deadline_s):
    """Poll ``probe()`` until it returns something truthy; a child that
    exits meanwhile fails the run at once."""
    deadline = time.monotonic() + deadline_s
    while True:
        cluster.check_alive()
        value = probe()
        if value:
            return value
        if time.monotonic() > deadline:
            raise SmokeFailure(f"timed out after {deadline_s:.0f}s: {what}")
        time.sleep(0.25)


def connect(cluster, deadline_s=60.0):
    """An ``RPC`` client once the controller answers pings."""
    import logging

    from bqueryd_tpu.rpc import RPC, RPCError

    def probe():
        try:
            # retries=1: a timed-out query fails the run, it is not re-sent
            return RPC(
                coordination_url=cluster.url, timeout=RPC_TIMEOUT_S,
                retries=1, loglevel=logging.WARNING,
            )
        except RPCError:
            return None

    return poll(cluster, "a controller that answers pings", probe, deadline_s)


def worker_slice(rpc, cluster, after=0.0, deadline_s=180.0):
    """The calc worker's debug slice, once it names the device, reflecting
    the worker's state at ``after`` (a ``time.time()``; 0 = any slice).

    The slice rides the worker's heartbeats (every <= 10 s from its liveness
    thread) and is re-sent only when its inputs changed.  So a slice TAKEN
    after ``after`` arrives within one heartbeat — unless nothing changed
    since the last one sent, and then that one already holds the final
    counters: after ``SLICE_SETTLE_S`` the newest slice is the answer."""
    pid = cluster.worker().pid
    started = time.monotonic()

    def probe():
        for entry in rpc.debug_bundle()["workers"].values():
            snap = entry.get("snapshot") or {}
            if snap.get("pid") == pid and snap.get("device") and (
                snap.get("taken_at", 0.0) > after
                or time.monotonic() - started > SLICE_SETTLE_S
            ):
                return snap
        return None

    return poll(
        cluster, "the worker's debug slice (device facts)", probe, deadline_s
    )


def wait_registered(rpc, cluster, names, deadline_s=90.0):
    """Block until the worker advertises every shard."""
    pid = cluster.worker().pid

    def probe():
        for info in rpc.info()["workers"].values():
            if info.get("pid") == pid and set(names) <= set(
                info.get("data_files") or ()
            ):
                return True
        return False

    poll(cluster, f"registration of {len(names)} shards", probe, deadline_s)


# -- 5. what the worker and the reply must say -----------------------------------

def check_worker_state(snap, expect_platform):
    """The device did the work and nothing degraded: platform, wedge latch,
    every degrade counter, read from the worker's own report."""
    device = snap["device"]
    check(
        device["platform"] == expect_platform,
        f"the worker computes on platform={device['platform']!r}, "
        f"not {expect_platform!r}",
    )
    health = snap["device_health"]
    check(not health["wedged"], "the worker's backend is latched wedged")
    check(
        health["wedge_generation"] == 0,
        f"the backend wedged {health['wedge_generation']} time(s)",
    )
    fired = {k: v for k, v in snap["degrades"].items() if v}
    check(not fired, f"degraded paths answered queries: {fired}")


def check_reply(query, rpc):
    """The reply itself names a device route — the one the kernel rule
    takes for this query — and the device merge."""
    strategies = rpc.last_call_strategies or {}
    effective = set(strategies.get("effective", {}).values())
    check(
        effective and effective <= EXPECTED_ROUTES[query],
        f"{query}: answered by route(s) {sorted(effective) or 'none'}, "
        f"expected {sorted(EXPECTED_ROUTES[query])}",
    )
    merges = set((rpc.last_call_merge_modes or {}).values())
    check(
        merges == {"device"},
        f"{query}: merge mode(s) {sorted(merges) or 'none'}, not 'device'",
    )
    return "/".join(sorted(effective))


def check_devices_used(snap):
    """Every local device holds data (after ``sharded`` the working set is
    resident).  Only a backend that reports memory stats can show it."""
    device = snap["device"]
    memory = device["memory"]
    if device["platform"] == "cpu" and not memory:
        return "not measured (cpu reports no memory stats)"
    check(
        len(memory) == device["count"],
        f"{len(memory)} of {device['count']} devices report memory stats",
    )
    idle = [m["device"] for m in memory if not (m["bytes_in_use"] or 0) > 0]
    check(not idle, f"devices {idle} hold no data after the queries")
    return " ".join(
        f"dev{m['device']}={m['bytes_in_use']}" for m in memory
    )


def timed_query(query, rpc, names):
    t0 = time.perf_counter()
    result = rpc.groupby(*query_args(query, names))
    return time.perf_counter() - t0, result


def run_queries(rpc, cluster, names, frames, queries=tuple(EXPECTED_ROUTES),
                warm_repeats=WARM_REPEATS, tag=""):
    """Each query once cold and ``warm_repeats`` times warm, every answer
    compared with pandas and every reply checked for a device route.
    Returns ``{query: {"cold_s", "warm_s", "answer", ...}}``."""
    import statistics

    rows_total = sum(len(f) for f in frames)
    results = {}
    for query in queries:
        expected = reference_answer(query, frames)
        walls, routes, answer, worst = [], [], None, 0.0
        for _ in range(1 + warm_repeats):
            cluster.check_alive()
            wall, got = timed_query(query, rpc, names)
            routes.append(check_reply(query, rpc))
            worst = max(worst, compare_answer(query, got, expected))
            walls.append(wall)
            answer = got
        warm = walls[1:]
        rows = len(frames[0]) if query == "single" else rows_total
        # the kernel rule reads only the query and the data: a repeat that
        # took another route compiled another program, so its wall is cold
        check(
            len(set(routes)) == 1,
            f"{query}: repeats of one query took routes {routes}",
        )
        route = routes[0]
        results[query] = {
            "cold_s": walls[0], "warm_s": warm, "answer": answer,
            "route": route, "rows": rows,
        }
        out(
            f"query={query} rows={rows} groups={len(expected)} "
            f"cold_s={walls[0]:.4f} "
            f"warm_s=[{','.join(f'{w:.4f}' for w in warm)}] "
            + (
                f"warm_median_s={statistics.median(warm):.4f} "
                if warm else ""
            )
            + f"effective={route} merge=device "
            f"max_rel_err={worst:.2e} ints=bit-exact device={tag}"
        )
    return results


def cluster_phase(cluster, rows=ROWS, shards=SHARDS, seed=SEED,
                  expect_platform="tpu", warm_repeats=WARM_REPEATS):
    """Phases 2-6 on a fresh :class:`Cluster`: start, first line, dataset,
    six queries, device checks, restart + compile cache.  Returns the
    summary dict (device facts, walls)."""
    cluster.start_controller()
    cluster.start_worker()
    rpc = connect(cluster)
    # backend bring-up runs in the worker's background warm-up; a worker
    # that cannot initialise its backend exits non-zero and ends the run
    # here, before any data is built
    before = worker_slice(rpc, cluster)
    device = {k: before["device"][k]
              for k in ("platform", "device_kind", "count")}
    tag = device_tag(device)
    cluster.devices = device["count"]
    versions = " ".join(
        f"{pkg}={before['runtime'].get(pkg)}"
        for pkg in ("jax", "jaxlib", "libtpu")
    )
    out(
        f"platform={device['platform']} device_kind={device['device_kind']} "
        f"devices={device['count']} {versions}"
    )
    check_worker_state(before, expect_platform)

    t0 = time.perf_counter()
    names, frames = build_dataset(cluster.data_dir, rows, shards, seed)
    out(
        f"dataset rows={rows} shards={shards} seed={seed} "
        f"build_s={time.perf_counter() - t0:.1f}"
    )
    wait_registered(rpc, cluster, names)

    results = run_queries(
        rpc, cluster, names, frames, warm_repeats=warm_repeats, tag=tag
    )
    after = worker_slice(rpc, cluster, after=time.time())
    check_worker_state(after, expect_platform)
    wedged = [
        wid for wid, info in rpc.info()["workers"].items()
        if info.get("backend_wedged")
    ]
    check(not wedged, f"workers advertise a wedged backend: {wedged}")
    out(f"devices_used {check_devices_used(after)} device={tag}")
    # the CLI worker keeps the reference's RSS watchdog (a limit on what it
    # holds above the accelerator runtime's footprint: shed caches, then
    # stop for a supervisor restart) — say how close it came
    import psutil

    from bqueryd_tpu.worker import DEFAULT_MEMORY_LIMIT_MB

    rss_mb = psutil.Process(cluster.worker().pid).memory_info().rss / 1e6
    runtime_mb = after["runtime_rss_mb"]
    out(
        f"worker_rss_mb={rss_mb:.0f} runtime_rss_mb={runtime_mb} "
        f"own_mb={rss_mb - runtime_mb:.0f} "
        f"(restart limit {DEFAULT_MEMORY_LIMIT_MB} on its own) "
        f"device={tag}"
    )

    # restart: the worker must let go of the chip, and a fresh one must find
    # the compiled programs in the persistent cache
    took = cluster.stop_worker()
    out(
        f"worker_stop_s={took:.2f} (limit {cluster.stop_deadline_s():.0f}) "
        f"device={tag}"
    )
    cluster.start_worker()
    wait_registered(rpc, cluster, names)
    # like the first worker's cold query: after backend bring-up, so the
    # two cold walls differ by the compile cache and nothing else
    worker_slice(rpc, cluster)
    cold2, again = timed_query("sharded", rpc, names)
    route2 = check_reply("sharded", rpc)
    check(
        again.equals(results["sharded"]["answer"]),
        "sharded: the restarted worker's answer differs",
    )
    restarted = worker_slice(rpc, cluster, after=time.time())
    check_worker_state(restarted, expect_platform)
    hits = restarted["compile"]["persistent_cache_hits"]
    cache = restarted["compile_cache"]
    # placed from outside, else the one fixed path inside the checkout
    expected_dir = (
        cluster.worker_env.get("JAX_COMPILATION_CACHE_DIR")
        or os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache")
    )
    check(
        cache["path"] == expected_dir,
        f"compile cache at {cache['path']!r}, expected {expected_dir!r}",
    )
    check(
        os.path.isdir(expected_dir) and os.listdir(expected_dir),
        f"compile cache directory {expected_dir!r} is empty",
    )
    check(hits > 0, "the restarted worker had no persistent compile-cache hit")
    out(
        f"restart query=sharded cold_first_worker_s="
        f"{results['sharded']['cold_s']:.4f} cold_restarted_worker_s="
        f"{cold2:.4f} effective={route2} persistent_cache_hits={hits} "
        f"compile_cache={cache['path']} device={tag}"
    )
    return {
        "device": device,
        "rows": rows,
        "shards": shards,
        "queries": {
            q: {"cold_s": round(r["cold_s"], 4),
                "warm_s": [round(w, 4) for w in r["warm_s"]],
                "route": r["route"]}
            for q, r in results.items()
        },
        "restart_cold_s": round(cold2, 4),
    }


# -- 7. both Pallas kernels through Mosaic (child process: it takes the chip) ---

def pallas_phase(rows=ROWS, interpret=False):
    """Runs in a CHILD (it imports jax): ``onehot_rows_dot`` at 9 groups and
    ``onehot_rows_dot_hicard`` at 70 225 groups over ``rows`` rows, compiled
    by Mosaic unless ``interpret``, each checked against NumPy.  The stacked
    rows are what the groupby feeds them: a count row and the eight 8-bit
    limbs of an int64 measure."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from bqueryd_tpu import ops  # noqa: F401  (x64 + compile cache config)
    from bqueryd_tpu.ops import pallas_groupby as pg

    device = jax.devices()[0]
    tag = f"{device.platform}/{device.device_kind}/{len(jax.devices())}"
    rng = np.random.RandomState(SEED)
    values = rng.randint(250, 20000, rows).astype(np.int64)
    stacked = np.stack(
        [np.ones(rows, dtype=np.float32)]
        + [((values >> (8 * i)) & 0xFF).astype(np.float32) for i in range(8)]
    )
    n_rows = len(stacked)
    stacked_d = jnp.asarray(stacked, dtype=jnp.bfloat16)
    for name, n_groups in (("onehot_rows_dot", 9),
                           ("onehot_rows_dot_hicard", HICARD_GROUPS)):
        codes = rng.randint(0, n_groups, rows).astype(np.int32)
        expected = np.stack(
            [np.bincount(codes, weights=row, minlength=n_groups)
             for row in stacked.astype(np.float64)]
        ).astype(np.uint64)
        codes_d = jnp.asarray(codes)
        walls = []
        for _ in range(2):
            t0 = time.perf_counter()
            got = jax.block_until_ready(
                getattr(pg, name)(
                    codes_d, stacked_d, n_rows=n_rows, n_groups=n_groups,
                    interpret=interpret,
                )
            )
            walls.append(time.perf_counter() - t0)
        got = np.asarray(got)
        if name == "onehot_rows_dot":   # per-block f32 partials [nb, R, G]
            got = got[:, :n_rows, :n_groups].astype(np.uint64).sum(axis=0)
        else:                           # uint32 limb totals [R, G]
            got = got[:n_rows, :n_groups].astype(np.uint64)
        check(
            np.array_equal(got, expected), f"{name} disagrees with NumPy"
        )
        out(
            f"pallas kernel={name} rows={rows} groups={n_groups} "
            f"interpret={interpret} compile_and_first_s={walls[0]:.4f} "
            f"second_s={walls[1]:.4f} matches_numpy=True device={tag}"
        )


def run_pallas_child():
    """Run :func:`pallas_phase` in one child process and relay its lines."""
    proc = subprocess.run(
        [sys.executable, "-c", "import chip_smoke; chip_smoke.pallas_phase()"],
        cwd=REPO, env=child_env(JAX_PLATFORMS="tpu"),
        stdout=subprocess.PIPE, text=True, timeout=900,
    )
    sys.stdout.write(proc.stdout)
    sys.stdout.flush()
    check(
        proc.returncode == 0,
        f"the Pallas child exited with status {proc.returncode}",
    )


# -- entry point -----------------------------------------------------------------

def _terminate(signum, _frame):
    raise SystemExit(f"signal {signum}")


def result_object(device):
    """The last line of stdout, as the chip check reads it: exactly ``ok``
    and ``device`` (``platform``, ``kind``, ``count`` as JAX reports them in
    the worker).  Everything else the run observed is on the ``summary``
    line above it."""
    return {
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["count"],
        },
    }


def main():
    check(
        os.path.isdir(os.path.join(REPO, "bqueryd_tpu")),
        f"no bqueryd_tpu package beside {__file__}: run from a checkout",
    )
    signal.signal(signal.SIGTERM, _terminate)   # so ``finally`` reaps children
    import pandas as pd

    # arrow-backed string inference buys nothing on numeric frames and the
    # reference must not depend on it (bench.py main() pins the same)
    pd.set_option("future.infer_string", False)

    t0 = time.perf_counter()
    codec = build_native()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    cluster = Cluster(workdir, worker_env={"JAX_PLATFORMS": "tpu"})
    try:
        summary = cluster_phase(cluster)
    except BaseException:
        for proc in cluster.procs.values():
            log(f"--- {proc.log_path} ---\n{log_tail(proc.log_path, 40)}")
        raise
    finally:
        cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    tag = device_tag(summary["device"])
    out(f"codec={codec} device={tag}")
    run_pallas_child()
    check("jax" not in sys.modules, "the parent process imported jax")
    out("summary " + json.dumps({
        **summary,
        "total_s": round(time.perf_counter() - t0, 1),
        "claim": None,
    }))
    out(json.dumps(result_object(summary["device"])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
