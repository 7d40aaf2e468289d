"""A controller and one calc worker as OS processes, started through the
CLI a supervisor would use (``deploy/supervisor.conf``): a copy of
``chip_smoke.py``'s ``Cluster`` and its polling helpers, without the
smoke's kill switches — the program runs at its deployment defaults, and
the benchmark sets only addresses, directories and the compile cache.
"""

import logging
import os
import signal
import subprocess
import sys
import time

STOP_DEADLINE_S = 15.0   # per chip held; the TPU runtime's teardown


class RunFailure(RuntimeError):
    """The run cannot produce a result; the process exits non-zero."""


def log(message):
    print(f"[benchmark] {message}", file=sys.stderr, flush=True)


class Cluster:
    def __init__(self, repo, workdir, cache_dir, worker_env):
        self.workdir = workdir
        self.data_dir = os.path.join(workdir, "data")
        self.log_dir = os.path.join(workdir, "logs")
        for path in (self.data_dir, self.log_dir, os.path.join(workdir, "run")):
            os.makedirs(path)
        self.url = "file://" + os.path.join(workdir, "coordination")
        self.env = dict(
            os.environ,
            PYTHONPATH=os.pathsep.join(
                p for p in (repo, os.environ.get("PYTHONPATH")) if p
            ),
            BQUERYD_TPU_IP="127.0.0.1",
            BQUERYD_TPU_RUNFILE_DIR=os.path.join(workdir, "run"),
            JAX_COMPILATION_CACHE_DIR=cache_dir,
        )
        self.worker_env = dict(worker_env)
        self.procs = {}
        self._starts = 0
        self.devices = 1

    def _spawn(self, name, role_args, extra_env=()):
        log_path = os.path.join(self.log_dir, f"{name}.log")
        with open(log_path, "ab") as log_file:
            proc = subprocess.Popen(
                [sys.executable, "-m", "bqueryd_tpu.node", *role_args,
                 f"--coordination={self.url}"],
                cwd=self.workdir, env=dict(self.env, **dict(extra_env)),
                stdout=log_file, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        proc.log_path = log_path
        self.procs[name] = proc
        return proc

    def start_controller(self):
        return self._spawn("controller", ["controller"])

    def start_worker(self):
        self._starts += 1
        return self._spawn(
            f"worker-{self._starts}",
            ["worker", f"--data_dir={self.data_dir}"], self.worker_env,
        )

    def worker(self):
        return self.procs[f"worker-{self._starts}"]

    def check_alive(self):
        for name, proc in self.procs.items():
            code = proc.poll()
            if code is not None:
                raise RunFailure(
                    f"{name} exited with status {code}; last log lines:\n"
                    + log_tail(proc.log_path)
                )

    def stop_worker(self):
        """SIGTERM the worker and wait until the chip is free again."""
        proc = self.procs.pop(f"worker-{self._starts}")
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=STOP_DEADLINE_S * self.devices)
        except subprocess.TimeoutExpired:
            pass
        kill_group(proc)

    def stop(self):
        """Reap everything, on every exit path."""
        for proc in self.procs.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + STOP_DEADLINE_S * self.devices
        for proc in self.procs.values():
            try:
                proc.wait(timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                pass
            kill_group(proc)
        self.procs.clear()


def kill_group(proc):
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def log_tail(path, lines=25):
    """The log's last lines, a native stack trace's frames left out."""
    with open(path, errors="replace") as f:
        kept = [line for line in f if not line.lstrip().startswith("@ ")]
    return "".join(kept[-lines:])


def poll(cluster, what, probe, deadline_s):
    deadline = time.monotonic() + deadline_s
    while True:
        cluster.check_alive()
        value = probe()
        if value:
            return value
        if time.monotonic() > deadline:
            raise RunFailure(f"timed out after {deadline_s:.0f}s: {what}")
        time.sleep(0.1)


def connect(cluster, client=None, client_id=None, deadline_s=60.0):
    """An ``RPC`` client once the controller answers pings; ``client`` holds
    a set-up caller's timeout and retries (the ``RPC`` defaults where it is
    None, as for every stream of the window)."""
    from bqueryd_tpu.rpc import RPC, RPCError

    kwargs = {}
    if client:
        kwargs = {"timeout": client["timeout_s"], "retries": client["retries"]}

    def probe():
        try:
            return RPC(coordination_url=cluster.url, loglevel=logging.WARNING,
                       client_id=client_id, **kwargs)
        except RPCError:
            return None

    return poll(cluster, "a controller that answers pings", probe, deadline_s)


def worker_slice(rpc, cluster, deadline_s=240.0):
    """The current worker's debug slice, once it names the device."""
    pid = cluster.worker().pid

    def probe():
        for entry in rpc.debug_bundle()["workers"].values():
            snap = entry.get("snapshot") or {}
            if snap.get("pid") == pid and snap.get("device"):
                return snap
        return None

    return poll(cluster, "the worker's debug slice (device facts)", probe, deadline_s)


def wait_registered(rpc, cluster, names, deadline_s=120.0):
    pid = cluster.worker().pid

    def probe():
        for info in rpc.info()["workers"].values():
            if info.get("pid") == pid and set(names) <= set(info.get("data_files") or ()):
                return True
        return False

    poll(cluster, f"registration of {len(names)} shards", probe, deadline_s)
