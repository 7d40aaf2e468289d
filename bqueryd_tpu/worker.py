"""Worker nodes: event loop base + calc / downloader / movebcolz roles.

Re-design of the reference worker stack (reference bqueryd/worker.py:43-637)
around the TPU data path: a calc worker owns the local JAX device(s), keeps a
decoded-column cache feeding HBM, and executes queries with the kernels in
:mod:`bqueryd_tpu.ops` through :class:`bqueryd_tpu.models.query.QueryEngine`.
Control-plane behaviour keeps the reference's observable contract:

* one ZeroMQ ROUTER socket with a random 8-byte hex identity, connected out
  to every controller found in the coordination store (reference
  bqueryd/worker.py:48-62,89-105);
* a WorkerRegisterMessage broadcast every ``heartbeat_interval`` seconds
  carrying the re-scanned ``*.bcolz`` / ``*.bcolzs`` data files — file
  discovery latency is bounded by this delay (reference
  bqueryd/worker.py:107-143);
* BusyMessage / DoneMessage wrapped around every piece of work, errors
  returned as ErrorMessage with a traceback (reference
  bqueryd/worker.py:168-180);
* built-in verbs kill / info / loglevel / readfile / sleep (reference
  bqueryd/worker.py:202-224);
* post-task memory watchdog: RSS above the limit stops the loop so a
  supervisor restarts the process (reference bqueryd/worker.py:232-241), plus
  a device-memory watermark check the reference has no analogue for.
"""

import dataclasses
import gc
import importlib
import os
import signal
import socket as socket_mod
import stat
import sys
import threading
import time
import traceback

import zmq

from bqueryd_tpu.utils import devicehealth

import bqueryd_tpu
from bqueryd_tpu import chaos, messages
from bqueryd_tpu.obs import metrics as obs_metrics
from bqueryd_tpu.coordination import chaos_store, coordination_store
from bqueryd_tpu.messages import (
    BusyMessage,
    DoneMessage,
    ErrorMessage,
    StopMessage,
    TicketDoneMessage,
    WorkerRegisterMessage,
    msg_factory,
)
from bqueryd_tpu.utils import tracing
from bqueryd_tpu.utils.net import get_my_ip
from bqueryd_tpu.utils.tracing import PhaseTimer

DEFAULT_HEARTBEAT_INTERVAL = 20.0   # WRM re-broadcast / rescan period
DEFAULT_POLL_TIMEOUT = 1.0          # seconds per zmq poll tick
#: RSS suicide threshold, over what the worker itself holds (_check_mem).
#: The reference capped each of its TEN calc workers per box at 2 GB
#: (reference bqueryd/worker.py:38, misc/supervisor.conf:19-20); here ONE
#: calc worker per box owns every chip and every cache, and its fixed
#: host cache budgets alone sum to 2.5 GiB (decode 2 GiB + factorize/result
#: 256 MiB each; the align segment takes a quarter of THIS limit,
#: ops/workingset.SHARES) — under a 2048 MB limit the watchdog stopped the
#: worker on the v5e three queries into the 10 M-row dataset.
DEFAULT_MEMORY_LIMIT_MB = 10 * 2048
#: min seconds between post-task gc.collect calls (the reference collected
#: after every task, reference bqueryd/worker.py:226; see handle())
DEFAULT_GC_INTERVAL = 10.0
DOWNLOAD_DELAY = 5.0                # downloader ticket poll period
SHARD_EXTENSIONS = (".bcolz", ".bcolzs")


class WorkerBase:
    workertype = "worker"
    #: chaos wedge latch (worker.execute "wedge" action): advertised in WRMs
    #: like the real device-health latch, and every groupby on this worker
    #: raises the transient DeviceBusyError so the controller fails the shard
    #: over to a replica holder.  Class-level default so partially
    #: constructed workers (tests build bare instances via ``__new__``) still
    #: answer ``prepare_wrm`` without the latch.
    _chaos_wedged = False
    #: MB of this process's RSS that belong to the accelerator runtime, not
    #: to the worker (a calc worker measures it around backend init, see
    #: WorkerNode.warmup): the RSS watchdog's limit applies above it
    _runtime_rss_mb = 0.0

    def __init__(
        self,
        coordination_url=None,
        redis_url=None,
        data_dir=None,
        loglevel=None,
        restart_check=True,
        heartbeat_interval=DEFAULT_HEARTBEAT_INTERVAL,
        poll_timeout=DEFAULT_POLL_TIMEOUT,
        memory_limit_mb=DEFAULT_MEMORY_LIMIT_MB,
        gc_interval=DEFAULT_GC_INTERVAL,
    ):
        import logging

        bqueryd_tpu.configure_logging(loglevel or logging.INFO)
        self.worker_id = os.urandom(8).hex()
        self.logger = bqueryd_tpu.logger.getChild(
            f"{self.workertype}.{self.worker_id[:6]}"
        )
        self.node_name = socket_mod.gethostname()
        # fault injection (bqueryd_tpu.chaos): armed only when
        # BQUERYD_TPU_FAULT_PLAN is set; unarmed sites are one None check.
        # The store is wrapped so the coordination.store site can partition
        # THIS worker from Redis while its zmq sockets stay up.
        chaos.maybe_arm_from_env()
        self.store = chaos_store(
            coordination_store(
                coordination_url or redis_url
                or bqueryd_tpu.DEFAULT_COORDINATION_URL
            ),
            node_id=self.worker_id,
        )
        self.data_dir = data_dir or bqueryd_tpu.DEFAULT_DATA_DIR
        if self.workertype == "calc" and not os.path.isdir(self.data_dir):
            raise ValueError(f"Datadir {self.data_dir} is not a valid directory")
        self.restart_check = restart_check
        self.heartbeat_interval = heartbeat_interval
        self.poll_timeout = poll_timeout
        self.memory_limit_mb = memory_limit_mb
        self.gc_interval = gc_interval
        self._last_gc = time.time()

        # -- observability ---------------------------------------------------
        from bqueryd_tpu import obs
        from bqueryd_tpu.obs import http as obs_http

        self.metrics = obs.MetricsRegistry()
        self.metrics.gauge(
            "bqueryd_tpu_worker_rss_bytes",
            "resident set size of this worker process",
            fn=self._rss_bytes,
        )
        self.metrics.gauge(
            "bqueryd_tpu_worker_uptime_seconds",
            "seconds since this worker process started",
            fn=lambda: time.time() - self.start_time,
        )
        self.work_errors = self.metrics.counter(
            "bqueryd_tpu_worker_errors_total",
            "work items that raised (returned as ErrorMessage)",
        )
        # flight recorder: the always-on forensic ring (envelopes, state
        # transitions, errors, wedge latches) behind rpc.debug_bundle() —
        # its tail rides WRMs so the controller can assemble a cross-node
        # artifact even after this worker dies
        self.flight = obs.FlightRecorder(node_id=self.worker_id)
        # where the ``send`` / ``post`` detail spans of handle() land: they
        # follow the reply, so their seconds ride the NEXT calc reply
        # (phase_timings["post_prev"]), which empties it.  Stays empty
        # unless BQUERYD_TPU_PROFILE=1
        self._after_reply = PhaseTimer(
            recorder=obs.SpanRecorder(
                trace_id=None, node=self.worker_id, root_name="post"
            )
        )
        self.metrics.gauge(
            "bqueryd_tpu_flight_evictions",
            "flight-ring events evicted by the entry/byte bounds (monotonic)",
            fn=lambda: self.flight.evictions,
        )
        self.metrics.gauge(
            "bqueryd_tpu_fault_injected_total",
            "faults injected by the armed chaos plan, process-lifetime "
            "(0 while BQUERYD_TPU_FAULT_PLAN is unarmed)",
            fn=chaos.injected_total,
        )
        self._wedge_gen_seen = devicehealth.health_snapshot()[
            "wedge_generation"
        ]
        self._metrics_server = obs_http.maybe_start(self.metrics, self.logger)

        self.context = zmq.Context.instance()
        self.socket = self.context.socket(zmq.ROUTER)
        self.socket.identity = self.worker_id.encode()
        self.socket.setsockopt(zmq.LINGER, 500)
        self.poller = zmq.Poller()
        self.poller.register(self.socket, zmq.POLLIN)

        self.controllers = set()     # connected controller addresses
        self.data_files = []
        self.running = False
        self.start_time = time.time()
        self._loop_started = self.start_time  # reset in go(), after warmup
        self.msg_count = 0
        self.last_heartbeat = 0.0
        self._hb_thread = None
        self._hb_stop = threading.Event()
        self._loop_thread = None

    # -- lifecycle ---------------------------------------------------------
    def _on_loop_start(self):
        """Role hook: runs once ``running`` is set, before the loop (so a
        background start-up step may clear ``running`` to stop the node)."""

    def go(self):
        self.running = True
        self._loop_thread = threading.current_thread()
        self._on_loop_start()
        try:
            signal.signal(signal.SIGTERM, self._term_signal)
            if hasattr(signal, "SIGUSR1"):
                # local forensic dump: kill -USR1 <pid> writes this node's
                # debug snapshot (flight ring + compile registry + device
                # health) as one JSON file without needing a live controller
                signal.signal(signal.SIGUSR1, self._dump_debug_signal)
        except ValueError:
            pass  # not the main thread (in-process test clusters)
        self.logger.info("starting %s worker %s", self.workertype, self.worker_id)
        self._loop_started = time.time()  # fast-start anchor (post-warmup)
        self._start_heartbeat_thread()
        while self.running:
            try:
                with tracing.detail("heartbeat"):
                    self.heartbeat()
                with tracing.detail("wait_for_work"):
                    events = dict(
                        self.poller.poll(int(self.poll_timeout * 1000))
                    )
                if self.socket in events:
                    self.handle_in()
            except zmq.ZMQError:
                self.logger.exception("zmq error in worker loop")
                time.sleep(0.2)
            except Exception:
                self.logger.exception("error in worker loop")
        self.stop()

    def _term_signal(self, *args):
        self.logger.info("SIGTERM received, stopping")
        self.running = False

    def _request_stop_only(self):
        """Flag the loop to exit.  Returns True when the caller is NOT the
        loop thread while the loop is alive — zmq sockets are
        single-thread-only, so socket teardown must then be left to the
        loop thread's own exit path (go()'s trailing stop())."""
        self.running = False
        self._hb_stop.set()
        loop = self._loop_thread
        external = (
            loop is not None
            and loop.is_alive()
            and threading.current_thread() is not loop
        )
        if not external and self._hb_thread is not None and (
            self._hb_thread.ident is not None  # racing go(): not yet started
        ):
            self._hb_thread.join(timeout=2.0)
        return external

    @staticmethod
    def _rss_bytes():
        import psutil

        return psutil.Process(os.getpid()).memory_info().rss

    def stop(self):
        # doubles as a cross-thread shutdown REQUEST (tests, embedders):
        # the flag ends the loop and the loop thread re-enters here for the
        # actual socket teardown
        if self._request_stop_only():
            return
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        for addr in list(self.controllers):
            try:
                self.send(addr, StopMessage({"worker_id": self.worker_id}))
            except zmq.ZMQError:
                pass
        if not self.socket.closed:
            self.socket.close()
            self.logger.info("worker %s stopped", self.worker_id)

    # -- liveness side-channel --------------------------------------------
    def _start_heartbeat_thread(self):
        """Broadcast WRMs from a dedicated thread so a long ``handle_work``
        (first-query XLA compile, a 10 M-row H2D, a slow blob fetch) cannot
        starve liveness and get this busy worker culled by the controller
        (the round-1 benchmark failure mode; cf. the reference's
        single-threaded WRM cycle, reference bqueryd/worker.py:131-143).

        ZeroMQ sockets are single-thread-only, so the thread owns a private
        DEALER socket per run; the controller keys worker liveness on the
        ``worker_id`` *inside* the WRM, not the delivering socket's identity,
        so heartbeats on this side channel refresh the same worker entry.
        """
        self._hb_stop.clear()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop,
            name=f"hb-{self.worker_id[:6]}",
            daemon=True,
        )
        self._hb_thread.start()

    def _heartbeat_loop(self):
        # ONE DEALER per controller address: a DEALER with several connected
        # peers round-robins sends across their pipes, so per-controller
        # delivery each tick would be probabilistic (a dead peer's pipe
        # absorbs copies while another gets duplicates).  A socket that
        # connects to exactly one endpoint makes every tick's delivery
        # addressed, whatever the controller count.
        socks = {}  # controller address -> DEALER connected only to it
        try:
            while not self._hb_stop.is_set() and self.running:
                try:
                    current = self.store.smembers(bqueryd_tpu.REDIS_SET_KEY)
                    for addr in current - socks.keys():
                        sock = self.context.socket(zmq.DEALER)
                        # distinct identity: this socket must never be
                        # addressed as the worker
                        sock.identity = (self.worker_id + ".hb").encode()
                        sock.setsockopt(zmq.LINGER, 0)
                        try:
                            sock.connect(addr)
                        except zmq.ZMQError:
                            # one bad membership entry must not leak a socket
                            # per tick nor abort this tick's broadcast to the
                            # healthy controllers
                            sock.close()
                            continue
                        socks[addr] = sock
                    for addr in socks.keys() - current:
                        socks.pop(addr).close()
                    wrm = self.prepare_wrm()
                    wrm["liveness_only"] = True  # files rescanned on main loop
                    payload = wrm.to_json().encode()
                    for sock in socks.values():
                        try:
                            sock.send_multipart([payload], zmq.NOBLOCK)
                        except zmq.ZMQError:
                            pass
                except Exception:
                    self.logger.debug("heartbeat thread tick failed", exc_info=True)
                # re-broadcast well inside the controller's dead timeout
                self._hb_stop.wait(min(self.heartbeat_interval, 10.0))
        finally:
            for sock in socks.values():
                sock.close()

    # -- discovery / registration -----------------------------------------
    def _sync_controller_connections(self, sock, connected):
        """Reconcile the main ROUTER socket's connections with the membership
        set.  (The liveness thread manages its own per-controller DEALER
        sockets inline in ``_heartbeat_loop`` — one socket per address, so
        heartbeat delivery is addressed rather than round-robined.)"""
        current = self.store.smembers(bqueryd_tpu.REDIS_SET_KEY)
        for addr in current - connected:
            self.logger.debug("connecting to controller %s", addr)
            sock.connect(addr)
            connected.add(addr)
        for addr in connected - current:
            self.logger.debug("dropping dead controller %s", addr)
            try:
                sock.disconnect(addr)
            except zmq.ZMQError:
                pass
            connected.discard(addr)
        return connected

    def check_controllers(self):
        self._sync_controller_connections(self.socket, self.controllers)

    def check_datafiles(self):
        found = []
        if os.path.isdir(self.data_dir):
            for name in sorted(os.listdir(self.data_dir)):
                if name.endswith(SHARD_EXTENSIONS) and os.path.isdir(
                    os.path.join(self.data_dir, name)
                ):
                    found.append(name)
        self.data_files = found
        return found

    def shard_stats(self):
        """Per-shard planning statistics advertised in the WRM (rows, column
        min/max, key cardinalities); None for roles without tables.  The calc
        role overrides."""
        return None

    #: re-advertise unchanged shard stats at most this often: WRMs fire every
    #: heartbeat on two threads, and serializing O(shards x columns) stats
    #: into each would make liveness cost scale with data size.  The
    #: periodic re-send (rather than change-only) covers controller restarts,
    #: which silently lose absorbed stats.
    STATS_READVERTISE_S = 60.0

    def _stats_to_advertise(self):
        """Shard stats for this WRM, or None when the receiver already has
        them (same snapshot object advertised within the re-send window)."""
        stats = self.shard_stats()
        if stats is None:
            return None
        now = time.time()
        if (
            stats is getattr(self, "_stats_sent_obj", None)
            and now - getattr(self, "_stats_sent_ts", 0.0)
            < self.STATS_READVERTISE_S
        ):
            return None
        self._stats_sent_obj = stats
        self._stats_sent_ts = now
        return stats

    def _backend_wedged(self):
        """The device-health latch this worker advertises.  CALC workers own
        the device, so their heartbeat ticks the probe clock too — an IDLE
        wedged worker still recovers (and stops advertising wedged) without
        waiting for a query.  Downloader/move roles never touch the device;
        their reads stay passive so a WRM can never spawn a jax probe thread
        as a side effect.  Instance-overridable (tests wedge ONE worker of an
        in-process cluster without touching the process-global latch).
        A chaos ``wedge`` fault latches the same advertisement path."""
        if self._chaos_wedged:
            return True
        return devicehealth.backend_wedged(launch=self.workertype == "calc")

    def _debug_snapshot(self, flight_limit=32):
        """This node's slice of a debug bundle: flight-ring tail, compile
        registry, device health + degrade counters, the device this process
        computes on, runtime versions.  Rides every WRM (small: the tail is
        capped) so a controller can produce a cross-node artifact even for
        a worker that has since died."""
        from bqueryd_tpu.obs import profile

        flight = getattr(self, "flight", None)
        # NOTE: no histogram snapshot here — the WRM's own "metrics" key
        # already carries it, and the controller keeps the latest copy per
        # worker; duplicating it would double every heartbeat's size
        return {
            "node_id": getattr(self, "worker_id", None),
            "workertype": self.workertype,
            "pid": os.getpid(),
            "flight": flight.tail(flight_limit) if flight is not None else [],
            "flight_evictions": (
                flight.evictions if flight is not None else 0
            ),
            # when this slice was taken (worker clock): a reader waiting
            # for the state AFTER some event compares against it
            "taken_at": time.time(),
            "compile": profile.profiler().snapshot(),
            "device_health": devicehealth.health_snapshot(),
            # times each wedge-survival path answered a query from
            # somewhere other than the device path it was routed to
            "degrades": devicehealth.degrade_counts(),
            # platform / device_kind / count / per-device memory as JAX
            # reports them; None until a kernel call proved the backend
            "device": profile.profiler().device_facts(),
            # the accelerator runtime's share of this process's RSS, which
            # the RSS watchdog leaves out of its limit (_check_mem)
            "runtime_rss_mb": round(self._runtime_rss_mb),
            "runtime": profile.runtime_versions(),
            "compile_cache": profile.compile_cache_info(),
        }

    #: re-send an unchanged debug slice at most this often (covers
    #: controller restarts, which silently lose absorbed slices) — same
    #: policy as STATS_READVERTISE_S for shard stats
    DEBUG_READVERTISE_S = 60.0

    def _debug_change_key(self):
        """Cheap fingerprint of the debug slice's inputs: flight ring seq,
        profiler call seq + cache counters, wedge generation, degrade
        total, whether the device has been enumerated."""
        from bqueryd_tpu.obs import profile

        flight = getattr(self, "flight", None)
        prof = profile.profiler()
        return (
            flight._seq if flight is not None else 0,
            prof._call_seq,
            prof.jit_cache_hits,
            prof.persistent_cache_hits,
            devicehealth.health_snapshot()["wedge_generation"],
            sum(devicehealth.degrade_counts().values()),
            prof._devices is not None,
        )

    def _debug_to_advertise(self):
        """The debug slice for this WRM, or None when the receiver already
        has it (unchanged since the last send, inside the re-send window).
        WRMs fire every <=10 s on two threads; serializing an identical
        multi-KB snapshot into each would tax every heartbeat for data that
        changes only on compile/flight/wedge events."""
        key = self._debug_change_key()
        now = time.time()
        if (
            key == getattr(self, "_debug_sent_key", None)
            and now - getattr(self, "_debug_sent_ts", 0.0)
            < self.DEBUG_READVERTISE_S
        ):
            return None
        snapshot = self._debug_snapshot()
        self._debug_sent_key = key
        self._debug_sent_ts = now
        return snapshot

    def _dump_debug_signal(self, *args):
        from bqueryd_tpu.obs import flightrec, profile

        try:
            # build_bundle applies the same path redaction the controller's
            # bundle gets — a worker-side dump must be just as safe to
            # attach to a public bug report
            allowed = [self.data_dir]
            cache_path = profile.compile_cache_info().get("path")
            if cache_path:
                allowed.append(cache_path)
            path = flightrec.dump_bundle(
                flightrec.build_bundle(
                    None,
                    {self.worker_id: {
                        "data": self._debug_snapshot(flight_limit=512),
                        "ts": time.time(),
                        "registered": True,
                    }},
                    allowed_path_prefixes=allowed,
                ),
                role=self.workertype,
            )
            self.logger.warning("SIGUSR1: debug snapshot written to %s", path)
        except Exception:
            self.logger.exception("SIGUSR1 debug dump failed")

    def _pipeline_busy_to_advertise(self):
        """The StageClock busy snapshot riding calc WRMs: the controller's
        capacity model (obs.capacity) reads per-stage busy DELTAS from it
        to name each worker's bottleneck stage (decode vs kernel vs merge)
        beside its utilization.  Cumulative totals — the absorb side
        rebases on a restart's reset, same contract as the histogram
        snapshot.  None for non-calc roles (no data path, no stages) and
        on any failure: busy accounting must never break liveness."""
        if getattr(self, "workertype", None) != "calc":
            return None
        try:
            from bqueryd_tpu.parallel import pipeline

            return pipeline.clock().snapshot()
        except Exception:
            return None

    def prepare_wrm(self):
        # getattr defence: embedders and tests build workers piecemeal
        # (__new__), and a missing registry must never break the WRM
        # heartbeat (same rule as shard_stats)
        registry = getattr(self, "metrics", None)
        errors = getattr(self, "work_errors", None)
        try:
            debug = self._debug_to_advertise()
        except Exception:
            debug = None  # a debug failure must never break liveness
        return WorkerRegisterMessage(
            {
                "worker_id": self.worker_id,
                "node": self.node_name,
                "ip": get_my_ip(),
                "data_dir": self.data_dir,
                "data_files": self.data_files,
                "workertype": self.workertype,
                "pid": os.getpid(),
                "uptime": time.time() - self.start_time,
                "msg_count": self.msg_count,
                # degraded-mode visibility: operators watching rpc.info()
                # see a wedged accelerator the moment routing does (and the
                # controller's health scorer marks this worker "wedged")
                "backend_wedged": self._backend_wedged(),
                # error-counter total: the health scorer's windowed error
                # rate is the delta of this across heartbeats
                "work_errors": errors.value if errors is not None else 0,
                # the node's debug-bundle slice (flight tail + compile
                # registry + device health), absorbed controller-side for
                # rpc.debug_bundle()
                "debug": debug,
                # metadata-only per-shard stats (rows, min/max) feeding the
                # controller's plan-time pruning; None for non-calc roles
                # and for beats where the unchanged stats were advertised
                # recently
                "shard_stats": self._stats_to_advertise(),
                # latency histogram snapshot (fixed buckets, JSON-safe):
                # controllers aggregate these fleet-wide by bucket-vector
                # addition (get_info "worker_histograms" + peer gossip)
                "metrics": (
                    registry.histogram_snapshot()
                    if registry is not None else None
                ),
                # per-stage pipeline busy clocks (cumulative seconds): the
                # capacity model's bottleneck-stage signal; None for
                # non-calc roles
                "pipeline_busy": self._pipeline_busy_to_advertise(),
            }
        )

    def heartbeat(self):
        now = time.time()
        # wedge-latch transitions land in the flight ring the moment the
        # loop notices them (forensic event: never gated by the metrics
        # kill switch) — the debug bundle's answer to "when did it wedge?"
        health = devicehealth.health_snapshot()
        if health["wedge_generation"] != self._wedge_gen_seen:
            self._wedge_gen_seen = health["wedge_generation"]
            self.flight.record(
                "wedge_latched",
                generation=health["wedge_generation"],
                abandoned_probes=health["abandoned_probes"],
            )
            self.logger.warning(
                "accelerator backend latched wedged (generation %d)",
                health["wedge_generation"],
            )
        interval = self.heartbeat_interval
        # fast start: the first WRM on a freshly connected ROUTER socket is
        # dropped if the peer handshake hasn't finished (identity not yet
        # routable), so rebroadcast every second until registration settles
        # rather than waiting a full heartbeat_interval to become queryable
        if now - self._loop_started < 10.0:
            interval = min(interval, 1.0)
        if now - self.last_heartbeat < interval:
            return
        self.last_heartbeat = now
        self.check_controllers()
        self.check_datafiles()
        self.send_to_all(self.prepare_wrm())

    # -- messaging ---------------------------------------------------------
    def send(self, addr, msg):
        """Send to a controller by identity; a bytes 'data' value travels as
        its own frame so JSON never sees binary."""
        data = msg.pop("data", None)
        frames = [
            addr.encode() if isinstance(addr, str) else addr,
            msg.to_json().encode(),
        ]
        if data is not None:
            if isinstance(data, str):
                data = data.encode()
            frames.append(data)
        self.socket.send_multipart(frames)

    def send_to_all(self, msg):
        for addr in list(self.controllers):
            try:
                self.send(addr, msg.copy())
            except zmq.ZMQError as exc:
                self.logger.debug("send to %s failed: %s", addr, exc)

    def handle_in(self):
        frames = self.socket.recv_multipart()
        if len(frames) < 2:
            self.logger.warning("dropping short message: %r", frames)
            return
        sender, payload = frames[0], frames[1]
        self.msg_count += 1
        try:
            msg = msg_factory(payload)
        except messages.MalformedMessage:
            self.logger.warning("dropping malformed message from %r", sender)
            return
        if msg.isa(StopMessage) or msg.isa("kill"):
            self.running = False
            return
        if msg.isa("loglevel"):
            self._set_loglevel(msg)
            return
        if msg.isa("info"):
            self.send(sender, self.prepare_wrm())
            return
        # detail (BQUERYD_TPU_PROFILE=1 only): the whole unit of work, Busy
        # ack to the end of gc / the RSS check, on the device trace.
        # ``wall_ts`` is the clock of every span's ``start_ts``, so this one
        # event maps the profiler's clock onto the spans'
        with tracing.detail(
            "calc",
            trace_id=(msg.get_trace() or {}).get("trace_id"),
            wall_ts=time.time(),
        ):
            self.handle(msg, sender)

    def _set_loglevel(self, msg):
        import logging

        args, _ = msg.get_args_kwargs()
        level = {"debug": logging.DEBUG, "info": logging.INFO}.get(
            (args[0] if args else "info"), logging.INFO
        )
        bqueryd_tpu.logger.setLevel(level)
        self.logger.info("loglevel set to %s", level)

    # -- work --------------------------------------------------------------
    def handle(self, msg, sender):
        from bqueryd_tpu import obs

        busy = BusyMessage({"worker_id": self.worker_id})
        self.send_to_all(busy)
        wire = msg.get_trace()
        log_fields = {
            "trace_id": (wire or {}).get("trace_id"),
            "query_id": msg.get("parent_token") or msg.get("token"),
        }
        # flight ring: every envelope this worker accepts (hot path — obeys
        # the metrics kill switch; failures below are recorded regardless)
        if obs.enabled():
            self.flight.record(
                "envelope",
                verb=msg.get("payload"),
                token=msg.get("token"),
                parent=msg.get("parent_token"),
                trace_id=log_fields["trace_id"],
            )
        work_clock = time.perf_counter()
        # correlation ids on every log line this work emits (JSON
        # formatter), and the active TraceContext for trace_span tagging;
        # the except body stays INSIDE the bind — the failure traceback is
        # the log line that most needs to join the rpc.trace() waterfall
        with obs.bind_log_context(**log_fields), obs.use_trace(
            obs.TraceContext.from_wire(wire)
        ):
            try:
                # chaos site worker.execute: transient raises (the failover
                # trigger), wedge latch, die-after-ack (the Busy above WAS
                # the ack), delay — all before the deadline check so an
                # injected stall can expire a deadline like a real one
                fault = chaos.fire(
                    "worker.execute",
                    worker=self.worker_id,
                    verb=msg.get("payload"),
                    token=msg.get("token"),
                    filename=str(msg.get("filename")),
                ) if chaos.enabled() else None
                if fault is not None and fault.action == "die_after_ack":
                    self._chaos_die()
                    return  # hard crash: no reply, no Done, no goodbye
                if fault is not None and fault.action == "wedge":
                    self._chaos_wedged = True
                    self.flight.record("chaos_wedged")
                    self.logger.warning(
                        "chaos: wedge latched — advertising backend_wedged"
                    )
                if self._chaos_wedged and msg.isa("groupby"):
                    raise chaos.DeviceBusyError(
                        "chaos: accelerator backend wedged"
                    )
                if msg.deadline_expired():
                    # the client's budget is already gone: burning kernel
                    # time on an answer nobody is waiting for starves
                    # admitted queries
                    raise TimeoutError(
                        f"deadline exceeded "
                        f"{-msg.deadline_remaining():.3f}s before execution"
                    )
                result = self.handle_work(msg)
            except Exception as exc:
                self.logger.exception("error handling work")
                self.work_errors.inc()
                # forensic event (never gated): the first line of the
                # failure plus its correlation ids — the flight ring is what
                # explains an ErrorMessage after the query is long gone
                self.flight.record(
                    "work_error",
                    verb=msg.get("payload"),
                    token=msg.get("token"),
                    trace_id=log_fields["trace_id"],
                    error=f"{type(exc).__name__}: {exc}"[:300],
                )
                err = ErrorMessage(msg)
                err["payload"] = traceback.format_exc()
                if isinstance(exc, chaos.TransientError):
                    # retryable class (DeviceBusyError & co): the controller
                    # fails the shard over to a different holder instead of
                    # aborting the parent query (messages.py `transient`)
                    err["transient"] = True
                result = err
            else:
                if obs.enabled():
                    self.flight.record(
                        "work_done",
                        verb=msg.get("payload"),
                        token=msg.get("token"),
                        trace_id=log_fields["trace_id"],
                        wall_s=round(time.perf_counter() - work_clock, 6),
                    )
        if result is not None:
            # chaos site worker.reply: drop loses the finished result on
            # the wire (dispatch timeout + failover must recover), delay
            # stretches reply latency (hedging territory)
            fault = chaos.fire(
                "worker.reply",
                worker=self.worker_id,
                verb=msg.get("payload"),
                token=msg.get("token"),
            ) if chaos.enabled() else None
            if fault is not None and fault.action == "drop":
                self.flight.record(
                    "chaos_reply_dropped", token=msg.get("token")
                )
                result = None
        if result is not None:
            try:
                with tracing.detail("send", self._after_reply):
                    self.send(sender, result)
            except zmq.ZMQError:
                self.logger.exception("could not send result to %r", sender)
        with tracing.detail("post", self._after_reply):
            self.send_to_all(DoneMessage({"worker_id": self.worker_id}))
            # The reference collects after EVERY task (reference
            # bqueryd/worker.py:226) — necessary for its per-query bcolz
            # allocations, but here steady-state serving is cache-resident
            # and a full gen-2 collect walks those caches: ~17 ms per query
            # at 10 M rows, a measured ~20% of the fixed per-query cost.
            # Throttle to one collect per interval; the RSS watchdog
            # (_check_mem) remains the backstop between collects.
            now = time.time()
            if now - self._last_gc >= self.gc_interval:
                self._last_gc = now
                gc.collect()
            self._check_mem()

    def _chaos_die(self):
        """die_after_ack: simulate a hard crash after accepting work — the
        Busy ack went out, then silence.  No reply, no Done, no StopMessage
        goodbye, heartbeats stop; the controller must recover through its
        dispatch timeout / dead-worker cull + replica failover.  The loop
        thread still runs its own socket teardown on exit (zmq sockets are
        single-thread-only)."""
        self.logger.warning(
            "chaos: die_after_ack fired — simulating hard worker crash"
        )
        self.flight.record("chaos_die_after_ack")
        self._hb_stop.set()
        self.send = lambda *a, **k: None  # silent: no replies, no goodbye
        self.running = False

    def handle_work(self, msg):
        # base verbs shared by every role
        if msg.isa("readfile"):
            return self._readfile(msg)
        if msg.isa("sleep"):
            args, _ = msg.get_args_kwargs()
            duration = float(args[0]) if args else 0.0
            time.sleep(min(duration, 60.0))
            reply = msg.copy()
            reply.add_as_binary("result", f"slept {duration} {self.worker_id}")
            return reply
        raise ValueError(f"unhandled message payload {msg.get('payload')!r}")

    def _readfile(self, msg):
        """Read a file strictly inside data_dir (the reference's readfile verb,
        reference bqueryd/worker.py:216-220, with path traversal closed)."""
        args, _ = msg.get_args_kwargs()
        filename = args[0]
        path = os.path.realpath(os.path.join(self.data_dir, filename))
        if not path.startswith(os.path.realpath(self.data_dir) + os.sep):
            raise ValueError(f"path {filename!r} escapes data_dir")
        with open(path, "rb") as f:
            reply = msg.copy()
            reply["data"] = f.read()
            return reply

    def _check_mem(self):
        """The reference's RSS watchdog (reference bqueryd/worker.py:232-241)
        over what the WORKER holds: the accelerator runtime's own footprint
        (``_runtime_rss_mb`` — a process that has initialised the TPU
        backend shows ~13.5 GB of RSS before it has served a row) is not
        the worker's to shed and does not count against the limit."""
        if not self.restart_check:
            return
        try:
            import psutil

            rss_mb = psutil.Process(os.getpid()).memory_info().rss / 1e6
        except Exception:
            return
        limit_mb = self.memory_limit_mb + self._runtime_rss_mb
        if rss_mb > limit_mb:
            # shed caches first; suicide (the reference's policy) only if
            # that wasn't enough
            shed_mb = self._shed_caches()
            if shed_mb is not None and shed_mb <= limit_mb:
                return
            # unmeasurable post-shed RSS counts as still-over: the pre-shed
            # reading already proved the limit breached, and a silent pass
            # here would disable the supervisor-restart safety net
            self.logger.warning(
                "RSS %s MB above limit %d MB (%d MB + the runtime's %.0f), "
                "stopping for supervisor restart",
                "?" if shed_mb is None else f"{shed_mb:.0f}",
                limit_mb, self.memory_limit_mb, self._runtime_rss_mb,
            )
            self.running = False

    def _shed_caches(self):
        """Drop query caches + collect; returns post-shed RSS in MB."""
        import gc

        try:
            from bqueryd_tpu.storage import free_cachemem

            free_cachemem()
        except Exception:
            pass
        executor = getattr(self, "_mesh_executor", None)
        if executor is not None:
            executor.clear_caches()
        engine = getattr(self, "_engine", None)
        if engine is not None:
            engine.clear_caches()
        # dict-column instances pin their value dictionaries — not "light"
        # under memory pressure
        getattr(self, "_table_cache", {}).clear()
        result_cache = getattr(self, "_result_cache", None)
        if result_cache:
            result_cache.clear()
        delta_cache = getattr(self, "_delta_cache", None)
        if delta_cache is not None:
            delta_cache.clear()
        gc.collect()
        try:
            import psutil

            return psutil.Process(os.getpid()).memory_info().rss / 1e6
        except Exception:
            return None


class WorkerNode(WorkerBase):
    """The compute leaf: executes groupby / execute_code (reference
    bqueryd/worker.py:247-348)."""

    workertype = "calc"
    #: set by warmup() when the JAX backend could not be initialised; go()
    #: then raises so the process exits non-zero (class-level default for
    #: piecemeal ``__new__`` construction, like ``_chaos_wedged``)
    _warmup_fatal = None

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._engine = None
        self._mesh_executor = None
        self._result_cache = None
        self._table_cache = {}
        self._stats_collector = None
        self._warmup_thread = None
        # who read a table's identity off the filesystem (a stat of
        # meta.json and a realpath): the open, once per shard per unit, or
        # a consumer that was handed none and asked again
        self._identity_passes = {
            source: self.metrics.counter(
                "bqueryd_tpu_table_identity_total",
                "filesystem identity passes per table, by who made them: "
                "open = the unit's open (handed down to every cache key "
                "of the unit); recomputed = a consumer given no identity "
                "(0 in a served unit but for chunk-pruned views)",
                labels={"source": source},
            )
            for source in ("open", "recomputed")
        }
        # how the open made each shard's canonical path (``_open_unit``)
        self._identity_paths = {
            form: self.metrics.counter(
                "bqueryd_tpu_identity_path_total",
                "canonical paths of the shards a unit opened, by form: "
                "joined = the unit's one realpath of data_dir and the "
                "shard's name, proven by one lstat; resolved = a realpath "
                "of the shard (a symlinked shard or a nested name)",
                labels={"form": form},
            )
            for form in ("joined", "resolved")
        }
        self._distinct_payloads = obs_metrics.distinct_payload_counters(
            self.metrics
        )
        # device-health gauges: read-only snapshots (never launch a probe
        # from a metrics scrape) — operators see the wedge latch and its
        # probe debt wherever they already scrape worker metrics
        snap = devicehealth.health_snapshot
        self.metrics.gauge(
            "bqueryd_tpu_backend_wedged",
            "1 while the accelerator backend is latched as wedged",
            fn=lambda: snap()["wedged"],
        )
        self.metrics.gauge(
            "bqueryd_tpu_device_probes_abandoned",
            "health probes written off as hung since the last success",
            fn=lambda: snap()["abandoned_probes"],
        )
        self.groupby_queries = self.metrics.counter(
            "bqueryd_tpu_worker_groupby_total",
            "groupby CalcMessages executed by this worker",
        )
        # -- streaming ingest (PR 14) ------------------------------------
        self._delta_cache = None  # DeltaAggCache, built lazily when enabled
        self._last_chunk_prune = None
        self.appends_total = self.metrics.counter(
            "bqueryd_tpu_worker_appends_total",
            "append CalcMessages applied by this worker",
        )
        self.append_rows_total = self.metrics.counter(
            "bqueryd_tpu_worker_append_rows_total",
            "rows appended into served shards by this worker",
        )
        self.chunks_decoded_total = self.metrics.counter(
            "bqueryd_tpu_chunks_decoded_total",
            "storage chunks the zone-map pruning pass kept for decode on "
            "filtered queries (with chunks_skipped: the decode fraction)",
        )
        self.chunks_skipped_total = self.metrics.counter(
            "bqueryd_tpu_chunks_skipped_total",
            "storage chunks proven unmatchable by per-chunk zone maps and "
            "never decoded",
        )
        self.delta_refreshes_total = self.metrics.counter(
            "bqueryd_tpu_delta_refreshes_total",
            "cached aggregate results refreshed by aggregating only "
            "appended chunks and merging the delta partial "
            "(ops.workingset.DeltaAggCache)",
        )
        self.metrics.gauge(
            "bqueryd_tpu_delta_cache_bytes",
            "serialized payload bytes held by the delta-maintained "
            "aggregate cache",
            fn=lambda: (
                0 if self._delta_cache is None
                else self._delta_cache.nbytes
            ),
        )
        self.groupby_seconds = self.metrics.histogram(
            "bqueryd_tpu_worker_groupby_seconds",
            "whole-CalcMessage wall on the worker (open to serialize)",
        )
        from bqueryd_tpu.obs.metrics import BYTES_BUCKETS

        self.reply_bytes = self.metrics.histogram(
            "bqueryd_tpu_reply_bytes",
            "serialized groupby result-payload size per calc reply "
            "(the wire bytes the device-resident merge shrinks)",
            buckets=BYTES_BUCKETS,
        )
        # the process-global compile/device profiler exposed on this node's
        # registry: compile-seconds histogram (same instance process-wide),
        # jit/persistent-cache counters, HBM watermark gauges
        from bqueryd_tpu.obs import profile as obs_profile

        obs_profile.profiler().bind(self.metrics)
        self._bind_pipeline_metrics()
        # join a multi-host JAX job if configured (pod slice = one logical
        # calc worker; must happen before any JAX backend touch)
        from bqueryd_tpu import ops

        ops.maybe_init_distributed(self.logger)

    def _bind_pipeline_metrics(self):
        """Pipeline + working-set telemetry on this node's registry: stage
        busy clocks (process-global — the worker owns the process's data
        path), working-set segment counters (per mesh executor, created
        lazily: gauges read 0 until the first mesh query), and result-cache
        counters.  All fn-backed so a scrape reads live state."""
        from bqueryd_tpu.parallel import pipeline

        self.metrics.gauge(
            "bqueryd_tpu_pipeline_threads",
            "effective shard-pipeline pool width "
            "(BQUERYD_TPU_PIPELINE_THREADS)",
            fn=pipeline.pipeline_threads,
        )
        for stage_name in pipeline.STAGES:
            self.metrics.gauge(
                "bqueryd_tpu_pipeline_busy_seconds",
                "cumulative wall spent inside each pipeline stage across "
                "all threads (sum > query wall proves stage overlap)",
                labels={"stage": stage_name},
                fn=(lambda s=stage_name: pipeline.clock().busy_seconds(s)),
            )

        def ws_stat(segment, field):
            executor = self._mesh_executor
            if executor is None:
                return 0
            # direct attribute reads (plain ints under the GIL): a /metrics
            # scrape must not rebuild full stats() snapshots — 12 gauges per
            # scrape would take every cache lock 4x each against the hot path
            cache = executor.workingset.segment(segment)
            return cache.nbytes if field == "bytes" else getattr(cache, field)

        for segment in ("align", "codes", "blocks"):
            for field, help_text in (
                ("bytes", "bytes held per working-set cache segment"),
                ("hits", "working-set cache hits per segment (monotonic)"),
                ("misses",
                 "working-set cache misses per segment (monotonic)"),
                ("evictions",
                 "working-set LRU evictions per segment (monotonic)"),
            ):
                self.metrics.gauge(
                    f"bqueryd_tpu_workingset_{field}",
                    help_text,
                    labels={"segment": segment},
                    fn=(
                        lambda s=segment, f=field: ws_stat(s, f)
                    ),
                )
        self.metrics.gauge(
            "bqueryd_tpu_workingset_pressure_evictions",
            "device cache entries shed by the HBM watermark policy "
            "(monotonic)",
            fn=lambda: (
                0 if self._mesh_executor is None
                else self._mesh_executor.workingset.pressure_evictions
            ),
        )

        # device-resident merge byte movement (parallel/devicemerge): D2H
        # bytes per merge mode and the per-device partial bytes the
        # span-owned collective merge kept out of the fetch.  Process-global
        # like the stage clocks — the worker owns the process's data path.
        from bqueryd_tpu.parallel import devicemerge

        for mode in ("device", "host"):
            self.metrics.gauge(
                "bqueryd_tpu_merge_bytes_fetched",
                "D2H bytes fetched by the partial-table merge, per mode "
                "(device = final spans only; host = every device's table)",
                labels={"mode": mode},
                fn=(lambda m=mode: devicemerge.stats().fetched(m)),
            )
            self.metrics.gauge(
                "bqueryd_tpu_merge_queries",
                "mesh queries merged per merge mode (monotonic)",
                labels={"mode": mode},
                fn=(lambda m=mode: devicemerge.stats().count(m)),
            )
        self.metrics.gauge(
            "bqueryd_tpu_merge_d2h_bytes_saved",
            "per-device partial-table bytes the device-resident merge kept "
            "out of the D2H fetch (monotonic)",
            fn=lambda: devicemerge.stats().saved(),
        )

        def result_stat(field):
            cache = self._result_cache
            if cache is None or cache is False:  # unbuilt or disabled
                return 0
            return getattr(cache, field)

        for field, help_text in (
            ("hits", "worker result-cache hits (monotonic)"),
            ("misses", "worker result-cache misses (monotonic)"),
            ("evictions", "worker result-cache LRU evictions (monotonic)"),
        ):
            self.metrics.gauge(
                f"bqueryd_tpu_result_cache_{field}",
                help_text,
                fn=(lambda f=field: result_stat(f)),
            )

    def _on_loop_start(self):
        if os.environ.get("BQUERYD_TPU_WARMUP", "1") == "1":
            self._warmup_thread = threading.Thread(
                target=self.warmup,
                name=f"warmup-{self.worker_id[:6]}",
                daemon=True,
            )
            self._warmup_thread.start()

    def go(self):
        super().go()
        if self._warmup_fatal is not None:
            raise RuntimeError(
                "calc worker stopped: JAX backend could not be initialised"
            ) from self._warmup_fatal

    def warmup(self):
        """Prime the JAX backend (PJRT client init + a tiny kernel compile)
        in the BACKGROUND so the worker advertises its shards immediately.

        Backend bring-up takes seconds (about 15 s to reach a local chip)
        and a cold first compile more; gating the first WRM broadcast on it
        would make every worker restart a registration blackout.  Instead
        the worker is queryable at once — a query arriving mid-warmup simply
        blocks on the same JAX backend-init lock, and the liveness heartbeat
        thread plus the controller's inflight-aware cull keep the busy
        worker alive for however long that takes (reference
        bqueryd/worker.py:107-143 was queryable ~20s after start).

        A backend that cannot be INITIALISED is fatal unless the operator
        asked for the CPU (``JAX_PLATFORMS=cpu``): the worker stops and
        ``go()`` raises, so the process exits non-zero and a supervisor
        sees a start failure — instead of a worker that advertises shards
        it would serve from the NumPy host kernels.  Start chip workers
        with ``JAX_PLATFORMS=tpu`` so JAX itself raises rather than falls
        back to another backend."""
        t0 = time.time()
        self.logger.info("starting JAX backend warmup in background")
        try:
            import jax

            rss_before = self._rss_bytes()
            devices = jax.devices()
            # what backend init added to RSS is the runtime's (device
            # mappings, premapped staging buffers), not a cache the RSS
            # watchdog could shed: keep it out of the restart limit
            self._runtime_rss_mb = max(
                self._rss_bytes() - rss_before, 0
            ) / 1e6
        except Exception as exc:
            if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
                self.logger.exception("JAX backend init failed (continuing)")
                return
            self.logger.critical(
                "JAX backend could not be initialised (JAX_PLATFORMS=%r): "
                "%s — stopping this calc worker",
                os.environ.get("JAX_PLATFORMS"), exc,
            )
            self._warmup_fatal = exc
            self.running = False
            return
        self.logger.info(
            "JAX backend up: platform=%s device_kind=%s devices=%d "
            "(runtime holds %.0f MB of RSS, outside the %s MB restart "
            "limit)",
            devices[0].platform, devices[0].device_kind, len(devices),
            self._runtime_rss_mb, self.memory_limit_mb,
        )
        try:
            import numpy as np

            from bqueryd_tpu import ops
            from bqueryd_tpu.obs import profile as obs_profile

            codes = np.zeros(8, dtype=np.int32)
            vals = np.ones(8, dtype=np.int64)
            partials = ops.partial_tables(codes, (vals,), ("sum",), 4, None)
            ops.finalize(partials, ("sum",))
            # the kernel above answered: the device facts of the debug
            # slice (platform, device_kind, count) are readable from now on
            obs_profile.profiler().note_devices()
            # a dispatch-floor sample taken by a query while this compile
            # held the backend is inflated; replace it with a clean one so
            # host routing doesn't mis-route for the process lifetime
            from bqueryd_tpu.models.query import device_dispatch_floor

            device_dispatch_floor(remeasure=True)
            self.logger.info("kernel warmup done in %.1fs", time.time() - t0)
        except Exception:
            self.logger.exception("kernel warmup failed (continuing)")

    def shard_stats(self):
        """Metadata-only stats for every advertised shard (memoized; see
        plan.stats.StatsCollector).  Disable with BQUERYD_TPU_SHARD_STATS=0
        — the planner then treats this worker's shards as stats-less (no
        pruning)."""
        if os.environ.get("BQUERYD_TPU_SHARD_STATS", "1") == "0":
            return None
        # getattr defences: embedders (and tests) build workers piecemeal,
        # and a stats failure must never break the WRM heartbeat
        try:
            collector = getattr(self, "_stats_collector", None)
            if collector is None:
                from bqueryd_tpu.plan.stats import StatsCollector

                collector = StatsCollector(table_opener=self._open_table)
                self._stats_collector = collector
            return collector.collect(
                self.data_dir, list(self.data_files)
            )
        except Exception:
            log = getattr(self, "logger", None)
            if log is not None:
                log.debug("shard stats gathering failed", exc_info=True)
            return None

    @property
    def engine(self):
        if self._engine is None:
            from bqueryd_tpu.models.query import QueryEngine

            self._engine = QueryEngine()
        return self._engine

    @property
    def mesh_executor(self):
        if self._mesh_executor is None:
            from bqueryd_tpu.parallel.executor import MeshQueryExecutor

            # memory_limit_mb: what _check_mem holds this process to
            self._mesh_executor = MeshQueryExecutor(
                host_limit_bytes=self.memory_limit_mb * 10**6,
                on_identity_recomputed=self._identity_passes[
                    "recomputed"
                ].inc,
            )
        return self._mesh_executor

    @property
    def result_cache(self):
        """Serialized-result cache keyed by (table identities, query
        signature).  Table identity includes the shard's meta.json mtime, so
        activation of new data invalidates naturally — a repeated query on
        unchanged shards costs one dict lookup, no kernel dispatch.  The
        identities are the ones the unit's open read (``_open_unit``), not
        a second look at the filesystem.  Bounded by
        BQUERYD_TPU_RESULT_CACHE_BYTES (0 disables)."""
        if self._result_cache is None:
            from bqueryd_tpu.utils.cache import BytesCappedCache

            try:
                cap = int(
                    os.environ.get(
                        "BQUERYD_TPU_RESULT_CACHE_BYTES", 256 * 1024**2
                    )
                )
            except ValueError:
                self.logger.warning(
                    "unparseable BQUERYD_TPU_RESULT_CACHE_BYTES, cache off"
                )
                cap = 0
            self._result_cache = BytesCappedCache(cap) if cap > 0 else False
        # explicit False check: an EMPTY BytesCappedCache is len()-falsy
        return None if self._result_cache is False else self._result_cache

    # -- delta-maintained hot aggregates (streaming ingest, PR 14) ---------
    def delta_cache(self):
        """The per-worker :class:`~bqueryd_tpu.ops.workingset.DeltaAggCache`
        (None while BQUERYD_TPU_DELTA_SERVE=0)."""
        from bqueryd_tpu.ops import workingset

        if not workingset.delta_serve_enabled():
            return None
        if self._delta_cache is None:
            self._delta_cache = workingset.DeltaAggCache()
        return self._delta_cache

    @staticmethod
    def _delta_eligible(query):
        """Shapes whose cached result can be maintained by merging a
        tail-only partial: plain mergeable aggregations.  Basket expansion
        re-selects OLD rows when a NEW row of the same basket matches, so
        its cached result is not tail-refreshable; distinct counts carry
        value sets the flat merge forms don't cover here."""
        from bqueryd_tpu import ops

        return (
            query is not None
            and query.aggregate
            and not query.expand_filter_column
            and all(op in ops.MERGEABLE_OPS for op in query.ops)
        )

    @staticmethod
    def _delta_key(identities, query):
        """The delta store files a shard group by where it lives, not by
        what its meta.json reads (an append must FIND the entry): the
        realpath of each rootdir, which is the first element of the
        identity the open read."""
        return (
            # (a table with no stat-able meta.json has a token for an
            # identity: filed under it, never found again)
            tuple(
                ident[0] if isinstance(ident, tuple) else ident
                for ident in identities
            ),
            query.signature(),
        )

    def _serve_delta(self, cache, key, tables, query, timer):
        """Serve a grown shard group from the delta cache: aggregate ONLY
        the appended chunks of each grown table through the ordinary
        engine path and merge the tail partials into the cached payload.
        ``key`` is the unit's ``_delta_key``.  Returns the refreshed
        serialized payload, or None (no entry / not an append-only growth
        — the caller recomputes)."""
        from bqueryd_tpu.models.query import ResultPayload
        from bqueryd_tpu.parallel import hostmerge

        with tracing.detail("cache_probe", timer):
            entry = cache.get(key)
        if entry is None:
            return None
        per_table_ids = cache.refresh_ids(entry, tables)
        if per_table_ids is None:
            # rewrite/reshard/shrink: the mtime-keyed identity backstop —
            # drop the entry, recompute fresh (and re-base below)
            cache.discard(key)
            return None
        tails = [
            table.chunk_view(ids)
            for table, ids in zip(tables, per_table_ids)
            if ids
        ]
        if not tails:
            # no growth: identical repeats are the RESULT cache's job —
            # serving the bytes here would turn the delta cache into a
            # second result cache that ignores RESULT_CACHE_BYTES=0
            return None
        payloads = [ResultPayload.from_bytes(entry["data"])]
        delta_rows = 0
        self.engine.timer = timer
        for view in tails:
            payloads.append(self.engine.execute_local(view, query))
            delta_rows += int(view.nrows)
        with tracing.trace_span("hostmerge"), timer.phase("hostmerge"):
            merged = ResultPayload(hostmerge.merge_payloads(payloads))
        with tracing.trace_span("serialize"), timer.phase("serialize"):
            data = merged.to_bytes()
        cache.store(key, tables, data)
        cache.refreshes += 1
        cache.delta_rows += delta_rows
        self.delta_refreshes_total.inc()
        self._last_merge_mode = "host"
        return data

    def _append_rows(self, msg):
        """The ``rpc.append`` verb: apply a dataframe-like batch of rows to
        a locally served shard.  Column data + chunk indexes commit before
        the meta.json row count (storage.ctable.append_dataframe), so
        concurrent queries on this worker keep a consistent snapshot; the
        stats collector window is dropped so the grown shard advertises
        fresh min/max/cardinality on the next heartbeat."""
        if os.environ.get("BQUERYD_TPU_APPEND", "1") == "0":
            raise ValueError(
                "streaming append disabled on this worker "
                "(BQUERYD_TPU_APPEND=0)"
            )
        from bqueryd_tpu.storage.ctable import ctable

        args, _kwargs = msg.get_args_kwargs()
        if len(args) != 2:
            raise ValueError("append needs (filename, dataframe_like)")
        filename, frame = args
        rootdir = os.path.realpath(os.path.join(self.data_dir, filename))
        if not rootdir.startswith(
            os.path.realpath(self.data_dir) + os.sep
        ):
            raise ValueError(f"path {filename!r} escapes data_dir")
        if not os.path.exists(os.path.join(rootdir, "meta.json")):
            raise ValueError(f"Path {rootdir} does not exist")
        table = ctable(rootdir, mode="a")
        appended = table.append(frame)
        self.appends_total.inc()
        self.append_rows_total.inc(appended)
        collector = self._stats_collector
        if collector is not None:
            collector.invalidate()
        self.flight.record(
            "append", filename=filename, rows=appended,
            total=int(table.nrows),
        )
        reply = msg.copy()
        # the request params carry the whole appended frame — echoing them
        # back worker->controller per holder would double the wire cost
        reply.pop("params", None)
        reply.add_as_binary(
            "result",
            {
                "filename": filename,
                "appended": int(appended),
                "rows": int(table.nrows),
                "worker": self.worker_id,
                "node": self.node_name,
            },
        )
        return reply

    def _rollup_census(self, table):
        """Column metadata the subsumption lattice proves against:
        per-column kind ("int" columns are null-free by dtype — that is
        what licenses key-folds), per-chunk zone maps (what licenses
        zone-proof filter subsumption).  Metadata-only — no chunk decode."""
        import numpy as np

        from bqueryd_tpu.storage.ctable import KIND_DATETIME, KIND_NUMERIC

        cols = {}
        for name in table.names:
            k = table.kind(name)
            if k == KIND_NUMERIC:
                np_kind = np.dtype(table.physical_dtype(name)).kind
                kind = "int" if np_kind in "iu" else "float"
            elif k == KIND_DATETIME:
                kind = "datetime"
            else:
                kind = "dict"
            zones = (
                table.chunk_zone_maps(name)
                if k in (KIND_NUMERIC, KIND_DATETIME) else None
            )
            cols[name] = {
                "kind": kind,
                "zones": zones,
                # float/datetime zone maps skip NaN/NaT rows, so null
                # absence is only ever provable for integer columns
                "nulls": kind != "int",
            }
        return cols

    def _rollup_build(self, msg):
        """The controller-originated ``rollup`` verb: materialize (or
        delta-refresh) the mergeable partials of one hot plan over ONE
        local shard (serve.rollup).  Refresh requests carry the prior
        partials plus the chunk-prefix fingerprint they were computed
        against (``rollup_base``): an exact prefix aggregates only the
        appended tail chunks and hostmerges them into the prior — the
        PR-14 delta discipline — while any rewrite/desync (or a windowed
        plan, whose tail execution path differs) rebuilds from scratch.
        The reply ships partials bytes, the refreshed fingerprint, and
        the column census the subsumption proofs need."""
        from bqueryd_tpu.models.query import GroupByQuery, ResultPayload
        from bqueryd_tpu.ops import workingset
        from bqueryd_tpu.parallel import hostmerge
        from bqueryd_tpu.plan import dag as dagmod

        timer = PhaseTimer()
        args, _kwargs = msg.get_args_kwargs()
        filename, groupby_cols, agg_list, where_terms = args[:4]
        (table,), (identity,) = self._open_unit([filename])
        dag = None
        if msg.get("dag"):
            dag = dagmod.OperatorDAG.from_wire(msg.get_from_binary("dag"))
            dag.sole_payload = False  # rollups store the mergeable form
            query = dag.plain_groupby_query()
        else:
            query = GroupByQuery(
                groupby_cols, agg_list, where_terms or [], aggregate=True
            )
            dag = dagmod.dag_from_query(query)
            query = dag.plain_groupby_query()

        mode = "rebuild"
        data = None
        prior = (
            msg.get_from_binary("rollup_prior")
            if msg.get("rollup_prior") else None
        )
        base = (
            msg.get_from_binary("rollup_base")
            if msg.get("rollup_base") else None
        )
        if prior is not None and base is not None and query is not None:
            new_ids = workingset.growth_since(base, table)
            if new_ids is not None and not new_ids:
                mode, data = "fresh", prior
            elif new_ids is not None:
                self.engine.timer = timer
                tail_payload = self.engine.execute_local(
                    table.chunk_view(new_ids), query
                )
                with tracing.trace_span("hostmerge"), timer.phase(
                    "hostmerge"
                ):
                    merged = hostmerge.merge_payloads(
                        [ResultPayload.from_bytes(prior), tail_payload]
                    )
                data = ResultPayload(merged).to_bytes()
                mode = "delta"
        if data is None:
            if query is not None:
                self.engine.timer = timer
                payload = self.engine.execute_local(table, query)
            else:
                payload = self._execute_dag(
                    [table], dag, timer, (identity,)
                )
            with tracing.trace_span("serialize"), timer.phase("serialize"):
                data = payload.to_bytes()
        self.flight.record(
            "rollup_build", filename=filename, mode=mode,
            bytes=len(data), token=msg.get("token"),
        )
        reply = msg.copy()
        reply.pop("params", None)
        reply.pop("dag", None)
        reply.pop("rollup_prior", None)
        reply.pop("rollup_base", None)
        reply["data"] = data
        reply["rollup_mode"] = mode
        reply["phase_timings"] = timer.as_dict()
        reply.add_as_binary("rollup_base", workingset.table_growth_base(table))
        reply.add_as_binary("rollup_zones", self._rollup_census(table))
        return reply

    def _execute(self, tables, query, timer, identities=None):
        """Psum-mergeable aggregations (any shard count), and distinct
        counts where the payload is the whole answer on a one-device mesh
        (``MeshQueryExecutor.takes``) -> mesh executor (on-device merge +
        HBM-resident caches); other distinct-count / raw-rows single shard
        -> single-device engine; other multi-shard shapes -> per-shard
        engine + host value-keyed merge, each shard's query stripped of
        ``sole_payload`` (a shard's payload is never the whole answer of a
        unit of several: its distinct counts would be added to the other
        shards').  Always returns ONE payload per CalcMessage.  The kernel
        route is the kernel dispatcher's (``ops.groupby.kernel_route``);
        what it took is kept in ``_last_effective_strategy`` for the
        reply.  ``identities``:
        what the unit's open read of each table (``_open_identified``),
        handed on to the mesh executor; None (bare tables) leaves the
        executor to ask the filesystem itself."""
        from bqueryd_tpu.models.query import (
            _host_ns_estimate,
            host_kernel_rows,
        )
        from bqueryd_tpu import ops as ops_mod
        from bqueryd_tpu.parallel import hostmerge
        from bqueryd_tpu.parallel.executor import (
            MeshQueryExecutor,
            view_identities,
        )

        # what the kernel actually ran, for the reply envelope / kernel span
        self._last_effective_strategy = None
        # detail (BQUERYD_TPU_PROFILE=1 only): the form the mesh executor's
        # float64 sums took, the aggregate_wait span's ``float_sum`` tag
        self._last_float_sum = None
        # how this query's partials merged ("device" = ICI-mesh collective,
        # "host" = hostmerge.merge_payloads, "none" = single payload, no
        # merge) — the reply envelope's ``merge_mode`` key
        self._last_merge_mode = None
        # chunk-granular zone-map pruning: a selective filter whose
        # per-chunk min/max prove most chunks unmatchable executes over
        # views of only the surviving chunks — decode, alignment and H2D
        # shrink proportionally.  Basket expansion is excluded (expansion
        # re-selects rows of the same basket living in pruned chunks).
        self._last_chunk_prune = None
        if query.where_terms and not query.expand_filter_column:
            from bqueryd_tpu.ops import predicates

            if predicates.chunk_prune_enabled():
                with tracing.trace_span("prune"), timer.phase("prune"):
                    pruned = [
                        predicates.chunk_pruned_table(t, query.where_terms)
                        for t in tables
                    ]
                decoded = sum(p[1] for p in pruned)
                skipped = sum(p[2] for p in pruned)
                if decoded or skipped:
                    views = [p[0] for p in pruned]
                    identities = view_identities(
                        identities, tables, views,
                        self._identity_passes["recomputed"].inc,
                    )
                    tables = views
                    self.chunks_decoded_total.inc(decoded)
                    self.chunks_skipped_total.inc(skipped)
                    self._last_chunk_prune = (decoded, skipped)
        total_rows = sum(int(t.nrows) for t in tables)
        # the same per-query cost estimate execute_local uses, worst shard
        # wins — a mismatched (optimistic) rate here would let slow-rated
        # queries skip the mesh executor only to device-dispatch per shard.
        # A wedged accelerator backend skips the mesh outright: the engine
        # path below host-routes everything (host_kernel_rows returns its
        # wedged sentinel) instead of hanging on a device dispatch.
        if not devicehealth.backend_wedged() and (
            # a mergeable query asks nothing of the mesh
            MeshQueryExecutor.supports(query)
            or self.mesh_executor.takes(query)
        ) and total_rows > host_kernel_rows(
            max(
                (
                    _host_ns_estimate(t, query.agg_list, total_rows)
                    for t in tables
                ),
                default=None,
            )
        ):
            # single shards go through the mesh executor too: its alignment +
            # HBM block caches make repeat queries one kernel dispatch.
            # Queries at or below the host threshold fall through to the
            # per-shard engine path, whose execute_local picks the host
            # kernel (latency-aware routing, models.query.host_kernel_rows).
            self.mesh_executor.timer = timer
            import jax

            try:
                result = self.mesh_executor.execute(
                    tables, query, identities=identities
                )
                self._last_effective_strategy = (
                    self.mesh_executor.last_effective_strategy
                )
                self._last_float_sum = self.mesh_executor.last_float_sum
                self._last_merge_mode = self.mesh_executor.last_merge_mode
                return result
            except ops_mod.CompositeOverflow:
                # the mesh alignment needs radix-packed composites; a key
                # space past int64 degrades to the per-shard engine path,
                # which factorizes key TUPLES instead of refusing the query
                self.logger.info(
                    "composite key space exceeds int64; serving via the "
                    "per-shard engine path"
                )
            except jax.errors.JaxRuntimeError as exc:
                # wedge survival: a failed device program must not fail the
                # query — the engine path compiles different, smaller
                # programs that usually still succeed (worst case ITS error
                # propagates instead).  That also answers a program the
                # compiler REJECTED from somewhere else, so the firing is
                # counted where a client can read it (debug slice
                # "degrades"; chip_smoke.py fails on it)
                devicehealth.note_degrade("mesh_to_engine")
                self.logger.warning(
                    "mesh executor failed (%s); retrying via the per-shard "
                    "engine path",
                    (str(exc).splitlines() or [""])[0][:200],
                )
        if len(tables) == 1:
            self.engine.timer = timer
            result = self.engine.execute_local(tables[0], query)
            self._last_effective_strategy = (
                self.engine.last_effective_strategy
            )
            self._last_merge_mode = "none"  # one payload, nothing merged
            return result
        self.engine.timer = timer
        # pipelined per-shard fallback: shards run on the bounded pipeline
        # pool (BQUERYD_TPU_PIPELINE_THREADS; 1 restores the serial loop),
        # so shard i+1's decode+factorize overlaps shard i's kernel — the
        # engine's caches are lock-protected and map_ordered returns
        # payloads in input order, keeping hostmerge.merge_payloads
        # deterministic (bit-identical to the serial path)
        from bqueryd_tpu.parallel import pipeline

        shard_query = dataclasses.replace(query, sole_payload=False)
        payloads = pipeline.map_ordered(
            lambda t: self.engine.execute_local(t, shard_query),
            tables,
        )
        # shards share one query shape, so the engine's last route speaks
        # for the group (a host/device split across shards reports the last)
        self._last_effective_strategy = self.engine.last_effective_strategy
        self._last_merge_mode = "host"
        with tracing.trace_span("hostmerge"), timer.phase("hostmerge"):
            merged = hostmerge.merge_payloads(payloads)
        from bqueryd_tpu.models.query import ResultPayload

        return ResultPayload(merged)

    def _execute_dag(self, tables, dag, timer, identities=None):
        """Extended operator-DAG execution (joins / top-k / quantile
        sketches / window rollups).  Device-mergeable shapes (classic +
        top-k + sketch part kinds) take the MESH FAST PATH: one
        decode/align/H2D pass over the whole shard group and one compiled
        mesh program whose span-owned collective merge ships only the
        final table (``merge_mode`` "device") — the same execution
        machinery plain groupbys have had since PR 7.  Everything else —
        count_distinct sets, raw rows, object-dtype derived measures,
        over-budget sketch grids, the ``BQUERYD_TPU_DAG_BATCH=0`` /
        ``BQUERYD_TPU_DEVICE_MERGE=0`` kill switches, wedged backends, or
        a failed device program — falls back to the PR-13 per-shard
        operator pipelines on the stage pool with the host value-keyed
        merge.  Plain DAGs never reach here (handle_work routes them
        through ``_execute`` bit-identically).  ``identities`` as in
        ``_execute``."""
        from bqueryd_tpu.models.query import host_kernel_rows
        from bqueryd_tpu.parallel.opexec import DagExecutor
        from bqueryd_tpu.plan import dag as dagmod

        self._last_chunk_prune = None
        total_rows = sum(int(t.nrows) for t in tables)
        if (
            dagmod.dag_batchable(dag)
            and not devicehealth.backend_wedged()
            and total_rows > host_kernel_rows()
        ):
            import jax

            from bqueryd_tpu import ops as ops_mod
            from bqueryd_tpu.parallel import executor as executor_mod

            self.mesh_executor.timer = timer
            try:
                payload = self.mesh_executor.execute_dag(
                    tables, dag, identities=identities
                )
                self._last_effective_strategy = (
                    self.mesh_executor.last_effective_strategy
                )
                self._last_merge_mode = (
                    self.mesh_executor.last_merge_mode
                )
                self._fold_chunk_prune(
                    self.mesh_executor.last_prune_counts
                )
                return payload
            except executor_mod.DagFastPathUnsupported as exc:
                self.logger.debug(
                    "DAG fast path unavailable (%s); serving via the "
                    "per-shard pipeline", exc,
                )
            except ops_mod.CompositeOverflow:
                self.logger.info(
                    "composite key space exceeds int64; serving the DAG "
                    "via the per-shard pipeline"
                )
            except jax.errors.JaxRuntimeError as exc:
                devicehealth.note_degrade("dag_to_pershard")
                self.logger.warning(
                    "DAG mesh program failed (%s); retrying via the "
                    "per-shard pipeline",
                    (str(exc).splitlines() or [""])[0][:200],
                )
        executor = DagExecutor(self.engine)
        payload = executor.execute(tables, dag, timer=timer)
        self._last_effective_strategy = executor.last_effective_strategy
        self._last_merge_mode = executor.last_merge_mode
        self._fold_chunk_prune(executor._prune_counts)
        return payload

    def _fold_chunk_prune(self, prune_counts):
        """Fold a DAG execution's per-shard (decoded, skipped) chunk-prune
        counts into the worker counters + the prune-span tags."""
        decoded = sum(c[0] for c in prune_counts)
        skipped = sum(c[1] for c in prune_counts)
        if decoded or skipped:
            self.chunks_decoded_total.inc(decoded)
            self.chunks_skipped_total.inc(skipped)
            self._last_chunk_prune = (decoded, skipped)

    def _open_identified(self, rootdir, canonical=None):
        """``(table, identity)``.  Table instances are cached by meta
        identity: re-opening per query costs a meta.json parse per shard;
        activation (fresh inode/mtime) misses naturally.  Instances are
        read-only and light — column bytes live in the storage module's
        global cache, not per instance.

        The ``rootdir_cache_key`` that validates the cached instance is the
        unit's ONE look at the filesystem for this shard, and the identity
        is made from it: ``key + (nrows,)``, letter for letter what
        ``table_cache_key(table)`` would return, so every cache keyed by it
        keeps its entries.  The caller hands it down with the table (result
        cache, delta store, mesh executor).  It is NOT kept on the instance
        or anywhere that outlives the unit: the stat per unit is what makes
        an activation, a movebcolz or an append miss.  ``canonical`` is
        ``realpath(rootdir)`` where the caller has it (``_open_unit``)."""
        from bqueryd_tpu.storage import ctable
        from bqueryd_tpu.storage.ctable import (
            rootdir_cache_key,
            table_cache_key,
        )

        key = rootdir_cache_key(rootdir, canonical)
        self._identity_passes["open"].inc()
        if key is None:
            # no stat-able meta.json: nothing to cache the instance by
            table = ctable(rootdir, mode="r", auto_cache=True)
            return table, table_cache_key(table)
        table = self._table_cache.get(key)
        if table is None:
            table = ctable(rootdir, mode="r", auto_cache=True)
            if len(self._table_cache) > 512:
                self._table_cache.clear()
            self._table_cache[key] = table
        return table, key + (int(table.nrows),)

    def _open_table(self, rootdir):
        """The table alone, for a caller that files nothing under its
        identity (the stats collector)."""
        return self._open_identified(rootdir)[0]

    def _open_unit(self, filenames):
        """``(tables, identities)`` of a unit's shard files, in file
        order: each opened, and its identity read, once.

        The identity's path is the shard's ``realpath``.  Where ``name`` is
        one plain entry of ``data_dir`` and that entry is not a symlink (a
        shard ``movebcolz`` moved in), it is ``join(realpath(data_dir),
        name)``: one ``lstat`` proves it and says the shard exists, and
        ``data_dir`` is resolved once a unit, kept no longer, so a
        re-pointed ``data_dir`` shows at the next unit.  Any other shard (a
        symlink, a nested name, a dangling link, a missing entry) takes
        ``exists`` and a ``realpath`` of its own."""
        real_data_dir = os.path.realpath(self.data_dir)
        tables, identities = [], []
        for name in filenames:
            rootdir = os.path.join(self.data_dir, name)
            try:
                joined = (
                    name not in ("", ".", "..")
                    and os.sep not in name
                    and not stat.S_ISLNK(os.lstat(rootdir).st_mode)
                )
            except (OSError, ValueError):
                joined = False   # missing, or no path: ``exists`` says so
            if joined:
                canonical, form = os.path.join(real_data_dir, name), "joined"
            elif os.path.exists(rootdir):
                canonical, form = None, "resolved"
            else:
                raise ValueError(f"Path {rootdir} does not exist")
            self._identity_paths[form].inc()
            table, identity = self._open_identified(rootdir, canonical)
            tables.append(table)
            identities.append(identity)
        return tables, tuple(identities)

    def handle_work(self, msg):
        if msg.isa("execute_code"):
            return self.execute_code(msg)
        if msg.isa("append"):
            return self._append_rows(msg)
        if msg.isa("rollup"):
            return self._rollup_build(msg)
        if not msg.isa("groupby"):
            return super().handle_work(msg)
        if msg.get("bundle"):
            return self._handle_bundle(msg)

        from bqueryd_tpu import obs
        from bqueryd_tpu.models.query import GroupByQuery, distinct_wire
        from bqueryd_tpu.obs import profile as obs_profile

        # distributed tracing: phases double as spans (PhaseTimer records
        # into the recorder), the worker's "calc" root span parents to the
        # controller's dispatch span via the envelope TraceContext
        recorder = None
        if obs.enabled():
            ctx = obs.TraceContext.from_wire(msg.get_trace())
            recorder = obs.SpanRecorder(
                trace_id=ctx.trace_id if ctx else obs.new_id(16),
                node=self.worker_id,
                root_name="calc",
                root_parent=ctx.span_id if ctx else None,
            )
        timer = PhaseTimer(recorder=recorder, span_names=obs.PHASE_SPAN_NAMES)
        # the compile mark: jit misses of the compile registry before and
        # after this unit (reply key ``compiled``, set only when it rose)
        misses_before = obs_profile.profiler().jit_cache_misses
        with tracing.detail("parse", timer):
            args, kwargs = msg.get_args_kwargs()
            filename, groupby_cols, agg_list, where_terms = args[:4]
            from bqueryd_tpu.plan import dag as dagmod

            # EVERY groupby now compiles through the operator-DAG layer
            # (plan.dag).  A `dag` envelope key is the authoritative program
            # (the rpc.query verb's richer shapes: joins, top-k, sketches,
            # windows); otherwise the classic fragment/params build a plain
            # DAG, whose plain_groupby_query() round trip is field-exact —
            # the engine path below executes it bit-identically to the
            # pre-DAG sequence (proven over the fuzz corpus).
            dag = None
            if msg.get("dag"):
                dag = dagmod.OperatorDAG.from_wire(msg.get_from_binary("dag"))
                dag.sole_payload = bool(msg.get("sole_payload"))
                query = dag.plain_groupby_query()
            else:
                # a planning controller ships the compiled plan fragment
                # alongside the reference-shaped params: the fragment is
                # authoritative (it carries the rewritten query); bare
                # params keep working for mixed-version clusters and direct
                # tests
                fragment = (
                    msg.get_from_binary("plan") if msg.get("plan") else None
                )
                if fragment:
                    from bqueryd_tpu.plan import fragment_to_query

                    query = fragment_to_query(fragment)
                else:
                    query = GroupByQuery(
                        groupby_cols,
                        agg_list,
                        where_terms or [],
                        aggregate=kwargs.get("aggregate", True),
                        expand_filter_column=kwargs.get(
                            "expand_filter_column"
                        ),
                        sole_payload=bool(msg.get("sole_payload")),
                    )
                # round-trip through the DAG layer: compile, then rebuild the
                # query from the compiled form — the pair is field-exact, so
                # execution (and the result-cache key) stays bit-identical
                dag = dagmod.dag_from_query(query)
                query = dag.plain_groupby_query()
            filenames = filename if isinstance(filename, list) else [filename]
        with tracing.trace_span("open"), timer.phase("open"):
            tables, identities = self._open_unit(filenames)
        with tracing.detail("cache_probe", timer):
            cache = self.result_cache
            cache_key = None
            data = None
            if cache is not None:
                cache_key = (
                    identities,
                    # extended DAGs have no GroupByQuery form; their identity
                    # is the DAG signature (join table / window / sketch
                    # params included).  Plain shapes keep the historical
                    # query-signature key, so warm caches survive the DAG
                    # refactor untouched.
                    query.signature() if query is not None
                    else dag.signature(),
                )
                data = cache.get(cache_key)
                if data is not None:
                    timer.timings["result_cache"] = 0.0
            mem_tags = None
            # a result-cache hit compiled nothing: "cached" keeps the reply's
            # route report honest instead of silently dropping the key
            effective = "cached" if data is not None else None
            # delta-maintained serving: on a result-cache miss for a
            # delta-eligible shape, try refreshing a cached result by
            # aggregating ONLY the chunks appended since it was computed
            # (ops.workingset; "delta" in the route report)
            delta_cache = None
            delta_key = None
            if query is not None and self._delta_eligible(query):
                delta_cache = self.delta_cache()
                if delta_cache is not None:
                    delta_key = self._delta_key(identities, query)
        if data is None and delta_cache is not None:
            self._last_merge_mode = None
            data = self._serve_delta(
                delta_cache, delta_key, tables, query, timer
            )
            if data is not None:
                effective = "delta"
                if cache is not None and len(data) <= cache.max_bytes // 8:
                    cache.put(cache_key, data, nbytes=len(data))
        merge_mode = (
            getattr(self, "_last_merge_mode", None)
            if effective == "delta" else None
        )  # otherwise only freshly computed queries merged anything
        if data is None:
            with tracing.detail("mem_sample", timer):
                mem_before = obs_profile.profiler().memory_sample()
            if query is not None:
                # plain shape: the unchanged engine/mesh path —
                # bit-identical to the pre-DAG hardwired sequence
                payload = self._execute(tables, query, timer, identities)
            else:
                payload = self._execute_dag(tables, dag, timer, identities)
            effective = getattr(self, "_last_effective_strategy", None)
            merge_mode = getattr(self, "_last_merge_mode", None)
            # detail: the form the mesh executor's float64 sums took (dense
            # / segmented), which it reports under the profile switch only —
            # where the aggregate_wait span that carries it exists
            float_sum = (
                getattr(self, "_last_float_sum", None)
                if query is not None else None
            )
            # the form this payload's distinct counts leave in: final
            # counts, or value sets for a merge downstream
            wire = distinct_wire(payload)
            if wire is not None:
                self._distinct_payloads[wire].inc()
                for span in recorder.spans if recorder is not None else ():
                    if span.get("name") == "distinct_wait":
                        span.setdefault("tags", {}).update(
                            wire=wire,
                            rows=sum(int(t.nrows) for t in tables),
                        )
            if recorder is not None and effective:
                # the kernel span carries what the executor actually
                # compiled post-guards — rpc.trace() waterfalls can now
                # tell a promoted matmul from a silently-normalized hint
                for span in recorder.spans:
                    if span.get("name") == "float_sum_wait" and float_sum:
                        span.setdefault("tags", {})["form"] = float_sum
                    if span.get("name") in ("kernel", "aggregate_wait"):
                        tags = span.setdefault("tags", {})
                        tags["effective_strategy"] = effective
                        if span["name"] == "aggregate_wait":
                            # a detail span (switch only): which merge this
                            # launch ran, over how many devices — a degraded
                            # fetch does not pass for the normal path
                            if float_sum:
                                tags["float_sum"] = float_sum
                            tags["merge_mode"] = merge_mode
                            tags["devices"] = int(
                                self.mesh_executor.mesh.devices.size
                            )
            if float_sum:
                self.metrics.counter(
                    "bqueryd_tpu_float_sum_total",
                    "mesh-executor queries by the form their float64 sums "
                    "took (ops.float_sum_route); counted on workers "
                    "started with BQUERYD_TPU_PROFILE=1 only",
                    labels={"form": float_sum},
                ).inc()
            if recorder is not None and self._last_chunk_prune:
                # zone-map pruning effect on the trace: the prune span
                # says how many chunks the decode stages never touched
                decoded_n, skipped_n = self._last_chunk_prune
                for span in recorder.spans:
                    if span.get("name") == "prune":
                        tags = span.setdefault("tags", {})
                        tags["chunks_decoded"] = decoded_n
                        tags["chunks_skipped"] = skipped_n
                        break
            # the execute above is proof the backend answered: safe to
            # (lazily) enumerate devices for HBM sampling from now on
            with tracing.detail("mem_sample", timer):
                obs_profile.profiler().note_devices()
                mem_after = obs_profile.profiler().memory_sample()
            if mem_after is not None:
                # device-memory attribution on the calc root span (visible
                # in rpc.trace waterfalls).  peak_bytes_in_use is the
                # allocator's PROCESS-LIFETIME watermark, so it is reported
                # as exactly that; the per-QUERY attribution is the pair of
                # deltas — how much this query raised the watermark, and
                # what it added to live device memory
                before = mem_before or mem_after
                mem_tags = {
                    "device_hbm_watermark_bytes":
                        mem_after["peak_bytes_in_use"],
                    "device_peak_delta_bytes": (
                        mem_after["peak_bytes_in_use"]
                        - before["peak_bytes_in_use"]
                    ),
                    "device_bytes_delta": (
                        mem_after["bytes_in_use"] - before["bytes_in_use"]
                    ),
                }
            with tracing.trace_span("serialize"), timer.phase("serialize"):
                data = payload.to_bytes()
            with tracing.detail("cache_probe", timer):
                if cache is not None and len(data) <= cache.max_bytes // 8:
                    cache.put(cache_key, data, nbytes=len(data))
                if delta_cache is not None:
                    # record the delta base: the snapshots of the very table
                    # instances this result was computed from, so a later
                    # append refreshes it from the tail alone
                    delta_cache.store(delta_key, tables, data)
        if obs.enabled():
            # result-payload size per reply — observed for cache hits too,
            # so this histogram and its controller-side twin
            # (reply_payload_bytes) count the same replies and the bench's
            # merge section can cross-check them
            self.reply_bytes.observe(len(data))
        # a result comparable to the worker's memory budget (1/32 of the
        # restart limit, 640 MB at the default 20 GB) means the query caches
        # are the next thing to evict
        if self.memory_limit_mb and sys.getsizeof(data) > (
            self.memory_limit_mb * (1 << 20) // 32
        ):
            self._shed_caches()
        reply = msg.copy()
        # the reply must not echo the request's DAG (the broadcast join
        # ships the whole dimension table under that key — re-shipping it
        # worker->controller per shard reply is pure wire waste; the
        # controller only consults the key on ERROR replies, which keep it)
        reply.pop("dag", None)
        reply["data"] = data
        reply["phase_timings"] = self._with_post_prev(timer.as_dict())
        compiled = obs_profile.profiler().jit_cache_misses - misses_before
        if compiled > 0:
            # this unit compiled (or loaded from the persistent cache) that
            # many programs; the key is absent from a steady-state reply
            reply["compiled"] = compiled
        if recorder is not None:
            # the span list rides the JSON reply; the controller folds it
            # into the query timeline behind rpc.trace(trace_id); device
            # memory attribution tags the calc root span
            reply["spans"] = recorder.export(tags=mem_tags)
            self.groupby_queries.inc()
            self.groupby_seconds.observe(timer.total())
            self._observe_phase_histograms(timer)
        # deadline propagation: the reply keeps the envelope's ``deadline``
        # (msg.copy) and reports the budget left after execution
        remaining = msg.deadline_remaining()
        if remaining is not None:
            reply["deadline_remaining"] = round(remaining, 4)
        if effective is not None:
            # the route the kernel rule took: declared in
            # messages.RESULT_ENVELOPE_SCHEMA/ENVELOPE_SCHEMA, folded by the
            # controller into the client result envelope
            reply["effective_strategy"] = effective
        if merge_mode is not None:
            # how this reply's partials merged: "device" (ICI-mesh
            # collective, final table only fetched), "host"
            # (hostmerge.merge_payloads — the kill switch / non-mergeable
            # fallback), or "none" (single payload).  Declared in
            # messages.ENVELOPE_SCHEMA; the controller folds it into the
            # client result envelope's merge_modes
            reply["merge_mode"] = merge_mode
        self.logger.debug("calc %s done: %s", filename, timer.as_dict())
        return reply

    def _with_post_prev(self, timings):
        """Under BQUERYD_TPU_PROFILE=1 the unit BEFORE this one left its
        ``send`` / ``post`` detail spans behind (handle()): their seconds
        ride this reply as ``post_prev``.  No phase of this reply's wall —
        whatever sums ``phase_timings`` against ``_total`` skips it."""
        spans = self._after_reply.recorder.spans
        if spans:
            timings["post_prev"] = sum(s["duration_s"] for s in spans)
            del spans[:]
        return timings

    def _observe_phase_histograms(self, timer):
        """One ``bqueryd_tpu_query_phase_seconds{phase=...}`` observation
        per timed phase — the single registration site both groupby reply
        paths (solo and bundle) share, so the family's help text and label
        mapping can never diverge between them."""
        from bqueryd_tpu import obs

        for phase, seconds in timer.timings.items():
            self.metrics.histogram(
                "bqueryd_tpu_query_phase_seconds",
                "per-phase worker latency (storage decode, H2D, "
                "kernel, merge, ...)",
                labels={"phase": obs.PHASE_SPAN_NAMES.get(phase, phase)},
            ).observe(seconds)

    def _bundle_mesh_eligible(self, tables, queries):
        """Mirror of the single-query ``_execute`` routing decision for a
        whole bundle: the shared-scan mesh path runs when every member is
        mergeable, the backend is healthy, and the row count clears the
        host-kernel threshold (worst member's rate estimate wins)."""
        from bqueryd_tpu.models.query import (
            _host_ns_estimate,
            host_kernel_rows,
        )
        from bqueryd_tpu.parallel.executor import MeshQueryExecutor

        if devicehealth.backend_wedged():
            return False
        if not all(MeshQueryExecutor.supports(q) for q in queries):
            return False
        total_rows = sum(int(t.nrows) for t in tables)
        try:
            worst = max(
                (
                    _host_ns_estimate(t, q.agg_list, total_rows)
                    for t in tables
                    for q in queries
                ),
                default=None,
            )
        except Exception:
            # an unestimable member (e.g. a column the shard doesn't have)
            # routes the bundle to the per-member path, where the offender
            # errors ALONE instead of failing its bundle-mates
            return False
        return total_rows > host_kernel_rows(worst)

    def _handle_bundle(self, msg):
        """Shared-scan bundle execution: one CalcMessage carrying several
        compatible member queries (``plan.bundle``).  Scan work — open,
        decode, align/factorize, uploads — happens once; each member keeps
        its own identity: per-member result-cache keys, per-member deadline
        enforcement (an expired member is dropped from the stack, not the
        bundle), per-member error isolation on the fallback path.  The
        reply demultiplexes through the ``bundle_members`` wire key: its
        data frame is one pickled ``{"payloads": {member_id: bytes},
        "errors": {member_id: text}}`` envelope."""
        import pickle

        from bqueryd_tpu import chaos, obs
        from bqueryd_tpu.obs import profile as obs_profile
        from bqueryd_tpu.plan import bundle as bundlemod

        recorder = None
        if obs.enabled():
            ctx = obs.TraceContext.from_wire(msg.get_trace())
            recorder = obs.SpanRecorder(
                trace_id=ctx.trace_id if ctx else obs.new_id(16),
                node=self.worker_id,
                root_name="calc",
                root_parent=ctx.span_id if ctx else None,
            )
        timer = PhaseTimer(recorder=recorder, span_names=obs.PHASE_SPAN_NAMES)
        misses_before = obs_profile.profiler().jit_cache_misses
        with tracing.detail("parse", timer):
            fragment = msg.get_from_binary("bundle")
            members = bundlemod.bundle_to_queries(fragment)
            filename = msg.get("filename") or fragment.get("filenames")
            filenames = filename if isinstance(filename, list) else [filename]
        with tracing.trace_span("open"), timer.phase("open"):
            tables, tables_sig = self._open_unit(filenames)

        with tracing.detail("cache_probe", timer):
            cache = self.result_cache
            payloads = {}      # member_id -> serialized ResultPayload bytes
            errors = {}        # member_id -> failure text (member-only abort)
            active = []        # (member_id, query) still needing execution
            now = time.time()
            for member_id, deadline, query in members:
                if deadline is not None and float(deadline) <= now:
                    # the member's budget is gone: drop it from the stack, not
                    # the bundle — its bundle-mates keep their answers
                    errors[member_id] = (
                        f"deadline exceeded "
                        f"{now - float(deadline):.3f}s before execution"
                    )
                    continue
                if cache is not None:
                    hit = cache.get((tables_sig, query.signature()))
                    if hit is not None:
                        payloads[member_id] = hit
                        continue
                active.append((member_id, query))

        # per-member segment shares (messages.py `member_shares`): measured
        # walls on the fallback path, an equal split on the one-program
        # mesh path; cached members report 0.0 (they consumed no scan)
        cached_ids = list(payloads)
        member_walls = {}
        results = {}
        if active:
            queries = [q for _mid, q in active]
            mesh_payloads = None
            if self._bundle_mesh_eligible(tables, queries):
                import jax

                from bqueryd_tpu import ops as ops_mod

                try:
                    mesh_payloads = self.mesh_executor_for_bundle(
                        tables, queries, timer, tables_sig
                    )
                except chaos.TransientError:
                    # a transient device fault fails the whole bundle over
                    # to a replica holder — never silently degrades one
                    # member
                    raise
                except (
                    ops_mod.CompositeOverflow,
                    jax.errors.JaxRuntimeError,
                ) as exc:
                    if isinstance(exc, jax.errors.JaxRuntimeError):
                        devicehealth.note_degrade("bundle_to_members")
                    self.logger.warning(
                        "bundle mesh path failed (%s); retrying members "
                        "via the per-member engine path",
                        (str(exc).splitlines() or [""])[0][:200],
                    )
                except ValueError as exc:
                    # a member-shape rejection (e.g. datetime sum) must
                    # isolate to the per-member path, where the offender
                    # errors alone
                    self.logger.info(
                        "bundle mesh path rejected (%s); running members "
                        "individually", exc,
                    )
            if mesh_payloads is not None:
                results = dict(zip((m for m, _q in active), mesh_payloads))
            else:
                for member_id, query in active:
                    try:
                        exec_clock = time.perf_counter()
                        results[member_id] = self._execute(
                            tables, query, timer, tables_sig
                        )
                        member_walls[member_id] = (
                            time.perf_counter() - exec_clock
                        )
                    except chaos.TransientError:
                        raise  # whole-bundle failover, as above
                    except Exception as exc:
                        self.logger.exception(
                            "bundle member %s failed", member_id
                        )
                        errors[member_id] = (
                            f"{type(exc).__name__}: {exc}"
                        )

        with tracing.trace_span("serialize"), timer.phase("serialize"):
            for member_id, payload in results.items():
                data = payload.to_bytes()
                payloads[member_id] = data
                if cache is not None and len(data) <= cache.max_bytes // 8:
                    query = next(
                        q for mid, q in active if mid == member_id
                    )
                    cache.put(
                        (tables_sig, query.signature()), data,
                        nbytes=len(data),
                    )
            data = pickle.dumps(
                {"v": 1, "payloads": payloads, "errors": errors},
                protocol=4,
            )
        if obs.enabled():
            self.reply_bytes.observe(len(data))
        # same memory backstop as the solo reply path — a bundle envelope
        # is ~N solo payloads in one message, the LARGEST reply this
        # worker produces, so the cache shed matters here most
        if self.memory_limit_mb and sys.getsizeof(data) > (
            self.memory_limit_mb * (1 << 20) // 32
        ):
            self._shed_caches()
        reply = msg.copy()
        reply["data"] = data
        reply["bundle_members"] = [mid for mid, _dl, _q in members]
        reply["member_shares"] = {
            **{mid: 0.0 for mid in cached_ids},
            **bundlemod.member_shares(list(results), walls=member_walls),
        }
        reply["phase_timings"] = self._with_post_prev(timer.as_dict())
        compiled = obs_profile.profiler().jit_cache_misses - misses_before
        if compiled > 0:
            reply["compiled"] = compiled
        if recorder is not None:
            reply["spans"] = recorder.export()
            # one CalcMessage executed, whatever its member count (the
            # counter's help text promise); member volume is the
            # controller's plan_bundled_queries
            self.groupby_queries.inc()
            self.groupby_seconds.observe(timer.total())
            # same per-phase histograms as the solo reply path: with the
            # window on, bundles ARE the dominant serving path — a phase
            # regression there must not vanish from the very histograms
            # built to catch it
            self._observe_phase_histograms(timer)
        # route/merge visibility mirrors the single-query reply: the last
        # executed route speaks for the bundle (members share one shape);
        # "cached" only when cache hits actually served members — a bundle
        # whose members ALL errored pre-execution served nothing
        effective = (
            getattr(self, "_last_effective_strategy", None)
            if active
            else ("cached" if payloads else None)
        )
        merge_mode = (
            getattr(self, "_last_merge_mode", None) if active else None
        )
        if effective is not None:
            reply["effective_strategy"] = effective
        if merge_mode is not None:
            reply["merge_mode"] = merge_mode
        self.logger.debug(
            "bundle calc %s done: %d members (%d cached/served, %d "
            "errored): %s",
            filename, len(members),
            len(payloads) - len(results), len(errors), timer.as_dict(),
        )
        return reply

    def mesh_executor_for_bundle(self, tables, queries, timer,
                                 identities=None):
        """Run the shared-scan mesh path for a bundle (seam kept separate
        so tests can spy on it): returns per-member ResultPayloads."""
        self._last_effective_strategy = None
        self._last_merge_mode = None
        self.mesh_executor.timer = timer
        payloads = self.mesh_executor.execute_bundle(
            tables, queries, identities=identities
        )
        self._last_effective_strategy = (
            self.mesh_executor.last_effective_strategy
        )
        self._last_merge_mode = self.mesh_executor.last_merge_mode
        return payloads

    def execute_code(self, msg):
        """Import a dotted function path and call it — the reference's
        deliberate remote-execution feature for trusted clusters (reference
        bqueryd/worker.py:250-267, warned in reference README.md:129).
        Gated: set BQUERYD_TPU_ENABLE_EXECUTE_CODE=1 to enable."""
        if os.environ.get("BQUERYD_TPU_ENABLE_EXECUTE_CODE") != "1":
            raise PermissionError(
                "execute_code disabled; set BQUERYD_TPU_ENABLE_EXECUTE_CODE=1"
            )
        args, kwargs = msg.get_args_kwargs()
        function = msg.get("function") or kwargs.pop("function", None)
        if not function:
            raise ValueError("execute_code needs a function=module.path.fn")
        # reference calling convention (reference bqueryd/worker.py:250-267):
        # the function's positional/keyword args travel as the RPC kwargs
        # `args=[...]` / `kwargs={...}`
        call_args = kwargs.pop("args", None) or list(args)
        call_kwargs = kwargs.pop("kwargs", None) or {}
        # any other keywords are the function's own (direct-kwarg convention)
        call_kwargs = {**kwargs, **call_kwargs}
        module_name, _, fn_name = function.rpartition(".")
        fn = getattr(importlib.import_module(module_name), fn_name)
        result = fn(*call_args, **call_kwargs)
        reply = msg.copy()
        reply.add_as_binary("result", result)
        return reply


class DownloaderNode(WorkerBase):
    """Ticket-driven blob downloader (reference bqueryd/worker.py:351-567).
    Full pipeline logic in bqueryd_tpu.download (phase: distribution).

    Fetches run on a small thread pool (the reference ran 3 downloader
    *processes* per box, reference misc/supervisor.conf) so a slow or hung
    blob stream never blocks the event loop: ticket polling, WRM heartbeats,
    and cancellation stay live during long downloads.  Pool threads never
    touch the zmq socket — controller notifications go through a thread-safe
    outbox drained by the event loop."""

    workertype = "download"

    def __init__(self, *args, **kw):
        download_threads = kw.pop("download_threads", None)
        kw.setdefault("heartbeat_interval", DEFAULT_HEARTBEAT_INTERVAL)
        super().__init__(*args, **kw)
        self.download_interval = DOWNLOAD_DELAY
        self._last_download_check = 0.0
        if download_threads is None:
            download_threads = int(
                os.environ.get("BQUERYD_TPU_DOWNLOAD_THREADS", "3")
            )
        self.download_threads = max(1, download_threads)
        self._download_pool = None
        self.downloads_done = self.metrics.counter(
            "bqueryd_tpu_downloads_total",
            "download tickets completed by this node",
        )
        self.downloads_failed = self.metrics.counter(
            "bqueryd_tpu_download_failures_total",
            "download tickets failed terminally by this node",
        )
        import queue

        self._outbox = queue.Queue()

    @property
    def download_pool(self):
        if self._download_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._download_pool = ThreadPoolExecutor(
                max_workers=self.download_threads,
                thread_name_prefix=f"dl-{self.worker_id[:6]}",
            )
        return self._download_pool

    def heartbeat(self):
        super().heartbeat()
        self._drain_outbox()
        now = time.time()
        if now - self._last_download_check >= self.download_interval:
            self._last_download_check = now
            try:
                self.check_downloads()
            except Exception:
                self.logger.exception("error checking downloads")

    def _drain_outbox(self):
        """Send controller notifications queued by pool threads (zmq sockets
        are single-thread-only, so only the event loop may send)."""
        import queue

        while True:
            try:
                msg = self._outbox.get_nowait()
            except queue.Empty:
                return
            self.send_to_all(msg)

    def stop(self):
        if self._request_stop_only():
            return  # outbox/socket teardown belongs to the loop thread
        if self._download_pool is not None:
            self._download_pool.shutdown(wait=False, cancel_futures=True)
        self._drain_outbox()
        super().stop()

    def check_downloads(self):
        from bqueryd_tpu.download import check_downloads

        check_downloads(self)

    def run_download(self, ticket, fileurl, lock):
        """Run one claimed download on the pool; the claim lock is held for
        the download's lifetime and released by the pool thread."""

        def job():
            try:
                self.download_file(ticket, fileurl, lock=lock)
            except Exception as exc:
                self.logger.exception("download %s failed", fileurl)
                self.fail_ticket(ticket, fileurl, str(exc))
            finally:
                lock.release()

        self.download_pool.submit(job)

    def download_file(self, ticket, fileurl, lock=None):
        from bqueryd_tpu.download import download_file

        download_file(self, ticket, fileurl, lock=lock)

    def file_downloader_progress(self, ticket, fileurl, progress):
        from bqueryd_tpu.download import set_progress

        set_progress(self.store, self.node_name, ticket, fileurl, progress)

    def remove_ticket(self, ticket):
        from bqueryd_tpu.download import remove_ticket

        remove_ticket(self, ticket)
        self.downloads_done.inc()
        self._outbox.put(TicketDoneMessage({"ticket": ticket}))

    def fail_ticket(self, ticket, fileurl, error):
        """Terminal download failure: poison the ticket (ERROR slot blocks
        activation on every node) and tell controllers so waiting clients get
        the error instead of the reference's false DONE."""
        from bqueryd_tpu.download import fail_ticket

        fail_ticket(self, ticket, fileurl, error)
        self.downloads_failed.inc()
        self._outbox.put(
            TicketDoneMessage({"ticket": ticket, "error": str(error)})
        )


class MoveBcolzNode(DownloaderNode):
    """Second phase of the two-phase distribute commit: flips downloaded
    shards into the serving dir only when every node finished (reference
    bqueryd/worker.py:570-637)."""

    workertype = "movebcolz"

    def check_downloads(self):
        from bqueryd_tpu.download import check_moves

        check_moves(self)
