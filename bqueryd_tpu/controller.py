"""ControllerNode: the broker — discovery, scheduling, fan-out, sink merge.

Re-design of the reference controller (reference bqueryd/controller.py:28-578)
with the same observable surface (verbs, WRM registration cycle, dead-worker
cull, affinity queues, peer gossip) and three deliberate changes:

* **results are small**: workers return partial aggregation tables (or
  filtered rows), already merged across their local device mesh, so the sink
  keeps payloads in memory instead of spooling tar files to disk (reference
  bqueryd/controller.py:174-211);
* **dispatch is tracked**: every in-flight shard has a timestamp and is
  re-queued (bounded retries) if its worker dies or times out — the TODO the
  reference never implemented (reference bqueryd/controller.py:265);
* **the controller never imports JAX or pandas** — merging partial tables is
  the client's job (value-keyed NumPy merge), keeping the broker cheap.

Wire framing on the single ROUTER socket (identity ``tcp://ip:port``, random
port in 14300-14399, reference bqueryd/controller.py:33-42):

* 3 frames with empty middle  = RPC request from a REQ client
* 3 frames, non-empty middle  = worker reply carrying a binary result frame
* 2 frames                    = worker/peer control message
"""

import base64
import binascii
import os
import pickle
import random
import signal
import threading
import time

import zmq

import bqueryd_tpu
from bqueryd_tpu import backoff, chaos, messages
from bqueryd_tpu.coordination import coordination_store
from bqueryd_tpu.messages import (
    BusyMessage,
    CalcMessage,
    DoneMessage,
    ErrorMessage,
    Message,
    RPCMessage,
    StopMessage,
    TicketDoneMessage,
    WorkerRegisterMessage,
    msg_factory,
)
from bqueryd_tpu.utils.env import env_num
from bqueryd_tpu.utils.net import bind_to_random_port, get_my_ip

POLLING_TIMEOUT = 0.5        # seconds
DEAD_WORKER_TIMEOUT = 60.0   # cull workers silent longer than this
HEARTBEAT_INTERVAL = 2.0     # store re-registration + peer sync period
DISPATCH_TIMEOUT = 120.0     # re-queue in-flight work after this
DISPATCH_HARD_TIMEOUT = 1800.0  # ...even if the worker still heartbeats
MAX_DISPATCH_RETRIES = 2
RUNFILE_DIR = os.environ.get("BQUERYD_TPU_RUNFILE_DIR", "/srv")
#: failover pacing: exponential backoff between dispatch attempts of one
#: shard (base * 2^retries, capped) plus a deterministic per-token jitter so
#: a burst of simultaneous failovers doesn't stampede the surviving holder
#: (shared formula: bqueryd_tpu.backoff — the RPC client retries use it too)
RETRY_BACKOFF_BASE_S = backoff.BACKOFF_BASE_S
RETRY_BACKOFF_CAP_S = backoff.BACKOFF_CAP_S


# env-tunable timing knobs: the registered BQUERYD_TPU_* override when
# parseable, the module-constant default otherwise (chaos scenarios and
# small test clusters shrink these without monkeypatching)
_env_num = env_num

CONTROLLER_VERBS = (
    "ping", "loglevel", "info", "kill", "killworkers", "killall",
    "download", "readfile", "execute_code", "sleep", "groupby", "query",
    "trace", "metrics", "slow_queries", "health", "debug_bundle",
    "autopsy", "timeline", "capacity", "append",
)

#: how long an append fan-out may wait for every holder's reply before the
#: client gets a structured partial-failure error (a client deadline, when
#: set, wins)
APPEND_TIMEOUT = 120.0

#: help text for every controller counter — the spec the registry-backed
#: ``counters`` dict (obs.metrics.RegistryCounters) is built from; the same
#: keys keep working as plain dict entries everywhere (tests, bench, info)
COUNTER_SPECS = {
    "plan_pruned_shards": "shards excluded at plan time by advertised stats",
    "plan_shared_dispatches":
        "concurrent queries that joined an existing dispatch instead of "
        "paying their own (identical-work dedup + shared-scan bundle "
        "members beyond the first)",
    "plan_bundles":
        "shared-scan bundle dispatches: one decode/align/upload pass and "
        "one mesh program serving a whole compatible micro-batch",
    "plan_bundled_queries":
        "member queries that rode a shared-scan bundle dispatch",
    "admission_busy": "BUSY backpressure replies sent to clients",
    "admission_queued": "plans held in the admission wait queue",
    "admission_superseded": "abandoned queries retired early on resend",
    "deadline_expired": "work expired by its deadline before running",
    "dispatched_shards": "groupby CalcMessages sent to workers",
    "queries_completed": "groupby parents finished (reply sent or aborted)",
    "slow_queries": "finished queries past BQUERYD_TPU_SLOW_QUERY_MS",
    "health_avoided_dispatches":
        "dispatch decisions that routed around a degraded/wedged worker",
    "reply_payload_bytes":
        "cumulative result-payload bytes received in worker calc replies "
        "(the controller-side twin of the worker's reply_bytes histogram)",
    "failover_dispatches":
        "shards re-queued after a worker loss, timeout, or transient fault "
        "(the retry excludes the failed holder)",
    "transient_faults":
        "transient (retryable) worker error replies that triggered a "
        "shard failover instead of a query abort",
    "hedged_dispatches":
        "duplicate tail-shard dispatches issued past BQUERYD_TPU_HEDGE_MS",
    "hedge_wins":
        "hedged dispatches whose duplicate replied before the original",
    "duplicate_replies":
        "worker replies deduplicated by query token (hedge losers, "
        "late retries, chaos-duplicated envelopes) — counted, never "
        "double-merged",
    "capacity_scale_up_advised":
        "shadow-advisor scale_up recommendations emitted (advisory only — "
        "logged to the flight ring, never acted on)",
    "capacity_scale_down_advised":
        "shadow-advisor scale_down recommendations emitted (advisory only)",
    "capacity_rebalance_advised":
        "shadow-advisor shard-rebalance recommendations emitted (advisory "
        "only)",
    "append_requests":
        "client rpc.append calls accepted for fan-out to shard holders",
    "append_dispatches":
        "per-holder append CalcMessages dispatched (one per distinct "
        "(node, data_dir) replica of the target shard)",
    "rollup_builds":
        "materialized-rollup shard builds completed (serve.rollup full "
        "rebuilds; delta refreshes count separately)",
    "rollup_refreshes":
        "rollup shard refreshes served by aggregating only appended tail "
        "chunks (growth_since exact-prefix validation)",
    "rollup_evictions":
        "rollup entries dropped by the retention sweep (count/byte caps, "
        "wedged-build timeout)",
}


class ControllerNode:
    def __init__(
        self,
        coordination_url=None,
        redis_url=None,
        loglevel=None,
        runfile_dir=RUNFILE_DIR,
        heartbeat_interval=HEARTBEAT_INTERVAL,
        dead_worker_timeout=None,
        dispatch_timeout=None,
        dispatch_hard_timeout=None,
        port_range=(14300, 14400),
        admit_max_active=None,
        admit_queue_depth=None,
        admit_client_quota=None,
        max_dispatch_retries=None,
        hedge_ms=None,
    ):
        import logging

        bqueryd_tpu.configure_logging(loglevel or logging.INFO)
        # fault injection (bqueryd_tpu.chaos): armed only when
        # BQUERYD_TPU_FAULT_PLAN is set; every injection site below is a
        # single None check otherwise
        chaos.maybe_arm_from_env()
        self.store = coordination_store(
            coordination_url or redis_url or bqueryd_tpu.DEFAULT_COORDINATION_URL
        )
        self.heartbeat_interval = heartbeat_interval
        # timing knobs resolve ctor arg -> registered env var -> module
        # constant, so chaos scenarios can shrink them per process
        if dead_worker_timeout is None:
            dead_worker_timeout = _env_num(
                "BQUERYD_TPU_DEAD_WORKER_TIMEOUT", DEAD_WORKER_TIMEOUT
            )
        if dispatch_timeout is None:
            dispatch_timeout = _env_num(
                "BQUERYD_TPU_DISPATCH_TIMEOUT", DISPATCH_TIMEOUT
            )
        if dispatch_hard_timeout is None:
            dispatch_hard_timeout = _env_num(
                "BQUERYD_TPU_DISPATCH_HARD_TIMEOUT", DISPATCH_HARD_TIMEOUT
            )
        self.dead_worker_timeout = dead_worker_timeout
        self.dispatch_timeout = dispatch_timeout
        self.dispatch_hard_timeout = max(dispatch_hard_timeout, dispatch_timeout)
        self.max_dispatch_retries = (
            max_dispatch_retries
            if max_dispatch_retries is not None
            else _env_num(
                "BQUERYD_TPU_MAX_DISPATCH_RETRIES", MAX_DISPATCH_RETRIES, int
            )
        )
        # hedged duplicate dispatch for tail shards: 0 (the default) is OFF;
        # >0 duplicates a shard still inflight past this many milliseconds
        # onto a second healthy holder, first reply wins (dedup by token)
        self.hedge_ms = (
            hedge_ms if hedge_ms is not None
            else _env_num("BQUERYD_TPU_HEDGE_MS", 0.0)
        )
        # replica placement hint: download fan-out targets this many holders
        # per shard (0 = every node, the historical behaviour; see
        # download.setup_download); surfaced in get_info and the
        # replica_holders gauges so under-replication is visible
        self.replica_factor = max(
            _env_num("BQUERYD_TPU_REPLICA_FACTOR", 0, int), 0
        )

        self.context = zmq.Context.instance()
        self.socket = self.context.socket(zmq.ROUTER)
        self.socket.setsockopt(zmq.ROUTER_MANDATORY, 1)
        self.socket.setsockopt(zmq.SNDTIMEO, 1000)
        self.socket.setsockopt(zmq.LINGER, 500)
        ip = get_my_ip()
        self.address = bind_to_random_port(
            self.socket, f"tcp://{ip}", port_range[0], port_range[1]
        )
        self.logger = bqueryd_tpu.logger.getChild(f"controller.{self.address}")
        self.node_name = __import__("socket").gethostname()

        self.poller = zmq.Poller()
        self.poller.register(self.socket, zmq.POLLIN)

        # state
        self.worker_map = {}          # worker_id -> wrm info (+ last_seen/busy)
        self._adoption_blocked = {}   # worker_id -> until-ts (hb-only quarantine)
        self.files_map = {}           # filename -> set(worker_id)
        self.others = {}              # peer address -> info
        self.worker_out_messages = {None: []}  # affinity -> [msg, ...]
        self._affinity_rr = 0
        self.rpc_segments = {}        # parent_token -> fan-out bookkeeping
        self.inflight = {}            # shard token -> dict(worker, sent_at, msg, parent)
        self._hedged_tokens = {}      # token -> hedge ts (late-reply dedup)
        self._hedge_losers = {}       # token -> dict(workers, since): reclaim
        #                               handle on the non-winning side of a
        #                               hedge (its inflight entry is gone)
        self._requeued_tokens = set()  # retries parked in the dispatch queue
        #                                (backoff window): a late reply from
        #                                the failed attempt must not abort
        #                                or double-execute past them
        # streaming-append fan-out bookkeeping (rpc_append): one segment
        # per client call, one dispatch token per replica holder
        self._append_segments = {}    # segment key -> fan-out state
        self._append_waiters = {}     # dispatch token -> segment key
        self._holder_counts_memo = None  # (ts, counts) scrape-window memo
        # -- planning & admission state -------------------------------------
        from bqueryd_tpu.plan import AdmissionController

        self.admission = AdmissionController(
            max_active=admit_max_active,
            queue_depth=admit_queue_depth,
            client_quota=admit_client_quota,
        )
        self._admitting = False
        self._ticket_sigs = {}        # live ticket -> plan signature
        self.shard_stats = {}         # filename -> advertised planning stats
        # -- semantic serving (PR 16) ---------------------------------------
        # subsumption lattice + materialized-rollup manager (serve/): hit
        # replies skip admission entirely; BQUERYD_TPU_SERVE=0 makes every
        # entry point a no-op without tearing the object down
        from bqueryd_tpu.serve import ServingLayer

        self.serving = ServingLayer(self)
        self._rollup_waiters = {}     # dispatch token -> (entry key, filename)
        self._work_subscribers = {}   # shard token -> [parent_token, ...]
        self._work_keys = {}          # shard token -> shared-dispatch key
        self._work_index = {}         # shared-dispatch key -> shard token
        # admission micro-batch window (plan.bundle): admitted groupby
        # plans staged here until the window closes, then flushed grouped
        # by compatibility signature; empty (and bypassed) at window 0
        self._pending_window = []     # [(msg, plan, kwargs), ...]
        self._window_opened = 0.0
        # -- observability ---------------------------------------------------
        from bqueryd_tpu import obs

        self.metrics = obs.MetricsRegistry()
        # the ad-hoc counters dict, migrated: same dict surface, every write
        # mirrored into a typed registry Counter (Prometheus exposition)
        self.counters = obs.RegistryCounters(self.metrics, COUNTER_SPECS)
        # liveness gauges are callback-backed: read at scrape time, no upkeep
        self.metrics.gauge(
            "bqueryd_tpu_admission_active",
            "plans currently executing", fn=lambda: len(self.admission._active),
        )
        self.metrics.gauge(
            "bqueryd_tpu_admission_queue_depth",
            "plans waiting in the admission queue",
            fn=lambda: len(self.admission._queued),
        )
        self.metrics.gauge(
            "bqueryd_tpu_inflight_shards",
            "shard dispatches awaiting a worker reply",
            fn=lambda: len(self.inflight),
        )
        self.metrics.gauge(
            "bqueryd_tpu_workers_known",
            "workers currently registered", fn=lambda: len(self.worker_map),
        )
        self.metrics.gauge(
            "bqueryd_tpu_fault_injected_total",
            "faults injected by the armed chaos plan, process-lifetime "
            "(0 while BQUERYD_TPU_FAULT_PLAN is unarmed)",
            fn=chaos.injected_total,
        )
        # replica visibility: shards by live holder count — failover needs
        # at least 2 holders, so the holders="1" gauge is the pager signal
        for bucket in ("1", "2", "3plus"):
            self.metrics.gauge(
                "bqueryd_tpu_replica_holders",
                "advertised shards by live holder count (failover needs a "
                "second holder; see BQUERYD_TPU_REPLICA_FACTOR)",
                labels={"holders": bucket},
                fn=(lambda b=bucket: self._holder_counts().get(b, 0)),
            )
        self.query_seconds = self.metrics.histogram(
            "bqueryd_tpu_groupby_seconds",
            "end-to-end groupby wall at the controller (admission to reply)",
        )
        self.admission_wait_seconds = self.metrics.histogram(
            "bqueryd_tpu_admission_wait_seconds",
            "time queued in admission before launch",
        )
        # admission wait observations ride the controller's hook so the
        # admission module stays metrics-agnostic
        self.admission.wait_observer = self._observe_admission_wait
        self.trace_store = obs.TraceStore()
        self.slow_queries = obs.SlowQueryLog()
        # SLO accounting (obs.slo): per-client-class deadline-margin
        # histograms + burn-rate gauges, fed by every finished groupby in
        # _finalize_query_obs; the timeline ring snapshots the registry
        # periodically behind rpc.timeline() for regression spotting
        self.slo = obs.slo.SLOTracker(self.metrics)
        self.timeline_ring = obs.slo.SnapshotTimeline()
        # fleet capacity model (obs.capacity): per-worker μ from WRM
        # histogram deltas, per-class λ from the admission tap, ρ/states
        # with hysteresis, shard heat map, shadow scale/rebalance advice —
        # evaluated each heartbeat, served by rpc.capacity()
        self.capacity = obs.capacity.CapacityModel(
            on_advice=self._record_capacity_advice
        )
        self.admission.arrival_observer = self._observe_arrival
        self.metrics.gauge(
            "bqueryd_tpu_capacity_fleet_utilization",
            "fleet utilization estimate ρ (dispatch rate over aggregate "
            "service rate, tempered by measured busy fractions)",
            fn=lambda: self.capacity.fleet_gauge("utilization"),
        )
        self.metrics.gauge(
            "bqueryd_tpu_capacity_fleet_state",
            "fleet saturation state code (0=ok 1=warm 2=saturated "
            "3=overloaded, hysteresis applied)",
            fn=lambda: self.capacity.fleet_gauge("state"),
        )
        self.metrics.gauge(
            "bqueryd_tpu_capacity_headroom_qps",
            "estimated additional query arrival rate the fleet can absorb "
            "before utilization crosses BQUERYD_TPU_CAPACITY_TARGET_RHO",
            fn=lambda: self.capacity.fleet_gauge("headroom_qps"),
        )
        self.metrics.gauge(
            "bqueryd_tpu_capacity_model_drift",
            "model-vs-measured queue-delay drift: (predicted - measured) / "
            "max(both) — near 0 means the M/G/1 prediction tracks reality",
            fn=lambda: self.capacity.fleet_gauge("model_drift"),
        )
        self.metrics.gauge(
            "bqueryd_tpu_capacity_worker_resets",
            "WRM counter restarts the capacity model detected and rebased "
            "(worker processes restarting under the same node id)",
            fn=self.capacity.worker_resets,
        )
        self._worker_metrics = {}     # worker_id -> last histogram snapshot
        self._worker_metrics_rev = 0  # bumped on absorb/remove (cache key)
        self._worker_hist_cache = (-1, None)  # (rev, merged aggregate)
        # -- forensics & health (PR 3) --------------------------------------
        # flight recorder: bounded always-on ring of envelopes/dispatches/
        # timeouts/worker churn behind rpc.debug_bundle() + SIGUSR1
        self.flight = obs.FlightRecorder(node_id=self.address)
        # WRM-absorbed per-worker debug snapshots (flight tail + compile
        # registry + device health).  DELIBERATELY kept after a worker is
        # removed: a dead peer's last words are exactly what a debug bundle
        # is for — bounded to the newest entries so churn can't grow it
        self._worker_debug = {}       # worker_id -> {"data", "ts"}
        self._worker_debug_cap = 64
        self._worker_wedged = {}      # worker_id -> last advertised latch
        # health scorer: rolling latency/error baselines from the WRM
        # signals, fed back into find_free_worker's candidate ordering
        self.health = obs.HealthScorer()
        for name, help_text, fn in (
            (
                "bqueryd_tpu_trace_buffer_evictions",
                "trace timelines evicted by the ring's entry/byte bounds "
                "(monotonic)",
                lambda: self.trace_store.evictions,
            ),
            (
                "bqueryd_tpu_slow_query_evictions",
                "slow-query entries evicted by the ring's entry/byte bounds "
                "(monotonic)",
                lambda: self.slow_queries.evictions,
            ),
            (
                "bqueryd_tpu_flight_evictions",
                "flight-ring events evicted by the entry/byte bounds "
                "(monotonic)",
                lambda: self.flight.evictions,
            ),
            (
                "bqueryd_tpu_workers_degraded",
                "registered workers currently scored degraded or wedged",
                lambda: sum(
                    1 for s in self.health.statuses().values()
                    if s.get("status") != obs.STATUS_OK
                ),
            ),
        ):
            self.metrics.gauge(name, help_text, fn=fn)
        from bqueryd_tpu.obs import http as obs_http

        self._metrics_server = obs_http.maybe_start(self.metrics, self.logger)
        self.msg_count_in = 0
        self.start_time = time.time()
        self.running = False
        self._loop_thread = None
        self.last_heartbeat = 0.0

        self.runfile_dir = runfile_dir
        self._write_runfiles()

    # -- runfiles ----------------------------------------------------------
    def _write_runfiles(self):
        self._runfiles = []
        try:
            for suffix, content in (
                ("address", self.address),
                ("pid", str(os.getpid())),
            ):
                path = os.path.join(
                    self.runfile_dir, f"bqueryd_tpu_controller.{suffix}"
                )
                with open(path, "w") as f:
                    f.write(content)
                self._runfiles.append(path)
        except OSError:
            self.logger.debug("runfile dir %s not writable", self.runfile_dir)

    def _remove_runfiles(self):
        for path in self._runfiles:
            try:
                os.remove(path)
            except OSError:
                pass

    # -- main loop ---------------------------------------------------------
    def go(self):
        self.running = True
        self._loop_thread = threading.current_thread()
        try:
            # graceful supervisord stop: deregister from the store and
            # remove runfiles instead of dying mid-dispatch (the worker
            # installs the same handler; reference nodes relied on process
            # teardown alone)
            signal.signal(signal.SIGTERM, self._term_signal)
            if hasattr(signal, "SIGUSR1"):
                # local forensic dump: kill -USR1 <pid> writes the full
                # debug bundle without needing a live client
                signal.signal(signal.SIGUSR1, self._dump_debug_signal)
        except ValueError:
            pass  # not the main thread (in-process test clusters)
        self.logger.info("controller %s running", self.address)
        try:
            while self.running:
                try:
                    self.heartbeat()
                    self.free_dead_workers()
                    self.retry_stale_dispatches()
                    self.maybe_hedge()
                    self._sweep_append_segments()
                    # a pending micro-batch window bounds the poll sleep:
                    # the flush must fire when the window closes, not a full
                    # POLLING_TIMEOUT later (closed-loop clients send
                    # nothing while their queries sit staged)
                    timeout_s = POLLING_TIMEOUT
                    if self._pending_window:
                        remaining = self._window_deadline() - time.time()
                        timeout_s = max(min(timeout_s, remaining), 0.0)
                    events = dict(self.poller.poll(int(timeout_s * 1000)))
                    if self.socket in events:
                        # drain everything available this tick
                        while True:
                            try:
                                frames = self.socket.recv_multipart(zmq.NOBLOCK)
                            except zmq.Again:
                                break
                            self.handle_in(frames)
                    self._admit_ready()
                    self._flush_window()
                    self.dispatch_pending()
                except Exception:
                    self.logger.exception("error in controller loop")
        finally:
            self.stop()

    def _term_signal(self, *args):
        self.logger.info("SIGTERM received, stopping")
        self.running = False

    def stop(self):
        # doubles as a cross-thread shutdown REQUEST (see WorkerBase.stop):
        # an external caller only flags the loop — the loop thread re-enters
        # here on exit for the store/socket teardown (zmq sockets are
        # single-thread-only)
        self.running = False
        loop = self._loop_thread
        if (
            loop is not None
            and loop.is_alive()
            and threading.current_thread() is not loop
        ):
            return
        try:
            self.store.srem(bqueryd_tpu.REDIS_SET_KEY, self.address)
        except Exception:
            pass
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        self._remove_runfiles()
        if not self.socket.closed:
            self.socket.close()
            self.logger.info("controller %s stopped", self.address)

    # -- membership --------------------------------------------------------
    def heartbeat(self):
        now = time.time()
        if now - self.last_heartbeat < self.heartbeat_interval:
            return
        self.last_heartbeat = now
        # capacity model evaluation: per-worker/fleet ρ + states
        # (hysteresis is wall-clock based, so the heartbeat cadence doesn't
        # matter) + shadow advice; no-op under BQUERYD_TPU_CAPACITY=0.
        # BEFORE the timeline snapshot, so every ring entry carries THIS
        # beat's capacity slice (and the first entry is never empty)
        self.capacity.evaluate(now=now)
        # controller timeline ring: one bounded registry snapshot per
        # BQUERYD_TPU_TIMELINE_INTERVAL_S (the ring paces itself; <=0
        # disables), served by rpc.timeline()
        self.timeline_ring.maybe_snapshot(self._timeline_snapshot, now=now)
        # serving housekeeping: abandon wedged rollup builds, enforce the
        # retention caps, dispatch delta refreshes for stale entries
        self.serving.tick()
        self.store.sadd(bqueryd_tpu.REDIS_SET_KEY, self.address)
        current = self.store.smembers(bqueryd_tpu.REDIS_SET_KEY)
        for addr in current:
            if addr == self.address or addr in self.others:
                continue
            self.logger.debug("connecting to peer %s", addr)
            self.socket.connect(addr)
            self.others[addr] = {"last_seen": 0.0}
        for addr in list(self.others):
            if addr not in current:
                self.others.pop(addr, None)
                continue
            gossip = Message({"payload": "peer_info"})
            gossip["from"] = self.address
            gossip.add_as_binary("info", self.get_info(include_peers=False))
            try:
                self.socket.send_multipart(
                    [addr.encode(), gossip.to_json().encode()]
                )
            except zmq.ZMQError:
                # unreachable peer: drop it from the registry so clients and
                # workers stop trying it (reference bqueryd/controller.py:94-97)
                self.logger.warning("peer %s unreachable, removing", addr)
                self.store.srem(bqueryd_tpu.REDIS_SET_KEY, addr)
                self.others.pop(addr, None)

    def free_dead_workers(self):
        """Cull workers silent longer than ``dead_worker_timeout`` — but never
        one we handed in-flight work younger than ``dispatch_timeout``: culling
        it would drop its ``files_map`` entries and fail-fast the very query it
        is busy computing (the round-1 benchmark failure).  A genuinely hung
        worker is still reclaimed: its dispatch times out, the shard is
        requeued, and with nothing in flight the cull proceeds next tick."""
        now = time.time()
        for worker_id, info in list(self.worker_map.items()):
            # hb_only adoptees heartbeat forever even with a permanently
            # wedged main loop — and while advertised-but-busy they block the
            # 'no longer on any worker' fail-fast for their shards without
            # ever going inflight (so no dispatch timeout fires either).
            # Give the main socket dispatch_hard_timeout to speak (a legit
            # first-query compile fits), then reclaim.
            hb_since = info.get("hb_only")
            if hb_since and now - hb_since > self.dispatch_hard_timeout:
                self.logger.warning(
                    "hb-only worker %s never spoke on its main socket in "
                    "%.0fs, removing", worker_id, now - hb_since,
                )
                # quarantine against instant re-adoption by its (still
                # ticking) heartbeat thread; a real main-socket WRM lifts it
                self._adoption_blocked[worker_id] = (
                    now + self.dispatch_hard_timeout
                )
                self.remove_worker(worker_id)
                continue
            if now - info.get("last_seen", now) <= self.dead_worker_timeout:
                continue
            if any(
                e["worker"] == worker_id
                and now - e["sent_at"] <= self.dispatch_timeout
                for e in self.inflight.values()
            ):
                continue
            self.logger.warning("culling dead worker %s", worker_id)
            self.remove_worker(worker_id)

    def remove_worker(self, worker_id):
        if worker_id in self.worker_map:
            # forensic event (never gated); the worker's debug snapshot in
            # _worker_debug deliberately survives for rpc.debug_bundle()
            self.flight.record("worker_removed", worker=worker_id)
        self.worker_map.pop(worker_id, None)
        self.health.remove(worker_id)
        self.capacity.remove_worker(worker_id)
        self._worker_wedged.pop(worker_id, None)
        if self._worker_metrics.pop(worker_id, None) is not None:
            self._worker_metrics_rev += 1
        for filename in list(self.files_map):
            self.files_map[filename].discard(worker_id)
            if not self.files_map[filename]:
                del self.files_map[filename]
                self.shard_stats.pop(filename, None)
        # fail pending append dispatches to the removed holder FAST: the
        # fan-out cannot complete anymore, and the client would otherwise
        # wait out the whole segment timeout for a worker that is gone
        for seg_key, segment in list(self._append_segments.items()):
            gone = [
                t for t, w in segment["pending"].items() if w == worker_id
            ]
            for t in gone:
                segment["pending"].pop(t, None)
                self._append_waiters.pop(t, None)
                segment["errors"][worker_id] = (
                    "holder removed (worker lost before confirming)"
                )
            if gone and not segment["pending"]:
                self._finish_append_segment(seg_key, segment)
        # re-queue anything in flight on that worker; a hedged flight
        # collapses onto its surviving side instead (the duplicate is
        # still computing — a fresh dispatch would be redundant)
        for token, entry in list(self.inflight.items()):
            if entry.get("hedged") == worker_id:
                self.inflight.pop(token)
                self._collapse_hedge(token, entry, worker_id)
            elif entry["worker"] == worker_id:
                self.inflight.pop(token)
                if entry.get("hedged"):
                    self._collapse_hedge(token, entry, worker_id)
                else:
                    self._requeue(entry)

    def _absorb_worker_metrics(self, worker_id, info):
        """Latest histogram snapshot per worker (rides the WRM like shard
        stats); aggregated by bucket-vector addition in get_info.  Also
        feeds the WRM's health signals (histograms + error counter +
        backend_wedged) into the health scorer, records wedge-latch flips
        in the flight ring, and absorbs the worker's debug-bundle slice."""
        snap = info.get("metrics")
        wedged = bool(info.get("backend_wedged"))
        # fleet capacity ingestion: μ from the service-histogram deltas +
        # bottleneck stages from the pipeline busy clocks + the wedge
        # latch (a wedged device's μ is excluded from fleet capacity).
        # Calc workers only — downloaders serve no queries and would drag
        # the model's coverage/μ averages.  Runs BEFORE the dedup below:
        # deltas need the fresh cumulative totals every heartbeat,
        # identical or not.
        pipeline_busy = info.pop("pipeline_busy", None)
        if info.get("workertype") == "calc" and isinstance(snap, dict):
            self.capacity.absorb_worker(
                worker_id, snap, pipeline_busy=pipeline_busy,
                wedged=wedged, pid=info.get("pid"),
            )
        if isinstance(snap, dict) and snap != self._worker_metrics.get(
            worker_id
        ):
            # equality check before the rev bump: an idle fleet heartbeats
            # identical snapshots, and bumping on those would defeat the
            # aggregate memo in _aggregate_worker_histograms
            self._worker_metrics[worker_id] = snap
            self._worker_metrics_rev += 1
        # keep worker_map lean: the snapshot lives in _worker_metrics; a
        # second copy per worker entry would bloat get_info and peer gossip
        info.pop("metrics", None)
        prev_wedged = self._worker_wedged.get(worker_id)
        self._worker_wedged[worker_id] = wedged
        if wedged and not prev_wedged:
            # forensic event (never gated): the moment the fleet view
            # learned this worker's accelerator latched
            self.flight.record("worker_wedged", worker=worker_id)
            self.logger.warning(
                "worker %s advertises a wedged accelerator backend",
                worker_id,
            )
        elif prev_wedged and not wedged:
            self.flight.record("worker_unwedged", worker=worker_id)
        # every heartbeat is a health sample, even when the histogram totals
        # did not move — a silent window is itself signal (no throughput)
        self.health.observe(
            worker_id,
            snapshot=self._worker_metrics.get(worker_id),
            wedged=wedged,
            errors=info.get("work_errors"),
            pid=info.get("pid"),
        )
        debug = info.pop("debug", None)
        if isinstance(debug, dict):
            self._worker_debug[worker_id] = {
                "data": debug, "ts": time.time(),
            }
            while len(self._worker_debug) > self._worker_debug_cap:
                # evict dead peers' stale last-words before any live
                # worker's slice: a fleet larger than the cap must never
                # present a reporting worker as "partial" in the bundle
                # (registered entries go only when everything is registered)
                victim = min(
                    self._worker_debug,
                    key=lambda w: (
                        w in self.worker_map,
                        self._worker_debug[w]["ts"],
                    ),
                )
                self._worker_debug.pop(victim, None)

    def _absorb_shard_stats(self, info):
        """Planning stats ride the WRM; keep the freshest copy per shard.
        Entries are shape-checked here: one malformed advertisement (a
        version-skewed or buggy worker) must poison at most its own shard's
        stats, never a query — downstream consumers assume dicts."""
        stats = info.get("shard_stats")
        if isinstance(stats, dict):
            for fname, entry in stats.items():
                if (
                    isinstance(fname, str)
                    and isinstance(entry, dict)
                    and isinstance(entry.get("cols", {}), dict)
                ):
                    self.shard_stats[fname] = entry

    def _holder_counts(self):
        """Advertised shards bucketed by live holder count ("1"/"2"/"3plus")
        — the replica_holders gauge family and get_info's replication view.
        Briefly memoized: one metrics scrape reads all three buckets (and
        get_info a fourth), which would otherwise walk files_map once per
        bucket."""
        now = time.time()
        cached = self._holder_counts_memo
        if cached is not None and now - cached[0] < 0.25:
            return cached[1]
        counts = {"1": 0, "2": 0, "3plus": 0}
        # list(): gauges render on the metrics HTTP thread while the main
        # loop mutates files_map (WRM registration, worker cull)
        for holders in list(self.files_map.values()):
            n = len(holders)
            if n >= 3:
                counts["3plus"] += 1
            elif n:
                counts[str(n)] += 1
        self._holder_counts_memo = (now, counts)
        return counts

    # -- scheduling --------------------------------------------------------
    def find_free_worker(self, needs_local=False, filename=None, exclude=()):
        """Random choice among free calc workers, constrained to workers
        advertising ``filename`` — a single name or, for a batched shard
        group, a list the worker must advertise in full — and optionally to
        this controller's host (reference bqueryd/controller.py:113-144).

        Health-aware (the observability → scheduling feedback loop): among
        eligible candidates, workers the :class:`obs.HealthScorer` flags
        degraded/wedged are used only when no healthy candidate is free —
        deprioritized, never excluded, so the sole holder of a shard still
        serves it.  ``BQUERYD_TPU_HEALTH_ROUTING=0`` disables the
        preference.

        ``exclude`` is the failover set: holders this shard already failed
        on.  They are avoided while ANY other candidate exists, but — same
        rule as health routing — a shard whose only remaining holder is
        excluded is still served by it (a transient fault may have cleared;
        refusing outright would turn every sole-holder hiccup terminal)."""
        from bqueryd_tpu.obs import health as health_mod

        needed = (
            [filename] if isinstance(filename, str) else list(filename or [])
        )
        candidates = []
        for worker_id, info in self.worker_map.items():
            if info.get("workertype") != "calc" or info.get("busy"):
                continue
            if any(
                worker_id not in self.files_map.get(f, ()) for f in needed
            ):
                continue
            if needs_local and info.get("node") != self.node_name:
                continue
            candidates.append(worker_id)
        if exclude:
            kept = [w for w in candidates if w not in exclude]
            if kept:
                candidates = kept
        if not candidates:
            return None
        if len(candidates) > 1 and health_mod.routing_enabled():
            healthy = self.health.healthy_subset(candidates)
            if healthy and len(healthy) < len(candidates):
                self.counters["health_avoided_dispatches"] += 1
                candidates = healthy
        return random.choice(candidates)

    def dispatch_pending(self):
        """Drain affinity queues round-robin, one message per queue per tick
        (reference bqueryd/controller.py:223-268)."""
        affinities = sorted(self.worker_out_messages, key=lambda a: (a is None, a))
        if not affinities:
            return
        for offset in range(len(affinities)):
            affinity = affinities[
                (self._affinity_rr + offset) % len(affinities)
            ]
            queue = self.worker_out_messages.get(affinity, [])
            if not queue:
                if affinity is not None:
                    self.worker_out_messages.pop(affinity, None)
                continue
            # one action per queue per tick, but a shard inside its failover
            # backoff window must not head-of-line block the messages queued
            # behind it (workers may be free for THEM) — scan for the first
            # actionable message instead of only ever examining the head
            now = time.time()
            idx = None
            for i, msg in enumerate(queue):
                not_before = msg.get("_not_before")
                if not_before is not None and not_before > now:
                    continue  # backing off: skip it, don't block the queue
                idx = i
                break
            if idx is None:
                continue  # whole queue is backing off: retry next tick
            msg = queue[idx]
            if msg.deadline_expired():
                # nobody is waiting anymore: expire instead of dispatching
                queue.pop(idx)
                self.counters["deadline_expired"] += 1
                self._abort_work(
                    msg, "deadline exceeded before dispatch"
                )
                continue
            worker_id = msg.get("worker_id") or self.find_free_worker(
                needs_local=msg.get("needs_local", False),
                filename=msg.get("filename"),
                exclude=frozenset(msg.get("_excluded_workers") or ()),
            )
            if worker_id is None:
                filename = msg.get("filename")
                needed = (
                    [filename]
                    if isinstance(filename, str)
                    else list(filename or [])
                )
                if needed and any(f not in self.files_map for f in needed):
                    # the file vanished from every worker (all holders died):
                    # no future tick can serve this — fail fast instead of
                    # head-of-line-blocking the queue forever
                    queue.pop(idx)
                    self._abort_work(
                        msg,
                        f"file(s) no longer on any worker: "
                        f"{[f for f in needed if f not in self.files_map]}",
                    )
                elif isinstance(filename, list) and not self._servable_by_one(
                    filename
                ):
                    # placement changed since batching (e.g. the co-locating
                    # worker died): re-split the group into per-shard
                    # messages, which the normal scheduler can place
                    queue.pop(idx)
                    children = self._split_batch(msg)
                    self._transfer_work(msg, children)
                    queue.extend(children)
                continue  # retry next tick
            queue.pop(idx)
            self._send_to_worker(worker_id, msg)
        self._affinity_rr += 1

    def _servable_by_one(self, filenames):
        """True if ANY calc worker (busy or not) advertises every file."""
        sets = [self.files_map.get(f, set()) for f in filenames]
        common = set.intersection(*sets) if sets else set()
        return any(
            self.worker_map.get(w, {}).get("workertype") == "calc"
            for w in common
        )

    def _split_batch(self, msg):
        """Explode a batched shard-group CalcMessage back into per-shard
        messages (same parent, fresh tokens, retry count carried over)."""
        args, kwargs = msg.get_args_kwargs()
        children = []
        # a child's payload is one of several: never the whole answer
        msg = CalcMessage(dict(msg))
        msg.pop("sole_payload", None)
        for key in ("plan", "bundle"):
            if msg.get(key):
                fragment = dict(msg.get_from_binary(key), sole=False)
                msg.add_as_binary(key, fragment)
        for filename in msg["filename"]:
            child = CalcMessage(dict(msg))
            child.set_args_kwargs([filename] + list(args[1:]), kwargs)
            child["token"] = os.urandom(8).hex()
            child["filename"] = filename
            # each child is its own dispatch attempt: a fresh trace hop +
            # queue clock (same rule as _requeue), or every child's
            # dispatch/calc spans would share the batch's one span_id
            wire = child.get_trace()
            if wire:
                wire = dict(wire)
                wire["span_id"] = os.urandom(8).hex()
                child.set_trace(wire)
                child["_dispatch_queued_ts"] = time.time()
            children.append(child)
        return children

    # -- shared-dispatch work tracking -------------------------------------
    # Every groupby work unit (one CalcMessage) carries a subscriber list:
    # the parent queries awaiting its payload.  Two concurrent admitted
    # plans that need the same computation over the same shard group fuse
    # into ONE dispatch — one column read, one device transfer, one kernel
    # run — and the result fans out to every subscriber (multi-query
    # batching; observable via counters["plan_shared_dispatches"]).
    def _register_work(self, msg, subscribers, work_key=None):
        token = msg.get("token")
        if not token:
            return
        self._work_subscribers[token] = list(subscribers)
        if work_key is not None:
            self._work_keys[token] = work_key
            self._work_index[work_key] = token

    def _drop_work(self, token):
        self._work_subscribers.pop(token, None)
        self._requeued_tokens.discard(token)
        key = self._work_keys.pop(token, None)
        if key is not None and self._work_index.get(key) == token:
            self._work_index.pop(key, None)

    def _work_parents(self, msg):
        """Every parent awaiting this work unit (shared dispatch aware)."""
        subs = self._work_subscribers.get(msg.get("token"))
        if subs:
            return list(subs)
        parent = msg.get("parent_token")
        return [parent] if parent else []

    def _transfer_work(self, msg, children):
        """Re-home a batch's subscribers onto its re-split children."""
        subs = self._work_subscribers.get(msg.get("token"))
        self._drop_work(msg.get("token"))
        if subs is None:
            return
        for child in children:
            self._register_work(child, subs)

    def _abort_work(self, msg, error_text, error_class=None, attempts=None):
        """Fail every parent subscribed to one work unit."""
        parents = self._work_parents(msg)
        self._drop_work(msg.get("token"))
        for parent in parents:
            self.abort_parent(
                parent, error_text,
                error_class=error_class, attempts=attempts,
            )

    def _dispatch_wire(self, worker_id, msg):
        """The low-level dispatch seam shared by the primary and hedge
        paths: the controller.dispatch chaos site (drop / duplicate /
        delay) plus the raw ROUTER send.  Returns the wall clock at the
        send, after the envelope's encoding (where the dispatch span ends
        and the in-flight window starts: the worker may be computing before
        this call returns), or None when the envelope was chaos-dropped
        (recorded here; callers decide whether that means 'lost on the
        wire' or 'never sent'); zmq.ZMQError from a gone peer propagates to
        the caller."""
        fault = chaos.fire(
            "controller.dispatch",
            worker=worker_id,
            verb=msg.get("payload"),
            token=msg.get("token"),
            filename=str(msg.get("filename")),
        ) if chaos.enabled() else None
        if fault is not None and fault.action == "drop":
            self.flight.record(
                "chaos_dispatch_dropped",
                worker=worker_id, token=msg.get("token"),
            )
            return None
        frames = [worker_id.encode(), msg.to_json().encode()]
        sent_at = time.time()
        self.socket.send_multipart(frames)
        if fault is not None and fault.action == "duplicate":
            self.socket.send_multipart(frames)
        return sent_at

    def _send_to_worker(self, worker_id, msg):
        # chaos site controller.dispatch: drop (the envelope "leaves" but
        # never arrives — the dispatch-timeout/failover path must recover),
        # duplicate (the worker sees the work twice — reply dedup must
        # hold), delay (handled inside fire)
        try:
            sent_at = self._dispatch_wire(worker_id, msg) or time.time()
        except zmq.ZMQError as exc:
            self.logger.warning("send to worker %s failed: %s", worker_id, exc)
            self.remove_worker(worker_id)
            # a missing route (EHOSTUNREACH) is a controller-side routing
            # fact, not evidence against the shard: requeue without charging
            # the retry budget.  Progress is still guaranteed — the worker
            # was just removed, so the shard either reschedules onto another
            # holder or fails fast via 'no longer on any worker'.  Any OTHER
            # send failure (e.g. EAGAIN on a congested pipe under SNDTIMEO)
            # still charges, or a live-but-wedged worker that keeps
            # re-registering would loop the dispatch forever.
            unroutable = getattr(exc, "errno", None) == zmq.EHOSTUNREACH
            self._requeue(
                {"msg": msg, "retries": msg.get("_retries", 0),
                 "parent": msg.get("parent_token")},
                charge_retry=not unroutable,
                failed_worker=worker_id,
                reason=f"send failed: {exc}",
            )
            return
        if msg.isa("groupby"):
            self.counters["dispatched_shards"] += 1
            # capacity model: per-worker λ window + the per-shard dispatch
            # heat map (skew detection feeding the rebalance advice)
            self.capacity.observe_dispatch(worker_id, msg.get("filename"))
        from bqueryd_tpu import obs

        # flight ring: every work envelope handed to a worker (hot path —
        # kill-switch gated), the forensic counterpart of dispatch_timeout
        if obs.enabled():
            self.flight.record(
                "dispatch",
                worker=worker_id,
                verb=msg.get("payload"),
                token=msg.get("token"),
                filename=str(msg.get("filename"))[:200]
                if msg.get("filename") is not None else None,
                trace_id=(msg.get_trace() or {}).get("trace_id"),
            )
        self._record_dispatch_span(msg, worker_id, sent_at=sent_at)
        if worker_id in self.worker_map:
            self.worker_map[worker_id]["busy"] = True
            # a successful dispatch is proof of liveness: the send would have
            # raised on a gone peer (ROUTER_MANDATORY)
            self.worker_map[worker_id]["last_seen"] = time.time()
        token = msg.get("token")
        if token:
            self._requeued_tokens.discard(token)
            self.inflight[token] = {
                "worker": worker_id,
                "sent_at": sent_at,
                "msg": msg,
                "parent": msg.get("parent_token"),
                "retries": msg.get("_retries", 0),
            }

    def _record_dispatch_span(self, msg, worker_id, hedge=False,
                              sent_at=None):
        """One "dispatch" span per successful send: queue-entry -> send, its
        span_id the CalcMessage's trace hop (the worker's calc span parents
        to it).  Recorded into EVERY live subscriber segment so shared
        dispatches appear on each joined query's timeline.  Tags carry the
        attempt metadata the attribution layer reads: retry count, the
        charged backoff window (carved out as a retry_backoff segment),
        failover exclusions, and the hedge flag for duplicate dispatches."""
        from bqueryd_tpu import obs

        wire = msg.get_trace()
        queued_ts = msg.get("_dispatch_queued_ts")
        if not wire or queued_ts is None or not obs.enabled():
            return
        if hedge:
            # the hedge dispatched NOW with no backoff of its own: the
            # original attempt's retry/backoff/exclusion tags must not
            # bleed onto its marker (they would read as hedge delay)
            tags = {"worker": worker_id, "hedge": True}
        else:
            tags = {
                "worker": worker_id,
                "filename": str(msg.get("filename")),
                "retries": msg.get("_retries", 0),
            }
            backoff_s = msg.get("_backoff_s")
            if backoff_s:
                tags["backoff_s"] = backoff_s
            excluded = msg.get("_excluded_workers")
            if excluded:
                tags["excluded"] = list(excluded)
        now = sent_at if sent_at is not None else time.time()
        span = obs.make_span(
            wire["trace_id"], "dispatch",
            now if hedge else queued_ts,
            0.0 if hedge else max(now - float(queued_ts), 0.0),
            # a hedge duplicates the original attempt's trace hop: its span
            # gets its own id (make_span mints one when None) so both
            # attempts stay distinct on the timeline
            span_id=None if hedge else wire["span_id"],
            parent_span_id=wire.get("parent_span_id"),
            node=self.address,
            tags=tags,
        )
        for parent in self._work_parents(msg):
            segment = self.rpc_segments.get(parent)
            if segment is not None and segment.get("obs"):
                segment["obs"]["spans"].append(span)

    def retry_stale_dispatches(self):
        """Requeue in-flight work whose worker stopped heartbeating (after
        ``dispatch_timeout``) or that exceeded ``dispatch_hard_timeout`` even
        on a live worker.  A live, heartbeating worker inside the hard cap is
        left alone — first-query XLA compilation on a TPU can legitimately
        outlast ``dispatch_timeout``, and requeueing a shard that is still
        being computed would double-execute it and then abort the parent
        after MAX_DISPATCH_RETRIES."""
        now = time.time()
        for token, entry in list(self.inflight.items()):
            if token not in self.inflight:
                continue  # already reclaimed by a remove_worker below
            age = now - entry["sent_at"]
            if age <= self.dispatch_timeout:
                continue
            winfo = self.worker_map.get(entry["worker"])
            worker_alive = (
                winfo is not None
                and now - winfo.get("last_seen", 0.0) <= self.dead_worker_timeout
            )
            if worker_alive and age <= self.dispatch_hard_timeout:
                continue
            self.logger.warning(
                "dispatch %s to %s timed out (age %.0fs, worker %s)",
                token, entry["worker"],
                age, "alive" if worker_alive else "dead",
            )
            # forensic event (never gated): hard timeouts are one of the
            # debug bundle's trigger conditions
            self.flight.record(
                "dispatch_timeout",
                token=token,
                worker=entry["worker"],
                age_s=round(age, 3),
                hard=age > self.dispatch_hard_timeout,
                worker_alive=worker_alive,
                filename=str(entry["msg"].get("filename"))[:200],
                trace_id=(entry["msg"].get_trace() or {}).get("trace_id"),
            )
            self.inflight.pop(token)
            if entry.get("hedged"):
                # the original side timed out while its hedge duplicate is
                # still computing: collapse onto the survivor instead of a
                # redundant third dispatch (the survivor keeps its own
                # freshly-rebased timeout clock)
                self._collapse_hedge(token, entry, entry["worker"])
            else:
                self._requeue(
                    entry,
                    reason=f"dispatch timeout after {age:.0f}s "
                           f"(worker {'alive' if worker_alive else 'dead'})",
                )
            if worker_alive:
                # heartbeating but wedged past the hard cap: reclaim it fully
                # (drop its files_map entries + requeue its other inflight)
                # or it would sit busy-and-advertised forever, head-of-line
                # blocking every query for files only it holds
                self.logger.warning(
                    "worker %s hung past hard timeout, removing", entry["worker"]
                )
                self.remove_worker(entry["worker"])
        # outdistanced workers (hedge losers, stale-attempt holders a late
        # first-worker reply beat) have no inflight entry — the winning
        # reply popped it — but may still be wedged mid-execution: past the
        # hard cap, reclaim each exactly like a hung dispatch.  Their shard
        # is already answered, so there is nothing to requeue for THIS token
        for token, rec in list(self._hedge_losers.items()):
            remaining = []
            for worker in rec["workers"]:
                if worker not in self.worker_map:
                    continue  # culled independently
                age = now - rec["since"]
                if age <= self.dispatch_hard_timeout:
                    remaining.append(worker)
                    continue
                self.logger.warning(
                    "hedge loser %s silent past hard timeout on %s, removing",
                    worker, token,
                )
                self.flight.record(
                    "hedge_loser_timeout",
                    token=token, worker=worker, age_s=round(age, 3),
                )
                self.remove_worker(worker)
            if remaining:
                rec["workers"] = remaining
            else:
                self._hedge_losers.pop(token, None)

    def _mark_hedged(self, token, ts):
        """Record a token in the late-reply dedup ring, bounded: markers
        for workers that die before answering are never popped by a reply,
        so the cap (not the pop) is what keeps a long-lived controller's
        memory flat."""
        self._hedged_tokens[token] = ts
        while len(self._hedged_tokens) > 256:
            self._hedged_tokens.pop(next(iter(self._hedged_tokens)))

    def _withdraw_queued(self, token):
        """Remove a not-yet-dispatched queued work message by token: its
        query was answered by a late reply from a previous attempt, so
        dispatching it would only burn a worker on a finished shard."""
        for affinity, queue in list(self.worker_out_messages.items()):
            kept = [m for m in queue if m.get("token") != token]
            if len(kept) != len(queue):
                self.worker_out_messages[affinity] = kept

    def _collapse_hedge(self, token, entry, failed_worker):
        """One side of a hedged pair is gone (transient fault, timeout,
        cull): re-key the inflight entry onto the surviving side instead of
        requeueing — a third execution would be redundant while the
        duplicate lives, and the survivor needs a hard-timeout reclaim
        handle.  Clears the token's hedge dedup marker: the flight is no
        longer hedged, so the survivor's reply must be processed as THE
        reply, not deduplicated."""
        hedged = entry.get("hedged")
        survivor = hedged if failed_worker == entry.get("worker") \
            else entry["worker"]
        refiled = dict(entry, worker=survivor)
        refiled.pop("hedged", None)
        refiled.pop("hedged_at", None)
        if survivor == hedged:
            # timeout clock restarts at the hedge dispatch, not the
            # original one, or the survivor is reclaimed the moment it
            # inherits the entry
            refiled["sent_at"] = entry.get("hedged_at", entry["sent_at"])
        excluded = list(entry["msg"].get("_excluded_workers") or [])
        if failed_worker and failed_worker not in excluded:
            excluded.append(failed_worker)
        entry["msg"]["_excluded_workers"] = excluded
        self._hedged_tokens.pop(token, None)
        self.inflight[token] = refiled
        self.flight.record(
            "hedge_collapsed",
            token=token, failed=failed_worker, survivor=survivor,
        )
        return refiled

    def _note_losers(self, token, workers):
        """Keep hard-timeout reclaim handles on workers still computing an
        already-answered token (hedge losers, outdistanced stale attempts):
        retry_stale_dispatches reclaims them like any hung dispatch, and a
        loser that answers after all is discarded from tracking."""
        workers = [w for w in workers if w]
        if workers:
            self._hedge_losers[token] = {
                "workers": workers, "since": time.time(),
            }

    def _discard_loser(self, token, worker_id):
        """A tracked loser answered after all — stop holding a reclaim
        handle on it (others computing the same token stay tracked)."""
        rec = self._hedge_losers.get(token)
        if rec is None:
            return
        rec["workers"] = [w for w in rec["workers"] if w != worker_id]
        if not rec["workers"]:
            self._hedge_losers.pop(token, None)

    def maybe_hedge(self):
        """Hedged duplicate dispatch for tail shards (off unless
        ``BQUERYD_TPU_HEDGE_MS`` > 0): a shard still inflight past the
        threshold is duplicated onto a second healthy holder (excluding the
        original and every previously failed one).  First reply wins; the
        loser's reply is deduplicated by query token and **counted**
        (``duplicate_replies``), never double-merged — results are keyed by
        shard filename, so a duplicate could only ever overwrite its own
        identical payload."""
        if self.hedge_ms <= 0 or not self.inflight:
            return
        now = time.time()
        for token, entry in list(self.inflight.items()):
            if token not in self.inflight:
                # remove_worker() below (gone hedge target) requeues that
                # worker's other entries mid-iteration: a snapshot item no
                # longer inflight must not be hedged — its retry is parked,
                # and a ring marker here would discard the retry's valid
                # reply as a duplicate (same guard as
                # retry_stale_dispatches)
                continue
            if entry.get("hedged"):
                continue
            if (now - entry["sent_at"]) * 1000.0 < self.hedge_ms:
                continue
            msg = entry["msg"]
            if not msg.isa("groupby") or msg.get("worker_id"):
                # hedging duplicates EXECUTION: only the idempotent shard
                # verb is safe to run twice (execute_code & co. carry side
                # effects), and a worker-pinned message chose its target
                continue
            exclude = {entry["worker"]} | set(
                msg.get("_excluded_workers") or ()
            )
            target = self.find_free_worker(
                needs_local=msg.get("needs_local", False),
                filename=msg.get("filename"),
                exclude=exclude,
            )
            if target is None or target in exclude:
                continue  # no second healthy holder free right now
            # the hedge rides the same chaos dispatch site as the primary
            # path; a chaos-dropped hedge is simply not sent (no
            # bookkeeping — the next tick may try again, the plan's
            # counters decide)
            try:
                if self._dispatch_wire(target, msg) is None:
                    continue
            except zmq.ZMQError:
                # gone peer (ROUTER_MANDATORY): cull it like the primary
                # dispatch path does, or this loop re-hedges onto the dead
                # route every tick until the heartbeat cull
                self.remove_worker(target)
                continue
            entry["hedged"] = target
            entry["hedged_at"] = now
            self._mark_hedged(token, now)
            # the duplicate attempt lands on the timeline too (tagged
            # hedge=True so attribution lists it beside the original)
            self._record_dispatch_span(msg, target, hedge=True)
            if target in self.worker_map:
                self.worker_map[target]["busy"] = True
                self.worker_map[target]["last_seen"] = now
            self.counters["hedged_dispatches"] += 1
            self.flight.record(
                "hedged_dispatch",
                token=token, worker=target, original=entry["worker"],
                age_ms=round((now - entry["sent_at"]) * 1000.0, 1),
            )

    def _retry_backoff(self, msg, retries):
        """Exponential backoff + deterministic jitter between dispatch
        attempts of one shard: base * 2^retries capped, stretched by up to
        25% keyed on the work token (stable across re-runs, different
        across shards — simultaneous failovers de-stampede)."""
        return backoff.backoff_delay(
            retries,
            str(msg.get("token") or ""),
            base=RETRY_BACKOFF_BASE_S,
            cap=RETRY_BACKOFF_CAP_S,
        )

    def _requeue(self, entry, charge_retry=True, failed_worker=None,
                 reason=None):
        from bqueryd_tpu import obs

        msg = entry["msg"]
        retries = entry.get("retries", 0)
        if failed_worker is None:
            failed_worker = entry.get("worker")
        # the failed attempt's in-flight window becomes its own dispatch
        # span (tagged with the failure): a shard that sat 1.5 s on a dead
        # worker must autopsy as dispatch wait on THAT worker, not as
        # unattributed wall
        sent_at = entry.get("sent_at")
        wire = msg.get_trace()
        if sent_at is not None and wire and obs.enabled():
            span = obs.make_span(
                wire["trace_id"], "dispatch", sent_at,
                max(time.time() - float(sent_at), 0.0),
                parent_span_id=wire.get("parent_span_id"),
                node=self.address,
                tags={
                    "worker": failed_worker,
                    "retries": retries,
                    "failed": str(
                        reason or "worker lost or dispatch timed out"
                    )[:120],
                },
            )
            for parent in self._work_parents(msg):
                segment = self.rpc_segments.get(parent)
                if segment is not None and segment.get("obs"):
                    segment["obs"]["spans"].append(span)
        # per-attempt forensic history rides the message (bounded by the
        # retry budget); the structured exhaustion envelope surfaces it so
        # a client sees WHERE its query died instead of timing out blind
        history = list(msg.get("_attempt_history") or [])
        history.append(
            {
                "worker": failed_worker,
                "reason": str(
                    reason or "worker lost or dispatch timed out"
                )[:200],
                "retries": retries,
                "ts": round(time.time(), 3),
            }
        )
        msg["_attempt_history"] = history
        if failed_worker:
            # replica failover: the retry must land on a DIFFERENT holder
            # while one exists (find_free_worker's exclude contract)
            excluded = list(msg.get("_excluded_workers") or [])
            if failed_worker not in excluded:
                excluded.append(failed_worker)
            msg["_excluded_workers"] = excluded
        if charge_retry and retries >= self.max_dispatch_retries:
            self._abort_work(
                msg,
                f"shard {msg.get('filename')} failed after "
                f"{retries} retries (worker lost, timed out, or faulted)",
                error_class="DispatchExhausted",
                attempts=history,
            )
            return
        if charge_retry and failed_worker:
            self.counters["failover_dispatches"] += 1
        msg["_retries"] = retries + 1 if charge_retry else retries
        backoff_s = self._retry_backoff(msg, retries)
        msg["_not_before"] = time.time() + backoff_s
        # the charged backoff rides the message so the attempt's dispatch
        # span can tag it — attribution carves it out as retry_backoff
        msg["_backoff_s"] = round(backoff_s, 6)
        # each dispatch ATTEMPT is its own trace hop: a fresh span_id (a
        # slow-but-alive first worker's calc span keeps parenting to the
        # original attempt's recorded span) and a fresh queue-entry clock
        wire = msg.get_trace()
        if wire:
            wire = dict(wire)
            wire["span_id"] = os.urandom(8).hex()
            msg.set_trace(wire)
            msg["_dispatch_queued_ts"] = time.time()
        affinity = msg.get("affinity")
        if msg.get("token"):
            self._requeued_tokens.add(msg.get("token"))
        self.worker_out_messages.setdefault(affinity, []).append(msg)

    # -- inbound demux -----------------------------------------------------
    def handle_in(self, frames):
        self.msg_count_in += 1
        # the pickup, on the spans' two clocks: where a query's
        # request_decode and a reply's reply_absorb span start
        picked_up = (time.time(), time.perf_counter())
        if len(frames) == 3 and frames[1] == b"":
            self.handle_rpc(frames[0], frames[2], picked_up)
            return
        if len(frames) == 3:
            try:
                msg = msg_factory(frames[1])
            except messages.MalformedMessage:
                self.logger.warning("malformed worker reply dropped")
                return
            msg["data"] = frames[2]
            self.handle_worker(frames[0], msg, picked_up)
            return
        if len(frames) == 2:
            try:
                msg = msg_factory(frames[1])
            except messages.MalformedMessage:
                self.logger.warning("malformed message dropped")
                return
            if msg.get("payload") == "peer_info":
                self.handle_peer(msg)
            elif msg.get("_relayed") and msg.get("payload") in (
                "killall", "kill", "loglevel",
            ):
                # control verb fanned out by a peer controller (reference
                # bqueryd/controller.py:291-295): dispatch like an RPC, but
                # there is no client to answer (no token)
                getattr(self, f"rpc_{msg['payload']}")(msg)
            else:
                self.handle_worker(frames[0], msg, picked_up)
            return
        self.logger.warning("dropping %d-frame message", len(frames))

    # -- worker messages ---------------------------------------------------
    def handle_worker(self, sender, msg, picked_up=None):
        worker_id = (
            msg.get("worker_id")
            or (sender.decode() if isinstance(sender, bytes) else sender)
        )
        now = time.time()
        if msg.isa(WorkerRegisterMessage):
            if msg.get("liveness_only"):
                # side-channel heartbeat from the worker's liveness thread:
                # for a KNOWN worker refresh last_seen only — its data_files
                # snapshot may lag the main loop's rescan, and dropping
                # advertisements for a busy worker aborts its query.  For an
                # UNKNOWN worker (controller restart while the worker's event
                # loop is deep in a long handle_work) adopt the snapshot
                # additively: without it the sole holder of a shard would look
                # file-less until its main loop resumes, failing every query
                # for that shard with 'no longer on any worker'.
                known = self.worker_map.get(worker_id)
                if known is not None:
                    known["last_seen"] = now
                    # the worker's one-shot stats advertisement may ride
                    # EITHER socket (the liveness thread races the main
                    # loop for it); dropping it here would suppress fresh
                    # stats for a whole re-advertise window
                    self._absorb_shard_stats(msg)
                    self._absorb_worker_metrics(worker_id, msg)
                elif self._adoption_blocked.get(worker_id, 0) > now:
                    # quarantined: this worker was hard-culled as an hb_only
                    # adoptee whose main loop never spoke — its heartbeat
                    # thread is still ticking, and re-adopting it would
                    # repopulate files_map and make every new query wait out
                    # another full hard-timeout window
                    return
                else:
                    # adopt as BUSY + hb_only: the worker's main loop is deep
                    # in a long handle_work and the ROUTER may only hold a
                    # route for the '.hb' identity — dispatching now would
                    # EHOSTUNREACH, remove the worker, and burn the shard's
                    # retry budget in a re-adopt loop.  The first message on
                    # the main socket (WRM/Done/result) proves the real route
                    # and clears both flags.
                    info = dict(msg)
                    info["last_seen"] = now
                    info["busy"] = True
                    info["hb_only"] = now  # adoption time: expiry-checked in cull
                    self.worker_map[worker_id] = info
                    for filename in info.get("data_files") or []:
                        self.files_map.setdefault(filename, set()).add(worker_id)
                    self._absorb_shard_stats(info)
                    self._absorb_worker_metrics(worker_id, info)
                return
            prev = self.worker_map.get(worker_id, {})
            if not prev:
                self.flight.record(
                    "worker_registered",
                    worker=worker_id,
                    workertype=msg.get("workertype"),
                    node=msg.get("node"),
                )
            self._adoption_blocked.pop(worker_id, None)  # main loop is back
            info = dict(msg)
            info["last_seen"] = now
            # an hb_only adoption's busy=True was a placeholder, not observed
            # state — a main-socket WRM proves the route and resets it
            info["busy"] = False if prev.get("hb_only") else prev.get("busy", False)
            self.worker_map[worker_id] = info
            current_files = set(info.get("data_files", []))
            for filename in current_files:
                self.files_map.setdefault(filename, set()).add(worker_id)
            for filename in list(self.files_map):
                if filename not in current_files:
                    self.files_map[filename].discard(worker_id)
                    if not self.files_map[filename]:
                        del self.files_map[filename]
                        self.shard_stats.pop(filename, None)
            self._absorb_shard_stats(info)
            self._absorb_worker_metrics(worker_id, info)
            return
        if worker_id not in self.worker_map:
            # a message from a culled worker: ask it to re-register by just
            # recording minimal liveness (reference bqueryd/controller.py:315-318)
            self.worker_map[worker_id] = {
                "worker_id": worker_id, "last_seen": now, "busy": False,
                "workertype": "unknown",
            }
        else:
            self.worker_map[worker_id]["last_seen"] = now
            # any main-socket message proves the real route exists
            self.worker_map[worker_id].pop("hb_only", None)
            self._adoption_blocked.pop(worker_id, None)

        if msg.isa(BusyMessage):
            self.worker_map[worker_id]["busy"] = True
            return
        if msg.isa(DoneMessage):
            self.worker_map[worker_id]["busy"] = False
            return
        if msg.isa(StopMessage):
            self.remove_worker(worker_id)
            return
        if msg.isa(TicketDoneMessage):
            self.release_ticket_waiters(msg.get("ticket"), msg.get("error"))
            return
        token = msg.get("token")
        if token:
            self.worker_map[worker_id]["busy"] = False
            # chaos site controller.reply (shard results only — faulting a
            # lockstep REQ verb's reply would mis-pair the client socket):
            # drop simulates a reply lost on the wire, duplicate replays it
            fault = (
                chaos.fire(
                    "controller.reply",
                    worker=worker_id,
                    token=token,
                    verb=msg.get("payload"),
                    parent=msg.get("parent_token"),
                )
                if chaos.enabled() and msg.get("parent_token") else None
            )
            if fault is not None and fault.action == "drop":
                self.flight.record(
                    "chaos_reply_dropped", token=token, worker=worker_id
                )
                return
            entry = self.inflight.pop(token, None)
            if entry is None and token in self._hedged_tokens:
                # hedge loser / outdistanced stale attempt (or a chaos
                # duplicate of the winner): the token already completed —
                # first reply won, this one is counted and dropped, never
                # merged a second time
                self._hedged_tokens.pop(token, None)
                self._discard_loser(token, worker_id)  # answered after all
                self.counters["duplicate_replies"] += 1
                return
            if entry is None and token in self._requeued_tokens:
                # the shard's retry is still parked in the dispatch queue
                # (backoff window / waiting for a free holder) and a late
                # reply from the FAILED attempt landed first
                if msg.isa(ErrorMessage):
                    # a stale fault is not news — the queued retry stands;
                    # aborting here would fail the query with a healthy
                    # replica attempt still pending
                    self.counters["duplicate_replies"] += 1
                    self.flight.record(
                        "stale_reply_dropped",
                        token=token, worker=worker_id,
                        error=str(msg.get("payload"))[:200],
                    )
                    return
                # a late VALID result wins: withdraw the queued retry (a
                # fresh execution would be redundant) and deliver.  Mark
                # the token in the dedup ring — another superseded attempt
                # may still be computing it, and its later reply (valid OR
                # a non-transient error) must be counted and dropped, not
                # abort the parent the orphan fall-through would reach
                self._requeued_tokens.discard(token)
                self._withdraw_queued(token)
                self._mark_hedged(token, time.time())
            if entry is not None:
                assigned = entry.get("worker")
                hedged = entry.get("hedged")
                outstanding = [
                    w for w in (assigned, hedged)
                    if w is not None and w != worker_id
                ]
                if worker_id not in (assigned, hedged):
                    # late reply from a PREVIOUS attempt's worker: the shard
                    # was requeued (timeout/fault) and the CURRENT attempt
                    # is still computing on `outstanding`
                    if msg.isa(ErrorMessage):
                        # a stale fault is not news — the live attempt
                        # stands; reinstate its reclaim handle untouched
                        self.inflight[token] = entry
                        self.counters["duplicate_replies"] += 1
                        self.flight.record(
                            "stale_reply_dropped",
                            token=token, worker=worker_id,
                            error=str(msg.get("payload"))[:200],
                        )
                        return
                    # a late VALID result: first reply wins (replica holders
                    # compute the identical payload).  Dedup the live
                    # attempt's eventual reply, and keep reclaim handles on
                    # every worker still computing it — the popped entry was
                    # their hard-timeout handle, and without one a wedged
                    # holder sits busy-and-advertised forever
                    self._mark_hedged(token, time.time())
                    self._note_losers(token, outstanding)
                elif hedged and msg.isa(ErrorMessage):
                    # one side of a hedged pair failed — transiently or not
                    # — while the other is still computing and may well
                    # answer: fail over THIS side only — re-key the inflight
                    # entry to the survivor.  No requeue (a third execution
                    # would be redundant while the duplicate lives), no
                    # retry charge (the attempt continues), and no abort
                    # even for a permanent error or at the budget's edge —
                    # the outstanding answer decides; if the survivor also
                    # errors, its un-hedged entry takes the normal
                    # requeue/abort path
                    refiled = self._collapse_hedge(token, entry, worker_id)
                    transient = bool(msg.get("transient"))
                    if transient:
                        self.counters["transient_faults"] += 1
                    self.flight.record(
                        "transient_fault" if transient
                        else "hedge_side_error",
                        token=token, worker=worker_id,
                        survivor=refiled["worker"],
                        error=str(msg.get("payload"))[:200],
                    )
                    return
                elif hedged:
                    self._mark_hedged(token, time.time())  # loser still due
                    # forensic outcome events (rare, never gated): the
                    # debug-bundle timeline must explain every hedge's
                    # win/loss, not just that one was issued
                    if worker_id == hedged:
                        self.counters["hedge_wins"] += 1
                        self.flight.record(
                            "hedge_win",
                            token=token, winner=worker_id, loser=assigned,
                        )
                    else:
                        self.flight.record(
                            "hedge_loss",
                            token=token, winner=worker_id, loser=hedged,
                        )
                    # the pop above destroyed the token's inflight entry,
                    # which was also the hard-timeout reclaim handle on the
                    # side that has NOT replied yet — keep one, or a wedged
                    # loser sits busy-and-advertised forever
                    # (retry_stale_dispatches reclaims it like any other
                    # hung dispatch)
                    self._note_losers(token, outstanding)
            else:
                # orphaned late reply (its dedup-ring marker may have been
                # evicted on a busy cluster): still drain any reclaim
                # handle held on this worker — a loser that answered must
                # not be hard-timeout removed as 'silent' later
                if token in self._hedge_losers:
                    # loser tracking outlives the 256-entry ring and proves
                    # this token was already answered: count-and-drop like
                    # the ring branch — a late non-transient ErrorMessage
                    # here must not abort a parent whose shard is merged
                    self._discard_loser(token, worker_id)
                    self.counters["duplicate_replies"] += 1
                    self.flight.record(
                        "stale_reply_dropped",
                        token=token, worker=worker_id,
                        error=(
                            str(msg.get("payload"))[:200]
                            if msg.isa(ErrorMessage) else None
                        ),
                    )
                    return
                self._discard_loser(token, worker_id)
            self.process_worker_result(msg, entry, picked_up)
            if fault is not None and fault.action == "duplicate":
                # replay the envelope through the sink: definitionally a
                # duplicate.  A still-open segment counts it at the
                # in-segment key dedup; only a COMPLETED segment orphans
                # the replay before that site, so count it here exactly
                # when no open segment will (one injected duplicate = one
                # increment, never two)
                parent = msg.get("parent_token")
                subs = self._work_subscribers.get(token) or (
                    (parent,) if parent is not None else ()
                )
                if not any(p in self.rpc_segments for p in subs):
                    self.counters["duplicate_replies"] += 1
                self.process_worker_result(msg, None)

    def _record_inflight_span(self, msg, entry, picked_up=None):
        """The send→reply window, up to the reply's pickup, as an
        ``inflight`` span: worker spans carve the actual execution out of
        it at higher sweep priority, so what it surfaces in an autopsy (as
        dispatch) is the wire / poll-loop transit the controller cannot
        otherwise see — without it, a fast query's coverage is eaten by
        gaps no node owns.  Its own name keeps it out of the attempts list
        (the queue-entry dispatch span already represents the attempt)
        and off the dispatch spans, which hold no worker time."""
        from bqueryd_tpu import obs

        wire = msg.get_trace()
        sent_at = (entry or {}).get("sent_at")
        if not wire or sent_at is None or not obs.enabled():
            return
        now = picked_up[0] if picked_up is not None else time.time()
        new_spans = [
            obs.make_span(
                wire["trace_id"], "inflight", sent_at,
                max(now - float(sent_at), 0.0),
                parent_span_id=wire.get("parent_span_id"),
                node=self.address,
                tags={
                    "worker": entry.get("worker"),
                    "retries": entry.get("retries", 0),
                },
            )
        ]
        hedged_at = entry.get("hedged_at")
        if entry.get("hedged") and hedged_at is not None:
            # the hedge duplicate's racing window (hedge dispatch → this
            # reply): surfaces as the hedge_dispatch segment — how long
            # the query's tail was spent racing two holders
            new_spans.append(
                obs.make_span(
                    wire["trace_id"], "inflight", hedged_at,
                    max(now - float(hedged_at), 0.0),
                    parent_span_id=wire.get("parent_span_id"),
                    node=self.address,
                    tags={"worker": entry.get("hedged"), "hedge": True},
                )
            )
        for parent in self._work_parents(msg):
            segment = self.rpc_segments.get(parent)
            if segment is not None and segment.get("obs"):
                segment["obs"]["spans"].extend(new_spans)

    # -- results sink ------------------------------------------------------
    def process_worker_result(self, msg, entry=None, picked_up=None):
        """``picked_up`` (wall, perf_counter): handle_in's pickup of the
        reply, where its reply_absorb span starts and its inflight ends."""
        parent = msg.get("parent_token")
        token = msg.get("token")
        if token is not None and token in self._append_waiters:
            # streaming-append fan-out reply: collected per holder, the
            # client answered once every replica confirmed
            self._absorb_append_reply(token, msg)
            return
        if isinstance(token, str) and token.startswith("append_"):
            # orphaned append reply: its waiter already failed fast
            # (holder removal) or timed out — the client was answered.
            # Matched by the synthetic token prefix, NOT the verb: an
            # ErrorMessage reply's payload is the traceback, so
            # isa("append") would miss it and the fall-through would hand
            # the non-hex dispatch token to reply_rpc_message
            self.flight.record(
                "append_reply_orphaned", token=token,
            )
            return
        if token is not None and token in self._rollup_waiters:
            # controller-originated rollup build/refresh reply: absorbed
            # into the serving layer, never forwarded to any client
            self._absorb_rollup_reply(token, msg)
            return
        if isinstance(token, str) and token.startswith("rollup_"):
            # orphaned rollup reply: the entry was evicted/abandoned while
            # the build was in flight (same prefix-match rationale as the
            # append orphan above — ErrorMessage payloads aren't the verb)
            self.flight.record("rollup_reply_orphaned", token=token)
            return
        subscribers = self._work_subscribers.get(token)
        if entry is not None and not (
            msg.isa(ErrorMessage) and msg.get("transient")
        ):
            # the transient-fault path records its own (failed-tagged)
            # in-flight span inside _requeue
            self._record_inflight_span(msg, entry, picked_up)
        if parent is None and not subscribers:
            # single-segment RPC (execute_code, sleep, readfile): a binary
            # data frame is folded into the JSON reply as base64
            data = msg.pop("data", None)
            if data is not None:
                msg.add_as_binary("result", data)
            self.reply_rpc_message(msg.get("token"), msg)
            return
        if msg.isa(ErrorMessage) and msg.get("transient"):
            # transient (retryable) worker fault — DeviceBusyError class:
            # fail the shard over to a different holder instead of killing
            # the query; _requeue excludes the faulted worker and aborts
            # with the structured envelope only once the budget is spent
            reason = str(msg.get("payload") or "transient fault")
            reason = (reason.strip().splitlines() or ["transient fault"])[-1]
            if entry is not None:
                self.counters["transient_faults"] += 1
                self.flight.record(
                    "transient_fault",
                    token=token,
                    worker=entry.get("worker"),
                    error=reason[:200],
                )
                self._requeue(
                    entry, failed_worker=entry.get("worker"), reason=reason
                )
            # entry is None: the shard was already requeued (timeout) or
            # completed (hedge), or this is a chaos replay — a duplicate of
            # a fault, not a new one (one real fault = one count)
            else:
                self.counters["duplicate_replies"] += 1
            return
        self._drop_work(token)
        parents = list(subscribers) if subscribers else [parent]
        if msg.isa(ErrorMessage):
            error_class = None
            error_text = msg.get("payload")
            if msg.get("dag") and "unknown aggregation op" in str(error_text):
                # a DAG dispatch answered by a pre-DAG worker, which
                # executed the positional params and rejected the extended
                # op string: reply the STRUCTURED mixed-version error
                # MIGRATION "PR 13" promises instead of relaying the
                # worker traceback
                error_class = "UnsupportedOp"
                error_text = (
                    "query dispatched to a worker that does not understand "
                    "operator DAGs (pre-PR-13 build); upgrade every calc "
                    "worker before using rpc.query (see MIGRATION.md PR 13)"
                )
            for p in parents:
                self.abort_parent(p, error_text, error_class=error_class)
            return
        if msg.get("_bundle_parents"):
            if msg.get("bundle_members") is not None:
                # shared-scan bundle reply: one envelope, one payload PER
                # member — demultiplexed into each member's own segment
                self._demux_bundle(msg)
            else:
                # a bundle dispatch answered WITHOUT the bundle_members key:
                # a pre-PR-9 worker executed only the positional params
                # (member 0's query).  Falling through to the shared-
                # dispatch sink would hand that one payload to EVERY
                # member — silent wrong results.  Abort all members with
                # the mixed-version error MIGRATION.md promises instead.
                self.logger.warning(
                    "bundle %s answered without bundle_members "
                    "(pre-PR-9 worker?); aborting its members",
                    token,
                )
                for p in dict.fromkeys(msg["_bundle_parents"].values()):
                    self.abort_parent(
                        p,
                        "bundle dispatched to a worker that does not "
                        "understand shared-scan bundles; keep "
                        "BQUERYD_TPU_BATCH_WINDOW_MS=0 until every calc "
                        "worker is upgraded (see MIGRATION.md PR 9)",
                    )
            return
        filename = msg.get("filename")
        # a batched shard-group reply covers several filenames with ONE
        # already-merged payload (the worker's on-device psum merge);
        # completion is counted in covered filenames, not replies
        key = tuple(filename) if isinstance(filename, list) else (filename,)
        data = msg.get("data") or b""
        # payload bytes over the wire, counted once per reply (not per
        # subscriber): the metric the bench's merge section reads as the
        # host-gather baseline the device-resident merge is judged against
        self.counters["reply_payload_bytes"] += len(data)
        delivered = False
        counted_duplicate = False
        for p in parents:
            segment = self.rpc_segments.get(p)
            if segment is None:
                continue  # that subscriber aborted earlier
            delivered = True
            if key in segment["results"] and not counted_duplicate:
                # token/key dedup backstop (late retry, hedge loser, chaos
                # duplicate): the payload slot is keyed by shard filename,
                # so a duplicate overwrites its own identical payload —
                # counted for visibility (once per ENVELOPE, not per
                # subscriber of shared work), structurally never
                # double-merged
                self.counters["duplicate_replies"] += 1
                counted_duplicate = True
            segment["results"][key] = data
            segment["timings"][key] = msg.get("phase_timings")
            effective = msg.get("effective_strategy")
            if isinstance(effective, str):
                segment.setdefault("effective", {})[key] = effective
            merge_mode = msg.get("merge_mode")
            if isinstance(merge_mode, str):
                segment.setdefault("merge", {})[key] = merge_mode
            compiled = msg.get("compiled")
            if isinstance(compiled, int):
                # the compile mark (absent from a steady-state reply)
                segment["compiled"] = segment.get("compiled", 0) + compiled
            # worker-side spans (calc root + phases) fold into the timeline;
            # shared dispatches land on every subscriber's segment
            spans = msg.get("spans")
            if isinstance(spans, list) and segment.get("obs"):
                segment["obs"]["spans"].extend(
                    s for s in spans if isinstance(s, dict)
                )
            self._maybe_complete_segment(
                p, self._record_reply_absorb(segment, picked_up)
            )
        if not delivered:
            self.logger.warning("orphaned result for parent %s dropped", parent)

    def _demux_bundle(self, msg):
        """Per-member demultiplex of a shared-scan bundle reply: the data
        frame is one pickled ``{"payloads": {member_id: bytes}, "errors":
        {member_id: text}}`` envelope.  Fault isolation is per member: an
        errored/expired member aborts ITS parent only; members whose
        parents aborted earlier (supersede, deadline) are skipped; the
        others complete normally."""
        from bqueryd_tpu import obs

        token = msg.get("token")
        data = msg.get("data") or b""
        # payload bytes over the wire, once per reply (the controller-side
        # twin of the worker's reply_bytes histogram)
        self.counters["reply_payload_bytes"] += len(data)
        bundle_parents = msg.get("_bundle_parents") or {}
        try:
            envelope = pickle.loads(data) if data else {}
        except Exception:
            for parent in set(bundle_parents.values()):
                self.abort_parent(parent, "undecodable bundle reply")
            return
        member_payloads = envelope.get("payloads") or {}
        member_errors = envelope.get("errors") or {}
        # per-member segment shares (messages.py `member_shares`): the
        # fraction of the bundle's shared scan each member is accountable
        # for — shared phase timings are scaled by it so a slow BUNDLE
        # lands each member in the slow-query ring (and its autopsy) with
        # ITS share of the wall, not the whole bundle's; pre-PR-10 workers
        # ship no shares and the timings pass through unscaled
        member_shares = msg.get("member_shares")
        if not isinstance(member_shares, dict):
            member_shares = {}
        filename = msg.get("filename")
        key = tuple(filename) if isinstance(filename, list) else (filename,)
        delivered = False
        counted_duplicate = False
        for member_id, parent in bundle_parents.items():
            # per-member demux clock: a member's span must cover ITS slice
            # of the demultiplex only — measured from iteration start to
            # span append, so an earlier member's completion work (merge,
            # attribution, reply — it runs inside _maybe_complete_segment)
            # can never inflate a later member's bundle_demux segment
            member_start_ts = time.time()
            member_clock = time.perf_counter()
            segment = self.rpc_segments.get(parent)
            if segment is None:
                continue  # that member aborted earlier
            delivered = True
            error = member_errors.get(member_id)
            if error is not None:
                # member-only failure (deadline expiry, a member-shape
                # rejection): abort THIS member; bundle-mates complete
                self.abort_parent(parent, error)
                continue
            buf = member_payloads.get(member_id)
            if buf is None:
                self.abort_parent(
                    parent, "bundle reply missing this member's payload"
                )
                continue
            if key in segment["results"] and not counted_duplicate:
                # same dedup backstop as the shared-dispatch sink: a
                # duplicate envelope overwrites its own identical payloads
                self.counters["duplicate_replies"] += 1
                counted_duplicate = True
            segment["results"][key] = buf
            share = member_shares.get(member_id)
            try:
                share = float(share) if share is not None else None
            except (TypeError, ValueError):
                share = None
            timings = msg.get("phase_timings")
            if share is not None and isinstance(timings, dict):
                scaled = {
                    k: round(v * share, 6)
                    for k, v in timings.items()
                    if isinstance(v, (int, float))
                }
                # underscore-namespaced like _total, so it can never
                # collide with a real phase name
                scaled["_member_share"] = round(share, 6)
                timings = scaled
            segment["timings"][key] = timings
            effective = msg.get("effective_strategy")
            if isinstance(effective, str):
                segment.setdefault("effective", {})[key] = effective
            merge_mode = msg.get("merge_mode")
            if isinstance(merge_mode, str):
                segment.setdefault("merge", {})[key] = merge_mode
            compiled = msg.get("compiled")
            if isinstance(compiled, int):
                # the compile mark (absent from a steady-state reply)
                segment["compiled"] = segment.get("compiled", 0) + compiled
            spans = msg.get("spans")
            if isinstance(spans, list) and segment.get("obs"):
                obs_state = segment["obs"]
                if share is not None:
                    # per-member span copies tagged with the share: the
                    # autopsy keeps true-wall segments and reports this
                    # member's accountable slice beside them
                    obs_state["spans"].extend(
                        dict(
                            s,
                            tags={
                                **(s.get("tags") or {}),
                                "bundle_share": round(share, 6),
                            },
                        )
                        for s in spans if isinstance(s, dict)
                    )
                else:
                    obs_state["spans"].extend(
                        s for s in spans if isinstance(s, dict)
                    )
                obs_state["spans"].append(
                    obs.make_span(
                        obs_state["trace_id"], "demux", member_start_ts,
                        time.perf_counter() - member_clock,
                        parent_span_id=obs_state["qspan_id"],
                        node=self.address,
                    )
                )
            self._maybe_complete_segment(parent)
        if not delivered:
            self.logger.warning(
                "orphaned bundle result %s dropped", token
            )

    def _maybe_complete_segment(self, parent, began=None):
        """Reply to the client once every requested shard is covered (by a
        worker payload, a batched group payload, or a plan-time prune).
        ``began`` (wall, perf_counter): where the reply_absorb span ended,
        and so where reply_encode starts; else the cover check's end."""
        segment = self.rpc_segments.get(parent)
        if segment is None:
            return
        # greedy DISJOINT cover, largest keys first: a re-split batch can
        # leave both the late batch payload and its per-shard children in
        # results (keys are laminar — a group and/or singletons from its
        # re-split), and overlapping keys must neither complete the
        # segment early nor merge a shard's payload twice
        chosen, covered = [], set()
        for k in sorted(segment["results"], key=len, reverse=True):
            files = set(k)
            if files & covered:
                continue
            chosen.append(k)
            covered |= files
        if not covered.issuperset(segment["filenames"]):
            return
        if began is None:
            began = (time.time(), time.perf_counter())
        self.rpc_segments.pop(parent)
        # payloads in requested-filename order (not reply-arrival order):
        # the aggregate=False rows path concatenates payloads client-side,
        # and the reference's row order is deterministic by filename
        covering = {f: k for k in chosen for f in k}
        payloads, seen = [], set()
        for f in segment["filenames"]:
            k = covering[f]
            if k not in seen:
                seen.add(k)
                payloads.append(segment["results"][k])
        # compact key: a batched shard-group is labelled by its first
        # file + count, not the joined list (a 10-shard join produced a
        # 130+ char key that bloated the bench's one-line JSON past what
        # log tails keep intact); same labelling as the slow-query log
        timings = self._compact_timings(segment["timings"])
        # answer provenance for the dispatched path: every shard served
        # from a worker result cache -> "cached"; any delta-maintained
        # shard -> "delta"; else a real recompute.  The serving layer's
        # direct replies (_reply_served) stamp "rollup"/"subsume"
        effective_routes = set((segment.get("effective") or {}).values())
        if effective_routes and effective_routes <= {"cached"}:
            answer_source = "cached"
        elif "delta" in effective_routes:
            answer_source = "delta"
        else:
            answer_source = "recompute"
        self._count_answer(answer_source)
        envelope = {
            "ok": True,
            "payloads": payloads,
            "timings": timings,
            # PR-16 provenance: how this answer was produced, and (for
            # subsumption serves) which materialized view proved it
            "answer_source": answer_source,
            "subsumed_from": None,
            # route visibility end to end: "hints" counts the shards
            # dispatched ({"auto": n} — a dispatch names no kernel),
            # "effective" is the route each worker's kernel rule took
            "strategies": {
                "hints": dict(segment.get("strategies", {})),
                "effective": self._compact_timings(
                    segment.get("effective")
                ),
            },
            # per shard-group: how the worker merged the payload
            # (device = ICI-mesh collective, host = hostmerge fallback,
            # none = single payload)
            "merge_modes": self._compact_timings(segment.get("merge")),
        }
        if segment.get("compiled"):
            envelope["compiled"] = segment["compiled"]
        reply = pickle.dumps(envelope, protocol=4)
        self._finish_segment(parent, segment, reply, encode_began=began)

    def _finish_segment(self, parent, segment, reply_bytes=None, error=None,
                        encode_began=None):
        """Final reply for a groupby parent + admission slot release.
        ``reply_bytes=None`` finishes silently (a cancelled query whose
        client is no longer waiting — replying would mis-pair with the
        identity's next request).  ``encode_began``: the reply_encode
        span's start (envelope build, pickle, send to the client)."""
        if reply_bytes is not None:
            self.reply_rpc_raw(segment["client_token"], reply_bytes)
        if encode_began is not None and segment.get("obs"):
            from bqueryd_tpu import obs

            if obs.enabled():
                obs_state = segment["obs"]
                obs_state["spans"].append(
                    obs.make_span(
                        obs_state["trace_id"], "reply_encode",
                        encode_began[0],
                        time.perf_counter() - encode_began[1],
                        parent_span_id=obs_state["qspan_id"],
                        node=self.address,
                    )
                )
        self._finalize_query_obs(parent, segment, error=error)
        ticket = segment.get("admission_ticket")
        if ticket is not None:
            self.admission.release(ticket)
            self._ticket_sigs.pop(ticket, None)
            self._admit_ready()

    def _new_obs_state(self, ctx, picked_up=None):
        """Per-query trace state: the client's context, the controller
        "groupby" span id every query span parents to, the span list the
        timeline is assembled from, and the submit clock the admission
        span measures against.  ``picked_up`` (wall, perf_counter): where
        handle_in picked the request up; the request_decode span runs from
        there to here (msg_factory, the flight record, the verb's checks),
        before the groupby root, under the client's span."""
        from bqueryd_tpu import obs

        decoded = time.perf_counter()
        state = {
            "trace_id": ctx.trace_id,
            "root_span_id": ctx.span_id,
            "qspan_id": obs.new_id(),
            "spans": [],
            "submitted_ts": time.time(),
        }
        if picked_up is not None and obs.enabled():
            state["spans"].append(
                obs.make_span(
                    ctx.trace_id, "request_decode", picked_up[0],
                    decoded - picked_up[1], parent_span_id=ctx.span_id,
                    node=self.address,
                )
            )
        return state

    def _record_reply_absorb(self, segment, picked_up):
        """The reply_absorb span: handle_in's pickup of a worker's reply to
        this segment's completion check.  Returns that instant on both
        clocks, where reply_encode starts, so the two never overlap."""
        from bqueryd_tpu import obs

        if picked_up is None or not segment.get("obs") or not obs.enabled():
            return None
        # the end on the wall clock is the pickup's plus the elapsed time,
        # not a second read of time.time(): a thread switched out between
        # two clock reads would end the span after reply_encode starts
        now = time.perf_counter()
        elapsed = now - picked_up[1]
        obs_state = segment["obs"]
        obs_state["spans"].append(
            obs.make_span(
                obs_state["trace_id"], "reply_absorb", picked_up[0], elapsed,
                parent_span_id=obs_state["qspan_id"], node=self.address,
            )
        )
        return (picked_up[0] + elapsed, now)

    def _observe_admission_wait(self, wait_s):
        """Admission's wait hook: queued time before launch."""
        from bqueryd_tpu import obs

        if obs.enabled():
            self.admission_wait_seconds.observe(wait_s)
        # the capacity model's measured-wait cross-check has its own kill
        # switch (BQUERYD_TPU_CAPACITY) — a queue-wait sample is capacity
        # evidence whether or not the span hot path is on
        self.capacity.observe_queue_wait(wait_s, source="admission")

    def _observe_arrival(self, decision, payload):
        """Admission's arrival tap: every offered groupby (ADMIT, QUEUED
        and BUSY alike) lands in the capacity model's per-class arrival
        window — λ is offered load, and shed load is what saturation looks
        like."""
        del decision  # offered load counts every outcome alike
        msg = payload[0] if payload else None
        slo_class = (
            self.slo.resolve(msg.get("slo_class"))
            if msg is not None else "default"
        )
        self.capacity.observe_arrival(slo_class)

    def _record_capacity_advice(self, rec):
        """Shadow-advisor sink: every NEW recommendation is a flight event
        (ungated — advice changes are rare by construction) and a counter
        bump.  Nothing acts on it; a later enforcement PR consumes these."""
        action = rec.get("action")
        counter_key = f"capacity_{action}_advised"
        if counter_key in self.counters:
            self.counters[counter_key] += 1
        self.flight.record(
            "capacity_advice",
            action=action,
            n=rec.get("n"),
            shard=rec.get("shard"),
            to_worker=rec.get("to_worker"),
            reason=str(rec.get("reason"))[:200],
        )

    def _timeline_snapshot(self):
        """One ``rpc.timeline()`` ring entry: the compact controller state
        a regression diff needs — counters, queue/inflight depths, fleet
        size, groupby latency quantiles, SLO burn rates."""
        from bqueryd_tpu.obs import metrics as obs_metrics

        snap = self.query_seconds.snapshot()
        admission = self.admission.stats()
        return {
            "counters": dict(self.counters),
            "inflight": len(self.inflight),
            "workers": len(self.worker_map),
            "admission_active": admission["active"],
            "admission_queued": admission["queued"],
            "groupby_count": sum(snap.get("counts", ())),
            "groupby_p50_s": obs_metrics.quantile_from_snapshot(snap, 0.5),
            "groupby_p99_s": obs_metrics.quantile_from_snapshot(snap, 0.99),
            "slo": self.slo.snapshot(),
            # fleet utilization/saturation per tick: the existing ring
            # doubles as capacity history (was this cluster saturated an
            # hour ago is one rpc.timeline() away)
            "capacity": self._capacity_timeline_fields(),
        }

    def _capacity_timeline_fields(self):
        """The compact capacity slice each timeline-ring entry carries."""
        fleet = self.capacity.snapshot().get("fleet") or {}
        return {
            key: fleet.get(key)
            for key in (
                "utilization", "state", "arrival_qps", "knee_qps",
                "headroom_qps", "model_drift",
            )
        }

    @staticmethod
    def _compact_timings(timings):
        """Tuple-keyed per-shard timings -> JSON-safe compact keys (same
        labelling as the client reply: first file + count for a group)."""
        return {
            (k[0] if len(k) == 1 else f"{k[0]}+{len(k) - 1}more"): v
            for k, v in (timings or {}).items()
        }

    def _finalize_query_obs(self, parent, segment, error=None):
        """Every finished groupby parent (success, abort, or silent
        supersede) lands here exactly once: latency histogram observation,
        timeline assembly into the trace ring buffer, slow-query check."""
        from bqueryd_tpu import obs

        began = (time.time(), time.perf_counter())
        wall = began[1] - segment.get("created_clock", began[1])
        self.counters["queries_completed"] += 1
        obs_state = segment.get("obs")
        if error is not None:
            # forensic event (never gated): failed queries are exactly what
            # a debug bundle gets pulled for
            self.flight.record(
                "query_failed",
                parent=parent,
                trace_id=(obs_state or {}).get("trace_id"),
                wall_s=round(wall, 6),
                error=str(error)[:300],
            )
        if not obs.enabled():
            return
        if error is None:
            self.flight.record(
                "query_done",
                parent=parent,
                trace_id=(obs_state or {}).get("trace_id"),
                wall_s=round(wall, 6),
                shards=len(segment.get("filenames", ())),
            )
        self.query_seconds.observe(wall)
        # SLO accounting: every finished groupby lands in its client class's
        # deadline-margin histogram and burn-rate window.  An absolute
        # client deadline wins over the class target; without one the
        # margin is measured against the class's target_s
        msg = segment["msg"]
        deadline = msg.get("deadline")
        margin_s = (
            float(deadline) - time.time() if deadline is not None else None
        )
        self.slo.record(
            msg.get("slo_class"),
            wall,
            margin_s=margin_s,
            ok=error is None,
        )
        if not obs_state:
            return
        trace_id = obs_state["trace_id"]
        # the parent span opens at SUBMIT (so its admission-wait child can
        # never start before it) and closes now: queue wait + execution
        submitted = obs_state.get("submitted_ts", segment["created"])
        spans = [
            obs.make_span(
                trace_id, "groupby", submitted,
                wall + max(segment["created"] - submitted, 0.0),
                span_id=obs_state["qspan_id"],
                parent_span_id=obs_state["root_span_id"],
                node=self.address,
                tags={"parent_token": parent},
            )
        ]
        for span in obs_state["spans"]:
            # shared-dispatch worker spans were recorded under the trace of
            # whichever subscriber created the work unit — retag so every
            # timeline is self-consistent
            span = dict(span)
            span["trace_id"] = trace_id
            spans.append(span)
        spans.sort(key=lambda s: s.get("start_ts", 0.0))
        timeline = {
            "trace_id": trace_id,
            "ok": error is None,
            "wall_s": round(wall, 6),
            "created_ts": segment["created"],
            "filenames": list(segment["filenames"]),
            "pruned": list(segment.get("pruned", ())),
            "spans": spans,
        }
        if error is not None:
            timeline["error"] = str(error)[:500]
        # critical-path attribution, assembled at trace completion: the
        # autopsy record rides the stored timeline (rpc.autopsy /
        # debug_bundle read it back for free); a malformed span set must
        # never break query completion
        try:
            timeline["attribution"] = obs.slo.attribute(timeline)
        except Exception:
            self.logger.exception("attribution failed for %s", trace_id)
        # capacity cross-check: the query's MEASURED pre-worker wait
        # (admission_wait + dispatch segments — submit to worker send,
        # exactly what the M/G/1 prediction models; retry backoff is
        # failure-induced, not load-induced, and stays out) feeds the
        # model's measured-wait EWMA, whose gap to the prediction is the
        # model_drift gauge
        attribution = timeline.get("attribution")
        if isinstance(attribution, dict) and error is None:
            segments = attribution.get("segments") or {}
            self.capacity.observe_queue_wait(
                segments.get("admission_wait", 0.0)
                + segments.get("dispatch", 0.0),
                source="autopsy",
            )
        self.trace_store.put(trace_id, timeline)
        recorded = self.slow_queries.maybe_record(
            wall,
            {
                "trace_id": trace_id,
                "ok": error is None,
                **({"error": str(error)[:200]} if error is not None else {}),
                "filenames": len(segment["filenames"]),
                "pruned_shards": len(segment.get("pruned", ())),
                "plan_signature": segment.get("plan_sig"),
                "slo_class": self.slo.resolve(msg.get("slo_class")),
                "strategy_hints": dict(segment.get("strategies", {})),
                "effective_strategies": self._compact_timings(
                    segment.get("effective")
                ),
                "phase_timings": self._compact_timings(segment.get("timings")),
                # compact critical-path view (full record: rpc.autopsy)
                "attribution": obs.slo.summarize(
                    timeline.get("attribution")
                ),
            },
        )
        if recorded:
            self.counters["slow_queries"] += 1
        # this function's own span, after the client's reply: it costs the
        # loop, not the query, and the next request may wait behind it.
        # Appended to the stored timeline itself, after the put
        spans.append(
            obs.make_span(
                trace_id, "finalize", began[0],
                time.perf_counter() - began[1],
                parent_span_id=obs_state["root_span_id"], node=self.address,
            )
        )

    def abort_parent(self, parent, error_text, reply=True, error_class=None,
                     attempts=None):
        segment = self.rpc_segments.pop(parent, None)
        if segment is None:
            return
        # detach this parent from shared work units; units with no remaining
        # subscriber die, shared ones keep computing for their other parents
        dead = set()
        for token, subs in list(self._work_subscribers.items()):
            if parent in subs:
                subs[:] = [p for p in subs if p != parent]
                if not subs:
                    dead.add(token)
                    self._drop_work(token)
        for token in dead:
            self.inflight.pop(token, None)
        # drop queued siblings of the aborted query (shared units survive
        # via their live subscriber list)
        for queue in self.worker_out_messages.values():
            queue[:] = [
                m for m in queue
                if m.get("token") not in dead
                and not (
                    m.get("parent_token") == parent
                    and m.get("token") not in self._work_subscribers
                )
            ]
        self._finish_segment(
            parent,
            segment,
            pickle.dumps(
                {
                    "ok": False,
                    "error": str(error_text),
                    # structured failure detail (messages.py result-envelope
                    # schema): the error class plus the per-attempt
                    # worker/fault history the flight recorder accumulated,
                    # so a retry-exhausted client learns WHERE its query
                    # died instead of a bare string (or a blind timeout)
                    "error_class": error_class,
                    "attempts": list(attempts or []),
                },
                protocol=4,
            ) if reply else None,
            error=error_text,
        )

    def reply_rpc_raw(self, client_token, payload_bytes):
        client = binascii.unhexlify(client_token)
        try:
            self.socket.send_multipart([client, b"", payload_bytes])
        except zmq.ZMQError:
            self.logger.exception("could not reply to client %r", client_token)

    def reply_rpc_message(self, client_token, msg):
        if client_token is None:
            return
        msg.pop("data", None)
        self.reply_rpc_raw(client_token, msg.to_json().encode())

    # -- peer gossip -------------------------------------------------------
    def handle_peer(self, msg):
        addr = msg.get("from")
        if addr and addr != self.address:
            info = msg.get_from_binary("info", {})
            info["last_seen"] = time.time()
            self.others[addr] = info

    # -- RPC dispatch ------------------------------------------------------
    def handle_rpc(self, client, payload, picked_up=None):
        token = binascii.hexlify(client).decode()
        try:
            msg = msg_factory(payload)
        except messages.MalformedMessage:
            self.reply_rpc_raw(token, b'{"payload": "malformed request"}')
            return
        msg["token"] = token
        verb = msg.get("payload")
        if picked_up is not None and verb in ("groupby", "query"):
            msg["_picked_up"] = picked_up   # popped by the verb, never sent on
        from bqueryd_tpu import obs

        # flight ring: client envelopes (hot path — kill-switch gated; pings
        # are connection noise, not forensics)
        if verb != "ping" and obs.enabled():
            self.flight.record(
                "rpc",
                verb=verb,
                client=token[:12],
                trace_id=(msg.get_trace() or {}).get("trace_id"),
            )
        handler = getattr(self, f"rpc_{verb}", None)
        if verb not in CONTROLLER_VERBS or handler is None:
            err = ErrorMessage(msg)
            err["payload"] = f"Sorry, unknown verb {verb!r}"
            self.reply_rpc_message(token, err)
            return
        try:
            handler(msg)
        except Exception as exc:
            self.logger.exception("rpc %s failed", verb)
            err = ErrorMessage(msg)
            err["payload"] = f"{type(exc).__name__}: {exc}"
            self.reply_rpc_message(token, err)

    def rpc_ping(self, msg):
        reply = msg.copy()
        reply["payload"] = "pong"
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_info(self, msg):
        reply = msg.copy()
        reply.add_as_binary("result", self.get_info())
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_metrics(self, msg):
        """Prometheus text exposition of this controller's registry — the
        RPC twin of the opt-in /metrics HTTP endpoint."""
        reply = msg.copy()
        reply.add_as_binary("result", self.metrics.render())
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_trace(self, msg):
        """The assembled per-query timeline for one trace_id (or None when
        it fell out of the ring buffer): ``rpc.trace(rpc.last_trace_id)``."""
        args, _ = msg.get_args_kwargs()
        trace_id = args[0] if args else None
        reply = msg.copy()
        reply.add_as_binary("result", self.trace_store.get(trace_id))
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_slow_queries(self, msg):
        """The slow-query ring buffer (threshold BQUERYD_TPU_SLOW_QUERY_MS),
        newest last: plan signature, strategy hints, pruned-shard count, and
        phase breakdown per offender."""
        reply = msg.copy()
        reply.add_as_binary("result", self.slow_queries.entries())
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_autopsy(self, msg):
        """``rpc.autopsy(trace_id=None)``: the attributed critical-path
        breakdown for one query (or the newest) — wall decomposed into
        non-overlapping named segments with coverage accounting, the
        per-attempt dispatch history (retries, backoff, hedges), and the
        slow-query ring entry when the query crossed the threshold.  None
        when the timeline fell out of the ring."""
        args, kwargs = msg.get_args_kwargs()
        trace_id = args[0] if args else kwargs.get("trace_id")
        reply = msg.copy()
        reply.add_as_binary("result", self.build_autopsy(trace_id))
        self.reply_rpc_message(msg.get("token"), reply)

    def build_autopsy(self, trace_id=None):
        from bqueryd_tpu import obs

        timeline = (
            self.trace_store.get(trace_id)
            if trace_id
            else self.trace_store.latest()
        )
        if timeline is None:
            return None
        record = timeline.get("attribution")
        if not isinstance(record, dict):
            # a timeline stored before attribution existed (or whose
            # assembly failed): attribute on demand
            record = obs.slo.attribute(timeline)
        record = dict(record)
        slow = self.slow_queries.entry_for(record.get("trace_id"))
        if slow is not None:
            record["slow_query"] = slow
        return record

    def rpc_timeline(self, msg):
        """``rpc.timeline()``: the bounded ring of periodic controller
        registry snapshots (counters, queue depths, latency quantiles, SLO
        burn rates; one entry per BQUERYD_TPU_TIMELINE_INTERVAL_S), oldest
        first — regression spotting from one verb."""
        reply = msg.copy()
        reply.add_as_binary("result", self.timeline_ring.entries())
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_capacity(self, msg):
        """``rpc.capacity()``: the fleet capacity model — per-worker μ/λ/ρ
        and saturation state (hysteresis applied), fleet utilization,
        predicted-vs-measured queue delay with the drift between them, the
        per-shard dispatch heat map, headroom QPS / the predicted
        saturation knee, and the shadow advisor's current recommendations
        with their evidence.  Advisory only: nothing here is acted on."""
        self.capacity.evaluate()
        reply = msg.copy()
        reply.add_as_binary("result", self.capacity.snapshot())
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_health(self, msg):
        """Per-worker health statuses (ok/degraded/wedged) from the rolling
        latency/error baselines — the view dispatch routing acts on."""
        from bqueryd_tpu.obs import health as health_mod

        reply = msg.copy()
        reply.add_as_binary(
            "result",
            {
                "workers": self.health.statuses(),
                "routing_enabled": health_mod.routing_enabled(),
            },
        )
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_debug_bundle(self, msg):
        """``rpc.debug_bundle(trace_id=None)``: the cross-node forensic
        artifact (schema ``bqueryd_tpu.debug_bundle/4``) — flight rings,
        the requested (or newest) trace timeline, metrics and slow-query
        snapshots, per-worker compile registries and device health.  One
        JSON-safe dict you can attach to a bug report; dead peers degrade
        it (stale snapshots, ``partial`` list), never fail it."""
        args, kwargs = msg.get_args_kwargs()
        trace_id = args[0] if args else kwargs.get("trace_id")
        reply = msg.copy()
        reply.add_as_binary("result", self.build_debug_bundle(trace_id))
        self.reply_rpc_message(msg.get("token"), reply)

    def build_debug_bundle(self, trace_id=None):
        """Assemble the debug artifact from controller-held state (no
        blocking round-trips: worker slices come from absorbed WRM
        heartbeats, so a wedged or dead worker can't stall the bundle)."""
        from bqueryd_tpu import obs
        from bqueryd_tpu.obs import profile as obs_profile

        timeline = (
            self.trace_store.get(trace_id)
            if trace_id
            else self.trace_store.latest()
        )
        controller_section = {
            "address": self.address,
            "node": self.node_name,
            "uptime_s": round(time.time() - self.start_time, 3),
            "flight": self.flight.events(),
            "flight_evictions": self.flight.evictions,
            "counters": dict(self.counters),
            "admission": self.admission.stats(),
            "workers_known": sorted(self.worker_map),
            "inflight": {
                token: {
                    "worker": e["worker"],
                    "age_s": round(time.time() - e["sent_at"], 3),
                    "retries": e.get("retries", 0),
                }
                for token, e in self.inflight.items()
            },
            "health": self.health.statuses(),
            "trace": timeline,
            # the attributed critical path of the bundled trace: the "where
            # did the wall go" answer inline, not one more verb away
            "autopsy": (timeline or {}).get("attribution"),
            "slow_queries": self.slow_queries.entries(),
            "metrics": self.metrics.histogram_snapshot(),
            "worker_histograms": self._aggregate_worker_histograms(),
            "runtime": obs_profile.runtime_versions(),
            "compile_cache": obs_profile.compile_cache_info(),
            # subsystems grown since PR 3 — the forensic artifact must
            # cover the failure surfaces that now shape a query's fate:
            # chaos/fault-injection and replica placement (PR 8), the
            # micro-batch window (PR 9), and the SLO/timeline accounting
            # this PR adds
            "chaos": {
                "armed": chaos.enabled(),
                "injected_total": chaos.injected_total(),
                "site_stats": chaos.site_stats(),
            },
            "replication": self._replication_info(),
            "batch_window": self._batch_window_info(),
            "slo": self.slo.snapshot(),
            "timeline_ring": self.timeline_ring.entries()[-16:],
            # the fleet capacity model (PR 12): per-worker μ/ρ/state, shard
            # heat map, predicted-vs-measured queue delay, last shadow
            # recommendations — freshly evaluated, the bundle must not
            # ship a stale saturation verdict
            "capacity": self._capacity_bundle_section(),
            # the semantic serving layer (PR 16, schema /4): rollup entry
            # states + heat, and the last subsumption decisions with their
            # rejected candidates and reasons
            "serving": self.serving.snapshot(),
        }
        snapshots = {}
        for worker_id in set(self.worker_map) | set(self._worker_debug):
            absorbed = self._worker_debug.get(worker_id)
            snapshots[worker_id] = {
                "data": absorbed["data"] if absorbed else None,
                "ts": absorbed["ts"] if absorbed else None,
                "registered": worker_id in self.worker_map,
            }
        # redaction roots: serving data dirs, the runfile dir, and the
        # compile-cache path are operational facts; everything else path-
        # shaped (home dirs, site-packages in tracebacks) is reduced to
        # <redacted>/basename before the bundle can leave the cluster
        allowed = {self.runfile_dir}
        allowed.update(
            info.get("data_dir") for info in self.worker_map.values()
        )
        # the compile cache belongs to the jax-owning processes: each
        # worker's slice reports where ITS cache resolved (a JAX-free
        # controller has none of its own)
        for section in [controller_section] + [
            (absorbed.get("data") or {})
            for absorbed in self._worker_debug.values()
        ]:
            allowed.add((section.get("compile_cache") or {}).get("path"))
        return obs.build_bundle(
            controller_section,
            snapshots,
            trace_id=trace_id or (timeline or {}).get("trace_id"),
            allowed_path_prefixes=[p for p in allowed if p],
        )

    def _dump_debug_signal(self, *args):
        from bqueryd_tpu.obs import flightrec

        try:
            path = flightrec.dump_bundle(
                self.build_debug_bundle(), role="controller"
            )
            self.logger.warning("SIGUSR1: debug bundle written to %s", path)
        except Exception:
            self.logger.exception("SIGUSR1 debug dump failed")

    def get_info(self, include_peers=True):
        from bqueryd_tpu.obs import profile as obs_profile

        health_runtime = {
            "runtime": obs_profile.runtime_versions(),
            "compile_cache": obs_profile.compile_cache_info(),
            "worker_runtime": {
                worker_id: (absorbed.get("data") or {}).get("runtime")
                for worker_id, absorbed in self._worker_debug.items()
                if worker_id in self.worker_map
            },
        }
        info = {
            "address": self.address,
            "node": self.node_name,
            "uptime": time.time() - self.start_time,
            "msg_count_in": self.msg_count_in,
            "workers": self.worker_map,
            "worker_out_messages": {
                str(k): len(v) for k, v in self.worker_out_messages.items()
            },
            "inflight": len(self.inflight),
            "rpc_segments": len(self.rpc_segments),
            "counters": dict(self.counters),
            "admission": self.admission.stats(),
            "shard_stats_known": len(self.shard_stats),
            # replica placement visibility: the configured factor, shards
            # bucketed by live holder count, and the shards failover can't
            # yet help (fewer holders than the factor asks for)
            "replication": self._replication_info(),
            # every worker's latency histograms, merged by bucket-vector
            # addition (identical fixed buckets are the precondition, see
            # obs.metrics) — rides peer gossip too, so any controller can
            # answer for the fleet
            "worker_histograms": self._aggregate_worker_histograms(),
            "trace_buffer": len(self.trace_store),
            "slow_queries": len(self.slow_queries),
            # heterogeneous-fleet triage facts (see ops/__init__.py's SIGILL
            # note): this process's jax/jaxlib/libtpu versions + the
            # persistent-compile-cache decision, plus every worker's own
            # versions as gossiped in WRM debug slices
            "runtime": health_runtime["runtime"],
            "compile_cache": health_runtime["compile_cache"],
            "worker_runtime": health_runtime["worker_runtime"],
            "health": self.health.statuses(),
        }
        if include_peers:
            info["others"] = self.others
        return info

    def _replication_info(self):
        """Replica placement visibility, shared by get_info and the debug
        bundle: the configured factor, shards bucketed by live holder
        count, and the shards failover can't yet help (factor 0 = "all
        nodes" mode, where a single-holder shard is still the pager
        signal)."""
        return {
            "replica_factor": self.replica_factor,
            "shards_by_holders": self._holder_counts(),
            "under_replicated": sorted(
                f for f, holders in self.files_map.items()
                if len(holders) < (self.replica_factor or 2)
            )[:64],
        }

    def _capacity_bundle_section(self):
        """The debug bundle's capacity slice: a fresh evaluation (a bundle
        pulled during an incident must carry the live saturation verdict,
        not the last heartbeat's)."""
        self.capacity.evaluate()
        return self.capacity.snapshot()

    def _batch_window_info(self):
        """Micro-batch window state for the debug bundle: the live knobs
        plus what is staged right now (a wedged flush shows up here)."""
        from bqueryd_tpu.plan import bundle as bundlemod

        window_state = {
            "window_ms": bundlemod.batch_window_ms(),
            "batch_max": bundlemod.batch_max(),
            "staged": len(self._pending_window),
        }
        if self._pending_window:
            window_state["opened_age_s"] = round(
                max(time.time() - self._window_opened, 0.0), 3
            )
        return window_state

    def _aggregate_worker_histograms(self):
        # memoized on the snapshot revision: get_info runs once per peer per
        # gossip tick, and redoing the O(workers x histograms) vector merge
        # for each peer when nothing changed is pure waste
        rev, cached = self._worker_hist_cache
        if rev == self._worker_metrics_rev:
            return cached
        from bqueryd_tpu import obs

        merged = obs.merge_histogram_snapshots(self._worker_metrics.values())
        self._worker_hist_cache = (self._worker_metrics_rev, merged)
        return merged

    def rpc_loglevel(self, msg):
        args, _ = msg.get_args_kwargs()
        self._fan_out_to_workers(msg)
        self._fan_out_to_peers(msg)
        import logging

        level = {"debug": logging.DEBUG, "info": logging.INFO}.get(
            args[0] if args else "info", logging.INFO
        )
        bqueryd_tpu.logger.setLevel(level)
        reply = msg.copy()
        reply["payload"] = "OK"
        self.reply_rpc_message(msg.get("token"), reply)

    def _fan_out_to_workers(self, msg):
        for worker_id in list(self.worker_map):
            fan = msg.copy()
            fan.pop("token", None)
            try:
                self.socket.send_multipart(
                    [worker_id.encode(), fan.to_json().encode()]
                )
            except zmq.ZMQError:
                pass

    def _fan_out_to_peers(self, msg):
        if msg.get("_relayed"):
            return  # no gossip storms
        for addr in list(self.others):
            fan = msg.copy()
            fan.pop("token", None)
            fan["_relayed"] = True
            try:
                self.socket.send_multipart([addr.encode(), fan.to_json().encode()])
            except zmq.ZMQError:
                pass

    def rpc_kill(self, msg):
        reply = msg.copy()
        reply["payload"] = "OK"
        self.reply_rpc_message(msg.get("token"), reply)
        self.running = False

    def rpc_killworkers(self, msg):
        kill = Message({"payload": "kill"})
        self._fan_out_to_workers(kill)
        reply = msg.copy()
        reply["payload"] = "OK"
        self.reply_rpc_message(msg.get("token"), reply)

    def rpc_killall(self, msg):
        fan = msg.copy()
        fan.pop("token", None)  # killall itself answers the client, not this
        self.rpc_killworkers(fan)
        if not msg.get("_relayed"):
            for addr in list(self.others):
                fan = RPCMessage({"payload": "killall", "_relayed": True})
                try:
                    self.socket.send_multipart(
                        [addr.encode(), fan.to_json().encode()]
                    )
                except zmq.ZMQError:
                    pass
        reply = msg.copy()
        reply["payload"] = "OK"
        self.reply_rpc_message(msg.get("token"), reply)
        self.running = False

    def rpc_sleep(self, msg):
        args, kwargs = msg.get_args_kwargs()
        if args and isinstance(args[0], (list, tuple)):
            # scatter without gather (reference bqueryd/controller.py:411-424)
            for duration in args[0]:
                scatter = CalcMessage({"payload": "sleep"})
                scatter.set_args_kwargs([duration], {})
                self.worker_out_messages[None].append(scatter)
            reply = msg.copy()
            reply["payload"] = "OK"
            self.reply_rpc_message(msg.get("token"), reply)
            return
        calc = CalcMessage({"payload": "sleep", "token": msg["token"]})
        calc.set_args_kwargs(args, kwargs)
        self.worker_out_messages[None].append(calc)

    def rpc_readfile(self, msg):
        calc = CalcMessage(dict(msg))
        calc["payload"] = "readfile"
        self.worker_out_messages[None].append(calc)

    def rpc_execute_code(self, msg):
        args, kwargs = msg.get_args_kwargs()
        if "function" not in kwargs and not msg.get("function"):
            raise ValueError("execute_code requires function= kwarg")
        wait = kwargs.pop("wait", False)
        calc = CalcMessage(dict(msg))
        calc["payload"] = "execute_code"
        calc.set_args_kwargs(args, kwargs)
        if not wait:
            calc.pop("token", None)
            self.worker_out_messages[None].append(calc)
            reply = msg.copy()
            reply["payload"] = "OK"
            self.reply_rpc_message(msg.get("token"), reply)
        else:
            self.worker_out_messages[None].append(calc)

    def rpc_download(self, msg):
        from bqueryd_tpu.download import setup_download

        setup_download(self, msg)

    # -- streaming append (PR 14) ------------------------------------------
    def rpc_append(self, msg):
        """``rpc.append(filename, dataframe_like)``: route the batch to
        every replica holder of the shard — one dispatch per distinct
        (node, data_dir), so co-located workers sharing one directory
        apply it once — and reply when ALL holders confirmed.  Holder
        stats for the shard are dropped on completion so plan-time pruning
        never acts on pre-append min/max while fresh WRM stats are in
        flight.  Replica divergence contract: a holder that fails leaves
        replicas inconsistent; the error reply names it, and re-issuing
        the append (or re-downloading the shard) is the repair path."""
        args, _kwargs = msg.get_args_kwargs()
        if len(args) != 2:
            raise ValueError("append needs (filename, dataframe_like)")
        filename = args[0]
        holders = sorted(self.files_map.get(filename) or ())
        if not holders:
            raise ValueError(
                f"file {filename!r} is not served by any worker"
            )
        # one target per physical replica directory: workers co-located on
        # one (node, data_dir) serve the SAME bytes — appending through
        # each would duplicate the rows
        targets = {}
        for worker_id in holders:
            info = self.worker_map.get(worker_id) or {}
            group = (info.get("node"), info.get("data_dir") or worker_id)
            targets.setdefault(group, worker_id)
        # rollups covering this shard go stale BEFORE any worker mutates
        # its replica: a stale-but-unchanged entry refreshes back to ready,
        # the reverse order could serve pre-append partials as fresh
        self.serving.note_append(filename)
        deadline = msg.get("deadline")
        seg_key = f"append_{os.urandom(8).hex()}"
        segment = {
            "client_token": msg["token"],
            "filename": filename,
            "created": time.time(),
            "expires": (
                float(deadline) if deadline is not None
                else time.time() + APPEND_TIMEOUT
            ),
            "pending": {},   # dispatch token -> worker_id
            "results": {},   # worker_id -> result dict
            "errors": {},    # worker_id -> error text
        }
        for worker_id in sorted(targets.values()):
            calc = CalcMessage(dict(msg))
            calc["payload"] = "append"
            calc["filename"] = filename
            calc["token"] = f"append_{os.urandom(8).hex()}"
            calc["worker_id"] = worker_id
            segment["pending"][calc["token"]] = worker_id
            self._append_waiters[calc["token"]] = seg_key
            self.worker_out_messages.setdefault(worker_id, []).append(calc)
            self.counters["append_dispatches"] += 1
        self._append_segments[seg_key] = segment
        self.counters["append_requests"] += 1
        self.flight.record(
            "append_fanout", filename=filename,
            holders=len(segment["pending"]),
        )

    def _absorb_append_reply(self, token, msg):
        """One holder's append reply: record it and, when every holder
        answered, reply to the client (all-ok -> per-holder summary;
        any failure -> structured error naming the failed holders)."""
        seg_key = self._append_waiters.pop(token, None)
        segment = self._append_segments.get(seg_key)
        if segment is None:
            return
        worker_id = segment["pending"].pop(token, None)
        if worker_id is None:
            return
        if msg.isa(ErrorMessage):
            text = str(msg.get("payload") or "append failed")
            if "unhandled message payload" in text:
                # pre-PR-14 worker: its base handler rejects the verb with
                # a traceback — rewrite into the structured mixed-version
                # error MIGRATION documents
                text = (
                    "UnsupportedVerb: worker predates streaming append "
                    "(PR 14); upgrade calc workers before using rpc.append"
                )
            else:
                text = (text.strip().splitlines() or ["append failed"])[-1]
            segment["errors"][worker_id] = text[:300]
        else:
            segment["results"][worker_id] = (
                msg.get_from_binary("result") or {}
            )
        if segment["pending"]:
            return
        self._finish_append_segment(seg_key, segment)

    def _finish_append_segment(self, seg_key, segment, timeout=False):
        self._append_segments.pop(seg_key, None)
        filename = segment["filename"]
        # pruning safety: advertised pre-append min/max could prune shards
        # whose NEW rows match — drop the stats until fresh WRMs land
        # (stats-less shards conservatively match everything)
        self.shard_stats.pop(filename, None)
        reply_to = segment["client_token"]
        if segment["errors"] or timeout:
            for token in list(self._append_waiters):
                if self._append_waiters.get(token) == seg_key:
                    self._append_waiters.pop(token, None)
            detail = "; ".join(
                f"{w}: {e}" for w, e in sorted(segment["errors"].items())
            )
            if timeout and segment["pending"]:
                waiting = ", ".join(sorted(segment["pending"].values()))
                detail = (
                    f"{detail}; " if detail else ""
                ) + f"no reply from {waiting}"
            ok_part = (
                f" ({len(segment['results'])} holder(s) DID apply the "
                f"append — replicas may have diverged; re-issue the "
                f"append or re-download the shard)"
                if segment["results"] else ""
            )
            err = ErrorMessage({"token": reply_to})
            err["payload"] = (
                f"append {filename!r} failed: {detail}{ok_part}"
            )
            self.flight.record(
                "append_failed", filename=filename, detail=detail[:200],
            )
            self.reply_rpc_message(reply_to, err)
            return
        reply = Message({"token": reply_to, "payload": "append"})
        reply.add_as_binary(
            "result",
            {
                "filename": filename,
                "holders": segment["results"],
                "appended": max(
                    (r.get("appended", 0) for r in
                     segment["results"].values()),
                    default=0,
                ),
            },
        )
        self.reply_rpc_message(reply_to, reply)

    def _sweep_append_segments(self):
        """Fail append fan-outs whose holders never answered (dead worker,
        lost reply) instead of hanging the client past its RPC timeout."""
        if not self._append_segments:
            return
        now = time.time()
        for seg_key, segment in list(self._append_segments.items()):
            if now > segment["expires"]:
                self._finish_append_segment(seg_key, segment, timeout=True)

    def release_ticket_waiters(self, ticket, error=None):
        segment = self.rpc_segments.pop(f"ticket_{ticket}", None)
        if segment is not None:
            if error:
                reply = ErrorMessage(segment["msg"])
                reply["payload"] = f"download ticket {ticket} failed: {error}"
            else:
                reply = segment["msg"].copy()
                reply["payload"] = "DONE"
            reply["ticket"] = ticket
            self.reply_rpc_message(segment["client_token"], reply)

    # -- groupby planning, admission & fan-out -----------------------------
    def rpc_groupby(self, msg):
        """Admission-controlled, plan-driven groupby.

        The verb no longer fans out verbatim: it compiles to a
        :class:`~bqueryd_tpu.plan.LogicalPlan` (rewrites applied), passes
        admission control (explicit BUSY backpressure instead of unbounded
        inflight growth), and launches via :meth:`_launch_plan`, which
        prunes shards against advertised stats and fuses identical
        concurrent work."""
        from bqueryd_tpu import obs
        from bqueryd_tpu import plan as planmod

        picked_up = msg.pop("_picked_up", None)
        args, kwargs = msg.get_args_kwargs()
        if len(args) != 4:
            raise ValueError(
                "groupby needs (filenames, groupby_cols, agg_list, where_terms)"
            )
        filenames, groupby_cols, agg_list, where_terms = args
        # an op outside the groupby surface fails HERE, as a structured
        # envelope (error_class="UnsupportedOp", like PR-8's
        # DispatchExhausted) — not as a worker traceback relayed three
        # hops later.  The richer operators live behind rpc.query().
        from bqueryd_tpu.models.query import AGG_OPS, normalize_agg_list

        try:
            bad = sorted(
                {
                    str(a[1]) for a in normalize_agg_list(agg_list)
                    if a[1] not in AGG_OPS
                }
            )
        except Exception:
            bad = []  # malformed agg lists fall through to plan compile
        if bad:
            self.reply_rpc_raw(
                msg["token"],
                pickle.dumps(
                    {
                        "ok": False,
                        "error_class": "UnsupportedOp",
                        "error": (
                            f"unsupported aggregation op(s) {bad}; groupby "
                            f"supports {list(AGG_OPS)} — joins, top-k, "
                            f"quantiles and window rollups go through the "
                            f"query verb (rpc.query)"
                        ),
                    },
                    protocol=4,
                ),
            )
            return
        # tracing: adopt the client's TraceContext (mint one for traceless
        # clients); the controller "groupby" span parents every query span
        # and is itself a child of the client's root span
        ctx = obs.TraceContext.from_wire(msg.get_trace())
        if ctx is None:
            ctx = obs.TraceContext.new_root()
        obs_state = self._new_obs_state(ctx, picked_up)
        msg["_obs"] = obs_state
        plan_start = time.time()
        plan_clock = time.perf_counter()
        # dedup, order-preserving (inside plan compilation): duplicates would
        # double-count on the batched path and deadlock the per-shard path
        plan = planmod.plan_groupby(
            filenames, groupby_cols, agg_list, where_terms,
            aggregate=kwargs.get("aggregate", True),
            expand_filter_column=kwargs.get("expand_filter_column"),
        )
        if obs.enabled():
            obs_state["spans"].append(
                obs.make_span(
                    ctx.trace_id, "plan", plan_start,
                    time.perf_counter() - plan_clock,
                    parent_span_id=obs_state["qspan_id"], node=self.address,
                )
            )
        self._admit_plan(msg, plan, kwargs)

    def rpc_query(self, msg):
        """The operator-DAG verb: compiles the ``rpc.query(spec)`` dict
        into a typed :class:`~bqueryd_tpu.plan.dag.OperatorDAG` (broadcast
        hash joins, per-group top-k, mergeable quantile sketches,
        time-window rollups), derives its groupby-shaped logical plan, and
        admits it through the SAME machinery as ``rpc_groupby`` — so
        admission quotas, shard pruning, replica failover, SLO accounting
        and autopsy attribution all apply to the new operators for free.
        Spec validation failures reply a structured envelope
        (``error_class`` "UnsupportedOp" / "InvalidPlan")."""
        from bqueryd_tpu import obs
        from bqueryd_tpu.plan import dag as dagmod

        picked_up = msg.pop("_picked_up", None)
        args, kwargs = msg.get_args_kwargs()
        if len(args) != 1 or not isinstance(args[0], dict):
            raise ValueError("query needs one spec dict argument")
        ctx = obs.TraceContext.from_wire(msg.get_trace())
        if ctx is None:
            ctx = obs.TraceContext.new_root()
        obs_state = self._new_obs_state(ctx, picked_up)
        msg["_obs"] = obs_state
        plan_start = time.time()
        plan_clock = time.perf_counter()
        try:
            dag = dagmod.compile_query(args[0])
            plan, dag_kwargs = dagmod.groupby_equivalent(dag)
        except dagmod.DagValidationError as exc:
            self.reply_rpc_raw(
                msg["token"],
                pickle.dumps(
                    {
                        "ok": False,
                        "error_class": exc.error_class,
                        "error": str(exc),
                    },
                    protocol=4,
                ),
            )
            return
        kwargs = dict(kwargs)
        kwargs.update(dag_kwargs)
        if obs.enabled():
            obs_state["spans"].append(
                obs.make_span(
                    ctx.trace_id, "plan", plan_start,
                    time.perf_counter() - plan_clock,
                    parent_span_id=obs_state["qspan_id"], node=self.address,
                )
            )
        self._admit_plan(msg, plan, kwargs)

    # -- semantic serving wire plumbing (PR 16) ---------------------------
    # All rollup message construction and reply absorption live HERE (not
    # in serve/) so the wire lint audits every key both ways.

    def _dispatch_rollup_build(self, entry, prior=None):
        """Fan one ``rollup`` CalcMessage per shard of a rollup entry to a
        live holder.  A refresh (``prior`` set) ships each shard's previous
        partials plus the chunk-prefix fingerprint they were computed
        against; the worker delta-aggregates only the appended tail when
        the prefix still validates (ops.workingset.growth_since)."""
        spec = entry.spec
        dag_blob = None
        if spec.get("dag_wire") is not None:
            dag_blob = base64.b64encode(
                pickle.dumps(
                    spec["dag_wire"], protocol=messages.PICKLE_PROTOCOL
                )
            ).decode("ascii")
        keys, agg_list, where = spec["args"]
        for fname in entry.filenames:
            holders = self.files_map.get(fname) or set()
            worker_id = next(
                (w for w in sorted(holders) if w in self.worker_map), None
            )
            if worker_id is None:
                self.serving.manager.fail(entry.key, "no-holder")
                self.flight.record(
                    "rollup_build_failed", entry=entry.key,
                    filename=fname, reason="no-holder",
                )
                return
            calc = CalcMessage({
                "payload": "rollup",
                "filename": fname,
                "token": f"rollup_{os.urandom(8).hex()}",
                "worker_id": worker_id,
            })
            calc.set_args_kwargs(
                [fname, keys, agg_list, where], {"aggregate": True}
            )
            if dag_blob is not None:
                calc["dag"] = dag_blob
            pinfo = (prior or {}).get(fname) or {}
            if pinfo.get("data") and pinfo.get("base") is not None:
                # partials bytes ride base64-framed: the calc wire is JSON
                calc.add_as_binary("rollup_prior", pinfo["data"])
                calc.add_as_binary("rollup_base", pinfo["base"])
            self._rollup_waiters[calc["token"]] = (entry.key, fname)
            self.worker_out_messages.setdefault(worker_id, []).append(calc)
        self.flight.record(
            "rollup_dispatch", entry=entry.key,
            shards=len(entry.filenames), refresh=prior is not None,
        )

    def _absorb_rollup_reply(self, token, msg):
        """One shard's rollup build/refresh reply: parse the partials and
        proof metadata into the serving layer.  An error reply (including
        a pre-PR-16 worker's base-handler rejection of the verb) drops the
        whole entry — serving simply stays on the recompute path."""
        key, fname = self._rollup_waiters.pop(token)
        if msg.isa(ErrorMessage):
            text = str(msg.get("payload") or "rollup build failed")
            if "unhandled message payload" in text:
                text = (
                    "UnsupportedVerb: worker predates semantic serving "
                    "(PR 16); rollups stay disabled until calc workers "
                    "are upgraded"
                )
            else:
                text = (text.strip().splitlines() or ["failed"])[-1]
            self.serving.manager.fail(key, text)
            self.flight.record(
                "rollup_build_failed", entry=key, filename=fname,
                reason=text[:200],
            )
            return
        from bqueryd_tpu.models.query import ResultPayload

        data = msg.get("data")
        mode = msg.get("rollup_mode") or "rebuild"
        base = (
            msg.get_from_binary("rollup_base")
            if msg.get("rollup_base") else None
        )
        zones = (
            msg.get_from_binary("rollup_zones")
            if msg.get("rollup_zones") else {}
        )
        try:
            payload = dict(ResultPayload.from_bytes(data))
        except Exception:
            self.serving.manager.fail(key, "undecodable payload")
            return
        groups = (
            len(payload.get("rows", ()))
            if payload.get("kind") == "partials" else 0
        )
        state = self.serving.absorb_build(key, fname, {
            "data": data,
            "payload": payload,
            "base": base,
            "zones": zones,
            "groups": int(groups),
            "mode": mode,
        })
        if mode == "delta":
            self.counters["rollup_refreshes"] += 1
        elif mode == "rebuild":
            self.counters["rollup_builds"] += 1
        if state == "ready":
            self.flight.record(
                "rollup_materialized", entry=key, mode=mode,
            )

    def _reply_served(self, msg, payloads, source, subsumed_from):
        """Answer a groupby-shaped verb straight from the serving layer.
        The envelope mirrors _maybe_complete_segment's (empty timing /
        strategy maps: nothing was dispatched) plus the PR-16 provenance
        pair.  A live admission ticket on this REQ identity is retired
        first — the REQ socket is lockstep, so the abandoned run's reply
        would otherwise mis-pair with this client's next request."""
        token = msg["token"]
        if token in self._ticket_sigs:
            self._cancel_ticket(token)
        self._count_answer(source)
        self.reply_rpc_raw(
            token,
            pickle.dumps(
                {
                    "ok": True,
                    "payloads": payloads,
                    "timings": {},
                    "strategies": {"hints": {}, "effective": {}},
                    "merge_modes": {},
                    "answer_source": source,
                    "subsumed_from": subsumed_from,
                },
                protocol=4,
            ),
        )

    def _count_answer(self, source):
        """Per-source answer provenance counter (every client reply path
        funnels through here exactly once)."""
        self.metrics.counter(
            "bqueryd_tpu_serve_answers_total",
            "groupby answers by provenance source "
            "(recompute|cached|delta|rollup|subsume)",
            labels={"source": source},
        ).inc()

    def _admit_plan(self, msg, plan, kwargs):
        """Shared admission tail of the groupby-shaped verbs (groupby and
        query): unknown-shard check, quota/dedup/supersede handling, BUSY
        backpressure, and the micro-batch staging launch."""
        from bqueryd_tpu import plan as planmod

        unknown = [f for f in plan.filenames if f not in self.files_map]
        if unknown:
            raise ValueError(f"filenames not found on any worker: {unknown}")

        # semantic serving (PR 16): a provable subsumption/rollup hit
        # answers right here — no admission slot, no dispatch, no scan.
        # Misses (and every refusal) fall through bit-identically to the
        # pre-serving pipeline; _reply_served retires any live ticket on
        # this REQ identity first (a timed-out resend), since that run's
        # eventual reply would mis-pair with the client's next request
        if self.serving.try_serve(msg, plan, kwargs):
            return

        # admission: the REQ token is the ticket (one live ticket per
        # lockstep REQ socket); the quota key is the client-declared
        # client_id when present, so one application's many sockets share
        # one quota bucket
        quota_key = msg.get("client_id") or msg["token"]
        # deadline/priority are deliberately NOT part of the resend
        # signature: an application-level retry restamps a fresh absolute
        # deadline, and reading that as a *new* query would cancel and
        # restart the in-flight run on every retry — a livelock for any
        # query longer than the retry interval.  An identical resend joins
        # the in-flight run; that run's (earlier) deadline governs.
        req_sig = (tuple(plan.filenames), plan.signature())
        decision = self.admission.submit(
            ticket_id=msg["token"],
            client=quota_key,
            priority=msg.get("priority", 0),
            deadline=msg.get("deadline"),
            payload=(msg, plan, kwargs),
        )
        if (
            decision == planmod.DUPLICATE
            and self._ticket_sigs.get(msg["token"]) != req_sig
        ):
            # a DIFFERENT query on a live identity: the REQ socket is
            # lockstep, so the client has abandoned the earlier query — its
            # reply would mis-pair with this request.  Retire the abandoned
            # run silently and admit this one in its place.
            self.counters["admission_superseded"] += 1
            self._cancel_ticket(msg["token"])
            decision = self.admission.submit(
                ticket_id=msg["token"],
                client=quota_key,
                priority=msg.get("priority", 0),
                deadline=msg.get("deadline"),
                payload=(msg, plan, kwargs),
            )
        if decision == planmod.BUSY:
            self.counters["admission_busy"] += 1
            self.reply_rpc_raw(
                msg["token"],
                pickle.dumps(
                    {
                        "ok": False,
                        "busy": True,
                        "error": "BUSY: admission queue full or client "
                                 "quota exceeded; retry with backoff",
                    },
                    protocol=4,
                ),
            )
            return
        if decision == planmod.QUEUED:
            self._ticket_sigs[msg["token"]] = req_sig
            self.counters["admission_queued"] += 1
            return  # launched later by _admit_ready
        if decision == planmod.DUPLICATE:
            # a client retrying after its own timeout resent the identical
            # query on a live ticket: the in-flight run will answer this
            # identity; launching a second fan-out would double the work
            # outside the admission bound and queue a stale extra reply
            # for the client's NEXT call
            self.logger.info(
                "duplicate groupby from client %s ignored (already running)",
                msg["token"][:12],
            )
            return
        self._ticket_sigs[msg["token"]] = req_sig
        try:
            self._stage_plan(msg, plan, kwargs)
        except Exception:
            self.admission.release(msg["token"])
            self._ticket_sigs.pop(msg["token"], None)
            raise

    def _cancel_ticket(self, ticket):
        """Silently retire a live ticket whose client has moved on: an
        active run is detached from its work units and finished with no
        reply (replying would mis-pair with the identity's next request);
        a still-queued one is dropped before it ever launches."""
        # a plan still STAGED in the micro-batch window has no segment yet:
        # drop it before the flush can launch it — its reply would queue as
        # a stale extra answer for this identity's NEXT request
        staged = [
            entry for entry in self._pending_window
            if entry[0].get("token") == ticket
        ]
        if staged:
            self._pending_window = [
                entry for entry in self._pending_window
                if entry[0].get("token") != ticket
            ]
            if self.admission.release(ticket):
                self._ticket_sigs.pop(ticket, None)
            return
        parent = next(
            (
                p for p, s in self.rpc_segments.items()
                if s.get("admission_ticket") == ticket
            ),
            None,
        )
        if parent is not None:
            self.abort_parent(parent, "superseded", reply=False)
        elif self.admission.release(ticket):
            self._ticket_sigs.pop(ticket, None)

    def _admit_ready(self):
        """Launch queued plans into freed capacity; expire stale ones."""
        if self._admitting:
            return  # re-entered via a completion inside _launch_plan
        self._admitting = True
        try:
            while True:
                launch, expired = self.admission.pop_ready()
                if not launch and not expired:
                    return
                for payload in expired:
                    msg, _plan, _kwargs = payload
                    self._ticket_sigs.pop(msg["token"], None)
                    self.counters["deadline_expired"] += 1
                    self.reply_rpc_raw(
                        msg["token"],
                        pickle.dumps(
                            {
                                "ok": False,
                                "error": "deadline exceeded while queued "
                                         "for admission",
                            },
                            protocol=4,
                        ),
                    )
                for payload in launch:
                    msg, plan, kwargs = payload
                    try:
                        self._stage_plan(msg, plan, kwargs)
                    except Exception as exc:
                        self.logger.exception("queued plan launch failed")
                        self.admission.release(msg["token"])
                        self._ticket_sigs.pop(msg["token"], None)
                        self.reply_rpc_raw(
                            msg["token"],
                            pickle.dumps(
                                {"ok": False, "error": f"{exc}"},
                                protocol=4,
                            ),
                        )
        finally:
            self._admitting = False

    def _stage_plan(self, msg, plan, kwargs):
        """Launch now (window 0 — bit-identical to the pre-window path) or
        stage into the admission micro-batch window so concurrent
        compatible queries can fuse into one shared-scan dispatch."""
        from bqueryd_tpu.plan import bundle as bundlemod

        from bqueryd_tpu import obs

        window_ms = bundlemod.batch_window_ms()
        if window_ms <= 0:
            self._launch_plan(msg, plan, kwargs)
            return
        if not self._pending_window:
            self._window_opened = time.time()
            # flight ring: staging decisions are what a "why was this query
            # 40 ms slower" timeline needs (hot path — kill-switch gated)
            if obs.enabled():
                self.flight.record("window_open", window_ms=window_ms)
        # the batch_window span (staged -> flush) is carved out of the
        # admission wait in _open_query_segment
        obs_state = msg.get("_obs")
        if isinstance(obs_state, dict):
            obs_state["staged_ts"] = time.time()
        self._pending_window.append((msg, plan, kwargs))
        if len(self._pending_window) >= bundlemod.batch_max():
            self._flush_window(force=True)

    def _window_deadline(self):
        """Absolute time the open micro-batch window closes."""
        from bqueryd_tpu.plan import bundle as bundlemod

        return self._window_opened + bundlemod.batch_window_ms() / 1000.0

    def _flush_window(self, force=False):
        """Close the micro-batch window: group the staged plans by
        compatibility signature, launch each compatible group as ONE
        shared-scan bundle, and everything else individually.  A launch
        failure is replied per member (same contract as ``_admit_ready``)
        and never poisons the other groups."""
        if not self._pending_window:
            return
        if not force and time.time() < self._window_deadline():
            return
        from bqueryd_tpu.plan import bundle as bundlemod

        from bqueryd_tpu import obs

        pending, self._pending_window = self._pending_window, []
        groups = {}
        for staged in pending:
            msg, plan, kwargs = staged
            try:
                keep, pruned = self._prune_shards(plan)
                key = bundlemod.compat_key(plan, keep, kwargs)
            except Exception:
                # one malformed plan must not poison the whole window:
                # group it solo; its own launch path replies the error
                self.logger.exception("window compatibility probe failed")
                # forensic event (never gated): a degrade-to-solo is the
                # anomaly a "why didn't these fuse" timeline must show
                self.flight.record(
                    "window_degrade_solo",
                    token=str(msg.get("token"))[:12],
                )
                keep, pruned, key = list(plan.filenames), [], None
            if key is None:
                # unfusable (raw rows, basket expansion, non-mergeable
                # aggs, batch=False, fully pruned): solo launch
                key = ("solo", id(msg))
            groups.setdefault(key, []).append((msg, plan, kwargs, keep, pruned))
        if obs.enabled():
            self.flight.record(
                "window_flush",
                staged=len(pending),
                groups=len(groups),
                fused=sum(1 for g in groups.values() if len(g) > 1),
                held_ms=round(
                    max(time.time() - self._window_opened, 0.0) * 1000.0, 1
                ),
            )
        for entries in groups.values():
            try:
                if len(entries) == 1:
                    msg, plan, kwargs, keep, pruned = entries[0]
                    self._launch_plan(
                        msg, plan, kwargs, preplanned=(keep, pruned)
                    )
                else:
                    self._launch_bundle(entries)
            except Exception as exc:
                self.logger.exception("window flush launch failed")
                for msg, _plan, _kwargs, _keep, _pruned in entries:
                    self.admission.release(msg["token"])
                    self._ticket_sigs.pop(msg["token"], None)
                    self.reply_rpc_raw(
                        msg["token"],
                        pickle.dumps(
                            {"ok": False, "error": f"{exc}"}, protocol=4
                        ),
                    )

    def _prune_shards(self, plan):
        """Plan-time shard pruning: ``(keep, pruned)`` — a shard whose
        advertised min/max stats exclude the pushed-down predicate
        conjunction is never dispatched."""
        from bqueryd_tpu import plan as planmod

        planner_on = planmod.planner_enabled()
        keep, pruned = [], []
        for f in plan.filenames:
            stats = self.shard_stats.get(f)
            if (
                planner_on
                and plan.scan.pushdown
                and stats is not None
                and not planmod.stats_can_match(stats, plan.scan.pushdown)
            ):
                pruned.append(f)
            else:
                keep.append(f)
        return keep, pruned

    def _open_query_segment(self, msg, plan, pruned):
        """Per-query result segment + observability state (shared by the
        solo launch path and every bundle member — a member keeps its own
        trace, deadline, quota ticket and reply identity).  Pruned shards'
        (provably empty) payload slots are pre-filled so the client-side
        merge contract is unchanged."""
        from bqueryd_tpu import obs

        parent_token = os.urandom(8).hex()
        # observability state: created in rpc_groupby; a traceless caller
        # (tests driving _launch_plan directly) gets a fresh one here
        obs_state = msg.get("_obs")
        if not isinstance(obs_state, dict):
            obs_state = self._new_obs_state(obs.TraceContext.new_root())
        # the admission span covers submit -> launch (~0 for an immediate
        # ADMIT, the queue wait for staged plans); time spent staged in the
        # micro-batch window is carved into its own batch_window span so an
        # autopsy can tell fusion-induced wait from admission backpressure
        if obs.enabled():
            now = time.time()
            staged_ts = obs_state.get("staged_ts")
            admitted_until = (
                min(staged_ts, now) if staged_ts is not None else now
            )
            obs_state["spans"].append(
                obs.make_span(
                    obs_state["trace_id"], "admission",
                    obs_state["submitted_ts"],
                    max(admitted_until - obs_state["submitted_ts"], 0.0),
                    parent_span_id=obs_state["qspan_id"], node=self.address,
                )
            )
            if staged_ts is not None:
                obs_state["spans"].append(
                    obs.make_span(
                        obs_state["trace_id"], "batch_window", staged_ts,
                        max(now - staged_ts, 0.0),
                        parent_span_id=obs_state["qspan_id"],
                        node=self.address,
                    )
                )
        # capacity model: one LAUNCHED query — the shards-per-query
        # denominator counts runs that actually open (shed/expired/
        # superseded offers never reach here)
        self.capacity.observe_launch()
        segment = {
            "client_token": msg["token"],
            "msg": msg,
            "filenames": list(plan.filenames),
            "results": {(f,): b"" for f in pruned},
            "timings": {},
            "created": time.time(),
            # monotonic anchor for the reported wall (an NTP step must not
            # produce a negative or inflated query latency observation)
            "created_clock": time.perf_counter(),
            "admission_ticket": msg["token"],
            "pruned": list(pruned),
            "obs": obs_state,
            "plan_sig": str(plan.signature()),
            # shards dispatched, under the one route a dispatch asks for:
            # the envelope's ``strategies["hints"]`` ({"auto": n}); which
            # kernel ran is the worker's report, in "effective"
            "strategies": {},
            "effective": {},          # shard-group key -> executed route
            "merge": {},              # shard-group key -> merge_mode
        }
        self.rpc_segments[parent_token] = segment
        return parent_token

    def _launch_plan(self, msg, plan, kwargs, preplanned=None):
        # ``preplanned``: the (keep, pruned) the window flush already
        # computed for compat grouping — re-pruning every solo launch would
        # double the plan-time stats_can_match cost on the event loop
        keep, pruned = (
            preplanned if preplanned is not None
            else self._prune_shards(plan)
        )
        self.counters["plan_pruned_shards"] += len(pruned)
        parent_token = self._open_query_segment(msg, plan, pruned)
        if not keep:
            # every shard pruned: answer immediately with empty payloads
            self._maybe_complete_segment(parent_token)
            return
        try:
            self._dispatch_plan(msg, plan, kwargs, parent_token, keep)
        except Exception:
            # a half-launched parent can never complete (its later groups
            # were never queued): leaving it would leak the segment, its
            # work-unit registrations, and worker time on the groups that
            # DID queue — detach them all; the caller replies the error
            self.abort_parent(parent_token, "launch failed", reply=False)
            raise

    def _launch_bundle(self, entries):
        """Launch a compatible micro-batch as shared-scan bundles: one
        CalcMessage per shard group carrying every member's fragment; the
        worker executes one decode/align/upload pass + one mesh program and
        the reply demultiplexes per member (``_demux_bundle``)."""
        from bqueryd_tpu.plan import bundle as bundlemod

        _msg0, plan0, kwargs0, keep, _pruned0 = entries[0]
        member_parents = {}     # member_id -> parent_token
        members = []            # (member_id, plan, deadline)
        opened = []
        try:
            for msg, plan, _kwargs, _keep, pruned in entries:
                self.counters["plan_pruned_shards"] += len(pruned)
                parent_token = self._open_query_segment(msg, plan, pruned)
                opened.append(parent_token)
                member_id = os.urandom(6).hex()
                member_parents[member_id] = parent_token
                members.append((member_id, plan, msg.get("deadline")))
            groupby_cols = list(plan0.groupby.keys)
            agg_list0 = plan0.physical_agg_list()
            parents = [member_parents[m[0]] for m in members]
            # the bundle envelope's deadline is the LAST member's (its
            # expiry implies every member's); per-member deadlines ride the
            # fragment and are enforced per member on the worker
            deadlines = [m[2] for m in members]
            bundle_deadline = (
                max(deadlines)
                if deadlines and all(d is not None for d in deadlines)
                else None
            )
            affinity = kwargs0.get("affinity")
            groups = self._shard_groups(
                keep, groupby_cols, agg_list0, kwargs0
            )
            # one group holding every kept file: its payload is the answer
            sole = len(groups) == 1
            for group in groups:
                target = group if len(group) > 1 else group[0]
                for parent in parents:
                    self._count_dispatched_shards(parent, len(group))
                shard = CalcMessage({"payload": "groupby"})
                if sole:
                    shard["sole_payload"] = True
                # reference-shaped params carry the FIRST member's query so
                # _split_batch re-splitting keeps working; the bundle
                # fragment is authoritative on capable workers (MIGRATION:
                # enable the window only on >=PR-9 fleets)
                shard.set_args_kwargs(
                    [target, groupby_cols, agg_list0,
                     [list(t) for t in plan0.where_terms]],
                    {},
                )
                shard["token"] = os.urandom(8).hex()
                shard["parent_token"] = parents[0]
                shard["filename"] = target
                shard["affinity"] = affinity
                obs_state = (
                    self.rpc_segments.get(parents[0], {}).get("obs") or {}
                )
                if obs_state:
                    shard.set_trace(
                        {
                            "trace_id": obs_state["trace_id"],
                            "span_id": os.urandom(8).hex(),
                            "parent_span_id": obs_state["qspan_id"],
                        }
                    )
                    shard["_dispatch_queued_ts"] = time.time()
                if bundle_deadline is not None:
                    shard["deadline"] = bundle_deadline
                shard.add_as_binary(
                    "bundle",
                    bundlemod.bundle_fragment(
                        plan0, group, members, sole=sole
                    ),
                )
                shard["_bundle_parents"] = dict(member_parents)
                self._register_work(shard, parents)
                self.counters["plan_bundles"] += 1
                self.counters["plan_bundled_queries"] += len(members)
                # every member beyond the first shares a dispatch it would
                # otherwise have paid for itself — the same meaning the
                # identical-work dedup counter always had
                self.counters["plan_shared_dispatches"] += len(members) - 1
                self.worker_out_messages.setdefault(affinity, []).append(
                    shard
                )
        except Exception:
            for parent in opened:
                self.abort_parent(parent, "bundle launch failed", reply=False)
            raise

    def _count_dispatched_shards(self, parent_token, n):
        segment = self.rpc_segments.get(parent_token)
        if segment is not None:
            hints = segment["strategies"]
            hints["auto"] = hints.get("auto", 0) + n

    def _dispatch_plan(self, msg, plan, kwargs, parent_token, keep):
        from bqueryd_tpu import plan as planmod

        affinity = kwargs.get("affinity")
        # operator-DAG dispatch (rpc.query): the wire DAG rides every
        # CalcMessage under the `dag` binary key
        dag_wire = kwargs.get("dag")
        dag_blob = None
        if dag_wire is not None:
            # encode ONCE: the wire DAG carries the whole broadcast
            # dimension table, and re-pickling it per shard group would
            # put O(groups x table_bytes) on the dispatch hot path
            dag_blob = base64.b64encode(
                pickle.dumps(dag_wire, protocol=messages.PICKLE_PROTOCOL)
            ).decode("ascii")
        groupby_cols = list(plan.groupby.keys)
        agg_list = plan.physical_agg_list()
        where_terms = plan.where_terms
        groups = self._shard_groups(keep, groupby_cols, agg_list, kwargs)
        # one dispatch group holding every kept file produces exactly one
        # payload with no merge downstream: the worker may finalize
        # representation-heavy aggregations (count_distinct) on device
        # instead of shipping mergeable sets.  Two groups never: their
        # counts would be added, a value held by both counted twice
        sole = len(groups) == 1 and plan.aggregate_rows
        plan_sig = plan.signature()  # group-invariant: computed once
        for group in groups:
            target = group if len(group) > 1 else group[0]
            self._count_dispatched_shards(parent_token, len(group))
            # multi-query batching: identical pending work is joined, not
            # re-dispatched.  The deadline is part of the identity: fusing
            # across deadlines would let one client's budget expire (or
            # never enforce) another client's work.  So is affinity: fusing
            # across pins would silently run a pinned query elsewhere
            work_key = (
                tuple(group), plan_sig, sole, msg.get("deadline"), affinity,
            )
            existing = self._work_index.get(work_key)
            if existing is not None and existing in self._work_subscribers:
                self._work_subscribers[existing].append(parent_token)
                self.counters["plan_shared_dispatches"] += 1
                continue

            shard = CalcMessage({"payload": "groupby"})
            if sole:
                shard["sole_payload"] = True
            shard.set_args_kwargs(
                [target, groupby_cols, agg_list, where_terms],
                {
                    k: v
                    for k, v in kwargs.items()
                    if k in ("aggregate", "expand_filter_column")
                },
            )
            shard["token"] = os.urandom(8).hex()
            shard["parent_token"] = parent_token
            shard["filename"] = target
            shard["affinity"] = affinity
            # per-dispatch trace hop: the worker parents its "calc" span to
            # this dispatch span id; the span itself is recorded at send
            # time (queue wait + routing), see _send_to_worker
            obs_state = (
                self.rpc_segments.get(parent_token, {}).get("obs") or {}
            )
            if obs_state:
                shard.set_trace(
                    {
                        "trace_id": obs_state["trace_id"],
                        "span_id": os.urandom(8).hex(),
                        "parent_span_id": obs_state["qspan_id"],
                    }
                )
                shard["_dispatch_queued_ts"] = time.time()
            if msg.get("deadline") is not None:
                shard["deadline"] = msg["deadline"]
            shard.add_as_binary(
                "plan",
                planmod.fragment_for(plan, group, sole=sole),
            )
            if dag_blob is not None:
                # capable workers execute the DAG; pre-DAG workers fall
                # back to the positional params, whose extended op strings
                # they reject — process_worker_result rewrites that
                # rejection into the structured mixed-version error
                # (MIGRATION "PR 13")
                shard["dag"] = dag_blob
            self._register_work(shard, [parent_token], work_key=work_key)
            self.worker_out_messages.setdefault(affinity, []).append(shard)

    def _shard_groups(self, filenames, groupby_cols, agg_list, kwargs):
        """Partition the requested shard files into dispatch groups.

        Shards sharing an identical advertising-worker set are batched into
        ONE CalcMessage so the worker merges them on its device mesh with a
        psum instead of the controller collecting N serialized partials —
        the core TPU redesign of the reference's per-shard fan-out
        (reference bqueryd/controller.py:494-506).  Batching applies to
        device-mergeable part kinds: the psum-mergeable classic ops, plus —
        for DAG dispatches (``kwargs["dag"]``, whose ``batch`` flag
        ``plan.dag.groupby_equivalent`` already gates on the part kinds and
        the ``BQUERYD_TPU_DAG_BATCH`` kill switch) — the extended top-k /
        quantile-sketch ops the worker's mesh fast path merges on device.
        A query whose other ops are ``count_distinct`` is one group only
        where every file it keeps has one placement, so that one payload is
        the whole answer (``_dispatch_plan``'s ``sole``); where the
        placement splits its files, it keeps per-shard dispatch and ships
        value sets, as raw-rows and ``sorted_count_distinct`` queries
        always do (a run is counted per file).  ``batch=False`` forces the
        reference's one-message-per-shard behaviour (finer retry
        granularity).
        """
        from bqueryd_tpu.models.query import MERGEABLE_OPS, GroupByQuery
        from bqueryd_tpu.plan.dag import is_extended_op

        probe = GroupByQuery(
            groupby_cols, agg_list, aggregate=kwargs.get("aggregate", True)
        )
        from bqueryd_tpu.parallel import devicemerge

        dag_riding = kwargs.get("dag") is not None
        per_shard = [[f] for f in filenames]
        if not (
            kwargs.get("batch", True)
            and probe.aggregate
            # BQUERYD_TPU_DEVICE_MERGE=0: the merge stays host-side end to
            # end — per-shard dispatch so every shard's partial table rides
            # the wire and merges via hostmerge (the measurable host-gather
            # baseline the device-resident merge is judged against)
            and devicemerge.device_merge_enabled()
        ):
            return per_shard
        groups = {}
        for f in filenames:
            placement = tuple(sorted(self.files_map.get(f, ())))
            groups.setdefault(placement, []).append(f)
        mergeable = all(
            op in MERGEABLE_OPS or (dag_riding and is_extended_op(op))
            for op in probe.ops
        )
        whole = len(groups) == 1 and not dag_riding and all(
            op in MERGEABLE_OPS or op == "count_distinct" for op in probe.ops
        )
        if not (mergeable or whole):
            return per_shard
        return list(groups.values())
