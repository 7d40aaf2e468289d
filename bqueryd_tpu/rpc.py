"""RPC: the client proxy object.

Same call surface as the reference client (reference bqueryd/rpc.py:29-207):
attribute access becomes a remote call on a randomly chosen live controller
(``rpc.groupby(...)``, ``rpc.info()``, ...), with ping-verified connection,
reconnect-and-retry, and ``last_call_duration`` timing.

The groupby result path is redesigned: instead of a tar-of-tars that the
client untars and re-aggregates through bcolz (reference bqueryd/rpc.py:135-175),
the controller returns one pickled list of per-shard partial payloads (already
psum-merged across each worker's device mesh) and the client does a value-keyed
NumPy merge + finalize (:mod:`bqueryd_tpu.parallel.hostmerge`).  Mean is a
correct weighted mean; ``legacy_merge=True`` restores the reference's
sum-of-shard-means quirk (reference bqueryd/rpc.py:171) for byte-compatible
comparisons.

An ``RPC`` instance wraps one zmq REQ socket and is therefore
single-thread lockstep, exactly like the reference client: concurrent
callers must each hold their own instance (they are cheap — one ping).
"""

import collections
import logging
import os
import pickle
import random
import threading
import time

import zmq

import bqueryd_tpu
from bqueryd_tpu import backoff, chaos
from bqueryd_tpu.coordination import coordination_store
from bqueryd_tpu.messages import ErrorMessage, RPCMessage, msg_factory


#: the verbs whose calls the controller keeps a timeline of
QUERY_VERBS = ("groupby", "query")
#: client spans of this process's latest query calls, by trace id: what
#: ``RPC.trace`` merges into the controller's timeline and ``RPC.autopsy``
#: reads its client segment from.  Process-wide, since each thread holds
#: an ``RPC`` of its own; as many as the controller's ring keeps by default
CALL_SPANS_KEPT = 256
_call_spans = collections.OrderedDict()
_call_spans_lock = threading.Lock()


def _keep_call_spans(trace_id, spans):
    with _call_spans_lock:
        _call_spans[trace_id] = spans
        while len(_call_spans) > CALL_SPANS_KEPT:
            _call_spans.popitem(last=False)


def call_spans(trace_id):
    """The client spans this process recorded for one query call (a
    list, empty when none)."""
    with _call_spans_lock:
        return list(_call_spans.get(trace_id, ()))


class RPCError(Exception):
    pass


class RPCBusyError(RPCError):
    """The controller's admission queue rejected the query (backpressure).
    Deliberate and immediate — retry with backoff or shed load upstream."""


class RPC:
    #: capped exponential backoff between retry attempts (timeouts, zmq
    #: errors, BUSY backpressure): base * 2^attempt, capped, stretched by a
    #: deterministic per-socket jitter so a thundering herd of retrying
    #: clients de-synchronizes the same way on every run (shared formula:
    #: bqueryd_tpu.backoff — the controller's failover pacing uses it too)
    BACKOFF_BASE_S = backoff.BACKOFF_BASE_S
    BACKOFF_CAP_S = backoff.BACKOFF_CAP_S

    def __init__(
        self,
        address=None,
        timeout=120,
        coordination_url=None,
        redis_url=None,
        loglevel=logging.INFO,
        retries=3,
        legacy_merge=False,
        client_id=None,
        slo_class=None,
    ):
        bqueryd_tpu.configure_logging(loglevel)
        self.logger = bqueryd_tpu.logger.getChild("rpc")
        chaos.maybe_arm_from_env()
        self.timeout = timeout
        self.retries = retries
        self.legacy_merge = legacy_merge
        # admission quota bucket: sockets sharing a client_id share the
        # controller's per-client quota (BQUERYD_TPU_ADMIT_CLIENT_QUOTA);
        # unset, each socket identity is its own bucket
        self.client_id = client_id
        # SLO class declaration: rides every request envelope (`slo_class`
        # key) so the controller buckets this client's deadline margins and
        # burn rates under the right class (obs.slo; unknown -> "default")
        self.slo_class = slo_class
        self.last_call_duration = None
        #: attempts the most recent call consumed (1 = first try answered;
        #: >1 means timeouts/reconnects/BUSY backoff were absorbed) — the
        #: companion to last_call_duration when diagnosing tail latency
        self.last_call_attempts = None
        #: trace id of the most recent call — feed it to ``rpc.trace(...)``
        #: to pull the controller's per-phase waterfall for that query
        self.last_trace_id = None
        #: per-shard-group phase timings / strategy report of the most
        #: recent groupby reply ({"hints": ..., "effective": ...} for the
        #: latter — shards dispatched ({"auto": n}) and the kernel route
        #: each shard group's worker took)
        self.last_call_timings = None
        self.last_call_strategies = None
        #: per-shard-group merge modes of the most recent groupby reply
        #: ("device" = ICI-mesh collective merge, "host" = hostmerge
        #: fallback, "none" = single payload) — how the answer was merged
        self.last_call_merge_modes = None
        #: programs the workers compiled for the most recent groupby (the
        #: calc replies' ``compiled`` mark, summed); 0 = steady state
        self.last_call_compiled = None
        #: answer provenance of the most recent groupby reply (PR 16):
        #: "recompute" | "cached" | "delta" | "rollup" | "subsume" — and,
        #: for subsumption serves, the materialized view that proved it.
        #: None against a pre-PR-16 controller.
        self.last_call_answer_source = None
        self.last_call_subsumed_from = None
        #: the most recent call's own spans, always on: ``client_encode``
        #: (the request's build -> its send) and ``client_decode`` (the
        #: reply's receipt -> the finished result), wall start + duration
        #: like every span; a query call's are also kept by trace id
        #: (``call_spans``) for ``trace()`` and ``autopsy()``
        self.last_call_spans = []
        self.identity = os.urandom(8).hex()
        self.store = coordination_store(
            coordination_url or redis_url or bqueryd_tpu.DEFAULT_COORDINATION_URL
        )
        self.context = zmq.Context.instance()
        self.socket = None
        self.address = None
        self.connect(address)

    # -- connection --------------------------------------------------------
    def connect(self, address=None):
        if address:
            candidates = [address]
        else:
            candidates = list(self.store.smembers(bqueryd_tpu.REDIS_SET_KEY))
            random.shuffle(candidates)
        if not candidates:
            raise RPCError("No controllers found in the coordination store")
        for candidate in candidates:
            if self._try_connect(candidate):
                self.address = candidate
                self.logger.debug("connected to controller %s", candidate)
                return
        raise RPCError(f"No controller answered a ping among {candidates}")

    def _try_connect(self, address, ping_timeout=2000):
        self._close_socket()
        self.socket = self.context.socket(zmq.REQ)
        self.socket.identity = self.identity.encode()
        self.socket.setsockopt(zmq.LINGER, 0)
        self.socket.connect(address)
        ping = RPCMessage({"payload": "ping"})
        ping.set_args_kwargs([], {})
        self.socket.send(ping.to_json().encode())
        if self.socket.poll(ping_timeout, zmq.POLLIN):
            reply = msg_factory(self.socket.recv())
            return reply.get("payload") == "pong"
        self._close_socket()
        return False

    def _close_socket(self):
        if self.socket is not None:
            self.socket.close()
            self.socket = None

    # -- proxy -------------------------------------------------------------
    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)

        def remote_call(*args, **kwargs):
            return self._rpc(name, args, kwargs)

        remote_call.__name__ = name
        return remote_call

    def _rpc(self, name, args, kwargs):
        # perf_counter, not time.time(): last_call_duration measures this
        # process's elapsed time, and an NTP step mid-call used to make it
        # negative (the reference's quirk, reference bqueryd/rpc.py:128-129)
        started = time.perf_counter()
        started_ts = time.time()
        if name == "groupby" and self.legacy_merge:
            # the sum-of-shard-means quirk needs per-shard payloads: disable
            # the controller's batched (pre-merged) shard-group dispatch
            kwargs.setdefault("batch", False)
        # serving-layer kwargs ride the ENVELOPE, not the call params: the
        # controller reads them before any plan compilation, and the worker
        # must never see them as query arguments
        deadline = kwargs.pop("deadline", None)
        priority = kwargs.pop("priority", None)
        msg = RPCMessage({"payload": name})
        if deadline is not None:
            msg.set_deadline(seconds=float(deadline))
        if priority is not None:
            msg["priority"] = priority
        if self.client_id is not None:
            msg["client_id"] = self.client_id
        if self.slo_class is not None:
            msg["slo_class"] = self.slo_class
        # end-to-end tracing: every call mints a root TraceContext; the
        # controller parents its query spans to it and keeps the assembled
        # timeline retrievable via rpc.trace(rpc.last_trace_id)
        from bqueryd_tpu.obs.trace import TraceContext, make_span

        ctx = TraceContext.new_root()
        msg.set_trace(ctx)
        self.last_trace_id = ctx.trace_id
        msg.set_args_kwargs(list(args), kwargs)
        wire = msg.to_json().encode()
        spans = self.last_call_spans = []
        last_error = None
        for attempt in range(1, self.retries + 1):
            self.last_call_attempts = attempt
            try:
                if self.socket is None:
                    self.connect()
                # chaos site rpc.call: "timeout" discards the reply window
                # (the retry/backoff path must recover), "disconnect"
                # forces a reconnect storm, "delay" stretches the call
                fault = chaos.fire(
                    "rpc.call", verb=name, attempt=attempt,
                ) if chaos.enabled() else None
                if fault is not None and fault.action == "disconnect":
                    self._close_socket()
                    raise zmq.ZMQError(zmq.ENOTCONN, "chaos: disconnected")
                if not spans:   # up to the first send: the send is the wire's
                    spans.append(make_span(
                        ctx.trace_id, "client_encode", started_ts,
                        time.perf_counter() - started,
                        parent_span_id=ctx.span_id,
                    ))
                self.socket.send(wire)
                timed_out = not self.socket.poll(
                    int(self.timeout * 1000), zmq.POLLIN
                )
                if fault is not None and fault.action == "timeout":
                    timed_out = True  # pretend the reply never arrived
                if not timed_out:
                    reply = self.socket.recv()
                    received_ts, received = time.time(), time.perf_counter()
                    try:
                        result = self._parse_reply(name, reply)
                    except RPCBusyError:
                        # deliberate admission backpressure: retry with
                        # capped exponential backoff inside the attempt
                        # budget (the REQ send/recv cycle completed, so no
                        # reconnect is needed; an identical resend joins
                        # the original run if it got admitted meanwhile)
                        if attempt >= self.retries:
                            raise
                        last_error = "BUSY backpressure"
                        self.logger.info(
                            "rpc %s attempt %d got BUSY, backing off",
                            name, attempt,
                        )
                        time.sleep(self._backoff_delay(attempt))
                        continue
                    spans.append(make_span(
                        ctx.trace_id, "client_decode", received_ts,
                        time.perf_counter() - received,
                        parent_span_id=ctx.span_id,
                    ))
                    if name in QUERY_VERBS:
                        _keep_call_spans(ctx.trace_id, spans)
                    self.last_call_duration = time.perf_counter() - started
                    return result
                last_error = f"timeout after {self.timeout}s"
            except zmq.ZMQError as exc:
                last_error = str(exc)
            if attempt >= self.retries:
                # the REQ socket is mid send/recv cycle (send done, reply
                # never read) — drop it so the NEXT call reconnects cleanly
                # instead of hitting EFSM on a poisoned socket
                self._close_socket()
                break
            self.logger.warning(
                "rpc %s attempt %d failed (%s), backing off + reconnecting",
                name, attempt, last_error,
            )
            time.sleep(self._backoff_delay(attempt))
            try:
                self.connect()
            except RPCError as exc:
                last_error = str(exc)
        self.last_call_duration = time.perf_counter() - started
        raise RPCError(
            f"rpc {name} failed after {self.last_call_attempts} attempts: "
            f"{last_error}"
        )

    def _backoff_delay(self, attempt):
        """Capped exponential backoff with deterministic jitter: base *
        2^(attempt-1) up to the cap, stretched by up to 25% keyed on this
        socket's identity + attempt — stable across re-runs (chaos scenarios
        replay bit-for-bit), distinct across clients (no thundering herd)."""
        return backoff.backoff_delay(
            attempt - 1,
            f"{self.identity}:{attempt}",
            base=self.BACKOFF_BASE_S,
            cap=self.BACKOFF_CAP_S,
        )

    def _parse_reply(self, name, reply):
        if name in ("groupby", "query"):
            # both groupby-shaped verbs reply the same pickled result
            # envelope (per-shard payloads + timings); the payload ops are
            # self-describing, so extended operators (topk/quantile)
            # finalize through the same merge path
            return self._parse_groupby_reply(reply)
        msg = msg_factory(reply)
        if isinstance(msg, ErrorMessage):
            raise RPCError(msg.get("payload"))
        if "result" in msg:
            return msg.get_from_binary("result")
        return msg.get("payload")

    def _parse_groupby_reply(self, reply):
        from bqueryd_tpu.models.query import ResultPayload
        from bqueryd_tpu.parallel import hostmerge

        # error replies come back as JSON messages; results as raw pickle
        if reply[:1] == b"{":
            msg = msg_factory(reply)
            raise RPCError(msg.get("payload"))
        envelope = pickle.loads(reply)
        if not envelope.get("ok"):
            if envelope.get("busy"):
                raise RPCBusyError(envelope.get("error"))
            # structured failure envelope (messages.py result schema): the
            # error class + per-attempt worker/fault history replace the
            # blind client timeout the exhaustion path used to produce
            error_class = envelope.get("error_class")
            attempts = envelope.get("attempts") or []
            text = str(envelope.get("error"))
            if error_class:
                trail = "; ".join(
                    f"{a.get('worker')}: {a.get('reason')}"
                    for a in attempts if isinstance(a, dict)
                )
                text = f"{error_class}: {text}"
                if trail:
                    text = f"{text} [attempts: {trail}]"
            err = RPCError(text)
            err.error_class = error_class
            err.attempts = attempts
            raise err
        payloads = [ResultPayload.from_bytes(b) for b in envelope["payloads"]]
        self.last_call_timings = envelope.get("timings")
        self.last_call_strategies = envelope.get("strategies")
        self.last_call_merge_modes = envelope.get("merge_modes")
        self.last_call_compiled = envelope.get("compiled", 0)
        self.last_call_answer_source = envelope.get("answer_source")
        self.last_call_subsumed_from = envelope.get("subsumed_from")
        if self.legacy_merge:
            result = self._legacy_merge_frames(payloads)
        else:
            merged = hostmerge.merge_payloads(payloads)
            result = hostmerge.payload_to_dataframe(merged)
        return result

    def _legacy_merge_frames(self, payloads):
        """Reference-quirk mode: finalize each shard separately, then re-merge
        every measure with 'sum' — reproducing sum-of-shard-means for mean
        (reference bqueryd/rpc.py:159-173)."""
        import pandas as pd

        from bqueryd_tpu.parallel import hostmerge

        frames = []
        key_cols = None
        for payload in payloads:
            if payload.get("kind") == "empty":
                continue
            key_cols = payload.get("key_cols", key_cols)
            frames.append(
                hostmerge.payload_to_dataframe(hostmerge.merge_payloads([payload]))
            )
        if not frames:
            return pd.DataFrame()
        stacked = pd.concat(frames, ignore_index=True)
        if key_cols is None:
            return stacked
        return stacked.groupby(key_cols, sort=True).sum().reset_index()

    # -- operator-DAG queries ----------------------------------------------
    def query(self, spec, deadline=None, priority=None):
        """The operator-DAG verb: richer shapes than ``groupby`` — broadcast
        hash joins of small dimension tables, per-group top-k, approximate
        quantiles (mergeable sketches), and time-window rollups — compiled
        controller-side into a typed operator DAG
        (:mod:`bqueryd_tpu.plan.dag`; spec shape documented there and in
        the README's "Relational operators" section).  Returns a pandas
        DataFrame like ``groupby``: top-k columns hold per-group
        best-first value arrays, quantile columns hold the sketch
        estimates (error bound <= the op's alpha).  The spec is validated
        client-side first so malformed queries fail without a round trip;
        the controller re-validates authoritatively."""
        from bqueryd_tpu.plan import dag as dagmod

        dagmod.compile_query(spec)
        kwargs = {}
        if deadline is not None:
            kwargs["deadline"] = deadline
        if priority is not None:
            kwargs["priority"] = priority
        return self._rpc("query", (spec,), kwargs)

    # -- streaming ingest --------------------------------------------------
    def append(self, filename, data, deadline=None):
        """Append a dataframe-like batch of rows to a served shard: the
        controller routes the frame to every replica holder of
        ``filename`` (one per distinct (node, data_dir)) and replies once
        ALL holders confirmed.  Returns ``{"filename", "appended",
        "holders": {worker: {...}}}``.  Worker-side, the committed row
        count flips atomically after the chunk data lands, so queries
        racing the append see either the pre- or post-append snapshot —
        never a torn one; repeat queries after the append are served by
        delta maintenance (only the appended chunks re-aggregate).  A
        holder failure raises with the failed workers named — replicas
        may then have diverged; re-issue the append or re-download."""
        kwargs = {}
        if deadline is not None:
            kwargs["deadline"] = deadline
        return self._rpc("append", (filename, data), kwargs)

    # -- query trace and autopsy -------------------------------------------
    def trace(self, trace_id=None):
        """The controller's assembled timeline of one query (None once it
        fell out of the ring), with the spans this process recorded for the
        call merged in (``client_encode``, ``client_decode``: the client's
        part, which the controller cannot see)."""
        timeline = self._rpc("trace", (trace_id,), {})
        local = call_spans(trace_id)
        if isinstance(timeline, dict) and local:
            timeline["spans"] = sorted(
                list(timeline.get("spans") or []) + local,
                key=lambda s: s.get("start_ts", 0.0),
            )
        return timeline

    def autopsy(self, trace_id=None):
        """The attributed critical-path breakdown for one query (default:
        the controller's newest trace): named non-overlapping segments,
        coverage accounting, per-attempt dispatch history.  When this
        process made the call (the usual groupby path), its
        ``client_decode`` span — invisible to the controller, which seals
        the trace before the client unpickles — is folded in as the
        ``client_deserialize`` segment and the coverage recomputed over the
        extended wall."""
        record = self._rpc("autopsy", (trace_id,) if trace_id else (), {})
        if not isinstance(record, dict):
            return record
        merge_s = sum(
            s["duration_s"] for s in call_spans(record.get("trace_id"))
            if s["name"] == "client_decode"
        )
        if merge_s:
            segments = record.setdefault("segments", {})
            segments["client_deserialize"] = round(merge_s, 6)
            wall = float(record.get("wall_s") or 0.0) + merge_s
            covered = float(record.get("covered_s") or 0.0) + merge_s
            record["wall_s"] = round(wall, 6)
            record["covered_s"] = round(covered, 6)
            if wall > 0:
                record["coverage"] = round(covered / wall, 4)
        return record

    # -- fleet capacity ----------------------------------------------------
    def capacity(self):
        """The controller's fleet capacity model (``obs.capacity``): per
        worker μ (service rate), λ (dispatch rate), ρ and saturation state
        (ok/warm/saturated/overloaded, hysteresis applied); fleet
        utilization, the predicted saturation knee / headroom QPS, the
        M/G/1-predicted vs measured queue delay and their drift; the
        per-shard dispatch heat map; and the shadow advisor's current
        ``scale_up``/``scale_down``/``rebalance`` recommendations with
        their evidence.  Advisory only — the controller never acts on
        them.  (An explicit method rather than the ``__getattr__`` proxy
        purely for discoverability; the verb is plain ``capacity``.)"""
        return self._rpc("capacity", (), {})

    # -- download helpers (client-local, straight to the store) ------------
    def get_download_data(self):
        """Raw ticket hashes keyed by their full store key — the reference's
        exact shape (reference bqueryd/rpc.py:181-188), for tooling written
        against it."""
        data = {}
        for key in self.store.keys(bqueryd_tpu.REDIS_TICKET_KEY_PREFIX + "*"):
            data[key] = self.store.hgetall(key)
        return data

    def downloads(self):
        """Summaries of in-flight download tickets as ``(ticket,
        "done/total")`` tuples — the reference's output shape (reference
        bqueryd/rpc.py:190-199).  Per-slot detail: ``download_progress()``."""
        out = []
        prefix = bqueryd_tpu.REDIS_TICKET_KEY_PREFIX
        for key, entries in self.get_download_data().items():
            done = sum(1 for v in entries.values() if v.endswith("_DONE"))
            out.append((key[len(prefix):], f"{done}/{len(entries)}"))
        return out

    def download_progress(self):
        """Per-slot download states: ``[(ticket, {(node, fileurl): state})]``
        — richer than the reference's done/total summary; ERROR states are
        visible here."""
        out = []
        prefix = bqueryd_tpu.REDIS_TICKET_KEY_PREFIX
        for key, entries in self.get_download_data().items():
            progress = {}
            for slot, value in entries.items():
                node, _, fileurl = slot.partition("_")
                _, _, state = value.rpartition("_")
                progress[(node, fileurl)] = state
            out.append((key[len(prefix):], progress))
        return out

    def delete_download(self, ticket):
        """Cancel a ticket by deleting its slots; downloaders abort mid-flight
        on the next progress update (reference bqueryd/worker.py:418-428)."""
        key = bqueryd_tpu.REDIS_TICKET_KEY_PREFIX + ticket
        existed = bool(self.store.hgetall(key))
        self.store.delete(key)
        return existed
