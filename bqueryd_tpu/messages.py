"""Wire protocol: JSON dict envelopes with base64-pickled binary fields.

Format-compatible with the reference protocol (reference bqueryd/messages.py:1-102):
a message is a plain dict serialized to JSON with at least ``msg_type``,
``payload``, ``version`` and ``created`` keys; call parameters travel as a
pickled ``{'args': ..., 'kwargs': ...}`` dict, base64-encoded, under the
``params`` key.  ``msg_factory`` maps ``msg_type`` strings to classes using the
same type names (``calc``, ``rpc``, ``error``, ``worker_register``, ``busy``,
``done``, ``ticketdone``, ``stop``).

Deliberate fixes over the reference (flagged in SURVEY.md §7.4):

* parse failures raise :class:`MalformedMessage` instead of the silent
  ``msg is None`` dead statement (reference bqueryd/messages.py:11);
  callers that want the lenient behaviour use ``msg_factory(..., strict=False)``.
* binary values are pickled with an explicit protocol so Python 3 nodes of
  mixed minor versions interoperate.

Security note: like the reference (reference README.md:129) pickled payloads
assume a trusted network.  ``Message.get_from_binary`` is the single choke
point, so a restricted unpickler can be installed here later.

Observability envelope schema (all keys optional, all JSON-safe — nodes
without them interoperate):

* ``trace`` — the distributed-tracing context injected by the RPC client and
  propagated on every hop: ``{"trace_id": hex, "span_id": hex,
  "parent_span_id": hex?}`` (:class:`bqueryd_tpu.obs.trace.TraceContext`).
  ``span_id`` is the SENDER's active span; the receiver parents its root
  span to it.  ``Message.set_trace``/``get_trace`` are the accessors.
* ``spans`` — on worker calc REPLIES: the worker's span list (see
  ``obs.trace.make_span`` for the per-span fields) which the controller
  folds into the query's ``rpc.trace(trace_id)`` timeline.
* ``phase_timings`` — on worker calc replies: ``{phase_name: seconds, ...,
  "_total": seconds}``.  Phase keys are the worker's own phase names
  (``open``, ``align``, ``mask``, ``layout``, ``aggregate``, ``collect``,
  ``serialize``, ``hostmerge``, ...); the synthetic whole-call wall lives
  under the underscore-namespaced ``_total`` key precisely so it can never
  collide with (and silently overwrite) a real phase named ``total``.
  A worker running with ``BQUERYD_TPU_PROFILE=1`` adds ONE key,
  ``post_prev``: the seconds of reply send + Done + gc + RSS check that
  followed the unit(s) replied to since its last calc reply.  It is no
  phase of this reply's wall: whatever sums the phases against ``_total``
  skips it, as it skips ``_total``.
* on WorkerRegisterMessages (all optional; controllers ignore what they
  don't know): ``backend_wedged`` (bool, the device-health latch),
  ``work_errors`` (cumulative error-counter total — the controller's
  health scorer derives windowed error rates from its deltas),
  ``metrics`` (histogram snapshot, see obs.metrics), and ``debug`` — the
  node's debug-bundle slice (flight-ring tail, compile registry, device
  health, runtime versions; see obs.flightrec) absorbed controller-side
  so ``rpc.debug_bundle()`` can speak for dead peers.
"""

import base64
import json
import pickle
import time

PICKLE_PROTOCOL = 4

# -- wire schema --------------------------------------------------------------
# The single declared truth of every envelope key that crosses (or rides) the
# wire, diffed by ``bqueryd_tpu.analysis.wire`` against the key literals the
# wire modules (controller.py / worker.py / rpc.py) actually read and write:
# a key added on one side without the other is a LINT failure, not a silent
# ``None`` three hops later.  Adding a key to the protocol means adding it
# here, with help text, in the same commit.

#: JSON envelope keys (Message dicts).  Keys prefixed ``_`` are controller-
#: internal riders: they travel inside the process (and harmlessly on the
#: wire) but no peer may ever rely on them.
ENVELOPE_SCHEMA = {
    # base Message fields (set by the constructor / accessors below)
    "msg_type": "message class discriminator (msg_factory dispatch)",
    "payload": "verb name on requests; result/error text on replies",
    "version": "protocol version, currently 1",
    "created": "sender timestamp (preserved across parse/copy)",
    "params": "base64-pickled {'args', 'kwargs'} call parameters",
    "deadline": "absolute unix deadline, propagated client->worker",
    "trace": "distributed-tracing context {trace_id, span_id, ...}",
    # client -> controller
    "token": "request identity: client socket token / shard work token",
    "priority": "admission queue priority (ascending)",
    "client_id": "admission quota bucket for RPC(client_id=...)",
    "slo_class": "client-declared SLO class (RPC(slo_class=...)): selects "
                 "the deadline-margin histogram / burn-rate bucket the "
                 "query's outcome lands in (obs.slo; unknown classes fold "
                 "into 'default')",
    "function": "remote-execution verb: pickled callable name",
    "needs_local": "route only to workers holding the file locally",
    # controller -> worker shard dispatch
    "parent_token": "client query a shard CalcMessage belongs to",
    "filename": "shard rootdir(s) this work unit covers",
    "affinity": "pin dispatch to one worker id",
    "sole_shard": "single-shard query: worker may finalize on device",
    "plan": "base64-pickled plan fragment (query + predicates + strategy)",
    "bundle": "base64-pickled shared-scan bundle fragment: shared shard "
              "group + group-key columns plus one record per member query "
              "(member_id, aggs, filters, deadline) — the worker executes "
              "the whole compatible micro-batch as one scan "
              "(plan.bundle.bundle_fragment)",
    "dag": "base64-pickled operator-DAG wire form (plan.dag.OperatorDAG."
           "to_wire): the rpc.query verb's compiled program — broadcast "
           "join dimension table, window rollup, post-derivation filter, "
           "and the ordered physical agg list with extended op strings "
           "(topk:<k>:..., quantile:<q>:<alpha>).  Authoritative on "
           "capable workers; pre-DAG workers fall back to the positional "
           "params and reject the extended ops, which the controller "
           "rewrites into the structured UnsupportedOp mixed-version "
           "error (MIGRATION 'PR 13').  Extended partials ride the "
           "ordinary data frame as ResultPayload part kinds "
           "topk_values/topk_offsets and sketch_keys/sketch_counts/"
           "sketch_offsets (parallel.opexec)",
    "worker_id": "explicit dispatch target / WRM sender identity",
    "ticket": "download/movebcolz ticket id",
    # controller-originated rollup build/refresh (PR 16, serve.rollup)
    "rollup_prior": "on rollup refresh dispatches: the entry's previous "
                    "partials bytes for this shard — the worker merges the "
                    "appended tail into them when the stored chunk prefix "
                    "still validates (ops.workingset.growth_since)",
    "rollup_base": "base64-pickled chunk-prefix fingerprint "
                   "(ops.workingset.table_growth_base): on refresh "
                   "dispatches, the prefix the prior partials were computed "
                   "against; on build/refresh replies, the fingerprint of "
                   "the shard the returned partials cover — the next "
                   "refresh validates against it",
    "rollup_mode": "rollup reply provenance: 'rebuild' (full scan), 'delta' "
                   "(tail chunks aggregated and hostmerged into the "
                   "prior), 'fresh' (no growth, prior returned verbatim)",
    "rollup_zones": "base64-pickled per-column census of the shard the "
                    "rollup covers ({col: {kind, zones, nulls}}): dtype "
                    "kind plus per-chunk (min,max) zone maps — what the "
                    "subsumption lattice's key-fold null-freedom and "
                    "full-chunk filter proofs check (serve.subsume)",
    # worker -> controller replies
    "data": "raw result payload bytes",
    "phase_timings": "per-phase seconds dict; whole-call wall under _total",
    "spans": "worker span list folded into the query trace timeline",
    "deadline_remaining": "seconds left at reply serialization",
    "effective_strategy": "physical kernel route the worker ran post-guards "
                          "(matmul/scatter/sort/host; 'cached' = result-"
                          "cache hit, nothing compiled; 'delta' = delta-"
                          "maintained refresh: only appended chunks "
                          "re-aggregated, merged into the cached result) — "
                          "hints may normalize",
    "compiled": "on calc replies, ONLY when the unit compiled: how many "
                "instrumented jitted calls compiled a new program (or "
                "loaded it from the persistent cache) inside it — the "
                "compile registry's jit_cache_misses delta.  Absent from "
                "a steady-state reply; the controller sums it into the "
                "client result envelope (rpc.last_call_compiled)",
    "merge_mode": "how the reply's partials merged: 'device' (ICI-mesh "
                  "collective, final table only fetched — classic groupbys "
                  "since PR 7, batched extended-DAG dispatches since "
                  "PR 15), 'host' (hostmerge.merge_payloads fallback, also "
                  "the per-shard DAG pipeline's cross-shard merge), 'none' "
                  "(single payload, nothing merged)",
    "bundle_members": "on shared-scan bundle replies: the member_id list "
                      "the reply's data frame covers (its bytes are one "
                      "pickled {payloads: {member_id: bytes}, errors: "
                      "{member_id: text}} envelope the controller "
                      "demultiplexes per member)",
    "member_shares": "on shared-scan bundle replies: {member_id: fraction} "
                     "of the bundle's shared scan wall each member is "
                     "accountable for (measured per-member walls on the "
                     "fallback path, an equal split on the one-program "
                     "mesh path, 0.0 for result-cache hits) — the "
                     "controller scales the shared phase_timings by it so "
                     "a slow BUNDLE never lands every member in the "
                     "slow-query ring with the whole bundle's wall",
    "transient": "on worker ErrorMessage replies: the failure is retryable "
                 "(chaos.TransientError class, e.g. DeviceBusyError) — the "
                 "controller fails the shard over to a different holder "
                 "instead of aborting the query",
    "error": "failure detail on error/ticketdone paths",
    "result": "base64-pickled rpc verb return value",
    # worker register messages (WRM heartbeats)
    "node": "worker host name",
    "ip": "worker advertised IP",
    "data_dir": "worker shard directory",
    "data_files": "shard files the worker serves",
    "workertype": "calc | download",
    "pid": "worker process id",
    "uptime": "seconds since worker start",
    "msg_count": "messages handled by the worker",
    "backend_wedged": "device-health latch (health scoring + routing)",
    "work_errors": "cumulative error-counter total (health windows)",
    "debug": "node debug-bundle slice (flight tail, compile registry, ...)",
    "shard_stats": "per-shard planning stats (rows, min/max)",
    "metrics": "histogram snapshot (bucket-vector mergeable)",
    "pipeline_busy": "cumulative per-stage StageClock busy seconds "
                     "(parallel.pipeline snapshot) — the controller's "
                     "capacity model (obs.capacity) derives per-stage busy "
                     "deltas from it to name each worker's bottleneck "
                     "stage; None for non-calc roles",
    "liveness_only": "heartbeat-thread WRM: skip data_files rescan",
    # controller gossip + bookkeeping riders
    "from": "gossiping controller address",
    "info": "base64-pickled controller info snapshot (peer gossip)",
    "others": "peer-controller snapshots inside rpc.info(include_peers)",
    "last_seen": "controller-local: last WRM/gossip arrival time",
    "busy": "controller-local: worker has work in flight",
    "hb_only": "controller-local: worker seen only via heartbeats so far",
    "_retries": "controller-internal: dispatch retry count rider",
    "_excluded_workers": "controller-internal: holders this shard already "
                         "failed on — failover dispatch avoids them while "
                         "another candidate exists",
    "_attempt_history": "controller-internal: per-attempt worker/fault "
                        "records, surfaced in the structured exhaustion "
                        "envelope (attempts key)",
    "_not_before": "controller-internal: failover backoff gate — the "
                   "dispatcher holds the shard until this timestamp",
    "_backoff_s": "controller-internal: the backoff delay charged before "
                  "this attempt's dispatch — the attribution layer carves "
                  "it out of the dispatch span as a retry_backoff segment",
    "_bundle_parents": "controller-internal: member_id -> parent_token map "
                       "of a bundle dispatch; rides the envelope so the "
                       "reply (msg.copy) carries its own demux table",
    "_dispatch_queued_ts": "controller-internal: dispatch queue-entry time",
    "_picked_up": "controller-internal: (wall, perf_counter) of handle_in's "
                  "pickup of a groupby / query request, where its "
                  "request_decode span starts; popped by the verb, never "
                  "sent on",
    "_relayed": "controller-internal: fan-out marker on relayed verbs",
    "_obs": "controller-internal: per-query observability state rider",
}

#: the pickled groupby RESULT envelope (not a Message): what rpc.py unpickles
#: from a calc reply
RESULT_ENVELOPE_SCHEMA = {
    "ok": "False when the query failed (error carries the reason)",
    "busy": "admission BUSY backpressure marker (RPCBusyError client-side)",
    "payloads": "per-shard-group ResultPayload byte strings (client result "
                "envelope); in a shared-scan bundle reply data frame, the "
                "{member_id: ResultPayload bytes} demux map",
    "v": "version stamp of a shared-scan bundle reply data frame",
    "errors": "in a shared-scan bundle reply data frame: {member_id: text} "
              "member-only failures (deadline expiry, member-shape "
              "rejection) — the controller aborts just those members",
    "timings": "compacted per-phase timing summary",
    "strategies": "planner report: {hints: hint->dispatches, effective: "
                  "shard-group->executed kernel route}",
    "merge_modes": "shard-group -> merge_mode the worker reported "
                   "(device/host/none; see the merge_mode envelope key)",
    "compiled": "programs the query's workers compiled for it (sum of the "
                "calc replies' compiled key); key absent when none did — "
                "rpc.last_call_compiled reads 0 then",
    "error": "failure reason when ok is False",
    "error_class": "structured failure class when ok is False (e.g. "
                   "'DispatchExhausted' once the retry/failover budget is "
                   "spent); None for plain errors",
    "attempts": "per-attempt worker/fault history ({worker, reason, "
                "retries, ts} dicts) behind an error_class failure — the "
                "flight-recorder trail a client can act on",
    "answer_source": "answer provenance (PR 16): 'recompute' | 'cached' "
                     "(every shard from a worker result cache) | 'delta' "
                     "(delta-maintained refresh) | 'rollup' (materialized "
                     "rollup served verbatim) | 'subsume' (folded from a "
                     "finer rollup by the subsumption lattice); surfaced "
                     "as rpc.last_call_answer_source",
    "subsumed_from": "on rollup/subsume answers: the materialized-view key "
                     "the answer was proven from (serve.subsume.view_key); "
                     "None on dispatched answers",
}

#: keys legitimately touched on only one side of the wire MODULES — the peer
#: lives elsewhere (the Message base class in this module, plan/admission,
#: client tooling).  Every waiver states where the other side is.
WIRE_ONE_SIDED_OK = {
    "msg_type": "written/read by Message.__init__ and msg_factory here",
    "version": "written by Message.__init__ here; never read yet (v1)",
    "created": "written by Message.__init__ here; age derived by readers",
    "params": "set_args_kwargs/get_args_kwargs accessors in this module",
    "deadline": "written via Message.set_deadline; read via the deadline "
                "helpers in this module",
    "trace": "set_trace/get_trace accessors in this module",
    "priority": "written by rpc.py; read by plan/admission.py (not a wire "
                "module)",
    "function": "read by worker.py execute_code; set by client tooling",
    "needs_local": "read by controller dispatch; set by download tooling",
    "ticket": "written by controller ticketdone replies; read by download "
              "tooling and coordination paths",
    "last_seen": "controller-local worker_map/gossip bookkeeping",
    "hb_only": "controller-local worker_map bookkeeping",
    "_obs": "controller-internal rider, intentionally unread elsewhere",
    "deadline_remaining": "informational reply field for clients/tests; "
                          "the controller deliberately ignores it",
    "others": "written into get_info(); read by rpc.info() clients/tests",
    "ip": "operator-facing WRM field surfaced via rpc.info(); the "
          "controller routes by socket identity, not this",
    "pid": "operator-facing WRM field surfaced via rpc.info()",
    "uptime": "operator-facing WRM field surfaced via rpc.info()",
    "msg_count": "operator-facing WRM field surfaced via rpc.info()",
    "v": "bundle data-envelope version stamp written by "
         "worker._handle_bundle; the controller's demux tolerates v1 only "
         "today, so nothing reads it yet",
}

#: The declared truth of every SPAN NAME that can appear on a query trace
#: timeline, diffed by ``bqueryd_tpu.analysis.spans`` against the literal
#: span sites (``timer.phase("...")`` / ``self._phase("...")`` /
#: ``recorder.span("...")`` / ``obs.make_span(trace_id, "...", ...)`` /
#: ``SpanRecorder(root_name="...")``) the package actually contains, and
#: against the attribution map in ``obs.slo.SPAN_CATEGORIES`` — so a new
#: dispatch path cannot ship spans that ``rpc.autopsy`` silently drops into
#: ``unattributed``.  RAW entries are worker PhaseTimer phase names; they
#: surface on the wire under their public name via
#: ``obs.trace.PHASE_SPAN_NAMES`` (noted per entry).  Adding a span site
#: means adding its name here (and a category in obs.slo) in the same
#: commit.
SPAN_SCHEMA = {
    # controller-side spans
    "groupby": "the query's controller root span: submit -> final reply",
    "admission": "admission-queue wait: submit -> launch (or -> staging)",
    "batch_window": "micro-batch staging wait: window stage -> flush "
                    "(BQUERYD_TPU_BATCH_WINDOW_MS)",
    "plan": "logical-plan compilation + rewrites inside rpc_groupby",
    "dispatch": "one dispatch ATTEMPT: queue entry -> worker send; tags "
                "carry worker/retries/backoff_s/hedge so the attribution "
                "layer can split out retry_backoff and hedge duplicates "
                "(tag failed: a failed attempt's send -> failover window)",
    "inflight": "one answered attempt's send -> the reply's pickup at the "
                "controller (the worker's calc and the wire both ways); "
                "tag hedge: the hedge duplicate's race window",
    "request_decode": "controller: handle_in's pickup of the client's "
                      "frames -> the query's trace state (msg_factory, "
                      "the flight record, the verb's checks); before the "
                      "groupby root",
    "reply_absorb": "controller: handle_in's pickup of a worker's calc "
                    "reply -> the segment's completion check (spans, "
                    "payload and timings folded in)",
    "reply_encode": "controller: the result envelope's build, pickle and "
                    "send to the client; ends the groupby root",
    "finalize": "controller: _finalize_query_obs after the client's reply "
                "(SLO record, attribution, trace store put, slow-query "
                "check); appended to the stored timeline",
    "demux": "shared-scan bundle reply demultiplex at the controller",
    # client-side spans (rpc.py; on a timeline only as rpc.trace returns it
    # to the process that made the call)
    "client_encode": "client: the request envelope's build -> its send",
    "client_decode": "client: the reply's receipt -> the finished result "
                     "(pickle.loads, ResultPayload.from_bytes, hostmerge, "
                     "payload_to_dataframe)",
    # worker-side spans (public names)
    "calc": "the worker's root span for one CalcMessage",
    "storage_decode": "raw phase 'open': shard open + column decode",
    "prune": "raw phase: chunk-level predicate pruning",
    "filter": "raw phase 'mask': where-term mask evaluation",
    "factorize": "raw phase: key factorization (engine path)",
    "join_probe": "raw phase 'join': broadcast hash-join key factorize + "
                  "dimension probe gather (operator-DAG executor)",
    "window_rollup": "raw phase 'rollup': datetime-bucket derived group "
                     "key computation (operator-DAG executor)",
    "align": "raw phase: cross-shard key alignment / global key space",
    "h2d_transfer": "raw phase 'layout': host->device uploads",
    "kernel": "raw phase 'aggregate': the compiled mesh program (collective "
              "merge fused in; includes async dispatch wait)",
    "d2h_fetch": "raw phase 'fetch': device->host fetch of the merged "
                 "result buffer",
    "merge": "raw phases 'collect'/'hostmerge': materialization / host "
             "value-keyed merge of partials",
    "reply_serialization": "raw phase 'serialize': result payload encoding",
    # raw PhaseTimer names (surface via obs.trace.PHASE_SPAN_NAMES)
    "open": "raw name of storage_decode",
    "mask": "raw name of filter",
    "join": "raw name of join_probe",
    "rollup": "raw name of window_rollup",
    "layout": "raw name of h2d_transfer",
    "aggregate": "raw name of kernel",
    "fetch": "raw name of d2h_fetch",
    "collect": "raw name of merge (device-path materialization)",
    "hostmerge": "raw name of merge (host value-keyed merge)",
    "serialize": "raw name of reply_serialization",
    # DETAIL names (utils.tracing.detail): exist only on a worker running
    # with BQUERYD_TPU_PROFILE=1, as a jax.profiler annotation and — where
    # marked "span" — a span nested by its interval in the phase named.
    # Never a phase_timings key.
    "parse": "detail span in calc: envelope args, plan fragment / DAG "
             "round trip, up to the first table open",
    "cache_probe": "detail span in calc: result-cache key + get and the "
                   "delta key before the execution, both made from the "
                   "identities the open read; the delta cache's own "
                   "look-up (_serve_delta); cache.put + delta_cache.store "
                   "after serialize",
    "table_keys": "detail span in calc, between the executor's prune and "
                  "align: the identities of the unit's tables for the "
                  "executor's cache keys, taken from what the worker's "
                  "open read (a stat and a realpath each, once a unit); "
                  "asked of the filesystem here only for bare tables",
    "mem_sample": "detail span in calc: each device-memory sample around "
                  "the execution (with note_devices)",
    "layout_fold": "detail span in h2d_transfer: the row mask folded into "
                   "the codes, on the device or on the host",
    "layout_pack": "detail span in h2d_transfer: _pack of codes, stacked "
                   "masks or an inline-built measure column",
    "layout_h2d": "detail span in h2d_transfer: a host->device placement "
                  "(returns at once; a name for the device trace)",
    "layout_columns": "detail span in h2d_transfer: decode + narrow of a "
                      "missing measure column on the loop thread, or its "
                      "wait for one built on the pool",
    "aggregate_launch": "detail span in kernel: the program call until it "
                        "returns (jit look-up, enqueue; a compile when "
                        "there is one)",
    "aggregate_wait": "detail span in kernel: block_until_ready — the "
                      "device's time as the host waits for it; tagged "
                      "effective_strategy and, where the mesh executor "
                      "summed in float64 on an accelerator, float_sum "
                      "(dense | segmented); for a solo or DAG unit also "
                      "merge_mode (device | host, the reply's own) and "
                      "devices (the mesh's size)",
    "float_sum_wait": "detail span in aggregate_wait: the same wait, only "
                      "for a launch whose float64 sums took a form other "
                      "than dense (tagged form: segmented) - the device's "
                      "time is then mostly the float sum's",
    "send": "detail annotation: the reply's send; its seconds ride the "
            "next calc reply's phase_timings['post_prev']",
    "post": "detail annotation: Done + throttled gc.collect() + RSS check "
            "after the reply; seconds in post_prev like send",
    "wait_for_work": "detail annotation: the worker loop's poller.poll",
    "heartbeat": "detail annotation: the worker loop's heartbeat check",
}


class MalformedMessage(Exception):
    pass


class Message(dict):
    """A message is a dict; subclasses only pin ``msg_type``."""

    msg_type = None

    def __init__(self, datadict=None):
        super().__init__()
        if not datadict:
            datadict = {}
        self.update(datadict)
        self["payload"] = datadict.get("payload")
        self["version"] = datadict.get("version", 1)
        self["msg_type"] = self.msg_type
        # Preserve the sender's timestamp across parse/copy so envelope age is
        # measurable; only stamp fresh messages.  (The reference re-stamped on
        # every parse, reference bqueryd/messages.py:37.)
        self["created"] = datadict.get("created", time.time())

    def copy(self):
        return msg_factory(dict(self))

    def isa(self, payload_or_class):
        """True if this message's type matches ``payload_or_class`` (a Message
        subclass) or its payload equals it (a string verb)."""
        if self.msg_type is not None and self.msg_type == getattr(
            payload_or_class, "msg_type", "_"
        ):
            return True
        return self.get("payload") == payload_or_class

    # -- binary fields -----------------------------------------------------
    def add_as_binary(self, key, value):
        self[key] = base64.b64encode(
            pickle.dumps(value, protocol=PICKLE_PROTOCOL)
        ).decode("ascii")

    def get_from_binary(self, key, default=None):
        buf = self.get(key)
        if not buf:
            return default
        if isinstance(buf, str):
            buf = buf.encode("ascii")
        return pickle.loads(base64.b64decode(buf))

    # -- deadlines ---------------------------------------------------------
    # A deadline is an absolute unix timestamp under the ``deadline`` key.
    # The RPC client stamps it, the controller copies it onto every shard
    # CalcMessage it fans out (and expires queued work past it), and the
    # worker refuses work that arrives already expired — replies keep the
    # field (Message.copy()), so deadlines propagate end to end.
    def set_deadline(self, seconds=None, at=None):
        """Absolute (``at``) or relative-to-now (``seconds``) deadline."""
        if at is not None:
            self["deadline"] = float(at)
        elif seconds is not None:
            self["deadline"] = time.time() + float(seconds)

    def deadline_remaining(self, now=None):
        """Seconds until the deadline, or None when none is set."""
        deadline = self.get("deadline")
        if deadline is None:
            return None
        return float(deadline) - (time.time() if now is None else now)

    def deadline_expired(self, now=None):
        remaining = self.deadline_remaining(now)
        return remaining is not None and remaining <= 0

    # -- tracing -----------------------------------------------------------
    # The trace context is a plain dict (schema in the module docstring) so
    # this module stays stdlib-only; obs.trace.TraceContext.from_wire parses
    # it at the hops that record spans.
    def set_trace(self, wire):
        """Attach a wire TraceContext dict (or a TraceContext via its
        ``to_wire``); None clears."""
        if wire is None:
            self.pop("trace", None)
            return
        if hasattr(wire, "to_wire"):
            wire = wire.to_wire()
        self["trace"] = dict(wire)

    def get_trace(self):
        """The wire TraceContext dict, or None."""
        wire = self.get("trace")
        return wire if isinstance(wire, dict) else None

    # -- call params -------------------------------------------------------
    def set_args_kwargs(self, args, kwargs):
        self.add_as_binary("params", {"args": args, "kwargs": kwargs})

    def get_args_kwargs(self):
        params = self.get_from_binary("params", {})
        return params.get("args", []), params.get("kwargs", {})

    def to_json(self):
        return json.dumps(self)


class WorkerRegisterMessage(Message):
    msg_type = "worker_register"


class CalcMessage(Message):
    """A unit of work for a calc worker.  Beyond the reference fields it may
    carry ``deadline`` (absolute ts, see the deadline helpers above) and
    ``plan`` — a pickled plan fragment (:func:`bqueryd_tpu.plan.fragment_for`)
    holding the rewritten query, pushed-down predicates, and the planner's
    kernel-strategy hint; workers execute the fragment when present and fall
    back to the positional params otherwise (mixed-version clusters)."""

    msg_type = "calc"


class RPCMessage(Message):
    msg_type = "rpc"


class ErrorMessage(Message):
    msg_type = "error"


class BusyMessage(Message):
    msg_type = "busy"


class DoneMessage(Message):
    msg_type = "done"


class StopMessage(Message):
    msg_type = "stop"


class TicketDoneMessage(Message):
    msg_type = "ticketdone"


MSG_MAPPING = {
    "calc": CalcMessage,
    "rpc": RPCMessage,
    "error": ErrorMessage,
    "worker_register": WorkerRegisterMessage,
    "busy": BusyMessage,
    "done": DoneMessage,
    "ticketdone": TicketDoneMessage,
    "stop": StopMessage,
    None: Message,
}


def msg_factory(msg, strict=True):
    """Parse ``msg`` (JSON str/bytes or dict) into the right Message subclass.

    Same dispatch table as the reference factory (reference
    bqueryd/messages.py:14-20); unknown ``msg_type`` values map to the base
    class so protocol extensions degrade gracefully.
    """
    if isinstance(msg, bytes):
        msg = msg.decode("utf-8", errors="replace")
    if isinstance(msg, str):
        try:
            msg = json.loads(msg)
        except ValueError as exc:
            if strict:
                raise MalformedMessage(f"unparseable message: {exc}") from exc
            msg = None
    if not msg:
        return Message()
    msg_class = MSG_MAPPING.get(msg.get("msg_type"), Message)
    return msg_class(msg)
