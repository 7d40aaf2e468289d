"""Flight recorder: the forensic artifact for wedges and hard timeouts.

When a node wedges, a dispatch blows ``DISPATCH_HARD_TIMEOUT``, or a worker
dies mid-query, PR 2's instruments say *that* it happened (a gauge flips, a
timeline is missing its tail) but not *what led up to it*.  The flight
recorder is the black box: a bounded, always-on ring per node of recent
envelopes, state transitions, and query outcomes.  Bounds are BOTH entry
count and bytes (a single huge traceback must not silently hold hours of
history hostage — nor grow without limit), with an eviction counter so
operators can size the ring from data.

``build_bundle`` assembles the cross-node JSON debug artifact behind the
controller's ``rpc.debug_bundle(trace_id=None)`` verb (and the SIGUSR1
local dump): controller flight ring + the trace timeline + metrics snapshot
+ slow queries + per-worker flight/compile/device-health snapshots absorbed
from WRM heartbeats.  A dead peer degrades the bundle, never fails it: its
last absorbed snapshot ships marked stale, and workers that never reported
are listed under ``"partial"``.  Every string in the bundle passes
:func:`redact_paths` — filesystem paths outside the declared data roots are
reduced to ``<redacted>/basename`` so a bundle can be attached to a public
bug report without leaking home directories or infra layout.

Control-plane module: stdlib only.
"""

import collections
import json
import os
import re
import tempfile
import threading
import time

#: schema /2 (PR 10): additive controller-section keys — ``autopsy`` (the
#: bundled trace's attributed critical path), ``chaos`` (fault-injection
#: stats, PR 8), ``replication`` (replica placement, PR 8), ``batch_window``
#: (micro-batch staging state, PR 9), ``slo`` (per-class accounting),
#: ``timeline_ring`` (periodic registry snapshots).
#: schema /3 (PR 12): additive ``capacity`` controller-section key — the
#: fleet capacity model's freshly-evaluated snapshot (per-worker μ/ρ/state,
#: shard heat map, predicted-vs-measured queue delay, last shadow
#: recommendations; see obs.capacity).  /1 and /2 consumers keep working:
#: nothing was removed or renamed.
#: schema /4 (PR 16): additive ``serving`` controller-section key — the
#: semantic serving layer's snapshot (materialized-rollup entry states,
#: tracked-view heat, append epochs, and the most recent subsumption
#: decisions with chosen source + rejected candidates and reasons; see
#: bqueryd_tpu.serve).  Earlier consumers keep working unchanged.
BUNDLE_SCHEMA = "bqueryd_tpu.debug_bundle/4"

DEFAULT_CAPACITY = 512
DEFAULT_MAX_BYTES = 1 << 20  # 1 MiB of ring per node

#: WRM-absorbed worker snapshots older than this are marked ``stale`` in the
#: bundle (the worker may be dead; its last words still ship)
DEFAULT_STALE_AFTER_S = 120.0


def approx_json_bytes(obj):
    """Cheap recursive size estimate of ``obj``'s JSON form — used for ring
    byte accounting, where an exact ``json.dumps`` per hot-path event would
    cost more than the event itself."""
    if obj is None or isinstance(obj, bool):
        return 4
    if isinstance(obj, (int, float)):
        return 12
    if isinstance(obj, str):
        return len(obj) + 2
    if isinstance(obj, bytes):
        return len(obj) + 2
    if isinstance(obj, dict):
        return 2 + sum(
            approx_json_bytes(k) + approx_json_bytes(v) + 2
            for k, v in obj.items()
        )
    if isinstance(obj, (list, tuple, set)):
        return 2 + sum(approx_json_bytes(v) + 1 for v in obj)
    return len(str(obj)) + 2


class FlightRecorder:
    """Bounded ring of JSON-safe events, newest last.

    Hot-path callers gate themselves on ``obs.enabled()``; rare forensic
    events (wedge latches, timeouts, worker removals, errors) are recorded
    unconditionally — they are the reason this exists."""

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    #: (lock-unguarded-attr)
    _bqtpu_guarded_ = {
        "_lock": ("_events", "_sizes", "_nbytes", "_evictions", "_seq"),
    }

    def __init__(self, node_id=None, capacity=None, max_bytes=None):
        if capacity is None:
            try:
                capacity = int(
                    os.environ.get("BQUERYD_TPU_FLIGHT_CAPACITY",
                                   DEFAULT_CAPACITY)
                )
            except ValueError:
                capacity = DEFAULT_CAPACITY
        if max_bytes is None:
            try:
                max_bytes = int(
                    os.environ.get("BQUERYD_TPU_FLIGHT_BYTES",
                                   DEFAULT_MAX_BYTES)
                )
            except ValueError:
                max_bytes = DEFAULT_MAX_BYTES
        self.node_id = node_id
        self.capacity = max(1, capacity)
        self.max_bytes = max(1024, max_bytes)
        self._events = collections.deque()
        self._sizes = collections.deque()
        self._nbytes = 0
        self._evictions = 0
        self._seq = 0
        self._lock = threading.Lock()

    def record(self, kind, **fields):
        event = {"ts": round(time.time(), 6), "kind": kind}
        event.update(fields)
        size = approx_json_bytes(event)
        with self._lock:
            self._seq += 1
            event["seq"] = self._seq
            self._events.append(event)
            self._sizes.append(size)
            self._nbytes += size
            while len(self._events) > self.capacity or (
                self._nbytes > self.max_bytes and len(self._events) > 1
            ):
                self._events.popleft()
                self._nbytes -= self._sizes.popleft()
                self._evictions += 1
        return event

    def events(self):
        """Full ring contents, oldest first."""
        with self._lock:
            return [dict(e) for e in self._events]

    def tail(self, limit=32):
        """The newest ``limit`` events, oldest first — the WRM-sized view."""
        with self._lock:
            picked = list(self._events)[-max(1, limit):]
            return [dict(e) for e in picked]

    @property
    def evictions(self):
        with self._lock:
            return self._evictions

    @property
    def nbytes(self):
        with self._lock:
            return self._nbytes

    def __len__(self):
        with self._lock:
            return len(self._events)


# -- redaction ----------------------------------------------------------------

#: an absolute filesystem path of depth >= 2; the lookbehind keeps URL
#: authority slashes (``tcp://host``) and interior path slashes from
#: matching as fresh path starts
_PATH_RE = re.compile(r"(?<![\w:/.])/(?:[\w.+-]+/)+[\w.+-]+")


def _redact_string(text, allowed):
    def sub(match):
        path = match.group(0)
        for prefix in allowed:
            if prefix and (
                path == prefix or path.startswith(prefix.rstrip("/") + "/")
            ):
                return path
        return "<redacted>/" + path.rsplit("/", 1)[-1]

    return _PATH_RE.sub(sub, text)


def redact_paths(obj, allowed_prefixes=()):
    """Recursively replace absolute filesystem paths outside the allowed
    roots with ``<redacted>/basename``.  Dict KEYS are redacted too (worker
    snapshots key some maps by filename).  Non-string leaves pass through
    untouched."""
    allowed = tuple(p for p in allowed_prefixes if p)
    if isinstance(obj, str):
        return _redact_string(obj, allowed)
    if isinstance(obj, dict):
        return {
            redact_paths(k, allowed): redact_paths(v, allowed)
            for k, v in obj.items()
        }
    if isinstance(obj, (list, tuple)):
        return [redact_paths(v, allowed) for v in obj]
    return obj


# -- bundle assembly ----------------------------------------------------------

def build_bundle(controller_section, worker_snapshots, trace_id=None,
                 allowed_path_prefixes=(), stale_after_s=DEFAULT_STALE_AFTER_S,
                 now=None):
    """Assemble the cross-node debug artifact (deterministic schema).

    ``controller_section``: the controller's own state dict (flight ring,
    counters, metrics, trace timeline, slow queries, health, ...).
    ``worker_snapshots``: ``{worker_id: {"data": <absorbed WRM debug snapshot
    or None>, "ts": <absorb time>, "registered": bool}}``.  Workers with no
    absorbed data land in ``"partial"`` — a dead or never-reporting peer
    degrades the bundle instead of failing it.
    """
    now = time.time() if now is None else now
    workers = {}
    partial = []
    for worker_id in sorted(worker_snapshots):
        snap = worker_snapshots[worker_id] or {}
        data = snap.get("data")
        entry = {
            "registered": bool(snap.get("registered")),
            "snapshot": data,
        }
        ts = snap.get("ts")
        if ts is not None:
            entry["age_s"] = round(max(now - ts, 0.0), 3)
            entry["stale"] = entry["age_s"] > stale_after_s
        if data is None:
            partial.append(worker_id)
        workers[worker_id] = entry
    bundle = {
        "schema": BUNDLE_SCHEMA,
        "generated_ts": round(now, 6),
        "trace_id": trace_id,
        "controller": controller_section,
        "workers": workers,
        "partial": partial,
    }
    return redact_paths(bundle, allowed_path_prefixes)


def dump_bundle(bundle, role="node", directory=None):
    """Write a bundle as one JSON file (the SIGUSR1 local dump); returns the
    path.  Directory: ``BQUERYD_TPU_DEBUG_DIR``, default the system tmpdir."""
    directory = (
        directory
        or os.environ.get("BQUERYD_TPU_DEBUG_DIR")
        or tempfile.gettempdir()
    )
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(
        directory,
        f"bqueryd_tpu_debug_{role}_{os.getpid()}_{int(time.time())}.json",
    )
    with open(path, "w") as f:
        json.dump(bundle, f, default=str, indent=1)
    return path
