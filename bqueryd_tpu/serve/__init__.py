"""Semantic serving layer: answer admitted queries from proofs, not scans.

``ServingLayer`` is the controller's single entry point into PR-16
serving.  It composes the two halves of the subsystem:

* :mod:`bqueryd_tpu.serve.subsume` — the pure plan-subsumption lattice
  (exact / window-fold / key-fold / zone-proof matching plus the
  costed source choice);
* :mod:`bqueryd_tpu.serve.rollup` — heat tracking and the materialized
  rollup entry lifecycle (build / delta-refresh / evict, append-epoch
  staleness).

The layer sits at the very top of ``ControllerNode._admit_plan``: a hit
replies to the client immediately — consuming no admission slot, no
worker dispatch, no scan — and a miss falls through to the ordinary
pipeline untouched.  ``BQUERYD_TPU_SERVE=0`` (read per call, so it can
be flipped on a live process) disables both serving and rollup
bookkeeping; behavior then round-trips bit-identically to the exact
-signature-only tree.

All zmq message construction and envelope assembly stay in
``controller.py`` (``_dispatch_rollup_build`` / ``_absorb_rollup_reply``
/ ``_reply_served``) where the wire lint audits them; this package never
touches a socket.
"""

import os
import time
from collections import deque

from bqueryd_tpu.serve import rollup, subsume


def serve_enabled():
    """Kill switch ``BQUERYD_TPU_SERVE`` (default on).  Checked on every
    public entry point rather than cached: flipping the env var mid-run
    must restore exact-signature-only behavior immediately."""
    return os.environ.get("BQUERYD_TPU_SERVE", "1") == "1"


class ServingLayer:
    """Controller-side orchestration of subsumption + rollups."""

    def __init__(self, controller):
        self.controller = controller
        self.manager = rollup.RollupManager()
        self.decisions = deque(maxlen=32)
        self.served = 0

    # -- admission hook ------------------------------------------------

    def try_serve(self, msg, plan, kwargs):
        """Called by ``_admit_plan`` after shard validation, before any
        admission accounting.  Returns True when the query was answered
        here (reply already sent); False on any miss or refusal — the
        caller then proceeds exactly as before PR 16."""
        if not serve_enabled():
            return False
        try:
            return self._try_serve(msg, plan, kwargs)
        except Exception:
            # serving is an optimization: any internal error must degrade
            # to the always-correct dispatch path, never fail the query
            self.controller.logger.exception("serving layer error (miss)")
            return False

    def _try_serve(self, msg, plan, kwargs):
        now = time.monotonic()
        view = subsume.plan_view(plan)
        ok, reason = subsume.plan_eligible(view)
        if not ok:
            self._record_decision(None, "recompute", [("plan", reason)])
            return False
        key = subsume.view_key(view)
        spec = {
            "args": [
                list(view["keys"]),
                [list(a) for a in plan.physical_agg_list()],
                [list(t) for t in plan.where_terms],
            ],
            "dag_wire": kwargs.get("dag"),
        }
        if self.manager.note_query(key, view, spec, now):
            entry = self.manager.start_build(key, now)
            if entry is not None:
                self.controller._dispatch_rollup_build(entry)
        matches, rejected = [], []
        for entry in self.manager.candidates(view["filenames"]):
            transform, why = subsume.match(entry.view, view, entry.meta())
            if transform is None:
                rejected.append((entry.key, why))
            else:
                matches.append((entry.key, transform, entry.group_rows()))
        total_rows = 0
        for fname in view["filenames"]:
            stats = self.controller.shard_stats.get(fname) or {}
            total_rows += int(stats.get("rows", 0) or 0)
        choice = subsume.choose_source(matches, total_rows)
        if choice is None:
            if matches:
                rejected.extend((m[0], "cost") for m in matches)
            self._record_decision(key, "recompute", rejected)
            return False
        entry_key, transform, _groups = choice
        entry = self.manager.entries[entry_key]
        payloads = self._render(entry, transform)
        if payloads is None:
            self.manager.fail(entry_key, "render")
            self._record_decision(key, "recompute", rejected + [
                (entry_key, "render-error")
            ])
            return False
        source = "rollup" if transform["kind"] in ("exact", "zone") else "subsume"
        self.manager.note_hit(entry_key, now)
        self.served += 1
        self._record_decision(key, source, rejected, chosen=entry_key)
        self.controller._reply_served(msg, payloads, source, entry_key)
        return True

    def _render(self, entry, transform):
        """Per-file payload bytes for the reply envelope; None on any
        transform failure (falls back to recompute)."""
        import pickle

        out = []
        try:
            for fname in entry.filenames:
                info = entry.per_file[fname]
                if transform["kind"] in ("exact", "zone"):
                    out.append(info["data"])
                else:
                    folded = subsume.apply_transform(
                        info["payload"], transform
                    )
                    out.append(pickle.dumps(dict(folded), protocol=4))
        except Exception:
            self.controller.logger.exception("rollup fold failed")
            return None
        return out

    def _record_decision(self, key, source, rejected, chosen=None):
        self.decisions.append({
            "view": key,
            "source": source,
            "chosen": chosen,
            "rejected": [list(r) for r in rejected],
        })
        if rejected or source != "recompute":
            self.controller.flight.record(
                "serve_decision",
                view=key,
                source=source,
                chosen=chosen,
                rejected=[list(r) for r in rejected],
            )

    # -- lifecycle hooks ------------------------------------------------

    def note_append(self, filename):
        """An append for ``filename`` is about to be dispatched: stale-out
        covering rollups *before* any worker mutates its shard."""
        if not serve_enabled():
            return
        flipped = self.manager.note_append(filename, time.monotonic())
        if flipped:
            self.controller.flight.record(
                "rollup_stale", filename=filename, entries=flipped
            )

    def absorb_build(self, key, fname, info):
        """One worker build/refresh reply landed (controller-decoded)."""
        return self.manager.absorb(key, fname, info, time.monotonic())

    def tick(self):
        """Heartbeat-paced housekeeping: abandon wedged builds, enforce
        retention caps, and dispatch delta refreshes for stale entries."""
        if not serve_enabled():
            return
        now = time.monotonic()
        dropped = self.manager.sweep(now)
        if dropped:
            for key, why in dropped:
                self.controller.flight.record(
                    "rollup_evict", entry=key, reason=why
                )
            self.controller.counters["rollup_evictions"] += len(dropped)
        for key in self.manager.stale_keys():
            res = self.manager.begin_refresh(key, now)
            if res is None:
                continue
            entry, prior = res
            self.controller._dispatch_rollup_build(entry, prior=prior)

    def snapshot(self):
        """``serving`` section of the debug bundle."""
        return {
            "enabled": serve_enabled(),
            "served": self.served,
            "rollups": self.manager.snapshot(),
            "recent_decisions": list(self.decisions),
        }
