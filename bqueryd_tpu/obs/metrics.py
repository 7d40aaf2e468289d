"""Typed metrics primitives + per-node registry + Prometheus text rendering.

The reference's entire observability surface was one client-side wall clock
(reference bqueryd/rpc.py:128-129); this build had an untyped ``counters``
dict on the controller and nothing anywhere else.  This module replaces both
with the standard three primitives:

* :class:`Counter`   — monotonic (plus an explicit ``set_total`` seam so the
  controller's dict-compatible counter view can mirror writes);
* :class:`Gauge`     — settable, or callback-backed (``fn=``) so liveness
  values (RSS, queue depth, wedge latch) are read at render time;
* :class:`Histogram` — FIXED log-scale latency buckets
  (:data:`LATENCY_BUCKETS_S`), stored as a non-cumulative per-bucket count
  vector so merging histograms across workers is a plain vector add
  (:func:`merge_histogram_snapshots`) — the controller aggregates every
  worker's phase histograms in ``get_info``/gossip without parsing text.

A :class:`MetricsRegistry` is **per node instance**, not process-global: the
test topology (and bench) runs controller + workers as threads of one
process, and their metrics must not bleed into each other.  Rendering follows
the Prometheus text exposition format v0.0.4; every metric name must match
``^bqueryd_tpu_[a-z0-9_]+$`` and carry help text (:meth:`MetricsRegistry.lint`
enforces both, plus the identical-bucket merge precondition — tests invoke it
against live node registries).

Control-plane module: stdlib only (no numpy/JAX).
"""

import math
import os
import re
import threading

METRIC_NAME_RE = re.compile(r"^bqueryd_tpu_[a-z0-9_]+$")

#: Fixed log-scale latency buckets (seconds), ~2.5x steps from 100 µs to 60 s.
#: A module constant, never instance-configurable for latency metrics: every
#: node must hold the identical vector or the controller's cross-worker
#: bucket-vector addition would silently mis-merge (lint enforces this).
LATENCY_BUCKETS_S = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 60.0,
)

#: Fixed log-scale payload-size buckets (bytes), 4x steps from 256 B to
#: 1 GiB.  Same contract as LATENCY_BUCKETS_S: a module constant, never
#: instance-configurable, so every node holds the identical vector and
#: cross-worker merging stays bucket-vector addition (lint-enforced — a
#: size histogram with latency buckets would be as wrong as the reverse).
BYTES_BUCKETS = tuple(float(256 << (2 * i)) for i in range(12))


def _fmt_value(v):
    if v == math.inf:
        return "+Inf"
    if isinstance(v, float) and v.is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(v) if isinstance(v, float) else str(v)


def _fmt_labels(labels):
    if not labels:
        return ""
    inner = ",".join(
        '%s="%s"' % (k, str(v).replace("\\", r"\\").replace('"', r"\""))
        for k, v in sorted(labels.items())
    )
    return "{%s}" % inner


class Counter:
    """Monotonic counter.  ``inc`` is the public surface; ``set_total`` exists
    only for the controller's dict-compatible mirror (RegistryCounters), which
    assigns absolute values — it must never go backwards in normal use."""

    kind = "counter"

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    _bqtpu_guarded_ = {"_lock": ("_value",)}

    def __init__(self, name, help_text, labels=None):
        self.name = name
        self.help = help_text
        self.labels = dict(labels or {})
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount=1.0):
        with self._lock:
            self._value += amount

    def set_total(self, value):
        with self._lock:
            self._value = float(value)

    @property
    def value(self):
        with self._lock:
            return self._value

    def samples(self):
        with self._lock:
            return [(self.name, self.labels, self._value)]


class Gauge:
    """Settable value, or callback-backed (``fn``) read at render time."""

    kind = "gauge"

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    _bqtpu_guarded_ = {"_lock": ("_value",)}

    def __init__(self, name, help_text, labels=None, fn=None):
        self.name = name
        self.help = help_text
        self.labels = dict(labels or {})
        self._fn = fn
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value):
        with self._lock:
            self._value = float(value)

    def inc(self, amount=1.0):
        with self._lock:
            self._value += amount

    def dec(self, amount=1.0):
        self.inc(-amount)

    @property
    def value(self):
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                # a gauge callback must never break rendering (e.g. psutil
                # gone, device probe raising); NaN marks it unreadable
                return float("nan")
        with self._lock:
            return self._value

    def samples(self):
        return [(self.name, self.labels, self.value)]


class Histogram:
    """Fixed-bucket histogram with vector-add mergeable counts.

    Internally stores NON-cumulative per-bucket counts (len(buckets)+1, the
    last slot is the +Inf overflow) plus a running sum; rendering converts to
    Prometheus cumulative ``_bucket{le=...}`` samples.  ``counts`` vectors
    from different nodes merge by element-wise addition as long as the bucket
    vectors are identical — the lint's merge precondition."""

    kind = "histogram"

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    _bqtpu_guarded_ = {"_lock": ("_counts", "_sum")}

    def __init__(self, name, help_text, labels=None, buckets=LATENCY_BUCKETS_S):
        self.name = name
        self.help = help_text
        self.labels = dict(labels or {})
        self.buckets = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._lock = threading.Lock()

    def observe(self, value):
        value = float(value)
        # linear scan beats bisect at this bucket count for typical (small)
        # latencies, and the loop body is branch-predictable
        idx = len(self.buckets)
        for i, b in enumerate(self.buckets):
            if value <= b:
                idx = i
                break
        with self._lock:
            self._counts[idx] += 1
            self._sum += value

    @property
    def count(self):
        with self._lock:
            return sum(self._counts)

    @property
    def sum(self):
        with self._lock:
            return self._sum

    def snapshot(self):
        """JSON-safe state: {"buckets", "counts", "sum"} (counts non-cumulative)."""
        with self._lock:
            return {
                "buckets": list(self.buckets),
                "counts": list(self._counts),
                "sum": self._sum,
            }

    def samples(self):
        with self._lock:
            counts = list(self._counts)
            total_sum = self._sum
        out = []
        cumulative = 0
        for b, c in zip(self.buckets, counts):
            cumulative += c
            labels = dict(self.labels)
            labels["le"] = _fmt_value(float(b))
            out.append((self.name + "_bucket", labels, cumulative))
        cumulative += counts[-1]
        inf_labels = dict(self.labels)
        inf_labels["le"] = "+Inf"
        out.append((self.name + "_bucket", inf_labels, cumulative))
        out.append((self.name + "_sum", self.labels, total_sum))
        out.append((self.name + "_count", self.labels, cumulative))
        return out


class MetricsRegistry:
    """Per-node metric store: get-or-create by (name, label set), grouped
    into families for rendering.  All mutating/creating calls are
    lock-protected; the hot path (a created metric's ``inc``/``observe``)
    takes only the metric's own lock."""

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    _bqtpu_guarded_ = {"_lock": ("_metrics", "_families")}

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}   # (name, labels-frozenset) -> metric
        self._families = {}  # name -> (kind, help)

    def _get_or_create(self, cls, name, help_text, labels, **kw):
        key = (name, frozenset((labels or {}).items()))
        with self._lock:
            hit = self._metrics.get(key)
            if hit is not None:
                return hit
            family = self._families.get(name)
            if family is not None and family[0] != cls.kind:
                raise ValueError(
                    f"metric {name!r} already registered as {family[0]}"
                )
            metric = cls(name, help_text, labels=labels, **kw)
            self._metrics[key] = metric
            self._families.setdefault(name, (cls.kind, help_text))
            return metric

    def register(self, metric):
        """Adopt an EXISTING metric instance (process-global metrics like
        the compile profiler's histogram, shared across every node registry
        in one process).  Re-registering the same instance is a no-op; a
        different instance under a taken (name, labels) key is an error."""
        key = (metric.name, frozenset(metric.labels.items()))
        with self._lock:
            hit = self._metrics.get(key)
            if hit is metric:
                return metric
            if hit is not None:
                raise ValueError(
                    f"metric {metric.name!r} already registered with a "
                    "different instance"
                )
            family = self._families.get(metric.name)
            if family is not None and family[0] != metric.kind:
                raise ValueError(
                    f"metric {metric.name!r} already registered as "
                    f"{family[0]}"
                )
            self._metrics[key] = metric
            self._families.setdefault(metric.name, (metric.kind, metric.help))
            return metric

    def counter(self, name, help_text, labels=None):
        return self._get_or_create(Counter, name, help_text, labels)

    def gauge(self, name, help_text, labels=None, fn=None):
        return self._get_or_create(Gauge, name, help_text, labels, fn=fn)

    def histogram(self, name, help_text, labels=None,
                  buckets=LATENCY_BUCKETS_S):
        return self._get_or_create(
            Histogram, name, help_text, labels, buckets=buckets
        )

    def metrics(self):
        with self._lock:
            return list(self._metrics.values())

    # -- rendering ----------------------------------------------------------
    def render(self):
        """Prometheus text exposition format v0.0.4."""
        with self._lock:
            metrics = list(self._metrics.values())
            families = dict(self._families)
        by_family = {}
        for metric in metrics:
            by_family.setdefault(metric.name, []).append(metric)
        lines = []
        for name in sorted(by_family):
            kind, help_text = families[name]
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {kind}")
            for metric in by_family[name]:
                for sample_name, labels, value in metric.samples():
                    lines.append(
                        f"{sample_name}{_fmt_labels(labels)} "
                        f"{_fmt_value(value)}"
                    )
        return "\n".join(lines) + "\n"

    def histogram_snapshot(self):
        """All histograms as a JSON-safe mergeable snapshot — rides worker
        WRMs so the controller can aggregate by bucket-vector addition:
        ``{name: [{"labels": {...}, "buckets": [...], "counts": [...],
        "sum": s}, ...]}``."""
        out = {}
        for metric in self.metrics():
            if metric.kind != "histogram":
                continue
            entry = metric.snapshot()
            entry["labels"] = dict(metric.labels)
            out.setdefault(metric.name, []).append(entry)
        return out

    # -- self-check ---------------------------------------------------------
    def lint(self):
        """Registry self-check (invoked from tests): every metric name
        matches METRIC_NAME_RE (counters may suffix ``_total``), has
        non-empty help text, and every histogram carries one of the shared
        module bucket vectors — LATENCY_BUCKETS_S for latencies,
        BYTES_BUCKETS for sizes (the cross-node merge precondition).
        Returns a list of violation strings — empty means clean."""
        problems = []
        for metric in self.metrics():
            base = metric.name
            if base.endswith("_total"):
                base = base[: -len("_total")]
            if not METRIC_NAME_RE.match(base):
                problems.append(f"{metric.name}: name fails {METRIC_NAME_RE.pattern}")
            if not (metric.help or "").strip():
                problems.append(f"{metric.name}: missing help text")
            if metric.kind == "histogram" and metric.buckets not in (
                tuple(LATENCY_BUCKETS_S), tuple(BYTES_BUCKETS)
            ):
                problems.append(
                    f"{metric.name}: bucket vector is neither "
                    "LATENCY_BUCKETS_S nor BYTES_BUCKETS (cross-node "
                    "merge precondition: buckets must be a shared module "
                    "constant)"
                )
            for label in metric.labels:
                if not re.match(r"^[a-z][a-z0-9_]*$", label):
                    problems.append(f"{metric.name}: bad label name {label!r}")
        return problems


#: the two forms a ``count_distinct`` payload leaves a worker in
#: (``models.query.distinct_wire``): the label set of
#: ``bqueryd_tpu_distinct_payloads_total``, and all of it
DISTINCT_WIRES = ("counts", "sets")


def distinct_payload_counters(registry):
    """``{wire: Counter}`` of ``bqueryd_tpu_distinct_payloads_total`` in a
    worker's registry, one per form of :data:`DISTINCT_WIRES`."""
    return {
        wire: registry.counter(
            "bqueryd_tpu_distinct_payloads_total",
            "calc payloads holding a count_distinct, by the form it "
            "leaves in: counts = final per-group counts (the payload is "
            "the whole answer); sets = per-group value sets, unioned "
            "downstream",
            labels={"wire": wire},
        )
        for wire in DISTINCT_WIRES
    }


def readme_coverage_problems(registries, readme_text):
    """Doc-coverage lint (run from tests alongside :meth:`MetricsRegistry.lint`
    against live node registries): every registered metric family must be
    named in the README's metrics documentation, or operators discover
    metrics by grepping source.  Returns violation strings, empty = clean."""
    problems = []
    seen = set()
    for registry in registries:
        for metric in registry.metrics():
            if metric.name in seen:
                continue
            seen.add(metric.name)
            if metric.name not in readme_text:
                problems.append(
                    f"{metric.name}: registered but missing from the README "
                    "metrics table"
                )
    return sorted(problems)


def quantile_from_snapshot(entry, q):
    """Approximate quantile from one histogram snapshot entry
    (``{"buckets", "counts", ...}``, counts non-cumulative): the upper
    bound of the bucket where the cumulative count crosses ``q``.  The
    +Inf overflow slot reports the last finite bound (a ceiling is still
    actionable; None would hide the signal).  None on empty/malformed
    entries — the timeline ring snapshots latency quantiles per tick and a
    cold histogram must render as "no data", not 0."""
    try:
        buckets = list(entry["buckets"])
        counts = list(entry["counts"])
    except (KeyError, TypeError):
        return None
    total = sum(counts)
    if total <= 0 or len(counts) != len(buckets) + 1:
        return None
    threshold = max(float(q), 0.0) * total
    cumulative = 0
    for bound, count in zip(buckets, counts):
        cumulative += count
        if cumulative >= threshold:
            return float(bound)
    return float(buckets[-1]) if buckets else None


def merge_histogram_snapshots(snapshots):
    """Aggregate per-worker histogram snapshots by bucket-vector addition.

    ``snapshots`` is an iterable of :meth:`MetricsRegistry.histogram_snapshot`
    dicts (one per worker).  Series merge when (name, labels) match AND the
    bucket vectors are identical; a mismatched vector (version skew) is
    surfaced under ``"_skipped"`` instead of silently corrupting the sums.
    """
    merged = {}   # name -> {labels_key: {"labels", "buckets", "counts", "sum"}}
    skipped = []
    for snap in snapshots:
        if not isinstance(snap, dict):
            continue
        for name, series in snap.items():
            if not isinstance(series, list):
                continue
            for entry in series:
                try:
                    labels = dict(entry.get("labels") or {})
                    buckets = list(entry["buckets"])
                    counts = list(entry["counts"])
                    esum = float(entry.get("sum", 0.0))
                except (KeyError, TypeError, ValueError):
                    skipped.append(name)
                    continue
                key = frozenset(labels.items())
                slot = merged.setdefault(name, {}).get(key)
                if slot is None:
                    merged[name][key] = {
                        "labels": labels,
                        "buckets": buckets,
                        "counts": counts,
                        "sum": esum,
                    }
                elif slot["buckets"] != buckets or len(
                    slot["counts"]
                ) != len(counts):
                    skipped.append(name)
                else:
                    slot["counts"] = [
                        a + b for a, b in zip(slot["counts"], counts)
                    ]
                    slot["sum"] += esum
    out = {
        name: list(by_labels.values()) for name, by_labels in merged.items()
    }
    if skipped:
        out["_skipped"] = sorted(set(skipped))
    return out


class RegistryCounters(dict):
    """The controller's ``counters`` dict, registry-backed.

    A drop-in dict (every existing ``counters["x"] += 1`` call site and the
    ``dict(self.counters)`` snapshots in ``get_info``/bench keep working
    verbatim) whose writes mirror into typed registry :class:`Counter`
    instances named ``bqueryd_tpu_<key>_total`` — so the same numbers appear
    in the Prometheus exposition without double bookkeeping at call sites."""

    def __init__(self, registry, spec):
        """``spec``: ordered mapping of dict key -> help text."""
        super().__init__()
        self._registry = registry
        self._mirror = {}
        for key, help_text in spec.items():
            self._mirror[key] = registry.counter(
                f"bqueryd_tpu_{key}_total", help_text
            )
            super().__setitem__(key, 0)

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        mirror = self._mirror.get(key)
        if mirror is None:
            # an unspecced key appearing at runtime still gets a metric —
            # lint will flag it if the name is malformed
            mirror = self._mirror[key] = self._registry.counter(
                f"bqueryd_tpu_{key}_total", f"controller counter {key}"
            )
        mirror.set_total(value)
