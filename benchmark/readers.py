"""Per-layer metric readers.  A metric is a data file
(``layer_metrics/<name>.json``) that names one reader and its arguments; a
reader takes the run's evidence and returns a number, or None where it
found nothing to read (the harness then leaves the metric out).

Evidence: ``records`` (one dict per window query: shape, wall_s, rows,
answer_source, effective, timings, trace_id, args, t_send, t_reply),
``cold_records``, ``traces`` ({trace_id: the controller's timeline}),
``counters_before`` / ``counters_after`` (``in_worker.counters``),
``device_trace`` (``in_worker.stop``), ``slice`` ((start, stop) on the
client's clock), ``warm_routes`` ({shape: route}), ``column_dtypes``,
``device_kind``, ``chips``.
"""

from benchmark import roofline

DEVICE_ROUTES = frozenset({"matmul", "scatter", "sort"})


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def _span_seconds(timeline, name):
    return sum(s["duration_s"] for s in timeline["spans"] if s["name"] == name)


def _traced(ev):
    """(record, timeline) of the window queries whose timeline was fetched."""
    traces = ev.get("traces") or {}
    return [(r, traces[r["trace_id"]]) for r in ev["records"]
            if r.get("trace_id") in traces and traces[r["trace_id"]]]


def span_self_time(ev, span, minus=(), scale=1000.0):
    """Mean per query of one span's time less the spans it waits on."""
    return _scaled(_mean(
        _span_seconds(t, span) - sum(_span_seconds(t, m) for m in minus)
        for _r, t in _traced(ev)
    ), scale)


def client_minus_span(ev, span, scale=1000.0):
    """Mean per query of the client's wall less the controller's root span
    of the same trace id: serialisation, the socket, the client's merge."""
    return _scaled(_mean(
        r["wall_s"] - _span_seconds(t, span) for r, t in _traced(ev)
    ), scale)


def _slice_share(ev, record):
    """The share of a query's wall that lies inside the traced slice."""
    if not ev.get("slice") or not record.get("ok"):
        return 0.0
    start, stop = ev["slice"]
    overlap = min(record["t_reply"], stop) - max(record["t_send"], start)
    return max(overlap, 0.0) / max(record["t_reply"] - record["t_send"], 1e-9)


def span_minus_device(ev, span, scale=1000.0):
    """Mean per query, over the traced slice, of a worker span less the
    time the device was busy: what the worker's host code costs.  A query
    that lies partly in the slice counts by that part."""
    trace = ev.get("device_trace") or {}
    inside = [(_slice_share(ev, r), t) for r, t in _traced(ev)]
    queries = sum(share for share, _t in inside)
    if not queries or not trace.get("busy_s"):
        return None
    spans = sum(share * _span_seconds(t, span) for share, t in inside)
    return _scaled(max(spans - trace["busy_s"], 0.0) / queries, scale)


def phase_mean(ev, phase, over="window", stat="mean", scale=1000.0):
    """A phase of the reply's own timings, summed over a query's shard
    groups: its mean per window query, or its sum over the cold pass."""
    records = ev["records"] if over == "window" else ev.get("cold_records") or []
    values = [
        sum(group.get(phase, 0.0) for group in r["timings"].values())
        for r in records if r.get("timings")
    ]
    if not values:
        return None
    return _scaled(sum(values) if stat == "sum" else _mean(values), scale)


def counter_delta(ev, counter):
    before, after = ev.get("counters_before"), ev.get("counters_after")
    if not before or not after or after.get(counter) is None:
        return None
    return float(after[counter] - before[counter])


def counter_value(ev, counter, scale=1.0):
    after = ev.get("counters_after")
    if not after or not after.get(counter):
        return None
    return after[counter] * scale


def reply_field_share(ev, field, not_in):
    """Share (%) of the window's replies whose field is not in ``not_in``."""
    values = [r.get(field) for r in ev["records"] if r.get("ok")]
    if not values:
        return None
    return 100.0 * sum(v not in not_in for v in values) / len(values)


def route_changes(ev):
    """Shapes some window reply of which took another device route than
    the one the warm-up ended on."""
    warm = ev.get("warm_routes") or {}
    changed = {
        r["shape"] for r in ev["records"]
        if r.get("fresh") and r["shape"] in warm and r.get("effective")
        and r["effective"] != warm[r["shape"]]
    }
    return float(len(changed)) if warm else None


def trace_idle(ev):
    trace = ev.get("device_trace") or {}
    if not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def trace_roofline(ev):
    """Bytes the slice's device-answered queries need, per chip, over the
    chip's peak bandwidth: the least time the device could take, as a share
    of the time it was busy.  Bytes-bound.  The device's busy time is that
    of the whole slice, so a query that lies partly in the slice counts the
    same part of its bytes."""
    trace = ev.get("device_trace") or {}
    needed = sum(
        _slice_share(ev, r) * roofline.bytes_needed(ev["column_dtypes"], r["args"], r["rows"])
        for r in ev["records"] if set(r.get("effective") or ()) & DEVICE_ROUTES
    )
    if not needed or not trace.get("busy_s"):
        return None
    peak = roofline.peaks(ev["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (needed / ev["chips"] / peak) / trace["busy_s"]


def _scaled(value, scale):
    return None if value is None else value * scale


READERS = {
    f.__name__: f for f in (
        span_self_time, client_minus_span, span_minus_device, phase_mean,
        counter_delta, counter_value, reply_field_share, route_changes,
        trace_idle, trace_roofline,
    )
}


def read(metric, ev):
    """One metric file's number, or None."""
    return READERS[metric["reader"]](ev, **metric.get("args", {}))
