"""The device's idle time booked by a sweep on one clock (PR 40).

``in_worker.reduce_planes`` books each whole idle gap of a device plane to
the host annotation that covers the gap's middle.  In a closed loop one gap
runs from the end of a query's device work to the start of the next one's,
and its middle falls in the worker's ``wait_for_work``: the worker's own
tail (``serialize``, ``send``, ``post``) and head (``parse``, ``open``,
``cache_probe``, ``layout``) go there with it.  Here every idle interval is
cut at the boundaries of the host annotations instead, and each piece is
booked to the innermost annotation that covers it (the midpoint's rule:
the latest start).

The worker's ``calc`` annotation carries ``wall_ts``, the clock of every
span's ``start_ts``, which maps the profiler's clock onto the spans'.  The
pieces booked to ``wait_for_work``, or to no annotation, keep their ends on
that clock, so ``idle_by_host`` can book them once more: to the innermost
controller or client span of the fetched timelines that covers them, or to
``between`` (the wire, the controller loop's housekeeping).  The
benchmark's controller, worker and client run on one host, on one clock.

The benchmark's own midpoint table (``idle_gaps``) stays as it is.  What
wires this module in is a ``benchmark`` PR's (PERF.md, Open questions):
``harness.Tracer`` calls ``stop`` here in place of ``in_worker.stop``,
``harness.run_cell`` writes ``idle_by_host`` and ``clock_offset_spread_ms``
into ``observed``, and ``readers.READERS`` takes ``trace_idle_booked``.

Plain Python over plain lists, like ``reduce_planes``.
"""

import glob
import heapq
import os
import statistics

from benchmark import in_worker, readers

WAIT = "wait_for_work"
NO_SPAN = "no_host_span"
#: the worker annotation that carries ``wall_ts``
CALC = "calc"
IDLE_BY_HOST = 10
#: span names by the process that records them (``messages.SPAN_SCHEMA``)
CONTROLLER_SPANS = frozenset({
    "groupby", "request_decode", "admission", "batch_window", "plan",
    "dispatch", "inflight", "demux", "reply_absorb", "reply_encode",
    "finalize",
})
CLIENT_SPANS = frozenset({"client_encode", "client_decode"})


def stop(logdir):
    """``in_worker.stop``, with the sweep and the clock offset beside its
    numbers (run inside the worker, like it)."""
    out = in_worker.stop(logdir)
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if paths:
        path = max(paths, key=os.path.getmtime)
        out.update(sweep(in_worker.load_planes(path), load_walls(path)))
    return out


def load_walls(path):
    """``[[start_ns, wall_ts], ...]`` of the host's ``calc`` annotations
    that carry ``wall_ts``."""
    from jax.profiler import ProfileData

    walls = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == CALC:
                    stats = dict(event.stats)
                    if "wall_ts" in stats:
                        walls.append([int(event.start_ns), float(stats["wall_ts"])])
    return walls


def clock_offset(walls):
    """``(offset_ns, spread_ns)``: the median of ``wall_ts * 1e9 - start_ns``
    over the calc events, and how far those differences range; ``(None,
    None)`` without any."""
    diffs = sorted(wall * 1e9 - start for start, wall in walls)
    if not diffs:
        return None, None
    return statistics.median(diffs), diffs[-1] - diffs[0]


def _tile(host, first, last):
    """``[[start, end, name], ...]`` tiling ``[first, last]``: each instant
    booked to the interval of ``host`` (sorted ``(start, end, name)``) with
    the latest start that covers it, ``NO_SPAN`` where none does."""
    bounds = sorted({first, last} | {
        t for start, end, _name in host for t in (start, end) if first < t < last
    })
    tiles, active, i = [], [], 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(host) and host[i][0] <= lo:
            heapq.heappush(active, (-host[i][0], host[i][1], host[i][2]))
            i += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        name = active[0][2] if active else NO_SPAN
        if tiles and tiles[-1][2] == name and tiles[-1][1] == lo:
            tiles[-1][1] = hi
        else:
            tiles.append([lo, hi, name])
    return tiles


def _cut(intervals, tiles):
    """Each of ``intervals`` (``[start, end, ...]``, sorted by start) cut
    by ``tiles``: ``[[start, end, tile name, interval], ...]``."""
    pieces, j = [], 0
    for interval in intervals:
        lo, hi = interval[0], interval[1]
        while j < len(tiles) and tiles[j][1] <= lo:
            j += 1
        k = j
        while k < len(tiles) and tiles[k][0] < hi:
            start, end = max(lo, tiles[k][0]), min(hi, tiles[k][1])
            if end > start:
                pieces.append([start, end, tiles[k][2], interval])
            k += 1
    return pieces


def sweep(planes, walls=()):
    """Every idle interval of every device plane cut at the host
    annotations' boundaries: ``idle_swept`` ``[[annotation, seconds], ...]``
    (a mean over the device planes, as ``reduce_planes`` reports; it sums
    to ``window_s - busy_s``), and where ``walls`` give the clock offset,
    ``clock_offset_spread_ms`` and ``idle_pieces``: the pieces booked to
    ``wait_for_work`` or to no annotation, ``[[wall_start_s, wall_end_s,
    name], ...]``, the longest ``NAMED_GAPS`` of them."""
    devices = [
        lines[in_worker.OPS_LINE] for name, lines in planes.items()
        if name.startswith("/device:") and lines.get(in_worker.OPS_LINE)
    ]
    if not devices:
        return {}
    # the host annotations and the window, chosen as reduce_planes does
    host = sorted(
        (start, start + dur, name)
        for pname, lines in planes.items() if pname.startswith("/host:")
        for lname, events in lines.items()
        if lname == in_worker.HOST_LINE or in_worker.HOST_LINE not in lines
        for name, start, dur in events if dur > 0 and not name.startswith("$")
    )
    first = min(e[1] for events in devices for e in events)
    last = max(e[1] + e[2] for events in devices for e in events)
    if host:
        first, last = min(first, host[0][0]), max(last, max(h[1] for h in host))
    tiles = _tile(host, first, last)
    booked, kept = {}, []
    for events in devices:
        busy = in_worker._union([e[1], e[1] + e[2]] for e in events)
        edges = [first] + [t for pair in busy for t in pair] + [last]
        idle = [
            [edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        for start, end, name, _gap in _cut(idle, tiles):
            booked[name] = booked.get(name, 0.0) + (end - start) / 1e9
            if name in (WAIT, NO_SPAN):
                kept.append([start, end, name])
    n = len(devices)
    out = {"idle_swept": sorted(
        ([name[:64], seconds / n] for name, seconds in booked.items()),
        key=lambda kv: -kv[1],
    )}
    offset, spread = clock_offset(walls)
    if offset is not None:
        kept = sorted(kept, key=lambda p: p[0] - p[1])[:in_worker.NAMED_GAPS]
        out["clock_offset_spread_ms"] = spread / 1e6
        out["idle_pieces"] = sorted(
            [(start + offset) / 1e9, (end + offset) / 1e9, name]
            for start, end, name in kept
        )
    return out


def idle_by_host(device_trace, timelines, top=IDLE_BY_HOST):
    """The whole sweep by host, ``[[name, seconds], ...]``, the ``top``
    largest: the worker's annotations as ``worker:<name>``, and the pieces
    booked to ``wait_for_work`` or to no annotation booked once more, on the
    wall clock, to the innermost controller or client span of
    ``timelines`` that covers them (``controller:<span>``,
    ``client:<span>``) or to ``between``.  What the piece list left out
    stays with its worker annotation.  Seconds are a mean over the device
    planes.  None without a sweep."""
    swept = (device_trace or {}).get("idle_swept")
    if swept is None:
        return None
    table = {f"worker:{name}": seconds for name, seconds in swept}
    pieces = device_trace.get("idle_pieces") or []
    if pieces:
        n = device_trace.get("device_planes") or 1
        host = sorted(
            (s["start_ts"], s["start_ts"] + s["duration_s"],
             ("controller:" if s["name"] in CONTROLLER_SPANS else "client:") + s["name"])
            for t in timelines for s in t.get("spans", [])
            if s["name"] in CONTROLLER_SPANS or s["name"] in CLIENT_SPANS
        )
        first, last = pieces[0][0], max(p[1] for p in pieces)
        tiles = _tile([h for h in host if h[1] > first and h[0] < last], first, last)
        for lo, hi, span, piece in _cut(pieces, tiles):
            span = "between" if span == NO_SPAN else span
            table[span] = table.get(span, 0.0) + (hi - lo) / n
            table[f"worker:{piece[2]}"] -= (hi - lo) / n
    ranked = sorted(table.items(), key=lambda kv: -kv[1])
    return [[name, seconds] for name, seconds in ranked[:top] if seconds > 0]


def trace_idle_booked(ev, name=WAIT, scale=1000.0):
    """Reader: the sweep's idle seconds booked to one worker annotation,
    per query of the traced slice (a query that lies partly in the slice
    counts by that part), in ms.  None without a sweep."""
    trace = ev.get("device_trace") or {}
    swept = trace.get("idle_swept")
    queries = sum(readers._slice_share(ev, r) for r in ev["records"])
    if swept is None or not queries:
        return None
    return dict(swept).get(name, 0.0) / queries * scale
