"""What a traced run executes inside the worker, the only process that
holds the chip, through the program's own ``rpc.execute_code`` verb:
``start`` / ``stop`` bracket a slice with ``jax.profiler``, ``stop`` reduces
the ``.xplane.pb`` to busy time, per-op time and named idle gaps, and
``counters`` reads the compile registry and the device's memory.

``reduce_planes`` is plain Python over plain lists, so a test can hold it
against a small recorded trace.
"""

import bisect
import glob
import os
import time

_started = {}

#: a device plane's line that holds one event per executed XLA op
OPS_LINE = "XLA Ops"
HOST_LINE = "python"
MAX_EVENTS_PER_LINE = 400_000
BREAKDOWN_ENTRIES = 10
#: gaps attributed to a host span one by one; the rest go under "short_gaps"
NAMED_GAPS = 400


def start(logdir):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the program's own annotations are enough
    jax.profiler.start_trace(logdir, profiler_options=options)
    _started[logdir] = time.time()
    return _started[logdir]


def stop(logdir, dump=None):
    import jax

    jax.profiler.stop_trace()
    stopped = time.time()
    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        return {"error": f"no .xplane.pb under {logdir}"}
    planes = load_planes(max(paths, key=os.path.getmtime))
    if dump:
        import json

        with open(dump, "w") as f:
            json.dump(planes, f)
    out = reduce_planes(planes)
    out["slice_s"] = stopped - _started.pop(logdir, stopped)
    return out


def load_planes(path):
    """``{plane: {line: [[name, start_ns, duration_ns], ...]}}``."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for event in line.events:
                if len(events) >= MAX_EVENTS_PER_LINE:
                    break
                events.append([event.name, int(event.start_ns), int(event.duration_ns)])
    return planes


def _union(intervals):
    """Merged, sorted ``[start, end]`` list."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce_planes(planes):
    """Busy seconds (union of op intervals, mean over the device planes),
    the traced window, the ops that took most time, and the idle gaps by
    the innermost host span that covers each gap's middle."""
    devices = {
        name: lines[OPS_LINE] for name, lines in planes.items()
        if name.startswith("/device:") and lines.get(OPS_LINE)
    }
    if not devices:
        return {"device_planes": 0}
    # the worker's Python thread carries the program's own annotations
    # (align, layout, aggregate ...); "$..." events are the Python tracer's
    host = sorted(
        (start, start + dur, name)
        for pname, lines in planes.items() if pname.startswith("/host:")
        for lname, events in lines.items() if lname == HOST_LINE or HOST_LINE not in lines
        for name, start, dur in events if dur > 0 and not name.startswith("$")
    )
    host_starts = [h[0] for h in host]
    first = min(e[1] for ev in devices.values() for e in ev)
    last = max(e[1] + e[2] for ev in devices.values() for e in ev)
    if host:
        first, last = min(first, host[0][0]), max(last, max(h[1] for h in host))
    busy, op_seconds, gap_seconds = 0.0, {}, {}
    for events in devices.values():
        merged = _union([e[1], e[1] + e[2]] for e in events)
        busy += sum(end - start for start, end in merged) / 1e9
        for name, _start, dur in events:
            op_seconds[name] = op_seconds.get(name, 0.0) + dur / 1e9
        edges = [first] + [t for pair in merged for t in pair] + [last]
        gaps = sorted(
            ((edges[i + 1] - edges[i], edges[i]) for i in range(0, len(edges), 2)),
            reverse=True,
        )
        for rank, (length, start) in enumerate(gaps):
            if length <= 0:
                continue
            name = "short_gaps"
            if rank < NAMED_GAPS:
                name = _innermost(host, host_starts, start + length // 2)
            gap_seconds[name] = gap_seconds.get(name, 0.0) + length / 1e9
    n = len(devices)

    def top(table):
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:BREAKDOWN_ENTRIES]
        return [[name[:64], seconds / n] for name, seconds in ranked]

    return {
        "device_planes": n,
        "busy_s": busy / n,
        "window_s": (last - first) / 1e9,
        "device_ops": top(op_seconds),
        "idle_gaps": top(gap_seconds),
    }


def _innermost(host, host_starts, when, look_back=2000):
    """The host span with the latest start that covers ``when``."""
    i = bisect.bisect_right(host_starts, when)
    for start, end, name in reversed(host[max(0, i - look_back):i]):
        if end >= when:
            return name
    return "no_host_span"


def counters():
    """The compile registry's counters and the fullest device's memory."""
    import jax

    from bqueryd_tpu.obs import profile

    snap = profile.profiler().snapshot()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    device = jax.local_devices()[0]
    return {
        "jit_cache_misses": snap.get("jit_cache_misses"),
        "jit_cache_hits": snap.get("jit_cache_hits"),
        "persistent_cache_hits": snap.get("persistent_cache_hits"),
        "persistent_cache_misses": snap.get("persistent_cache_misses"),
        "peak_bytes_in_use": max((s.get("peak_bytes_in_use") or 0) for s in stats),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.local_devices()),
    }
