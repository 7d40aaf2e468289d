"""Accelerator-backend health: detect a wedged device without ever hanging.

The failure mode this exists for: an accelerator that stops answering makes
``jax.devices()`` (or any dispatch) block forever INSIDE native code — no
signal can interrupt it, so any thread that touches the device is lost.
The reference stack never had this problem (its compute was host-only,
reference bqueryd/worker.py); a framework whose hot path is an accelerator
needs an answer or a single unresponsive device wedges every worker loop
that routes a query to it.

Strategy: all device liveness questions are answered by SACRIFICIAL daemon
threads.  A probe thread runs one trivial jitted dispatch + fetch; the
asking thread waits at most a deadline and never joins the probe — a hung
probe thread parks on the dead backend forever (daemon: it cannot block
process exit) while callers see the backend latched as wedged.  Routing
then sends every query the host kernels can serve to the host
(:func:`bqueryd_tpu.models.query.host_kernel_rows` returns its cap), and
device-only queries fail fast with a clear error instead of hanging the
worker loop.  A later successful probe unlatches, so a recovered device
resumes serving without a restart.

At most one probe is ever in flight; a wedged backend costs one parked
thread per probe attempt, rate-limited to the recheck interval.

The survival paths answer a query RIGHT from somewhere else (host kernels,
the per-shard engine, a per-leaf fetch), which is exactly what makes them
invisible: :func:`note_degrade` counts every time one fires, per site, and
the counts ride the worker's debug slice next to :func:`health_snapshot` —
a client (and ``chip_smoke.py``) can see that the device path was left.
"""

import os
import threading
import time

_lock = threading.Lock()
_wedged = False
_probe_started = None     # monotonic start of the in-flight probe, or None
_last_probe_start = 0.0   # start of the most recent probe, any outcome
_abandoned = 0            # probes written off as hung since the last success
_generation = 0           # incremented on every not-wedged -> wedged flip

#: the places a query leaves the device path it was routed to and is still
#: answered; every firing is counted by note_degrade
DEGRADE_SITES = (
    "mesh_to_engine",      # mesh program failed -> per-shard engine
    "dag_to_pershard",     # DAG mesh program failed -> per-shard pipelines
    "bundle_to_members",   # bundle mesh program failed -> member at a time
    "inplace_retry",       # transient device error retried in place
    "packed_to_perleaf",   # packed fetch failed -> per-leaf device_get
    "packed_latched",      # packed fetch latched off for the process
)
_degrades = dict.fromkeys(DEGRADE_SITES, 0)

#: past this many parked probe threads, relaunch only every 10 intervals —
#: a permanently dead backend must not grow a thread per interval forever
_MAX_ABANDONED_FAST = 16


def probe_timeout_s():
    """Deadline for one trivial dispatch + fetch.  Generous: a first
    compile of even ``x + 1`` behind a cold backend takes seconds, and a
    real wedge hangs for minutes — 60 s cleanly separates the two.  ``0``
    disables wedge detection entirely (no probes, never latched): for
    benchmarks or debugging where a hang is preferable to a silent host
    fallback."""
    return float(os.environ.get("BQUERYD_TPU_DEVICE_PROBE_TIMEOUT_S", 60))


def _recheck_interval_s():
    return float(
        os.environ.get("BQUERYD_TPU_DEVICE_PROBE_INTERVAL_S", 30)
    )


def _default_probe():
    """One trivial jitted dispatch + host fetch on the default backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    np.asarray(jax.jit(lambda x: x + 1)(jnp.zeros(())))


#: test seam: replaced to simulate a wedged backend without real hangs
_probe_fn = _default_probe


def _latch_locked():
    """Set the latch (under _lock) and bump the generation on the
    not-wedged -> wedged transition — the single place the rule lives."""
    global _wedged, _generation
    if not _wedged:
        _generation += 1
    _wedged = True


def _probe_body(my_start):
    global _probe_started, _wedged, _abandoned
    try:
        _probe_fn()
    except Exception:
        # a probe that ERRORS (backend gone vs hung) still answered within
        # the deadline, but the device is unusable: latch wedged; the
        # interval clock keeps re-probes coming so recovery is automatic
        with _lock:
            if _probe_started == my_start:
                _probe_started = None
            _latch_locked()
        return
    with _lock:
        # an abandoned probe that finally returns after the device
        # recovers is still good news: any success unlatches
        if _probe_started == my_start:
            _probe_started = None
        _wedged = False
        _abandoned = 0


def _start_probe_locked():
    global _probe_started, _last_probe_start
    _probe_started = _last_probe_start = time.monotonic()
    threading.Thread(
        target=_probe_body,
        args=(_probe_started,),
        name="bqueryd-device-probe",
        daemon=True,
    ).start()


def backend_wedged(launch=True):
    """Whether the default backend is currently latched as wedged.

    Never blocks: state transitions ride the background probes.  An
    in-flight probe past the deadline flips the latch AND writes the probe
    off as hung, so the interval clock keeps launching fresh probes — a
    recovered device unlatches within interval + one dispatch even though
    the original hung thread never returns.  Past ``_MAX_ABANDONED_FAST``
    written-off probes the relaunch cadence drops to every 10 intervals
    (a permanently dead backend must not leak a thread per interval).

    ``launch=False`` reads the latch without ever starting a probe: for
    callers in processes that may have no device intent at all (e.g. the
    routing threshold under an operator env pin), where spawning a JAX
    dispatch thread as a side effect would be wrong.  Such processes can
    only see the latch set by their own failed device calls — which is
    exactly the right scope."""
    global _probe_started, _abandoned
    if probe_timeout_s() <= 0:
        return False  # detection disabled: never latched, no probes
    now = time.monotonic()
    with _lock:
        if _probe_started is not None:
            if now - _probe_started > probe_timeout_s():
                _latch_locked()
                # write the hung probe off so the clock can relaunch
                _probe_started = None
                _abandoned += 1
        elif launch:
            interval = _recheck_interval_s()
            if _abandoned >= _MAX_ABANDONED_FAST:
                interval *= 10
            if now - _last_probe_start > interval:
                _start_probe_locked()
        return _wedged


def run_with_deadline(fn, timeout_s):
    """Run ``fn`` in a sacrificial daemon thread; return ``(done, result)``.

    ``done`` is False when the deadline passed — the thread is abandoned
    (parked on the dead backend), never joined, and its eventual result is
    discarded.  Exceptions inside ``fn`` count as done with result None."""
    box = {}
    ev = threading.Event()

    def body():
        try:
            box["result"] = fn()
        except Exception:
            box["result"] = None
        finally:
            ev.set()

    threading.Thread(target=body, daemon=True).start()
    if ev.wait(timeout_s):
        return True, box.get("result")
    return False, None


def latch_wedged():
    """Latch the backend as wedged on direct evidence (a device call that
    blew its deadline, e.g. the dispatch-floor measurement).  The interval
    clock keeps probing, so recovery stays automatic."""
    with _lock:
        _latch_locked()


def wedge_marker():
    """Snapshot for evidence windows: ``(generation, currently_wedged)``.
    A measurement window is CLEAN iff the marker is identical before and
    after AND neither end is wedged — a transient wedge that recovered
    mid-window bumps the generation even though both endpoint reads of
    ``backend_wedged`` say False."""
    with _lock:
        return (_generation, _wedged)


def window_dirty(start_marker, end_marker=None):
    """Whether a wedge overlapped the window between two markers."""
    if end_marker is None:
        end_marker = wedge_marker()
    return (
        start_marker != end_marker or start_marker[1] or end_marker[1]
    )


def health_snapshot():
    """Gauge-friendly state for the observability registry: read-only (never
    launches a probe — metric scrapes must not spawn device dispatch threads
    as a side effect).  ``{"wedged": 0/1, "abandoned_probes": n,
    "wedge_generation": n}``."""
    with _lock:
        return {
            "wedged": 1 if _wedged else 0,
            "abandoned_probes": _abandoned,
            "wedge_generation": _generation,
        }


def note_degrade(site):
    """Count one firing of a degrade site (``DEGRADE_SITES``)."""
    with _lock:
        _degrades[site] += 1


def degrade_counts():
    """``{site: times fired}`` for every degrade site, zeros included —
    process-lifetime, read-only."""
    with _lock:
        return dict(_degrades)


def force_state(wedged):
    """Test seam: pin the latch without probing (also resets the interval
    clock so the next ``backend_wedged`` call does not immediately launch
    a real probe under a pinned state)."""
    global _wedged, _probe_started, _last_probe_start, _abandoned
    with _lock:
        _wedged = bool(wedged)
        _probe_started = None
        _last_probe_start = time.monotonic()
        _abandoned = 0
