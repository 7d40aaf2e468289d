"""Per-phase timing and profiler hooks.

The reference's only timing surface is a per-call wall clock on the client
(reference bqueryd/rpc.py:128-129).  The TPU build needs to attribute a query's
latency to its phases — storage decode, host→device transfer, kernel, and
collective merge — so workers attach a :class:`PhaseTimer` to every calc result
(surfaced in the reply under ``phase_timings``; schema documented in
:mod:`bqueryd_tpu.messages`).

Two levels.  The coarse phases are always on (:meth:`PhaseTimer.phase`).
:func:`detail` names what happens INSIDE and BETWEEN them, and exists only
under ``BQUERYD_TPU_PROFILE=1`` (the traced run's switch, the one
:func:`trace_span` obeys): a ``jax.profiler.TraceAnnotation`` on the device
trace's clock plus, where the timer carries a SpanRecorder, a span on the
``rpc.trace()`` timeline — never a ``phase_timings`` key, a debit or a
histogram, so every number read with the switch off reads the same with it
on.  With the switch unset a detail site costs one shared no-op context.

A PhaseTimer may carry an :class:`bqueryd_tpu.obs.trace.SpanRecorder`: each
phase then also records a distributed-tracing span (wall-clock start +
perf_counter duration), which is how worker phases reach the controller's
``rpc.trace(trace_id)`` waterfall without a second set of timing call sites.

All durations use ``time.perf_counter`` — including :meth:`PhaseTimer.total`
and the anchor it is measured from.  (``time.time()`` is NOT monotonic: an
NTP step used to make totals negative or smaller than the phase sum.)
"""

import contextlib
import os
import time

#: synthetic key added by :meth:`PhaseTimer.as_dict` — deliberately
#: underscore-namespaced so a real phase named ``total`` can never be
#: silently overwritten (see the reply schema note in messages.py)
TOTAL_KEY = "_total"


class PhaseTimer:
    """Accumulates named phase durations; phases may recur (times sum).

    ``recorder``/``span_names`` (optional): a SpanRecorder receiving one span
    per phase occurrence, names mapped through ``span_names`` (e.g.
    obs.trace.PHASE_SPAN_NAMES' ``open`` -> ``storage_decode``)."""

    def __init__(self, recorder=None, span_names=None):
        import threading

        self.timings = {}
        self.recorder = recorder
        self.span_names = span_names or {}
        self._started = time.perf_counter()
        # phases may now run CONCURRENTLY (the pipelined per-shard engine
        # path times every shard's phases into one timer); the lock keeps
        # the read-modify-write sum from losing updates.  Busy sums of
        # overlapped phases legitimately exceed the wall — that overlap is
        # exactly what bench.py's pipeline section measures.
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def phase(self, name):
        start_ts = time.time()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            duration = time.perf_counter() - t0
            with self._lock:
                self.timings[name] = self.timings.get(name, 0.0) + duration
            if self.recorder is not None:
                self.recorder.record(
                    self.span_names.get(name, name), start_ts, duration
                )

    def debit(self, name, seconds):
        """Subtract a SERIALLY-NESTED sub-phase's wall from its enclosing
        phase (e.g. the device→host ``fetch`` runs inside ``aggregate``):
        without the debit the same seconds bill twice — once per phase —
        in ``phase_timings`` and the per-phase histograms.  The enclosing
        phase's entry may not exist yet (it lands on context exit), so
        this accumulates a negative adjustment that the later sum nets
        out exactly.  Only for nested SERIAL work — genuinely concurrent
        phase overlap is a measured property, never debited."""
        with self._lock:
            self.timings[name] = self.timings.get(name, 0.0) - seconds

    def total(self):
        return time.perf_counter() - self._started

    def as_dict(self):
        out = dict(self.timings)
        out[TOTAL_KEY] = self.total()
        return out


def _annotation(name, args):
    """A ``jax.profiler.TraceAnnotation`` on the calling thread, or None
    where JAX cannot be imported.  Without a ``trace_id`` among ``args``
    the active distributed TraceContext (obs.trace contextvar) gives its
    own, so device profiler timelines line up with the RPC waterfall."""
    try:
        import jax.profiler
    except ImportError:
        return None
    if "trace_id" not in args:
        try:
            from bqueryd_tpu.obs.trace import current_trace

            ctx = current_trace()
            if ctx is not None:
                args["trace_id"] = ctx.trace_id
        except Exception:
            pass
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def trace_span(name):
    """A ``jax.profiler.TraceAnnotation`` span when JAX is importable and
    profiling is enabled via BQUERYD_TPU_PROFILE=1; otherwise a no-op.

    When a distributed TraceContext is active (obs.trace contextvar), the
    annotation is tagged with its ``trace_id`` so device profiler timelines
    line up with the RPC trace waterfall."""
    annotation = None
    if os.environ.get("BQUERYD_TPU_PROFILE") == "1":
        annotation = _annotation(name, {})
    if annotation is not None:
        with annotation:
            yield
    else:
        yield


#: what every detail site gets with the switch unset: ONE object, entered
#: and left, nothing else
_NO_DETAIL = contextlib.nullcontext()


def detail_enabled():
    """Whether this process runs under the traced run's switch (read per
    call): what every detail site, span or tag, asks first."""
    return os.environ.get("BQUERYD_TPU_PROFILE") == "1"


def detail(name, timer=None, **args):
    """A DETAIL span: exists only under BQUERYD_TPU_PROFILE=1 (read per
    call), else the shared no-op.  Under the switch it opens a
    ``jax.profiler.TraceAnnotation`` on the calling thread (``args`` ride
    it; the request's trace id is added) and, when ``timer`` carries a
    SpanRecorder, records ONE span — wall-clock start, ``perf_counter``
    duration, parent = the recorder's root like every phase span; it shows
    as nested by its interval.  It never touches
    ``timer.timings``, a debit or a histogram.  Loop thread only: a
    recorder's span list is not locked."""
    if not detail_enabled():
        return _NO_DETAIL
    return _detail(name, getattr(timer, "recorder", None), args)


@contextlib.contextmanager
def _detail(name, recorder, args):
    args = {k: v for k, v in args.items() if v is not None}
    if recorder is not None and recorder.trace_id:
        args.setdefault("trace_id", recorder.trace_id)
    annotation = _annotation(name, args) or _NO_DETAIL
    start_ts = time.time()
    t0 = time.perf_counter()
    try:
        with annotation:
            yield
    finally:
        if recorder is not None:
            recorder.record(name, start_ts, time.perf_counter() - t0)
