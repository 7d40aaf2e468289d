"""Compile & device profiling: the two TPU costs PR 2's tracing can't see.

The wall that dominates a cold query is XLA compilation (seconds to minutes
per program shape, ``ops/__init__.py``'s persistent cache notwithstanding),
and the resource that silently kills a hot one is device memory.  Neither
shows up in span waterfalls: a compile hides inside the first ``kernel``
span of its shape, and HBM pressure shows up only as an eventual
RESOURCE_EXHAUSTED.  This module makes both first-class:

* :func:`instrument` wraps a jitted entry point (``ops/groupby.py``'s
  partial-table programs, ``parallel/executor.py``'s mesh program).  Every
  top-level call is accounted against the jit cache (`hit` when the traced
  program was reused, `miss` when the call compiled — detected by cache-size
  growth), compile walls land in a fixed-bucket histogram, and each new
  program shape gets a registry entry carrying ``lower().cost_analysis()``
  FLOPs / bytes-accessed (host-side HLO cost analysis — deliberately NOT
  ``lower().compile().cost_analysis()``, which would pay a second backend
  compile per shape for the same numbers).
* persistent-compile-cache hits/misses are counted via ``jax.monitoring``
  event listeners (the channel ``jax._src.compiler`` reports on), so the
  fleet-warming story of the disk cache is measurable, not assumed.
* :meth:`ProgramProfiler.bind` exposes it all on a node's
  :class:`~bqueryd_tpu.obs.metrics.MetricsRegistry`, including HBM-watermark
  gauges sampled from ``device.memory_stats()`` — read at scrape time from
  devices cached AFTER a successful kernel call, so a metrics scrape can
  never be the thing that first touches (and hangs on) an unresponsive
  accelerator.

The profiler is process-global (one XLA backend, one persistent cache per
process), unlike the per-node registries: in-process test clusters share it,
which :meth:`MetricsRegistry.register` makes explicit by adopting the same
metric instances into several registries.

Control-plane module at import time: stdlib only; JAX is imported lazily
inside the call paths that only jax-owning processes reach.
"""

import os
import threading
import time

from bqueryd_tpu.obs import metrics as metrics_mod

#: registry entries kept; least-recently-called evicted past this
MAX_PROGRAMS = 256

#: jax.monitoring event names for the persistent compilation cache
_PERSISTENT_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_PERSISTENT_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def profiling_enabled():
    """Compile profiling on/off (read per call: live-tunable).  Rides the
    same hot path as span recording, so ``BQUERYD_TPU_METRICS=0`` disables
    it too (checked by the caller via ``obs.enabled()``)."""
    return os.environ.get("BQUERYD_TPU_COMPILE_PROFILE", "1") != "0"


def cost_analysis_enabled():
    """Whether a compile event also runs host-side HLO cost analysis (one
    re-trace + lowering per NEW shape — milliseconds, but gated anyway)."""
    return os.environ.get("BQUERYD_TPU_COST_ANALYSIS", "1") != "0"


def _under_trace(args, kwargs):
    """True when any argument leaf is a tracer: an instrumented inner
    program (e.g. ``partial_tables`` inlined into the mesh program's
    shard_map body) is being TRACED, not dispatched — it must pass straight
    through, there is no real call or compile to account."""
    import jax

    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def _shape_signature(name, args, kwargs):
    """Stable per-shape key: abstract (dtype[shape]) per array leaf, repr for
    static values — what the jit cache itself keys on, human-readable."""
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            dims = ",".join(str(d) for d in leaf.shape)
            parts.append(f"{leaf.dtype}[{dims}]")
        else:
            parts.append(repr(leaf)[:48])
    return f"{name}({';'.join(parts)})"


class ProgramProfiler:
    """Process-wide compile/device profile state (see module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compile_seconds = metrics_mod.Histogram(
            "bqueryd_tpu_compile_seconds",
            "wall of jitted calls that compiled a new program "
            "(compile + first run)",
        )
        self.jit_cache_hits = 0
        self.jit_cache_misses = 0
        self.persistent_cache_hits = 0
        self.persistent_cache_misses = 0
        self.programs = {}        # signature -> registry entry dict
        self.programs_evicted = 0
        self._call_seq = 0        # recency order for eviction/snapshot
                                  # (wall timestamps tie at sub-ms cadence)
        self._monitoring_hooked = False
        self._devices = None      # cached jax.local_devices(), post-success

    # -- jax.monitoring bridge ----------------------------------------------
    def _ensure_monitoring(self):
        """Register persistent-cache listeners once per process.  Lazy (on
        the first compile event) so jax-free processes never import jax."""
        if self._monitoring_hooked:
            return
        self._monitoring_hooked = True
        try:
            import jax.monitoring

            def _event(event, *args, **kwargs):
                if event == _PERSISTENT_HIT_EVENT:
                    with self._lock:
                        self.persistent_cache_hits += 1
                elif event == _PERSISTENT_MISS_EVENT:
                    with self._lock:
                        self.persistent_cache_misses += 1

            jax.monitoring.register_event_listener(_event)
        except Exception:
            pass  # old jax without monitoring: counters just stay 0

    # -- per-call accounting -------------------------------------------------
    def record_call(self, name, jitted, args, kwargs, compiled, duration_s,
                    signature=None):
        if signature is None:
            signature = _shape_signature(name, args, kwargs)
        cost = None
        if compiled:
            self._ensure_monitoring()
            self.compile_seconds.observe(duration_s)
            cost = self._cost_analysis(jitted, args, kwargs)
        now = time.time()
        with self._lock:
            if compiled:
                self.jit_cache_misses += 1
            else:
                self.jit_cache_hits += 1
            entry = self.programs.get(signature)
            if entry is None:
                entry = self.programs[signature] = {
                    "name": name,
                    "signature": signature,
                    "calls": 0,
                    "compiles": 0,
                    "jit_cache_hits": 0,
                    "total_compile_s": 0.0,
                    "last_compile_s": None,
                    "flops": None,
                    "bytes_accessed": None,
                    "first_ts": round(now, 3),
                    # stamped before the eviction scan below: a new entry
                    # missing its recency marker would min() as the oldest
                    # and evict ITSELF, freezing the registry at the first
                    # MAX_PROGRAMS shapes ever seen
                    "_seq": self._call_seq + 1,
                }
                while len(self.programs) > MAX_PROGRAMS:
                    oldest = min(
                        self.programs.values(),
                        key=lambda e: e.get("_seq", 0),
                    )
                    self.programs.pop(oldest["signature"], None)
                    self.programs_evicted += 1
            self._call_seq += 1
            entry["calls"] += 1
            entry["_seq"] = self._call_seq
            entry["last_call_ts"] = round(now, 3)
            if compiled:
                entry["compiles"] += 1
                entry["last_compile_s"] = round(duration_s, 4)
                entry["total_compile_s"] = round(
                    entry["total_compile_s"] + duration_s, 4
                )
                if cost:
                    entry.update(cost)
            else:
                entry["jit_cache_hits"] += 1

    @staticmethod
    def _cost_analysis(jitted, args, kwargs):
        """FLOPs / bytes for one program shape via host-side HLO cost
        analysis on the re-traced lowering (no backend compile)."""
        if not cost_analysis_enabled():
            return None
        try:
            cost = jitted.lower(*args, **kwargs).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            return {
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            }
        except Exception:
            return None

    # -- device memory -------------------------------------------------------
    def note_devices(self):
        """Cache the local device list AFTER a successful kernel call — the
        only moment it is provably safe to enumerate devices without risking
        a first backend touch that hangs on an unresponsive accelerator."""
        if self._devices is None:
            try:
                import jax

                self._devices = list(jax.local_devices())
            except Exception:
                pass

    def device_memory(self):
        """Per-device ``memory_stats()`` snapshots (may be empty: backend
        not yet proven alive, or a backend without stats, e.g. CPU)."""
        out = []
        for i, dev in enumerate(self._devices or ()):
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if stats:
                out.append(
                    {
                        "device": i,
                        "kind": getattr(dev, "device_kind", "?"),
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit"),
                    }
                )
        return out

    def device_facts(self):
        """What this process computes on, as JAX reports it: ``platform``,
        ``device_kind``, device ``count`` and the per-device memory
        snapshots — or None until a kernel call has proven the backend
        alive (:meth:`note_devices`).  Rides the worker's debug slice, so a
        client can tell a TPU worker from one that resolved another
        backend."""
        devices = self._devices
        if not devices:
            return None
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            "memory": self.device_memory(),
        }

    def memory_sample(self):
        """Fleet-of-local-devices summary for gauges and span attribution:
        ``{"bytes_in_use": sum, "peak_bytes_in_use": max, "bytes_limit":
        sum}`` — or None when no device reports stats."""
        per_device = self.device_memory()
        if not per_device:
            return None
        return {
            "bytes_in_use": sum(d["bytes_in_use"] or 0 for d in per_device),
            "peak_bytes_in_use": max(
                d["peak_bytes_in_use"] or 0 for d in per_device
            ),
            "bytes_limit": sum(d["bytes_limit"] or 0 for d in per_device),
        }

    def _memory_gauge(self, key):
        def read():
            sample = self.memory_sample()
            return float("nan") if sample is None else float(sample[key] or 0)

        return read

    # -- export --------------------------------------------------------------
    def snapshot(self, max_programs=32):
        """JSON-safe state for WRM debug snapshots / the debug bundle.
        Programs capped to the ``max_programs`` most recently called."""
        with self._lock:
            programs = sorted(
                (dict(e) for e in self.programs.values()),
                key=lambda e: e.get("_seq", 0),
                reverse=True,
            )[:max_programs]
            return {
                "jit_cache_hits": self.jit_cache_hits,
                "jit_cache_misses": self.jit_cache_misses,
                "persistent_cache_hits": self.persistent_cache_hits,
                "persistent_cache_misses": self.persistent_cache_misses,
                "programs_tracked": len(self.programs),
                "programs_evicted": self.programs_evicted,
                "compile_seconds": self.compile_seconds.snapshot(),
                "programs": programs,
            }

    def bind(self, registry):
        """Expose the profiler on a node's registry.  The histogram is the
        SAME instance across every bound registry (process-global compiles);
        counters/gauges are fn-backed reads of the shared state."""
        registry.register(self.compile_seconds)
        for name, help_text, fn in (
            (
                "bqueryd_tpu_jit_cache_hits",
                "instrumented jitted calls served by an already-compiled "
                "program (monotonic)",
                lambda: self.jit_cache_hits,
            ),
            (
                "bqueryd_tpu_jit_cache_misses",
                "instrumented jitted calls that compiled a new program "
                "(monotonic)",
                lambda: self.jit_cache_misses,
            ),
            (
                "bqueryd_tpu_persistent_cache_hits",
                "XLA persistent compile-cache hits (monotonic)",
                lambda: self.persistent_cache_hits,
            ),
            (
                "bqueryd_tpu_persistent_cache_misses",
                "XLA persistent compile-cache misses (monotonic)",
                lambda: self.persistent_cache_misses,
            ),
            (
                "bqueryd_tpu_device_bytes_in_use",
                "device memory in use, summed over local devices",
                self._memory_gauge("bytes_in_use"),
            ),
            (
                "bqueryd_tpu_device_peak_bytes_in_use",
                "high-watermark device memory across local devices",
                self._memory_gauge("peak_bytes_in_use"),
            ),
            (
                "bqueryd_tpu_device_bytes_limit",
                "device memory capacity, summed over local devices",
                self._memory_gauge("bytes_limit"),
            ),
        ):
            registry.gauge(name, help_text, fn=fn)


_profiler = ProgramProfiler()


def profiler():
    """The process-global :class:`ProgramProfiler`."""
    return _profiler


def _reset_for_tests():
    """Test seam: fresh process-global profiler state."""
    global _profiler
    _profiler = ProgramProfiler()
    return _profiler


def instrument(name, jitted, signature_args=None):
    """Wrap a jitted callable with compile/call accounting.

    Transparent when: profiling or the obs hot path is disabled, the call
    happens under an outer jax trace (tracer args), or the wrapped object
    does not expose a jit cache.  The wrapper never lets accounting raise
    into the query path.

    ``signature_args(*args, **kwargs)`` gives what the shape signature is
    made from in place of the call's own arguments: for a program whose
    TRACED arguments include Python scalars, which the jit cache keys by
    type and the default signature by value (one registry entry per
    constant)."""
    # signatures THIS wrapper has already seen compiled: cache-size growth
    # alone is racy when several threads share one jitted function (an
    # in-process cluster), where thread A's compile of shape X lands inside
    # thread B's before/after window for already-compiled shape Y and would
    # misclassify B's call as a ~0s compile — a seen signature is never
    # re-counted as one
    seen_sigs = set()

    def wrapped(*args, **kwargs):
        from bqueryd_tpu import obs

        cache_size = getattr(jitted, "_cache_size", None)
        if (
            cache_size is None
            or not profiling_enabled()
            or not obs.enabled()
            or _under_trace(args, kwargs)
        ):
            return jitted(*args, **kwargs)
        try:
            before = cache_size()
        except Exception:
            return jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = jitted(*args, **kwargs)
        duration = time.perf_counter() - t0
        try:
            signature = _shape_signature(name, *(
                (args, kwargs) if signature_args is None
                else (signature_args(*args, **kwargs), {})
            ))
            compiled = cache_size() > before and signature not in seen_sigs
            if len(seen_sigs) > 4096:  # pathological shape drift backstop
                seen_sigs.clear()
            seen_sigs.add(signature)
            _profiler.record_call(
                name, jitted, args, kwargs,
                compiled=compiled,
                duration_s=duration,
                signature=signature,
            )
        except Exception:
            pass  # accounting must never fail the query
        return out

    wrapped.__name__ = name.rsplit(".", 1)[-1]
    wrapped.__wrapped__ = jitted
    return wrapped


# -- environment facts (stdlib-only: controller processes report these too) --

_runtime_versions = None


def runtime_versions():
    """Installed jax/jaxlib/libtpu/numpy versions via package metadata — no
    import of jax itself, so a controller (or a worker whose backend is
    wedged inside native code) can always answer.  Memoized: installed
    versions cannot change under a running process."""
    global _runtime_versions
    if _runtime_versions is None:
        from importlib import metadata

        out = {}
        for pkg in ("jax", "jaxlib", "libtpu", "libtpu-nightly", "numpy"):
            try:
                out[pkg] = metadata.version(pkg)
            except Exception:
                continue
        _runtime_versions = out
    return dict(_runtime_versions)


def compile_cache_info():
    """The persistent-compile-cache decision as facts: enabled?, resolved
    path, writable?  A process that has imported ``jax`` (a calc worker:
    ``ops/__init__.py`` placed the cache at import) reports what
    ``jax.config`` holds; a JAX-free process (controller, client) has no
    compile cache and says so — it never imports jax to find out."""
    import sys

    # getattr: another thread may be mid-``import jax`` (worker warm-up)
    config = getattr(sys.modules.get("jax"), "config", None)
    path = None
    if config is not None and config.jax_enable_compilation_cache:
        path = config.jax_compilation_cache_dir or None
    return {
        "enabled": path is not None,
        "path": path,
        "writable": path is not None
        and os.path.isdir(path) and os.access(path, os.W_OK),
    }
