"""Device-resident working-set cache: codes + measure blocks + alignment.

What the mesh executor keeps between queries, each thing once and at the
width it is used, in three content-keyed LRU segments:

* ``align`` (host): dense group codes at the narrowest dtype holding the
  key set's group count, the global combos and the key dictionaries, per
  (table set, groupby columns).
* ``codes`` (device): the packed UNMASKED codes per (table set, groupby
  columns), and the host-folded codes of the filters that cannot fold on
  the device, per (table set, groupby columns, filter).  A filter that
  folds on the device caches nothing: the unmasked entry and the resident
  filter column are the state, the fold is a dispatch.
* ``blocks`` (device): packed wire-dtype measure columns, and the
  stored-dtype filter columns the device-side fold compares, per (table
  set, column).

Keys carry the shard identity (rootdir + meta.json inode/mtime + rows,
:func:`bqueryd_tpu.storage.ctable.table_cache_key`), so activation
invalidates naturally and a repeat query with a DIFFERENT measure or filter
still hits the codes/alignment segments.  Hit/miss/eviction counters are
exported as worker gauges (``bqueryd_tpu_workingset_*{segment=...}``).

**What bounds a segment** is memory the worker measures, by the shares in
:data:`SHARES`: the device segments by the ``bytes_limit`` of the profiler's
memory sample (``obs.profile.profiler().memory_sample()``, summed over the
local devices like a global array's ``nbytes``), the host segment by the
worker's ``memory_limit_mb`` — the number its RSS watchdog enforces.  Where
no device reports memory (the CPU backend; any backend before a first
kernel call has proven it alive) the "device" arrays are host memory, and
the device segments take their shares of the host limit until a sample
says otherwise; an executor built outside a worker has no watchdog and is
bounded by the machine's physical memory.
:meth:`WorkingSet.evict_under_pressure` sheds LRU device entries while the
sample's ``bytes_in_use`` sits above :data:`EVICT_WATERMARK` of the limit —
before the allocator hits RESOURCE_EXHAUSTED and ``DeviceHealth`` latches
the backend as wedged.

A :class:`WorkingSet` is per-executor (the worker owns one mesh executor),
not process-global: in-process test clusters and bench workers must not
bleed cached device blocks into each other, same per-node rule as the
metrics registries.
"""

import os

from bqueryd_tpu.utils.cache import BytesCappedCache

#: segments holding DEVICE buffers, in memory-pressure eviction order —
#: blocks first: they are the biggest and the cheapest to rebuild from the
#: still-cached host alignment
DEVICE_SEGMENTS = ("blocks", "codes")

#: each segment's share of the memory that bounds it (module docstring)
SHARES = {
    # the deployment's columns are what the device is for; the other half
    # holds the codes, the programs' scratch and the runtime
    "blocks": 1 / 2,
    # one entry a key set, 1-4 bytes a row where a column block is up to 8:
    # a quarter of the blocks' room holds as many key sets as columns
    "codes": 1 / 8,
    # the decoded-column cache (2 GiB), the factorize, result and delta
    # caches (under 1 GiB) and a cold query's packs share the other 3/4
    "align": 1 / 4,
}

#: shed device entries above this share of ``bytes_limit``.  Full segments
#: leave 3/8 of the device to a program's scratch (the f64 sort path's is
#: the largest: PERF.md §6, PR 30, says what it is on the chip); the last
#: tenth is for what the allocator cannot hand out in one piece
EVICT_WATERMARK = 0.9


def _physical_memory():
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def _device_nbytes(value):
    """Accounted size of a device array (jax.Array exposes nbytes)."""
    return getattr(value, "nbytes", 0)


class WorkingSet:
    """Named LRU cache segments + the device-memory-pressure eviction policy
    (module docstring).  ``host_limit_bytes`` is the worker's RSS limit;
    ``budgets`` fixes a segment's bytes whatever is measured (tests)."""

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    #: (lock-unguarded-attr).  ``_segments`` is read-only after __init__
    #: (the per-segment caches carry their own locks), so only the
    #: pressure-eviction counter is guarded.
    _bqtpu_guarded_ = {"_pressure_lock": ("pressure_evictions",)}

    def __init__(self, budgets=None, host_limit_bytes=None):
        import threading

        self._fixed = dict(budgets or {})
        host = host_limit_bytes or _physical_memory()
        self._segments = {
            name: BytesCappedCache(
                self._fixed.get(name, int(share * host)),
                sizeof=_device_nbytes,
            )
            for name, share in SHARES.items()
        }
        self.pressure_evictions = 0  # entries shed by the watermark policy
        self._pressure_lock = threading.Lock()

    def segment(self, name):
        return self._segments[name]

    def clear(self):
        for cache in self._segments.values():
            cache.clear()

    def stats(self):
        """Per-segment counters + the pressure-eviction total (JSON-safe,
        feeds the worker gauges and bench's ``pipeline`` section)."""
        out = {
            name: cache.stats() for name, cache in self._segments.items()
        }
        with self._pressure_lock:
            out["pressure_evictions"] = self.pressure_evictions
        return out

    # -- memory pressure -----------------------------------------------------
    def evict_under_pressure(self, sample=None, watermark=EVICT_WATERMARK):
        """Bound the device segments by the sample's ``bytes_limit`` and
        shed their LRU entries while its ``bytes_in_use`` sits above the
        watermark.  ``sample`` is a ``{"bytes_in_use", "bytes_limit", ...}``
        dict (default: the live profiler sample; None — CPU backends, a
        backend no kernel call has proven alive yet — is a no-op).  Returns
        bytes freed (accounted cache bytes, a proxy for the HBM the dropped
        references release at the allocator's next sweep).

        Eviction order is ``blocks`` before ``codes``: measure blocks are
        the bulk of residency and rebuild from the still-cached host
        alignment with one decode+H2D, while codes rebuilding also re-runs
        mask folding."""
        if sample is None:
            from bqueryd_tpu.obs import profile

            sample = profile.profiler().memory_sample()
        if not sample:
            return 0
        limit = sample.get("bytes_limit") or 0
        in_use = sample.get("bytes_in_use") or 0
        if limit <= 0:
            return 0
        for name in DEVICE_SEGMENTS:
            if name not in self._fixed:
                self._segments[name].max_bytes = int(SHARES[name] * limit)
        if in_use <= watermark * limit:
            return 0
        target = int(in_use - watermark * limit)
        freed = 0
        for name in DEVICE_SEGMENTS:
            cache = self._segments[name]
            seg_freed, seg_count = cache.evict_bytes(target - freed)
            freed += seg_freed
            with self._pressure_lock:
                self.pressure_evictions += seg_count
            if freed >= target:
                break
        if freed:
            import logging

            logging.getLogger("bqueryd_tpu").info(
                "HBM watermark pressure: shed %d cached device bytes "
                "(in_use %d > %.0f%% of limit %d)",
                freed, in_use, watermark * 100, limit,
            )
        return freed


# -- delta-maintained hot aggregates (streaming ingest) ---------------------
#
# The serving-layer upgrade of the working set: a cached groupby result for
# a shard group whose ctables only GREW (the streaming-append signature) is
# refreshed by running the kernels over the appended chunks alone and
# merging the delta partial into the cached partial through the same
# value-keyed hostmerge forms every cross-shard merge uses — sum/count/
# count_na/min/max merge exactly, mean merges through its (sum, count)
# partials.  Non-mergeable shapes (distinct counts, basket expansion, raw
# rows) never enter; the existing identity-keyed (meta inode + row count)
# invalidation of every other cache remains the correctness backstop: any
# non-append change (reshard, activation, rewrite) fails the chunk-prefix
# validation below and drops the entry to a full recompute.

def delta_serve_enabled():
    """Delta maintenance kill switch (``BQUERYD_TPU_DELTA_SERVE``,
    default on)."""
    return os.environ.get("BQUERYD_TPU_DELTA_SERVE", "1") == "1"


def _delta_budget():
    try:
        return int(
            os.environ.get(
                "BQUERYD_TPU_DELTA_CACHE_BYTES", 128 * 1024**2
            )
        )
    except ValueError:
        return 128 * 1024**2


def table_growth_base(table):
    """The append-diff base of one table INSTANCE: its committed per-column
    chunk indexes + row count, captured from the snapshot the computation
    actually read.  None when the table exposes no committed chunk grid
    (legacy formats, torn state) — such tables never delta-serve."""
    committed = getattr(table, "committed_chunks", None)
    if committed is None:
        return None
    cols = {}
    for name in table.names:
        snap = committed(name)
        if snap is None:
            return None
        cols[name] = [dict(c) for c in snap]
    return {
        "rows": int(table.nrows),
        "names": list(table.names),
        "cols": cols,
    }


def growth_since(base, table):
    """The NEW committed chunk ids of ``table`` relative to ``base``
    (possibly empty), or None when the table is not an append-only growth
    of the base.  Validation is exact: the base's chunk dicts (offset,
    csize, crc, zone map) must be a verbatim prefix of the current index
    for EVERY column — any rewrite mismatches and the caller recomputes."""
    if base is None or not isinstance(base, dict):
        return None
    committed = getattr(table, "committed_chunks", None)
    if committed is None:
        return None
    if list(table.names) != base.get("names"):
        return None
    if int(table.nrows) < base.get("rows", 0):
        return None
    new_ids = None
    grown_rows = None
    for name, bchunks in base.get("cols", {}).items():
        cur = committed(name)
        if cur is None or len(cur) < len(bchunks):
            return None
        if cur[: len(bchunks)] != bchunks:
            return None
        ids = list(range(len(bchunks), len(cur)))
        rows = sum(int(c["nrows"]) for c in cur[len(bchunks):])
        if new_ids is None:
            new_ids, grown_rows = ids, rows
        elif ids != new_ids or rows != grown_rows:
            return None  # desynchronized chunk grid: not a clean append
    if new_ids is None:
        new_ids, grown_rows = [], 0
    if grown_rows != int(table.nrows) - base["rows"]:
        return None
    return new_ids


class DeltaAggCache:
    """Byte-bounded cache of delta-maintainable aggregate results.

    Entries are keyed by (table identity tuple, query signature) —
    supplied by the worker — and hold the serialized merged
    :class:`~bqueryd_tpu.models.query.ResultPayload` plus the growth base
    of every table it covers.  ``refresh_ids`` validates a later lookup
    against live tables and names the appended chunks to re-aggregate."""

    def __init__(self, max_bytes=None):
        self._cache = BytesCappedCache(
            _delta_budget() if max_bytes is None else max_bytes
        )
        #: cached results refreshed by aggregating only appended chunks
        self.refreshes = 0
        #: rows the delta kernels aggregated instead of the full tables
        self.delta_rows = 0

    def get(self, key):
        return self._cache.get(key)

    def discard(self, key):
        self._cache.delete(key)

    def store(self, key, tables, data):
        """Record ``data`` (serialized payload bytes) as the delta base for
        ``tables`` — a no-op when any table exposes no growth base."""
        bases = [table_growth_base(t) for t in tables]
        if any(b is None for b in bases):
            return False
        # refreshes REPLACE the entry (put() keeps an existing key)
        self._cache.delete(key)
        self._cache.put(
            key, {"bases": bases, "data": data}, nbytes=len(data)
        )
        return True

    def refresh_ids(self, entry, tables):
        """Per-table NEW chunk ids for a cached entry against live tables,
        or None when any table is not an append-only growth of its base
        (the caller drops the entry and recomputes)."""
        bases = entry.get("bases") or []
        if len(bases) != len(tables):
            return None
        out = []
        for base, table in zip(bases, tables):
            ids = growth_since(base, table)
            if ids is None:
                return None
            out.append(ids)
        return out

    @property
    def nbytes(self):
        return self._cache.nbytes

    def clear(self):
        self._cache.clear()

    def stats(self):
        out = self._cache.stats()
        out["refreshes"] = self.refreshes
        out["delta_rows"] = self.delta_rows
        return out
