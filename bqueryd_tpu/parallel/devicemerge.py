"""Device-resident distributed merge over the ICI mesh.

The mesh executor's merge used to be an all-reduce: every device psummed the
FULL merged table and the host fetched one replicated copy — and its kill
path (and every non-mesh route) shipped whole per-shard partial tables to a
host for :func:`bqueryd_tpu.parallel.hostmerge.merge_payloads`.  Both shapes
move table-sized data for every participant.  This module is the
partition-then-collective replacement (*Theseus*' minimize-data-movement
rule; the partition-based cross-node aggregation of *A Fast, Scalable,
Universal Approach For Distributed Data Aggregations*):

* **key-span partitioning** — the global dense group codes are already one
  shared key space (the executor's host alignment), so the bucket layout is
  a static slice: device ``d`` of an ``n``-device mesh owns the contiguous
  span ``[d * span, (d + 1) * span)`` of the (padded) group axis
  (:func:`bucket_span`).  ``ops.bucketize_partials`` emits partial tables
  padded onto that layout behind the existing kernel guards.
* **collective merge** — inside the compiled mesh program every leaf is
  all-gathered over the ``shards`` axis, reduced locally along the device
  axis (sum for sums/counts, min/max for extrema) and sliced to the
  device's own span (:func:`scatter_merge_partials`).  ONE mechanism for
  every dtype and kind, because it is the one the TPU compiler lowers for
  64-bit leaves — and the partial tables are 64-bit (int64 sums and counts,
  float64 sums): on the v5e ``psum_scatter`` is refused for s64/u64/f64
  ("rewriting [X64 element types] is not implemented: reduce-scatter"),
  ``pmin``/``pmax`` for every 64-bit type and ``psum`` for u64, while
  ``all_gather`` and elementwise 64-bit arithmetic lower for all of them
  (PERF.md, PR 21).  The gather moves ``n_devices`` dense tables to each
  device where a reduce-scatter would move one — a few MB at the dense
  table sizes every route assumes (<= 2^18 groups), next to kernels that
  read gigabytes.
* **D2H of the final table only** — the program's outputs are span-sized
  per device, so the only bytes that ever cross PCIe are the final merged
  table, fetched in parallel from all devices.  Per-shard
  partial tables never leave HBM.

``BQUERYD_TPU_DEVICE_MERGE=0`` is the kill switch: the executor then fetches
every device's partial table and merges them on the worker host with
``hostmerge.merge_payloads`` (the always-correct fallback), and the
controller stops batching shard groups so partials ride ZeroMQ per shard —
the reference's host-gather architecture, preserved as a measurable
baseline.  Multi-host meshes (``jax.process_count() > 1``) pin the
replicated contract regardless (same merge, no own-span slice): a
span-sharded output is not host-fetchable across processes.

Byte movement is accounted in :class:`MergeStats` (exported as the
``bqueryd_tpu_merge_*`` worker gauges and bench.py's ``merge`` section):
``bytes_fetched`` per mode, and ``d2h_bytes_saved`` — the per-device table
bytes the device-resident merge kept out of the fetch.

Import-light on purpose: the controller consults :func:`device_merge_enabled`
for its batching decision, so this module (like ``hostmerge``) must import
without JAX; collectives import it lazily inside the traced functions.
"""

import os
import threading

#: merge modes the mesh program traces (part of its cache key)
MODE_DEVICE = "device"   # span-owned device merge, span-only fetch
MODE_HOST = "host"       # fetch every device's partials, hostmerge on host
MODE_PSUM = "psum"       # replicated device merge + fetch (multi-host pods)


def device_merge_enabled():
    """The ``BQUERYD_TPU_DEVICE_MERGE`` kill switch (default on).  Off, the
    merge stays host-side end to end: the executor falls back to
    ``hostmerge.merge_payloads`` over per-device partials and the controller
    dispatches per shard instead of batching shard groups."""
    return os.environ.get("BQUERYD_TPU_DEVICE_MERGE", "1") != "0"


def resolve_mode():
    """The merge mode the mesh executor should trace for this query.

    ``device`` (default) / ``host`` (kill switch) on single-process
    backends; multi-host JAX jobs always get ``psum`` — each process can
    only fetch its addressable shards, so a span-sharded (or per-device)
    output is not host-materializable there and the replicated merge
    remains the multi-host contract."""
    import jax

    if jax.process_count() > 1:
        return MODE_PSUM
    return MODE_DEVICE if device_merge_enabled() else MODE_HOST


def bucket_span(n_groups, n_devices):
    """Key-span partitioner: ``(span, padded_groups)`` for laying a
    ``n_groups``-wide table over ``n_devices`` contiguous owners.  Device
    ``d`` owns ``[d * span, (d + 1) * span)``; ``padded_groups ==
    span * n_devices >= n_groups`` and the pad tail holds no real group."""
    n_groups = max(int(n_groups), 1)
    n_devices = max(int(n_devices), 1)
    span = -(-n_groups // n_devices)
    return span, span * n_devices


def _gather_reduce(kind, value, axis_name):
    """Cross-device reduction of one leaf INSIDE the shard_map program:
    all-gather over the mesh axis, then reduce the gathered device axis
    locally — ``min``/``max`` for extrema, a sum for everything else.  The
    result is replicated and, the reduction order being the device order on
    every device, bit-identical across devices (floats included)."""
    from jax import lax

    gathered = lax.all_gather(value, axis_name)  # [n_devices, ...]
    if kind == "min":
        return gathered.min(axis=0)
    if kind == "max":
        return gathered.max(axis=0)
    # dtype pinned: jnp.sum would widen a narrow integer leaf
    return gathered.sum(axis=0, dtype=value.dtype)


def _own_span(reduced, axis_name, span):
    """This device's contiguous key span of a replicated merged leaf (group
    axis leading); ``span=None`` keeps the whole replicated leaf."""
    import jax.numpy as jnp
    from jax import lax

    if span is None:
        return reduced
    start = lax.axis_index(axis_name) * span
    zero = jnp.zeros_like(start)  # one index dtype for every dimension
    return lax.dynamic_slice(
        reduced,
        (start,) + (zero,) * (reduced.ndim - 1),
        (span,) + tuple(reduced.shape[1:]),
    )


def scatter_merge_partials(partials, axis_name, span):
    """Merge partial tables across a mesh axis, span-owned.

    Runs INSIDE the shard_map program, per device: ``partials`` leaves are
    the padded flat ``[n_devices * span]`` tables from
    ``ops.bucketize_partials``.  Every leaf is gathered, reduced by its
    kind's rule (sum/count add, min/max take the extremum) and sliced to
    this device's span — the OUTPUT is span-sized, which is what keeps the
    D2H fetch to exactly one final table.  ``span=None`` (the multi-host
    contract) returns the whole merged table replicated instead.
    """
    def merge_leaf(kind, value):
        return _own_span(
            _gather_reduce(kind, value, axis_name), axis_name, span
        )

    rows = merge_leaf("rows", partials["rows"])
    aggs = tuple(
        {kind: merge_leaf(kind, value) for kind, value in part.items()}
        for part in partials["aggs"]
    )
    return {"rows": rows, "aggs": aggs}


def allgather_topk_merge(values, counts, axis_name, span, largest,
                         float_neg):
    """Cross-device merge of dense per-group top-k partials INSIDE the mesh
    program: all-gather the ``[padded_groups, k]`` dense tables + per-group
    counts, re-select the best ``k`` per group over the ``n_dev * k``
    candidates, and keep this device's own key span — ``[span, k]`` +
    ``[span]`` outputs, so (like the reduce-scattered classic partials)
    only final-table bytes ever leave HBM.

    The re-select is MULTISET-equal to the host k-way merge
    (``opexec.merge_topk_parts``): top-k payloads carry VALUES only, so
    which of several equal-valued candidates survives is unobservable.
    Validity rides a lexsort primary key (a dense slot is live iff its
    rank < its device's count), which is what lets the gathered zero-pad
    slots never shadow a real candidate.  ``span=None`` (the multi-host
    psum contract) skips the own-span slice and returns the full
    replicated merged table."""
    import jax.numpy as jnp
    from jax import lax

    k = int(values.shape[1])
    gathered = lax.all_gather(values, axis_name)     # [n_dev, G, k]
    gcounts = lax.all_gather(counts, axis_name)      # [n_dev, G]
    n_dev = int(gathered.shape[0])
    n_groups = int(gathered.shape[1])
    rank = jnp.arange(k, dtype=jnp.int64)
    valid = rank[None, None, :] < gcounts[:, :, None]
    cand = jnp.moveaxis(gathered, 0, 1).reshape(n_groups, n_dev * k)
    vmask = jnp.moveaxis(valid, 0, 1).reshape(n_groups, n_dev * k)
    if largest:
        sort_v = -cand if float_neg else ~cand
    else:
        sort_v = cand
    # primary key: validity (valid first); secondary: best-first value
    order = jnp.lexsort((sort_v, ~vmask), axis=-1)
    top = jnp.take_along_axis(cand, order[:, :k], axis=-1)
    cnt = jnp.minimum(gcounts.sum(axis=0), k)
    return (
        _own_span(top, axis_name, span), _own_span(cnt, axis_name, span)
    )


def scatter_merge_grid(grid, axis_name, span):
    """Bucket-count ADDITION of dense per-(group, bucket) sketch grids
    across the mesh axis, span-owned over the padded group axis (the
    :func:`scatter_merge_partials` contract) — ``[span, width]`` out per
    device.  ``span=None`` (multi-host) keeps the replicated full grid."""
    return _own_span(_gather_reduce("sum", grid, axis_name), axis_name, span)


class MergeStats:
    """Process-wide merge byte-movement accounting (thread-safe): D2H bytes
    fetched per merge mode, queries per mode, and the per-device partial
    bytes the device-resident merge kept out of the fetch.  Process-global
    like the pipeline stage clocks — the worker owns the process's data
    path and exports these as the ``bqueryd_tpu_merge_*`` gauges."""

    #: lock discipline, statically checked by bqueryd_tpu.analysis
    #: (lock-unguarded-attr)
    _bqtpu_guarded_ = {"_lock": ("_bytes_fetched", "_bytes_saved", "_queries")}

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes_fetched = {MODE_DEVICE: 0, MODE_HOST: 0}
        self._bytes_saved = 0
        self._queries = {MODE_DEVICE: 0, MODE_HOST: 0}

    def record(self, mode, fetched, saved=0):
        """One merged query: ``fetched`` D2H bytes under ``mode``; ``saved``
        is the counterfactual host-gather fetch minus the actual one
        (device-resident modes only).  The psum mode counts as ``device`` —
        the merge is device-resident, only the fetch is replicated."""
        key = MODE_HOST if mode == MODE_HOST else MODE_DEVICE
        with self._lock:
            self._bytes_fetched[key] += int(fetched)
            self._bytes_saved += max(int(saved), 0)
            self._queries[key] += 1

    def fetched(self, mode):
        with self._lock:
            return self._bytes_fetched.get(mode, 0)

    def saved(self):
        with self._lock:
            return self._bytes_saved

    def count(self, mode):
        with self._lock:
            return self._queries.get(mode, 0)

    def snapshot(self):
        with self._lock:
            return {
                "bytes_fetched": dict(self._bytes_fetched),
                "d2h_bytes_saved": self._bytes_saved,
                "queries": dict(self._queries),
            }

    def reset(self):
        """Bench/test seam: zero the counters for a bracketed measurement."""
        with self._lock:
            self._bytes_fetched = {MODE_DEVICE: 0, MODE_HOST: 0}
            self._bytes_saved = 0
            self._queries = {MODE_DEVICE: 0, MODE_HOST: 0}


_stats = MergeStats()


def stats():
    """The process-global :class:`MergeStats`."""
    return _stats
