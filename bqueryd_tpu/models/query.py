"""Query model + local execution engine (the framework's "flagship model").

A groupby query (the payload of a ``CalcMessage``, same positional contract as
the reference: ``(filename, groupby_col_list, agg_list, where_terms_list)``
with kwargs ``aggregate`` / ``expand_filter_column``, reference
bqueryd/worker.py:277-284) compiles to a pipeline of the kernels in
:mod:`bqueryd_tpu.ops`:

    storage decode -> H2D -> where-mask -> key codes -> packed composite ->
    segment partials -> (mesh psum) -> finalize

Results travel as :class:`ResultPayload`:

* ``kind="partials"``: per-group partial tables **keyed by actual key values**
  (not local codes), so payloads from different workers merge without any
  cross-host dictionary coordination — the host-side merge in
  :mod:`bqueryd_tpu.parallel.hostmerge` aligns them by key.  Mean partials
  carry (sum, count): the correct weighted mean, not the reference's
  sum-of-shard-means (reference bqueryd/rpc.py:171).
* ``kind="rows"``: the ``aggregate=False`` raw-rows path — filtered selected
  columns, concatenated client-side (reference bqueryd/worker.py:316-323,
  rpc.py:172-173).
* ``kind="empty"``: shard pruned by ``shard_can_match`` (the
  factorization-check early-out, reference bqueryd/worker.py:296-301).
"""

import os
import pickle
from dataclasses import dataclass, field

import numpy as np

from bqueryd_tpu.utils import devicehealth, tracing

PAYLOAD_FORMAT = "bqueryd-tpu-result-1"

#: the bquery aggregation surface (reference bquery API; reference tests
#: exercise sum/mean/count) plus min/max.  Defined here, JAX-free, so
#: control-plane processes (controller batching decisions) can consult them;
#: bqueryd_tpu.ops re-exports.
AGG_OPS = (
    "sum",
    "mean",
    "count",
    "count_na",
    "count_distinct",
    "sorted_count_distinct",
    "min",
    "max",
)

#: ops whose partials merge with elementwise +/min/max (psum-able); the two
#: distinct-count ops need value sets and take the gather path instead.
MERGEABLE_OPS = ("sum", "mean", "count", "count_na", "min", "max")


def extremum_fill(dtype, kind):
    """Identity fill for per-group ``min``/``max`` partials of ``dtype``:
    'min' fills with the dtype's maximum so any real value wins (and vice
    versa); bool uses its and/or identities, floats +/-inf.  Shared by the
    device kernels, the host kernels, and the cross-payload merge so a new
    dtype special case lives in exactly one place."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        return np.inf if kind == "min" else -np.inf
    if dtype == np.bool_:
        return kind == "min"
    info = np.iinfo(dtype)
    return info.max if kind == "min" else info.min


def normalize_agg_list(agg_list):
    """Agg shorthand normalization: ``"col"`` -> ``[col, 'sum', col]``;
    2-item ``[in, op]`` -> ``[in, op, in]``.  The ONE copy of these rules —
    the worker's :class:`GroupByQuery` and the controller's logical plan
    both use it, so the plan signature (the shared-dispatch fusion key) and
    the executed query can never normalize differently."""
    normalized = []
    for agg in agg_list:
        if isinstance(agg, str):
            normalized.append([agg, "sum", agg])
        elif len(agg) == 2:
            agg = list(agg)
            normalized.append([agg[0], agg[1], agg[0]])
        else:
            normalized.append(list(agg))
    return normalized


def freeze_value(value):
    """Canonical, hashable, collision-free form of a query parameter
    (repr() is ambiguous for numpy arrays, which truncate their repr)."""
    import hashlib

    if isinstance(value, np.ndarray):
        if value.dtype == object:
            # tobytes() of an object array is its POINTER bytes: unstable
            # across (de)serializations and aliasable under allocator
            # reuse — freeze the contained VALUES instead (string
            # dimension-table columns, plan.dag join signatures)
            return ("ndarray-obj", value.shape,
                    tuple(freeze_value(v) for v in value.ravel().tolist()))
        return ("ndarray", value.dtype.str, value.shape,
                hashlib.sha1(value.tobytes()).hexdigest())
    if isinstance(value, (list, tuple)):
        return ("seq", tuple(freeze_value(v) for v in value))
    if isinstance(value, (set, frozenset)):
        return ("set", tuple(sorted((freeze_value(v) for v in value), key=repr)))
    if isinstance(value, np.generic):
        return value.item()
    return value


@dataclass
class GroupByQuery:
    groupby_cols: list
    agg_list: list          # [[in_col, op, out_col], ...]
    where_terms: list = field(default_factory=list)
    aggregate: bool = True
    expand_filter_column: str = None
    #: controller-set hint: this unit's payload is the WHOLE query (one
    #: dispatch group holds every file it keeps), so no cross-payload merge
    #: will happen — count_distinct may ship final per-group counts
    #: (computed by the device sort) instead of the distinct value sets an
    #: exact cross-shard union needs.  Whoever splits the unit into
    #: payloads of its own clears it
    sole_payload: bool = False

    def signature(self):
        """Hashable identity of the query (cache key component)."""
        return (
            tuple(self.groupby_cols),
            freeze_value(self.agg_list),
            freeze_value(self.where_terms or []),
            bool(self.aggregate),
            self.expand_filter_column,
            bool(self.sole_payload),
        )

    def __post_init__(self):
        self.agg_list = normalize_agg_list(self.agg_list)

    @property
    def in_cols(self):
        return [a[0] for a in self.agg_list]

    @property
    def ops(self):
        return tuple(a[1] for a in self.agg_list)

    @property
    def out_cols(self):
        return [a[2] for a in self.agg_list]


def _group_distinct_flat(group_codes, value_codes, value_uniques, n_groups,
                         mask=None):
    """Per-group distinct values in FLAT form: ``(values, offsets)`` where
    group ``g``'s distinct values are ``values[offsets[g]:offsets[g+1]]``.

    The flat form (vs an object array of per-group arrays) keeps the payload
    one contiguous array + one int64 offsets array: cheap to pickle, and the
    cross-shard union merge stays fully vectorized (no per-group Python).

    Null group keys, null values (code < 0, e.g. NaN — matching pandas
    ``nunique(dropna=True)``), and masked-out rows contribute nothing."""
    valid = (group_codes >= 0) & (value_codes >= 0)
    if mask is not None:
        valid &= mask
    nv = max(len(value_uniques), 1)
    pairs = np.unique(
        group_codes[valid].astype(np.int64) * nv + value_codes[valid]
    )
    g_of = pairs // nv
    v_of = pairs % nv
    offsets = np.searchsorted(g_of, np.arange(n_groups + 1)).astype(np.int64)
    return np.asarray(value_uniques)[v_of], offsets


def _segment_local_arange(counts):
    """[0..c0), [0..c1), ... concatenated — index-within-segment helper."""
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    starts = np.cumsum(counts) - counts
    return np.arange(total, dtype=np.int64) - np.repeat(starts, counts)


def filter_distinct_part(part, present):
    """Row-filter a flat distinct part to the ``present`` groups."""
    values = part["distinct_values"]
    offsets = part["distinct_offsets"]
    counts = np.diff(offsets)
    sel = counts[present]
    starts = offsets[:-1][present]
    idx = np.repeat(starts, sel) + _segment_local_arange(sel)
    new_offsets = np.zeros(len(sel) + 1, dtype=np.int64)
    np.cumsum(sel, out=new_offsets[1:])
    return {"distinct_values": values[idx], "distinct_offsets": new_offsets}


def distinct_wire(payload):
    """The form a payload's ``count_distinct`` leaves its worker in:
    ``"counts"`` (final per-group counts: the payload is the whole answer)
    or ``"sets"`` (per-group value sets, unioned downstream); None where it
    holds no distinct count."""
    if payload.get("kind") != "partials":
        return None
    for op, part in zip(payload["ops"], payload["aggs"]):
        if op == "count_distinct":
            return "counts" if "distinct" in part else "sets"
    return None


class ResultPayload(dict):
    """Wire form of a shard/worker result; a plain dict for pickling."""

    @classmethod
    def empty(cls):
        return cls(format=PAYLOAD_FORMAT, kind="empty")

    @classmethod
    def rows(cls, columns, order):
        return cls(format=PAYLOAD_FORMAT, kind="rows", columns=columns, order=order)

    @classmethod
    def partials(cls, key_cols, keys, rows, aggs, ops, out_cols,
                 value_kinds=None):
        return cls(
            format=PAYLOAD_FORMAT,
            kind="partials",
            key_cols=list(key_cols),
            keys=keys,        # {col: np.ndarray[G] of key values}
            rows=rows,        # np.int64[G]
            aggs=aggs,        # list of {partname: np.ndarray[G]}
            ops=list(ops),
            out_cols=list(out_cols),
            # storage kind per agg (None | 'datetime'): partials of datetime
            # measures ride and merge as raw int64; finalize views min/max
            # back to datetime64[ns] (NaT for empty groups)
            value_kinds=(
                [None] * len(list(out_cols))
                if value_kinds is None
                else list(value_kinds)
            ),
        )

    def to_bytes(self):
        return pickle.dumps(dict(self), protocol=4)

    @classmethod
    def from_bytes(cls, buf):
        if not buf:
            return cls.empty()
        obj = pickle.loads(buf)
        if obj.get("format") != PAYLOAD_FORMAT:
            raise ValueError("unknown result payload format")
        return cls(obj)


_measured_floor = None


def device_dispatch_floor(remeasure=False):
    """Measured wall of one trivial jitted dispatch + host fetch on the
    default backend (min of 3, cached per process): microseconds to a
    fraction of a millisecond on a local chip, more behind a remote one.  The fetch is included because the device query path ends
    in a ``device_get`` — that is the cost host routing competes against.

    A measurement taken while another thread holds the backend (e.g. the
    worker's background warmup compile) is inflated; the warmup thread
    calls ``remeasure=True`` when it finishes to replace any such sample."""
    global _measured_floor
    if devicehealth.backend_wedged():
        # do NOT cache: a recovered backend must remeasure a real floor
        return devicehealth.probe_timeout_s()
    if _measured_floor is None or remeasure:
        import time

        def _measure():
            import jax
            import jax.numpy as jnp
            import numpy as np

            f = jax.jit(lambda x: x + 1)
            np.asarray(f(jnp.zeros(())))
            walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(f(jnp.zeros(())))
                walls.append(time.perf_counter() - t0)
            return min(walls)

        # the measurement IS a device dispatch: on a wedged backend it
        # would hang the calling thread (historically the worker loop, via
        # the first query's routing) forever.  Run it sacrificially; a
        # deadline miss latches the backend and host routing takes over.
        timeout = devicehealth.probe_timeout_s()
        if timeout <= 0:  # detection disabled: measure directly
            _measured_floor = _measure()
            return _measured_floor
        done, floor = devicehealth.run_with_deadline(_measure, timeout)
        if not done or floor is None:
            devicehealth.latch_wedged()
            return devicehealth.probe_timeout_s()
        _measured_floor = floor
    return _measured_floor


#: assumed host aggregation cost per row (cached codes + fast-path
#: bincounts: ~7ns/row measured at 1M rows x 9 groups, rounded up),
#: used only to convert the measured dispatch floor into a row threshold
_HOST_NS_PER_ROW = 8e-9

#: cost when a measure misses the fast paths: the 16-bit-limb exact int
#: sum (4 weighted bincounts) or np.minimum/maximum.at extrema run ~4x
#: the fast-path rate, so near-threshold queries must not be host-routed
#: on the optimistic estimate
_HOST_NS_PER_ROW_SLOW = 32e-9


def _host_ns_estimate(table, agg_list, n_rows):
    """Per-row host-kernel cost for routing, from column METADATA only
    (physical dtype + chunk min/max stats — no decode): integer sums whose
    ``n x max|value|`` bound stays under 2^53 take the single-bincount
    fast path; larger-magnitude (or stats-less) int sums and min/max pay
    the slow rate."""
    from bqueryd_tpu.ops.groupby import (
        _NATIVE_GROUPBY_MIN_ROWS,
        HOST_EXACT_SUM_BOUND,
    )

    native_ok = None  # computed lazily: import + symbol probe

    def native_takes_it():
        # The C++ kernels sum in uint64 (exact at any magnitude) and do
        # min/max in one striped pass, so queries they will take have no
        # slow fallback to price in.  (They decline above their group
        # ceiling — unknown until factorize — in which case the numpy
        # path runs mis-rated; high-cardinality host routes are rare
        # enough to accept that.)
        nonlocal native_ok
        if native_ok is None:
            from bqueryd_tpu.storage import native

            native_ok = (
                n_rows >= _NATIVE_GROUPBY_MIN_ROWS
                and native.groupby_available()
            )
        return native_ok

    minmax_ok = None

    def _native_minmax_ok():
        nonlocal minmax_ok
        if minmax_ok is None:
            from bqueryd_tpu.storage import native

            minmax_ok = native.groupby_minmax_available()
        return minmax_ok

    for in_col, op, _out in agg_list:
        if op in ("min", "max"):
            # extrema need the dedicated min/max kernel, which also
            # declines unsigned dtypes (uint64 would wrap its signed i64
            # accumulator) — those queries run numpy ufunc.at, the slow rate
            if (
                table.kind(in_col) == "datetime"
                or not native_takes_it()
                or not _native_minmax_ok()
                or np.issubdtype(
                    table.physical_dtype(in_col), np.unsignedinteger
                )
            ):
                return _HOST_NS_PER_ROW_SLOW  # numpy ufunc.at extrema
            continue
        if op in ("sum", "mean") and np.issubdtype(
            table.physical_dtype(in_col), np.integer
        ):
            if native_takes_it():
                continue
            stats = table.col_stats(in_col)
            if stats is None:
                return _HOST_NS_PER_ROW_SLOW
            bound = max(abs(int(stats[0])), abs(int(stats[1])))
            if bound * max(int(n_rows), 1) >= HOST_EXACT_SUM_BOUND:
                return _HOST_NS_PER_ROW_SLOW
    return _HOST_NS_PER_ROW

#: never host-route queries above this many rows, however slow the device
#: link — large queries belong on the device program.  (A blanket
#: host-route-everything rule for CPU backends was tried and measured WORSE:
#: numpy wins on few-group sums but XLA's scatter wins at high cardinality,
#: so the latency-derived threshold below is the rule on every backend.)
_HOST_ROUTE_CAP = 4_000_000

#: multi-key composite spaces at most this large aggregate directly over the
#: full (K1*...*Kn)-slot space instead of paying an O(n) compaction pass;
#: empty combos are dropped at collect, so only kernel minlength grows
_DENSE_COMBO_CAP = 1 << 16


def host_kernel_rows(ns_per_row=None):
    """Row threshold below which mergeable aggregations run on the HOST
    (:func:`ops.host_partial_tables`) instead of paying a device round-trip.

    Latency-aware routing: for small inputs the dispatch+fetch floor dwarfs
    the kernel, so the host is strictly faster; the threshold is derived
    from the MEASURED floor (a local chip's microseconds collapse it to
    ~10k rows).  ``ns_per_row`` lets the caller
    pass a per-query cost estimate (:func:`_host_ns_estimate`); default is
    the fast-path rate.  Override with BQUERYD_TPU_HOST_KERNEL_ROWS
    (0 disables host routing)."""
    if devicehealth.backend_wedged(launch=False):
        # wedged backend: EVERY query the host kernels can serve must go
        # host — the alternative is a worker loop hung inside native code.
        # Deliberately overrides the env pin (an operator's device-only
        # setting is about performance; a wedge is about survival) and the
        # 4M-row cap (the cap encodes host-vs-device economics that do not
        # exist while the device cannot answer at all).
        return 1 << 62
    env = os.environ.get("BQUERYD_TPU_HOST_KERNEL_ROWS")
    if env is not None:
        try:
            return max(int(env), 0)
        except ValueError:
            import logging

            logging.getLogger("bqueryd_tpu").warning(
                "unparseable BQUERYD_TPU_HOST_KERNEL_ROWS=%r, "
                "host routing disabled", env,
            )
            return 0
    ns = _HOST_NS_PER_ROW if ns_per_row is None else ns_per_row
    return min(int(device_dispatch_floor() / ns), _HOST_ROUTE_CAP)


def _value_kind_for(table, col):
    """Storage-kind tag carried per agg in the payload: 'datetime' restores
    datetime64 at finalize; 'uint64' re-views mod-2^64 sums as unsigned
    (every kernel path accumulates the same bits either way — only the
    presentation differs, matching pandas' uint64 groupby sums); 'uint'
    marks narrower unsigned storage so a cross-shard merge can tell a
    narrow unsigned sibling of a uint64 shard (reconcile to the unsigned
    view) from a signed/float sibling (refuse: reinterpreting a widened
    signed or float total as uint64 would corrupt it)."""
    if table.kind(col) == "datetime":
        return "datetime"
    dt = table.physical_dtype(col)
    if dt == np.dtype(np.uint64):
        return "uint64"
    if dt.kind == "u":
        return "uint"
    return None


class QueryEngine:
    """Executes queries against local tpucolz tables on the local JAX device
    (single-device path; the multi-device mesh path lives in
    bqueryd_tpu.parallel.executor).  JAX imports happen lazily on first use so
    control-plane processes can import this module freely."""

    def __init__(self, timer=None):
        self.timer = timer
        #: the physical kernel route of the last execute_local (post-guards;
        #: "host" for host-routed queries) — surfaced by the worker as
        #: ``effective_strategy`` in calc replies and kernel trace spans
        self.last_effective_strategy = None
        from bqueryd_tpu.utils.cache import BytesCappedCache

        # per-(table, column) factorization cache: the host analogue of
        # bquery's on-disk factorize cache (reference bqueryd/worker.py:291,
        # auto_cache=True) — repeated queries on unchanged shards skip the
        # hash factorize entirely.  Keyed on the shard's meta identity, so
        # activation invalidates naturally.
        self._factorize_cache = BytesCappedCache(
            int(
                os.environ.get(
                    "BQUERYD_TPU_FACTORIZE_CACHE_BYTES", 256 * 1024**2
                )
            )
        )

    def clear_caches(self):
        """Drop the factorize cache (memory-watchdog hook)."""
        self._factorize_cache.clear()

    def _phase(self, name):
        import contextlib

        if self.timer is None:
            return contextlib.nullcontext()
        return self.timer.phase(name)

    # -- key handling ------------------------------------------------------
    def _key_codes(self, table, col, mask_np=None):
        """Physical dense codes + key-value array for one groupby column."""
        from bqueryd_tpu import ops

        kind = table.kind(col)
        if kind == "dict":
            codes = table.column_raw(col)
            values = np.asarray(table.dictionary(col), dtype=object)
            return codes, values
        from bqueryd_tpu.storage.ctable import table_cache_key

        cache_key = (table_cache_key(table), col)
        hit = self._factorize_cache.get(cache_key)
        if hit is not None:
            return hit
        # disk sidecar (bquery's auto_cache analogue, stored POST null-poison
        # so a load skips the NaN/NaT scan too) before paying the
        # decode+factorize
        loader = getattr(table, "factor_cache_load", None)
        if loader is not None:
            disk = loader(col)
            if disk is not None:
                codes, uniques = disk
                if kind == "datetime" and uniques.dtype.kind != "M":
                    uniques = uniques.view("datetime64[ns]")
                self._factorize_cache.put(
                    cache_key, (codes, uniques),
                    nbytes=codes.nbytes + uniques.nbytes,
                )
                return codes, uniques
        # stamp BEFORE the read: if the shard is rewritten mid-factorize the
        # sidecar lands stale (future miss), never poisoned (see the TOCTOU
        # note in storage/ctable.py)
        stamper = getattr(table, "factor_stamp", None)
        stamp = stamper(col) if stamper is not None else None
        raw = table.column_raw(col)
        codes, uniques = ops.factorize(raw)
        if kind == "datetime":
            uniques = uniques.view("datetime64[ns]")
        # NaN/NaT uniques are nulls, not values: poison their codes to -1 so
        # those rows drop from group keys (pandas dropna) and from distinct
        # sets (pandas nunique skips nulls) — ops.factorize itself treats
        # them as ordinary keys and documents that callers pre-filter
        null_at = None
        if kind == "datetime":
            null_at = np.flatnonzero(np.isnat(uniques))
        elif np.issubdtype(np.asarray(uniques).dtype, np.floating):
            null_at = np.flatnonzero(np.isnan(uniques))
        if null_at is not None and len(null_at):
            codes = np.where(
                np.isin(codes, null_at), np.int64(-1), codes
            )
        storer = getattr(table, "factor_cache_store", None)
        if storer is not None and stamp is not None:
            storer(col, codes, uniques, stamp=stamp)
        self._factorize_cache.put(
            cache_key, (codes, uniques), nbytes=codes.nbytes + uniques.nbytes
        )
        return codes, uniques

    def _basket_codes(self, table, col):
        """Basket-expansion codes for ``expand_filter_column`` — cached like
        :meth:`_key_codes` but with the basket semantics the engine always
        shipped: the factorize runs over the PHYSICAL column, so dict-encoded
        nulls (code -1) become one ordinary, selectable basket group (the
        basket key is a plain value column, matching the reference's
        ``is_in_ordered_subgroups`` which knows nothing about nulls)."""
        from bqueryd_tpu import ops
        from bqueryd_tpu.storage.ctable import table_cache_key

        cache_key = (table_cache_key(table), col, "basket")
        hit = self._factorize_cache.get(cache_key)
        if hit is not None:
            return hit
        codes, uniques = ops.factorize(np.asarray(table.column_raw(col)))
        self._factorize_cache.put(
            cache_key, (codes, uniques), nbytes=codes.nbytes + uniques.nbytes
        )
        return codes, uniques

    # -- execution ---------------------------------------------------------
    def execute_local(self, table, query: GroupByQuery,
                      strategy=None) -> ResultPayload:
        """``strategy`` is the seam by which a test reaches one kernel:
        ``"host"`` forces the NumPy kernels (bypassing the latency
        threshold), ``"scatter"`` / ``"sort"`` / ``"matmul"`` flow into
        :func:`ops.partial_tables` (matmul stays advisory there); None/"auto"
        is what every served query runs.  A wedged backend overrides every
        device route."""
        from bqueryd_tpu import ops

        self.last_effective_strategy = None  # set by the kernel dispatch
        if query.aggregate:
            # reject pandas-meaningless datetime sums/means before any
            # decode/factorize work is spent on the query
            for in_col, op in zip(query.in_cols, query.ops):
                if op in ("sum", "mean") and table.kind(in_col) == "datetime":
                    raise ValueError(
                        f"{op!r} is not defined for datetime "
                        f"column {in_col!r}"
                    )

        with self._phase("prune"):
            if query.where_terms and not ops.shard_can_match(
                table, query.where_terms
            ):
                return ResultPayload.empty()

        with self._phase("mask"):
            mask = ops.build_mask(table, query.where_terms)
            if query.expand_filter_column:
                basket_codes, basket_uniques = self._basket_codes(
                    table, query.expand_filter_column
                )
                mask = ops.expand_mask_by_group(
                    basket_codes, mask, n_groups=len(basket_uniques)
                )

        if not query.aggregate:
            return self._raw_rows(table, query, mask)

        with self._phase("factorize"):
            per_key = [self._key_codes(table, c) for c in query.groupby_cols]
            code_arrays = [np.asarray(c) for c, _ in per_key]
            key_values = [v for _, v in per_key]
            cards = [len(v) for v in key_values]
            combo_cols = None  # set by the CompositeOverflow fallback only
            # Null keys (code -1, dict-encoded missing values) stay -1 in the
            # dense codes: every kernel treats negative codes as invalid, so
            # null-key rows vanish from the aggregation (pandas dropna
            # semantics, same convention as the mesh executor's alignment).
            # Re-factorizing them into a real group would make ``collect``
            # index key_values[-1] — a wrapped, wrong key.
            if len(code_arrays) == 1:
                # _key_codes already produced dense first-seen codes into
                # key_values, so a second factorize is the identity map —
                # skipping it saves ~12ms/M rows, the whole host-route budget
                dense = code_arrays[0]
                combos = np.arange(cards[0], dtype=np.int64)
                n_groups = max(cards[0], 1)
            elif ops.total_cardinality(cards) >= ops.MAX_COMPOSITE:
                # radix packing would wrap (CompositeOverflow): factorize
                # the key TUPLES instead.  O(n log n) via a void-record
                # unique, null rows (any component -1) poisoned up front.
                # combos are not radix-decodable here, so the per-column
                # codes of each combo ride along for collect.
                stacked = np.stack(
                    [np.asarray(c, dtype=np.int64) for c in code_arrays],
                    axis=1,
                )
                valid = (stacked >= 0).all(axis=1)
                view = np.ascontiguousarray(stacked[valid]).view(
                    [("", np.int64)] * stacked.shape[1]
                ).ravel()
                uniq, inv = np.unique(view, return_inverse=True)
                dense = np.full(len(stacked), np.int64(-1))
                dense[valid] = inv
                combo_cols = (
                    uniq.view(np.int64).reshape(len(uniq), stacked.shape[1])
                )
                combos = np.arange(len(uniq), dtype=np.int64)
                n_groups = max(len(uniq), 1)
            else:
                packed = ops.pack_codes(code_arrays, cards)
                total_card = ops.total_cardinality(cards)
                if total_card <= _DENSE_COMBO_CAP:
                    # composite space small enough to aggregate over
                    # directly; empty combos drop at collect via rows == 0
                    dense = packed
                    combos = np.arange(total_card, dtype=np.int64)
                    n_groups = max(total_card, 1)
                else:
                    # compact the sparse composite space with the O(n) hash
                    # factorizer, then evict the null composite (-1) from
                    # the group dictionary so it stays invalid downstream.
                    # (Unsorted first-seen combos are fine here: hostmerge
                    # aligns payloads by key VALUES, unlike the mesh
                    # executor's alignment which needs a sorted global
                    # order.)
                    dense, combos = ops.factorize(packed)
                    null_at = np.flatnonzero(combos == -1)
                    if len(null_at):
                        j = int(null_at[0])
                        remap = np.empty(len(combos), dtype=np.int64)
                        remap[:j] = np.arange(j)
                        remap[j] = -1
                        remap[j + 1:] = np.arange(j, len(combos) - 1)
                        dense = remap[dense]
                        combos = np.delete(combos, j)
                    n_groups = max(len(combos), 1)

        with self._phase("aggregate"):
            mask_arr = None if mask is None else np.asarray(mask)
            mergeable = [
                (i, a) for i, a in enumerate(query.agg_list)
                if a[1] in ops.MERGEABLE_OPS
            ]
            distinct = [
                (i, a) for i, a in enumerate(query.agg_list)
                if a[1] not in ops.MERGEABLE_OPS
            ]
            agg_parts = [None] * len(query.agg_list)
            if mergeable:
                measures = tuple(
                    table.column_raw(a[0]) for _, a in mergeable
                )
                mops = tuple(a[1] for _, a in mergeable)
                # datetime measures: NaT (int64 min) is a null sentinel so
                # those rows skip counts/extrema like float NaNs (pandas);
                # datetime sums/means were rejected on entry
                sentinels = tuple(
                    np.iinfo(np.int64).min
                    if table.kind(a[0]) == "datetime"
                    else None
                    for _, a in mergeable
                )
                if strategy == "host" or len(dense) <= host_kernel_rows(
                    _host_ns_estimate(
                        table, [a for _, a in mergeable], len(dense)
                    )
                ):
                    # latency-aware routing: below the threshold the host
                    # beats the device's dispatch+fetch floor (see
                    # host_kernel_rows); identical partial semantics.
                    # strategy="host" forces this branch outright.
                    self.last_effective_strategy = "host"
                    partials = ops.host_partial_tables(
                        dense.astype(np.int32), measures, mops, n_groups,
                        mask_arr, null_sentinels=sentinels,
                    )
                else:
                    import jax

                    # bucketed group count (ops.program_bucket): program
                    # reuse across cardinality drift; padded groups are
                    # zero-row and sliced off after the fetch
                    n_prog = ops.program_bucket(n_groups)
                    kernel_strategy = (
                        strategy
                        if strategy in ("matmul", "scatter", "sort")
                        else None
                    )
                    self.last_effective_strategy = ops.kernel_route(
                        kernel_strategy,
                        [np.asarray(m) for m in measures], mops,
                        len(dense), n_prog,
                    )
                    partials = jax.device_get(  # ONE batched D2H round-trip
                        ops.partial_tables(
                            dense.astype(np.int32), measures, mops, n_prog,
                            mask_arr, null_sentinels=sentinels,
                            strategy=kernel_strategy,
                        )
                    )
                    if n_prog != n_groups:
                        partials = jax.tree_util.tree_map(
                            lambda a: a[:n_groups], partials
                        )
                rows = partials["rows"]
                for (i, _a), part in zip(mergeable, partials["aggs"]):
                    agg_parts[i] = dict(part)
            else:
                # rows still needed to drop empty groups
                if devicehealth.backend_wedged():
                    # the numpy twin shares partial_tables' exact row
                    # semantics (negative codes dropped, mask applied)
                    rows = np.asarray(
                        ops.host_partial_tables(
                            dense.astype(np.int32), (), (), n_groups,
                            mask_arr,
                        )["rows"]
                    )[:n_groups]
                else:
                    rows = np.asarray(
                        ops.partial_tables(
                            dense.astype(np.int32),
                            (np.zeros(len(dense)),),
                            ("count",),
                            ops.program_bucket(n_groups),
                            mask_arr,
                        )["rows"]
                    )[:n_groups]
            for i, agg in distinct:
                in_col, op, _out = agg
                if op == "count_distinct":
                    # what the distinct count costs, counts or sets (the
                    # worker tags the span with the payload's wire form)
                    with tracing.detail("distinct_wait", self.timer):
                        agg_parts[i] = self._distinct_part(
                            table, query, in_col, dense, n_groups, mask_arr
                        )
                elif op == "sorted_count_distinct":
                    # run-boundary counts are inherently per-shard (the sort
                    # order is local); cross-shard merge stays additive
                    vals = table.column_raw(in_col)
                    if devicehealth.backend_wedged():
                        # numpy twin with identical run-leader semantics:
                        # the last device-only op also survives a wedge
                        counts = ops.host_sorted_count_distinct(
                            dense.astype(np.int32), vals,
                            n_groups, mask_arr,
                        )
                    else:
                        counts = ops.groupby_sorted_count_distinct(
                            dense.astype(np.int32), vals,
                            ops.program_bucket(n_groups), mask_arr,
                        )
                    agg_parts[i] = {
                        "distinct": np.asarray(counts)[:n_groups]
                    }
                else:
                    raise ValueError(f"unknown aggregation op {op!r}")

        with self._phase("collect"):
            present = rows > 0
            combos_present = combos[present]
            keys = {}
            if len(query.groupby_cols) == 1:
                key_codes = [combos_present]
            elif combo_cols is not None:
                # tuple-factorized combos (CompositeOverflow fallback):
                # per-column codes were kept alongside, not radix-packed
                key_codes = [
                    combo_cols[np.asarray(combos_present), ci]
                    for ci in range(combo_cols.shape[1])
                ]
            else:
                from bqueryd_tpu import ops as _ops

                key_codes = _ops.unpack_codes(combos_present, cards)
            for col, codes_g, values in zip(
                query.groupby_cols, key_codes, key_values
            ):
                idx = np.asarray(codes_g, dtype=np.int64)
                keys[col] = np.asarray(values)[idx]
            aggs = [
                filter_distinct_part(part, present)
                if "distinct_offsets" in part
                else {k: v[present] for k, v in part.items()}
                for part in agg_parts
            ]
            return ResultPayload.partials(
                key_cols=query.groupby_cols,
                keys=keys,
                rows=np.asarray(rows)[present],
                aggs=aggs,
                ops=query.ops,
                out_cols=query.out_cols,
                value_kinds=[_value_kind_for(table, a[0])
                             for a in query.agg_list],
            )

    def _distinct_part(self, table, query, in_col, dense, n_groups,
                       mask_arr):
        """One ``count_distinct`` part: the per-group counts where this
        payload is the whole answer (``query.sole_payload``), else the
        per-group distinct value SETS, which union exactly across payloads
        where added counts would count a value held by two shards twice
        (the reference's forced-'sum' merge, reference bqueryd/rpc.py:171).
        ``_key_codes`` resolves dict-encoded and datetime columns to their
        actual values: per-shard dictionary codes live in incompatible code
        spaces and never cross a shard boundary raw."""
        from bqueryd_tpu import ops

        vcodes, vuniques = self._key_codes(table, in_col)
        # a wedged backend ships the sets instead of hanging on the sort
        if query.sole_payload and not devicehealth.backend_wedged():
            try:
                counts = ops.groupby_count_distinct(
                    dense.astype(np.int32), np.asarray(vcodes),
                    ops.program_bucket(n_groups), mask_arr,
                )
                return {"distinct": np.asarray(counts)[:n_groups]}
            except ops.CompositeOverflow:
                pass  # groups past the int32 sort key: the sets below
        values, offsets = _group_distinct_flat(
            np.asarray(dense), np.asarray(vcodes), np.asarray(vuniques),
            n_groups, mask_arr,
        )
        # the sets grow with the distinct values (worst case ~ the whole
        # column); a configurable cap keeps a pathological query from
        # exhausting worker/client memory
        limit = int(os.environ.get(
            "BQUERYD_TPU_DISTINCT_VALUES_LIMIT", 5_000_000
        ))
        if limit and len(values) > limit:
            raise ValueError(
                f"count_distinct on {in_col!r}: {len(values)} "
                f"(group, value) pairs exceeds the payload cap "
                f"{limit}; raise "
                f"BQUERYD_TPU_DISTINCT_VALUES_LIMIT to allow"
            )
        return {"distinct_values": values, "distinct_offsets": offsets}

    def _raw_rows(self, table, query, mask):
        column_list = list(query.groupby_cols) + list(query.in_cols)
        seen = set()
        column_list = [c for c in column_list if not (c in seen or seen.add(c))]
        idx = None if mask is None else np.flatnonzero(np.asarray(mask))
        columns = {}
        for col in column_list:
            values = table.column(col)
            columns[col] = values if idx is None else values[idx]
        return ResultPayload.rows(columns, column_list)
