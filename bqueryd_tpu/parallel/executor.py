"""Mesh executor: one query over many shards, merged on-device with psum.

This is the TPU-native replacement for the reference's shard fan-out + merge
pipeline (per-shard tar results at reference bqueryd/worker.py:335-346,
controller tar-of-tars at reference bqueryd/controller.py:186-211, client
re-groupby at reference bqueryd/rpc.py:150-173).  Where the reference ships N
serialized result tables over TCP and re-aggregates them twice, here the N
shards are laid out over a 1-D ``jax.sharding.Mesh`` and the merge is a
``jax.lax.psum`` of index-aligned partial tables riding the ICI — one compiled
program, zero host serialization between partial and merged result.

What makes the psum legal is host-side key alignment: every shard's group
codes are remapped into one *global* composite-key space before the kernel
runs (SURVEY.md §7.3 "Merge alignment"), so row ``g`` of every device's
partial table refers to the same group.  The alignment is cheap (NumPy
searchsorted over per-shard dictionaries, not data rows) and happens once per
query.

Layout: all shards' rows are concatenated and split EVENLY across the mesh's
devices (legal because codes are global — any row partition psums to the same
answer), right-padded with code ``-1`` (the null code — padding contributes
to no group, see ``ops.partial_tables``), giving a balanced, static
``[n_devices, rows_per_device]`` shape XLA can tile.

Falls back to nothing: callers (worker, __graft_entry__, bench) route
non-mergeable aggregations (count_distinct family) and the aggregate=False
raw-rows path through the per-shard ``QueryEngine`` + host merge instead —
those results carry value *sets*, which a fixed-width psum cannot merge.

Steady-state serving is cache-resident (the TPU analogue of bquery's
``auto_cache`` factorization cache, reference bqueryd/worker.py:291),
organized as the working-set layer in :mod:`bqueryd_tpu.ops.workingset`:
host-side key alignment cached per (table-set, groupby-cols), and the
packed device blocks — group codes and measure columns — HBM-resident in
LRU byte-budgeted segments keyed by table identity (rootdir + mtime, so
shard activation invalidates naturally).  A repeated query — including one
with a DIFFERENT measure column, aggregate op or filter — therefore skips
decode, factorize, alignment and (for codes) H2D, and costs one compiled
kernel dispatch; under HBM pressure the working set sheds LRU device
entries before the allocator can wedge.

The cold path is a staged pipeline on the bounded pool in
:mod:`bqueryd_tpu.parallel.pipeline`: storage decode of cache-missing
measure columns is prefetched while key alignment runs, per-shard
decode/factorize fans out on the same pool, and the column build loop
keeps one decode+pack in flight ahead of each H2D transfer — stage busy
clocks feed the ``bqueryd_tpu_pipeline_busy_seconds`` gauges and bench.py's
overlap ratio.
"""

import contextlib
import functools
import os
import threading
import time

import numpy as np

from bqueryd_tpu.models.query import GroupByQuery, ResultPayload
from bqueryd_tpu.utils import devicehealth, tracing


def make_mesh(n_devices=None, axis_name="shards"):
    """A 1-D mesh over the first ``n_devices`` JAX devices.

    In a multi-host job (``ops.maybe_init_distributed``) ``jax.devices()``
    spans every host of the slice, so the shard mesh — and the psum merge —
    covers all chips: ICI within a host, DCN across hosts."""
    import jax

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return jax.sharding.Mesh(np.asarray(devices), (axis_name,))


def _put(arr_np, sharding):
    """Host->device placement that also works when the mesh spans hosts:
    multi-host shardings reject a plain device_put of a host-global array,
    so each process materializes only its addressable shards via callback
    (every worker process computes the same global array)."""
    import jax

    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            arr_np.shape, sharding, lambda idx: arr_np[idx]
        )
    return jax.device_put(arr_np, sharding)


def _wire_dtype(tables, col):
    """Narrowest signed int dtype covering every shard's stored [min, max]
    for ``col``, or None to ship the stored dtype unchanged.

    Host->device bytes are the cold path's cost floor (PCIe), so integer
    measures ride the wire at the width their actual value range needs;
    the kernel accumulates sums in
    int64 regardless (``ops.groupby._accum_dtype``), keeping aggregates
    bit-exact.  min/max partials are cast back to the stored dtype on the
    host after the merge."""
    lo = hi = None
    stored = None
    for t in tables:
        if t.kind(col) != "numeric":
            return None
        dt = t.physical_dtype(col)
        if dt.kind not in "iu":
            return None
        stored = dt if stored is None else max(stored, dt, key=lambda d: d.itemsize)
        stats = t.col_stats(col)
        if stats is None:
            return None
        lo = stats[0] if lo is None else min(lo, stats[0])
        hi = stats[1] if hi is None else max(hi, stats[1])
    for cand in (np.int8, np.int16, np.int32):
        info = np.iinfo(cand)
        if lo >= info.min and hi <= info.max:
            cand = np.dtype(cand)
            return cand if cand.itemsize < stored.itemsize else None
    return None


def _stored_dtype(tables, col):
    """Widest stored numeric dtype of ``col`` across shards, or None when any
    shard stores it non-numerically (dict/datetime)."""
    dts = []
    for t in tables:
        if t.kind(col) != "numeric":
            return None
        dts.append(t.physical_dtype(col))
    return np.result_type(*dts)


def _measure_kind(tables, col):
    """'datetime' when every shard stores ``col`` as a datetime, 'uint64'
    when every shard stores it unsigned-64 (mod-2^64 sums re-view as
    unsigned at finalize, pandas semantics), None for other numeric/dict;
    mixed datetime/non-datetime storage across shards is a data error."""
    kinds = {t.kind(col) for t in tables}
    if kinds == {"datetime"}:
        return "datetime"
    if "datetime" in kinds:
        raise ValueError(
            f"column {col!r} is datetime on some shards but not others"
        )
    dtypes = [t.physical_dtype(col) for t in tables]
    # the measures themselves widen via result_type (_stored_dtype), so the
    # unsigned tag must follow the WIDENED dtype: u64+u32 shards accumulate
    # in uint64 and their mod-2^64 sums still need the unsigned view
    if dtypes:
        widened = np.result_type(*dtypes)
        if widened == np.dtype(np.uint64):
            return "uint64"
        if widened.kind == "u":
            return "uint"
    return None


def _where_signature(query):
    """Hashable, canonical identity of a query's row-filter; ``None`` gives
    that of a query with no filter (the unmasked codes' own)."""
    from bqueryd_tpu.models.query import freeze_value

    if query is None:
        return (freeze_value([]), None)
    return (
        freeze_value(query.where_terms or []),
        query.expand_filter_column,
    )


#: the where ops whose constant is one scalar (``in`` / ``not in`` carry a
#: list, whose length is a program shape)
_DEVICE_FOLD_OPS = ("==", "!=", "<", "<=", ">", ">=")


def _device_fold_terms(tables, query):
    """The row filter as ``[(column, op, physical constant), ...]`` when it
    can fold into the group codes ON THE DEVICE (``_fold_program``), else
    None and the host builds the masks.  Decided by what the query and the
    tables show: every op compares against one scalar, no basket expansion,
    and every term's column is numeric or datetime with ONE stored dtype on
    every table of the dispatch — so the packed column is the very array
    each shard's host mask reads, and one constant serves all shards.  A
    ``dict`` column translates its constant per shard's dictionary; shards
    of differing widths compare each in its own precision."""
    from bqueryd_tpu import ops

    if not query.where_terms or query.expand_filter_column:
        return None
    terms = []
    for term in query.where_terms:
        try:
            column, op, value = term
        except (TypeError, ValueError):
            return None
        if op not in _DEVICE_FOLD_OPS:
            return None
        if not all(column in t for t in tables):
            return None
        stored = {(t.kind(column), t.physical_dtype(column)) for t in tables}
        if len(stored) != 1 or stored.pop()[0] not in ("numeric", "datetime"):
            return None
        phys = ops.translate_value(tables[0], column, value, op)
        # bool is an int: all three are what ``jit`` traces as weak-typed
        # scalars, the way the host's eager compare takes them
        if not isinstance(phys, (int, float)):
            return None
        terms.append((column, op, phys))
    return terms


def _codes_dtype(n_groups):
    """Narrowest signed dtype holding dense codes in [-1, n_groups)."""
    if n_groups <= np.iinfo(np.int8).max:
        return np.dtype(np.int8)
    if n_groups <= np.iinfo(np.int16).max:
        return np.dtype(np.int16)
    return np.dtype(np.int32)


# canonical table cache identity lives with the storage layer; kept under
# the old private name for existing importers
from bqueryd_tpu.storage.ctable import table_cache_key as _table_key  # noqa: E402,E501


def _matching_shards(tables, identities, where_terms):
    """Host-side shard pruning: the tables that can hold a matching row,
    and with them the identities their caller handed down (or None)."""
    from bqueryd_tpu import ops

    keep = [ops.shard_can_match(t, where_terms) for t in tables]
    tables = [t for t, k in zip(tables, keep) if k]
    if identities is not None:
        identities = [i for i, k in zip(identities, keep) if k]
    return tables, identities


def view_identities(identities, tables, views, on_recomputed=None):
    """What handed-down ``identities`` become once chunk pruning put
    ``views`` in the place of ``tables``: a table that pruning left whole
    keeps what its open read; a :class:`ChunkView` is filed under its own
    token (the chunk selection and its parent's meta identity, which
    building the view read off the filesystem again: ``on_recomputed`` is
    told how many did)."""
    if identities is None:
        return None
    n_views = sum(view is not table for view, table in zip(views, tables))
    if n_views and on_recomputed is not None:
        on_recomputed(n_views)
    return tuple(
        ident if view is table else _table_key(view)
        for ident, table, view in zip(identities, tables, views)
    )


class MeshQueryExecutor:
    """Executes a :class:`GroupByQuery` over a list of shard tables on a
    device mesh, merging per-shard partials inside the compiled program
    (``parallel.devicemerge``).

    Handles the mergeable aggregation set (``ops.MERGEABLE_OPS``); the worker
    falls back to per-shard execution for distinct-count ops and raw rows.
    """

    def __init__(self, mesh=None, axis_name="shards", timer=None,
                 host_limit_bytes=None, on_identity_recomputed=None):
        self._mesh = mesh
        self.axis_name = axis_name
        self.timer = timer
        #: called with the number of tables whose identity an execute*()
        #: asked the filesystem for itself, because its caller handed none
        #: down (the worker counts them:
        #: ``bqueryd_tpu_table_identity_total{source="recomputed"}``)
        self._on_identity_recomputed = on_identity_recomputed
        self._align_engine = None
        #: the physical kernel route the last execute() dispatched
        #: (post-guards) — the worker surfaces it as ``effective_strategy``
        #: in calc replies and the ``kernel`` trace span
        self.last_effective_strategy = None
        #: detail (BQUERYD_TPU_PROFILE=1 only, else None): the form the
        #: float64 sums of the last execute() took, ops.float_sum_route —
        #: the worker tags the ``aggregate_wait`` span ``float_sum`` with
        #: it, and a form other than dense gets the span ``float_sum_wait``
        self.last_float_sum = None
        #: how the last execute() merged partials across the mesh
        #: ("device" | "host") — the worker surfaces it as the reply
        #: envelope's ``merge_mode`` key
        self.last_merge_mode = None
        #: per-shard (decoded, skipped) chunk-prune counts of the last
        #: execute_dag() — the worker folds the totals into its chunk
        #: counters, mirroring opexec.DagExecutor._prune_counts
        self.last_prune_counts = []
        from bqueryd_tpu.ops.workingset import WorkingSet

        # the device-resident working-set layer (ops/workingset.py): LRU
        # segments bounded by measured memory (``host_limit_bytes``: the
        # worker's RSS limit), with hit/miss/eviction telemetry and
        # HBM-watermark pressure eviction.
        #   align:  (tables_key, groupby_cols) -> (dense codes per shard at
        #           _codes_dtype, combos, cards, key_values) — host side
        #   codes:  packed unmasked (or host-folded) group codes
        #           -> jax.Array [n_dev, width]
        #   blocks: packed wire-dtype measure columns, and stored-dtype
        #           filter columns (_fold_on_device) -> jax.Array
        # On CPU backends the device segments count against host RSS; the
        # RSS watchdog clears them before giving up (worker._check_mem)
        self.workingset = WorkingSet(host_limit_bytes=host_limit_bytes)
        self._align_cache = self.workingset.segment("align")
        self._hbm_cache = self.workingset.segment("blocks")
        self._codes_cache = self.workingset.segment("codes")

    def clear_caches(self):
        """Drop host alignment + HBM working-set segments (memory-watchdog
        hook)."""
        self.workingset.clear()
        if self._align_engine is not None:
            self._align_engine.clear_caches()

    @staticmethod
    def _map_shards(fn, items):
        """Map ``fn`` over shards on the shared pipeline pool (the
        decode/factorize/np work dominating cold alignment releases the
        GIL); sequential for single shards or one-thread pipelines.
        BQUERYD_TPU_ALIGN_THREADS caps the alignment fan-out specifically;
        BQUERYD_TPU_PIPELINE_THREADS sizes the pool itself."""
        from bqueryd_tpu.parallel import pipeline

        items = list(items)
        cap = os.environ.get("BQUERYD_TPU_ALIGN_THREADS")
        max_workers = int(cap) if cap is not None else len(items)
        return pipeline.map_ordered(fn, items, max_workers=max_workers)

    def _engine(self):
        """The engine used for alignment/key factorization — persistent so
        its factorize cache survives across queries (a fresh engine per
        execute() would re-factorize every alignment-cache miss)."""
        if self._align_engine is None:
            from bqueryd_tpu.models.query import QueryEngine

            self._align_engine = QueryEngine()
        return self._align_engine

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_mesh(axis_name=self.axis_name)
        return self._mesh

    def _phase(self, name):
        import contextlib

        from bqueryd_tpu.utils.tracing import trace_span

        # every phase is wall-timed (PhaseTimer -> reply phase_timings), a
        # distributed-tracing span when the timer carries a SpanRecorder
        # (obs.trace: "layout" surfaces as "h2d_transfer", "aggregate" as
        # "kernel" — the psum collective merge is fused into that compiled
        # program — and "collect" as "merge", the materialization of the
        # merged partials), and, under BQUERYD_TPU_PROFILE=1, a jax.profiler
        # TraceAnnotation tagged with the active trace_id so device
        # timelines line up with the RPC waterfall
        stack = contextlib.ExitStack()
        stack.enter_context(trace_span(name))
        if self.timer is not None:
            stack.enter_context(self.timer.phase(name))
        return stack

    @staticmethod
    def supports(query: GroupByQuery):
        from bqueryd_tpu import ops

        return query.aggregate and all(
            op in ops.MERGEABLE_OPS for op in query.ops
        )

    # -- key alignment (host-side, dictionary-sized work only) --------------
    def _global_key_space(self, tables, query, engine):
        """Remap every shard's per-column key codes into one global space.

        Returns ``(dense, combos, cards, key_values)`` where ``dense`` holds
        each shard's dense group codes at ``_codes_dtype(len(combos))`` —
        the width every consumer packs them at — ``combos`` is the sorted
        global composite-key array, ``cards`` the global per-column
        cardinalities, and ``key_values[col]`` the global per-column
        key-value arrays (indexable by unpacked codes).
        """
        n_cols = len(query.groupby_cols)
        shard_codes = [[] for _ in range(n_cols)]   # [col][shard] -> codes
        shard_values = [[] for _ in range(n_cols)]  # [col][shard] -> uniques
        # composite-sidecar stamps, captured BEFORE any key column is read
        # (TOCTOU note in storage/ctable.py): a mid-align shard rewrite then
        # stores a stale-stamped sidecar that future loads miss
        comp_stamps = [
            getattr(t, "composite_stamp", lambda cols: None)(
                query.groupby_cols
            )
            for t in tables
        ]
        # per-shard decode+factorize is embarrassingly parallel and the
        # native decode/factorize/np IO all release the GIL; the caches the
        # engine touches are lock-protected (utils/cache.BytesCappedCache)
        per_table = self._map_shards(
            lambda table: [
                engine._key_codes(table, col)
                for col in query.groupby_cols
            ],
            tables,
        )
        for results in per_table:
            for ci, (codes, values) in enumerate(results):
                shard_codes[ci].append(np.asarray(codes))
                shard_values[ci].append(np.asarray(values))

        cards = []
        global_values = []
        pos_maps = [[] for _ in range(n_cols)]  # [col][shard] -> local->global
        for ci in range(n_cols):
            allv = np.concatenate(shard_values[ci])
            gvals = np.unique(allv)
            # strip null VALUES (float NaN / datetime NaT) from the global
            # dictionary: the rows referencing them already carry poisoned
            # codes (-1, models/query._key_codes), so keeping the null entry
            # would only create a never-referenced dictionary slot — and the
            # single-key dense shortcut below needs "every dictionary entry
            # is an observed group" to hold exactly
            if gvals.dtype.kind == "f":
                gvals = gvals[~np.isnan(gvals)]
            elif gvals.dtype.kind == "M":
                gvals = gvals[~np.isnat(gvals)]
            cards.append(max(len(gvals), 1))
            global_values.append(gvals)
            for si in range(len(tables)):
                # local dictionary -> global position (dictionary-sized);
                # the rows-sized gather through it happens lazily so a
                # composite-sidecar hit below skips it entirely
                pos_maps[ci].append(
                    np.searchsorted(gvals, shard_values[ci][si])
                )

        def mapped_codes(si, ci):
            # gather local codes through the local->global map; null codes
            # (<0) stay null
            codes = shard_codes[ci][si]
            pos = pos_maps[ci][si]
            return np.where(
                codes >= 0, pos[np.clip(codes, 0, None)], np.int64(-1)
            )

        from bqueryd_tpu import ops

        if n_cols == 1:
            # dense shortcut: every global dictionary entry came from some
            # shard's factorize/dictionary, so it is observed in >=1 row —
            # the global codes are ALREADY dense positions in the sorted
            # dictionary.  Skips the former rows-scale unique, which was
            # ~80% of the cold align wall at bench shapes.
            combos = np.arange(len(global_values[0]), dtype=np.int64)
            cdt = _codes_dtype(max(len(combos), 1))
            dense = self._map_shards(
                lambda si: mapped_codes(si, 0).astype(cdt),
                range(len(tables)),
            )
            key_values = dict(zip(query.groupby_cols, global_values))
            return dense, combos, cards, key_values

        # guard BEFORE the composite sidecar loader: a sidecar stored by a
        # build predating the overflow guard holds silently WRAPPED packs
        # under the same dictionaries+cards digest — a cache hit must not
        # resurrect corrupt composites.  (The mesh alignment needs the
        # radix order, so past-int64 spaces degrade to the engine path at
        # the worker.)
        if ops.total_cardinality(cards) >= ops.MAX_COMPOSITE:
            raise ops.CompositeOverflow(
                "composite group-key space "
                f"{'x'.join(str(int(c)) for c in cards)} exceeds int64"
            )

        # multi-key: observed composites per shard via the native hash
        # factorizer (O(rows) per shard, small unique sets) instead of one
        # rows-scale sort-unique over the concatenated shards.  The result
        # is persisted next to the shard (composite sidecar) keyed by a
        # digest of the GLOBAL dictionaries + cardinalities: packed codes
        # depend on the whole shard set, so any set change invalidates.
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        h.update(np.asarray(cards, dtype=np.int64).tobytes())
        for g in global_values:
            a = np.asarray(g)
            if a.dtype == object:
                h.update(repr(a.tolist()).encode())
            else:
                h.update(a.dtype.str.encode())
                h.update(a.tobytes())
        digest = h.digest()

        def shard_composites(si):
            table = tables[si]
            loader = getattr(table, "composite_cache_load", None)
            if loader is not None:
                # validate against the PRE-READ stamp: shard_codes came from
                # those bytes, not from whatever the file holds now
                hit = loader(
                    query.groupby_cols, digest, stamp=comp_stamps[si]
                )
                if hit is not None:
                    return (
                        np.asarray(hit[0]),
                        np.asarray(hit[1], dtype=np.int64),
                    )
            packed = ops.pack_codes(
                [mapped_codes(si, ci) for ci in range(n_cols)], cards
            )
            inv, uniq = ops.factorize(packed)
            inv = np.asarray(inv)
            uniq = np.asarray(uniq, dtype=np.int64)
            storer = getattr(table, "composite_cache_store", None)
            if storer is not None and comp_stamps[si] is not None:
                storer(
                    query.groupby_cols, digest, inv, uniq,
                    stamp=comp_stamps[si],
                )
            return inv, uniq

        composites = self._map_shards(shard_composites, range(len(tables)))
        local_inverse = [c[0] for c in composites]
        local_uniques = [c[1] for c in composites]
        observed = [u[u >= 0] for u in local_uniques]
        observed = [o for o in observed if len(o)]
        combos = (
            np.unique(np.concatenate(observed))
            if observed
            else np.empty(0, dtype=np.int64)
        )
        # dense codes ride the per-shard dictionary: map each shard's few
        # observed composites into the sorted global combos, then gather
        cdt = _codes_dtype(max(len(combos), 1))
        dense = []
        for inv, uniq in zip(local_inverse, local_uniques):
            lut = np.searchsorted(combos, np.clip(uniq, 0, None)).astype(cdt)
            lut[uniq < 0] = -1
            dense.append(lut[inv])
        key_values = dict(zip(query.groupby_cols, global_values))
        return dense, combos, cards, key_values

    # -- device layout ------------------------------------------------------
    @staticmethod
    def _pack(arrays, n_devices, pad, dtype=None):
        """Concat shard arrays and split evenly into ``[n_devices, width]``.

        Because every row carries a GLOBAL dense code, any row partition is
        valid — shard boundaries don't matter to the psum merge.  An even
        split beats greedy shard->device packing: devices are perfectly
        balanced and a single big shard still uses the whole mesh.  ``dtype``
        defaults to the common (widest) dtype of the inputs so mixed-width
        shards never silently wrap."""
        if dtype is None:
            dtype = (
                np.result_type(*[a.dtype for a in arrays])
                if len(arrays) > 1
                else arrays[0].dtype
            )
        from bqueryd_tpu import ops

        total = sum(len(a) for a in arrays)
        # bucketed per-device width (ops.program_bucket): row-count drift
        # across data refreshes reuses the compiled program; padded rows
        # carry the pad code (-1 for codes) and drop from every reduction
        width = ops.program_bucket(
            max(-(-total // n_devices), 1), fine=True
        )
        out = np.full(n_devices * width, pad, dtype=dtype)
        off = 0
        for arr in arrays:
            out[off : off + len(arr)] = arr
            off += len(arr)
        return out.reshape(n_devices, width)

    def _fold_on_device(self, tables, unmasked_key, dense, fold_terms,
                        sharding):
        """The folded codes of a filtered query, made on the device: one
        small program (``_fold_program``) over the resident UNMASKED codes
        of the query's keys — the entry an unfiltered query of the same keys
        uses, under ``unmasked_key`` — and the resident filter columns,
        packed like the codes (same concat order, same bucketed width: row
        *i* of a column is row *i* of the codes) and kept in the ``blocks``
        segment at their STORED dtype, which is the dtype each shard's host
        mask compares in (a measure block may be narrowed by value range,
        ``_wire_dtype``; a constant outside the narrow range would wrap
        there).  Both are built once per table set and are all the state
        there is: a steady fresh filter costs the dispatch, and its folded
        codes are returned, not cached.  Inside the ``layout`` phase."""
        from bqueryd_tpu.parallel import pipeline

        tables_key, n_dev = unmasked_key[0], unmasked_key[-1]
        unmasked = self._codes_cache.get(unmasked_key)
        if unmasked is None:
            self.workingset.evict_under_pressure()
            with pipeline.stage("align"), tracing.detail(
                "layout_pack", self.timer
            ):
                packed = self._pack(dense, n_dev, -1)
            with pipeline.stage("h2d"), tracing.detail(
                "layout_h2d", self.timer
            ):
                unmasked = _put(packed, sharding)
            self._codes_cache.put(unmasked_key, unmasked)
        term_columns, term_ops, constants = zip(*fold_terms)
        columns = []
        for column in term_columns:
            fkey = (tables_key, "where", column, n_dev)
            arr = self._hbm_cache.get(fkey)
            if arr is None:
                self.workingset.evict_under_pressure()
                with pipeline.stage("decode"):
                    with tracing.detail("layout_columns", self.timer):
                        cols = [
                            np.asarray(t.column_raw(column)) for t in tables
                        ]
                    with tracing.detail("layout_pack", self.timer):
                        # the pad value is free: pad rows' codes are -1
                        packed = self._pack(cols, n_dev, 0)
                with pipeline.stage("h2d"), tracing.detail(
                    "layout_h2d", self.timer
                ):
                    arr = _put(packed, sharding)
                self._hbm_cache.put(fkey, arr)
            columns.append(arr)
        program = _fold_program(term_ops, sharding)
        # the span times a dispatch that returns at once; ``site`` tells
        # the device trace which fold ran
        with tracing.detail("layout_fold", self.timer, site="device"):
            return program(unmasked, tuple(columns), constants)

    def _tables_key(self, tables, identities):
        """The first element of every cache key of a unit: the identity of
        each table, in table order.  A worker hands down what its open
        read (``identities``, one per table: the value ``_table_key``
        would return, read once per unit); for bare tables — tests, a
        caller with no open of its own — each is asked of the filesystem
        here, as before."""
        with tracing.detail("table_keys", self.timer):
            if identities is not None:
                return tuple(identities)
            if self._on_identity_recomputed is not None:
                self._on_identity_recomputed(len(tables))
            return tuple(_table_key(t) for t in tables)

    # -- execution ----------------------------------------------------------
    def execute(self, tables, query: GroupByQuery,
                strategy=None, identities=None) -> ResultPayload:
        """``strategy`` is the planner's kernel-route hint, threaded into the
        mesh program's ``partial_tables`` call (and its trace cache key);
        None/"auto" keeps the dispatcher's own adaptive choice.
        ``identities``: see :meth:`_tables_key`."""
        from bqueryd_tpu import chaos, ops

        # chaos site worker.device: a transient DeviceBusyError raised here
        # rides the same recovery seam as a real transient device fault —
        # the worker's handler marks the ErrorMessage transient and the
        # controller fails the shard over to a replica holder.  The
        # enabled() pre-check keeps the disarmed hot path from paying the
        # signature stringification just to hand fire() a discarded ctx
        if chaos.enabled():
            chaos.fire(
                "worker.device",
                n_tables=len(tables),
                signature=str(query.signature())[:120],
            )
        self.last_effective_strategy = None  # set at the kernel dispatch
        self.last_float_sum = None           # ditto, under the switch
        self.last_merge_mode = None          # set once the mode resolves
        if strategy in (None, "auto", "host"):
            # "host" is meaningless inside a mesh program; the worker should
            # not have routed such a query here, but degrade to auto rather
            # than refuse
            strategy = None

        if not self.supports(query):
            raise ValueError(
                "MeshQueryExecutor handles mergeable aggregations only; "
                "route distinct-count / raw-rows queries per shard"
            )
        # datetime measures ride the mesh as raw int64 with NaT (int64 min)
        # declared as a null sentinel so NaT rows skip counts and extrema
        # exactly like float NaNs (pandas semantics).  Sums/means of
        # datetimes are rejected HERE, before any alignment/decode/upload
        # work is spent on an invalid query.
        measure_kinds = tuple(
            _measure_kind(tables, col) for col in query.in_cols
        )
        for col, kind, op in zip(query.in_cols, measure_kinds, query.ops):
            if kind == "datetime" and op in ("sum", "mean"):
                raise ValueError(
                    f"{op!r} is not defined for datetime column {col!r}"
                )
        engine = self._engine()

        with self._phase("prune"):
            if query.where_terms:
                tables, identities = _matching_shards(
                    tables, identities, query.where_terms
                )
        if not tables:
            return ResultPayload.empty()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bqueryd_tpu.parallel import pipeline

        tables_key = self._tables_key(tables, identities)
        cols_key = tuple(query.groupby_cols)
        mesh = self.mesh
        n_dev = mesh.devices.size
        from bqueryd_tpu.parallel import devicemerge

        # the traced cross-device merge for this query: span-owned
        # reduce-scatter by default, the hostmerge fallback under the
        # BQUERYD_TPU_DEVICE_MERGE=0 kill switch, replicated psum on
        # multi-host pods (devicemerge.resolve_mode)
        merge_mode = devicemerge.resolve_mode()
        self.last_merge_mode = (
            "host" if merge_mode == devicemerge.MODE_HOST else "device"
        )
        sharding = NamedSharding(mesh, P(self.axis_name, None))
        codes_key = (
            tables_key, "codes", cols_key, _where_signature(query), n_dev,
        )

        # fused multi-agg gather: sum+count+mean over the same column pack,
        # upload and feed ONE device block; measure_index maps each agg
        # back to its slot inside the compiled program, so codes are
        # gathered against each distinct column exactly once
        unique_cols = list(dict.fromkeys(query.in_cols))
        measure_index = tuple(
            unique_cols.index(col) for col in query.in_cols
        )
        missing_cols = [
            col for col in unique_cols
            if (tables_key, "col", col, n_dev) not in self._hbm_cache
        ]
        align_warm = (tables_key, cols_key) in self._align_cache

        # shed LRU device cache BEFORE this query adds residency, while the
        # PR-3 HBM watermark sample still reflects the previous steady state
        # (evicting after the allocation failed would be a wedge, not a
        # plan).  Only on the branches that add residency — here a missing
        # column; below a first build of codes or of a filter column: a
        # warm query, and a fresh filter that folds on the device, add
        # nothing, and the memory sample costs a device.memory_stats()
        # round-trip that must never tax steady-state latency.
        if missing_cols:
            self.workingset.evict_under_pressure()

        # chunk-decode prefetch (pipeline stage 1): fire storage decode of
        # the cache-missing measure columns on the pipeline pool so decode
        # overlaps the mask/fold + codes-H2D work below.  Deferred until
        # AFTER alignment when the alignment is cold: align's own per-shard
        # fan-out needs the pool, and a FIFO pool would drain these decode
        # jobs first, serializing decode ahead of align instead of
        # overlapping either.  (Firing nowhere on the cold path was the
        # 0.115 cold storage-decode hit rate: the depth-2 column build paid
        # every decode inline with nothing warmed.)  The prefetch decodes
        # through ``ctable.column_raw`` on the SAME table instances the
        # build loop probes, so the warmed entries land under the content
        # keys the build path reads.
        prefetch = {}

        def _prefetch_missing():
            if pipeline.pipeline_threads() <= 1:
                return
            for col in missing_cols:
                futs = []
                for t in tables:
                    warm = getattr(t, "prefetch", None)
                    if warm is not None:
                        futs.extend(warm([col]))
                if futs:
                    prefetch[col] = futs

        if align_warm:
            _prefetch_missing()

        with self._phase("align"), pipeline.stage("align"):
            cached = self._align_cache.get((tables_key, cols_key))
            if cached is None:
                dense, combos, cards, key_values = self._global_key_space(
                    tables, query, engine
                )
                self._align_cache.put(
                    (tables_key, cols_key),
                    (dense, combos, cards, key_values),
                    nbytes=sum(d.nbytes for d in dense)
                    + combos.nbytes
                    + sum(v.nbytes for v in key_values.values()),
                )
            else:
                dense, combos, cards, key_values = cached
            n_groups = max(len(combos), 1)

        if not align_warm:
            # cold-align queries fire the measure prefetch HERE, once the
            # align fan-out has released the pool: decode overlaps the
            # mask/fold/pack + codes-H2D work below instead of serializing
            # inside the column build
            _prefetch_missing()

        codes_d = self._codes_cache.get(codes_key)
        if codes_d is None:
            # On a cache hit the whole filter evaluation is skipped — the
            # folded codes ARE the filter.  A filter of scalar compares on
            # numeric/datetime columns folds ON THE DEVICE, from resident
            # unmasked codes and resident filter columns (_fold_on_device):
            # nothing per row crosses the host and nothing is cached, so
            # such a query misses here every time and hits the unmasked
            # entry there.  Every other filter: masks + fold + pack + H2D on
            # the host, as below, cached under ``codes_key``.
            with self._phase("mask"):
                # all the mask-making the device path has: its constants
                fold_terms = _device_fold_terms(tables, query)
                masks = []
                if fold_terms is None:
                    for table in tables:
                        mask = ops.build_mask(table, query.where_terms)
                        if query.expand_filter_column:
                            # cached, with nulls-are-a-basket semantics
                            bcodes, buniques = engine._basket_codes(
                                table, query.expand_filter_column
                            )
                            mask = ops.expand_mask_by_group(
                                bcodes, mask, n_groups=len(buniques)
                            )
                        masks.append(
                            None if mask is None else np.asarray(mask)
                        )
            with self._phase("layout"):
                if fold_terms is not None:
                    codes_d = self._fold_on_device(
                        tables,
                        (tables_key, "codes", cols_key,
                         _where_signature(None), n_dev),
                        dense, fold_terms, sharding,
                    )
                else:
                    # a first build of these codes adds residency
                    self.workingset.evict_under_pressure()
                    # fold the row mask into the codes: masked-out rows
                    # become null (code -1) and vanish from every segment
                    # reduction.  Folds into fresh arrays — cached dense
                    # stays unmasked.
                    with pipeline.stage("align"):
                        with tracing.detail(
                            "layout_fold", self.timer, site="host"
                        ):
                            folded = [
                                d if mask is None
                                else np.where(mask, d, d.dtype.type(-1))
                                for d, mask in zip(dense, masks)
                            ]
                        with tracing.detail("layout_pack", self.timer):
                            packed = self._pack(folded, n_dev, -1)
                    with pipeline.stage("h2d"), tracing.detail(
                        "layout_h2d", self.timer
                    ):
                        codes_d = _put(packed, sharding)
                    self._codes_cache.put(codes_key, codes_d)

        with self._phase("layout"):
            def build_packed(col, timer=None):
                # ``timer``: given by the loop thread's inline call alone —
                # a build on the pool records no detail span
                # wait for this column's prefetched decodes first: they
                # populate the storage cache, and racing a duplicate decode
                # here would burn the cores the pipeline is trying to share
                for fut in prefetch.get(col, ()):
                    fut.result()
                with pipeline.stage("decode"):
                    # decode (C++ chunk threads, GIL released) + narrow +
                    # pack into the [n_dev, width] device layout
                    with tracing.detail("layout_columns", timer):
                        wire = (
                            _wire_dtype(tables, col)
                            or _stored_dtype(tables, col)
                        )
                        cols = [
                            np.asarray(t.column_raw(col)) for t in tables
                        ]
                        if wire is not None:
                            cols = [c.astype(wire, copy=False) for c in cols]
                    with tracing.detail("layout_pack", timer):
                        return self._pack(cols, n_dev, 0, dtype=wire)

            # cold path with several columns: overlap the NEXT column's
            # decode+pack with the CURRENT column's host->device transfer
            # (the two dominate cold latency and use disjoint resources)
            missing = [
                col
                for col in unique_cols
                if (tables_key, "col", col, n_dev) not in self._hbm_cache
            ]
            futures = {}
            use_pool = len(missing) > 1 and pipeline.pipeline_threads() > 1
            missing_iter = iter(missing)

            def submit_next():
                for c in missing_iter:
                    futures[c] = pipeline.submit(build_packed, c)
                    return

            if use_pool:
                # prime ONE build ahead of the put loop; the next is
                # submitted as each is consumed — exactly one build in
                # flight plus the column being uploaded, so peak host
                # residency stays ~2 packed columns however many are
                # missing (priming two would run both concurrently on the
                # shared pool: ~3 resident)
                submit_next()
            measures_d = []
            for col in unique_cols:
                mkey = (tables_key, "col", col, n_dev)
                arr = self._hbm_cache.get(mkey)
                if arr is None:
                    if col in futures:
                        with tracing.detail("layout_columns", self.timer):
                            packed = futures.pop(col).result()
                        submit_next()
                    else:
                        packed = build_packed(col, self.timer)
                    with pipeline.stage("h2d"), tracing.detail(
                        "layout_h2d", self.timer
                    ):
                        arr = _put(packed, sharding)
                    self._hbm_cache.put(mkey, arr)
                measures_d.append(arr)

        with self._phase("aggregate"), pipeline.stage("kernel"):
            sentinels = tuple(
                np.iinfo(np.int64).min if k == "datetime" else None
                for k in measure_kinds
            )
            # returns host numpy partials; with packed fetch (default) the
            # whole merged pytree comes back as ONE device buffer — per-leaf
            # pulls cost one D2H round-trip each
            # the program computes over the BUCKETED group count (shape
            # reuse across cardinality drift, ops.program_bucket); padded
            # groups have zero rows and are sliced off right below, on host
            n_prog = ops.program_bucket(n_groups)
            # the physical route this dispatch takes: reported as
            # effective_strategy
            per_agg_d = tuple(measures_d[i] for i in measure_index)
            # normalize a forced route BEFORE predicting/labelling it: one
            # the guards would normalize inside _mesh_partials (e.g.
            # "scatter" on a backend whose auto dispatch internally sorts)
            # must not be reported as a route the program never ran
            strategy = _effective_mesh_strategy(
                strategy, tuple(query.ops), n_prog, per_agg_d,
                int(codes_d.shape[1]),
            )
            route = ops.kernel_route(
                strategy, per_agg_d, tuple(query.ops),
                int(codes_d.shape[1]), n_prog,
            )
            self.last_effective_strategy = route
            if tracing.detail_enabled():
                self.last_float_sum = ops.float_sum_route(
                    strategy, per_agg_d, tuple(query.ops),
                    int(codes_d.shape[1]), n_prog,
                )
            # a transiently-classed runtime error (_TRANSIENT_STATUSES: a
            # preempted or briefly unavailable device) gets one retry that
            # keeps the on-device merge path; a second failure propagates
            # to the worker, which degrades to the per-shard engine path.
            # Either way the firing is counted (devicehealth.note_degrade)
            for attempt in range(2):
                try:
                    merged = _mesh_partials(
                        mesh, self.axis_name, query.ops, n_prog,
                        codes_d, tuple(measures_d),
                        null_sentinels=sentinels,
                        strategy=strategy,
                        measure_index=measure_index,
                        merge_mode=merge_mode,
                        timer=self.timer,
                        float_form=self.last_float_sum,
                    )
                    break
                except jax.errors.JaxRuntimeError as exc:
                    # deterministic failures (INVALID_ARGUMENT, device OOM)
                    # would fail identically: propagate at once and let the
                    # worker degrade, keeping the sleep out of their path
                    # (and out of the aggregate-phase timing)
                    if attempt or not _transient_status(exc):
                        raise
                    devicehealth.note_degrade("inplace_retry")
                    time.sleep(0.5)
            if n_prog != n_groups:
                import jax as _jax

                # group axis is LAST: host-mode partials carry a leading
                # per-device axis, merged tables are flat
                merged = _jax.tree_util.tree_map(
                    lambda a: a[..., :n_groups], merged
                )

        with self._phase("collect"), pipeline.stage("merge"):
            return self._finish_collect(
                merged, merge_mode, int(n_dev), query, tables,
                combos, cards, key_values, measure_kinds,
            )

    def _collect_payload(self, partial_table, query, tables, combos, cards,
                         key_values, measure_kinds):
        """One merged (or single-device) partial table -> ResultPayload
        keyed by actual key values."""
        from bqueryd_tpu import ops

        rows = partial_table["rows"]
        present = rows > 0
        combos_present = combos[present]
        if len(query.groupby_cols) == 1:
            key_codes = [combos_present]
        else:
            key_codes = ops.unpack_codes(combos_present, cards)
        keys = {}
        for col, codes_g in zip(query.groupby_cols, key_codes):
            idx = np.asarray(codes_g, dtype=np.int64)
            keys[col] = key_values[col][idx]
        aggs = []
        for in_col, part in zip(query.in_cols, partial_table["aggs"]):
            stored = _stored_dtype(tables, in_col)
            selected = {}
            for k, v in part.items():
                v = v[present]
                # min/max partials computed on a narrowed wire dtype go
                # back to the column's stored dtype
                if (
                    k in ("min", "max")
                    and stored is not None
                    and v.dtype != stored
                    and stored.kind in "iu"
                ):
                    v = v.astype(stored)
                selected[k] = v
            aggs.append(selected)
        return ResultPayload.partials(
            key_cols=query.groupby_cols,
            keys=keys,
            rows=rows[present],
            aggs=aggs,
            ops=query.ops,
            out_cols=query.out_cols,
            value_kinds=list(measure_kinds),
        )

    def _finish_collect(self, merged, merge_mode, n_dev, query, tables,
                        combos, cards, key_values, measure_kinds):
        """Merged partials (one query's pytree) -> its ResultPayload, per
        merge mode.  Host mode re-merges the per-device tables with the
        always-correct value-keyed merge — bit-identical aggregates,
        host-gather economics."""
        import jax

        from bqueryd_tpu.parallel import devicemerge

        if merge_mode == devicemerge.MODE_HOST:
            from bqueryd_tpu.parallel import hostmerge

            payloads = [
                self._collect_payload(
                    jax.tree_util.tree_map(lambda a: a[d], merged),
                    query, tables, combos, cards, key_values, measure_kinds,
                )
                for d in range(int(n_dev))
            ]
            return ResultPayload(hostmerge.merge_payloads(payloads))
        return self._collect_payload(
            merged, query, tables, combos, cards, key_values, measure_kinds,
        )

    # -- shared-scan bundles -------------------------------------------------
    def execute_bundle(self, tables, queries, strategy=None,
                       identities=None):
        """Shared-scan execution of a compatible query bundle: every query
        scans the same ``tables`` with the same group-key columns; measures
        and filters may differ per member.  One decode/align/factorize pass,
        one (unmasked) codes upload, one deduplicated union measure upload,
        one stacked-mask H2D, and ONE mesh program whose per-member partial
        tables merge in one collective pass.  Returns one
        :class:`ResultPayload` per query, input order.

        Parity contract: each member's partials are emitted by the same
        per-member :func:`ops.partial_tables` dispatch its solo execution
        would run (the mask rides the kernel's ``mask=`` argument, which
        zeroes exactly the contributions code-folding would drop), so
        integer aggregates are bit-identical to unfused execution and float
        aggregates differ only by kernel-route reassociation."""
        from bqueryd_tpu import chaos, ops
        from bqueryd_tpu.models.query import freeze_value

        if not queries:
            return []
        if chaos.enabled():
            chaos.fire(
                "worker.device",
                n_tables=len(tables),
                signature=f"bundle:{len(queries)}",
            )
        self.last_effective_strategy = None
        self.last_merge_mode = None
        if strategy in (None, "auto", "host"):
            strategy = None
        gcols = tuple(queries[0].groupby_cols)
        for query in queries:
            if tuple(query.groupby_cols) != gcols:
                raise ValueError(
                    "bundle members must share group-key columns"
                )
            if not self.supports(query):
                raise ValueError(
                    "bundle members must be mergeable aggregations"
                )
        # the union measure upload: every DISTINCT column across the bundle,
        # first-seen order; per-member aggs map onto slots in this union
        union_cols = list(
            dict.fromkeys(c for q in queries for c in q.in_cols)
        )
        union_kinds = tuple(
            _measure_kind(tables, col) for col in union_cols
        )
        kind_of = dict(zip(union_cols, union_kinds))
        for query in queries:
            for col, op in zip(query.in_cols, query.ops):
                if kind_of[col] == "datetime" and op in ("sum", "mean"):
                    raise ValueError(
                        f"{op!r} is not defined for datetime column {col!r}"
                    )
        engine = self._engine()

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bqueryd_tpu.parallel import devicemerge, pipeline

        tables_key = self._tables_key(tables, identities)
        cols_key = tuple(gcols)
        mesh = self.mesh
        n_dev = mesh.devices.size
        merge_mode = devicemerge.resolve_mode()
        self.last_merge_mode = (
            "host" if merge_mode == devicemerge.MODE_HOST else "device"
        )
        sharding = NamedSharding(mesh, P(self.axis_name, None))
        # the bundle's codes ride UNMASKED (each member's filter applies on
        # device through the stacked mask axis) — which is exactly the codes
        # entry an unfiltered single query folds, so the cache key is shared
        # with (and warms) the plain single-query path
        codes_key = (
            tables_key, "codes", cols_key, (freeze_value([]), None), n_dev,
        )
        missing_cols = [
            col for col in union_cols
            if (tables_key, "col", col, n_dev) not in self._hbm_cache
        ]
        align_warm = (tables_key, cols_key) in self._align_cache
        codes_warm = codes_key in self._codes_cache
        if missing_cols or not codes_warm:
            self.workingset.evict_under_pressure()

        # prefetch depth = the whole bundle's union: every member's missing
        # measure column fires its storage decode on the pool up front, so
        # the shared pass never pays a member's decode inline (the single-
        # query path prefetches only its own columns)
        prefetch = {}

        def _prefetch_missing():
            if pipeline.pipeline_threads() <= 1:
                return
            for col in missing_cols:
                futs = []
                for t in tables:
                    warm = getattr(t, "prefetch", None)
                    if warm is not None:
                        futs.extend(warm([col]))
                if futs:
                    prefetch[col] = futs

        if align_warm:
            _prefetch_missing()

        with self._phase("align"), pipeline.stage("align"):
            cached = self._align_cache.get((tables_key, cols_key))
            if cached is None:
                dense, combos, cards, key_values = self._global_key_space(
                    tables, queries[0], engine
                )
                self._align_cache.put(
                    (tables_key, cols_key),
                    (dense, combos, cards, key_values),
                    nbytes=sum(d.nbytes for d in dense)
                    + combos.nbytes
                    + sum(v.nbytes for v in key_values.values()),
                )
            else:
                dense, combos, cards, key_values = cached
            n_groups = max(len(combos), 1)

        if not align_warm:
            _prefetch_missing()

        codes_d = self._codes_cache.get(codes_key)
        if codes_d is None:
            with self._phase("layout"):
                with pipeline.stage("align"), tracing.detail(
                    "layout_pack", self.timer
                ):
                    packed = self._pack(dense, n_dev, -1)
                with pipeline.stage("h2d"), tracing.detail(
                    "layout_h2d", self.timer
                ):
                    codes_d = _put(packed, sharding)
                self._codes_cache.put(codes_key, codes_d)

        # stacked per-member masks: one row per member that filters, one
        # H2D for the whole stack.  Members without filters index None and
        # feed the kernel mask=None — the bit-identical solo form.
        mask_rows = []
        mask_idx_of = {}
        with self._phase("mask"):
            for qi, query in enumerate(queries):
                if not query.where_terms:
                    continue
                shard_masks = []
                for table in tables:
                    mask = ops.build_mask(table, query.where_terms)
                    shard_masks.append(
                        np.ones(int(table.nrows), dtype=bool)
                        if mask is None else np.asarray(mask)
                    )
                mask_idx_of[qi] = len(mask_rows)
                with tracing.detail("layout_pack", self.timer):
                    mask_rows.append(
                        self._pack(shard_masks, n_dev, False, dtype=np.bool_)
                    )
        masks_d = None
        if mask_rows:
            with self._phase("layout"), pipeline.stage("h2d"):
                with tracing.detail("layout_h2d", self.timer):
                    masks_d = _put(
                        np.stack(mask_rows),
                        NamedSharding(mesh, P(None, self.axis_name, None)),
                    )

        with self._phase("layout"):
            def build_packed(col, timer=None):
                for fut in prefetch.get(col, ()):
                    fut.result()
                with pipeline.stage("decode"):
                    with tracing.detail("layout_columns", timer):
                        wire = (
                            _wire_dtype(tables, col)
                            or _stored_dtype(tables, col)
                        )
                        cols = [
                            np.asarray(t.column_raw(col)) for t in tables
                        ]
                        if wire is not None:
                            cols = [c.astype(wire, copy=False) for c in cols]
                    with tracing.detail("layout_pack", timer):
                        return self._pack(cols, n_dev, 0, dtype=wire)

            missing = [
                col
                for col in union_cols
                if (tables_key, "col", col, n_dev) not in self._hbm_cache
            ]
            futures = {}
            use_pool = len(missing) > 1 and pipeline.pipeline_threads() > 1
            missing_iter = iter(missing)

            def submit_next():
                for c in missing_iter:
                    futures[c] = pipeline.submit(build_packed, c)
                    return

            if use_pool:
                submit_next()
            measures_d = []
            for col in union_cols:
                mkey = (tables_key, "col", col, n_dev)
                arr = self._hbm_cache.get(mkey)
                if arr is None:
                    if col in futures:
                        with tracing.detail("layout_columns", self.timer):
                            packed = futures.pop(col).result()
                        submit_next()
                    else:
                        packed = build_packed(col, self.timer)
                    with pipeline.stage("h2d"), tracing.detail(
                        "layout_h2d", self.timer
                    ):
                        arr = _put(packed, sharding)
                    self._hbm_cache.put(mkey, arr)
                measures_d.append(arr)

        slot_of = {col: i for i, col in enumerate(union_cols)}
        sentinels = tuple(
            np.iinfo(np.int64).min if k == "datetime" else None
            for k in union_kinds
        )
        member_specs = tuple(
            (
                mask_idx_of.get(qi),
                tuple(
                    (slot_of[col], op)
                    for col, op in zip(query.in_cols, query.ops)
                ),
            )
            for qi, query in enumerate(queries)
        )

        with self._phase("aggregate"), pipeline.stage("kernel"):
            n_prog = ops.program_bucket(n_groups)
            # route label: on CPU the shared-scan kernel is the batched
            # scatter family regardless of any hint; on accelerators the
            # bundle runs per-member partial_tables dispatches (the
            # batched form would be the emulated wide scatter — see
            # ops.bundle_partial_tables), where the first member's
            # predicted route speaks for the bundle
            import jax as _jax

            if _jax.default_backend() == "cpu":
                self.last_effective_strategy = "scatter"
            else:
                first = queries[0]
                self.last_effective_strategy = ops.kernel_route(
                    strategy,
                    tuple(measures_d[slot_of[c]] for c in first.in_cols),
                    tuple(first.ops), int(codes_d.shape[1]), n_prog,
                )
            merged_members = _mesh_bundle_partials(
                mesh, self.axis_name, n_prog, codes_d, masks_d,
                tuple(measures_d), member_specs, sentinels,
                strategy=strategy, merge_mode=merge_mode,
                timer=self.timer,
            )
            if n_prog != n_groups:
                merged_members = jax.tree_util.tree_map(
                    lambda a: a[..., :n_groups], merged_members
                )

        with self._phase("collect"), pipeline.stage("merge"):
            out = []
            for query, merged in zip(queries, merged_members):
                member_kinds = [kind_of[c] for c in query.in_cols]
                out.append(
                    self._finish_collect(
                        merged, merge_mode, int(n_dev), query, tables,
                        combos, cards, key_values, member_kinds,
                    )
                )
            return out

    # -- operator-DAG fast path ----------------------------------------------
    def execute_dag(self, tables, dag, identities=None):
        """Batched mesh execution of an EXTENDED operator DAG (joins /
        top-k / quantile sketches / window rollups): one decode/align/H2D
        pass over the whole shard group — join-probe gathers, window-bucket
        derived keys and the folded composite codes all land in the same
        content-keyed working-set segments the classic path uses — one
        compiled mesh program emitting every aggregation's partial state,
        and the PR-7 span-owned device-resident merge: classic GroupAgg
        partials and sketch bucket grids reduce-scatter (associative
        bucket-count addition), top-k dense tables all-gather + re-select
        on device, so only the final merged table leaves HBM.  Returns ONE
        :class:`ResultPayload` for the whole group (``merge_mode``
        "device").

        Raises :class:`DagFastPathUnsupported` for shapes the mesh cannot
        merge (count_distinct sets, raw rows, object-dtype derived
        measures, an over-budget sketch grid, composite overflow, the
        ``BQUERYD_TPU_DEVICE_MERGE=0`` kill switch): the worker then falls
        back to the PR-13 per-shard pipeline + host value-keyed merge.
        Parity vs that fallback: integer aggregates, top-k value multisets
        and sketch buckets are bit-identical; float sums/means differ only
        by reassociation (the same tolerance class as every kernel route
        choice); query-shape validation errors (:class:`DagValidationError`,
        datetime sums) raise identically on both routes."""
        from bqueryd_tpu import chaos, ops
        from bqueryd_tpu.models.query import (
            MERGEABLE_OPS,
            ResultPayload,
        )
        from bqueryd_tpu.parallel import devicemerge, opexec, pipeline
        from bqueryd_tpu.plan.dag import DagValidationError, parse_op

        if chaos.enabled():
            chaos.fire(
                "worker.device",
                n_tables=len(tables),
                signature=f"dag:{str(dag.signature())[:100]}",
            )
        self.last_effective_strategy = None
        self.last_merge_mode = None
        self.last_prune_counts = []
        merge_mode = devicemerge.resolve_mode()
        if merge_mode == devicemerge.MODE_HOST:
            raise DagFastPathUnsupported(
                "BQUERYD_TPU_DEVICE_MERGE=0: merge stays host-side"
            )
        if not dag.aggregate_rows:
            raise DagFastPathUnsupported("raw-rows DAGs dispatch per shard")
        parsed = [parse_op(a[1]) for a in dag.aggs]
        classic_idx, topk_idx, sketch_idx = [], [], []
        for i, p in enumerate(parsed):
            if p[0] in MERGEABLE_OPS:
                classic_idx.append(i)
            elif p[0] == "topk":
                topk_idx.append(i)
            elif p[0] == "quantile":
                sketch_idx.append(i)
            else:
                raise DagFastPathUnsupported(
                    f"op {dag.aggs[i][1]!r} has no device-mergeable partial"
                )

        with self._phase("prune"):
            if dag.scan.pushdown:
                tables, identities = _matching_shards(
                    tables, identities, dag.scan.pushdown
                )
                pruned = []
                for t in tables:
                    view, decoded, skipped = ops.chunk_pruned_table(
                        t, dag.scan.pushdown
                    )
                    pruned.append(view)
                    if decoded or skipped:
                        self.last_prune_counts.append((decoded, skipped))
                identities = view_identities(
                    identities, tables, pruned, self._on_identity_recomputed
                )
                tables = pruned
        if not tables:
            return ResultPayload.empty()

        first = tables[0]

        def col_source(col):
            if dag.window is not None and col == dag.window.alias:
                return "window"
            if dag.join is not None and col in dag.join.select:
                return "join"
            if col not in first:
                raise DagValidationError(
                    f"column {col!r} is not a fact column, a join-selected "
                    f"column, or the window alias"
                )
            return "fact"

        from bqueryd_tpu.parallel.opexec import NAT_SENTINEL

        unique_cols = list(dict.fromkeys(a[0] for a in dag.aggs))
        kind_of, sentinel_of = {}, {}
        for col in unique_cols:
            src = col_source(col)
            if src == "window":
                kind_of[col], sentinel_of[col] = "datetime", NAT_SENTINEL
            elif src == "join":
                dimv = np.asarray(dag.join.table[col])
                if dimv.dtype == object:
                    raise DagFastPathUnsupported(
                        f"object-dtype join measure {col!r}"
                    )
                # the ONE shared copy of the dim-measure dtype rules
                # (opexec.dim_measure_kind): leg parity depends on it
                sentinel_of[col], kind_of[col] = opexec.dim_measure_kind(
                    dimv.dtype
                )
            else:
                kind_of[col] = _measure_kind(tables, col)
                sentinel_of[col] = (
                    NAT_SENTINEL if kind_of[col] == "datetime" else None
                )
        # query-shape validation, identical (message and class) to the
        # per-shard route so the fast path never masks or changes an error
        for i, (in_col, op, _out) in enumerate(dag.aggs):
            kind = parsed[i][0]
            if kind in ("sum", "mean") and kind_of[in_col] == "datetime":
                raise ValueError(
                    f"{kind!r} is not defined for datetime column {in_col!r}"
                )
            src = col_source(in_col)
            is_dict = src == "fact" and first.kind(in_col) == "dict"
            if kind == "topk" and is_dict:
                raise DagValidationError(
                    f"topk measure {in_col!r} must be numeric or "
                    f"datetime, not strings"
                )
            if kind == "quantile" and (
                is_dict or sentinel_of[in_col] is not None
            ):
                raise DagValidationError(
                    f"quantile measure {in_col!r} must be numeric "
                    f"(strings/datetimes have no sketch ordering)"
                )

        engine = self._engine()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        tables_key = self._tables_key(tables, identities)
        derive_sig = dag.derive_signature()
        mesh = self.mesh
        n_dev = mesh.devices.size
        self.last_merge_mode = "device"
        sharding = NamedSharding(mesh, P(self.axis_name, None))

        # per-shard derivations (join probe / window buckets / per-key
        # codes) — the EXACT per-shard host code of the fallback route
        # (opexec.DagExecutor), content-keyed in the align segment so a
        # repeat query (same derivations, any measures) skips them all
        dexec = opexec.DagExecutor(engine)

        def derive(shard):
            table, table_key = shard
            dkey = (table_key, "dagderive", derive_sig)
            hit = self._align_cache.get(dkey)
            if hit is not None:
                return hit
            state = opexec._ShardState(table, dag)
            mask = ops.build_mask(table, dag.scan.pushdown)
            mask = None if mask is None else np.asarray(mask, dtype=bool)
            if dag.join is not None:
                mask = dexec._probe_join(state, mask)
            if dag.window is not None:
                dexec._derive_window(state)
            if dag.filter is not None and dag.filter.terms:
                for col, fop, value in dag.filter.terms:
                    m = opexec._eval_post_term(
                        dexec._post_filter_values(state, col), fop, value
                    )
                    mask = m if mask is None else (mask & m)
            per_key = [
                dexec._key_codes_for(state, c) for c in dag.group_keys
            ]
            entry = (mask, per_key, state.row_pos, state.window_ints)
            nbytes = sum(
                np.asarray(c).nbytes + np.asarray(v).nbytes
                for c, v in per_key
            )
            for extra in (mask, state.row_pos, state.window_ints):
                if extra is not None:
                    nbytes += np.asarray(extra).nbytes
            self._align_cache.put(dkey, entry, nbytes=nbytes)
            return entry

        derived_memo = {}

        def get_derived():
            if "v" not in derived_memo:
                derived_memo["v"] = self._map_shards(
                    derive, zip(tables, tables_key)
                )
            return derived_memo["v"]

        missing_cols = [
            col for col in unique_cols
            if (
                (tables_key, "col", col, n_dev) not in self._hbm_cache
                if col_source(col) == "fact"
                else (tables_key, "dagcol", col, derive_sig, n_dev)
                not in self._hbm_cache
            )
        ]
        codes_key = (tables_key, "dagcodes", derive_sig, n_dev)
        codes_warm = codes_key in self._codes_cache
        if missing_cols or not codes_warm:
            self.workingset.evict_under_pressure()

        with self._phase("align"), pipeline.stage("align"):
            akey = (tables_key, "dagalign", derive_sig)
            cached = self._align_cache.get(akey)
            if cached is None:
                dense, combo_cols, key_values = self._dag_key_space(
                    get_derived(), dag
                )
                self._align_cache.put(
                    akey, (dense, combo_cols, key_values),
                    nbytes=sum(d.nbytes for d in dense)
                    + combo_cols.nbytes
                    + sum(
                        np.asarray(v).nbytes for v in key_values.values()
                    ),
                )
            else:
                dense, combo_cols, key_values = cached
            n_groups = max(len(combo_cols), 1)

        # sketch-grid budget BEFORE any upload: the device merge
        # materializes one dense [padded_groups, width] int64 grid per
        # sketch agg per device — past the cell budget the flat host merge
        # is the better economics and the whole query falls back
        n_prog = ops.program_bucket(n_groups)
        span, padded = devicemerge.bucket_span(n_prog, int(n_dev))
        sketch_geo = {}
        for i in sketch_idx:
            alpha = parsed[i][2]
            width, kmin = opexec.sketch_grid_layout(alpha)
            if padded * width > sketch_grid_cells_limit():
                raise DagFastPathUnsupported(
                    f"sketch grid {padded}x{width} cells exceeds "
                    f"BQUERYD_TPU_SKETCH_GRID_CELLS"
                )
            sketch_geo[i] = (width, kmin)

        codes_d = self._codes_cache.get(codes_key)
        if codes_d is None:
            with self._phase("layout"):
                with pipeline.stage("align"), tracing.detail(
                    "layout_pack", self.timer
                ):
                    packed = self._pack(dense, n_dev, -1)
                with pipeline.stage("h2d"), tracing.detail(
                    "layout_h2d", self.timer
                ):
                    codes_d = _put(packed, sharding)
                self._codes_cache.put(codes_key, codes_d)

        with self._phase("layout"):
            measures_d, slot_of = [], {}
            for col in unique_cols:
                if col_source(col) == "fact":
                    mkey = (tables_key, "col", col, n_dev)
                    arr = self._hbm_cache.get(mkey)
                    if arr is None:
                        with pipeline.stage("decode"):
                            with tracing.detail(
                                "layout_columns", self.timer
                            ):
                                wire = (
                                    _wire_dtype(tables, col)
                                    or _stored_dtype(tables, col)
                                )
                                cols = [
                                    np.asarray(t.column_raw(col))
                                    for t in tables
                                ]
                                if wire is not None:
                                    cols = [
                                        c.astype(wire, copy=False)
                                        for c in cols
                                    ]
                            with tracing.detail("layout_pack", self.timer):
                                packed = self._pack(
                                    cols, n_dev, 0, dtype=wire
                                )
                        with pipeline.stage("h2d"), tracing.detail(
                            "layout_h2d", self.timer
                        ):
                            arr = _put(packed, sharding)
                        self._hbm_cache.put(mkey, arr)
                else:
                    mkey = (tables_key, "dagcol", col, derive_sig, n_dev)
                    arr = self._hbm_cache.get(mkey)
                    if arr is None:
                        with pipeline.stage("decode"):
                            with tracing.detail(
                                "layout_columns", self.timer
                            ):
                                vals = []
                                for entry in get_derived():
                                    _m, _pk, row_pos, window_ints = entry
                                    if col_source(col) == "window":
                                        vals.append(np.asarray(window_ints))
                                    else:
                                        vals.append(
                                            opexec.gathered_dim_values(
                                                dag.join.table[col], row_pos
                                            )
                                        )
                            with tracing.detail("layout_pack", self.timer):
                                packed = self._pack(vals, n_dev, 0)
                        with pipeline.stage("h2d"), tracing.detail(
                            "layout_h2d", self.timer
                        ):
                            arr = _put(packed, sharding)
                        self._hbm_cache.put(mkey, arr)
                slot_of[col] = len(measures_d)
                measures_d.append(arr)

        classic_spec = tuple(
            (
                slot_of[dag.aggs[i][0]],
                parsed[i][0],
                sentinel_of[dag.aggs[i][0]],
            )
            for i in classic_idx
        )
        topk_spec = []
        for i in topk_idx:
            col = dag.aggs[i][0]
            dt = np.dtype(measures_d[slot_of[col]].dtype)
            if dt == object:
                raise DagFastPathUnsupported(
                    f"object-dtype topk measure {col!r}"
                )
            is_float = np.issubdtype(dt, np.floating)
            topk_spec.append(
                (
                    slot_of[col], parsed[i][1], parsed[i][2],
                    is_float,
                    None if sentinel_of[col] is None
                    else int(sentinel_of[col]),
                    is_float,
                )
            )
        topk_spec = tuple(topk_spec)
        sketch_spec = []
        for i in sketch_idx:
            col = dag.aggs[i][0]
            alpha = parsed[i][2]
            _gamma, lg, imin, imax = opexec.sketch_layout(alpha)
            width, kmin = sketch_geo[i]
            sketch_spec.append(
                (slot_of[col], float(lg), int(imin), int(imax),
                 int(kmin), int(width))
            )
        sketch_spec = tuple(sketch_spec)

        with self._phase("aggregate"), pipeline.stage("kernel"):
            per_classic_d = tuple(
                measures_d[s] for s, _op, _st in classic_spec
            )
            self.last_effective_strategy = ops.kernel_route(
                None, per_classic_d,
                tuple(op for _s, op, _st in classic_spec),
                int(codes_d.shape[1]), n_prog,
            )
            merged = _mesh_dag_partials(
                mesh, self.axis_name, n_prog, codes_d, tuple(measures_d),
                classic_spec, topk_spec, sketch_spec,
                merge_mode=merge_mode, timer=self.timer,
            )
            if n_prog != n_groups:
                merged = jax.tree_util.tree_map(
                    lambda a: a[:n_groups], merged
                )

        with self._phase("collect"), pipeline.stage("merge"):
            rows = np.asarray(merged["classic"]["rows"])
            present = rows > 0
            present_idx = np.flatnonzero(present)
            keys = {}
            for ci, col in enumerate(dag.group_keys):
                vals = np.asarray(key_values[col])
                keys[col] = vals[combo_cols[present_idx, ci]]
            aggs_out = [None] * len(dag.aggs)
            for pos, i in enumerate(classic_idx):
                in_col = dag.aggs[i][0]
                stored = (
                    _stored_dtype(tables, in_col)
                    if col_source(in_col) == "fact" else None
                )
                sel = {}
                for kname, v in dict(
                    merged["classic"]["aggs"][pos]
                ).items():
                    v = np.asarray(v)[present]
                    if (
                        kname in ("min", "max")
                        and stored is not None
                        and v.dtype != stored
                        and stored.kind in "iu"
                    ):
                        v = v.astype(stored)
                    sel[kname] = v
                aggs_out[i] = sel
            for pos, i in enumerate(topk_idx):
                in_col = dag.aggs[i][0]
                top, cnt = merged["topk"][pos]
                top = np.asarray(top)[present_idx]
                cnt = np.asarray(cnt)[present_idx]
                stored = (
                    _stored_dtype(tables, in_col)
                    if col_source(in_col) == "fact" else None
                )
                if (
                    stored is not None
                    and top.dtype != stored
                    and stored.kind in "iu"
                ):
                    top = top.astype(stored)
                flat, offsets = opexec.dense_topk_to_flat(top, cnt)
                aggs_out[i] = {
                    "topk_values": flat, "topk_offsets": offsets
                }
            for pos, i in enumerate(sketch_idx):
                grid = np.asarray(merged["sketch"][pos])[present_idx]
                _width, kmin = sketch_geo[i]
                skeys, scounts, soffs = opexec.sketch_grid_to_flat(
                    grid, kmin
                )
                aggs_out[i] = {
                    "sketch_keys": skeys,
                    "sketch_counts": scounts,
                    "sketch_offsets": soffs,
                }
            value_kinds = [
                None if parsed[i][0] == "quantile"
                else kind_of[dag.aggs[i][0]]
                for i in range(len(dag.aggs))
            ]
            return ResultPayload.partials(
                key_cols=list(dag.group_keys),
                keys=keys,
                rows=rows[present],
                aggs=aggs_out,
                ops=[a[1] for a in dag.aggs],
                out_cols=[a[2] for a in dag.aggs],
                value_kinds=value_kinds,
            )

    def _dag_key_space(self, derived, dag):
        """Global composite key space over the DAG's (possibly derived)
        group keys — the DAG twin of :meth:`_global_key_space`, fed by the
        cached per-shard derivations instead of ``engine._key_codes``.
        The pushdown / join-miss / post-derivation-filter mask is folded
        INTO the dense codes here (the derivation signature keys the cache
        entry, so a different filter is a different entry): masked rows
        carry code -1 and vanish from every reduction, exactly like the
        classic folded codes.  Returns ``(folded dense codes per shard at
        ``_codes_dtype`` of the combo count, combo_cols [n_combos, n_cols]
        global dictionary positions, key_values)`` with combos in sorted
        composite order."""
        from bqueryd_tpu import ops

        n_cols = len(dag.group_keys)
        n_shards = len(derived)
        masks = [d[0] for d in derived]
        shard_codes = [
            [np.asarray(d[1][ci][0]) for d in derived]
            for ci in range(n_cols)
        ]
        shard_values = [
            [np.asarray(d[1][ci][1]) for d in derived]
            for ci in range(n_cols)
        ]
        cards, global_values = [], []
        pos_maps = [[] for _ in range(n_cols)]
        for ci in range(n_cols):
            gvals = np.unique(np.concatenate(shard_values[ci]))
            # null VALUES (NaN/NaT) strip from the global dictionary: the
            # rows referencing them already carry poisoned codes (-1) —
            # same rule as the classic alignment
            if gvals.dtype.kind == "f":
                gvals = gvals[~np.isnan(gvals)]
            elif gvals.dtype.kind == "M":
                gvals = gvals[~np.isnat(gvals)]
            cards.append(max(len(gvals), 1))
            global_values.append(gvals)
            for si in range(n_shards):
                pos_maps[ci].append(
                    np.searchsorted(gvals, shard_values[ci][si])
                )

        def mapped(si, ci):
            codes = shard_codes[ci][si]
            pos = pos_maps[ci][si]
            if len(pos) == 0:
                return np.full(len(codes), np.int64(-1))
            return np.where(
                codes >= 0, pos[np.clip(codes, 0, None)], np.int64(-1)
            )

        key_values = dict(zip(dag.group_keys, global_values))
        if n_cols == 1:
            cdt = _codes_dtype(max(len(global_values[0]), 1))

            def folded(si):
                dense_si = mapped(si, 0).astype(cdt)
                m = masks[si]
                return (
                    dense_si if m is None
                    else np.where(m, dense_si, cdt.type(-1))
                )

            dense = self._map_shards(folded, range(n_shards))
            combo_cols = np.arange(
                len(global_values[0]), dtype=np.int64
            )[:, None]
            return dense, combo_cols, key_values

        if ops.total_cardinality(cards) >= ops.MAX_COMPOSITE:
            raise ops.CompositeOverflow(
                "composite group-key space "
                f"{'x'.join(str(int(c)) for c in cards)} exceeds int64"
            )

        def shard_composites(si):
            packed = np.asarray(
                ops.pack_codes(
                    [mapped(si, ci) for ci in range(n_cols)], cards
                )
            )
            m = masks[si]
            if m is not None:
                packed = np.where(m, packed, np.int64(-1))
            inv, uniq = ops.factorize(packed)
            return np.asarray(inv), np.asarray(uniq, dtype=np.int64)

        composites = self._map_shards(shard_composites, range(n_shards))
        observed = [u[u >= 0] for _inv, u in composites]
        observed = [o for o in observed if len(o)]
        combos = (
            np.unique(np.concatenate(observed))
            if observed
            else np.empty(0, dtype=np.int64)
        )
        cdt = _codes_dtype(max(len(combos), 1))
        dense = []
        for inv, uniq in composites:
            lut = np.searchsorted(combos, np.clip(uniq, 0, None)).astype(cdt)
            lut[uniq < 0] = -1
            dense.append(lut[inv])
        combo_cols = (
            np.stack(ops.unpack_codes(combos, cards), axis=1)
            if len(combos)
            else np.empty((0, n_cols), dtype=np.int64)
        )
        return dense, combo_cols, key_values


class DagFastPathUnsupported(Exception):
    """The mesh fast path cannot serve this extended-DAG dispatch (shape,
    dtype, budget, or the device-merge kill switch).  NOT an error the
    client ever sees: the worker catches it and falls back to the PR-13
    per-shard operator pipeline + host value-keyed merge, which serves
    every DAG shape."""


def sketch_grid_cells_limit():
    """Cell budget (padded groups x bucket width) above which a quantile
    sketch keeps the per-shard host path: the device merge materializes one
    dense int64 ``[groups, width]`` grid per sketch agg per device, and
    past this budget (default 2^23 cells = 64 MiB of HBM + ICI per agg)
    the flat host merge it replaces is the better economics.  Tune with
    BQUERYD_TPU_SKETCH_GRID_CELLS."""
    return int(
        os.environ.get("BQUERYD_TPU_SKETCH_GRID_CELLS", str(1 << 23))
    )


def _f64_rides_as_f32_pair():
    """Whether float64 leaves cross the packed fetch as (hi, lo) float32
    pairs instead of their bytes.  On a TPU they must: float64 is emulated
    there and has no bit pattern to cast — the compiler refuses
    ``bitcast-convert`` on f64 ("rewriting [X64 element types] is not
    implemented", seen on the v5e, PERF.md PR 21), while float32 bitcasts
    lower.  Read at trace time by :func:`_pack_leaf` and at fetch time by
    :func:`_unpack_host`: one process, one backend, one answer."""
    import jax

    return jax.default_backend() == "tpu"


def _pack_leaf(leaf):
    """Bitcast any result leaf to its native bytes (lossless, no widening —
    the packed buffer carries exactly the leaves' own byte sizes).  A
    float64 leaf on a TPU rides as its float32 (hi, lo) split: all the his,
    then all the los — the same 8 bytes per element, and as much of the
    value as the emulated f64 holds."""
    import jax.numpy as jnp
    from jax import lax

    if leaf.dtype.itemsize == 1:
        return leaf.astype(jnp.uint8).ravel() if leaf.dtype != jnp.uint8 \
            else leaf.ravel()
    if leaf.dtype == jnp.float64 and _f64_rides_as_f32_pair():
        hi = leaf.astype(jnp.float32)
        # inf/nan (empty-group extrema fills) live in hi alone
        lo = jnp.where(
            jnp.isfinite(hi), leaf - hi.astype(jnp.float64), 0.0
        ).astype(jnp.float32)
        return jnp.concatenate([_pack_leaf(hi), _pack_leaf(lo)])
    # bitcast to a SMALLER dtype appends a trailing byte axis
    return lax.bitcast_convert_type(leaf, jnp.uint8).ravel()


def _unpack_host(flat, spec):
    """Invert :func:`_pack_leaf` on the fetched numpy uint8 byte buffer."""
    split_f64 = _f64_rides_as_f32_pair()
    leaves = []
    off = 0
    for dtype, shape in spec:
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = n * dtype.itemsize
        seg = flat[off:off + nbytes]
        off += nbytes
        # copy() realigns the slice so the view is valid at any offset
        if split_f64 and dtype == np.float64:
            pair = seg.copy().view(np.float32).astype(np.float64)
            leaves.append((pair[:n] + pair[n:]).reshape(shape))
        else:
            leaves.append(seg.copy().view(dtype).reshape(shape))
    return leaves


def packed_fetch_enabled():
    """Fetch the merged result as ONE device buffer (default on): the merged
    pytree has one leaf per aggregation partial, and ``jax.device_get``
    copies leaves buffer-by-buffer, one D2H round-trip each.  Packing
    bitcasts every leaf to its native bytes and
    concatenates INSIDE the compiled mesh program, so dispatch+fetch is
    exactly one program and one buffer of the leaves' own total size."""
    return os.environ.get("BQUERYD_TPU_PACKED_FETCH", "1") == "1"


def _route_key():
    """The env-derived knobs that steer the kernel route inside
    ``ops.partial_tables`` AT TRACE TIME.  They must be part of the
    ``_mesh_program`` cache key: the dispatcher reads them per call, but a
    cached program never re-runs the dispatcher — without the key a
    runtime flag flip (the bench's pallas variants, a live worker being
    re-tuned) would silently keep serving the previously-traced route."""
    from bqueryd_tpu.ops import groupby as gb
    from bqueryd_tpu.ops import pallas_groupby as pg

    return (
        pg.pallas_enabled(),
        os.environ.get("BQUERYD_TPU_FORCE_MATMUL") == "1",
        gb.matmul_groups_limit(),
        gb._matmul_cells_limit(),
        pg.hicard_groups_limit(),
    )


@functools.lru_cache(maxsize=64)
def _fold_program(term_ops, sharding):
    """Build + cache the jitted mask-and-fold program for one tuple of
    where ops: ``(codes, columns, constants) -> where(mask, codes, -1)``,
    the mask made by the ``ops.term_mask`` the host path calls and AND-ed
    over the terms, so promotion, NaN and weak-type semantics are the host
    path's.  A program of its own, not part of the mesh program: elementwise
    over arrays sharded on their first axis, no collective, and its output
    carries the sharding, shape and dtype ``_put`` gives the host-folded
    codes, so the mesh program sees the input it always saw.

    The constants are TRACED arguments, passed as the Python scalars
    ``translate_value`` returns — the weak-typed scalars the host's eager
    compare hands its own jitted op: a fresh constant is a cache hit, and a
    Python float against a float32 column compares in float32."""
    import jax
    import jax.numpy as jnp

    from bqueryd_tpu import ops
    from bqueryd_tpu.obs import profile as obsprofile

    def fold(codes, columns, constants):
        mask = None
        for values, op, constant in zip(columns, term_ops, constants):
            m = ops.term_mask(values, op, constant)
            mask = m if mask is None else (mask & m)
        return jnp.where(mask, codes, -1)

    return obsprofile.instrument(
        "executor.fold_program",
        jax.jit(fold, out_shardings=sharding),
        # the jit cache keys a traced Python scalar by its type: so does
        # the registry, not by its value
        signature_args=lambda codes, columns, constants: (
            codes, columns, tuple(type(c).__name__ for c in constants),
        ),
    )


@functools.lru_cache(maxsize=64)
def _mesh_program(mesh, axis, agg_ops, n_groups, in_dtypes, in_width, pack,
                  null_sentinels=None, route=None, strategy=None,
                  measure_index=None, merge_mode="psum"):
    """Build + cache the jitted shard_map program for one query shape.

    The key carries everything that can change the traced program — measure
    wire dtypes AND the per-device row width (``in_width``): the packed
    output's host-side unpack spec is captured at trace time, and both leaf
    dtypes (via the measure dtypes) and the kernel route (via the row count,
    ``_matmul_cells_limit``, and the ``route`` flag tuple) feed it, so one
    cache entry must map to exactly one trace.  ``measure_index`` (static)
    maps each aggregation to its slot in the DEDUPLICATED measure blocks:
    ``sum+count+mean`` of one column ride one uploaded block and one
    program argument instead of three.

    ``merge_mode`` (static, devicemerge.MODE_*) picks the cross-device
    merge traced into the program:

    * ``device`` — bucketized partials merge over the mesh axis so each
      device owns a contiguous key span (``devicemerge``: all-gather +
      local reduce + own-span slice); outputs are span-sized and the D2H
      fetch is the final table only (the default);
    * ``psum``   — the same merge with a replicated output (multi-host
      pods, where a span-sharded output is not host-fetchable);
    * ``host``   — NO collective: every device's full partial table comes
      back (leading device axis host-side) for ``hostmerge.merge_payloads``
      — the kill-switch baseline."""
    import jax
    from jax.sharding import PartitionSpec as P

    from bqueryd_tpu import ops
    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    spec = {}  # populated at trace time: treedef + (dtype, shape) per leaf

    def block_fn(codes_blk, *measure_blks):
        per_block = tuple(m[0] for m in measure_blks)
        per_agg = (
            per_block
            if measure_index is None
            else tuple(per_block[i] for i in measure_index)
        )
        partials = ops.partial_tables(
            codes_blk[0],
            per_agg,
            agg_ops,
            n_groups,
            null_sentinels=null_sentinels,
            strategy=strategy,
        )
        if merge_mode == devicemerge.MODE_DEVICE:
            # key-span ownership: pad onto the bucket layout (behind the
            # kernel guards — this is the dispatched partials' OUTPUT) and
            # reduce-scatter so this device keeps only its span's totals
            bucketized, span = ops.bucketize_partials(
                partials, n_groups, n_dev
            )
            merged = devicemerge.scatter_merge_partials(
                bucketized, axis, span
            )
        elif merge_mode == devicemerge.MODE_HOST:
            # kill switch: no collective — the per-device partial tables
            # leave HBM whole and merge on the worker host
            merged = partials
        else:
            merged = devicemerge.scatter_merge_partials(
                partials, axis, None
            )
        if not pack:
            return merged
        leaves, treedef = jax.tree_util.tree_flatten(merged)
        spec["treedef"] = treedef
        spec["leaves"] = tuple(
            (np.dtype(leaf.dtype), tuple(leaf.shape)) for leaf in leaves
        )
        import jax.numpy as jnp

        return jnp.concatenate([_pack_leaf(leaf).ravel() for leaf in leaves])

    # pallas_call outputs carry no varying-mesh-axes metadata, so the vma/rep
    # check would reject the kernel path; the merge in block_fn is what makes
    # the out_specs=P() replication true by construction.  Span-owned
    # (device) and per-device (host) outputs are axis-sharded instead: the
    # global result concatenates every device's slice in device order.
    out_spec = P() if merge_mode == devicemerge.MODE_PSUM else P(axis)
    fn = jax.shard_map(
        block_fn,
        mesh=mesh,
        in_specs=tuple([P(axis, None)] * len(in_dtypes)),
        out_specs=out_spec,
        check_vma=False,
    )
    # compile/call accounting (obs.profile): every mesh-program call lands
    # in the jit-cache hit/miss counters, compiles in the compile-seconds
    # histogram + per-shape program registry with cost_analysis FLOPs
    from bqueryd_tpu.obs import profile as obsprofile

    return obsprofile.instrument("executor.mesh_program", jax.jit(fn)), spec


@functools.lru_cache(maxsize=32)
def _mesh_bundle_program(mesh, axis, n_groups, in_dtypes, in_width, pack,
                         member_specs, null_sentinels, route=None,
                         strategy=None, merge_mode="psum", n_masks=0):
    """Build + cache the jitted shared-scan BUNDLE program for one bundle
    shape.  The key carries everything that changes the trace: the static
    per-member spec tuple (mask slot + (measure slot, op) pairs), the
    stacked-mask count, the union measure dtypes, and the same route/merge
    knobs as :func:`_mesh_program`.  The program emits one merged partial
    table PER MEMBER (a tuple pytree): each member's emission is the same
    :func:`ops.partial_tables` dispatch its solo program runs, under its
    own stacked-mask row, and each member's cross-device merge is the same
    collective the solo program traces — the whole bundle reduces in one
    compiled dispatch."""
    import jax
    from jax.sharding import PartitionSpec as P

    from bqueryd_tpu import ops
    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    spec = {}

    def merge_member(partials):
        if merge_mode == devicemerge.MODE_DEVICE:
            bucketized, span = ops.bucketize_partials(
                partials, n_groups, n_dev
            )
            return devicemerge.scatter_merge_partials(
                bucketized, axis, span
            )
        if merge_mode == devicemerge.MODE_HOST:
            return partials
        return devicemerge.scatter_merge_partials(partials, axis, None)

    def body(codes_blk, masks_blk, measure_blks):
        codes = codes_blk[0]
        masks = None if masks_blk is None else masks_blk[:, 0, :]
        per_col = tuple(m[0] for m in measure_blks)
        members = ops.bundle_partial_tables(
            codes, masks, per_col, member_specs, n_groups,
            null_sentinels=null_sentinels, strategy=strategy,
        )
        merged = tuple(merge_member(partials) for partials in members)
        if not pack:
            return merged
        leaves, treedef = jax.tree_util.tree_flatten(merged)
        spec["treedef"] = treedef
        spec["leaves"] = tuple(
            (np.dtype(leaf.dtype), tuple(leaf.shape)) for leaf in leaves
        )
        import jax.numpy as jnp

        return jnp.concatenate([_pack_leaf(leaf).ravel() for leaf in leaves])

    n_measures = len(in_dtypes) - 1 - (1 if n_masks else 0)
    if n_masks:
        def block_fn(codes_blk, masks_blk, *measure_blks):
            return body(codes_blk, masks_blk, measure_blks)

        in_specs = (P(axis, None), P(None, axis, None)) + tuple(
            [P(axis, None)] * n_measures
        )
    else:
        def block_fn(codes_blk, *measure_blks):
            return body(codes_blk, None, measure_blks)

        in_specs = tuple([P(axis, None)] * (1 + n_measures))
    out_spec = P() if merge_mode == devicemerge.MODE_PSUM else P(axis)
    fn = jax.shard_map(
        block_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_spec,
        check_vma=False,
    )
    from bqueryd_tpu.obs import profile as obsprofile

    return obsprofile.instrument(
        "executor.mesh_bundle_program", jax.jit(fn)
    ), spec


def _fetch_merged(run, call, merge_mode, n_dev, finish, timer, latch, what,
                  float_form=None):
    """The ONE packed-fetch scaffold shared by the three mesh fetch paths
    (:func:`_mesh_partials`, :func:`_mesh_bundle_partials`,
    :func:`_mesh_dag_partials`): run the packed program and fetch one byte
    buffer, falling back to the per-leaf ``device_get`` of the unpacked
    program when the packed one fails.

    ``latch`` is the per-path policy after a DETERMINISTIC packed failure:
    ``True`` (the solo and DAG paths) counts consecutive transient-classed
    failures against ``_PACKED_TRANSIENT_LIMIT`` (a deterministic XLA bug
    misclassed INTERNAL cannot dodge the latch forever) and commits
    ``_packed_fetch_broken`` once per-leaf succeeds — per-leaf working
    where packed failed is the actual evidence against packing; ``False``
    (bundles) propagates transients unconditionally and never latches, the
    solo path owning the packed-broken diagnosis.  ``run(pack_flag)``
    returns ``(program, spec)``; ``call(program)`` invokes it with the
    caller's argument tuple; ``finish(merged, fetched_bytes)`` is the
    caller's layout normalization + merge-byte accounting.  ``float_form``
    (``ops.float_sum_route``, under the profile switch only) names the form
    the launch's float64 sums took: other than ``dense`` it gets the detail
    span ``float_sum_wait`` inside ``aggregate_wait``."""
    global _packed_fetch_broken, _packed_transient_count
    import jax

    from bqueryd_tpu.parallel import devicemerge

    pack = packed_fetch_enabled() and not _packed_fetch_broken
    latch_pending = False
    if pack:
        try:
            program, spec = run(True)
            with _collective_guard():
                with tracing.detail("aggregate_launch", timer):
                    out = call(program)
                with tracing.detail("aggregate_wait", timer):
                    with _float_sum_wait(float_form, timer):
                        jax.block_until_ready(out)
                with _fetch_phase(timer):
                    flat = np.asarray(jax.device_get(out))
        except Exception as exc:
            transient = isinstance(
                exc, jax.errors.JaxRuntimeError
            ) and _transient_status(exc)
            if transient and (
                not latch
                or _packed_transient_count + 1 < _PACKED_TRANSIENT_LIMIT
            ):
                # transient infrastructure fault (INTERNAL, UNAVAILABLE,
                # ...): NOT evidence against packing — propagate so the
                # caller's retry / the worker's degrade+failover machinery
                # decides instead of re-executing the whole program
                # per-leaf on the same faulting backend
                if latch:
                    _packed_transient_count += 1
                raise
            if latch:
                # deterministic packed failure: per-leaf retry below, and
                # the process latches off packed fetch once it succeeds
                latch_pending = True
            devicehealth.note_degrade("packed_to_perleaf")
            import logging

            logging.getLogger("bqueryd_tpu").exception(
                "packed %s fetch failed; retrying via per-leaf "
                "device_get", what,
            )
        else:
            if latch:
                _packed_transient_count = 0
            if merge_mode == devicemerge.MODE_PSUM:
                merged = jax.tree_util.tree_unflatten(
                    spec["treedef"], _unpack_host(flat, spec["leaves"])
                )
            else:
                merged = _assemble_sharded(flat, spec, n_dev, merge_mode)
            return finish(merged, flat.nbytes)
    program, _spec = run(False)
    with _collective_guard():
        with tracing.detail("aggregate_launch", timer):
            out = call(program)
        with tracing.detail("aggregate_wait", timer):
            jax.block_until_ready(out)
        with _fetch_phase(timer):
            result = jax.device_get(out)
    if latch_pending:
        _packed_fetch_broken = True
        _packed_transient_count = 0
        devicehealth.note_degrade("packed_latched")
        import logging

        logging.getLogger("bqueryd_tpu").warning(
            "packed fetch unavailable on this backend (per-leaf fetch "
            "succeeded where the packed %s program failed); using "
            "per-leaf device_get for the process lifetime", what,
        )
    fetched = sum(
        np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(result)
    )
    return finish(result, fetched)


def _mesh_bundle_partials(mesh, axis, n_groups, codes_d, masks_d, measures_d,
                          member_specs, null_sentinels, strategy=None,
                          merge_mode="psum", timer=None):
    """Run the bundle program and return the per-member merged partials
    tuple ON HOST (numpy leaves) — one packed fetch for the whole bundle
    when packing is enabled, with a per-query fallback to per-leaf
    ``device_get`` (no process latch: the single-query path owns the
    packed-broken diagnosis).  Shapes follow :func:`_mesh_partials`:
    ``device``/``psum`` leaves are ``[n_groups]`` per member, ``host``
    leaves ``[n_dev, n_groups]`` for the hostmerge fallback."""
    import jax

    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    in_dtypes = (
        (str(codes_d.dtype),)
        + ((str(masks_d.dtype),) if masks_d is not None else ())
        + tuple(str(m.dtype) for m in measures_d)
    )
    n_masks = 0 if masks_d is None else int(masks_d.shape[0])
    args = (
        (codes_d,)
        + ((masks_d,) if masks_d is not None else ())
        + tuple(measures_d)
    )

    def run(pack_flag):
        return _mesh_bundle_program(
            mesh, axis, int(n_groups), in_dtypes, int(codes_d.shape[1]),
            pack_flag, member_specs, null_sentinels,
            route=_route_key(), strategy=strategy, merge_mode=merge_mode,
            n_masks=n_masks,
        )

    def finish(merged, fetched):
        if merge_mode == devicemerge.MODE_DEVICE:
            merged = jax.tree_util.tree_map(
                lambda a: a[: int(n_groups)], merged
            )
        elif merge_mode == devicemerge.MODE_HOST:
            merged = jax.tree_util.tree_map(
                lambda a: np.asarray(a).reshape(n_dev, int(n_groups)),
                merged,
            )
        _record_merge_bytes(
            merge_mode, fetched, n_dev, int(n_groups), merged
        )
        return merged

    return _fetch_merged(
        run, lambda program: program(*args), merge_mode, n_dev, finish,
        timer, latch=False, what="bundle",
    )


@functools.lru_cache(maxsize=32)
def _mesh_dag_program(mesh, axis, n_groups, in_dtypes, in_width, pack,
                      classic_spec, topk_spec, sketch_spec, route=None,
                      merge_mode="device"):
    """Build + cache the jitted mesh program of one extended-DAG shape:
    every aggregation's partial state emitted AND cross-device merged in
    one compiled dispatch, so the only D2H is the final merged table.

    Static specs (all in the lru key, like every knob that changes the
    trace):

    * ``classic_spec`` — ``((measure_slot, op, sentinel), ...)``: ONE
      :func:`ops.partial_tables` dispatch (every kernel guard / strategy
      route unchanged) whose bucketized output merges span-owned
      (the PR-7 ``devicemerge.scatter_merge_partials`` machinery);
    * ``topk_spec`` — ``((slot, k, largest, drop_nan, sentinel,
      float_neg), ...)``: dense ``[padded_groups, k]`` emission via
      :func:`ops.relops.topk_dense_emit` — the SAME routed dispatcher
      (matrix-argmax / k-pass / lexsort, all value-multiset identical)
      the jitted per-shard kernel runs — merged by all-gather +
      on-device re-select (:func:`devicemerge.allgather_topk_merge`);
    * ``sketch_spec`` — ``((slot, log_gamma, imin, imax, kmin,
      width), ...)``: dense bucket-count grids
      (:func:`ops.relops.sketch_grid_block`) merged by span-owned
      ADDITION (:func:`devicemerge.scatter_merge_grid`) — the mergeable-
      histogram property, now on the ICI instead of the host.

    ``merge_mode`` is ``device`` or ``psum`` only: under the
    ``BQUERYD_TPU_DEVICE_MERGE=0`` / ``BQUERYD_TPU_DAG_BATCH=0`` kill
    switches the controller stops batching DAG dispatches, so no batched
    program ever runs host-merged."""
    import jax
    from jax.sharding import PartitionSpec as P

    from bqueryd_tpu import ops
    from bqueryd_tpu.ops import relops
    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    span, padded = devicemerge.bucket_span(n_groups, n_dev)
    device_mode = merge_mode == devicemerge.MODE_DEVICE
    g_emit = padded if device_mode else n_groups
    span_arg = span if device_mode else None
    spec = {}

    def block_fn(codes_blk, *measure_blks):
        codes = codes_blk[0]
        per_slot = tuple(m[0] for m in measure_blks)
        partials = ops.partial_tables(
            codes,
            tuple(per_slot[s] for s, _op, _st in classic_spec),
            tuple(op for _s, op, _st in classic_spec),
            n_groups,
            null_sentinels=tuple(st for _s, _op, st in classic_spec),
        )
        if device_mode:
            bucketized, sp = ops.bucketize_partials(
                partials, n_groups, n_dev
            )
            classic = devicemerge.scatter_merge_partials(
                bucketized, axis, sp
            )
        else:
            classic = devicemerge.scatter_merge_partials(
                partials, axis, None
            )
        topk = []
        for slot, k, largest, drop_nan, sentinel, float_neg in topk_spec:
            dense, cnt = relops.topk_dense_emit(
                codes, per_slot[slot], None, k, largest, g_emit,
                drop_nan, sentinel, float_neg,
            )
            topk.append(
                devicemerge.allgather_topk_merge(
                    dense, cnt, axis, span_arg, largest, float_neg
                )
            )
        sketches = []
        for slot, lg, imin, imax, kmin, width in sketch_spec:
            grid = relops.sketch_grid_block(
                codes, per_slot[slot], g_emit, lg, imin, imax, kmin,
                width,
            )
            sketches.append(
                devicemerge.scatter_merge_grid(grid, axis, span_arg)
            )
        merged = {
            "classic": classic,
            "topk": tuple(topk),
            "sketch": tuple(sketches),
        }
        if not pack:
            return merged
        leaves, treedef = jax.tree_util.tree_flatten(merged)
        spec["treedef"] = treedef
        spec["leaves"] = tuple(
            (np.dtype(leaf.dtype), tuple(leaf.shape)) for leaf in leaves
        )
        import jax.numpy as jnp

        return jnp.concatenate([_pack_leaf(leaf).ravel() for leaf in leaves])

    out_spec = P(axis) if device_mode else P()
    fn = jax.shard_map(
        block_fn,
        mesh=mesh,
        in_specs=tuple([P(axis, None)] * len(in_dtypes)),
        out_specs=out_spec,
        check_vma=False,
    )
    from bqueryd_tpu.obs import profile as obsprofile

    return obsprofile.instrument(
        "executor.mesh_dag_program", jax.jit(fn)
    ), spec


def _mesh_dag_partials(mesh, axis, n_groups, codes_d, measures_d,
                       classic_spec, topk_spec, sketch_spec,
                       merge_mode="device", timer=None):
    """Run the DAG program and return the merged pytree ON HOST (numpy
    leaves, group axis leading, length ``n_groups`` = the program bucket):
    one packed fetch for the whole query when packing is enabled, with the
    per-leaf ``device_get`` fallback (same transient-vs-deterministic
    contract as the bundle fetch — the worker's degrade path owns
    failures).  Every leaf's group axis is fully merged: classic tables
    ``[n_groups]``, top-k ``([n_groups, k], [n_groups])`` pairs, sketch
    grids ``[n_groups, width]``."""
    import jax

    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    in_dtypes = (str(codes_d.dtype),) + tuple(
        str(m.dtype) for m in measures_d
    )
    args = (codes_d,) + tuple(measures_d)

    def run(pack_flag):
        return _mesh_dag_program(
            mesh, axis, int(n_groups), in_dtypes, int(codes_d.shape[1]),
            pack_flag, classic_spec, topk_spec, sketch_spec,
            route=_route_key(), merge_mode=merge_mode,
        )

    def finish(merged, fetched):
        if merge_mode == devicemerge.MODE_DEVICE:
            # device-mode leaves concatenate spans to the PADDED group
            # axis; slice back to the program bucket (the caller slices
            # the bucket down to the real group count)
            merged = jax.tree_util.tree_map(
                lambda a: a[: int(n_groups)], merged
            )
        # host-gather counterfactual: every device's full merged-size
        # partial state crossing to the host (the =0 economics)
        counterfactual = n_dev * sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(merged)
        )
        devicemerge.stats().record(
            merge_mode, int(fetched), saved=counterfactual - int(fetched)
        )
        return merged

    return _fetch_merged(
        run, lambda program: program(*args), merge_mode, n_dev, finish,
        timer, latch=True, what="DAG",
    )


#: set when the packed program failed to build/run on this backend (seen
#: nowhere yet; guards against a backend rejecting the byte bitcasts) — all
#: later queries go straight to the per-leaf fetch
_packed_fetch_broken = False

#: consecutive transiently-classed packed-fetch failures; once it reaches
#: _PACKED_TRANSIENT_LIMIT the "transient" diagnosis is abandoned and the
#: per-leaf latch sets anyway (an XLA lowering bug classed INTERNAL would
#: otherwise dodge the latch forever, costing every query two failed packed
#: dispatches and an engine degrade)
_packed_transient_count = 0
_PACKED_TRANSIENT_LIMIT = 3

#: gRPC-style status prefixes the runtime surfaces for infrastructure
#: (retry-worthy) failures, as opposed to deterministic program rejections
#: (INVALID_ARGUMENT, UNIMPLEMENTED, FAILED_PRECONDITION) or deterministic
#: resource exhaustion.  INTERNAL is ambiguous — a compiler bug reports it
#: too — which is why a retried call is counted, never silent.
_TRANSIENT_STATUSES = (
    "INTERNAL", "UNAVAILABLE", "DEADLINE_EXCEEDED", "CANCELLED", "UNKNOWN"
)


def _transient_status(exc):
    """Whether a JaxRuntimeError looks like transient infrastructure failure
    (worth one in-place retry) rather than a deterministic rejection."""
    msg = str(exc)
    return any(s in msg for s in _TRANSIENT_STATUSES)


def _effective_mesh_strategy(strategy, agg_ops, n_groups, measures_d, width):
    """Canonicalize a forced route for the mesh-program cache key: one that
    cannot change the traced route must key (and trace) exactly like
    ``auto``, or an identical program would be compiled twice — "matmul" is
    advisory by definition (the dispatcher decides identically under auto),
    and where auto takes the scatter entry "scatter" or "sort" is a no-op
    when it names the form that entry's integers take there anyway (the
    blocked scatter on a CPU backend up to the blocks x groups budget; the
    sort past it and on an accelerator)."""
    if strategy in (None, "auto", "matmul"):
        return None
    from bqueryd_tpu.ops import groupby as gb

    mm = gb._matmul_profitable(
        measures_d, agg_ops, width, int(n_groups)
    ) or gb._hicard_matmul_profitable(
        measures_d, agg_ops, width, int(n_groups)
    )
    if not mm and (strategy == "sort") == gb._int_sums_sort(
        width, int(n_groups)
    ):
        return None
    return strategy


#: serializes mesh-program execution on CPU backends: XLA:CPU cross-module
#: collectives rendezvous by participant count process-globally, so two
#: concurrent psum programs from different threads (an in-process multi-
#: worker test cluster) interleave their AllReduce participants and
#: deadlock.  Production topology is one process per device set, where the
#: lock is uncontended; TPU backends skip it entirely.
_CPU_COLLECTIVE_LOCK = threading.Lock()


def _collective_guard():
    import contextlib

    import jax

    if jax.default_backend() == "cpu":
        return _CPU_COLLECTIVE_LOCK
    return contextlib.nullcontext()


def _assemble_sharded(flat, spec, n_dev, merge_mode):
    """Host-side reassembly of a packed axis-sharded fetch: the global byte
    buffer concatenates every device's packed slice in device order.  Device
    mode concatenates the span slices back into the (padded) merged table;
    host mode stacks the full per-device tables onto a leading device axis.
    Layout normalization (pad-tail slice / device-axis reshape) is the
    caller's ``finish`` — the contract lives there for BOTH fetch paths."""
    import jax

    per_dev = [
        _unpack_host(chunk, spec["leaves"])
        for chunk in flat.reshape(n_dev, -1)
    ]
    from bqueryd_tpu.parallel import devicemerge

    if merge_mode == devicemerge.MODE_DEVICE:
        leaves = [
            np.concatenate([dev[i] for dev in per_dev])
            for i in range(len(spec["leaves"]))
        ]
    else:
        leaves = [
            np.stack([dev[i] for dev in per_dev])
            for i in range(len(spec["leaves"]))
        ]
    return jax.tree_util.tree_unflatten(spec["treedef"], leaves)


def _record_merge_bytes(merge_mode, fetched, n_dev, n_groups, merged):
    """Account the D2H movement of one merged fetch: ``fetched`` actual
    bytes vs the host-gather counterfactual — every device's full partial
    table (``n_dev x n_groups`` rows per leaf) crossing to the host."""
    from bqueryd_tpu.parallel import devicemerge

    leaves = []
    import jax

    for leaf in jax.tree_util.tree_leaves(merged):
        leaves.append(np.dtype(np.asarray(leaf).dtype).itemsize)
    counterfactual = n_dev * n_groups * sum(leaves)
    devicemerge.stats().record(
        merge_mode, fetched, saved=counterfactual - int(fetched)
    )


def _float_sum_wait(form, timer):
    """The detail span ``float_sum_wait`` round the wait for a launch whose
    float64 sums took a form other than ``dense`` (the forms whose cost
    does not shrink with the group count: the device's time is then mostly
    theirs); no span where the launch summed no float64 or densely."""
    if form in (None, "dense"):
        return contextlib.nullcontext()
    return tracing.detail("float_sum_wait", timer, form=form)


@contextlib.contextmanager
def _fetch_phase(timer):
    """The D2H fetch timed as its own phase ("fetch" -> span "d2h_fetch"):
    the program output is blocked-until-ready first, so what this phase
    measures is the transfer itself, not the async kernel dispatch it used
    to hide inside the "aggregate" wall.  The fetch runs serially nested
    inside the open "aggregate" phase, so its wall is DEBITED from
    aggregate — one second of D2H bills the fetch phase once, not the
    kernel histogram too."""
    if timer is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        with timer.phase("fetch"):
            yield
    finally:
        timer.debit("aggregate", time.perf_counter() - t0)


def _mesh_partials(mesh, axis, agg_ops, n_groups, codes_d, measures_d,
                   null_sentinels=None, strategy=None, measure_index=None,
                   merge_mode="psum", timer=None, float_form=None):
    """Run the mesh program and return the merged partials pytree ON HOST
    (numpy leaves) — fetching one packed buffer when packing is enabled.
    ``measures_d`` holds one device block per DISTINCT measure column;
    ``measure_index`` maps each agg onto those slots (None = identity).

    ``merge_mode`` shapes the result: ``device``/``psum`` return the merged
    table (leaves ``[n_groups]``); ``host`` returns the UNMERGED per-device
    partials (leaves ``[n_dev, n_groups]``) for the hostmerge fallback.

    ``timer``: optional PhaseTimer; the device→host fetch is carved into
    its own "fetch" phase so attribution can split kernel wall from D2H."""
    import jax

    from bqueryd_tpu.parallel import devicemerge

    n_dev = int(mesh.devices.size)
    per_agg_measures = (
        measures_d
        if measure_index is None
        else tuple(measures_d[i] for i in measure_index)
    )
    strategy = _effective_mesh_strategy(
        strategy, tuple(agg_ops), n_groups, per_agg_measures,
        int(codes_d.shape[1]),
    )
    in_dtypes = (str(codes_d.dtype),) + tuple(str(m.dtype) for m in measures_d)

    def run(pack_flag):
        return _mesh_program(
            mesh, axis, tuple(agg_ops), int(n_groups), in_dtypes,
            int(codes_d.shape[1]), pack_flag,
            null_sentinels,  # part of the lru key: it changes the trace
            route=_route_key(),  # ditto: the flags steer the traced route
            strategy=strategy,  # planner hint: a different traced route too
            measure_index=measure_index,  # agg -> deduped block slot
            merge_mode=merge_mode,  # the traced cross-device merge differs
        )

    def finish(merged, fetched):
        if merge_mode == devicemerge.MODE_DEVICE:
            # axis-sharded span outputs concatenate to the padded table;
            # the bucket pad tail holds no real group
            merged = jax.tree_util.tree_map(
                lambda a: a[: int(n_groups)], merged
            )
        elif merge_mode == devicemerge.MODE_HOST:
            merged = jax.tree_util.tree_map(
                lambda a: np.asarray(a).reshape(n_dev, int(n_groups)),
                merged,
            )
        _record_merge_bytes(
            merge_mode, fetched, n_dev, int(n_groups), merged
        )
        return merged

    return _fetch_merged(
        run, lambda program: program(codes_d, *measures_d), merge_mode,
        n_dev, finish, timer, latch=True, what="query",
        float_form=float_form,
    )
