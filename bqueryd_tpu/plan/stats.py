"""Per-shard statistics: gathered by calc workers, advertised in their
WorkerRegisterMessage, consumed by the controller's planner.

A shard's stats are metadata-only reads — nothing is decompressed:

* ``rows`` from the table's meta.json;
* per-column ``min``/``max`` from the chunk-writer stats in each column's
  meta (:meth:`ctable.col_stats`), datetime columns in int64 ns.

``stats_can_match`` is the controller-side twin of
:func:`bqueryd_tpu.ops.predicates.shard_can_match`: it decides from
advertised stats alone whether a shard can contain ANY row matching a
filter conjunction, so provably-empty shards are pruned at plan time and
never dispatched.  It only prunes on plain numeric comparisons (the
controller has no pandas for datetime translation and no dictionaries for
dict-code translation); anything else conservatively matches — the worker's
own ``shard_can_match`` remains the second, stronger pruning line.

Control-plane module: no JAX, no pandas.
"""

import os

#: numbers the controller can compare against min/max stats without any
#: column-kind translation (bool excluded on purpose: bool storage has no
#: stats anyway)
_NUMBER = (int, float)


def _chunk_prefix_sig(table, name, count):
    """CRC of the identity (offset, csize, crc) of the first ``count``
    committed chunks of a column — the metadata-only fingerprint the
    incremental gather validates against, so a shard REPLACED in place
    (same name, same-or-more chunks, different bytes) can never pass as
    an append and fold stale min/max into fresh advertisements."""
    import zlib

    committed = getattr(table, "committed_chunks", None)
    if committed is None:
        return None
    chunks = committed(name)
    if chunks is None or len(chunks) < count:
        return None
    sig = 0
    for c in chunks[:count]:
        sig = zlib.crc32(
            f"{c.get('offset')}:{c.get('csize')}:{c.get('crc')};".encode(),
            sig,
        )
    return sig


def gather_table_stats(table, prev=None):
    """One shard's advertised stats (JSON-safe dict).

    ``prev`` is the previous snapshot for the same shard (if any): when the
    table only GREW since it was taken (chunk counts monotonic AND the old
    chunks an unchanged prefix, validated per column by the metadata-only
    ``sig`` fingerprint — the streaming-append signature), per-column work
    is incremental: min/max fold the NEW chunks' zone maps into the
    previous bounds.
    Any non-growth change — including an in-place replacement with
    different content — fails the fingerprint and falls back to the full
    gather."""
    prev_cols = (prev or {}).get("cols") if isinstance(prev, dict) else None
    if not isinstance(prev_cols, dict):
        prev_cols = {}
    cols = {}
    for name in table.names:
        kind = table.kind(name)
        entry = {"kind": kind}
        counts = table.chunk_rows(name) if hasattr(table, "chunk_rows") \
            else None
        nchunks = len(counts) if counts is not None else None
        if nchunks is not None:
            entry["chunks"] = nchunks
            entry["sig"] = _chunk_prefix_sig(table, name, nchunks)
        pentry = prev_cols.get(name)
        grown = (
            isinstance(pentry, dict)
            and pentry.get("kind") == kind
            and nchunks is not None
            and isinstance(pentry.get("chunks"), int)
            and nchunks >= pentry["chunks"]
            # the old chunks must be an UNCHANGED prefix of the current
            # index: an in-place replacement with >= chunks is not growth
            and pentry.get("sig") is not None
            and _chunk_prefix_sig(table, name, pentry["chunks"])
            == pentry["sig"]
        )
        if (
            grown
            and "min" in pentry
            and "max" in pentry
            and nchunks > pentry["chunks"]
        ):
            # fold only the appended chunks' zone maps into the previous
            # bounds; a new chunk without a zone map degrades to col_stats
            maps = table.chunk_zone_maps(name)
            new = (
                maps[pentry["chunks"]:] if maps is not None else [None]
            )
            if all(m is not None for m in new):
                entry["min"] = min(
                    [pentry["min"]] + [m[0] for m in new]
                )
                entry["max"] = max(
                    [pentry["max"]] + [m[1] for m in new]
                )
        if "min" not in entry:
            stats = table.col_stats(name)
            if stats is not None:
                entry["min"], entry["max"] = stats
        cols[name] = entry
    return {"rows": int(table.nrows), "cols": cols}


class StatsCollector:
    """Memoized per-shard stats for a worker's data dir.

    Called from both the worker's main loop and its liveness heartbeat
    thread, so gathering must stay cheap: full stats are memoized per shard
    and re-gathered only when the shard's meta identity changes."""

    #: min seconds between full stamp sweeps: inside the window collect()
    #: returns the previous snapshot OBJECT without touching the filesystem,
    #: so per-heartbeat cost is O(1) however many shards/columns exist (the
    #: identity also lets the WRM builder skip re-advertising unchanged
    #: stats, see WorkerBase.prepare_wrm)
    MIN_REFRESH_S = 5.0

    def __init__(self, table_opener=None, min_refresh_s=None):
        self._open = table_opener
        self._memo = {}  # shard name -> (stamp, stats dict)
        self.min_refresh_s = (
            self.MIN_REFRESH_S if min_refresh_s is None else min_refresh_s
        )
        self._snapshot = None
        self._snapshot_names = None
        self._snapshot_ts = 0.0

    def invalidate(self):
        """Drop the snapshot window so the NEXT collect re-stamps every
        shard immediately.  Called by the worker's append path: a grown
        shard must advertise fresh stats on the next heartbeat, not after
        ``min_refresh_s`` — stale controller-side min/max would prune
        shards whose appended rows now match.  Per-shard memos are kept:
        the re-stamp detects the one grown shard and refreshes it
        incrementally."""
        self._snapshot = None
        self._snapshot_names = None
        self._snapshot_ts = 0.0

    def collect(self, data_dir, names):
        """{shard name: stats} for every shard that opens cleanly.  Returns
        the SAME dict object until the refresh window elapses or the shard
        list changes — callers may use identity to detect staleness."""
        import time

        from bqueryd_tpu.storage.ctable import rootdir_cache_key

        now = time.time()
        if (
            self._snapshot is not None
            and now - self._snapshot_ts < self.min_refresh_s
            and self._snapshot_names == tuple(names)
        ):
            return self._snapshot
        out = {}
        for name in names:
            rootdir = os.path.join(data_dir, name)
            try:
                table = (
                    self._open(rootdir)
                    if self._open is not None
                    else _default_open(rootdir)
                )
                stamp = rootdir_cache_key(rootdir)
                hit = self._memo.get(name)
                if hit is not None and hit[0] == stamp:
                    out[name] = hit[1]
                    continue
                # stale memo: re-gather INCREMENTALLY against the previous
                # snapshot (append-grown shards fold only the new chunks'
                # zone maps)
                stats = gather_table_stats(
                    table, prev=hit[1] if hit is not None else None
                )
                self._memo[name] = (stamp, stats)
                out[name] = stats
            except Exception:
                continue  # an unreadable shard simply advertises no stats
        for gone in set(self._memo) - set(names):
            self._memo.pop(gone, None)
        # keep the previous snapshot OBJECT when nothing changed, so the
        # WRM builder's identity check keeps suppressing re-advertisement
        if self._snapshot is not None and out == self._snapshot:
            out = self._snapshot
        self._snapshot = out
        self._snapshot_names = tuple(names)
        self._snapshot_ts = now
        return out


def _default_open(rootdir):
    from bqueryd_tpu.storage.ctable import ctable

    return ctable(rootdir, mode="r", auto_cache=True)


def zone_can_match(lo, hi, op, value):
    """Per-chunk twin of :func:`stats_can_match`: True unless NO value in
    the chunk's ``[lo, hi]`` zone map can satisfy ``(op, value)``.  Values
    are PHYSICAL (the worker translates datetimes to int64 ns before
    calling); anything incomparable conservatively matches — garbage must
    read as "cannot prune", never raise mid-query.

    Only the provable ops prune.  ``!=``/``not in`` are deliberately
    excluded even when ``lo == hi``: a float chunk's zone map skips NaNs,
    and NaN rows *do* satisfy ``!=`` — pruning on bounds alone would drop
    them."""
    try:
        if op == "==":
            return not (value < lo or value > hi)
        if op == ">":
            return hi > value
        if op == ">=":
            return hi >= value
        if op == "<":
            return lo < value
        if op == "<=":
            return lo <= value
        if op == "in":
            if isinstance(value, (list, tuple, set, frozenset)) and value:
                return any(not (v < lo or v > hi) for v in value)
            return True
    except TypeError:
        return True
    return True


def stats_can_match(stats, where_terms):
    """False only if NO row of the shard can satisfy the conjunction, judged
    from advertised stats alone.  Mirrors ``ops.predicates.shard_can_match``
    restricted to plain numeric comparisons; unknown columns, kinds, ops or
    value types conservatively match."""
    cols = stats.get("cols") if isinstance(stats, dict) else None
    if not isinstance(cols, dict):
        cols = {}
    for term in where_terms or []:
        try:
            column, op, value = term
        except (TypeError, ValueError):
            continue
        entry = cols.get(column)
        if not isinstance(entry, dict) or entry.get("kind") != "numeric":
            continue
        lo, hi = entry.get("min"), entry.get("max")
        # advertised bounds must themselves be numbers: garbage stats must
        # read as "cannot prune", never raise mid-launch
        if not isinstance(lo, _NUMBER) or not isinstance(hi, _NUMBER):
            continue
        if op == "in":
            if (
                isinstance(value, (list, tuple, set, frozenset))
                and value
                and all(isinstance(v, _NUMBER) for v in value)
                and all(v < lo or v > hi for v in value)
            ):
                return False
            continue
        if not isinstance(value, _NUMBER) or isinstance(value, bool):
            continue
        if op == "==" and (value < lo or value > hi):
            return False
        if op == ">" and hi <= value:
            return False
        if op == ">=" and hi < value:
            return False
        if op == "<" and lo >= value:
            return False
        if op == "<=" and lo > value:
            return False
    return True
