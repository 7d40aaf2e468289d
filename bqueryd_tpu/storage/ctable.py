"""tpucolz ctable: chunked, compressed, columnar on-disk tables.

The storage role bcolz plays in the reference (opened at reference
bqueryd/worker.py:291, written by tests via ``ctable.fromdataframe``,
reference tests/test_simple_rpc.py:78-99), redesigned for the TPU data path:

* **single data file per column** (``cols/<name>/data.tpc``) holding
  back-to-back compressed chunks plus a JSON chunk index — one sequential read
  per column, then a multithreaded native decode straight into one contiguous
  host buffer sized for a single host→device transfer;
* **dictionary encoding at ingest** for string/category columns: the physical
  column is dense int32 codes and the dictionary is stored beside it.  Group
  keys are therefore *pre-factorized on disk*, which is what the TPU kernels
  want (TPUs can't factorize strings) and subsumes bquery's on-disk
  factorization cache;
* **datetimes stored as int64 nanoseconds** (TPU-friendly), reconstructed on
  the way out;
* same sharding semantics as the reference: a table is a directory named
  ``*.bcolz`` (full table) or ``*.bcolzs`` (shard), discovered by workers
  scanning their data_dir.

Layout::

    <root>/
      meta.json                  format header, nrows, column order
      __attrs__.json             user attrs (provenance metadata etc.)
      cols/<enc(name)>/meta.json chunk index: [{offset,csize,usize,nrows}...]
      cols/<enc(name)>/data.tpc  compressed chunks, back to back
      cols/<enc(name)>/dictionary.json   (dict-encoded columns only)
"""

import json
import os
import zlib

import numpy as np

from bqueryd_tpu.storage import codec
from bqueryd_tpu.utils.cache import BytesCappedCache
from bqueryd_tpu.utils.fs import mkdir_p, rm_file_or_dir

FORMAT_NAME = "tpucolz"
FORMAT_VERSION = 1
DEFAULT_CHUNKLEN = 1 << 18  # rows per chunk

KIND_NUMERIC = "numeric"
KIND_DICT = "dict"
KIND_DATETIME = "datetime"


def _pd():
    import pandas as pd

    return pd


def _atomic_json_dump(obj, path):
    """Write-then-rename so a crash mid-write never truncates committed data."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def _enc_name(name):
    out = []
    for ch in name:
        if ch.isalnum() or ch in "._-":
            out.append(ch)
        else:
            out.append("%%%02X" % ord(ch))
    return "".join(out)


class _ColumnMeta:
    def __init__(self, name, kind, dtype, chunks=None, vmin=None, vmax=None):
        self.name = name
        self.kind = kind
        self.dtype = dtype  # physical numpy dtype string, e.g. "<i8"
        self.chunks = chunks or []
        # column-level min/max over physical values (numeric/datetime only):
        # powers host-side shard pruning before any decompression
        self.vmin = vmin
        self.vmax = vmax

    def to_json(self):
        return {
            "name": self.name,
            "kind": self.kind,
            "dtype": self.dtype,
            "chunks": self.chunks,
            "min": self.vmin,
            "max": self.vmax,
        }

    @classmethod
    def from_json(cls, d):
        return cls(
            d["name"], d["kind"], d["dtype"], d["chunks"],
            d.get("min"), d.get("max"),
        )


# Process-wide decoded-column cache: the in-memory analogue of bquery's
# auto_cache (reference bqueryd/worker.py:291).  Keyed by (realpath, column,
# data-file mtime+size) so reshard/activation invalidates naturally.
_COLUMN_CACHE = BytesCappedCache(
    int(os.environ.get("BQUERYD_TPU_COLUMN_CACHE_BYTES", 2 * 1024**3))
)


def free_cachemem():
    """Drop the process-wide decoded-column cache (parity with bquery's
    ``free_cachemem``, called post-task at reference bqueryd/worker.py:330)."""
    _COLUMN_CACHE.clear()


def column_cache_stats():
    """Decoded-column cache counters (hits/misses/evictions/bytes) — feeds
    the bench ``pipeline`` section's storage-decode hit rate."""
    return _COLUMN_CACHE.stats()


def _cache_get(key):
    return _COLUMN_CACHE.get(key)


def _cache_put(key, arr):
    _COLUMN_CACHE.put(key, arr)


# -- sidecar persistence helpers (factor + composite caches) ---------------

def _sidecar_enabled():
    return os.environ.get("BQUERYD_TPU_DISK_FACTOR_CACHE", "1") == "1"


def _sidecar_save(dirname, path, **arrays):
    """Atomic best-effort npz write (tempfile + rename); failures are
    swallowed — read-only media just keeps paying the recompute."""
    import tempfile

    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(dir=dirname, suffix=".sidecar.tmp")
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
    except Exception:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _sidecar_load(path, stamp, digest=None):
    """(codes, uniques) from an npz sidecar iff its stamp (and digest, when
    given) match; None on absent/stale/corrupt."""
    if stamp is None:
        return None
    try:
        with np.load(path, allow_pickle=False) as z:
            if not np.array_equal(z["stamp"], stamp):
                return None
            if digest is not None and z["digest"].tobytes() != digest:
                return None
            return z["codes"], z["uniques"]
    except Exception:
        return None


def _narrow_codes(codes, uniques):
    codes = np.asarray(codes)
    if len(uniques) < 2**31 and codes.dtype != np.int32:
        codes = codes.astype(np.int32)  # halves sidecar IO
    return codes


class ctable:
    """Open (mode='r'/'a') or create (mode='w') a tpucolz table directory."""

    def __init__(self, rootdir, mode="r", auto_cache=True, nthreads=0,
                 chunklen=DEFAULT_CHUNKLEN, codec_id=codec.DEFAULT_CODEC):
        self.rootdir = rootdir
        self.mode = mode
        self.auto_cache = auto_cache
        self.nthreads = nthreads
        self._meta_path = os.path.join(rootdir, "meta.json")
        self._attrs_path = os.path.join(rootdir, "__attrs__.json")
        if mode == "w":
            rm_file_or_dir(rootdir)
            mkdir_p(os.path.join(rootdir, "cols"))
            self.nrows = 0
            self.chunklen = chunklen
            self.codec_id = codec_id
            self._columns = {}
            self._order = []
            self._dictionaries = {}
            self._dict_lookups = {}
            self._write_meta()
        elif mode in ("r", "a"):
            if not os.path.exists(self._meta_path):
                raise IOError(f"not a tpucolz table: {rootdir}")
            with open(self._meta_path) as f:
                meta = json.load(f)
            if meta.get("format") != FORMAT_NAME:
                raise IOError(f"unknown table format in {rootdir}")
            self.nrows = meta["nrows"]
            self.chunklen = meta["chunklen"]
            self.codec_id = meta["codec"]
            self._order = meta["columns"]
            self._columns = {}
            for name in self._order:
                with open(self._col_path(name, "meta.json")) as f:
                    self._columns[name] = _ColumnMeta.from_json(json.load(f))
            self._dictionaries = {}
            self._dict_lookups = {}
        else:
            raise ValueError(f"bad mode {mode!r}")

    # -- paths & meta ------------------------------------------------------
    def _col_dir(self, name):
        return os.path.join(self.rootdir, "cols", _enc_name(name))

    def _col_path(self, name, fname):
        return os.path.join(self._col_dir(name), fname)

    def _write_meta(self):
        meta = {
            "format": FORMAT_NAME,
            "version": FORMAT_VERSION,
            "nrows": self.nrows,
            "chunklen": self.chunklen,
            "codec": self.codec_id,
            "columns": self._order,
        }
        _atomic_json_dump(meta, self._meta_path)

    # -- public surface ----------------------------------------------------
    @property
    def names(self):
        return list(self._order)

    def __len__(self):
        return self.nrows

    def __contains__(self, name):
        return name in self._columns

    def kind(self, name):
        return self._columns[name].kind

    @property
    def attrs(self):
        if os.path.exists(self._attrs_path):
            with open(self._attrs_path) as f:
                return json.load(f)
        return {}

    def set_attrs(self, **kv):
        attrs = self.attrs
        attrs.update(kv)
        _atomic_json_dump(attrs, self._attrs_path)

    def physical_dtype(self, name):
        """Stored physical numpy dtype of a column (metadata only, no decode)."""
        return np.dtype(self._columns[name].dtype)

    def col_stats(self, name):
        """(min, max) over the column's physical values, or None if unknown
        (dict columns, empty columns, legacy tables)."""
        col = self._columns[name]
        if col.vmin is None:
            return None
        return (col.vmin, col.vmax)

    def dictionary(self, name):
        """The value dictionary of a dict-encoded column (list), else None."""
        col = self._columns[name]
        if col.kind != KIND_DICT:
            return None
        if name not in self._dictionaries:
            with open(self._col_path(name, "dictionary.json")) as f:
                self._dictionaries[name] = json.load(f)
        return self._dictionaries[name]

    def dict_lookup(self, name):
        """Memoized {value: code} mapping for a dict column (predicate
        translation hot path — rebuilt only when the dictionary grows)."""
        dictionary = self.dictionary(name)
        if dictionary is None:
            return None
        cached = self._dict_lookups.get(name)
        if cached is None or len(cached) != len(dictionary):
            cached = {v: i for i, v in enumerate(dictionary)}
            self._dict_lookups[name] = cached
        return cached

    # -- on-disk factorize cache -------------------------------------------
    # The full analogue of bquery's auto_cache=True (reference
    # bqueryd/worker.py:291): factorizations persist NEXT TO THE SHARD, so a
    # cold process (or a different worker adopting the shard) skips the
    # decode+factorize entirely.  Validated against the column data file's
    # (mtime, size) and the table's row count — reshard/activation rewrites
    # the data file, invalidating naturally; a plain directory move keeps
    # both, and keeps the cache valid, which is correct (content unchanged).
    #
    # TOCTOU discipline: callers must capture the stamp BEFORE reading the
    # column bytes they factorize and pass it to the store.  If the shard is
    # rewritten mid-computation, the sidecar then lands with the OLD stamp
    # and every future load misses (recompute) — stamping at store time
    # would instead pair new-stamp with old-bytes codes and poison the
    # cache permanently.

    _FACTOR_CACHE_VERSION = 1

    def factor_stamp(self, name):
        """Identity of one column's data bytes (+ table rows); capture
        before reading, pass to the matching ``*_cache_store``.  st_ino
        closes the same-mtime same-size atomic-rewrite window exactly as
        :func:`rootdir_cache_key` does for meta.json; a same-filesystem
        directory move is a rename (inode kept, cache stays valid — content
        unchanged), a cross-filesystem copy invalidates conservatively."""
        try:
            st = os.stat(self._col_path(name, "data.tpc"))
        except OSError:
            return None
        return np.array(
            [self._FACTOR_CACHE_VERSION, st.st_mtime_ns, st.st_size,
             st.st_ino, self.nrows],
            dtype=np.int64,
        )

    def composite_stamp(self, cols):
        stamps = [self.factor_stamp(c) for c in cols]
        if any(s is None for s in stamps):
            return None
        return np.concatenate(stamps)

    def _composite_path(self, cols):
        tag = zlib.crc32("|".join(_enc_name(c) for c in cols).encode())
        return self._col_path(cols[0], f"composite_{tag:08x}.npz")

    def factor_cache_load(self, name):
        """Load a persisted (codes, uniques) factorization for a column, or
        None when absent/stale/disabled."""
        if not _sidecar_enabled():
            return None
        return _sidecar_load(
            self._col_path(name, "factor.npz"), self.factor_stamp(name)
        )

    def factor_cache_store(self, name, codes, uniques, stamp):
        """Persist a factorization sidecar (atomic, best-effort: read-only
        media simply keeps paying the factorize).  ``stamp`` must have been
        captured via :meth:`factor_stamp` before the column was read."""
        uniques = np.asarray(uniques)
        if not _sidecar_enabled() or stamp is None:
            return
        if uniques.dtype == object:
            return  # npz would need pickle; object keys never take this path
        _sidecar_save(
            self._col_dir(name),
            self._col_path(name, "factor.npz"),
            stamp=stamp,
            codes=_narrow_codes(codes, uniques),
            uniques=uniques,
        )

    def composite_cache_load(self, cols, digest, stamp=None):
        """Load a persisted multi-key composite factorization
        (packed-code inverse + observed composites), or None.  ``digest``
        must capture everything the packed codes depend on beyond this
        shard's data — the executor hashes the global dictionaries and
        cardinalities into it, so a change in the SHARD SET invalidates.
        Pass the ``stamp`` captured before the key columns were read so the
        sidecar is validated against the bytes the caller actually holds,
        not whatever the file mutated into since."""
        if not _sidecar_enabled():
            return None
        if stamp is None:
            stamp = self.composite_stamp(cols)
        return _sidecar_load(
            self._composite_path(cols), stamp, digest=digest
        )

    def composite_cache_store(self, cols, digest, codes, uniques, stamp):
        """``stamp`` must come from :meth:`composite_stamp` captured before
        the key columns were read (see the TOCTOU note above)."""
        if not _sidecar_enabled() or stamp is None:
            return
        uniques = np.asarray(uniques)
        _sidecar_save(
            self._col_dir(cols[0]),
            self._composite_path(cols),
            stamp=stamp,
            digest=np.frombuffer(digest, dtype=np.uint8),
            codes=_narrow_codes(codes, uniques),
            uniques=uniques,
        )

    def committed_chunks(self, name):
        """This instance's committed chunk prefix for a column: the chunks
        covering exactly ``self.nrows`` rows.  Appends commit through the
        final meta.json rename, so a reader opened mid-append may see extra
        UNCOMMITTED chunks in the column index — they are excluded here,
        which is what gives concurrent readers a consistent row-count
        snapshot.  None when the index cannot cover the committed row count
        on a chunk boundary (truncated/torn data — the caller raises)."""
        col = self._columns[name]
        acc = 0
        out = []
        for c in col.chunks:
            if acc >= self.nrows:
                break
            out.append(c)
            acc += int(c["nrows"])
        return out if acc == self.nrows else None

    def chunk_rows(self, name=None):
        """Per-chunk row counts of the committed chunk grid (all columns of
        a table share one grid: every append chunks all columns by the same
        batch + chunklen), or None when the grid is unreadable.  The grid is
        what zone-map pruning and delta tails select over."""
        if name is None:
            if not self._order:
                return None
            name = self._order[0]
        chunks = self.committed_chunks(name)
        if chunks is None:
            return None
        return [int(c["nrows"]) for c in chunks]

    def chunk_zone_maps(self, name):
        """Per-chunk ``(min, max)`` zone maps over the committed chunks of a
        numeric/datetime column (physical values; datetimes in int64 ns), or
        None when the column kind carries no zone maps.  Individual entries
        are None for chunks written before zone maps existed or holding no
        stats-able values (all-NaN/NaT) — those conservatively match every
        predicate."""
        col = self._columns[name]
        if col.kind not in (KIND_NUMERIC, KIND_DATETIME):
            return None
        chunks = self.committed_chunks(name)
        if chunks is None:
            return None
        return [
            (c["min"], c["max"])
            if c.get("min") is not None and c.get("max") is not None
            else None
            for c in chunks
        ]

    def chunk_view(self, chunk_ids):
        """A :class:`ChunkView` over the given committed-chunk indices."""
        return ChunkView(self, chunk_ids)

    def tail_view(self, start_row):
        """A :class:`ChunkView` of the rows appended after ``start_row``,
        or None when ``start_row`` does not fall on a chunk boundary (only
        append-grown tables have boundary-aligned tails — anything else
        means a rewrite, and the caller must recompute)."""
        counts = self.chunk_rows()
        if counts is None:
            return None
        acc = 0
        for i, n in enumerate(counts):
            if acc == start_row:
                return ChunkView(self, range(i, len(counts)))
            acc += n
        if acc == start_row:  # tail starts exactly at the end: empty view
            return ChunkView(self, ())
        return None

    def _column_cache_key(self, name, extra=()):
        """Content key of one column's decoded bytes.  Beyond the data
        file's (mtime, size), the key carries this INSTANCE's committed
        chunk count + row count: a reader opened mid-append decodes only
        its snapshot prefix, and caching that truncated array under the
        grown file's stat alone would serve stale bytes to the next reader
        of the fully-committed table."""
        col = self._columns[name]
        data_path = self._col_path(name, "data.tpc")
        st = os.stat(data_path) if os.path.exists(data_path) else None
        return (
            os.path.realpath(self.rootdir),
            name,
            st.st_mtime_ns if st else 0,
            st.st_size if st else 0,
            len(col.chunks),
            self.nrows,
        ) + tuple(extra)

    def column_raw(self, name):
        """Physical column values as one contiguous ndarray: int32 codes for
        dict columns, int64 ns for datetimes, the stored dtype otherwise.
        This is the array the TPU kernels consume.  Decodes the committed
        snapshot only: chunks an in-flight append has written past this
        instance's meta.json row count are ignored."""
        col = self._columns[name]
        data_path = self._col_path(name, "data.tpc")
        key = self._column_cache_key(name)
        if self.auto_cache:
            hit = _cache_get(key)
            if hit is not None:
                return hit
        dtype = np.dtype(col.dtype)
        chunks = self.committed_chunks(name)
        if chunks is None:
            chunk_rows = sum(c["nrows"] for c in col.chunks)
            raise IOError(
                f"inconsistent table {self.rootdir!r}: column {name!r} has "
                f"{chunk_rows} rows in its chunk index but meta says {self.nrows}"
            )
        out = np.empty(self.nrows, dtype=dtype)
        self._read_decode_chunks(name, chunks, out)
        if self.auto_cache:
            out.setflags(write=False)
            _cache_put(key, out)
        return out

    def _read_decode_chunks(self, name, chunks, out):
        """Read + decode an ordered chunk subset into ``out``.  Reads each
        file-contiguous run with one seek+read and rebases offsets into the
        compact buffer — the decoder needs back-to-back chunks, and the
        on-disk index may carry byte gaps (pruned selections, orphaned
        bytes left by a repaired torn append)."""
        if not chunks:
            return
        dtype = out.dtype
        parts = []
        runs = [[chunks[0]]]
        for c in chunks[1:]:
            prev = runs[-1][-1]
            if c["offset"] == prev["offset"] + prev["csize"]:
                runs[-1].append(c)
            else:
                runs.append([c])
        rebased = []
        pos = 0
        data_path = self._col_path(name, "data.tpc")
        with open(data_path, "rb") as f:
            for run in runs:
                start = run[0]["offset"]
                length = run[-1]["offset"] + run[-1]["csize"] - start
                f.seek(start)
                parts.append(f.read(length))
                for c in run:
                    nc = dict(c)
                    nc["offset"] = pos + (c["offset"] - start)
                    rebased.append(nc)
                pos += length
        codec.decode_column_into(
            b"".join(parts) if len(parts) > 1 else parts[0], rebased,
            dtype.itemsize, self.codec_id, out, self.nthreads,
        )

    def column_raw_chunks(self, name, chunk_ids):
        """Decode only the given committed-chunk indices (ascending) of a
        column, returning their rows concatenated — the zone-map-pruning /
        delta-tail decode path.  Only the selected chunks' byte ranges are
        read and decompressed; cached like :meth:`column_raw`, keyed
        additionally by the chunk selection."""
        chunk_ids = [int(i) for i in chunk_ids]
        col = self._columns[name]
        key = self._column_cache_key(name, extra=("sel", tuple(chunk_ids)))
        if self.auto_cache:
            hit = _cache_get(key)
            if hit is not None:
                return hit
        snap = self.committed_chunks(name)
        if snap is None:
            raise IOError(
                f"inconsistent table {self.rootdir!r}: column {name!r} "
                f"chunk index does not cover the committed row count"
            )
        chosen = [snap[i] for i in chunk_ids]
        dtype = np.dtype(col.dtype)
        out = np.empty(sum(c["nrows"] for c in chosen), dtype=dtype)
        self._read_decode_chunks(name, chosen, out)
        if self.auto_cache:
            out.setflags(write=False)
            _cache_put(key, out)
        return out

    def prefetch(self, names, submit=None):
        """Warm the decoded-column cache for ``names`` — the chunk-decode
        prefetch stage of the shard pipeline: the executor submits these on
        the pipeline pool so storage decode of the NEXT query inputs
        overlaps alignment/kernel work instead of serializing in front of
        the H2D loop.  ``submit`` is a ``fn -> Future`` scheduler (default:
        the shared pipeline pool); returns the futures (callers that must
        have the bytes wait on them, everyone else just lets the cache
        absorb the result)."""
        if submit is None:
            from bqueryd_tpu.parallel import pipeline

            submit = pipeline.submit

        def decode(name):
            from bqueryd_tpu.parallel import pipeline

            with pipeline.stage("decode"):
                return self.column_raw(name)

        return [
            submit(decode, name) for name in names if name in self._columns
        ]

    def column(self, name):
        """Logical column values: strings decoded from the dictionary,
        datetimes as datetime64[ns]."""
        return _logical_values(self, name, self.column_raw(name))

    def __getitem__(self, name):
        return self.column(name)

    def todataframe(self, columns=None):
        import pandas as pd

        cols = columns or self._order
        return pd.DataFrame({c: self.column(c) for c in cols}, columns=cols)

    # -- writing -----------------------------------------------------------
    def _append_physical(self, name, values):
        """Append physical values (already codes/int64ns/numeric) as chunks."""
        col = self._columns[name]
        dtype = np.dtype(col.dtype)
        values = np.ascontiguousarray(values, dtype=dtype)
        if (
            col.kind in (KIND_NUMERIC, KIND_DATETIME)
            and dtype.kind in "iuf"  # no stats for complex/bool storage
            and len(values)
        ):
            stat_values = values
            if col.kind == KIND_DATETIME:
                # NaT is INT64_MIN in the ns view; it must not poison vmin
                stat_values = values[values != np.iinfo(np.int64).min]
            if len(stat_values):
                import warnings

                with np.errstate(all="ignore"), warnings.catch_warnings():
                    # all-NaN slices legitimately yield NaN bounds (dropped
                    # below); the RuntimeWarning is noise
                    warnings.simplefilter("ignore", RuntimeWarning)
                    lo = np.nanmin(stat_values)
                    hi = np.nanmax(stat_values)
                if not (isinstance(lo, np.floating) and np.isnan(lo)):
                    lo, hi = lo.item(), hi.item()
                    col.vmin = lo if col.vmin is None else min(col.vmin, lo)
                    col.vmax = hi if col.vmax is None else max(col.vmax, hi)
        mkdir_p(self._col_dir(name))
        data_path = self._col_path(name, "data.tpc")
        offset = os.path.getsize(data_path) if os.path.exists(data_path) else 0
        with open(data_path, "ab") as f:
            for start in range(0, len(values), self.chunklen):
                part = values[start:start + self.chunklen]
                used_codec, buf = codec.encode_chunk(
                    part.tobytes(), dtype.itemsize, self.codec_id
                )
                f.write(buf)
                chunk = {
                    "offset": offset,
                    "csize": len(buf),
                    "usize": part.nbytes,
                    "nrows": len(part),
                    "crc": zlib.crc32(buf) & 0xFFFFFFFF,
                }
                # per-chunk zone map (numeric/datetime): min/max over THIS
                # chunk's values, NaN/NaT-skipped like the column stats —
                # what query-time chunk pruning reads to prove a predicate
                # cannot touch the chunk.  Chunks without one (legacy
                # tables, all-null chunks) conservatively match everything.
                if (
                    col.kind in (KIND_NUMERIC, KIND_DATETIME)
                    and dtype.kind in "iuf"
                    and len(part)
                ):
                    zpart = part
                    if col.kind == KIND_DATETIME:
                        zpart = part[part != np.iinfo(np.int64).min]
                    if len(zpart):
                        import warnings

                        with np.errstate(all="ignore"), \
                                warnings.catch_warnings():
                            warnings.simplefilter(
                                "ignore", RuntimeWarning
                            )
                            zlo = np.nanmin(zpart)
                            zhi = np.nanmax(zpart)
                        if not (
                            isinstance(zlo, np.floating) and np.isnan(zlo)
                        ):
                            chunk["min"] = zlo.item()
                            chunk["max"] = zhi.item()
                # A fallback writer may use a different codec than the table
                # default (e.g. zlib instead of LZ4 without the native lib);
                # record it per chunk so mixed tables stay readable.
                if used_codec != self.codec_id:
                    chunk["codec"] = used_codec
                col.chunks.append(chunk)
                offset += len(buf)
        _atomic_json_dump(col.to_json(), self._col_path(name, "meta.json"))

    def _truncate_uncommitted(self):
        """Drop chunk-index entries past the committed row count: a crash
        mid-append leaves some columns with chunks that the final meta.json
        rename never committed, and appending fresh batches on top of a
        torn index would desynchronize the chunk grid across columns.  The
        orphaned data-file bytes stay (appends write at the file end, so
        chunk offsets remain exact); only the index is repaired."""
        for name in self._order:
            col = self._columns[name]
            committed = self.committed_chunks(name)
            if committed is not None and len(committed) < len(col.chunks):
                col.chunks = committed
                _atomic_json_dump(
                    col.to_json(), self._col_path(name, "meta.json")
                )

    def append_dataframe(self, df):
        """Append a pandas DataFrame; creates columns on first append.

        Atomicity contract: column data + chunk indexes land first, the
        meta.json row count last (atomic rename) — readers opened mid-append
        keep a consistent pre-append snapshot (:meth:`committed_chunks`),
        and a crash between the two leaves uncommitted chunks that the next
        append repairs via :meth:`_truncate_uncommitted`."""
        if self.mode == "r":
            raise IOError("table opened read-only")
        first = not self._columns
        if not first:
            self._truncate_uncommitted()
        if first:
            for name in df.columns:
                kind, phys_dtype = _classify_dtype(df[name].dtype)
                self._columns[name] = _ColumnMeta(name, kind, phys_dtype)
                self._order.append(name)
                mkdir_p(self._col_dir(name))
                if kind == KIND_DICT:
                    self._dictionaries[name] = []
        elif list(df.columns) != self._order:
            raise ValueError("appended frame has different columns")

        for name in self._order:
            col = self._columns[name]
            series = df[name]
            if col.kind == KIND_DICT:
                dictionary = self.dictionary(name)
                # Vectorized ingest: factorize the batch, then remap the
                # batch-local uniques into the persistent dictionary.
                local_codes, local_uniques = _pd().factorize(
                    series.to_numpy(dtype=object), use_na_sentinel=True
                )
                local_codes = np.asarray(local_codes)
                # memoized mapping; mutated in place alongside the dictionary
                # (length-based invalidation in dict_lookup stays correct)
                lookup = self.dict_lookup(name)
                remap = np.empty(len(local_uniques), dtype=np.int32)
                for j, v in enumerate(local_uniques):
                    v = str(v)
                    code = lookup.get(v)
                    if code is None:
                        code = len(dictionary)
                        dictionary.append(v)
                        lookup[v] = code
                    remap[j] = code
                codes = np.where(
                    local_codes < 0, np.int32(-1), remap[local_codes]
                ).astype(np.int32)
                _atomic_json_dump(
                    dictionary, self._col_path(name, "dictionary.json")
                )
                self._append_physical(name, codes)
            elif col.kind == KIND_DATETIME:
                self._append_physical(
                    name, series.to_numpy(dtype="datetime64[ns]").view(np.int64)
                )
            else:
                self._append_physical(name, series.to_numpy())
        self.nrows += len(df)
        self._write_meta()

    def append(self, data):
        """Append rows from a dataframe-like: a pandas DataFrame, or any
        mapping of column name -> array-like (converted in column order).
        The streaming-ingest entry point (``rpc.append`` lands here)."""
        pd = _pd()
        if not isinstance(data, pd.DataFrame):
            data = pd.DataFrame(
                dict(data), columns=self._order or None
            )
        self.append_dataframe(data)
        return len(data)

    def flush(self):
        self._write_meta()

    # -- constructors ------------------------------------------------------
    @classmethod
    def fromdataframe(cls, df, rootdir, chunklen=DEFAULT_CHUNKLEN,
                      codec_id=codec.DEFAULT_CODEC, mode="w"):
        ct = cls(rootdir, mode=mode, chunklen=chunklen, codec_id=codec_id)
        ct.append_dataframe(df)
        return ct


def _logical_values(table, name, raw):
    """Physical -> logical values for one column (shared by ctable and
    ChunkView): dictionary decode for dict columns, datetime64 view for
    datetimes, passthrough otherwise."""
    kind = table.kind(name)
    if kind == KIND_DICT:
        dictionary = np.asarray(table.dictionary(name), dtype=object)
        out = np.empty(len(raw), dtype=object)
        valid = raw >= 0
        out[valid] = dictionary[raw[valid]]
        out[~valid] = None
        return out
    if kind == KIND_DATETIME:
        return raw.view("datetime64[ns]")
    return raw


class ChunkView:
    """Read-only row subset of a ctable at chunk granularity.

    The two streaming-ingest consumers:

    * **zone-map pruning** — a selective predicate whose per-chunk min/max
      prove most chunks unmatchable executes over a view of only the
      surviving chunks, so storage decode / alignment / H2D touch a
      fraction of the table (:func:`bqueryd_tpu.ops.predicates.
      chunk_pruned_table`);
    * **delta maintenance** — the chunks an append added (named by
      :func:`bqueryd_tpu.ops.workingset.growth_since`, viewed via
      :meth:`ctable.chunk_view`) re-aggregate alone, and the delta partial
      merges into the cached result; :meth:`ctable.tail_view` is the
      storage-level convenience for the same "rows after N" selection.

    The view quacks like a read-only table for every query-time consumer
    (engine, mesh executor, DAG executor): ``column_raw`` decodes only the
    selected chunks, ``col_stats`` folds the selected chunks' zone maps
    (falling back to the parent's conservative column stats), dictionaries
    and dtypes delegate.  It deliberately exposes NO sidecar methods
    (``factor_stamp``/``factor_cache_load``), so factorize caching falls
    back to the in-memory layer keyed by the view's own cache identity —
    a sidecar stored for a chunk subset would poison full-table loads.
    Row order is preserved (chunks ascending), so float reductions over
    the surviving rows are bit-identical to the masked full-table pass.
    """

    def __init__(self, parent, chunk_ids):
        self.parent = parent
        self.chunk_ids = sorted(int(i) for i in chunk_ids)
        counts = parent.chunk_rows()
        if counts is None:
            raise IOError(
                f"table {parent.rootdir!r} has no readable chunk grid"
            )
        if self.chunk_ids and self.chunk_ids[-1] >= len(counts):
            raise IndexError(
                f"chunk id {self.chunk_ids[-1]} out of range "
                f"({len(counts)} committed chunks)"
            )
        self.nrows = sum(counts[i] for i in self.chunk_ids)
        self.rootdir = None  # table_cache_key falls through to the token
        self.mode = "r"
        self.auto_cache = parent.auto_cache
        # deterministic cache identity: parent meta identity + row count +
        # the chunk selection — an appended/rewritten parent (or a
        # different selection) yields a different token, so every
        # content-keyed cache (factorize, align, codes, blocks) invalidates
        # exactly like it does for real tables
        pkey = rootdir_cache_key(getattr(parent, "rootdir", None))
        if pkey is None:
            pkey = ("unstable", os.urandom(8).hex())
        sig = zlib.crc32(
            np.asarray(self.chunk_ids, dtype=np.int64).tobytes()
        )
        self._bqueryd_cache_token = (
            f"{pkey}|r{int(parent.nrows)}|"
            f"c{len(self.chunk_ids)}:{sig:08x}"
        )

    # -- delegated metadata ------------------------------------------------
    @property
    def names(self):
        return self.parent.names

    def __len__(self):
        return self.nrows

    def __contains__(self, name):
        return name in self.parent

    def kind(self, name):
        return self.parent.kind(name)

    def physical_dtype(self, name):
        return self.parent.physical_dtype(name)

    def dictionary(self, name):
        return self.parent.dictionary(name)

    def dict_lookup(self, name):
        return self.parent.dict_lookup(name)

    def chunk_rows(self, name=None):
        counts = self.parent.chunk_rows(name)
        if counts is None:
            return None
        return [counts[i] for i in self.chunk_ids]

    def chunk_zone_maps(self, name):
        maps = self.parent.chunk_zone_maps(name)
        if maps is None:
            return None
        return [maps[i] for i in self.chunk_ids]

    def col_stats(self, name):
        """(min, max) over the SELECTED chunks' zone maps when every
        selected chunk carries one; the parent's column-level stats (a
        conservative superset range) otherwise."""
        maps = self.chunk_zone_maps(name)
        if maps and all(m is not None for m in maps):
            return (
                min(m[0] for m in maps),
                max(m[1] for m in maps),
            )
        return self.parent.col_stats(name)

    # -- data --------------------------------------------------------------
    def column_raw(self, name):
        return self.parent.column_raw_chunks(name, self.chunk_ids)

    def column(self, name):
        return _logical_values(self.parent, name, self.column_raw(name))

    def __getitem__(self, name):
        return self.column(name)

    def prefetch(self, names, submit=None):
        """Same contract as :meth:`ctable.prefetch`, decoding only the
        selected chunks — the executor's stage-1 prefetch works on views."""
        if submit is None:
            from bqueryd_tpu.parallel import pipeline

            submit = pipeline.submit

        def decode(name):
            from bqueryd_tpu.parallel import pipeline

            with pipeline.stage("decode"):
                return self.column_raw(name)

        return [
            submit(decode, name) for name in names if name in self.parent
        ]


def _classify_dtype(dtype):
    """Map a pandas dtype to (kind, physical numpy dtype string)."""
    dtype = getattr(dtype, "numpy_dtype", dtype)  # pandas extension dtypes
    try:
        np_dtype = np.dtype(dtype)
    except TypeError:
        return KIND_DICT, "<i4"
    if np_dtype.kind == "M":
        return KIND_DATETIME, "<i8"
    if np_dtype.kind in "biufc":
        return KIND_NUMERIC, np_dtype.str
    return KIND_DICT, "<i4"


def open_ctable(rootdir, mode="r", **kw):
    return ctable(rootdir, mode=mode, **kw)


def rootdir_cache_key(rootdir, canonical=None):
    """Stat-based identity of a table rootdir, or None when meta.json is
    not stat-able.  st_ino closes the same-mtime rewrite window: meta.json
    is written atomically (tempfile + rename), so every activation yields a
    fresh inode even when the timestamp granularity would hide the change.
    ``canonical``, where the caller already knows ``realpath(rootdir)``
    (the worker's open), stands in for asking it again; the stat is made
    either way."""
    try:
        st = os.stat(os.path.join(rootdir, "meta.json"))
    except (OSError, TypeError):
        return None
    if canonical is None:
        canonical = os.path.realpath(rootdir)
    return (canonical, st.st_ino, st.st_mtime_ns)


def table_cache_key(table):
    """Cache identity of an on-disk table: path + metadata mtime + rows, so
    reshard/activation (which rewrites meta.json) invalidates naturally.
    Tables without a stat-able meta.json get a one-time random token pinned
    to the instance (NOT id(): CPython reuses addresses after GC, which
    would let a new table hit a dead table's cached blocks).

    This asks the filesystem (a stat and a realpath) at every call.  A
    worker's unit of work does not call it: ``WorkerNode._open_identified``
    reads :func:`rootdir_cache_key` once per shard at open and hands the
    same value, ``key + (nrows,)``, down to the result cache, the delta
    store and the mesh executor.  What calls it is a consumer given bare
    tables: the engine path, tests, and a :class:`ChunkView` (its token)."""
    key = rootdir_cache_key(getattr(table, "rootdir", None))
    if key is not None:
        return key + (int(table.nrows),)
    token = getattr(table, "_bqueryd_cache_token", None)
    if token is None:
        token = os.urandom(8).hex()
        try:
            table._bqueryd_cache_token = token
        except AttributeError:
            pass  # slotted/frozen table: unique token per call = no reuse
    return ("unstable", token)
