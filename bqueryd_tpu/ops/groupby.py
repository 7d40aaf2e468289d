"""Segment-reduction groupby kernels.

The TPU replacement for bquery's Cython ``ctable.groupby`` (the only place
real computation happens in the reference, reference bqueryd/worker.py:311-314).
Design:

* group keys arrive as dense int codes (see :mod:`bqueryd_tpu.ops.factorize`);
* the hot reduction (sums and counts) runs on the **MXU as a one-hot
  matmul**, not a scatter: XLA lowers ``segment_sum`` to scatter-add, which
  on TPU costs ~90 ms for 10 M rows (and ~9x that again in emulated-s64
  mode), while the same contraction as ``limbs[blocks, R, K] x
  one_hot(codes)[blocks, K, G]`` rides the systolic array in ~1-4 ms.
  Exactness is preserved by 8-bit limb decomposition: every value is biased
  to unsigned, split into byte limbs (each exactly representable in
  bfloat16), and block sums are bounded below 2^24 so the MXU's float32
  accumulation is exact; per-block tables are then recombined in uint64
  (mod-2^64 arithmetic == two's complement) — bit-exact for the full int64
  range.  Counts ride along as a row of ones in the same matmul.  min/max
  and cardinalities above ``matmul_groups_limit()`` use the scatter path,
  whose exact integer counts and sums take the form the backend does
  cheapest — never the emulated-s64 scatter.  On an accelerator, where a
  scatter retires one update every ~9 ns at any group count, that is ONE
  sort of the rows by group code with the contributions carried as sort
  operands, then wrapping 64-bit prefix sums differenced at the group
  boundaries (:class:`_SortedGroups`).  On a CPU backend it is 16-bit-limb
  int32 scatters over 64Ki row blocks (mod-2^32 wrap recovered by a uint32
  bitcast), switching to a sort + prefix-diff reduction at extreme
  cardinality where the blocked table would outgrow
  ``_MAX_BLOCK_SEGMENTS``.  A float64 sum
  (and an integer mean, which accumulates in float64 like pandas) rides
  neither: on either route it is :func:`_float64_segment_sum` — on an
  accelerator a dense masked reduction in float64 up to
  ``_DENSE_SUM_GROUPS`` groups (no sort, no gather, no scatter) and above
  it a segmented scan over the rows sorted by group code, the value
  carried through the sort (:func:`_segmented_sums`; the scatter route's
  own sort where it has one), in both of which a group's sum meets only
  its own values — while its ``rows`` and the mean's count take
  the route's own form, so such a query goes by the MXU route wherever
  that is allowed: the counts are two rows of the one-hot dot.
  A pure-NumPy twin (:func:`host_partial_tables`) serves latency-aware
  host routing for small inputs;
* results are produced as **partial tables** (pytrees of fixed-width arrays,
  e.g. mean = {sum, count}) that are closed under elementwise merge: merging
  shard partials is ``combine_partials`` on host/device or
  ``parallel.devicemerge.scatter_merge_partials`` over a mesh axis, and only :func:`finalize` turns partials into final
  values.  This is what moves the reference's tar-merge + client re-groupby
  (reference bqueryd/controller.py:186-211, rpc.py:150-173) onto the
  interconnect — and fixes the reference's sum-of-shard-means quirk
  (reference bqueryd/rpc.py:171), since mean partials carry (sum, count).

Aggregation ops supported: the bquery set (sum, mean, count, count_na,
count_distinct, sorted_count_distinct) plus min/max.
"""

import functools
import os
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# canonical definitions live JAX-free in models.query (the controller needs
# them to decide shard batching without importing jax); re-exported here
from bqueryd_tpu.models.query import (  # noqa: F401
    AGG_OPS,
    MERGEABLE_OPS,
    extremum_fill,
)
# compile/call accounting on the jit entry points below (obs.profile is
# stdlib-only at import; the wrappers pass straight through under an outer
# trace and under the BQUERYD_TPU_METRICS=0 kill switch)
from bqueryd_tpu.obs import profile as _obsprofile


def _accum_dtype(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer) or jnp.issubdtype(dtype, jnp.bool_):
        return jnp.int64
    if jnp.issubdtype(dtype, jnp.floating) and jax.config.jax_enable_x64:
        # f32 scatter sums accumulate in f64: the MXU path represents f32
        # losslessly via its 3-limb split, and the scatter path must match
        # that accuracy (a plain f32 segment_sum drifts ~1e-4 at 1M-row
        # groups, outside the bench's float gate)
        return jnp.float64
    return dtype


#: native host-groupby routing: below the row floor thread spawn overhead
#: beats the striping win; above the group ceiling the per-thread [G]
#: accumulators (16 B x workers x G) stop being cache/memory friendly
_NATIVE_GROUPBY_MIN_ROWS = 200_000
_NATIVE_GROUPBY_MAX_GROUPS = 1 << 18

#: float64 mantissa bound: a weighted bincount over int64 values is exact
#: iff every partial sum stays below this (|partial| <= n rows x max|v|).
#: Shared with the host-routing cost estimate (models.query), which must
#: rate queries beyond it at the limb-fallback cost.
HOST_EXACT_SUM_BOUND = 2**53


def _null_mask(values):
    if jnp.issubdtype(values.dtype, jnp.floating):
        return jnp.isnan(values)
    return jnp.zeros(values.shape, dtype=bool)


def _measure_null(values, sentinel):
    """Per-measure null rows, or None when the measure cannot be null.

    ``sentinel`` marks an integer encoding whose one reserved value means
    missing — datetime columns store NaT as int64 min (pandas convention) —
    so those rows must vanish from counts/extrema exactly like float NaNs.
    """
    if sentinel is not None:
        return values == jnp.asarray(sentinel, dtype=values.dtype)
    if jnp.issubdtype(values.dtype, jnp.floating):
        return jnp.isnan(values)
    return None


def _normalize_sentinels(null_sentinels, n):
    if null_sentinels is None:
        return (None,) * n
    t = tuple(
        None if s is None else int(s) for s in null_sentinels
    )
    if len(t) != n:
        raise ValueError(
            f"null_sentinels has {len(t)} entries for {n} measures"
        )
    return t


#: rows per scatter block in the exact-int64 segment sum.  A 16-bit limb's
#: block sum stays below ``2^16 (max limb) * 2^16 (rows) = 2^32``: exactly
#: representable in the int32 scatter's mod-2^32 arithmetic, recovered by a
#: uint32 bitcast (unsigned limbs) or plain sign extension (the top limb,
#: whose magnitude is bounded by 2^16 * 2^15 = 2^31).
_SUM_BLOCK = 65536

#: above this many scatter buckets (blocks x groups) the blocked decomposition
#: stops paying for itself in HBM; switch to the sort-based path
_MAX_BLOCK_SEGMENTS = 1 << 25

#: up to this many groups a float64 per-group sum on an accelerator is the
#: dense masked reduction (:func:`_dense_segment_sum`), whose cost grows with
#: the group count; above it the sorted rows' segmented scan
#: (:func:`_segmented_sums`), whose cost does not.
#: OBSERVED, not set by anyone: standalone timings at 11 010 048 rows on a
#: TPU v5e (PERF.md section 6, PR 31) read 2.3 ms at 10 groups, 18 at 256,
#: 136 at 2 048, 271 at 4 096 and 406 at 6 144 for the dense sum (0.066 ms a
#: group) against 346-410 at any count for the sort + gather + float64
#: prefix difference that stood above it until PR 38 (which also missed the
#: 1e-7 a group is promised: PERF.md section 6, PR 38)
_DENSE_SUM_GROUPS = 2048


#: kernel routes partial_tables accepts as ``strategy`` (None == "auto", what
#: every served query runs; the rest is the seam by which tests and
#: chip_smoke.py reach one kernel).  "matmul" is advisory — every
#: profitability/backend guard still applies — while "scatter"/"sort" are
#: binding (both are always-correct fallbacks).
KERNEL_STRATEGIES = ("auto", "matmul", "scatter", "sort")


#: keys a level of the boundary search (:func:`_group_ends`) reads as one
#: row.  OBSERVED: standalone at 11 010 048 rows x 73 728 groups on a TPU
#: v5e (PERF.md section 6) the search takes 1.12 ms at 128 (1.07 with
#: a top level of 672 keys), 1.38 at 256, 1.79 at 512 and 3.13 at 1 024
#: (one level, 10 752 keys compared at the top), where ``searchsorted``'s
#: loop took about 13 inside the launch
_SEARCH_ROW = 128


def _group_ends(key_s, n_groups, row=None):
    """int32[n_groups]: one past the last row of group ``g`` in ``key_s``,
    the keys sorted ascending, each in ``[0, n_groups]`` (``n_groups`` the
    rows that count for no group) — ``searchsorted(key_s, arange(n_groups),
    side="right")`` element for element, read a row of keys at a time.

    The keys are viewed as rows of ``row`` (padded with ``n_groups``, which
    no group counts), and each row's last key makes the level above, until
    one row's worth is left; those few keys are compared with every group.
    Going down, the keys up to ``g`` are ``row`` times the rows whose last
    key is up to ``g`` — the count the level above gave — plus those of the
    next row, ONE gathered row a group.  So the levels follow the rows
    (three at 11 M: 86 016 rows of 128, then 672 keys, then 6), each one
    gather of contiguous rows, not a loop of dependent gathers of one key
    a group."""
    row = _SEARCH_ROW if row is None else row
    groups = jnp.arange(n_groups, dtype=jnp.int32)[:, None]
    levels = []
    keys = key_s
    while keys.shape[0] > row:
        n_rows = -(-keys.shape[0] // row)
        rows = jnp.pad(
            keys, (0, n_rows * row - keys.shape[0]), constant_values=n_groups
        ).reshape(n_rows, row)
        levels.append(rows)
        keys = lax.index_in_dim(rows, row - 1, axis=1, keepdims=False)
    count = (keys[None, :] <= groups).sum(axis=1, dtype=jnp.int32)
    for rows in reversed(levels):
        # every row before it lies up to g; a count of all of them reads the
        # last row, whose every key is up to g too
        at = jnp.minimum(count, rows.shape[0] - 1)
        count = at * row + (rows[at] <= groups).sum(axis=1, dtype=jnp.int32)
    return count


def _sorted_segment_sum(values, safe, n_groups, acc_dtype=jnp.int64):
    """Per-group sums without a wide scatter: sort rows by group code,
    prefix-sum the sorted values in ``acc_dtype``, and difference the prefix
    at group boundaries.  One O(n log n) device sort + cheap elementwise
    wide adds (only the SCATTER is expensive in emulated 64-bit arithmetic),
    and no ``blocks x groups`` table, so cost is independent of ``n_groups``.
    For int64 the wrapping (mod 2^64) prefix sums difference back exactly —
    bit-exact for the full range; in a float ``acc_dtype`` the prefix-diff
    matches direct summation only to ~1 ulp of the running prefix.

    One sort, one gather of every row and one boundary search PER SUM:
    where a query's integer reductions can share a sort and carry their
    values through it they take :class:`_SortedGroups` instead.  The one
    caller left: the int64 sums of :func:`_int64_segment_sum` past the
    ``blocks x groups`` budget (a CPU backend only: an accelerator never
    reaches the blocked form under ``auto``).  No float sum takes it since
    PR 38: the difference of a running prefix of the whole table carries
    one rounding of that prefix, which a group of a few cents beside a
    table of millions cannot absorb (:func:`_segmented_sums` instead)."""
    codes_s, order = lax.sort(
        (safe, jnp.arange(safe.shape[0], dtype=jnp.int32)), num_keys=1
    )
    v_s = values[order].astype(acc_dtype)
    prefix = jnp.cumsum(v_s)
    # one past the last row of each group (== prefix index of its total)
    ends = _group_ends(codes_s, n_groups)
    zero = jnp.zeros(1, acc_dtype)
    bounds = jnp.concatenate([zero, prefix])[ends]
    return jnp.diff(jnp.concatenate([zero, bounds]))


class _SortedGroups:
    """The accelerator's form of the scatter route's exact integer counts
    and sums: ONE sort of the query's rows by folded group code with every
    integer contribution carried through it as a 32-bit sort operand, the
    group boundaries found once, then each count the difference of the
    boundaries (or of an int32 prefix of carried flags) and each sum an
    exact prefix sum read at the boundaries and differenced in wrapping 64
    bits — bit-exact mod 2^64 for the full int64 range, like the blocked
    limb scatter it stands in for.  No scatter, no ``arange`` operand, no
    gather of rows: the boundaries are :func:`_group_ends`' one gathered
    row of 128 sorted keys a group and level, and every other gather reads
    ``n_groups`` elements.

    Rows that do not count (null key, filtered out) are keyed past the last
    group, so they sort off the end where no boundary reads them and no
    operand needs a mask of its own.  Contributions are registered first
    (:meth:`count`, :meth:`total`; each returns a thunk) and the one sort
    runs when the first thunk is called, so every reduction of a query
    shares it."""

    def __init__(self, valid, codes, n_groups):
        self._key = jnp.where(valid, codes, n_groups).astype(jnp.int32)
        self._n_groups = n_groups
        self._words = []     # 32-bit operands carried through the sort
        self._sorted = None  # (ends, sorted words), once the sort has run
        self._key_s = None   # the sorted key, for the segmented float sums

    def _carry(self, word):
        if self._sorted is not None:
            raise RuntimeError("contribution registered after the sort ran")
        self._words.append(word)
        return len(self._words) - 1

    def _run(self):
        if self._sorted is None:
            key_s, *words_s = lax.sort(
                (self._key, *self._words), num_keys=1, is_stable=False
            )
            # ends[g]: one past the last sorted row of group g
            ends = _group_ends(key_s, self._n_groups)
            self._sorted = ends, words_s
            self._key_s = key_s
        return self._sorted

    def _prefix_at_ends(self, word):
        """int64[n_groups]: the exact sum of a sorted 32-bit ``word`` over
        the rows before each group's end.  Everything at row scale is 32
        bits wide: the word's 16-bit limbs are scanned inside
        ``_SUM_BLOCK``-row blocks, where an unsigned limb's prefix stays
        below 2^32 and the signed top limb's within +-2^31, and only the
        per-block totals and the values read at the boundaries meet in 64
        bits (a 64-bit scan of every row is emulated: 13.4 ms at 11 M rows
        on a v5e against 2.6 for both limbs' scans, and a compile of one
        crashed the TPU compiler — PERF.md section 6, PR 33)."""
        ends, _ = self._run()
        n = word.shape[0]
        n_blocks = -(-n // _SUM_BLOCK)
        blocks = jnp.pad(word, (0, n_blocks * _SUM_BLOCK - n)).reshape(
            n_blocks, _SUM_BLOCK
        )
        # the top limb keeps the word's sign (arithmetic shift if signed)
        limbs = (((blocks & 0xFFFF).astype(jnp.uint32), 0), (blocks >> 16, 16))
        last = jnp.maximum(ends - 1, 0)  # a group's last row, if it has one
        total = jnp.zeros(self._n_groups, jnp.int64)
        for limb, shift in limbs:
            scan = jnp.cumsum(limb, axis=1)
            whole = lax.index_in_dim(  # [n_blocks] totals
                scan, _SUM_BLOCK - 1, axis=1, keepdims=False
            ).astype(jnp.int64)
            # (log-step adds over the few blocks: no 64-bit reduce-window)
            before = lax.associative_scan(jnp.add, whole) - whole
            at = before[last // _SUM_BLOCK] + scan.reshape(-1)[last].astype(
                jnp.int64
            )
            total = total + (at << shift)
        return jnp.where(ends > 0, total, 0)

    def rows(self):
        """Thunk of the valid rows per group: no prefix sum at all."""
        def resolve():
            ends, _ = self._run()
            return jnp.diff(ends, prepend=0).astype(jnp.int64)
        return resolve

    def count(self, flags):
        """Thunk of the per-group count of valid rows whose flag is set."""
        slot = self._carry(flags.astype(jnp.int32))

        def resolve():
            ends, words = self._run()
            # n < 2^31 rows a dispatch: a flat int32 prefix cannot wrap
            prefix = jnp.cumsum(words[slot])
            at = jnp.where(ends > 0, prefix[jnp.maximum(ends - 1, 0)], 0)
            return jnp.diff(at, prepend=0).astype(jnp.int64)
        return resolve

    def total(self, values):
        """Thunk of the per-group int64 sum (mod 2^64) of integer values."""
        if values.dtype == jnp.bool_:
            values = values.astype(jnp.uint8)
        if values.dtype.itemsize < 4:
            slots = (self._carry(values.astype(jnp.int32)),)
        elif values.dtype.itemsize == 4:
            slots = (self._carry(values),)
        else:
            # a 64-bit value rides as its two 32-bit halves; the high half
            # keeps the sign (arithmetic shift; logical for unsigned)
            half = (
                jnp.int32
                if jnp.issubdtype(values.dtype, jnp.signedinteger)
                else jnp.uint32
            )
            slots = (
                self._carry(values.astype(jnp.uint32)),
                self._carry((values >> 32).astype(half)),
            )

        def resolve():
            _, words = self._run()
            at = self._prefix_at_ends(words[slots[0]])
            if len(slots) == 2:
                at = at + (self._prefix_at_ends(words[slots[1]]) << 32)
            return jnp.diff(at, prepend=0)
        return resolve

    def float_total(self, contrib):
        """Thunk of the per-group float64 sum of ``contrib`` (zero on the
        rows that do not count for it), carried through the one sort and
        summed group by group (:func:`_segmented_sums`): no second sort of
        the rows, no gather of every row, no prefix of the whole table."""
        slot = self._carry(contrib)

        def resolve():
            ends, words = self._run()
            return _segmented_sums(
                self._key_s, words[slot], ends, self._n_groups
            )
        return resolve


#: rows per block of the float64 segmented scan (:func:`_segmented_sums`):
#: ``log2`` of it is the number of passes over every row, and the blocks'
#: last rows (rows / block of them) are scanned once more.  OBSERVED:
#: standalone at 11 010 048 rows x 73 728 groups on a TPU v5e (PERF.md
#: section 6, PR 38) the sort, the boundary search and the scan together
#: take 47.4 ms a launch at 128, 52.1 at 1 024 and 56.0 at 65 536, of which
#: the three-operand sort alone is 35.5
_SEGMENT_BLOCK = 128


def _segmented_scan(keys, values):
    """The inclusive scan of ``values`` along the last axis that starts
    anew wherever the (sorted) ``keys`` change: at step ``d`` an element
    takes in the partial sum ``d`` places before it where both have one
    key — the keys are sorted, so every element between them has it too —
    and after ``log2`` of the axis' length steps each holds the sum of its
    own key's elements up to itself.  A balanced tree of adds, each one
    elementwise pass; a sum never meets a value of another key."""
    lead = [(0, 0)] * (keys.ndim - 1)
    zero = jnp.zeros((), values.dtype)
    d = 1
    while d < keys.shape[-1]:
        same = keys[..., d:] == keys[..., :-d]
        values = values + jnp.pad(
            jnp.where(same, values[..., :-d], zero), lead + [(d, 0)]
        )
        d *= 2
    return values


def _segmented_sums(key_s, v_s, ends, n_groups, block=None):
    """float[n_groups]: per-group sums of ``v_s``, rows already sorted by
    group key ``key_s`` with ``ends[g]`` one past group ``g``'s last row, in
    which a group's sum only ever meets its own values — no running prefix
    of the table to difference, whose one rounding (of up to the table's
    total) a small group's sum cannot absorb.

    :func:`_segmented_scan` inside ``block``-row blocks, so each row holds
    the sum of its group's rows from the group's first row in the block up
    to itself, with an error that grows with the log of a group's rows.  A
    group's total is the value at its last row plus, where the group
    started in an earlier block, the same scan over the blocks' last rows.
    No 64-bit ``cumsum`` or ``associative_scan`` at row scale (PERF.md
    section 7: one crashed the TPU compiler, one never returned), no
    gather but the ``n_groups`` reads at the boundaries."""
    block = _SEGMENT_BLOCK if block is None else block
    n = key_s.shape[0]
    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    # pad rows take a key no group has and add nothing
    k = jnp.pad(key_s, (0, pad), constant_values=n_groups).reshape(
        n_blocks, block
    )
    v = _segmented_scan(k, jnp.pad(v_s, (0, pad)).reshape(n_blocks, block))
    # the blocks' last rows: the part of the block's last group that lies
    # in the block, scanned the same way over the few blocks
    tail_key = k[:, -1]
    tail = _segmented_scan(tail_key, v[:, -1])
    last = jnp.maximum(ends - 1, 0)  # a group's last row, if it has one
    before = jnp.maximum(last // block - 1, 0)  # the block before its block
    groups = jnp.arange(n_groups, dtype=key_s.dtype)
    zero = jnp.zeros((), v.dtype)
    carried = jnp.where(
        (last >= block) & (tail_key[before] == groups), tail[before], zero
    )
    present = jnp.diff(ends, prepend=0) > 0
    return jnp.where(present, v.reshape(-1)[last] + carried, zero)


def _resolved(partials):
    """The partial tables with every :class:`_SortedGroups` thunk called:
    the one sort can only run once every contribution is registered, so
    the sorted reductions are values only at the end of a route."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf() if callable(leaf) else leaf, partials
    )


def _int_sums_sort(n, n_groups):
    """Whether the scatter route's integer counts and sums take a sorted
    form under ``auto``, from what the trace observes (the backend, the
    rows, the group count): an accelerator always sorts
    (:class:`_SortedGroups`); a CPU backend, whose scatter is cheap, keeps
    the blocked limb scatter up to ``_MAX_BLOCK_SEGMENTS`` buckets and
    past them :func:`_sorted_segment_sum`, as it always has.

    OBSERVED, nothing to tune: standalone on a TPU v5e (PERF.md section 6,
    PR 33) a blocked scatter costs 10.9 ns a row a limb at any group count
    (119.7 ms for ``rows`` alone at 11 010 048 rows, 357.5 with an
    int32-wide sum, 595.8 with an int64-wide one) where the sorted form
    costs 22.1, 36.9 and 53.2 ms — about 20 ms for the sort and the scans
    plus 0.235 ms per 1 000 groups for the boundary search and the reads
    (21.9 ms at 6 656 groups, 81.1 at 262 144, against 220.4 and 374.4).
    The search was most of that: the row search (:func:`_group_ends`)
    reads 1.12 ms at 73 728 groups and 0.64 at 2 304 where
    ``searchsorted`` read 1.49 at 2 304, and the launch of an int64 sum at
    11 010 048 rows x 73 728 groups takes 40.7 ms, not 53.0.  The
    sorted form still wins at 1 048 576 rows x 73 728 groups, 14 rows a group
    (14.8 against 22.2 ms); below that the two are a few ms apart and no
    reading says which is ahead."""
    if jax.default_backend() != "cpu":
        return True
    return -(-n // _SUM_BLOCK) * n_groups > _MAX_BLOCK_SEGMENTS


def _dense_segment_sum(contrib, safe, n_groups):
    """Per-group float sums with no per-row data movement — no sort, no
    gather, no scatter: for each group ``g`` the rows of a block whose code
    is ``g`` are summed (``sum(where(safe == g, contrib, 0))``), then the
    blocks are.  One fused compare-select-reduce over ``[groups, rows]``
    that XLA never materialises; every add is in ``contrib.dtype`` (float64
    for every caller: no float32 partial, no MXU limb), as a tree over
    ``_SUM_BLOCK``-row blocks, so a group's sum only ever meets its own
    values — at least as accurate as the prefix difference it replaces.
    ``contrib`` is already zero on the rows that do not count (null
    measure, null key, filtered out), where ``safe`` reads group 0.  Cost
    grows with ``n_groups``: callers bound it by ``_DENSE_SUM_GROUPS``."""
    n = contrib.shape[0]
    n_blocks = -(-n // _SUM_BLOCK)
    pad = n_blocks * _SUM_BLOCK - n
    v = jnp.pad(contrib, (0, pad)).reshape(n_blocks, _SUM_BLOCK)
    c = jnp.pad(safe, (0, pad)).reshape(n_blocks, _SUM_BLOCK)
    hit = c[None] == jnp.arange(n_groups, dtype=c.dtype)[:, None, None]
    zero = jnp.zeros((), contrib.dtype)
    return jnp.where(hit, v[None], zero).sum(axis=2).sum(axis=1)


def _float_sum_form(n_groups, force_sort=False):
    """Which reduction a float64 per-group sum takes, from what the trace
    can observe (the backend and the group count, both static):
    ``"segmented"`` under a binding sort hint and above
    ``_DENSE_SUM_GROUPS`` groups on an accelerator, ``"dense"`` up to it,
    None on a CPU backend (native float64: the plain scatter-add is the
    cheap one there).  In all three a group's sum meets only its own
    values, so a group of one row of cents is as exact beside a table of
    millions as alone."""
    if force_sort:
        return "segmented"
    if jax.default_backend() == "cpu":
        return None
    return "dense" if n_groups <= _DENSE_SUM_GROUPS else "segmented"


def _float64_segment_sum(contrib, safe, n_groups, sorted_groups,
                         force_sort=False):
    """The per-group sum of a float64 ``contrib`` for BOTH kernel routes
    (the MXU route's ``f64_scatter`` plan and the scatter route's float
    branch), so a binding hint changes the counts' route and not the sum.
    ``sorted_groups()`` gives the query's :class:`_SortedGroups` — the
    route's own where its integer reductions sort, one made for the float
    sums where they do not — and is called only by the segmented form,
    which then returns a thunk (the one sort runs once every contribution
    is registered); the other forms return the table."""
    form = _float_sum_form(n_groups, force_sort)
    if form == "dense":
        return _dense_segment_sum(contrib, safe, n_groups)
    if form == "segmented":
        # no native f64 on TPU: an emulated-f64 scatter is the wide-scatter
        # cost this module exists to avoid
        return sorted_groups().float_total(contrib)
    return jax.ops.segment_sum(contrib, safe, num_segments=n_groups)


def _int64_segment_sum(values, valid, safe, n_groups):
    """Exact per-group int64 sums of integer ``values`` without any int64
    scatter: the blocked form, which every CPU backend takes under ``auto``
    and any backend under a binding ``"scatter"`` hint (an accelerator's
    ``auto`` sorts instead: :class:`_SortedGroups`).

    Split values into 16-bit limbs, scatter each limb in int32 over
    ``blocks x groups`` buckets, recover each bucket exactly (mod-2^32 wrap
    is invertible because a block's true limb sum is < 2^32), then reduce
    the per-block tables in uint64 and recombine limbs with shifts.
    Bit-exact for the full int64 range.  Past ``_MAX_BLOCK_SEGMENTS``
    buckets (~extreme group counts) the sort-based path takes over instead
    of the emulated-s64 scatter that used to cost ~3 s at 10 M rows."""
    n = values.shape[0]
    v = jnp.where(valid, values, 0)
    nbits = values.dtype.itemsize * 8
    signed_in = jnp.issubdtype(values.dtype, jnp.signedinteger)
    n_blocks = -(-n // _SUM_BLOCK)
    if n_blocks * n_groups > _MAX_BLOCK_SEGMENTS:
        return _sorted_segment_sum(v, safe, n_groups)
    # limbs: (int32 row, shift, signed). Non-top limbs are unsigned 16-bit
    # slices; the top limb carries the sign for signed inputs.
    if nbits <= 16:
        limbs = [(v.astype(jnp.int32), 0, signed_in)]
    else:
        n_limbs = nbits // 16
        limbs = [
            (((v >> (16 * i)) & 0xFFFF).astype(jnp.int32), 16 * i, False)
            for i in range(n_limbs - 1)
        ]
        # top limb keeps the sign via arithmetic shift (logical for unsigned)
        limbs.append(
            ((v >> (16 * (n_limbs - 1))).astype(jnp.int32),
             16 * (n_limbs - 1), signed_in)
        )
    pad = n_blocks * _SUM_BLOCK - n
    safe_p = jnp.pad(safe, (0, pad))
    ids = (
        jnp.arange(n_blocks * _SUM_BLOCK, dtype=jnp.int32) // _SUM_BLOCK
    ) * n_groups + safe_p
    total = jnp.zeros(n_groups, dtype=jnp.uint64)
    for limb, shift, signed in limbs:
        part = jax.ops.segment_sum(
            jnp.pad(limb, (0, pad)), ids, num_segments=n_blocks * n_groups
        ).reshape(n_blocks, n_groups)
        if signed:
            # |block sum| <= 2^16 * 2^15 = 2^31: no wrap, sign-extend
            bs = part.astype(jnp.int64).astype(jnp.uint64).sum(0)
        else:
            # true block sum < 2^16 * 2^16 = 2^32: the int32 wrap is exactly
            # the uint32 value
            bs = (
                lax.bitcast_convert_type(part, jnp.uint32)
                .astype(jnp.uint64)
                .sum(0)
            )
        total = total + (bs << jnp.uint64(shift))
    return total.astype(jnp.int64)


#: rows per MXU block: 8-bit limb block sums stay <= 32768 * 255 < 2^24, so
#: the MXU's float32 accumulation of a block is exact
_MATMUL_BLOCK = 32768


def program_bucket(n, fine=False):
    """Round a program-shape dimension UP onto a coarse grid so XLA programs
    are reused across data refreshes and cardinality drift.

    Static shapes are the TPU contract: every exact (rows, groups) pair is
    its own compile (seconds per program, minutes for the float64 sort
    path) — while real serving data drifts a few percent per refresh.
    Grid: pow2/64 steps for row counts (``fine=True``, <=~3.2% padding) and
    pow2/16 for group counts (<=~12.5%, typically ~5%).  Padded groups get
    zero rows and are sliced off by callers after fetch; padded rows carry
    code -1 and vanish from every reduction.  BQUERYD_TPU_SHAPE_BUCKETS=0
    disables (exact shapes, maximum compiles)."""
    n = int(n)
    if n <= 16 or os.environ.get("BQUERYD_TPU_SHAPE_BUCKETS", "1") == "0":
        return max(n, 0)
    step = 1 << max((n - 1).bit_length() - (6 if fine else 4), 0)
    return -(-n // step) * step


def matmul_groups_limit():
    """Above this group cardinality the one-hot matmul's N*G FLOPs cost more
    than the scatter it replaces (crossover ~8-16k groups at 10 M rows on
    v5e); tune with BQUERYD_TPU_MATMUL_GROUPS (0 disables the MXU path)."""
    return int(os.environ.get("BQUERYD_TPU_MATMUL_GROUPS", 8192))


def _matmul_cells_limit():
    """Cap on rows * groups for the MXU path: bounds the one-hot contraction's
    FLOPs (and its worst-case materialized size, should an XLA version decline
    to fuse the one-hot into the dot).  Default ~6.9e10 cells = the measured
    10 M-row x 8k-group crossover on v5e."""
    return int(os.environ.get("BQUERYD_TPU_MATMUL_CELLS", 1 << 36))


def matmul_route_allowed(n, n_groups):
    """The MXU route's SAFETY guards: backend (the one-hot contraction
    emulates ~7x slower than the int32 scatter on CPU backends —
    BQUERYD_TPU_FORCE_MATMUL=1 overrides, pinned by the test suite for
    MXU-path coverage on the CPU test backend), group ceiling, and the
    rows x groups cells budget."""
    if (
        jax.default_backend() == "cpu"
        and os.environ.get("BQUERYD_TPU_FORCE_MATMUL") != "1"
    ):
        return False
    if not (0 < n_groups <= matmul_groups_limit()):
        return False
    if n * n_groups > _matmul_cells_limit():
        return False
    return True


def _matmul_profitable(measures, ops, n, n_groups):
    """MXU path only when within budget AND some sum/count rides the matmul
    beside ``rows``: a query made only of min/max gains too little from
    building the one-hot (its extrema scatter regardless).  A float64 sum
    does not ride the dot either, but it does not decline the route: its
    ``rows`` — and a mean's count — are rows of the stacked dot, where the
    scatter route pays a blocked int32 scatter of every row for each
    (97 ms each at 11 M rows on a v5e, at any group count, against 2-51 ms
    for the two-row dot from 10 groups to the top of the allowed range:
    PERF.md section 6, PR 31), and the sum itself is
    :func:`_float64_segment_sum` on either route."""
    if not matmul_route_allowed(n, n_groups):
        return False
    if not measures:
        return True  # rows-count-only query still benefits
    return any(op in ("count", "count_na", "sum", "mean") for op in ops)


def _hicard_matmul_profitable(measures, ops, n, n_groups):
    """Whether the group-tiled Pallas MXU path should take a query past
    ``matmul_groups_limit``.  Opt-in (BQUERYD_TPU_PALLAS=1) until proven on
    hardware; INT sums/counts only — the kernel's in-kernel mod-2^32 limb
    accumulation has no wrap-free encoding for float Dekker limbs, and
    min/max ride dedicated scatter kernels regardless.  What it has to
    beat at 11 M rows x 73 728 groups on a v5e is the sorted form's 37 ms
    (the blocked scatter took 357 standalone; PERF.md section 6, PR 33);
    its one standalone reading there was 0.456 s (CHANGES.md, PR 21),
    though the one-hot contraction is ~1.4e12 bf16 MACs, tens of ms at
    realistic MXU utilization."""
    from bqueryd_tpu.ops import pallas_groupby

    if not pallas_groupby.pallas_enabled():
        return False
    if (
        jax.default_backend() == "cpu"
        and os.environ.get("BQUERYD_TPU_FORCE_MATMUL") != "1"
    ):
        return False  # same CPU-emulation economics as _matmul_profitable
    if not (
        matmul_groups_limit()
        < n_groups
        <= pallas_groupby.hicard_groups_limit()
    ):
        return False
    if n > pallas_groupby.HICARD_MAX_ROWS:
        return False
    if not measures:
        return True  # rows-count-only query
    for values, op in zip(measures, ops):
        if op in ("count", "count_na"):
            continue
        if op not in ("sum", "mean"):
            return False  # min/max scatter anyway: no matmul rows to win
        dt = jnp.dtype(values.dtype)
        if jnp.issubdtype(dt, jnp.floating):
            return False
    # the stacked row count must fit the kernel's VMEM plan: one count row,
    # per-measure present rows (worst case), 8 limbs per 64-bit measure
    est_rows = 1 + sum(
        1 + (jnp.dtype(v.dtype).itemsize if v.dtype != jnp.bool_ else 1)
        for v in measures
    )
    return pallas_groupby.hicard_fits_vmem(est_rows)


def partial_tables(codes, measures, ops, n_groups, mask=None,
                   null_sentinels=None, strategy=None):
    """Compute per-group partial tables for one shard.

    codes:    int[n] dense group codes in [0, n_groups); negative = null key
              (row dropped, matching pandas groupby's NaN-key behaviour)
    measures: tuple of value arrays [n], one per aggregation
    ops:      static tuple of op names aligned with measures (MERGEABLE_OPS)
    mask:     optional bool[n] row filter (where_terms pushdown)
    null_sentinels: optional tuple aligned with measures; an int entry marks
              that integer value as the measure's missing-data encoding
              (datetime NaT = int64 min) so those rows skip counts/extrema
              the way float NaNs do.  sum/mean measures may not carry a
              sentinel (the engine rejects datetime sums as pandas-meaningless
              before reaching the kernels).

    Returns a pytree: {"rows": int64[n_groups],
                       "aggs": tuple of per-measure partial dicts}.

    Sums and counts route to the MXU one-hot matmul (module docstring) when
    the cardinality is within :func:`matmul_groups_limit`; min/max-only and
    high-cardinality queries use segment scatters.  A float64 sum or mean
    takes the MXU route for its counts too (``auto`` and ``"matmul"`` are
    then one traced program) and sums by
    :func:`_float64_segment_sum` on whichever route it goes.

    ``strategy`` (:data:`KERNEL_STRATEGIES`) forces one route for a test:
    ``"scatter"`` goes straight to the blocked scatters, ``"sort"`` to the
    scatter entry with the sorted reductions forced (the form an
    accelerator's ``auto`` takes there: :func:`_int_sums_sort`), and
    ``"matmul"``/``"auto"``/None keep the full profitability logic, the ONE
    rule that routes every served query (:func:`kernel_route` is its
    host-side twin) — ``"matmul"`` never overrides the backend guard (a CPU
    backend still declines).
    """
    ops = tuple(ops)
    measures = tuple(measures)
    null_sentinels = _normalize_sentinels(null_sentinels, len(measures))
    for sentinel, op in zip(null_sentinels, ops):
        if sentinel is not None and op in ("sum", "mean"):
            # the MXU limb path contracts raw rows (exclusion rides the
            # one-hot of the SHARED codes, so per-measure nulls can't be
            # expressed there) and a sentinel sum is semantically undefined
            # anyway — the engine raises long before this
            raise ValueError(
                f"op {op!r} cannot aggregate a sentinel-null measure"
            )
    if strategy is not None and strategy not in KERNEL_STRATEGIES:
        raise ValueError(f"unknown kernel strategy {strategy!r}")
    if strategy == "scatter":
        return _partial_tables_scatter(
            codes, measures, ops, int(n_groups), mask,
            null_sentinels=null_sentinels, force_sort=False,
        )
    if strategy == "sort":
        return _partial_tables_scatter(
            codes, measures, ops, int(n_groups), mask,
            null_sentinels=null_sentinels, force_sort=True,
        )
    if _matmul_profitable(measures, ops, int(codes.shape[0]), int(n_groups)):
        # env flags are read HERE, outside jit, so toggling them takes effect
        # per call instead of being frozen into the first trace
        from bqueryd_tpu.ops import pallas_groupby

        return _partial_tables_mm(
            codes, measures, ops, int(n_groups), mask,
            # the Pallas kernel has its own (VMEM-bound) cardinality ceiling:
            # a raised BQUERYD_TPU_MATMUL_GROUPS must not push it past the
            # group count where its smallest one-hot tile still fits
            use_pallas=pallas_groupby.pallas_enabled()
            and int(n_groups) <= pallas_groupby.pallas_groups_limit(),
            null_sentinels=null_sentinels,
        )
    if _hicard_matmul_profitable(
        measures, ops, int(codes.shape[0]), int(n_groups)
    ):
        return _partial_tables_mm(
            codes, measures, ops, int(n_groups), mask,
            use_pallas="hicard",
            null_sentinels=null_sentinels,
        )
    return _partial_tables_scatter(
        codes, measures, ops, int(n_groups), mask,
        null_sentinels=null_sentinels,
    )


def bucketize_partials(partials, n_groups, n_buckets):
    """Re-emit a partial-table pytree on the key-span bucket layout: every
    leaf's group axis is padded from ``n_groups`` to ``span * n_buckets``
    (``span = ceil(n_groups / n_buckets)``) so bucket ``d`` — device ``d``
    of the merge mesh — owns the contiguous span ``[d*span, (d+1)*span)``.

    Returns ``(padded_partials, span)``.  Pad entries are zeros; they are
    appended PAST every real group, so sums/counts gain nothing and min/max
    pads can never shadow a real group — the collector slices the pad tail
    off after the fetch.  Trace-safe (``jnp.pad`` only), and called on the
    OUTPUT of :func:`partial_tables`, so every kernel guard (matmul
    backend/ceiling, scatter budgets, strategy hints) applies unchanged to
    the bucketized emission."""
    from bqueryd_tpu.parallel.devicemerge import bucket_span

    span, padded = bucket_span(n_groups, n_buckets)
    pad = padded - int(n_groups)
    if pad == 0:
        return partials, span
    out = jax.tree_util.tree_map(
        lambda leaf: jnp.pad(leaf, (0, pad)), partials
    )
    return out, span


def bundle_partial_tables(codes, masks, measures, member_specs, n_groups,
                          null_sentinels=None, strategy=None):
    """Stacked-mask shared-scan emission: per-member partial tables over ONE
    codes array and ONE set of deduplicated measure blocks.

    codes:        int[n] dense group codes, shared by every member (uploaded
                  once, unmasked — each member's filter applies per member)
    masks:        bool[n_masks, n] stacked row filters, one row per member
                  that carries a filter (members without one index None)
    measures:     tuple of value arrays [n], one per DISTINCT measure column
                  across the whole bundle (the union upload)
    member_specs: static tuple, one entry per member:
                  ``(mask_idx_or_None, ((measure_slot, op), ...))`` — which
                  stacked mask row (None = unfiltered) and which
                  (deduplicated measure block, op) pairs this member
                  aggregates
    null_sentinels: optional tuple aligned with ``measures`` (per distinct
                  column, same semantics as :func:`partial_tables`)

    Returns a tuple of per-member partial-table pytrees, each shaped exactly
    like :func:`partial_tables` produces for that member alone.

    On CPU backends this is the shared-scan KERNEL, not just a member
    loop: every (measure slot, op) family shared across members runs as
    ONE batched segment reduction over the ``[members, rows]`` stack of
    masked contributions — the scan/build work that dominates GROUP BY
    cost is paid once per bundle, not once per member (measured 4x+ over
    the member-at-a-time loop at bench shapes).  On accelerator backends
    the batched form would be exactly the emulated wide scatter
    (s64/f64 ``segment_sum``) that :func:`_int64_segment_sum` and
    :func:`_sorted_segment_sum` exist to avoid, so each member runs its
    own :func:`partial_tables` dispatch there instead — full guards, limb
    paths and MXU routes intact; the bundle still shares every host-side
    pass (decode/align/H2D/program dispatch), just not the reduction.
    Backend is read at trace time, like the solo kernels' own backend
    branches.  Exactness contract vs solo execution: integer partials are
    bit-identical (integer segment sums are order-exact under any
    reduction), float partials accumulate in the same widened dtype
    (:func:`_accum_dtype`) and differ from a member's solo route at most
    by reassociation — the same tolerance class as the matmul-vs-scatter
    route choice."""
    measures = tuple(measures)
    sentinels = _normalize_sentinels(null_sentinels, len(measures))
    for _mask_idx, aggs in member_specs:
        for slot, op in aggs:
            if op not in MERGEABLE_OPS and op != "count_na":
                raise ValueError(
                    f"op {op!r} has no mergeable partial; bundles carry "
                    "mergeable aggregations only"
                )
            if sentinels[slot] is not None and op in ("sum", "mean"):
                raise ValueError(
                    f"op {op!r} cannot aggregate a sentinel-null measure"
                )

    if jax.default_backend() != "cpu":
        return tuple(
            partial_tables(
                codes,
                tuple(measures[slot] for slot, _op in aggs),
                tuple(op for _slot, op in aggs),
                n_groups,
                mask=None if mask_idx is None else masks[mask_idx],
                null_sentinels=tuple(
                    sentinels[slot] for slot, _op in aggs
                ),
                strategy=strategy,
            )
            for mask_idx, aggs in member_specs
        )

    key_valid = codes >= 0
    safe = jnp.where(key_valid, codes, 0).astype(jnp.int32)
    n_groups = int(n_groups)

    # per-member validity stack (the shared scan's one mask fold)
    valids = tuple(
        key_valid if mask_idx is None else key_valid & masks[mask_idx]
        for mask_idx, _aggs in member_specs
    )

    def batched_count(flags_2d):
        """bool[k, n] -> int64[k, n_groups] in ONE segment pass.  Counts
        accumulate in int32 (a per-dispatch block holds < 2^31 rows) and
        widen to the partials' int64 contract after."""
        return jax.ops.segment_sum(
            flags_2d.T.astype(jnp.int32), safe, num_segments=n_groups
        ).T.astype(jnp.int64)

    rows_all = batched_count(jnp.stack(valids))  # [n_q, n_groups]

    nulls = {
        slot: _measure_null(measures[slot], sentinels[slot])
        for slot in {s for _m, aggs in member_specs for s, _o in aggs}
    }

    # job plan: one batched reduction per (measure slot, op) family across
    # every member that needs it
    jobs = {}
    for qi, (_mask_idx, aggs) in enumerate(member_specs):
        for ai, (slot, op) in enumerate(aggs):
            jobs.setdefault((slot, op), []).append((qi, ai))

    results = [
        [None] * len(aggs) for _mask_idx, aggs in member_specs
    ]
    for (slot, op), takers in jobs.items():
        values = measures[slot]
        null = nulls[slot]
        present = tuple(
            valids[qi] if null is None else valids[qi] & ~null
            for qi, _ai in takers
        )
        stacked = jnp.stack(present)  # [k, n]

        def taker_counts():
            if null is None:
                return tuple(rows_all[qi] for qi, _ai in takers)
            counted = batched_count(stacked)
            return tuple(counted[j] for j in range(len(takers)))

        if op in ("sum", "mean"):
            floating = jnp.issubdtype(values.dtype, jnp.floating)
            if floating or op == "mean":
                # integer means accumulate in float like pandas (and the
                # solo kernels) — see _partial_tables_scatter
                acc = _accum_dtype(
                    values.dtype if floating else jnp.float64
                )
            else:
                acc = jnp.int64
            contrib = jnp.where(stacked, values[None, :], 0).astype(acc)
            sums = jax.ops.segment_sum(
                contrib.T, safe, num_segments=n_groups
            ).T
            counts = taker_counts() if op == "mean" else None
            for j, (qi, ai) in enumerate(takers):
                part = {"sum": sums[j]}
                if op == "mean":
                    part["count"] = counts[j]
                results[qi][ai] = part
        elif op == "count":
            counts = taker_counts()
            for j, (qi, ai) in enumerate(takers):
                results[qi][ai] = {"count": counts[j]}
        elif op == "count_na":
            if null is None:
                zero = jnp.zeros(n_groups, dtype=jnp.int64)
                for qi, ai in takers:
                    results[qi][ai] = {"count": zero}
            else:
                na = batched_count(
                    jnp.stack(tuple(valids[qi] & null for qi, _ai in takers))
                )
                for j, (qi, ai) in enumerate(takers):
                    results[qi][ai] = {"count": na[j]}
        else:  # min / max
            src = values
            as_bool = src.dtype == jnp.bool_
            if as_bool:
                src = src.astype(jnp.uint8)  # bool has no iinfo
            fill = np.dtype(src.dtype).type(extremum_fill(src.dtype, op))
            data = jnp.where(stacked, src[None, :], fill)
            seg = jax.ops.segment_min if op == "min" else jax.ops.segment_max
            ext = seg(data.T, safe, num_segments=n_groups).T
            if as_bool:
                ext = ext.astype(jnp.bool_)
            counts = taker_counts()
            for j, (qi, ai) in enumerate(takers):
                results[qi][ai] = {op: ext[j], "count": counts[j]}

    return tuple(
        {"rows": rows_all[qi], "aggs": tuple(results[qi])}
        for qi in range(len(member_specs))
    )


def partial_tables_bucketized(codes, measures, ops, n_groups, n_buckets,
                              mask=None, null_sentinels=None, strategy=None):
    """:func:`partial_tables` with the output re-laid onto the
    ``n_buckets``-way key-span bucket layout (see
    :func:`bucketize_partials`) — the emission form the device-resident
    distributed merge consumes.  Same guards, same strategies, same
    partial semantics; only the group-axis padding differs."""
    partials = partial_tables(
        codes, measures, ops, n_groups, mask=mask,
        null_sentinels=null_sentinels, strategy=strategy,
    )
    return bucketize_partials(partials, n_groups, n_buckets)


def kernel_route(strategy, measures, ops, n, n_groups):
    """Predict the physical route :func:`partial_tables` takes for this
    dispatch WITHOUT running it — the ``effective_strategy`` reported in
    calc replies / kernel trace spans.  Mirrors the dispatch above;
    ``measures`` only needs ``.dtype`` per entry (device arrays, numpy
    arrays, and dtype stubs all work).  Granularity note: the rare
    in-kernel demotions (a hicard Pallas plan that fails its VMEM recheck
    at trace time) are not modelled — those differ per XLA trace, not per
    dispatch."""
    n, n_groups = int(n), int(n_groups)
    if strategy == "scatter":
        return "scatter"
    if strategy == "sort":
        return "sort"
    if _matmul_profitable(measures, tuple(ops), n, n_groups):
        return "matmul"
    if _hicard_matmul_profitable(measures, tuple(ops), n, n_groups):
        return "matmul"
    return "sort" if _int_sums_sort(n, n_groups) else "scatter"


def float_sum_route(strategy, measures, ops, n, n_groups):
    """Which form the float64-accumulated sums of this dispatch take —
    ``"dense"`` or ``"segmented"`` (:func:`_float_sum_form`) — or None where it
    has none or the backend scatter-adds float64 natively.  The host-side
    twin of the kernels' trace-time choice, like :func:`kernel_route` (same
    arguments): the ``float_sum`` tag of the ``aggregate_wait`` detail span
    and the label of ``bqueryd_tpu_float_sum_total``."""
    route = kernel_route(strategy, measures, ops, n, n_groups)
    wide = False
    for values, op in zip(measures, ops):
        if op not in ("sum", "mean"):
            continue
        dt = jnp.dtype(values.dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            wide = wide or op == "mean"  # integer means float like pandas
        elif route != "matmul" or dt == jnp.float64:
            wide = True  # a float32 sum is bf16 limbs on the MXU route only
    if not (wide and jax.config.jax_enable_x64):
        return None
    return _float_sum_form(int(n_groups), force_sort=strategy == "sort")


def _segment_extremum(kind, values, present, safe, n_groups):
    """Per-group min/max via segment scatter; absent rows carry the identity
    fill so they never win (empty groups are masked later by count==0)."""
    if values.dtype == jnp.bool_:
        # bool has no iinfo; reduce as uint8 and view back
        ext = _segment_extremum(
            kind, values.astype(jnp.uint8), present, safe, n_groups
        )
        return ext.astype(jnp.bool_)
    # typed scalar, not a python int: uint64's max overflows the weak int64
    # a bare literal would trace as
    fill = np.dtype(values.dtype).type(extremum_fill(values.dtype, kind))
    seg = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
    return seg(
        jnp.where(present, values, fill), safe, num_segments=n_groups
    )


def _blocked(arr, nb, pad, fill=0):
    """Pad a row vector to ``nb * _MATMUL_BLOCK`` and shape it ``[nb, K]``."""
    return jnp.pad(arr, (0, pad), constant_values=fill).reshape(
        nb, _MATMUL_BLOCK
    )


def _limb_rows(values, nbits):
    """8-bit unsigned limbs of biased values, each as an exact bfloat16 row.

    Signed inputs are biased by ``2^(nbits-1)`` into unsigned range; the
    wrap-around of the uint64 cast is harmless because only the low
    ``nbits/8`` limbs are extracted (arithmetic mod 2^nbits), and the bias is
    subtracted again group-wise (``count * bias``) after the merge."""
    signed = jnp.issubdtype(values.dtype, jnp.signedinteger)
    u = values.astype(jnp.uint64)
    bias = 0
    if signed:
        bias = int(1) << (nbits - 1)
        u = u + jnp.uint64(bias)
    rows = [
        (
            (lax.shift_right_logical(u, jnp.uint64(8 * i)) & jnp.uint64(0xFF))
            .astype(jnp.bfloat16)
        )
        for i in range(nbits // 8)
    ]
    return rows, bias


@functools.partial(
    jax.jit,
    static_argnames=("n_groups", "ops", "use_pallas", "null_sentinels"),
)
def _partial_tables_mm(codes, measures, ops, n_groups, mask=None,
                       use_pallas=False, null_sentinels=None):
    """MXU path: one ``dot_general`` of stacked bf16 rows (a ones row for
    counts, byte limbs for int sums, a 3-limb bf16 split for float32 sums)
    against the blocked one-hot of the folded codes."""
    valid = codes >= 0
    if mask is not None:
        valid = valid & mask
    n = codes.shape[0]
    nb = -(-n // _MATMUL_BLOCK)
    pad = nb * _MATMUL_BLOCK - n

    folded = jnp.where(valid, codes, -1).astype(jnp.int32)
    c_blk = _blocked(folded, nb, pad, fill=-1)

    rows = []          # flat [n] bf16 rows, blocked right before the dot
    int_rows = []      # indices reduced exactly in uint64
    float_rows = []    # indices reduced in float64

    def add_int(row):
        rows.append(row)
        int_rows.append(len(rows) - 1)
        return len(rows) - 1

    def add_float(row):
        rows.append(row)
        float_rows.append(len(rows) - 1)
        return len(rows) - 1

    valid_count_row = add_int(valid.astype(jnp.bfloat16))

    sentinels = _normalize_sentinels(null_sentinels, len(measures))
    # per-measure row plans, resolved after the single dot below
    plans = []
    for values, op, sentinel in zip(measures, ops, sentinels):
        if op not in MERGEABLE_OPS:
            raise ValueError(
                f"op {op!r} has no mergeable partial; use the dedicated kernel"
            )
        is_float = jnp.issubdtype(values.dtype, jnp.floating)
        null = _measure_null(values, sentinel)
        if null is None:
            present_row = valid_count_row
        elif op == "count_na":
            # consumes only the null row below — a presence row would be a
            # wasted [n] bf16 contraction row in the stacked dot
            present_row = None
        else:
            present_row = add_int((valid & ~null).astype(jnp.bfloat16))
        if op in ("sum", "mean"):
            if not is_float and op == "mean":
                # pandas float-mean semantics (see the scatter path)
                plans.append(
                    ("f64_scatter", op, values.astype(jnp.float64),
                     present_row)
                )
            elif not is_float:
                v = values
                if v.dtype == jnp.bool_:
                    v = v.astype(jnp.uint8)
                nbits = v.dtype.itemsize * 8
                limbs, bias = _limb_rows(v, nbits)
                idxs = [add_int(r) for r in limbs]
                plans.append(("int_sum", op, idxs, bias, present_row))
            elif values.dtype == jnp.float64 and jax.config.jax_enable_x64:
                plans.append(("f64_scatter", op, values, present_row))
            else:
                v = values.astype(jnp.float32)
                v = jnp.where(valid & ~_null_mask(v), v, 0.0)
                # 3-limb Dekker split: each bf16 limb captures >=8 mantissa
                # bits and each residual is exact in f32, so hi+mid+lo
                # reconstructs all 24 f32 mantissa bits — the measure's
                # REPRESENTATION on the MXU path is lossless and the only
                # error left is the accumulation rounding any f32 sum has.
                # The rounding MUST be lax.reduce_precision, not an
                # f32->bf16->f32 astype round-trip: on TPU the XLA
                # excess-precision pass elides the round-trip, which turns
                # r1 into v - v == 0 and silently drops the mid/lo limbs
                # (~0.9% relative error, caught on hardware by
                # tpu_validate.py; reduce_precision is contractually never
                # folded away).
                hi_f = lax.reduce_precision(v, exponent_bits=8,
                                            mantissa_bits=7)
                r1 = v - hi_f
                mid_f = lax.reduce_precision(r1, exponent_bits=8,
                                             mantissa_bits=7)
                r2 = r1 - mid_f
                hi = hi_f.astype(jnp.bfloat16)
                mid = mid_f.astype(jnp.bfloat16)
                lo = lax.reduce_precision(
                    r2, exponent_bits=8, mantissa_bits=7
                ).astype(jnp.bfloat16)
                plans.append(
                    ("float_sum", op, add_float(hi), add_float(mid),
                     add_float(lo), present_row)
                )
        elif op == "count":
            plans.append(("count", op, present_row))
        elif op == "count_na":
            if null is not None:
                null_row = add_int(
                    (valid & null).astype(jnp.bfloat16)
                )
                plans.append(("count", op, null_row))
            else:  # plain integers can't be null: no matmul row needed
                plans.append(("zero_count", op))
        elif op in ("min", "max"):
            plans.append((op, op, values, present_row, null))

    # resolve the contraction route now that the stacked row count is
    # known (all static python: len(rows) and n_groups are trace-time
    # constants).  The dispatcher's gates only knew n_groups.
    route = {False: "xla", True: "pallas", "hicard": "hicard"}[use_pallas]
    if route == "hicard":
        from bqueryd_tpu.ops import pallas_groupby

        # past the VMEM plan the scatter path must take over (NOT the XLA
        # dot below, whose [nb, K, G] one-hot materializes gigabytes at
        # this cardinality)
        if not (
            pallas_groupby.hicard_fits_vmem(len(rows))
            and not float_rows
        ):
            return _partial_tables_scatter(
                codes, measures, ops, n_groups, mask,
                null_sentinels=null_sentinels,
            )
    elif route == "pallas":
        from bqueryd_tpu.ops import pallas_groupby

        # demote to the XLA dot when the full working set (rows x groups
        # scratch + lhs blocks) would overflow VMEM
        if not pallas_groupby.fits_vmem(len(rows), n_groups):
            route = "xla"

    if route == "hicard":
        # group-tiled fused kernel: [R, G] uint32 limb totals mod 2^32,
        # zero-extended so the downstream uint64 recombination is unchanged
        # (the sum over the singleton block axis is a no-op)
        out = pallas_groupby.onehot_rows_dot_hicard(
            folded,
            jnp.stack(rows, axis=0),
            n_rows=len(rows),
            n_groups=n_groups,
            interpret=jax.default_backend() != "tpu",
        )[None, : len(rows), :n_groups]
    elif route == "pallas":
        # fused VMEM kernel: one-hot tiles formed on the fly, never in HBM
        out = pallas_groupby.onehot_rows_dot(
            folded,
            jnp.stack(rows, axis=0),
            n_rows=len(rows),
            n_groups=n_groups,
            interpret=jax.default_backend() != "tpu",
        )[:, : len(rows), :n_groups]
    else:
        lhs = jnp.stack(
            [_blocked(r, nb, pad) for r in rows], axis=1
        )  # [nb,R,K]
        one_hot = (
            c_blk[:, :, None]
            == jnp.arange(n_groups, dtype=jnp.int32)[None, None, :]
        ).astype(jnp.bfloat16)
        out = lax.dot_general(
            lhs,
            one_hot,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [nb, R, G]

    int_idx = jnp.asarray(int_rows, dtype=jnp.int32)
    tot_u = jnp.take(out, int_idx, axis=1).astype(jnp.uint64).sum(axis=0)
    u_pos = {ridx: i for i, ridx in enumerate(int_rows)}
    if float_rows:
        f_idx = jnp.asarray(float_rows, dtype=jnp.int32)
        f_dt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        tot_f = jnp.take(out, f_idx, axis=1).astype(f_dt).sum(axis=0)
        f_pos = {ridx: i for i, ridx in enumerate(float_rows)}

    def int_row(ridx):
        return tot_u[u_pos[ridx]]

    rows_count = int_row(valid_count_row).astype(jnp.int64)
    safe = jnp.where(valid, codes, 0).astype(jnp.int32)

    @functools.cache
    def sorted_groups():
        # the counts are rows of the dot: only a float64 sum above
        # _DENSE_SUM_GROUPS groups sorts here, all such sums of a query in
        # one sort
        return _SortedGroups(valid, codes, n_groups)

    aggs = []
    for plan in plans:
        kind, op = plan[0], plan[1]
        if kind == "int_sum":
            _, _, idxs, bias, present_row = plan
            s = jnp.zeros(n_groups, dtype=jnp.uint64)
            for j, ridx in enumerate(idxs):
                s = s + (int_row(ridx) << jnp.uint64(8 * j))
            count = int_row(present_row)
            if bias:
                s = s - count * jnp.uint64(bias)
            partial = {"sum": s.astype(jnp.int64)}
            if op == "mean":
                partial["count"] = count.astype(jnp.int64)
            aggs.append(partial)
        elif kind == "float_sum":
            _, _, hi_idx, mid_idx, lo_idx, present_row = plan
            # add smallest-magnitude limbs first for accuracy
            partial = {
                "sum": (tot_f[f_pos[lo_idx]] + tot_f[f_pos[mid_idx]])
                + tot_f[f_pos[hi_idx]]
            }
            if op == "mean":
                partial["count"] = int_row(present_row).astype(jnp.int64)
            aggs.append(partial)
        elif kind == "f64_scatter":
            _, _, values, present_row = plan
            present = valid & ~_null_mask(values)
            contrib = jnp.where(present, values, 0).astype(jnp.float64)
            partial = {
                "sum": _float64_segment_sum(
                    contrib, safe, n_groups, sorted_groups
                )
            }
            if op == "mean":
                partial["count"] = int_row(present_row).astype(jnp.int64)
            aggs.append(partial)
        elif kind == "count":
            _, _, ridx = plan
            aggs.append({"count": int_row(ridx).astype(jnp.int64)})
        elif kind == "zero_count":
            aggs.append({"count": jnp.zeros(n_groups, dtype=jnp.int64)})
        elif kind in ("min", "max"):
            _, _, values, present_row, null = plan
            present = valid if null is None else valid & ~null
            ext = _segment_extremum(kind, values, present, safe, n_groups)
            aggs.append(
                {kind: ext, "count": int_row(present_row).astype(jnp.int64)}
            )
    return _resolved({"rows": rows_count, "aggs": tuple(aggs)})


_partial_tables_mm = _obsprofile.instrument(
    "ops.partial_tables_mm", _partial_tables_mm
)


@functools.partial(
    jax.jit,
    static_argnames=("n_groups", "ops", "null_sentinels", "force_sort"),
)
def _partial_tables_scatter(codes, measures, ops, n_groups, mask=None,
                            null_sentinels=None, force_sort=None):
    """Scatter path: exact integer counts and sums with no s64 scatter, in
    the form the backend does cheapest — one carried-payload sort and
    prefix differences (:class:`_SortedGroups`) on an accelerator, blocked
    int32 limb scatters (:func:`_int64_segment_sum`) on a CPU backend;
    identical partials either way.  ``force_sort`` is None for every served
    query (the form follows the backend, read at trace time), True under
    the binding "sort" hint (the sorted form on any backend, float64 sums
    segmented over the same sort) and False under the binding "scatter"
    hint (the blocked scatters on any backend)."""
    valid = codes >= 0
    if mask is not None:
        valid = valid & mask
    safe = jnp.where(valid, codes, 0).astype(jnp.int32)

    seg_sum = functools.partial(
        jax.ops.segment_sum, segment_ids=safe, num_segments=n_groups
    )

    # (the accelerator reading is the first line of _int_sums_sort; its
    # second, a CPU backend past the budget, is _int64_segment_sum's own)
    sort_ints = (
        jax.default_backend() != "cpu" if force_sort is None else force_sort
    )
    sort_floats = force_sort is True
    # the integer reductions below are values in the blocked form and
    # thunks in the sorted one, where the one sort can only run once every
    # contribution is known: the tables are resolved at the end
    if sort_ints:
        by_sort = _SortedGroups(valid, codes, n_groups)
        rows = by_sort.rows()
        int_sum = by_sort.total
        # a flag's rows need not be valid: an invalid row sorts off the end
        int_count = by_sort.count
    else:
        def int_count(flags):  # bool[n] -> int64[n_groups], no s64 scatter
            return _int64_segment_sum(
                flags.astype(jnp.int8), flags, safe, n_groups
            )

        def int_sum(values):
            return _int64_segment_sum(values, valid, safe, n_groups)

        rows = int_count(valid)

    @functools.cache
    def sorted_groups():
        # a segmented float64 sum rides the integer reductions' one sort;
        # where they scatter (a binding "scatter" hint on an accelerator)
        # the float sums share a sort of their own
        return by_sort if sort_ints else _SortedGroups(valid, codes, n_groups)

    sentinels = _normalize_sentinels(null_sentinels, len(measures))
    aggs = []
    for values, op, sentinel in zip(measures, ops, sentinels):
        if op not in MERGEABLE_OPS:
            raise ValueError(
                f"op {op!r} has no mergeable partial; use the dedicated kernel"
            )
        floating = jnp.issubdtype(values.dtype, jnp.floating)
        # plain integer measures can't be null, so their presence IS
        # key-validity: reuse the rows scatter instead of re-scanning 10M
        # rows per count; sentinel measures (datetime NaT) null like floats
        null = _measure_null(values, sentinel)
        present = valid if null is None else valid & ~null

        def present_count():
            return rows if null is None else int_count(present)

        if op in ("sum", "mean"):
            if floating or op == "mean":
                # integer MEANS also accumulate in float like pandas: an
                # exact mod-2^64 int sum divided by count diverges once the
                # group sum wraps past 2^63, which float accumulation never
                # does (sum stays bit-exact int — only mean floats)
                acc = _accum_dtype(
                    values.dtype if floating else jnp.float64
                )
                contrib = jnp.where(present, values, 0).astype(acc)
                # the forms of _float64_segment_sum are for the float64
                # accumulator (the x64 default here); a float32 accumulator
                # (x64 off) stays on the plain scatter-add whatever the hint
                if contrib.dtype == jnp.float64:
                    # backend and group count read at trace time, outside
                    # data flow
                    partial = {
                        "sum": _float64_segment_sum(
                            contrib, safe, n_groups, sorted_groups,
                            force_sort=sort_floats,
                        )
                    }
                else:
                    partial = {"sum": seg_sum(contrib)}
            else:
                # (no null without a sentinel, and a sentinel never sums)
                partial = {"sum": int_sum(values)}
            if op == "mean":
                partial["count"] = present_count()
            aggs.append(partial)
        elif op == "count":
            aggs.append({"count": present_count()})
        elif op == "count_na":
            na = (
                int_count(valid & null)
                if null is not None
                else jnp.zeros(n_groups, dtype=jnp.int64)
            )
            aggs.append({"count": na})
        elif op in ("min", "max"):
            aggs.append(
                {
                    op: _segment_extremum(op, values, present, safe, n_groups),
                    "count": present_count(),
                }
            )
    return _resolved({"rows": rows, "aggs": tuple(aggs)})


_partial_tables_scatter = _obsprofile.instrument(
    "ops.partial_tables_scatter", _partial_tables_scatter
)


def host_partial_tables(codes, measures, ops, n_groups, mask=None,
                        null_sentinels=None):
    """Pure-NumPy :func:`partial_tables` — same pytree, host execution.

    Exists for latency-aware routing (below the row threshold of
    ``models.query.host_kernel_rows`` a device dispatch+fetch costs more
    than the host aggregation) and for wedge survival (an unresponsive
    accelerator host-routes everything).  Bit-exactness is preserved without s64 overflow hazards:
    int sums split into 16-bit limbs whose float64 ``bincount`` weights stay
    exact integers (< 2^16 max limb x up to 2^37 rows < 2^53), recombined
    mod 2^64.  NumPy is the reference semantics the device kernels are
    tested against, so the two paths are interchangeable by construction.
    """
    from bqueryd_tpu.utils.tracing import trace_span

    # runtime (un-traced) host kernel: annotate it like the device phases so
    # a BQUERYD_TPU_PROFILE=1 timeline shows host-routed queries too, tagged
    # with the active trace_id (obs.trace)
    with trace_span("host_kernel"):
        return _host_partial_tables(
            codes, measures, ops, n_groups, mask=mask,
            null_sentinels=null_sentinels,
        )


def _host_partial_tables(codes, measures, ops, n_groups, mask=None,
                         null_sentinels=None):
    import numpy as np

    codes = np.asarray(codes)
    valid = codes >= 0
    if mask is not None:
        valid = valid & np.asarray(mask, dtype=bool)
    # the common case — no null keys, no filter — skips every np.where
    # masking pass and takes integer (unweighted) bincounts throughout
    all_valid = bool(valid.all())
    safe = (
        codes.astype(np.int64)
        if all_valid
        else np.where(valid, codes, 0).astype(np.int64)
    )
    minlength = max(int(n_groups), 1)

    # Native fast path: the striped C++ kernels in native/tpucolz.cpp run the
    # same reductions multithreaded, and their int sums accumulate in uint64
    # (mod 2^64) so they are exact at ANY magnitude — no 2^53 bincount bound.
    # Bounded by a row floor (thread spawn overhead) and a group ceiling
    # (per-thread accumulator memory).
    native_mod = None
    if (
        len(codes) >= _NATIVE_GROUPBY_MIN_ROWS
        and minlength <= _NATIVE_GROUPBY_MAX_GROUPS
    ):
        from bqueryd_tpu.storage import native as _native

        if _native.groupby_available():
            native_mod = _native
    codes32 = base_mask = None
    if native_mod is not None:
        codes32 = np.ascontiguousarray(codes, dtype=np.int32)
        if not all_valid:
            # numpy bool is 1 byte: the uint8 view keeps every native call
            # zero-copy on the mask
            base_mask = valid.view(np.uint8)

    def count_where(flags):
        if native_mod is not None:
            m = base_mask if flags is None else (
                flags.view(np.uint8) if flags.dtype == np.bool_ else flags
            )
            return native_mod.groupby_i64(codes32, None, m, minlength)[1]
        if flags is None:  # all rows count
            return np.bincount(safe, minlength=minlength).astype(np.int64)
        return np.bincount(
            safe, weights=flags.astype(np.float64), minlength=minlength
        ).astype(np.int64)

    def exact_int_sum(values, present):
        v = values.astype(np.int64, copy=False)
        if present is not None:
            v = np.where(present, v, 0)
        if len(v):
            # one float64-weighted bincount is exact when every partial sum
            # stays below 2^53: |any partial| <= n rows x max|value|
            bound = max(abs(int(v.min())), abs(int(v.max())))
            if bound * len(v) < HOST_EXACT_SUM_BOUND:
                return np.bincount(
                    safe, weights=v.astype(np.float64), minlength=minlength
                ).astype(np.int64)
        # full-range fallback: 16-bit limbs keep the weighted bincounts
        # exact (< 2^16 max limb x up to 2^37 rows < 2^53) at 4x the cost
        total = np.zeros(minlength, dtype=np.uint64)
        for i in range(4):
            if i < 3:  # unsigned 16-bit slices of the two's complement
                limb = ((v >> np.int64(16 * i)) & np.int64(0xFFFF))
            else:      # top limb keeps the sign via arithmetic shift
                limb = v >> np.int64(48)
            limb_sum = np.bincount(
                safe, weights=limb.astype(np.float64), minlength=minlength
            )
            # float64 totals are exact integers; recombine mod 2^64
            total = total + (
                limb_sum.astype(np.int64).astype(np.uint64)
                << np.uint64(16 * i)
            )
        return total.astype(np.int64)

    def null_mask(values, sentinel):
        if sentinel is not None:
            return values == np.asarray(sentinel, dtype=values.dtype)
        if np.issubdtype(values.dtype, np.floating):
            return np.isnan(values)
        return np.zeros(values.shape, dtype=bool)

    rows = count_where(None if all_valid else valid)
    sentinels = _normalize_sentinels(null_sentinels, len(measures))
    # (values id, dtype) -> (values, (mins, maxs, counts)); the array is
    # cached alongside the result to pin its id() for the cache's lifetime
    _minmax_cache = {}
    aggs = []
    for values, op, sentinel in zip(measures, ops, sentinels):
        if op not in MERGEABLE_OPS:
            raise ValueError(
                f"op {op!r} has no mergeable partial; use the dedicated kernel"
            )
        if sentinel is not None and op in ("sum", "mean"):
            raise ValueError(
                f"op {op!r} cannot aggregate a sentinel-null measure"
            )
        values = np.asarray(values)
        if (
            native_mod is not None
            and sentinel is None
            and op in ("min", "max")
            and native_mod.groupby_minmax_available()
            # unsigned values >= 2^63 would wrap in the signed i64 kernel
            # (and uint64's identity fill overflows int64): numpy path
            and not np.issubdtype(values.dtype, np.unsignedinteger)
        ):
            # one striped pass yields min+max+present counts; empty groups
            # re-filled with the MEASURE dtype's identity after the int64/f64
            # kernel so cross-shard merges stay correct post-cast.  min and
            # max of the SAME measure share the pass via the cache.
            cache_key = (id(values), values.dtype.str)
            entry = _minmax_cache.get(cache_key)
            if entry is None:
                hit = native_mod.groupby_minmax(
                    codes32, values, base_mask, minlength
                )
                # the cached array keeps ``values`` alive so its id() can't
                # be recycled onto a different same-dtype measure while the
                # cache exists (callers may pass non-ndarray measures whose
                # asarray conversion would otherwise die with the iteration)
                _minmax_cache[cache_key] = entry = (values, hit)
            mns, mxs, cnts = entry[1]
            ext64 = mns if op == "min" else mxs
            target = values.dtype
            ext = np.where(
                cnts == 0, extremum_fill(target, op), ext64
            ).astype(target)
            aggs.append({op: ext, "count": cnts})
            continue
        if native_mod is not None and op in ("sum", "mean"):
            # one striped kernel call yields sum AND presence count (the
            # mean denominator) — and runs before any isnan/present
            # bookkeeping, which the kernels handle internally.  Integer
            # MEANS go through the f64 kernel (pandas float-mean semantics,
            # see the scatter path).
            if np.issubdtype(values.dtype, np.floating) or op == "mean":
                fsums, fcounts = native_mod.groupby_f64(
                    codes32, np.asarray(values, dtype=np.float64),
                    base_mask, minlength, want_counts=(op == "mean"),
                )
                partial = {"sum": fsums}
                if op == "mean":
                    partial["count"] = fcounts
            else:
                isums, _ = native_mod.groupby_i64(
                    codes32, values.astype(np.int64, copy=False),
                    base_mask, minlength,
                )
                partial = {"sum": isums}
            aggs.append(partial)
            continue
        null = null_mask(values, sentinel)
        has_null = null.any() if (
            sentinel is not None
            or np.issubdtype(values.dtype, np.floating)
        ) else False
        # present=None means "every row contributes" — the fast paths above
        present = None if (all_valid and not has_null) else (valid & ~null)
        if op in ("sum", "mean"):
            if np.issubdtype(values.dtype, np.floating) or op == "mean":
                # integer means accumulate in f64 like pandas (wrapped
                # mod-2^64 int sums would corrupt the mean past 2^63)
                contrib = (
                    values if present is None else np.where(present, values, 0)
                ).astype(np.float64)
                partial = {
                    "sum": np.bincount(
                        safe, weights=contrib, minlength=minlength
                    )
                }
            else:
                partial = {"sum": exact_int_sum(values, present)}
            if op == "mean":
                partial["count"] = count_where(present)
            aggs.append(partial)
        elif op == "count":
            aggs.append({"count": count_where(present)})
        elif op == "count_na":
            na = (
                np.zeros(minlength, dtype=np.int64)
                if not has_null
                else count_where(valid & null)
            )
            aggs.append({"count": na})
        elif op in ("min", "max"):
            sel = slice(None) if present is None else present
            ext = np.full(
                minlength, extremum_fill(values.dtype, op),
                dtype=values.dtype,
            )
            if op == "min":
                np.minimum.at(ext, safe[sel], values[sel])
            else:
                np.maximum.at(ext, safe[sel], values[sel])
            aggs.append({op: ext, "count": count_where(present)})
    return {"rows": rows, "aggs": tuple(aggs)}


def combine_partials(a, b):
    """Merge two partial-table pytrees (host- or device-side tree reduce)."""
    rows = a["rows"] + b["rows"]
    aggs = []
    for pa, pb in zip(a["aggs"], b["aggs"]):
        merged = {}
        for key in pa:
            if key == "min":
                merged[key] = jnp.minimum(pa[key], pb[key])
            elif key == "max":
                merged[key] = jnp.maximum(pa[key], pb[key])
            else:  # sum / count
                merged[key] = pa[key] + pb[key]
        aggs.append(merged)
    return {"rows": rows, "aggs": tuple(aggs)}


def finalize(partials, ops):
    """Turn merged partials into final per-group aggregate arrays.

    mean = sum / count (correct weighted mean across shards — deliberately
    NOT the reference's sum-of-shard-means, reference bqueryd/rpc.py:171).
    Groups with no contributing rows yield NaN for mean/min/max and 0 for
    sum/count, matching pandas.
    """
    out = []
    for partial, op in zip(partials["aggs"], ops):
        if op == "mean":
            count = partial["count"]
            out.append(
                jnp.where(
                    count > 0,
                    partial["sum"] / jnp.maximum(count, 1),
                    jnp.nan,
                )
            )
        elif op in ("sum",):
            out.append(partial["sum"])
        elif op in ("count", "count_na"):
            out.append(partial["count"])
        elif op in ("min", "max"):
            value = partial[op]
            empty = partial["count"] == 0
            if jnp.issubdtype(value.dtype, jnp.floating):
                # empty groups -> NaN by count, never by value: genuine
                # +/-inf data must survive
                out.append(jnp.where(empty, jnp.nan, value))
            else:
                # int columns have no NaN; empty groups report 0 and are
                # dropped upstream by the rows>0 filter
                out.append(jnp.where(empty, 0, value))
        else:
            raise ValueError(f"cannot finalize op {op!r}")
    return tuple(out)


def groupby_aggregate(codes, measures, ops, n_groups, mask=None):
    """Single-shard convenience: partials -> finalize in one call.

    Returns ``(tables, rows)`` where ``tables[i]`` is the aggregate array for
    ``ops[i]`` (shape [n_groups]) and ``rows`` counts valid rows per group
    (used to drop never-seen groups)."""
    ops = tuple(ops)
    partials = partial_tables(codes, tuple(measures), ops, n_groups, mask)
    return finalize(partials, ops), partials["rows"]


@functools.partial(jax.jit, static_argnames=("n_groups", "n_values"))
def groupby_count_distinct(codes, value_codes, n_groups, n_values, mask=None):
    """Distinct-value count per group via sort + boundary detection.

    ``value_codes`` are dense codes of the measure values (host-factorized).
    Static shapes throughout: sort of [n], then a segment_sum of boundary
    flags.  O(n log n) but bandwidth-friendly on TPU."""
    from bqueryd_tpu.ops.factorize import (
        MAX_COMPOSITE,
        CompositeOverflow,
        total_cardinality,
    )

    if total_cardinality((n_groups, n_values)) >= MAX_COMPOSITE:
        # static args: raises at trace time.  Both factors are bounded by
        # row count, so this needs ~3e9-row single shards to fire — but a
        # wrapped (group, value) composite would undercount distincts
        # silently, which is never acceptable.  The engine degrades to the
        # distinct-value-set path on this error.
        raise CompositeOverflow(
            f"count_distinct composite space {n_groups}x{n_values} "
            "exceeds int64"
        )
    valid = (codes >= 0) & (value_codes >= 0)
    if mask is not None:
        valid = valid & mask
    composite = jnp.where(
        valid, codes.astype(jnp.int64) * n_values + value_codes, jnp.int64(-1)
    )
    ordered = jnp.sort(composite)
    first = jnp.concatenate(
        [jnp.array([True]), ordered[1:] != ordered[:-1]]
    )
    is_new = first & (ordered >= 0)
    group_of = jnp.where(is_new, ordered // n_values, 0).astype(jnp.int32)
    return jax.ops.segment_sum(
        is_new.astype(jnp.int64), group_of, num_segments=n_groups
    )


groupby_count_distinct = _obsprofile.instrument(
    "ops.groupby_count_distinct", groupby_count_distinct
)


def expand_mask_by_group(group_codes, mask, n_groups=None):
    """Expand a row mask to whole groups: every row whose group contains at
    least one selected row becomes selected (the basket-expansion semantics of
    ``is_in_ordered_subgroups(basket_col, bool_arr)`` at reference
    bqueryd/worker.py:306-307, without requiring sorted input).

    segment-max of the mask over group codes, gathered back to rows.
    Negative codes (null baskets) are never selected.  Pass ``n_groups`` (the
    dense code cardinality) to keep the scatter O(groups); it defaults to the
    safe-but-wasteful row count."""
    if mask is None:
        return None
    from bqueryd_tpu.utils import devicehealth

    if devicehealth.backend_wedged():
        # host equivalent (same semantics: any selected row selects its
        # whole group; negative codes never selected) — a wedged backend
        # must not hang the basket filter
        codes_np = np.asarray(group_codes)
        mask_np = np.asarray(mask, dtype=bool)
        if n_groups is None:
            n_groups = codes_np.shape[0]
        valid = codes_np >= 0
        hit = np.zeros(max(int(n_groups), 1), dtype=bool)
        # out-of-range codes (>= n_groups) mirror the device twin exactly:
        # the jit scatter (segment_max with num_segments) silently DROPS
        # them, and the jit gather CLAMPS the index — an unguarded numpy
        # fancy-index would instead raise IndexError (divergent edge
        # semantics between two interchangeable paths, ADVICE r5 low #2)
        sel = valid & mask_np & (codes_np < int(n_groups))
        hit[codes_np[sel]] = True
        gather = np.minimum(
            np.where(valid, codes_np, 0), max(int(n_groups) - 1, 0)
        )
        return valid & hit[gather]
    group_codes = jnp.asarray(group_codes)
    if n_groups is None:
        n_groups = group_codes.shape[0]
    # bucketed (program_bucket): basket cardinality drifts per shard and per
    # refresh; the output is row-shaped, so padding the segment table needs
    # no slicing — padded groups are simply never hit
    return _expand_mask_jit(
        group_codes, jnp.asarray(mask), program_bucket(int(n_groups))
    )


@functools.partial(jax.jit, static_argnames=("n_groups",))
def _expand_mask_jit(group_codes, mask, n_groups):
    valid = group_codes >= 0
    safe = jnp.where(valid, group_codes, 0).astype(jnp.int32)
    hit = jax.ops.segment_max(
        (mask & valid).astype(jnp.int32), safe, num_segments=max(n_groups, 1),
    )
    return (hit[safe] > 0) & valid


_expand_mask_jit = _obsprofile.instrument(
    "ops.expand_mask", _expand_mask_jit
)


def host_sorted_count_distinct(codes, values, n_groups, mask=None):
    """NumPy twin of :func:`groupby_sorted_count_distinct` (identical
    run-boundary semantics, including masked-row bridging and NaN != NaN
    starting a new run) — serves the op while the accelerator backend is
    wedged (:mod:`bqueryd_tpu.utils.devicehealth`)."""
    codes = np.asarray(codes)
    values = np.asarray(values)
    if codes.shape[0] == 0:
        return np.zeros(int(n_groups), dtype=np.int64)
    valid = codes >= 0
    if mask is not None:
        valid = valid & np.asarray(mask, dtype=bool)
    idx = np.arange(codes.shape[0])
    marked = np.where(valid, idx, -1)
    last_valid = np.maximum.accumulate(marked)
    prev_idx = np.concatenate([[-1], last_valid[:-1]])
    has_prev = prev_idx >= 0
    gather = np.clip(prev_idx, 0, None)
    with np.errstate(invalid="ignore"):
        same = (
            has_prev
            & (codes[gather] == codes)
            & (values[gather] == values)
        )
    is_new_run = valid & ~same
    out = np.zeros(max(int(n_groups), 1), dtype=np.int64)
    np.add.at(out, codes[is_new_run].astype(np.int64), 1)
    return out[: int(n_groups)]


@functools.partial(jax.jit, static_argnames=("n_groups",))
def groupby_sorted_count_distinct(codes, values, n_groups, mask=None):
    """bquery's ``sorted_count_distinct``: counts value *runs* per group,
    assuming rows are pre-sorted by value within each group (reference
    bquery API surface; run-boundary semantics).  Works on raw values (no
    factorize needed) since only adjacent comparison matters."""
    valid = codes >= 0
    if mask is not None:
        valid = valid & mask
    # Run boundaries must be measured against the previous *valid* row (a
    # masked-out row in the middle of a run must not split or hide it):
    # last-valid-index-before-i via an exclusive cumulative max.
    idx = jnp.arange(codes.shape[0])
    marked = jnp.where(valid, idx, -1)
    last_valid = jax.lax.cummax(marked)
    prev_idx = jnp.concatenate([jnp.array([-1]), last_valid[:-1]])
    has_prev = prev_idx >= 0
    gather = jnp.clip(prev_idx, 0, None)
    same = (
        has_prev
        & (codes[gather] == codes)
        & (values[gather] == values)
    )
    is_new_run = valid & ~same
    safe = jnp.where(valid, codes, 0).astype(jnp.int32)
    return jax.ops.segment_sum(
        is_new_run.astype(jnp.int64), safe, num_segments=n_groups
    )


groupby_sorted_count_distinct = _obsprofile.instrument(
    "ops.groupby_sorted_count_distinct", groupby_sorted_count_distinct
)
