"""where_terms: filter terms -> boolean row masks, with shard pruning.

The TPU equivalent of bquery's ``where_terms`` / ``where_terms_factorization_check``
(reference bqueryd/worker.py:296-303): a filter is a list of
``(column, op, value)`` terms AND-ed together.  Ops: ==, !=, <, <=, >, >=,
in, not in.

Masks are computed with jnp ops so the whole predicate fuses into the
aggregation kernel when evaluated under jit (the "masked segment_sum pushdown"
from SURVEY.md §2.3 — no materialized row copies, unlike the reference's
bool-array + fancy-indexing path).

Value translation happens host-side against the table's dictionaries:

* dict columns compare by code; a value absent from the dictionary maps to
  code -2, which naturally yields all-false for ==/in and all-true for
  !=/not-in (codes are always >= -1);
* datetime columns compare as int64 nanoseconds.

:func:`shard_can_match` is the cheap host-side precheck (the
factorization-check early-out at reference bqueryd/worker.py:296-301): column
min/max stats and dictionary membership decide whether a shard can contain any
matching row before anything is decompressed or shipped to the device.
"""


import os

WHERE_OPS = ("==", "!=", "<", "<=", ">", ">=", "in", "not in")

#: ops the per-chunk zone maps can prune on (plan.stats.zone_can_match);
#: ``!=``/``not in`` never prune — NaN rows satisfy them but are invisible
#: to the NaN-skipping zone maps
ZONE_PRUNABLE_OPS = ("==", "<", "<=", ">", ">=", "in")


def _to_ns(value):
    import pandas as pd

    return int(pd.Timestamp(value).value)


def translate_value(table, column, value, op="=="):
    """Translate a user-facing term value into physical column space.

    Range ops on dict columns are rejected: dictionary codes are in
    first-seen order, so ``<``/``>`` over codes would compare ingestion order,
    not values."""
    if isinstance(value, (set, frozenset)):
        value = list(value)  # sets accepted for in/not-in on any column kind
    kind = table.kind(column)
    if kind == "dict":
        if op in ("<", "<=", ">", ">="):
            raise ValueError(
                f"range op {op!r} is not supported on dictionary-encoded "
                f"column {column!r} (codes are unordered)"
            )
        lookup = table.dict_lookup(column)
        if isinstance(value, (list, tuple)):
            return [lookup.get(str(v), -2) for v in value]
        return lookup.get(str(value), -2)
    if kind == "datetime":
        if isinstance(value, (list, tuple)):
            return [_to_ns(v) for v in value]
        return _to_ns(value)
    return value


def term_mask(values, op, value):
    """Boolean mask for one term over a physical value array (jnp or np).

    On a wedged accelerator backend the mask computes in NumPy instead —
    identical elementwise semantics, and the filter path must not be the
    one device dispatch that hangs an otherwise host-served query.  (The
    executor's device-resident columns never reach here while wedged: the
    worker skips the mesh path entirely then.)"""
    from bqueryd_tpu.utils import devicehealth

    if devicehealth.backend_wedged():
        import numpy as xp

        if not isinstance(values, xp.ndarray) and type(values).__module__.split(
            ".", 1
        )[0].startswith("jax"):
            # a device-resident jax Array here means the latch flipped AFTER
            # columns were device-put: np.asarray on it would perform the
            # blocking device transfer this branch exists to avoid.  Fail
            # fast instead of hanging the worker loop; the caller's wedged
            # routing retries from host-resident columns.
            raise TypeError(
                "term_mask received a device-resident array while the "
                "accelerator backend is wedged; re-evaluate the filter from "
                "host-resident columns"
            )
    else:
        import jax.numpy as xp
    values = xp.asarray(values)
    if op == "==":
        return values == value
    if op == "!=":
        return values != value
    if op == "<":
        return values < value
    if op == "<=":
        return values <= value
    if op == ">":
        return values > value
    if op == ">=":
        return values >= value
    if op == "in":
        return xp.isin(values, xp.asarray(value))
    if op == "not in":
        return ~xp.isin(values, xp.asarray(value))
    raise ValueError(f"unsupported where op {op!r}")


def build_mask(table, where_terms_list, column_getter=None):
    """AND together all terms into one bool mask (jnp array), or return None
    for an empty term list (no filtering — same contract as the reference
    passing bool_arr=None, reference bqueryd/worker.py:294-309).

    ``column_getter`` overrides physical column access; no caller in the
    package passes one (the mesh executor, the DAG executors and the
    per-shard engine all read from the table).  The default hands
    :func:`term_mask` the table's HOST copy of each column, which it
    uploads on EVERY call (``jnp.asarray``): a caller that evaluates fresh
    filters over the same tables should keep the column on the device, as
    ``MeshQueryExecutor._fold_on_device`` does with :func:`term_mask`
    itself under one jit."""
    if not where_terms_list:
        return None
    get = column_getter or (lambda name: table.column_raw(name))
    mask = None
    for term in where_terms_list:
        column, op, value = term
        phys = translate_value(table, column, value, op)
        m = term_mask(get(column), op, phys)
        mask = m if mask is None else (mask & m)
    return mask


def chunk_prune_enabled():
    """Chunk-granular zone-map pruning kill switch
    (``BQUERYD_TPU_CHUNK_PRUNE``, default on)."""
    return os.environ.get("BQUERYD_TPU_CHUNK_PRUNE", "1") == "1"


def chunk_prune_selectivity():
    """Surviving-chunk fraction ABOVE which pruning is skipped
    (``BQUERYD_TPU_CHUNK_PRUNE_SELECTIVITY``, default 0.9): a filter that
    keeps nearly every chunk would fragment the content-keyed caches for
    no decode savings."""
    try:
        return float(
            os.environ.get("BQUERYD_TPU_CHUNK_PRUNE_SELECTIVITY", "0.9")
        )
    except ValueError:
        return 0.9


def chunk_selection(table, where_terms_list):
    """Boolean keep-mask over the table's committed chunk grid for an
    AND-ed term list, or None when nothing is prunable (no zone maps, no
    prunable ops, single chunk).  A False entry is PROOF (from per-chunk
    min/max) that no row of that chunk satisfies the conjunction."""
    import numpy as np

    from bqueryd_tpu.plan.stats import zone_can_match

    counts = getattr(table, "chunk_rows", lambda: None)()
    if counts is None or len(counts) <= 1:
        return None
    keep = np.ones(len(counts), dtype=bool)
    prunable = False
    for term in where_terms_list or []:
        try:
            column, op, value = term
        except (TypeError, ValueError):
            continue
        if op not in ZONE_PRUNABLE_OPS or column not in table:
            continue
        maps = table.chunk_zone_maps(column)
        if maps is None or len(maps) != len(counts):
            continue
        phys = translate_value(table, column, value, op)
        for i, zone in enumerate(maps):
            if not keep[i] or zone is None:
                continue
            if not zone_can_match(zone[0], zone[1], op, phys):
                keep[i] = False
                prunable = True
    return keep if prunable else None


def chunk_pruned_table(table, where_terms_list):
    """``(table_or_view, chunks_decoded, chunks_skipped)``: the zone-map
    pruning seam the worker's execute paths call.  Returns the original
    table untouched (counters still meaningful) unless pruning is enabled,
    at least one chunk is provably unmatchable, and the surviving fraction
    sits at or under the selectivity floor.  NEVER use with basket
    expansion (``expand_filter_column``): expansion re-selects rows of the
    same basket that live in pruned chunks."""
    counts = getattr(table, "chunk_rows", lambda: None)()
    total = len(counts) if counts is not None else 0
    if not chunk_prune_enabled():
        return table, 0, 0
    keep = chunk_selection(table, where_terms_list)
    if keep is None:
        return table, total, 0
    selected = int(keep.sum())
    if selected == total or selected / total > chunk_prune_selectivity():
        return table, total, 0
    import numpy as np

    view = table.chunk_view(np.flatnonzero(keep))
    return view, selected, total - selected


def shard_can_match(table, where_terms_list):
    """Host-side pruning: False only if NO row of this shard can satisfy the
    conjunction.  Uses column min/max stats (numeric/datetime) and dictionary
    membership (dict columns); unknown columns/ops conservatively match."""
    for term in where_terms_list or []:
        column, op, value = term
        if column not in table:
            continue
        try:
            kind = table.kind(column)
            if kind == "dict":
                phys = translate_value(table, column, value, op)
                if op == "==" and phys == -2:
                    return False
                if op == "in" and isinstance(phys, list) and all(
                    p == -2 for p in phys
                ):
                    return False
                continue
            stats = table.col_stats(column)
            if stats is None:
                continue
            lo, hi = stats
            if kind == "datetime":
                value_phys = translate_value(table, column, value, op)
            else:
                value_phys = value
            if op == "==" and not (
                isinstance(value_phys, (list, tuple))
            ) and (value_phys < lo or value_phys > hi):
                return False
            if op == ">" and hi <= value_phys:
                return False
            if op == ">=" and hi < value_phys:
                return False
            if op == "<" and lo >= value_phys:
                return False
            if op == "<=" and lo > value_phys:
                return False
            if op == "in" and isinstance(value_phys, (list, tuple)) and all(
                v < lo or v > hi for v in value_phys
            ):
                return False
        except ValueError:
            raise  # range-op-on-dict is a real query error, surface it
        except TypeError:
            # value not comparable with stats (wrong type, etc.): pruning is
            # best-effort — conservatively keep the shard and let the mask
            # path produce the proper error or coercion
            continue
    return True

