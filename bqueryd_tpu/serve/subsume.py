"""Plan-signature subsumption lattice: which cached answers PROVE a query.

Every earlier cache in the system hits only on exact identities — the
worker result cache on (table identity, query signature), PR-1 dedup on
the request signature, PR-9 bundles on one admission window.  This module
is the semantic half: given the logical plan of an admitted query and the
set of controller-resident materialized rollups (:mod:`.rollup`), it
enumerates the candidates that *provably contain* the query's answer and
the transform that extracts it:

* **exact** — same plan view: serve the stored partials verbatim;
* **window-fold** — a finer time-window rollup answers a coarser window
  when the coarse grid is a refinement-aligned multiple of the fine one
  (``q_every % c_every == 0`` and the origins agree modulo the fine
  width): every fine bucket lands wholly inside one coarse bucket, so
  re-flooring the bucket keys and re-aggregating through the hostmerge
  value-kinds forms is exact;
* **key-fold** — a finer group-key rollup answers a coarser groupby by
  dropping key columns and re-aggregating.  Sound ONLY when every
  dropped key column is a null-free integer column (proven by the
  build-time column census): null group keys drop rows from the finer
  grouping that the coarser query would have kept;
* **zone-proof filter** — a cached *weaker-filtered* (usually
  unfiltered) rollup answers a filtered query when per-chunk zone maps
  (PR 14) prove each extra predicate term selects EVERY committed chunk
  whole.  Integer columns only — float/datetime zone maps skip NaN/NaT
  rows, so "full chunk" cannot be proven for them.

Refusal is the default: partial-chunk filter overlap, window
misalignment, non-mergeable aggregations (count_distinct, top-k,
sketches, raw rows, basket expansion), joins, and anything this module
cannot prove falls back to ``recompute`` — the dispatch path is always
correct, serving is only ever an optimization.

The chosen source is costed through :func:`analytic_units`: folding a
G-group partial must be cheaper than re-scanning N rows, which it is
whenever G << N — the *Global Hash Tables Strike Back!* observation this
layer is built on.

Pure control-plane module: NumPy only, importable by the (JAX-free)
controller; all functions are deterministic on their inputs.
"""

import math

from bqueryd_tpu.models.query import MERGEABLE_OPS

#: aggregation ops a rollup partial can be re-aggregated under (the
#: hostmerge-mergeable classics; distinct counts carry value sets, top-k /
#: sketch parts are DAG-only and never enter the lattice)
SERVE_OPS = frozenset(MERGEABLE_OPS)

#: index of the window signature inside :meth:`plan.dag.OperatorDAG.signature`
_DAG_WINDOW_IDX = 7
#: index of the join signature (any join disqualifies)
_DAG_JOIN_IDX = 6

#: operators a zone map can prove full-chunk selection for
_FULL_SELECT_OPS = frozenset({"==", "!=", ">", ">=", "<", "<=", "in"})


def _freeze_term(term):
    col, op, value = term
    if isinstance(value, (list, tuple, set)):
        value = tuple(value)
    return (col, op, value)


def plan_view(plan):
    """The hashable lattice view of a logical plan: the fields subsumption
    reasons over, decoupled from :meth:`LogicalPlan.signature`'s frozen
    encoding so candidate/query comparison can be structural."""
    return {
        "filenames": tuple(plan.filenames),
        "keys": tuple(plan.groupby.keys),
        "aggs": tuple(tuple(a) for a in plan.physical_agg_list()),
        "where": tuple(_freeze_term(t) for t in plan.where_terms),
        "aggregate_rows": bool(plan.aggregate_rows),
        "expand": plan.expand_filter_column,
        "dag_sig": getattr(plan, "dag_sig", None),
    }


def view_key(view):
    """Stable string identity of a view — the rollup-store key and the
    ``subsumed_from`` wire value."""
    import hashlib

    digest = hashlib.sha1(repr(sorted(view.items())).encode()).hexdigest()
    return f"rollup:{'+'.join(view['keys']) or 'all'}:{digest[:12]}"


def plan_eligible(view):
    """``(ok, reason)``: can this plan shape be semantically served / rolled
    up at all?  Strict — anything outside the mergeable-aggregate lattice
    is refused with a reason (surfaced in the flight recorder)."""
    if not view["aggregate_rows"]:
        return False, "raw-rows"
    if view["expand"]:
        return False, "expand-filter"
    for _in_col, op, _out in view["aggs"]:
        if op not in SERVE_OPS:
            return False, f"op:{op}"
    dag_sig = view["dag_sig"]
    if dag_sig is not None:
        if dag_sig[_DAG_JOIN_IDX] is not None:
            return False, "join"
        if dag_sig[_DAG_WINDOW_IDX] is None:
            # a plain DAG (rpc.query without window): exact signature
            # match still works, but no fold is defined over it
            return True, None
    return True, None


def zone_full_select(lo, hi, op, value):
    """PROOF from one chunk's ``(min, max)`` zone that ``op value`` selects
    every row of the chunk.  Conservative: unknown ops / incomparable
    values return False."""
    try:
        if op == "==":
            return lo == hi == value
        if op == "!=":
            return not isinstance(value, (list, tuple)) and (
                value < lo or value > hi
            )
        if op == ">":
            return lo > value
        if op == ">=":
            return lo >= value
        if op == "<":
            return hi < value
        if op == "<=":
            return hi <= value
        if op == "in":
            return lo == hi and lo in value
    except TypeError:
        return False
    return False


def term_full_selects(cols_meta, term):
    """True when the build-time column census proves ``term`` selects every
    committed chunk of ONE file whole.  Requires an integer column (float /
    datetime zone maps skip NaN/NaT — "all rows" is unprovable) with a zone
    map on every chunk."""
    col, op, value = term
    if op not in _FULL_SELECT_OPS:
        return False
    info = (cols_meta or {}).get(col)
    if not info or info.get("kind") != "int":
        return False
    zones = info.get("zones")
    if not zones:
        return False
    return all(
        z is not None and zone_full_select(z[0], z[1], op, value)
        for z in zones
    )


def _match_window(cand, query):
    """Window-fold match over two DAG-shaped views; returns (transform,
    refusal_reason)."""
    c_sig, q_sig = cand["dag_sig"], query["dag_sig"]
    if len(c_sig) != len(q_sig):
        return None, "dag-version"
    for i in range(len(c_sig)):
        if i != _DAG_WINDOW_IDX and c_sig[i] != q_sig[i]:
            return None, "dag-shape"
    c_win, q_win = c_sig[_DAG_WINDOW_IDX], q_sig[_DAG_WINDOW_IDX]
    if c_win is None or q_win is None:
        return None, "window-missing"
    c_col, c_every, c_alias, c_origin = c_win
    q_col, q_every, q_alias, q_origin = q_win
    if c_col != q_col or c_alias != q_alias:
        return None, "window-column"
    if q_every % c_every != 0:
        return None, "window-misaligned"
    if (q_origin - c_origin) % c_every != 0:
        return None, "window-origin"
    return {
        "kind": "fold",
        "window": (q_alias, int(q_every), int(q_origin)),
    }, None


def _match_fold(cand, query, meta):
    """Key-fold + agg projection + zone-proof extra-filter match over two
    plain (dag-free) views.  ``meta`` is ``{filename: {col: {"kind", "zones",
    "nulls"}}}`` from the candidate's build census."""
    if not set(query["keys"]) <= set(cand["keys"]):
        return None, "keys"
    dropped = [k for k in cand["keys"] if k not in query["keys"]]
    for k in dropped:
        # a null group key drops its row from the finer grouping; the
        # coarser query keeps that row — fold only over proven-null-free
        # (integer) key columns, checked per file
        for fname in query["filenames"]:
            info = ((meta or {}).get(fname) or {}).get(k)
            if not info or info.get("kind") != "int" or info.get("nulls"):
                return None, f"key-nullable:{k}"
    cand_aggs = list(cand["aggs"])
    agg_idx = []
    for agg in query["aggs"]:
        if agg not in cand_aggs:
            return None, f"agg-missing:{agg[2]}"
        agg_idx.append(cand_aggs.index(agg))
    c_where, q_where = set(cand["where"]), set(query["where"])
    if not c_where <= q_where:
        return None, "filter-weaker"
    extra = [t for t in query["where"] if t not in c_where]
    for term in extra:
        for fname in query["filenames"]:
            if not term_full_selects((meta or {}).get(fname), term):
                return None, f"filter-partial:{term[0]}"
    transform = {"kind": "fold"}
    if tuple(query["keys"]) != tuple(cand["keys"]):
        transform["keys"] = tuple(query["keys"])
    if agg_idx != list(range(len(cand_aggs))):
        transform["aggs"] = tuple(agg_idx)
    if len(transform) == 1 and not extra:
        # structurally identical after all: exact
        transform = {"kind": "exact"}
    elif len(transform) == 1:
        # zone-proven filter over the identical shape: the stored payload
        # serves verbatim, no fold needed
        transform = {"kind": "zone"}
    return transform, None


def match(cand, query, meta=None):
    """Match one candidate view against a query view.

    Returns ``(transform, None)`` on success or ``(None, reason)`` on
    refusal.  ``transform["kind"]`` is ``"exact"`` (serve stored bytes
    verbatim), ``"zone"`` (verbatim, justified by zone proofs), or
    ``"fold"`` (re-key and/or project and collapse)."""
    if cand["filenames"] != query["filenames"]:
        return None, "filenames"
    if cand["aggregate_rows"] != query["aggregate_rows"] or (
        cand["expand"] != query["expand"]
    ):
        return None, "shape"
    if cand == query:
        return {"kind": "exact"}, None
    c_dag, q_dag = cand["dag_sig"], query["dag_sig"]
    if (c_dag is None) != (q_dag is None):
        return None, "shape"
    if c_dag is not None:
        return _match_window(cand, query)
    return _match_fold(cand, query, meta)


def apply_transform(payload, transform):
    """Apply a match transform to ONE partials payload dict, returning a new
    payload dict.  ``exact``/``zone`` pass through; ``fold`` projects the
    aggregation slots, re-keys (window re-floor and/or key-column drop) and
    collapses duplicate key tuples through
    :func:`bqueryd_tpu.parallel.hostmerge.collapse_partials` — the same
    value-kinds merge forms every cross-shard combine uses."""
    import numpy as np

    from bqueryd_tpu.parallel import hostmerge

    if payload.get("kind") != "partials" or transform["kind"] != "fold":
        return payload
    p = dict(payload)
    sel = transform.get("aggs")
    if sel is not None:
        p["aggs"] = [payload["aggs"][i] for i in sel]
        p["ops"] = [payload["ops"][i] for i in sel]
        p["out_cols"] = [payload["out_cols"][i] for i in sel]
        kinds = payload.get("value_kinds")
        if kinds is not None:
            p["value_kinds"] = [kinds[i] for i in sel]
    window = transform.get("window")
    if window is not None:
        alias, every, origin = window
        arr = np.asarray(p["keys"][alias])
        ints = arr.astype(np.int64, copy=False)
        floored = origin + ((ints - origin) // every) * every
        # NaT bucket keys (int64 min) pass through unfloored — the window
        # derivation drops NaT rows, so none should exist; belt-and-braces
        nat = ints == np.iinfo(np.int64).min
        if nat.any():
            floored = np.where(nat, ints, floored)
        keys = dict(p["keys"])
        keys[alias] = (
            floored.view(arr.dtype) if arr.dtype.kind == "M"
            else floored.astype(arr.dtype)
        )
        p["keys"] = keys
    keep = transform.get("keys")
    if keep is not None:
        p["key_cols"] = list(keep)
        p["keys"] = {c: p["keys"][c] for c in keep}
    return hostmerge.collapse_partials(p)


def analytic_units(strategy, rows, groups):
    """Backend-free relative cost of a route at (rows, groups) — the same
    quantities HLO ``cost_analysis`` counts, in arbitrary units: the one-hot
    contraction is rows x groups MACs, the blocked scatter is a per-limb
    rows pass plus its ``blocks x groups`` table, the sort is
    ``rows log rows`` comparisons per limb."""
    rows = max(int(rows), 1)
    groups = max(int(groups), 1)
    if strategy == "matmul":
        return float(rows) * groups
    if strategy == "sort":
        return float(rows) * max(math.log2(max(rows, 2)), 1.0) * 8.0
    # scatter: 4 16-bit limb passes over rows + the blocked bucket table,
    # whose blocks x groups cells are written AND reduced (memory-bound) —
    # the term that makes extreme cardinality favour the sort, matching the
    # engine's own _MAX_BLOCK_SEGMENTS economics
    blocks = -(-rows // 65536)
    return float(rows) * 8.0 + float(blocks) * groups * 8.0


def serving_cost(groups, out_groups):
    """Relative cost of answering from a G-group partial (host fold)."""
    return analytic_units("scatter", groups, max(out_groups, 1))


def recompute_cost(total_rows, out_groups):
    """Relative cost of the dispatch path re-scanning ``total_rows``."""
    return analytic_units("scatter", max(total_rows, 1), max(out_groups, 1))


def choose_source(matches, total_rows):
    """Pick the cheapest-correct candidate: ``matches`` is a list of
    ``(entry_key, transform, candidate_group_rows)``; returns the winning
    tuple or None when recompute is estimated cheaper than every candidate
    (tiny tables) — the cost decision the lattice defers to."""
    best = None
    floor = recompute_cost(total_rows, 1)
    for entry_key, transform, groups in matches:
        cost = serving_cost(groups, 1)
        if cost >= floor:
            continue
        if best is None or cost < best[3]:
            best = (entry_key, transform, groups, cost)
    if best is None:
        return None
    return best[:3]
