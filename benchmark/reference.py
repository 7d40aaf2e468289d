"""The plain reference and the comparison that decides ``correct``.

A copy of ``chip_smoke.reference_answer``: the same query as a pandas
groupby over the same seeded frames.  It imports nothing of ``bqueryd_tpu``
and takes nothing the program made.  It answers every aggregation op and
every filter operator that ``rpc.groupby`` takes, under the program's own
names (``AGGS``, ``OPS``: copied, not imported), so a configuration's
queries go unchanged to the program and to the reference; ``unanswerable``
names what it cannot answer, before a run starts.  ``compare`` returns
numbers, each held against a limit of its own (the configuration's
``check_limits``):

``int_mismatch``  group keys, integer and datetime aggregates (every count
                  among them) that are not bit for bit the reference's (a
                  missing or extra group counts too)
``f32_mean_rel``  widest relative gap of a float aggregate over a float32
                  column (a mean, a sum, an extreme)
``f64_mean_rel``  the same over a float64 column
``unanswered``    sampled queries of the window that never got an answer
"""

import numpy as np
import pandas as pd

#: the program's filter operators (``WHERE_OPS`` of ``bqueryd_tpu.ops.predicates``):
#: a term keeps the rows where ``OPS[op](column, value)`` holds; ``in`` and
#: ``not in`` take a list
OPS = {">": np.greater, ">=": np.greater_equal, "<": np.less,
       "<=": np.less_equal, "==": np.equal, "!=": np.not_equal,
       "in": np.isin, "not in": lambda values, value: ~np.isin(values, value)}


def _count_na(df, gcols, col, file_of):
    """1 for a null value (NaN, NaT); an integer column has none."""
    return df[col].isna().to_numpy()


def _count_distinct(df, gcols, col, file_of):
    """1 for the first row of each (keys, value) pair with a value that is
    not null: the group's sum is its number of distinct values, nulls
    dropped (pandas' ``nunique``), over every file the query names."""
    return df[col].notna().to_numpy() & ~df.duplicated(list(gcols) + [col]).to_numpy()


def _sorted_count_distinct(df, gcols, col, file_of):
    """1 where a run of equal values begins: a row whose file, keys or value
    differ from those of the row before it among the rows that passed the
    filter (NaN differs from NaN).  So a group's sum is its runs in each
    file's stored order, added over the files: bquery's
    ``sorted_count_distinct`` assumes each file sorted, and counts per file."""
    new = np.ones(len(df), dtype=bool)
    same = file_of[1:] == file_of[:-1]
    for column in list(gcols) + [col]:
        values = df[column].to_numpy()
        same &= values[1:] == values[:-1]
    new[1:] = ~same
    return new


#: the program's aggregation ops (``AGG_OPS`` of ``bqueryd_tpu.models.query``),
#: each to a pandas aggregation of the filtered rows, or to a rule that
#: flags rows (``rule(df, gcols, col, file_of)``, ``file_of`` the index of
#: each row's file among the query's) whose flags the group sums
AGGS = {"sum": "sum", "mean": "mean", "count": "count", "min": "min", "max": "max",
        "count_na": _count_na, "count_distinct": _count_distinct,
        "sorted_count_distinct": _sorted_count_distinct}
#: what the control passes through from the exact reference: nothing is
#: accumulated, so no precision applies
CONTROL_EXACT = ("count", "count_na", "count_distinct", "sorted_count_distinct",
                 "min", "max")


def unanswerable(config):
    """``[(query, "op" | "filter operator", name)]`` for every name in the
    configuration's queries, and in its slot's term, that the reference
    has no rule for."""
    out = []
    for name, query in config["queries"].items():
        out += [(name, "op", op) for _col, op, _out in query["aggs"] if op not in AGGS]
        out += [(name, "filter operator", op) for _col, op, _value in query["where"]
                if op not in OPS]
    slot = config.get("slot")
    if slot and slot["op"] not in OPS:
        out.append(("the slot", "filter operator", slot["op"]))
    return out


class Reference:
    """Holds the seeded frames once; answers any (files, keys, aggs, where)."""

    def __init__(self, frames_by_name):
        self.frames = frames_by_name
        self._concat = {}

    def _frame(self, files, columns):
        """The files' rows in the columns asked, one after another, and
        where each file's rows start."""
        key = (tuple(files), tuple(columns))
        if key not in self._concat:
            if len(self._concat) > 8:
                self._concat.clear()
            parts = [self.frames[f][list(columns)] for f in files]
            self._concat[key] = (
                pd.concat(parts, ignore_index=True),
                np.cumsum([0] + [len(p) for p in parts[:-1]]),
            )
        return self._concat[key]

    def answer(self, args, accumulate=None):
        """``accumulate="float32"`` is the control: every sum and mean taken
        as a float32 running sum and its differences at the group borders
        (a prefix-diff in the next precision down), which breaks the
        bit-for-bit and true-mean guarantees."""
        files, gcols, aggs, where = args
        columns = list(dict.fromkeys(
            list(gcols) + [a[0] for a in aggs] + [w[0] for w in where]
        ))
        df, starts = self._frame(files, columns)
        for col, op, value in where:
            df = df[OPS[op](df[col].to_numpy(), value)]
        rules = [(col, AGGS[op], out) for col, op, out in aggs]
        flagged = [(col, rule, out) for col, rule, out in rules if not isinstance(rule, str)]
        if flagged:
            df = df[df[list(gcols)].notna().all(axis=1)]   # a null key is in no group
            file_of = np.searchsorted(starts, df.index.to_numpy(), side="right") - 1
            df = df.assign(**{
                "flags:" + out: rule(df, gcols, col, file_of).astype(np.int64)
                for col, rule, out in flagged
            })
        named = {out: (col, rule) if isinstance(rule, str) else ("flags:" + out, "sum")
                 for col, rule, out in rules}
        exact = df.groupby(list(gcols), as_index=False).agg(**named)
        if accumulate is None:
            return exact
        return _lower_precision_groupby(df, gcols, aggs, np.dtype(accumulate), exact)


def _lower_precision_groupby(df, gcols, aggs, dtype, exact):
    df = df.sort_values(list(gcols), kind="stable")
    keys = df[list(gcols)].to_numpy()
    first = np.ones(len(df), dtype=bool)
    first[1:] = (keys[1:] != keys[:-1]).any(axis=1)
    starts = np.flatnonzero(first)
    ends = np.append(starts[1:], len(df)) - 1
    out = {c: df[c].to_numpy()[starts] for c in gcols}
    counts = (ends - starts + 1).astype(np.int64)
    for col, op, name in aggs:
        if op in CONTROL_EXACT:
            out[name] = exact[name].to_numpy()
            continue
        source = df[col].to_numpy()
        running = np.cumsum(source.astype(dtype), dtype=dtype)
        before = np.where(starts > 0, running[np.maximum(starts - 1, 0)], dtype.type(0))
        sums = running[ends] - before
        if op == "sum":
            out[name] = sums.astype(source.dtype) if source.dtype.kind == "i" else sums
        elif op == "mean":
            out[name] = sums.astype(np.float64) / counts
        else:
            raise ValueError(f"the control has no rule for the op {op!r}")
    return pd.DataFrame(out)


def compare(args, got, expected, column_dtypes):
    """Numbers for one answer against the reference's."""
    _files, gcols, aggs, _where = args
    numbers = {"int_mismatch": 0, "f32_mean_rel": 0.0, "f64_mean_rel": 0.0}
    if got is None:
        return dict(numbers, unanswered=1)
    numbers["unanswered"] = 0
    gcols = list(gcols)
    if len(got) != len(expected) or not set(gcols) <= set(got.columns):
        numbers["int_mismatch"] = max(len(got), len(expected), 1)
        return numbers
    got = got.sort_values(gcols).reset_index(drop=True)
    expected = expected.sort_values(gcols).reset_index(drop=True)
    for col in gcols + [out for _in, _op, out in aggs]:
        if col not in got.columns:
            numbers["int_mismatch"] += len(expected)
            continue
        g, e = got[col].to_numpy(), expected[col].to_numpy()
        if e.dtype.kind in "iuM":
            if g.dtype.kind not in ("iu" if e.dtype.kind in "iu" else "M"):
                numbers["int_mismatch"] += len(e)
            else:
                numbers["int_mismatch"] += int(np.count_nonzero(g != e))
            continue
        source = next(c for c, _op, out in aggs if out == col)
        which = "f64_mean_rel" if column_dtypes[source] == "float64" else "f32_mean_rel"
        g = g.astype(np.float64)
        if not np.isfinite(g).all():
            numbers[which] = float("inf")
            continue
        scale = np.maximum(np.abs(e), 1e-300)
        numbers[which] = max(numbers[which], float(np.max(np.abs(g - e) / scale)))
    return numbers


def worst(all_numbers):
    """The widest reading of each number over the compared answers."""
    out = {"int_mismatch": 0, "unanswered": 0, "f32_mean_rel": 0.0, "f64_mean_rel": 0.0}
    for numbers in all_numbers:
        out["int_mismatch"] += numbers["int_mismatch"]
        out["unanswered"] += numbers["unanswered"]
        for k in ("f32_mean_rel", "f64_mean_rel"):
            out[k] = max(out[k], numbers[k])
    return out


def verdict(numbers, limits):
    """``(correct, [[name, number, limit], ...])``; a number that is not
    finite fails."""
    rows = [[k, numbers[k], limits[k]] for k in sorted(limits)]
    return all(v == v and v <= lim for _k, v, lim in rows), rows
