"""The plain reference and the comparison that decides ``correct``.

A copy of ``chip_smoke.reference_answer``: the same query as a pandas
groupby over the same seeded frames.  It imports nothing of ``bqueryd_tpu``
and takes nothing the program made.  ``compare`` returns numbers, each held
against a limit of its own (the configuration's ``check_limits``):

``int_mismatch``  group keys and int64 aggregates that are not bit for bit
                  the reference's (a missing or extra group counts too)
``f32_mean_rel``  widest relative gap of a mean over a float32 column
``f64_mean_rel``  the same over a float64 column
``unanswered``    sampled queries of the window that never got an answer
"""

import numpy as np
import pandas as pd

OPS = {">": np.greater, ">=": np.greater_equal, "<": np.less,
       "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}


class Reference:
    """Holds the seeded frames once; answers any (files, keys, aggs, where)."""

    def __init__(self, frames_by_name):
        self.frames = frames_by_name
        self._concat = {}

    def _frame(self, files, columns):
        key = (tuple(files), tuple(columns))
        if key not in self._concat:
            if len(self._concat) > 8:
                self._concat.clear()
            self._concat[key] = pd.concat(
                [self.frames[f][list(columns)] for f in files], ignore_index=True
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
        df = self._frame(files, columns)
        for col, op, value in where:
            df = df[OPS[op](df[col].to_numpy(), value)]
        if accumulate is None:
            named = {out: (col, op) for col, op, out in aggs}
            return df.groupby(list(gcols), as_index=False).agg(**named)
        return _lower_precision_groupby(df, gcols, aggs, np.dtype(accumulate))


def _lower_precision_groupby(df, gcols, aggs, dtype):
    df = df.sort_values(list(gcols), kind="stable")
    keys = df[list(gcols)].to_numpy()
    first = np.ones(len(df), dtype=bool)
    first[1:] = (keys[1:] != keys[:-1]).any(axis=1)
    starts = np.flatnonzero(first)
    ends = np.append(starts[1:], len(df)) - 1
    out = {c: df[c].to_numpy()[starts] for c in gcols}
    counts = (ends - starts + 1).astype(np.int64)
    for col, op, name in aggs:
        if op == "count":
            out[name] = counts
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
            raise ValueError(f"the control has no operator {op!r}")
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
        if e.dtype.kind in "iu":
            if g.dtype.kind not in "iu":
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
