"""The one traffic generator: a mix file and a seed give each stream's
query list.

A mix is data (``traffic/<name>.json``): closed-loop client streams with no
think time, each with its shapes and weights and the share of requests that
repeat a tile as it stands; every other request carries a fresh constant in
the configuration's slot.  Every seed gets the same work in another order:
shapes come in shuffled cycles that hold each shape exactly ``weight`` times
(times four where some repeat, so the repeat share is exact per cycle), and
the constant is drawn stratified over its range, never twice.  Every
``check_every``-th planned query is marked for the check (its answer is
kept); ``check_at_most`` is how many of the kept answers the reference
compares after the window (``harness.chosen``).
"""

import json

import numpy as np

MIX_KEYS = {"name", "why", "streams", "check_every", "check_at_most", "warmup_max_s",
            "settle_allow"}
STREAM_KEYS = {"count", "repeat_share", "shapes"}


def read_mix(path):
    """A mix file; a key the generator does not read is an error, so that
    no mix asks for an open loop or a think time and gets neither, and so
    is a key it reads and the mix leaves out, so that none arrives with an
    uncapped check."""
    with open(path) as f:
        mix = json.load(f)
    missing = MIX_KEYS - set(mix)
    if missing:
        raise ValueError(f"{path}: the mix states no {sorted(missing)}")
    unread = set(mix) - MIX_KEYS
    for stream in mix["streams"]:
        unread |= set(stream) - STREAM_KEYS
    if unread:
        raise ValueError(f"{path}: the generator reads no key {sorted(unread)}")
    return mix


class Query:
    """One planned request."""

    __slots__ = ("shape", "fresh", "value", "args", "rows", "check")

    def __init__(self, shape, fresh, value, args, rows):
        self.shape, self.fresh, self.value = shape, fresh, value
        self.args, self.rows, self.check = args, rows, False


def query_args(config, shape, names, value=None):
    """(filenames, groupby_cols, agg_list, where_terms) of one shape.
    ``value`` fills the slot (appended where the shape has none); without
    it the slot takes its default and a shape without a slot stays bare."""
    q, slot = config["queries"][shape], config["slot"]
    files = list(names[:1] if q["files"] == "first" else names)
    where, filled = [], False
    for col, op, val in q["where"]:
        if val == slot["name"]:
            val, filled = (slot["default"] if value is None else value), True
        where.append([col, op, val])
    if value is not None and not filled:
        where.append([slot["column"], slot["op"], value])
    return files, list(q["groupby"]), [list(a) for a in q["aggs"]], where


class Constants:
    """Distinct values of the slot, stratified: each run of ``strata``
    draws covers the whole range once, and no grid point is used twice.
    ``lane`` 0 is the window's half of the grid and 1 the warm-up's, so no
    window query repeats a warm-up query."""

    def __init__(self, slot, rng, lane=0):
        self.slot, self.rng, self.lane = slot, rng, lane
        self.per = slot["grid"] // slot["strata"] // 2
        self.perms = [rng.permutation(self.per) for _ in range(slot["strata"])]
        self.order, self.drawn = [], 0

    def next(self):
        s = self.slot
        if not self.order:
            self.order = list(self.rng.permutation(s["strata"]))
        stratum = self.order.pop()
        round_ = self.drawn // s["strata"]
        self.drawn += 1
        if round_ >= self.per:
            raise RuntimeError("the slot's grid is used up")
        j = 2 * (stratum * self.per + int(self.perms[stratum][round_])) + self.lane
        # half a grid step off every two-decimal data value
        return round(s["low"] + (s["high"] - s["low"]) * (j + 0.5) / s["grid"], 6)


def stream_plan(config, mix, stream, stream_index, seed, names, rows_of, length):
    """The first ``length`` queries of one client stream."""
    rng = np.random.default_rng([int(seed), 7, int(stream_index)])
    repeat = float(stream.get("repeat_share", 0.0))
    # a cycle: each shape `weight` times, in `block` variants of which
    # `block * repeat` are the tile as it stands
    block = 1 if repeat == 0.0 else int(round(1.0 / (1.0 - repeat)))
    cycle = [
        (entry["shape"], variant >= block * repeat - 1e-9)
        for entry in stream["shapes"]
        for _ in range(int(entry["weight"]))
        for variant in range(block)
    ]
    constants = {e["shape"]: Constants(config["slot"], rng) for e in stream["shapes"]}
    plan = []
    while len(plan) < length:
        for i in rng.permutation(len(cycle)):
            shape, fresh = cycle[i]
            value = constants[shape].next() if fresh else None
            args = query_args(config, shape, names, value)
            plan.append(Query(shape, fresh, value, args, sum(rows_of[f] for f in args[0])))
    every = int(mix["check_every"])
    offset = int(rng.integers(0, every))
    for i, query in enumerate(plan):
        query.check = (i + offset) % every == 0
    return plan[:length]


def plans(config, mix, seed, names, rows_of, length):
    """One plan per client stream of the mix."""
    out = []
    for stream in mix["streams"]:
        for _ in range(int(stream.get("count", 1))):
            out.append(
                stream_plan(config, mix, stream, len(out), seed, names, rows_of, length)
            )
    return out


def shapes_of(mix):
    """The mix's shapes in file order, each with whether it is ever sent
    fresh and ever sent as it stands."""
    seen = {}
    for stream in mix["streams"]:
        repeat = float(stream.get("repeat_share", 0.0))
        for entry in stream["shapes"]:
            fresh, fixed = seen.get(entry["shape"], (False, False))
            seen[entry["shape"]] = (fresh or repeat < 1.0, fixed or repeat > 0.0)
    return seen
