"""The seven per-layer metrics that read the controller's and the client's
own spans (PR 40): each is a data file over ``span_self_time``, a reader
the benchmark had.  A rehearsed traced run on the CPU backend gives each a
number; the named controller children of the ``groupby`` root never
overlap, so with ``controller_unnamed_ms`` they sum to ``controller_ms``;
evidence from a program without the spans (the parent) reads 0.0, or for
``controller_unnamed_ms`` the parent's root less what it names (below zero
there: the parent's send -> reply window is a ``dispatch`` span that holds
the worker's calc), and raises nothing."""

import json
import os

import pandas as pd
import pytest
from test_perf_benchmark import HEAVY, REPO, rehearse, time_limit  # noqa: F401

from benchmark import harness, readers

HOST_METRICS = {
    "controller_decode_ms": ("controller", "request_decode"),
    "controller_absorb_ms": ("controller", "reply_absorb"),
    "controller_encode_ms": ("controller", "reply_encode"),
    "controller_finalize_ms": ("controller", "finalize"),
    "controller_unnamed_ms": ("controller", "groupby"),
    "client_encode_ms": ("client", "client_encode"),
    "client_decode_ms": ("client", "client_decode"),
}
CELLS = ["taxi-1chip.adhoc-heavy", "taxi-4chip.adhoc-heavy", "taxi-1chip-dollars.adhoc-dollars"]
#: the controller's own children of the groupby root (the worker's calc
#: is the rest of what ``controller_ms`` takes out)
CHILDREN = ("admission", "batch_window", "dispatch", "reply_absorb", "reply_encode")


def _metric(name):
    with open(os.path.join(REPO, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced rehearsal of the heavy cell, children at the program's
    defaults, with the evidence its readers read."""
    patch = pytest.MonkeyPatch()
    for name in ("BQUERYD_TPU_SERVE", "BQUERYD_TPU_HOST_KERNEL_ROWS",
                 "BQUERYD_TPU_FORCE_MATMUL", "JAX_COMPILATION_CACHE_DIR"):
        patch.delenv(name, raising=False)
    seen = []
    read = readers.read
    patch.setattr(readers, "read", lambda m, ev: (seen.append(ev), read(m, ev))[1])
    pd.set_option("future.infer_string", False)
    try:
        result = rehearse(tmp_path_factory.mktemp("host_spans"), HEAVY, trace=True)
    finally:
        patch.undo()
    return json.loads(json.dumps(result)), seen[0]


@pytest.mark.parametrize("name", sorted(HOST_METRICS))
def test_a_traced_rehearsal_gives_the_metric_a_number(traced, name):
    result, _ev = traced
    assert result["correct"] is True and result["failed"] == 0
    metric = result["metrics"][name]
    assert metric["unit"] == "ms" and metric["value"] >= 0.0
    if name not in ("controller_unnamed_ms", "controller_finalize_ms"):
        assert metric["value"] > 0.0   # the span was there to read


def test_the_named_children_and_the_remainder_make_up_controller_ms(traced):
    result, ev = traced
    values = {k: v["value"] for k, v in result["metrics"].items()}
    children = sum(
        readers.span_self_time(ev, span) for span in CHILDREN
    )
    calc = readers.span_self_time(ev, "calc")
    assert children + values["controller_unnamed_ms"] == pytest.approx(
        values["controller_ms"], abs=0.01)
    assert values["controller_ms"] == pytest.approx(
        readers.span_self_time(ev, "groupby") - calc, abs=0.01)


def test_the_children_of_every_query_do_not_overlap(traced):
    _result, ev = traced
    timelines = [t for _r, t in readers._traced(ev)]
    assert timelines
    for timeline in timelines:
        spans = sorted(
            (s["start_ts"], s["start_ts"] + s["duration_s"]) for s in timeline["spans"]
            if s["name"] in CHILDREN + ("calc",)
        )
        for (_lo, hi), (next_lo, _next_hi) in zip(spans, spans[1:]):
            assert hi <= next_lo + 2e-6   # spans round to the microsecond
        (root,) = [s for s in timeline["spans"] if s["name"] == "groupby"]
        unnamed = root["duration_s"] - sum(hi - lo for lo, hi in spans)
        assert unnamed >= -len(spans) * 2e-6


@pytest.mark.parametrize("name", sorted(HOST_METRICS))
def test_a_program_without_the_spans_reads_zero_and_raises_nothing(name):
    metric = _metric(name)
    layer, span = HOST_METRICS[name]
    assert metric["reader"] == "span_self_time" and metric["args"]["span"] == span
    entry = next(m for m in harness.load_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]
                 if m["name"] == name)
    assert entry["layer"] == metric["layer"] == layer
    assert entry["moves"] == "query_ms" and entry["workloads"] == CELLS
    assert entry["source"] == metric["source"] == "program_span"
    # a timeline from the parent: send -> reply as a "dispatch" window
    parent_spans = [
        {"name": "groupby", "duration_s": 0.050}, {"name": "admission", "duration_s": 0.001},
        {"name": "plan", "duration_s": 0.0005}, {"name": "dispatch", "duration_s": 0.002},
        {"name": "dispatch", "duration_s": 0.040}, {"name": "calc", "duration_s": 0.038},
    ]
    ev = {"records": [{"ok": True, "trace_id": "t", "wall_s": 0.053}],
          "traces": {"t": {"spans": parent_spans}}}
    value = readers.read(metric, ev)
    if name == "controller_unnamed_ms":
        assert value == pytest.approx(1000 * (0.050 - 0.038 - 0.001 - 0.042))
    else:
        assert value == 0.0
    assert readers.read(metric, {"records": []}) is None
