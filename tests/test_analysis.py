"""Static-analysis suite tests: framework semantics, one injected violation
per analyzer family, the runtime lock-order recorder (ABBA fixture + real
pipeline/worker-path locks), and the tier-1 gate that the shipped tree is
clean."""

import json
import os
import subprocess
import sys
import threading

import pytest

from bqueryd_tpu.analysis import default_analyzers, run_suite
from bqueryd_tpu.analysis.concurrency import LockDisciplineAnalyzer
from bqueryd_tpu.analysis.configreg import (
    ENV_REGISTRY,
    ConfigRegistryAnalyzer,
    EnvVar,
    registry_markdown_rows,
)
from bqueryd_tpu.analysis.core import (
    Finding,
    Project,
    load_baseline,
    parse_suppressions,
    run_suite as core_run_suite,
)
from bqueryd_tpu.analysis.lockorder import (
    LockOrderError,
    LockOrderRecorder,
)
from bqueryd_tpu.analysis.metricslint import (
    MetricNameAnalyzer,
    MetricReadmeAnalyzer,
)
from bqueryd_tpu.analysis.purity import JitPurityAnalyzer
from bqueryd_tpu.analysis.wire import WireSchemaAnalyzer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_project(tmp_path, files, readme="(no config table)"):
    """A throwaway project tree: ``files`` maps package-relative paths to
    source text."""
    pkg = tmp_path / "bqueryd_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, text in files.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        if path.parent != pkg and not (
            path.parent / "__init__.py"
        ).exists():
            (path.parent / "__init__.py").write_text("")
        path.write_text(text)
    (tmp_path / "README.md").write_text(readme)
    return Project(str(tmp_path))


def rules_of(result):
    return {f.rule for f in result.new}


# -- framework ---------------------------------------------------------------

def test_pragma_requires_reason_and_rule():
    sups, problems = parse_suppressions(
        "x = 1  # bqtpu: allow[some-rule] measured, tolerable\n"
        "y = 2  # bqtpu: allow[other-rule]\n"
        "z = 3  # bqtpu: allow[]\n"
    )
    assert len(sups) == 1 and sups[0].rules == ("some-rule",)
    assert sups[0].reason == "measured, tolerable"
    assert len(problems) == 2


def test_pragma_in_docstring_is_not_a_pragma():
    sups, problems = parse_suppressions(
        '"""docs show the syntax: # bqtpu: allow[rule-id] reason"""\n'
    )
    assert sups == [] and problems == []


def test_pragma_suppresses_same_line_and_standalone_previous_line(tmp_path):
    project = make_project(tmp_path, {
        "mod.py": (
            "import os\n"
            "# bqtpu: allow[config-unregistered-env] test fixture var\n"
            'A = os.environ.get("BQUERYD_TPU_FIXTURE_ONLY")\n'
            'B = os.environ.get("BQUERYD_TPU_FIXTURE_TWO")'
            "  # bqtpu: allow[config-unregistered-env] also a fixture\n"
            'C = os.environ.get("BQUERYD_TPU_FIXTURE_THREE")\n'
        ),
    })
    reg = {
        v.name: v for v in [EnvVar(
            "BQUERYD_TPU_FIXTURE_THREE", "str", "-", "x")]
    }
    result = core_run_suite(
        project=project, analyzers=[ConfigRegistryAnalyzer(registry=reg)],
    )
    suppressed_rules = {f.rule for f, _reason in result.suppressed}
    assert "config-unregistered-env" in suppressed_rules
    assert len(result.suppressed) == 2
    # the third read is registered; remaining findings are doc/readme ones
    assert "config-unregistered-env" not in rules_of(result)


def test_unknown_rule_pragma_is_a_finding(tmp_path):
    project = make_project(tmp_path, {
        "mod.py": "x = 1  # bqtpu: allow[no-such-rule] because reasons\n",
    })
    result = core_run_suite(project=project, analyzers=[])
    assert "analysis-unknown-rule" in rules_of(result)


def test_baseline_grandfathers_and_stale_entries_flag(tmp_path):
    files = {
        "mod.py": 'import os\nA = os.environ.get("BQUERYD_TPU_LEGACY_X")\n',
    }
    project = make_project(tmp_path, files)
    analyzer = ConfigRegistryAnalyzer(registry={})
    result = core_run_suite(project=project, analyzers=[analyzer])
    (unmatched,) = [
        f for f in result.new if f.rule == "config-unregistered-env"
    ]

    baseline = tmp_path / "ANALYSIS_BASELINE.json"
    baseline.write_text(json.dumps({
        unmatched.fingerprint: "grandfathered: pre-registry legacy knob",
    }))
    result2 = core_run_suite(
        project=project, analyzers=[analyzer],
        baseline_path=str(baseline),
    )
    assert "config-unregistered-env" not in rules_of(result2)
    assert any(
        f.fingerprint == unmatched.fingerprint
        for f, _ in result2.baselined
    )

    # a baseline entry matching nothing is itself a finding
    baseline.write_text(json.dumps({"bogus:rule:path": "stale"}))
    result3 = core_run_suite(
        project=project, analyzers=[ConfigRegistryAnalyzer(registry={
            "BQUERYD_TPU_LEGACY_X": EnvVar(
                "BQUERYD_TPU_LEGACY_X", "str", "-", "x"),
        })],
        baseline_path=str(baseline),
    )
    assert "analysis-stale-baseline" in rules_of(result3)


def test_unused_pragma_is_a_finding(tmp_path):
    """A pragma whose finding was fixed must not linger (same only-shrinks
    contract as the baseline)."""
    project = make_project(tmp_path, {
        "mod.py": (
            "# bqtpu: allow[config-unregistered-env] nothing here anymore\n"
            "x = 1\n"
        ),
    })
    result = core_run_suite(
        project=project, analyzers=[ConfigRegistryAnalyzer(registry={})],
    )
    assert "analysis-unused-pragma" in rules_of(result)
    # but not when the family that owns the rule sat the run out
    result2 = core_run_suite(project=project, analyzers=[])
    assert "analysis-unused-pragma" not in rules_of(result2)


def test_fingerprint_is_line_independent():
    a = Finding("r", "p.py", 10, "msg", symbol="sym")
    b = Finding("r", "p.py", 99, "different msg", symbol="sym")
    assert a.fingerprint == b.fingerprint


# -- config registry ---------------------------------------------------------

def test_config_family_detects_each_violation(tmp_path):
    project = make_project(tmp_path, {
        "mod.py": (
            "import os\n"
            'A = os.environ.get("BQUERYD_TPU_UNKNOWN_KNOB")\n'     # unregistered
            'B = os.environ.get("SOMEONE_ELSES_VAR")\n'            # external
            'C = os.environ.get("BQUERYD_TPU_LIVE_KNOB")\n'        # import-read
            "def f(name):\n"
            "    return os.environ.get(name)\n"                    # dynamic
        ),
    }, readme="documents BQUERYD_TPU_GHOST_VAR only")
    registry = {v.name: v for v in [
        EnvVar("BQUERYD_TPU_LIVE_KNOB", "int", "1", "live", "call"),
        EnvVar("BQUERYD_TPU_DEAD_KNOB", "int", "1", "dead", "call"),
        EnvVar("BQUERYD_TPU_TRACE_THING", "int", "1", "a", "call"),
        EnvVar("BQUERYD_TPU_TRACE_THING_BYTES", "int", "1", "b", "call"),
    ]}
    result = core_run_suite(
        project=project,
        analyzers=[ConfigRegistryAnalyzer(registry=registry)],
    )
    got = rules_of(result)
    assert {
        "config-unregistered-env", "config-external-env",
        "config-import-time-read", "config-dynamic-env-key",
        "config-dead-var", "config-undocumented", "config-readme-unknown",
        "config-name-collision",
    } <= got


def test_config_doc_and_dead_checks_match_exact_tokens(tmp_path):
    """Substring matching would let FOO hide inside FOO_BYTES — the exact
    near-collision pairs the registry polices.  The README documenting (and
    the source referencing) only the longer sibling must still flag the
    shorter one."""
    project = make_project(tmp_path, {
        "mod.py": (
            "import os\n"
            'A = os.environ.get("BQUERYD_TPU_RING_BYTES")\n'
        ),
    }, readme="| `BQUERYD_TPU_RING_BYTES` | 16 MiB | byte cap |")
    registry = {v.name: v for v in [
        EnvVar("BQUERYD_TPU_RING", "int", "256", "entry cap", "call",
               related=("BQUERYD_TPU_RING_BYTES",)),
        EnvVar("BQUERYD_TPU_RING_BYTES", "int", "16 MiB", "byte cap",
               "call", related=("BQUERYD_TPU_RING",)),
    ]}
    result = core_run_suite(
        project=project,
        analyzers=[ConfigRegistryAnalyzer(registry=registry)],
    )
    undocumented = {
        f.symbol for f in result.new if f.rule == "config-undocumented"
    }
    dead = {f.symbol for f in result.new if f.rule == "config-dead-var"}
    assert undocumented == {"BQUERYD_TPU_RING"}
    assert dead == {"BQUERYD_TPU_RING"}


def test_registry_markdown_rows_cover_every_var():
    rows = registry_markdown_rows()
    assert len(rows) == len(ENV_REGISTRY)
    for name in ENV_REGISTRY:
        assert any(name in row for row in rows)


def test_trace_buffer_near_collision_is_reconciled():
    """The TRACE_BUFFER (entries) vs TRACE_BUFFER_BYTES near-collision: both
    registered, cross-referenced, with help text that distinguishes the
    entry cap from the byte cap."""
    entries = ENV_REGISTRY["BQUERYD_TPU_TRACE_BUFFER"]
    byts = ENV_REGISTRY["BQUERYD_TPU_TRACE_BUFFER_BYTES"]
    assert "BQUERYD_TPU_TRACE_BUFFER_BYTES" in entries.related
    assert "BQUERYD_TPU_TRACE_BUFFER" in byts.related
    assert "ENTRY-COUNT" in entries.help and "BYTE" in byts.help


# -- lock discipline ---------------------------------------------------------

LOCKED_CLASS = """
import threading


class Box:
    _bqtpu_guarded_ = {"_lock": ("_data", "_count")}

    def __init__(self):
        self._lock = threading.Lock()
        self._data = {}
        self._count = 0

    def ok(self):
        with self._lock:
            self._count += 1
            return dict(self._data)

    def _drop_locked(self):
        self._data.clear()

    def racy(self):
        self._count += 1          # unguarded write

    def racy_helper(self):
        self._drop_locked()       # *_locked called lock-free
"""


def test_lock_discipline_flags_unguarded_and_helper(tmp_path):
    project = make_project(tmp_path, {"mod.py": LOCKED_CLASS})
    result = core_run_suite(
        project=project, analyzers=[LockDisciplineAnalyzer()],
    )
    by_rule = {}
    for f in result.new:
        by_rule.setdefault(f.rule, []).append(f)
    (unguarded,) = by_rule["lock-unguarded-attr"]
    assert unguarded.symbol == "Box.racy._count"
    (helper,) = by_rule["lock-helper-outside-lock"]
    assert "racy_helper" in helper.symbol


def test_lock_discipline_multi_item_with(tmp_path):
    """``with self._lock, ctx(self._data):`` holds the lock while the second
    context expression evaluates — no false finding."""
    project = make_project(tmp_path, {"mod.py": (
        "import threading\n"
        "class Box:\n"
        "    _bqtpu_guarded_ = {\"_lock\": (\"_data\",)}\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._data = {}\n"
        "    def both(self, ctx):\n"
        "        with self._lock, ctx(self._data):\n"
        "            return len(self._data)\n"
    )})
    result = core_run_suite(
        project=project, analyzers=[LockDisciplineAnalyzer()],
    )
    assert "lock-unguarded-attr" not in rules_of(result)


def test_lock_discipline_nonliteral_declaration_fails_loudly(tmp_path):
    """Refactoring the declaration into a computed value must be a finding,
    never a silent loss of checking for the whole class."""
    project = make_project(tmp_path, {"mod.py": (
        "ATTRS = (\"_x\",)\n"
        "class Box:\n"
        "    _bqtpu_guarded_ = {\"_lock\": ATTRS}\n"
        "    def racy(self):\n"
        "        return self._x\n"
    )})
    result = core_run_suite(
        project=project, analyzers=[LockDisciplineAnalyzer()],
    )
    assert "lock-bad-declaration" in rules_of(result)


def test_lock_discipline_missing_lock_attr(tmp_path):
    project = make_project(tmp_path, {"mod.py": (
        "class Odd:\n"
        "    _bqtpu_guarded_ = {\"_ghost_lock\": (\"_x\",)}\n"
        "    def get(self):\n"
        "        return 1\n"
    )})
    result = core_run_suite(
        project=project, analyzers=[LockDisciplineAnalyzer()],
    )
    assert "lock-missing-lock-attr" in rules_of(result)


# -- jit purity ---------------------------------------------------------------

IMPURE_JIT = """
import functools
import os
import time

import jax
import numpy as np


@functools.partial(jax.jit, static_argnames=("n",))
def impure(x, n):
    t = time.time()
    e = os.environ.get("BQUERYD_TPU_METRICS")
    if x > 0:
        y = float(x)
    z = np.asarray(x)
    return x + n


def caller():
    return impure(1.0, n=[1, 2])


def outer():
    big = [1, 2, 3]

    @functools.lru_cache(maxsize=8)
    def closure_cache(k):
        return big[k]

    return closure_cache
"""

PURE_JIT = """
import functools

import jax
import jax.numpy as jnp

from bqueryd_tpu.obs import profile as _obsprofile


@functools.partial(jax.jit, static_argnames=("n_groups",))
def clean(codes, n_groups, mask=None):
    valid = codes >= 0
    if mask is not None:
        valid = valid & mask
    return jnp.where(valid, codes, 0).astype(jnp.int32)


clean = _obsprofile.instrument("ops.clean", clean)
"""


def test_purity_family_detects_each_violation(tmp_path):
    project = make_project(tmp_path, {"ops/kern.py": IMPURE_JIT})
    result = core_run_suite(project=project, analyzers=[JitPurityAnalyzer()])
    got = rules_of(result)
    assert {
        "jit-impure-time", "jit-impure-env", "jit-traced-branch",
        "jit-traced-coerce", "jit-host-numpy", "jit-nonhashable-static",
        "jit-lru-closure", "jit-uninstrumented",
    } <= got


def test_purity_clean_idioms_pass(tmp_path):
    """static-arg branches, `is None` structure checks, and instrumented
    entry points produce no findings."""
    project = make_project(tmp_path, {"ops/kern.py": PURE_JIT})
    result = core_run_suite(project=project, analyzers=[JitPurityAnalyzer()])
    assert rules_of(result) == set()


def test_purity_static_argnums_resolved_positionally(tmp_path):
    """Branching on a positionally-static parameter is legal; branching on
    the traced one still flags."""
    project = make_project(tmp_path, {"ops/kern.py": (
        "import functools\n"
        "import jax\n"
        "from bqueryd_tpu.obs import profile as _p\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def f(x, n):\n"
        "    if n > 4:\n"          # static: fine
        "        return x\n"
        "    if x > 0:\n"          # traced: finding
        "        return x + n\n"
        "    return x\n"
        "f = _p.instrument('ops.f', f)\n"
    )})
    result = core_run_suite(project=project, analyzers=[JitPurityAnalyzer()])
    branches = [f for f in result.new if f.rule == "jit-traced-branch"]
    assert len(branches) == 1 and branches[0].symbol == "f.if.x"


# -- wire schema --------------------------------------------------------------

def test_wire_family_detects_each_violation(tmp_path):
    project = make_project(tmp_path, {
        "controller.py": (
            "def handle(msg):\n"
            "    msg[\"brand_new_key\"] = 1\n"       # undeclared
            "    msg[\"sole_payload\"] = True\n"     # written, never read here
            "    return msg.get(\"payload\")\n"
        ),
        "worker.py": "def noop(msg):\n    msg[\"payload\"] = \"ok\"\n",
        "rpc.py": "",
    })
    result = core_run_suite(project=project, analyzers=[WireSchemaAnalyzer()])
    got = rules_of(result)
    assert "wire-undeclared-key" in got
    assert "wire-one-sided-key" in got       # sole_payload written, never read
    assert "wire-dead-key" in got            # e.g. token: declared, untouched
    assert any(
        f.rule == "wire-undeclared-key" and f.symbol == "brand_new_key"
        for f in result.new
    )


def test_wire_result_envelope_anchored_on_pickle_dumps(tmp_path):
    """Bookkeeping dicts sharing a result-schema key ('busy', 'error') must
    NOT count as envelope writes; only the pickled dict does — and an
    undeclared key inside a pickled envelope is flagged."""
    project = make_project(tmp_path, {
        "controller.py": (
            "import pickle\n"
            "def bookkeeping():\n"
            "    info = {\"busy\": False, \"error\": None}\n"   # not wire
            "    return info\n"
            "def reply_ok(payloads):\n"
            "    return pickle.dumps({\"ok\": True, \"payloads\": payloads,"
            " \"timings\": {}, \"sneaky\": 1})\n"
        ),
        "worker.py": "",
        "rpc.py": (
            "import pickle\n"
            "def parse(raw):\n"
            "    envelope = pickle.loads(raw)\n"
            "    if envelope.get(\"busy\"):\n"
            "        raise RuntimeError(envelope.get(\"error\"))\n"
            "    return envelope[\"payloads\"], envelope.get(\"timings\")\n"
        ),
    })
    result = core_run_suite(project=project, analyzers=[WireSchemaAnalyzer()])
    assert any(
        f.rule == "wire-undeclared-key" and f.symbol == "sneaky"
        for f in result.new
    )
    one_sided = {
        f.symbol for f in result.new if f.rule == "wire-one-sided-key"
    }
    # 'busy'/'error' are READ here but their only "writes" are the
    # bookkeeping dict, which must not count -> one-sided reads; 'ok'
    # written-only likewise; payloads/timings are two-sided
    assert {"busy", "error", "ok"} <= one_sided
    assert "payloads" not in one_sided and "timings" not in one_sided


def test_wire_schema_covers_shipped_tree():
    """The real controller/worker/rpc trio against the declared schema: the
    gate that catches a one-sided key at review time."""
    project = Project(REPO_ROOT)
    result = core_run_suite(
        project=project, analyzers=[WireSchemaAnalyzer()],
        baseline_path=os.path.join(REPO_ROOT, "ANALYSIS_BASELINE.json"),
    )
    assert [f.render() for f in result.new] == []


# -- migrated metric lints ----------------------------------------------------

def test_metric_lints_detect_violations(tmp_path):
    project = make_project(tmp_path, {
        "m.py": (
            "def setup(reg):\n"
            "    reg.counter(\"Bad-Name\", \"help text\")\n"
            "    reg.gauge(\"bqueryd_tpu_thing\", \"\")\n"
        ),
    }, readme="no metrics table at all")
    result = core_run_suite(
        project=project,
        analyzers=[MetricNameAnalyzer(), MetricReadmeAnalyzer()],
    )
    got = rules_of(result)
    assert {
        "metric-name-format", "metric-missing-help",
        "metric-readme-coverage",
    } <= got


def test_runtime_metric_lint_entry_points_still_work():
    """The originals the analyzers migrated from keep their contracts."""
    from bqueryd_tpu.obs.metrics import (
        MetricsRegistry,
        readme_coverage_problems,
    )

    reg = MetricsRegistry()
    reg.counter("bqueryd_tpu_ok_total", "fine")
    assert reg.lint() == []
    assert readme_coverage_problems([reg], "bqueryd_tpu_ok_total") == []
    assert readme_coverage_problems([reg], "nothing here") != []


# -- lock-order recorder ------------------------------------------------------

def test_lockorder_abba_cycle_detected_with_sites():
    recorder = LockOrderRecorder()
    a = recorder.lock("A")
    b = recorder.lock("B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=ba)
    t2.start()
    t2.join()

    cycles = recorder.cycles()
    assert cycles and set(cycles[0]) == {"A", "B"}
    report = recorder.report()
    # the report names BOTH acquisition sites of both edges
    assert "lock-order cycle: A -> B -> A" in report
    assert report.count(__file__) == 4
    assert "while holding" in report
    with pytest.raises(LockOrderError):
        recorder.assert_no_cycles()


def test_lockorder_reports_both_orientations_over_same_locks():
    """A->B->C->A and A->C->B->A are distinct deadlock orderings with
    distinct witness sites — node-set dedup would hide the second."""
    recorder = LockOrderRecorder()
    a, b, c = (recorder.lock(n) for n in "ABC")
    for first, second, third in ((a, b, c), (a, c, b)):
        with first:
            with second:
                with third:
                    pass
    # edges: A->B, A->C, B->C, C->B  =>  cycles B->C->B plus both
    # three-node orientations if closed; at minimum the 2-cycle plus
    # every distinct ordered cycle is present exactly once
    cycles = {tuple(cyc) for cyc in recorder.cycles()}
    assert ("B", "C") in cycles or ("C", "B") in cycles
    assert len(cycles) == len(recorder.cycles())  # no duplicates


def test_lockorder_consistent_order_is_clean():
    recorder = LockOrderRecorder()
    a = recorder.lock("A")
    b = recorder.lock("B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert recorder.cycles() == []
    recorder.assert_no_cycles()


def test_lockorder_self_deadlock_raises():
    recorder = LockOrderRecorder()
    a = recorder.lock("A")
    with a:
        with pytest.raises(LockOrderError, match="self-deadlock"):
            a.acquire()


def test_lockorder_real_pipeline_and_worker_paths_run_clean():
    """Drive the PR-4 concurrency surface — shared caches, working set,
    stage clocks, metrics registry, flight ring — under instrumented locks
    from several threads and prove the acquisition graph is acyclic."""
    from bqueryd_tpu.obs.flightrec import FlightRecorder
    from bqueryd_tpu.obs.metrics import MetricsRegistry
    from bqueryd_tpu.ops.workingset import WorkingSet
    from bqueryd_tpu.parallel import pipeline
    from bqueryd_tpu.utils.cache import BytesCappedCache

    recorder = LockOrderRecorder()
    cache = BytesCappedCache(1 << 16, sizeof=len)
    ws = WorkingSet(budgets={"align": 1 << 14, "codes": 1 << 14,
                             "blocks": 1 << 14})
    registry = MetricsRegistry()
    counter = registry.counter("bqueryd_tpu_lockorder_test_total", "t")
    hist = registry.histogram("bqueryd_tpu_lockorder_test_seconds", "t")
    flight = FlightRecorder(node_id="t", capacity=64, max_bytes=1 << 14)
    clock = pipeline.StageClock()

    assert recorder.instrument_object(cache)
    recorder.instrument_object(ws)
    for name in ("align", "codes", "blocks"):
        recorder.instrument_object(ws.segment(name), prefix=f"ws.{name}")
    recorder.instrument_object(registry)
    recorder.instrument_object(counter, prefix="Counter")
    recorder.instrument_object(hist, prefix="Histogram")
    recorder.instrument_object(flight)
    recorder.instrument_object(clock, prefix="StageClock")

    sample = {"bytes_in_use": 10 * (1 << 14), "bytes_limit": 1 << 14}

    def storm(seed):
        for i in range(50):
            key = f"k{(seed * 50 + i) % 17}"
            cache.put(key, b"x" * 100)
            cache.get(key)
            cache.nbytes, len(cache)
            seg = ws.segment(("align", "codes", "blocks")[i % 3])
            seg.put((seed, i % 7), b"y" * 200, nbytes=200)
            seg.get((seed, i % 7))
            ws.stats()
            if i % 10 == 0:
                ws.evict_under_pressure(sample=sample)
            counter.inc()
            hist.observe(0.001 * i)
            registry.render()
            flight.record("rpc", verb="groupby", seq=i)
            flight.tail(8)
            len(flight), flight.nbytes, flight.evictions
            clock.add("decode", 0.001)
            clock.snapshot()

    threads = [threading.Thread(target=storm, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert recorder.acquisitions > 0
    assert recorder.cycles() == [], recorder.report()


def test_lockorder_instrumented_pipeline_map_ordered_clean():
    """The shared stage pool + busy clocks under instrumented module locks:
    the fallback multi-shard worker path's concurrency substrate."""
    from bqueryd_tpu.parallel import pipeline

    recorder = LockOrderRecorder()
    restore_pool = recorder.instrument_module_lock(pipeline, "_pool_lock")
    clock = pipeline.clock()
    clock_locks = dict(vars(clock))
    clock_wrapped = recorder.instrument_object(clock, prefix="StageClock")
    try:
        assert clock_wrapped

        def work(i):
            with pipeline.stage("decode"):
                with pipeline.stage("kernel"):
                    return i * 2

        out = pipeline.map_ordered(work, range(32))
        assert out == [i * 2 for i in range(32)]
        assert recorder.cycles() == [], recorder.report()
    finally:
        restore_pool()
        # the process-wide clock outlives the test: a tracked lock left on
        # it would walk the stack (and stat every frame's file) on every
        # pipeline stage of every later test in this process
        vars(clock).update(clock_locks)


# -- root/readme robustness ---------------------------------------------------

def test_missing_readme_is_one_finding_not_sixty(tmp_path):
    pkg = tmp_path / "bqueryd_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("x = 1\n")
    project = Project(str(tmp_path))        # no README.md written
    result = core_run_suite(project=project)
    assert "analysis-missing-readme" in rules_of(result)
    assert "config-undocumented" not in rules_of(result)
    assert "metric-readme-coverage" not in rules_of(result)


def test_sourceless_root_fails_loudly(tmp_path):
    with pytest.raises(FileNotFoundError, match="source checkout"):
        Project(str(tmp_path))


def test_wire_schema_read_from_analyzed_tree_not_live_import(tmp_path):
    """--root must diff a checkout against ITS OWN messages.py schema."""
    project = make_project(tmp_path, {
        "messages.py": (
            'ENVELOPE_SCHEMA = {"payload": "verb", "custom_key": "theirs"}\n'
            "RESULT_ENVELOPE_SCHEMA = {}\n"
            "WIRE_ONE_SIDED_OK = {}\n"
        ),
        "controller.py": (
            "def handle(msg):\n"
            "    msg[\"custom_key\"] = 1\n"
            "    return msg.get(\"custom_key\"), msg.get(\"payload\"),"
            " msg.get(\"token\")\n"
        ),
        "worker.py": "def f(msg):\n    msg[\"payload\"] = 1\n",
        "rpc.py": "",
    })
    result = core_run_suite(project=project, analyzers=[WireSchemaAnalyzer()])
    # custom_key is declared in THIS tree's schema: no undeclared finding —
    # but 'token' (declared only in the live module) is undeclared here
    undeclared = {
        f.symbol for f in result.new if f.rule == "wire-undeclared-key"
    }
    assert "custom_key" not in undeclared
    assert "token" in undeclared


# -- suite + CLI on the shipped tree -----------------------------------------

def test_shipped_tree_has_zero_gating_findings():
    """THE tier-1 gate: the full suite over the real tree is clean (inline
    suppressions and the checked-in baseline are the only escapes, and the
    baseline must stay near-empty)."""
    result = run_suite(root=REPO_ROOT)
    assert [f.render() for f in result.gating] == []
    baseline = load_baseline(
        os.path.join(REPO_ROOT, "ANALYSIS_BASELINE.json")
    )
    assert len(baseline) <= 3, (
        "the suppression baseline must stay near-empty; fix findings "
        "instead of growing it"
    )
    # every analyzer family actually ran
    assert {
        "config-registry", "lock-discipline", "jit-purity", "wire-schema",
        "metric-lint", "metric-readme",
    } <= set(result.analyzers_run)


def test_suite_runtime_stays_fast():
    import time

    t0 = time.perf_counter()
    run_suite(root=REPO_ROOT)
    assert time.perf_counter() - t0 < 10.0


def test_cli_json_clean_and_violation_exit_codes(tmp_path, capsys):
    from bqueryd_tpu.analysis.__main__ import main

    rc = main(["--format", "json", "--root", REPO_ROOT])
    out = capsys.readouterr().out
    assert rc == 0
    payload = json.loads(out)
    assert payload["schema"] == "bqueryd_tpu.analysis/1"
    assert payload["exit_code"] == 0
    assert payload["findings"] == []
    # counts_by_analyzer is RAW (pre-suppression): the two justified
    # dynamic-env-key pragma sites still show up as having been found
    assert payload["counts_by_analyzer"]["config-registry"] == len(
        payload["suppressed"]
    )

    # an injected violation flips the exit code
    make_project(tmp_path, {
        "mod.py": 'import os\nX = os.environ.get("BQUERYD_TPU_NEW_KNOB")\n',
    })
    rc = main(["--format", "json", "--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    payload = json.loads(out)
    assert payload["exit_code"] == 1
    assert any(
        f["rule"] == "config-unregistered-env" for f in payload["findings"]
    )


def test_cli_list_rules_and_unknown_analyzer(capsys):
    from bqueryd_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("config-unregistered-env", "lock-unguarded-attr",
                 "jit-impure-time", "wire-undeclared-key",
                 "metric-name-format", "analysis-stale-baseline"):
        assert rule in out
    assert main(["--analyzer", "no-such"]) == 2


def test_cli_subprocess_entry_point():
    """`python -m bqueryd_tpu.analysis` is the operator/CI surface."""
    proc = subprocess.run(
        [sys.executable, "-m", "bqueryd_tpu.analysis", "--format", "json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["exit_code"] == 0
