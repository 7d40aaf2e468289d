"""One run of one cell: set-up, the measured window, the check, the line.

The entry the window drives is ``bqueryd_tpu.RPC(...).groupby(...)`` from
this JAX-free process to a controller and one calc worker that owns every
local chip, at the program's deployment defaults.  A cell is data: its
configuration, its traffic mix and its per-layer metrics are files found
by the names in ``BENCHMARK.json``.
"""

import json
import os
import shutil
import statistics
import sys
import threading
import time

import numpy as np

from benchmark import cluster as cl
from benchmark import data, readers, reference, traffic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE_SLICE_S = 4.0          # the traced slice at the end of the window
TRACES_FETCHED = 200         # timelines read back (the controller keeps 256)
# A checkout's first run compiles every route; its warm-up ends when settled,
# and at the latest so that the run stays inside the 1200 s a first run may
# take: 30 s to a ready worker, a compiling pass of 300 s, a restart, then
# this, the window and the reference.
FIRST_RUN_WARMUP_S = 500.0
SETUP_CLIENT = {"timeout_s": 900, "retries": 1}   # a first compile takes minutes
SLOW_FACTOR = 3.0            # a warm-up reply this far over its shape's
                             # median compiled or loaded a program
# Facts of the program, not of a mix: the worker's heartbeats carry its shard
# statistics and first measured walls to the controller within 25 s, and the
# planner tries an unmeasured route on every 20th decision of a bucket
# (``plan/calibrate.py``), so 21 replies hold one exploration slot.
SETTLE_AFTER_S = 25.0
SETTLE_RUN = 21


def ask(rpc, args):
    """The timed call.  (Tests break the path here.)"""
    return rpc.groupby(*args)


# -- the cell, from data files -------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, home=REPO):
    """Everything a cell is, found by the names in ``home``'s
    ``BENCHMARK.json`` among the data files under ``home``'s ``benchmark/``."""
    bench = load_json(os.path.join(home, "BENCHMARK.json"))
    root = os.path.join(home, "benchmark")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise cl.RunFailure(f"BENCHMARK.json has no workload {workload!r}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    file = entry["file"]
    config = load_json(os.path.join(home, file))
    refused = reference.unanswerable(config)
    if refused:   # refused here, before a shard is written or a process started
        raise cl.RunFailure(f"{file}: the plain reference has no rule for " + "; ".join(
            f"the {kind} {name!r} of query {query!r}" for query, kind, name in refused
        ))
    mix = traffic.read_mix(os.path.join(root, "traffic", cell["traffic"] + ".json"))

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    layer = {}
    for metric in filter(mine, bench["per_layer"]):
        layer[metric["name"]] = dict(
            load_json(os.path.join(root, "layer_metrics", metric["name"] + ".json")),
            unit=metric["unit"],
        )
    return {
        "name": workload, "chips": cell["chips"], "config": config, "mix": mix,
        "end_to_end": {m["name"]: m["unit"] for m in filter(mine, bench["end_to_end"])},
        "per_layer": layer,
    }


# -- queries ---------------------------------------------------------------------

def issue(rpc, query, record_answer=False):
    """Send one query; returns its record."""
    t0 = time.perf_counter()
    answer, error = None, None
    try:
        answer = ask(rpc, query.args)
    except Exception as exc:   # a failed query is counted, not fatal
        error = f"{type(exc).__name__}: {exc}"
    t1 = time.perf_counter()
    strategies = rpc.last_call_strategies or {}
    record = {
        "shape": query.shape, "fresh": query.fresh, "value": query.value,
        "args": query.args, "rows": query.rows, "ok": error is None,
        "error": error, "t_send": t0, "t_reply": t1, "wall_s": t1 - t0,
        "trace_id": rpc.last_trace_id, "check": query.check,
    }
    if error is None:
        record.update(
            answer_source=rpc.last_call_answer_source,
            effective=sorted(set((strategies.get("effective") or {}).values())),
            hints=sorted(strategies.get("hints") or {}),
            timings=rpc.last_call_timings or {},
        )
    if record_answer:
        record["answer"] = answer
    return record


def warm_query(cell, names, rows_of, shape, constants):
    """A warm-up query: the shape fresh (``constants`` given) or as it stands."""
    value = constants.next() if constants is not None else None
    args = traffic.query_args(cell["config"], shape, names, value)
    return traffic.Query(shape, value is not None, value, args,
                         sum(rows_of[f] for f in args[0]))


def one_pass(rpc, cell, names, rows_of, constants, history=None):
    """Each shape of the mix once, in file order: as it stands where the
    mix repeats it, and fresh where the mix varies it."""
    records = []
    for shape, (fresh, fixed) in traffic.shapes_of(cell["mix"]).items():
        for variant in ([None] if fixed else []) + ([constants[shape]] if fresh else []):
            record = issue(rpc, warm_query(cell, names, rows_of, shape, variant))
            if not record["ok"]:
                done = [f"{r['shape']}:{route_of(r)}" for r in records[-8:]]
                raise cl.RunFailure(
                    f"warm-up query {shape} (fresh={record['fresh']}, {record['args'][3]}) "
                    f"failed after {done}: {record['error']}"
                )
            records.append(record)
            if history is not None:
                history.setdefault((shape, record["fresh"]), []).append(record)
    return records


def route_of(record):
    return (f"{'/'.join(record['hints'])}>{'/'.join(record['effective'])}"
            f":{record['answer_source']}")


def unsettled(history, run, allow=0):
    """None once every shape's last ``run`` replies were steady: none slow
    enough to have compiled, and all from one hint by one route from one
    source - but for at most ``allow`` replies (the planner's exploration
    slot, where it never stops exploring) whose route the warm-up had
    already seen, and so compiled, before the run.  Else: what the first
    shape that has not settled did last."""
    for (shape, _fresh), records in history.items():
        last = records[-run:]
        routes = [route_of(r) for r in last]
        usual = max(set(routes), key=routes.count)
        seen = {route_of(r) for r in records[:-run]}
        odd = [route for route in routes if route != usual]
        median = statistics.median(r["wall_s"] for r in last)
        slow = [round(r["wall_s"], 3) for r in last if r["wall_s"] > SLOW_FACTOR * median + 0.05]
        if len(last) < run or slow or len(odd) > allow or not set(odd) <= seen:
            runs = []
            for route in routes:
                if runs and runs[-1][0] == route:
                    runs[-1][1] += 1
                else:
                    runs.append([route, 1])
            return (f"{shape}: {' '.join(f'{r} x{n}' for r, n in runs)}; "
                    f"median {median:.3f}s, slow {slow}")
    return None


# -- the window --------------------------------------------------------------------

def stream_loop(make_rpc, plan, barrier, clock, out):
    rpc = make_rpc()
    barrier.wait()
    for query in plan:
        if time.perf_counter() >= clock["end"]:
            break
        out.append(issue(rpc, query, record_answer=query.check))
    else:
        clock["plan_used_up"] = True


def run_window(cluster, plans, seconds, tracer=None):
    """All streams, closed loop, for ``seconds``; the parent only waits.
    Streams are threads of this process, each with an ``RPC`` of its own
    at the client's defaults (120 s, 3 tries)."""
    clock = {"end": float("inf")}
    barrier = threading.Barrier(len(plans) + 1)
    outs = [[] for _ in plans]
    threads = [
        threading.Thread(
            target=stream_loop, daemon=True,
            args=(lambda i=i: cl.connect(cluster, client_id=f"stream-{i}"),
                  plan, barrier, clock, outs[i]),
        )
        for i, plan in enumerate(plans)
    ]
    for thread in threads:
        thread.start()
    barrier.wait(timeout=120)
    start = time.perf_counter()
    clock["end"] = start + seconds
    if tracer is not None:
        tracer.schedule(clock["end"])
    for thread in threads:
        thread.join()
    if tracer is not None:
        tracer.finish()
    if clock.get("plan_used_up"):
        raise cl.RunFailure("a stream used up its plan inside the window")
    records = sorted((r for out in outs for r in out), key=lambda r: r["t_send"])
    return start, records


class Tracer:
    """Brackets the last seconds of the window with ``jax.profiler`` inside
    the worker, through the program's own ``execute_code`` verb."""

    def __init__(self, cluster, logdir):
        self.rpc = cl.connect(cluster, {"timeout_s": 300, "retries": 1})
        self.logdir = logdir
        self.slice, self.result, self.thread = None, None, None

    def call(self, function, **kwargs):
        return self.rpc.execute_code(
            function=f"benchmark.in_worker.{function}", wait=True, kwargs=kwargs
        )

    def schedule(self, window_end):
        def run():
            time.sleep(max(window_end - TRACE_SLICE_S - time.perf_counter(), 0.0))
            self.call("start", logdir=self.logdir)
            self.slice = [time.perf_counter(), None]

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def finish(self):
        self.thread.join()
        self.slice[1] = time.perf_counter()
        self.result = self.call("stop", logdir=self.logdir)


# -- metrics ---------------------------------------------------------------------

def end_to_end(cell, records, start, seconds_cold, setup_s):
    """The cell's end-to-end numbers: all the work and all the time of the
    window, and the tail of all its queries."""
    done = [r for r in records if r["ok"]]
    if not done:
        raise cl.RunFailure("the window completed no query")
    wall = max(r["t_reply"] for r in records) - start
    walls_ms = [1000.0 * r["wall_s"] for r in done]
    values = {
        "setup_s": setup_s,
        "query_ms": 1000.0 * wall / len(done),
        "query_p95_ms": float(np.percentile(walls_ms, 95)),
        "rows_per_s": sum(r["rows"] for r in done) / wall,
        "cold_query_s": seconds_cold,
    }
    return {
        name: {"value": values[name], "unit": unit}
        for name, unit in cell["end_to_end"].items() if values.get(name) is not None
    }


def memory_peak(rpc, records):
    """The process-lifetime HBM watermark of the fullest device, from the
    worker's own tag on the calc span of the window's last device queries."""
    peak = 0
    for record in [r for r in records if r["ok"]][-8:]:
        timeline = rpc.trace(record["trace_id"]) or {}
        for span in timeline.get("spans", []):
            peak = max(peak, (span.get("tags") or {}).get("device_hbm_watermark_bytes", 0))
    return peak


# -- the check ---------------------------------------------------------------------

def evenly(items, count):
    """``count`` of ``items``, evenly spaced, the first and the last among them."""
    if count >= len(items) or count < 2:
        return list(items[:max(count, 0)])
    last, steps = len(items) - 1, count - 1
    return [items[(i * last + steps // 2) // steps] for i in range(count)]


def chosen(mix, recorded):
    """The recorded answers the reference compares: all of them up to the
    mix's ``check_at_most``, beyond it that many, so that what a run does
    after its window does not grow with what the window completed.  Each
    shape the window recorded gets an equal share (a remainder goes to the
    shapes first in file order; what a shape with fewer answers leaves
    passes to the others), evenly spaced over its answers in send order.
    The choice follows from the records alone: no draw, the same twice."""
    by_shape = {shape: [] for shape in traffic.shapes_of(mix)}
    for record in recorded:
        by_shape[record["shape"]].append(record)
    waiting = [shape for shape, answers in by_shape.items() if answers]
    share_of, left = {}, int(mix["check_at_most"])
    while waiting:
        share, extra = divmod(left, len(waiting))
        offer = {shape: share + (i < extra) for i, shape in enumerate(waiting)}
        short = [shape for shape in waiting if len(by_shape[shape]) <= offer[shape]]
        if not short:
            share_of.update(offer)
            break
        for shape in short:
            share_of[shape] = len(by_shape[shape])
            left -= share_of[shape]
            waiting.remove(shape)
    picked = {id(r) for shape, n in share_of.items() for r in evenly(by_shape[shape], n)}
    return [r for r in recorded if id(r) in picked]


def check(cell, ref, records, control=False):
    """Compare answers the window recorded with the plain reference ``ref``.
    A marked query that has no answer counts in ``unanswered`` whatever is
    chosen; of the answered ones ``chosen`` says which are compared."""
    config = cell["config"]
    marked = [r for r in records if r["check"]]
    recorded = [r for r in marked if r.get("answer") is not None]
    compared = chosen(cell["mix"], recorded)
    numbers = [
        reference.compare(r["args"], None, None, config["columns"])
        for r in marked if r.get("answer") is None
    ]
    control_numbers = []
    for record in compared:
        expected = ref.answer(record["args"])
        numbers.append(reference.compare(
            record["args"], record["answer"], expected, config["columns"]
        ))
        if control:
            control_numbers.append(reference.compare(
                record["args"], ref.answer(record["args"], accumulate="float32"),
                expected, config["columns"],
            ))
    limits = config["guarantees"]["check_limits"]
    correct, rows = reference.verdict(reference.worst(numbers), limits)
    correct = correct and len(compared) > 0
    rows.append(["answers_recorded", len(recorded), None])
    rows.append(["answers_compared", len(compared), None])
    out = {"correct": correct, "rows": rows}
    if control:
        out["control"] = reference.verdict(reference.worst(control_numbers), limits)
    return out


class Laps:
    """Where a run's own seconds went: the clock at the points the run
    passes, each logged as it is passed, so that a run that is cut shows in
    its log how far it got."""

    def __init__(self, label, started):
        self.label, self.started, self.last, self.parts = label, started, started, {}

    def __call__(self, part, note=""):
        now = time.perf_counter()
        self.parts[part] = now - self.last
        self.last = now
        cl.log(f"{self.label}: {part} took {self.parts[part]:.1f}s, "
               f"done at {now - self.started:.1f}s{note}")
        return now

    def total(self):
        """The parts and their total, for ``observed["run_s"]``."""
        cl.log(f"{self.label}: run {self.last - self.started:.1f}s = "
               + " + ".join(f"{part} {s:.1f}" for part, s in self.parts.items()))
        return dict(self.parts, total=self.last - self.started)


# -- one run -----------------------------------------------------------------------

def run_cell(workload, seed, seconds, trace, started=None, control=False,
             home=REPO, rehearsal=None):
    """Returns the result object of one run (the last line of stdout).
    ``home`` holds ``BENCHMARK.json``, the cell's data files and the run's
    state (compile cache, working directory).  ``rehearsal`` is for a run
    without a chip: the ``platform`` the worker is to compute on in place
    of ``tpu``, a ``worker_env`` and a short ``warmup_max_s``."""
    started = time.perf_counter() if started is None else started
    rehearsal = rehearsal or {}
    expect_platform = rehearsal.get("platform", "tpu")
    warmup_max_s = rehearsal.get("warmup_max_s")
    cell = load_cell(workload, home)
    config, mix = cell["config"], cell["mix"]
    lap = Laps(workload, started)
    state = os.path.join(home, "benchmark")
    from bqueryd_tpu.storage import native

    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, os.environ.get("PYTHONPATH")) if p
    )
    if not native.available():   # builds libtpucolz once, keeps it in the checkout
        raise cl.RunFailure("the native codec did not build")
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        state, ".cache", "jax"
    )
    os.makedirs(cache_dir, exist_ok=True)
    marker = os.path.join(cache_dir, f"compiled-{workload}.marker")
    first_run = not os.path.exists(marker)
    workdir = os.path.join(state, ".work", f"run-{os.getpid()}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    env = {"JAX_PLATFORMS": expect_platform}
    if trace:
        env.update(BQUERYD_TPU_ENABLE_EXECUTE_CODE="1", BQUERYD_TPU_PROFILE="1")
    env.update(rehearsal.get("worker_env", {}))
    cluster = cl.Cluster(REPO, workdir, cache_dir, env)
    try:
        cluster.start_controller()
        names = data.build_dataset(config, seed, cluster.data_dir)
        rows_of = {
            n: data.shard_rows(config["rows"], config["shards"], i)
            for i, n in enumerate(names)
        }
        cluster.start_worker()
        rpc = cl.connect(cluster, SETUP_CLIENT)

        def ready():
            cl.wait_registered(rpc, cluster, names)
            return cl.worker_slice(rpc, cluster)["device"]

        device = ready()
        cluster.devices = device["count"]
        if device["platform"] != expect_platform or device["count"] < cell["chips"]:
            raise cl.RunFailure(
                f"the worker computes on {device['count']} x {device['platform']}, "
                f"the cell needs {cell['chips']} x {expect_platform}"
            )
        lap("worker_ready", f" on {device['count']} x {device['device_kind']}")

        warm_rng = np.random.default_rng([int(seed), 11])
        constants = {
            shape: traffic.Constants(config["slot"], warm_rng, lane=1)
            for shape in traffic.shapes_of(mix)
        }
        wants_cold = "cold_query_s" in cell["end_to_end"] or any(
            m["args"].get("over") == "cold" for m in cell["per_layer"].values()
        )
        cold_s, cold_records = None, []
        if wants_cold:
            if first_run:   # "cold" always means: compile cache warm
                one_pass(rpc, cell, names, rows_of, constants)
                cluster.stop_worker()
                cluster.start_worker()
                ready()
            t0 = time.perf_counter()
            cold_records = one_pass(rpc, cell, names, rows_of, constants)
            cold_s = (time.perf_counter() - t0) / len(cold_records)
        lap("cold_pass")

        history, warm_since = {}, None
        now = time.perf_counter()
        settle_after = SETTLE_AFTER_S
        if warmup_max_s is not None:   # a rehearsal's short limit cuts both phases
            settle_after = min(settle_after, warmup_max_s / 2)
        deadline = now + (
            warmup_max_s if warmup_max_s is not None
            else FIRST_RUN_WARMUP_S if first_run else float(mix["warmup_max_s"])
        )
        while True:
            cluster.check_alive()
            one_pass(rpc, cell, names, rows_of, constants, history)
            if warm_since is None:
                # until the worker's heartbeats have carried its shard
                # statistics and first measured walls to the controller, the
                # planner neither steers nor explores, and replies look
                # settled before anything is
                if time.perf_counter() > now + settle_after:
                    warm_since, history = time.perf_counter(), {}
                continue
            why = unsettled(history, SETTLE_RUN, int(mix["settle_allow"]))
            if why is None:
                break
            if time.perf_counter() > deadline:
                cl.log(f"{workload}: warm-up did not settle before its limit: {why}")
                break
        passes = max(len(v) for v in history.values())
        warm_routes = {
            shape: records[-1]["effective"]
            for (shape, fresh), records in history.items() if fresh
        }
        fastest = min(r["wall_s"] for v in history.values() for r in v)
        cl.log(f"{workload}: warm-up {passes} passes, routes {warm_routes}, "
               f"sources { {k[0]: v[-1]['answer_source'] for k, v in history.items()} }")
        with open(marker, "w") as f:
            f.write("every route of this cell's shapes compiled once here\n")

        length = int(seconds / max(fastest, 1e-3)) + 200
        plans = traffic.plans(config, mix, seed, names, rows_of, length)
        tracer, evidence = None, {}
        if trace:
            tracer = Tracer(cluster, os.path.join(workdir, "trace"))
            evidence["counters_before"] = tracer.call("counters")
        setup_s = lap("warm_up") - started
        start, records = run_window(cluster, plans, seconds, tracer)
        lap("window")

        peak = memory_peak(rpc, records)
        if trace:
            evidence.update(
                counters_after=tracer.call("counters"), device_trace=tracer.result,
                slice=tracer.slice,
                traces={
                    r["trace_id"]: rpc.trace(r["trace_id"])
                    for r in records[-TRACES_FETCHED:] if r["ok"]
                },
            )
            peak = evidence["counters_after"]["peak_bytes_in_use"] or peak
    except BaseException:
        for proc in cluster.procs.values():
            cl.log(f"--- {proc.log_path} ---\n{cl.log_tail(proc.log_path, 60)}")
        raise
    finally:
        cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    lap("stop")
    # the chip is free and the peak is read: now the reference
    ref = reference.Reference(dict(zip(names, data.frames(config, seed))))
    lap("frames")
    checked = check(cell, ref, records, control)
    lap("reference")
    result = {
        "correct": checked["correct"],
        "attempted": len(records),
        "failed": sum(not r["ok"] for r in records),
        "device": {
            "platform": device["platform"], "kind": device["device_kind"],
            "count": device["count"], "memory_peak_bytes": int(peak),
        },
    }
    if trace:
        evidence.update(
            records=records, cold_records=cold_records, warm_routes=warm_routes,
            column_dtypes=config["columns"], device_kind=device["device_kind"],
            chips=device["count"],
        )
        values = {name: readers.read(m, evidence) for name, m in cell["per_layer"].items()}
        result["metrics"] = {
            name: {"value": v, "unit": cell["per_layer"][name]["unit"]}
            for name, v in values.items() if v is not None
        }
        reduced = evidence["device_trace"] or {}
        if reduced.get("busy_s"):
            result["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {
                "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
            }
    else:
        result["metrics"] = end_to_end(cell, records, start, cold_s, setup_s)
    result["observed"] = dict(observed(records, warm_routes, passes), run_s=lap.total())
    if control:
        result["control"] = {"fails": not checked["control"][0], "rows": checked["control"][1]}
    result["check"] = {name: [number, limit] for name, number, limit in checked["rows"]}
    return result


def observed(records, warm_routes, passes):
    """What answered the window, for PERF.md (the driver ignores the key)."""
    sources, routes, walls = {}, {}, {}
    for r in (r for r in records if r["ok"]):
        sources[r["answer_source"]] = sources.get(r["answer_source"], 0) + 1
        key = f"{r['shape']}:{'/'.join(r['hints'])}>{'/'.join(r['effective'])}"
        routes[key] = routes.get(key, 0) + 1
        walls.setdefault(r["shape"], []).append(r["wall_s"])
    first = min((r["t_send"] for r in records), default=0.0)
    slowest = [
        {"shape": r["shape"], "route": route_of(r), "wall_ms": 1000.0 * r["wall_s"],
         "sent_at_s": r["t_send"] - first,
         "phases_ms": {
             phase: 1000.0 * sum(group.get(phase, 0.0) for group in r["timings"].values())
             for phase in sorted({p for group in r["timings"].values() for p in group})
         }}
        for r in sorted((r for r in records if r["ok"]), key=lambda r: -r["wall_s"])[:3]
    ]
    return {"answer_source": sources, "routes": routes, "warmup_passes": passes,
            "warm_routes": warm_routes, "slowest": slowest,
            "mean_wall_ms": {k: 1000.0 * sum(v) / len(v) for k, v in walls.items()}}


def print_check(result, stream=sys.stderr):
    """Each number compared beside its limit: the run's last stderr lines."""
    for name, (number, limit) in result["check"].items():
        print(f"check {name}={number} limit={limit}", file=stream)
    print(f"check correct={result['correct']} attempted={result['attempted']} "
          f"failed={result['failed']}", file=stream, flush=True)
