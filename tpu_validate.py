"""Hardware validation: run the differential-fuzz gates on the DEFAULT
backend (the TPU, where there is one) and bound the f64 sort+prefix-diff
error.

The test suite pins ``JAX_PLATFORMS=cpu`` (tests/conftest.py), so the float64
sum path it exercises is the direct ``segment_sum`` — NOT the
``_sorted_segment_sum`` prefix-diff path a TPU takes (``ops/groupby.py``
routes f64 sums through the sort path on non-CPU backends, because TPUs have
no native f64 and an emulated-f64 scatter dominates the query).  This script
is the missing gate: it runs the same pandas-differential cases on whatever
backend the machine provides (``JAX_PLATFORMS=cpu`` for a smoke of the
script itself), plus a dedicated 1M-row f64 sum whose ground truth is ``math.fsum``, and records the
max observed relative error per case.

Usage:  python tpu_validate.py [out.json]
Exit 0 iff every phase ran to completion AND passed its tolerances; a
budget-truncated fuzz phase (TPU_VALIDATE_BUDGET_S, measured over the fuzz
loop only) reports ok=false/complete=false even with zero failures among
the cases that did run.
"""

import json
import math
import os
import sys
import tempfile
import time
import traceback

# device kernels only: the point is the TPU path, not the host fallback
os.environ.setdefault("BQUERYD_TPU_HOST_KERNEL_ROWS", "0")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "tests"))

import numpy as np
import pandas as pd

pd.set_option("future.infer_string", False)


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "TPU_VALIDATE.json"
    t0 = time.time()
    from bqueryd_tpu.utils import devicehealth

    wedge_start = devicehealth.wedge_marker()
    import jax

    report = {
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
        "kernel_bench": {},
        "cases": {},
        "f64_large": None,
        "ok": False,
    }

    def checkpoint():
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)

    # ---- kernel micro-bench FIRST: the scarcest evidence (chip time is
    # budgeted) is per-kernel hardware walls at bench shapes, including one
    # REAL (non-interpret) Pallas run — no cluster needed.
    # Each case is gated on numpy ground truth so a wrong-route or wrong-
    # result kernel can't post a number.
    def kernel_bench():
        import jax.numpy  # noqa: F401  (backend bring-up)

        from bqueryd_tpu.ops import groupby as gb

        rng = np.random.default_rng(0)
        # pre-set route flags would silently re-route the non-pallas cases
        # (flags are read per call in the un-jitted dispatcher); pop them
        # for the whole bench and restore after (same hygiene as bench.py)
        prior_env = {
            flag: os.environ.pop(flag, None)
            for flag in ("BQUERYD_TPU_PALLAS", "BQUERYD_TPU_FORCE_MATMUL")
        }
        shapes = [
            # (name, rows, groups, op, dtype, pallas)
            ("sum_i64_1M_9g", 1_000_000, 9, "sum", np.int64, False),
            ("sum_i64_10M_9g", 10_000_000, 9, "sum", np.int64, False),
            ("mean_f64_10M_9g", 10_000_000, 9, "mean", np.float64, False),
            ("sum_i64_10M_70225g", 10_000_000, 70_225, "sum", np.int64,
             False),
            ("sum_i64_10M_9g_pallas", 10_000_000, 9, "sum", np.int64,
             True),
        ]
        for name, n, g, op, dt, use_pallas in shapes:
            if use_pallas and jax.default_backend() == "cpu":
                # same honesty rule as bench.py: off-TPU the flag would
                # re-measure the scatter path under a pallas label
                report["kernel_bench"][name] = {
                    "skipped": "needs a tpu backend"
                }
                continue
            try:
                codes = rng.integers(0, g, n).astype(np.int64)
                if dt == np.float64:
                    vals = (rng.random(n) * 100 - 50).astype(dt)
                else:
                    vals = rng.integers(-1000, 1000, n).astype(dt)
                if use_pallas:
                    os.environ["BQUERYD_TPU_PALLAS"] = "1"
                try:
                    t_h2d = time.perf_counter()
                    codes_d = jax.device_put(codes)
                    vals_d = jax.device_put(vals)
                    jax.block_until_ready((codes_d, vals_d))
                    h2d_s = time.perf_counter() - t_h2d
                    t_first = time.perf_counter()
                    r = gb.partial_tables(codes_d, (vals_d,), (op,), g)
                    jax.block_until_ready(jax.tree_util.tree_leaves(r))
                    first_s = time.perf_counter() - t_first
                    walls = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        r = gb.partial_tables(
                            codes_d, (vals_d,), (op,), g
                        )
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(r)
                        )
                        walls.append(time.perf_counter() - t0)
                finally:
                    if use_pallas:
                        os.environ.pop("BQUERYD_TPU_PALLAS", None)
                got = np.asarray(r["aggs"][0]["sum"])  # mean partials: sum
                truth = np.zeros(g, dtype=np.float64 if dt == np.float64
                                 else np.int64)
                with np.errstate(over="ignore"):
                    np.add.at(truth, codes, vals)
                if dt == np.float64:
                    exact = bool(np.allclose(got, truth, rtol=1e-9))
                else:
                    exact = bool((got == truth).all())
                report["kernel_bench"][name] = {
                    "wall_s": round(min(walls), 5),
                    "rows_per_sec": round(n / min(walls), 1),
                    "h2d_s": round(h2d_s, 3),
                    "compile_plus_first_s": round(first_s, 2),
                    "exact": exact,
                }
            except Exception:
                report["kernel_bench"][name] = {
                    "error": traceback.format_exc(limit=2)
                }
            print(
                f"[tpu_validate] kernel {name}: "
                f"{report['kernel_bench'][name]}",
                file=sys.stderr,
                flush=True,
            )
            # checkpoint after every kernel so a wedging backend (or a
            # killed run) still leaves the completed entries on disk
            checkpoint()
        # route-tuning data point: the SORT+prefix-diff path at the
        # highcard bench shape.  The dispatcher picks the blocked scatter
        # here (n_blocks*groups fits _MAX_BLOCK_SEGMENTS); measuring the
        # sorted path next to it on hardware tells us whether the 70k-group
        # crossover belongs lower on this chip (pre-fix hardware sample:
        # blocked path 0.583 s at this shape — thin margin vs the 0.833 s
        # baseline).
        name = "sum_i64_10M_70225g_sorted"
        try:
            import jax.numpy as jnp

            n, g = 10_000_000, 70_225
            codes = rng.integers(0, g, n).astype(np.int64)
            vals = rng.integers(-1000, 1000, n).astype(np.int64)

            @jax.jit
            def _sorted(c, v):
                safe = c.astype(jnp.int32)
                return gb._sorted_segment_sum(v, safe, g)

            codes_d = jax.device_put(codes)
            vals_d = jax.device_put(vals)
            jax.block_until_ready((codes_d, vals_d))
            t_first = time.perf_counter()
            r = _sorted(codes_d, vals_d)
            jax.block_until_ready(r)
            first_s = time.perf_counter() - t_first
            walls = []
            for _ in range(3):
                t0 = time.perf_counter()
                r = _sorted(codes_d, vals_d)
                jax.block_until_ready(r)
                walls.append(time.perf_counter() - t0)
            truth = np.zeros(g, dtype=np.int64)
            np.add.at(truth, codes, vals)
            report["kernel_bench"][name] = {
                "wall_s": round(min(walls), 5),
                "rows_per_sec": round(n / min(walls), 1),
                "compile_plus_first_s": round(first_s, 2),
                "exact": bool((np.asarray(r) == truth).all()),
            }
        except Exception:
            report["kernel_bench"][name] = {
                "error": traceback.format_exc(limit=2)
            }
        print(
            f"[tpu_validate] kernel {name}: {report['kernel_bench'][name]}",
            file=sys.stderr,
            flush=True,
        )
        checkpoint()

        # the group-tiled Pallas MXU path at the same highcard shape: the
        # candidate replacement for the 0.583 s blocked scatter (route
        # decision data; gated off by default until this number exists)
        name = "sum_i64_10M_70225g_hicard_pallas"
        if jax.default_backend() != "tpu":
            report["kernel_bench"][name] = {"skipped": "needs a tpu backend"}
        else:
            try:
                import jax.numpy as jnp

                n, g = 10_000_000, 70_225
                codes = rng.integers(0, g, n).astype(np.int64)
                vals = rng.integers(-1000, 1000, n).astype(np.int64)
                os.environ["BQUERYD_TPU_PALLAS"] = "1"
                try:
                    codes_d = jax.device_put(codes)
                    vals_d = jax.device_put(vals)
                    jax.block_until_ready((codes_d, vals_d))
                    assert gb._hicard_matmul_profitable(
                        (vals_d,), ("sum",), n, g
                    ), "hicard gate did not fire"
                    t_first = time.perf_counter()
                    r = gb.partial_tables(codes_d, (vals_d,), ("sum",), g)
                    jax.block_until_ready(jax.tree_util.tree_leaves(r))
                    first_s = time.perf_counter() - t_first
                    walls = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        r = gb.partial_tables(
                            codes_d, (vals_d,), ("sum",), g
                        )
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(r)
                        )
                        walls.append(time.perf_counter() - t0)
                finally:
                    os.environ.pop("BQUERYD_TPU_PALLAS", None)
                truth = np.zeros(g, dtype=np.int64)
                np.add.at(truth, codes, vals)
                report["kernel_bench"][name] = {
                    "wall_s": round(min(walls), 5),
                    "rows_per_sec": round(n / min(walls), 1),
                    "compile_plus_first_s": round(first_s, 2),
                    "exact": bool(
                        (np.asarray(r["aggs"][0]["sum"]) == truth).all()
                    ),
                }
            except Exception:
                report["kernel_bench"][name] = {
                    "error": traceback.format_exc(limit=2)
                }
            print(
                f"[tpu_validate] kernel {name}: "
                f"{report['kernel_bench'][name]}",
                file=sys.stderr,
                flush=True,
            )
            checkpoint()

        # one MESH-program data point: the exact serving program (shard_map
        # + psum merge + packed single-buffer fetch) on this backend's
        # devices — distinct from the bare kernel above, which skips the
        # collective and the packed fetch
        name = "mesh_sum_i64_10M_9g"
        try:
            from bqueryd_tpu.parallel import executor as ex_mod

            mesh = ex_mod.make_mesh()
            n_dev = mesh.devices.size
            n, g = 10_000_000, 9
            codes = rng.integers(0, g, n).astype(np.int32)
            vals = rng.integers(-1000, 1000, n).astype(np.int64)
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(mesh, P("shards", None))
            # the serving path narrows codes to _codes_dtype(g) (int8 at 9
            # groups) and the dtype is part of the traced program: match it
            # or this measures a different trace than serving runs
            cdt = ex_mod._codes_dtype(g)
            codes_p = ex_mod.MeshQueryExecutor._pack(
                [codes.astype(cdt)], n_dev, cdt.type(-1), dtype=cdt
            )
            vals_p = ex_mod.MeshQueryExecutor._pack([vals], n_dev, 0)
            codes_d = jax.device_put(codes_p, sharding)
            vals_d = jax.device_put(vals_p, sharding)
            t_first = time.perf_counter()
            merged = ex_mod._mesh_partials(
                mesh, "shards", ("sum",), g, codes_d, (vals_d,)
            )
            first_s = time.perf_counter() - t_first
            walls = []
            for _ in range(3):
                t1 = time.perf_counter()
                merged = ex_mod._mesh_partials(
                    mesh, "shards", ("sum",), g, codes_d, (vals_d,)
                )
                walls.append(time.perf_counter() - t1)
            truth = np.zeros(g, dtype=np.int64)
            with np.errstate(over="ignore"):
                np.add.at(truth, codes, vals)
            exact = bool(
                (np.asarray(merged["aggs"][0]["sum"]) == truth).all()
            )
            report["kernel_bench"][name] = {
                "wall_s": round(min(walls), 5),
                "rows_per_sec": round(n / min(walls), 1),
                "n_devices": int(n_dev),
                "compile_plus_first_s": round(first_s, 2),
                "exact": exact,
            }
        except Exception:
            report["kernel_bench"][name] = {
                "error": traceback.format_exc(limit=2)
            }
        print(
            f"[tpu_validate] kernel {name}: {report['kernel_bench'][name]}",
            file=sys.stderr,
            flush=True,
        )
        checkpoint()
        for flag, prior in prior_env.items():
            if prior is not None:
                os.environ[flag] = prior

    kernel_bench()

    failures = 0

    # ---- dedicated f64 error bound SECOND (it is the round's f64 evidence;
    # the 54 fuzz case-paths behind it compile one program each and can
    # outlast a budgeted chip call): 1M rows, 1000 groups, values spanning
    # 12 orders of magnitude; truth = per-group math.fsum
    try:
        from bqueryd_tpu.ops import groupby as gb

        rng = np.random.default_rng(7)
        n, g = 1_000_000, 1_000
        codes = rng.integers(0, g, n).astype(np.int64)
        vals = (rng.random(n) * 2 - 1) * 10.0 ** rng.integers(-6, 6, n)
        truth = np.array(
            [math.fsum(vals[codes == i].tolist()) for i in range(g)]
        )
        tbl = gb.partial_tables(codes, (vals,), ("sum",), g)
        got = np.asarray(tbl["aggs"][0]["sum"])
        denom = np.maximum(np.abs(truth), 1e-30)
        rel = np.abs(got - truth) / denom
        report["f64_large"] = {
            "rows": n,
            "groups": g,
            "max_rel_err": float(rel.max()),
            "max_abs_err": float(np.abs(got - truth).max()),
            "pass": bool(np.allclose(got, truth, rtol=1e-9, atol=1e-6)),
        }
        if not report["f64_large"]["pass"]:
            failures += 1
    except Exception:
        failures += 1
        report["f64_large"] = {"error": traceback.format_exc(limit=3)}
    checkpoint()

    import test_differential_fuzz as fz
    from bqueryd_tpu.models.query import GroupByQuery, QueryEngine
    from bqueryd_tpu.parallel import hostmerge
    from bqueryd_tpu.parallel.executor import MeshQueryExecutor
    from bqueryd_tpu.storage.ctable import ctable

    frames = fz._dataset(seed=1234)
    root = tempfile.mkdtemp(prefix="tpu_validate_")
    tables = []
    for i, df in enumerate(frames):
        p = os.path.join(root, f"shard_{i}.bcolzs")
        ctable.fromdataframe(df, p)
        tables.append(ctable(p, mode="r"))

    engine = QueryEngine()
    # fuzz phase budget: each case-path compiles a fresh program, which
    # can outlast a budgeted chip call; unstarted cases are recorded rather
    # than silently missing
    budget_s = float(os.environ.get("TPU_VALIDATE_BUDGET_S", 2400))
    over_budget = False
    t_fuzz = time.time()  # the budget bounds the fuzz loop only
    # a case whose program wedges the backend blocks the loop from INSIDE a
    # native call (no signal can interrupt it).  The skip list lets a
    # re-run route around a known-wedging case and still bank the rest:
    # TPU_VALIDATE_SKIP_CASES="20,23"
    skip_cases = {
        int(c)
        for c in os.environ.get("TPU_VALIDATE_SKIP_CASES", "").split(",")
        if c.strip()
    }
    for case_i, (gcols, agg_list, where) in enumerate(fz.CASES):
        if case_i in skip_cases:
            report["cases"][f"case{case_i}:engine"] = {"status": "skipped"}
            report["cases"][f"case{case_i}:mesh"] = {"status": "skipped"}
            continue
        if time.time() - t_fuzz > budget_s:
            over_budget = True
            break
        expected = fz._expected(frames, gcols, agg_list, where)
        query = GroupByQuery(gcols, agg_list, where, aggregate=True)
        for path in ("engine", "mesh"):
            label = f"case{case_i}:{path}"
            t = time.perf_counter()
            try:
                if path == "engine":
                    payloads = [
                        engine.execute_local(tbl, query) for tbl in tables
                    ]
                    got = hostmerge.payload_to_dataframe(
                        hostmerge.merge_payloads(payloads)
                    )
                else:
                    if not MeshQueryExecutor.supports(query):
                        report["cases"][label] = {"status": "skipped"}
                        continue
                    payload = MeshQueryExecutor().execute(tables, query)
                    got = hostmerge.payload_to_dataframe(
                        hostmerge.merge_payloads([payload])
                    )
                fz._compare(got, expected, gcols, agg_list)
                # max relative error across float outputs, for the record
                max_rel = 0.0
                g2 = got.sort_values(gcols).reset_index(drop=True)
                e2 = expected.sort_values(gcols).reset_index(drop=True)
                for in_col, op, out_col in agg_list:
                    e = np.asarray(e2[out_col])
                    if not np.issubdtype(e.dtype, np.floating):
                        continue
                    g = g2[out_col].to_numpy().astype(np.float64)
                    denom = np.maximum(np.abs(e), 1e-30)
                    with np.errstate(invalid="ignore"):
                        rel = np.abs(g - e.astype(np.float64)) / denom
                    rel = rel[np.isfinite(rel)]
                    if rel.size:
                        max_rel = max(max_rel, float(rel.max()))
                report["cases"][label] = {
                    "status": "pass",
                    "wall_s": round(time.perf_counter() - t, 3),
                    "max_rel_err": max_rel,
                }
            except Exception:
                failures += 1
                report["cases"][label] = {
                    "status": "FAIL",
                    "error": traceback.format_exc(limit=3),
                }
            print(
                f"[tpu_validate] {label}: "
                f"{report['cases'][label]['status']}",
                file=sys.stderr,
                flush=True,
            )
        # checkpoint after every case so a wedging backend keeps the
        # completed entries
        checkpoint()
    if over_budget:
        report["cases_not_run"] = len(fz.CASES) - case_i
        print(
            f"[tpu_validate] budget {budget_s:.0f}s exhausted: "
            f"{report['cases_not_run']} cases not run",
            file=sys.stderr,
            flush=True,
        )

    failures += sum(
        1
        for v in report["kernel_bench"].values()
        if "error" in v or v.get("exact") is False
    )
    # operator-skipped cases are partial validation, same as a budget
    # truncation: the one-line gate must not read as a full pass
    report["cases_skipped"] = len(skip_cases)
    # evidence integrity: engine/mesh cases host-route if the devicehealth
    # latch flipped at ANY point in the run (the window marker catches a
    # transient wedge that recovered before this line) — their walls are
    # then host numbers
    report["backend_wedged_during_run"] = devicehealth.window_dirty(
        wedge_start
    )
    report["complete"] = not over_budget and not skip_cases
    report["ok"] = failures == 0 and report["complete"]
    report["failures"] = failures
    report["total_s"] = round(time.time() - t0, 1)
    checkpoint()
    print(
        json.dumps(
            {
                k: report[k]
                for k in (
                    "backend", "ok", "complete", "failures",
                    "cases_skipped", "backend_wedged_during_run",
                )
            }
        )
    )
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
