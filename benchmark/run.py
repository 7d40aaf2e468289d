#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <config>.<traffic> --seed n
--seconds s --trace 0|1``: one run of one cell on the machine it is started
on.  The last line of stdout is the result object; the numbers compared
for ``correct`` stand beside their limits in the last lines of stderr.
Exits non-zero, with no result, where the worker finds no TPU.
"""

import time

STARTED = time.perf_counter()   # set-up counts from the start of the process

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import signal     # noqa: E402
import sys        # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _terminate(signum, _frame):
    raise SystemExit(f"signal {signum}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0,
                        help="also put the float32-accumulating reference in "
                             "the program's place and say whether the check fails it")
    args = parser.parse_args(argv)
    signal.signal(signal.SIGTERM, _terminate)   # so ``finally`` reaps children
    import pandas as pd

    pd.set_option("future.infer_string", False)
    from benchmark import harness

    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        started=STARTED, control=bool(args.control),
    )
    if "jax" in sys.modules:
        raise RuntimeError("the benchmark's parent process imported jax")
    harness.print_check(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
