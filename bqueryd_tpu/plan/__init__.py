"""Query planning & admission: the controller's serving-layer brain.

Five pieces, all control-plane safe (no JAX, no pandas):

* :mod:`bqueryd_tpu.plan.logical`   — typed logical plans compiled from the
  ``groupby`` RPC, with rewrite rules (predicate pushdown, mean
  decomposition) and per-dispatch plan fragments;
* :mod:`bqueryd_tpu.plan.stats`     — per-shard statistics (rows, column
  min/max) gathered by workers, advertised in their registration messages,
  and the stats-only shard pruning predicate;
* :mod:`bqueryd_tpu.plan.admission` — bounded priority admission queue with
  per-client quotas, deadlines, and explicit BUSY backpressure;
* :mod:`bqueryd_tpu.plan.bundle`    — shared-scan multi-query fusion: the
  admission micro-batch window (``BQUERYD_TPU_BATCH_WINDOW_MS``), the plan
  compatibility signature, and the bundle fragments whole compatible groups
  dispatch (and demultiplex) as one unit.
* :mod:`bqueryd_tpu.plan.dag`       — the typed operator DAG behind
  ``rpc.query``: broadcast hash joins, per-group top-k, mergeable quantile
  sketches, time-window rollups — compiled from query specs (and from
  plain groupbys, which round-trip bit-identically onto the engine path).

``BQUERYD_TPU_PLANNER=0`` disables plan-time pruning (queries revert to the
static fan-out); admission limits are controlled by their own env knobs (see
:mod:`.admission`).  Which kernel answers a groupby is not planned here:
``ops.groupby.kernel_route`` decides it on the worker from the actual rows,
groups, ops and backend.
"""

import os

from bqueryd_tpu.plan.admission import (  # noqa: F401
    ADMIT,
    BUSY,
    DUPLICATE,
    QUEUED,
    AdmissionController,
)
from bqueryd_tpu.plan.logical import (  # noqa: F401
    LogicalPlan,
    compile_groupby,
    fragment_for,
    fragment_to_query,
    plan_groupby,
    rewrite_plan,
)
from bqueryd_tpu.plan.stats import (  # noqa: F401
    StatsCollector,
    gather_table_stats,
    stats_can_match,
)
from bqueryd_tpu.plan import bundle  # noqa: F401
from bqueryd_tpu.plan import dag  # noqa: F401


def planner_enabled():
    """Plan-time shard pruning; on unless BQUERYD_TPU_PLANNER=0.
    Read per query so a live controller can be re-tuned."""
    return os.environ.get("BQUERYD_TPU_PLANNER", "1") != "0"
