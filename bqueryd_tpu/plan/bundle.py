"""Shared-scan multi-query fusion: bundle compilation for the admission
micro-batch window.

PR-1's multi-query batching fuses only *bit-identical* concurrent queries —
the same shards, the same aggs, the same filters — which real serving
traffic essentially never produces (``plan_shared_dispatches`` sat at 0
across whole bench rounds).  This module widens sharing to *compatible*
queries: same shard set after pruning and same group-key columns, while
measures and filters may differ.  A compatible group dispatched together
pays the expensive per-scan work — storage decode, key alignment/factorize,
codes H2D, measure-block upload — exactly once, and runs ONE mesh program
whose per-member partial tables merge in one collective pass
(:meth:`bqueryd_tpu.parallel.executor.MeshQueryExecutor.execute_bundle`).

The window is the admission-side knob: ``BQUERYD_TPU_BATCH_WINDOW_MS``
(default 0 = off, single-query behaviour bit-identical to before) holds
admitted groupby plans for up to that many milliseconds so concurrent
queries can land in the same flush; ``BQUERYD_TPU_BATCH_MAX`` caps the
members per flush.  Grouping happens at flush time via :func:`compat_key`;
queries that cannot fuse (raw-rows, basket expansion, non-mergeable aggs,
``batch=False``) launch individually, exactly as before.

Each bundle member keeps its own identity end to end: its trace context,
deadline, quota ticket and result envelope — the bundle fragment carries a
per-member record (:func:`bundle_fragment`) the worker demultiplexes, and a
member past its deadline is dropped from the stack, never the bundle.

Control-plane module: stdlib + models.query only (no JAX, no pandas).
"""

from bqueryd_tpu.models.query import MERGEABLE_OPS, GroupByQuery
from bqueryd_tpu.utils.env import env_num

BUNDLE_VERSION = 1


def batch_window_ms():
    """Admission micro-batch window in milliseconds; 0 (the default)
    disables staging entirely — groupby plans launch the moment they are
    admitted, bit-identical to the pre-window controller.  Read per query
    so a live controller can be re-tuned."""
    return max(env_num("BQUERYD_TPU_BATCH_WINDOW_MS", 0.0), 0.0)


def batch_max():
    """Most member queries one window flush may hold; a full window flushes
    early instead of stretching the first member's latency further."""
    return max(env_num("BQUERYD_TPU_BATCH_MAX", 16, int), 2)


def compat_key(plan, keep, kwargs):
    """The plan-compatibility signature: queries with equal keys over the
    same flush window fuse into one shared-scan bundle.  Returns None for
    queries that cannot ride a bundle (they launch individually):

    * raw-rows (``aggregate=False``) and basket-expansion queries — their
      payloads are not per-group partial tables;
    * non-mergeable aggregation ops (count_distinct family) — the stacked
      partial merge is psum-shaped;
    * ``batch=False`` callers — they asked for per-shard dispatch;
    * fully-pruned plans — nothing to scan.

    The key deliberately excludes measures, filters and deadlines (the
    whole point is fusing across them: measures dedupe into a union upload,
    filters become the stacked mask axis, deadlines stay per member) and
    includes the POST-PRUNE shard set — two queries whose filters prune to
    different shard subsets scan different data and must not share a pass.
    """
    if not keep:
        return None
    if not plan.aggregate_rows or plan.expand_filter_column:
        return None
    if not kwargs.get("batch", True):
        return None
    if any(a[1] not in MERGEABLE_OPS for a in plan.physical_agg_list()):
        return None
    return (
        tuple(keep),
        tuple(plan.groupby.keys),
        kwargs.get("affinity"),
    )


def bundle_fragment(plan, filenames, members, sole=False):
    """The per-dispatch slice of a BUNDLE: what one CalcMessage executes
    for a whole compatible group.  Shared fields (shard group, group-key
    columns) ride once; each member record carries only what differs — its
    aggs, filter conjunction, deadline, and the ``member_id`` the reply
    demultiplexes on.

    ``members`` is ``[(member_id, plan, deadline), ...]``."""
    return {
        "v": BUNDLE_VERSION,
        "filenames": list(filenames),
        "groupby_cols": list(plan.groupby.keys),
        "sole": bool(sole),
        "members": [
            {
                "member_id": member_id,
                "agg_list": member_plan.physical_agg_list(),
                "where_terms": [list(t) for t in member_plan.where_terms],
                "deadline": deadline,
            }
            for member_id, member_plan, deadline in members
        ],
    }


def bundle_to_queries(fragment):
    """Rebuild the worker-side member queries from a bundle fragment:
    ``[(member_id, deadline, GroupByQuery), ...]`` in fragment order."""
    if fragment.get("v") != BUNDLE_VERSION:
        raise ValueError(f"unknown bundle version {fragment.get('v')!r}")
    groupby_cols = list(fragment["groupby_cols"])
    sole = bool(fragment.get("sole"))
    out = []
    for member in fragment["members"]:
        out.append(
            (
                member["member_id"],
                member.get("deadline"),
                GroupByQuery(
                    list(groupby_cols),
                    [list(a) for a in member["agg_list"]],
                    [tuple(t) for t in member["where_terms"]],
                    aggregate=True,
                    sole_payload=sole,
                ),
            )
        )
    return out


def member_shares(executed_ids, walls=None):
    """Per-member accountability fractions for a bundle's shared scan:
    ``{member_id: share}`` summing to 1.0 over the executed members.

    On the per-member fallback path the worker measures each member's own
    execution wall (``walls``) and shares are proportional; on the
    one-program mesh path no per-member wall exists, so the shared scan
    splits equally — the honest prior when one kernel served everyone.
    Result-cache hits are NOT executed members (the caller reports them at
    0.0: they consumed no scan).  The controller scales the bundle reply's
    shared ``phase_timings`` by these, so a slow bundle never lands every
    member in the slow-query ring with the whole bundle's wall."""
    executed = list(executed_ids)
    if not executed:
        return {}
    if walls:
        total = sum(max(float(walls.get(m, 0.0)), 0.0) for m in executed)
        if total > 0.0 and all(
            float(walls.get(m, 0.0)) > 0.0 for m in executed
        ):
            return {
                m: round(float(walls[m]) / total, 6) for m in executed
            }
    share = round(1.0 / len(executed), 6)
    return {m: share for m in executed}
