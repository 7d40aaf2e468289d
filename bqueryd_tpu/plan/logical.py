"""Logical query plans: the typed form of a ``groupby`` RPC.

The reference (and the port until this subsystem) fanned the RPC verb out
verbatim: every shard received the raw ``(filenames, groupby_cols, agg_list,
where_terms)`` tuple and every route decision happened at kernel-dispatch
time.  A :class:`LogicalPlan` makes the query a first-class object the
control plane can reason about *before* anything is dispatched:

* **compile** — ``compile_groupby`` turns the RPC arguments into a small node
  pipeline ``Scan -> Filter -> GroupBy -> Aggregate -> Project`` with the
  same normalization rules as :class:`bqueryd_tpu.models.query.GroupByQuery`;
* **rewrite** — ``rewrite_plan`` applies rule passes:
  ``predicate_pushdown`` moves filter terms into the scan node (the terms
  become the scan's pruning predicate, enabling plan-time shard elimination
  against advertised min/max stats), and ``mean_decomposition`` lowers
  ``mean`` into the primitive ``sum`` + ``count`` partials plus a divide in
  the project node — the algebraic identity that makes shard partials
  mergeable (it is also exactly what the kernels compute physically, so the
  rewrite documents and deduplicates rather than changes the wire math);
* **fragment** — ``fragment_for`` cuts the per-dispatch slice of the plan (a
  shard group, the sole-payload flag) into a small
  pickle-friendly dict a :class:`~bqueryd_tpu.messages.CalcMessage` carries
  under its ``plan`` binary field; ``fragment_to_query`` rebuilds the
  worker-side :class:`GroupByQuery` from it.

This module is control-plane code: **no JAX, no pandas** — the controller
imports it freely.
"""

from dataclasses import dataclass, field

# the ONE copy of the agg shorthand rules (JAX-free), shared with the
# worker's GroupByQuery so plan signatures and executed queries can never
# normalize differently
from bqueryd_tpu.models.query import freeze_value, normalize_agg_list

PLAN_VERSION = 1


@dataclass
class ScanNode:
    filenames: list
    columns: list                       # every column the query touches
    pushdown: list = field(default_factory=list)  # where terms pushed down


@dataclass
class FilterNode:
    terms: list = field(default_factory=list)


@dataclass
class GroupByNode:
    keys: list = field(default_factory=list)


@dataclass
class AggregateNode:
    #: [[in_col, op, slot], ...] — primitive partials after rewriting
    aggs: list = field(default_factory=list)


@dataclass
class ProjectNode:
    #: ordered [(out_col, expr)]; expr is ("slot", name) or
    #: ("div", numerator_slot, denominator_slot)
    exprs: list = field(default_factory=list)


@dataclass
class LogicalPlan:
    scan: ScanNode
    filter: FilterNode
    groupby: GroupByNode
    aggregate: AggregateNode
    project: ProjectNode
    aggregate_rows: bool = True         # the RPC ``aggregate=`` kwarg
    expand_filter_column: str = None
    rewrites: list = field(default_factory=list)  # applied rule names

    @property
    def filenames(self):
        return self.scan.filenames

    @property
    def where_terms(self):
        """Effective filter conjunction wherever the terms currently live."""
        return list(self.scan.pushdown) + list(self.filter.terms)

    # -- physical form ------------------------------------------------------
    def physical_agg_list(self):
        """The engine-facing agg list this plan computes, reconstructed from
        the (possibly rewritten) aggregate + project nodes in original output
        order.  Decomposed means come back as ``[in, 'mean', out]`` — the
        kernels' mean partial already carries (sum, count), so this IS the
        decomposed physical form on the wire."""
        by_slot = {slot: (in_col, op) for in_col, op, slot in self.aggregate.aggs}
        out = []
        for out_col, expr in self.project.exprs:
            if expr[0] == "slot":
                in_col, op = by_slot[expr[1]]
                out.append([in_col, op, out_col])
            elif expr[0] == "div":
                in_col, _op = by_slot[expr[1]]
                out.append([in_col, "mean", out_col])
            else:
                raise ValueError(f"unknown project expr {expr!r}")
        return out

    def signature(self):
        """Hashable identity of the plan MINUS the shard set: two queries with
        equal signatures over the same shard group compute identical payloads
        (the shared-dispatch fusion key in the controller).  A DAG query
        (``plan.dag``) folds the full operator-DAG signature in — its join
        table / window / post-derivation filter are invisible to the
        groupby-shaped fields, and without this a DAG query could dedup-fuse
        with a plain groupby over the same projection."""
        return (
            tuple(self.groupby.keys),
            freeze_value(self.physical_agg_list()),
            freeze_value(self.where_terms),
            bool(self.aggregate_rows),
            self.expand_filter_column,
            getattr(self, "dag_sig", None),
        )

    def explain(self):
        lines = [f"LogicalPlan (rewrites: {', '.join(self.rewrites) or 'none'})"]
        lines.append(
            f"  Scan {len(self.scan.filenames)} shard(s), "
            f"cols={self.scan.columns}, pushdown={self.scan.pushdown}"
        )
        if self.filter.terms:
            lines.append(f"  Filter {self.filter.terms}")
        lines.append(f"  GroupBy {self.groupby.keys}")
        lines.append(f"  Aggregate {self.aggregate.aggs}")
        lines.append(f"  Project {self.project.exprs}")
        return "\n".join(lines)

    # -- wire form ----------------------------------------------------------
    def to_wire(self):
        return {
            "v": PLAN_VERSION,
            "scan": {
                "filenames": list(self.scan.filenames),
                "columns": list(self.scan.columns),
                "pushdown": [list(t) for t in self.scan.pushdown],
            },
            "filter": [list(t) for t in self.filter.terms],
            "groupby": list(self.groupby.keys),
            "aggregate": [list(a) for a in self.aggregate.aggs],
            "project": [[out, list(expr)] for out, expr in self.project.exprs],
            "aggregate_rows": bool(self.aggregate_rows),
            "expand_filter_column": self.expand_filter_column,
            "rewrites": list(self.rewrites),
        }

    @classmethod
    def from_wire(cls, wire):
        if wire.get("v") != PLAN_VERSION:
            raise ValueError(f"unknown plan version {wire.get('v')!r}")
        return cls(
            scan=ScanNode(
                filenames=list(wire["scan"]["filenames"]),
                columns=list(wire["scan"]["columns"]),
                pushdown=[tuple(t) for t in wire["scan"]["pushdown"]],
            ),
            filter=FilterNode(terms=[tuple(t) for t in wire["filter"]]),
            groupby=GroupByNode(keys=list(wire["groupby"])),
            aggregate=AggregateNode(aggs=[list(a) for a in wire["aggregate"]]),
            project=ProjectNode(
                exprs=[(out, tuple(expr)) for out, expr in wire["project"]]
            ),
            aggregate_rows=wire["aggregate_rows"],
            expand_filter_column=wire.get("expand_filter_column"),
            rewrites=list(wire.get("rewrites", [])),
        )


# -- compilation -------------------------------------------------------------

def compile_groupby(filenames, groupby_cols, agg_list, where_terms=None,
                    aggregate=True, expand_filter_column=None):
    """RPC arguments -> un-rewritten LogicalPlan (call :func:`rewrite_plan`
    to optimize).  Filenames are deduplicated order-preserving, matching the
    controller's fan-out contract."""
    if isinstance(filenames, str):
        filenames = [filenames]
    filenames = list(dict.fromkeys(filenames))
    aggs = normalize_agg_list(agg_list)
    where_terms = [tuple(t) for t in (where_terms or [])]
    columns, seen = [], set()
    for col in (
        list(groupby_cols)
        + [a[0] for a in aggs]
        + [t[0] for t in where_terms]
        + ([expand_filter_column] if expand_filter_column else [])
    ):
        if col not in seen:
            seen.add(col)
            columns.append(col)
    return LogicalPlan(
        scan=ScanNode(filenames=filenames, columns=columns),
        filter=FilterNode(terms=where_terms),
        groupby=GroupByNode(keys=list(groupby_cols)),
        aggregate=AggregateNode(aggs=[list(a) + [] for a in aggs]),
        project=ProjectNode(),
        aggregate_rows=aggregate,
        expand_filter_column=expand_filter_column,
    )


def _rule_predicate_pushdown(plan):
    """Filter terms -> scan pushdown: the conjunction is evaluated inside the
    scan (masked segment reduction) and, at plan time, against per-shard
    min/max statistics to prune shards that cannot match."""
    if not plan.filter.terms:
        return False
    plan.scan.pushdown = list(plan.scan.pushdown) + list(plan.filter.terms)
    plan.filter.terms = []
    return True


def _rule_mean_decomposition(plan):
    """``mean`` -> primitive ``sum`` + ``count`` partials and a project-time
    divide; duplicate primitives over the same input column are shared."""
    raw = plan.aggregate.aggs
    slots = {}       # (in_col, op) -> slot name
    new_aggs = []
    exprs = []
    changed = False

    def slot_for(in_col, op):
        key = (in_col, op)
        if key not in slots:
            slots[key] = f"__{in_col}__{op}"
            new_aggs.append([in_col, op, slots[key]])
        else:
            nonlocal changed
            changed = True  # a primitive got shared between outputs
        return slots[key]

    for in_col, op, out_col in raw:
        if op == "mean":
            changed = True
            s = slot_for(in_col, "sum")
            c = slot_for(in_col, "count")
            exprs.append((out_col, ("div", s, c)))
        else:
            exprs.append((out_col, ("slot", slot_for(in_col, op))))
    plan.aggregate.aggs = new_aggs
    plan.project.exprs = exprs
    return changed


#: rule pipeline, applied in order by rewrite_plan
REWRITE_RULES = (
    ("predicate_pushdown", _rule_predicate_pushdown),
    ("mean_decomposition", _rule_mean_decomposition),
)


def rewrite_plan(plan):
    """Apply every rewrite rule; records the names of rules that fired.
    The project node is always materialized (identity projection when no
    mean decomposes) so ``physical_agg_list`` round-trips uniformly."""
    for name, rule in REWRITE_RULES:
        if rule(plan):
            plan.rewrites.append(name)
    if not plan.project.exprs:
        # identity projection (no aggregate at all: raw-rows query)
        plan.project.exprs = [
            (out, ("slot", out)) for _in, _op, out in plan.aggregate.aggs
        ]
    return plan


def plan_groupby(filenames, groupby_cols, agg_list, where_terms=None,
                 aggregate=True, expand_filter_column=None):
    """compile + rewrite in one call (the controller's entry point)."""
    return rewrite_plan(
        compile_groupby(
            filenames, groupby_cols, agg_list, where_terms,
            aggregate=aggregate, expand_filter_column=expand_filter_column,
        )
    )


# -- fragments ---------------------------------------------------------------

def fragment_for(plan, filenames, sole=False):
    """The per-dispatch slice of a plan: what ONE CalcMessage executes.
    Travels as the message's ``plan`` binary field (pickled, like params).
    It names the work, never the kernel: the worker's
    ``ops.groupby.kernel_route`` chooses that from what it observes."""
    return {
        "v": PLAN_VERSION,
        "filenames": list(filenames),
        "groupby_cols": list(plan.groupby.keys),
        "agg_list": plan.physical_agg_list(),
        "where_terms": [list(t) for t in plan.where_terms],
        "aggregate": bool(plan.aggregate_rows),
        "expand_filter_column": plan.expand_filter_column,
        "sole": bool(sole),
    }


def fragment_to_query(fragment):
    """Rebuild the worker-side GroupByQuery from a plan fragment."""
    from bqueryd_tpu.models.query import GroupByQuery

    return GroupByQuery(
        list(fragment["groupby_cols"]),
        [list(a) for a in fragment["agg_list"]],
        [tuple(t) for t in fragment["where_terms"]],
        aggregate=fragment.get("aggregate", True),
        expand_filter_column=fragment.get("expand_filter_column"),
        sole_payload=bool(fragment.get("sole")),
    )
