"""Correlated subquery rewrite — decorrelation into joins.

The reference rewrites correlated scalar/IN/EXISTS subqueries into
(semi-)apply joins (planner/core/expression_rewriter.go buildSemiApply)
and then removes the apply where the correlation is a plain equality
(planner/core/rule_decorrelate.go). This module implements the
decorrelated forms directly for WHERE-clause subqueries — the TPC-H
Q4/Q17/Q20/Q21/Q22 shapes:

  * `EXISTS (SELECT … WHERE inner.k = outer.k AND P)`      → semi join
  * `NOT EXISTS (…)`                                       → anti join
  * `x IN (SELECT y FROM … WHERE corr)`                    → semi join
  * `x NOT IN (SELECT y …)` → anti join with the null-aware match
    condition (y = x OR x IS NULL OR y IS NULL) as a join condition —
    exactly MySQL's three-valued NOT IN: an empty per-key set passes even
    NULL x; any NULL in the set (or NULL x against a non-empty set)
    filters the row.
  * `x <cmp> (SELECT agg(…) FROM … WHERE inner.k = outer.k)` → the inner
    aggregate grouped by its correlation keys, LEFT-joined on them; the
    comparison becomes an ordinary filter over the joined row (NULL for
    missing keys ⇒ filtered, matching scalar-subquery semantics; COUNT
    slots are IFNULL'd to 0 — COUNT over an empty set is 0, not NULL).

Correlated references may appear only in Selection conjuncts of the
subquery (equality with an inner expression lifts into join keys;
anything else rides as a join `other_condition`). Correlations in deeper
positions (join ON, aggregate arguments, nested subqueries) raise a clear
PlanError rather than planning something wrong.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from tidb_tpu.errors import PlanError, SubqueryRowError
from tidb_tpu.expression import (ColumnRef, Constant, CorrelatedRef,
                                 Expression, ScalarFunc, func, lit)
from tidb_tpu.parser import ast
from tidb_tpu.planner.logical import (LogicalAggregation, LogicalDataSource,
                                      LogicalJoin, LogicalLimit, LogicalPlan,
                                      LogicalProjection, LogicalSelection,
                                      LogicalSort, LogicalWindow, Schema,
                                      SchemaColumn)

_CMP = {"eq", "ne", "lt", "le", "gt", "ge"}
_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
         "eq": "eq", "ne": "ne"}


def is_correlated(e: Expression) -> bool:
    return any(isinstance(s, CorrelatedRef) for s in e.walk())


def _plan_exprs(plan: LogicalPlan):
    if isinstance(plan, LogicalSelection):
        yield from plan.conditions
    elif isinstance(plan, LogicalProjection):
        yield from plan.exprs
    elif isinstance(plan, LogicalAggregation):
        yield from plan.group_exprs
        for a in plan.aggs:
            yield from a.args
    elif isinstance(plan, LogicalJoin):
        for l, r in plan.equi or []:
            yield l
            yield r
        yield from plan.other_conditions or []
    elif isinstance(plan, LogicalSort):
        yield from plan.by
    elif isinstance(plan, LogicalWindow):
        for d in plan.wdescs:
            yield from d.args
            yield from d.partition
            yield from d.order
    elif isinstance(plan, LogicalDataSource):
        yield from plan.filters
    for c in plan.children:
        yield from _plan_exprs(c)


def plan_is_correlated(plan: LogicalPlan) -> bool:
    return any(is_correlated(e) for e in _plan_exprs(plan))


def _subst_corr(e: Expression) -> Expression:
    """CorrelatedRef(i) → ColumnRef(i): outer columns are the left prefix
    of the joined schema."""
    if isinstance(e, CorrelatedRef):
        return ColumnRef(e.index, e.ftype, e.name)
    if isinstance(e, ScalarFunc):
        return e.rebuild([_subst_corr(a) for a in e.args])
    return e


def _shift_inner(e: Expression, delta: int) -> Expression:
    """Shift INNER ColumnRefs by delta; CorrelatedRefs become outer
    ColumnRefs (unshifted)."""
    if isinstance(e, CorrelatedRef):
        return ColumnRef(e.index, e.ftype, e.name)
    if isinstance(e, ColumnRef):
        return ColumnRef(e.index + delta, e.ftype, e.name)
    if isinstance(e, ScalarFunc):
        return e.rebuild([_shift_inner(a, delta) for a in e.args])
    return e


def _strip(plan: LogicalPlan, corr_out: List[Expression],
           for_exists: bool) -> LogicalPlan:
    """Descend through the subquery's root operators, removing correlated
    Selection conjuncts into corr_out. For EXISTS the row-shaping wrappers
    (Projection/Sort/Limit≥1) are dropped entirely — existence doesn't
    depend on them."""
    if isinstance(plan, LogicalSelection):
        keep = [c for c in plan.conditions if not is_correlated(c)]
        corr_out.extend(c for c in plan.conditions if is_correlated(c))
        child = _strip(plan.children[0], corr_out, for_exists)
        return LogicalSelection(keep, child) if keep else child
    if for_exists:
        if isinstance(plan, (LogicalProjection, LogicalSort)):
            return _strip(plan.children[0], corr_out, for_exists)
        if isinstance(plan, LogicalLimit):
            if plan.offset:
                # per-outer-row LIMIT/OFFSET cannot decorrelate into a
                # plain semi join (existence would need ≥ offset+1 rows)
                raise CorrelationError(
                    "correlated EXISTS with LIMIT OFFSET")
            # count==0 is folded to a constant by rewrite_exists; any
            # other LIMIT is irrelevant to existence
            return _strip(plan.children[0], corr_out, for_exists)
    return plan


def _lift(corr: List[Expression], inner_schema_len: int
          ) -> Tuple[List[Tuple[Expression, Expression]], List[Expression]]:
    """Split correlated conjuncts into equi pairs (outer_expr, inner_expr)
    and residual join conditions over the concatenated schema."""
    equi: List[Tuple[Expression, Expression]] = []
    other: List[Expression] = []
    for c in corr:
        if isinstance(c, ScalarFunc) and c.op == "eq":
            l, r = c.args
            l_corr, r_corr = is_correlated(l), is_correlated(r)
            l_inner = bool(l.references())
            r_inner = bool(r.references())
            if l_corr and not l_inner and r_inner and not r_corr:
                equi.append((_subst_corr(l), r))
                continue
            if r_corr and not r_inner and l_inner and not l_corr:
                equi.append((_subst_corr(r), l))
                continue
        other.append(c)
    return equi, other


class CorrelationError(PlanError):
    pass


def _check_fully_decorrelated(plan: LogicalPlan):
    if plan_is_correlated(plan):
        raise CorrelationError(
            "correlated subquery is too complex: outer references are "
            "only supported in the subquery's WHERE clause")


def _run_uncorrelated(builder, inner: LogicalPlan):
    """Execute an already-built uncorrelated subquery plan (avoids the
    re-plan/re-execute of handing the AST back to the eager path — which
    would also re-run any nested subqueries it contains)."""
    run_plan = getattr(builder.subq, "run_plan", None) \
        if builder.subq is not None else None
    if run_plan is None:
        return None
    return run_plan(inner)


def _out_ref(plan: LogicalPlan, i: int) -> ColumnRef:
    c = plan.schema.columns[i]
    return ColumnRef(i, c.ftype, c.name)


def _grouped_keys_over_large_scan(builder, inner: LogicalPlan) -> bool:
    """Is `inner` SELECT k … GROUP BY … [HAVING …] with k one of its group
    keys, over a base table whose live rows clear the device row
    threshold? Such a subquery is worth a semijoin on the device; smaller
    ones stay on the eager path (run once, folded into constants)."""
    ctx = getattr(builder, "ctx", None)
    if ctx is None or not getattr(ctx, "use_tpu", False) \
            or len(inner.schema) != 1:
        return False
    node = inner
    if not (isinstance(node, LogicalProjection)
            and isinstance(node.exprs[0], ColumnRef)):
        return False
    col = node.exprs[0].index
    node = node.children[0]
    while isinstance(node, LogicalSelection):
        node = node.children[0]
    if not (isinstance(node, LogicalAggregation)
            and col < len(node.group_exprs)):
        return False
    threshold = int(getattr(ctx, "tpu_row_threshold", 0))

    def scans(p):
        if isinstance(p, LogicalDataSource):
            yield p
        for c in p.children:
            yield from scans(c)

    return any(ctx.table_row_count(d.table.id) >= threshold
               for d in scans(node))


def rewrite_exists(builder, outer: LogicalPlan, node: ast.ExistsExpr
                   ) -> Optional[Tuple[LogicalPlan, List[Expression]]]:
    """EXISTS/NOT EXISTS conjunct → semi/anti join; uncorrelated
    subqueries execute once on their already-built plan."""
    inner = builder.build_subquery_plan(node.subquery.select, outer.schema)
    if not plan_is_correlated(inner):
        ran = _run_uncorrelated(builder, inner)
        if ran is None:
            return None                  # no evaluator: eager path
        rows, _ = ran
        val = bool(rows) != bool(node.negated)
        return outer, [lit(val)]
    # EXISTS (… LIMIT 0) is constant FALSE regardless of correlation
    probe = inner
    while isinstance(probe, (LogicalProjection, LogicalSort,
                             LogicalSelection)):
        probe = probe.children[0]
    if isinstance(probe, LogicalLimit) and probe.count == 0:
        return outer, [lit(bool(node.negated))]
    corr: List[Expression] = []
    src = _strip(inner, corr, for_exists=True)
    _check_fully_decorrelated(src)
    equi, other = _lift(corr, len(src.schema))
    other = [_shift_inner(c, len(outer.schema)) for c in other]
    kind = "anti" if node.negated else "semi"
    return LogicalJoin(kind, outer, src, equi, other), []


def rewrite_in(builder, outer: LogicalPlan, node: ast.InExpr,
               x: Expression) -> Optional[Tuple[LogicalPlan,
                                                List[Expression]]]:
    """Correlated `x [NOT] IN (SELECT y …)` → semi/anti join on x=y (plus
    lifted correlations); NOT IN gets the null-aware condition."""
    inner = builder.build_subquery_plan(node.subquery.select, outer.schema)
    if not plan_is_correlated(inner):
        if not node.negated and _grouped_keys_over_large_scan(builder, inner):
            # x IN (SELECT k … GROUP BY k [HAVING …]) over a device-sized
            # scan: a semijoin whose build side is the aggregate (unique
            # on k). Nothing runs at plan time; the device fragments keep
            # the qualifying keys in HBM and the outer tree probes them
            return (LogicalJoin("semi", outer, inner,
                                [(x, _out_ref(inner, 0))], []), [])
        ran = _run_uncorrelated(builder, inner)
        if ran is None:
            return None                  # no evaluator: eager path
        rows, ftypes = ran
        if len(ftypes) != 1:
            raise PlanError("Operand should contain 1 column(s)")
        if not rows:
            val = bool(node.negated)     # x IN (∅) is FALSE even for NULL x
            return outer, [lit(val)]
        items = [Constant(r[0], ftypes[0]) for r in rows]
        cond = func("in", x, *items)
        return outer, [func("not", cond) if node.negated else cond]
    if len(inner.schema) != 1:
        raise PlanError("Operand should contain 1 column(s)")
    if is_correlated(x):
        raise CorrelationError("correlated IN probe expression")
    # peel the value projection to reach the source row space; correlated
    # conds above the projection (not produced by build_select for this
    # shape) are unsupported
    if not isinstance(inner, LogicalProjection):
        raise CorrelationError("unsupported correlated IN subquery shape")
    probe_y: Expression = inner.exprs[0]
    if is_correlated(probe_y):
        raise CorrelationError("correlated IN value expression")
    corr: List[Expression] = []
    src = _strip(inner.children[0], corr, for_exists=False)
    _check_fully_decorrelated(src)
    equi, other = _lift(corr, len(src.schema))
    lw = len(outer.schema)
    other = [_shift_inner(c, lw) for c in other]
    if node.negated:
        # null-aware anti join: match when y = x OR x IS NULL OR y IS NULL
        xj = _subst_corr(x)                        # outer space == joined
        yj = _shift_inner(probe_y, lw)
        na = func("or", func("or", func("eq", xj, yj),
                             func("isnull", xj)), func("isnull", yj))
        return (LogicalJoin("anti", outer, src, equi, other + [na]), [])
    return (LogicalJoin("semi", outer, src, equi + [(x, probe_y)], other),
            [])


def rewrite_scalar_cmp(builder, outer: LogicalPlan, op: str,
                       x_ast: ast.ExprNode, sub: ast.Subquery,
                       flip: bool) -> Optional[Tuple[LogicalPlan,
                                                     List[Expression]]]:
    """Correlated `x <cmp> (SELECT agg(…) WHERE corr)` → group the inner
    aggregate by its correlation keys, LEFT-join, filter on the joined
    value column."""
    inner = builder.build_subquery_plan(sub.select, outer.schema)
    if not plan_is_correlated(inner):
        ran = _run_uncorrelated(builder, inner)
        if ran is None:
            return None                  # no evaluator: eager path
        rows, ftypes = ran
        if len(ftypes) != 1:
            raise PlanError("Operand should contain 1 column(s)")
        if len(rows) > 1:
            raise SubqueryRowError("Subquery returns more than 1 row")
        val = Constant(rows[0][0] if rows else None,
                       ftypes[0].with_nullable(True))
        x_rw = builder.make_rewriter(outer.schema).rewrite(x_ast)
        return outer, [func(_FLIP[op] if flip else op, x_rw, val)]
    if len(inner.schema) != 1:
        raise PlanError("Operand should contain 1 column(s)")
    # expected shape: Projection(value over agg schema) ← Aggregation(no
    # groups) ← [Selection w/ corr] ← source
    if not isinstance(inner, LogicalProjection):
        raise CorrelationError("unsupported correlated scalar subquery")
    value_expr = inner.exprs[0]
    agg = inner.children[0]
    if not isinstance(agg, LogicalAggregation) or agg.group_exprs:
        raise CorrelationError(
            "correlated scalar subquery must be a single ungrouped "
            "aggregate")
    corr: List[Expression] = []
    src = _strip(agg.children[0], corr, for_exists=False)
    _check_fully_decorrelated(src)
    if any(is_correlated(a) for d in agg.aggs for a in d.args) or \
            is_correlated(value_expr):
        raise CorrelationError("correlated aggregate argument")
    equi, other = _lift(corr, len(src.schema))
    if other or not equi:
        raise CorrelationError(
            "correlated scalar subquery supports only equality "
            "correlation")
    n = builder.next_subq_id()
    group_exprs = [ie for _, ie in equi]
    group_names = [f"_subq{n}_k{i}" for i in range(len(group_exprs))]
    new_agg = LogicalAggregation(group_exprs, agg.aggs, src, group_names)
    ng = len(group_exprs)
    # rebase the value expr: old agg schema was [aggs…] (no groups); new
    # schema is [groups…, aggs…]
    count_slots = {i for i, d in enumerate(agg.aggs)
                   if d.name == "count"}

    def rebase(e: Expression) -> Expression:
        if isinstance(e, ColumnRef):
            return ColumnRef(e.index + ng, e.ftype, e.name)
        if isinstance(e, ScalarFunc):
            return e.rebuild([rebase(a) for a in e.args])
        return e

    def uses_count(e: Expression) -> bool:
        return any(isinstance(s, ColumnRef) and s.index in count_slots
                   for s in e.walk())

    def empty_value(e: Expression) -> Expression:
        """The value the subquery yields over an EMPTY set: COUNT slots
        read 0, every other aggregate reads NULL."""
        if isinstance(e, ColumnRef):
            if e.index in count_slots:
                return lit(0, e.ftype)
            return Constant(None, e.ftype.with_nullable(True))
        if isinstance(e, ScalarFunc):
            out = e.rebuild([empty_value(a) for a in e.args])
            out.ftype = e.ftype.with_nullable(True)
            return out
        return e

    value = rebase(value_expr)
    proj_exprs = [ColumnRef(i, ge.ftype, group_names[i])
                  for i, ge in enumerate(group_exprs)] + [value]
    proj_names = group_names + [f"_subq{n}_v"]
    needs_marker = uses_count(value_expr)
    if needs_marker:
        proj_exprs.append(lit(1))
        proj_names.append(f"_subq{n}_m")
    proj = LogicalProjection(proj_exprs, proj_names, new_agg,
                             [None] * len(proj_exprs))
    lw = len(outer.schema)
    join_equi = [(oe, ColumnRef(i, ge.ftype, group_names[i]))
                 for i, (oe, ge) in enumerate(equi)]
    joined = LogicalJoin("left", outer, proj, join_equi, [])
    # the comparison over the joined row (value col after the group keys)
    vref: Expression = ColumnRef(lw + ng, value.ftype.with_nullable(True),
                                 f"_subq{n}_v")
    if needs_marker:
        # a missing join key means the correlated set was EMPTY — the
        # subquery still yields a value there (COUNT()=0); the marker
        # column's null-extension detects that case
        mref = ColumnRef(lw + ng + 1, proj_exprs[-1].ftype.with_nullable(
            True), f"_subq{n}_m")
        vref = ScalarFunc("if", [func("isnull", mref),
                                 empty_value(value_expr), vref],
                          vref.ftype.with_nullable(True))
    x_rw = builder.make_rewriter(outer.schema).rewrite(x_ast)
    if is_correlated(x_rw):
        raise CorrelationError("correlated comparison operand")
    cond = func(_FLIP[op] if flip else op, x_rw, vref)
    return joined, [cond]
