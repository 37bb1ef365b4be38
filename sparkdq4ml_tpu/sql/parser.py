"""Minimal SQL subset: SELECT / alias / CAST / function calls / WHERE.

Exactly the surface the reference app exercises (SURVEY.md §2.2 "SQL over
temp view"):

    SELECT cast(guest as int) guest, price_no_min AS price
    FROM price WHERE price_no_min > 0

plus the obvious closures of that grammar (arithmetic, AND/OR/NOT, comparison
chains, parentheses, literals, registered UDF calls). Queries compile to the
same :mod:`~sparkdq4ml_tpu.ops.expressions` trees the fluent API builds, so SQL
filtering is mask-AND like ``Frame.filter`` — one fused XLA predicate, not a
row interpreter.

Grammar (recursive descent):

    query      := [WITH ident AS '(' set ')' (',' ident AS '(' set ')')*] set
    set        := select ((UNION [ALL] | INTERSECT | EXCEPT) select)*
    select     := SELECT [DISTINCT] select_list FROM relation join*
                  [WHERE or_expr]
                  [GROUP BY (expr|position),* | ROLLUP/CUBE '(' ident,* ')']
                  [HAVING or_expr]
                  [ORDER BY (expr|position) [ASC|DESC]
                   [NULLS FIRST|LAST],*]
                  [LIMIT n] [OFFSET m]
    relation   := ident [[AS] ident] | '(' set ')' [AS] [ident]
                  -- derived table; aliases scope qualified refs a.col
    join       := [INNER|LEFT [OUTER|SEMI|ANTI]|RIGHT [OUTER]|FULL [OUTER]
                  |CROSS] JOIN relation
                  (ON ident '=' ident | USING '(' ident,* ')')
    select_list:= '*' | item (',' item)*
    item       := expr [OVER window] [[AS] ident]
    window     := '(' [PARTITION BY ident,*] [ORDER BY ident [ASC|DESC],*]
                      [(ROWS|RANGE) BETWEEN bound AND bound] ')'
    bound      := UNBOUNDED (PRECEDING|FOLLOWING) | CURRENT ROW
                  | int (PRECEDING|FOLLOWING)
                  -- after a ranking fn (ROW_NUMBER/RANK/DENSE_RANK/
                  -- PERCENT_RANK/CUME_DIST/NTILE/LAG/LEAD) or an aggregate;
                  -- default frame RANGE UNBOUNDED PRECEDING..CURRENT ROW
    or_expr    := and_expr (OR and_expr)*
    and_expr   := not_expr (AND not_expr)*
    not_expr   := NOT not_expr | cmp
    cmp        := add ((= | == | != | <> | < | <= | > | >=) add)?
                | add IS [NOT] NULL
                | add [NOT] IN '(' (or_expr,* | set) ')'
                | add [NOT] BETWEEN add AND add
                | add [NOT] LIKE 'pattern'
                | EXISTS '(' set ')'          -- uncorrelated subqueries
    add        := mul (('+'|'-') mul)*
    mul        := unary (('*'|'/') unary)*
    unary      := '-' unary | atom
    atom       := number | 'string' | TRUE | FALSE | NULL
                | CAST '(' expr AS ident ')'
                | CASE (WHEN or_expr THEN or_expr)+ [ELSE or_expr] END
                | ident '(' [expr (',' expr)*] ')'     -- UDF or builtin fn
                | ident | '(' or_expr ')'
                | '(' set ')'                 -- scalar subquery (1 col,
                                              -- <=1 row; null when empty)
"""

from __future__ import annotations

import copy
import math
import re
from typing import Optional

from ..ops import expressions as E
from ..utils import observability as _obs
from ..utils.profiling import counters

_TOKEN_RE = re.compile(
    r"\s*(?:"
    r"(?P<number>\d+\.\d*(?:[eE][+-]?\d+)?|\.\d+(?:[eE][+-]?\d+)?|\d+(?:[eE][+-]?\d+)?)"
    r"|(?P<string>'(?:[^']|'')*')"
    r"|(?P<ident>[A-Za-z_][A-Za-z_0-9]*)"
    r"|(?P<op>->|\|\||<=|>=|<>|!=|==|=|<|>|\+|-|\*|/|%|\(|\)|,|\.)"
    r")")

_KEYWORDS = {"select", "from", "where", "as", "and", "or", "not", "cast",
             "true", "false", "null", "group", "by", "order", "limit",
             "asc", "desc", "join", "inner", "left", "right", "full",
             "outer", "cross", "on", "using", "case", "when", "then",
             "else", "end", "is", "in", "between", "like", "having",
             "distinct", "union", "all"}
# OVER / PARTITION are contextual (recognized only after a function call /
# inside a window spec), so columns named "over"/"partition" keep working.

_AGG_FNS = {"count", "sum", "avg", "mean", "min", "max", "stddev", "variance",
            "stddev_pop", "var_pop", "median", "mode",
            "collect_list", "collect_set", "first", "last",
            "skewness", "kurtosis"}
# percentile_approx(col, p[, accuracy]) takes a literal percentage
_AGG_FNS_PCT = {"percentile_approx", "approx_percentile"}
# two-column aggregates: CORR(a, b), COVAR_SAMP(a, b), COVAR_POP(a, b)
_AGG_FNS_2 = {"corr", "covar_samp", "covar_pop", "max_by", "min_by"}
# boolean/conditional aggregates desugared into agg + post-agg forms
_BOOL_AGGS = {"count_if", "any", "some", "every", "bool_or", "bool_and"}
_WINDOW_FNS = {"row_number", "rank", "dense_rank", "percent_rank",
               "cume_dist", "ntile", "lag", "lead",
               "first_value", "last_value", "nth_value"}


def _lit_value(expr, what: str):
    """Extract a literal value, accepting a leading unary minus (``-1``
    parses as UnaryOp('-', Lit) — still a literal to the user)."""
    if isinstance(expr, E.Lit):
        return expr.value
    if (isinstance(expr, E.UnaryOp) and expr.op == "-"
            and isinstance(expr.child, E.Lit)):
        return -expr.child.value
    raise ValueError(f"{what} must be a literal")


def _check_agg_args(fn: str, col, args) -> None:
    """Aggregate argument rule, shared by the plain and windowed (OVER)
    paths: a single column name, or bare ``*``/no args for COUNT only."""
    if col is None and not (fn.lower() == "count" and not args):
        raise ValueError(f"{fn} argument must be * or a column name")


class _AggRef(E.Expr):
    """A parsed aggregate appearing inside select-list arithmetic
    (``SELECT max(p) - min(p)``): carries the AggExpr; rewritten to a
    Col over the aggregated output before any eval."""

    def __init__(self, agg):
        self.agg = agg

    @property
    def name(self) -> str:
        return self.agg.name

    def __str__(self):
        return self.agg.name

    def eval(self, frame):
        raise ValueError(
            "aggregate expressions are only valid in a SQL select list — "
            "this tree still holds an unresolved aggregate reference")


class PostAggItem:
    """A select item that is an expression OVER aggregate results
    (``max(p) - min(p) AS spread``): ``expr`` references the aggregated
    output columns of ``aggs``, and is computed on the aggregated frame."""

    __slots__ = ("expr", "aggs", "_name")

    def __init__(self, expr, aggs, name=None):
        self.expr = expr
        self.aggs = list(aggs)
        self._name = name

    @property
    def name(self) -> str:
        return self._name if self._name is not None else str(self.expr)

    def alias(self, name: str) -> "PostAggItem":
        return PostAggItem(self.expr, self.aggs, name)


class _Token:
    __slots__ = ("kind", "value")

    def __init__(self, kind: str, value: str):
        self.kind = kind
        self.value = value

    def __repr__(self):
        return f"{self.kind}:{self.value}"


def tokenize(sql: str) -> list[_Token]:
    tokens, pos = [], 0
    while pos < len(sql):
        if sql[pos:].strip() == "":
            break
        m = _TOKEN_RE.match(sql, pos)
        if m is None or m.end() == pos:
            raise ValueError(f"SQL syntax error near: {sql[pos:pos+20]!r}")
        pos = m.end()
        if m.group("number") is not None:
            tokens.append(_Token("number", m.group("number")))
        elif m.group("string") is not None:
            tokens.append(_Token("string", m.group("string")[1:-1].replace("''", "'")))
        elif m.group("ident") is not None:
            ident = m.group("ident")
            kind = "kw" if ident.lower() in _KEYWORDS else "ident"
            tokens.append(_Token(kind, ident))
        else:
            tokens.append(_Token("op", m.group("op")))
    tokens.append(_Token("eof", ""))
    return tokens


class _Parser:
    def __init__(self, tokens: list[_Token]):
        self.toks = tokens
        self.i = 0

    def peek(self) -> _Token:
        return self.toks[self.i]

    def next(self) -> _Token:
        t = self.toks[self.i]
        self.i += 1
        return t

    def accept(self, kind: str, value: Optional[str] = None) -> Optional[_Token]:
        t = self.peek()
        if t.kind == kind and (value is None or t.value.lower() == value):
            return self.next()
        return None

    def expect(self, kind: str, value: Optional[str] = None) -> _Token:
        t = self.accept(kind, value)
        if t is None:
            raise ValueError(f"SQL parse error: expected {value or kind}, "
                             f"got {self.peek().value!r}")
        return t

    # -- query -------------------------------------------------------------
    def parse_relation(self):
        """A FROM/JOIN source: a view name (with optional ``[AS] alias``),
        or a parenthesized derived table ``(SELECT ...) [AS] alias``.
        Returns ``(source, alias)`` where source is a name or Query."""
        if (self.peek().kind == "op" and self.peek().value == "("
                and self.toks[self.i + 1].kind == "kw"
                and self.toks[self.i + 1].value.lower() == "select"):
            self.next()
            sub = self.parse_set_expr()
            self.expect("op", ")")
            self.accept("kw", "as")
            alias = None
            if (self.peek().kind == "ident"
                    and not self._ident_starts_clause()):
                alias = self.next().value
            return DerivedTable(sub, alias), alias
        view = self.expect("ident").value
        alias = None
        if self.accept("kw", "as"):
            alias = self.expect("ident").value
        elif self.peek().kind == "ident" and not self._ident_starts_clause():
            alias = self.next().value
        return view, alias

    def _ident_starts_clause(self) -> bool:
        """Contextual idents that begin a clause rather than alias a
        relation (ON/USING/keywords are kw-kind already; these are the
        ident-kind clause openers, so relations cannot be aliased to
        these names without AS)."""
        return self.peek().value.lower() in ("semi", "anti", "intersect",
                                             "except", "offset")

    def parse_query(self):
        self.expect("kw", "select")
        distinct = bool(self.accept("kw", "distinct"))
        items = self.parse_select_list()
        # Spark allows FROM-less SELECT (``SELECT 1``, ``SELECT
        # current_date()``): the projection runs over OneRowRelation.
        view = None
        view_alias = None
        joins = []
        if self.accept("kw", "from"):
            view, view_alias = self.parse_relation()
            while True:
                if self.accept("op", ","):
                    # ``FROM a, b``: an inner join whose keys are the
                    # WHERE clause's equalities (resolve_join_keys)
                    rel, rel_alias = self.parse_relation()
                    joins.append((rel, "inner", [], rel_alias))
                    continue
                join = self.parse_join()
                if join is None:
                    break
                joins.append(join)
        where = None
        if self.accept("kw", "where"):
            where = self.parse_or()
        group_by = []
        group_mode = "group"
        if self.accept("kw", "group"):
            self.expect("kw", "by")
            # GROUP BY ROLLUP(a, b) / CUBE(a, b) — Spark subtotal grouping
            nxt = self.peek()
            if (nxt.kind == "ident" and nxt.value.lower() in ("rollup", "cube")
                    and self.toks[self.i + 1].kind == "op"
                    and self.toks[self.i + 1].value == "("):
                group_mode = self.next().value.lower()
                self.expect("op", "(")
                group_by.append(self.expect("ident").value)
                while self.accept("op", ","):
                    group_by.append(self.expect("ident").value)
                self.expect("op", ")")
            else:
                group_by.append(self.parse_group_item())
                while self.accept("op", ","):
                    group_by.append(self.parse_group_item())
        having = None
        if self.accept("kw", "having"):
            having = self.parse_or()
        order_by = []
        if self.accept("kw", "order"):
            self.expect("kw", "by")
            order_by.append(self.parse_sort_item())
            while self.accept("op", ","):
                order_by.append(self.parse_sort_item())
        limit = None
        offset = 0
        if self.accept("kw", "limit"):
            limit = int(self.expect("number").value)
        if self.accept("ident", "offset"):     # LIMIT n OFFSET m / OFFSET m
            offset = int(self.expect("number").value)
        q = Query(items, view, where, group_by, order_by, limit, joins,
                  distinct=distinct, having=having)
        q.group_mode = group_mode
        q.view_alias = view_alias
        q.offset = offset
        return q

    def parse_set_expr(self):
        """query ((UNION [ALL] | INTERSECT | EXCEPT) query)* — set
        operators over identical schemas, left-associative (standard
        SQL's higher INTERSECT precedence is not modeled; parenthesize
        to force grouping). No EOF expectation, so it also parses
        parenthesized subqueries."""
        q = self.parse_query()
        while True:
            if self.accept("kw", "union"):
                dedup = not self.accept("kw", "all")
                q.unions.append(("union_all" if not dedup else "union",
                                 self.parse_query()))
            elif (self.peek().kind == "ident"
                  and self.peek().value.lower() in ("intersect", "except")):
                op = self.next().value.lower()
                q.unions.append((op, self.parse_query()))
            else:
                return q

    def parse_union_query(self):
        """Top-level statement: ``[WITH name AS (query), ...] set_expr``.
        WITH is contextual (like OVER/PARTITION) so columns named "with"
        keep working: it is only recognized as the first token."""
        ctes = []
        if (self.peek().kind == "ident"
                and self.peek().value.lower() == "with"):
            self.next()
            while True:
                name = self.expect("ident").value
                self.expect("kw", "as")
                self.expect("op", "(")
                ctes.append((name, self.parse_set_expr()))
                self.expect("op", ")")
                if not self.accept("op", ","):
                    break
        q = self.parse_set_expr()
        q.ctes = ctes
        self.expect("eof")
        return q

    def parse_join(self):
        """``[INNER|LEFT [OUTER]|RIGHT [OUTER]|FULL [OUTER]|CROSS] JOIN view
        (ON a = b | USING (k, ...))`` → ``(view, how, keys, alias)``; a key
        is a shared column name or, from ``ON a = b`` over two names, the
        pair ``(a, b)``."""
        how = None
        for kw in ("inner", "left", "right", "full", "cross"):
            if self.accept("kw", kw):
                how = {"full": "outer"}.get(kw, kw)
                if kw == "left":
                    # LEFT SEMI / LEFT ANTI (contextual idents, so columns
                    # named "semi"/"anti" keep working elsewhere)
                    if self.accept("ident", "semi"):
                        how = "left_semi"
                    elif self.accept("ident", "anti"):
                        how = "left_anti"
                self.accept("kw", "outer")
                break
        if how is None:
            if not self.accept("kw", "join"):
                return None
            how = "inner"
        else:
            self.expect("kw", "join")
        view, alias = self.parse_relation()
        keys: list[str] = []
        if how != "cross":
            if self.accept("kw", "using"):
                self.expect("op", "(")
                keys.append(self.expect("ident").value)
                while self.accept("op", ","):
                    keys.append(self.expect("ident").value)
                self.expect("op", ")")
            else:
                self.expect("kw", "on")
                a = self._parse_maybe_dotted()
                self.expect("op", "=")
                b = self._parse_maybe_dotted()
                # qualified ON (``ON t.k = g.k``) reduces to the shared
                # base column — the engine's joins are USING-shaped; two
                # different names stay a pair until resolve_join_keys
                # knows which relation holds which
                a_col = a.rpartition(".")[2]
                b_col = b.rpartition(".")[2]
                keys.append(a_col if a_col == b_col else (a, b))
        return (view, how, keys, alias)

    def _parse_maybe_dotted(self) -> str:
        name = self.expect("ident").value
        while self.accept("op", "."):
            name += "." + self.expect("ident").value
        return name

    def parse_order_item(self):
        """Window-spec ORDER BY: plain column names only (a window's sort
        key is a physical column of the partition)."""
        name = self.expect("ident").value
        ascending = True
        if self.accept("kw", "desc"):
            ascending = False
        else:
            self.accept("kw", "asc")
        return (name, ascending)

    def parse_group_item(self):
        """GROUP BY key: a column name, a 1-based select-item position
        (``GROUP BY 1``), or any expression (``GROUP BY cast(p as int)``);
        non-name keys resolve at execute. ROLLUP/CUBE keep plain names."""
        expr = self.parse_or()
        if isinstance(expr, E.Col):
            return expr.name
        if (isinstance(expr, E.Lit) and isinstance(expr.value, int)
                and not isinstance(expr.value, bool)):
            return expr.value
        return expr

    def parse_sort_item(self):
        """Query-level ORDER BY key: a column name, a 1-based select-item
        position (``ORDER BY 2``), or any expression — including
        aggregates (``ORDER BY count(*) DESC``), resolved at execute.
        ``NULLS FIRST|LAST`` (contextual idents) pins null placement;
        the default is Spark's asc→first / desc→last."""
        expr = self.parse_or()
        ascending = True
        if self.accept("kw", "desc"):
            ascending = False
        else:
            self.accept("kw", "asc")
        nulls_first = None
        if self.accept("ident", "nulls"):
            if self.accept("ident", "first"):
                nulls_first = True
            elif self.accept("ident", "last"):
                nulls_first = False
            else:
                raise ValueError("expected FIRST or LAST after NULLS")
        if (isinstance(expr, E.Lit) and isinstance(expr.value, int)
                and not isinstance(expr.value, bool)):
            if nulls_first is not None:
                raise ValueError("NULLS FIRST/LAST with a positional "
                                 "ORDER BY key is not supported")
            return (expr.value, ascending)
        if nulls_first is not None:
            return (E.SortOrder(expr, ascending, nulls_first), ascending)
        if isinstance(expr, E.Col):
            return (expr.name, ascending)
        return (expr, ascending)

    def parse_select_list(self):
        items = [self.parse_select_item()]
        while self.accept("op", ","):
            items.append(self.parse_select_item())
        return items

    def parse_select_item(self):
        # ``*`` may appear alongside other items (``SELECT *, a+b AS c``)
        if self.accept("op", "*"):
            return "*"
        return self.parse_item()

    def parse_window_spec(self):
        """``( [PARTITION BY ident,*] [ORDER BY item,*]
        [ROWS|RANGE BETWEEN bound AND bound] )`` after OVER, with
        ``bound := UNBOUNDED PRECEDING|FOLLOWING | CURRENT ROW |
        <n> PRECEDING|FOLLOWING`` — the same frames as the fluent
        ``rowsBetween``/``rangeBetween`` API."""
        from ..frame.window import WindowSpec

        self.expect("op", "(")
        partition, order = [], []
        if self.accept("ident", "partition"):
            self.expect("kw", "by")
            partition.append(self.expect("ident").value)
            while self.accept("op", ","):
                partition.append(self.expect("ident").value)
        if self.accept("kw", "order"):
            self.expect("kw", "by")
            order.append(self.parse_order_item())
            while self.accept("op", ","):
                order.append(self.parse_order_item())
        spec = WindowSpec(partition, order)
        kind = None
        if self.accept("ident", "rows"):
            kind = "rows"
        elif self.accept("ident", "range"):
            kind = "range"
        if kind is not None:
            self.expect("kw", "between")
            lo = self._parse_frame_bound()
            self.expect("kw", "and")
            hi = self._parse_frame_bound()
            spec = (spec.rows_between(lo, hi) if kind == "rows"
                    else spec.range_between(lo, hi))
        self.expect("op", ")")
        return spec

    def _parse_frame_bound(self) -> int:
        from ..frame.window import Window

        if self.accept("ident", "unbounded"):
            if self.accept("ident", "preceding"):
                return Window.unbounded_preceding
            self.expect("ident", "following")
            return Window.unbounded_following
        if self.accept("ident", "current"):
            self.expect("ident", "row")
            return 0
        n = self.expect("number").value
        if float(n) != int(float(n)):
            raise ValueError(f"SQL parse error: frame bound must be an "
                             f"integer, got {n!r}")
        off = int(float(n))
        if self.accept("ident", "preceding"):
            return -off
        self.expect("ident", "following")
        return off

    def _build_window_fn(self, fn: str, col, args: list):
        """Bind a parsed ``fn(args...)`` to a WindowFunction (pre-OVER)."""
        from ..frame import window as W

        fl = fn.lower()
        if fl in _AGG_FNS:
            from ..frame.aggregates import AggExpr

            _check_agg_args(fn, col, args)
            return AggExpr(fn, col).over  # bound later by caller
        if fl == "ntile":
            if len(args) != 1 or not isinstance(args[0], E.Lit):
                raise ValueError("ntile(n) requires an integer literal")
            return W.ntile(int(args[0].value)).over
        if fl in _AGG_FNS_PCT:
            raise ValueError(
                f"windowed {fl}() is not supported (Spark <=2.x SQL "
                "windows the running aggregates only)")
        if fl in ("first_value", "last_value"):
            if len(args) != 1 or not isinstance(args[0], E.Col):
                raise ValueError(f"{fl}(col) requires a column argument")
            return getattr(W, fl)(args[0].name).over
        if fl == "nth_value":
            if (len(args) != 2 or not isinstance(args[0], E.Col)):
                raise ValueError("nth_value(col, n) requires a column and "
                                 "an integer literal")
            return W.nth_value(args[0].name,
                               int(_lit_value(args[1], "nth_value n"))).over
        if fl in ("lag", "lead"):
            if not args or not isinstance(args[0], E.Col):
                raise ValueError(f"{fl}(col[, offset[, default]]) requires a "
                                 "column first argument")
            offset = 1
            default = None
            if len(args) > 1:
                offset = int(_lit_value(args[1], f"{fl} offset"))
            if len(args) > 2:
                default = _lit_value(args[2], f"{fl} default")
            builder = W.lag if fl == "lag" else W.lead
            return builder(args[0].name, offset, default).over
        if args:
            raise ValueError(f"{fl}() takes no arguments")
        return getattr(W, fl)().over

    def parse_item(self):
        # aggregate or window fn at top level: COUNT(*), AVG(price),
        # COUNT(DISTINCT guest), CORR(a, b), ROW_NUMBER() OVER (...),
        # SUM(price) OVER (...), ...
        t = self.peek()
        if (t.kind == "ident"
                and t.value.lower() in (_AGG_FNS | _AGG_FNS_2
                                        | _AGG_FNS_PCT | _WINDOW_FNS
                                        | _BOOL_AGGS
                                        | {"approx_count_distinct"})
                and self.toks[self.i + 1].kind == "op"
                and self.toks[self.i + 1].value == "("):
            from ..frame.aggregates import AggExpr, AggOfExpr

            fn = self.next().value
            self.expect("op", "(")
            col = None
            args: list = []
            distinct = False
            if not self.accept("op", ")"):
                if self.accept("op", "*"):
                    pass
                else:
                    distinct = bool(self.accept("kw", "distinct"))
                    args.append(self.parse_or())
                    while self.accept("op", ","):
                        args.append(self.parse_or())
                self.expect("op", ")")
            if len(args) == 1 and isinstance(args[0], E.Col):
                col = args[0].name
            if distinct:
                if fn.lower() not in ("count", "sum") or col is None:
                    raise ValueError(
                        "DISTINCT is supported in COUNT(DISTINCT col) and "
                        "SUM(DISTINCT col)")
                expr = AggExpr(f"{fn.lower()}_distinct", col)
            elif self.accept("ident", "over"):
                make = self._build_window_fn(fn, col, args)
                expr = make(self.parse_window_spec())
            elif fn.lower() in _AGG_FNS_2:
                if (len(args) != 2 or not all(isinstance(a, E.Col)
                                              for a in args)):
                    raise ValueError(f"{fn}(col1, col2) takes two columns")
                expr = AggExpr(fn, args[0].name, column2=args[1].name)
            elif fn.lower() == "approx_count_distinct":
                if not args or not isinstance(args[0], E.Col):
                    raise ValueError(
                        "approx_count_distinct(col[, rsd]) takes a column")
                from ..frame.aggregates import \
                    approx_count_distinct as _acd

                rsd = (float(_lit_value(args[1], "rsd"))
                       if len(args) > 1 else 0.05)
                expr = _acd(args[0].name, rsd)
            elif fn.lower() in _BOOL_AGGS:
                if len(args) != 1:
                    raise ValueError(f"{fn}(predicate) takes one argument")
                pred = args[0]
                flag = E.CaseWhen([(pred, E.Lit(1))], E.Lit(0))
                low = fn.lower()
                if low == "count_if":
                    expr = _AggRef(AggOfExpr(
                        "sum", flag, alias=f"count_if({pred})"))
                else:
                    # any/some/bool_or ≡ max(flag) > 0;
                    # every/bool_and ≡ min(flag) > 0
                    red = "max" if low in ("any", "some", "bool_or")                         else "min"
                    expr = E.BinOp(">", _AggRef(AggOfExpr(red, flag)),
                                   E.Lit(0))
            elif fn.lower() in _AGG_FNS:
                if col is None and len(args) == 1                         and isinstance(args[0], E.Expr):
                    # aggregate over an expression: sum(price * qty)
                    expr = AggOfExpr(fn, args[0])
                else:
                    _check_agg_args(fn, col, args)
                    expr = AggExpr(fn, col)
            elif fn.lower() in _AGG_FNS_PCT:
                if (len(args) not in (2, 3) or not isinstance(args[0], E.Col)
                        or not isinstance(args[1], E.Lit)):
                    raise ValueError(
                        f"{fn}(col, percentage[, accuracy]) requires a "
                        "column and a literal percentage")
                from ..frame.aggregates import percentile_approx as _pa

                expr = _pa(args[0].name, float(args[1].value))
            else:
                raise ValueError(f"window function {fn}() requires an "
                                 "OVER clause")
            from ..frame.aggregates import AggExpr as _AggE

            # Aggregate arithmetic in the select list (``SELECT max(p) -
            # min(p) AS spread``): continue precedence climbing with the
            # parsed aggregate as the left operand, then detect below.
            if (isinstance(expr, _AggE)
                    and self.peek().kind == "op"
                    and self.peek().value in ("+", "-", "*", "/")):
                expr = self.parse_add(_AggRef(expr))
            elif isinstance(expr, _AggE) or not isinstance(expr, E.Expr):
                # plain aggregate / percentile item — no detection needed
                if self.accept("kw", "as"):
                    return expr.alias(self.expect("ident").value)
                alias = self.accept("ident")
                if alias is not None:
                    return expr.alias(alias.value)
                return expr
            elif (self.peek().kind == "op"
                  and self.peek().value in ("+", "-", "*", "/")):
                # desugared bool-agg forms compose arithmetically too
                expr = self.parse_add(expr)
        else:
            expr = self.parse_or()
        # Post-aggregate detection: an expression whose tree contains
        # aggregate calls projects over the aggregated frame.
        collected: list = []
        rewritten = _rewrite_having(expr, collected)
        item = PostAggItem(rewritten, collected) if collected else expr
        if self.accept("kw", "as"):
            return item.alias(self.expect("ident").value)
        alias = self.accept("ident")
        if alias is not None:  # bare alias: `cast(guest as int) guest`
            return item.alias(alias.value)
        return item

    # -- expressions (precedence climbing) ----------------------------------
    def parse_or(self):
        left = self.parse_and()
        while self.accept("kw", "or"):
            left = E.BinOp("|", left, self.parse_and())
        return left

    def parse_and(self):
        left = self.parse_not()
        while self.accept("kw", "and"):
            left = E.BinOp("&", left, self.parse_not())
        return left

    def parse_not(self):
        if self.accept("kw", "not"):
            return E.UnaryOp("!", self.parse_not())
        return self.parse_cmp()

    _CMP = {"=": "==", "==": "==", "!=": "!=", "<>": "!=",
            "<": "<", "<=": "<=", ">": ">", ">=": ">="}

    def parse_cmp(self):
        left = self.parse_add()
        t = self.peek()
        if t.kind == "op" and t.value in self._CMP:
            self.next()
            return E.BinOp(self._CMP[t.value], left, self.parse_add())
        if self.accept("kw", "is"):
            negated = bool(self.accept("kw", "not"))
            self.expect("kw", "null")
            return left.is_not_null() if negated else left.is_null()
        # [NOT] IN / BETWEEN / LIKE
        negated = False
        if (self.peek().kind == "kw" and self.peek().value.lower() == "not"
                and self.toks[self.i + 1].kind == "kw"
                and self.toks[self.i + 1].value.lower() in ("in", "between",
                                                            "like")):
            self.next()
            negated = True
        if self.accept("kw", "in"):
            self.expect("op", "(")
            if (self.peek().kind == "kw"
                    and self.peek().value.lower() == "select"):
                sub = self.parse_set_expr()
                self.expect("op", ")")
                return SubqueryIn(left, sub, negated)
            values = [self.parse_or()]
            while self.accept("op", ","):
                values.append(self.parse_or())
            self.expect("op", ")")
            return E.InList(left, values, negated=negated)
        if self.accept("kw", "between"):
            lo = self.parse_add()
            self.expect("kw", "and")
            hi = self.parse_add()
            expr = left.between(lo, hi)
            return E.UnaryOp("!", expr) if negated else expr
        if self.accept("kw", "like"):
            pat = self.expect("string").value
            return E.StringMatch("like", left, pat, negated=negated)
        return left

    def parse_add(self, left=None):
        left = self.parse_mul(left)
        while True:
            if self.accept("op", "+"):
                left = E.BinOp("+", left, self.parse_mul())
            elif self.accept("op", "-"):
                left = E.BinOp("-", left, self.parse_mul())
            elif self.accept("op", "||"):
                # SQL || = concat (Spark: strings; null-propagating)
                left = E.UdfCall("concat", [left, self.parse_mul()])
            else:
                return left

    def parse_mul(self, left=None):
        left = self.parse_unary() if left is None else left
        while True:
            if self.accept("op", "*"):
                left = E.BinOp("*", left, self.parse_unary())
            elif self.accept("op", "/"):
                left = E.BinOp("/", left, self.parse_unary())
            elif self.accept("op", "%"):
                left = E.BinOp("%", left, self.parse_unary())
            else:
                return left

    def parse_unary(self):
        if self.accept("op", "-"):
            return E.UnaryOp("-", self.parse_unary())
        return self.parse_atom()

    def parse_atom(self):
        t = self.peek()
        if t.kind == "number":
            self.next()
            text = t.value
            if re.fullmatch(r"\d+", text):
                return E.Lit(int(text))
            return E.Lit(float(text))
        if t.kind == "string":
            self.next()
            return E.Lit(t.value)
        if self.accept("kw", "true"):
            return E.Lit(True)
        if self.accept("kw", "false"):
            return E.Lit(False)
        if self.accept("kw", "null"):
            return E.Lit(math.nan)
        if self.accept("kw", "cast"):
            self.expect("op", "(")
            inner = self.parse_or()
            self.expect("kw", "as")
            tname = self.expect("ident").value
            self.expect("op", ")")
            return E.Cast(inner, tname)
        if (t.kind == "ident" and t.value.lower() == "extract"
                and self.toks[self.i + 1].kind == "op"
                and self.toks[self.i + 1].value == "("):
            # extract(FIELD FROM expr) — sugar over the field functions
            self.next()
            self.expect("op", "(")
            field = self.expect("ident").value.lower()
            aliases = {"day": "dayofmonth", "dow": "dayofweek",
                       "doy": "dayofyear", "week": "weekofyear"}
            field = aliases.get(field, field)
            self.expect("kw", "from")
            inner = self.parse_or()
            self.expect("op", ")")
            return E.UdfCall(field, [inner])
        if self.accept("kw", "case"):
            # simple form: CASE operand WHEN v THEN r ... — each WHEN
            # value compares against the operand by equality
            operand = None
            if not (self.peek().kind == "kw"
                    and self.peek().value.lower() == "when"):
                operand = self.parse_or()
            branches = []
            while self.accept("kw", "when"):
                cond = self.parse_or()
                if operand is not None:
                    cond = E.BinOp("==", operand, cond)
                self.expect("kw", "then")
                branches.append((cond, self.parse_or()))
            if not branches:
                raise ValueError("CASE requires at least one WHEN branch")
            otherwise = self.parse_or() if self.accept("kw", "else") else None
            self.expect("kw", "end")
            return E.CaseWhen(branches, otherwise)
        # LEFT(s, n) / RIGHT(s, n): the string functions named by join
        # keywords — recognized only in call position
        if (t.kind == "kw" and t.value.lower() in ("left", "right")
                and self.toks[self.i + 1].kind == "op"
                and self.toks[self.i + 1].value == "("):
            self.next()
            self.expect("op", "(")
            args = [self.parse_or()]
            while self.accept("op", ","):
                args.append(self.parse_or())
            self.expect("op", ")")
            return E.UdfCall(t.value.lower(), args)
        if t.kind == "ident":
            self.next()
            if self.accept("op", "("):
                # COUNT(*) in expression position (e.g. HAVING COUNT(*) > 2)
                if t.value.lower() in _AGG_FNS and self.accept("op", "*"):
                    self.expect("op", ")")
                    return E.UdfCall(t.value, [E.Lit("*")])
                # COUNT(DISTINCT x)/SUM(DISTINCT x) inside an expression
                # context (HAVING): encode as the _distinct aggregate name
                fn_name = t.value
                if (t.value.lower() in ("count", "sum")
                        and self.accept("kw", "distinct")):
                    fn_name = f"{t.value.lower()}_distinct"
                # if(cond, a, b) — Spark's CASE sugar
                if fn_name.lower() == "if":
                    cond = self.parse_or()
                    self.expect("op", ",")
                    then = self.parse_or()
                    self.expect("op", ",")
                    other = self.parse_or()
                    self.expect("op", ")")
                    return E.CaseWhen([(cond, then)], other)
                # EXISTS (SELECT ...) — the predicate form; EXISTS(arr,
                # x -> ...) remains the higher-order array function.
                if (fn_name.lower() == "exists" and self.peek().kind == "kw"
                        and self.peek().value.lower() == "select"):
                    sub = self.parse_set_expr()
                    self.expect("op", ")")
                    return SubqueryExists(sub)
                if fn_name.lower() in ("transform", "filter", "exists",
                                       "aggregate"):
                    return self.parse_higher_order(fn_name.lower())
                args = []
                if not self.accept("op", ")"):
                    args.append(self.parse_or())
                    while self.accept("op", ","):
                        args.append(self.parse_or())
                    self.expect("op", ")")
                # fn(...) OVER (...) in EXPRESSION position (e.g.
                # ``price - first_value(price) OVER (...)``): a window
                # expr is a regular column Expr, so it composes
                if (self.peek().kind == "ident"
                        and self.peek().value.lower() == "over"
                        and fn_name.lower() in (_WINDOW_FNS | _AGG_FNS)):
                    self.next()
                    col = (args[0].name if len(args) == 1
                           and isinstance(args[0], E.Col) else None)
                    make = self._build_window_fn(fn_name, col, args)
                    return make(self.parse_window_spec())
                return E.UdfCall(fn_name, args)
            # qualified column ref: alias.col (resolved at execute
            # against the relation scope; a literal dotted column name
            # on the frame wins first)
            name = t.value
            while (self.peek().kind == "op" and self.peek().value == "."):
                self.next()
                name += "." + self.expect("ident").value
            return E.Col(name)
        if self.accept("op", "("):
            if (self.peek().kind == "kw"
                    and self.peek().value.lower() == "select"):
                sub = self.parse_set_expr()
                self.expect("op", ")")
                return ScalarSubquery(sub)
            inner = self.parse_or()
            self.expect("op", ")")
            return inner
        raise ValueError(f"SQL parse error at {t.value!r}")

    def parse_lambda(self):
        """``x -> expr`` / ``(acc, x) -> expr`` — Spark 2.4's SQL lambda.
        Parameters surface as Col refs in the body; the higher-order
        evaluator's scope frame binds them (shadowing outer columns)."""
        params = []
        if self.accept("op", "("):
            params.append(self.expect("ident").value)
            while self.accept("op", ","):
                params.append(self.expect("ident").value)
            self.expect("op", ")")
        else:
            params.append(self.expect("ident").value)
        self.expect("op", "->")
        return E.Lambda(params, self.parse_or())

    def parse_higher_order(self, fn: str):
        """transform/filter/exists (col, lambda); aggregate
        (col, init, merge[, finish]) — '(' already consumed."""
        source = self.parse_or()
        self.expect("op", ",")
        if fn == "aggregate":
            init = self.parse_or()
            self.expect("op", ",")
            merge = self.parse_lambda()
            finish = self.parse_lambda() if self.accept("op", ",") else None
            self.expect("op", ")")
            return E.HigherOrder("aggregate", source, merge, init=init,
                                 finish=finish)
        lam = self.parse_lambda()
        self.expect("op", ")")
        return E.HigherOrder(fn, source, lam)


class DerivedTable:
    """A parenthesized subquery in relation position: ``FROM (SELECT
    ...) [AS] alias`` — executed into a Frame at lookup time."""

    __slots__ = ("query", "alias")

    def __init__(self, query, alias=None):
        self.query = query
        self.alias = alias


class _AliasableSubquery(E.Expr):
    """Subquery placeholders are Expr subclasses so every grammar position
    a column can take — ``(SELECT ...) IS NULL``, ``BETWEEN``, ``LIKE``,
    ``AS name`` — composes; the resolution walk replaces them with
    literals before any eval. eval() itself is unreachable after
    resolution and raises a clear error if a placeholder escapes."""

    __slots__ = ()

    def eval(self, frame):
        raise ValueError(
            "subqueries are only supported inside session.sql() — this "
            "expression still holds an unresolved subquery placeholder")


class ScalarSubquery(_AliasableSubquery):
    """``(SELECT agg FROM ...)`` in expression position. Uncorrelated
    only; resolved to a literal (its single value, null when empty)
    before the enclosing query runs."""

    __slots__ = ("query",)

    def __init__(self, query):
        self.query = query


class SubqueryIn(_AliasableSubquery):
    """``expr [NOT] IN (SELECT col FROM ...)`` — resolved to an InList
    over the subquery's materialized (uncorrelated) value set."""

    __slots__ = ("child", "query", "negated")

    def __init__(self, child, query, negated=False):
        self.child = child
        self.query = query
        self.negated = negated

    def __str__(self):
        return f"({self.child} {'NOT IN' if self.negated else 'IN'} " \
            "(subquery))"


class SubqueryExists(_AliasableSubquery):
    """``EXISTS (SELECT ...)`` — uncorrelated; resolved to a boolean
    literal (row count > 0)."""

    __slots__ = ("query",)

    def __init__(self, query):
        self.query = query


class Query:
    """Parsed query: select items, view, joins, where, group/having/order/
    limit, distinct flag, trailing UNION branches, and WITH CTEs."""

    def __init__(self, items, view, where, group_by=(), order_by=(),
                 limit=None, joins=(), distinct=False, having=None,
                 unions=()):
        self.items = items
        self.view = view
        self.where = where
        self.group_by = list(group_by)
        self.order_by = list(order_by)
        self.limit = limit
        self.joins = list(joins)
        self.distinct = distinct
        self.having = having
        self.unions = list(unions)  # [(op, Query)] op ∈ union[_all]/
        #                             intersect/except, left-assoc
        self.group_mode = "group"   # "group" | "rollup" | "cube"
        self.ctes = []              # [(name, Query), ...]
        self.view_alias = None      # FROM-relation alias (qualified refs)
        self.offset = 0             # rows skipped before LIMIT applies


def parse(sql: str) -> Query:
    """Parse a query into a Query plan object."""
    return _Parser(tokenize(sql)).parse_union_query()


def _rewrite_having(expr, extra_aggs: list):
    """HAVING may reference aggregates directly (``HAVING COUNT(*) > 2``).
    Rewrite agg-function calls into references to the aggregated output
    column, collecting aggs that must be computed but aren't in SELECT."""
    from ..frame.aggregates import AggExpr

    having_aggs = _AGG_FNS | _AGG_FNS_2 | {"count_distinct", "sum_distinct"}
    if isinstance(expr, _AggRef):
        extra_aggs.append(expr.agg)
        return E.Col(expr.agg.name)
    if (isinstance(expr, E.UdfCall)
            and expr.udf_name.lower() in _BOOL_AGGS
            and len(expr.args) == 1):
        from ..frame.aggregates import AggOfExpr

        low = expr.udf_name.lower()
        flag = E.CaseWhen([(expr.args[0], E.Lit(1))], E.Lit(0))
        if low == "count_if":
            agg = AggOfExpr("sum", flag,
                            alias=f"count_if({expr.args[0]})")
            extra_aggs.append(agg)
            return E.Col(agg.name)
        red = ("max" if low in ("any", "some", "bool_or") else "min")
        agg = AggOfExpr(red, flag)
        extra_aggs.append(agg)
        return E.BinOp(">", E.Col(agg.name), E.Lit(0))
    if (isinstance(expr, E.UdfCall) and expr.udf_name.lower() in having_aggs
            and (len(expr.args) <= 1
                 or expr.udf_name.lower() in _AGG_FNS_2)):
        fn = expr.udf_name.lower()
        if fn in _AGG_FNS_2:
            if (len(expr.args) != 2
                    or not all(isinstance(a, E.Col) for a in expr.args)):
                raise ValueError(f"{fn}(col1, col2) takes two columns")
            agg = AggExpr(fn, expr.args[0].name, column2=expr.args[1].name)
            extra_aggs.append(agg)
            return E.Col(agg.name)
        arg = expr.args[0] if expr.args else None
        if arg is None or (isinstance(arg, E.Lit) and arg.value == "*"):
            col = None
        elif isinstance(arg, E.Col):
            col = arg.name
        else:
            from ..frame.aggregates import AggOfExpr

            agg = AggOfExpr(expr.udf_name, arg)
            extra_aggs.append(agg)
            return E.Col(agg.name)
        agg = AggExpr(expr.udf_name, col)
        extra_aggs.append(agg)
        return E.Col(agg.name)
    if isinstance(expr, E.BinOp):
        return E.BinOp(expr.op, _rewrite_having(expr.left, extra_aggs),
                       _rewrite_having(expr.right, extra_aggs))
    if isinstance(expr, E.UnaryOp):
        return E.UnaryOp(expr.op, _rewrite_having(expr.child, extra_aggs))
    if isinstance(expr, E.InList):
        return E.InList(_rewrite_having(expr.child, extra_aggs),
                        [_rewrite_having(v, extra_aggs) for v in expr.values],
                        expr.negated)
    if isinstance(expr, E.UdfCall):     # non-aggregate call: recurse args
        return E.UdfCall(expr.udf_name,
                         [_rewrite_having(a, extra_aggs) for a in expr.args],
                         registry=expr._registry)
    if isinstance(expr, E.Cast):
        return E.Cast(_rewrite_having(expr.child, extra_aggs),
                      expr.type_name)
    if isinstance(expr, E.CaseWhen):
        return E.CaseWhen(
            [(_rewrite_having(c, extra_aggs), _rewrite_having(v, extra_aggs))
             for c, v in expr.branches],
            None if expr.otherwise_expr is None
            else _rewrite_having(expr.otherwise_expr, extra_aggs))
    return expr


class _OverlayCatalog:
    """CTE scope: WITH-bound names shadow the base catalog for the
    duration of one statement, without mutating it."""

    def __init__(self, base):
        self._base = base
        self._views: dict[str, object] = {}

    def register(self, name: str, frame) -> None:
        self._views[name.lower()] = frame

    def lookup(self, name: str):
        try:
            return self._views[name.lower()]
        except KeyError:
            return self._base.lookup(name)


def _pyval(v):
    """numpy scalar → python scalar (Lit dispatches on python types)."""
    # dqlint: ok(host-sync): SQL literal folding — the values are parsed
    # host scalars (numpy or python), never device arrays
    return v.item() if hasattr(v, "item") else v


def _conjuncts(e) -> list:
    """Flatten an AND tree into its conjuncts."""
    if isinstance(e, E.BinOp) and e.op == "&":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def _conjoin(parts):
    out = None
    for p in parts:
        out = p if out is None else E.BinOp("&", out, p)
    return out


_ON_ERROR = ("JOIN ON supports equi-join on a shared column name or on one "
             "column of each side; got {!r} = {!r}")


class KeyPair(tuple):
    """A settled join key over two names: (left column, right column).
    The parser's unsettled ``ON a = b`` is a plain tuple."""

    __slots__ = ()


def join_key_names(keys) -> tuple:
    """(left names, right names) of a join's resolved key list: a shared
    name counts on both sides, a pair gives one to each."""
    return ([k if isinstance(k, str) else k[0] for k in keys],
            [k if isinstance(k, str) else k[1] for k in keys])


def _orient_key(a: str, b: str, left: list, right: tuple):
    """``a = b`` as the key of the join that adds the relation ``right`` =
    (bind, columns) to the relations ``left`` joined before it: the shared
    name, or the :class:`KeyPair`; None when it is not one column of each
    side (an unqualified name that both sides carry is on neither)."""
    def side(name):
        qual, _, col = name.rpartition(".")
        if qual and qual.lower() == right[0]:
            return "r", col
        if qual and any(qual.lower() == bind for bind, _ in left):
            return "l", col
        in_l = any(name in cols for _, cols in left)
        in_r = name in right[1]
        return ("l" if in_l else "r") if in_l != in_r else None, name

    (sa, ca), (sb, cb) = side(a), side(b)
    if {sa, sb} != {"l", "r"}:
        return None
    pair = (ca, cb) if sa == "l" else (cb, ca)
    return pair[0] if pair[0] == pair[1] else KeyPair(pair)


def _unsettled(join) -> bool:
    _view, how, keys, _alias = join
    return (how == "inner" and not keys) or any(
        not isinstance(k, (str, KeyPair)) for k in keys)


def resolve_join_keys(q: "Query", rel_cols: list):
    """The joins and the WHERE clause of ``q`` with every join key
    settled: an ``ON a = b`` pair turned the way its relations stand, a
    comma relation (``inner`` with no key) given the WHERE clause's
    equalities between it and the relations before it, or made a cross
    join where there is none. ``rel_cols`` lists the columns of the base
    relation and of every joined one; returns None while one is unknown
    (a derived table before it has run), ``(joins, where)`` otherwise.
    Settled keys pass through, so a second call changes nothing."""
    if not any(_unsettled(j) for j in q.joins):
        return q.joins, q.where
    if any(c is None for c in rel_cols):
        return None

    def bind(view, alias):
        return (alias or (view if isinstance(view, str) else "")).lower()

    base = q.view.alias if isinstance(q.view, DerivedTable) else q.view
    left = [(bind(base, q.view_alias), rel_cols[0])]
    parts = _conjuncts(q.where) if q.where is not None else []
    joins = []
    for (view, how, keys, alias), cols in zip(q.joins, rel_cols[1:]):
        right = (bind(view, alias), cols)
        settled = []
        for k in keys:
            if not isinstance(k, (str, KeyPair)):
                a, b = k
                k = _orient_key(a, b, left, right)
                if k is None:
                    raise ValueError(_ON_ERROR.format(a, b))
            settled.append(k)
        if how == "inner" and not keys:
            rest = []
            for c in parts:
                k = (_orient_key(c.left.name, c.right.name, left, right)
                     if isinstance(c, E.BinOp) and c.op == "=="
                     and isinstance(c.left, E.Col)
                     and isinstance(c.right, E.Col) else None)
                if k is None:
                    rest.append(c)
                else:
                    settled.append(k)
            parts = rest
            if not settled:
                how = "cross"
        joins.append((view, how, settled, alias))
        left.append(right)
    return joins, _conjoin(parts)


def _relation_aliases(q: Query) -> set:
    """The relation aliases a query's own FROM/JOIN clause binds."""
    names = set()
    if isinstance(q.view, str):
        names.add((q.view_alias or q.view).lower())
    elif isinstance(q.view, DerivedTable) and q.view.alias:
        names.add(q.view.alias.lower())
    for view, _how, _keys, jalias in q.joins:
        nm = jalias or (view if isinstance(view, str) else None)
        if nm:
            names.add(nm.lower())
    return names


def _outer_refs(expr, outer_scope: dict, inner_aliases: set) -> set:
    """Qualified names in ``expr`` whose alias binds in the OUTER scope
    but not in the subquery's own relations — the correlation points."""
    cols: set = set()
    _referenced_cols(expr, cols)
    out = set()
    for name in cols:
        if "." not in name or "(" in name:
            continue
        alias = name.partition(".")[0].lower()
        if alias in outer_scope and alias not in inner_aliases:
            out.add(name)
    return out


def _decorrelate_one(sub: Query, extra_outer_cols, outer_scope, cat):
    """Rewrite one correlated predicate subquery into a semi-join input.

    Returns ``(right_frame, keys)`` where ``right_frame``'s columns are
    named after the OUTER flat columns and ``keys`` joins it left-semi
    (EXISTS/IN) or left-anti (negations) — Spark's own decorrelation.
    ``extra_outer_cols`` carries the IN form's outer expression paired
    with the subquery's select item. Only conjunctive equi-correlation
    is supported; anything else raises the unsupported-correlation error.
    """
    inner_aliases = _relation_aliases(sub)

    def unsupported(why):
        return ValueError(
            f"unsupported correlated subquery ({why}); only conjunctive "
            "equality correlation decorrelates (the Spark semi/anti-join "
            "rewrite) — rewrite the query as an explicit JOIN")

    if sub.unions or sub.group_by or sub.having or sub.limit is not None \
            or getattr(sub, "offset", 0) or sub.ctes:
        raise unsupported("the subquery uses set ops, grouping, or limits")
    eq_pairs = []      # (outer flat col, inner expr)
    rest = []
    for c in _conjuncts(sub.where) if sub.where is not None else []:
        refs = _outer_refs(c, outer_scope, inner_aliases)
        if not refs:
            rest.append(c)
            continue
        if (isinstance(c, E.BinOp) and c.op == "=="
                and isinstance(c.left, E.Col) and isinstance(c.right, E.Col)):
            l_out = c.left.name in refs
            r_out = c.right.name in refs
            if l_out != r_out:
                outer_name = c.left.name if l_out else c.right.name
                inner_col = c.right if l_out else c.left
                eq_pairs.append((
                    _resolve_name(outer_name, outer_scope, ()), inner_col))
                continue
        raise unsupported(f"non-equi correlated predicate {c}")
    for outer_expr, item in extra_outer_cols:
        if not isinstance(outer_expr, E.Col):
            raise unsupported("the IN operand must be a plain column")
        eq_pairs.append((outer_expr.name, item))
    if not eq_pairs:
        raise unsupported("no equality correlation found")

    def _inner_key(ie):
        # normalized inner-column identity: strip the subquery's own
        # relation qualifier so ``g.guest`` and ``guest`` compare equal
        if isinstance(ie, E.Col):
            alias, _, col = ie.name.partition(".")
            return col if alias.lower() in inner_aliases else ie.name
        return str(ie)

    deduped: dict = {}
    for o, ie in eq_pairs:
        k = _inner_key(ie)
        if o in deduped and deduped[o][1] != k:
            raise unsupported("two different correlation keys target one "
                              "outer column")
        deduped.setdefault(o, (ie, k))
    eq_pairs = [(o, ie) for o, (ie, _) in deduped.items()]
    names = [o for o, _ in eq_pairs]
    inner = Query([E.Alias(ie if isinstance(ie, E.Expr) else E.Col(ie), o)
                   for o, ie in eq_pairs],
                  sub.view, _conjoin(rest), joins=sub.joins, distinct=True)
    inner.view_alias = sub.view_alias
    # Decorrelation-aware pushdown: the subquery branch is a full SELECT
    # over its own relation scope (correlated conjuncts are already
    # lifted into ``eq_pairs`` above, so only decorrelated predicates
    # remain) — route it through the cost-based optimizer like any other
    # executed query so its residual filters push into the scans and its
    # projection prunes, instead of scanning the branch unoptimized.
    return _execute_set(_maybe_optimize(inner, cat), cat), names


def _decorrelate_where(where, scope: dict, cat):
    """Split WHERE into plain conjuncts and correlated predicate
    subqueries; the latter become (right_frame, keys, how) semi/anti
    joins. Uncorrelated subqueries stay put (literal resolution handles
    them, preserving their null semantics)."""
    keep = []
    joins = []
    for c in _conjuncts(where):
        neg = False
        target = c
        if (isinstance(c, E.UnaryOp) and c.op == "!"
                and isinstance(c.child, (SubqueryExists, SubqueryIn))):
            neg, target = True, c.child
        if isinstance(target, SubqueryExists):
            sub, extra = target.query, []
        elif isinstance(target, SubqueryIn):
            from ..frame.aggregates import AggExpr

            sub = target.query
            neg = neg != target.negated
            if len(sub.items) != 1 or isinstance(sub.items[0],
                                                 (str, AggExpr)):
                keep.append(c)
                continue
            extra = [(target.child, sub.items[0])]
        else:
            keep.append(c)
            continue
        inner_aliases = _relation_aliases(sub)
        correlated = bool(_outer_refs(sub.where, scope, inner_aliases)
                          if sub.where is not None else False)
        if not correlated:
            keep.append(c)          # uncorrelated: existing literal path
            continue
        right, keys = _decorrelate_one(sub, extra, scope, cat)
        joins.append((right, keys, "left_anti" if neg else "left_semi"))
    return _conjoin(keep), joins


def semi_join_in(c, aliases) -> bool:
    """Whether a WHERE conjunct is planned as a left-semi join: a plain
    (not negated) ``expr IN (SELECT one_column ...)`` whose subquery names
    none of the relations ``aliases`` binds outside it, and whose ``expr``
    reads a column. Spark's rewrite: the subquery's frame is the build
    side, its one column the key against ``expr``. ``NOT IN`` keeps the
    literal path, whose NULL rule is not an anti join's."""
    if not isinstance(c, SubqueryIn) or c.negated:
        return False
    cols: set = set()
    _referenced_cols(c.child, cols)
    sub = c.query
    return bool(cols) and not (
        sub.where is not None
        and _outer_refs(sub.where, aliases, _relation_aliases(sub)))


def _in_list(child, frame, negated):
    """``child [NOT] IN`` the one column of a subquery's frame, as the
    literal list it reads to the host."""
    cols = frame.columns
    if len(cols) != 1:
        raise ValueError("IN (subquery) must select exactly one "
                         f"column, got {len(cols)}: {cols}")
    values = frame.to_pydict()[cols[0]]
    counters.increment("subquery.literal_in")
    _obs.current_span().set(how="literal", build_rows=len(values))
    return E.InList(child, [E.Lit(_pyval(v)) for v in values], negated)


def _semi_join_in(where, frame, aliases, cat):
    """The WHERE conjuncts :func:`semi_join_in` accepts, each run as a
    left-semi join of ``frame`` against its subquery's frame (compacted
    to its valid rows first: a HAVING leaves a grouped result sparse);
    returns the rest of WHERE and the joined frame. A key pair the device
    join does not take (a string, an integer against a float) keeps the
    literal list instead. No value of a joined subquery reaches the
    host: the join reads its row count, the compaction the build side's."""
    from ..frame.frame import Frame, _is_string_col
    from ..ops import joins as _joins

    parts = _conjuncts(where)
    if not any(semi_join_in(c, aliases) for c in parts):
        return where, frame
    rest = []
    for c in parts:
        if not semi_join_in(c, aliases):
            rest.append(c)
            continue
        with _obs.span("sql.subquery.in", cat="sql") as s:
            child = _resolve_subqueries(c.child, cat)
            sub = _execute_subquery(c.query, cat)
            if len(sub.columns) != 1:
                rest.append(_in_list(child, sub, False))
                continue
            right = sub.columns[0]
            left = child.name if isinstance(child, E.Col) \
                and child.name in frame.columns else None
            probe = frame if left is not None \
                else frame.with_column("__in_key", child)
            lk, rk = probe._data[left or "__in_key"], sub._data[right]
            if _is_string_col(lk) or _is_string_col(rk) \
                    or _joins.key_dtype(lk, rk) is None:
                rest.append(_in_list(child, sub, False))
                continue
            rows = sub.num_slots
            if not sub._every_slot_valid():
                (rk,), mask, rows = _joins.compact_rows([rk], sub._mask)
                sub = Frame({right: rk}, mask=mask)
            key = left or "__in_key"
            frame = probe.join(sub, on=key if key == right
                               else [(key, right)], how="left_semi")
            if left is None:
                frame = frame.drop("__in_key")
            counters.increment("subquery.semi_join")
            s.set(how="left_semi", build_rows=rows)
    return _conjoin(rest), frame


def _execute_subquery(q: Query, cat):
    """Run a subquery, converting an outer-alias reference into the
    clear diagnosis: correlation is not supported — Spark itself
    rewrites correlated EXISTS/IN into semi/anti joins, and those are
    first-class here."""
    try:
        return _execute_set(q, cat)
    except ValueError as e:
        if "unknown relation alias" in str(e):
            raise ValueError(
                "correlated subqueries are not supported (the subquery "
                f"references an outer relation: {e}); rewrite as a join "
                "— LEFT SEMI for EXISTS/IN, LEFT ANTI for NOT "
                "EXISTS/NOT IN") from e
        raise


def _resolve_subqueries(expr, cat):
    """Replace uncorrelated subquery placeholders with literal values by
    executing them against the catalog, rebuilding the expression tree."""
    if isinstance(expr, ScalarSubquery):
        frame = _execute_subquery(expr.query, cat)
        cols = frame.columns
        if len(cols) != 1:
            raise ValueError("scalar subquery must return exactly one "
                             f"column, got {len(cols)}: {cols}")
        values = [_pyval(v) for v in frame.to_pydict()[cols[0]]]
        if len(values) > 1:
            raise ValueError("scalar subquery returned more than one row")
        return E.Lit(values[0] if values else math.nan)
    if isinstance(expr, SubqueryIn):
        with _obs.span("sql.subquery.in", cat="sql"):
            frame = _execute_subquery(expr.query, cat)
            return _in_list(_resolve_subqueries(expr.child, cat), frame,
                            expr.negated)
    if isinstance(expr, SubqueryExists):
        return E.Lit(_execute_subquery(expr.query, cat).count() > 0)
    if isinstance(expr, E.BinOp):
        return E.BinOp(expr.op, _resolve_subqueries(expr.left, cat),
                       _resolve_subqueries(expr.right, cat))
    if isinstance(expr, E.UnaryOp):
        return E.UnaryOp(expr.op, _resolve_subqueries(expr.child, cat))
    if isinstance(expr, E.InList):
        return E.InList(_resolve_subqueries(expr.child, cat),
                        [_resolve_subqueries(v, cat) for v in expr.values],
                        expr.negated)
    if isinstance(expr, E.UdfCall):
        return E.UdfCall(expr.udf_name,
                         [_resolve_subqueries(a, cat) for a in expr.args],
                         registry=expr._registry)
    if isinstance(expr, E.Cast):
        return E.Cast(_resolve_subqueries(expr.child, cat), expr.type_name)
    if isinstance(expr, E.StringMatch):
        return E.StringMatch(expr.kind,
                             _resolve_subqueries(expr.child, cat),
                             expr.pattern, negated=expr.negated)
    if isinstance(expr, E.CaseWhen):
        return E.CaseWhen(
            [(_resolve_subqueries(c, cat), _resolve_subqueries(v, cat))
             for c, v in expr.branches],
            None if expr.otherwise_expr is None
            else _resolve_subqueries(expr.otherwise_expr, cat))
    if isinstance(expr, E.Alias):
        return E.Alias(_resolve_subqueries(expr.child, cat), expr._name)
    if isinstance(expr, PostAggItem):
        return PostAggItem(_resolve_subqueries(expr.expr, cat),
                           expr.aggs, expr._name)
    return expr


def _execute_set(q: Query, cat):
    """Run one set expression: a SELECT plus trailing UNION [ALL] /
    INTERSECT / EXCEPT branches (left-associative)."""
    frame = _execute_single(q, cat)
    for op, sub in q.unions:
        rhs = _execute_single(sub, cat)
        if op == "union_all":
            frame = frame.union(rhs)
        elif op == "union":
            frame = frame.union(rhs).distinct()
        elif op == "intersect":
            frame = frame.intersect(rhs)
        else:                              # except
            frame = frame.subtract(rhs)
    return frame


class _AnyColSchema(dict):
    """Optimistic column schema for plan_summary's structural fused-stage
    check: every column resolves as a device column of unknown dtype
    (``p``), so the check keys on expression FORM only."""

    def get(self, key, default=None):  # noqa: ARG002 - dict signature
        return "p"


_OPTIMISTIC_SCHEMA = _AnyColSchema()


def _segment_lowerable_aggs(items) -> bool:
    """Structural check for the ``SegmentedAggregate`` plan marker: every
    aggregate in the select list (including the components of post-agg
    expressions) passes the executor's OWN eligibility predicate
    (``segments.agg_lowerable`` — one definition, marker and executor in
    lockstep) — same optimistic-dtype convention as the FusedStage
    check."""
    from ..frame.aggregates import AggExpr
    from ..ops.segments import agg_lowerable

    found = False
    for it in items:
        aggs = (it.aggs if isinstance(it, PostAggItem)
                else [it] if isinstance(it, AggExpr) else [])
        for a in aggs:
            found = True
            if not agg_lowerable(a):
                return False
    return found


_DDL_RE = re.compile(
    r"^\s*create\s+(?:or\s+replace\s+)?(?:temp(?:orary)?\s+)?view\s+"
    r"([A-Za-z_][A-Za-z_0-9]*)\s+as\s+(.*)$",
    re.IGNORECASE | re.DOTALL)
_DROP_RE = re.compile(
    r"^\s*drop\s+(?:temp(?:orary)?\s+)?view\s+(if\s+exists\s+)?"
    r"([A-Za-z_][A-Za-z_0-9]*)\s*$", re.IGNORECASE)


def plan_summary(q: Query) -> str:
    """``explain()``-style one-line plan for a parsed query — the operator
    chain root-first (the shape Spark's ``explain`` prints), attached to
    every ``sql.query`` span so traces show WHAT a query did, not just its
    text.

    When the pipeline compiler is on (``spark.pipeline.enabled``, the
    default) and the WHERE predicate plus every projection expression is
    *structurally* compilable, the Project+Filter pair of a
    non-aggregating query prints as ``FusedStage(Project[n] <- Filter)``
    — one compiled XLA program. Structural means column dtypes are
    assumed numeric (the plan is summarized before execution binds the
    frame): a string-COLUMN reference still executes eagerly, but
    string/UDF/subquery expression forms are detected and keep the
    unfused ``Project <- Filter`` rendering.

    Grouped execution markers follow the same structural rule: with
    ``spark.groupedExec.enabled`` (the default), ``ORDER BY`` prints as
    ``DeviceSort[n]`` (one on-device ``lax.sort`` program) and a plain
    ``GROUP BY`` whose aggregates are all segment-lowerable prints as
    ``SegmentedAggregate[groupBy:n]`` (one sort + segment-reduce
    program, see ``ops/segments.py``); a string key discovered at
    execution time silently takes the host fallback, exactly like a
    string column under ``FusedStage``."""
    chain = plan_tree(q).main_chain()
    s = " <- ".join(n.label for n in chain)
    if q.unions:
        s += f" (+{len(q.unions)} set-op)"
    if q.ctes:
        s = f"With[{len(q.ctes)}] " + s
    return s


def _structurally_fusable(q: Query) -> bool:
    """The FusedStage predicate — one definition for the plan-summary
    marker, the plan tree, and EXPLAIN (the pipeline compiler re-checks
    against real dtypes at flush time; see :func:`plan_summary`)."""
    from ..config import config as _cfg
    from ..frame.aggregates import AggExpr
    from ..ops.compiler import is_compilable

    aggregating = bool(q.group_by) or any(
        isinstance(it, (AggExpr, PostAggItem)) for it in q.items)
    return (_cfg.pipeline and q.where is not None and not aggregating
            and is_compilable(q.where, _OPTIMISTIC_SCHEMA)
            and all(isinstance(it, str)
                    or is_compilable(it, _OPTIMISTIC_SCHEMA)
                    or isinstance(it, E.Col)
                    for it in q.items))


def _structurally_segmented(q: Query) -> bool:
    from ..config import config as _cfg

    return (_cfg.grouped_exec and q.group_mode == "group"
            and _segment_lowerable_aggs(q.items))


class PlanNode:
    """One operator of the structural query plan — the per-operator node
    tree ``plan_summary``'s flat chain is derived from, and the carrier
    of EXPLAIN ANALYZE's measured stats (``stats`` stays empty on the
    un-executed ``plan_tree`` output; EXPLAIN adds the static
    ``est_peak`` column, ANALYZE the measured schema). ``children[0]``
    is the operator's input; a Join's ``children[1]`` is the probe-side
    Scan. ``meta`` carries structural facts the static-memory estimator
    needs (Scan view name, the FusedStage's parsed query) — never
    rendered."""

    __slots__ = ("op", "detail", "children", "stats", "meta")

    def __init__(self, op: str, detail: str = "", children=()):
        self.op = op
        self.detail = detail
        self.children = list(children)
        self.stats: dict = {}
        self.meta: dict = {}

    @property
    def label(self) -> str:
        return f"{self.op}{self.detail}"

    def walk(self):
        """Preorder traversal over every node."""
        yield self
        for c in self.children:
            yield from c.walk()

    def execution_order(self):
        """Nodes in the order the engine RUNS them (inputs before
        consumers) — the order their spans arrive in, which is what FIFO
        span attribution must follow (a root-first walk would hand the
        WHERE filter's span to the Having node). Postorder — which is
        already execution order for chains, Join probe sides, and SetOps
        union branches — except ``With``, whose CTEs (children[1:]) run
        BEFORE the main query (children[0])."""
        if self.op == "With":
            for c in self.children[1:]:
                yield from c.execution_order()
            if self.children:
                yield from self.children[0].execution_order()
            yield self
            return
        for c in self.children:
            yield from c.execution_order()
        yield self

    def main_chain(self) -> list:
        """Root-first operator chain down ``children[0]``, ending at the
        Scan — exactly the shape :func:`plan_summary` prints. (A Scan
        may carry a derived-table subquery plan as its child; the chain
        does not descend into it.)"""
        out, node = [], self
        while node is not None:
            out.append(node)
            node = (node.children[0]
                    if node.children and node.op != "Scan" else None)
        return out

    def render(self, analyze: bool = False) -> str:
        """Indented operator tree; any annotated stats (the static
        ``est_peak`` column on EXPLAIN, the full measured schema on
        ANALYZE) print as a logfmt suffix."""
        from ..utils.logging import format_kv

        lines: list[str] = []

        def emit(node, depth):
            pad = "" if depth == 0 else "   " * (depth - 1) + "+- "
            line = pad + node.label
            if node.stats:
                # unknowns render as "-" so every node shows the full
                # stat schema (format_kv would elide None)
                stats = {k: ("-" if node.stats[k] is None
                             else node.stats[k]) for k in node.stats}
                kv = format_kv(**stats)
                if kv:
                    line += f"  ({kv})"
            lines.append(line)
            for c in node.children:
                emit(c, depth + 1)

        emit(self, 0)
        return "\n".join(lines)


def plan_tree(q: Query) -> PlanNode:
    """Build the per-operator plan-node tree for a parsed query (the
    structural plan: built before execution binds the frame, so markers
    follow the same optimistic-dtype convention as ``plan_summary``)."""
    def scan_node(view):
        """Scan leaf; a derived table carries its subquery's plan as a
        child (outside the main chain) so EXPLAIN shows it and span
        attribution consumes the subquery's spans at the right point
        instead of handing them to outer same-named operators."""
        if isinstance(view, DerivedTable):
            return PlanNode("Scan", "[(subquery)]",
                            [plan_tree(view.query)])
        if isinstance(view, str):
            n = PlanNode("Scan", f"[{view}]")
            n.meta["view"] = view      # static-memory estimator lookup
            return n
        return PlanNode("Scan", "[(subquery)]")  # OneRowRelation et al.

    node = scan_node(q.view)
    hints = list(getattr(q, "join_build", ()) or ())
    hints += [None] * (len(q.joins) - len(hints))
    # in execution order: the first join is the innermost node
    for (view, how, _keys, _alias), hint in zip(q.joins, hints):
        how = how if isinstance(how, str) else "inner"
        detail = f"[{how},build={hint}]" if hint else f"[{how}]"
        node = PlanNode("Join", detail, [node, scan_node(view)])
    if q.where is not None:
        # IN subqueries the executor runs as left-semi joins, after the
        # joins and before the rest of WHERE
        aliases = _relation_aliases(q)
        parts = _conjuncts(q.where)
        semi = [c for c in parts if semi_join_in(c, aliases)]
        for c in semi:
            node = PlanNode("Join", "[left_semi]", [node, PlanNode(
                "Scan", "[(subquery)]", [plan_tree(c.query)])])
        if semi:
            q = copy.copy(q)
            q.where = _conjoin([c for c in parts if not any(
                c is s for s in semi)])
    if _structurally_fusable(q):
        node = PlanNode("FusedStage",
                        f"(Project[{len(q.items)}] <- Filter)", [node])
        node.meta["query"] = q         # abstract-traceable stage
    else:
        if q.where is not None:
            node = PlanNode("Filter", "", [node])
            node.meta["query"] = q     # est-rows history lookup
        node = PlanNode("Project", f"[{len(q.items)}]", [node])
    if q.group_by:
        mode = q.group_mode if q.group_mode != "group" else "groupBy"
        op = ("SegmentedAggregate" if _structurally_segmented(q)
              else "Aggregate")
        node = PlanNode(op, f"[{mode}:{len(q.group_by)}]", [node])
        node.meta["query"] = q         # cardinality-history lookup
    if q.having is not None:
        node = PlanNode("Having", "", [node])
    if q.distinct:
        node = PlanNode("Distinct", "", [node])
        node.meta["query"] = q         # cardinality-history lookup
    if q.order_by:
        from ..config import config as _cfg

        node = PlanNode("DeviceSort" if _cfg.grouped_exec else "Sort",
                        f"[{len(q.order_by)}]", [node])
    if q.offset:
        node = PlanNode("Offset", f"[{q.offset}]", [node])
        node.meta["offset"] = q.offset
    if q.limit is not None:
        node = PlanNode("Limit", f"[{q.limit}]", [node])
        node.meta["limit"] = q.limit
    return node


_EXPLAIN_RE = re.compile(r"^\s*explain(\s+analyze)?\b(.*)$",
                         re.IGNORECASE | re.DOTALL)

#: Plan-node op → the span names that measure it, most specific first.
#: ``frame.grouped.flush:<op>`` keys the grouped-engine flush spans by
#: their ``op`` attribute. Spans are consumed FIFO, so a query with two
#: joins attributes the first ``frame.join`` span to the first Join node.
_NODE_SPAN_CANDIDATES = {
    "FusedStage": ("frame.pipeline.flush", "frame.filter", "frame.select"),
    "ShardedStage": ("frame.pipeline.flush", "frame.filter",
                     "frame.select"),
    "Filter": ("frame.filter",),
    "Project": ("frame.select",),
    "Aggregate": ("frame.agg",),
    "SegmentedAggregate": ("frame.grouped.flush:group_by", "frame.agg"),
    "Having": ("frame.filter",),
    "Sort": ("frame.sort",),
    "DeviceSort": ("frame.sort", "frame.grouped.flush:sort"),
    "Distinct": ("frame.distinct", "frame.drop_duplicates",
                 "frame.grouped.flush:distinct"),
    "Join": ("frame.join",),
}

#: Nodes whose program (if any) is the pipeline compiler's — a deferred
#: filter/projection flushes OUTSIDE its own op span (at the next
#: materialization point), so the verdict may ride an unconsumed
#: ``frame.pipeline.flush`` span at query level. The predicate keys on
#: the flush span's shape: ``steps`` are with_column/filter steps (the
#: Filter node's program), ``outputs`` are fused select projections (the
#: Project node's program); FusedStage owns both.
_PIPELINE_NODE_PRED = {
    "FusedStage": lambda a: True,
    "ShardedStage": lambda a: True,
    "Filter": lambda a: a.get("steps", 0) > 0,
    "Project": lambda a: a.get("outputs", 0) > 0,
}

#: The acceptance contract: EVERY operator node carries these keys after
#: an ANALYZE pass (measured where a span matched, defaults otherwise).
_ANALYZE_DEFAULTS = (("rows_in", None), ("rows_out", None),
                     ("wall_ms", 0.0), ("compile", "none"),
                     ("host_syncs", 0), ("peak_mem", None))


def _annotate_plan(tree: PlanNode, qs) -> None:
    """Attribute one query's collected spans to plan-tree operators.

    ``qs`` is an ``observability.QueryStatsCollector`` whose window was
    exactly this query's execution. Attribution is name-based and FIFO
    (frame ops execute in plan order within one query); the compile-vs-
    cache-hit verdict comes from the operator's own flush span or the
    flush span nested directly under it. After the walk every node holds
    the full stat schema (:data:`_ANALYZE_DEFAULTS`)."""
    by_name: dict[str, list] = {}
    children_of: dict = {}
    for s in qs.spans:
        by_name.setdefault(s.name, []).append(s)
        children_of.setdefault(s.parent_id, []).append(s)
        if s.name == "frame.grouped.flush":
            by_name.setdefault(
                f"frame.grouped.flush:{s.attrs.get('op')}", []).append(s)

    def pop(name, pred=None):
        lst = by_name.get(name)
        for s in list(lst or ()):
            if pred is not None and not pred(s.attrs):
                continue
            for other in by_name.values():   # one span feeds ONE node
                if s in other:
                    other.remove(s)
            return s
        return None

    peak_attr = max((s.attrs.get("peak_mem", 0) for s in qs.spans),
                    default=0) or None
    # EXECUTION order, not render order: spans arrive input-side-first,
    # and FIFO queues must be consumed the same way (a root-first walk
    # would hand the WHERE filter's span to the Having node).
    for node in tree.execution_order():
        primary = None
        for name in _NODE_SPAN_CANDIDATES.get(node.op, ()):
            primary = pop(name)
            if primary is not None:
                break
        stats = node.stats
        if primary is not None:
            a = primary.attrs
            # cost-observatory join handles: the plan key (when the
            # span's program has one) addresses the CostProfile cache;
            # "measured" marks operators that actually ran (the roofline
            # `host` verdict's evidence). meta, never rendered.
            node.meta["measured"] = True
            if a.get("plan_key"):
                node.meta["plan_key"] = a["plan_key"]
            if "rows_in" in a:
                stats["rows_in"] = a.get("rows_in")
                stats["rows_out"] = a.get("rows_out")
            else:                 # a flush span: rows/groups vocabulary
                stats["rows_in"] = a.get("rows")
                stats["rows_out"] = a.get("groups", a.get("rows"))
            stats["wall_ms"] = round((primary.dur_us or 0) / 1e3, 3)
            stats["host_syncs"] = a.get("host_syncs", 0)
            if a.get("peak_mem") is not None:
                stats["peak_mem"] = a["peak_mem"]
            if a.get("lowering"):
                stats["lowering"] = a["lowering"]
            verdict = a.get("cache")
            if verdict is None:
                # the flush program ran nested under this op's span
                # (grouped sort/distinct on accelerators)
                for c in children_of.get(primary.sid, ()):
                    if c.name in ("frame.pipeline.flush",
                                  "frame.grouped.flush") \
                            and c.attrs.get("cache"):
                        verdict = c.attrs["cache"]
                        break
            pred = _PIPELINE_NODE_PRED.get(node.op)
            if verdict is None and pred is not None:
                # deferred pipeline steps flush at the next
                # materialization point, outside the op's own span
                flush = pop("frame.pipeline.flush", pred)
                if flush is not None:
                    verdict = flush.attrs.get("cache")
                    stats["flush_ms"] = round((flush.dur_us or 0) / 1e3, 3)
                    if flush.attrs.get("plan_key"):
                        node.meta["plan_key"] = flush.attrs["plan_key"]
            if verdict is not None:
                stats["compile"] = verdict
            for k, v in a.items():
                if k.startswith("recovery_"):
                    stats[k] = v
        for key, default in _ANALYZE_DEFAULTS:
            stats.setdefault(key, default)
        if stats["peak_mem"] is None:
            stats["peak_mem"] = peak_attr
    # Row counts flow along edges: an operator with no span of its own
    # (Scan, Limit, Offset) inherits its input's output count and its
    # consumer's input count — static shape info, never a device read.
    chain = tree.main_chain()
    for parent, child in zip(chain, chain[1:]):
        if child.stats.get("rows_out") is None \
                and parent.stats.get("rows_in") is not None:
            child.stats["rows_out"] = parent.stats["rows_in"]
        if parent.stats.get("rows_in") is None \
                and child.stats.get("rows_out") is not None:
            parent.stats["rows_in"] = child.stats["rows_out"]


def _filter_history_key(q, cat) -> Optional[str]:
    """The statstore selectivity key a flush of this query's WHERE would
    record under — computed from the parsed predicate plus the scanned
    view's REAL column dtypes (catalog lookup; zero execution, zero
    device reads). None when the view is unregistered, the predicate is
    not structurally compilable (those flushes run eager and record no
    history), or the query joins (the flush-time schema then carries
    joined columns this static walk cannot see)."""
    view = q.view if isinstance(q.view, str) else None
    if view is None or q.where is None or q.joins:
        return None
    try:
        frame = cat.lookup(view)
    except Exception:
        return None
    # Mirror the executor's name resolution (qualified ``t.x`` refs
    # rewrite to flat columns BEFORE the filter defers — the flush-time
    # history key is recorded against the RESOLVED predicate). Subquery
    # markers are deliberately NOT resolved here (that would execute
    # them); they fail the compilability walk below and yield None,
    # exactly like their flushes record nothing.
    where = q.where
    try:
        scope = {(q.view_alias or view).lower():
                 {c: c for c in frame.columns}}
        where = _resolve_qualified(where, scope, frame.columns)
    except Exception:
        return None
    from ..ops import compiler as C

    return C.selectivity_key_for((("filter", where),),
                                 frame._pipe_schema())


def _annotate_est_rows(tree: PlanNode, cat) -> None:
    """History-informed cardinality column (``est_rows``) — the plan-
    stats observatory's EXPLAIN surface, next to dqaudit's ``est_peak``:
    Scan rows are static slot counts, Filter/FusedStage apply the
    HISTORICAL selectivity recorded for the structurally-same filter
    stack (``utils.statstore``; persisted across sessions), and
    row-preserving operators propagate. Unknowns stay None and render as
    ``-``. Zero execution: catalog lookups + one ``_linearize`` walk per
    filter, never a compile or device read (the deferred-observation
    drain is a host pull of already-dispatched scalars). Never raises —
    estimation is advisory."""
    from ..utils import statstore as _stats

    try:
        _stats.STORE.drain_pending()
    except Exception:
        pass
    #: CTE-name -> estimated rows (filled from the With wrapper's CTE
    #: bodies BEFORE the main query annotates, so a Scan of a CTE name
    #: resolves history-informed cardinality instead of going "-")
    cte_est: dict[str, int] = {}

    def est(node) -> Optional[int]:
        try:
            child = est(node.children[0]) if node.children else None
        except RecursionError:   # pathological depth: stop annotating
            return None
        out: Optional[int] = None
        op = node.op
        if op == "Scan":
            view = node.meta.get("view")
            if isinstance(view, str):
                if view.lower() in cte_est:
                    out = cte_est[view.lower()]
                else:
                    try:
                        out = int(cat.lookup(view).num_slots)
                    except Exception:
                        out = None
            else:
                out = child      # derived table: its subquery's estimate
        elif op in ("FusedStage", "ShardedStage", "Filter"):
            q = node.meta.get("query")
            if child is not None and q is not None:
                skey = _filter_history_key(q, cat)
                if skey is not None:
                    sel = _stats.STORE.selectivity(skey)
                    if sel is not None:
                        out = int(round(sel * child))
        elif op in ("Project", "Sort", "DeviceSort", "Exchange"):
            out = child
        elif op == "Limit":
            lim = node.meta.get("limit")
            out = (min(child, int(lim)) if child is not None
                   and lim is not None else None)
        elif op == "Offset":
            off = node.meta.get("offset")
            out = (max(child - int(off), 0) if child is not None
                   and off is not None else None)
        elif op in ("Aggregate", "SegmentedAggregate", "Distinct"):
            # output-cardinality history (ROADMAP item 4's named
            # headroom): the grouped engine records observed
            # rows-in → groups-out under a name+dtype-addressed key
            # (ops/segments.cardinality_history_key), so aggregates no
            # longer estimate blind — the recorded group ratio scales
            # the input estimate. Still advisory; unknown stays "-".
            q = node.meta.get("query")
            if child is not None and q is not None:
                ckey = _cardinality_history_key(q, cat,
                                                op == "Distinct")
                if ckey is not None:
                    sel = _stats.STORE.selectivity(ckey)
                    if sel is not None:
                        out = int(round(sel * child))
        # Join/SetOps output cardinality has no history key yet —
        # stays unknown rather than a guess. DDL and wrapper nodes
        # have no cardinality at all and stay unannotated.
        if op not in ("CreateView", "DropView", "With", "SetOps"):
            node.stats["est_rows"] = out
        # cardinality propagates along children[0], but side arms (a
        # Join's probe-side Scan) still deserve their own annotation —
        # the column must not silently disappear on the right arm
        for side in node.children[1:]:
            est(side)
        return out

    def annotate(node) -> Optional[int]:
        """Wrapper-aware walk: With annotates its CTE bodies first (in
        registration order — later CTEs may scan earlier ones) and
        propagates the main query's estimate onto the wrapper; SetOps
        annotates every branch and folds branch estimates through the
        operator chain (UNION sums — an upper bound under dedup —,
        INTERSECT takes the min, EXCEPT keeps the left bound)."""
        if node.op == "With":
            for name, sub in zip(node.meta.get("cte_names") or (),
                                 node.children[1:]):
                v = annotate(sub)
                if v is not None:
                    cte_est[str(name).lower()] = v
            out = annotate(node.children[0]) if node.children else None
            node.stats["est_rows"] = out
            return out
        if node.op == "SetOps":
            vals = [annotate(c) for c in node.children]
            out = vals[0] if vals else None
            for op, v in zip(node.meta.get("set_ops") or (), vals[1:]):
                if op in ("union", "union_all"):
                    out = out + v if out is not None and v is not None \
                        else None
                elif op == "intersect":
                    out = min(out, v) if out is not None and v is not None \
                        else None
                # except: the left branch bound stands
            node.stats["est_rows"] = out
            return out
        if node.op == "CreateView":
            for c in node.children:
                annotate(c)
            return None
        return est(node)

    try:
        annotate(tree)
    except Exception:
        pass


def _cardinality_history_key(q, cat, distinct: bool):
    """The statstore output-cardinality key a grouped/distinct flush of
    this query would record under (``ops/segments.
    cardinality_history_key`` — name+dtype addressed, zero execution).
    None when the view is unregistered, the query joins (the flush-time
    frame carries joined columns this static walk cannot see), or any
    key is not a plain resolvable column."""
    view = q.view if isinstance(q.view, str) else None
    if view is None or q.joins:
        return None
    try:
        frame = cat.lookup(view)
    except Exception:
        return None
    if distinct:
        names = []
        for it in q.items:
            # plain column projections only (str or a bare Col ref) —
            # computed items change the distinct key surface in ways
            # this static probe cannot mirror
            if isinstance(it, str) and it != "*":
                names.append(it)
            elif isinstance(it, E.Col):
                names.append(it.name)
            else:
                return None
        if not names:
            return None
    else:
        names = [k for k in q.group_by if isinstance(k, str)]
        if len(names) != len(q.group_by) or not names:
            return None
    arrs = [frame._data_store.get(n) for n in names]
    if any(a is None for a in arrs):
        return None
    from ..ops import segments as _segments

    return _segments.cardinality_history_key(
        "d" if distinct else "g", names, arrs)


def _annotate_costs(tree: PlanNode) -> None:
    """Device-cost observatory columns (``utils/costprof.py``) for
    EXPLAIN ANALYZE: per operator node, the AOT cost profile addressed
    by the plan key its flush span carried (``est_flops``/``est_bytes``),
    achieved throughput against the node's measured wall
    (``gflops``/``gbps`` — structural on the CPU sandbox, meaningful on
    TPU captures), and the roofline ``bound`` verdict
    (compute|memory|sync|host). COLD surface: a cache-miss profile can
    cost one XLA compile of the un-counted trace body — zero device
    execution, zero counted host syncs, zero counted compiles
    (test-pinned). A degraded extraction (the ``cost_profile`` fault
    ladder) leaves every column "-". Never raises — cost annotation is
    advisory."""
    from ..utils import costprof as _costprof

    try:
        # ONE batched resolution (one registry enumeration) for every
        # keyed node, then a second walk annotates
        profiles = _costprof.profiles_for(
            n.meta.get("plan_key") for n in tree.execution_order())
        for node in tree.execution_order():
            stats = node.stats
            if "wall_ms" not in stats:
                continue              # un-analyzed node (no stat schema)
            key = node.meta.get("plan_key")
            prof = profiles.get(key) if key else None
            wall = stats.get("flush_ms") or stats.get("wall_ms")
            gflops, gbps = _costprof.achieved(prof, wall)
            if prof is not None:
                bound = _costprof.roofline(
                    prof, int(stats.get("host_syncs") or 0))
            elif key:
                bound = None          # extraction degraded: render "-"
            elif node.meta.get("measured"):
                bound = "host"        # ran, but with no device program
            else:
                bound = None
            stats["est_flops"] = (None if prof is None
                                  else int(prof.flops))
            stats["est_bytes"] = (None if prof is None
                                  else int(prof.bytes_accessed))
            stats["gflops"] = gflops
            stats["gbps"] = gbps
            stats["bound"] = bound
    except Exception:
        pass


def _annotate_sharded(tree: PlanNode, cat) -> None:
    """Sharded-frames EXPLAIN markers (``spark.shard.enabled``): when a
    scanned view's frame is row-sharded, Scan nodes carry the per-shard
    row counts, the fused stage renders as ``ShardedStage[k]`` (one
    ``shard_map`` program over ``k`` shards, zero cross-shard traffic),
    and operators that move rows across shards gain an ``Exchange``
    child — ``[merge:psum]`` under grouped aggregation (the per-shard
    slot-table merge collective), ``[hash:all_to_all]`` under DISTINCT
    and join (the shuffle lowering), ``[gather]`` under a total sort.
    Pure annotation: zero execution, never raises."""
    from ..parallel.shard import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return
    k = int(mesh.devices.size)

    def store_of(node):
        view = node.meta.get("view")
        if not isinstance(view, str):
            return None
        try:
            return getattr(cat.lookup(view), "_shard", None)
        except Exception:
            return None

    def exchange(node, kind):
        node.children[0] = PlanNode("Exchange", f"[{kind}]",
                                    [node.children[0]])

    def visit(node) -> bool:
        """Returns whether the node's OUTPUT rows are shard-resident."""
        child_sharded = [visit(c) for c in node.children]
        if node.op == "Scan":
            store = store_of(node)
            if store is not None:
                node.stats["shards"] = store.devices
                node.stats["rows_per_shard"] = "/".join(
                    str(c) for c in store.shard_counts())
                return True
            return bool(child_sharded) and child_sharded[0]
        inp = bool(child_sharded) and child_sharded[0]
        if node.op == "FusedStage" and inp:
            node.op = "ShardedStage"
            node.detail = f"[{k}]" + node.detail
            return True
        if node.op in ("Filter", "Project", "Having", "Offset",
                       "Limit") and inp:
            return True
        if node.op in ("Aggregate", "SegmentedAggregate") and inp:
            exchange(node, "merge:psum")
            return False
        if node.op == "Distinct" and inp:
            exchange(node, "hash:all_to_all")
            return False
        if node.op in ("Sort", "DeviceSort") and inp:
            exchange(node, "gather")
            return False
        if node.op == "Join" and any(child_sharded):
            for i, sharded in enumerate(child_sharded):
                if sharded:
                    node.children[i] = PlanNode(
                        "Exchange", "[hash:all_to_all]",
                        [node.children[i]])
            return False
        return False

    try:
        visit(tree)
    except Exception:
        pass


def _parse_explain_tree(body: str):
    """Parse an EXPLAIN'd statement into ``(plan_tree, kind, payload)``:
    ``("query", Query)`` for a SELECT statement, ``("create"|"drop",
    body)`` for the DDL forms (their child tree is the materializing
    query's plan)."""
    m = _DDL_RE.match(body)
    if m:
        name, inner = m.group(1), m.group(2)
        sub = _EXPLAIN_RE.match(inner)
        if sub:       # EXPLAIN CREATE VIEW v AS EXPLAIN ... is nonsense
            raise ValueError("nested EXPLAIN is not supported")
        tree = PlanNode("CreateView", f"[{name}]",
                        [plan_tree(parse(inner))])
        return tree, "create", body
    m = _DROP_RE.match(body)
    if m:
        return PlanNode("DropView", f"[{m.group(2)}]"), "drop", body
    q = parse(body)
    return _wrap_plan_tree(q), "query", q


def _wrap_plan_tree(q: Query) -> PlanNode:
    """Plan tree for a full statement: the main query's tree plus the
    With/SetOps wrapper nodes. ``meta`` carries the CTE names and the
    set-operator list so ``_annotate_est_rows`` can propagate
    cardinality through the wrappers (a Scan of a CTE name resolves
    against the CTE body's estimate, not the catalog)."""
    tree = plan_tree(q)
    if q.ctes:
        # children[0] = main query; children[1:] = the CTE bodies in
        # registration order (execution_order runs them first)
        tree = PlanNode("With", f"[{len(q.ctes)}]",
                        [tree] + [plan_tree(sub) for _name, sub in q.ctes])
        tree.meta["cte_names"] = [name for name, _sub in q.ctes]
    if q.unions:
        tree = PlanNode("SetOps", f"[+{len(q.unions)}]",
                        [tree] + [plan_tree(sub) for _op, sub in q.unions])
        tree.meta["set_ops"] = [op for op, _sub in q.unions]
    return tree


def _cache_lines(before: dict, after: dict) -> list[str]:
    """One line per cache (and per cached program) the query touched —
    the diff of two ``observability.cache_report()`` snapshots."""
    lines: list[str] = []
    for name, post in sorted(after.items()):
        pre = before.get(name, {})
        if not isinstance(post, dict) or not isinstance(pre, dict):
            continue
        deltas = {}
        for k in ("hits", "misses", "evictions", "fallbacks",
                  "dense_misses"):
            d = (post.get(k) or 0) - (pre.get(k) or 0)
            if d:
                deltas[k] = d
        if not deltas and post.get("entries") == pre.get("entries"):
            continue
        summary = " ".join(f"{k}+{v}" for k, v in deltas.items())
        lines.append(f"{name}: size={post.get('size', '?')}"
                     + (f" {summary}" if summary else ""))
        pre_entries = {e.get("key"): e for e in pre.get("entries") or ()}
        for e in post.get("entries") or ():
            p = pre_entries.get(e.get("key"), {})
            touched = any((e.get(k) or 0) > (p.get(k) or 0)
                          for k in ("hits", "compiles", "builds"))
            if not touched:
                continue
            # program_key duplicates key= (it is the un-truncated form
            # the program auditor addresses) — one rendering is enough
            detail = {k: v for k, v in e.items()
                      if k not in ("key", "program_key")}
            from ..utils.logging import format_kv

            lines.append(f"  program {format_kv(**detail)} key="
                         f"{e.get('key', '')!r}")
    return lines


def _execute_explain(body: str, cat, analyze: bool):
    """Run an ``EXPLAIN [ANALYZE]`` statement. EXPLAIN renders the
    structural plan tree WITHOUT executing (zero compiles, zero device
    work — pure parsing); EXPLAIN ANALYZE executes the statement under a
    per-query stats collector (``observability.query_stats``) and
    annotates every operator with measured rows, wall ms, compile/hit
    verdicts, host syncs, recovery events, and peak device bytes, plus a
    cache section (one line per compiled program touched). Returns a
    one-row Frame with the plan text in a ``plan`` column (the Spark
    ``EXPLAIN`` result shape)."""
    from ..config import config as _cfg
    from ..frame.frame import Frame

    tree, kind, payload = _parse_explain_tree(body)
    # Cost-based optimizer (sql/optimizer.py): rewrite the parsed query
    # exactly as execution would — zero execution, static metadata +
    # statstore history only — and render the before/after plan diff
    # plus one line per applied rewrite. The optimized payload is what
    # ANALYZE then executes, so the annotated tree matches the plan
    # that actually ran.
    opt_rewrites: list[str] = []
    before_text: Optional[str] = None
    if kind == "query" and _cfg.optimizer_enabled:
        from . import optimizer as _optimizer

        q_opt, rewrites = _optimizer.optimize_or_fallback(payload, cat)
        if rewrites:
            before_text = tree.render()
            tree = _wrap_plan_tree(q_opt)
            payload = q_opt
            opt_rewrites.extend(str(r) for r in rewrites)
    _annotate_sharded(tree, cat)
    _obs.current_span().set(
        plan=("ExplainAnalyze" if analyze else "Explain"))
    # Static memory bounds (dqaudit tier, analysis/program/static_mem):
    # the `est peak` column — computed BEFORE execution from shape
    # metadata + one abstract trace of the fused stage (zero compiles,
    # zero device work), where EXPLAIN ANALYZE only measures after the
    # fact. Gated on spark.audit.enabled; the audit package imports
    # lazily so the default query path never loads it.
    budget_line = None
    if _cfg.audit_enabled:
        from ..analysis.program import static_mem as _static_mem
        from ..analysis.program.detectors import audit_budget_bytes

        root_est = _static_mem.annotate_plan(tree, cat)
        if root_est is not None:
            # the SAME budget policy as the audit-memory detector —
            # EXPLAIN and session.audit_report() must agree on one plan
            budget = audit_budget_bytes(int(_cfg.audit_device_budget))
            if budget is not None and \
                    root_est > _cfg.audit_memory_fraction * budget:
                budget_line = (
                    f"!! est peak {root_est} bytes exceeds "
                    f"{_cfg.audit_memory_fraction:g} x device limit "
                    f"{budget} bytes (spark.audit.memoryFraction)")
                if _cfg.optimizer_enabled:
                    # the PR-9 static bound, promoted to a PLANNED
                    # decision: over-budget flushes run row-chunked
                    # up front (ops/compiler.run_pipeline), not as an
                    # allocator-fault ladder rung
                    opt_rewrites.append(
                        "mem-chunk: planned row-chunked execution "
                        f"(est peak {root_est} B vs budget {budget} B)")
    # History-informed `est rows` (plan-stats observatory,
    # utils/statstore.py): annotated BEFORE any execution — on plain
    # EXPLAIN this is the whole point (zero-execution cardinality from
    # persisted history), on ANALYZE it is the *pre-query* historical
    # view the measured rows are then compared against (drift).
    if _cfg.stats_enabled:
        _annotate_est_rows(tree, cat)
    def _opt_sections() -> list[str]:
        out: list[str] = []
        if opt_rewrites:
            out.append("== Rewrites ==")
            out.extend(opt_rewrites)
        if before_text is not None:
            out.append("== Before Optimization ==")
            out.append(before_text)
        return out

    if not analyze:
        text = "== Physical Plan ==\n" + tree.render()
        if budget_line:
            text += "\n" + budget_line
        for ln in _opt_sections():
            text += "\n" + ln
        return Frame({"plan": [text]})

    import time as _time

    import jax as _jax

    from . import adaptive as _adaptive

    caches_before = _obs.cache_report() if _cfg.explain_caches else {}
    # Data-quality observatory marks (utils/dqprof.py) — gated on ONE
    # flag read; disabled restores the exact pre-observatory ANALYZE
    # schema (acceptance-pinned byte-identical). The pre-execution
    # drain is this cold surface's own counted sync, outside the
    # query-stats window so per-query attribution is untouched.
    dq_marks = None
    if _cfg.dq_profile_enabled:
        from ..utils import dqprof as _dqprof

        dq_marks = _dqprof.rule_marks()
    # ANALYZE executes under the adaptive capture scope: any mid-query
    # re-plan the hooks apply (sql/adaptive.py) records an event here
    # and renders as the `== Adaptive ==` section. No events (AQE off,
    # or simply no drift) -> no section — output stays byte-identical
    # to the static engine.
    with _adaptive.capture() as aqe_events, \
            _obs.query_stats(sample_memory=_cfg.explain_memory) as qs:
        t0 = _time.perf_counter()
        if kind == "query":
            out = _run_parsed(payload, cat)
        else:
            out = _execute_statement(payload, cat)
        # honest wall-clock: flush any pending fused pipeline and wait
        # for the async dispatches the query enqueued
        _jax.block_until_ready(out._mask)
        wall_ms = (_time.perf_counter() - t0) * 1e3
    _annotate_plan(tree, qs)
    # Device-cost observatory columns (utils/costprof.py) — gated on
    # ONE flag read; disabled restores the exact pre-observatory
    # ANALYZE schema (acceptance-pinned byte-identical).
    if _cfg.costprof_enabled:
        _annotate_costs(tree)
    top = tree.main_chain()[0]
    if top.stats.get("rows_out") is None:
        top.stats["rows_out"] = out.num_slots
    rows_valid = None
    if _cfg.stats_enabled:
        # Observed-vs-historical drift: the query's TRUE valid-row count
        # (one mask reduction, outside the stats window so per-operator
        # attribution is untouched) against the pre-query est_rows. The
        # same execution's own deferred observation lands in the store,
        # so the NEXT estimate has already absorbed this drift.
        try:
            rows_valid = int(out.count())
        except Exception:
            rows_valid = None
        top.stats["rows_valid"] = rows_valid
        est = top.stats.get("est_rows")
        if est is not None and rows_valid is not None:
            top.stats["est_drift"] = (
                f"x{est / rows_valid:.2f}" if rows_valid
                else f"+{est}")
        from ..utils import statstore as _statstore

        _statstore.STORE.absorb_query_stats(qs)
    delta = qs.counter_delta()
    lines = ["== Analyzed Plan ==", tree.render(analyze=True),
             "== Query Stats =="]
    from ..utils.logging import format_kv

    totals = {
        "wall_ms": round(wall_ms, 3),
        "rows_out": out.num_slots,
        "host_syncs": delta.get("frame.host_sync", 0),
        "compiles": (delta.get("pipeline.compile", 0)
                     + delta.get("grouped.compile", 0)),
        "cache_hits": (delta.get("pipeline.hit", 0)
                       + delta.get("grouped.hit", 0)),
        "fallbacks": (delta.get("pipeline.fallback", 0)
                      + delta.get("grouped.fallback", 0)),
        # action-level keys only: the per-site mirrors
        # (recovery.retry.<site>) would double-count every event
        "recovery_events": sum(v for k, v in delta.items()
                               if k.startswith("recovery.")
                               and "." not in k[len("recovery."):]),
    }
    if _cfg.explain_memory:
        from ..utils import meminfo as _meminfo

        totals["live_bytes"] = _meminfo.sample()
        totals["peak_bytes"] = _meminfo.peak_bytes()
    lines.append(format_kv(**totals))
    if _cfg.explain_caches:
        cl = _cache_lines(caches_before, _obs.cache_report())
        if cl:
            lines.append("== Caches ==")
            lines.extend(cl)
    if budget_line:
        lines.append(budget_line)
    lines.extend(_opt_sections())
    if aqe_events:
        lines.append("== Adaptive ==")
        lines.extend(_adaptive.render(aqe_events))
    if dq_marks is not None:
        from ..utils import dqprof as _dqprof

        # renders only when this query evaluated a registered DQ rule
        # (delta over dq_marks) — rule-free ANALYZE stays byte-identical
        lines.extend(_dqprof.explain_lines(dq_marks))
    return Frame({"plan": ["\n".join(lines)]})


def execute(sql: str, catalog=None):
    """Run a statement (WITH CTEs + query + UNIONs) against the catalog.

    Besides queries, two DDL forms Spark users reach for from
    ``session.sql``: ``CREATE [OR REPLACE] [TEMP] VIEW name AS query``
    (materializes the query and registers it — all views here are temp
    views over device-resident Frames) and ``DROP [TEMP] VIEW
    [IF EXISTS] name``. Both return an empty no-column Frame like
    Spark's DDL commands.

    While the tracer records, each statement runs inside an ``sql.query``
    span carrying the query text, the plan summary (:func:`plan_summary`),
    and the output row count; its children ``sql.parse``, ``sql.optimize``
    (rewrites applied) and ``sql.execute`` tell the statement's host cost
    (text to plan) from its execution.
    """
    if not _obs.TRACER.recording:
        return _execute_statement(sql, catalog)
    with _obs.span("sql.query", cat="sql",
                   query=" ".join(sql.split())[:300]) as s:
        out = _execute_statement(sql, catalog)
        n = getattr(out, "_n", None)
        if n is not None:
            s.set(rows_out=n)
        return out


def _maybe_optimize(q: Query, cat):
    """Cost-based rewrite hook (``sql/optimizer.py``), gated on
    ``spark.optimizer.enabled`` — ONE flag read when disabled. Any
    optimizer failure (including the injected ``optimizer`` fault)
    degrades to the unrewritten plan inside ``optimize_or_fallback``."""
    from ..config import config as _cfg

    if not _cfg.optimizer_enabled or getattr(q, "_optimized", False):
        return q
    from . import optimizer as _optimizer

    with _obs.span("sql.optimize", cat="sql") as s:
        q2, rewrites = _optimizer.optimize_or_fallback(q, cat)
        s.set(rewrites=len(rewrites))
    return q2


def _execute_optimized(q, cat):
    """Optimize, then execute, one set expression (the ``sql.optimize``
    and ``sql.execute`` children of the statement's ``sql.query``)."""
    q = _maybe_optimize(q, cat)
    with _obs.span("sql.execute", cat="sql"):
        return _execute_set(q, cat)


def _run_parsed(q: Query, cat):
    """Execute an already-parsed query: CTE overlay + set expression.
    Each CTE body and the main set expression pass through the
    cost-based optimizer first (CTE frames are registered in the overlay
    before the main query optimizes, so its relation metadata resolves
    CTE names like any view)."""
    if q.ctes:
        cat = _OverlayCatalog(cat)
        for name, sub in q.ctes:
            # Later CTEs may reference earlier ones (executed in order).
            cat.register(name, _execute_optimized(sub, cat))
    return _execute_optimized(q, cat)


def _execute_statement(sql: str, catalog=None):
    from .catalog import default_catalog

    cat = catalog if catalog is not None else default_catalog()
    m = _EXPLAIN_RE.match(sql)
    if m and m.group(2).strip():
        return _execute_explain(m.group(2), cat, analyze=bool(m.group(1)))
    m = _DDL_RE.match(sql)
    if m:
        name, body = m.group(1), m.group(2)
        if _obs.TRACER.recording:
            # format only when the span is live (off-mode no-op)
            _obs.current_span().set(plan=f"CreateView[{name}]")
        frame = execute(body, cat)
        cat.register(name, frame)
        from ..frame.frame import Frame

        return Frame({"__one_row__": [0.0]}).drop("__one_row__").limit(0)
    m = _DROP_RE.match(sql)
    if m:
        if_exists, name = bool(m.group(1)), m.group(2)
        if _obs.TRACER.recording:
            # format only when the span is live (off-mode no-op)
            _obs.current_span().set(plan=f"DropView[{name}]")
        existed = cat.drop(name)
        if not existed and not if_exists:
            raise KeyError(f"temp view {name!r} not found")
        from ..frame.frame import Frame

        return Frame({"__one_row__": [0.0]}).drop("__one_row__").limit(0)
    with _obs.span("sql.parse", cat="sql"):
        q = parse(sql)
    if _obs.TRACER.recording:
        # plan_summary walks the WHERE/projection trees — skip the build
        # entirely when the span is a no-op (the SQL hot path)
        _obs.current_span().set(plan=plan_summary(q))
    return _run_parsed(q, cat)


def _map_cols(expr, fn):
    """Rebuild an expression tree with ``fn`` applied to every Col leaf
    (the shared walk under qualified-ref resolution and agg renaming)."""
    if isinstance(expr, E.Col):
        new = fn(expr.name)
        return expr if new == expr.name else E.Col(new)
    if isinstance(expr, E.SortOrder):
        return E.SortOrder(_map_cols(expr.child, fn), expr.ascending,
                           expr.nulls_first)
    if isinstance(expr, E.BinOp):
        return E.BinOp(expr.op, _map_cols(expr.left, fn),
                       _map_cols(expr.right, fn))
    if isinstance(expr, E.UnaryOp):
        return E.UnaryOp(expr.op, _map_cols(expr.child, fn))
    if isinstance(expr, E.InList):
        return E.InList(_map_cols(expr.child, fn),
                        [_map_cols(v, fn) for v in expr.values],
                        expr.negated)
    if isinstance(expr, E.UdfCall):
        return E.UdfCall(expr.udf_name,
                         [_map_cols(a, fn) for a in expr.args],
                         registry=expr._registry)
    if isinstance(expr, E.Cast):
        return E.Cast(_map_cols(expr.child, fn), expr.type_name)
    if isinstance(expr, E.StringMatch):
        return E.StringMatch(expr.kind, _map_cols(expr.child, fn),
                             expr.pattern, negated=expr.negated)
    if isinstance(expr, E.CaseWhen):
        return E.CaseWhen(
            [(_map_cols(c, fn), _map_cols(v, fn))
             for c, v in expr.branches],
            None if expr.otherwise_expr is None
            else _map_cols(expr.otherwise_expr, fn))
    if isinstance(expr, E.Alias):
        return E.Alias(_map_cols(expr.child, fn), expr._name)
    if isinstance(expr, SubqueryIn):
        # only the OUTER-scope side is mapped; the subquery resolves in
        # its own scope when it executes
        return SubqueryIn(_map_cols(expr.child, fn), expr.query,
                          expr.negated)
    if isinstance(expr, E.HigherOrder):
        # lambda params shadow columns inside the body, so the body's
        # Col refs are left alone; only the source array is mapped
        return E.HigherOrder(expr.kind, _map_cols(expr.source, fn),
                             expr.lam, init=expr.init, finish=expr.finish)
    return expr


def _resolve_agg_cols(agg, scope: dict, columns):
    """Resolve dotted column names inside an AggExpr (mutating the
    parse-fresh object is safe: every Query executes exactly once)."""
    if getattr(agg, "column", None) is not None:
        agg.column = _resolve_name(agg.column, scope, columns)
    if getattr(agg, "column2", None) is not None:
        agg.column2 = _resolve_name(agg.column2, scope, columns)
    return agg


def _resolve_name(name: str, scope: dict, columns) -> str:
    """Resolve a possibly-qualified name against the relation scope.
    A literal column of that (dotted) name wins first — frames may carry
    dotted names from CSV headers; Spark needs backticks there, here the
    literal match is the tiebreak. Names with parens are aggregate-output
    references, never qualified refs."""
    if "." not in name or "(" in name or name in columns:
        return name
    alias, _, col = name.partition(".")
    m = scope.get(alias.lower())
    if m is None:
        raise ValueError(
            f"unknown relation alias {alias!r} in {name!r} "
            f"(aliases in scope: {sorted(scope)})")
    if col not in m:
        raise ValueError(f"column {col!r} not found in relation "
                         f"{alias!r} (has: {sorted(m)})")
    return m[col]


def _resolve_qualified(expr, scope: dict, columns):
    """Rewrite qualified Col refs (``t.price``) to flat output columns;
    inside post-aggregate items, also re-point references at the
    aggregates' renamed output columns (``max(t.p)`` → ``max(p)``)."""
    if not scope:
        return expr
    if isinstance(expr, PostAggItem):
        renames = {}
        aggs = []
        for a in expr.aggs:
            old = a.name
            a = _resolve_agg_cols(a, scope, columns)
            if a.name != old:
                renames[old] = a.name
            aggs.append(a)
        inner = expr.expr
        if renames:
            inner = _map_cols(inner, lambda n: renames.get(n, n))
        inner = _map_cols(inner,
                          lambda n: _resolve_name(n, scope, columns))
        return PostAggItem(inner, aggs, expr._name)
    return _map_cols(expr, lambda n: _resolve_name(n, scope, columns))


def _referenced_cols(expr, out: set) -> None:
    """Collect every column name an expression tree references."""
    if isinstance(expr, E.Col):
        out.add(expr.name)
    for attr in ("left", "right", "child", "otherwise_expr"):
        v = getattr(expr, attr, None)
        if v is not None:
            _referenced_cols(v, out)
    for v in getattr(expr, "args", None) or ():
        _referenced_cols(v, out)
    for v in getattr(expr, "values", None) or ():
        _referenced_cols(v, out)
    for c, v in getattr(expr, "branches", None) or ():
        _referenced_cols(c, out)
        _referenced_cols(v, out)


def _sort_with_exprs(frame, order_by, extra_drops=()):
    """Sort by a mix of column names, SortOrder markers (direction +
    NULLS FIRST/LAST), and expressions: expression keys materialize as
    temp columns (one fused device pass each), sort, then drop the temps
    plus any caller-supplied post-sort columns."""
    cols, asc, temps = [], [], []
    for i, (key, a) in enumerate(order_by):
        if isinstance(key, str):
            cols.append(key)
        elif isinstance(key, E.SortOrder):
            if not isinstance(key.child, E.Col):
                tmp = f"__ord_{i}"
                frame = frame.with_column(tmp, key.child)
                temps.append(tmp)
                key = E.SortOrder(E.Col(tmp), key.ascending,
                                  key.nulls_first)
            cols.append(key)
        else:
            tmp = f"__ord_{i}"
            frame = frame.with_column(tmp, key)
            temps.append(tmp)
            cols.append(tmp)
        asc.append(a)
    frame = frame.sort(*cols, ascending=asc)
    drops = temps + [c for c in extra_drops if c in frame.columns]
    return frame.drop(*drops) if drops else frame


def _execute_single(q: Query, cat):
    """Run one SELECT (no union handling) and return a Frame."""
    from ..frame.aggregates import AggExpr

    scope: dict = {}       # relation alias → {source col: output col}
    if q.view is None:
        # OneRowRelation: a single anonymous row for literal projections
        from ..frame.frame import Frame

        frame = Frame({"__one_row__": [0.0]}).drop("__one_row__")
    elif isinstance(q.view, DerivedTable):
        frame = _execute_set(q.view.query, cat)
        if q.view.alias:
            scope[q.view.alias.lower()] = {c: c for c in frame.columns}
    else:
        frame = cat.lookup(q.view)
        # the alias replaces the name when given (Spark scoping)
        scope[(q.view_alias or q.view).lower()] = \
            {c: c for c in frame.columns}
    build_hints = list(getattr(q, "join_build", ()) or ())
    # optimizer-attached (left, right) row-estimate pairs per join — the
    # drift baseline the adaptive hooks compare observed counts against
    join_ests = list(getattr(q, "join_est", ()) or ())
    rights: dict = {}

    def relation(jidx):
        if jidx not in rights:
            view = q.joins[jidx][0]
            rights[jidx] = (_execute_set(view.query, cat)
                            if isinstance(view, DerivedTable)
                            else cat.lookup(view))
        return rights[jidx]

    if any(_unsettled(j) for j in q.joins):
        # ON pairs and comma relations the optimizer did not settle from
        # the catalog (it is off, or a relation is a derived table):
        # settle them against the frames' own columns
        q.joins, q.where = resolve_join_keys(
            q, [list(frame.columns)]
            + [list(relation(j).columns) for j in range(len(q.joins))])
    for jidx, (view, how, keys, jalias) in enumerate(q.joins):
        right = relation(jidx)
        rcols = list(right.columns)
        pre = set(frame.columns)
        frame = frame.join(right, on=keys or None, how=how,
                           build=(build_hints[jidx]
                                  if jidx < len(build_hints) else None),
                           est=(join_ests[jidx]
                                if jidx < len(join_ests) else None))
        name = jalias or (view if isinstance(view, str) else None)
        if name:
            post = set(frame.columns)
            if how in ("left_semi", "left_anti"):
                # semi/anti output carries left columns only; the right
                # side is addressable just through the join keys
                mapping = dict(zip(*reversed(join_key_names(keys))))
            else:
                mapping = {c: (f"{c}_right" if c not in keys and c in pre
                               and f"{c}_right" in post else c)
                           for c in rcols}
            scope[name.lower()] = mapping
    # Qualified refs (``t.price``) resolve to flat output columns now
    # that the join scope is known.
    if scope:
        cols_now = frame.columns
        if q.where is not None:
            q.where = _resolve_qualified(q.where, scope, cols_now)
        if q.having is not None:
            q.having = _resolve_qualified(q.having, scope, cols_now)
        q.items = [_resolve_agg_cols(it, scope, cols_now)
                   if isinstance(it, AggExpr)
                   else it if isinstance(it, str)
                   else _resolve_qualified(it, scope, cols_now)
                   for it in q.items]
        q.group_by = [_resolve_name(k, scope, cols_now)
                      if isinstance(k, str) else k for k in q.group_by]
        q.order_by = [(_resolve_name(k, scope, cols_now)
                       if isinstance(k, str)
                       else _resolve_qualified(k, scope, cols_now), a)
                      for k, a in q.order_by]
    # Correlated EXISTS/IN predicates decorrelate into semi/anti joins
    # (the rewrite Spark itself performs). CORRELATED NOT IN keeps the
    # anti-join's null semantics (a null key never matches, so its row
    # survives), not SQL's three-valued NOT IN. The UNCORRELATED path
    # below implements the full three-valued rule: subquery/literal value
    # sets materialize into an InList, whose eval makes NOT IN filter
    # every row when the set contains a NULL/NaN and drops the NULL for
    # plain IN (ops/expressions.InList).
    if q.where is not None and scope:
        q.where, corr_joins = _decorrelate_where(q.where, scope, cat)
        for right, keys, how in corr_joins:
            frame = frame.join(right, on=keys, how=how)
    # Uncorrelated ``expr IN (SELECT c ...)`` conjuncts: left-semi joins
    # against the subquery's frame. The other uncorrelated subqueries
    # (scalar / NOT IN / EXISTS, IN under OR) resolve to literals against
    # the same catalog before the enclosing query evaluates.
    if q.where is not None:
        q.where, frame = _semi_join_in(q.where, frame, scope, cat)
    if q.where is not None:
        q.where = _resolve_subqueries(q.where, cat)
    if q.having is not None:
        q.having = _resolve_subqueries(q.having, cat)
    q.items = [it if isinstance(it, (str, AggExpr))
               else _resolve_subqueries(it, cat) for it in q.items]
    if q.where is not None:
        frame = frame.filter(q.where)
        # Stage boundary (sql/adaptive.py): the WHERE filter just
        # defined the TRUE survivor set behind the mask. When history
        # says far fewer rows survive than the static slot count and a
        # downstream stage exists to profit, compact into the smaller
        # power-of-two bucket so grouping/sort/distinct run with fewer
        # padded slots. ONE conf read when AQE is off.
        from ..config import config as _aqe_cfg

        if _aqe_cfg.aqe_enabled and isinstance(q.view, str) \
                and not q.joins \
                and (q.group_by or q.order_by or q.distinct
                     or any(isinstance(it, AggExpr) for it in q.items)):
            from ..utils import statstore as _statstore
            from . import adaptive as _adaptive

            _skey = _filter_history_key(q, cat)
            if _skey is not None:
                frame = _adaptive.maybe_rebucket(
                    frame,
                    _statstore.STORE.est_rows(_skey, frame.num_slots))

    # ORDER BY <position>: 1-based index into the select list (Spark/ANSI)
    if any(isinstance(k, int) for k, _ in q.order_by):
        resolved = []
        for key, asc in q.order_by:
            if isinstance(key, int):
                if not 1 <= key <= len(q.items):
                    raise ValueError(f"ORDER BY position {key} is not in "
                                     f"the select list (1..{len(q.items)})")
                item = q.items[key - 1]
                if isinstance(item, str):
                    raise ValueError(
                        "ORDER BY position cannot reference *")
                key = item.name
            resolved.append((key, asc))
        q.order_by = resolved

    # GROUP BY <position> / <expression>: positions resolve against the
    # select list; expression keys materialize as device columns before
    # grouping — under the select item's name when the same expression
    # appears there (``SELECT cast(p as int) pi ... GROUP BY cast(p as
    # int)`` groups as ``pi``), else under a temp name the projection
    # drops. Matched select items become plain Col refs so they are not
    # re-evaluated against the aggregated frame.
    if q.group_by and any(not isinstance(k, str) for k in q.group_by):
        keys = []
        for j, key in enumerate(q.group_by):
            if isinstance(key, str):
                keys.append(key)
                continue
            if isinstance(key, int):
                if not 1 <= key <= len(q.items):
                    raise ValueError(f"GROUP BY position {key} is not in "
                                     f"the select list (1..{len(q.items)})")
                item = q.items[key - 1]
                if isinstance(item, str):
                    raise ValueError("GROUP BY position cannot reference *")
                if isinstance(item, AggExpr):
                    raise ValueError(
                        "GROUP BY position cannot reference an aggregate")
                if isinstance(item, E.Col):
                    keys.append(item.name)
                    continue
                name = item.name
                frame = frame.with_column(name, item)
                q.items[key - 1] = E.Col(name)
                keys.append(name)
                continue
            matched = next(
                (idx for idx, it in enumerate(q.items)
                 if not isinstance(it, (str, AggExpr))
                 and (str(it) == str(key)
                      or (isinstance(it, E.Alias)
                          and str(it.child) == str(key)))), None)
            if matched is not None:
                name = q.items[matched].name
                frame = frame.with_column(name, q.items[matched])
                q.items[matched] = E.Col(name)
            else:
                name = f"__grp_{j}"
                frame = frame.with_column(name, key)
            keys.append(name)
        q.group_by = keys

    aggs = [it for it in q.items if isinstance(it, AggExpr)]
    post_items = [it for it in q.items if isinstance(it, PostAggItem)]
    # Component aggregates a post-agg expression needs, minus those the
    # select list already computes (dedup by output-column name).
    known_names = {a.name for a in aggs}
    component_aggs = []
    for it in post_items:
        for a in it.aggs:
            if a.name not in known_names:
                known_names.add(a.name)
                component_aggs.append(a)
    having = q.having
    if (having is not None and not q.group_by
            and not (aggs or post_items)):
        # Spark allows HAVING without GROUP BY only over an aggregate
        # projection (it filters the single global-aggregate row).
        raise ValueError("HAVING requires GROUP BY or an aggregate "
                         "select list")
    if aggs or post_items or q.group_by:
        if any(isinstance(it, str) and it == "*" for it in q.items):
            raise ValueError(
                "SELECT * cannot be combined with aggregates/GROUP BY; "
                "list the grouped columns explicitly")
        non_aggs = [it for it in q.items
                    if not isinstance(it, (AggExpr, PostAggItem, str))]
        for it in non_aggs:
            if not isinstance(it, E.Col) or (q.group_by
                                             and it.name not in q.group_by):
                raise ValueError(
                    f"non-aggregate select item {it} must be a GROUP BY key")
        if q.group_by:
            extra_aggs: list = []
            if having is not None:
                having = _rewrite_having(having, extra_aggs)
            # ORDER BY over aggregates (``ORDER BY count(*) DESC``):
            # rewrite agg calls into references to aggregated output
            # columns, computing any that aren't already in SELECT and
            # dropping them again after the final sort.
            order_by = []
            for key, asc in q.order_by:
                if isinstance(key, E.SortOrder):
                    key = E.SortOrder(_rewrite_having(key.child, extra_aggs),
                                      key.ascending, key.nulls_first)
                elif not isinstance(key, str):
                    key = _rewrite_having(key, extra_aggs)
                    if isinstance(key, E.Col):
                        key = key.name
                order_by.append((key, asc))
            q.order_by = order_by
            known = {a.name for a in aggs} \
                | {a.name for a in component_aggs}
            seen: set = set()
            extra_aggs = [a for a in extra_aggs
                          if a.name not in known and a.name not in seen
                          and not seen.add(a.name)]
            grouped = (frame.rollup(*q.group_by)
                       if q.group_mode == "rollup"
                       else frame.cube(*q.group_by)
                       if q.group_mode == "cube"
                       else frame.group_by(*q.group_by))
            frame = grouped.agg(*aggs, *component_aggs, *extra_aggs)
            if having is not None:
                frame = frame.filter(having)
            for it in post_items:
                frame = frame.with_column(it.name, it.expr)
            keep = [it.name for it in q.items
                    if isinstance(it, (E.Col, AggExpr, PostAggItem))]
            # Columns the final sort still needs (extra aggs referenced
            # by ORDER BY) survive the projection and drop after sorting.
            order_needs: set = set()
            for key, _ in q.order_by:
                if isinstance(key, str):
                    order_needs.add(key)
                else:
                    _referenced_cols(key, order_needs)
            drop_after = [c for c in order_needs
                          if c in frame.columns and c not in keep]
            frame = frame.select(*keep, *drop_after)
            q.drop_after_sort = drop_after
        else:
            if non_aggs:
                raise ValueError("plain columns in an aggregate query "
                                 "require GROUP BY")
            # Global aggregate: HAVING filters the single result row
            # (Spark's groupless HAVING), using component aggregates
            # that are computed then dropped by the final projection.
            having_extras: list = []
            if having is not None:
                having = _rewrite_having(having, having_extras)
                names = {a.name for a in aggs} \
                    | {a.name for a in component_aggs}
                having_extras = [a for a in having_extras
                                 if a.name not in names]
            frame = frame.agg(*aggs, *component_aggs, *having_extras)
            if having is not None:
                frame = frame.filter(having)
            if post_items or having_extras or component_aggs:
                for it in post_items:
                    frame = frame.with_column(it.name, it.expr)
                frame = frame.select(*[it.name for it in q.items])
    else:
        # NB: Expr overloads ==, so compare with identity-safe checks, never
        # `items == ["*"]` (a single-Expr list would compare truthy).
        if (len(q.items) > 1
                and any(isinstance(it, str) and it == "*" for it in q.items)):
            # ``SELECT *, expr`` — expand the star against the (joined,
            # filtered) source columns in place
            expanded: list = []
            for it in q.items:
                if isinstance(it, str) and it == "*":
                    expanded.extend(E.Col(c) for c in frame.columns)
                else:
                    expanded.append(it)
            q2 = Query(expanded, q.view, None, [], q.order_by, q.limit,
                       distinct=q.distinct)
            q2.offset = q.offset
            q = q2
        star = (len(q.items) == 1 and isinstance(q.items[0], str)
                and q.items[0] == "*")
        if q.order_by and not star:
            # SQL sorts before projecting, so ORDER BY may reference columns
            # the SELECT drops — sort first when the source has them all
            # (otherwise fall through: some key must be a SELECT alias).
            # Expression keys materialize as temp columns on the source
            # frame here (they reference source columns); the projection
            # below drops the temps for free.
            keys = []
            for i, (key, asc) in enumerate(q.order_by):
                if isinstance(key, E.SortOrder):
                    if not isinstance(key.child, E.Col):
                        tmp = f"__ord_{i}"
                        frame = frame.with_column(tmp, key.child)
                        key = E.SortOrder(E.Col(tmp), key.ascending,
                                          key.nulls_first)
                elif not isinstance(key, str):
                    tmp = f"__ord_{i}"
                    frame = frame.with_column(tmp, key)
                    key = tmp
                keys.append((key, asc))
            q.order_by = keys
            if all((c if isinstance(c, str) else c.name) in frame.columns
                   for c, _ in q.order_by):
                frame = frame.sort(*[c for c, _ in q.order_by],
                                   ascending=[a for _, a in q.order_by])
                q2 = Query(q.items, q.view, None, [], [], q.limit,
                           distinct=q.distinct)
                q2.offset = q.offset
                q = q2
        if not star:
            keep_for_sort: list = []
            if q.order_by:
                # Post-projection sort (a key is a SELECT alias): any
                # other key column the projection would drop — the
                # __ord_N temps materialized above, or a plain source
                # column — must survive the projection and be dropped
                # after _sort_with_exprs (same drop_after_sort protocol
                # as the aggregate path; ADVICE.md #1).
                produced = {it.name for it in q.items
                            if not isinstance(it, str)}
                needed: set = set()
                for key, _ in q.order_by:
                    if isinstance(key, str):
                        needed.add(key)
                    else:
                        _referenced_cols(key, needed)
                keep_for_sort = [c for c in frame.columns
                                 if c in needed and c not in produced]
                if keep_for_sort and q.distinct:
                    raise ValueError(
                        "SELECT DISTINCT: ORDER BY keys must appear in "
                        "the select list (sorting by "
                        f"{sorted(needed - produced)} would change the "
                        "distinct rows)")
            frame = frame.select(*q.items, *keep_for_sort)
            if keep_for_sort:
                q.drop_after_sort = keep_for_sort

    if q.distinct:
        # SELECT DISTINCT dedups the projected rows (mask-based: keeps the
        # first occurrence, so any pre-projection sort order is preserved).
        frame = frame.distinct()
    if q.order_by:
        frame = _sort_with_exprs(frame, q.order_by,
                                 getattr(q, "drop_after_sort", ()))
    elif getattr(q, "drop_after_sort", ()):
        frame = frame.drop(*q.drop_after_sort)
    if q.offset:
        frame = frame.offset(q.offset)
    if q.limit is not None:
        frame = frame.limit(q.limit)
    return frame
