"""Recursive-descent SQL parser (ref: parser/parser.y grammar, hand-rolled).

Expression precedence ladder (subset of parser/misc.go):
    OR < XOR < AND < NOT < predicate(cmp, IS, LIKE, IN, BETWEEN)
       < add/sub < mul/div/mod < unary < primary
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from tidb_tpu import types as T
from tidb_tpu.errors import ParseError
from tidb_tpu.parser import ast
from tidb_tpu.parser.lexer import Token, tokenize
from tidb_tpu.types import FieldType, TypeKind


def parse(sql: str) -> List[ast.StmtNode]:
    """Parse a semicolon-separated script → statement list."""
    p = Parser(tokenize(sql), sql)
    stmts = []
    while not p.at("eof"):
        if p.try_op(";"):
            continue
        stmts.append(p.statement())
        if not p.at("eof"):
            p.expect_op(";")
    return stmts


def parse_with_text(sql: str) -> List[Tuple[ast.StmtNode, str]]:
    """Like parse(), but pairs each statement with its own source slice
    (for per-statement logging/digests in multi-statement scripts)."""
    toks = tokenize(sql)
    p = Parser(toks, sql)
    out = []
    while not p.at("eof"):
        if p.try_op(";"):
            continue
        start = p.cur.pos
        stmt = p.statement()
        end = p.cur.pos if not p.at("eof") else len(sql)
        out.append((stmt, sql[start:end].strip().rstrip(";").strip()))
        if not p.at("eof"):
            p.expect_op(";")
    return out


def parse_one(sql: str) -> ast.StmtNode:
    stmts = parse(sql)
    if len(stmts) != 1:
        raise ParseError(f"expected one statement, got {len(stmts)}")
    return stmts[0]


class Parser:
    def __init__(self, tokens: List[Token], src: str = ""):
        # hint comments are only meaningful right after SELECT; anywhere
        # else they behave like ordinary comments (dropped), so e.g.
        # INSERT /*+ x() */ INTO keeps parsing
        kept: List[Token] = []
        for t in tokens:
            if t.kind == "hint" and not (kept and kept[-1].is_kw("select")):
                continue
            kept.append(t)
        self.toks = kept
        self.src = src
        self.i = 0

    # ---- token plumbing --------------------------------------------------
    @property
    def cur(self) -> Token:
        return self.toks[self.i]

    def at(self, kind: str) -> bool:
        return self.cur.kind == kind

    def at_kw(self, *kws: str) -> bool:
        return self.cur.is_kw(*kws)

    def at_op(self, *ops: str) -> bool:
        return self.cur.kind == "op" and self.cur.value in ops

    def advance(self) -> Token:
        t = self.cur
        self.i += 1
        return t

    def try_kw(self, *kws: str) -> Optional[Token]:
        if self.at_kw(*kws):
            return self.advance()
        return None

    def try_op(self, *ops: str) -> Optional[Token]:
        if self.at_op(*ops):
            return self.advance()
        return None

    def expect_kw(self, *kws: str) -> Token:
        if not self.at_kw(*kws):
            raise ParseError(
                f"expected {'/'.join(kws).upper()} near {self._near()}")
        return self.advance()

    def expect_op(self, op: str) -> Token:
        if not self.at_op(op):
            raise ParseError(f"expected {op!r} near {self._near()}")
        return self.advance()

    def ident(self) -> str:
        if self.at("ident"):
            return self.advance().value
        # non-reserved keywords usable as identifiers
        if self.cur.kind == "kw" and self.cur.value in (
                "date", "time", "timestamp", "key", "tables", "columns",
                "comment", "engine", "charset", "begin", "analyze", "offset",
                "set", "values", "variables", "if",
                "add", "to", "column", "rename", "over", "partition",
                "alter", "mod", "user", "grants", "privileges", "of",
                "data", "load"):
            return self.advance().value
        raise ParseError(f"expected identifier near {self._near()}")

    def _near(self) -> str:
        t = self.cur
        return f"{t.kind}:{t.value!r} (token {self.i})"

    # ---- statements ------------------------------------------------------
    def statement(self) -> ast.StmtNode:
        if self.at_kw("with"):
            return self.with_stmt()
        if self.at_kw("select") or self.at_op("("):
            return self.select_with_setops()
        if self.at_kw("create"):
            if self.toks[self.i + 1].is_kw("user"):
                return self.create_user()
            nxt = [str(self.toks[self.i + k].value).lower()
                   for k in (1, 2, 3)
                   if self.i + k < len(self.toks)]
            if nxt and (nxt[0] == "view" or nxt[:1] == ["or"]
                        and "view" in nxt):
                return self.create_view()
            return self.create_table()
        if self.at_kw("drop"):
            if self.toks[self.i + 1].is_kw("user"):
                return self.drop_user()
            if str(self.toks[self.i + 1].value).lower() == "view":
                return self.drop_view()
            return self.drop_table()
        if self.at_kw("load"):
            return self.load_data()
        if self.at_kw("backup"):
            self.advance()
            self.expect_kw("to")
            if not self.at("str"):
                raise ParseError(f"expected path string near {self._near()}")
            return ast.BackupStmt(self.advance().value)
        if self.at_kw("restore"):
            self.advance()
            self.expect_kw("from")
            if not self.at("str"):
                raise ParseError(f"expected path string near {self._near()}")
            return ast.RestoreStmt(self.advance().value)
        if self.at_kw("grant"):
            return self.grant_stmt()
        if self.at_kw("revoke"):
            return self.grant_stmt(revoke=True)
        if self.at_kw("alter"):
            return self.alter_table()
        if self.at_kw("truncate"):
            self.advance()
            self.try_kw("table")
            return ast.TruncateTable(self.ident())
        if self.at_kw("insert", "replace"):
            return self.insert()
        if self.at_kw("update"):
            return self.update()
        if self.at_kw("delete"):
            return self.delete()
        if self.at_kw("explain"):
            self.advance()
            analyze = bool(self.try_kw("analyze"))
            return ast.Explain(self.statement(), analyze)
        if self.at_kw("trace"):
            self.advance()
            fmt = "row"
            if self._word("format"):
                self.try_op("=")
                if not self.at("str"):
                    raise ParseError(
                        f"expected format string near {self._near()}")
                fmt = str(self.advance().value).lower()
                if fmt not in ("row", "chrome"):
                    raise ParseError(f"unknown TRACE format {fmt!r}")
            return ast.TraceStmt(self.statement(), fmt)
        if self.at_kw("set"):
            return self.set_stmt()
        if self.at_kw("show"):
            return self.show_stmt()
        if self.at_kw("analyze"):
            self.advance()
            self.expect_kw("table")
            names = [self.ident()]
            while self.try_op(","):
                names.append(self.ident())
            return ast.AnalyzeTable(names)
        if self.at_kw("use"):
            self.advance()
            return ast.UseStmt(self.ident())
        if self.at_kw("begin"):
            self.advance()
            mode = None
            if self.at("ident") and str(self.cur.value).lower() in (
                    "pessimistic", "optimistic"):
                mode = self.advance().value.lower()
            return ast.BeginStmt(mode)
        if self.at_kw("start"):
            self.advance()
            self.expect_kw("transaction")
            return ast.BeginStmt()
        if self.at_kw("commit"):
            self.advance()
            return ast.CommitStmt()
        if self.at_kw("rollback"):
            self.advance()
            return ast.RollbackStmt()
        if self.at("ident") and str(self.cur.value).lower() == "kill":
            # KILL [QUERY|CONNECTION] <id> — "kill" stays an ident (like
            # BEGIN's modes) so it remains usable as a column name
            self.advance()
            query_only = False
            if self.at("ident") and str(self.cur.value).lower() in (
                    "query", "connection"):
                query_only = str(self.advance().value).lower() == "query"
            if not self.at("int"):
                raise ParseError(
                    f"expected connection id near {self._near()}")
            return ast.KillStmt(int(self.advance().value), query_only)
        raise ParseError(f"unsupported statement near {self._near()}")

    def load_data(self) -> ast.StmtNode:
        """LOAD DATA [LOCAL] INFILE 'p' INTO TABLE t
        [FIELDS TERMINATED BY 'c'] [IGNORE n LINES]"""
        self.expect_kw("load")
        self.expect_kw("data")
        if self.at("ident") and str(self.cur.value).lower() == "local":
            self.advance()
        if not (self.at("ident") and
                str(self.cur.value).lower() == "infile"):
            raise ParseError(f"expected INFILE near {self._near()}")
        self.advance()
        if not self.at("str"):
            raise ParseError(f"expected file path near {self._near()}")
        path = self.advance().value
        self.expect_kw("into")
        self.expect_kw("table")
        table = self.ident()
        delimiter = ","
        if self.at("ident") and str(self.cur.value).lower() == "fields":
            self.advance()
            if not (self.at("ident") and
                    str(self.cur.value).lower() == "terminated"):
                raise ParseError(f"expected TERMINATED near {self._near()}")
            self.advance()
            self.expect_kw("by")
            delimiter = self.advance().value
        ignore_lines = 0
        if self.try_kw("ignore"):
            ignore_lines = int(self.advance().value)
            if self.at("ident") and str(self.cur.value).lower() == "lines":
                self.advance()
        return ast.LoadData(table, path, delimiter, ignore_lines)

    # ---- user admin (ref: parser grammar CreateUserStmt/GrantStmt) -------
    def _user_spec(self) -> str:
        """'u'@'host' | u@'host' | u — host is parsed and ignored (the
        single-process engine has no host-based rules)."""
        if self.at("str"):
            name = self.advance().value
        else:
            name = self.ident()
        if self.try_op("@"):
            if self.at("str"):
                self.advance()
            else:
                self.ident()
        return name

    def create_user(self) -> ast.StmtNode:
        self.expect_kw("create")
        self.expect_kw("user")
        if_not_exists = False
        if self.try_kw("if"):
            self.expect_kw("not")
            self.expect_kw("exists")
            if_not_exists = True
        user = self._user_spec()
        password = ""
        if self.try_kw("identified"):
            self.expect_kw("by")
            if not self.at("str"):
                raise ParseError(f"expected password string near "
                                 f"{self._near()}")
            password = self.advance().value
        return ast.CreateUser(user, password, if_not_exists)

    def drop_user(self) -> ast.StmtNode:
        self.expect_kw("drop")
        self.expect_kw("user")
        if_exists = False
        if self.try_kw("if"):
            self.expect_kw("exists")
            if_exists = True
        return ast.DropUser(self._user_spec(), if_exists)

    def grant_stmt(self, revoke: bool = False) -> ast.StmtNode:
        self.advance()                      # GRANT | REVOKE
        privs = []
        while True:
            if self.try_kw("all"):
                self.try_kw("privileges")
                privs.append("ALL")
            elif self.at_kw("select", "insert", "update", "delete",
                            "create", "drop", "alter", "index"):
                privs.append(self.advance().value.upper())
            elif self.at("ident") and \
                    str(self.cur.value).lower() in ("process", "super"):
                # global admin privileges (not reserved words in MySQL)
                privs.append(str(self.advance().value).upper())
            else:
                raise ParseError(f"expected privilege near {self._near()}")
            if not self.try_op(","):
                break
        self.expect_kw("on")
        scope = self._grant_scope()
        self.expect_kw("from" if revoke else "to")
        user = self._user_spec()
        return ast.GrantStmt(privs, scope, user, revoke)

    def _grant_scope(self) -> str:
        if self.try_op("*"):
            if self.try_op("."):
                if self.try_op("*"):
                    return "*.*"
                return f"*.{self.ident()}"
            return "*.*"
        first = self.ident()
        if self.try_op("."):
            if self.try_op("*"):
                return f"{first}.*"
            return f"{first}.{self.ident()}"
        return first

    # ---- SELECT ----------------------------------------------------------
    def with_stmt(self) -> ast.StmtNode:
        self.expect_kw("with")
        recursive = bool(self.try_kw("recursive"))
        ctes = []
        while True:
            name = self.ident()
            cols = None
            if self.try_op("("):
                cols = [self.ident()]
                while self.try_op(","):
                    cols.append(self.ident())
                self.expect_op(")")
            self.expect_kw("as")
            self.expect_op("(")
            sel = self.select_with_setops()
            self.expect_op(")")
            ctes.append(ast.CteDef(name, cols, sel))
            if not self.try_op(","):
                break
        return ast.WithStmt(recursive, ctes, self.select_with_setops())

    def select_with_setops(self) -> ast.StmtNode:
        left = self.select_core()
        while self.at_kw("union", "except", "intersect"):
            op = self.advance().value
            all_ = bool(self.try_kw("all"))
            self.try_kw("distinct")
            # trailing ORDER BY/LIMIT belongs to the set-op, not the operand
            right = self.select_core(allow_tail=False)
            left = ast.SetOpStmt(op, all_, left, right)
        # trailing ORDER BY / LIMIT bind to the set-op result; also handles
        # "(select ...) order by ..." where the parens consumed no tail
        if isinstance(left, (ast.SetOpStmt, ast.SelectStmt)):
            ob = self.order_by_clause()
            lim = self.limit_clause()
            if ob:
                left.order_by = ob
            if lim is not None:
                left.limit = lim
        return left

    def select_core(self, allow_tail: bool = True) -> ast.StmtNode:
        if self.try_op("("):
            s = self.select_with_setops()
            self.expect_op(")")
            return s
        self.expect_kw("select")
        hints = self._parse_hints() if self.at("hint") else []
        distinct = bool(self.try_kw("distinct"))
        self.try_kw("all")
        items = [self.select_item()]
        while self.try_op(","):
            items.append(self.select_item())
        from_ = None
        if self.try_kw("from"):
            from_ = self.table_refs()
        where = self.expr() if self.try_kw("where") else None
        group_by: List[ast.ExprNode] = []
        rollup = False
        if self.try_kw("group"):
            self.expect_kw("by")
            group_by.append(self.expr())
            while self.try_op(","):
                group_by.append(self.expr())
            if self.try_kw("with"):
                self.expect_kw("rollup")
                rollup = True
        having = self.expr() if self.try_kw("having") else None
        order_by = self.order_by_clause() if allow_tail else []
        limit = self.limit_clause() if allow_tail else None
        for_update = False
        if allow_tail and self.try_kw("for"):
            self.expect_kw("update")
            for_update = True
        return ast.SelectStmt(items, from_, where, group_by, having,
                               order_by, limit, distinct,
                               for_update=for_update, hints=hints,
                               rollup=rollup)

    def _parse_hints(self) -> List:
        """/*+ NAME(arg, ...) NAME2() ... */ → [(name_lower, [args])]
        (ref: parser/hintparser.y; unknown hints are kept — the planner
        ignores what it doesn't steer)."""
        import re as _re
        text = str(self.advance().value)
        out = []
        for m in _re.finditer(r"([A-Za-z_]\w*)\s*\(([^()]*)\)", text):
            args = [a.strip().strip("`").lower()
                    for a in m.group(2).split(",") if a.strip()]
            out.append((m.group(1).lower(), args))
        return out

    def select_item(self) -> ast.SelectItem:
        if self.at_op("*"):
            self.advance()
            return ast.SelectItem(ast.Star())
        # t.* form
        if self.at("ident") and self.toks[self.i + 1].kind == "op" \
                and self.toks[self.i + 1].value == "." \
                and self.toks[self.i + 2].kind == "op" \
                and self.toks[self.i + 2].value == "*":
            t = self.advance().value
            self.advance()
            self.advance()
            return ast.SelectItem(ast.Star(table=t))
        e = self.expr()
        alias = None
        if self.try_kw("as"):
            alias = self.ident_or_string()
        elif self.at("ident"):
            alias = self.advance().value
        elif self.at("str"):
            alias = self.advance().value
        return ast.SelectItem(e, alias)

    def ident_or_string(self) -> str:
        if self.at("str"):
            return self.advance().value
        return self.ident()

    def order_by_clause(self):
        out = []
        if self.try_kw("order"):
            self.expect_kw("by")
            while True:
                e = self.expr()
                desc = False
                if self.try_kw("desc"):
                    desc = True
                else:
                    self.try_kw("asc")
                out.append((e, desc))
                if not self.try_op(","):
                    break
        return out

    def limit_clause(self):
        if not self.try_kw("limit"):
            return None
        first = self._int_value()
        if self.try_op(","):
            return (first, self._int_value())
        if self.try_kw("offset"):
            return (self._int_value(), first)
        return (0, first)

    def _int_value(self) -> int:
        if not self.at("int"):
            raise ParseError(f"expected integer near {self._near()}")
        return self.advance().value

    # ---- table references ------------------------------------------------
    def table_refs(self) -> ast.TableRef:
        left = self.join_chain()
        while self.try_op(","):
            right = self.join_chain()
            left = ast.JoinExpr("cross", left, right)
        return left

    def join_chain(self) -> ast.TableRef:
        left = self.table_factor()
        while True:
            kind = None
            if self.try_kw("inner"):
                self.expect_kw("join")
                kind = "inner"
            elif self.try_kw("cross"):
                self.expect_kw("join")
                kind = "cross"
            elif self.at_kw("left", "right"):
                side = self.advance().value
                self.try_kw("outer")
                self.expect_kw("join")
                kind = side
            elif self.try_kw("join"):
                kind = "inner"
            else:
                break
            right = self.table_factor()
            on = None
            using = None
            if self.try_kw("on"):
                on = self.expr()
            elif self.try_kw("using"):
                self.expect_op("(")
                using = [self.ident()]
                while self.try_op(","):
                    using.append(self.ident())
                self.expect_op(")")
            left = ast.JoinExpr(kind, left, right, on, using)
        return left

    def table_factor(self) -> ast.TableRef:
        if self.try_op("("):
            if self.at_kw("select"):
                s = self.select_with_setops()
                self.expect_op(")")
                self.try_kw("as")
                alias = self.ident()
                return ast.SubqueryTable(s, alias)
            refs = self.table_refs()
            self.expect_op(")")
            return refs
        name = self.ident()
        db = None
        if self.try_op("."):
            db = name
            name = self.ident()
        alias = None
        as_of = None
        if self.try_kw("as"):
            if self.try_kw("of"):
                self.expect_kw("timestamp")
                as_of = self.expr()
            else:
                alias = self.ident()
        elif self.at("ident"):
            alias = self.advance().value
        if as_of is not None and alias is None:
            # optional alias AFTER the AS OF clause: t AS OF ... [AS] x
            if self.try_kw("as"):
                alias = self.ident()
            elif self.at("ident"):
                alias = self.advance().value
        return ast.TableName(name, alias, as_of=as_of, db=db)

    # ---- DDL -------------------------------------------------------------
    def create_table(self):
        self.expect_kw("create")
        unique = bool(self.try_kw("unique"))
        if unique or self.at_kw("index", "key"):
            if not self.at_kw("index", "key"):
                raise ParseError(f"expected INDEX near {self._near()}")
            self.advance()                 # INDEX | KEY
            iname = self.ident()
            self.expect_kw("on")
            tname = self.ident()
            self.expect_op("(")
            cols = [self.ident()]
            while self.try_op(","):
                cols.append(self.ident())
            self.expect_op(")")
            return ast.CreateIndex(iname, tname, cols, unique)
        self.expect_kw("table")
        if_not_exists = False
        if self.try_kw("if"):
            self.expect_kw("not")
            # "exists" arrives as kw
            self.expect_kw("exists")
            if_not_exists = True
        name = self.ident()
        self.expect_op("(")
        columns: List[ast.ColumnDef] = []
        pk: List[str] = []
        indexes: List[Tuple[str, List[str]]] = []
        while True:
            if self.try_kw("primary"):
                self.expect_kw("key")
                self.expect_op("(")
                pk = [self.ident()]
                while self.try_op(","):
                    pk.append(self.ident())
                self.expect_op(")")
            elif self.at_kw("key", "index", "unique"):
                unique = bool(self.try_kw("unique"))
                self.try_kw("key") or self.try_kw("index")
                iname = self.ident() if self.at("ident") else f"idx_{len(indexes)}"
                self.expect_op("(")
                cols = [self.ident()]
                while self.try_op(","):
                    cols.append(self.ident())
                self.expect_op(")")
                indexes.append(ast.IndexDef(iname, cols, unique))
            else:
                columns.append(self.column_def())
            if not self.try_op(","):
                break
        self.expect_op(")")
        # swallow table options (ENGINE=x CHARSET=y …) up to an optional
        # PARTITION BY clause
        while not self.at("eof") and not self.at_op(";") \
                and not self.at_kw("partition"):
            self.advance()
        part = self._partition_spec() if self.at_kw("partition") else None
        while not self.at("eof") and not self.at_op(";"):
            self.advance()
        for c in columns:
            if c.primary_key:
                pk = [c.name]
        if pk:
            for c in columns:
                if c.name in pk:
                    c.ftype = c.ftype.with_nullable(False)
        return ast.CreateTable(name, columns, pk, indexes, if_not_exists,
                               part)

    def create_view(self) -> ast.CreateView:
        self.expect_kw("create")
        or_replace = False
        if self.try_kw("or"):
            self.expect_kw("replace")
            or_replace = True
        if not self._word("view"):
            raise ParseError(f"expected VIEW near {self._near()}")
        name = self.ident()
        cols = None
        if self.try_op("("):
            cols = [self.ident()]
            while self.try_op(","):
                cols.append(self.ident())
            self.expect_op(")")
        self.expect_kw("as")
        start = self.cur.pos
        sel = self.select_with_setops()
        end = self.cur.pos if not self.at("eof") else len(self.src)
        text = self.src[start:end].strip().rstrip(";").strip()
        return ast.CreateView(name, sel, cols, or_replace, text)

    def drop_view(self) -> ast.DropView:
        self.expect_kw("drop")
        if not self._word("view"):
            raise ParseError(f"expected VIEW near {self._near()}")
        if_exists = False
        if self.try_kw("if"):
            self.expect_kw("exists")
            if_exists = True
        names = [self.ident()]
        while self.try_op(","):
            names.append(self.ident())
        return ast.DropView(names, if_exists)

    def _word(self, w: str) -> bool:
        """Match a non-reserved word token (ident or kw) by value."""
        if (self.cur.kind in ("ident", "kw")
                and str(self.cur.value).lower() == w):
            self.advance()
            return True
        return False

    def _partition_spec(self) -> ast.PartitionSpec:
        """PARTITION BY RANGE [COLUMNS] (col) (PARTITION p VALUES LESS
        THAN (bound|MAXVALUE), …) | PARTITION BY HASH (col) PARTITIONS n
        (ref: parser/parser.y PartitionOpt)."""
        self.expect_kw("partition")
        self.expect_kw("by")
        if self._word("range"):
            self._word("columns")
            self.expect_op("(")
            col = self.ident()
            self.expect_op(")")
            self.expect_op("(")
            defs = []
            while True:
                self.expect_kw("partition")
                pname = self.ident()
                self.expect_kw("values")
                if not self._word("less") or not self._word("than"):
                    raise ParseError(
                        f"expected VALUES LESS THAN near {self._near()}")
                self.expect_op("(")
                if self._word("maxvalue"):
                    bound = None
                else:
                    bound = self.expr()
                self.expect_op(")")
                defs.append(ast.PartitionDef(pname, bound))
                if not self.try_op(","):
                    break
            self.expect_op(")")
            if not defs:
                raise ParseError("RANGE partitioning needs partitions")
            return ast.PartitionSpec("range", col, defs)
        if self._word("hash"):
            self.expect_op("(")
            col = self.ident()
            self.expect_op(")")
            if not self._word("partitions"):
                raise ParseError(
                    f"expected PARTITIONS near {self._near()}")
            tok = self.advance()
            try:
                num = int(tok.value)
            except (TypeError, ValueError):
                raise ParseError("PARTITIONS requires an integer")
            if num < 1:
                raise ParseError("PARTITIONS must be at least 1")
            return ast.PartitionSpec(
                "hash", col,
                [ast.PartitionDef(f"p{i}") for i in range(num)], num)
        raise ParseError(
            f"unsupported PARTITION BY near {self._near()} "
            f"(RANGE and HASH are supported)")

    def alter_table(self) -> ast.AlterTable:
        self.expect_kw("alter")
        self.expect_kw("table")
        name = self.ident()
        if self.try_kw("add"):
            if self.at_kw("partition"):
                self.advance()
                self.expect_op("(")
                self.expect_kw("partition")
                pname = self.ident()
                self.expect_kw("values")
                if not self._word("less") or not self._word("than"):
                    raise ParseError(
                        f"expected VALUES LESS THAN near {self._near()}")
                self.expect_op("(")
                bound = None if self._word("maxvalue") else self.expr()
                self.expect_op(")")
                self.expect_op(")")
                return ast.AlterTable(name, "add_partition",
                                      partition_def=ast.PartitionDef(
                                          pname, bound))
            self.try_kw("column")
            return ast.AlterTable(name, "add_column",
                                  column=self.column_def())
        if self.try_kw("drop"):
            if self.at_kw("partition"):
                self.advance()
                return ast.AlterTable(name, "drop_partition",
                                      partition_name=self.ident())
            self.try_kw("column")
            return ast.AlterTable(name, "drop_column",
                                  column_name=self.ident())
        if self.try_kw("truncate"):
            self.expect_kw("partition")
            return ast.AlterTable(name, "truncate_partition",
                                  partition_name=self.ident())
        if self.try_kw("rename"):
            self.try_kw("to")
            return ast.AlterTable(name, "rename",
                                  new_name=self.ident())
        raise ParseError(f"unsupported ALTER TABLE near {self._near()}")

    def column_def(self) -> ast.ColumnDef:
        name = self.ident()
        ftype = self.field_type()
        primary = False
        default = None
        nullable = True
        auto_inc = False
        while True:
            if self.try_kw("not"):
                self.expect_kw("null")
                nullable = False
            elif self.try_kw("null"):
                nullable = True
            elif self.try_kw("primary"):
                self.expect_kw("key")
                primary = True
                nullable = False
            elif self.try_kw("default"):
                default = self.expr()
            elif self.try_kw("auto_increment"):
                auto_inc = True
            elif self.try_kw("unique", "key"):
                pass
            elif self.try_kw("comment"):
                self.advance()  # the comment string
            elif self.at_kw("charset", "collate"):
                is_collate = str(self.cur.value).lower() == "collate"
                self.advance()
                self.try_op("=")
                cname = str(self.advance().value).lower()
                if is_collate:
                    from dataclasses import replace as _replace

                    from tidb_tpu.types import (BIN_COLLATIONS,
                                                CI_COLLATIONS)
                    if cname in CI_COLLATIONS:
                        if not ftype.kind.is_string:
                            raise ParseError(
                                f"COLLATE is not valid for "
                                f"{ftype.kind.value} columns")
                        ftype = _replace(ftype, collation=cname)
                    elif cname not in BIN_COLLATIONS:
                        raise ParseError(f"Unknown collation: '{cname}'")
            else:
                break
        ftype = ftype.with_nullable(nullable)
        return ast.ColumnDef(name, ftype, primary, default, auto_inc)

    def field_type(self) -> FieldType:
        t = self.advance()
        if t.kind == "ident" and str(t.value).lower() == "json":
            return FieldType(TypeKind.JSON, True)
        if t.kind == "kw" and t.value == "set" or \
                t.kind == "ident" and str(t.value).lower() == "enum":
            kind = TypeKind.SET if t.value == "set" else TypeKind.ENUM
            self.expect_op("(")
            elems = []
            while True:
                if not self.at("str"):
                    raise ParseError(
                        f"expected string element near {self._near()}")
                elems.append(self.advance().value)
                if not self.try_op(","):
                    break
            self.expect_op(")")
            return FieldType(kind, True, elems=tuple(elems))
        if t.kind != "kw":
            raise ParseError(f"expected type near {self._near()}")
        kw = t.value
        args: List[int] = []
        if self.try_op("("):
            args.append(self._int_value())
            while self.try_op(","):
                args.append(self._int_value())
            self.expect_op(")")
        unsigned = bool(self.try_kw("unsigned"))
        self.try_kw("signed")
        kind_map = {
            "int": TypeKind.INT, "integer": TypeKind.INT,
            "bigint": TypeKind.BIGINT, "smallint": TypeKind.SMALLINT,
            "tinyint": TypeKind.TINYINT, "float": TypeKind.FLOAT,
            "double": TypeKind.DOUBLE, "decimal": TypeKind.DECIMAL,
            "numeric": TypeKind.DECIMAL, "char": TypeKind.CHAR,
            "varchar": TypeKind.VARCHAR, "text": TypeKind.VARCHAR,
            "date": TypeKind.DATE, "datetime": TypeKind.DATETIME,
            "timestamp": TypeKind.TIMESTAMP, "time": TypeKind.TIME,
        }
        kind = kind_map.get(kw)
        if kind is None:
            raise ParseError(f"unsupported type {kw!r}")
        precision = args[0] if args else (10 if kind is TypeKind.DECIMAL else 0)
        scale = args[1] if len(args) > 1 else 0
        return FieldType(kind, True, precision, scale, unsigned)

    def drop_table(self):
        self.expect_kw("drop")
        if self.try_kw("index"):
            iname = self.ident()
            self.expect_kw("on")
            return ast.DropIndex(iname, self.ident())
        self.expect_kw("table")
        if_exists = False
        if self.try_kw("if"):
            self.expect_kw("exists")
            if_exists = True
        names = [self.ident()]
        while self.try_op(","):
            names.append(self.ident())
        return ast.DropTable(names, if_exists)

    # ---- DML -------------------------------------------------------------
    def insert(self) -> ast.Insert:
        replace = self.advance().value == "replace"
        ignore = bool(self.try_kw("ignore"))
        self.expect_kw("into")
        table = self.ident()
        columns = None
        if self.try_op("("):
            columns = [self.ident()]
            while self.try_op(","):
                columns.append(self.ident())
            self.expect_op(")")
        if self.at_kw("select"):
            return ast.Insert(table, columns,
                              select=self.select_with_setops(),
                              replace=replace, ignore=ignore)
        self.expect_kw("values")
        rows = []
        while True:
            self.expect_op("(")
            row = [self._value()]
            while self.try_op(","):
                row.append(self._value())
            self.expect_op(")")
            rows.append(row)
            if not self.try_op(","):
                break
        return ast.Insert(table, columns, rows, replace=replace, ignore=ignore)

    _PLAIN_LITERALS = ("int", "decimal", "float", "str")

    def _value(self) -> ast.ExprNode:
        """One value of a VALUES row: a bare literal (what a bulk INSERT
        is made of) is taken as it stands, anything else is an
        expression."""
        t = self.cur
        if t.kind in self._PLAIN_LITERALS:
            nxt = self.toks[self.i + 1]
            if nxt.kind == "op" and nxt.value in (",", ")"):
                self.i += 1
                return ast.Literal(t.value, t.kind)
        return self.expr()

    def update(self) -> ast.Update:
        self.expect_kw("update")
        tname = self.ident()
        alias = None
        if self.try_kw("as"):
            alias = self.ident()
        elif self.at("ident"):
            alias = self.advance().value
        self.expect_kw("set")
        assigns = []
        while True:
            col = self.ident()
            # allow qualified t.col
            if self.try_op("."):
                col = self.ident()
            self.expect_op("=")
            assigns.append((col, self.expr()))
            if not self.try_op(","):
                break
        where = self.expr() if self.try_kw("where") else None
        return ast.Update(ast.TableName(tname, alias), assigns, where)

    def delete(self) -> ast.Delete:
        self.expect_kw("delete")
        self.expect_kw("from")
        tname = self.ident()
        alias = None
        if self.at("ident"):
            alias = self.advance().value
        where = self.expr() if self.try_kw("where") else None
        return ast.Delete(ast.TableName(tname, alias), where)

    # ---- misc statements -------------------------------------------------
    def set_stmt(self) -> ast.SetStmt:
        self.expect_kw("set")
        global_scope = bool(self.try_kw("global"))
        self.try_kw("session")
        assigns = []
        while True:
            if self.try_op("@@"):
                name = self._sysvar_name()
            elif self.try_op("@"):
                name = "@" + self.ident()
            else:
                name = self.ident()
            if not self.try_op("="):
                self.expect_op(":=")
            assigns.append((name, self.expr()))
            if not self.try_op(","):
                break
        return ast.SetStmt(assigns, global_scope)

    def _sysvar_name(self) -> str:
        # @@x | @@session.x | @@global.x
        if self.try_kw("session", "global"):
            self.expect_op(".")
            return self.ident()
        name = self.ident()
        if self.try_op("."):
            name = self.ident()
        return name

    def show_stmt(self) -> ast.ShowStmt:
        self.expect_kw("show")
        if self.try_kw("grants"):
            target = None
            if self.try_kw("for"):
                target = self._user_spec()
            return ast.ShowStmt("grants", target=target)
        if self.try_kw("tables"):
            return ast.ShowStmt("tables")
        if self.try_kw("databases"):
            return ast.ShowStmt("databases")
        if self.try_kw("variables"):
            like = None
            if self.try_kw("like"):
                if not self.at("str"):
                    raise ParseError(
                        f"expected string pattern near {self._near()}")
                like = self.advance().value
            return ast.ShowStmt("variables", like=like)
        if self.try_kw("columns"):
            self.expect_kw("from")
            return ast.ShowStmt("columns", target=self.ident())
        if self.at_kw("index", "key") or (
                self.cur.kind == "ident"
                and str(self.cur.value).lower() in ("indexes", "keys")):
            self.advance()
            self.expect_kw("from")
            return ast.ShowStmt("index", target=self.ident())
        if self.try_kw("create"):
            if self._word("view"):
                return ast.ShowStmt("create_view", target=self.ident())
            self.expect_kw("table")
            return ast.ShowStmt("create_table", target=self.ident())
        if self.at("ident") or self.at("kw"):
            word = str(self.cur.value).lower()
            if word == "metrics":
                self.advance()
                return ast.ShowStmt("metrics")
            if word == "slow":
                self.advance()
                self.ident()       # QUERIES
                return ast.ShowStmt("slow_queries")
            if word == "statement":
                self.advance()
                self.ident()       # SUMMARY
                return ast.ShowStmt("statement_summary")
            if word == "processlist":
                self.advance()
                return ast.ShowStmt("processlist")
            if word == "warnings":
                self.advance()
                return ast.ShowStmt("warnings")
            if word == "collation":
                self.advance()
                return ast.ShowStmt("collation")
            if word == "charset":
                self.advance()
                return ast.ShowStmt("charset")
        raise ParseError(f"unsupported SHOW near {self._near()}")

    # ---- expressions -----------------------------------------------------
    def expr(self) -> ast.ExprNode:
        return self.or_expr()

    def or_expr(self) -> ast.ExprNode:
        left = self.xor_expr()
        while self.at_kw("or") or self.at_op("||"):
            self.advance()
            left = ast.BinaryOp("or", left, self.xor_expr())
        return left

    def xor_expr(self) -> ast.ExprNode:
        left = self.and_expr()
        while self.try_kw("xor"):
            left = ast.BinaryOp("xor", left, self.and_expr())
        return left

    def and_expr(self) -> ast.ExprNode:
        left = self.not_expr()
        while self.at_kw("and") or self.at_op("&&"):
            self.advance()
            left = ast.BinaryOp("and", left, self.not_expr())
        return left

    def not_expr(self) -> ast.ExprNode:
        if self.try_kw("not") or self.try_op("!"):
            return ast.UnaryOp("not", self.not_expr())
        return self.predicate()

    _CMP = {"=": "eq", "<>": "ne", "!=": "ne", "<": "lt", "<=": "le",
            ">": "gt", ">=": "ge", "<=>": "nulleq"}

    def predicate(self) -> ast.ExprNode:
        left = self.add_expr()
        while True:
            if self.cur.kind == "op" and self.cur.value in self._CMP:
                op = self._CMP[self.advance().value]
                # comparison with subquery: = (SELECT ...)
                right = self.add_expr()
                left = ast.BinaryOp(op, left, right)
                continue
            negated = False
            save = self.i
            if self.try_kw("not"):
                negated = True
            if self.try_kw("is"):
                neg2 = bool(self.try_kw("not"))
                self.expect_kw("null")
                left = ast.IsNull(left, negated ^ neg2)
                continue
            if self.try_kw("in"):
                self.expect_op("(")
                if self.at_kw("select"):
                    sub = ast.Subquery(self.select_with_setops())
                    self.expect_op(")")
                    left = ast.InExpr(left, None, sub, negated)
                else:
                    items = [self.expr()]
                    while self.try_op(","):
                        items.append(self.expr())
                    self.expect_op(")")
                    left = ast.InExpr(left, items, None, negated)
                continue
            if self.try_kw("between"):
                low = self.add_expr()
                self.expect_kw("and")
                high = self.add_expr()
                left = ast.Between(left, low, high, negated)
                continue
            if self.try_kw("like"):
                left = ast.LikeExpr(left, self.add_expr(), negated)
                continue
            if self.at_kw("regexp", "rlike") or (
                    self.at("ident") and
                    str(self.cur.value).lower() in ("regexp", "rlike")):
                self.advance()
                node = ast.FuncCall("regexp_like",
                                    [left, self.add_expr()])
                left = ast.UnaryOp("not", node) if negated else node
                continue
            if negated:
                self.i = save
            break
        return left

    def add_expr(self) -> ast.ExprNode:
        left = self.mul_expr()
        while self.at_op("+", "-"):
            op = "plus" if self.advance().value == "+" else "minus"
            left = ast.BinaryOp(op, left, self.mul_expr())
        return left

    def mul_expr(self) -> ast.ExprNode:
        left = self.unary_expr()
        while True:
            if self.at_op("*", "/", "%"):
                sym = self.advance().value
                op = {"*": "mul", "/": "div", "%": "mod"}[sym]
            elif self.at_kw("div"):
                self.advance()
                op = "intdiv"
            elif self.at_kw("mod"):
                self.advance()
                op = "mod"
            else:
                break
            left = ast.BinaryOp(op, left, self.unary_expr())
        return left

    def unary_expr(self) -> ast.ExprNode:
        if self.try_op("-"):
            return ast.UnaryOp("minus", self.unary_expr())
        if self.try_op("+"):
            return self.unary_expr()
        e = self.primary()
        # JSON path extraction operators: col->'$.a' / col->>'$.a'
        while self.at_op("->", "->>"):
            op = self.advance().value
            if not self.at("str"):
                raise ParseError(f"expected path string near {self._near()}")
            path = ast.Literal(self.advance().value, "str")
            e = ast.FuncCall("json_extract", [e, path])
            if op == "->>":
                e = ast.FuncCall("json_unquote", [e])
        return e

    def primary(self) -> ast.ExprNode:
        t = self.cur
        if t.kind in ("int", "decimal", "float", "str"):
            self.advance()
            return ast.Literal(t.value, t.kind)
        if t.is_kw("null"):
            self.advance()
            return ast.Literal(None, "null")
        if t.is_kw("true"):
            self.advance()
            return ast.Literal(1, "int")
        if t.is_kw("false"):
            self.advance()
            return ast.Literal(0, "int")
        if self.try_op("@@"):
            return ast.VariableRef(self._sysvar_name(), system=True)
        if self.try_op("@"):
            return ast.VariableRef(self.ident(), system=False)
        if self.try_op("("):
            if self.at_kw("select"):
                s = self.select_with_setops()
                self.expect_op(")")
                return ast.Subquery(s)
            e = self.expr()
            self.expect_op(")")
            return e
        if t.is_kw("exists"):
            self.advance()
            self.expect_op("(")
            s = self.select_with_setops()
            self.expect_op(")")
            return ast.ExistsExpr(ast.Subquery(s))
        if t.is_kw("case"):
            return self.case_expr()
        if t.is_kw("cast"):
            self.advance()
            self.expect_op("(")
            e = self.expr()
            self.expect_kw("as")
            ftype = self.field_type()
            self.expect_op(")")
            return ast.CastExpr(e, ftype)
        if t.is_kw("interval"):
            # INTERVAL(N, N1, ...) the comparison FUNCTION vs
            # INTERVAL expr UNIT the temporal literal: a comma at paren
            # depth 1 decides (MySQL's own disambiguation rule)
            if self.toks[self.i + 1].kind == "op" and \
                    self.toks[self.i + 1].value == "(":
                depth = 0
                is_fn = False
                for k in range(self.i + 1, len(self.toks)):
                    tk = self.toks[k]
                    if tk.kind != "op":
                        continue
                    if tk.value == "(":
                        depth += 1
                    elif tk.value == ")":
                        depth -= 1
                        if depth == 0:
                            break
                    elif tk.value == "," and depth == 1:
                        is_fn = True
                        break
                if is_fn:
                    self.advance()
                    return self._call("interval")
            self.advance()
            v = self.add_expr()
            unit = self.ident().lower()
            return ast.IntervalExpr(v, unit)
        if (t.kind == "ident" and str(t.value).lower() == "extract") and \
                self.toks[self.i + 1].kind == "op" and \
                self.toks[self.i + 1].value == "(":
            # EXTRACT(unit FROM expr) → the matching part function
            self.advance()
            self.expect_op("(")
            unit = str(self.ident()).lower()
            self.expect_kw("from")
            e = self.expr()
            self.expect_op(")")
            fn = {"year": "year", "month": "month", "day": "dayofmonth",
                  "hour": "hour", "minute": "minute", "second": "second",
                  "microsecond": "microsecond", "week": "week",
                  "quarter": "quarter"}.get(unit)
            if fn is None:
                raise ParseError(f"unsupported EXTRACT unit: {unit}")
            return ast.FuncCall(fn, [e])
        if t.is_kw("if"):  # IF(c, a, b) function form
            self.advance()
            self.expect_op("(")
            args = [self.expr()]
            while self.try_op(","):
                args.append(self.expr())
            self.expect_op(")")
            return ast.FuncCall("if", args)
        if t.is_kw("date", "time", "timestamp") and \
                self.toks[self.i + 1].kind == "str":
            # temporal literal: DATE '1994-01-01'
            kw = self.advance().value
            s = self.advance().value
            return ast.FuncCall(f"{kw}_literal", [ast.Literal(s, "str")])
        if t.is_kw("replace", "left", "right", "database",
                   "truncate", "mod", "user", "data", "insert", "char",
                   "format", "set", "charset", "collate",
                   "values", "default", "analyze"):
            # keywords that double as function names
            if self.toks[self.i + 1].kind == "op" and \
                    self.toks[self.i + 1].value == "(":
                name = self.advance().value
                return self._call(name)
        if t.kind == "ident" or (t.kind == "kw" and t.value in (
                "date", "time", "timestamp", "values", "if",
                "add", "to", "column", "rename", "partition")):
            name = self.advance().value
            if self.at_op("("):
                return self._call(name.lower())
            parts = [name]
            while self.try_op("."):
                if self.at_op("*"):
                    self.advance()
                    return ast.Star(table=parts[-1])
                parts.append(self.ident())
            return ast.Name(tuple(parts))
        raise ParseError(f"unexpected token near {self._near()}")

    def _call(self, name: str) -> ast.ExprNode:
        self.expect_op("(")
        if self.try_op("*"):
            self.expect_op(")")
            return self._maybe_window(ast.FuncCall(name, [ast.Star()]))
        if self.try_op(")"):
            return self._maybe_window(ast.FuncCall(name, []))
        distinct = bool(self.try_kw("distinct"))
        args = [self.expr()]
        while self.try_op(","):
            args.append(self.expr())
        self.expect_op(")")
        return self._maybe_window(ast.FuncCall(name, args, distinct))

    def _maybe_window(self, call: ast.FuncCall) -> ast.FuncCall:
        """OVER (PARTITION BY … ORDER BY …) window attachment."""
        if not self.try_kw("over"):
            return call
        self.expect_op("(")
        partition: list = []
        order: list = []
        if self.try_kw("partition"):
            self.expect_kw("by")
            partition.append(self.expr())
            while self.try_op(","):
                partition.append(self.expr())
        if self.try_kw("order"):
            self.expect_kw("by")
            while True:
                e = self.expr()
                desc = False
                if self.try_kw("desc"):
                    desc = True
                elif self.try_kw("asc"):
                    pass
                order.append((e, desc))
                if not self.try_op(","):
                    break
        frame = None
        if self.at("ident") and str(self.cur.value).lower() in ("rows",
                                                                "range"):
            unit = self.advance().value.lower()
            if self.try_kw("between"):
                start = self._frame_bound()
                self.expect_kw("and")
                end = self._frame_bound()
            else:
                # shorthand: only UNBOUNDED PRECEDING / n PRECEDING /
                # CURRENT ROW are legal starts (MySQL frame grammar)
                start = self._frame_bound()
                if start not in (("unbounded", "preceding"),
                                 ("current", 0)) and \
                        not (isinstance(start[0], int)
                             and start[1] == "preceding"):
                    raise ParseError(
                        "frame shorthand requires a PRECEDING or "
                        "CURRENT ROW bound")
                end = ("current", 0)
            frame = (unit, start, end)
        self.expect_op(")")
        call.window = ast.WindowSpec(partition, order, frame)
        return call

    def _frame_bound(self):
        """UNBOUNDED PRECEDING|FOLLOWING | CURRENT ROW | n PRECEDING|
        FOLLOWING → ('unbounded'|'current'|n, direction)."""
        if self.at("ident") and str(self.cur.value).lower() == "unbounded":
            self.advance()
            d = str(self.advance().value).lower()
            if d not in ("preceding", "following"):
                raise ParseError(f"expected PRECEDING/FOLLOWING near "
                                 f"{self._near()}")
            return ("unbounded", d)
        if self.at("ident") and str(self.cur.value).lower() == "current":
            self.advance()
            if not (self.at("ident") and
                    str(self.cur.value).lower() == "row"):
                raise ParseError(f"expected ROW near {self._near()}")
            self.advance()
            return ("current", 0)
        if self.at("int"):
            n = self.advance().value
            d = str(self.advance().value).lower()
            if d not in ("preceding", "following"):
                raise ParseError(f"expected PRECEDING/FOLLOWING near "
                                 f"{self._near()}")
            return (int(n), d)
        raise ParseError(f"expected frame bound near {self._near()}")

    def case_expr(self) -> ast.CaseExpr:
        self.expect_kw("case")
        operand = None
        if not self.at_kw("when"):
            operand = self.expr()
        whens = []
        while self.try_kw("when"):
            c = self.expr()
            self.expect_kw("then")
            r = self.expr()
            whens.append((c, r))
        else_ = None
        if self.try_kw("else"):
            else_ = self.expr()
        self.expect_kw("end")
        return ast.CaseExpr(operand, whens, else_)
