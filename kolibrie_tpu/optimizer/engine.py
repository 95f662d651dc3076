"""ID-space execution engine: interprets physical plans over columnar binding
tables; strings are decoded only at the final projection.

Parity: ``streamertail_optimizer/execution/engine.rs`` —
``execute_with_ids`` (:54), index/table scans (:558,:1240), star join (:635),
hash joins (:758,:814), NLJ (:862), merge join (:1018), quoted-triple scan
resolution (:1159), ``Condition::evaluate_with_ids`` (types.rs:110-185), Bind
with CONCAT/UDFs and the RDF-star builtins TRIPLE/SUBJECT/PREDICATE/OBJECT/
isTRIPLE (:144-260).

Every operator returns a whole binding table (dict var -> u32 column), so
execution is a dataflow of vectorized kernels instead of a tuple-at-a-time
Volcano loop — the form XLA can run on device.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from kolibrie_tpu.core.dictionary import QUOTED_BIT
from kolibrie_tpu.optimizer import plan as P
from kolibrie_tpu.ops.join import UNBOUND, BindingTable, equi_join_tables, table_len
from kolibrie_tpu.ops.unique import unique_table
from kolibrie_tpu.query.ast import (
    ArithOp,
    Comparison,
    FuncExpr,
    FunctionCall,
    IriRef,
    LogicalAnd,
    LogicalNot,
    LogicalOr,
    NumberLit,
    PatternTerm,
    PatternTriple,
    QuotedPattern,
    StringLit,
    Var,
)

def resolve_pattern(db, pattern: PatternTriple) -> PatternTriple:
    """Resolve term strings to dictionary IDs (kind 'term' -> kind 'id').

    Unknown constants resolve to id None — a scan that can never match.
    Quoted patterns with all-constant parts resolve to their quoted-triple ID;
    with variables they stay structural for the scan resolver.
    """

    def rt(t: PatternTerm) -> PatternTerm:
        if t.kind == "var":
            return t
        if t.kind == "id":
            return t
        if t.kind == "quoted":
            s, p, o = (rt(x) for x in t.value)  # type: ignore[misc]
            if all(x.kind == "id" for x in (s, p, o)):
                if any(x.value is None for x in (s, p, o)):
                    return PatternTerm("id", None)
                qid = db.quoted.lookup(s.value, p.value, o.value)
                return PatternTerm("id", qid)
            return PatternTerm("quoted", (s, p, o))
        expanded = db.expand_term(t.value)  # type: ignore[arg-type]
        return PatternTerm("id", db.dictionary.lookup(expanded))

    return PatternTriple(rt(pattern.subject), rt(pattern.predicate), rt(pattern.object))


def strip_literal(s: Optional[str]) -> Optional[str]:
    """Lexical form of a quoted literal (escaped-quote aware), raw term
    otherwise — THE string-function stripping rule, shared by the host
    engine and the device string-predicate masks."""
    if s is None:
        return None
    if s.startswith('"'):
        end = s.find('"', 1)
        while end != -1 and s[end - 1] == "\\":
            end = s.find('"', end + 1)
        if end > 0:
            return s[1:end]
    return s


class ExecutionEngine:
    def __init__(self, db, subquery_eval: Optional[Callable] = None):
        self.db = db
        self.subquery_eval = subquery_eval  # callback: SubQuery -> BindingTable
        self._qt_cache = None

    # ------------------------------------------------------------- dispatch

    def execute_with_ids(self, op) -> BindingTable:
        if isinstance(op, (P.PhysIndexScan, P.PhysTableScan)):
            return self._scan(op.pattern)
        if isinstance(op, (P.PhysHashJoin, P.PhysMergeJoin, P.PhysParallelJoin)):
            left = self.execute_with_ids(op.left)
            right = self.execute_with_ids(op.right)
            return equi_join_tables(left, right)
        if isinstance(op, P.PhysNestedLoopJoin):
            left = self.execute_with_ids(op.left)
            right = self.execute_with_ids(op.right)
            return equi_join_tables(left, right)
        if isinstance(op, P.PhysStarJoin):
            out: Optional[BindingTable] = None
            for scan in op.scans:
                t = self.execute_with_ids(scan)
                out = t if out is None else equi_join_tables(out, t)
            return out if out is not None else {}
        if isinstance(op, P.WcojNode):
            # host fallback: binary joins give the same bindings (set
            # semantics); the worst-case-optimal evaluation is the DEVICE
            # lowering's concern.  Scans join in a CONNECTED order — the
            # next one shares a variable with what is already joined
            # whenever any does — because textual order can put two
            # disjoint patterns first (LUBM Q2) and materialize their
            # cross product.
            pending = [self.execute_with_ids(scan) for scan in op.scans]
            wout: Optional[BindingTable] = None
            while pending:
                k = 0
                if wout is not None:
                    k = next(
                        (i for i, t in enumerate(pending)
                         if not wout.keys().isdisjoint(t)),
                        0,
                    )
                t = pending.pop(k)
                wout = t if wout is None else equi_join_tables(wout, t)
            return wout if wout is not None else {}
        if isinstance(op, P.PhysFilter):
            table = self.execute_with_ids(op.child)
            mask = self.eval_filter(op.expr, table)
            return {k: v[mask] for k, v in table.items()}
        if isinstance(op, P.PhysBind):
            table = self.execute_with_ids(op.child)
            col = self.eval_arith_to_ids(op.bind.expr, table)
            out = dict(table)
            out[op.bind.var] = col
            return out
        if isinstance(op, P.PhysValues):
            return self._values_table(op.values)
        if isinstance(op, P.PhysSubquery):
            if self.subquery_eval is None:
                raise RuntimeError("subquery evaluation requires executor context")
            return self.subquery_eval(op.subquery)
        if isinstance(op, P.PhysProjection):
            table = self.execute_with_ids(op.child)
            return {v: table[v] for v in op.variables if v in table}
        raise TypeError(f"unknown physical operator {op!r}")

    # ----------------------------------------------------------------- scans

    def _quoted_table(self) -> Dict[str, np.ndarray]:
        """Materialized quoted-triple store as columns (qid, s, p, o)."""
        if self._qt_cache is None or self._qt_cache[0] != len(self.db.quoted):
            n = len(self.db.quoted)
            qid = np.empty(n, dtype=np.uint32)
            qs = np.empty(n, dtype=np.uint32)
            qp = np.empty(n, dtype=np.uint32)
            qo = np.empty(n, dtype=np.uint32)
            for i, (q, (s, p, o)) in enumerate(self.db.quoted.items()):
                qid[i], qs[i], qp[i], qo[i] = q, s, p, o
            self._qt_cache = (n, qid, qs, qp, qo)
        return {
            "qid": self._qt_cache[1],
            "s": self._qt_cache[2],
            "p": self._qt_cache[3],
            "o": self._qt_cache[4],
        }

    def _scan(self, pattern: PatternTriple) -> BindingTable:
        """Triple-pattern scan via the sorted orders; handles repeated
        variables and quoted-pattern positions."""
        terms = [pattern.subject, pattern.predicate, pattern.object]
        # empty if any constant is unknown
        for t in terms:
            if t.kind == "id" and t.value is None:
                return self._empty_for(pattern)
        # quoted positions with variables become internal join columns
        consts = [t.value if t.kind == "id" else None for t in terms]
        s_col, p_col, o_col = self.db.store.match(
            s=consts[0], p=consts[1], o=consts[2]
        )
        cols = [s_col, p_col, o_col]
        out: BindingTable = {}
        mask: Optional[np.ndarray] = None
        for t, col in zip(terms, cols):
            if t.kind == "var":
                name = t.value
                if name in out:  # repeated variable: rows must agree
                    m = out[name] == col
                    mask = m if mask is None else (mask & m)
                else:
                    out[name] = col
        if mask is not None:
            out = {k: v[mask] for k, v in out.items()}
            cols = [c[mask] if mask is not None else c for c in cols]
        if not out and not any(t.kind == "quoted" for t in terms):
            # fully-constant pattern: presence row so the match count survives
            out["__exists"] = np.zeros(min(len(cols[0]), 1), dtype=np.uint32)
        # quoted-pattern positions: join against the quoted-triple table
        for pos, t in enumerate(terms):
            if t.kind != "quoted":
                continue
            out = self._join_quoted(out, cols[pos] if mask is None else cols[pos], t)
            if table_len(out) == 0:
                return out
        return out

    def _join_quoted(
        self, table: BindingTable, pos_col: np.ndarray, qterm: PatternTerm
    ) -> BindingTable:
        """Join scan rows whose position held a quoted-triple ID against the
        quoted store, binding inner variables (engine.rs:1159 parity)."""
        qt = self._quoted_table()
        inner_s, inner_p, inner_o = qterm.value  # type: ignore[misc]
        keep = (pos_col & QUOTED_BIT).astype(bool)
        sub = {k: v[keep] for k, v in table.items()}
        pos_ids = pos_col[keep]
        qtab: BindingTable = {"__qid": qt["qid"]}
        m = np.ones(len(qt["qid"]), dtype=bool)
        for part, col in (("s", inner_s), ("p", inner_p), ("o", inner_o)):
            if col.kind == "id":
                m &= qt[part] == col.value
        inner_seen: Dict[str, str] = {}
        for part, col in (("s", inner_s), ("p", inner_p), ("o", inner_o)):
            if col.kind == "var":
                if col.value in inner_seen:
                    # repeated inner variable (<< ?x p ?x >>): rows must agree
                    m &= qt[part] == qt[inner_seen[col.value]]
                else:
                    inner_seen[col.value] = part
                    qtab[col.value] = qt[part]
            elif col.kind == "quoted":
                raise NotImplementedError(
                    "doubly-nested quoted variable patterns in scans"
                )
        qtab = {k: v[m] for k, v in qtab.items()}
        sub["__qid"] = pos_ids
        joined = equi_join_tables(sub, qtab)
        joined.pop("__qid", None)
        return joined

    def _empty_for(self, pattern: PatternTriple) -> BindingTable:
        out: BindingTable = {}
        for v in pattern.variables():
            out[v] = np.empty(0, dtype=np.uint32)
        return out

    def _values_table(self, values) -> BindingTable:
        rows = values.rows
        out: BindingTable = {}
        n = len(rows)
        for j, var in enumerate(values.variables):
            col = np.empty(n, dtype=np.uint32)
            for i, row in enumerate(rows):
                term = row[j] if j < len(row) else None
                if term is None:
                    col[i] = UNBOUND
                else:
                    expanded = self.db.expand_term(term)
                    col[i] = self.db.dictionary.encode(expanded)
            out[var] = col
        return out

    # -------------------------------------------------------------- filters

    def eval_filter(self, expr, table: BindingTable) -> np.ndarray:
        n = table_len(table)
        if isinstance(expr, LogicalAnd):
            return self.eval_filter(expr.left, table) & self.eval_filter(
                expr.right, table
            )
        if isinstance(expr, LogicalOr):
            return self.eval_filter(expr.left, table) | self.eval_filter(
                expr.right, table
            )
        if isinstance(expr, LogicalNot):
            return ~self.eval_filter(expr.inner, table)
        if isinstance(expr, Comparison):
            return self._eval_comparison(expr, table)
        if isinstance(expr, (FunctionCall, FuncExpr)):
            return self._eval_bool_function(expr, table)
        raise TypeError(f"unknown filter expression {expr!r}")

    def _eval_comparison(self, cmp: Comparison, table: BindingTable) -> np.ndarray:
        n = table_len(table)
        lnum = self._try_numeric(cmp.left, table)
        rnum = self._try_numeric(cmp.right, table)
        if lnum is not None and rnum is not None:
            valid = ~(np.isnan(lnum) | np.isnan(rnum))
            if cmp.op == "=":
                res = lnum == rnum
            elif cmp.op == "!=":
                res = lnum != rnum
            elif cmp.op == "<":
                res = lnum < rnum
            elif cmp.op == "<=":
                res = lnum <= rnum
            elif cmp.op == ">":
                res = lnum > rnum
            else:
                res = lnum >= rnum
            if cmp.op in ("=", "!=") and (np.isnan(lnum).any() or np.isnan(rnum).any()):
                # fall back to term identity for non-numeric rows
                lid = self._try_ids(cmp.left, table)
                rid = self._try_ids(cmp.right, table)
                if lid is not None and rid is not None:
                    id_res = (lid == rid) if cmp.op == "=" else (lid != rid)
                    return np.where(valid, res, id_res)
            return res & valid
        # identity / string comparison
        lid = self._try_ids(cmp.left, table)
        rid = self._try_ids(cmp.right, table)
        if lid is not None and rid is not None:
            if cmp.op == "=":
                return lid == rid
            if cmp.op == "!=":
                return lid != rid
        # compare on the stripped lexical forms so the quote character never
        # participates in the ordering
        lstr = [self._strip_literal(x) for x in self._eval_strings(cmp.left, table)]
        rstr = [self._strip_literal(x) for x in self._eval_strings(cmp.right, table)]
        ops = {
            "=": lambda a, b: a == b,
            "!=": lambda a, b: a != b,
            "<": lambda a, b: a < b,
            "<=": lambda a, b: a <= b,
            ">": lambda a, b: a > b,
            ">=": lambda a, b: a >= b,
        }
        f = ops[cmp.op]
        return np.fromiter(
            (
                a is not None and b is not None and f(a, b)
                for a, b in zip(lstr, rstr)
            ),
            dtype=bool,
            count=n,
        )

    def _try_numeric(self, expr, table: BindingTable) -> Optional[np.ndarray]:
        """Evaluate to an f64 column, or None if inherently non-numeric."""
        n = table_len(table)
        if isinstance(expr, NumberLit):
            return np.full(n, expr.value)
        if isinstance(expr, Var):
            col = table.get(expr.name)
            if col is None:
                return None
            return self.db.numeric_values()[np.minimum(col, len(self.db.numeric_values()) - 1)]
        if isinstance(expr, ArithOp):
            l = self._try_numeric(expr.left, table)
            r = self._try_numeric(expr.right, table)
            if l is None or r is None:
                return None
            if expr.op == "+":
                return l + r
            if expr.op == "-":
                return l - r
            if expr.op == "*":
                return l * r
            with np.errstate(divide="ignore", invalid="ignore"):
                return l / r
        if isinstance(expr, StringLit):
            try:
                v = float(expr.value.strip('"').split('"')[0])
                return np.full(n, v)
            except ValueError:
                return None
        if isinstance(expr, FuncExpr):
            if expr.name == "ABS":
                inner = self._try_numeric(expr.args[0], table)
                return None if inner is None else np.abs(inner)
            if expr.name == "STRLEN":
                s = self._eval_strings(expr.args[0], table)
                return np.array([len(x or "") for x in s], dtype=np.float64)
        return None

    def _try_ids(self, expr, table: BindingTable) -> Optional[np.ndarray]:
        n = table_len(table)
        if isinstance(expr, Var):
            return table.get(expr.name)
        if isinstance(expr, IriRef):
            tid = self.db.dictionary.lookup(self.db.expand_term(expr.iri))
            return np.full(n, 0xFFFFFFFF if tid is None else tid, dtype=np.uint32)
        if isinstance(expr, StringLit):
            tid = self.db.dictionary.lookup(expr.value)
            return np.full(n, 0xFFFFFFFF if tid is None else tid, dtype=np.uint32)
        if isinstance(expr, QuotedPattern):
            ids = []
            for part in (expr.subject, expr.predicate, expr.object):
                sub = self._try_ids(part, table)
                if sub is None or len(np.unique(sub)) > 1:
                    return None  # per-row quoted construction handled in TRIPLE()
                ids.append(int(sub[0]) if n else 0)
            qid = self.db.quoted.lookup(*ids) if n else None
            return np.full(n, 0xFFFFFFFF if qid is None else qid, dtype=np.uint32)
        return None

    def _eval_strings(self, expr, table: BindingTable) -> List[Optional[str]]:
        n = table_len(table)
        if isinstance(expr, Var):
            col = table.get(expr.name)
            if col is None:
                return [None] * n
            dec = self.db.decode_term
            return [dec(int(i)) for i in col]
        if isinstance(expr, StringLit):
            lex = expr.value
            if lex.startswith('"'):
                lex_plain = lex[1:].split('"')[0]
            else:
                lex_plain = lex
            return [lex_plain] * n
        if isinstance(expr, IriRef):
            return [self.db.expand_term(expr.iri)] * n
        if isinstance(expr, NumberLit):
            v = expr.value
            s = str(int(v)) if v == int(v) else str(v)
            return [s] * n
        if isinstance(expr, FuncExpr):
            return self._eval_string_function(expr, table)
        if isinstance(expr, ArithOp):
            num = self._try_numeric(expr, table)
            if num is not None:
                return [
                    (str(int(v)) if v == int(v) else str(v)) if not np.isnan(v) else None
                    for v in num
                ]
        return [None] * n

    def _strip_literal(self, s: Optional[str]) -> Optional[str]:
        return strip_literal(s)

    def _eval_string_function(self, expr: FuncExpr, table: BindingTable) -> List[Optional[str]]:
        name = expr.name
        n = table_len(table)
        if name == "CONCAT":
            parts = [self._eval_strings(a, table) for a in expr.args]
            parts = [[self._strip_literal(x) for x in p] for p in parts]
            return [
                "".join(x or "" for x in row) for row in zip(*parts)
            ] if parts else [""] * n
        if name in ("STR",):
            return [self._strip_literal(x) for x in self._eval_strings(expr.args[0], table)]
        if name == "UCASE":
            return [
                None if x is None else self._strip_literal(x).upper()
                for x in self._eval_strings(expr.args[0], table)
            ]
        if name == "LCASE":
            return [
                None if x is None else self._strip_literal(x).lower()
                for x in self._eval_strings(expr.args[0], table)
            ]
        if name in ("SUBJECT", "PREDICATE", "OBJECT"):
            col = self._try_ids(expr.args[0], table)
            out: List[Optional[str]] = []
            idx = {"SUBJECT": 0, "PREDICATE": 1, "OBJECT": 2}[name]
            for qid in col:
                inner = self.db.quoted.get(int(qid))
                out.append(None if inner is None else self.db.decode_term(inner[idx]))
            return out
        if name in self.db.udfs:
            fn = self.db.udfs[name]
            arg_strs = [
                [self._strip_literal(x) for x in self._eval_strings(a, table)]
                for a in expr.args
            ]
            return [fn(*row) for row in zip(*arg_strs)] if arg_strs else [fn()] * n
        raise ValueError(f"unknown function {name}")

    def _eval_bool_function(self, expr, table: BindingTable) -> np.ndarray:
        name = expr.name
        args = expr.args
        n = table_len(table)
        if name == "BOUND":
            col = self._try_ids(args[0], table)
            if col is None:
                return np.zeros(n, dtype=bool)
            return col != UNBOUND
        if name == "ISTRIPLE":
            col = self._try_ids(args[0], table)
            if col is None:
                return np.zeros(n, dtype=bool)
            return (col & QUOTED_BIT).astype(bool)
        if name == "REGEX":
            import re as _re

            strs = self._eval_strings(args[0], table)
            pat_l = self._eval_strings(args[1], table)
            pat = self._strip_literal(pat_l[0]) if pat_l else ""
            rx = _re.compile(pat or "")
            return np.array(
                [bool(rx.search(self._strip_literal(s) or "")) for s in strs],
                dtype=bool,
            )
        if name == "CONTAINS":
            strs = self._eval_strings(args[0], table)
            sub_l = self._eval_strings(args[1], table)
            return np.array(
                [
                    (self._strip_literal(s) or "").find(self._strip_literal(b) or "") >= 0
                    for s, b in zip(strs, sub_l)
                ],
                dtype=bool,
            )
        if name in ("STRSTARTS", "STRENDS"):
            strs = self._eval_strings(args[0], table)
            sub_l = self._eval_strings(args[1], table)
            if name == "STRSTARTS":
                return np.array(
                    [
                        (self._strip_literal(s) or "").startswith(self._strip_literal(b) or "")
                        for s, b in zip(strs, sub_l)
                    ],
                    dtype=bool,
                )
            return np.array(
                [
                    (self._strip_literal(s) or "").endswith(self._strip_literal(b) or "")
                    for s, b in zip(strs, sub_l)
                ],
                dtype=bool,
            )
        if name in self.db.udfs:
            fn = self.db.udfs[name]
            arg_strs = [
                [self._strip_literal(x) for x in self._eval_strings(a, table)]
                for a in args
            ]
            return np.array(
                [bool(fn(*row)) for row in zip(*arg_strs)] if arg_strs else [bool(fn())] * n,
                dtype=bool,
            )
        raise ValueError(f"unknown boolean function {name}")

    # ----------------------------------------------------------------- BIND

    def eval_arith_to_ids(self, expr, table: BindingTable) -> np.ndarray:
        """Evaluate an expression and encode results as dictionary IDs
        (numbers become plain literals; TRIPLE() builds quoted-triple IDs)."""
        n = table_len(table)
        if isinstance(expr, FuncExpr) and expr.name == "TRIPLE":
            s_ids = self._coerce_ids(expr.args[0], table)
            p_ids = self._coerce_ids(expr.args[1], table)
            o_ids = self._coerce_ids(expr.args[2], table)
            out = np.empty(n, dtype=np.uint32)
            for i in range(n):
                out[i] = self.db.quoted.intern(
                    int(s_ids[i]), int(p_ids[i]), int(o_ids[i])
                )
            return out
        if isinstance(expr, Var):
            col = table.get(expr.name)
            return col if col is not None else np.zeros(n, dtype=np.uint32)
        num = self._try_numeric(expr, table)
        if num is not None and not isinstance(expr, (StringLit, IriRef)):
            out = np.empty(n, dtype=np.uint32)
            enc = self.db.dictionary.encode
            for i, v in enumerate(num):
                if np.isnan(v):
                    out[i] = UNBOUND
                else:
                    sv = str(int(v)) if v == int(v) else f"{v:g}"
                    out[i] = enc(f'"{sv}"')
            return out
        strs = self._eval_strings(expr, table)
        out = np.empty(n, dtype=np.uint32)
        enc = self.db.dictionary.encode
        for i, sv in enumerate(strs):
            out[i] = UNBOUND if sv is None else enc(f'"{sv}"')
        return out

    def _coerce_ids(self, expr, table: BindingTable) -> np.ndarray:
        ids = self._try_ids(expr, table)
        if ids is not None:
            return ids
        return self.eval_arith_to_ids(expr, table)
