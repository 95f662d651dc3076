"""The plain reference: a small SPARQL evaluator in numpy.

It imports nothing of the program and takes nothing the program has made: it
reads the generator's id columns and term table and answers the subset of
SPARQL the benchmark's templates use -- ``PREFIX``, ``SELECT`` of variables
and ``(COUNT(?x) AS ?n)``, a basic graph pattern, a nested ``{ SELECT ... }``
of variables, ``FILTER(?v <op> term)`` with ``= != < <= > >=`` and ``GROUP
BY`` -- by sort-merge joins of whole columns.  Anything else raises, so a template outside the subset cannot pass
unnoticed.  Rows come back as the server renders them: an IRI without its
angle brackets, a plain literal without its quotes, a count as a decimal
string.  Numeric comparison reads a plain literal's text as a number, as the
program (and upstream Kolibrie) does for ``"50000"``.
"""

import re

import numpy as np

_TOKEN = re.compile(
    r"""\s*(?:(<[^<>\s]*>)            # IRI
        |("(?:[^"\\]|\\.)*")          # string
        |(\?[A-Za-z_]\w*)             # variable
        |([A-Za-z_][\w-]*:[\w.-]*\w|[A-Za-z_][\w-]*:)  # prefixed name / prefix
        |([+-]?\d+(?:\.\d+)?)         # number
        |(<=|>=|!=|[{}().=<>*])       # punctuation
        |([A-Za-z_]\w*))""",          # keyword
    re.X,
)


def _tokens(text):
    pos, out = 0, []
    text = text.rstrip()
    while pos < len(text):
        m = _TOKEN.match(text, pos)
        if not m:
            raise ValueError(f"reference: cannot read SPARQL at {text[pos:pos+40]!r}")
        pos = m.end()
        kind = m.lastindex
        out.append((("iri", "str", "var", "pname", "num", "punct", "word")[kind - 1],
                    m.group(kind)))
    return out


class _Parser:
    def __init__(self, text):
        self.toks, self.i, self.prefixes = _tokens(text), 0, {}

    def peek(self):
        return self.toks[self.i] if self.i < len(self.toks) else (None, None)

    def take(self, kind=None, value=None):
        k, v = self.peek()
        if (kind and k != kind) or (value and v.upper() != value):
            raise ValueError(f"reference: expected {value or kind}, found {v!r}")
        self.i += 1
        return v

    def term(self):
        """A variable as ``("var", name)``, a constant as ``("const", nt)``."""
        k, v = self.peek()
        self.i += 1
        if k == "var":
            return ("var", v)
        if k == "iri" or k == "str":
            return ("const", v)
        if k == "num":
            return ("num", v)
        if k == "pname":
            pre, _, local = v.partition(":")
            return ("const", f"<{self.prefixes[pre]}{local}>")
        raise ValueError(f"reference: unexpected {v!r}")

    def parse(self):
        while self.peek()[1] and self.peek()[1].upper() == "PREFIX":
            self.take()
            pre = self.take("pname").rstrip(":")
            self.prefixes[pre] = self.take("iri")[1:-1]
        query = self.select_query()
        if self.peek()[1] is not None:
            raise ValueError(f"reference: unsupported clause at {self.peek()[1]!r}")
        return query

    def select_query(self):
        """``(select, patterns, filters, nested, group)``; ``nested`` holds
        the sub-SELECTs of the group, each such a tuple itself."""
        self.take("word", "SELECT")
        select = []  # ("var", name) | ("count", var, alias)
        while self.peek()[1].upper() != "WHERE":
            if self.peek() == ("punct", "("):
                self.take()
                self.take("word", "COUNT")
                self.take("punct", "(")
                counted = self.take("var")
                self.take("punct", ")")
                self.take("word", "AS")
                select.append(("count", counted, self.take("var")))
                self.take("punct", ")")
            else:
                select.append(("var", self.take("var")))
        self.take("word", "WHERE")
        self.take("punct", "{")
        patterns, filters, nested = [], [], []
        while self.peek() != ("punct", "}"):
            if self.peek() == ("punct", "{"):
                self.take()
                nested.append(self.select_query())
                self.take("punct", "}")
            elif self.peek()[0] == "word":
                self.take("word", "FILTER")
                self.take("punct", "(")
                left, op, right = self.term(), self.take("punct"), self.term()
                self.take("punct", ")")
                filters.append((left, op, right))
            else:
                patterns.append((self.term(), self.term(), self.term()))
            if self.peek() == ("punct", "."):
                self.take()
        self.take("punct", "}")
        group = []
        if self.peek()[1] is not None and self.peek()[1].upper() == "GROUP":
            self.take("word", "GROUP")
            self.take("word", "BY")
            while self.peek()[0] == "var":
                group.append(self.take("var"))
        return select, patterns, filters, nested, group


def render(term: str) -> str:
    """An N-Triples term as the server's JSON rows carry it."""
    if term.startswith("<"):
        return term[1:-1]
    if term.startswith('"'):
        return term[1:term.rindex('"')]
    return term


class Reference:
    """Answers queries over one generated data set."""

    def __init__(self, terms, s, p, o):
        self.terms = terms
        self.ids = {t: i for i, t in enumerate(terms)}
        self.n = len(terms)
        self.s, self.p, self.o = (np.asarray(c, np.int64) for c in (s, p, o))
        order = np.argsort(self.p, kind="stable")
        ps = self.p[order]
        self._by_p = {}
        starts = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1]])
        for a, b in zip(starts, np.r_[starts[1:], len(ps)]):
            self._by_p[int(ps[a])] = order[a:b]
        self._rendered = None
        self._numeric = None

    # -- one triple pattern -> columns of its variables
    def _scan(self, pattern):
        consts = [self.ids.get(t[1], -1) if t[0] == "const" else None
                  for t in pattern]
        if consts[1] is not None:
            rows = self._by_p.get(consts[1], np.empty(0, np.int64))
        else:
            rows = np.arange(len(self.s))
        cols = [self.s[rows], self.p[rows], self.o[rows]]
        keep = np.ones(len(rows), bool)
        for c, col in zip((consts[0], consts[2]), (cols[0], cols[2])):
            if c is not None:
                keep &= col == c
        out = {}
        for t, col in zip(pattern, cols):
            if t[0] != "var":
                continue
            if t[1] in out:
                keep &= out[t[1]] == col
            else:
                out[t[1]] = col
        return {v: col[keep] for v, col in out.items()}

    def _key(self, table, names):
        key = np.zeros(len(next(iter(table.values()))), np.int64)
        for v in names:
            key = key * self.n + table[v]
        return key

    def _join(self, left, right):
        shared = sorted(set(left) & set(right))
        if len(shared) > 3:
            raise ValueError("reference: join key too wide")
        nl = len(next(iter(left.values())))
        if shared:
            rk = self._key(right, shared)
            order = np.argsort(rk, kind="stable")
            rk = rk[order]
            lk = self._key(left, shared)
            lo = np.searchsorted(rk, lk, "left")
            counts = np.searchsorted(rk, lk, "right") - lo
        else:
            nr = len(next(iter(right.values())))
            order = np.arange(nr)
            lo, counts = np.zeros(nl, np.int64), np.full(nl, nr, np.int64)
        total = int(counts.sum())
        li = np.repeat(np.arange(nl), counts)
        ri = order[np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
                   + np.repeat(lo, counts)]
        out = {v: col[li] for v, col in left.items()}
        out.update({v: col[ri] for v, col in right.items() if v not in out})
        return out

    def _compare(self, table, flt):
        (lk, lv), op, (rk, rv) = flt
        if lk != "var":
            raise ValueError("reference: FILTER needs a variable on the left")
        col = table[lv]
        if op in ("=", "!=") and rk == "const":
            same = col == self.ids.get(rv, -1)
            return same if op == "=" else ~same
        if self._numeric is None:
            def num(t):
                try:
                    return float(render(t))
                except ValueError:
                    return np.nan
            self._numeric = np.array([num(t) for t in self.terms])
        a = self._numeric[col]
        b = self._numeric[table[rv]] if rk == "var" else float(render(rv))
        with np.errstate(invalid="ignore"):
            return {"=": a == b, "!=": a != b, "<": a < b, "<=": a <= b,
                    ">": a > b, ">=": a >= b}[op]

    def _solutions(self, query):
        """Id columns of a (sub-)SELECT's solutions, one per selected name."""
        select, patterns, filters, nested, group = query
        # a sub-SELECT joins on the variables it projects; the others are
        # its own
        tables = [self._scan(pt) for pt in patterns]
        tables += [self._solutions(sub) for sub in nested]
        todo = sorted(range(len(tables)),
                      key=lambda i: len(next(iter(tables[i].values()), ())))
        table = tables[todo.pop(0)]
        while todo:
            # a connected table next, the smallest first; a cross product
            # only where nothing connects
            nxt = next((i for i in todo if set(tables[i]) & set(table)), todo[0])
            todo.remove(nxt)
            table = self._join(table, tables[nxt])
        for flt in filters:
            keep = self._compare(table, flt)
            table = {v: col[keep] for v, col in table.items()}
        if any(item[0] == "count" for item in select):
            key = self._key(table, group)
            _, first, counts = np.unique(key, return_index=True, return_counts=True)
            out = {}
            for item in select:
                if item[0] == "var":
                    if item[1] not in group:
                        raise ValueError("reference: selected variable not grouped")
                    out[item[1]] = table[item[1]][first]
                else:
                    out[item[2]] = ("count", counts)
            return out
        if group:
            raise ValueError("reference: GROUP BY without an aggregate")
        return {item[1]: table[item[1]] for item in select}

    def query(self, sparql: str):
        """The answer as a list of rows of strings (a multiset: order free)."""
        query = _Parser(sparql).parse()
        table = self._solutions(query)
        if self._rendered is None:
            self._rendered = np.array([render(t) for t in self.terms], object)
        cols = []
        for item in query[0]:
            col = table[item[-1]]
            if isinstance(col, tuple):  # a count
                cols.append(np.array([str(c) for c in col[1]], object))
            else:
                cols.append(self._rendered[col])
        return [list(r) for r in zip(*(c.tolist() for c in cols))]
