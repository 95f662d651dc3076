"""Native C++ runtime agreement tests: the native SDD engine and N-Triples
bulk parser must agree exactly with their pure-Python twins.

The native library is built on demand (native/Makefile) by the loader; if
the toolchain is unavailable these tests are skipped, and the package keeps
running pure-Python.
"""

import random

import numpy as np
import pytest

from kolibrie_tpu import native as native_loader
from kolibrie_tpu.reasoner.diff_sdd import wmc_gradient
from kolibrie_tpu.reasoner.sdd import FALSE, TRUE, SddManager, make_sdd_manager

pytestmark = pytest.mark.skipif(
    not native_loader.available(), reason="native library unavailable"
)


def make_native():
    from kolibrie_tpu.native.sdd_native import NativeSddManager

    return NativeSddManager()


def random_formula(mgr, n_vars, rng, n_ops=40):
    """Build the same random formula against any manager; returns node id."""
    vars_ = [mgr.new_var(w_pos=rng.uniform(0.1, 0.9)) for _ in range(n_vars)]
    pool = [mgr.literal(v, rng.random() < 0.5) for v in vars_]
    for _ in range(n_ops):
        a, b = rng.choice(pool), rng.choice(pool)
        op = rng.choice(["and", "or"])
        node = mgr.apply(a, b, op)
        if rng.random() < 0.3:
            node = mgr.negate(node)
        pool.append(node)
    return pool[-1]


def test_factory_returns_native():
    mgr = make_sdd_manager()
    assert type(mgr).__name__ == "NativeSddManager"


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_sdd_agreement_random_formulas(seed):
    rng_a, rng_b = random.Random(seed), random.Random(seed)
    py, nat = SddManager(), make_native()
    node_py = random_formula(py, 6, rng_a)
    node_nat = random_formula(nat, 6, rng_b)
    # identical construction order => identical arena => identical node ids
    assert node_py == node_nat
    assert py.wmc(node_py) == pytest.approx(nat.wmc(node_nat), abs=1e-12)
    assert py.size(node_py) == nat.size(node_nat)


def test_terminals_and_literals():
    nat = make_native()
    v = nat.new_var(0.3)
    lit = nat.literal(v)
    assert nat.apply(lit, FALSE, "and") == FALSE
    assert nat.apply(lit, TRUE, "and") == lit
    assert nat.apply(lit, TRUE, "or") == TRUE
    assert nat.negate(nat.negate(lit)) == lit
    assert nat.wmc(lit) == pytest.approx(0.3)
    assert nat.wmc(nat.negate(lit)) == pytest.approx(0.7)


def test_conjoin_disjoin_wmc():
    nat = make_native()
    a, b = nat.new_var(0.5), nat.new_var(0.4)
    la, lb = nat.literal(a), nat.literal(b)
    assert nat.wmc(nat.conjoin(la, lb)) == pytest.approx(0.2)
    assert nat.wmc(nat.disjoin(la, lb)) == pytest.approx(0.5 + 0.4 - 0.2)


def test_exactly_one_semantics():
    py, nat = SddManager(), make_native()
    for mgr in (py, nat):
        vs = [mgr.new_var(p, kind="exclusive", group_id=1) for p in (0.2, 0.3, 0.5)]
        node = mgr.exactly_one(vs)
        # WMC of the constraint over exclusive weights (w_neg=1):
        # sum_i p_i * prod_{j!=i} 1 = 1.0
        assert mgr.wmc(node) == pytest.approx(1.0)
    # same arena state
    assert py.wmc(py.literal(0)) == pytest.approx(nat.wmc(nat.literal(0)))


def test_set_weight_updates_wmc():
    nat = make_native()
    v = nat.new_var(0.5)
    lit = nat.literal(v)
    nat.set_weight(v, 0.9)
    assert nat.wmc(lit) == pytest.approx(0.9)
    assert nat.vars[v].w_neg == pytest.approx(0.1)


@pytest.mark.parametrize("seed", [0, 7])
def test_gradient_agreement_and_finite_differences(seed):
    rng_a, rng_b = random.Random(seed), random.Random(seed)
    py, nat = SddManager(), make_native()
    node_py = random_formula(py, 5, rng_a, n_ops=25)
    node_nat = random_formula(nat, 5, rng_b, n_ops=25)
    g_py = wmc_gradient(py, node_py)
    g_nat = wmc_gradient(nat, node_nat)
    assert set(g_py) == set(g_nat)
    for v in g_py:
        assert g_py[v] == pytest.approx(g_nat[v], abs=1e-12)
    # finite differences on the native engine
    eps = 1e-6
    for v in range(5):
        p0 = nat.vars[v].w_pos
        nat.set_weight(v, p0 + eps)
        up = nat.wmc(node_nat)
        nat.set_weight(v, p0 - eps)
        dn = nat.wmc(node_nat)
        nat.set_weight(v, p0)
        assert g_nat[v] == pytest.approx((up - dn) / (2 * eps), abs=1e-5)


def test_enumerate_models_agreement():
    rng_a, rng_b = random.Random(3), random.Random(3)
    py, nat = SddManager(), make_native()
    node_py = random_formula(py, 5, rng_a, n_ops=20)
    node_nat = random_formula(nat, 5, rng_b, n_ops=20)
    assert py.enumerate_models(node_py) == nat.enumerate_models(node_nat)


def test_enumerate_models_respects_limit():
    nat = make_native()
    vs = [nat.new_var(0.5) for _ in range(8)]
    node = FALSE
    for v in vs:
        node = nat.disjoin(node, nat.literal(v))
    assert len(nat.enumerate_models(node, limit=3)) == 3


# ------------------------------------------------------------- N-Triples


NT_DOC = """
# a comment line
<http://e/a> <http://e/p> <http://e/b> .
<http://e/a> <http://e/name> "Alice \\"quoted\\" \\u00e9" .
_:b1 <http://e/p> "30"^^<http://www.w3.org/2001/XMLSchema#integer> .
<http://e/a> <http://e/label> "bonjour"@fr .
<http://e/a> <http://e/p> <http://e/b> .
"""


def test_nt_bulk_parse_agreement():
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples
    from kolibrie_tpu.query.rdf_parsers import parse_ntriples

    result = bulk_parse_ntriples(NT_DOC)
    assert result is not None
    ids, terms = result
    native_triples = [
        (terms[ids[i, 0] - 1], terms[ids[i, 1] - 1], terms[ids[i, 2] - 1])
        for i in range(ids.shape[0])
    ]
    assert native_triples == parse_ntriples(NT_DOC)


def _parse_with_threads(doc: str, nthreads: int):
    """Production decode path (bulk_parse_ntriples) with an EXPLICIT thread
    count so the chunk-split/merge/remap path runs even on tiny documents."""
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples

    result = bulk_parse_ntriples(doc, nthreads=nthreads)
    assert result is not None
    ids, terms = result
    return ids.shape[0], ids, terms


def _decoded_triples(n, ids, terms):
    return [
        (terms[ids[i, 0] - 1], terms[ids[i, 1] - 1], terms[ids[i, 2] - 1])
        for i in range(n)
    ]


def test_nt_multithreaded_merge_agreement():
    """4-way chunked parse must produce the same triples (and term dedup) as
    the single-threaded parse, with cross-chunk repeated terms remapped to
    one id."""
    from kolibrie_tpu.query.rdf_parsers import parse_ntriples

    # repeated terms across what will be different chunks force the merge
    # remap; escapes/typed/lang literals exercise materialized terms too
    doc = "\n".join(
        f'<http://e/s{i % 7}> <http://e/p{i % 3}> '
        + (
            f'"val \\"{i}\\" \\u00e9"'
            if i % 4 == 0
            else f'"{i}"^^<http://www.w3.org/2001/XMLSchema#integer>'
            if i % 4 == 1
            else f"<http://e/o{i % 5}>"
        )
        + " ."
        for i in range(200)
    )
    n1, ids1, terms1 = _parse_with_threads(doc, 1)
    n4, ids4, terms4 = _parse_with_threads(doc, 4)
    assert n1 == n4 == 200
    assert _decoded_triples(n1, ids1, terms1) == _decoded_triples(
        n4, ids4, terms4
    )
    assert sorted(terms1) == sorted(terms4)  # same dedup across chunks
    assert len(set(terms4)) == len(terms4)  # merge produced no duplicate ids
    assert _decoded_triples(n4, ids4, terms4) == parse_ntriples(doc)


def test_nt_multithreaded_spanning_statement_falls_back():
    """A statement spanning a chunk cut must still parse correctly (the mt
    path detects the failed chunk and re-parses single-threaded)."""
    from kolibrie_tpu.query.rdf_parsers import parse_ntriples

    # every statement spread over three lines: any mid-statement cut makes
    # that chunk's parse fail, forcing the documented fallback
    doc = "\n".join(
        f"<http://e/s{i}>\n<http://e/p>\n<http://e/o{i}> ." for i in range(50)
    )
    n4, ids4, terms4 = _parse_with_threads(doc, 4)
    assert n4 == 50
    assert _decoded_triples(n4, ids4, terms4) == parse_ntriples(doc)


def test_nt_bulk_parse_falls_back_on_rdf_star():
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples

    assert (
        bulk_parse_ntriples("<< <http://a> <http://p> <http://o> >> <http://q> <http://r> .")
        is None
    )


def test_nt_lone_surrogate_escape_matches_python():
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples
    from kolibrie_tpu.query.rdf_parsers import parse_ntriples

    doc = '<http://a> <http://b> "\\uD800" .'
    result = bulk_parse_ntriples(doc)
    assert result is not None
    ids, terms = result
    native = (terms[ids[0, 0] - 1], terms[ids[0, 1] - 1], terms[ids[0, 2] - 1])
    assert native == parse_ntriples(doc)[0]


def test_nt_bulk_parse_falls_back_on_turtle():
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples

    assert bulk_parse_ntriples("@prefix ex: <http://e/> . ex:a ex:p ex:b .") is None


def test_sparql_database_native_load_equivalence():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db_native = SparqlDatabase()
    assert db_native._parse_ntriples_native(NT_DOC) == 5

    db_py = SparqlDatabase()
    from kolibrie_tpu.query import rdf_parsers

    db_py._ingest(rdf_parsers.parse_ntriples(NT_DOC))

    assert sorted(db_native.iter_decoded()) == sorted(db_py.iter_decoded())


def test_sparql_database_parse_ntriples_empty_and_comment_only():
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    db = SparqlDatabase()
    assert db.parse_ntriples("# only a comment\n") == 0
    assert len(db) == 0


def test_nt_bulk_parse_empty_first_term():
    """A zero-length first term ("<>") must intern safely — the arena must
    not touch blocks.back() before any block exists (regression: segfault)."""
    from kolibrie_tpu.native.nt_native import bulk_parse_ntriples

    r = bulk_parse_ntriples("<> <http://p> <http://o> .\n")
    if r is None:  # native unavailable: Python path covers it elsewhere
        return
    ids, terms = r
    assert ids.shape == (1, 3)
    assert terms[ids[0, 0] - 1] == ""
    assert terms[ids[0, 1] - 1] == "http://p"


TTL_DOC = """@prefix ex: <http://example.org/> .
@prefix foaf: <http://xmlns.com/foaf/0.1/> .
PREFIX ds: <https://data.example/ontology#>
# comment line
ex:alice a foaf:Person ;
    foaf:knows ex:bob, ex:carol ;
    ds:salary 42000 ;
    ds:score 3.5 ;
    ds:big 1.5e3 ;
    ds:active true .
ex:bob foaf:name "Bob \\"quoted\\""@en .
ex:carol ds:note "w"^^<http://www.w3.org/2001/XMLSchema#string> ;
    ds:typed "7"^^ds:custom .
_:b1 ex:linked ex:alice .
"""


def _turtle_both_paths(doc, nthreads=0):
    """(native triples, python triples) as decoded string sets."""
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    def load(native):
        db = SparqlDatabase()
        if not native:
            db._parse_turtle_native = lambda data: None
        n = db.parse_turtle(doc)
        trips = {
            tuple(db.dictionary.decode(x) for x in t)
            for t in db.store.triples_set()
        }
        return n, trips, dict(db.prefixes)

    return load(True), load(False)


def test_ttl_bulk_parse_agreement():
    (n1, t1, p1), (n0, t0, p0) = _turtle_both_paths(TTL_DOC)
    assert n1 == n0
    assert t1 == t0
    assert p1 == p0


def test_ttl_multithreaded_merge_agreement():
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    doc = TTL_DOC + "\n".join(
        f"ex:n{i} ds:salary {1000 + i} ." for i in range(997)
    )
    r_mt = bulk_parse_turtle(doc, {}, nthreads=4)
    r_st = bulk_parse_turtle(doc, {}, nthreads=1)
    assert r_mt is not None and r_st is not None
    ids_mt, terms_mt, pf_mt = r_mt
    ids_st, terms_st, pf_st = r_st
    set_mt = {tuple(terms_mt[j - 1] for j in row) for row in ids_mt}
    set_st = {tuple(terms_st[j - 1] for j in row) for row in ids_st}
    assert set_mt == set_st
    assert len(ids_mt) == len(ids_st)
    assert pf_mt == pf_st


def test_ttl_bulk_parse_falls_back_on_unsupported():
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    head = "@prefix ex: <http://e/> .\n"
    for bad in (
        "ex:a ex:p [ ex:q ex:r ] .",
        "ex:a ex:p ( 1 2 ) .",
        'ex:a ex:p """multi\nline""" .',
        "ex:a ex:p 'single' .",
        "@base <http://b/> .",
        "<< ex:a ex:p ex:o >> ex:q ex:r .",
    ):
        assert bulk_parse_turtle(head + bad, {}) is None, bad


def test_ttl_initial_prefixes_and_undefined_prefix():
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    # prefixes handed in by the caller (db.prefixes) apply without
    # document directives
    r = bulk_parse_turtle(
        "ex:a ex:p ex:o .", {"ex": "http://init.example/"}
    )
    assert r is not None
    ids, terms, _ = r
    assert terms[ids[0][0] - 1] == "http://init.example/a"
    # an undefined prefix is a hard error -> Python fallback decides
    assert bulk_parse_turtle("nope:a nope:b nope:c .", {}) is None


def test_ttl_statement_spanning_chunk_boundary():
    """';'-continued statements span lines; the chunk splitter must cut at
    statement terminators only (or fall back), never mis-parse."""
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    doc = "@prefix ex: <http://e/> .\n" + "\n".join(
        f'ex:s{i} ex:p ex:a{i} ;\n    ex:q ex:b{i} ;\n    ex:r "v{i}" .'
        for i in range(400)
    )
    r_mt = bulk_parse_turtle(doc, {}, nthreads=6)
    r_st = bulk_parse_turtle(doc, {}, nthreads=1)
    assert r_st is not None and r_mt is not None
    ids_mt, terms_mt, _ = r_mt
    ids_st, terms_st, _ = r_st
    set_mt = {tuple(terms_mt[j - 1] for j in row) for row in ids_mt}
    set_st = {tuple(terms_st[j - 1] for j in row) for row in ids_st}
    assert set_mt == set_st
    assert len(ids_mt) == 1200


def test_sdd_batched_round_matches_per_row():
    """The batched SDD derivation round (apply_batch + reduce_groups) must
    produce the same facts and WMC values as the per-row tag loop."""
    from kolibrie_tpu.reasoner.provenance_seminaive import (
        infer_with_provenance,
        seed_tag_store,
    )
    from kolibrie_tpu.reasoner.reasoner import Reasoner
    from kolibrie_tpu.reasoner.sdd import SddProvenance

    def build():
        r = Reasoner()
        for i in range(60):  # n >= 32 rows so the batched path engages
            r.add_tagged_triple(f"x{i}", "p", f"y{i % 6}", 0.2 + 0.1 * (i % 7))
            r.add_tagged_triple(f"y{i % 6}", "q", f"z{i % 3}", 0.5)
        r.add_rule(
            r.rule_from_strings(
                [("?a", "p", "?b"), ("?b", "q", "?c")], [("?a", "pq", "?c")]
            )
        )
        return r

    r1 = build()
    prov1 = SddProvenance()
    st1 = seed_tag_store(r1, prov1)
    infer_with_provenance(r1, prov1, st1)

    r2 = build()
    prov2 = SddProvenance()
    st2 = seed_tag_store(r2, prov2)
    real = prov2.manager

    class NoBatch:
        def __getattr__(self, k):
            if k == "apply_batch":
                raise AttributeError(k)
            return getattr(real, k)

    prov2.manager = NoBatch()
    infer_with_provenance(r2, prov2, st2)

    assert r1.facts.triples_set() == r2.facts.triples_set()
    assert set(st1.tags) == set(st2.tags)
    for k in sorted(st1.tags):
        w1 = prov1.manager.wmc(st1.tags[k])
        w2 = real.wmc(st2.tags[k])
        assert abs(w1 - w2) < 1e-12, (k, w1, w2)


RX_DOC = """<?xml version="1.0"?>
<rdf:RDF xmlns:rdf="http://www.w3.org/1999/02/22-rdf-syntax-ns#" xmlns:ex="http://e/">
<ex:Person rdf:about="http://e/a" ex:nick="al">
  <ex:knows rdf:resource="http://e/b"/>
  <ex:age rdf:datatype="http://www.w3.org/2001/XMLSchema#int">30</ex:age>
  <ex:note xml:lang="fr">salut &amp; bye</ex:note>
  <ex:friend rdf:nodeID="bn1"/>
  <ex:empty></ex:empty>
</ex:Person>
<rdf:Description rdf:nodeID="bn1"><ex:age>7</ex:age></rdf:Description>
<rdf:Description rdf:ID="frag"><ex:p>v</ex:p></rdf:Description>
</rdf:RDF>"""


def test_rdfxml_bulk_parse_agreement():
    """Native streaming RDF/XML parser vs the ElementTree path: typed
    nodes, attribute properties, resource/nodeID/datatype/lang, entity
    escapes, rdf:ID."""
    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    def load(native):
        db = SparqlDatabase()
        if not native:
            db._parse_rdf_native = lambda d: None
        n = db.parse_rdf(RX_DOC)
        return n, {
            tuple(db.dictionary.decode(x) for x in t)
            for t in db.store.triples_set()
        }

    n1, t1 = load(True)
    n0, t0 = load(False)
    assert n1 == n0
    assert t1 == t0


def test_rdfxml_bulk_parse_falls_back_on_unsupported():
    from kolibrie_tpu.native.nt_native import bulk_parse_rdf_xml

    rdfns = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"
    for bad in (
        # nested node element in property position
        f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">'
        '<rdf:Description rdf:about="http://e/a">'
        '<e:p><rdf:Description rdf:about="http://e/b"/></e:p>'
        "</rdf:Description></rdf:RDF>",
        # default namespace
        '<r xmlns="http://d/"/>',
        # DOCTYPE
        f'<!DOCTYPE x><rdf:RDF xmlns:rdf="{rdfns}"/>',
        # fresh blank node (no about/ID/nodeID)
        f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">'
        "<rdf:Description><e:p>v</e:p></rdf:Description></rdf:RDF>",
        # parseType
        f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">'
        '<rdf:Description rdf:about="http://e/a">'
        '<e:p rdf:parseType="Literal">x</e:p>'
        "</rdf:Description></rdf:RDF>",
    ):
        assert bulk_parse_rdf_xml(bad) is None


def test_ttl_dot_terminated_pname_falls_back():
    """'ex:c.' (no space before the statement dot) parses differently in
    the Python tokenizer; the native path must fall back, never diverge."""
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    assert (
        bulk_parse_turtle("@prefix ex: <http://e/> .\nex:a ex:p ex:c.", {})
        is None
    )
    # interior dots stay native
    r = bulk_parse_turtle("@prefix ex: <http://e/> .\nex:a ex:p ex:c.d .", {})
    assert r is not None
    ids, terms, _ = r
    assert terms[ids[0][2] - 1] == "http://e/c.d"


def test_ttl_forward_referenced_prefix_rejected_in_mt():
    """A prefix used before its directive must fail in BOTH thread modes
    (the chunked pre-pass may not legalize forward references)."""
    from kolibrie_tpu.native.ttl_native import bulk_parse_turtle

    fwd = "ex:a ex:p ex:o .\n@prefix ex: <http://e/> .\n" + "\n".join(
        f"ex:n{i} ex:p ex:o ." for i in range(50)
    )
    assert bulk_parse_turtle(fwd, {}, nthreads=4) is None
    assert bulk_parse_turtle(fwd, {}, nthreads=1) is None


def test_rdfxml_whitespace_normalization_parity():
    """CRLF text content and raw-newline attribute values must normalize
    exactly like ElementTree (XML attribute-value + line-ending rules)."""
    from kolibrie_tpu.native.nt_native import bulk_parse_rdf_xml
    from kolibrie_tpu.query.rdf_parsers import parse_rdf_xml

    rdfns = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"
    doc = (
        f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">\r\n'
        '<rdf:Description rdf:about="http://e/a" e:attr="a\nb">\r\n'
        "<e:txt>line1\r\nline2</e:txt>\r\n"
        "</rdf:Description>\r\n</rdf:RDF>"
    )
    r = bulk_parse_rdf_xml(doc)
    assert r is not None
    ids, terms = r
    objs = {terms[row[2] - 1] for row in ids}
    assert objs == {t[2] for t in parse_rdf_xml(doc)}
    assert '"a b"' in objs and '"line1\nline2"' in objs


def test_rdfxml_multithreaded_chunk_agreement():
    """Chunked RDF/XML parse (splits after </rdf:Description>) must agree
    with sequential native AND ElementTree on a doc mixing Description
    nodes, typed nodes, and comments; a typed-node-fragment chunk falls
    back to the sequential parse rather than mis-parsing."""
    from kolibrie_tpu.native.nt_native import bulk_parse_rdf_xml
    from kolibrie_tpu.query.rdf_parsers import parse_rdf_xml

    rdfns = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"
    parts = [f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">']
    for i in range(400):
        if i % 7 == 0:
            parts.append(
                f'<e:Person rdf:about="http://e/p{i}">'
                f'<e:knows rdf:resource="http://e/p{i + 1}"/></e:Person>'
            )
        else:
            parts.append(
                f'<rdf:Description rdf:about="http://e/d{i}">'
                f"<e:v>{i}</e:v><!-- c{i} --></rdf:Description>"
            )
    parts.append("</rdf:RDF>")
    doc = "\n".join(parts)

    def tset(r):
        ids, terms = r
        return {tuple(terms[j - 1] for j in row) for row in ids}

    r_mt = bulk_parse_rdf_xml(doc, nthreads=6)
    r_st = bulk_parse_rdf_xml(doc, nthreads=1)
    assert r_mt is not None and r_st is not None
    assert tset(r_mt) == tset(r_st) == {
        (s, p, o) for s, p, o in parse_rdf_xml(doc)
    }
    assert len(r_mt[0]) == len(r_st[0])


def test_rdfxml_truncated_document_rejected():
    """A document missing </rdf:RDF> (partial download) must NOT silently
    load partial triples in either thread mode — ElementTree raises, so
    the native path falls back rather than diverge."""
    from kolibrie_tpu.native.nt_native import bulk_parse_rdf_xml

    rdfns = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"
    trunc = (
        f'<rdf:RDF xmlns:rdf="{rdfns}" xmlns:e="http://e/">'
        + "".join(
            f'<rdf:Description rdf:about="http://e/a{i}">'
            f"<e:v>{i}</e:v></rdf:Description>"
            for i in range(500)
        )
    )
    assert bulk_parse_rdf_xml(trunc, nthreads=1) is None
    assert bulk_parse_rdf_xml(trunc, nthreads=4) is None
    ok = trunc + "</rdf:RDF>"
    r = bulk_parse_rdf_xml(ok, nthreads=4)
    assert r is not None and len(r[0]) == 500


def test_parser_parity_fuzz():
    """Randomized documents through native AND Python parsers must agree
    triple-for-triple (or the native path must decline).  Seeded RNG keeps
    failures reproducible."""
    import random

    from kolibrie_tpu.query.sparql_database import SparqlDatabase

    rng = random.Random(20260730)
    iri_pool = [f"http://fz.example/r{i}" for i in range(30)]
    pfx_pool = ["a", "zz", "p-x", "d.t"]

    def rnd_literal():
        kind = rng.randrange(5)
        body = "".join(
            rng.choice(["x", "y", " ", "\\t", "\\n", "\\\"", "é", "&", "7"])
            for _ in range(rng.randrange(0, 6))
        )
        if kind == 0:
            return f'"{body}"'
        if kind == 1:
            return f'"{body}"@en-GB'
        if kind == 2:
            return f'"{body}"^^<http://www.w3.org/2001/XMLSchema#string>'
        if kind == 3:
            return str(rng.randrange(-50, 5000))
        return rng.choice(["3.25", "1.5e2", "true", "false"])

    def turtle_doc():
        lines = [f"@prefix {p}: <http://fz.example/{p}#> ." for p in pfx_pool]
        for _ in range(rng.randrange(1, 25)):
            s = (
                f"<{rng.choice(iri_pool)}>"
                if rng.random() < 0.5
                else f"{rng.choice(pfx_pool)}:l{rng.randrange(9)}"
            )
            parts = []
            for _ in range(rng.randrange(1, 4)):
                pred = (
                    "a"
                    if rng.random() < 0.15
                    else f"{rng.choice(pfx_pool)}:p{rng.randrange(6)}"
                )
                objs = ", ".join(
                    (
                        f"<{rng.choice(iri_pool)}>"
                        if rng.random() < 0.4
                        else (rnd_literal() if pred != "a" else f"{rng.choice(pfx_pool)}:C")
                    )
                    for _ in range(rng.randrange(1, 3))
                )
                parts.append(f"{pred} {objs}")
            lines.append(f"{s} " + " ;\n    ".join(parts) + " .")
        return "\n".join(lines)

    def load_both(doc, parse_name, native_attr):
        def one(native):
            db = SparqlDatabase()
            if not native:
                setattr(db, native_attr, lambda d: None)
            try:
                getattr(db, parse_name)(doc)
            except Exception as e:
                return ("error", type(e).__name__)
            return (
                "ok",
                frozenset(
                    tuple(db.dictionary.decode(x) for x in t)
                    for t in db.store.triples_set()
                ),
            )

        return one(True), one(False)

    for trial in range(40):
        doc = turtle_doc()
        got, want = load_both(doc, "parse_turtle", "_parse_turtle_native")
        assert got == want, (trial, doc[:400], got[0], want[0])

    rdfns = "http://www.w3.org/1999/02/22-rdf-syntax-ns#"

    def xml_doc():
        parts = [
            f'<rdf:RDF xmlns:rdf="{rdfns}" '
            + " ".join(
                f'xmlns:{p}="http://fz.example/{p}#"'
                for p in ("a", "zz")
            )
            + ">"
        ]
        for i in range(rng.randrange(1, 15)):
            tagpfx = rng.choice(["rdf:Description", "a:T", "zz:Node"])
            attrs = f' rdf:about="{rng.choice(iri_pool)}"'
            if rng.random() < 0.3:
                attrs += f' a:lit="v&amp;{i}"'
            props = []
            for _ in range(rng.randrange(0, 3)):
                p = f"{rng.choice(['a', 'zz'])}:p{rng.randrange(5)}"
                r = rng.random()
                if r < 0.4:
                    props.append(f'<{p} rdf:resource="{rng.choice(iri_pool)}"/>')
                elif r < 0.6:
                    props.append(f'<{p} xml:lang="fr">txt {i}</{p}>')
                else:
                    props.append(f"<{p}>v&lt;{i}&gt;</{p}>")
            parts.append(f"<{tagpfx}{attrs}>" + "".join(props) + f"</{tagpfx.split()[0]}>")
        parts.append("</rdf:RDF>")
        return "\n".join(parts)

    for trial in range(40):
        doc = xml_doc()
        got, want = load_both(doc, "parse_rdf", "_parse_rdf_native")
        assert got == want, (trial, doc[:400], got[0], want[0])
