"""Agreement corpus for the GENERAL distributed fixpoint vs the host
reasoner, on the virtual 8-device CPU mesh (conftest.py).

VERDICT round-1 item 4: the distributed path must handle arbitrary premise
counts/shapes — constants anywhere, shared variables, filters, NAF — not
just unary/binary chains.  Each case below builds the same reasoner twice
and checks the distributed closure equals the host semi-naive closure
exactly (the reference's agreement-test pattern, SURVEY §4).
"""

import numpy as np
import pytest

import jax

from kolibrie_tpu.core.rule import FilterCondition
from kolibrie_tpu.parallel import distributed_seminaive_general, make_mesh
from kolibrie_tpu.parallel.dist_general import Unsupported, lower_rules_dist
from kolibrie_tpu.reasoner.reasoner import Reasoner


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


def base_facts(r: Reasoner, n=24):
    for i in range(n):
        r.add_abox_triple(f"p{i}", "worksAt", f"org{i % 5}")
        r.add_abox_triple(f"org{i % 5}", "partOf", f"corp{i % 2}")
        r.add_abox_triple(f"corp{i % 2}", "locatedIn", "city")
        r.add_abox_triple(f"p{i}", "age", f'"{20 + i}"')
        r.add_abox_triple(f"p{i}", "knows", f"p{(i + 1) % n}")
        if i % 4 == 0:
            r.add_abox_triple(f"p{i}", "retired", "yes")
    r.add_abox_triple("org1", "suspended", "yes")


# Each entry: (name, [premises], [conclusions], negatives, filters)
RULE_CORPUS = [
    (
        "chain2",
        [("?x", "worksAt", "?o"), ("?o", "partOf", "?c")],
        [("?x", "memberOf", "?c")],
        None,
        None,
    ),
    (
        "chain3",
        [("?x", "worksAt", "?o"), ("?o", "partOf", "?c"), ("?c", "locatedIn", "?l")],
        [("?x", "basedIn", "?l")],
        None,
        None,
    ),
    (
        "const_object",
        [("?x", "worksAt", "org2"), ("?x", "knows", "?y")],
        [("?y", "knowsOrg2Worker", "yes")],
        None,
        None,
    ),
    (
        "const_filter_join",
        [("?x", "worksAt", "?o"), ("?o", "partOf", "?c")],
        [("?x", "inConglomerate", "?c")],
        None,
        "org1-eq",  # ?o = org1, resolved in _add_rule
    ),
    (
        "shared_two_vars",
        [("?x", "knows", "?y"), ("?y", "knows", "?x")],
        [("?x", "mutual", "?y")],
        None,
        None,
    ),
    (
        "multi_head",
        [("?x", "worksAt", "?o")],
        [("?x", "employed", "yes"), ("?o", "hasStaff", "?x")],
        None,
        None,
    ),
    (
        "naf_simple",
        [("?x", "worksAt", "?o")],
        [("?x", "active", "yes")],
        [("?x", "retired", "yes")],
        None,
    ),
    (
        "naf_on_object",
        [("?x", "worksAt", "?o")],
        [("?x", "stable", "yes")],
        [("?o", "suspended", "yes")],
        None,
    ),
    (
        "filter_gt",
        [("?x", "age", "?a")],
        [("?x", "adultSenior", "yes")],
        None,
        [FilterCondition("a", ">", 35.0)],
    ),
    (
        "filter_range_chain",
        [("?x", "age", "?a"), ("?x", "worksAt", "?o")],
        [("?o", "hasYoung", "?x")],
        None,
        [FilterCondition("a", "<", 30.0)],
    ),
    (
        "naf_plus_filter",
        [("?x", "age", "?a"), ("?x", "worksAt", "?o")],
        [("?x", "promotable", "yes")],
        [("?x", "retired", "yes")],
        [FilterCondition("a", ">=", 25.0)],
    ),
    (
        "triangle",
        [("?x", "knows", "?y"), ("?y", "knows", "?z"), ("?x", "worksAt", "?o")],
        [("?z", "reachableFrom", "?o")],
        None,
        None,
    ),
    (
        "recursive_transitive",
        [("?a", "partOf", "?b"), ("?b", "locatedIn", "?c")],
        [("?a", "locatedIn", "?c")],
        None,
        None,
    ),
    (
        "diamond",
        [("?x", "knows", "?y"), ("?x", "worksAt", "?o"), ("?y", "worksAt", "?o")],
        [("?x", "colleagueFriend", "?y")],
        None,
        None,
    ),
    (
        "four_premise",
        [
            ("?x", "knows", "?y"),
            ("?y", "knows", "?z"),
            ("?z", "knows", "?w"),
            ("?w", "retired", "yes"),
        ],
        [("?x", "nearRetiree", "yes")],
        None,
        None,
    ),
    (
        "const_predicate_value",
        [("?x", "retired", "yes"), ("?x", "worksAt", "?o")],
        [("?o", "hasRetiree", "?x")],
        None,
        None,
    ),
    (
        "repeated_var_premise",
        [("?x", "knows", "?x")],
        [("?x", "selfAware", "yes")],
        None,
        None,
    ),
    (
        "two_rules_cascade",  # exercised combined with chain2 below
        [("?x", "memberOf", "?c"), ("?c", "locatedIn", "?l")],
        [("?x", "cityWorker", "?l")],
        None,
        None,
    ),
    (
        "naf_unbound_neg_const",
        [("?x", "worksAt", "?o")],
        [("?x", "normalEra", "yes")],
        [("corp0", "dissolved", "yes")],
        None,
    ),
    (
        "filter_eq_id",
        [("?x", "worksAt", "?o")],
        [("?x", "atOrgThree", "yes")],
        None,
        "org3-eq",  # placeholder resolved in _add_rule
    ),
    (
        "head_constant_all",
        [("?x", "retired", "yes")],
        [("system", "hasRetirees", "yes")],
        None,
        None,
    ),
]


def _add_rule(r: Reasoner, spec):
    name, prems, concls, negs, filters = spec
    if filters == "org3-eq":
        filters = [FilterCondition("o", "=", r.dictionary.encode("org3"))]
    elif filters == "org1-eq":
        filters = [FilterCondition("o", "=", r.dictionary.encode("org1"))]
    r.add_rule(r.rule_from_strings(prems, concls, negative=negs, filters=filters))


@pytest.mark.parametrize("spec", RULE_CORPUS, ids=lambda s: s[0])
def test_rule_agreement(mesh, spec):
    r_host = Reasoner()
    base_facts(r_host)
    _add_rule(r_host, spec)
    r_host.infer_new_facts_semi_naive()

    r_dist = Reasoner()
    base_facts(r_dist)
    _add_rule(r_dist, spec)
    distributed_seminaive_general(mesh, r_dist)

    assert r_dist.facts.triples_set() == r_host.facts.triples_set(), spec[0]


def test_multi_rule_program_agreement(mesh):
    """Several interacting rules at once, including a cascade and NAF."""
    chosen = [RULE_CORPUS[0], RULE_CORPUS[17], RULE_CORPUS[6], RULE_CORPUS[8]]
    r_host = Reasoner()
    base_facts(r_host)
    for spec in chosen:
        _add_rule(r_host, spec)
    r_host.infer_new_facts_semi_naive()

    r_dist = Reasoner()
    base_facts(r_dist)
    for spec in chosen:
        _add_rule(r_dist, spec)
    derived = distributed_seminaive_general(mesh, r_dist)

    assert r_dist.facts.triples_set() == r_host.facts.triples_set()
    assert derived > 0


def test_capacity_doubling_converges(mesh):
    r_host = Reasoner()
    base_facts(r_host)
    _add_rule(r_host, RULE_CORPUS[1])
    r_host.infer_new_facts_semi_naive()

    r_dist = Reasoner()
    base_facts(r_dist)
    _add_rule(r_dist, RULE_CORPUS[1])
    from kolibrie_tpu.parallel import DistGeneralReasoner

    dr = DistGeneralReasoner(
        mesh, r_dist, fact_cap=64, delta_cap=16, join_cap=16, bucket_cap=8
    )
    dr.infer()
    assert r_dist.facts.triples_set() == r_host.facts.triples_set()


def test_cartesian_rule_unsupported(mesh):
    """Premises with no shared variables (true cross product) stay on the
    host path."""
    r = Reasoner()
    r.add_abox_triple("a", "p", "b")
    r.add_rule(
        r.rule_from_strings(
            [("org1", "partOf", "?c"), ("?x", "worksAt", "org1")],
            [("?x", "inConglomerate", "?c")],
        )
    )
    with pytest.raises(Unsupported):
        lower_rules_dist(r, r.rules)


def test_predicate_position_join_unsupported(mesh):
    """A join on a predicate-position variable can't route on the mesh."""
    r = Reasoner()
    r.add_abox_triple("a", "p", "b")
    r.add_rule(
        r.rule_from_strings(
            [("?x", "?p", "?y"), ("?z", "?p", "?w")], [("?x", "same", "?z")]
        )
    )
    with pytest.raises(Unsupported):
        lower_rules_dist(r, r.rules)


def test_dist_pallas_join_composition():
    """KOLIBRIE_PALLAS=force: the shard-local joins run through the
    Pallas kernel INSIDE shard_map (interpret mode on the CPU mesh).
    Subprocess-isolated: the flag is read at trace time and the compiled
    round programs are cached per process."""
    import os
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
os.environ["KOLIBRIE_PALLAS"] = "force"
import jax; jax.config.update("jax_platforms", "cpu")
import kolibrie_tpu.parallel.dist_join as dj
from kolibrie_tpu.parallel import DistGeneralReasoner, make_mesh
from kolibrie_tpu.reasoner.reasoner import Reasoner

# trace-time marker: the kernel route must ACTUALLY be taken — a silent
# fallback to the XLA join would still produce agreeing closures
_pallas_calls = []
_orig = dj._local_join_u32_pallas
dj._local_join_u32_pallas = (
    lambda *a, **k: (_pallas_calls.append(1), _orig(*a, **k))[1]
)

def build():
    r = Reasoner()
    for i in range(16):
        r.add_abox_triple(f"s{i}", "knows", f"s{(i + 3) % 16}")
    r.add_rule(r.rule_from_strings(
        [("?x", "knows", "?y"), ("?y", "knows", "?z")],
        [("?x", "fof", "?z")]))
    return r

d, h = build(), build()
DistGeneralReasoner(make_mesh(8), d, fact_cap=128, delta_cap=64,
                    join_cap=64, bucket_cap=32).infer()
h.infer_new_facts_semi_naive()
assert d.facts.triples_set() == h.facts.triples_set()
assert _pallas_calls, "Pallas local-join route was never traced"
print("DIST_PALLAS_OK")
"""
    env = dict(os.environ)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=480,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DIST_PALLAS_OK" in proc.stdout
