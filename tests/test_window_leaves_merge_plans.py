"""ISSUE 48: the window of a WCOJ accessor changes the plans that hold a
``WcojSpec`` and no other.

- Every merge-join text the benchmark sends (LUBM Q1, Q3, Q4, Q7, Q8,
  upstream's two employee queries, WatDiv's star S1, BSBM's BI Q1), lowered
  against its generator's data at a rehearsal scale, assembles the
  ``PlanSpec`` it assembled at the parent commit (``1d8a845``): the digests
  below were taken there, with this file's own code.  A ``PlanSpec`` is the
  jit's static argument, so an equal one is the same executable under the
  same cache key.  (A later PR that means to move one of these plans
  re-records its digest and says so.)
- LUBM Q2 and Q9 at the same scale: every accessor names a predicate, so
  every one carries a window, and no search runs over a whole order.
- ISSUE 51 changes a WCOJ level's body (``expand``'s slot map), not its
  spec: the merge-join digests stand as recorded, and Q2's and Q9's
  ``PlanSpec``s are the ones the parent (``4d196e2``) assembled.

No device program runs: ``build(operands=False)`` assembles the spec from
the numpy twin's counts.
"""

import hashlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import data as files  # noqa: E402
from benchmark.harness.traffic import Traffic  # noqa: E402

SEED = 48
# configuration, rehearsal scale, traffic, the templates of its cycle checked
DEPLOYMENTS = {
    "lubm-5": (1, "lookups", ("lubm_q1", "lubm_q3", "lubm_q4", "lubm_q7", "lubm_q8")),
    "employee-100k": (2000, "upstream", ("employee_join", "employee_nested_select")),
    "watdiv-100": (1, "stars_snowflakes", ("watdiv_S1",)),
    "bsbm-10m": (2, "bi_counts", ("bsbm_bi_q1",)),
}
# sha256 of repr(PlanSpec) at the parent commit
PARENT = {
    "lubm_q1": "32cbb6f9ca08cd2bb5b64828c342a204cac46df8c8043bc9565462f6446930d3",
    "lubm_q3": "32cbb6f9ca08cd2bb5b64828c342a204cac46df8c8043bc9565462f6446930d3",
    "lubm_q4": "c24bb5613d24a93cbfc9e3e92e30c3eb36df45167c2c11d1ff368b250a4b6688",
    "lubm_q7": "638fbf39237c8ec652132ada9b3c4ebe516882d94acc59132e78f3f57ba052e0",
    "lubm_q8": "7bd412be6bf67acf30d42fba2a5bbf39f261281a37aa5f3e279edcb676c80e5c",
    "employee_join": "b1caef962445498c6baff9f19d48821d09e4ed9f18c23f7f322f98b783ba98f5",
    "employee_nested_select": "aa1bca3afa2984b622394328ff65057540a8ba6c0f774f2d436794ea85c524b4",
    "watdiv_S1": "9eeea28b8f73657fa02ca1adb6f91e9c054807e6a9d6c3129f7b60c1496b73e5",
    "bsbm_bi_q1": "fa6f85fe716c7bdf4e360fba81241cbcd8ffcb07ccb104a582331635ff1760e0",
}
# the two cyclic queries' specs at ISSUE 51's parent (``4d196e2``), same code
PARENT_CYCLIC = {
    "lubm_q2": "0a889e2b64113d00ec3c7451d77350bcab02dce03663d53251f741a6cb64c4f0",
    "lubm_q9": "a25c647b3895433af2de7c706ae9fe89634c005dd08adb18329d570ac8a9e9d1",
}
CASES = [(config, name) for config, (_s, _t, names) in DEPLOYMENTS.items()
         for name in names]
_DBS = {}


def _database(config_name):
    """The deployment's data at its rehearsal scale, loaded once a module."""
    if config_name not in _DBS:
        from kolibrie_tpu.query.sparql_database import SparqlDatabase

        scale, traffic, _names = DEPLOYMENTS[config_name]
        config = files.read_json("configs", config_name + ".json")
        data = files.load_module("generators", config["generator"]).generate(
            config, SEED, scale)
        db = SparqlDatabase()
        for chunk in files.ntriples_chunks(data):
            db.parse_ntriples(chunk)
        db.store.compact()
        db.execution_mode = "device"
        texts = dict(Traffic(traffic, data["domains"], SEED).cycle(0))
        _DBS[config_name] = (db, texts)
    return _DBS[config_name]


def _lowered(db, sparql):
    """The text's WHERE as the executor plans and lowers it."""
    from kolibrie_tpu.optimizer import device_engine as de
    from kolibrie_tpu.optimizer.engine import resolve_pattern
    from kolibrie_tpu.optimizer.planner import Streamertail, build_logical_plan
    from kolibrie_tpu.query.parser import parse_sparql_query
    from kolibrie_tpu.query.subquery_inline import inline_subqueries

    db.register_prefixes_from_query(sparql)
    w = inline_subqueries(parse_sparql_query(sparql, db.prefixes).where)
    assert w.patterns and not w.subqueries
    resolved = [resolve_pattern(db, p) for p in w.patterns]
    logical = build_logical_plan(resolved, list(w.filters), [], w.values)
    plan = Streamertail(db.get_or_build_stats()).find_best_plan(logical)
    return de.lower_plan(db, plan)


def digest(spec) -> str:
    return hashlib.sha256(repr(spec).encode()).hexdigest()


@pytest.mark.parametrize("config,name", CASES)
def test_a_merge_join_plan_is_the_parents(config, name):
    from kolibrie_tpu.optimizer import device_engine as de

    db, texts = _database(config)
    low = _lowered(db, texts[name])
    assert not list(de._spec_nodes(low.root, de.WcojSpec))
    spec, operands = low.build(operands=False)
    assert operands is None
    assert low._window_caps == {} and len(low.cap_key) == 3
    assert digest(spec) == PARENT[name]
    # and nothing of it would be counted as a range search
    low._seg_rows, low._tiers_np = (), ()
    assert list(low._range_searches()) == []


@pytest.mark.parametrize("name", ["lubm_q2", "lubm_q9"])
def test_every_accessor_of_the_cyclic_queries_carries_a_window(name):
    import numpy as np

    from kolibrie_tpu.ops import round_cap
    from kolibrie_tpu.optimizer import device_engine as de

    db, _texts = _database("lubm-5")
    low = _lowered(db, files.template_text(name))
    assert isinstance(low.root, de.WcojSpec)
    spec, _ = low.build(operands=False)
    assert spec.orders == ("pos", "pso")  # the constants lead: two orders, not three
    slots = round_cap(len(db.store.base_order("spo")))
    accessors = [a for lv in spec.root.levels for a in lv.accessors]
    assert len(accessors) == 9
    assert digest(spec) == PARENT_CYCLIC[name]  # ISSUE 51: the body moved, not the spec
    for a in accessors:
        assert a.lead >= 1 and 0 < a.window < slots
    low._seg_rows = tuple((slots, db.store.delta_device_cap) for _ in spec.orders)
    low._tiers_np = np.zeros(len(spec.orders), dtype=np.int32)
    searched = list(low._range_searches())
    assert len(searched) == 18  # one in `probe`, one in `live`, an accessor
    assert {extent for _n, _p, _k, extent in searched} == {"window"}
    order_wide_rows = sum(n for n, _p, _k, extent in searched if extent == "order")
    assert order_wide_rows == 0
    assert sum(n for n, _p, _k, _e in searched) * 5 <= len(searched) * slots


if __name__ == "__main__":  # record the digests: python tests/<this file>
    for config_, name_ in CASES:
        db_, texts_ = _database(config_)
        print(f'    "{name_}": "{digest(_lowered(db_, texts_[name_]).build(operands=False)[0])}",')
    for name_ in PARENT_CYCLIC:
        low_ = _lowered(_database("lubm-5")[0], files.template_text(name_))
        print(f'    "{name_}": "{digest(low_.build(operands=False)[0])}",')
