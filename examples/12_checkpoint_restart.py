"""Preemption / restart walkthrough (docs/PREEMPTION.md).

1. Database checkpoint: one compressed file holding triple columns +
   dictionary + quoted-triple table + prefixes + probability seeds;
   ``from_checkpoint`` rebuilds a queryable database (indexes and device
   copies rebuild lazily).
2. RSP stream checkpoint: snapshot a live engine mid-window, rebuild a
   FRESH engine from the same query (configuration), restore the blob
   (data), and continue the stream with exact ISTREAM semantics — events
   from before the "preemption" still join and diff correctly.

    python examples/12_checkpoint_restart.py
"""

import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from kolibrie_tpu.query.executor import execute_query_volcano  # noqa: E402
from kolibrie_tpu.query.sparql_database import SparqlDatabase  # noqa: E402
from kolibrie_tpu.rsp.builder import RSPBuilder  # noqa: E402
from kolibrie_tpu.rsp.s2r import WindowTriple  # noqa: E402

QUERY = """PREFIX ex: <http://e/>
REGISTER ISTREAM <http://out/stream> AS
SELECT ?s ?o
FROM NAMED WINDOW <http://e/w> ON ?stream [RANGE 3 STEP 1]
WHERE { WINDOW <http://e/w> { ?s ex:val ?o } }
"""


def database_checkpoint() -> None:
    db = SparqlDatabase()
    db.parse_turtle(
        """@prefix ex: <http://example.org/> .
        ex:a ex:p ex:b ; ex:salary 52000 .
        ex:b ex:p ex:c ."""
    )
    with tempfile.TemporaryDirectory() as tmp:
        path = str(Path(tmp) / "snapshot.npz")
        db.checkpoint(path)
        size = Path(path).stat().st_size
        restored = SparqlDatabase.from_checkpoint(path)
    q = "PREFIX ex: <http://example.org/> SELECT ?x ?y WHERE { ?x ex:p ?y }"
    assert execute_query_volcano(q, restored) == execute_query_volcano(q, db)
    print(f"database checkpoint: {size} bytes, restored rows match ✓")


def rsp_checkpoint() -> None:
    def build(sink):
        return RSPBuilder(QUERY).with_consumer(lambda r: sink.append(r)).build()

    def event(i):
        return WindowTriple(f"<http://e/s{i}>", "<http://e/val>", f'"{i}"')

    # uninterrupted reference run
    ref = []
    e = build(ref)
    for i, ts in enumerate([1, 2, 3, 4, 5], start=1):
        e.add_to_stream(":stream", event(i), ts)
    e.stop()

    # "preempted" run: snapshot after two events, restore into a NEW engine
    part1 = []
    e1 = build(part1)
    for i, ts in enumerate([1, 2], start=1):
        e1.add_to_stream(":stream", event(i), ts)
    blob = e1.checkpoint_state()  # JSON bytes — safe to ship over HTTP
    e1.stop()

    part2 = []
    e2 = build(part2)  # same CONFIGURATION (query); fresh process in real life
    e2.restore_state(blob)  # same DATA (window contents, ISTREAM memory)
    for i, ts in enumerate([3, 4, 5], start=3):
        e2.add_to_stream(":stream", event(i), ts)
    e2.stop()

    vals = lambda rows: [dict(r).get("o") for r in rows]  # noqa: E731
    assert vals(part1 + part2) == vals(ref)
    print(
        f"rsp checkpoint: {len(blob)} byte blob; interrupted run emitted "
        f"{vals(part1 + part2)} == uninterrupted {vals(ref)} ✓"
    )


if __name__ == "__main__":
    database_checkpoint()
    rsp_checkpoint()
