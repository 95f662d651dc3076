"""Device-resident reasoning: untagged and provenance fixpoints on the
accelerator, single-chip and mesh-distributed.

Three demos:

1. the single-chip device fixpoint — whole Datalog closure as one XLA
   dispatch (a ``lax.while_loop``), with the chunked per-round driver used
   automatically past the toolchain-safe join capacity;
2. the device provenance fixpoint — expiry-tagged facts (the cross-window
   SDS+ semiring) closed with tags as an f64 device column;
3. the distributed tagged fixpoint over an 8-device mesh.

Run:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/10_device_reasoning.py
"""

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from kolibrie_tpu.parallel import DistProvenanceReasoner, make_mesh  # noqa: E402
from kolibrie_tpu.reasoner.device_fixpoint import DeviceFixpoint  # noqa: E402
from kolibrie_tpu.reasoner.device_provenance import (  # noqa: E402
    infer_provenance_device,
)
from kolibrie_tpu.reasoner.provenance import ExpirationProvenance  # noqa: E402
from kolibrie_tpu.reasoner.provenance_seminaive import (  # noqa: E402
    seed_tag_store,
)
from kolibrie_tpu.reasoner.reasoner import Reasoner  # noqa: E402


def build_graph(n=200):
    r = Reasoner()
    for i in range(n):
        r.add_abox_triple(f"sensor{i}", "feeds", f"sensor{(i + 1) % n}")
        r.add_abox_triple(f"sensor{i}", "inZone", f"zone{i % 8}")
    r.add_rule(
        r.rule_from_strings(
            [("?a", "feeds", "?b"), ("?b", "feeds", "?c")],
            [("?a", "reaches", "?c")],
        )
    )
    return r


# 1 ── single-chip device fixpoint ------------------------------------------
r = build_graph()
before = len(r.facts)
t0 = time.perf_counter()
derived = r.infer_new_facts_device()  # None would mean host fallback
dt = time.perf_counter() - t0
print(f"device fixpoint: {derived} facts derived in {dt*1000:.1f}ms "
      f"(base {before})")

# the chunked per-round driver is what the same API uses past the
# one-dispatch join-capacity bound; it can also be forced:
r2 = build_graph()
DeviceFixpoint(r2).infer_chunked(chunk_rows=128)
assert r2.facts.triples_set() == r.facts.triples_set()
print("chunked per-round driver: identical closure")

# 2 ── expiry-tagged provenance on device -----------------------------------
prov = ExpirationProvenance()
r3 = build_graph(60)
store = seed_tag_store(r3, prov)
s, p, o = r3.facts.columns()
now_ms = 1_700_000_000_000
for j, k in enumerate(zip(s.tolist(), p.tolist(), o.tolist())):
    store.tags[k] = now_ms + 250 * j  # per-observation expiry
out = infer_provenance_device(r3, prov, store)
assert out is not None
sample = next(iter(sorted(store.tags.items())))
print(f"device provenance fixpoint: {len(store.tags)} tagged facts; "
      f"derived facts expire with their shortest-lived premise "
      f"(sample tag {sample[1]})")

# 3 ── distributed tagged fixpoint over the mesh ----------------------------
mesh = make_mesh(min(8, len(jax.devices())))
r4 = build_graph(60)
store4 = seed_tag_store(r4, prov)
s, p, o = r4.facts.columns()
for j, k in enumerate(zip(s.tolist(), p.tolist(), o.tolist())):
    store4.tags[k] = now_ms + 250 * j
n_dist = DistProvenanceReasoner(mesh, r4, prov, store4).infer()
assert r4.facts.triples_set() == r3.facts.triples_set()
assert store4.tags == store.tags
print(f"distributed tagged fixpoint ({mesh.devices.size} devices): "
      f"{n_dist} derived, tags identical to the single-chip run")

# --------------------------------------------------------------------------
# 4. RDF-star on device (round 4): a ground quoted ANNOTATION GATE —
#    << :sensorNet :mode :strict >> is a fully-ground guard premise whose
#    closure-constant tag caps every derivation's confidence, and the
#    stratified NAF pass runs on device too.
# --------------------------------------------------------------------------
from kolibrie_tpu.core.rule import Rule
from kolibrie_tpu.core.terms import Term, TriplePattern
from kolibrie_tpu.reasoner.provenance import MinMaxProbability
from kolibrie_tpu.reasoner.provenance_seminaive import infer_with_provenance

mm = MinMaxProbability()


def build_star():
    r = Reasoner()
    d = r.dictionary
    C, V = Term.constant, Term.variable
    # the gate itself: asserted with confidence 0.8
    r.add_tagged_triple(":net", ":mode", ":strict", 0.8)
    for i in range(12):
        r.add_tagged_triple(f":s{i}", ":reading", f":v{i}", 0.95)
    r.add_tagged_triple(":s5", ":faulty", ":yes", 1.0)
    r.add_rule(
        Rule(
            premise=[
                TriplePattern(  # ground guard: drops from the join plan,
                    C(d.encode(":net")),  # its 0.8 tag caps every ⊗
                    C(d.encode(":mode")),
                    C(d.encode(":strict")),
                ),
                TriplePattern(V("x"), C(d.encode(":reading")), V("v")),
            ],
            conclusion=[TriplePattern(V("x"), C(d.encode(":valid")), V("v"))],
        )
    )
    # NAF: a faulty sensor blocks its validation
    r.add_rule(
        r.rule_from_strings(
            [("?x", ":valid", "?v")],
            [("?x", ":trusted", "?v")],
            negative=[("?x", ":faulty", ":yes")],
        )
    )
    return r

r_host = build_star()
st_host = seed_tag_store(r_host, mm)
infer_with_provenance(r_host, mm, st_host)
r_dev = build_star()
st_dev = seed_tag_store(r_dev, mm)
out = infer_provenance_device(r_dev, mm, st_dev)
assert out is not None, "device refused the RDF-star/NAF program"
assert dict(st_host.tags) == dict(st_dev.tags)
d = r_dev.dictionary
from kolibrie_tpu.core.triple import Triple
t0 = Triple(d.encode(":s0"), d.encode(":trusted"), d.encode(":v0"))
print(f"RDF-star gate + NAF on device: trusted(:s0)={st_dev.tags[t0]} "
      f"(capped by the 0.8 gate), faulty :s5 blocked, "
      f"{len(st_dev.tags)} tags identical to host")
