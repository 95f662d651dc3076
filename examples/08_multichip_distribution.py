"""Multi-chip distribution: shard the triple store over a device mesh, run
a distributed BGP join and a distributed semi-naive fixpoint.

Run with a virtual 8-device CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/08_multichip_distribution.py

(on a real pod the same code uses all visible TPU chips; collectives ride
ICI via shard_map + psum/all-to-all).
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# Make the host platform expose 8 virtual devices (harmless when a real
# accelerator is selected: the flag only affects the CPU platform, so on a
# TPU pod the demo runs on the real chips).
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import jax  # noqa: E402

from kolibrie_tpu.parallel.dist_fixpoint import (  # noqa: E402
    DistributedReasoner,
    DistRuleSet,
)
from kolibrie_tpu.parallel.dist_join import dist_bgp_join_count  # noqa: E402
from kolibrie_tpu.parallel.mesh import make_mesh  # noqa: E402
from kolibrie_tpu.parallel.sharded_store import ShardedTripleStore  # noqa: E402
from kolibrie_tpu.core.rule import Rule  # noqa: E402
from kolibrie_tpu.core.terms import Term, TriplePattern  # noqa: E402

mesh = make_mesh(len(jax.devices()))
print(f"mesh: {mesh.devices.size} x {jax.devices()[0].platform}")

# a parentOf chain, sharded by subject/object hash across all chips
P_PARENT = 100
n = 100
s = np.arange(1, n + 1, dtype=np.uint32)
p = np.full(n, P_PARENT, dtype=np.uint32)
o = s + 1
store = ShardedTripleStore.from_columns(mesh, s, p, o, cap_per_shard=1 << 16)

two_hops = dist_bgp_join_count(store, P_PARENT, P_PARENT)
print("2-hop paths:", two_hops)

# distributed transitive closure: delta exchanged all-to-all each round
var = Term.variable
rule = Rule(
    premise=[
        TriplePattern(var("x"), Term.constant(P_PARENT), var("y")),
        TriplePattern(var("y"), Term.constant(P_PARENT), var("z")),
    ],
    conclusion=[TriplePattern(var("x"), Term.constant(P_PARENT), var("z"))],
)
rs = DistRuleSet.from_rules([rule])
dr = DistributedReasoner(
    mesh, rs, fact_cap=1 << 16, delta_cap=1 << 15, join_cap=1 << 17,
    bucket_cap=1 << 14,
)
rounds = dr.infer(store)
s2, _, o2 = store.gather_host()
print(f"closure in {rounds} rounds: {len(s2)} facts "
      f"(expect {n * (n + 1) // 2})")
