"""A rehearsal of a four-chip cell on four virtual CPU devices, in a process
of its own (the device count is fixed before JAX starts).  Started by
``test_traffic_and_mesh.py``; prints the result line.

    python benchmark/tests/mesh_rehearsal.py <cell> <seed> <seconds> <sound|no_env|tamper>
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
for name in ("KOLIBRIE_SHARDED", "KOLIBRIE_BENCH_REHEARSAL_SCALE"):
    os.environ.pop(name, None)
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

CHIP_LOOK = frozenset({"platform_is_tpu", "pallas_enabled_not_interpreted",
                       "scale_as_configured"})

if __name__ == "__main__":
    from benchmark.harness import data as files
    from benchmark.harness import runner
    from benchmark.tests import mesh4_entries

    cell, seed, seconds, mode = sys.argv[1:]
    files.read_json = mesh4_entries.read_json
    if mode == "no_env":
        # the timed path broken underneath: the cell's environment emptied,
        # so the program never attaches the mesh and one chip serves
        files.read_json = lambda *parts: (
            {"env": {}} if parts[0] == "workloads" else mesh4_entries.read_json(*parts))
    result, code = runner.run_cell(
        cell, int(seed), float(seconds), False, time.perf_counter(), scale=1,
        waive=CHIP_LOOK, tamper=(3, "alter_value") if mode == "tamper" else None)
    print(json.dumps(dict(result, exit_code=code)), flush=True)
