#!/usr/bin/env python3
"""The benchmark: one run of one cell on the served query path.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts the real server in a thread, generates the configuration's data from
``--seed``, loads it through ``POST /store/load``, warms the cell's cycle,
measures for ``--seconds`` with the traffic file's clients in a closed loop,
compares every answer of the window with the plain reference and proves that
the device (for a cell on four chips: the mesh) served them.  The last line of standard output is the result; earlier lines
are JSON too and free-form.  Without a TPU (or with fewer chips than the cell
asks for) it prints no result and exits 3.

``--selftest`` (CPU, seconds) checks the comparison, the trace reduction and
``BENCHMARK.json``'s files and names.  ``KOLIBRIE_BENCH_REHEARSAL_SCALE=<n>``
overrides the configuration's scale for a rehearsal off the chip: every phase
runs, the result says ``"correct": false`` and the exit code is 1.  Nothing
turns an off-TPU run into a pass.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also answer the window's queries from the control "
                         "(a stale store) and print how many come out wrong")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args()
    if args.selftest:
        from benchmark.harness import selftest

        return selftest.main()
    if not args.workload:
        ap.error("--workload is required")
    from benchmark.harness import runner

    scale = os.environ.get("KOLIBRIE_BENCH_REHEARSAL_SCALE")
    result, code = runner.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_START,
        scale=int(scale) if scale else None, control=args.control,
    )
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
