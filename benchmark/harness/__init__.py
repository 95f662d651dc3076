"""What a cell requires of the program, asked before anything is started.

A cell may have a file ``benchmark/requires/<cell>.json``:

    {"module": "<module of the program>", "registers": "<metric family>",
     "why": "<what a program without it does to the cell>"}

A program whose ``module`` registers no such metric cannot run the cell's
deployment.  ``run.py --workload <cell>`` then says so on standard error and
exits 1 within seconds, with no result line: before the data is generated,
the server started or a chip taken, instead of after a whole run whose
``correct`` a program without it passes or fails by chance.  A cell without
the file requires nothing.

This file stands in for a check at the top of ``runner.run_cell``, whose
file was not this PR's to edit (PERF.md section 7): the cell is read from the
command line here, in the started process only, when ``run.py`` imports the
harness.
"""

import argparse
import importlib
import json
import multiprocessing
import os
import sys


BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _requires(workload: str, bench_dir: str = BENCH_DIR) -> None:
    need_file = os.path.join(bench_dir, "requires", workload + ".json")
    if not os.path.exists(need_file):
        return
    with open(need_file, encoding="utf-8") as f:
        need = json.load(f)
    # the cell's environment first, as the runner sets it before it imports
    # the program
    with open(os.path.join(bench_dir, "workloads", workload + ".json"),
              encoding="utf-8") as f:
        os.environ.update(json.load(f)["env"])
    if os.path.dirname(BENCH_DIR) not in sys.path:
        sys.path.insert(0, os.path.dirname(BENCH_DIR))
    importlib.import_module(need["module"])
    from kolibrie_tpu.obs import metrics

    if metrics.REGISTRY.get(need["registers"]) is None:
        raise SystemExit(
            f"benchmark: this program cannot run cell {workload}: "
            f"{need['module']} registers no {need['registers']} "
            f"({need['why']})")


if multiprocessing.parent_process() is None:
    _ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    _ap.add_argument("--workload")
    _workload = _ap.parse_known_args(sys.argv[1:])[0].workload
    if _workload:
        _requires(_workload)
