"""Every per-layer metric ``BENCHMARK.json`` declares can be read: its file is
there, its reader loads and takes the file's arguments, and a run of a program
that lacks the span or counter behind it (the parent of the PR that brought
the metric) gives nothing instead of raising.  ``counter_at_open`` on a
hand-made context.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import inspect
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import data as files  # noqa: E402
from benchmark.readers import counter_at_open  # noqa: E402
from benchmark.tests import mesh4_entries  # noqa: E402

# with the metrics that wait for the cell ``lubm5.mesh4`` (mesh4_entries.py)
PER_LAYER = mesh4_entries.bench()["per_layer"]
EMPTY_RUN = {"cycles": [], "spans_by_trace": {}, "counters0": {}, "counters1": {},
             "phases": {}, "quantities": {}, "trace": None}


@pytest.mark.parametrize("metric", [m["name"] for m in PER_LAYER])
def test_metric_has_its_file_and_a_loadable_reader(metric):
    args = dict(files.read_json("layer_metrics", metric + ".json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    inspect.signature(reader.read).bind(EMPTY_RUN, **args)
    assert reader.read(dict(EMPTY_RUN), **args) is None


def test_counter_at_open_sums_the_prefixed_counters_at_window_open():
    ctx = {
        "counters0": {
            'metrics.kolibrie_store_order_build_seconds_total{order="pos"}': 1.5,
            'metrics.kolibrie_store_order_build_seconds_total{order="osp"}': 0.0,
            'metrics.kolibrie_store_h2d_seconds_total{segment="base"}': 0.25,
            'metrics.kolibrie_store_h2d_bytes_total{segment="base"}': 4096.0,
        },
        # what the window adds is counter_delta's to read, not this reader's
        "counters1": {
            'metrics.kolibrie_store_h2d_seconds_total{segment="base"}': 9.0},
    }
    orders = "metrics.kolibrie_store_order_build_seconds_total"
    assert counter_at_open.read(ctx, [orders]) == 1.5
    assert counter_at_open.read(
        ctx, [orders, "metrics.kolibrie_store_h2d_seconds_total"]) == 1.75
    # a counter that never grew reads 0; one the program lacks reads nothing
    assert counter_at_open.read(ctx, [orders + '{order="osp"}']) == 0.0
    assert counter_at_open.read(ctx, ["metrics.kolibrie_cap_retry_seconds"]) is None


def test_all_to_all_pct_matches_the_name_jax_gives_the_instruction():
    """On the chip the instruction is ``%all_to_all.7 = ... all-to-all(...)``:
    the trace reduction keeps the name, which has underscores."""
    args = dict(files.read_json("layer_metrics", "all_to_all_pct.json")["reader"])
    reader = files.load_module("readers", args.pop("kind"))
    trace = {"busy_s": 10.0, "top": {"while.183": 10.0},
             "any": {"while.183": 10.0, "all_to_all.7": 0.5, "all-to-all.2": 0.25,
                     "all-reduce.27": 0.1, "fusion.226": 4.0}}
    assert reader.read({"trace": trace}, **args) == 7.5
