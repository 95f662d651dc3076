"""Per-layer metrics: each has a file (``benchmark/layer_metrics/<name>.json``)
naming its reader (``benchmark/readers/<kind>.py``) and the reader's
arguments; unit, layer and the rest are ``BENCHMARK.json``'s to say."""

from statistics import median

from . import data as files


def per_cycle(ctx, value_of_trace):
    """Median over whole cycles of the sum, over the cycle's requests, of
    ``value_of_trace(spans of one request)``."""
    vals = []
    for cyc in ctx["cycles"]:
        groups = [ctx["spans_by_trace"].get(t) for t in cyc["trace_ids"]]
        if any(g is None for g in groups):
            continue  # the ring lost this cycle's spans
        vals.append(sum(value_of_trace(g) for g in groups))
    return median(vals) if vals else None


def has_span(ctx, names) -> bool:
    return any(sp["name"] in names
               for group in ctx["spans_by_trace"].values() for sp in group)


def declared(bench: dict, kind: str, workload: str):
    """The metrics of ``BENCHMARK.json``'s list ``kind`` that this cell
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_all(bench: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for m in declared(bench, "per_layer", workload):
        decl = files.read_json("layer_metrics", m["name"] + ".json")
        args = dict(decl["reader"])
        reader = files.load_module("readers", args.pop("kind"))
        value = reader.read(ctx, **args)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
