"""Total time of the named spans in one cycle, median over whole cycles."""

from benchmark.harness.layers import per_cycle


def read(ctx, spans):
    names = set(spans)
    if not any(sp["name"] in names
               for group in ctx["spans_by_trace"].values() for sp in group):
        return None
    return per_cycle(
        ctx, lambda group: sum(sp["dur_ms"] for sp in group if sp["name"] in names)
    )
