"""Total time of the named spans in one cycle, median over the window's cycles."""

from benchmark.harness.layers import has_span, per_cycle


def read(ctx, spans):
    names = set(spans)
    if not has_span(ctx, names):
        return None
    return per_cycle(
        ctx, lambda group: sum(sp["dur_ms"] for sp in group if sp["name"] in names)
    )
