"""Self time of the named span -- its duration less its child spans' -- summed
over one cycle, median over whole cycles."""

from benchmark.harness.layers import per_cycle


def read(ctx, span):
    def self_ms(group):
        total = 0.0
        for sp in group:
            if sp["name"] == span:
                total += sp["dur_ms"] - sum(
                    c["dur_ms"] for c in group if c["parent_id"] == sp["span_id"])
        return total

    if not any(sp["name"] == span
               for group in ctx["spans_by_trace"].values() for sp in group):
        return None
    return per_cycle(ctx, self_ms)
