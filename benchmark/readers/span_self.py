"""Self time of the named span -- its duration less its child spans' -- summed
over one cycle, median over the window's cycles."""

from benchmark.harness.layers import has_span, per_cycle


def read(ctx, span):
    def self_ms(group):
        return sum(
            sp["dur_ms"] - sum(c["dur_ms"] for c in group
                               if c["parent_id"] == sp["span_id"])
            for sp in group if sp["name"] == span)

    return per_cycle(ctx, self_ms) if has_span(ctx, {span}) else None
