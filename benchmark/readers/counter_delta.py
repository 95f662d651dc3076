"""Growth over the window of the counters whose key starts with ``prefix``."""


def read(ctx, prefix):
    c0, c1 = ctx["counters0"], ctx["counters1"]
    keys = [k for k in c1 if k.startswith(prefix)]
    if not keys:
        return None
    return float(sum(c1[k] - c0.get(k, 0.0) for k in keys))
