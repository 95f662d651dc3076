"""Growth over the window of the counters whose key starts with ``prefix``,
times ``scale`` (1e-6 reads bytes as MB).

A labelled counter has no line until it first grows.  Where ``beside`` is
given and the program has a counter that starts with it (the same layer's,
without labels), a ``prefix`` that matches nothing has never grown and reads
0; without such a counter the program lacks the layer and the reader gives
nothing."""


def read(ctx, prefix, scale=1.0, beside=None):
    c0, c1 = ctx["counters0"], ctx["counters1"]
    keys = [k for k in c1 if k.startswith(prefix)]
    if not keys:
        layer_is_there = beside and any(k.startswith(beside) for k in c1)
        return 0.0 if layer_is_there else None
    return float(sum(c1[k] - c0.get(k, 0.0) for k in keys)) * scale
