"""Share of the device's busy time in the ops whose name matches ``match``.

``level``: ``top`` counts ops no other op contains (a ``while`` with all it
runs); ``any`` counts every matching op at its own duration; ``self`` takes
each op's children out."""

import re


def read(ctx, match, level="top"):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    rx = re.compile(match)
    hit = sum(v for name, v in trace[level].items() if rx.search(name))
    return 100.0 * hit / trace["busy_s"]
