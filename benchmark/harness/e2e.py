"""The end-to-end metrics, taken on the client's clock.  Which cell reports
which is ``BENCHMARK.json``'s to say; units come from there too."""

import numpy as np


def cycle_ms(run):
    """Client wall time of one whole pass over the cell's cycle: the time
    from the window's first send to its last response, on the client's
    clock, over the number of whole cycles -- what lies between two cycles
    is in it."""
    whole = run["cycles"]
    if not whole:
        return None
    return (whole[-1]["t1"] - whole[0]["t0"]) * 1000.0 / len(whole)


def latency_p95_ms(run):
    """95th percentile of single-request client latency over every request
    the window sent."""
    lat = [r["ms"] for r in run["requests"]]
    return float(np.percentile(lat, 95)) if lat else None


def setup_s(run):
    """Process start to window open."""
    return run["setup_s"]


METRICS = {"cycle_ms": cycle_ms, "latency_p95_ms": latency_p95_ms,
           "setup_s": setup_s}
