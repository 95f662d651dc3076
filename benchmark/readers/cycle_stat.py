"""A statistic of the window's single cycles on the client's clock --
``median`` or ``max`` -- beside ``cycle_ms``, which is their mean with what
lies between them: a request that stalls moves the mean and not the median."""

from statistics import median


def read(ctx, stat):
    ms = [c["ms"] for c in ctx["cycles"]]
    if not ms:
        return None
    return {"median": median, "max": max}[stat](ms)
