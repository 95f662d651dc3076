"""Seconds of a set-up phase, or a quantity per second of it, times ``scale``."""


def read(ctx, phase, quantity=None, scale=1.0):
    seconds = ctx["phases"].get(phase)
    if not seconds:
        return None
    if quantity is None:
        return seconds * scale
    return ctx["quantities"][quantity] / seconds * scale
