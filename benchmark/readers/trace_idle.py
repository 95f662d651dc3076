"""Share of the traced window in which no op ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["window_s"] or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
