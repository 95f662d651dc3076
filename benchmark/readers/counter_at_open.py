"""Value at window open of the counters whose key starts with one of
``prefixes``, summed, times ``scale`` (1e-6 reads bytes as MB).  The
program's counters start at 0 with the process, so what one reads when the
window opens is what set-up spent: generate, load, compile or cache load,
warm-up.  A gauge reads as it stood then.  A program that has no such counter
gives nothing."""


def read(ctx, prefixes, scale=1.0):
    c0 = ctx["counters0"]
    keys = [k for k in c0 if k.startswith(tuple(prefixes))]
    if not keys:
        return None
    return float(sum(c0[k] for k in keys)) * scale
