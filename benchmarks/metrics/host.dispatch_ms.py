"""``host.dispatch_ms``: the median, over the traced calls, of the host
time spent inside the program's entry functions until they return, in
milliseconds (the harness's own clock).  Where an entry waits on the
device inside itself, that wait is host time, as a caller sees it."""

import statistics


def read(ctx):
    if not ctx.dispatch_s:
        return None
    return 1e3 * statistics.median(ctx.dispatch_s)
