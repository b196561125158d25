"""``device.idle_pct``: 100 x (1 - device busy time / traced stretch),
averaged over the cell's chips (device trace).  Busy is the union of the
intervals in which an operation ran on the chip."""


def read(ctx):
    view = ctx.trace
    if view is None or not view.chips or view.window_ns() <= 0:
        return None
    busy = sum(view.busy_ns(c) for c in view.chips) / len(view.chips)
    return 100.0 * (1.0 - busy / view.window_ns())
