"""``device.peak_hbm_pct``: the runtime's peak bytes in use over its
byte limit, on the fullest of the cell's chips, in percent, read after
the window.  It guards memory; it should not decide a change."""


def read(ctx):
    if not ctx.memory_peak_bytes or not ctx.memory_limit_bytes:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.memory_limit_bytes
