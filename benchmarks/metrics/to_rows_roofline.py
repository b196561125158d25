"""``to_rows_roofline``: the ``to_rows`` entries' share of their HBM
roofline, in percent (device trace)."""

from benchmarks.harness import roofline


def read(ctx):
    return roofline.share_pct(ctx, "to_rows")
