"""``murmur3_roofline``: the ``murmur3`` entries' share of their HBM
roofline, in percent (device trace)."""

from benchmarks.harness import roofline


def read(ctx):
    return roofline.share_pct(ctx, "murmur3")
