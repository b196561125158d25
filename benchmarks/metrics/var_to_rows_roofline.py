"""``var_to_rows_roofline``: the ``var_to_rows`` entries' share of their
HBM roofline, in percent (device trace).  The rows are charged at their
compact JCUDF size (``harness/var_work.py``)."""

from benchmarks.harness import roofline


def read(ctx):
    return roofline.share_pct(ctx, "var_to_rows")
