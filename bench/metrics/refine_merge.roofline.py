"""Share of its roofline that ``refine_merge`` reaches: the algorithm's work for the
cell's shapes (``bench/work.py``) over the device time of the ops under
the ``repro.kernels.refine_merge`` scope in the traced window."""
from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "refine_merge.roofline", "refine_merge")
