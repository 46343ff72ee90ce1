"""Share of its roofline that ``gather_score`` reaches: the algorithm's work for the
cell's shapes (``bench/work.py``) over the device time of the ops under
the ``repro.kernels.gather_score`` scope in the traced window."""
from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "gather_score.roofline", "gather_score")
