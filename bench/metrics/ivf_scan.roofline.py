"""Share of its roofline that ``ivf_scan`` reaches: the algorithm's work for the
cell's shapes (``bench/work.py``: the live rows of the probed lists) over
the device time of the ops under the ``repro.kernels.ivf_scan`` scope in
the traced window."""
from bench.layer import roofline


def read(ctx):
    return roofline(ctx, "ivf_scan.roofline", "ivf_scan")
