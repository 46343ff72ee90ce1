"""Device ms per query batch spent outside every ``repro.kernels`` scope
(tile map, top-k merge)."""
from bench.layer import nonkernel_ms


def read(ctx):
    return nonkernel_ms(ctx, "batches")
