"""Device ms per engine epoch spent outside every ``repro.kernels`` scope."""
from bench.layer import nonkernel_ms


def read(ctx):
    return nonkernel_ms(ctx, "epochs")
