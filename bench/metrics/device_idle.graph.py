"""Share of the traced window in which no op ran on the device, %."""
from bench.layer import device_idle


def read(ctx):
    return device_idle(ctx)
