"""step_device_ms_p50.decode: median device time of one run of the engine's
step programs (XLA Modules line).
"""
from harness import metric_lib


# which programs are the engine's steps, as data
MODULE = r"jit_pure"


def read(ctx):
    return metric_lib.step_device_ms_p50(ctx, MODULE)
