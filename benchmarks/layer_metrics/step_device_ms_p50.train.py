"""step_device_ms_p50.train: median device time of one run of the training
step program (XLA Modules line).
"""
from harness import metric_lib


# which program is the training step, as data
MODULE = r"jit_step"


def read(ctx):
    return metric_lib.step_device_ms_p50(ctx, MODULE)
