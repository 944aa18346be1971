"""step_mfu.train: whole training step: forward + backward FLOPs of the
traced steps (6P per token + causal attention, no recomputation) over
the traced window and the chips' bf16 peak.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.train_step_mfu(ctx)
