"""step_mfu.tpot: whole serving step: forward FLOPs of the tokens through
the model in the traced window (2P per token + attention over the live
context) over the traced window and the chip's bf16 peak.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.serve_step_mfu(ctx)
