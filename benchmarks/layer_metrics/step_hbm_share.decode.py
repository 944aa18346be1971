"""step_hbm_share.decode: memory-bound: bytes the traced iterations must
read (weights once an iteration + the K and V rows of every attended
context) over busy device time and 819 GB/s.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.serve_step_hbm_share(ctx)
