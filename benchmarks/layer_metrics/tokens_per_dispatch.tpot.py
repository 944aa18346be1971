"""tokens_per_dispatch.tpot: tokens through the model per dispatched step
program in the window.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.tokens_per_dispatch(ctx)
