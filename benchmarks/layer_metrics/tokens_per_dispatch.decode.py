"""tokens_per_dispatch.decode: tokens through the model per dispatched step
program in the window.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.tokens_per_dispatch(ctx)
