"""norm_time_share.train: share of the device's busy time in operations
scoped `norm`: every LayerNorm, forward and backward.
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("norm",)


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
