"""mlp_time_share.train: share of the device's busy time in operations
scoped `mlp`, forward and backward, without the LayerNorm nested in it.
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("mlp",)


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
