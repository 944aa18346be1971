"""optimizer_time_share.train: share of the device's busy time in operations
scoped `optimizer`: the parameter update that stands as operations of its
own (what the compiler fuses into a gradient's matmul counts there).
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("optimizer",)


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
