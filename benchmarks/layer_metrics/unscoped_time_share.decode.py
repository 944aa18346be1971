"""unscoped_time_share.decode: share of the device's busy time in operations
with no vocabulary word on their path: how much the attribution misses.
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("",)


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
