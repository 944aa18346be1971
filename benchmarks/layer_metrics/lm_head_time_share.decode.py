"""lm_head_time_share.decode: share of the device's busy time in operations
scoped `lm_head` or `sample`: the vocabulary projection and the choice
of the next token.
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("lm_head", "sample")


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
