"""lm_head_loss_time_share.train: share of the device's busy time in
operations scoped `lm_head` or `loss`: the vocabulary projection and the
cross entropy, forward and backward.
"""
from harness import span_reduce


# which named scopes count, as data ("" = no vocabulary word)
SCOPES = ("lm_head", "loss")


def read(ctx):
    return span_reduce.scope_time_share(ctx, SCOPES)
