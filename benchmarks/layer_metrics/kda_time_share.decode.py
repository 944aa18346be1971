"""kda_time_share.decode: share of the device's busy time spent in
operations whose scope path holds `attn_kda`: the gated delta-rule
layers' projections, convolution, gates, both forms of the recurrence
and the output projection.
"""
from harness import scope_paths

WORDS = ("attn_kda",)


def read(ctx):
    return scope_paths.share(ctx, WORDS)
