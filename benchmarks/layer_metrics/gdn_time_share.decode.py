"""gdn_time_share.decode: share of the device's busy time spent in
operations whose scope path holds `attn_gdn`: the gated DeltaNet layers'
projections, convolution, gates, both forms of the recurrence and the
output's norm, gate and projection.
"""
from harness import scope_paths

WORDS = ("attn_gdn",)


def read(ctx):
    return scope_paths.share(ctx, WORDS)
