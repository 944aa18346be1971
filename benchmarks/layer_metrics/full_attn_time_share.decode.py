"""full_attn_time_share.decode: share of the device's busy time in
operations under the scope `attn_full` (projections, rotary, cache
write, the paged kernel over every earlier position, gate, W_o).
"""
from harness import scope_paths

WORDS = ("attn_full",)


def read(ctx):
    return scope_paths.share(ctx, WORDS)
