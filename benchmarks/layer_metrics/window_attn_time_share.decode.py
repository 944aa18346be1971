"""window_attn_time_share.decode: share of the device's busy time in
operations under the scope `attn_window` (projections, rotary, cache
write, the paged kernel over the window, gate, W_o).
"""
from harness import scope_paths

WORDS = ("attn_window",)


def read(ctx):
    return scope_paths.share(ctx, WORDS)
