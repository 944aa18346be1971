"""gqa_paged_attn_time_share.decode: share of the device's busy time in
the custom calls under the scopes `attn_full` and `attn_window`: the
head-major paged kernel alone, without the projections, rotary, cache
write, gate and W_o that `full_attn_time_share.decode` and
`window_attn_time_share.decode` hold beside it. The same operations as
the denominator of `gqa_paged_attn_roofline.decode`.
"""
from harness import scope_paths

WORDS = ("attn_full", "attn_window")
EVENT = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    return scope_paths.share(ctx, WORDS, EVENT)
