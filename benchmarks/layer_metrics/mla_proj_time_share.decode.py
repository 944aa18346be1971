"""mla_proj_time_share.decode: share of the device's busy time under the
scope `attn_mla` OUTSIDE the latent walk's custom calls and the scope
`mla_expand`: W_q with its norm, rotary and the absorption of W_UK
(`mla_q`), W_kv_a with its norms, rotary and the row's scatter
(`mla_latent_write`), W_UV and W_o (`mla_out`), and the tick's block
layout round the walk.
"""
from harness import scope_paths

EVENT = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    red = scope_paths.reduction(ctx)
    whole = scope_paths.seconds(ctx, ("attn_mla",))
    if red is None or whole is None or not red["busy_s"]:
        return None
    walk = scope_paths.seconds(ctx, ("mla_walk",), EVENT) or 0.0
    expand = scope_paths.seconds(ctx, ("mla_expand",)) or 0.0
    return 100.0 * (whole - walk - expand) / red["busy_s"]
