"""kda_proj_time_share.decode: share of the device's busy time under the
scope `attn_kda` OUTSIDE the recurrence's two forms (`kda_chunk`,
`kda_recur`): the four projections, the short convolution with its tail,
the norms, the decay and the gates (`kda_proj`), and the output's norm,
gate and W_o (`kda_out`).
"""
from harness import scope_paths


def read(ctx):
    red = scope_paths.reduction(ctx)
    whole = scope_paths.seconds(ctx, ("attn_kda",))
    if red is None or whole is None or not red["busy_s"]:
        return None
    forms = scope_paths.seconds(ctx, ("kda_chunk", "kda_recur")) or 0.0
    return 100.0 * (whole - forms) / red["busy_s"]
