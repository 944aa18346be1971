"""mla_walk_time_share.decode: share of the device's busy time in the
custom calls under the scope `mla_walk`: the latent paged walk alone,
without the projections, norms, rotary, the row's write and W_o that
`mla_proj_time_share.decode` holds beside it. The same operations as
the denominator of `mla_walk_roofline.decode`.
"""
from harness import scope_paths

WORDS = ("mla_walk",)
EVENT = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    return scope_paths.share(ctx, WORDS, EVENT)
