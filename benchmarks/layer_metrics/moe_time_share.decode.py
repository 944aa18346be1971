"""moe_time_share.decode: share of the device's busy time in operations
under the scope `moe` (router, the held experts' grouped products with
their sort and scatter, the shared expert).
"""
from harness import scope_paths

# which scope words count, as data
WORDS = ("moe",)


def read(ctx):
    return scope_paths.share(ctx, WORDS)
