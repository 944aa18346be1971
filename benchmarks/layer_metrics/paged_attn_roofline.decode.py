"""paged_attn_roofline.decode: memory-bound: K and V bytes of the contexts
attended in the traced window (from the traffic served) over 819 GB/s,
over the kernel's device time.
"""
from harness import metric_lib


# the same matcher as paged_attn_time_share
EVENT = r'custom_call_target="tpu_custom_call"'
FIELD = "name"


def read(ctx):
    return metric_lib.paged_attn_roofline(ctx, EVENT, FIELD)
