"""flash_attn_roofline: compute-bound: causal forward + backward attention
FLOPs of the traced steps (from the batch's shapes) over the bf16 peak,
over the kernels' device time.
"""
from harness import metric_lib


# the same matcher as flash_attn_time_share
EVENT = r'custom_call_target="tpu_custom_call"'
FIELD = "name"


def read(ctx):
    return metric_lib.flash_attn_roofline(ctx, EVENT, FIELD)
