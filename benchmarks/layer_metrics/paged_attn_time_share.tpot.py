"""paged_attn_time_share.tpot: share of the device's busy time spent in the
paged attention kernel.
"""
from harness import metric_lib


# which device events are the paged attention kernel, as data: today
# every Pallas call inside the step programs is this kernel
EVENT = r'custom_call_target="tpu_custom_call"'
FIELD = "name"


def read(ctx):
    return metric_lib.kernel_time_share(ctx, EVENT, FIELD)
