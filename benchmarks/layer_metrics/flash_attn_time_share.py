"""flash_attn_time_share: share of the device's busy time spent in the
flash attention kernels (forward, dq, dkv).
"""
from harness import metric_lib


# which device events are the flash kernels, as data: today every
# Pallas call inside the training step is one of them
EVENT = r'custom_call_target="tpu_custom_call"'
FIELD = "name"


def read(ctx):
    return metric_lib.kernel_time_share(ctx, EVENT, FIELD)
