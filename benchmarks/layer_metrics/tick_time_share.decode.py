"""tick_time_share.decode: share of the device's busy time spent in the
programs that single ticks launched (device 0's "XLA Modules" events
that belong to `llm_engine.step` spans): what refilling slots costs
beside the fused windows.
"""
from harness import span_reduce


# which spans dispatch step programs, and which of them is the tick,
# as data
SPAN = "llm_engine.step"
DISPATCH_SPANS = ("llm_engine.step", "llm_engine.fused_step")


def read(ctx):
    return span_reduce.launched_time_share(ctx, SPAN, DISPATCH_SPANS)
