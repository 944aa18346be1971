"""fused_window_device_ms_p50.decode: median device time of one fused
decode window (the step programs that `llm_engine.fused_step` spans
launched), apart from the single ticks that share its module name.
"""
from harness import span_reduce


# which spans dispatch step programs, which of them is the fused
# window, and which modules are the engine's steps, as data
SPAN = "llm_engine.fused_step"
DISPATCH_SPANS = ("llm_engine.step", "llm_engine.fused_step")
MODULE = r"jit_pure"


def read(ctx):
    return span_reduce.launched_module_ms_p50(ctx, SPAN, DISPATCH_SPANS,
                                              MODULE)
