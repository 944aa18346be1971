"""step_turnaround_ms_p50.decode: median idle time on device 0 between
the end of one step program and the start of the next: the host's emit
-> admit -> plan or reserve -> dispatch.
"""
from harness import span_reduce


# which programs are the engine's steps, as data
MODULE = r"jit_pure"


def read(ctx):
    return span_reduce.step_turnaround_ms_p50(ctx, MODULE)
