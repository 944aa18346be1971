"""preemptions: requests preempted for pool pages inside the window."""
from harness import metric_lib


def read(ctx):
    return metric_lib.window_count(ctx, "preemptions")
