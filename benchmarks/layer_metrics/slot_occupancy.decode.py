"""slot_occupancy.decode: mean share of the engine's slots in use over the
window's steps.
"""
from harness import metric_lib


def read(ctx):
    return metric_lib.slot_occupancy(ctx)
