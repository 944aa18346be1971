"""queue_wait_p50_s: median wait from the server's queue to the start of
prefill (reqtrace stamps queued -> prefill_start).
"""


def read(ctx):
    return ctx["obs"].get("queue_wait_p50_s")
