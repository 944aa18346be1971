"""gen_lag_max_s: how late the load generator sent a request, at worst (its
own clock).
"""


def read(ctx):
    return ctx["obs"].get("gen_lag_max_s")
