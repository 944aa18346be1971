"""newarch_serve_flops: forward operations of the window's work, by the
configuration's own reference, from the segments the driver saw."""


def read(ctx):
    return ctx["ref"].serve_flops(ctx["cfg"], ctx["obs"]["window"])
