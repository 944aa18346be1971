"""newarch_attended: positions attended in the window, over its
segments."""
from harness import arith


def read(ctx):
    return sum(arith.context_sum(s, n)
               for s, n in ctx["obs"]["window"]["segments"])
