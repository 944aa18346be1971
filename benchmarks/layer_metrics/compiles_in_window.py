"""compiles_in_window: executables added to the step programs' dispatch
caches between the window's opening and its close (should be 0).
"""


def read(ctx):
    return ctx["obs"].get("compiles_in_window")
