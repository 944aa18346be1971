"""newarch_processed: positions through the model in the window."""


def read(ctx):
    return ctx["obs"]["window"]["processed"]
