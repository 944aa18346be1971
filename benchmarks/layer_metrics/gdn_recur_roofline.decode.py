"""gdn_recur_roofline.decode: memory-bound: the state bytes of the rows
that the program's counter says went through the RECURRENT form in the
traced window (the reference's `gdn_state_bytes`: a row reads its
sequence's float32 state once and writes it once, a layer) over 819
GB/s, over the device time of the scope `gdn_recur`, whatever implements
it.
"""
from harness import metric_lib, scope_paths

WORDS = ("gdn_recur",)


def read(ctx):
    obs = ctx["obs"]
    count = getattr(ctx["ref"], "gdn_state_bytes", None)
    secs = scope_paths.seconds(ctx, WORDS)
    if secs is None or count is None or "traced" not in obs:
        return None
    need = count(ctx["cfg"], obs["traced"])
    return metric_lib.pct(need / secs, ctx["peaks"]["hbm_bytes_per_s"])
