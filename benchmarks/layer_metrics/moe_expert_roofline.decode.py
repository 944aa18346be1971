"""moe_expert_roofline.decode: memory-bound: the bytes the held experts'
grouped products must move in the traced window (the weights of the
experts TOUCHED, by the program's counter, and a row in and a row out
an assignment: the reference's `moe_expert_bytes`) over 819 GB/s, over
the device time of the scope `moe_experts`.
"""
from harness import metric_lib, scope_paths

WORDS = ("moe_experts",)


def read(ctx):
    obs = ctx["obs"]
    count = getattr(ctx["ref"], "moe_expert_bytes", None)
    secs = scope_paths.seconds(ctx, WORDS)
    if secs is None or count is None or "traced" not in obs:
        return None
    need = count(ctx["cfg"], obs["weight_dtype"], obs["traced"])
    if need is None:
        return None
    return metric_lib.pct(need / secs, ctx["peaks"]["hbm_bytes_per_s"])
