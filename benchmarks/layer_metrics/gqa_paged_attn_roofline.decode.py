"""gqa_paged_attn_roofline.decode: memory-bound: K and V bytes of the
positions attended in the traced window (the reference's
`kv_bytes_attended`: window layers clipped at the window, a row counted
once for the query heads that share it) over 819 GB/s, over the device
time of the custom calls under the scopes `attn_full` and
`attn_window`.
"""
from harness import metric_lib, scope_paths

WORDS = ("attn_full", "attn_window")
EVENT = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    obs = ctx["obs"]
    secs = scope_paths.seconds(ctx, WORDS, EVENT)
    if secs is None or "traced" not in obs:
        return None
    need = ctx["ref"].kv_bytes_attended(ctx["cfg"], obs["traced"],
                                        obs["kv_dtype"])
    return metric_lib.pct(need / secs, ctx["peaks"]["hbm_bytes_per_s"])
