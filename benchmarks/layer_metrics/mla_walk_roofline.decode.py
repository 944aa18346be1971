"""mla_walk_roofline.decode: the latent walk against the LARGER of its two
bounds for the rows it served in the traced window: the latent bytes
they must read (the reference's `kv_bytes_attended`: a row once for all
heads and once for the rows of a slot that a step reads together) over
the HBM's bandwidth, or attention's own FLOPs (`mla_attn_flops`: the
cheaper of the absorbed and the expanded form, whichever ran) over the
bf16 peak; over the device time of the custom calls under `mla_walk`.
The bound it took is said on standard error.
"""
import sys

from harness import metric_lib, scope_paths

WORDS = ("mla_walk",)
EVENT = r'custom_call_target="tpu_custom_call"'


def read(ctx):
    obs, ref = ctx["obs"], ctx["ref"]
    flops = getattr(ref, "mla_attn_flops", None)
    secs = scope_paths.seconds(ctx, WORDS, EVENT)
    if secs is None or flops is None or "traced" not in obs:
        return None
    work = obs["traced"]
    by_bytes = ref.kv_bytes_attended(ctx["cfg"], work, obs["kv_dtype"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = flops(ctx["cfg"], work) / ctx["peaks"]["bf16_flops"]
    print(f"[mla_walk_roofline.decode] bound by "
          f"{'bandwidth' if by_bytes >= by_flops else 'compute'}: "
          f"{by_bytes:.4f}s of bytes, {by_flops:.4f}s of FLOPs, "
          f"{secs:.4f}s in the kernel", file=sys.stderr, flush=True)
    return metric_lib.pct(max(by_bytes, by_flops), secs)
