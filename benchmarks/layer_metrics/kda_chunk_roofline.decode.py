"""kda_chunk_roofline.decode: the CHUNKED form against the LARGER of its
two bounds for the rows the program's counter says it served in the
traced window: the reference's `kda_chunk_bytes` (q, k, v, the decay and
o of every row, a run's state in and out) over the HBM's bandwidth, or
`kda_chunk_flops` (the WY / UT transform at a stated chunk of 64) over
the bf16 peak; over the device time of the scope `kda_chunk`. The bound
it took is said on standard error.
"""
import sys

from harness import metric_lib, scope_paths

WORDS = ("kda_chunk",)


def read(ctx):
    obs, ref = ctx["obs"], ctx["ref"]
    flops = getattr(ref, "kda_chunk_flops", None)
    secs = scope_paths.seconds(ctx, WORDS)
    if secs is None or flops is None or "traced" not in obs:
        return None
    work = obs["traced"]
    by_bytes = ref.kda_chunk_bytes(ctx["cfg"], work) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    by_flops = flops(ctx["cfg"], work) / ctx["peaks"]["bf16_flops"]
    print(f"[kda_chunk_roofline.decode] bound by "
          f"{'bandwidth' if by_bytes >= by_flops else 'compute'}: "
          f"{by_bytes:.4f}s of bytes, {by_flops:.4f}s of FLOPs, "
          f"{secs:.4f}s under the scope", file=sys.stderr, flush=True)
    return metric_lib.pct(max(by_bytes, by_flops), secs)
