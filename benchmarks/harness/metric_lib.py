"""Shared arithmetic of the per-layer readers (`layer_metrics/<name>.py`,
each `read(ctx) -> number | None`). `ctx` holds: `obs` (the driver's
counts: `window` and `traced` counter deltas, the configuration),
`trace` (the reduction of the traced window, None without one), `cfg`,
`chips`, `peaks` (the chip's row of the peaks table).

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line. A share of a peak is never clamped.
"""
import statistics

from . import arith, trace_reduce


def pct(num, den):
    if num is None or not den:
        return None
    return 100.0 * num / den


def window_count(ctx, key):
    return ctx["obs"].get("window", {}).get(key)


def share_of_counts(ctx, num_keys, den_keys):
    w = ctx["obs"].get("window")
    if not w:
        return None
    den = sum(w[k] for k in den_keys)
    return pct(sum(w[k] for k in num_keys), den)


def slot_occupancy(ctx):
    w = ctx["obs"].get("window")
    if not w or not w.get("steps"):
        return None
    return 100.0 * w["occupancy_sum"] / w["steps"]


def tokens_per_dispatch(ctx):
    w = ctx["obs"].get("window")
    if not w or not w.get("dispatches"):
        return None
    return (w["prefill_tokens"] + w["decode_tokens"]) / w["dispatches"]


def device0(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    return tr["devices"][min(tr["devices"])]


def step_device_ms_p50(ctx, module_pattern):
    dev = device0(ctx)
    if dev is None:
        return None
    d = trace_reduce.module_durations_ms(dev, module_pattern)
    return statistics.median(d) if d else None


def kernel_seconds(ctx, pattern, field="name"):
    """Mean over the devices of the seconds spent in matching events."""
    tr = ctx.get("trace")
    if not tr:
        return None
    secs = [trace_reduce.matching_seconds(d, pattern, field)
            for d in tr["devices"].values()]
    secs = [s for s in secs if s is not None]
    return sum(secs) / len(tr["devices"]) if secs else None


def kernel_time_share(ctx, pattern, field="name"):
    tr = ctx.get("trace")
    if not tr:
        return None
    return pct(kernel_seconds(ctx, pattern, field), tr["busy_s"])


# ---- shares of a peak ------------------------------------------------

def train_step_mfu(ctx):
    tr, obs = ctx.get("trace"), ctx["obs"]
    if not tr or "traced" not in obs:
        return None
    flops = obs["traced"]["steps"] * arith.train_step_flops(
        ctx["cfg"], obs["batch"], obs["seq"])
    return pct(flops / tr["window_s"],
               ctx["chips"] * ctx["peaks"]["bf16_flops"])


def flash_attn_roofline(ctx, pattern, field="name"):
    """Compute-bound: causal forward + backward attention FLOPs from
    the batch's shapes over the kernel's device time."""
    obs = ctx["obs"]
    secs = kernel_seconds(ctx, pattern, field)
    if secs is None or "traced" not in obs:
        return None
    flops = obs["traced"]["steps"] * arith.flash_attn_flops(
        ctx["cfg"], obs["batch"], obs["seq"]) / ctx["chips"]
    return pct(flops / secs, ctx["peaks"]["bf16_flops"])


def serve_step_mfu(ctx):
    tr, obs = ctx.get("trace"), ctx["obs"]
    if not tr or "traced" not in obs:
        return None
    t = obs["traced"]
    flops = arith.serve_flops(ctx["cfg"], t["processed"], t["context_sum"])
    return pct(flops / tr["window_s"],
               ctx["chips"] * ctx["peaks"]["bf16_flops"])


def model_iterations(ctx):
    """Forward passes in the traced window: a fused dispatch scans
    decode_k iterations, a single tick is one."""
    t = ctx["obs"]["traced"]
    return t["fused_steps"] * ctx["obs"]["decode_k"] \
        + (t["steps"] - t["fused_steps"])


def serve_step_hbm_share(ctx):
    """Memory-bound: bytes the iterations of the traced window must
    read at the batch the engine formed — the weights once an
    iteration and every attended context's K and V rows — over the
    device's busy time."""
    tr, obs = ctx.get("trace"), ctx["obs"]
    if not tr or "traced" not in obs or not tr["busy_s"]:
        return None
    cfg = ctx["cfg"]
    need = model_iterations(ctx) * arith.weight_bytes(
        cfg, obs["weight_dtype"]) + arith.paged_attn_bytes(
        cfg, obs["traced"]["context_sum"], obs["kv_dtype"])
    return pct(need / tr["busy_s"], ctx["peaks"]["hbm_bytes_per_s"])


def paged_attn_roofline(ctx, pattern, field="name"):
    """Memory-bound: K and V bytes of the contexts attended in the
    traced window over the attention kernel's device time."""
    obs = ctx["obs"]
    secs = kernel_seconds(ctx, pattern, field)
    if secs is None or "traced" not in obs:
        return None
    need = arith.paged_attn_bytes(ctx["cfg"], obs["traced"]["context_sum"],
                                  obs["kv_dtype"])
    return pct(need / secs, ctx["peaks"]["hbm_bytes_per_s"])
