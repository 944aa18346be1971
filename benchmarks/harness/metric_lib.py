"""Shared arithmetic of the per-layer readers (`layer_metrics/<name>.py`,
each `read(ctx) -> number | None`). `ctx` holds: `obs` (the driver's
counts: `window` and `traced` counter deltas, the configuration),
`trace` (the reduction of the traced window, None without one), `cfg`,
`chips`, `peaks` (the chip's row of the peaks table), and `ref`: the
configuration's reference (`references/<name>.py`), which owns the
operations and bytes of every share of a peak. A serving driver's
`obs["traced"]` is the traced window's `work` (`harness/arith.py`).

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line. A share of a peak is never clamped.
"""
import statistics

from . import trace_reduce


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
    flops = obs["traced"]["steps"] * ctx["ref"].train_step_flops(
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
    flops = obs["traced"]["steps"] * ctx["ref"].flash_attn_flops(
        ctx["cfg"], obs["batch"], obs["seq"]) / ctx["chips"]
    return pct(flops / secs, ctx["peaks"]["bf16_flops"])


def serve_step_mfu(ctx):
    tr, obs = ctx.get("trace"), ctx["obs"]
    if not tr or "traced" not in obs:
        return None
    flops = ctx["ref"].serve_flops(ctx["cfg"], obs["traced"])
    return pct(flops / tr["window_s"],
               ctx["chips"] * ctx["peaks"]["bf16_flops"])


def serve_step_hbm_share(ctx):
    """Memory-bound: bytes the iterations of the traced window must
    read at the batch the engine formed — the weights once an
    iteration and every attended context's K and V rows — over the
    device's busy time."""
    tr, obs = ctx.get("trace"), ctx["obs"]
    if not tr or "traced" not in obs or not tr["busy_s"]:
        return None
    cfg, ref, work = ctx["cfg"], ctx["ref"], obs["traced"]
    need = ref.weight_bytes(cfg, obs["weight_dtype"], work) \
        + ref.kv_bytes_attended(cfg, work, obs["kv_dtype"])
    return pct(need / tr["busy_s"], ctx["peaks"]["hbm_bytes_per_s"])


def paged_attn_roofline(ctx, pattern, field="name"):
    """Memory-bound: K and V bytes of the contexts attended in the
    traced window over the attention kernel's device time."""
    obs = ctx["obs"]
    secs = kernel_seconds(ctx, pattern, field)
    if secs is None or "traced" not in obs:
        return None
    need = ctx["ref"].kv_bytes_attended(ctx["cfg"], obs["traced"],
                                        obs["kv_dtype"])
    return pct(need / secs, ctx["peaks"]["hbm_bytes_per_s"])
