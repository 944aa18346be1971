"""mla_least_share_of_rows.decode: the latent bytes a step must read at
least (`mla_walk_roofline.decode`'s bandwidth numerator: a slot's rows
of ONE step read their context once, which the PROGRAM counts in
`mla_rows_attended_least`) as a share of what the reference counts from
the driver's own `segments` when every row reads its own context
(`kv_bytes_attended_by_row`). The two differ by what a prefill chunk's
rows share; the share depends on the traffic and the token budget alone,
so a change of the program's count shows here as a drift.
"""
from harness import metric_lib


def read(ctx):
    obs = ctx["obs"]
    ref = ctx["ref"]
    by_row = getattr(ref, "kv_bytes_attended_by_row", None)
    work = obs.get("traced") or obs["window"]
    stats = work.get("stats") or {}
    if by_row is None or "mla_rows_attended_least" not in stats:
        return None
    rows = by_row(ctx["cfg"], work, obs["kv_dtype"])
    if not rows:
        return None
    return metric_lib.pct(
        ref.kv_bytes_attended(ctx["cfg"], work, obs["kv_dtype"]), rows)
