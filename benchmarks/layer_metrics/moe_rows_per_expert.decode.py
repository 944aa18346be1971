"""moe_rows_per_expert.decode: rows a held expert saw when it was
touched: the program's counters `moe_assignments_held` over
`moe_experts_touched` (both summed over sparse layers and iterations of
the window).
"""


def read(ctx):
    stats = ctx["obs"].get("window", {}).get("stats") or {}
    touched = stats.get("moe_experts_touched")
    if not touched or "moe_assignments_held" not in stats:
        return None
    return stats["moe_assignments_held"] / touched
