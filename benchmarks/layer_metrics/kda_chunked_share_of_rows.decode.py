"""kda_chunked_share_of_rows.decode: of the query rows that went through
the gated delta-rule layers in the window, the share that took the
CHUNKED form (the program's counters `kda_rows_chunked` and
`kda_rows_recurrent`, rows × layers). It follows the traffic's prefill
share and the run length at which the forms change over.
"""
from harness import metric_lib


def read(ctx):
    stats = ctx["obs"].get("window", {}).get("stats") or {}
    if "kda_rows_chunked" not in stats:
        return None
    chunked = stats["kda_rows_chunked"]
    return metric_lib.pct(chunked, chunked + stats["kda_rows_recurrent"])
