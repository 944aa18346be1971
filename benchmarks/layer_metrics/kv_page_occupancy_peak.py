"""kv_page_occupancy_peak: highest share of the page pool in use, polled at
every step boundary of the run.
"""


def read(ctx):
    peak = ctx["obs"].get("page_occ_peak")
    return None if peak is None else 100.0 * peak
