"""Cut a recorded `.xplane.pb` down to a fixture small enough to keep:

    python benchmarks/tools/trim_trace.py <in.xplane.pb> <out.xplane.pb.gz> \
        [--seconds 0.26] [--skip 0.0]

Keeps what the reductions read (`harness/trace_reduce.py`,
`harness/span_reduce.py`) and nothing else: the planes `/device:TPU:<n>`
(lines "XLA Modules" and "XLA Ops") and `/host:CPU`, the events that
start inside [window start + skip, + seconds) of the
`bench_traced_window` span, that span itself shortened to the kept
part, and of the statistics only those named in KEEP_STATS (a span's
args, an operation's name path). Times and names stay as the profiler
wrote them. The file is parsed against the schema that
`harness/span_reduce.py` declares, so the tool needs nothing but
`protobuf`.
"""
import argparse
import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from harness.span_reduce import load_xspace, stat_value  # noqa: E402
from harness.trace_reduce import WINDOW_SPAN  # noqa: E402

DEVICE_LINES = ("XLA Modules", "XLA Ops")
# statistics worth their bytes: what a reduction reads
KEEP_STATS = ("tf_op", "rows", "prefill_tokens", "decode_tokens", "k",
              "waiting", "admitted", "step")


def trim(space, seconds, skip=0.0, keep_stats=KEEP_STATS):
    """A new XSpace holding the kept part of `space`."""
    window = None
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                if names.get(ev.metadata_id) == WINDOW_SPAN:
                    window = line.timestamp_ns * 1000 + ev.offset_ps
    if window is None:
        raise SystemExit(f"no {WINDOW_SPAN} span in the trace")
    a = window + int(skip * 1e12)
    b = a + int(seconds * 1e12)
    out = type(space)()
    out.hostnames.extend(space.hostnames)
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        ev_meta = {e.key: e.value for e in plane.event_metadata}
        st_meta = {e.key: e.value for e in plane.stat_metadata}
        stat_names = {k: v.name for k, v in st_meta.items()}
        keep_ids = {k for k, v in stat_names.items() if v in keep_stats}
        new = out.planes.add(id=plane.id, name=plane.name)
        used_ev, used_st = set(), set()

        def copy_stats(src, dst):
            for st in src:
                if st.metadata_id not in keep_ids:
                    continue
                # a reference to a string kept as a stat name is stored
                # as the string itself
                field, val = stat_value(st, stat_names)
                dst.add(metadata_id=st.metadata_id, **{field: val})
                used_st.add(st.metadata_id)

        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            base = line.timestamp_ns * 1000
            kept = []
            for ev in line.events:
                start = base + ev.offset_ps
                is_window = ev_meta[ev.metadata_id].name == WINDOW_SPAN
                if is_window or a <= start < b:
                    kept.append((ev, is_window))
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               display_name=line.display_name,
                               timestamp_ns=line.timestamp_ns)
            for ev, is_window in kept:
                ne = nl.events.add(metadata_id=ev.metadata_id,
                                   offset_ps=ev.offset_ps,
                                   duration_ps=ev.duration_ps)
                if is_window:
                    ne.offset_ps = a - base
                    ne.duration_ps = b - a
                copy_stats(ev.stats, ne.stats)
                used_ev.add(ev.metadata_id)
        for k in sorted(used_ev):
            src = ev_meta[k]
            entry = new.event_metadata.add(key=k)
            entry.value.id = src.id
            entry.value.name = src.name
            entry.value.display_name = src.display_name
            copy_stats(src.stats, entry.value.stats)
        for k in sorted(used_st):
            entry = new.stat_metadata.add(key=k)
            entry.value.id = st_meta[k].id
            entry.value.name = st_meta[k].name
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--seconds", type=float, default=0.26)
    ap.add_argument("--skip", type=float, default=0.0)
    args = ap.parse_args(argv)
    out = trim(load_xspace(args.src), args.seconds, args.skip)
    data = out.SerializeToString()
    with gzip.GzipFile(args.dst, "wb", mtime=0) as f:
        f.write(data)
    print(f"{args.dst}: {len(data)} bytes before gzip, "
          f"{sum(len(ln.events) for p in out.planes for ln in p.lines)} "
          "events", file=sys.stderr)


if __name__ == "__main__":
    main()
