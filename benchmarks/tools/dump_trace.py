"""Look at a trace by hand before writing code against it:

    python benchmarks/tools/dump_trace.py <file.xplane.pb> [events per line]

Prints every plane, its lines with their event counts, and the first
events of each line with their statistics."""
import sys


def main(path, per_line=6):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            names = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            print(f"    most frequent names: {top}")
            for ev in events[:per_line]:
                try:
                    stats = {k: v for k, v in ev.stats}
                except Exception as e:  # noqa: BLE001
                    stats = {"<stats unreadable>": repr(e)}
                stats = {k: (str(v)[:80]) for k, v in stats.items()}
                print(f"    {ev.name[:100]!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} {stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
