"""Device time by the WORDS on an operation's scope path, for readers
whose scopes are not among `span_reduce.VOCABULARY`'s innermost words.

`span_reduce` gives every operation to ONE word, the innermost of its
fixed vocabulary. A model with further scopes nests them under those
words (`mlp/moe/moe_experts`, `attn/attn_window/rope`), so the older
readers still place every operation; a reader of the further scopes
asks here for the seconds of the operations whose path HOLDS a word,
whatever lies inside it, optionally only those whose HLO text matches a
pattern (a kernel's custom call). Same file, same window, same self
time as `span_reduce` (whose parser this uses); read once a run.

    seconds(ctx, words, event=None) -> None | float
        mean over the devices of the self seconds, inside the traced
        window, of the operations with one of `words` on their path
        (and `event` matching their HLO text); None where the trace
        has no such operation
    share(ctx, words, event=None) -> None | that, as % of the device's
        busy seconds
"""
import functools
import re

from . import span_reduce, trace_reduce


def words_of(op_name):
    """The scope words on an `op_name` path, outermost first."""
    out = []
    for seg in op_name.rstrip(":").split("/"):
        m = span_reduce._SEGMENT.fullmatch(seg)
        if m:
            out.append(m.group(1))
    return out


@functools.lru_cache(maxsize=1)
def reduce_file(path):
    """{"busy_s", "ops": [(frozenset of path words, HLO text, self
    seconds / devices)]} of the traced window."""
    space = span_reduce.load_xspace(path)
    devices, window = [], None
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        if span_reduce._DEVICE_PLANE.match(plane.name):
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            scope_id = next((k for k, v in stat_names.items()
                             if v == span_reduce.SCOPE_STAT), None)
            op_names = {}
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if st.metadata_id == scope_id:
                        op_names[e.key] = span_reduce.stat_value(
                            st, stat_names)[1]
            events = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    events.append((ev.metadata_id,
                                   (base + ev.offset_ps) // 1000,
                                   ev.duration_ps // 1000))
            devices.append((events, op_names, names))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    if names.get(ev.metadata_id) == \
                            trace_reduce.WINDOW_SPAN:
                        start = (base + ev.offset_ps) // 1000
                        window = (start, start + ev.duration_ps // 1000)
    if not devices:
        return None
    if window is None:
        every = [e for evs, _o, _n in devices for e in evs]
        window = (min(e[1] for e in every),
                  max(e[1] + e[2] for e in every))
    ops, busy = [], 0.0
    for events, op_names, names in devices:
        clipped = []
        for mid, s, dur in events:
            c = trace_reduce._clip(s, s + dur, *window)
            if c is not None:
                clipped.append((mid, c[0], c[1] - c[0]))
        own = trace_reduce.self_times([(e[1], e[2]) for e in clipped])
        for (mid, _s, _d), self_ns in zip(clipped, own):
            secs = self_ns / 1e9 / len(devices)
            busy += secs
            ops.append((frozenset(words_of(op_names.get(mid, ""))),
                        names.get(mid, ""), secs))
    return {"busy_s": busy, "ops": ops}


def reduction(ctx):
    trace_dir = ctx["obs"].get("trace_dir")
    if not trace_dir:
        return None
    try:
        return reduce_file(trace_reduce.find_xplane(trace_dir))
    except FileNotFoundError:
        return None


def seconds(ctx, words, event=None):
    red = reduction(ctx)
    if red is None:
        return None
    rx = re.compile(event) if event else None
    hits = [s for path, text, s in red["ops"]
            if path & set(words) and (rx is None or rx.search(text))]
    return sum(hits) if hits else None


def share(ctx, words, event=None):
    red = reduction(ctx)
    secs = seconds(ctx, words, event)
    if secs is None or not red["busy_s"]:
        return None
    return 100.0 * secs / red["busy_s"]
