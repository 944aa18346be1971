"""The traced window's `.xplane.pb` → what the span and scope metrics
read: the program's own spans with their args (host plane), the step
programs of device 0, and every device operation's scope with its self
time. Read once a run (`reduction(ctx)`); per-layer readers pick from
it by names they keep as data.

    reduction(ctx) -> None | {
      "window": (t0_ns, t1_ns), "busy_s": mean over devices,
      "spans": [(name, start_ns, end_ns, {arg: value})],   # host events
                                    # over the window, by start
      "modules": [(name, start_ns, dur_ns)],    # device 0, by start
      "scopes": {word or "": self seconds},     # mean over devices
      "scoped": True when any operation carries an `op_name` at all
    }

Where the scopes come from. The TPU profiler writes each operation's
HLO `op_name` (`jit(step)/transpose(jvp(mlp))/dot_general:`) as the
statistic `tf_op` on the event's METADATA, which
`jax.profiler.ProfileData` does not expose (it shows an event's own
statistics only). So the file is parsed against tsl's `xplane.proto`,
declared below, with `protobuf` alone. An operation belongs to the
innermost word of VOCABULARY on its path (a backward operation's
segment reads `transpose(jvp(<word>))`); a fusion has the `op_name` the
compiler gave it, its root's or its hero's; an operation without
`op_name` (the compiler's own copies and slices) or with no word on
its path counts under "". Seconds are SELF time inside the window, as
in `trace_reduce`.
"""
import functools
import gzip
import re
import statistics

from . import trace_reduce

# the program's named scopes (paddle_tpu/text/models/gpt.py and
# jit.TrainStep._build), as data
VOCABULARY = ("embed", "attn", "mlp", "norm", "lm_head", "loss",
              "optimizer", "sample")
SCOPE_STAT = "tf_op"

_SEGMENT = re.compile(r"(?:\w+\()*(\w+)\)*")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

# ---- tsl/profiler/protobuf/xplane.proto, declared --------------------
_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11
_SCHEMA = {
    "XSpace": [("planes", 1, _MESSAGE, "XPlane", True),
               ("errors", 2, _STRING, None, True),
               ("warnings", 3, _STRING, None, True),
               ("hostnames", 4, _STRING, None, True)],
    "XPlane": [("id", 1, _INT64, None, False),
               ("name", 2, _STRING, None, False),
               ("lines", 3, _MESSAGE, "XLine", True),
               ("event_metadata", 4, _MESSAGE, "EventMetadataEntry", True),
               ("stat_metadata", 5, _MESSAGE, "StatMetadataEntry", True),
               ("stats", 6, _MESSAGE, "XStat", True)],
    "EventMetadataEntry": [("key", 1, _INT64, None, False),
                           ("value", 2, _MESSAGE, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _INT64, None, False),
                          ("value", 2, _MESSAGE, "XStatMetadata", False)],
    "XLine": [("id", 1, _INT64, None, False),
              ("display_id", 10, _INT64, None, False),
              ("name", 2, _STRING, None, False),
              ("display_name", 11, _STRING, None, False),
              ("timestamp_ns", 3, _INT64, None, False),
              ("duration_ps", 9, _INT64, None, False),
              ("events", 4, _MESSAGE, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _INT64, None, False),
               ("offset_ps", 2, _INT64, None, False),
               ("num_occurrences", 5, _INT64, None, False),
               ("duration_ps", 3, _INT64, None, False),
               ("stats", 4, _MESSAGE, "XStat", True)],
    "XStat": [("metadata_id", 1, _INT64, None, False),
              ("double_value", 2, _DOUBLE, None, False),
              ("uint64_value", 3, _UINT64, None, False),
              ("int64_value", 4, _INT64, None, False),
              ("str_value", 5, _STRING, None, False),
              ("bytes_value", 6, _BYTES, None, False),
              ("ref_value", 7, _UINT64, None, False)],
    "XEventMetadata": [("id", 1, _INT64, None, False),
                       ("name", 2, _STRING, None, False),
                       ("display_name", 4, _STRING, None, False),
                       ("metadata", 3, _BYTES, None, False),
                       ("stats", 5, _MESSAGE, "XStat", True),
                       ("child_id", 6, _INT64, None, True)],
    "XStatMetadata": [("id", 1, _INT64, None, False),
                      ("name", 2, _STRING, None, False),
                      ("description", 3, _STRING, None, False)],
}
_VALUE_FIELDS = ("str_value", "int64_value", "uint64_value",
                 "double_value", "ref_value", "bytes_value")


@functools.lru_cache(maxsize=1)
def xspace_class():
    """The XSpace message class, built from `_SCHEMA`."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    pkg = "perfbench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package=pkg, syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, number, ftype, type_name, repeated in fields:
            f = m.field.add(name=name, number=number, type=ftype,
                            label=3 if repeated else 1)
            if type_name:
                f.type_name = f".{pkg}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))


def load_xspace(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = xspace_class()()
        space.ParseFromString(f.read())
    return space


def stat_value(stat, stat_names=None):
    """(field, value) of an XStat; a `ref_value` is looked up in the
    plane's stat names when they are given."""
    for field in _VALUE_FIELDS:
        v = getattr(stat, field)
        if v:
            if field == "ref_value" and stat_names is not None:
                return "str_value", stat_names.get(v, "")
            return field, v
    return "int64_value", 0


# ---- scopes ----------------------------------------------------------

def scope_of(op_name, vocabulary=VOCABULARY):
    """The innermost vocabulary word on an `op_name` path, else ""."""
    word = ""
    for seg in op_name.rstrip(":").split("/"):
        m = _SEGMENT.fullmatch(seg)
        if m and m.group(1) in vocabulary:
            word = m.group(1)
    return word


def scope_seconds(events, op_names, window):
    """{word or "": self seconds} of one device's op events
    [(metadata id, start, dur)] inside `window`; `op_names` maps a
    metadata id to its `op_name` (absent: no name at all)."""
    t0, t1 = window
    clipped = []
    for mid, s, dur in events:
        c = trace_reduce._clip(s, s + dur, t0, t1)
        if c is not None:
            clipped.append((mid, c[0], c[1] - c[0]))
    own = trace_reduce.self_times([(e[1], e[2]) for e in clipped])
    words, out = {}, {}
    for (mid, _s, _d), self_ns in zip(clipped, own):
        if mid not in words:
            words[mid] = scope_of(op_names.get(mid, ""))
        w = words[mid]
        out[w] = out.get(w, 0.0) + self_ns / 1e9
    return out


# ---- the step programs -----------------------------------------------

def turnarounds_ms(modules, pattern):
    """Idle milliseconds between the end of one step program (a module
    matching `pattern`) and the start of the next, less the time of
    the other programs that ran in between."""
    rx = re.compile(pattern)
    out, prev_end, between = [], None, 0
    for name, start, dur in sorted(modules, key=lambda m: m[1]):
        if not rx.search(name):
            between += dur
            continue
        if prev_end is not None:
            out.append(max(0, start - prev_end - between) / 1e6)
        prev_end, between = start + dur, 0
    return out


# ---- the file --------------------------------------------------------

def reduce_xspace(space):
    spans, per_device, window = [], {}, None
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            scope_id = next((k for k, v in stat_names.items()
                             if v == SCOPE_STAT), None)
            op_names = {}
            for e in plane.event_metadata:
                for st in e.value.stats:
                    if st.metadata_id == scope_id:
                        op_names[e.key] = stat_value(st, stat_names)[1]
            dev = per_device.setdefault(
                int(m.group(1)), {"ops": [], "modules": [],
                                  "op_names": op_names})
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    start = (base + ev.offset_ps) // 1000
                    dur = ev.duration_ps // 1000
                    if line.name == "XLA Ops":
                        dev["ops"].append((ev.metadata_id, start, dur))
                    else:
                        dev["modules"].append(
                            (names.get(ev.metadata_id, ""), start, dur))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for ev in line.events:
                    name = names.get(ev.metadata_id, "")
                    start = (base + ev.offset_ps) // 1000
                    end = start + ev.duration_ps // 1000
                    if name == trace_reduce.WINDOW_SPAN:
                        window = (start, end)
                    elif end > start:
                        spans.append((name, start, end, {
                            stat_names.get(st.metadata_id, "?"):
                            stat_value(st, stat_names)[1]
                            for st in ev.stats}))
    if not per_device:
        return None
    if window is None:
        ops = [e for d in per_device.values() for e in d["ops"]]
        window = (min(e[1] for e in ops), max(e[1] + e[2] for e in ops))
    t0, t1 = window
    scopes, busy = {}, 0.0
    for dev in per_device.values():
        secs = scope_seconds(dev["ops"], dev["op_names"], window)
        busy += sum(secs.values())
        for w, s in secs.items():
            scopes[w] = scopes.get(w, 0.0) + s / len(per_device)
    first = per_device[min(per_device)]
    return {
        "window": window,
        "busy_s": busy / len(per_device),
        "spans": sorted((s for s in spans if s[2] > t0 and s[1] < t1),
                        key=lambda s: s[1]),
        "modules": sorted(
            ((n, c[0], c[1] - c[0]) for n, s, d in first["modules"]
             for c in [trace_reduce._clip(s, s + d, t0, t1)] if c),
            key=lambda mod: mod[1]),
        "scopes": scopes,
        "scoped": any(d["op_names"] for d in per_device.values()),
    }


@functools.lru_cache(maxsize=1)
def reduce_file(path):
    return reduce_xspace(load_xspace(path))


def reduction(ctx):
    """The reduction of this run's traced window; None without one
    (no traced run, or no device plane in it)."""
    trace_dir = ctx["obs"].get("trace_dir")
    if not trace_dir:
        return None
    try:
        return reduce_file(trace_reduce.find_xplane(trace_dir))
    except FileNotFoundError:
        return None


# ---- what the readers compute ----------------------------------------

def scope_time_share(ctx, scopes):
    """Self seconds of the operations whose scope is one of `scopes`
    ("" = no word) over the device's busy seconds, in %. None where the
    trace names no operation at all, or none of these scopes."""
    red = reduction(ctx)
    if red is None or not red["scoped"] or not red["busy_s"]:
        return None
    hit = [red["scopes"][w] for w in scopes if w in red["scopes"]]
    if not hit:
        return None
    return 100.0 * sum(hit) / red["busy_s"]


def step_turnaround_ms_p50(ctx, module):
    red = reduction(ctx)
    if red is None:
        return None
    gaps = turnarounds_ms(red["modules"], module)
    return statistics.median(gaps) if gaps else None
