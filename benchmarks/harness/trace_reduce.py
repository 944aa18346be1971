"""`.xplane.pb` → what the metrics read. Nothing here knows a model or
a cell; per-layer readers pick from the reduction by matchers they keep
as data.

    reduce_trace(path) -> {
      "window": (t0_ns, t1_ns),           # the traced window
      "devices": {device id: {
          "busy_s", "ops": {name: self seconds}, "events": [(name, cat,
          start_ns, dur_ns, self_ns)], "modules": [(name, start_ns, dur_ns)],
          "gaps": [(start_ns, dur_ns)]}},
      "busy_s": mean over devices, "window_s",
      "idle_gaps": [(host span name, seconds)],   # device 0's gaps
      "device_ops": [(name, seconds)]             # summed over devices
    }

Device planes are `/device:TPU:<n>`; their "XLA Ops" line holds one
event per executed HLO operation, named by its whole HLO text
(`%closed_call.259 = bf16[...] custom-call(...), custom_call_target=
"tpu_custom_call", ...`), containers such as `while` around their
bodies' events; "XLA Modules" holds one event per executed program.
Busy time is the union of the op intervals inside the window; an op's
seconds are its SELF time (its interval less the events nested in it),
so a `while` does not count its body twice.
The window is the host span named `WINDOW_SPAN` when the trace has one
(the drivers wrap the traced window in it), else first op to last op.
An idle gap is attributed to the host event that overlaps most of it.
"""
import glob
import os
import re

WINDOW_SPAN = "bench_traced_window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\.\d+)+$")
_UNSAFE = re.compile(r"[^A-Za-z0-9_.:/-]+")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(event_name, category=""):
    """A stable short name for an HLO op event: `custom-call:<target>`
    for a custom call (a Pallas kernel is `custom-call:tpu_custom_call`
    whatever jax named the instruction), else the instruction's name
    without its numeric suffix, under its category when the trace gives
    one."""
    target = _TARGET.search(event_name)
    if target:
        return "custom-call:" + _UNSAFE.sub("_", target.group(1))
    base = event_name.strip().lstrip("%").split(" = ")[0].split("(")[0]
    base = _UNSAFE.sub("_", _SUFFIX.sub("", base.split(".remat")[0]))
    return f"{category}:{base}" if category else base


def self_times(events):
    """[(start, dur)] properly nested on one line → self durations in
    the same order: each event's duration less its direct children's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_ns = [e[1] for e in events]
    stack = []
    for i in order:
        s, d = events[i]
        while stack and s >= stack[-1][1]:
            stack.pop()
        if stack:
            self_ns[stack[-1][0]] -= d
        stack.append((i, s + d))
    return [max(0, x) for x in self_ns]


def _union(intervals):
    """Merged [start, end) intervals and their total length."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged, sum(e - s for s, e in merged)


def _clip(s, e, t0, t1):
    s, e = max(s, t0), min(e, t1)
    return (s, e) if e > s else None


def reduce_trace(path, top=10, min_gap_ns=2000, max_gaps=400):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    raw, host = {}, []
    window = None
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dev = raw.setdefault(int(m.group(1)),
                                     {"XLA Ops": [], "XLA Modules": []})
                for ev in line.events:
                    dev[line.name].append((
                        ev.name, "", int(ev.start_ns),
                        int(ev.duration_ns)))
            elif not m and plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
                    elif ev.duration_ns > 0:
                        host.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     ev.name))
    if not raw or not any(d["XLA Ops"] for d in raw.values()):
        raise ValueError(f"{path}: no operation ran on a device")
    if window is None:
        ops = [e for d in raw.values() for e in d["XLA Ops"]]
        window = (min(e[2] for e in ops), max(e[2] + e[3] for e in ops))
    t0, t1 = window

    devices, total_ops = {}, {}
    for dev_id, d in sorted(raw.items()):
        ops, events, spans = {}, [], []
        clipped = []
        for name, cat, s, dur in d["XLA Ops"]:
            c = _clip(s, s + dur, t0, t1)
            if c is not None:
                clipped.append((name, cat, c[0], c[1] - c[0]))
        own = self_times([(e[2], e[3]) for e in clipped])
        for (name, cat, s, dur), self_ns in zip(clipped, own):
            key = op_name(name, cat)
            ops[key] = ops.get(key, 0.0) + self_ns / 1e9
            total_ops[key] = total_ops.get(key, 0.0) + self_ns / 1e9
            events.append((name, cat, s, dur, self_ns))
            spans.append((s, s + dur))
        merged, busy_ns = _union(spans)
        edges = [t0] + [x for se in merged for x in se] + [t1]
        gaps = [(edges[i], edges[i + 1] - edges[i])
                for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] >= min_gap_ns]
        modules = [(n, s, dur) for n, _c, s, dur in d["XLA Modules"]
                   if _clip(s, s + dur, t0, t1)]
        devices[dev_id] = {"busy_s": busy_ns / 1e9, "ops": ops,
                           "events": events, "modules": modules,
                           "gaps": gaps}
    first = devices[min(devices)]
    return {
        "window": window, "window_s": (t1 - t0) / 1e9,
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices.values())
        / len(devices),
        "device_ops": sorted(total_ops.items(),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": attribute_gaps(first["gaps"], host, top, max_gaps),
    }


def attribute_gaps(gaps, host_events, top=10, max_gaps=400):
    """[(host span name, idle seconds)]: each of the longest gaps goes
    to the host event overlapping most of it (the shorter event on a
    tie: the innermost span); the rest are `shorter_gaps`; a gap no
    host event overlaps is `unattributed`."""
    import numpy as np

    out = {}
    gaps = sorted(gaps, key=lambda g: -g[1])
    rest = sum(g[1] for g in gaps[max_gaps:])
    if rest:
        out["shorter_gaps"] = rest / 1e9
    if host_events:
        hs = np.array([h[0] for h in host_events], np.int64)
        he = np.array([h[1] for h in host_events], np.int64)
    for a, dur in gaps[:max_gaps]:
        b = a + dur
        name = "unattributed"
        if host_events:
            ov = np.minimum(he, b) - np.maximum(hs, a)
            best = ov.max()
            if best > 0:
                cand = np.flatnonzero(ov == best)
                i = cand[np.argmin((he - hs)[cand])]
                name = _UNSAFE.sub("_", host_events[i][2])[:64]
        out[name] = out.get(name, 0.0) + dur / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def matching_seconds(device, pattern, field="name"):
    """Σ seconds of one device's op events whose name (or category)
    matches the regex `pattern`; None when nothing matches."""
    rx = re.compile(pattern)
    idx = 0 if field == "name" else 1
    hits = [e[3] for e in device["events"] if rx.search(e[idx])]
    return sum(hits) / 1e9 if hits else None


def module_durations_ms(device, pattern):
    rx = re.compile(pattern)
    return [dur / 1e6 for n, _s, dur in device["modules"] if rx.search(n)]
