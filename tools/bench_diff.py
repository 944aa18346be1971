#!/usr/bin/env python
"""Machine-check the BENCH_*.json trajectory: diff two bench stamps.

The repo's perf history is a series of ``BENCH_r<NN>.json`` stamps that
until now only humans read — a regression between two captures was
whatever a reviewer happened to notice. This tool is the sentinel:

    python tools/bench_diff.py BENCH_r03.json BENCH_r04.json
    python tools/bench_diff.py .            # latest vs previous in a dir
    python tools/bench_diff.py old new --tol 0.05

Every numeric leaf of the stamp's detail tree becomes a dotted metric
path. Direction is inferred from the metric name (``mfu`` / ``ips`` /
``tok_s`` / ``*_per_s`` / hit rates are higher-better; ``*_ms`` /
``*_s`` / percentiles / byte counts are lower-better; anything
unrecognized is reported but never gated). A metric regresses when it
moves past the tolerance band (``--tol``, relative, default 10%, plus
an absolute floor ``--abs-tol`` so micro-noise near zero never trips).

Honesty rules, enforced before any comparison:

* stamps from different devices are NEVER compared — the `device`
  record (platform, device_kind, count) must be equal, and an old
  cpu_fallback capture vs a chip capture is apples-to-oranges: exit 2
  (not-comparable), not 0 or 1;
* a stamp whose payload is missing (the driver-shell ``parsed: null``
  of a timed-out capture) also exits 2 — "no data" must not read as
  "no regression".

Exit codes: 0 within tolerance, 1 regression(s), 2 not comparable.
Stdlib only; tests/test_bench_diff.py pins the semantics on synthetic
stamp pairs.
"""
import argparse
import glob
import json
import os
import sys

# metric-name rules → direction. Rates (a *_per_s suffix) are checked
# before the unit words, so "bytes_per_s" is a higher-better bandwidth
# while a bare "bytes" payload count is lower-better. Unmatched
# metrics are informational only — never gated.
_HIGHER_SUFFIX = ("per_sec", "per_second", "per_s", "tok_s",
                  "vs_baseline", "hit_rate", "hit_ratio")
_HIGHER_PARTS = frozenset(("mfu", "ips", "speedup", "reduction",
                           "capacity", "acceptance", "goodput"))
_LOWER_PARTS = frozenset(("ms", "s", "us", "seconds", "p50", "p90",
                          "p95", "p99", "ttft", "latency", "stall",
                          "overhead", "bytes", "compile", "compiles",
                          "recompiles", "executables", "delta", "loss",
                          "ratio"))


def direction_of(path):
    """'higher' / 'lower' / None (ungated) for a dotted metric path."""
    leaf = path.rsplit(".", 1)[-1].lower().replace("-", "_")
    parts = set(leaf.split("_"))
    if any(leaf.endswith(sfx) for sfx in _HIGHER_SUFFIX) or \
            parts & _HIGHER_PARTS:
        return "higher"
    if parts & _LOWER_PARTS:
        return "lower"
    return None


def load_stamp(path):
    """A stamp's headline dict, unwrapping the capture driver's shell
    ({n, cmd, rc, tail, parsed}). Returns (stamp_or_None, reason)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc and "cmd" in doc:
        if doc.get("parsed") is None:
            return None, (f"{os.path.basename(path)}: capture shell has "
                          f"parsed=null (rc={doc.get('rc')}) — no data")
        doc = doc["parsed"]
    if not isinstance(doc, dict):
        return None, f"{os.path.basename(path)}: not a stamp object"
    return doc, None


def flatten(obj, prefix=""):
    """Numeric leaves of a nested dict/list as {dotted.path: float}.
    Booleans and strings are identity/config, not metrics — skipped."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}.{k}" if prefix else str(k)))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}.{i}" if prefix else str(i)))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix] = float(obj)
    return out


def diff(old, new, tol=0.10, abs_tol=1e-9):
    """Compare two headline stamps. Returns a report dict:
    {"comparable", "reason", "backend", "rows", "regressions",
    "improvements"} — rows only for metrics present in BOTH stamps."""
    # what the stamp ran on: the `device` record bench.py writes since
    # PR 21 (platform, device_kind, count), or the `backend` word of the
    # older stamps
    b_old = old.get("device") or old.get("backend")
    b_new = new.get("device") or new.get("backend")
    if b_old != b_new:
        return {"comparable": False,
                "reason": f"backend mismatch: {b_old!r} vs {b_new!r} — "
                          "captures from different devices (or an old "
                          "cpu_fallback capture and a chip capture) "
                          "never compare",
                "backend": (b_old, b_new), "rows": [],
                "regressions": [], "improvements": []}
    f_old = flatten(old)
    f_new = flatten(new)
    rows, regressions, improvements = [], [], []
    for path in sorted(set(f_old) & set(f_new)):
        a, b = f_old[path], f_new[path]
        d = direction_of(path)
        delta = b - a
        rel = delta / abs(a) if a else (0.0 if not delta else float("inf"))
        row = {"metric": path, "old": a, "new": b, "delta": delta,
               "rel": rel, "direction": d, "verdict": "ok"}
        band = tol * abs(a) + abs_tol
        if d == "lower" and delta > band:
            row["verdict"] = "regression"
        elif d == "higher" and -delta > band:
            row["verdict"] = "regression"
        elif d is not None and abs(delta) > band:
            row["verdict"] = "improvement"
        elif d is None:
            row["verdict"] = "ungated"
        if row["verdict"] == "regression":
            regressions.append(row)
        elif row["verdict"] == "improvement":
            improvements.append(row)
        rows.append(row)
    return {"comparable": True, "reason": None, "backend": (b_old, b_new),
            "rows": rows, "regressions": regressions,
            "improvements": improvements}


def pick_pair(directory):
    """(previous, latest) BENCH_*.json in a directory, by name order
    (the r<NN> capture numbering is the trajectory order)."""
    stamps = sorted(glob.glob(os.path.join(directory, "BENCH_*.json")))
    if len(stamps) < 2:
        return None
    return stamps[-2], stamps[-1]


def _fmt(v):
    return f"{v:.6g}"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("inputs", nargs="+",
                    help="two stamp files, or one directory holding "
                         "BENCH_*.json (latest vs previous)")
    ap.add_argument("--tol", type=float, default=0.10,
                    help="relative tolerance band (default 0.10)")
    ap.add_argument("--abs-tol", type=float, default=1e-9,
                    help="absolute band floor (default 1e-9)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the full report as JSON")
    args = ap.parse_args(argv)

    if len(args.inputs) == 1 and os.path.isdir(args.inputs[0]):
        pair = pick_pair(args.inputs[0])
        if pair is None:
            print("need at least two BENCH_*.json stamps to diff",
                  file=sys.stderr)
            return 2
        old_path, new_path = pair
    elif len(args.inputs) == 2:
        old_path, new_path = args.inputs
    else:
        print("expected two stamp files or one directory",
              file=sys.stderr)
        return 2

    old, why = load_stamp(old_path)
    if old is None:
        print(f"not comparable: {why}", file=sys.stderr)
        return 2
    new, why = load_stamp(new_path)
    if new is None:
        print(f"not comparable: {why}", file=sys.stderr)
        return 2

    report = diff(old, new, tol=args.tol, abs_tol=args.abs_tol)
    report["old"] = os.path.basename(old_path)
    report["new"] = os.path.basename(new_path)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)
    if not report["comparable"]:
        print(f"not comparable: {report['reason']}", file=sys.stderr)
        return 2
    print(f"{report['old']} -> {report['new']} "
          f"(backend={report['backend'][0]}, tol={args.tol:.0%})")
    for row in report["rows"]:
        if row["verdict"] == "ok" or (
                row["verdict"] == "ungated" and not row["delta"]):
            continue
        mark = {"regression": "✗", "improvement": "✓",
                "ungated": "·"}[row["verdict"]]
        print(f"  {mark} {row['metric']}: {_fmt(row['old'])} -> "
              f"{_fmt(row['new'])} ({row['rel']:+.1%}) "
              f"[{row['verdict']}]")
    n_reg = len(report["regressions"])
    print(f"{len(report['rows'])} shared metric(s), {n_reg} "
          f"regression(s), {len(report['improvements'])} improvement(s)")
    return 1 if n_reg else 0


if __name__ == "__main__":
    sys.exit(main())
