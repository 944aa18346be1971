"""One run of one cell:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A new process: finds the cell in BENCHMARK.json and everything that
belongs to it by name (configs/<config>.json, traffic/<mix>.json, the
driver by the mix's `kind`, the builder by the configuration's
`builder`, the reference by its `reference`, limits/<workload>.json,
each per-layer metric's reader in layer_metrics/<name>.py), builds the
system from --seed, warms only that cell's shapes, measures for
--seconds, compares what the timed path produced with the plain
reference, and prints ONE JSON object as its last line. Earlier lines
(stderr) carry everything else.

What knows a model lives in three files chosen by name from the
configuration's file: the file itself, `builders/<builder>.py` and
`references/<reference>.py`. Of a configuration file the harness
reads: `builder`, `reference`; `vocab_size` (the rows held here: the
traffic draws its ids from it); `serve` (`weight_dtype`) with `engine`
(`num_slots`, `token_budget`, `decode_k`, `kv_dtype` ... the
deployment's choices) for a served cell, or `train` for one that
trains; `rehearse` (the overlay of `--rehearse-cpu`); and, for the
reader, `source`, `reduced`, `assumed`. Every other key is the
architecture's own, read by its builder and its reference alone.

It fails, with no result line, where JAX reports no TPU or fewer chips
than the cell asks for. `--rehearse-cpu` is a debugging aid: the same
control flow at each file's `rehearse` sizes on the CPU; it stamps
platform=cpu and prints counts only, never a time or a share.
"""
import time

T0 = time.perf_counter()      # process start, as near as Python lets us

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg):
    print(f"[bench {time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def assign(tree, spec):
    path, _, val = spec.partition("=")
    *parents, last = path.split(".")
    for k in parents:
        tree = tree.setdefault(k, {})
    tree[last] = json.loads(val)


def find_cell(manifest, name):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return cell, cfg_entry


def metrics_of(manifest, group, cell_name):
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def device_report(devices, chips):
    peak = None
    for d in devices[:chips]:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peak = max(peak or 0, int(st["peak_bytes_in_use"]))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the harness on the CPU at the files' "
                         "`rehearse` sizes; proves nothing about the chip")
    ap.add_argument("--control", default=None,
                    help="builder's tool, never used by a check: also "
                         "put the reference in this lower precision "
                         "(int8, fp8) in the program's place and print "
                         "the numbers it reads")
    ap.add_argument("--fault", default=None, choices=("half_batch",),
                    help="builder's tool: also read a planted fault in "
                         "the reference put in the program's place")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="builder's tool: copy the run's .xplane.pb here")
    ap.add_argument("--cfg-set", action="append", default=[],
                    metavar="a.b=json", help="builder's tool (sweeps): "
                    "override one key of the configuration file")
    ap.add_argument("--mix-set", action="append", default=[],
                    metavar="a.b=json", help="builder's tool (sweeps): "
                    "override one key of the traffic mix")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, cfg_entry = find_cell(manifest, args.workload)
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    import references
    from harness import check, peaks, trace_reduce, traffic

    mix = traffic.load_mix(os.path.join(HERE, "traffic"), cell["traffic"])
    with open(os.path.join(HERE, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)
    if args.rehearse_cpu:
        cfg = overlay(cfg, cfg.get("rehearse", {}))
        mix = overlay(mix, mix.get("rehearse", {}))
    for spec in args.cfg_set:
        assign(cfg, spec)
    for spec in args.mix_set:
        assign(mix, spec)
    if args.cfg_set or args.mix_set:
        log(f"OVERRIDDEN (not the committed cell): {args.cfg_set} "
            f"{args.mix_set}")
    driver = load_module(
        os.path.join(HERE, "drivers", mix["kind"] + ".py"),
        "drivers." + mix["kind"])
    ref = references.load(cfg["reference"],
                          training=driver.COMPARES == "trained")

    # the compile cache: where the environment says, else a fixed path
    # inside the checkout (the program's own default is the same path)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    log(f"jax {jax.__version__}: platform={platform} device_kind="
        f"{devices[0].device_kind!r} count={len(devices)}; cell "
        f"{cell['name']} = {cell['config']} x {cell['traffic']} on "
        f"{cell['chips']} chip(s), seed {args.seed}")
    if args.rehearse_cpu:
        if platform != "cpu":
            raise SystemExit("--rehearse-cpu runs on the CPU only")
    elif platform != "tpu" or len(devices) < int(cell["chips"]):
        raise SystemExit(
            f"cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX "
            f"found {len(devices)} x {platform}. Nothing is measured on "
            "anything else (--rehearse-cpu debugs the harness itself).")
    chip_peaks = None if args.rehearse_cpu else peaks.peaks_for(
        devices[0].device_kind)

    from paddle_tpu.core import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    log("traffic: " + json.dumps(traffic.summary(mix, args.seconds)))

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)

    builder = load_module(
        os.path.join(HERE, "builders", cfg["builder"] + ".py"),
        "bench_builder_" + cfg["builder"])
    handle = builder.build(cfg, args.seed, mix["kind"])
    log("system built")
    timing = {}

    def window_opened(t):
        timing["setup_s"] = t - T0
        log(f"window opens; set-up {timing['setup_s']:.2f}s")

    out = driver.run({
        "handle": handle, "cfg": cfg, "mix": mix, "cell": cell,
        "seed": args.seed, "seconds": args.seconds, "log": log,
        "trace_dir": trace_dir, "window_opened": window_opened})
    device = device_report(devices, int(cell["chips"]))
    log(f"window closed; memory_peak_bytes {device['memory_peak_bytes']}")

    # the kernels the timed steps were lowered with
    kernels_ok = True
    calls = (handle.custom_calls() if out["check"]["kind"] == "served"
             else {"train": handle.custom_calls(
                 out["check"]["custom_calls_batch"])})
    log(f"custom calls in the lowered steps: {calls}")
    if not args.rehearse_cpu:
        kernels_ok = all(c.get("tpu_custom_call", 0) > 0
                         for c in calls.values())
    tree_position = getattr(builder, "tree_position", None)
    handle.free()
    handle = None
    gc.collect()

    # ---- correct: the timed path's output against the reference ----
    t_ref = time.perf_counter()
    chk = out["check"]
    extra = {}
    if chk["kind"] == "served":
        numbers = check.served_numbers(ref, cfg, args.seed,
                                       chk["sample"], chk["rows_to"])
        numbers["malformed_answers"] = chk["malformed"]
        limits = dict(limits, malformed_answers=0)
        if args.control:
            extra["control_" + args.control] = check.served_numbers(
                ref, cfg, args.seed, chk["sample"], chk["rows_to"],
                quant=args.control)
    else:
        want = check.reference_training(ref, cfg, args.seed,
                                        chk["batches"])
        numbers = check.trained_numbers(
            want, check.program_training(ref, chk, tree_position, want))
        if args.control:
            extra["control_" + args.control] = check.trained_numbers(
                want, check.reference_training(
                    ref, cfg, args.seed, chk["batches"],
                    quant=args.control))
        if args.fault == "half_batch":
            half = list(range(len(chk["batches"][0]) // 2))
            extra["fault_half_batch"] = check.trained_numbers(
                want, check.reference_training(
                    ref, cfg, args.seed, chk["batches"], keep_rows=half))
    for name, nums in extra.items():
        log(f"{name}: {json.dumps(nums)}")
    worst = numbers.pop("_worst", None)
    if worst:
        log(f"worst leaves: {json.dumps(worst)}")
    for nums in extra.values():
        nums.pop("_worst", None)
    rows = check.judge(numbers, limits)
    correct = kernels_ok and all(ok for *_x, ok in rows) \
        and out["failed"] == 0
    log(f"reference and comparison {time.perf_counter() - t_ref:.1f}s; "
        f"all numbers {json.dumps(numbers)}")

    # ---- metrics ---------------------------------------------------
    metrics, breakdown = {}, None
    if args.trace:
        reduction = None
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.find_xplane(trace_dir),
                        os.path.join(args.keep_trace,
                                     cell["name"] + ".xplane.pb"))
        if not args.rehearse_cpu:
            reduction = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(trace_dir))
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
            breakdown = {
                "device_ops": [[n, s] for n, s in reduction["device_ops"]],
                "idle_gaps": [[n, s] for n, s in reduction["idle_gaps"]]}
        mctx = {"obs": out["obs"], "trace": reduction, "cfg": cfg,
                "chips": int(cell["chips"]), "peaks": chip_peaks,
                "cell": cell, "ref": ref}
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            if args.rehearse_cpu and m["source"] != "program_counter":
                continue
            reader = load_module(
                os.path.join(HERE, "layer_metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            val = reader.read(mctx)
            if val is not None:
                metrics[m["name"]] = {"value": float(val),
                                      "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif not args.rehearse_cpu:
        e2e = dict(out["end_to_end"], setup_s=timing["setup_s"])
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    compared = {n: {"value": v, "limit": lim, "ok": ok}
                for n, v, lim, ok in rows}
    compared["pallas_kernel_in_timed_steps"] = {
        "value": int(kernels_ok), "limit": 1, "ok": kernels_ok}
    compared["failed_operations"] = {
        "value": out["failed"], "limit": 0, "ok": out["failed"] == 0}
    for n, c in compared.items():
        print(f"compared {n}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse_cpu:
        result["rehearsal"] = True
    if extra:
        result["builder_readings"] = extra
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
