"""A checkout with one more architecture, laid out as a change that adds
a model lays it out: the configuration, builder, reference and readers
of `data/newarch/` added as NEW files, and its entries APPENDED to
BENCHMARK.json (see conftest.py and test_perfbench_manifest.py)."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEWARCH = os.path.join(ROOT, "tests", "benchmarks", "data", "newarch")
CONFIG = "hf-decoder"
CELL = "newarch_decode"
# the cell's own readers: name -> (unit, source, layer)
READERS = {
    "newarch_serve_flops": ("count", "program_counter", "whole model step"),
    "newarch_processed": ("count", "program_counter", "whole model step"),
    "newarch_attended": ("count", "program_counter", "whole model step"),
    "newarch_paged_attn_roofline.decode": (
        "%", "device_trace", "paged attention kernel"),
}


def manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cells_of(m, metric):
    """The cells that report the end-to-end metric `metric`."""
    return next(x for x in m["end_to_end"] if x["name"] == metric)[
        "workloads"]


def copy_of_the_benchmark(root):
    """`benchmarks/` copied into `root`, the program linked beside
    it; returns the copy's path."""
    b = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    return b


def with_a_new_architecture(m):
    """`m` with the new configuration and its decode cell appended, the
    cell listed under `decode_tok_s` and under every reader that every
    decode cell shares, and its own readers appended after all the
    others."""
    with open(os.path.join(NEWARCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    decode = set(cells_of(m, "decode_tok_s"))
    m["configs"].append({
        "name": CONFIG, "source": cfg["source"],
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": [],
        "why": "a second architecture's keys"})
    m["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "decode_heavy",
        "chips": 1, "why": "a second architecture's keys"})
    cells_of(m, "decode_tok_s").append(CELL)
    for x in m["per_layer"]:
        if decode <= set(x.get("workloads", ())):
            x["workloads"].append(CELL)
    for name, (unit, source, layer) in READERS.items():
        m["per_layer"].append({
            "name": name, "unit": unit, "better": "higher",
            "source": source, "layer": layer, "moves": "decode_tok_s",
            "workloads": [CELL]})
    return m


def copy_with_a_new_architecture(root, fault="", train=False):
    """A temporary checkout at `root`: `benchmarks/` with the files of
    `data/newarch/` ADDED (none may replace one that is there) and the
    cell's limits, `tests/` and `PERF.md` linked, and BENCHMARK.json
    `with_a_new_architecture`. `fault` is appended to the new
    reference; `train` appends a cell that trains the new configuration
    too."""
    b = copy_of_the_benchmark(root)
    before = {os.path.join(d, f) for d, _s, fs in os.walk(b) for f in fs}
    for d, _s, files in os.walk(NEWARCH):
        for f in files:
            dst = os.path.join(b, os.path.relpath(d, NEWARCH), f)
            assert dst not in before, f"{dst} would edit, not add"
            shutil.copy(os.path.join(d, f), dst)
    if fault:
        with open(os.path.join(b, "references", "hfdecoder.py"), "a") as f:
            f.write(fault)
    shutil.copy(os.path.join(b, "limits", "cgpt1p3b_decode.json"),
                os.path.join(b, "limits", CELL + ".json"))
    for name in ("tests", "PERF.md"):
        os.symlink(os.path.join(ROOT, name), os.path.join(root, name))
    m = with_a_new_architecture(manifest())
    if train:
        shutil.copy(os.path.join(b, "limits", "gpt2m_train.json"),
                    os.path.join(b, "limits", "newarch_train.json"))
        m["workloads"].append({
            "name": "newarch_train", "config": CONFIG,
            "traffic": "pretrain_s1024", "chips": 1,
            "why": "a second architecture's keys"})
        cells_of(m, "train_tok_s").append("newarch_train")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=2)
    return root
