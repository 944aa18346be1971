"""BENCHMARK.json with the pending cells admitted (see conftest.py)."""
import glob
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(ROOT, "benchmarks")


def manifest_with_pending():
    """BENCHMARK.json with the entries of `benchmarks/pending/*.json`
    merged in: the cells that are built and tested but not admitted."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    for path in sorted(glob.glob(os.path.join(_BENCH, "pending",
                                              "*.json"))):
        with open(path) as f:
            p = json.load(f)
        cell = p["workload"]["name"]
        m["workloads"].append(p["workload"])
        m["end_to_end"] += p["end_to_end"]
        by_name = {x["name"]: x for x in m["per_layer"]}
        for x in p["per_layer"]:
            if "add_to_workloads" in x:
                by_name[x["name"]]["workloads"].append(cell)
            else:
                m["per_layer"].append(x)
    return m
