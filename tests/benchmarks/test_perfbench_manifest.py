"""BENCHMARK.json against the contract's schema, and the harness's
finder-by-name: a configuration, a mix, a per-layer metric and a cell
added as NEW files and NEW entries run without an edit to any file that
is there."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import references

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_limits():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= len(m["command"]) <= 32 and all(
        _line(w) for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    rs = m["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells has to fit
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(m["configs"]) <= 24
    assert 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16
    assert 1 <= len(m["per_layer"]) <= 128


def test_every_file_under_paths_is_named_from_a_names_characters():
    for p in manifest()["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_configs():
    m = manifest()
    names = [c["name"] for c in m["configs"]]
    files = [c["file"] for c in m["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        # the file's own contract (run.py's docstring): what the harness
        # reads of it, and the two modules it names
        assert {"builder", "reference", "vocab_size", "rehearse",
                "reduced", "assumed"} <= set(body)
        assert {"serve", "engine"} <= set(body) or "train" in body
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "builders", body["builder"] + ".py"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "references", body["reference"] + ".py"))
        ref = references.load(body["reference"], training="train" in body)
        for name in references.SERVING + references.ARITHMETIC:
            assert callable(getattr(ref, name)), name


def test_workloads():
    m = manifest()
    cfgs = {c["name"] for c in m["configs"]}
    names = [w["name"] for w in m["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "limits", w["name"] + ".json"))
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(names) // 4)


def test_metrics():
    m = manifest()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    every = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(every)) == len(every)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
        assert cells_of(x) <= cells and cells_of(x)
    layers = set()
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES and _line(x["layer"])
        layers.add(x["layer"])
        assert x["moves"] in e2e
        # every listed cell reports the end-to-end metric it moves
        assert cells_of(x) and cells_of(x) <= cells_of(e2e[x["moves"]])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", x["name"] + ".py"))
        if x["name"].endswith("_roofline") or "mfu" in x["name"]:
            assert x["unit"] == "%"
    for c in cells:                 # every cell reports enough
        assert sum(c in cells_of(x) for x in m["end_to_end"]) >= 2
        assert any(c in cells_of(x) for x in m["per_layer"])
        # a whole-step share of peak beside the kernels' rooflines
        mine = [x for x in m["per_layer"] if c in cells_of(x)]
        roof = {x["moves"] for x in mine if x["name"].endswith(
            "_roofline") or "_roofline." in x["name"]}
        mfu = {x["moves"] for x in mine if "mfu" in x["name"]}
        assert roof and roof <= mfu
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def _clean_env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run(root, *args, timeout=420):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         *args], cwd=root, env=_clean_env(), capture_output=True,
        text=True, timeout=timeout)


def test_a_cell_refuses_the_cpu_and_prints_no_result():
    p = _run(ROOT, "--workload", manifest()["workloads"][0]["name"],
             "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "platform=cpu" in p.stderr
    assert not [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")]


def _copy_of_the_benchmark(root):
    """`benchmarks/` copied into `root`, the program linked beside
    it; returns the copy's path."""
    b = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), b,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "paddle_tpu"),
               os.path.join(root, "paddle_tpu"))
    return b


@pytest.fixture(scope="module")
def copy_with_a_dummy_cell(tmp_path_factory):
    """A temporary copy of the benchmark with a configuration, a mix, a
    per-layer metric and a cell ADDED: new files and new manifest
    entries only."""
    root = str(tmp_path_factory.mktemp("copy"))
    b = _copy_of_the_benchmark(root)
    m = manifest()
    with open(os.path.join(b, "configs", "gpt2-medium.json")) as f:
        cfg = json.load(f)
    cfg["rehearse"]["n_layer"] = 3
    with open(os.path.join(b, "configs", "dummy-model.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "pretrain_s1024.json")) as f:
        mix = json.load(f)
    mix["rehearse"]["batch"] = 2
    with open(os.path.join(b, "traffic", "dummy_mix.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(b, "limits", "gpt2m_train.json"),
                os.path.join(b, "limits", "dummy_cell.json"))
    with open(os.path.join(b, "layer_metrics", "dummy_steps.py"),
              "w") as f:
        f.write('def read(ctx):\n'
                '    return ctx["obs"]["window"]["steps"]\n')
    m["configs"].append({
        "name": "dummy-model", "source": cfg["source"],
        "file": "benchmarks/configs/dummy-model.json", "reduced": [],
        "why": "a dummy"})
    m["workloads"].append({
        "name": "dummy_cell", "config": "dummy-model",
        "traffic": "dummy_mix", "chips": 1, "why": "a dummy"})
    for x in m["end_to_end"]:
        if x["name"] == "train_tok_s":
            x["workloads"].append("dummy_cell")
    m["per_layer"].append({
        "name": "dummy_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "compiled steps",
        "moves": "train_tok_s", "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def test_a_new_cell_runs_from_new_files_alone(copy_with_a_dummy_cell):
    p = _run(copy_with_a_dummy_cell, "--workload", "dummy_cell", "--seed",
             str(2 ** 31 + 12345), "--seconds", "1", "--trace", "1",
             "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # the last line's keys, `compared` last among them
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu" and last["rehearsal"]
    # counts only: the new metric, found by its name; no time, no share
    assert last["metrics"]["dummy_steps"]["value"] == last["attempted"]
    assert set(last["metrics"]) == {"dummy_steps"}
    for c in last["compared"].values():
        assert set(c) == {"value", "limit", "ok"}
    # each number compared is printed beside its limit as the last
    # lines of standard error
    tail = p.stderr.strip().splitlines()[-len(last["compared"]):]
    assert all(ln.startswith("compared ") and "limit" in ln
               for ln in tail)


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    p = _run(root, "--workload", manifest()["workloads"][0]["name"],
             "--seed", "1", "--seconds", "1", "--trace", "0",
             "--rehearse-cpu")
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")]


# ---- a second ARCHITECTURE from new files alone ---------------------

NEWARCH = os.path.join(ROOT, "tests", "benchmarks", "data", "newarch")
GPT2_KEYS = ("n_embd", "n_layer", "n_head", "n_inner", "n_positions")
ONE_LAYER_DROPPED = '''

_sound = served_token_gaps


def served_token_gaps(cfg, w, *args, **kwargs):
    """The planted fault: the forward pass leaves out the last layer."""
    w = dict(w, layers={k: v[:-1] for k, v in w["layers"].items()})
    return _sound(cfg, w, *args, **kwargs)
'''


def _copy_with_a_new_architecture(root, fault=""):
    """A temporary copy of the benchmark with a configuration that has
    none of GPT-2's keys, its own reference and builder, a served cell,
    a cell that trains and three per-layer readers ADDED: new files
    (tests/benchmarks/data/newarch/) and new manifest entries only."""
    b = _copy_of_the_benchmark(root)
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
                os.path.join(b, "limits", "newarch_decode.json"))
    shutil.copy(os.path.join(b, "limits", "gpt2m_train.json"),
                os.path.join(b, "limits", "newarch_train.json"))
    with open(os.path.join(b, "configs", "hf-decoder.json")) as f:
        cfg = json.load(f)
    assert not set(GPT2_KEYS) & (set(cfg) | set(cfg["rehearse"]))
    m = manifest()
    m["configs"].append({
        "name": "hf-decoder", "source": cfg["source"],
        "file": "benchmarks/configs/hf-decoder.json", "reduced": [],
        "why": "a second architecture's keys"})
    for cell, mix, moves in (
            ("newarch_decode", "decode_heavy", "decode_tok_s"),
            ("newarch_train", "pretrain_s1024", "train_tok_s")):
        m["workloads"].append({
            "name": cell, "config": "hf-decoder", "traffic": mix,
            "chips": 1, "why": "a second architecture's keys"})
        for x in m["end_to_end"]:
            if x["name"] == moves:
                x["workloads"].append(cell)
    for x in m["per_layer"]:      # readers the benchmark has, unedited
        if x["name"] in ("kv_page_occupancy_peak",
                         "prefill_token_share.decode"):
            x["workloads"].append("newarch_decode")
    for name in ("newarch_serve_flops", "newarch_processed",
                 "newarch_attended"):
        m["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "whole model step",
            "moves": "decode_tok_s", "workloads": ["newarch_decode"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def _rehearse(root, cell, trace="1"):
    return _run(root, "--workload", cell, "--seed", str(2 ** 31 + 2727),
                "--seconds", "1", "--trace", trace, "--rehearse-cpu")


@pytest.fixture(scope="module")
def copy_with_a_new_architecture(tmp_path_factory):
    return _copy_with_a_new_architecture(
        str(tmp_path_factory.mktemp("newarch")))


def test_a_new_architecture_runs_from_new_files_alone(
        copy_with_a_new_architecture):
    root = copy_with_a_new_architecture
    p = _rehearse(root, "newarch_decode")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["served_noise_power"]["ok"]
    assert last["attempted"] > 0
    # the new readers got the configuration's own reference and the
    # driver's segments; its arithmetic is GPT-2's at the same sizes
    got = {k: v["value"] for k, v in last["metrics"].items()}
    processed, attended = got["newarch_processed"], got["newarch_attended"]
    assert processed > 0 and attended > processed
    from references import gpt2

    with open(os.path.join(root, "benchmarks", "configs",
                           "hf-decoder.json")) as f:
        cfg = json.load(f)
    same = {"n_embd": cfg["rehearse"]["hidden_size"],
            "n_layer": cfg["rehearse"]["num_hidden_layers"],
            "n_head": cfg["rehearse"]["num_attention_heads"],
            "n_inner": cfg["rehearse"]["intermediate_size"],
            "n_positions": cfg["rehearse"]["max_position_embeddings"],
            "vocab_size": cfg["rehearse"]["vocab_size"]}
    d, layers, *_ = gpt2.dims(same)
    assert got["newarch_serve_flops"] == \
        2 * gpt2.matmul_params(same) * processed + 4 * d * layers * attended
    # and the readers the benchmark has read the new cell as it is:
    # the pool's occupancy is asked of the builder's handle
    assert 0 < got["kv_page_occupancy_peak"] <= 100
    assert 0 < got["prefill_token_share.decode"] < 100


def test_a_fault_planted_in_the_new_reference_reads_not_correct(tmp_path):
    """The dispatch reaches the module the configuration names: with
    one layer dropped from THAT reference's forward pass, the same run
    reads `correct` false."""
    root = _copy_with_a_new_architecture(str(tmp_path), ONE_LAYER_DROPPED)
    p = _rehearse(root, "newarch_decode", trace="0")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert not last["compared"]["served_noise_power"]["ok"]
    assert last["compared"]["served_noise_power"]["value"] > 10 * \
        last["compared"]["served_noise_power"]["limit"]


def test_a_train_cell_whose_reference_cannot_train_fails_at_start(
        copy_with_a_new_architecture):
    p = _rehearse(copy_with_a_new_architecture, "newarch_train", trace="0")
    assert p.returncode != 0
    assert "has no training part" in p.stderr
    assert "jax" not in p.stderr        # said before anything is built
    assert not [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")]


# ---- the contract, and who may name a model's keys ------------------

def _reference_names():
    d = os.path.join(ROOT, "benchmarks", "references")
    return sorted(f[:-3] for f in os.listdir(d)
                  if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name", _reference_names())
def test_every_reference_exports_the_contract(name):
    ref = references.load(name)
    for part in (references.SERVING, references.ARITHMETIC):
        for fn in part:
            assert callable(getattr(ref, fn)), fn
            assert fn in references.__doc__
    have = [n for n in references.TRAINING if hasattr(ref, n)]
    assert have in ([], list(references.TRAINING))    # whole, or absent
    if have:
        assert {"lr", "beta1", "beta2", "eps", "weight_decay"} <= set(
            ref.ADAMW)
        assert references.load(name, training=True) is ref


def test_a_reference_that_is_missing_or_partial_is_said_at_the_start(
        monkeypatch):
    import types

    with pytest.raises(SystemExit, match="references/nope.py"):
        references.load("nope")
    from references import gpt2

    partial = types.ModuleType("references.partial_one")
    for n in references.SERVING + references.ARITHMETIC:
        setattr(partial, n, getattr(gpt2, n))
    monkeypatch.setitem(sys.modules, "references.partial_one", partial)
    assert references.load("partial_one") is partial
    with pytest.raises(SystemExit, match="no training part"):
        references.load("partial_one", training=True)
    del partial.serve_flops
    with pytest.raises(SystemExit, match="serve_flops"):
        references.load("partial_one")


def test_only_references_and_builders_name_a_models_keys():
    """`run.py`, `harness/`, the drivers and the per-layer readers name
    no model's configuration key and import no model's module: what
    knows a model is chosen by name from the configuration's file."""
    b = os.path.join(ROOT, "benchmarks")
    files = [os.path.join(b, "run.py")]
    for sub in ("harness", "drivers", "layer_metrics"):
        for d, dirs, names in os.walk(os.path.join(b, sub)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            files += [os.path.join(d, f) for f in names
                      if f.endswith(".py")]
    assert len(files) > 40
    key = re.compile(r"\b(" + "|".join(GPT2_KEYS) + r")\b")
    models = "|".join(_reference_names())
    imports = re.compile(
        r"harness\.(reference|weights)\b|from \.? ?import .*\b(reference"
        r"|weights)\b|references\.(" + models + r")\b"
        r"|from references import (?!load\b)|import builders|from builders")
    bad = []
    for path in files:
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if key.search(line) or imports.search(line):
                    bad.append(f"{os.path.relpath(path, ROOT)}:{i}: "
                               f"{line.strip()}")
    assert not bad, "\n".join(bad)
    # the guard sees what it is for: the same search finds the keys
    # where they belong
    with open(os.path.join(b, "references", "gpt2.py")) as f:
        assert key.search(f.read())
    with open(os.path.join(b, "builders", "gpt.py")) as f:
        text = f.read()
    assert key.search(text) and imports.search(text)
