"""Configuration `olmo-hybrid-7b`: the program (`text/models/
olmo_hybrid.py` through `inference.LLMEngine`) held to the plain
reference (`benchmarks/references/olmo_hybrid.py`) at small sizes on the
CPU, the configuration's cut and widths, the reference's arithmetic
frozen, the cell's rehearsal and a fault planted in its reference, and
the cell's readers. No test here pins a position in `workloads` or
`per_layer`: a cell added later is appended after these."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import perfbench_newarch as newarch
import references
from builders import olmo_hybrid as builder
from harness import traffic
from run import load_module, overlay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ref = references.load("olmo_hybrid")
CELL = "olmohybrid7b_decode"
NEW = ("gdn_time_share.decode", "gdn_recur_roofline.decode",
       "gdn_chunk_roofline.decode", "gdn_chunked_share_of_rows.decode",
       "mha_paged_attn_roofline.decode")


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def _small(**over):
    """The configuration's `rehearse` sizes in float32: one period L L L
    F, 3 GDN heads of 16 × 32, 4 full heads of 16, vocabulary 256."""
    cfg = _published()
    cfg = overlay(cfg, cfg["rehearse"])
    cfg["serve"]["weight_dtype"] = "float32"
    return overlay(cfg, over)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(
        np.int32)


# ---- the program against the reference --------------------------------

@pytest.mark.parametrize("seed", [7, 2 ** 31 + 7])
def test_the_builders_model_matches_the_reference(seed):
    """`builders/olmo_hybrid.py`'s program on the seed's weights (fused
    as that module fuses them) against the reference's token-by-token recurrence and
    dense attention: 2e-5 of logits up to ≈ 1 (float32 sums in another
    order, four layers)."""
    cfg = _small()
    model = builder.build_model(cfg, seed, "float32")
    ids = _ids(150, seed=seed % 97)
    want = np.asarray(ref.logits_fn(
        cfg, ref.make_weights(cfg, seed, "float32"), jnp.asarray(ids)))
    got = np.asarray(model(ids[None])._value)[0]
    assert got.shape == want.shape == (150, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_bf16_control_moves_the_references_logits():
    """`bf16` (every product's operands rounded: the stated precision)
    moves the reference's own logits, as the program's bfloat16 weights
    and activations do."""
    cfg = _small()
    w = ref.make_weights(cfg, 5, "float32")
    ids = jnp.asarray(_ids(120))
    exact = np.asarray(ref.logits_fn(cfg, w, ids))
    plain = np.asarray(ref.logits_fn(cfg, w, ids, "bf16"))
    assert 1e-4 < np.abs(plain - exact).max() < 0.1


# ---- the configuration ------------------------------------------------

# the published widths (allenai/Olmo-Hybrid-7B config.json), none cut
WIDTHS = {"hidden_size": 3840, "num_attention_heads": 30,
          "num_key_value_heads": 30, "linear_num_key_heads": 30,
          "linear_num_value_heads": 30, "linear_key_head_dim": 96,
          "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
          "intermediate_size": 11008, "vocab_size": 100352}


def test_the_configuration_keeps_every_width_and_states_its_cut():
    cfg = _published()
    for key, val in WIDTHS.items():
        assert cfg[key] == val, key
    assert cfg["linear_allow_neg_eigval"] is True
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], pub["num_hidden_layers"]) == (8, 32)
    assert cfg["layer_types"] == pub["layer_types"][:8]
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 2
    assert len(pub["layer_types"]) == 32
    dep = cfg["deployment"]
    assert (dep["chips"], dep["pipeline_stages"],
            dep["chips_sharing_a_layer"]) == (4, 4, 1)
    assert dep["pipeline_stages"] * cfg["num_hidden_layers"] == \
        pub["num_hidden_layers"]
    for word in ("OUTPUT", "q_norm", "NoPE", "use_gate", "softplus",
                 "linear_allow_neg_eigval", "SiLU", "1e-6", "96^-0.5",
                 "FLA's draws"):
        assert word in json.dumps(cfg["assumed"]), word
    e = cfg["engine"]
    assert (e["num_slots"], e["page_size"], e["token_budget"],
            e["decode_k"], e["max_model_len"], e["pool_budget_bytes"],
            e["kv_dtype"], e["prefix_cache"]) == (
        128, 16, 512, 8, 2048, 11 * 2 ** 29, "bfloat16", False)
    # the harness's warm-up: a prompt of two token budgets and decode_k
    # + 1 answers must fit a sequence, as the mix's longest does
    assert 2 * e["token_budget"] + e["decode_k"] + 1 <= e["max_model_len"]
    mix = traffic.load_mix(os.path.join(ROOT, "benchmarks", "traffic"),
                           "decode_heavy")
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= min(e["max_model_len"], ref.SEQ_BUCKET)
    assert mix["check_sample"] == 64 and mix["greedy"]


def test_arithmetic_at_the_published_sizes():
    cfg = _published()
    s = ref.dims(cfg)
    linear = ref.mix_matrix_params(s, 0) + ref.mix_small_params(s, 0)
    full = ref.mix_matrix_params(s, 3) + ref.mix_small_params(s, 3)
    # the published shapes' reckoning, to the parameter: a linear mixer
    # 88.75 M (+ norms), a full layer's 4 · 3 840² (+ norms), a SwiGLU
    # of 11 008, embedding and head 770.70 M
    assert ref.mix_matrix_params(s, 0) == 88_704_000
    assert linear == 88_704_000 + 4 * 30 * 384 + 2 * 30 + 192 + 3840
    assert ref.mix_matrix_params(s, 3) == 4 * 3840 ** 2
    assert full == 4 * 3840 ** 2 + 3 * 3840
    assert ref.mlp_params(s) == 3 * 3840 * 11008 + 3840
    assert ref.param_count(cfg) == 2_435_748_072 == cfg["parameters_held"]
    assert ref.param_count(cfg) == 2 * 100352 * 3840 + 3840 + 6 * (
        linear + ref.mlp_params(s)) + 2 * (full + ref.mlp_params(s))
    assert ref.weight_bytes(cfg, "bfloat16") == 4_871_496_144
    assert (ref.gdn_layers(s), ref.full_layers(s)) == (6, 2)
    # the state a slot a layer: 2.21 MB as the reference counts it
    assert ref.state_bytes(s) == 30 * 96 * 192 * 4 == 2_211_840
    # K and V of a token: 15 360 B a full layer
    assert ref.kv_bytes_per_token(cfg, "bfloat16") == 2 * 15360
    # the pool: 5.5 GiB = 192 224 tokens holds the closed loop's worst
    # case in flight, 128 slots of the grid's longest 1 370 + a page
    pages = cfg["engine"]["pool_budget_bytes"] // (16 * 2 * 15360)
    assert pages * 16 == 192_224 >= 128 * (1370 + 16)
    work = {"segments": [(0, 600), (3000, 400)], "processed": 1000,
            "iterations": 10, "stats": {}}
    rows = 600 * 601 // 2 + 400 * 3000 + 400 * 401 // 2
    # without the program's counters: every row recurrent, every row
    # reads its own context
    assert ref.gdn_rows(cfg, work) == (6000, 0)
    assert ref.gdn_state_bytes(cfg, work) == 6000 * 2 * 2_211_840
    assert ref.gdn_chunk_flops(cfg, work) == 0 == ref.gdn_chunk_bytes(
        cfg, work)
    assert ref.kv_bytes_attended(cfg, work, "bfloat16") == 30720 * rows
    # with them: the least K/V is the engine's count of a step's span
    st = {"gdn_rows_recurrent": 1200, "gdn_rows_chunked": 4800,
          "gdn_chunk_launches": 12, "kv_positions_least_full": 5000}
    counted = dict(work, stats=st)
    assert ref.kv_bytes_attended(cfg, counted, "bfloat16") == 30720 * 5000
    assert ref.gdn_state_bytes(cfg, counted) == 1200 * 2 * 2_211_840
    per_row = 30 * (2 * 64 * 96 + 64 * 288 + 4 * 96 * 192 + 64 * 192
                    + 2 * 96 * 192)
    assert ref.gdn_chunk_flops(cfg, counted) == 4800 * per_row
    assert ref.gdn_chunk_bytes(cfg, counted) == \
        4800 * 30 * (2 * 96 + 2 * 192 + 2) * 2 + 12 * 2 * 2_211_840
    assert ref.weight_bytes(cfg, "bfloat16", work) == 10 * 4_871_496_144
    per_token = 100352 * 3840 + 6 * 88_704_000 + 2 * 4 * 3840 ** 2 \
        + 8 * 3 * 3840 * 11008
    assert ref.serve_flops(cfg, counted) == 2 * per_token * 1000 \
        + 4 * 128 * 30 * 2 * rows + 7 * 30 * 96 * 192 * 1200 \
        + 4800 * per_row
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.train_step_flops(cfg, 1, 1)
    with pytest.raises(ValueError, match="rotary"):
        ref.dims(dict(cfg, rope_parameters={"rope_theta": 1e4}))


# ---- the cell ----------------------------------------------------------

def _env():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _rehearse(root, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 4343), "--seconds",
         "2", "--rehearse-cpu", *extra], cwd=root, env=_env(),
        capture_output=True, text=True, timeout=900)


def test_the_cell_rehearses_on_the_cpu_and_reads_correct():
    p = _rehearse(ROOT, "--trace", "1", "--control", "int8")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] and last["device"]["platform"] == "cpu"
    m = last["metrics"]
    # the rehearsal's token budget (32) is under two chunks: all
    # recurrent
    assert m["gdn_chunked_share_of_rows.decode"]["value"] == 0.0
    assert m["preemptions"]["value"] == 0
    assert m["compiles_in_window"]["value"] == 0
    assert not any(n.endswith("_roofline.decode") or "time_share" in n
                   for n in m)
    assert "control_int8" in last["builder_readings"]


ANOTHER_SEEDS_WEIGHTS = '''

_sound = served_token_gaps


def served_token_gaps(cfg, w, *args, **kwargs):
    """The planted fault: the forward pass runs on another seed's
    weights, so nearly every served token lies off its best."""
    w = make_weights(cfg, 1, cfg["serve"]["weight_dtype"])
    return _sound(cfg, w, *args, **kwargs)
'''


def test_a_fault_planted_in_the_reference_reads_not_correct(tmp_path):
    """The same rehearsal with the reference's forward pass on another
    seed's weights reads `correct` false, by a power many times the
    limit."""
    root = str(tmp_path)
    b = newarch.copy_of_the_benchmark(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(b, "references", "olmo_hybrid.py"), "a") as f:
        f.write(ANOTHER_SEEDS_WEIGHTS)
    p = _rehearse(root, "--trace", "0")
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    power = last["compared"]["served_noise_power"]
    assert not power["ok"] and power["value"] > 4 * power["limit"]


# ---- the manifest and the readers -------------------------------------

def test_the_manifest_lists_the_cell_and_its_readers(bench_root):
    """The cell, its configuration, and its own five readers together
    and in their order wherever they stand; the cell listed under
    `decode_tok_s` and under every reader every other decode cell
    shares."""
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "decode_heavy", 1)
    assert len(cell["why"]) <= 200
    conf = next(c for c in m["configs"] if c["name"] == "olmo-hybrid-7b")
    assert conf["reduced"] == _published()["reduced"]
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert [p["name"] for p in mine] == list(NEW)
    at = m["per_layer"].index(mine[0])
    assert m["per_layer"][at:at + len(NEW)] == mine
    assert all(p["moves"] == "decode_tok_s" for p in mine)
    decode = next(x for x in m["end_to_end"]
                  if x["name"] == "decode_tok_s")["workloads"]
    assert CELL in decode
    others = set(decode) - {CELL}
    for p in m["per_layer"]:
        if others <= set(p.get("workloads", ())):
            assert CELL in p["workloads"], p["name"]


def _reader(name):
    return load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_and_counters_reads_nothing(name):
    """The parent's checkout (no `attn_gdn` scope, no `gdn_*` counter, no
    trace of this cell): every new reader returns None and does not
    raise."""
    ctx = {"cfg": _published(), "ref": references.load("sarvam_mla"),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"window": {"segments": [(0, 9)], "stats": {}},
                   "traced": {"segments": [(0, 9)], "stats": {},
                              "processed": 9, "iterations": 1},
                   "kv_dtype": "bfloat16"}}
    assert _reader(name)(ctx) is None


def test_the_new_readers_on_a_made_up_trace(tmp_path):
    from harness import scope_paths
    from test_perfbench_sarvam_mla import _made_up_trace

    scope_paths.reduce_file.cache_clear()
    call = 'custom-call(...), custom_call_target="tpu_custom_call"'
    trace_dir = _made_up_trace(tmp_path, [
        ("jit(pure)/attn/attn_gdn/gdn_proj/dot_general", "fusion.1"),
        ("jit(pure)/attn/attn_gdn/gdn_chunk/pallas_call", call),
        ("jit(pure)/attn/attn_gdn/gdn_recur/pallas_call", call),
        ("jit(pure)/attn/attn_gdn/gdn_out/norm/mul", "fusion.2"),
        ("jit(pure)/attn/attn_full/pallas_call", call),
        ("jit(pure)/attn/attn_full/norm/mul", "fusion.3"),
        ("jit(pure)/mlp/dot_general", "fusion.4"),
        ("jit(pure)/lm_head/dot_general", "fusion.5"),
    ])
    cfg = _published()
    st = {"gdn_rows_recurrent": 600, "gdn_rows_chunked": 1800,
          "gdn_chunk_launches": 6, "kv_positions_least_full": 3000}
    work = {"segments": [(0, 400)], "processed": 400, "iterations": 1,
            "stats": st}
    ctx = {"cfg": cfg, "ref": ref, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"trace_dir": trace_dir, "traced": work, "window": work,
                   "kv_dtype": "bfloat16", "weight_dtype": "bfloat16"}}
    # eight operations of 50 ns: four under attn_gdn, one of each form
    assert _reader("gdn_time_share.decode")(ctx) == pytest.approx(50.0)
    assert _reader("gdn_recur_roofline.decode")(ctx) == pytest.approx(
        100 * 600 * 2 * 2_211_840 / 819e9 / 50e-9)
    by_bytes = ref.gdn_chunk_bytes(cfg, work) / 819e9
    by_flops = ref.gdn_chunk_flops(cfg, work) / 197e12
    assert _reader("gdn_chunk_roofline.decode")(ctx) == pytest.approx(
        100 * max(by_bytes, by_flops) / 50e-9)
    assert _reader("gdn_chunked_share_of_rows.decode")(ctx) == \
        pytest.approx(75.0)
    # the walk's custom call alone, not the norms beside it
    assert _reader("mha_paged_attn_roofline.decode")(ctx) == \
        pytest.approx(100 * 3000 * 30720 / 819e9 / 50e-9)
