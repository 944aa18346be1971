"""Configuration `ling-3.0-flash-vl`: the program (`text/models/
ling_hybrid.py` through `inference.LLMEngine`) held to the plain
reference (`benchmarks/references/ling_hybrid.py`) at small sizes on the
CPU, the share of a deployment tied to the whole layer, the program's
counters tied to the reference's counts, the cell's mix, the new readers
and the reference's arithmetic frozen."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import references
from builders import ling_hybrid as builder
from harness import traffic
from run import load_module, overlay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ref = references.load("ling_hybrid")
CELL = "ling3flash_manyseq_decode"


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        return json.load(f)


def _small(**over):
    """The configuration's `rehearse` sizes in float32: seven layers of
    a period of six (0-4 and 6 KDA, 5 MLA), a leading dense layer, 4
    heads of 16, latent 32, 8 of 32 experts held (groups 0-1 of 8),
    top-8 out of 4 groups."""
    cfg = _published()
    cfg = overlay(cfg, cfg["rehearse"])
    cfg["serve"]["weight_dtype"] = "float32"
    return overlay(cfg, over)


def _ids(n, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(
        np.int32)


@pytest.mark.parametrize("held,seed", [(8, 7), (32, 2 ** 31 + 7)])
def test_model_matches_the_reference_on_seeded_weights(held, seed):
    """The eager forward (KDA as ONE chunked run from zero, MLA
    expanded) against the reference's token-by-token recurrence: 3e-5 of
    logits up to 0.8 (float32 sums in another order, seven layers)."""
    cfg = _small(num_experts=held)
    model = builder.build_model(cfg, seed, "float32")
    ids = _ids(150, seed=seed % 97)
    want = np.asarray(ref.logits_fn(
        cfg, ref.make_weights(cfg, seed, "float32"), jnp.asarray(ids)))
    got = np.asarray(model(ids[None])._value)[0]
    assert got.shape == want.shape == (150, 256)
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.mark.parametrize("budget,decode_k", [(80, 4), (100, 1)])
def test_chunks_that_split_a_run_then_decode_give_the_references_logits(
        budget, decode_k):
    """Through `LLMEngine`: prompts prefilled in chunks that end inside
    a delta-rule chunk (80 = 64 + 16, 100 = 64 + 36), lone prompt rows
    beside them, then decode from the slabs: every served token is the
    REFERENCE's argmax over its full forward, and its logit gap is 0."""
    from paddle_tpu import inference

    cfg = _small(engine={"max_model_len": 256})
    model = builder.build_model(cfg, 11, "float32")
    model.eval()
    w = ref.make_weights(cfg, 11, "float32")
    eng = inference.LLMEngine(model, inference.LLMEngineConfig(
        num_slots=3, page_size=16, max_model_len=256, token_budget=budget,
        kv_dtype="float32", decode_k=decode_k))
    prompts = [_ids(n, seed=n) for n in (150, 97, 70, 130)]
    reqs = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    while eng.has_work():
        eng.step()
    for p, r in zip(prompts, reqs):
        toks = np.asarray(r.future.result()).astype(np.int32)
        gap, margin = ref.served_token_gaps(cfg, w, toks, len(p), 256, 128)
        assert len(gap) == 10 and float(gap.max()) == 0.0
        assert float(margin.min()) > 0.0
    assert eng.stats["kda_rows_chunked"] > 0 < eng.stats[
        "kda_rows_recurrent"]


def test_the_state_rounded_to_bfloat16_is_a_control_the_reference_has():
    """`kda_state_bf16` moves the reference's own logits (the state
    carries every earlier token), as `bf16` (every product's operands
    rounded) does."""
    cfg = _small()
    w = ref.make_weights(cfg, 5, "float32")
    ids = jnp.asarray(_ids(120))
    exact = np.asarray(ref.logits_fn(cfg, w, ids))
    rounded = np.asarray(ref.logits_fn(cfg, w, ids, "kda_state_bf16"))
    assert 1e-4 < np.abs(rounded - exact).max() < 0.05
    plain = np.asarray(ref.logits_fn(cfg, w, ids, "bf16"))
    assert np.abs(plain - exact).max() > 1e-4       # the stated precision


def test_the_decays_spread_over_the_whole_of_their_range():
    """The seed's A_log and dt_bias: the decay a key channel a token
    spans (e^-5, 1): a tenth of the channels under e^-4 a token, a
    twentieth over e^-0.1, at the published widths of one layer."""
    cfg = _published()
    s = ref.dims(cfg)
    key = jax.random.PRNGKey(0)
    n = jax.random.normal(key, (64, s["d"]), jnp.float32)
    lw = {"w_a": 0.02 * jax.random.normal(key, (s["d"], s["hk"])),
          "a_log": jax.random.uniform(key, (s["H"],), jnp.float32,
                                      -ref.A_LOG_SPAN, ref.A_LOG_SPAN),
          "dt_bias": jax.random.uniform(key, (s["hk"],), jnp.float32,
                                        -ref.DT_BIAS_SPAN, ref.DT_BIAS_SPAN),
          "w_beta": jnp.zeros((s["d"], s["H"]))}
    g, _ = ref.kda_gates(s, n, lw, None)
    g = np.asarray(g)
    assert g.min() > -5.0 and g.max() < 0.0
    assert np.mean(g < -4.0) > 0.1 and np.mean(g > -0.1) > 0.05


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide §4: four chips hold 8 of a layer's 32 experts
    each (two whole groups); the routed parts of the four shares and the
    shared expert counted ONCE add up to what the uncut reference gives
    for the whole feed-forward layer (group-limited selection)."""
    from paddle_tpu.nn import expert_layer

    cfg = _small(num_experts=32)
    s = ref.dims(cfg)
    w = ref.make_weights(cfg, 9, "float32")
    lw = w["layers"][1]
    n = jax.random.normal(jax.random.PRNGKey(1), (24, s["d"]), jnp.float32)
    shared, routed = ref.sparse_ffn(s, n, lw, None)
    whole = np.asarray(shared + routed)
    gate_up = jnp.concatenate([lw["e_gate"], lw["e_up"]], axis=2)
    weights, ids = expert_layer.route_top_k(
        n, lw["router"], s["top_k"], scoring="sigmoid",
        select_bias=lw["router_bias"], n_group=s["groups"],
        topk_group=s["top_groups"])
    want_w, want_ids = ref.route(s, n, lw["router"], lw["router_bias"],
                                 None)
    assert np.array_equal(np.sort(np.asarray(ids), -1),
                          np.sort(np.asarray(want_ids), -1))
    parts = jnp.zeros_like(n)
    touched = 0
    for first in range(0, 32, 8):
        part, counters = expert_layer.held_experts_ffn(
            n, weights, ids, jnp.ones((24,), bool),
            gate_up[first:first + 8], lw["e_down"][first:first + 8],
            first_expert=first)
        want = ref.sparse_ffn(s, n, lw, None, held=(first, 8))[1]
        np.testing.assert_allclose(np.asarray(s["scale"] * part),
                                   np.asarray(want), atol=2e-6)
        parts = parts + part
        touched += int(counters[1])
    assert touched == 24 * s["top_k"]       # every assignment, once
    np.testing.assert_allclose(
        np.asarray(shared + s["scale"] * parts), whole, atol=5e-6)


def test_the_cell_rehearses_on_the_cpu_and_reads_correct():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 77), "--seconds", "2",
         "--trace", "1", "--rehearse-cpu", "--control", "kda_state_bf16"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] and last["device"]["platform"] == "cpu"
    m = last["metrics"]
    assert m["moe_rows_per_expert.decode"]["value"] >= 1.0
    assert 0 < m["mla_least_share_of_rows.decode"]["value"] <= 100
    # the rehearsal's token budget (32) is under one chunk: all recurrent
    assert m["kda_chunked_share_of_rows.decode"]["value"] == 0.0
    assert m["preemptions"]["value"] == 0
    assert m["compiles_in_window"]["value"] == 0
    assert not any(n.endswith("_roofline.decode") or "time_share" in n
                   for n in m)
    assert "control_kda_state_bf16" in last["builder_readings"]


# ---- the mix ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 32 + 99])
def test_every_block_of_eight_holds_each_prompt_length_once(seed):
    mix = traffic.load_mix(os.path.join(ROOT, "benchmarks", "traffic"),
                           "manyseq_capped")
    assert mix["kind"] == "closed_loop" and mix["grid"] == [8, 8]
    grid = traffic.closed_grid(mix)
    prompts = sorted({p for p, _ in grid})
    assert len(prompts) == 8 and prompts[0] >= 1707 and prompts[-1] <= 2458
    assert {o for _, o in grid} <= set(range(1017, 1032))
    walk = traffic.closed_walk(mix, seed)
    for _ in range(12):               # 96 first requests = 12 blocks
        block = [next(walk) for _ in range(8)]
        assert sorted(p for p, _ in block) == prompts
    cfg = _published()
    e = cfg["engine"]
    longest = max(p + o for p, o in grid)
    assert longest == 2458 - 0 + 1031 - 0 or longest <= 3489
    # the harness's warm-up: a prompt of two token budgets and decode_k
    # + 1 answers must fit a sequence
    assert 2 * e["token_budget"] + e["decode_k"] + 1 <= e["max_model_len"]


# ---- arithmetic -------------------------------------------------------

WORK = {"segments": [(0, 600), (3000, 400)], "processed": 1000,
        "iterations": 10, "stats": {
            "moe_assignments": 48000, "moe_assignments_held": 12000,
            "moe_experts_touched": 700}}
ROWS = 600 * 601 // 2 + 400 * 3000 + 400 * 401 // 2


def test_arithmetic_at_the_published_sizes():
    cfg = _published()
    s = ref.dims(cfg)
    # the issue's reckoning, to the parameter
    kda = ref.attn_matrix_params(s, 0) + ref.attn_small_params(s, 0)
    mla = ref.attn_matrix_params(s, 5) + ref.attn_small_params(s, 5)
    assert kda == 52_651_168 and mla == 31_971_072
    assert kda == 5 * 2560 * 4096 + 2 * 2560 * 32 + 2 * 2560 \
        + 3 * 4 * 4096 + 32 + 4096 + 128
    assert ref.expert_params(s) == 5_898_240
    assert ref.ffn_params(s, 0) == 47_185_920
    assert ref.ffn_params(s, 1) == 128 * 5_898_240 + 5_898_240 \
        + 2560 * 512 + 512 == 762_184_192
    assert [ref.is_mla(s, l) for l in range(7)] == [
        False] * 5 + [True, False]
    assert ref.kda_layers(s) == 6 and ref.mla_layers(s) == 1
    assert ref.param_count(cfg) == 5_169_367_232 == cfg["parameters_held"]
    assert ref.param_count(cfg) == 2 * 39296 * 2560 + 2560 + (
        kda + 47_185_920) + 5 * (kda + 762_184_192) + (mla + 762_184_192)
    assert ref.weight_bytes(cfg, "bfloat16") == 10_338_734_464
    # the caches at 96 slots: 1.21 GB of float32 state, 42 MB of tails
    assert ref.state_bytes(s) == 2 * 2 ** 20
    assert 96 * 6 * ref.state_bytes(s) == 1_207_959_552
    assert 96 * 6 * 3 * 12288 * 2 == 42_467_328
    assert ref.kv_bytes_per_token(cfg, "bfloat16") == 1152
    assert ref.positions(cfg) == 131_072
    # without the program's counters: every row recurrent, every MLA row
    # a step of its own
    assert ref.kda_rows(cfg, WORK) == (6000, 0)
    assert ref.kda_state_bytes(cfg, WORK) == 6000 * 4 * 2 ** 20
    assert ref.kda_chunk_flops(cfg, WORK) == 0 == ref.kda_chunk_bytes(
        cfg, WORK)
    assert ref.kv_bytes_attended(cfg, WORK, "bfloat16") == 1152 * ROWS \
        == ref.kv_bytes_attended_by_row(cfg, WORK, "bfloat16")
    # with them. The MLA readers' bytes are the LATENT bytes only: no
    # state byte may enter `kv_bytes_attended`
    st = dict(WORK["stats"], kda_rows_recurrent=1200, kda_rows_chunked=4800,
              kda_chunk_launches=12, mla_rows_attended_least=5000,
              mla_rows_attended_single=1000)
    counted = dict(WORK, stats=st)
    assert ref.kda_state_bytes(cfg, counted) == 1200 * 4 * 2 ** 20
    per_row = 32 * (2 * 64 * 128 + 64 * 256 + 4 * 128 * 128 + 64 * 128
                    + 2 * 128 * 128)
    assert ref.kda_chunk_flops(cfg, counted) == 4800 * per_row
    assert ref.kda_chunk_flops(cfg, counted, chunk=128) > 4800 * per_row
    assert ref.kda_chunk_bytes(cfg, counted) == \
        4800 * 5 * 4096 * 2 + 12 * 4 * 2 ** 20
    assert ref.kv_bytes_attended(cfg, counted, "bfloat16") == 1152 * 5000
    experts = 6 * 128 * 5_898_240 * 2
    assert ref.weight_bytes(cfg, "bfloat16", WORK) == \
        10 * (10_338_734_464 - experts) + 700 * 5_898_240 * 2
    assert ref.moe_expert_bytes(cfg, "bfloat16", WORK) == \
        700 * 5_898_240 * 2 + 12000 * 2 * 2560 * 2
    per_token = 39296 * 2560 + 6 * ref.attn_matrix_params(s, 0) \
        + ref.attn_matrix_params(s, 5) + 47_185_920 + 6 * (
            2560 * 512 + 512 + 5_898_240)
    assert ref.serve_flops(cfg, counted) == 2 * per_token * 1000 \
        + 2 * 5_898_240 * 12000 + ref.mla_attn_flops(cfg, counted) \
        + 7 * 32 * 128 * 128 * 1200 + 4800 * per_row
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.train_step_flops(cfg, 1, 1)
    with pytest.raises(ValueError, match="clamp's form"):
        ref.dims(dict(cfg, expert_swiglu_limit_list=[0] * 6 + [4]))


def test_the_configuration_states_its_cut_and_its_deployment():
    cfg = _published()
    assert cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "expert_swiglu_limit_list",
        "share_expert_swiglu_limit_list"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 128, 39296)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["first_k_dense_replace"],
            pub["num_experts"], pub["vocab_size"]) == (42, 2, 512, 157184)
    assert len(pub["expert_swiglu_limit_list"]) == 42
    assert cfg["expert_swiglu_limit_list"] == [0] * 7 == cfg[
        "share_expert_swiglu_limit_list"]
    # the floors of the model-configs guide §4
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    dep = cfg["deployment"]
    assert dep["chips"] == 28 and dep["pipeline_stages"] == 7
    assert dep["chips_sharing_a_layer"] == 4 and "rank 0" in dep[
        "this_chip"]
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
        pub["num_experts"]
    assert cfg["num_experts"] % (pub["num_experts"] // cfg["n_group"]) == 0
    for word in ("vision tower", "multi-token"):
        assert word in dep["left_out"]
    # every published width unchanged, as the catalog's row has it
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    assert cfg["source"] == row["source_url"]
    for key, val in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == val, key
        else:
            assert pub[key] == val, key
    for word in ("layer_group_size", "kda_safe_gate", "no_kda_lora",
                 "head_wise", "q_lora_rank", "use_qk_norm", "sigmoid",
                 "2 largest", "pre-norm", "silu", "A_log", "FLOAT32"):
        assert word in json.dumps(cfg["assumed"]), word
    e = cfg["engine"]
    assert (e["num_slots"], e["page_size"], e["token_budget"],
            e["decode_k"], e["pool_budget_bytes"], e["kv_dtype"],
            e["prefix_cache"]) == (96, 16, 2048, 8, 2 ** 30, "bfloat16",
                                   False)
    # the latent row is stored 640 lanes wide: pages the budget buys
    pages = e["pool_budget_bytes"] // (16 * 640 * 2)
    assert pages * 16 >= 96 * (3489 + 16)


# ---- the new readers --------------------------------------------------

def _reader(name):
    return load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


NEW = ("kda_time_share.decode", "kda_proj_time_share.decode",
       "kda_recur_roofline.decode", "kda_chunk_roofline.decode",
       "kda_chunked_share_of_rows.decode")


def test_the_manifest_lists_the_cell_and_its_readers(bench_root):
    """The cell, and its own five readers together and in their order
    wherever they stand: a cell added later goes after them."""
    with open(os.path.join(bench_root, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-vl", "manyseq_capped", 1)
    assert len(cell["why"]) <= 200
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    assert [p["name"] for p in mine] == list(NEW)
    at = m["per_layer"].index(mine[0])
    assert m["per_layer"][at:at + len(NEW)] == mine
    assert all(p["moves"] == "decode_tok_s" for p in mine)
    sarvam = {p["name"] for p in m["per_layer"]
              if "sarvam105b_longdoc_decode" in p.get("workloads", ())}
    assert sarvam == {p["name"] for p in m["per_layer"]
                      if CELL in p.get("workloads", ())} - set(NEW)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    """The parent's checkout (no `attn_kda` scope, no `kda_*` counter, no
    trace of this cell): every new reader returns None and does not
    raise."""
    import references as refs

    ctx = {"cfg": _published(), "ref": refs.load("sarvam_mla"),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"window": {"segments": [(0, 9)], "stats": {}},
                   "traced": {"segments": [(0, 9)], "stats": {},
                              "processed": 9, "iterations": 1},
                   "kv_dtype": "bfloat16"}}
    assert _reader(name)(ctx) is None


def test_the_new_readers_on_a_made_up_trace(tmp_path):
    from test_perfbench_sarvam_mla import _made_up_trace

    call = 'custom-call(...), custom_call_target="tpu_custom_call"'
    trace_dir = _made_up_trace(tmp_path, [
        ("jit(pure)/attn/attn_kda/kda_proj/dot_general", "fusion.1"),
        ("jit(pure)/attn/attn_kda/kda_proj/norm/mul", "fusion.2"),
        ("jit(pure)/attn/attn_kda/kda_chunk/while/body/dot_general",
         "fusion.3"),
        ("jit(pure)/attn/attn_kda/kda_recur/pallas_call", call),
        ("jit(pure)/attn/attn_kda/kda_out/dot_general", "fusion.4"),
        ("jit(pure)/attn/attn_mla/mla_walk/pallas_call", call),
        ("jit(pure)/mlp/moe/moe_experts/gmm", call),
        ("jit(pure)/lm_head/dot_general", "fusion.5"),
    ])
    cfg = _published()
    st = {"kda_rows_recurrent": 600, "kda_rows_chunked": 1800,
          "kda_chunk_launches": 6}
    work = {"segments": [(0, 400)], "processed": 400, "iterations": 1,
            "stats": st}
    ctx = {"cfg": cfg, "ref": ref, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"trace_dir": trace_dir, "traced": work, "window": work,
                   "kv_dtype": "bfloat16", "weight_dtype": "bfloat16"}}
    # eight operations of 50 ns: five under attn_kda, two of them forms
    assert _reader("kda_time_share.decode")(ctx) == pytest.approx(62.5)
    assert _reader("kda_proj_time_share.decode")(ctx) == pytest.approx(37.5)
    assert _reader("kda_recur_roofline.decode")(ctx) == pytest.approx(
        100 * 600 * 4 * 2 ** 20 / 819e9 / 50e-9)
    by_bytes = (1800 * 5 * 4096 * 2 + 6 * 4 * 2 ** 20) / 819e9
    by_flops = ref.kda_chunk_flops(cfg, work) / 197e12
    assert by_bytes > by_flops      # at these counts bandwidth bounds it
    assert _reader("kda_chunk_roofline.decode")(ctx) == pytest.approx(
        100 * by_bytes / 50e-9)
    assert _reader("kda_chunked_share_of_rows.decode")(ctx) == \
        pytest.approx(75.0)
