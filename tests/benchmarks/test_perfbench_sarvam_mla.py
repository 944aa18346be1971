"""Configuration `sarvam-105b`: the program (`text/models/sarvam_mla.py`
through `inference.LLMEngine`) held to the plain reference
(`benchmarks/references/sarvam_mla.py`) at small sizes on the CPU, the
share of a deployment tied to the whole layer, the program's counters
tied to the reference's counts, the cell's mix, and the reference's
arithmetic frozen."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import references
from builders import sarvam_mla as builder
from harness import traffic
from run import load_module, overlay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ref = references.load("sarvam_mla")


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sarvam-105b.json")) as f:
        return json.load(f)


def _small(**over):
    """The configuration's `rehearse` sizes in float32: a dense layer
    and four sparse ones, 4 heads of 16 + 8, latent 32, values 16, 8 of
    16 experts held, top-4."""
    cfg = _published()
    cfg = overlay(cfg, cfg["rehearse"])
    cfg["serve"]["weight_dtype"] = "float32"
    return overlay(cfg, over)


def _ids(n, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(
        np.int32)


@pytest.mark.parametrize("held", [8, 16])
def test_model_matches_the_reference_on_seeded_weights(held):
    """The eager forward (the EXPANDED form) against the reference's."""
    cfg = _small(num_experts=held)
    model = builder.build_model(cfg, 2 ** 31 + 7, "float32")
    w = ref.make_weights(cfg, 2 ** 31 + 7, "float32")
    ids = _ids(50)
    got = np.asarray(model(ids[None])._value[0])
    want = np.asarray(ref.logits_fn(cfg, w, ids))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def _serve(cfg, seed, requests, decode_k, **engine):
    from paddle_tpu import inference

    model = builder.build_model(cfg, seed, "float32")
    model.eval()
    e = dict(cfg["engine"], **engine)
    ecfg = inference.LLMEngineConfig.for_pool_budget(
        model.config, e["pool_budget_bytes"], page_size=16,
        kv_dtype="float32", num_slots=e["num_slots"],
        token_budget=e["token_budget"], max_model_len=e["max_model_len"],
        decode_k=decode_k)
    eng = inference.LLMEngine(model, ecfg)
    reqs = [eng.add_request(p, max_new_tokens=n) for p, n in requests]
    while eng.has_work():
        eng.step()
    return eng, [np.asarray(r.future.result()) for r in reqs]


def _gaps(cfg, w, toks, plen):
    """How far each served token's logit lies below the reference's
    best at its position (the full forward, no cache)."""
    lg = np.asarray(ref.logits_fn(cfg, w, toks[:-1].astype(np.int32)))
    rows = np.arange(plen - 1, len(toks) - 1)
    return lg[rows].max(-1) - lg[rows, toks[plen:]]


@pytest.mark.parametrize("decode_k", [1, 4])
def test_prefill_then_decode_through_the_latent_pages(decode_k):
    """Chunked prefill (token budget 16 < the prompts), then single
    ticks or fused windows, every row in the ABSORBED form over latent
    pages: every served token is the reference's best on ITS logits
    (gap 0 up to float32 rounding: 1e-4 of logits of order 1, the
    absorbed products' other order of summation), 5 requests over 4
    slots."""
    cfg = _small()
    seed = 2 ** 31 + 11
    ids = _ids(64, seed=1)
    requests = [(ids[:50], 30), (ids[:9], 20), (ids[10:43], 25),
                (ids[5:25], 8), (ids[:5], 40)]
    eng, outs = _serve(cfg, seed, requests, decode_k)
    w = ref.make_weights(cfg, seed, "float32")
    for (prompt, n), toks in zip(requests, outs):
        assert len(toks) == len(prompt) + n
        assert np.array_equal(toks[:len(prompt)], prompt)
        assert _gaps(cfg, w, toks, len(prompt)).max() < 1e-4
    assert eng.pool.num_live == 0 and eng.stats["latent_pages_live"] == 0
    eng.pool.assert_consistent()
    assert eng.compile_stats()["executables"] == 1
    assert eng.compile_stats().get("fused_executables", 1) == 1
    # ONE pool a layer, a row of 40 stored as 128 lanes, no head axis
    assert len(eng._kv) == 5
    assert eng._kv[0].shape[1:] == (16, 128)
    # every query row took the absorbed form, once a layer
    assert eng.stats["mla_rows_expanded"] == 0
    assert eng.stats["mla_rows_absorbed"] == 5 * sum(
        len(t) - 1 for t in outs)


def test_absorbed_equals_expanded():
    """The two forms are the same numbers: the step body's absorbed
    walk over latent pages against the eager forward's expanded
    attention, by logits, to float32 round-off."""
    from paddle_tpu.text.models.sarvam_mla import _rope_tables

    cfg = _small()
    model = builder.build_model(cfg, 3, "float32")
    c = model.config
    ids = _ids(40, seed=4)
    want = np.asarray(model(ids[None])._value[0])           # expanded
    kind = c.cache_kinds()[0]
    pools = [jnp.zeros(kind.pool_shape(8, 16), jnp.float32)
             for _ in range(c.num_layers)]
    pos = jnp.arange(40, dtype=jnp.int32)
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    logits, new, counters = model._paged_core(
        jnp.asarray(ids), pos, jnp.zeros((40,), jnp.int32), 16 + pos,
        tables, pos + 1, pos, pools)
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-5)
    # the row a token leaves: [c | k_r] normed and rotated, zeros beyond
    layer = model.layers[0]
    x = model.embed._value[ids].astype(jnp.float32)
    from paddle_tpu.text.models.laguna import _rms_norm
    n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
    lat, kr = model._latent_row(layer, n, _rope_tables(c, pos))
    row = np.asarray(new[0]).reshape(-1, 128)[16:56]
    np.testing.assert_allclose(row[:, :32], np.asarray(lat), atol=1e-6)
    np.testing.assert_allclose(row[:, 32:40], np.asarray(kr), atol=1e-6)
    assert not row[:, 40:].any()
    assert [int(v) for v in counters[3:]] == [5 * 40, 0, 5 * 40, 0]


def test_the_counters_equal_the_references_counts():
    """One request: positions 0 … len-2 each go through the model once,
    so the program's `moe_assignments_held` is the reference's routing
    (sigmoid + bias) counted over them, and the latent rows a step must
    read are the engine's own count a layer."""
    cfg = _small()
    seed = 5
    eng, (toks,) = _serve(cfg, seed, [(_ids(40, seed=3), 21)], 4)
    w = ref.make_weights(cfg, seed, "float32")
    s = ref.dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = f32(w["embed"])[toks[:-1]]
    held = 0
    for l, lw in enumerate(w["layers"]):
        if ref.is_sparse(s, l):
            h = x + ref.attention(
                s, cfg, ref.rms_norm(x, f32(lw["attn_norm"]), s["eps"]),
                lw, None)
            _, top = ref.route(
                s, ref.rms_norm(h, f32(lw["ffn_norm"]), s["eps"]),
                lw["router"], lw["router_bias"], None)
            held += int((np.asarray(top) < s["held"]).sum())
        x = ref.layer_forward(cfg, l, x, lw)
    n = len(toks) - 1
    assert eng.stats["moe_assignments"] == n * 4 * s["top_k"]
    assert eng.stats["moe_assignments_held"] == held
    # 40 prompt positions in chunks of 16, 16, 8 (the last samples token
    # 40), then 20 single rows: a step reads a slot's context once
    least = 16 + 32 + 40 + sum(range(41, 61))
    assert eng.stats["kv_positions_least_latent"] == least
    assert eng.stats["mla_rows_attended_least"] == 5 * least
    assert eng.stats["mla_rows_attended_single"] == 5 * sum(range(41, 61))
    work = {"stats": dict(eng.stats), "iterations": n, "processed": n,
            "segments": [(0, n)]}
    assert ref.mla_counts(cfg, work) == (5 * least,
                                         5 * sum(range(41, 61)))
    assert ref.kv_bytes_attended(cfg, work, "float32") == \
        40 * 4 * 5 * least
    reader = load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics",
        "mla_least_share_of_rows.decode.py"), "mla_least_share_of_rows")
    got = reader.read({"cfg": cfg, "ref": ref, "obs": {
        "window": work, "kv_dtype": "float32"}})
    assert got == pytest.approx(100.0 * least / (n * (n + 1) // 2))
    assert reader.read({"cfg": cfg, "ref": ref, "obs": {
        "window": {"segments": [(0, 9)], "stats": {}},
        "kv_dtype": "float32"}}) is None


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide §4: four chips hold 4 of a layer's 16
    experts each; the routed parts of the four shares and the shared
    expert counted ONCE add up to what the uncut reference gives for
    the whole feed-forward layer (sigmoid scores, selection by score +
    bias, weights by score)."""
    from paddle_tpu.nn import expert_layer

    cfg = _small(num_experts=16)
    s = ref.dims(cfg)
    w = ref.make_weights(cfg, 9, "float32")
    lw = w["layers"][1]
    n = jax.random.normal(jax.random.PRNGKey(1), (24, s["d"]), jnp.float32)
    shared, routed = ref.sparse_ffn(s, n, lw, None)
    whole = np.asarray(shared + routed)
    gate_up = jnp.concatenate([lw["e_gate"], lw["e_up"]], axis=2)
    weights, ids = expert_layer.route_top_k(
        n, lw["router"], s["top_k"], scoring="sigmoid",
        select_bias=lw["router_bias"])
    parts = jnp.zeros_like(n)
    touched = 0
    for first in range(0, 16, 4):
        part, counters = expert_layer.held_experts_ffn(
            n, weights, ids, jnp.ones((24,), bool),
            gate_up[first:first + 4], lw["e_down"][first:first + 4],
            first_expert=first)
        want = ref.sparse_ffn(s, n, lw, None, held=(first, 4))[1]
        np.testing.assert_allclose(np.asarray(s["scale"] * part),
                                   np.asarray(want), atol=2e-6)
        parts = parts + part
        touched += int(counters[1])
    assert touched == 24 * s["top_k"]       # every assignment, once
    np.testing.assert_allclose(
        np.asarray(shared + s["scale"] * parts), whole, atol=5e-6)


def test_the_bias_selects_and_does_not_weigh():
    """`route_top_k` with sigmoid scoring and a selection bias against a
    plain spelling; a large bias changes WHO is chosen and leaves the
    chosen's weights their scores'."""
    from paddle_tpu.nn import expert_layer

    rng = np.random.default_rng(2)
    x = rng.normal(size=(12, 16)).astype(np.float32)
    wr = rng.normal(size=(16, 10)).astype(np.float32)
    bias = np.zeros((10,), np.float32)
    bias[7] = 10.0                           # expert 7: always chosen
    w, ids = expert_layer.route_top_k(
        jnp.asarray(x), jnp.asarray(wr), 3, scoring="sigmoid",
        select_bias=jnp.asarray(bias))
    score = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wr)))
    for t in range(12):
        chosen = np.argsort(-(score[t] + bias))[:3]
        assert set(np.asarray(ids[t])) == set(chosen) and 7 in chosen
        want = score[t][np.asarray(ids[t])]
        np.testing.assert_allclose(np.asarray(w[t]), want / want.sum(),
                                   rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        expert_layer.route_top_k(jnp.asarray(x), jnp.asarray(wr), 3,
                                 scoring="tanh")


def test_the_cell_rehearses_on_the_cpu_and_reads_correct():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "sarvam105b_longdoc_decode", "--seed",
         str(2 ** 31 + 77), "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] and last["device"]["platform"] == "cpu"
    # counts only on the CPU: the counter-fed readers among them
    assert last["metrics"]["moe_rows_per_expert.decode"]["value"] >= 1.0
    assert 0 < last["metrics"]["mla_least_share_of_rows.decode"][
        "value"] <= 100
    assert last["metrics"]["preemptions"]["value"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert not any(n.endswith("_roofline.decode") or "time_share" in n
                   for n in last["metrics"])


# ---- the mix ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 32 + 99])
def test_every_block_of_eight_holds_each_prompt_length_once(seed):
    mix = traffic.load_mix(os.path.join(ROOT, "benchmarks", "traffic"),
                           "longdoc_capped")
    prompts = [10476, 10965, 11476, 12011, 12571, 13158, 13771, 14414]
    outputs = [1018, 1020, 1021, 1023, 1025, 1027, 1028, 1030]
    walk = traffic.closed_walk(mix, seed)
    sent = [next(walk) for _ in range(3 * 64)]
    for b in range(0, len(sent), 8):
        ps, os_ = zip(*sent[b:b + 8])
        assert sorted(ps) == prompts and sorted(os_) == outputs
    # the first wave (32 slots = four blocks): 395 368 prompt tokens
    assert sum(p for p, _o in sent[:32]) == 395_368
    # every request ends within ± 18 % of 12 k and a dozen tokens of 1 024
    assert all(abs(p / 12288 - 1) <= 0.18 and abs(o - 1024) <= 7
               for p, o in sent)
    e = _published()["engine"]
    longest = max(p + o for p, o in sent)
    assert longest == 15_444 <= e["max_model_len"]
    assert e["num_slots"] * (longest + e["page_size"]) == 494_720


# ---- the arithmetic, frozen ------------------------------------------

WORK = {"processed": 1000, "iterations": 10,
        "segments": [(0, 600), (3000, 400)],
        "stats": {"moe_assignments_held": 1500,
                  "moe_experts_touched": 300}}
ROWS = 600 * 601 // 2 + 400 * 3000 + 400 * 401 // 2


def test_arithmetic_at_the_published_sizes():
    cfg = _published()
    assert ref.param_count(cfg) == 4_535_402_752 == cfg["parameters_held"]
    assert ref.weight_bytes(cfg, "bfloat16") == 9_070_805_504
    s = ref.dims(cfg)
    assert ref.attn_matrix_params(s) == 94_633_984
    assert ref.norm_params(s) == 2 * 4096 + 192 + 512 + 64
    assert ref.ffn_params(s, 0) == 201_326_592
    assert ref.attn_matrix_params(s) + ref.norm_params(s) \
        + ref.ffn_params(s, 0) == 295_969_536
    assert ref.attn_matrix_params(s) + ref.norm_params(s) \
        + ref.ffn_params(s, 1) == 925_639_552
    assert ref.expert_params(s) == 25_165_824
    assert ref.kv_bytes_per_token(cfg, "bfloat16") == 5_760
    assert ref.positions(cfg) == 131_072
    assert ref.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert ref.rows_attended_by_row(WORK) == ROWS
    # without the program's counters every row is a step of its own:
    # absorbed, 2·64·1088 a row attended, 1 152 B a row a layer
    assert ref.absorbed_flops_per_row_attended(s) == 2 * 64 * 1088
    assert ref.expanded_flops_per_row_attended(s) == 2 * 64 * 320
    assert ref.expand_flops_per_cached_row(s) == 2 * 512 * 16384
    assert ref.mla_attn_flops(cfg, WORK) == 2 * 64 * 1088 * 5 * ROWS
    assert ref.kv_bytes_attended(cfg, WORK, "bfloat16") == \
        1152 * 5 * ROWS == ref.kv_bytes_attended_by_row(cfg, WORK,
                                                        "bfloat16")
    # with them: single rows absorbed, the chunks' rows the cheaper form
    st = dict(WORK["stats"], mla_rows_attended_least=5 * 5000,
              mla_rows_attended_single=5 * 1000)
    counted = dict(WORK, stats=st)
    assert ref.kv_bytes_attended(cfg, counted, "bfloat16") == \
        1152 * 5 * 5000
    chunk_rows = 5 * ROWS - 5 * 1000
    assert ref.mla_attn_flops(cfg, counted) == \
        2 * 64 * 1088 * 5 * 1000 + min(
            2 * 64 * 1088 * chunk_rows,
            2 * 64 * 320 * chunk_rows + 2 * 512 * 16384 * 5 * 4000)
    experts = 4 * 32 * 25_165_824 * 2
    assert ref.weight_bytes(cfg, "bfloat16", WORK) == \
        10 * (9_070_805_504 - experts) + 300 * 25_165_824 * 2
    assert ref.moe_expert_bytes(cfg, "bfloat16", WORK) == \
        300 * 25_165_824 * 2 + 1500 * 2 * 4096 * 2
    bare = dict(WORK, stats={})
    assert ref.weight_bytes(cfg, "bfloat16", bare) == 10 * 9_070_805_504
    assert ref.moe_expert_bytes(cfg, "bfloat16", bare) is None
    per_token = 65536 * 4096 + 5 * 94_633_984 + 201_326_592 + 4 * (
        4096 * 128 + 128 + 25_165_824)
    assert ref.serve_flops(cfg, WORK) == 2 * per_token * 1000 \
        + 2 * 25_165_824 * 1500 + ref.mla_attn_flops(cfg, WORK)
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.train_step_flops(cfg, 1, 1)
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.flash_attn_flops(cfg, 1, 1)


def test_the_configuration_states_its_cut_and_its_deployment():
    cfg = _published()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 65536)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (32, 128, 262144)
    # the floors of the model-configs guide §4
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["num_experts"] >= 8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    dep = cfg["deployment"]
    assert dep["chips"] == 32 and dep["pipeline_stages"] == 8
    assert dep["chips_sharing_a_layer"] == 4 and "rank 0" in dep[
        "this_chip"]
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
        pub["num_experts"]
    # every published width unchanged, as the catalog's row has it
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "sarvam-105b")
    assert cfg["source"] == row["source_url"]
    for key, val in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == val, key
    for word in ("q_lora_rank", "RMSNorm", "sigmoid", "bias", "pre-norm",
                 "silu", "deepseek_yarn"):
        assert word in json.dumps(cfg["assumed"])
    # the engine the issue states (its token budget the larger of the
    # two it allows: PERF.md §6, PR 33), and a pool its worst case fits
    e = cfg["engine"]
    assert (e["num_slots"], e["page_size"], e["token_budget"],
            e["decode_k"], e["max_model_len"], e["pool_budget_bytes"],
            e["prefix_cache"]) == (32, 16, 2048, 8, 16384, 3 * 2 ** 30,
                                   False)
    # the row is stored 640 lanes wide: pages the budget really buys
    pages = e["pool_budget_bytes"] // (5 * 16 * 640 * 2)
    assert pages * 16 >= 494_720


# ---- the new readers on a made-up trace -------------------------------

def _reader(name):
    return load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


def _made_up_trace(tmp_path, ops):
    """An XSpace with one device plane: `ops` [(op_name path, HLO
    text)], 50 ns each every 100 ns, inside the traced-window span."""
    from harness import span_reduce as sr, trace_reduce as tr

    space = sr.xspace_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = sr.SCOPE_STAT
    line = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for i, (path, text) in enumerate(ops, start=1):
        meta = dev.event_metadata.add(key=i).value
        meta.id, meta.name = i, text
        meta.stats.add(metadata_id=1, str_value=path)
        line.events.add(metadata_id=i, offset_ps=i * 100_000,
                        duration_ps=50_000)
    host = space.planes.add(id=2, name="/host:CPU")
    hline = host.lines.add(id=1, name="python3", timestamp_ns=1000)
    meta = host.event_metadata.add(key=1).value
    meta.id, meta.name = 1, tr.WINDOW_SPAN
    hline.events.add(metadata_id=1, offset_ps=0, duration_ps=2_000_000)
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space.SerializeToString())
    return str(tmp_path)


def test_the_new_readers_on_a_made_up_trace(tmp_path):
    from harness import scope_paths

    scope_paths.reduce_file.cache_clear()
    call = '%cc = bf16[32,64,512] custom-call(q, pool), ' \
        'custom_call_target="tpu_custom_call"'
    fusion = "%f = f32[8] fusion(x)"
    pre = "jit(pure)/while/body/attn/attn_mla/"
    trace_dir = _made_up_trace(tmp_path, [
        (pre + "mla_walk/pallas_call:", call),
        (pre + "mla_walk/scatter:", fusion),          # the block layout
        (pre + "mla_q/norm/mul:", fusion),
        (pre + "mla_latent_write/rope/mul:", fusion),
        (pre + "mla_out/dot_general:", fusion),
        (pre + "mla_expand/dot_general:", fusion),
        ("jit(pure)/while/body/mlp/moe/moe_experts/gmm:", call),
        ("jit(pure)/while/body/lm_head/dot_general:", fusion)])
    cfg = _published()
    work = {"processed": 1000, "iterations": 10,
            "segments": [(12000, 8)] * 32,
            "stats": {"moe_assignments_held": 1500,
                      "moe_experts_touched": 300,
                      "mla_rows_attended_least": 5 * 32 * 8 * 12004,
                      "mla_rows_attended_single": 5 * 32 * 8 * 12004}}
    ctx = {"cfg": cfg, "ref": ref, "chips": 1,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"trace_dir": trace_dir, "traced": work, "window": work,
                   "kv_dtype": "bfloat16", "weight_dtype": "bfloat16"}}
    # 8 operations of 50 ns: the walk's custom call is one of them; the
    # expert layer's custom call is not the walk's
    assert _reader("mla_walk_time_share.decode")(ctx) == \
        pytest.approx(100 / 8)
    # attn_mla holds six; less the walk's call and mla_expand: four
    assert _reader("mla_proj_time_share.decode")(ctx) == \
        pytest.approx(100 * 4 / 8)
    # decoding rows only: bound by bandwidth, 1 152 B a row a layer
    need = 1152 * 5 * 32 * 8 * 12004 / 819e9
    assert ref.mla_attn_flops(cfg, work) / 197e12 < need
    assert _reader("mla_walk_roofline.decode")(ctx) == \
        pytest.approx(100 * need / 50e-9)
    assert _reader("mla_least_share_of_rows.decode")(ctx) == \
        pytest.approx(100 * 12004 / 12004.5)
    # a program without the scopes (the parent): nothing to read
    scope_paths.reduce_file.cache_clear()
    bare = _made_up_trace(tmp_path / "bare", [
        ("jit(pure)/attn/dot_general:", fusion)])
    ctx["obs"]["trace_dir"] = bare
    for name in ("mla_walk_time_share.decode", "mla_walk_roofline.decode",
                 "mla_proj_time_share.decode"):
        assert _reader(name)(ctx) is None


def test_the_new_readers_on_the_recorded_window(tmp_path):
    """A window recorded on a TPU v5e (tests/benchmarks/data/
    README_spans.txt): the walk's custom calls are found under
    `mla_walk` and not confused with the expert layer's, the scopes'
    shares add up to no more than the whole, no row expands."""
    import gzip

    from harness import scope_paths, span_reduce, trace_reduce

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(
            ROOT, "tests", "benchmarks", "data",
            "sarvam_spans_v5e_400ms.xplane.pb.gz")) as src:
        (d / "host.xplane.pb").write_bytes(src.read())
    scope_paths.reduce_file.cache_clear()
    cfg = _published()
    # what a window of that length might have done (made up; the shares
    # of a peak below are only asked to read something): 2 ticks of
    # 2 048 prompt rows from 6 k of context, a window of 8 × 14 rows
    work = {"processed": 2 * 2048 + 112, "iterations": 10,
            "segments": [(6000, 4096)] + [(13000, 8)] * 14,
            "stats": {"moe_assignments_held": 4 * 2 * (2 * 2048 + 112),
                      "moe_experts_touched": 4 * 32 * 2 + 8 * 4 * 25,
                      "mla_rows_attended_least": 5 * (
                          8048 + 10096 + 14 * 8 * 13004),
                      "mla_rows_attended_single": 5 * 14 * 8 * 13004}}
    trace = trace_reduce.reduce_trace(trace_reduce.find_xplane(
        str(tmp_path)))
    ctx = {"cfg": cfg, "ref": ref, "chips": 1, "cell": {}, "trace": trace,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "obs": {"trace_dir": str(tmp_path), "traced": work,
                   "window": work, "kv_dtype": "bfloat16",
                   "weight_dtype": "bfloat16", "decode_k": 8}}
    walk = _reader("mla_walk_time_share.decode")(ctx)
    proj = _reader("mla_proj_time_share.decode")(ctx)
    moe = _reader("moe_time_share.decode")(ctx)
    head = _reader("lm_head_time_share.decode")(ctx)
    none = _reader("unscoped_time_share.decode")(ctx)
    assert 20 < walk < 60 and 5 < proj < 25 and 20 < moe < 60
    assert 0 < head < 10 and 0 <= none < 8
    assert walk + proj + moe + head + none <= 100.0
    # no served row takes the expanded form: nothing under its scope
    assert scope_paths.seconds(ctx, ("mla_expand",)) is None
    assert _reader("mla_walk_roofline.decode")(ctx) > 0
    assert _reader("moe_expert_roofline.decode")(ctx) > 0
    # the walk is one custom call a layer a program, the experts' two
    red = scope_paths.reduction(ctx)
    calls = [p for p, text, _s in red["ops"]
             if "tpu_custom_call" in text]
    assert {bool(p & {"mla_walk"}) for p in calls} == {True, False}
    assert all(("mla_walk" in p) != ("moe_experts" in p) for p in calls)
