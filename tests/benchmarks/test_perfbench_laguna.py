"""Configuration `laguna-s-2.1`: the program (`text/models/laguna.py`
through `inference.LLMEngine`) held to the plain reference
(`benchmarks/references/laguna.py`) at small sizes on the CPU, the share
of a deployment tied to the whole layer, the program's counters tied to
the reference's counts, and the reference's arithmetic frozen."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import references
from builders import laguna as builder
from run import overlay

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ref = references.load("laguna")


def _published():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        return json.load(f)


def _small(**over):
    """The configuration's `rehearse` sizes in float32: a dense layer
    and two periods, window 16, 4 / 6 query heads over 2 KV heads, 8 of
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
    cfg = _small(num_experts=held)
    model = builder.build_model(cfg, 2 ** 31 + 7, "float32")
    w = ref.make_weights(cfg, 2 ** 31 + 7, "float32")
    ids = _ids(50)                      # three windows long
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
        model.config, {"full": e["pool_budget_bytes"],
                       "window": e["window_pool_budget_bytes"]},
        page_size=16, kv_dtype="float32", num_slots=e["num_slots"],
        token_budget=e["token_budget"], max_model_len=e["max_model_len"],
        decode_k=decode_k)
    eng = inference.LLMEngine(model, ecfg)
    reqs = [eng.add_request(p, max_new_tokens=n) for p, n in requests]
    most = 0
    while eng.has_work():
        eng.step()
        for ks in eng._extra:
            for r in eng._slots:
                if r is not None:
                    most = max(most, len(r.kind_pages[ks.index]))
    return eng, [np.asarray(r.future.result()) for r in reqs], most


def _gaps(cfg, w, toks, plen):
    """How far each served token's logit lies below the reference's
    best at its position (the full forward, no cache)."""
    lg = np.asarray(ref.logits_fn(cfg, w, toks[:-1].astype(np.int32)))
    rows = np.arange(plen - 1, len(toks) - 1)
    return lg[rows].max(-1) - lg[rows, toks[plen:]]


@pytest.mark.parametrize("decode_k", [1, 4])
def test_prefill_then_decode_through_both_pools(decode_k):
    """Chunked prefill (token budget 16 < the prompts), then single
    ticks or fused windows, through the full and the window pool: every
    served token is the reference's best on ITS logits (gap 0 up to
    float32 rounding), 5 requests over 4 slots."""
    cfg = _small()
    seed = 2 ** 31 + 11
    ids = _ids(64, seed=1)
    requests = [(ids[:50], 30), (ids[:9], 20), (ids[10:43], 25),
                (ids[5:25], 8), (ids[:5], 40)]
    eng, outs, most = _serve(cfg, seed, requests, decode_k)
    w = ref.make_weights(cfg, seed, "float32")
    for (prompt, n), toks in zip(requests, outs):
        assert len(toks) == len(prompt) + n
        assert np.array_equal(toks[:len(prompt)], prompt)
        assert _gaps(cfg, w, toks, len(prompt)).max() < 1e-4
    # the window pool holds window / page + 2 pages a slot at most,
    # whatever the context (80 positions = 5 windows here)
    assert 0 < most <= 16 // 16 + 2
    assert eng.stats["window_pages_freed"] > 0
    assert eng.pool.num_live == 0 and eng._extra[0].pool.num_live == 0
    eng.pool.assert_consistent()
    eng._extra[0].pool.assert_consistent()
    assert eng.compile_stats()["executables"] == 1
    assert eng.compile_stats().get("fused_executables", 1) == 1


def test_the_counters_equal_the_references_counts():
    """One request, single ticks: positions 0 … len-2 each go through
    the model once, so the program's `moe_assignments` and `moe_
    assignments_held` are the reference's routing counted over them."""
    cfg = _small()
    seed = 5
    eng, (toks,), _ = _serve(cfg, seed, [(_ids(21, seed=3), 12)], 1)
    w = ref.make_weights(cfg, seed, "float32")
    s = ref.dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    x = f32(w["embed"])[toks[:-1]]
    held = 0
    for l, lw in enumerate(w["layers"]):
        if s["mlps"][l] != "dense":
            h = x + ref.attention(
                s, cfg, l, ref.rms_norm(x, f32(lw["attn_norm"]), s["eps"]),
                lw, None)
            _, top = ref.route(
                s, ref.rms_norm(h, f32(lw["ffn_norm"]), s["eps"]),
                lw["router"], None)
            held += int((np.asarray(top) < s["held"]).sum())
        x = ref.layer_forward(cfg, l, x, lw)
    n = len(toks) - 1
    assert eng.stats["moe_assignments"] == n * 8 * s["top_k"]
    assert eng.stats["moe_assignments_held"] == held
    assert 0 < eng.stats["moe_experts_touched"] <= held
    work = {"stats": dict(eng.stats), "iterations": n, "processed": n,
            "segments": [(0, n)]}
    assert ref.moe_counts(work) == (held, eng.stats["moe_experts_touched"])


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide §4: four chips hold 4 of a layer's 16
    experts each; the routed parts of the four shares and the shared
    expert counted ONCE add up to what the uncut reference gives for
    the whole feed-forward layer."""
    from paddle_tpu.nn import expert_layer

    cfg = _small(num_experts=16)
    s = ref.dims(cfg)
    w = ref.make_weights(cfg, 9, "float32")
    lw = w["layers"][1]
    n = jax.random.normal(jax.random.PRNGKey(1), (24, s["d"]), jnp.float32)
    shared, routed = ref.sparse_ffn(s, n, lw, None)
    whole = np.asarray(shared + routed)
    gate_up = jnp.concatenate([lw["e_gate"], lw["e_up"]], axis=2)
    weights, ids = expert_layer.route_top_k(n, lw["router"], s["top_k"])
    parts = jnp.zeros_like(n)
    touched = 0
    for first in range(0, 16, 4):
        part, counters = expert_layer.held_experts_ffn(
            n, weights, ids, jnp.ones((24,), bool),
            gate_up[first:first + 4], lw["e_down"][first:first + 4],
            first_expert=first)
        # a share is the reference given that share, too
        want = ref.sparse_ffn(s, n, lw, None, held=(first, 4))[1]
        np.testing.assert_allclose(np.asarray(s["scale"] * part),
                                   np.asarray(want), atol=2e-6)
        parts = parts + part
        touched += int(counters[1])
    assert touched == 24 * s["top_k"]       # every assignment, once
    np.testing.assert_allclose(
        np.asarray(shared + s["scale"] * parts), whole, atol=5e-6)


def test_a_vocabulary_slice_is_those_rows_of_the_whole_head():
    cfg = _small()
    half = _small(vocab_size=128)
    whole_m = builder.build_model(cfg, 3, "float32")
    half_m = builder.build_model(half, 3, "float32")
    for name in ("embed", "lm_head"):
        getattr(half_m, name)._value = getattr(whole_m, name)._value[:128]
    for a, b in zip(whole_m.layers.parameters(), half_m.layers.parameters()):
        b._value = a._value
    half_m.final_norm._value = whole_m.final_norm._value
    ids = _ids(40, vocab=128)
    np.testing.assert_allclose(
        np.asarray(half_m(ids[None])._value),
        np.asarray(whole_m(ids[None])._value)[..., :128], atol=1e-6)


def test_the_cell_rehearses_on_the_cpu_and_reads_correct():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_ENABLE_X64", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "laguna_s21_decode", "--seed", str(2 ** 31 + 77),
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] and last["device"]["platform"] == "cpu"
    # counts only on the CPU: the counter-fed reader among them
    assert last["metrics"]["moe_rows_per_expert.decode"]["value"] >= 1.0
    assert 0 < last["metrics"]["kv_least_share_of_rows.decode"][
        "value"] <= 100
    assert last["metrics"]["preemptions"]["value"] == 0
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert not any(n.endswith("_roofline.decode") or "time_share" in n
                   for n in last["metrics"])


# ---- the arithmetic, frozen ------------------------------------------

WORK = {"processed": 1000, "iterations": 10,
        "segments": [(0, 600), (3000, 400)],
        "stats": {"moe_assignments_held": 1500,
                  "moe_experts_touched": 300}}


def test_arithmetic_at_the_published_sizes():
    cfg = _published()
    assert ref.param_count(cfg) == 4_681_933_824 == cfg["parameters_held"]
    assert ref.weight_bytes(cfg, "bfloat16") == 9_363_867_648
    s = ref.dims(cfg)
    assert [ref.attn_params(s, l) for l in (0, 1)] == [44_187_648,
                                                       63_135_744]
    assert ref.ffn_params(s, 0) == 113_246_208
    assert ref.expert_params(s) == 9_437_184
    assert ref.kv_bytes_per_token(cfg, "bfloat16") == 16_384
    assert ref.window_kv_bytes_per_token(cfg, "bfloat16") == 36_864
    assert ref.positions(cfg) == 1_048_576
    # a full layer attends every earlier position, a window layer 512
    assert ref.attended(s, WORK, 0) == 600 * 601 // 2 + 400 * 3000 \
        + 400 * 401 // 2
    assert ref.attended(s, WORK, 1) == 512 * 513 // 2 + 88 * 512 \
        + 400 * 512
    assert ref.kv_bytes_attended(cfg, WORK, "bfloat16") == 4096 * (
        4 * ref.attended(s, WORK, 0) + 9 * ref.attended(s, WORK, 1))
    experts = 12 * 32 * 9_437_184 * 2
    assert ref.weight_bytes(cfg, "bfloat16", WORK) == \
        10 * (9_363_867_648 - experts) + 300 * 9_437_184 * 2
    assert ref.moe_expert_bytes(cfg, "bfloat16", WORK) == \
        300 * 9_437_184 * 2 + 1500 * 2 * 3072 * 2
    bare = dict(WORK, stats={})
    assert ref.weight_bytes(cfg, "bfloat16", bare) == 10 * 9_363_867_648
    assert ref.moe_expert_bytes(cfg, "bfloat16", bare) is None
    assert ref.serve_flops(cfg, WORK) == 2_067_185_664_000 + \
        ref.serve_flops(cfg, dict(WORK, processed=0, stats={
            "moe_assignments_held": 0, "moe_experts_touched": 0}))
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.train_step_flops(cfg, 1, 1)
    with pytest.raises(NotImplementedError, match="no training cell"):
        ref.flash_attn_flops(cfg, 1, 1)


def test_arithmetic_at_the_rehearse_sizes():
    cfg = _small()
    assert ref.param_count(cfg) == 647_360
    assert ref.kv_bytes_per_token(cfg, "float32") == 3 * 2 * 2 * 16 * 4
    assert ref.window_kv_bytes_per_token(cfg, "float32") == \
        6 * 2 * 2 * 16 * 4
    s = ref.dims(cfg)
    assert ref.attended(s, WORK, 1) == 16 * 17 // 2 + 584 * 16 + 400 * 16


def test_the_configuration_states_its_cut_and_its_deployment():
    cfg = _published()
    assert cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "gating_types",
        "num_attention_heads_per_layer"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (13, 32, 12544)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 256, 100352)
    # the floors of the model-configs guide §4
    assert cfg["vocab_size"] * 8 >= pub["vocab_size"]
    assert cfg["num_experts"] >= 8
    assert cfg["layer_types"][1:] == ["sliding_attention"] * 3 + [
        "full_attention"] + (["sliding_attention"] * 3
                             + ["full_attention"]) * 2
    dep = cfg["deployment"]
    assert dep["chips"] == 32 and dep["chips_sharing_a_layer"] == 8
    assert dep["chips_sharing_a_layer"] * cfg["num_experts"] == \
        pub["num_experts"]
    # every width as published
    for key, val in (("hidden_size", 3072), ("intermediate_size", 12288),
                     ("head_dim", 128), ("num_key_value_heads", 8),
                     ("moe_intermediate_size", 1024),
                     ("shared_expert_intermediate_size", 1024),
                     ("num_experts_per_tok", 10),
                     ("sliding_window", 512)):
        assert cfg[key] == val
    for word in ("pre-norm", "silu", "softmax", "sigmoid", "ungated",
                 "none"):
        assert word in json.dumps(cfg["assumed"])


def test_attended_bytes_follow_the_programs_least_positions():
    """With the engine's `kv_positions_least_*` counters a prefill
    chunk's context counts once a step; without them every token counts
    its own."""
    cfg = _published()
    st = dict(WORK["stats"], kv_positions_least_full=1000,
              kv_positions_least_window=300)
    assert ref.kv_bytes_attended(cfg, dict(WORK, stats=st), "bfloat16") \
        == 4096 * (4 * 1000 + 9 * 300)
    eng, _, _ = _serve(_small(), 5, [(_ids(40, seed=3), 6)], 1)
    # 40 prompt positions in chunks of 16 (the token budget), then 5
    # single rows: a full layer's spans are the chunk ENDS 16, 32, 40
    # then 41 … 45; a window layer (16) reads 16, 31, 23, then 16 a row
    assert eng.stats["kv_positions_least_full"] == 16 + 32 + 40 + sum(
        range(41, 46))
    assert eng.stats["kv_positions_least_window"] == 16 + 31 + 23 + 5 * 16


def test_least_positions_are_the_references_row_count_in_decode():
    """Where every step is one row a slot (fused windows after the
    prefill), the span a step must read IS what each row attends: the
    program's count equals the reference's own from the segments, and
    `kv_least_share_of_rows.decode` reads 100. A prefill chunk's rows
    share their context, so over the whole request it reads less."""
    from run import load_module

    reader = load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics",
        "kv_least_share_of_rows.decode.py"), "kv_least_share_of_rows")
    cfg = _small()
    eng, (toks,), _ = _serve(cfg, 5, [(_ids(40, seed=3), 21)], 4)
    whole = {"stats": dict(eng.stats), "segments": [(0, len(toks) - 1)]}
    # the prompt's 40 positions went in chunks of 16, 16, 8; the tick
    # that ends the prefill samples token 40, and 20 rows follow
    prefill = {"kv_positions_least_full": 16 + 32 + 40,
               "kv_positions_least_window": 16 + 31 + 23}
    decode = {"stats": {k: whole["stats"][k] - v
                        for k, v in prefill.items()},
              "segments": [(40, 20)]}
    for work, share in ((decode, 100.0), (whole, None)):
        least = ref.kv_bytes_attended(cfg, work, "float32")
        rows = ref.kv_bytes_attended_by_row(cfg, work, "float32")
        got = reader.read({"cfg": cfg, "ref": ref, "obs": {
            "window": work, "kv_dtype": "float32"}})
        assert got == pytest.approx(100.0 * least / rows)
        assert (got == share) if share else (50 < got < 100)
    assert reader.read({"cfg": cfg, "ref": ref, "obs": {
        "window": {"segments": [(0, 9)], "stats": {}},
        "kv_dtype": "float32"}}) is None
