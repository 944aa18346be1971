"""Quantized runtime (quantization/runtime.py — the ISSUE-4 tentpole,
int4 extended in ISSUE-12).

Covers four legs: int8 weight-only serving (dynamic-act int8 matmul
parity, state_dict carries int8 buffers), the int8 paged KV cache
(bounded attention error, Pallas dequant-on-gather interpret parity,
engine greedy token-match ≥ 0.98, ≥ 1.8× sequence capacity at equal
pool bytes), the packed-int4 path (nibble pack/unpack roundtrip,
Int4WeightOnlyLinear bounded logits parity via the MSE clip search,
int4-KV engine logits within tolerance, ≥ 1.8×-vs-int8 equal-bytes
capacity, Pallas unpack-in-VMEM parity), and the int8 wire codec
(roundtrip error/savings, bf16 master-copy guard, slow 2-proc
quantized all-reduce convergence).
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.nn import functional as F
from paddle_tpu.quantization import runtime as qrt
from paddle_tpu.text.models import GPTForCausalLM
from paddle_tpu.text.models.gpt import gpt_tiny

pytestmark = pytest.mark.quant

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _serial_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield


# --------------------------------------------------------------------
# int8 weight-only serving
# --------------------------------------------------------------------

def test_int8_weight_only_linear_matches_fp32():
    rng = np.random.default_rng(0)
    paddle.seed(7)
    lin = nn.Linear(64, 48)
    q = qrt.Int8WeightOnlyLinear(lin)
    x = paddle.to_tensor(rng.standard_normal((16, 64)).astype(np.float32))
    ref = lin(x).numpy()
    out = q(x).numpy()
    # weight int8 + dynamic per-row act int8: ~1% of dynamic range
    assert np.abs(out - ref).max() <= 0.03 * np.abs(ref).max() + 1e-3
    assert str(q.weight_q._value.dtype) == "int8"
    assert q.w_step._value.shape == (1, 48)
    # buffers ride state_dict (the compiled-step weight-threading path)
    sd = q.state_dict()
    assert "weight_q" in sd and "w_step" in sd


def test_quantize_model_int8_gpt_logits_close():
    paddle.seed(30)
    cfg = gpt_tiny()
    ref_model = GPTForCausalLM(cfg)
    ref_model.eval()
    rng = np.random.default_rng(5)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int64))
    ref = ref_model(ids).numpy()

    paddle.seed(30)
    model = GPTForCausalLM(cfg)
    report = qrt.quantize_model_int8(model)
    # every decoder Linear swapped: qkv/proj/fc1/fc2 × num_layers
    assert report["layers"] == 4 * cfg.num_layers
    assert report["weight_bytes_int8"] < 0.3 * report["weight_bytes_fp"]
    out = model(ids).numpy()
    rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-6)
    assert rel < 0.05, rel
    # int8 buffers are IN state_dict → compiled steps carry int8 weights
    int8_keys = [k for k, v in model.state_dict().items()
                 if str(v._value.dtype) == "int8"]
    assert len(int8_keys) == 4 * cfg.num_layers
    # embeddings / tied head stay float
    assert "int8" not in str(model.gpt.wte.weight._value.dtype)


def test_int8_weight_only_engine_serves():
    """The full quantized serving stack: int8 weights AND int8 KV pool
    through the ONE compiled decode executable."""
    paddle.seed(30)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    qrt.quantize_model_int8(model)
    rng = np.random.default_rng(11)
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, token_budget=8, max_model_len=64,
        kv_dtype="int8"))
    reqs = [eng.add_request(rng.integers(0, cfg.vocab_size, (L,)),
                            max_new_tokens=6) for L in (5, 11)]
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 200
    for r in reqs:
        out = r.future.result(timeout=0)
        assert len(out) == r.prompt_len + 6
    assert eng.compile_stats() == {"executables": 1}
    # int8 pools AND their fp32 scale planes ride one donated pytree —
    # the donation probe must see every leaf aliased (a dropped alias
    # = per-tick pool copies, the PR-2 bug shape)
    don = eng.compile_stats(check_donation=True)["donation"]
    assert don["held"] and don["expected"] == don["aliased"], don


# --------------------------------------------------------------------
# int8 paged KV cache
# --------------------------------------------------------------------

def _build_quant_paged_case(rng, page_size, lens, H=2, D=16,
                            extra_tokens=()):
    """Int8 variant of test_llm_engine._build_paged_case: contiguous
    ground-truth K/V quantized row-by-row into shuffled int8 pools with
    per-row scale planes."""
    import jax.numpy as jnp

    S = len(lens)
    P = page_size
    MP = -(-max(lens) // P)
    N = sum(-(-int(l) // P) for l in lens) + 1
    kc = rng.standard_normal((S, MP * P, H, D)).astype(np.float32)
    vc = rng.standard_normal((S, MP * P, H, D)).astype(np.float32)
    pool_k = np.zeros((N, P, H, D), np.int8)
    pool_v = np.zeros((N, P, H, D), np.int8)
    sk = np.zeros((N, P, H), np.float32)
    sv = np.zeros((N, P, H), np.float32)
    pt = np.zeros((S, MP), np.int32)
    perm = list(rng.permutation(np.arange(1, N)))
    for s in range(S):
        for j in range(-(-int(lens[s]) // P)):
            pid = int(perm.pop())
            pt[s, j] = pid
            kq, ks = qrt.quantize_kv_rows(
                jnp.asarray(kc[s, j * P:(j + 1) * P]))
            vq, vs = qrt.quantize_kv_rows(
                jnp.asarray(vc[s, j * P:(j + 1) * P]))
            pool_k[pid], sk[pid] = np.asarray(kq), np.asarray(ks)
            pool_v[pid], sv[pid] = np.asarray(vq), np.asarray(vs)
    sid = list(range(S)) + [s for s, _ in extra_tokens] + [0]
    klen = [int(l) for l in lens] + [k for _, k in extra_tokens] + [0]
    T = len(sid)
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    return (q, pool_k, pool_v, sk, sv, pt, np.asarray(sid, np.int32),
            np.asarray(klen, np.int32), kc, vc)


def _dense_reference(q, kc, vc, sid, klen):
    T, H, D = q.shape
    out = np.zeros((T, H, D))
    for t in range(T):
        L = int(klen[t])
        if L == 0:
            continue
        K = kc[sid[t], :L].astype(np.float64)
        V = vc[sid[t], :L].astype(np.float64)
        sc = np.einsum("hd,lhd->hl", q[t].astype(np.float64),
                       K) / math.sqrt(D)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        out[t] = np.einsum("hl,lhd->hd", w, V)
    return out


def test_paged_attention_int8_kv_bounded_error():
    """Dequant-on-gather attention over an int8 pool tracks the fp32
    dense reference within the per-row quantization budget — the
    bounded per-layer error leg of the parity suite."""
    rng = np.random.default_rng(23)
    (q, pk, pv, sk, sv, pt, sid, klen, kc,
     vc) = _build_quant_paged_case(rng, 16, [40, 19, 1],
                                   extra_tokens=[(0, 7), (1, 13)])
    out = F.paged_attention(
        paddle.to_tensor(q), paddle.to_tensor(pk), paddle.to_tensor(pv),
        paddle.to_tensor(pt), paddle.to_tensor(sid),
        paddle.to_tensor(klen), k_scales=paddle.to_tensor(sk),
        v_scales=paddle.to_tensor(sv)).numpy()
    ref = _dense_reference(q, kc, vc, sid, klen)
    # per-row absmax int8: elementwise error ≤ absmax/254; through the
    # softmax-weighted sum the output stays within ~1% of the kv range
    assert np.abs(out - ref).max() < 0.02 * np.abs(vc).max()
    assert np.all(out[-1] == 0)  # padding token exactly zero


def test_pallas_int8_paged_attention_interpret_parity():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    rng = np.random.default_rng(29)
    (q, pk, pv, sk, sv, pt, sid, klen, _,
     _) = _build_quant_paged_case(rng, 16, [40, 19, 1],
                                  extra_tokens=[(0, 7), (1, 13)])
    jnp_out = F.paged_attention(
        paddle.to_tensor(q), paddle.to_tensor(pk), paddle.to_tensor(pv),
        paddle.to_tensor(pt), paddle.to_tensor(sid),
        paddle.to_tensor(klen), k_scales=paddle.to_tensor(sk),
        v_scales=paddle.to_tensor(sv)).numpy()
    pl_out = np.asarray(pak.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(pt), jnp.asarray(sid), jnp.asarray(klen),
        k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv),
        interpret=True))
    np.testing.assert_allclose(pl_out, jnp_out, rtol=1e-5, atol=1e-6)


def _tiny_model(seed=30):
    paddle.seed(seed)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def test_engine_int8_kv_greedy_token_match():
    """The parity-suite acceptance: int8-KV engine greedy decode vs the
    fp32 generate() reference — ≥ 98% of generated tokens identical on
    the test GPT. Aggregated over THREE model seeds (seed 30 is known to
    carry a near-tie argmax that the quantization noise flips — the
    bound is demonstrated through it, not around it)."""
    rng = np.random.default_rng(41)
    gen = 12
    total = match = 0
    for mseed in (30, 24, 31):
        cfg, model = _tiny_model(seed=mseed)
        prompts = [rng.integers(0, cfg.vocab_size, (L,))
                   for L in (5, 13, 8, 21, 11)]
        eng = LLMEngine(model, LLMEngineConfig(
            num_slots=3, page_size=16, token_budget=8, max_model_len=64,
            kv_dtype="int8"))
        assert eng.kv_quantized and eng.kv_dtype == "int8"
        reqs = [eng.add_request(p, max_new_tokens=gen) for p in prompts]
        steps = 0
        while eng.has_work():
            eng.step()
            eng.pool.assert_consistent()
            steps += 1
            assert steps < 500
        for p, r in zip(prompts, reqs):
            got = r.future.result(timeout=0)
            ref = model.generate(
                paddle.to_tensor(np.asarray(p)[None].astype(np.int64)),
                max_new_tokens=gen).numpy()[0]
            assert got.shape == ref.shape
            total += gen
            match += int((got[len(p):] == ref[len(p):]).sum())
        assert eng.pool.num_live == 0
        assert eng.compile_stats() == {"executables": 1}
    assert match / total >= 0.98, f"{match}/{total}"


def test_engine_int8_admits_more_sequences_at_equal_bytes():
    """Equal page-pool BYTE budget, fp32 vs int8: the int8 engine must
    ADMIT ≥ 1.8× the concurrent sequences (scale planes included in its
    byte accounting — this is ~3.5× at head_dim 32, 1.8 is the floor)."""
    cfg, model = _tiny_model(seed=33)
    budget = 512 * 1024
    prompt_len = 30
    rng = np.random.default_rng(43)

    def admitted(kv_dtype):
        ecfg = LLMEngineConfig.for_pool_budget(
            cfg, budget, page_size=16, kv_dtype=kv_dtype, num_slots=64,
            max_model_len=48)
        eng = LLMEngine(model, ecfg)
        assert eng.pool_bytes() <= budget * 1.25  # the budget is real
        for _ in range(64):
            eng.add_request(
                rng.integers(0, cfg.vocab_size, (prompt_len,)),
                max_new_tokens=4)
        eng.step()  # one tick: admission + plan + decode
        live = sum(r is not None for r in eng._slots)
        return live, eng

    fp_live, fp_eng = admitted(None)
    q_live, q_eng = admitted("int8")
    assert str(fp_eng.kv_dtype) == "float32"
    assert q_live >= 1.8 * fp_live, (q_live, fp_live)
    # and the byte accounting agrees with the gauge/metrics surface
    assert q_eng.metrics()["kv_pool_bytes"] == q_eng.pool_bytes()


def test_kv_dtype_env_knob(monkeypatch):
    cfg, model = _tiny_model(seed=34)
    monkeypatch.setenv("PT_KV_DTYPE", "int8")
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=32))
    assert eng.kv_quantized
    assert str(eng._kv[0].dtype) == "int8"
    assert len(eng._kv_scales) == len(eng._kv)
    monkeypatch.setenv("PT_KV_DTYPE", "bfloat16")
    eng2 = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=32))
    assert not eng2.kv_quantized
    assert str(eng2._kv[0].dtype) == "bfloat16"
    monkeypatch.setenv("PT_KV_DTYPE", "float8")
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(model, LLMEngineConfig(
            num_slots=2, page_size=16, max_model_len=32))


# --------------------------------------------------------------------
# int4: packed weights + packed KV (the ISSUE-12 lower-bit axis)
# --------------------------------------------------------------------

def test_pack_unpack_int4_roundtrip_and_odd_axis():
    import jax.numpy as jnp

    rng = np.random.default_rng(50)
    codes = rng.integers(-7, 8, (16, 6)).astype(np.int8)
    for axis in (0, -1):
        packed = qrt.pack_int4(jnp.asarray(codes), axis=axis)
        assert packed.shape[axis] == codes.shape[axis] // 2
        back = np.asarray(qrt.unpack_int4(packed, axis=axis))
        np.testing.assert_array_equal(back, codes)
    with pytest.raises(ValueError, match="odd"):
        qrt.pack_int4(jnp.asarray(codes[:15]), axis=0)


def test_quantize_kv_rows_int4_bounded_roundtrip():
    import jax.numpy as jnp

    rng = np.random.default_rng(51)
    x = rng.standard_normal((5, 4, 8)).astype(np.float32)
    q, s = qrt.quantize_kv_rows_int4(jnp.asarray(x))
    assert q.shape == (5, 4, 4) and s.shape == (5, 4)
    deq = np.asarray(qrt.dequantize_kv_int4(q, s))
    # per-(token, head) absmax at qmax 7: error <= row absmax / 14
    row_absmax = np.abs(x).max(axis=-1, keepdims=True)
    assert np.all(np.abs(deq - x) <= row_absmax / 14 + 1e-6)


def test_int4_weight_only_linear_parity_and_packing():
    """Bounded logits parity of the packed-int4 Linear + the packing
    contract: the buffer is HALF the int8 bytes, state_dict carries
    it, and odd in_features is rejected loudly (nibble pairing)."""
    paddle.seed(52)
    lin = nn.Linear(64, 32)
    q4 = qrt.Int4WeightOnlyLinear(lin)
    x = paddle.to_tensor(np.random.default_rng(53).standard_normal(
        (4, 64)).astype(np.float32))
    ref = lin(x).numpy()
    out = q4(x).numpy()
    # 15-level grid + MSE-searched per-channel scales: a few percent
    # of the output range (int8's bound is ~1%; int4 trades precision
    # for bytes — the regression pin is the bound, not exactness)
    assert np.abs(out - ref).max() <= 0.10 * np.abs(ref).max()
    assert q4.weight_q._value.shape == (32, 32)  # [in/2, out] packed
    assert str(q4.weight_q._value.dtype) == "int8"
    assert int(q4.weight_q._value.nbytes) == 64 * 32 // 2
    assert "weight_q" in q4.state_dict()
    with pytest.raises(ValueError, match="odd"):
        qrt.Int4WeightOnlyLinear(nn.Linear(7, 4))


def test_quantize_model_int4_swaps_and_skips_odd():
    paddle.seed(54)

    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(64, 32)
            self.b = nn.Linear(32, 7)
            self.c = nn.Linear(7, 4)   # odd in — must be skipped

        def forward(self, x):
            return self.c(self.b(self.a(x)))

    m = M()
    x = paddle.to_tensor(np.random.default_rng(55).standard_normal(
        (4, 64)).astype(np.float32))
    ref = m(x).numpy()
    rep = qrt.quantize_model_int4(m)
    assert rep["layers"] == 2 and rep["skipped_odd"] == 1
    assert rep["weight_bytes_int4"] * 6 < rep["weight_bytes_fp"]
    assert isinstance(m.a, qrt.Int4WeightOnlyLinear)
    assert isinstance(m.c, nn.Linear)
    out = m(x).numpy()
    assert np.abs(out - ref).max() <= 0.25 * np.abs(ref).max()
    # idempotent under the int8 swapper: already-quantized layers stay
    rep8 = qrt.quantize_model_int8(m)
    assert rep8["layers"] == 1  # only the odd straggler
    assert isinstance(m.a, qrt.Int4WeightOnlyLinear)


@pytest.mark.slow
def test_int4_gpt_logits_parity_bounded():
    """`Int4WeightOnlyLinear` on the tier-1 GPT: logits track fp32
    within the int4 budget and the argmax survives on most positions
    (the engine-level greedy bound lives in the engine test)."""
    cfg, model = _tiny_model(seed=56)
    paddle.seed(56)
    ref_model = GPTForCausalLM(cfg)
    ref_model.eval()
    ids = paddle.to_tensor(np.random.default_rng(57).integers(
        0, cfg.vocab_size, (2, 24)).astype(np.int64))
    ref = ref_model(ids).numpy()
    rep = qrt.quantize_model_int4(model)
    assert rep["layers"] > 0 and rep["skipped_odd"] == 0
    out = model(ids).numpy()
    denom = np.abs(ref).max()
    assert np.abs(out - ref).max() <= 0.15 * denom, \
        np.abs(out - ref).max() / denom
    agree = (out.argmax(-1) == ref.argmax(-1)).mean()
    assert agree >= 0.8, agree


# int4-KV engine logits vs the plain fp32 forward, max |Δ| over every
# sampling frontier of the three seeds. Why this number: a packed-nibble
# row dequantizes to within absmax/14 per element (15 levels), 18× the
# int8 pool's absmax/254; on this model the int8 engine measures
# 2.7e-3, 18× that is 0.05, and the int4 engine measures 0.056 (fp32
# pool: 6.6e-7; logits std 0.23) — all on jax 0.9.0 XLA:CPU. The bound
# is ~2× the measurement.
INT4_KV_LOGITS_TOL = 0.1


def test_engine_int4_kv_logits_within_tolerance():
    """The int4-KV acceptance: packed-nibble pool engine decode vs a
    plain fp32 full forward of the same weights, compared on LOGITS at
    every sampling frontier (teacher-forced on the engine's own tokens).
    Not on sampled tokens: the median top-2 logit gap of this model
    (0.035) is below int4's noise, so one flipped near-tie re-writes the
    rest of a sequence and a token-match rate measures where the first
    flip fell, not the error (160/180 on jax 0.9.0 against a 0.95 bar).
    Also holds the one-executable + donation probes on the packed pool
    pytree."""
    from chip_smoke import _LogitsTap

    rng = np.random.default_rng(58)
    gen = 12
    worst = 0.0
    for mseed in (30, 24, 31):
        cfg, model = _tiny_model(seed=mseed)
        prompts = [rng.integers(0, cfg.vocab_size, (L,))
                   for L in (5, 13, 8, 21, 11)]
        eng = LLMEngine(model, LLMEngineConfig(
            num_slots=3, page_size=16, token_budget=8, max_model_len=64,
            kv_dtype="int4"))
        assert eng.kv_quantized == 4 and eng.kv_dtype == "int4"
        hd = cfg.hidden_size // cfg.num_heads
        assert eng._kv[0].shape[-1] == hd // 2  # packed
        tap = eng._step_fn = _LogitsTap(eng)
        reqs = [eng.add_request(p, max_new_tokens=gen) for p in prompts]
        steps = 0
        while eng.has_work():
            eng.step()
            eng.pool.assert_consistent()
            steps += 1
            assert steps < 500
        for p, r in zip(prompts, reqs):
            got = r.future.result(timeout=0)
            assert got.shape == (len(p) + gen,)
            ref = model(paddle.to_tensor(
                got[None, :-1].astype(np.int64))).numpy()[0]
            for pos in range(len(p) - 1, len(got) - 1):
                worst = max(worst, float(np.abs(
                    tap.rows[r.rid][pos] - ref[pos]).max()))
        assert eng.pool.num_live == 0
        stats = eng.compile_stats(check_donation=True)
        assert stats["executables"] == 1
        assert stats["donation"]["held"], stats["donation"]
    assert worst <= INT4_KV_LOGITS_TOL, worst


def test_int4_equal_bytes_capacity_vs_int8_and_fp32():
    """Equal-bytes capacity math + live pools: int4 pages cost <= 1/1.8
    of int8 and <= 1/3.5 of fp32 per page (the acceptance floors;
    measured ~1.8x / ~6.4x at head_dim 32), and a same-geometry engine
    pool's real nbytes agree with kv_bytes_per_page."""
    cfg, model = _tiny_model(seed=59)
    per = {kv: LLMEngineConfig.kv_bytes_per_page(cfg, 16, kv)
           for kv in ("float32", "int8", "int4")}
    assert per["int8"] >= 1.8 * per["int4"], per
    assert per["float32"] >= 3.5 * per["int4"], per
    ecfg = LLMEngineConfig(num_slots=2, page_size=16, max_model_len=32,
                           kv_dtype="int4")
    eng = LLMEngine(model, ecfg)
    num_pages = eng.pool.num_pages
    assert eng.pool_bytes() == per["int4"] * num_pages
    assert eng.metrics()["kv_pool_bytes"] == eng.pool_bytes()
    # for_pool_budget admits ~1.8x the pages of int8 at one budget
    budget = 512 * 1024
    p4 = LLMEngineConfig.for_pool_budget(cfg, budget, page_size=16,
                                         kv_dtype="int4").num_pages
    p8 = LLMEngineConfig.for_pool_budget(cfg, budget, page_size=16,
                                         kv_dtype="int8").num_pages
    assert p4 >= 1.8 * p8 * 0.98, (p4, p8)  # 2% slack: the +1 trash page


def test_pallas_int4_paged_attention_interpret_parity():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    rng = np.random.default_rng(60)
    P_, H, D, N, S, MP = 16, 2, 8, 9, 3, 4
    lens = [40, 19, 1]
    pool_k = np.zeros((N, P_, H, D // 2), np.int8)
    pool_v = np.zeros_like(pool_k)
    sk = np.zeros((N, P_, H), np.float32)
    sv = np.zeros_like(sk)
    pt = np.zeros((S, MP), np.int32)
    kc = rng.standard_normal((S, MP * P_, H, D)).astype(np.float32)
    vc = rng.standard_normal((S, MP * P_, H, D)).astype(np.float32)
    perm = list(rng.permutation(np.arange(1, N)))
    for s in range(S):
        for j in range(-(-lens[s] // P_)):
            pid = int(perm.pop())
            pt[s, j] = pid
            kq, ks = qrt.quantize_kv_rows_int4(
                jnp.asarray(kc[s, j * P_:(j + 1) * P_]))
            vq, vs = qrt.quantize_kv_rows_int4(
                jnp.asarray(vc[s, j * P_:(j + 1) * P_]))
            pool_k[pid], sk[pid] = np.asarray(kq), np.asarray(ks)
            pool_v[pid], sv[pid] = np.asarray(vq), np.asarray(vs)
    sid = np.asarray([0, 1, 2, 0, 1, 0], np.int32)
    klen = np.asarray([40, 19, 1, 7, 13, 0], np.int32)
    q = rng.standard_normal((len(sid), H, D)).astype(np.float32)

    jnp_out = F.paged_attention(
        paddle.to_tensor(q), paddle.to_tensor(pool_k),
        paddle.to_tensor(pool_v), paddle.to_tensor(pt),
        paddle.to_tensor(sid), paddle.to_tensor(klen),
        k_scales=paddle.to_tensor(sk),
        v_scales=paddle.to_tensor(sv)).numpy()
    # the jnp reference itself stays within the int4 budget of the
    # unquantized dense math
    ref = _dense_reference(q, kc, vc, sid, klen)
    assert np.abs(jnp_out - ref).max() < 0.08 * np.abs(vc).max()
    assert np.all(jnp_out[-1] == 0)  # padding row exactly zero
    # Pallas kernel (unpack in VMEM) matches the jnp reference
    pl_out = np.asarray(pak.ragged_paged_attention(
        jnp.asarray(q),
        jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(pt),
        jnp.asarray(sid), jnp.asarray(klen),
        k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv),
        interpret=True))
    np.testing.assert_allclose(pl_out, jnp_out, rtol=1e-5, atol=1e-6)


def test_kv_dtype_int4_env_knob(monkeypatch):
    cfg, model = _tiny_model(seed=61)
    monkeypatch.setenv("PT_KV_DTYPE", "int4")
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=32))
    assert eng.kv_quantized == 4 and eng.kv_dtype == "int4"
    assert str(eng._kv[0].dtype) == "int8"  # packed storage
    assert len(eng._kv_scales) == len(eng._kv)


# --------------------------------------------------------------------
# int8 wire codec
# --------------------------------------------------------------------

def test_wire_codec_roundtrip_savings_and_magic():
    rng = np.random.default_rng(3)
    for shape, dtype in [((1000,), np.float32), ((3, 5, 129), np.float32),
                         ((700,), np.float64)]:
        a = (rng.standard_normal(shape) * 7).astype(dtype)
        buf = qrt.encode_int8_wire(a)
        assert qrt.is_quant_wire(buf)
        b = qrt.decode_int8_wire(buf)
        assert b.dtype == a.dtype and b.shape == a.shape
        assert np.abs(b - a).max() <= 0.005 * np.abs(a).max()
        # ≥ 3× smaller than the raw float bytes (scales + header only)
        assert len(buf) < a.nbytes / 3 + 64
    # per-BLOCK scales: a huge block can't crush a small one's grid
    mixed = np.concatenate([rng.standard_normal(2048).astype(np.float32),
                            rng.standard_normal(2048).astype(np.float32)
                            * 1e-4])
    back = qrt.decode_int8_wire(qrt.encode_int8_wire(mixed, block=2048))
    small = slice(2048, 4096)
    # error in the small block is bounded by ITS OWN absmax/127, four
    # orders of magnitude below the big block's grid step
    assert (np.abs(back[small] - mixed[small]).max()
            <= np.abs(mixed[small]).max() / 120)
    # wire magic stays in sync with the socket transport's prefix check
    from paddle_tpu.distributed import xproc

    assert xproc._QUANT_WIRE_MAGIC == qrt.WIRE_MAGIC


def test_wire_codec_eligibility_and_nan_poison():
    assert not qrt.wire_eligible(np.arange(4096))           # ints exact
    assert not qrt.wire_eligible(np.ones(8, np.float32))    # too small
    assert qrt.wire_eligible(np.ones(4096, np.float32))
    # eligibility is DATA-INDEPENDENT — in a collective every rank must
    # take the same encode path, so a NaN on one rank may not fork the
    # wire format. Non-finite payloads round-trip as NaN-poisoned
    # blocks instead: the signal downstream grad guards key on.
    bad = np.ones(4096, np.float32)
    bad[5] = np.nan
    bad[3000] = np.inf
    assert qrt.wire_eligible(bad)
    back = qrt.decode_int8_wire(qrt.encode_int8_wire(bad, block=2048))
    assert np.isnan(back[:2048]).all()      # the NaN block poisons
    assert np.isnan(back[2048:]).all()      # the inf block poisons
    good = np.ones(4096, np.float32)
    assert np.isfinite(qrt.decode_int8_wire(
        qrt.encode_int8_wire(good))).all()
    assert not qrt.quant_allreduce_enabled()  # default OFF
    os.environ["PT_QUANT_ALLREDUCE"] = "1"
    try:
        assert qrt.quant_allreduce_enabled()
    finally:
        del os.environ["PT_QUANT_ALLREDUCE"]


def test_fused_allreduce_bf16_master_copy_guard(monkeypatch):
    """With the quantized wire ON, bf16 grads must cross the wire as
    fp32 (the codec path) and the bf16 PARAMS must stay bit-identical —
    only p.grad is rewritten, in fp32."""
    import jax.numpy as jnp

    from paddle_tpu.distributed.fleet.utils import hybrid_parallel_util
    from paddle_tpu.tensor_core import Tensor

    paddle.seed(3)
    m = nn.Linear(32, 32)
    # hand the params bf16 grads (the O2 shape)
    for p in m.parameters():
        p.grad = Tensor(jnp.ones(p._value.shape, jnp.bfloat16),
                        stop_gradient=True)
    params_before = [np.asarray(p._value).copy() for p in m.parameters()]

    seen = {}

    def fake_all_reduce(flat, op="sum"):
        seen["dtype"] = flat.dtype
        return flat

    monkeypatch.setenv("PT_QUANT_ALLREDUCE", "1")
    monkeypatch.setattr("paddle_tpu.distributed.xproc.all_reduce_np",
                        fake_all_reduce)
    monkeypatch.setattr("paddle_tpu.distributed.xproc.is_multiprocess",
                        lambda: True)
    hybrid_parallel_util.fused_allreduce_gradients(m.parameters())
    assert seen["dtype"] == np.float32
    for p, before in zip(m.parameters(), params_before):
        np.testing.assert_array_equal(np.asarray(p._value), before)
        assert str(p.grad._value.dtype) == "float32"


@pytest.mark.slow
def test_quant_allreduce_2proc_convergence(tmp_path):
    """The acceptance scenario: a 2-process eager-DP run whose gradient
    all-reduces ride the int8 wire codec must converge to the same final
    loss as the exact run (within the codec's error budget), actually
    save wire bytes, and keep both replicas' parameters IDENTICAL."""

    def launch(out_dir, extra_env):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ""
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.update(extra_env)
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nproc_per_node=2", f"--log_dir={out_dir}/log",
               os.path.join(ROOT, "tests", "quant_allreduce_worker.py"),
               str(out_dir)]
        return subprocess.run(cmd, env=env, cwd=ROOT,
                              capture_output=True, text=True,
                              timeout=420)

    qdir = tmp_path / "quant"
    qdir.mkdir()
    r = launch(qdir, {"PT_QUANT_ALLREDUCE": "1"})
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    r2 = launch(ref_dir, {})
    assert r2.returncode == 0, f"stdout:{r2.stdout}\nstderr:{r2.stderr}"

    out = {}
    for which, d in (("quant", qdir), ("ref", ref_dir)):
        for rank in (0, 1):
            with open(d / f"quant_ar_out_{rank}.json") as f:
                out[(which, rank)] = json.load(f)
    # both runs exercised the KV collective fallback (CPU backend)
    assert out[("quant", 0)]["kv_fallback"]
    # the codec really ran, and really saved bytes
    assert out[("quant", 0)]["bytes_saved"] > 0
    assert out[("ref", 0)]["bytes_saved"] == 0
    # replicas stay in lockstep under quantization (identical params)
    assert (out[("quant", 0)]["param_sha"]
            == out[("quant", 1)]["param_sha"])
    # convergence: same final loss within the int8 wire error budget
    qf = out[("quant", 0)]["losses"][-1]
    rf = out[("ref", 0)]["losses"][-1]
    assert qf == pytest.approx(rf, rel=0.05, abs=0.01), (qf, rf)
    # the loss actually went DOWN in the quantized run
    assert qf < out[("quant", 0)]["losses"][0]
