"""Continuous-batching LLM serving engine (inference/llm_engine.py).

The ISSUE-2 acceptance suite: paged attention == dense attention to
fp32 tolerance across page sizes and ragged lengths, engine greedy
decode == generate() token-for-token, page-pool alloc/free invariants
(incl. the 100-request soak, slow), and the zero-recompile-after-warmup
probe on the one compiled decode executable.
"""
import math
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.inference.llm_engine import (
    LLMEngine, LLMEngineConfig, PagePool, PoolExhausted, _CacheKindState,
    _Request)
from paddle_tpu.nn import functional as F
from paddle_tpu.text.models import GPTForCausalLM
from paddle_tpu.text.models.gpt import gpt_tiny
from paddle_tpu.text.models.serving_protocol import CacheKind

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _serial_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield


# --------------------------------------------------------------------
# paged attention parity
# --------------------------------------------------------------------

def _build_paged_case(rng, page_size, lens, H=2, D=16, extra_tokens=()):
    """Scatter contiguous per-slot K/V into a shuffled page pool.

    Returns (q, pool_k, pool_v, page_tables, slot_ids, kv_lens, kc, vc)
    where kc/vc are the contiguous [S, L, H, D] ground truth."""
    S = len(lens)
    P = page_size
    MP = -(-max(lens) // P)
    N = sum(-(-int(l) // P) for l in lens) + 1  # exact + trash
    kc = rng.standard_normal((S, MP * P, H, D)).astype(np.float32)
    vc = rng.standard_normal((S, MP * P, H, D)).astype(np.float32)
    pool_k = np.zeros((N, P, H, D), np.float32)
    pool_v = np.zeros((N, P, H, D), np.float32)
    pt = np.zeros((S, MP), np.int32)
    perm = list(rng.permutation(np.arange(1, N)))
    for s in range(S):
        for j in range(-(-int(lens[s]) // P)):
            pid = int(perm.pop())
            pt[s, j] = pid
            pool_k[pid] = kc[s, j * P:(j + 1) * P]
            pool_v[pid] = vc[s, j * P:(j + 1) * P]
    # one token at every slot frontier + ragged mid-sequence extras +
    # one padding token (kv_len 0)
    sid = list(range(S)) + [s for s, _ in extra_tokens] + [0]
    klen = [int(l) for l in lens] + [k for _, k in extra_tokens] + [0]
    T = len(sid)
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    return (q, pool_k, pool_v, pt, np.asarray(sid, np.int32),
            np.asarray(klen, np.int32), kc, vc)


def _dense_reference(q, kc, vc, sid, klen):
    """float64 softmax attention per token over its own prefix."""
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


@pytest.mark.parametrize("page_size", [16, 64, 128])
def test_paged_attention_matches_dense(page_size):
    rng = np.random.default_rng(page_size)
    # ragged: full pages, a partial tail, a single token, page-crossing
    lens = [2 * page_size + 7, page_size, page_size - 1, 1]
    extras = [(0, 5), (0, page_size + 1), (1, 3)]
    q, pk, pv, pt, sid, klen, kc, vc = _build_paged_case(
        rng, page_size, lens, extra_tokens=extras)
    out = F.paged_attention(
        paddle.to_tensor(q), paddle.to_tensor(pk), paddle.to_tensor(pv),
        paddle.to_tensor(pt), paddle.to_tensor(sid),
        paddle.to_tensor(klen)).numpy()
    ref = _dense_reference(q, kc, vc, sid, klen)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # the padding token (kv_len 0) is exactly zero, not NaN
    assert np.all(out[-1] == 0)


def test_pallas_ragged_paged_attention_interpret_matches_jnp():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    rng = np.random.default_rng(3)
    q, pk, pv, pt, sid, klen, kc, vc = _build_paged_case(
        rng, 16, [40, 19, 1], extra_tokens=[(0, 7), (1, 13)])
    jnp_out = F.paged_attention(
        paddle.to_tensor(q), paddle.to_tensor(pk), paddle.to_tensor(pv),
        paddle.to_tensor(pt), paddle.to_tensor(sid),
        paddle.to_tensor(klen)).numpy()
    pl_out = np.asarray(pak.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(pt), jnp.asarray(sid), jnp.asarray(klen),
        interpret=True))
    # online softmax vs plain softmax: identical to fp32 tolerance
    np.testing.assert_allclose(pl_out, jnp_out, rtol=1e-5, atol=1e-6)


# the page walk: pools × heads whose pages the kernel copies itself
# (float32 at 12 heads: the VPU body; float32 and bfloat16 at 16: the MXU
# body, with float32 queries, which widen a bf16 pool's operands, and
# with the pool's own bf16) and quantized pools, which stay on the page
# grid; head_dim 128 throughout (int4 packs it to 64 lanes)
_WALK_POOLS = [("float32", 12, None), ("bfloat16", 16, None),
               ("int8", 12, None), ("int4", 16, None),
               ("float32", 16, None), ("bfloat16", 16, "bfloat16")]


def _walk_case(pool, heads, scenario, q_dtype=None):
    """(kernel kwargs, reference kwargs) of one scenario, sized from
    the kernel's own group length G so that every boundary of the walk
    is crossed: page, group, the last partial group, the table's end."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak
    from paddle_tpu.quantization import runtime as qrt

    P, D = 16, 128
    kdim = D // 2 if pool == "int4" else D
    store = {"int8": "int8", "int4": "int8"}.get(pool, pool)
    G = pak._pages_per_group(P, heads, kdim, store, 10 ** 6)
    MP = 2 * G + 3                     # two whole groups and a partial one
    cap = MP * P
    qps = off = None
    if scenario in ("ragged", "garbage"):
        # padding among live rows; 1 token; exactly a page; exactly a
        # group; one past it; a live-page count that is no multiple of
        # G; the whole table
        lens = [0, 1, P, 0, G * P, G * P + 1, (G + 3) * P - 5, cap, 0]
        sid = [0, 1, 2, 3, 0, 1, 2, 3, 2]
    elif scenario == "frontier":
        # the offset carries a row into a new page, into a new group,
        # up to the table's end; a padding row stays padding
        off = 3
        lens = [P - 2, G * P - 1, cap - 3, 0, 2 * G * P - 3, 1]
        sid = [0, 1, 2, 3, 3, 0]
    elif scenario == "verify":
        # the verify layout: 3 rows a slot, ragged inside a block —
        # across a group boundary, a dead tail row, an all-dead block,
        # the table's end
        qps = 3
        lens = [G * P - 1, G * P, G * P + 1, 5, 6, 0, 0, 0, 0,
                cap - 2, cap - 1, cap]
        sid = [0, 0, 0, 1, 1, 1, 2, 2, 2, 3, 3, 3]
    else:
        # blocks of 5 with the frontier offset on top: rows a whole
        # GROUP shorter than their block's longest (groups they must
        # not touch), a one-page block with a dead tail, an all-dead
        # block, the table's end reached by the offset
        qps, off = 5, 2
        lens = [3, G * P - 3, G * P - 2, 2 * G * P - 1, 2 * G * P + 6,
                1, 2, 3, 0, 0, 0, 0, 0, 0, 0,
                cap - 6, cap - 5, cap - 4, cap - 3, cap - 2]
        sid = [0] * 5 + [1] * 5 + [2] * 5 + [3] * 5
    rng = np.random.default_rng(len(lens) * heads + G)
    S = 4
    N = S * MP + 1
    pt = (rng.permutation(np.arange(1, N)).reshape(S, MP).astype(np.int32))
    if scenario == "garbage":
        # table entries behind a slot's last live page hold ids of no
        # page: the walk reads the table only as far as a row is long
        live = np.zeros(S, np.int64)
        np.maximum.at(live, sid, -(-np.asarray(lens) // P))
        pt[np.arange(MP)[None, :] >= live[:, None]] = 7 * N
    q = jnp.asarray(rng.standard_normal((len(sid), heads, D)),
                    q_dtype or jnp.float32)
    kv = [jnp.asarray(rng.standard_normal((N, P, heads, D)), jnp.float32)
          for _ in range(2)]
    scales = {}
    if pool in ("int8", "int4"):
        quant = (qrt.quantize_kv_rows if pool == "int8"
                 else qrt.quantize_kv_rows_int4)
        packed = [quant(x.reshape(N * P, heads, D)) for x in kv]
        kv = [c.reshape(N, P, heads, -1) for c, _ in packed]
        scales = {n: sc.reshape(N, P, heads)
                  for n, (_, sc) in zip(("k_scales", "v_scales"), packed)}
    else:
        kv = [x.astype(pool) for x in kv]
    args = (pt, np.asarray(sid, np.int32), np.asarray(lens, np.int32))
    offv = None if off is None else jnp.asarray(off, jnp.int32)
    kern = dict(args=(q, *kv, *args), kw=dict(
        frontier_offset=offv, q_per_slot=qps, **scales))
    # the reference reads the SAME stored values, widened (a bf16 pool
    # is exact in f32), so both sides are f32 arithmetic
    wide = kv if scales else [x.astype(jnp.float32) for x in kv]
    ref = dict(args=(q.astype(jnp.float32), *wide, *map(jnp.asarray, args)),
               kw=dict(frontier_offset=offv, max_tokens_per_slot=qps,
                       **scales))
    return kern, ref, np.asarray(lens) == 0


@pytest.mark.parametrize("scenario", ["ragged", "frontier", "verify",
                                      "verify5_frontier", "garbage"])
@pytest.mark.parametrize("pool,heads,q_dtype", _WALK_POOLS)
def test_pallas_paged_walk_matches_jnp(pool, heads, q_dtype, scenario):
    from paddle_tpu.nn.functional.attention import paged_attention_jnp
    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    kern, ref, dead = _walk_case(pool, heads, scenario, q_dtype)
    got = np.asarray(pak.ragged_paged_attention(
        *kern["args"], **kern["kw"], interpret=True).astype("float32"))
    want = np.asarray(paged_attention_jnp(*ref["args"], **ref["kw"]))
    # bf16 queries: the MXU body's operands are bf16 as stored, its
    # weights p are rounded to bf16 for the second product, and so is
    # the result (chip_smoke.py holds the same launch to 2e-2)
    tol = (dict(rtol=2e-2, atol=2e-2) if q_dtype
           else dict(rtol=1e-5, atol=2e-6))
    np.testing.assert_allclose(got, want, **tol)
    assert dead.any() and np.all(got[dead] == 0)


# which body a launch gets (`paged_attention._multiplies_on_mxu`), and
# that `launch_sites()` sees it: (pool dtype, heads, head_dim) → body
_BODIES = [("bfloat16", 16, 128, "mxu"), ("float32", 16, 128, "mxu"),
           ("float32", 8, 128, "mxu"), ("bfloat16", 32, 128, "mxu"),
           ("float32", 12, 128, "vpu"), ("bfloat16", 12, 128, "vpu"),
           ("bfloat16", 8, 128, "vpu"), ("float32", 16, 64, "vpu"),
           ("int8", 16, 128, "vpu"), ("int4", 16, 128, "vpu")]


@pytest.mark.parametrize("pool,heads,dim,body", _BODIES)
def test_which_body_a_paged_launch_runs(pool, heads, dim, body):
    """16 heads × 128 of a bf16 pool (the decode cell) multiply on the
    MXU, as every float pool the walk takes whose heads are whole
    sublane tiles; 12 heads, head_dim 64 and the quantized pools keep
    the VPU page body (and the grid they had)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    z = jnp.zeros
    quant = pool in ("int8", "int4")
    store = jnp.int8 if quant else jnp.dtype(pool)
    page = (9, 16, heads, dim // 2 if pool == "int4" else dim)
    scales = {n: z(page[:3], jnp.float32)
              for n in (("k_scales", "v_scales") if quant else ())}
    with pak.launch_sites() as sites:
        jaxpr = jax.make_jaxpr(lambda q, k, v: pak.ragged_paged_attention(
            q, k, v, z((2, 4), jnp.int32), z((6,), jnp.int32),
            z((6,), jnp.int32), **scales))(
            z((6, heads, dim), jnp.float32 if quant else store),
            z(page, store), z(page, store))
    assert sites == {"mxu": int(body == "mxu"), "vpu": int(body == "vpu")}
    assert pak._multiplies_on_mxu(heads, page[3], store,
                                  bool(quant)) == (body == "mxu")
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    walks = pak._walks_in_kernel(heads, page[3], store, bool(quant))
    assert len(call.params["grid_mapping"].grid) == (1 if walks else 2)
    assert pak._open_site_counts.stack == []     # the block is closed


def _pallas_grid(page_tables_width, heads, dim, dtype, tokens=6, qps=None):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    z = jnp.zeros
    jaxpr = jax.make_jaxpr(lambda *a: pak.ragged_paged_attention(
        *a, q_per_slot=qps))(
        z((tokens, heads, dim), dtype), z((9, 16, heads, dim), dtype),
        z((9, 16, heads, dim), dtype), z((2, page_tables_width), jnp.int32),
        z((tokens,), jnp.int32), z((tokens,), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return tuple(calls[0].params["grid_mapping"].grid)


def test_pallas_paged_grid_does_not_scale_with_max_model_len():
    """The decode cell's launch (16 heads × 128, bf16): one grid step
    per query block, whatever `page_tables.shape[1]` (= max_model_len /
    page_size) is. A pool Mosaic cannot slice for a manual copy keeps
    the page dimension in its grid — pinned here so that the day it
    can, this fails and the page grid is deleted."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    assert _pallas_grid(128, 16, 128, jnp.bfloat16) == (6,)
    assert _pallas_grid(256, 16, 128, jnp.bfloat16) == (6,)
    assert _pallas_grid(256, 16, 128, jnp.bfloat16, qps=3) == (2,)
    assert _pallas_grid(256, 12, 128, jnp.float32) == (6,)
    assert pak._walks_in_kernel(16, 128, jnp.bfloat16, 0)
    # head_dim 64, 12 heads of a 16-bit pool, any quantized pool
    assert _pallas_grid(128, 12, 64, jnp.float32) == (6, 128)
    assert not pak._walks_in_kernel(12, 128, jnp.bfloat16, 0)
    assert not pak._walks_in_kernel(16, 128, jnp.int8, 8)


def _count_launches_of(model):
    """Serve two prompts through a fused-window engine; the engine and
    what it served."""
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=64, token_budget=8,
        decode_k=4))
    reqs = [eng.add_request(np.arange(n) % 50, max_new_tokens=9)
            for n in (5, 11)]
    while eng.has_work():
        eng.step()
    served = [r.future.result(timeout=0) for r in reqs]
    assert [len(t) for t in served] == [5 + 9, 11 + 9]
    return eng, served


def test_paged_launch_counters_read_zero_on_the_jnp_path():
    """The CPU tier's engine attends through `paged_attention_jnp`: no
    kernel launch of either body, and both counters are there to say
    so, in `stats` and in `metrics()`."""
    eng, _ = _count_launches_of(_tiny_model()[1])
    assert eng.stats["fused_steps"] > 0
    for name in ("paged_attn_mxu_launches", "paged_attn_vpu_launches"):
        assert eng.stats[name] == 0 and eng.metrics()[name] == 0
    assert eng._step_fn.launches == {"mxu": 0, "vpu": 0}
    assert eng._fused_fn.launches == {"mxu": 0, "vpu": 0}


@pytest.mark.parametrize("heads,body", [(8, "mxu"), (4, "vpu")])
def test_paged_launch_counters_count_every_dispatch(monkeypatch, heads,
                                                    body):
    """With the kernel forced on (interpreted): a tick adds a launch a
    layer, a window of k adds k a layer, all of the body the shapes get
    (float32 pool, head_dim 128: 8 heads are a whole sublane tile, 4
    are not), and the served tokens are the jnp path's."""
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops.pallas_kernels import paged_attention as pak
    from paddle_tpu.text.models.gpt import GPTConfig

    paddle.seed(3)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=128 * heads, num_layers=2,
        num_heads=heads, max_seq_len=64))
    model.eval()
    _, want = _count_launches_of(model)
    kernel = pak.ragged_paged_attention
    monkeypatch.setattr(attention, "_paged_pallas_eligible",
                        lambda q, k_pool: True)
    monkeypatch.setattr(
        pak, "ragged_paged_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}))
    eng, served = _count_launches_of(model)
    windows = eng.stats["fused_steps"]
    ticks = eng.stats["steps"] - windows
    assert windows > 0 and ticks > 0
    assert eng._step_fn.launches[body] == 2
    assert eng._fused_fn.launches[body] == 2 * 4
    other = "vpu" if body == "mxu" else "mxu"
    assert eng.stats[f"paged_attn_{body}_launches"] == 2 * (
        ticks + 4 * windows)
    assert eng.stats[f"paged_attn_{other}_launches"] == 0
    for got, ref in zip(served, want):
        np.testing.assert_array_equal(got, ref)


# --------------------------------------------------------------------
# engine == generate()
# --------------------------------------------------------------------

def _tiny_model(seed=30):
    paddle.seed(seed)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def _ref_generate(model, prompt, max_new, **kw):
    return model.generate(
        paddle.to_tensor(np.asarray(prompt)[None].astype(np.int64)),
        max_new_tokens=max_new, **kw).numpy()[0]


def test_engine_greedy_matches_generate_token_for_token():
    cfg, model = _tiny_model()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (L,))
               for L in (5, 13, 8, 21, 3)]
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64))
    reqs = [eng.add_request(p, max_new_tokens=7) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
        steps += 1
        assert steps < 300
    for p, r in zip(prompts, reqs):
        got = r.future.result(timeout=0)
        ref = _ref_generate(model, p, 7)
        np.testing.assert_array_equal(got, ref)
    assert eng.pool.num_live == 0
    assert eng.stats["finished"] == len(prompts)
    assert 0.0 < eng.mean_occupancy <= 1.0


def test_engine_eos_matches_generate_contract():
    cfg, model = _tiny_model(seed=24)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, (6,))
    base = _ref_generate(model, prompt, 8)
    eos = int(base[6 + 1])  # the row's 2nd generated token
    # generate(): emits eos, then stops early (and would pad a batch)
    stopped = _ref_generate(model, prompt, 8, eos_token_id=eos)
    assert stopped.shape[0] == 6 + 2
    np.testing.assert_array_equal(stopped, base[:8])
    # engine: same stop semantics — eos kept, nothing after it
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=64))
    req = eng.add_request(prompt, max_new_tokens=8, eos_token_id=eos)
    while eng.has_work():
        eng.step()
    np.testing.assert_array_equal(req.future.result(timeout=0), stopped)


def test_engine_preemption_stays_deterministic():
    cfg, model = _tiny_model(seed=31)
    rng = np.random.default_rng(7)
    # 4 sequences of 3 pages each through a 5-page pool: the scheduler
    # must preempt to make progress, and greedy decode must not notice
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=3, page_size=16, num_pages=6, max_model_len=48,
        token_budget=8))
    prompts = [rng.integers(0, cfg.vocab_size, (20,)) for _ in range(4)]
    reqs = [eng.add_request(p, max_new_tokens=20) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
        steps += 1
        assert steps < 500
    assert eng.stats["preemptions"] > 0, "pool was not tight enough"
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(r.future.result(timeout=0),
                                      _ref_generate(model, p, 20))
    assert eng.pool.num_live == 0


def test_engine_zero_recompiles_after_warmup():
    cfg, model = _tiny_model(seed=32)
    rng = np.random.default_rng(11)
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, token_budget=8, max_model_len=64))
    # warmup: the first step compiles THE decode executable
    eng.add_request(rng.integers(0, cfg.vocab_size, (4,)),
                    max_new_tokens=3)
    while eng.has_work():
        eng.step()
    warm = eng.compile_stats()
    assert warm == {"executables": 1}, warm
    # the executable must also have KEPT its donation: a dropped alias
    # map (the jax-0.4.x persistent-cache bug) serves correct tokens
    # 25% slower — invisible to the recompile probe alone
    don = eng.compile_stats(check_donation=True)["donation"]
    assert don["held"], don
    assert don["aliased"] == don["expected"] > 0, don
    # steady state: mixed prompt lengths, admissions, evictions — the
    # fixed-shape step must never recompile
    for L in (3, 17, 30, 9, 25):
        eng.add_request(rng.integers(0, cfg.vocab_size, (L,)),
                        max_new_tokens=4)
    while eng.has_work():
        eng.step()
    assert eng.compile_stats() == warm, (
        "steady-state serving recompiled the decode step")


# --------------------------------------------------------------------
# page pool
# --------------------------------------------------------------------

def test_page_pool_alloc_free_invariants():
    pool = PagePool(num_pages=5, page_size=16)
    assert pool.num_free == 4  # page 0 reserved as trash
    pages = [pool.alloc() for _ in range(4)]
    assert 0 not in pages and len(set(pages)) == 4
    with pytest.raises(PoolExhausted):
        pool.alloc()
    pool.free(pages[:2])
    pool.assert_consistent()
    with pytest.raises(RuntimeError, match="double free"):
        pool.free([pages[0]])
    pool.free(pages[2:])
    pool.assert_consistent()
    assert pool.num_free == 4 and pool.num_live == 0


# --------------------------------------------------------------------
# the page bookkeeping of one cache kind (`_CacheKindState`)
# --------------------------------------------------------------------

_WINDOWS = pytest.mark.parametrize(
    "window", [None, 16, 48], ids=["full", "window16", "window48"])


def _kind_state(window, num_pages=50, slots=2, pages_per_seq=24):
    kind = CacheKind("kv", (0,), 2, 16, window, False)
    return _CacheKindState(0, kind, num_pages, 16, slots, pages_per_seq)


def _fake_request():
    return _Request([1, 2, 3], 1, None, None)


def _must_hold(window, first, last, page=16):
    """Brute force: the logical pages that hold a position which the
    queries first … last read."""
    lo = 0 if window is None else max(0, first - window + 1)
    return {p // page for p in range(lo, last + 1)}


@_WINDOWS
def test_kind_state_holds_what_the_queries_read(window):
    """Chunks of 1 … 40 positions as the engine feeds them (grow for the
    chunk, write it, trim at the boundary), against the brute-force set
    of pages, on two slots at once."""
    ks = _kind_state(window)
    rng = np.random.default_rng(3)
    reqs = [_fake_request(), _fake_request()]
    allocated = 0
    while min(r.n_prefilled for r in reqs) < 300:
        slot = int(rng.integers(0, 2))
        req = reqs[slot]
        held = req.kind_pages[0]
        first = req.n_prefilled
        last = first + int(rng.integers(1, 41)) - 1
        need = _must_hold(window, first, last)
        lack = len(need - set(held))
        assert ks.missing(req, first, last) == lack
        live = ks.pool.num_live
        ks.grow(slot, req, first, last)
        allocated += lack
        assert ks.pool.num_live == live + lack
        assert need <= set(held)
        assert list(held) == list(range(held.first,
                                        held.first + len(held)))
        assert ks.covered(req) == (max(held) + 1) * 16 > last
        # the table maps what is held, and nothing else
        row = ks.tables[slot]
        assert [int(row[j]) for j in held] == held.pages
        assert np.count_nonzero(row) == len(held)
        assert np.array_equal(
            ks.rows(np.array([slot, slot]), np.array([first, last])),
            [held.pages[first // 16 - held.first] * 16 + first % 16,
             held.pages[last // 16 - held.first] * 16 + last % 16])
        req.n_prefilled = last + 1
        before = set(held)
        freed = ks.trim(slot, req)
        # what goes lies wholly behind what the next query reads
        lo = min(_must_hold(window, req.n_prefilled, req.n_prefilled))
        assert set(held) == {j for j in before if j >= lo}
        assert freed == len(before) - len(held)
        assert np.count_nonzero(ks.tables[slot]) == len(held)
        if window is None:
            assert freed == 0 and held.first == 0
        else:
            assert len(held) <= window // 16 + 2
        ks.pool.assert_consistent()
    assert ks.pool.num_live == sum(len(r.kind_pages[0]) for r in reqs)
    for slot, req in enumerate(reqs):
        ks.release(slot, req)
        assert len(req.kind_pages[0]) == 0 and ks.covered(req) == 0
    assert ks.pool.num_live == 0 and not ks.tables.any()
    assert allocated > (20 if window is None else 40)


@_WINDOWS
def test_kind_state_covered_asks_for_nothing_and_allocates_nothing(
        window):
    ks = _kind_state(window)
    req = _fake_request()
    ks.grow(0, req, 0, 70)
    req.n_prefilled = 71
    ks.trim(0, req)
    live, table = ks.pool.num_live, ks.tables.copy()
    ks.pool.alloc = None            # any allocation would be a TypeError
    for last in range(71, 80):      # the tail page covers 64 … 79
        assert ks.missing(req, 71, last) == 0
        ks.grow(0, req, 71, last)
    assert ks.missing(req, 71, 80) == 1
    assert ks.pool.num_live == live and np.array_equal(ks.tables, table)


class _StubTrie:
    """Holds pages of the pool the way the prefix trie does."""

    def __init__(self, pool, pages):
        self.pool, self.pages, self.calls = pool, pages, []

    def reclaimable_pages(self):
        return len(self.pages)

    def evict(self, n):
        self.calls.append(n)
        gone, self.pages = self.pages[:n], self.pages[n:]
        self.pool.free(gone)
        return len(gone)


@_WINDOWS
def test_kind_state_reclaims_from_the_trie_before_it_gives_up(window):
    ks = _kind_state(window, num_pages=7)       # 6 pages to hand out
    ks.trie = _StubTrie(ks.pool, [ks.pool.alloc() for _ in range(2)])
    assert ks.available() == 4 + 2
    req = _fake_request()
    ks.grow(0, req, 0, 63)                      # 4 pages: pool not dry
    assert ks.trie.calls == [] and ks.available() == 2
    ks.grow(0, req, 64, 95)                     # 2 more: the trie's
    assert ks.trie.calls == [1, 1] and ks.trie.pages == []
    assert ks.pool.num_free == 0 and len(req.kind_pages[0]) == 6
    with pytest.raises(PoolExhausted):          # asked once more, then
        ks.grow(0, req, 96, 140)                # … raises
    assert ks.trie.calls == [1, 1, 1]
    # what was taken before stays: `covered` says how far it reaches
    assert ks.covered(req) == 96 and ks.missing(req, 96, 140) == 3
    ks.release(0, req)
    req2 = _fake_request()
    with pytest.raises(PoolExhausted):
        ks.grow(1, req2, 0, 16 * 6 + 5)         # 7 pages of 6
    assert ks.covered(req2) == 96               # the six it got
    ks.pool.assert_consistent()


@_WINDOWS
def test_kind_state_adopts_shared_pages_and_releases_its_share(window):
    ks = _kind_state(window)
    theirs = [ks.pool.alloc() for _ in range(3)]     # the trie's pages
    mapped = [ks.pool.share(p) for p in theirs[:2]]  # … two mapped
    req = _fake_request()
    ks.adopt(1, req, mapped)
    assert req.kind_pages[0].pages == mapped and req.pages == mapped
    assert list(ks.tables[1, :3]) == mapped + [0]
    assert ks.covered(req) == 32 and ks.missing(req, 32, 40) == 1
    ks.grow(1, req, 32, 40)                          # a private page
    private = req.pages[2]
    assert ks.pool.refcount(private) == 1
    assert [ks.pool.refcount(p) for p in theirs] == [2, 2, 1]
    ks.release(1, req)
    # shared pages lose ONE holder and stay live; the private one goes
    assert [ks.pool.refcount(p) for p in theirs] == [1, 1, 1]
    assert ks.pool.refcount(private) == 0
    assert req.pages == [] and not ks.tables.any()
    assert ks.pool.num_live == 3
    ks.pool.assert_consistent()


@_WINDOWS
def test_kind_state_asks_for_the_prompt_or_the_window_at_admission(
        window):
    ks = _kind_state(window)
    for n, budget in ((5, 24), (40, 24), (200, 24), (200, 64)):
        if window is None:
            want = -(-n // 16)
        else:       # the first chunk, the window behind it, one ahead
            want = -(-min(n, window + budget) // 16) + 1
        assert ks.pages_to_admit(n, budget) == want


def test_engine_rejects_unservable_requests():
    cfg, model = _tiny_model(seed=33)
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, num_pages=3, max_model_len=64))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.add_request(np.zeros((0,), np.int64))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.add_request(np.zeros((65,), np.int64))
    # prompt alone needs 3 pages; the pool holds 2 allocable
    with pytest.raises(ValueError, match="KV pages"):
        eng.add_request(np.zeros((40,), np.int64))
    # zero generation budget echoes the prompt (generate() contract)
    req = eng.add_request(np.arange(5), max_new_tokens=0)
    np.testing.assert_array_equal(req.future.result(timeout=0),
                                  np.arange(5))


@pytest.mark.slow
def test_page_pool_soak_100_mixed_requests():
    """100 mixed-length requests through a tight pool: hundreds of
    scheduler steps with admissions, evictions, and preemptions — the
    allocator must never double-free or leak."""
    cfg, model = _tiny_model(seed=34)
    rng = np.random.default_rng(17)
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=4, page_size=16, num_pages=10, max_model_len=64,
        token_budget=12))
    reqs = []
    for i in range(100):
        L = int(rng.integers(1, 41))
        gen = int(rng.integers(1, 17))
        reqs.append(eng.add_request(
            rng.integers(0, cfg.vocab_size, (L,)), max_new_tokens=gen))
    steps = 0
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
        steps += 1
        assert steps < 5000
    assert steps > 100  # a genuine multi-hundred-step soak
    assert eng.pool.num_live == 0
    assert eng.stats["finished"] == 100
    for r in reqs:
        out = r.future.result(timeout=0)
        assert out.ndim == 1 and len(out) > r.prompt_len


# --------------------------------------------------------------------
# LLMServer surface
# --------------------------------------------------------------------

def test_llm_server_concurrent_submits_match_generate():
    cfg, model = _tiny_model(seed=35)
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, cfg.vocab_size, (L,))
               for L in (4, 11, 7, 16, 2, 9)]
    server = inference.LLMServer(model, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64))
    results = {}
    lock = threading.Lock()

    def client(idxs):
        futs = [(i, server.submit(prompts[i], max_new_tokens=5))
                for i in idxs]
        for i, f in futs:
            out = f.result(timeout=120)
            with lock:
                results[i] = out

    with server:
        threads = [threading.Thread(target=client, args=(r,))
                   for r in (range(0, 3), range(3, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(results[i],
                                      _ref_generate(model, p, 5))
    assert server.stats["requests"] == len(prompts)
    assert server.engine.pool.num_live == 0


def test_llm_server_bad_request_fails_future_not_server():
    cfg, model = _tiny_model(seed=36)
    with inference.LLMServer(model, LLMEngineConfig(
            num_slots=2, page_size=16, max_model_len=32)) as server:
        bad = server.submit(np.zeros((200,), np.int64), max_new_tokens=4)
        ok = server.submit(np.arange(3), max_new_tokens=2)
        with pytest.raises(ValueError, match="max_model_len"):
            bad.result(timeout=60)
        assert len(ok.result(timeout=60)) == 5  # server stays alive


def test_llm_server_cancelled_future_does_not_abort_others():
    # a client cancel() must fail quietly at resolution time, not bubble
    # an InvalidStateError into the serve loop's abort-everything path
    cfg, model = _tiny_model(seed=38)
    rng = np.random.default_rng(23)
    prompts = [rng.integers(0, cfg.vocab_size, (L,)) for L in (5, 9, 7)]
    with inference.LLMServer(model, LLMEngineConfig(
            num_slots=2, page_size=16, token_budget=6,
            max_model_len=64)) as server:
        futs = [server.submit(p, max_new_tokens=8) for p in prompts]
        futs[1].cancel()  # races resolution: both outcomes must be safe
        results = {i: futs[i].result(timeout=120) for i in (0, 2)}
    # reference generate() AFTER the server stops: tracing swaps live
    # param values, which must not race the serving thread
    for i in (0, 2):
        np.testing.assert_array_equal(results[i],
                                      _ref_generate(model, prompts[i], 8))
    assert server.engine.pool.num_live == 0


def test_llm_server_requires_start():
    cfg, model = _tiny_model(seed=37)
    server = inference.LLMServer(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=32))
    with pytest.raises(RuntimeError, match="not started"):
        server.submit(np.arange(3))
