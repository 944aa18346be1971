"""The serving path's new mechanisms, on the CPU at small sizes: grouped
queries and a lower bound in the paged kernel, the dropless expert layer
that is told which experts it holds, the cache manager of two pools, and
the model protocol `GPTForCausalLM` answers with one kind."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.nn import expert_layer
from paddle_tpu.nn.functional.attention import paged_attention_gqa_jnp
from paddle_tpu.ops.pallas_kernels.paged_attention import (
    ragged_paged_attention)
from paddle_tpu.text.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.text.models.laguna import LagunaForCausalLM, laguna_tiny


# ---- the kernel -------------------------------------------------------

def _dense_masked(q, kp, vp, tables, sids, lens, starts):
    """Plain masked attention a row at a time (numpy, float64)."""
    n, kv, page, d = kp.shape
    T, H, _ = q.shape
    g = H // kv
    out = np.zeros((T, H, d))
    kp, vp, q = (np.asarray(a, np.float64) for a in (kp, vp, q))
    for t in range(T):
        lo, hi = max(int(starts[t]), 0), int(lens[t])
        if hi == 0:
            continue
        pos = np.arange(lo, hi)
        phys = tables[sids[t], pos // page]
        k = kp[phys, :, pos % page]                      # [L, KV, d]
        v = vp[phys, :, pos % page]
        for h in range(H):
            s = k[:, h // g] @ q[t, h] / math.sqrt(d)
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ v[:, h // g]
    return out


def _case(dtype, g, page):
    rng = np.random.default_rng(0)
    kv, d, S, MP = 2, 128, 3, 12
    n = S * MP + 1
    kp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    q = jnp.asarray(rng.normal(size=(9, kv * g, d)), dtype)
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    sids = np.array([0, 0, 0, 1, 2, 2, 1, 0, 0], np.int32)
    lens = np.array([5, 6, 7, 90, 33, 34, 0, page * MP - 3, 1], np.int32)
    return q, kp, vp, tables, sids, lens


@pytest.mark.parametrize("dtype,page,tol", [("float32", 8, 2e-5),
                                            ("bfloat16", 16, 3e-2)])
@pytest.mark.parametrize("g,window", [(3, None), (3, 20), (2, 40)])
@pytest.mark.parametrize("offset", [None, 3])
def test_gqa_kernel_with_a_lower_bound_against_dense_masked_attention(
        dtype, page, tol, g, window, offset):
    q, kp, vp, tables, sids, lens = _case(jnp.dtype(dtype), g, page)
    starts = None if window is None else lens - window
    off = offset or 0
    hi = np.where(lens > 0, lens + off, 0)
    lo = np.zeros_like(hi) if window is None else np.where(
        lens > 0, lens - window + off, 0)
    want = _dense_masked(q, kp, vp, tables, sids, hi, lo)
    got = ragged_paged_attention(
        q, kp, vp, tables, sids, lens, kv_starts=starts,
        frontier_offset=offset, head_major=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)
    ref = paged_attention_gqa_jnp(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(sids),
        jnp.asarray(lens), None if starts is None else jnp.asarray(starts),
        None if offset is None else jnp.asarray(offset))
    np.testing.assert_allclose(np.asarray(ref, np.float64), want, atol=tol)
    assert not np.asarray(got[6], np.float32).any()    # the padding row


def test_the_head_major_walk_says_what_it_cannot_slice():
    q, kp, vp, tables, sids, lens = _case(jnp.bfloat16, 3, 8)
    with pytest.raises(ValueError, match="whole sublane tiles"):
        ragged_paged_attention(q, kp, vp, tables, sids, lens,
                               head_major=True, interpret=True)
    with pytest.raises(ValueError, match="kv_starts needs head_major"):
        ragged_paged_attention(q, kp, vp, tables, sids, lens,
                               kv_starts=lens)


# ---- the expert layer -------------------------------------------------

def _expert_by_expert(x, router_w, w_gate_up, w_down, top_k, first=0):
    """The held experts' part computed densely, an expert at a time."""
    w, ids = expert_layer.route_top_k(x, router_w, top_k)
    m = w_gate_up.shape[2] // 2
    out = jnp.zeros(x.shape, jnp.float32)
    for j in range(w_gate_up.shape[0]):
        wj = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)
        h = jnp.matmul(x, w_gate_up[j], precision="highest")
        y = jnp.matmul(jax.nn.silu(h[:, :m]) * h[:, m:], w_down[j],
                       precision="highest")
        out = out + wj[:, None] * y
    return out


def _experts(rng, E, d=32, m=16, routed=16):
    return (jnp.asarray(rng.normal(size=(d, routed)), jnp.float32),
            jnp.asarray(rng.normal(size=(E, d, 2 * m)) * 0.2, jnp.float32),
            jnp.asarray(rng.normal(size=(E, m, d)) * 0.2, jnp.float32))


@pytest.mark.parametrize("first,held", [(0, 4), (4, 4), (12, 4), (0, 16)])
def test_held_experts_match_the_expert_by_expert_sum(first, held):
    rng = np.random.default_rng(1)
    router, gate_up, down = _experts(rng, held)
    x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    valid = jnp.asarray(np.arange(24) < 20)
    w, ids = expert_layer.route_top_k(x, router, 4)
    assert np.allclose(np.asarray(w.sum(-1)), 1.0)
    out, counters = jax.jit(
        expert_layer.held_experts_ffn,
        static_argnames=("first_expert",))(
            x, w, ids, valid, gate_up, down, first_expert=first)
    want = _expert_by_expert(
        x, router, gate_up, down, 4, first) * valid[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)
    on_held = (np.asarray(ids)[:20] >= first) \
        & (np.asarray(ids)[:20] < first + held)
    assert counters.tolist() == [
        80, int(on_held.sum()),
        len(set(np.asarray(ids)[:20][on_held].tolist()))]


def test_routing_drops_nothing_when_every_row_picks_one_expert():
    """No capacity: 40 rows that all choose experts 0-3 (the router is
    rigged) are all computed; a capacity layer would drop most."""
    rng = np.random.default_rng(2)
    _, gate_up, down = _experts(rng, 4)
    x = jnp.abs(jnp.asarray(rng.normal(size=(40, 32)), jnp.float32))
    router = jnp.zeros((32, 16)).at[:, :4].set(
        jnp.asarray([8.0, 6.0, 4.0, 2.0]))
    w, ids = expert_layer.route_top_k(x, router, 4)
    assert set(np.asarray(ids).reshape(-1).tolist()) == {0, 1, 2, 3}
    out, counters = expert_layer.held_experts_ffn(
        x, w, ids, jnp.ones((40,), bool), gate_up, down)
    assert counters.tolist() == [160, 160, 4]
    want = _expert_by_expert(x, router, gate_up, down, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-5)
    assert float(jnp.abs(out).min(axis=-1).max()) > 0     # no zero row


# ---- the cache manager of two pools -----------------------------------

def _engine(model=None, **kw):
    model = model or LagunaForCausalLM(laguna_tiny())
    cfg = dict(num_slots=2, page_size=16, max_model_len=128,
               token_budget=24, decode_k=4)
    cfg.update(kw)
    return LLMEngine(model, LLMEngineConfig(**cfg))


def test_the_window_pool_frees_behind_the_window():
    """A context of 6 windows (window 16, page 16): a slot never holds
    more than window / page + 2 window pages, while the full pool keeps
    a page every 16 positions; both empty at the end."""
    eng = _engine(num_pages={"full": 20, "window": 7})
    full, window = eng._caches
    req = eng.add_request(np.arange(40) % 250, max_new_tokens=56)
    most, full_most = 0, 0
    while eng.has_work():
        eng.step()
        if eng._slots[0] is not None:
            most = max(most, len(req.kind_pages[1]))
            full_most = max(full_most, len(req.kind_pages[0]))
            live = list(req.kind_pages[1])
            # what is held is the tail of the context, and the table
            # reads 0 (the trash page) behind it
            assert live == list(range(live[0], live[-1] + 1))
            assert not window.tables[0, :live[0]].any()
            assert window.tables[0, live[0]:live[-1] + 1].all()
    assert len(req.future.result()) == 96
    assert most <= 16 // 16 + 2 and full_most == 6
    assert eng.stats["window_pages_freed"] >= 3
    assert full.pool.num_live == 0 and window.pool.num_live == 0
    assert eng.stats["full_pages_live"] == 0


def test_preemption_releases_both_pools_and_replays_the_same_tokens():
    model = LagunaForCausalLM(laguna_tiny())
    prompts = [np.arange(30) % 250, (np.arange(34) * 7) % 250]
    roomy = _engine(model)
    want = [roomy.add_request(p, max_new_tokens=40) for p in prompts]
    while roomy.has_work():
        roomy.step()
    tight = _engine(model, num_pages={"full": 8, "window": 20})
    got = [tight.add_request(p, max_new_tokens=40) for p in prompts]
    while tight.has_work():
        tight.step()
        for c in tight._caches:
            c.pool.assert_consistent()
    assert tight.stats["preemptions"] > 0
    for a, b in zip(want, got):
        assert np.array_equal(a.future.result(), b.future.result())
    assert [c.pool.num_live for c in tight._caches] == [0, 0]


@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, kv_tier=True), "kv_tier"),
    (dict(spec_mode="ngram"), "speculative"),
])
def test_what_assumes_one_geometry_refuses_with_a_sentence(kw, word):
    with pytest.raises(ValueError, match=word + ".*cache kinds"):
        _engine(**kw)


def test_the_kv_wire_refuses_a_model_of_two_kinds():
    eng = _engine()
    with pytest.raises(ValueError, match="one page geometry"):
        eng.add_request(np.arange(8), prefill_only=True)
    with pytest.raises(ValueError, match="float pools"):
        _engine(kv_dtype="int8")


def test_two_budgets_size_two_pools():
    mc = laguna_tiny()
    per = {k.name: LLMEngineConfig.kv_bytes_per_page(mc, 16, "float32",
                                                     kind=k.name)
           for k in mc.cache_kinds()}
    assert per == {"full": 3 * 2 * 16 * 2 * 16 * 4,
                   "window": 6 * 2 * 16 * 2 * 16 * 4}
    ecfg = LLMEngineConfig.for_pool_budget(
        mc, {"full": 10 * per["full"], "window": 5 * per["window"]},
        kv_dtype="float32", num_slots=2, max_model_len=64)
    assert ecfg.num_pages == {"full": 11, "window": 6}
    eng = LLMEngine(LagunaForCausalLM(mc), ecfg)
    assert [c.pool.num_pages for c in eng._caches] == [11, 6]
    shapes = {tuple(p.shape) for p in eng._kv}
    assert shapes == {(11, 2, 16, 16), (6, 2, 16, 16)}     # head-major
    assert eng.pool_bytes() == 11 * per["full"] + 6 * per["window"]
    with pytest.raises(ValueError, match="cache kinds are"):
        LLMEngine(LagunaForCausalLM(mc),
                  LLMEngineConfig(num_pages={"sliding": 4}))


# ---- GPT behind the protocol ------------------------------------------

def test_gpt_answers_the_protocol_with_one_kind_and_keeps_its_layout():
    mc = gpt_tiny()
    (kind,) = mc.cache_kinds()
    assert (kind.name, kind.layers, kind.kv_heads, kind.head_dim,
            kind.window, kind.head_major) == ("kv", (0, 1), 4, 32, None,
                                              False)
    for kv, row in (("float32", 4 * 32 * 4), ("int8", 4 * (32 + 4)),
                    ("int4", 4 * (16 + 4))):
        assert LLMEngineConfig.kv_bytes_per_page(mc, 16, kv) == \
            2 * 2 * 16 * row
    model = GPTForCausalLM(mc)
    assert model.step_counters == ()
    assert model.compute_dtype() == model.gpt.wte.weight._value.dtype
    eng = LLMEngine(model, LLMEngineConfig(num_slots=2, num_pages=9,
                                           decode_k=4))
    (cache,) = eng._caches
    assert eng._step_tables() is cache.tables
    assert {tuple(p.shape) for p in eng._kv} == {(9, 16, 4, 32)}
    req = eng.add_request(np.arange(10), max_new_tokens=6)
    while eng.has_work():
        eng.step()
    assert len(req.future.result()) == 16
    assert "window_pages_freed" not in eng.stats
    assert not any(k.startswith("moe_") for k in eng.stats)


def test_served_through_llmserver_with_the_counters_in_stats():
    model = LagunaForCausalLM(laguna_tiny(num_experts_held=8))
    with inference.LLMServer(model, LLMEngineConfig(
            num_slots=2, max_model_len=96, token_budget=16,
            decode_k=4)) as server:
        out = server.submit(np.arange(20) % 250,
                            max_new_tokens=12).result(timeout=600)
        assert len(out) == 32
        st = server.engine.stats
        assert st["moe_assignments"] == 31 * 8 * 4
        assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
        assert 0 < st["moe_experts_touched"] <= st["moe_assignments_held"]
        assert server.engine.compile_stats() == {
            "executables": 1, "fused_executables": 1}


@pytest.mark.parametrize("rows", [4, 8])
def test_query_blocks_of_one_slot_read_its_pages_once(rows):
    """A tick's rows laid out again in blocks of one slot
    (`SlotBlockLayout`) and the kernel over those blocks give what
    the flat rows give: three prefill chunks of ragged lengths, two
    decode rows, dead rows at the end."""
    from paddle_tpu.nn.functional.attention import SlotBlockLayout

    rng = np.random.default_rng(3)
    kv, g, d, page, S, MP = 2, 3, 128, 8, 5, 12
    n = S * MP + 1
    kp = jnp.asarray(rng.normal(size=(n, kv, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, kv, page, d)), jnp.float32)
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    runs = [(3, 20, 11), (0, 0, 5), (4, 70, 1), (1, 33, 9), (2, 8, 1)]
    sids = np.concatenate([[s] * c for s, _, c in runs] + [[0] * 5])
    lens = np.concatenate([np.arange(a + 1, a + c + 1)
                           for _, a, c in runs] + [[0] * 5])
    sids, lens = sids.astype(np.int32), lens.astype(np.int32)
    q = jnp.asarray(rng.normal(size=(len(sids), kv * g, d)), jnp.float32)
    starts = lens - 24
    lay = SlotBlockLayout(jnp.asarray(sids), jnp.asarray(lens), rows, S)
    dest = np.asarray(lay.dest)
    live = lens > 0
    assert lay.total % rows == 0 and len(set(dest[live])) == live.sum()
    first_of_run = np.cumsum([0] + [c for *_, c in runs])[:-1]
    assert not (dest[first_of_run] % rows).any()     # runs start blocks
    assert (np.diff(dest[live]) >= 1).all() and dest[live].max() \
        < lay.total - 1 == dest[~live].max()
    assert np.array_equal(np.asarray(lay.lens)[dest[live]], lens[live])
    got = ragged_paged_attention(
        lay.spread(q), kp, vp, tables, lay.sids, lay.lens,
        kv_starts=lay.spread(jnp.asarray(starts)), head_major=True,
        q_per_slot=rows, interpret=True)[lay.dest]
    want = _dense_masked(q, kp, vp, tables, sids, lens,
                         np.where(live, starts, 0))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=2e-5)
