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


# the walk's page group at 30 K/V heads of 128 (bf16 pages of 16 or
# float32 pages of 8): 17 pages, 8 MiB over its four buffers
_GROUP_AT_30_HEADS = 17


def _case(dtype, g, page, kv=2, MP=12):
    rng = np.random.default_rng(0)
    d, S = 128, 3
    n = S * MP + 1
    kp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    q = jnp.asarray(rng.normal(size=(9, kv * g, d)), dtype)
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    sids = np.array([0, 0, 0, 1, 2, 2, 1, 0, 0], np.int32)
    if MP == 12:
        lens = [5, 6, 7, 90, 33, 34, 0, page * MP - 3, 1]
    else:
        # rows of several page groups: one filling a group exactly (with
        # and without the frontier offset of 3), one a token into the
        # next, one into the third, the last page of the table
        G = _GROUP_AT_30_HEADS * page
        lens = [5, G - 3, G, 2 * G + 5, 33, G + 1, 0, page * MP - 3, 1]
    return q, kp, vp, tables, sids, np.array(lens, np.int32)


@pytest.mark.parametrize("dtype,page,tol", [("float32", 8, 2e-5),
                                            ("bfloat16", 16, 3e-2)])
# (query heads a K/V head, K/V heads, window, pages a slot); at ONE
# query head a K/V head the walk takes its heads in blocks
# (`_mha_walk_kernel`): 30 heads are a block and a short one, 3 less
# than one; 40 pages a slot at 30 heads walk up to three page groups
@pytest.mark.parametrize("g,kv,window,mp", [
    pytest.param(3, 2, None, 12, id="3-None"),
    pytest.param(3, 2, 20, 12, id="3-20"),
    pytest.param(2, 2, 40, 12, id="2-40"),
    pytest.param(1, 30, None, 12, id="1-kv30-None"),
    pytest.param(1, 30, 20, 12, id="1-kv30-20"),
    pytest.param(1, 3, None, 12, id="1-kv3-None"),
    pytest.param(1, 3, 20, 12, id="1-kv3-20"),
    pytest.param(1, 30, None, 40, id="1-kv30-None-groups"),
    pytest.param(1, 30, 300, 40, id="1-kv30-300-groups")])
@pytest.mark.parametrize("offset", [None, 3])
def test_gqa_kernel_with_a_lower_bound_against_dense_masked_attention(
        dtype, page, tol, g, kv, window, mp, offset):
    q, kp, vp, tables, sids, lens = _case(jnp.dtype(dtype), g, page, kv, mp)
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


# (rows a block, K/V heads, query heads a K/V head, dtype, page, tol,
# pages a slot, window, frontier offset); 40 pages a slot at 30 heads
# walk up to three page groups of 17 pages
@pytest.mark.parametrize("rows,kv,g,dtype,page,tol,mp,window,offset", [
    pytest.param(4, 2, 3, "float32", 8, 2e-5, 12, 24, 0, id="4"),
    pytest.param(8, 2, 3, "float32", 8, 2e-5, 12, 24, 0, id="8"),
    pytest.param(8, 30, 1, "bfloat16", 16, 3e-2, 12, 24, 0,
                 id="8-kv30-g1-bfloat16"),
    pytest.param(1, 30, 1, "float32", 8, 2e-5, 12, 24, 0,
                 id="1-kv30-g1-float32"),
    pytest.param(8, 3, 1, "float32", 8, 2e-5, 12, 24, 0,
                 id="8-kv3-g1-float32"),
    pytest.param(1, 3, 1, "bfloat16", 16, 3e-2, 12, 24, 0,
                 id="1-kv3-g1-bfloat16"),
    pytest.param(8, 30, 1, "bfloat16", 16, 3e-2, 40, None, 0,
                 id="8-kv30-g1-bfloat16-groups"),
    pytest.param(8, 30, 1, "bfloat16", 16, 3e-2, 40, None, 3,
                 id="8-kv30-g1-bfloat16-groups-offset"),
    pytest.param(8, 30, 1, "float32", 8, 2e-5, 40, 150, 3,
                 id="8-kv30-g1-float32-groups-window-offset")])
def test_query_blocks_of_one_slot_read_its_pages_once(
        rows, kv, g, dtype, page, tol, mp, window, offset):
    """A tick's rows laid out again in blocks of one slot
    (`SlotBlockLayout`) and the kernel over those blocks give what
    the flat rows give: three prefill chunks of ragged lengths, two
    decode rows, dead rows at the end; at G = 1 through the blocks of
    heads of `_mha_walk_kernel`, and over several page groups: blocks
    whose rows end on a group's last page (under the frontier offset
    too), a chunk into the next group, a block ending two groups in."""
    from paddle_tpu.nn.functional.attention import SlotBlockLayout

    rng = np.random.default_rng(3)
    d, S, MP = 128, 5, mp
    n = S * MP + 1
    dtype = jnp.dtype(dtype)
    kp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n, kv, page, d)), dtype)
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    if MP == 12:
        runs = [(3, 20, 11), (0, 0, 5), (4, 70, 1), (1, 33, 9), (2, 8, 1)]
    else:
        G = _GROUP_AT_30_HEADS * page - offset
        runs = [(3, G - 8, 8), (0, 0, 5), (4, G + 8, 17),
                (1, 2 * G + offset - 8, 8), (2, 8, 1)]
    sids = np.concatenate([[s] * c for s, _, c in runs] + [[0] * 5])
    lens = np.concatenate([np.arange(a + 1, a + c + 1)
                           for _, a, c in runs] + [[0] * 5])
    sids, lens = sids.astype(np.int32), lens.astype(np.int32)
    q = jnp.asarray(rng.normal(size=(len(sids), kv * g, d)), dtype)
    # no window: no lower bound (far enough below 0)
    starts = lens - (window or 2 ** 20)
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
        kv_starts=lay.spread(jnp.asarray(starts)),
        frontier_offset=offset or None, head_major=True,
        q_per_slot=rows, interpret=True)[lay.dest]
    hi = np.where(live, lens + offset, 0)
    want = _dense_masked(q, kp, vp, tables, sids, hi,
                         np.where(live, starts + offset, 0))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=tol)
    assert not np.asarray(got[~live], np.float32).any()   # padding rows


def _head_major_model(family):
    """A model of head-major pools at head_dim 128 (what the walk
    takes): olmo-hybrid's 3 full heads over 3 K/V heads, or laguna's 4
    and 6 query heads over 2."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                                    olmo_hybrid_tiny)

    paddle.seed(7)
    if family == "olmo":
        model = OlmoHybridForCausalLM(olmo_hybrid_tiny(
            hidden_size=384, num_heads=3, num_kv_heads=3))
    else:
        model = LagunaForCausalLM(laguna_tiny(head_dim=128))
    model.eval()
    return model


def _serve_two(model):
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=64, token_budget=16,
        decode_k=4))
    reqs = [eng.add_request(np.arange(n) % 250, max_new_tokens=7)
            for n in (5, 19)]
    while eng.has_work():
        eng.step()
    return eng, [r.future.result(timeout=0) for r in reqs]


@pytest.mark.parametrize("family,body,layers", [("olmo", "mha", 1),
                                                ("laguna", "gqa", 9)])
def test_head_major_launches_are_counted_by_body(monkeypatch, family, body,
                                                 layers):
    """With the walk forced on (interpreted): a model whose query heads
    are its K/V heads runs every head-major launch through the `mha`
    body, one whose queries share them through the `gqa` body; a tick
    adds a launch a paged layer, a window of k that k times; the served
    tokens are the plain path's. Both counters read 0 on the plain
    path."""
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops.pallas_kernels import paged_attention as pak
    from paddle_tpu.text.models import olmo_hybrid

    model = _head_major_model(family)
    eng, want = _serve_two(model)
    for name in ("paged_attn_mha_launches", "paged_attn_gqa_launches"):
        assert eng.stats[name] == 0 == eng.metrics()[name]
    kernel = pak.ragged_paged_attention
    monkeypatch.setattr(attention, "_pallas_backend_ok", lambda: True)
    monkeypatch.setattr(olmo_hybrid, "_pallas_backend_ok", lambda: True)
    monkeypatch.setattr(
        pak, "ragged_paged_attention",
        lambda *a, **kw: kernel(*a, **{**kw, "interpret": True}))
    eng, served = _serve_two(model)
    windows = eng.stats["fused_steps"]
    ticks = eng.stats["steps"] - windows
    assert windows > 0 and ticks > 0
    other = "gqa" if body == "mha" else "mha"
    assert eng._step_fn.launches[body] == layers
    assert eng._fused_fn.launches[body] == layers * 4
    assert other not in eng._step_fn.launches
    assert eng.stats[f"paged_attn_{body}_launches"] == layers * (
        ticks + 4 * windows) == eng.metrics()[f"paged_attn_{body}_launches"]
    assert eng.stats[f"paged_attn_{other}_launches"] == 0
    for got, ref in zip(served, want):
        np.testing.assert_array_equal(got, ref)
