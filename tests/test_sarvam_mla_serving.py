"""A LATENT cache kind on the serving path, on the CPU at small sizes:
the latent walk (one pool a layer, a row a token with no head axis,
keys AND values from the row) against plain masked attention, the cache
kind's pool and byte budget, what the engine refuses for it, the
sigmoid router beside the softmax one (whose numbers stay to the
digit), and the model served through `LLMServer`."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.nn import expert_layer
from paddle_tpu.nn.functional.attention import (SlotBlockLayout,
                                                paged_attention_latent_jnp)
from paddle_tpu.ops.pallas_kernels.paged_attention import (
    latent_paged_attention, launch_sites)
from paddle_tpu.text.models.sarvam_mla import (SarvamMLAForCausalLM,
                                               sarvam_mla_tiny)
from paddle_tpu.text.models.serving_protocol import CacheKind


# ---- the kernel -------------------------------------------------------

def _dense_latent(q, pool, tables, sids, lens, v_dim, scale):
    """Plain masked attention a row at a time (numpy, float64): every
    head's query against the slot's rows, values their first lanes."""
    T, H, _ = q.shape
    page = pool.shape[1]
    out = np.zeros((T, H, v_dim))
    pool, q = np.asarray(pool, np.float64), np.asarray(q, np.float64)
    for t in range(T):
        if int(lens[t]) == 0:
            continue
        pos = np.arange(int(lens[t]))
        rows = pool[tables[sids[t], pos // page], pos % page]   # [L, R]
        s = q[t] @ rows.T * scale                               # [H, L]
        p = np.exp(s - s.max(-1, keepdims=True))
        out[t] = (p / p.sum(-1, keepdims=True)) @ rows[:, :v_dim]
    return out


def _case(dtype):
    rng = np.random.default_rng(0)
    H, R, P, S, MP = 4, 128, 16, 3, 12
    n = S * MP + 1
    pool = jnp.asarray(rng.normal(size=(n, P, R)) * 0.5, dtype)
    q = jnp.asarray(rng.normal(size=(12, H, R)) * 0.5, dtype)
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    # the tick's layout: live rows first, a slot's rows side by side
    sids = np.array([0, 0, 0, 0, 0, 1, 2, 2, 0, 0, 0, 0], np.int32)
    lens = np.array([60, 61, 62, 63, 64, 150, P * MP - 1, P * MP, 0, 0, 0,
                     0], np.int32)
    return q, pool, tables, sids, lens


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 3e-2)])
@pytest.mark.parametrize("group_tokens", [16, 64, 512])
def test_latent_walk_against_dense_masked_attention(dtype, tol,
                                                    group_tokens):
    """One row a block: every head of a row against its slot's rows, a
    group of pages a step (one page, four, more than the context)."""
    q, pool, tables, sids, lens = _case(dtype)
    want = _dense_latent(q, pool, tables, sids, lens, 96, 0.2)
    got = latent_paged_attention(q, pool, tables, sids, lens, 96, 0.2,
                                 group_tokens=group_tokens, interpret=True)
    assert got.shape == (12, 4, 96) and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol)
    ref = paged_attention_latent_jnp(q, pool, tables, sids, lens, 96, 0.2)
    np.testing.assert_allclose(np.asarray(ref, np.float64), want, atol=tol)
    assert not np.asarray(got[8:], np.float32).any()    # padding rows


@pytest.mark.parametrize("rows", [2, 4, 8])
def test_query_blocks_of_one_slot_read_its_pages_once(rows):
    """The tick's slot-block layout: a block of `rows` rows of ONE slot
    (a lone decoding row takes the one-row product inside the same
    kernel), the frontier offset advancing live rows only."""
    q, pool, tables, sids, lens = _case("float32")
    want = _dense_latent(q, pool, tables, sids, lens, 96, 0.2)
    lay = SlotBlockLayout(jnp.asarray(sids), jnp.asarray(lens), rows, 3)
    base = jnp.where(lay.lens > 0, lay.lens - 2, 0)
    got = latent_paged_attention(
        lay.spread(q), pool, tables, lay.sids, base, 96, 0.2,
        frontier_offset=jnp.int32(2), q_per_slot=rows, group_tokens=64,
        interpret=True)[lay.dest]
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got, np.float64)[live],
                               want[live], atol=2e-5)


def test_the_latent_walk_says_what_it_cannot_take():
    q, pool, tables, sids, lens = _case("float32")
    with pytest.raises(ValueError, match="as wide as the pool's row"):
        latent_paged_attention(q[..., :64], pool, tables, sids, lens, 32,
                               0.2, interpret=True)
    with pytest.raises(ValueError, match="whole sublane tiles"):
        latent_paged_attention(q, pool[:, :4], tables, sids, lens, 32,
                               0.2, interpret=True)
    with pytest.raises(ValueError, match="whole blocks"):
        latent_paged_attention(q[:11], pool, tables, sids[:11], lens[:11],
                               32, 0.2, q_per_slot=4, interpret=True)
    with launch_sites() as sites:
        latent_paged_attention(q, pool, tables, sids, lens, 96, 0.2,
                               interpret=True)
    assert sites == {"mxu": 0, "vpu": 0, "latent": 1}


# ---- the cache kind ---------------------------------------------------

def test_a_latent_kind_is_one_pool_of_rows_in_whole_lane_tiles():
    kind = CacheKind("latent", (0, 1, 2), None, None, None, False, 576)
    assert kind.latent and kind.pools_per_layer == 1
    assert kind.row_store == 640
    assert kind.pool_shape(100, 16) == (100, 16, 640)
    two = CacheKind("kv", (0,), 4, 32, None, False)
    assert not two.latent and two.pools_per_layer == 2
    assert two.pool_shape(100, 16) == (100, 16, 4, 32)
    mc = sarvam_mla_tiny()
    (only,) = mc.cache_kinds()
    assert only.latent and only.row_dim == 32 + 8 and only.window is None
    # bytes a page: layers · page · the row AS STORED · itemsize
    per = LLMEngineConfig.kv_bytes_per_page(mc, 16, "float32")
    assert per == 5 * 16 * 128 * 4
    cfg = LLMEngineConfig.for_pool_budget(mc, 40 * per, page_size=16,
                                          kv_dtype="float32")
    assert cfg.num_pages == 41


def _engine(**kw):
    model = SarvamMLAForCausalLM(sarvam_mla_tiny())
    args = dict(num_slots=3, page_size=16, max_model_len=128,
                token_budget=16, kv_dtype="float32")
    args.update(kw)
    return LLMEngine(model, LLMEngineConfig(**args))


@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, kv_tier=True), "kv_tier"),
    (dict(spec_mode="ngram"), "speculative"),
])
def test_what_reads_keys_and_values_a_head_refuses_a_latent_kind(kw, word):
    with pytest.raises(ValueError, match=word + ".*latent"):
        _engine(**kw)


def test_the_kv_wire_and_quantised_pools_refuse_a_latent_kind():
    eng = _engine()
    with pytest.raises(ValueError, match="one page geometry"):
        eng.add_request(np.arange(8), prefill_only=True)
    with pytest.raises(ValueError, match="float pools"):
        _engine(kv_dtype="int8")


def test_preemption_releases_the_latent_pool_and_replays_the_same_tokens():
    model = SarvamMLAForCausalLM(sarvam_mla_tiny())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (40, 33, 25)]

    def run(num_pages):
        eng = LLMEngine(model, LLMEngineConfig(
            num_slots=3, page_size=16, max_model_len=128, token_budget=16,
            kv_dtype="float32", decode_k=4, num_pages=num_pages))
        reqs = [eng.add_request(p, max_new_tokens=30) for p in prompts]
        while eng.has_work():
            eng.step()
            eng.pool.assert_consistent()
        return eng, [np.asarray(r.future.result()) for r in reqs]

    roomy, want = run(None)
    tight, got = run(10)
    assert roomy.stats["preemptions"] == 0 < tight.stats["preemptions"]
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    assert tight.pool.num_live == 0
    assert tight.stats["latent_pages_live"] == 0


def test_served_through_llmserver_with_the_counters_in_stats():
    model = SarvamMLAForCausalLM(sarvam_mla_tiny(num_experts_held=8))
    model.eval()
    cfg = LLMEngineConfig(num_slots=2, page_size=16, max_model_len=96,
                          token_budget=16, kv_dtype="float32", decode_k=4)
    ids = np.random.default_rng(1).integers(0, 256, (30,)).astype(np.int32)
    with inference.LLMServer(model, cfg) as server:
        out = np.asarray(server.submit(ids, max_new_tokens=12).result(
            timeout=600))
        stats = dict(server.engine.stats)
        occ = server.metrics()["kv_page_occupancy"]
    assert len(out) == 42 and np.array_equal(out[:30], ids)
    # greedy through the cache = greedy on the eager (expanded) forward
    lg = np.asarray(model(out[None, :-1])._value[0])
    assert np.array_equal(lg[29:].argmax(-1), out[30:])
    n = 41
    assert stats["moe_assignments"] == n * 4 * 4
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    assert stats["mla_rows_absorbed"] == 5 * n
    assert stats["mla_rows_attended_least"] == \
        5 * stats["kv_positions_least_latent"]
    assert stats["paged_attn_latent_launches"] == 0     # the jnp path
    assert occ == 0.0


# ---- the router -------------------------------------------------------

def test_softmax_routing_keeps_its_numbers_to_the_digit():
    """Laguna's call (softmax, no bias): the weights and ids of a fixed
    input, frozen before `route_top_k` learned another scoring."""
    x = jnp.asarray(np.linspace(-1.0, 1.0, 3 * 8).reshape(3, 8),
                    jnp.float32)
    w = jnp.asarray(np.sin(np.arange(8 * 6, dtype=np.float64)).reshape(
        8, 6), jnp.float32)
    weights, ids = expert_layer.route_top_k(x, w, 2)
    assert np.asarray(ids).tolist() == [[5, 0], [4, 5], [3, 2]]
    # read off the parent commit's `route_top_k` (float.hex)
    frozen = [["0x1.5caf50p-1", "0x1.46a15ep-2"],
              ["0x1.27da9ep-1", "0x1.b04ac2p-2"],
              ["0x1.83b3f2p-1", "0x1.f13040p-3"]]
    assert [[float(v) for v in r] for r in np.asarray(weights)] == [
        [float.fromhex(v) for v in r] for r in frozen]
    raw, _ = expert_layer.route_top_k(x, w, 2, renormalise=False)
    assert float(np.asarray(raw).sum(-1).max()) < 1.0


def test_the_grouped_products_tiles_follow_the_shapes():
    tile = expert_layer._gmm_tile_k
    # Laguna's widths keep their tiles; d 4096 and 2 048 halve / fit
    assert [tile(k) for k in (3072, 1024, 4096, 2048)] == [
        3072, 1024, 2048, 2048]
    assert tile(64) == 64 and tile(5120) == 2560
    for k in (3072, 1024, 4096, 2048, 5120):
        assert k % tile(k) == 0 and tile(k) <= 3072


def test_yarn_scale_and_rotary_are_the_familys():
    c = sarvam_mla_tiny()
    inv, factor = c.rope_frequencies()
    assert factor == 1.0 and inv.shape == (4,)
    assert c.softmax_scale() == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    plain = sarvam_mla_tiny(rope_scaling=None)
    assert plain.softmax_scale() == pytest.approx(24 ** -0.5)
    np.testing.assert_allclose(plain.rope_frequencies()[0],
                               10000.0 ** (-np.arange(0, 8, 2) / 8))
