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
from paddle_tpu.nn.functional import attention as attention_fn
from paddle_tpu.nn.functional.attention import (
    SlotBlockLayout, SlotRunLayout, paged_attention_latent_expanded_jnp,
    paged_attention_latent_jnp)
from paddle_tpu.ops.pallas_kernels.paged_attention import (
    latent_expanded_attention, latent_paged_attention, launch_sites)
from paddle_tpu.text.models import sarvam_mla
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


# ---- the expanded form ------------------------------------------------

LATENT, ROPE, NOPE, VDIM = 32, 8, 16, 16      # sarvam_mla_tiny's widths


def _expanded_case(dtype, runs, total):
    """A pool of `[c | k_r | zeros]` rows, W_UK / W_UV, queries for a
    laid-out `total` rows and the runs' table (slot, first laid-out
    row, first kv length, live rows)."""
    rng = np.random.default_rng(1)
    H, P, S, MP, R = 4, 16, 3, 12, 128
    n = S * MP + 1
    pool = np.zeros((n, P, R))
    pool[..., :LATENT + ROPE] = rng.normal(size=(n, P, LATENT + ROPE)) * 0.5
    tables = rng.permutation(np.arange(1, n)).reshape(S, MP).astype(
        np.int32)
    w_uk = rng.normal(size=(H, NOPE, LATENT)) * 0.3
    w_uv = rng.normal(size=(H, LATENT, VDIM)) * 0.3
    q_nope = rng.normal(size=(total, H, NOPE))
    q_rope = rng.normal(size=(total, H, ROPE))
    table = [jnp.asarray([r[k] for r in runs] + [0] * (4 - len(runs)),
                         jnp.int32) for k in range(4)]
    cast = lambda x: jnp.asarray(x, dtype)              # noqa: E731
    return (cast(q_nope), cast(q_rope), cast(pool), cast(w_uk), cast(w_uv),
            tables, table)


def _dense_expanded(q_nope, q_rope, pool, w_uk, w_uv, tables, slot, first,
                    n, scale):
    """Dense masked attention on up-projected rows (numpy, float64):
    row i of the run attends its slot's positions below first + i."""
    f = lambda x: np.asarray(x, np.float64)             # noqa: E731
    q_nope, q_rope, pool, w_uk, w_uv = map(
        f, (q_nope, q_rope, pool, w_uk, w_uv))
    page = pool.shape[1]
    out = np.zeros((n,) + q_nope.shape[1:2] + (w_uv.shape[2],))
    for i in range(n):
        pos = np.arange(first + i)
        rows = pool[tables[slot, pos // page], pos % page]
        c, kr = rows[:, :LATENT], rows[:, LATENT:LATENT + ROPE]
        k = np.einsum("lc,hnc->hln", c, w_uk)
        v = np.einsum("lc,hcv->hlv", c, w_uv)
        s = (np.einsum("hn,hln->hl", q_nope[i], k)
             + np.einsum("hr,lr->hl", q_rope[i], kr)) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[i] = np.einsum("hl,hlv->hv", p / p.sum(-1, keepdims=True), v)
    return out


# (slot, first laid-out row, first row's kv length, live rows): from the
# start of a prompt; from a position that is no multiple of the page or
# the tile, ending mid-page; four runs in one launch, one of one row,
# each starting where the last one's rows end (rounded up to 16): a
# run's last sub-block of 8 or 32 rows runs over the next run's rows
_EXPANDED_RUNS = {
    "from_0": [(1, 0, 1, 24)],
    "odd_start_ends_mid_page": [(2, 16, 38, 21)],
    "four_runs": [(1, 0, 1, 20), (2, 32, 38, 13), (0, 48, 101, 1),
                  (1, 64, 150, 30)],
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 3e-2)])
@pytest.mark.parametrize("runs", list(_EXPANDED_RUNS))
@pytest.mark.parametrize("sub,tile,heads", [(8, 16, 1), (32, 64, 4)])
def test_expanded_walk_against_dense_attention_and_the_absorbed_walk(
        dtype, tol, runs, sub, tile, heads):
    """The kernel that up-projects in VMEM, a sub-block of 8 rows and a
    tile of one page, or 32 rows and four pages: against dense masked
    attention on up-projected rows, and against the absorbed walk's
    reference fed absorbed queries (the same numbers in the other
    form)."""
    runs, total = _EXPANDED_RUNS[runs], 128
    q_nope, q_rope, pool, w_uk, w_uv, tables, table = _expanded_case(
        dtype, runs, total)
    q = jnp.pad(jnp.concatenate([q_nope, q_rope], -1),
                ((0, 0), (0, 0), (0, 128 - LATENT - ROPE)))
    got = latent_expanded_attention(
        q.reshape(total, -1), pool, w_uk, w_uv, tables, *table, 0.2,
        sub_rows=sub, tile_tokens=tile, heads_per_step=heads,
        interpret=True)
    assert got.shape == (total, 4 * VDIM) and got.dtype == pool.dtype
    got = np.asarray(got, np.float64).reshape(total, 4, VDIM)
    f32 = jnp.float32       # (the CPU has no batched bf16 product)
    qa = jnp.concatenate([
        jnp.einsum("thn,hnc->thc", q_nope.astype(f32),
                   w_uk.astype(f32)).astype(pool.dtype), q_rope], -1)
    qa = jnp.pad(qa, ((0, 0), (0, 0), (0, 128 - LATENT - ROPE)))
    for slot, row0, first, n in runs:
        want = _dense_expanded(q_nope[row0:row0 + n], q_rope[row0:row0 + n],
                               pool, w_uk, w_uv, tables, slot, first, n, 0.2)
        np.testing.assert_allclose(got[row0:row0 + n], want, atol=tol)
        absorbed = paged_attention_latent_jnp(
            qa[row0:row0 + n], pool, tables, np.full(n, slot, np.int32),
            first + np.arange(n, dtype=np.int32), LATENT, 0.2)
        np.testing.assert_allclose(
            np.einsum("thc,hcv->thv", np.asarray(absorbed, np.float64),
                      np.asarray(w_uv, np.float64)), want, atol=tol)


def test_the_expanded_walk_says_what_it_cannot_take():
    q_nope, q_rope, pool, w_uk, w_uv, tables, table = _expanded_case(
        "float32", _EXPANDED_RUNS["from_0"], 32)
    q = jnp.pad(jnp.concatenate([q_nope, q_rope], -1), (
        (0, 0), (0, 0), (0, 128 - LATENT - ROPE))).reshape(32, -1)
    call = lambda q, pool=pool, w_uk=w_uk, **kw: latent_expanded_attention(  # noqa: E731
        q, pool, w_uk, w_uv, tables, *table, 0.2, interpret=True,
        **{"sub_rows": 8, **kw})
    with pytest.raises(ValueError, match="rotary padded to the row"):
        call(q[:, :4 * (NOPE + ROPE)])
    with pytest.raises(ValueError, match="whole sublane tiles"):
        call(q, pool=pool[:, :4])
    with pytest.raises(ValueError, match="at least a sub-block"):
        call(q, sub_rows=64)
    with pytest.raises(ValueError, match="whole tiles of 16 rows"):
        call(q[:24])
    with pytest.raises(ValueError, match="of the same heads"):
        call(q[:, :2 * 112], w_uk=w_uk[:2])
    with launch_sites() as sites:
        call(q)
    assert sites == {"mxu": 0, "vpu": 0, "latent_expanded": 1}


def _tick_rows(n_long, n_short, total, n_first=1):
    """A tick's rows: `n_first` rows of slot 2 from position 76 (a lone
    row), `n_long` of slot 0 from position 40, `n_short` of slot 1 from
    position 5, padding."""
    sids, lens = np.zeros(total, np.int32), np.zeros(total, np.int32)
    a, b = n_first + n_long, n_first + n_long + n_short
    sids[:n_first], lens[:n_first] = 2, 77 + np.arange(n_first)
    sids[n_first:a], lens[n_first:a] = 0, 41 + np.arange(n_long)
    sids[a:b], lens[a:b] = 1, 6 + np.arange(n_short)
    return jnp.asarray(sids), jnp.asarray(lens), b


def test_the_run_layout_keeps_runs_of_consecutive_positions_of_one_slot():
    sids, lens, _ = _tick_rows(9, 5, 24)
    # a jump of position inside slot 1's rows ends a run there
    lens = lens.at[12].add(3).at[13].add(3).at[14].add(3)
    lay = SlotRunLayout(sids, lens, 4, 8, 16)
    assert lay.max_runs == 6 and lay.total == 24 + 6 * 7 + 6 + 16
    assert np.asarray(lay.expanded).tolist() == [False] + [True] * 9 \
        + [False] * 14                     # runs of 9, 2 and 3 rows, 1 alone
    assert np.asarray(lay.run_slots)[:1].tolist() == [0]
    assert np.asarray(lay.run_rows).tolist() == [9, 0, 0, 0, 0, 0]
    assert np.asarray(lay.run_first)[0] == 41
    assert np.asarray(lay.dest)[1:10].tolist() == list(range(9))
    assert (np.asarray(lay.dest)[10:] == lay.total - 1).all()
    assert np.asarray(lay.src)[:9].tolist() == list(range(1, 10))
    two = SlotRunLayout(sids, lens, 3, 8, 0)
    assert np.asarray(two.run_rows).tolist() == [9, 3, 0, 0, 0, 0, 0, 0]
    assert np.asarray(two.run_row0)[:2].tolist() == [0, 16]
    assert np.asarray(two.run_first)[:2].tolist() == [41, 11]
    assert np.asarray(two.dest)[12:15].tolist() == [16, 17, 18]


E = sarvam_mla._EXPANDED_MIN_ROWS


@pytest.mark.parametrize("n_first,n_long,n_short,total,expanded,chunks", [
    pytest.param(1, E + 4, 20, E + 40, E + 4, 1, id="long_short_lone"),
    pytest.param(1, E, E - 1, 2 * E + 8, E, 1, id="one_chunk_left"),
    pytest.param(2, E + 1, E - 1, 2 * E + 8, E + 1, 2,
                 id="two_chunks_left"),
    pytest.param(1, E - 1, 3, E + 8, 0, 2, id="no_run_long_enough"),
    pytest.param(0, E + 8, 0, E + 8, E + 8, 0, id="one_run_alone"),
])
def test_a_tick_of_both_forms_gives_each_row_the_absorbed_walks_numbers(
        n_first, n_long, n_short, total, expanded, chunks):
    """One tick that mixes a run of at least E rows, a shorter run and a
    lone row of another slot: merged by row, the same numbers as the
    absorbed walk over every row (float32)."""
    model = SarvamMLAForCausalLM(sarvam_mla_tiny(max_seq_len=640))
    rng = np.random.default_rng(2)
    S, P, MP = 3, 16, 40
    n = S * MP + 1
    pool = np.zeros((n, P, 128), np.float32)
    pool[..., :LATENT + ROPE] = rng.normal(size=(n, P, LATENT + ROPE)) * 0.5
    pool = jnp.asarray(pool)
    tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(
        S, MP).astype(np.int32))
    sids, lens, live = _tick_rows(n_long, n_short, total, n_first)
    q_nope = jnp.asarray(rng.normal(size=(total, 4, NOPE)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(total, 4, ROPE)), jnp.float32)
    layer = model.layers[1]
    forms = sarvam_mla._TickForms(sids, lens)
    assert int(forms.runs.expanded.sum()) == expanded
    assert int(forms.chunks) == chunks
    want = model._walk_absorbed(layer, q_nope, q_rope, pool, tables, sids,
                                lens, None, None)
    got = model._walk_by_form(layer, q_nope, q_rope, pool, tables, sids,
                              lens, forms, False)
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], atol=2e-6)
    assert not np.asarray(got)[live:].any()
    ref = paged_attention_latent_expanded_jnp(
        q_nope, q_rope, pool, layer.w_uk._value, layer.w_uv._value, tables,
        sids, lens, model.config.softmax_scale())
    np.testing.assert_allclose(np.asarray(ref)[:live],
                               np.asarray(want)[:live], atol=2e-6)


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


@pytest.mark.parametrize("budget,expanded", [
    pytest.param(E + 16, E + 16, id="a_budget_of_long_runs"),
    pytest.param(16, 0, id="a_budget_under_the_threshold"),
])
def test_rows_by_form_in_stats_and_the_same_greedy_tokens(budget, expanded):
    """A budget of at least E rows serves its long runs EXPANDED (rows ×
    layers of exactly those runs; the rest absorbed), a budget under E
    no row; the greedy tokens are the eager forward's under both."""
    model = SarvamMLAForCausalLM(sarvam_mla_tiny(max_seq_len=640))
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=E + 64,
        token_budget=budget, kv_dtype="float32", decode_k=4))
    ids = np.random.default_rng(5).integers(0, 256, (E + 40,)).astype(
        np.int32)
    req = eng.add_request(ids, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    out = np.asarray(req.future.result())
    lg = np.asarray(model(out[None, :-1])._value[0])
    assert np.array_equal(lg[len(ids) - 1:].argmax(-1), out[len(ids):])
    # the first tick takes the whole budget of the prompt's rows: one
    # run of `budget` rows; what is left of the prompt is shorter than E
    assert eng.stats["mla_rows_expanded"] == 5 * expanded
    assert eng.stats["mla_rows_absorbed"] == 5 * (len(out) - 1 - expanded)
    assert eng.stats["paged_attn_latent_expanded_launches"] == 0  # jnp


@pytest.mark.parametrize("rows,sites", [
    pytest.param(16, {"latent": 5}, id="under_the_threshold"),
    pytest.param(E, {"latent": 5, "latent_expanded": 5},
                 id="at_the_threshold"),
])
def test_a_tick_under_the_threshold_holds_no_expanded_launch(
        monkeypatch, rows, sites):
    """The tick program traced as on a TPU (nothing runs): one absorbed
    launch (in the loop over chunks of the rows left) and one expanded
    a layer from E rows on; below it the absorbed launch alone, as
    before the expanded form was served."""
    monkeypatch.setattr(attention_fn, "_pallas_backend_ok", lambda: True)
    model = SarvamMLAForCausalLM(sarvam_mla_tiny(max_seq_len=640))
    kind = model.config.cache_kinds()[0]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    pools = [jax.ShapeDtypeStruct(kind.pool_shape(82, 16), jnp.float32)
             for _ in range(5)]
    with launch_sites() as seen:
        jax.eval_shape(
            lambda *a: model._paged_core(*a, slot_blocks=True),
            i32(rows), i32(rows), i32(rows), i32(rows), i32(2, 40),
            i32(rows), i32(2), pools)
    assert seen == {"mxu": 0, "vpu": 0, **sites}


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
