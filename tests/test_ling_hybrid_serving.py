"""A STATE cache kind on the serving path, on the CPU at small sizes: the
gated delta rule's two forms against the recurrence written out a token
at a time, the kernel of the recurrent step against its plain spelling,
the cache kind's slab and what the engine refuses for it, group-limited
routing, and the hybrid model (KDA state slabs beside MLA's latent
pages) served through `LLMEngine`: slots reused, requests preempted and
replayed, the counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import inference
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.nn import expert_layer
from paddle_tpu.nn.functional import delta_rule as dr
from paddle_tpu.nn.functional.attention import SlotRunLayout
from paddle_tpu.ops.pallas_kernels import delta_rule as kernels
from paddle_tpu.ops.pallas_kernels.delta_rule import delta_rule_recurrent
from paddle_tpu.text.models import ling_hybrid
from paddle_tpu.text.models.ling_hybrid import (LingHybridForCausalLM,
                                                ling_hybrid_tiny)
from paddle_tpu.text.models.serving_protocol import CacheKind

H, DK = 2, 16


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    nrm = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    k = nrm(n, H, DK)
    return (nrm(n, H, DK) * DK ** -0.5,
            k / jnp.linalg.norm(k, axis=-1, keepdims=True), nrm(n, H, DK),
            -5.0 * jax.nn.sigmoid(2.0 * nrm(n, H, DK)),
            jax.nn.sigmoid(nrm(n, H)))


def _by_token(state, q, k, v, g, beta):
    """The recurrence as written, float64 numpy, a token at a time."""
    S = np.asarray(state, np.float64).copy()
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            Sd = S[h] * np.exp(g[t, h])[:, None]
            u = beta[t, h] * (v[t, h] - Sd.T @ k[t, h])
            S[h] = Sd + np.outer(k[t, h], u)
            out[t, h] = S[h].T @ q[t, h]
    return out, S


# ---- the two forms ----------------------------------------------------

@pytest.mark.parametrize("n,pos0,chunk", [
    pytest.param(40, 9, 64, id="part_of_one_chunk"),
    pytest.param(64, 1, 64, id="one_whole_chunk"),
    pytest.param(150, 33, 64, id="two_chunks_and_a_part"),
    pytest.param(150, 33, 32, id="chunks_of_32"),
    pytest.param(70, 0, 64, id="from_position_0_the_state_counts_as_zero"),
])
def test_chunked_equals_recurrent_from_a_state_that_is_not_zero(n, pos0,
                                                                chunk):
    """One run of `n` rows of slot 1 (of 3) from position `pos0`, its
    slot's state random: the CHUNKED form, the RECURRENT step a row at a
    time, and the recurrence written out in float64. Decays down to e^-5
    a token: exp(-G) alone would overflow float32 inside a chunk; the
    sub-block references keep every factor finite (2e-5: float32
    products of up to 64 terms in another order)."""
    T = 160
    q, k, v, g, beta = _rows(T, seed=n)
    state0 = jnp.asarray(np.random.default_rng(1).normal(
        size=(3, H, DK, DK)), jnp.float32)
    sids = np.zeros((T,), np.int32)
    lens = np.zeros((T,), np.int32)
    sids[:n], lens[:n] = 1, pos0 + 1 + np.arange(n)
    start = np.zeros_like(state0[1]) if pos0 == 0 else state0[1]
    want_o, want_s = _by_token(start, q[:n], k[:n], v[:n], g[:n], beta[:n])
    runs = SlotRunLayout(jnp.asarray(sids), jnp.asarray(lens), 1, chunk, 0)
    o, st, used = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                        chunk=chunk)
    assert int(used) == -(-n // chunk)
    np.testing.assert_allclose(np.asarray(o[:n]), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st[1]), want_s, atol=2e-5)
    assert not np.asarray(o[n:]).any()
    for other in (0, 2):                      # nobody else's state moved
        assert np.array_equal(np.asarray(st[other]),
                              np.asarray(state0[other]))
    live = jnp.asarray([False, True, False])
    st2 = state0
    for t in range(n):
        row = lambda a: jnp.broadcast_to(a[t], (3,) + a.shape[1:])  # noqa
        got, st2 = dr.delta_rule_step(
            st2, row(q), row(k), row(v), row(g), row(beta), live,
            live & (pos0 + t == 0))
        np.testing.assert_allclose(np.asarray(got[1]), want_o[t],
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(st2[1]), want_s, atol=2e-5)


@pytest.mark.parametrize("live,fresh", [
    pytest.param([1, 0, 1, 1, 0], [0, 0, 1, 0, 0], id="some_slots"),
    pytest.param([0, 0, 0, 0, 0], [0, 0, 0, 0, 0], id="no_live_slot"),
    pytest.param([1, 1, 1, 1, 1], [0, 0, 0, 0, 1], id="every_slot"),
])
def test_the_recurrent_kernel_visits_the_live_slots_only(live, fresh):
    """The Pallas kernel (interpreted) against the plain spelling: the
    live slots' states and outputs to float32 rounding, every other
    slot's state BIT for bit (it is never visited: the aliasing keeps
    it), a fresh row's stored state forgotten."""
    rng = np.random.default_rng(0)
    S, Hk, dk = 5, 4, 128
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    state = f(S, Hk, dk, dk)
    q, k, v, beta = f(S, Hk, dk), f(S, Hk, dk) * 0.1, f(S, Hk, dk), \
        jax.nn.sigmoid(f(S, Hk))
    g = -jnp.abs(f(S, Hk, dk))
    live, fresh = jnp.asarray(live, bool), jnp.asarray(fresh, bool)
    want_o, want_s = dr.delta_rule_step(state, q, k, v, g, beta, live,
                                        fresh, kernel=False)
    import functools
    from unittest import mock

    with mock.patch("paddle_tpu.ops.pallas_kernels.delta_rule."
                    "delta_rule_recurrent", functools.partial(
                        delta_rule_recurrent, interpret=True)):
        got_o, got_s = dr.delta_rule_step(state, q, k, v, g, beta, live,
                                          fresh, kernel=True)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_o)[on],
                               np.asarray(want_o)[on], atol=1e-4)
    assert np.array_equal(np.asarray(got_s)[~on], np.asarray(state)[~on])


def _tick(runs_of, T):
    """sids, lens [T] of a tick whose runs are (slot, first flat row,
    rows, first position); every other row dead."""
    sids = np.zeros((T,), np.int32)
    lens = np.zeros((T,), np.int32)
    for slot, at, n, pos0 in runs_of:
        sids[at:at + n], lens[at:at + n] = slot, pos0 + 1 + np.arange(n)
    return sids, lens


def _both_chunked_forms(state0, q, k, v, g, beta, runs, chunk=dr.CHUNK):
    """(the plain XLA form's, the interpreted kernel's) results."""
    import functools
    from unittest import mock

    want = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                 chunk=chunk, kernel=False)
    with mock.patch.object(kernels, "delta_rule_chunks", functools.partial(
            kernels.delta_rule_chunks, interpret=True)):
        got = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                    chunk=chunk, kernel=True)
    return want, got


def _kernel_rows(T, Hk=2, dk=128, slots=4, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    k = f(T, Hk, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (f(slots, Hk, dk, dk), f(T, Hk, dk) * dk ** -0.5, k,
            f(T, Hk, dk), -5.0 * jax.nn.sigmoid(2.0 * f(T, Hk, dk)),
            jax.nn.sigmoid(f(T, Hk)))


@pytest.mark.parametrize("runs_of,chunk", [
    pytest.param(((1, 0, 70, 33), (2, 70, 50, 0)), 32,
                 id="two_runs_one_from_0"),
    pytest.param(((1, 0, 100, 5),), 32, id="one_run_into_a_fourth_chunk"),
    pytest.param((), 32, id="no_run_at_all"),
    pytest.param(((1, 37, 75, 12),), 32,
                 id="a_run_from_a_flat_row_that_is_no_multiple_of_the_chunk"),
    pytest.param(((3, 3, 45, 0), (1, 50, 41, 9)), 32,
                 id="two_runs_of_two_slots_lone_rows_between"),
    pytest.param(((2, 1, 40, 7), (0, 43, 64, 0), (3, 111, 49, 100)), 32,
                 id="three_runs_lone_rows_between_the_last_to_the_ticks_end"),
    pytest.param(((1, 90, 70, 2),), 32,
                 id="a_partial_chunk_whose_copy_would_pass_the_last_row"),
    pytest.param(((1, 5, 150, 3),), 64, id="chunks_of_64"),
])
def test_the_chunk_kernel_against_the_plain_chunked_form(runs_of, chunk):
    """The Pallas kernel of the chunked form (interpreted; a chunk a grid
    step read from the tick's flat rows where they lie, the state carried
    in the result's block, (I + A)⁻¹ as a product of I + (−A)^(2^i))
    against the plain XLA spelling (the runs laid out again, batched
    products, `solve_triangular`): outputs and states to 5e-6; a run's
    last, partial chunk writes nothing past its rows: the rows off the
    runs (lone rows between them among those) read ZERO; the slots
    without a run keep their state bit for bit, and a tick without a run
    every state (passed through, aliased)."""
    T = 160
    state0, q, k, v, g, beta = _kernel_rows(T)
    sids, lens = _tick(runs_of, T)
    for lone in (0, 48, 159):       # lone rows where no run lies
        if not lens[lone]:
            lens[lone] = 500 + lone
    runs = SlotRunLayout(jnp.asarray(sids), jnp.asarray(lens), 40,
                         chunk, 0)
    want, got = _both_chunked_forms(state0, q, k, v, g, beta, runs, chunk)
    assert int(got[2]) == int(want[2]) == sum(
        -(-n // chunk) for _, _, n, _ in runs_of)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=5e-6)
    off_runs = np.ones((T,), bool)
    for _, at, n, _ in runs_of:
        off_runs[at:at + n] = False
    assert not np.asarray(got[0])[off_runs].any()
    for slot in set(range(4)) - {r[0] for r in runs_of}:
        assert np.array_equal(np.asarray(got[1][slot]),
                              np.asarray(state0[slot]))


def test_the_chunk_kernel_takes_four_heads_as_one_block_diagonal_operand():
    """Eight heads at a chunk of 32: two GROUPS of 128 / 32 = 4 heads,
    whose (I + A)⁻¹, T · [W | V] and B · U are one block-diagonal 128 ×
    128 product a group; against the plain form, head by head."""
    T = 96
    state0, q, k, v, g, beta = _kernel_rows(T, Hk=8, seed=1)
    runs = SlotRunLayout(*map(jnp.asarray, _tick(
        ((1, 2, 41, 6), (3, 50, 44, 0)), T)), 40, dr.CHUNK, 0)
    want, got = _both_chunked_forms(state0, q, k, v, g, beta, runs)
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)
    assert not np.asarray(got[0])[[0, 1, 43, 49, 94, 95]].any()


def test_a_run_continues_from_the_state_the_tick_before_stored():
    """A prompt of 150 rows in two ticks (86 rows from flat row 3, then
    64 from flat row 21 of the next): the second tick's run starts from
    the state the first wrote back; against ONE run of 150 rows and
    against the recurrence a token at a time."""
    T, n1, n = 160, 86, 150
    state0, q, k, v, g, beta = _kernel_rows(2 * T, seed=4)
    first = SlotRunLayout(*map(jnp.asarray, _tick(((2, 3, n1, 0),), T)),
                          40, dr.CHUNK, 0)
    second = SlotRunLayout(*map(jnp.asarray, _tick(((2, 21, n - n1, n1),),
                                                   T)), 40, dr.CHUNK, 0)
    rows1 = [a[:T] for a in (q, k, v, g, beta)]
    rows2 = [a[T:] for a in (q, k, v, g, beta)]
    _, (o1, st, _) = _both_chunked_forms(state0, *rows1, first)
    _, (o2, st, _) = _both_chunked_forms(st, *rows2, second)
    whole = [jnp.concatenate([a[3:3 + n1], b[21:21 + n - n1]])
             for a, b in zip(rows1, rows2)]
    want_o, want_s = _by_token(np.zeros_like(state0[2]), *whole)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(
        [o1[3:3 + n1], o2[21:21 + n - n1]])), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st[2]), want_s, atol=2e-5)
    assert np.array_equal(np.asarray(st[1]), np.asarray(state0[1]))


def test_the_chunk_kernel_says_what_it_cannot_take():
    state0, q, k, v, g, beta = _kernel_rows(64)
    table = [jnp.zeros((3,), jnp.int32)] * 5 + [jnp.zeros((1,), jnp.int32)]
    with pytest.raises(ValueError, match="power of two"):
        kernels.delta_rule_chunks(state0, q, k, v, g, beta,
                                  jnp.zeros_like(v), *table, chunk=48)
    with pytest.raises(ValueError, match="whole tiles of 8"):
        kernels.delta_rule_chunks(state0, q, k, v, g, beta,
                                  jnp.zeros_like(v), *table)
    with pytest.raises(ValueError, match="at least a chunk"):
        kernels.delta_rule_chunks(state0, q[:16], k[:16], v[:16], g[:16],
                                  beta[:16], jnp.zeros_like(v[:16]), *table,
                                  interpret=True)


# ---- routing ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_selection_keeps_the_best_groups_experts(seed):
    """32 experts in 8 groups of 4, 4 groups kept, 8 a token, against a
    plain spelling in float64 (seeds without ties): a group's score is
    the sum of its two largest score + bias; nobody outside the kept
    groups is chosen; the weights are the chosen scores renormalised."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(20, 16)).astype(np.float32)
    wr = rng.normal(size=(16, 32)).astype(np.float32)
    bias = (rng.normal(size=(32,)) * 0.3).astype(np.float32)
    w, ids = expert_layer.route_top_k(
        jnp.asarray(x), jnp.asarray(wr), 8, scoring="sigmoid",
        select_bias=jnp.asarray(bias), n_group=8, topk_group=4)
    score = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wr)))
    sel = score + bias
    limited = 0
    for t in range(20):
        group = np.sort(sel[t].reshape(8, 4), -1)[:, -2:].sum(-1)
        kept = np.argsort(-group)[:4]
        masked = np.where(np.isin(np.arange(32) // 4, kept), sel[t],
                          -np.inf)
        chosen = np.argsort(-masked)[:8]
        assert set(np.asarray(ids[t])) == set(chosen)
        limited += set(chosen) != set(np.argsort(-sel[t])[:8])
        want = score[t][np.asarray(ids[t])]
        np.testing.assert_allclose(np.asarray(w[t]), want / want.sum(),
                                   rtol=1e-5)
    assert limited                 # the limit changed someone's choice


def test_the_grouped_products_tiles_at_ling_shapes():
    """d 2560 contracts whole; 768 contracts whole; the column tile
    divides the 1 536 and 2 560 columns that 1 024 does not."""
    assert expert_layer._gmm_tile_n(1536) == 768
    assert expert_layer._gmm_tile_n(2560) == 1280
    assert [expert_layer._gmm_tile_n(n) for n in (
        256, 1024, 2048, 3072, 4096)] == [256, 1024, 1024, 1024, 1024]
    assert expert_layer._gmm_tile_k(2560) == 2560
    assert expert_layer._gmm_tile_k(768) == 768
    assert expert_layer._gmm_tile_k(3072) == 3072     # as before
    assert expert_layer._gmm_tile_k(4096) == 2048


# ---- the cache kind ---------------------------------------------------

def test_a_state_kind_is_a_slab_a_slot_and_no_pages():
    c = ling_hybrid_tiny()
    latent, state = c.cache_kinds()
    assert latent.latent and not latent.state
    assert latent.layers == (5,) and state.layers == (0, 1, 2, 3, 4, 6)
    assert state.state and not state.latent
    assert state.pools_per_layer == 2
    assert state.slab_arrays(3, "bfloat16") == [
        ((3, 4, 16, 16), "float32"), ((3, 3, 192), "bfloat16")]
    plain = CacheKind("full", (0,), 2, 16, None, False)
    assert not plain.state and plain.pools_per_layer == 2


def _engine(model=None, **kw):
    model = model or LingHybridForCausalLM(ling_hybrid_tiny())
    args = dict(num_slots=3, page_size=16, max_model_len=256,
                token_budget=16, kv_dtype="float32")
    args.update(kw)
    return LLMEngine(model, LLMEngineConfig(**args))


def test_the_engine_keeps_slabs_beside_the_latent_pool():
    eng = _engine()
    shapes = [tuple(a.shape) for a in eng._kv]
    assert shapes[:2] == [(3, 4, 16, 16), (3, 3, 192)]      # layer 0
    assert shapes[10] == (3 * 16 + 1, 16, 128)              # layer 5
    assert len(shapes) == 6 * 2 + 1
    assert eng._kv[0].dtype == jnp.float32
    assert [c.kind.name for c in eng._caches] == ["latent"]
    # the byte budget counts the slabs beside the pool
    assert eng.pool_bytes() == sum(int(a.nbytes) for a in eng._kv) \
        == 49 * 16 * 128 * 4 + 3 * 6 * (4096 + 3 * 192 * 4)
    assert eng.stats["state_slabs_live"] == 0


@pytest.mark.parametrize("kw,word", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, kv_tier=True), "kv_tier"),
    (dict(spec_mode="ngram"), "speculative"),
])
def test_what_needs_a_snapshot_of_the_state_is_refused(kw, word):
    with pytest.raises(ValueError,
                       match=word + ".*kda_state.*SNAPSHOT of the state"):
        _engine(**kw)


def test_the_kv_wire_quantised_pools_and_page_budgets_refuse_a_state_kind():
    eng = _engine()
    with pytest.raises(ValueError, match="one page geometry"):
        eng.add_request(np.arange(8), prefill_only=True)
    with pytest.raises(ValueError, match="state slab is float32"):
        _engine(kv_dtype="int8")
    with pytest.raises(ValueError, match="fixed slab a slot, not pages"):
        LLMEngineConfig.for_pool_budget(
            ling_hybrid_tiny(), {"latent": 1 << 20, "kda_state": 1 << 20})

    class OnlyState:
        vocab_size, max_seq_len = 256, 64

        def cache_kinds(self):
            return [ling_hybrid_tiny().cache_kinds()[1]]

    class Model:
        config = OnlyState()

        def eval(self):
            pass

    with pytest.raises(ValueError, match="at least one PAGED kind"):
        LLMEngine(Model(), LLMEngineConfig(num_slots=2))


# ---- served -----------------------------------------------------------

def _serve(model, prompts, new, **kw):
    eng = _engine(model, **kw)
    reqs = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
    return eng, [np.asarray(r.future.result()) for r in reqs]


@pytest.mark.parametrize("budget,decode_k", [
    pytest.param(80, 4, id="a_run_split_inside_a_chunk"),
    pytest.param(200, 1, id="whole_prompts_a_tick_no_window"),
    pytest.param(16, 4, id="every_run_recurrent"),
])
def test_chunks_then_decode_give_the_eager_forwards_tokens(budget,
                                                           decode_k):
    """Six prompts through three slots (so slots are REUSED after a
    finished request): chunked prefill whose runs begin and end inside a
    chunk, single prompt rows beside them, then decode: every served
    token is the eager forward's (one chunked run from zero) argmax."""
    model = LingHybridForCausalLM(ling_hybrid_tiny())
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (150, 97, 5, 70, 130, 1)]
    eng, outs = _serve(model, prompts, 10, token_budget=budget,
                       decode_k=decode_k)
    for p, out in zip(prompts, outs):
        assert len(out) == len(p) + 10
        lg = np.asarray(model(out[None, :-1])._value[0])
        assert np.array_equal(lg[len(p) - 1:].argmax(-1), out[len(p):])
    st = eng.stats
    rows = sum(len(o) - 1 for o in outs)
    assert st["kda_rows_recurrent"] + st["kda_rows_chunked"] == 6 * rows
    long_runs = budget >= ling_hybrid._CHUNKED_MIN_ROWS
    assert (st["kda_rows_chunked"] > 0) == long_runs
    assert (st["kda_chunk_launches"] > 0) == long_runs
    assert st["mla_rows_absorbed"] + st["mla_rows_expanded"] == rows
    assert st["state_slabs_zeroed"] == 6 * 6      # requests × KDA layers
    assert st["state_slabs_live"] == 0 == eng.pool.num_live


def test_a_preempted_request_replays_its_state_from_position_0():
    model = LingHybridForCausalLM(ling_hybrid_tiny())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (40, 33, 25)]
    roomy, want = _serve(model, prompts, 30, decode_k=4)
    tight, got = _serve(model, prompts, 30, decode_k=4, num_pages=10)
    assert roomy.stats["preemptions"] == 0 < tight.stats["preemptions"]
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    assert tight.stats["state_slabs_zeroed"] == 6 * (
        3 + tight.stats["preemptions"])
    assert tight.stats["state_slabs_live"] == 0


def test_served_through_llmserver_with_two_step_programs():
    model = LingHybridForCausalLM(ling_hybrid_tiny(num_experts_held=8))
    model.eval()
    cfg = LLMEngineConfig(num_slots=2, page_size=16, max_model_len=160,
                          token_budget=96, kv_dtype="float32", decode_k=4)
    ids = np.random.default_rng(1).integers(0, 256, (100,)).astype(np.int32)
    with inference.LLMServer(model, cfg) as server:
        out = np.asarray(server.submit(ids, max_new_tokens=12).result(
            timeout=600))
        stats = dict(server.engine.stats)
        compiled = server.engine.compile_stats()
    assert len(out) == 112 and np.array_equal(out[:100], ids)
    assert compiled == {"executables": 1, "fused_executables": 1}
    assert stats["kda_rows_chunked"] == 6 * (96 + 0)   # 96, then 4 rows
    assert stats["kda_chunk_launches"] == 6
    assert stats["kda_rows_recurrent"] == 6 * (111 - 96)
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]


def test_a_tick_program_traces_the_chunk_kernel_once():
    """Six KDA layers call ONE jitted launch: the first layer's g and β
    descend from the token ids alone and would carry another type than
    the later layers' (which descend from the caches too), a second
    trace of the kernel and a second copy of it in the program (PR 36:
    4–5 s of every start)."""
    import functools
    from unittest import mock

    traced = []
    plain = kernels.delta_rule_chunks.__wrapped__

    def launch(*args, chunk):
        traced.append(chunk)
        return plain(*args, chunk=chunk, interpret=True)

    model = LingHybridForCausalLM(ling_hybrid_tiny(num_heads=8))
    model.eval()
    with mock.patch.object(dr, "_pallas_backend_ok", lambda: True), \
            mock.patch.object(kernels, "delta_rule_chunks", jax.jit(
                launch, static_argnames=("chunk",))), \
            mock.patch.object(kernels, "delta_rule_recurrent",
                              functools.partial(delta_rule_recurrent,
                                                interpret=True)):
        eng = _engine(model, token_budget=64, decode_k=1)
        eng.add_request(np.arange(100, dtype=np.int32), max_new_tokens=2)
        eng.step()
    assert traced == [dr.CHUNK]


def test_the_threshold_is_two_chunks():
    """PR 36: without a layout round the kernel the two forms cross near
    4 rows (the table beside the constant), not 35; the constant waits
    for the benchmark's own test of a 32-row tick."""
    assert ling_hybrid._CHUNKED_MIN_ROWS == 2 * dr.CHUNK == 64
    assert dr.CHUNK % dr.SUB_BLOCK == 0
