"""Gated DeltaNet beside full attention on the serving path, on the CPU at
small sizes: the delta rule with a decay a HEAD in both forms (plain and
Pallas, interpreted) against the recurrence written out a token at a
time, at the published head count and widths and at gates KDA's form
cannot take; the cache kinds; and `olmo_hybrid.py` served through
`LLMEngine` against the plain reference (`benchmarks/references/
olmo_hybrid.py`): prefill in chunks, then decode in the tick and the
fused window, slots reused, both forms of the recurrence."""
import functools
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.nn.functional import delta_rule as dr
from paddle_tpu.nn.functional.attention import SlotRunLayout
from paddle_tpu.ops.pallas_kernels import delta_rule as kernels
from paddle_tpu.text.models import olmo_hybrid
from paddle_tpu.text.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                                olmo_hybrid_tiny)

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

from builders import olmo_hybrid as builder  # noqa: E402
from references import olmo_hybrid as ref  # noqa: E402


def _by_token(state, q, k, v, g, beta):
    """The recurrence as written, float64 numpy, a token at a time, a
    decay a head: g [T, H]."""
    S = np.asarray(state, np.float64).copy()
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    out = np.zeros(v.shape)
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            Sd = S[h] * np.exp(g[t, h])
            u = beta[t, h] * (v[t, h] - Sd.T @ k[t, h])
            S[h] = Sd + np.outer(k[t, h], u)
            out[t, h] = S[h].T @ q[t, h]
    return out, S


def _rows(T, H, dk, dv, slots=3, seed=0, floor=-30.0):
    """State [slots, H, d_k, d_v] and rows: unit keys, gates a head down
    to `floor` a token, β over (0, 2) with a third of it above 1.9."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    k = f(T, H, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = floor * jax.nn.sigmoid(2.0 * f(T, H) - 1.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * f(T, H) + 2.0)
    return (f(slots, H, dk, dv), f(T, H, dk) * dk ** -0.5, k, f(T, H, dv),
            g, beta)


def _tick(runs_of, T):
    """sids, lens [T] of a tick whose runs are (slot, first flat row,
    rows, first position); every other row dead."""
    sids = np.zeros((T,), np.int32)
    lens = np.zeros((T,), np.int32)
    for slot, at, n, pos0 in runs_of:
        sids[at:at + n], lens[at:at + n] = slot, pos0 + 1 + np.arange(n)
    return SlotRunLayout(jnp.asarray(sids), jnp.asarray(lens), 16, dr.CHUNK,
                         0)


def _interpreted():
    """Both kernels of a decay a head in interpret mode."""
    return mock.patch.multiple(
        kernels, delta_rule_head_chunks=functools.partial(
            kernels.delta_rule_head_chunks, interpret=True),
        delta_rule_recurrent=functools.partial(
            kernels.delta_rule_recurrent, interpret=True))


# ---- the delta rule with a decay a head --------------------------------

RUNS = ((1, 2, 70, 5), (2, 75, 20, 0))


@pytest.mark.parametrize("H,dk,dv", [
    pytest.param(30, 96, 192, id="published_30_heads_96x192"),
    pytest.param(3, 16, 32, id="3_heads_not_a_tile_of_8"),
])
def test_both_forms_agree_with_the_token_scan(H, dk, dv):
    """Two runs (one from a stored state, one from position 0) of a
    96-row tick, gates down to −30 a token, β up to 2: the plain chunked
    form, the chunk kernel and the recurrent kernel (interpreted) against
    the recurrence in float64. 1e-4 of outputs and states whose entries
    reach ≈ 5 (read: 3.1e-5 at the published widths): float32 products
    of up to 96 terms in three orders, and β near 2 carries a token's
    rounding on with a factor near −1 where a KDA β damps it."""
    state0, q, k, v, g, beta = _rows(96, H, dk, dv)
    runs = _tick(RUNS, 96)
    plain = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                  kernel=False)
    with _interpreted():
        got = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                    kernel=True)
    assert int(got[2]) == int(plain[2]) == 3 + 1
    for slot, at, n, pos0 in RUNS:
        start = np.zeros_like(state0[slot]) if pos0 == 0 else state0[slot]
        want_o, want_s = _by_token(start, *(a[at:at + n] for a in (
            q, k, v, g, beta)))
        for o, st, _ in (plain, got):
            np.testing.assert_allclose(np.asarray(o[at:at + n]), want_o,
                                       atol=1e-4)
            np.testing.assert_allclose(np.asarray(st[slot]), want_s,
                                       atol=1e-4)
    off = np.ones((96,), bool)
    for _, at, n, _ in RUNS:
        off[at:at + n] = False
    assert not np.asarray(got[0])[off].any()
    # slot 0 held no run: its state passes through bit for bit
    assert np.array_equal(np.asarray(got[1][0]), np.asarray(state0[0]))
    # the recurrent step, a row a slot: slot 0 continues, slot 1 starts
    live = jnp.asarray([True, True, False])
    fresh = jnp.asarray([False, True, False])
    rows = [a[:3] for a in (q, k, v, g, beta)]
    with _interpreted():
        o, st = dr.delta_rule_step(state0, *rows, live, fresh, kernel=True)
    for slot in (0, 1):
        start = np.zeros_like(state0[slot]) if slot else state0[slot]
        want_o, want_s = _by_token(start, *(a[slot:slot + 1] for a in rows))
        np.testing.assert_allclose(np.asarray(o[slot]), want_o[0],
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(st[slot]), want_s, atol=1e-4)
    assert np.array_equal(np.asarray(st[2]), np.asarray(state0[2]))


def test_a_decay_a_channel_cannot_stand_in_for_a_decay_a_head():
    """The same gates broadcast over d_k through KDA's chunked form: its
    sub-block references hold a factor inside float32 only for g ≥ −5 a
    token, and its clamp at e^80 cuts what a gate of −30 needs; the form
    with a decay a head holds them (1e-4 of outputs of scale 1, read
    1.6e-5: float32 sums of 32 terms, β near 2)."""
    state0, q, k, v, g, beta = _rows(64, 2, 16, 16, floor=-30.0)
    runs = _tick(((1, 0, 64, 0),), 64)
    want_o, _ = _by_token(np.zeros_like(state0[1]), q, k, v, g, beta)
    head, _, _ = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                       kernel=False)
    channel, _, _ = dr.delta_rule_chunked(
        state0, q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta,
        runs, kernel=False)
    np.testing.assert_allclose(np.asarray(head), want_o, atol=1e-4)
    assert not np.allclose(np.asarray(channel), want_o, atol=1e-2)


def test_the_head_chunk_kernel_takes_any_head_count_in_groups():
    """Five heads at a chunk of 32: one group of 128 / 32 = 4 heads as
    ONE block-diagonal operand, then a group of one; against the plain
    form, head by head (1e-4 of outputs up to ≈ 5, read 6.2e-5: the
    inverse by doubling against a triangular solve, β near 2)."""
    state0, q, k, v, g, beta = _rows(64, 5, 16, 32, seed=3)
    runs = _tick(((0, 3, 41, 6), (2, 44, 20, 0)), 64)
    want = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                 kernel=False)
    with _interpreted():
        got = dr.delta_rule_chunked(state0, q, k, v, g, beta, runs,
                                    kernel=True)
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_the_head_chunk_kernel_says_what_it_cannot_take():
    state0, q, k, v, g, beta = _rows(64, 2, 16, 16)
    laid = [x.reshape((2, 32) + x.shape[1:]).swapaxes(1, 2)
            for x in (q, k, v, g, beta)]
    table = [jnp.zeros((2,), jnp.int32)] * 3 + [jnp.zeros((1,), jnp.int32)]
    with pytest.raises(ValueError, match="power of"):
        kernels.delta_rule_head_chunks(
            state0, *[x[:, :, :24] for x in laid], *table, interpret=True)


# ---- the cache kinds ---------------------------------------------------

def test_full_pages_beside_a_gdn_slab_of_whole_lane_tiles():
    c = olmo_hybrid_tiny()
    full, state = c.cache_kinds()
    assert full.layers == (3,) and full.head_major and not full.state
    assert (full.kv_heads, full.head_dim) == (4, 16)
    assert state.layers == (0, 1, 2) and state.state
    # the value width is stored in whole lane tiles: 32 → 128
    assert state.slab_arrays(2, "bfloat16") == [
        ((2, 3, 16, 128), "float32"), ((2, 3, 3 * (16 + 16 + 32)),
                                       "bfloat16")]
    pub = builder.model_config(_published())
    assert pub.cache_kinds()[1].slab[0] == ((30, 96, 256), "float32")
    with pytest.raises(ValueError, match="full attention"):
        olmo_hybrid_tiny(layer_types=["linear_attention"] * 4)


def _published():
    import json

    with open(os.path.join(_BENCH, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)


# ---- served against the reference -------------------------------------

def _small(**rehearse):
    """The configuration's `rehearse` sizes in float32: one period L L L
    F, 3 GDN heads of 16 × 32, 4 full heads of 16, vocabulary 256."""
    from run import overlay

    cfg = _published()
    cfg = overlay(cfg, overlay(cfg["rehearse"], rehearse))
    cfg["serve"]["weight_dtype"] = "float32"
    cfg["engine"]["max_model_len"] = 256
    return cfg


def _wide_gates(tree):
    """Gates that reach −30 a token and β that reaches 2: the decay's
    rate A = 16 in every head and W_a, W_b of a unit-scale projection."""
    out = dict(tree, layers=[dict(lw) for lw in tree["layers"]])
    for lw in out["layers"]:
        if "w_a" in lw:
            lw["a_log"] = jnp.full_like(lw["a_log"], np.log(16.0))
            lw["w_a"] = lw["w_a"] * 20.0
            lw["w_b"] = lw["w_b"] * 12.0
    return out


def _model(cfg, tree):
    model = OlmoHybridForCausalLM(builder.model_config(cfg,
                                                       init_weights=True))
    flat = builder.program_tree(tree, ref.dims(cfg))
    for name, p in model.state_dict().items():
        p._value = flat[name]
    model.eval()
    return model


def _serve(model, prompts, new, **kw):
    args = dict(num_slots=3, page_size=16, max_model_len=256,
                token_budget=16, kv_dtype="float32")
    args.update(kw)
    eng = LLMEngine(model, LLMEngineConfig(**args))
    reqs = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
    return eng, [np.asarray(r.future.result()).astype(np.int32)
                 for r in reqs]


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (n,)).astype(np.int32)
            for n in (150, 97, 5, 70, 130, 1)]


def test_the_eager_forward_is_the_references():
    """Seeded weights with gates down to −30 and β up to 2: the eager
    forward (a GDN layer as ONE chunked run from zero, full attention
    dense) against the reference's token-by-token recurrence: 2e-5 of
    logits up to ≈ 1 (float32 sums in another order, four layers)."""
    cfg = _small()
    tree = _wide_gates(ref.make_weights(cfg, 7, "float32"))
    ids = _prompts()[0]
    s = ref.dims(cfg)
    x = tree["embed"][ids].astype(jnp.float32)
    g, beta = ref.gdn_gates(s, x, tree["layers"][0], None)
    assert float(g.min()) < -30.0 and float(beta.max()) > 1.95
    want = np.asarray(ref.logits_fn(cfg, tree, jnp.asarray(ids)))
    got = np.asarray(_model(cfg, tree)(ids[None])._value)[0]
    assert got.shape == want.shape == (150, 256)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("budget,decode_k,chunked", [
    pytest.param(80, 4, True, id="chunked_runs_split_inside_a_chunk"),
    pytest.param(200, 1, True, id="whole_prompts_a_tick_no_window"),
    pytest.param(16, 4, False, id="every_run_recurrent"),
])
def test_served_tokens_are_the_references_best(budget, decode_k, chunked):
    """Six prompts through three slots (a slot taken again after a
    finished request), prefilled in chunks of the token budget, then
    decoded in the tick and the fused window: every served token is the
    reference's argmax over its full forward at that position (its
    logit gap 0) where the reference decides by more than float32's
    reach (a margin over 1e-4: the program's logits agree with the
    reference's to 2e-5)."""
    cfg = _small()
    tree = _wide_gates(ref.make_weights(cfg, 11, "float32"))
    prompts = _prompts()
    eng, outs = _serve(_model(cfg, tree), prompts, 10, token_budget=budget,
                       decode_k=decode_k)
    decided = 0
    for p, toks in zip(prompts, outs):
        assert len(toks) == len(p) + 10 and np.array_equal(
            toks[:len(p)], p)
        gap, margin = ref.served_token_gaps(cfg, tree, toks, len(p), 256,
                                            128)
        sure = margin > 1e-4
        assert float(gap[sure].max()) == 0.0
        decided += int(sure.sum())
    assert decided >= 55
    st = eng.stats
    rows = sum(len(o) - 1 for o in outs)
    assert st["gdn_rows_recurrent"] + st["gdn_rows_chunked"] == 3 * rows
    assert (st["gdn_rows_chunked"] > 0) == chunked
    assert (st["gdn_chunk_launches"] > 0) == chunked
    assert st["state_slabs_zeroed"] == 6 * 3      # requests × GDN layers
    assert st["state_slabs_live"] == 0 == eng.pool.num_live
    assert st["kv_positions_least_full"] > 0


def test_the_gdn_kernels_interpreted_serve_the_same_tokens():
    """The delta rule's two Pallas kernels (interpreted) in the step
    programs in place of the plain forms: the same tokens as the plain
    forms, a chunked run in the tick and the window's rows recurrent."""
    cfg = _small()
    tree = _wide_gates(ref.make_weights(cfg, 5, "float32"))
    model = _model(cfg, tree)
    prompts = _prompts(2)[:3]
    _, plain = _serve(model, prompts, 6, token_budget=80, decode_k=4)
    with _interpreted(), mock.patch.object(dr, "_pallas_backend_ok",
                                           lambda: True):
        eng, got = _serve(model, prompts, 6, token_budget=80, decode_k=4)
    for a, b in zip(plain, got):
        assert np.array_equal(a, b)
    assert eng.stats["gdn_rows_chunked"] > 0 < eng.stats[
        "gdn_rows_recurrent"]


def test_a_preempted_request_replays_its_state_from_position_0():
    model = OlmoHybridForCausalLM(olmo_hybrid_tiny())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32)
               for n in (40, 33, 25)]
    roomy, want = _serve(model, prompts, 30, decode_k=4)
    tight, got = _serve(model, prompts, 30, decode_k=4, num_pages=10)
    assert roomy.stats["preemptions"] == 0 < tight.stats["preemptions"]
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    assert tight.stats["state_slabs_zeroed"] == 3 * (
        3 + tight.stats["preemptions"])


def test_a_tick_without_a_long_run_has_no_chunked_form():
    """Under two chunks of one slot's rows the tick takes no chunked
    form (`ling_hybrid._CHUNKED_MIN_ROWS`, shared)."""
    from paddle_tpu.text.models import ling_hybrid

    assert olmo_hybrid._SlotRuns is ling_hybrid._SlotRuns
    eng, _ = _serve(OlmoHybridForCausalLM(olmo_hybrid_tiny()),
                    [np.arange(60, dtype=np.int32)], 2, token_budget=64,
                    decode_k=1)
    assert eng.stats["gdn_rows_chunked"] == 0
