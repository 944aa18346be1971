"""Laguna — a decoder with routed and shared experts, window and full
attention with their own head counts, rotary positions of two kinds and
a per-head output gate (`model_type: laguna`, poolside/Laguna-S-2.1).

For layer l over x [T, d]:

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

then a final RMSNorm and an UNTIED head. `Attn_l` has
`num_attention_heads_per_layer[l]` query heads over `num_key_value_
heads` K/V heads (grouped queries), rotary by `layer_types[l]` (full:
YaRN on the leading half of a head; sliding: plain rotary on the whole
head, and position p sees p - window + 1 … p), and a gate g =
sigmoid(n·W_g), one scalar a query head, on the attention output before
W_o. `FFN_0` is a dense gated MLP; the others route over ALL
`num_routed_experts` in float32 (softmax, top-k, renormalised, scaled)
and add one ungated shared expert. The equations, their sources and
what is assumed are written out in `benchmarks/references/laguna.py`,
which the tests hold this file to.

SERVING UNDER EXPERT PARALLELISM. `num_experts_held` (≤ `num_routed_
experts`, ids from `first_expert`) is what this chip holds of a layer's
experts: the router keeps its width, a token's assignments to experts
held elsewhere are left out here (`nn/expert_layer.py`), and
`vocab_size` is the rows of embedding and head held here.

The model answers the engine's protocol (`serving_protocol.py`) with
TWO cache kinds, `full` and `window`, both head-major pools. Compute is
raw `jax.numpy` on the parameters' values: the residual stream, RMSNorm,
rotary, routing (from the float32 normed input), softmax and the sum of
expert outputs in float32; every matrix product takes its operands in
the weights' dtype (bf16 as served) and accumulates in float32.

Named scopes: `embed`; `attn` ⊃ `attn_full` | `attn_window` ⊃ `norm`,
`rope`; `mlp` ⊃ `norm`, and in a sparse layer `moe` ⊃ `moe_router`,
`moe_experts`, `moe_shared`; `norm` (final); `lm_head`; `sample`. The
feed-forward of every layer sits under `mlp`, a sparse one's `moe`
inside it, so that a reader who knows only the older words still places
every operation (docs/OBSERVABILITY.md "Spans and scopes").
"""
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...nn import expert_layer
from ...nn.functional import attention as _attention
from ...nn.functional.attention import (paged_attention_gqa,
                                        SlotBlockLayout)
from ...tensor_core import Parameter, Tensor
from .gpt import sample_tokens
from .serving_protocol import CacheKind

__all__ = ["LagunaConfig", "LagunaForCausalLM", "laguna_tiny"]

_scope = jax.named_scope

FULL, SLIDING = "full_attention", "sliding_attention"
_SEEDS = itertools.count(1)      # one key a randomly initialised leaf
# query rows of one slot the paged kernel takes as ONE block in the
# single tick: a prefill chunk's rows share their slot's pages, which
# are then copied once a block (a row a block 990.9 tokens/s, 8 rows
# 1 247.7 in `laguna_s21_decode`: PERF.md §6, PR 28)
_TICK_ROWS_PER_BLOCK = 8


class LagunaConfig:
    """The published config's keys under the program's names. Per-layer
    lists are cut to `num_layers` entries."""

    def __init__(self, vocab_size, hidden_size, num_layers, layer_types,
                 num_heads_per_layer, num_kv_heads, head_dim,
                 mlp_layer_types, intermediate_size, moe_intermediate_size,
                 shared_expert_intermediate_size, num_routed_experts,
                 num_experts_per_tok, rope_parameters, sliding_window,
                 num_experts_held=None, first_expert=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 rms_norm_eps=1e-6, max_seq_len=8192, dtype="bfloat16",
                 init_weights=True):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.layer_types = list(layer_types)[:self.num_layers]
        self.num_heads_per_layer = [
            int(h) for h in num_heads_per_layer][:self.num_layers]
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.mlp_layer_types = list(mlp_layer_types)[:self.num_layers]
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.shared_expert_intermediate_size = int(
            shared_expert_intermediate_size)
        self.num_routed_experts = int(num_routed_experts)
        self.num_experts_held = int(
            num_routed_experts if num_experts_held is None
            else num_experts_held)
        self.first_expert = int(first_expert)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rope_parameters = rope_parameters
        self.sliding_window = int(sliding_window)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_seq_len)
        self.dtype = str(dtype)
        # False: parameters are shapes only (a checkpoint or a
        # benchmark's seed fills them; 4.7 B random values made twice
        # would not fit a chip)
        self.init_weights = bool(init_weights)
        if not (len(self.layer_types) == len(self.num_heads_per_layer)
                == len(self.mlp_layer_types) == self.num_layers):
            raise ValueError("per-layer lists shorter than num_layers")
        for h in self.num_heads_per_layer:
            if h % self.num_kv_heads:
                raise ValueError(
                    f"{h} query heads do not group over "
                    f"{self.num_kv_heads} KV heads")
        if self.first_expert + self.num_experts_held \
                > self.num_routed_experts:
            raise ValueError("held experts run past the router's width")

    def cache_kinds(self):
        """Two kinds, both head-major: `full` layers keep every page,
        `window` layers only what position p - window + 1 … p needs."""
        full = tuple(i for i, t in enumerate(self.layer_types)
                     if t != SLIDING)
        win = tuple(i for i, t in enumerate(self.layer_types)
                    if t == SLIDING)
        kinds = []
        if full:
            kinds.append(CacheKind("full", full, self.num_kv_heads,
                                   self.head_dim, None, True))
        if win:
            kinds.append(CacheKind("window", win, self.num_kv_heads,
                                   self.head_dim, self.sliding_window,
                                   True))
        return kinds


def laguna_tiny(**kw):
    """A CPU-test preset: a leading dense layer and two periods, window
    shorter than a test sequence, head counts differing by layer type,
    16 routed experts top-4 (all held unless told otherwise)."""
    types = [FULL] + [SLIDING, SLIDING, SLIDING, FULL] * 2
    args = dict(
        vocab_size=256, hidden_size=64, num_layers=9, layer_types=types,
        num_heads_per_layer=[4 if t == FULL else 6 for t in types],
        num_kv_heads=2, head_dim=16,
        mlp_layer_types=["dense"] + ["sparse"] * 8, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        num_routed_experts=16, num_experts_per_tok=4,
        rope_parameters={
            FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                   "original_max_position_embeddings": 8192,
                   "beta_slow": 1, "beta_fast": 32,
                   "attention_factor": 1.4852030263919618,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}},
        sliding_window=16, routed_scaling_factor=2.5, max_seq_len=256,
        dtype="float32")
    args.update(kw)
    return LagunaConfig(**args)


# -------------------------------------------------------------- rotary

def rope_inv_frequencies(rope, rotary_dim):
    """([rotary_dim / 2] inverse frequencies, the factor on cos and
    sin) of one `rope_parameters` entry: `default`, or `yarn` as
    `transformers` computes it."""
    base = float(rope["rope_theta"])
    freqs = base ** (np.arange(0, rotary_dim, 2, dtype=np.float64)
                     / rotary_dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / freqs, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rotary_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))),
               rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)
    return inv, float(rope["attention_factor"])


def _rope_tables(config, kind, pos):
    """(cos, sin [T, rotary_dim] float32, rotary_dim) at positions
    `pos` [T] for a layer of `kind`."""
    rope = config.rope_parameters[kind]
    rd = int(round(config.head_dim
                   * float(rope.get("partial_rotary_factor", 1))))
    inv, factor = rope_inv_frequencies(rope, rd)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        inv.astype(np.float32))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor, rd


def _apply_rope(x, tables):
    """x [T, H, D] float32: the leading rotary_dim dimensions of every
    head rotated (rotate-half), the rest passed through."""
    cos, sin, rd = tables
    xr = x[..., :rd]
    x1, x2 = xr[..., :rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    xr = xr * cos[:, None, :] + rot * sin[:, None, :]
    if rd == x.shape[-1]:
        return xr
    return jnp.concatenate([xr, x[..., rd:]], axis=-1)


# -------------------------------------------------------------- pieces

def _rms_norm(x, w, eps):
    """RMSNorm of the float32 residual stream, in float32."""
    with _scope("norm"):
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y * w.astype(jnp.float32)


def _mm(x, w):
    """x [..., k] @ w [k, n]: operands in the weights' dtype, the
    product accumulated and returned in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _gated_mlp(x, w_gate_up, w_down):
    h = _mm(x, w_gate_up)
    m = h.shape[-1] // 2
    return _mm(jax.nn.silu(h[..., :m]) * h[..., m:], w_down)


def _pool_write(pool, new, write_idx):
    """Scatter rows `new` [T, KV, D] into a head-major pool [N, KV, P,
    D] at flat rows `write_idx` (page · P + offset; row 0 of page 0 is
    the trash row)."""
    n, kv, page_size, d = pool.shape
    idx = write_idx.astype(jnp.int32)
    # as rows of ONE [N·KV·P, D] matrix (a bitcast of the pool): a
    # scatter along the major dimension alone leaves the pool's layout
    # to the kernel that reads it; with (page, offset) as two scattered
    # dimensions XLA re-laid the pool out page-major inside the fused
    # window's loop and copied every pool in and out of it
    rows = ((idx // page_size)[:, None] * kv
            + jnp.arange(kv, dtype=jnp.int32)[None, :]) * page_size \
        + (idx % page_size)[:, None]                       # [T, KV]
    flat = pool.reshape(n * kv * page_size, d).at[rows.reshape(-1)].set(
        new.astype(pool.dtype).reshape(-1, d))
    return flat.reshape(pool.shape)


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.index = index
        self.kind = c.layer_types[index]
        self.heads = c.num_heads_per_layer[index]
        self.sparse = c.mlp_layer_types[index] != "dense"
        d, hd, kv, L = c.hidden_size, c.head_dim, c.num_kv_heads, \
            c.num_layers
        res = 0.02 / math.sqrt(2 * L)
        mk = lambda shape, std=0.02, one=False: _parameter(  # noqa: E731
            c, shape, std, one)
        self.attn_norm = mk((d,), one=True)
        self.wqkv = mk((d, (self.heads + 2 * kv) * hd))
        self.wg = mk((d, self.heads))
        self.wo = mk((self.heads * hd, d), res)
        self.ffn_norm = mk((d,), one=True)
        if not self.sparse:
            self.w_gate_up = mk((d, 2 * c.intermediate_size))
            self.w_down = mk((c.intermediate_size, d), res)
        else:
            E, m = c.num_experts_held, c.moe_intermediate_size
            ms = c.shared_expert_intermediate_size
            self.router = mk((d, c.num_routed_experts))
            self.experts_gate_up = mk((E, d, 2 * m))
            self.experts_down = mk((E, m, d), res)
            self.shared_gate_up = mk((d, 2 * ms))
            self.shared_down = mk((ms, d), res)


def _parameter(config, shape, std, one):
    dt = jnp.dtype(config.dtype)
    if not config.init_weights:
        p = Parameter(jnp.zeros((), dt))
        p._value = jax.ShapeDtypeStruct(tuple(shape), dt)
        return p
    x = std * jax.random.normal(jax.random.PRNGKey(next(_SEEDS)), shape,
                                jnp.float32)
    return Parameter(((1.0 + x) if one else x).astype(dt))


class LagunaForCausalLM(nn.Layer):
    """The served model: eager `forward`, and the engine's two step
    bodies over paged pools."""

    # int32 counters the step bodies return, summed over sparse layers
    # (nn/expert_layer.held_experts_ffn)
    step_counters = ("moe_assignments", "moe_assignments_held",
                     "moe_experts_touched")

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        self.embed = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                False)
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(c, i) for i in range(c.num_layers)])
        self.final_norm = _parameter(c, (c.hidden_size,), 0.02, True)
        self.lm_head = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                  False)

    def compute_dtype(self):
        return jnp.dtype(self.config.dtype)

    # ---- shared arithmetic ------------------------------------------

    def _qkvg(self, layer, x, tables):
        """The normed input's projections: q [T, H, D] and k [T, KV, D]
        rotated, v [T, KV, D], gate [T, H] float32."""
        c = self.config
        T = x.shape[0]
        hd, kv, H = c.head_dim, c.num_kv_heads, layer.heads
        dt = layer.wqkv._value.dtype
        n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
        qkv = _mm(n, layer.wqkv._value)
        q = qkv[:, :H * hd].reshape(T, H, hd)
        k = qkv[:, H * hd:(H + kv) * hd].reshape(T, kv, hd)
        v = qkv[:, (H + kv) * hd:].reshape(T, kv, hd)
        with _scope("rope"):
            q, k = _apply_rope(q, tables), _apply_rope(k, tables)
        gate = jax.nn.sigmoid(_mm(n, layer.wg._value))
        return q.astype(dt), k.astype(dt), v.astype(dt), gate

    def _attn_out(self, layer, x, o, gate):
        o = o.astype(jnp.float32) * gate[:, :, None]
        return x + _mm(o.reshape(x.shape[0], -1), layer.wo._value)

    def _ffn(self, layer, x, valid):
        """x + FFN_l(RMSNorm(x)); returns (y, counters int32 [3])."""
        c = self.config
        with _scope("mlp"):
            n = _rms_norm(x, layer.ffn_norm._value, c.rms_norm_eps)
            if not layer.sparse:
                return (x + _gated_mlp(n, layer.w_gate_up._value,
                                       layer.w_down._value),
                        jnp.zeros((3,), jnp.int32))
            with _scope("moe"):
                # the router reads the float32 normed input: a choice
                # between two near-equal experts should not turn on the
                # rounding of its input to 8 bits of mantissa
                w, ids = expert_layer.route_top_k(
                    n, layer.router._value, c.num_experts_per_tok,
                    c.norm_topk_prob)
                routed, counters = expert_layer.held_experts_ffn(
                    n.astype(layer.experts_down._value.dtype), w, ids,
                    valid, layer.experts_gate_up._value,
                    layer.experts_down._value, c.first_expert)
                with _scope("moe_shared"):
                    shared = _gated_mlp(n, layer.shared_gate_up._value,
                                        layer.shared_down._value)
                return (x + shared + c.routed_scaling_factor * routed,
                        counters)

    def _head(self, x):
        c = self.config
        x = _rms_norm(x, self.final_norm._value, c.rms_norm_eps)
        with _scope("lm_head"):
            return _mm(x, self.lm_head._value.T)

    # ---- eager forward (no cache) -----------------------------------

    def forward(self, input_ids):
        """Logits [b, s, vocab] (float32) of token ids [b, s]: dense
        causal attention a sequence at a time, no cache."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        return Tensor(jnp.stack([self._forward_one(r) for r in ids]),
                      stop_gradient=True)

    def _forward_one(self, ids):
        c = self.config
        S = ids.shape[0]
        pos = jnp.arange(S)
        tables = {k: _rope_tables(c, k, pos) for k in set(c.layer_types)}
        valid = jnp.ones((S,), bool)
        with _scope("embed"):
            x = self.embed._value[ids].astype(jnp.float32)
        for layer in self.layers:
            word = "attn_window" if layer.kind == SLIDING else "attn_full"
            with _scope("attn"), _scope(word):
                q, k, v, gate = self._qkvg(layer, x, tables[layer.kind])
                g = layer.heads // c.num_kv_heads
                sc = jnp.einsum(
                    "qhgd,khd->hgqk",
                    q.reshape(S, c.num_kv_heads, g, c.head_dim), k,
                    preferred_element_type=jnp.float32) \
                    / math.sqrt(c.head_dim)
                see = pos[None, :] <= pos[:, None]
                if layer.kind == SLIDING:
                    see = see & (pos[None, :]
                                 > pos[:, None] - c.sliding_window)
                p = jax.nn.softmax(jnp.where(see, sc, -jnp.inf), -1)
                o = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                               preferred_element_type=jnp.float32)
                x = self._attn_out(
                    layer, x, o.reshape(S, layer.heads, c.head_dim), gate)
            x, _ = self._ffn(layer, x, valid)
        return self._head(x)

    # ---- the engine's step bodies -----------------------------------

    def _paged_core(self, tok, pos, slot_ids, write_idx, page_tables,
                    kv_lens, sample_idx, kv, frontier_offset=None,
                    slot_blocks=False):
        """Raw arrays. write_idx [kinds, T], page_tables [kinds, S, MP]
        in the order of `config.cache_kinds()`; kv the flat list k0, v0,
        k1, v1 … in layer order. `slot_blocks`: the single tick's rows
        (a slot's side by side), which the kernel takes in blocks of
        `_TICK_ROWS_PER_BLOCK` of one slot; the fused window has one row
        a slot. Returns (logits [S, vocab] float32, new kv, counters
        int32 [3])."""
        c = self.config
        kinds = c.cache_kinds()
        kind_of = {i: n for n, kd in enumerate(kinds) for i in kd.layers}
        valid = kv_lens > 0
        tables = {k: _rope_tables(c, k, pos) for k in set(c.layer_types)}
        layout = None
        if slot_blocks and _attention._pallas_backend_ok():
            with _scope("attn"):
                layout = SlotBlockLayout(slot_ids, kv_lens,
                                         _TICK_ROWS_PER_BLOCK,
                                         page_tables.shape[1])
        with _scope("embed"):
            x = self.embed._value[tok].astype(jnp.float32)
        new_kv = []
        counters = jnp.zeros((3,), jnp.int32)
        for i, layer in enumerate(self.layers):
            n = kind_of[i]
            window = kinds[n].window
            word = "attn_full" if window is None else "attn_window"
            with _scope("attn"), _scope(word):
                q, k, v, gate = self._qkvg(layer, x, tables[layer.kind])
                ck = _pool_write(kv[2 * i], k, write_idx[n])
                cv = _pool_write(kv[2 * i + 1], v, write_idx[n])
                starts = None if window is None else kv_lens - window
                o = paged_attention_gqa(
                    q, ck, cv, page_tables[n], slot_ids, kv_lens, starts,
                    frontier_offset, layout)
                x = self._attn_out(layer, x, o, gate)
            new_kv += [ck, cv]
            x, cnt = self._ffn(layer, x, valid)
            counters = counters + cnt
        with _scope("lm_head"):
            x = x[sample_idx]
        return self._head(x), new_kv, counters

    def _paged_decode_core(self, tok, pos_ids, slot_ids, write_idx,
                           page_tables, kv_lens, sample_idx, kv,
                           kv_scales=None, frontier_offset=None,
                           max_q_per_slot=None):
        """The single tick (serving_protocol.py): Tensors in and out;
        returns (logits [1, S, vocab], *new pools, counters)."""
        if kv_scales:
            raise ValueError("LagunaForCausalLM serves float KV pools")
        val = lambda t: None if t is None else t._value   # noqa: E731
        logits, new_kv, counters = self._paged_core(
            val(tok), val(pos_ids), val(slot_ids), val(write_idx),
            val(page_tables), val(kv_lens), val(sample_idx),
            [val(p) for p in kv], val(frontier_offset),
            slot_blocks=True)
        t = lambda v: Tensor(v, stop_gradient=True)       # noqa: E731
        return (t(logits[None]), *[t(p) for p in new_kv], t(counters))

    def _paged_decode_fused(self, k, page_size, tok0, pos0, rem, fin0,
                            eos_ids, temps, top_ps, streams, page_tables,
                            kv, kv_scales, key, lag=None, frontier=None,
                            gstate0=None, gtrans=None, gmask=None):
        """`k` decode iterations in one scan, sampling inside (the
        contract of `GPTGenerationMixin._paged_decode_fused`, without
        its speculative and grammar arguments). Returns (emits [k, S],
        new kv, [], counters [k, 3])."""
        if lag is not None or gtrans is not None or kv_scales:
            raise ValueError(
                "LagunaForCausalLM's fused window takes no draft lag, "
                "grammar tables or quantized pools")
        S = tok0.shape[0]
        sl = jnp.arange(S, dtype=jnp.int32)
        pt = jnp.asarray(page_tables, jnp.int32)      # [kinds, S, MP]
        klen0 = pos0 + 1
        pad = jnp.asarray(-1, jnp.int32)

        def body(carry, i):
            tok, fin, kv_c = carry
            live = ~fin
            tok_in = jnp.where(live, tok, 0)
            pos_in = jnp.where(live, pos0 + i, 0)
            klen = jnp.where(live, klen0, 0)   # + i rides the offset
            page = pt[:, sl, pos_in // page_size]          # [kinds, S]
            widx = jnp.where(live[None],
                             page * page_size + pos_in % page_size, 0)
            logits, kv2, cnt = self._paged_core(
                tok_in, pos_in, sl, widx, pt, klen, sl, kv_c,
                frontier_offset=i)
            with _scope("sample"):
                nxt = sample_tokens(logits, temps, top_ps, streams,
                                    pos_in + 1, key)
                emit = jnp.where(live, nxt, pad)
                fin2 = (fin | (live & (eos_ids >= 0) & (nxt == eos_ids))
                        | (live & (i + 1 >= rem)))
                tok2 = jnp.where(live, nxt, tok)
            return (tok2, fin2, kv2), (emit, cnt)

        (_, _, kv_f), (emits, counters) = jax.lax.scan(
            body, (tok0, fin0, list(kv)),
            jnp.arange(int(k), dtype=jnp.int32))
        return emits, kv_f, [], counters
