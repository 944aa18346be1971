"""Sarvam-MLA — a decoder with multi-head LATENT attention, sigmoid-routed
experts with a selection bias, one shared expert and leading dense
layers (`model_type: sarvam_mla`, sarvamai/sarvam-105b).

For layer l over x [T, d]:

    h = x + Attn(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

then a final RMSNorm and an UNTIED head. With n the normed input:

    q = n·W_q → [H, nope + rope], an RMSNorm over each head's query,
        its last `rope` dims rotated
    [c_kv | k_r] = n·W_kv_a;  c = RMSNorm(c_kv) [latent];  k_r =
        RoPE(RMSNorm(k_r)) [rope], ONE for all heads
    [k_nope_h | v_h] = c·W_kv_b[h]
    s_h = (q_nope_h·k_nope_h + q_rope_h·k_r) · scale, causal softmax in
        float32, o_h = Σ p v_h, y = concat(o_h)·W_o

What a token leaves in the cache is the ROW `[c | k_r]` (latent + rope
wide, normed and rotated), not keys and values a head: one cache kind,
`latent`, ONE pool a layer with no head axis (`serving_protocol.
CacheKind.row_dim`). Two forms of the same numbers:

    EXPANDED  as written: k_nope and v up-projected from the latent of
              every attended token. What `forward` (no cache) computes.
    ABSORBED  W_kv_b folded into the query and the output: q̃_h =
              [q_nope_h·W_UK[h]ᵀ | q_rope_h], s_h = q̃_h·row, õ_h = Σ p
              row[:latent], o_h = õ_h·W_UV[h]. What every row that
              attends PAGES takes (`nn.functional.attention.
              paged_attention_latent`): the walk reads a row once for
              all heads and nothing is expanded.

`W_kv_b` is held as its two halves in the layout each product reads,
`w_uk` [H, nope, latent] and `w_uv` [H, latent, v], so that neither is
re-laid out an iteration. Rotary is YaRN (`deepseek_yarn`) on the rope
dims, interleaved pairs re-ordered to halves first as the family does.

`FFN_l` is a dense gated MLP in the first `first_k_dense` layers; the
others route over ALL `num_routed_experts` in float32: σ = sigmoid(n·
W_r), the top-k of σ + bias (the bias selects only), weights σ_sel /
Σ σ_sel · routed_scaling_factor, plus the shared expert with weight 1.
`num_experts_held` / `first_expert` / `vocab_size` are this chip's share
under expert and vocabulary parallelism, as `laguna.py` has them.

The equations, their sources and what is assumed are written out in
`benchmarks/references/sarvam_mla.py`, which the tests hold this file
to. Compute is raw `jax.numpy`: residual stream, norms, rotary, routing
and softmax in float32; every matrix product takes its operands in the
weights' dtype and accumulates in float32.

Named scopes: `embed`; `attn` ⊃ `attn_mla` ⊃ `mla_q`, `mla_latent_write`,
`mla_walk`, `mla_expand`, `mla_out` (⊃ `norm`, `rope`); `mlp` ⊃ `norm`,
and in a sparse layer `moe` ⊃ `moe_router`, `moe_experts`, `moe_shared`;
`norm` (final); `lm_head`; `sample` (docs/OBSERVABILITY.md "Spans and
scopes").
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from ... import nn
from ...nn import expert_layer
from ...nn.functional import attention as _attention
from ...nn.functional.attention import (latent_run_layout,
                                        paged_attention_latent,
                                        paged_attention_latent_expanded,
                                        SlotBlockLayout)
from ...tensor_core import Tensor
from .gpt import sample_tokens
from .laguna import (_gated_mlp, _mm, _parameter, _rms_norm,
                     rope_inv_frequencies)
from .serving_protocol import CacheKind

__all__ = ["SarvamMLAConfig", "SarvamMLAForCausalLM", "sarvam_mla_tiny"]

_scope = jax.named_scope

# query rows of one slot the latent walk takes as ONE block in the
# single tick: a prefill chunk's rows share their slot's pages, and with
# 64 heads a block of 16 rows is a 1 024-row matrix product (a tick's
# launch at 8 / 16 / 32 rows a block: 12.30 / 11.25 / 10.91 ms from 8 k of
# context, 20.19 / 18.10 / 17.26 ms from 15 k, 3.56 / 3.55 / 3.70 ms from 0,
# on a v5e: PERF.md §6, PR 33, step 0 (b))
_TICK_ROWS_PER_BLOCK = 16

# a run of one slot's rows in the single tick takes the EXPANDED form
# from this many rows on: the module docstring's two forms cost the same
# FLOPs at 171 rows of 64 heads, and on a v5e from 8 k of context the
# expanded kernel, whose cost up to a sub-block of 512 rows is that of
# 512, takes 2.93 / 2.93 / 2.95 ms at 128 / 256 / 512 rows where the
# absorbed walk takes 1.20 / 2.35 / 4.63 (PERF.md §6, PR 34, step 0). A
# tick of fewer rows holds no expanded launch at all. Also how many of
# the rows LEFT to the absorbed form one launch of it serves (gathered,
# a chunk of as many rows at a time, beside the expanded runs).
_EXPANDED_MIN_ROWS = 512


class SarvamMLAConfig:
    """The published config's keys under the program's names."""

    def __init__(self, vocab_size, hidden_size, num_layers, num_heads,
                 kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                 v_head_dim, intermediate_size, moe_intermediate_size,
                 num_routed_experts, num_experts_per_tok,
                 num_shared_experts=1, first_k_dense=1,
                 num_experts_held=None, first_expert=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 rope_theta=10000.0, rope_scaling=None, rms_norm_eps=1e-6,
                 max_seq_len=8192, dtype="bfloat16", init_weights=True,
                 n_group=None, topk_group=None):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_shared_experts = int(num_shared_experts)
        self.first_k_dense = int(first_k_dense)
        self.num_routed_experts = int(num_routed_experts)
        self.num_experts_held = int(
            num_routed_experts if num_experts_held is None
            else num_experts_held)
        self.first_expert = int(first_expert)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_seq_len)
        self.dtype = str(dtype)
        self.init_weights = bool(init_weights)   # as LagunaConfig's
        # group-limited routing (`expert_layer.route_top_k`); None: none
        self.n_group = int(n_group) if n_group else None
        self.topk_group = int(topk_group) if n_group else None
        if self.first_expert + self.num_experts_held \
                > self.num_routed_experts:
            raise ValueError("held experts run past the router's width")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary pairs need an even qk_rope_head_dim")

    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_dim(self):
        """What a token leaves in the cache: `[c | k_r]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def sparse(self, index):
        return index >= self.first_k_dense

    def cache_kinds(self):
        """ONE kind, latent: a row of `row_dim` a token a layer."""
        return [CacheKind("latent", tuple(range(self.num_layers)), None,
                          None, None, False, self.row_dim)]

    # ---- rotary (YaRN as the family computes it) ---------------------

    def _yarn(self):
        rs = self.rope_scaling
        return rs if rs and float(rs.get("factor", 1)) > 1 else None

    def rope_frequencies(self):
        """([rope / 2] inverse frequencies, the factor on cos and sin):
        `mscale / mscale_all_dim` of the two YaRN temperatures."""
        rope = {"rope_theta": self.rope_theta, "rope_type": "default"}
        rs = self._yarn()
        if rs is not None:
            rope.update(
                rope_type="yarn", factor=rs["factor"],
                original_max_position_embeddings=rs[
                    "original_max_position_embeddings"],
                beta_fast=rs.get("beta_fast", 32),
                beta_slow=rs.get("beta_slow", 1),
                attention_factor=_yarn_mscale(rs["factor"],
                                              rs.get("mscale", 1))
                / _yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))
        return rope_inv_frequencies(rope, self.qk_rope_head_dim)

    def softmax_scale(self):
        """q_head_dim^-½, times YaRN's `mscale_all_dim` temperature
        squared."""
        scale = 1.0 / math.sqrt(self.q_head_dim)
        rs = self._yarn()
        if rs is not None and rs.get("mscale_all_dim", 0):
            scale *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale


def _yarn_mscale(factor, mscale):
    factor, mscale = float(factor), float(mscale)
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def sarvam_mla_tiny(**kw):
    """A CPU-test preset that keeps every ratio: a leading dense layer
    and four sparse ones, 4 heads of 16 + 8, latent 32, values 16, 16
    routed experts top-4 (all held unless told otherwise), one shared."""
    args = dict(
        vocab_size=256, hidden_size=64, num_layers=5, num_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_routed_experts=16, num_experts_per_tok=4,
        routed_scaling_factor=2.5, rope_theta=10000,
        rope_scaling={"type": "deepseek_yarn", "factor": 40,
                      "original_max_position_embeddings": 64,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                      "mscale_all_dim": 1},
        max_seq_len=256, dtype="float32")
    args.update(kw)
    return SarvamMLAConfig(**args)


# -------------------------------------------------------------- pieces

def _rope_tables(config, pos):
    """(cos, sin) [T, rope] float32 at positions `pos` [T]."""
    inv, factor = config.rope_frequencies()
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        inv.astype(np.float32))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def _rotate(x, tables):
    """x [T, ..., rope] float32: interleaved pairs (x0, x1), (x2, x3) …
    re-ordered to halves (x0, x2, … | x1, x3, …), then rotate-half."""
    cos, sin = tables
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    extra = (None,) * (x.ndim - 2)
    return x * cos[(slice(None), *extra)] + rot * sin[(slice(None), *extra)]


def _heads_mm(x, w):
    """x [T, H, a] · w [H, a, b] → [T, H, b] float32: one product a
    head, the heads as the LEADING batch dimension of both operands."""
    return jnp.swapaxes(jnp.matmul(
        jnp.swapaxes(x, 0, 1), w, preferred_element_type=jnp.float32), 0, 1)


def _row_write(pool, rows, write_idx):
    """Scatter `rows` [T, R] into a latent pool [N, P, R] at flat rows
    `write_idx` (page · P + offset; row 0 of page 0 is the trash row):
    along the major dimension of the pool seen as [N·P, R], a bitcast,
    so the pool's layout is the walk's and nothing is copied."""
    n, page_size, r = pool.shape
    flat = pool.reshape(n * page_size, r).at[
        write_idx.astype(jnp.int32)].set(rows.astype(pool.dtype))
    return flat.reshape(pool.shape)


class _TickForms:
    """Which form each row of a single tick takes, from what the tick's
    rows are (slot_ids, kv_lens [T]); made once a tick, used by every
    layer. `runs`: the runs of at least `_EXPANDED_MIN_ROWS` rows of one
    slot, which attend EXPANDED. Every other live row attends ABSORBED,
    `_EXPANDED_MIN_ROWS` of them at a time: `left` their rows in order
    (a slot's side by side still), T where there is none, `chunks` how
    many such chunks hold one."""

    def __init__(self, slot_ids, kv_lens):
        T = slot_ids.shape[0]
        A = _EXPANDED_MIN_ROWS
        self.runs = latent_run_layout(slot_ids, kv_lens, A)
        left = (kv_lens > 0) & ~self.runs.expanded
        rank = jnp.cumsum(left) - 1
        self.left = jnp.full((-(-T // A) * A,), T, jnp.int32).at[
            jnp.where(left, rank, T)].set(
                jnp.arange(T, dtype=jnp.int32), mode="drop")
        self.chunks = (rank[-1] + A) // A


class SarvamMLADecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.index = index
        self.sparse = c.sparse(index)
        d, H, L = c.hidden_size, c.num_heads, c.num_layers
        res = 0.02 / math.sqrt(2 * L)
        mk = lambda shape, std=0.02, one=False: _parameter(  # noqa: E731
            c, shape, std, one)
        self.attn_norm = mk((d,), one=True)
        self.wq = mk((d, H * c.q_head_dim))
        self.q_norm = mk((c.q_head_dim,), one=True)
        self.wkv_a = mk((d, c.row_dim))
        self.kv_norm = mk((c.kv_lora_rank,), one=True)
        self.kr_norm = mk((c.qk_rope_head_dim,), one=True)
        self.w_uk = mk((H, c.qk_nope_head_dim, c.kv_lora_rank))
        self.w_uv = mk((H, c.kv_lora_rank, c.v_head_dim))
        self.wo = mk((H * c.v_head_dim, d), res)
        self.ffn_norm = mk((d,), one=True)
        if not self.sparse:
            self.w_gate_up = mk((d, 2 * c.intermediate_size))
            self.w_down = mk((c.intermediate_size, d), res)
        else:
            E, m = c.num_experts_held, c.moe_intermediate_size
            ms = c.num_shared_experts * m
            self.router = mk((d, c.num_routed_experts))
            self.router_bias = _router_bias(c)
            self.experts_gate_up = mk((E, d, 2 * m))
            self.experts_down = mk((E, m, d), res)
            self.shared_gate_up = mk((d, 2 * ms))
            self.shared_down = mk((ms, d), res)


def _router_bias(config):
    """The selection bias, float32 whatever the weights' dtype: it is
    added to float32 scores and decides between near-equal experts."""
    p = _parameter(config, (config.num_routed_experts,), 0.01, False)
    v = p._value
    p._value = (jax.ShapeDtypeStruct(v.shape, jnp.float32)
                if isinstance(v, jax.ShapeDtypeStruct)
                else v.astype(jnp.float32))
    return p


class SarvamMLAForCausalLM(nn.Layer):
    """The served model: eager `forward` (the expanded form), and the
    engine's two step bodies over latent pages (the absorbed form)."""

    # int32 counters the step bodies return: the expert layer's three
    # (summed over sparse layers); latent rows the step's queries must
    # read (a row once a layer a slot) and those of them read for a
    # slot's ONE query row; the step's query rows by form (all × layers).
    # The last is the ENGINE's count (`_note_launches` adds a tick's
    # expanded launches to the entry of `stats` this name opens); the
    # step bodies add 0 to it
    step_counters = ("moe_assignments", "moe_assignments_held",
                     "moe_experts_touched", "mla_rows_attended_least",
                     "mla_rows_attended_single", "mla_rows_absorbed",
                     "mla_rows_expanded",
                     "paged_attn_latent_expanded_launches")

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        self.embed = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                False)
        self.layers = nn.LayerList(
            [SarvamMLADecoderLayer(c, i) for i in range(c.num_layers)])
        self.final_norm = _parameter(c, (c.hidden_size,), 0.02, True)
        self.lm_head = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                  False)

    def compute_dtype(self):
        return jnp.dtype(self.config.dtype)

    # ---- shared arithmetic ------------------------------------------

    def _queries(self, layer, n, tables):
        """(q_nope [T, H, nope], q_rope [T, H, rope]) float32 of the
        normed input: each head's query normed, its rope dims rotated."""
        c = self.config
        q = _mm(n, layer.wq._value).reshape(
            n.shape[0], c.num_heads, c.q_head_dim)
        q = _rms_norm(q, layer.q_norm._value, c.rms_norm_eps)
        with _scope("rope"):
            q_rope = _rotate(q[..., c.qk_nope_head_dim:], tables)
        return q[..., :c.qk_nope_head_dim], q_rope

    def _latent_row(self, layer, n, tables):
        """(c [T, latent], k_r [T, rope]) float32: what the token keeps."""
        c = self.config
        ckr = _mm(n, layer.wkv_a._value)
        lat = _rms_norm(ckr[:, :c.kv_lora_rank], layer.kv_norm._value,
                        c.rms_norm_eps)
        kr = _rms_norm(ckr[:, c.kv_lora_rank:], layer.kr_norm._value,
                       c.rms_norm_eps)
        with _scope("rope"):
            kr = _rotate(kr, tables)
        return lat, kr

    def _absorb(self, layer, q_nope, q_rope):
        """q̃ [T, H, row_dim] in the weights' dtype: W_UK folded into the
        query, a batched product over heads."""
        w = layer.w_uk._value
        qc = _heads_mm(q_nope.astype(w.dtype), w)
        return jnp.concatenate([qc, q_rope], axis=-1).astype(w.dtype)

    def _expanded_attention(self, layer, q_nope, q_rope, lat, kr, see):
        """The EXPANDED form over rows that hold their own context:
        k_nope, v up-projected from `lat` [U, latent]; `see` [T, U] bool
        which keys each query attends. Returns o [T, H, v] float32."""
        c = self.config
        dt = layer.w_uk._value.dtype
        lat = lat.astype(dt)
        f32 = jnp.float32
        # [H, U, nope] and [H, U, v]: heads lead every product
        k_nope = jnp.matmul(lat[None], jnp.swapaxes(layer.w_uk._value, 1, 2),
                            preferred_element_type=f32).astype(dt)
        v = jnp.matmul(lat[None], layer.w_uv._value,
                       preferred_element_type=f32).astype(dt)
        qn = jnp.swapaxes(q_nope, 0, 1).astype(dt)          # [H, T, nope]
        qr = jnp.swapaxes(q_rope, 0, 1).astype(dt)
        sc = jnp.matmul(qn, jnp.swapaxes(k_nope, 1, 2),
                        preferred_element_type=f32) \
            + jnp.matmul(qr, kr.astype(dt).T[None],
                         preferred_element_type=f32)        # [H, T, U]
        sc = jnp.where(see[None], sc * c.softmax_scale(), -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.swapaxes(jnp.matmul(p.astype(dt), v,
                                       preferred_element_type=f32), 0, 1)

    def _attn_out(self, layer, x, o):
        """x + concat(o_h)·W_o, o [T, H, v] float32."""
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
                # routed from the float32 normed input, as laguna.py
                w, ids = expert_layer.route_top_k(
                    n, layer.router._value, c.num_experts_per_tok,
                    c.norm_topk_prob, scoring="sigmoid",
                    select_bias=layer.router_bias._value,
                    n_group=c.n_group, topk_group=c.topk_group)
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

    # ---- eager forward (no cache): the expanded form -----------------

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
        tables = _rope_tables(c, pos)
        see = pos[None, :] <= pos[:, None]
        valid = jnp.ones((S,), bool)
        with _scope("embed"):
            x = self.embed._value[ids].astype(jnp.float32)
        for layer in self.layers:
            with _scope("attn"), _scope("attn_mla"):
                n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
                q_nope, q_rope = self._queries(layer, n, tables)
                lat, kr = self._latent_row(layer, n, tables)
                with _scope("mla_expand"):
                    o = self._expanded_attention(layer, q_nope, q_rope,
                                                 lat, kr, see)
                x = self._attn_out(layer, x, o)
            x, _ = self._ffn(layer, x, valid)
        return self._head(x)

    # ---- the engine's step bodies -----------------------------------

    def _walk_absorbed(self, layer, q_nope, q_rope, pool, page_tables,
                       slot_ids, kv_lens, frontier_offset, layout):
        """o [T, H, v] float32 of rows that attend ABSORBED: W_UK folded
        into the query, the walk over the rows' latent pages, W_UV on
        what it returns."""
        c = self.config
        with _scope("mla_q"):
            qa = self._absorb(layer, q_nope, q_rope)
            qa = jnp.pad(qa, ((0, 0), (0, 0),
                              (0, pool.shape[-1] - qa.shape[-1])))
        with _scope("mla_walk"):
            oc = paged_attention_latent(
                qa, pool, page_tables, slot_ids, kv_lens, c.kv_lora_rank,
                c.softmax_scale(), frontier_offset, layout)
        with _scope("mla_out"):
            w = layer.w_uv._value
            return _heads_mm(oc.astype(w.dtype), w)

    def _walk_by_form(self, layer, q_nope, q_rope, pool, page_tables,
                      slot_ids, kv_lens, forms, blocks):
        """o [T, H, v] of a tick whose long runs attend EXPANDED and
        whose other rows ABSORBED (`forms`, a `_TickForms`), merged by
        row: the same numbers a row whichever form serves it, to the
        rounding of the products. The absorbed rows are gathered a
        chunk of `_EXPANDED_MIN_ROWS` at a time (one chunk beside a long
        run, as a rule), so that form's products and its launch's
        blocks are those of the rows it serves, not of the tick's."""
        c = self.config
        T, A = slot_ids.shape[0], _EXPANDED_MIN_ROWS
        with _scope("mla_walk"):
            o = paged_attention_latent_expanded(
                q_nope, q_rope, pool, layer.w_uk._value, layer.w_uv._value,
                page_tables, slot_ids, kv_lens, c.softmax_scale(),
                forms.runs)

        def chunk(k, o):
            rows = jax.lax.dynamic_slice(forms.left, (k * A,), (A,))
            at = jnp.minimum(rows, T - 1)
            sids = slot_ids[at]
            lens = jnp.where(rows < T, kv_lens[at], 0)
            layout = SlotBlockLayout(
                sids, lens, _TICK_ROWS_PER_BLOCK,
                min(A, page_tables.shape[0])) if blocks else None
            got = self._walk_absorbed(
                layer, q_nope[at], q_rope[at], pool, page_tables, sids,
                lens, None, layout)
            return o.at[rows].set(got.astype(o.dtype), mode="drop")

        return jax.lax.fori_loop(0, forms.chunks, chunk, o)

    def _paged_core(self, tok, pos, slot_ids, write_idx, page_tables,
                    kv_lens, sample_idx, kv, frontier_offset=None,
                    slot_blocks=False):
        """Raw arrays; ONE cache kind, so write_idx [T], page_tables
        [S, MP] and `kv` one latent pool a layer. `slot_blocks`: the
        single tick's rows (a slot's side by side), which the walk takes
        in blocks of `_TICK_ROWS_PER_BLOCK` of one slot, and in the
        EXPANDED form where a slot has `_EXPANDED_MIN_ROWS` of them (a
        tick of fewer rows, or a pool in another dtype than the weights
        that expand it, has one form); the fused window has one row a
        slot. Returns (logits [S, vocab] float32, new kv, counters
        int32 [7])."""
        c = self.config
        valid = kv_lens > 0
        tables = _rope_tables(c, pos)
        how = self._tick_walk(slot_ids, kv_lens, page_tables.shape[0],
                              kv[0].dtype, slot_blocks)
        with _scope("embed"):
            x = self.embed._value[tok].astype(jnp.float32)
        new_kv = []
        moe = jnp.zeros((3,), jnp.int32)
        for i, layer in enumerate(self.layers):
            with _scope("attn"), _scope("attn_mla"):
                n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
                o, pool = self._attend_pages(
                    layer, n, kv[i], tables, write_idx, page_tables,
                    slot_ids, kv_lens, frontier_offset, how)
                with _scope("mla_out"):
                    x = self._attn_out(layer, x, o)
            new_kv.append(pool)
            x, cnt = self._ffn(layer, x, valid)
            moe = moe + cnt
        counters = jnp.concatenate([moe, self._walk_counters(
            c.num_layers, slot_ids, kv_lens, page_tables.shape[0],
            frontier_offset, how)])
        with _scope("lm_head"):
            x = x[sample_idx]
        return self._head(x), new_kv, counters

    def _tick_walk(self, slot_ids, kv_lens, n_slots, pool_dtype,
                   slot_blocks):
        """How a step's rows attend the latent pages, made once a step:
        (`_TickForms` or None, `SlotBlockLayout` or None, whether the
        absorbed walk takes slot blocks). The single tick's rows
        (`slot_blocks`) go in blocks of `_TICK_ROWS_PER_BLOCK` of one
        slot, and from `_EXPANDED_MIN_ROWS` rows a tick on its long runs
        EXPANDED (a pool in another dtype than the weights that expand
        it has one form); the fused window has one row a slot."""
        T = slot_ids.shape[0]
        blocks = slot_blocks and _attention._pallas_backend_ok()
        layout = forms = None
        with _scope("attn"):
            if slot_blocks and T >= _EXPANDED_MIN_ROWS \
                    and pool_dtype == self._latent_weight_dtype():
                forms = _TickForms(slot_ids, kv_lens)
            elif blocks:
                layout = SlotBlockLayout(slot_ids, kv_lens,
                                         _TICK_ROWS_PER_BLOCK, n_slots)
        return forms, layout, blocks

    def _latent_weight_dtype(self):
        return self.layers[0].w_uk._value.dtype

    def _attend_pages(self, layer, n, pool, tables, write_idx, page_tables,
                      slot_ids, kv_lens, frontier_offset, how):
        """A latent layer's attention over its pages for the normed input
        n [T, d]: the rows `[c | k_r]` written into `pool`, the queries,
        the walk in the form `how` gives each row. Returns (o [T, H, v]
        float32, the new pool)."""
        forms, layout, blocks = how
        with _scope("mla_latent_write"):
            lat, kr = self._latent_row(layer, n, tables)
            row = jnp.concatenate([lat, kr], axis=-1)
            # the pool's row is stored in whole 128-lane tiles
            # (`CacheKind.pool_shape`); the lanes past row_dim hold
            # zeros and multiply zeros
            row = jnp.pad(row, ((0, 0),
                                (0, pool.shape[-1] - row.shape[-1])))
            pool = _row_write(pool, row, write_idx)
        with _scope("mla_q"):
            q_nope, q_rope = self._queries(layer, n, tables)
        if forms is None:
            return self._walk_absorbed(
                layer, q_nope, q_rope, pool, page_tables, slot_ids,
                kv_lens, frontier_offset, layout), pool
        return self._walk_by_form(
            layer, q_nope, q_rope, pool, page_tables, slot_ids, kv_lens,
            forms, blocks), pool

    @staticmethod
    def _walk_counters(n_layers, slot_ids, kv_lens, n_slots,
                       frontier_offset, how):
        """int32 [4] over `n_layers` latent layers: the latent rows the
        step must read (each slot's longest row's context, once a layer;
        the frontier offset advances live rows), those of them read for
        a slot's ONE query row, the query rows by form (absorbed,
        expanded)."""
        forms = how[0]
        valid = kv_lens > 0
        lens = jnp.where(valid, kv_lens + (
            0 if frontier_offset is None else frontier_offset), 0)
        longest = jax.ops.segment_max(
            lens, slot_ids, num_segments=n_slots).clip(0)
        alone = jax.ops.segment_sum(
            valid.astype(jnp.int32), slot_ids, num_segments=n_slots) == 1
        expanded = (jnp.zeros((), jnp.int32) if forms is None
                    else jnp.sum(forms.runs.expanded))
        return n_layers * jnp.stack([
            jnp.sum(longest), jnp.sum(jnp.where(alone, longest, 0)),
            jnp.sum(valid) - expanded, expanded]).astype(jnp.int32)

    def _paged_decode_core(self, tok, pos_ids, slot_ids, write_idx,
                           page_tables, kv_lens, sample_idx, kv,
                           kv_scales=None, frontier_offset=None,
                           max_q_per_slot=None):
        """The single tick (serving_protocol.py): Tensors in and out;
        returns (logits [1, S, vocab], *new pools, counters [8])."""
        if kv_scales:
            raise ValueError(f"{type(self).__name__} serves float pools")
        val = lambda t: None if t is None else t._value   # noqa: E731
        logits, new_kv, counters = self._paged_core(
            val(tok), val(pos_ids), val(slot_ids), val(write_idx),
            val(page_tables), val(kv_lens), val(sample_idx),
            [val(p) for p in kv], val(frontier_offset), slot_blocks=True)
        t = lambda v: Tensor(v, stop_gradient=True)       # noqa: E731
        # + 0 for the engine's own count, `step_counters`' last name
        return (t(logits[None]), *[t(p) for p in new_kv],
                t(jnp.pad(counters, (0, 1))))

    def _paged_decode_fused(self, k, page_size, tok0, pos0, rem, fin0,
                            eos_ids, temps, top_ps, streams, page_tables,
                            kv, kv_scales, key, lag=None, frontier=None,
                            gstate0=None, gtrans=None, gmask=None):
        """`k` decode iterations in one scan, sampling inside (the
        contract of `LagunaForCausalLM._paged_decode_fused`). Returns
        (emits [k, S], new kv, [], counters [k, 8])."""
        if lag is not None or gtrans is not None or kv_scales:
            raise ValueError(
                f"{type(self).__name__}'s fused window takes no draft "
                "lag, grammar tables or quantized pools")
        S = tok0.shape[0]
        sl = jnp.arange(S, dtype=jnp.int32)
        pt = jnp.asarray(page_tables, jnp.int32)           # [S, MP]
        klen0 = pos0 + 1
        pad = jnp.asarray(-1, jnp.int32)

        def body(carry, i):
            tok, fin, kv_c = carry
            live = ~fin
            tok_in = jnp.where(live, tok, 0)
            pos_in = jnp.where(live, pos0 + i, 0)
            klen = jnp.where(live, klen0, 0)   # + i rides the offset
            page = pt[sl, pos_in // page_size]
            widx = jnp.where(live, page * page_size + pos_in % page_size,
                             0)
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
        return emits, kv_f, [], jnp.pad(counters, ((0, 0), (0, 1)))
