"""Olmo-hybrid — a decoder whose layers keep TWO kinds of attention
(`model_type: olmo_hybrid`, allenai/Olmo-Hybrid-7B): gated DeltaNet
layers (a gated delta rule with a decay a HEAD) and full multi-head
attention with no position encoding, in the order `layer_types` gives.
Norms sit on each sublayer's OUTPUT, as in OLMo 2: for layer l over the
residual stream x [T, d]

    h = x + RMSNorm(Mix_l(x));   y = h + RMSNorm(MLP(h))

with MLP a SwiGLU, then a final RMSNorm and an UNTIED head.

  GDN   q, k, v = SiLU(conv(x·W_q)), SiLU(conv(x·W_k)), SiLU(conv(x·W_v)),
        a causal depthwise convolution of K taps a channel; q, k
        L2-normed a head, q · d_k^-½; g = −exp(A_log_h) · softplus(x·W_a
        + dt_bias_h) the log decay of a head, unbounded below; β =
        2·sigmoid(x·W_b) with negative eigenvalues allowed (else
        sigmoid); a float32 state a head
          S_t = exp(g_t) (I − β_t k_t k_tᵀ) S_{t−1} + β_t k_t v_tᵀ
          o_t = S_tᵀ q_t
        o RMSNormed a head over d_v, times SiLU(x·W_g) (value width),
        then W_o.
  FULL  q = RMSNorm(x·W_q), k = RMSNorm(x·W_k) over the whole width
        before the heads are split, v = x·W_v; causal softmax of q·kᵀ ·
        d_head^-½ a head, grouped queries where there are fewer K/V
        heads; no rotary; then W_o.

What a GDN layer keeps of a sequence is a fixed SLAB: the state `[H,
d_k, d_v']` float32, d_v' the value width in whole lane tiles (192 →
256: a narrower last dimension is laid out at that width on the device
anyway, and a Pallas kernel takes the array only in its row-major
layout, which the device gives only to whole tiles; the lanes past d_v
stay zero), and the last K − 1 rows of `[q | k | v]` before the
convolution (the compute dtype): the cache kind `gdn_state`, beside the
full layers' head-major pages `full`. A step's first row of a slot at
position 0 starts the slot's state from zero. The recurrence takes the
two forms of `ling_hybrid.py` (`delta_rule_forms`) by what a step's rows
are; the gate's shape `[T, H]` selects the head-gate arithmetic inside
them (`nn/functional/delta_rule.py`).

The equations, their sources and what is assumed are written out in
`benchmarks/references/olmo_hybrid.py`, which the tests hold this file
to. Compute is raw `jax.numpy`: residual stream, norms, gates, state and
softmax in float32; every projection takes its operands in the weights'
dtype and accumulates in float32.

Named scopes: `embed`; `attn` ⊃ `attn_gdn` ⊃ `gdn_proj` (projections,
convolution, L2 norms, gate and β), `gdn_chunk`, `gdn_recur`, `gdn_out`
(the per-head norm, the SiLU gate, W_o and the output norm); `attn` ⊃
`attn_full`; `mlp` (with its output norm); `norm` (final); `lm_head`;
`sample` (docs/OBSERVABILITY.md "Spans and scopes").
"""
import math

import jax
import jax.numpy as jnp

from ... import nn
from ...nn.functional import delta_rule
from ...nn.functional.attention import (_pallas_backend_ok,
                                        paged_attention_gqa, SlotBlockLayout,
                                        SlotRunLayout)
from .laguna import (_TICK_ROWS_PER_BLOCK, _gated_mlp, _mm, _parameter,
                     _pool_write, _rms_norm, LagunaForCausalLM)
from .ling_hybrid import (_float32, _l2_norm, _SlotRuns, delta_rule_forms,
                          short_conv)
from .sarvam_mla import SarvamMLAForCausalLM
from .serving_protocol import CacheKind

__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM", "olmo_hybrid_tiny"]

_scope = jax.named_scope

FULL, LINEAR = "full_attention", "linear_attention"
_LANES = 128


class OlmoHybridConfig:
    """The published config's keys under the program's names; the layer
    list is cut to `num_layers` entries."""

    def __init__(self, vocab_size, hidden_size, num_layers, layer_types,
                 num_heads, num_kv_heads, intermediate_size,
                 linear_num_heads, linear_key_head_dim,
                 linear_value_head_dim, conv_taps=4,
                 allow_neg_eigval=True, rms_norm_eps=1e-6,
                 max_seq_len=8192, dtype="bfloat16", init_weights=True):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_layers)
        self.layer_types = list(layer_types)[:self.num_layers]
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = self.hidden_size // self.num_heads
        self.intermediate_size = int(intermediate_size)
        self.linear_heads = int(linear_num_heads)
        self.key_dim = int(linear_key_head_dim)
        self.value_dim = int(linear_value_head_dim)
        self.conv_taps = int(conv_taps)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_seq_len)
        self.dtype = str(dtype)
        self.init_weights = bool(init_weights)   # as LagunaConfig's
        if len(self.layer_types) != self.num_layers or not set(
                self.layer_types) <= {FULL, LINEAR}:
            raise ValueError(f"layer_types {self.layer_types}: one of "
                             f"{FULL!r} / {LINEAR!r} a layer")
        if FULL not in self.layer_types:
            raise ValueError("no layer of this depth is full attention: "
                             "the engine needs one paged cache kind")
        if self.num_heads % self.num_kv_heads or \
                self.head_dim * self.num_heads != self.hidden_size:
            raise ValueError("query heads must group over the K/V heads "
                             "and split the hidden width")

    @property
    def conv_width(self):
        """`[q | k | v]` before the convolution."""
        return self.linear_heads * (2 * self.key_dim + self.value_dim)

    @property
    def value_store(self):
        """The state's value width in whole lane tiles."""
        return -(-self.value_dim // _LANES) * _LANES

    def cache_kinds(self):
        """The full layers' head-major pages, then the GDN layers' slab:
        the float32 state and the convolution's tail."""
        layers = range(self.num_layers)
        return [
            CacheKind("full", tuple(i for i in layers
                                    if self.layer_types[i] == FULL),
                      self.num_kv_heads, self.head_dim, None, True),
            CacheKind("gdn_state", tuple(i for i in layers
                                         if self.layer_types[i] == LINEAR),
                      None, None, None, False, None,
                      (((self.linear_heads, self.key_dim, self.value_store),
                        "float32"),
                       ((self.conv_taps - 1, self.conv_width), None)))]


def olmo_hybrid_tiny(**kw):
    """A CPU-test preset: one period L L L F, 3 GDN heads of d_k 16 and
    d_v 32 (the published 1 : 2), 4 full heads of 16."""
    args = dict(
        vocab_size=256, hidden_size=64, num_layers=4,
        layer_types=[LINEAR] * 3 + [FULL], num_heads=4, num_kv_heads=4,
        intermediate_size=128, linear_num_heads=3, linear_key_head_dim=16,
        linear_value_head_dim=32, max_seq_len=512, dtype="float32")
    args.update(kw)
    return OlmoHybridConfig(**args)


class OlmoHybridDecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.index = index
        self.full = c.layer_types[index] == FULL
        d, L = c.hidden_size, c.num_layers
        res = 0.02 / math.sqrt(2 * L)
        mk = lambda shape, std=0.02, one=False: _parameter(  # noqa: E731
            c, shape, std, one)
        if self.full:
            hd, H, kv = c.head_dim, c.num_heads, c.num_kv_heads
            self.wqkv = mk((d, (H + 2 * kv) * hd))
            self.q_norm = mk((H * hd,), one=True)
            self.k_norm = mk((kv * hd,), one=True)
            self.wo = mk((H * hd, d), res)
        else:
            H, dv = c.linear_heads, c.value_dim
            # q | k | v (convolved) | output gate | decay | β
            self.w_in = mk((d, c.conv_width + H * dv + 2 * H))
            self.conv = mk((c.conv_taps, c.conv_width), 0.5)
            self.a_log = _float32(mk((H,), 0.3))
            self.dt_bias = _float32(mk((H,), 1.0))
            self.o_norm = mk((dv,), one=True)
            self.wo = mk((H * dv, d), res)
        self.attn_out_norm = mk((d,), one=True)
        self.w_gate_up = mk((d, 2 * c.intermediate_size))
        self.w_down = mk((c.intermediate_size, d), res)
        self.mlp_out_norm = mk((d,), one=True)


class OlmoHybridForCausalLM(nn.Layer):
    """The served model: eager `forward`, and the engine's two step
    bodies over the full layers' pages and the GDN layers' slabs. The
    single tick's and the fused window's shells, the eager entry and the
    head are the other served models' (`SarvamMLAForCausalLM`'s with
    ONE paged kind; `LagunaForCausalLM`'s); this class gives them its
    `_paged_core`."""

    # int32 counters the step bodies return: the GDN layers' query rows ×
    # layers by form, and the chunked runs × layers
    step_counters = ("gdn_rows_recurrent", "gdn_rows_chunked",
                     "gdn_chunk_launches")

    compute_dtype = LagunaForCausalLM.compute_dtype
    forward = LagunaForCausalLM.forward
    _head = LagunaForCausalLM._head
    _paged_decode_core = SarvamMLAForCausalLM._paged_decode_core
    _paged_decode_fused = SarvamMLAForCausalLM._paged_decode_fused

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        self.embed = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                False)
        self.layers = nn.LayerList(
            [OlmoHybridDecoderLayer(c, i) for i in range(c.num_layers)])
        self.final_norm = _parameter(c, (c.hidden_size,), 0.02, True)
        self.lm_head = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                  False)

    # ---- the two mixers ---------------------------------------------

    def _gdn_inputs(self, layer, x, tail, slot_ids, pos, sr):
        """Of the residual stream x [T, d]: (q k [T, H, d_k], v [T, H,
        d_v], g β [T, H], the output gate [T, H·d_v], float32; the
        slots' new tails, or None without a cache)."""
        c = self.config
        T, H, dk, dv = x.shape[0], c.linear_heads, c.key_dim, c.value_dim
        cw = c.conv_width
        p = _mm(x, layer.w_in._value)
        y, new_tail = short_conv(p[:, :cw], layer.conv._value, tail,
                                 slot_ids, pos, sr)
        y = jax.nn.silu(y)
        q = _l2_norm(y[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
        k = _l2_norm(y[:, H * dk:2 * H * dk].reshape(T, H, dk))
        v = y[:, 2 * H * dk:].reshape(T, H, dv)
        gate = p[:, cw:cw + H * dv]
        a, b = p[:, cw + H * dv:cw + H * dv + H], p[:, cw + H * dv + H:]
        g = -jnp.exp(layer.a_log._value)[None, :] * jax.nn.softplus(
            a + layer.dt_bias._value[None, :])
        beta = jax.nn.sigmoid(b) * (2.0 if c.allow_neg_eigval else 1.0)
        return q, k, v, g, beta, gate, new_tail

    def _gdn_recurrence(self, state, q, k, v, g, beta, pos, sr):
        """Through the slots' states, whose value width is stored in
        whole lane tiles: v padded with zeros, o cut back."""
        dv = v.shape[-1]
        v = jnp.pad(v, ((0, 0), (0, 0), (0, state.shape[-1] - dv)))
        o, state = delta_rule_forms(state, q, k, v, g, beta, pos, sr,
                                    ("gdn_chunk", "gdn_recur"))
        return o[..., :dv], state

    def _gdn_out(self, layer, x, o, gate):
        """x + RMSNorm(concat(RMSNorm_h(o_h) ⊙ SiLU(gate))·W_o)."""
        c = self.config
        o = _rms_norm(o, layer.o_norm._value, c.rms_norm_eps)
        o = o.reshape(x.shape[0], -1) * jax.nn.silu(gate)
        return x + _rms_norm(_mm(o, layer.wo._value),
                             layer.attn_out_norm._value, c.rms_norm_eps)

    def _full_qkv(self, layer, x):
        """q [T, H, D], k v [T, KV, D] in the weights' dtype: q and k
        RMSNormed over the whole width before the heads are split."""
        c = self.config
        T, hd, H, kv = x.shape[0], c.head_dim, c.num_heads, c.num_kv_heads
        dt = layer.wqkv._value.dtype
        qkv = _mm(x, layer.wqkv._value)
        q = _rms_norm(qkv[:, :H * hd], layer.q_norm._value, c.rms_norm_eps)
        k = _rms_norm(qkv[:, H * hd:(H + kv) * hd], layer.k_norm._value,
                      c.rms_norm_eps)
        return (q.reshape(T, H, hd).astype(dt), k.reshape(T, kv, hd).astype(
            dt), qkv[:, (H + kv) * hd:].reshape(T, kv, hd).astype(dt))

    def _full_out(self, layer, x, o):
        c = self.config
        return x + _rms_norm(
            _mm(o.astype(jnp.float32).reshape(x.shape[0], -1),
                layer.wo._value), layer.attn_out_norm._value,
            c.rms_norm_eps)

    def _mlp(self, layer, x):
        c = self.config
        with _scope("mlp"):
            return x + _rms_norm(
                _gated_mlp(x, layer.w_gate_up._value, layer.w_down._value),
                layer.mlp_out_norm._value, c.rms_norm_eps)

    # ---- eager forward (no cache) ------------------------------------

    def _forward_one(self, ids):
        c = self.config
        S = ids.shape[0]
        pos = jnp.arange(S)
        see = pos[None, :] <= pos[:, None]
        zeros = jnp.zeros((S,), jnp.int32)
        runs = SlotRunLayout(zeros, pos.astype(jnp.int32) + 1, S,
                             delta_rule.CHUNK, 0)             # ONE run
        state = jnp.zeros((1, c.linear_heads, c.key_dim, c.value_store),
                          jnp.float32)
        with _scope("embed"):
            x = self.embed._value[ids].astype(jnp.float32)
        for layer in self.layers:
            if layer.full:
                with _scope("attn"), _scope("attn_full"):
                    q, k, v = self._full_qkv(layer, x)
                    g = c.num_heads // c.num_kv_heads
                    sc = jnp.einsum(
                        "qhgd,khd->hgqk",
                        q.reshape(S, c.num_kv_heads, g, c.head_dim), k,
                        preferred_element_type=jnp.float32) \
                        / math.sqrt(c.head_dim)
                    p = jax.nn.softmax(jnp.where(see, sc, -jnp.inf), -1)
                    o = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
                    x = self._full_out(layer, x, o)
            else:
                with _scope("attn"), _scope("attn_gdn"):
                    q, k, v, g, beta, gate, _ = self._gdn_inputs(
                        layer, x, None, zeros, pos, None)
                    dv = v.shape[-1]
                    o, _, _ = delta_rule.delta_rule_chunked(
                        state, q, k, jnp.pad(v, ((0, 0), (0, 0), (
                            0, c.value_store - dv))), g, beta, runs)
                    x = self._gdn_out(layer, x, o[..., :dv], gate)
            x = self._mlp(layer, x)
        return self._head(x)

    # ---- the engine's step bodies -----------------------------------

    def _paged_core(self, tok, pos, slot_ids, write_idx, page_tables,
                    kv_lens, sample_idx, kv, frontier_offset=None,
                    slot_blocks=False):
        """Raw arrays. ONE paged cache kind (the full layers' pages), so
        write_idx [T] and page_tables [S, MP]; `kv` in layer order: a
        full layer's K and V pools, a GDN layer's state [S, H, d_k,
        d_v'] float32 and tail [S, K − 1, conv_width]. `slot_blocks`:
        the single tick's rows (a slot's side by side), whose long runs
        take the CHUNKED delta rule and whose rows the paged kernel
        takes in blocks of one slot; the fused window has one row a
        slot. Returns (logits [S, vocab] float32, new kv, counters int32
        [3])."""
        c = self.config
        n_slots = page_tables.shape[0]
        valid = kv_lens > 0
        layout = None
        with _scope("attn"):
            if slot_blocks and _pallas_backend_ok():
                layout = SlotBlockLayout(slot_ids, kv_lens,
                                         _TICK_ROWS_PER_BLOCK, n_slots)
            sr = _SlotRuns(slot_ids, kv_lens, n_slots, slot_blocks)
        with _scope("embed"):
            x = self.embed._value[tok].astype(jnp.float32)
        new_kv = []
        for i, layer in enumerate(self.layers):
            if layer.full:
                with _scope("attn"), _scope("attn_full"):
                    q, k, v = self._full_qkv(layer, x)
                    ck = _pool_write(kv[2 * i], k, write_idx)
                    cv = _pool_write(kv[2 * i + 1], v, write_idx)
                    o = paged_attention_gqa(
                        q, ck, cv, page_tables, slot_ids, kv_lens, None,
                        frontier_offset, layout)
                    x = self._full_out(layer, x, o)
                new_kv += [ck, cv]
            else:
                with _scope("attn"), _scope("attn_gdn"):
                    with _scope("gdn_proj"):
                        q, k, v, g, beta, gate, tail = self._gdn_inputs(
                            layer, x, kv[2 * i + 1], slot_ids, pos, sr)
                    o, state = self._gdn_recurrence(
                        kv[2 * i], q, k, v, g, beta, pos, sr)
                    with _scope("gdn_out"):
                        x = self._gdn_out(layer, x, o, gate)
                new_kv += [state, tail]
            x = self._mlp(layer, x)
        # the GDN layers' query rows × layers by form, and chunked runs
        if sr.runs is None:
            chunked = runs = jnp.zeros((), jnp.int32)
        else:
            chunked = jnp.sum(sr.runs.expanded)
            runs = jnp.sum(sr.runs.run_rows > 0)
        n_gdn = sum(not layer.full for layer in self.layers)
        counters = n_gdn * jnp.stack(
            [jnp.sum(valid) - chunked, chunked, runs]).astype(jnp.int32)
        with _scope("lm_head"):
            x = x[sample_idx]
        return self._head(x), new_kv, counters
