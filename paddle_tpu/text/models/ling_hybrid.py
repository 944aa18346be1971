"""Ling-hybrid — a decoder whose layers keep TWO kinds of attention
(the language model of inclusionAI/Ling-3.0-flash-VL): layer l is
multi-head LATENT attention where (l + 1) % layer_group_size == 0 and
KDA (Kimi Delta Attention, a gated delta rule) otherwise; leading dense
layers, then sigmoid-routed experts with a selection bias, GROUP-LIMITED,
and one shared expert. The vision tower and the multi-token head of the
published model are not here: this serves the language model on text
ids.

For layer l over x [T, d]:

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

then a final RMSNorm and an UNTIED head. With n the normed input:

  KDA   q, k, v = SiLU(conv(n·W_q)), SiLU(conv(n·W_k)), SiLU(conv(n·W_v)),
        a causal depthwise convolution of K taps a channel; q, k
        L2-normed a head, q · d_k^-½; g = lower · sigmoid(exp(A_log_h) ·
        (n·W_a + dt_bias)) the log decay a key channel; β = sigmoid(n·W_β)
        a head; a float32 state a head
          S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t v_tᵀ
          o_t = S_tᵀ q_t
        o RMSNormed a head, gated a head by sigmoid(n·W_g), then W_o.
  MLA   `sarvam_mla.py`'s, with plain rotary, and the same gate a head
        before W_o.

What a KDA layer keeps of a sequence is a fixed SLAB, whatever its
length: the state `[H, d_k, d_v]` float32 and the last K − 1 rows of
`[q | k | v]` before the convolution (the compute dtype): the cache kind
`kda_state` (`serving_protocol.CacheKind.slab`), one slab a slot, beside
the MLA layers' `latent` pages. A step's first row of a slot at position
0 starts the slot's state from zero.

The recurrence takes one of two forms from what a step's rows ARE
(`nn/functional/delta_rule.py`), never from a flag:

    RECURRENT  a row reads its slot's state once and writes it once: the
               fused window's rows (one a slot), and in the single tick
               the rows of every run shorter than `_CHUNKED_MIN_ROWS`, a
               row of each slot an iteration.
    CHUNKED    a run of at least `_CHUNKED_MIN_ROWS` rows of one slot (a
               prompt's chunk; it may begin and end anywhere) from the
               slot's stored state, `delta_rule.CHUNK` rows a chunk, the
               state after the run written back.

The equations, their sources and what is assumed are written out in
`benchmarks/references/ling_hybrid.py`, which the tests hold this file
to. Compute is raw `jax.numpy`: residual stream, norms, gates, state and
softmax in float32; every projection takes its operands in the weights'
dtype and accumulates in float32.

Named scopes: `embed`; `attn` ⊃ `attn_kda` ⊃ `kda_proj` (projections,
convolution, norms, gates), `kda_chunk`, `kda_recur`, `kda_out`; `attn` ⊃
`attn_mla` ⊃ `mla_q`, `mla_latent_write`, `mla_walk`, `mla_expand`,
`mla_out`; `mlp` ⊃ `norm`, and in a sparse layer `moe` ⊃ `moe_router`,
`moe_experts`, `moe_shared`; `norm` (final); `lm_head`; `sample`
(docs/OBSERVABILITY.md "Spans and scopes").
"""
import math

import jax
import jax.numpy as jnp

from ... import nn
from ...nn.functional import delta_rule
from ...nn.functional.attention import SlotRunLayout
from .laguna import _mm, _parameter, _rms_norm
from .sarvam_mla import (_rope_tables, _router_bias, SarvamMLAConfig,
                         SarvamMLAForCausalLM)
from .serving_protocol import CacheKind

__all__ = ["LingHybridConfig", "LingHybridForCausalLM", "ling_hybrid_tiny"]

_scope = jax.named_scope

# a run of one slot's rows in the single tick takes the CHUNKED form from
# this many rows on; a shorter one goes a row an iteration through the
# recurrent step. On a v5e, alone in a 2 048-row tick of 96 slots, a layer
# (`tools/kda_sweep.py --tables cross`, PERF.md §6, PR 36):
#     rows            4      8     16     32     64    128    256
#     chunked      0.41   0.38   0.37   0.35   0.34   0.39   0.51 ms
#     recurrent    0.37   0.59   1.04   1.97   3.77   7.40  14.64 ms
# (59 µs an iteration; the chunked form's 0.3 ms are the zeroed result and
# the chunk table, which every tick pays whatever it holds): they cross
# near 4 rows, where with PR 35's layout round the kernel (5.2 ms a tick)
# they crossed near 35. The threshold stays two chunks all the same: the
# benchmark's own test holds a tick of 32 rows to NO chunked launch
# (`tests/benchmarks/test_perfbench_ling_hybrid.py`), so moving it under
# 33 waits for a `benchmark` PR (PERF.md §7); what it costs meanwhile is a
# prompt's remainder of 16–63 rows going a row an iteration
_CHUNKED_MIN_ROWS = 2 * delta_rule.CHUNK


class LingHybridConfig(SarvamMLAConfig):
    """The published config's keys under the program's names: the MLA
    layer's and the experts' as `SarvamMLAConfig` has them, and KDA's."""

    def __init__(self, head_dim=128, layer_group_size=6,
                 short_conv_kernel_size=4, kda_lower_bound=-5.0, **kw):
        super().__init__(**kw)
        self.head_dim = int(head_dim)
        self.layer_group_size = int(layer_group_size)
        self.conv_taps = int(short_conv_kernel_size)
        self.kda_lower_bound = float(kda_lower_bound)
        if self.head_dim != self.v_head_dim:
            raise ValueError("one W_o serves both kinds: the KDA head's "
                             "values are as wide as the MLA head's")
        if not any(self.is_mla(i) for i in range(self.num_layers)):
            raise ValueError("no layer of this depth is MLA: the engine "
                             "needs one paged cache kind")

    def is_mla(self, index):
        return (index + 1) % self.layer_group_size == 0

    @property
    def kda_width(self):
        return self.num_heads * self.head_dim

    def cache_kinds(self):
        """The MLA layers' latent pages, then the KDA layers' slab: the
        float32 state and the convolution's tail."""
        layers = range(self.num_layers)
        H, dk = self.num_heads, self.head_dim
        return [
            CacheKind("latent", tuple(i for i in layers if self.is_mla(i)),
                      None, None, None, False, self.row_dim),
            CacheKind("kda_state",
                      tuple(i for i in layers if not self.is_mla(i)),
                      None, None, None, False, None,
                      (((H, dk, dk), "float32"),
                       ((self.conv_taps - 1, 3 * H * dk), None)))]


def ling_hybrid_tiny(**kw):
    """A CPU-test preset that keeps every ratio: seven layers of a period
    of six (layer 5 MLA), a leading dense layer, 4 heads of 16, latent
    32, 32 routed experts in 8 groups of which 4 are kept, top-8."""
    args = dict(
        vocab_size=256, hidden_size=64, num_layers=7, num_heads=4,
        head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_routed_experts=32,
        num_experts_per_tok=8, n_group=8, topk_group=4, first_k_dense=1,
        routed_scaling_factor=2.5, rope_theta=6e6, max_seq_len=512,
        dtype="float32")
    args.update(kw)
    return LingHybridConfig(**args)


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


class LingDecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.index = index
        self.mla = c.is_mla(index)
        self.sparse = c.sparse(index)
        d, H, L = c.hidden_size, c.num_heads, c.num_layers
        res = 0.02 / math.sqrt(2 * L)
        mk = lambda shape, std=0.02, one=False: _parameter(  # noqa: E731
            c, shape, std, one)
        self.attn_norm = mk((d,), one=True)
        if self.mla:
            self.wq = mk((d, H * c.q_head_dim))
            self.q_norm = mk((c.q_head_dim,), one=True)
            self.wkv_a = mk((d, c.row_dim))
            self.kv_norm = mk((c.kv_lora_rank,), one=True)
            self.kr_norm = mk((c.qk_rope_head_dim,), one=True)
            self.w_uk = mk((H, c.qk_nope_head_dim, c.kv_lora_rank))
            self.w_uv = mk((H, c.kv_lora_rank, c.v_head_dim))
            self.wg = mk((d, H))
        else:
            hk = c.kda_width
            self.w_qkva = mk((d, 4 * hk))           # q | k | v | decay
            self.conv = mk((c.conv_taps, 3 * hk), 0.5)
            self.a_log = _float32(mk((H,), 0.3))
            self.dt_bias = _float32(mk((hk,), 1.0))
            self.w_bg = mk((d, 2 * H))              # β | output gate
            self.o_norm = mk((c.head_dim,), one=True)
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


def _float32(p):
    """A parameter kept in float32 whatever the weights' dtype (the
    decay's A_log and dt_bias: they sit inside an exponential)."""
    v = p._value
    p._value = (jax.ShapeDtypeStruct(v.shape, jnp.float32)
                if isinstance(v, jax.ShapeDtypeStruct)
                else v.astype(jnp.float32))
    return p


class _SlotRuns:
    """What a step's rows are, a slot: made once a step, used by every
    KDA layer. `start` / `rows` [S] a slot's first live row and how many
    it has (its rows stand side by side at consecutive positions);
    `offset` [T] a row's place in its slot's run. In the single tick
    (`chunked`), `runs` the `SlotRunLayout` of the runs long enough to
    chunk, and `short_start` / `short_rows` [S] the others'; elsewhere
    (the fused window) `one_row_a_slot`."""

    def __init__(self, slot_ids, kv_lens, n_slots, chunked):
        T = slot_ids.shape[0]
        self.live = kv_lens > 0
        seg = jnp.where(self.live, slot_ids, n_slots)
        r = jnp.arange(T, dtype=jnp.int32)
        self.rows = jax.ops.segment_sum(
            self.live.astype(jnp.int32), seg, num_segments=n_slots)
        self.start = jax.ops.segment_min(
            r, seg, num_segments=n_slots).clip(0, T - 1).astype(jnp.int32)
        self.offset = r - self.start[slot_ids]
        self.one_row_a_slot = not chunked          # the fused window
        self.runs = None
        self.short_start, self.short_rows = self.start, self.rows
        if chunked and T >= _CHUNKED_MIN_ROWS:
            self.runs = SlotRunLayout(slot_ids, kv_lens, _CHUNKED_MIN_ROWS,
                                      delta_rule.CHUNK, 0)
            self.short_rows = jnp.where(
                self.runs.expanded[self.start], 0, self.rows)


def short_conv(pre, taps, tail, slot_ids, pos, sr):
    """The causal depthwise convolution of K taps [K, W] (the last weighs
    a row's own input) over a step's rows `pre` [T, W] float32: (y [T,
    W] before the activation, the slots' new tails). `tail` [S, K − 1,
    W] the rows before each slot's run that the convolution still reads,
    or None (no cache: every run starts its sequence; `sr` then unused).
    A step's rows stand side by side a slot (`_SlotRuns` `sr`)."""
    T, K = pre.shape[0], taps.shape[0]
    taps = taps.astype(jnp.float32)
    new_tail = None
    if tail is not None:
        # the rows a later step reads back are rounded as stored
        pre = pre.astype(tail.dtype).astype(jnp.float32)
    y = pre * taps[K - 1][None, :]
    for s in range(1, K):
        prev = jnp.roll(pre, s, axis=0)         # row t − s
        if tail is None:
            prev = jnp.where((pos >= s)[:, None], prev, 0.0)
        else:
            # before the run: the slot's tail; before the sequence: 0
            kept = tail[slot_ids, (sr.offset - s + K - 1).clip(0, K - 2)]
            prev = jnp.where(
                (sr.offset >= s)[:, None], prev,
                jnp.where((pos >= s)[:, None],
                          kept.astype(jnp.float32), 0.0))
        y = y + prev * taps[K - 1 - s][None, :]
    if tail is not None:
        # the last K − 1 rows of each slot's sequence after its run:
        # the run's own, and the old tail's where the run is shorter
        i = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        back = sr.rows[:, None] - (K - 1) + i              # [S, K − 1]
        own = pre.astype(tail.dtype)[
            (sr.start[:, None] + back).clip(0, T - 1)]
        old = jnp.take_along_axis(
            tail, (i + sr.rows[:, None]).clip(0, K - 2)[:, :, None],
            axis=1)
        new_tail = jnp.where(
            (sr.rows > 0)[:, None, None],
            jnp.where((back >= 0)[:, :, None], own, old), tail)
    return y, new_tail


def delta_rule_forms(state, q, k, v, g, beta, pos, sr, words):
    """A step's rows through the slots' states [S, H, d_k, d_v], each run
    by the form its length gives it (`_SlotRuns` `sr`): (o [T, H, d_v],
    the new state). g a decay a channel [T, H, d_k] or a head [T, H];
    `words` the scopes of the chunked and the recurrent form."""
    T = q.shape[0]
    o = jnp.zeros(v.shape, jnp.float32)
    # a row that is not live is neutral. The two selects fuse into what
    # computes g and β, and give them the TYPE q, k and v have: in the
    # first layer g and β descend from the step's token ids alone and
    # carry no mesh, after it from the caches too, and the chunk kernel
    # would be traced once for each (2 s a trace and as much again to
    # lower: PERF.md §6). `offset` is made of the slot ids, which are
    # typed as the caches are
    live = sr.live & (sr.offset >= 0)          # = sr.live
    g = jnp.where(live.reshape((T,) + (1,) * (g.ndim - 1)), g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    if sr.runs is not None:
        with _scope(words[0]):
            o, state, _ = delta_rule.delta_rule_chunked(
                state, q, k, v, g, beta, sr.runs)

    def one(i, carry):
        o, state = carry
        live = i < sr.short_rows
        rows = (sr.short_start + i).clip(0, T - 1)
        got, state = delta_rule.delta_rule_step(
            state, q[rows], k[rows], v[rows], g[rows], beta[rows],
            live, live & (pos[rows] == 0))
        return o.at[jnp.where(live, rows, T)].set(got, mode="drop"), state

    with _scope(words[1]):
        if sr.one_row_a_slot:
            return one(0, (o, state))          # the fused window
        return jax.lax.fori_loop(0, jnp.max(sr.short_rows), one,
                                 (o, state))


class LingHybridForCausalLM(SarvamMLAForCausalLM):
    """The served model: eager `forward`, and the engine's two step
    bodies over latent pages and state slabs. The MLA layer's arithmetic
    and walk, the feed-forward, the single tick's and the fused window's
    shells (`_paged_decode_core`, `_paged_decode_fused`) are
    `SarvamMLAForCausalLM`'s; this class gives them its `_paged_core`."""

    # int32 counters the step bodies return: SarvamMLAForCausalLM's (the
    # expert layer's three, the latent walk's four), the step's KDA query
    # rows × layers by form and the chunked runs × layers
    step_counters = SarvamMLAForCausalLM.step_counters + (
        "kda_rows_recurrent", "kda_rows_chunked", "kda_chunk_launches")

    def __init__(self, config):
        nn.Layer.__init__(self)
        self.config = config
        c = config
        self.embed = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                False)
        self.layers = nn.LayerList(
            [LingDecoderLayer(c, i) for i in range(c.num_layers)])
        self.final_norm = _parameter(c, (c.hidden_size,), 0.02, True)
        self.lm_head = _parameter(c, (c.vocab_size, c.hidden_size), 0.02,
                                  False)
        self._mla_layers = [l for l in self.layers if l.mla]

    # ---- KDA: projections, convolution, gates -------------------------

    def _kda_inputs(self, layer, n, tail, slot_ids, pos, sr):
        """Of the normed input n [T, d]: (q k g [T, H, d_k], v [T, H,
        d_v], β, gate [T, H], float32; the slots' new tails). `tail`
        [S, K − 1, 3·H·d_k] the rows before each slot's run that the
        convolution still reads, or None (no cache: every run starts its
        sequence)."""
        c = self.config
        T, H, dk = n.shape[0], c.num_heads, c.head_dim
        hk = c.kda_width
        p = _mm(n, layer.w_qkva._value)
        pre, a = p[:, :3 * hk], p[:, 3 * hk:]
        y, new_tail = short_conv(pre, layer.conv._value, tail, slot_ids,
                                 pos, sr)
        y = jax.nn.silu(y).reshape(T, 3, H, dk)
        q = _l2_norm(y[:, 0]) * dk ** -0.5
        k = _l2_norm(y[:, 1])
        g = c.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(layer.a_log._value)[None, :, None]
            * (a + layer.dt_bias._value[None, :]).reshape(T, H, dk))
        bg = jax.nn.sigmoid(_mm(n, layer.w_bg._value))
        return q, k, y[:, 2], g, bg[:, :H], bg[:, H:], new_tail

    def _kda_out(self, layer, x, o, gate):
        """x + concat(gate_h · RMSNorm(o_h))·W_o, o [T, H, d_v]."""
        c = self.config
        o = _rms_norm(o, layer.o_norm._value, c.rms_norm_eps)
        return x + _mm((o * gate[:, :, None]).reshape(x.shape[0], -1),
                       layer.wo._value)

    def _kda_recurrence(self, state, q, k, v, g, beta, pos, sr):
        """The step's rows through the slots' states, each run by the
        form its length gives it: (o [T, H, d_v], the new state)."""
        return delta_rule_forms(state, q, k, v, g, beta, pos, sr,
                                ("kda_chunk", "kda_recur"))

    # ---- eager forward (no cache) ------------------------------------

    def _forward_one(self, ids):
        c = self.config
        S = ids.shape[0]
        pos = jnp.arange(S)
        tables = _rope_tables(c, pos)
        see = pos[None, :] <= pos[:, None]
        valid = jnp.ones((S,), bool)
        zeros = jnp.zeros((S,), jnp.int32)
        runs = SlotRunLayout(zeros, pos.astype(jnp.int32) + 1, S,
                             delta_rule.CHUNK, 0)     # ONE run
        H, dk = c.num_heads, c.head_dim
        with _scope("embed"):
            x = self.embed._value[ids].astype(jnp.float32)
        for layer in self.layers:
            if layer.mla:
                with _scope("attn"), _scope("attn_mla"):
                    n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
                    q_nope, q_rope = self._queries(layer, n, tables)
                    lat, kr = self._latent_row(layer, n, tables)
                    with _scope("mla_expand"):
                        o = self._expanded_attention(layer, q_nope, q_rope,
                                                     lat, kr, see)
                    gate = jax.nn.sigmoid(_mm(n, layer.wg._value))
                    x = self._attn_out(layer, x, o * gate[:, :, None])
            else:
                with _scope("attn"), _scope("attn_kda"):
                    n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
                    q, k, v, g, beta, gate, _ = self._kda_inputs(
                        layer, n, None, zeros, pos, None)
                    o, _, _ = delta_rule.delta_rule_chunked(
                        jnp.zeros((1, H, dk, dk), jnp.float32), q, k, v, g,
                        beta, runs)
                    x = self._kda_out(layer, x, o, gate)
            x, _ = self._ffn(layer, x, valid)
        return self._head(x)

    # ---- the engine's step bodies -----------------------------------

    def _latent_weight_dtype(self):
        return self._mla_layers[0].w_uk._value.dtype

    def _paged_core(self, tok, pos, slot_ids, write_idx, page_tables,
                    kv_lens, sample_idx, kv, frontier_offset=None,
                    slot_blocks=False):
        """Raw arrays. ONE paged cache kind (the MLA layers' latent
        pool), so write_idx [T] and page_tables [S, MP]; `kv` in layer
        order: a KDA layer's state [S, H, d_k, d_v] float32 and tail [S,
        K − 1, 3·H·d_k], an MLA layer's one latent pool. `slot_blocks`:
        the single tick's rows (a slot's side by side), whose long runs
        take the CHUNKED delta rule and the EXPANDED latent walk; the
        fused window has one row a slot. Returns (logits [S, vocab]
        float32, new kv, counters int32 [10])."""
        c = self.config
        n_slots = page_tables.shape[0]
        valid = kv_lens > 0
        tables = _rope_tables(c, pos)
        kv = list(kv)
        pool0 = kv[sum(1 if l.mla else 2
                       for l in self.layers[:self._mla_layers[0].index])]
        how = self._tick_walk(slot_ids, kv_lens, n_slots, pool0.dtype,
                              slot_blocks)
        with _scope("attn"):
            sr = _SlotRuns(slot_ids, kv_lens, n_slots, slot_blocks)
        with _scope("embed"):
            x = self.embed._value[tok].astype(jnp.float32)
        new_kv = []
        moe = jnp.zeros((3,), jnp.int32)
        at = 0
        for layer in self.layers:
            if layer.mla:
                with _scope("attn"), _scope("attn_mla"):
                    n = _rms_norm(x, layer.attn_norm._value, c.rms_norm_eps)
                    o, pool = self._attend_pages(
                        layer, n, kv[at], tables, write_idx, page_tables,
                        slot_ids, kv_lens, frontier_offset, how)
                    with _scope("mla_out"):
                        gate = jax.nn.sigmoid(_mm(n, layer.wg._value))
                        x = self._attn_out(layer, x, o * gate[:, :, None])
                new_kv.append(pool)
                at += 1
            else:
                with _scope("attn"), _scope("attn_kda"):
                    with _scope("kda_proj"):
                        n = _rms_norm(x, layer.attn_norm._value,
                                      c.rms_norm_eps)
                        q, k, v, g, beta, gate, tail = self._kda_inputs(
                            layer, n, kv[at + 1], slot_ids, pos, sr)
                    o, state = self._kda_recurrence(
                        kv[at], q, k, v, g, beta, pos, sr)
                    with _scope("kda_out"):
                        x = self._kda_out(layer, x, o, gate)
                new_kv += [state, tail]
                at += 2
            x, cnt = self._ffn(layer, x, valid)
            moe = moe + cnt
        # KDA's query rows × layers by form, and its chunked runs
        if sr.runs is None:
            chunked = runs = jnp.zeros((), jnp.int32)
        else:
            chunked = jnp.sum(sr.runs.expanded)
            runs = jnp.sum(sr.runs.run_rows > 0)
        n_mla = len(self._mla_layers)
        counters = jnp.concatenate([
            moe, self._walk_counters(n_mla, slot_ids, kv_lens, n_slots,
                                     frontier_offset, how),
            (c.num_layers - n_mla) * jnp.stack([
                jnp.sum(valid) - chunked, chunked, runs]).astype(jnp.int32)])
        with _scope("lm_head"):
            x = x[sample_idx]
        return self._head(x), new_kv, counters
