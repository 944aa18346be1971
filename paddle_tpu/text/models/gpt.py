"""GPT — decoder-only causal language model, the flagship transformer.

The reference ships GPT through PaddleNLP on top of the fleet TP/PP layers
(reference capability: fleet/layers/mpu/mp_layers.py + the GPT-3 hybrid
configs named in BASELINE.json); here the model is built directly on the
framework's tensor-parallel layers so ONE model definition runs serial,
DP, TP, ZeRO, and sequence-parallel — the mesh axes and PartitionSpecs
decide, not the model code (GSPMD-first design).

TPU-first choices:
- attention runs through F.scaled_dot_product_attention → the Pallas
  flash-attention kernel on TPU (ops/pallas_kernels/flash_attention.py);
- qkv is ONE fused ColumnParallelLinear (3·d_model output, mp-sharded) so
  the MXU sees one big matmul;
- the LM head is tied to the vocab-sharded embedding; the loss is
  ParallelCrossEntropy (vocab-parallel softmax-CE, reference
  c_softmax_with_cross_entropy_op).
"""
import math

import jax

from ... import nn
from ...distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
    shard_activation,
    split_fused_qkv,
)
from ...nn import functional as F
from ...ops import manipulation as manip

__all__ = [
    "GPTConfig", "GPTDecoderLayer", "GPTModel", "GPTForCausalLM",
    "GPTPretrainingCriterion", "gpt_tiny", "gpt_small", "gpt_medium",
    "gpt_1p3b", "sample_tokens",
]


# Named scopes (`jax.named_scope`, metadata only) put ONE small
# vocabulary into every operation's `op_name`, shared by the training
# and the serving forward, so a device profile can be read by block:
# embed, attn, mlp, norm (nested inside attn/mlp, and ln_f), lm_head,
# loss, sample — plus `optimizer` in jit.TrainStep. Backward operations
# inherit the word through `transpose(jvp(...))`. The catalogue and the
# metrics that read it: docs/OBSERVABILITY.md "Spans and scopes".
_scope = jax.named_scope


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size=None, max_seq_len=1024,
                 dropout=0.0, tie_embeddings=True, recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_size = ffn_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.tie_embeddings = tie_embeddings
        # per-LAYER activation recompute for the serial/dp path (the
        # big-model-on-few-chips lever; PP has its own ring-buffer remat).
        # False | True (keep nothing) | policy name ('dots_saveable', ...)
        self.recompute = recompute

    def cache_kinds(self):
        """What the serving engine asks (serving_protocol.py): ONE kind
        of K/V cache, every layer's, page-major pools."""
        from .serving_protocol import CacheKind

        return [CacheKind("kv", tuple(range(self.num_layers)),
                          self.num_heads,
                          self.hidden_size // self.num_heads, None,
                          False)]


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                     num_heads=4, max_seq_len=256, **kw)


def gpt_small(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=1024, **kw)


def gpt_medium(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, max_seq_len=1024, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_heads=32, max_seq_len=2048, **kw)


class GPTDecoderLayer(nn.Layer):
    """Pre-LN decoder block: LN → fused-qkv attn → residual, LN → MLP →
    residual. Column/Row parallel pairs keep the intermediate activations
    mp-sharded with zero manual collectives."""

    def __init__(self, config):
        super().__init__()
        d = config.hidden_size
        self.nh = config.num_heads
        self.hd = d // config.num_heads
        self.ln1 = nn.LayerNorm(d)
        self.qkv = ColumnParallelLinear(d, 3 * d, gather_output=False)
        self.proj = RowParallelLinear(d, d, input_is_parallel=True)
        self.ln2 = nn.LayerNorm(d)
        self.fc1 = ColumnParallelLinear(d, config.ffn_size,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(config.ffn_size, d,
                                     input_is_parallel=True)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        b = x.shape[0]
        s = x.shape[1]
        with _scope("attn"):
            with _scope("norm"):
                h = self.ln1(x)
            qkv = self.qkv(h)  # [b, s, 3d] (mp-sharded last dim)
            q, k, v = split_fused_qkv(qkv, b, s, self.nh, self.hd)
            attn = F.scaled_dot_product_attention(q, k, v,
                                                  is_causal=True)
            attn = manip.reshape(attn, [b, s, self.nh * self.hd])
            x = x + self.dropout(self.proj(attn))
        with _scope("mlp"):
            with _scope("norm"):
                h = self.ln2(x)
            x = x + self.dropout(self.fc2(F.gelu(self.fc1(h))))
        return x


class GPTModel(nn.Layer):
    """Token + position embeddings, N decoder layers, final LN."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.wte = VocabParallelEmbedding(config.vocab_size,
                                          config.hidden_size)
        self.wpe = nn.Embedding(config.max_seq_len, config.hidden_size)
        self.drop = nn.Dropout(config.dropout)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size)

    def forward(self, input_ids):
        s = input_ids.shape[1]
        from ...ops.creation import arange

        with _scope("embed"):
            pos = arange(0, s, dtype="int64")
            x = self.wte(input_ids) + self.wpe(pos)
            x = self.drop(x)
            x = shard_activation(x, "dp", "sp", None)
        rc = self.config.recompute
        if rc:
            from ...distributed.fleet.recompute import recompute as _rc

            # checkpoint_policy() normalizes True -> keep-nothing
            for layer in self.layers:
                x = _rc(layer, x, policy=rc)
        else:
            for layer in self.layers:
                x = layer(x)
        with _scope("norm"):
            return self.ln_f(x)


# ------------------------------------------------------------ generation

def _cached_attention(q, k_new, v_new, cache_k, cache_v, index,
                      pad_lens=None):
    """Write k/v into the static cache at `index` and attend q against
    the valid prefix (TPU decode pattern: fixed-size buffers +
    dynamic_update_slice, no shape changes step to step).

    pad_lens: optional [b] int32 LEFT-pad counts per example (ragged
    prompts padded on the left so every row's generation frontier is
    aligned); columns < pad_lens[b] are masked out."""
    import math as _math

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ...ops._helpers import apply_jfn

    def jfn(qv, kn, vn, ck, cv, idx, *rest):
        idx = idx.astype(jnp.int32)
        zero = jnp.asarray(0, idx.dtype)  # all start indices same dtype
        starts = (zero, idx, zero, zero)
        ck = lax.dynamic_update_slice(ck, kn.astype(ck.dtype), starts)
        cv = lax.dynamic_update_slice(cv, vn.astype(cv.dtype), starts)
        qt = jnp.swapaxes(qv, 1, 2)
        kt = jnp.swapaxes(ck, 1, 2)
        vt = jnp.swapaxes(cv, 1, 2)
        d = qv.shape[-1]
        sc = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / _math.sqrt(d)
        s_new, L = qv.shape[1], ck.shape[1]
        allowed = (jnp.arange(L)[None, :]
                   <= (idx + jnp.arange(s_new))[:, None])[None, None]
        if rest:  # left-pad mask: [b,1,1,L] AND the causal window
            pads = rest[0].astype(jnp.int32)
            allowed = jnp.logical_and(
                allowed,
                (jnp.arange(L)[None, :]
                 >= pads[:, None])[:, None, None, :])
        sc = jnp.where(allowed, sc, jnp.float32(-1e30))
        # softmax statistics in f32 even for bf16 caches
        w = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(
            vt.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", w, vt).astype(qv.dtype)
        return jnp.swapaxes(out, 1, 2), ck, cv

    tensors = [q, k_new, v_new, cache_k, cache_v, index]
    if pad_lens is not None:
        tensors.append(pad_lens)
    return apply_jfn("cached_attention", jfn, *tensors)


def _layer_forward_cached(layer, x, cache, index, pad_lens=None):
    """Functional: returns (x_out, new_cache) — no mutation, so the whole
    decode step can be captured by to_static and dispatched as ONE
    compiled program per token."""
    b, s = x.shape[0], x.shape[1]
    with _scope("attn"):
        with _scope("norm"):
            h = layer.ln1(x)
        qkv = layer.qkv(h)
        q, k, v = split_fused_qkv(qkv, b, s, layer.nh, layer.hd)
        attn, ck, cv = _cached_attention(q, k, v, cache["k"], cache["v"],
                                         index, pad_lens=pad_lens)
        attn = manip.reshape(attn, [b, s, layer.nh * layer.hd])
        x = x + layer.proj(attn)
    with _scope("mlp"):
        with _scope("norm"):
            h = layer.ln2(x)
        return x + layer.fc2(F.gelu(layer.fc1(h))), {"k": ck, "v": cv}


def _paged_cache_write(k_pool, v_pool, k_new, v_new, write_idx):
    """Scatter per-token k/v rows into the paged KV pool.

    k_pool/v_pool [num_pages, page_size, heads, head_dim]; k_new/v_new
    [T, heads, head_dim]; write_idx [T] int32 flat destination rows
    (page_id * page_size + offset). Page 0 is the engine's trash page:
    padding tokens all target row 0, where collisions are harmless —
    trash content is never attended with nonzero weight."""
    import jax.numpy as jnp

    from ...ops._helpers import apply_jfn

    def jfn(kp, vp, kn, vn, idx):
        shape = kp.shape
        flat = (shape[0] * shape[1],) + shape[2:]
        idx = idx.astype(jnp.int32)
        kp2 = kp.reshape(flat).at[idx].set(
            kn.astype(kp.dtype)).reshape(shape)
        vp2 = vp.reshape(flat).at[idx].set(
            vn.astype(vp.dtype)).reshape(shape)
        return kp2, vp2

    return apply_jfn("paged_cache_write", jfn, k_pool, v_pool, k_new,
                     v_new, write_idx)


def _paged_cache_write_quant(k_pool, v_pool, k_scales, v_scales, k_new,
                             v_new, write_idx):
    """Int8/int4 variant of `_paged_cache_write`: each incoming k/v row
    is quantized per (token, head) absmax (quantization.runtime
    `quantize_kv_rows` / `quantize_kv_rows_int4`) and scattered into
    the quantized pools, with its fp32 scale scattered into the
    page-shaped scale planes at the same flat row. A row is quantized
    exactly once with its own scale, so later writes to the same page
    never invalidate earlier tokens.

    The pool's last dim picks the codec: head_dim → int8 rows,
    head_dim/2 → PACKED int4 (two nibbles per byte, `kv_dtype="int4"`
    — the shape mismatch is unambiguous, so the compiled step needs no
    extra bits argument threaded through)."""
    import jax.numpy as jnp

    from ...ops._helpers import apply_jfn
    from ...quantization import runtime as _qrt

    packed4 = int(k_pool.shape[-1]) * 2 == int(k_new.shape[-1])
    quant_rows = (_qrt.quantize_kv_rows_int4 if packed4
                  else _qrt.quantize_kv_rows)

    def jfn(kp, vp, ks, vs, kn, vn, idx):
        shape = kp.shape
        flat = (shape[0] * shape[1],) + shape[2:]
        sflat = (shape[0] * shape[1],) + ks.shape[2:]
        idx = idx.astype(jnp.int32)
        kq, kscale = quant_rows(kn)
        vq, vscale = quant_rows(vn)
        kp2 = kp.reshape(flat).at[idx].set(kq).reshape(shape)
        vp2 = vp.reshape(flat).at[idx].set(vq).reshape(shape)
        ks2 = ks.reshape(sflat).at[idx].set(kscale).reshape(ks.shape)
        vs2 = vs.reshape(sflat).at[idx].set(vscale).reshape(vs.shape)
        return kp2, vp2, ks2, vs2

    return apply_jfn("paged_cache_write_int4" if packed4
                     else "paged_cache_write_int8", jfn, k_pool, v_pool,
                     k_scales, v_scales, k_new, v_new, write_idx)


def _layer_forward_paged(layer, x, cache_k, cache_v, write_idx,
                         page_tables, slot_ids, kv_lens,
                         k_scales=None, v_scales=None,
                         frontier_offset=None, max_q_per_slot=None):
    """Paged-cache decoder block over the FLAT token layout [1, T, d] —
    the continuous-batching analog of `_layer_forward_cached`: write the
    step's k/v into pool pages, then ragged paged attention against each
    token's own sequence prefix. Functional (returns new pools), so the
    whole engine step compiles to ONE program.

    With `k_scales`/`v_scales` (int8 pools) the write quantizes each row
    and attention dequantizes on gather; returns the new scale planes
    after the pools. `frontier_offset` is the fused-decode window's
    per-iteration scalar: kv_lens stays the window-invariant BASE
    length and attention adds the offset to every nonzero row.
    `max_q_per_slot` is the speculative-verify grid hint: a caller that
    packs at most that many query tokens per slot (the verify step:
    exactly k+1) lets attention size its slot grid [S, k+1] instead of
    the worst-case [S, T]."""
    T = x.shape[1]
    with _scope("attn"):
        with _scope("norm"):
            h = layer.ln1(x)
        qkv = layer.qkv(h)
        q, k, v = split_fused_qkv(qkv, 1, T, layer.nh, layer.hd)
        q = manip.reshape(q, [T, layer.nh, layer.hd])
        k = manip.reshape(k, [T, layer.nh, layer.hd])
        v = manip.reshape(v, [T, layer.nh, layer.hd])
        if k_scales is None:
            ck, cv = _paged_cache_write(cache_k, cache_v, k, v,
                                        write_idx)
            attn = F.paged_attention(q, ck, cv, page_tables, slot_ids,
                                     kv_lens,
                                     frontier_offset=frontier_offset,
                                     max_tokens_per_slot=max_q_per_slot)
            cks = cvs = None
        else:
            ck, cv, cks, cvs = _paged_cache_write_quant(
                cache_k, cache_v, k_scales, v_scales, k, v, write_idx)
            attn = F.paged_attention(q, ck, cv, page_tables, slot_ids,
                                     kv_lens, k_scales=cks, v_scales=cvs,
                                     frontier_offset=frontier_offset,
                                     max_tokens_per_slot=max_q_per_slot)
        attn = manip.reshape(attn, [1, T, layer.nh * layer.hd])
        x = x + layer.proj(attn)
    with _scope("mlp"):
        with _scope("norm"):
            h = layer.ln2(x)
        out = x + layer.fc2(F.gelu(layer.fc1(h)))
    if k_scales is None:
        return out, ck, cv
    return out, ck, cv, cks, cvs


def sample_tokens(logits, temps, top_ps, streams, positions, key,
                  allowed=None):
    """Greedy / temperature / top-p next-token sampler — pure jnp,
    shared by the engine's host tick (first tokens after prefill) and
    the fused decode window's in-executable scan, so both paths pick
    identical tokens from identical logits.

    logits [S, vocab] f32; temps/top_ps [S] f32; streams/positions [S]
    int32; key uint32[2] (the engine-owned PRNG key, threaded as a step
    ARGUMENT so reseeding never recompiles). allowed (optional)
    [S, vocab] bool — the structured-decoding grammar mask: False
    entries are excluded BEFORE both the greedy argmax and the top-p
    truncation, so a constrained row's pick is always grammar-legal
    under either decode mode. An all-True row is a value-level no-op:
    unconstrained rows pick bit-identically to `allowed=None` (the
    engine's mask-identity contract rides on this).

    Rows with temps <= 0 take the greedy argmax (the generate()/engine
    default pick, bit-identical to the host argmax path). Sampling rows
    draw from the temperature-scaled, top-p-truncated distribution with
    a per-row key `fold_in(fold_in(key, stream), position)` — the draw
    depends ONLY on (engine seed, request stream, token position), so a
    request's sampled continuation is invariant to the window size k,
    to batch composition, and to preemption replays (the same
    determinism contract greedy decode gets for free). The grammar mask
    reshapes the distribution but not the key: constrained +
    speculative composes losslessly because acceptance is exact-match
    against this same keyed pick, masked or not."""
    import jax.numpy as jnp

    with _scope("sample"):
        if allowed is not None:
            logits = jnp.where(allowed, logits, jnp.float32(-1e30))
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def drawn(_):
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            # top-p: keep the smallest prefix of the descending-prob
            # list whose EXCLUSIVE cumulative mass is < top_p (always
            # keeps the top-1)
            srt = jnp.sort(scaled, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = (cum - probs) < top_ps[:, None]
            thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
            masked = jnp.where(scaled >= thresh[:, None], scaled,
                               jnp.float32(-1e30))
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(
                    jax.random.fold_in(key, s), p)
            )(streams.astype(jnp.uint32), positions.astype(jnp.uint32))
            pick = jax.vmap(jax.random.categorical)(keys, masked)
            return jnp.where(temps > 0, pick, greedy).astype(jnp.int32)

        # all-greedy batches skip the whole sort/cumsum/draw branch at
        # RUN time (lax.cond executes one side): the fused scan calls
        # this every iteration, and a vocab-wide sort per tick would
        # tax exactly the dispatch-bound serving the fused window
        # exists to speed up
        return jax.lax.cond(jnp.any(temps > 0), drawn,
                            lambda _: greedy, None)


def grammar_allowed(gmask, gstate, vocab):
    """Expand grammar-arena mask bitsets to a boolean logits mask:
    gmask [G, ceil(vocab/32)] uint32, gstate [R] int32 (arena-absolute
    DFA state per row) → [R, vocab] bool for `sample_tokens(allowed=)`.
    Pure jnp — runs inside the fused/verify executables
    (inference/structured has the arena contract: row 0 is the
    mask-identity every unconstrained row carries)."""
    import jax.numpy as jnp

    words = gmask[gstate]                       # [R, W] uint32
    v = jnp.arange(int(vocab), dtype=jnp.int32)
    bits = words[:, v // 32] >> (v % 32).astype(jnp.uint32)
    return (bits & jnp.uint32(1)).astype(jnp.bool_)


class GPTGenerationMixin:
    """Greedy / temperature / top-k decoding with a static KV cache
    (reference capability: PaddleNLP generate() on GPT; here designed
    for XLA — fixed-length cache buffers, dynamic_update_slice writes,
    every step the same compiled shape)."""

    def _forward_cached(self, input_ids, caches, index, pad_lens=None):
        from ...ops.creation import arange

        model = self.gpt
        s = input_ids.shape[1]
        with _scope("embed"):
            pos = arange(0, s, dtype="int64") + index
            if pad_lens is not None:
                # left-padded rows start their position ids AFTER the
                # pads (clamped at 0 for the pad slots themselves, which
                # attention masks out anyway)
                pos = (pos.unsqueeze(0) - pad_lens.unsqueeze(1)).clip(
                    0, self.config.max_seq_len - 1)
            x = model.wte(input_ids) + model.wpe(pos)
        new_caches = []
        for layer, cache in zip(model.layers, caches):
            x, nc = _layer_forward_cached(layer, x, cache, index,
                                          pad_lens=pad_lens)
            new_caches.append(nc)
        with _scope("norm"):
            x = model.ln_f(x)
        return self._logits_from_hidden(x, shard=False), new_caches

    def _decode_core(self, tok, idx, pad_lens, kv):
        L = self.config.num_layers
        caches = [{"k": kv[2 * i], "v": kv[2 * i + 1]} for i in range(L)]
        logits, new = self._forward_cached(tok, caches, idx,
                                           pad_lens=pad_lens)
        flat = []
        for c in new:
            flat += [c["k"], c["v"]]
        return (logits, *flat)

    # two impls so to_static sees two distinct signatures (the padded
    # step threads pad_lens as a traced argument)
    def _decode_step_impl(self, tok, idx, *kv):
        return self._decode_core(tok, idx, None, kv)

    def _decode_step_padded_impl(self, tok, idx, pad_lens, *kv):
        return self._decode_core(tok, idx, pad_lens, kv)

    def _make_step(self, padded=False):
        """ONE to_static-wrapped step per INSTANCE: the trace cache
        persists across generate() calls but dies with the model (a
        class-level cache would pin every instance's weights forever —
        the traced closures capture them). Invoked as a bound Layer
        method, so weights are threaded as jit ARGUMENTS, not baked
        into each executable as constants."""
        key = "_decode_step_static_padded" if padded else \
            "_decode_step_static"
        if key not in self.__dict__:
            from ... import jit as jit_mod

            impl = (type(self)._decode_step_padded_impl if padded
                    else type(self)._decode_step_impl)
            self.__dict__[key] = jit_mod.to_static(impl)
        return self.__dict__[key].__get__(self, type(self))

    # ---- paged-cache ragged decode (continuous-batching serving) ----

    def _paged_decode_core(self, tok, pos_ids, slot_ids, write_idx,
                           page_tables, kv_lens, sample_idx, kv,
                           kv_scales=None, frontier_offset=None,
                           max_q_per_slot=None):
        """One ragged engine step over flat tokens: tok/pos_ids/slot_ids/
        write_idx/kv_lens [T], page_tables [S, MP], sample_idx [S] (the
        flat row holding each slot's sampling frontier; stale slots
        point anywhere — their logits are ignored), kv = 2·num_layers
        pool arrays. Returns (logits [1, S, vocab], *new_pools).
        The vocab head — the step's single biggest matmul — runs ONLY
        on the S gathered frontier rows, never on prefill tokens.
        Compiled ONCE by inference/llm_engine.py's _CompiledPagedStep —
        the TrainStep-style executable behind every scheduler tick
        (weights as jit arguments, pools donated).

        kv_scales: for int8 pools (kv_dtype="int8"), the 2·num_layers
        page-shaped fp32 scale planes; the new planes are returned
        AFTER the new pools: (logits, *new_pools, *new_scales).

        frontier_offset: optional scalar added to every NONZERO kv_len
        (the fused decode window passes iteration i here so the base
        kv_lens vector stays window-invariant).

        max_q_per_slot: the speculative-verify grid hint (see
        `_layer_forward_paged`) — the caller guarantees no slot owns
        more than this many flat tokens this step."""
        model = self.gpt
        with _scope("embed"):
            x = model.wte(tok.unsqueeze(0)) + model.wpe(pos_ids)
        flat, scale_flat = [], []
        for i, layer in enumerate(model.layers):
            if kv_scales is None:
                x, ck, cv = _layer_forward_paged(
                    layer, x, kv[2 * i], kv[2 * i + 1], write_idx,
                    page_tables, slot_ids, kv_lens,
                    frontier_offset=frontier_offset,
                    max_q_per_slot=max_q_per_slot)
            else:
                x, ck, cv, cks, cvs = _layer_forward_paged(
                    layer, x, kv[2 * i], kv[2 * i + 1], write_idx,
                    page_tables, slot_ids, kv_lens,
                    k_scales=kv_scales[2 * i],
                    v_scales=kv_scales[2 * i + 1],
                    frontier_offset=frontier_offset,
                    max_q_per_slot=max_q_per_slot)
                scale_flat += [cks, cvs]
            flat += [ck, cv]
        with _scope("norm"):
            x = model.ln_f(x)
        with _scope("lm_head"):
            x = manip.gather(x, sample_idx, axis=1)  # [1, S, d] frontiers
        return (self._logits_from_hidden(x, shard=False), *flat,
                *scale_flat)

    def _paged_decode_fused(self, k, page_size, tok0, pos0, rem, fin0,
                            eos_ids, temps, top_ps, streams,
                            page_tables, kv, kv_scales, key,
                            lag=None, frontier=None, gstate0=None,
                            gtrans=None, gmask=None):
        """k decode ticks fused into ONE `lax.scan` over the paged step
        — the body of the engine's fused executable (`_CompiledFusedStep`
        in inference/llm_engine.py): per iteration, write the frontier
        token's KV, ragged paged attention over each slot's own prefix,
        vocab head on the S frontier rows, and sampling (greedy /
        temperature / top-p via `sample_tokens`) IN-EXECUTABLE, so the
        host syncs once per k tokens instead of once per token.

        Raw jax values in and out (the jit wrapper owns the Tensor
        boundary): tok0/pos0/rem/streams [S] int32 (frontier token, its
        write position, tokens the row may still emit, sampling stream
        id), fin0 [S] bool (True = empty/ignored slot), eos_ids [S]
        int32 (-1 = no eos), temps/top_ps [S] f32, page_tables [S, MP],
        kv / kv_scales the pool pytree, key the engine PRNG key.

        In-executable EOS + budget masking: a row that samples its eos
        or exhausts `rem` mid-window flips finished — later iterations
        write its KV to the trash row, skip its attention (kv_len 0),
        and emit the pad sentinel -1 — no host sync. Page capacity for
        every live iteration is reserved by the engine BEFORE dispatch
        (`rem` is pre-clamped to the reserved window), so in-scan write
        indices never leave the request's own pages. Returns
        (emitted [k, S] int32, new_kv, new_scales) — the key passes
        through the donated pytree untouched (sampling folds per-row
        (stream, position) into it instead of splitting, which is what
        makes the draw window-size-invariant).

        lag/frontier (speculative draft PROPOSE mode — both [S] or
        both None): a row with lag 1 starts the scan ONE position
        early at pos0-1 — `tok0` then carries the token AT pos0-1 —
        so its missing draft-KV row (the previous window's k-th
        accepted token, which the propose scan never wrote) is
        replayed inside this same dispatch instead of costing a
        separate catch-up tick; iteration 0's carry is FORCED to
        `frontier` (the already-known token at pos0) for lag rows, so
        the later proposals condition on the true sequence, not on
        the draft's guess of a token the engine already holds.

        gstate0/gtrans/gmask (structured decoding — all three or
        none): gstate0 [S] int32 arena-absolute grammar DFA states,
        gtrans [G, vocab] int32 / gmask [G, ceil(vocab/32)] uint32 the
        engine's grammar-arena tables. The DFA state rides the scan
        carry like the token does: each iteration masks the live rows'
        logits through `grammar_allowed` BEFORE sampling and advances
        `gs2 = gtrans[gs, nxt]`. Arena row 0 is the mask identity, so
        unconstrained rows sample bit-identically — and a whole-window
        `lax.cond` on `any(gstate0 > 0)` skips the gather/expand
        entirely when no constrained row is resident (same discipline
        as the all-greedy fast path in `sample_tokens`). The tables
        are plain arguments at engine-static shapes: grammar churn is
        a value swap, never a retrace."""
        import jax
        import jax.numpy as jnp

        from ...tensor_core import Tensor

        S = tok0.shape[0]
        sl = jnp.arange(S, dtype=jnp.int32)
        pt = jnp.asarray(page_tables, jnp.int32)
        start = pos0 if lag is None else pos0 - lag
        klen0 = start + 1
        pad = jnp.asarray(-1, jnp.int32)
        structured = gtrans is not None
        if structured:
            any_g = jnp.any(gstate0 > 0)

        def t(v):
            return Tensor(v, stop_gradient=True)

        def body(carry, i):
            if structured:
                tok, fin, gs, kv_c, kvs_c = carry
            else:
                tok, fin, kv_c, kvs_c = carry
            live = ~fin
            tok_in = jnp.where(live, tok, 0)
            pos_in = jnp.where(live, start + i, 0)
            klen = jnp.where(live, klen0, 0)  # + i rides the offset
            page = pt[sl, pos_in // page_size]
            widx = jnp.where(live,
                             page * page_size + pos_in % page_size, 0)
            out = self._paged_decode_core(
                t(tok_in), t(pos_in), t(sl), t(widx), t(pt), t(klen),
                t(sl), [t(v) for v in kv_c],
                kv_scales=([t(s) for s in kvs_c] if kvs_c else None),
                frontier_offset=t(i))
            logits, *new = out
            n = len(kv_c)
            kv2 = [x._value for x in new[:n]]
            kvs2 = [x._value for x in new[n:]]
            with _scope("sample"):
                lv = logits._value[0].astype(jnp.float32)  # [S, vocab]
                allowed = None
                if structured:
                    V = lv.shape[1]
                    allowed = jax.lax.cond(
                        any_g,
                        lambda s: grammar_allowed(gmask, s, V),
                        lambda s: jnp.ones((S, V), jnp.bool_), gs)
                nxt = sample_tokens(lv, temps, top_ps, streams,
                                    pos_in + 1, key, allowed=allowed)
                if lag is not None:
                    # propose mode: a lag row's iteration-0 output IS
                    # the already-known frontier token — force it so
                    # later proposals condition on the true sequence
                    nxt = jnp.where((i == 0) & (lag > 0), frontier, nxt)
                emit = jnp.where(live, nxt, pad)
                fin2 = (fin | (live & (eos_ids >= 0) & (nxt == eos_ids))
                        | (live & (i + 1 >= rem)))
                tok2 = jnp.where(live, nxt, tok)
                if structured:
                    gs2 = jnp.where(live, gtrans[gs, nxt], gs)
                    return (tok2, fin2, gs2, kv2, kvs2), emit
            return (tok2, fin2, kv2, kvs2), emit

        init = ((tok0, fin0, gstate0, list(kv), list(kv_scales or []))
                if structured
                else (tok0, fin0, list(kv), list(kv_scales or [])))
        carry_f, emits = jax.lax.scan(
            body, init, jnp.arange(int(k), dtype=jnp.int32))
        kv_f, kvs_f = carry_f[-2], carry_f[-1]
        return emits, kv_f, kvs_f

    def _paged_verify_fused(self, k, page_size, tok0, pos0, drafts,
                            width, rem, fin0, eos_ids, temps, top_ps,
                            streams, page_tables, kv, kv_scales, key,
                            gstate0=None, gtrans=None, gmask=None):
        """Speculative-decoding verify: score ALL k+1 positions of every
        slot — the real frontier token plus k draft proposals — in ONE
        ragged batched step, then accept the longest prefix of drafts
        that matches the target model's own keyed picks
        (inference/speculative.py has the window orchestration;
        docs/SERVING.md "Speculative decoding" the contract).

        Lossless by construction: `sample_tokens` keys every draw on
        (engine seed, stream, position) only, so the target pick at a
        position is a deterministic function of the accepted prefix —
        greedy AND sampled outputs are token-identical to the
        non-speculative engine, and invariant to spec_k. Acceptance is
        therefore exact-match against the target pick (for greedy rows
        that IS longest-prefix argmax match; for sampled rows the
        rejection test degenerates to equality because the keyed
        categorical draw is the target sample itself — couple the draft
        to the same key and agreement is high whenever the
        distributions are close).

        Raw jax values in and out (the jit wrapper in speculative.py
        owns the Tensor boundary): tok0/pos0 [S] int32 (frontier token
        + its write position), drafts [S, k] int32 (draft proposals —
        entries at or past `width` are ignored), width [S] int32
        (drafts actually processed this window: positions
        pos0+1..pos0+width get KV written; pre-clamped by the engine to
        the reserved pages), rem [S] int32 (emit budget: at most this
        many tokens may be emitted), fin0 [S] bool (True = dead slot),
        eos_ids/temps/top_ps/streams [S], page_tables [S, MP], kv /
        kv_scales the pool pytree, key the engine PRNG key (passes
        through untouched — same contract as the fused scan).

        Flat layout is slot-major [S*(k+1)]: row s*(k+1)+j carries the
        token at position pos0[s]+j with kv_len pos0[s]+j+1, so ragged
        paged attention lets every draft attend to the earlier drafts
        written in this same dispatch and never to later ones. Invalid
        rows (dead slots, j > width) write the trash page at kv_len 0.
        Rejected-draft KV rows stay in the pool as stale garbage past
        the accepted frontier — never attended (kv_len masks them) and
        overwritten by position when the real tokens arrive: rollback
        is positional, no cleanup pass (the draft pool relies on the
        same property — tests pin it).

        gstate0/gtrans/gmask (structured decoding — all three or
        none): same arena tables the fused scan threads. The k+1
        per-position DFA states are chained HYPOTHETICALLY through the
        draft tokens (`st_{j+1} = gtrans[st_j, drafts[:, j]]` — a
        static k-step chain, no scan) and each flat row's logits are
        masked through `grammar_allowed` before the keyed pick.
        Lossless composition falls out: up to the first rejected
        draft the hypothetical states ARE the true states, so every
        accepted pick saw exactly the mask the non-speculative fused
        scan would have applied; states past the first mismatch are
        garbage but their picks are never emitted (acceptance is the
        exact-match prefix). Arena row 0 keeps unconstrained rows
        bit-identical, and the whole-window `lax.cond` on
        `any(gstate0 > 0)` skips the expansion when no constrained
        row is resident.

        Returns (emits [k+1, S] int32, new_kv, new_scales): column s
        holds the accepted target picks — between 1 and k+1 tokens —
        then -1 padding; EOS and budget masking applied in-executable
        (the emitted eos is kept, nothing after it)."""
        import jax
        import jax.numpy as jnp

        from ...tensor_core import Tensor

        S = tok0.shape[0]
        Q = int(k) + 1
        T = S * Q
        live = ~fin0
        j = jnp.arange(Q, dtype=jnp.int32)
        pt = jnp.asarray(page_tables, jnp.int32)
        drafts = drafts.astype(jnp.int32)
        tok_mat = jnp.concatenate([tok0[:, None], drafts], axis=1)
        valid = live[:, None] & (j[None, :] <= width[:, None])  # [S, Q]
        pos_mat = pos0[:, None] + j[None, :]
        sid = jnp.repeat(jnp.arange(S, dtype=jnp.int32), Q)
        tokf = jnp.where(valid, tok_mat, 0).reshape(T)
        posf = jnp.where(valid, pos_mat, 0).reshape(T)
        validf = valid.reshape(T)
        page = pt[sid, posf // page_size]
        widx = jnp.where(validf,
                         page * page_size + posf % page_size, 0)
        klen = jnp.where(validf, posf + 1, 0)

        def t(v):
            return Tensor(v, stop_gradient=True)

        out = self._paged_decode_core(
            t(tokf), t(posf), t(sid), t(widx), t(pt), t(klen),
            t(jnp.arange(T, dtype=jnp.int32)), [t(v) for v in kv],
            kv_scales=([t(s) for s in kv_scales] if kv_scales
                       else None),
            max_q_per_slot=Q)
        logits, *new = out
        n = len(kv)
        kv2 = [x._value for x in new[:n]]
        kvs2 = [x._value for x in new[n:]]
        lv = logits._value[0].astype(jnp.float32)       # [T, vocab]
        allowed = None
        if gtrans is not None:
            # hypothetical DFA state per (slot, position): chain the
            # draft tokens through the arena table (static k steps)
            sts = [gstate0]
            for jj in range(int(k)):
                sts.append(gtrans[sts[-1], drafts[:, jj]])
            st_flat = jnp.stack(sts, axis=1).reshape(T)
            V = lv.shape[1]
            allowed = jax.lax.cond(
                jnp.any(gstate0 > 0),
                lambda s: grammar_allowed(gmask, s, V),
                lambda s: jnp.ones((T, V), jnp.bool_), st_flat)
        picks = sample_tokens(
            lv, jnp.repeat(temps, Q), jnp.repeat(top_ps, Q),
            jnp.repeat(streams, Q), posf + 1, key,
            allowed=allowed).reshape(S, Q)
        # longest matching draft prefix, clamped to the window width
        match = (drafts == picks[:, :k]) & (
            jnp.arange(int(k), dtype=jnp.int32)[None, :]
            < width[:, None])
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
        a = jnp.sum(acc, axis=1)                        # [S] accepted
        n_emit = jnp.where(live, jnp.minimum(a + 1, rem), 0)
        # in-executable EOS masking: the emitted eos is kept, every
        # later pick in the window is suppressed (exclusive cumsum)
        is_eos = ((eos_ids[:, None] >= 0)
                  & (picks == eos_ids[:, None])).astype(jnp.int32)
        eos_before = jnp.cumsum(is_eos, axis=1) - is_eos
        emit_mask = (j[None, :] < n_emit[:, None]) & (eos_before == 0)
        emits = jnp.where(emit_mask, picks,
                          jnp.asarray(-1, jnp.int32))
        return jnp.swapaxes(emits, 0, 1), kv2, kvs2     # [Q, S]

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, do_sample=False, attention_mask=None,
                 eos_token_id=None, pad_token_id=None):
        """input_ids [b, prompt] → [b, min(prompt + max_new_tokens,
        max_seq_len)].

        attention_mask: optional [b, prompt] keep-mask for RAGGED
        prompts, LEFT-padded (zeros first — every row's last prompt
        token sits at the same column, so one uniform decode loop
        serves the whole batch); pad columns are masked out of
        attention and position ids start after the pads.

        eos_token_id: optional early-stop contract (shared with the
        continuous-batching engine, inference/llm_engine.py): a row
        that GENERATES eos is finished — it emits `pad_token_id`
        (default: eos_token_id) for every later step instead of fresh
        tokens, and the loop exits as soon as every row is finished, so
        the result can be shorter than max_new_tokens. Prompt tokens
        never count as eos. NOTE: the all-finished check syncs one bool
        per step, trading the decode loop's async dispatch for early
        exit — only pay it when stopping is actually wanted.
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ... import to_tensor
        from ...autograd import no_grad
        from ...core import rng as rng_mod
        from ...tensor_core import Tensor

        cfg = self.config
        b, prompt = int(input_ids.shape[0]), int(input_ids.shape[1])
        pad_lens = None
        if attention_mask is not None:
            mask_np = np.asarray(attention_mask._value if isinstance(
                attention_mask, Tensor) else attention_mask)
            if mask_np.shape != (b, prompt):
                raise ValueError(
                    f"attention_mask shape {mask_np.shape} != "
                    f"{(b, prompt)}")
            pads_np = (mask_np == 0).sum(axis=1)
            # generate() is a host loop, so left-contiguity is checkable
            # eagerly — reject ambiguous (non-left-padded) masks
            expect = (np.arange(prompt)[None, :] >= pads_np[:, None])
            if not np.array_equal(mask_np != 0, expect):
                raise ValueError(
                    "generate() requires LEFT-padded prompts: "
                    "attention_mask must be 0s followed by 1s per row")
            if (pads_np >= prompt).any():
                raise ValueError(
                    "attention_mask has an all-zero row (empty prompt): "
                    "every example needs at least one real token")
            if pads_np.any():
                pad_lens = to_tensor(pads_np.astype(np.int32))
        if prompt > cfg.max_seq_len:
            raise ValueError(
                f"prompt length {prompt} exceeds max_seq_len "
                f"{cfg.max_seq_len}")
        total = min(prompt + max_new_tokens, cfg.max_seq_len)
        if total <= prompt:  # no budget: nothing to generate
            return Tensor(input_ids._value.astype(jnp.int64),
                          stop_gradient=True)
        # bucket the cache length so different max_new_tokens reuse the
        # SAME compiled decode program (each distinct shape is a fresh
        # XLA compile)
        cache_len = min(-(-total // 128) * 128, cfg.max_seq_len)
        nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads

        def pick(logits_row):
            lv = logits_row._value[:, -1, :].astype(jnp.float32)
            if not do_sample or temperature == 0:
                return jnp.argmax(lv, axis=-1)
            lv = lv / max(temperature, 1e-6)
            if top_k is not None:
                k_eff = min(int(top_k), lv.shape[-1])
                kth = jnp.sort(lv, axis=-1)[:, -k_eff][:, None]
                lv = jnp.where(lv < kth, -1e30, lv)
            return jax.random.categorical(rng_mod.next_key(), lv, axis=-1)

        with no_grad():
            # cache in the model's compute dtype: decode is HBM-bound,
            # an fp32 cache for a bf16 model doubles the traffic
            cache_dt = self.gpt.wte.weight._value.dtype
            flat_kv = []
            for _ in range(cfg.num_layers):
                flat_kv += [
                    to_tensor(jnp.zeros((b, cache_len, nh, hd),
                                        cache_dt)),
                    to_tensor(jnp.zeros((b, cache_len, nh, hd),
                                        cache_dt))]
            step = self._make_step(padded=pad_lens is not None)

            def run_step(tok_t, idx_t, kv):
                if pad_lens is not None:
                    return step(tok_t, idx_t, pad_lens, *kv)
                return step(tok_t, idx_t, *kv)

            finished = None
            if eos_token_id is not None:
                pad_id = (eos_token_id if pad_token_id is None
                          else pad_token_id)
                finished = jnp.zeros((b,), bool)

            def stop_update(tok):
                # finished rows emit pad; a fresh eos marks its row
                # finished (the emitted eos itself is kept)
                nonlocal finished
                if finished is None:
                    return tok
                tok = jnp.where(finished,
                                jnp.asarray(pad_id, tok.dtype), tok)
                finished = finished | (tok == eos_token_id)
                return tok

            idx0 = to_tensor(jnp.asarray(0, jnp.int32))
            logits, *flat_kv = run_step(input_ids, idx0, flat_kv)
            out = [input_ids._value.astype(jnp.int64)]
            tok = stop_update(pick(logits))
            out.append(tok[:, None].astype(jnp.int64))
            for t in range(1, total - prompt):
                if finished is not None and bool(finished.all()):
                    break  # every row hit eos: stop early
                step_idx = to_tensor(jnp.asarray(prompt + t - 1, jnp.int32))
                logits, *flat_kv = run_step(
                    Tensor(tok[:, None], stop_gradient=True), step_idx,
                    flat_kv)
                tok = stop_update(pick(logits))
                out.append(tok[:, None].astype(jnp.int64))
        return Tensor(jnp.concatenate(out, axis=1), stop_gradient=True)



class GPTForCausalLM(GPTGenerationMixin, nn.Layer):
    """LM head tied to the (vocab-sharded) embedding by default."""

    # the serving engine's protocol (serving_protocol.py): no counters
    # of its own, one cache kind (`GPTConfig.cache_kinds`)
    step_counters = ()

    def compute_dtype(self):
        return self.gpt.wte.weight._value.dtype

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)

    def _logits_from_hidden(self, x, shard=True):
        """ONE head projection shared by training forward and cached
        decode (shard hints only matter on a mesh)."""
        with _scope("lm_head"):
            if self.lm_head is not None:
                return self.lm_head(x)
            w = self.gpt.wte.weight  # [vocab, d], mp-sharded on vocab
            logits = F.linear(x, manip.transpose(w, [1, 0]))
            if shard:
                logits = shard_activation(logits, "dp", "sp", "mp")
            return logits

    def forward(self, input_ids):
        return self._logits_from_hidden(self.gpt(input_ids))

    def fused_head_loss(self, input_ids, labels=None, block_size=4096):
        """Shifted next-token loss with the head projection and softmax-CE
        fused (F.fused_linear_cross_entropy): the [b, s, vocab] logits are
        never materialized in HBM — the dominant activation slab of the
        step (docs/PERF_NOTES.md hypothesis 1). Single-chip / dp / sp
        path; vocab-sharded TP training should keep forward() +
        ParallelCrossEntropy (the vocab-parallel reduction lives there).
        """
        from ...distributed import mesh as mesh_mod

        if mesh_mod.has_mesh() and mesh_mod.axis_size("mp") > 1:
            raise ValueError(
                "fused_head_loss computes softmax over the FULL vocab; "
                "with mp>1 the tied head weight is vocab-sharded and the "
                "result would be silently wrong. Use forward() + "
                "GPTPretrainingCriterion (ParallelCrossEntropy) under TP.")
        if labels is None:
            labels = input_ids
        x = self.gpt(input_ids)  # [b, s, d]
        with _scope("lm_head"):
            shift_x = manip.slice(x, [1], [0], [x.shape[1] - 1])
            shift_labels = manip.slice(labels, [1], [1],
                                       [labels.shape[1]])
            # sum/total-count, NOT mean-over-valid:
            # GPTPretrainingCriterion means over ALL positions (ignored
            # ones contribute 0), and the two paths must stay loss- and
            # grad-scale identical for the BENCH_GPT_FUSED_HEAD A/B to
            # be meaningful
            total = shift_labels.shape[0] * shift_labels.shape[1]
            if self.lm_head is not None:
                s = F.fused_linear_cross_entropy(
                    shift_x, self.lm_head.weight, shift_labels,
                    reduction="sum", block_size=block_size)
            else:
                s = F.fused_linear_cross_entropy(
                    shift_x, self.gpt.wte.weight, shift_labels,
                    transpose_weight=True, reduction="sum",
                    block_size=block_size)
            return s / float(total)


class GPTPretrainingCriterion(nn.Layer):
    """Shifted next-token vocab-parallel cross entropy."""

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels):
        from ...ops.math import mean

        with _scope("loss"):
            shift_logits = manip.slice(
                logits, [1], [0], [logits.shape[1] - 1])
            shift_labels = manip.slice(labels, [1], [1],
                                       [labels.shape[1]])
            loss = self.ce(shift_logits, shift_labels)
            return mean(loss)


