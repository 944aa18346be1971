"""Attention functional.

(Reference: the fused attention CUDA ops
paddle/fluid/operators/fused/fused_attention_op.cu and fmha_ref.h. On TPU
the default path is the jnp softmax formulation — XLA fuses it well — and
when shapes warrant, the Pallas flash-attention kernel
(ops/pallas_kernels/flash_attention.py) is used instead.)
"""
import math

import jax
import jax.numpy as jnp

from ...ops._helpers import apply_jfn, ensure_tensor

__all__ = ["scaled_dot_product_attention", "dense_attention_bshd",
           "paged_attention", "paged_attention_jnp"]


def dense_attention_bshd(q, k, v, is_causal=False, attn_mask=None,
                         drop_key=None, dropout_p=0.0):
    """Pure-jnp softmax attention on [batch, seq, heads, head_dim] — the
    XLA-fused fallback used when the Pallas kernel is not eligible. Shared
    by scaled_dot_product_attention and the pipelined GPT block."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if is_causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, jnp.asarray(-jnp.inf,
                                                       scores.dtype))
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores,
                               jnp.asarray(-jnp.inf, scores.dtype))
        else:
            scores = scores + attn_mask
    w = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    if drop_key is not None and dropout_p > 0.0:
        import jax

        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(vt.dtype), vt)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, kv_lens=None, name=None):
    """Inputs [batch, seq, heads, head_dim] (paddle convention).

    kv_lens: optional [batch] int per-example valid key length — the
    prefix key-padding mask (padded BERT/ERNIE batches). Unlike a dense
    `attn_mask` (whose values are unknown at trace time, forcing the jnp
    path), a lengths vector states its structure up front, so it rides
    the Pallas flash kernel. Mutually exclusive with attn_mask.
    """
    query = ensure_tensor(query)
    key = ensure_tensor(key)
    value = ensure_tensor(value)
    if kv_lens is not None and attn_mask is not None:
        raise ValueError("pass either attn_mask or kv_lens, not both")
    tensors = [query, key, value]
    if attn_mask is not None:
        tensors.append(ensure_tensor(attn_mask))

    use_pallas = _pallas_eligible(query, key)
    if use_pallas and attn_mask is None and dropout_p == 0.0:
        from ...ops.pallas_kernels import flash_attention

        if kv_lens is not None:
            lens_t = ensure_tensor(kv_lens)

            def jfn_lens(q, k, v, lens):
                return flash_attention.flash_attention_bshd(
                    q, k, v, causal=is_causal, kv_lens=lens)

            return apply_jfn("flash_attention", jfn_lens, query, key,
                             value, lens_t)

        def jfn(q, k, v):
            return flash_attention.flash_attention_bshd(q, k, v, causal=is_causal)

        return apply_jfn("flash_attention", jfn, query, key, value)

    drop_key = None
    if dropout_p > 0.0 and training:
        from ...core import rng

        drop_key = rng.next_key()

    if kv_lens is not None:
        lens_t = ensure_tensor(kv_lens)

        def jfn_lens(q, k, v, lens):
            lens = lens.astype(jnp.int32)
            # zero-length rows: mask against max(len, 1) (a fully-masked
            # softmax row is NaN and the NaN survives where-grads), then
            # zero those rows — matching the Pallas kernel's safe_l
            # zeros so CPU and TPU agree
            keep = (jnp.arange(k.shape[1])[None, :]
                    < jnp.maximum(lens, 1)[:, None])[:, None, None, :]
            out = dense_attention_bshd(
                q, k, v, is_causal=is_causal, attn_mask=keep,
                drop_key=drop_key, dropout_p=dropout_p)
            return jnp.where((lens > 0)[:, None, None, None], out, 0.0)

        return apply_jfn("scaled_dot_product_attention", jfn_lens, query,
                         key, value, lens_t)

    def jfn(q, k, v, *rest):
        return dense_attention_bshd(
            q, k, v, is_causal=is_causal,
            attn_mask=rest[0] if rest else None,
            drop_key=drop_key, dropout_p=dropout_p)

    return apply_jfn("scaled_dot_product_attention", jfn, *tensors)


def paged_attention(query, k_pool, v_pool, page_tables, slot_ids, kv_lens,
                    k_scales=None, v_scales=None, frontier_offset=None,
                    max_tokens_per_slot=None, name=None):
    """Ragged paged attention over a paged KV-cache pool — the serving
    decode path (inference/llm_engine.py; PAPERS.md "Ragged Paged
    Attention"). One query per FLAT scheduled token, so a single call
    serves a continuous batch mixing decode tokens (1 per sequence) and
    chunked-prefill tokens (many per sequence) with zero padding between
    sequences.

    query        [T, heads, head_dim] — flat token batch
    k_pool/v_pool [num_pages, page_size, heads, head_dim] — the pool;
                 page 0 is by convention the engine's trash page
    page_tables  [num_slots, pages_per_seq] int — physical page id per
                 (slot, logical page); unallocated entries may hold any
                 valid id (they are masked by kv_lens)
    slot_ids     [T] int — owning decode slot per token
    kv_lens      [T] int — valid kv length for each token (its position
                 + 1, i.e. the token attends to its own k/v and every
                 earlier one); 0 marks a padding token → zero output
    k_scales/v_scales  [num_pages, page_size, heads] fp32 — the
                 per-row dequant scales of an INT8 or packed-INT4 pool
                 (quantization runtime, kv_dtype="int8"/"int4"):
                 gathered rows are dequantized `codes * scale` before
                 attention (dequant-on-gather). A pool whose head_dim
                 is HALF the query's holds packed int4 nibbles and is
                 unpacked after the gather. None for float pools.
    frontier_offset  optional scalar int added to every NONZERO
                 kv_lens row (zero rows stay padding). The fused
                 decode window (gpt.py `_paged_decode_fused`) passes
                 its scan iteration here, so the kv_lens VECTOR stays
                 window-invariant and only one scalar advances the
                 frontier per iteration.
    max_tokens_per_slot  optional STATIC int: the caller's guarantee
                 that no slot owns more than this many of the T query
                 tokens. Sizes the jnp slot grid [S, C] at
                 C = max_tokens_per_slot instead of the worst-case
                 C = T — the speculative verify step packs exactly
                 k+1 tokens per slot, so its score tensor shrinks from
                 [S, h, T, L] to [S, h, k+1, L]. When the T tokens are
                 additionally slot-major contiguous in blocks of this
                 size (the verify layout), the Pallas path amortizes
                 each slot's page DMAs across the whole query block.
                 A caller that VIOLATES the bound gets silently
                 dropped queries (out-of-bounds scatter) — it is a
                 contract, not a clamp.

    jnp reference semantics everywhere (mirrors the dense decode path in
    text/models/gpt.py `_cached_attention` op for op, so engine greedy
    decode stays token-identical to `generate()`); the Pallas kernel
    (ops/pallas_kernels/paged_attention.py) takes over behind the same
    TPU gate as flash attention.
    """
    q = ensure_tensor(query)
    kp = ensure_tensor(k_pool)
    vp = ensure_tensor(v_pool)
    pt = ensure_tensor(page_tables)
    sid = ensure_tensor(slot_ids)
    lens = ensure_tensor(kv_lens)
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    scales = () if k_scales is None else (
        ensure_tensor(k_scales), ensure_tensor(v_scales))
    has_off = frontier_offset is not None
    off = (ensure_tensor(frontier_offset),) if has_off else ()

    use_pallas = _paged_pallas_eligible(q, kp)

    def jfn(qv, kpool, vpool, tables, sids, ls, *rest):
        off_v, sc = (rest[0], rest[1:]) if has_off else (None, rest)
        ks, vs = sc if sc else (None, None)
        if use_pallas:
            from ...ops.pallas_kernels import paged_attention as pa_kernel

            # the only caller that bounds tokens per slot (the verify
            # step) also packs them slot-major in blocks of that size:
            # the kernel then DMAs each slot's pages once per block
            return pa_kernel.ragged_paged_attention(
                qv, kpool, vpool, tables, sids, ls, k_scales=ks,
                v_scales=vs, frontier_offset=off_v,
                q_per_slot=max_tokens_per_slot)
        return paged_attention_jnp(
            qv, kpool, vpool, tables, sids, ls, k_scales=ks,
            v_scales=vs, frontier_offset=off_v,
            max_tokens_per_slot=max_tokens_per_slot)

    return apply_jfn("paged_attention", jfn, q, kp, vp, pt, sid, lens,
                     *off, *scales)


def paged_attention_jnp(qv, kpool, vpool, tables, sids, ls, k_scales=None,
                        v_scales=None, frontier_offset=None,
                        max_tokens_per_slot=None):
    """`paged_attention` on raw arrays in plain jnp: the path every
    non-TPU backend runs, and the reference the Pallas kernel is
    compared with (interpret mode in the tests, compiled on the chip in
    chip_smoke.py). Mirrors the dense decode path in text/models/gpt.py
    `_cached_attention` op for op, so engine greedy decode stays
    token-identical to `generate()`."""
    import jax

    n_pages, page_size, h, d = kpool.shape
    # a quantized pool whose rows are HALF the query head_dim holds
    # PACKED int4 (kv_dtype="int4"): unpack after the gather, then
    # dequant by the same per-row scale planes. The shape mismatch
    # is the discriminator — an unpacked pool always matches q.
    packed4 = k_scales is not None and d * 2 == qv.shape[-1]
    n_slots, pages_per_seq = tables.shape
    tokens = qv.shape[0]
    L = pages_per_seq * page_size
    ls = ls.astype(jnp.int32)
    if frontier_offset is not None:
        # advance every live token's frontier; padding rows stay 0
        ls = jnp.where(ls > 0, ls + frontier_offset.astype(jnp.int32), 0)
    sids = sids.astype(jnp.int32)
    # gather each SLOT's kv once ([S, L, h, d]) and scatter the
    # queries onto a [S, C] slot grid, so the per-TOKEN [T, L, h, d]
    # materialization never forms — 2× fewer bytes moved than the
    # naive per-token gather at serving shapes, and the slot-level
    # einsum is a clean batched matmul. (The Pallas kernel avoids
    # even the [S, L] gather by DMA-ing pages from the table.)
    l_idx = jnp.arange(L, dtype=jnp.int32)
    phys = (tables.astype(jnp.int32)[:, l_idx // page_size]
            * page_size + (l_idx % page_size)[None, :])   # [S, L]
    k_all = kpool.reshape(n_pages * page_size, h, d)
    v_all = vpool.reshape(n_pages * page_size, h, d)
    ks = k_all[phys]                            # [S, L, h, d]
    vs = v_all[phys]
    if k_scales is not None:
        # int8/int4 pool: dequant-on-gather by per-row scales
        if packed4:
            from ...quantization.runtime import unpack_int4

            ks = unpack_int4(ks, axis=-1)   # [S, L, h, 2d] int8
            vs = unpack_int4(vs, axis=-1)
            d = d * 2
        ksc = k_scales.reshape(n_pages * page_size, h)[phys]  # [S,L,h]
        vsc = v_scales.reshape(n_pages * page_size, h)[phys]
        ks = ks.astype(jnp.float32) * ksc[..., None]
        vs = vs.astype(jnp.float32) * vsc[..., None]
    # chunk position of each token within its slot (order-stable):
    # cpos[t] = #earlier tokens with the same slot — collision-free
    # grid coordinates whatever order the scheduler packed
    eq = sids[:, None] == sids[None, :]
    cpos = jnp.sum(jnp.tril(eq, -1), axis=1)    # [T]
    # worst case one slot owns every token; a caller-provided
    # per-slot bound (the verify step: exactly k+1) shrinks the
    # grid — and the [S, h, C, L] score tensor — accordingly
    C = (tokens if max_tokens_per_slot is None
         else min(tokens, int(max_tokens_per_slot)))
    qs = jnp.zeros((n_slots, C, h, d), qv.dtype).at[
        (sids, cpos)].set(qv)
    lgrid = jnp.zeros((n_slots, C), jnp.int32).at[
        (sids, cpos)].set(ls)
    sc = jnp.einsum("schd,slhd->shcl", qs, ks) / math.sqrt(d)
    allowed = (l_idx[None, None, None, :]
               < lgrid[:, None, :, None])
    sc = jnp.where(allowed, sc, jnp.float32(-1e30))
    # softmax statistics in f32 even for bf16 pools (same contract
    # as _cached_attention); empty grid cells softmax to uniform
    # garbage but are never gathered back
    w = jax.nn.softmax(sc.astype(jnp.float32), axis=-1).astype(
        vs.dtype)
    o = jnp.einsum("shcl,slhd->schd", w, vs).astype(qv.dtype)
    out = o[(sids, cpos)]                       # [T, h, d]
    # padding tokens (kv_len 0): the fully-masked softmax row is
    # uniform garbage — zero it explicitly
    return jnp.where((ls > 0)[:, None, None], out,
                     jnp.zeros_like(out))


def paged_attention_gqa(qv, kpool, vpool, tables, sids, ls, starts=None,
                        frontier_offset=None, block_layout=None):
    """Ragged paged attention with GROUPED queries over HEAD-MAJOR pools,
    on raw arrays: q [T, H, D], pools [N, KV, P, D] (query head j reads
    KV head j // (H / KV)), tables [S, MP], sids / ls [T] as
    `paged_attention`, `starts` [T] the first position each row sees
    (a sliding window's lower bound; may be negative; None: 0). The
    frontier offset advances both bounds of every live row. The Pallas
    head-major walk on a TPU, `paged_attention_gqa_jnp` elsewhere.

    block_layout: a `SlotBlockLayout` of these rows (made once a step,
    used by every layer). The kernel then runs over the padded layout in
    query blocks of `block_layout.rows` rows of one slot, so a slot's
    pages are copied once a block and not once a row (a prefill chunk's
    rows share their slot's context)."""
    if not _pallas_backend_ok():
        return paged_attention_gqa_jnp(qv, kpool, vpool, tables, sids, ls,
                                       starts, frontier_offset)
    from ...ops.pallas_kernels import paged_attention as pa_kernel

    if block_layout is None:
        return pa_kernel.ragged_paged_attention(
            qv, kpool, vpool, tables, sids, ls, kv_starts=starts,
            frontier_offset=frontier_offset, head_major=True)
    lay = block_layout
    out = pa_kernel.ragged_paged_attention(
        lay.spread(qv), kpool, vpool, tables, lay.sids, lay.lens,
        kv_starts=None if starts is None else lay.spread(starts),
        frontier_offset=frontier_offset, head_major=True,
        q_per_slot=lay.rows)
    return out[lay.dest]


class SlotBlockLayout:
    """The rows of a flat step, laid out again so that every slot's run
    of rows STARTS a block of `rows` rows (padding rows, length 0, fill
    each run's last block). sids / lens [T] with the live rows first and
    each slot's rows side by side (the engine's tick); at most
    `max_runs` runs. `dest` [T] is where each row went (dead rows to the
    last, dead, row), `sids` / `lens` the padded layout's, `spread(x)`
    any per-row array in it."""

    def __init__(self, sids, lens, rows, max_runs):
        T = sids.shape[0]
        self.rows = int(rows)
        self.total = -(-(T + max_runs * (self.rows - 1)) // self.rows) \
            * self.rows
        r = jnp.arange(T, dtype=jnp.int32)
        sids = sids.astype(jnp.int32)
        live = lens > 0
        starts_run = live & ((r == 0) | (sids != jnp.roll(sids, 1))
                             | ~jnp.roll(live, 1))
        run_start = jax.lax.cummax(jnp.where(starts_run, r, 0))
        # the padding behind the run that ends where this one starts
        pad = jnp.where(starts_run & (r > 0),
                        (-(r - jnp.roll(run_start, 1))) % self.rows, 0)
        self.dest = jnp.where(live, r + jnp.cumsum(pad), self.total - 1)
        self.sids = self.spread(sids)
        self.lens = self.spread(jnp.where(live, lens, 0))

    def spread(self, x):
        return jnp.zeros((self.total,) + x.shape[1:], x.dtype).at[
            self.dest].set(x)


class SlotRunLayout:
    """Which rows of a flat step stand in RUNS of at least `min_rows`
    rows of ONE slot at consecutive positions (a prefill chunk: sids
    equal, lens ascending by one), and those rows laid out again so
    that every such run starts at a multiple of `align` rows, with
    `spare` rows past the last: what the EXPANDED latent walk takes
    (`paged_attention_latent_expanded`: its sub-blocks start at a run's
    first row and the last of them runs past the run's rows). At most
    `T // min_rows` runs qualify, so the layout is `total` = T + that
    many · (align − 1) rows, rounded up, + spare. `expanded` [T] bool the
    rows in such runs; `dest` [T] where each went (every other row to
    the last row, which no run holds); `src` [total] a row to read for
    each laid-out row; `run_slots` / `run_row0` / `run_first` /
    `run_rows` [max_runs] int32 a run's slot, first laid-out row, first
    row's kv length and live rows (0: no run), in the order of their
    rows."""

    def __init__(self, sids, lens, min_rows, align, spare):
        import jax

        T = sids.shape[0]
        align = int(align)
        self.max_runs = T // int(min_rows)
        self.total = -(-(T + self.max_runs * (align - 1)) // align) \
            * align + int(spare)
        r = jnp.arange(T, dtype=jnp.int32)
        sids = sids.astype(jnp.int32)
        lens = lens.astype(jnp.int32)
        live = lens > 0
        starts = live & ((r == 0) | (sids != jnp.roll(sids, 1))
                         | ~jnp.roll(live, 1)
                         | (lens != jnp.roll(lens, 1) + 1))
        run = jnp.cumsum(starts) - 1          # of a live row
        size = jax.ops.segment_sum(live.astype(jnp.int32),
                                   jnp.where(live, run, T),
                                   num_segments=T)[run.clip(0)]
        self.expanded = live & (size >= int(min_rows))
        head = starts & self.expanded
        k = (jnp.cumsum(head) - 1).clip(0)    # rank among the runs kept

        def a_run(x):
            return jnp.zeros((self.max_runs,), jnp.int32).at[
                jnp.where(head, k, self.max_runs)].set(x, mode="drop")

        self.run_slots, self.run_first = a_run(sids), a_run(lens)
        self.run_rows = a_run(size)
        padded = -(-self.run_rows // align) * align
        self.run_row0 = jnp.cumsum(padded) - padded
        self.dest = jnp.where(
            self.expanded, self.run_row0[k] + r - a_run(r)[k],
            self.total - 1)
        self.src = jnp.zeros((self.total,), jnp.int32).at[self.dest].set(r)


def paged_attention_gqa_jnp(qv, kpool, vpool, tables, sids, ls, starts=None,
                            frontier_offset=None):
    """`paged_attention_gqa` in plain jnp: what every non-TPU backend
    runs and what the head-major kernel is compared with. Same slot-grid
    shape as `paged_attention_jnp`; a position is attended where
    start <= position < kv_len."""
    import jax

    n_pages, kvh, page_size, d = kpool.shape
    tokens, heads, _ = qv.shape
    g = heads // kvh
    n_slots, pages_per_seq = tables.shape
    L = pages_per_seq * page_size
    ls = ls.astype(jnp.int32)
    st = (jnp.full_like(ls, -(2 ** 30)) if starts is None
          else starts.astype(jnp.int32))
    if frontier_offset is not None:
        off = jnp.asarray(frontier_offset, jnp.int32)
        st = jnp.where(ls > 0, st + off, 0)
        ls = jnp.where(ls > 0, ls + off, 0)
    st = jnp.maximum(st, 0)
    sids = sids.astype(jnp.int32)
    l_idx = jnp.arange(L, dtype=jnp.int32)
    phys = (tables.astype(jnp.int32)[:, l_idx // page_size]
            * page_size + (l_idx % page_size)[None, :])   # [S, L]
    k_all = jnp.swapaxes(kpool, 1, 2).reshape(n_pages * page_size, kvh, d)
    v_all = jnp.swapaxes(vpool, 1, 2).reshape(n_pages * page_size, kvh, d)
    ks, vs = k_all[phys], v_all[phys]                     # [S, L, KV, d]
    eq = sids[:, None] == sids[None, :]
    cpos = jnp.sum(jnp.tril(eq, -1), axis=1)              # [T]
    qs = jnp.zeros((n_slots, tokens, kvh, g, d), qv.dtype).at[
        (sids, cpos)].set(qv.reshape(tokens, kvh, g, d))
    hi = jnp.zeros((n_slots, tokens), jnp.int32).at[(sids, cpos)].set(ls)
    lo = jnp.zeros((n_slots, tokens), jnp.int32).at[(sids, cpos)].set(st)
    sc = jnp.einsum("schgd,slhd->shgcl", qs, ks,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    seen = ((l_idx[None, None, :] < hi[:, :, None])
            & (l_idx[None, None, :] >= lo[:, :, None]))   # [S, C, L]
    sc = jnp.where(seen[:, None, None], sc, jnp.float32(-1e30))
    w = jax.nn.softmax(sc, axis=-1).astype(vs.dtype)
    o = jnp.einsum("shgcl,slhd->schgd", w, vs,
                   preferred_element_type=jnp.float32).astype(qv.dtype)
    out = o[(sids, cpos)].reshape(tokens, heads, d)
    return jnp.where((ls > 0)[:, None, None], out, jnp.zeros_like(out))


def paged_attention_latent(qv, pool, tables, sids, ls, v_dim, scale,
                           frontier_offset=None, block_layout=None):
    """Ragged paged attention over a LATENT pool, on raw arrays: q
    [T, H, R] ABSORBED queries, pool [N, P, R] one row a token with no
    head axis (every head reads every row), tables [S, MP], sids / ls
    [T] as `paged_attention`. A row's score is q · row · `scale`; its
    value the row's first `v_dim` lanes: out [T, H, v_dim], which the
    caller projects up a head. The Pallas latent walk on a TPU,
    `paged_attention_latent_jnp` elsewhere. `block_layout`: as
    `paged_attention_gqa` (query blocks of one slot's rows, its pages
    copied once a block)."""
    if not _pallas_backend_ok():
        return paged_attention_latent_jnp(qv, pool, tables, sids, ls,
                                          v_dim, scale, frontier_offset)
    from ...ops.pallas_kernels import paged_attention as pa_kernel

    if block_layout is None:
        return pa_kernel.latent_paged_attention(
            qv, pool, tables, sids, ls, v_dim, scale,
            frontier_offset=frontier_offset)
    lay = block_layout
    out = pa_kernel.latent_paged_attention(
        lay.spread(qv), pool, tables, lay.sids, lay.lens, v_dim, scale,
        frontier_offset=frontier_offset, q_per_slot=lay.rows)
    return out[lay.dest]


def paged_attention_latent_jnp(qv, pool, tables, sids, ls, v_dim, scale,
                               frontier_offset=None):
    """`paged_attention_latent` in plain jnp: what every non-TPU backend
    runs and what the latent walk is compared with. The slot-grid shape
    of `paged_attention_jnp`."""
    import jax

    n_pages, page_size, row = pool.shape
    tokens, heads, _ = qv.shape
    n_slots, pages_per_seq = tables.shape
    L = pages_per_seq * page_size
    ls = ls.astype(jnp.int32)
    if frontier_offset is not None:
        ls = jnp.where(ls > 0, ls + jnp.asarray(frontier_offset,
                                                jnp.int32), 0)
    sids = sids.astype(jnp.int32)
    l_idx = jnp.arange(L, dtype=jnp.int32)
    phys = (tables.astype(jnp.int32)[:, l_idx // page_size]
            * page_size + (l_idx % page_size)[None, :])   # [S, L]
    rows = pool.reshape(n_pages * page_size, row)[phys]   # [S, L, R]
    eq = sids[:, None] == sids[None, :]
    cpos = jnp.sum(jnp.tril(eq, -1), axis=1)              # [T]
    qs = jnp.zeros((n_slots, tokens, heads, row), qv.dtype).at[
        (sids, cpos)].set(qv)
    hi = jnp.zeros((n_slots, tokens), jnp.int32).at[(sids, cpos)].set(ls)
    sc = jnp.einsum("schr,slr->shcl", qs, rows,
                    preferred_element_type=jnp.float32) * scale
    seen = l_idx[None, None, :] < hi[:, :, None]          # [S, C, L]
    sc = jnp.where(seen[:, None], sc, jnp.float32(-1e30))
    w = jax.nn.softmax(sc, axis=-1).astype(rows.dtype)
    # [S, H·C, L] · [S, L, v]: the slot leads both operands
    o = jnp.matmul(w.reshape(n_slots, heads * tokens, L),
                   rows[..., :v_dim], preferred_element_type=jnp.float32)
    o = jnp.swapaxes(o.reshape(n_slots, heads, tokens, v_dim), 1, 2)
    out = o.astype(qv.dtype)[(sids, cpos)]
    return jnp.where((ls > 0)[:, None, None], out, jnp.zeros_like(out))


def latent_run_layout(sids, lens, min_rows):
    """The `SlotRunLayout` the expanded latent walk takes of a step's
    rows: runs from `min_rows` rows on, laid out at the kernel's row
    alignment with a sub-block to spare."""
    from ...ops.pallas_kernels import paged_attention as pa_kernel

    return SlotRunLayout(sids, lens, min_rows,
                         pa_kernel.LATENT_EXPANDED_ROW_ALIGN,
                         pa_kernel.latent_expanded_tiles()[0])


def paged_attention_latent_expanded(q_nope, q_rope, pool, w_uk, w_uv,
                                    tables, sids, ls, scale, runs):
    """The EXPANDED form of latent attention over pages, for the rows
    of `runs.expanded` (the `latent_run_layout` of these rows): q_nope
    [T, H, nope] and q_rope [T, H, rope] as the model has them before
    any absorption, pool [N, P, R] (`[c | k_r | zeros]` a row), w_uk
    [H, nope, latent], w_uv [H, latent, v]. Per head softmax((q_nope ·
    (c W_UKᵀ) + q_rope · k_r) · scale) · (c W_UV) over the row's slot's
    positions below its kv length, the up-projected rows rounded to the
    pool's dtype: out [T, H, v] in the pool's dtype, no W_UV left to
    apply; zeros in every row outside the runs. The Pallas kernel that
    up-projects in VMEM on a TPU, `paged_attention_latent_expanded_jnp`
    elsewhere."""
    if not _pallas_backend_ok():
        return paged_attention_latent_expanded_jnp(
            q_nope, q_rope, pool, w_uk, w_uv, tables, sids,
            jnp.where(runs.expanded, ls, 0), scale)
    from ...ops.pallas_kernels import paged_attention as pa_kernel

    latent, rope = w_uk.shape[2], q_rope.shape[-1]
    # [q_nope | q_rope | zeros]: the rotary part as wide as the pool
    # row's lanes past the latent (whose lanes past k_r hold zeros)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(pool.dtype)
    q = jnp.pad(q, ((0, 0), (0, 0),
                    (0, pool.shape[-1] - latent - rope)))
    tokens, heads, _ = q.shape
    out = pa_kernel.latent_expanded_attention(
        q.reshape(tokens, -1)[runs.src], pool, w_uk, w_uv, tables,
        runs.run_slots, runs.run_row0, runs.run_first, runs.run_rows, scale)
    return jnp.where(runs.expanded[:, None], out[runs.dest], 0).reshape(
        tokens, heads, -1)


def paged_attention_latent_expanded_jnp(q_nope, q_rope, pool, w_uk, w_uv,
                                        tables, sids, ls, scale):
    """`paged_attention_latent_expanded` in plain jnp (every live row):
    what every non-TPU backend runs and what the kernel is compared
    with. It up-projects EVERY slot's positions: small shapes only."""
    import jax

    n_pages, page_size, row = pool.shape
    latent, rope = w_uk.shape[2], q_rope.shape[-1]
    n_slots, pages_per_seq = tables.shape
    L = pages_per_seq * page_size
    dt, f32 = pool.dtype, jnp.float32
    ls = ls.astype(jnp.int32)
    sids = sids.astype(jnp.int32)
    l_idx = jnp.arange(L, dtype=jnp.int32)
    phys = (tables.astype(jnp.int32)[:, l_idx // page_size]
            * page_size + (l_idx % page_size)[None, :])   # [S, L]
    rows = pool.reshape(n_pages * page_size, row)[phys]   # [S, L, R]
    lat, kr = rows[..., :latent], rows[..., latent:latent + rope]
    k_nope = jnp.einsum("slc,hnc->shln", lat, w_uk,
                        preferred_element_type=f32).astype(dt)
    v = jnp.einsum("slc,hcv->shlv", lat, w_uv,
                   preferred_element_type=f32).astype(dt)
    sc = jnp.einsum("thn,thln->thl", q_nope.astype(dt), k_nope[sids],
                    preferred_element_type=f32) \
        + jnp.einsum("thr,tlr->thl", q_rope.astype(dt), kr[sids],
                     preferred_element_type=f32)
    sc = jnp.where(l_idx[None, None, :] < ls[:, None, None], sc * scale,
                   f32(-1e30))
    w = jax.nn.softmax(sc, axis=-1).astype(dt)
    o = jnp.einsum("thl,thlv->thv", w, v[sids],
                   preferred_element_type=f32).astype(dt)
    return jnp.where((ls > 0)[:, None, None], o, jnp.zeros_like(o))


def _pallas_backend_ok():
    """The shared Pallas gate policy: kernels flag on AND a real TPU
    backend (ONE place — both the flash and the paged gates call it).
    Selects on the platform only; a backend that cannot initialise
    raises here, it does not become "use jnp"."""
    import jax

    from ...core import flags

    return bool(flags.get_flag("use_pallas_kernels")
                and jax.default_backend() == "tpu")


def _paged_pallas_eligible(q, k_pool):
    """Pallas ragged-paged-attention gate: `_pallas_backend_ok` + a
    head_dim the kernel's `[H, D]` lane tiles take + a sublane-tileable
    page size. Nothing about sequence lengths: a query block is one
    token (or one slot's verify rows) and walks only the pages its row
    has; whether the kernel copies those pages itself or Mosaic's
    pipeline does is the kernel's own choice from the pool's shape."""
    return (
        _pallas_backend_ok()
        and len(q.shape) == 3
        and q.shape[2] in (64, 128, 256)
        and k_pool.shape[1] % 8 == 0
    )


def _pallas_eligible(q, k):
    """Use the Pallas kernel only on real TPU backends with tileable shapes
    (both q and kv sequence lengths; the kernel assumes self-attention
    geometry for the causal diagonal)."""
    shape = q.shape
    return (
        _pallas_backend_ok()
        and len(shape) == 4
        and shape[1] % 128 == 0
        and k.shape[1] == shape[1]
        and shape[3] in (64, 128, 256)
    )
