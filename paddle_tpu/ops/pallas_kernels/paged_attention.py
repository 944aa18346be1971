"""Pallas ragged paged attention for TPU — the serving decode kernel.

TPU-native kernel for the continuous-batching LLM engine
(inference/llm_engine.py): attention over a PAGED KV cache, one query per
flat scheduled token, so decode tokens (1 per sequence) and chunked
prefill tokens (many per sequence) ride one launch with zero padding
between sequences (PAPERS.md "Ragged Paged Attention"; the reference's
serving stack keeps a contiguous per-request cache instead — paging is
what lets HBM scale with live tokens).

Layout: q [T, heads, head_dim]; the pool [num_pages, page_size, heads,
head_dim]. Grid (T / qb, pages_per_seq) with the page dimension
innermost (qb = 1 per-token, or the verify step's k+1 rows per slot):
each query block revisits its output block across page steps, so the
f32 accumulator and the online-softmax (m, l) statistics live in VMEM
scratch and are finalized on the last page step — the same
FlashAttention-2 shape as flash_attention.py, but the kv blocks are
GATHERED through the page table: the page id for grid step (b, j) is
read from scalar-prefetch SMEM (page_tables[slot_ids[b·qb], j]) inside
the BlockSpec index_map, so Mosaic DMAs exactly the pages the block
needs and blocks past its kv length are skipped.

Why the body is VPU work over [H, ·] tiles and not an MXU batched
matmul: the pool block is `[P, H, D]` — heads on the SUBLANE dim, and
H = 12 is not a sublane multiple. A per-head `q·kᵀ` needs `[H, P, D]`
(a major↔sublane transpose Mosaic does not lower at 12 rows), and its
lhs `q[H, D]` has no free dimension anyway (one query row per head: an
MXU pass at 1/128 occupancy). So the contraction is reordered: per page
row p, `sum_d q[H, D]·k_p[H, D]` is an elementwise multiply and a lane
reduce, the softmax runs over the P row-columns `[H, 1]`, and the
weighted sum of `v_p[H, D]` is a lane-broadcast multiply-add. The pool
layout is shared with PagePool, the KV wire, the tier store and the
trie; a head-major pool that would feed the MXU is a layout change,
not a kernel change.

Decode-only (no VJP): serving runs under no_grad. Numerics follow the
flash kernel: f32 accumulation, masked positions get -1e30,
fully-masked rows (padding tokens, kv_len 0) finalize to exact zeros.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention"]

NEG_INF = -1e30


def _eye_column(row, heads):
    """[1, H] lane-major row → [H, 1] sublane-major column, without a
    transpose: broadcast the row down the sublanes, keep the diagonal,
    reduce over lanes. The scale planes arrive `[P, H]` (heads on the
    LANE dim) while everything they multiply is `[H, D]` (heads on the
    SUBLANE dim); Mosaic has no general lane→sublane relayout for a
    12-wide row, and these three VPU/XLU ops are all it takes."""
    r = jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 1)
    full = jnp.broadcast_to(row, (heads, heads))
    return jnp.sum(jnp.where(r == c, full, 0.0), axis=-1, keepdims=True)


def _rpa_kernel(sid_ref, pt_ref, lens_ref, off_ref, q_ref, k_ref, v_ref,
                *rest, page_size, pages_per_seq, quantized, qb):
    """One grid step = (query block b of `qb` rows owned by ONE slot,
    logical page j of that slot). Every array the body touches is 2-D
    `[H, ·]` with heads on the sublanes — the layout a `[P, H, D]` pool
    block already has per page row — so nothing is transposed,
    reshaped or concatenated in VMEM (see the module docstring)."""
    if quantized:
        # int8/int4 pools ride with per-row fp32 scale planes, gathered
        # through the SAME page_map (quantization runtime, PT_KV_DTYPE);
        # quantized == 4 marks packed nibbles (pool lane dim D/2)
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    # lazy: keeps the kernel module free of the package import cycle
    from ...quantization.runtime import unpack_int4_halves

    b = pl.program_id(0)
    j = pl.program_id(1)
    halves, heads, kdim = q_ref.shape[1:]
    scale = 1.0 / math.sqrt(halves * kdim)

    # per-row kv lengths from scalar-prefetch SMEM: scalar reads,
    # unrolled over the STATIC block height. The frontier offset
    # advances every LIVE row; padding rows (base 0) stay padding — the
    # fused decode window's per-iteration frontier (one scalar per
    # iteration, the lens vector itself stays window-invariant)
    kvlens = []
    for i in range(qb):
        base = lens_ref[b * qb + i]
        kvlens.append(jnp.where(base > 0, base + off_ref[0], 0))
    kvmax = kvlens[0]
    for kl in kvlens[1:]:
        kvmax = jnp.maximum(kvmax, kl)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages past the LONGEST row's prefix contribute to no row — skip
    # (padding rows have kvlen 0, so an all-padding block skips every
    # page)
    @pl.when(j * page_size < kvmax)
    def _page():
        # the page, once per BLOCK: per page row p and lane half c an
        # [H, kdim] f32 tile; for a quantized pool the codes stay
        # unscaled and the per-(row, head) scale is applied to the
        # reduced score / the softmax weight instead ([H, 1] work
        # instead of [H, D])
        kt, vt, kcol, vcol = [], [], [], []
        for p in range(page_size):
            kp, vp = k_ref[0, p], v_ref[0, p]
            if quantized == 4:
                # the ONE nibble codec (quantization.runtime), as its
                # two planes — a second copy here would have to stay
                # bit-identical with `pack_int4` forever
                kh, vh = unpack_int4_halves(kp), unpack_int4_halves(vp)
            elif quantized:
                kh, vh = (kp.astype(jnp.int32),), (vp.astype(jnp.int32),)
            else:
                kh, vh = (kp,), (vp,)
            kt.append([t.astype(jnp.float32) for t in kh])
            vt.append([t.astype(jnp.float32) for t in vh])
            if quantized:
                kcol.append(_eye_column(ks_ref[0, pl.ds(p, 1), :], heads))
                vcol.append(_eye_column(vs_ref[0, pl.ds(p, 1), :], heads))
        pos1 = jnp.zeros((heads, 1), jnp.int32) + j * page_size
        posd = jnp.zeros((heads, kdim), jnp.int32) + j * page_size

        for i in range(qb):
            kvlen = kvlens[i]

            # a row this page is entirely PAST (the block ran because a
            # longer sibling row needed it) must not touch its (m, l,
            # acc): its scores would all be NEG_INF and exp(s - m)
            # would read exp(0) = 1 across the page
            @pl.when(j * page_size < kvlen)
            def _row(i=i, kvlen=kvlen):
                q = [q_ref[i, c].astype(jnp.float32) * scale
                     for c in range(halves)]
                s = []
                for p in range(page_size):
                    sp = jnp.sum(q[0] * kt[p][0], axis=-1,
                                 keepdims=True)          # [H, 1]
                    for c in range(1, halves):
                        sp = sp + jnp.sum(q[c] * kt[p][c], axis=-1,
                                          keepdims=True)
                    if quantized:
                        sp = sp * kcol[p]
                    s.append(jnp.where(pos1 + p < kvlen, sp, NEG_INF))
                m_prev = m_ref[i][:, :1]     # [H, 1] (stats broadcast lanes)
                l_prev = l_ref[i][:, :1]
                m_new = m_prev
                for sp in s:
                    m_new = jnp.maximum(m_new, sp)
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_prev
                acc = [acc_ref[i, c] * alpha for c in range(halves)]
                for p in range(page_size):
                    w = jnp.exp(s[p] - m_new)            # [H, 1]
                    l_new = l_new + w
                    if quantized:
                        w = w * vcol[p]
                    # freed/unwritten page rows hold stale-but-finite
                    # garbage (the pool is zero-initialized); their
                    # weight is exactly 0, but zero the v rows anyway
                    # so no accidental inf·0 can form
                    live = posd + p < kvlen
                    for c in range(halves):
                        acc[c] = acc[c] + w * jnp.where(
                            live, vt[p][c], 0.0)
                for c in range(halves):
                    acc_ref[i, c] = acc[c]
                m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
                l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == pages_per_seq - 1)
    def _finalize():
        for i in range(qb):
            l = l_ref[i][:, :1]
            # padding rows (kv_len 0) never ran a page: l == 0 → zeros
            safe_l = jnp.where(l == 0.0, 1.0, l)
            for c in range(halves):
                o_ref[i, c] = (acc_ref[i, c] / safe_l).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pool, v_pool, page_tables, slot_ids,
                           kv_lens, k_scales=None, v_scales=None,
                           frontier_offset=None, q_per_slot=None,
                           interpret=False):
    """q [T, H, D], pools [N, P, H, D], page_tables [S, MP] int,
    slot_ids [T] int, kv_lens [T] int → out [T, H, D].

    frontier_offset: optional scalar int32 added to every NONZERO
    kv_lens row (rides scalar-prefetch SMEM like the page table). The
    fused multi-token decode window passes its scan iteration here so
    one loop-invariant lens vector serves every iteration — rows with
    base 0 (padding / finished) keep skipping all pages.

    k_scales/v_scales [N, P, H] fp32: per-row dequant scales of INT8
    pools (quantization runtime). They are gathered through the same
    page-table index_map as the pools and the dequant happens in VMEM
    after the DMA, so HBM traffic for the cache stays int8 — the whole
    point of the quantized pool (page bytes ≈ ×4 down vs fp32).

    q_per_slot: optional STATIC int — the caller's guarantee that the
    T query rows are slot-major contiguous blocks of exactly this many
    rows, one slot per block (the speculative VERIFY layout: k+1 rows
    per slot). The grid becomes (T/q_per_slot, pages_per_seq): each
    slot's pages are DMA'd once per BLOCK instead of once per row,
    while per-row kv_lens keep the in-window causal raggedness (row i
    masks its scores at its own kv_len, which is what lets draft token
    j attend to drafts 0..j-1 written in this same dispatch and never
    to later ones). Ignored when T is not a multiple.

    A quantized pool whose last dim is HALF the query head_dim holds
    PACKED int4 nibbles (kv_dtype="int4"): the kernel unpacks in VMEM
    after the DMA, so HBM traffic for the cache is int4 — page bytes
    ≈ ×8 down vs fp32 (same shape discriminator as the jnp reference).

    interpret: run in the Pallas interpreter (the CPU test tier passes
    True; nothing here looks at the backend).

    Semantics contract: identical to the jnp reference in
    nn/functional/attention.py `paged_attention_jnp` (pinned by the
    interpret-mode parity tests in tests/test_llm_engine.py,
    tests/test_quant_runtime.py and tests/test_speculative.py, and
    compiled on the chip by chip_smoke.py)."""
    tokens, heads, dim = q.shape
    _, page_size, _, kdim = k_pool.shape
    _, pages_per_seq = page_tables.shape
    quantized = 0
    if k_scales is not None:
        quantized = 4 if kdim * 2 == dim else 8
    # packed int4 splits head_dim into its two nibble planes: q and out
    # ride as [T, 2, H, D/2] so each plane sits at lane offset 0 in the
    # kernel (no lane slice / concatenate in VMEM); float and int8
    # pools are the halves == 1 case of the same layout
    halves = dim // kdim
    qb = 1
    if q_per_slot is not None and tokens % int(q_per_slot) == 0:
        qb = int(q_per_slot)

    if frontier_offset is None:
        frontier_offset = 0
    off = jnp.asarray(frontier_offset, jnp.int32).reshape((1,))

    kernel = functools.partial(
        _rpa_kernel, page_size=page_size, pages_per_seq=pages_per_seq,
        quantized=quantized, qb=qb)

    def _blk_page(b, j, sid, pt, lens, offv):
        # clamp j to the LAST live page any row of block b needs (index_
        # map twin of the kernel's kvmax): grid steps past the valid
        # prefix re-request the same block, so Mosaic elides their
        # HBM→VMEM copy (the compute is already pl.when-gated) — without
        # the clamp every dead page would still be DMA'd and kernel
        # bandwidth would scale with max_model_len, not live tokens.
        # The prefetched operands are SMEM refs here — scalar reads
        # only, unrolled over the STATIC block height.
        eff_max = jnp.asarray(0, jnp.int32)
        for i in range(qb):
            base = lens[b * qb + i]
            eff = jnp.where(base > 0, base + offv[0], 0)
            eff_max = jnp.maximum(eff_max, eff)
        last = jnp.maximum(eff_max - 1, 0) // page_size
        # one slot per block (the slot-major contract): the block's
        # first row names it
        return pt[sid[b * qb] * pages_per_seq + jnp.minimum(j, last)]

    def page_map(b, j, *prefetch):
        return (_blk_page(b, j, *prefetch), 0, 0, 0)

    def scale_map(b, j, *prefetch):
        return (_blk_page(b, j, *prefetch), 0, 0)

    def q_map(b, j, *prefetch):
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((qb, halves, heads, kdim), q_map),
        pl.BlockSpec((1, page_size, heads, kdim), page_map),
        pl.BlockSpec((1, page_size, heads, kdim), page_map),
    ]
    q4 = jnp.swapaxes(q.reshape(tokens, heads, halves, kdim), 1, 2)
    inputs = [q4, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, page_size, heads), scale_map),
                     pl.BlockSpec((1, page_size, heads), scale_map)]
        inputs += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tokens // qb, pages_per_seq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((qb, halves, heads, kdim), q_map),
        scratch_shapes=[
            pltpu.VMEM((qb, halves, heads, kdim), jnp.float32),  # acc
            pltpu.VMEM((qb, heads, 128), jnp.float32),   # running max
            pltpu.VMEM((qb, heads, 128), jnp.float32),   # running sum
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(slot_ids, jnp.int32),
      jnp.asarray(page_tables, jnp.int32).reshape(-1),
      jnp.asarray(kv_lens, jnp.int32), off,
      *inputs)
    return jnp.swapaxes(out, 1, 2).reshape(tokens, heads, dim)
