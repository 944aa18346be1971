"""Pallas ragged paged attention for TPU — the serving decode kernel.

TPU-native kernel for the continuous-batching LLM engine
(inference/llm_engine.py): attention over a PAGED KV cache, one query per
flat scheduled token, so decode tokens (1 per sequence) and chunked
prefill tokens (many per sequence) ride one launch with zero padding
between sequences (PAPERS.md "Ragged Paged Attention"; the reference's
serving stack keeps a contiguous per-request cache instead — paging is
what lets HBM scale with live tokens).

Layout: q [T, heads, head_dim]; the pool [num_pages, page_size, heads,
head_dim]. A query block is `qb` rows of ONE slot (qb = 1 per-token, or
the verify step's k+1 rows per slot); its f32 accumulator and
online-softmax (m, l) statistics live in VMEM scratch while the slot's
pages stream past in ascending order — the FlashAttention-2 shape of
flash_attention.py, with the kv blocks GATHERED through the page table
(page_tables[slot_ids[b·qb], j], read from scalar-prefetch SMEM).

Who walks the pages (`_walks_in_kernel`, static shapes only):

* THE WALK — grid (T / qb,), one grid step per query block. The pools
  stay in HBM and the kernel copies pages itself (`make_async_copy`)
  into two VMEM halves of `G` pages each: while group g is computed,
  group g + 1 flies. The loop runs `cdiv(live pages, G)` times, so a
  block costs what its row HAS — nothing in the launch scales with
  `pages_per_seq` (= max_model_len / page_size), and a padding row is
  one grid step and no loop iteration. A grid step costs ≈ 0.15 µs on a
  v5e even when it does nothing (PERF.md §6, PR 26); with the page
  dimension in the grid a 475-token row at max_model_len 2048 paid for
  128 steps to use 30.
* THE PAGE GRID — grid (T / qb, pages_per_seq), page dimension
  innermost, pages DMA'd by Mosaic's own pipeline through a BlockSpec
  index_map (clamped at the block's last live page, so dead steps
  re-request the resident page and copy nothing). Kept ONLY for what
  Mosaic (jax 0.9.0) cannot slice for a manual copy: a `.at[page]` on a
  tiled HBM ref must leave whole tiles in the last two dims, which
  head_dim 64, packed int4 (lane dim D/2), 12 heads of a 16-bit or 8-bit
  pool, and every `[P, H]` scale plane (so every int8/int4 pool) do
  not. It shares its page body with the walk's VPU body.

Which unit multiplies (`_multiplies_on_mxu`, static shapes only). A
page is `[P, H, D]`, heads on the SUBLANE dim, and a head has ONE query
row: a per-head `q·kᵀ` wants `[H, P, D]` (a major↔sublane transpose)
and leaves the MXU no free dimension. Where the heads are whole sublane
tiles of the pool's dtype (16 of a 16-bit pool, 8 of a 32-bit one) a
page IS `[P·H, D]` with no data movement, and ALL heads go through at
once (`_mxu_body`): `q[H, D] · Kᵀ[D, G·P·H]` scores every head's query
against every head's keys, a mask keeps the columns of the row's own
head, and `p · V[G·P·H, D]` is exact because a masked weight is 0:
H − 1 of H products thrown away on a unit that had nothing to do, for
a tenth of the vector work. Everything else keeps the contraction on
the VPU (`_attend_page`): a multiply and a lane reduce a page row.

THE HEAD-MAJOR WALK (`head_major=True`) changes the layout instead, for a
model whose pools are its own: the pool is [num_pages, kv_heads,
page_size, head_dim], a page's `[page_size, head_dim]` per KV head
fills whole tiles at any head count (8 KV heads of a bf16 pool
included, which the `[H, D]` page above cannot), and the query heads
that share a KV head (GROUPED queries: q heads = kv_heads · G) are the
free dimension the MXU lacked: per KV head and group of pages,
`q[G, D] · Kᵀ[D, pages·P]` and `p[G, pages·P] · V[pages·P, D]` are two
matrix products. Rows carry a LOWER bound beside the upper one
(`kv_starts`: the first position a row sees, a sliding window's p -
W + 1): the walk starts at that position's page, so pages behind the
window are neither copied nor computed, and the table entries of pages
the cache manager freed there are never read.

Decode-only (no VJP): serving runs under no_grad. Numerics follow the
flash kernel: f32 accumulation, masked positions get -1e30,
fully-masked rows (padding tokens, kv_len 0) finalize to exact zeros.
"""
import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ragged_paged_attention"]

NEG_INF = -1e30
# VMEM for the walk's double-buffered K and V page groups
_GROUP_VMEM_BYTES = 2 * 1024 * 1024


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


def _walks_in_kernel(heads, kdim, dtype, quantized):
    """True where the kernel can copy pages itself (module docstring):
    a page slice `pool.at[page]` has to leave whole (sublane, 128-lane)
    tiles in its last two dims `[heads, kdim]` — Mosaic refuses the
    slice otherwise, for the HBM side of a manual copy as for the VMEM
    side. A 32-bit pool tiles HBM by single rows; 16- and 8-bit pools
    by 8. The `[P, H]` scale planes of a quantized pool never fill 128
    lanes."""
    rows = 1 if jnp.dtype(dtype).itemsize == 4 else 8
    return not quantized and kdim % 128 == 0 and heads % rows == 0


def _pages_per_group(page_size, heads, kdim, dtype, pages_per_seq):
    """How many pages one DMA group of the walk holds: the two halves
    of K and of V together stay within `_GROUP_VMEM_BYTES`, heads
    counted as VMEM pads them (to the dtype's sublane tile)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    page_bytes = (page_size * -(-heads // sublanes) * sublanes * kdim
                  * itemsize)
    return max(1, min(pages_per_seq, _GROUP_VMEM_BYTES // (4 * page_bytes)))


def _block_kv_lens(lens_ref, off_ref, b, qb):
    """Per-row kv lengths of block `b` and their maximum, from
    scalar-prefetch SMEM: scalar reads, unrolled over the STATIC block
    height. The frontier offset advances every LIVE row; padding rows
    (base 0) stay padding — the fused decode window's per-iteration
    frontier (one scalar per iteration, the lens vector itself stays
    window-invariant)."""
    kvlens = []
    for i in range(qb):
        base = lens_ref[b * qb + i]
        kvlens.append(jnp.where(base > 0, base + off_ref[0], 0))
    kvmax = kvlens[0]
    for kl in kvlens[1:]:
        kvmax = jnp.maximum(kvmax, kl)
    return kvlens, kvmax


def _init_stats(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finalize(o_ref, acc_ref, m_ref, l_ref):
    qb, halves = acc_ref.shape[:2]
    for i in range(qb):
        l = l_ref[i][:, :1]
        # padding rows (kv_len 0) never ran a page: l == 0 → zeros
        safe_l = jnp.where(l == 0.0, 1.0, l)
        for c in range(halves):
            o_ref[i, c] = (acc_ref[i, c] / safe_l).astype(o_ref.dtype)


def _attend_page(j, row, kvlens, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                 acc_ref, m_ref, l_ref, *, quantized):
    """Fold logical page `j` of the block's slot — held in row `row` of
    the page refs — into every row of the block. Every array touched is
    2-D `[H, ·]` with heads on the sublanes — the layout a `[P, H, D]`
    page already has per page row — so nothing is transposed, reshaped
    or concatenated in VMEM (see the module docstring)."""
    # lazy: keeps the kernel module free of the package import cycle
    from ...quantization.runtime import unpack_int4_halves

    qb, halves, heads, kdim = q_ref.shape
    page_size = k_ref.shape[1]
    scale = 1.0 / math.sqrt(halves * kdim)
    # the page, once per BLOCK: per page row p and lane half c an
    # [H, kdim] f32 tile; for a quantized pool the codes stay unscaled
    # and the per-(row, head) scale is applied to the reduced score /
    # the softmax weight instead ([H, 1] work instead of [H, D])
    kt, vt, kcol, vcol = [], [], [], []
    for p in range(page_size):
        kp, vp = k_ref[row, p], v_ref[row, p]
        if quantized == 4:
            # the ONE nibble codec (quantization.runtime), as its two
            # planes — a second copy here would have to stay
            # bit-identical with `pack_int4` forever
            kh, vh = unpack_int4_halves(kp), unpack_int4_halves(vp)
        elif quantized:
            kh, vh = (kp.astype(jnp.int32),), (vp.astype(jnp.int32),)
        else:
            kh, vh = (kp,), (vp,)
        kt.append([t.astype(jnp.float32) for t in kh])
        vt.append([t.astype(jnp.float32) for t in vh])
        if quantized:
            kcol.append(_eye_column(ks_ref[row, pl.ds(p, 1), :], heads))
            vcol.append(_eye_column(vs_ref[row, pl.ds(p, 1), :], heads))
    pos1 = jnp.zeros((heads, 1), jnp.int32) + j * page_size
    posd = jnp.zeros((heads, kdim), jnp.int32) + j * page_size

    for i in range(qb):
        kvlen = kvlens[i]

        # a row this page is entirely PAST (the block visits it because
        # a longer sibling row needs it) must not touch its (m, l,
        # acc): its scores would all be NEG_INF and exp(s - m) would
        # read exp(0) = 1 across the page
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
                # garbage (the pool is zero-initialized); their weight
                # is exactly 0, but zero the v rows anyway so no
                # accidental inf·0 can form
                live = posd + p < kvlen
                for c in range(halves):
                    acc[c] = acc[c] + w * jnp.where(live, vt[p][c], 0.0)
            for c in range(halves):
                acc_ref[i, c] = acc[c]
            m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _walk_kernel(sid_ref, pt_ref, lens_ref, off_ref, q_ref, k_hbm, v_hbm,
                 o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
                 pages_per_seq, group, body):
    """One grid step = one query block; the walk over its slot's live
    pages is the loop in here. `k_buf`/`v_buf` are `[2·G, P, H, D]`
    (`_mxu_body`: `[2·G, P·H, D]`): two halves of `G` pages, one DMA
    semaphore a half. `body` (`_vpu_body` / `_mxu_body`, static) folds
    a copied group into the block's statistics."""
    b = pl.program_id(0)
    qb, _, heads, _ = q_ref.shape
    page_size = math.prod(k_buf.shape[1:-1]) // heads
    kvlens, kvmax = _block_kv_lens(lens_ref, off_ref, b, qb)
    # pages past the LONGEST row's prefix contribute to no row: the
    # walk ends there (padding rows have kvlen 0, so an all-padding
    # block walks nothing). A slot has `pages_per_seq` table entries
    # and no more, whatever a length claims.
    n_pages = jnp.minimum((kvmax + (page_size - 1)) // page_size,
                          pages_per_seq)
    n_groups = (n_pages + (group - 1)) // group
    # one slot per block (the slot-major contract): the block's first
    # row names it
    table = sid_ref[b * qb] * pages_per_seq

    def live_in(g):
        # a page of the last group that lies past the row is neither
        # copied nor computed
        return jnp.minimum(group, n_pages - g * group)

    def group_copies(g, half, start):
        """Start (or wait for) the copies of group `g`'s live pages
        into buffer half `half`. A wait only needs the copy's SHAPE, so
        it names page 0 instead of reading the table again."""
        def one(i, carry):
            page = pt_ref[table + g * group + i] if start else 0
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                dma = pltpu.make_async_copy(
                    hbm.at[page], buf.at[half * group + i], sems.at[half])
                dma.start() if start else dma.wait()
            return carry

        jax.lax.fori_loop(0, live_in(g), one, None)

    _init_stats(acc_ref, m_ref, l_ref)
    attend = body(b, n_pages, kvlens, q_ref, k_buf, v_buf, acc_ref, m_ref,
                  l_ref, group=group, page_size=page_size)

    @pl.when(n_groups > 0)
    def _first():
        group_copies(0, 0, start=True)

    def one_group(g, carry):
        half = g % 2

        # the next group's pages fly while this group's are computed
        @pl.when(g + 1 < n_groups)
        def _next():
            group_copies(g + 1, 1 - half, start=True)

        group_copies(g, half, start=False)
        attend(g, half, live_in(g))
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, None)
    _finalize(o_ref, acc_ref, m_ref, l_ref)


def _page_grid_kernel(sid_ref, pt_ref, lens_ref, off_ref, q_ref, k_ref,
                      v_ref, *rest, quantized):
    """One grid step = (query block b, logical page j of its slot);
    the page's block `[1, P, H, D]` was DMA'd by the pipeline."""
    # int8/int4 pools ride with per-row fp32 scale planes, gathered
    # through the SAME page_map (quantization runtime, PT_KV_DTYPE);
    # quantized == 4 marks packed nibbles (pool lane dim D/2)
    ks_ref, vs_ref = rest[:2] if quantized else (None, None)
    o_ref, acc_ref, m_ref, l_ref = rest[-4:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    kvlens, kvmax = _block_kv_lens(lens_ref, off_ref, b, q_ref.shape[0])

    @pl.when(j == 0)
    def _init():
        _init_stats(acc_ref, m_ref, l_ref)

    # pages past the LONGEST row's prefix contribute to no row — skip
    # (padding rows have kvlen 0, so an all-padding block skips every
    # page)
    @pl.when(j * k_ref.shape[1] < kvmax)
    def _page():
        _attend_page(j, 0, kvlens, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                     acc_ref, m_ref, l_ref, quantized=quantized)

    @pl.when(j == pl.num_programs(1) - 1)
    def _last():
        _finalize(o_ref, acc_ref, m_ref, l_ref)


# VMEM for the head-major walk's double-buffered K and V page groups.
# Larger is faster up to what compiles: a group costs a fixed few
# microseconds beside its pages (at 0.5 / 1 / 2 / 4 / 8 MiB a decode row
# over a full context read 20 / 33 / 38 / 53-58 / 61 % of 819 GB/s on a
# v5e, PERF.md §6, PR 28); 16 MiB no longer fits the scoped VMEM.
_GQA_GROUP_VMEM_BYTES = 8 * 1024 * 1024


def _gqa_walks(page_size, kdim, dtype):
    """True where the head-major walk can copy a page `[KV, P, D]` into
    its place in a group buffer: `[P, D]` must be whole (sublane,
    128-lane) tiles of the pool's dtype."""
    rows = 8 * (4 // jnp.dtype(dtype).itemsize)
    return kdim % 128 == 0 and page_size % rows == 0


def _gqa_walk_kernel(sid_ref, pt_ref, lens_ref, starts_ref, off_ref, q_ref,
                     k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, acc_ref,
                     m_ref, l_ref, *, pages_per_seq, group):
    """One grid step = one query block: `q_ref` [qb, KV, G, D] (qb rows
    of ONE slot; the G query heads of each KV head padded to whole
    sublane tiles), the walk over the block's live pages [the earliest
    row's first position's page, the longest row's last page] the loop
    in here; each row masks at its own bounds. `k_buf`/`v_buf` are
    `[2, KV, group·P, D]`: two halves, in each the group's pages side
    by side per KV head, so that a head's keys are ONE `[group·P, D]`
    operand and its queries one `[qb·G, D]`."""
    b = pl.program_id(0)
    qb, kv_heads, gp, dim = q_ref.shape
    rows = k_buf.shape[2]
    page_size = rows // group
    scale = 1.0 / math.sqrt(dim)
    # stated, not inherited: a process-wide "highest" (the test suite's)
    # is no precision Mosaic has for 16-bit operands
    precision = (jax.lax.Precision.HIGHEST
                 if k_buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    # per-row bounds from SMEM (static unroll); a padding row (length
    # 0) bounds nothing
    kvlens, starts = [], []
    for i in range(qb):
        base = lens_ref[b * qb + i]
        kvlens.append(jnp.where(base > 0, base + off_ref[0], 0))
        starts.append(jnp.maximum(jnp.where(
            base > 0, starts_ref[b * qb + i] + off_ref[0], 0), 0))
    kvmax = kvlens[0]
    first = jnp.where(kvlens[0] > 0, starts[0], 2 ** 30)
    for i in range(1, qb):
        kvmax = jnp.maximum(kvmax, kvlens[i])
        first = jnp.minimum(first, jnp.where(kvlens[i] > 0, starts[i],
                                             2 ** 30))
    first_page = jnp.where(kvmax > 0, first, 0) // page_size
    n_pages = jnp.maximum(
        jnp.minimum((kvmax + (page_size - 1)) // page_size, pages_per_seq)
        - first_page, 0)
    n_groups = (n_pages + (group - 1)) // group
    # one slot per block (the slot-major contract): its first row names it
    table = sid_ref[b * qb] * pages_per_seq + first_page

    # a group's dead tail (pages past the block) is never copied: what
    # it multiplies (weight exactly 0) must be finite, so the buffers
    # start from zeros; later they only ever hold pool pages
    @pl.when(b == 0)
    def _zero():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def live_in(g):
        return jnp.minimum(group, n_pages - g * group)

    def group_copies(g, half, start_copy):
        def one(i, carry):
            page = pt_ref[table + g * group + i] if start_copy else 0
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                dma = pltpu.make_async_copy(
                    hbm.at[page],
                    buf.at[half, :, pl.ds(i * page_size, page_size), :],
                    sems.at[half])
                dma.start() if start_copy else dma.wait()
            return carry

        jax.lax.fori_loop(0, live_in(g), one, None)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    # each matmul row's own bounds: row r belongs to block row r // gp
    row_of = jax.lax.broadcasted_iota(jnp.int32, (qb * gp, rows), 0) // gp
    lo = jnp.zeros((qb * gp, rows), jnp.int32)
    hi = jnp.zeros((qb * gp, rows), jnp.int32)
    for i in range(qb):
        lo = jnp.where(row_of == i, starts[i], lo)
        hi = jnp.where(row_of == i, kvlens[i], hi)

    @pl.when(n_groups > 0)
    def _first():
        group_copies(0, 0, True)

    def one_group(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            group_copies(g + 1, 1 - half, True)

        group_copies(g, half, False)
        pos = (first_page + g * group) * page_size \
            + jax.lax.broadcasted_iota(jnp.int32, (qb * gp, rows), 1)
        valid = (pos >= lo) & (pos < hi)
        for h in range(kv_heads):
            k = k_buf[half, h]                       # [group·P, D]
            v = v_buf[half, h]
            q = q_ref[:, h].reshape(qb * gp, dim)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h][:, :1]
            l_prev = l_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, None)
    for h in range(kv_heads):
        l = l_ref[h][:, :1]
        # padding rows (kv_len 0) attended nothing: l == 0 -> zeros
        o_ref[:, h] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype).reshape(qb, gp, dim)


@functools.lru_cache(maxsize=None)
def _gqa_paged_call(q_shape, q_dtype, pool_shape, pool_dtype, pages_per_seq,
                    qb, interpret):
    """The head-major launch for one set of static shapes, ONE jitted
    function a set (as `_paged_call`)."""
    tokens, heads, dim = q_shape
    _, kv_heads, page_size, kdim = pool_shape
    if heads % kv_heads or kdim != dim:
        raise ValueError(
            f"grouped queries need q heads ({heads}) a multiple of the "
            f"pool's KV heads ({kv_heads}) and one head_dim ({dim} / "
            f"{kdim})")
    if not _gqa_walks(page_size, kdim, pool_dtype):
        raise ValueError(
            f"a head-major {jnp.dtype(pool_dtype).name} pool needs "
            f"head_dim % 128 == 0 and pages of whole sublane tiles; got "
            f"page_size {page_size}, head_dim {kdim}")
    if tokens % qb:
        raise ValueError(f"{tokens} rows are not whole blocks of {qb}")
    g = heads // kv_heads
    tile = 8 * (4 // jnp.dtype(q_dtype).itemsize)
    gp = -(-g // tile) * tile          # the group, in whole sublane tiles
    page_bytes = kv_heads * page_size * kdim * jnp.dtype(pool_dtype).itemsize
    group = max(1, min(pages_per_seq,
                       _GQA_GROUP_VMEM_BYTES // (4 * page_bytes)))
    q_block = (qb, kv_heads, gp, kdim)

    def q_map(b, *_):
        return (b, 0, 0, 0)

    q_spec = pl.BlockSpec(q_block, q_map)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    buf = pltpu.VMEM((2, kv_heads, group * page_size, kdim), pool_dtype)
    launch = pl.pallas_call(
        functools.partial(_gqa_walk_kernel, pages_per_seq=pages_per_seq,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(tokens // qb,),
            in_specs=[q_spec, hbm, hbm], out_specs=q_spec,
            scratch_shapes=[
                buf, buf, pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv_heads, qb * gp, kdim), jnp.float32),
                pltpu.VMEM((kv_heads, qb * gp, 128), jnp.float32),
                pltpu.VMEM((kv_heads, qb * gp, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, kv_heads, gp, kdim),
                                       q_dtype),
        interpret=interpret,
    )

    def call(sid, table, lens, starts, off, q, k_pool, v_pool):
        q4 = q.reshape(tokens, kv_heads, g, dim)
        if gp != g:
            q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        out = launch(sid, table, lens, starts, off, q4, k_pool, v_pool)
        return out[:, :, :g].reshape(tokens, heads, dim)

    return jax.jit(call, inline=True)


def ragged_paged_attention(q, k_pool, v_pool, page_tables, slot_ids,
                           kv_lens, k_scales=None, v_scales=None,
                           frontier_offset=None, q_per_slot=None,
                           interpret=False, kv_starts=None,
                           head_major=False):
    """q [T, H, D], pools [N, P, H, D], page_tables [S, MP] int,
    slot_ids [T] int, kv_lens [T] int → out [T, H, D].

    head_major (STATIC): the pools are [N, KV, P, D] and the H query
    heads share the KV heads in groups of H / KV (query head j reads KV
    head j // (H / KV)): the head-major walk of the module docstring.
    Float pools only. kv_starts [T] int (with head_major): the first
    position each row sees (may be negative: clamped at 0); the frontier
    offset advances it with kv_lens. None: every row sees from position
    0. `q_per_slot` there is a CONTRACT and no hint: T is whole blocks
    of that many rows, every block's rows are one slot's (padding rows,
    length 0, only behind its live rows), and the block's pages are
    copied once and multiplied as `[q_per_slot · G, D]` queries.

    frontier_offset: optional scalar int32 added to every NONZERO
    kv_lens row (rides scalar-prefetch SMEM like the page table). The
    fused multi-token decode window passes its scan iteration here so
    one loop-invariant lens vector serves every iteration — rows with
    base 0 (padding / finished) keep skipping all pages.

    k_scales/v_scales [N, P, H] fp32: per-row dequant scales of INT8
    pools (quantization runtime). They are gathered through the same
    page-table index_map as the pools (the page grid: module docstring)
    and the dequant happens in VMEM after the DMA, so HBM traffic for
    the cache stays int8 — the whole point of the quantized pool (page
    bytes ≈ ×4 down vs fp32).

    q_per_slot: optional STATIC int — the caller's guarantee that the
    T query rows are slot-major contiguous blocks of exactly this many
    rows, one slot per block (the speculative VERIFY layout: k+1 rows
    per slot). A query block becomes q_per_slot rows: each slot's
    pages are DMA'd once per BLOCK instead of once per row,
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
    if frontier_offset is None:
        frontier_offset = 0
    if head_major:
        if k_scales is not None:
            raise ValueError("the head-major walk takes float pools")
        if kv_starts is None:
            # no lower bound: far enough below 0 that the frontier
            # offset never lifts it above
            kv_starts = jnp.full((tokens,), -(2 ** 30), jnp.int32)
        call = _gqa_paged_call(q.shape, q.dtype, k_pool.shape,
                               k_pool.dtype, page_tables.shape[1],
                               int(q_per_slot or 1), interpret)
        return call(jnp.asarray(slot_ids, jnp.int32),
                    jnp.asarray(page_tables, jnp.int32).reshape(-1),
                    jnp.asarray(kv_lens, jnp.int32),
                    jnp.asarray(kv_starts, jnp.int32),
                    jnp.asarray(frontier_offset, jnp.int32).reshape((1,)),
                    q, k_pool, v_pool)
    if kv_starts is not None:
        raise ValueError("kv_starts needs head_major pools")
    _, page_size, _, kdim = k_pool.shape
    quantized = 0
    if k_scales is not None:
        quantized = 4 if kdim * 2 == dim else 8
    qb = 1
    if q_per_slot is not None and tokens % int(q_per_slot) == 0:
        qb = int(q_per_slot)
    scales = (k_scales, v_scales) if quantized else ()
    call = _paged_call(q.shape, q.dtype, k_pool.shape, k_pool.dtype,
                       page_tables.shape[1], quantized, qb, interpret)
    for sites in _open_site_counts.stack:
        sites[call.body] += 1
    return call(jnp.asarray(slot_ids, jnp.int32),
                jnp.asarray(page_tables, jnp.int32).reshape(-1),
                jnp.asarray(kv_lens, jnp.int32),
                jnp.asarray(frontier_offset, jnp.int32).reshape((1,)),
                q, k_pool, v_pool, *scales)


class _OpenSiteCounts(threading.local):
    """The `launch_sites()` blocks open on this thread."""

    def __init__(self):
        self.stack = []


_open_site_counts = _OpenSiteCounts()


@contextlib.contextmanager
def launch_sites():
    """Which body the page-major launches traced inside the block run:
    yields `{"mxu": n, "vpu": n}`, one count a CALL SITE (a launch
    inside a scan body counts once, however long the scan). The body is
    static a launch (`_multiplies_on_mxu`), so a step program traced
    inside the block knows how many launches of each one dispatch
    makes; host integers, nothing on the device."""
    sites = {"mxu": 0, "vpu": 0}
    _open_site_counts.stack.append(sites)
    try:
        yield sites
    finally:
        _open_site_counts.stack.remove(sites)


@functools.lru_cache(maxsize=None)
def _paged_call(q_shape, q_dtype, pool_shape, pool_dtype, pages_per_seq,
                quantized, qb, interpret):
    """The launch for one set of static shapes, as ONE jitted function:
    a model calls the kernel once a layer with the same shapes, and a
    fresh `pallas_call` would trace the kernel body again at every
    site (0.5–0.9 s each on the v5e's host, 48 sites in the decode
    cell's two programs: PERF.md §6, PR 26). `inline=True` leaves the
    caller's jaxpr and HLO as they were — one `pallas_call` a site."""
    tokens, heads, dim = q_shape
    _, page_size, _, kdim = pool_shape
    # packed int4 splits head_dim into its two nibble planes: q and out
    # ride as [T, 2, H, D/2] so each plane sits at lane offset 0 in the
    # kernel (no lane slice / concatenate in VMEM); float and int8
    # pools are the halves == 1 case of the same layout
    halves = dim // kdim
    q_block = (qb, halves, heads, kdim)
    stats = [
        pltpu.VMEM(q_block, jnp.float32),            # accumulator
        pltpu.VMEM((qb, heads, 128), jnp.float32),   # running max
        pltpu.VMEM((qb, heads, 128), jnp.float32),   # running sum
    ]

    def q_map(b, *_):
        return (b, 0, 0, 0)

    q_spec = pl.BlockSpec(q_block, q_map)
    mxu = _multiplies_on_mxu(heads, kdim, pool_dtype, quantized)
    if _walks_in_kernel(heads, kdim, pool_dtype, quantized):
        group = _pages_per_group(page_size, heads, kdim, pool_dtype,
                                 pages_per_seq)
        kernel = functools.partial(
            _walk_kernel, pages_per_seq=pages_per_seq, group=group,
            body=_mxu_body if mxu else _vpu_body)
        grid = (tokens // qb,)
        hbm = pl.BlockSpec(memory_space=pltpu.HBM)
        in_specs = [q_spec, hbm, hbm]
        # the MXU body's operands are a page's `[P·H, D]` rows
        page = ((page_size * heads, kdim) if mxu
                else (page_size, heads, kdim))
        scratch = [pltpu.VMEM((2 * group, *page), pool_dtype)] * 2
        scratch += [pltpu.SemaphoreType.DMA((2,))] + stats
    else:
        kernel = functools.partial(_page_grid_kernel, quantized=quantized)
        grid = (tokens // qb, pages_per_seq)

        def _blk_page(b, j, sid, pt, lens, offv):
            # clamp j to the LAST live page any row of block b needs
            # (index_map twin of the kernel's kvmax): grid steps past
            # the valid prefix re-request the same block, so Mosaic
            # elides their HBM→VMEM copy (the compute is already
            # pl.when-gated)
            _, eff_max = _block_kv_lens(lens, offv, b, qb)
            last = jnp.maximum(eff_max - 1, 0) // page_size
            # one slot per block (the slot-major contract): the block's
            # first row names it
            return pt[sid[b * qb] * pages_per_seq + jnp.minimum(j, last)]

        def page_map(b, j, *prefetch):
            return (_blk_page(b, j, *prefetch), 0, 0, 0)

        def scale_map(b, j, *prefetch):
            return (_blk_page(b, j, *prefetch), 0, 0)

        page = pl.BlockSpec((1, page_size, heads, kdim), page_map)
        in_specs = [q_spec, page, page]
        if quantized:
            in_specs += [pl.BlockSpec((1, page_size, heads), scale_map)] * 2
        scratch = stats

    launch = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid, in_specs=in_specs,
            out_specs=q_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((tokens, halves, heads, kdim),
                                       q_dtype),
        interpret=interpret,
    )

    def call(sid, table, lens, off, q, *pools):
        q4 = jnp.swapaxes(q.reshape(tokens, heads, halves, kdim), 1, 2)
        if mxu:
            # `[N, P, H, D]` as `[N, P·H, D]`: whole sublane tiles of
            # heads, so the same bytes in the same order (a bitcast)
            pools = [x.reshape(-1, page_size * heads, kdim) for x in pools]
        out = launch(sid, table, lens, off, q4, *pools)
        return jnp.swapaxes(out, 1, 2).reshape(tokens, heads, dim)

    jitted = jax.jit(call, inline=True)
    jitted.body = "mxu" if mxu else "vpu"
    return jitted


# ---- the page-major walk's two bodies ---------------------------------
# Below the head-major section on purpose: a Pallas call's serialized
# body keeps its source lines (PERF.md §6, PRs 25 and 28), and lines
# that move above `_gqa_walk_kernel` compile its step programs again.

def _multiplies_on_mxu(heads, kdim, dtype, quantized):
    """True where the walk's body is `_mxu_body`: a pool the walk takes
    whose heads are whole sublane tiles of its dtype (16 of a 16-bit
    pool, 8 of a 32-bit one), so that a page `[P, H, D]` IS `[P·H, D]`
    (module docstring)."""
    tile = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (_walks_in_kernel(heads, kdim, dtype, quantized)
            and heads % tile == 0)


def _vpu_body(b, n_pages, kvlens, q_ref, k_buf, v_buf, acc_ref, m_ref,
              l_ref, *, group, page_size):
    """The walk's body a page at a time on the VPU (`_attend_page`, the
    page grid's too), over the group's live pages only."""
    def attend(g, half, n_live):
        def one_page(i, c):
            _attend_page(g * group + i, half * group + i, kvlens, q_ref,
                         k_buf, v_buf, None, None, acc_ref, m_ref, l_ref,
                         quantized=0)
            return c

        jax.lax.fori_loop(0, n_live, one_page, None)

    return attend


def _mxu_body(b, n_pages, kvlens, q_ref, k_buf, v_buf, acc_ref, m_ref,
              l_ref, *, group, page_size):
    """The walk's body a GROUP at a time on the MXU, all heads at once
    (module docstring). Matmul row r is head r % H of block row r // H;
    column c of a group is head c % H of the group's token c // H."""
    qb, _, heads, dim = q_ref.shape
    rows, cols = qb * heads, group * page_size * heads
    scale = 1.0 / math.sqrt(dim)
    # operands as stored, f32 accumulation; stated, not inherited: a
    # process-wide "highest" (the test suite's) is no precision Mosaic
    # has for 16-bit operands
    dt = jnp.promote_types(q_ref.dtype, k_buf.dtype)
    precision = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    # a last group's dead pages are never copied: what their columns
    # multiply (weight exactly 0) must be finite, so the buffers start
    # from zeros; later they only ever hold pool pages
    @pl.when(b == 0)
    def _zero():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    # once a block, outside the group loop: the column's token offset in
    # its group where the heads match, a position no length reaches
    # where they do not, so ONE compare a group decides both; and each
    # matmul row's own length (the table holds `n_pages` and no more)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    head_pos = jnp.where(col % heads == row % heads, col // heads, 2 ** 30)
    block_row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // heads
    kvlen = jnp.zeros((rows, 1), jnp.int32)
    for i in range(qb):
        kvlen = jnp.where(block_row == i, kvlens[i], kvlen)
    kvlen = jnp.minimum(kvlen, n_pages * page_size)

    def attend(g, half, n_live):
        q = q_ref[:, 0].reshape(rows, dim).astype(dt)
        k = k_buf[pl.ds(half * group, group)].reshape(cols, dim).astype(dt)
        v = v_buf[pl.ds(half * group, group)].reshape(cols, dim).astype(dt)
        valid = head_pos < kvlen - g * (group * page_size)
        # scaled in f32 AFTER the product: q is not rounded again
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...].reshape(rows, 128)[:, :1]
        l_prev = l_ref[...].reshape(rows, 128)[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row that has attended nothing yet (a padding row beside
        # live ones) still has m_new == NEG_INF, and exp(s - m_new)
        # would read 1 across it: the mask again
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # the one rounding the VPU body has not: p to the operands' dtype
        acc = alpha * acc_ref[:, 0].reshape(rows, dim) + jax.lax.dot_general(
            p.astype(dt), v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        acc_ref[:, 0] = acc.reshape(qb, heads, dim)
        m_ref[...] = jnp.broadcast_to(m_new, (rows, 128)).reshape(m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, (rows, 128)).reshape(l_ref.shape)

    return attend


# ---- the latent walk ---------------------------------------------------
# At the end of the file on purpose (see the note above the page-major
# walk's bodies): nothing above moves.
#
# A LATENT pool is `[num_pages, page_size, R]`: ONE row a token with no
# head axis (multi-head latent attention keeps `[c | k_rope]`, R = 576,
# and every head reads it). Keys AND values come from that row: a head's
# score is its ABSORBED query `[q_nope · W_UKᵀ | q_rope]` (R wide) against
# the row, its value the row's first `v_dim` lanes; the caller projects
# the `[H, v_dim]` result up afterwards. All heads share every row, so
# the MXU's free dimension is `rows · H` with no mask between heads:
# per group of pages `q[qb·H, R] · rowsᵀ[R, G·P]`, a lane-dense softmax,
# `p · rows[:, :v_dim]`.

# the scoped VMEM the latent walk asks for: its score tile `[qb·H,
# group·P]` and accumulator `[qb·H, v_dim]` in float32 beside the two
# page halves outgrow the 16 MiB default at 16 rows of 64 heads
_LATENT_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _latent_group_tokens(qb):
    """Tokens a DMA group of the latent walk holds (two such halves of
    `[tokens, R]` in VMEM), a static function of the block's rows. A
    group costs a fixed few microseconds beside its bytes, so a lone
    row (the fused window's launch: bound by the copies) reads best at
    2 048 (28.7 / 35.9 / 39.9 / 41.4 / 41.3 % of 819 GB/s at 256 / 512 /
    1 024 / 2 048 / 4 096 tokens, 32 rows at 13 k of context on a v5e);
    a block of several rows (the tick: bound by the MXU) at 1 024, whose
    score tile is half as large (3.55 against 4.34 ms at the start of a
    prompt, 18.10 against 18.16 ms at 15 k: PERF.md §6, PR 33, step 0)."""
    return 2048 if qb == 1 else 1024


def _latent_walk_kernel(sid_ref, pt_ref, lens_ref, off_ref, q_ref, pool_hbm,
                        o_ref, buf, sems, acc_ref, m_ref, l_ref, *,
                        pages_per_seq, group, v_dim, scale):
    """One grid step = one query block: `q_ref` [qb, H, R] (qb rows of
    ONE slot, live rows first), the walk over the slot's live pages the
    loop in here. `buf` is `[2, group·P, R]`: two halves, in each the
    group's pages one under the other, so the group's rows are ONE
    `[group·P, R]` operand. A block whose rows past the first are all
    padding (a decoding row in the tick's slot-block layout) multiplies
    its one live row's `[H, R]` alone."""
    b = pl.program_id(0)
    qb, heads, dim = q_ref.shape
    rows = buf.shape[1]
    page_size = rows // group
    precision = (jax.lax.Precision.HIGHEST if buf.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    kvlens, kvmax = _block_kv_lens(lens_ref, off_ref, b, qb)
    n_pages = jnp.minimum((kvmax + (page_size - 1)) // page_size,
                          pages_per_seq)
    n_groups = (n_pages + (group - 1)) // group
    table = sid_ref[b * qb] * pages_per_seq

    # a last group's dead pages are never copied: what they multiply
    # (weight exactly 0) must be finite
    @pl.when(b == 0)
    def _zero():
        buf[...] = jnp.zeros_like(buf)

    def live_in(g):
        return jnp.minimum(group, n_pages - g * group)

    def group_copies(g, half, start):
        def one(i, carry):
            page = pt_ref[table + g * group + i] if start else 0
            dma = pltpu.make_async_copy(
                pool_hbm.at[page],
                buf.at[half, pl.ds(i * page_size, page_size), :],
                sems.at[half])
            dma.start() if start else dma.wait()
            return carry

        jax.lax.fori_loop(0, live_in(g), one, None)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def attend_rows(n):
        """The group's fold for the block's first `n` rows (static)."""
        mm_rows = n * heads
        block_row = jax.lax.broadcasted_iota(
            jnp.int32, (mm_rows, 1), 0) // heads
        kvlen = jnp.zeros((mm_rows, 1), jnp.int32)
        for i in range(n):
            kvlen = jnp.where(block_row == i, kvlens[i], kvlen)
        kvlen = jnp.minimum(kvlen, n_pages * page_size)
        col = jax.lax.broadcasted_iota(jnp.int32, (mm_rows, rows), 1)

        def attend(g, half):
            q = q_ref[:n].reshape(mm_rows, dim)
            kv = buf[half]                                   # [rows, R]
            valid = col < kvlen - g * rows
            s = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[:mm_rows, :1]
            l_prev = l_ref[:mm_rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:mm_rows] = alpha * acc_ref[:mm_rows] \
                + jax.lax.dot_general(
                    p.astype(kv.dtype), kv[:, :v_dim],
                    (((1,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)
            m_ref[:mm_rows] = jnp.broadcast_to(m_new, (mm_rows, 128))
            l_ref[:mm_rows] = jnp.broadcast_to(l_new, (mm_rows, 128))

        return attend

    attend_one = attend_rows(1)
    if qb > 1:
        attend_all = attend_rows(qb)
        others = kvlens[1]
        for kl in kvlens[2:]:
            others = jnp.maximum(others, kl)

    @pl.when(n_groups > 0)
    def _first():
        group_copies(0, 0, True)

    def one_group(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _next():
            group_copies(g + 1, 1 - half, True)

        group_copies(g, half, False)
        if qb == 1:
            attend_one(g, half)
        else:
            @pl.when(others == 0)
            def _single():
                attend_one(g, half)

            @pl.when(others > 0)
            def _block():
                attend_all(g, half)
        return carry

    jax.lax.fori_loop(0, n_groups, one_group, None)
    l = l_ref[:, :1]
    # padding rows (kv_len 0) attended nothing: l == 0 -> zeros
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype).reshape(o_ref.shape)


def _latent_walks(page_size, dtype):
    """True where the latent walk can copy a page `[P, R]` into its
    place in a group buffer: whole sublane tiles of the pool's dtype."""
    return page_size % (8 * (4 // jnp.dtype(dtype).itemsize)) == 0


@functools.lru_cache(maxsize=None)
def _latent_paged_call(q_shape, q_dtype, pool_shape, pool_dtype,
                       pages_per_seq, qb, v_dim, scale, group_tokens,
                       interpret):
    """The latent launch for one set of static shapes, ONE jitted
    function a set (as `_paged_call`)."""
    tokens, heads, dim = q_shape
    _, page_size, row = pool_shape
    if row != dim or not 0 < v_dim <= row:
        raise ValueError(
            f"absorbed queries are as wide as the pool's row ({dim} / "
            f"{row}) and values its first v_dim ({v_dim}) lanes")
    if not _latent_walks(page_size, pool_dtype):
        raise ValueError(
            f"a latent {jnp.dtype(pool_dtype).name} pool needs pages of "
            f"whole sublane tiles; got page_size {page_size}")
    if tokens % qb:
        raise ValueError(f"{tokens} rows are not whole blocks of {qb}")
    group = max(1, min(pages_per_seq, int(group_tokens) // page_size))

    q_spec = pl.BlockSpec((qb, heads, dim), lambda b, *_: (b, 0, 0))
    o_spec = pl.BlockSpec((qb, heads, v_dim), lambda b, *_: (b, 0, 0))
    launch = pl.pallas_call(
        functools.partial(_latent_walk_kernel, pages_per_seq=pages_per_seq,
                          group=group, v_dim=v_dim, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tokens // qb,),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((2, group * page_size, row), pool_dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((qb * heads, v_dim), jnp.float32),
                pltpu.VMEM((qb * heads, 128), jnp.float32),
                pltpu.VMEM((qb * heads, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, heads, v_dim), q_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LATENT_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    jitted = jax.jit(launch, inline=True)
    jitted.body = "latent"
    return jitted


def latent_paged_attention(q, pool, page_tables, slot_ids, kv_lens, v_dim,
                           scale, frontier_offset=None, q_per_slot=None,
                           group_tokens=None, interpret=False):
    """q [T, H, R] absorbed queries, pool [N, P, R] latent rows,
    page_tables [S, MP], slot_ids / kv_lens [T] → out [T, H, v_dim]:
    softmax(q · row · scale) over the row's slot's positions below
    kv_len, times the rows' first `v_dim` lanes. `frontier_offset` and
    `q_per_slot` as `ragged_paged_attention`: the latter a CONTRACT
    (every block of that many rows is one slot's, its live rows first).
    `scale` and `v_dim` are static; `group_tokens` overrides
    `_latent_group_tokens` (the sweep's and the tests')."""
    if frontier_offset is None:
        frontier_offset = 0
    call = _latent_paged_call(
        q.shape, q.dtype, pool.shape, pool.dtype, page_tables.shape[1],
        int(q_per_slot or 1), int(v_dim), float(scale),
        int(group_tokens or _latent_group_tokens(int(q_per_slot or 1))),
        interpret)
    for sites in _open_site_counts.stack:
        sites[call.body] = sites.get(call.body, 0) + 1
    return call(jnp.asarray(slot_ids, jnp.int32),
                jnp.asarray(page_tables, jnp.int32).reshape(-1),
                jnp.asarray(kv_lens, jnp.int32),
                jnp.asarray(frontier_offset, jnp.int32).reshape((1,)),
                q, pool)


# ---- the latent walk, EXPANDED -----------------------------------------
# Below the absorbed walk on purpose (nothing above moves).
#
# The same attention in its other form, for a RUN of one slot's rows at
# consecutive positions (a prefill chunk): the absorbed form pays
# 2·(2·latent + rope) FLOPs a head a row attended, the expanded form
# 2·(nope + rope + v) plus the up-projection 2·latent·(nope + v) a head a
# cached row ONCE A RUN: at 64 heads of 128 + 64 / 128 over a latent of
# 512 the two cross at 171 rows. One grid step holds a few heads'
# queries of EVERY run of the launch (`[total, nope + rope']` a head)
# and, per run, walks the slot's live pages in tiles of tokens with the
# absorbed walk's double-buffered copies; a tile is up-projected in VMEM
# for each head of the step (`k_nope = c · W_UKᵀ`, `v = c · W_UV`, rounded
# to the pool's dtype as the eager forward rounds them) and then meets
# the run's rows a SUB-BLOCK at a time: scores formed transposed `[tile,
# sub]` (max and sum are lane-major rows, reduced over sublanes: what
# the resident flash kernel learned, PERF.md §6, PR 31) and the
# accumulator transposed with them (`vᵀ[v, tile] · p[tile, sub]`: no
# transpose of p or of its rescale a tile), no mask where the
# sub-block's first row sees the whole tile, nothing at all where its
# last row sees none of it. The score tile never leaves VMEM.

# a run starts at a multiple of this many laid-out rows (a sublane tile
# of a 16-bit query, two of a 32-bit one), so a sub-block is read and
# written at an aligned row wherever its run starts
LATENT_EXPANDED_ROW_ALIGN = 16


def latent_expanded_tiles():
    """(rows a sub-block, tokens a tile) of the expanded walk. One
    slot's 2 016 rows ending at 13 312, bf16, 64 heads, 4 a grid step,
    ms a layer on a v5e, the absorbed walk's 26.07 beside them: (256,
    512) 11.60, (256, 1 024) 10.80, (256, 2 048) 10.76, (512, 512)
    11.04, (512, 1 024) 10.72, (512, 2 048) 12.07, (1 024, 512) 11.09,
    (1 024, 1 024) 11.54, (1 024, 2 048) 15.31; from the start of a
    prompt (992 rows) 0.70–0.78 to a tile of 1 024, 1.18–2.01 at 2 048:
    PERF.md §6, PR 34, step 0."""
    return 512, 1024


def _latent_expanded_kernel(slot_ref, row0_ref, first_ref, rows_ref, pt_ref,
                            q_ref, wuk_ref, wuv_ref, pool_hbm, o_ref, buf,
                            sems, acc_ref, m_ref, l_ref, *, pages_per_seq,
                            tile_pages, sub, latent, scale):
    """One grid step = `hh` heads of every run. `q_ref` [total, hh ·
    (nope + rope')] (rope' the rotary lanes padded with zeros to the
    pool row's lanes past the latent), `wuk_ref` [hh, nope, latent],
    `wuv_ref` [hh, v, latent], `o_ref` [total, hh·v]. Run r (scalar
    prefetch), in the order of their rows: slot `slot_ref[r]`,
    `rows_ref[r]` live rows (0: no run) from row `row0_ref[r]` (a
    multiple of `LATENT_EXPANDED_ROW_ALIGN`), row i of it at kv length
    `first_ref[r] + i`. A run's last sub-block runs past its rows (over
    the next run's, which that run then writes, or over rows nobody
    reads). `buf` [2, tile, R]: two halves, in each the tile's pages
    one under the other."""
    g = pl.program_id(0)
    hh, nope, vd = wuk_ref.shape[0], wuk_ref.shape[1], wuv_ref.shape[1]
    qd = q_ref.shape[1] // hh
    tile = buf.shape[1]
    page_size = tile // tile_pages
    dt = buf.dtype
    precision = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def dot(a, b, contract):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=precision,
                                   preferred_element_type=jnp.float32)

    nt, nn = ((1,), (1,)), ((1,), (0,))     # a · bᵀ, a · b

    # a last tile's dead pages are never copied: what they multiply
    # (weight exactly 0) must be finite
    @pl.when(g == 0)
    def _zero():
        buf[...] = jnp.zeros_like(buf)

    # a transposed score's kv position in its tile less its row in its
    # sub-block: the causal mask is ONE compare with a scalar
    ahead = (jax.lax.broadcasted_iota(jnp.int32, (tile, sub), 0)
             - jax.lax.broadcasted_iota(jnp.int32, (tile, sub), 1))

    def one_run(r, carry):
        n = rows_ref[r]

        @pl.when(n > 0)
        def _run():
            first = first_ref[r]
            table = slot_ref[r] * pages_per_seq
            row0 = row0_ref[r]
            nsb = (n + (sub - 1)) // sub
            n_pages = jnp.minimum(
                (first + n - 1 + (page_size - 1)) // page_size,
                pages_per_seq)
            n_tiles = (n_pages + (tile_pages - 1)) // tile_pages
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

            def rows_of(s):
                return pl.ds(pl.multiple_of(
                    row0 + s * sub, LATENT_EXPANDED_ROW_ALIGN), sub)

            def copies(ti, half, start):
                def one(i, c):
                    page = pt_ref[table + ti * tile_pages + i] if start \
                        else 0
                    dma = pltpu.make_async_copy(
                        pool_hbm.at[page],
                        buf.at[half, pl.ds(i * page_size, page_size), :],
                        sems.at[half])
                    dma.start() if start else dma.wait()
                    return c

                jax.lax.fori_loop(
                    0, jnp.minimum(tile_pages, n_pages - ti * tile_pages),
                    one, None)

            def fold(h, s, st, vt):
                m_prev = m_ref[h, s, :1, :]                  # [1, sub]
                l_prev = l_ref[h, s, :1, :]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(st, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # every row saw position 0 in the run's first tile, so
                # m_new is finite wherever a masked score is folded
                pt = jnp.exp(st - m_new)
                l_new = alpha * l_prev + jnp.sum(pt, axis=0, keepdims=True)
                acc_ref[h, s] = acc_ref[h, s] * alpha + dot(
                    vt, pt.astype(dt), nn)                   # [v, sub]
                m_ref[h, s] = jnp.broadcast_to(m_new, (8, sub))
                l_ref[h, s] = jnp.broadcast_to(l_new, (8, sub))

            copies(0, 0, True)

            def one_tile(ti, carry):
                half = ti % 2

                @pl.when(ti + 1 < n_tiles)
                def _next():
                    copies(ti + 1, 1 - half, True)

                copies(ti, half, False)
                j0 = ti * tile
                rows = buf[half]
                lat, kr = rows[:, :latent], rows[:, latent:]
                # sub-blocks from `lo` see some of the tile (the last
                # row of sub-block s sees positions below first +
                # (s + 1)·sub - 1), those from `mid` all of it
                lo = jnp.minimum(jnp.maximum(j0 - first + 1, 0) // sub, nsb)
                mid = jnp.clip(
                    (jnp.maximum(j0 + tile - first, 0) + (sub - 1)) // sub,
                    lo, nsb)
                # every head's up-projection first, then a sub-block
                # meets all of them in one loop body: the heads' chains
                # (product, softmax, product) are independent and
                # overlap (10.98 against 11.48 ms: PERF.md §6, PR 34)
                ks = [dot(lat, wuk_ref[h], nt).astype(dt) for h in range(hh)]
                vts = [dot(wuv_ref[h], lat, nt).astype(dt)   # [v, tile]
                       for h in range(hh)]

                def attend(s, masked):
                    at = rows_of(s)
                    keep = ahead < first + s * sub - j0
                    for h in range(hh):
                        q = q_ref[at, h * qd:(h + 1) * qd]
                        # scaled in f32 AFTER the product, as the
                        # absorbed walk: q is not rounded again
                        st = (dot(ks[h], q[:, :nope], nt)
                              + dot(kr, q[:, nope:], nt)) * scale
                        if masked:
                            st = jnp.where(keep, st, NEG_INF)
                        fold(h, s, st, vts[h])

                jax.lax.fori_loop(lo, mid, lambda s, c: attend(s, True),
                                  None)
                jax.lax.fori_loop(mid, nsb, lambda s, c: attend(s, False),
                                  None)
                return carry

            jax.lax.fori_loop(0, n_tiles, one_tile, None)

            def finish(s, carry):
                for h in range(hh):
                    o = (acc_ref[h, s] / l_ref[h, s, :1, :]).T
                    o_ref[rows_of(s), h * vd:(h + 1) * vd] = o.astype(
                        o_ref.dtype)
                return carry

            jax.lax.fori_loop(0, nsb, finish, None)

        return carry

    jax.lax.fori_loop(0, rows_ref.shape[0], one_run, None)


def _latent_expanded_heads(heads, v_dim):
    """Heads a grid step: the fewest whose values fill whole 128-lane
    tiles of the `[total, H·v]` result, doubled to 4 while they divide
    the heads (a tile's copies are issued once for all of them, and
    their chains overlap: 11.39 / 10.72 ms at 2 / 4; 13.11 / 12.14 /
    11.69 / 11.64 at 1 / 2 / 4 / 8 in an earlier form of the kernel:
    PERF.md §6, PR 34, step 0); all the heads where no such count
    divides them."""
    hh = 128 // math.gcd(128, v_dim)
    while hh < 4 and heads % (2 * hh) == 0:
        hh *= 2
    return hh if heads % hh == 0 else heads


@functools.lru_cache(maxsize=None)
def _latent_expanded_call(q_shape, q_dtype, pool_shape, pool_dtype,
                          wuk_shape, wuv_shape, pages_per_seq, max_runs,
                          sub, tile_tokens, hh, scale, interpret):
    """The expanded launch for one set of static shapes, ONE jitted
    function a set (as `_paged_call`)."""
    total, width = q_shape
    _, page_size, row = pool_shape
    heads, nope, latent = wuk_shape
    vd = wuv_shape[1]
    if wuv_shape != (heads, vd, latent):
        raise ValueError(
            f"W_UK {wuk_shape} / W_UVᵀ {wuv_shape} are not [heads, nope, "
            f"latent] / [heads, v, latent] of the same heads")
    qd = nope + row - latent
    if width != heads * qd or not 0 < latent < row:
        raise ValueError(
            f"a head's query is [nope | rotary padded to the row's lanes "
            f"past the latent]: {heads} · ({nope} + {row - latent}), got "
            f"{width}")
    if not _latent_walks(page_size, pool_dtype):
        raise ValueError(
            f"a latent {jnp.dtype(pool_dtype).name} pool needs pages of "
            f"whole sublane tiles; got page_size {page_size}")
    if total < sub or total % LATENT_EXPANDED_ROW_ALIGN or heads % hh:
        raise ValueError(
            f"{total} rows of {heads} heads are not at least a sub-block "
            f"of {sub}, whole tiles of {LATENT_EXPANDED_ROW_ALIGN} rows "
            f"and steps of {hh} heads")
    tile_pages = max(1, min(pages_per_seq, int(tile_tokens) // page_size))
    tile = tile_pages * page_size
    nsub = -(-total // sub)

    launch = pl.pallas_call(
        functools.partial(_latent_expanded_kernel,
                          pages_per_seq=pages_per_seq,
                          tile_pages=tile_pages, sub=sub, latent=latent,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(heads // hh,),
            in_specs=[
                pl.BlockSpec((total, hh * qd), lambda g, *_: (0, g)),
                pl.BlockSpec((hh, nope, latent), lambda g, *_: (g, 0, 0)),
                pl.BlockSpec((hh, vd, latent), lambda g, *_: (g, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((total, hh * vd), lambda g, *_: (0, g)),
            scratch_shapes=[
                pltpu.VMEM((2, tile, row), pool_dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hh, nsub, vd, sub), jnp.float32),
                pltpu.VMEM((hh, nsub, 8, sub), jnp.float32),
                pltpu.VMEM((hh, nsub, 8, sub), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((total, heads * vd), q_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LATENT_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )

    jitted = jax.jit(launch, inline=True)
    jitted.body = "latent_expanded"
    return jitted


def latent_expanded_attention(q, pool, w_uk, w_uv, page_tables, run_slots,
                              run_row0, run_first, run_rows, scale,
                              sub_rows=None, tile_tokens=None,
                              heads_per_step=None, interpret=False):
    """The EXPANDED form over latent pages, for runs of one slot's rows
    at consecutive positions whose own latent rows are in the pool. q
    [total, H · (nope + rope')], a head after the other: `[q_nope |
    q_rope | zeros]`, the rotary part padded to the pool row's lanes
    past the latent (two dimensions: on the device `[total, H, ·]` is
    tiled otherwise, and a reshape of `total` rows is a copy); pool
    [N, P, R] with the latent in a row's first lanes; w_uk [H, nope,
    latent]; w_uv [H, latent, v]; page_tables [S, MP]. Run r (`run_*` [runs] int32, in
    the order of their rows): `run_rows[r]` live rows (0: none) of slot
    `run_slots[r]` from row `run_row0[r]` of `total`, a multiple of
    `LATENT_EXPANDED_ROW_ALIGN` with a whole sub-block of rows from a
    run's last sub-block's start still inside `total` (a CONTRACT:
    `nn.functional.attention.SlotRunLayout`), row i of it at kv length
    `run_first[r] + i` (it attends positions below that). Returns
    [total, H · v]: softmax((q_nope · (c W_UKᵀ) + q_rope · k_r) · scale) ·
    (c W_UV) a head in the runs' rows, numbers nobody may use in every
    other row. `scale` is static; `sub_rows`, `tile_tokens` and
    `heads_per_step` override the defaults (the sweep's and the
    tests')."""
    heads, vd = w_uv.shape[0], w_uv.shape[2]
    sub, tile = latent_expanded_tiles()
    call = _latent_expanded_call(
        q.shape, q.dtype, pool.shape, pool.dtype, w_uk.shape,
        (heads, vd, w_uv.shape[1]), page_tables.shape[1],
        int(run_rows.shape[0]), int(sub_rows or sub),
        int(tile_tokens or tile),
        int(heads_per_step or _latent_expanded_heads(heads, vd)),
        float(scale), interpret)
    for sites in _open_site_counts.stack:
        sites[call.body] = sites.get(call.body, 0) + 1
    i32 = lambda x: jnp.asarray(x, jnp.int32)   # noqa: E731
    # [H, v, latent]: vᵀ = W_UVᵀ · cᵀ
    return call(i32(run_slots), i32(run_row0), i32(run_first), i32(run_rows),
                i32(page_tables).reshape(-1), q, w_uk,
                jnp.swapaxes(w_uv, 1, 2), pool)
