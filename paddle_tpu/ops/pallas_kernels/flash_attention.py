"""Pallas flash attention for TPU.

TPU-native fused attention kernel — the counterpart of the reference's CUDA
fused attention (reference: paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h). Algorithm: FlashAttention-2 style online softmax — the score
matrix is never materialized in HBM, so HBM traffic is O(seq·d) instead
of O(seq²). Two sets of kernels behind ONE entry, `flash_attention_bshd`,
which chooses by shapes and dtype (`resident_eligible`):

RESIDENT (self-attention, head_dim 64 / 128, a sequence that fits VMEM:
the training steps). Grid (batch row, 128-lane block of [B, S, H·D]): a
block is two heads at head_dim 64, kept apart by a lane mask, or one at
128, and its whole sequence sits in VMEM. The walk over tiles is a loop
inside the kernel: only live causal tiles are visited, only the
triangles on the diagonal build a mask, the backward is ONE kernel
(s, p, dp, ds formed once: five products a tile), and the operands are
read in place: no transpose round the kernel.

TILED (everything else, and `flash_attention_lse_bhd` for ring
attention). Grid (batch·heads, q_blocks, kv_blocks) over [B·H, S, D]
with the kv dimension innermost — Mosaic revisits the same output block
across kv steps, so the f32 accumulator and the (m, l) statistics live
in VMEM scratch and are finalized on the last kv step. Backward: a dq
pass and a dk/dv pass that recompute scores blockwise from
(q, k, v, lse).

Both multiply operands as given (bf16 stays bf16) with
preferred_element_type=f32, and round p and ds to the operand dtype
before their products. The counter `flash_attn_launches` says which
path a call site took. Tests check both directions of both paths
against a dense jnp attention in interpret mode
(tests/test_pallas_kernels.py).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability import metrics as _metrics

__all__ = ["flash_attention_bshd"]

NEG_INF = -1e30

# how often each path engages: one count a Pallas launch TRACED (a
# compiled call site counts once, however often it then runs)
_LAUNCHES = _metrics.counter(
    "flash_attn_launches", "flash attention kernel launches traced, by "
    "path: resident (a head pair's whole sequence in VMEM) / tiled",
    labelnames=("path",))

# the TILED kernels' tiles. Step 0 on a v5e (PERF.md section 6, PR 31;
# tools/flash_sweep.py, bf16, forward + backward alone): b16·s1024·h16·d64
# causal 6.85 ms at 512, 11.6 at 256, 22.2 at 128; b4·s2048·h16·d128
# causal 4.34 / 8.68 / 18.3 ms
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _fa_kernel(q_ref, k_ref, v_ref, lens_ref, o_ref, lse_ref,
               acc_ref, m_ref, l_ref, *, causal, scale, block_q, block_k,
               kv_blocks, seq_k, use_lens):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the diagonal
    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    if use_lens:
        # per-batch valid kv length (key-padding mask): whole blocks past
        # the valid prefix are skipped dynamically
        kl = lens_ref[0, 0, 0]
        run = jnp.logical_and(run, ki * block_k < kl)

    @pl.when(run)
    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1) + ki * block_k
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qi * block_q
            s = jnp.where(rows >= cols, s, NEG_INF)
        if use_lens:
            # kl <= seq_k, so this also covers the padded buffer tail
            s = jnp.where(cols < kl, s, NEG_INF)
            vrows = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) + ki * block_k
            v = jnp.where(vrows < kl, v, jnp.zeros_like(v))
        elif seq_k % block_k != 0:
            # mask the padded tail of the last kv block; without this the
            # padding columns inflate the softmax sum — and zero padded v
            # rows, since even 0-weight × garbage (NaN) rows would poison
            # the accumulator
            s = jnp.where(cols < seq_k, s, NEG_INF)
            vrows = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) + ki * block_k
            v = jnp.where(vrows < seq_k, v, jnp.zeros_like(v))

        m_prev = m_ref[:, :1]  # [block_q, 1] (stats broadcast over lanes)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [block_q, block_k] f32
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        # fully-masked rows (can't happen under causal) would have l == 0
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # lse laid out [bh, 1, seq]: the row vector lives on the LANE dim,
        # so the tile pads 8x (sublane), not 128x — a [bh, seq, 1] layout
        # padded each per-layer residual from 1.5M to 192M
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(safe_l[:, 0])


def _lens_operand(lens, bh, seq_k):
    """[bh] int32 lengths → a [bh, 1, 128] VMEM-tileable operand (the
    kernel reads lane 0); full-length dummy when lens is None."""
    if lens is None:
        return jnp.full((bh, 1, 128), seq_k, jnp.int32)
    return jnp.broadcast_to(
        lens.astype(jnp.int32)[:, None, None], (bh, 1, 128))


def _fa_forward(q, k, v, causal, block_q, block_k, interpret, lens=None):
    """q,k,v: [bh, seq, d] → (out [bh, seq, d], lse [bh, 1, seq]).
    lens: optional [bh] int32 per-row valid kv length (key padding)."""
    bh, seq, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq)
    block_k = min(block_k, seq_k)
    scale = 1.0 / math.sqrt(d)
    q_blocks = pl.cdiv(seq, block_q)
    kv_blocks = pl.cdiv(seq_k, block_k)

    _LAUNCHES.labels(path="tiled").inc()
    use_lens = lens is not None
    kernel = functools.partial(
        _fa_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, kv_blocks=kv_blocks, seq_k=seq_k,
        use_lens=use_lens)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    operands = [q, k, v]
    if use_lens:
        in_specs.append(pl.BlockSpec((1, 1, 128), lambda b, i, j: (b, 0, 0)))
        operands.append(_lens_operand(lens, bh, seq_k))
    else:
        # keep the hot path free of a dummy operand: adapt the kernel's
        # lens_ref slot away (it is only read under use_lens)
        body = kernel
        kernel = lambda qr, kr, vr, *rest: body(qr, kr, vr, None, *rest)

    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        ],
        interpret=interpret,
    )(*operands)
    return out, lse  # lse: [bh, 1, seq]


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      lens_ref, dq_ref, acc_ref, *, causal, scale, block_q,
                      block_k, kv_blocks, seq_q, seq_k, use_lens):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        run = ki * block_k <= qi * block_q + block_q - 1
    if use_lens:
        kl = lens_ref[0, 0, 0]
        run = jnp.logical_and(run, ki * block_k < kl)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]                    # bf16 inputs stay on the MXU
        lse = lse_ref[0, 0][:, None]    # [block_q, 1] f32 (lane-major row)
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1) + ki * block_k
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + qi * block_q
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        if use_lens:
            # key-padding columns contribute nothing to dq
            p = jnp.where(cols < kl, p, 0.0)
            kvrows = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) + ki * block_k
            v = jnp.where(kvrows < kl, v, jnp.zeros_like(v))
            k = jnp.where(kvrows < kl, k, jnp.zeros_like(k))
        elif seq_k % block_k != 0:
            # padded kv tail: p→0 and k/v pad rows zeroed so 0·NaN never
            # forms in dp or the final ds·k product
            p = jnp.where(cols < seq_k, p, 0.0)
            kvrows = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) + ki * block_k
            v = jnp.where(kvrows < seq_k, v, jnp.zeros_like(v))
            k = jnp.where(kvrows < seq_k, k, jnp.zeros_like(k))
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                       lens_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                       scale, block_q, block_k, q_blocks, seq_q, seq_k,
                       use_lens):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = qi * block_q + block_q - 1 >= ki * block_k
    if use_lens:
        # kv blocks entirely past the valid prefix get zero dk/dv: skip
        kl = lens_ref[0, 0, 0]
        run = jnp.logical_and(run, ki * block_k < kl)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]                    # bf16 inputs stay on the MXU
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + qi * block_q
        if causal:
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        if use_lens:
            # key-padding columns: p→0 so padded k/v rows accumulate
            # exactly zero gradient (ds = p·(dp−delta) follows)
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            p = jnp.where(cols < kl, p, 0.0)
        if seq_q % block_q != 0:
            # padded q tail: those rows carry garbage lse/delta/g/q — zero
            # their weight so they contribute nothing to dk/dv (and no
            # 0·NaN forms in the ds^T·q product)
            p = jnp.where(rows < seq_q, p, 0.0)
            grows = jax.lax.broadcasted_iota(
                jnp.int32, g.shape, 0) + qi * block_q
            g = jnp.where(grows < seq_q, g, jnp.zeros_like(g))
            q = jnp.where(grows < seq_q, q, jnp.zeros_like(q))
        if seq_k % block_k != 0:
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + ki * block_k
            p = jnp.where(cols < seq_k, p, 0.0)
            vrows = jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) + ki * block_k
            v = jnp.where(vrows < seq_k, v, jnp.zeros_like(v))
        dv_acc[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if seq_q % block_q != 0:
            # delta/lse are garbage on padded q rows, so 0·NaN leaked into
            # ds despite p being zeroed there — mask ds itself
            ds = jnp.where(rows < seq_q, ds, 0.0)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == q_blocks - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:]).astype(dk_ref.dtype)
        dv_ref[0] = (dv_acc[:]).astype(dv_ref.dtype)


def _attn_bwd_pallas(q, k, v, out, lse, g, causal, block_q, block_k,
                     interpret, g_lse=None, lens=None):
    """Flash backward: dq pass + dk/dv pass, each O(seq·d) HBM traffic.

    g_lse: optional cotangent of the lse output (ring attention's
    streaming merge differentiates through lse). Math: the score grad is
    ds = p∘(dp − delta) with delta = rowsum(do·o); an lse cotangent adds
    +p·g_lse (d lse/d s = p), i.e. delta_eff = delta − g_lse — one
    subtraction, the kernels are unchanged."""
    bh, seq, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq)
    block_k = min(block_k, seq_k)
    scale = 1.0 / math.sqrt(d)
    q_blocks = pl.cdiv(seq, block_q)
    kv_blocks = pl.cdiv(seq_k, block_k)
    gf = g.astype(q.dtype)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bh, 1, seq] (lane-major)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    lse3 = lse  # already [bh, 1, seq]
    _LAUNCHES.labels(path="tiled").inc(2)  # dq, dk/dv

    use_lens = lens is not None

    def with_lens_slot(body):
        # no-lens path: adapt the kernel's lens_ref slot away so the hot
        # path carries no dummy operand (lens_ref only read under
        # use_lens)
        if use_lens:
            return body
        return lambda qr, kr, vr, gr, lr, dr, *rest: body(
            qr, kr, vr, gr, lr, dr, None, *rest)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    operands = [q, k, v, gf, lse3, delta]
    if use_lens:
        in_specs.append(pl.BlockSpec((1, 1, 128), lambda b, i, j: (b, 0, 0)))
        operands.append(_lens_operand(lens, bh, seq_k))

    dq = pl.pallas_call(
        with_lens_slot(functools.partial(
            _fa_bwd_dq_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, kv_blocks=kv_blocks, seq_q=seq, seq_k=seq_k,
            use_lens=use_lens)),
        grid=(bh, q_blocks, kv_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # dkv pass: grid transposed so the q dimension is innermost
    q_spec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    operands2 = [q, k, v, gf, lse3, delta]
    if use_lens:
        in_specs2.append(
            pl.BlockSpec((1, 1, 128), lambda b, j, i: (b, 0, 0)))
        operands2.append(_lens_operand(lens, bh, seq_k))
    dk, dv = pl.pallas_call(
        with_lens_slot(functools.partial(
            _fa_bwd_dkv_kernel, causal=causal, scale=scale, block_q=block_q,
            block_k=block_k, q_blocks=q_blocks, seq_q=seq, seq_k=seq_k,
            use_lens=use_lens)),
        grid=(bh, kv_blocks, q_blocks),
        in_specs=in_specs2,
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(*operands2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_bhd(q, k, v, lens, causal, block_q, block_k,
                         interpret):
    out, _ = _fa_forward(q, k, v, causal, block_q, block_k, interpret,
                         lens=lens)
    return out


def _fa_fwd_rule(q, k, v, lens, causal, block_q, block_k, interpret):
    out, lse = _fa_forward(q, k, v, causal, block_q, block_k, interpret,
                           lens=lens)
    return out, (q, k, v, lens, out, lse)


def _zero_lens_cotangent(lens):
    import numpy as np

    return (None if lens is None
            else np.zeros(lens.shape, jax.dtypes.float0))


def _fa_bwd_rule(causal, block_q, block_k, interpret, res, g):
    q, k, v, lens, out, lse = res
    dq, dk, dv = _attn_bwd_pallas(q, k, v, out, lse, g, causal, block_q,
                                  block_k, interpret, lens=lens)
    return dq, dk, dv, _zero_lens_cotangent(lens)


_flash_attention_bhd.defvjp(_fa_fwd_rule, _fa_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse_bhd(q, k, v, causal=False,
                            block_q=DEFAULT_BLOCK_Q,
                            block_k=DEFAULT_BLOCK_K, interpret=False):
    """(out [bh,s,d], lse [bh,1,s]) with BOTH outputs differentiable —
    the building block for cross-device streaming merges (ring
    attention): the caller combines per-block results by lse and AD
    composes through the merge."""
    return _fa_forward(q, k, v, causal, block_q, block_k, interpret)


def _fa_lse_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _fa_forward(q, k, v, causal, block_q, block_k, interpret)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(causal, block_q, block_k, interpret, res, cts):
    q, k, v, out, lse = res
    g_out, g_lse = cts
    return _attn_bwd_pallas(q, k, v, out, lse, g_out, causal, block_q,
                            block_k, interpret, g_lse=g_lse)


flash_attention_lse_bhd.defvjp(_fa_lse_fwd, _fa_lse_bwd)


# --------------------------------------------------------------- resident
# (the module docstring says what these kernels are)

_LANES = 128

# What a resident pass may ask of VMEM for its BLOCKS, in bytes: the
# backward's q, k, v, o, dO in and dq, dk, dv out, double-buffered, and
# its float32 dq accumulator = S * 128 * (16 * itemsize + 4). 20 MiB
# keeps bf16 resident to S 4096 (18 MiB) and float32 to S 2048 (17 MiB);
# beyond it the tiled kernels stream the sequence. _RESIDENT_VMEM_LIMIT
# is what Mosaic is told it may use, with the [tile, tile] float32
# intermediates on top (a v5e core has 128 MiB).
RESIDENT_VMEM_BUDGET = 20 * 2 ** 20
_RESIDENT_VMEM_LIMIT = 64 * 2 ** 20


def _resident_block_bytes(seq, itemsize):
    return seq * _LANES * (16 * itemsize + 4)


def _resident_tiles(seq):
    """(tile, diagonal cut) of a resident sequence: the largest of 2048 /
    1024 / 512 / 256 / 128 that divides it (to 2048 the whole sequence is
    one tile and every loop is unrolled), its diagonal cut to 256. Step 0
    on a v5e (PERF.md section 6, PR 31; forward + backward, ms):
    b16 s1024 h16 d64 causal 1.89 at (1024, 256), 2.31 at (512, 256), 2.34
    at (1024, 512), 2.69 at (1024, 128), 3.22 at (256, 256);
    b4 s2048 h16 d128 causal 1.71 at (2048, 256), 1.95 at (1024, 256)."""
    for tile in (2048, 1024, 512, 256, 128):
        if seq % tile == 0:
            return tile, min(tile, 256)
    return None


def resident_eligible(q, k, v, block_q=DEFAULT_BLOCK_Q,
                      block_k=DEFAULT_BLOCK_K):
    """Whether flash_attention_bshd takes the resident kernels: a static
    function of shapes and dtype. Self-attention (one shape for q, k, v),
    head_dim 64 or 128 with H*D a multiple of 128 (so a 128-lane block
    holds whole heads), a sequence that tiles by 128 and whose blocks fit
    RESIDENT_VMEM_BUDGET. block_q / block_k are the TILED kernels' tiles:
    a caller that names other than the defaults has asked for those."""
    b, s, h, d = q.shape
    return (q.shape == k.shape == v.shape
            and q.dtype == k.dtype == v.dtype
            and d in (64, 128) and (h * d) % _LANES == 0
            and _resident_tiles(s) is not None
            and _resident_block_bytes(s, jnp.dtype(q.dtype).itemsize)
            <= RESIDENT_VMEM_BUDGET
            and (block_q, block_k) == (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))


def _lane_masks(d, shape):
    """One bool mask a head of the 128-lane block, at `shape` ([.., 128]);
    [None] where the block is one head."""
    if d == _LANES:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return [(lane >= h * d) & (lane < (h + 1) * d)
            for h in range(_LANES // d)]


def _keep_lanes(mask, x):
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _by_head(masks, xs):
    """Each head's lanes from its own array (one head, or a pair)."""
    return xs[0] if len(xs) == 1 else jnp.where(masks[0], xs[0], xs[1])


def _keep_mask(nc, nr, diag, kv0, kl):
    """Which of the TRANSPOSED scores [nc kv rows from position kv0, nr q
    rows] count, or None for all: on the diagonal (same first position,
    nc == nr) kv <= q; with a kv length `kl` (else None) kv < kl."""
    kv = jax.lax.broadcasted_iota(jnp.int32, (nc, nr), 0)
    keep = None
    if diag:
        keep = kv <= jax.lax.broadcasted_iota(jnp.int32, (nc, nr), 1)
    if kl is not None:
        live = kv + kv0 < kl
        keep = live if keep is None else jnp.logical_and(keep, live)
    return keep


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))   # a . b^T
_NN = ((1,), (0,))   # a . b
_TN = ((0,), (0,))   # a^T . b


def _res_fwd_kernel(*refs, causal, scale, seq, d, tile, cut, use_lens):
    """One (batch row, lane block): q, k, v, o [1, S, 128], lse
    [1, 1, 128/d, S]. Scores are formed TRANSPOSED, [kv, q]: the running
    max and sum are lane-major rows (what lse is stored as), reduced over
    sublanes on the VPU, where [q, kv] scores cost a cross-lane reduction
    a row (step 0: 1.53 ms against 0.73 at b16 s1024 h16 d64, PERF.md
    section 6, PR 31)."""
    kl = None
    if use_lens:
        lens_ref, *refs = refs
        kl = lens_ref[pl.program_id(0)]
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    heads = range(_LANES // d)
    n_tiles = seq // tile

    def attend(qs, r0, nr, c0, nc, diag):
        """Rows [r0, r0 + nr) of the current q tile (static; `qs` their
        q, one masked copy a head) against kv rows [c0, c0 + nc) of the
        sequence; `diag`: the triangle on the diagonal (nr == nc, same
        first position)."""
        k = k_ref[0, pl.ds(c0, nc), :]
        v = v_ref[0, pl.ds(c0, nc), :]
        keep = _keep_mask(nc, nr, diag, c0, kl)
        if use_lens:
            vrows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + c0
            v = jnp.where(vrows < kl, v, jnp.zeros_like(v))
        rows = slice(r0, r0 + nr)
        for h in heads:
            st = _dot(k, qs[h], _NT) * scale            # [nc, nr]
            if keep is not None:
                st = jnp.where(keep, st, NEG_INF)
            m_prev = m_ref[h, :1, rows]                  # [1, nr]
            l_prev = l_ref[h, :1, rows]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_new = alpha * l_prev + jnp.sum(pt, axis=0, keepdims=True)
            alpha_col = jnp.broadcast_to(alpha, (_LANES, nr)).T
            acc_ref[h, rows, :] = acc_ref[h, rows, :] * alpha_col + _dot(
                pt.astype(v.dtype), v, _TN)
            m_ref[h, :, rows] = jnp.broadcast_to(m_new, (8, nr))
            l_ref[h, :, rows] = jnp.broadcast_to(l_new, (8, nr))

    def q_tile(i, _):
        r = pl.multiple_of(i * tile, tile)
        q = q_ref[0, pl.ds(r, tile), :]
        masks = _lane_masks(d, q.shape)
        qs = [_keep_lanes(mask, q) for mask in masks]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

        n_in = i if causal else n_tiles
        if use_lens:
            n_in = jnp.minimum(n_in, (kl + tile - 1) // tile)

        def interior(j, _):
            attend(qs, 0, tile, pl.multiple_of(j * tile, tile), tile, False)

        jax.lax.fori_loop(0, n_in, interior, None)

        def diagonal():
            # q rows a of the diagonal tile see its kv rows up to their
            # own cut: a strip with no mask, then the cut x cut triangle
            for a in range(tile // cut):
                sub = [x[a * cut:(a + 1) * cut] for x in qs]
                if a:
                    attend(sub, a * cut, cut, r, a * cut, False)
                attend(sub, a * cut, cut, r + a * cut, cut, True)

        if causal and use_lens:
            pl.when(r < kl)(diagonal)
        elif causal:
            diagonal()

        outs = []
        for h in heads:
            l = l_ref[h, :1, :]                         # [1, tile]
            # a row nothing attended to (a zero kv length): l == 0
            safe_l = jnp.where(l == 0.0, 1.0, l)
            outs.append(acc_ref[h] / jnp.broadcast_to(
                safe_l, (_LANES, tile)).T)
            lse_ref[0, 0, pl.ds(h, 1), pl.ds(r, tile)] = (
                m_ref[h, :1, :] + jnp.log(safe_l))
        o_ref[0, pl.ds(r, tile), :] = _by_head(masks, outs).astype(
            o_ref.dtype)

    jax.lax.fori_loop(0, n_tiles, q_tile, None)


def _res_bwd_kernel(*refs, causal, scale, seq, d, tile, cut, use_lens):
    """One (batch row, lane block), the whole backward: kv tiles outside
    (dk, dv accumulate a tile), q tiles at or below the diagonal inside,
    dq a [S, 128] float32 scratch written at the end; s, p, dp, ds formed
    once a tile pair, five products."""
    kl = None
    if use_lens:
        lens_ref, *refs = refs
        kl = lens_ref[pl.program_id(0)]
    (q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref, dq_ref, dk_ref, dv_ref,
     dq_acc, dk_acc, dv_acc, delta_ref) = refs
    heads = range(_LANES // d)
    n_tiles = seq // tile
    dq_acc[...] = jnp.zeros_like(dq_acc)

    def delta_tile(i, _):
        # delta = rowsum(dO . o) a head, as lane-major rows like lse:
        # the product transposed, then each head's d sublanes summed
        rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
        prod_t = (g_ref[0, rows, :].astype(jnp.float32)
                  * o_ref[0, rows, :].astype(jnp.float32)).T
        for h in heads:
            delta_ref[h, :, rows] = jnp.broadcast_to(jnp.sum(
                prod_t[h * d:(h + 1) * d], axis=0, keepdims=True), (8, tile))

    jax.lax.fori_loop(0, n_tiles, delta_tile, None)

    def kv_tile(j, _):
        c = pl.multiple_of(j * tile, tile)
        k = k_ref[0, pl.ds(c, tile), :]
        v = v_ref[0, pl.ds(c, tile), :]
        if use_lens:
            # key-padding rows: zeroed so that 0 * (whatever a padded
            # row holds) never forms in dp or ds^T . k
            live_rows = jax.lax.broadcasted_iota(
                jnp.int32, k.shape, 0) + c < kl
            k = jnp.where(live_rows, k, jnp.zeros_like(k))
            v = jnp.where(live_rows, v, jnp.zeros_like(v))
        masks = _lane_masks(d, k.shape)
        ks = [_keep_lanes(mask, k) for mask in masks]
        vs = [_keep_lanes(mask, v) for mask in masks]
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

        def pair(c0, nc, r, nr, diag):
            """kv rows [c0, c0 + nc) of this tile (static) against q rows
            [r, r + nr) of the sequence, TRANSPOSED: scores are [kv, q],
            so lse and delta broadcast as the lane-major rows they are
            stored as, and dv, dk need no transposed product."""
            q = q_ref[0, pl.ds(r, nr), :]
            g = g_ref[0, pl.ds(r, nr), :]
            keep = _keep_mask(nc, nr, diag, c + c0, kl)
            cols = slice(c0, c0 + nc)
            dq = None
            for h in heads:
                kh, vh = ks[h][cols], vs[h][cols]
                st = _dot(kh, q, _NT) * scale
                if keep is not None:
                    st = jnp.where(keep, st, NEG_INF)
                pt = jnp.exp(st - lse_ref[0, 0, pl.ds(h, 1), pl.ds(r, nr)])
                dpt = _dot(vh, g, _NT)
                dst = pt * (dpt - delta_ref[h, :1, pl.ds(r, nr)])
                dv_acc[h, cols, :] += _dot(pt.astype(g.dtype), g, _NN)
                dk_acc[h, cols, :] += _dot(dst.astype(q.dtype), q, _NN)
                part = _dot(dst.astype(k.dtype), kh, _TN)
                dq = part if dq is None else dq + part
            dq_acc[pl.ds(r, nr), :] += dq

        def whole(i, _):
            pair(0, tile, pl.multiple_of(i * tile, tile), tile, False)

        def live_tile():
            if not causal:
                jax.lax.fori_loop(0, n_tiles, whole, None)
                return
            # kv rows b of the diagonal tile: their cut x cut triangle,
            # then the q rows below it inside the tile with no mask
            for b in range(tile // cut):
                pair(b * cut, cut, c + b * cut, cut, True)
                rest = tile - (b + 1) * cut
                if rest:
                    pair(b * cut, cut, c + (b + 1) * cut, rest, False)
            jax.lax.fori_loop(j + 1, n_tiles, whole, None)

        if use_lens:
            pl.when(c < kl)(live_tile)
        else:
            live_tile()
        dk_ref[0, pl.ds(c, tile), :] = (_by_head(
            masks, [dk_acc[h] for h in heads]) * scale).astype(dk_ref.dtype)
        dv_ref[0, pl.ds(c, tile), :] = _by_head(
            masks, [dv_acc[h] for h in heads]).astype(dv_ref.dtype)

    jax.lax.fori_loop(0, n_tiles, kv_tile, None)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


@functools.lru_cache(maxsize=None)
def _resident_launch(backward, shape, dtype, use_lens, causal, d, tile, cut,
                     interpret):
    """The forward or the backward launch for one set of static shapes,
    as ONE jitted function: a model calls it once a layer with the same
    shapes, and a fresh `pallas_call` would trace the unrolled kernel
    body again at every site (24 layers forward and backward: 7.4 s of
    host time to trace and lower against 0.6, and a warm `setup_s` of
    51 s against the parent's 36: PERF.md section 6, PR 31).
    `inline=True` leaves the caller's jaxpr and HLO as they were. Takes
    ([lens], q, k, v) and gives (out, lse), or ([lens], q, k, v, out, dO,
    lse) and gives (dq, dk, dv); [B, S, H*D] each, lens [B] int32 a
    scalar-prefetch operand, lse [B, H*D/128, 128/D, S] float32 (a lane
    block's heads together)."""
    b, seq, hd = shape
    hpb, blocks = _LANES // d, hd // _LANES
    seq_spec = pl.BlockSpec((1, seq, _LANES), lambda b, j, *_: (b, 0, j))
    row_spec = pl.BlockSpec((1, 1, hpb, seq), lambda b, j, *_: (b, j, 0, 0))
    whole = jax.ShapeDtypeStruct(shape, dtype)
    tile_f32 = pltpu.VMEM((hpb, tile, _LANES), jnp.float32)
    if backward:
        kernel = _res_bwd_kernel
        in_specs, out_specs = [seq_spec] * 5 + [row_spec], [seq_spec] * 3
        out_shape = [whole] * 3
        scratch = [pltpu.VMEM((seq, _LANES), jnp.float32),    # dq, whole
                   tile_f32, tile_f32,                        # dk, dv
                   pltpu.VMEM((hpb, 8, seq), jnp.float32)]    # delta, rows
    else:
        kernel = _res_fwd_kernel
        in_specs, out_specs = [seq_spec] * 3, [seq_spec, row_spec]
        out_shape = [whole, jax.ShapeDtypeStruct((b, blocks, hpb, seq),
                                                 jnp.float32)]
        rows = pltpu.VMEM((hpb, 8, tile), jnp.float32)
        scratch = [tile_f32, rows, rows]        # acc, running max and sum
    launch = pl.pallas_call(
        functools.partial(kernel, causal=causal, scale=1.0 / math.sqrt(d),
                          seq=seq, d=d, tile=tile, cut=cut,
                          use_lens=use_lens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(use_lens), grid=(b, blocks),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_RESIDENT_VMEM_LIMIT),
        interpret=interpret,
    )
    return jax.jit(launch, inline=True)


def _resident_pass(backward, lens, operands, causal, d, tile, cut,
                   interpret):
    q = operands[0]
    _LAUNCHES.labels(path="resident").inc()
    launch = _resident_launch(backward, q.shape, jnp.dtype(q.dtype),
                              lens is not None, causal, d, tile, cut,
                              interpret)
    return launch(*([] if lens is None else [lens]), *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_resident(q, k, v, lens, causal, d, tile, cut,
                              interpret):
    """q, k, v: [B, S, H*D]; lens: None or [B] int32 (<= S)."""
    return _resident_pass(False, lens, (q, k, v), causal, d, tile, cut,
                          interpret)[0]


def _res_fwd_rule(q, k, v, lens, causal, d, tile, cut, interpret):
    out, lse = _resident_pass(False, lens, (q, k, v), causal, d, tile, cut,
                              interpret)
    return out, (q, k, v, lens, out, lse)


def _res_bwd_rule(causal, d, tile, cut, interpret, res, g):
    q, k, v, lens, out, lse = res
    dq, dk, dv = _resident_pass(
        True, lens, (q, k, v, out, g.astype(q.dtype), lse), causal, d, tile,
        cut, interpret)
    return dq, dk, dv, _zero_lens_cotangent(lens)


_flash_attention_resident.defvjp(_res_fwd_rule, _res_bwd_rule)


def flash_attention_bshd(q, k, v, causal=False,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False, kv_lens=None):
    """Fused attention on [batch, seq, heads, head_dim] (paddle layout).

    Differentiable; forward and backward are Pallas kernels. Which ones
    is a static function of shapes and dtype (`resident_eligible`): the
    RESIDENT kernels read [B, S, H*D] in place, a 128-lane block of whole
    heads a grid step with its sequence in VMEM; everything else (cross
    attention, head_dim 256, an odd head count at head_dim 64, a sequence
    over RESIDENT_VMEM_BUDGET, explicit block_q / block_k) takes the TILED
    kernels over [batch*heads, seq, d], one (q tile, kv tile) a grid step,
    and pays one transpose an operand each way. `interpret=True` runs in
    the Pallas interpreter (CPU test tier).

    kv_lens: optional [batch] int per-example valid key length (prefix
    key-padding mask, the BERT/ERNIE padded-batch case): columns >= len
    get zero attention weight and their k/v rows zero gradient; whole kv
    tiles past the valid prefix are skipped. Composes with `causal`.
    """
    b, s, h, d = q.shape
    sk = k.shape[1]
    if causal and s != sk:
        # the kernel's diagonal is top-aligned; the jnp/backward reference
        # is bottom-aligned — only identical for self-attention
        raise ValueError(
            f"causal flash attention requires seq_q == seq_k, got {s} vs "
            f"{sk}; use the jnp path for cross-length causal masks")
    lens = None
    if kv_lens is not None:
        # Clamp to seq_k: the kernels' `cols < kl` masking subsumes the
        # buffer tail mask ONLY when kl <= seq_k — an oversized length
        # would let uninitialized block padding into the softmax.
        lens = jnp.minimum(jnp.asarray(kv_lens, jnp.int32), sk)

    if resident_eligible(q, k, v, block_q, block_k):
        tile, cut = _resident_tiles(s)
        out = _flash_attention_resident(
            q.reshape(b, s, h * d), k.reshape(b, s, h * d),
            v.reshape(b, s, h * d), lens, bool(causal), d, tile, cut,
            bool(interpret))
        return out.reshape(b, s, h, d)

    def to_bhd(t, sl):
        return jnp.swapaxes(t, 1, 2).reshape(b * h, sl, t.shape[-1])

    if lens is not None:
        lens = jnp.repeat(lens, h)  # [b] -> [b*h]: batch-major then head
    out = _flash_attention_bhd(to_bhd(q, s), to_bhd(k, sk), to_bhd(v, sk),
                               lens, bool(causal), int(block_q),
                               int(block_k), bool(interpret))
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
