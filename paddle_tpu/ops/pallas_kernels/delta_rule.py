"""The gated delta rule as Pallas kernels: the RECURRENT step (one read
and one write of a sequence's state a row) and the CHUNKED form (a run of
rows from its slot's stored state, a chunk a grid step), the second at
the end of this file.

THE RECURRENT STEP

    Sd = Diag(α) S;  u = β (v − Sdᵀ k);  S' = Sd + k uᵀ;  o = S'ᵀ q

for `S` [d_k, d_v] a head, float32. The state of all heads of one slot
(`[H, d_k, d_v]`, 2 MiB at 32 × 128 × 128) is ONE block: it is copied
into VMEM once, every head's four passes run over it there on the VPU in
float32, and it is copied out once, in place (the state argument is
aliased to the state result, so a donated state is never held twice).
Plain XLA makes each pass a trip to HBM.

The per-channel vectors (α, k, β·k, q) arrive TRANSPOSED, `[S, d_k, H]`:
a head's is then a column `[d_k, 1]` of the block, which broadcasts
along the state's lanes with no relayout; v and o are rows `[1, d_v]`.

Only the LIVE slots are visited: `order` [S] lists them first and
repeats the last of them after (`n_live` of them), so the grid's
remaining steps name a block that is already resident and move nothing;
a slot that is not visited keeps its state (the aliasing) and its row of
`o` is unspecified. Step 0 always runs: with no live slot at all it
passes slot `order[0]` through the neutral inputs its caller gives dead
rows (α = 1, k = 0).
"""
import functools

import jax
import jax.numpy as jnp

__all__ = ["delta_rule_recurrent", "delta_rule_chunks"]


def _kernel(order_ref, n_ref, s_ref, a_ref, k_ref, kb_ref, q_ref, vb_ref,
            o_ref, s_out_ref, *, heads):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when((i == 0) | (i < n_ref[0]))
    def _():
        for h in range(heads):
            col = (slice(None), slice(h, h + 1))
            sd = s_ref[0, h] * a_ref[0][col]
            u = vb_ref[0, h:h + 1, :] - jnp.sum(
                sd * kb_ref[0][col], axis=0, keepdims=True)
            s2 = sd + k_ref[0][col] * u
            s_out_ref[0, h] = s2
            o_ref[0, h:h + 1, :] = jnp.sum(
                s2 * q_ref[0][col], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_recurrent(state, a_t, k_t, kb_t, q_t, vb, order, n_live,
                         interpret=False):
    """state [S, H, d_k, d_v] float32; a_t k_t kb_t q_t [S, d_k, H]
    float32 (α, k, β·k, q, transposed); vb [S, H, d_v] float32 (β·v);
    order [S] int32 the slots to visit, the live ones first; n_live [1]
    int32 → (o [S, H, d_v] float32, the new state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk, dv = state.shape
    by_slot3 = lambda i, order, n: (order[i], 0, 0)       # noqa: E731
    by_slot4 = lambda i, order, n: (order[i], 0, 0, 0)    # noqa: E731
    vec = pl.BlockSpec((1, dk, H), by_slot3)
    row = pl.BlockSpec((1, H, dv), by_slot3)
    slab = pl.BlockSpec((1, H, dk, dv), by_slot4)
    o, new = pl.pallas_call(
        functools.partial(_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S,),
            in_specs=[slab, vec, vec, vec, vec, row],
            out_specs=[row, slab]),
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
    )(order.astype(jnp.int32), n_live.astype(jnp.int32), state, a_t, k_t,
      kb_t, q_t, vb)
    return o, new


# ---- the chunked form ---------------------------------------------------
#
# One grid step is one chunk of C rows of one run, all heads, in the order
# of the run layout; a run's chunks follow each other, so the state a head
# `[d_k, d_v]` is carried from a chunk to the next in the RESULT's block
# (all heads of one slot: the block stays resident while consecutive steps
# name the same slot, and is written back when the next run's slot differs
# or the grid ends). A run's first chunk starts from the stored state (the
# argument's block, aliased to the result) or from zero. The arithmetic is
# `nn/functional/delta_rule.py`'s (its docstring has the equations): G the
# cumulative log decay (a product with a triangle of ones), the pairwise
# decays as two factors against a sub-block's reference row, A and B, T =
# (I + A)⁻¹ as a product of I + (−A)^(2^i) (A is strictly lower: nilpotent),
# W, Û, then U, O and the new state; every product float32 at HIGHEST.
# β is folded into the operands by the caller: `kb` = β·k, `vb` = β·v.
# Chunks past `n_used` name the last used blocks again and do nothing.

_SUB = 16          # rows a decay reference serves (delta_rule.SUB_BLOCK)


def _chunk_kernel(slot_ref, first_ref, fresh_ref, used_ref, s_ref, q_ref,
                  k_ref, kb_ref, vb_ref, g_ref, o_ref, s_out_ref, *, heads,
                  chunk):
    from jax.experimental import pallas as pl

    n = pl.program_id(0)
    C = chunk
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def mm(a, b):
        return jnp.dot(a, b, precision=hi, preferred_element_type=f32)

    def mm_t(a, b):             # a · bᵀ
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=hi, preferred_element_type=f32)

    def t_mm(a, b):             # aᵀ · b
        return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                   precision=hi, preferred_element_type=f32)

    @pl.when((n == 0) & (used_ref[0] == 0))
    def _():                    # no chunk at all: slot 0 passes through
        s_out_ref[...] = s_ref[...]

    @pl.when(n < used_ref[0])
    def _():
        starts = first_ref[n] == 1
        fresh = fresh_ref[n] == 1
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        ones_tri = (row >= col).astype(f32)
        eye = (row == col).astype(f32)
        dk = q_ref.shape[-1]
        r_dk = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
        one = jnp.ones((1, 1), f32)

        def head(h, carry):
            q, k, kb, vb, g = (r[0, h] for r in (q_ref, k_ref, kb_ref,
                                                 vb_ref, g_ref))
            G = mm(ones_tri, g)                                # [C, dk]
            prod = None
            for a in range(C // _SUB):
                # rows of sub-block a against every earlier column, both
                # factors taken against G at the last row before it
                ref = jnp.zeros((1, dk), f32) if a == 0 else \
                    G[a * _SUB - 1:a * _SUB, :]
                down = jnp.exp(jnp.minimum(G - ref, 0.0))
                cols = jnp.where(r_dk < (a + 1) * _SUB,
                                 k * jnp.exp(jnp.minimum(ref - G, 80.0)),
                                 0.0)
                pa = mm_t(jnp.concatenate([kb * down, q * down], axis=0),
                          cols)                                # [2C, C]
                in_a = (jax.lax.broadcasted_iota(jnp.int32, (2 * C, C), 0)
                        % C) // _SUB == a
                prod = jnp.where(in_a, pa, 0.0 if prod is None else prod)
            A = jnp.where(row > col, prod[:C], 0.0)
            B = jnp.where(row >= col, prod[C:], 0.0)
            # (I + A)⁻¹ = Π (I + (−A)^(2^i))
            P = -A
            T = eye + P
            for _ in range(max(C.bit_length() - 2, 0)):
                P = mm(P, P)
                T = T + mm(T, P)
            gam = jnp.exp(G)
            W = mm(T, kb * gam)
            Uh = mm(T, vb)
            S0 = jnp.where(starts,
                           jnp.where(fresh, 0.0, s_ref[0, h]),
                           s_out_ref[0, h])
            U = Uh - mm(W, S0)
            o_ref[0, h] = mm(q * gam, S0) + mm(B, U)
            last = G[C - 1:C, :]
            # the chunk's whole decay as a COLUMN (a product with [[1]]
            # transposes the row): it scales the state's rows
            s_out_ref[0, h] = t_mm(jnp.exp(last), one) * S0 + t_mm(
                k * jnp.exp(last - G), U)
            return carry

        jax.lax.fori_loop(0, heads, head, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_chunks(state, q, k, kb, vb, g, slot_of, starts_run, fresh,
                      n_used, interpret=False):
    """state [S, H, d_k, d_v] float32; q k kb g [N, H, C, d_k], vb [N, H,
    C, d_v] float32, chunk-major (rows that are not live neutral: zeros);
    slot_of / starts_run / fresh [N] int32 a chunk's slot, whether it
    begins a run and whether that run begins its sequence (for the chunks
    past `n_used`: the last used chunk's slot); n_used [1] int32 → (o [N,
    H, C, d_v] float32, unspecified past `n_used`; the new state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk, dv = state.shape
    N, _, C, _ = q.shape
    if C % _SUB or C & (C - 1):
        raise ValueError(f"a chunk of {C} rows: whole sub-blocks of "
                         f"{_SUB} and a power of two")

    def by_chunk(n, slot, first, fresh_, used):
        return (jnp.maximum(jnp.minimum(n, used[0] - 1), 0), 0, 0, 0)

    def by_slot(n, slot, first, fresh_, used):
        return (slot[n], 0, 0, 0)

    rows = pl.BlockSpec((1, H, C, dk), by_chunk)
    vals = pl.BlockSpec((1, H, C, dv), by_chunk)
    slab = pl.BlockSpec((1, H, dk, dv), by_slot)
    o, new = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=H, chunk=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N,),
            in_specs=[slab, rows, rows, rows, vals, rows],
            out_specs=[vals, slab]),
        out_shape=[jax.ShapeDtypeStruct((N, H, C, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(slot_of.astype(jnp.int32), starts_run.astype(jnp.int32),
      fresh.astype(jnp.int32), n_used.astype(jnp.int32), state, q, k, kb,
      vb, g)
    return o, new
