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
import math

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
# One grid step is one chunk of C rows of one run, all heads; a run's
# chunks follow each other, so the state a head `[d_k, d_v]` is carried
# from a chunk to the next in the RESULT's block (all heads of one slot:
# the block stays resident while consecutive steps name the same slot, and
# is written back when the next run's slot differs or the grid ends). A
# run's first chunk starts from the stored state (the argument's block,
# aliased to the result) or from zero.
#
# NO LAYOUT. q, k, v, g stay the tick's flat `[T, H, d]` arrays in HBM
# (seen as `[T·H, d]`: T is not a tiled dimension, so a chunk's rows are
# one contiguous copy from ANY first row), β `[T, 1, 128]` (a row a lane
# tile) for the same; the kernel copies chunk n + 1 while it computes
# chunk n, takes a head's `[C, d]` out of the copy with a strided load,
# masks the rows past the chunk's live ones to the neutral row (k = q = v
# = g = β = 0) and copies the live rows of the result back to the flat
# `[T, H, d_v]`, which arrives zeroed and aliased: rows off the runs are
# never written. A copy that would run past row T starts at T − C and the
# loads skip the rows before the chunk's first.
#
# The arithmetic is `nn/functional/delta_rule.py`'s (its docstring has the
# equations), every product float32 at HIGHEST. What bounds the kernel is
# not the MXU's passes but how many products stand one behind the other (a
# product of 32 rows costs its latency, about 0.2 µs, whatever it holds:
# PERF.md §6, PR 36), so the heads go in GROUPS of 128 / C (4 at C = 32)
# and everything that is C × C a head is ONE block-diagonal 128 × 128
# operand a group. A group of 4 heads makes 19 products where PR 35's
# kernel made 72:
#   G        a scan of log2 C shifted adds (no product)
#   A, B     one product a HEAD: the rows [k; q], each scaled against its
#            OWN sub-block's reference row, against every sub-block's
#            column set side by side
#   T        (I + A)⁻¹ = Π (I + P^(2^i)), P = −A (strictly lower a block:
#            nilpotent): T and the power are polynomials in P and commute,
#            so one product P_i · [T_i | P_i] gives both T_{i+1} − T_i and
#            P_{i+1}: log2 C products a GROUP
#   W, Û     one product a group, T · [β Γ ⊙ K | β V] (the heads' rows one
#            under the other)
#   W S, Q S one product a head, [W; Γ ⊙ Q] · S
#   B U      one a group; K̄ᵀU one a head; the chunk's decay as a COLUMN is
#            a transpose on the XLU, not a product.
# Chunks past `n_used` name the last used slot again and do nothing.

_SUB = 16          # rows a decay reference serves (delta_rule.SUB_BLOCK)


def _chunk_kernel(row0_ref, live_ref, slot_ref, first_ref, fresh_ref,
                  used_ref, s_ref, q_hbm, k_hbm, v_hbm, g_hbm, b_hbm, _o_in,
                  o_hbm, s_out_ref, qbuf, kbuf, vbuf, gbuf, bbuf, obuf,
                  in_sems, out_sems, *, heads, chunk, rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = pl.program_id(0)
    C, T, H, nb = chunk, rows, heads, chunk // _SUB
    hb = math.gcd(H, max(1, 128 // C))      # heads a group
    GC = hb * C
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32

    def mm(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, precision=hi,
                                   preferred_element_type=f32)

    def copies(m, half):
        """Chunk m's five copies into buffer `half` (to start, or the
        same descriptors to wait on)."""
        base = jnp.minimum(row0_ref[m], T - C)
        out = [pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of(base * H, 8), C * H)],
            buf.at[pl.ds(half * 2 * C * H, C * H)], in_sems.at[half, i])
            for i, (src, buf) in enumerate((
                (q_hbm, qbuf), (k_hbm, kbuf), (v_hbm, vbuf), (g_hbm, gbuf)))]
        out.append(pltpu.make_async_copy(
            b_hbm.at[pl.ds(base, C)], bbuf.at[half, pl.ds(0, C)],
            in_sems.at[half, 4]))
        return out

    def write(m, half, at, size, start):
        """`size` (static) rows of chunk m's result from row `at` of it."""
        dma = pltpu.make_async_copy(
            obuf.at[pl.ds(pl.multiple_of((half * C + at) * H, 8), size * H)],
            o_hbm.at[pl.ds(pl.multiple_of((row0_ref[m] + at) * H, 8),
                           size * H)], out_sems.at[half])
        dma.start() if start else dma.wait()

    def on_diagonal(x, j):
        """[C, C] as block (j, j) of a group's row block [C, GC]."""
        parts = [jnp.zeros((C, w), f32) if w else None
                 for w in (j * C, GC - (j + 1) * C)]
        parts = [p for p in (parts[0], x, parts[1]) if p is not None]
        return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x

    def stacked(xs):
        return jnp.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]

    @pl.when((n == 0) & (used_ref[0] == 0))
    def _():                    # no chunk at all: slot 0 passes through
        s_out_ref[...] = s_ref[...]

    @pl.when(n < used_ref[0])
    def _():
        half = n % 2

        @pl.when(n == 0)
        def _():
            for c in copies(0, 0):
                c.start()

        @pl.when(n + 1 < used_ref[0])
        def _():
            for c in copies(n + 1, 1 - half):
                c.start()

        for c in copies(n, half):
            c.wait()

        starts = first_ref[n] == 1
        fresh = fresh_ref[n] == 1
        live = live_ref[n]
        off = row0_ref[n] - jnp.minimum(row0_ref[n], T - C)
        row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        g_row = jax.lax.broadcasted_iota(jnp.int32, (GC, GC), 0)
        g_col = jax.lax.broadcasted_iota(jnp.int32, (GC, GC), 1)
        eye = (g_row == g_col).astype(f32)
        dk = q_hbm.shape[-1]
        r_dk = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
        on = r_dk < live
        sub_of = (jax.lax.broadcasted_iota(jnp.int32, (2 * C, C), 0)
                  % C) // _SUB
        beta_rows = bbuf[half, pl.ds(off, C), 0, :]            # [C, 128]
        lane_h = jax.lax.broadcasted_iota(jnp.int32, beta_rows.shape, 1)

        def a_head(h, j):
            """Head h's state-free terms: its blocks of the group's P and
            B, [β Γ ⊙ K | β V], Γ ⊙ Q, K̄, the chunk's log decay, S."""
            # a head's row of every row of the chunk: H rows apart
            mine = pl.ds((half * 2 * C + off) * H + h, C, stride=H)
            q, k, v, g = (jnp.where(on, buf[mine, :], 0.0)
                          for buf in (qbuf, kbuf, vbuf, gbuf))
            beta = jnp.where(on[:, :1], jnp.sum(
                jnp.where(lane_h == h, beta_rows, 0.0), axis=1,
                keepdims=True), 0.0)                           # [C, 1]
            G = g
            s = 1
            while s < C:        # the cumulative log decay, ≤ 0
                G = G + jnp.where(r_dk >= s, pltpu.roll(G, s, 0), 0.0)
                s *= 2
            # a row's factors are taken against G at the last row before
            # its sub-block; its columns are every earlier row's
            refs = [jnp.zeros((1, dk), f32)] + [
                G[a * _SUB - 1:a * _SUB, :] for a in range(1, nb)]
            ref_row = refs[0]
            for a in range(1, nb):
                ref_row = jnp.where(r_dk >= a * _SUB, refs[a], ref_row)
            down = jnp.exp(G - ref_row)
            cols = jnp.concatenate([
                (k * jnp.exp(jnp.minimum(refs[a] - G, 80.0)))[
                    :(a + 1) * _SUB] for a in range(nb)], axis=0)
            prod = mm(jnp.concatenate([k * down, q * down], axis=0), cols,
                      (((1,), (1,)), ((), ())))                # [2C, ·]
            sel, at = None, 0
            for a in range(nb):
                # sub-block a's columns and, after them, a later set's:
                # those lie above the diagonal
                pa = prod[:, at:at + C]
                sel = pa if sel is None else jnp.where(sub_of == a, pa, sel)
                at += (a + 1) * _SUB
            gam = jnp.exp(G)
            last = G[C - 1:C, :]
            return (on_diagonal(jnp.where(row > col, sel[:C], 0.0) * -beta, j),
                    on_diagonal(jnp.where(row >= col, sel[C:], 0.0), j),
                    jnp.concatenate([k * gam, v], axis=1) * beta, q * gam,
                    k * jnp.exp(last - G), last,
                    jnp.where(starts, jnp.where(fresh, 0.0, s_ref[0, h]),
                              s_out_ref[0, h]))

        def group(gi, carry):
            P, B, R, Qg, Kbar, last, S0 = zip(*(
                a_head(gi * hb + j, j) for j in range(hb)))
            P, B = stacked(P), stacked(B)
            Tm = eye + P
            P = mm(P, P)
            for _ in range(C.bit_length() - 3):
                X = mm(P, jnp.concatenate([Tm, P], axis=1))
                Tm, P = Tm + X[:, :GC], X[:, GC:]
            Tm = Tm + mm(P, Tm)
            WU = mm(Tm, stacked(R))                            # [GC, 2d]
            U, QS = [], []
            for j in range(hb):
                mine = slice(j * C, (j + 1) * C)
                XS = mm(jnp.concatenate([WU[mine, :dk], Qg[j]], axis=0),
                        S0[j])
                U.append(WU[mine, dk:] - XS[:C])
                QS.append(XS[C:])
            BU = mm(B, stacked(U))
            for j in range(hb):
                h = gi * hb + j
                obuf[pl.ds(half * C * H + h, C, stride=H), :] = \
                    QS[j] + BU[j * C:(j + 1) * C]
                decay = jnp.transpose(jnp.broadcast_to(
                    jnp.exp(last[j]), (8, dk)))[:, :1]         # [dk, 1]
                s_out_ref[0, h] = decay * S0[j] + mm(
                    Kbar[j], U[j], (((0,), (0,)), ((), ())))
            return carry

        jax.lax.fori_loop(0, H // hb, group, 0)

        # the live rows back to the flat result: a whole chunk is one
        # copy, waited for a chunk later; a run's last, partial chunk one
        # copy a set bit of its row count, so nothing past its rows
        @pl.when((n > 0) & (live_ref[jnp.maximum(n - 1, 0)] == C))
        def _():
            write(n - 1, 1 - half, 0, C, False)

        @pl.when(live == C)
        def _():
            write(n, half, 0, C, True)

            @pl.when(n == used_ref[0] - 1)
            def _():
                write(n, half, 0, C, False)

        @pl.when(live < C)
        def _():
            size = C // 2
            while size:
                @pl.when(live & size != 0)
                def _(size=size):
                    at = live & ~(2 * size - 1)     # the larger bits' rows
                    write(n, half, at, size, True)
                    write(n, half, at, size, False)
                size //= 2


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def delta_rule_chunks(state, q, k, v, g, beta, o, row0, live, slot_of,
                      starts_run, fresh, n_used, chunk=32, interpret=False):
    """state [S, H, d_k, d_v] float32; q k g [T, H, d_k], v [T, H, d_v],
    β [T, H] float32: the tick's flat rows; o [T, H, d_v] float32 ZEROS
    (aliased to the result). A chunk n < n_used [1] is rows row0[n] ..
    row0[n] + live[n] (1 ≤ live ≤ `chunk`) of slot slot_of[n];
    starts_run / fresh [N] whether it begins a run and whether that run
    begins its sequence (past `n_used`: the last used chunk's slot); a
    run's chunks stand in order → (o with the chunks' live rows written,
    the new state). T at least `chunk`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, dk, dv = state.shape
    T, C = q.shape[0], int(chunk)
    if C % _SUB or C & (C - 1) or C < 2 * _SUB:
        raise ValueError(f"a chunk of {C} rows: a power of two of at "
                         f"least two sub-blocks of {_SUB}")
    if T < C or H > 128 or (H % 8 and not interpret):
        raise ValueError(f"{T} rows of {H} heads: at least a chunk of {C} "
                         f"rows; heads in whole tiles of 8, at most 128")

    def by_slot(n, row0_, live_, slot, *_):
        return (slot[n], 0, 0, 0)

    slab = pl.BlockSpec((1, H, dk, dv), by_slot)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    # two chunks' rows `[C·H, d]` each, and as much again that a head's
    # strided load may run into when a copy had to start before its chunk
    buf = lambda d: pltpu.VMEM((2 * 2 * C * H, d), jnp.float32)  # noqa: E731
    new_o, new = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=H, chunk=C, rows=T),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(row0.shape[0],),
            in_specs=[slab, hbm, hbm, hbm, hbm, hbm, hbm],
            out_specs=[hbm, slab],
            scratch_shapes=[
                buf(dk), buf(dk), buf(dv), buf(dk),
                pltpu.VMEM((2, 2 * C, 1, 128), jnp.float32),
                pltpu.VMEM((2 * C * H, dv), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 5)),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((T * H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1, 12: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(*(x.astype(jnp.int32) for x in (row0, live, slot_of, starts_run,
                                      fresh, n_used)),
      state, *(x.reshape(T * H, x.shape[-1]) for x in (q, k, v, g)),
      jnp.pad(beta, ((0, 0), (0, 128 - H)))[:, None, :],
      o.reshape(T * H, dv))
    return new_o.reshape(T, H, dv), new
