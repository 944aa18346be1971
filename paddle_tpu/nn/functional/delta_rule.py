"""The gated delta rule (Kimi Delta Attention's recurrence) in the two
forms a serving step takes, raw `jax.numpy` in and out. A head's state
`S` [d_k, d_v] is float32 and obeys, a token t of its sequence,

    S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t−1} + β_t k_t v_tᵀ,  o_t = S_tᵀ q_t

with α_t = exp(g_t) a key CHANNEL (g ≤ 0) and β_t a head.

    RECURRENT  `delta_rule_step`: one row a slot, every slot at once.
               Each row reads its slot's state once and writes it once;
               a Pallas kernel on a TPU (`ops/pallas_kernels/
               delta_rule.py`), the same arithmetic in plain XLA
               elsewhere. What a decoding row takes, and every row of a
               run too short to chunk, a row an iteration.
    CHUNKED    `delta_rule_chunked`: the runs of a `SlotRunLayout` (rows
               of one slot at consecutive positions), C rows a chunk
               from a run's first row on, from the slot's stored state,
               the state after the run written back. With G the
               cumulative sum of g inside a chunk and Γ = exp(G):
                 A[i, j] = β_i Σ_c k_ic k_jc Γ_ic / Γ_jc   (j < i)
                 B[i, j] =     Σ_c q_ic k_jc Γ_ic / Γ_jc   (j ≤ i)
                 T = (I + A)⁻¹;  W = T (β Γ ⊙ K);  Û = T (β V)
               are computed for every chunk at once (they do not depend
               on the state: the WY / UT transform), and then a chunk at
               a time, in order,
                 U = Û − W S;  O = (Γ ⊙ Q) S + B U
                 S ← Γ_C ⊙ S + (K ⊙ Γ_C / Γ)ᵀ U.
               The ratios Γ_i / Γ_j are products of two factors taken
               against a REFERENCE row, a sub-block of 16 rows each (the
               last row before the sub-block of i): both factors then
               lie in (e^-80·…, e^80] for g ≥ −5 and neither overflows
               float32, which exp(−G_j) alone would after 18 rows. A
               Pallas kernel on a TPU (a chunk a grid step, copied from
               the flat rows where they lie, the state carried in the
               result's block); in plain XLA elsewhere: the runs laid
               out again so that each starts a chunk, batched products,
               `solve_triangular`, a `fori_loop` over the chunks in use.

Both forms treat a row that is not live as the identity on the state (α
= 1, β = 0, k = 0).
"""
import jax
import jax.numpy as jnp

from .attention import _pallas_backend_ok

__all__ = ["delta_rule_step", "delta_rule_chunked", "CHUNK", "SUB_BLOCK"]

# rows a chunk: one run of 2 048 rows of a 2 048-row tick, a layer, on a
# v5e: in plain XLA 6.72 ms at 32, 10.24 at 64, 25.92 at 128; the Pallas
# kernel 2.10 at 32, 2.28 at 64, 2.70 at 128 (PERF.md §6, PR 36, step 0:
# four heads' 32 × 32 blocks fill one 128 × 128 operand; PR 35's kernel
# from laid-out operands: 5.41 · 5.33 · 6.37); a sub-block is the longest
# stretch over which exp(−G) stays inside float32 at the gate's lower
# bound of −5 a token
CHUNK = 32
SUB_BLOCK = 16

_HI = jax.lax.Precision.HIGHEST


def delta_rule_step(state, q, k, v, g, beta, live, fresh, kernel=None):
    """One recurrent step, a row a slot: q k g [S, H, d_k], v [S, H, d_v],
    β [S, H], all float32; `live` [S] bool; `fresh` [S] bool: the row is
    its sequence's first, so its slot's state counts as zero whatever is
    stored (α = 0 forgets it); `state` [S, H, d_k, d_v] float32. Returns
    (o [S, H, d_v] float32, unspecified where not live; the new state).
    `kernel`: None takes the Pallas kernel where Pallas kernels run; a
    bool says which (`tools/kda_sweep.py` times both)."""
    S = state.shape[0]
    lv = live[:, None, None]
    a = jnp.where(lv, jnp.where(fresh[:, None, None], 0.0, jnp.exp(g)), 1.0)
    k = jnp.where(lv, k, 0.0)
    kb = k * beta[:, :, None]
    vb = v * beta[:, :, None]
    if _pallas_backend_ok() if kernel is None else kernel:
        from ...ops.pallas_kernels.delta_rule import delta_rule_recurrent

        # the live slots first, then the last of them again and again
        order = jnp.argsort(~live, stable=True).astype(jnp.int32)
        n_live = jnp.sum(live).astype(jnp.int32)
        order = order[jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))]
        t = lambda x: jnp.swapaxes(x, 1, 2)           # noqa: E731
        return delta_rule_recurrent(state, t(a), t(k), t(kb), t(q), vb,
                                    order, n_live[None])
    sd = state * a[..., None]
    u = vb - jnp.sum(sd * kb[..., None], axis=2)
    new = sd + k[..., None] * u[:, :, None, :]
    return jnp.sum(new * q[..., None], axis=2), new


def _chunk_terms(q, k, v, g, beta):
    """The state-free part of the chunked form for chunks [N, H, C, ·]
    (float32; rows that are not live already neutral): returns (Qg, Kbar
    [N, H, C, d_k], dec [N, H, d_k], W [N, H, C, d_k], Uh [N, H, C, d_v],
    B [N, H, C, C])."""
    N, H, C, dk = k.shape
    nb = C // SUB_BLOCK
    G = jnp.cumsum(g, axis=2)                                  # ≤ 0
    # the reference of a row's sub-block: G at the last row before it
    ref = jnp.concatenate([jnp.zeros_like(G[:, :, :1]),
                           G[:, :, SUB_BLOCK - 1:-1:SUB_BLOCK]], axis=2)
    ref_row = jnp.repeat(ref, SUB_BLOCK, axis=2)               # [N,H,C,dk]
    down = jnp.exp(G - ref_row)                                # ≤ 1
    rows = jnp.concatenate([k * down, q * down], axis=2)       # [N,H,2C,dk]
    rows = rows.reshape(N, H, 2, nb, SUB_BLOCK, dk)
    blk = jnp.arange(C) // SUB_BLOCK
    # the columns under sub-block a: k_j · exp(ref_a − G_j), the
    # sub-blocks after a (never attended) zeroed, the exponent ≤ 80
    up = ref[:, :, :, None, :] - G[:, :, None, :, :]           # [N,H,nb,C,dk]
    cols = jnp.where((blk[None, :] <= jnp.arange(nb)[:, None])[
        None, None, :, :, None],
        k[:, :, None] * jnp.exp(jnp.minimum(up, 80.0)), 0.0)
    prod = jnp.einsum("nhsbik,nhbjk->nhsbij", rows, cols, precision=_HI)
    prod = prod.reshape(N, H, 2, C, C)
    tri = jnp.arange(C)[:, None] - jnp.arange(C)[None, :]
    A = jnp.where(tri > 0, prod[:, :, 0], 0.0) * beta[..., None]
    B = jnp.where(tri >= 0, prod[:, :, 1], 0.0)
    gam = jnp.exp(G)
    rhs = jnp.concatenate([k * gam, v], axis=-1) * beta[..., None]
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    last = G[:, :, -1:, :]
    return (q * gam, k * jnp.exp(last - G), jnp.exp(last[:, :, 0]),
            sol[..., :dk], sol[..., dk:], B)


def delta_rule_chunked(state, q, k, v, g, beta, runs, chunk=CHUNK,
                       kernel=None):
    """The CHUNKED form over the runs of `runs` (a `SlotRunLayout`) of a
    flat step's rows: q k g [T, H, d_k], v [T, H, d_v], β [T, H],
    float32; `state` [S, H, d_k, d_v] float32. A run whose first row's kv
    length is 1 (position 0) starts from zero, any other from its slot's
    stored state. Returns (o [T, H, d_v] float32, zero off the runs'
    rows; the new state; chunks run). `kernel`: None takes the Pallas
    kernel where Pallas kernels run (T holds a chunk, H whole tiles of 8
    heads), which reads the runs' rows where they lie; a bool says which.
    The plain form lays the runs out again, a run from a multiple of
    `chunk` (`runs.align` is then `chunk`, spare 0)."""
    T, H, dk = k.shape
    dv = v.shape[-1]
    C = int(chunk)
    if kernel is None:
        kernel = _pallas_backend_ok() and T >= C and H % 8 == 0
    # chunk n is chunk `part` of run `run_of`: a run's chunks in order,
    # the runs in the order of their rows
    N = T // C + runs.max_runs if kernel else runs.total // C
    per_run = -(-runs.run_rows // C)
    ends = jnp.cumsum(per_run)
    n_used = ends[-1]
    chunks = jnp.arange(N, dtype=jnp.int32)
    run_of = jnp.minimum(jnp.searchsorted(ends, chunks, side="right").astype(
        jnp.int32), runs.max_runs - 1)
    part = chunks - (ends - per_run)[run_of]
    starts_run = part == 0
    slot_of = runs.run_slots[run_of]
    fresh = runs.run_first[run_of] == 1

    if kernel:
        from ...ops.pallas_kernels.delta_rule import delta_rule_chunks

        # a run's first FLAT row: the one its first laid-out row reads
        row0 = runs.src[runs.run_row0][run_of] + part * C
        live = jnp.clip(runs.run_rows[run_of] - part * C, 0, C)
        # past the chunks in use: the last used chunk's slot (no block moves)
        slot_of = slot_of[jnp.minimum(chunks, jnp.maximum(n_used - 1, 0))]
        o, state = delta_rule_chunks(
            state, q, k, v, g, beta, jnp.zeros((T, H, dv), jnp.float32),
            row0, live, slot_of, starts_run, fresh, n_used[None], chunk=C)
        return o, state, n_used

    total = runs.total
    laid = jnp.zeros((total,), bool).at[
        jnp.where(runs.expanded, runs.dest, total)].set(True, mode="drop")

    def lay(x):
        x = jnp.where(laid.reshape((total,) + (1,) * (x.ndim - 1)),
                      x[runs.src], 0.0)
        x = x.reshape((N, C) + x.shape[1:])
        return jnp.moveaxis(x, 2, 1)                           # [N,H,C,·]

    ends_run = part == per_run[run_of] - 1
    Qg, Kbar, dec, W, Uh, B = _chunk_terms(
        lay(q), lay(k), lay(v), lay(g), lay(beta))

    def body(n, carry):
        S_c, st, O = carry
        slot = slot_of[n]
        stored = jax.lax.dynamic_index_in_dim(st, slot, keepdims=False)
        S0 = jnp.where(starts_run[n],
                       jnp.where(fresh[n], 0.0, stored), S_c)
        U = Uh[n] - jnp.einsum("hck,hkv->hcv", W[n], S0, precision=_HI)
        o = jnp.einsum("hck,hkv->hcv", Qg[n], S0, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", B[n], U, precision=_HI)
        S1 = dec[n][:, :, None] * S0 + jnp.einsum(
            "hck,hcv->hkv", Kbar[n], U, precision=_HI)
        st = jax.lax.dynamic_update_index_in_dim(
            st, jnp.where(ends_run[n], S1, stored), slot, 0)
        return S1, st, jax.lax.dynamic_update_index_in_dim(O, o, n, 0)

    _, state, O = jax.lax.fori_loop(
        0, n_used, body,
        (jnp.zeros((H, dk, dv), jnp.float32), state,
         jnp.zeros((N, H, C, dv), jnp.float32)))
    O = jnp.moveaxis(O, 1, 2).reshape(total, H, dv)
    return jnp.where(runs.expanded[:, None, None], O[runs.dest], 0.0), \
        state, n_used
