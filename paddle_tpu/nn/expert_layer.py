"""A serving expert layer that is told which experts it holds.

`distributed/moe.py` is training's layer: capacity buckets, dropped
tokens, every expert on every chip. Serving under expert parallelism
asks something else of one chip (model-configs guide §4): the router
keeps its published width and experts per token, this chip holds
`experts_held` of the experts (ids `first_expert` … `first_expert +
experts_held - 1`), computes ITS experts' part of the result for the
tokens routed to them, and drops nothing. What the absent experts would
add arrives from the other chips in a deployment and is simply absent
on one chip: no code here stands in for them.

Raw `jax.numpy` in and out (the callers are step bodies already under
`jax.jit`). Shapes are static whatever the routing: the `T·k`
assignments are sorted by expert with the ones this chip does not hold
(and those of padding rows) behind the last group, and ONE grouped
matrix product per projection runs over the sorted rows, its group
sizes a traced vector. So a step program holds one executable, and the
bytes the product reads are the experts that were touched.
"""
import jax
import jax.numpy as jnp

from .functional.attention import _pallas_backend_ok

__all__ = ["route_top_k", "held_experts_ffn", "grouped_matmul"]

_scope = jax.named_scope


def route_top_k(x, router_w, top_k, renormalise=True, scoring="softmax",
                select_bias=None, n_group=None, topk_group=None):
    """Routing in float32 over ALL `router_w.shape[1]` experts: x
    [T, d], router_w [d, E_all] → (weights [T, top_k] float32, expert
    ids [T, top_k] int32). `scoring` turns the logits into scores:
    "softmax" over the experts, or "sigmoid" of each. The `top_k`
    largest of score + `select_bias` [E_all] are chosen (the bias
    selects only: an auxiliary-loss-free load balance); the weights are
    the chosen SCORES, divided by their sum with `renormalise`
    (norm_topk_prob). With `n_group` the selection is GROUP-LIMITED: the
    experts stand in `n_group` groups of consecutive ids, a group's
    score is the sum of its 2 largest score + bias, and only the
    `topk_group` best groups' experts can be chosen."""
    with _scope("moe_router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision="highest")
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"router scoring {scoring!r}")
        if select_bias is None and not n_group:
            w, ids = jax.lax.top_k(scores, int(top_k))
        else:
            sel = scores if select_bias is None else \
                scores + select_bias.astype(jnp.float32)
            if n_group:
                T, E = sel.shape
                G, per = int(n_group), E // int(n_group)
                group = jnp.sum(jax.lax.top_k(
                    sel.reshape(T, G, per), 2)[0], axis=-1)
                _, best = jax.lax.top_k(group, int(topk_group))
                keep = jnp.zeros((T, G), bool).at[
                    jnp.arange(T)[:, None], best].set(True)
                sel = jnp.where(jnp.repeat(keep, per, axis=1), sel,
                                -jnp.inf)
            _, ids = jax.lax.top_k(sel, int(top_k))
            w = jnp.take_along_axis(scores, ids, axis=-1)
        if renormalise:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w, ids.astype(jnp.int32)


# the Pallas grouped matmul's tiles: 128 rows, and of (k, n) the whole
# contraction of the first projection and 1 024 columns read best at 48
# and at 512 rows on a v5e at d 3072 (PERF.md §6, PR 28). A function of
# the shapes: the contraction tile is the largest divisor of k in whole
# 128-lane tiles that two buffers of `[tile_k, tile_n]` keep inside the
# scoped VMEM (3 072 of d 3072 and 1 024 of 1 024 as before; 2 048 of d
# 4096 and of 2 048)
_GMM_TILE_M = 128
_GMM_TILE_K_MOST = 3072
_GMM_TILE_N = 1024
_GMM_TILE_N_MOST = 1280


def _gmm_tile_k(k):
    for tile in range(min(_GMM_TILE_K_MOST, k) // 128 * 128, 0, -128):
        if k % tile == 0:
            return tile
    return min(_GMM_TILE_K_MOST, k)


def _gmm_tile_n(n):
    """1 024 columns where they divide n (2 048, 3 072, 4 096: as before);
    else the largest divisor of n in whole 128-lane tiles up to 1 280: a
    last tile that is half empty costs its whole copy (on a v5e at 192
    assignments over 128 experts, d 2560: n 1 536 at 1 024 / 768 / 512
    columns 1.292 / 1.216 / 1.179 ms, n 2 560 at 1 024 / 1 280 / 2 560 0.696 /
    0.623 / 0.626 ms: PERF.md §6, PR 35, step 0 (c))."""
    if n <= _GMM_TILE_N or n % _GMM_TILE_N == 0:
        return min(_GMM_TILE_N, n)
    for tile in range(_GMM_TILE_N_MOST, 0, -128):
        if n % tile == 0:
            return tile
    return _GMM_TILE_N


def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [M, K] sorted by group, rhs [G, K, N], group_sizes [G] int32
    (Σ ≤ M) → [M, N]; rows past the last group are unspecified. Where
    Pallas kernels run (a TPU: `_pallas_backend_ok`), the grouped matmul
    shipped with JAX, M a multiple of its row tile (0.762 against
    `ragged_dot`'s 0.757 ms at 48 rows, 1.779 against 2.860 ms at 512:
    PERF.md §6, PR 28); `jax.lax.ragged_dot` elsewhere."""
    if _pallas_backend_ok():
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        m, k = lhs.shape
        n = rhs.shape[2]
        tiling = (min(_GMM_TILE_M, m), _gmm_tile_k(k), _gmm_tile_n(n))
        return gmm(lhs, rhs, group_sizes,
                   preferred_element_type=lhs.dtype, tiling=tiling)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def held_experts_ffn(x, weights, ids, valid, w_gate_up, w_down,
                     first_expert=0):
    """The held experts' part of a sparse feed-forward layer, dropless.

    x [T, d] the normed input; weights / ids [T, k] from `route_top_k`;
    valid [T] bool (False: a padding row, routed nowhere); w_gate_up
    [E, d, 2m] (gate | up) and w_down [E, m, d] the E experts held here,
    expert j of them being router output `first_expert + j`.

    Returns (out [T, d] float32 = Σ over the held experts a row chose of
    weight · E_e(x), counters int32 [3] = assignments of valid rows,
    those that fell on a held expert, held experts with at least one
    row)."""
    T, d = x.shape
    k = ids.shape[1]
    E, _, m2 = w_gate_up.shape
    m = m2 // 2
    with _scope("moe_experts"):
        flat_e = ids.reshape(-1) - int(first_expert)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        flat_w = weights.reshape(-1)
        live = jnp.repeat(valid, k)
        held = live & (flat_e >= 0) & (flat_e < E)
        key = jnp.where(held, flat_e, E)            # not held: last
        order = jnp.argsort(key, stable=True)
        key_s = key[order]
        tok_s = flat_t[order]
        sizes = jnp.bincount(key, length=E + 1)[:E].astype(jnp.int32)
        rows = T * k
        pad = (-rows) % _GMM_TILE_M if _pallas_backend_ok() else 0
        xs = x[tok_s]
        if pad:
            xs = jnp.pad(xs, ((0, pad), (0, 0)))
        h = grouped_matmul(xs, w_gate_up, sizes)
        act = (jax.nn.silu(h[:, :m].astype(jnp.float32))
               * h[:, m:].astype(jnp.float32)).astype(x.dtype)
        y = grouped_matmul(act, w_down, sizes)[:rows]
        y = jnp.where((key_s < E)[:, None],
                      y.astype(jnp.float32) * flat_w[order][:, None], 0.0)
        out = jax.ops.segment_sum(y, tok_s, num_segments=T)
        counters = jnp.stack([
            jnp.sum(live), jnp.sum(held), jnp.sum(sizes > 0)]).astype(
                jnp.int32)
    return out, counters
