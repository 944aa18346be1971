"""Weights from `--seed`, on the device, in ONE jitted call, in the
dtype they are served or trained in. The tree is the plain GPT-2 one,
layers stacked on a leading axis:

    wte [V,d]  wpe [P,d]  lnf_w lnf_b [d]
    layers: ln1_w ln1_b ln2_w ln2_b [L,d]  qkv_w [L,d,3d] qkv_b [L,3d]
            proj_w [L,d,d] proj_b [L,d]  fc1_w [L,d,f] fc1_b [L,f]
            fc2_w [L,f,d] fc2_b [L,d]

Matrices are [in, out] (y = x @ W + b); qkv's output is [q | k | v],
each [n_head, head] inside. The initialisation is GPT-2's (normal 0.02,
residual projections scaled by 1/sqrt(2L)) with small random biases and
LayerNorm offsets so that no leaf is exactly zero or one.

The program gets these through a builder; the reference makes them
again from the seed with this same function and never sees the
program's copies.
"""
import functools
import math


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (seeds above 2**31
    overflow `PRNGKey` on a 32-bit build)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def shapes(cfg):
    from .arith import dims

    d, L, _, f, v, p = dims(cfg)
    top = {"wte": (v, d), "wpe": (p, d), "lnf_w": (d,), "lnf_b": (d,)}
    layers = {"ln1_w": (L, d), "ln1_b": (L, d), "ln2_w": (L, d),
              "ln2_b": (L, d), "qkv_w": (L, d, 3 * d), "qkv_b": (L, 3 * d),
              "proj_w": (L, d, d), "proj_b": (L, d), "fc1_w": (L, d, f),
              "fc1_b": (L, f), "fc2_w": (L, f, d), "fc2_b": (L, d)}
    return top, layers


def tree_from_key(key, cfg_items, dtype):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    top, layers = shapes(cfg)
    L = int(cfg["n_layer"])
    names = sorted(top) + ["layers/" + n for n in sorted(layers)]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def draw(name, shape):
        base = name.rsplit("/", 1)[-1]
        std = 0.02
        if base in ("proj_w", "fc2_w"):
            std = 0.02 / math.sqrt(2 * L)
        x = std * jax.random.normal(keys[name], shape, jnp.float32)
        if base.endswith("_w") and base.startswith("ln"):
            x = 1.0 + x
        return x.astype(dtype)

    out = {n: draw(n, s) for n, s in top.items()}
    out["layers"] = {n: draw("layers/" + n, s) for n, s in layers.items()}
    return out


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax

    return jax.jit(tree_from_key, static_argnums=(1, 2))


def cfg_items(cfg):
    return tuple(sorted((k, int(cfg[k])) for k in (
        "n_embd", "n_layer", "n_head", "n_inner", "n_positions",
        "vocab_size") if cfg.get(k) is not None))


def make_weights(cfg, seed, dtype):
    """The whole tree, made on the device from `seed` in one call."""
    return _jitted()(seed_key(seed), cfg_items(cfg), str(dtype))
