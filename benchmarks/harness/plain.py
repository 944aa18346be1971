"""What every plain reference (`references/<name>.py`) shares, written
once: the PRNG key of a seed, and the matrix product in float32 at
`precision="highest"` with the CONTROLS beside it — the nearest
precision below the one a configuration states (bf16 → 8 bits).

`quant` selects the control: "int8" rounds every matmul's activations
(per row) and weights (per output channel) to int8 and multiplies in
integers; "fp8" rounds both to float8_e4m3 (per-tensor scale) in the
forward pass, straight-through backward.
"""


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (seeds above 2**31
    overflow `PRNGKey` on a 32-bit build)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def mm(x, w, quant):
    """x [..., k] @ w [k, n] in float32/highest, or the control."""
    import jax
    import jax.numpy as jnp

    if quant is None:
        return jnp.matmul(x, w, precision="highest")
    if quant == "int8":
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0 + 1e-30
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0 + 1e-30
        xq = jnp.round(x / sx).astype(jnp.int8)
        wq = jnp.round(w / sw).astype(jnp.int8)
        acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sx * sw
    if quant == "fp8":
        def q(t):
            s = jnp.max(jnp.abs(t)) / 448.0 + 1e-30
            r = (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
            return t + jax.lax.stop_gradient(r - t)
        return jnp.matmul(q(x), q(w), precision="highest")
    raise ValueError(f"unknown control precision {quant!r}")
