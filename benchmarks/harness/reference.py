"""The plain reference: GPT-2 (pre-LN block, learned positions, tied
head, exact GELU, LayerNorm eps 1e-5) in straightforward `jax.numpy`
float32 under `precision="highest"`, with no kernel, no cache and no
batching tricks. It imports nothing from the program and is given
nothing the program made: weights come from `harness.weights` and the
seed, tokens from the traffic generator and the served output.

It is computed in blocks so that it fits beside nothing else: layers
through `lax.scan` (one compile for any depth), training rows a few at
a time with the gradient accumulated.

`quant` selects the CONTROL, the reference in the nearest precision
below the one the configuration states (bf16 → 8 bits): "int8" rounds
every matmul's activations (per row) and weights (per output channel)
to int8 and multiplies in integers; "fp8" rounds both to float8_e4m3
(per-tensor scale) in the forward pass, straight-through backward.
"""
import functools
import math

LN_EPS = 1e-5
ADAMW = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
         "weight_decay": 0.01}


def _ln(x, w, b):
    import jax

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


def _mm(x, w, quant):
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


def _block(x, lw, n_head, quant):
    """One pre-LN decoder block over x [b, s, d]; lw is one layer's
    slice of the stacked tree (any float dtype, used as float32)."""
    import jax
    import jax.numpy as jnp

    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    b, s, d = x.shape
    hd = d // n_head
    h = _ln(x, lw["ln1_w"], lw["ln1_b"])
    qkv = _mm(h, lw["qkv_w"], quant) + lw["qkv_b"]
    q, k, v = (t.reshape(b, s, n_head, hd)
               for t in jnp.split(qkv, 3, axis=-1))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision="highest") / math.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision="highest").reshape(b, s, d)
    x = x + _mm(a, lw["proj_w"], quant) + lw["proj_b"]
    h = _ln(x, lw["ln2_w"], lw["ln2_b"])
    h = jax.nn.gelu(_mm(h, lw["fc1_w"], quant) + lw["fc1_b"],
                    approximate=False)
    return x + _mm(h, lw["fc2_w"], quant) + lw["fc2_b"]


def hidden(w, ids, n_head, quant=None, remat=False):
    """Final-LayerNorm hidden states [b, s, d] of token ids [b, s]."""
    import jax
    import jax.numpy as jnp

    s = ids.shape[1]
    x = w["wte"].astype(jnp.float32)[ids] \
        + w["wpe"].astype(jnp.float32)[:s]

    def body(x, lw):
        return _block(x, lw, n_head, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["layers"])
    return _ln(x, w["lnf_w"].astype(jnp.float32),
               w["lnf_b"].astype(jnp.float32))


def logits_fn(w, ids, n_head, quant=None):
    import jax.numpy as jnp

    x = hidden(w, ids, n_head, quant)
    return _mm(x, w["wte"].astype(jnp.float32).T, quant)


def loss_sum(w, ids, n_head, quant=None):
    """Σ over rows and the s-1 shifted positions of the next-token
    cross entropy (the caller divides by the count)."""
    import jax
    import jax.numpy as jnp

    x = hidden(w, ids, n_head, quant, remat=True)[:, :-1]
    lg = _mm(x, w["wte"].astype(jnp.float32).T, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


# ------------------------------------------------------------- serving

@functools.lru_cache(maxsize=None)
def _gap_fn(n_head, quant):
    import jax
    import jax.numpy as jnp

    def fn(w, ids, rows, served):
        """ids [1, S] (right-padded: causal, so harmless); rows [R] the
        positions whose logits chose a served token (padding rows
        repeat a real one); served [R] the tokens served there. Returns
        for each row how far the served token's logit — or, in the
        control, the logit of the token the low precision puts first —
        lies below the reference's best, and the reference's own margin
        there (its best logit less its second best)."""
        x = hidden(w, ids, n_head)[0][rows]
        lg = _mm(x, w["wte"].astype(jnp.float32).T, None)
        top2 = jax.lax.top_k(lg, 2)[0]
        if quant is None:
            tok = served
        else:
            xq = hidden(w, ids, n_head, quant)[0][rows]
            tok = jnp.argmax(
                _mm(xq, w["wte"].astype(jnp.float32).T, quant), -1)
        gap = top2[:, 0] - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
        return gap, top2[:, 0] - top2[:, 1]

    return jax.jit(fn)


def served_token_gaps(w, n_head, toks, plen, pad_to, rows_to, quant=None):
    """(gaps, reference margins) of the served tokens `toks[plen:]` of
    one sequence, one forward over the whole of it. Shapes are padded
    to (`pad_to`, `rows_to`) so that every seed compiles the same few
    programs."""
    import numpy as np

    toks = np.asarray(toks, np.int32)
    n = len(toks) - plen                   # served tokens
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(toks)] = toks
    rows = np.full((rows_to,), plen - 1, np.int32)
    rows[:n] = np.arange(plen - 1, len(toks) - 1)
    served = np.full((rows_to,), toks[plen], np.int32)
    served[:n] = toks[plen:]
    gap, margin = _gap_fn(int(n_head), quant)(w, ids, rows, served)
    return np.asarray(gap)[:n], np.asarray(margin)[:n]


# ------------------------------------------------------------ training

def leaf_norms(tree):
    """Per-leaf L2 norms with each layer of a stacked leaf its own
    leaf, and the fused qkv leaves split into their q, k and v thirds
    (a key's bias has no gradient under softmax; as a third of a fused
    leaf it would hide in the other two):
    {"wte": scalar, "layers/fc1_w": [L], "layers/qkv_b": [L, 3], ...}."""
    import jax.numpy as jnp

    out = {}
    for name, v in tree.items():
        if name == "layers":
            for ln, lv in v.items():
                lv = lv.astype(jnp.float32)
                if ln.startswith("qkv"):
                    lv = lv.reshape(lv.shape[:-1] + (3, lv.shape[-1] // 3))
                    lv = jnp.moveaxis(lv, -2, 1)        # [L, 3, ...]
                    axes = tuple(range(2, lv.ndim))
                else:
                    axes = tuple(range(1, lv.ndim))
                out["layers/" + ln] = jnp.sqrt(jnp.sum(lv * lv, axis=axes))
        else:
            v = v.astype(jnp.float32)
            out[name] = jnp.sqrt(jnp.sum(v * v))
    return out


@functools.lru_cache(maxsize=None)
def _grad_fn(n_head, quant):
    import jax

    return jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, n_head=n_head, quant=quant)))


@functools.lru_cache(maxsize=None)
def _adamw_fn():
    import jax
    import jax.numpy as jnp

    h = ADAMW

    def fn(w, g, m, v, t):
        def one(p, g, m, v):
            m = h["beta1"] * m + (1 - h["beta1"]) * g
            v = h["beta2"] * v + (1 - h["beta2"]) * g * g
            p = p * (1.0 - h["lr"] * h["weight_decay"])
            mh = m / (1 - h["beta1"] ** t)
            vh = v / (1 - h["beta2"] ** t)
            return p - h["lr"] * mh / (jnp.sqrt(vh) + h["eps"]), m, v

        out = jax.tree_util.tree_map(one, w, g, m, v)
        pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    return jax.jit(fn, donate_argnums=(0, 2, 3))


def train_three_steps(w0, batches, n_head, quant=None, rows_per_block=4,
                      keep_rows=None):
    """AdamW (the program's hyper-parameters, decoupled decay on every
    leaf) from float32 weights `w0` over `batches` (a list of int32
    [b, s] arrays). `keep_rows` plants the half-batch fault: only those
    rows are used and the mean is taken over them.

    Returns {"losses": [..], "grad1": leaf norms of the first gradient,
    "change": leaf norms of w_after − w0}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    grad = _grad_fn(int(n_head), quant)
    adamw = _adamw_fn()
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, c: jax.tree_util.tree_map(
        lambda x: x * c, a), donate_argnums=(0,))
    zeros = jax.jit(lambda a: jax.tree_util.tree_map(jnp.zeros_like, a))

    w = jax.tree_util.tree_map(lambda x: x + 0, w0)     # w0 stays
    m, v = zeros(w), zeros(w)
    losses, grad1 = [], None
    for t, ids in enumerate(batches, start=1):
        ids = np.asarray(ids, np.int32)
        if keep_rows is not None:
            ids = ids[np.asarray(keep_rows)]
        count = ids.shape[0] * (ids.shape[1] - 1)
        total, g = 0.0, None
        for r in range(0, ids.shape[0], rows_per_block):
            ls, gb = grad(w, ids[r:r + rows_per_block])
            total += float(ls)
            g = gb if g is None else add(g, gb)
        g = scale(g, np.float32(1.0 / count))
        losses.append(total / count)
        if t == 1:
            grad1 = {k: np.asarray(x) for k, x in norms(g).items()}
        w, m, v = adamw(w, g, m, v, np.float32(t))
    change = {k: np.asarray(x) for k, x in diff_norms(w, w0).items()}
    return {"losses": losses, "grad1": grad1, "change": change}
