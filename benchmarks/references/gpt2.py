"""Reference `gpt2`: GPT-2 (pre-LN block, learned positions, tied
head, exact GELU, LayerNorm eps 1e-5) behind the contract of
`references/__init__.py`: its weights from the seed, its plain forward
pass (serving gaps, three training steps) and its arithmetic. Key
names are GPT-2's own (n_embd, n_layer, n_head, n_inner, n_positions,
vocab_size): nothing outside this file and its builder reads them.

The forward pass is straightforward `jax.numpy` float32 under
`precision="highest"`, with no kernel, no cache and no batching
tricks. It imports nothing from the program and is given nothing the
program made: weights come from `make_weights` and the seed, tokens
from the traffic generator and the served output. It is computed in
blocks so that it fits beside nothing else: layers through `lax.scan`
(one compile for any depth), training rows a few at a time with the
gradient accumulated. `quant` selects the CONTROL (`harness.plain.mm`).

The weights' tree is the plain GPT-2 one, layers stacked on a leading
axis:

    wte [V,d]  wpe [P,d]  lnf_w lnf_b [d]
    layers: ln1_w ln1_b ln2_w ln2_b [L,d]  qkv_w [L,d,3d] qkv_b [L,3d]
            proj_w [L,d,d] proj_b [L,d]  fc1_w [L,d,f] fc1_b [L,f]
            fc2_w [L,f,d] fc2_b [L,d]

Matrices are [in, out] (y = x @ W + b); qkv's output is [q | k | v],
each [n_head, head] inside. The initialisation is GPT-2's (normal 0.02,
residual projections scaled by 1/sqrt(2L)) with small random biases and
LayerNorm offsets so that no leaf is exactly zero or one. The program
gets these through its builder; the comparison makes them again from
the seed with the same function and never sees the program's copies.

The arithmetic counts from SHAPES (the rule of `harness/arith.py`).
Copies of the program's (the originals are listed in PERF.md for a
later PR to delete): `observability.steptrace.model_flops`,
`LLMEngineConfig.kv_bytes_per_page`.
"""
import functools
import math

from harness.arith import ITEMSIZE, context_sum
from harness.plain import mm as _mm, seed_key

LN_EPS = 1e-5
ADAMW = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
         "weight_decay": 0.01}


# --------------------------------------------------------------- sizes

def dims(cfg):
    d = int(cfg["n_embd"])
    ffn = int(cfg.get("n_inner") or 4 * d)
    return d, int(cfg["n_layer"]), int(cfg["n_head"]), ffn, \
        int(cfg["vocab_size"]), int(cfg["n_positions"])


def positions(cfg):
    """The longest sequence the reference takes: its learned positions."""
    return int(cfg["n_positions"])


# ------------------------------------------------------------- weights

def shapes(cfg):
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


# ------------------------------------------------------------- forward

def _ln(x, w, b):
    import jax

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


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


def served_token_gaps(cfg, w, toks, plen, pad_to, rows_to, quant=None):
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
    gap, margin = _gap_fn(int(cfg["n_head"]), quant)(w, ids, rows,
                                                     served)
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


def train_three_steps(cfg, w0, batches, quant=None, keep_rows=None,
                      rows_per_block=4):
    """AdamW (the program's hyper-parameters, decoupled decay on every
    leaf) from float32 weights `w0` over `batches` (a list of int32
    [b, s] arrays). `keep_rows` plants the half-batch fault: only those
    rows are used and the mean is taken over them.

    Returns {"losses": [..], "grad1": leaf norms of the first gradient,
    "change": leaf norms of w_after − w0}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    grad = _grad_fn(int(cfg["n_head"]), quant)
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


# ---------------------------------------------------------- arithmetic

def param_count(cfg):
    """Every parameter of the tied-head GPT-2 (embeddings, positions,
    biases and LayerNorms included; the head is the embedding)."""
    d, L, _, ffn, v, npos = dims(cfg)
    per_layer = (d * 3 * d + 3 * d) + (d * d + d) \
        + (d * ffn + ffn) + (ffn * d + d) + 4 * d
    return v * d + npos * d + L * per_layer + 2 * d


def matmul_params(cfg):
    """Weights a token is multiplied by: the four matrices of each
    block and the tied vocabulary head (6·P / 2·P counts these)."""
    d, L, _, ffn, v, _ = dims(cfg)
    return L * (4 * d * d + 2 * d * ffn) + v * d


def train_step_flops(cfg, batch, seq):
    """Forward + backward of one training step, no recomputation:
    6·P per token plus causal attention (scores and context, forward
    once and backward twice, half the square)."""
    d, L, *_ = dims(cfg)
    tokens = int(batch) * int(seq)
    return 6 * matmul_params(cfg) * tokens + flash_attn_flops(
        cfg, batch, seq)


def flash_attn_flops(cfg, batch, seq):
    """Causal attention of one training step: q·kᵀ and p·v are 2·s²·d
    each per layer and row, forward once and backward twice (dq, and
    dk with dv), over the causal half."""
    d, L, *_ = dims(cfg)
    return L * int(batch) * (4 * int(seq) ** 2 * d) * 3 * 0.5


def attended(work):
    """Σ over the work's tokens of the context length each attended:
    every layer attends every earlier position, so a segment of `n`
    positions from `start` reads `context_sum(start, n)`."""
    return sum(context_sum(int(s), int(n)) for s, n in work["segments"])


def serve_flops(cfg, work):
    """Forward only. `work["processed"]` tokens went through the model
    (prefill rows and decode rows alike: 2·P each), and `attended` is
    the sum over those tokens of the context length each attended
    (scores and context: 4·d per attended position per layer)."""
    d, L, *_ = dims(cfg)
    return 2 * matmul_params(cfg) * int(work["processed"]) \
        + 4 * d * L * attended(work)


def kv_bytes_per_token(cfg, kv_dtype):
    """K and V rows of one token over all layers."""
    d, L, *_ = dims(cfg)
    return 2 * L * d * ITEMSIZE[kv_dtype]


def kv_bytes_per_page(cfg, page_size, kv_dtype):
    return int(page_size) * kv_bytes_per_token(cfg, kv_dtype)


def weight_bytes(cfg, dtype, work=None):
    """Bytes of the whole tree; given `work`, the least its iterations
    must read of it: every weight once an iteration."""
    held = param_count(cfg) * ITEMSIZE[dtype]
    return held if work is None else int(work["iterations"]) * held


def kv_bytes_attended(cfg, work, kv_dtype):
    """Least bytes attention must read: the K and V rows of every
    attended position, once per attending token."""
    return attended(work) * kv_bytes_per_token(cfg, kv_dtype)
