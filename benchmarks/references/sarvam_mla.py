"""Reference `sarvam_mla`: the Sarvam block (`model_type: sarvam_mla`,
sarvamai/sarvam-105b) behind the contract of `references/__init__.py`:
its weights from the seed, its plain forward pass (serving gaps) and its
arithmetic. Key names are the published config's own (hidden_size,
kv_lora_rank, qk_nope_head_dim, num_experts, ...): nothing outside this
file and its builder reads them.

THE EQUATIONS. For layer l over x [T, d]:

    h = x + Attn(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

and after the last layer a final RMSNorm and logits = y · W_headᵀ
(untied). RMSNorm(x) = x / sqrt(mean(x²) + eps) · w, eps = rms_norm_eps.

  Attn     multi-head LATENT attention, H = num_attention_heads, with n
           the normed input:
             q = n·W_q → [H, q_head_dim] = [q_nope (qk_nope_head_dim) |
                 q_rope (qk_rope_head_dim)], an RMSNorm of q_head_dim
                 over each head's query (use_qk_norm), then q_rope
                 rotated
             [c_kv (kv_lora_rank) | k_r (qk_rope_head_dim)] = n·W_kv_a
             c = RMSNorm(c_kv);  k_r = RoPE(RMSNorm(k_r)), ONE key for
                 all heads
             [k_nope_h | v_h (v_head_dim)] = c·W_kv_b[h]
             s_h = (q_nope_h·k_nope_h + q_rope_h·k_r) · scale, causal
                 softmax in float32, o_h = Σ p v_h, y = concat(o_h)·W_o
           RoPE: interleaved pairs (x0, x1), (x2, x3) … re-ordered to
           halves, then rotate-half; frequencies `deepseek_yarn` (the
           YaRN ramp between beta_fast and beta_slow rotations over
           original_max_position_embeddings, factor 40); cos and sin
           scaled by mscale / mscale_all_dim of the two YaRN
           temperatures (= 1 here) and scale = q_head_dim^-½ · (0.1 ·
           mscale_all_dim · ln factor + 1)².
           This is the EXPANDED form and the only one here: the
           reference never folds W_kv_b into the query (the program's
           absorbed form is held to it by the tests).
  FFN_l    l < first_k_dense_replace: (silu(n·W_gate) ⊙ n·W_up)·W_down
           at intermediate_size. Else: σ = sigmoid(n·W_r) over ALL
           published experts, float32; the num_experts_per_tok largest
           of σ + b (b the selection bias: it selects only); w_e = σ_e /
           Σ_sel σ · routed_scaling_factor; out = Shared(n) + Σ_e w_e ·
           E_e(n), every E_e and Shared a gated MLP of
           moe_intermediate_size (num_shared_experts of them, side by
           side), Shared added with weight 1.

THE SHARE (model-configs guide §4). `num_experts` is the number of
routed experts HELD here (ids 0 … num_experts-1 of `published.
num_experts`); the router keeps the published width and the published
experts per token; the sum runs over the held experts a token chose, and
what the absent ones would add is left out. `vocab_size` is the rows of
embedding and head held here; traffic, logits and argmax are over them.

ASSUMED (the config has no key; the configuration file lists the same
under `assumed`, each with its reason): no q_lora_rank (W_q is full);
`use_qk_norm` as the three norms above; sigmoid scoring with
norm_topk_prob; no group limits; the bias drawn from the seed, normal(0,
0.01); pre-norm residuals; silu.

The forward pass is straightforward `jax.numpy` float32 under
`precision="highest"`, no kernel, no cache, no batching tricks; it
imports nothing from the program. It is computed in blocks so that a
15 616-token sequence fits beside the seed's bf16 tree (9.07 GB): one
layer upcast at a time, queries a block, the dense MLP a slice of its
width and the experts one at a time. `quant` selects the CONTROL
(`harness.plain.mm`) for every matrix product, the router's included.

The weights' tree, matrices [in, out] (y = x @ W):

    embed [V, d]  head [V, d]  final_norm [d]
    layers[l]: attn_norm ffn_norm [d]  wq [d, H·q_head_dim]
               q_norm [q_head_dim]  wkv_a [d, latent + rope]
               kv_norm [latent]  kr_norm [rope]
               wkv_b [latent, H·(nope + v)]  wo [H·v, d]
      dense:   w_gate w_up [d, f]  w_down [f, d]
      sparse:  router [d, E_pub]  router_bias [E_pub] (float32)
               e_gate e_up [E, d, m]  e_down [E, m, d]
               s_gate s_up [d, ms]  s_down [ms, d]
"""
import functools
import math
import sys
import time

from harness.arith import ITEMSIZE, context_sum
from harness.plain import mm as _plain_mm, seed_key

CONTROLS = ("bf16", "router_bf16", "softmax_bf16")


def _mm(x, w, quant):
    """`harness.plain.mm`, with this reference's own controls beside its
    int8 / fp8 (builder's tools, `--control <name>`): "bf16" rounds both
    operands of every product to bfloat16 and accumulates in float32
    (the precision the configuration STATES: what a sound program's
    reading should look like); "router_bf16" is float32 everywhere but
    rounds the router's input to bfloat16 (`route`); "softmax_bf16" is
    float32 everywhere but rounds attention's scores to bfloat16 before
    the softmax (`attention`)."""
    import jax.numpy as jnp

    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return _plain_mm(x, w, None if quant in CONTROLS else quant)


NO_TRAINING = ("configuration sarvam-105b has no training cell: at 16 "
               "bytes a parameter its smallest allowed cut does not fit "
               "one chip")


# --------------------------------------------------------------- sizes

def dims(cfg):
    """The sizes every function here needs, as one dict."""
    m = int(cfg["moe_intermediate_size"])
    out = {
        "d": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
        "H": int(cfg["num_attention_heads"]),
        "latent": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "f": int(cfg["intermediate_size"]), "m": m,
        "ms": m * int(cfg["num_shared_experts"]),
        "held": int(cfg["num_experts"]),
        "routed": int(cfg["published"]["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "v": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }
    out["qd"] = out["nope"] + out["rope"]
    out["row"] = out["latent"] + out["rope"]
    assert out["qd"] == int(cfg.get("q_head_dim", out["qd"]))
    return out


def positions(cfg):
    """The longest sequence the reference takes: rotary positions have
    no table, so the config's own limit."""
    return int(cfg["max_position_embeddings"])


def is_sparse(s, l):
    return l >= s["dense"]


# ------------------------------------------------------------- weights

def layer_shapes(s, l):
    d, H = s["d"], s["H"]
    out = {"attn_norm": (d,), "ffn_norm": (d,), "wq": (d, H * s["qd"]),
           "q_norm": (s["qd"],), "wkv_a": (d, s["row"]),
           "kv_norm": (s["latent"],), "kr_norm": (s["rope"],),
           "wkv_b": (s["latent"], H * (s["nope"] + s["vd"])),
           "wo": (H * s["vd"], d)}
    if not is_sparse(s, l):
        out.update(w_gate=(d, s["f"]), w_up=(d, s["f"]),
                   w_down=(s["f"], d))
    else:
        E, m, ms = s["held"], s["m"], s["ms"]
        out.update(router=(d, s["routed"]), router_bias=(s["routed"],),
                   e_gate=(E, d, m), e_up=(E, d, m), e_down=(E, m, d),
                   s_gate=(d, ms), s_up=(d, ms), s_down=(ms, d))
    return out


def top_shapes(s):
    return {"embed": (s["v"], s["d"]), "head": (s["v"], s["d"]),
            "final_norm": (s["d"],)}


RESIDUAL = ("wo", "w_down", "e_down", "s_down")
BIAS_STD = 0.01


def tree_from_key(key, cfg_json, dtype):
    """The whole tree from one key: normal(0, 0.02), residual
    projections scaled by 1/sqrt(2L), norm weights 1 + normal(0, 0.02),
    the router's selection bias normal(0, 0.01) in float32. `cfg_json`
    is the configuration as a JSON string (hashable)."""
    import json

    import jax
    import jax.numpy as jnp

    s = dims(json.loads(cfg_json))
    names = sorted(top_shapes(s)) + [
        f"layers/{l}/{n}" for l in range(s["L"])
        for n in sorted(layer_shapes(s, l))]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def draw(name, shape):
        base = name.rsplit("/", 1)[-1]
        if base == "router_bias":
            return BIAS_STD * jax.random.normal(keys[name], shape,
                                                jnp.float32)
        std = 0.02 / math.sqrt(2 * s["L"]) if base in RESIDUAL else 0.02
        x = std * jax.random.normal(keys[name], shape, jnp.float32)
        if base.endswith("_norm"):
            x = 1.0 + x
        return x.astype(dtype)

    out = {n: draw(n, sh) for n, sh in top_shapes(s).items()}
    out["layers"] = [
        {n: draw(f"layers/{l}/{n}", sh)
         for n, sh in layer_shapes(s, l).items()} for l in range(s["L"])]
    return out


MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "num_shared_experts", "num_experts", "num_experts_per_tok",
    "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta",
    "rope_scaling", "max_position_embeddings")


def cfg_json(cfg):
    """The model's own keys as a canonical JSON string: the static
    argument of every jitted function here."""
    import json

    body = {k: cfg[k] for k in MODEL_KEYS}
    body["published"] = {"num_experts": cfg["published"]["num_experts"]}
    return json.dumps(body, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted_tree():
    import jax

    return jax.jit(tree_from_key, static_argnums=(1, 2))


def make_weights(cfg, seed, dtype):
    """The whole tree, made on the device from `seed` in one call."""
    return _jitted_tree()(seed_key(seed), cfg_json(cfg), str(dtype))


# -------------------------------------------------------------- rotary

def yarn_mscale(factor, mscale):
    if float(factor) <= 1 or not float(mscale):
        return 1.0
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def inv_frequencies(cfg):
    """([rope / 2] inverse frequencies, float64; the factor multiplying
    cos and sin) as the family's `deepseek_yarn` computes them."""
    import numpy as np

    rd = int(cfg["qk_rope_head_dim"])
    base = float(cfg["rope_theta"])
    pos_freqs = base ** (np.arange(0, rd, 2, dtype=np.float64) / rd)
    rs = cfg.get("rope_scaling")
    if not rs or float(rs.get("factor", 1)) <= 1:
        return 1.0 / pos_freqs, 1.0
    if rs.get("type") != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {rs.get('type')!r}")
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rd * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), rd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rd // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) \
        * (1.0 - ramp)
    return inv, (yarn_mscale(factor, rs.get("mscale", 1))
                 / yarn_mscale(factor, rs.get("mscale_all_dim", 0)))


def softmax_scale(cfg):
    s = dims(cfg)
    scale = 1.0 / math.sqrt(s["qd"])
    rs = cfg.get("rope_scaling")
    if rs and float(rs.get("factor", 1)) > 1 and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope_tables(cfg, positions_):
    """cos, sin [S, rope] (float32) of `positions_`."""
    import jax.numpy as jnp
    import numpy as np

    inv, factor = inv_frequencies(cfg)
    ang = positions_.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def apply_rope(x, cos, sin):
    """x [S, (H,) rope]: interleaved pairs re-ordered to halves, then
    rotate-half."""
    import jax.numpy as jnp

    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    return x * cos + rot * sin


# ------------------------------------------------------------- forward

def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(s, cfg, n, lw, quant, q_block=128):
    """Attn of the normed input n [S, d] (float32), the expanded form,
    queries a block at a time (the scores of 64 heads over 15 616
    positions would not fit at once)."""
    import jax
    import jax.numpy as jnp

    S = n.shape[0]
    H, nope, vd, eps = s["H"], s["nope"], s["vd"], s["eps"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    pos = jnp.arange(S)
    cos, sin = rope_tables(cfg, pos)
    scale = softmax_scale(cfg)
    q = _mm(n, f32(lw["wq"]), quant).reshape(S, H, s["qd"])
    q = rms_norm(q, f32(lw["q_norm"]), eps)
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    ckr = _mm(n, f32(lw["wkv_a"]), quant)
    c = rms_norm(ckr[:, :s["latent"]], f32(lw["kv_norm"]), eps)
    k_r = apply_rope(rms_norm(ckr[:, s["latent"]:], f32(lw["kr_norm"]),
                              eps), cos, sin)
    kv = _mm(c, f32(lw["wkv_b"]), quant).reshape(S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    if S % q_block:
        q_block = S               # a short sequence: one block

    def block(args):
        qn, qr, qi = args                     # [B, H, ·], [B, H, ·], [B]
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision="highest")
              + jnp.einsum("qhr,kr->hqk", qr, k_r, precision="highest")) \
            * scale
        if quant == "softmax_bf16":
            sc = sc.astype(jnp.bfloat16).astype(jnp.float32)
        sc = jnp.where(pos[None, :] <= qi[:, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision="highest")

    nb = S // q_block
    o = jax.lax.map(block, (
        q_nope.reshape(nb, q_block, H, nope),
        q_rope.reshape(nb, q_block, H, s["rope"]),
        pos.reshape(nb, q_block)))
    return _mm(o.reshape(S, H * vd), f32(lw["wo"]), quant)


def gated_mlp(x, wg, wu, wd, quant):
    import jax

    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd,
               quant)


def dense_mlp(s, n, lw, quant, width=4096):
    """The dense layer's gated MLP a slice of its width at a time (the
    [S, f] activations of 15 616 positions at f 16 384 would not fit in
    float32): the slices' outputs add up to the whole."""
    import jax
    import jax.numpy as jnp

    d, f = s["d"], s["f"]
    if f % width:
        width = f
    k = f // width
    wg = lw["w_gate"].reshape(d, k, width).transpose(1, 0, 2)
    wu = lw["w_up"].reshape(d, k, width).transpose(1, 0, 2)
    wd = lw["w_down"].reshape(k, width, d)

    def one(acc, w3):
        g, u, dn = (a.astype(jnp.float32) for a in w3)
        return acc + gated_mlp(n, g, u, dn, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), (wg, wu, wd))
    return out


def route(s, n, router, bias, quant):
    """(weights [S, E_pub] float32, zero off the chosen; chosen ids
    [S, top_k]): sigmoid scores over all published experts, the top_k
    largest of score + bias, the chosen SCORES renormalised."""
    import jax
    import jax.numpy as jnp

    if quant == "router_bf16":
        n = n.astype(jnp.bfloat16).astype(jnp.float32)
    r = _mm(n, router.astype(jnp.float32),
            None if quant in CONTROLS else quant)
    score = jax.nn.sigmoid(r)
    _, top_i = jax.lax.top_k(score + bias.astype(jnp.float32)[None, :],
                             s["top_k"])
    rows = jnp.arange(n.shape[0])[:, None]
    top_s = score[rows, top_i]
    w = top_s / jnp.sum(top_s, -1, keepdims=True)
    return jnp.zeros_like(score).at[rows, top_i].set(w), top_i


def sparse_ffn(s, n, lw, quant, held=None):
    """(Shared(n), scale · Σ over the HELD experts a token chose).
    `held` (first, count) narrows the held experts further (the share
    test)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    w, _ = route(s, n, lw["router"], lw["router_bias"], quant)
    first, count = held or (0, s["held"])

    def one(routed, e):
        """An expert at a time, every token through it, weighted by
        the router (0 for the tokens that did not choose it)."""
        wg, wu, wd, col = e
        return routed + col[:, None] * gated_mlp(
            n, f32(wg), f32(wu), f32(wd), quant), None

    sl = slice(first, first + count)
    routed, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        lw["e_gate"][sl], lw["e_up"][sl], lw["e_down"][sl],
        w[:, sl].T))
    shared = gated_mlp(n, f32(lw["s_gate"]), f32(lw["s_up"]),
                       f32(lw["s_down"]), quant)
    return shared, s["scale"] * routed


def layer_forward(cfg, l, x, lw, quant=None):
    """One decoder layer over x [S, d] float32."""
    import jax.numpy as jnp

    s = dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    h = x + attention(s, cfg, rms_norm(x, f32(lw["attn_norm"]), s["eps"]),
                      lw, quant)
    n = rms_norm(h, f32(lw["ffn_norm"]), s["eps"])
    if not is_sparse(s, l):
        return h + dense_mlp(s, n, lw, quant)
    shared, routed = sparse_ffn(s, n, lw, quant)
    return h + shared + routed


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_str, sparse, quant):
    """One jitted program a layer SHAPE: dense, or sparse."""
    import json

    import jax

    cfg = json.loads(cfg_str)
    l = dims(cfg)["dense"] if sparse else 0
    return jax.jit(lambda x, lw: layer_forward(cfg, l, x, lw, quant))


def hidden(cfg, w, ids, quant=None):
    """Final-RMSNorm hidden states [S, d] of token ids [S]."""
    import jax.numpy as jnp

    s = dims(cfg)
    key = cfg_json(cfg)
    x = w["embed"].astype(jnp.float32)[ids]
    for l in range(s["L"]):
        x = _layer_fn(key, is_sparse(s, l), quant)(x, w["layers"][l])
    return rms_norm(x, w["final_norm"].astype(jnp.float32), s["eps"])


def logits_fn(cfg, w, ids, quant=None):
    """[S, V] float32 logits of one sequence of token ids [S]."""
    import jax.numpy as jnp

    return _mm(hidden(cfg, w, ids, quant),
               w["head"].astype(jnp.float32).T, quant)


# ------------------------------------------------------------- serving

# a layer here compiles in tens of seconds a shape: ONE length for the
# cell's sequences (11 494 … 15 444 tokens), a multiple of the query
# block; sequences up to SHORT keep the caller's padding (the tests')
SEQ_BUCKETS = (15616,)
SHORT = 4096


@functools.lru_cache(maxsize=None)
def _gap_tail(quant):
    import jax
    import jax.numpy as jnp

    def fn(x, xq, head, rows, served):
        head = head.astype(jnp.float32).T
        lg = _mm(x[rows], head, None)
        top2 = jax.lax.top_k(lg, 2)[0]
        tok = served if quant is None else jnp.argmax(
            _mm(xq[rows], head, quant), -1)
        gap = top2[:, 0] - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
        return gap, top2[:, 0] - top2[:, 1]

    return jax.jit(fn)


def served_token_gaps(cfg, w, toks, plen, pad_to, rows_to, quant=None):
    """(gaps, reference margins) of the served tokens `toks[plen:]` of
    one sequence, one forward over the whole of it (right-padded:
    causal, so harmless). Shapes are padded to (`pad_to`, `rows_to`) so
    that every seed compiles the same few programs."""
    import numpy as np

    toks = np.asarray(toks, np.int32)
    n = len(toks) - plen
    if len(toks) > SHORT:
        pad_to = max(pad_to, next(b for b in SEQ_BUCKETS + (pad_to,)
                                  if b >= len(toks)))
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(toks)] = toks
    rows = np.full((rows_to,), plen - 1, np.int32)
    rows[:n] = np.arange(plen - 1, len(toks) - 1)
    served = np.full((rows_to,), toks[plen], np.int32)
    served[:n] = toks[plen:]
    t0 = time.perf_counter()
    x = hidden(cfg, w, ids)
    xq = x if quant is None else hidden(cfg, w, ids, quant)
    gap, margin = _gap_tail(quant)(x, xq, w["head"], rows, served)
    gap, margin = np.asarray(gap)[:n], np.asarray(margin)[:n]
    print(f"[reference sarvam_mla] {len(toks)} positions as {pad_to}, {n} "
          f"served rows, quant {quant}: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return gap, margin


# ---------------------------------------------------------- arithmetic

def attn_matrix_params(s):
    """W_q, W_kv_a, W_kv_b, W_o."""
    d, H = s["d"], s["H"]
    return (d * H * s["qd"] + d * s["row"]
            + s["latent"] * H * (s["nope"] + s["vd"]) + H * s["vd"] * d)


def norm_params(s):
    """A layer's five norms: input, post-attention, query, latent, rope
    key."""
    return 2 * s["d"] + s["qd"] + s["latent"] + s["rope"]


def expert_params(s):
    """One routed expert."""
    return 3 * s["d"] * s["m"]


def ffn_params(s, l, experts=None):
    """Feed-forward parameters of layer l with `experts` routed experts
    counted (default: the held ones); a sparse layer's router has its
    selection bias."""
    d = s["d"]
    if not is_sparse(s, l):
        return 3 * d * s["f"]
    e = s["held"] if experts is None else experts
    return (d * s["routed"] + s["routed"] + e * expert_params(s)
            + 3 * d * s["ms"])


def param_count(cfg):
    """Every parameter held here: embedding and untied head (the rows
    held), every layer's attention, norms, router with its bias, held
    experts and shared expert, the final norm."""
    s = dims(cfg)
    return 2 * s["v"] * s["d"] + s["d"] + sum(
        attn_matrix_params(s) + norm_params(s) + ffn_params(s, l)
        for l in range(s["L"]))


def sparse_layers(s):
    return sum(is_sparse(s, l) for l in range(s["L"]))


def moe_counts(work):
    """(assignments to held experts, experts touched) in `work`, from
    the program's counters (summed over sparse layers and iterations);
    None where the work carries none."""
    st = work.get("stats") or {}
    if "moe_assignments_held" not in st:
        return None
    return int(st["moe_assignments_held"]), int(st["moe_experts_touched"])


def rows_attended_by_row(work):
    """Σ over the work's tokens of the positions each attends (every
    earlier one and its own), one layer: this reference's OWN count from
    the driver's `segments`."""
    return sum(context_sum(int(start), int(n))
               for start, n in work["segments"])


def mla_counts(cfg, work):
    """(latent rows the work's steps had to read at least, those of
    them read by slot-steps of ONE query row), a row once a layer a
    slot a step, both summed over layers: the program's counters
    `mla_rows_attended_least` / `mla_rows_attended_single` (the driver's
    segments do not say which rows shared a step). Without them: every
    row a step of its own."""
    st = work.get("stats") or {}
    if "mla_rows_attended_least" in st:
        return (int(st["mla_rows_attended_least"]),
                int(st.get("mla_rows_attended_single", 0)))
    by_row = dims(cfg)["L"] * rows_attended_by_row(work)
    return by_row, by_row


def absorbed_flops_per_row_attended(s):
    """Scores against the row (latent + rope) and the context over the
    latent, all heads: 2·H·(2·latent + rope)."""
    return 2 * s["H"] * (2 * s["latent"] + s["rope"])


def expanded_flops_per_row_attended(s):
    """Scores (nope + rope) and the context (v), all heads."""
    return 2 * s["H"] * (s["nope"] + s["rope"] + s["vd"])


def expand_flops_per_cached_row(s):
    """k_nope and v of one cached row: 2 FLOPs a parameter of W_kv_b."""
    return 2 * s["latent"] * s["H"] * (s["nope"] + s["vd"])


def mla_attn_flops(cfg, work):
    """Attention's own FLOPs (scores, context, and in the expanded form
    the up-projection of the cached rows), the CHEAPER of the two forms,
    whichever ran. A slot-step of one query row (a decoding row) is
    cheaper absorbed. The slot-steps of several rows (prefill chunks)
    are taken together: absorbed a·R, or expanded e·R + x·C with R their
    rows attended (by row, from the driver's segments less the single
    rows') and C the cached rows they read once a layer."""
    s = dims(cfg)
    a = absorbed_flops_per_row_attended(s)
    e = expanded_flops_per_row_attended(s)
    x = expand_flops_per_cached_row(s)
    least, single = mla_counts(cfg, work)
    rows = max(s["L"] * rows_attended_by_row(work), least)
    chunk_rows, chunk_cached = max(rows - single, 0), max(least - single, 0)
    return a * single + min(a * chunk_rows,
                            e * chunk_rows + x * chunk_cached)


def serve_flops(cfg, work):
    """Forward only, of what THIS chip computes. Per processed token 2
    FLOPs a parameter of attention's matrices (W_kv_b among them: the
    absorbed form applies its two halves to every query row), the dense
    or shared feed-forward, the router and the head's rows held here
    (the head runs on the sampled rows only; it is counted for every
    token, as the accepted references count it). Per assignment to a
    held expert 2 · 3·d·m, from the program's counter (without one, the
    expected share top_k · held / routed a token a sparse layer). And
    attention's own `mla_attn_flops`."""
    s = dims(cfg)
    tokens = int(work["processed"])
    per_token = s["v"] * s["d"] + sum(
        attn_matrix_params(s) + ffn_params(s, l, experts=0)
        for l in range(s["L"]))
    counts = moe_counts(work)
    assignments = counts[0] if counts else \
        tokens * sparse_layers(s) * s["top_k"] * s["held"] / s["routed"]
    return 2 * per_token * tokens + 2 * expert_params(s) * assignments \
        + mla_attn_flops(cfg, work)


def kv_bytes_per_token(cfg, kv_dtype):
    """The latent row `[c | k_r]` a token keeps in every layer."""
    s = dims(cfg)
    return s["L"] * s["row"] * ITEMSIZE[kv_dtype]


def weight_bytes(cfg, dtype, work=None):
    """Bytes of the whole tree; given `work`, the least its iterations
    must read of it: everything but the routed experts once an
    iteration, and of the routed experts those the program's counter
    says were touched (`moe_experts_touched`, summed over sparse layers
    and iterations). Without the counter: every held expert, every
    iteration."""
    s = dims(cfg)
    b = ITEMSIZE[dtype]
    held = param_count(cfg) * b
    if work is None:
        return held
    experts = sparse_layers(s) * s["held"] * expert_params(s) * b
    its = int(work["iterations"])
    counts = moe_counts(work)
    touched = counts[1] * expert_params(s) * b if counts \
        else its * experts
    return its * (held - experts) + touched


def moe_expert_bytes(cfg, dtype, work):
    """Least bytes the routed experts' grouped products must move: the
    weights of the experts touched, and a row of d in and a row of d
    out an assignment to a held expert. None without the counters."""
    s = dims(cfg)
    counts = moe_counts(work)
    if counts is None:
        return None
    b = ITEMSIZE[dtype]
    return counts[1] * expert_params(s) * b + counts[0] * 2 * s["d"] * b


def kv_bytes_attended_by_row(cfg, work, kv_dtype):
    """Latent bytes when every token of the driver's `segments` reads
    its own context, a row once for the 64 heads: this reference's OWN
    count, and no lower bound: a walk that reads a prefill chunk's
    context once for several rows reads less."""
    s = dims(cfg)
    return s["row"] * ITEMSIZE[kv_dtype] * s["L"] \
        * rows_attended_by_row(work)


def kv_bytes_attended(cfg, work, kv_dtype):
    """Least bytes attention must read: the latent row of every attended
    position, once for the 64 heads and once for the rows of a slot that
    a step reads together (`mla_counts`: the program's counter; without
    it, by row)."""
    s = dims(cfg)
    return s["row"] * ITEMSIZE[kv_dtype] * mla_counts(cfg, work)[0]


def train_step_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)


def flash_attn_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)
