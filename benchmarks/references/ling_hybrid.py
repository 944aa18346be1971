"""Reference `ling_hybrid`: the language model of inclusionAI/
Ling-3.0-flash-VL behind the contract of `references/__init__.py`: its
weights from the seed, its plain forward pass (serving gaps) and its
arithmetic. Key names are the published config's own (hidden_size,
layer_group_size, kv_lora_rank, n_group, ...): nothing outside this file
and its builder reads them. The vision tower and the multi-token head
that the model's description names are NOT here: the catalog's config
holds the language model alone, and this file serves text ids.

THE EQUATIONS. For layer l over x [T, d]:

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

and after the last layer a final RMSNorm and logits = y · W_headᵀ
(untied), no biases. RMSNorm(x) = x / sqrt(mean(x²) + eps) · w, eps =
rms_norm_eps. Layer l is MLA where (l + 1) % layer_group_size == 0 and
KDA (Kimi Delta Attention, arXiv:2510.26692) otherwise. With n the
normed input, H = num_attention_heads, d_k = d_v = head_dim:

  KDA      q, k, v = SiLU(conv(n·W_q)), SiLU(conv(n·W_k)), SiLU(conv(n·W_v)):
             a causal depthwise convolution of short_conv_kernel_size
             taps, a channel each, no bias: y_t = Σ_i c[i] · x_{t-K+1+i}
             (c[K-1] weighs the token's own row; rows before the
             sequence are zeros). q ← L2norm_head(q) · d_k^-½, k ←
             L2norm_head(k) (x · rsqrt(Σx² + 1e-6)); no rotary.
           decay, a key CHANNEL each: a = n·W_a [H·d_k] (full rank:
             no_kda_lora), g = kda_lower_bound · sigmoid(exp(A_log_h) ·
             (a + dt_bias)) (kda_safe_gate), α = exp(g) ∈ (e^-5, 1);
             β = sigmoid(n·W_β) [H].
           state a head, FLOAT32, S_0 = 0 [d_k, d_v]:
             S_t = (I − β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ
             o_t = S_tᵀ q_t
           o ← RMSNorm_{d_v}(o) a head; o_h ← sigmoid(n·W_g)_h · o_h (a
             gate a head, W_g [d, H]); y = concat(o)·W_o.
           Computed here AS WRITTEN: a `lax.scan` over tokens, no
           chunking, no cache, so it is independent of both forms the
           program has (recurrent, chunked).
  MLA      q = n·W_q → [H, nope + rope], an RMSNorm of nope + rope over
             each head's query (use_qk_norm), the rope dims rotated;
             [c_kv (kv_lora_rank) | k_r (rope)] = n·W_kv_a; c =
             RMSNorm(c_kv); k_r = RoPE(RMSNorm(k_r)), ONE key for all
             heads; [k_nope_h | v_h] = c·W_kv_b[h]; s_h = (q_nope_h·
             k_nope_h + q_rope_h·k_r) · (nope + rope)^-½, causal softmax
             in float32, o_h = Σ p v_h; o_h ← sigmoid(n·W_g)_h · o_h; y =
             concat(o_h)·W_o. RoPE: interleaved pairs re-ordered to
             halves, then rotate-half, plain frequencies rope_theta^(-2i
             / rope), no scaling. The EXPANDED form, the only one here.
  FFN_l    l < first_k_dense_replace: (silu(n·W_gate) ⊙ n·W_up)·W_down at
           intermediate_size. Else: s = sigmoid(n·W_r) over ALL published
           experts, float32; selection on s + b (b the selection bias):
           n_group groups of consecutive experts, a group's score the
           sum of its 2 largest s + b, the topk_group best groups kept,
           the num_experts_per_tok best experts inside them; w_e = s_e /
           Σ_sel s · routed_scaling_factor; out = Shared(n) + Σ_e w_e ·
           E_e(n), every E_e a gated MLP of moe_intermediate_size,
           Shared one of moe_shared_expert_intermediate_size, weight 1.
           No clamp: expert_swiglu_limit_list and share_expert_swiglu_
           limit_list are 0 in every layer held here, and this file
           refuses a configuration where they are not.

THE SHARE (model-configs guide §4). `num_experts` is the number of
routed experts HELD here (ids 0 … num_experts-1 of `published.
num_experts`: whole groups); the router keeps the published width, its
groups and the published experts per token; the sum runs over the held
experts a token chose. `vocab_size` is the rows of embedding and head
held here; traffic, logits and argmax are over them.

ASSUMED (the configuration file lists the same under `assumed`, each
with its reason): the safe gate's form; L2 norms' epsilon; the output
gate's granularity (a head) and that both attention kinds have it; the
MLA layer's three norms; the group score (top-2 sum); pre-norm
residuals; the seed's draws (below).

The forward pass is straightforward `jax.numpy` float32 under
`precision="highest"`; it imports nothing from the program. `quant`
selects a CONTROL: `harness.plain.mm`'s int8 / fp8 for every matrix
product, or this file's own: "bf16" (both operands of every product
rounded to bfloat16: the stated precision) and "kda_state_bf16" (float32
everywhere, but the KDA state rounded to bfloat16 after every token).

The weights' tree, matrices [in, out] (y = x @ W):

    embed [V, d]  head [V, d]  final_norm [d]
    layers[l]: attn_norm ffn_norm [d]  w_g [d, H]  wo [H·d_v, d]
      KDA:     wq wk wv w_a [d, H·d_k]  conv_q conv_k conv_v [K, H·d_k]
               a_log [H] dt_bias [H·d_k] (float32)  w_beta [d, H]
               o_norm [d_v]
      MLA:     wq [d, H·(nope + rope)]  q_norm [nope + rope]
               wkv_a [d, latent + rope]  kv_norm [latent]  kr_norm [rope]
               wkv_b [latent, H·(nope + v)]
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

CONTROLS = ("bf16", "kda_state_bf16")

# the chunk the reference's count of the chunked form is stated at,
# whatever the program's
CHUNK = 64


def _mm(x, w, quant):
    import jax.numpy as jnp

    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return _plain_mm(x, w, None if quant in CONTROLS else quant)


NO_TRAINING = ("configuration ling-3.0-flash-vl has no training cell: the "
               "chunked scan has no backward pass in this repository")


# --------------------------------------------------------------- sizes

def dims(cfg):
    """The sizes every function here needs, as one dict."""
    out = {
        "d": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
        "H": int(cfg["num_attention_heads"]), "dk": int(cfg["head_dim"]),
        "K": int(cfg["short_conv_kernel_size"]),
        "lower": float(cfg["kda_lower_bound"]),
        "period": int(cfg["layer_group_size"]),
        "latent": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]),
        "vd": int(cfg["v_head_dim"]),
        "dense": int(cfg["first_k_dense_replace"]),
        "f": int(cfg["intermediate_size"]),
        "m": int(cfg["moe_intermediate_size"]),
        "ms": int(cfg["moe_shared_expert_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "routed": int(cfg["published"]["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "groups": int(cfg["n_group"]), "top_groups": int(cfg["topk_group"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "v": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }
    out["qd"] = out["nope"] + out["rope"]
    out["row"] = out["latent"] + out["rope"]
    out["hk"] = out["H"] * out["dk"]
    if int(cfg.get("num_kv_heads_for_linear_attn", 0)) not in (0, out["H"]):
        raise ValueError("KDA layers with fewer K/V heads are not written")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg.get(key, ())):
            raise ValueError(f"{key} is non-zero in a held layer: the "
                             "clamp's form is not in the config")
    return out


def positions(cfg):
    """The longest sequence the reference takes: neither attention kind
    has a table, so the config's own limit."""
    return int(cfg["max_position_embeddings"])


def is_sparse(s, l):
    return l >= s["dense"]


def is_mla(s, l):
    return (l + 1) % s["period"] == 0


def kda_layers(s):
    return sum(not is_mla(s, l) for l in range(s["L"]))


def mla_layers(s):
    return s["L"] - kda_layers(s)


# ------------------------------------------------------------- weights

def layer_shapes(s, l):
    d, H, hk = s["d"], s["H"], s["hk"]
    out = {"attn_norm": (d,), "ffn_norm": (d,), "w_g": (d, H),
           "wo": (H * s["vd"], d)}
    if is_mla(s, l):
        out.update(wq=(d, H * s["qd"]), q_norm=(s["qd"],),
                   wkv_a=(d, s["row"]), kv_norm=(s["latent"],),
                   kr_norm=(s["rope"],),
                   wkv_b=(s["latent"], H * (s["nope"] + s["vd"])))
    else:
        out.update(wq=(d, hk), wk=(d, hk), wv=(d, hk), w_a=(d, hk),
                   conv_q=(s["K"], hk), conv_k=(s["K"], hk),
                   conv_v=(s["K"], hk), a_log=(H,), dt_bias=(hk,),
                   w_beta=(d, H), o_norm=(s["dk"],))
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
FLOAT32 = ("router_bias", "a_log", "dt_bias")
BIAS_STD = 0.01
CONV_STD = 0.5
A_LOG_SPAN = math.log(2.0)      # exp(A_log) in (1/2, 2)
DT_BIAS_SPAN = 3.0              # dt_bias in (-3, 3)


def tree_from_key(key, cfg_json, dtype):
    """The whole tree from one key: normal(0, 0.02), residual
    projections scaled by 1/sqrt(2L), norm weights 1 + normal(0, 0.02).
    What a trained model has and a 0.02 draw would not, float32:
      router_bias  normal(0, 0.01): it changes selections between
                   near-equal experts and groups
      conv_*       normal(0, 0.5) a tap: a row's q, k, v mix its three
                   predecessors at the order of its own weight
      a_log        uniform(-ln 2, ln 2) a head, dt_bias uniform(-3, 3) a
                   channel: with n·W_a of unit scale the gate's argument
                   spans about ±8, so the decays α spread over the whole
                   of (e^-5, 1): channels that forget in a token beside
                   channels that keep thousands
    `cfg_json` is the configuration as a JSON string (hashable)."""
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
        k = keys[name]
        if base == "router_bias":
            return BIAS_STD * jax.random.normal(k, shape, jnp.float32)
        if base == "a_log":
            return jax.random.uniform(k, shape, jnp.float32,
                                      -A_LOG_SPAN, A_LOG_SPAN)
        if base == "dt_bias":
            return jax.random.uniform(k, shape, jnp.float32,
                                      -DT_BIAS_SPAN, DT_BIAS_SPAN)
        std = 0.02 / math.sqrt(2 * s["L"]) if base in RESIDUAL else 0.02
        if base.startswith("conv_"):
            std = CONV_STD
        x = std * jax.random.normal(k, shape, jnp.float32)
        if base.endswith("_norm"):
            x = 1.0 + x
        return x.astype(dtype)

    out = {n: draw(n, sh) for n, sh in top_shapes(s).items()}
    out["layers"] = [
        {n: draw(f"layers/{l}/{n}", sh)
         for n, sh in layer_shapes(s, l).items()} for l in range(s["L"])]
    return out


MODEL_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim",
    "short_conv_kernel_size", "kda_lower_bound", "layer_group_size",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "num_experts",
    "num_experts_per_tok", "n_group", "topk_group",
    "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta",
    "max_position_embeddings")


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


# ------------------------------------------------------------- forward

def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def l2_norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def short_conv(x, taps):
    """Causal depthwise convolution: x [S, C], taps [K, C]; the last tap
    weighs a row's own input, rows before the sequence are zeros."""
    import jax.numpy as jnp

    K, S = taps.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[i][None, :] * xp[i:i + S] for i in range(K))


def kda_gates(s, n, lw, quant):
    """(g [S, H, d_k] the log decay, β [S, H]) float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    S, H, dk = n.shape[0], s["H"], s["dk"]
    a = _mm(n, f32(lw["w_a"]), quant) + f32(lw["dt_bias"])[None, :]
    g = s["lower"] * jax.nn.sigmoid(
        jnp.exp(f32(lw["a_log"]))[None, :, None] * a.reshape(S, H, dk))
    return g, jax.nn.sigmoid(_mm(n, f32(lw["w_beta"]), quant))


def delta_rule_scan(q, k, v, g, beta, state, round_state=False):
    """The recurrence as written, a token at a time: q k g [S, H, d_k],
    v [S, H, d_v], β [S, H], state [H, d_k, d_v] → (o [S, H, d_v], the
    state after the last token)."""
    import jax
    import jax.numpy as jnp

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", S, k_t, precision="highest"))
        S = S + k_t[:, :, None] * u[:, None, :]
        if round_state:
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision="highest")

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda_attention(s, n, lw, quant):
    """Attn of the normed input n [S, d] (float32), a KDA layer."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    S, H, dk = n.shape[0], s["H"], s["dk"]

    def branch(w, taps):
        return jax.nn.silu(short_conv(_mm(n, f32(lw[w]), quant),
                                      f32(lw[taps]))).reshape(S, H, dk)

    q = l2_norm(branch("wq", "conv_q")) * dk ** -0.5
    k = l2_norm(branch("wk", "conv_k"))
    v = branch("wv", "conv_v")
    g, beta = kda_gates(s, n, lw, quant)
    o, _ = delta_rule_scan(q, k, v, g, beta,
                           jnp.zeros((H, dk, dk), jnp.float32),
                           round_state=quant == "kda_state_bf16")
    return rms_norm(o, f32(lw["o_norm"]), s["eps"])


def rope_tables(cfg, positions_):
    """cos, sin [S, rope] (float32) of `positions_`: plain rotary."""
    import jax.numpy as jnp
    import numpy as np

    rd = int(cfg["qk_rope_head_dim"])
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rd, 2, dtype=np.float64) / rd)
    ang = positions_.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


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


def mla_attention(s, cfg, n, lw, quant, q_block=128):
    """Attn of the normed input n [S, d] (float32), the MLA layer in the
    expanded form, queries a block at a time."""
    import jax
    import jax.numpy as jnp

    S = n.shape[0]
    H, nope, vd, eps = s["H"], s["nope"], s["vd"], s["eps"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    pos = jnp.arange(S)
    cos, sin = rope_tables(cfg, pos)
    scale = 1.0 / math.sqrt(s["qd"])
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
        qn, qr, qi = args
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision="highest")
              + jnp.einsum("qhr,kr->hqk", qr, k_r, precision="highest")) \
            * scale
        sc = jnp.where(pos[None, :] <= qi[:, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                          precision="highest")

    nb = S // q_block
    return jax.lax.map(block, (
        q_nope.reshape(nb, q_block, H, nope),
        q_rope.reshape(nb, q_block, H, s["rope"]),
        pos.reshape(nb, q_block)))


def attention(s, cfg, l, n, lw, quant):
    """Attn_l of the normed input: the layer's kind, then the gate a
    head and W_o, which both kinds share."""
    import jax
    import jax.numpy as jnp

    S, H = n.shape[0], s["H"]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    o = (mla_attention(s, cfg, n, lw, quant) if is_mla(s, l)
         else kda_attention(s, n, lw, quant)).reshape(S, H, -1)
    gate = jax.nn.sigmoid(_mm(n, f32(lw["w_g"]), quant))
    return _mm((o * gate[:, :, None]).reshape(S, -1), f32(lw["wo"]), quant)


def gated_mlp(x, wg, wu, wd, quant):
    import jax

    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd,
               quant)


def route(s, n, router, bias, quant):
    """(weights [S, E_pub] float32, zero off the chosen; chosen ids
    [S, top_k]): sigmoid scores over all published experts; of score +
    bias, a group's two largest summed, the best groups kept, the top_k
    largest inside them; the chosen SCORES renormalised."""
    import jax
    import jax.numpy as jnp

    r = _mm(n, router.astype(jnp.float32),
            None if quant in CONTROLS else quant)
    score = jax.nn.sigmoid(r)
    sel = score + bias.astype(jnp.float32)[None, :]
    S, E = sel.shape
    G = s["groups"]
    group_score = jnp.sum(jax.lax.top_k(sel.reshape(S, G, E // G), 2)[0],
                          axis=-1)
    _, best = jax.lax.top_k(group_score, s["top_groups"])
    keep = jnp.zeros((S, G), bool).at[jnp.arange(S)[:, None], best].set(
        True)
    sel = jnp.where(jnp.repeat(keep, E // G, axis=1), sel, -jnp.inf)
    _, top_i = jax.lax.top_k(sel, s["top_k"])
    rows = jnp.arange(S)[:, None]
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
    h = x + attention(s, cfg, l, rms_norm(x, f32(lw["attn_norm"]),
                                          s["eps"]), lw, quant)
    n = rms_norm(h, f32(lw["ffn_norm"]), s["eps"])
    if not is_sparse(s, l):
        return h + gated_mlp(n, f32(lw["w_gate"]), f32(lw["w_up"]),
                             f32(lw["w_down"]), quant)
    shared, routed = sparse_ffn(s, n, lw, quant)
    return h + shared + routed


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_str, mla, sparse, quant):
    """One jitted program a layer SHAPE: (attention kind, FFN kind)."""
    import json

    import jax

    cfg = json.loads(cfg_str)
    s = dims(cfg)
    l = next(i for i in range(s["L"])
             if is_mla(s, i) == mla and is_sparse(s, i) == sparse)
    return jax.jit(lambda x, lw: layer_forward(cfg, l, x, lw, quant))


def hidden(cfg, w, ids, quant=None):
    """Final-RMSNorm hidden states [S, d] of token ids [S]."""
    import jax.numpy as jnp

    s = dims(cfg)
    key = cfg_json(cfg)
    x = w["embed"].astype(jnp.float32)[ids]
    for l in range(s["L"]):
        x = _layer_fn(key, is_mla(s, l), is_sparse(s, l), quant)(
            x, w["layers"][l])
    return rms_norm(x, w["final_norm"].astype(jnp.float32), s["eps"])


def logits_fn(cfg, w, ids, quant=None):
    """[S, V] float32 logits of one sequence of token ids [S]."""
    import jax.numpy as jnp

    return _mm(hidden(cfg, w, ids, quant),
               w["head"].astype(jnp.float32).T, quant)


# ------------------------------------------------------------- serving

# a layer here compiles in seconds a shape: ONE length for the cell's
# sequences (2 724 … 3 489 tokens), a multiple of the query block;
# sequences up to SHORT keep the caller's padding (the tests')
SEQ_BUCKET = 3584
SHORT = 1024


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
    if SHORT < len(toks) <= SEQ_BUCKET:
        pad_to = SEQ_BUCKET
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
    print(f"[reference ling_hybrid] {len(toks)} positions as {pad_to}, {n} "
          f"served rows, quant {quant}: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return gap, margin


# ---------------------------------------------------------- arithmetic

def attn_matrix_params(s, l):
    """The layer's attention matrices (what a token multiplies): W_g and
    W_o, and KDA's four projections and W_β, or MLA's W_q, W_kv_a,
    W_kv_b."""
    d, H = s["d"], s["H"]
    shared = d * H + H * s["vd"] * d
    if is_mla(s, l):
        return shared + d * H * s["qd"] + d * s["row"] \
            + s["latent"] * H * (s["nope"] + s["vd"])
    return shared + 4 * d * s["hk"] + d * H


def attn_small_params(s, l):
    """Norm weights, and KDA's convolution taps, A_log and dt_bias."""
    if is_mla(s, l):
        return 2 * s["d"] + s["qd"] + s["latent"] + s["rope"]
    return 2 * s["d"] + 3 * s["K"] * s["hk"] + s["H"] + s["hk"] + s["dk"]


def expert_params(s):
    """One routed expert."""
    return 3 * s["d"] * s["m"]


def ffn_params(s, l, experts=None):
    d = s["d"]
    if not is_sparse(s, l):
        return 3 * d * s["f"]
    e = s["held"] if experts is None else experts
    return (d * s["routed"] + s["routed"] + e * expert_params(s)
            + 3 * d * s["ms"])


def param_count(cfg):
    """Every parameter held here."""
    s = dims(cfg)
    return 2 * s["v"] * s["d"] + s["d"] + sum(
        attn_matrix_params(s, l) + attn_small_params(s, l)
        + ffn_params(s, l) for l in range(s["L"]))


def sparse_layers(s):
    return sum(is_sparse(s, l) for l in range(s["L"]))


def moe_counts(work):
    """(assignments to held experts, experts touched) in `work`, from
    the program's counters; None where the work carries none."""
    st = work.get("stats") or {}
    if "moe_assignments_held" not in st:
        return None
    return int(st["moe_assignments_held"]), int(st["moe_experts_touched"])


def rows_attended_by_row(work):
    """Σ over the work's tokens of the positions each attends, one
    layer: this reference's OWN count from the driver's `segments`."""
    return sum(context_sum(int(start), int(n))
               for start, n in work["segments"])


def mla_counts(cfg, work):
    """(latent rows the work's steps had to read at least, those of
    them read by slot-steps of ONE query row), summed over the MLA
    layers: the program's counters; without them, every row a step of
    its own."""
    st = work.get("stats") or {}
    if "mla_rows_attended_least" in st:
        return (int(st["mla_rows_attended_least"]),
                int(st.get("mla_rows_attended_single", 0)))
    by_row = mla_layers(dims(cfg)) * rows_attended_by_row(work)
    return by_row, by_row


def mla_attn_flops(cfg, work):
    """The MLA layers' own FLOPs (scores, context, and in the expanded
    form the up-projection of the cached rows), the CHEAPER of the two
    forms, whichever ran (as `references/sarvam_mla.py` counts them)."""
    s = dims(cfg)
    a = 2 * s["H"] * (2 * s["latent"] + s["rope"])
    e = 2 * s["H"] * (s["nope"] + s["rope"] + s["vd"])
    x = 2 * s["latent"] * s["H"] * (s["nope"] + s["vd"])
    least, single = mla_counts(cfg, work)
    rows = max(mla_layers(s) * rows_attended_by_row(work), least)
    chunk_rows, chunk_cached = max(rows - single, 0), max(least - single, 0)
    return a * single + min(a * chunk_rows,
                            e * chunk_rows + x * chunk_cached)


def kda_rows(cfg, work):
    """(query rows × KDA layers that went through the recurrent form,
    through the chunked form): the program's counters; without them,
    every row recurrent."""
    st = work.get("stats") or {}
    if "kda_rows_recurrent" in st:
        return int(st["kda_rows_recurrent"]), int(st["kda_rows_chunked"])
    return kda_layers(dims(cfg)) * int(work["processed"]), 0


def state_bytes(s):
    """One layer's float32 state of one sequence."""
    return s["H"] * s["dk"] * s["dk"] * 4


def kda_state_bytes(cfg, work):
    """Least bytes the RECURRENT form must move: a row reads its
    sequence's state once and writes it once, a layer."""
    return 2 * state_bytes(dims(cfg)) * kda_rows(cfg, work)[0]


def kda_recurrent_flops_per_row(s):
    """Sᵀk, the rank-one update and Sᵀq, all heads: 2 FLOPs an element
    of the state each, and the decay one."""
    return 7 * s["H"] * s["dk"] * s["dk"]


def kda_chunk_flops(cfg, work, chunk=CHUNK):
    """FLOPs of the chunked form (WY / UT transform) at a stated chunk
    of C rows, for the rows the counter says went chunked, all heads, a
    row: the two C-wide products against the keys (k·k, q·k: 2·2·C·d_k,
    halved: causal), the triangular solve applied to [k | v] (C·(d_k +
    d_v), halved) , the products with the state (w·S, q·S: 2·2·d_k·d_v),
    the intra-chunk output (2·C·d_v, halved) and the state's update
    (2·d_k·d_v)."""
    s = dims(cfg)
    dk, C = s["dk"], int(chunk)
    per_row = s["H"] * (2 * C * dk + C * 2 * dk + 4 * dk * dk + C * dk
                        + 2 * dk * dk)
    return per_row * kda_rows(cfg, work)[1]


def kda_chunk_bytes(cfg, work):
    """Least bytes the chunked form must move: q, k, v and the decay of
    every row in and o out at the compute dtype's two bytes (5 rows of
    H·d_k), and a run's state in and out once, counted through
    `kda_chunk_launches` (runs × layers); without it, none."""
    s = dims(cfg)
    st = work.get("stats") or {}
    runs = int(st.get("kda_chunk_launches", 0))
    return 5 * s["hk"] * 2 * kda_rows(cfg, work)[1] \
        + 2 * state_bytes(s) * runs


def serve_flops(cfg, work):
    """Forward only, of what THIS chip computes. Per processed token 2
    FLOPs a parameter of each layer's attention matrices, the dense or
    shared feed-forward, the router and the head's rows held here. Per
    assignment to a held expert 2 · 3·d·m, from the program's counter.
    The MLA layers' own `mla_attn_flops`; a KDA layer's recurrence by
    the form the counter says a row took."""
    s = dims(cfg)
    tokens = int(work["processed"])
    per_token = s["v"] * s["d"] + sum(
        attn_matrix_params(s, l) + ffn_params(s, l, experts=0)
        for l in range(s["L"]))
    counts = moe_counts(work)
    assignments = counts[0] if counts else \
        tokens * sparse_layers(s) * s["top_k"] * s["held"] / s["routed"]
    return 2 * per_token * tokens + 2 * expert_params(s) * assignments \
        + mla_attn_flops(cfg, work) \
        + kda_recurrent_flops_per_row(s) * kda_rows(cfg, work)[0] \
        + kda_chunk_flops(cfg, work)


def kv_bytes_per_token(cfg, kv_dtype):
    """The latent row `[c | k_r]` a token keeps in every MLA layer (a
    KDA layer keeps nothing a token)."""
    s = dims(cfg)
    return mla_layers(s) * s["row"] * ITEMSIZE[kv_dtype]


def weight_bytes(cfg, dtype, work=None):
    """Bytes of the whole tree; given `work`, the least its iterations
    must read of it: everything but the routed experts once an
    iteration, and of the routed experts those the program's counter
    says were touched."""
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
    """Least bytes the routed experts' grouped products must move."""
    s = dims(cfg)
    counts = moe_counts(work)
    if counts is None:
        return None
    b = ITEMSIZE[dtype]
    return counts[1] * expert_params(s) * b + counts[0] * 2 * s["d"] * b


def kv_bytes_attended_by_row(cfg, work, kv_dtype):
    """Latent bytes when every token of the driver's `segments` reads
    its own context in every MLA layer."""
    s = dims(cfg)
    return s["row"] * ITEMSIZE[kv_dtype] * mla_layers(s) \
        * rows_attended_by_row(work)


def kv_bytes_attended(cfg, work, kv_dtype):
    """Least bytes the MLA layers' walk must read: the LATENT bytes
    only. The KDA layers' state is `kda_state_bytes`: the readers of the
    latent walk divide this by the walk's time."""
    s = dims(cfg)
    return s["row"] * ITEMSIZE[kv_dtype] * mla_counts(cfg, work)[0]


def train_step_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)


def flash_attn_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)
