"""Reference `olmo_hybrid`: allenai/Olmo-Hybrid-7B behind the contract of
`references/__init__.py`: its weights from the seed, its plain forward
pass (serving gaps) and its arithmetic. Key names are the published
config's own (hidden_size, layer_types, linear_key_head_dim, ...):
nothing outside this file and its builder reads them.

THE EQUATIONS. Norms on each sublayer's OUTPUT (the OLMo 2 family's
placement): for layer l over the residual stream x [T, d]

    h = x + RMSNorm(Mix_l(x));   y = h + RMSNorm(MLP(h))

MLP(x) = (silu(x·W_gate) ⊙ x·W_up)·W_down at intermediate_size; after
the last layer a final RMSNorm and logits = y · W_headᵀ (untied), no
biases. RMSNorm(x) = x / sqrt(mean(x²) + eps) · w, eps = rms_norm_eps.
Layer l's Mix is `layer_types[l]`:

  linear_attention (gated DeltaNet), H = linear_num_key_heads (=
           linear_num_value_heads) heads, d_k = linear_key_head_dim, d_v
           = linear_value_head_dim:
           q, k, v = SiLU(conv(x·W_q)), SiLU(conv(x·W_k)), SiLU(conv(x·W_v)):
             a causal depthwise convolution of linear_conv_kernel_dim
             taps a channel, no bias: y_t = Σ_i c[i] · x_{t-K+1+i} (c[K-1]
             weighs the token's own row; rows before the sequence are
             zeros); q ← L2norm_head(q) · d_k^-½, k ← L2norm_head(k) (x ·
             rsqrt(Σx² + 1e-6)); no rotary.
           decay, ONE a head: g = −exp(A_log_h) · softplus(x·W_a +
             dt_bias_h), unbounded below; α = exp(g) ∈ (0, 1);
             β = 2·sigmoid(x·W_b) (linear_allow_neg_eigval; else sigmoid).
           state a head, FLOAT32, S_0 = 0 [d_k, d_v]:
             S_t = α_t (I − β_t k_t k_tᵀ) S_{t-1} + β_t k_t v_tᵀ
             o_t = S_tᵀ q_t
           o ← RMSNorm_{d_v}(o) a head (one weight [d_v] for all heads);
           o ← o ⊙ SiLU(x·W_g) (W_g [d, H·d_v]); Mix = concat(o)·W_o.
           Computed here AS WRITTEN: a `lax.scan` over tokens, no
           chunking, no cache, so it is independent of both forms the
           program has (recurrent, chunked).
  full_attention, num_attention_heads query heads over
           num_key_value_heads K/V heads of d = hidden_size /
           num_attention_heads: q = RMSNorm(x·W_q), k = RMSNorm(x·W_k),
           each over the WHOLE projection before the heads are split; v
           = x·W_v; s = q·kᵀ · d^-½ a head, causal softmax in float32, o
           = Σ p v; Mix = concat(o)·W_o. No position encoding
           (`rope_parameters.rope_theta` is null).

THE SHARE (model-configs guide §4). `num_hidden_layers` and
`layer_types` are this pipeline stage's layers (the first two periods);
the stage holds the whole vocabulary, so ids go in and logits come out.

ASSUMED (the configuration file lists the same under `assumed`, each
with its reason): the norms' placement and the whole-width Q/K norms
(the `olmo_*` family); no position encoding (rope_theta null); the
output gate of value width with SiLU and the per-head norm before it
(FLA's GatedDeltaNet `use_gate`, which the catalog's 208 M a layer bears
out); the convolution causal, depthwise, with SiLU, on q, k and v; the
L2 norms' epsilon 1e-6; the scale d_k^-½; the seed's draws (below).

The forward pass is straightforward `jax.numpy` float32 under
`precision="highest"`; it imports nothing from the program. `quant`
selects a CONTROL: `harness.plain.mm`'s int8 / fp8 for every matrix
product, or this file's own "bf16" (both operands of every product
rounded to bfloat16: the stated precision).

The weights' tree, matrices [in, out] (y = x @ W):

    embed [V, d]  head [V, d]  final_norm [d]
    layers[l]: attn_out_norm mlp_out_norm [d]  wo [heads·d_v, d]
               w_gate w_up [d, f]  w_down [f, d]
      linear:  wq wk [d, H·d_k]  wv w_g [d, H·d_v]  w_a w_b [d, H]
               conv_q conv_k [K, H·d_k]  conv_v [K, H·d_v]
               a_log dt_bias [H] (float32)  o_norm [d_v]
      full:    wq [d, heads·hd]  wk wv [d, kv·hd]  q_norm [heads·hd]
               k_norm [kv·hd]
"""
import functools
import math
import sys
import time

from harness.arith import ITEMSIZE, context_sum
from harness.plain import mm as _plain_mm, seed_key

CONTROLS = ("bf16",)
FULL = "full_attention"

# the chunk the reference's count of the chunked form is stated at,
# whatever the program's
CHUNK = 64


def _mm(x, w, quant):
    import jax.numpy as jnp

    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return _plain_mm(x, w, quant)


NO_TRAINING = ("configuration olmo-hybrid-7b has no training cell: the "
               "chunked scan has no backward pass in this repository")


# --------------------------------------------------------------- sizes

def dims(cfg):
    """The sizes every function here needs, as one dict."""
    out = {
        "d": int(cfg["hidden_size"]), "L": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv": int(cfg["num_key_value_heads"]),
        "H": int(cfg["linear_num_key_heads"]),
        "dk": int(cfg["linear_key_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]),
        "K": int(cfg["linear_conv_kernel_dim"]),
        "neg": bool(cfg["linear_allow_neg_eigval"]),
        "f": int(cfg["intermediate_size"]), "v": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "types": tuple(cfg["layer_types"][:int(cfg["num_hidden_layers"])]),
    }
    out["hd"] = out["d"] // out["heads"]
    if int(cfg["linear_num_value_heads"]) != out["H"]:
        raise ValueError("linear layers with fewer key than value heads "
                         "are not written")
    if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("a rotary full layer is not written: this file "
                         "reads rope_theta null as no position encoding")
    if len(out["types"]) != out["L"] or FULL not in out["types"]:
        raise ValueError("layer_types must name every layer held and hold "
                         "a full_attention layer")
    return out


def positions(cfg):
    """The longest sequence the reference takes: no attention kind has
    a table, so the config's own limit."""
    return int(cfg["max_position_embeddings"])


def is_full(s, l):
    return s["types"][l] == FULL


def full_layers(s):
    return sum(is_full(s, l) for l in range(s["L"]))


def gdn_layers(s):
    return s["L"] - full_layers(s)


# ------------------------------------------------------------- weights

def layer_shapes(s, l):
    d, f = s["d"], s["f"]
    out = {"attn_out_norm": (d,), "mlp_out_norm": (d,), "w_gate": (d, f),
           "w_up": (d, f), "w_down": (f, d)}
    if is_full(s, l):
        hd, heads, kv = s["hd"], s["heads"], s["kv"]
        out.update(wq=(d, heads * hd), wk=(d, kv * hd), wv=(d, kv * hd),
                   q_norm=(heads * hd,), k_norm=(kv * hd,),
                   wo=(heads * hd, d))
    else:
        H, dk, dv, K = s["H"], s["dk"], s["dv"], s["K"]
        out.update(wq=(d, H * dk), wk=(d, H * dk), wv=(d, H * dv),
                   w_g=(d, H * dv), w_a=(d, H), w_b=(d, H),
                   conv_q=(K, H * dk), conv_k=(K, H * dk),
                   conv_v=(K, H * dv), a_log=(H,), dt_bias=(H,),
                   o_norm=(dv,), wo=(H * dv, d))
    return out


def top_shapes(s):
    return {"embed": (s["v"], s["d"]), "head": (s["v"], s["d"]),
            "final_norm": (s["d"],)}


RESIDUAL = ("wo", "w_down")
CONV_STD = 0.5
EMBED_STD = 1.0
A_RANGE = (1.0, 16.0)           # exp(A_log), log-uniform
DT_RANGE = (1e-3, 1e-1)         # softplus(dt_bias), log-uniform


def tree_from_key(key, cfg_json, dtype):
    """The whole tree from one key: normal(0, 0.02), residual
    projections scaled by 1/sqrt(2L), norm weights 1 + normal(0, 0.02).
    What a trained model has and a 0.02 draw would not, float32 where
    the program keeps it so:
      embed    normal(0, 1): no norm stands before the first layer's
               mixer, so its inputs are the embedding rows themselves,
               at the unit scale of a trained model's rows
      conv_*   normal(0, 0.5) a tap: a row's q, k, v mix its three
               predecessors at the order of its own weight
      a_log    log A, A log-uniform in (1, 16) a head; dt_bias the
               inverse softplus of a rate log-uniform in (0.001, 0.1) a
               head (FLA's GatedDeltaNet draws A in (0, 16) and the rate
               so): with x·W_a of unit scale the decays a token spread
               from e^-0.001 to e^-30, heads that keep thousands of
               tokens beside heads that forget in one
    `cfg_json` is the configuration as a JSON string (hashable)."""
    import json

    import jax
    import jax.numpy as jnp

    s = dims(json.loads(cfg_json))
    names = sorted(top_shapes(s)) + [
        f"layers/{l}/{n}" for l in range(s["L"])
        for n in sorted(layer_shapes(s, l))]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def log_uniform(k, shape, lo, hi):
        return jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                          math.log(lo), math.log(hi)))

    def draw(name, shape):
        base = name.rsplit("/", 1)[-1]
        k = keys[name]
        if base == "a_log":
            return jnp.log(log_uniform(k, shape, *A_RANGE))
        if base == "dt_bias":
            dt = log_uniform(k, shape, *DT_RANGE)
            return dt + jnp.log(-jnp.expm1(-dt))     # softplus⁻¹
        std = 0.02 / math.sqrt(2 * s["L"]) if base in RESIDUAL else 0.02
        if base.startswith("conv_"):
            std = CONV_STD
        if base == "embed":
            std = EMBED_STD
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
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "intermediate_size", "vocab_size",
    "rms_norm_eps", "max_position_embeddings", "layer_types",
    "rope_parameters")


def cfg_json(cfg):
    """The model's own keys as a canonical JSON string: the static
    argument of every jitted function here."""
    import json

    return json.dumps({k: cfg[k] for k in MODEL_KEYS}, sort_keys=True)


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


def gdn_gates(s, x, lw, quant):
    """(g [S, H] the log decay a head, β [S, H]) float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    g = -jnp.exp(f32(lw["a_log"]))[None, :] * jax.nn.softplus(
        _mm(x, f32(lw["w_a"]), quant) + f32(lw["dt_bias"])[None, :])
    beta = jax.nn.sigmoid(_mm(x, f32(lw["w_b"]), quant))
    return g, (2.0 if s["neg"] else 1.0) * beta


def delta_rule_scan(q, k, v, g, beta, state):
    """The recurrence as written, a token at a time: q k [S, H, d_k], v
    [S, H, d_v], g β [S, H], state [H, d_k, d_v] → (o [S, H, d_v], the
    state after the last token)."""
    import jax
    import jax.numpy as jnp

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = S * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", S, k_t, precision="highest"))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision="highest")

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def gdn_mix(s, x, lw, quant):
    """Mix of a linear_attention layer over x [S, d] (float32)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    S, H, dk, dv = x.shape[0], s["H"], s["dk"], s["dv"]

    def branch(w, taps, width):
        return jax.nn.silu(short_conv(_mm(x, f32(lw[w]), quant),
                                      f32(lw[taps]))).reshape(S, H, width)

    q = l2_norm(branch("wq", "conv_q", dk)) * dk ** -0.5
    k = l2_norm(branch("wk", "conv_k", dk))
    v = branch("wv", "conv_v", dv)
    g, beta = gdn_gates(s, x, lw, quant)
    o, _ = delta_rule_scan(q, k, v, g, beta,
                           jnp.zeros((H, dk, dv), jnp.float32))
    o = rms_norm(o, f32(lw["o_norm"]), s["eps"]).reshape(S, H * dv)
    o = o * jax.nn.silu(_mm(x, f32(lw["w_g"]), quant))
    return _mm(o, f32(lw["wo"]), quant)


def full_mix(s, x, lw, quant):
    """Mix of a full_attention layer over x [S, d] (float32): causal,
    no position encoding."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    S, hd, heads, kv = x.shape[0], s["hd"], s["heads"], s["kv"]
    q = rms_norm(_mm(x, f32(lw["wq"]), quant), f32(lw["q_norm"]), s["eps"])
    k = rms_norm(_mm(x, f32(lw["wk"]), quant), f32(lw["k_norm"]), s["eps"])
    v = _mm(x, f32(lw["wv"]), quant).reshape(S, kv, hd)
    q = q.reshape(S, kv, heads // kv, hd)
    sc = jnp.einsum("qhgd,khd->hgqk", q, k.reshape(S, kv, hd),
                    precision="highest") / math.sqrt(hd)
    pos = jnp.arange(S)
    sc = jnp.where(pos[None, :] <= pos[:, None], sc, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v,
                   precision="highest")
    return _mm(o.reshape(S, heads * hd), f32(lw["wo"]), quant)


def layer_forward(cfg, l, x, lw, quant=None):
    """One decoder layer over x [S, d] float32."""
    import jax
    import jax.numpy as jnp

    s = dims(cfg)
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    mix = full_mix if is_full(s, l) else gdn_mix
    h = x + rms_norm(mix(s, x, lw, quant), f32(lw["attn_out_norm"]),
                     s["eps"])
    m = _mm(jax.nn.silu(_mm(h, f32(lw["w_gate"]), quant))
            * _mm(h, f32(lw["w_up"]), quant), f32(lw["w_down"]), quant)
    return h + rms_norm(m, f32(lw["mlp_out_norm"]), s["eps"])


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_str, full, quant):
    """One jitted program a layer KIND."""
    import json

    import jax

    cfg = json.loads(cfg_str)
    s = dims(cfg)
    l = next(i for i in range(s["L"]) if is_full(s, i) == full)
    return jax.jit(lambda x, lw: layer_forward(cfg, l, x, lw, quant))


def hidden(cfg, w, ids, quant=None):
    """Final-RMSNorm hidden states [S, d] of token ids [S]."""
    import jax.numpy as jnp

    s = dims(cfg)
    key = cfg_json(cfg)
    x = w["embed"].astype(jnp.float32)[ids]
    for l in range(s["L"]):
        x = _layer_fn(key, is_full(s, l), quant)(x, w["layers"][l])
    return rms_norm(x, w["final_norm"].astype(jnp.float32), s["eps"])


def logits_fn(cfg, w, ids, quant=None):
    """[S, V] float32 logits of one sequence of token ids [S]."""
    import jax.numpy as jnp

    return _mm(hidden(cfg, w, ids, quant),
               w["head"].astype(jnp.float32).T, quant)


# ------------------------------------------------------------- serving

# a layer here compiles in seconds a shape: ONE length for the cell's
# sequences (320 … 1 536 tokens); sequences up to SHORT keep the
# caller's padding (the tests')
SEQ_BUCKET = 1536
SHORT = 256


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
    print(f"[reference olmo_hybrid] {len(toks)} positions as {pad_to}, "
          f"{n} served rows, quant {quant}: "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)
    return gap, margin


# ---------------------------------------------------------- arithmetic

def mix_matrix_params(s, l):
    """The layer's mixer matrices (what a token multiplies)."""
    d = s["d"]
    if is_full(s, l):
        return d * (s["heads"] + 2 * s["kv"]) * s["hd"] \
            + s["heads"] * s["hd"] * d
    H, dk, dv = s["H"], s["dk"], s["dv"]
    return d * H * (2 * dk + 2 * dv + 2) + H * dv * d


def mix_small_params(s, l):
    """Norm weights, and GDN's convolution taps, A_log and dt_bias."""
    d = s["d"]
    if is_full(s, l):
        return (s["heads"] + s["kv"]) * s["hd"] + d
    H = s["H"]
    return s["K"] * H * (2 * s["dk"] + s["dv"]) + 2 * H + s["dv"] + d


def mlp_params(s):
    return 3 * s["d"] * s["f"] + s["d"]


def param_count(cfg):
    """Every parameter held here."""
    s = dims(cfg)
    return 2 * s["v"] * s["d"] + s["d"] + sum(
        mix_matrix_params(s, l) + mix_small_params(s, l) + mlp_params(s)
        for l in range(s["L"]))


def rows_attended_by_row(work):
    """Σ over the work's tokens of the positions each attends, one
    layer: this reference's OWN count from the driver's `segments`."""
    return sum(context_sum(int(start), int(n))
               for start, n in work["segments"])


def gdn_rows(cfg, work):
    """(query rows × GDN layers that went through the recurrent form,
    through the chunked form): the program's counters; without them,
    every row recurrent."""
    st = work.get("stats") or {}
    if "gdn_rows_recurrent" in st:
        return int(st["gdn_rows_recurrent"]), int(st["gdn_rows_chunked"])
    return gdn_layers(dims(cfg)) * int(work["processed"]), 0


def state_bytes(s):
    """One layer's float32 state of one sequence."""
    return s["H"] * s["dk"] * s["dv"] * 4


def gdn_state_bytes(cfg, work):
    """Least bytes the RECURRENT form must move: a row reads its
    sequence's state once and writes it once, a layer."""
    return 2 * state_bytes(dims(cfg)) * gdn_rows(cfg, work)[0]


def gdn_recurrent_flops_per_row(s):
    """The decay, Sᵀk, the rank-one update and Sᵀq, all heads."""
    return 7 * s["H"] * s["dk"] * s["dv"]


def gdn_chunk_flops(cfg, work, chunk=CHUNK):
    """FLOPs of the chunked form (WY / UT transform) at a stated chunk
    of C rows, for the rows the counter says went chunked, all heads, a
    row: q·k and k·k (2·2·C·d_k, halved: causal), the triangular solve
    applied to [k | v] (2·C·(d_k + d_v), halved), w·S and q·S
    (2·2·d_k·d_v), the intra-chunk output (2·C·d_v, halved) and the
    state's update (2·d_k·d_v)."""
    s = dims(cfg)
    dk, dv, C = s["dk"], s["dv"], int(chunk)
    per_row = s["H"] * (2 * C * dk + C * (dk + dv) + 4 * dk * dv
                        + C * dv + 2 * dk * dv)
    return per_row * gdn_rows(cfg, work)[1]


def gdn_chunk_bytes(cfg, work):
    """Least bytes the chunked form must move: q, k, v, the decay, β
    and o of every row in and out at the compute dtype's two bytes, and
    a run's state in and out once, counted through `gdn_chunk_launches`
    (runs × layers); without it, none."""
    s = dims(cfg)
    st = work.get("stats") or {}
    runs = int(st.get("gdn_chunk_launches", 0))
    row = s["H"] * (2 * s["dk"] + 2 * s["dv"] + 2) * 2
    return row * gdn_rows(cfg, work)[1] + 2 * state_bytes(s) * runs


def kv_bytes_per_token(cfg, kv_dtype):
    """The K and V rows a token keeps in every full layer (a GDN layer
    keeps nothing a token)."""
    s = dims(cfg)
    return full_layers(s) * 2 * s["kv"] * s["hd"] * ITEMSIZE[kv_dtype]


def kv_bytes_attended_by_row(cfg, work, kv_dtype):
    """Full layers' K and V bytes when every token of the driver's
    `segments` reads its own context: this reference's OWN count."""
    return kv_bytes_per_token(cfg, kv_dtype) * rows_attended_by_row(work)


def kv_bytes_attended(cfg, work, kv_dtype):
    """Least bytes the full layers' walk must read: the K and V rows of
    every attended position, once a KV head. Given the program's counter
    `kv_positions_least_full` (positions a slot's rows of ONE step
    attend, counted once a step), the count is its; the GDN layers'
    state is `gdn_state_bytes`, not here."""
    st = work.get("stats") or {}
    if "kv_positions_least_full" in st:
        return kv_bytes_per_token(cfg, kv_dtype) * int(
            st["kv_positions_least_full"])
    return kv_bytes_attended_by_row(cfg, work, kv_dtype)


def weight_bytes(cfg, dtype, work=None):
    """Bytes of the whole tree; given `work`, the least its iterations
    must read of it: all of it once an iteration (no experts)."""
    held = param_count(cfg) * ITEMSIZE[dtype]
    if work is None:
        return held
    return int(work["iterations"]) * held


def serve_flops(cfg, work):
    """Forward only, of what THIS chip computes. Per processed token 2
    FLOPs a parameter of each layer's mixer matrices, the MLP and the
    head (counted for every token, as the accepted references count
    it); the full layers' scores + context, 4·d a query head an attended
    position; a GDN layer's recurrence by the form the counter says a
    row took."""
    s = dims(cfg)
    tokens = int(work["processed"])
    per_token = s["v"] * s["d"] + sum(
        mix_matrix_params(s, l) + 3 * s["d"] * s["f"]
        for l in range(s["L"]))
    att = 4 * s["hd"] * s["heads"] * full_layers(s) \
        * rows_attended_by_row(work)
    recur, _ = gdn_rows(cfg, work)
    return 2 * per_token * tokens + att \
        + gdn_recurrent_flops_per_row(s) * recur + gdn_chunk_flops(cfg, work)


def train_step_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)


def flash_attn_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)
