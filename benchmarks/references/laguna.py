"""Reference `laguna`: the Laguna block (`model_type: laguna`) behind
the contract of `references/__init__.py`: its weights from the seed,
its plain forward pass (serving gaps) and its arithmetic. Key names are
the published config's own (hidden_size, num_attention_heads_per_layer,
layer_types, num_experts, ...): nothing outside this file and its
builder reads them.

THE EQUATIONS. For layer l over x [T, d]:

    h = x + Attn_l(RMSNorm(x));   y = h + FFN_l(RMSNorm(h))

and after the last layer a final RMSNorm and logits = y · W_headᵀ
(untied). RMSNorm(x) = x / sqrt(mean(x²) + eps) · w.

  Attn_l   H_l = num_attention_heads_per_layer[l] query heads, KV =
           num_key_value_heads key/value heads, all of head_dim. q =
           n·W_q [T, H_l, D], k, v = n·W_k, n·W_v [T, KV, D], n the
           normed input. Rotary by layer_types[l] from rope_parameters
           (full_attention: YaRN, attention_factor multiplying cos and
           sin, the leading partial_rotary_factor·D dimensions of a head
           rotated and the rest passed through; sliding_attention:
           default, the whole head). Query head j attends KV head
           j // (H_l / KV); scores / sqrt(D); causal; in a sliding layer
           position p sees keys p - (sliding_window - 1) … p. Gate g =
           sigmoid(n·W_g) [T, H_l]; output concat_j(g_j · o_j) · W_o.
  FFN_l    dense (mlp_layer_types[l] == "dense"): (silu(n·W_gate) ⊙
           n·W_up) · W_down at intermediate_size. Sparse: r = n·W_r over
           ALL published experts, float32; p = softmax(r); the
           num_experts_per_tok largest; w_e = p_e / Σ_top p
           (norm_topk_prob); out = Shared(n) + moe_routed_scaling_factor
           · Σ_e w_e · E_e(n), every E_e and Shared a gated MLP.

THE SHARE (model-configs guide §4). `num_experts` is the number of
routed experts HELD here (ids 0 … num_experts-1 of `published.
num_experts`); the router keeps the published width and the published
experts per token; the sum runs over the held experts a token chose, and
what the absent ones would add is left out. `vocab_size` is the rows of
embedding and head held here; traffic, logits and argmax are over them.

ASSUMED (the config has no key; the configuration file lists the same
under `assumed`): pre-norm residual placement; silu; softmax scoring of
the router; the gate's form (sigmoid, from the normed layer input, on
the attention output before W_o, one scalar a head); an ungated shared
expert; no q/k norm. Rotary uses the rotate-half convention and YaRN's
frequencies as `transformers` computes them.

The forward pass is straightforward `jax.numpy` float32 under
`precision="highest"`, no kernel, no cache, no batching tricks; it
imports nothing from the program. It is computed in blocks so that it
fits beside the seed's bf16 tree (9.4 GB): one layer upcast at a time,
queries a block and experts one at a time. `quant` selects the CONTROL
(`harness.plain.mm`) for every matrix product, the router's included.

The weights' tree, matrices [in, out] (y = x @ W):

    embed [V, d]  head [V, d]  final_norm [d]
    layers[l]: attn_norm ffn_norm [d]  wq [d, H_l·D]  wk wv [d, KV·D]
               wg [d, H_l]  wo [H_l·D, d]
      dense:   w_gate w_up [d, f]  w_down [f, d]
      sparse:  router [d, E_pub]  e_gate e_up [E, d, m]  e_down [E, m, d]
               s_gate s_up [d, ms]  s_down [ms, d]
"""
import functools
import math
import sys
import time

from harness.arith import ITEMSIZE
from harness.plain import mm as _plain_mm, seed_key


def _mm(x, w, quant):
    """`harness.plain.mm`, with two controls of this reference's own
    beside its int8 / fp8 (builder's tools, `--control <name>`): "bf16"
    rounds both operands of every product to bfloat16 and accumulates
    in float32 (the precision the configuration STATES: what a sound
    program's reading should look like); "router_bf16" is float32
    everywhere but rounds the router's input to bfloat16 (`route`): how
    much of a reading discrete routing alone makes."""
    import jax.numpy as jnp

    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return _plain_mm(x, w, None if quant == "router_bf16" else quant)

NO_TRAINING = ("configuration laguna-s-2.1 has no training cell: at 16 "
               "bytes a parameter its smallest allowed cut does not fit "
               "one chip")


# --------------------------------------------------------------- sizes

def dims(cfg):
    """The sizes every function here needs, as one dict."""
    L = int(cfg["num_hidden_layers"])
    out = {
        "d": int(cfg["hidden_size"]), "L": L,
        "kv": int(cfg["num_key_value_heads"]), "hd": int(cfg["head_dim"]),
        "heads": [int(h) for h in cfg["num_attention_heads_per_layer"][:L]],
        "kinds": list(cfg["layer_types"][:L]),
        "mlps": list(cfg["mlp_layer_types"][:L]),
        "f": int(cfg["intermediate_size"]),
        "m": int(cfg["moe_intermediate_size"]),
        "ms": int(cfg["shared_expert_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "routed": int(cfg["published"]["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["moe_routed_scaling_factor"]),
        "window": int(cfg["sliding_window"]),
        "v": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }
    assert len(out["heads"]) == len(out["kinds"]) == len(out["mlps"]) == L
    return out


def positions(cfg):
    """The longest sequence the reference takes: rotary positions have
    no table, so the config's own limit."""
    return int(cfg["max_position_embeddings"])


# ------------------------------------------------------------- weights

def layer_shapes(s, l):
    d, hd, kv, H = s["d"], s["hd"], s["kv"], s["heads"][l]
    out = {"attn_norm": (d,), "ffn_norm": (d,), "wq": (d, H * hd),
           "wk": (d, kv * hd), "wv": (d, kv * hd), "wg": (d, H),
           "wo": (H * hd, d)}
    if s["mlps"][l] == "dense":
        out.update(w_gate=(d, s["f"]), w_up=(d, s["f"]),
                   w_down=(s["f"], d))
    else:
        E, m, ms = s["held"], s["m"], s["ms"]
        out.update(router=(d, s["routed"]), e_gate=(E, d, m),
                   e_up=(E, d, m), e_down=(E, m, d), s_gate=(d, ms),
                   s_up=(d, ms), s_down=(ms, d))
    return out


def top_shapes(s):
    return {"embed": (s["v"], s["d"]), "head": (s["v"], s["d"]),
            "final_norm": (s["d"],)}


RESIDUAL = ("wo", "w_down", "e_down", "s_down")


def tree_from_key(key, cfg_json, dtype):
    """The whole tree from one key: normal(0, 0.02), residual
    projections scaled by 1/sqrt(2L), norm weights 1 + normal(0, 0.02).
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
    "hidden_size", "num_hidden_layers", "num_key_value_heads", "head_dim",
    "num_attention_heads_per_layer", "layer_types", "mlp_layer_types",
    "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "moe_routed_scaling_factor", "sliding_window", "vocab_size",
    "rms_norm_eps", "rope_parameters", "max_position_embeddings")


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

def inv_frequencies(rope, rotary_dim):
    """[rotary_dim / 2] inverse frequencies and the factor multiplying
    cos and sin, for one entry of `rope_parameters` (numpy, float64)."""
    import numpy as np

    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, rotary_dim, 2, dtype=np.float64)
                         / rotary_dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freqs, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rotary_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))),
               rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolation) \
        + (1.0 / pos_freqs) * extrapolation
    return inv, float(rope["attention_factor"])


def rope_tables(cfg, kind, positions_):
    """cos, sin [S, rotary_dim] (float32) of `positions_` for a layer of
    `kind`, and rotary_dim."""
    import jax.numpy as jnp
    import numpy as np

    rope = cfg["rope_parameters"][kind]
    rd = int(round(int(cfg["head_dim"])
                   * float(rope.get("partial_rotary_factor", 1))))
    inv, factor = inv_frequencies(rope, rd)
    ang = positions_.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv.astype(np.float32))[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor, rd


def apply_rope(x, cos, sin, rd):
    """x [S, H, D]: the first `rd` dimensions of every head rotated
    (rotate-half), the rest passed through."""
    import jax.numpy as jnp

    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., :rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    xr = xr * cos[:, None, :] + rot * sin[:, None, :]
    return jnp.concatenate([xr, xp], axis=-1)


# ------------------------------------------------------------- forward

def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(s, cfg, l, n, lw, quant, q_block=512):
    """Attn_l of the normed input n [S, d] (float32)."""
    import jax
    import jax.numpy as jnp

    S = n.shape[0]
    H, kv, hd = s["heads"][l], s["kv"], s["hd"]
    kind = s["kinds"][l]
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    pos = jnp.arange(S)
    cos, sin, rd = rope_tables(cfg, kind, pos)
    q = apply_rope(_mm(n, f32(lw["wq"]), quant).reshape(S, H, hd),
                   cos, sin, rd)
    k = apply_rope(_mm(n, f32(lw["wk"]), quant).reshape(S, kv, hd),
                   cos, sin, rd)
    v = _mm(n, f32(lw["wv"]), quant).reshape(S, kv, hd)
    gate = jax.nn.sigmoid(_mm(n, f32(lw["wg"]), quant))          # [S, H]
    group = H // kv
    if S % q_block:
        q_block = S               # a short sequence: one block

    def block(args):
        """Queries a block at a time (the scores of 72 heads over 5 120
        positions would not fit at once): plain masked softmax."""
        qb, qi = args                                  # [B, ...], [B]
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k,
                        precision="highest") / math.sqrt(hd)
        see = pos[None, :] <= qi[:, None]
        if kind == "sliding_attention":
            see = see & (pos[None, :] > qi[:, None] - s["window"])
        sc = jnp.where(see, sc, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v,
                          precision="highest")

    o = jax.lax.map(block, (
        q.reshape(S // q_block, q_block, kv, group, hd),
        pos.reshape(S // q_block, q_block)))
    o = o.reshape(S, H, hd) * gate[:, :, None]
    return _mm(o.reshape(S, H * hd), f32(lw["wo"]), quant)


def gated_mlp(x, wg, wu, wd, quant):
    import jax

    return _mm(jax.nn.silu(_mm(x, wg, quant)) * _mm(x, wu, quant), wd,
               quant)


def route(s, n, router, quant):
    """(weights [S, E_pub] float32, zero off the chosen; chosen ids
    [S, top_k]): softmax over all published experts, the top_k largest,
    renormalised."""
    import jax
    import jax.numpy as jnp

    if quant == "router_bf16":
        n = n.astype(jnp.bfloat16).astype(jnp.float32)
    r = _mm(n, router.astype(jnp.float32),
            None if quant in ("bf16", "router_bf16") else quant)
    p = jax.nn.softmax(r, axis=-1)
    top_p, top_i = jax.lax.top_k(p, s["top_k"])
    w = top_p / jnp.sum(top_p, -1, keepdims=True)
    full = jnp.zeros_like(p).at[
        jnp.arange(n.shape[0])[:, None], top_i].set(w)
    return full, top_i


def sparse_ffn(s, n, lw, quant, held=None):
    """(Shared(n), scale · Σ over the HELD experts a token chose).
    `held` (first, count) narrows the held experts further (the share
    test)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    w, _ = route(s, n, lw["router"], quant)
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
    h = x + attention(s, cfg, l, rms_norm(x, f32(lw["attn_norm"]),
                                          s["eps"]), lw, quant)
    n = rms_norm(h, f32(lw["ffn_norm"]), s["eps"])
    if s["mlps"][l] == "dense":
        return h + gated_mlp(n, f32(lw["w_gate"]), f32(lw["w_up"]),
                             f32(lw["w_down"]), quant)
    shared, routed = sparse_ffn(s, n, lw, quant)
    return h + shared + routed


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_str, l, quant):
    """One jitted program a layer SHAPE: layers that share head count,
    kind and feed-forward share the executable."""
    import json

    import jax

    cfg = json.loads(cfg_str)
    s = dims(cfg)
    same = next(j for j in range(s["L"])
                if (s["heads"][j], s["kinds"][j], s["mlps"][j])
                == (s["heads"][l], s["kinds"][l], s["mlps"][l]))
    if same != l:
        return _layer_fn(cfg_str, same, quant)
    return jax.jit(lambda x, lw: layer_forward(cfg, l, x, lw, quant))


def hidden(cfg, w, ids, quant=None):
    """Final-RMSNorm hidden states [S, d] of token ids [S]."""
    import jax.numpy as jnp

    s = dims(cfg)
    key = cfg_json(cfg)
    x = w["embed"].astype(jnp.float32)[ids]
    for l in range(s["L"]):
        x = _layer_fn(key, l, quant)(x, w["layers"][l])
    return rms_norm(x, w["final_norm"].astype(jnp.float32), s["eps"])


def logits_fn(cfg, w, ids, quant=None):
    """[S, V] float32 logits of one sequence of token ids [S]."""
    import jax.numpy as jnp

    return _mm(hidden(cfg, w, ids, quant),
               w["head"].astype(jnp.float32).T, quant)


# ------------------------------------------------------------- serving

SEQ_BUCKETS = (2048, 5120)


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
    # a layer here compiles in tens of seconds a shape: two lengths,
    # not one every 256 positions
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
    print(f"[reference laguna] {len(toks)} positions as {pad_to}, {n} "
          f"served rows, quant {quant}: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)
    return gap, margin


# ---------------------------------------------------------- arithmetic

def attn_params(s, l):
    H, kv, hd, d = s["heads"][l], s["kv"], s["hd"], s["d"]
    return d * H * hd + 2 * d * kv * hd + d * H + H * hd * d


def expert_params(s):
    """One routed expert."""
    return 3 * s["d"] * s["m"]


def ffn_params(s, l, experts=None):
    """Feed-forward parameters of layer l with `experts` routed experts
    counted (default: the held ones)."""
    d = s["d"]
    if s["mlps"][l] == "dense":
        return 3 * d * s["f"]
    e = s["held"] if experts is None else experts
    return d * s["routed"] + e * expert_params(s) + 3 * d * s["ms"]


def param_count(cfg):
    """Every parameter held here: embedding and untied head (the rows
    held), every layer's attention, norms, router, held experts and
    shared expert, the final norm."""
    s = dims(cfg)
    return 2 * s["v"] * s["d"] + s["d"] + sum(
        attn_params(s, l) + ffn_params(s, l) + 2 * s["d"]
        for l in range(s["L"]))


def sparse_layers(s):
    return sum(m != "dense" for m in s["mlps"])


def moe_counts(work):
    """(assignments to held experts, experts touched) in `work`, from
    the program's counters (summed over sparse layers and iterations);
    None where the work carries none."""
    st = work.get("stats") or {}
    if "moe_assignments_held" not in st:
        return None
    return int(st["moe_assignments_held"]), int(st["moe_experts_touched"])


def attended(s, work, l):
    """Σ over the work's tokens of the positions layer l attends: every
    earlier position in a full layer, at most `window` in a sliding
    one."""
    full = s["kinds"][l] != "sliding_attention"
    W = s["window"]
    tot = 0
    for start, n in work["segments"]:
        start, n = int(start), int(n)
        if full:
            tot += n * start + n * (n + 1) // 2
            continue
        # token i (0-based) attends min(start + i + 1, W)
        grow = max(0, min(n, W - start))     # tokens still under W
        tot += grow * start + grow * (grow + 1) // 2 + (n - grow) * W
    return tot


def serve_flops(cfg, work):
    """Forward only, of what THIS chip computes. Per processed token 2
    FLOPs a parameter of attention, the dense or shared feed-forward,
    the router and the head's rows held here (the head runs on the
    sampled rows only; it is counted for every token, as the accepted
    reference counts it: 39 M of 1 019 M a token). Per assignment to a
    held expert 2 · 3·d·m, from the program's counter (without one, the
    expected share top_k · held / routed a token a sparse layer). And
    scores + context: 4·D a query head an attended position."""
    s = dims(cfg)
    tokens = int(work["processed"])
    per_token = s["v"] * s["d"] + sum(
        attn_params(s, l) + ffn_params(s, l, experts=0)
        for l in range(s["L"]))
    counts = moe_counts(work)
    assignments = counts[0] if counts else \
        tokens * sparse_layers(s) * s["top_k"] * s["held"] / s["routed"]
    att = sum(4 * s["hd"] * s["heads"][l] * attended(s, work, l)
              for l in range(s["L"]))
    return 2 * per_token * tokens + 2 * expert_params(s) * assignments \
        + att


def kv_bytes_per_token(cfg, kv_dtype):
    """K and V rows a token keeps for as long as its sequence lives:
    the FULL layers' (the pool `engine.pool_budget_bytes` sizes). A
    sliding layer's rows live in the window pool, bounded a slot by
    window + chunk positions and not by the sequence: `window_kv_bytes_
    per_token` a position there."""
    s = dims(cfg)
    full = sum(k != "sliding_attention" for k in s["kinds"])
    return 2 * full * s["kv"] * s["hd"] * ITEMSIZE[kv_dtype]


def window_kv_bytes_per_token(cfg, kv_dtype):
    s = dims(cfg)
    win = sum(k == "sliding_attention" for k in s["kinds"])
    return 2 * win * s["kv"] * s["hd"] * ITEMSIZE[kv_dtype]


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
    """K and V bytes when every token of the driver's `segments` reads
    its own context (sliding layers clipped at the window; a row once
    for the query heads that share it): this reference's OWN count, and
    no lower bound: a kernel that reads a prefill chunk's context once
    for several rows reads less."""
    s = dims(cfg)
    row = 2 * s["kv"] * s["hd"] * ITEMSIZE[kv_dtype]
    return row * sum(attended(s, work, l) for l in range(s["L"]))


def kv_bytes_attended(cfg, work, kv_dtype):
    """Least bytes attention must read: the K and V rows of every
    attended position, once a KV head (the 6 or 9 query heads that
    share it read its row once), sliding layers clipped at the window.
    Given the program's counters `kv_positions_least_full` / `_window`
    (positions a slot's rows of ONE step attend, counted once a step: a
    prefill chunk's rows share their context), the count is theirs:
    the driver's segments do not say which rows shared a step (PERF.md
    §7), and `kv_least_share_of_rows.decode` reports it beside
    `kv_bytes_attended_by_row`, which stands in without the counters."""
    s = dims(cfg)
    row = 2 * s["kv"] * s["hd"] * ITEMSIZE[kv_dtype]
    st = work.get("stats") or {}
    if "kv_positions_least_full" in st:
        full = sum(k != "sliding_attention" for k in s["kinds"])
        return row * (full * int(st["kv_positions_least_full"])
                      + (s["L"] - full)
                      * int(st.get("kv_positions_least_window", 0)))
    return kv_bytes_attended_by_row(cfg, work, kv_dtype)


def train_step_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)


def flash_attn_flops(cfg, batch, seq):
    raise NotImplementedError(NO_TRAINING)
