"""Operations and bytes from SHAPES — never from a kernel's grid or
tiles, so that a share of a peak reads the same work whatever
implements it. `cfg` is a configuration file's dict (GPT-2 key names:
n_embd, n_layer, n_head, n_inner, n_positions, vocab_size).

Copies of the program's arithmetic (the originals are listed in PERF.md
for a later PR to delete): `observability.steptrace.model_flops`,
`LLMEngineConfig.kv_bytes_per_page`.
"""

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def dims(cfg):
    d = int(cfg["n_embd"])
    ffn = int(cfg.get("n_inner") or 4 * d)
    return d, int(cfg["n_layer"]), int(cfg["n_head"]), ffn, \
        int(cfg["vocab_size"]), int(cfg["n_positions"])


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


def serve_flops(cfg, n_tokens, context_sum):
    """Forward only. `n_tokens` tokens went through the model (prefill
    rows and decode rows alike: 2·P each), and `context_sum` is the sum
    over those tokens of the context length each attended (scores and
    context: 4·d per attended position per layer)."""
    d, L, *_ = dims(cfg)
    return 2 * matmul_params(cfg) * int(n_tokens) \
        + 4 * d * L * int(context_sum)


def kv_bytes_per_token(cfg, kv_dtype):
    """K and V rows of one token over all layers."""
    d, L, *_ = dims(cfg)
    return 2 * L * d * _ITEMSIZE[kv_dtype]


def kv_bytes_per_page(cfg, page_size, kv_dtype):
    return int(page_size) * kv_bytes_per_token(cfg, kv_dtype)


def weight_bytes(cfg, dtype):
    return param_count(cfg) * _ITEMSIZE[dtype]


def paged_attn_bytes(cfg, context_sum, kv_dtype):
    """Least bytes attention must read: the K and V rows of every
    attended position, once per attending token."""
    return int(context_sum) * kv_bytes_per_token(cfg, kv_dtype)


def context_sum(start, n):
    """Σ of the context lengths attended by `n` consecutive tokens of
    one sequence, the first of which attends `start + 1` positions."""
    return n * start + n * (n + 1) // 2
