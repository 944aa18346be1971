"""Reference `hfdecoder`, test data: the small decoder of the `gpt2`
reference with its configuration spelled in other keys (hidden_size,
num_hidden_layers, num_attention_heads, intermediate_size,
max_position_embeddings) and none of GPT-2's. A test copies this file
into a temporary benchmark as `references/hfdecoder.py`, with the
builder, configuration and readers beside it, and runs a cell from
those new files alone.

The weights and the plain forward pass are the same decoder's, so
they are `references.gpt2`'s under a translation of the keys; the
arithmetic is written here from this file's own keys and the parts
every reference shares (`harness.arith`), and the test holds it
against GPT-2's at the same sizes. No training part: the contract
leaves it optional.
"""
from harness.arith import ITEMSIZE, context_sum
from references import gpt2

KEYS = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
        "num_attention_heads": "n_head", "intermediate_size": "n_inner",
        "max_position_embeddings": "n_positions"}


def as_gpt2(cfg):
    """The same sizes under the keys the shared decoder reads."""
    return dict(cfg, **{theirs: cfg[ours] for ours, theirs in KEYS.items()})


def make_weights(cfg, seed, dtype):
    return gpt2.make_weights(as_gpt2(cfg), seed, dtype)


def positions(cfg):
    return int(cfg["max_position_embeddings"])


def served_token_gaps(cfg, w, tokens, prompt_len, pad_to, rows_to,
                      quant=None):
    return gpt2.served_token_gaps(as_gpt2(cfg), w, tokens, prompt_len,
                                  pad_to, rows_to, quant=quant)


# ---- arithmetic, from this configuration's own keys -----------------

def _sizes(cfg):
    return (int(cfg["hidden_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["intermediate_size"]), int(cfg["vocab_size"]))


def _matmul_params(cfg):
    d, layers, ffn, vocab = _sizes(cfg)
    return layers * (4 * d * d + 2 * d * ffn) + vocab * d


def param_count(cfg):
    d, layers, ffn, vocab = _sizes(cfg)
    per_layer = 4 * d * d + 2 * d * ffn + 9 * d + ffn
    return (vocab + positions(cfg)) * d + layers * per_layer + 2 * d


def attended(work):
    return sum(context_sum(int(s), int(n)) for s, n in work["segments"])


def serve_flops(cfg, work):
    d, layers, *_ = _sizes(cfg)
    return 2 * _matmul_params(cfg) * int(work["processed"]) \
        + 4 * d * layers * attended(work)


def kv_bytes_per_token(cfg, kv_dtype):
    d, layers, *_ = _sizes(cfg)
    return 2 * layers * d * ITEMSIZE[kv_dtype]


def kv_bytes_attended(cfg, work, kv_dtype):
    return attended(work) * kv_bytes_per_token(cfg, kv_dtype)


def weight_bytes(cfg, dtype, work=None):
    held = param_count(cfg) * ITEMSIZE[dtype]
    return held if work is None else int(work["iterations"]) * held


def flash_attn_flops(cfg, batch, seq):
    d, layers, *_ = _sizes(cfg)
    return layers * int(batch) * (4 * int(seq) ** 2 * d) * 3 * 0.5


def train_step_flops(cfg, batch, seq):
    return 6 * _matmul_params(cfg) * int(batch) * int(seq) \
        + flash_attn_flops(cfg, batch, seq)
