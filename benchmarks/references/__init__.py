"""References, found by a configuration's `reference` key:
`references/<reference>.py`, as a builder is found by `builder` and a
driver by the mix's `kind`. One file is one architecture's yardstick:
its weights from the seed, its plain forward pass and its arithmetic.
It imports nothing of the program and names its own configuration
keys; `run.py`, `harness/`, the drivers and the per-layer readers name
none. What knows no model is written once, for every reference to
import: `harness.plain` (`seed_key`, the matrix product `mm` with its
int8 and fp8 controls), `harness.arith` (`ITEMSIZE`, `context_sum`).
A second architecture is a NEW file here (with its builder and its
configuration), and no edit to a file that is there.

THE CONTRACT. `cfg` is the configuration file's dict as it is run.

  Weights and the served comparison (every reference):
    make_weights(cfg, seed, dtype) -> the reference's own tree, on the
        device, in one jitted call; the same seed gives the same tree
    positions(cfg) -> the longest sequence the forward pass takes
    served_token_gaps(cfg, w, tokens, prompt_len, pad_to, rows_to,
        quant=None) -> (gaps, margins), one per served token of
        `tokens[prompt_len:]`: how far the served token's logit lies
        below the reference's best, and the reference's own margin
        there. With `quant` (a control precision of `harness.plain.mm`)
        the token is the one that precision puts first. Shapes are
        padded to (`pad_to`, `rows_to`) so that every seed compiles the
        same few programs.

  Arithmetic, from shapes and never from a grid (every reference):
    param_count(cfg)
    weight_bytes(cfg, dtype, work=None) -> bytes held; given `work`,
        the least its iterations must read of them
    serve_flops(cfg, work)
    kv_bytes_attended(cfg, work, kv_dtype)
    kv_bytes_per_token(cfg, kv_dtype)
    train_step_flops(cfg, batch, seq)
    flash_attn_flops(cfg, batch, seq)
  `work` is what the driver saw (`harness/arith.py` describes it).

  Training (optional; a train cell needs it):
    ADAMW -> {"lr", "beta1", "beta2", "eps", "weight_decay"}
    train_three_steps(cfg, w0, batches, quant=None, keep_rows=None)
        -> {"losses": [..], "grad1": leaf norms of the first gradient,
        "change": leaf norms of w_after - w0}, leaf norms as
        {key: array}, a stacked leaf giving one norm a layer. The
        cell's builder exports `tree_position(program name) -> (key,
        layer or None)` into that layout.
"""
import importlib

SERVING = ("make_weights", "positions", "served_token_gaps")
ARITHMETIC = ("param_count", "weight_bytes", "serve_flops",
              "kv_bytes_attended", "kv_bytes_per_token",
              "train_step_flops", "flash_attn_flops")
TRAINING = ("ADAMW", "train_three_steps")


def load(name, training=False):
    """The module `references/<name>.py`, held to the contract: a part
    it lacks is said here, at the start of a run, not by an
    AttributeError after the window."""
    try:
        ref = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise SystemExit(
            f"no reference {name!r}: a configuration's `reference` key "
            f"names a file benchmarks/references/{name}.py") from None
    lacks = [n for n in SERVING + ARITHMETIC if not hasattr(ref, n)]
    if lacks:
        raise SystemExit(f"reference {name!r} lacks {lacks} of the "
                         "contract (benchmarks/references/__init__.py)")
    lacks = [n for n in TRAINING if not hasattr(ref, n)]
    if training and lacks:
        raise SystemExit(
            f"reference {name!r} has no training part ({lacks} are "
            "optional in the contract): it cannot decide `correct` for "
            "a cell that trains")
    return ref
