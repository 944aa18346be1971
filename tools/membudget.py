"""HBM budget analysis WITHOUT hardware: lower+compile a train step on
the CPU backend (no execution) and print XLA's buffer assignment.

    JAX_PLATFORMS=cpu python tools/membudget.py --model gpt-small
    JAX_PLATFORMS=cpu python tools/membudget.py --model gpt-1.3b [--o1]

argument_size ≈ resident state (params + optimizer moments + batch):
the half of the fit question CPU analysis answers exactly (same
dtypes/shapes as TPU). temp_size is CPU-only and OVERSTATES the TPU
figure — the CPU graph uses the dense-attention fallback and ignores
remat hints (docs/PERF_NOTES.md records both effects). Measured
reference points: GPT-1.3B O2 resident = 13.16 GB (fits v5e 16 GB);
O1 would be ~15.6 GB before activations.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt-1.3b",
                    choices=["gpt-small", "gpt-1.3b"])
    ap.add_argument("--o1", action="store_true",
                    help="fp32 params (default: O2 bf16)")
    ap.add_argument("--no-recompute", action="store_true")
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    # hardware-free by definition (shapes and dtypes are the same on the
    # CPU, and the chip stays free for whoever is measuring on it)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_1p3b, gpt_small

    if args.model == "gpt-1.3b":
        cfg = gpt_1p3b(recompute=not args.no_recompute)
        batch, seq = args.batch or 1, 2048
    else:
        cfg = gpt_small(recompute=not args.no_recompute)
        batch, seq = args.batch or 16, 1024
    level = "O1" if args.o1 else "O2"

    t0 = time.time()
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if level == "O2":
        model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids):
        with amp.auto_cast(level=level, dtype="bfloat16"):
            return m.fused_head_loss(ids, block_size=2048)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    ids = paddle.to_tensor(np.zeros((batch, seq), np.int32))
    print(f"[membudget] built {args.model} {level} b{batch}·s{seq} "
          f"recompute={cfg.recompute} in {time.time()-t0:.0f}s; "
          f"lower+compile (no execution)...", flush=True)

    t0 = time.time()
    c = step.lower(ids).compile()
    ma = c.memory_analysis()
    print(f"[membudget] compiled in {time.time()-t0:.0f}s")
    print(f"resident (args) = {ma.argument_size_in_bytes/1e9:.2f} GB "
          f"(params+moments+batch; exact for TPU)")
    print(f"temp            = {ma.temp_size_in_bytes/1e9:.2f} GB "
          f"(CPU-only figure: dense-attention fallback, remat unbound — "
          f"OVERSTATES TPU)")
    print(f"outputs alias donated args: {ma.alias_size_in_bytes/1e9:.2f} GB")
    fit = ma.argument_size_in_bytes / 1e9
    print(f"verdict: resident {fit:.2f} GB vs v5e HBM 16 GB -> "
          f"{'FITS (activation headroom %.2f GB)' % (16 - fit) if fit < 16 else 'DOES NOT FIT'}")


if __name__ == "__main__":
    main()
