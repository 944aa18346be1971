"""On-chip MFU sweep for the flagship GPT train step.

Run on the real TPU (NOT under the CPU test env):

    python tools/mfu_sweep.py [--quick]

Sweeps, one dimension at a time around the bench configuration
(b16·s1024 GPT-small, amp O1, AdamW):

  * global batch (HBM util / pipeline depth),
  * fused-head CE block size (PERF_NOTES hypothesis 1),
  * remat policy dots_saveable (hypothesis 3),
  * flash-attention block_q/block_k (MXU tiling vs VMEM pressure,
    hypothesis 2; full sweep only),

printing a table of ms/step and MFU so the best point can be promoted
into bench.py. Each config runs in-process (one backend init); the
persistent compile cache keeps reruns cheap. One process holds the
chip while this runs; nothing else can use it until it exits.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(batch, seq, block_q, block_k, iters=8, fused_head=False,
            fused_block=4096, remat=False):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa
    from paddle_tpu.text.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_small)
    from bench import gpt_flops_per_step
    from paddle_tpu.device.peaks import running_device_peaks

    old_q, old_k = fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K
    fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = block_q, block_k
    try:
        paddle.seed(0)
        cfg = gpt_small()
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

        def loss_fn(m, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                if fused_head:
                    # head matmul + softmax-CE fused, [b,s,vocab] logits
                    # never hit HBM (PERF_NOTES hypothesis 1); block size
                    # trades logits-tile size vs dw-carry round-trips
                    return m.fused_head_loss(ids, block_size=fused_block)
                return crit(m(ids), ids)

        step = paddle.jit.TrainStep(model, loss_fn, opt, remat=remat)
        rng = np.random.default_rng(0)
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
        t0 = time.perf_counter()
        float(step(ids).numpy())
        compile_s = time.perf_counter() - t0
        for _ in range(2):
            step(ids)
        float(step(ids).numpy())
        t0 = time.perf_counter()
        for _ in range(iters):
            last = step(ids)
        float(last.numpy())
        dt = (time.perf_counter() - t0) / iters
        mfu = (gpt_flops_per_step(cfg, batch, seq) / dt
               / running_device_peaks()["bf16_flops"])
        return dt * 1e3, mfu, compile_s
    finally:
        fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K = old_q, old_k


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the 3x3 flash-block grid (runs batch + fusedce + remat arms)")
    ap.add_argument("--seq", type=int, default=1024,
                    help="sequence length for every arm (PERF_NOTES "
                         "hypothesis 2 re-sweeps flash tiles at s1024)")
    args = ap.parse_args()

    import jax

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    print(f"devices: {jax.devices()}", flush=True)

    seq = args.seq
    # config tuple: (kind, batch, seq, block_q, block_k, fused_block,
    # remat) — fused_block 0 = materialized-logits baseline
    configs = [("batch", b, seq, 512, 512, 0, False)
               for b in (8, 16, 24, 32)]
    # flash-tile RE-SWEEP at the bench seq (PERF_NOTES hypothesis 2):
    # the 512-tile winner was measured at s2048; at s1024 the kv loop
    # runs only 2 iterations per 512-q-tile, so 256 tiles may pipeline
    # better. Runs even under --quick (3 extra configs; the 512/512
    # baseline is the b16 batch arm above). Promote any winner into
    # flash_attention.py DEFAULT_BLOCK_* + docs/PERF_NOTES.md.
    configs += [("tile_rs", 16, seq, bq, bk, 0, False)
                for (bq, bk) in ((256, 256), (256, 512), (512, 256))]
    # fused-head arms: decide whether bench.py should flip
    # BENCH_GPT_FUSED_HEAD on by default, and at which block size
    # (small fb = small logits tiles but more dw-carry round-trips)
    configs += [("fusedce", 16, seq, 512, 512, fb, False)
                for fb in (2048, 4096, 8192)]
    # remat arm: 'dots_saveable' trades elementwise HBM writes for
    # recompute (PERF_NOTES hypothesis 3)
    configs += [("remat", 16, seq, 512, 512, 0, "dots_saveable")]
    if not args.quick:
        configs += [("fusedce", 24, seq, 512, 512, 4096, False)]
        configs += [("blocks", 16, seq, bq, bk, 0, False)
                    for bq in (256, 512, 1024)
                    for bk in (256, 512, 1024)
                    if (bq, bk) != (512, 512)]
    best = None
    print(f"{'kind':<8}{'batch':>6}{'bq':>6}{'bk':>6}{'fb':>6}{'ms':>10}"
          f"{'MFU':>8}{'compile_s':>10}")
    for kind, b, s, bq, bk, fb, remat in configs:
        try:
            ms, mfu, comp = measure(b, s, bq, bk, fused_head=fb > 0,
                                    fused_block=fb or 4096, remat=remat)
        except Exception as e:
            print(f"{kind:<8}{b:>6}{bq:>6}{bk:>6}{fb:>6}      FAIL  {e!r}",
                  flush=True)
            continue
        print(f"{kind:<8}{b:>6}{bq:>6}{bk:>6}{fb:>6}{ms:>10.1f}{mfu:>8.3f}"
              f"{comp:>10.1f}", flush=True)
        if best is None or mfu > best[0]:
            best = (mfu, kind, b, bq, bk, fb, ms)
    if best:
        mfu, kind, b, bq, bk, fb, ms = best
        print(f"\nBEST: {kind} batch={b} block_q={bq} block_k={bk} "
              f"fused_block={fb} -> {ms:.1f} ms, MFU {mfu:.3f}", flush=True)


if __name__ == "__main__":
    main()
