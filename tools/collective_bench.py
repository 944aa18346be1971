"""Collective microbenchmark — all-reduce/all-gather/reduce-scatter
bus bandwidth over the framework mesh (BASELINE.md config 6; reference
counterpart: the NCCL ring benchmarks the reference's CI implies and
`paddle/fluid/operators/collective/` ops).

Run on real hardware:        python tools/collective_bench.py
Correctness run (CPU mesh):  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                             python tools/collective_bench.py --sizes 1,4

Bus bandwidth uses the standard ring-algorithm formulas (what NCCL
reports, so numbers are comparable):
  all_reduce:      busbw = 2*(n-1)/n * bytes / t
  all_gather:      busbw =   (n-1)/n * bytes / t   (bytes = full output)
  reduce_scatter:  busbw =   (n-1)/n * bytes / t   (bytes = full input)
Each op is ONE compiled XLA program over shard_map; timing excludes
compile (first call) and uses block_until_ready.
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,16,64,256",
                    help="comma-separated payload MB per device")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line per (op, size)")
    args = ap.parse_args()
    args.iters = max(1, args.iters)

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod

    n = len(jax.devices())
    if n < 2:
        print("1 device: no interconnect to measure — run on a multi-chip "
              "slice (or the 8-device virtual CPU mesh for correctness).")
        return []
    mesh_mod.init_mesh(dp=n)
    mesh = mesh_mod.global_mesh()
    print(f"devices: {n} × {jax.devices()[0].platform}", flush=True)

    def timed(fn, x):
        fn(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(x)
        out.block_until_ready()
        return (time.perf_counter() - t0) / args.iters

    results = []
    for mb in [float(s) for s in args.sizes.split(",")]:
        elems = int(mb * 1e6 / 4)
        # global array sharded over dp: each device owns `elems` floats
        x = jnp.zeros((n * elems,), jnp.float32)
        x = jax.device_put(x, mesh_mod.named_sharding("dp"))
        bytes_full = n * elems * 4

        def smap(fn, ins, outs):
            # all_gather output is replicated in VALUE but jax's
            # varying-axis check can't prove it — disable the check
            return jax.jit(shard_map(fn, mesh=mesh, in_specs=ins,
                                     out_specs=outs, check_vma=False))

        ar = smap(lambda v: jax.lax.psum(v, "dp"), P("dp"), P())
        ag = smap(lambda v: jax.lax.all_gather(v, "dp", tiled=True),
                  P("dp"), P())
        rs = smap(lambda v: jax.lax.psum_scatter(v, "dp", tiled=True),
                  P(None), P("dp"))

        xr = jax.device_put(jnp.zeros((n * elems,), jnp.float32),
                            mesh_mod.named_sharding(None))
        # S in each NCCL formula is the op's nominal buffer: all_reduce
        # reduces the per-device shard (elems — the '--sizes MB/dev'
        # payload); all_gather's S is the full OUTPUT and
        # reduce_scatter's the full INPUT (both n*elems).
        for name, fn, inp, factor, nbytes in (
                ("all_reduce", ar, x, 2 * (n - 1) / n, elems * 4),
                ("all_gather", ag, x, (n - 1) / n, bytes_full),
                ("reduce_scatter", rs, xr, (n - 1) / n, bytes_full)):
            t = timed(fn, inp)
            busbw = factor * nbytes / t / 1e9
            row = {"op": name, "mb_per_dev": mb, "ms": round(t * 1e3, 3),
                   "busbw_GBps": round(busbw, 2), "devices": n}
            results.append(row)
            if args.json:
                print(json.dumps(row), flush=True)
            else:
                print(f"{name:<16}{mb:>8.0f} MB/dev {t*1e3:>9.3f} ms "
                      f"{busbw:>9.2f} GB/s bus", flush=True)
    return results


if __name__ == "__main__":
    main()
