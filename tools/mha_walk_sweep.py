"""The head-major paged walk at ONE query head a K/V head, ALONE on the chip.

    python tools/mha_walk_sweep.py [--out DIR] [--variants 16:1024,...]

run on a machine with one TPU v5e.

olmo-hybrid-7b's full layers: 30 heads x 128, bf16 pools `[pages, 30, 16,
128]` of 4 GiB, 128 slots of 2 048 positions; best of 3 x 10 launches.
Each launch is timed with both bodies the walk has for these shapes: `gqa`
(`_gqa_walk_kernel`: a head's one query row padded to a sublane tile, the
heads one after the other) and `mha` (`_mha_walk_kernel`: blocks of heads
as one pair of products a span of pages), the latter at each VARIANT
`heads:score_kib` (`_MHA_HEADS`, `_MHA_SCORE_BYTES` / 1 024).
Two tables:

  window   128 rows, one a slot (the fused window's launch), contexts
           prompt + a seeded share of the output over the cell's grid
           (prompts 70-431, outputs 279-939)
  tick     one slot's 385 prompt rows from position 0 beside 127 decoding
           rows at such contexts, laid out in blocks of 8 rows of one slot
           (`SlotBlockLayout`: 1 408 rows, the tick's launch)

A row prints ms a launch, us a (slot, layer) and % of 819 GB/s on the K/V
bytes a slot's rows must read (a slot's context once), and the largest
difference from the `gqa` body's output.

Fails where JAX finds no TPU; `--rehearse-cpu` runs tiny shapes through
the Pallas interpreter to debug the script (its times mean nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM = 819e9       # TPU v5e, bytes/s


def _best(fn, args, reps=3, launches=10):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(launches):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / launches)
    return best * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--tables", default="window,tick")
    ap.add_argument("--variants", default="16:1024",
                    help="heads:score_kib of the mha body, "
                    "comma-separated")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional.attention import SlotBlockLayout
    from paddle_tpu.ops.pallas_kernels import paged_attention as pak

    rehearse = args.rehearse_cpu
    if not rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: nothing is measured on anything else")
    H, D, P = (3, 128, 16) if rehearse else (30, 128, 16)
    S, MLEN = (4, 256) if rehearse else (128, 2048)
    prompts = (8, 40) if rehearse else (70, 431)
    outputs = (16, 80) if rehearse else (279, 939)
    chunk = 24 if rehearse else 385
    MP = MLEN // P
    N = S * MP + 1
    dt = jnp.bfloat16
    rng = np.random.default_rng(0)
    # the pools made on the device: 2 x 2.1 GB of normals
    kk, kv = jax.random.split(jax.random.key(0))
    kp = jax.random.normal(kk, (N, H, P, D), dt)
    vp = jax.random.normal(kv, (N, H, P, D), dt)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(S, MP), jnp.int32)
    out = {"device": jax.devices()[0].device_kind, "rows": []}

    def note(row):
        out["rows"].append(row)
        print(json.dumps(row), flush=True)

    def contexts(n):
        """Decoding contexts over the cell's 8 x 8 grid of (prompt,
        output): a prompt and a seeded share of its output."""
        p = np.linspace(*prompts, 8).round()[rng.integers(0, 8, n)]
        o = np.linspace(*outputs, 8).round()[rng.integers(0, 8, n)]
        return (p + rng.uniform(0, 1, n) * o).astype(np.int32) + 1

    def launch(body, qb, sids, lens):
        """The walk through one body, on its operands as ARGUMENTS (a
        closed-over pool is a constant of the executable)."""
        make = pak._gqa_paged_call if body == "gqa" else pak._mha_paged_call

        def fn(kp, vp, tables, q, sids, lens):
            lay = None
            if qb > 1:
                lay = SlotBlockLayout(sids, lens, qb, S)
                q, sids, lens = lay.spread(q), lay.sids, lay.lens
            call = make(q.shape, q.dtype, kp.shape, kp.dtype, MP, qb,
                        rehearse)
            o = call(sids, tables.reshape(-1), lens,
                     jnp.full(lens.shape, -(2 ** 30), jnp.int32),
                     jnp.zeros((1,), jnp.int32), q, kp, vp)
            return o if lay is None else o[lay.dest]

        jitted = jax.jit(fn)
        return lambda q: jitted(kp, vp, tables, q, sids, lens)

    variants = [tuple(int(x) for x in v.split(":"))
                for v in args.variants.split(",")]

    def table(name, qb, sids, lens, slot_ctx):
        q = jnp.asarray(rng.normal(size=(len(sids), H, D)), dt)
        kv_bytes = float(np.sum(slot_ctx)) * H * D * 2 * 2
        want = None
        for body, heads, kib in [("gqa", 0, 0)] + [
                ("mha", *v) for v in variants]:
            if body == "mha":
                pak._MHA_HEADS, pak._MHA_SCORE_BYTES = heads, kib * 1024
                pak._mha_paged_call.cache_clear()
            row = {"table": name, "body": body, "rows": int(len(sids)),
                   "slots": int(len(slot_ctx)),
                   "mean_ctx": float(np.mean(slot_ctx))}
            if body == "mha":
                row.update(heads=heads, score_kib=kib)
            try:
                fn = launch(body, qb, jnp.asarray(sids, jnp.int32),
                            jnp.asarray(lens, jnp.int32))
                ms = _best(fn, (q,))
                got = np.asarray(fn(q), np.float32)
            except Exception as e:  # noqa: BLE001 - a sweep's row
                note({**row, "error": str(e)[:400]})
                continue
            if want is None:
                want = got
            note({**row, "ms": ms,
                  "us_per_slot_layer": 1e3 * ms / len(slot_ctx),
                  "pct_hbm": 100 * kv_bytes / (ms / 1e3) / HBM,
                  "max_diff_from_gqa": float(np.max(np.abs(got - want)))})

    asked = args.tables.split(",")
    if "window" in asked:
        ctx = contexts(S)
        table("window", 1, np.arange(S), ctx, ctx)
    if "tick" in asked:
        dec = contexts(S - 1)
        sids = np.concatenate([np.full(chunk, S - 1), np.arange(S - 1)])
        lens = np.concatenate([1 + np.arange(chunk), dec])
        table("tick", 8, sids, lens, np.concatenate([[chunk], dec]))

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "mha_walk_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
