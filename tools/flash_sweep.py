"""Step 0 of a flash-kernel change: the kernels ALONE, on the chip.

    chiprun -- python tools/flash_sweep.py [--shapes a,b,c] [--out DIR]

bf16, best of 3 x 20 launches, forward and backward timed apart, for
the TILED kernels ([B*H, S, D], one (q tile, kv tile) a grid step) at
tiles 512 / 256 / 128 and the RESIDENT kernels ([B, S, H*D], a lane
block's whole sequence in VMEM) at interior tiles x diagonal cuts.
Each row gives the kernels alone (fwd, bwd) and the whole entry on
[B, S, H, D] operands (fwd + bwd with the layout changes the path
needs: `entry`), in ms and as % of the bf16 peak on the COUNTED FLOPs
(two products forward, four backward, over the live scores only).

Shapes: (a) b16 s1024 h16 d64 causal (gpt2-medium's training step);
(b) b4 s2048 h16 d128 causal (cerebras-gpt-1.3b's); (c) b16 s512 h12
d64 not causal with kv_lens (a padded BERT batch).

Fails where JAX finds no TPU; `--rehearse-cpu` runs tiny shapes through
the Pallas interpreter to debug the script (its times mean nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_BF16 = 197e12  # TPU v5e, Google Cloud documentation "TPU v5e"

SHAPES = {
    "a": dict(b=16, s=1024, h=16, d=64, causal=True, lens=False),
    "b": dict(b=4, s=2048, h=16, d=128, causal=True, lens=False),
    "c": dict(b=16, s=512, h=12, d=64, causal=False, lens=True),
}
TILED = (512, 256, 128)
RESIDENT = ((2048, 256), (1024, 512), (1024, 256), (1024, 128),
            (512, 256), (512, 128), (256, 256), (256, 128))


def _best(fn, args, reps=3, launches=20):
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


def sweep(shape_name, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    sh = dict(SHAPES[shape_name])
    if rehearse:
        sh.update(b=2, s=256, h=2)
    b, s, h, d, causal = sh["b"], sh["s"], sh["h"], sh["d"], sh["causal"]
    rng = np.random.default_rng(0)
    q, k, v, g = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                              jnp.bfloat16) for _ in range(4))
    lens = None
    live = b * s * s * (0.5 if causal else 1.0)
    if sh["lens"]:
        lens = jnp.asarray(rng.integers(s // 4, s + 1, size=b), jnp.int32)
        live = float(s * np.asarray(lens).sum())
    fwd_flops = 4 * live * h * d            # q.k^T and p.v
    counted = {"fwd": fwd_flops, "bwd": 2 * fwd_flops,
               "entry": 3 * fwd_flops}
    interp = rehearse

    def to_bhd(t):
        return jnp.swapaxes(t, 1, 2).reshape(b * h, s, d)

    def flat(t):
        return t.reshape(b, s, h * d)

    rows = []

    def row(path, tiles, fns, operands):
        r = {"shape": shape_name, "path": path, "tiles": list(tiles)}
        for name, fn in fns.items():
            try:
                ms = _best(jax.jit(fn), operands[name],
                           launches=2 if rehearse else 20)
                r[name + "_ms"] = ms
                r[name + "_pct_peak"] = (
                    100 * counted[name] / PEAK_BF16 / (ms * 1e-3))
            except Exception as e:  # noqa: BLE001 - a refusal is a result
                r[name + "_ms"] = None
                r[name + "_refused"] = " ".join(str(e).split())[:400]
        rows.append(r)
        print(json.dumps(r), flush=True)

    # ---- tiled: [B*H, S, D] operands, one (q tile, kv tile) a grid step
    qb, kb, vb, gb = (to_bhd(t) for t in (q, k, v, g))
    lens_bh = None if lens is None else jnp.repeat(lens, h)
    for t in TILED:
        if t > s:
            continue

        def t_fwd(q_, k_, v_, t=t):
            return fa._fa_forward(q_, k_, v_, causal, t, t, interp,
                                  lens=lens_bh)

        ob, lb = jax.jit(t_fwd)(qb, kb, vb)

        def t_bwd(q_, k_, v_, o_, l_, g_, t=t):
            return fa._attn_bwd_pallas(q_, k_, v_, o_, l_, g_, causal, t, t,
                                       interp, lens=lens_bh)

        def t_entry(q_, k_, v_, g_, t=t):
            def f(q3, k3, v3):
                out = fa._flash_attention_bhd(
                    to_bhd(q3), to_bhd(k3), to_bhd(v3), lens_bh, causal, t,
                    t, interp)
                return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)
            _o, vjp = jax.vjp(f, q_, k_, v_)
            return vjp(g_)

        row("tiled", (t, t),
            {"fwd": t_fwd, "bwd": t_bwd, "entry": t_entry},
            {"fwd": (qb, kb, vb), "bwd": (qb, kb, vb, ob, lb, gb),
             "entry": (q, k, v, g)})

    # ---- resident: [B, S, H*D] operands, a lane block's sequence in VMEM
    qf, kf, vf, gf = (flat(t) for t in (q, k, v, g))
    for tile, cut in RESIDENT:
        if s % tile:
            continue

        def r_fwd(q_, k_, v_, tile=tile, cut=cut):
            return fa._resident_pass(False, lens, (q_, k_, v_), causal, d,
                                     tile, cut, interp)

        try:
            of, lf = jax.jit(r_fwd)(qf, kf, vf)
        except Exception as e:  # noqa: BLE001
            rows.append({"shape": shape_name, "path": "resident",
                         "tiles": [tile, cut],
                         "fwd_refused": " ".join(str(e).split())[:400]})
            print(json.dumps(rows[-1]), flush=True)
            continue

        def r_bwd(q_, k_, v_, o_, l_, g_, tile=tile, cut=cut):
            return fa._resident_pass(True, lens, (q_, k_, v_, o_, g_, l_),
                                     causal, d, tile, cut, interp)

        def r_entry(q_, k_, v_, g_, tile=tile, cut=cut):
            def f(q4, k4, v4):
                return fa._flash_attention_resident(
                    flat(q4), flat(k4), flat(v4), lens, causal, d, tile,
                    cut, interp).reshape(b, s, h, d)
            _o, vjp = jax.vjp(f, q_, k_, v_)
            return vjp(g_)

        row("resident", (tile, cut),
            {"fwd": r_fwd, "bwd": r_bwd, "entry": r_entry},
            {"fwd": (qf, kf, vf), "bwd": (qf, kf, vf, of, lf, gf),
             "entry": (q, k, v, g)})

    # the two paths agree on the chip (bf16: a few 1e-2)
    def both(q_, k_, v_, g_):
        outs = []
        for blocks in ((fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K), (256, 256)):
            o, vjp = jax.vjp(lambda a, b_, c: fa.flash_attention_bshd(
                a, b_, c, causal=causal, kv_lens=lens, block_q=blocks[0],
                block_k=blocks[1], interpret=interp), q_, k_, v_)
            outs.append((o,) + vjp(g_))
        return [jnp.max(jnp.abs(x.astype(jnp.float32)
                                - y.astype(jnp.float32)))
                for x, y in zip(*outs)]

    diffs = [float(x) for x in jax.jit(both)(q, k, v, g)]
    print(json.dumps({"shape": shape_name, "resident_vs_tiled_maxdiff":
                      dict(zip(("out", "dq", "dk", "dv"), diffs))}),
          flush=True)
    return rows, diffs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="a,b,c")
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse_cpu:
        sys.exit(f"flash_sweep needs a TPU, found {dev.platform}")
    report = {"device": dev.device_kind, "platform": dev.platform,
              "rows": [], "maxdiff": {}}
    for name in args.shapes.split(","):
        rows, diffs = sweep(name, args.rehearse_cpu)
        report["rows"] += rows
        report["maxdiff"][name] = diffs
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "flash_sweep.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
