"""Step 0 of the gated delta rule (KDA) and of the expert products at
Ling-3.0-flash-VL's shapes: the pieces ALONE, on the chip.

    chiprun -- python tools/kda_sweep.py [--tables recur,chunk,gmm,cross]

H 32, d_k = d_v 128, float32 state; best of 3 x 10 launches. Tables:

  recur  the RECURRENT step for 32 / 64 / 96 / 128 rows (a row a slot, all
         live): plain XLA against the Pallas kernel of one read and one
         write of the state: ms, and % of 819 GB/s on 2 x 2.0 MiB a row
  chunk  the CHUNKED form on ONE run of 512 / 1 024 / 2 048 rows of a
         2 048-row tick, chunk 32 / 64 / 128, plain XLA against the Pallas
         kernel, both from the tick's FLAT rows (what the model hands
         them: the plain form's layout is inside its number, the kernel
         reads the rows where they lie), and the kernel on a run that
         starts at flat row 37: ms a layer, % of 197 TFLOP/s on the
         reference's count at chunk 64
  gmm    the held experts' two grouped products (128 experts, d 2560,
         width 768) at 24 / 192 / 512 / 4 096 assignments by tiles (k, n):
         ms, % of 819 GB/s on the weights of the experts touched
  cross  a run of n rows: recurrent-in-a-loop against chunked, in a
         2 048-row tick of 96 slots

Fails where JAX finds no TPU; `--rehearse-cpu` runs tiny shapes with the
kernel interpreted to debug the script (its times mean nothing).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM = 819e9       # TPU v5e, bytes/s
PEAK_BF16 = 197e12


def _best(fn, args, reps=3, launches=10, carry=None):
    """Best ms a launch; `carry`: the index of the argument that the
    result's LAST element replaces (a donated state)."""
    import jax

    args = list(args)

    def run():
        out = fn(*args)
        if carry is not None:
            args[carry] = out[-1]
        return out

    jax.block_until_ready(run())  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(launches):
            out = run()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / launches)
    return best * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--tables", default="recur,chunk,gmm,cross")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn import expert_layer
    from paddle_tpu.nn.functional import delta_rule as dr
    from paddle_tpu.nn.functional.attention import SlotRunLayout

    rehearse = args.rehearse_cpu
    if not rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: nothing is measured on anything else")
    if rehearse:
        import paddle_tpu.ops.pallas_kernels.delta_rule as pk
        import functools

        pk.delta_rule_recurrent = functools.partial(
            pk.delta_rule_recurrent, interpret=True)
        pk.delta_rule_chunks = functools.partial(
            pk.delta_rule_chunks, interpret=True)
    H, dk = (2, 128) if rehearse else (32, 128)
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    out = {"device": jax.devices()[0].device_kind, "rows": []}
    tables = args.tables.split(",")

    def note(row):
        out["rows"].append(row)
        print(json.dumps(row), flush=True)

    def rows_of(n):
        nrm = lambda *s: jnp.asarray(rng.normal(size=s), f32)   # noqa: E731
        q = nrm(n, H, dk) * dk ** -0.5
        k = nrm(n, H, dk)
        k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
        g = -5.0 * jax.nn.sigmoid(2.0 * nrm(n, H, dk))
        return q, k, nrm(n, H, dk), g, jax.nn.sigmoid(nrm(n, H))

    state_bytes = H * dk * dk * 4

    if "recur" in tables:
        for S in ((3, 4) if rehearse else (32, 64, 96, 128)):
            q, k, v, g, beta = rows_of(S)
            live = jnp.ones((S,), bool)
            fresh = jnp.zeros((S,), bool)
            for kernel in (False, True):
                fn = jax.jit(lambda st, *a, kernel=kernel: dr.delta_rule_step(
                    st, *a, kernel=kernel), donate_argnums=(0,))
                ms = _best(fn, (jnp.zeros((S, H, dk, dk), f32), q, k, v, g,
                                beta, live, fresh), carry=0)
                note({"table": "recur", "rows": S,
                      "form": "pallas" if kernel else "xla", "ms": ms,
                      "hbm_share": 2 * state_bytes * S / (ms / 1e3) / HBM})

    def tick_rows(T, S, n, pos0=0, at=0):
        """A tick of T rows: slot 1 has a run of n rows from pos0, its
        first at flat row `at`."""
        sids = np.zeros((T,), np.int32)
        lens = np.zeros((T,), np.int32)
        sids[at:at + n] = 1
        lens[at:at + n] = pos0 + 1 + np.arange(n)
        return jnp.asarray(sids), jnp.asarray(lens)

    T, S = (128, 3) if rehearse else (2048, 96)
    if "chunk" in tables:
        q, k, v, g, beta = rows_of(T)
        kernels = [(C, True) for C in ((32,) if rehearse else (32, 64, 128))]
        plain = [(C, False) for C, _ in kernels]
        for n, at in ((96, 0), (75, 37)) if rehearse else (
                (512, 0), (1024, 0), (2048, 0), (1024, 37)):
            sids, lens = tick_rows(T, S, n, 7, at)
            # the plain form's time does not depend on where the run lies
            for C, kernel in kernels if at else plain + kernels:
                def fn(st, q, k, v, g, beta, sids, lens, C=C, kernel=kernel):
                    runs = SlotRunLayout(sids, lens, 64, C, 0)
                    o, st, _ = dr.delta_rule_chunked(
                        st, q, k, v, g, beta, runs, chunk=C, kernel=kernel)
                    return o, st
                ms = _best(jax.jit(fn, donate_argnums=(0,)), (
                    jnp.zeros((S, H, dk, dk), f32), q, k, v, g, beta, sids,
                    lens), carry=0)
                flops = n * H * (2 * 64 * dk + 64 * 2 * dk + 4 * dk * dk
                                 + 64 * dk + 2 * dk * dk)
                note({"table": "chunk", "run_rows": n, "first_row": at,
                      "chunk": C,
                      "form": "pallas" if kernel else "xla", "ms": ms,
                      "peak_share": flops / (ms / 1e3) / PEAK_BF16})

    if "cross" in tables:
        q, k, v, g, beta = rows_of(T)
        for n in ((8, 64) if rehearse else (4, 8, 16, 32, 64, 128, 256)):
            sids, lens = tick_rows(T, S, n, 7)

            def chunked(st, q, k, v, g, beta, sids, lens):
                runs = SlotRunLayout(sids, lens, min(n, 64), dr.CHUNK, 0)
                o, st, _ = dr.delta_rule_chunked(st, q, k, v, g, beta, runs)
                return o, st

            def looped(st, q, k, v, g, beta, sids, lens):
                live = jnp.arange(S) == 1

                def one(i, carry):
                    o, st = carry
                    rows = jnp.full((S,), i, jnp.int32)
                    got, st = dr.delta_rule_step(
                        st, q[rows], k[rows], v[rows], g[rows], beta[rows],
                        live, jnp.zeros((S,), bool))
                    return o.at[jnp.where(live, rows, T)].set(
                        got, mode="drop"), st

                return jax.lax.fori_loop(0, n, one, (jnp.zeros_like(v), st))

            for name, fn in (("chunked", chunked), ("recurrent_loop", looped)):
                ms = _best(jax.jit(fn, donate_argnums=(0,)), (
                    jnp.zeros((S, H, dk, dk), f32), q, k, v, g, beta, sids,
                    lens), carry=0)
                note({"table": "cross", "run_rows": n, "form": name,
                      "ms": ms})

    def gmm_at(x, w, sizes, tiling):
        """`expert_layer.grouped_matmul`, or the same kernel at `tiling`."""
        if tiling is None or rehearse:
            return expert_layer.grouped_matmul(x, w, sizes)
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(x, w, sizes, preferred_element_type=x.dtype,
                   tiling=tiling)

    if "gmm" in tables:
        E, d, m = (8, 256, 128) if rehearse else (128, 2560, 768)
        dt = jnp.float32 if rehearse else jnp.bfloat16
        w1 = jnp.asarray(rng.normal(size=(E, d, 2 * m)) * 0.02, dt)
        w2 = jnp.asarray(rng.normal(size=(E, m, d)) * 0.02, dt)
        tiles1 = [None] if rehearse else [
            None, (128, 2560, 512), (128, 2560, 768), (128, 2560, 1536),
            (128, 1280, 1536), (128, 1280, 768), (128, 640, 1536)]
        tiles2 = [None] if rehearse else [
            None, (128, 768, 512), (128, 768, 1280), (128, 768, 2560),
            (128, 384, 2560)]
        for M in ((24,) if rehearse else (24, 192, 512, 4096)):
            ids = np.sort(rng.integers(0, E, (M,)))
            sizes = jnp.asarray(np.bincount(ids, minlength=E), jnp.int32)
            touched = int((np.bincount(ids, minlength=E) > 0).sum())
            rows = -(-M // 128) * 128
            x1 = jnp.asarray(rng.normal(size=(rows, d)), dt)
            x2 = jnp.asarray(rng.normal(size=(rows, m)), dt)
            for which, x, w, tiles, per in (
                    ("gate_up", x1, w1, tiles1, d * 2 * m),
                    ("down", x2, w2, tiles2, m * d)):
                for tiling in tiles:
                    fn = jax.jit(lambda x, w, s, tiling=tiling: gmm_at(
                        x, w, s, tiling))
                    try:
                        ms = _best(fn, (x, w, sizes))
                    except Exception as e:  # noqa: BLE001 - a refused tile
                        note({"table": "gmm", "assignments": M,
                              "product": which, "tiling": tiling,
                              "refused": repr(e)[:200]})
                        continue
                    note({"table": "gmm", "assignments": M, "product": which,
                          "touched": touched, "tiling": tiling, "ms": ms,
                          "hbm_share": touched * per * 2 / (ms / 1e3) / HBM})

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "kda_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
