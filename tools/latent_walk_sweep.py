"""Step 0 of the latent (MLA) paged walk: the kernel ALONE, on the chip.

    chiprun -- python tools/latent_walk_sweep.py [--out DIR]

bf16, 64 heads, rows of 576 stored as 640 lanes, pages of 16, best of
3 x 10 launches. Three tables:

  decode   32 rows, one a slot (the fused window's launch), contexts
           10 k / 13 k / 16 k, by tokens a DMA group: ms a launch, µs a
           live page, % of 819 GB/s on the 1 152 B a token the rows hold
  tick     992 rows of ONE slot from context c0 beside 31 decoding rows
           at 13 k (the single tick's launch through `SlotBlockLayout`),
           by rows a query block and tokens a group: ms a launch, % of
           197 TFLOP/s on the absorbed FLOPs
  expand   the same tick's prompt rows in the EXPANDED form in plain XLA
           (gather the slot's latent rows, up-project k_nope and v, a
           causal product a block of queries): ms and temporaries, at
           token budgets 512 / 1 024 / 2 048
  expanded the EXPANDED form as a kernel that up-projects in VMEM
           (`latent_expanded_attention`): one slot's 2 016 rows ending
           at 13 312 and 992 rows from c0 = 0 / 8 192 / 15 360, by rows a
           sub-block, tokens a tile and heads a grid step: ms a layer,
           % of 197 TFLOP/s on the expanded FLOPs + the expansion, the
           largest gap to the absorbed walk's numbers; then short runs
           from 8 192 in both forms (where the forms cross), and what
           the absorbed launch costs when every row of it is dead

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
PEAK_BF16 = 197e12


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
    ap.add_argument("--tables", default="decode,tick,expand,expanded")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional.attention import SlotBlockLayout
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        latent_expanded_attention, latent_paged_attention)

    rehearse = args.rehearse_cpu
    if not rehearse and jax.default_backend() != "tpu":
        raise SystemExit("no TPU: nothing is measured on anything else")
    H, R, RS, V, P = (4, 40, 128, 32, 16) if rehearse else \
        (64, 576, 640, 512, 16)
    S, MLEN = (4, 256) if rehearse else (32, 16384)
    MP = MLEN // P
    N = S * MP + 1
    dt = jnp.float32 if rehearse else jnp.bfloat16
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(N, P, RS)) * 0.3, dt)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N)).reshape(S, MP), jnp.int32)
    scale = 0.1
    out = {"device": jax.devices()[0].device_kind, "rows": []}

    def note(row):
        out["rows"].append(row)
        print(json.dumps(row), flush=True)

    # the pool and the tables are ARGUMENTS of every jitted function: a
    # closed-over 0.7 GB pool is a constant of the executable (1.5 GB
    # to compile and cache, minutes a shape: PERF.md §6, PR 33)
    def walk(qb, group_tokens):
        def fn(pool, tables, q, sids, lens):
            return latent_paged_attention(
                q, pool, tables, sids, lens, V, scale, q_per_slot=qb,
                group_tokens=group_tokens, interpret=rehearse)
        jitted = jax.jit(fn)
        return lambda q, sids, lens: jitted(pool, tables, q, sids, lens)

    contexts = (60, 200, 250) if rehearse else (10240, 13312, 16384)
    tables_asked = args.tables.split(",")
    if "decode" in tables_asked:
        q = jnp.asarray(rng.normal(size=(S, H, RS)), dt)
        sids = jnp.arange(S, dtype=jnp.int32)
        for gt in ((32, 64) if rehearse else (256, 512, 1024, 2048, 4096)):
            for ctx in contexts:
                lens = jnp.full((S,), ctx, jnp.int32)
                try:
                    ms = _best(walk(1, gt), (q, sids, lens))
                except Exception as e:  # noqa: BLE001 - a sweep's row
                    note({"table": "decode", "group_tokens": gt,
                          "ctx": ctx, "error": str(e)[:300]})
                    continue
                pages = S * -(-ctx // P)
                note({"table": "decode", "group_tokens": gt, "ctx": ctx,
                      "ms": ms, "us_per_live_page": 1e3 * ms / pages,
                      "pct_hbm_1152": 100 * S * ctx * R * 2 / (ms / 1e3)
                      / HBM})

    T = 64 if rehearse else 1024
    chunk = T - (S - 1)

    def tick_rows(c0):
        """31 decoding rows at 13 k and one slot's `chunk` rows from
        context c0, the engine's tick: live rows first, a slot's side
        by side."""
        dec = contexts[1]
        sids = np.concatenate([np.arange(S - 1), np.full(chunk, S - 1)])
        lens = np.concatenate([np.full(S - 1, dec),
                               c0 + 1 + np.arange(chunk)])
        return (jnp.asarray(sids, jnp.int32), jnp.asarray(lens, jnp.int32))

    starts = (0, 100) if rehearse else (0, 8192, 15360)
    cache = {}

    def jitted(fn, *key):
        """One jitted function a static configuration (the start of the
        chunk is data)."""
        if key not in cache:
            cache[key] = jax.jit(fn)
        return cache[key]
    if "tick" in tables_asked:
        q = jnp.asarray(rng.normal(size=(T, H, RS)), dt)
        for qb in ((4,) if rehearse else (8, 16, 32)):
            for gt in ((32,) if rehearse else (1024, 2048)):
                for c0 in starts:
                    sids, lens = tick_rows(c0)

                    def fn(pool, tables, q, sids, lens, qb=qb, gt=gt):
                        lay = SlotBlockLayout(sids, lens, qb, S)
                        o = latent_paged_attention(
                            lay.spread(q), pool, tables, lay.sids,
                            lay.lens, V, scale, q_per_slot=qb,
                            group_tokens=gt, interpret=rehearse)
                        return o[lay.dest]
                    try:
                        ms = _best(jitted(fn, qb, gt),
                                   (pool, tables, q, sids, lens))
                    except Exception as e:  # noqa: BLE001
                        note({"table": "tick", "qb": qb,
                              "group_tokens": gt, "c0": c0,
                              "error": str(e)[:300]})
                        continue
                    attended = float(np.asarray(lens).sum())
                    flops = 2 * H * (2 * V + (R - V)) * attended
                    note({"table": "tick", "qb": qb, "group_tokens": gt,
                          "c0": c0, "ms": ms,
                          "pct_peak_absorbed": 100 * flops / (ms / 1e3)
                          / PEAK_BF16})

    nope, vd = (16, 16) if rehearse else (128, 128)
    rope = R - V
    w_uk = jnp.asarray(rng.normal(size=(H, nope, V)) * 0.02, dt)
    w_uv = jnp.asarray(rng.normal(size=(H, V, vd)) * 0.02, dt)
    if "expand" in tables_asked:

        def expanded(pool, tables, qn, qr, c0, slot, qblock):
            """One slot's `rows` prompt rows from context c0: gather
            its latent rows (all MLEN positions: static), up-project,
            a causal product a block of queries."""
            rows = qn.shape[0]
            l_idx = jnp.arange(MLEN, dtype=jnp.int32)
            phys = tables[slot, l_idx // P] * P + l_idx % P
            lat = pool.reshape(N * P, RS)[phys]            # [MLEN, RS]
            k_nope = jnp.matmul(lat[None, :, :V], jnp.swapaxes(w_uk, 1, 2),
                                preferred_element_type=jnp.float32
                                ).astype(dt)               # [H, L, nope]
            v = jnp.matmul(lat[None, :, :V], w_uv,
                           preferred_element_type=jnp.float32).astype(dt)
            kr = lat[:, V:R]

            def block(args):
                qnb, qrb, pos = args      # [H, B, nope], [H, B, rope], [B]
                sc = jnp.matmul(qnb, jnp.swapaxes(k_nope, 1, 2),
                                preferred_element_type=jnp.float32) \
                    + jnp.matmul(qrb, kr.T[None],
                                 preferred_element_type=jnp.float32)
                sc = jnp.where(l_idx[None, None, :] <= pos[None, :, None],
                               sc * scale, -1e30)
                p = jax.nn.softmax(sc, axis=-1).astype(dt)
                return jnp.matmul(p, v, preferred_element_type=jnp.float32)

            nb = rows // qblock
            pos = c0 + jnp.arange(rows, dtype=jnp.int32)
            o = jax.lax.map(block, (
                jnp.swapaxes(qn, 0, 1).reshape(H, nb, qblock, nope)
                .swapaxes(0, 1),
                jnp.swapaxes(qr, 0, 1).reshape(H, nb, qblock, rope)
                .swapaxes(0, 1),
                pos.reshape(nb, qblock)))
            return o

        for budget in ((32,) if rehearse else (512, 1024, 2048)):
            rows = budget - (S - 1) if rehearse else budget - 32
            qblock = 1 if rehearse else 32     # divides 480, 992, 2016
            qn = jnp.asarray(rng.normal(size=(rows, H, nope)), dt)
            qr = jnp.asarray(rng.normal(size=(rows, H, rope)), dt)
            c0 = jnp.int32(contexts[1] - rows)
            fn = jax.jit(lambda pool, tables, qn, qr, c0, qblock=qblock:
                         expanded(pool, tables, qn, qr, c0, S - 1, qblock))
            try:
                compiled = fn.lower(pool, tables, qn, qr, c0).compile()
                temp = compiled.memory_analysis().temp_size_in_bytes
                ms = _best(fn, (pool, tables, qn, qr, c0))
            except Exception as e:  # noqa: BLE001
                note({"table": "expand", "budget": budget,
                      "error": str(e)[:300]})
                continue
            note({"table": "expand", "budget": budget, "rows": rows,
                  "qblock": qblock, "ctx_static": MLEN, "ms": ms,
                  "temp_bytes": int(temp)})
            # the absorbed walk over the same rows alone, qb 8 and 16
            for qb in ((4,) if rehearse else (8, 16)):
                q = jnp.asarray(rng.normal(size=(rows, H, RS)), dt)
                sids = jnp.full((rows,), S - 1, jnp.int32)
                lens = c0 + 1 + jnp.arange(rows, dtype=jnp.int32)
                try:
                    ms = _best(walk(qb, 32 if rehearse else 2048),
                               (q[:rows - rows % qb], sids[:rows - rows % qb],
                                lens[:rows - rows % qb]))
                except Exception as e:  # noqa: BLE001
                    note({"table": "expand", "budget": budget, "qb": qb,
                          "error": str(e)[:300]})
                    continue
                note({"table": "expand", "budget": budget, "rows": rows,
                      "absorbed_walk_qb": qb, "ms": ms})

    if "expanded" in tables_asked:
        total = 64 if rehearse else 2048 + 512   # a sub-block to spare
        max_runs = 4
        qd = nope + RS - V
        qx = rng.normal(size=(total, H, nope + rope))
        qx = jnp.asarray(np.concatenate(
            [qx, np.zeros((total, H, qd - nope - rope))], -1), dt)

        def one_run(first, n):
            z = np.zeros(max_runs, np.int32)
            return tuple(jnp.asarray(np.concatenate([[v], z[1:]]), jnp.int32)
                         for v in (S - 1, 0, first, n))

        def expanded(sub, tile, hh):
            def fn(pool, tables, q, wk, wv, slots, row0, first, rows):
                return latent_expanded_attention(
                    q, pool, wk, wv, tables, slots, row0, first, rows,
                    scale, sub_rows=sub, tile_tokens=tile,
                    heads_per_step=hh, interpret=rehearse)
            return jitted(fn, "expanded", sub, tile, hh)

        def absorbed_rows(n, first, qb, gt):
            """The absorbed walk + the up-projection of its result over
            the run's rows: what the expanded kernel must equal."""
            def fn(pool, tables, q, wk, wv, first):
                qa = jnp.einsum("thn,hnc->thc", q[:, :, :nope], wk,
                                preferred_element_type=jnp.float32)
                qa = jnp.concatenate(
                    [qa.astype(dt), q[:, :, nope:]], -1)    # [n, H, RS]
                lens = first + jnp.arange(q.shape[0], dtype=jnp.int32)
                o = latent_paged_attention(
                    qa, pool, tables, jnp.full((q.shape[0],), S - 1), lens,
                    V, scale, q_per_slot=qb, group_tokens=gt,
                    interpret=rehearse)
                return jnp.einsum("thc,hcv->thv", o, wv,
                                  preferred_element_type=jnp.float32)
            return jitted(fn, "absorbed_rows", n, qb, gt)(
                pool, tables, qx[:n], w_uk, w_uv, jnp.int32(first))

        def flops(first, n):
            attended = n * first + n * (n - 1) / 2
            return (2 * H * (nope + rope + vd) * attended
                    + 2 * H * V * (nope + vd) * (first + n - 1))

        cases = ([(40, 100), (24, 0)] if rehearse else
                 [(2016, 13312 - 2016), (992, 0), (992, 8192), (992, 15360)])
        grid = ([(8, 32, 2), (16, 16, 4)] if rehearse else
                [(sub, tile, hh) for sub in (256, 512, 1024)
                 for tile in (512, 1024, 2048) for hh in (2, 4)])
        # two dimensions, as the kernel takes them (on the device a
        # reshape of `[total, H, ·]` is a copy, and would be timed)
        q2 = qx.reshape(total, -1)
        want = {}
        for sub, tile, hh in grid:
            for n, c0 in cases:
                run = one_run(c0 + 1, n)
                row = {"table": "expanded", "sub": sub, "tile": tile,
                       "heads": hh, "rows": n, "c0": c0}
                try:
                    launch = expanded(sub, tile, hh)
                    ops = (pool, tables, q2, w_uk, w_uv, *run)
                    ms = _best(launch, ops)
                    if (n, c0) not in want:
                        qb, gt = (4, 32) if rehearse else (16, 1024)
                        m = n - n % qb
                        want[n, c0] = (m, np.asarray(
                            absorbed_rows(m, c0 + 1, qb, gt), np.float32))
                    m, ref = want[n, c0]
                    got = np.asarray(launch(*ops)[:m], np.float32).reshape(
                        m, H, vd)
                    row.update(ms=ms, pct_peak=100 * flops(c0 + 1, n)
                               / (ms / 1e3) / PEAK_BF16,
                               gap_to_absorbed=float(
                                   np.abs(got - ref).max()),
                               ref_abs_max=float(np.abs(ref).max()))
                except Exception as e:  # noqa: BLE001
                    row["error"] = str(e)[:300]
                note(row)
        # where the forms cross: a short run from 8 k, both forms
        sub, tile = (8, 32) if rehearse else (None, None)
        for n in ((8, 16) if rehearse else (64, 128, 256, 512)):
            c0 = 100 if rehearse else 8192
            row = {"table": "expanded", "crossover_rows": n, "c0": c0}
            try:
                row["ms_expanded"] = _best(
                    expanded(sub, tile, None),
                    (pool, tables, q2, w_uk, w_uv, *one_run(c0 + 1, n)))
                qb, gt = (4, 32) if rehearse else (16, 1024)
                q = jnp.asarray(rng.normal(size=(n, H, RS)), dt)
                row["ms_absorbed"] = _best(walk(qb, gt), (
                    q, jnp.full((n,), S - 1, jnp.int32),
                    c0 + 1 + jnp.arange(n, dtype=jnp.int32)))
            except Exception as e:  # noqa: BLE001
                row["error"] = str(e)[:300]
            note(row)
        # the absorbed launch over rows it no longer serves (all dead):
        # a tick's whole block layout, and a compacted one
        for rows_live in ((16,) if rehearse else (2048, 512, 256)):
            qb = 4 if rehearse else 16
            t = rows_live + min(rows_live, S) * (qb - 1)
            t += -t % qb
            q = jnp.zeros((t, H, RS), dt)
            z = jnp.zeros((t,), jnp.int32)
            try:
                ms = _best(walk(qb, 32 if rehearse else 1024), (q, z, z))
                note({"table": "expanded", "dead_absorbed_rows": rows_live,
                      "layout_rows": t, "ms": ms})
            except Exception as e:  # noqa: BLE001
                note({"table": "expanded", "dead_absorbed_rows": rows_live,
                      "error": str(e)[:300]})

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "latent_walk_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
