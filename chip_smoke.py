"""chip_smoke.py — does the main path still start on the chip?

    python chip_smoke.py            # on a machine with a TPU: exit 0
    python chip_smoke.py --rehearse-cpu   # debugging aid, see below

ONE process, the only one that touches JAX (a chip belongs to one
process). It prints what JAX found, FAILS unless that is a TPU, then
drives GPT-small (`gpt_small()`: d768, 12 heads of 64, 12 layers, vocab
50304, s1024 — full width and depth, random weights from a seed)
through the entry points a user calls:

  kernels  every Pallas entry the two paths dispatch, compiled
           (interpret=False) against its jnp reference
  train    paddle.jit.TrainStep + AdamW under amp O1/bf16, b16·s1024
  serve    inference.LLMServer, paged pool sized by for_pool_budget,
           mixed prompts, decode_k=1 and the fused window (8); logits
           against a plain float32 forward
  hybrid   only when JAX reports >= 4 devices: HybridTrainStep at
           tp2×pp2 and dp2×pp2

Each phase fails the run on its own. The LAST stdout line is one JSON
object, `{"ok": true, "device": {...}}`; any failure exits non-zero and
prints no such line. Seconds printed here are smoke timings (compile
included), not metrics.

`--rehearse-cpu` runs the same control flow at `gpt_tiny` with the
Pallas kernels in interpret mode, to debug this script without the
chip. It stamps platform=cpu on every line, refuses to run on anything
but the CPU, and is never selected by the absence of a chip.
"""
import argparse
import functools
import hashlib
import json
import math
import os
import sys
import time
import traceback

# ---- tolerances, each with its reason --------------------------------
# Pallas paged kernel vs paged_attention_jnp (reference under
# precision=highest): both are f32 end to end; the kernel accumulates
# over page rows in another order. Outputs are O(1); measured on the
# v5e: <= 2.4e-6 over every variant (PR 21).
TOL_PAGED = 2e-5
# the same at the decode cell's dtypes: bf16 q and pool, f32 inside,
# bf16 out — the reference runs in f32 on the same bf16-rounded inputs,
# so what is left is the output's rounding: 2^-9 relative, on values
# that reach 4 where a row attends one or two N(0, 1) tokens (7.8e-3);
# measured on the v5e 1.4e-3 (24 long rows) and 7.5e-3 (rows of 1..69
# tokens) (PR 26), and the same two numbers with the MXU body, whose
# softmax weights are rounded to bf16 before p·v (PR 29); the float32
# pool at these shapes (products at Precision.HIGHEST) reads 8.5e-7
# under TOL_PAGED.
TOL_PAGED_BF16 = 2e-2
# flash fwd, bf16 operands: the kernel rounds p to bf16 before p·v and
# returns bf16 (8 mantissa bits, 2^-8 = 3.9e-3 relative); the reference
# is f32/highest on the same bf16-rounded inputs. Outputs are O(1);
# measured on the v5e 7.2e-3 (PR 21).
TOL_FLASH_FWD_BF16 = 3e-2
# flash dq/dk/dv, bf16: three chained bf16 matmuls (p, ds, and the
# cotangent g are each rounded to bf16), on gradients of O(1); measured
# on the v5e 1.3e-2 (PR 21).
TOL_FLASH_BWD_BF16 = 6e-2
# flash in f32: MXU default precision rounds f32 operands to bf16
# inside the kernel (same as any TPU matmul at default precision);
# measured on the v5e 1.4e-2 (PR 21).
TOL_FLASH_F32 = 3e-2
# engine logits (f32 weights, TPU default matmul precision = one bf16
# pass per product, 12 layers deep, paged f32 attention) vs the plain
# f32 forward under precision=highest. Logits of the seeded random
# model have std ~0.55; measured max |Δ| on the v5e over 91 frontier
# rows is 1.38e-2 (PR 21) and the bound is ~4× that.
TOL_LOGITS = 0.06
# four-chip HybridTrainStep losses vs the same pipelined model, seed,
# batch and microbatching on ONE chip: bf16 matmuls partitioned
# differently (tp splits the contraction and all-reduces partial sums,
# dp means per-replica gradients); the loss (~10.9) is reduced in f32.
# Measured on four v5e chips: 9.7e-5 (tp2×pp2), 7.5e-5 (dp2×pp2) over
# four steps (PR 21); the bound is ~20× that.
TOL_HYBRID_LOSS = 2e-3


def _sha(*arrays):
    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


class Smoke:
    def __init__(self, rehearse):
        self.rehearse = rehearse
        self.failed = []
        self.report = {"phases": {}}
        self.platform = "?"

    def say(self, msg):
        print(f"[smoke platform={self.platform}] {msg}", flush=True)

    def check(self, cond, what):
        """Record a failed check and keep going (one chip call should
        show every failure, not the first)."""
        if not cond:
            self.failed.append(what)
            self.say(f"FAIL {what}")
        return bool(cond)

    def attempt(self, what, fn, out, key):
        """out[key] = fn(); an exception becomes a failed check named
        `what` and the traceback is kept in its place — one Mosaic
        objection, one mesh configuration or one phase must not hide
        the others' results."""
        try:
            out[key] = fn()
        except Exception as e:  # noqa: BLE001 - reported, run fails
            out[key] = {"error": traceback.format_exc()}
            first = " | ".join(str(e).strip().splitlines()[:6])
            self.check(False, f"{what} raised {type(e).__name__}: "
                              f"{first[:900]}")
            self.say(out[key]["error"])

    def case(self, key, fn, out):
        self.attempt(f"kernel {key}", fn, out, key)

    def phase(self, name, fn):
        t0 = time.perf_counter()
        n_before = len(self.failed)
        got = {}
        self.attempt(name, lambda: fn(self), got, "out")
        out = got["out"]
        dt = time.perf_counter() - t0
        ok = len(self.failed) == n_before
        self.report["phases"][name] = {"ok": ok, "smoke_seconds":
                                       round(dt, 1), **(out or {})}
        self.say(f"phase {name}: {'ok' if ok else 'FAILED'} "
                 f"({dt:.1f}s smoke timing, compile included)")


# ---------------------------------------------------------------- kernels

def _maxdiff(a, b):
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _flash_cases(sm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional.attention import dense_attention_bshd
    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        flash_attention_bshd)

    interp = sm.rehearse
    # (b, s, h, d), dtypes. The first three take the RESIDENT kernels (a
    # 128-lane block's whole sequence in VMEM): two head pairs; the
    # gpt2m_train cell's s1024 x h16 x d64; head_dim 128, one head a
    # block. Three heads of 64 are half a lane block, so the last shape
    # keeps the TILED kernels on the chip.
    both, bf16 = ("bf16", "f32"), ("bf16",)
    shapes = ([((2, 256, 2, 64), both), ((2, 256, 4, 64), bf16),
               ((2, 256, 2, 128), bf16), ((2, 256, 3, 64), bf16)]
              if sm.rehearse else
              [((2, 1024, 4, 64), both), ((2, 1024, 16, 64), bf16),
               ((2, 2048, 4, 128), bf16), ((2, 1024, 3, 64), bf16)])
    dtypes = {"bf16": (jnp.bfloat16, TOL_FLASH_FWD_BF16, TOL_FLASH_BWD_BF16),
              "f32": (jnp.float32, TOL_FLASH_F32, TOL_FLASH_F32)}
    out = {}
    for (b, s, h, d), names in shapes:
        rng = np.random.default_rng(0)
        base = [rng.standard_normal((b, s, h, d)).astype(np.float32)
                for _ in range(4)]
        lens = jnp.asarray([s, (s * 5) // 8 + 3], jnp.int32)
        for dt_name in names:
            dtype, tol_f, tol_b = dtypes[dt_name]
            q, k, v, g = (jnp.asarray(x).astype(dtype) for x in base)
            qf, kf, vf, gf = (x.astype(jnp.float32) for x in (q, k, v, g))
            for name, causal, kvl in (("causal", True, None),
                                      ("kv_lens", False, lens)):
                def fl(q_, k_, v_, causal=causal, kvl=kvl):
                    return flash_attention_bshd(
                        q_, k_, v_, causal=causal, kv_lens=kvl,
                        interpret=interp)

                def ref(q_, k_, v_, causal=causal, kvl=kvl, s=s):
                    mask = None
                    if kvl is not None:
                        mask = (jnp.arange(s)[None, :]
                                < kvl[:, None])[:, None, None, :]
                    return dense_attention_bshd(
                        q_, k_, v_, is_causal=causal, attn_mask=mask)

                key = f"flash_{dt_name}_{name}_s{s}_h{h}_hd{d}"

                def run_case(fl=fl, ref=ref, key=key, tol_f=tol_f,
                             tol_b=tol_b, ops=(q, k, v, g),
                             ops32=(qf, kf, vf, gf)):
                    t0 = time.perf_counter()
                    o, vjp = jax.vjp(jax.jit(fl), *ops[:3])
                    dq, dk, dv = vjp(ops[3])
                    jax.block_until_ready((o, dq, dk, dv))
                    secs = time.perf_counter() - t0
                    with jax.default_matmul_precision("highest"):
                        o_r, vjp_r = jax.vjp(jax.jit(ref), *ops32[:3])
                        dq_r, dk_r, dv_r = vjp_r(ops32[3])
                    errs = {"fwd": _maxdiff(o, o_r),
                            "dq": _maxdiff(dq, dq_r),
                            "dk": _maxdiff(dk, dk_r),
                            "dv": _maxdiff(dv, dv_r)}
                    finite = all(math.isfinite(e) for e in errs.values())
                    ok = (finite and errs["fwd"] <= tol_f
                          and max(errs["dq"], errs["dk"],
                                  errs["dv"]) <= tol_b)
                    sm.say(f"kernel {key}: max|Δ| fwd {errs['fwd']:.2e} dq "
                           f"{errs['dq']:.2e} dk {errs['dk']:.2e} dv "
                           f"{errs['dv']:.2e} (tol {tol_f:g}/{tol_b:g}) "
                           f"{secs:.1f}s")
                    sm.check(ok, f"kernel {key} outside tolerance: {errs}")
                    return errs

                sm.case(key, run_case, out)
    return out


def _paged_case(rng, heads, dim, page_size, kind, n_slots=4,
                pages_per_seq=8, table_slots=None):
    """A ragged batch over a shuffled pool. kind: 'token' (decode
    frontiers + a prefill chunk + padding rows) or an int Q (the verify
    layout: Q rows per slot, slot-major)."""
    import numpy as np

    S = table_slots or n_slots
    MP, P = pages_per_seq, page_size
    N = n_slots * MP + 1
    kp = rng.standard_normal((N, P, heads, dim)).astype(np.float32)
    vp = rng.standard_normal((N, P, heads, dim)).astype(np.float32)
    pt = np.zeros((S, MP), np.int32)
    pt[:n_slots] = (rng.permutation(np.arange(1, N))
                    .reshape(n_slots, MP).astype(np.int32))
    cap = MP * P
    if kind == "token":
        sid = [0, 1, 2, 3] + [1] * 7 + [0, 0, 3, 0, 0]
        lens = [cap - 28, 37, 1, cap] + list(range(30, 37)) \
            + [cap - 29, 0, 17, 0, 0]
    else:
        Q = int(kind)
        pos0, width = [5, cap - Q - 1, 0, 40], [Q - 1, 2, -1, Q - 1]
        sid, lens = [], []
        for s_ in range(n_slots):
            for j in range(Q):
                sid.append(s_)
                live = width[s_] >= 0 and j <= width[s_]
                lens.append(pos0[s_] + j + 1 if live else 0)
    T = len(sid)
    q = rng.standard_normal((T, heads, dim)).astype(np.float32)
    return (q, kp, vp, pt, np.asarray(sid, np.int32),
            np.asarray(lens, np.int32))


def _paged_cases(sm):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional.attention import paged_attention_jnp
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        ragged_paged_attention)
    from paddle_tpu.quantization import runtime as qrt

    interp = sm.rehearse
    out = {}
    geoms = [("gpt_small", 12, 64)] if sm.rehearse else [
        ("gpt_small", 12, 64), ("gpt_1p3b", 32, 64)]
    variants = [("token", None), (5, None), ("token", 3)]
    for gname, H, D in geoms:
        for pool in ("float32", "int8", "int4"):
            for kind, off in variants:
                if sm.rehearse and (kind, off) != ("token", None) \
                        and pool != "int4":
                    continue   # the interpreter is slow; tests cover these
                rng = np.random.default_rng(7)
                q, kp, vp, pt, sid, lens = _paged_case(rng, H, D, 16, kind)
                ks = vs = None
                if pool != "float32":
                    quant = (qrt.quantize_kv_rows if pool == "int8"
                             else qrt.quantize_kv_rows_int4)
                    N, P = kp.shape[:2]
                    kq, ks = quant(jnp.asarray(kp).reshape(N * P, H, D))
                    vq, vs = quant(jnp.asarray(vp).reshape(N * P, H, D))
                    kp = kq.reshape(N, P, H, -1)
                    vp = vq.reshape(N, P, H, -1)
                    ks, vs = ks.reshape(N, P, H), vs.reshape(N, P, H)
                if off is not None:
                    lens = np.where(lens > off, lens - off, 0).astype(
                        np.int32)
                qps = None if kind == "token" else int(kind)
                offv = None if off is None else jnp.asarray(off, jnp.int32)

                def run(q_, kp_, vp_, pt_, sid_, lens_, ks_, vs_):
                    return ragged_paged_attention(
                        q_, kp_, vp_, pt_, sid_, lens_, k_scales=ks_,
                        v_scales=vs_, frontier_offset=offv,
                        q_per_slot=qps, interpret=interp)

                def ref(q_, kp_, vp_, pt_, sid_, lens_, ks_, vs_):
                    return paged_attention_jnp(
                        q_, kp_, vp_, pt_, sid_, lens_, k_scales=ks_,
                        v_scales=vs_, frontier_offset=offv,
                        max_tokens_per_slot=qps)

                args = tuple(None if a is None else jnp.asarray(a)
                             for a in (q, kp, vp, pt, sid, lens, ks, vs))
                key = (f"paged_{gname}_h{H}x{D}_p16_{pool}_"
                       f"{'token' if qps is None else f'q{qps}'}"
                       f"{'' if off is None else f'_off{off}'}")

                def run_case(run=run, ref=ref, args=args, key=key):
                    t0 = time.perf_counter()
                    got = jax.block_until_ready(jax.jit(run)(*args))
                    secs = time.perf_counter() - t0
                    with jax.default_matmul_precision("highest"):
                        want = jax.jit(ref)(*args)
                    err = _maxdiff(got, want)
                    pad_zero = bool(np.all(
                        np.asarray(got)[np.asarray(args[5]) == 0] == 0))
                    sm.say(f"kernel {key}: max|Δ| {err:.2e} "
                           f"(tol {TOL_PAGED:g}) padding rows zero="
                           f"{pad_zero} {secs:.1f}s")
                    sm.check(math.isfinite(err) and err <= TOL_PAGED
                             and pad_zero,
                             f"kernel {key}: err {err}, pad_zero "
                             f"{pad_zero}")
                    return {"max_abs_err": err, "pad_rows_zero": pad_zero}

                sm.case(key, run_case, out)
    if not sm.rehearse:
        # the whole page table rides scalar-prefetch SMEM, flat: check
        # it at the slots × pages-per-sequence a big engine implies
        # (256 slots × 128 pages = gpt_1p3b's 2048-token sequences at
        # page 16: 32768 int32 = 128 KiB)
        rng = np.random.default_rng(9)
        q, kp, vp, pt, sid, lens = _paged_case(
            rng, 12, 64, 16, "token", pages_per_seq=128, table_slots=256)
        args = tuple(jnp.asarray(a) for a in (q, kp, vp, pt, sid, lens))

        def run_case():
            got = jax.block_until_ready(jax.jit(
                lambda *a: ragged_paged_attention(*a))(*args))
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda *a: paged_attention_jnp(*a))(*args)
            err = _maxdiff(got, want)
            sm.say(f"kernel paged page table 256×128 in SMEM "
                   f"({pt.size * 4} B): max|Δ| {err:.2e}")
            sm.check(err <= TOL_PAGED, f"paged SMEM table case: err {err}")
            return {"max_abs_err": err, "table_bytes": pt.size * 4}

        sm.case("paged_smem_table_256x128", run_case, out)

        # the decode cell's own launches (cerebras-gpt-1.3b: 16 heads ×
        # 128, bf16 pool, 24 slots × 128 pages of 16): the fused
        # window's 24 rows all live, and a single tick's 256 rows of
        # which 69 prefill a fresh prompt and the rest are padding.
        # These shapes take the kernel's in-kernel page walk and its
        # all-heads MXU body; the window again over a float32 pool,
        # whose products run at `Precision.HIGHEST`.
        rng = np.random.default_rng(11)
        n_pool = 1024
        pools = [jnp.asarray(rng.standard_normal((n_pool, 16, 16, 128)),
                             jnp.bfloat16) for _ in range(2)]
        pt = jnp.asarray(rng.integers(1, n_pool, (24, 128)), jnp.int32)
        window = (np.arange(24), np.concatenate(
            [rng.integers(70, 1371, 22), [1, 2048]]))
        tick = (np.where(np.arange(256) < 69, 3, 0),
                np.where(np.arange(256) < 69, np.arange(256) + 1, 0))
        for name, (sid, lens), dtype, tol in (
                ("window_t24", window, jnp.bfloat16, TOL_PAGED_BF16),
                ("tick_t256_69live", tick, jnp.bfloat16, TOL_PAGED_BF16),
                ("window_t24", window, jnp.float32, TOL_PAGED)):
            q = jnp.asarray(rng.standard_normal((len(sid), 16, 128)), dtype)
            args = (q, *(x.astype(dtype) for x in pools), pt,
                    jnp.asarray(sid, jnp.int32), jnp.asarray(lens, jnp.int32))
            key = f"paged_cell_h16x128_p16_{jnp.dtype(dtype).name}_{name}"

            def run_cell(args=args, key=key, tol=tol):
                got = jax.block_until_ready(jax.jit(
                    lambda *a: ragged_paged_attention(*a))(*args))
                f32 = tuple(a.astype(jnp.float32) for a in args[:3])
                with jax.default_matmul_precision("highest"):
                    want = jax.jit(lambda *a: paged_attention_jnp(*a))(
                        *f32, *args[3:])
                err = _maxdiff(got, want)
                pad_zero = bool(np.all(
                    np.asarray(got, np.float32)[np.asarray(args[5]) == 0]
                    == 0))
                sm.say(f"kernel {key}: max|Δ| {err:.2e} (tol "
                       f"{tol:g}) padding rows zero={pad_zero}")
                sm.check(err <= tol and pad_zero,
                         f"kernel {key}: err {err}, pad_zero {pad_zero}")
                return {"max_abs_err": err, "pad_rows_zero": pad_zero}

            sm.case(key, run_cell, out)
    return out


def _latent_cases(sm):
    """The latent (MLA) walk in the ABSORBED form, and the kernel that
    serves the EXPANDED form, against the expanded form in plain float32
    `jax.numpy`, at sarvam-105b's serving shape: 64 heads of 128 + 64, a
    bf16 pool of 576-wide rows stored as 640 lanes, pages of 16,
    contexts of 10 k and 16 k; a fused window's rows (one a slot), a
    tick's block of 8 rows of one slot, and two runs of 6 rows that end
    at the two contexts (the expanded kernel's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        latent_expanded_attention, latent_paged_attention)

    H, nope, rope, lat, vd, P = (4, 16, 8, 32, 16, 16) if sm.rehearse \
        else (64, 128, 64, 512, 128, 16)
    store = -(-(lat + rope) // 128) * 128
    ctxs = (40, 64) if sm.rehearse else (10240, 16384)
    dt = jnp.float32 if sm.rehearse else jnp.bfloat16
    tol = TOL_PAGED * 10 if sm.rehearse else TOL_PAGED_BF16
    MP = -(-max(ctxs) // P)
    n_pool = 2 * MP + 1
    rng = np.random.default_rng(13)
    rows = rng.standard_normal((n_pool, P, store)) * 0.5
    rows[..., lat + rope:] = 0.0
    pool = jnp.asarray(rows, dt)
    pt = jnp.asarray(rng.permutation(np.arange(1, n_pool)).reshape(2, MP),
                     jnp.int32)
    w_uk = jnp.asarray(rng.standard_normal((H, nope, lat)) * lat ** -0.5, dt)
    w_uv = jnp.asarray(rng.standard_normal((H, lat, vd)) * lat ** -0.5, dt)
    scale = (nope + rope) ** -0.5
    out = {}
    # (name, slot ids, lengths, rows a block; "runs": the expanded
    # kernel, each slot's rows a run)
    cases = [("window", np.array([0, 1]), np.array(ctxs), None),
             ("tick_block", np.zeros(8, int),
              np.concatenate([ctxs[0] - 5 + np.arange(6), [0, 0]]), 8),
             ("expanded_runs", np.repeat([0, 1], 6),
              np.concatenate([c - 5 + np.arange(6) for c in ctxs]), "runs")]
    for name, sid, lens, qb in cases:
        T = len(sid)
        q_nope = jnp.asarray(rng.standard_normal((T, H, nope)), dt)
        q_rope = jnp.asarray(rng.standard_normal((T, H, rope)), dt)
        key = f"latent_h{H}_r{lat + rope}_{jnp.dtype(dt).name}_{name}"

        def run_case(sid=sid, lens=lens, qb=qb, q_nope=q_nope,
                     q_rope=q_rope, key=key):
            f32 = jnp.float32

            def absorbed(q_nope, q_rope):
                qc = jnp.einsum("thd,hdc->thc", q_nope, w_uk,
                                preferred_element_type=f32)
                qa = jnp.concatenate([qc, q_rope.astype(f32)], -1).astype(dt)
                qa = jnp.pad(qa, ((0, 0), (0, 0),
                                  (0, store - qa.shape[-1])))
                oc = latent_paged_attention(
                    qa, pool, pt, jnp.asarray(sid, jnp.int32),
                    jnp.asarray(lens, jnp.int32), lat, scale,
                    q_per_slot=qb, interpret=sm.rehearse)
                return jnp.einsum("thc,hcd->thd", oc, w_uv,
                                  preferred_element_type=f32)

            def expanded_kernel(q_nope, q_rope):
                # a run a slot: rows 0–5 and 16–21 of the laid-out rows
                # (the first run's sub-block runs over the second's rows)
                sub = 16 if sm.rehearse else 512
                q = jnp.concatenate([q_nope, q_rope], -1)
                q = jnp.pad(q, ((0, 0), (0, 0), (0, store - lat - rope)))
                q = jnp.zeros((16 + sub,) + q.shape[1:], q.dtype).at[
                    np.r_[0:6, 16:22]].set(q)
                i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
                o = latent_expanded_attention(
                    q.reshape(16 + sub, -1), pool, w_uk, w_uv, pt,
                    i32([0, 1]), i32([0, 16]), i32(lens[[0, 6]]),
                    i32([6, 6]), scale, sub_rows=sub,
                    tile_tokens=16 if sm.rehearse else None,
                    interpret=sm.rehearse)
                return o[np.r_[0:6, 16:22]].reshape(12, H, vd)

            def expanded(q_nope, q_rope):
                outs = []
                for t in range(len(sid)):
                    n = int(lens[t])
                    if n == 0:
                        outs.append(jnp.zeros((H, vd), f32))
                        continue
                    pos = np.arange(n)
                    r = pool[pt[sid[t], pos // P], pos % P].astype(f32)
                    c, kr = r[:, :lat], r[:, lat:lat + rope]
                    k_nope = jnp.einsum("uc,hdc->uhd", c, w_uk.astype(f32))
                    v = jnp.einsum("uc,hcd->uhd", c, w_uv.astype(f32))
                    s = (jnp.einsum("hd,uhd->hu", q_nope[t].astype(f32),
                                    k_nope)
                         + jnp.einsum("hr,ur->hu", q_rope[t].astype(f32),
                                      kr)) * scale
                    outs.append(jnp.einsum(
                        "hu,uhd->hd", jax.nn.softmax(s, -1), v))
                return jnp.stack(outs)

            served = expanded_kernel if qb == "runs" else absorbed
            got = jax.block_until_ready(jax.jit(served)(q_nope, q_rope))
            with jax.default_matmul_precision("highest"):
                want = expanded(q_nope, q_rope)
            err = _maxdiff(got, want)
            pad_zero = bool(np.all(
                np.asarray(got, np.float32)[np.asarray(lens) == 0] == 0))
            what = "expanded kernel" if qb == "runs" else "absorbed walk"
            sm.say(f"kernel {key}: {what} against the expanded "
                   f"form max|Δ| {err:.2e} (tol {tol:g}) padding rows "
                   f"zero={pad_zero}")
            sm.check(math.isfinite(err) and err <= tol and pad_zero,
                     f"kernel {key}: err {err}, pad_zero {pad_zero}")
            return {"max_abs_err": err, "pad_rows_zero": pad_zero}

        sm.case(key, run_case, out)
    return out


def _delta_rule_cases(sm):
    """The gated delta rule's two forms (`nn/functional/delta_rule.py`:
    the RECURRENT step and the CHUNKED form, each a Pallas kernel)
    against the recurrence a token at a time in plain float32
    `jax.numpy` at highest precision, at Ling-3.0-flash-VL's serving
    shape: 32 heads of 128 × 128, decays down to e^-5 a token, a run of
    200 rows of one slot that starts mid-sequence from a state that is
    not zero; the chunked form also on the same rows standing from flat
    row 37 on (no multiple of a chunk, nor of a tile of 8 rows: the
    kernel reads them where they lie)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.nn.functional import delta_rule as dr
    from paddle_tpu.nn.functional.attention import SlotRunLayout

    H, dk, S, T, n = (2, 128, 3, 96, 70) if sm.rehearse \
        else (32, 128, 8, 256, 200)
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)   # noqa: E731
    q, k, v = f(T, H, dk) * dk ** -0.5, f(T, H, dk), f(T, H, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(2.0 * f(T, H, dk))
    beta = jax.nn.sigmoid(f(T, H))
    state0 = f(S, H, dk, dk)
    at = 19 if sm.rehearse else 37

    def by_token(st):
        def step(s_, row):
            q_t, k_t, v_t, g_t, b_t = row
            s_ = s_ * jnp.exp(g_t)[:, :, None]
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s_, k_t))
            s_ = s_ + k_t[:, :, None] * u[:, None, :]
            return s_, jnp.einsum("hkv,hk->hv", s_, q_t)
        return jax.lax.scan(step, st, (q[:n], k[:n], v[:n], g[:n],
                                       beta[:n]))

    with jax.default_matmul_precision("highest"):
        want_s, want_o = jax.jit(by_token)(state0[1])
    out = {}

    def chunked(at=0):
        sids = np.zeros((T,), np.int32)
        lens = np.zeros((T,), np.int32)
        sids[at:at + n], lens[at:at + n] = 1, 1000 + np.arange(n)
        # the run's rows moved to flat row `at`, the rest of the tick dead
        there = lambda a: jnp.roll(a, at, axis=0)   # noqa: E731

        def fn(st):
            runs = SlotRunLayout(jnp.asarray(sids), jnp.asarray(lens), 64,
                                 dr.CHUNK, 0)
            return dr.delta_rule_chunked(st, there(q), there(k), there(v),
                                         there(g), there(beta), runs)[:2]
        o, st = jax.jit(fn)(state0)
        off_run = jnp.concatenate([o[:at], o[at + n:]])
        sm.check(not bool(jnp.any(off_run)),
                 "kernel delta_rule_chunked: rows off the run are not zero")
        return o[at:at + n], st

    def recurrent():
        live = jnp.arange(S) == 1

        def fn(st):
            def one(t, carry):
                o, st = carry
                row = lambda a: jnp.broadcast_to(   # noqa: E731
                    a[t], (S,) + a.shape[1:])
                got, st = dr.delta_rule_step(
                    st, row(q), row(k), row(v), row(g), row(beta), live,
                    jnp.zeros((S,), bool),
                    kernel=None if not sm.rehearse else False)
                return o.at[t].set(got[1]), st
            return jax.lax.fori_loop(
                0, n, one, (jnp.zeros((n, H, dk), jnp.float32), st))
        return jax.jit(fn)(state0)

    for name, fn in (("chunked", chunked),
                     ("chunked_from_an_odd_row",
                      functools.partial(chunked, at)),
                     ("recurrent", recurrent)):
        def run_case(fn=fn, name=name):
            o, st = jax.block_until_ready(fn())
            err_o, err_s = _maxdiff(o, want_o), _maxdiff(st[1], want_s)
            kept = bool(np.array_equal(np.asarray(st[0]),
                                       np.asarray(state0[0])))
            sm.say(f"kernel delta_rule_{name}: against the recurrence a "
                   f"token at a time max|Δ| o {err_o:.2e} state "
                   f"{err_s:.2e} (tol 2e-3), other slots kept={kept}")
            sm.check(err_o <= 2e-3 and err_s <= 2e-3 and kept,
                     f"kernel delta_rule_{name}: {err_o} {err_s} {kept}")
            return {"max_abs_err": max(err_o, err_s)}
        sm.case(f"delta_rule_h{H}_d{dk}_{name}", run_case, out)
    return out


def phase_kernels(sm):
    out = _flash_cases(sm)
    out.update(_paged_cases(sm))
    out.update(_latent_cases(sm))
    out.update(_delta_rule_cases(sm))
    return {"cases": out}


# ------------------------------------------------------------------ model

def _model_cfg(sm):
    from paddle_tpu.text.models.gpt import gpt_small, gpt_tiny

    return gpt_tiny() if sm.rehearse else gpt_small()


def _train_batch(sm, cfg):
    import numpy as np

    batch, seq = (8, 128) if sm.rehearse else (16, 1024)
    rng = np.random.default_rng(0)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def phase_train(sm):
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp, analysis
    from paddle_tpu.text.models import (GPTForCausalLM,
                                        GPTPretrainingCriterion)

    paddle.seed(0)
    cfg = _model_cfg(sm)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return crit(m(ids), ids)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    ids_np = _train_batch(sm, cfg)
    ids = paddle.to_tensor(ids_np)
    t0 = time.perf_counter()
    losses = [float(step(ids).numpy())]
    compile_s = time.perf_counter() - t0
    for _ in range(5):
        losses.append(float(step(ids).numpy()))
    sm.say(f"train b{ids_np.shape[0]}·s{ids_np.shape[1]}: compile+step0 "
           f"{compile_s:.1f}s (smoke timing); losses "
           + " ".join(f"{x:.4f}" for x in losses))
    sm.say("digest train_losses="
           + _sha(np.asarray(losses, np.float64)))
    sm.check(all(math.isfinite(x) for x in losses), "train: loss not finite")
    sm.check(losses[-1] < losses[0] - 0.05,
             f"train: loss did not fall on a fixed batch: {losses}")
    # ln(vocab) is where a seeded random model starts
    sm.check(abs(losses[0] - math.log(cfg.vocab_size)) < 0.5,
             f"train: step-0 loss {losses[0]} far from ln(vocab)")
    stats = step.compile_stats()
    sm.check(stats["executables"] == 1 and stats["batch_signatures"] == 1,
             f"train: expected one executable, got {stats}")
    rep = analysis.analyze_step(step, ids)
    sm.say(f"train: donation {rep.donation['aliased']}/"
           f"{rep.donation['expected']} held={rep.donation['held']}; "
           f"custom calls {rep.custom_calls}")
    sm.check(rep.donation["held"],
             f"train: donation dropped: {rep.donation['dropped'][:4]}")
    if not sm.rehearse:
        sm.check(rep.custom_calls.get("tpu_custom_call", 0) > 0,
                 "train: no tpu_custom_call in the lowered step — the "
                 "flash kernel did not run (dense_attention_bshd did)")
    sm.model = model         # the serve phase serves this model
    return {"losses": losses, "compile_plus_step0_s": round(compile_s, 1),
            "executables": stats["executables"],
            "donation": rep.donation, "custom_calls": rep.custom_calls}


# ------------------------------------------------------------------ serve

def _reference_logits(model, cfg, seqs):
    """Plain float32 jnp forward of the SAME weights (pre-LN GPT-2
    block, tied head) under precision=highest — written here against
    jax.numpy only, so it shares no code with the model or the engine.
    seqs: list of 1-D token arrays → list of [len, vocab] logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sd = {k: v._value for k, v in model.state_dict().items()}
    nh = cfg.num_heads
    L = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s          # right padding: causal ⇒ harmless

    def ln(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    def fwd(sd, ids):
        b, s = ids.shape
        x = sd["gpt.wte.weight"][ids] + sd["gpt.wpe.weight"][:s]
        d = x.shape[-1]
        for i in range(cfg.num_layers):
            p = f"gpt.layers.{i}."
            h = ln(x, sd[p + "ln1.weight"], sd[p + "ln1.bias"])
            qkv = h @ sd[p + "qkv.weight"] + sd[p + "qkv.bias"]
            q, k, v = (t.reshape(b, s, nh, d // nh)
                       for t in jnp.split(qkv, 3, axis=-1))
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d // nh)
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            x = x + a.reshape(b, s, d) @ sd[p + "proj.weight"] \
                + sd[p + "proj.bias"]
            h = ln(x, sd[p + "ln2.weight"], sd[p + "ln2.bias"])
            h = jax.nn.gelu(h @ sd[p + "fc1.weight"] + sd[p + "fc1.bias"],
                            approximate=False)
            x = x + h @ sd[p + "fc2.weight"] + sd[p + "fc2.bias"]
        x = ln(x, sd["gpt.ln_f.weight"], sd["gpt.ln_f.bias"])
        return x @ sd["gpt.wte.weight"].T

    with jax.default_matmul_precision("highest"):
        out = np.asarray(jax.jit(fwd)(
            {k: v.astype(jnp.float32) for k, v in sd.items()}, ids))
    return [out[i, :len(s)] for i, s in enumerate(seqs)]


class _LogitsTap:
    """Stands in for the engine's single-tick step to copy out the
    logits row of every sampling frontier it dispatches, keyed by
    request id and position. Observation only — the call and its
    result pass through untouched."""

    def __init__(self, engine):
        self.engine = engine
        self.fn = engine._step_fn
        self.rows = {}                # rid -> {position: logits[vocab]}
        self.shared_ticks = 0         # ticks with prefill AND decode rows

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, tok, pos, sid, widx, pt, klen, smp, kv_state):
        import numpy as np

        out = self.fn(tok, pos, sid, widx, pt, klen, smp, kv_state)
        logits = np.asarray(out[0][0])            # [slots, vocab]
        sid_np, smp_np = np.asarray(sid), np.asarray(smp)
        frontiers = decoding = 0
        for slot, req in enumerate(self.engine._slots):
            if req is None:
                continue
            r = int(smp_np[slot])
            n = len(req.tokens)
            if (int(sid_np[r]) == slot and int(klen[r]) == n
                    and int(pos[r]) == n - 1):
                self.rows.setdefault(req.rid, {})[n - 1] = \
                    logits[slot].copy()
                frontiers += 1
                decoding += req.num_generated > 0
        # live rows that are not a sampling frontier are prefill chunks
        if decoding and int(np.sum(np.asarray(klen) > 0)) > frontiers:
            self.shared_ticks += 1
        return out


def _serve_once(sm, model, cfg, decode_k, budget_bytes):
    import numpy as np

    from paddle_tpu import analysis, inference

    if sm.rehearse:
        slots, tok_budget, max_len = 4, 16, 96
        plens, gens = [5, 37, 20, 9], [9, 12, 10, 14]
    else:
        slots, tok_budget, max_len = 8, 48, 384
        plens = [5, 230, 37, 100, 64, 17]
        gens = [12, 16, 20, 9, 24, 10]
    ecfg = inference.LLMEngineConfig.for_pool_budget(
        cfg, budget_bytes, page_size=16, num_slots=slots,
        token_budget=tok_budget, max_model_len=max_len,
        decode_k=decode_k)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in plens]
    server = inference.LLMServer(model, ecfg)
    eng = server.engine
    tap = None
    if decode_k == 1:
        tap = eng._step_fn = _LogitsTap(eng)
    t0 = time.perf_counter()
    with server:
        # warm every executable the run can reach: a multi-tick prompt
        # (chunked-prefill single ticks) and > k generated tokens (one
        # full fused window)
        server.submit(np.zeros((2 * tok_budget,), np.int32),
                      max_new_tokens=max(2, decode_k + 1)).result(
                          timeout=1500)
        warm_s = time.perf_counter() - t0
        before = eng.compile_stats()
        futs = [server.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, gens)]
        outs = [np.asarray(f.result(timeout=1500)) for f in futs]
        rids = [f.pt_request.rid for f in futs]
        after = eng.compile_stats()
        m = server.metrics()
    tag = f"serve k={decode_k}"
    sm.say(f"{tag}: pool {ecfg.num_pages} pages "
           f"({eng.pool_bytes()} B by shape) slots {slots} budget "
           f"{tok_budget}; warm-up {warm_s:.1f}s (smoke timing); "
           f"executables {after}; steps {m.get('steps')} "
           f"prefill_tokens {m.get('prefill_tokens')} decode_tokens "
           f"{m.get('decode_tokens')} fused_steps {m.get('fused_steps')}")
    for i, (o, p, g) in enumerate(zip(outs, prompts, gens)):
        sm.check(len(o) == len(p) + g and np.array_equal(o[:len(p)], p),
                 f"{tag}: request {i} asked {g} tokens after {len(p)}, "
                 f"got {len(o) - len(p)}")
    sm.check(before == after and all(v == 1 for v in after.values()),
             f"{tag}: recompiled after warm-up: {before} -> {after}")
    if decode_k > 1:
        sm.check(m.get("fused_steps", 0) > 0,
                 f"{tag}: the fused window never ran")
    if tap is not None:
        sm.say(f"{tag}: ticks carrying chunked-prefill rows AND decode "
               f"rows: {tap.shared_ticks}")
        sm.check(tap.shared_ticks > 0,
                 f"{tag}: prefill and decode never shared a tick")
    stats = eng.compile_stats(check_donation=True)
    kinds = [("paged", stats["donation"])]
    if decode_k > 1:
        kinds.append(("fused", stats["fused"]["donation"]))
    calls = {}
    for which, don in kinds:
        sm.check(don["held"], f"{tag}: {which} step dropped donation: "
                              f"{don['dropped'][:4]}")
        rep = analysis.analyze_step(eng, check_donation=False, which=which)
        calls[which] = rep.custom_calls
        if not sm.rehearse:
            sm.check(rep.custom_calls.get("tpu_custom_call", 0) > 0,
                     f"{tag}: no tpu_custom_call in the lowered {which} "
                     "step — the jnp gather path ran, not the kernel")
    sm.say(f"{tag}: donation held "
           f"{ {w: d['held'] for w, d in kinds} }; custom calls {calls}")

    # logits, not sampled tokens (a seeded random model's top-2 gap is
    # often below bf16 noise, so exact token match is not a sound check)
    refs = _reference_logits(model, cfg, [o[:-1] for o in outs])
    worst, n_rows, off_argmax = 0.0, 0, 0
    digest_rows = []
    for i, (o, p, ref) in enumerate(zip(outs, prompts, refs)):
        for pos_ in range(len(p) - 1, len(o) - 1):
            if tap is not None:
                got = tap.rows.get(rids[i], {}).get(pos_)
                if not sm.check(got is not None,
                                f"{tag}: no logits captured for request "
                                f"{i} position {pos_}"):
                    continue
                worst = max(worst, float(np.max(np.abs(got - ref[pos_]))))
                digest_rows.append(got)
            # the emitted token must be (within tolerance) the argmax
            # of the reference — this is what checks the fused window,
            # whose logits never leave the device
            gap = float(ref[pos_].max() - ref[pos_][o[pos_ + 1]])
            off_argmax += gap > 2 * TOL_LOGITS
            n_rows += 1
    if tap is not None:
        sm.say(f"{tag}: engine logits vs f32/highest forward over "
               f"{n_rows} frontier rows: max|Δ| {worst:.3e} "
               f"(tol {TOL_LOGITS:g})")
        sm.check(worst <= TOL_LOGITS,
                 f"{tag}: logits max|Δ| {worst} > {TOL_LOGITS}")
        sm.say("digest serve_logits=" + _sha(*digest_rows))
    sm.say(f"{tag}: emitted tokens within 2·tol of the reference argmax: "
           f"{n_rows - off_argmax}/{n_rows}")
    sm.check(off_argmax == 0,
             f"{tag}: {off_argmax}/{n_rows} emitted tokens are not the "
             "reference argmax within tolerance")
    sm.say(f"digest serve_tokens_k{decode_k}=" + _sha(*outs))
    return {"warmup_s": round(warm_s, 1), "executables": after,
            "logits_max_abs_err": worst if tap is not None else None,
            "rows": n_rows, "custom_calls": calls,
            "pool_pages": ecfg.num_pages,
            "pool_bytes_by_shape": eng.pool_bytes()}


def phase_serve(sm):
    import gc

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM

    cfg = _model_cfg(sm)
    model = getattr(sm, "model", None)
    if model is None:            # the train phase failed before building
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
    model.eval()
    gc.collect()
    out = {}
    budget = (8 << 20) if sm.rehearse else (1 << 30)
    dev = jax.devices()[0]
    for k in (1, 8):
        stats0 = dev.memory_stats() or {}
        out[f"k{k}"] = _serve_once(sm, model, cfg, k, budget)
        stats1 = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats1:
            sm.say(f"serve k={k}: device bytes_in_use "
                   f"{stats0.get('bytes_in_use')} -> "
                   f"{stats1.get('bytes_in_use')}, peak "
                   f"{stats1['peak_bytes_in_use']}")
        gc.collect()
    return out


# ----------------------------------------------------------------- hybrid

def _hybrid_config(sm, cfg, ids_np, cfg3d, ref):
    """One mesh configuration: a few steps, loss parity with `ref` (the
    one-chip losses; None for the reference itself), where the state
    lives, one donated executable, the flash call inside the stage
    body. Returns the record (its "losses" are the next one's `ref`)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.distributed import hybrid3d, mesh as mesh_mod

    tag = cfg3d.tag()
    mesh_mod.reset_mesh()
    hybrid3d.init_hybrid_mesh(cfg3d,
                              devices=jax.devices()[:cfg3d.n_devices])
    try:
        paddle.seed(0)
        m = hybrid3d.build_gpt3d(cfg, cfg3d)
        opt = paddle.optimizer.AdamW(1e-4, parameters=m.parameters())
        # f32 parameters, the TPU's default matmul precision (one bf16
        # pass on the MXU) — how bench.py's train_3d arm drives it
        step = hybrid3d.HybridTrainStep(m, lambda mm, i: mm.loss(i), opt,
                                        config=cfg3d)
        ids = paddle.to_tensor(ids_np)
        t0 = time.perf_counter()
        losses = [float(step(ids).numpy())]
        compile_s = time.perf_counter() - t0
        for _ in range(3):
            losses.append(float(step(ids).numpy()))
        sm.say(f"hybrid {tag}: compile+step0 {compile_s:.1f}s (smoke "
               "timing); losses " + " ".join(f"{x:.4f}" for x in losses))
        sm.check(all(math.isfinite(x) for x in losses)
                 and losses[-1] < losses[0],
                 f"hybrid {tag}: losses not finite and falling: {losses}")
        if ref is not None:
            worst = max(abs(a - b) for a, b in zip(losses, ref))
            sm.say(f"hybrid {tag}: max |loss - one-chip loss| over "
                   f"{len(losses)} steps {worst:.2e} "
                   f"(tol {TOL_HYBRID_LOSS:g})")
            sm.check(worst <= TOL_HYBRID_LOSS,
                     f"hybrid {tag}: losses {losses} vs one-chip {ref}")
        # where the state really lives
        leaves = [p._value for p in step._param_objs] + [
            v for st in step._opt_states for v in st.values()]
        per_dev = {}
        for a in leaves:
            for sh in a.addressable_shards:
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                    + sh.data.nbytes
        mem = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
               for d in jax.devices()[:cfg3d.n_devices]}
        sm.say(f"hybrid {tag}: param+opt shard bytes per device "
               f"{per_dev}; memory_stats bytes_in_use {mem}")
        sm.check(len(per_dev) == cfg3d.n_devices,
                 f"hybrid {tag}: state on {len(per_dev)} devices, "
                 f"expected {cfg3d.n_devices}")
        stats = step.compile_stats()
        rep = analysis.analyze_step(step, ids)
        sm.say(f"hybrid {tag}: executables {stats['executables']} "
               f"donation held={rep.donation['held']} custom calls "
               f"{rep.custom_calls} collectives "
               f"{rep.collectives.get('per_axis_counts')}")
        sm.check(stats["executables"] == 1 and rep.donation["held"],
                 f"hybrid {tag}: {stats}, donation {rep.donation}")
        if not sm.rehearse:
            sm.check(rep.custom_calls.get("tpu_custom_call", 0) > 0,
                     f"hybrid {tag}: no tpu_custom_call inside the stage "
                     "body")
        return {"losses": losses, "shard_bytes_per_device": per_dev,
                "bytes_in_use": mem, "custom_calls": rep.custom_calls,
                "compile_plus_step0_s": round(compile_s, 1)}
    finally:
        mesh_mod.reset_mesh()


def phase_hybrid(sm):
    """Four chips, one process: HybridTrainStep at gpt_small widths."""
    import gc

    from paddle_tpu.distributed import hybrid3d

    sm.model = None          # chip 0 needs the room
    cfg = _model_cfg(sm)
    ids_np = _train_batch(sm, cfg)
    out = {}
    ref = None
    # the first config is the SAME pipelined model on one chip (same
    # seed, batch and microbatching): the reference the four-chip losses
    # are held to. Per-layer remat there: all 12 layers on one chip do
    # not fit b16·s1024 in f32 under the default per-stage remat (XLA
    # asked for 20.2 GB of the 15.75 GB; remat changes what is
    # recomputed, not what is computed).
    for cfg3d in (hybrid3d.Hybrid3DConfig(remat="layer"),
                  hybrid3d.Hybrid3DConfig(tp=2, pp=2),
                  hybrid3d.Hybrid3DConfig(dp=2, pp=2)):
        gc.collect()
        sm.attempt(f"hybrid {cfg3d.tag()}",
                   lambda: _hybrid_config(sm, cfg, ids_np, cfg3d, ref),
                   out, cfg3d.tag())
        if ref is None:
            ref = out[cfg3d.tag()].get("losses")
    return out


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="debug this script on the CPU at gpt_tiny with the Pallas "
             "kernels in interpret mode; stamps platform=cpu on every "
             "line and proves nothing about the chip")
    args = ap.parse_args(argv)
    sm = Smoke(args.rehearse_cpu)

    import jax

    devs = jax.devices()
    dev = devs[0]
    sm.platform = dev.platform
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    sm.say(f"jax {jax.__version__}: platform={dev.platform} "
           f"device_kind={dev.device_kind!r} count={len(devs)}")
    if sm.rehearse:
        if dev.platform != "cpu":
            print(f"--rehearse-cpu runs on the CPU only; JAX found "
                  f"platform={dev.platform}", file=sys.stderr)
            return 2
    elif dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform={dev.platform} "
              f"({dev.device_kind}), not a TPU — nothing to smoke. "
              "(--rehearse-cpu debugs the script itself.)",
              file=sys.stderr)
        return 2

    from paddle_tpu.core import compile_cache

    sm.say(f"compile cache: {compile_cache.enable()}")
    if not sm.rehearse:
        from paddle_tpu.device.peaks import DEVICE_PEAKS

        sm.check(dev.device_kind in DEVICE_PEAKS,
                 f"device_kind {dev.device_kind!r} has no row in "
                 "paddle_tpu/device/peaks.py")

    sm.phase("kernels", phase_kernels)
    sm.phase("train", phase_train)
    sm.phase("serve", phase_serve)
    if len(devs) >= 4:
        sm.phase("hybrid", phase_hybrid)

    sm.report.update(device=device, failed=sm.failed,
                     rehearsal=sm.rehearse)
    try:
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
            json.dump(sm.report, f, indent=1, default=str)
    except OSError as e:
        sm.say(f"could not write chiprun_out/chip_smoke.json: {e!r}")
    if sm.failed:
        sm.say(f"{len(sm.failed)} check(s) failed:")
        for f_ in sm.failed:
            sm.say(f"  - {f_}")
        return 1
    result = {"ok": True, "device": device}
    if sm.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
