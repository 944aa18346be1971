"""Pallas kernel correctness vs the jnp reference, in interpret mode
(SURVEY.md §4 implication (a): numpy/CPU-reference tier for native kernels;
the compiled path runs on real TPU via bench.py)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels.flash_attention import flash_attention_bshd


def dense_attention(q, k, v, causal=False):
    d = q.shape[-1]
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", w, vt), 1, 2)


def _rand_qkv(b=2, s=256, h=2, d=64, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.standard_normal((b, s, h, d)).astype(dtype))
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = _rand_qkv()
        out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_multi_block_seq(self):
        q, k, v = _rand_qkv(b=1, s=512, h=1, d=64, seed=3)
        out = flash_attention_bshd(q, k, v, causal=True, block_q=128,
                                   block_k=128, interpret=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _rand_qkv(b=1, s=128, h=2, d=64, seed=7)

        def loss_fa(q, k, v):
            o = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = dense_attention(q, k, v, causal=causal)
            return jnp.sum(o * o)

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16_inputs(self):
        q, k, v = _rand_qkv(b=1, s=128, h=1, d=64, seed=9)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
        out = flash_attention_bshd(qb, kb, vb, causal=True, interpret=True)
        ref = dense_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref), rtol=5e-2,
            atol=5e-2)

    def test_functional_dispatch_uses_kernel_shapes(self):
        # the functional wrapper's eligibility gate: seq%128==0 and
        # head_dim in {64,128,256} — make sure jnp fallback handles the
        # ineligible shapes identically
        from paddle_tpu.nn.functional import scaled_dot_product_attention
        import paddle_tpu as paddle

        q, k, v = _rand_qkv(b=1, s=100, h=2, d=32, seed=11)
        out = scaled_dot_product_attention(
            paddle.to_tensor(np.asarray(q)), paddle.to_tensor(np.asarray(k)),
            paddle.to_tensor(np.asarray(v)), is_causal=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=1e-5,
                                   atol=1e-5)

    def test_ragged_seq_k_masked(self):
        # seq_k not a multiple of block_k: padded kv tail must not leak
        # into the softmax
        q, k, v = _rand_qkv(b=1, s=384, h=1, d=64, seed=13)
        out = flash_attention_bshd(q, k, v, causal=False, block_q=128,
                                   block_k=256, interpret=True)
        ref = dense_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense_multiblock(self, causal):
        # multi-block grid exercises the accumulating dq and dk/dv kernels
        q, k, v = _rand_qkv(b=1, s=384, h=2, d=64, seed=17)

        def loss_fa(q, k, v):
            o = flash_attention_bshd(q, k, v, causal=causal, block_q=128,
                                     block_k=128, interpret=True)
            return jnp.sum(o * jnp.cos(o))

        def loss_ref(q, k, v):
            o = dense_attention(q, k, v, causal=causal)
            return jnp.sum(o * jnp.cos(o))

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_ragged_blocks(self, causal):
        # seq not a multiple of either block size: the padded q tail must
        # contribute nothing to dk/dv and the padded kv tail nothing to dq
        # (both with and without the causal mask interacting with the tails)
        q, k, v = _rand_qkv(b=1, s=320, h=1, d=64, seed=19)

        def loss_fa(q, k, v):
            o = flash_attention_bshd(q, k, v, causal=causal, block_q=256,
                                     block_k=256, interpret=True)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = dense_attention(q, k, v, causal=causal)
            return jnp.sum(o * o)

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_causal_cross_length_raises(self):
        q, _, _ = _rand_qkv(b=1, s=128, h=1, d=64)
        _, k, v = _rand_qkv(b=1, s=256, h=1, d=64, seed=1)
        with pytest.raises(ValueError):
            flash_attention_bshd(q, k, v, causal=True, interpret=True)


def dense_attention_lens(q, k, v, kv_lens, causal=False):
    """Dense reference with per-batch key-padding lengths."""
    d = q.shape[-1]
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    sk = s.shape[-1]
    keep = (jnp.arange(sk)[None, :]
            < jnp.asarray(kv_lens)[:, None])[:, None, None, :]
    s = jnp.where(keep, s, -jnp.inf)
    if causal:
        sq = s.shape[-2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", w, vt), 1, 2)


class TestFlashAttentionKVLens:
    """Per-batch key-padding lengths (the padded BERT/ERNIE batch case)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, causal):
        q, k, v = _rand_qkv(b=3, s=256, h=2, d=64, seed=21)
        lens = jnp.asarray([256, 130, 77])
        out = flash_attention_bshd(q, k, v, causal=causal, block_q=128,
                                   block_k=128, interpret=True,
                                   kv_lens=lens)
        ref = dense_attention_lens(q, k, v, lens, causal=causal)
        # rows can only attend to the valid kv prefix, so compare there
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_dense_and_zero_on_pad(self):
        q, k, v = _rand_qkv(b=2, s=256, h=2, d=64, seed=22)
        lens = jnp.asarray([200, 64])

        def loss_fa(q, k, v):
            o = flash_attention_bshd(q, k, v, block_q=128, block_k=128,
                                     interpret=True, kv_lens=lens)
            return jnp.sum(o * jnp.cos(o))

        def loss_ref(q, k, v):
            o = dense_attention_lens(q, k, v, lens)
            return jnp.sum(o * jnp.cos(o))

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        # padded k/v rows must get exactly zero gradient
        dk, dv = np.asarray(g_fa[1]), np.asarray(g_fa[2])
        assert np.all(dk[0, 200:] == 0) and np.all(dk[1, 64:] == 0)
        assert np.all(dv[0, 200:] == 0) and np.all(dv[1, 64:] == 0)

    def test_full_lens_equals_no_lens(self):
        q, k, v = _rand_qkv(b=2, s=256, h=1, d=64, seed=23)
        full = flash_attention_bshd(q, k, v, block_q=128, block_k=128,
                                    interpret=True,
                                    kv_lens=jnp.asarray([256, 256]))
        plain = flash_attention_bshd(q, k, v, block_q=128, block_k=128,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(full), np.asarray(plain),
                                   rtol=1e-6, atol=1e-6)


def test_sdpa_kv_lens_dispatches_to_flash(monkeypatch):
    """When the kernel is eligible, SDPA with kv_lens must route to the
    flash kernel and pass the lengths through (spied; the kernel itself
    is exercised in interpret mode above)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional import attention as attn_mod
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    calls = {}

    def spy(q, k, v, causal=False, kv_lens=None, **kw):
        calls["kv_lens"] = kv_lens
        calls["causal"] = causal
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(attn_mod, "_pallas_eligible", lambda q, k: True)
    monkeypatch.setattr(fa, "flash_attention_bshd", spy)
    q = paddle.to_tensor(np.zeros((2, 128, 2, 64), np.float32))
    lens = paddle.to_tensor(np.array([128, 60]))
    F.scaled_dot_product_attention(q, q, q, kv_lens=lens)
    assert calls["kv_lens"] is not None
    np.testing.assert_array_equal(np.asarray(calls["kv_lens"]), [128, 60])


def test_kv_lens_oversized_clamped_and_zero_row():
    """Oversized lengths clamp to seq_k (no uninitialized-tail leak even
    with a ragged buffer) and zero-length rows return exact zeros."""
    q, k, v = _rand_qkv(b=2, s=384, h=1, d=64, seed=24)  # 384 % 256 != 0
    out = flash_attention_bshd(q, k, v, block_q=128, block_k=256,
                               interpret=True,
                               kv_lens=jnp.asarray([999, 0]))
    ref = dense_attention(q, k, v)  # batch 0: full attention
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(ref)[0],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out)[1] == 0.0)
    assert np.all(np.isfinite(np.asarray(out)))


def test_sdpa_dense_fallback_zero_length_row_no_nan():
    """The jnp kv_lens fallback must match the kernel's zero-output
    convention for all-pad rows instead of producing NaN."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((2, 8, 2, 16)
                                                 ).astype(np.float32),
        stop_gradient=False)
    lens = paddle.to_tensor(np.array([8, 0]))
    out = F.scaled_dot_product_attention(x, x, x, kv_lens=lens)
    o = out.numpy()
    assert np.all(np.isfinite(o))
    assert np.all(o[1] == 0.0)
    out.sum().backward()
    assert np.all(np.isfinite(x.grad.numpy()))


# ------------------------------------------------- the resident kernels
# A 128-lane block of [B, S, H*D] with its whole sequence in VMEM: two
# heads a block at head_dim 64 (kept apart by a lane mask), one at 128.

from paddle_tpu.ops.pallas_kernels import flash_attention as _fa  # noqa: E402


def _launches():
    m = _fa._LAUNCHES
    return {path: m.labels(path=path).value for path in ("resident", "tiled")}


def _resident_vjp(q, k, v, g, causal, lens, tiles):
    """(out, dq, dk, dv) through the entry (tiles None: the chooser's own)
    or through the resident kernels at the named (tile, cut)."""
    b, s, h, d = q.shape
    if tiles is None:
        before = _launches()

        def f(q, k, v):
            return flash_attention_bshd(q, k, v, causal=causal,
                                        kv_lens=lens, interpret=True)
    else:
        def f(q, k, v):
            flat = [t.reshape(b, s, h * d) for t in (q, k, v)]
            return _fa._flash_attention_resident(
                *flat, lens, causal, d, tiles[0], tiles[1],
                True).reshape(b, s, h, d)

    out, vjp = jax.vjp(f, q, k, v)
    grads = vjp(g)
    if tiles is None:
        # the counter reads what the chooser chose: forward and backward
        after = _launches()
        assert after["resident"] - before["resident"] == 2
        assert after["tiled"] == before["tiled"]
    return (out,) + grads


def _dense_vjp(q, k, v, g, causal, lens):
    from paddle_tpu.nn.functional.attention import dense_attention_bshd

    def f(q, k, v):
        mask = None
        if lens is not None:
            # a zero length: attend to one column so that no softmax row
            # is empty, then zero the row, as the kernels do
            mask = (jnp.arange(k.shape[1])[None, :]
                    < jnp.maximum(lens, 1)[:, None])[:, None, None, :]
        out = dense_attention_bshd(q, k, v, is_causal=causal, attn_mask=mask)
        if lens is not None:
            out = jnp.where((lens > 0)[:, None, None, None], out, 0.0)
        return out

    out, vjp = jax.vjp(f, *(t.astype(jnp.float32) for t in (q, k, v)))
    return (out,) + vjp(g.astype(jnp.float32))


# S 256 at (256, 128): a diagonal cut finer than the tile, so the strip
# beside the triangle runs; S 384: the chooser's (128, 128), three tiles,
# so the loops over interior tiles run more than once
_RESIDENT_SEQ = [(256, (256, 128)), (384, None)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("with_lens", [False, True])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,tiles", _RESIDENT_SEQ)
@pytest.mark.parametrize("heads,dim", [(4, 64), (2, 128)])
def test_resident_matches_dense(heads, dim, seq, tiles, causal, with_lens,
                                dtype):
    """out, dq, dk, dv of the resident kernels against dense attention:
    two lane blocks of a head pair (D 64, H 4) and of one head (D 128,
    H 2); with kv_lens a full, a partial and a ZERO length."""
    b = 3 if with_lens else 1
    rng = np.random.default_rng(31)
    q, k, v, g = (jnp.asarray(rng.standard_normal((b, seq, heads, dim)),
                              jnp.dtype(dtype)) for _ in range(4))
    lens = jnp.asarray([seq, 130, 0], jnp.int32) if with_lens else None
    got = _resident_vjp(q, k, v, g, causal, lens, tiles)
    want = _dense_vjp(q, k, v, g, causal, lens)
    # bf16: the outputs are rounded to bf16, and p and ds before their
    # products as in the tiled kernels (read: out <= 0.008, grads <= 0.021)
    tol = ((2e-5, 1e-4) if dtype == "float32" else (2e-2, 5e-2))
    for name, a, w, t in zip(("out", "dq", "dk", "dv"), got, want,
                             (tol[0],) + (tol[1],) * 3):
        assert a.dtype == jnp.dtype(dtype), name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(w),
                                   rtol=t, atol=t, err_msg=name)
    if with_lens:
        dk, dv = np.asarray(got[2], np.float32), np.asarray(got[3], np.float32)
        # key-padding rows get exactly zero gradient
        assert np.all(dk[1, 130:] == 0) and np.all(dv[1, 130:] == 0)
        assert np.all(dk[2] == 0) and np.all(dv[2] == 0)
        assert np.all(np.asarray(got[0], np.float32)[2] == 0)


@pytest.mark.parametrize("causal", [False, True])
def test_resident_lane_mask_leaks_nothing_across_the_pair(causal):
    """Heads 0 and 1 share one 128-lane block: head 1's output and
    gradients are bit-for-bit the same when head 0's q, k, v and dO are
    replaced by (large) noise."""
    rng = np.random.default_rng(32)
    q, k, v, g = (jnp.asarray(rng.standard_normal((1, 256, 2, 64)),
                              jnp.bfloat16) for _ in range(4))
    noisy = [t.at[:, :, 0].set(jnp.asarray(
        1e3 * rng.standard_normal((1, 256, 64)), jnp.bfloat16))
        for t in (q, k, v, g)]
    clean = _resident_vjp(q, k, v, g, causal, None, (256, 128))
    other = _resident_vjp(*noisy, causal, None, (256, 128))
    for a, b in zip(clean, other):
        np.testing.assert_array_equal(np.asarray(a[:, :, 1], np.float32),
                                      np.asarray(b[:, :, 1], np.float32))


@pytest.mark.parametrize("shape,dtype,blocks,resident", [
    ((16, 1024, 16, 64), "bfloat16", None, True),    # gpt2-medium's step
    ((4, 2048, 16, 128), "bfloat16", None, True),    # cerebras-gpt-1.3b's
    ((2, 512, 12, 64), "float32", None, True),
    ((2, 256, 3, 64), "bfloat16", None, False),      # half a lane block
    ((2, 256, 2, 256), "bfloat16", None, False),     # head_dim 256
    ((2, 320, 2, 64), "bfloat16", None, False),      # 320 tiles by no 128
    ((1, 8192, 2, 64), "bfloat16", None, False),     # 36 MiB of blocks
    ((1, 4096, 2, 64), "float32", None, False),      # 34 MiB of blocks
    ((1, 4096, 2, 64), "bfloat16", None, True),      # 18 MiB: inside 20
    ((2, 256, 2, 64), "bfloat16", (128, 128), False),  # tiles were named
])
def test_resident_chooser(shape, dtype, blocks, resident):
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    args = (x, x, x) + (blocks or ())
    assert _fa.resident_eligible(*args) is resident
    if resident:
        assert (_fa._resident_block_bytes(shape[1], x.dtype.itemsize)
                <= _fa.RESIDENT_VMEM_BUDGET)


def test_resident_chooser_cross_attention_stays_tiled():
    q = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 512, 2, 64), jnp.bfloat16)
    assert not _fa.resident_eligible(q, kv, kv)


@pytest.mark.parametrize("case", ["lse_bhd", "odd_heads", "named_tiles"])
def test_tiled_paths_count_as_tiled(case):
    """What stays on the tiled kernels says so in the counter: the
    lse-returning entry ring attention uses, an odd head count at D 64,
    and a call that names its tiles."""
    q, k, v = _rand_qkv(b=1, s=128, h=3 if case == "odd_heads" else 2, d=64,
                        seed=33)
    before = _launches()
    if case == "lse_bhd":
        flat = [jnp.swapaxes(t, 1, 2).reshape(2, 128, 64) for t in (q, k, v)]
        jax.grad(lambda a, b, c: jnp.sum(_fa.flash_attention_lse_bhd(
            a, b, c, True, 128, 128, True)[0]))(*flat)
    else:
        kw = dict(block_q=128, block_k=128) if case == "named_tiles" else {}
        jax.grad(lambda a, b, c: jnp.sum(flash_attention_bshd(
            a, b, c, causal=True, interpret=True, **kw)))(q, k, v)
    after = _launches()
    assert after["resident"] == before["resident"]
    assert after["tiled"] - before["tiled"] == 3      # forward, dq, dk/dv
