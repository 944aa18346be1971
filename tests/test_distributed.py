"""Distributed tests on the virtual 8-device CPU mesh
(SURVEY.md §4 implication (b)+(c): multi-device tests without a cluster;
serial-vs-parallel numerical equivalence for every parallelism mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn
from paddle_tpu.distributed import mesh as mesh_mod

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


@pytest.fixture(autouse=True)
def fresh_mesh():
    mesh_mod.reset_mesh()
    yield
    mesh_mod.reset_mesh()


class TestMeshAndGroups:
    def test_init_mesh_shapes(self):
        m = mesh_mod.init_mesh(dp=2, mp=4)
        assert m.shape["dp"] == 2 and m.shape["mp"] == 4

    def test_hcg_topology(self):
        import paddle_tpu.distributed.fleet as fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 2, "sharding_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_communicate_group()
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.nranks == 8

    def test_topology_comm_lists(self):
        from paddle_tpu.distributed.fleet import CommunicateTopology

        topo = CommunicateTopology(("data", "pipe", "sharding", "model"),
                                   (2, 2, 1, 2))
        mp_lists = topo.get_comm_list("model")
        assert len(mp_lists) == 4 and all(len(g) == 2 for g in mp_lists)
        assert topo.get_rank(data=1, pipe=0, sharding=0, model=1) in range(8)


class TestCollectives:
    def test_allreduce_spmd(self):
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(axes=("dp",))

        def fn(x):
            t = paddle.Tensor(x)
            return dist.all_reduce(t, group=g)._value

        f = dist.spmd(fn, in_specs=P("dp"), out_specs=P("dp"),
                      group_axes=("dp",))
        out = f(jnp.arange(8.0))
        np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))

    def test_allgather_spmd(self):
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(axes=("dp",))

        def fn(x):
            return dist.all_gather(None, paddle.Tensor(x), group=g)._value

        f = dist.spmd(fn, in_specs=P("dp"), out_specs=P(None),
                      group_axes=("dp",))
        out = f(jnp.arange(8.0).reshape(8, 1))
        # every device sees the full gathered vector
        np.testing.assert_allclose(np.asarray(out).ravel(), np.arange(8.0))

    def test_reduce_scatter_spmd(self):
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(axes=("dp",))

        def fn(x):
            return dist.reduce_scatter(paddle.Tensor(x), group=g)._value

        f = dist.spmd(fn, in_specs=P(None), out_specs=P("dp"),
                      group_axes=("dp",))
        out = f(jnp.ones((8, 4)))
        np.testing.assert_allclose(np.asarray(out), np.full((8, 4), 8.0))

    def test_p2p_shift_ring(self):
        mesh_mod.init_mesh(pp=8)
        g = dist.new_group(axes=("pp",))

        def fn(x):
            return dist.p2p_shift(paddle.Tensor(x), group=g)._value

        f = dist.spmd(fn, in_specs=P("pp"), out_specs=P("pp"),
                      group_axes=("pp",))
        out = np.asarray(f(jnp.arange(8.0)))
        np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))

    def test_serial_identity_fallback(self):
        # default 1-device mesh: collectives are identity
        t = paddle.to_tensor(np.ones(3, "float32"))
        out = dist.all_reduce(t)
        np.testing.assert_allclose(out.numpy(), np.ones(3))

    def test_reduce_to_dst_masks_non_roots(self):
        # reference collective.py:849: ONLY dst receives the reduction,
        # every other rank keeps its original tensor
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(axes=("dp",))

        def fn(x):
            return dist.reduce(paddle.Tensor(x), dst=2, group=g)._value

        f = dist.spmd(fn, in_specs=P("dp"), out_specs=P("dp"),
                      group_axes=("dp",))
        out = np.asarray(f(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[2] = 28.0
        np.testing.assert_allclose(out, expect)

    def test_rank_subset_group_allreduce(self):
        # new_group(ranks=[1,3,5]): members reduce among themselves,
        # non-members untouched (reference subgroup semantics)
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(ranks=[1, 3, 5], axes=("dp",))
        assert g.nranks == 3
        assert g.get_group_rank(3) == 1 and g.get_group_rank(2) == -1

        def fn(x):
            return dist.all_reduce(paddle.Tensor(x), group=g)._value

        f = dist.spmd(fn, in_specs=P("dp"), out_specs=P("dp"),
                      group_axes=("dp",))
        out = np.asarray(f(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[[1, 3, 5]] = 1.0 + 3.0 + 5.0
        np.testing.assert_allclose(out, expect)

    def test_rank_subset_group_max_and_avg(self):
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(ranks=[0, 4, 6], axes=("dp",))

        def fmax(x):
            return dist.all_reduce(paddle.Tensor(x), op=dist.ReduceOp.MAX,
                                   group=g)._value

        out = np.asarray(dist.spmd(fmax, in_specs=P("dp"),
                                   out_specs=P("dp"),
                                   group_axes=("dp",))(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[[0, 4, 6]] = 6.0
        np.testing.assert_allclose(out, expect)

        def fimax(x):  # integer max: identity must be iinfo.min, not -inf
            return dist.all_reduce(paddle.Tensor(x), op=dist.ReduceOp.MAX,
                                   group=g)._value

        out = np.asarray(dist.spmd(fimax, in_specs=P("dp"),
                                   out_specs=P("dp"), group_axes=("dp",))(
            jnp.arange(8, dtype=jnp.int32)))
        expect_i = np.arange(8)
        expect_i[[0, 4, 6]] = 6
        np.testing.assert_array_equal(out, expect_i)

        def favg(x):
            return dist.all_reduce(paddle.Tensor(x), op=dist.ReduceOp.AVG,
                                   group=g)._value

        out = np.asarray(dist.spmd(favg, in_specs=P("dp"),
                                   out_specs=P("dp"),
                                   group_axes=("dp",))(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[[0, 4, 6]] = (0.0 + 4.0 + 6.0) / 3
        np.testing.assert_allclose(out, expect)

    def test_rank_subset_group_broadcast_and_reduce(self):
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(ranks=[2, 5, 7], axes=("dp",))

        def fb(x):  # src=5 is a GLOBAL rank (reference get_group_rank)
            return dist.broadcast(paddle.Tensor(x), src=5, group=g)._value

        out = np.asarray(dist.spmd(fb, in_specs=P("dp"),
                                   out_specs=P("dp"),
                                   group_axes=("dp",))(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[[2, 5, 7]] = 5.0
        np.testing.assert_allclose(out, expect)

        # a non-member src is an error, not a silent reinterpretation
        with pytest.raises(ValueError, match="not a member"):
            dist.spmd(
                lambda x: dist.broadcast(
                    paddle.Tensor(x), src=3, group=g)._value,
                in_specs=P("dp"), out_specs=P("dp"),
                group_axes=("dp",))(jnp.arange(8.0))

        def fr(x):  # dst=7 is a GLOBAL rank
            return dist.reduce(paddle.Tensor(x), dst=7, group=g)._value

        out = np.asarray(dist.spmd(fr, in_specs=P("dp"),
                                   out_specs=P("dp"),
                                   group_axes=("dp",))(jnp.arange(8.0)))
        expect = np.arange(8.0)
        expect[7] = 2.0 + 5.0 + 7.0
        np.testing.assert_allclose(out, expect)

    def test_scatter_rank_subset_group(self):
        # subgroup scatter: src is a GLOBAL rank, chunks deal only to
        # members (len(ranks) chunks), non-members receive zeros
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(ranks=[1, 4, 6], axes=("dp",))

        def fn(x):
            return dist.scatter(paddle.Tensor(x[0]), src=1, group=g)._value

        f = dist.spmd(fn, in_specs=P("dp", None), out_specs=P("dp"),
                      group_axes=("dp",))
        full = np.tile(np.arange(6.0)[None, :], (8, 1))
        full += 1000.0 * np.arange(8.0)[:, None]  # rank-divergent
        out = np.asarray(f(jnp.asarray(full))).reshape(8, 2)
        # src = global rank 1 (group rank 0); its vector is arange(6)+1000
        expect = np.zeros((8, 2))
        expect[1] = [1000.0, 1001.0]
        expect[4] = [1002.0, 1003.0]
        expect[6] = [1004.0, 1005.0]
        np.testing.assert_allclose(out, expect)

    def test_scatter_follows_src(self):
        # rank-divergent inputs: every rank must get a slice of SRC's
        # tensor (reference collective.py:1140), not of its own
        mesh_mod.init_mesh(dp=8)
        g = dist.new_group(axes=("dp",))

        def fn(x):
            # x: (1, 8) shard -> this rank's own full vector
            return dist.scatter(paddle.Tensor(x[0]), src=3, group=g)._value

        f = dist.spmd(fn, in_specs=P("dp", None), out_specs=P("dp"),
                      group_axes=("dp",))
        # per-rank input row r: full vector = arange(8) + 100*r
        full = np.arange(8.0)[None, :] + 100.0 * np.arange(8.0)[:, None]
        out = np.asarray(f(jnp.asarray(full)))
        # src=3's tensor is arange(8)+300; rank r receives element r
        np.testing.assert_allclose(out.ravel(), np.arange(8.0) + 300.0)


def _copy_net(dst, src):
    dst.set_state_dict({k: v.numpy() for k, v in src.state_dict().items()})


class TestDataParallelEquivalence:
    def test_dp_step_matches_serial(self):
        """batch sharded over dp == serial large-batch step (the EagerReducer
        parity test, SURVEY §4(c))."""
        paddle.seed(7)
        mesh_mod.init_mesh(dp=8)
        net_p = nn.Linear(16, 4)
        net_s = nn.Linear(16, 4)
        _copy_net(net_s, net_p)
        opt_p = paddle.optimizer.SGD(0.1, parameters=net_p.parameters())
        opt_s = paddle.optimizer.SGD(0.1, parameters=net_s.parameters())

        def loss_fn(model, x, y):
            return nn.functional.mse_loss(model(x), y)

        step = dist.DistributedTrainStep(net_p, loss_fn, opt_p)
        x = np.random.randn(32, 16).astype("float32")
        y = np.random.randn(32, 4).astype("float32")
        for _ in range(3):
            l_p = step(paddle.to_tensor(x), paddle.to_tensor(y))
            l_s = loss_fn(net_s, paddle.to_tensor(x), paddle.to_tensor(y))
            l_s.backward()
            opt_s.step()
            opt_s.clear_grad()
        np.testing.assert_allclose(l_p.numpy(), l_s.numpy(), rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(net_p.weight.numpy(), net_s.weight.numpy(),
                                   rtol=1e-4, atol=1e-5)


class TestTensorParallelEquivalence:
    def test_mp_layers_match_serial(self):
        """ColumnParallel→RowParallel == two plain Linears
        (reference test hybrid_parallel_mp_layers.py)."""
        paddle.seed(11)
        mesh_mod.init_mesh(mp=8)
        from paddle_tpu.distributed.fleet.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)

        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 8, input_is_parallel=True)
        ref1 = nn.Linear(16, 32)
        ref2 = nn.Linear(32, 8)
        ref1.weight._value = col.weight._value
        ref1.bias._value = col.bias._value
        ref2.weight._value = row.weight._value
        ref2.bias._value = row.bias._value
        x = paddle.randn([4, 16])
        out_p = row(col(x))
        out_s = ref2(ref1(x))
        np.testing.assert_allclose(out_p.numpy(), out_s.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_mp_training_matches_serial(self):
        paddle.seed(13)
        mesh_mod.init_mesh(mp=8)
        from paddle_tpu.distributed.fleet.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)

        class MPNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.c = ColumnParallelLinear(8, 32, gather_output=False)
                self.r = RowParallelLinear(32, 8, input_is_parallel=True)

            def forward(self, x):
                return self.r(nn.functional.relu(self.c(x)))

        class SNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.c = nn.Linear(8, 32)
                self.r = nn.Linear(32, 8)

            def forward(self, x):
                return self.r(nn.functional.relu(self.c(x)))

        mp = MPNet()
        sn = SNet()
        # copies, not aliases: the compiled step donates mp's param buffers
        sn.c.weight._value = jnp.array(mp.c.weight._value)
        sn.c.bias._value = jnp.array(mp.c.bias._value)
        sn.r.weight._value = jnp.array(mp.r.weight._value)
        sn.r.bias._value = jnp.array(mp.r.bias._value)
        opt_p = paddle.optimizer.Adam(1e-2, parameters=mp.parameters())
        opt_s = paddle.optimizer.Adam(1e-2, parameters=sn.parameters())

        def loss_fn(model, x, y):
            return nn.functional.mse_loss(model(x), y)

        step = dist.DistributedTrainStep(mp, loss_fn, opt_p)
        x = np.random.randn(8, 8).astype("float32")
        y = np.random.randn(8, 8).astype("float32")
        for _ in range(3):
            l_p = step(paddle.to_tensor(x), paddle.to_tensor(y))
            l_s = loss_fn(sn, paddle.to_tensor(x), paddle.to_tensor(y))
            l_s.backward()
            opt_s.step()
            opt_s.clear_grad()
        np.testing.assert_allclose(l_p.numpy(), l_s.numpy(), rtol=1e-3,
                                   atol=1e-4)


class TestZeroSharding:
    def test_zero2_matches_serial(self):
        paddle.seed(17)
        mesh_mod.init_mesh(sharding=8)
        net_p = nn.Linear(16, 8)
        net_s = nn.Linear(16, 8)
        _copy_net(net_s, net_p)
        opt_p = paddle.optimizer.Adam(1e-2, parameters=net_p.parameters())
        opt_s = paddle.optimizer.Adam(1e-2, parameters=net_s.parameters())
        from paddle_tpu.distributed.sharding import group_sharded_parallel

        net_p, opt_p = group_sharded_parallel(net_p, opt_p, level="os_g")

        def loss_fn(model, x, y):
            return nn.functional.mse_loss(model(x), y)

        step = dist.DistributedTrainStep(net_p, loss_fn, opt_p,
                                         zero_level="os_g")
        x = np.random.randn(16, 16).astype("float32")
        y = np.random.randn(16, 8).astype("float32")
        for _ in range(3):
            l_p = step(paddle.to_tensor(x), paddle.to_tensor(y))
            l_s = loss_fn(net_s, paddle.to_tensor(x), paddle.to_tensor(y))
            l_s.backward()
            opt_s.step()
            opt_s.clear_grad()
        np.testing.assert_allclose(net_p.weight.numpy(),
                                   net_s.weight.numpy(), rtol=1e-4,
                                   atol=1e-5)

    def test_zero3_param_sharding(self):
        paddle.seed(19)
        mesh_mod.init_mesh(sharding=8)
        net = nn.Linear(64, 8)
        opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
        from paddle_tpu.distributed.sharding import group_sharded_parallel

        net, opt = group_sharded_parallel(net, opt, level="p_g_os")
        assert net.weight._pspec is not None
        assert "sharding" in tuple(net.weight._pspec)

        def loss_fn(model, x, y):
            return nn.functional.mse_loss(model(x), y)

        step = dist.DistributedTrainStep(net, loss_fn, opt,
                                         zero_level="p_g_os")
        x = paddle.randn([16, 64])
        y = paddle.randn([16, 8])
        l0 = float(step(x, y).numpy())
        for _ in range(10):
            l = step(x, y)
        assert float(l.numpy()) < l0


class TestRingAttention:
    @pytest.mark.slow
    def test_ring_matches_dense(self):
        mesh_mod.init_mesh(sp=8)
        b, s, h, d = 2, 32, 4, 8
        rng = np.random.default_rng(0)
        q = rng.standard_normal((b, s, h, d), dtype=np.float32)
        k = rng.standard_normal((b, s, h, d), dtype=np.float32)
        v = rng.standard_normal((b, s, h, d), dtype=np.float32)

        for causal in (False, True):
            f = dist.spmd(
                lambda qq, kk, vv: dist.ring_attention(
                    qq, kk, vv, causal=causal),
                in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                out_specs=P(None, "sp"), group_axes=("sp",))
            out = np.asarray(f(q, k, v))
            ref = _dense_attention(q, k, v, causal)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_ring_flash_matches_dense_and_grads(self):
        """Pallas-flash ring attention (per-block kernel + lse merge +
        causal block skipping) must match dense attention in values AND
        gradients — the lse cotangent path through the kernel's custom
        vjp is what this pins."""
        import jax

        mesh_mod.init_mesh(sp=8)
        b, s, h, d = 1, 64, 2, 8
        rng = np.random.default_rng(3)
        q = rng.standard_normal((b, s, h, d), dtype=np.float32)
        k = rng.standard_normal((b, s, h, d), dtype=np.float32)
        v = rng.standard_normal((b, s, h, d), dtype=np.float32)

        for causal in (False, True):
            f = dist.spmd(
                lambda qq, kk, vv: dist.ring_flash_attention(
                    qq, kk, vv, causal=causal, interpret=True),
                in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                out_specs=P(None, "sp"), group_axes=("sp",))
            out = np.asarray(f(q, k, v))
            ref = _dense_attention(q, k, v, causal)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

            def loss_ring(qq, kk, vv):
                f_in = dist.spmd(
                    lambda a, bb, c: dist.ring_flash_attention(
                        a, bb, c, causal=causal, interpret=True),
                    in_specs=(P(None, "sp"), P(None, "sp"),
                              P(None, "sp")),
                    out_specs=P(None, "sp"), group_axes=("sp",))
                o = f_in(qq, kk, vv)
                return (jnp.asarray(o) * w_probe).sum()

            def loss_dense(qq, kk, vv):
                o = _dense_attention_jnp(qq, kk, vv, causal)
                return (o * w_probe).sum()

            w_probe = jnp.asarray(
                rng.standard_normal((b, s, h, d)).astype(np.float32))
            g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
            for gr, gd in zip(g_ring, g_dense):
                np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                           rtol=3e-4, atol=3e-4)

    def test_ulysses_matches_dense(self):
        mesh_mod.init_mesh(sp=8)
        b, s, h, d = 2, 32, 8, 4
        rng = np.random.default_rng(1)
        q = rng.standard_normal((b, s, h, d), dtype=np.float32)
        k = rng.standard_normal((b, s, h, d), dtype=np.float32)
        v = rng.standard_normal((b, s, h, d), dtype=np.float32)
        f = dist.spmd(
            lambda qq, kk, vv: dist.ulysses_attention(qq, kk, vv,
                                                      causal=True),
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"), group_axes=("sp",))
        out = np.asarray(f(q, k, v))
        ref = _dense_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def _dense_attention_jnp(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        s_len = q.shape[1]
        mask = jnp.tril(jnp.ones((s_len, s_len), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vt)
    return jnp.swapaxes(out, 1, 2)


def _dense_attention(q, k, v, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    qt = np.swapaxes(q, 1, 2)
    kt = np.swapaxes(k, 1, 2)
    vt = np.swapaxes(v, 1, 2)
    scores = np.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        s = scores.shape[-1]
        mask = np.tril(np.ones((s, s), bool))
        scores = np.where(mask, scores, -1e30)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bhkd->bhqd", w, vt)
    return np.swapaxes(out, 1, 2).astype(np.float32)


class TestPipeline:
    def test_spmd_pipeline_matches_sequential(self):
        mesh_mod.init_mesh(pp=8)
        from paddle_tpu.distributed.fleet.meta_parallel import spmd_pipeline

        rng = np.random.default_rng(3)
        n_stages, micro, n_micro, dim = 8, 2, 4, 16
        Ws = rng.standard_normal((n_stages, dim, dim),
                                 dtype=np.float32) * 0.2
        xs = rng.standard_normal((n_micro, micro, dim), dtype=np.float32)

        def block_fn(params, x):
            return jnp.tanh(x @ params)

        out = jax.jit(lambda W, x: spmd_pipeline(block_fn, W, x))(
            jnp.asarray(Ws), jnp.asarray(xs))
        # sequential reference
        ref = xs.copy()
        for i in range(n_stages):
            ref = np.tanh(ref @ Ws[i])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-5)

    def test_spmd_pipeline_grads(self):
        mesh_mod.init_mesh(pp=8)
        from paddle_tpu.distributed.fleet.meta_parallel import spmd_pipeline

        rng = np.random.default_rng(4)
        Ws = jnp.asarray(rng.standard_normal((8, 8, 8),
                                             dtype=np.float32) * 0.3)
        xs = jnp.asarray(rng.standard_normal((4, 2, 8), dtype=np.float32))

        def block_fn(params, x):
            return jnp.tanh(x @ params)

        def loss(W):
            return spmd_pipeline(block_fn, W, xs).sum()

        g = jax.jit(jax.grad(loss))(Ws)
        # numeric check on one element
        eps = 1e-3
        Wp = Ws.at[3, 0, 0].add(eps)
        Wm = Ws.at[3, 0, 0].add(-eps)
        num = (jax.jit(loss)(Wp) - jax.jit(loss)(Wm)) / (2 * eps)
        np.testing.assert_allclose(float(g[3, 0, 0]), float(num), rtol=2e-2,
                                   atol=1e-3)

    def test_pipeline_layer_api(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer)

        descs = [LayerDesc(nn.Linear, 8, 8) for _ in range(6)]
        pl = PipelineLayer(descs, num_stages=2)
        assert pl.segments == [0, 3, 6]
        out = pl(paddle.randn([2, 8]))
        assert out.shape == [2, 8]
        assert len(pl.get_stage_layers(0)) == 3

    def test_pipeline_parallel_train_batch(self):
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer)
        from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \
            import PipelineParallel

        pl = PipelineLayer([LayerDesc(nn.Linear, 8, 8),
                            LayerDesc(nn.Linear, 8, 4)], num_stages=1,
                           loss_fn=nn.MSELoss())
        opt = paddle.optimizer.SGD(0.05, parameters=pl.parameters())

        class S:
            pipeline_configs = {"accumulate_steps": 4}

        pp = PipelineParallel(pl, None, S())
        x = paddle.randn([8, 8])
        y = paddle.randn([8, 4])
        l0 = float(pp.train_batch((x, y), opt).numpy())
        for _ in range(20):
            l = float(pp.train_batch((x, y), opt).numpy())
        assert l < l0


class TestMoE:
    def test_moe_forward_backward(self):
        mesh_mod.reset_mesh()
        from paddle_tpu.distributed.moe import MoELayer

        moe = MoELayer(d_model=16, d_hidden=32, num_experts=4, topk=2,
                       capacity_factor=2.0)
        x = paddle.randn([4, 6, 16])
        out = moe(x)
        assert out.shape == [4, 6, 16]
        out.sum().backward()
        assert moe.w1.grad is not None
        assert moe.gate.gate.weight.grad is not None

    def test_moe_capacity_routing_total_mass(self):
        mesh_mod.reset_mesh()
        from paddle_tpu.distributed.moe import MoELayer

        # identity-ish experts: with generous capacity every token routed
        moe = MoELayer(d_model=8, d_hidden=8, num_experts=2, topk=1,
                       capacity_factor=4.0)
        x = paddle.randn([32, 8])
        out = moe(x)
        assert np.isfinite(out.numpy()).all()


class TestRecompute:
    def test_recompute_grads_match(self):
        from paddle_tpu.distributed import recompute

        net = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 8))
        x = paddle.randn([4, 8])
        out = recompute(net, x)
        out.sum().backward()
        g_rc = net[0].weight.grad.numpy().copy()
        net[0].weight.grad = None
        net(x).sum().backward()
        np.testing.assert_allclose(g_rc, net[0].weight.grad.numpy(),
                                   rtol=1e-5, atol=1e-6)

    def test_recompute_policy_grads_match(self):
        """Every named policy changes only WHAT the backward saves —
        gradients must be identical."""
        import pytest

        from paddle_tpu.distributed import recompute
        from paddle_tpu.distributed.fleet.recompute import checkpoint_policy

        net = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 8))
        x = paddle.randn([4, 8])
        net(x).sum().backward()
        want = net[0].weight.grad.numpy().copy()
        for pol in ("dots_saveable", "nothing_saveable",
                    "everything_saveable"):
            net[0].weight.grad = None
            recompute(net, x, policy=pol).sum().backward()
            np.testing.assert_allclose(
                net[0].weight.grad.numpy(), want, rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError):
            checkpoint_policy("bogus")


class TestFusedHeadSPMD:
    def test_fused_head_loss_dp_parity(self):
        """fused_linear_cross_entropy (scan over token blocks) must be
        SPMD-safe: dp=8 DistributedTrainStep losses == serial TrainStep
        losses with the same seed."""
        from paddle_tpu.text.models import GPTForCausalLM
        from paddle_tpu.text.models.gpt import GPTConfig

        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                        num_heads=2, max_seq_len=32)
        ids_np = np.random.default_rng(0).integers(
            0, 64, (8, 9)).astype(np.int32)

        paddle.seed(7)
        m0 = GPTForCausalLM(cfg)
        o0 = paddle.optimizer.AdamW(1e-3, parameters=m0.parameters())
        s0 = paddle.jit.TrainStep(m0, lambda m, i: m.fused_head_loss(i), o0)
        ref = [float(s0(paddle.to_tensor(ids_np)).numpy())
               for _ in range(3)]

        mesh_mod.init_mesh(dp=8)
        paddle.seed(7)
        m1 = GPTForCausalLM(cfg)
        o1 = paddle.optimizer.AdamW(1e-3, parameters=m1.parameters())
        s1 = dist.DistributedTrainStep(
            m1, lambda m, i: m.fused_head_loss(i), o1)
        got = [float(s1(paddle.to_tensor(ids_np)).numpy())
               for _ in range(3)]
        np.testing.assert_allclose(ref, got, rtol=1e-4)
