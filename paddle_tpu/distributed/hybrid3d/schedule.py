"""GPipe microbatch schedule as ONE SPMD program.

Sibling of the lockstep 1F1B scan
(`fleet.meta_parallel.pipeline_1f1b._run_schedule`): the same
shard_map-over-'pp' design — activations hop stages on a `lax.ppermute`
ring, the backward is hand-scheduled by re-linearizing each stage from
its saved input — but with GPipe's two serialized halves (reference:
fleet/meta_parallel/pipeline_parallel.py `forward_backward_pipeline`
run with all-forward-then-all-backward ordering; Huang et al., GPipe):

    forward  : stage s forwards micro m at tick  t = m + s
    backward : stage s backwards micro m at tick t = (M−1−m) + (pp−1−s)

Each half is a fill-drain pass of M + pp − 1 ticks, so the whole step
is 2(M + pp − 1) ticks vs 1F1B's M + 2(pp − 1) — the classic GPipe
bubble — and every stage keeps ALL M micro inputs alive across the
halves, so activation memory is O(M) per stage vs 1F1B's O(pp). The
trade is simplicity and schedule symmetry; `schedule_ticks`'s docstring
derives why 1F1B is the lockstep optimum. Both schedules share
`PipelineSpecs` (mp/dp/sp composition), remat, and the MoE aux channel,
so a model can flip between them without touching its specs.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import mesh as mesh_mod
from ..fleet.meta_parallel.pipeline_1f1b import (
    PipelineSpecs, _tree_add_masked, _tree_zeros, _unflatten_like)

__all__ = ["pipeline_gpipe", "gpipe_ticks"]


def gpipe_ticks(M, pp):
    """Total scan length of the two serialized GPipe halves."""
    return 2 * (M + pp - 1)


def _run_gpipe(block_fn, loss_fn, stacked_params, post_params, x_micro,
               y_micro, pp, remat, dp_axis=None, sum_axes=None,
               aux_weight=None, quant_dp=False):
    """Inside shard_map over 'pp'. Returns (loss, aux, param_grads,
    post_grads, dx_micro) — the same contract as 1F1B's `_run_schedule`,
    with the same psum/pmean finishing, so the two schedules are
    interchangeable behind `pipeline_gpipe`/`pipeline_1f1b`."""
    from ..fleet.recompute import checkpoint_policy

    params = stacked_params
    stage = lax.axis_index("pp")
    M = x_micro.shape[0]
    Tf = M + pp - 1

    has_aux = aux_weight is not None
    aw = float(aux_weight) if has_aux else 0.0
    # identical aux-cotangent scaling story as _run_schedule: the block's
    # aux is the GLOBAL value, each rank's vjp yields a partial, and the
    # loss-grad reductions (psum over sum_axes, pmean over dp) reassemble
    # aw·d(aux_global) iff the seed carries the axis sizes
    aux_seed = aw
    if has_aux:
        if dp_axis is not None:
            aux_seed *= mesh_mod.axis_size(dp_axis)
        for ax in (sum_axes or ()):
            aux_seed *= mesh_mod.axis_size(ax)
    blk0 = (block_fn if has_aux
            else (lambda p, x: (block_fn(p, x), jnp.zeros([], jnp.float32))))
    blk = (jax.checkpoint(blk0, policy=checkpoint_policy(remat))
           if remat else blk0)
    micro_shape = x_micro.shape[1:]

    # ---------------- forward half: fill-drain, save EVERY input -------
    def fwd_tick(carry, t):
        saved, aux_sum, fwd_recv = carry
        m = t - stage
        valid = (m >= 0) & (m < M)
        m_c = jnp.clip(m, 0, M - 1)
        x_in = jnp.where(stage == 0, x_micro[m_c], fwd_recv)
        out, aux_f = blk(params, x_in)
        aux_sum = aux_sum + jnp.where(valid, aux_f, 0.0).astype(jnp.float32)
        # GPipe keeps all M inputs (the O(M) activation footprint);
        # clipped ticks must not clobber slot 0 / M−1
        saved = lax.cond(
            valid,
            lambda b: lax.dynamic_update_index_in_dim(b, x_in, m_c, 0),
            lambda b: b,
            saved,
        )
        fwd_recv = lax.ppermute(
            out, "pp", [(i, (i + 1) % pp) for i in range(pp)])
        return (saved, aux_sum, fwd_recv), None

    (saved, aux_sum, _), _ = lax.scan(
        fwd_tick,
        (jnp.zeros((M,) + micro_shape, x_micro.dtype),
         jnp.zeros([], jnp.float32),
         jnp.zeros(micro_shape, x_micro.dtype)),
        jnp.arange(Tf))

    # ---------------- backward half: drain in reverse micro order ------
    def bwd_tick(carry, t):
        pgrads, hgrads, dxs, loss_sum, bwd_recv = carry
        u = t - (pp - 1 - stage)
        valid = (u >= 0) & (u < M)
        m = M - 1 - jnp.clip(u, 0, M - 1)
        x_saved = saved[m]
        y_m = y_micro[m]

        (out_b, _aux_b), vjp_blk = jax.vjp(blk, params, x_saved)
        is_head = (stage == pp - 1) & valid

        def head_branch(ob, y):
            loss_val, vjp_head = jax.vjp(
                lambda o, hp: loss_fn(o, y, hp), ob, post_params)
            d_out, dh_l = vjp_head(jnp.ones_like(loss_val))
            return loss_val.astype(jnp.float32), d_out, dh_l

        def skip_branch(ob, y):
            return (jnp.zeros([], jnp.float32), jnp.zeros_like(ob),
                    _tree_zeros(post_params))

        loss_val, d_out, dh_l = lax.cond(
            is_head, head_branch, skip_branch, out_b, y_m)
        cot = jnp.where(is_head, d_out, bwd_recv)
        aux_cot = jnp.where(valid, jnp.float32(aux_seed), jnp.float32(0.0))
        dparams, dx = vjp_blk((cot, aux_cot))

        pgrads = _tree_add_masked(pgrads, dparams, valid)
        hgrads = jax.tree_util.tree_map(lambda a, d: a + d, hgrads, dh_l)
        loss_sum = loss_sum + loss_val
        dxs = lax.cond(
            valid & (stage == 0),
            lambda bf: lax.dynamic_update_index_in_dim(bf, dx, m, 0),
            lambda bf: bf,
            dxs,
        )
        bwd_recv = lax.ppermute(
            dx, "pp", [(i, (i - 1) % pp) for i in range(pp)])
        return (pgrads, hgrads, dxs, loss_sum, bwd_recv), None

    (pgrads, hgrads, dxs, loss_sum, _), _ = lax.scan(
        bwd_tick,
        (_tree_zeros(params), _tree_zeros(post_params),
         jnp.zeros_like(x_micro), jnp.zeros([], jnp.float32),
         jnp.zeros(micro_shape, x_micro.dtype)),
        jnp.arange(Tf))

    # ---------------- finishing reductions (same as _run_schedule) -----
    loss = lax.psum(loss_sum, "pp") / M
    aux = lax.psum(aux_sum, "pp") / M
    inv_m = 1.0 / M
    pgrads = jax.tree_util.tree_map(lambda g: g * inv_m, pgrads)
    hgrads = jax.tree_util.tree_map(
        lambda g: lax.psum(g, "pp") * inv_m, hgrads)
    dxs = lax.psum(dxs, "pp") * inv_m
    if sum_axes:
        for ax in sum_axes:
            loss = lax.psum(loss, ax)
            aux = lax.psum(aux, ax)
            pgrads = jax.tree_util.tree_map(
                lambda g, _ax=ax: lax.psum(g, _ax), pgrads)
            hgrads = jax.tree_util.tree_map(
                lambda g, _ax=ax: lax.psum(g, _ax), hgrads)
    if dp_axis is not None:
        inv_dp = 1.0 / mesh_mod.axis_size(dp_axis)
        loss = lax.pmean(loss, dp_axis)
        aux = lax.pmean(aux, dp_axis)
        if quant_dp:
            # the 1F1B schedule's int8 grad all-reduce, identically
            # (see _run_schedule — the two schedules share the
            # finishing-reduction contract)
            from ..quant_collective import quantized_pmean_tree

            pgrads, hgrads = quantized_pmean_tree(
                (pgrads, hgrads), dp_axis)
        else:
            pgrads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), pgrads)
            hgrads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), hgrads)
        dxs = dxs * inv_dp
    return loss + aw * aux, aux, pgrads, hgrads, dxs


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 5, 6, 7))
def pipeline_gpipe(block_fn, loss_fn, stacked_params, post_params, batch,
                   remat=True, specs=None, aux_weight=None):
    """Differentiable GPipe pipeline loss — `pipeline_1f1b`'s contract
    (block_fn/loss_fn/stacked/post/batch/specs/aux_weight all identical;
    see its docstring) on the all-forward-then-all-backward schedule.
    No virtual stages: interleaving is a 1F1B refinement — chunks of one
    micro would collide in GPipe's serialized halves."""
    loss, aux, _, _, _ = _gpipe_call(block_fn, loss_fn, stacked_params,
                                     post_params, batch, remat, specs,
                                     aux_weight)
    return loss if aux_weight is None else (loss, aux)


def _gpipe_call(block_fn, loss_fn, stacked_params, post_params, batch,
                remat, specs=None, aux_weight=None):
    mesh = mesh_mod.global_mesh()
    pp = mesh.shape["pp"]
    has_aux = aux_weight is not None
    aw = float(aux_weight) if has_aux else 0.0
    x_micro, y_micro = batch
    if pp == 1:
        # degenerate single-stage path: identical to 1F1B's (there is no
        # schedule left to differ on) — straight-line micro-batched vjp
        from ..fleet.recompute import checkpoint_policy

        blk0 = (block_fn if has_aux else
                (lambda p, x: (block_fn(p, x),
                               jnp.zeros([], jnp.float32))))
        blk1 = (jax.checkpoint(blk0, policy=checkpoint_policy(remat))
                if remat else blk0)

        def full(sp_, hp, xm):
            def one(x, y):
                out, a = blk1(sp_, x)
                return loss_fn(out, y, hp), a

            losses, auxs = jax.vmap(one)(xm, y_micro)
            aux = jnp.mean(auxs)
            return jnp.mean(losses) + aw * aux, aux

        (loss, aux), vjp = jax.vjp(full, stacked_params, post_params,
                                   x_micro)
        pg, hg, dx = vjp((jnp.ones_like(loss), jnp.zeros_like(aux)))
        return loss, aux, pg, hg, dx

    sp = specs if specs is not None else PipelineSpecs()
    stack_spec = _unflatten_like(
        stacked_params, sp.stacked,
        lambda a: P(*(["pp"] + [None] * (a.ndim - 1))), require_pp=True)
    post_spec = _unflatten_like(
        post_params, sp.post, lambda a: P(*([None] * a.ndim)))
    x_spec = sp.x if sp.x is not None else P(*([None] * x_micro.ndim))
    y_spec = sp.y if sp.y is not None else P(*([None] * y_micro.ndim))

    run = jax.shard_map(
        functools.partial(_run_gpipe, block_fn, loss_fn, pp=pp,
                          remat=remat, dp_axis=sp.dp_axis,
                          sum_axes=sp.sum_axes, aux_weight=aux_weight,
                          quant_dp=sp.quant_dp),
        mesh=mesh,
        in_specs=(stack_spec, post_spec, x_spec, y_spec),
        out_specs=(P(), P(), stack_spec, post_spec, x_spec),
        check_vma=False,
    )
    # ALWAYS jit (same reasoning as _pipeline_call)
    run = jax.jit(run)
    return run(stacked_params, post_params, x_micro, y_micro)


def _gpipe_fwd(block_fn, loss_fn, stacked_params, post_params, batch,
               remat, specs=None, aux_weight=None):
    loss, aux, pg, hg, dx = _gpipe_call(
        block_fn, loss_fn, stacked_params, post_params, batch, remat,
        specs, aux_weight)
    out = loss if aux_weight is None else (loss, aux)
    return out, (pg, hg, dx, batch[1])


def _gpipe_bwd(block_fn, loss_fn, remat, specs, aux_weight, res, g):
    pg, hg, dx, y = res
    if aux_weight is not None:
        g, _ = g
    scale = lambda t: jax.tree_util.tree_map(lambda a: a * g, t)
    return (scale(pg), scale(hg),
            (scale(dx), jax.tree_util.tree_map(jnp.zeros_like, y)))


pipeline_gpipe.defvjp(_gpipe_fwd, _gpipe_bwd)
