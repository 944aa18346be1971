"""1F1B pipeline parallelism as ONE SPMD program.

TPU-native re-design of the reference 1F1B runtime
(reference: fleet/meta_parallel/pipeline_parallel.py:105
`forward_backward_pipeline` — warmup fwd / steady 1F1B / cooldown bwd over
NCCL p2p, with `PipelineParallelWithInterleave:416` for virtual stages).

Design (no per-rank processes, no send/recv ops): the whole fwd+bwd
schedule is a single `lax.scan` inside `shard_map` over the 'pp' mesh axis.
Each tick, every stage does one forward micro-step AND one backward
micro-step (lockstep 1F1B); activations move stage→stage with
`lax.ppermute` over ICI, cotangents move with the reverse permutation.
Backward is hand-scheduled: each stage re-linearizes its block for the
micro-batch leaving flight (remat — only the stage INPUT is kept, in a ring
buffer of 2·pp−1 slots), so peak activation memory is O(pp) per stage,
independent of the number of micro-batches — the 1F1B memory property.
The schedule timing:

    stage s forwards micro m at tick  t = m + s
    stage s backwards micro m at tick t = m + 2(pp−1) − s

(last stage: fwd and bwd of a micro land on the same tick, exactly 1F1B;
total ticks M + 2(pp−1) vs GPipe's 2(M + pp − 1) serialized halves.)

The whole thing is wrapped in jax.custom_vjp so outer autodiff composes:
heterogeneous pre-stages (embedding) differentiate through the returned
input cotangents, and head/loss params (possibly TIED to the embedding)
get grads from the last stage's vjp — weight tying needs no shared-weight
allreduce (reference pp_utils/utils.py FusedAllReduceBuffer): both paths'
grads meet in the outer AD sum.
"""
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ... import mesh as mesh_mod

__all__ = ["pipeline_1f1b", "pipeline_forward_loss",
           "interleaved_pipeline_loss", "interleaved_stacking_order",
           "schedule_ticks", "PipelineSpecs"]


class PipelineSpecs(NamedTuple):
    """Per-leaf PartitionSpecs for a hybrid (pp × mp × dp) pipeline run.

    Hashable (tuples of PartitionSpec) so it can ride custom_vjp
    nondiff_argnums without retracing. `stacked`/`post` are the specs of
    `tree_leaves(stacked_params)` / `tree_leaves(post_params)` IN LEAF
    ORDER (every stacked spec must lead with 'pp'); `x`/`y` shard the
    micro-batched inputs (e.g. P(None, 'dp', None, None) to data-shard
    the within-micro batch dim); `dp_axis` names the mesh axis to
    pmean losses/grads over (the reference's DP allreduce —
    fleet/meta_parallel/.../pipeline_parallel.py composes pp with the
    dp communicator the same way).
    """
    stacked: Optional[Tuple] = None
    post: Optional[Tuple] = None
    x: Optional[P] = None
    y: Optional[P] = None
    dp_axis: Optional[str] = None
    # axes over which the per-shard loss is a PARTIAL SUM of the global
    # loss (e.g. 'sp': each sequence shard computes masked_sum/global_N):
    # loss and param grads are psum'd; input cotangents need NO scaling
    # (the block's own collective transposes already deliver cross-shard
    # contributions) — contrast dp_axis, whose shards each compute a
    # full mean and therefore pmean + 1/dp-scale.
    sum_axes: Optional[Tuple[str, ...]] = None
    # quantize the dp-axis gradient pmean: the block-scaled int8
    # all-reduce of distributed.quant_collective replaces the fp32
    # pgrads/hgrads pmean (EQuARX in-XLA; loss/aux scalars stay exact).
    # Hashable bool — rides custom_vjp nondiff_argnums like the rest.
    quant_dp: bool = False


def _unflatten_like(tree, leaf_specs, default_fn, require_pp=False):
    """Spec pytree matching `tree`: from `leaf_specs` (tuple in leaf
    order) or `default_fn(leaf)` when leaf_specs is None. With
    `require_pp`, every spec must lead with 'pp' (stage-stacked leaves) —
    checked on BOTH the training and forward-only entry points, since a
    missing 'pp' silently mis-shards instead of erroring."""
    if leaf_specs is None:
        tree = jax.tree_util.tree_map(default_fn, tree)
    else:
        treedef = jax.tree_util.tree_structure(tree)
        if treedef.num_leaves != len(leaf_specs):
            raise ValueError(
                f"PipelineSpecs has {len(leaf_specs)} leaf specs, params "
                f"have {treedef.num_leaves} leaves")
        tree = jax.tree_util.tree_unflatten(treedef, list(leaf_specs))
    if require_pp:
        for leaf in jax.tree_util.tree_leaves(
                tree, is_leaf=lambda s: isinstance(s, P)):
            if len(leaf) == 0 or leaf[0] != "pp":
                raise ValueError(
                    f"stacked spec {leaf} must lead with the 'pp' axis")
    return tree


def _tree_zeros(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _tree_add_masked(acc, new, valid):
    return jax.tree_util.tree_map(
        lambda a, n: a + jnp.where(valid, n, jnp.zeros_like(n)), acc, new)


def schedule_ticks(M, pp, num_virtual=1):
    """Scan length of the (interleaved) 1F1B lockstep schedule.

    For M divisible by pp this is M·V + (V+1)·pp − 2 — the PROVABLE minimum
    for a barrier-synchronous schedule where every tick runs one forward and
    one backward chunk-step per device: the last work unit's forward cannot
    start before tick M·V−1 (M·V units enter stage 0 one per tick), finishes
    on the last stage at M·V+pp−2, and its cotangent then has to traverse
    all V·pp logical stages, one hop per tick. At V=1 this is the classic
    M + 2(pp−1). (The reference's asynchronous interleave —
    pipeline_parallel.py:488 — quotes a bubble of 2(pp−1)/V in *half*-slot
    units; that relies on per-device free-running progress, which a
    ppermute-synchronized SPMD program cannot express without making every
    slot cost max(fwd, bwd). The lockstep optimum realized here cuts the
    1F1B bubble from 2V(pp−1) — V serial fill-drain passes — to
    (V+1)·pp − 2, and keeps activation memory O(V·pp), independent of M.)
    """
    V = num_virtual
    qh, rh = divmod(M - 1, pp)
    return qh * V * pp + (V - 1) * pp + rh + (V + 1) * pp - 1


def _run_schedule(block_fn, loss_fn, stacked_params, post_params, x_micro,
                  y_micro, pp, remat, num_virtual=1, dp_axis=None,
                  sum_axes=None, aux_weight=None, quant_dp=False):
    """Inside shard_map over 'pp'. Returns (loss, aux, param_grads,
    post_grads, dx_micro).

    aux_weight: when not None, block_fn returns (y, aux_scalar) — an
    auxiliary loss produced INSIDE the stage body (e.g. the MoE
    load-balancing term, reference moe_layer.py gates) — and the total
    loss becomes mean_loss + aux_weight·mean_aux. The aux accumulator
    rides the same carry as loss_sum; its gradient is seeded into each
    backward tick's block vjp (cotangent aux_weight per valid unit), so
    aux grads flow through the identical psum/pmean reductions as the
    loss grads. The aux value follows the loss's partial-sum convention
    under sum_axes (blocks must pre-scale, as loss_fn does).

    Generalized tick-interleaved schedule (reference:
    fleet/meta_parallel/pipeline_parallel.py:416
    PipelineParallelWithInterleave / interleave_pipeline:488). With V
    virtual chunks per stage, micro-batch m = q·pp + r traverses logical
    stage v·pp + s (chunk v on device s) as work unit

        u(m, v) = q·V·pp + v·pp + r          forward at tick u + s .

    Consecutive chunks of a micro are exactly pp units apart, so chunk v+1
    on device 0 consumes the ring value device pp−1 produced for chunk v
    one tick earlier — the SAME single ppermute ring as V=1. Backward
    reverses chunk order within each pp-micro group,

        β(m, v) = q·V·pp + (V−1−v)·pp + r    backward at tick
                                             (V·pp−1) + β + (pp−1) − s ,

    which makes the cotangent of (m, v) arrive on device pp−1 exactly one
    tick after device 0 finishes (m, v+1) — again the unmodified reverse
    ring. Every formula reduces to the V=1 1F1B schedule (fwd t = m + s,
    bwd t = m + 2(pp−1) − s) when V == 1.

    Params: for V == 1 `stacked_params` is this stage's chunk pytree as
    before; for V > 1 its leaves carry a leading [V] axis (chunk v of this
    stage at index v — rows of the global [pp·V] stack ordered by
    `interleaved_stacking_order`), selected per tick with a dynamic slice.

    The head/loss vjp runs under `lax.cond`, only on the device/tick pairs
    that actually need it (last stage, last chunk) — on every other stage
    it previously burned a full head vjp per tick (vocab-sized matmuls).
    """
    V = num_virtual
    params = stacked_params
    stage = lax.axis_index("pp")
    M = x_micro.shape[0]
    Vpp = V * pp
    qh, rh = divmod(M - 1, pp)
    u_max = qh * Vpp + (V - 1) * pp + rh   # last valid work unit / β index
    T = schedule_ticks(M, pp, V)
    # Slots: in-flight units at one device span a u-window < 2·V·pp − 1
    # (forward is u-ordered, backward β-ordered with |u − β| ≤ (V−1)·pp),
    # so slot = u mod S never collides. V=1 → the familiar 2·pp − 1.
    S = 2 * Vpp - 1

    # remat: False -> off, True -> keep nothing, str/callable -> policy
    from ..recompute import checkpoint_policy

    has_aux = aux_weight is not None
    aw = float(aux_weight) if has_aux else 0.0
    # The block's aux is GLOBAL (its statistics are reduced over dp and
    # the sum_axes; value pre-scaled 1/prod(sum_axes)), so each rank's
    # vjp yields only its PARTIAL of d(aux)/dθ on the pre-scaled output.
    # The grads then ride the loss reductions (psum over sum_axes, pmean
    # over dp, ×1/dp on dx) — seeding the cotangent with
    # aw·|sum_axes|·|dp| makes those reductions reassemble exactly
    # aw·d(aux_global).
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

    def chunk_params(v):
        if V == 1:
            return params
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, v, 0, keepdims=False),
            params)

    def decode(idx):
        """idx (clipped to [0, u_max]) → (q, v_or_vr, r)."""
        q, rem = idx // Vpp, idx % Vpp
        return q, rem // pp, rem % pp

    def tick(carry, t):
        (saved, pgrads, hgrads, dxs, loss_sum, aux_sum, fwd_recv,
         bwd_recv) = carry

        # ---------------- forward micro-step ----------------
        u = t - stage
        u_c = jnp.clip(u, 0, u_max)
        qf, vf, rf = decode(u_c)
        mf = qf * pp + rf
        fwd_valid = (u >= 0) & (u <= u_max) & (mf < M)
        mf_c = jnp.clip(mf, 0, M - 1)
        x_in = jnp.where((stage == 0) & (vf == 0), x_micro[mf_c], fwd_recv)
        out, aux_f = blk(chunk_params(vf), x_in)
        aux_sum = aux_sum + jnp.where(fwd_valid, aux_f,
                                      0.0).astype(jnp.float32)
        # only save valid units: clipped ticks must not overwrite a slot
        # whose unit is still awaiting backward
        saved = lax.cond(
            fwd_valid,
            lambda b: lax.dynamic_update_index_in_dim(b, x_in, u_c % S, 0),
            lambda b: b,
            saved,
        )

        # ---------------- backward micro-step ----------------
        b = t + stage - Vpp - pp + 2
        b_c = jnp.clip(b, 0, u_max)
        qb, vrb, rb = decode(b_c)
        vb = (V - 1) - vrb
        mb = qb * pp + rb
        bwd_valid = (b >= 0) & (b <= u_max) & (mb < M)
        mb_c = jnp.clip(mb, 0, M - 1)
        u_b = qb * Vpp + vb * pp + rb       # forward index of this unit
        x_saved = saved[u_b % S]
        y_mb = y_micro[mb_c]

        # ONE re-linearization of the block per tick; the last stage's
        # boundary cotangent comes from a vjp of just the head+loss on the
        # block output (gated: other stages/chunks skip it entirely),
        # interior logical stages use the received cotangent.
        params_b = chunk_params(vb)
        (out_b, _aux_b), vjp_blk = jax.vjp(blk, params_b, x_saved)
        is_head = (stage == pp - 1) & (vb == V - 1) & bwd_valid

        def head_branch(ob, y):
            loss_val, vjp_head = jax.vjp(
                lambda o, hp: loss_fn(o, y, hp), ob, post_params)
            d_out, dh_l = vjp_head(jnp.ones_like(loss_val))
            return loss_val.astype(jnp.float32), d_out, dh_l

        def skip_branch(ob, y):
            return (jnp.zeros([], jnp.float32), jnp.zeros_like(ob),
                    _tree_zeros(post_params))

        loss_val, d_out, dh_l = lax.cond(
            is_head, head_branch, skip_branch, out_b, y_mb)
        cot = jnp.where(is_head, d_out, bwd_recv)
        # aux cotangent per valid backward unit — aux grads accumulate
        # into pgrads/dx on exactly the loss grads' reduction path (see
        # aux_seed above for the dp/sum_axes scaling)
        aux_cot = jnp.where(bwd_valid, jnp.float32(aux_seed),
                            jnp.float32(0.0))
        dparams, dx = vjp_blk((cot, aux_cot))

        if V == 1:
            pgrads = _tree_add_masked(pgrads, dparams, bwd_valid)
        else:
            g_old = jax.tree_util.tree_map(
                lambda g: lax.dynamic_index_in_dim(g, vb, 0,
                                                   keepdims=False), pgrads)
            g_new = _tree_add_masked(g_old, dparams, bwd_valid)
            pgrads = jax.tree_util.tree_map(
                lambda g, n: lax.dynamic_update_index_in_dim(g, n, vb, 0),
                pgrads, g_new)
        # loss_val / dh_l are exactly zero off the head ticks (cond)
        hgrads = jax.tree_util.tree_map(lambda a, d: a + d, hgrads, dh_l)
        loss_sum = loss_sum + loss_val
        dxs = lax.cond(
            bwd_valid & (stage == 0) & (vb == 0),
            lambda bf: lax.dynamic_update_index_in_dim(bf, dx, mb_c, 0),
            lambda bf: bf,
            dxs,
        )

        # ---------------- ring communication ----------------
        fwd_recv = lax.ppermute(
            out, "pp", [(i, (i + 1) % pp) for i in range(pp)])
        bwd_recv = lax.ppermute(
            dx, "pp", [(i, (i - 1) % pp) for i in range(pp)])
        return (saved, pgrads, hgrads, dxs, loss_sum, aux_sum, fwd_recv,
                bwd_recv), None

    init = (
        jnp.zeros((S,) + micro_shape, x_micro.dtype),       # saved inputs
        _tree_zeros(params),                                # param grads
        _tree_zeros(post_params),                           # head grads
        jnp.zeros_like(x_micro),                            # input cotangents
        jnp.zeros([], jnp.float32),                         # loss sum
        jnp.zeros([], jnp.float32),                         # aux sum
        jnp.zeros(micro_shape, x_micro.dtype),              # fwd ring reg
        jnp.zeros(micro_shape, x_micro.dtype),              # bwd ring reg
    )
    (saved, pgrads, hgrads, dxs, loss_sum, aux_sum, _, _), _ = lax.scan(
        tick, init, jnp.arange(T))

    # replicate stage-local results: loss/head-grads live on the last
    # stage, dx on stage 0 — psum of the masked values broadcasts them.
    # Each micro was seeded with cotangent 1.0, so grads of the MEAN loss
    # need the 1/M factor. Each stage accumulated ITS chunks' aux, so the
    # pp-psum assembles aux across the whole layer stack.
    loss = lax.psum(loss_sum, "pp") / M
    aux = lax.psum(aux_sum, "pp") / M
    inv_m = 1.0 / M
    pgrads = jax.tree_util.tree_map(lambda g: g * inv_m, pgrads)
    hgrads = jax.tree_util.tree_map(
        lambda g: lax.psum(g, "pp") * inv_m, hgrads)
    dxs = lax.psum(dxs, "pp") * inv_m
    if sum_axes:
        # partial-sum shards (sequence parallelism): the global loss is
        # the SUM over shards; grads likewise (standard SPMD AD — each
        # shard holds a partial of dθ). dx needs no touch-up: the
        # block's ring-collective transposes already routed cross-shard
        # cotangent contributions.
        for ax in sum_axes:
            loss = lax.psum(loss, ax)
            aux = lax.psum(aux, ax)
            pgrads = jax.tree_util.tree_map(
                lambda g, _ax=ax: lax.psum(g, _ax), pgrads)
            hgrads = jax.tree_util.tree_map(
                lambda g, _ax=ax: lax.psum(g, _ax), hgrads)
    if dp_axis is not None:
        # data parallel composed into the SAME program: each dp shard ran
        # the schedule on its slice of every micro-batch, so the global
        # loss is the mean over shards and param grads are pmean'd (the
        # reference's DP allreduce, fused here by XLA with the schedule).
        # dx stays dp-sharded — each shard owns its slice's cotangent of
        # the GLOBAL mean loss, hence the 1/dp factor.
        inv_dp = 1.0 / mesh_mod.axis_size(dp_axis)
        loss = lax.pmean(loss, dp_axis)
        aux = lax.pmean(aux, dp_axis)
        if quant_dp:
            # block-scaled int8 all-reduce of the WHOLE grad tree
            # (pgrads + hgrads fused into one payload) — the EQuARX
            # in-XLA path; the scalar loss/aux reductions above stay
            # exact fp32 (distributed.quant_collective, ROADMAP item 2)
            from ...quant_collective import quantized_pmean_tree

            pgrads, hgrads = quantized_pmean_tree(
                (pgrads, hgrads), dp_axis)
        else:
            pgrads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), pgrads)
            hgrads = jax.tree_util.tree_map(
                lambda g: lax.pmean(g, dp_axis), hgrads)
        dxs = dxs * inv_dp
    return loss + aw * aux, aux, pgrads, hgrads, dxs


def pipeline_forward_loss(block_fn, loss_fn, stacked_params, post_params,
                          batch, specs=None, aux_weight=None):
    """Forward-only fill-drain pipeline loss (eval path — no gradient
    machinery, M + pp − 1 ticks instead of the 1F1B schedule's fwd+bwd).
    `specs` composes mp/dp exactly as in `pipeline_1f1b`. With
    `aux_weight`, block_fn returns (y, aux) and the result is the pair
    (loss + aux_weight·mean_aux, mean_aux)."""
    mesh = mesh_mod.global_mesh()
    pp = mesh.shape["pp"]
    has_aux = aux_weight is not None
    aw = float(aux_weight) if has_aux else 0.0
    blk = (block_fn if has_aux else
           (lambda p, x: (block_fn(p, x), jnp.zeros([], jnp.float32))))
    x_micro, y_micro = batch
    M = x_micro.shape[0]
    if pp == 1:
        def one(x, y):
            out, a = blk(stacked_params, x)
            return loss_fn(out, y, post_params), a

        losses, auxs = jax.vmap(one)(x_micro, y_micro)
        aux = jnp.mean(auxs)
        loss = jnp.mean(losses) + aw * aux
        return (loss, aux) if has_aux else loss
    sp = specs if specs is not None else PipelineSpecs()

    def per_stage(params, post_params, xs, ys):
        stage = lax.axis_index("pp")
        T = M + pp - 1

        def tick(carry, t):
            loss_sum, aux_sum, fwd_recv = carry
            mf = t - stage
            valid = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            x_in = jnp.where(stage == 0, xs[mf_c], fwd_recv)
            out, aux_f = blk(params, x_in)
            lv = loss_fn(out, ys[mf_c], post_params)
            loss_sum = loss_sum + jnp.where(
                valid & (stage == pp - 1), lv, 0.0).astype(jnp.float32)
            aux_sum = aux_sum + jnp.where(valid, aux_f,
                                          0.0).astype(jnp.float32)
            fwd_recv = lax.ppermute(
                out, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return (loss_sum, aux_sum, fwd_recv), None

        (loss_sum, aux_sum, _), _ = lax.scan(
            tick, (jnp.zeros([], jnp.float32), jnp.zeros([], jnp.float32),
                   jnp.zeros(xs.shape[1:], xs.dtype)), jnp.arange(T))
        loss = lax.psum(loss_sum, "pp") / M
        aux = lax.psum(aux_sum, "pp") / M
        for ax in (sp.sum_axes or ()):
            loss = lax.psum(loss, ax)
            aux = lax.psum(aux, ax)
        if sp.dp_axis is not None:
            loss = lax.pmean(loss, sp.dp_axis)
            aux = lax.pmean(aux, sp.dp_axis)
        return loss + aw * aux, aux

    stack_spec = _unflatten_like(
        stacked_params, sp.stacked,
        lambda a: P(*(["pp"] + [None] * (a.ndim - 1))), require_pp=True)
    post_spec = _unflatten_like(
        post_params, sp.post, lambda a: P(*([None] * a.ndim)))
    x_spec = sp.x if sp.x is not None else P(*([None] * x_micro.ndim))
    y_spec = sp.y if sp.y is not None else P(*([None] * y_micro.ndim))
    run = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(stack_spec, post_spec, x_spec, y_spec),
        out_specs=(P(), P()),
        check_vma=False,
    )
    # ALWAYS jit the shard_map. The eager eval path reaches here under
    # jax.vjp's trace (engine.apply linearizes), not a jit; un-jitted,
    # jax 0.9.0 evaluates the schedule op by op across the mesh
    # (correct, and measurably slower: the pipeline tests take 16%
    # longer), where 0.4.37 could not evaluate a body with closed_calls
    # (remat/custom_vjp) at all. Under an outer jit the nested pjit is
    # inlined by XLA; standalone it compiles the schedule.
    run = jax.jit(run)
    loss, aux = run(stacked_params, post_params, x_micro, y_micro)
    return (loss, aux) if has_aux else loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 5, 6, 7, 8))
def pipeline_1f1b(block_fn, loss_fn, stacked_params, post_params, batch,
                  remat=True, num_virtual=1, specs=None, aux_weight=None):
    """Differentiable 1F1B pipeline loss.

    block_fn(stage_params, x) -> y   one stage's pure forward; stage_params
        are `stacked_params` leaves with the leading (stage-sharded) axis
        REMOVED by shard_map slicing... i.e. leaves [L/pp, ...] for leaves
        stacked [L, ...] — block_fn decides how to consume its slice
        (typically lax.scan over the per-stage sub-layers).
    loss_fn(y, labels, post_params) -> scalar   last-stage head + loss.
    stacked_params: pytree, leading dim divisible by pp, sharded P('pp').
    post_params: pytree (head weights — may alias embedding weights in the
        OUTER function for tying).
    batch: (x_micro [M, ...], y_micro [M, ...]) — micro-batched input
        activations and labels.
    specs: optional PipelineSpecs composing tensor parallelism INSIDE the
        stage blocks (mp-sharded weight leaves; block_fn/loss_fn use the
        mp_ops collectives) and data parallelism across the within-micro
        batch dim — the reference's hybrid TP+PP+DP flagship
        (fleet/meta_parallel/pipeline_parallel.py:105 with mp_layers
        ColumnParallel/RowParallel inside each stage) as ONE SPMD program.

    Returns the mean micro-batch loss. Differentiable w.r.t.
    stacked_params, post_params and x_micro (so an embedding stage in the
    caller composes through outer AD).

    aux_weight: when not None, block_fn must return (y, aux) and the
    result is the PAIR (loss + aux_weight·mean_aux, mean_aux). The
    second element is a DETACHED metric — its gradient contribution is
    already inside the first element; differentiate the first only.
    """
    loss, aux, _, _, _ = _pipeline_call(block_fn, loss_fn, stacked_params,
                                        post_params, batch, remat,
                                        num_virtual, specs, aux_weight)
    return loss if aux_weight is None else (loss, aux)


def _pipeline_call(block_fn, loss_fn, stacked_params, post_params, batch,
                   remat, num_virtual=1, specs=None, aux_weight=None):
    mesh = mesh_mod.global_mesh()
    pp = mesh.shape["pp"]
    V = num_virtual
    has_aux = aux_weight is not None
    aw = float(aux_weight) if has_aux else 0.0
    x_micro, y_micro = batch
    if pp == 1:
        # degenerate: straight-line execution, still micro-batched.
        # remat is honored here too — a 1-chip run of a large model
        # (the gpt1p3b bench arm) needs the same activation economy as
        # the pipelined path.
        from ..recompute import checkpoint_policy

        blk0 = (block_fn if has_aux else
                (lambda p, x: (block_fn(p, x),
                               jnp.zeros([], jnp.float32))))
        blk1 = (jax.checkpoint(blk0, policy=checkpoint_policy(remat))
                if remat else blk0)

        def apply_chunks(sp, x):
            aux = jnp.zeros([], jnp.float32)
            if V == 1:
                x, aux = blk1(sp, x)
                return x, aux
            for v in range(V):
                x, a = blk1(
                    jax.tree_util.tree_map(lambda a_, _v=v: a_[_v], sp), x)
                aux = aux + a
            return x, aux

        def full(sp, hp, xm):
            def one(x, y):
                out, a = apply_chunks(sp, x)
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

    # For V > 1 the stage's shard of the [pp·V] stack is its V chunks in
    # order (rows [s·V, (s+1)·V), see interleaved_stacking_order) — exactly
    # the leading-[V] layout _run_schedule selects from per tick.
    run = jax.shard_map(
        functools.partial(_run_schedule, block_fn, loss_fn, pp=pp,
                          remat=remat, num_virtual=V, dp_axis=sp.dp_axis,
                          sum_axes=sp.sum_axes, aux_weight=aux_weight,
                          quant_dp=sp.quant_dp),
        mesh=mesh,
        in_specs=(stack_spec, post_spec, x_spec, y_spec),
        out_specs=(P(), P(), stack_spec, post_spec, x_spec),
        check_vma=False,
    )
    # ALWAYS jit (see pipeline_forward_loss): eager model.loss() calls
    # arrive here under jax.vjp's trace, not a jit. Under TrainStep the
    # nested pjit is inlined at the (cached) outer trace; a PURE-eager
    # loop retraces per call because block_fn/loss_fn are fresh closures
    # — the supported hot path is the compiled step, eager is for eval.
    run = jax.jit(run)
    return run(stacked_params, post_params, x_micro, y_micro)


def _pipeline_fwd(block_fn, loss_fn, stacked_params, post_params, batch,
                  remat, num_virtual=1, specs=None, aux_weight=None):
    loss, aux, pg, hg, dx = _pipeline_call(
        block_fn, loss_fn, stacked_params, post_params, batch, remat,
        num_virtual, specs, aux_weight)
    out = loss if aux_weight is None else (loss, aux)
    return out, (pg, hg, dx, batch[1])


def _pipeline_bwd(block_fn, loss_fn, remat, num_virtual, specs, aux_weight,
                  res, g):
    pg, hg, dx, y = res
    if aux_weight is not None:
        # second output is a detached metric: its cotangent is dropped
        # (the aux gradient is already inside the total-loss grads)
        g, _ = g
    scale = lambda t: jax.tree_util.tree_map(lambda a: a * g, t)
    return (scale(pg), scale(hg),
            (scale(dx), jax.tree_util.tree_map(jnp.zeros_like, y)))


pipeline_1f1b.defvjp(_pipeline_fwd, _pipeline_bwd)


# ---------------------------------------------------------------------
# Interleaved virtual stages
# ---------------------------------------------------------------------

def interleaved_stacking_order(pp, num_virtual):
    """Row order for stacking global blocks into the [pp·V, ...] param
    pytree of `interleaved_pipeline_loss`: stack row r holds global block
    order[r]. Global block g runs in virtual pass v = g // pp on stage
    s = g % pp, and stage s's shard is rows [s·V, (s+1)·V), so
    order[s·V + v] = v·pp + s (the reference's round-robin layer
    assignment, pp_layers.py SegmentLayers with virtual stages)."""
    order = [0] * (pp * num_virtual)
    for g in range(pp * num_virtual):
        v, s = divmod(g, pp)
        order[s * num_virtual + v] = g
    return order


def interleaved_pipeline_loss(block_fn, loss_fn, stacked_params,
                              post_params, batch, num_virtual=1,
                              remat=True, specs=None, aux_weight=None):
    """Tick-interleaved virtual-stage 1F1B loss (reference:
    fleet/meta_parallel/pipeline_parallel.py:416
    PipelineParallelWithInterleave, parallel_layers/pp_layers.py:198).

    Each device owns `num_virtual` NON-contiguous model chunks
    (round-robin layer placement). stacked_params leaves are [pp·V, ...]
    sharded P('pp'), rows ordered by `interleaved_stacking_order` so stage
    s's shard is its V chunks. All V·pp logical stages run in ONE scan —
    per-tick chunk selection on the unified 1F1B schedule (see
    `_run_schedule` / `schedule_ticks`): `schedule_ticks(M, pp, V)` ≈
    M·V + (V+1)·pp − 2 ticks instead of the V·(M + 2(pp−1)) of V serial
    fill-drain passes, with activation memory O(V·pp) per stage
    (independent of M — the 1F1B property).

    Returns mean micro-loss; differentiable w.r.t. params/post/x_micro.
    With `aux_weight`, block_fn returns (y, aux) and the result is the
    (loss + aux_weight·mean_aux, detached mean_aux) pair — same contract
    as `pipeline_1f1b`.
    NOTE: like `pipeline_1f1b`, the custom_vjp treats labels (y_micro) as
    non-differentiable — their cotangent is zero. Losses that need label
    gradients (e.g. soft-label distillation) must route the differentiable
    part through x_micro or post_params instead.
    """
    pp = mesh_mod.global_mesh().shape["pp"]
    lead = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if lead != pp * num_virtual:
        raise ValueError(
            f"stacked_params leading dim {lead} != pp*V = {pp}*{num_virtual}")
    return pipeline_1f1b(block_fn, loss_fn, stacked_params, post_params,
                         batch, remat, num_virtual, specs, aux_weight)
