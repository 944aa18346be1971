"""DistributedTrainStep — the SPMD training engine.

TPU-native replacement for the whole reference gradient-synchronization
stack (reference: EagerReducer bucketing distributed/collective/reducer.h:88,
DataParallel python/paddle/fluid/dygraph/parallel.py:437, sharding stages
fleet/meta_parallel/sharding/group_sharded_stage{2,3}.py, and the
HybridParallelOptimizer). One jit'ed step over the global mesh:

- batch sharded over ('dp', 'sp') → XLA inserts the gradient all-reduce
  (the EagerReducer's fused-bucket allreduce, minus the buckets — the
  compiler overlaps comm with backward compute itself);
- param/opt-state PartitionSpecs implement TP (from mp layers), ZeRO-1/2
  (opt state sharded over 'sharding'), ZeRO-3 (params sharded too);
- all collectives ride ICI, scheduled by XLA.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability import steptrace as _steptrace
from ..tensor_core import Tensor
from . import mesh as mesh_mod

__all__ = ["DistributedTrainStep", "shard_params_and_opt", "sharding_of"]


def sharding_of(param_value, pspec):
    mesh = mesh_mod.global_mesh()
    return NamedSharding(mesh, pspec if pspec is not None else P())


def _contains_axis(entry, axis):
    if entry is None:
        return False
    if isinstance(entry, (tuple, list)):
        return axis in entry
    return entry == axis


def _zero_spec(pv, level, base_pspec, axis="sharding"):
    """Choose the ZeRO placement for a param/state leaf: shard the
    largest divisible dim not already taken by the base spec, over
    `axis` — 'sharding' (the dedicated axis) or 'dp' (ZeRO composed on
    the replica axis, the hybrid3d default: in a DP×TP×PP mesh the dp
    ranks ARE the replica group the optimizer states shard over).
    Idempotent: a spec already carrying `axis` (e.g. both
    group_sharded_parallel and DistributedTrainStep(zero_level=...) were
    applied) is returned unchanged."""
    base = tuple(base_pspec) if base_pspec is not None else ()
    base = base + (None,) * (pv.ndim - len(base))
    if any(_contains_axis(e, axis) for e in base):
        return P(*base)
    n = mesh_mod.axis_size(axis)
    if n == 1:
        return P(*base) if any(base) else P()
    for d in np.argsort([-s for s in pv.shape]):
        d = int(d)
        if base[d] is None and pv.shape[d] % n == 0:
            new = list(base)
            new[d] = axis
            return P(*new)
    if any(e is None for e in base):
        # a free dim existed but none was divisible — the user CAN fix
        # this (pad the dim / change the axis size). Leaves whose dims
        # are all taken by TP axes are expected to replicate: no warning.
        import warnings

        warnings.warn(
            f"ZeRO ({level}): no free dim of shape {tuple(pv.shape)} is "
            f"divisible by the sharding axis ({n}) — this leaf stays "
            "REPLICATED and saves no memory; pad the dim or change the "
            "axis size", RuntimeWarning, stacklevel=2)
    return P(*base) if any(base) else P()


def shard_params_and_opt(model, optimizer, level="os_g", axis="sharding"):
    """Assign ZeRO placements (reference group_sharded_parallel levels:
    os = stage1, os_g = stage2, p_g_os = stage3). `axis` picks the mesh
    axis storage shards over — 'sharding' (dedicated) or 'dp' (the
    hybrid3d composition)."""
    for _, p in model.named_parameters():
        if level == "p_g_os":
            p._pspec = _zero_spec(p._value, level, p._pspec, axis=axis)
        # place now so the first jit call doesn't need a resharding copy
        try:
            p._value = jax.device_put(
                p._value, sharding_of(p._value, p._pspec))
        except Exception:  # ptlint: disable=PTL804 (placement is advisory; first jit call re-places)
            pass
    return model


class DistributedTrainStep:
    """Compiled hybrid-parallel train step.

    loss_fn(model, *batch) -> scalar loss. Batch tensors are sharded on
    axis 0 over ('dp',) (pass batch_specs to override, e.g. sequence
    sharding over 'sp' for long-context).
    """

    def __init__(self, model, loss_fn, optimizer, zero_level=None,
                 batch_specs=None, remat=False, quant_allreduce=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.zero = zero_level
        self.batch_specs = batch_specs
        self.remat = remat
        # quantized gradient all-reduce (block-scaled int8 in-XLA —
        # distributed.quant_collective): None follows the
        # PT_QUANT_ALLREDUCE_XLA env. On the plain-jit step the grad
        # sync is partitioner-inserted and invisible; with the knob on,
        # the grad computation moves into an explicit shard_map over
        # the replica axes so the int8 exchange (and its schedule —
        # extract_schedule sees it) replaces the fp32 psum. Supported
        # for the replicated-param DP/ZeRO-1/2 shape only (validated
        # at build).
        if quant_allreduce is None:
            from .quant_collective import xla_quant_enabled

            quant_allreduce = xla_quant_enabled()
        self.quant_allreduce = bool(quant_allreduce)
        if zero_level:
            shard_params_and_opt(model, optimizer, zero_level)
        sd = model.state_dict()
        self._names = list(sd.keys())
        self._param_objs = [sd[n] for n in self._names]
        self._trainable = [not p.stop_gradient for p in self._param_objs]
        self._opt_states = None
        self._compiled = None
        # phase-trace state (observability.steptrace): batch-signature
        # set drives the quiet-warm-up exclusion + recompile sentinel
        # (same accounting as jit.TrainStep), prev_end anchors the
        # next step's data_wait segment
        self._batch_signatures = set()
        self._steptrace_prev_end = None

    # ---- shardings ----
    def _param_shardings(self, objs):
        return [sharding_of(p._value, p._pspec) for p in objs]

    def _state_shardings(self, train_objs, states):
        """Opt-state leaves follow their param's spec (ZeRO-1/2: moments
        sharded over 'sharding' even when params replicated)."""
        out = []
        zero_opt = self.zero in ("os", "os_g", "p_g_os")
        for p, st in zip(train_objs, states):
            d = {}
            for k, v in st.items():
                if v.ndim == p._value.ndim and v.shape == p._value.shape:
                    spec = p._pspec
                    if zero_opt:
                        spec = _zero_spec(v, self.zero, p._pspec)
                    d[k] = sharding_of(v, spec)
                else:
                    d[k] = sharding_of(v, P())
            out.append(d)
        return out

    def _build(self, batch_vals):
        from ..core import rng as rng_mod

        mesh = mesh_mod.global_mesh()
        model = self.model
        loss_fn = self.loss_fn
        opt = self.optimizer
        param_objs = self._param_objs
        trainable = self._trainable
        # runtime argument, not a closure constant — a baked key makes
        # each instance a distinct HLO, so no two instances could share
        # a compile-cache entry (see jit.TrainStep)
        self._base_key = rng_mod.next_key()

        def pure_loss(train_vals, frozen_vals, batch_vals, step_key):
            originals = [p._value for p in param_objs]
            it_t, it_f = iter(train_vals), iter(frozen_vals)
            for p, tr in zip(param_objs, trainable):
                p._value = next(it_t) if tr else next(it_f)
            try:
                batch = [Tensor(v, stop_gradient=True) for v in batch_vals]
                with rng_mod.trace_key_scope(step_key):
                    loss = loss_fn(model, *batch)
                new_frozen = [p._value for p, tr in zip(param_objs, trainable)
                              if not tr]
            finally:
                for p, v in zip(param_objs, originals):
                    p._value = v
            return loss._value, new_frozen

        # remat: False -> off, True -> keep nothing, str/callable ->
        # policy ('dots_saveable' keeps MXU outputs; see fleet.recompute)
        from .fleet.recompute import checkpoint_policy

        loss_f = (jax.checkpoint(pure_loss,
                                 policy=checkpoint_policy(self.remat))
                  if self.remat else pure_loss)

        train_objs = [p for p, t in zip(param_objs, trainable) if t]
        frozen_objs = [p for p, t in zip(param_objs, trainable) if not t]

        quant_axes = ()
        if self.quant_allreduce:
            quant_axes = tuple(a for a in ("dp", "sharding")
                               if mesh_mod.axis_size(a) > 1)
        if quant_axes:
            self._validate_quant_path()
            grad_sm = self._quant_grad_program(loss_f, batch_vals,
                                               quant_axes, mesh)

        def step(train_vals, frozen_vals, opt_states, lr, batch_vals,
                 step_idx, base_key):
            step_key = jax.random.fold_in(base_key, step_idx)
            if quant_axes:
                loss, grads, new_frozen = grad_sm(
                    train_vals, frozen_vals, batch_vals, step_key)
            else:
                (loss, new_frozen), grads = jax.value_and_grad(
                    loss_f, has_aux=True)(
                    train_vals, frozen_vals, batch_vals, step_key)
            new_vals, new_states = opt.apply_gradients_tree(
                train_vals, grads, opt_states, lr, param_objs=train_objs)
            return loss, new_vals, new_states, new_frozen
        t_sh = self._param_shardings(train_objs)
        f_sh = self._param_shardings(frozen_objs)
        states = self.optimizer.init_states_tree(
            [p._value for p in train_objs])
        s_sh = self._state_shardings(train_objs, states)
        if self._opt_states is not None:
            # restored from a checkpoint before the first step — keep the
            # values, (re)place them on the computed shardings
            states = self._opt_states
        if self.batch_specs is not None:
            b_sh = [NamedSharding(mesh, s) for s in self.batch_specs]
        else:
            # batch rides BOTH data-parallel axes: in real ZeRO the
            # sharding world IS a data-parallel world (each 'sharding'
            # rank sees different data and owns a slice of grads/opt
            # state) — with sharding=1 this reduces to plain P('dp')
            b_sh = [
                NamedSharding(mesh, P(*([("dp", "sharding")]
                                        + [None] * (np.ndim(v) - 1))))
                for v in batch_vals
            ]
        self._opt_states = jax.device_put(states, s_sh)
        self._batch_shardings = b_sh
        jitted = jax.jit(
            step,
            in_shardings=(t_sh, f_sh, s_sh, None, b_sh, None, None),
            out_shardings=(NamedSharding(mesh, P()), t_sh, s_sh, f_sh),
            donate_argnums=self._donate_argnums,
        )
        self._compiled = jitted

    # ---- quantized gradient all-reduce (in-XLA EQuARX) ----
    def _validate_quant_path(self):
        """The quant path moves the grad computation into a manual
        shard_map over the replica axes: params must be REPLICATED
        (ZeRO-3 sharded storage and TP pspecs would need their own
        in_specs and in-shard collectives) and the batch must ride the
        default replica-axis sharding. Fail loudly, not numerically."""
        if self.zero == "p_g_os":
            raise ValueError(
                "quant_allreduce does not compose with zero_level="
                "'p_g_os' (sharded param storage): the int8 grad "
                "exchange assumes replicated params. Use 'os'/'os_g' "
                "(sharded optimizer state composes fine) or disable "
                "PT_QUANT_ALLREDUCE_XLA for this step")
        if self.batch_specs is not None:
            raise ValueError(
                "quant_allreduce supports the default replica-axis "
                "batch sharding only (custom batch_specs — e.g. "
                "sequence sharding — would need their own loss "
                "reduction semantics inside the shard_map)")
        for p in self._param_objs:
            spec = getattr(p, "_pspec", None)
            if spec is not None and any(s is not None for s in spec):
                raise ValueError(
                    f"quant_allreduce: parameter with _pspec {spec} is "
                    "mesh-sharded — the int8 grad exchange supports "
                    "replicated params only (TP models: use "
                    "HybridTrainStep, whose pipeline schedule "
                    "quantizes the dp axis while mp stays exact)")

    def _quant_grad_program(self, loss_f, batch_vals, quant_axes, mesh):
        """shard_map'd (loss, grads, new_frozen) with the block-scaled
        int8 all-reduce-mean in place of the partitioner's fp32 grad
        psum. Per-shard loss is the local-batch mean → pmean'd exact;
        float buffer updates (BN stats) are pmean'd so replicas stay
        identical; int buffers pass through (identical by
        construction)."""
        from .quant_collective import quantized_pmean_tree

        axes = quant_axes if len(quant_axes) > 1 else quant_axes[0]

        def grad_program(train_vals, frozen_vals, batch_vals, step_key):
            # decorrelate per-replica randomness: the plain-jit path's
            # dropout mask spans the GLOBAL batch (different per row);
            # inside shard_map every replica would otherwise draw from
            # the identical key and apply the SAME mask to its local
            # rows — fold the replica index in so flipping
            # quant_allreduce doesn't change RNG semantics
            rank = jnp.int32(0)
            for a in quant_axes:
                rank = rank * mesh_mod.axis_size(a) + \
                    jax.lax.axis_index(a)
            step_key = jax.random.fold_in(step_key, rank)
            (loss, new_frozen), grads = jax.value_and_grad(
                loss_f, has_aux=True)(
                train_vals, frozen_vals, batch_vals, step_key)
            loss = jax.lax.pmean(loss, axes)
            grads = quantized_pmean_tree(grads, quant_axes)
            new_frozen = [
                jax.lax.pmean(v, axes)
                if jnp.issubdtype(v.dtype, jnp.floating) else v
                for v in new_frozen]
            return loss, grads, new_frozen

        rep = P()
        bspecs = [P(*((("dp", "sharding"),)
                      + (None,) * (np.ndim(v) - 1)))
                  if np.ndim(v) else rep for v in batch_vals]
        return jax.shard_map(
            grad_program, mesh=mesh,
            in_specs=(rep, rep, bspecs, rep),
            out_specs=(rep, rep, rep),
            check_vma=False)

    # ONE layout definition, shared by __call__ and the analysis
    # probes (analyze_step / extract_schedule) — probe-vs-runtime
    # drift would silently defeat the donation/schedule guards (the
    # same single-source rule jit.TrainStep._step_args follows)
    _STEP_ARG_NAMES = ("train_vals", "frozen_vals", "opt_state", "lr",
                       "batch", "step_idx", "base_key")
    _donate_argnums = (0, 1, 2)
    # step-family label for pt_train_phase_seconds flight events and
    # pt_step_recompiles_total (jit.TrainStep publishes as "train",
    # HybridTrainStep as "hybrid3d")
    _steptrace_family = "dist"

    def _step_args(self, batch_vals):
        """Positional args of the compiled step for the CURRENT live
        state; `batch_vals` may be arrays or ShapeDtypeStructs."""
        train_vals = [p._value for p, t in zip(self._param_objs,
                                               self._trainable) if t]
        frozen_vals = [p._value for p, t in zip(self._param_objs,
                                                self._trainable) if not t]
        # committed f32, not a weak python float — same reasoning as
        # jit.TrainStep (weak-vs-committed is a retrace hazard)
        return (train_vals, frozen_vals, self._opt_states,
                np.float32(self.optimizer.get_lr()), list(batch_vals),
                jnp.asarray(self.optimizer._step_count, jnp.uint32),
                self._base_key)

    def compile_stats(self):
        """Recompile probe (jit.TrainStep.compile_stats shape, minus
        the per-batch-signature accounting): executables held by the
        step. Steady state — INCLUDING a save+restore lifecycle — is 1;
        a restore that flipped a leaf's commitment would read 2+ (the
        ISSUE-10 retrace family, docs/RESILIENCE.md)."""
        if self._compiled is None:
            return {"executables": 0}
        return {"executables": int(self._compiled._cache_size())}

    def __call__(self, *batch):
        t_entry = _steptrace.now()
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        t_h2d = _steptrace.now()
        if self._compiled is None:
            self._build(batch_vals)
        sig = tuple((tuple(v.shape), str(v.dtype)) for v in batch_vals)
        new_sig = sig not in self._batch_signatures
        if new_sig:
            self._batch_signatures.add(sig)
            if len(self._batch_signatures) > 1:
                _steptrace.note_recompile(
                    self._steptrace_family,
                    step=int(self.optimizer._step_count),
                    signatures=len(self._batch_signatures),
                    batch_sig=repr(sig))
        # phase trace (observability.steptrace): a new batch signature
        # compiles — run QUIET so the stall stays out of the histograms
        tr = _steptrace.begin_step(
            self._steptrace_family, int(self.optimizer._step_count),
            prev_end=self._steptrace_prev_end, quiet=new_sig,
            t_entry=t_entry)
        tr.stamp("h2d", t_h2d)
        _steptrace.chaos_fire("step.dispatch")
        loss, new_vals, self._opt_states, new_frozen = self._compiled(
            *self._step_args(batch_vals))
        tr.stamp("dispatch")
        if _steptrace.full():
            # device_step = block_until_ready delta: full telemetry only
            # (see jit.TrainStep — a sync per step stalls the pipeline)
            jax.block_until_ready(
                (loss, new_vals, self._opt_states, new_frozen))
            tr.stamp("device_step")
        it = iter(new_vals)
        it_f = iter(new_frozen)
        for p, t in zip(self._param_objs, self._trainable):
            p._value = next(it) if t else next(it_f)
        self.optimizer._step_count += 1
        tr.stamp("opt_publish")
        _, self._steptrace_prev_end = _steptrace.end_step(tr)
        return Tensor(loss)
