"""HybridTrainStep — the 3D-parallel (DP × TP × PP) compiled train step.

`jit.TrainStep`'s sibling for hybrid meshes: the SAME step function,
argument layout (`_STEP_ARG_NAMES` / `_step_args`) and donation spec —
so `analysis.analyze_step`, the zero-recompile probe and
`compile_stats(check_donation=True)` all work unchanged — plus the
mesh-aware placement the generic step cannot know about:

* parameter/buffer in- AND out-shardings pinned from each Parameter's
  `_pspec` (the `mark_sharding` annotations the pipelined/TP models
  attach) — the executable never pays a silent reshard copy, and the
  donated buffers alias outputs with identical layouts;
* ZeRO optimizer-state placement composed on the **dp** axis
  (config.zero: 'os' / 'os_g' shard the moments, 'p_g_os' additionally
  shards the parameters — `parallel_step._zero_spec` placement policy,
  axis-parameterized);
* the donation probe publishes `pt_step_donation_held{step="hybrid3d"}`;
* `collective_schedule(*batch)` (inherited from TrainStep, backed by
  `analysis.spmd_analysis`) emits the ordered per-mesh-axis collective
  schedule of the compiled step — the tier-1 dp2.tp2.pp2 schedule is
  pinned as a golden (tests/golden/hybrid3d_dp2tp2pp2_schedule.json),
  and the per-axis payload bytes are the baseline ROADMAP item 2's
  quantized all-reduce must beat (docs/ANALYSIS.md "SPMD passes").

Strategy meta-optimizers compose for free: LARS/DGC run through the
same `apply_gradients_tree` protocol inside the compiled step, so
`fleet.distributed_optimizer(opt)` with `strategy.lars = True` hands
this step a LarsMomentum and the whole 3D program stays ONE donated
executable per mesh config.
"""
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import TrainStep

__all__ = ["HybridTrainStep"]


class HybridTrainStep(TrainStep):
    """Compiled DP × TP × PP train step over the global mesh.

    model: typically a `PipelinedGPTForCausalLM` (pp via the 1F1B/GPipe
        shard_map scan, tp via the Megatron specs, dp via the batch
        specs) — but any model whose parameters carry `_pspec`
        annotations composes.
    config: optional `hybrid3d.Hybrid3DConfig` — supplies the ZeRO
        level/axis and rides along into `describe()`/bench stamps. When
        None the step is placement-pinning only (no ZeRO).
    quant_allreduce: quantize the dp-axis gradient all-reduce to
        block-scaled int8 inside the compiled step
        (distributed.quant_collective — EQuARX in-XLA). Resolution
        order: this argument → config.quant_allreduce → the
        PT_QUANT_ALLREDUCE_XLA env. The knob lands on the MODEL
        (PipelinedGPTForCausalLM.quant_allreduce) because the pipeline
        specs are built at trace time — so `collective_schedule` and
        the dispatched executable always agree.
    """

    _donation_gauge_label = "hybrid3d"

    def __init__(self, model, loss_fn, optimizer, config=None,
                 donate_params=True, remat=False, quant_allreduce=None):
        self.config = config
        self._zero = getattr(config, "zero", None)
        self._zero_axis = getattr(config, "zero_axis", "dp")
        if quant_allreduce is None:
            quant_allreduce = getattr(config, "quant_allreduce", None)
        if hasattr(model, "quant_allreduce"):
            # write None too: a model REUSED across steps must not
            # inherit the previous step's pinned setting — None
            # restores the documented arg → config → env chain
            model.quant_allreduce = (None if quant_allreduce is None
                                     else bool(quant_allreduce))
        self.quant_allreduce = quant_allreduce
        if self._zero == "p_g_os":
            # param storage sharded too (ZeRO-3): placement must happen
            # BEFORE the step captures the parameter values
            from ..distributed.parallel_step import shard_params_and_opt

            shard_params_and_opt(model, optimizer, "p_g_os",
                                 axis=self._zero_axis)
        super().__init__(model, loss_fn, optimizer,
                         donate_params=donate_params, remat=remat)
        # commit EVERY param/buffer to its mesh placement now: leaves the
        # model builder didn't mark (final LN, scalar buffers) start as
        # uncommitted single-device arrays, flip to mesh-committed step
        # outputs after step 0, and that signature change would cost a
        # second executable (the zero-recompile probe would read 2)
        for p in self._param_objs:
            if not isinstance(p._value, jax.core.Tracer):
                try:
                    p._value = jax.device_put(
                        p._value, self._sharding_of(p))
                except (ValueError, RuntimeError):
                    pass  # incompatible degenerate mesh: keep as-is

    # ---- placement ----
    def _sharding_of(self, p):
        from ..distributed.parallel_step import sharding_of

        return sharding_of(p._value, getattr(p, "_pspec", None))

    def _state_shardings(self, train_objs):
        """Opt-state leaves follow their param's spec, plus the ZeRO
        axis on a free divisible dim (parallel_step._zero_spec — ZeRO-1
        composed on the dp axis: the dp ranks are the replica group the
        states shard over; XLA all-gathers the updated params)."""
        from ..distributed.parallel_step import _zero_spec, sharding_of

        # shapes only — eval_shape allocates nothing. A real
        # init_states_tree here would materialize the full UNSHARDED
        # moment tree (2× param bytes for AdamW) just to be discarded,
        # and the zero='os' case exists precisely because that tree may
        # not fit un-sharded.
        states = jax.eval_shape(
            self.optimizer.init_states_tree,
            [p._value for p in train_objs])
        out = []
        for p, st in zip(train_objs, states):
            d = {}
            for k, v in st.items():
                if v.ndim == p._value.ndim and v.shape == p._value.shape:
                    spec = getattr(p, "_pspec", None)
                    if self._zero:
                        spec = _zero_spec(v, self._zero, spec,
                                          axis=self._zero_axis)
                    d[k] = sharding_of(v, spec)
                else:
                    d[k] = sharding_of(v, P())
            out.append(d)
        return out

    def _jit_step(self, step):
        from ..distributed import mesh as mesh_mod

        mesh = mesh_mod.global_mesh()
        train_objs = [p for p, t in zip(self._param_objs, self._trainable)
                      if t]
        frozen_objs = [p for p, t in zip(self._param_objs, self._trainable)
                       if not t]
        t_sh = [self._sharding_of(p) for p in train_objs]
        f_sh = [self._sharding_of(p) for p in frozen_objs]
        s_sh = self._state_shardings(train_objs)
        self._shardings = (t_sh, f_sh, s_sh)
        rep = NamedSharding(mesh, P())
        # lr / batch / step_idx / base_key stay auto (None): the batch
        # enters the pipeline whole (the shard_map in_specs slice it),
        # scalars are replicated by construction
        in_sh = (t_sh, f_sh, s_sh, None, None, None, None)
        out_sh = (rep, t_sh, s_sh, f_sh)
        if self._telemetry_full:
            out_sh = out_sh + (rep,)
        if self._opt_states is not None:
            # checkpoint-restored BEFORE the first step: the restore
            # kept the accumulators' original commitment (uncommitted
            # host arrays — the ISSUE-10 rule), but the hybrid step's
            # steady state is COMMITTED mesh placements (its outputs
            # carry out_shardings). (Re)place them now so the first
            # dispatch's signature already matches step 2's — otherwise
            # the commitment flip costs a second executable, exactly
            # the retrace the save+restore one-executable probe pins.
            self._opt_states = jax.device_put(self._opt_states, s_sh)
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=self._donate_argnums)

    def _init_opt_states(self, train_vals):
        states = self.optimizer.init_states_tree(train_vals)
        if getattr(self, "_shardings", None) is not None:
            states = jax.device_put(states, self._shardings[2])
        return states

    def describe(self):
        """Mesh/config stamp for bench records and telemetry."""
        from ..distributed import mesh as mesh_mod

        mesh = mesh_mod.global_mesh()
        out = {"mesh": {a: int(s) for a, s in mesh.shape.items()
                        if s > 1 or a in ("dp", "pp", "mp")}}
        if self.config is not None:
            out.update(self.config.describe())
        return out
