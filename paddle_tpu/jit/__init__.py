"""paddle_tpu.jit — graph capture via jax tracing.

TPU-native replacement for the reference's ENTIRE dy2static subsystem
(reference: python/paddle/fluid/dygraph/jit.py:164 `declarative`,
dygraph_to_static/program_translator.py:239 `StaticFunction`, the 30-file
AST-transformer suite, and partial_program.py:121 `PartialProgramLayer`).
Design: no AST rewriting — the python function runs once under a jax trace
per input signature; the traced whole program becomes ONE tape op, so eager
autograd sees a single fused node whose vjp is the XLA-compiled backward.
This is both the API-parity layer (`@to_static`) and the performance layer
(whole-graph XLA compilation replaces per-op dispatch).
"""
import functools
import inspect
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..autograd import engine
from ..observability import metrics as _obs
from ..observability import steptrace as _steptrace
from ..observability.tracing import trace_span as _trace_span
from ..tensor_core import Parameter, Tensor

# runtime telemetry (docs/OBSERVABILITY.md). Step time is dispatch-side
# wall time — donated-buffer steps chain, so once the pipeline fills it
# converges to true device step time (same reasoning as profiler's
# _StepTimer). Loss, grad-norm and the `device_step` phase stamp are
# FULL-telemetry only: reading them forces a device sync that would
# stall the async dispatch pipeline.
_STEP_SECONDS = _obs.histogram(
    "pt_train_step_seconds", "compiled train-step wall time")
_STEPS_TOTAL = _obs.counter(
    "pt_train_steps_total", "compiled train steps dispatched")
_COMPILES_TOTAL = _obs.counter(
    "pt_train_compiles_total",
    "distinct TrainStep batch signatures seen — each is one XLA "
    "compile; growth after warmup is recompile churn (the PR-2 "
    "zero-recompile probe, as a counter)")
_LOSS_GAUGE = _obs.gauge(
    "pt_train_loss", "last loss (full telemetry only: syncs the device)")
_GRAD_NORM = _obs.histogram(
    "pt_train_grad_norm",
    "global grad L2 norm per step (full telemetry only)",
    buckets=(0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0,
             100.0, 300.0, 1000.0))
_DONATION_HELD = _obs.gauge(
    "pt_step_donation_held",
    "1 when every donated buffer of the compiled step aliased an "
    "output at the last compile_stats(check_donation=True) probe — 0 "
    "means the executable copies instead of updating in place "
    "(analysis.donation_coverage; docs/ANALYSIS.md)",
    labelnames=("step",))

__all__ = ["to_static", "not_to_static", "save", "load", "TranslatedLayer",
           "InputSpec", "TrainStep", "ignore_module", "enable_to_static"]

_to_static_enabled = True


def enable_to_static(flag):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


class InputSpec:
    """(reference: python/paddle/static/input_spec.py)."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = tuple(
            -1 if s is None else int(s) for s in shape
        )
        from ..core import dtype as dtype_mod

        self.dtype = dtype_mod.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, tensor.dtype, name)


def _sig_of(value):
    if isinstance(value, Tensor):
        return ("T", tuple(value._value.shape), str(value._value.dtype),
                bool(value.stop_gradient))
    if isinstance(value, (list, tuple)):
        return (type(value).__name__,) + tuple(_sig_of(v) for v in value)
    if isinstance(value, dict):
        return ("dict",) + tuple(
            (k, _sig_of(v)) for k, v in sorted(value.items())
        )
    return ("py", value if isinstance(value, (int, float, str, bool,
                                              type(None))) else id(value))


def _tree_tensors(obj, out):
    """Collect Tensors in (args, kwargs) pytree, preserving structure via a
    rebuild closure."""
    if isinstance(obj, Tensor):
        idx = len(out)
        out.append(obj)
        return ("tensor", idx)
    if isinstance(obj, (list, tuple)):
        spec = [_tree_tensors(v, out) for v in obj]
        return (type(obj).__name__, spec)
    if isinstance(obj, dict):
        return ("dict", {k: _tree_tensors(v, out) for k, v in obj.items()})
    return ("leaf", obj)


def _tree_rebuild(spec, values):
    kind = spec[0]
    if kind == "tensor":
        return values[spec[1]]
    if kind in ("list", "tuple"):
        seq = [_tree_rebuild(s, values) for s in spec[1]]
        return seq if kind == "list" else tuple(seq)
    if kind == "dict":
        return {k: _tree_rebuild(s, values) for k, s in spec[1].items()}
    return spec[1]


def _closure_modes(fn):
    """training flags of Layers a standalone @to_static function closes
    over — the jitted program freezes `self.training` reads at trace
    time, so a train/eval flip on a captured layer must key a new
    program (direct closure cells only; layers reached through nested
    containers still need a re-decorated function)."""
    out = []
    f = getattr(fn, "__func__", fn)
    for cell in getattr(f, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        tr = getattr(v, "training", None)
        if isinstance(tr, bool):
            out.append(tr)
    return tuple(out)


class StaticFunction:
    """Traced-function cache, one compiled program per input signature
    (≈ ConcreteProgram cache keyed by FunctionSpec in the reference)."""

    def __init__(self, fn, input_spec=None):
        self._fn = fn
        self._input_spec = input_spec
        self._cache = {}
        self._last_concrete = None
        functools.update_wrapper(self, fn)

    def __get__(self, instance, owner):
        if instance is None:
            return self
        return functools.partial(self.__call__, instance)

    def _params_of(self, bound_self):
        if bound_self is None:
            return [], []
        names, params = [], []
        for n, p in bound_self.named_parameters():
            names.append(n)
            params.append(p)
        for n, b in bound_self.named_buffers():
            names.append("buffer:" + n)
            params.append(b)
        return names, params

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)
        bound_self = None
        if args and hasattr(args[0], "named_parameters"):
            bound_self, args = args[0], args[1:]

        arg_tensors = []
        spec = _tree_tensors((args, kwargs), arg_tensors)
        _, params = self._params_of(bound_self)
        # the jitted program freezes python state read at trace time, so
        # everything that may change between calls must be in the cache
        # key (mode flags) or threaded as an argument (PRNG key below)
        key = (_sig_of((args, kwargs)), id(bound_self),
               engine.is_grad_enabled(),
               getattr(bound_self, "training", None),
               _closure_modes(self._fn))
        entry = self._cache.get(key)
        if entry is None:
            entry = self._trace(bound_self, spec, arg_tensors, params)
            self._cache[key] = entry
        jfn, out_spec_holder = entry
        from ..core import rng as rng_mod

        key_t = Tensor(rng_mod.next_key(), stop_gradient=True)
        all_inputs = [key_t] + list(arg_tensors) + list(params)
        flat_out = engine.apply(
            f"to_static:{self._fn.__name__}", jfn, tuple(all_inputs)
        )
        if not isinstance(flat_out, tuple):
            flat_out = (flat_out,)
        return _tree_rebuild(out_spec_holder[0], list(flat_out))

    def _trace(self, bound_self, spec, arg_tensors, params):
        from . import autograph

        n_args = len(arg_tensors)
        # AutoGraph (reference dygraph_to_static convert_operators.py):
        # tensor-dependent if/while/for compile to lax control flow;
        # python-valued control flow keeps python semantics; conversion
        # failure falls back to the untransformed function with a warning
        fn = autograph.maybe_convert(self._fn)
        out_spec_holder = [None]
        sg_flags = [t.stop_gradient for t in arg_tensors] + [
            p.stop_gradient for p in params
        ]
        param_objs = params

        def jfn(step_key, *flat_vals):
            from ..core import rng as rng_mod

            arg_vals = flat_vals[:n_args]
            param_vals = flat_vals[n_args:]
            wrapped = [
                Tensor(v, stop_gradient=sg)
                for v, sg in zip(arg_vals, sg_flags[:n_args])
            ]
            args, kwargs = _tree_rebuild(spec, wrapped)
            # temporarily swap live param values for traced ones
            originals = [p._value for p in param_objs]
            for p, v in zip(param_objs, param_vals):
                p._value = v
            try:
                # per-call PRNG key threaded as an ARGUMENT: dropout etc.
                # draw from it, so the jitted program doesn't bake the
                # trace-time key in (same-mask-every-call bug)
                with rng_mod.trace_key_scope(step_key):
                    if bound_self is not None:
                        out = fn(bound_self, *args, **kwargs)
                    else:
                        out = fn(*args, **kwargs)
            finally:
                for p, v in zip(param_objs, originals):
                    p._value = v
            out_tensors = []
            out_spec = _tree_tensors(out, out_tensors)
            out_spec_holder[0] = out_spec
            vals = tuple(t._value for t in out_tensors)
            return vals if len(vals) != 1 else vals[0]

        # jit the captured program: repeated same-signature calls hit the
        # XLA executable cache instead of re-tracing the python function
        # (jax caches the jaxpr by avals, so vjp/tape composition around
        # it also stops re-entering python)
        return jax.jit(jfn), out_spec_holder

    @property
    def concrete_program(self):
        return self._last_concrete

    def get_traced(self, *example_args, **example_kwargs):
        """Return (pure_jax_fn, flat_example_vals) for export/bench.
        The traced fn's first argument is the per-call PRNG key; the
        returned example vals include one."""
        from ..core import rng as rng_mod

        arg_tensors = []
        spec = _tree_tensors((example_args, example_kwargs), arg_tensors)
        bound_self = None
        jfn, _ = self._trace(bound_self, spec, arg_tensors, [])
        return jfn, [rng_mod.next_key()] + [t._value for t in arg_tensors]


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator (reference API: paddle.jit.to_static)."""

    def deco(fn):
        if isinstance(fn, StaticFunction):
            return fn
        from ..nn import Layer

        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(type(layer).forward, input_spec)
            layer.forward = functools.partial(sf.__call__, layer)
            layer._static_function = sf
            return layer
        return StaticFunction(fn, input_spec)

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# ---------------------------------------------------------------- save/load
def _resolve_forward(layer, input_spec):
    """Build a pure jax fn(params_dict, *inputs) from a Layer."""
    names = []
    params = []
    for n, p in layer.state_dict().items():
        names.append(n)
        params.append(p)

    def pure_fn(param_vals, *input_vals):
        originals = [p._value for p in params]
        for p, v in zip(params, param_vals):
            p._value = v
        try:
            with engine.no_grad_guard():
                ins = [Tensor(v) for v in input_vals]
                out = layer.forward(*ins)
        finally:
            for p, v in zip(params, originals):
                p._value = v
        if isinstance(out, (list, tuple)):
            return tuple(t._value for t in out)
        return out._value

    return pure_fn, names, [p._value for p in params]


def save(layer, path, input_spec=None, **configs):
    """Serialize a Layer's forward as a portable StableHLO artifact +
    params (reference: paddle.jit.save → .pdmodel/.pdiparams; here
    .stablehlo via jax.export + .pdiparams via paddle.save).
    """
    import os

    from ..framework.io_state import save as tensor_save

    if input_spec is None:
        raise ValueError("input_spec is required for jit.save")
    was_training = layer.training
    layer.eval()
    try:
        pure_fn, names, param_vals = _resolve_forward(layer, input_spec)
        shaped = [
            jax.ShapeDtypeStruct(
                tuple(1 if s in (-1, None) else s for s in sp.shape), sp.dtype
            )
            for sp in input_spec
        ]
        param_shaped = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for v in param_vals]
        exported = jax.export.export(jax.jit(pure_fn))(param_shaped, *shaped)
        blob = exported.serialize()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path + ".stablehlo", "wb") as f:
            f.write(blob)
        tensor_save({"names": names,
                     "params": [np.asarray(v) for v in param_vals],
                     "n_inputs": len(input_spec)},
                    path + ".pdiparams")
    finally:
        if was_training:
            layer.train()


class TranslatedLayer:
    """Inference-only loaded program (reference: paddle.jit.load →
    TranslatedLayer, C++ twin paddle/fluid/jit/layer.cc). Execution is
    jitted ONCE per input signature (exported.call re-staged through a
    cached executable, optionally AOT-compiled with XLA compiler
    options — the TPU-native analog of the reference inference pass
    pipeline's per-predictor optimization config)."""

    def __init__(self, exported, names, param_vals, n_inputs=None):
        self._exported = exported
        self._names = names
        self._param_vals = param_vals
        self._n_inputs = n_inputs
        self._compiler_options = None
        self._jitted = jax.jit(self._call_fn)
        self.training = False

    def set_compiler_options(self, options):
        """XLA compiler options applied to every (re)compile — the
        AnalysisConfig pass-pipeline hook (reference
        analysis_predictor.cc pass registry; here: XLA flag overrides,
        e.g. {"xla_cpu_enable_fast_math": True}). jit's own dispatch
        cache handles per-signature executable reuse."""
        self._compiler_options = dict(options) if options else None
        self._jitted = jax.jit(
            self._call_fn,
            **({"compiler_options": self._compiler_options}
               if self._compiler_options else {}))
        return self

    def _call_fn(self, params, *vals):
        return self._exported.call(params, *vals)

    def __call__(self, *inputs):
        vals = [x._value if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        out = self._jitted(self._param_vals, *vals)
        if isinstance(out, (list, tuple)):
            outs = [Tensor(o) for o in out]
            return outs if len(outs) > 1 else outs[0]
        return Tensor(out)

    forward = __call__

    def eval(self):
        return self

    def state_dict(self):
        return {n: Tensor(v) for n, v in zip(self._names, self._param_vals)}


def load(path, **configs):
    from ..framework.io_state import load as tensor_load

    with open(path + ".stablehlo", "rb") as f:
        exported = jax.export.deserialize(f.read())
    bundle = tensor_load(path + ".pdiparams", return_numpy=True)
    param_vals = [jnp.asarray(v) for v in bundle["params"]]
    return TranslatedLayer(exported, bundle["names"], param_vals,
                           n_inputs=bundle.get("n_inputs"))


# ------------------------------------------------------------- train step
class TrainStep:
    """Whole-step compilation: loss + backward + optimizer update as ONE
    XLA program over the parameter pytree. This is the idiomatic TPU
    training path (replaces the reference's per-op executor hot loop,
    SURVEY.md §3.3) and what bench.py runs.

    loss_fn(model, *batch_tensors) -> scalar loss Tensor.
    """

    def __init__(self, model, loss_fn, optimizer, donate_params=True,
                 remat=False):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.donate_params = donate_params
        # remat: False -> off, True -> keep nothing, str/callable ->
        # jax.checkpoint policy name ('dots_saveable' keeps MXU outputs;
        # see fleet.recompute.checkpoint_policy) — same knob as
        # DistributedTrainStep, usable single-chip where the step is
        # HBM-bound (docs/PERF_NOTES.md hypothesis 3)
        self.remat = remat
        self._names = list(model.state_dict().keys())
        self._param_objs = [model.state_dict()[n] for n in self._names]
        self._trainable = [not p.stop_gradient for p in self._param_objs]
        self._opt_states = None
        self._compiled = None
        self._last_batch_avals = None
        self._telemetry_full = False
        # shape-churn accounting (see __call__'s recompile guard)
        self._batch_signatures = set()
        self._sig_warned = False
        self.max_batch_signatures = 8
        # previous step's last phase stamp — the next step's data_wait
        # anchor (observability.steptrace; per-instance so interleaved
        # steps don't cross-pollute their input-wait attribution)
        self._steptrace_prev_end = None

    @property
    def num_batch_signatures(self):
        """Distinct batch (shape, dtype) signatures seen — each one is
        a separate compiled program."""
        return len(self._batch_signatures)

    def _build(self):
        from ..core import rng as rng_mod

        self._telemetry_full = _obs._STATE.mode >= _obs._STATE.FULL
        model = self.model
        loss_fn = self.loss_fn
        param_objs = self._param_objs
        trainable = self._trainable
        opt = self.optimizer
        train_objs = [p for p, t in zip(param_objs, trainable) if t]
        # per-step dropout keys: fold the step index into this base key
        # inside the compiled program (constant-baked keys would replay the
        # same mask every step). The key is a RUNTIME ARGUMENT, not a
        # closure constant: a baked key makes every TrainStep instance a
        # distinct HLO, so no two instances could share a persistent-
        # cache entry. As an argument, all structurally-equal steps
        # share one.
        self._base_key = rng_mod.next_key()

        def pure_loss(train_vals, frozen_vals, batch_vals, step_key):
            originals = [p._value for p in param_objs]
            it_t = iter(train_vals)
            it_f = iter(frozen_vals)
            for p, tr in zip(param_objs, trainable):
                p._value = next(it_t) if tr else next(it_f)
            try:
                batch = [Tensor(v, stop_gradient=True) for v in batch_vals]
                with rng_mod.trace_key_scope(step_key):
                    loss = loss_fn(model, *batch)
                # buffer updates (BN running stats) written during forward
                new_frozen = [p._value for p, tr in zip(param_objs, trainable)
                              if not tr]
            finally:
                for p, v in zip(param_objs, originals):
                    p._value = v
            return loss._value, new_frozen

        if self.remat:
            from ..distributed.fleet.recompute import checkpoint_policy

            pure_loss = jax.checkpoint(
                pure_loss, policy=checkpoint_policy(self.remat))

        # full telemetry folds the global grad L2 norm into the step
        # program (free on-device; reading it costs one sync in
        # __call__). Decided at BUILD time: the aux output changes the
        # HLO, and flipping per-call would defeat the one-executable
        # design.
        telemetry_full = self._telemetry_full

        def step(train_vals, frozen_vals, opt_states, lr, batch_vals,
                 step_idx, base_key):
            step_key = jax.random.fold_in(base_key, step_idx)
            (loss, new_frozen), grads = jax.value_and_grad(
                pure_loss, has_aux=True)(
                train_vals, frozen_vals, batch_vals, step_key)
            # named scope (metadata only): the optimizer's share of a
            # device profile, beside the model's words (gpt.py)
            with jax.named_scope("optimizer"):
                new_vals, new_states = opt.apply_gradients_tree(
                    train_vals, grads, opt_states, lr,
                    param_objs=train_objs)
            if telemetry_full:
                gn = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)))
                return loss, new_vals, new_states, new_frozen, gn
            return loss, new_vals, new_states, new_frozen

        # donate param + optimizer-state + buffer arrays so XLA updates in
        # place (no HBM copy per step); donate_params=False keeps the
        # pre-step arrays readable (e.g. for step-over-step diffing).
        # _jit_step is the subclass hook: HybridTrainStep pins mesh
        # in/out shardings around the SAME step fn and donate layout.
        self._compiled = self._jit_step(step)

    def _jit_step(self, step):
        return jax.jit(step, donate_argnums=self._donate_argnums)

    def _init_opt_states(self, train_vals):
        """First-call optimizer-state init (subclass hook: the hybrid 3D
        step device_puts the fresh states onto their ZeRO placements so
        the compiled step never pays a reshard copy)."""
        return self.optimizer.init_states_tree(train_vals)

    # the compiled step's signature, ONE definition for every off-path
    # consumer (lower(), the donation probe, analysis.analyze_step) —
    # __call__ inlines the same layout on the hot path; a signature
    # change must touch _build/__call__ and this block together
    _STEP_ARG_NAMES = ("params", "buffers", "opt_state", "lr", "batch",
                       "step_idx", "base_key")
    # label for pt_step_donation_held — subclasses that are a distinct
    # step family (HybridTrainStep) publish under their own series
    _donation_gauge_label = "train"

    @property
    def _donate_argnums(self):
        return (0, 1, 2) if self.donate_params else ()

    def _step_args(self, batch_vals):
        """Positional args of the compiled step for the CURRENT live
        state; `batch_vals` may be arrays or ShapeDtypeStructs."""
        train_vals, frozen_vals = self._split_vals()
        states = (self._opt_states if self._opt_states is not None
                  else self.optimizer.init_states_tree(train_vals))
        return (train_vals, frozen_vals, states,
                np.float32(self.optimizer.get_lr()), list(batch_vals),
                jnp.asarray(self.optimizer._step_count, jnp.uint32),
                self._base_key)

    def _split_vals(self):
        train_vals = [p._value for p, t in zip(self._param_objs,
                                               self._trainable) if t]
        frozen_vals = [p._value for p, t in zip(self._param_objs,
                                                self._trainable) if not t]
        return train_vals, frozen_vals

    def lower(self, *batch):
        """Lower the compiled step WITHOUT executing it — for compile-time
        inspection (cost/memory analysis: `.compile().memory_analysis()`
        is how tools/membudget.py measures HBM budgets off-hardware)."""
        if self._compiled is None:
            self._build()
        batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        return self._compiled.lower(*self._step_args(batch_vals))

    def __call__(self, *batch):
        t_entry = _steptrace.now()
        if self._compiled is None:
            self._build()
        train_vals, frozen_vals = self._split_vals()
        if self._opt_states is None:
            self._opt_states = self._init_opt_states(train_vals)
        with _trace_span("jit.TrainStep.h2d"):
            batch_vals = [b._value if isinstance(b, Tensor)
                          else jnp.asarray(b) for b in batch]
        t_h2d = _steptrace.now()
        # recompile guard: every distinct batch signature is a separate
        # XLA compile. Ragged text pipelines that skip bucketing
        # (io.BucketedBatchSampler + pad_to_bucket_collate) would
        # silently compile per unique length — warn once past the
        # threshold (reference LoD workloads, SURVEY hard part 3).
        sig = tuple((tuple(v.shape), str(v.dtype)) for v in batch_vals)
        new_sig = sig not in self._batch_signatures
        if new_sig:
            self._batch_signatures.add(sig)
            _COMPILES_TOTAL.inc()
            if len(self._batch_signatures) > 1:
                # post-warm-up signature growth: the recompile sentinel
                # (counts + flight-recorder postmortem)
                _steptrace.note_recompile(
                    self._donation_gauge_label,
                    step=int(self.optimizer._step_count),
                    signatures=len(self._batch_signatures),
                    batch_sig=repr(sig))
            # abstract batch signature for the donation probe
            # (compile_stats(check_donation=True) re-lowers without a
            # batch) — captured per SIGNATURE, not per step: this is
            # the dispatch hot path
            self._last_batch_avals = [
                jax.ShapeDtypeStruct(v.shape, v.dtype)
                for v in batch_vals]
        if (len(self._batch_signatures) == self.max_batch_signatures + 1
                and not self._sig_warned):
            self._sig_warned = True
            import warnings

            warnings.warn(
                f"TrainStep has now seen {len(self._batch_signatures)} "
                "distinct batch shapes — each one triggers a fresh XLA "
                "compile. Variable-length data should be bucketed: "
                "io.BucketedBatchSampler + io.pad_to_bucket_collate "
                "compile at most one program per bucket.",
                RuntimeWarning, stacklevel=2)
        # lr rides as a COMMITTED f32 scalar, not a bare python float: a
        # weak-typed scalar hashes differently from any committed array
        # (one stray jnp.asarray at a call site = a second executable),
        # and under x64 it drags f64 scalar chains through the program
        # (analysis.analyze_step flagged 62 f64 converts on the tier-1
        # GPT step). np.float32 keeps the python-float update path free
        # of device transfers.
        lr = np.float32(self.optimizer.get_lr())
        step_idx = jnp.asarray(self.optimizer._step_count, jnp.uint32)
        # phase trace (observability.steptrace): compile steps run
        # QUIET so their stall never enters pt_train_phase_seconds
        tr = _steptrace.begin_step(
            self._donation_gauge_label, int(self.optimizer._step_count),
            prev_end=self._steptrace_prev_end, quiet=new_sig,
            t_entry=t_entry)
        tr.stamp("h2d", t_h2d)
        _steptrace.chaos_fire("step.dispatch")
        t0 = _time.perf_counter()
        with _trace_span("jit.TrainStep",
                         step=int(self.optimizer._step_count)):
            out = self._compiled(
                train_vals, frozen_vals, self._opt_states, lr,
                batch_vals, step_idx, self._base_key)
        tr.stamp("dispatch")
        if self._telemetry_full:
            loss, new_vals, self._opt_states, new_frozen, grad_norm = out
        else:
            loss, new_vals, self._opt_states, new_frozen = out
            grad_norm = None
        if _steptrace.full():
            # device_step = the block_until_ready delta: full telemetry
            # accepts a device sync per step. Below it nothing waits
            # here — a loop that does not read the loss every step
            # keeps the next batch's conversion and dispatch under the
            # device's work, and a step's wall time (previous step's
            # end to this one's) converges to the device step once the
            # dispatch queue is full.
            jax.block_until_ready(
                (loss, new_vals, self._opt_states, new_frozen))
            tr.stamp("device_step")
        _STEP_SECONDS.observe(_time.perf_counter() - t0)
        _STEPS_TOTAL.inc()
        with _trace_span("jit.TrainStep.publish"):
            it = iter(new_vals)
            it_f = iter(new_frozen)
            for p, t in zip(self._param_objs, self._trainable):
                p._value = next(it) if t else next(it_f)
            self.optimizer._step_count += 1
        if grad_norm is not None:
            _LOSS_GAUGE.set(float(np.asarray(loss)))
            _GRAD_NORM.observe(float(np.asarray(grad_norm)))
        tr.stamp("opt_publish")
        total_s, self._steptrace_prev_end = _steptrace.end_step(tr)
        from ..profiler import benchmark

        bm = benchmark()
        if bm.enabled:  # armed ips meter (reference profiler/timer.py)
            n = batch_vals[0].shape[0] if batch_vals and \
                getattr(batch_vals[0], "ndim", 0) else None
            # feed the steptrace-measured wall (anchor -> opt_publish)
            # so the ips meter and the phase plane report ONE number;
            # quiet/compile steps keep the meter's own clock
            bm.auto_step(num_samples=n,
                         dt=(total_s if _steptrace.active()
                             and not tr.quiet else None))
        return Tensor(loss)

    def compile_stats(self, check_donation=False):
        """Recompile probe (same shape as LLMEngine.compile_stats):
        batch signatures seen + the jit dispatch-cache executable
        count. Steady-state training holds both at 1.

        `check_donation=True` additionally re-lowers the current
        signature through the live compile-cache path and reports
        whether every donated buffer (params/buffers/opt state)
        actually aliased an output in the executable — the mechanical
        regression guard for donation silently dropping, e.g. through a
        cache-served executable (docs/RESILIENCE.md). Adds a
        `"donation"` key: {"expected", "aliased", "held", "dropped"}.
        """
        n = getattr(self._compiled, "_cache_size", None)
        out = {"batch_signatures": len(self._batch_signatures),
               "executables": int(n()) if callable(n) else -1}
        if not check_donation:
            return out
        if self._compiled is None or \
                getattr(self, "_last_batch_avals", None) is None:
            raise RuntimeError(
                "compile_stats(check_donation=True) needs at least one "
                "executed step (the probe re-lowers the last batch "
                "signature)")
        from ..analysis import donation_coverage

        out["donation"] = donation_coverage(
            self._compiled, self._step_args(self._last_batch_avals),
            self._donate_argnums, names=self._STEP_ARG_NAMES)
        _DONATION_HELD.labels(step=self._donation_gauge_label).set(
            1.0 if out["donation"]["held"] else 0.0)
        return out

    def collective_schedule(self, *batch):
        """Ordered per-mesh-axis collective schedule of the compiled
        step (analysis.spmd_analysis.extract_schedule): op kind, axes,
        reduce op, payload bytes, execution count. The per-axis byte
        totals are the measured baseline ROADMAP item 2's quantized
        in-XLA all-reduce must beat; the tier-1 hybrid3d schedule is
        pinned as a golden in tests. Pure trace inspection — nothing
        executes, but like analyze_step it must run on the thread that
        owns the step."""
        from ..analysis.spmd_analysis import extract_schedule

        return extract_schedule(self, *batch)


class ProgramTranslator:
    """Global dy2static switch (reference:
    fluid/dygraph/dygraph_to_static/program_translator.py). Trace capture
    replaces AST rewriting here; the switch gates whether to_static
    functions trace or fall through to eager."""

    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        self.enable_to_static = True

    def enable(self, enable_to_static):
        self.enable_to_static = bool(enable_to_static)
        enable_to_static_fn = globals().get("enable_to_static")
        if enable_to_static_fn is not None:
            enable_to_static_fn(bool(enable_to_static))


class TracedLayer:
    """dygraph→traced executable wrapper (reference:
    fluid/dygraph/jit.py TracedLayer). On this stack trace() is just
    to_static capture; save_inference_model delegates to jit.save."""

    def __init__(self, static_fn, layer):
        self._fn = static_fn
        self._layer = layer

    @staticmethod
    def trace(layer, inputs):
        fn = to_static(layer.forward)
        outs = fn(*inputs)
        return outs, TracedLayer(fn, layer)

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    def save_inference_model(self, path, feed=None, fetch=None, **configs):
        return save(self._layer, path, **configs)


_log_verbosity = 0
_code_level = 0


def set_verbosity(level=0, also_to_stdout=False):
    """dy2static debug verbosity (reference: jit/api set_verbosity).
    Tracing has no transform pipeline to log; the level is recorded and
    exposed for tooling."""
    global _log_verbosity
    _log_verbosity = int(level)


def set_code_level(level=100, also_to_stdout=False):
    """(reference: jit/api set_code_level) — records the requested level;
    there is no transformed source to print under trace capture."""
    global _code_level
    _code_level = int(level)


class _Dy2StaticNamespace:
    """paddle.jit.dy2static compatibility surface."""

    ProgramTranslator = ProgramTranslator
    set_verbosity = staticmethod(set_verbosity)
    set_code_level = staticmethod(set_code_level)


dy2static = _Dy2StaticNamespace()

__all__ += ["ProgramTranslator", "TracedLayer", "set_verbosity",
            "set_code_level", "dy2static"]

# the mesh-aware 3D sibling (distributed.hybrid3d docs) — imported LAST:
# hybrid_step late-imports paddle_tpu.distributed, whose ps module
# imports TrainStep back from this (by now fully-populated) namespace
from .hybrid_step import HybridTrainStep  # noqa: E402,F401

__all__ += ["HybridTrainStep"]
