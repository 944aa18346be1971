"""jaxpr/HLO-level step analysis — the compiled half of
`paddle_tpu.analysis`.

`analyze_step()` traces a live training/serving step and reports what
the SOURCE linter cannot see, because it only exists after lowering:

* **Donation coverage** — which donated buffers actually aliased an
  output in the compiled executable. This catches the PR-2 bug
  mechanically: on jax 0.4.37 a persistent-cache-served donating
  executable could silently drop (or mismatch) its input/output
  aliasing map — bit-correct results, 25% slower serving, and a
  step-corruption hazard. jax 0.9.0 keeps the map (probed on the chip
  and the CPU, PR 21), and the check stays as the guard: it compiles
  through the SAME cache path the runtime uses, so a poisoned cache
  entry is visible here.

* **Dtype promotions** — every `convert_element_type` in the program,
  with the silent upcasts (bf16→f32, f16→f32, f32→f64) split out and
  anything landing in f64 flagged: a TPU-targeted step has no business
  computing in f64 (MXU has no f64; on CPU-x64 it doubles scalar
  traffic).

* **Host callbacks / transfers** — `*_callback`, infeed/outfeed
  primitives in the step body. A compiled hot-path step should have
  none; each one is a per-step device↔host round trip.

* **Kernel path** — the custom-call targets of the lowered program
  (`custom_calls`): a Pallas kernel Mosaic compiled shows up as
  `tpu_custom_call`, so a chip run can say whether the flash / paged
  kernel ran or the jnp path did.

* **Retrace hazards** — weak-typed inputs (python scalars riding as
  jit arguments hash differently from committed arrays — one stray
  `jnp.asarray` at a call site makes a second executable) and the full
  input signature, with `signature_diff()` to name what forced a
  recompile between two traces.

Accepts a `jit.TrainStep`, an `inference.LLMEngine` / `LLMServer`
(the `_CompiledPagedStep` is analyzed with the engine's live
geometry), or any `jax.jit`-wrapped callable plus example args.
"""
import dataclasses
import re
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp

from .lint import Finding

__all__ = ["StepReport", "analyze_step", "analyze_jit",
           "donation_coverage", "signature_diff", "ANALYSIS_RULES"]

# analyzer finding ids (the AST linter owns PTL1xx-4xx; the step
# analyzer owns PTL5xx — same Finding shape, same suppression story in
# reports)
ANALYSIS_RULES = {
    "PTL511": "donation-dropped",
    "PTL512": "f64-in-program",
    "PTL513": "host-callback-in-step",
}

_HOST_CALL_PRIMS = ("callback", "infeed", "outfeed")


@dataclasses.dataclass
class StepReport:
    kind: str
    # {"expected": n, "aliased": n, "held": bool, "dropped": [labels]}
    donation: dict
    # every convert_element_type, keyed "src->dst"
    conversions: dict
    # the silent-upcast subset (bf16->f32, f16->f32, f32->f64, ...)
    promotions: dict
    # primitives that leave the device mid-step
    host_calls: dict
    # labels of weak-typed inputs (python scalars in the signature)
    weak_type_args: list
    # ((shape, dtype, weak_type), ...) per flat input — diffable
    signature: tuple
    findings: list
    # collective summary from the same trace (spmd_analysis walk):
    # {"n_collectives", "executions", "per_axis_bytes",
    # "per_axis_counts"} — {} when the program has no collectives
    collectives: dict = dataclasses.field(default_factory=dict)
    # custom-call targets in the lowered program, {target: count} —
    # which kernel path the step took: a Pallas kernel compiled by
    # Mosaic is `tpu_custom_call`; the jnp path has none
    custom_calls: dict = dataclasses.field(default_factory=dict)

    def ok(self):
        return not self.findings

    def as_dict(self):
        d = dataclasses.asdict(self)
        d["findings"] = [f.as_dict() for f in self.findings]
        return d


# ------------------------------------------------------------ jaxpr walk

def _walk_jaxpr(jaxpr, conversions, host_calls):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(tok in name for tok in _HOST_CALL_PRIMS):
            host_calls[name] += 1
        if name == "convert_element_type" and eqn.invars and \
                hasattr(eqn.invars[0], "aval"):
            src = eqn.invars[0].aval.dtype
            dst = eqn.outvars[0].aval.dtype
            conversions[f"{src}->{dst}"] += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns"):
                    _walk_jaxpr(sub, conversions, host_calls)
                elif hasattr(sub, "jaxpr") and \
                        hasattr(sub.jaxpr, "eqns"):
                    _walk_jaxpr(sub.jaxpr, conversions, host_calls)


_UPCASTS = {("bfloat16", "float32"), ("float16", "float32"),
            ("float16", "bfloat16"), ("float32", "float64"),
            ("bfloat16", "float64"), ("float16", "float64")}


def _split_promotions(conversions):
    promos = {}
    for key, n in conversions.items():
        src, dst = key.split("->")
        if (src, dst) in _UPCASTS:
            promos[key] = n
    return promos


# ------------------------------------------------------------- donation

def _flat_labels(args, names=None):
    """One label per flat leaf of the positional args tuple."""
    labels = []
    for i, a in enumerate(args):
        leaves_paths = jax.tree_util.tree_flatten_with_path(a)[0]
        base = (names[i] if names and i < len(names) and names[i]
                else f"arg{i}")
        for path, _ in leaves_paths:
            suffix = jax.tree_util.keystr(path)
            labels.append(base + suffix if suffix else base)
    return labels


def _donated_flat_indices(args, donate_argnums):
    idx, out = 0, []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate_argnums:
            out.extend(range(idx, idx + n))
        idx += n
    return out


def _aliased_param_indices(compiled):
    """Flat parameter indices that alias an output, parsed from the
    optimized-HLO module header (`input_output_alias={ {o}: (i, {},
    may-alias), ... }`)."""
    try:
        txt = compiled.as_text()
    except Exception:
        return None                       # backend can't render: unknown
    start = txt.find("input_output_alias={")
    if start == -1:
        # no alias map at all — either nothing was donated or XLA
        # dropped every alias
        return []
    i = start + len("input_output_alias=")
    depth, j = 0, i
    for j in range(i, len(txt)):
        if txt[j] == "{":
            depth += 1
        elif txt[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = txt[i:j + 1]
    # entries look like `{out}: (param, {tuple_path}, may-alias)` —
    # the param index is the first integer after each `: (`
    return sorted({int(g) for g in
                   re.findall(r":\s*\(\s*(\d+)\s*,", body)})


def donation_coverage(jitfn, args, donate_argnums, names=None,
                      lowered=None):
    """Compile (through the live cache path) and report which donated
    leaves actually aliased. Returns {"expected", "aliased", "held",
    "dropped"} — `held` means every donated buffer aliased an output,
    i.e. the in-place update actually happened.

    An empty `donate_argnums` short-circuits without lowering; a
    caller that already holds a Lowered for these args can pass it to
    skip the re-trace."""
    expected_idx = _donated_flat_indices(args, tuple(donate_argnums))
    if not expected_idx:
        return {"expected": 0, "aliased": 0, "held": True,
                "dropped": []}
    if lowered is None:
        lowered = jitfn.lower(*args)
    aliased_params = _aliased_param_indices(lowered.compile())
    if aliased_params is None:
        return {"expected": len(expected_idx), "aliased": -1,
                "held": False, "dropped": ["<unreadable executable>"]}
    # HLO parameter numbering skips UNUSED flat args (jit prunes them
    # under the default keep_unused=False) — map param j back to its
    # flat arg index through kept_var_idx before comparing, else one
    # unused leaf ahead of a donated one shifts every index and the
    # probe cries wolf. A donated-but-pruned leaf stays "dropped":
    # XLA never aliased it, the caller's buffer is consumed for
    # nothing.
    kept = None
    try:
        kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
    except (AttributeError, KeyError, TypeError):
        pass          # private attribute moved: treat numbering as flat
    if kept is not None:
        aliased_flat = {kept[j] for j in aliased_params
                        if j < len(kept)}
    else:
        aliased_flat = set(aliased_params)
    labels = _flat_labels(args, names)
    dropped = [labels[i] if i < len(labels) else f"flat[{i}]"
               for i in expected_idx if i not in aliased_flat]
    return {"expected": len(expected_idx),
            "aliased": len(aliased_flat & set(expected_idx)),
            "held": not dropped,
            "dropped": dropped}


# ------------------------------------------------------------ signatures

def _signature(in_avals):
    return tuple((tuple(a.shape), str(a.dtype),
                  bool(getattr(a, "weak_type", False)))
                 for a in in_avals)


def signature_diff(sig_a, sig_b):
    """Human-readable list of what changed between two step signatures
    — each entry is one retrace cause (shape churn, dtype flip, or a
    weak↔committed scalar flip)."""
    out = []
    if len(sig_a) != len(sig_b):
        out.append(f"arity {len(sig_a)} -> {len(sig_b)}")
    for i, (a, b) in enumerate(zip(sig_a, sig_b)):
        if a == b:
            continue
        sa, da, wa = a
        sb, db, wb = b
        if sa != sb:
            out.append(f"flat[{i}] shape {sa} -> {sb}")
        if da != db:
            out.append(f"flat[{i}] dtype {da} -> {db}")
        if wa != wb:
            out.append(f"flat[{i}] weak_type {wa} -> {wb} "
                       "(python scalar vs committed array)")
    return out


# ------------------------------------------------------------- analyzers

def analyze_jit(jitfn, args, donate_argnums=(), kind="jit", names=None,
                check_donation=True):
    """Analyze one jit-wrapped callable with example args (abstract
    `jax.ShapeDtypeStruct`s work — nothing is executed)."""
    traced = jitfn.trace(*args)
    closed = traced.jaxpr
    conversions, host_calls = Counter(), Counter()
    _walk_jaxpr(closed.jaxpr, conversions, host_calls)
    conversions = dict(conversions)
    promotions = _split_promotions(conversions)
    labels = _flat_labels(args, names)
    weak = [labels[i] if i < len(labels) else f"flat[{i}]"
            for i, a in enumerate(closed.in_avals)
            if getattr(a, "weak_type", False)]
    sig = _signature(closed.in_avals)

    # traced.lower() reuses the trace above — one trace, not two
    lowered = traced.lower()
    custom_calls = dict(Counter(re.findall(
        r'custom_call\s*@([\w.$-]+)', lowered.as_text())))
    if check_donation and donate_argnums:
        donation = donation_coverage(jitfn, args, donate_argnums,
                                     names=names, lowered=lowered)
    else:
        donation = {"expected": 0, "aliased": 0, "held": True,
                    "dropped": []}

    findings = []

    def f(rule, msg):
        findings.append(Finding(
            rule=rule, name=ANALYSIS_RULES[rule], path=f"<{kind}>",
            line=0, col=0, message=msg, func=kind))

    if not donation["held"]:
        f("PTL511",
          f"donation dropped for {len(donation['dropped'])} of "
          f"{donation['expected']} donated buffers "
          f"({', '.join(donation['dropped'][:4])}"
          f"{'…' if len(donation['dropped']) > 4 else ''}) — the "
          "compiled executable copies instead of updating in place "
          "(the PR-2 persistent-cache aliasing bug shape)")
    f64 = {k: n for k, n in conversions.items()
           if k.endswith("->float64")}
    if f64:
        f("PTL512",
          f"program promotes into float64 ({f64}) — TPU has no f64 "
          "MXU path; pin dtypes (weak python scalars under x64 are "
          "the usual source)")
    if host_calls:
        f("PTL513",
          f"host callbacks inside the step body ({dict(host_calls)}) "
          "— each is a per-step device-host round trip")

    # collective schedule off the SAME trace (no second lowering):
    # summary stats ride the report, and a rank-conditioned collective
    # (PTL604) found during the walk is a finding like any other
    from .spmd_analysis import collectives_of_jaxpr

    sched = collectives_of_jaxpr(closed)
    findings.extend(sched.findings)
    collectives = {}
    if sched.ops:
        collectives = {"n_collectives": len(sched.ops),
                       "executions": sum(c.count for c in sched.ops),
                       "per_axis_bytes": sched.per_axis_bytes,
                       "per_axis_counts": sched.per_axis_counts}

    return StepReport(kind=kind, donation=donation,
                      conversions=conversions, promotions=promotions,
                      host_calls=dict(host_calls),
                      weak_type_args=weak, signature=sig,
                      findings=findings, collectives=collectives,
                      custom_calls=custom_calls)


def _analyze_trainstep(step, batch, check_donation):
    from ..tensor_core import Tensor

    if type(step).__name__ == "SparseTrainStep":
        # its compiled signature carries per-step rows/inv operands
        # (distributed/ps.py) — the 7-arg TrainStep layout below would
        # trace with the wrong arity
        raise TypeError(
            "analyze_step does not support SparseTrainStep: its "
            "compiled signature carries per-step rows/inv operands — "
            "analyze a dense TrainStep of the same model instead")
    if step._compiled is None:
        step._build()
    if not batch:
        raise ValueError(
            "analyze_step(TrainStep) needs one example batch: "
            "analyze_step(step, x, y)")
    batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
    # the step's own signature helper — ONE layout definition shared
    # with lower() and compile_stats(check_donation=True)
    return analyze_jit(step._compiled, step._step_args(batch_vals),
                       donate_argnums=step._donate_argnums,
                       kind="TrainStep", names=step._STEP_ARG_NAMES,
                       check_donation=check_donation)


def _analyze_dist_trainstep(step, batch, check_donation):
    from ..tensor_core import Tensor

    if not batch:
        raise ValueError(
            "analyze_step(DistributedTrainStep) needs one example "
            "batch: analyze_step(step, x, y)")
    batch_vals = [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
    if step._compiled is None:
        step._build(batch_vals)
    # the step's OWN layout helpers (parallel_step._step_args /
    # _donate_argnums / _STEP_ARG_NAMES) — one definition shared with
    # __call__, so probe-vs-runtime drift can't defeat the guard
    return analyze_jit(step._compiled, step._step_args(batch_vals),
                       donate_argnums=step._donate_argnums,
                       kind="DistributedTrainStep",
                       names=step._STEP_ARG_NAMES,
                       check_donation=check_donation)


def _paged_step_args(engine):
    """The engine's compiled-step example args, from its live geometry
    and pools (nothing is executed — donation is safe to analyze). The
    kv_state pytree is (pools, scale planes, PRNG key) — the sampling
    key rides the donated state so reseeding never recompiles."""
    from ..distributed import mesh as mesh_mod

    T = engine.token_budget
    i32 = np.int32
    sf = engine._step_fn
    sharding = mesh_mod.named_sharding()
    flat = np.zeros((T,), i32)
    # sid / sample_idx are device-COMMITTED at runtime (the engine's
    # staging cache) — match, or the probe itself would trace a second
    # signature
    return (
        [p._value for p in sf._params],
        np.zeros((T,), i32), np.zeros((T,), i32),
        jax.device_put(np.zeros((T,), i32), sharding),
        engine._write_index(flat, flat, flat),  # one a cache kind
        engine._step_tables(), np.zeros((T,), i32),
        jax.device_put(np.zeros((engine.num_slots,), i32), sharding),
        (engine._kv, engine._kv_scales, engine._key),
    )


_PAGED_NAMES = ("weights", "tok", "pos", "slot_id", "write_idx",
                "page_tables", "kv_len", "sample_idx", "kv_state")


def _fused_step_args(engine):
    """Example args of the fused k-step decode executable
    (`_CompiledFusedStep`): per-SLOT frontier state + the same donated
    kv_state pytree as the single-tick step."""
    S = engine.num_slots
    i32 = np.int32
    sf = engine._ensure_fused()
    # grammar args must MATCH the live dispatch (committed device
    # tables on a token_strs engine, all-None otherwise), or the
    # probe itself would trace a second signature
    gst, gtrans, gmask = engine._grammar_args(())
    return (
        [p._value for p in sf._params],
        np.zeros((S,), i32), np.zeros((S,), i32), np.ones((S,), i32),
        np.zeros((S,), bool), np.full((S,), -1, i32),
        np.zeros((S,), np.float32), np.ones((S,), np.float32),
        np.zeros((S,), i32), gst, gtrans, gmask,
        engine._step_tables(),
        (engine._kv, engine._kv_scales, engine._key),
    )


_FUSED_NAMES = ("weights", "tok0", "pos0", "rem", "fin0", "eos",
                "temps", "top_ps", "streams", "gstate0", "gtrans",
                "gmask", "page_tables", "kv_state")


def _verify_step_args(engine):
    """Example args of the speculative-verify executable
    (`speculative._CompiledVerifyStep`): per-SLOT frontier state plus
    the [S, k] draft-proposal matrix, and the same donated kv_state
    pytree as every other decode executable."""
    spec = engine._spec
    if spec is None:
        raise TypeError(
            "analyze_step(which='verify') needs a speculative engine — "
            "configure LLMEngineConfig(draft_model=..., spec_k=...)")
    S = engine.num_slots
    i32 = np.int32
    gst, gtrans, gmask = engine._grammar_args(())
    return (
        [p._value for p in spec._verify_fn._params],
        np.zeros((S,), i32), np.zeros((S,), i32),
        np.zeros((S, spec.k), i32), np.ones((S,), i32),
        np.ones((S,), i32), np.zeros((S,), bool),
        np.full((S,), -1, i32), np.zeros((S,), np.float32),
        np.ones((S,), np.float32), np.zeros((S,), i32),
        gst, gtrans, gmask,
        engine._step_tables(),
        (engine._kv, engine._kv_scales, engine._key),
    )


_VERIFY_NAMES = ("weights", "tok0", "pos0", "drafts", "width", "rem",
                 "fin0", "eos", "temps", "top_ps", "streams",
                 "gstate0", "gtrans", "gmask",
                 "page_tables", "kv_state")


def _propose_step_args(engine):
    """Example args of the draft propose executable
    (`speculative._CompiledProposeStep`) — donates the DRAFT pool
    pytree + the shared PRNG key."""
    spec = engine._spec
    if spec is None:
        raise TypeError(
            "analyze_step(which='propose') needs a speculative engine "
            "— configure LLMEngineConfig(draft_model=..., spec_k=...)")
    S = engine.num_slots
    i32 = np.int32
    return (
        [p._value for p in spec._propose_fn._params],
        np.zeros((S,), i32), np.zeros((S,), i32),
        np.ones((S,), i32), np.zeros((S,), bool),
        np.full((S,), -1, i32), np.zeros((S,), np.float32),
        np.ones((S,), np.float32), np.zeros((S,), i32),
        np.zeros((S,), i32), np.zeros((S,), i32),
        engine._step_tables(),
        (spec._kv, spec._kv_scales, engine._key),
    )


_PROPOSE_NAMES = ("weights", "tok0", "pos0", "rem", "fin0", "eos",
                  "temps", "top_ps", "streams", "lag", "frontier",
                  "page_tables", "kv_state")


def _analyze_engine(engine, check_donation, which="paged"):
    if which == "verify":
        # the speculative CI contract (tests/test_speculative.py):
        # zero host callbacks (PTL513) in the one-dispatch ragged
        # verify and full donation of the big pools + scales + PRNG
        # key pytree (gauge pt_step_donation_held{step="spec_verify"})
        args = _verify_step_args(engine)
        return analyze_jit(engine._spec._verify_fn._jit, args,
                           donate_argnums=(15,), kind="SpecVerify",
                           names=_VERIFY_NAMES,
                           check_donation=check_donation)
    if which == "propose":
        # the DRAFT side of the speculative contract: the propose
        # scan donates the draft pool pytree — a silent aliasing drop
        # there would copy the whole draft pool every window
        args = _propose_step_args(engine)
        return analyze_jit(engine._spec._propose_fn._jit, args,
                           donate_argnums=(12,), kind="SpecPropose",
                           names=_PROPOSE_NAMES,
                           check_donation=check_donation)
    if which == "fused":
        # the fused-window CI contract (tests/test_fused_decode.py):
        # zero host callbacks (PTL513) in the k-step scan and full
        # donation of the pools + scales + PRNG key pytree
        args = _fused_step_args(engine)
        return analyze_jit(engine._fused_fn._jit, args,
                           donate_argnums=(13,), kind="FusedDecode",
                           names=_FUSED_NAMES,
                           check_donation=check_donation)
    args = _paged_step_args(engine)
    return analyze_jit(engine._step_fn._jit, args, donate_argnums=(8,),
                       kind="PagedDecode", names=_PAGED_NAMES,
                       check_donation=check_donation)


def analyze_step(step, *batch, check_donation=True, which="paged"):
    """Analyze a live step object. Dispatches on type:

    * `jit.TrainStep` (incl. `HybridTrainStep`) or
      `distributed.DistributedTrainStep` — pass one example batch:
      `analyze_step(step, x, y)`
    * `inference.LLMEngine` / `LLMServer` — no batch needed (the
      compiled decode step has fixed geometry). `which="fused"`
      analyzes the fused k-step decode executable instead of the
      single-tick step (building it if the engine hasn't yet);
      `which="verify"` analyzes the speculative-decoding ragged verify
      executable and `which="propose"` the draft propose scan (both
      require a draft_model-configured engine).
    * anything `jax.jit`-wrapped — `analyze_step(jitted, *args)`
      (donation not inferred; use `analyze_jit` to pass
      `donate_argnums`)

    THREADING: analyzing a TrainStep/engine re-traces its pure step,
    and the trace body temporarily swaps the model's live parameter
    values for tracers — run it from the thread that owns the step (a
    serving tick on another thread mid-trace would dispatch tracers).
    """
    # late imports: analysis must not drag serving into train-only use
    try:
        from ..inference.llm_engine import LLMEngine, LLMServer
    except Exception:           # pragma: no cover - circular-import guard
        LLMEngine = LLMServer = ()
    from ..jit import TrainStep
    from ..distributed.parallel_step import DistributedTrainStep

    if isinstance(step, TrainStep):
        return _analyze_trainstep(step, batch, check_donation)
    if isinstance(step, DistributedTrainStep):
        return _analyze_dist_trainstep(step, batch, check_donation)
    if LLMServer and isinstance(step, LLMServer):
        return _analyze_engine(step.engine, check_donation, which=which)
    if LLMEngine and isinstance(step, LLMEngine):
        return _analyze_engine(step, check_donation, which=which)
    if hasattr(step, "trace") and hasattr(step, "lower"):
        return analyze_jit(step, batch, kind="jit",
                           check_donation=check_donation)
    raise TypeError(
        f"analyze_step: unsupported step type {type(step).__name__} — "
        "expected jit.TrainStep, inference.LLMEngine/LLMServer, or a "
        "jax.jit-wrapped callable")
