"""Quantized RUNTIME — int8 as a throughput format, not a file format.

The QAT/PTQ stack in `paddle_tpu.quantization` trains and calibrates
models INTO int8; this module is the other half: running the live system
ON int8 where the bits buy bandwidth (the MXU has a native int8 path and
every serving byte is HBM- or wire-bound):

* **Int8 weight-only serving** (`quantize_model_int8`): every Linear in
  a loaded model is swapped for `Int8WeightOnlyLinear` — per-channel
  int8 weights held as BUFFERS (so `state_dict()` carries them and the
  engine's compiled decode executable threads int8 weight buffers as jit
  arguments), activations quantized dynamically per row inside the op,
  and the matmul runs `lax.dot_general(..., preferred_element_type=
  int32)` with the dequant folded into the epilogue. No calibration
  pass: weight-only + dynamic activation scales is calibration-free.

* **Int8 KV-cache codecs** (`quantize_kv_rows` / `dequantize_kv`): the
  per-(token, head) absmax quantization used by the paged KV pool
  (inference/llm_engine.py `kv_dtype="int8"`): each written row carries
  its own scale, so incremental page writes never re-scale earlier
  tokens (scales live in page-shaped planes alongside the pool).

* **Int8 wire codec** (`encode_int8_wire` / `decode_int8_wire`): the
  EQuARX-style (PAPERS.md) all-reduce/p2p payload format — per-block
  absmax scales + int8 payload, ~4× fewer bytes than fp32. Opt-in via
  `PT_QUANT_ALLREDUCE=1`; distributed/xproc.py applies it to the
  coordination-KV collective fallback and the socket p2p transport.

Env knobs (docs/QUANTIZATION.md):
  PT_KV_DTYPE        default kv-cache dtype for LLMEngine
                     (float32 | bfloat16 | int8; unset = model dtype)
  PT_QUANT_ALLREDUCE 1 = int8-with-scale wire codec for eager
                     collectives / float p2p payloads
"""
import os
import struct

import numpy as np

import jax.numpy as jnp
from jax import lax

from .. import nn
from ..ops._helpers import apply_jfn, ensure_tensor

__all__ = [
    "Int8WeightOnlyLinear", "Int4WeightOnlyLinear", "quantize_model_int8",
    "quantize_model_int4", "resolve_kv_dtype", "kv_scale_shape",
    "quantize_kv_rows", "dequantize_kv", "pack_int4", "unpack_int4",
    "quantize_kv_rows_int4", "dequantize_kv_int4",
    "quant_allreduce_enabled", "wire_eligible", "encode_int8_wire",
    "decode_int8_wire", "WIRE_MAGIC",
]

QMAX = 127.0
QMAX4 = 7.0


# ------------------------------------------------------------- int4 pack

def pack_int4(codes, axis=0):
    """int8 codes in [-8, 7] → packed bytes, HALF the size along `axis`
    (which must be even-sized). Split-halves layout: byte j holds code
    j (low nibble) and code j + size/2 (high nibble), so unpacking is a
    cheap CONCATENATE of the two de-nibbled halves — never an
    interleave reshape (the Pallas paged-attention kernel unpacks in
    VMEM, where a lane-dim interleave would not lower)."""
    codes = jnp.asarray(codes)
    n = codes.shape[axis]
    if n % 2:
        raise ValueError(f"pack_int4: axis {axis} size {n} is odd")
    lo, hi = jnp.split(codes, 2, axis=axis)
    lo_u = lo.astype(jnp.uint8) & jnp.uint8(0x0F)
    hi_u = (hi.astype(jnp.uint8) & jnp.uint8(0x0F)) << 4
    return (lo_u | hi_u).astype(jnp.int8)


def unpack_int4_halves(packed):
    """Packed int8 bytes → the two sign-extended nibble planes
    (low nibbles = the first half of the packed axis, high nibbles =
    the second), as int32. Pure shift/mask arithmetic in int32 (the
    `(x ^ 8) - 8` sign-extension), so it lowers identically under XLA
    and inside Pallas kernels — the paged-attention kernel consumes the
    planes as they are, `unpack_int4` concatenates them."""
    p = jnp.asarray(packed).astype(jnp.int32) & 0xFF
    return ((p & 0xF) ^ 8) - 8, (((p >> 4) & 0xF) ^ 8) - 8


def unpack_int4(packed, axis=0):
    """Inverse of `pack_int4`: packed int8 bytes → sign-extended int8
    codes, double the size along `axis`."""
    lo, hi = unpack_int4_halves(packed)
    return jnp.concatenate([lo.astype(jnp.int8), hi.astype(jnp.int8)],
                           axis=axis)


# ---------------------------------------------------------------- weights

class Int8WeightOnlyLinear(nn.Layer):
    """Serving-time Linear over per-channel int8 weights.

    Built from an existing (fp) linear layer at model-load time. The
    int8 weight and its per-out-channel dequant step are registered as
    persistable BUFFERS — they appear in `state_dict()`, so compiled
    steps that thread `state_dict().values()` as jit arguments (the
    `_CompiledPagedStep` / TrainStep pattern) carry int8 buffers in the
    executable instead of fp32 weights. The fp weight is dropped.

    Forward = dynamic per-row activation quant → int8×int8 matmul with
    int32 accumulation (`preferred_element_type` — the MXU-native path)
    → dequant in the epilogue by (activation step × weight step).
    Inference-only: serving runs under no_grad; there is no fake-quant
    STE here (that is the QAT stack's job)."""

    def __init__(self, linear, post_shard=None):
        super().__init__()
        from . import quantize_weight_int8
        from ..tensor_core import Tensor

        w = linear.weight  # [in, out] (paddle layout)
        q, scale = quantize_weight_int8(w, axis=1)  # scale [1, out]
        self.in_features = int(w.shape[0])
        self.out_features = int(w.shape[1])
        self.register_buffer("weight_q", Tensor(jnp.asarray(q)))
        self.register_buffer("w_step", Tensor(
            jnp.asarray(np.asarray(scale, np.float32) / QMAX)))
        self.bias = getattr(linear, "bias", None)
        # activation-layout epilogue of the layer this wrapper replaced
        # (Column/RowParallelLinear apply a shard_activation hint);
        # identity off-mesh
        self._post_shard = post_shard

    def forward(self, x):
        x = ensure_tensor(x)

        def jfn(v, wq, wstep, *b):
            f = v.astype(jnp.float32)
            a_step = jnp.maximum(
                jnp.max(jnp.abs(f), axis=-1, keepdims=True), 1e-8) / QMAX
            qv = jnp.clip(jnp.round(f / a_step), -QMAX, QMAX).astype(
                jnp.int8)
            acc = lax.dot_general(
                qv, wq, (((f.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * a_step * wstep
            if b:
                out = out + b[0].astype(jnp.float32)
            return out.astype(v.dtype)

        args = (x, self.weight_q, self.w_step)
        if self.bias is not None:
            args = args + (self.bias,)
        out = apply_jfn("int8_weight_only_matmul", jfn, *args)
        if self._post_shard is not None:
            out = self._post_shard(out)
        return out

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features}, "
                f"weight=int8 per-channel")


class Int4WeightOnlyLinear(nn.Layer):
    """Serving-time Linear over per-channel PACKED int4 weights — the
    lower-bit sibling of `Int8WeightOnlyLinear` (half the weight bytes
    again: two nibbles per byte along the in-dim, split-halves layout).

    At 4 bits (15 levels) plain absmax wastes most of the grid on one
    outlier, so the MSE clip search (`quantize_weight_int8(bits=4,
    search_mse=True)` — documented in PR 4 as "the knob that matters at
    int4") is ALWAYS on. Forward: unpack nibbles → sign-extended int8
    codes → the same dynamic per-row activation quant →
    `dot_general(int8, int8, preferred_element_type=int32)` → dequant
    epilogue. The unpack is shift/mask arithmetic the compiler fuses
    into the matmul's operand read; HBM (and `state_dict()` /
    checkpoint bytes) stay packed.

    in_features must be even (nibble pairing); `quantize_model_int4`
    leaves odd layers unquantized. TP note: the packed in-dim interleaves
    rows j and j+in/2 into one byte, so row/column mesh sharding of the
    packed buffer would split activation rows non-contiguously — int4
    buffers stay REPLICATED (use int8 for TP-sharded weight-stationary
    serving)."""

    def __init__(self, linear, post_shard=None):
        super().__init__()
        from . import quantize_weight_int8
        from ..tensor_core import Tensor

        w = linear.weight  # [in, out] (paddle layout)
        self.in_features = int(w.shape[0])
        self.out_features = int(w.shape[1])
        if self.in_features % 2:
            raise ValueError(
                f"Int4WeightOnlyLinear: in_features "
                f"{self.in_features} is odd — nibble packing pairs "
                "in-dim rows (quantize_model_int4 skips such layers)")
        q, scale = quantize_weight_int8(w, axis=1, bits=4,
                                        search_mse=True)  # scale [1, out]
        self.register_buffer("weight_q",
                             Tensor(pack_int4(jnp.asarray(q), axis=0)))
        self.register_buffer("w_step", Tensor(
            jnp.asarray(np.asarray(scale, np.float32) / QMAX4)))
        self.bias = getattr(linear, "bias", None)
        self._post_shard = post_shard

    def forward(self, x):
        x = ensure_tensor(x)

        def jfn(v, wq_packed, wstep, *b):
            wq = unpack_int4(wq_packed, axis=0)     # [in, out] int8
            f = v.astype(jnp.float32)
            a_step = jnp.maximum(
                jnp.max(jnp.abs(f), axis=-1, keepdims=True), 1e-8) / QMAX
            qv = jnp.clip(jnp.round(f / a_step), -QMAX, QMAX).astype(
                jnp.int8)
            acc = lax.dot_general(
                qv, wq, (((f.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            out = acc.astype(jnp.float32) * a_step * wstep
            if b:
                out = out + b[0].astype(jnp.float32)
            return out.astype(v.dtype)

        args = (x, self.weight_q, self.w_step)
        if self.bias is not None:
            args = args + (self.bias,)
        out = apply_jfn("int4_weight_only_matmul", jfn, *args)
        if self._post_shard is not None:
            out = self._post_shard(out)
        return out

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features}, "
                f"weight=int4 packed per-channel (MSE clip)")


def _linear_classes():
    from .. import nn
    from ..distributed.fleet.meta_parallel.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)

    return nn.Linear, ColumnParallelLinear, RowParallelLinear


def _post_shard_for(sub):
    """Reproduce the activation-sharding epilogue of the parallel-linear
    classes so a quantized model keeps the same layout hints on a mesh
    (all of them collapse to the identity off-mesh)."""
    from ..distributed.fleet.meta_parallel.mp_layers import (
        ColumnParallelLinear, shard_activation)

    if isinstance(sub, ColumnParallelLinear) and not sub.gather_output:
        return lambda out: shard_activation(
            out, *(["dp"] + [None] * (out.ndim - 2) + ["mp"]))
    return lambda out: shard_activation(
        out, *(["dp"] + [None] * (out.ndim - 1)))


def quantize_model_int8(model, skip=(), tp_shard=True):
    """Swap every Linear-family sublayer for `Int8WeightOnlyLinear`,
    in place, at model-load time. Embeddings (and the tied vocab head
    that reads the embedding weight) stay in the float dtype — the
    gather needs the float table anyway and the head wants full logit
    precision.

    skip: attribute-name substrings to leave unquantized
    (e.g. ``skip=("lm_head",)``).
    tp_shard: on a mesh with 'mp' > 1, shard the int8 weight + scale
    buffers over the tp axis (weight-stationary: ColumnParallelLinear
    ancestry → column placement, RowParallelLinear → row, plain Linear
    → whichever dim divides; distributed.hybrid3d.tp rules). False
    keeps the buffers replicated.

    Returns a report dict: layers swapped, fp bytes before, int8 bytes
    after (weights only), and — when sharding applied — a
    ``tp_placements`` {path: 'column'|'row'|None} map.
    """
    from . import QuantizedLinear
    from ..distributed import mesh as mesh_mod
    from ..distributed.fleet.meta_parallel.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)

    linear_types = _linear_classes()
    report = {"layers": 0, "weight_bytes_fp": 0, "weight_bytes_int8": 0}
    swapped = []  # (path, wrapped, tp kind)

    def swap(layer, prefix=""):
        for name, sub in list(layer.named_children()):
            path = f"{prefix}.{name}" if prefix else name
            if isinstance(sub, (Int8WeightOnlyLinear, Int4WeightOnlyLinear,
                                QuantizedLinear)):
                continue  # already quantized (runtime or QAT stack)
            if isinstance(sub, linear_types) and not any(
                    s in path for s in skip):
                w = sub.weight._value
                wrapped = Int8WeightOnlyLinear(
                    sub, post_shard=_post_shard_for(sub))
                report["layers"] += 1
                report["weight_bytes_fp"] += int(
                    w.size * w.dtype.itemsize)
                report["weight_bytes_int8"] += int(
                    wrapped.weight_q._value.nbytes
                    + wrapped.w_step._value.nbytes)
                kind = "auto"
                if isinstance(sub, ColumnParallelLinear):
                    kind = "column"
                elif isinstance(sub, RowParallelLinear):
                    kind = "row"
                swapped.append((path, wrapped, kind))
                setattr(layer, name, wrapped)
            else:
                swap(sub, path)

    swap(model)
    if tp_shard and mesh_mod.axis_size("mp") > 1:
        from ..distributed.hybrid3d.tp import shard_int8_linear

        placements = {}
        for path, wrapped, kind in swapped:
            placements[path] = shard_int8_linear(wrapped, kind)
        report["tp_placements"] = placements
    model.eval()
    return report


def quantize_model_int4(model, skip=()):
    """`quantize_model_int8`'s packed-int4 sibling: swap every
    Linear-family sublayer for `Int4WeightOnlyLinear` in place (MSE
    clip search per out-channel — load-bearing at 4 bits). Layers with
    an ODD in_features cannot nibble-pair and are left unquantized
    (counted in the report as `skipped_odd`). Buffers stay REPLICATED
    on a mesh (see the class TP note); embeddings/tied head stay float
    as in the int8 path.

    Returns {layers, skipped_odd, weight_bytes_fp, weight_bytes_int4}.
    """
    from . import QuantizedLinear

    linear_types = _linear_classes()
    report = {"layers": 0, "skipped_odd": 0,
              "weight_bytes_fp": 0, "weight_bytes_int4": 0}

    def swap(layer, prefix=""):
        for name, sub in list(layer.named_children()):
            path = f"{prefix}.{name}" if prefix else name
            if isinstance(sub, (Int4WeightOnlyLinear,
                                Int8WeightOnlyLinear, QuantizedLinear)):
                continue
            if isinstance(sub, linear_types) and not any(
                    s in path for s in skip):
                w = sub.weight._value
                if int(w.shape[0]) % 2:
                    report["skipped_odd"] += 1
                    continue
                wrapped = Int4WeightOnlyLinear(
                    sub, post_shard=_post_shard_for(sub))
                report["layers"] += 1
                report["weight_bytes_fp"] += int(
                    w.size * w.dtype.itemsize)
                report["weight_bytes_int4"] += int(
                    wrapped.weight_q._value.nbytes
                    + wrapped.w_step._value.nbytes)
                setattr(layer, name, wrapped)
            else:
                swap(sub, path)

    swap(model)
    model.eval()
    return report


# ---------------------------------------------------------------- kv cache

_KV_DTYPES = {
    "float32": jnp.float32, "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
    "int8": jnp.int8,
}


def resolve_kv_dtype(requested, compute_dtype):
    """(requested | $PT_KV_DTYPE | model compute dtype) → (storage jnp
    dtype, quantized bits). `requested` may be a string name or a
    dtype. `bits` is 0 for float pools, 8 for int8, 4 for packed int4
    (storage dtype int8, head_dim HALVED in the pool — two nibbles per
    byte; truthiness keeps every existing `if quantized:` site
    working)."""
    req = requested
    if req is None:
        req = os.environ.get("PT_KV_DTYPE", "").strip() or None
    if req is None:
        dt = jnp.dtype(compute_dtype)
        return dt, 0
    if isinstance(req, str):
        key = req.lower()
        if key in ("int4", "i4"):
            return jnp.dtype(jnp.int8), 4
        if key not in _KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {req!r}: expected one of "
                f"{sorted(set(_KV_DTYPES) | {'int4'})}")
        dt = jnp.dtype(_KV_DTYPES[key])
    else:
        dt = jnp.dtype(req)
    return dt, 8 if dt == jnp.dtype(jnp.int8) else 0


def kv_scale_shape(num_pages, page_size, num_heads):
    """Shape of the per-page scale plane stored alongside an int8 pool:
    one fp32 scale per (page, row, head) — each written token row is
    quantized ONCE with its own scale, so incremental page writes never
    invalidate earlier rows (a single per-page scalar would)."""
    return (num_pages, page_size, num_heads)


def quantize_kv_rows(x):
    """[T, H, D] float → (int8 values [T, H, D], fp32 scales [T, H]).

    Per-(token, head) absmax: dequant error ≤ absmax/254 per element,
    and the scale plane costs 4/D of the int8 payload (~6% at D=64)."""
    f = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), 1e-8) / QMAX
    q = jnp.clip(jnp.round(f / scale[..., None]), -QMAX, QMAX).astype(
        jnp.int8)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of `quantize_kv_rows` (broadcasts a trailing dim onto
    the scales)."""
    return q.astype(jnp.float32) * scale[..., None]


def quantize_kv_rows_int4(x):
    """[T, H, D] float → (packed int4 values [T, H, D/2], fp32 scales
    [T, H]). Per-(token, head) absmax against qmax 7 (15 levels);
    dequant error ≤ absmax/14 per element — 18× coarser than int8,
    which is why the engine acceptance pins its logits error, not a
    token-match rate. Packed split-halves along head_dim
    (`pack_int4`), so the pool's last dim is D/2 and the existing
    per-row scale planes carry the dequant exactly as for int8."""
    f = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(f), axis=-1), 1e-8) / QMAX4
    q = jnp.clip(jnp.round(f / scale[..., None]), -QMAX4, QMAX4).astype(
        jnp.int8)
    return pack_int4(q, axis=-1), scale


def dequantize_kv_int4(packed, scale):
    """Inverse of `quantize_kv_rows_int4` → [T, H, D] float32."""
    return unpack_int4(packed, axis=-1).astype(jnp.float32) \
        * scale[..., None]


# ---------------------------------------------------------------- wire

WIRE_MAGIC = b"PTQ8"
_WIRE_VERSION = 1
_WIRE_DTYPES = {0: np.float32, 1: np.float64}
_WIRE_CODES = {np.dtype(np.float32): 0, np.dtype(np.float64): 1}
_WIRE_HDR = struct.Struct("<4sBBHIQ")  # magic, ver, dtype, ndim, block, size


def quant_allreduce_enabled():
    return os.environ.get("PT_QUANT_ALLREDUCE", "0").strip().lower() in (
        "1", "true", "yes", "on")


def wire_eligible(arr, min_size=512):
    """Only fp32/fp64 payloads above a size floor ride the codec: tiny
    arrays (barriers, scalar telemetry) would pay header overhead for
    nothing, and int/bool payloads (ids, tokens) must stay exact.

    Deliberately DATA-INDEPENDENT (dtype + size only): inside a
    collective every rank must take the same encode path, and a
    value-dependent probe (e.g. isfinite) would let one rank's NaN grad
    publish a raw frame while its peers publish PTQ8 frames — a
    mixed-format crash mid-collective. Non-finite values are instead
    handled inside `encode_int8_wire`: they decode back as NaN blocks,
    so the NaN signal survives for downstream grad guards on every rank
    identically. Also keeps eligibility O(1) on the DP-sync hot path."""
    return arr.dtype in (np.float32, np.float64) and arr.size >= min_size


def encode_int8_wire(arr, block=2048):
    """float array → self-describing int8-with-scale frame.

    Layout: header | shape (u32 each) | per-block fp32 scales | int8
    payload. Scales are per-`block`-element absmax/127, so the relative
    error is bounded by each block's own dynamic range — the property
    that makes a quantized GRADIENT all-reduce converge (EQuARX): big
    layers can't crush small layers' scale. ~4× smaller than fp32."""
    a = np.ascontiguousarray(arr)
    code = _WIRE_CODES[np.dtype(a.dtype)]
    flat = a.reshape(-1).astype(np.float32)
    n = flat.size
    nblocks = -(-n // block) if n else 0
    pad = nblocks * block - n
    padded = np.pad(flat, (0, pad)).reshape(nblocks, block)
    # a non-finite value makes its block's scale NaN/inf, which decodes
    # the WHOLE block to NaN — the poison signal survives the wire for
    # every rank identically (see wire_eligible: eligibility must stay
    # data-independent, so crashing here is not an option either)
    scales = np.maximum(np.abs(padded).max(axis=1), 1e-12) / QMAX
    with np.errstate(invalid="ignore", over="ignore"):
        ratio = np.nan_to_num(padded / scales[:, None],
                              nan=0.0, posinf=QMAX, neginf=-QMAX)
    q = np.clip(np.round(ratio), -QMAX, QMAX).astype(np.int8)
    head = _WIRE_HDR.pack(WIRE_MAGIC, _WIRE_VERSION, code, a.ndim,
                          block, n)
    shape = np.asarray(a.shape, np.uint32).tobytes()
    return head + shape + scales.astype(np.float32).tobytes() + \
        q.reshape(-1)[:n].tobytes()


def decode_int8_wire(buf):
    """Inverse of `encode_int8_wire` → np array in the original float
    dtype."""
    magic, ver, code, ndim, block, n = _WIRE_HDR.unpack_from(buf, 0)
    if magic != WIRE_MAGIC or ver != _WIRE_VERSION:
        raise ValueError("not a PTQ8 int8 wire frame")
    off = _WIRE_HDR.size
    shape = tuple(np.frombuffer(buf, np.uint32, ndim, off))
    off += 4 * ndim
    nblocks = -(-n // block) if n else 0
    scales = np.frombuffer(buf, np.float32, nblocks, off)
    off += 4 * nblocks
    q = np.frombuffer(buf, np.int8, n, off).astype(np.float32)
    pad = nblocks * block - n
    with np.errstate(invalid="ignore"):  # poison blocks: 0 × inf → NaN
        vals = (np.pad(q, (0, pad)).reshape(nblocks, block)
                * scales[:, None]).reshape(-1)[:n]
    return vals.astype(_WIRE_DTYPES[code]).reshape(shape)


def is_quant_wire(buf):
    return bytes(buf[:4]) == WIRE_MAGIC
