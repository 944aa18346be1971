"""Test bootstrap: virtual 8-device CPU mesh (SURVEY.md §4 implication (b)).

Must run before jax is imported anywhere.
"""
import os

# tests run on the CPU (the tier-1 command sets this too)
os.environ["JAX_PLATFORMS"] = "cpu"
# silence XLA:CPU AOT cache-load feature-mismatch E-spam (pseudo-features
# like +prefer-no-scatter are never reported by the host probe; same box).
# override for debugging via PADDLE_TPU_TEST_LOG_LEVEL.
os.environ["TF_CPP_MIN_LOG_LEVEL"] = os.environ.get(
    "PADDLE_TPU_TEST_LOG_LEVEL", "3")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from paddle_tpu.core import compile_cache

# Persistent compile cache: test wall time is compile-dominated, and the
# cache (keyed by HLO hash) makes warm reruns several× faster.
compile_cache.enable()

# Numeric-parity tests compare against float64 numpy; keep CPU matmuls exact.
# (On TPU the framework default stays bf16-on-MXU.)
jax.config.update("jax_default_matmul_precision", "highest")
# int64/float64 fidelity for numpy-parity tests (paddle defaults to int64
# indices); on real TPU runs x64 stays off and indices are int32.
jax.config.update("jax_enable_x64", True)
