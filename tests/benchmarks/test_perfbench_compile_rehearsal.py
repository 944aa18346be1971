"""Compile rehearsals of the cells' kernels at their real widths against
a described (not attached) `v5e:2x2` chip: what Mosaic would refuse on
the chip it refuses here, at no chip time. Nothing runs. ONE file, the
topology described inside a fixture (the on-chip-measurement guide,
section 2): only the worker that is given this file loads the TPU
compiler; where it cannot be described the tests skip."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _cfg(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _compile(fn, *shapes):
    import jax

    # tests/conftest.py turns on x64 and "highest" matmuls for the CPU
    # parity tests; the chip runs neither, and Mosaic refuses both (f64
    # scalars; fp32 contraction of bf16 operands)
    before = (jax.config.jax_default_matmul_precision,
              jax.config.jax_enable_x64)
    jax.config.update("jax_default_matmul_precision", None)
    jax.config.update("jax_enable_x64", False)
    try:
        compiled = jax.jit(fn).lower(*shapes).compile()
    finally:
        jax.config.update("jax_default_matmul_precision", before[0])
        jax.config.update("jax_enable_x64", before[1])
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_kernel_at_the_serving_cells_geometry(one_chip):
    """16 heads of 128, bf16 pool of the engine section's page size and
    byte budget, the single tick's token budget and the slots' table."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        ragged_paged_attention)

    cfg = _cfg("cerebras-gpt-1.3b")
    e = cfg["engine"]
    h, d = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    per_page = 2 * cfg["n_layer"] * e["page_size"] * h * d * 2
    pages = e["pool_budget_bytes"] // per_page + 1
    mp = -(-e["max_model_len"] // e["page_size"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((pages, e["page_size"], h, d), jnp.bfloat16)
    table = sds((e["num_slots"], mp), jnp.int32)
    for t in (e["token_budget"], e["num_slots"]):
        _compile(lambda q, kp, vp, pt, sid, ln: ragged_paged_attention(
            q, kp, vp, pt, sid, ln),
            sds((t, h, d), jnp.bfloat16), pool, pool, table,
            sds((t,), jnp.int32), sds((t,), jnp.int32))
    # the fused window's call: a frontier offset rides along
    _compile(lambda q, kp, vp, pt, sid, ln, off: ragged_paged_attention(
        q, kp, vp, pt, sid, ln, frontier_offset=off),
        sds((e["num_slots"], h, d), jnp.bfloat16), pool, pool, table,
        sds((e["num_slots"],), jnp.int32),
        sds((e["num_slots"],), jnp.int32), sds((), jnp.int32))


@pytest.mark.parametrize("config,batch,seq", [
    ("gpt2-medium", 16, 1024), ("cerebras-gpt-1.3b", 2, 2048)])
def test_flash_forward_dq_dkv(one_chip, config, batch, seq):
    """Causal flash attention forward and backward (dq, dkv) in bf16 at
    the training cells' shapes: s1024 x hd64 and s2048 x hd128."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        flash_attention_bshd)

    cfg = _cfg(config)
    h, d = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    x = jax.ShapeDtypeStruct((batch, seq, h, d), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True)

    def bwd(q, k, v, g):
        _o, vjp = jax.vjp(fwd, q, k, v)
        return vjp(g)

    _compile(fwd, x, x, x)
    text = _compile(bwd, x, x, x, x).as_text()
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv
