"""The paged attention kernel compiled for a DESCRIBED TPU v5e — no
chip: the installed TPU compiler lowers `ragged_paged_attention` at the
shapes the serving paths launch, so what Mosaic refuses (a slice that is
not tile-aligned, too much VMEM) fails here and not on the chip.
Interpret-mode parity (tests/test_llm_engine.py) cannot see either.
The resident flash kernels (PR 31) are compiled here too, at the
training steps' widths, for the same reason and the reason below.

Nothing runs and nothing is timed. All of these live in ONE file: the
worker that describes the topology holds libtpu until it exits.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas_kernels.paged_attention import (
    ragged_paged_attention)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any refusal means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# (id, tokens, heads, head_dim, table [slots, pages], pool, q_per_slot)
_LAUNCHES = [
    # the decode cell (cerebras-gpt-1.3b, bf16 pool): the fused window's
    # 24 rows and the single tick's 256 — the in-kernel page walk
    ("cell_window", 24, 16, 128, (24, 128), "bfloat16", None),
    ("cell_tick", 256, 16, 128, (24, 128), "bfloat16", None),
    ("cell_verify", 120, 16, 128, (24, 128), "bfloat16", 5),
    # those three multiply on the MXU (bf16 operands as stored); a
    # float32 pool at 16 heads does too, at `Precision.HIGHEST`; 12
    # heads keep the VPU body inside the same walk
    ("walk_f32_h16", 24, 16, 128, (24, 128), "float32", None),
    ("walk_f32_h16_verify", 120, 16, 128, (24, 128), "float32", 5),
    ("walk_f32_h12", 17, 12, 128, (4, 8), "float32", None),
    # what the walk cannot slice stays on the page grid: head_dim 64,
    # 12 heads of a 16-bit pool, quantized pools
    ("grid_f32_h12x64", 17, 12, 64, (4, 8), "float32", None),
    ("grid_bf16_h12", 17, 12, 128, (4, 8), "bfloat16", None),
    ("grid_int8_h16", 17, 16, 128, (24, 128), "int8", None),
    ("grid_int4_h16", 20, 16, 128, (24, 128), "int4", 5),
]


@pytest.mark.parametrize(
    "tokens,heads,dim,table,pool,qps",
    [pytest.param(*c[1:], id=c[0]) for c in _LAUNCHES])
def test_paged_kernel_compiles_for_v5e(one_chip, tokens, heads, dim, table,
                                       pool, qps):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    quant = pool in ("int8", "int4")
    store = jnp.int8 if quant else jnp.dtype(pool)
    page = (table[0] * 4 + 1, 16, heads, dim // 2 if pool == "int4" else dim)
    args = [sds((tokens, heads, dim), jnp.float32 if quant else store),
            sds(page, store), sds(page, store), sds(table, jnp.int32),
            sds((tokens,), jnp.int32), sds((tokens,), jnp.int32),
            sds((), jnp.int32)]
    if quant:
        args += [sds(page[:3], jnp.float32)] * 2

    def call(q, k, v, pt, sid, lens, off, *scales):
        ks, vs = scales or (None, None)
        return ragged_paged_attention(
            q, k, v, pt, sid, lens, k_scales=ks, v_scales=vs,
            frontier_offset=off, q_per_slot=qps)

    # conftest turns x64 on for numpy parity; the chip runs without it
    # (and Mosaic has no 64-bit scalars to lower a Python int to)
    with jax.enable_x64(False):
        text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# The head-major walk at the published shapes of the window / full cell
# (laguna-s-2.1: 48 and 72 query heads over 8 KV heads of 128, bf16
# pools [pages, 8, 16, 128], a lower bound a row): the fused window's
# 48 rows and the single tick's 512, both cache kinds' page counts.
_GQA_LAUNCHES = [
    ("full_window_rows", 48, 48, 13313, None),
    ("full_tick_rows", 512, 48, 13313, None),
    ("sliding_window_rows", 48, 72, 1821, None),
    ("sliding_tick_rows", 512, 72, 1821, None),
    # the tick as the model launches it: 512 rows laid out in blocks of
    # 8 rows of one slot (`SlotBlockLayout`: 512 + 48 · 7 rows)
    ("full_tick_blocks", 848, 48, 13313, 8),
    ("sliding_tick_blocks", 848, 72, 1821, 8),
]


@pytest.mark.parametrize(
    "tokens,heads,pages,qps",
    [pytest.param(*c[1:], id=c[0]) for c in _GQA_LAUNCHES])
def test_head_major_gqa_kernel_compiles_for_v5e(one_chip, tokens, heads,
                                                pages, qps):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (pages, 8, 16, 128)
    args = [sds((tokens, heads, 128), jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds((48, 320), jnp.int32), sds((tokens,), jnp.int32),
            sds((tokens,), jnp.int32), sds((tokens,), jnp.int32),
            sds((), jnp.int32)]

    def call(q, k, v, pt, sid, lens, starts, off):
        return ragged_paged_attention(
            q, k, v, pt, sid, lens, kv_starts=starts,
            frontier_offset=off, head_major=True, q_per_slot=qps)

    with jax.enable_x64(False):
        text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# The LATENT walk at sarvam-105b's serving shapes: 64 heads, a row of
# 576 stored as 640 lanes, a bf16 pool of 3 GiB over 5 layers, pages of
# 16, 32 slots of 16 384 positions. (id, tokens, q_per_slot, tokens a
# DMA group)
_LATENT_LAUNCHES = [
    ("cell_window", 32, None, None),
    ("cell_tick_blocks_of_16", 2048 + 32 * 15, 16, None),
    ("tick_blocks_of_8", 1248, 8, 2048),
]


@pytest.mark.parametrize(
    "tokens,qps,group",
    [pytest.param(*c[1:], id=c[0]) for c in _LATENT_LAUNCHES])
def test_latent_walk_compiles_for_v5e(one_chip, tokens, qps, group):
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        latent_paged_attention)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((tokens, 64, 640), jnp.bfloat16),
            sds((31458, 16, 640), jnp.bfloat16),
            sds((32, 1024), jnp.int32), sds((tokens,), jnp.int32),
            sds((tokens,), jnp.int32), sds((), jnp.int32)]

    def call(q, pool, pt, sid, lens, off):
        return latent_paged_attention(
            q, pool, pt, sid, lens, 512, 0.1, frontier_offset=off,
            q_per_slot=qps, group_tokens=group)

    with jax.enable_x64(False):
        text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# The EXPANDED latent walk at the same serving shapes: a tick of 2 048
# rows laid out with its runs at multiples of 16 rows (`SlotRunLayout`:
# at most 4 runs of 512 rows, 2 048 + 4 · 15 rows and a sub-block of 512
# to spare), queries `[nope | rope | zeros]` 256 wide a head, W_UK / W_UV
# a head. (id, laid-out rows, runs)
_LATENT_EXPANDED_LAUNCHES = [
    ("cell_tick_runs_of_512", 2048 + 64 + 512, 4),
    ("one_sub_block", 512, 1),
]


@pytest.mark.parametrize(
    "total,runs",
    [pytest.param(*c[1:], id=c[0]) for c in _LATENT_EXPANDED_LAUNCHES])
def test_latent_expanded_walk_compiles_for_v5e(one_chip, total, runs):
    """Inside the VMEM it asks for (`_LATENT_VMEM_LIMIT_BYTES`): 4
    heads' queries, accumulators and results of every laid-out row
    beside two tiles of 1 024 tokens and a `[1 024, 512]` score."""
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        latent_expanded_attention)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((total, 64 * 256), jnp.bfloat16),
            sds((31458, 16, 640), jnp.bfloat16),
            sds((64, 128, 512), jnp.bfloat16),
            sds((64, 512, 128), jnp.bfloat16),
            sds((32, 1024), jnp.int32)] + [sds((runs,), jnp.int32)] * 4

    def call(q, pool, w_uk, w_uv, pt, slots, row0, first, rows):
        return latent_expanded_attention(q, pool, w_uk, w_uv, pt, slots,
                                         row0, first, rows, 0.1)

    with jax.enable_x64(False):
        text = jax.jit(call).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


def test_the_sarvam_tick_holds_an_expanded_and_an_absorbed_walk_a_layer(
        one_chip, monkeypatch):
    """sarvam-105b's tick program at the serving widths (a dense and a
    sparse layer of the five), traced as on a TPU: a layer's custom
    calls are the expanded walk, the absorbed walk (inside the loop over
    chunks of the rows left to it) and, in the sparse layer, the
    experts' two grouped products; nothing else is a kernel."""
    from paddle_tpu.nn import expert_layer
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.text.models.sarvam_mla import (SarvamMLAConfig,
                                                   SarvamMLAForCausalLM)

    monkeypatch.setattr(attention, "_pallas_backend_ok", lambda: True)
    monkeypatch.setattr(expert_layer, "_pallas_backend_ok", lambda: True)
    layers, rows, slots = 2, 2048, 32
    model = SarvamMLAForCausalLM(SarvamMLAConfig(
        vocab_size=65536, hidden_size=4096, num_layers=layers,
        num_heads=64, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=16384,
        moe_intermediate_size=2048, num_routed_experts=128,
        num_experts_per_tok=8, num_experts_held=32,
        routed_scaling_factor=2.5, max_seq_len=16384, dtype="bfloat16",
        init_weights=False))
    params = list(model.state_dict().values())

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(values, tok, pos, sid, widx, pt, klen, smp, kv):
        for p, v in zip(params, values):
            p._value = v
        return model._paged_core(tok, pos, sid, widx, pt, klen, smp, kv,
                                 slot_blocks=True)

    shapes = [sds(p._value.shape, p._value.dtype) for p in params]
    # (the suite's process-wide "highest" is no precision the experts'
    # grouped product has for bf16 operands; the chip runs without it)
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        with jax.enable_x64(False):
            text = jax.jit(step, donate_argnums=(8,)).lower(
                shapes, sds((rows,)), sds((rows,)), sds((rows,)),
                sds((rows,)), sds((slots, 1024)), sds((rows,)),
                sds((slots,)),
                [sds((31458, 16, 640), jnp.bfloat16)] * layers
            ).compile().as_text()
    finally:
        jax.config.update("jax_default_matmul_precision", before)
        for p, v in zip(params, shapes):
            p._value = v
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    walks = [ln for ln in calls if "mla_walk/pallas_call" in ln]
    assert len(walks) == 2 * layers
    assert sum("while/body/mla_walk" in ln for ln in walks) == layers
    assert sum("mla_expand" in ln for ln in calls) == 0
    assert len([ln for ln in calls if "moe_experts" in ln]) == 2
    assert len(calls) == 2 * layers + 2


def test_mosaic_refuses_a_page_slice_of_576_lanes(one_chip):
    """Why the latent row is STORED 640 wide (`CacheKind.row_store`):
    the device tiles a `[pages, 16, 576]` pool at 640 lanes anyway, and
    a manual copy of one page of it is no whole-tile slice."""
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        latent_paged_attention)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((32, 64, 576), jnp.bfloat16),
            sds((1025, 16, 576), jnp.bfloat16), sds((32, 32), jnp.int32),
            sds((32,), jnp.int32), sds((32,), jnp.int32)]
    with jax.enable_x64(False), pytest.raises(
            Exception, match="aligned to tiling"):
        jax.jit(lambda q, pool, pt, sid, lens: latent_paged_attention(
            q, pool, pt, sid, lens, 512, 0.1)).lower(*args).compile()


# The RESIDENT flash kernels (a 128-lane block of [B, S, H*D] with its
# whole sequence in VMEM) at the widths of the training steps: what a
# whole-sequence block, an in-kernel transpose or a dynamic lane offset
# costs Mosaic is only seen here. (id, (b, s, h, d), dtype, causal,
# kv_lens)
_RESIDENT_FLASH = [
    ("gpt2m_step", (16, 1024, 16, 64), "bfloat16", True, False),
    ("cgpt1p3b_step", (4, 2048, 16, 128), "bfloat16", True, False),
    ("padded_bert_batch", (16, 512, 12, 64), "bfloat16", False, True),
    ("longest_bf16", (1, 4096, 2, 64), "bfloat16", True, True),
    ("longest_f32", (1, 2048, 2, 64), "float32", True, False),
    ("three_tiles_of_128", (2, 384, 2, 128), "bfloat16", True, True),
]


@pytest.mark.parametrize(
    "shape,dtype,causal,with_lens",
    [pytest.param(*c[1:], id=c[0]) for c in _RESIDENT_FLASH])
def test_resident_flash_compiles_for_v5e(one_chip, shape, dtype, causal,
                                         with_lens):
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    lens = jax.ShapeDtypeStruct((shape[0],), jnp.int32, sharding=one_chip)
    assert fa.resident_eligible(x, x, x)

    def step(q, k, v, g, kv_lens=None):
        _o, vjp = jax.vjp(lambda a, b, c: fa.flash_attention_bshd(
            a, b, c, causal=causal, kv_lens=kv_lens), q, k, v)
        return vjp(g)

    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        with jax.enable_x64(False):
            text = jax.jit(step).lower(
                x, x, x, x, *([lens] if with_lens else [])
            ).compile().as_text()
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    # the forward and ONE backward kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_delta_rules_recurrent_step_compiles_for_v5e(one_chip):
    """PR 35: 96 slots of 32 heads' 128 × 128 float32 state, a slot's 2
    MiB a block, in place (the state argument aliased to the result): 4
    blocks double-buffered are over the default scoped VMEM, which the
    kernel raises."""
    from paddle_tpu.ops.pallas_kernels.delta_rule import (
        delta_rule_recurrent)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, dk = 96, 32, 128
    f32 = jnp.float32
    args = [sds((S, H, dk, dk), f32)] + [sds((S, dk, H), f32)] * 4 + [
        sds((S, H, dk), f32), sds((S,), jnp.int32), sds((1,), jnp.int32)]
    with jax.enable_x64(False):
        compiled = jax.jit(delta_rule_recurrent, donate_argnums=(0,)).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * dk * dk * 4   # in place
    assert mem.temp_size_in_bytes == 0


def test_the_delta_rules_chunk_kernel_compiles_for_v5e(one_chip):
    """PR 36: the chunked form of a 2 048-row tick of 96 slots, 32 heads,
    from the tick's FLAT rows as the model hands them (`delta_rule_chunked`
    with its kernel): chunks copied by their row offset from operands
    left in HBM, a head's rows a strided load, float32 products at
    HIGHEST, a product with the left operand transposed, dynamic head
    indices; the state in place, the zeroed result aliased; and NO
    laid-out copy of the rows: no `[3 040, 32, 128]` float32 (PR 35's
    five gathers), no temporary as large as one operand."""
    from paddle_tpu.nn.functional import delta_rule as dr
    from paddle_tpu.nn.functional.attention import SlotRunLayout
    from paddle_tpu.text.models.ling_hybrid import _CHUNKED_MIN_ROWS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, dk, T = 96, 32, 128, 2048
    f32 = jnp.float32

    def tick(state, q, k, v, g, beta, sids, lens):
        runs = SlotRunLayout(sids, lens, _CHUNKED_MIN_ROWS, dr.CHUNK, 0)
        return dr.delta_rule_chunked(state, q, k, v, g, beta, runs,
                                     kernel=True)

    args = [sds((S, H, dk, dk), f32)] + [sds((T, H, dk), f32)] * 4 + [
        sds((T, H), f32)] + [sds((T,), jnp.int32)] * 2
    with jax.enable_x64(False):
        compiled = jax.jit(tick, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the rows laid out again (what the plain form gathers): 3 040
    laid = SlotRunLayout(jnp.zeros((T,), jnp.int32),
                         jnp.zeros((T,), jnp.int32), _CHUNKED_MIN_ROWS,
                         dr.CHUNK, 0).total
    assert laid == 3040 and f"f32[{laid},{H},{dk}]" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == S * H * dk * dk * 4   # in place
    # beside the zeroed result only β's rows padded to a lane tile (1
    # MiB) and the layout's index vectors: a tenth of ONE operand
    assert mem.temp_size_in_bytes < T * H * dk * 4 // 10


def _head_major_walk(call, args):
    """The head-major launch `call` makes on `args`: the body counted by
    `launch_sites()`, the kernel function's name and its compiler
    parameters."""
    from paddle_tpu.ops.pallas_kernels.paged_attention import launch_sites

    with launch_sites() as sites, jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(call)(*args)
    (launch,) = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
    kernel = launch.params["jaxpr"].debug_info.func_src_info.split()[0]
    return ({b: n for b, n in sites.items() if n}, kernel,
            dict(launch.params["compiler_params"]))


# laguna-s-2.1's launches as the model makes them (the fused window's 48
# rows, the tick's 512 in blocks of 8 rows of one slot), full and sliding
# layers: grouped queries keep `_gqa_walk_kernel` and the default scoped
# VMEM (a raised limit for every head count cost that cell 7.8 %:
# PERF.md §6)
@pytest.mark.parametrize("tokens,heads,pages,qps", [
    pytest.param(48, 48, 13313, None, id="full_window_rows"),
    pytest.param(848, 48, 13313, 8, id="full_tick_blocks"),
    pytest.param(48, 72, 1821, None, id="sliding_window_rows"),
    pytest.param(848, 72, 1821, 8, id="sliding_tick_blocks"),
])
def test_grouped_queries_keep_the_gqa_walk_and_its_compiler_parameters(
        one_chip, tokens, heads, pages, qps):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (pages, 8, 16, 128)
    args = [sds((tokens, heads, 128), jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds((48, 320), jnp.int32), sds((tokens,), jnp.int32),
            sds((tokens,), jnp.int32), sds((tokens,), jnp.int32),
            sds((), jnp.int32)]

    def call(q, k, v, pt, sid, lens, starts, off):
        return ragged_paged_attention(
            q, k, v, pt, sid, lens, kv_starts=starts,
            frontier_offset=off, head_major=True, q_per_slot=qps)

    assert _head_major_walk(call, args) == (
        {"gqa": 1}, "_gqa_walk_kernel", {})


# olmo-hybrid-7b's full layers: the head-major walk with ONE query head a
# K/V head (30 of 128, bf16 pools [8 739, 30, 16, 128] = 4 GiB over two
# layers): the fused window's 128 rows and the tick's 512 in blocks of 8
# rows of one slot (512 + 128 · 7 rows). Both take `_mha_walk_kernel`
# (blocks of 16 heads, a float32 score tile of at most 1 MiB a product)
# inside the default scoped VMEM; the parent's head-at-a-time body needed
# 59.5 MB for the tick's 30 unrolled heads and a raised limit.
@pytest.mark.parametrize("tokens,qps", [
    pytest.param(128, None, id="window_rows"),
    pytest.param(1408, 8, id="tick_blocks"),
])
def test_head_major_walk_at_one_query_head_a_kv_head_compiles_for_v5e(
        one_chip, tokens, qps):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (8739, 30, 16, 128)
    args = [sds((tokens, 30, 128), jnp.bfloat16), sds(pool, jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds((128, 128), jnp.int32),
            sds((tokens,), jnp.int32), sds((tokens,), jnp.int32),
            sds((), jnp.int32)]

    def call(q, k, v, pt, sid, lens, off):
        return ragged_paged_attention(q, k, v, pt, sid, lens,
                                      frontier_offset=off, head_major=True,
                                      q_per_slot=qps)

    assert _head_major_walk(call, args) == ({"mha": 1}, "_mha_walk_kernel",
                                            {})
    with jax.enable_x64(False):
        text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_the_delta_rules_head_gate_forms_compile_for_v5e(one_chip):
    """olmo-hybrid-7b's GDN layers at the cell's shapes: 128 slots of 30
    heads' [96, 256] float32 state (the value width 192 in whole lane
    tiles), a 512-row tick's chunked form from its runs laid out head-
    major, and the recurrent step a row a slot, a decay a head: both
    kernels take the state in place, with no copy of it (a `[…, 96,
    192]` state would be laid out slot-minor and copied in and out)."""
    from paddle_tpu.nn.functional import delta_rule as dr
    from paddle_tpu.nn.functional.attention import SlotRunLayout
    from paddle_tpu.text.models.ling_hybrid import _CHUNKED_MIN_ROWS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, H, dk, dv, T = 128, 30, 96, 256, 512
    f32 = jnp.float32
    state = sds((S, H, dk, dv), f32)

    def tick(state, q, k, v, g, beta, sids, lens):
        runs = SlotRunLayout(sids, lens, _CHUNKED_MIN_ROWS, dr.CHUNK, 0)
        return dr.delta_rule_chunked(state, q, k, v, g, beta, runs,
                                     kernel=True)

    def step(state, q, k, v, g, beta, live, fresh):
        return dr.delta_rule_step(state, q, k, v, g, beta, live, fresh,
                                  kernel=True)

    for fn, rows, tail in (
            (tick, T, [sds((T,), jnp.int32)] * 2),
            (step, S, [sds((S,), jnp.bool_)] * 2)):
        args = [state, sds((rows, H, dk), f32), sds((rows, H, dk), f32),
                sds((rows, H, dv), f32), sds((rows, H), f32),
                sds((rows, H), f32)] + tail
        with jax.enable_x64(False):
            compiled = jax.jit(fn, donate_argnums=(0,)).lower(
                *args).compile()
        assert compiled.as_text().count(
            'custom_call_target="tpu_custom_call"') == 1
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == S * H * dk * dv * 4   # in place
        assert mem.temp_size_in_bytes < S * H * dk * dv * 4 // 50
