"""Builder `ling_hybrid`: a configuration file → the program's own
objects, through the entry points a user calls (`LingHybridConfig`,
`LingHybridForCausalLM`, `inference.LLMServer`). The weights are the
benchmark's (`references.ling_hybrid`, from the seed), handed over leaf
by leaf under the program's names: the program fuses what the reference
keeps apart (a KDA layer's q|k|v|decay projections, its three
convolutions' taps, β|gate; gate|up) and holds an MLA layer's `W_kv_b`
as its two halves a head (`w_uk` [H, nope, latent], `w_uv` [H, latent,
v]), the layout its absorbed products read.
"""
from harness.plain import seed_key
from references import ling_hybrid as ref

NO_PROGRAM = ("this checkout's program has no paddle_tpu.text.models."
              "ling_hybrid: it cannot run configuration ling-3.0-flash-vl")


def model_config(cfg, init_weights=False):
    try:
        from paddle_tpu.text.models.ling_hybrid import LingHybridConfig
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    s = ref.dims(cfg)
    return LingHybridConfig(
        vocab_size=s["v"], hidden_size=s["d"], num_layers=s["L"],
        num_heads=s["H"], head_dim=s["dk"],
        layer_group_size=s["period"], short_conv_kernel_size=s["K"],
        kda_lower_bound=s["lower"], kv_lora_rank=s["latent"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["vd"], intermediate_size=s["f"],
        moe_intermediate_size=s["m"],
        num_shared_experts=s["ms"] // s["m"],
        first_k_dense=s["dense"], num_routed_experts=s["routed"],
        num_experts_held=s["held"], first_expert=0,
        num_experts_per_tok=s["top_k"], n_group=s["groups"],
        topk_group=s["top_groups"], routed_scaling_factor=s["scale"],
        norm_topk_prob=True, rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=s["eps"],
        max_seq_len=int(cfg["engine"]["max_model_len"]),
        dtype=cfg["serve"]["weight_dtype"], init_weights=init_weights)


def program_tree(tree, s):
    """The reference's tree → {program name: array} (inside jit)."""
    import jax.numpy as jnp

    out = {"embed": tree["embed"], "lm_head": tree["head"],
           "final_norm": tree["final_norm"]}
    for i, lw in enumerate(tree["layers"]):
        pre = f"layers.{i}."
        for same in ("attn_norm", "ffn_norm", "wo"):
            out[pre + same] = lw[same]
        if ref.is_mla(s, i):
            for same in ("wq", "q_norm", "wkv_a", "kv_norm", "kr_norm"):
                out[pre + same] = lw[same]
            kvb = lw["wkv_b"].reshape(s["latent"], s["H"],
                                      s["nope"] + s["vd"])
            out[pre + "w_uk"] = jnp.transpose(kvb[:, :, :s["nope"]],
                                              (1, 2, 0))
            out[pre + "w_uv"] = jnp.transpose(kvb[:, :, s["nope"]:],
                                              (1, 0, 2))
            out[pre + "wg"] = lw["w_g"]
        else:
            out[pre + "w_qkva"] = jnp.concatenate(
                [lw["wq"], lw["wk"], lw["wv"], lw["w_a"]], axis=1)
            out[pre + "conv"] = jnp.concatenate(
                [lw["conv_q"], lw["conv_k"], lw["conv_v"]], axis=1)
            out[pre + "a_log"] = lw["a_log"]
            out[pre + "dt_bias"] = lw["dt_bias"]
            out[pre + "w_bg"] = jnp.concatenate(
                [lw["w_beta"], lw["w_g"]], axis=1)
            out[pre + "o_norm"] = lw["o_norm"]
        if "w_gate" in lw:
            out[pre + "w_gate_up"] = jnp.concatenate(
                [lw["w_gate"], lw["w_up"]], axis=1)
            out[pre + "w_down"] = lw["w_down"]
        else:
            out[pre + "router"] = lw["router"]
            out[pre + "router_bias"] = lw["router_bias"]
            out[pre + "experts_gate_up"] = jnp.concatenate(
                [lw["e_gate"], lw["e_up"]], axis=2)
            out[pre + "experts_down"] = lw["e_down"]
            out[pre + "shared_gate_up"] = jnp.concatenate(
                [lw["s_gate"], lw["s_up"]], axis=1)
            out[pre + "shared_down"] = lw["s_down"]
    return out


def flat_weights(cfg, seed, dtype):
    """{program name: array}: made and fused in ONE jitted call."""
    import jax

    key_json, s = ref.cfg_json(cfg), ref.dims(cfg)
    return jax.jit(lambda key: program_tree(
        ref.tree_from_key(key, key_json, dtype), s))(seed_key(seed))


def build_model(cfg, seed, dtype):
    try:
        from paddle_tpu.text.models.ling_hybrid import LingHybridForCausalLM
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    model = LingHybridForCausalLM(model_config(cfg))
    flat = flat_weights(cfg, seed, dtype)
    sd = model.state_dict()
    if set(sd) != set(flat):
        raise RuntimeError(
            "the program's parameter names differ from the benchmark's "
            f"map: {sorted(set(sd) ^ set(flat))[:6]}")
    for name, p in sd.items():
        if tuple(p._value.shape) != tuple(flat[name].shape):
            raise RuntimeError(f"{name}: program {p._value.shape}, "
                               f"benchmark {flat[name].shape}")
        p._value = flat[name]
    return model


class Served:
    """The system under test for a serving cell: the attributes
    `drivers/_serving.py` reads (`cfg`, `server`, `engine`,
    `page_occupancy`, `custom_calls`, `free`)."""

    def __init__(self, cfg, seed):
        from paddle_tpu import inference

        e = cfg["engine"]
        self.cfg = cfg
        self.model = build_model(cfg, seed, cfg["serve"]["weight_dtype"])
        self.model.eval()
        self.engine_config = inference.LLMEngineConfig.for_pool_budget(
            self.model.config, int(e["pool_budget_bytes"]),
            page_size=int(e["page_size"]), kv_dtype=e["kv_dtype"],
            num_slots=int(e["num_slots"]),
            token_budget=int(e["token_budget"]),
            max_model_len=int(e["max_model_len"]),
            decode_k=int(e["decode_k"]),
            prefix_cache=bool(e["prefix_cache"]))
        self.server = inference.LLMServer(self.model, self.engine_config)
        self.engine = self.server.engine

    def page_occupancy(self):
        """Share of the MLA layers' latent page pool in use now (page 0
        is never handed out). The KDA layers' slabs are not pages: a
        slot holds its slab whole, and `engine.stats["state_slabs_live"]`
        counts them."""
        return self.engine.metrics()["kv_page_occupancy"]

    def custom_calls(self):
        """{step: {custom call target: count}} of the lowered step
        programs (call with the server stopped: it re-traces)."""
        from paddle_tpu import analysis

        which = ["paged"] + (["fused"] if self.engine.decode_k > 1 else [])
        return {w: analysis.analyze_step(
            self.engine, check_donation=False, which=w).custom_calls
            for w in which}

    def free(self):
        self.server = self.engine = self.model = None


def build(cfg, seed, kind):
    if kind in ("closed_loop", "open_loop"):
        return Served(cfg, seed)
    raise ValueError(f"builder ling_hybrid serves no mix of kind {kind!r}")
