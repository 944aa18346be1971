"""Builder `laguna`: a configuration file → the program's own objects,
through the entry points a user calls (`LagunaConfig`,
`LagunaForCausalLM`, `inference.LLMServer`). The weights are the
benchmark's (`references.laguna`, from the seed), handed over leaf by
leaf under the program's names: the program fuses what the reference
keeps apart (q|k|v, gate|up), so a fused leaf is the reference's leaves
side by side.
"""
from harness.plain import seed_key
from references import laguna as ref

NO_PROGRAM = ("this checkout's program has no paddle_tpu.text.models."
              "laguna: it cannot run configuration laguna-s-2.1")


def model_config(cfg, init_weights=False):
    try:
        from paddle_tpu.text.models.laguna import LagunaConfig
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    s = ref.dims(cfg)
    return LagunaConfig(
        vocab_size=s["v"], hidden_size=s["d"], num_layers=s["L"],
        layer_types=s["kinds"], num_heads_per_layer=s["heads"],
        num_kv_heads=s["kv"], head_dim=s["hd"], mlp_layer_types=s["mlps"],
        intermediate_size=s["f"], moe_intermediate_size=s["m"],
        shared_expert_intermediate_size=s["ms"],
        num_routed_experts=s["routed"], num_experts_held=s["held"],
        first_expert=0, num_experts_per_tok=s["top_k"],
        routed_scaling_factor=s["scale"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        rope_parameters=cfg["rope_parameters"],
        sliding_window=s["window"], rms_norm_eps=s["eps"],
        max_seq_len=int(cfg["engine"]["max_model_len"]),
        dtype=cfg["serve"]["weight_dtype"], init_weights=init_weights)


def program_tree(tree):
    """The reference's tree → {program name: array} (inside jit)."""
    import jax.numpy as jnp

    out = {"embed": tree["embed"], "lm_head": tree["head"],
           "final_norm": tree["final_norm"]}
    for i, lw in enumerate(tree["layers"]):
        pre = f"layers.{i}."
        out[pre + "attn_norm"] = lw["attn_norm"]
        out[pre + "ffn_norm"] = lw["ffn_norm"]
        out[pre + "wqkv"] = jnp.concatenate(
            [lw["wq"], lw["wk"], lw["wv"]], axis=1)
        out[pre + "wg"] = lw["wg"]
        out[pre + "wo"] = lw["wo"]
        if "w_gate" in lw:
            out[pre + "w_gate_up"] = jnp.concatenate(
                [lw["w_gate"], lw["w_up"]], axis=1)
            out[pre + "w_down"] = lw["w_down"]
        else:
            out[pre + "router"] = lw["router"]
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

    key_json = ref.cfg_json(cfg)
    return jax.jit(lambda key: program_tree(
        ref.tree_from_key(key, key_json, dtype)))(seed_key(seed))


def build_model(cfg, seed, dtype):
    try:
        from paddle_tpu.text.models.laguna import LagunaForCausalLM
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    model = LagunaForCausalLM(model_config(cfg))
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
            self.model.config,
            {"full": int(e["pool_budget_bytes"]),
             "window": int(e["window_pool_budget_bytes"])},
            page_size=int(e["page_size"]), kv_dtype=e["kv_dtype"],
            num_slots=int(e["num_slots"]),
            token_budget=int(e["token_budget"]),
            max_model_len=int(e["max_model_len"]),
            decode_k=int(e["decode_k"]),
            prefix_cache=bool(e["prefix_cache"]))
        self.server = inference.LLMServer(self.model, self.engine_config)
        self.engine = self.server.engine

    def page_occupancy(self):
        """Share of the FULLER page pool in use now (page 0 of a pool is
        never handed out)."""
        pools = [self.engine.pool] + [k.pool for k in self.engine._extra]
        return max(p.num_live / (p.num_pages - 1) for p in pools)

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
    raise ValueError(f"builder laguna serves no mix of kind {kind!r}")
