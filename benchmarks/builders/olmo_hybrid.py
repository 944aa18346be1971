"""Builder `olmo_hybrid`: a configuration file → the program's own
objects, through the entry points a user calls (`OlmoHybridConfig`,
`OlmoHybridForCausalLM`, `inference.LLMServer`). The weights are the
benchmark's (`references.olmo_hybrid`, from the seed), handed over leaf
by leaf under the program's names: the program fuses what the reference
keeps apart (a GDN layer's q|k|v|gate|decay|β projections and its three
convolutions' taps; a full layer's q|k|v; gate|up).
"""
from harness.plain import seed_key
from references import olmo_hybrid as ref

NO_PROGRAM = ("this checkout's program has no paddle_tpu.text.models."
              "olmo_hybrid: it cannot run configuration olmo-hybrid-7b")


def model_config(cfg, init_weights=False):
    try:
        from paddle_tpu.text.models.olmo_hybrid import OlmoHybridConfig
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    s = ref.dims(cfg)
    return OlmoHybridConfig(
        vocab_size=s["v"], hidden_size=s["d"], num_layers=s["L"],
        layer_types=list(s["types"]), num_heads=s["heads"],
        num_kv_heads=s["kv"], intermediate_size=s["f"],
        linear_num_heads=s["H"], linear_key_head_dim=s["dk"],
        linear_value_head_dim=s["dv"], conv_taps=s["K"],
        allow_neg_eigval=s["neg"], rms_norm_eps=s["eps"],
        max_seq_len=int(cfg["engine"]["max_model_len"]),
        dtype=cfg["serve"]["weight_dtype"], init_weights=init_weights)


def program_tree(tree, s):
    """The reference's tree → {program name: array} (inside jit)."""
    import jax.numpy as jnp

    out = {"embed": tree["embed"], "lm_head": tree["head"],
           "final_norm": tree["final_norm"]}
    for i, lw in enumerate(tree["layers"]):
        pre = f"layers.{i}."
        for same in ("attn_out_norm", "mlp_out_norm", "wo", "w_down"):
            out[pre + same] = lw[same]
        out[pre + "w_gate_up"] = jnp.concatenate(
            [lw["w_gate"], lw["w_up"]], axis=1)
        if ref.is_full(s, i):
            out[pre + "wqkv"] = jnp.concatenate(
                [lw["wq"], lw["wk"], lw["wv"]], axis=1)
            out[pre + "q_norm"] = lw["q_norm"]
            out[pre + "k_norm"] = lw["k_norm"]
        else:
            out[pre + "w_in"] = jnp.concatenate(
                [lw["wq"], lw["wk"], lw["wv"], lw["w_g"], lw["w_a"],
                 lw["w_b"]], axis=1)
            out[pre + "conv"] = jnp.concatenate(
                [lw["conv_q"], lw["conv_k"], lw["conv_v"]], axis=1)
            for same in ("a_log", "dt_bias", "o_norm"):
                out[pre + same] = lw[same]
    return out


def flat_weights(cfg, seed, dtype):
    """{program name: array}: made and fused in ONE jitted call."""
    import jax

    key_json, s = ref.cfg_json(cfg), ref.dims(cfg)
    return jax.jit(lambda key: program_tree(
        ref.tree_from_key(key, key_json, dtype), s))(seed_key(seed))


def build_model(cfg, seed, dtype):
    try:
        from paddle_tpu.text.models.olmo_hybrid import OlmoHybridForCausalLM
    except ImportError:
        raise SystemExit(NO_PROGRAM) from None

    model = OlmoHybridForCausalLM(model_config(cfg))
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
        """Share of the full layers' page pool in use now (page 0 is
        never handed out). The GDN layers' slabs are not pages: a slot
        holds its slab whole, and `engine.stats["state_slabs_live"]`
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
    raise ValueError(f"builder olmo_hybrid serves no mix of kind {kind!r}")
