"""Builder `gpt`: a configuration file → the program's own objects,
through the entry points a user calls (`GPTConfig`, `GPTForCausalLM`,
`inference.LLMServer`, `paddle.jit.TrainStep`). This and the drivers
are the only benchmark files that import the program. The weights are
the benchmark's (`references.gpt2`, from the seed), handed over leaf by
leaf under the program's names.
"""
from harness.plain import seed_key
from references import gpt2

# tree name -> the program's state_dict suffix inside gpt.layers.<i>.
_LAYER_NAMES = {
    "ln1_w": "ln1.weight", "ln1_b": "ln1.bias", "ln2_w": "ln2.weight",
    "ln2_b": "ln2.bias", "qkv_w": "qkv.weight", "qkv_b": "qkv.bias",
    "proj_w": "proj.weight", "proj_b": "proj.bias",
    "fc1_w": "fc1.weight", "fc1_b": "fc1.bias", "fc2_w": "fc2.weight",
    "fc2_b": "fc2.bias"}
_TOP_NAMES = {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight",
              "lnf_w": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def program_name(tree_name, layer=None):
    if layer is None:
        return _TOP_NAMES[tree_name]
    return f"gpt.layers.{layer}.{_LAYER_NAMES[tree_name]}"


def tree_position(name):
    """Inverse of `program_name`: → (tree key, layer or None)."""
    for k, v in _TOP_NAMES.items():
        if v == name:
            return k, None
    _, _, i, rest = name.split(".", 3)
    for k, v in _LAYER_NAMES.items():
        if v == rest:
            return "layers/" + k, int(i)
    raise KeyError(name)


def gpt_config(cfg, recompute=False):
    from paddle_tpu.text.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["n_embd"]),
        num_layers=int(cfg["n_layer"]), num_heads=int(cfg["n_head"]),
        ffn_size=cfg.get("n_inner") or None,
        max_seq_len=int(cfg["n_positions"]), dropout=0.0,
        tie_embeddings=True, recompute=recompute)


def flat_weights(cfg, seed, dtype):
    """{program name: array}: made and unstacked in ONE jitted call."""
    import jax

    items = gpt2.cfg_items(cfg)
    n_layer = int(cfg["n_layer"])

    def make(key):
        tree = gpt2.tree_from_key(key, items, dtype)
        out = {program_name(k): v for k, v in tree.items()
               if k != "layers"}
        for k, v in tree["layers"].items():
            for i in range(n_layer):
                out[program_name(k, i)] = v[i]
        return out

    return jax.jit(make)(seed_key(seed))


def build_model(cfg, seed, dtype, recompute=False):
    from paddle_tpu.text.models import GPTForCausalLM

    model = GPTForCausalLM(gpt_config(cfg, recompute))
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


def _leaf_norm(name, x):
    """L2 norm of one program leaf; a fused qkv leaf gives the norms of
    its q, k and v thirds (the layout the reference's leaves have)."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if ".qkv." in name:
        x = x.reshape(x.shape[:-1] + (3, x.shape[-1] // 3))
        x = jnp.moveaxis(x, -2, 0).reshape(3, -1)
        return jnp.sqrt(jnp.sum(x * x, axis=1))
    return jnp.sqrt(jnp.sum(x * x))


def _to_host(tree):
    import numpy as np

    return {n: np.asarray(v, np.float64) for n, v in tree.items()}


class Served:
    """The system under test for a serving cell."""

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
        """Share of the page pool in use now (page 0 is never handed
        out); a cache manager with several pools answers for its
        fullest."""
        pool = self.engine.pool
        return pool.num_live / (pool.num_pages - 1)

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


class Trained:
    """The system under test for a one-chip training cell: ONE compiled
    step with its state, used by set-up's first steps and by the window
    alike."""

    def __init__(self, cfg, seed):
        import paddle_tpu as paddle
        from paddle_tpu import amp
        from paddle_tpu.text.models import GPTPretrainingCriterion

        t = cfg["train"]
        self.cfg = cfg
        self.seed = seed
        self.model = build_model(cfg, seed, t["param_dtype"],
                                 recompute=t.get("recompute", False))
        crit = GPTPretrainingCriterion()
        o = t["optimizer"]
        opt = paddle.optimizer.AdamW(
            float(o["lr"]), parameters=self.model.parameters(),
            weight_decay=float(o["weight_decay"]))

        def loss_fn(m, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return crit(m(ids), ids)

        self.step = paddle.jit.TrainStep(self.model, loss_fn, opt)
        self._to_tensor = paddle.to_tensor

    def feed(self, ids_np):
        return self._to_tensor(ids_np)

    def compile_stats(self):
        return self.step.compile_stats()

    def custom_calls(self, ids_np):
        from paddle_tpu import analysis

        return analysis.analyze_step(self.step, self.feed(ids_np)
                                     ).custom_calls

    def _named(self, values):
        names = [n for n, t in zip(self.step._names,
                                   self.step._trainable) if t]
        return dict(zip(names, values))

    def first_moment_norms(self):
        """‖moment1‖ of every leaf, by program name (after step 1 the
        first gradient is moment1 / (1 - beta1))."""
        import jax

        m = self._named([s["moment1"] for s in self.step._opt_states])
        return _to_host(jax.jit(lambda t: {
            n: _leaf_norm(n, x) for n, x in t.items()})(m))

    def change_norms(self):
        """‖parameter − its seeded start‖ of every leaf, by program
        name; the start is made again from the seed."""
        import jax
        import jax.numpy as jnp

        start = flat_weights(self.cfg, self.seed,
                             self.cfg["train"]["param_dtype"])
        now = {n: p._value for n, p in self.model.state_dict().items()}
        return _to_host(jax.jit(lambda a, b: {
            n: _leaf_norm(n, a[n].astype(jnp.float32)
                          - b[n].astype(jnp.float32)) for n in a})(
            now, start))

    def free(self):
        self.step = self.model = None


def build(cfg, seed, kind):
    if kind in ("closed_loop", "open_loop"):
        return Served(cfg, seed)
    if kind == "train_steps":
        return Trained(cfg, seed)
    raise ValueError(f"builder gpt serves no mix of kind {kind!r}")
