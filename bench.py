"""Driver benchmark — one JSON line on stdout.

Measures the flagship GPT-small compiled train step (paddle_tpu.jit.TrainStep:
loss + backward + AdamW in ONE XLA program) on the chip, bf16 compute via
amp O1, and reports MFU against the published bf16 peak of the
`device_kind` that ran (paddle_tpu/device/peaks.py).

Process model: a chip belongs to one process at a time, so the top-level
process never imports jax; each arm runs in its OWN subprocess, one after
the other, and releases the chip when it exits. A run that finds no TPU
FAILS: there is no CPU geometry and no fallback record, and an arm that
dies fails the run (non-zero exit). Every result carries the device it
ran on (`platform`, `device_kind`, `count`). The persistent XLA
compilation cache is placed by `paddle_tpu.core.compile_cache`
(`JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`). The headline
JSON line is emitted the moment the GPT result exists; the other arms
report to stderr and `bench_detail.json`.

vs_baseline: the reference repo publishes no numbers (BASELINE.md); the
north-star is ≥0.8× GPU-reference throughput. A well-tuned GPU LLM trainer
of the reference's era runs ≈0.35 MFU, so the comparable bar is
0.8 × 0.35 = 0.28 MFU and vs_baseline = mfu / 0.28.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

BASELINE_MFU = 0.28     # 0.8 × (typical 0.35 GPU-trainer MFU): see docstring


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# Worker side: runs ONE model benchmark in its own process.
# --------------------------------------------------------------------------

def _device_stamp():
    """The device as JAX reports it — rides on every result."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bf16_flops():
    """Published bf16 peak of the chip that is running (one chip).
    Raises UnknownDeviceKind for a device without a published peak."""
    from paddle_tpu.device.peaks import running_device_peaks

    return running_device_peaks()["bf16_flops"]


def _worker_bootstrap():
    """Bring a bench worker up on the chip, or fail: a benchmark number
    exists only for a TPU."""
    import jax

    dev = _device_stamp()
    log(f"[bench] device: {dev}")
    if dev["platform"] != "tpu":
        log(f"[bench] JAX found platform={dev['platform']}, not a TPU — "
            "the benchmark does not run there")
        sys.exit(2)
    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    return jax


def gpt_flops_per_step(cfg, batch, seq):
    """Analytic fwd+bwd FLOPs: 6·P per token for matmuls (fwd 2P + bwd 4P)
    plus causal attention scores/context terms. ONE accountant shared
    with the live pt_train_mfu gauge (observability.steptrace) — bench
    math and continuous telemetry must agree on the numerator."""
    from paddle_tpu.observability.steptrace import model_flops

    return model_flops(cfg, batch, seq)


def bench_gpt():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.text.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_small)

    paddle.seed(0)
    cfg = gpt_small()
    batch, seq = 16, 1024
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    # O1: fp32 params cast to bf16 at the matmuls. (O2 bf16 params were
    # measured equal within noise once optimizer accumulators are held
    # in fp32 — the moments, not the params, were the traffic saved.)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    # BENCH_GPT_FUSED_HEAD=1: head matmul + softmax-CE fused so the
    # [b, s, vocab] logits never hit HBM (docs/PERF_NOTES.md hyp. 1).
    # Off by default until tools/mfu_sweep.py measures it on-chip.
    fused_head = os.environ.get("BENCH_GPT_FUSED_HEAD", "0") == "1"
    fused_block = int(os.environ.get("BENCH_FUSED_BLOCK", "4096"))
    # BENCH_GPT_REMAT=dots_saveable|full: rematerialization policy for
    # the whole step (PERF_NOTES hypothesis 3; off by default)
    remat = os.environ.get("BENCH_GPT_REMAT", "").strip().lower()
    if remat in ("", "0", "off", "false"):
        remat = False
    elif remat in ("1", "full", "true"):
        remat = True  # keep-nothing policy

    def loss_fn(m, ids):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            if fused_head:
                return m.fused_head_loss(ids, block_size=fused_block)
            return crit(m(ids), ids)

    step = paddle.jit.TrainStep(model, loss_fn, opt, remat=remat)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    t0 = time.perf_counter()
    l0 = float(step(ids).numpy())  # compile + step 0
    log(f"[bench] gpt-small compile+step0 {time.perf_counter()-t0:.1f}s "
        f"loss {l0:.3f}")
    for _ in range(2):  # warmup
        step(ids)
    float(step(ids).numpy())  # sync

    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step(ids)
    lN = float(last.numpy())  # sync: params chain step-to-step
    dt = (time.perf_counter() - t0) / iters
    flops = gpt_flops_per_step(cfg, batch, seq)
    mfu = flops / dt / _peak_bf16_flops()
    tokens_per_sec = batch * seq / dt
    log(f"[bench] gpt-small: {dt*1e3:.1f} ms/step, "
        f"{tokens_per_sec:,.0f} tok/s, mfu {mfu:.3f}, loss→{lN:.3f}")
    return {
        "model": "gpt-small-124M",
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(tokens_per_sec),
        "mfu": round(mfu, 4),
    }


def bench_resnet():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, nn
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000)
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())

    def loss_fn(m, x, y):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return nn.functional.cross_entropy(m(x), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    batch = 64
    x = paddle.to_tensor(
        rng.standard_normal((batch, 3, 224, 224)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)))
    t0 = time.perf_counter()
    float(step(x, y).numpy())
    log(f"[bench] resnet50 compile+step0 {time.perf_counter()-t0:.1f}s")
    for _ in range(2):
        step(x, y)
    float(step(x, y).numpy())
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step(x, y)
    float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    log(f"[bench] resnet50: {dt*1e3:.1f} ms/step, "
        f"{batch/dt:,.0f} img/s")
    return {"model": "resnet50", "ms_per_step": round(dt * 1e3, 2),
            "images_per_sec": round(batch / dt)}


def bench_bert():
    """ERNIE-3.0/BERT-base MLM pretraining step (BASELINE.md config 3)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.text.models import (
        BertForPretraining, BertPretrainingCriterion, bert_base)

    paddle.seed(0)
    cfg = bert_base()
    batch, seq = 32, 512
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    # see BENCH_GPT_FUSED_HEAD — same fused-vocab-head trade for MLM
    fused_head = os.environ.get("BENCH_BERT_FUSED_HEAD", "0") == "1"
    fused_block = int(os.environ.get("BENCH_FUSED_BLOCK", "4096"))

    def loss_fn(m, ids, labels, nsp):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            if fused_head:
                return m.fused_mlm_loss(ids, labels, nsp_labels=nsp,
                                        block_size=fused_block)
            mlm, nsp_logits = m(ids)
            return crit(mlm, labels, nsp_logits, nsp)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, cfg.vocab_size, (batch, seq))
    labels = np.full((batch, seq), -100, np.int64)
    mask = rng.random((batch, seq)) < 0.15
    labels[mask] = ids_np[mask]
    ids = paddle.to_tensor(ids_np.astype(np.int32))
    labels_t = paddle.to_tensor(labels)
    nsp = paddle.to_tensor(rng.integers(0, 2, (batch,)))

    t0 = time.perf_counter()
    float(step(ids, labels_t, nsp).numpy())
    log(f"[bench] bert-base compile+step0 {time.perf_counter()-t0:.1f}s")
    for _ in range(2):
        step(ids, labels_t, nsp)
    float(step(ids, labels_t, nsp).numpy())
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step(ids, labels_t, nsp)
    float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    # analytic fwd+bwd matmul FLOPs: 6·P_matmul per token + attention
    d, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    per_layer = 4 * d * d + 2 * d * cfg.intermediate_size
    p_matmul = L * per_layer + v * d + 2 * d * d  # + mlm head transforms
    tokens = batch * seq
    flops = 6 * p_matmul * tokens + L * batch * (4 * seq * seq * d) * 3
    mfu = flops / dt / _peak_bf16_flops()
    samples_per_sec = batch / dt
    log(f"[bench] bert-base: {dt*1e3:.1f} ms/step, "
        f"{samples_per_sec:.1f} samples/s, mfu {mfu:.3f}")
    return {"model": "bert-base-mlm", "ms_per_step": round(dt * 1e3, 2),
            "samples_per_sec": round(samples_per_sec, 1),
            "mfu": round(mfu, 4)}


def bench_deepfm():
    """DeepFM CTR step over the host-PS sparse embedding with prefetch
    overlap (BASELINE.md config 5; reference async-PS training shape,
    ps/service/communicator/communicator.h:427)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    num_fields, vocab, batch = 26, 1_000_000, 4096
    model = paddle.rec.DeepFM(num_fields=num_fields, embed_dim=16,
                              hidden=(400, 400, 400), sparse=True,
                              sparse_rule="adagrad")
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.default_rng(0)
    nb = 12
    batches = [rng.integers(0, vocab, (batch, num_fields)) for _ in range(nb)]
    ys = [paddle.to_tensor((b.sum(1) % 7 < 3).astype(np.float32))
          for b in batches]

    def prefetch(i):
        model.fm._first.emb.prefetch(batches[i % nb])
        model.fm._embed.emb.prefetch(batches[i % nb])

    # default: SparseTrainStep (host pulls + ONE compiled program + host
    # pushes; eager-parity pinned by tests). BENCH_DEEPFM_EAGER=1 falls
    # back to the per-op eager loop for an A/B.
    compiled = os.environ.get("BENCH_DEEPFM_EAGER", "0") != "1"
    if compiled:
        from paddle_tpu.distributed.ps import SparseTrainStep

        def loss_fn(m, ids, y):
            return nn.functional.binary_cross_entropy_with_logits(
                m(ids), y)

        sts = SparseTrainStep(model, loss_fn, opt)

        def step(i):
            # prefetch AFTER the step: the single pending slot must not
            # be overwritten before sts consumes it (a pre-step prefetch
            # would key-miss every _acquire — 0 hits, doubled pulls)
            out = sts(paddle.to_tensor(batches[i % nb]), ys[i % nb])
            prefetch(i + 1)
            return out
    else:
        def step(i):
            logits = model(paddle.to_tensor(batches[i % nb]))
            prefetch(i + 1)  # pull NEXT batch's rows during backward/opt
            loss = nn.functional.binary_cross_entropy_with_logits(
                logits, ys[i % nb])
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    prefetch(0)
    t0 = time.perf_counter()
    l0 = float(step(0).numpy())
    log(f"[bench] deepfm compile+step0 {time.perf_counter()-t0:.1f}s "
        f"loss {l0:.3f}")
    for i in range(1, 3):
        step(i)
    iters = 10
    t0 = time.perf_counter()
    for i in range(3, 3 + iters):
        last = step(i)
    lN = float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    eps = batch / dt
    log(f"[bench] deepfm: {dt*1e3:.1f} ms/step, {eps:,.0f} examples/s, "
        f"loss→{lN:.3f}")
    return {"model": "deepfm-ctr-ps", "ms_per_step": round(dt * 1e3, 2),
            "examples_per_sec": round(eps)}


def bench_mnist():
    """LeNet eager single-device steps/sec (BASELINE.md config 1) — the
    per-op eager-dispatch overhead metric; everything else here is jitted."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Momentum(0.01, parameters=model.parameters())
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((128, 1, 28, 28),
                                             ).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 10, (128,)).astype(np.int64))

    def step():
        loss = nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    t0 = time.perf_counter()
    float(step().numpy())
    log(f"[bench] mnist warmup {time.perf_counter()-t0:.1f}s")
    for _ in range(3):
        step()
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step()
    float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    log(f"[bench] mnist-lenet eager: {dt*1e3:.1f} ms/step, "
        f"{1/dt:.1f} steps/s")
    return {"model": "mnist-lenet-eager", "ms_per_step": round(dt * 1e3, 2),
            "steps_per_sec": round(1 / dt, 1)}


def bench_gpt1p3b():
    """GPT-1.3B on ONE chip (manual arm — NOT in the best-effort loop:
    first compile is heavy). Exact recipe from docs/PERF_NOTES.md: O2
    bf16 params (resident 13.16 GB measured — O1 would not fit), fused
    vocab head, per-layer recompute. BASELINE.md config 4's single-chip
    fallback number: tokens/sec/chip + MFU."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_1p3b

    paddle.seed(0)
    cfg = gpt_1p3b(recompute=True)
    batch, seq = 1, 2048
    model = GPTForCausalLM(cfg)
    model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, ids):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            return m.fused_head_loss(ids, block_size=2048)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    t0 = time.perf_counter()
    l0 = float(step(ids).numpy())
    log(f"[bench] gpt-1.3b compile+step0 {time.perf_counter()-t0:.1f}s "
        f"loss {l0:.3f}")
    for _ in range(2):
        step(ids)
    float(step(ids).numpy())
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step(ids)
    float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    flops = gpt_flops_per_step(cfg, batch, seq)
    mfu = flops / dt / _peak_bf16_flops()
    tps = batch * seq / dt
    log(f"[bench] gpt-1.3b: {dt*1e3:.1f} ms/step, {tps:,.0f} tok/s, "
        f"mfu {mfu:.3f}")
    return {"model": "gpt-1.3b-single-chip", "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(tps), "mfu": round(mfu, 4)}


def bench_gpt1p3b_pp():
    """GPT-1.3B through the HYBRID pipeline path (pipeline_1f1b with
    Megatron mp inside stages + vocab-parallel head — the reference's
    headline TP+PP+DP call stack). On one chip the (dp, pp, mp) mesh is
    degenerate and the same code runs serially with per-layer remat; on
    an n-chip slice set BENCH_PP/BENCH_MP/BENCH_DP — zero new code.
    Manual arm like gpt1p3b (heavy first compile)."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import amp
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.text.models.gpt import gpt_1p3b
    from paddle_tpu.text.models.gpt_pipeline import PipelinedGPTForCausalLM

    n = len(jax.devices())
    pp = int(os.environ.get("BENCH_PP", 2 if n % 2 == 0 and n > 1 else 1))
    mp = int(os.environ.get("BENCH_MP", 2 if n % (2 * pp) == 0 else 1))
    dp = int(os.environ.get("BENCH_DP", n // (pp * mp)))
    vp = int(os.environ.get("BENCH_VP", 1))  # interleaved virtual stages
    ep = int(os.environ.get("BENCH_EP", 1))  # MoE expert parallelism
    moe = int(os.environ.get("BENCH_MOE_EXPERTS", 0))
    mesh_mod.init_mesh(dp=dp, pp=pp, mp=mp, ep=ep)
    log(f"[bench] gpt-1.3b-pp mesh dp={dp} pp={pp} mp={mp} ep={ep} "
        f"V={vp} moe={moe}")

    paddle.seed(0)
    smoke = os.environ.get("BENCH_PP_SMOKE", "0") == "1"
    if smoke:   # tiny-config machinery check, NOT a benchmark
        from paddle_tpu.text.models.gpt import GPTConfig

        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=128)
        batch, seq, n_micro = 2 * max(dp, 1), 128, 2
    else:
        cfg = gpt_1p3b()
        batch, seq, n_micro = 2 * max(dp, 1), 2048, 2
    model = PipelinedGPTForCausalLM(cfg, n_micro=n_micro, remat="layer",
                                    n_virtual=vp, moe_experts=moe)
    model = amp.decorate(model, level="O2", dtype="bfloat16")
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda m, i: m.loss(i), opt)
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    t0 = time.perf_counter()
    l0 = float(step(ids).numpy())
    log(f"[bench] gpt-1.3b-pp compile+step0 {time.perf_counter()-t0:.1f}s "
        f"loss {l0:.3f}")
    for _ in range(2):
        step(ids)
    float(step(ids).numpy())
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        last = step(ids)
    float(last.numpy())
    dt = (time.perf_counter() - t0) / iters
    flops = gpt_flops_per_step(cfg, batch, seq)
    mfu = flops / dt / (_peak_bf16_flops() * n)
    tps = batch * seq / dt
    log(f"[bench] gpt-1.3b-pp: {dt*1e3:.1f} ms/step, {tps:,.0f} tok/s, "
        f"mfu {mfu:.3f} (of {n}-chip peak)")
    return {"model": ("gpt-tiny-hybrid-pipeline-SMOKE" if smoke
                      else "gpt-1.3b-hybrid-pipeline"),
            "mesh": {"dp": dp, "pp": pp, "mp": mp, "ep": ep,
                     "n_virtual": vp, "moe_experts": moe},
            "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(tps), "mfu": round(mfu, 4)}


def bench_generate():
    """GPT-small KV-cache greedy decode throughput (serving-side metric;
    static cache + one compiled step per token — text/models/gpt.py)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM, gpt_small

    paddle.seed(0)
    cfg = gpt_small()
    model = GPTForCausalLM(cfg)
    batch, prompt, gen = 8, 128, 128
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, prompt)).astype(np.int32))

    t0 = time.perf_counter()
    # compile prompt+decode steps; sync so leftover device work can't
    # bleed into the timed window (the decode loop is fully
    # async-dispatchable — tokens never reach the host)
    model.generate(ids, max_new_tokens=8).numpy()
    log(f"[bench] generate compile {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=gen)
    out.numpy()  # block: dt must cover execution, not dispatch
    dt = time.perf_counter() - t0
    n_new = int(out.shape[1]) - prompt
    tps = batch * n_new / dt
    log(f"[bench] generate: {dt:.2f}s for {batch}x{n_new} new tokens, "
        f"{tps:,.0f} tok/s, {dt / n_new * 1e3:.2f} ms/token-step")
    return {"model": "gpt-small-decode", "tokens_per_sec": round(tps),
            "ms_per_token_step": round(dt / n_new * 1e3, 2),
            "batch": batch}


def bench_serving():
    """Dynamic-batching inference server requests/s (the serving-side
    metric for the analysis_predictor/serving analog): concurrent
    clients submit single ResNet-ish MLP requests; the server buckets,
    pads, and runs one compiled program per bucket."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference, nn

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(256, 1024), nn.ReLU(),
                          nn.Linear(1024, 1024), nn.ReLU(),
                          nn.Linear(1024, 64))
    model.eval()
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((512, 256)).astype(np.float32)
    server = inference.InferenceServer(
        model, inference.BatchingConfig(max_batch_size=64,
                                        max_delay_ms=2.0))
    n_clients, per_client = 8, 64

    def client(k, out):
        futs = [server.submit(xs[(k * per_client + i) % 512])
                for i in range(per_client)]
        out.extend(f.result(timeout=120) for f in futs)

    with server:
        server.infer(xs[0])  # warm bucket 1; others compile on first hit
        t0 = time.perf_counter()
        threads, sink = [], []
        for k in range(n_clients):
            out = []
            sink.append(out)
            threads.append(threading.Thread(target=client, args=(k, out)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
    total = n_clients * per_client
    rps = total / dt
    log(f"[bench] serving: {total} requests in {dt:.2f}s = {rps:,.0f} "
        f"req/s, mean batch {server.mean_batch_size:.1f}")
    return {"model": "mlp-serving", "requests_per_sec": round(rps),
            "mean_batch_size": round(server.mean_batch_size, 1)}


def _quiet_trace():
    """Trace for WARM-UP submits: stamps but emits nothing, so the
    compile stall inside a warm request's prefill segment never enters
    the pt_request_phase_seconds distribution or the recent-requests
    view the phase-breakdown stamps read (observability.reqtrace)."""
    from paddle_tpu.observability import reqtrace

    return reqtrace.quiet_trace()


def bench_llm_serve():
    """Continuous-batching LLM engine vs the static-batch generate()
    baseline under ONE Poisson workload with mixed prompt AND mixed
    generation lengths (the ISSUE-2 acceptance A/B). Both sides serve
    the same arrival schedule on the same model/backend:

      * static: the pre-engine serving shape — batches of 8, launched
        only when full (head-of-line), prompts LEFT-padded to the 256
        bucket, one generate() call per batch decoding until the
        LONGEST request in the batch finishes (rows are trimmed to
        their own budget afterwards — the in-batch head-of-line waste).
      * engine: inference.LLMServer — paged KV, chunked prefill into
        the running batch, per-request eviction the step a sequence
        meets its own budget.

    The engine side runs TWICE per rep — decode_k = BENCH_DECODE_K
    (default 8, the fused multi-token window) and decode_k = 1 (the
    single-tick host loop) — interleaved on the same Poisson schedule,
    each side scored best-of-2: the fused-decode acceptance A/B
    (ISSUE 8, docs/PERF_NOTES.md "Fused decode").

    Reports tok/s (requested generated tokens / wall), p50/p99 request
    latency (completion − arrival), mean live-slot occupancy, the
    speedups (fused vs k=1, fused vs static), and whether greedy
    outputs matched token-for-token across all three servers."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_small

    paddle.seed(0)
    fused_k = int(os.environ.get("BENCH_DECODE_K", "8"))
    cfg, name = gpt_small(), "gpt-small-llm-serve"
    n_req, bucket, B = 32, 256, 8
    len_lo, gen_lo, slots, budget, rate = 16, 8, 16, 48, 0.03
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    lens = rng.integers(len_lo, bucket + 1, n_req)
    gens = rng.integers(gen_lo, 65, n_req)   # mixed per-request budgets
    max_gen = 64
    prompts = [rng.integers(0, cfg.vocab_size, (int(L),)).astype(np.int32)
               for L in lens]
    arrive = np.cumsum(rng.exponential(rate, n_req))  # Poisson arrivals

    def pctl(lat, p):
        return float(np.percentile(np.asarray(lat), p))

    def run_static():
        # warm the prompt + padded decode executables outside the timed
        # window (the engine warms its one executable the same way)
        wids = np.zeros((B, bucket), np.int32)
        wmask = np.ones((B, bucket), np.int32)
        wmask[:, 0] = 0  # left-pad present → the padded decode variant
        model.generate(paddle.to_tensor(wids), max_new_tokens=2,
                       attention_mask=paddle.to_tensor(wmask))
        outs, lat = {}, {}
        t0 = time.perf_counter()
        qi = 0
        while qi < n_req:
            idxs = list(range(qi, min(qi + B, n_req)))
            qi += len(idxs)
            # the batch can't launch before its LAST member arrives
            wait = arrive[idxs[-1]] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            ids = np.zeros((B, bucket), np.int32)
            mask = np.zeros((B, bucket), np.int32)
            for r, j in enumerate(idxs):
                L = len(prompts[j])
                ids[r, bucket - L:] = prompts[j]
                mask[r, bucket - L:] = 1
            for r in range(len(idxs), B):  # pad rows: repeat row 0
                ids[r], mask[r] = ids[0], mask[0]
            # the whole batch decodes until its LONGEST request is done
            # (the in-batch head-of-line cost; the 128-bucketed cache
            # keeps every batch on one compiled step regardless)
            bmax = max(int(gens[j]) for j in idxs)
            out = model.generate(
                paddle.to_tensor(ids), max_new_tokens=bmax,
                attention_mask=paddle.to_tensor(mask)).numpy()
            tdone = time.perf_counter() - t0
            for r, j in enumerate(idxs):
                L = len(prompts[j])
                # strip left pads; trim to the request's own budget
                outs[j] = out[r, bucket - L:bucket + int(gens[j])]
                lat[j] = tdone - arrive[j]
        total = time.perf_counter() - t0
        return outs, lat, total

    # counter fields in LLMServer.metrics() are PROCESS-cumulative
    # (warmup + every rep share the registry) — report per-rep deltas
    # so "metrics of the best run" means that run
    _COUNTER_KEYS = ("requests", "finished", "preemptions", "steps",
                     "aborts", "prefill_tokens", "decode_tokens",
                     "fused_steps", "dispatches")

    def run_engine(decode_k):
        ecfg = inference.LLMEngineConfig(
            num_slots=slots, page_size=16, token_budget=budget,
            max_model_len=bucket + max_gen, decode_k=decode_k)
        server = inference.LLMServer(model, ecfg)
        outs, lat = {}, [None] * n_req
        with server:
            # warm BOTH decode executables outside the timed window: a
            # multi-page prompt forces chunked-prefill single ticks
            # (the single-tick step) and a > k generation runs at least
            # one fused window. A 1-token warmup on a fused engine
            # never leaves the fused path, and the first mixed tick of
            # the measured run then eats the single-tick compile
            # (observed: one 1.2 s tick mid-window). Then drop the
            # warmup's low-occupancy steps from the stats the occupancy
            # metric averages over.
            server.submit(np.zeros((2 * budget,), np.int32),
                          max_new_tokens=max(2, decode_k + 1),
                          trace=_quiet_trace()).result(timeout=1800)
            server.engine.stats.update(
                {"steps": 0, "tokens_in": 0, "occupancy_sum": 0.0})
            m0 = server.metrics()
            t0 = time.perf_counter()
            futs = []
            for j in range(n_req):
                wait = arrive[j] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                f = server.submit(prompts[j],
                                  max_new_tokens=int(gens[j]))

                def _done(f, j=j):
                    lat[j] = time.perf_counter() - t0 - arrive[j]
                f.add_done_callback(_done)
                futs.append(f)
            for j, f in enumerate(futs):
                outs[j] = f.result(timeout=1800)
            total = time.perf_counter() - t0
            # result() can return BEFORE the done-callback has stamped
            # the latency (callbacks fire after waiters wake) — join so
            # the slowest sample is never dropped from the percentiles
            t_join = time.perf_counter()
            while (any(x is None for x in lat)
                   and time.perf_counter() - t_join < 5):
                time.sleep(0.001)
            # registry-sourced engine metrics (LLMServer.metrics), read
            # while the server is still up; counters as THIS-rep deltas
            # (histogram-derived percentiles stay process-cumulative)
            em = server.metrics()
            for k in _COUNTER_KEYS:
                em[k] -= m0[k]
        occ = server.engine.mean_occupancy
        return outs, lat, total, occ, em

    # every phase runs SEQUENTIALLY, so drifting background load on a
    # shared host would skew a single A/B either way (observed ±30%
    # machine-wide swings between runs). Interleave F/E/S, F/E/S
    # (fused engine / k=1 engine / static) and score each side by its
    # best run — noise only ever slows a run down.
    f_runs, e_runs, s_runs = [], [], []
    for rep in range(2):
        f_out, f_lat, f_total, f_occ, fm = run_engine(fused_k)
        log(f"[bench] llm_serve fused-k{fused_k}[{rep}]: "
            f"{f_total:.2f}s, occ {f_occ:.2f}, "
            f"fused_steps {fm['fused_steps']}")
        f_runs.append((f_total, f_out, f_lat, f_occ, fm))
        e_out, e_lat, e_total, occ, em = run_engine(1)
        log(f"[bench] llm_serve k1[{rep}]: {e_total:.2f}s, "
            f"occ {occ:.2f}")
        e_runs.append((e_total, e_out, e_lat, occ, em))
        s_out, s_lat, s_total = run_static()
        log(f"[bench] llm_serve static[{rep}]: {s_total:.2f}s")
        s_runs.append((s_total, s_out, s_lat))
    f_total, f_out, f_lat, f_occ, fm = min(f_runs, key=lambda r: r[0])
    e_total, e_out, e_lat, occ, em = min(e_runs, key=lambda r: r[0])
    s_total, s_out, s_lat = min(s_runs, key=lambda r: r[0])
    gen_tokens = sum(len(f_out[j]) - len(prompts[j]) for j in range(n_req))
    # greedy identity across ALL THREE servers: fused == k1 == static
    match = all(np.array_equal(f_out[j], s_out[j])
                and np.array_equal(f_out[j], e_out[j])
                for j in range(n_req))
    f_tps = gen_tokens / f_total
    e_tps, s_tps = gen_tokens / e_total, gen_tokens / s_total
    speedup = f_tps / s_tps if s_tps else 0.0
    speedup_k1 = f_tps / e_tps if e_tps else 0.0
    log(f"[bench] llm_serve: fused-k{fused_k} {f_tps:,.0f} tok/s vs "
        f"k1 {e_tps:,.0f} = {speedup_k1:.2f}x, vs static "
        f"{s_tps:,.0f} = {speedup:.2f}x, greedy_match={match}")
    f_lat = [x for x in f_lat if x is not None]
    e_lat = [x for x in e_lat if x is not None]

    def _eng_block(total, lat, occ_v, m, runs):
        return {"tokens_per_sec": round(gen_tokens / total),
                "p50_latency_ms": round(pctl(lat, 50) * 1e3, 1),
                "p99_latency_ms": round(pctl(lat, 99) * 1e3, 1),
                "mean_slot_occupancy": round(occ_v, 3),
                "totals_s": [round(r[0], 2) for r in runs],
                # registry-sourced (LLMServer.metrics of the best run):
                # occupancy/preemptions/token split/dispatch
                # amortization + latency percentiles with attribution.
                # recent_requests (per-request phase timelines) stays
                # out of the trend record — the per-phase percentiles
                # in request_phase_seconds carry the aggregate story
                "metrics": {k: (round(v, 4)
                                if isinstance(v, float) else v)
                            for k, v in m.items()
                            if k != "recent_requests"}}

    result = {
        "model": name,
        "requests": n_req, "gen_tokens": gen_tokens,
        "decode_k": fused_k,
        "greedy_match": bool(match),
        "speedup_vs_static": round(speedup, 3),
        "speedup_vs_k1": round(speedup_k1, 3),
        "engine": _eng_block(f_total, f_lat, f_occ, fm, f_runs),
        "engine_k1": _eng_block(e_total, e_lat, occ, em, e_runs),
        "static": {"tokens_per_sec": round(s_tps),
                   "p50_latency_ms": round(pctl(list(s_lat.values()), 50)
                                           * 1e3, 1),
                   "p99_latency_ms": round(pctl(list(s_lat.values()), 99)
                                           * 1e3, 1),
                   "totals_s": [round(r[0], 2) for r in s_runs]},
    }
    if os.environ.get("BENCH_SPEC", "1") != "0":
        result["spec"] = _bench_llm_serve_spec()
    return result


def _spec_draft_pair(cfg_kw, draft_layers, damp):
    """A draft-FAVORABLE (target, draft) pair without training: the
    target's deep layers get their residual output projections damped
    by `damp`, and the draft is the target's first `draft_layers`
    layers plus its embeddings/final-LN/tied head, copied
    weight-for-weight — an emulated distilled draft whose logits track
    the target's, so the stamped acceptance rate is a real measured
    quantity, not an artifact of comparing two unrelated random
    models (docs/PERF_NOTES.md "Speculative decoding")."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import GPTConfig

    paddle.seed(42)
    big = GPTForCausalLM(GPTConfig(**cfg_kw))
    big.eval()
    for layer in big.gpt.layers[draft_layers:]:
        for lin in (layer.proj, layer.fc2):
            lin.weight._value = lin.weight._value * damp
            if lin.bias is not None:
                lin.bias._value = lin.bias._value * damp
    dkw = dict(cfg_kw, num_layers=draft_layers)
    draft = GPTForCausalLM(GPTConfig(**dkw))
    draft.eval()
    bsd = big.state_dict()
    for k, p in draft.state_dict().items():
        p._value = bsd[k]._value
    return big, draft


def _bench_llm_serve_spec():
    """The spec-decode arm of llm_serve (the ISSUE-10 acceptance A/B):
    a DRAFT-FAVORABLE workload — emulated-distilled draft (deep-layer
    damping, `_spec_draft_pair`) over repetitive motif-structured
    prompts — served three ways on one Poisson schedule:

      * spec: draft proposes BENCH_SPEC_K tokens/slot, the big model
        verifies all k+1 positions per slot in ONE ragged dispatch
      * fused: the PR-8 fused-k engine (k = BENCH_SPEC_K ticks of the
        big model per dispatch) — the bar the acceptance criterion
        names (spec >= 1.5x its tok/s)
      * k1: the single-tick engine

    Interleaved S/F/E x2, each side best-of-2 (same drifting-host
    defense as the main arm); greedy identity asserted across ALL
    arms (lossless acceptance makes it exact, whatever the acceptance
    rate); stamps the measured acceptance rate + draft seconds."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference

    spec_k = int(os.environ.get("BENCH_SPEC_K", "12"))
    cfg_kw = dict(vocab_size=8192, hidden_size=256, num_layers=12,
                  num_heads=8, max_seq_len=512)
    n_req, slots, budget, rate = 16, 8, 24, 0.02
    draft_layers, damp = 1, 0.01
    big, draft = _spec_draft_pair(cfg_kw, draft_layers, damp)
    rng = np.random.default_rng(7)
    # repetitive motif prompts: short alphabet, tiled motifs — the
    # draft-favorable content story to go with the distilled draft
    motif = rng.integers(0, 64, (8,))
    prompts = []
    for j in range(n_req):
        reps = int(rng.integers(2, 5))
        tail = rng.integers(0, 64, (int(rng.integers(2, 8)),))
        prompts.append(np.concatenate([np.tile(motif, reps), tail])
                       .astype(np.int32))
    gens = rng.integers(32, 57, n_req)
    arrive = np.cumsum(rng.exponential(rate, n_req))
    max_len = max(len(p) for p in prompts) + 64

    def run(engine_cfg):
        server = inference.LLMServer(big, engine_cfg)
        outs, lat = {}, [None] * n_req
        with server:
            server.submit(np.zeros((2 * budget,), np.int32),
                          max_new_tokens=max(2, spec_k + 2),
                          trace=_quiet_trace()).result(timeout=1800)
            server.engine.stats.update(
                {"steps": 0, "tokens_in": 0, "occupancy_sum": 0.0})
            # per-RUN acceptance: the registry counters are
            # process-cumulative (warmup + every rep pollute them), so
            # the stamped rate comes from engine-stats deltas
            st = server.engine.stats
            p0 = st.get("spec_proposed", 0)
            a0 = st.get("spec_accepted", 0)
            t0 = time.perf_counter()
            futs = []
            for j in range(n_req):
                wait = arrive[j] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                futs.append(server.submit(prompts[j],
                                          max_new_tokens=int(gens[j])))
            for j, f in enumerate(futs):
                outs[j] = f.result(timeout=1800)
            total = time.perf_counter() - t0
            em = server.metrics()
            dp = st.get("spec_proposed", 0) - p0
            em["run_acceptance_rate"] = (
                (st.get("spec_accepted", 0) - a0) / dp if dp else None)
        return outs, total, em

    def cfgs(kind):
        base = dict(num_slots=slots, page_size=16, token_budget=budget,
                    max_model_len=max_len)
        if kind == "spec":
            return inference.LLMEngineConfig(
                draft_model=draft, spec_k=spec_k, **base)
        if kind == "fused":
            return inference.LLMEngineConfig(decode_k=spec_k, **base)
        return inference.LLMEngineConfig(decode_k=1, **base)

    runs = {"spec": [], "fused": [], "k1": []}
    for rep in range(2):
        for kind in ("spec", "fused", "k1"):
            o, t, m = run(cfgs(kind))
            log(f"[bench] llm_serve spec-arm {kind}[{rep}]: {t:.2f}s")
            runs[kind].append((t, o, m))
    best = {k: min(v, key=lambda r: r[0]) for k, v in runs.items()}
    gen_tokens = sum(len(best["spec"][1][j]) - len(prompts[j])
                     for j in range(n_req))
    match = all(
        np.array_equal(best["spec"][1][j], best["k1"][1][j])
        and np.array_equal(best["fused"][1][j], best["k1"][1][j])
        for j in range(n_req))
    tps = {k: gen_tokens / v[0] for k, v in best.items()}
    sm = best["spec"][2]["spec"] or {}
    acc = best["spec"][2].get("run_acceptance_rate")
    log(f"[bench] llm_serve spec-arm: spec {tps['spec']:,.0f} tok/s vs "
        f"fused-k{spec_k} {tps['fused']:,.0f} = "
        f"{tps['spec'] / tps['fused']:.2f}x, vs k1 {tps['k1']:,.0f} = "
        f"{tps['spec'] / tps['k1']:.2f}x, acceptance="
        f"{acc if acc is None else round(acc, 3)}, "
        f"greedy_match={match}")
    # lossless is the CONTRACT, not a stamp: a verify regression must
    # fail the bench loudly, not ship a false-speedup JSON
    assert match, "spec-arm greedy outputs diverged across engines"
    return {
        "spec_k": spec_k,
        "model_layers": cfg_kw["num_layers"],
        "draft_layers": draft_layers, "damp": damp,
        "requests": n_req, "gen_tokens": gen_tokens,
        "greedy_match": bool(match),
        "acceptance_rate": (None if acc is None else round(acc, 4)),
        "acceptance_rate_cumulative": sm.get("acceptance_rate"),
        "draft_seconds": sm.get("draft_seconds"),
        "speedup_vs_fused": round(tps["spec"] / tps["fused"], 3),
        "speedup_vs_k1": round(tps["spec"] / tps["k1"], 3),
        "tokens_per_sec": {k: round(v) for k, v in tps.items()},
        "totals_s": {k: [round(r[0], 2) for r in v]
                     for k, v in runs.items()},
    }


def bench_llm_serve_int8():
    """Quantized-runtime serving A/B (the ISSUE-4 acceptance arm): the
    SAME Poisson workload as llm_serve, served twice by the
    continuous-batching engine — fp32 KV pool vs int8 KV pool
    (PT_KV_DTYPE machinery; per-row scale planes, dequant-on-gather).
    Identical pool GEOMETRY both sides, so the int8 arm reports the
    page-pool byte shrink directly (~3.8× vs fp32, ~1.9× vs the bf16
    pool a TPU deployment would otherwise run) plus tok/s vs fp32,
    achieved concurrency, and the greedy token match rate.

    BENCH_INT8_WEIGHTS=1 additionally swaps the decoder Linears for
    int8 weight-only matmuls (quantize_model_int8). Off by default:
    the int8 weight path is an MXU-native feature that has not been
    measured on the chip yet (docs/QUANTIZATION.md).
    """
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.text.models import GPTForCausalLM, gpt_small

    paddle.seed(0)
    cfg = gpt_small()
    model = GPTForCausalLM(cfg)
    model.eval()
    int8_weights = os.environ.get("BENCH_INT8_WEIGHTS", "0") == "1"
    qmodel = model
    if int8_weights:
        from paddle_tpu.quantization import runtime as qrt

        paddle.seed(0)
        qmodel = GPTForCausalLM(cfg)
        qmodel.eval()
        qrt.quantize_model_int8(qmodel)
    rng = np.random.default_rng(0)
    n_req, bucket, max_gen = 32, 256, 64
    lens = rng.integers(16, bucket + 1, n_req)
    gens = rng.integers(8, 65, n_req)
    prompts = [rng.integers(0, cfg.vocab_size, (int(L),)).astype(np.int32)
               for L in lens]
    arrive = np.cumsum(rng.exponential(0.03, n_req))

    def pctl(lat, p):
        return float(np.percentile(np.asarray(lat), p))

    def run(kv_dtype, m):
        ecfg = inference.LLMEngineConfig(
            num_slots=16, page_size=16, token_budget=48,
            max_model_len=bucket + max_gen, kv_dtype=kv_dtype)
        server = inference.LLMServer(m, ecfg)
        outs, lat = {}, [None] * n_req
        with server:
            server.submit(np.zeros((1,), np.int32),
                          max_new_tokens=1,
                          trace=_quiet_trace()).result(timeout=1800)
            server.engine.stats.update(
                {"steps": 0, "tokens_in": 0, "occupancy_sum": 0.0})
            pool_bytes = server.engine.pool_bytes()
            t0 = time.perf_counter()
            futs = []
            for j in range(n_req):
                wait = arrive[j] - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                f = server.submit(prompts[j],
                                  max_new_tokens=int(gens[j]))

                def _done(f, j=j):
                    lat[j] = time.perf_counter() - t0 - arrive[j]
                f.add_done_callback(_done)
                futs.append(f)
            for j, f in enumerate(futs):
                outs[j] = f.result(timeout=1800)
            total = time.perf_counter() - t0
            t_join = time.perf_counter()
            while (any(x is None for x in lat)
                   and time.perf_counter() - t_join < 5):
                time.sleep(0.001)
        occ = server.engine.mean_occupancy
        return outs, [x for x in lat if x is not None], total, occ, \
            pool_bytes

    # interleave int8/fp32 (and the ISSUE-12 int4-KV variant) ×2 and
    # score each side's best run — the same drifting-host-noise
    # defense as llm_serve. BENCH_INT4_KV=0 skips the third arm.
    int4_kv = os.environ.get("BENCH_INT4_KV", "1") != "0"
    q_runs, f_runs, i4_runs = [], [], []
    for rep in range(2):
        q = run("int8", qmodel)
        log(f"[bench] llm_serve_int8 int8[{rep}]: {q[2]:.2f}s, "
            f"occ {q[3]:.2f}, pool {q[4]/1e6:.1f} MB")
        q_runs.append(q)
        f = run("float32", model)
        log(f"[bench] llm_serve_int8 fp32[{rep}]: {f[2]:.2f}s, "
            f"occ {f[3]:.2f}, pool {f[4]/1e6:.1f} MB")
        f_runs.append(f)
        if int4_kv:
            i4 = run("int4", qmodel)
            log(f"[bench] llm_serve_int8 int4[{rep}]: {i4[2]:.2f}s, "
                f"occ {i4[3]:.2f}, pool {i4[4]/1e6:.1f} MB")
            i4_runs.append(i4)
    q_out, q_lat, q_total, q_occ, q_bytes = min(q_runs,
                                                key=lambda r: r[2])
    f_out, f_lat, f_total, f_occ, f_bytes = min(f_runs,
                                                key=lambda r: r[2])
    gen_tokens = sum(len(f_out[j]) - len(prompts[j])
                     for j in range(n_req))
    tok_match = tok_total = 0
    for j in range(n_req):
        a, b = f_out[j], q_out[j]
        pl = len(prompts[j])
        tok_total += len(a) - pl
        tok_match += int((np.asarray(a[pl:]) == np.asarray(
            b[pl:len(a)])).sum())
    match_rate = tok_match / max(tok_total, 1)
    q_tps, f_tps = gen_tokens / q_total, gen_tokens / f_total
    # the bf16 comparison point: what the pool would cost in the
    # compute dtype a TPU deployment serves in
    bf16_bytes = (inference.LLMEngineConfig.kv_bytes_per_page(
        cfg, 16, "bfloat16")
        * (q_bytes // inference.LLMEngineConfig.kv_bytes_per_page(
            cfg, 16, "int8")))
    log(f"[bench] llm_serve_int8: int8 {q_tps:,.0f} tok/s vs fp32 "
        f"{f_tps:,.0f} tok/s ({q_tps / f_tps:.2f}x), pool bytes "
        f"{q_bytes / f_bytes:.3f}x of fp32 / "
        f"{q_bytes / bf16_bytes:.3f}x of bf16, match {match_rate:.3f}")
    result = {
        "model": "gpt-small-llm-serve-int8",
        "int8_weights": int8_weights,
        "requests": n_req, "gen_tokens": gen_tokens,
        "greedy_match_rate": round(match_rate, 4),
        "tok_s": {"int8": round(q_tps), "fp32": round(f_tps)},
        "speedup_int8_vs_fp32": round(q_tps / f_tps, 3),
        "page_pool_bytes": {
            "int8": int(q_bytes), "fp32": int(f_bytes),
            "ratio_vs_fp32": round(q_bytes / f_bytes, 4),
            "ratio_vs_bf16": round(q_bytes / bf16_bytes, 4)},
        "achieved_concurrency": {
            "int8": round(q_occ * 16, 2), "fp32": round(f_occ * 16, 2)},
        "p99_latency_ms": {
            "int8": round(pctl(q_lat, 99) * 1e3, 1),
            "fp32": round(pctl(f_lat, 99) * 1e3, 1)},
        "totals_s": {"int8": [round(r[2], 2) for r in q_runs],
                     "fp32": [round(r[2], 2) for r in f_runs]},
    }
    if i4_runs:
        # the int4-KV variant (ISSUE-12): same workload, packed-nibble
        # pool — stamp the EQUAL-BYTES capacity (pages a fixed byte
        # budget admits, the serving-economics lever) next to the
        # greedy match vs the fp32 outputs
        i4_out, i4_lat, i4_total, i4_occ, i4_bytes = min(
            i4_runs, key=lambda r: r[2])
        i4_match = i4_tot = 0
        for j in range(n_req):
            a, b = f_out[j], i4_out[j]
            pl = len(prompts[j])
            i4_tot += len(a) - pl
            i4_match += int((np.asarray(a[pl:]) == np.asarray(
                b[pl:len(a)])).sum())
        per_page = {kv: inference.LLMEngineConfig.kv_bytes_per_page(
            cfg, 16, kv) for kv in ("float32", "int8", "int4")}
        result["int4_kv"] = {
            "greedy_match_rate": round(i4_match / max(i4_tot, 1), 4),
            "tok_s": round(gen_tokens / i4_total),
            "page_pool_bytes": int(i4_bytes),
            "pool_ratio_vs_int8": round(i4_bytes / q_bytes, 4),
            "pool_ratio_vs_fp32": round(i4_bytes / f_bytes, 4),
            "equal_bytes_capacity": {
                "pages_per_mb": {k: round(1e6 / v, 2)
                                 for k, v in per_page.items()},
                "vs_int8": round(per_page["int8"] / per_page["int4"], 3),
                "vs_fp32": round(per_page["float32"] / per_page["int4"],
                                 3)},
            "p99_latency_ms": round(pctl(i4_lat, 99) * 1e3, 1),
            "totals_s": [round(r[2], 2) for r in i4_runs],
        }
        log(f"[bench] llm_serve_int8 int4_kv: match "
            f"{result['int4_kv']['greedy_match_rate']}, equal-bytes "
            f"capacity {result['int4_kv']['equal_bytes_capacity']['vs_int8']}x "
            f"int8 / {result['int4_kv']['equal_bytes_capacity']['vs_fp32']}x "
            f"fp32")
    return result


def bench_llm_fleet():
    """Fleet serving A/B (ISSUE-7 acceptance): a shared-system-prompt
    Poisson workload served twice by the SAME model/backend —

      * fifo:  prefix cache OFF, default scheduler (the pre-fleet
        engine: every request re-prefills the full system prompt);
      * fleet: prefix cache ON + multi-tenant traffic through the SLA
        scheduler (the shared prefix maps copy-on-write from the radix
        trie, so its prefill is paid once).

    Reports the prefill-token reduction (the acceptance floor is 30%),
    p50/p99 TTFT per side, greedy token parity fifo-vs-fleet, and the
    prefix-cache / scheduler snapshots of the fleet run. Prefill token
    counts are deterministic; TTFT is timing, so the phases interleave
    F/S/F/S and each side scores its best run (the llm_serve noise
    defense). Both sides decode through the fused k-step executable
    (BENCH_DECODE_K, default 8) — the arm doubles as the ISSUE-8 proof
    that boundary-granularity scheduling keeps fleet parity."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_small

    paddle.seed(0)
    cfg, n_req, sys_len, max_suffix = gpt_small(), 24, 192, 48
    name = "gpt-small-llm-fleet"
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(0, cfg.vocab_size, (sys_len,)).astype(
        np.int32)
    prompts = [np.concatenate([sys_prompt, rng.integers(
        0, cfg.vocab_size, (int(L),)).astype(np.int32)])
        for L in rng.integers(8, max_suffix + 1, n_req)]
    gens = rng.integers(8, 33, n_req)
    arrive = np.cumsum(rng.exponential(0.02, n_req))
    # multi-tenant traffic: 3 tenants, one of them interactive-class —
    # greedy outputs are schedule-independent (each continuation depends
    # only on its own prompt), so parity vs the FIFO run still holds
    tenants = [f"tenant{j % 3}" for j in range(n_req)]
    prios = [inference.Priority.INTERACTIVE if j % 3 == 0
             else inference.Priority.STANDARD for j in range(n_req)]

    def pctl(lat, p):
        return float(np.percentile(np.asarray(lat), p))

    # the fleet arm runs with the fused multi-token decode ON (both
    # sides) to prove scheduler parity at window-boundary granularity:
    # admission/preemption/SLO escalation now only happen once per k
    # tokens, and greedy outputs must STILL match the FIFO engine
    # token-for-token (docs/SERVING.md "Fused decode")
    fused_k = int(os.environ.get("BENCH_DECODE_K", "8"))

    def run(fleet):
        eng = inference.LLMEngine(model, inference.LLMEngineConfig(
            num_slots=8, page_size=16, token_budget=48,
            max_model_len=sys_len + max_suffix + 40,
            prefix_cache=fleet, decode_k=fused_k))
        # warm BOTH decode executables outside the timed window (the
        # llm_serve warmup note: a 1-token prompt never leaves the
        # fused path, leaving the single-tick compile inside the
        # measured window)
        eng.add_request(np.zeros((8,), np.int32),
                        max_new_tokens=fused_k + 1)
        while eng.has_work():
            eng.step()
        eng.stats.update({"steps": 0, "tokens_in": 0, "generated": 0,
                          "occupancy_sum": 0.0, "fused_steps": 0})
        reqs, nxt = [None] * n_req, 0
        t0 = time.perf_counter()
        while nxt < n_req or eng.has_work():
            now = time.perf_counter() - t0
            while nxt < n_req and arrive[nxt] <= now:
                kw = (dict(tenant=tenants[nxt], priority=prios[nxt])
                      if fleet else {})
                reqs[nxt] = eng.add_request(
                    prompts[nxt], max_new_tokens=int(gens[nxt]), **kw)
                nxt += 1
            if eng.has_work():
                eng.step()
            elif nxt < n_req:
                time.sleep(min(0.002, arrive[nxt] - now))
        total = time.perf_counter() - t0
        outs = [r.future.result(timeout=0) for r in reqs]
        ttft = [r.t_first_token - r.t_submit for r in reqs]
        prefill = eng.stats["tokens_in"] - eng.stats["generated"]
        snap = (eng.prefix_cache.snapshot() if eng.prefix_cache
                else None)
        sched = eng.sched.snapshot()
        fused_steps = eng.stats["fused_steps"]
        eng.close()   # retract the trie's resident-pages gauge delta
        return outs, ttft, total, prefill, snap, sched, fused_steps

    f_runs, s_runs = [], []
    for rep in range(2):
        f_runs.append(run(fleet=True))
        log(f"[bench] llm_fleet fleet[{rep}]: {f_runs[-1][2]:.2f}s, "
            f"prefill {f_runs[-1][3]} tok")
        s_runs.append(run(fleet=False))
        log(f"[bench] llm_fleet fifo[{rep}]: {s_runs[-1][2]:.2f}s, "
            f"prefill {s_runs[-1][3]} tok")
    f_out, f_ttft, f_total, f_prefill, f_snap, f_sched, f_fused = min(
        f_runs, key=lambda r: r[2])
    s_out, s_ttft, s_total, s_prefill, _, _, s_fused = min(
        s_runs, key=lambda r: r[2])
    match = all(np.array_equal(a, b) for a, b in zip(f_out, s_out))
    saved_frac = 1.0 - f_prefill / s_prefill
    gen_tokens = sum(len(f_out[j]) - len(prompts[j])
                     for j in range(n_req))
    log(f"[bench] llm_fleet: prefill {s_prefill} -> {f_prefill} tok "
        f"(-{saved_frac:.1%}), ttft p50 {pctl(s_ttft, 50)*1e3:.0f} -> "
        f"{pctl(f_ttft, 50)*1e3:.0f} ms, p99 {pctl(s_ttft, 99)*1e3:.0f}"
        f" -> {pctl(f_ttft, 99)*1e3:.0f} ms, greedy_match={match}")
    return {
        "model": name,
        "requests": n_req, "gen_tokens": gen_tokens,
        "sys_prompt_tokens": sys_len,
        "decode_k": fused_k,
        "fused_steps": {"fleet": int(f_fused), "fifo": int(s_fused)},
        "greedy_match": bool(match),
        "prefill_tokens": {"fifo": int(s_prefill),
                           "fleet": int(f_prefill),
                           "saved_frac": round(saved_frac, 4)},
        "ttft_ms": {
            "fifo": {"p50": round(pctl(s_ttft, 50) * 1e3, 1),
                     "p99": round(pctl(s_ttft, 99) * 1e3, 1)},
            "fleet": {"p50": round(pctl(f_ttft, 50) * 1e3, 1),
                      "p99": round(pctl(f_ttft, 99) * 1e3, 1)}},
        "tok_s": {"fifo": round(gen_tokens / s_total),
                  "fleet": round(gen_tokens / f_total)},
        "prefix_cache": f_snap,
        "sched": f_sched,
        "totals_s": {"fleet": [round(r[2], 2) for r in f_runs],
                     "fifo": [round(r[2], 2) for r in s_runs]},
    }


def bench_llm_fleet_multi():
    """Multi-replica fleet A/B (ISSUE-13 acceptance): the SAME shared-
    prefix Poisson workload served by ONE engine (threaded LLMServer,
    fused decode, prefix cache) and by a 2-replica FleetRouter
    (radix-affinity routing, each replica its own forked model +
    pools). Headline: aggregate tok/s ratio (the capacity-doubling
    claim — the single engine is slot-saturated by the arrival rate,
    the fleet has 2x slots), plus router TTFT p50/p99, affinity hit
    rate and per-replica occupancy. Phases interleave M/S/M/S and each
    side scores its best run (the llm_serve noise defense); greedy
    outputs must be token-identical across ALL sides.

    Two guarded extra scenarios (a stamp failure can't kill the
    headline): a seeded replica-kill mid-stream (failover requeue,
    outputs still token-identical) and a long-prompt PREFILL STORM
    A/B — short interactive TTFT p99 with the storm prefilling on a
    dedicated prefill replica (KV pages streamed to the decode
    replica) vs mixed into the single engine."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.distributed import chaos
    from paddle_tpu.inference.fleet_serving import (AutoscalePolicy,
                                                    FleetRouter,
                                                    LocalReplica,
                                                    fork_model)
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_small

    paddle.seed(0)
    cfg, n_req, sys_len, max_suffix = gpt_small(), 96, 96, 32
    name = "gpt-small-llm-fleet-multi"
    base = GPTForCausalLM(cfg)
    base.eval()
    rng = np.random.default_rng(0)
    # 4 tenant groups, each sharing a system prompt — the affinity
    # workload: the router should concentrate each group on one
    # replica (hit rate > 0.5 is the acceptance floor)
    sys_prompts = [rng.integers(0, cfg.vocab_size, (sys_len,)).astype(
        np.int32) for _ in range(4)]
    prompts = [np.concatenate([sys_prompts[j % 4], rng.integers(
        0, cfg.vocab_size, (int(L),)).astype(np.int32)])
        for j, L in enumerate(rng.integers(4, max_suffix + 1, n_req))]
    gens = rng.integers(24, 49, n_req)
    # arrival rate chosen to SATURATE one 4-slot engine (queue builds),
    # so the fleet's extra slots are the binding resource under test
    arrive = np.cumsum(rng.exponential(0.002, n_req))
    fused_k = int(os.environ.get("BENCH_DECODE_K", "8"))
    ecfg_kw = dict(num_slots=4, page_size=16, token_budget=48,
                   max_model_len=sys_len + max_suffix + 40,
                   prefix_cache=True, decode_k=fused_k)

    def pctl(lat, p):
        vals = [v for v in lat if v is not None]
        return float(np.percentile(np.asarray(vals), p)) if vals else -1.0

    def drive(submit, arrivals=None, plist=None):
        """Poisson-feed `plist` (default: the main workload) through
        `submit(j, prompt) -> Future`; returns (outputs, client-TTFTs,
        makespan). ONE driver for every phase — single, fleet, and the
        storm A/B must pace and stamp identically or the comparison
        silently measures different things."""
        arrivals = arrive if arrivals is None else arrivals
        plist = prompts if plist is None else plist
        n = len(plist)
        futs, stamps, nxt = [None] * n, [None] * n, 0
        t0 = time.perf_counter()
        while nxt < n:
            now = time.perf_counter() - t0
            if arrivals[nxt] <= now:
                stamps[nxt] = time.perf_counter()
                futs[nxt] = submit(nxt, plist[nxt])
                nxt += 1
            else:
                time.sleep(min(0.002, arrivals[nxt] - now))
        outs = [f.result(timeout=600) for f in futs]
        total = time.perf_counter() - t0
        ttfts = []
        for f, s in zip(futs, stamps):
            req = getattr(f, "pt_request", None)
            t = getattr(req, "t_first_token", None)
            ttfts.append(None if t is None else t - s)
        return outs, ttfts, total

    def run_single():
        server = inference.LLMServer(
            fork_model(base), inference.LLMEngineConfig(**ecfg_kw))
        with server:
            # warm both executables outside the timed window
            server.submit(np.zeros((2,), np.int32),
                          max_new_tokens=fused_k + 1,
                          trace=_quiet_trace()).result(timeout=300)
            outs, ttfts, total = drive(
                lambda j, p: server.submit(
                    p, max_new_tokens=int(gens[j])))
            occ = server.engine.mean_occupancy
        return outs, ttfts, total, occ

    def make_replica(nm, role="serve"):
        return LocalReplica(fork_model(base), name=nm, role=role,
                            config=inference.LLMEngineConfig(**ecfg_kw))

    def run_multi(tag, chaos_kill=None):
        names = [f"{tag}0", f"{tag}1"]
        if chaos_kill is not None:
            chaos.install({"seed": 13, "injectors": [
                {"scope": f"replica.kill.{names[0]}", "kind": "error",
                 "at": [chaos_kill]}]})
        router = FleetRouter(
            replicas=[make_replica(nm) for nm in names],
            hash_block_tokens=16,
            policy=AutoscalePolicy(min_replicas=1, max_replicas=2,
                                   heartbeat_timeout_s=1.0,
                                   poll_s=0.01))
        try:
            with router:
                outs, _, total = drive(
                    lambda j, p: router.submit(
                        p, max_new_tokens=int(gens[j])))
                m = router.metrics()
        finally:
            if chaos_kill is not None:
                chaos.clear()
        return outs, total, m

    m_runs, s_runs = [], []
    for rep in range(2):
        m_runs.append(run_multi(f"m{rep}r"))
        log(f"[bench] llm_fleet_multi fleet[{rep}]: "
            f"{m_runs[-1][1]:.2f}s, affinity "
            f"{m_runs[-1][2]['affinity_hit_rate']:.2f}")
        s_runs.append(run_single())
        log(f"[bench] llm_fleet_multi single[{rep}]: "
            f"{s_runs[-1][2]:.2f}s")
    m_out, m_total, m_metrics = min(m_runs, key=lambda r: r[1])
    s_out, s_ttft, s_total, s_occ = min(s_runs, key=lambda r: r[2])
    match = all(np.array_equal(a, b) for a, b in zip(s_out, m_out))
    gen_tokens = sum(len(s_out[j]) - len(prompts[j])
                     for j in range(n_req))
    s_tps, m_tps = gen_tokens / s_total, gen_tokens / m_total
    log(f"[bench] llm_fleet_multi: fleet {m_tps:,.0f} tok/s vs single "
        f"{s_tps:,.0f} ({m_tps / s_tps:.2f}x), affinity "
        f"{m_metrics['affinity_hit_rate']:.2f}, greedy_match={match}")
    result = {
        "model": name, "requests": n_req, "gen_tokens": gen_tokens,
        "decode_k": fused_k, "replicas": 2,
        "greedy_match": bool(match),
        "tok_s": {"single": round(s_tps), "fleet": round(m_tps)},
        "speedup_fleet_vs_single": round(m_tps / s_tps, 3),
        "affinity_hit_rate": round(m_metrics["affinity_hit_rate"], 4),
        "router_ttft_ms": {
            "p50": round((m_metrics["ttft_p50_s"] or 0) * 1e3, 1),
            "p99": round((m_metrics["ttft_p99_s"] or 0) * 1e3, 1)},
        "single_ttft_ms": {
            "p50": round(pctl(s_ttft, 50) * 1e3, 1),
            "p99": round(pctl(s_ttft, 99) * 1e3, 1)},
        "per_replica_occupancy": {
            nm: round(v["mean_slot_occupancy"], 3)
            for nm, v in m_metrics["replicas"].items()},
        "single_occupancy": round(s_occ, 3),
        "totals_s": {"fleet": [round(r[1], 2) for r in m_runs],
                     "single": [round(r[2], 2) for r in s_runs]},
    }

    # guarded extra 0: TTFT phase decomposition of the winning fleet
    # run (observability.reqtrace): p50/p99 per phase over the router's
    # merged per-request timelines — the serving-economics attribution
    # (queue vs route vs prefill vs transfer vs decode) the ISSUE-15
    # tracing plane exists to price
    try:
        segs = {}
        for tl in m_metrics.get("recent_requests", []):
            for s in tl.get("phases", [])[1:]:   # [0] is the anchor
                segs.setdefault(s["phase"], []).append(s["dt_s"])
        result["ttft_phase_breakdown_ms"] = {
            ph: {"p50": round(float(np.percentile(v, 50)) * 1e3, 2),
                 "p99": round(float(np.percentile(v, 99)) * 1e3, 2),
                 "n": len(v)}
            for ph, v in sorted(segs.items())}
        log(f"[bench] llm_fleet_multi ttft phases: "
            + ", ".join(f"{ph} p50={d['p50']}ms"
                        for ph, d in
                        result['ttft_phase_breakdown_ms'].items()))
    except Exception as e:
        log(f"[bench] llm_fleet_multi phase stamp failed: {e!r}")
        result["ttft_phase_breakdown_ms"] = {"error": repr(e)}

    # guarded extra 1: seeded replica-kill recovery mid-stream
    try:
        k_out, k_total, k_metrics = run_multi("kill", chaos_kill=12)
        k_match = all(np.array_equal(a, b)
                      for a, b in zip(s_out, k_out))
        result["replica_kill_recovery"] = {
            "greedy_match": bool(k_match),
            "replicas_lost": k_metrics["replicas_lost"],
            "requeues": k_metrics["requeues"],
            "total_s": round(k_total, 2),
            "tok_s": round(gen_tokens / k_total),
        }
        log(f"[bench] llm_fleet_multi kill-recovery: match={k_match}, "
            f"requeues={k_metrics['requeues']}, {k_total:.2f}s")
    except Exception as e:
        log(f"[bench] llm_fleet_multi kill-recovery stamp failed: "
            f"{e!r}")
        result["replica_kill_recovery"] = {"error": repr(e)}

    # guarded extra 2: long-prompt prefill storm — disaggregated
    # prefill replica vs everything on one engine; the decode-side
    # interactive TTFT p99 is the measured win
    try:
        n_short, n_long = 12, 8
        long_len = ecfg_kw["max_model_len"] - 12
        shorts = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
                  for _ in range(n_short)]
        longs = [rng.integers(0, cfg.vocab_size,
                              (long_len,)).astype(np.int32)
                 for _ in range(n_long)]
        storm, kinds = [], []
        for i in range(max(n_short, n_long)):
            if i < n_long:
                storm.append(longs[i])
                kinds.append("long")
            if i < n_short:
                storm.append(shorts[i])
                kinds.append("short")
        s_arrive = np.cumsum(
            rng.exponential(0.004, len(storm)))

        def storm_gen(j):
            return 16 if kinds[j] == "short" else 8

        server = inference.LLMServer(
            fork_model(base), inference.LLMEngineConfig(**ecfg_kw))
        with server:
            server.submit(np.zeros((2,), np.int32),
                          max_new_tokens=fused_k + 1,
                          trace=_quiet_trace()).result(timeout=300)
            sp_out, sp_ttft, _ = drive(
                lambda j, p: server.submit(
                    p, max_new_tokens=storm_gen(j)),
                arrivals=s_arrive, plist=storm)
        router = FleetRouter(
            replicas=[make_replica("storm_d")],
            prefill_replicas=[make_replica("storm_p", role="prefill")],
            prefill_min_tokens=48,
            policy=AutoscalePolicy(min_replicas=1, max_replicas=1))
        with router:
            # router futures carry pt_request too (the FleetRouter
            # contract mirrors LLMServer.submit), so the same driver
            # paces and stamps both sides of the A/B
            dp_out, dp_ttft, _ = drive(
                lambda j, p: router.submit(
                    p, max_new_tokens=storm_gen(j)),
                arrivals=s_arrive, plist=storm)
            dm = router.metrics()
        storm_match = all(np.array_equal(a, b)
                          for a, b in zip(sp_out, dp_out))
        short_ttft_single = [t for t, k in zip(sp_ttft, kinds)
                             if k == "short"]
        short_ttft_disagg = [t for t, k in zip(dp_ttft, kinds)
                             if k == "short"]
        result["prefill_storm"] = {
            "greedy_match": bool(storm_match),
            "short_ttft_p99_ms": {
                "single": round(pctl(short_ttft_single, 99) * 1e3, 1),
                "disagg": round(pctl(short_ttft_disagg, 99) * 1e3, 1)},
            "short_ttft_p50_ms": {
                "single": round(pctl(short_ttft_single, 50) * 1e3, 1),
                "disagg": round(pctl(short_ttft_disagg, 50) * 1e3, 1)},
            "disagg_handoffs": dm["disagg_handoffs"],
        }
        log(f"[bench] llm_fleet_multi prefill-storm: short ttft p99 "
            f"{result['prefill_storm']['short_ttft_p99_ms']['single']}"
            f" -> "
            f"{result['prefill_storm']['short_ttft_p99_ms']['disagg']}"
            f" ms, match={storm_match}")
    except Exception as e:
        log(f"[bench] llm_fleet_multi prefill-storm stamp failed: "
            f"{e!r}")
        result["prefill_storm"] = {"error": repr(e)}
    return result


def bench_overload_storm_ab():
    """Overload-control-plane A/B (ISSUE-16 acceptance): the SAME
    seeded Poisson storm at ~2.5x fleet capacity, with one replica
    running SLOW under a seeded chaos delay, served twice — overload
    plane OFF (every arrival admitted, latency unbounded) and ON
    (per-request deadlines, brownout ladder, hedging). Headline:
    admitted-TTFT p99 on vs off — the plane must buy bounded latency
    for what it admits — plus the shed rate that bound costs and the
    per-level brownout dwell. Each side builds fresh forked replicas
    and warms outside the timed window; both sides replay the SAME
    arrival sleeps (cut from the off side's measured warm capacity),
    so the comparison never measures two different storms. Guarded
    stamps: an overload-introspection failure can't kill the
    headline."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.distributed import chaos
    from paddle_tpu.inference.fleet_serving import (AutoscalePolicy,
                                                    FleetRouter,
                                                    LocalReplica,
                                                    OverloadPolicy,
                                                    RequestCancelled,
                                                    RequestShed,
                                                    fork_model)
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_small

    paddle.seed(0)
    cfg, n_req, name = gpt_small(), 64, "gpt-small-overload-storm"
    base = GPTForCausalLM(cfg)
    base.eval()
    rng = np.random.default_rng(25)
    prompts = [rng.integers(0, cfg.vocab_size, (int(L),)).astype(
        np.int32) for L in rng.integers(8, 24, n_req)]
    gen = 12
    burst = 12      # opening burst deeper than the fleet's 8 slots
    ecfg_kw = dict(num_slots=4, page_size=16, token_budget=48,
                   max_model_len=128)

    def pctl(vals, p):
        vals = [v for v in vals if v is not None]
        return float(np.percentile(np.asarray(vals), p)) if vals else -1.0

    state = {"sleeps": None, "deadline_s": None}

    def run_side(tag, overload, with_deadlines):
        """One storm pass; returns (outcome lists, ttfts, totals,
        router introspection). The slow replica is `<tag>a` — the
        chaos scope is per-name, so each side gets its own injector
        against an identically-shaped plan."""
        chaos.install({"seed": 17, "injectors": [
            {"scope": f"replica.kill.{tag}a", "kind": "delay",
             "p": 0.35, "delay_s": 0.05}]})
        router = FleetRouter(
            replicas=[LocalReplica(
                fork_model(base), name=f"{tag}{s}",
                config=inference.LLMEngineConfig(**ecfg_kw))
                for s in ("a", "b")],
            policy=AutoscalePolicy(min_replicas=2, max_replicas=2,
                                   heartbeat_timeout_s=60.0,
                                   poll_s=0.02),
            overload=overload)
        try:
            with router:
                # unloaded warm-up: compile + TTFT baseline + capacity
                tw = time.monotonic()
                for p in prompts[:4]:
                    router.submit(p, max_new_tokens=gen).result(
                        timeout=600)
                warm_elapsed = max(time.monotonic() - tw, 1e-3)
                if state["sleeps"] is None:
                    rate = 4.0 / warm_elapsed
                    state["sleeps"] = [min(float(rng.exponential(
                        1.0 / (2.5 * rate))), 0.05)
                        for _ in range(n_req)]
                    state["deadline_s"] = max(
                        2.0 * router.ttft_quantile(0.99), 1.0)
                t_sub, t_done, futs = [], {}, []
                t0 = time.perf_counter()
                for i, p in enumerate(prompts):
                    if i >= burst:
                        time.sleep(state["sleeps"][i])
                    kw = ({"deadline_s": state["deadline_s"]}
                          if with_deadlines else {})
                    t_sub.append(time.perf_counter())
                    f = router.submit(p, max_new_tokens=gen, **kw)
                    f.add_done_callback(
                        lambda _f, i=i: t_done.setdefault(
                            i, time.perf_counter()))
                    futs.append(f)
                done, shed, cancelled, reasons = [], [], [], {}
                for i, f in enumerate(futs):
                    try:
                        f.result(timeout=600)
                        done.append(i)
                    except RequestShed as e:
                        shed.append(i)
                        reasons[e.reason] = reasons.get(e.reason, 0) + 1
                    except RequestCancelled as e:
                        cancelled.append(i)
                        reasons["cancelled:" + e.reason] = reasons.get(
                            "cancelled:" + e.reason, 0) + 1
                total = time.perf_counter() - t0
                ttfts = []
                for i in done:
                    req = getattr(futs[i], "pt_request", None)
                    t = getattr(req, "t_first_token", None)
                    ttfts.append(t - t_sub[i] if t is not None
                                 else t_done[i] - t_sub[i])
                # let the ladder drain back to L0 before teardown so
                # dwell() prices the WHOLE episode, recovery included
                if overload is not None:
                    cool = time.monotonic() + 20
                    while (router.stats.get("brownout_level", 0) != 0
                           and time.monotonic() < cool):
                        time.sleep(0.05)
                dwell = (list(router._brownout_ctl.dwell())
                         if overload is not None else None)
                ov = router.metrics() if overload is not None else None
        finally:
            chaos.clear()
        return done, shed, cancelled, reasons, ttfts, total, dwell, ov

    off = run_side("off", None, with_deadlines=False)
    log(f"[bench] overload_storm off: {len(off[0])} done in "
        f"{off[5]:.2f}s, ttft p99 {pctl(off[4], 99) * 1e3:.0f}ms")
    on = run_side("on", OverloadPolicy(
        brownout_high=0.5, brownout_low=0.1, brownout_step_ticks=2,
        brownout_recover_ticks=4, hedge_after_s=2.0, hedge_stale_s=1.0,
        max_parked=64), with_deadlines=True)
    o_done, o_shed, o_cancel, o_reasons, o_ttft, o_total, dwell, ov = on
    shed_rate = (len(o_shed) + len(o_cancel)) / float(n_req)
    log(f"[bench] overload_storm on: {len(o_done)} done, "
        f"{len(o_shed)} shed, {len(o_cancel)} cancelled "
        f"({shed_rate:.0%}), ttft p99 {pctl(o_ttft, 99) * 1e3:.0f}ms "
        f"in {o_total:.2f}s")
    result = {
        "model": name, "requests": n_req, "gen_tokens_each": gen,
        "storm_x_capacity": 2.5, "burst": burst,
        "deadline_s": round(state["deadline_s"], 3),
        "admitted_ttft_p99_ms": {"off": round(pctl(off[4], 99) * 1e3, 1),
                                 "on": round(pctl(o_ttft, 99) * 1e3, 1)},
        "admitted_ttft_p50_ms": {"off": round(pctl(off[4], 50) * 1e3, 1),
                                 "on": round(pctl(o_ttft, 50) * 1e3, 1)},
        "outcomes_on": {"done": len(o_done), "shed": len(o_shed),
                        "cancelled": len(o_cancel)},
        "shed_rate": round(shed_rate, 4),
        "shed_reasons": o_reasons,
        "totals_s": {"off": round(off[5], 2), "on": round(o_total, 2)},
    }
    # guarded: brownout dwell per level + control-plane introspection
    try:
        result["brownout_dwell_s"] = {
            f"L{lv}": round(d, 3) for lv, d in enumerate(dwell)}
        result["brownout_max_level"] = max(
            [0] + [lv for lv, d in enumerate(dwell) if d > 0])
        if ov is not None:
            result["breaker_state"] = ov["overload"]["breaker"]["state"]
            result["hedges"] = ov.get("hedges", 0)
        log(f"[bench] overload_storm dwell: "
            f"{result['brownout_dwell_s']}")
    except Exception as e:
        log(f"[bench] overload_storm dwell stamp failed: {e!r}")
        result["brownout_dwell_s"] = {"error": repr(e)}
    return result


def bench_train_3d():
    """3D-parallel (DP × TP × PP) train-step arm: per-config step time +
    mesh shape for the tier-1-size GPT over the hybrid3d subsystem. The
    point is the TREND of the hybrid step (schedule/placement changes
    show up here), stamped with each config's mesh so a regression
    arrives with its topology. Runs on the chips the host has (the
    configs are chosen from the device count)."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import hybrid3d, mesh as mesh_mod
    from paddle_tpu.text.models.gpt import GPTConfig

    ndev = len(jax.devices())
    model_cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                          num_heads=4, max_seq_len=64)
    configs = []
    if ndev >= 8:
        configs = [
            hybrid3d.Hybrid3DConfig(dp=2, tp=2, pp=2),
            hybrid3d.Hybrid3DConfig(dp=2, tp=2, pp=2, schedule="gpipe"),
            hybrid3d.Hybrid3DConfig(tp=4, pp=2),
            hybrid3d.Hybrid3DConfig(dp=2, tp=2, pp=2, zero="os"),
            # the ISSUE-12 quantized-collective arm: identical geometry
            # to config 0 so the A/B block below can stamp the dp-axis
            # byte shrink + final-loss delta vs the exact run
            hybrid3d.Hybrid3DConfig(dp=2, tp=2, pp=2,
                                    quant_allreduce=True),
        ]
    elif ndev >= 4:
        configs = [hybrid3d.Hybrid3DConfig(dp=2, pp=2),
                   hybrid3d.Hybrid3DConfig(tp=2, pp=2),
                   hybrid3d.Hybrid3DConfig(dp=2, pp=2,
                                           quant_allreduce=True)]
    else:
        configs = [hybrid3d.Hybrid3DConfig()]  # degenerate 1-device
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, model_cfg.vocab_size, (8, 32))
    out = {}
    for cfg3d in configs:
        mesh_mod.reset_mesh()
        hybrid3d.init_hybrid_mesh(
            cfg3d, devices=jax.devices()[:cfg3d.n_devices])
        paddle.seed(0)
        m = hybrid3d.build_gpt3d(model_cfg, cfg3d)
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = hybrid3d.HybridTrainStep(m, lambda mm, i: mm.loss(i), opt,
                                        config=cfg3d)
        ids = paddle.to_tensor(ids_np)
        t0 = time.perf_counter()
        l0 = float(step(ids).numpy())  # compile + step 0
        compile_s = time.perf_counter() - t0
        step(ids)  # warmup
        float(step(ids).numpy())
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            last = step(ids)
        lN = float(last.numpy())
        dt = (time.perf_counter() - t0) / iters
        stats = step.compile_stats(check_donation=True)
        # per-axis collective bytes off the live trace: the measured
        # baseline ROADMAP item 2's quantized all-reduce must beat
        # (the dp axis carries the gradient psums), plus the
        # jaxpr-level finding count (rank-conditioned collectives /
        # placement drift) — one call, same aggregation as the
        # `ptlint --spmd` gate (docs/ANALYSIS.md "SPMD passes").
        # Guarded like _ptlint_stamp: metadata must never kill the
        # measured headline timings.
        try:
            from paddle_tpu.analysis import spmd_report
            spmd = spmd_report(step, ids)
        except Exception as e:
            log(f"[bench] train_3d spmd stamp failed: {e!r}")
            spmd = {"per_axis_bytes": {}, "per_axis_counts": {},
                    "num_findings": -1, "error": repr(e)}
        # steptrace phase breakdown (ISSUE-18): p50/p99 per phase over
        # a short metrics-mode window. Separate from the timed loop so
        # the headline ms_per_step trend stays comparable with the
        # mode-off captures; guarded like the spmd stamp.
        try:
            from paddle_tpu import observability
            from paddle_tpu.observability import steptrace

            prev_mode = observability.set_mode("metrics")
            steptrace.reset()
            try:
                for _ in range(8):
                    step(ids)
                recs = steptrace.recent_steps()
            finally:
                observability.set_mode(prev_mode)
                steptrace.reset()
            phase_samples = {}
            for r in recs:
                for e in r["timeline"]:
                    if e["phase"] == "start":
                        continue
                    phase_samples.setdefault(e["phase"],
                                             []).append(e["dt_s"])
            breakdown = {
                p: {"p50_ms": round(
                        float(np.percentile(v, 50)) * 1e3, 3),
                    "p99_ms": round(
                        float(np.percentile(v, 99)) * 1e3, 3)}
                for p, v in sorted(phase_samples.items())}
        except Exception as e:
            log(f"[bench] train_3d phase breakdown failed: {e!r}")
            breakdown = {"error": repr(e)}
        out[cfg3d.tag()] = {
            **cfg3d.describe(),
            "compile_s": round(compile_s, 2),
            "ms_per_step": round(dt * 1e3, 2),
            "loss_first": round(l0, 4),
            "loss_last": round(lN, 4),
            "executables": stats["executables"],
            "donation_held": stats["donation"]["held"],
            "collective_bytes_per_axis": spmd["per_axis_bytes"],
            "collective_execs_per_axis": spmd["per_axis_counts"],
            "spmd_findings": spmd["num_findings"],
            "step_phase_breakdown_ms": breakdown,
        }
        log(f"[bench] train_3d {cfg3d.tag()}: {dt*1e3:.1f} ms/step, "
            f"donation_held={stats['donation']['held']}, "
            f"coll_bytes={spmd['per_axis_bytes']}, "
            f"spmd_findings={spmd['num_findings']}")
        mesh_mod.reset_mesh()
    # quant_allreduce A/B (ISSUE-12): pair each -q8 config with its
    # exact twin and stamp collective bytes before/after + the
    # final-loss delta — same model seed and batch both sides, so the
    # delta IS the quantization noise. Guarded like the spmd stamp:
    # a pairing miss must not kill the measured per-config records.
    try:
        quant_ab = {}
        for tag, rec in out.items():
            if not tag.endswith("-q8"):
                continue
            base = out.get(tag[:-len("-q8")])
            if base is None:
                continue
            b_dp = base["collective_bytes_per_axis"].get("dp", 0)
            q_dp = rec["collective_bytes_per_axis"].get("dp", 0)
            quant_ab[tag] = {
                "collective_bytes_per_axis": {
                    "exact": base["collective_bytes_per_axis"],
                    "quant": rec["collective_bytes_per_axis"]},
                "dp_bytes_ratio": round(b_dp / q_dp, 3) if q_dp else None,
                "final_loss": {"exact": base["loss_last"],
                               "quant": rec["loss_last"]},
                "final_loss_delta": round(
                    rec["loss_last"] - base["loss_last"], 5),
                "ms_per_step": {"exact": base["ms_per_step"],
                                "quant": rec["ms_per_step"]},
            }
            # collective-time attribution (ISSUE-18): join the per-axis
            # byte deltas of the quant on/off twins with their measured
            # step-time delta -> achieved bytes/s per mesh axis (None
            # where noise swamps the signal — honest, not invented)
            try:
                from paddle_tpu.observability.steptrace import (
                    collective_bytes_per_second)

                quant_ab[tag]["achieved_axis_bytes_per_s"] = \
                    collective_bytes_per_second(
                        rec["collective_bytes_per_axis"],
                        rec["ms_per_step"] / 1e3,
                        base["collective_bytes_per_axis"],
                        base["ms_per_step"] / 1e3)
            except Exception as e:
                quant_ab[tag]["achieved_axis_bytes_per_s"] = {
                    "error": repr(e)}
            log(f"[bench] train_3d quant_ab {tag}: dp bytes "
                f"{b_dp} -> {q_dp} "
                f"({quant_ab[tag]['dp_bytes_ratio']}x), loss delta "
                f"{quant_ab[tag]['final_loss_delta']}")
    except Exception as e:
        log(f"[bench] train_3d quant_ab stamp failed: {e!r}")
        quant_ab = {"error": repr(e)}
    # ckpt_overlap_ab (ISSUE-14): step-time p50/p99 with per-N-step
    # checkpointing, synchronous vs overlapped (async snapshot/commit)
    # saves, plus the measured step-path stall per save straight off
    # pt_ckpt_step_stall_seconds. The acceptance bar is overlapped
    # stall ≤ 20% of the synchronous stall at the same cadence.
    # Guarded like the spmd stamp: metadata must never kill the
    # measured headline timings.
    try:
        import shutil
        import tempfile

        from paddle_tpu.distributed import checkpoint as ckpt_mod
        from paddle_tpu.text.models import (GPTForCausalLM,
                                            GPTPretrainingCriterion)

        mesh_mod.reset_mesh()
        # cadence sized so the ~fsync-bound commit fits inside the
        # inter-save window (commit ~0.5s vs ~30ms steps): overlap can
        # only hide what the cadence gives it room to hide — a tighter
        # cadence measures back-pressure, not the snapshot split
        EVERY, STEPS = 16, 49
        ids_small = paddle.to_tensor(
            rng.integers(0, model_cfg.vocab_size, (8, 32)))
        crit = GPTPretrainingCriterion()

        def run_mode(async_save):
            paddle.seed(0)
            m = GPTForCausalLM(model_cfg)
            opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
            step = paddle.jit.TrainStep(
                m, lambda mm, i: crit(mm(i), i), opt)
            float(step(ids_small).numpy())      # compile + warm
            root = tempfile.mkdtemp(prefix="pt_ckpt_ab_")
            cp = ckpt_mod.Checkpointer(root, model=m, train_step=step,
                                       async_save=async_save)
            hist = ckpt_mod._STALL_SECONDS
            stall0, saves0 = hist.sum, hist.count
            times = []
            try:
                for i in range(1, STEPS):
                    t0 = time.perf_counter()
                    step(ids_small)
                    if i % EVERY == 0:
                        cp.save(i)
                    times.append(time.perf_counter() - t0)
                cp.wait()
            finally:
                shutil.rmtree(root, ignore_errors=True)
            n_saves = max(1, hist.count - saves0)
            return {
                "p50_ms": round(float(np.percentile(times, 50)) * 1e3, 3),
                "p99_ms": round(float(np.percentile(times, 99)) * 1e3, 3),
                "saves": n_saves,
                "stall_s_per_save": round(
                    (hist.sum - stall0) / n_saves, 5),
            }

        sync_rec = run_mode(False)
        over_rec = run_mode(True)
        ratio = (over_rec["stall_s_per_save"]
                 / sync_rec["stall_s_per_save"]
                 if sync_rec["stall_s_per_save"] else None)
        ckpt_ab = {"every_n_steps": EVERY, "train_steps": STEPS - 1,
                   "sync": sync_rec, "overlapped": over_rec,
                   "stall_ratio": round(ratio, 4) if ratio else None,
                   "meets_20pct_bar": (ratio is not None
                                       and ratio <= 0.20)}
        log(f"[bench] train_3d ckpt_overlap_ab: stall/save "
            f"{sync_rec['stall_s_per_save']}s sync -> "
            f"{over_rec['stall_s_per_save']}s overlapped "
            f"(ratio {ckpt_ab['stall_ratio']}), step p99 "
            f"{sync_rec['p99_ms']} -> {over_rec['p99_ms']} ms")
        mesh_mod.reset_mesh()
    except Exception as e:
        log(f"[bench] train_3d ckpt_overlap_ab stamp failed: {e!r}")
        ckpt_ab = {"error": repr(e)}
    return {"n_devices": ndev, "configs": out,
            "quant_allreduce_ab": quant_ab,
            "ckpt_overlap_ab": ckpt_ab}


def bench_kv_tier_ab():
    """Hierarchical KV memory A/B (ISSUE-17 acceptance): the SAME
    multi-turn chat workload — S sessions x T turns, each turn's
    prompt embedding the previous turn's full output — served twice on
    an identically-sized device pool small enough that conversation
    histories evict between turns. Tier OFF is the plain radix trie
    (evicted history re-prefills); tier ON adds the host-RAM/disk
    spill tier plus `session_id` pinning, so a returning turn
    prefetches its frontier back through the import scatter instead of
    recomputing it. Headline: prefill-token reduction (target >= 30%)
    with greedy outputs token-identical across the sides and cold TTFT
    no worse. Guarded stamps: TTFT phase breakdown (kv_prefetch vs
    prefill segments) and a pool-capacity-vs-tier-hit-rate sweep."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.inference.llm_engine import LLMEngine
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_small

    paddle.seed(0)
    cfg, sessions, turns, name = gpt_small(), 8, 4, "gpt-small-kv-tier"
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(31)
    gen = 16
    user_toks = [[rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
                  for _ in range(turns)] for _ in range(sessions)]
    # pool sized to hold ~2 live conversations: round-robin turns
    # evict every session's history between its own turns
    ecfg_kw = dict(num_slots=2, page_size=16, token_budget=64,
                   max_model_len=256, prefix_cache=True, num_pages=40)
    tier_dir = os.path.join(tempfile.mkdtemp(prefix="ptkv_"), "tier")

    def drain(eng):
        while eng.has_work():
            eng.step()

    def run_side(tier_on):
        kw = dict(ecfg_kw)
        if tier_on:
            kw["kv_tier"] = dict(ram_bytes=256 << 20, disk_dir=tier_dir)
        eng = LLMEngine(model, inference.LLMEngineConfig(**kw))
        history = [None] * sessions
        outs, ttfts, prompt_total = [], [], 0
        t0 = time.perf_counter()
        for t in range(turns):
            for s in range(sessions):
                prompt = (user_toks[s][t] if history[s] is None else
                          np.concatenate([history[s].astype(np.int32),
                                          user_toks[s][t]]))
                prompt_total += len(prompt)
                req = eng.add_request(
                    prompt, max_new_tokens=gen,
                    session_id=f"chat-{s}" if tier_on else None)
                drain(eng)
                out = req.future.result(timeout=0)
                history[s] = out
                outs.append(out)
                if (req.t_first_token is not None):
                    ttfts.append(req.t_first_token - req.t_submit)
        total_s = time.perf_counter() - t0
        saved = eng.prefix_cache.stats["tokens_saved"]
        tier_snap = (eng.kv_tier.snapshot() if tier_on else None)
        recent = list(eng._timelines)
        eng.close()
        return {"outs": outs, "ttfts": ttfts, "total_s": total_s,
                "prompt_tokens": prompt_total,
                "prefill_tokens": prompt_total - saved,
                "tier": tier_snap, "recent": recent}

    def pctl(vals, p):
        return (round(float(np.percentile(np.asarray(vals), p)) * 1e3, 2)
                if vals else -1.0)

    off = run_side(False)
    log(f"[bench] kv_tier off: {off['prefill_tokens']} prefill tokens "
        f"of {off['prompt_tokens']} in {off['total_s']:.2f}s")
    on = run_side(True)
    log(f"[bench] kv_tier on: {on['prefill_tokens']} prefill tokens, "
        f"tier {{spills {on['tier']['spills']}, ram_hits "
        f"{on['tier']['ram_hits']}, disk_hits {on['tier']['disk_hits']}}} "
        f"in {on['total_s']:.2f}s")
    reduction = (1.0 - on["prefill_tokens"] / off["prefill_tokens"]
                 if off["prefill_tokens"] else 0.0)
    greedy_match = (len(on["outs"]) == len(off["outs"]) and all(
        np.array_equal(a, b) for a, b in zip(on["outs"], off["outs"])))
    # cold TTFT = each session's FIRST turn (nothing cached either side)
    cold_idx = list(range(sessions))
    result = {
        "model": name, "sessions": sessions, "turns": turns,
        "gen_tokens_each": gen, "num_pages": ecfg_kw["num_pages"],
        "prefill_tokens": {"off": off["prefill_tokens"],
                           "on": on["prefill_tokens"]},
        "prefill_token_reduction": round(reduction, 4),
        "meets_30pct_bar": reduction >= 0.30,
        "greedy_match": greedy_match,
        "ttft_p50_ms": {"off": pctl(off["ttfts"], 50),
                        "on": pctl(on["ttfts"], 50)},
        "ttft_p99_ms": {"off": pctl(off["ttfts"], 99),
                        "on": pctl(on["ttfts"], 99)},
        "ttft_cold_p50_ms": {
            "off": pctl([off["ttfts"][i] for i in cold_idx], 50),
            "on": pctl([on["ttfts"][i] for i in cold_idx], 50)},
        "tier": {k: on["tier"][k] for k in
                 ("spills", "spill_pages", "ram_hits", "disk_hits",
                  "misses", "demotions", "spill_rejected")},
        "totals_s": {"off": round(off["total_s"], 2),
                     "on": round(on["total_s"], 2)},
    }
    log(f"[bench] kv_tier_ab: prefill reduction {reduction:.1%} "
        f"(>=30% bar: {result['meets_30pct_bar']}), greedy_match "
        f"{greedy_match}")
    # guarded: TTFT phase breakdown — kv_prefetch vs prefill segments
    try:
        def phase_sums(recent):
            acc = {}
            for tl in recent:
                for seg in tl.get("phases", ()):
                    acc[seg["phase"]] = (acc.get(seg["phase"], 0.0)
                                         + seg["dt_s"])
            return {k: round(v * 1e3, 2) for k, v in sorted(acc.items())}

        result["phase_breakdown_ms"] = {"off": phase_sums(off["recent"]),
                                        "on": phase_sums(on["recent"])}
        result["kv_prefetch_requests"] = sum(
            any(seg["phase"] == "kv_prefetch"
                for seg in tl.get("phases", ()))
            for tl in on["recent"])
    except Exception as e:
        log(f"[bench] kv_tier_ab phase stamp failed: {e!r}")
        result["phase_breakdown_ms"] = {"error": repr(e)}
    # guarded: pool-capacity-vs-tier-hit-rate sweep (tier on, 2-turn
    # shape — how much HBM the spill tier buys back at each size)
    try:
        sweep = []
        for num_pages in (28, 40, 64):
            kw = dict(ecfg_kw, num_pages=num_pages,
                      kv_tier=dict(ram_bytes=256 << 20))
            eng = LLMEngine(model, inference.LLMEngineConfig(**kw))
            hist = [None] * sessions
            for t in range(min(3, turns)):
                for s in range(sessions):
                    prompt = (user_toks[s][t] if hist[s] is None else
                              np.concatenate([hist[s].astype(np.int32),
                                              user_toks[s][t]]))
                    req = eng.add_request(prompt, max_new_tokens=gen,
                                          session_id=f"sweep-{s}")
                    drain(eng)
                    hist[s] = req.future.result(timeout=0)
            snap = eng.kv_tier.snapshot()
            looked = snap["ram_hits"] + snap["disk_hits"] + snap["misses"]
            sweep.append({
                "num_pages": num_pages,
                "tier_hits": snap["ram_hits"] + snap["disk_hits"],
                "tier_hit_rate": (round((snap["ram_hits"]
                                         + snap["disk_hits"]) / looked, 4)
                                  if looked else None),
                "spills": snap["spills"],
                "trie_tokens_saved": eng.prefix_cache.stats[
                    "tokens_saved"]})
            eng.close()
        result["capacity_sweep"] = sweep
        log(f"[bench] kv_tier_ab capacity sweep: {json.dumps(sweep)}")
    except Exception as e:
        log(f"[bench] kv_tier_ab capacity sweep failed: {e!r}")
        result["capacity_sweep"] = {"error": repr(e)}
    return result


def bench_llm_structured_ab():
    """Structured-decoding A/B (the ISSUE-19 acceptance arms): one
    char-level model (vocab 96 = eos + printable ASCII) built with
    `token_strs`, so grammars close over real token text.

      * arm A — constrained overhead: the never-accepting grammar
        `[0-9]{200,}` keeps every constrained row generating for its
        full max_new budget, so U (all plain) vs C (all constrained)
        is a clean per-token cost A/B on identical schedules; the M
        (mixed) run pins the co-residency contract — unconstrained
        rows must be token-identical to run U.
      * arm B — draft-free n-gram speculation vs the fused-k engine
        on a grammar-TEMPLATED workload (`\\[(\\{"k":[0-9]\\},){8,12}\\]`):
        the literal scaffolding between the model-chosen digits is
        exactly what prompt-lookup proposes, so the stamped
        acceptance/speedup measure the subsystem, not model memory.

    Both arms interleave x2 and take best-of-2 per side; greedy
    identity, 100% grammar validity, and zero fused recompiles under
    constrained traffic are ASSERTED — a mask/verify regression must
    fail the bench loudly, not ship a false-speedup JSON."""
    import re

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import GPTConfig

    toks = [""] + [chr(c) for c in range(32, 127)]  # token 0 = eos
    paddle.seed(30)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=len(toks), hidden_size=128, num_layers=6,
        num_heads=4, max_seq_len=512))
    model.eval()
    rng = np.random.default_rng(19)
    n_req, spec_k = 6, int(os.environ.get("BENCH_SPEC_K", "8"))
    prompts = [rng.integers(1, len(toks), (24,)).astype(np.int32)
               for _ in range(n_req)]
    base = dict(num_slots=4, page_size=16, token_budget=16,
                max_model_len=256, token_strs=toks, grammar_states=256)
    digits = r"[0-9]{200,}"               # no accepting state in budget
    template = r'\[(\{"k":[0-9]\},){8,12}\]'

    def text_of(j, out):
        return "".join(toks[t] for t in out[len(prompts[j]):])

    def run(cfg, max_new, grammars, warm_grammar=None):
        """One timed serve; `grammars` maps request index -> regex (or
        absent = unconstrained). Grammar compile + arena load happen
        in the WARMUP submit, so the timed region is decode-only —
        the same steady state the cache-hit path serves."""
        server = inference.LLMServer(model, cfg)
        outs = {}
        with server:
            wk = {"grammar": warm_grammar} if warm_grammar else {}
            server.submit(np.ones((8,), np.int32), max_new_tokens=4,
                          eos_token_id=0, trace=_quiet_trace(),
                          **wk).result(timeout=1800)
            server.engine.stats.update(
                {"steps": 0, "tokens_in": 0, "occupancy_sum": 0.0})
            st = server.engine.stats
            p0 = st.get("ngram_proposed", 0)
            a0 = st.get("ngram_accepted", 0)
            t0 = time.perf_counter()
            futs = [server.submit(prompts[j], max_new_tokens=max_new,
                                  eos_token_id=0, grammar=grammars.get(j))
                    for j in range(n_req)]
            for j, f in enumerate(futs):
                outs[j] = f.result(timeout=1800)
            total = time.perf_counter() - t0
            dp = st.get("ngram_proposed", 0) - p0
            acc = (st.get("ngram_accepted", 0) - a0) / dp if dp else None
            cs = server.engine.compile_stats()
        return outs, total, acc, cs

    # arm A: constrained-overhead + co-residency (fused-k engine)
    fused_cfg = inference.LLMEngineConfig(decode_k=spec_k, **base)
    all_digits = {j: digits for j in range(n_req)}
    mixed = {j: digits for j in range(0, n_req, 2)}
    a_runs = {"U": [], "C": [], "M": []}
    for rep in range(2):
        for kind, (gr, warm) in (("U", ({}, None)),
                                 ("C", (all_digits, digits)),
                                 ("M", (mixed, digits))):
            r = run(fused_cfg, 96, gr, warm_grammar=warm)
            log(f"[bench] llm_structured_ab A:{kind}[{rep}]: "
                f"{r[1]:.2f}s")
            a_runs[kind].append(r)
    a_best = {k: min(v, key=lambda r: r[1]) for k, v in a_runs.items()}
    for kind in ("C", "M"):
        gr = all_digits if kind == "C" else mixed
        for j in gr:
            txt = text_of(j, a_best[kind][0][j])
            assert re.fullmatch(r"[0-9]+", txt), (
                f"arm A {kind} row {j} escaped the grammar: {txt!r}")
    coresident_ok = all(
        np.array_equal(a_best["M"][0][j], a_best["U"][0][j])
        for j in range(n_req) if j not in mixed)
    assert coresident_ok, \
        "arm A: constrained co-residents perturbed unconstrained rows"
    gen = {k: sum(len(a_best[k][0][j]) - len(prompts[j])
                  for j in range(n_req)) for k in a_best}
    per_tok = {k: a_best[k][1] / gen[k] for k in a_best}
    overhead_pct = (per_tok["C"] / per_tok["U"] - 1.0) * 100.0
    recompiles = a_best["C"][3].get("fused_executables", 1) - 1
    assert recompiles == 0, (
        f"arm A: constrained traffic recompiled the fused step "
        f"({recompiles} extra executables)")
    log(f"[bench] llm_structured_ab arm A: constrained overhead "
        f"{overhead_pct:+.1f}%/tok, co-resident identity "
        f"{coresident_ok}, fused recompiles {recompiles}")

    # arm B: n-gram speculation vs fused-k on the templated grammar
    ngram_cfg = inference.LLMEngineConfig(
        spec_mode="ngram", spec_k=spec_k, **base)
    all_tmpl = {j: template for j in range(n_req)}
    b_runs = {"ngram": [], "fused": []}
    for rep in range(2):
        for kind, cfg in (("ngram", ngram_cfg), ("fused", fused_cfg)):
            r = run(cfg, 120, all_tmpl, warm_grammar=template)
            log(f"[bench] llm_structured_ab B:{kind}[{rep}]: "
                f"{r[1]:.2f}s")
            b_runs[kind].append(r)
    b_best = {k: min(v, key=lambda r: r[1]) for k, v in b_runs.items()}
    b_match = all(np.array_equal(b_best["ngram"][0][j],
                                 b_best["fused"][0][j])
                  for j in range(n_req))
    assert b_match, "arm B: ngram greedy outputs diverged from fused"
    for j in range(n_req):
        txt = text_of(j, b_best["ngram"][0][j])
        assert re.fullmatch(template, txt), (
            f"arm B row {j} not grammar-valid: {txt!r}")
    b_gen = sum(len(b_best["ngram"][0][j]) - len(prompts[j])
                for j in range(n_req))
    tps = {k: b_gen / v[1] for k, v in b_best.items()}
    acc = b_best["ngram"][2]
    log(f"[bench] llm_structured_ab arm B: ngram {tps['ngram']:,.0f} "
        f"tok/s vs fused-k{spec_k} {tps['fused']:,.0f} = "
        f"{tps['ngram'] / tps['fused']:.2f}x, acceptance="
        f"{acc if acc is None else round(acc, 3)}, "
        f"greedy_match={b_match}")
    return {
        "spec_k": spec_k, "requests": n_req,
        "greedy_match": bool(b_match),
        "coresident_identity": bool(coresident_ok),
        "grammar_valid_pct": 100.0,
        "constrained_overhead_pct": round(overhead_pct, 2),
        "constrained_fused_recompiles": recompiles,
        "ngram_speedup_vs_fused": round(tps["ngram"] / tps["fused"], 3),
        "acceptance_rate": (None if acc is None else round(acc, 4)),
        "gen_tokens": {"overhead_arm": gen, "ngram_arm": b_gen},
        "tokens_per_sec": {k: round(v) for k, v in tps.items()},
        "totals_s": {
            "overhead_arm": {k: [round(r[1], 2) for r in v]
                             for k, v in a_runs.items()},
            "ngram_arm": {k: [round(r[1], 2) for r in v]
                          for k, v in b_runs.items()}},
    }


_WORKERS = {"gpt": bench_gpt, "resnet": bench_resnet, "bert": bench_bert,
            "deepfm": bench_deepfm, "mnist": bench_mnist,
            "generate": bench_generate, "gpt1p3b": bench_gpt1p3b,
            "gpt1p3b_pp": bench_gpt1p3b_pp, "serving": bench_serving,
            "llm_serve": bench_llm_serve,
            "llm_serve_int8": bench_llm_serve_int8,
            "llm_fleet": bench_llm_fleet,
            "llm_fleet_multi": bench_llm_fleet_multi,
            "overload_storm_ab": bench_overload_storm_ab,
            "kv_tier_ab": bench_kv_tier_ab,
            "llm_structured_ab": bench_llm_structured_ab,
            "train_3d": bench_train_3d}


def worker_main(which):
    _worker_bootstrap()
    result = dict(_WORKERS[which]())
    result["device"] = _device_stamp()
    # Stamp the arm with its telemetry snapshot (registry dump incl.
    # recompile/retry/preemption counters) so a perf regression in the
    # trend series arrives WITH its attribution.
    try:
        from paddle_tpu import observability

        result["telemetry"] = observability.bench_snapshot()
    except Exception as e:
        log(f"[bench] telemetry stamp failed: {e!r}")
    # Machine-readable result on stdout (the parent parses; user sees stderr).
    print(json.dumps({"worker": which, "result": result}), flush=True)


# --------------------------------------------------------------------------
# Parent side: never imports jax (one process per chip).
# --------------------------------------------------------------------------

def _run_worker(which, timeout_s):
    """Run one arm in a subprocess. Returns (status, result_dict) with
    status ∈ {"ok", "error", "timeout"}. The subprocess owns the chip
    only while alive, so killing it on timeout releases the TPU for the
    next arm."""
    cmd = [sys.executable, os.path.abspath(__file__), "--worker", which]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=sys.stderr,
                            text=True,
                            cwd=os.path.dirname(os.path.abspath(__file__)))
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        return "timeout", None
    if proc.returncode != 0:
        return "error", None
    for line in (out or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                payload = json.loads(line)
                if payload.get("worker") == which:
                    return "ok", payload["result"]
            except (json.JSONDecodeError, KeyError):
                continue
    return "error", None


def _ptlint_stamp():
    """ptlint version + finding count for the run metadata: a perf
    trend record is only comparable when the measured tree was
    jit-clean (a host sync or dropped donation skews the number before
    any kernel change does). Loads the stdlib-only linter standalone —
    no paddle_tpu/jax import in the supervisor."""
    try:
        import importlib.util

        here = os.path.dirname(os.path.abspath(__file__))
        # one loader, owned by the CLI: tools/ptlint.py knows how to
        # bring the linter up standalone and which paths the gate covers
        spec = importlib.util.spec_from_file_location(
            "_bench_ptlint_cli", os.path.join(here, "tools", "ptlint.py"))
        cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cli)
        mod = cli._load_lint()
        res = mod.lint_paths(
            [os.path.join(here, p) for p in cli.DEFAULT_PATHS])
        # the SPMD families ride the same stamp: version of the
        # jaxpr-level pass suite (stdlib-readable from lint.py) plus
        # the AST-side PTL6xx/PTL7xx finding count — the jaxpr-level
        # counts are stamped per-config by the train_3d arm, which
        # owns a live step
        spmd_ast = sum(1 for f in res["findings"]
                       if f.rule.startswith(("PTL6", "PTL7")))
        # the lock-discipline graph rides the same stamp (ISSUE-20):
        # a perf trend across PRs is only comparable when the lock
        # topology is the blessed one — a new cross-class edge can BE
        # the regression (serialization the profiler sees as idle)
        lock_rep = mod.lock_graph_report(
            [os.path.join(here, p) for p in cli.DEFAULT_PATHS])
        return {"version": mod.PTLINT_VERSION,
                "findings": len(res["findings"]),
                "suppressed": res["suppressed"],
                "files": res["files"],
                "spmd": {"version": mod.SPMD_ANALYSIS_VERSION,
                         "ast_findings": spmd_ast},
                "locks": {"version": mod.LOCK_ANALYSIS_VERSION,
                          "classes": lock_rep["classes"],
                          "edges": lock_rep["edges"],
                          "findings": len(lock_rep["findings"])}}
    except Exception as e:  # metadata must never kill the headline
        log(f"[bench] ptlint stamp failed: {e!r}")
        return {"error": repr(e)}


def _write_detail(detail):
    """Durable per-arm record (the driver captures stdout only; the
    headline line must stay the sole stdout JSON). Written on EVERY
    path — an outage truncates the file instead of leaving a stale
    success record from a previous run."""
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_detail.json"), "w") as f:
            json.dump(detail, f, indent=1)
    except OSError as e:
        log(f"[bench] detail record failed: {e!r}")


def main():
    # Headline: GPT. No probe, no retry, no other geometry: the worker
    # fails if JAX finds no TPU, and then so does the run.
    status, gpt = _run_worker("gpt", timeout_s=900)
    if status != "ok":
        log(f"[bench] gpt -> {status}: no result")
        return 1

    detail = {"gpt": gpt}
    # A run under fault injection (distributed/chaos.py) measures
    # resilience, not speed — stamp the record so chaos runs never
    # pollute the trend series. The ptlint stamp serves the same
    # comparability purpose for jit-safety (docs/ANALYSIS.md).
    chaos_active = bool(os.environ.get("PT_CHAOS_PLAN"))
    ptlint_stamp = _ptlint_stamp()
    detail["ptlint"] = ptlint_stamp
    mfu = gpt["mfu"]
    line = {
        "metric": "gpt_small_train_mfu",
        "value": mfu,
        "unit": "fraction_of_bf16_peak_of_device_kind",
        "device": gpt["device"],
        "vs_baseline": round(mfu / BASELINE_MFU, 4),
        "chaos_plan_active": chaos_active,
        "ptlint": ptlint_stamp,
        "detail": detail,
    }
    # Emit the headline NOW: nothing after this point can zero the result.
    print(json.dumps(line), flush=True)
    _write_detail(detail)

    # The other arms — stderr and bench_detail.json, one attempt each,
    # bounded. A dead arm fails the run.
    failed = []
    for which in ("resnet", "bert", "deepfm", "mnist", "generate",
                  "serving", "llm_serve", "llm_serve_int8", "llm_fleet",
                  "llm_fleet_multi", "overload_storm_ab",
                  "kv_tier_ab", "llm_structured_ab", "train_3d"):
        # the llm_serve/llm_fleet arms run TWO serving phases each
        # (engine vs baseline / int8 vs fp32 / fleet vs fifo) plus both
        # compiles, so they need a wider cap than the single-model arms
        status, res = _run_worker(
            which,
            timeout_s=900 if which.startswith(("llm_", "overload_",
                                               "kv_"))
            else 420)
        if status == "ok":
            log(f"[bench] {which} result: {json.dumps(res)}")
            detail[which] = res
        else:
            log(f"[bench] {which} FAILED ({status})")
            failed.append(which)
    _write_detail(detail)
    if failed:
        log(f"[bench] arms failed: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        worker_main(sys.argv[2])
    else:
        sys.exit(main())
