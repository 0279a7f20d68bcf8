"""Driver benchmark: paddle_tpu training/serving rows on one TPU chip.

The parent process imports neither jax nor paddle_tpu — a chip belongs
to one process, and every row (the llama headline included) runs in a
child of its own. The parent first asks a probe child which device JAX
sees and exits non-zero before any row when it is not a TPU; a row that
fails makes the whole run exit non-zero.

Prints as its last stdout line ONE JSON object: the headline metric
{"metric", "value", "unit"} — MFU of the jit-staged Llama pretrain step
(fwd+bwd+AdamW in one donated XLA program, bf16 compute, Pallas flash
attention, chunked fused LM-head loss) — plus every metric a row
reported, the device the rows ran on and the rows that failed. Rows log
to stderr. ``BENCH_ONLY=llama,decode`` restricts the rows run.

The row functions below predate the chip this repo now runs on and are
ROADMAP A0's to replace with a table of cells; no number they print has
been recorded on the current stack.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _timed_steps(fn, steps, sync, warmup=10):
    """Steady-state step time: warm up, then time ``steps`` calls ending
    in a host sync."""
    out = None
    for _ in range(warmup):
        out = fn()
    sync(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / steps


def bench_llama(paddle, on_tpu, peak):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    # Single-chip headline model: 745M-class decoder (h=2048, L=12),
    # the largest width whose fwd+bwd+AdamW(fp32 master) steady state
    # fits one 16G v5e; batch 12 with the chunked fused LM-head loss
    # (no [b,s,vocab] fp32 logits) is the measured MFU sweet spot.
    if on_tpu:
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=12, num_attention_heads=16,
            max_position_embeddings=2048, fused_loss_chunk=2048,
        )
        paddle.set_flags({"FLAGS_flash_attention_min_seq": 1024})
        batch, seq, steps, warmup = 12, 1024, 10, 3
    else:  # CPU smoke path so the script always emits its line
        cfg = LlamaConfig.tiny(fused_loss_chunk=64)
        batch, seq, steps, warmup = 2, 32, 3, 1
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n_params = model.num_params()
    log(f"[llama] device={paddle.get_device()} params={n_params/1e6:.1f}M "
        f"batch={batch} seq={seq}")

    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, weight_decay=0.1,
        parameters=model.parameters(), multi_precision=True,
    )

    def loss_fn(m, ids):
        _, loss = m(ids, labels=ids)
        return loss

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32")
    )

    t0 = time.perf_counter()
    loss = step(ids)
    float(loss.numpy())
    log(f"[llama] compile+first step: {time.perf_counter()-t0:.1f}s "
        f"loss={float(loss.numpy()):.3f}")
    for _ in range(warmup):
        step(ids)
    float(step(ids).numpy())

    dt = _timed_steps(
        lambda: step(ids), steps, lambda o: float(o.numpy())
    )
    tokens_per_sec = batch * seq / dt
    # PaLM-appendix MFU accounting: 6N per token (fwd+bwd matmuls) plus
    # causal attention 12*L*d*s (QK^T and PV, fwd+bwd, halved for causality)
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * \
        cfg.hidden_size * seq * 0.5
    mfu = tokens_per_sec * flops_per_token / peak
    log(f"[llama] step={dt*1e3:.1f}ms tokens/s={tokens_per_sec:,.0f} "
        f"MFU={mfu*100:.1f}% (peak {peak/1e12:.0f} TF)")

    # eager-vs-jit ratio on a TINY probe model (the full config OOMs the
    # chip in eager mode: every op allocates its own intermediates)
    try:
        paddle.seed(0)
        probe = LlamaForCausalLM(LlamaConfig(
            vocab_size=32000, hidden_size=512, intermediate_size=1408,
            num_hidden_layers=4, num_attention_heads=8,
            max_position_embeddings=1024,
        ))
        probe.bfloat16()
        popt = paddle.optimizer.AdamW(
            learning_rate=3e-4, parameters=probe.parameters()
        )
        pids = paddle.to_tensor(
            rng.randint(0, 32000, (2, 256)).astype("int32")
        )
        pstep = paddle.jit.TrainStep(probe, loss_fn, popt)
        float(pstep(pids).numpy())  # compile + sync
        jdt = _timed_steps(
            lambda: pstep(pids), 3, lambda o: float(o.numpy())
        )

        def eager_once():
            ls = loss_fn(probe, pids)
            ls.backward()
            popt.step()
            popt.clear_grad()
            return ls

        eager_once()
        edt = _timed_steps(eager_once, 2, lambda o: float(o.numpy()))
        log(f"[llama] eager-vs-jit probe (68M): eager={edt*1e3:.0f}ms "
            f"jit={jdt*1e3:.1f}ms -> {edt/jdt:.0f}x")
    except Exception as e:  # diagnostics must never break the contract
        log(f"[llama] eager comparison skipped: {e}")
    return mfu


def bench_decode(paddle, on_tpu):
    """KV-cache greedy decode throughput (BASELINE serving row)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    batch, prompt, new = (8, 128, 64) if on_tpu else (2, 8, 4)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, prompt)
        ).astype("int64")
    )
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=new)
    log(f"[decode] compile+first generate: {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=new)
    dt = time.perf_counter() - t0
    tps = batch * new / dt
    log(f"[decode] {cfg.hidden_size=} batch={batch} prompt={prompt} "
        f"new={new}: {tps:,.0f} tokens/s ({dt/new*1e3:.1f} ms/token-step)")
    return tps


# MoE shrink ladder (BASELINE config #4): level 0 is the documented
# single-chip ceiling (653M, batch 8 — OOMs a v5e: each expert holds 8x
# the dense FFN weights while only k=2 earn their activations); the
# parent retries the row at successive levels in FRESH subprocesses
# until one fits, so BENCH always records a real MoE number.
_MOE_LEVELS = [
    dict(num_hidden_layers=8, batch=8),
    dict(num_hidden_layers=6, batch=4),
    dict(num_hidden_layers=4, batch=4),
    dict(num_hidden_layers=4, batch=2, hidden_size=768,
         intermediate_size=2048, num_attention_heads=12),
]


def bench_moe(paddle, on_tpu, peak):
    """Mixtral-style MoE decoder step (BASELINE config #4 row)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    level = int(os.environ.get("BENCH_MOE_LEVEL", "0"))
    lv = dict(_MOE_LEVELS[level])
    batch_l = lv.pop("batch")
    kw = dict(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_attention_heads=16, max_position_embeddings=2048,
        num_experts=8, num_experts_per_tok=2, fused_loss_chunk=2048,
    )
    kw.update(lv)  # level overrides (level 3 shrinks h/ffn/heads too)
    cfg = LlamaConfig(**kw) if on_tpu else LlamaConfig.tiny(num_experts=4)
    if on_tpu:
        # same flash gate as the llama row: unflashed seq-1024 attention
        # stashes [b, h, s, s] scores per layer for bwd and thrashes HBM
        paddle.set_flags({"FLAGS_flash_attention_min_seq": 1024})
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n = model.num_params()
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
    )

    def loss_fn(m, ids):
        _, loss = m(ids, labels=ids)
        return loss

    # donation (the default) halves the transient optimizer-state
    # footprint, which is what lets level 0 fit
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    batch, seq = (batch_l, 1024) if on_tpu else (2, 32)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, seq)
        ).astype("int32")
    )
    t0 = time.perf_counter()
    float(step(ids).numpy())
    log(f"[moe] compile+first: {time.perf_counter()-t0:.1f}s")
    step(ids)
    dt = _timed_steps(lambda: step(ids), 5, lambda o: float(o.numpy()))
    tps = batch * seq / dt
    # active params per token: shared + k of e experts
    expert = 3 * cfg.hidden_size * cfg.intermediate_size
    active = n - cfg.num_hidden_layers * (
        (cfg.num_experts - cfg.num_experts_per_tok) * expert
    )
    mfu = tps * 6 * active / peak
    log(f"[moe] level {level}: {n/1e6:.0f}M total/{active/1e6:.0f}M "
        f"active, e=8 k=2, batch={batch}: step={dt*1e3:.0f}ms "
        f"{tps:,.0f} tokens/s active-MFU={mfu*100:.1f}%")
    return tps


def bench_kernels(paddle, on_tpu, peak):
    """[kernels] row — the fused hot-path kernel lane (ISSUE 12):
    ragged (dropless grouped_matmul) vs dense (capacity-padded einsum)
    MoE layer throughput, paged decode-attention kernel throughput, and
    the int8 KV-cache byte budget. On TPU the Pallas kernels run; on
    CPU the XLA fallbacks run (the exact code path tier-1 exercises),
    so the CPU smoke quantifies the dispatch-layer win (no capacity
    padding) while the TPU run adds the kernel win."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate import MoELayer

    # --- ragged vs dense MoE layer (forward, staged) ------------------
    if on_tpu:
        d_model, d_ff, e, k, b, s = 1024, 2816, 8, 2, 8, 1024
    else:
        d_model, d_ff, e, k, b, s = 64, 256, 8, 2, 2, 512
    layers = {}
    for impl in ("dense", "ragged"):
        paddle.seed(0)
        layers[impl] = MoELayer(
            d_model=d_model, num_experts=e, d_ff=d_ff, k=k, impl=impl,
        )
    x = paddle.to_tensor(np.random.RandomState(0).randn(
        b, s, d_model
    ).astype(np.float32))
    tps = {}
    for impl, layer in layers.items():
        staged = paddle.jit.to_static(
            lambda t, _l=layer: _l(t)[0], full_graph=True
        )
        staged(x)  # compile
        dt = _timed_steps(
            lambda: staged(x), 5, lambda o: o.numpy(), warmup=3,
        )
        tps[impl] = b * s / dt
        log(f"[kernels] moe_{impl}: {b * s} tokens in {dt*1e3:.1f}ms "
            f"-> {tps[impl]:,.0f} tokens/s")
    speedup = tps["ragged"] / tps["dense"]
    log(f"[kernels] ragged vs dense speedup: {speedup:.2f}x")
    print(json.dumps({
        "metric": "moe_ragged_tokens_per_s",
        "value": round(tps["ragged"]), "unit": "tokens/s",
    }))
    print(json.dumps({
        "metric": "moe_ragged_vs_dense_speedup",
        "value": round(speedup, 3), "unit": "x",
    }))

    # --- paged decode attention kernel --------------------------------
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_attention, paged_attention_xla,
    )

    if on_tpu:
        batch, kvh, qh, d, pages, bs_pg, pps = 64, 8, 32, 128, 2048, 16, 64
    else:
        batch, kvh, qh, d, pages, bs_pg, pps = 8, 2, 8, 64, 64, 16, 8
    rng = np.random.RandomState(1)
    kp = jnp.asarray(rng.randn(kvh, pages, bs_pg, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(kvh, pages, bs_pg, d).astype(np.float32))
    q = jnp.asarray(rng.randn(batch, qh, d).astype(np.float32))
    bt = jnp.asarray(
        rng.randint(0, pages, (batch, pps)).astype(np.int32)
    )
    lens = jnp.asarray(
        rng.randint(1, pps * bs_pg, batch).astype(np.int32)
    )
    kern = paged_attention if on_tpu else paged_attention_xla
    f = jax.jit(lambda *a: kern(*a))
    jax.block_until_ready(f(q, kp, vp, bt, lens))
    t0 = time.perf_counter()
    iters = 50
    for _ in range(iters):
        out = f(q, kp, vp, bt, lens)
    jax.block_until_ready(out)
    dk_tps = batch * iters / (time.perf_counter() - t0)
    log(f"[kernels] paged decode attention ({'pallas' if on_tpu else 'xla'}"
        f" path): {dk_tps:,.0f} tokens/s (batch={batch} ctx<="
        f"{pps * bs_pg})")
    print(json.dumps({
        "metric": "decode_paged_kernel_tokens_per_s",
        "value": round(dk_tps), "unit": "tokens/s",
    }))

    # --- int8 KV byte budget ------------------------------------------
    from paddle_tpu.serving import KVPool

    layers_n = 8
    fp = KVPool(layers_n, kvh, pages, bs_pg, d, "float32")
    q8 = KVPool(layers_n, kvh, pages, bs_pg, d, "float32",
                quant_dtype="int8")
    ratio = fp.bytes_per_token() / q8.bytes_per_token()
    log(f"[kernels] kv bytes/token: fp32 {fp.bytes_per_token():.0f} -> "
        f"int8 {q8.bytes_per_token():.0f} ({ratio:.2f}x)")
    print(json.dumps({
        "metric": "kv_int8_bytes_per_token",
        "value": round(q8.bytes_per_token(), 1), "unit": "bytes",
    }))
    return tps["ragged"]


def bench_resnet(paddle, on_tpu):
    """ResNet-50 training throughput (BASELINE config #1 row)."""
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=10)
    opt = paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9,
        parameters=model.parameters(), weight_decay=5e-4,
    )
    ce = nn.CrossEntropyLoss()

    def loss_fn(m, x, y):
        return ce(m(x), y)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    batch = 128 if on_tpu else 4
    size = 32
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(
        rng.randn(batch, 3, size, size).astype("float32")
    )
    y = paddle.to_tensor(rng.randint(0, 10, (batch,)).astype("int64"))
    t0 = time.perf_counter()
    float(step(x, y).numpy())
    log(f"[resnet50] compile+first: {time.perf_counter()-t0:.1f}s")
    step(x, y)
    dt = _timed_steps(
        lambda: step(x, y), 5, lambda o: float(o.numpy())
    )
    ips = batch / dt
    log(f"[resnet50] CIFAR-10 batch={batch}: step={dt*1e3:.1f}ms "
        f"{ips:,.0f} images/s")
    return ips


def bench_dit(paddle, on_tpu):
    """DiT denoising training step (BASELINE config #5 row)."""
    from paddle_tpu.models.dit import DiT, DiTConfig

    cfg = DiTConfig(
        input_size=32, patch_size=2, in_channels=4, hidden_size=512,
        depth=8, num_heads=8, num_classes=10,
    ) if on_tpu else DiTConfig.tiny()
    paddle.seed(0)
    model = DiT(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters()
    )

    def loss_fn(m, x, t, y, target):
        return ((m(x, t, y) - target) ** 2).mean()

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    batch = 32 if on_tpu else 2
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(
        batch, cfg.in_channels, cfg.input_size, cfg.input_size
    ).astype("float32"))
    tt = paddle.to_tensor(
        rng.randint(0, 1000, (batch,)).astype("int32")
    )
    y = paddle.to_tensor(
        rng.randint(0, cfg.num_classes, (batch,)).astype("int64")
    )
    target = paddle.to_tensor(rng.randn(*x.shape).astype("float32"))
    t0 = time.perf_counter()
    float(step(x, tt, y, target).numpy())
    log(f"[dit] compile+first: {time.perf_counter()-t0:.1f}s")
    step(x, tt, y, target)
    dt = _timed_steps(
        lambda: step(x, tt, y, target), 5, lambda o: float(o.numpy())
    )
    log(f"[dit] latent 32x32 p2 h={cfg.hidden_size} d={cfg.depth} "
        f"batch={batch}: step={dt*1e3:.1f}ms "
        f"{batch/dt:,.0f} samples/s")
    return batch / dt


def bench_serving(paddle, on_tpu):
    """Continuous-batching mixed workload (serving row): many concurrent
    requests with heterogeneous prompt/output lengths through ONE
    fixed-shape compiled decode step + bucketed prefill. The [serving]
    metric is end-to-end generated tokens/s including scheduling,
    admission, and KV-block management — the multi-tenant counterpart of
    the single-stream [decode] row."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    n_req, slots, mml = (32, 8, 512) if on_tpu else (8, 4, 64)
    ecfg = EngineConfig(
        max_batch_slots=slots, max_model_len=mml,
        page_size=16 if on_tpu else 8,
    )
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab_size, rng.randint(8, mml // 4)).tolist()
        for _ in range(n_req)
    ]
    params = [
        SamplingParams(max_new_tokens=int(rng.randint(mml // 8, mml // 2)))
        for _ in range(n_req)
    ]

    eng = Engine(model, ecfg)   # reused: the timed run hits warm programs

    def run():
        outs = eng.generate(prompts, params)
        return outs, sum(len(o.token_ids) for o in outs)

    t0 = time.perf_counter()
    run()
    log(f"[serving] compile+first run: {time.perf_counter()-t0:.1f}s "
        f"(prefill compiles={eng.metrics.prefill_compiles}, "
        f"decode compiles={eng.metrics.decode_compiles})")
    t0 = time.perf_counter()
    outs, n_tokens = run()
    dt = time.perf_counter() - t0
    tps = n_tokens / dt
    ttft = float(np.mean([o.time_to_first_token for o in outs]))
    bm = eng.block_manager
    log(f"[serving] {n_req} reqs x {slots} slots mml={mml}: "
        f"{n_tokens} tokens in {dt:.2f}s -> {tps:,.0f} tokens/s "
        f"(ttft={ttft*1e3:.0f}ms hw={bm.high_water} "
        f"preempt={eng.metrics.preemptions} "
        f"compiles={eng.metrics.prefill_compiles}"
        f"+{eng.metrics.decode_compiles})")
    # stdout: picked up by main() into the BENCH json line
    print(json.dumps({
        "metric": "serving_mixed_tokens_per_s",
        "value": round(tps, 1),
        "unit": "tokens/s",
    }))

    # ---- streaming latency percentiles over the WARM timed run (the
    # engine's own cumulative digests also hold the compile-heavy
    # first run — a cold-replica tail worth scraping in production but
    # noise as a tracked bench number): rebuild the digest from the
    # warm run's per-request timelines, the same sketch the scrape
    # exports
    from paddle_tpu.observability.latency import LatencyDigest

    dig = {"ttft": LatencyDigest(), "tpot": LatencyDigest()}
    for o in outs:
        for k in dig:
            v = o.metrics[f"{k}_s"]
            if v is not None:
                dig[k].record(v)
    ttft_p99 = dig["ttft"].quantile(0.99)
    tpot_p99 = dig["tpot"].quantile(0.99)
    log(f"[serving] warm-run latency digests: ttft p50/p99="
        f"{dig['ttft'].quantile(0.5)*1e3:.1f}/{ttft_p99*1e3:.1f}ms "
        f"tpot p50/p99={dig['tpot'].quantile(0.5)*1e3:.2f}/"
        f"{tpot_p99*1e3:.2f}ms "
        f"(n={dig['ttft'].count})")
    print(json.dumps({
        "metric": "serving_ttft_p99_ms",
        "value": round(ttft_p99 * 1e3, 2),
        "unit": "ms",
    }))
    print(json.dumps({
        "metric": "serving_tpot_p99_ms",
        "value": round(tpot_p99 * 1e3, 3),
        "unit": "ms",
    }))

    # ---- durable request journal: WAL cost on a mixed workload with
    # production-representative stream lengths (tens-to-hundreds of
    # output tokens — the 8..32-token smoke streams above would price
    # the per-completion durable write against runs 4x shorter than
    # anything a serving deployment sees). Same heterogeneous mixed
    # character: random prompts, random output budgets, more requests
    # than slots. Acceptance bar: <3% overhead.
    import shutil
    import tempfile

    j_mml = 2048 if on_tpu else 256
    rng = np.random.RandomState(7)
    j_prompts = [
        rng.randint(1, cfg.vocab_size, rng.randint(8, j_mml // 8)
                    ).tolist()
        for _ in range(n_req)
    ]
    j_params = [
        SamplingParams(
            max_new_tokens=int(rng.randint(j_mml // 8, j_mml // 2)),
        )
        for _ in range(n_req)
    ]
    j_kw = dict(
        max_batch_slots=slots, max_model_len=j_mml,
        page_size=16 if on_tpu else 8,
    )
    jroot = tempfile.mkdtemp(prefix="paddle_tpu_journal_bench_")

    def floor_pair(eng_base, eng_inst, iters):
        """Floor-to-floor overhead timing: run-to-run noise (scheduler
        jitter, GC, XLA dispatch variance) is the same order as the
        cost under test, so the engines run in interleaved pairs
        (order alternating) and only the per-engine FLOOR — the one
        statistic that converges here — is compared. Returns
        ``(dt_base, dt_inst, overhead_pct)``."""
        dt_base = dt_inst = None
        for i in range(iters):
            order = (
                (eng_base, eng_inst) if i % 2 == 0
                else (eng_inst, eng_base)
            )
            for engine in order:
                t0 = time.perf_counter()
                engine.generate(j_prompts, j_params)
                dt = time.perf_counter() - t0
                if engine is eng_base:
                    dt_base = (
                        dt if dt_base is None else min(dt_base, dt)
                    )
                else:
                    dt_inst = (
                        dt if dt_inst is None else min(dt_inst, dt)
                    )
        return dt_base, dt_inst, (dt_inst - dt_base) / dt_base * 100.0

    try:
        eng_p = Engine(model, EngineConfig(**j_kw))
        eng_j = Engine(model, EngineConfig(
            **j_kw, journal=os.path.join(jroot, "wal"),
        ))
        for engine in (eng_p, eng_j):
            engine.generate(j_prompts, j_params)   # warm programs
        dt_plain, dt_journal, overhead_pct = floor_pair(
            eng_p, eng_j, 8 if on_tpu else 24,
        )
        j = eng_j.journal
        log(f"[serving] journal overhead: {dt_journal:.3f}s vs "
            f"{dt_plain:.3f}s plain -> {overhead_pct:+.2f}% "
            f"({j.writes} writes, {j.records_written} records, "
            f"{j.bytes_written/1e3:.0f}KB, "
            f"segments={len(j.segments())})")
        print(json.dumps({
            "metric": "serving_journal_overhead_pct",
            "value": round(overhead_pct, 2),
            "unit": "percent",
        }))

        # ---- access-log overhead: same floor-to-floor discipline as
        # the journal pair (one JSONL line per finished request +
        # always-on timelines vs the plain engine) — the <2% contract
        eng_a = Engine(model, EngineConfig(
            **j_kw, access_log=os.path.join(jroot, "alog"),
        ))
        eng_a.generate(j_prompts, j_params)   # warm programs
        dt_plain2, dt_alog, alog_pct = floor_pair(
            eng_p, eng_a, 8 if on_tpu else 24,
        )
        al = eng_a.access_log
        log(f"[serving] access-log overhead: {dt_alog:.3f}s vs "
            f"{dt_plain2:.3f}s plain -> {alog_pct:+.2f}% "
            f"({al.records_written} lines, "
            f"{al.bytes_written/1e3:.0f}KB, "
            f"files={len(al.files())}, errors={al.write_errors})")
        print(json.dumps({
            "metric": "serving_accesslog_overhead_pct",
            "value": round(alog_pct, 2),
            "unit": "percent",
        }))
    finally:
        shutil.rmtree(jroot, ignore_errors=True)

    # ---- prefix caching + chunked prefill: TTFT under long-prompt
    # mixed traffic, and prefill compute saved on shared system prompts.
    # A LONG shared prefix (half the context) dominates every prompt;
    # the baseline engine must prefill it per request in one stall-the-
    # batch launch, the cached+chunked engine forks it and interleaves
    # the remaining chunks with decode.
    chunk = 128 if on_tpu else 16
    rng = np.random.RandomState(1)
    sys_prefix = rng.randint(1, cfg.vocab_size, mml // 2).tolist()
    tail = mml // 16
    long_prompts = [
        sys_prefix + rng.randint(1, cfg.vocab_size, tail).tolist()
        for _ in range(n_req // 2)
    ]
    long_params = SamplingParams(max_new_tokens=mml // 16)

    def mean_ttft(engine):
        outs = engine.generate(long_prompts, long_params)
        return float(np.mean([o.time_to_first_token for o in outs]))

    mean_ttft(eng)              # warm the baseline's long buckets
    ttft_base = mean_ttft(eng)
    ecfg2 = EngineConfig(
        max_batch_slots=slots, max_model_len=mml,
        page_size=16 if on_tpu else 8,
        enable_prefix_cache=True, prefill_chunk_tokens=chunk,
        # one chunk per occupant per step: admissions are not starved,
        # but no single step runs more prefill than one chunk per slot
        max_prefill_chunks_per_step=slots,
    )
    eng2 = Engine(model, ecfg2)
    mean_ttft(eng2)             # warm + publish the shared prefix
    m2 = eng2.metrics
    computed0, hit0 = m2.prefill_tokens, m2.prefix_hit_tokens
    ttft_chunked = mean_ttft(eng2)
    computed = m2.prefill_tokens - computed0
    hit = m2.prefix_hit_tokens - hit0
    hit_rate = hit / max(hit + computed, 1)
    log(f"[serving] long-prompt ttft: baseline={ttft_base*1e3:.1f}ms "
        f"prefix+chunked={ttft_chunked*1e3:.1f}ms "
        f"(prefill computed={computed} cached={hit} "
        f"hit_rate={hit_rate:.2f} chunks={m2.prefill_chunks})")
    print(json.dumps({
        "metric": "serving_ttft_ms",
        "value": round(ttft_chunked * 1e3, 2),
        "unit": "ms",
    }))
    print(json.dumps({
        "metric": "serving_ttft_unchunked_ms",
        "value": round(ttft_base * 1e3, 2),
        "unit": "ms",
    }))
    print(json.dumps({
        "metric": "serving_prefix_hit_rate",
        "value": round(hit_rate, 4),
        "unit": "fraction",
    }))
    print(json.dumps({
        "metric": "serving_prefill_tokens_computed",
        "value": int(computed),
        "unit": "tokens",
    }))

    # ---- speculative decoding: n-gram drafting + batched verification
    # on a repetition-heavy workload (constant-token prompts drive the
    # model into its greedy quasi-cycles, where prompt-lookup drafts
    # land). Spec and baseline engines share the exact config except
    # speculate_tokens; greedy outputs are asserted byte-identical, so
    # the rows measure pure launch-amortization speedup.
    spec_k = 4 if on_tpu else 3
    s_slots, s_mml = (8, 512) if on_tpu else (4, 128)
    rng = np.random.RandomState(3)
    rep_prompts = [
        [int(t)] * 12 for t in rng.randint(1, cfg.vocab_size, s_slots)
    ]
    rep_params = SamplingParams(max_new_tokens=s_mml - 16)
    base_kw = dict(
        max_batch_slots=s_slots, max_model_len=s_mml, page_size=16,
    )
    eng_base = Engine(model, EngineConfig(**base_kw))
    eng_spec = Engine(model, EngineConfig(
        **base_kw, speculate_tokens=spec_k,
    ))
    eng_base.generate(rep_prompts, rep_params)   # warm programs
    eng_spec.generate(rep_prompts, rep_params)
    n_spec_tok = s_slots * rep_params.max_new_tokens

    def timed(engine):
        # launches are tracked PER RUN (the workload is deterministic,
        # but counters are cumulative) so tokens/launch and step_ms
        # normalize against the same run the best wall time came from
        best = launches = None
        for _ in range(3):
            v_before = engine.metrics.verify_steps
            t0 = time.perf_counter()
            outs = engine.generate(rep_prompts, rep_params)
            dt = time.perf_counter() - t0
            if best is None or dt < best:
                best = dt
                launches = engine.metrics.verify_steps - v_before
        return outs, best, launches

    outs_base, dt_base, _ = timed(eng_base)
    ms = eng_spec.metrics
    p0, a0 = ms.spec_proposed, ms.spec_accepted
    outs_spec, dt_spec, launches = timed(eng_spec)
    assert ([o.token_ids for o in outs_spec]
            == [o.token_ids for o in outs_base]), "spec broke parity"
    accept_rate = (ms.spec_accepted - a0) / max(ms.spec_proposed - p0, 1)
    spec_tps = n_spec_tok / dt_spec
    base_tps = n_spec_tok / dt_base
    step_ms = dt_spec / max(launches, 1) * 1e3
    log(f"[serving] speculative decode K={spec_k}: "
        f"{spec_tps:,.0f} tokens/s vs {base_tps:,.0f} baseline "
        f"(accept_rate={accept_rate:.2f} "
        f"tokens/launch={n_spec_tok / max(launches, 1):.2f} "
        f"step={step_ms:.2f}ms)")
    print(json.dumps({
        "metric": "serving_spec_tokens_per_s",
        "value": round(spec_tps, 1),
        "unit": "tokens/s",
    }))
    print(json.dumps({
        "metric": "serving_spec_baseline_tokens_per_s",
        "value": round(base_tps, 1),
        "unit": "tokens/s",
    }))
    print(json.dumps({
        "metric": "serving_spec_accept_rate",
        "value": round(accept_rate, 4),
        "unit": "fraction",
    }))
    print(json.dumps({
        "metric": "serving_spec_step_ms",
        "value": round(step_ms, 3),
        "unit": "ms",
    }))

    # ---- host KV spill tier (serving/spill.py): a num_blocks-starved
    # pool drives preemption thrash; the spill-on engine swaps victims'
    # KV to host RAM and restores at re-admission instead of
    # re-prefilling. Floor-pair against the identical spill-off engine
    # (whose preemptions recompute), greedy outputs asserted
    # byte-identical — the rows are restore latency and the fraction of
    # preemptions that resumed through a restore (contract: >= 0.9).
    sp_slots, sp_mml, sp_blocks = (8, 256, 48) if on_tpu else (4, 32, 10)
    rng = np.random.RandomState(11)
    sp_prompts = [
        rng.randint(1, cfg.vocab_size, rng.randint(6, sp_mml // 4)
                    ).tolist()
        for _ in range(sp_slots * 2)
    ]
    sp_params = [
        SamplingParams(
            max_new_tokens=int(rng.randint(sp_mml // 8, sp_mml // 4)),
            do_sample=False,
        )
        for _ in range(sp_slots * 2)
    ]
    sp_kw = dict(
        max_batch_slots=sp_slots, max_model_len=sp_mml,
        page_size=16 if on_tpu else 4, num_blocks=sp_blocks,
    )
    eng_off = Engine(model, EngineConfig(**sp_kw))
    eng_sp = Engine(model, EngineConfig(
        **sp_kw, host_spill_bytes=256 * 1024 * 1024,
    ))
    outs_off = eng_off.generate(sp_prompts, sp_params)   # warm + thrash
    m_sp, tier = eng_sp.metrics, eng_sp.spill
    pre0 = m_sp.preemptions
    s0 = tier.stats()
    outs_sp = eng_sp.generate(sp_prompts, sp_params)
    assert ([o.token_ids for o in outs_sp]
            == [o.token_ids for o in outs_off]), "spill broke parity"
    s1 = tier.stats()
    preempts = m_sp.preemptions - pre0
    restores = s1["restore_hits"] - s0["restore_hits"]
    restore_fraction = restores / preempts if preempts else 1.0
    n_restores = s1["restores"] - s0["restores"]
    restore_ms = (
        (s1["restore_seconds_total"] - s0["restore_seconds_total"])
        / n_restores * 1e3 if n_restores else 0.0
    )
    log(f"[serving] spill tier: {preempts} preemptions, "
        f"{restores} restored ({restore_fraction:.2f} fraction), "
        f"restore={restore_ms:.2f}ms/req, "
        f"spilled={s1['spilled_bytes']['request']/1e3:.0f}KB "
        f"errors={s1['spill_errors']}+{s1['restore_errors']}")
    assert restore_fraction >= 0.9 or preempts == 0, (
        f"preempt-restore fraction {restore_fraction:.2f} below the "
        f"0.9 contract ({restores}/{preempts})"
    )
    print(json.dumps({
        "metric": "serving_spill_restore_ms",
        "value": round(restore_ms, 3),
        "unit": "ms",
    }))
    print(json.dumps({
        "metric": "serving_preempt_restore_fraction",
        "value": round(restore_fraction, 4),
        "unit": "fraction",
    }))

    # ---- tensor-parallel sharded engine (serving/sharding.py): the
    # same mixed workload as the headline row through a tp=2 engine —
    # every program one single-launch SPMD program over the 1 x tp
    # mesh, the KV pool's head dim sharded so per-chip KV bytes drop
    # ~tp-fold. Parity with the single-chip outputs is asserted
    # in-bench (exact-mode numerics). Skips cleanly when the backend
    # exposes one device (the normal single-chip CPU smoke; force more
    # with --xla_force_host_platform_device_count).
    import jax as _jax

    tp = 2 if len(_jax.devices()) >= 2 else 1
    if tp == 1:
        log("[serving] tensor-parallel row skipped: one device visible")
        for metric in ("serving_tp_tokens_per_s",
                       "serving_tp_kv_bytes_per_chip"):
            print(json.dumps({"metric": metric, "skipped": True}))
    else:
        eng_tp = Engine(model, EngineConfig(
            max_batch_slots=slots, max_model_len=mml,
            page_size=16 if on_tpu else 8, tp_degree=tp,
        ))
        eng_tp.generate(prompts, params)    # compile + warm
        t0 = time.perf_counter()
        outs_tp = eng_tp.generate(prompts, params)
        dt_tp = time.perf_counter() - t0
        assert ([o.token_ids for o in outs_tp]
                == [o.token_ids for o in outs]), "tp broke parity"
        tp_tps = sum(len(o.token_ids) for o in outs_tp) / dt_tp
        per_chip = eng_tp.pool.bytes_per_token_per_chip()
        single = eng.pool.bytes_per_token()
        log(f"[serving] tensor-parallel tp={tp}: {tp_tps:,.0f} tokens/s "
            f"(single-chip row {tps:,.0f}); KV "
            f"{per_chip:,.0f} B/token/chip vs {single:,.0f} single-chip "
            f"({per_chip / single:.2f}x)")
        print(json.dumps({
            "metric": "serving_tp_tokens_per_s",
            "value": round(tp_tps, 1),
            "unit": "tokens/s",
        }))
        print(json.dumps({
            "metric": "serving_tp_kv_bytes_per_chip",
            "value": round(per_chip, 1),
            "unit": "bytes/token",
        }))
    return tps


def bench_server(paddle, on_tpu):
    """HTTP front door overhead (server row): the SAME mixed workload
    timed in-process (``engine.generate``) and as open-loop concurrent
    ``POST /v1/completions`` arrivals against a :class:`serving.Server`
    fronting the same engine. ``serving_http_tokens_per_s`` is
    end-to-end generated tokens/s through the wire (admission, QoS
    accounting, SSE-less blocking responses, JSON marshalling);
    ``serving_http_overhead_pct`` is the floor-to-floor cost of the
    HTTP layer over the in-process call (the journal row's interleaved
    floor_pair discipline — the driver thread only steps while HTTP
    requests are in flight, so the in-process passes time the bare
    engine)."""
    import http.client
    import threading

    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
    from paddle_tpu.serving.server import Server

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    n_req, slots, mml = (16, 8, 256) if on_tpu else (8, 4, 64)
    engine = Engine(model, EngineConfig(
        max_batch_slots=slots, max_model_len=mml,
        page_size=16 if on_tpu else 8,
    ))
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab_size,
                    int(rng.randint(4, mml // 4))).tolist()
        for _ in range(n_req)
    ]
    n_new = mml // 8
    params = SamplingParams(max_new_tokens=n_new)
    srv = Server(engine, port=0)

    def http_pass():
        total = [0]
        lock = threading.Lock()

        def one(prompt):
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=600,
            )
            try:
                conn.request(
                    "POST", "/v1/completions",
                    body=json.dumps({
                        "prompt": prompt, "max_new_tokens": n_new,
                    }).encode(),
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                body = json.loads(resp.read())
                assert resp.status == 200, body
                with lock:
                    total[0] += body["usage"]["completion_tokens"]
            finally:
                conn.close()

        threads = [
            threading.Thread(target=one, args=(p,)) for p in prompts
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, total[0]

    def inproc_pass():
        t0 = time.perf_counter()
        outs = engine.generate(prompts, params)
        dt = time.perf_counter() - t0
        return dt, sum(len(o.token_ids) for o in outs)

    try:
        inproc_pass()   # warm programs
        http_pass()     # warm the wire path (handler threads, parser)
        dt_in = dt_http = None
        toks_http = 0
        for i in range(8 if on_tpu else 12):
            order = ("in", "http") if i % 2 == 0 else ("http", "in")
            for which in order:
                if which == "in":
                    dt, _ = inproc_pass()
                    dt_in = dt if dt_in is None else min(dt_in, dt)
                else:
                    dt, toks = http_pass()
                    if dt_http is None or dt < dt_http:
                        dt_http, toks_http = dt, toks
        overhead_pct = (dt_http - dt_in) / dt_in * 100.0
        tps = toks_http / dt_http
        m = srv.metrics
        log(f"[server] http front door: {tps:,.0f} tokens/s "
            f"({dt_http:.3f}s vs {dt_in:.3f}s in-process -> "
            f"{overhead_pct:+.2f}%; {m.requests} requests, "
            f"{m.responses['2xx']} 2xx)")
        print(json.dumps({
            "metric": "serving_http_tokens_per_s",
            "value": round(tps, 1),
            "unit": "tokens/s",
        }))
        print(json.dumps({
            "metric": "serving_http_overhead_pct",
            "value": round(overhead_pct, 2),
            "unit": "percent",
        }))
    finally:
        srv.close()


def bench_fleet(paddle, on_tpu):
    """Replica-failover recovery (fleet row): ``fleet_failover_ms`` is
    the kill-to-first-recovered-token wall clock — an injected
    ``serving.replica`` fault kills one of two replicas mid-decode, its
    in-flight requests are re-enqueued on the survivor (deterministic
    re-prefill), and the clock stops when the first failed-over request
    produces its next token. This is the serving-side RTO term next to
    the checkpoint-restore one measured by the [resilience] row.
    ``fleet_scale_up_ms`` / ``fleet_shrink_migration_ms`` time the
    elastic path: autoscaler burn-signal-to-first-token on a freshly
    placed replica (warm cache) and scale_down drain-to-last-migrated-
    token (journal-backed migration + re-prefill on a survivor)."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.resilience import FaultSpec, faults
    from paddle_tpu.serving import (
        EngineConfig, Fleet, FleetConfig, SamplingParams,
    )

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    n_req, slots, mml = (16, 8, 512) if on_tpu else (8, 4, 64)
    fleet = Fleet(model, EngineConfig(
        max_batch_slots=slots, max_model_len=mml,
        page_size=16 if on_tpu else 8,
    ), FleetConfig(num_replicas=2, analysis_check=None))
    rng = np.random.RandomState(0)
    prompts = [
        rng.randint(1, cfg.vocab_size, rng.randint(4, mml // 8)).tolist()
        for _ in range(n_req)
    ]
    params = SamplingParams(max_new_tokens=mml // 8)

    t0 = time.perf_counter()
    fleet.generate(prompts, params)   # warm both replicas' programs
    log(f"[fleet] compile+first run (2 replicas): "
        f"{time.perf_counter()-t0:.1f}s")
    spec = FaultSpec(
        RuntimeError("bench kill"),
        when=lambda c: (c.get("phase") == "step"
                        and c.get("replica") == "r0"),
        at=4,  # a few steps in: r0 holds in-flight decodes
    )
    with faults.inject({"serving.replica": spec}):
        outs = fleet.generate(prompts, params)
    m = fleet.metrics
    recovery = m.failover_recovery_s
    if m.failovers != 1 or recovery is None:
        raise RuntimeError(
            f"fleet bench did not exercise a failover (failovers="
            f"{m.failovers}, recovery={recovery})"
        )
    failover_ms = recovery * 1e3
    n_tokens = sum(len(o.token_ids) for o in outs)
    log(f"[fleet] {n_req} reqs x 2 replicas x {slots} slots: kill at "
        f"step 4 -> {m.failover_requests} requests failed over, "
        f"first recovered token {failover_ms:.1f}ms after detection "
        f"({n_tokens} tokens served, hedges={m.hedges_started})")
    print(json.dumps({
        "metric": "fleet_failover_ms",
        "value": round(failover_ms, 1),
        "unit": "ms",
    }))

    # merged-digest tail under failover: the pull-time merge of both
    # replicas' latency digests (merge == pooled), sampled over the
    # run that just lost a replica mid-decode — the p99 a client
    # actually saw through the kill, not the surviving replica's view
    merged = fleet.merged_latency()
    p99 = merged["ttft"].quantile(0.99)
    log(f"[fleet] merged digest under failover: ttft p50="
        f"{merged['ttft'].quantile(0.5)*1e3:.1f}ms "
        f"p99={p99*1e3:.1f}ms e2e p99="
        f"{merged['e2e'].quantile(0.99)*1e3:.1f}ms "
        f"(n={merged['ttft'].count} across "
        f"{sum(1 for s in fleet.replicas if s.engine is not None)} "
        f"replicas)")
    print(json.dumps({
        "metric": "fleet_merged_ttft_p99_ms",
        "value": round(p99 * 1e3, 1),
        "unit": "ms",
    }))

    # ---- crash replay: kill-to-first-recovered-token through the
    # durable request journal + warm compile cache. A journaled fleet
    # is abandoned mid-decode (no shutdown hook runs — byte-for-byte
    # the disk state a SIGKILL leaves); the clock runs from the
    # restarted fleet's construction (manifest replay, journal replay,
    # re-admission) to the first token a recovered request produces.
    import shutil
    import tempfile

    root = tempfile.mkdtemp(prefix="paddle_tpu_crash_bench_")
    try:
        jdir = os.path.join(root, "wal")
        ecfg_j = EngineConfig(
            max_batch_slots=slots, max_model_len=mml,
            page_size=16 if on_tpu else 8,
            compile_cache=os.path.join(root, "cc"),
        )
        fcfg = FleetConfig(
            num_replicas=1, analysis_check=None, journal_dir=jdir,
        )
        t0 = time.perf_counter()
        f1 = Fleet(model, ecfg_j, fcfg)
        log(f"[fleet] journaled fleet cold build: "
            f"{time.perf_counter()-t0:.1f}s")
        reqs = [f1.add_request(p, params) for p in prompts]
        for _ in range(6):
            f1.step()   # mid-decode: requests carry tokens
        del f1          # the "kill": nothing flushes beyond the WAL
        cursors = None
        t0 = time.perf_counter()
        f2 = Fleet(model, ecfg_j, fcfg)
        cursors = {
            fr.request_id: len(fr.request.output_token_ids)
            for fr in f2._pending
        }
        recovered_ms = None
        for _ in range(10000):
            f2.step()
            if any(
                len(d.request.output_token_ids)
                > cursors.get(d.fleet_req.request_id, 0)
                for d in f2._routes.values()
            ):
                recovered_ms = (time.perf_counter() - t0) * 1e3
                break
        if recovered_ms is None or not cursors:
            raise RuntimeError(
                f"crash-replay bench recovered nothing "
                f"(replayed={f2.metrics.journal_replayed})"
            )
        while f2.has_unfinished():
            f2.step()
        eng2 = f2.replica("r0").engine
        log(f"[fleet] crash replay: {f2.metrics.journal_replayed} "
            f"requests from the journal, first recovered token "
            f"{recovered_ms:.1f}ms after restart began "
            f"(compiles={eng2.metrics.prefill_compiles}"
            f"+{eng2.metrics.decode_compiles} — warm cache)")
        print(json.dumps({
            "metric": "fleet_crash_replay_ms",
            "value": round(recovered_ms, 1),
            "unit": "ms",
        }))
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # ---- elastic scaling (placement plans): ``fleet_scale_up_ms`` is
    # burn-signal-to-first-token — the wall clock from the sustained
    # SLO burn flipping to the first token the autoscaler-spawned
    # replica serves through the warm compile cache (its slice's
    # programs pre-serialized, zero fresh traces).
    # ``fleet_shrink_migration_ms`` is drain-to-last-migrated-token —
    # scale_down() journaling + re-admitting the victim's in-flight
    # requests, until every one of them has produced its next token on
    # a surviving replica (re-prefill included). Needs 3 tp=2 slices;
    # skips below 6 visible devices.
    import jax as _jax

    if len(_jax.devices()) < 6:
        log("[fleet] elastic row skipped: needs >= 6 devices "
            "(3 tp=2 slices; force with "
            "--xla_force_host_platform_device_count)")
        for metric in ("fleet_scale_up_ms", "fleet_shrink_migration_ms"):
            print(json.dumps({"metric": metric, "skipped": True}))
        return failover_ms
    from paddle_tpu.observability.latency import SLOConfig
    from paddle_tpu.serving import PlacementPlan, ScalingPolicy

    root = tempfile.mkdtemp(prefix="paddle_tpu_elastic_bench_")
    try:
        ecfg_e = EngineConfig(
            max_batch_slots=slots, max_model_len=mml,
            page_size=16 if on_tpu else 8, tp_degree=2,
            compile_cache=os.path.join(root, "cc"),
            slo=SLOConfig(ttft_p99_ms=1.0, tpot_p99_ms=1.0,
                          window_s=60.0, min_samples=4),
        )
        # pre-warm the expansion slice's programs: the scale-up figure
        # measures the warm path (the cold path is the [compilecache]
        # row's cold build)
        from paddle_tpu.serving import Engine as _Engine

        ecfg_w = EngineConfig(
            max_batch_slots=slots, max_model_len=mml,
            page_size=16 if on_tpu else 8, tp_degree=2,
            devices=[4, 5], compile_cache=os.path.join(root, "cc"),
        )
        t0 = time.perf_counter()
        warm_eng = _Engine(model, ecfg_w)
        warm_eng.generate(prompts[:2], params)
        del warm_eng
        log(f"[fleet] expansion slice pre-warm: "
            f"{time.perf_counter()-t0:.1f}s")
        f3 = Fleet(model, ecfg_e, FleetConfig(
            num_replicas=2,
            placement=PlacementPlan(tp_degree=2),
            scaling=ScalingPolicy(
                min_replicas=2, max_replicas=3, up_hold_s=0.0,
                down_hold_s=1e9, cooldown_s=1e9,
            ),
            analysis_check=None,
        ))
        f3.generate(prompts, params)   # warm r0/r1, steady state
        reqs = [f3.add_request(p, params) for p in prompts]
        # the burn signal flips now; the next step's autoscaler tick
        # spawns r2 and the open-loop arrival stream below routes onto
        # it (least-loaded) the moment it joins
        t0 = time.perf_counter()
        for s in f3.replicas:
            for _ in range(6):
                s.engine.slo.record(ttft_s=1.0)
        scale_up_ms = None
        for i in range(10000):
            f3.step()
            reqs.append(
                f3.add_request(prompts[i % len(prompts)], params)
            )
            if any(
                d.replica == "r2" and d.request.output_token_ids
                for d in f3._routes.values()
            ):
                scale_up_ms = (time.perf_counter() - t0) * 1e3
                break
        if scale_up_ms is None or f3.metrics.scale_ups != 1:
            raise RuntimeError(
                f"elastic bench did not scale up (scale_ups="
                f"{f3.metrics.scale_ups})"
            )
        new_eng = f3.replica("r2").engine
        fresh = (new_eng.metrics.prefill_compiles
                 + new_eng.metrics.decode_compiles)
        log(f"[fleet] scale-up burn-signal-to-first-token: "
            f"{scale_up_ms:.1f}ms (replica r2 on devices "
            f"{new_eng.tp.device_ids}, fresh traces={fresh})")
        print(json.dumps({
            "metric": "fleet_scale_up_ms",
            "value": round(scale_up_ms, 1),
            "unit": "ms",
        }))
        while f3.has_unfinished():
            f3.step()

        # forced shrink: migrate the most-loaded replica's in-flight
        # requests and clock until the last migrated request produces
        # its next token on a survivor
        reqs = [f3.add_request(p, params) for p in prompts]
        for _ in range(4):
            f3.step()
        victim = max(
            (s for s in f3.replicas if s.engine is not None),
            key=lambda s: s.load(),
        )
        moving = {
            d.fleet_req.request_id: len(d.request.output_token_ids)
            for d in f3._routes.values()
            if d.replica == victim.name and not d.cancelled
            and not d.finished
        }
        t0 = time.perf_counter()
        released = f3.scale_down(replica=victim.name)
        if released is None or not moving:
            raise RuntimeError(
                f"elastic bench shrink moved nothing "
                f"(migrated={f3.metrics.requests_migrated})"
            )
        shrink_ms = None
        done_rids = set()
        for _ in range(10000):
            for out in f3.step():
                done_rids.add(out.request_id)
            if all(
                rid in done_rids or any(
                    d.fleet_req.request_id == rid
                    and len(d.request.output_token_ids) > cur
                    for d in f3._routes.values()
                )
                for rid, cur in moving.items()
            ):
                shrink_ms = (time.perf_counter() - t0) * 1e3
                break
        if shrink_ms is None:
            raise RuntimeError("elastic bench shrink never drained")
        log(f"[fleet] shrink drain-to-last-migrated-token: "
            f"{shrink_ms:.1f}ms ({len(moving)} in-flight requests "
            f"migrated off {victim.name}, "
            f"{f3.metrics.requests_migrated} total)")
        print(json.dumps({
            "metric": "fleet_shrink_migration_ms",
            "value": round(shrink_ms, 1),
            "unit": "ms",
        }))
        while f3.has_unfinished():
            f3.step()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return failover_ms


def bench_compilecache(paddle, on_tpu):
    """Warm-restart latency (compilecache row): ``cc_warm_restart_ms``
    is the engine kill→ready wall clock with a warm persistent compile
    cache — the second ``Engine`` build replays its warmup manifest
    from disk (AOT executables, zero fresh traces) instead of paying
    the trace+XLA-compile cost the cold figure shows. This is the fixed
    cost every fleet replica restart and rolling weight reload saves."""
    import shutil
    import tempfile

    from paddle_tpu import compilecache
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    slots, mml = (8, 512) if on_tpu else (4, 64)
    root = tempfile.mkdtemp(prefix="paddle_tpu_cc_bench_")
    try:
        ecfg = EngineConfig(
            max_batch_slots=slots, max_model_len=mml,
            page_size=16 if on_tpu else 8, compile_cache=root,
        )
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8]]
        params = SamplingParams(max_new_tokens=4)

        t0 = time.perf_counter()
        eng = Engine(model, ecfg)
        eng.generate(prompts, params)
        cold_s = time.perf_counter() - t0
        compiles = (eng.metrics.prefill_compiles
                    + eng.metrics.decode_compiles)

        # "kill": drop the engine; the cache + manifest survive on disk
        del eng
        t0 = time.perf_counter()
        eng = Engine(model, ecfg)   # manifest replay — ready for traffic
        warm_build_s = time.perf_counter() - t0
        eng.generate(prompts, params)
        warm_total_s = time.perf_counter() - t0
        warm_compiles = (eng.metrics.prefill_compiles
                         + eng.metrics.decode_compiles)
        m = compilecache.resolve(root).metrics
        if warm_compiles or m.fallbacks:
            log(f"[compilecache] WARNING: warm restart was not trace-"
                f"free (compiles={warm_compiles} "
                f"fallbacks={m.fallbacks} store_errors={m.store_errors})")
        warm_ms = warm_build_s * 1e3
        log(f"[compilecache] cold build+first-run {cold_s:.1f}s "
            f"({compiles} compiles, {m.bytes_written/1e6:.1f}MB "
            f"persisted) -> warm restart {warm_ms:.0f}ms to ready "
            f"({warm_total_s:.2f}s incl. first tokens; "
            f"{m.hits} AOT loads, {warm_compiles} compiles, "
            f"{cold_s/max(warm_build_s, 1e-9):.0f}x)")
        print(json.dumps({
            "metric": "cc_warm_restart_ms",
            "value": round(warm_ms, 1),
            "unit": "ms",
        }))
        print(json.dumps({
            "metric": "cc_cold_build_s",
            "value": round(cold_s, 2),
            "unit": "s",
        }))
        return warm_ms
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_resilience(paddle, on_tpu):
    """Failure-recovery time (resilience row): checkpoint a model-sized
    state dict twice, tear the newest write, and measure kill-and-restore
    — the wall clock from 'process restarts' to 'weights verified and in
    memory from the last verified checkpoint' (fallback path included).
    This is the RTO term of the serving north-star: how long a replica
    is dark after a crash."""
    import shutil
    import tempfile

    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict,
    )

    rng = np.random.RandomState(0)
    n_arrays, mb_each = (16, 8) if on_tpu else (8, 2)
    sd = {
        f"layer{i}.w": rng.rand(mb_each * 128, 2048).astype("float32")
        for i in range(n_arrays)
    }
    total_mb = sum(v.nbytes for v in sd.values()) / 1e6
    root = tempfile.mkdtemp(prefix="bench_resilience_")
    try:
        t0 = time.perf_counter()
        save_state_dict(sd, root, keep_last_k=2)
        save_ms = (time.perf_counter() - t0) * 1e3
        save_state_dict(sd, root, keep_last_k=2)
        # tear the newest checkpoint (simulated crash mid-write)
        victim = os.path.join(root, "ckpt-00000002", "data.npz")
        with open(victim, "r+b") as f:
            f.seek(512)
            f.write(b"\x00" * 4096)
        target = {k: np.zeros_like(v) for k, v in sd.items()}
        t0 = time.perf_counter()
        load_state_dict(target, root)
        recover_ms = (time.perf_counter() - t0) * 1e3
        ok = np.array_equal(
            np.asarray(target["layer0.w"].numpy()), sd["layer0.w"]
        )
        log(f"[resilience] {total_mb:.0f}MB state: verified save "
            f"{save_ms:.0f}ms, kill-and-restore (w/ corrupt-latest "
            f"fallback) {recover_ms:.0f}ms, bits_ok={ok}")
        print(json.dumps({
            "metric": "resilience_recover_ms",
            "value": round(recover_ms, 1),
            "unit": "ms",
        }))
        return recover_ms
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_train_resume(paddle, on_tpu):
    """Preemption-recovery time (train_resume row): run a smoke
    training job under the elastic TrainLoop, take the emergency
    checkpoint a SIGTERM would trigger (``train_emergency_ckpt_ms`` —
    the window a preemption notice must leave open), then measure
    kill-to-first-resumed-step: a freshly constructed incarnation
    restoring the full TrainState (model + optimizer + RNG streams +
    mid-epoch dataloader cursor) and completing its first step
    (``train_resume_ms``). Process boot + import cost is the
    [compilecache] warm-restart row's business, not this one's."""
    import shutil
    import tempfile

    from paddle_tpu.io import (
        BatchSampler, DataLoader, RandomSampler, TensorDataset,
    )
    from paddle_tpu.resilience import TrainLoop, TrainState

    hidden = 512 if on_tpu else 32

    def build():
        paddle.seed(0)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(hidden, hidden), paddle.nn.ReLU(),
            paddle.nn.Linear(hidden, hidden),
        )
        opt = paddle.optimizer.Adam(
            learning_rate=1e-3, parameters=model.parameters()
        )
        data = np.random.RandomState(7).rand(64, hidden).astype(
            "float32"
        )
        ds = TensorDataset([data])
        loader = DataLoader(ds, batch_sampler=BatchSampler(
            sampler=RandomSampler(ds, seed=3), batch_size=8,
        ))
        state = TrainState(model=model, optimizer=opt,
                           dataloader=loader)

        def step_fn(batch, st):
            x = batch[0]
            loss = ((model(x) - x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return state, step_fn

    root = tempfile.mkdtemp(prefix="bench_train_resume_")
    try:
        state, step_fn = build()
        TrainLoop(state, step_fn, root).run(6)  # warm, then "preempt"
        emergency_ms = state.save(root, emergency=True) * 1e3
        killed_at = state.step
        state2, step2 = build()
        t0 = time.perf_counter()
        TrainLoop(state2, step2, root).run(killed_at + 1)
        resume_ms = (time.perf_counter() - t0) * 1e3
        assert state2.step == killed_at + 1
        log(f"[train_resume] h={hidden} smoke: emergency ckpt "
            f"{emergency_ms:.0f}ms, kill-to-first-resumed-step "
            f"{resume_ms:.0f}ms (restore incl. RNG + data cursor)")
        print(json.dumps({
            "metric": "train_emergency_ckpt_ms",
            "value": round(emergency_ms, 1),
            "unit": "ms",
        }))
        print(json.dumps({
            "metric": "train_resume_ms",
            "value": round(resume_ms, 1),
            "unit": "ms",
        }))
        return resume_ms
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_analysis(paddle, on_tpu):
    """Static-analyzer overhead (analysis row): wall-time of
    ``analysis.check`` on the serving decode step — the cost of the
    Engine warmup gate (EngineConfig(analysis_check=...)). Pure host
    work (trace + passes, nothing executes), so the row is chip-load
    independent; it is tracked so analyzer regressions show up next to
    the serving numbers they gate."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    eng = Engine(model, EngineConfig(
        max_batch_slots=8 if on_tpu else 2,
        max_model_len=512 if on_tpu else 32,
        page_size=16 if on_tpu else 8,
        # the full 7-program family: prefill_ext per bucket + the COW
        # copy + the speculative verify join decode + prefill — what
        # the L3 compiled-family number below actually sweeps
        enable_prefix_cache=True,
        prefill_chunk_tokens=256 if on_tpu else 16,
        speculate_tokens=2,
    ))
    report = eng.check_decode(mode="error")  # warm (imports, caches)
    t0 = time.perf_counter()
    report = eng.check_decode(mode="error")
    dt_ms = (time.perf_counter() - t0) * 1e3
    log(f"[analysis] decode-step check: {dt_ms:.0f}ms "
        f"({len(report.findings)} findings, h={cfg.hidden_size} "
        f"L={cfg.num_hidden_layers})")
    print(json.dumps({
        "metric": "analysis_decode_check_ms",
        "value": round(dt_ms, 1),
        "unit": "ms",
    }))
    # L3 (census + per-chip memory) over the whole program family:
    # the first call pays the isolated AOT compiles and memoizes the
    # summaries; the steady-state number is rule re-evaluation over
    # stored summaries — what EVERY later gate (and a warm restart)
    # pays. Both are reported; the steady-state one is the metric.
    t0 = time.perf_counter()
    eng.check_compiled_programs()  # cold: compiles + extracts
    cold_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    r3 = eng.check_compiled_programs()
    l3_ms = (time.perf_counter() - t0) * 1e3
    progs = len(eng.metrics.program_bytes)
    log(f"[analysis] compiled-family check: {l3_ms:.1f}ms warm / "
        f"{cold_ms:.0f}ms cold ({progs} programs, "
        f"{len(r3.findings)} findings)")
    print(json.dumps({
        "metric": "analysis_compiled_check_ms",
        "value": round(l3_ms, 1),
        "unit": "ms",
    }))
    return dt_ms


def bench_observability(paddle, on_tpu):
    """Telemetry cost (observability row): ``obs_scrape_ms`` is the
    wall clock of one GET /metrics against a live engine's registry
    view (what a Prometheus scraper pays), and stderr logs the decode
    step-time overhead of running the serving loop WITH the scrape
    endpoint up and a scraper hammering it vs without — the < 2%
    acceptance number. Telemetry's per-step hooks (span + compile-log
    watch) are always on in both runs; what the delta measures is the
    cost of actually being observed."""
    import threading
    import urllib.request

    from paddle_tpu import observability as obs
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, EngineConfig, SamplingParams

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=12, num_attention_heads=16,
        max_position_embeddings=2048,
    ) if on_tpu else LlamaConfig.tiny()
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    slots, mml = (8, 512) if on_tpu else (4, 64)
    ecfg = dict(
        max_batch_slots=slots, max_model_len=mml,
        page_size=16 if on_tpu else 8,
    )
    eng = Engine(model, EngineConfig(**ecfg))
    rng = np.random.RandomState(0)

    def run_steps(n_steps, engine=None):
        """Keep every slot busy and time n_steps decode steps."""
        e = eng if engine is None else engine
        new = mml // 2
        for _ in range(slots):
            e.add_request(
                rng.randint(1, cfg.vocab_size, 8).tolist(),
                SamplingParams(max_new_tokens=new),
            )
        for _ in range(2):
            e.step()   # admit + warm
        t0 = time.perf_counter()
        for _ in range(n_steps):
            e.step()
        dt = (time.perf_counter() - t0) / n_steps
        while e.has_unfinished():   # drain
            e.step()
        return dt

    steps = 64 if on_tpu else 16
    run_steps(steps)                       # compile + settle
    base = min(run_steps(steps) for _ in range(3))

    # step-observatory cost: the same loop with stepstats disabled is
    # the floor; the default-on engine must stay within the <2% budget
    # (the hot path is host-side attribute arithmetic only)
    eng_off = Engine(model, EngineConfig(**ecfg, stepstats=False))
    run_steps(steps, eng_off)              # compile + settle
    floor = min(run_steps(steps, eng_off) for _ in range(3))
    stats_overhead = (base - floor) / floor if floor else 0.0
    assert stats_overhead < 0.02, (
        f"step observatory overhead {stats_overhead * 100:+.2f}% "
        f"breaches the <2% budget "
        f"({floor * 1e3:.3f}ms -> {base * 1e3:.3f}ms)"
    )

    srv = obs.start_scrape_server()
    stop = threading.Event()

    scrape_errors = [0]

    def scraper():
        # 4 Hz is already ~100x a production Prometheus cadence; a
        # tighter loop measures CPU starvation of the host feed thread
        # on small boxes, not telemetry cost. One transient failure
        # must not silently kill the load thread — an unloaded
        # "under scrape load" measurement would report fiction.
        while not stop.is_set():
            try:
                urllib.request.urlopen(
                    srv.url + "/metrics", timeout=10
                ).read()
            except Exception:
                scrape_errors[0] += 1
            time.sleep(0.25)

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    try:
        observed = min(run_steps(steps) for _ in range(3))
        scrape_ms = []
        for _ in range(20):
            t0 = time.perf_counter()
            urllib.request.urlopen(srv.url + "/metrics", timeout=10).read()
            scrape_ms.append((time.perf_counter() - t0) * 1e3)
        scrape_ms.sort()
        obs_scrape_ms = scrape_ms[len(scrape_ms) // 2]
    finally:
        stop.set()
        t.join(timeout=5)
        srv.close()
    # mixed 32-request workload (heterogeneous prompt/output lengths)
    # on the observed engine: goodput / decode occupancy / step p99
    # straight off the step-observatory ring
    st = eng.stepstats
    for n in rng.choice([4, 8, 12], 32):
        eng.add_request(
            rng.randint(1, cfg.vocab_size, int(n)).tolist(),
            SamplingParams(max_new_tokens=max(2, mml // 8)),
        )
    while eng.has_unfinished():
        eng.step()
    goodput = st.goodput_fraction()
    walls = sorted(s["wall_ms"] for s in st.samples)
    step_p99_ms = walls[min(int(len(walls) * 0.99), len(walls) - 1)]
    occs = [
        s["occupancy"] for s in st.samples
        if any(p == "decode" for p, _ in s["launches"])
    ]
    decode_occ = sum(occs) / len(occs) if occs else 0.0
    overhead = (observed - base) / base if base else 0.0
    log(f"[observability] decode step {base*1e3:.2f}ms -> "
        f"{observed*1e3:.2f}ms under scrape load "
        f"({overhead*100:+.2f}% overhead), stepstats "
        f"{stats_overhead*100:+.2f}% vs off-floor {floor*1e3:.2f}ms, "
        f"/metrics scrape {obs_scrape_ms:.2f}ms, "
        f"scrape_errors={scrape_errors[0]}, "
        f"goodput={goodput:.3f} decode_occupancy={decode_occ:.2f} "
        f"step_p99={step_p99_ms:.2f}ms, "
        f"retraces_after_warmup="
        f"{obs.jit_events.retraces_after_warmup():.0f}")
    print(json.dumps({
        "metric": "obs_scrape_ms",
        "value": round(obs_scrape_ms, 2),
        "unit": "ms",
    }))
    print(json.dumps({
        "metric": "serving_goodput_fraction",
        "value": round(goodput, 4),
        "unit": "fraction",
    }))
    print(json.dumps({
        "metric": "serving_decode_occupancy",
        "value": round(decode_occ, 4),
        "unit": "fraction",
    }))
    print(json.dumps({
        "metric": "serving_step_p99_ms",
        "value": round(step_p99_ms, 2),
        "unit": "ms",
    }))
    return obs_scrape_ms


ROWS = {
    "llama": lambda p, tpu, peak: bench_llama(p, tpu, peak),
    "decode": lambda p, tpu, peak: bench_decode(p, tpu),
    "serving": lambda p, tpu, peak: bench_serving(p, tpu),
    "server": lambda p, tpu, peak: bench_server(p, tpu),
    "fleet": lambda p, tpu, peak: bench_fleet(p, tpu),
    "moe": lambda p, tpu, peak: bench_moe(p, tpu, peak),
    "kernels": lambda p, tpu, peak: bench_kernels(p, tpu, peak),
    "resnet": lambda p, tpu, peak: bench_resnet(p, tpu),
    "dit": lambda p, tpu, peak: bench_dit(p, tpu),
    "compilecache": lambda p, tpu, peak: bench_compilecache(p, tpu),
    "resilience": lambda p, tpu, peak: bench_resilience(p, tpu),
    "train_resume": lambda p, tpu, peak: bench_train_resume(p, tpu),
    "analysis": lambda p, tpu, peak: bench_analysis(p, tpu),
    "observability": lambda p, tpu, peak: bench_observability(p, tpu),
}


ROW_ORDER = (
    "llama", "decode", "serving", "server", "fleet", "compilecache",
    "resilience", "train_resume", "analysis", "observability", "kernels",
    "moe", "resnet", "dit",
)
HEADLINE = "llama_pretrain_mfu_1chip"


def _device_info():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _probe():
    """Child body: report the device JAX sees; non-zero unless a TPU."""
    info = _device_info()
    print(json.dumps(info), flush=True)
    return 0 if info["platform"] == "tpu" else 1


def _run_row(name):
    """Child body: one row, on the chip, under the shared compile cache."""
    import paddle_tpu as paddle
    from paddle_tpu.compilecache import enable_persistent_cache
    from paddle_tpu.core.device import device_peaks, on_tpu

    cache = enable_persistent_cache()
    info = _device_info()
    log(f"[{name}] device={info} compile_cache={cache}")
    if not on_tpu():
        log(f"[{name}] refusing to measure on {info['platform']!r}")
        return 1
    value = ROWS[name](paddle, True, device_peaks().bf16_flops)
    if name == "llama":
        print(json.dumps({"metric": HEADLINE,
                          "value": round(value * 100, 2)}), flush=True)
    return 0


def _child(*argv, timeout=900):
    """Run this file in a child; (returncode, metric/info dicts it printed
    on stdout)."""
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *argv],
        stdout=subprocess.PIPE, text=True, timeout=timeout,
    )
    found = []
    for line in r.stdout.splitlines():
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict):
            found.append(d)
    return r.returncode, found


def main():
    rc, found = _child("--probe")
    device = found[-1] if found else None
    if rc != 0:
        log(f"bench: no TPU (probe rc={rc}, device={device}); nothing "
            "is measured off the chip")
        return 1
    only = [n for n in os.environ.get("BENCH_ONLY", "").split(",") if n]
    unknown = sorted(set(only) - set(ROW_ORDER))
    if unknown:
        log(f"bench: unknown rows in BENCH_ONLY: {unknown}")
        return 2
    metrics, failed = {}, []
    for name in ROW_ORDER:
        if only and name not in only:
            continue
        try:
            rc, found = _child("--row", name)
        except subprocess.TimeoutExpired:
            rc, found = "timeout", []
        for d in found:
            if "metric" in d:
                metrics[d["metric"]] = d["value"]
        if rc != 0:
            log(f"[{name}] FAILED (rc={rc})")
            failed.append(name)
    print(json.dumps({
        "metric": HEADLINE,
        "value": metrics.pop(HEADLINE, None),
        "unit": "%",
        **metrics,
        "device": device,
        "failed_rows": failed,
    }))
    return 1 if failed else 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--probe"]:
        sys.exit(_probe())
    if sys.argv[1:2] == ["--row"] and len(sys.argv) == 3:
        sys.exit(_run_row(sys.argv[2]))
    sys.exit(main())
