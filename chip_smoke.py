"""chip_smoke.py — does the system still start, compile and finish on the chip?

One process drives the two main paths once, through the entry points a user
calls, at the full width of the model the repo carries for this purpose
(vocab 32000, h=2048, ffn 5632, 12 layers, 16 heads x 128, bf16, ~745M
parameters; weights random from a seed):

  device   platform / device_kind / count, versions, compile-cache root.
           Not a TPU -> exit 1 before anything is built.
  kernels  flash_attention (fwd+bwd), paged_attention (bf16 and int8 pool),
           grouped_matmul (bf16 and int8 rhs): compiled by Mosaic, not
           interpreted, and equal to their XLA fallbacks within a tolerance.
  hybrid   gated_delta_rule (fwd+bwd kernels) against its jax.numpy chunked
           form, and an expert layer that holds 8 of 64 experts
           (incubate.moe.MoELayer(held=), grouped_matmul with its dlhs and
           drhs kernels) against the same layer on the XLA path, in one
           pass over its rows and in four.
  state_space  mamba2_ssd (fwd+bwd kernels) at the shape the Granite cell
           runs it ([2, 8192, 4096], 64 heads of 64, state 128) against its
           jax.numpy chunked form and against the recurrence token by
           token: the output and the gradients of all six operands.
  latent   mla_attention (fwd+bwd kernels) at 32 heads of 128 + 64 against
           plain attention, the sigmoid router's held dispatch, and one MLA
           block under distributed.recompute: the segment keeps the forward
           kernel's output and log-sum-exp, so its value and gradient
           compile to one mla_attention_fwd call.
  train    AdamW(multi_precision) + jit.TrainStep (donation on) fed by the
           forked-worker DataLoader, seq 2048: loss finite and falling,
           traced and compiled once, parameters on the TPU.
  serve    serving.Engine (decode_kernel="auto", prefix cache, chunked
           prefill, AOT compile cache) behind serving.Server: HTTP
           completions, one streamed, two sharing a prefix; one greedy
           request equals model.generate; the decode program holds the
           Pallas custom call; a second Engine over the same cache
           directory compiles nothing and answers identically.
  4 chips  (only with >= 4 devices) the same trainer under
           dist.parallelize(dp=2 x mp=2) and the same engine at
           tp_degree=4: every device holds weight and KV shards, results
           agree with the one-chip phases.

Every phase evaluates all of its checks, prints them, and raises if one
failed; nothing is caught, so the first failed phase ends the run. The last
stdout line is one JSON object with exactly the keys "ok" and "device"
(platform, kind, count as JAX reports them), printed only when every phase
passed. The timings printed along the way are smoke output, not metrics.

    python3 chip_smoke.py
"""
from __future__ import annotations

import gc
import http.client
import json
import os
import re
import sys
import threading
import time

import numpy as np

WIDTH = dict(
    vocab_size=32000, hidden_size=2048, intermediate_size=5632,
    num_hidden_layers=12, num_attention_heads=16,
    max_position_embeddings=2048,
)
SEQ, HEADS, HEAD_DIM, PAGE = 2048, 16, 128, 16
TRAIN_BATCH, TRAIN_STEPS = 2, 4


def say(msg):
    print(msg, flush=True)


class Checks:
    """One phase's checks: all evaluated and printed, then the phase
    fails if any did."""

    def __init__(self, phase):
        self.phase, self.failed = phase, []
        self.t0 = time.perf_counter()
        say(f"== {phase}")

    def check(self, name, ok, detail=""):
        say(f"  [{'ok' if ok else 'FAIL'}] {name}" +
            (f" — {detail}" if detail else ""))
        if not ok:
            self.failed.append(name)

    def done(self):
        if self.failed:
            raise SystemExit(
                f"chip_smoke: phase {self.phase!r} FAILED: {self.failed}"
            )
        say(f"== {self.phase} passed "
            f"({time.perf_counter() - self.t0:.1f}s)")


# ---------------------------------------------------------------- device
def phase_device():
    import jax
    import jaxlib

    from paddle_tpu.compilecache import enable_persistent_cache

    cache_root = enable_persistent_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "absent"
    say(f"== device {device} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_version} "
        f"compile_cache={cache_root}")
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX reports platform {dev.platform!r}, not a "
            "TPU; nothing is built off the chip"
        )
    return device, cache_root


# --------------------------------------------------------------- kernels
def _rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(1.0, np.max(np.abs(ref))))


def _mosaic(fn, *args):
    """The jitted fn and whether its lowering carries the Mosaic call."""
    import jax

    jitted = jax.jit(fn)
    return jitted, "tpu_custom_call" in jitted.lower(*args).as_text()


def phase_kernels():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    from paddle_tpu.kernels.pallas.grouped_matmul import (
        grouped_matmul, grouped_matmul_xla,
    )
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_attention, paged_attention_xla, quantize_tokens,
    )
    from paddle_tpu.ops.impl.nn_ops import scaled_dot_product_attention
    from paddle_tpu.quantization import weight_quantize_grouped

    c = Checks("kernels")
    c.check("Pallas kernels are compiled, not interpreted",
            not _compat.interpret_mode())
    bf16, tol = jnp.bfloat16, 2e-2
    key = jax.random.key(0)

    # flash attention, forward and backward, against the math sdpa (an
    # explicit causal mask keeps sdpa off the kernel route)
    kq, kk, kv, kw, key = jax.random.split(key, 5)
    shape = (2, SEQ, HEADS, HEAD_DIM)
    q, k, v, w = (jax.random.normal(r, shape, bf16)
                  for r in (kq, kk, kv, kw))
    causal = jnp.tril(jnp.ones((SEQ, SEQ), bool))

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def math_loss(q, k, v):
        out = scaled_dot_product_attention(q, k, v, causal)
        return jnp.sum(out.astype(jnp.float32) * w), out

    flash, is_mosaic = _mosaic(
        jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True),
        q, k, v,
    )
    c.check("flash_attention fwd+bwd lowered to tpu_custom_call", is_mosaic)
    tiles = sorted(_compat.flash_blocks())
    c.check("flash_attention's three kernels recorded their tiles",
            len({kernel for kernel, _, _ in tiles}) == 3, str(tiles))
    (_, out), grads = flash(q, k, v)
    (_, ref), ref_grads = jax.jit(jax.value_and_grad(
        math_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    err = _rel_err(out, ref)
    c.check("flash_attention forward == math sdpa", err <= tol,
            f"rel err {err:.2e} (tol {tol})")
    for name, g, rg in zip("qkv", grads, ref_grads):
        err = _rel_err(g, rg)
        c.check(f"flash_attention d{name} == math sdpa", err <= tol,
                f"rel err {err:.2e} (tol {tol})")

    # paged decode attention: a length-0 slot, a one-token slot, full and
    # partial last pages, a full-length slot; pages scattered in the pool
    lengths = jnp.array([0, 1, 15, 16, 17, 700, SEQ - 1, SEQ], jnp.int32)
    batch, pages_per_seq = lengths.shape[0], SEQ // PAGE
    n_pages = batch * pages_per_seq
    kq, kk, kv, kt, key = jax.random.split(key, 5)
    qd = jax.random.normal(kq, (batch, HEADS, HEAD_DIM), bf16)
    pool = (HEADS, n_pages, PAGE, HEAD_DIM)
    kp = jax.random.normal(kk, pool, bf16)
    vp = jax.random.normal(kv, pool, bf16)
    tables = jax.random.permutation(kt, n_pages).reshape(
        batch, pages_per_seq).astype(jnp.int32)
    for label, kpool, vpool in (
        ("bf16 pool", kp, vp),
        ("int8 pool", quantize_tokens(kp), quantize_tokens(vp)),
    ):
        paged, is_mosaic = _mosaic(
            paged_attention, qd, kpool, vpool, tables, lengths)
        c.check(f"paged_attention ({label}) lowered to tpu_custom_call",
                is_mosaic)
        got = paged(qd, kpool, vpool, tables, lengths)
        ref = jax.jit(paged_attention_xla)(qd, kpool, vpool, tables, lengths)
        err = _rel_err(got, ref)
        c.check(f"paged_attention ({label}) == XLA gather path", err <= tol,
                f"rel err {err:.2e} (tol {tol})")
        c.check(f"paged_attention ({label}) length-0 slot is exact zeros",
                not np.asarray(got[0], np.float32).any())

    # ragged grouped matmul at the FFN shape: uneven groups, one empty,
    # boundaries off the 128-row tile
    sizes = jnp.array([1000, 0, 37, 1500, 128, 391, 9, 1031], jnp.int32)
    n, kdim, m = int(sizes.sum()), WIDTH["hidden_size"], \
        WIDTH["intermediate_size"]
    kl, kr, key = jax.random.split(key, 3)
    lhs = jax.random.normal(kl, (n, kdim), bf16)
    rhs = jax.random.normal(kr, (sizes.shape[0], kdim, m), bf16)
    rhs8, scales = (t._data for t in weight_quantize_grouped(rhs))
    for label, r, s in (("bf16", rhs, None), ("int8 rhs", rhs8, scales)):
        gmm, is_mosaic = _mosaic(
            lambda a, b, g, s=s: grouped_matmul(
                a, b, g, rhs_scales=s, impl="pallas"),
            lhs, r, sizes,
        )
        c.check(f"grouped_matmul ({label}) lowered to tpu_custom_call",
                is_mosaic)
        got = gmm(lhs, r, sizes)
        ref = jax.jit(
            lambda a, b, g, s=s: grouped_matmul_xla(a, b, g, s)
        )(lhs, r, sizes)
        err = _rel_err(got, ref)
        c.check(f"grouped_matmul ({label}) == XLA segment path", err <= tol,
                f"rel err {err:.2e} (tol {tol})")
    c.done()


# ---------------------------------------------------------------- hybrid
def phase_hybrid():
    """The kernels and the layer that Qwen3-Next's training path brought:
    through Mosaic, and equal to their jax.numpy forms."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import MoELayer
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas import gated_delta_rule as gdr

    c = Checks("hybrid")
    bf16, tol = jnp.bfloat16, 2e-2
    ks = jax.random.split(jax.random.key(1), 7)
    b, t, hk, hv, d = 2, 1024, 4, 8, 128
    q = jax.random.normal(ks[0], (b, t, hk, d))
    k = jax.random.normal(ks[1], (b, t, hk, d))
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
         ).astype(bf16)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(bf16)
    v, w = (jax.random.normal(r, (b, t, hv, d), bf16) for r in ks[2:4])
    g = -jnp.exp(jax.random.uniform(ks[4], (b, t, hv), minval=-4.0,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, hv)))

    def loss(impl):
        def fn(q, k, v, g, beta):
            out = gdr.gated_delta_rule(q, k, v, g, beta, impl=impl)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 4), has_aux=True)

    kernel, is_mosaic = _mosaic(loss("pallas"), q, k, v, g, beta)
    c.check("gated_delta_rule fwd+bwd lowered to tpu_custom_call", is_mosaic)
    steps = sorted(_compat.gdr_blocks())
    c.check("gated_delta_rule's two kernels recorded their grid step "
            "(kernel, key heads, chunks)",
            {kernel for kernel, _, _ in steps}
            == {"gated_delta_rule_fwd", "gated_delta_rule_bwd"}, str(steps))
    (_, out), grads = kernel(q, k, v, g, beta)
    (_, ref), ref_grads = jax.jit(loss("xla"))(q, k, v, g, beta)
    err = _rel_err(out, ref)
    c.check("gated_delta_rule forward == chunked jax.numpy form",
            err <= tol, f"rel err {err:.2e} (tol {tol})")
    for name, a, r in zip(("q", "k", "v", "g", "beta"), grads, ref_grads):
        err = _rel_err(a, r)
        c.check(f"gated_delta_rule d{name} == chunked jax.numpy form",
                err <= tol, f"rel err {err:.2e} (tol {tol})")
    # the flat form, [B, T, H d] as the kernels read it and the DeltaNet
    # mixer hands it over: the same numbers as the four-dimensional call
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))

    def flat_loss(q, k, v, g, beta):
        out = gdr.gated_delta_rule(q, k, v, g, beta, impl="pallas",
                                   num_k_heads=hk)
        return jnp.sum(out.astype(jnp.float32) * flat(w)), out

    (_, out_f), grads_f = jax.jit(jax.value_and_grad(
        flat_loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
        flat(q), flat(k), flat(v), g, beta)
    worst = max(_rel_err(a, flat(b) if b.ndim == 4 else b)
                for a, b in zip((out_f,) + grads_f, (out,) + grads))
    forms = _compat.gdr_operands()
    c.check("gated_delta_rule on flat operands == on [B, T, H, d], output "
            "and every gradient",
            worst <= 1e-6 and out_f.shape == flat(v).shape,
            f"rel err {worst:.2e}; gdr_operands {forms}")
    c.check("gated_delta_rule recorded both operand forms",
            forms.get("flat", 0) >= 1 and forms.get("heads", 0) >= 1,
            f"gdr_operands {forms}, gdr_blocks {sorted(_compat.gdr_blocks())}")

    # an expert layer holding 8 of 64 experts: kernel path against the
    # XLA path of the same layer, outputs and every gradient
    paddle.seed(0)
    with paddle.nn.initializer.param_init_override(dtype="bfloat16"):
        layer = MoELayer(1024, 64, d_ff=512, k=8, held=(16, 8),
                         router_dtype="float32")
    x = jax.random.normal(ks[6], (2, 2048, 1024), bf16)
    names = [n for n, _ in layer.named_parameters()]

    def layer_loss(arrays, x, impl, rows):
        old = [p._data for p in layer.parameters()]
        for p, a in zip(layer.parameters(), arrays):
            p._data = a
        try:
            with paddle.no_grad():
                flat = paddle.to_tensor(x.reshape(-1, 1024))
                tok, rw, load = paddle.ops.moe_held_dispatch(
                    flat, paddle.ops.moe_router_logits(
                        flat, layer.gate.weight),
                    k=8, start=16, count=8, rows=rows)
                ex = layer.experts
                out = paddle.ops.moe_held_experts(
                    flat, ex.w_gate, ex.w_up, ex.w_down, tok, rw, load,
                    rows=rows, impl=impl)
            return jnp.sum(out._data.astype(jnp.float32) ** 2), (
                out._data, load._data)
        finally:
            for p, a in zip(layer.parameters(), old):
                p._data = a

    arrays = [p._data for p in layer.parameters()]
    one_pass = layer.held_rows(4096)
    run = lambda impl, rows=one_pass: jax.value_and_grad(
        lambda a, x: layer_loss(a, x, impl, rows), argnums=(0, 1),
        has_aux=True)
    kernel, is_mosaic = _mosaic(run("pallas"), arrays, x)
    c.check("held-experts layer lowered to tpu_custom_call", is_mosaic)
    (_, (out, load)), (dw, dx) = kernel(arrays, x)
    (_, (ref, _)), (rdw, rdx) = jax.jit(run("xla"))(arrays, x)
    load = np.asarray(load)
    c.check("8 of 64 experts held: about an eighth of the assignments",
            0.08 < load.sum() / (4096 * 8) < 0.18, f"expert_load {load}")
    err = _rel_err(out, ref)
    c.check("held-experts layer forward == XLA path", err <= tol,
            f"rel err {err:.2e} (tol {tol})")
    err = _rel_err(dx, rdx)
    c.check("held-experts layer dx == XLA path", err <= tol,
            f"rel err {err:.2e} (tol {tol})")
    for name, a, r in zip(names, dw, rdw):
        err = _rel_err(a, r)
        c.check(f"held-experts layer d {name} == XLA path", err <= tol,
                f"rel err {err:.2e} (tol {tol})")
    # the same step worked off in passes of 1024 rows: what a router does
    # that sends this rank more than a pass holds
    passes = -(-int(load.sum()) // 1024)
    (_, (out_p, _)), (dw_p, dx_p) = jax.jit(run("pallas", 1024))(arrays, x)
    c.check("held-experts layer: one pass holds the step, 1024 rows do not",
            int(load.sum()) <= one_pass and passes >= 3,
            f"{int(load.sum())} rows, one pass {one_pass}, {passes} passes")
    worst = max(_rel_err(a, b) for a, b in zip(
        [out_p, dx_p, *dw_p], [out, dx, *dw]))
    c.check(f"held-experts layer in {passes} passes == in one, output and "
            "every gradient", worst <= tol, f"rel err {worst:.2e} (tol {tol})")
    c.done()


# ----------------------------------------------------------- state space
def phase_state_space():
    """The Mamba-2 kernels that granite-4.0-h's training path brought:
    through Mosaic at the cell's own shape, equal to the jax.numpy chunked
    form and to the recurrence."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas import mamba2_ssd as ssd

    c = Checks("state_space")
    bf16, tol = jnp.bfloat16, 2e-2
    b, t, h, p, n = 2, 8192, 64, 64, 128
    ks = jax.random.split(jax.random.key(2), 7)
    # the sizes a seeded model hands the kernel: x, B, C after silu of a
    # convolution, dt = softplus(1 + small), A = -(1 .. 64), D = 1
    x, w = (jax.random.normal(r, (b, t, h * p), bf16) for r in ks[:2])
    bm, cm = (0.5 * jax.random.normal(r, (b, t, n), bf16) for r in ks[2:4])
    dt = jax.nn.softplus(1.0 + 0.5 * jax.random.normal(ks[4], (b, t, h)))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    d = jnp.ones((h,), jnp.float32) + 0.1 * jax.random.normal(ks[5], (h,))
    ops = (x, dt, a, bm, cm, d)

    def loss(fn):
        def run(*ops):
            out = fn(*ops)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.value_and_grad(run, argnums=tuple(range(6)), has_aux=True)

    kernel, is_mosaic = _mosaic(
        loss(lambda *o: ssd.mamba2_ssd(*o, impl="pallas")), *ops)
    c.check("mamba2_ssd fwd+bwd lowered to tpu_custom_call", is_mosaic)
    steps, chunks = sorted(_compat.ssd_blocks()), sorted(_compat.ssd_chunks())
    c.check("mamba2_ssd's two kernels recorded their grid step (kernel, "
            "heads, chunks) and the call its chunk",
            {k for k, _, _ in steps} == {"mamba2_ssd_fwd", "mamba2_ssd_bwd"}
            and bool(chunks), f"{steps} {chunks}")
    (_, out), grads = kernel(*ops)
    names = ("x", "dt", "A", "B", "C", "D")
    for what, fn in (
            ("chunked jax.numpy form",
             lambda *o: ssd.mamba2_ssd(*o, impl="xla")),
            ("recurrence token by token", ssd.recurrent_mamba2_ssd)):
        (_, ref), ref_grads = jax.jit(loss(fn))(*ops)
        err = _rel_err(out, ref)
        c.check(f"mamba2_ssd forward == {what}", err <= tol,
                f"rel err {err:.2e} (tol {tol})")
        for name, got, want in zip(names, grads, ref_grads):
            err = _rel_err(got, want)
            c.check(f"mamba2_ssd d{name} == {what}", err <= tol,
                    f"rel err {err:.2e} (tol {tol})")
    c.done()


# ----------------------------------------------------- latent attention
def phase_latent():
    """What the DeepSeek-V3 block's training path brought: the MLA
    kernels through Mosaic at the cell's widths (32 heads of 128 + 64
    under values of 128, one shared rotary key), equal to plain attention
    forward and backward, the sigmoid router's held dispatch, and what a
    recomputed MLA block keeps (`_compat.recompute_kept()`)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.incubate.moe import MoELayer
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas import flash_attention as fa

    c = Checks("latent")
    bf16, tol = jnp.bfloat16, 2e-2
    b, t, h = 2, 2048, 32
    ks = jax.random.split(jax.random.key(3), 8)
    shapes = ((b, t, h, 128), (b, t, h, 64), (b, t, h, 128), (b, t, 1, 64),
              (b, t, h, 128))
    ops = [jax.random.normal(r, s, bf16) for r, s in zip(ks, shapes)]
    w = jax.random.normal(ks[5], shapes[-1], bf16)

    def loss(fn):
        def run(*ops):
            out = fn(*ops, scale=192 ** -0.5, causal=True)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4), has_aux=True)

    kernel, is_mosaic = _mosaic(loss(
        lambda *a, **kw: fa.mla_attention(*a, impl="pallas", **kw)), *ops)
    c.check("mla_attention fwd+bwd lowered to tpu_custom_call", is_mosaic)
    tiles = sorted(_compat.mla_blocks())
    c.check("the three MLA kernels recorded their tile",
            {k for k, _, _ in tiles} == set(fa.MLA_KERNELS), str(tiles))
    (_, out), grads = kernel(*ops)
    (_, ref), ref_grads = jax.jit(loss(fa.mla_attention_xla))(*ops)
    err = _rel_err(out, ref)
    c.check("mla_attention forward == plain attention", err <= tol,
            f"rel err {err:.2e} (tol {tol})")
    for name, got, want in zip(("q_nope", "q_rope", "k_nope",
                                "k_rope (summed over the heads)", "v"),
                               grads, ref_grads):
        err = _rel_err(got, want)
        c.check(f"mla_attention d {name} == plain attention", err <= tol,
                f"rel err {err:.2e} (tol {tol})")

    # an expert layer holding 16 of 128 behind the sigmoid router with a
    # selection bias: kernel path against the XLA path, output and load
    paddle.seed(0)
    with paddle.nn.initializer.param_init_override(dtype="bfloat16"):
        layer = MoELayer(2048, 128, d_ff=768, k=6, held=(16, 16),
                         router_dtype="float32", scoring="sigmoid",
                         routed_scaling_factor=2.448)
    bias = 0.1 * jax.random.normal(ks[6], (128,))
    layer.gate.e_score_correction_bias._rebind(bias)
    x = paddle.to_tensor(jax.random.normal(ks[7], (4096, 2048), bf16))
    rows = layer.held_rows(4096)

    def routed(impl):
        with paddle.no_grad():
            tok, rw, load = paddle.ops.moe_held_dispatch(
                x, paddle.ops.moe_router_logits(x, layer.gate.weight),
                k=6, start=16, count=16, rows=rows, scoring="sigmoid",
                bias=layer.gate.e_score_correction_bias, scale=2.448)
            ex = layer.experts
            out = paddle.ops.moe_held_experts(
                x, ex.w_gate, ex.w_up, ex.w_down, tok, rw, load, rows=rows,
                impl=impl)
        return out._data, np.asarray(load._data), np.asarray(rw._data)

    out, load, weights = routed("pallas")
    ref, _, _ = routed("xla")
    kept = int(load.sum())
    c.check("sigmoid held dispatch: 16 of 128 held, about an eighth of "
            "the assignments, weights of a token at most 2.448",
            0.09 < kept / (4096 * 6) < 0.16 and weights[:kept].min() > 0
            and weights.max() < 2.448, f"expert_load {load}")
    err = _rel_err(out, ref)
    c.check("sigmoid held experts forward == XLA path", err <= tol,
            f"rel err {err:.2e} (tol {tol})")

    # one MLA block at the published widths under `recompute`: the segment
    # keeps the forward kernel's output and log-sum-exp, so value and
    # gradient compile to ONE forward kernel call (two with nothing kept)
    from paddle_tpu.distributed import recompute
    from paddle_tpu.jit.api import _rng_lift
    from paddle_tpu.models import DeepseekV3Config
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Attention

    paddle.seed(0)
    with paddle.nn.initializer.param_init_override(dtype="bfloat16"):
        block = DeepseekV3Attention(DeepseekV3Config())
    params = list(block.parameters())
    hidden = jax.random.normal(ks[7], (b, t, 2048), bf16)

    def block_loss(rc):
        def run(arrays, x):
            old = [p._data for p in params]
            for p, a in zip(params, arrays):
                p._data = a
            try:
                # the segment's key is drawn from a key of this trace's
                with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                    x = paddle.to_tensor(x)
                    out = recompute(block, x) if rc else block(x)
                return jnp.sum(out._data.astype(jnp.float32))
            finally:
                for p, a in zip(params, old):
                    p._data = a
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1)))

    arrays = [p._data for p in params]
    before = _compat.recompute_kept().get("mla_attention", 0)
    compiled = block_loss(True).lower(arrays, hidden).compile()
    kept = _compat.recompute_kept().get("mla_attention", 0) - before
    forwards = len(re.findall(
        r"^\s*(?:ROOT )?%\w*?mla_attention_fwd[_.\d]* = .*custom-call\(",
        compiled.as_text(), re.M))
    c.check("a recomputed MLA block keeps the forward kernel's output and "
            "log-sum-exp: one mla_attention_fwd call in the compiled "
            "value-and-gradient", kept == 1 and forwards == 1,
            f"recompute_kept {_compat.recompute_kept()}, "
            f"{forwards} forward kernel call(s)")
    (value, (grads, dx)) = compiled(arrays, hidden)
    (want, (want_grads, want_dx)) = block_loss(False)(arrays, hidden)
    err = max(_rel_err(g, w) for g, w in
              zip([value, dx, *grads], [want, want_dx, *want_grads]))
    c.check("recomputed block == plain block, value and every gradient",
            err <= tol, f"rel err {err:.2e} (tol {tol})")
    # every call above had 128-wide parts: the kernels read them in place
    forms = _compat.mla_operands()
    c.check("the MLA kernels read q_nope, k_nope and v flat, [B, T, H d]",
            forms.get("flat", 0) >= 2 and not forms.get("heads"),
            f"mla_operands {forms}")
    c.done()


# ----------------------------------------------------------------- train
def _peak_gib(dev):
    return dev.memory_stats()["peak_bytes_in_use"] / 2**30


def _bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def _build_model():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**WIDTH, fused_loss_chunk=2048))
    model.bfloat16()
    return model


class _RepeatedSequence:
    """Map-style dataset whose every item is the same seeded token row:
    the loader is exercised and the batch still repeats, so the loss must
    fall."""

    def __init__(self, n):
        self._n = n
        self._row = np.random.RandomState(0).randint(
            0, WIDTH["vocab_size"], (SEQ,)).astype("int32")

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        return self._row


def _train(c, model, device_label):
    """TRAIN_STEPS steps of TrainStep over the forked-worker loader;
    returns the losses."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.observability import jit_events

    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, weight_decay=0.1,
        parameters=model.parameters(), multi_precision=True,
    )

    def loss_fn(m, ids):
        return m(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    loader = paddle.io.DataLoader(
        _RepeatedSequence(TRAIN_BATCH * TRAIN_STEPS),
        batch_size=TRAIN_BATCH, num_workers=2, use_shared_memory=True,
    )
    jit_events.clear_compile_log()
    losses, walls = [], []
    for ids in loader:
        t0 = time.perf_counter()
        loss = step(ids)
        jax.block_until_ready(loss._data)
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss.numpy()))
    compiles = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    c.check(f"{TRAIN_STEPS} steps from the forked-worker DataLoader",
            len(losses) == TRAIN_STEPS, f"losses {losses}")
    c.check("loss finite and falling",
            bool(np.all(np.isfinite(losses))) and losses[-1] < losses[0])
    c.check("exactly one TrainStep compile", len(compiles) == 1,
            f"{len(compiles)} compile events")
    say(f"  smoke output ({device_label}): compile+first step "
        f"{walls[0]:.1f}s, later steps "
        f"{[round(w * 1e3) for w in walls[1:]]} ms, batch {TRAIN_BATCH} x "
        f"seq {SEQ}")
    return losses


def phase_train():
    import jax

    c = Checks("train")
    dev = jax.devices()[0]
    model = _build_model()
    say(f"  model {model.num_params() / 1e6:.1f}M params on {dev}")
    losses = _train(c, model, str(dev))
    platforms = {d.platform for p in model.parameters()
                 for d in p._data.devices()}
    c.check("parameters live on the TPU", platforms == {"tpu"},
            str(platforms))
    say(f"  smoke output: peak_bytes_in_use {_peak_gib(dev):.2f} GiB")
    c.done()
    return losses


# ----------------------------------------------------------------- serve
def _requests():
    """The request set: three unrelated prompts of mixed length (sent
    concurrently, the long one chunk-prefilled, the middle one streamed)
    and two that share a prefix (sent in order, so the second meets the
    first's cached blocks)."""
    rng = np.random.RandomState(1)

    def toks(n):
        return [int(t) for t in rng.randint(1, WIDTH["vocab_size"], n)]

    shared = toks(160)
    return {
        "short": dict(prompt=toks(24), max_tokens=16),
        "streamed": dict(prompt=toks(200), max_tokens=24, stream=True),
        "chunked": dict(prompt=toks(700), max_tokens=12),
        "prefix_a": dict(prompt=shared + toks(30), max_tokens=12),
        "prefix_b": dict(prompt=shared + toks(45), max_tokens=12),
    }


def _post(port, body):
    """One /v1/completions call; (token_ids, finish_reason). A streamed
    call is reassembled from its SSE chunks."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
        if not body.get("stream"):
            choice = json.loads(resp.read())["choices"][0]
            return choice["token_ids"], choice["finish_reason"]
        streamed, final = [], None
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            final = json.loads(line[len(b"data: "):])["choices"][0]
            if final.get("finish_reason") is None:
                streamed += final["token_ids"]
        if streamed != final["token_ids"]:
            raise RuntimeError("SSE chunks do not reassemble the final "
                               f"tokens: {streamed} vs {final['token_ids']}")
        return final["token_ids"], final["finish_reason"]
    finally:
        conn.close()


def _serve_requests(engine, reqs):
    """Serve the request set over HTTP; {name: (tokens, finish_reason)}."""
    from paddle_tpu.serving import Server

    out = {}
    srv = Server(engine, port=0)
    try:
        threads = [
            threading.Thread(
                target=lambda n=n: out.__setitem__(n, _post(srv.port, reqs[n]))
            )
            for n in ("short", "streamed", "chunked")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for n in ("prefix_a", "prefix_b"):
            out[n] = _post(srv.port, reqs[n])
    finally:
        srv.close()
    return out


_PROBES = ("prefill_compiles", "prefill_ext_compiles", "decode_compiles",
           "cow_compiles", "verify_compiles")


def _serve(c, model, cache_dir, label, **engine_kw):
    """One engine, then a second over the same AOT directory. The store
    may already be warm from an earlier process: the first engine then
    compiles nothing either, and the checks say so."""
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.serving import Engine, EngineConfig

    def build():
        t0 = time.perf_counter()
        eng = Engine(model, EngineConfig(
            enable_prefix_cache=True, prefill_chunk_tokens=256,
            compile_cache=cache_dir, **engine_kw,
        ))
        return eng, time.perf_counter() - t0

    reqs = _requests()
    first, first_s = build()
    cc = first._cc.metrics
    store_was_cold = cc.misses > 0
    first_out = _serve_requests(first, reqs)
    for name, (tokens, reason) in first_out.items():
        c.check(f"{label} request {name!r} finished {reason!r} with "
                f"{len(tokens)} tokens",
                reason in ("stop", "length")
                and len(tokens) == reqs[name]["max_tokens"])
    m = first.metrics
    c.check(f"{label} decode ran from at most one compile",
            m.decode_compiles <= 1 and m.decode_steps > 0,
            f"decode_compiles={m.decode_compiles} over {m.decode_steps} "
            f"steps, AOT store was {'cold' if store_was_cold else 'warm'}")
    c.check(f"{label} prefix cache hit on the shared prefix",
            m.prefix_hit_tokens > 0, f"{m.prefix_hit_tokens} tokens")
    c.check(f"{label} chunked prefill ran", m.prefill_chunks > 0,
            f"{m.prefill_chunks} chunks")
    c.check(f"{label} block manager drained",
            first.block_manager.num_used == len(first.prefix_cache)
            and first.health()["kv_active_utilization"] == 0.0,
            f"used={first.block_manager.num_used} "
            f"cached={len(first.prefix_cache)}")

    second, second_s = build()
    second_out = _serve_requests(second, reqs)
    probes = {p: getattr(second.metrics, p) for p in _PROBES}
    c.check(f"{label} second engine: every compile probe 0",
            not any(probes.values()), str(probes))
    c.check(f"{label} second engine: identical outputs",
            second_out == first_out)
    c.check("no kernel or compile-cache fallback",
            _compat.fallbacks_total() == 0 and cc.fallbacks == 0
            and cc.store_errors == 0,
            f"kernel fallbacks={_compat.fallbacks_total()} cache "
            f"fallbacks={cc.fallbacks} store_errors={cc.store_errors} "
            f"hits={cc.hits} misses={cc.misses}")
    say(f"  smoke output ({label}): first engine build {first_s:.1f}s "
        f"({'compiled' if store_was_cold else 'loaded'} "
        f"{len(first.config.prefill_buckets)} prefill buckets), second "
        f"engine {second_s:.1f}s")
    return first, first_out


def phase_serve(cache_root):
    import jax

    import paddle_tpu as paddle

    c = Checks("serve")
    model = _build_model()
    model.eval()
    engine, out = _serve(c, model, os.path.join(cache_root, "aot", "tp1"),
                         "tp=1")
    decode_hlo = engine._ensure_program("decode", any_sample=False).as_text()
    c.check("decode program holds the Pallas paged-attention custom call",
            "tpu_custom_call" in decode_hlo)
    short = _requests()["short"]
    ref = model.generate(
        paddle.to_tensor(np.asarray([short["prompt"]], "int64")),
        max_new_tokens=short["max_tokens"],
    ).numpy()[0, len(short["prompt"]):].tolist()
    c.check("greedy request == one-at-a-time model.generate",
            out["short"][0] == ref, f"engine {out['short'][0]} ref {ref}")
    dev = jax.devices()[0]
    say(f"  served on {dev}; smoke output: peak_bytes_in_use "
        f"{_peak_gib(dev):.2f} GiB")
    c.done()
    return out


# ------------------------------------------------------------ four chips
def phase_four_chips(cache_root, one_chip_losses, one_chip_out):
    import jax

    import paddle_tpu.distributed as dist

    c = Checks("four chips")
    devices = jax.devices()[:4]

    model = _build_model()
    model, _ = dist.parallelize(
        model, None, config={"dp_degree": 2, "mp_degree": 2})
    losses = _train(c, model, "dp=2 x mp=2")
    weight = dict(model.named_parameters())[
        "llama.layers.0.self_attn.q_proj.weight"]._data
    c.check("a weight is sharded over all four devices",
            weight.sharding.device_set == set(devices)
            and not weight.sharding.is_fully_replicated,
            str(weight.sharding))
    c.check("first-step loss == one-chip first-step loss",
            abs(losses[0] - one_chip_losses[0]) <= 2e-2 * one_chip_losses[0],
            f"{losses[0]:.4f} vs {one_chip_losses[0]:.4f}")
    in_use = _bytes_in_use(devices)
    c.check("every device holds training state", all(in_use),
            f"bytes_in_use {in_use}")
    del model, weight
    gc.collect()

    model = _build_model()
    model.eval()
    engine, out = _serve(c, model, os.path.join(cache_root, "aot", "tp4"),
                         "tp=4", tp_degree=4)
    pool = engine.pool.k[0]
    pool = pool[0] if isinstance(pool, (tuple, list)) else pool
    c.check("the KV pool is sharded over all four devices",
            pool.sharding.device_set == set(devices)
            and not pool.sharding.is_fully_replicated, str(pool.sharding))
    in_use = _bytes_in_use(devices)
    c.check("every device holds serving state", all(in_use),
            f"bytes_in_use {in_use}")
    same = {n: out[n] == one_chip_out[n] for n in out}
    c.check("tp=4 outputs == one-chip outputs", all(same.values()),
            str(same))
    c.done()


def result_line(device):
    """The last stdout line: exactly these keys, which the driver parses."""
    return json.dumps({"ok": True, "device": device})


def main():
    device, cache_root = phase_device()
    phase_kernels()
    phase_hybrid()
    phase_state_space()
    phase_latent()
    losses = phase_train()
    gc.collect()
    out = phase_serve(cache_root)
    gc.collect()
    if device["count"] >= 4:
        phase_four_chips(cache_root, losses, out)
    say(result_line(device))


if __name__ == "__main__":
    sys.exit(main())
