"""The plain reference of the Mellum 2 block: float32 jax.numpy after the
published config (JetBrains/Mellum2-12B-A2.5B-Instruct, read as a
Qwen3-MoE-style decoder), with its loss, gradients and AdamW.

    Norm(x; w) = x rsqrt(mean(x^2) + eps) w
    layer i:  r = x + Attention_i(Norm(x));  y = r + MoE(Norm(r))

Attention_i is grouped-query softmax attention over a dense mask: on a
sliding layer (`layer_types`) a query at position p sees keys p - window +
1 .. p under plain rope at its theta; on a full layer every key up to p
under YaRN (`yarn_inv_freq`: the frequencies blended between the source's
and a factor slower along a ramp over the correction range, cos and sin
times `attention_factor`). The expert layer routes over all published
experts in float32 (softmax, top k, the weights normalised over the k) and
sums over the experts held on this chip only, a plain loop over them with
masks; no shared expert.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights_mellum2.py by seed. Matrix products
run at `highest` precision; matmul, rope, rms_norm and the AdamW step are
benchmarks/reference/decoder.py's own. Departures, for memory only:
attention is taken one group of query heads at a time (the scores of a
group, [heads / kv, s, s] float32, are what fits at 8192), every block is
rematerialised in the backward pass, and a training step takes its batch
one row at a time. Left out as in the program: the multi-token-prediction
head, a router balance term.

`mode` lowers the operands of every matrix product (decoder.MODES): the
control. `full_window` plants this model's first fault (the sliding layers
attend to every earlier key), `plain_rope` its second (the full layers
take plain rope at their theta, YaRN left out).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights_mellum2 as W
from benchmarks.reference.decoder import (_lower, _norm, adamw_leaf, matmul,
                                          rms_norm, rope)

_HI = jax.lax.Precision.HIGHEST


def yarn_inv_freq(d, p):
    """YaRN's per-pair frequencies and scale from a `rope_parameters`
    entry (transformers' `_compute_yarn_parameters`, truncated range)."""
    base, factor = float(p["rope_theta"]), float(p["factor"])
    orig = float(p["original_max_position_embeddings"])

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(p["beta_fast"])), 0)
    high = min(math.ceil(dim_of(p["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return freq / factor * ramp + freq * (1.0 - ramp), float(
        p["attention_factor"])


def rope_at(x, pos, inv_freq, scale):
    """decoder.rope with the frequencies given and cos, sin scaled."""
    d2 = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    c = scale * jnp.cos(ang)[:, None, :]
    s = scale * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, mode, window=None):
    """Causal grouped-query attention, q [s, heads, d], k/v [s, kv, d],
    a query seeing the `window` keys up to itself where given; one group
    of query heads (those sharing a kv head) at a time."""
    s, heads, d = q.shape
    kv = k.shape[1]
    qg = jnp.moveaxis(q.reshape(s, kv, heads // kv, d), 1, 0)
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args                       # [s, g, d], [s, d], [s, d]
        sc = jnp.einsum("sgd,td->gst", _lower(qh, mode), _lower(kh, mode),
                        precision=_HI) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), -1)
        return jnp.einsum("gst,td->sgd", _lower(p, mode), _lower(vh, mode),
                          precision=_HI)

    out = jax.lax.map(
        group, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(s, heads * d)


def self_attention(h, wl, cfg, sliding, mode, full_window, plain_rope):
    n = W.dims(cfg)
    s, d = h.shape[0], n["d"]
    q = matmul(h, wl["self_attn.q_proj.weight"], mode).reshape(s, -1, d)
    k = matmul(h, wl["self_attn.k_proj.weight"], mode).reshape(s, -1, d)
    v = matmul(h, wl["self_attn.v_proj.weight"], mode).reshape(s, -1, d)
    p = cfg["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    pos = jnp.arange(s)
    if p["rope_type"] == "yarn" and not plain_rope:
        inv_freq, scale = yarn_inv_freq(d, p)
        q, k = rope_at(q, pos, inv_freq, scale), rope_at(k, pos, inv_freq,
                                                         scale)
    else:
        q, k = rope(q, pos, p["rope_theta"]), rope(k, pos, p["rope_theta"])
    window = n["window"] if sliding and not full_window else None
    return matmul(attention(q, k, v, mode, window),
                  wl["self_attn.o_proj.weight"], mode)


def swiglu(h, wg, wu, wd, mode):
    return matmul(jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode),
                  wd, mode)


def moe(h, wl, cfg, mode):
    n = W.dims(cfg)
    p = jax.nn.softmax(matmul(h, wl["mlp.gate.weight"], mode), -1)
    w, idx = jax.lax.top_k(p, n["k"])
    w = w / jnp.sum(w, -1, keepdims=True)              # norm_topk_prob

    @jax.checkpoint
    def expert(y, xs):
        e, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(idx == n["held_start"] + e, w, 0.0), -1)
        return y + mine[:, None] * swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        jnp.arange(n["held"]), wl["mlp.experts.w_gate"],
        wl["mlp.experts.w_up"], wl["mlp.experts.w_down"]))
    return y


def block(x, wl, cfg, sliding, mode="float32", full_window=False,
          plain_rope=False):
    """One decoder layer over one sequence x [s, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, wl["input_layernorm.weight"], eps)
    x = x + self_attention(h, wl, cfg, sliding, mode, full_window,
                           plain_rope)
    h = rms_norm(x, wl["post_attention_layernorm.weight"], eps)
    return x + moe(h, wl, cfg, mode)


def layer_of(params, i):
    prefix = f"model.layers.{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def row_loss(params, ids, cfg, mode="float32", full_window=False,
             plain_rope=False):
    """Mean next-token cross entropy of one row of token ids."""
    x = params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, cfg=cfg, sliding=W.is_sliding_layer(cfg, i), mode=mode,
            full_window=full_window, plain_rope=plain_rope))(
                x, layer_of(params, i))
    x = rms_norm(x[:-1], params["model.norm.weight"], cfg["rms_norm_eps"])
    logits = matmul(x, params["lm_head.weight"], mode)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, ids[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def train_steps(cfg, seed, batches, opt, mode="float32", half_batch=False,
                full_window=False, plain_rope=False, dtype=jnp.bfloat16):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays) under the optimizer's linear warm-up:
    decoder.train_steps for this block. Returns each step's loss, the
    per-leaf norm of the first gradient and the per-leaf norm of the
    parameters' change after the last step."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    params = {n: a.astype(jnp.float32)
              for n, a in W.make_weights(cfg, seed, dtype).items()}
    loss_grad = jax.value_and_grad(functools.partial(
        row_loss, cfg=cfg, mode=mode, full_window=full_window,
        plain_rope=plain_rope))

    @functools.partial(jax.jit, donate_argnums=1)
    def grad_into(params, acc, ids):
        loss, g = loss_grad(params, ids)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    warm = opt.get("warmup_steps", 0)
    scaled_norm = jax.jit(lambda a, k: _norm(a) / k)
    moments = {}
    losses, grad_norms = [], {}
    for t, batch in enumerate(batches, 1):
        rows = batch[: len(batch) // 2] if half_batch else batch
        acc = {n: jnp.zeros_like(a) for n, a in params.items()}
        total = 0.0
        for row in rows:
            loss, acc = grad_into(params, acc, jnp.asarray(row, jnp.int32))
            total += float(loss)
        losses.append(total / len(rows))
        if t == 1:
            grad_norms = {n: float(scaled_norm(a, len(rows)))
                          for n, a in acc.items()}
        # step t of the job runs at learning_rate * t / warmup_steps
        lr = opt["learning_rate"] * (min(t, warm) / warm if warm else 1.0)
        update = jax.jit(
            functools.partial(adamw_leaf, opt=dict(opt, learning_rate=lr)),
            static_argnums=4, donate_argnums=(0, 2, 3))
        for n in list(params):
            g = acc.pop(n) / len(rows)
            m, v = moments.get(n) or (jnp.zeros_like(g), jnp.zeros_like(g))
            params[n], m, v = update(params[n], g, jnp.asarray(m),
                                     jnp.asarray(v), t)
            moments[n] = (np.asarray(m), np.asarray(v))
            del g, m, v
    std = float(cfg.get("initializer_range", 0.02))
    key = W.seed_key(seed)
    diff_norm = jax.jit(lambda a, b: _norm(a - b.astype(jnp.float32)))
    change = {}
    for i, (n, shape, kind) in enumerate(W.leaf_specs(cfg)):
        first = W.make_leaf(key, index=i, shape=shape, kind=kind, std=std,
                            dtype=dtype)
        change[n] = float(diff_norm(params[n], first))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
