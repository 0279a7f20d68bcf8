"""The plain reference of the DeepSeek-V3 block: float32 jax.numpy after
the published description (HF transformers `modeling_deepseek_v3.py`,
config kakaocorp/kanana-2-30b-a3b-instruct-2601: no query down-projection),
with its loss, gradients and AdamW.

    Norm(x; w) = x rsqrt(mean(x^2) + eps) w
    layer i:  r = x + MLA(Norm(x));  y = r + MLP_i(Norm(r))

MLA: q = x W_q as heads x (nope | rope); x W_kva = latent | one rotary key
for all heads; Norm(latent) W_kvb = heads x (key | value); the rotary
embedding is applied in the source's INTERLEAVED order, to the pairs
(x_2j, x_2j+1) as they are stored (the program de-interleaves its weights
and rotates halves: this checks that the scores are the same); scores
(q_nope . k_nope + q_rope . k_rope) (nope + rope)^-1/2, causal softmax,
one head at a time. MLP_i is dense SwiGLU before `first_k_dense_replace`,
else the expert layer: s = sigmoid(x W_g) over all published experts, the
k chosen by s + bias (the selection bias: a buffer, 0 unless given), their
weights s_i / (sum of the k + 1e-20) x routed_scaling_factor, summed over
the experts held on this chip only, a plain loop over them, every held
expert applied to every token and masked (no sort, no dispatch); the shared
experts are one SwiGLU added whole, ungated.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights_deepseek_v3.py by seed. Matrix
products run at `highest` precision; matmul and the AdamW step are
benchmarks/reference/decoder.py's own. Departures, for memory only: every
block is rematerialised in the backward pass, attention is mapped over the
heads, and a training step takes its batch one row at a time. Left out as
in the program: the selection bias's update rule, a router balance term, a
multi-token-prediction module.

`mode` lowers the operands of every matrix product (decoder.MODES): the
control. `drop_held` and `drop_rope` plant this model's own faults: the
held experts' part of every expert layer left out, and the rotary part of
every score (q_rope . k_rope) left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_deepseek_v3 as W
from benchmarks.reference.decoder import (_lower, _norm, adamw_leaf, matmul,
                                          rms_norm)

_HI = jax.lax.Precision.HIGHEST


def rope_interleaved(x, pos, theta):
    """x [s, heads, d] whose pairs are (x_2j, x_2j+1), pos [s]: pair j is
    turned by pos theta^(-2j/d)."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(*x.shape[:-1], d2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def latent_attention(h, wl, cfg, mode, drop_rope=False):
    n = W.dims(cfg)
    s, heads, nope, rope, dv = (h.shape[0], n["heads"], n["nope"],
                                n["rope"], n["dv"])
    pos = jnp.arange(s)
    q = matmul(h, wl["self_attn.q_proj.weight"], mode).reshape(
        s, heads, nope + rope)
    kva = matmul(h, wl["self_attn.kv_a_proj_with_mqa.weight"], mode)
    latent = rms_norm(kva[:, :n["rank"]], wl["self_attn.kv_a_layernorm.weight"],
                      cfg["rms_norm_eps"])
    kv = matmul(latent, wl["self_attn.kv_b_proj.weight"], mode).reshape(
        s, heads, nope + dv)
    q_rope = rope_interleaved(q[..., nope:], pos, cfg["rope_theta"])
    k_rope = rope_interleaved(kva[:, None, n["rank"]:], pos,
                              cfg["rope_theta"])[:, 0]      # [s, rope]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def head(args):
        qn, qr, kn, vh = args                  # [s, nope], [s, rope], ..
        sc = jnp.matmul(_lower(qn, mode), _lower(kn, mode).T, precision=_HI)
        if not drop_rope:
            sc = sc + jnp.matmul(_lower(qr, mode), _lower(k_rope, mode).T,
                                 precision=_HI)
        p = jax.nn.softmax(jnp.where(causal, sc * scale, -jnp.inf), -1)
        return jnp.matmul(_lower(p, mode), _lower(vh, mode), precision=_HI)

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    o = jax.lax.map(head, (by_head(q[..., :nope]), by_head(q_rope),
                           by_head(kv[..., :nope]), by_head(kv[..., nope:])))
    o = jnp.moveaxis(o, 0, 1).reshape(s, heads * dv)
    return matmul(o, wl["self_attn.o_proj.weight"], mode)


def swiglu(h, wg, wu, wd, mode):
    return matmul(jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode),
                  wd, mode)


def route(h, w_gate, cfg, mode, bias=None):
    """-> (weights [s, k], experts [s, k]) of the sigmoid router."""
    n = W.dims(cfg)
    s = jax.nn.sigmoid(matmul(h, w_gate, mode))
    _, idx = jax.lax.top_k(s if bias is None else s + bias, n["k"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], idx


def moe(h, wl, cfg, mode, drop_held=False, bias=None):
    n = W.dims(cfg)
    w, idx = route(h, wl["mlp.gate.weight"], cfg, mode, bias)

    @jax.checkpoint
    def expert(y, xs):
        e, wg, wu, wd = xs
        mine = jnp.sum(jnp.where(idx == n["held_start"] + e, w, 0.0), -1)
        return y + mine[:, None] * swiglu(h, wg, wu, wd, mode), None

    y = jnp.zeros_like(h)
    if not drop_held:
        y, _ = jax.lax.scan(expert, y, (
            jnp.arange(n["held"]), wl["mlp.experts.w_gate"],
            wl["mlp.experts.w_up"], wl["mlp.experts.w_down"]))
    return y + swiglu(h, wl["mlp.shared_experts.gate_proj.weight"],
                      wl["mlp.shared_experts.up_proj.weight"],
                      wl["mlp.shared_experts.down_proj.weight"], mode)


def block(x, wl, cfg, experts, mode="float32", drop_held=False,
          drop_rope=False, bias=None):
    """One decoder layer over one sequence x [s, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, wl["input_layernorm.weight"], eps)
    x = x + latent_attention(h, wl, cfg, mode, drop_rope)
    h = rms_norm(x, wl["post_attention_layernorm.weight"], eps)
    if experts:
        return x + moe(h, wl, cfg, mode, drop_held, bias)
    return x + swiglu(h, wl["mlp.gate_proj.weight"],
                      wl["mlp.up_proj.weight"], wl["mlp.down_proj.weight"],
                      mode)


def layer_of(params, i):
    prefix = f"model.layers.{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def hidden_states(params, ids, cfg, mode="float32", drop_held=False,
                  drop_rope=False, biases=None):
    """The last layer's output [s, hidden] (before the final norm).
    `biases`: {layer index: selection bias [experts]} (the tests')."""
    x = params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, cfg=cfg, experts=W.is_expert_layer(cfg, i), mode=mode,
            drop_held=drop_held, drop_rope=drop_rope,
            bias=(biases or {}).get(i)))(x, layer_of(params, i))
    return x


def row_logits(params, ids, cfg, mode="float32", **kw):
    x = hidden_states(params, ids, cfg, mode, **kw)
    x = rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return matmul(x, params["lm_head.weight"], mode)


def row_loss(params, ids, cfg, mode="float32", **kw):
    """Mean next-token cross entropy of one row of token ids."""
    logits = row_logits(params, ids, cfg, mode, **kw)[:-1]
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, ids[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def float32_params(cfg, seed, dtype=jnp.bfloat16):
    """The seed's weights as the program holds them, widened to float32."""
    return {n: a.astype(jnp.float32)
            for n, a in W.make_weights(cfg, seed, dtype).items()}


def train_steps(cfg, seed, batches, opt, mode="float32", half_batch=False,
                drop_held=False, drop_rope=False, dtype=jnp.bfloat16,
                biases=None):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays): decoder.train_steps for this block.
    Returns each step's loss, the per-leaf norm of the first gradient and
    the per-leaf norm of the parameters' change after the last step."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    params = float32_params(cfg, seed, dtype)
    loss_grad = jax.value_and_grad(functools.partial(
        row_loss, cfg=cfg, mode=mode, drop_held=drop_held,
        drop_rope=drop_rope, biases=biases))

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
