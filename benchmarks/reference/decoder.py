"""The plain reference: a Mistral/Llama-style decoder in straightforward
float32 jax.numpy, after the published description (pre-norm blocks,
RMSNorm, rotary embedding over half-split pairs, grouped-query causal
attention, SwiGLU, untied head), with its loss, gradients and AdamW.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights.py by seed. No kernels, no cache, no
batching; matrix products run at `highest` precision. Two departures, for
memory only: attention is mapped over groups of heads and every block is
rematerialised in the backward pass, and a training step takes its batch
one row at a time (the mean of the rows' gradients is the batch's).

`mode` computes every matrix product's operands in a lower precision; it is
how the control (the reference put in the program's place) is made:
"float32" (the reference), "bfloat16", "fp8" (e4m3 with a scale per
tensor, the usual recipe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights as W

_HI = jax.lax.Precision.HIGHEST
MODES = ("float32", "bfloat16", "fp8")


def _lower(x, mode):
    """x as the lower precision holds it. The rounding is straight-through
    for gradients: the forward operands are lowered, the cotangents are
    not (cast blindly they would underflow in fp8 and every gradient would
    read nought, a control that fails for the wrong reason)."""
    if mode == "float32":
        return x
    if mode == "bfloat16":
        low = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        low = (x / scale).astype(jnp.float8_e4m3fn).astype(
            jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision mode {mode!r}")
    return x + jax.lax.stop_gradient(low - x)


def matmul(a, b, mode="float32"):
    return jnp.matmul(_lower(a, mode), _lower(b, mode), precision=_HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [s, heads, d], pos [s]: rotate the halves (x1, x2) of each head."""
    d2 = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(d2, dtype=jnp.float32) / d2)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, mode):
    """Causal grouped-query attention, q [s, heads, d], k/v [s, kv, d];
    one group of query heads (those sharing a kv head) at a time."""
    s, heads, d = q.shape
    kv = k.shape[1]
    qg = jnp.moveaxis(q.reshape(s, kv, heads // kv, d), 1, 0)
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def group(args):
        qh, kh, vh = args                       # [s, g, d], [s, d], [s, d]
        sc = jnp.einsum("sgd,td->gst", _lower(qh, mode), _lower(kh, mode),
                        precision=_HI) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
        return jnp.einsum("gst,td->sgd", _lower(p, mode), _lower(vh, mode),
                          precision=_HI)

    out = jax.lax.map(
        group, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(s, heads * d)


def block(x, wl, cfg, mode="float32"):
    """One decoder layer over one sequence x [s, hidden]."""
    s = x.shape[0]
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    pos = jnp.arange(s)
    h = rms_norm(x, wl["ln1"], cfg["rms_norm_eps"])
    q = matmul(h, wl["wq"], mode).reshape(s, -1, d)
    k = matmul(h, wl["wk"], mode).reshape(s, -1, d)
    v = matmul(h, wl["wv"], mode).reshape(s, -1, d)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    x = x + matmul(attention(q, k, v, mode), wl["wo"], mode)
    h = rms_norm(x, wl["ln2"], cfg["rms_norm_eps"])
    gate = matmul(h, wl["wg"], mode)
    return x + matmul(jax.nn.silu(gate) * matmul(h, wl["wu"], mode),
                      wl["wd"], mode)


def layer_of(params, i):
    return {n: params[f"layers.{i}.{n}"] for n in W.LAYER_LEAVES}


def head_logits(x, params, cfg, mode="float32"):
    return matmul(rms_norm(x, params["norm"], cfg["rms_norm_eps"]),
                  params["head"], mode)


def row_loss(params, ids, cfg, mode="float32"):
    """Mean next-token cross entropy of one row of token ids."""
    x = params["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            functools.partial(block, cfg=cfg, mode=mode))(
                x, layer_of(params, i))
    logits = head_logits(x[:-1], params, cfg, mode)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, ids[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def float32_params(cfg, seed, dtype=jnp.bfloat16):
    """The seed's weights as the program serves them, widened to float32."""
    return {n: a.astype(jnp.float32)
            for n, a in W.make_weights(cfg, seed, dtype).items()}


def adamw_leaf(p, g, m, v, t, opt):
    """Decoupled weight decay, then Adam with both bias corrections
    (Loshchilov & Hutter; the program's rescaled form is the same
    mathematics), on one leaf."""
    b1, b2, lr = opt["beta1"], opt["beta2"], opt["learning_rate"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p * (1 - lr * opt["weight_decay"])
    p = p - lr * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + opt["epsilon"])
    return p, m, v


def _norm(a):
    return jnp.sqrt(jnp.sum(a * a))


def train_steps(cfg, seed, batches, opt, mode="float32", half_batch=False,
                dtype=jnp.bfloat16):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays). Returns the readings `correct` compares:
    each step's loss, the per-leaf norm of the first gradient, and the
    per-leaf norm of the parameters' change after the last step.
    `half_batch` plants the fault of a step that leaves half of its rows
    out and takes the mean over the rest. Adam's moments wait on the host
    while the gradients are taken, so that the chip holds the parameters,
    one gradient sum and one row's activations."""
    import numpy as np

    params = float32_params(cfg, seed, dtype)
    loss_grad = jax.value_and_grad(
        functools.partial(row_loss, cfg=cfg, mode=mode))

    @functools.partial(jax.jit, donate_argnums=1)
    def grad_into(params, acc, ids):
        loss, g = loss_grad(params, ids)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    update = jax.jit(functools.partial(adamw_leaf, opt=opt),
                     static_argnums=4, donate_argnums=(0, 2, 3))
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
    for i, (n, shape) in enumerate(W.leaf_specs(cfg)):
        first = W.make_leaf(key, index=i, shape=shape, std=std, dtype=dtype)
        change[n] = float(diff_norm(params[n], first))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def served_gaps(cfg, seed, sequences, mode="float32", dtype=jnp.bfloat16,
                pad_to=512):
    """Teacher-forced logits for served requests, layer by layer so that
    one float32 layer is resident at a time. `sequences` is a list of
    (prompt ids, served ids). Returns for each sequence the logits
    [served tokens, vocabulary] at the positions that produced its served
    tokens; with `mode` lower than float32 the products' operands are
    computed in that precision (the control)."""
    std = float(cfg.get("initializer_range", 0.02))
    specs = W.leaf_specs(cfg)
    index = {n: i for i, (n, _) in enumerate(specs)}
    key = W.seed_key(seed)

    def leaf(name):
        i = index[name]
        return W.make_leaf(key, index=i, shape=specs[i][1], std=std,
                           dtype=dtype).astype(jnp.float32)

    toks = [list(p) + list(o)[:-1] for p, o in sequences]
    lens = [-(-len(t) // pad_to) * pad_to for t in toks]
    embed = leaf("embed")
    xs = [embed[jnp.asarray(t + [0] * (n - len(t)), jnp.int32)]
          for t, n in zip(toks, lens)]
    del embed
    run = jax.jit(functools.partial(block, cfg=cfg, mode=mode))
    for i in range(cfg["num_hidden_layers"]):
        wl = {n: leaf(f"layers.{i}.{n}") for n in W.LAYER_LEAVES}
        xs = [run(x, wl) for x in xs]       # right padding is causal-safe
        del wl
    tail = {"norm": leaf("norm"), "head": leaf("head")}
    logits_of = jax.jit(functools.partial(head_logits, cfg=cfg, mode=mode))
    out = []
    for x, (p, o) in zip(xs, sequences):
        out.append(logits_of(x[len(p) - 1: len(p) - 1 + len(o)], tail))
    return out
