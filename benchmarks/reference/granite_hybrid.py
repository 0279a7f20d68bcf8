"""The plain reference of the Granite 4.0-H block: float32 jax.numpy after
the published description (HF transformers `modeling_granitemoehybrid.py`,
config ibm-granite/granite-4.0-h-micro), with its loss, gradients and AdamW.

    h0 = embedding_multiplier * E[ids]
    layer i:  h = h + residual_multiplier * Mixer_i(RMSNorm(h))
              h = h + residual_multiplier * MLP(RMSNorm(h))
    logits = (E RMSNorm(h)) / logits_scaling           (the tied matrix)

Mixer_i is grouped-query causal softmax attention of `attention_multiplier
* q k^T` without a position embedding where `layer_types[i]` is
"attention", and the Mamba-2 mixer otherwise: `[z | xBC | dt] = W_in u`,
`[x | B | C] = silu(conv(xBC) + bias)` (depthwise, causal), `dt =
softplus(dt + dt_bias)`, `A = -exp(A_log)`, and the recurrence run as
written, token by token,

    H_t = exp(dt_t A_j) H_{t-1} + dt_t B_t (x) x_t;   y_t = C_t^T H_t + D_j x_t

(never in chunks: the program's chunked kernel is what this checks), then
`RMSNorm(y * silu(z)) * w` over the whole inner width and the output
projection. The MLP is `W_out (silu(a) * b)`, `[a | b] = W_in x`.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights_granite_hybrid.py by seed. Matrix
products run at `highest` precision; matmul, attention, the norm and the
AdamW step are benchmarks/reference/decoder.py's own. Departures, for
memory only: every block is rematerialised in the backward pass, the
recurrence is checkpointed in blocks of 64 tokens, and a training step
takes its batch one row at a time with Adam's moments waiting on the host.

`mode` lowers the operands of every matrix product (decoder.MODES): the
control. `drop_scan` plants this model's own fault: the recurrence left
out, `y = D x`. `scan_dtype` keeps the recurrence's state in another dtype
(the tests: bfloat16 there has to fail a tolerance).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_granite_hybrid as W
from benchmarks.reference.decoder import (_norm, adamw_leaf, attention,
                                          matmul, rms_norm)

_HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


def gqa(h, wl, cfg, mode):
    n = W.dims(cfg)
    s, d = h.shape[0], n["d"]
    q = matmul(h, wl["self_attn.q_proj.weight"], mode).reshape(s, -1, d)
    k = matmul(h, wl["self_attn.k_proj.weight"], mode).reshape(s, -1, d)
    v = matmul(h, wl["self_attn.v_proj.weight"], mode).reshape(s, -1, d)
    # decoder.attention divides the scores by sqrt(d); this model's are
    # multiplied by attention_multiplier (exact here: powers of two)
    q = q * (cfg["attention_multiplier"] * jnp.sqrt(jnp.float32(d)))
    return matmul(attention(q, k, v, mode),
                  wl["self_attn.o_proj.weight"], mode)


def selective_scan(x, dt, a, b, c, d, dtype=jnp.float32):
    """The recurrence token by token. x [s, H, P], dt [s, H], a and d [H],
    b and c [s, H, N] -> y [s, H, P]; the state [H, N, P] in `dtype`."""
    s, heads, p = x.shape

    def token(state, inp):
        xt, dtt, bt, ct = inp
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * bt)[:, :, None] * xt[:, None, :]
                 ).astype(dtype)
        y = jnp.einsum("hn,hnp->hp", ct.astype(dtype), state, precision=_HI)
        return state, y.astype(jnp.float32) + d[:, None] * xt

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = (-s) % SCAN_BLOCK           # padded tokens write nothing (dt 0)
    xs = tuple(jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1)).reshape(
        (s + pad) // SCAN_BLOCK, SCAN_BLOCK, *v.shape[1:])
        for v in (x, dt, b, c))
    _, y = jax.lax.scan(
        block, jnp.zeros((heads, b.shape[-1], p), dtype), xs)
    return y.reshape(s + pad, heads, p)[:s]


def mamba(h, wl, cfg, mode, drop_scan=False, scan_dtype=jnp.float32):
    n = W.dims(cfg)
    s, inner, gn = h.shape[0], n["inner"], n["g"] * n["n"]
    zxbcdt = matmul(h, wl["mamba.in_proj.weight"], mode)
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + n["channels"]]
    dt = jax.nn.softplus(zxbcdt[:, inner + n["channels"]:]
                         + wl["mamba.dt_bias"])
    cw = wl["mamba.conv_weight"]                       # [taps, channels]
    taps = cw.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(wl["mamba.conv_bias"] + sum(
        cw[j] * padded[j:j + s] for j in range(taps)))
    x = xbc[:, :inner].reshape(s, n["mh"], n["mp"])
    if drop_scan:
        y = wl["mamba.D"][:, None] * x
    else:
        rep = n["mh"] // n["g"]
        b = xbc[:, inner:inner + gn].reshape(s, n["g"], n["n"])
        c = xbc[:, inner + gn:].reshape(s, n["g"], n["n"])
        y = selective_scan(
            x, dt, -jnp.exp(wl["mamba.A_log"]), jnp.repeat(b, rep, axis=1),
            jnp.repeat(c, rep, axis=1), wl["mamba.D"], scan_dtype)
    y = y.reshape(s, inner) * jax.nn.silu(z)           # the gate, then the norm
    y = rms_norm(y, wl["mamba.norm_weight"], cfg["rms_norm_eps"])
    return matmul(y, wl["mamba.out_proj.weight"], mode)


def mlp(h, wl, cfg, mode):
    f = cfg["shared_intermediate_size"]
    ab = matmul(h, wl["shared_mlp.input_linear.weight"], mode)
    return matmul(jax.nn.silu(ab[:, :f]) * ab[:, f:],
                  wl["shared_mlp.output_linear.weight"], mode)


def block(x, wl, cfg, attends, mode="float32", **fault):
    """One decoder layer over one sequence x [s, hidden]."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = rms_norm(x, wl["input_layernorm.weight"], eps)
    mixed = gqa(h, wl, cfg, mode) if attends else mamba(
        h, wl, cfg, mode, **fault)
    x = x + r * mixed
    h = rms_norm(x, wl["post_attention_layernorm.weight"], eps)
    return x + r * mlp(h, wl, cfg, mode)


def layer_of(params, i):
    prefix = f"model.layers.{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def hidden_states(params, ids, cfg, mode="float32", **fault):
    x = cfg["embedding_multiplier"] * params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, cfg=cfg, attends=cfg["layer_types"][i] == W.ATTENTION,
            mode=mode, **fault))(x, layer_of(params, i))
    return rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])


def logits(params, ids, cfg, mode="float32", **fault):
    """[s, vocabulary] of one row of token ids: the tied matrix, over
    logits_scaling."""
    x = hidden_states(params, ids, cfg, mode, **fault)
    return matmul(x, params["model.embed_tokens.weight"].T, mode) / (
        cfg["logits_scaling"])


def row_loss(params, ids, cfg, mode="float32", **fault):
    """Mean next-token cross entropy of one row of token ids."""
    z = logits(params, ids, cfg, mode, **fault)[:-1]
    lse = jax.scipy.special.logsumexp(z, -1)
    gold = jnp.take_along_axis(z, ids[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def float32_params(cfg, seed, dtype=jnp.bfloat16):
    """The seed's weights as the program holds them, widened to float32."""
    return {n: a.astype(jnp.float32)
            for n, a in W.make_weights(cfg, seed, dtype).items()}


def train_steps(cfg, seed, batches, opt, mode="float32", half_batch=False,
                dtype=jnp.bfloat16, **fault):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays): decoder.train_steps for this block, the
    learning rate warmed up as the configuration says. Returns each
    step's loss, the per-leaf norm of the first gradient and the per-leaf
    norm of the parameters' change after the last step."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    params = float32_params(cfg, seed, dtype)
    loss_grad = jax.value_and_grad(functools.partial(
        row_loss, cfg=cfg, mode=mode, **fault))

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
