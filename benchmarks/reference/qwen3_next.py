"""The plain reference of the Qwen3-Next block: float32 jax.numpy after the
published description (HF transformers `modeling_qwen3_next.py`, config
Qwen/Qwen3-Next-80B-A3B-Instruct), with its loss, gradients and AdamW.

    ZNorm(x; w) = x rsqrt(mean(x^2) + eps) (1 + w)
    layer i:  r = x + Mixer_i(ZNorm(x));  y = r + MoE(ZNorm(r))

Mixer_i is gated attention where (i + 1) % full_attention_interval == 0
(query and output gate from one projection, ZNorm on q and k per head,
rope on the first quarter of each head, causal softmax attention, o *
sigmoid(gate)) and Gated DeltaNet otherwise: the recurrence is run as
written, token by token,

    S' = exp(g_t) S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T;
    o_t = S^T q_t

(never in chunks: the program's chunked kernel is what this checks). The
expert layer routes over all published experts in float32 and sums over
the experts held on this chip only, a plain loop over them with masks, the
weights normalised over all the chosen; the shared expert is whole.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights_qwen3_next.py by seed. Matrix products
run at `highest` precision; matmul, rope, attention and the AdamW step are
benchmarks/reference/decoder.py's own. Departures, for memory only: every
block is rematerialised in the backward pass, the recurrence is
checkpointed in blocks of 64 tokens, and a training step takes its batch one
row at a time. Left out as in the program: the multi-token-prediction
module, a router balance term.

`mode` lowers the operands of every matrix product (decoder.MODES): the
control. `drop_held` plants this model's own fault: the held experts' part
of every expert layer left out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_qwen3_next as W
from benchmarks.reference.decoder import (_norm, adamw_leaf, attention,
                                          matmul, rope)

_HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


def znorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def gated_attention(h, wl, cfg, mode):
    n = W.dims(cfg)
    s, heads, d = h.shape[0], n["heads"], n["d"]
    eps = cfg["rms_norm_eps"]
    qg = matmul(h, wl["self_attn.q_proj.weight"], mode).reshape(
        s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = matmul(h, wl["self_attn.k_proj.weight"], mode).reshape(s, -1, d)
    v = matmul(h, wl["self_attn.v_proj.weight"], mode).reshape(s, -1, d)
    q = znorm(q, wl["self_attn.q_norm.weight"], eps)
    k = znorm(k, wl["self_attn.k_norm.weight"], eps)
    rot = int(d * cfg["partial_rotary_factor"])
    pos = jnp.arange(s)
    turn = lambda x: jnp.concatenate(
        [rope(x[..., :rot], pos, cfg["rope_theta"]), x[..., rot:]], -1)
    # every query head with its own copy of its kv head: the scores of
    # one head at a time, [s, s] float32, are what fits at 8192
    rep = heads // n["kv"]
    o = attention(turn(q), jnp.repeat(turn(k), rep, 1),
                  jnp.repeat(v, rep, 1), mode)        # [s, heads * d]
    o = o * jax.nn.sigmoid(gate.reshape(s, heads * d))
    return matmul(o, wl["self_attn.o_proj.weight"], mode)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token. q, k [s, H, d_k], v
    [s, H, d_v], g and beta [s, H] -> o [s, H, d_v]; float32 state."""
    s, heads, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, None, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt,
                                           precision=_HI))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt, precision=_HI)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    pad = (-s) % SCAN_BLOCK           # padded tokens write nothing
    xs = tuple(jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)).reshape(
        (s + pad) // SCAN_BLOCK, SCAN_BLOCK, *x.shape[1:])
        for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((heads, dk, dv), jnp.float32), xs)
    return o.reshape(s + pad, heads, dv)[:s]


def gated_delta_net(h, wl, cfg, mode):
    n = W.dims(cfg)
    s = h.shape[0]
    hk, hv, dk, dv = n["hk"], n["hv"], n["dk"], n["dv"]
    rep = hv // hk
    # HF's layout: per key head, [q | k | v of its value heads | z of them]
    x = matmul(h, wl["linear_attn.in_proj_qkvz.weight"], mode).reshape(
        s, hk, 2 * dk + 2 * rep * dv)
    q, k = x[..., :dk], x[..., dk:2 * dk]
    v = x[..., 2 * dk:2 * dk + rep * dv]
    z = x[..., 2 * dk + rep * dv:].reshape(s, hv, dv)
    ba = matmul(h, wl["linear_attn.in_proj_ba.weight"], mode).reshape(
        s, hk, 2 * rep)
    b, a = ba[..., :rep].reshape(s, hv), ba[..., rep:].reshape(s, hv)
    u = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                         v.reshape(s, -1)], -1)
    cw = wl["linear_attn.conv_weight"]                 # [width, channels]
    width = cw.shape[0]
    up = jnp.pad(u, ((width - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(cw[j] * up[j:j + s] for j in range(width)))
    q = u[:, :hk * dk].reshape(s, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(s, hv, dv)
    l2 = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(l2(q) / jnp.sqrt(jnp.float32(dk)), rep, axis=1)
    k = jnp.repeat(l2(k), rep, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(wl["linear_attn.A_log"]) * jax.nn.softplus(
        a + wl["linear_attn.dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = wl["linear_attn.norm_weight"] * (o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg["rms_norm_eps"]))
    o = (o * jax.nn.silu(z)).reshape(s, hv * dv)
    return matmul(o, wl["linear_attn.out_proj.weight"], mode)


def swiglu(h, wg, wu, wd, mode):
    return matmul(jax.nn.silu(matmul(h, wg, mode)) * matmul(h, wu, mode),
                  wd, mode)


def moe(h, wl, cfg, mode, drop_held=False):
    n = W.dims(cfg)
    p = jax.nn.softmax(matmul(h, wl["mlp.gate.weight"], mode), -1)
    w, idx = jax.lax.top_k(p, n["k"])
    w = w / jnp.sum(w, -1, keepdims=True)              # norm_topk_prob

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
    shared = swiglu(h, wl["mlp.shared_expert.gate_proj.weight"],
                    wl["mlp.shared_expert.up_proj.weight"],
                    wl["mlp.shared_expert.down_proj.weight"], mode)
    return y + jax.nn.sigmoid(
        matmul(h, wl["mlp.shared_gate.weight"], mode)) * shared


def block(x, wl, cfg, attends, mode="float32", drop_held=False):
    """One decoder layer over one sequence x [s, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = znorm(x, wl["input_layernorm.weight"], eps)
    mixer = gated_attention if attends else gated_delta_net
    x = x + mixer(h, wl, cfg, mode)
    h = znorm(x, wl["post_attention_layernorm.weight"], eps)
    return x + moe(h, wl, cfg, mode, drop_held)


def layer_of(params, i):
    prefix = f"model.layers.{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def row_loss(params, ids, cfg, mode="float32", drop_held=False):
    """Mean next-token cross entropy of one row of token ids."""
    x = params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, cfg=cfg, attends=W.is_attention_layer(cfg, i), mode=mode,
            drop_held=drop_held))(x, layer_of(params, i))
    x = znorm(x[:-1], params["model.norm.weight"], cfg["rms_norm_eps"])
    logits = matmul(x, params["lm_head.weight"], mode)
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, ids[1:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def float32_params(cfg, seed, dtype=jnp.bfloat16):
    """The seed's weights as the program holds them, widened to float32."""
    return {n: a.astype(jnp.float32)
            for n, a in W.make_weights(cfg, seed, dtype).items()}


def train_steps(cfg, seed, batches, opt, mode="float32", half_batch=False,
                drop_held=False, dtype=jnp.bfloat16):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays): decoder.train_steps for this block.
    Returns each step's loss, the per-leaf norm of the first gradient and
    the per-leaf norm of the parameters' change after the last step."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    params = float32_params(cfg, seed, dtype)
    loss_grad = jax.value_and_grad(functools.partial(
        row_loss, cfg=cfg, mode=mode, drop_held=drop_held))

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
