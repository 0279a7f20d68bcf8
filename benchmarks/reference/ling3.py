"""The plain reference of the Ling 3.0 block: float32 jax.numpy after the
published description (config inclusionAI/Ling-3.0-flash, `model_type`
`bailing_hybrid`; KDA's equations from the Kimi Linear report, arXiv
2510.26692), with its loss, gradients and AdamW.

    Norm(x; w) = x rsqrt(mean(x^2) + eps) w
    layer i:  r = x + Mixer_i(Norm(x));  y = r + MLP_i(Norm(r))

Mixer_i is MLA where (published i + 1) % layer_group_size == 0, else KDA:
q, k, v = SiLU(CausalConv(x W)) each, q and k L2-normalised per head (q
over sqrt(d_k)), beta = sigmoid(x W_b), g = kda_lower_bound sigmoid(exp(
A_log) (x W_f + dt_bias)) a key channel, and the recurrence run as
written, token by token,

    S' = Diag(exp(g_t)) S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T;
    o_t = S^T q_t

(never in chunks: the program's chunked kernels are what this checks);
out = W_o(RMSNorm_head(o) * sigmoid(x W_gate)_h). MLA is
reference/deepseek_v3.py's with the interleaved rope as stored, the query
RMS-normalised a head over its 192 columns, each head's key_nope and the
shared rotary key apart, before rope, and the output gated a head. The
expert layer scores s = sigmoid(x W_g) over all published experts in
float32, keeps each token's topk_group of n_group groups by the sum of
their two best s + bias, chooses the k best there, weighs them s_i / (the
k's sum + 1e-20) x routed_scaling_factor, and sums over the experts held
on this chip only, a plain loop over them with masks; the shared expert is
whole and ungated.

It imports nothing of paddle_tpu and takes nothing the program made: its
weights come from benchmarks/weights_ling3.py by seed. Matrix products run
at `highest` precision; matmul and the AdamW step are
benchmarks/reference/decoder.py's own. Departures, for memory only: every
block is rematerialised in the backward pass, the recurrence is
checkpointed in blocks of 64 tokens, attention is mapped over the heads,
and a training step takes its batch one row at a time. Left out as in the
program: the multi-token-prediction layer, the SwiGLU clamps, the balance
term, the selection bias's update rule.

`mode` lowers the operands of every matrix product (decoder.MODES): the
control. This model's own faults: `mean_decay` (each head's decay the mean
of its channels', the scalar decay of Gated DeltaNet), `no_group_limit`
(the top k over all experts), `drop_held` (the held experts' part left
out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights_ling3 as W
from benchmarks.reference.decoder import (_lower, _norm, adamw_leaf, matmul,
                                          rms_norm)
from benchmarks.reference.deepseek_v3 import rope_interleaved, swiglu

_HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64


def kda_recurrence(q, k, v, g, beta):
    """KDA token by token. q, k, g [s, H, d_k], v [s, H, d_v], beta [s, H]
    -> o [s, H, d_v]; float32 state."""
    s, heads, dk = q.shape
    dv = v.shape[-1]

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, :, None] * state
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


def _conv_silu(x, w):
    width, s = w.shape[0], x.shape[0]
    up = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * up[j:j + s] for j in range(width)))


def _head_rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def kda_mixer(h, wl, cfg, mode, mean_decay=False):
    n = W.dims(cfg)
    s, heads, d = h.shape[0], n["heads"], n["d"]
    mix = lambda name: _conv_silu(
        matmul(h, wl[f"attention.{name}_proj.weight"], mode),
        wl[f"attention.{name}_conv1d"]).reshape(s, heads, d)
    q, k, v = mix("q"), mix("k"), mix("v")
    l2 = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, -1, keepdims=True) + 1e-6)
    q, k = l2(q) / jnp.sqrt(jnp.float32(d)), l2(k)
    f = matmul(h, wl["attention.f_proj.weight"], mode) + wl[
        "attention.dt_bias"]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(wl["attention.A_log"])[:, None] * f.reshape(s, heads, d))
    if mean_decay:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(matmul(h, wl["attention.b_proj.weight"], mode))
    o = kda_recurrence(q, k, v, g, beta)
    o = _head_rms(o, wl["attention.o_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(matmul(h, wl["attention.g_proj.weight"], mode))
    o = (o * gate[:, :, None]).reshape(s, heads * d)
    return matmul(o, wl["attention.o_proj.weight"], mode)


def latent_attention(h, wl, cfg, mode):
    n = W.dims(cfg)
    s, heads, nope, rope, dv = (h.shape[0], n["heads"], n["nope"],
                                n["rope"], n["dv"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = matmul(h, wl["attention.q_proj.weight"], mode).reshape(
        s, heads, nope + rope)
    kva = matmul(h, wl["attention.kv_a_proj_with_mqa.weight"], mode)
    latent = rms_norm(kva[:, :n["rank"]],
                      wl["attention.kv_a_layernorm.weight"], eps)
    kv = matmul(latent, wl["attention.kv_b_proj.weight"], mode).reshape(
        s, heads, nope + dv)
    k_rope = kva[:, n["rank"]:]
    k_nope = kv[..., :nope]
    # use_qk_norm
    q = _head_rms(q, wl["attention.query_layernorm"], eps)
    k_nope = _head_rms(k_nope, wl["attention.key_layernorm"], eps)
    k_rope = _head_rms(k_rope, wl["attention.key_rope_layernorm"], eps)
    q_rope = rope_interleaved(q[..., nope:], pos, cfg["rope_theta"])
    k_rope = rope_interleaved(k_rope[:, None], pos, cfg["rope_theta"])[:, 0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def head(args):
        qn, qr, kn, vh = args
        sc = jnp.matmul(_lower(qn, mode), _lower(kn, mode).T, precision=_HI)
        sc = sc + jnp.matmul(_lower(qr, mode), _lower(k_rope, mode).T,
                             precision=_HI)
        p = jax.nn.softmax(jnp.where(causal, sc * scale, -jnp.inf), -1)
        return jnp.matmul(_lower(p, mode), _lower(vh, mode), precision=_HI)

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    o = jax.lax.map(head, (by_head(q[..., :nope]), by_head(q_rope),
                           by_head(k_nope), by_head(kv[..., nope:])))
    o = jnp.moveaxis(o, 0, 1)                          # [s, heads, dv]
    gate = jax.nn.sigmoid(matmul(h, wl["attention.g_proj.weight"], mode))
    o = (o * gate[:, :, None]).reshape(s, heads * dv)
    return matmul(o, wl["attention.o_proj.weight"], mode)


def route(h, w_gate, cfg, mode, bias=None, group_limit=True):
    """-> (weights [s, k], experts [s, k]) of the group-limited sigmoid
    router."""
    n = W.dims(cfg)
    s = jax.nn.sigmoid(matmul(h, w_gate, mode))
    choose = s if bias is None else s + bias
    if group_limit:
        per = n["experts"] // n["groups"]
        grouped = choose.reshape(-1, n["groups"], per)
        best2 = jnp.sort(grouped, -1)[..., -2:].sum(-1)    # [s, groups]
        rank = jnp.argsort(jnp.argsort(-best2, -1), -1)
        kept = rank < n["kept_groups"]
        choose = jnp.where(jnp.repeat(kept, per, -1), choose, -jnp.inf)
    _, idx = jax.lax.top_k(choose, n["k"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], idx


def moe(h, wl, cfg, mode, drop_held=False, no_group_limit=False, bias=None):
    n = W.dims(cfg)
    w, idx = route(h, wl["mlp.gate.weight"], cfg, mode, bias,
                   group_limit=not no_group_limit)

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


def block(x, wl, cfg, latent, experts, mode="float32", drop_held=False,
          mean_decay=False, no_group_limit=False, bias=None):
    """One decoder layer over one sequence x [s, hidden]."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, wl["input_layernorm.weight"], eps)
    if latent:
        x = x + latent_attention(h, wl, cfg, mode)
    else:
        x = x + kda_mixer(h, wl, cfg, mode, mean_decay)
    h = rms_norm(x, wl["post_attention_layernorm.weight"], eps)
    if experts:
        return x + moe(h, wl, cfg, mode, drop_held, no_group_limit, bias)
    return x + swiglu(h, wl["mlp.gate_proj.weight"],
                      wl["mlp.up_proj.weight"], wl["mlp.down_proj.weight"],
                      mode)


def layer_of(params, i):
    prefix = f"model.layers.{i}."
    return {n[len(prefix):]: a for n, a in params.items()
            if n.startswith(prefix)}


def hidden_states(params, ids, cfg, mode="float32", biases=None, **faults):
    """The last layer's output [s, hidden] (before the final norm).
    `biases`: {layer index: selection bias [experts]} (the tests')."""
    x = params["model.embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(
            block, cfg=cfg, latent=W.is_latent_attention_layer(cfg, i),
            experts=W.is_expert_layer(cfg, i), mode=mode,
            bias=(biases or {}).get(i), **faults))(x, layer_of(params, i))
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
                dtype=jnp.bfloat16, biases=None, **faults):
    """Follow the trainer from the seed through `batches` (a list of
    [rows, seq] integer arrays): decoder.train_steps for this block.
    `faults`: drop_held, mean_decay, no_group_limit. Returns each step's
    loss, the per-leaf norm of the first gradient and the per-leaf norm of
    the parameters' change after the last step."""
    import numpy as np

    dtype = jnp.dtype(dtype)
    params = float32_params(cfg, seed, dtype)
    loss_grad = jax.value_and_grad(functools.partial(
        row_loss, cfg=cfg, mode=mode, biases=biases, **faults))

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
