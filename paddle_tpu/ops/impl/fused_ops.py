"""Fused transformer building-block ops (math reference implementations).

ref: python/paddle/incubate/nn/functional/{fused_rotary_position_embedding,
swiglu, fused_rms_norm}.py — the exact op set SURVEY §2.11 marks for the TPU
build. These are the XLA-fused math paths; kernels/pallas/* provides TPU
Pallas overrides behind FLAGS_use_pallas_kernels where XLA fusion is not
enough.

Layouts follow the reference: q/k/v are [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _build_rope_cache(seq_len, head_dim, base, dtype, position_ids=None):
    inv_freq = 1.0 / (
        base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    if position_ids is None:
        t = jnp.arange(seq_len, dtype=jnp.float32)[None, :]
    else:
        t = position_ids.astype(jnp.float32)
    freqs = jnp.einsum("bs,d->bsd", t, inv_freq)  # [b, s, hd/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def _apply_rope(x, cos, sin, use_neox):
    """x: [b, s, h, d]; cos/sin: [b or 1, s, d/2]."""
    xf = x.astype(jnp.float32)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    if use_neox:
        # neox style: rotate halves [x1, x2] -> [x1*c - x2*s, x2*c + x1*s]
        d2 = x.shape[-1] // 2
        x1, x2 = xf[..., :d2], xf[..., d2:]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    else:
        # GPT-J interleaved pairs
        x1 = xf[..., 0::2]
        x2 = xf[..., 1::2]
        r1 = x1 * c - x2 * s
        r2 = x2 * c + x1 * s
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def fused_rotary_position_embedding(
    q, k=None, v=None, sin=None, cos=None, position_ids=None, *,
    use_neox_rotary_style=True, rotary_emb_base=10000.0,
):
    """ref: incubate/nn/functional/fused_rotary_position_embedding.py —
    applies RoPE to q (and k, v when given). sin/cos may be precomputed
    ([1, s, 1, d] or [s, d/2]-broadcastable); otherwise built from the base.
    Returns the same number of tensors as were passed (None for absent)."""
    b, s, h, d = q.shape
    if cos is None or sin is None:
        cos_h, sin_h = _build_rope_cache(
            s, d, rotary_emb_base, q.dtype, position_ids
        )
    else:
        cos_h = jnp.asarray(cos, jnp.float32)
        sin_h = jnp.asarray(sin, jnp.float32)
        # accept [1, s, 1, d] (paddle) by squeezing the head axis and
        # halving duplicated last dim
        if cos_h.ndim == 4:
            cos_h = cos_h[:, :, 0, :]
            sin_h = sin_h[:, :, 0, :]
        if cos_h.shape[-1] == d:
            cos_h = cos_h[..., : d // 2]
            sin_h = sin_h[..., : d // 2]
        if cos_h.ndim == 2:
            cos_h = cos_h[None]
            sin_h = sin_h[None]

    outs = [_apply_rope(q, cos_h, sin_h, use_neox_rotary_style)]
    for t in (k, v):
        outs.append(
            _apply_rope(t, cos_h, sin_h, use_neox_rotary_style)
            if t is not None
            else None
        )
    return tuple(outs)


def rope_qk(q, k, position_ids=None, *, base=10000.0,
            use_neox_rotary_style=True):
    """Fast path for the common q,k case (single op on the tape).
    position_ids ([b, s] or [s]) offsets the rotation — the decode path
    rotates the new token at its absolute cache position."""
    if position_ids is not None and position_ids.ndim == 1:
        position_ids = position_ids[None, :]
    out = fused_rotary_position_embedding(
        q, k, None, position_ids=position_ids,
        use_neox_rotary_style=use_neox_rotary_style,
        rotary_emb_base=base,
    )
    return out[0], out[1]


def yarn_parameters(head_dim, base, factor, original_max_position_embeddings,
                    beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                    truncate=True):
    """YaRN's rotary frequencies and the scale of cos and sin (ref: HF
    transformers ``_compute_yarn_parameters``): -> (inv_freq, a float32
    numpy array [head_dim / 2], attention_factor). A pair rotating fewer
    than ``beta_slow`` times over the original context is interpolated
    (its frequency over ``factor``), one rotating more than ``beta_fast``
    times is kept, and the pairs between are blended along a linear ramp
    over the correction range, whose ends are truncated to whole pairs.
    ``attention_factor`` None is 0.1 ln(factor) + 1."""
    import math

    import numpy as np

    def correction_dim(rotations):
        return (head_dim * math.log(original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    pos_freqs = base ** (np.arange(0, head_dim, 2, dtype=np.float32)
                         / head_dim)
    extrapolation = 1.0 - ramp
    inv_freq = ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
                + (1.0 / pos_freqs) * extrapolation)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv_freq.astype(np.float32), float(attention_factor)


def rope_qk_freqs(q, k, *, inv_freq, scale=1.0):
    """``rope_qk`` (rotate-half pairs, positions 0 .. s - 1) at the given
    per-pair frequencies ``inv_freq`` (a sequence of head_dim / 2 floats),
    cos and sin multiplied by ``scale``: YaRN's rope with
    ``yarn_parameters``' two results."""
    s = q.shape[1]
    freqs = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.asarray(inv_freq, jnp.float32)[None, :])[None]
    cos, sin = jnp.cos(freqs) * scale, jnp.sin(freqs) * scale
    return _apply_rope(q, cos, sin, True), _apply_rope(k, cos, sin, True)


def fused_linear(x, weight, bias=None, *, transpose_weight=False):
    """ref: incubate/nn/functional/fused_matmul_bias.py."""
    w = weight.T if transpose_weight else weight
    out = jnp.matmul(x, w)
    if bias is not None:
        out = out + bias
    return out


def fused_bias_act(x, bias=None, *, act_method="gelu"):
    """ref: incubate/nn/functional/fused_bias_act.py."""
    if bias is not None:
        x = x + bias
    if act_method == "gelu":
        return jax.nn.gelu(x)
    if act_method == "relu":
        return jax.nn.relu(x)
    if act_method in ("silu", "swish"):
        return jax.nn.silu(x)
    if act_method == "swiglu":
        a, b = jnp.split(x, 2, axis=-1)
        return jax.nn.silu(a) * b
    raise ValueError(f"unknown act_method {act_method!r}")


def fused_linear_cross_entropy(x, weight, labels, *, chunk_size=4096,
                               ignore_index=-100):
    """Chunked LM-head + softmax cross entropy: mean CE of
    (x @ weight) against labels WITHOUT materializing the [N, vocab]
    logits (the HBM hog at billion-param scale — fp32 logits for one
    1k-seq batch-8 step are >1GB before softmax temporaries).

    x: [N, d]; weight: [d, V]; labels: [N] int. Scans over N in
    ``chunk_size`` rows; each chunk's logits live only inside its scan
    step and are recomputed in the backward (jax.checkpoint), so peak
    memory is O(chunk_size * V) either direction.
    ref: the reference fuses this pair in
    incubate/nn/functional/fused_linear_activation + softmax_with_
    cross_entropy; serving frameworks call it fused_linear_cross_entropy.
    """
    n, d = x.shape
    chunk = max(1, min(int(chunk_size), n))
    pad = (-n) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels = jnp.pad(
            labels, (0, pad), constant_values=ignore_index
        )
    nc = x.shape[0] // chunk
    xs = x.reshape(nc, chunk, d)
    ys = labels.reshape(nc, chunk)

    @jax.checkpoint
    def body(acc, xy):
        xc, yc = xy
        logits = (xc @ weight).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe_y = jnp.clip(yc, 0, logits.shape[-1] - 1)
        gold = jnp.take_along_axis(
            logits, safe_y[:, None].astype(jnp.int32), axis=-1
        )[:, 0]
        valid = (yc != ignore_index)
        loss_sum, cnt = acc
        loss_sum = loss_sum + jnp.sum(
            jnp.where(valid, lse - gold, 0.0)
        )
        cnt = cnt + jnp.sum(valid.astype(jnp.float32))
        return (loss_sum, cnt), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ys)
    )
    return total / jnp.maximum(count, 1.0)
