"""The operations the Qwen3-Next block brought that another block could
use: the zero-centred RMSNorm, partial rotary embedding, the gated delta
rule (kernels/pallas/gated_delta_rule.py), and its per-channel decay
form, Kimi Delta Attention (kernels/pallas/kda.py). What only that block's
DeltaNet mixer needs (HF's interleaved layout, its convolution, gates and
output norm) is private to models/qwen3_next.py.

ref: HF transformers ``modeling_qwen3_next.py`` (Qwen3NextRMSNorm,
apply_rotary_pos_emb with ``partial_rotary_factor``). Each is one tape
entry; the norm is computed in float32 and cast back, as the source does,
and rematerialised in the backward pass (``jax.checkpoint``): what it
keeps is then its input in the activations' dtype, not a float32 copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .fused_ops import rope_qk


def zero_centered_rms_norm(x, weight, *, epsilon=1e-6):
    """x * rsqrt(mean(x^2) + eps) * (1 + weight) over the last axis, in
    float32 (the weight is initialised at 0)."""
    @jax.checkpoint
    def norm(x, weight):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), -1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + epsilon) * (
            1.0 + weight.astype(jnp.float32))
        return out.astype(x.dtype)

    return norm(x, weight)


def partial_rope_qk(q, k, *, rotary_dim, base=10000.0):
    """Rotate-half rope on the first ``rotary_dim`` dims of each head of
    q and k [b, s, heads, d] (frequencies over ``rotary_dim``), the rest
    untouched: ``partial_rotary_factor``."""
    qr, kr = rope_qk(q[..., :rotary_dim], k[..., :rotary_dim], base=base)
    return (jnp.concatenate([qr, q[..., rotary_dim:]], -1),
            jnp.concatenate([kr, k[..., rotary_dim:]], -1))


def gated_delta_rule(q, k, v, g, beta, *, chunk=64, impl="auto",
                     num_k_heads=None):
    """The public op face of ``kernels.pallas.gated_delta_rule`` (Pallas
    imports stay function-scoped, the nn_ops pattern): q, k, v as
    [b, t, heads, d], or as [b, t, heads * d] with ``num_k_heads``."""
    from ...kernels.pallas.gated_delta_rule import gated_delta_rule as _gdr

    return _gdr(q, k, v, g, beta, chunk, impl=impl, num_k_heads=num_k_heads)


def kda(q, k, v, g, beta, *, chunk=64, impl="auto", num_heads=None):
    """The public op face of ``kernels.pallas.kda``: q, k, g (a log-decay
    per key channel) and v as [b, t, heads, d], or as [b, t, heads * d]
    with ``num_heads``."""
    from ...kernels.pallas.kda import kda as _kda

    return _kda(q, k, v, g, beta, chunk, impl=impl, num_heads=num_heads)
