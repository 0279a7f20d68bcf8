"""Granite 4.0-H decoder: Mamba-2 state-space layers with grouped-query
attention layers among them, on Granite's scaled residual path.

ref: HF transformers ``modeling_granitemoehybrid.py`` and the published
``config.json`` of ibm-granite/granite-4.0-h-micro (``model_type``
``granitemoehybrid``, no routed experts). With ``h`` the residual stream
and RMSNorm (eps 1e-5) in float32::

    h0 = embedding_multiplier * E[ids]
    h  = h + residual_multiplier * Mixer_i(norm1(h))
    h  = h + residual_multiplier * MLP(norm2(h))
    logits = (E norm_f(h)) / logits_scaling          (tied embedding)

``Mixer_i`` is chosen by ``layer_types[i]``:

  * ``"attention"``: q, k, v, o without bias, grouped-query heads, **no
    rotary embedding** (``position_embedding_type`` ``nope``), causal
    softmax of ``attention_multiplier * q k^T`` (not ``d^-1/2``) through
    ``F.scaled_dot_product_attention(scale=)`` (the flash kernels on a
    TPU).
  * ``"mamba"`` (Mamba-2, HF's ``GraniteMoeHybridMambaLayer``): ``[z | x B
    C | dt] = W_in u``; ``[x | B | C] = silu(conv(xBC) + b_conv)``, the
    convolution depthwise, causal, ``mamba_d_conv`` taps; ``dt =
    softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the selective scan
    ``H_t = exp(dt_t A_j) H_{t-1} + dt_t B_t (x) x_t``, ``y_t = C_t^T H_t
    + D_j x_t`` (``F.mamba2_ssd``: the chunked Pallas kernels on a TPU);
    ``y = RMSNorm(y * silu(z)) * w`` over the whole inner width, the gate
    before the norm; ``out = W_out y``.
  * the MLP is SwiGLU from one in-projection: ``W_out (silu(a) * b)`` with
    ``[a | b] = W_in x``.

From ``in_proj`` to ``out_proj`` every activation of the Mamba mixer is
[b, t, channels], a head a block of consecutive columns: the form the SSD
kernels read in place; dt, A and D are per-head vectors the kernel spreads
itself. x, B and C are convolved apart (the convolution is depthwise), so
each leaves its convolution as an array of its own, as a kernel's operand
has to be, and not as a slice of [b, t, 4352] that is copied out.

Departures from the source, each for a stated reason: the convolution's
weight is [taps, channels] (the source's Conv1d holds [channels, 1, taps]);
``time_step_limit`` (0, inf) is a no-op and left out; the gated norm with
``mamba_n_groups`` > 1 normalises over the whole width as HF's
``GraniteMoeHybridRMSNormGated`` does; routed experts (``num_local_experts``
> 0, the family's larger models) and a rotary embedding are refused, not
guessed. The convolution and the gated norm are this file's own and not
``models/qwen3_next.py``'s: that convolution has no bias, and that norm is
taken per head with the gate after it. Training and plain logits only:
serving a state-space layer needs a single-token scan step and a
convolution state beside the KV pages (ROADMAP B-m1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops as F
from ..core import dispatch
from ..core.autograd import scope
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.parameter import ParamAttr
from .qwen3_next import _remat

MAMBA, ATTENTION = "mamba", "attention"


class GraniteHybridConfig:
    def __init__(
        self,
        vocab_size=100352,
        hidden_size=2048,
        shared_intermediate_size=8192,
        num_hidden_layers=40,
        layer_types=None,
        num_attention_heads=32,
        num_key_value_heads=8,
        mamba_n_heads=64,
        mamba_d_head=64,
        mamba_d_state=128,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_n_groups=1,
        mamba_chunk_size=256,
        mamba_conv_bias=True,
        mamba_proj_bias=False,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.015625,
        logits_scaling=8.0,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        num_local_experts=0,
        position_embedding_type="nope",
        initializer_range=0.02,
        recompute=False,
        fused_loss_chunk=0,
    ):
        if layer_types is None:
            # the published pattern: layers 5, 15, 25 and 35 of 40 attend
            layer_types = [ATTENTION if i % 10 == 5 else MAMBA
                           for i in range(num_hidden_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_hidden_layers or (
                set(layer_types) - {MAMBA, ATTENTION}):
            raise ValueError(
                f"GraniteHybridConfig: layer_types {layer_types} is not "
                f"{num_hidden_layers} of 'mamba' / 'attention'")
        if num_local_experts:
            raise NotImplementedError(
                "GraniteHybridConfig: routed experts (num_local_experts > "
                "0) are not implemented")
        if position_embedding_type != "nope":
            raise NotImplementedError(
                "GraniteHybridConfig: position_embedding_type "
                f"{position_embedding_type!r}; only 'nope' is implemented")
        if not (tie_word_embeddings and mamba_conv_bias) or mamba_proj_bias:
            raise NotImplementedError(
                "GraniteHybridConfig: only the published form is "
                "implemented (tied head, a convolution bias, no projection "
                "bias)")
        if mamba_n_heads * mamba_d_head != mamba_expand * hidden_size:
            raise ValueError(
                f"GraniteHybridConfig: {mamba_n_heads} heads x "
                f"{mamba_d_head} is not mamba_expand x hidden_size = "
                f"{mamba_expand * hidden_size}")
        if mamba_n_heads % mamba_n_groups or (
                hidden_size % num_attention_heads) or (
                num_attention_heads % num_key_value_heads):
            raise ValueError(
                "GraniteHybridConfig: heads do not divide into groups")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.shared_intermediate_size = shared_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_n_groups = mamba_n_groups
        self.mamba_chunk_size = mamba_chunk_size
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.attention_multiplier = attention_multiplier
        self.logits_scaling = logits_scaling
        self.rms_norm_eps = rms_norm_eps
        self.initializer_range = initializer_range
        # jax.checkpoint each decoder layer
        self.recompute = recompute
        # >0: the tied LM head fused into the chunked loss, as LlamaConfig's
        self.fused_loss_chunk = fused_loss_chunk

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config: two Mamba layers around one attention layer,
        4 Mamba heads x 8 with a state of 16 in chunks of 8."""
        base = dict(
            vocab_size=128, hidden_size=16, shared_intermediate_size=32,
            num_hidden_layers=3, layer_types=[MAMBA, ATTENTION, MAMBA],
            num_attention_heads=2, num_key_value_heads=1, mamba_n_heads=4,
            mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
            attention_multiplier=0.25,
        )
        base.update(overrides)
        return cls(**base)


def _normal(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


def _linear(config, n_in, n_out):
    return Linear(n_in, n_out, weight_attr=_normal(config), bias_attr=False)


def _op(fn, *tensors, **attrs):
    """One tape entry for a jax.numpy function of this file."""
    return dispatch.call(
        "granite_hybrid." + fn.__name__.lstrip("_"), fn, tensors, attrs)


@_remat
def _causal_conv_bias_silu(x, weight, bias):
    """Depthwise causal convolution along t with a bias, then SiLU, in
    float32. x [b, t, c], weight [w, c], bias [c]: ``c_t = bias + sum_j
    weight[j] * x[t - (w - 1) + j]``."""
    width, t = weight.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    wf = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32) + sum(
        wf[j] * xf[:, j:j + t] for j in range(width))
    return jax.nn.silu(out).astype(x.dtype)


@_remat
def _step_and_decay(dt, dt_bias, a_log):
    """The projection's dt columns [b, t, H] and the two per-head leaves ->
    what the scan takes: dt = softplus(dt + dt_bias) float32 [b, t, H] and
    A = -exp(A_log) float32 [H]."""
    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
            -jnp.exp(a_log.astype(f32)))


@_remat
def _gated_rms_norm(y, gate, weight, *, epsilon):
    """RMSNorm(y * silu(gate); weight) over the whole last axis, in
    float32: the gate before the norm, as the source's
    ``GraniteMoeHybridRMSNormGated``."""
    g = y.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), -1, keepdims=True)
    return (g * jax.lax.rsqrt(var + epsilon)
            * weight.astype(jnp.float32)).astype(y.dtype)


class GraniteHybridMamba(Layer):
    """Mamba-2 mixer. Every activation between ``in_proj`` and ``out_proj``
    is [b, t, channels]; nothing is reshaped to [b, t, heads, d_head]."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.mamba_n_heads
        self.d_inner = config.mamba_n_heads * config.mamba_d_head
        self.n_groups = config.mamba_n_groups
        self.group_state = config.mamba_n_groups * config.mamba_d_state
        self.chunk = config.mamba_chunk_size
        self._epsilon = config.rms_norm_eps
        channels = self.d_inner + 2 * self.group_state
        # the layer's own parameters before its sublayers': creation order
        # is then the order parameters() lists them in
        self.conv_weight = self.create_parameter(
            shape=[config.mamba_d_conv, channels], attr=_normal(config))
        self.conv_bias = self.create_parameter(
            shape=[channels], attr=ParamAttr(initializer=I.Constant(0.0)))
        self.dt_bias = self.create_parameter(
            shape=[self.num_heads],
            attr=ParamAttr(initializer=I.Constant(1.0)))
        # A = 1 .. heads, as the source initialises it
        self.A_log = self.create_parameter(
            shape=[self.num_heads], attr=ParamAttr(initializer=I.Assign(
                np.log(np.arange(1, self.num_heads + 1)).astype("float32"))))
        self.D = self.create_parameter(
            shape=[self.num_heads],
            attr=ParamAttr(initializer=I.Constant(1.0)))
        self.norm_weight = self.create_parameter(
            shape=[self.d_inner], attr=ParamAttr(initializer=I.Constant(1.0)))
        # columns: z | x | B | C | dt
        self.in_proj = _linear(config, h, self.d_inner + channels
                               + self.num_heads)
        self.out_proj = _linear(config, self.d_inner, h)

    def forward(self, hidden):
        di, gs = self.d_inner, self.group_state
        zxbcdt = self.in_proj(hidden)
        bounds = (di, 2 * di, 2 * di + gs, 2 * di + 2 * gs)
        # x, B and C convolved apart: each an array of its own
        x, b, c = (
            _op(_causal_conv_bias_silu, zxbcdt[:, :, lo:hi],
                self.conv_weight[:, lo - di:hi - di],
                self.conv_bias[lo - di:hi - di])
            for lo, hi in zip(bounds, bounds[1:]))
        dt, a = _op(_step_and_decay, zxbcdt[:, :, bounds[-1]:],
                    self.dt_bias, self.A_log)
        y = F.mamba2_ssd(x, dt, a, b, c, self.D, chunk=self.chunk,
                         n_groups=self.n_groups)
        y = _op(_gated_rms_norm, y, zxbcdt[:, :, :di], self.norm_weight,
                epsilon=self._epsilon)
        return self.out_proj(y)


class GraniteHybridAttention(Layer):
    """Grouped-query attention without a position embedding, the scores
    scaled by ``attention_multiplier``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = h // config.num_attention_heads
        self.scale = config.attention_multiplier
        self.q_proj = _linear(config, h, self.num_heads * self.head_dim)
        self.k_proj = _linear(config, h, self.num_kv_heads * self.head_dim)
        self.v_proj = _linear(config, h, self.num_kv_heads * self.head_dim)
        self.o_proj = _linear(config, self.num_heads * self.head_dim, h)

    def forward(self, hidden):
        b, s = hidden.shape[0], hidden.shape[1]
        heads, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = F.reshape(self.q_proj(hidden), [b, s, heads, d])
        k = F.reshape(self.k_proj(hidden), [b, s, kv, d])
        v = F.reshape(self.v_proj(hidden), [b, s, kv, d])
        if kv != heads:
            k = F.repeat_interleave(k, heads // kv, axis=2)
            v = F.repeat_interleave(v, heads // kv, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, None, 0.0, True, scale=self.scale)
        return self.o_proj(F.reshape(out, [b, s, heads * d]))


class GraniteHybridMLP(Layer):
    """SwiGLU from one in-projection: [a | b] = W_in x."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.width = config.shared_intermediate_size
        self.input_linear = _linear(config, config.hidden_size,
                                    2 * self.width)
        self.output_linear = _linear(config, self.width, config.hidden_size)

    def forward(self, x):
        ab = self.input_linear(x)
        return self.output_linear(
            F.swiglu(ab[:, :, :self.width], ab[:, :, self.width:]))


class GraniteHybridDecoderLayer(Layer):
    def __init__(self, config: GraniteHybridConfig, index):
        super().__init__()
        self.is_attention = config.layer_types[index] == ATTENTION
        self.residual_multiplier = config.residual_multiplier
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        if self.is_attention:
            self.self_attn = GraniteHybridAttention(config)
        else:
            self.mamba = GraniteHybridMamba(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.shared_mlp = GraniteHybridMLP(config)

    def forward(self, hidden):
        if self.is_attention:
            with scope("attention"):
                hidden = hidden + self.residual_multiplier * self.self_attn(
                    self.input_layernorm(hidden))
        else:
            with scope("state_space"):
                hidden = hidden + self.residual_multiplier * self.mamba(
                    self.input_layernorm(hidden))
        with scope("mlp"):
            return hidden + self.residual_multiplier * self.shared_mlp(
                self.post_attention_layernorm(hidden))


class GraniteHybridModel(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=_normal(config))
        self.layers = LayerList([
            GraniteHybridDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        with scope("embedding"):
            hidden = self.embed_tokens(input_ids) * (
                self.config.embedding_multiplier)
        for layer in self.layers:
            if self.config.recompute:
                from ..distributed.recompute import recompute as _rc

                hidden = _rc(layer, hidden)
            else:
                hidden = layer(hidden)
        with scope("lm_head_loss"):
            return self.norm(hidden)


class GraniteHybridForCausalLM(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteHybridModel(config)

    def forward(self, input_ids, labels=None):
        """``labels=None``: logits. With labels: ``(logits, loss)``, or
        ``(None, loss)`` when ``config.fused_loss_chunk > 0`` (the tied
        head is fused into the chunked loss and the [b, s, vocab] logits
        never exist): LlamaForCausalLM's contract."""
        hidden = self.model(input_ids)
        with scope("lm_head_loss"):
            # logits = (E h) / logits_scaling, taken on h
            hidden = hidden * (1.0 / self.config.logits_scaling)
            embedding = self.model.embed_tokens.weight
            if labels is not None and self.config.fused_loss_chunk > 0:
                h = hidden.shape[-1]
                return None, F.fused_linear_cross_entropy(
                    F.reshape(hidden[:, :-1], [-1, h]),
                    F.transpose(embedding, [1, 0]),
                    F.reshape(labels[:, 1:], [-1]),
                    chunk_size=self.config.fused_loss_chunk)
            logits = F.matmul(hidden, embedding, transpose_y=True)
            if labels is None:
                return logits
            v = logits.shape[-1]
            return logits, F.cross_entropy(
                F.reshape(logits[:, :-1], [-1, v]),
                F.reshape(labels[:, 1:], [-1]))

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
