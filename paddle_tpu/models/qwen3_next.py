"""Qwen3-Next decoder: Gated DeltaNet and gated attention layers over a
mixture of experts with a shared expert.

ref: HF transformers ``modeling_qwen3_next.py`` and the published
``config.json`` of Qwen/Qwen3-Next-80B-A3B-Instruct. With ``ZNorm(x; w) =
x rsqrt(mean(x^2) + eps) (1 + w)`` in float32, layer i is

    r = x + Mixer_i(ZNorm(x))         y = r + MoE(ZNorm(r))

where ``Mixer_i`` is gated attention when ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet otherwise, and every
layer's MLP is the expert layer. Final ZNorm, untied head, no bias.

  * Gated attention: the query projection carries an output gate (per
    head: query, then gate), q and k are ZNorm'ed per head, rope turns the
    first ``partial_rotary_factor`` of each head, the core is
    ``F.scaled_dot_product_attention`` (the flash kernels on a TPU), and
    ``out = (o * sigmoid(gate)) W_o``.
  * Gated DeltaNet: two projections whose weights keep HF's per-key-head
    interleaved columns (undone on the weight, ``_head_major``, so every
    activation is [b, t, heads * d] as the kernel reads it), a depthwise
    causal convolution with SiLU over q|k|v, the gated delta rule
    (``F.gated_delta_rule``: the chunked Pallas kernel on a TPU), RMSNorm
    gated by ``silu(z)``, an output projection.
  * MoE: ``incubate.moe.MoELayer`` told which experts it holds
    (``held_experts=(start, count)``: one expert-parallel rank's share;
    all of them by default), router in float32, top-k weights normalised
    over the k, a sigmoid-gated shared expert.

Left out: the multi-token-prediction module and a router balance
term. Training and plain logits
only: serving this family needs state snapshots beside the KV cache
(ROADMAP B-m4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops as F
from ..core import dispatch
from ..core.autograd import scope
from ..incubate.moe import MoELayer
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.parameter import ParamAttr


class Qwen3NextConfig:
    def __init__(
        self,
        vocab_size=151936,
        hidden_size=2048,
        num_hidden_layers=48,
        num_attention_heads=16,
        num_key_value_heads=2,
        head_dim=256,
        partial_rotary_factor=0.25,
        rope_theta=10000000.0,
        rms_norm_eps=1e-6,
        full_attention_interval=4,
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        num_experts=512,
        num_experts_per_tok=10,
        moe_intermediate_size=512,
        shared_expert_intermediate_size=512,
        norm_topk_prob=True,
        max_position_embeddings=262144,
        initializer_range=0.02,
        dtype="float32",
        held_experts=None,
        recompute=False,
        fused_loss_chunk=0,
    ):
        if not norm_topk_prob:
            raise NotImplementedError(
                "Qwen3NextConfig: norm_topk_prob=False is not implemented")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.full_attention_interval = full_attention_interval
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype
        # (start, count): the experts this rank holds of the num_experts
        # the router chooses among
        self.held_experts = tuple(held_experts or (0, num_experts))
        # jax.checkpoint each decoder layer
        self.recompute = recompute
        # >0: the LM head fused into the chunked loss, as LlamaConfig's
        self.fused_loss_chunk = fused_loss_chunk

    def is_attention_layer(self, i):
        return (i + 1) % self.full_attention_interval == 0

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config: one period of 3 DeltaNet + 1 attention."""
        base = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, max_position_embeddings=128,
        )
        base.update(overrides)
        return cls(**base)


def _normal(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


def _linear(config, n_in, n_out):
    return Linear(n_in, n_out, weight_attr=_normal(config), bias_attr=False)


class ZeroCenteredRMSNorm(Layer):
    """ZNorm: the weight is an offset from 1, initialised at 0."""

    def __init__(self, size, epsilon=1e-6):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[size], attr=ParamAttr(initializer=I.Constant(0.0)))

    def forward(self, x):
        return F.zero_centered_rms_norm(x, self.weight, self._epsilon)


class Qwen3NextGatedAttention(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d
        self.rotary_dim = int(d * config.partial_rotary_factor)
        self.rope_theta = config.rope_theta
        # per head: the query, then its output gate
        self.q_proj = _linear(config, h, self.num_heads * d * 2)
        self.k_proj = _linear(config, h, self.num_kv_heads * d)
        self.v_proj = _linear(config, h, self.num_kv_heads * d)
        self.o_proj = _linear(config, self.num_heads * d, h)
        self.q_norm = ZeroCenteredRMSNorm(d, config.rms_norm_eps)
        self.k_norm = ZeroCenteredRMSNorm(d, config.rms_norm_eps)

    def forward(self, hidden):
        b, s = hidden.shape[0], hidden.shape[1]
        heads, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        qg = F.reshape(self.q_proj(hidden), [b, s, heads, 2 * d])
        q, gate = qg[:, :, :, :d], qg[:, :, :, d:]
        k = F.reshape(self.k_proj(hidden), [b, s, kv, d])
        v = F.reshape(self.v_proj(hidden), [b, s, kv, d])
        q, k = F.partial_rope_qk(
            self.q_norm(q), self.k_norm(k), rotary_dim=self.rotary_dim,
            base=self.rope_theta)
        if kv != heads:
            k = F.repeat_interleave(k, heads // kv, axis=2)
            v = F.repeat_interleave(v, heads // kv, axis=2)
        out = F.scaled_dot_product_attention(q, k, v, None, 0.0, True)
        out = F.reshape(out * F.sigmoid(gate), [b, s, heads * d])
        return self.o_proj(out)


def _op(fn, *tensors, **attrs):
    """One tape entry for a jax.numpy function of this file."""
    return dispatch.call(
        "qwen3_next." + fn.__name__.lstrip("_"), fn, tensors, attrs)


def _remat(fn):
    """``fn`` rematerialised in the backward pass: what it keeps is then
    its inputs in the activations' dtype, not float32 copies of [batch,
    seq, channels] tensors (a gigabyte each at 4 x 8192 x 8192)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        return jax.checkpoint(functools.partial(fn, **kwargs))(*args)
    return wrapped


_HI = jax.lax.Precision.HIGHEST


def _head_major(weight, *, num_k_heads, widths):
    """A projection's weight [hidden, H_k * sum(widths)] with its columns
    in HF's per-key-head interleave (``fix_query_key_value_ordering``: per
    key head ``widths[0]`` columns of the first part, ``widths[1]`` of the
    next, ...) -> [hidden, H_k widths[0] | H_k widths[1] | ...]: part by
    part, each part's heads in order. The interleave is undone here, on
    the weight, once a forward (50 MB at the published widths, where every
    boundary is a multiple of 128 columns and whole lane blocks move), so
    that no activation is ever split by key head; the transpose puts the
    weight's gradient back in HF's order."""
    hidden = weight.shape[0]
    per_head = weight.reshape(hidden, num_k_heads, sum(widths))
    bounds = np.cumsum((0,) + tuple(widths))
    return jnp.concatenate(
        [per_head[:, :, lo:hi].reshape(hidden, -1)
         for lo, hi in zip(bounds, bounds[1:])], axis=-1)


def _head_indicator(heads, d):
    """[heads * d, heads] float32: 1 where the column is the head's."""
    return (jnp.arange(heads * d)[:, None] // d
            == jnp.arange(heads)).astype(jnp.float32)


def _head_sums(x, heads):
    """x [..., heads * d] float32 -> each head's sum [..., heads], as a
    product with the heads' indicator at ``HIGHEST``: a reshape to
    [..., heads, d] is a copy on a TPU (the tiles differ)."""
    return jnp.matmul(
        x, _head_indicator(heads, x.shape[-1] // heads), precision=_HI)


def _head_spread(x, d):
    """x [..., heads] float32 -> [..., heads * d], each head's value over
    its d columns: ``_head_sums``' transpose, exact at ``HIGHEST``."""
    return jnp.matmul(
        x, _head_indicator(x.shape[-1], d).T, precision=_HI)


@_remat
def _causal_conv_silu(x, weight):
    """Depthwise causal convolution along t, then SiLU. x [b, t, c],
    weight [w, c]: ``c_t = sum_j weight[j] * x[t - (w - 1) + j]``."""
    width, t = weight.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    wf = weight.astype(jnp.float32)
    out = sum(wf[j] * xf[:, j:j + t] for j in range(width))
    return jax.nn.silu(out).astype(x.dtype)


@_remat
def _delta_rule_inputs(qk, b, a, a_log, dt_bias, *, num_k_heads, head_k_dim):
    """The convolved q | k channels and the gate projections -> what the
    delta rule takes beside v: q, k [b, t, H_k d_k] (its flat form)
    L2-normalised over each head's d_k (eps 1e-6, as the source's
    ``l2norm``) with q scaled by d_k^-1/2, g = -exp(A_log) softplus(a +
    dt_bias) and beta = sigmoid(b), both float32 [b, t, H_v]."""
    q, k = jnp.split(qk, 2, axis=-1)

    def l2norm(y):
        yf = y.astype(jnp.float32)
        return yf * _head_spread(jax.lax.rsqrt(
            _head_sums(yf * yf, num_k_heads) + 1e-6), head_k_dim)

    f32 = jnp.float32
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))
    return ((l2norm(q) * head_k_dim ** -0.5).astype(qk.dtype),
            l2norm(k).astype(qk.dtype), g, jax.nn.sigmoid(b.astype(f32)))


@_remat
def _gated_rms_norm(x, weight, gate, *, epsilon):
    """RMSNorm(x; weight) * silu(gate) over each head's columns, in
    float32 (plain weight [d], initialised at 1): the mixer's output norm.
    x and gate [b, t, heads * d], as the delta rule hands o over."""
    d = weight.shape[0]
    heads = x.shape[-1] // d
    xf = x.astype(jnp.float32)
    var = _head_sums(jnp.square(xf), heads) / d
    out = jnp.tile(weight.astype(jnp.float32), heads) * (
        xf * _head_spread(jax.lax.rsqrt(var + epsilon), d))
    return (out * jax.nn.silu(gate.astype(jnp.float32))).astype(x.dtype)


class Qwen3NextGatedDeltaNet(Layer):
    """From ``in_proj_qkvz`` to ``out_proj`` every activation is [b, t,
    heads * d], a head a block of consecutive columns: the form the
    delta-rule kernels read in place. The parameters keep HF's shapes and
    column order."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        h = config.hidden_size
        self.num_k_heads = hk = config.linear_num_key_heads
        self.num_v_heads = hv = config.linear_num_value_heads
        self.head_k_dim = config.linear_key_head_dim
        self.head_v_dim = config.linear_value_head_dim
        key_dim, value_dim = hk * self.head_k_dim, hv * self.head_v_dim
        self._epsilon = config.rms_norm_eps
        # the layer's own parameters before its sublayers': creation order
        # is then the order parameters() lists them in
        self.conv_weight = self.create_parameter(
            shape=[config.linear_conv_kernel_dim, 2 * key_dim + value_dim],
            attr=_normal(config))
        self.dt_bias = self.create_parameter(
            shape=[hv], attr=ParamAttr(initializer=I.Constant(1.0)))
        # A = exp(A_log) drawn from U(0, 16), as the source initialises it
        self.A_log = self.create_parameter(
            shape=[hv], attr=ParamAttr(initializer=I.Assign(np.log(
                np.random.default_rng(0).uniform(1e-3, 16.0, hv)
            ).astype("float32"))))
        self.norm_weight = self.create_parameter(
            shape=[self.head_v_dim],
            attr=ParamAttr(initializer=I.Constant(1.0)))
        # columns per key head: q | k | v of its value heads | z of them,
        # and b of them | a of them
        self.in_proj_qkvz = _linear(config, h, 2 * key_dim + 2 * value_dim)
        self.in_proj_ba = _linear(config, h, 2 * hv)
        self.out_proj = _linear(config, value_dim, h)

    def forward(self, hidden):
        hk, hv = self.num_k_heads, self.num_v_heads
        dk, dv = self.head_k_dim, self.head_v_dim
        rep, keys, channels = hv // hk, 2 * hk * dk, 2 * hk * dk + hv * dv
        qkvz = F.linear(hidden, _op(
            _head_major, self.in_proj_qkvz.weight, num_k_heads=hk,
            widths=(dk, dk, rep * dv, rep * dv)))
        ba = F.linear(hidden, _op(
            _head_major, self.in_proj_ba.weight, num_k_heads=hk,
            widths=(rep, rep)))
        # q | k and v convolved apart (the convolution is depthwise): v is
        # then an array of its own, as a kernel's operand has to be, and
        # not a slice that is copied out
        qk = _op(_causal_conv_silu, qkvz[:, :, :keys],
                 self.conv_weight[:, :keys])
        v = _op(_causal_conv_silu, qkvz[:, :, keys:channels],
                self.conv_weight[:, keys:])
        q, k, g, beta = _op(
            _delta_rule_inputs, qk, ba[:, :, :hv], ba[:, :, hv:],
            self.A_log, self.dt_bias, num_k_heads=hk, head_k_dim=dk)
        o = F.gated_delta_rule(q, k, v, g, beta, num_k_heads=hk)
        o = _op(_gated_rms_norm, o, self.norm_weight, qkvz[:, :, channels:],
                epsilon=self._epsilon)
        return self.out_proj(o)


class Qwen3NextMLP(Layer):
    """SwiGLU over flattened tokens [n, hidden]: the shared expert."""

    def __init__(self, config, width):
        super().__init__()
        self.gate_proj = _linear(config, config.hidden_size, width)
        self.up_proj = _linear(config, config.hidden_size, width)
        self.down_proj = _linear(config, width, config.hidden_size)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, config: Qwen3NextConfig, index):
        super().__init__()
        self.is_attention = config.is_attention_layer(index)
        self.input_layernorm = ZeroCenteredRMSNorm(
            config.hidden_size, config.rms_norm_eps)
        if self.is_attention:
            self.self_attn = Qwen3NextGatedAttention(config)
        else:
            self.linear_attn = Qwen3NextGatedDeltaNet(config)
        self.post_attention_layernorm = ZeroCenteredRMSNorm(
            config.hidden_size, config.rms_norm_eps)
        self.mlp = MoELayer(
            config.hidden_size, config.num_experts,
            d_ff=config.moe_intermediate_size, k=config.num_experts_per_tok,
            held=config.held_experts, router_dtype="float32",
            shared_expert=lambda: Qwen3NextMLP(
                config, config.shared_expert_intermediate_size))

    def forward(self, hidden):
        """-> (hidden, expert_load): the load leaves the layer as a value
        because a recomputed layer cannot write the buffer itself."""
        if self.is_attention:
            with scope("attention"):
                hidden = hidden + self.self_attn(
                    self.input_layernorm(hidden))
        else:
            with scope("linear_attention"):
                hidden = hidden + self.linear_attn(
                    self.input_layernorm(hidden))
        with scope("moe"):
            out, _, stats = self.mlp(
                self.post_attention_layernorm(hidden), return_stats=True)
            return hidden + out, stats["expert_load"]


class Qwen3NextModel(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=_normal(config))
        self.layers = LayerList([
            Qwen3NextDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = ZeroCenteredRMSNorm(
            config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        with scope("embedding"):
            hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            if self.config.recompute:
                from ..distributed.recompute import recompute as _rc

                hidden, load = _rc(layer, hidden)
            else:
                hidden, load = layer(hidden)
            layer.mlp.record_load(load)
        with scope("lm_head_loss"):
            return self.norm(hidden)


class Qwen3NextForCausalLM(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = _linear(config, config.hidden_size, config.vocab_size)

    def forward(self, input_ids, labels=None):
        """``labels=None``: logits. With labels: ``(logits, loss)``, or
        ``(None, loss)`` when ``config.fused_loss_chunk > 0`` (the head is
        fused into the chunked loss and the [b, s, vocab] logits never
        exist): LlamaForCausalLM's contract."""
        hidden = self.model(input_ids)
        with scope("lm_head_loss"):
            if labels is not None and self.config.fused_loss_chunk > 0:
                h = hidden.shape[-1]
                return None, F.fused_linear_cross_entropy(
                    F.reshape(hidden[:, :-1], [-1, h]), self.lm_head.weight,
                    F.reshape(labels[:, 1:], [-1]),
                    chunk_size=self.config.fused_loss_chunk)
            logits = self.lm_head(hidden)
            if labels is None:
                return logits
            v = logits.shape[-1]
            return logits, F.cross_entropy(
                F.reshape(logits[:, :-1], [-1, v]),
                F.reshape(labels[:, 1:], [-1]))

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())
