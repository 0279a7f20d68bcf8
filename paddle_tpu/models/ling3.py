"""Ling 3.0 decoder (``bailing_hybrid``): Kimi Delta Attention in five of
every six layers and latent attention (MLA) in the sixth, over a stack
whose first layers are dense and whose others are expert layers behind a
group-limited sigmoid router.

ref: the published ``config.json`` of inclusionAI/Ling-3.0-flash and the
Kimi Linear report (arXiv 2510.26692) for KDA's equations. With
``Norm(x; w) = x rsqrt(mean(x^2) + eps) w`` in float32, layer i is

    r = x + Mixer_i(Norm(x))            y = r + MLP_i(Norm(r))

where ``Mixer_i`` is MLA when ``(i + 1) % layer_group_size == 0`` and KDA
otherwise, ``i`` counted in the published stack (``layer_ids`` names the
published index of each layer held here; all of them by default).

  * KDA: q, k, v are three products, each convolved along t by its own
    depthwise causal convolution of ``short_conv_kernel_size`` and passed
    through SiLU; q and k are L2-normalised per head (q scaled by
    d_k^-1/2); ``beta = sigmoid(x W_b)`` a head; the decay is one
    log-value per key channel, ``g = kda_lower_bound * sigmoid(exp(A_log)
    (x W_f + dt_bias))`` (the safe gate, ``no_kda_lora``: W_f one full
    product); ``F.kda`` runs the recurrence (the chunked Pallas kernels on
    a TPU); ``out = W_o(RMSNorm_head(o) * sigmoid(x W_gate)_h)``, a gate a
    head. Every activation is [b, t, heads * d], a head a 128-lane column
    block, as the kernels read it; the per-head sums are products with the
    heads' indicator (qwen3_next's helpers).
  * MLA: deepseek_v3's block (no query down-projection, the shared rotary
    key never broadcast, the weights reordered once a forward), with
    ``use_qk_norm``: each head's query (nope | rope, 192 columns), each
    head's key_nope (128) and the shared rotary key (64) RMS-normalised
    before rope, and the output gated a head like KDA's.
  * MLP_i: dense SwiGLU for published ``i < first_k_dense_replace``, else
    ``incubate.moe.MoELayer`` told which experts it holds,
    ``scoring="sigmoid"`` with the selection bias, ``n_group`` groups of
    which a token keeps ``topk_group``, weights normalised over the k and
    scaled by ``routed_scaling_factor``, plus ``num_shared_experts``
    ungated shared experts as one SwiGLU.

Left out: the multi-token-prediction layer (``mtp_loss_scaling_factor``
0), the SwiGLU clamps of the late layers, a sequence-wise balance term,
the selection bias's update rule, and serving. Training and plain logits
only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import ops as F
from ..core import dispatch
from ..core.autograd import scope
from ..incubate.moe import MoELayer
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.parameter import ParamAttr
from .deepseek_v3 import _part_major, _rope_tail, _rotate_half_order
from .qwen3_next import _causal_conv_silu, _head_spread, _head_sums, _remat


class Ling3Config:
    def __init__(
        self,
        vocab_size=157184,
        hidden_size=2560,
        intermediate_size=6144,
        moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768,
        num_hidden_layers=42,
        num_attention_heads=32,
        head_dim=128,
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=6000000.0,
        rope_interleave=True,
        rope_scaling=None,
        rms_norm_eps=1e-6,
        first_k_dense_replace=2,
        layer_group_size=6,
        num_experts=512,
        num_experts_per_tok=8,
        num_shared_experts=1,
        n_group=8,
        topk_group=4,
        norm_topk_prob=True,
        routed_scaling_factor=2.5,
        score_function="sigmoid",
        use_qk_norm=True,
        short_conv_kernel_size=4,
        kda_lower_bound=-5.0,
        kda_safe_gate=True,
        no_kda_lora=True,
        gated_attention_proj_granularity_type="head_wise",
        max_position_embeddings=262144,
        initializer_range=0.02,
        dtype="float32",
        layer_ids=None,
        held_experts=None,
        recompute=False,
        fused_loss_chunk=0,
    ):
        for name, value, only in (
                ("q_lora_rank", q_lora_rank, None),
                ("rope_scaling", rope_scaling, None),
                ("score_function", score_function, "sigmoid"),
                ("use_qk_norm", use_qk_norm, True),
                ("kda_safe_gate", kda_safe_gate, True),
                ("no_kda_lora", no_kda_lora, True),
                ("gated_attention_proj_granularity_type",
                 gated_attention_proj_granularity_type, "head_wise")):
            if value != only:
                raise NotImplementedError(
                    f"Ling3Config: {name}={value!r} is not implemented "
                    f"(only {only!r})")
        from ..kernels.pallas.kda import LOWER_BOUND

        if kda_lower_bound < LOWER_BOUND:
            raise NotImplementedError(
                f"Ling3Config: kda_lower_bound={kda_lower_bound} is below "
                f"the KDA kernels' floor {LOWER_BOUND}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = (
            moe_shared_expert_intermediate_size)
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_interleave = rope_interleave
        self.rms_norm_eps = rms_norm_eps
        self.first_k_dense_replace = first_k_dense_replace
        self.layer_group_size = layer_group_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.n_group = n_group
        self.topk_group = topk_group
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.short_conv_kernel_size = short_conv_kernel_size
        self.kda_lower_bound = float(kda_lower_bound)
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype
        # the published index of each layer held here (a pipeline stage
        # holds some of them)
        self.layer_ids = tuple(layer_ids or range(num_hidden_layers))
        if len(self.layer_ids) != num_hidden_layers:
            raise ValueError(
                f"Ling3Config: {len(self.layer_ids)} layer_ids for "
                f"{num_hidden_layers} layers")
        # (start, count): the experts this rank holds of the num_experts
        # the router chooses among
        self.held_experts = tuple(held_experts or (0, num_experts))
        # jax.checkpoint each decoder layer
        self.recompute = recompute
        # >0: the LM head fused into the chunked loss, as LlamaConfig's
        self.fused_loss_chunk = fused_loss_chunk

    def is_latent_attention_layer(self, i):
        return (self.layer_ids[i] + 1) % self.layer_group_size == 0

    def is_expert_layer(self, i):
        return self.layer_ids[i] >= self.first_k_dense_replace

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config: one dense KDA layer, then a period of six
        expert layers (five KDA, one MLA) over 4 groups of 4 experts."""
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
            num_hidden_layers=7, num_attention_heads=2, head_dim=16,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, first_k_dense_replace=2, layer_group_size=6,
            num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
            max_position_embeddings=128,
            layer_ids=(0, 6, 7, 8, 9, 10, 11),
        )
        base.update(overrides)
        return cls(**base)


def _normal(config):
    return ParamAttr(initializer=I.Normal(0.0, config.initializer_range))


def _constant(value):
    return ParamAttr(initializer=I.Constant(value))


def _linear(config, n_in, n_out):
    return Linear(n_in, n_out, weight_attr=_normal(config), bias_attr=False)


def _op(fn, *tensors, **attrs):
    """One tape entry for a jax.numpy function of this file."""
    return dispatch.call(
        "ling3." + fn.__name__.lstrip("_"), fn, tensors, attrs)


def _l2norm_heads(y, heads):
    """Each head's columns over their L2 norm (eps 1e-6 inside the root),
    in float32."""
    yf = y.astype(jnp.float32)
    return yf * _head_spread(jax.lax.rsqrt(
        _head_sums(yf * yf, heads) + 1e-6), y.shape[-1] // heads)


@_remat
def _kda_inputs(q, k, f, b, a_log, dt_bias, *, heads, lower_bound):
    """The convolved q and k and the gate projections -> what KDA takes
    beside v: q, k [b, t, H d_k] L2-normalised a head, q scaled by
    d_k^-1/2; g = lower_bound sigmoid(exp(A_log) (f + dt_bias)) float32
    [b, t, H d_k], a log-decay per key channel; beta = sigmoid(b) float32
    [b, t, H]."""
    d = q.shape[-1] // heads
    f32 = jnp.float32
    rate = _head_spread(jnp.exp(a_log.astype(f32))[None, None], d)
    g = lower_bound * jax.nn.sigmoid(
        rate * (f.astype(f32) + dt_bias.astype(f32)))
    return ((_l2norm_heads(q, heads) * d ** -0.5).astype(q.dtype),
            _l2norm_heads(k, heads).astype(k.dtype), g,
            jax.nn.sigmoid(b.astype(f32)))


@_remat
def _gated_head_norm(x, weight, gate, *, epsilon):
    """RMSNorm(x; weight) over each head's columns times sigmoid(gate) of
    its head, in float32: x [b, t, heads * d], weight [d] (one for all
    heads), gate [b, t, heads]."""
    d = weight.shape[0]
    heads = x.shape[-1] // d
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(_head_sums(jnp.square(xf), heads) / d + epsilon)
    scale = scale * jax.nn.sigmoid(gate.astype(jnp.float32))
    out = jnp.tile(weight.astype(jnp.float32), heads) * (
        xf * _head_spread(scale, d))
    return out.astype(x.dtype)


@_remat
def _head_gate(x, gate):
    """x [b, t, heads * d] times sigmoid(gate) [b, t, heads] of its head."""
    d = x.shape[-1] // gate.shape[-1]
    return (x.astype(jnp.float32) * _head_spread(
        jax.nn.sigmoid(gate.astype(jnp.float32)), d)).astype(x.dtype)


@_remat
def _query_norm(q_nope, q_rope, w_nope, w_rope, *, heads, epsilon):
    """``use_qk_norm`` on the query: each head's nope | rope columns (two
    arrays, [b, t, heads * 128] and [b, t, heads * 64]) over their joint
    RMS, weights w_nope [128] and w_rope [64] (the source's one weight of
    192, split)."""
    nf, rf = q_nope.astype(jnp.float32), q_rope.astype(jnp.float32)
    width = w_nope.shape[0] + w_rope.shape[0]
    scale = jax.lax.rsqrt((_head_sums(nf * nf, heads)
                           + _head_sums(rf * rf, heads)) / width + epsilon)
    n = jnp.tile(w_nope.astype(jnp.float32), heads) * (
        nf * _head_spread(scale, w_nope.shape[0]))
    r = jnp.tile(w_rope.astype(jnp.float32), heads) * (
        rf * _head_spread(scale, w_rope.shape[0]))
    return n.astype(q_nope.dtype), r.astype(q_rope.dtype)


def _rope_order(w, *, interleaved):
    """A norm's weight over rotary columns in the order the projections'
    rotary columns are put in (rotate-half where the source interleaves)."""
    return _rotate_half_order(w) if interleaved else w


@_remat
def _heads_rms_norm(x, weight, *, epsilon):
    """RMSNorm over each head's columns of x [b, t, heads * d] with one
    weight [d], in float32."""
    d = weight.shape[0]
    heads = x.shape[-1] // d
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(_head_sums(xf * xf, heads) / d + epsilon)
    return (jnp.tile(weight.astype(jnp.float32), heads)
            * (xf * _head_spread(scale, d))).astype(x.dtype)


class Ling3KDA(Layer):
    """From the projections to ``o_proj`` every activation is [b, t, heads
    * d], a head a block of consecutive columns: the form the KDA kernels
    read in place."""

    def __init__(self, config: Ling3Config):
        super().__init__()
        h = config.hidden_size
        self.num_heads = heads = config.num_attention_heads
        self.head_dim = d = config.head_dim
        width = heads * d
        self._epsilon = config.rms_norm_eps
        self._lower_bound = config.kda_lower_bound
        # the layer's own parameters before its sublayers': creation order
        # is then the order parameters() lists them in
        conv = [config.short_conv_kernel_size, width]
        self.q_conv1d = self.create_parameter(shape=conv,
                                              attr=_normal(config))
        self.k_conv1d = self.create_parameter(shape=conv,
                                              attr=_normal(config))
        self.v_conv1d = self.create_parameter(shape=conv,
                                              attr=_normal(config))
        self.A_log = self.create_parameter(shape=[heads],
                                           attr=_constant(0.0))
        self.dt_bias = self.create_parameter(shape=[width],
                                             attr=_constant(0.0))
        self.o_norm = self.create_parameter(shape=[d], attr=_constant(1.0))
        self.q_proj = _linear(config, h, width)
        self.k_proj = _linear(config, h, width)
        self.v_proj = _linear(config, h, width)
        self.f_proj = _linear(config, h, width)
        self.b_proj = _linear(config, h, heads)
        self.g_proj = _linear(config, h, heads)
        self.o_proj = _linear(config, width, h)

    def forward(self, hidden):
        heads = self.num_heads
        q = _op(_causal_conv_silu, self.q_proj(hidden), self.q_conv1d)
        k = _op(_causal_conv_silu, self.k_proj(hidden), self.k_conv1d)
        v = _op(_causal_conv_silu, self.v_proj(hidden), self.v_conv1d)
        q, k, g, beta = _op(
            _kda_inputs, q, k, self.f_proj(hidden), self.b_proj(hidden),
            self.A_log, self.dt_bias, heads=heads,
            lower_bound=self._lower_bound)
        o = F.kda(q, k, v, g, beta, num_heads=heads)
        o = _op(_gated_head_norm, o, self.o_norm, self.g_proj(hidden),
                epsilon=self._epsilon)
        return self.o_proj(o)


class Ling3Attention(Layer):
    """deepseek_v3's MLA block with query and key norms and a gate a head
    on its output."""

    def __init__(self, config: Ling3Config):
        super().__init__()
        h = config.hidden_size
        self.num_heads = heads = config.num_attention_heads
        self.nope = config.qk_nope_head_dim
        self.rope = config.qk_rope_head_dim
        self.v_dim = config.v_head_dim
        self.rank = config.kv_lora_rank
        self.rope_theta = config.rope_theta
        self.interleaved = bool(config.rope_interleave)
        self._epsilon = config.rms_norm_eps
        # use_qk_norm's weights: own parameters first, as Ling3KDA's
        self.query_layernorm = self.create_parameter(
            shape=[self.nope + self.rope], attr=_constant(1.0))
        self.key_layernorm = self.create_parameter(
            shape=[self.nope], attr=_constant(1.0))
        self.key_rope_layernorm = self.create_parameter(
            shape=[self.rope], attr=_constant(1.0))
        self.q_proj = _linear(config, h, heads * (self.nope + self.rope))
        self.kv_a_proj_with_mqa = _linear(config, h, self.rank + self.rope)
        self.kv_a_layernorm = RMSNorm(self.rank, epsilon=config.rms_norm_eps)
        self.kv_b_proj = _linear(
            config, self.rank, heads * (self.nope + self.v_dim))
        self.g_proj = _linear(config, h, heads)
        self.o_proj = _linear(config, heads * self.v_dim, h)

    def forward(self, hidden):
        b, s = hidden.shape[0], hidden.shape[1]
        heads, nope, rope, dv = (self.num_heads, self.nope, self.rope,
                                 self.v_dim)
        eps = self._epsilon
        with scope("attention.latent"):
            w_q = _op(_part_major, self.q_proj.weight, heads=heads,
                      widths=(nope, rope),
                      interleaved=(1,) if self.interleaved else ())
            q_nope = F.linear(hidden, w_q[:, :heads * nope])
            q_pe = F.linear(hidden, w_q[:, heads * nope:])
            kva = F.linear(hidden, _op(
                _rope_tail, self.kv_a_proj_with_mqa.weight, width=rope,
                interleaved=self.interleaved))
            latent = self.kv_a_layernorm(kva[:, :, :self.rank])
            # the norms' weights in the rope columns' rotate-half order,
            # as the projections' columns are
            w_qn = self.query_layernorm
            q_nope, q_pe = _op(
                _query_norm, q_nope, q_pe, w_qn[:nope],
                _op(_rope_order, w_qn[nope:], interleaved=self.interleaved),
                heads=heads, epsilon=eps)
            k_pe = _op(_heads_rms_norm, kva[:, :, self.rank:], _op(
                _rope_order, self.key_rope_layernorm,
                interleaved=self.interleaved), epsilon=eps)
        with scope("attention.expand"):
            w_kv = _op(_part_major, self.kv_b_proj.weight, heads=heads,
                       widths=(nope, dv))
            k_nope = _op(_heads_rms_norm,
                         F.linear(latent, w_kv[:, :heads * nope]),
                         self.key_layernorm, epsilon=eps)
            v = F.linear(latent, w_kv[:, heads * nope:])
        with scope("attention.core"):
            q_rope, k_rope = F.rope_qk(
                F.reshape(q_pe, [b, s, heads, rope]),
                F.reshape(k_pe, [b, s, 1, rope]), base=self.rope_theta)
            out = F.mla_attention(
                F.reshape(q_nope, [b, s, heads, nope]), q_rope,
                F.reshape(k_nope, [b, s, heads, nope]), k_rope,
                F.reshape(v, [b, s, heads, dv]),
                scale=(nope + rope) ** -0.5)
        with scope("attention.out"):
            out = _op(_head_gate, F.reshape(out, [b, s, heads * dv]),
                      self.g_proj(hidden))
            return self.o_proj(out)


class Ling3MLP(Layer):
    """SwiGLU: the dense layers' MLP, and the shared experts as one."""

    def __init__(self, config, width):
        super().__init__()
        self.gate_proj = _linear(config, config.hidden_size, width)
        self.up_proj = _linear(config, config.hidden_size, width)
        self.down_proj = _linear(config, width, config.hidden_size)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class Ling3DecoderLayer(Layer):
    def __init__(self, config: Ling3Config, index):
        super().__init__()
        self.is_latent_attention = config.is_latent_attention_layer(index)
        self.is_expert_layer = config.is_expert_layer(index)
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        if self.is_latent_attention:
            self.attention = Ling3Attention(config)
        else:
            self.attention = Ling3KDA(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        if self.is_expert_layer:
            self.mlp = MoELayer(
                config.hidden_size, config.num_experts,
                d_ff=config.moe_intermediate_size,
                k=config.num_experts_per_tok, held=config.held_experts,
                router_dtype="float32", scoring="sigmoid",
                norm_topk_prob=config.norm_topk_prob,
                routed_scaling_factor=config.routed_scaling_factor,
                shared_expert=lambda: Ling3MLP(
                    config, config.num_shared_experts
                    * config.moe_shared_expert_intermediate_size),
                shared_gate=False, shared_expert_name="shared_experts",
                n_group=config.n_group, topk_group=config.topk_group)
        else:
            self.mlp = Ling3MLP(config, config.intermediate_size)

    def forward(self, hidden):
        """-> hidden, or (hidden, expert_load, group_load) from an expert
        layer: the loads leave the layer as values because a recomputed
        layer cannot write the buffers itself."""
        with scope("attention" if self.is_latent_attention else "kda"):
            hidden = hidden + self.attention(self.input_layernorm(hidden))
        normed = self.post_attention_layernorm(hidden)
        if not self.is_expert_layer:
            with scope("mlp"):
                return hidden + self.mlp(normed)
        with scope("moe"):
            out, _, stats = self.mlp(normed, return_stats=True)
            return hidden + out, stats["expert_load"], stats["group_load"]


class Ling3Model(Layer):
    def __init__(self, config: Ling3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=_normal(config))
        self.layers = LayerList([
            Ling3DecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        with scope("embedding"):
            hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            if self.config.recompute:
                from ..distributed.recompute import recompute as _rc

                out = _rc(layer, hidden)
            else:
                out = layer(hidden)
            if layer.is_expert_layer:
                hidden, load, group_load = out
                layer.mlp.record_load(load)
                layer.mlp.record_group_load(group_load)
            else:
                hidden = out
        with scope("lm_head_loss"):
            return self.norm(hidden)


class Ling3ForCausalLM(Layer):
    def __init__(self, config: Ling3Config):
        super().__init__()
        self.config = config
        self.model = Ling3Model(config)
        self.lm_head = _linear(config, config.hidden_size, config.vocab_size)

    def forward(self, input_ids, labels=None):
        """``labels=None``: logits. With labels: ``(logits, loss)``, or
        ``(None, loss)`` when ``config.fused_loss_chunk > 0`` (the head is
        fused into the chunked loss): LlamaForCausalLM's contract."""
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
