"""DeepSeek-V3-style decoder: latent attention (MLA) over a stack whose
first layers are dense and whose others are expert layers behind a sigmoid
router with a selection bias.

ref: HF transformers ``modeling_deepseek_v3.py`` and the published
``config.json`` of kakaocorp/kanana-2-30b-a3b-instruct-2601 (``model_type``
``deepseek_v3``, no query down-projection: ``q_lora_rank`` null). With
``Norm(x; w) = x rsqrt(mean(x^2) + eps) w`` in float32, layer i is

    r = x + MLA(Norm(x))            y = r + MLP_i(Norm(r))

  * MLA: ``q = x W_q`` as heads x (d_nope + d_rope); ``x W_kva`` is a
    latent of ``kv_lora_rank`` and ONE rotary key of d_rope shared by all
    heads; ``Norm(latent) W_kvb`` is heads x (d_nope key + d_v value);
    rope on the d_rope columns of q and on the shared key; scores
    ``(q_nope . k_nope + q_rope . k_rope) * (d_nope + d_rope)^-1/2``,
    causal softmax, ``o_proj``. The core is ``F.mla_attention`` (the MLA
    kernels of kernels/pallas/flash_attention.py on a TPU): the shared key
    is never broadcast to the heads and no head is padded.
  * The parameters keep the source's names, shapes and column order; what
    the forward needs otherwise is undone on the WEIGHT, once a forward,
    never by a gather on an activation: ``q_proj``'s columns (per head:
    nope | rope, the rope columns stored interleaved, ``rope_interleave``)
    become all heads' nope columns, then all heads' rope columns in
    rotate-half order; the last d_rope columns of ``kv_a_proj_with_mqa``
    are permuted alike, so every score is what the interleaved rope gives;
    ``kv_b_proj``'s (per head: key | value) become all keys, then all
    values. Every boundary is then a multiple of 128 columns, and each
    part is a product of its own with its columns: q_nope, k_nope and v
    stay [b, s, heads * 128], which the kernels read in place.
  * MLP_i: dense SwiGLU for ``i < first_k_dense_replace`` (and where
    ``i % moe_layer_freq != 0``), else ``incubate.moe.MoELayer`` told which
    experts it holds, ``scoring="sigmoid"``: ``s = sigmoid(x W_g)`` in
    float32, the k experts chosen by ``s + e_score_correction_bias`` (a
    buffer; one group), their weights ``s_i / (sum of the k + 1e-20) *
    routed_scaling_factor``, plus ``n_shared_experts`` shared experts as
    one ungated SwiGLU added to every token.

Left out: the selection bias's update rule (a training recipe the source
does not publish: the buffer stays where it is put), a router balance
term, group-limited routing (``n_group`` > 1), a query down-projection,
rope scaling, and serving (the latent cache and absorbed-weight decode:
ROADMAP B-m6). Training and plain logits only.
"""
from __future__ import annotations

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
from ..nn.layer.norm import RMSNorm
from ..nn.parameter import ParamAttr


class DeepseekV3Config:
    def __init__(
        self,
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=6144,
        moe_intermediate_size=768,
        num_hidden_layers=48,
        num_attention_heads=32,
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=1000000.0,
        rope_interleave=True,
        rope_scaling=None,
        rms_norm_eps=1e-6,
        first_k_dense_replace=1,
        moe_layer_freq=1,
        n_routed_experts=128,
        n_shared_experts=2,
        num_experts_per_tok=6,
        n_group=1,
        topk_group=1,
        norm_topk_prob=True,
        routed_scaling_factor=2.448,
        scoring_func="sigmoid",
        max_position_embeddings=32768,
        initializer_range=0.02,
        dtype="float32",
        held_experts=None,
        recompute=False,
        fused_loss_chunk=0,
    ):
        for name, value, only in (
                ("q_lora_rank", q_lora_rank, None),
                ("rope_scaling", rope_scaling, None),
                ("n_group", n_group, 1), ("topk_group", topk_group, 1),
                ("scoring_func", scoring_func, "sigmoid")):
            if value != only:
                raise NotImplementedError(
                    f"DeepseekV3Config: {name}={value!r} is not "
                    f"implemented (only {only!r})")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.rope_interleave = rope_interleave
        self.rms_norm_eps = rms_norm_eps
        self.first_k_dense_replace = first_k_dense_replace
        self.moe_layer_freq = moe_layer_freq
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.dtype = dtype
        # (start, count): the experts this rank holds of the
        # n_routed_experts the router chooses among
        self.held_experts = tuple(held_experts or (0, n_routed_experts))
        # jax.checkpoint each decoder layer
        self.recompute = recompute
        # >0: the LM head fused into the chunked loss, as LlamaConfig's
        self.fused_loss_chunk = fused_loss_chunk

    def is_expert_layer(self, i):
        return (i >= self.first_k_dense_replace
                and i % self.moe_layer_freq == 0)

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config: one dense layer, then two expert layers."""
        base = dict(
            vocab_size=128, hidden_size=32, intermediate_size=48,
            moe_intermediate_size=16, num_hidden_layers=3,
            num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
            n_shared_experts=2, num_experts_per_tok=4,
            max_position_embeddings=128,
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
        "deepseek_v3." + fn.__name__.lstrip("_"), fn, tensors, attrs)


def _rotate_half_order(x):
    """The last axis from the interleaved layout (x0, y0, x1, y1, ...) to
    rotate-half order (x0, x1, ..., y0, y1, ...): what the source's
    ``apply_rotary_pos_emb_interleave`` does to q and k before the usual
    rope; a permutation applied to both leaves their product as it was."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    return jnp.swapaxes(pairs, -1, -2).reshape(x.shape)


def _part_major(weight, *, heads, widths, interleaved=()):
    """A projection's weight [n_in, heads * sum(widths)] whose columns are
    per head (``widths[0]`` of the first part, ``widths[1]`` of the next)
    -> [n_in, heads widths[0] | heads widths[1] | ...]: part by part, each
    part's heads in order. The parts named in ``interleaved`` have each
    head's columns put in rotate-half order on the way. On the weight,
    once a forward; the transpose puts the weight's gradient back in the
    source's order."""
    n_in = weight.shape[0]
    per_head = weight.reshape(n_in, heads, sum(widths))
    bounds = np.cumsum((0,) + tuple(widths))
    parts = []
    for i, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        part = per_head[:, :, lo:hi]
        if i in interleaved:
            part = _rotate_half_order(part)
        parts.append(part.reshape(n_in, -1))
    return jnp.concatenate(parts, axis=-1)


def _rope_tail(weight, *, width, interleaved):
    """``kv_a_proj_with_mqa``'s weight with its last ``width`` columns (the
    shared rotary key's) in rotate-half order."""
    if not interleaved:
        return weight
    keep = weight.shape[1] - width
    return jnp.concatenate(
        [weight[:, :keep], _rotate_half_order(weight[:, keep:])], -1)


class DeepseekV3Attention(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        h = config.hidden_size
        self.num_heads = heads = config.num_attention_heads
        self.nope = config.qk_nope_head_dim
        self.rope = config.qk_rope_head_dim
        self.v_dim = config.v_head_dim
        self.rank = config.kv_lora_rank
        self.rope_theta = config.rope_theta
        self.interleaved = bool(config.rope_interleave)
        self.q_proj = _linear(config, h, heads * (self.nope + self.rope))
        self.kv_a_proj_with_mqa = _linear(config, h, self.rank + self.rope)
        self.kv_a_layernorm = RMSNorm(self.rank, epsilon=config.rms_norm_eps)
        self.kv_b_proj = _linear(
            config, self.rank, heads * (self.nope + self.v_dim))
        self.o_proj = _linear(config, heads * self.v_dim, h)

    def forward(self, hidden):
        b, s = hidden.shape[0], hidden.shape[1]
        heads, nope, rope, dv = (self.num_heads, self.nope, self.rope,
                                 self.v_dim)
        # each part is a product of its own with its columns of the
        # reordered weight: the kernels read a part in place, and a slice
        # of one wider activation would be a copy of it (its gradient a
        # concatenation); the weights' gradients are joined at their size
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
        with scope("attention.expand"):
            w_kv = _op(_part_major, self.kv_b_proj.weight, heads=heads,
                       widths=(nope, dv))
            k_nope = F.linear(latent, w_kv[:, :heads * nope])
            v = F.linear(latent, w_kv[:, heads * nope:])
        with scope("attention.core"):
            q_rope, k_rope = F.rope_qk(
                F.reshape(q_pe, [b, s, heads, rope]),
                F.reshape(kva[:, :, self.rank:], [b, s, 1, rope]),
                base=self.rope_theta)
            out = F.mla_attention(
                F.reshape(q_nope, [b, s, heads, nope]), q_rope,
                F.reshape(k_nope, [b, s, heads, nope]), k_rope,
                F.reshape(v, [b, s, heads, dv]),
                scale=(nope + rope) ** -0.5)
        with scope("attention.out"):
            return self.o_proj(F.reshape(out, [b, s, heads * dv]))


class DeepseekV3MLP(Layer):
    """SwiGLU: the dense layers' MLP, and the shared experts as one."""

    def __init__(self, config, width):
        super().__init__()
        self.gate_proj = _linear(config, config.hidden_size, width)
        self.up_proj = _linear(config, config.hidden_size, width)
        self.down_proj = _linear(config, width, config.hidden_size)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, config: DeepseekV3Config, index):
        super().__init__()
        self.is_expert_layer = config.is_expert_layer(index)
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        if self.is_expert_layer:
            self.mlp = MoELayer(
                config.hidden_size, config.n_routed_experts,
                d_ff=config.moe_intermediate_size,
                k=config.num_experts_per_tok, held=config.held_experts,
                router_dtype="float32", scoring="sigmoid",
                norm_topk_prob=config.norm_topk_prob,
                routed_scaling_factor=config.routed_scaling_factor,
                shared_expert=lambda: DeepseekV3MLP(
                    config, config.n_shared_experts
                    * config.moe_intermediate_size),
                shared_gate=False, shared_expert_name="shared_experts")
        else:
            self.mlp = DeepseekV3MLP(config, config.intermediate_size)

    def forward(self, hidden):
        """-> hidden, or (hidden, expert_load) from an expert layer: the
        load leaves the layer as a value because a recomputed layer cannot
        write the buffer itself."""
        with scope("attention"):
            hidden = hidden + self.self_attn(self.input_layernorm(hidden))
        normed = self.post_attention_layernorm(hidden)
        if not self.is_expert_layer:
            with scope("mlp"):
                return hidden + self.mlp(normed)
        with scope("moe"):
            out, _, stats = self.mlp(normed, return_stats=True)
            return hidden + out, stats["expert_load"]


class DeepseekV3Model(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size, weight_attr=_normal(config))
        self.layers = LayerList([
            DeepseekV3DecoderLayer(config, i)
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
                hidden, load = out
                layer.mlp.record_load(load)
            else:
                hidden = out
        with scope("lm_head_loss"):
            return self.norm(hidden)


class DeepseekV3ForCausalLM(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
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
