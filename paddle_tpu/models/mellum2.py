"""Mellum 2 decoder: sliding-window and full GQA attention in a fixed
pattern, every layer's MLP a mixture of experts behind a softmax router.

ref: the published ``config.json`` of JetBrains/Mellum2-12B-A2.5B-Instruct
(``model_type`` ``mellum``) read as a Qwen3-MoE-style decoder. With
``Norm(x; w) = x rsqrt(mean(x^2) + eps) w`` in float32, layer i is

    r = x + Attention_i(Norm(x))        y = r + MoE(Norm(r))

  * Attention_i: GQA (``num_attention_heads`` queries over
    ``num_key_value_heads`` keys and values of ``head_dim``, no bias),
    rope on every column in rotate-half pairs, scale ``head_dim^-1/2``,
    ``o_proj``. ``layer_types[i]`` chooses the kind: on a
    ``"sliding_attention"`` layer a query at position p sees keys p -
    ``sliding_window`` + 1 .. p (``F.window_attention``: the flash
    kernels' band schedule on a TPU) under plain rope at its
    ``rope_theta``; on a ``"full_attention"`` layer it sees every earlier
    key (``F.scaled_dot_product_attention``: the causal kernels) under
    the rope its ``rope_parameters`` entry gives, YaRN where
    ``rope_type`` is ``"yarn"`` (``F.rope_qk_freqs`` at
    ``yarn_parameters``' frequencies, cos and sin times its
    ``attention_factor``).
  * MoE: ``incubate.moe.MoELayer`` told which experts it holds
    (``held_experts=(start, count)``: one expert-parallel rank's share;
    all of them by default), router in float32, softmax over all
    experts, top-k weights normalised over the k, no shared expert.

Left out: the multi-token-prediction head and a router balance term.
Training and plain logits only: serving the window needs a KV pool per
layer kind and the band in the paged kernel (ROADMAP B-m7).
"""
from __future__ import annotations

import numpy as np

from .. import ops as F
from ..core.autograd import scope
from ..incubate.moe import MoELayer
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..nn.parameter import ParamAttr
from ..ops.impl.fused_ops import yarn_parameters

SLIDING, FULL = "sliding_attention", "full_attention"


class Mellum2Config:
    def __init__(
        self,
        vocab_size=98304,
        hidden_size=2304,
        num_hidden_layers=28,
        num_attention_heads=32,
        num_key_value_heads=4,
        head_dim=128,
        layer_types=None,
        sliding_window=1024,
        rope_parameters=None,
        rms_norm_eps=1e-6,
        num_experts=64,
        num_experts_per_tok=8,
        moe_intermediate_size=896,
        norm_topk_prob=True,
        max_position_embeddings=131072,
        initializer_range=0.02,
        dtype="float32",
        held_experts=None,
        recompute=False,
        fused_loss_chunk=0,
    ):
        if not norm_topk_prob:
            raise NotImplementedError(
                "Mellum2Config: norm_topk_prob=False is not implemented")
        layer_types = list(layer_types or [
            FULL if (i + 1) % 4 == 0 else SLIDING
            for i in range(num_hidden_layers)])
        if len(layer_types) != num_hidden_layers or not set(
                layer_types) <= {SLIDING, FULL}:
            raise ValueError(
                f"Mellum2Config: layer_types {layer_types} is not one of "
                f"{SLIDING!r} / {FULL!r} for each of {num_hidden_layers} "
                f"layers")
        rope_parameters = dict(rope_parameters or {
            kind: {"rope_type": "default", "rope_theta": 500000.0}
            for kind in (SLIDING, FULL)})
        for kind in set(layer_types):
            kind_type = rope_parameters[kind]["rope_type"]
            if kind_type not in ("default", "yarn"):
                raise NotImplementedError(
                    f"Mellum2Config: rope_type {kind_type!r} ({kind})")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.layer_types = layer_types
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters
        self.rms_norm_eps = rms_norm_eps
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
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

    def yarn(self, kind):
        """(inv_freq as a tuple, cos and sin's scale) of YaRN on layers of
        ``kind``, or None where they take plain rope at their
        ``rope_theta``."""
        p = self.rope_parameters[kind]
        if p["rope_type"] == "default":
            return None
        inv_freq, scale = yarn_parameters(
            self.head_dim, float(p["rope_theta"]), float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p.get("beta_fast", 32.0)), float(p.get("beta_slow", 1.0)),
            p.get("attention_factor"))
        return tuple(float(f) for f in inv_freq), scale

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config: one period of 3 sliding + 1 full layer."""
        base = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            sliding_window=8, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16, max_position_embeddings=256,
            rope_parameters={
                SLIDING: {"rope_type": "default", "rope_theta": 500000.0},
                FULL: {"rope_type": "yarn", "rope_theta": 500000.0,
                       "factor": 16.0, "original_max_position_embeddings": 16,
                       "beta_fast": 32.0, "beta_slow": 1.0,
                       "attention_factor": 1.2772588722239782}},
        )
        base.update(overrides)
        return cls(**base)


def _linear(config, n_in, n_out):
    return Linear(n_in, n_out, weight_attr=ParamAttr(
        initializer=I.Normal(0.0, config.initializer_range)), bias_attr=False)


class Mellum2Attention(Layer):
    def __init__(self, config: Mellum2Config, kind):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d
        self.window = config.sliding_window if kind == SLIDING else None
        self.rope_theta = float(config.rope_parameters[kind]["rope_theta"])
        self.yarn = config.yarn(kind)
        self.q_proj = _linear(config, h, self.num_heads * d)
        self.k_proj = _linear(config, h, self.num_kv_heads * d)
        self.v_proj = _linear(config, h, self.num_kv_heads * d)
        self.o_proj = _linear(config, self.num_heads * d, h)

    def forward(self, hidden):
        b, s = hidden.shape[0], hidden.shape[1]
        heads, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = F.reshape(self.q_proj(hidden), [b, s, heads, d])
        k = F.reshape(self.k_proj(hidden), [b, s, kv, d])
        v = F.reshape(self.v_proj(hidden), [b, s, kv, d])
        if self.yarn is None:
            q, k = F.rope_qk(q, k, base=self.rope_theta)
        else:
            q, k = F.rope_qk_freqs(q, k, inv_freq=self.yarn[0],
                                   scale=self.yarn[1])
        if kv != heads:
            k = F.repeat_interleave(k, heads // kv, axis=2)
            v = F.repeat_interleave(v, heads // kv, axis=2)
        if self.window is None:
            out = F.scaled_dot_product_attention(q, k, v, None, 0.0, True)
        else:
            out = F.window_attention(q, k, v, self.window)
        return self.o_proj(F.reshape(out, [b, s, heads * d]))


class Mellum2DecoderLayer(Layer):
    def __init__(self, config: Mellum2Config, index):
        super().__init__()
        self.kind = config.layer_types[index]
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = Mellum2Attention(config, self.kind)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = MoELayer(
            config.hidden_size, config.num_experts,
            d_ff=config.moe_intermediate_size, k=config.num_experts_per_tok,
            held=config.held_experts, router_dtype="float32")

    def forward(self, hidden):
        """-> (hidden, expert_load): the load leaves the layer as a value
        because a recomputed layer cannot write the buffer itself."""
        with scope(self.kind):
            hidden = hidden + self.self_attn(self.input_layernorm(hidden))
        with scope("moe"):
            out, _, stats = self.mlp(
                self.post_attention_layernorm(hidden), return_stats=True)
            return hidden + out, stats["expert_load"]


class Mellum2Model(Layer):
    def __init__(self, config: Mellum2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(
                initializer=I.Normal(0.0, config.initializer_range)))
        self.layers = LayerList([
            Mellum2DecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

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


class Mellum2ForCausalLM(Layer):
    def __init__(self, config: Mellum2Config):
        super().__init__()
        self.config = config
        self.model = Mellum2Model(config)
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
