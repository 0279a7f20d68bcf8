"""Llama-family decoder.

Capability target: the reference's auto-parallel Llama integration model
(ref: test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py —
RMSNorm + RoPE + GQA attention + SwiGLU MLP). TPU-first choices:
  * attention routes through scaled_dot_product_attention (Pallas flash
    kernel dispatches on TPU; math fallback elsewhere),
  * RoPE via the fused rope_qk op (one tape entry),
  * bf16-friendly: norms accumulate fp32 inside their ops,
  * no KV-cache python branching inside the hot path — decode cache is a
    separate method so the training graph stays static.
"""
from __future__ import annotations

import numpy as np

from .. import ops as F
from ..core.autograd import scope
from ..generation import GenerationMixin, KVCache
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm


class LlamaConfig:
    def __init__(
        self,
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=None,
        max_position_embeddings=4096,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        dtype="float32",
        num_experts=0,
        num_experts_per_tok=2,
        router_aux_loss_coef=0.02,
        recompute=False,
        fused_loss_chunk=0,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.dtype = dtype
        # num_experts > 0 makes the MLP a Mixtral-style MoE (BASELINE #4)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.router_aux_loss_coef = router_aux_loss_coef
        # jax.checkpoint each decoder layer (the reference's recompute
        # pass, auto_parallel_recompute.py) — bigger batches per chip
        self.recompute = recompute
        # >0: compute the LM loss via the chunked fused head
        # (F.fused_linear_cross_entropy) so the [b, s, vocab] fp32 logits
        # never materialize — the HBM hog at billion-param scale
        self.fused_loss_chunk = fused_loss_chunk

    @classmethod
    def tiny(cls, **overrides):
        """Test-scale config (the reference's integration tests use the same
        trick: semi_auto_llama.py shrinks the model)."""
        base = dict(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=128,
        )
        base.update(overrides)
        return cls(**base)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.rope_theta = config.rope_theta

        bias = False
        self.q_proj = Linear(
            self.hidden_size, self.num_heads * self.head_dim, bias_attr=bias
        )
        self.k_proj = Linear(
            self.hidden_size, self.num_kv_heads * self.head_dim,
            bias_attr=bias,
        )
        self.v_proj = Linear(
            self.hidden_size, self.num_kv_heads * self.head_dim,
            bias_attr=bias,
        )
        self.o_proj = Linear(
            self.num_heads * self.head_dim, self.hidden_size, bias_attr=bias
        )

    def forward(self, hidden, attn_mask=None, cache=None, position=None):
        """cache: KVCache([b, max_len, kv_heads, d] k/v) with ``position``
        (int32 scalar Tensor) = tokens already in the cache. The cached
        branch keeps static shapes — the cache is a fixed buffer written
        via slice_scatter (lax.dynamic_update_slice), so every decode step
        reuses one compiled program (the reference instead grows
        cache_kvs per step; ref incubate/nn/functional/
        masked_multihead_attention.py)."""
        b, s = hidden.shape[0], hidden.shape[1]
        q = F.reshape(self.q_proj(hidden), [b, s, self.num_heads, self.head_dim])
        k = F.reshape(self.k_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        v = F.reshape(self.v_proj(hidden), [b, s, self.num_kv_heads, self.head_dim])
        new_cache = None
        if cache is None:
            q, k = F.rope_qk(q, k, base=self.rope_theta)
        else:
            pos_ids = position + F.arange(s, dtype="int32")
            q, k = F.rope_qk(q, k, pos_ids, base=self.rope_theta)
            k = F.slice_scatter(cache.k, k, axes=[1], starts=[position])
            v = F.slice_scatter(cache.v, v, axes=[1], starts=[position])
            new_cache = type(cache)(k, v)
        if self.num_kv_heads != self.num_heads:
            # GQA: repeat kv heads (XLA fuses the broadcast into the matmul)
            rep = self.num_heads // self.num_kv_heads
            k = F.repeat_interleave(k, rep, axis=2)
            v = F.repeat_interleave(v, rep, axis=2)
        if cache is None:
            # always causal: a user-supplied mask (e.g. padding) composes
            # with causality rather than replacing it
            out = F.scaled_dot_product_attention(q, k, v, attn_mask, 0.0, True)
        else:
            # causality against the absolute cache timeline: query i sits at
            # position+i and may see keys j <= position+i (unwritten tail
            # slots are masked out by the same comparison)
            max_len = k.shape[1]
            keep = F.unsqueeze(
                F.arange(max_len, dtype="int32")
                <= F.unsqueeze(position + F.arange(s, dtype="int32"), [-1]),
                [0, 1],
            )  # [1, 1, s, max_len] bool
            if attn_mask is not None:
                # compose with a user mask (e.g. prompt padding) over the
                # cache timeline, same contract as the non-cached branch
                if str(attn_mask.dtype) == "paddle_tpu.bool":
                    keep = F.logical_and(keep, attn_mask)
                else:
                    keep = F.where(
                        keep,
                        attn_mask.astype("float32"),
                        F.full_like(attn_mask.astype("float32"), -1e30),
                    )
            out = F.scaled_dot_product_attention(q, k, v, keep, 0.0, False)
        out = F.reshape(out, [b, s, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        return out if cache is None else (out, new_cache)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        bias = False
        self.gate_proj = Linear(
            config.hidden_size, config.intermediate_size, bias_attr=bias
        )
        self.up_proj = Linear(
            config.hidden_size, config.intermediate_size, bias_attr=bias
        )
        self.down_proj = Linear(
            config.intermediate_size, config.hidden_size, bias_attr=bias
        )

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps
        )
        self._moe = config.num_experts > 0
        if self._moe:
            from ..incubate.moe import MoELayer

            self.mlp = MoELayer(
                config.hidden_size, config.num_experts,
                d_ff=config.intermediate_size,
                k=config.num_experts_per_tok,
            )
        else:
            self.mlp = LlamaMLP(config)

    def forward(self, hidden, attn_mask=None, cache=None, position=None):
        with scope("attention"):
            residual = hidden
            hidden = self.input_layernorm(hidden)
            if cache is None:
                hidden = self.self_attn(hidden, attn_mask)
                new_cache = None
            else:
                hidden, new_cache = self.self_attn(
                    hidden, attn_mask, cache, position
                )
            hidden = residual + hidden
        with scope("mlp"):
            residual = hidden
            hidden = self.post_attention_layernorm(hidden)
            aux = None
            if self._moe:
                hidden, aux = self.mlp(hidden)
            else:
                hidden = self.mlp(hidden)
            out = residual + hidden
        if cache is not None:
            return out, new_cache
        return (out, aux) if self._moe else out


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_hidden_layers)]
        )
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None, position=None):
        with scope("embedding"):
            hidden = self.embed_tokens(input_ids)
        aux_total = None
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is not None:
                hidden, new_cache = layer(
                    hidden, attn_mask, caches[i], position
                )
                new_caches.append(new_cache)
                continue
            if self.config.recompute:
                from ..distributed.recompute import recompute as _rc

                out = _rc(layer, hidden, attn_mask)
            else:
                out = layer(hidden, attn_mask)
            if isinstance(out, tuple):
                hidden, aux = out
                if aux is not None:
                    aux_total = aux if aux_total is None else aux_total + aux
            else:
                hidden = out
        with scope("lm_head_loss"):
            hidden = self.norm(hidden)
        if caches is not None:
            return hidden, new_caches
        if self.config.num_experts > 0:
            return hidden, aux_total
        return hidden


class LlamaForCausalLM(GenerationMixin, Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(
                config.hidden_size, config.vocab_size, bias_attr=False
            )

    def init_kv_cache(self, batch_size, max_length, dtype=None):
        """Preallocated static-shape decode cache, one KVCache per layer
        ([b, max_length, kv_heads, head_dim]) — see GenerationMixin."""
        c = self.config
        head_dim = c.hidden_size // c.num_attention_heads
        dtype = dtype or c.dtype
        return [
            KVCache(
                F.zeros([batch_size, max_length, c.num_key_value_heads,
                         head_dim], dtype),
                F.zeros([batch_size, max_length, c.num_key_value_heads,
                         head_dim], dtype),
            )
            for _ in range(c.num_hidden_layers)
        ]

    def forward(self, input_ids, labels=None, attn_mask=None, caches=None,
                position=None):
        """Return contract, by arguments:
          * ``caches`` given (decode): returns ``(logits, new_caches)``.
          * ``labels=None``: returns bare ``logits``.
          * ``labels`` given: returns ``(logits, loss)`` — EXCEPT when
            ``config.fused_loss_chunk > 0``: then the LM head is fused
            into the chunked loss (fused_linear_cross_entropy), full
            [b, s, vocab] logits never materialize, and the return is
            ``(None, loss)``. Callers needing logits must set
            ``fused_loss_chunk=0`` (or call without labels)."""
        if caches is not None:
            hidden, new_caches = self.llama(
                input_ids, attn_mask, caches=caches, position=position
            )
            with scope("lm_head_loss"):
                return self._logits(hidden), new_caches
        hidden = self.llama(input_ids, attn_mask)
        aux = None
        if isinstance(hidden, tuple):
            hidden, aux = hidden
        with scope("lm_head_loss"):
            if labels is not None and self.config.fused_loss_chunk > 0:
                b, s, h = hidden.shape
                head_w = (
                    self.lm_head.weight if self.lm_head is not None
                    else F.transpose(self.llama.embed_tokens.weight, [1, 0])
                )
                logits = None
                loss = F.fused_linear_cross_entropy(
                    F.reshape(hidden[:, :-1], [-1, h]), head_w,
                    F.reshape(labels[:, 1:], [-1]),
                    chunk_size=self.config.fused_loss_chunk,
                )
            else:
                logits = self._logits(hidden)
                if labels is None:
                    return logits
                # causal LM loss: shift by one
                b, s, v = logits.shape
                loss = F.cross_entropy(
                    F.reshape(logits[:, :-1], [-1, v]),
                    F.reshape(labels[:, 1:], [-1]),
                )
            if aux is not None:
                loss = loss + self.config.router_aux_loss_coef * aux
        return logits, loss

    def _logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        return F.matmul(
            hidden, self.llama.embed_tokens.weight, transpose_y=True
        )

    def num_params(self):
        return sum(int(np.prod(p.shape)) for p in self.parameters())


# --------------------------------------------------------------------------
# Pipeline-parallel Llama: maps a LlamaForCausalLM onto the heterogeneous
# pipeline schedules (distributed/pipeline.py), embedding + head + loss
# INSIDE the pipelined region.  ref: the reference's PipelineLayer partition
# of its Llama integration model (fleet/meta_parallel/pp_layers.py:258
# SegmentLayers "uniform"; test/auto_parallel/hybrid_strategy/
# semi_auto_parallel_llama_model.py pp branch).
# --------------------------------------------------------------------------


class LlamaPipeline:
    """Pipelined training step for a Llama decoder.

    Owns stage-stacked COPIES of the model's weights (the reference's
    PipelineLayer likewise re-owns partitioned segments): `first` holds the
    embedding, `stages` the decoder blocks grouped `layers/n_stages` per
    stage, `last` the final norm + lm_head. ``__call__(ids, labels)``
    returns the causal-LM loss on the autograd tape; train the tensors
    from ``parameters()``.

        mesh = dist.ProcessMesh([[0,1],[2,3]], dim_names=["dp","pp"]) ...
        pipe = LlamaPipeline(model, mesh, schedule="1f1b")
        loss = pipe(ids, labels); loss.backward(); opt.step()
    """

    def __init__(self, model, mesh, axis_name="pp", num_micro_batches=None,
                 schedule="1f1b", remat=False, data_axis=None,
                 tp_axis=None, dtype=None, virtual_pp=1):
        from ..core.tensor import Tensor as _T

        cfg = model.config
        if cfg.num_experts > 0:
            raise NotImplementedError(
                "LlamaPipeline: MoE layers not supported (use EP)"
            )
        if cfg.tie_word_embeddings:
            raise NotImplementedError(
                "LlamaPipeline: tied embeddings not supported; the edge "
                "stages own separate embed/head weights"
            )
        if schedule not in ("1f1b", "gpipe", "vpp", "zero_bubble"):
            raise ValueError(f"unknown schedule {schedule!r}")
        if schedule in ("1f1b", "zero_bubble") and remat:
            raise ValueError(
                "remat applies to the gpipe/vpp schedules only; 1F1B and "
                "zero-bubble are inherently recompute-based (stages re-run "
                "in their backward micro-steps)"
            )
        if schedule == "vpp" and virtual_pp < 2:
            raise ValueError("vpp needs virtual_pp >= 2")
        if schedule != "vpp":
            virtual_pp = 1
        n_stages = mesh.get_dim_size(axis_name)
        L = cfg.num_hidden_layers
        if L % (n_stages * virtual_pp):
            raise ValueError(
                f"num_hidden_layers {L} not divisible by "
                f"{n_stages} stages x {virtual_pp} virtual chunks"
            )
        tp = mesh.get_dim_size(tp_axis) if tp_axis else 1
        if tp > 1:
            # Megatron TP inside the pipelined region: heads and FFN
            # columns split over the tp axis; vocab-parallel loss
            if cfg.num_attention_heads % tp or cfg.num_key_value_heads % tp:
                raise ValueError(
                    f"attention heads ({cfg.num_attention_heads}/"
                    f"{cfg.num_key_value_heads} kv) not divisible by "
                    f"tp={tp}"
                )
            if cfg.intermediate_size % tp or cfg.vocab_size % tp:
                raise ValueError(
                    f"intermediate_size/vocab_size not divisible by tp={tp}"
                )
        self.cfg = cfg
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_micro_batches = num_micro_batches
        self.schedule = schedule
        self.remat = remat
        self.data_axis = data_axis
        self.tp_axis = tp_axis if tp > 1 else None
        self.virtual_pp = virtual_pp
        # caller-owned compile cache: the pipeline re-uses one jitted
        # program per shape across training steps
        self._compile_cache = {}
        lps = L // (n_stages * virtual_pp)

        import jax.numpy as _jnp

        def stk(get):
            # stack on-device (no numpy round trip — at 8B scale the
            # host copy dominated wall clock)
            arrs = [get(model.llama.layers[i])._data for i in range(L)]
            if dtype:
                arrs = [a.astype(dtype) for a in arrs]
            a = _jnp.stack(arrs)
            if virtual_pp > 1:
                # [v, p, lps, ...] then swap -> [p, v, lps, ...]: entry
                # [d, c] = logical stage c*p + d (interleaved mapping,
                # ref pipeline_parallel.py:1172 chunk assignment)
                a = _jnp.swapaxes(
                    a.reshape((virtual_pp, n_stages, lps) + a.shape[1:]),
                    0, 1,
                )
            else:
                a = a.reshape((n_stages, lps) + a.shape[1:])
            t = _T(a)
            t.stop_gradient = False
            return t

        self.stages = {
            "ln1": stk(lambda l: l.input_layernorm.weight),
            "wq": stk(lambda l: l.self_attn.q_proj.weight),
            "wk": stk(lambda l: l.self_attn.k_proj.weight),
            "wv": stk(lambda l: l.self_attn.v_proj.weight),
            "wo": stk(lambda l: l.self_attn.o_proj.weight),
            "ln2": stk(lambda l: l.post_attention_layernorm.weight),
            "wg": stk(lambda l: l.mlp.gate_proj.weight),
            "wu": stk(lambda l: l.mlp.up_proj.weight),
            "wd": stk(lambda l: l.mlp.down_proj.weight),
        }

        def own(t):
            a = t._data
            if dtype:
                a = a.astype(dtype)
            c = _T(a + 0)  # fresh buffer, pipeline owns its copy
            c.stop_gradient = False
            return c

        self.first = {"embed": own(model.llama.embed_tokens.weight)}
        self.last = {
            "norm": own(model.llama.norm.weight),
            "head": own(model.lm_head.weight),
        }

        eps = cfg.rms_norm_eps
        theta = cfg.rope_theta
        n_heads = cfg.num_attention_heads
        n_kv = cfg.num_key_value_heads
        hd = cfg.hidden_size // n_heads
        nh_l, nkv_l = n_heads // tp, n_kv // tp  # per-tp-device heads
        tp_ax = self.tp_axis

        from ..ops.impl.activation import swiglu as _swiglu
        from ..ops.impl.fused_ops import rope_qk as _rope
        from ..ops.impl.nn_ops import (
            rms_norm as _rms,
            scaled_dot_product_attention as _sdpa,
        )
        import jax
        import jax.numpy as jnp

        def block(bp, h):
            # Megatron pattern when tp_ax is set: q/k/v/gate/up are
            # column-parallel (weights arrive as local column shards via
            # the tp placements), o/down are row-parallel with one psum
            # each; activations between blocks stay replicated over tp
            # (unvarying — shard_map's type system transposes grads
            # exactly, see distributed/pipeline.py scaffold docstring)
            # the scope names of LlamaDecoderLayer (plain named_scope:
            # this region is differentiated by jax, not by the tape)
            with jax.named_scope("attention"):
                x = _rms(h, bp["ln1"], epsilon=eps)
                b, s = x.shape[0], x.shape[1]
                q = (x @ bp["wq"]).reshape(b, s, nh_l, hd)
                k = (x @ bp["wk"]).reshape(b, s, nkv_l, hd)
                v = (x @ bp["wv"]).reshape(b, s, nkv_l, hd)
                q, k = _rope(q, k, base=theta)
                if nkv_l != nh_l:
                    rep = nh_l // nkv_l
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                o = _sdpa(q, k, v, is_causal=True)
                part = o.reshape(b, s, nh_l * hd) @ bp["wo"]
                if tp_ax:
                    part = jax.lax.psum(part, tp_ax)
                h = h + part
            with jax.named_scope("mlp"):
                x = _rms(h, bp["ln2"], epsilon=eps)
                part = _swiglu(x @ bp["wg"], x @ bp["wu"]) @ bp["wd"]
                if tp_ax:
                    part = jax.lax.psum(part, tp_ax)
                h = h + part
            return h

        def stage_fn(sp, h):
            h, _ = jax.lax.scan(
                lambda hh, bp: (block(bp, hh), None), h, sp
            )
            return h

        def first_fn(fp, ids):
            with jax.named_scope("embedding"):
                return fp["embed"][ids]

        @jax.named_scope("lm_head_loss")
        def last_fn(lp, h, labels):
            h = _rms(h, lp["norm"], epsilon=eps)
            logits = (h[:, :-1] @ lp["head"]).astype(jnp.float32)
            lbl = labels[:, 1:].astype(jnp.int32)
            if tp_ax is None:
                logp = jax.nn.log_softmax(logits, axis=-1)
                ll = jnp.take_along_axis(logp, lbl[..., None], axis=-1)
                return -ll.mean()
            # vocab-parallel softmax cross entropy (the reference's
            # c_softmax_with_cross_entropy_op.cu contract): head is a
            # vocab column shard; lse and the gold logit are assembled
            # with psums over tp. The max shift is a constant offset
            # (stop_gradient), keeping the grad the exact softmax.
            r = jax.lax.axis_index(tp_ax)
            vl = logits.shape[-1]
            # stop_gradient INSIDE pmax: the collective has no diff rule,
            # but with a zero-tangent operand it is never differentiated;
            # the shift is a constant so the grad stays the exact softmax
            m = jax.lax.pmax(
                jax.lax.stop_gradient(logits.max(-1)), tp_ax
            )
            se = jax.lax.psum(
                jnp.exp(logits - m[..., None]).sum(-1), tp_ax
            )
            loc = lbl - r * vl
            inr = jnp.logical_and(loc >= 0, loc < vl)
            safe = jnp.clip(loc, 0, vl - 1)
            gold_l = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
            gold = jax.lax.psum(jnp.where(inr, gold_l, 0.0), tp_ax)
            return (jnp.log(se) + m - gold).mean()

        self._fns = (first_fn, stage_fn, last_fn)
        off = 1 if virtual_pp > 1 else 0  # extra leading chunk dim
        self._stacked_tp_dims = (
            {k: d + off for k, d in
             {"wq": 3, "wk": 3, "wv": 3, "wg": 3, "wu": 3,
              "wo": 2, "wd": 2}.items()}
            if self.tp_axis else None
        )
        self._last_tp_dims = {"head": 1} if self.tp_axis else None

    def __call__(self, input_ids, labels):
        from ..distributed.pipeline import (
            pipeline_1f1b,
            pipeline_program,
            pipeline_vpp,
            pipeline_zero_bubble,
        )

        first_fn, stage_fn, last_fn = self._fns
        kw = dict(
            mesh=self.mesh, axis_name=self.axis_name,
            num_micro_batches=self.num_micro_batches,
            data_axis=self.data_axis, tp_axis=self.tp_axis,
            stacked_tp_dims=self._stacked_tp_dims,
            last_tp_dims=self._last_tp_dims, cache=self._compile_cache,
        )
        args = (first_fn, stage_fn, last_fn, self.first, self.stages,
                self.last, input_ids, labels)
        if self.schedule == "1f1b":
            return pipeline_1f1b(*args, **kw)
        if self.schedule == "zero_bubble":
            return pipeline_zero_bubble(*args, **kw)
        if self.schedule == "vpp":
            return pipeline_vpp(
                *args, virtual_chunks=self.virtual_pp, remat=self.remat,
                **kw,
            )
        return pipeline_program(*args, remat=self.remat, **kw)

    def parameters(self):
        return (
            list(self.first.values())
            + list(self.stages.values())
            + list(self.last.values())
        )
