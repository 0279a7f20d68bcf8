"""Seeded weights for the DeepSeek-V3 block, shared by the system under
test and the plain reference (benchmarks/reference/deepseek_v3.py): every
leaf is a function of (seed, leaf index) alone, as benchmarks/weights.py
makes the Llama block's.

The leaves carry the names `DeepseekV3ForCausalLM.named_parameters()` gives
them, in the order the model creates them (a test holds the two lists
together), in the source's shapes and column order: `q_proj` per head nope
| rope with the rope columns interleaved, `kv_a_proj_with_mqa` latent |
shared rotary key, `kv_b_proj` per head key | value. Linear weights are
[in, out]. Kinds: `normal` N(0, initializer_range); `ones` (every norm).
The router's selection bias is a buffer, not a leaf: 0 in the program and
in the reference. Values are drawn in float32 and rounded once to the
served dtype, so a float32 copy of a leaf is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key         # noqa: F401  (re-exported)


def dims(cfg):
    """The sizes the leaf list and the reference share."""
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
        "dense": cfg["intermediate_size"],
        # the router keeps its published width; n_routed_experts are held
        "experts": cfg["published"]["n_routed_experts"],
        "held": cfg["n_routed_experts"],
        "held_start": cfg["expert_parallel"]["rank"]
        * cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
    }


def is_expert_layer(cfg, i):
    return (i >= cfg["first_k_dense_replace"]
            and i % cfg["moe_layer_freq"] == 0)


def layer_leaves(cfg, i):
    """[(name within the layer, shape, kind)] of layer i."""
    n = dims(cfg)
    h, heads = n["h"], n["heads"]
    attention = [
        ("self_attn.q_proj.weight", (h, heads * (n["nope"] + n["rope"])),
         "normal"),
        ("self_attn.kv_a_proj_with_mqa.weight", (h, n["rank"] + n["rope"]),
         "normal"),
        ("self_attn.kv_a_layernorm.weight", (n["rank"],), "ones"),
        ("self_attn.kv_b_proj.weight",
         (n["rank"], heads * (n["nope"] + n["dv"])), "normal"),
        ("self_attn.o_proj.weight", (heads * n["dv"], h), "normal"),
    ]
    if is_expert_layer(cfg, i):
        mlp = [
            ("mlp.gate.weight", (h, n["experts"]), "normal"),
            ("mlp.experts.w_gate", (n["held"], h, n["f"]), "normal"),
            ("mlp.experts.w_up", (n["held"], h, n["f"]), "normal"),
            ("mlp.experts.w_down", (n["held"], n["f"], h), "normal"),
            ("mlp.shared_experts.gate_proj.weight", (h, n["fs"]), "normal"),
            ("mlp.shared_experts.up_proj.weight", (h, n["fs"]), "normal"),
            ("mlp.shared_experts.down_proj.weight", (n["fs"], h), "normal"),
        ]
    else:
        mlp = [
            ("mlp.gate_proj.weight", (h, n["dense"]), "normal"),
            ("mlp.up_proj.weight", (h, n["dense"]), "normal"),
            ("mlp.down_proj.weight", (n["dense"], h), "normal"),
        ]
    return ([("input_layernorm.weight", (h,), "ones")] + attention
            + [("post_attention_layernorm.weight", (h,), "ones")] + mlp)


def leaf_specs(cfg):
    """[(name, shape, kind)] in the model's creation order."""
    n = dims(cfg)
    specs = [("model.embed_tokens.weight", (n["v"], n["h"]), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"model.layers.{i}.{name}", shape, kind)
                  for name, shape, kind in layer_leaves(cfg, i)]
    return specs + [("model.norm.weight", (n["h"],), "ones"),
                    ("lm_head.weight", (n["h"], n["v"]), "normal")]


def _leaf(key, index, shape, kind, std, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("index", "shape", "kind", "std",
                                             "dtype"))
def make_leaf(key, *, index, shape, kind, std, dtype):
    """One leaf by its index in leaf_specs (the reference's way in)."""
    return _leaf(key, index, shape, kind, std, dtype)


@functools.partial(jax.jit, static_argnames=("specs", "std", "dtype"))
def _make_all(key, *, specs, std, dtype):
    return [_leaf(key, i, shape, kind, std, dtype)
            for i, (shape, kind) in enumerate(specs)]


def make_weights(cfg, seed, dtype=jnp.bfloat16):
    """Every leaf in one jitted call: {name: array}, ordered."""
    specs = leaf_specs(cfg)
    arrays = _make_all(
        seed_key(seed), specs=tuple((s, k) for _, s, k in specs),
        std=float(cfg.get("initializer_range", 0.02)), dtype=dtype)
    return dict(zip((n for n, _, _ in specs), arrays))
