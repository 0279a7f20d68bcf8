"""Seeded weights for the Mellum 2 block, shared by the system under test
and the plain reference (benchmarks/reference/mellum2.py): every leaf is a
function of (seed, leaf index) alone, as benchmarks/weights.py makes the
Llama block's.

The leaves carry the names `Mellum2ForCausalLM.named_parameters()` gives
them, in the order the model creates them (a test holds the two lists
together). Linear weights are [in, out]. Kinds: `normal` N(0,
initializer_range); `ones` (the RMSNorm weights). Values are drawn in
float32 and rounded once to the served dtype, so a float32 copy of a leaf
is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key         # noqa: F401  (re-exported)


def dims(cfg):
    """The sizes the leaf list, the reference and the work share."""
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "window": cfg["sliding_window"],
        # the router keeps its published width; num_experts are held here
        "experts": cfg["published"]["num_experts"],
        "held": cfg["num_experts"],
        "held_start": cfg["expert_parallel"]["rank"] * cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
    }


def is_sliding_layer(cfg, i):
    return cfg["layer_types"][i] == "sliding_attention"


def layer_leaves(cfg, i):
    """[(name within the layer, shape, kind)] of layer i (every layer has
    the same leaves; its kind is its attention's reach and rope)."""
    n = dims(cfg)
    h, d = n["h"], n["d"]
    return [
        ("input_layernorm.weight", (h,), "ones"),
        ("self_attn.q_proj.weight", (h, n["heads"] * d), "normal"),
        ("self_attn.k_proj.weight", (h, n["kv"] * d), "normal"),
        ("self_attn.v_proj.weight", (h, n["kv"] * d), "normal"),
        ("self_attn.o_proj.weight", (n["heads"] * d, h), "normal"),
        ("post_attention_layernorm.weight", (h,), "ones"),
        ("mlp.gate.weight", (h, n["experts"]), "normal"),
        ("mlp.experts.w_gate", (n["held"], h, n["f"]), "normal"),
        ("mlp.experts.w_up", (n["held"], h, n["f"]), "normal"),
        ("mlp.experts.w_down", (n["held"], n["f"], h), "normal"),
    ]


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
