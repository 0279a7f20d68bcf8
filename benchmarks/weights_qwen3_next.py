"""Seeded weights for the Qwen3-Next block, shared by the system under test
and the plain reference (benchmarks/reference/qwen3_next.py): every leaf is
a function of (seed, leaf index) alone, as benchmarks/weights.py makes the
Llama block's.

The leaves carry the names `Qwen3NextForCausalLM.named_parameters()` gives
them, in the order the model creates them (a test holds the two lists
together). Linear weights are [in, out]. Kinds: `normal` N(0,
initializer_range); `zeros` (the zero-centred norms' offsets); `ones` (the
DeltaNet output norm, dt_bias); `a_log` = log U(0, 16) (A's range in the
source's initialisation). Values are drawn in float32 and rounded once to
the served dtype, so a float32 copy of a leaf is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key         # noqa: F401  (re-exported)


def dims(cfg):
    """The sizes the leaf list and the reference share."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "hk": hk, "hv": hv, "dk": dk, "dv": dv,
        "key_dim": hk * dk, "value_dim": hv * dv,
        "conv": cfg["linear_conv_kernel_dim"],
        # the router keeps its published width; num_experts are held here
        "experts": cfg["published"]["num_experts"],
        "held": cfg["num_experts"],
        "held_start": cfg["expert_parallel"]["rank"] * cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["shared_expert_intermediate_size"],
    }


def is_attention_layer(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def layer_leaves(cfg, i):
    """[(name within the layer, shape, kind)] of layer i."""
    n = dims(cfg)
    h = n["h"]
    if is_attention_layer(cfg, i):
        mixer = [
            ("self_attn.q_proj.weight", (h, n["heads"] * n["d"] * 2), "normal"),
            ("self_attn.k_proj.weight", (h, n["kv"] * n["d"]), "normal"),
            ("self_attn.v_proj.weight", (h, n["kv"] * n["d"]), "normal"),
            ("self_attn.o_proj.weight", (n["heads"] * n["d"], h), "normal"),
            ("self_attn.q_norm.weight", (n["d"],), "zeros"),
            ("self_attn.k_norm.weight", (n["d"],), "zeros"),
        ]
    else:
        channels = 2 * n["key_dim"] + n["value_dim"]
        mixer = [
            ("linear_attn.conv_weight", (n["conv"], channels), "normal"),
            ("linear_attn.dt_bias", (n["hv"],), "ones"),
            ("linear_attn.A_log", (n["hv"],), "a_log"),
            ("linear_attn.norm_weight", (n["dv"],), "ones"),
            ("linear_attn.in_proj_qkvz.weight",
             (h, 2 * n["key_dim"] + 2 * n["value_dim"]), "normal"),
            ("linear_attn.in_proj_ba.weight", (h, 2 * n["hv"]), "normal"),
            ("linear_attn.out_proj.weight", (n["value_dim"], h), "normal"),
        ]
    moe = [
        ("mlp.gate.weight", (h, n["experts"]), "normal"),
        ("mlp.experts.w_gate", (n["held"], h, n["f"]), "normal"),
        ("mlp.experts.w_up", (n["held"], h, n["f"]), "normal"),
        ("mlp.experts.w_down", (n["held"], n["f"], h), "normal"),
        ("mlp.shared_expert.gate_proj.weight", (h, n["fs"]), "normal"),
        ("mlp.shared_expert.up_proj.weight", (h, n["fs"]), "normal"),
        ("mlp.shared_expert.down_proj.weight", (n["fs"], h), "normal"),
        ("mlp.shared_gate.weight", (h, 1), "normal"),
    ]
    return ([("input_layernorm.weight", (h,), "zeros")] + mixer
            + [("post_attention_layernorm.weight", (h,), "zeros")] + moe)


def leaf_specs(cfg):
    """[(name, shape, kind)] in the model's creation order."""
    n = dims(cfg)
    specs = [("model.embed_tokens.weight", (n["v"], n["h"]), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"model.layers.{i}.{name}", shape, kind)
                  for name, shape, kind in layer_leaves(cfg, i)]
    return specs + [("model.norm.weight", (n["h"],), "zeros"),
                    ("lm_head.weight", (n["h"], n["v"]), "normal")]


def _leaf(key, index, shape, kind, std, dtype):
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(
            k, shape, jnp.float32, 1e-3, 16.0)).astype(dtype)
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
