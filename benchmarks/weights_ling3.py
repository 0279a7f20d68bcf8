"""Seeded weights for the Ling 3.0 block, shared by the system under test
and the plain reference (benchmarks/reference/ling3.py): every leaf is a
function of (seed, leaf index) alone, as benchmarks/weights.py makes the
Llama block's.

The leaves carry the names `Ling3ForCausalLM.named_parameters()` gives
them, in the order the model creates them (a test holds the two lists
together), in the source's shapes and column order (the MLA block's as
weights_deepseek_v3's). Linear weights are [in, out]. Kinds: `normal`
N(0, initializer_range); `ones` (every norm); `zeros` (KDA's `A_log` and
`dt_bias`, so that g = -5 sigmoid(x W_f) spans the whole range down to
the kernels' floor). The router's selection bias is a buffer, not a leaf:
0 in the program and in the reference. Values are drawn in float32 and
rounded once to the served dtype, so a float32 copy of a leaf is exact.
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
        "heads": cfg["num_attention_heads"], "d": cfg["head_dim"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "dv": cfg["v_head_dim"], "rank": cfg["kv_lora_rank"],
        "conv": cfg["short_conv_kernel_size"],
        "dense": cfg["intermediate_size"],
        # the router keeps its published width; num_experts are held
        "experts": cfg["published"]["num_experts"],
        "held": cfg["num_experts"],
        "held_start": cfg["expert_parallel"]["rank"] * cfg["num_experts"],
        "k": cfg["num_experts_per_tok"],
        "groups": cfg["n_group"], "kept_groups": cfg["topk_group"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["num_shared_experts"]
        * cfg["moe_shared_expert_intermediate_size"],
    }


def layer_ids(cfg):
    """The published index of each layer held here."""
    return cfg.get("kept_layers") or list(range(cfg["num_hidden_layers"]))


def is_latent_attention_layer(cfg, i):
    return (layer_ids(cfg)[i] + 1) % cfg["layer_group_size"] == 0


def is_expert_layer(cfg, i):
    return layer_ids(cfg)[i] >= cfg["first_k_dense_replace"]


def layer_leaves(cfg, i):
    """[(name within the layer, shape, kind)] of layer i."""
    n = dims(cfg)
    h, heads = n["h"], n["heads"]
    if is_latent_attention_layer(cfg, i):
        mixer = [
            ("attention.query_layernorm", (n["nope"] + n["rope"],), "ones"),
            ("attention.key_layernorm", (n["nope"],), "ones"),
            ("attention.key_rope_layernorm", (n["rope"],), "ones"),
            ("attention.q_proj.weight",
             (h, heads * (n["nope"] + n["rope"])), "normal"),
            ("attention.kv_a_proj_with_mqa.weight",
             (h, n["rank"] + n["rope"]), "normal"),
            ("attention.kv_a_layernorm.weight", (n["rank"],), "ones"),
            ("attention.kv_b_proj.weight",
             (n["rank"], heads * (n["nope"] + n["dv"])), "normal"),
            ("attention.g_proj.weight", (h, heads), "normal"),
            ("attention.o_proj.weight", (heads * n["dv"], h), "normal"),
        ]
    else:
        width = heads * n["d"]
        mixer = [
            ("attention.q_conv1d", (n["conv"], width), "normal"),
            ("attention.k_conv1d", (n["conv"], width), "normal"),
            ("attention.v_conv1d", (n["conv"], width), "normal"),
            ("attention.A_log", (heads,), "zeros"),
            ("attention.dt_bias", (width,), "zeros"),
            ("attention.o_norm", (n["d"],), "ones"),
            ("attention.q_proj.weight", (h, width), "normal"),
            ("attention.k_proj.weight", (h, width), "normal"),
            ("attention.v_proj.weight", (h, width), "normal"),
            ("attention.f_proj.weight", (h, width), "normal"),
            ("attention.b_proj.weight", (h, heads), "normal"),
            ("attention.g_proj.weight", (h, heads), "normal"),
            ("attention.o_proj.weight", (width, h), "normal"),
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
    return ([("input_layernorm.weight", (h,), "ones")] + mixer
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
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
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
