"""Seeded weights for the Granite 4.0-H block, shared by the system under
test and the plain reference (benchmarks/reference/granite_hybrid.py): every
leaf is a function of (seed, leaf index) alone, as benchmarks/weights.py
makes the Llama block's.

The leaves carry the names `GraniteHybridForCausalLM.named_parameters()`
gives them, in the order the model creates them (a test holds the two lists
together); the embedding is the head (tied), so there is no head leaf.
Linear weights are [in, out], the convolution's [taps, channels]. Kinds:
`normal` N(0, initializer_range); `zeros` (the convolution's bias); `ones`
(the norms, dt_bias, D); `a_range` = log(1 .. heads), A as the source's
mixer creates it. Values are drawn in float32 and rounded once to the
served dtype, so a float32 copy of a leaf is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key         # noqa: F401  (re-exported)

MAMBA, ATTENTION = "mamba", "attention"


def dims(cfg):
    """The sizes the leaf list, the reference and the work file share."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    mh, mp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {
        "h": h, "v": cfg["vocab_size"], "heads": heads,
        "kv": cfg["num_key_value_heads"], "d": h // heads,
        "mh": mh, "mp": mp, "g": g, "n": n, "inner": mh * mp,
        "conv": cfg["mamba_d_conv"], "channels": mh * mp + 2 * g * n,
        "f": cfg["shared_intermediate_size"],
    }


def layer_kinds(cfg):
    """(Mamba layers, attention layers) of the stack."""
    attends = sum(t == ATTENTION for t in cfg["layer_types"])
    return len(cfg["layer_types"]) - attends, attends


def layer_leaves(cfg, i):
    """[(name within the layer, shape, kind)] of layer i."""
    n = dims(cfg)
    h = n["h"]
    if cfg["layer_types"][i] == ATTENTION:
        mixer = [
            ("self_attn.q_proj.weight", (h, n["heads"] * n["d"]), "normal"),
            ("self_attn.k_proj.weight", (h, n["kv"] * n["d"]), "normal"),
            ("self_attn.v_proj.weight", (h, n["kv"] * n["d"]), "normal"),
            ("self_attn.o_proj.weight", (n["heads"] * n["d"], h), "normal"),
        ]
    else:
        mixer = [
            ("mamba.conv_weight", (n["conv"], n["channels"]), "normal"),
            ("mamba.conv_bias", (n["channels"],), "zeros"),
            ("mamba.dt_bias", (n["mh"],), "ones"),
            ("mamba.A_log", (n["mh"],), "a_range"),
            ("mamba.D", (n["mh"],), "ones"),
            ("mamba.norm_weight", (n["inner"],), "ones"),
            # columns: z | x | B | C | dt
            ("mamba.in_proj.weight",
             (h, n["inner"] + n["channels"] + n["mh"]), "normal"),
            ("mamba.out_proj.weight", (n["inner"], h), "normal"),
        ]
    return ([("input_layernorm.weight", (h,), "ones")] + mixer + [
        ("post_attention_layernorm.weight", (h,), "ones"),
        ("shared_mlp.input_linear.weight", (h, 2 * n["f"]), "normal"),
        ("shared_mlp.output_linear.weight", (n["f"], h), "normal")])


def leaf_specs(cfg):
    """[(name, shape, kind)] in the model's creation order."""
    n = dims(cfg)
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    specs = [("model.embed_tokens.weight", (n["v"], n["h"]), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"model.layers.{i}.{name}", shape, kind)
                  for name, shape, kind in layer_leaves(cfg, i)]
    return specs + [("model.norm.weight", (n["h"],), "ones")]


def _leaf(key, index, shape, kind, std, dtype):
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "a_range":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
                       ).astype(dtype)
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
