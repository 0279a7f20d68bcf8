"""Seeded weights for the decoder, shared by the system under test and the
plain reference: every leaf is a function of (seed, leaf index) alone, so
the program can take all of them from one jitted call and the reference
can regenerate any one leaf when it needs it.

Matrices are N(0, initializer_range) as the source's config states, norm
weights are ones. Values are drawn in float32 and rounded once to the
served dtype, so a float32 copy of a leaf is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def leaf_specs(cfg):
    """[(name, shape)] in the order models.llama.LlamaForCausalLM creates
    its parameters; Linear weights are [in, out]."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    shapes = {"ln1": (h,), "wq": (h, q), "wk": (h, kv), "wv": (h, kv),
              "wo": (q, h), "ln2": (h,), "wg": (h, f), "wu": (h, f),
              "wd": (f, h)}
    specs = [("embed", (v, h))]
    for i in range(cfg["num_hidden_layers"]):
        specs += [(f"layers.{i}.{n}", shapes[n]) for n in LAYER_LEAVES]
    return specs + [("norm", (h,)), ("head", (h, v))]


def seed_key(seed):
    """A key from any whole number; seeds past 31 bits fold in twice."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed % (1 << 31)), seed >> 31)


def _leaf(key, index, shape, std, dtype):
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, index)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("index", "shape", "std",
                                             "dtype"))
def make_leaf(key, *, index, shape, std, dtype):
    """One leaf by its index in leaf_specs (the reference's way in)."""
    return _leaf(key, index, shape, std, dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "std", "dtype"))
def _make_all(key, *, shapes, std, dtype):
    return [_leaf(key, i, s, std, dtype) for i, s in enumerate(shapes)]


def make_weights(cfg, seed, dtype=jnp.bfloat16):
    """Every leaf in one jitted call: {name: array}, ordered."""
    specs = leaf_specs(cfg)
    arrays = _make_all(
        seed_key(seed), shapes=tuple(s for _, s in specs),
        std=float(cfg.get("initializer_range", 0.02)), dtype=dtype)
    return dict(zip((n for n, _ in specs), arrays))
