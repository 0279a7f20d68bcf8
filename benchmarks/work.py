"""The operations and bytes the mathematics needs, from shapes alone.

Nothing here looks at how the program computes: a matrix product of
[m, k] by [k, n] needs 2mkn operations, causal attention half the square,
the embedding is a gather and needs none, nothing recomputed is counted.
"""
from __future__ import annotations


def _dims(cfg):
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    return h, f, v, heads, kv, d


def layer_matmul_params(cfg):
    """Weights of one layer that a token is multiplied with."""
    h, f, _, heads, kv, d = _dims(cfg)
    return h * heads * d + 2 * h * kv * d + heads * d * h + 3 * h * f


def head_flops(cfg):
    h, _, v, _, _, _ = _dims(cfg)
    return 2 * h * v


def span_flops(cfg, start, stop):
    """Forward operations of the tokens at positions [start, stop) of one
    sequence (position p attends to p + 1 keys), without the head."""
    n = stop - start
    keys = (start + 1 + stop) * n / 2          # sum of p + 1
    _, _, _, heads, _, d = _dims(cfg)
    return cfg["num_hidden_layers"] * (
        2 * layer_matmul_params(cfg) * n + 4 * heads * d * keys)


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward) of one token of a row of
    seq_len: causal attention at half the square, head included."""
    fwd = span_flops(cfg, 0, seq_len) / seq_len + head_flops(cfg)
    return 3 * fwd


def flash_train_flops(cfg, seq_len, sequences):
    """Forward and backward operations causal attention needs for
    `sequences` rows in every layer: 4*heads*d*s^2/2 forward, twice that
    backward."""
    _, _, _, heads, _, d = _dims(cfg)
    fwd = 4 * heads * d * seq_len * (seq_len + 1) / 2
    return 3 * fwd * sequences * cfg["num_hidden_layers"]


def paged_decode_work(cfg, live_tokens, slot_steps, bytes_per=2):
    """(operations, bytes) decode attention needs over all layers for
    `live_tokens` keys summed over slots and steps: keys and values of
    live tokens read once, queries read and outputs written."""
    _, _, _, heads, kv, d = _dims(cfg)
    layers = cfg["num_hidden_layers"]
    ops = 4 * heads * d * live_tokens * layers
    nbytes = layers * bytes_per * (
        2 * kv * d * live_tokens + 2 * heads * d * slot_steps)
    return ops, nbytes
