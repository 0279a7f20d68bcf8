"""The operations and bytes the Granite 4.0-H block needs, from shapes alone
(benchmarks/work.py for the Llama block): a matrix product of [m, k] by
[k, n] needs 2mkn operations, causal attention half the square, the
embedding is a gather and needs none (the tied matrix counts once, as the
head), nothing recomputed is counted. The selective scan is counted as the
recurrence asks, whatever implements it: the state update and the read-out,
2 N P operations each a head and token.
"""
from __future__ import annotations

from benchmarks import weights_granite_hybrid as W

layer_kinds = W.layer_kinds


def mamba_matmul_params(cfg):
    """Weights of one Mamba mixer that a token is multiplied with."""
    n = W.dims(cfg)
    return n["h"] * (n["inner"] + n["channels"] + n["mh"]) \
        + n["inner"] * n["h"]


def attention_matmul_params(cfg):
    n = W.dims(cfg)
    return n["h"] * n["d"] * (n["heads"] + 2 * n["kv"]) \
        + n["heads"] * n["d"] * n["h"]


def mlp_matmul_params(cfg):
    n = W.dims(cfg)
    return 3 * n["h"] * n["f"]


def scan_flops_per_token(cfg):
    """One Mamba layer's recurrence, forward: H = decay H + dt B (x) x and
    y = C^T H are 2 N P each a head."""
    n = W.dims(cfg)
    return n["mh"] * 4 * n["n"] * n["mp"]


def conv_flops_per_token(cfg):
    n = W.dims(cfg)
    return 2 * n["conv"] * n["channels"]


def forward_flops_per_token(cfg, seq_len):
    """A token of a row of seq_len, head included; position p attends to
    p + 1 keys, so a token sees (seq_len + 1) / 2 on average."""
    n = W.dims(cfg)
    mamba, attends = layer_kinds(cfg)
    return (
        mamba * (2 * mamba_matmul_params(cfg) + scan_flops_per_token(cfg)
                 + conv_flops_per_token(cfg))
        + attends * (2 * attention_matmul_params(cfg)
                     + 4 * n["heads"] * n["d"] * (seq_len + 1) / 2)
        + cfg["num_hidden_layers"] * 2 * mlp_matmul_params(cfg)
        + 2 * n["h"] * n["v"])


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def scan_work(cfg, tokens, backward=False):
    """(operations, bytes) of the selective scan over all Mamba layers for
    `tokens` tokens. Forward: x, B and C (bf16) and dt (float32) read, y
    written once. Backward: twice both (those read again with y's
    cotangent, their cotangents written)."""
    n = W.dims(cfg)
    mamba, _ = layer_kinds(cfg)
    ops = mamba * tokens * scan_flops_per_token(cfg)
    per_token = 2 * (2 * n["inner"] + 2 * n["g"] * n["n"]) + 4 * n["mh"]
    nbytes = mamba * tokens * per_token
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes
