"""The operations and bytes the Ling 3.0 block needs, from shapes alone
(benchmarks/work.py for the Llama block): a matrix product of [m, k] by
[k, n] needs 2mkn operations, causal attention half the square, the
recurrence 6 d_k d_v a head and token (the decay, S^T k, k u^T, S^T q),
the embedding is a gather and needs none, nothing recomputed is counted.
In the step's required operations the routed experts are counted at the
rows a uniform router sends here, k x held / experts a token; the grouped
products' own work is counted at the rows the caller gives.
"""
from __future__ import annotations

from benchmarks import weights_ling3 as W


def layer_kinds(cfg):
    """(KDA layers, MLA layers, dense layers, expert layers)."""
    layers = range(cfg["num_hidden_layers"])
    mla = sum(W.is_latent_attention_layer(cfg, i) for i in layers)
    experts = sum(W.is_expert_layer(cfg, i) for i in layers)
    return (len(layers) - mla, mla, len(layers) - experts, experts)


def kda_matmul_params(cfg):
    """Weights of one KDA mixer a token is multiplied with: W_q, W_k, W_v,
    W_f, W_o and the two head-wide products (beta, the output gate)."""
    n = W.dims(cfg)
    width = n["heads"] * n["d"]
    return 5 * n["h"] * width + 2 * n["h"] * n["heads"]


def kda_core_flops_per_token(cfg):
    n = W.dims(cfg)
    return 6 * n["heads"] * n["d"] * n["d"]


def mla_matmul_params(cfg):
    """deepseek_v3's four products and the output gate."""
    n = W.dims(cfg)
    return (n["h"] * n["heads"] * (n["nope"] + n["rope"])
            + n["h"] * (n["rank"] + n["rope"])
            + n["rank"] * n["heads"] * (n["nope"] + n["dv"])
            + n["heads"] * n["dv"] * n["h"] + n["h"] * n["heads"])


def mla_core_flops_per_key(cfg):
    n = W.dims(cfg)
    return n["heads"] * (2 * (n["nope"] + n["rope"]) + 2 * n["dv"])


def routed_rows_per_token(cfg):
    """Assignments a token sends to this chip's experts, expected."""
    n = W.dims(cfg)
    return n["k"] * n["held"] / n["experts"]


def moe_matmul_params(cfg):
    n = W.dims(cfg)
    return (n["h"] * n["experts"] + 3 * n["h"] * n["fs"]
            + routed_rows_per_token(cfg) * 3 * n["h"] * n["f"])


def forward_flops_per_token(cfg, seq_len):
    """A token of a row of seq_len, head included."""
    n = W.dims(cfg)
    kda, mla, dense, experts = layer_kinds(cfg)
    return (
        kda * (2 * kda_matmul_params(cfg) + kda_core_flops_per_token(cfg))
        + mla * (2 * mla_matmul_params(cfg)
                 + mla_core_flops_per_key(cfg) * (seq_len + 1) / 2)
        + dense * 2 * 3 * n["h"] * n["dense"]
        + experts * 2 * moe_matmul_params(cfg)
        + 2 * n["h"] * n["v"])


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def kda_core_work(cfg, sequences, seq_len, backward=False):
    """(operations, bytes) of the KDA kernels over all KDA layers for
    `sequences` rows of `seq_len`: 6 d_k d_v a head and token forward;
    q, k, v (bf16), g (float32, a value a key channel) and beta read and o
    written once. Backward: twice the operations, those read again with o's
    cotangent and the five cotangents written."""
    n = W.dims(cfg)
    kda, _, _, _ = layer_kinds(cfg)
    tokens = sequences * seq_len
    width = n["heads"] * n["d"]
    ops = kda * tokens * kda_core_flops_per_token(cfg)
    # q, k, v and o in bf16, g in float32, beta in float32
    nbytes = kda * tokens * (4 * 2 * width + 4 * width + 4 * n["heads"])
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes


def grouped_matmul_work(cfg, rows, calls, backward=False):
    """(operations, bytes) of the three grouped products of an expert
    layer over `rows` routed rows in `calls` calls of the layer (layers x
    steps), as work_deepseek_v3's: 2 x rows x h x f each; the held
    experts' weights once a call and each product's rows in and out;
    backward twice both."""
    n = W.dims(cfg)
    ops = 3 * 2 * rows * n["h"] * n["f"]
    weights = 2 * 3 * n["held"] * n["h"] * n["f"]
    acts = 2 * rows * (2 * (n["h"] + n["f"]) + (n["f"] + n["h"]))
    nbytes = calls * weights + acts
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes
