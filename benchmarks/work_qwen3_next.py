"""The operations and bytes the Qwen3-Next block needs, from shapes alone
(benchmarks/work.py for the Llama block): a matrix product of [m, k] by
[k, n] needs 2mkn operations, causal attention half the square, the
embedding is a gather and needs none, nothing recomputed is counted. In
the step's required operations the routed experts are counted at the rows
a uniform router sends here, k x held / experts a token; the grouped
products' own work is counted at the rows the caller gives.
"""
from __future__ import annotations

from benchmarks import weights_qwen3_next as W


def layer_kinds(cfg):
    """(DeltaNet layers, attention layers) of the stack."""
    attends = sum(W.is_attention_layer(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attends, attends


def deltanet_matmul_params(cfg):
    """Weights of one DeltaNet mixer that a token is multiplied with."""
    n = W.dims(cfg)
    return n["h"] * (2 * n["key_dim"] + 2 * n["value_dim"] + 2 * n["hv"]) \
        + n["value_dim"] * n["h"]


def attention_matmul_params(cfg):
    n = W.dims(cfg)
    return n["h"] * n["d"] * (2 * n["heads"] + 2 * n["kv"]) \
        + n["heads"] * n["d"] * n["h"]


def routed_rows_per_token(cfg):
    """Assignments a token sends to this chip's experts, expected."""
    n = W.dims(cfg)
    return n["k"] * n["held"] / n["experts"]


def moe_matmul_params(cfg):
    """Weights of one expert layer a token is multiplied with: the
    router, the shared expert with its gate, and the routed experts at
    their expected rows."""
    n = W.dims(cfg)
    return n["h"] * n["experts"] + 3 * n["h"] * n["fs"] + n["h"] \
        + routed_rows_per_token(cfg) * 3 * n["h"] * n["f"]


def recurrence_flops_per_token(cfg):
    """One DeltaNet layer's recurrence, forward: S'^T k, k u^T and S^T q
    are 2 d_k d_v each a value head."""
    n = W.dims(cfg)
    return n["hv"] * 6 * n["dk"] * n["dv"]


def conv_flops_per_token(cfg):
    n = W.dims(cfg)
    return 2 * n["conv"] * (2 * n["key_dim"] + n["value_dim"])


def forward_flops_per_token(cfg, seq_len):
    """A token of a row of seq_len, head included; position p attends to
    p + 1 keys, so a token sees (seq_len + 1) / 2 on average."""
    n = W.dims(cfg)
    deltanet, attends = layer_kinds(cfg)
    return (
        deltanet * (2 * deltanet_matmul_params(cfg)
                    + recurrence_flops_per_token(cfg)
                    + conv_flops_per_token(cfg))
        + attends * (2 * attention_matmul_params(cfg)
                     + 4 * n["heads"] * n["d"] * (seq_len + 1) / 2)
        + cfg["num_hidden_layers"] * 2 * moe_matmul_params(cfg)
        + 2 * n["h"] * n["v"])


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def delta_rule_work(cfg, tokens, backward=False):
    """(operations, bytes) of the gated delta rule over all DeltaNet
    layers for `tokens` tokens. Forward: q, k, v, g and beta read, o
    written once. Backward: twice the operations; those read again with
    o's cotangent, five cotangents written."""
    n = W.dims(cfg)
    deltanet, _ = layer_kinds(cfg)
    ops = deltanet * tokens * recurrence_flops_per_token(cfg)
    qkv = 2 * (2 * n["key_dim"] + n["value_dim"])     # bf16
    gates = 2 * 4 * n["hv"]                            # float32
    o = 2 * n["value_dim"]
    nbytes = deltanet * tokens * (qkv + gates + o)
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes


def grouped_matmul_work(cfg, rows, calls, backward=False):
    """(operations, bytes) of the three grouped products of an expert
    layer over `rows` routed rows in `calls` calls of the layer (layers x
    steps): 2 x rows x h x f each. Bytes: the held experts' weights once a
    call, and each product's rows in and out. Backward: twice the
    operations (dlhs and drhs), the weights read and their gradients
    written, the rows and their cotangents."""
    n = W.dims(cfg)
    ops = 3 * 2 * rows * n["h"] * n["f"]
    weights = 2 * 3 * n["held"] * n["h"] * n["f"]
    acts = 2 * rows * (2 * (n["h"] + n["f"]) + (n["f"] + n["h"]))
    nbytes = calls * weights + acts
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes
