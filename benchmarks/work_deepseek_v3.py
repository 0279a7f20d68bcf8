"""The operations and bytes the DeepSeek-V3 block needs, from shapes alone
(benchmarks/work.py for the Llama block): a matrix product of [m, k] by
[k, n] needs 2mkn operations, causal attention half the square, the
embedding is a gather and needs none, nothing recomputed is counted. In
the step's required operations the routed experts are counted at the rows
a uniform router sends here, k x held / experts a token; the grouped
products' own work is counted at the rows the caller gives.
"""
from __future__ import annotations

from benchmarks import weights_deepseek_v3 as W


def layer_kinds(cfg):
    """(dense layers, expert layers) of the stack."""
    experts = sum(W.is_expert_layer(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - experts, experts


def attention_matmul_params(cfg):
    """Weights of one MLA block a token is multiplied with: q_proj,
    kv_a_proj_with_mqa, kv_b_proj, o_proj."""
    n = W.dims(cfg)
    return (n["h"] * n["heads"] * (n["nope"] + n["rope"])
            + n["h"] * (n["rank"] + n["rope"])
            + n["rank"] * n["heads"] * (n["nope"] + n["dv"])
            + n["heads"] * n["dv"] * n["h"])


def attention_core_flops_per_key(cfg):
    """Score and value products of one query against one key, all heads:
    2 (d_nope + d_rope) + 2 d_v a head."""
    n = W.dims(cfg)
    return n["heads"] * (2 * (n["nope"] + n["rope"]) + 2 * n["dv"])


def routed_rows_per_token(cfg):
    """Assignments a token sends to this chip's experts, expected."""
    n = W.dims(cfg)
    return n["k"] * n["held"] / n["experts"]


def moe_matmul_params(cfg):
    """Weights of one expert layer a token is multiplied with: the router,
    the shared experts, and the routed experts at their expected rows."""
    n = W.dims(cfg)
    return (n["h"] * n["experts"] + 3 * n["h"] * n["fs"]
            + routed_rows_per_token(cfg) * 3 * n["h"] * n["f"])


def dense_matmul_params(cfg):
    n = W.dims(cfg)
    return 3 * n["h"] * n["dense"]


def forward_flops_per_token(cfg, seq_len):
    """A token of a row of seq_len, head included; position p attends to
    p + 1 keys, so a token sees (seq_len + 1) / 2 on average."""
    n = W.dims(cfg)
    dense, experts = layer_kinds(cfg)
    return (
        cfg["num_hidden_layers"] * (
            2 * attention_matmul_params(cfg)
            + attention_core_flops_per_key(cfg) * (seq_len + 1) / 2)
        + dense * 2 * dense_matmul_params(cfg)
        + experts * 2 * moe_matmul_params(cfg)
        + 2 * n["h"] * n["v"])


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def attention_core_work(cfg, sequences, seq_len, backward=False):
    """(operations, bytes) of the MLA kernels over all layers for
    `sequences` rows of `seq_len`. Forward: half the square of score and
    value products; q (nope and rope), k_nope, v read and o written once
    a head, the shared rotary key once, the rows' logsumexp. Backward:
    twice the operations; those read again with o and its cotangent, the
    five cotangents written."""
    n = W.dims(cfg)
    layers = cfg["num_hidden_layers"]
    tokens = sequences * seq_len
    ops = layers * tokens * attention_core_flops_per_key(cfg) * (
        seq_len + 1) / 2
    per_head = 2 * (2 * n["nope"] + n["rope"] + 2 * n["dv"]) + 4
    nbytes = layers * tokens * (n["heads"] * per_head + 2 * n["rope"])
    if backward:
        return 2 * ops, 2 * nbytes + layers * tokens * n["heads"] * (
            2 * 2 * n["dv"])
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
