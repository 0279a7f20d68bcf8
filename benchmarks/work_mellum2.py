"""The operations and bytes the Mellum 2 block needs, from shapes alone
(benchmarks/work.py for the Llama block): a matrix product of [m, k] by
[k, n] needs 2mkn operations, the embedding is a gather and needs none,
nothing recomputed is counted. A full-attention layer's scores are
counted over half the square; a sliding layer's over the band's pairs, a
query at position p seeing min(p + 1, window) keys, never the triangle.
The routed experts are counted at the rows a uniform router sends here,
k x held / experts a token, in the step's required operations; the
grouped products' own work at the rows the caller gives.
"""
from __future__ import annotations

from benchmarks import weights_mellum2 as W


def layer_kinds(cfg):
    """(sliding layers, full layers) of the stack."""
    sliding = sum(W.is_sliding_layer(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    return sliding, cfg["num_hidden_layers"] - sliding


def band_pairs(seq, window):
    """(query, key) pairs a row of seq tokens attends to under the band:
    sum over p of min(p + 1, window)."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def visited_pairs(seq, window, block_q, block_k):
    """The pairs the tiles of a band schedule compute in a row: each q
    block of block_q rows takes every key block from the one its first
    row's first key lies in to the one its last row lies in, block_q x
    block_k pairs each, but for the tiles the kernels run in halves
    (square tiles no wider than the window and a multiple of 256: the
    diagonal tile and the far-edge tile, whose q - k offset is the window
    rounded up to whole tiles), which compute three quarters of theirs."""
    halves = block_q == block_k <= window and block_q % 256 == 0
    far = block_k * -(-(window - 1) // block_k)
    quarters = 0
    for i in range(seq // block_q):
        q_lo = i * block_q
        first = max((q_lo - window + 1) // block_k, 0)
        for j in range(first, (q_lo + block_q - 1) // block_k + 1):
            k_lo = j * block_k
            inside = (k_lo + block_k - 1 <= q_lo
                      and q_lo + block_q - 1 - k_lo <= window - 1)
            edge = halves and not inside and q_lo - k_lo in (0, far)
            quarters += 3 if edge else 4
    return quarters * block_q * block_k // 4


def attention_matmul_params(cfg):
    n = W.dims(cfg)
    return n["h"] * n["d"] * (2 * n["heads"] + 2 * n["kv"])


def routed_rows_per_token(cfg):
    """Assignments a token sends to this chip's experts, expected."""
    n = W.dims(cfg)
    return n["k"] * n["held"] / n["experts"]


def moe_matmul_params(cfg):
    """Weights of one expert layer a token is multiplied with: the router
    and the routed experts at their expected rows."""
    n = W.dims(cfg)
    return n["h"] * n["experts"] + routed_rows_per_token(cfg) * 3 * n["h"] \
        * n["f"]


def score_flops_per_key(cfg):
    """q . k and p . v of every head for one (query, key) pair."""
    n = W.dims(cfg)
    return 4 * n["heads"] * n["d"]


def forward_flops_per_token(cfg, seq_len):
    """A token of a row of seq_len, head included; on a full layer
    position p attends to p + 1 keys, (seq_len + 1) / 2 on average."""
    n = W.dims(cfg)
    sliding, full = layer_kinds(cfg)
    keys = (sliding * band_pairs(seq_len, n["window"]) / seq_len
            + full * (seq_len + 1) / 2)
    layers = cfg["num_hidden_layers"]
    return (layers * 2 * (attention_matmul_params(cfg)
                          + moe_matmul_params(cfg))
            + score_flops_per_key(cfg) * keys + 2 * n["h"] * n["v"])


def train_flops_per_token(cfg, seq_len):
    """Forward and backward (twice the forward)."""
    return 3 * forward_flops_per_token(cfg, seq_len)


def window_core_work(cfg, rows, seq, backward=False):
    """(operations, bytes) of the sliding layers' attention cores over
    `rows` rows of seq. Forward: the band's pairs at score_flops_per_key;
    q, k, v read once a head or kv head, o written and the float32
    log-sum-exp. Backward: twice the operations; those read again with o
    and its cotangent, the three cotangents written."""
    n = W.dims(cfg)
    sliding, _ = layer_kinds(cfg)
    tokens = rows * seq
    ops = sliding * rows * score_flops_per_key(cfg) * band_pairs(
        seq, n["window"])
    q = o = 2 * n["heads"] * n["d"]
    kv = 2 * 2 * n["kv"] * n["d"]
    nbytes = sliding * tokens * (q + kv + o + 4 * n["heads"])
    if backward:
        return 2 * ops, 2 * nbytes + sliding * tokens * (q + kv)
    return ops, nbytes


def grouped_matmul_work(cfg, rows, calls, backward=False):
    """(operations, bytes) of the three grouped products of an expert
    layer over `rows` routed rows in `calls` calls of the layer (layers x
    steps): 2 x rows x h x f each. Bytes: the held experts' weights once a
    call, and each product's rows in and out. Backward: twice both."""
    n = W.dims(cfg)
    ops = 3 * 2 * rows * n["h"] * n["f"]
    weights = 2 * 3 * n["held"] * n["h"] * n["f"]
    acts = 2 * rows * (2 * (n["h"] + n["f"]) + (n["f"] + n["h"]))
    nbytes = calls * weights + acts
    if backward:
        return 2 * ops, 2 * nbytes
    return ops, nbytes
