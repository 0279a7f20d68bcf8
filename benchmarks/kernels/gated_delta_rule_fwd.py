"""The forward kernel of kernels/pallas/gated_delta_rule.py, by the name its
`pl_call` gives it (`gated_delta_rule_fwd`), anchored at the instruction's
own name. With a layer rematerialised in the backward pass the kernel runs
twice a step; its required work is counted once.
"""
PROGRAMS = r"staged|train"
OPS = r"^%\S*gated_delta_rule_fwd\S* = "


def least_of(counts, peaks, names):
    """The larger of required operations over the bf16 peak and bytes read
    and written once over the memory's bandwidth, summed over `names`
    (`gdr_fwd`, `gdr_bwd`, `gmm_fwd`, `gmm_bwd`: what
    benchmarks/train_hybrid.py records from work_qwen3_next.py)."""
    if any(not counts.get(n + "_flops") for n in names):
        return None
    flops = sum(counts[n + "_flops"] for n in names)
    nbytes = sum(counts[n + "_bytes"] for n in names)
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def least_seconds(counts, cell, peaks):
    """6 d_k d_v operations a value head and token (memory-bound on a
    v5e: 36 KiB a token and layer against 3.1 MFLOP)."""
    return least_of(counts, peaks, ("gdr_fwd",))
