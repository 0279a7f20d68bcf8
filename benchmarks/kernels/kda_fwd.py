"""The forward kernel of kernels/pallas/kda.py, by the name its `pl_call`
gives it (`kda_fwd`), anchored at the instruction's own name. With a layer
rematerialised in the backward pass the kernel runs twice a step; its
required work is counted once.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*kda_fwd\S* = "


def least_seconds(counts, cell, peaks):
    """6 d_k d_v operations a head and token; q, k, v, g, beta and o once
    (memory-bound on a v5e: 48 KiB a token and layer against 3.1 MFLOP),
    as benchmarks/train_ling3.py records them from work_ling3.py."""
    return least_of(counts, peaks, ("kda_fwd",))
