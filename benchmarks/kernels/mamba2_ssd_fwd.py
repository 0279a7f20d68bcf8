"""The forward kernel of kernels/pallas/mamba2_ssd.py, by the name its
`pl_call` gives it (`mamba2_ssd_fwd`), anchored at the instruction's own
name. With a layer rematerialised in the backward pass the kernel runs
twice a step; its required work is counted once.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mamba2_ssd_fwd\S* = "


def least_seconds(counts, cell, peaks):
    """4 N P operations a head and token (the state's update and its
    read-out) against x, B, C and dt read and y written once: memory-bound
    on a v5e (17 KB a token and layer against 2.1 MFLOP). The counts are
    `ssd_fwd_*`, what benchmarks/train_granite_hybrid.py records from
    work_granite_hybrid.py."""
    return least_of(counts, peaks, ("ssd_fwd",))
