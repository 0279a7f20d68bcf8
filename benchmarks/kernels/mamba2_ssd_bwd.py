"""The backward kernel(s) of kernels/pallas/mamba2_ssd.py: every kernel
whose name starts `mamba2_ssd_bwd`.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mamba2_ssd_bwd\S* = "


def least_seconds(counts, cell, peaks):
    """Twice the forward's operations and bytes."""
    return least_of(counts, peaks, ("ssd_bwd",))
