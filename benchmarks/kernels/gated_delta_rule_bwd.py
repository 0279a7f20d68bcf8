"""The backward kernel(s) of kernels/pallas/gated_delta_rule.py: every
kernel whose name starts `gated_delta_rule_bwd`.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*gated_delta_rule_bwd\S* = "


def least_seconds(counts, cell, peaks):
    """Twice the forward's operations and bytes."""
    return least_of(counts, peaks, ("gdr_bwd",))
