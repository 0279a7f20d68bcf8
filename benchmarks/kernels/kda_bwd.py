"""The backward kernel of kernels/pallas/kda.py: every kernel whose name
starts `kda_bwd`.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*kda_bwd\S* = "


def least_seconds(counts, cell, peaks):
    """Twice the forward's operations and bytes."""
    return least_of(counts, peaks, ("kda_bwd",))
