"""Forward and backward kernels of the Mamba-2 selective scan together: the
state-space layers' kernels' share of the device's time.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mamba2_ssd_(fwd|bwd)\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("ssd_fwd", "ssd_bwd"))
