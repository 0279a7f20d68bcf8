"""Forward and backward kernels of the gated delta rule together: the
DeltaNet layers' share of the device's time.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*gated_delta_rule_(fwd|bwd)\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("gdr_fwd", "gdr_bwd"))
