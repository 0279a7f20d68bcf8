"""Forward and backward kernels of Kimi Delta Attention together: the KDA
layers' kernels' share of the device's time.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*kda_(fwd|bwd)\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("kda_fwd", "kda_bwd"))
