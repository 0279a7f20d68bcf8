"""The three MLA kernels together: their share of the device's time.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mla_attention_(fwd|bwd_dq|bwd_dkv)\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("mla_fwd", "mla_bwd"))
