"""The three kernels of kernels/pallas/grouped_matmul.py in a training
step: `grouped_matmul` (forward), `grouped_matmul_dlhs` and
`grouped_matmul_drhs` (its VJP), anchored at the instruction's own name.
Required work: three products of 2 x rows x hidden x expert width a layer
forward and twice that backward, at the rows the window's steps sent
here (train_hybrid.record_work: the layers' expert_load summed).
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*grouped_matmul\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("gmm_fwd", "gmm_bwd"))
