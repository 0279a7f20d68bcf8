"""The three band-schedule kernels of kernels/pallas/flash_attention.py
together (`flash_attention_window_fwd`, `_bwd_dq`, `_bwd_dkv`): their share
of the device's time. Anchored at the instruction's own name, and apart
from the causal kernels' patterns in both directions.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_window_(fwd|bwd_dq|bwd_dkv)\S* = "


def least_seconds(counts, cell, peaks):
    return least_of(counts, peaks, ("window_fwd", "window_bwd"))
