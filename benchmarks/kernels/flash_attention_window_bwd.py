"""Both backward band-schedule kernels of kernels/pallas/flash_attention.py
together (`flash_attention_window_bwd_dq`, `_bwd_dkv`): the mathematics
fixes what the backward needs, not how two kernels share it.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_window_bwd_(dq|dkv)\S* = "


def least_seconds(counts, cell, peaks):
    """Twice the forward's operations over the bf16 peak, or the bytes
    (`window_bwd_*`) over HBM bandwidth, whichever takes longer."""
    return least_of(counts, peaks, ("window_bwd",))
