"""Both backward kernels of kernels/pallas/flash_attention.py together
(`flash_attention_bwd_dq`, `flash_attention_bwd_dkv`): the mathematics
fixes what the backward needs, not how two kernels share it.
"""
from benchmarks import work

PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_bwd_(dq|dkv)\S* = "


def least_seconds(counts, cell, peaks):
    """Two thirds of the required forward and backward operations, over
    the bf16 peak."""
    n = counts.get("flash_sequences")
    if not n:
        return None
    flops = work.flash_train_flops(
        cell["config"], counts["flash_seq_len"], n)
    return flops * 2 / 3 / peaks["bf16_flops"]
