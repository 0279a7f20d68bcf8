"""The `flash_attention_bwd_dkv` kernel alone: a share of the device's time,
no roofline, since how the backward's work is split between `dq` and
`dkv` is a matter of implementation.
"""
PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_bwd_dkv\S* = "


def least_seconds(counts, cell, peaks):
    return None
