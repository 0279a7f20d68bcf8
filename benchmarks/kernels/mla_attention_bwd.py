"""Both backward MLA kernels of kernels/pallas/flash_attention.py together
(`mla_attention_bwd_dq`, `mla_attention_bwd_dkv`): the mathematics fixes
what the backward needs, not how two kernels share it.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mla_attention_bwd_(dq|dkv)\S* = "


def least_seconds(counts, cell, peaks):
    """Twice the forward's operations over the bf16 peak, or the bytes
    (`mla_bwd_*` of work_deepseek_v3.attention_core_work) over HBM
    bandwidth, whichever takes longer."""
    return least_of(counts, peaks, ("mla_bwd",))
