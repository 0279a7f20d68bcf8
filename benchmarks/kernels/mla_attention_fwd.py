"""The forward MLA kernel of kernels/pallas/flash_attention.py, by the name
its `pl_call` gives it (`mla_attention_fwd`), anchored at the instruction's
own name. With a layer rematerialised in the backward pass the kernel runs
twice a step; its required work is counted once.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*mla_attention_fwd\S* = "


def least_seconds(counts, cell, peaks):
    """Half the square of heads x (2 (d_nope + d_rope) + 2 d_v) operations
    a query and key over the bf16 peak, or the operands read and the
    output written once over HBM bandwidth, whichever takes longer
    (compute-bound at 8k: 83.9 MFLOP against 37 KB a token and layer). The
    counts are `mla_fwd_*`, what benchmarks/train_deepseek_v3.py records
    from work_deepseek_v3.attention_core_work."""
    return least_of(counts, peaks, ("mla_fwd",))
