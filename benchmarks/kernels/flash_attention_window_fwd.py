"""The forward band-schedule kernel of kernels/pallas/flash_attention.py,
by the name its `pl_call` gives it (`flash_attention_window_fwd`), anchored
at the instruction's own name. A recomputed layer keeps the kernel's
output, so it runs once a sliding layer and step.
"""
from benchmarks.kernels.gated_delta_rule_fwd import least_of

PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_window_fwd\S* = "


def least_seconds(counts, cell, peaks):
    """The band's pairs (never the triangle) at 4 x head_dim operations a
    head, over the bf16 peak, or q, k, v read and o written once over HBM
    bandwidth, whichever takes longer: `window_fwd_*`, what
    benchmarks/train_mellum2.py records from
    work_mellum2.window_core_work."""
    return least_of(counts, peaks, ("window_fwd",))
