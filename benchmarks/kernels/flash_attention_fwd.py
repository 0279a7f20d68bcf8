"""The forward kernel of kernels/pallas/flash_attention.py, by the name its
`pl_call` gives it (`flash_attention_fwd`).

A device trace names an operation by its whole instruction, operands
included, so OPS is anchored at the instruction's own name: an operation
that merely consumes the kernel's result does not match.
"""
from benchmarks import work

PROGRAMS = r"staged|train"
OPS = r"^%\S*flash_attention_fwd\S* = "


def least_seconds(counts, cell, peaks):
    """Compute-bound at training lengths: the forward is one third of the
    required forward and backward operations, over the bf16 peak."""
    n = counts.get("flash_sequences")
    if not n:
        return None
    flops = work.flash_train_flops(
        cell["config"], counts["flash_seq_len"], n)
    return flops / 3 / peaks["bf16_flops"]
