"""kernels/pallas/flash_attention.py as the training step calls it.

PROGRAMS and OPS are the trace-event patterns (the program's module, the
operation's name and stats) of the kernel's calls; a PR that replaces the
kernel adds a file for the new one beside this.
"""
from benchmarks import work

PROGRAMS = r"staged|train"
OPS = r"tpu_custom_call|pallas|flash"


def least_seconds(counts, cell, peaks):
    """Compute-bound at training lengths: required forward and backward
    operations over the bf16 peak."""
    n = counts.get("flash_sequences")
    if not n:
        return None
    flops = work.flash_train_flops(
        cell["config"], counts["flash_seq_len"], n)
    return flops / peaks["bf16_flops"]
