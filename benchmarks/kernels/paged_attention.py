"""kernels/pallas/paged_attention.py as the decode program calls it.

The work is reckoned from the live context lengths the harness records at
every decode step, never from the kernel's grid, its page capacity or its
operand shapes: the same work whatever implements it.
"""
from benchmarks import work

PROGRAMS = r"decode"
OPS = r"tpu_custom_call|pallas|paged"


def least_seconds(counts, cell, peaks):
    live = counts.get("decode_live_tokens")
    if not live:
        return None
    ops, nbytes = work.paged_decode_work(
        cell["config"], live, counts["decode_slot_steps"])
    return max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
