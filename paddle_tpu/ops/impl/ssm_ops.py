"""State-space operations: Mamba-2's selective scan in its chunked (SSD)
form (kernels/pallas/mamba2_ssd.py), the mixer of granite-4.0-h's Mamba
layers. What only that block's mixer needs (its convolution with bias, its
gated norm over the whole inner width) is private to
models/granite_hybrid.py.
"""
from __future__ import annotations


def mamba2_ssd(x, dt, a, b, c, d, *, chunk=256, n_groups=1, impl="auto"):
    """The public op face of ``kernels.pallas.mamba2_ssd`` (Pallas imports
    stay function-scoped, the nn_ops pattern): x [b, t, heads * d_head],
    dt [b, t, heads] after its softplus, a and d [heads], b and c
    [b, t, n_groups * d_state] -> y like x."""
    from ...kernels.pallas.mamba2_ssd import mamba2_ssd as _ssd

    return _ssd(x, dt, a, b, c, d, chunk, n_groups=n_groups, impl=impl)
