"""Auto-generated OpTest sweep over ops.yaml.

ref: the reference runs 1,196 per-op test files through
test/legacy_test/op_test.py:418 (forward vs oracle + analytic-vs-numeric
gradient per op/dtype). This sweep derives one forward check (finite,
well-formed outputs) and one numeric-gradient check per differentiable
op DIRECTLY from ops.yaml, so every new yaml entry is tested by default:
an op is either swept here or carries an explicit skip reason, and the
coverage floor (>=300 swept) is itself asserted.

Input synthesis: Tensor args default to [2,3] float32 in (0.15, 0.85)
(inside the domain of log/asin/sqrt/...); HINTS overrides shapes, dtypes,
ranges, attrs, and grad eligibility per op where the generic recipe
cannot apply (conv NCHW, index tensors, SPD matrices, ...).
"""
from __future__ import annotations

import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import ops as F

from op_test import GRAD_TOL

_YAML = os.path.join(
    os.path.dirname(__file__), "..", "paddle_tpu", "ops", "ops.yaml"
)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "paddle_tpu", "ops"))
from gen import parse_args  # noqa: E402  (the repo's own yaml arg parser)


def _load_ops():
    entries, cur = [], None
    for line in open(_YAML):
        if line.startswith("- op:"):
            cur = {"op": line.split(":", 1)[1].strip()}
            entries.append(cur)
        elif cur is not None and re.match(r"\s+\w+:", line):
            k, v = line.strip().split(":", 1)
            cur[k] = v.strip()
    return entries


ENTRIES = {e["op"]: e for e in _load_ops()}

# ---------------------------------------------------------------------------
# Ops not swept here, each with the test file that owns it or the reason.
SKIP = {
    # random ops: draws checked in test_ops_math/test_jit rng tests;
    # shape/finiteness swept via fwd below for the simple ones
    "randperm": "no tensor inputs + int dtype; covered by generation tests",
    "multinomial": "distribution-level checks in test_sparse_quant",
    "standard_gamma": "rng op; distribution moments unstable at [2,3]",
    "poisson": "rng op; integer-valued output",
    "rnn": "multi-gate recurrent contract; owned by test_nn_layers LSTM/GRU",
    "moe_gate_dispatch": "sort-based routing contract owned by test_sp_moe",
    "moe_combine": "owned by test_sp_moe",
    "moe_ragged_dispatch": "ragged routing contract owned by test_sp_moe",
    "moe_ragged_combine": "int32 order/weights contract owned by test_sp_moe",
    "grouped_matmul": "segment contract owned by test_pallas_kernels",
    "moe_held_dispatch": "held-share routing owned by test_qwen3_next",
    "moe_held_experts": "passes over kept rows owned by test_qwen3_next",
    "gated_delta_rule": "owned by test_gated_delta_rule",
    "mamba2_ssd": "six-operand scan contract owned by test_mamba2_ssd",
    "mla_attention": "five-operand score contract owned by test_deepseek_v3",
    "fused_linear_cross_entropy": "chunked loss owned by test_fused_loss",
    "fused_rotary_position_embedding": "owned by test_pallas_kernels",
    "rope_qk": "owned by test_pallas_kernels",
    "fused_bias_act": "owned by test_pallas_kernels",
    "empty": "uninitialized values are unasserted by contract",
    "empty_like": "uninitialized values are unasserted by contract",
    "batch_norm_with_stats": "stats plumbing owned by test_nn_layers",
    "max_pool2d_with_index": "tuple contract owned by test_nn_layers",
    "interpolate": "mode matrix owned by test_nn_layers",
    "upsample": "alias of interpolate",
    "histogram": "binning asserted in test_ops_math",
    "lstsq": "tuple-of-4 contract; rank cases in test_einsum_affine",
    "lu": "pivot encoding asserted in test_ops_math",
    "eig": "complex eigenvectors are phase-ambiguous",
    "eigvals": "complex spectrum; unordered comparison done in test_ops_math",
    "crop": "offset semantics owned by test_io_vision",
    "ctc_loss": "torch-oracle fwd+grad checks owned by "
                "test_ops_math.TestCTCLoss",
}

# ---------------------------------------------------------------------------
# Per-op synthesis overrides. Keys:
#   inputs: dict name -> np.ndarray (exact arrays)
#   range:  (lo, hi) uniform range for default-synthesized float tensors
#   shape:  default shape for synthesized tensors
#   attrs:  non-tensor kwargs
#   grad:   False -> forward-only; str/list -> wrt those inputs
#   out:    output index for tuple-returning ops (grad + finiteness)
#   rtol:   grad tolerance override
_R = np.random.RandomState


def _spd(n=3):
    a = _R(0).randn(n, n).astype("float32")
    return a @ a.T + n * np.eye(n, dtype="float32")


def _f(shape, lo=0.15, hi=0.85, seed=0):
    return (_R(seed).uniform(lo, hi, shape)).astype("float32")


def _i(shape, hi, seed=0):
    return _R(seed).randint(0, hi, shape).astype("int64")


HINTS = {
    # ---- math domains -----------------------------------------------------
    "acosh": dict(range=(1.1, 2.0)),
    "atanh": dict(range=(-0.7, 0.7)),
    "erfinv": dict(range=(-0.7, 0.7)),
    "logit": dict(range=(0.2, 0.8)),
    "polygamma": dict(attrs=dict(n=1)),
    "gcd": dict(inputs=dict(x=_i((2, 3), 20), y=_i((2, 3), 20)), grad=False),
    "lcm": dict(inputs=dict(x=_i((2, 3), 9) + 1, y=_i((2, 3), 9) + 1),
                grad=False),
    "ldexp": dict(inputs=dict(x=_f((2, 3)), y=_i((2, 3), 4)), grad="x"),
    "nextafter": dict(grad=False),
    "heaviside": dict(grad=False),
    "signbit": dict(grad=False),
    "sign": dict(grad=False),
    "trunc": dict(grad=False),
    "round": dict(grad=False),
    "ceil": dict(grad=False),
    "floor": dict(grad=False),
    "frac": dict(grad=False),  # sawtooth: numeric diff invalid at jumps
    "sinc": dict(range=(0.2, 0.8)),
    "angle": dict(grad=False),
    "conj": dict(grad=False),
    "real": dict(grad=False),
    "imag": dict(grad=False),
    "nan_to_num": dict(grad=False),
    "remainder": dict(grad=False),  # wrap kinks
    "fmod": dict(grad=False),  # wrap kinks in (0,1) ranges
    "floor_divide": dict(grad=False),
    "divide": dict(range=(0.3, 0.9)),
    "pow": dict(range=(0.3, 0.9)),
    "rsqrt": dict(range=(0.3, 0.9)),
    "reciprocal": dict(range=(0.3, 0.9)),
    "addmm": dict(inputs=dict(
        input=_f((3, 5)), x=_f((3, 4), seed=1), y=_f((4, 5), seed=2))),
    "inner": dict(inputs=dict(x=_f((3, 4)), y=_f((2, 4), seed=1))),
    "outer": dict(inputs=dict(x=_f((3,)), y=_f((4,), seed=1))),
    "multiplex": dict(inputs=dict(
        inputs=[_f((3, 4)), _f((3, 4), seed=1)],
        index=np.array([[0], [1], [0]], "int32")), grad=False),
    "trapezoid": dict(grad="y", inputs=dict(y=_f((2, 5)))),
    "diff": dict(),
    "scale": dict(attrs=dict(scale=2.0, bias=0.5)),
    "clip": dict(attrs=dict(min=0.3, max=0.7), range=(0.0, 1.0),
                 grad=False),  # numeric diff invalid at clip boundaries
    "lerp": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=1),
                             weight=_f((2, 3), seed=2))),
    "stanh": dict(),
    "i0": dict(), "i0e": dict(), "i1": dict(), "i1e": dict(),
    "hypot": dict(), "copysign": dict(grad="x"),
    "atan2": dict(), "logaddexp": dict(), "logaddexp2": dict(),
    "maximum": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=7))),
    "minimum": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=7))),
    "fmax": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=7))),
    "fmin": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=7))),
    # ---- activations ------------------------------------------------------
    "prelu": dict(inputs=dict(x=_f((2, 4), -0.8, 0.8),
                              weight=np.full((1,), 0.25, "float32"))),
    "glu": dict(inputs=dict(x=_f((2, 6), -0.8, 0.8))),
    "maxout": dict(inputs=dict(x=_f((2, 6, 2, 2))),
                   attrs=dict(groups=3), grad=False),
    "gumbel_softmax": dict(grad=False),
    "rrelu": dict(grad=False),
    "softshrink": dict(range=(0.6, 1.4)),
    "hardshrink": dict(range=(0.6, 1.4)),
    "thresholded_relu": dict(range=(1.1, 2.0)),
    "relu": dict(range=(0.1, 0.9)),
    "relu6": dict(range=(0.1, 0.9)),
    "leaky_relu": dict(range=(0.1, 0.9)),
    "hardtanh": dict(range=(-0.8, 0.8)),
    "hardsigmoid": dict(range=(-0.8, 0.8)),
    "hardswish": dict(range=(0.5, 2.0)),
    "swiglu": dict(inputs=dict(x=_f((2, 4), -1, 1),
                               y=_f((2, 4), -1, 1, seed=1))),
    # ---- creation ---------------------------------------------------------
    "zeros": dict(inputs={}, attrs=dict(shape=[2, 3]), grad=False),
    "ones": dict(inputs={}, attrs=dict(shape=[2, 3]), grad=False),
    "full": dict(inputs={}, attrs=dict(shape=[2, 3], fill_value=1.5),
                 grad=False),
    "arange": dict(inputs={}, attrs=dict(start=0, end=6, step=1),
                   grad=False),
    "linspace": dict(inputs={}, attrs=dict(start=0.0, stop=1.0, num=5),
                     grad=False),
    "logspace": dict(inputs={}, attrs=dict(start=0.0, stop=2.0, num=5),
                     grad=False),
    "eye": dict(inputs={}, attrs=dict(num_rows=3), grad=False),
    "tril_indices": dict(inputs={}, attrs=dict(row=3, col=3, offset=0),
                         grad=False),
    "triu_indices": dict(inputs={}, attrs=dict(row=3, col=3, offset=0),
                         grad=False),
    "complex": dict(grad=False),
    "polar": dict(grad=False),
    "vander": dict(inputs=dict(x=_f((4,)))),
    "zeros_like": dict(grad=False),
    "ones_like": dict(grad=False),
    "full_like": dict(attrs=dict(fill_value=2.0), grad=False),
    # ---- fft (fwd contract; complex-cotangent AD owned by
    #      test_fft_distribution) --------------------------------------
    **{op: dict(grad=False) for op in (
        "fft", "ifft", "rfft", "irfft", "hfft", "ihfft", "fft2", "ifft2",
        "rfft2", "irfft2", "fftn", "ifftn", "rfftn", "irfftn", "fftshift",
        "ifftshift",
    )},
    "fft2": dict(grad=False, shape=(3, 4)),
    "ifft2": dict(grad=False, shape=(3, 4)),
    "rfft2": dict(grad=False, shape=(3, 4)),
    "irfft2": dict(grad=False, shape=(3, 4)),
    "fftn": dict(grad=False, shape=(3, 4)),
    "ifftn": dict(grad=False, shape=(3, 4)),
    "rfftn": dict(grad=False, shape=(3, 4)),
    "irfftn": dict(grad=False, shape=(3, 4)),
    "fftfreq": dict(inputs={}, attrs=dict(n=6), grad=False),
    "rfftfreq": dict(inputs={}, attrs=dict(n=6), grad=False),
    # ---- linalg -----------------------------------------------------------
    "matmul": dict(inputs=dict(x=_f((3, 4)), y=_f((4, 5), seed=1))),
    "bmm": dict(inputs=dict(x=_f((2, 3, 4)), y=_f((2, 4, 5), seed=1))),
    "mv": dict(inputs=dict(x=_f((3, 4)), vec=_f((4,), seed=1))),
    "dot": dict(inputs=dict(x=_f((4,)), y=_f((4,), seed=1))),
    "t": dict(inputs=dict(x=_f((3, 4)))),
    "cross": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=1))),
    "kron": dict(inputs=dict(x=_f((2, 2)), y=_f((3, 3), seed=1))),
    "trace": dict(inputs=dict(x=_f((3, 3)))),
    "dist": dict(inputs=dict(x=_f((2, 3)), y=_f((2, 3), seed=1))),
    "cholesky": dict(inputs=dict(x=_spd())),
    "cholesky_solve": dict(
        inputs=dict(x=_f((3, 2)),
                    y=np.linalg.cholesky(_spd()).astype("float32")),
        grad=False),
    "inverse": dict(inputs=dict(x=_spd())),
    "pinv": dict(inputs=dict(x=_f((3, 4))), rtol=2e-2),
    "solve": dict(inputs=dict(x=_spd(), y=_f((3, 2), seed=1))),
    "triangular_solve": dict(
        inputs=dict(x=np.tril(_spd()).astype("float32"),
                    y=_f((3, 2), seed=1)),
        attrs=dict(upper=False)),
    "svd": dict(inputs=dict(x=_f((3, 4))), grad=False, out=1),
    "svdvals": dict(inputs=dict(x=_f((3, 4))), grad=False),
    "qr": dict(inputs=dict(x=_f((4, 3))), grad=False, out=1),
    "eigh": dict(inputs=dict(x=_spd()), grad=False, out=0),
    "eigvalsh": dict(inputs=dict(x=_spd()), grad=False),
    "matrix_power": dict(inputs=dict(x=_spd()), attrs=dict(n=2)),
    "matrix_rank": dict(inputs=dict(x=_f((3, 4))), grad=False),
    "det": dict(inputs=dict(x=_spd())),
    "slogdet": dict(inputs=dict(x=_spd()), grad=False),
    "multi_dot": dict(inputs=dict(
        x=[_f((3, 4)), _f((4, 2), seed=1), _f((2, 3), seed=2)])),
    "norm": dict(),
    "vector_norm": dict(),
    "matrix_norm": dict(inputs=dict(x=_f((3, 4)))),
    "bincount": dict(inputs=dict(x=_i((8,), 5)), grad=False),
    "corrcoef": dict(inputs=dict(x=_f((3, 6))), grad=False),
    "cov": dict(inputs=dict(x=_f((3, 6)))),
    "cdist": dict(inputs=dict(x=_f((3, 4)), y=_f((2, 4), seed=1))),
    "tensordot": dict(inputs=dict(x=_f((3, 4)), y=_f((4, 2), seed=1)),
                      attrs=dict(axes=1)),
    "householder_product": dict(
        inputs=dict(x=_f((4, 3)), tau=_f((3,), seed=1)), grad=False),
    # ---- logic (forward-only: boolean/integral outputs) -------------------
    **{op: dict(grad=False) for op in (
        "equal", "not_equal", "greater_than", "greater_equal", "less_than",
        "less_equal", "logical_and", "logical_or", "logical_xor",
        "logical_not", "isnan", "isinf", "isfinite", "isneginf",
        "isposinf", "isreal", "isclose", "allclose", "equal_all",
    )},
    **{op: dict(inputs=dict(x=_i((2, 3), 8), y=_i((2, 3), 8, seed=1)),
                grad=False)
       for op in ("bitwise_and", "bitwise_or", "bitwise_xor",
                  "bitwise_left_shift", "bitwise_right_shift")},
    "bitwise_not": dict(inputs=dict(x=_i((2, 3), 8)), grad=False),
    # ---- manipulation -----------------------------------------------------
    "reshape": dict(attrs=dict(shape=[3, 2])),
    "unsqueeze": dict(attrs=dict(axis=1)),
    "transpose": dict(attrs=dict(perm=[1, 0])),
    "moveaxis": dict(attrs=dict(source=0, destination=1)),
    "swapaxes": dict(attrs=dict(axis0=0, axis1=1)),
    "split": dict(attrs=dict(num_or_sections=3, axis=1), out=0),
    "chunk": dict(attrs=dict(chunks=3, axis=1), out=0),
    "tensor_split": dict(attrs=dict(num_or_indices=3, axis=1), out=0),
    "unbind": dict(out=0),
    "unstack": dict(out=0),
    "tile": dict(attrs=dict(repeat_times=[2, 1])),
    "expand": dict(inputs=dict(x=_f((1, 3))), attrs=dict(shape=[4, 3])),
    "broadcast_to": dict(inputs=dict(x=_f((1, 3))),
                         attrs=dict(shape=[4, 3])),
    "expand_as": dict(inputs=dict(x=_f((1, 3)), y=_f((4, 3), seed=1)),
                      grad="x"),
    "broadcast_tensors": dict(
        inputs=dict(input=[_f((1, 3)), _f((4, 1), seed=1)]), out=0),
    "concat": dict(inputs=dict(x=[_f((2, 3)), _f((2, 3), seed=1)])),
    "stack": dict(inputs=dict(x=[_f((2, 3)), _f((2, 3), seed=1)])),
    "slice": dict(attrs=dict(axes=[0, 1], starts=[0, 1], ends=[2, 3])),
    "strided_slice": dict(attrs=dict(
        axes=[1], starts=[0], ends=[3], strides=[2])),
    "gather": dict(inputs=dict(x=_f((4, 3)),
                               index=np.array([0, 2, 1], "int64")),
                   grad="x"),
    "gather_nd": dict(inputs=dict(x=_f((3, 4)),
                                  index=np.array([[0, 1], [2, 2]], "int64")),
                      grad="x"),
    "take": dict(inputs=dict(x=_f((3, 4)),
                             index=np.array([0, 5, 7], "int64")),
                 grad="x"),
    "take_along_axis": dict(
        inputs=dict(arr=_f((3, 4)), indices=_i((3, 2), 4)),
        attrs=dict(axis=1), grad="arr"),
    "put_along_axis": dict(
        inputs=dict(arr=_f((3, 4)), indices=_i((3, 2), 4),
                    values=_f((3, 2), seed=2)),
        attrs=dict(axis=1), grad="arr"),
    "scatter": dict(
        inputs=dict(x=_f((4, 3)), index=np.array([1, 3], "int64"),
                    updates=_f((2, 3), seed=2)),
        grad="updates"),
    "scatter_nd_add": dict(
        inputs=dict(x=_f((4, 3)), index=np.array([[1], [3]], "int64"),
                    updates=_f((2, 3), seed=2)),
        grad="x"),
    "scatter_nd": dict(
        inputs=dict(index=np.array([[1], [3]], "int64"),
                    updates=_f((2, 3), seed=2)),
        attrs=dict(shape=[4, 3]), grad="updates"),
    "slice_scatter": dict(
        inputs=dict(x=_f((4, 3)), value=_f((2, 3), seed=2)),
        attrs=dict(axes=[0], starts=[1], ends=[3], strides=[1]),
        grad="x"),
    "index_select": dict(
        inputs=dict(x=_f((4, 3)), index=np.array([0, 2], "int64")),
        grad="x"),
    "index_sample": dict(
        inputs=dict(x=_f((3, 4)), index=_i((3, 2), 4)), grad="x"),
    "index_add": dict(
        inputs=dict(x=_f((4, 3)), index=np.array([0, 2], "int64"),
                    value=_f((2, 3), seed=2)),
        attrs=dict(axis=0), grad="x"),
    "index_put": dict(
        inputs=dict(x=_f((4, 3)),
                    indices=[np.array([0, 2], "int64")],
                    value=_f((2, 3), seed=2)),
        grad="x"),
    "masked_select": dict(
        inputs=dict(x=_f((2, 3)),
                    mask=np.array([[True, False, True]] * 2)),
        grad=False),
    "masked_fill": dict(
        inputs=dict(x=_f((2, 3)),
                    mask=np.array([[True, False, True]] * 2)),
        attrs=dict(value=0.0), grad="x"),
    "masked_scatter": dict(
        inputs=dict(x=_f((2, 3)),
                    mask=np.array([[True, False, True]] * 2),
                    value=_f((4,), seed=2)),
        grad=False),
    "where": dict(
        inputs=dict(condition=np.array([[True, False, True]] * 2),
                    x=_f((2, 3)), y=_f((2, 3), seed=1)),
        grad=["x", "y"]),
    "roll": dict(attrs=dict(shifts=1)),
    "flip": dict(attrs=dict(axis=[0])),
    "rot90": dict(),
    "pad": dict(attrs=dict(pad=[1, 1])),
    "repeat_interleave": dict(attrs=dict(repeats=2)),
    "cast": dict(attrs=dict(dtype="float64"), grad=False),
    "assign": dict(),
    "numel": dict(grad=False),
    "diagonal": dict(inputs=dict(x=_f((3, 3)))),
    "diag": dict(inputs=dict(x=_f((4,)))),
    "diagflat": dict(inputs=dict(x=_f((4,)))),
    "diag_embed": dict(inputs=dict(input=_f((4,)))),
    "tril": dict(inputs=dict(x=_f((3, 3)))),
    "triu": dict(inputs=dict(x=_f((3, 3)))),
    "meshgrid": dict(inputs=dict(inputs=[_f((3,)), _f((4,), seed=1)]),
                     out=0),
    "one_hot": dict(inputs=dict(x=_i((4,), 5)),
                    attrs=dict(num_classes=5), grad=False),
    "unique": dict(inputs=dict(x=_i((8,), 4)), grad=False, out=0),
    "unique_consecutive": dict(inputs=dict(x=np.array([1, 1, 2, 2, 3],
                                                      "int64")),
                               grad=False, out=0),
    "nonzero": dict(inputs=dict(x=np.array([[0.0, 1.0], [2.0, 0.0]],
                                           "float32")),
                    grad=False),
    "shard_index": dict(inputs=dict(input=_i((4, 1), 16)),
                        attrs=dict(index_num=16, nshards=2, shard_id=0),
                        grad=False),
    "as_real": dict(inputs=dict(x=(_f((2, 3)) + 1j * _f((2, 3), seed=1)
                                   ).astype("complex64")),
                    grad=False),
    "as_complex": dict(inputs=dict(x=_f((2, 3, 2))), grad=False),
    "flatten": dict(),
    "squeeze": dict(inputs=dict(x=_f((2, 1, 3)))),
    # ---- nn_ops -----------------------------------------------------------
    "linear": dict(inputs=dict(x=_f((2, 4)), weight=_f((4, 3), seed=1),
                               bias=_f((3,), seed=2))),
    "conv1d": dict(inputs=dict(x=_f((1, 2, 8)),
                               weight=_f((3, 2, 3), seed=1))),
    "conv2d": dict(inputs=dict(x=_f((1, 2, 6, 6)),
                               weight=_f((3, 2, 3, 3), seed=1))),
    "conv3d": dict(inputs=dict(x=_f((1, 2, 4, 4, 4)),
                               weight=_f((3, 2, 2, 2, 2), seed=1))),
    "conv1d_transpose": dict(inputs=dict(x=_f((1, 2, 6)),
                                         weight=_f((2, 3, 3), seed=1))),
    "conv2d_transpose": dict(inputs=dict(x=_f((1, 2, 4, 4)),
                                         weight=_f((2, 3, 3, 3), seed=1))),
    "conv3d_transpose": dict(
        inputs=dict(x=_f((1, 2, 3, 3, 3)),
                    weight=_f((2, 2, 2, 2, 2), seed=1))),
    "max_pool1d": dict(inputs=dict(x=_f((1, 2, 8))),
                       attrs=dict(kernel_size=2)),
    "max_pool2d": dict(inputs=dict(x=_f((1, 2, 6, 6))),
                       attrs=dict(kernel_size=2)),
    "max_pool3d": dict(inputs=dict(x=_f((1, 2, 4, 4, 4))),
                       attrs=dict(kernel_size=2),
                       grad=False),  # near-tie windows break numeric diff
    "avg_pool1d": dict(inputs=dict(x=_f((1, 2, 8))),
                       attrs=dict(kernel_size=2)),
    "avg_pool2d": dict(inputs=dict(x=_f((1, 2, 6, 6))),
                       attrs=dict(kernel_size=2)),
    "avg_pool3d": dict(inputs=dict(x=_f((1, 2, 4, 4, 4))),
                       attrs=dict(kernel_size=2)),
    "adaptive_avg_pool1d": dict(inputs=dict(x=_f((1, 2, 8))),
                                attrs=dict(output_size=4)),
    "adaptive_avg_pool2d": dict(inputs=dict(x=_f((1, 2, 6, 6))),
                                attrs=dict(output_size=3)),
    "adaptive_max_pool2d": dict(inputs=dict(x=_f((1, 2, 6, 6))),
                                attrs=dict(output_size=3)),
    "layer_norm": dict(inputs=dict(x=_f((2, 4)),
                                   weight=_f((4,), seed=1),
                                   bias=_f((4,), seed=2)),
                       delta=1e-3, rtol=2e-2),
    "rms_norm": dict(inputs=dict(x=_f((2, 4)),
                                 weight=_f((4,), seed=1))),
    "instance_norm": dict(inputs=dict(x=_f((2, 3, 4, 4)))),
    "group_norm": dict(inputs=dict(x=_f((2, 4, 3, 3))),
                       attrs=dict(num_groups=2)),
    "local_response_norm": dict(inputs=dict(x=_f((1, 4, 5, 5))),
                                attrs=dict(size=3)),
    "batch_norm": dict(
        inputs=dict(x=_f((4, 3)),
                    running_mean=np.zeros(3, "float32"),
                    running_var=np.ones(3, "float32"),
                    weight=_f((3,), seed=1), bias=_f((3,), seed=2)),
        attrs=dict(training=False), grad="x"),
    "embedding": dict(inputs=dict(x=_i((2, 3), 6),
                                  weight=_f((6, 4), seed=1)),
                      grad="weight"),
    "dropout": dict(attrs=dict(p=0.0)),
    "alpha_dropout": dict(attrs=dict(p=0.0)),
    "dropout2d": dict(inputs=dict(x=_f((2, 3, 4, 4))),
                      attrs=dict(p=0.0)),
    "dropout3d": dict(inputs=dict(x=_f((2, 3, 2, 4, 4))),
                      attrs=dict(p=0.0)),
    "cross_entropy": dict(inputs=dict(input=_f((3, 5)),
                                      label=_i((3,), 5)),
                          grad="input"),
    "softmax_with_cross_entropy": dict(
        inputs=dict(logits=_f((3, 5)), label=_i((3, 1), 5)),
        grad="logits"),
    "binary_cross_entropy": dict(
        inputs=dict(input=_f((3, 4), 0.2, 0.8),
                    label=_f((3, 4), 0.0, 1.0, seed=1)),
        grad="input"),
    "binary_cross_entropy_with_logits": dict(
        inputs=dict(logit=_f((3, 4), -1, 1),
                    label=_f((3, 4), 0.0, 1.0, seed=1)),
        grad="logit"),
    "mse_loss": dict(inputs=dict(input=_f((3, 4)),
                                 label=_f((3, 4), seed=1))),
    "l1_loss": dict(inputs=dict(input=_f((3, 4)),
                                label=_f((3, 4), seed=1)),
                    grad=False),  # |x| kink
    "smooth_l1_loss": dict(inputs=dict(input=_f((3, 4)),
                                       label=_f((3, 4), seed=1)),
                           grad="input"),
    "nll_loss": dict(inputs=dict(log_prob=np.log(_f((3, 5), 0.1, 0.9)),
                                 label=_i((3,), 5)),
                     grad="log_prob"),
    "kl_div": dict(inputs=dict(input=np.log(_f((3, 4), 0.2, 0.8)),
                               label=_f((3, 4), 0.2, 0.8, seed=1)),
                   grad="input"),
    "hinge_embedding_loss": dict(
        inputs=dict(input=_f((3, 4), -1, 1),
                    label=np.sign(_f((3, 4), -1, 1, seed=1))),
        grad=False),
    "margin_ranking_loss": dict(
        inputs=dict(input=_f((3,)), other=_f((3,), seed=1),
                    label=np.array([1.0, -1.0, 1.0], "float32")),
        grad=False),  # hinge kink
    "cosine_embedding_loss": dict(
        inputs=dict(input1=_f((3, 4)), input2=_f((3, 4), seed=1),
                    label=np.array([1.0, -1.0, 1.0], "float32")),
        grad=False),
    "triplet_margin_loss": dict(
        inputs=dict(input=_f((3, 4)), positive=_f((3, 4), seed=1),
                    negative=_f((3, 4), seed=2)),
        grad=False),
    "log_loss": dict(inputs=dict(input=_f((3, 1), 0.2, 0.8),
                                 label=_f((3, 1), 0.0, 1.0, seed=1)),
                     grad="input"),
    "square_error_cost": dict(inputs=dict(input=_f((3, 4)),
                                          label=_f((3, 4), seed=1)),
                              grad="input"),
    "cosine_similarity": dict(inputs=dict(x1=_f((3, 4)),
                                          x2=_f((3, 4), seed=1))),
    "normalize": dict(),
    "label_smooth": dict(inputs=dict(label=_f((3, 5), 0.0, 1.0)),
                         grad=False),
    "pixel_shuffle": dict(inputs=dict(x=_f((1, 4, 3, 3))),
                          attrs=dict(upscale_factor=2)),
    "pixel_unshuffle": dict(inputs=dict(x=_f((1, 1, 6, 6))),
                            attrs=dict(downscale_factor=2)),
    "unfold": dict(inputs=dict(x=_f((1, 2, 5, 5))),
                   attrs=dict(kernel_sizes=2)),
    "affine_grid": dict(
        inputs=dict(theta=_f((1, 2, 3))),
        attrs=dict(out_shape=[1, 1, 4, 4])),
    "grid_sample": dict(
        inputs=dict(x=_f((1, 1, 4, 4)),
                    grid=_f((1, 3, 3, 2), -0.9, 0.9, seed=1)),
        grad="x"),
    "scaled_dot_product_attention": dict(
        inputs=dict(query=_f((1, 3, 2, 4)), key=_f((1, 3, 2, 4), seed=1),
                    value=_f((1, 3, 2, 4), seed=2)),
        grad="query"),
    "bilinear": dict(
        inputs=dict(x1=_f((3, 4)), x2=_f((3, 5), seed=1),
                    weight=_f((2, 4, 5), seed=2)),
        grad="x1"),
    "fused_linear": dict(inputs=dict(x=_f((2, 4)),
                                     weight=_f((4, 3), seed=1)),
                         grad="x"),
    # ---- random (fwd smoke only) ------------------------------------------
    "uniform": dict(inputs={}, attrs=dict(shape=[2, 3]), grad=False),
    "gaussian": dict(inputs={}, attrs=dict(shape=[2, 3]), grad=False),
    "randint": dict(inputs={}, attrs=dict(low=0, high=5, shape=[2, 3]),
                    grad=False),
    "bernoulli": dict(inputs=dict(x=_f((2, 3), 0.2, 0.8)), grad=False),
    # ---- reduction --------------------------------------------------------
    "max": dict(),
    "min": dict(),
    "median": dict(grad=False),     # piecewise selection; kink at ties
    "nanmedian": dict(grad=False),
    "quantile": dict(inputs=dict(x=_f((2, 6)),
                                 q=np.float32(0.5)), grad=False),
    "all": dict(inputs=dict(x=np.array([[True, False]] * 2)),
                grad=False),
    "any": dict(inputs=dict(x=np.array([[True, False]] * 2)),
                grad=False),
    "count_nonzero": dict(grad=False),
    "cummax": dict(out=0, grad=False),
    "cummin": dict(out=0, grad=False),
    "prod": dict(range=(0.5, 1.5)),
    # ---- r5 breadth additions ---------------------------------------------
    "gammaincc": dict(range=(0.5, 2.0)),
    # (increment is the in-place counter op in ops/api.py, not yaml)
    "fill": dict(grad=False),
    "fill_diagonal": dict(inputs=dict(x=_f((3, 3))),
                          attrs=dict(value=0.5)),
    "clip_by_norm": dict(attrs=dict(max_norm=10.0)),
    "renorm": dict(attrs=dict(max_norm=0.1)),
    "frobenius_norm": dict(inputs=dict(x=_f((3, 4)))),
    "is_empty": dict(grad=False),
    "reverse": dict(attrs=dict(axis=[0])),
    "as_strided": dict(attrs=dict(shape=[2, 2], stride=[1, 1])),
    "channel_shuffle": dict(inputs=dict(x=_f((1, 4, 2, 2))),
                            attrs=dict(groups=2)),
    "temporal_shift": dict(inputs=dict(x=_f((4, 4, 2, 2))),
                           attrs=dict(seg_num=2)),
    "huber_loss": dict(inputs=dict(input=_f((3, 4)),
                                   label=_f((3, 4), seed=1))),
    "hinge_loss": dict(
        inputs=dict(logits=_f((2, 3), -1, 1),
                    labels=(_f((2, 3), 0, 1, seed=1) > 0.5)
                    .astype("float32")),
        grad=False),
    "sequence_mask": dict(inputs=dict(lengths=_i((3,), 4) + 1),
                          attrs=dict(maxlen=5), grad=False),
    "max_unpool2d": dict(
        inputs=dict(x=_f((1, 1, 2, 2)),
                    indices=np.array([[[[0, 3], [8, 15]]]], "int64")),
        attrs=dict(kernel_size=2), grad="x"),
    "fold": dict(inputs=dict(x=_f((1, 4, 4))),
                 attrs=dict(output_sizes=[3, 3], kernel_sizes=2),
                 grad="x"),
    "spectral_norm": dict(inputs=dict(weight=_f((3, 4))), grad=False),
    "frame": dict(inputs=dict(x=_f((8,))),
                  attrs=dict(frame_length=4, hop_length=2), grad="x"),
    "overlap_add": dict(inputs=dict(x=_f((4, 3))),
                        attrs=dict(hop_length=2), grad="x"),
    "gather_tree": dict(
        inputs=dict(ids=_i((3, 2, 2), 4), parents=_i((3, 2, 2), 2)),
        grad=False),
    "edit_distance": dict(
        inputs=dict(hyps=_i((2, 4), 5), refs=_i((2, 5), 5, seed=1)),
        grad=False, out=0),
    "lu_unpack": dict(
        inputs=dict(x=_f((3, 3)),
                    y=np.array([1, 2, 3], "int32")),
        grad=False, out=1),
    "p_norm": dict(),
    "binomial": dict(
        inputs=dict(count=_i((2, 3), 5),
                    prob=_f((2, 3), 0.2, 0.8, seed=1)),
        grad=False),
    "exponential": dict(grad=False),
    "dirichlet": dict(inputs=dict(alpha=_f((4,), 0.5, 2.0)),
                      grad=False),
    "lp_pool2d": dict(inputs=dict(x=_f((1, 2, 6, 6))),
                      attrs=dict(kernel_size=2)),
    "fractional_max_pool2d": dict(inputs=dict(x=_f((1, 2, 8, 8))),
                                  attrs=dict(output_size=3),
                                  grad=False),  # max ties under u=0.5
    "max_unpool3d": dict(
        inputs=dict(x=_f((1, 1, 2, 2, 2)),
                    indices=np.arange(8).reshape(
                        1, 1, 2, 2, 2).astype("int64") * 7),
        attrs=dict(kernel_size=2), grad="x"),
    # ---- search (integral outputs) ----------------------------------------
    "argmax": dict(grad=False),
    "argmin": dict(grad=False),
    "argsort": dict(grad=False),
    "sort": dict(out=0, grad=False),
    "topk": dict(attrs=dict(k=2), out=0, grad=False),
    "kthvalue": dict(attrs=dict(k=2), out=0, grad=False),
    "mode": dict(out=0, grad=False),
    "searchsorted": dict(
        inputs=dict(sorted_sequence=np.sort(_f((6,))),
                    values=_f((3,), seed=1)),
        grad=False),
    "bucketize": dict(
        inputs=dict(x=_f((3,)),
                    sorted_sequence=np.sort(_f((5,), seed=1))),
        grad=False),
    # ---- brought by the Qwen3-Next block ------------------------------------
    "moe_router_logits": dict(inputs=dict(
        x=_f((4, 8), -1, 1), weight=_f((8, 6), -1, 1, seed=1))),
    "zero_centered_rms_norm": dict(inputs=dict(
        x=_f((2, 8), -1, 1), weight=_f((8,), -0.5, 0.5, seed=1))),
    "partial_rope_qk": dict(inputs=dict(
        q=_f((1, 4, 2, 8), -1, 1), k=_f((1, 4, 1, 8), -1, 1, seed=1)),
        attrs=dict(rotary_dim=4), out=0),
}


def _synth(op):
    """Build (callable, inputs, attrs, grad_wrt, out_index, rtol)."""
    entry = ENTRIES[op]
    hint = HINTS.get(op, {})
    params = parse_args(entry["args"])
    fn = getattr(F, op)

    if "inputs" in hint:
        inputs = {k: np.asarray(v) if not isinstance(v, list) else v
                  for k, v in hint["inputs"].items()}
    else:
        lo, hi = hint.get("range", (0.15, 0.85))
        shape = hint.get("shape", (2, 3))
        inputs = {}
        seed = 0
        for p in params:
            if not p["is_tensor"]:
                continue
            if p["type"].endswith("?") and p["default"] is None:
                continue  # optional tensor -> omit
            if p["type"].startswith("Tensor[]"):
                inputs[p["name"]] = [_f(shape, lo, hi, seed),
                                     _f(shape, lo, hi, seed + 1)]
                seed += 2
            else:
                inputs[p["name"]] = _f(shape, lo, hi, seed)
                seed += 1
    attrs = dict(hint.get("attrs", {}))
    grad = hint.get("grad", None)
    out = hint.get("out", None)
    rtol = hint.get("rtol", None)
    return fn, inputs, attrs, grad, out, rtol


def _numeric_grad(op_fn, inputs, wrt, delta=1e-2, output_index=None):
    """Central differences wrt inputs[wrt] (first element when it is a
    list input). Unlike op_test.numeric_gradient, non-wrt inputs keep
    their ORIGINAL dtypes (index tensors must stay integral) and the
    perturbed input stays float32 (ops need not support float64)."""

    def run(vals):
        out = op_fn(**_to_tensors(vals))
        if isinstance(out, (tuple, list)):
            out = out[output_index or 0]
        return float(out.sum().numpy())

    base = {k: ([np.asarray(e) for e in v] if isinstance(v, list)
                else np.asarray(v))
            for k, v in inputs.items()}
    target = base[wrt][0] if isinstance(base[wrt], list) else base[wrt]
    x = target.astype("float32")
    if isinstance(base[wrt], list):
        base[wrt][0] = x
    else:
        base[wrt] = x
    grad = np.zeros(x.shape, "float64")
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + delta
        plus = run(base)
        x[idx] = orig - delta
        minus = run(base)
        x[idx] = orig
        grad[idx] = (plus - minus) / (2 * delta)
        it.iternext()
    return grad


def _to_tensors(inputs, wrt=()):
    t = {}
    for k, v in inputs.items():
        if isinstance(v, list):
            # only element 0 is a grad target (matches _numeric_grad)
            t[k] = [paddle.to_tensor(
                        x, stop_gradient=(k not in wrt) or i > 0)
                    for i, x in enumerate(v)]
        else:
            t[k] = paddle.to_tensor(v, stop_gradient=k not in wrt)
    return t


SWEPT = sorted(set(ENTRIES) - set(SKIP))


@pytest.mark.parametrize("op", SWEPT)
def test_op_forward(op):
    """Forward runs and produces finite, well-formed outputs."""
    fn, inputs, attrs, grad, out, _ = _synth(op)
    result = fn(**_to_tensors(inputs), **attrs)
    leaves = result if isinstance(result, (tuple, list)) else [result]
    if out is not None:
        leaves = [leaves[out]]
    checked = 0
    for leaf in leaves:
        if leaf is None or not hasattr(leaf, "numpy"):
            continue
        a = np.asarray(leaf.numpy())
        if a.dtype.kind == "f":
            assert np.isfinite(a).all(), f"{op}: non-finite output"
        checked += 1
    assert checked, f"{op}: produced no tensor outputs"


GRAD_OPS = [
    op for op in SWEPT
    if HINTS.get(op, {}).get("grad", True) is not False
]


@pytest.mark.parametrize("op", GRAD_OPS)
def test_op_grad(op):
    """Analytic (tape) gradient matches numeric central differences on
    the first differentiable input — the reference's check_grad
    contract (test/legacy_test/op_test.py:148)."""
    fn, inputs, attrs, grad, out, rtol = _synth(op)
    if grad is None:
        wrt = [k for k, v in inputs.items()
               if np.asarray(v[0] if isinstance(v, list) else v
                             ).dtype.kind == "f"][:1]
    elif isinstance(grad, str):
        wrt = [grad]
    else:
        wrt = list(grad)
    assert wrt, f"{op}: no differentiable input (mark grad=False)"

    tensors = _to_tensors(inputs, wrt=wrt)
    result = fn(**tensors, **attrs)
    if isinstance(result, (tuple, list)):
        result = result[out or 0]
    result.sum().backward()

    k = wrt[0]
    holder = tensors[k][0] if isinstance(tensors[k], list) else tensors[k]
    analytic = holder.grad
    assert analytic is not None, f"{op}: no grad for {k}"

    def op_fn(**kw):
        return fn(**kw, **attrs)

    delta = HINTS.get(op, {}).get("delta", 1e-2)
    numeric = _numeric_grad(
        op_fn, inputs, k, delta=delta, output_index=out
    )
    np.testing.assert_allclose(
        np.asarray(analytic.numpy(), np.float64), numeric,
        rtol=rtol or GRAD_TOL["float32"], atol=rtol or GRAD_TOL["float32"],
        err_msg=f"{op}: wrong gradient wrt {k}",
    )


def test_frame_1d_axis0():
    """1-D frame with axis=0 must produce the (num_frames, frame_length)
    layout — the axis normalization regression: ``axis in (-1, ndim-1)``
    matched axis=0 when ndim == 1 and transposed the output."""
    x = np.arange(8, dtype="float32")
    out0 = F.frame(paddle.to_tensor(x), frame_length=4, hop_length=2,
                   axis=0).numpy()
    want = np.stack([x[0:4], x[2:6], x[4:8]])  # [num=3, fl=4]
    assert out0.shape == (3, 4)
    np.testing.assert_array_equal(out0, want)
    # axis=-1 on the same 1-D input keeps the reference's transposed
    # (frame_length, num_frames) layout
    out1 = F.frame(paddle.to_tensor(x), frame_length=4, hop_length=2,
                   axis=-1).numpy()
    np.testing.assert_array_equal(out1, want.T)
    # negative NON-last axes agree with their positive spelling (review
    # finding: `axis < 0` alone misclassified axis=-2 as the last axis)
    x3 = np.arange(60, dtype="float32").reshape(2, 10, 3)
    a_neg = F.frame(paddle.to_tensor(x3), frame_length=4, hop_length=2,
                    axis=-2).numpy()
    a_pos = F.frame(paddle.to_tensor(x3), frame_length=4, hop_length=2,
                    axis=1).numpy()
    np.testing.assert_array_equal(a_neg, a_pos)


def test_sweep_coverage():
    """Every yaml op is either swept or carries an explicit skip reason,
    and the sweep covers the >=300-op floor (VERDICT r4 item 6)."""
    assert set(SKIP) <= set(ENTRIES), "stale SKIP entries"
    assert len(SWEPT) >= 300, f"sweep covers only {len(SWEPT)} ops"
    assert len(SWEPT) + len(SKIP) == len(ENTRIES)
