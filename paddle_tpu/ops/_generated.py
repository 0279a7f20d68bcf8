"""AUTO-GENERATED by paddle_tpu/ops/gen.py from ops.yaml — do not edit.

This is artifact (1) of the single-YAML codegen pipeline: the eager op API.
Every function routes through core.dispatch.call which applies AMP casts,
the DistTensor branch, jax.vjp tape recording, and NaN/Inf checks.
"""
# fmt: off
from ..core import dispatch as _dispatch
from ..core.random import split_key as _split_key
from ..core.tensor import Tensor as _Tensor
from ..core.dtype import convert_dtype as _convert_dtype

from .impl import activation as _impl_activation
from .impl import creation as _impl_creation
from .impl import fft_ops as _impl_fft_ops
from .impl import fused_ops as _impl_fused_ops
from .impl import gdn_ops as _impl_gdn_ops
from .impl import linalg as _impl_linalg
from .impl import logic as _impl_logic
from .impl import manipulation as _impl_manipulation
from .impl import math as _impl_math
from .impl import moe_ops as _impl_moe_ops
from .impl import nn_ops as _impl_nn_ops
from .impl import random as _impl_random
from .impl import reduction as _impl_reduction
from .impl import rnn_ops as _impl_rnn_ops
from .impl import search as _impl_search
from .impl import ssm_ops as _impl_ssm_ops

_call = _dispatch.call


def _int_array(v):
    if v is None:
        return None
    if isinstance(v, _Tensor):
        return [int(i) for i in v.numpy().reshape(-1).tolist()]
    if isinstance(v, (int,)):
        return [int(v)]
    return [int(i) if not isinstance(i, _Tensor) else int(i.item()) for i in v]


def _scalar(v):
    if isinstance(v, _Tensor):
        return v.item()
    return v


def _dtype_attr(v):
    if v is None:
        return None
    return _convert_dtype(v).name


def _sections(v):
    """num_or_sections: plain int = section count (kept as int); list or
    Tensor = explicit section sizes (normalized to list[int])."""
    if isinstance(v, _Tensor):
        return [int(i) for i in v.numpy().reshape(-1).tolist()]
    if isinstance(v, (list, tuple)):
        return [
            int(i.item()) if isinstance(i, _Tensor) else int(i) for i in v
        ]
    return int(v)


def _inplace_rebind(x, out):
    from ..core import autograd as _autograd

    if (
        x.is_leaf
        and not x.stop_gradient
        and _autograd.is_grad_enabled()
    ):
        raise RuntimeError(
            "a leaf Tensor that requires grad is being used in an in-place operation"
        )
    x._data = out._data
    x._grad_node = out._grad_node
    x._out_index = out._out_index
    x.stop_gradient = out.stop_gradient
    x._dist_meta = out._dist_meta
    x._bump_version()
    return x



def abs(x, name=None):
    """abs (generated from ops.yaml)."""
    return _call('abs', _impl_math.abs, (x,), {})


def acos(x, name=None):
    """acos (generated from ops.yaml)."""
    return _call('acos', _impl_math.acos, (x,), {})


def acosh(x, name=None):
    """acosh (generated from ops.yaml)."""
    return _call('acosh', _impl_math.acosh, (x,), {})


def asin(x, name=None):
    """asin (generated from ops.yaml)."""
    return _call('asin', _impl_math.asin, (x,), {})


def asinh(x, name=None):
    """asinh (generated from ops.yaml)."""
    return _call('asinh', _impl_math.asinh, (x,), {})


def atan(x, name=None):
    """atan (generated from ops.yaml)."""
    return _call('atan', _impl_math.atan, (x,), {})


def atanh(x, name=None):
    """atanh (generated from ops.yaml)."""
    return _call('atanh', _impl_math.atanh, (x,), {})


def ceil(x, name=None):
    """ceil (generated from ops.yaml)."""
    return _call('ceil', _impl_math.ceil, (x,), {})


def ceil_(x, name=None):
    """Inplace variant of `ceil` (rebinds the payload; jax.Arrays are immutable)."""
    _out = ceil(x)
    return _inplace_rebind(x, _out)



def cos(x, name=None):
    """cos (generated from ops.yaml)."""
    return _call('cos', _impl_math.cos, (x,), {})


def cosh(x, name=None):
    """cosh (generated from ops.yaml)."""
    return _call('cosh', _impl_math.cosh, (x,), {})


def digamma(x, name=None):
    """digamma (generated from ops.yaml)."""
    return _call('digamma', _impl_math.digamma, (x,), {})


def erf(x, name=None):
    """erf (generated from ops.yaml)."""
    return _call('erf', _impl_math.erf, (x,), {})


def erfinv(x, name=None):
    """erfinv (generated from ops.yaml)."""
    return _call('erfinv', _impl_math.erfinv, (x,), {})


def exp(x, name=None):
    """exp (generated from ops.yaml)."""
    return _call('exp', _impl_math.exp, (x,), {})


def exp_(x, name=None):
    """Inplace variant of `exp` (rebinds the payload; jax.Arrays are immutable)."""
    _out = exp(x)
    return _inplace_rebind(x, _out)



def expm1(x, name=None):
    """expm1 (generated from ops.yaml)."""
    return _call('expm1', _impl_math.expm1, (x,), {})


def floor(x, name=None):
    """floor (generated from ops.yaml)."""
    return _call('floor', _impl_math.floor, (x,), {})


def floor_(x, name=None):
    """Inplace variant of `floor` (rebinds the payload; jax.Arrays are immutable)."""
    _out = floor(x)
    return _inplace_rebind(x, _out)



def lgamma(x, name=None):
    """lgamma (generated from ops.yaml)."""
    return _call('lgamma', _impl_math.lgamma, (x,), {})


def log(x, name=None):
    """log (generated from ops.yaml)."""
    return _call('log', _impl_math.log, (x,), {})


def log10(x, name=None):
    """log10 (generated from ops.yaml)."""
    return _call('log10', _impl_math.log10, (x,), {})


def log1p(x, name=None):
    """log1p (generated from ops.yaml)."""
    return _call('log1p', _impl_math.log1p, (x,), {})


def log2(x, name=None):
    """log2 (generated from ops.yaml)."""
    return _call('log2', _impl_math.log2, (x,), {})


def logit(x, eps=None, name=None):
    """logit (generated from ops.yaml)."""
    return _call('logit', _impl_math.logit, (x,), {'eps': eps})


def neg(x, name=None):
    """neg (generated from ops.yaml)."""
    return _call('neg', _impl_math.neg, (x,), {})


def reciprocal(x, name=None):
    """reciprocal (generated from ops.yaml)."""
    return _call('reciprocal', _impl_math.reciprocal, (x,), {})


def reciprocal_(x, name=None):
    """Inplace variant of `reciprocal` (rebinds the payload; jax.Arrays are immutable)."""
    _out = reciprocal(x)
    return _inplace_rebind(x, _out)



def round(x, decimals=0, name=None):
    """round (generated from ops.yaml)."""
    return _call('round', _impl_math.round, (x,), {'decimals': decimals})


def round_(x, decimals=0, name=None):
    """Inplace variant of `round` (rebinds the payload; jax.Arrays are immutable)."""
    _out = round(x, decimals)
    return _inplace_rebind(x, _out)



def rsqrt(x, name=None):
    """rsqrt (generated from ops.yaml)."""
    return _call('rsqrt', _impl_math.rsqrt, (x,), {})


def rsqrt_(x, name=None):
    """Inplace variant of `rsqrt` (rebinds the payload; jax.Arrays are immutable)."""
    _out = rsqrt(x)
    return _inplace_rebind(x, _out)



def sign(x, name=None):
    """sign (generated from ops.yaml)."""
    return _call('sign', _impl_math.sign, (x,), {})


def sin(x, name=None):
    """sin (generated from ops.yaml)."""
    return _call('sin', _impl_math.sin, (x,), {})


def sinc(x, name=None):
    """sinc (generated from ops.yaml)."""
    return _call('sinc', _impl_math.sinc, (x,), {})


def sinh(x, name=None):
    """sinh (generated from ops.yaml)."""
    return _call('sinh', _impl_math.sinh, (x,), {})


def sqrt(x, name=None):
    """sqrt (generated from ops.yaml)."""
    return _call('sqrt', _impl_math.sqrt, (x,), {})


def sqrt_(x, name=None):
    """Inplace variant of `sqrt` (rebinds the payload; jax.Arrays are immutable)."""
    _out = sqrt(x)
    return _inplace_rebind(x, _out)



def square(x, name=None):
    """square (generated from ops.yaml)."""
    return _call('square', _impl_math.square, (x,), {})


def tan(x, name=None):
    """tan (generated from ops.yaml)."""
    return _call('tan', _impl_math.tan, (x,), {})


def tanh(x, name=None):
    """tanh (generated from ops.yaml)."""
    return _call('tanh', _impl_math.tanh, (x,), {})


def tanh_(x, name=None):
    """Inplace variant of `tanh` (rebinds the payload; jax.Arrays are immutable)."""
    _out = tanh(x)
    return _inplace_rebind(x, _out)



def sigmoid(x, name=None):
    """sigmoid (generated from ops.yaml)."""
    return _call('sigmoid', _impl_math.sigmoid, (x,), {})


def sigmoid_(x, name=None):
    """Inplace variant of `sigmoid` (rebinds the payload; jax.Arrays are immutable)."""
    _out = sigmoid(x)
    return _inplace_rebind(x, _out)



def trunc(x, name=None):
    """trunc (generated from ops.yaml)."""
    return _call('trunc', _impl_math.trunc, (x,), {})


def frac(x, name=None):
    """frac (generated from ops.yaml)."""
    return _call('frac', _impl_math.frac, (x,), {})


def angle(x, name=None):
    """angle (generated from ops.yaml)."""
    return _call('angle', _impl_math.angle, (x,), {})


def conj(x, name=None):
    """conj (generated from ops.yaml)."""
    return _call('conj', _impl_math.conj, (x,), {})


def real(x, name=None):
    """real (generated from ops.yaml)."""
    return _call('real', _impl_math.real, (x,), {})


def imag(x, name=None):
    """imag (generated from ops.yaml)."""
    return _call('imag', _impl_math.imag, (x,), {})


def i0(x, name=None):
    """i0 (generated from ops.yaml)."""
    return _call('i0', _impl_math.i0, (x,), {})


def i0e(x, name=None):
    """i0e (generated from ops.yaml)."""
    return _call('i0e', _impl_math.i0e, (x,), {})


def i1(x, name=None):
    """i1 (generated from ops.yaml)."""
    return _call('i1', _impl_math.i1, (x,), {})


def i1e(x, name=None):
    """i1e (generated from ops.yaml)."""
    return _call('i1e', _impl_math.i1e, (x,), {})


def polygamma(x, n=1, name=None):
    """polygamma (generated from ops.yaml)."""
    return _call('polygamma', _impl_math.polygamma, (x,), {'n': n})


def deg2rad(x, name=None):
    """deg2rad (generated from ops.yaml)."""
    return _call('deg2rad', _impl_math.deg2rad, (x,), {})


def rad2deg(x, name=None):
    """rad2deg (generated from ops.yaml)."""
    return _call('rad2deg', _impl_math.rad2deg, (x,), {})


def nan_to_num(x, nan=0.0, posinf=None, neginf=None, name=None):
    """nan_to_num (generated from ops.yaml)."""
    return _call('nan_to_num', _impl_math.nan_to_num, (x,), {'nan': nan, 'posinf': posinf, 'neginf': neginf})


def signbit(x, name=None):
    """signbit (generated from ops.yaml)."""
    return _call('signbit', _impl_math.signbit, (x,), {})


def add(x, y, name=None):
    """add (generated from ops.yaml)."""
    return _call('add', _impl_math.add, (x, y,), {})


def add_(x, y, name=None):
    """Inplace variant of `add` (rebinds the payload; jax.Arrays are immutable)."""
    _out = add(x, y)
    return _inplace_rebind(x, _out)



def subtract(x, y, name=None):
    """subtract (generated from ops.yaml)."""
    return _call('subtract', _impl_math.subtract, (x, y,), {})


def subtract_(x, y, name=None):
    """Inplace variant of `subtract` (rebinds the payload; jax.Arrays are immutable)."""
    _out = subtract(x, y)
    return _inplace_rebind(x, _out)



def multiply(x, y, name=None):
    """multiply (generated from ops.yaml)."""
    return _call('multiply', _impl_math.multiply, (x, y,), {})


def multiply_(x, y, name=None):
    """Inplace variant of `multiply` (rebinds the payload; jax.Arrays are immutable)."""
    _out = multiply(x, y)
    return _inplace_rebind(x, _out)



def divide(x, y, name=None):
    """divide (generated from ops.yaml)."""
    return _call('divide', _impl_math.divide, (x, y,), {})


def divide_(x, y, name=None):
    """Inplace variant of `divide` (rebinds the payload; jax.Arrays are immutable)."""
    _out = divide(x, y)
    return _inplace_rebind(x, _out)



def floor_divide(x, y, name=None):
    """floor_divide (generated from ops.yaml)."""
    return _call('floor_divide', _impl_math.floor_divide, (x, y,), {})


def remainder(x, y, name=None):
    """remainder (generated from ops.yaml)."""
    return _call('remainder', _impl_math.remainder, (x, y,), {})


def fmod(x, y, name=None):
    """fmod (generated from ops.yaml)."""
    return _call('fmod', _impl_math.fmod, (x, y,), {})


def pow(x, y, name=None):
    """pow (generated from ops.yaml)."""
    return _call('pow', _impl_math.pow, (x, y,), {})


def maximum(x, y, name=None):
    """maximum (generated from ops.yaml)."""
    return _call('maximum', _impl_math.maximum, (x, y,), {})


def minimum(x, y, name=None):
    """minimum (generated from ops.yaml)."""
    return _call('minimum', _impl_math.minimum, (x, y,), {})


def fmax(x, y, name=None):
    """fmax (generated from ops.yaml)."""
    return _call('fmax', _impl_math.fmax, (x, y,), {})


def fmin(x, y, name=None):
    """fmin (generated from ops.yaml)."""
    return _call('fmin', _impl_math.fmin, (x, y,), {})


def atan2(x, y, name=None):
    """atan2 (generated from ops.yaml)."""
    return _call('atan2', _impl_math.atan2, (x, y,), {})


def logaddexp(x, y, name=None):
    """logaddexp (generated from ops.yaml)."""
    return _call('logaddexp', _impl_math.logaddexp, (x, y,), {})


def logaddexp2(x, y, name=None):
    """logaddexp2 (generated from ops.yaml)."""
    return _call('logaddexp2', _impl_math.logaddexp2, (x, y,), {})


def heaviside(x, y, name=None):
    """heaviside (generated from ops.yaml)."""
    return _call('heaviside', _impl_math.heaviside, (x, y,), {})


def nextafter(x, y, name=None):
    """nextafter (generated from ops.yaml)."""
    return _call('nextafter', _impl_math.nextafter, (x, y,), {})


def copysign(x, y, name=None):
    """copysign (generated from ops.yaml)."""
    return _call('copysign', _impl_math.copysign, (x, y,), {})


def hypot(x, y, name=None):
    """hypot (generated from ops.yaml)."""
    return _call('hypot', _impl_math.hypot, (x, y,), {})


def gcd(x, y, name=None):
    """gcd (generated from ops.yaml)."""
    return _call('gcd', _impl_math.gcd, (x, y,), {})


def lcm(x, y, name=None):
    """lcm (generated from ops.yaml)."""
    return _call('lcm', _impl_math.lcm, (x, y,), {})


def ldexp(x, y, name=None):
    """ldexp (generated from ops.yaml)."""
    return _call('ldexp', _impl_math.ldexp, (x, y,), {})


def inner(x, y, name=None):
    """inner (generated from ops.yaml)."""
    return _call('inner', _impl_math.inner, (x, y,), {})


def outer(x, y, name=None):
    """outer (generated from ops.yaml)."""
    return _call('outer', _impl_math.outer, (x, y,), {})


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, name=None):
    """scale (generated from ops.yaml)."""
    scale = _scalar(scale)
    return _call('scale', _impl_math.scale, (x,), {'scale': scale, 'bias': bias, 'bias_after_scale': bias_after_scale})


def scale_(x, scale=1.0, bias=0.0, bias_after_scale=True, name=None):
    """Inplace variant of `scale` (rebinds the payload; jax.Arrays are immutable)."""
    _out = scale(x, scale, bias, bias_after_scale)
    return _inplace_rebind(x, _out)



def clip(x, min=None, max=None, name=None):
    """clip (generated from ops.yaml)."""
    min = _scalar(min)
    max = _scalar(max)
    return _call('clip', _impl_math.clip, (x,), {'min': min, 'max': max})


def clip_(x, min=None, max=None, name=None):
    """Inplace variant of `clip` (rebinds the payload; jax.Arrays are immutable)."""
    _out = clip(x, min, max)
    return _inplace_rebind(x, _out)



def lerp(x, y, weight, name=None):
    """lerp (generated from ops.yaml)."""
    return _call('lerp', _impl_math.lerp, (x, y, weight,), {})


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    """stanh (generated from ops.yaml)."""
    return _call('stanh', _impl_math.stanh, (x,), {'scale_a': scale_a, 'scale_b': scale_b})


def addmm(input, x, y, beta=1.0, alpha=1.0, name=None):
    """addmm (generated from ops.yaml)."""
    return _call('addmm', _impl_math.addmm, (input, x, y,), {'beta': beta, 'alpha': alpha})


def multiplex(inputs, index, name=None):
    """multiplex (generated from ops.yaml)."""
    return _call('multiplex', _impl_math.multiplex, (inputs, index,), {})


def trapezoid(y, x=None, dx=None, axis=-1, name=None):
    """trapezoid (generated from ops.yaml)."""
    return _call('trapezoid', _impl_math.trapezoid, (y, x,), {'dx': dx, 'axis': axis})


def diff(x, n=1, axis=-1, name=None):
    """diff (generated from ops.yaml)."""
    return _call('diff', _impl_math.diff, (x,), {'n': n, 'axis': axis})


def sum(x, axis=None, dtype=None, keepdim=False, name=None):
    """sum (generated from ops.yaml)."""
    axis = _int_array(axis)
    dtype = _dtype_attr(dtype)
    return _call('sum', _impl_reduction.sum, (x,), {'axis': axis, 'dtype': dtype, 'keepdim': keepdim})


def mean(x, axis=None, keepdim=False, name=None):
    """mean (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('mean', _impl_reduction.mean, (x,), {'axis': axis, 'keepdim': keepdim})


def max(x, axis=None, keepdim=False, name=None):
    """max (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('max', _impl_reduction.max, (x,), {'axis': axis, 'keepdim': keepdim})


def min(x, axis=None, keepdim=False, name=None):
    """min (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('min', _impl_reduction.min, (x,), {'axis': axis, 'keepdim': keepdim})


def amax(x, axis=None, keepdim=False, name=None):
    """amax (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('amax', _impl_reduction.amax, (x,), {'axis': axis, 'keepdim': keepdim})


def amin(x, axis=None, keepdim=False, name=None):
    """amin (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('amin', _impl_reduction.amin, (x,), {'axis': axis, 'keepdim': keepdim})


def prod(x, axis=None, keepdim=False, dtype=None, name=None):
    """prod (generated from ops.yaml)."""
    axis = _int_array(axis)
    dtype = _dtype_attr(dtype)
    return _call('prod', _impl_reduction.prod, (x,), {'axis': axis, 'keepdim': keepdim, 'dtype': dtype})


def all(x, axis=None, keepdim=False, name=None):
    """all (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('all', _impl_reduction.all, (x,), {'axis': axis, 'keepdim': keepdim})


def any(x, axis=None, keepdim=False, name=None):
    """any (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('any', _impl_reduction.any, (x,), {'axis': axis, 'keepdim': keepdim})


def logsumexp(x, axis=None, keepdim=False, name=None):
    """logsumexp (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('logsumexp', _impl_reduction.logsumexp, (x,), {'axis': axis, 'keepdim': keepdim})


def nansum(x, axis=None, dtype=None, keepdim=False, name=None):
    """nansum (generated from ops.yaml)."""
    axis = _int_array(axis)
    dtype = _dtype_attr(dtype)
    return _call('nansum', _impl_reduction.nansum, (x,), {'axis': axis, 'dtype': dtype, 'keepdim': keepdim})


def nanmean(x, axis=None, keepdim=False, name=None):
    """nanmean (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('nanmean', _impl_reduction.nanmean, (x,), {'axis': axis, 'keepdim': keepdim})


def count_nonzero(x, axis=None, keepdim=False, name=None):
    """count_nonzero (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('count_nonzero', _impl_reduction.count_nonzero, (x,), {'axis': axis, 'keepdim': keepdim})


def std(x, axis=None, unbiased=True, keepdim=False, name=None):
    """std (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('std', _impl_reduction.std, (x,), {'axis': axis, 'unbiased': unbiased, 'keepdim': keepdim})


def var(x, axis=None, unbiased=True, keepdim=False, name=None):
    """var (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('var', _impl_reduction.var, (x,), {'axis': axis, 'unbiased': unbiased, 'keepdim': keepdim})


def median(x, axis=None, keepdim=False, name=None):
    """median (generated from ops.yaml)."""
    return _call('median', _impl_reduction.median, (x,), {'axis': axis, 'keepdim': keepdim})


def nanmedian(x, axis=None, keepdim=False, name=None):
    """nanmedian (generated from ops.yaml)."""
    return _call('nanmedian', _impl_reduction.nanmedian, (x,), {'axis': axis, 'keepdim': keepdim})


def quantile(x, q, axis=None, keepdim=False, interpolation="linear", name=None):
    """quantile (generated from ops.yaml)."""
    return _call('quantile', _impl_reduction.quantile, (x, q,), {'axis': axis, 'keepdim': keepdim, 'interpolation': interpolation})


def cumsum(x, axis=None, dtype=None, name=None):
    """cumsum (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('cumsum', _impl_reduction.cumsum, (x,), {'axis': axis, 'dtype': dtype})


def cumprod(x, dim=None, dtype=None, name=None):
    """cumprod (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('cumprod', _impl_reduction.cumprod, (x,), {'dim': dim, 'dtype': dtype})


def cummax(x, axis=None, dtype="int64", name=None):
    """cummax (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('cummax', _impl_reduction.cummax, (x,), {'axis': axis, 'dtype': dtype})


def cummin(x, axis=None, dtype="int64", name=None):
    """cummin (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('cummin', _impl_reduction.cummin, (x,), {'axis': axis, 'dtype': dtype})


def logcumsumexp(x, axis=None, name=None):
    """logcumsumexp (generated from ops.yaml)."""
    return _call('logcumsumexp', _impl_reduction.logcumsumexp, (x,), {'axis': axis})


def equal(x, y, name=None):
    """equal (generated from ops.yaml)."""
    return _call('equal', _impl_logic.equal, (x, y,), {})


def not_equal(x, y, name=None):
    """not_equal (generated from ops.yaml)."""
    return _call('not_equal', _impl_logic.not_equal, (x, y,), {})


def greater_than(x, y, name=None):
    """greater_than (generated from ops.yaml)."""
    return _call('greater_than', _impl_logic.greater_than, (x, y,), {})


def greater_equal(x, y, name=None):
    """greater_equal (generated from ops.yaml)."""
    return _call('greater_equal', _impl_logic.greater_equal, (x, y,), {})


def less_than(x, y, name=None):
    """less_than (generated from ops.yaml)."""
    return _call('less_than', _impl_logic.less_than, (x, y,), {})


def less_equal(x, y, name=None):
    """less_equal (generated from ops.yaml)."""
    return _call('less_equal', _impl_logic.less_equal, (x, y,), {})


def logical_and(x, y, name=None):
    """logical_and (generated from ops.yaml)."""
    return _call('logical_and', _impl_logic.logical_and, (x, y,), {})


def logical_or(x, y, name=None):
    """logical_or (generated from ops.yaml)."""
    return _call('logical_or', _impl_logic.logical_or, (x, y,), {})


def logical_xor(x, y, name=None):
    """logical_xor (generated from ops.yaml)."""
    return _call('logical_xor', _impl_logic.logical_xor, (x, y,), {})


def logical_not(x, name=None):
    """logical_not (generated from ops.yaml)."""
    return _call('logical_not', _impl_logic.logical_not, (x,), {})


def bitwise_and(x, y, name=None):
    """bitwise_and (generated from ops.yaml)."""
    return _call('bitwise_and', _impl_logic.bitwise_and, (x, y,), {})


def bitwise_or(x, y, name=None):
    """bitwise_or (generated from ops.yaml)."""
    return _call('bitwise_or', _impl_logic.bitwise_or, (x, y,), {})


def bitwise_xor(x, y, name=None):
    """bitwise_xor (generated from ops.yaml)."""
    return _call('bitwise_xor', _impl_logic.bitwise_xor, (x, y,), {})


def bitwise_not(x, name=None):
    """bitwise_not (generated from ops.yaml)."""
    return _call('bitwise_not', _impl_logic.bitwise_not, (x,), {})


def bitwise_left_shift(x, y, name=None):
    """bitwise_left_shift (generated from ops.yaml)."""
    return _call('bitwise_left_shift', _impl_logic.bitwise_left_shift, (x, y,), {})


def bitwise_right_shift(x, y, name=None):
    """bitwise_right_shift (generated from ops.yaml)."""
    return _call('bitwise_right_shift', _impl_logic.bitwise_right_shift, (x, y,), {})


def isnan(x, name=None):
    """isnan (generated from ops.yaml)."""
    return _call('isnan', _impl_logic.isnan, (x,), {})


def isinf(x, name=None):
    """isinf (generated from ops.yaml)."""
    return _call('isinf', _impl_logic.isinf, (x,), {})


def isfinite(x, name=None):
    """isfinite (generated from ops.yaml)."""
    return _call('isfinite', _impl_logic.isfinite, (x,), {})


def isneginf(x, name=None):
    """isneginf (generated from ops.yaml)."""
    return _call('isneginf', _impl_logic.isneginf, (x,), {})


def isposinf(x, name=None):
    """isposinf (generated from ops.yaml)."""
    return _call('isposinf', _impl_logic.isposinf, (x,), {})


def isreal(x, name=None):
    """isreal (generated from ops.yaml)."""
    return _call('isreal', _impl_logic.isreal, (x,), {})


def isclose(x, y, rtol=1e-05, atol=1e-08, equal_nan=False, name=None):
    """isclose (generated from ops.yaml)."""
    return _call('isclose', _impl_logic.isclose, (x, y,), {'rtol': rtol, 'atol': atol, 'equal_nan': equal_nan})


def allclose(x, y, rtol=1e-05, atol=1e-08, equal_nan=False, name=None):
    """allclose (generated from ops.yaml)."""
    return _call('allclose', _impl_logic.allclose, (x, y,), {'rtol': rtol, 'atol': atol, 'equal_nan': equal_nan})


def equal_all(x, y, name=None):
    """equal_all (generated from ops.yaml)."""
    return _call('equal_all', _impl_logic.equal_all, (x, y,), {})


def matmul(x, y, transpose_x=False, transpose_y=False, name=None):
    """matmul (generated from ops.yaml)."""
    return _call('matmul', _impl_linalg.matmul, (x, y,), {'transpose_x': transpose_x, 'transpose_y': transpose_y})


def bmm(x, y, name=None):
    """bmm (generated from ops.yaml)."""
    return _call('bmm', _impl_linalg.bmm, (x, y,), {})


def mv(x, vec, name=None):
    """mv (generated from ops.yaml)."""
    return _call('mv', _impl_linalg.mv, (x, vec,), {})


def dot(x, y, name=None):
    """dot (generated from ops.yaml)."""
    return _call('dot', _impl_linalg.dot, (x, y,), {})


def t(x, name=None):
    """t (generated from ops.yaml)."""
    return _call('t', _impl_linalg.t, (x,), {})


def cross(x, y, axis=None, name=None):
    """cross (generated from ops.yaml)."""
    return _call('cross', _impl_linalg.cross, (x, y,), {'axis': axis})


def kron(x, y, name=None):
    """kron (generated from ops.yaml)."""
    return _call('kron', _impl_linalg.kron, (x, y,), {})


def trace(x, offset=0, axis1=0, axis2=1, name=None):
    """trace (generated from ops.yaml)."""
    return _call('trace', _impl_linalg.trace, (x,), {'offset': offset, 'axis1': axis1, 'axis2': axis2})


def dist(x, y, p=2.0, name=None):
    """dist (generated from ops.yaml)."""
    return _call('dist', _impl_linalg.dist, (x, y,), {'p': p})


def cholesky(x, upper=False, name=None):
    """cholesky (generated from ops.yaml)."""
    return _call('cholesky', _impl_linalg.cholesky, (x,), {'upper': upper})


def cholesky_solve(x, y, upper=False, name=None):
    """cholesky_solve (generated from ops.yaml)."""
    return _call('cholesky_solve', _impl_linalg.cholesky_solve, (x, y,), {'upper': upper})


def inverse(x, name=None):
    """inverse (generated from ops.yaml)."""
    return _call('inverse', _impl_linalg.inverse, (x,), {})


def pinv(x, rcond=1e-15, hermitian=False, name=None):
    """pinv (generated from ops.yaml)."""
    return _call('pinv', _impl_linalg.pinv, (x,), {'rcond': rcond, 'hermitian': hermitian})


def solve(x, y, name=None):
    """solve (generated from ops.yaml)."""
    return _call('solve', _impl_linalg.solve, (x, y,), {})


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False, name=None):
    """triangular_solve (generated from ops.yaml)."""
    return _call('triangular_solve', _impl_linalg.triangular_solve, (x, y,), {'upper': upper, 'transpose': transpose, 'unitriangular': unitriangular})


def lstsq(x, y, rcond=None, driver=None, name=None):
    """lstsq (generated from ops.yaml)."""
    return _call('lstsq', _impl_linalg.lstsq, (x, y,), {'rcond': rcond, 'driver': driver})


def svd(x, full_matrices=False, name=None):
    """svd (generated from ops.yaml)."""
    return _call('svd', _impl_linalg.svd, (x,), {'full_matrices': full_matrices})


def svdvals(x, name=None):
    """svdvals (generated from ops.yaml)."""
    return _call('svdvals', _impl_linalg.svdvals, (x,), {})


def qr(x, mode="reduced", name=None):
    """qr (generated from ops.yaml)."""
    return _call('qr', _impl_linalg.qr, (x,), {'mode': mode})


def eig(x, name=None):
    """eig (generated from ops.yaml)."""
    return _call('eig', _impl_linalg.eig, (x,), {})


def eigh(x, UPLO="L", name=None):
    """eigh (generated from ops.yaml)."""
    return _call('eigh', _impl_linalg.eigh, (x,), {'UPLO': UPLO})


def eigvals(x, name=None):
    """eigvals (generated from ops.yaml)."""
    return _call('eigvals', _impl_linalg.eigvals, (x,), {})


def eigvalsh(x, UPLO="L", name=None):
    """eigvalsh (generated from ops.yaml)."""
    return _call('eigvalsh', _impl_linalg.eigvalsh, (x,), {'UPLO': UPLO})


def matrix_power(x, n, name=None):
    """matrix_power (generated from ops.yaml)."""
    return _call('matrix_power', _impl_linalg.matrix_power, (x,), {'n': n})


def matrix_rank(x, tol=None, hermitian=False, name=None):
    """matrix_rank (generated from ops.yaml)."""
    return _call('matrix_rank', _impl_linalg.matrix_rank, (x,), {'tol': tol, 'hermitian': hermitian})


def det(x, name=None):
    """det (generated from ops.yaml)."""
    return _call('det', _impl_linalg.det, (x,), {})


def slogdet(x, name=None):
    """slogdet (generated from ops.yaml)."""
    return _call('slogdet', _impl_linalg.slogdet, (x,), {})


def lu(x, pivot=True, name=None):
    """lu (generated from ops.yaml)."""
    return _call('lu', _impl_linalg.lu, (x,), {'pivot': pivot})


def multi_dot(x, name=None):
    """multi_dot (generated from ops.yaml)."""
    return _call('multi_dot', _impl_linalg.multi_dot, (x,), {})


def norm(x, p=None, axis=None, keepdim=False, name=None):
    """norm (generated from ops.yaml)."""
    p = _scalar(p)
    axis = _int_array(axis)
    return _call('norm', _impl_linalg.norm, (x,), {'p': p, 'axis': axis, 'keepdim': keepdim})


def vector_norm(x, p=2.0, axis=None, keepdim=False, name=None):
    """vector_norm (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('vector_norm', _impl_linalg.vector_norm, (x,), {'p': p, 'axis': axis, 'keepdim': keepdim})


def matrix_norm(x, p="fro", axis=[-2, -1], keepdim=False, name=None):
    """matrix_norm (generated from ops.yaml)."""
    p = _scalar(p)
    axis = _int_array(axis)
    return _call('matrix_norm', _impl_linalg.matrix_norm, (x,), {'p': p, 'axis': axis, 'keepdim': keepdim})


def histogram(input, bins=100, min=0, max=0, weight=None, density=False, name=None):
    """histogram (generated from ops.yaml)."""
    return _call('histogram', _impl_linalg.histogram, (input, weight,), {'bins': bins, 'min': min, 'max': max, 'density': density})


def bincount(x, weights=None, minlength=0, name=None):
    """bincount (generated from ops.yaml)."""
    return _call('bincount', _impl_linalg.bincount, (x, weights,), {'minlength': minlength})


def corrcoef(x, rowvar=True, name=None):
    """corrcoef (generated from ops.yaml)."""
    return _call('corrcoef', _impl_linalg.corrcoef, (x,), {'rowvar': rowvar})


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None, name=None):
    """cov (generated from ops.yaml)."""
    return _call('cov', _impl_linalg.cov, (x, fweights, aweights,), {'rowvar': rowvar, 'ddof': ddof})


def cdist(x, y, p=2.0, name=None):
    """cdist (generated from ops.yaml)."""
    return _call('cdist', _impl_linalg.cdist, (x, y,), {'p': p})


def tensordot(x, y, axes=2, name=None):
    """tensordot (generated from ops.yaml)."""
    axes = _scalar(axes)
    return _call('tensordot', _impl_linalg.tensordot, (x, y,), {'axes': axes})


def householder_product(x, tau, name=None):
    """householder_product (generated from ops.yaml)."""
    return _call('householder_product', _impl_linalg.householder_product, (x, tau,), {})


def reshape(x, shape, name=None):
    """reshape (generated from ops.yaml)."""
    shape = _int_array(shape)
    return _call('reshape', _impl_manipulation.reshape, (x,), {'shape': shape})


def reshape_(x, shape, name=None):
    """Inplace variant of `reshape` (rebinds the payload; jax.Arrays are immutable)."""
    _out = reshape(x, shape)
    return _inplace_rebind(x, _out)



def flatten(x, start_axis=0, stop_axis=-1, name=None):
    """flatten (generated from ops.yaml)."""
    return _call('flatten', _impl_manipulation.flatten, (x,), {'start_axis': start_axis, 'stop_axis': stop_axis})


def squeeze(x, axis=None, name=None):
    """squeeze (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('squeeze', _impl_manipulation.squeeze, (x,), {'axis': axis})


def squeeze_(x, axis=None, name=None):
    """Inplace variant of `squeeze` (rebinds the payload; jax.Arrays are immutable)."""
    _out = squeeze(x, axis)
    return _inplace_rebind(x, _out)



def unsqueeze(x, axis, name=None):
    """unsqueeze (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('unsqueeze', _impl_manipulation.unsqueeze, (x,), {'axis': axis})


def unsqueeze_(x, axis, name=None):
    """Inplace variant of `unsqueeze` (rebinds the payload; jax.Arrays are immutable)."""
    _out = unsqueeze(x, axis)
    return _inplace_rebind(x, _out)



def transpose(x, perm, name=None):
    """transpose (generated from ops.yaml)."""
    return _call('transpose', _impl_manipulation.transpose, (x,), {'perm': perm})


def moveaxis(x, source, destination, name=None):
    """moveaxis (generated from ops.yaml)."""
    source = _int_array(source)
    destination = _int_array(destination)
    return _call('moveaxis', _impl_manipulation.moveaxis, (x,), {'source': source, 'destination': destination})


def swapaxes(x, axis0, axis1, name=None):
    """swapaxes (generated from ops.yaml)."""
    return _call('swapaxes', _impl_manipulation.swapaxes, (x,), {'axis0': axis0, 'axis1': axis1})


def concat(x, axis=0, name=None):
    """concat (generated from ops.yaml)."""
    axis = _scalar(axis)
    return _call('concat', _impl_manipulation.concat, (x,), {'axis': axis})


def stack(x, axis=0, name=None):
    """stack (generated from ops.yaml)."""
    return _call('stack', _impl_manipulation.stack, (x,), {'axis': axis})


def split(x, num_or_sections, axis=0, name=None):
    """split (generated from ops.yaml)."""
    num_or_sections = _sections(num_or_sections)
    axis = _scalar(axis)
    return _call('split', _impl_manipulation.split, (x,), {'num_or_sections': num_or_sections, 'axis': axis})


def chunk(x, chunks, axis=0, name=None):
    """chunk (generated from ops.yaml)."""
    axis = _scalar(axis)
    return _call('chunk', _impl_manipulation.chunk, (x,), {'chunks': chunks, 'axis': axis})


def tensor_split(x, num_or_indices, axis=0, name=None):
    """tensor_split (generated from ops.yaml)."""
    num_or_indices = _int_array(num_or_indices)
    axis = _scalar(axis)
    return _call('tensor_split', _impl_manipulation.tensor_split, (x,), {'num_or_indices': num_or_indices, 'axis': axis})


def unbind(input, axis=0, name=None):
    """unbind (generated from ops.yaml)."""
    return _call('unbind', _impl_manipulation.unbind, (input,), {'axis': axis})


def unstack(x, axis=0, num=0, name=None):
    """unstack (generated from ops.yaml)."""
    return _call('unstack', _impl_manipulation.unstack, (x,), {'axis': axis, 'num': num})


def tile(x, repeat_times, name=None):
    """tile (generated from ops.yaml)."""
    repeat_times = _int_array(repeat_times)
    return _call('tile', _impl_manipulation.tile, (x,), {'repeat_times': repeat_times})


def expand(x, shape, name=None):
    """expand (generated from ops.yaml)."""
    shape = _int_array(shape)
    return _call('expand', _impl_manipulation.expand, (x,), {'shape': shape})


def broadcast_to(x, shape, name=None):
    """broadcast_to (generated from ops.yaml)."""
    shape = _int_array(shape)
    return _call('broadcast_to', _impl_manipulation.broadcast_to, (x,), {'shape': shape})


def expand_as(x, y, name=None):
    """expand_as (generated from ops.yaml)."""
    return _call('expand_as', _impl_manipulation.expand_as, (x, y,), {})


def broadcast_tensors(input, name=None):
    """broadcast_tensors (generated from ops.yaml)."""
    return _call('broadcast_tensors', _impl_manipulation.broadcast_tensors, (input,), {})


def slice(input, axes, starts, ends, name=None):
    """slice (generated from ops.yaml)."""
    starts = _int_array(starts)
    ends = _int_array(ends)
    return _call('slice', _impl_manipulation.slice, (input,), {'axes': axes, 'starts': starts, 'ends': ends})


def strided_slice(x, axes, starts, ends, strides, name=None):
    """strided_slice (generated from ops.yaml)."""
    starts = _int_array(starts)
    ends = _int_array(ends)
    strides = _int_array(strides)
    return _call('strided_slice', _impl_manipulation.strided_slice, (x,), {'axes': axes, 'starts': starts, 'ends': ends, 'strides': strides})


def gather(x, index, axis=0, name=None):
    """gather (generated from ops.yaml)."""
    axis = _scalar(axis)
    return _call('gather', _impl_manipulation.gather, (x, index,), {'axis': axis})


def gather_nd(x, index, name=None):
    """gather_nd (generated from ops.yaml)."""
    return _call('gather_nd', _impl_manipulation.gather_nd, (x, index,), {})


def take(x, index, mode="raise", name=None):
    """take (generated from ops.yaml)."""
    return _call('take', _impl_manipulation.take, (x, index,), {'mode': mode})


def take_along_axis(arr, indices, axis, broadcast=True, name=None):
    """take_along_axis (generated from ops.yaml)."""
    return _call('take_along_axis', _impl_manipulation.take_along_axis, (arr, indices,), {'axis': axis, 'broadcast': broadcast})


def put_along_axis(arr, indices, values, axis, reduce="assign", include_self=True, broadcast=True, name=None):
    """put_along_axis (generated from ops.yaml)."""
    return _call('put_along_axis', _impl_manipulation.put_along_axis, (arr, indices, values,), {'axis': axis, 'reduce': reduce, 'include_self': include_self, 'broadcast': broadcast})


def scatter(x, index, updates, overwrite=True, name=None):
    """scatter (generated from ops.yaml)."""
    return _call('scatter', _impl_manipulation.scatter, (x, index, updates,), {'overwrite': overwrite})


def scatter_(x, index, updates, overwrite=True, name=None):
    """Inplace variant of `scatter` (rebinds the payload; jax.Arrays are immutable)."""
    _out = scatter(x, index, updates, overwrite)
    return _inplace_rebind(x, _out)



def scatter_nd_add(x, index, updates, name=None):
    """scatter_nd_add (generated from ops.yaml)."""
    return _call('scatter_nd_add', _impl_manipulation.scatter_nd_add, (x, index, updates,), {})


def scatter_nd(index, updates, shape, name=None):
    """scatter_nd (generated from ops.yaml)."""
    shape = _int_array(shape)
    return _call('scatter_nd', _impl_manipulation.scatter_nd, (index, updates,), {'shape': shape})


def slice_scatter(x, value, axes=(), starts=(), ends=(), strides=(), name=None):
    """slice_scatter (generated from ops.yaml)."""
    return _call('slice_scatter', _impl_manipulation.slice_scatter, (x, value,), {'axes': axes, 'starts': starts, 'ends': ends, 'strides': strides})


def index_select(x, index, axis=0, name=None):
    """index_select (generated from ops.yaml)."""
    return _call('index_select', _impl_manipulation.index_select, (x, index,), {'axis': axis})


def index_sample(x, index, name=None):
    """index_sample (generated from ops.yaml)."""
    return _call('index_sample', _impl_manipulation.index_sample, (x, index,), {})


def index_add(x, index, value, axis=0, name=None):
    """index_add (generated from ops.yaml)."""
    return _call('index_add', _impl_manipulation.index_add, (x, index, value,), {'axis': axis})


def index_put(x, indices, value, accumulate=False, name=None):
    """index_put (generated from ops.yaml)."""
    return _call('index_put', _impl_manipulation.index_put, (x, indices, value,), {'accumulate': accumulate})


def masked_select(x, mask, name=None):
    """masked_select (generated from ops.yaml)."""
    return _call('masked_select', _impl_manipulation.masked_select, (x, mask,), {})


def masked_fill(x, mask, value, name=None):
    """masked_fill (generated from ops.yaml)."""
    value = _scalar(value)
    return _call('masked_fill', _impl_manipulation.masked_fill, (x, mask,), {'value': value})


def masked_fill_(x, mask, value, name=None):
    """Inplace variant of `masked_fill` (rebinds the payload; jax.Arrays are immutable)."""
    _out = masked_fill(x, mask, value)
    return _inplace_rebind(x, _out)



def masked_scatter(x, mask, value, name=None):
    """masked_scatter (generated from ops.yaml)."""
    return _call('masked_scatter', _impl_manipulation.masked_scatter, (x, mask, value,), {})


def where(condition, x, y, name=None):
    """where (generated from ops.yaml)."""
    return _call('where', _impl_manipulation.where, (condition, x, y,), {})


def roll(x, shifts, axis=None, name=None):
    """roll (generated from ops.yaml)."""
    shifts = _int_array(shifts)
    axis = _int_array(axis)
    return _call('roll', _impl_manipulation.roll, (x,), {'shifts': shifts, 'axis': axis})


def flip(x, axis, name=None):
    """flip (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('flip', _impl_manipulation.flip, (x,), {'axis': axis})


def rot90(x, k=1, axes=[0, 1], name=None):
    """rot90 (generated from ops.yaml)."""
    return _call('rot90', _impl_manipulation.rot90, (x,), {'k': k, 'axes': axes})


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", pad_from_last_axis=True, name=None):
    """pad (generated from ops.yaml)."""
    pad = _int_array(pad)
    return _call('pad', _impl_manipulation.pad, (x,), {'pad': pad, 'mode': mode, 'value': value, 'data_format': data_format, 'pad_from_last_axis': pad_from_last_axis})


def repeat_interleave(x, repeats, axis=None, name=None):
    """repeat_interleave (generated from ops.yaml)."""
    repeats = _int_array(repeats)
    return _call('repeat_interleave', _impl_manipulation.repeat_interleave, (x,), {'repeats': repeats, 'axis': axis})


def cast(x, dtype, name=None):
    """cast (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('cast', _impl_manipulation.cast, (x,), {'dtype': dtype})


def cast_(x, dtype, name=None):
    """Inplace variant of `cast` (rebinds the payload; jax.Arrays are immutable)."""
    _out = cast(x, dtype)
    return _inplace_rebind(x, _out)



def assign(x, name=None):
    """assign (generated from ops.yaml)."""
    return _call('assign', _impl_manipulation.assign, (x,), {})


def numel(x, name=None):
    """numel (generated from ops.yaml)."""
    return _call('numel', _impl_manipulation.numel, (x,), {})


def diagonal(x, offset=0, axis1=0, axis2=1, name=None):
    """diagonal (generated from ops.yaml)."""
    return _call('diagonal', _impl_manipulation.diagonal, (x,), {'offset': offset, 'axis1': axis1, 'axis2': axis2})


def diag(x, offset=0, padding_value=0.0, name=None):
    """diag (generated from ops.yaml)."""
    return _call('diag', _impl_manipulation.diag, (x,), {'offset': offset, 'padding_value': padding_value})


def diagflat(x, offset=0, name=None):
    """diagflat (generated from ops.yaml)."""
    return _call('diagflat', _impl_manipulation.diagflat, (x,), {'offset': offset})


def diag_embed(input, offset=0, dim1=-2, dim2=-1, name=None):
    """diag_embed (generated from ops.yaml)."""
    return _call('diag_embed', _impl_manipulation.diag_embed, (input,), {'offset': offset, 'dim1': dim1, 'dim2': dim2})


def tril(x, diagonal=0, name=None):
    """tril (generated from ops.yaml)."""
    return _call('tril', _impl_manipulation.tril, (x,), {'diagonal': diagonal})


def triu(x, diagonal=0, name=None):
    """triu (generated from ops.yaml)."""
    return _call('triu', _impl_manipulation.triu, (x,), {'diagonal': diagonal})


def meshgrid(inputs, name=None):
    """meshgrid (generated from ops.yaml)."""
    return _call('meshgrid', _impl_manipulation.meshgrid, (inputs,), {})


def one_hot(x, num_classes, name=None):
    """one_hot (generated from ops.yaml)."""
    return _call('one_hot', _impl_manipulation.one_hot, (x,), {'num_classes': num_classes})


def unique(x, return_index=False, return_inverse=False, return_counts=False, axis=None, name=None):
    """unique (generated from ops.yaml)."""
    return _call('unique', _impl_manipulation.unique, (x,), {'return_index': return_index, 'return_inverse': return_inverse, 'return_counts': return_counts, 'axis': axis})


def unique_consecutive(x, return_inverse=False, return_counts=False, axis=None, name=None):
    """unique_consecutive (generated from ops.yaml)."""
    return _call('unique_consecutive', _impl_manipulation.unique_consecutive, (x,), {'return_inverse': return_inverse, 'return_counts': return_counts, 'axis': axis})


def nonzero(x, as_tuple=False, name=None):
    """nonzero (generated from ops.yaml)."""
    return _call('nonzero', _impl_manipulation.nonzero, (x,), {'as_tuple': as_tuple})


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1, name=None):
    """shard_index (generated from ops.yaml)."""
    return _call('shard_index', _impl_manipulation.shard_index, (input,), {'index_num': index_num, 'nshards': nshards, 'shard_id': shard_id, 'ignore_value': ignore_value})


def as_real(x, name=None):
    """as_real (generated from ops.yaml)."""
    return _call('as_real', _impl_manipulation.as_real, (x,), {})


def as_complex(x, name=None):
    """as_complex (generated from ops.yaml)."""
    return _call('as_complex', _impl_manipulation.as_complex, (x,), {})


def crop(x, shape, offsets, name=None):
    """crop (generated from ops.yaml)."""
    shape = _int_array(shape)
    offsets = _int_array(offsets)
    return _call('crop', _impl_manipulation.crop, (x,), {'shape': shape, 'offsets': offsets})


def argmax(x, axis=None, keepdim=False, dtype="int64", name=None):
    """argmax (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('argmax', _impl_search.argmax, (x,), {'axis': axis, 'keepdim': keepdim, 'dtype': dtype})


def argmin(x, axis=None, keepdim=False, dtype="int64", name=None):
    """argmin (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('argmin', _impl_search.argmin, (x,), {'axis': axis, 'keepdim': keepdim, 'dtype': dtype})


def argsort(x, axis=-1, descending=False, stable=False, name=None):
    """argsort (generated from ops.yaml)."""
    return _call('argsort', _impl_search.argsort, (x,), {'axis': axis, 'descending': descending, 'stable': stable})


def sort(x, axis=-1, descending=False, stable=False, name=None):
    """sort (generated from ops.yaml)."""
    return _call('sort', _impl_search.sort, (x,), {'axis': axis, 'descending': descending, 'stable': stable})


def topk(x, k, axis=-1, largest=True, sorted=True, name=None):
    """topk (generated from ops.yaml)."""
    return _call('topk', _impl_search.topk, (x,), {'k': k, 'axis': axis, 'largest': largest, 'sorted': sorted})


def kthvalue(x, k, axis=-1, keepdim=False, name=None):
    """kthvalue (generated from ops.yaml)."""
    return _call('kthvalue', _impl_search.kthvalue, (x,), {'k': k, 'axis': axis, 'keepdim': keepdim})


def mode(x, axis=-1, keepdim=False, name=None):
    """mode (generated from ops.yaml)."""
    return _call('mode', _impl_search.mode, (x,), {'axis': axis, 'keepdim': keepdim})


def searchsorted(sorted_sequence, values, out_int32=False, right=False, name=None):
    """searchsorted (generated from ops.yaml)."""
    return _call('searchsorted', _impl_search.searchsorted, (sorted_sequence, values,), {'out_int32': out_int32, 'right': right})


def bucketize(x, sorted_sequence, out_int32=False, right=False, name=None):
    """bucketize (generated from ops.yaml)."""
    return _call('bucketize', _impl_search.bucketize, (x, sorted_sequence,), {'out_int32': out_int32, 'right': right})


def zeros(shape, dtype=None, name=None):
    """zeros (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    return _call('zeros', _impl_creation.zeros, (), {'shape': shape, 'dtype': dtype})


def ones(shape, dtype=None, name=None):
    """ones (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    return _call('ones', _impl_creation.ones, (), {'shape': shape, 'dtype': dtype})


def full(shape, fill_value, dtype=None, name=None):
    """full (generated from ops.yaml)."""
    shape = _int_array(shape)
    fill_value = _scalar(fill_value)
    dtype = _dtype_attr(dtype)
    return _call('full', _impl_creation.full, (), {'shape': shape, 'fill_value': fill_value, 'dtype': dtype})


def empty(shape, dtype=None, name=None):
    """empty (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    return _call('empty', _impl_creation.empty, (), {'shape': shape, 'dtype': dtype})


def zeros_like(x, dtype=None, name=None):
    """zeros_like (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('zeros_like', _impl_creation.zeros_like, (x,), {'dtype': dtype})


def ones_like(x, dtype=None, name=None):
    """ones_like (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('ones_like', _impl_creation.ones_like, (x,), {'dtype': dtype})


def full_like(x, fill_value, dtype=None, name=None):
    """full_like (generated from ops.yaml)."""
    fill_value = _scalar(fill_value)
    dtype = _dtype_attr(dtype)
    return _call('full_like', _impl_creation.full_like, (x,), {'fill_value': fill_value, 'dtype': dtype})


def empty_like(x, dtype=None, name=None):
    """empty_like (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('empty_like', _impl_creation.empty_like, (x,), {'dtype': dtype})


def arange(start=0, end=None, step=1, dtype=None, name=None):
    """arange (generated from ops.yaml)."""
    start = _scalar(start)
    end = _scalar(end)
    step = _scalar(step)
    dtype = _dtype_attr(dtype)
    return _call('arange', _impl_creation.arange, (), {'start': start, 'end': end, 'step': step, 'dtype': dtype})


def linspace(start, stop, num, dtype=None, name=None):
    """linspace (generated from ops.yaml)."""
    start = _scalar(start)
    stop = _scalar(stop)
    dtype = _dtype_attr(dtype)
    return _call('linspace', _impl_creation.linspace, (), {'start': start, 'stop': stop, 'num': num, 'dtype': dtype})


def logspace(start, stop, num, base=10.0, dtype=None, name=None):
    """logspace (generated from ops.yaml)."""
    start = _scalar(start)
    stop = _scalar(stop)
    base = _scalar(base)
    dtype = _dtype_attr(dtype)
    return _call('logspace', _impl_creation.logspace, (), {'start': start, 'stop': stop, 'num': num, 'base': base, 'dtype': dtype})


def eye(num_rows, num_columns=None, dtype=None, name=None):
    """eye (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('eye', _impl_creation.eye, (), {'num_rows': num_rows, 'num_columns': num_columns, 'dtype': dtype})


def tril_indices(row, col=None, offset=0, dtype="int64", name=None):
    """tril_indices (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('tril_indices', _impl_creation.tril_indices, (), {'row': row, 'col': col, 'offset': offset, 'dtype': dtype})


def triu_indices(row, col=None, offset=0, dtype="int64", name=None):
    """triu_indices (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('triu_indices', _impl_creation.triu_indices, (), {'row': row, 'col': col, 'offset': offset, 'dtype': dtype})


def complex(real, imag, name=None):
    """complex (generated from ops.yaml)."""
    return _call('complex', _impl_creation.complex, (real, imag,), {})


def polar(abs, angle, name=None):
    """polar (generated from ops.yaml)."""
    return _call('polar', _impl_creation.polar, (abs, angle,), {})


def vander(x, n=None, increasing=False, name=None):
    """vander (generated from ops.yaml)."""
    return _call('vander', _impl_creation.vander, (x,), {'n': n, 'increasing': increasing})


def uniform(shape, dtype=None, min=-1.0, max=1.0, name=None):
    """uniform (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    min = _scalar(min)
    max = _scalar(max)
    _key = _split_key()
    return _call('uniform', _impl_random.uniform, (), {'shape': shape, 'dtype': dtype, 'min': min, 'max': max, 'key': _key})


def gaussian(shape, dtype=None, mean=0.0, std=1.0, name=None):
    """gaussian (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    _key = _split_key()
    return _call('gaussian', _impl_random.gaussian, (), {'shape': shape, 'dtype': dtype, 'mean': mean, 'std': std, 'key': _key})


def randint(low=0, high=None, shape=[1], dtype="int64", name=None):
    """randint (generated from ops.yaml)."""
    shape = _int_array(shape)
    dtype = _dtype_attr(dtype)
    _key = _split_key()
    return _call('randint', _impl_random.randint, (), {'low': low, 'high': high, 'shape': shape, 'dtype': dtype, 'key': _key})


def randperm(n, dtype="int64", name=None):
    """randperm (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    _key = _split_key()
    return _call('randperm', _impl_random.randperm, (), {'n': n, 'dtype': dtype, 'key': _key})


def bernoulli(x, name=None):
    """bernoulli (generated from ops.yaml)."""
    _key = _split_key()
    return _call('bernoulli', _impl_random.bernoulli, (x,), {'key': _key})


def multinomial(x, num_samples=1, replacement=False, name=None):
    """multinomial (generated from ops.yaml)."""
    _key = _split_key()
    return _call('multinomial', _impl_random.multinomial, (x,), {'num_samples': num_samples, 'replacement': replacement, 'key': _key})


def poisson(x, name=None):
    """poisson (generated from ops.yaml)."""
    _key = _split_key()
    return _call('poisson', _impl_random.poisson, (x,), {'key': _key})


def standard_gamma(x, name=None):
    """standard_gamma (generated from ops.yaml)."""
    _key = _split_key()
    return _call('standard_gamma', _impl_random.standard_gamma, (x,), {'key': _key})


def relu(x, name=None):
    """relu (generated from ops.yaml)."""
    return _call('relu', _impl_activation.relu, (x,), {})


def relu_(x, name=None):
    """Inplace variant of `relu` (rebinds the payload; jax.Arrays are immutable)."""
    _out = relu(x)
    return _inplace_rebind(x, _out)



def relu6(x, name=None):
    """relu6 (generated from ops.yaml)."""
    return _call('relu6', _impl_activation.relu6, (x,), {})


def leaky_relu(x, negative_slope=0.01, name=None):
    """leaky_relu (generated from ops.yaml)."""
    return _call('leaky_relu', _impl_activation.leaky_relu, (x,), {'negative_slope': negative_slope})


def prelu(x, weight, name=None):
    """prelu (generated from ops.yaml)."""
    return _call('prelu', _impl_activation.prelu, (x, weight,), {})


def elu(x, alpha=1.0, name=None):
    """elu (generated from ops.yaml)."""
    return _call('elu', _impl_activation.elu, (x,), {'alpha': alpha})


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    """selu (generated from ops.yaml)."""
    return _call('selu', _impl_activation.selu, (x,), {'scale': scale, 'alpha': alpha})


def celu(x, alpha=1.0, name=None):
    """celu (generated from ops.yaml)."""
    return _call('celu', _impl_activation.celu, (x,), {'alpha': alpha})


def gelu(x, approximate=False, name=None):
    """gelu (generated from ops.yaml)."""
    return _call('gelu', _impl_activation.gelu, (x,), {'approximate': approximate})


def silu(x, name=None):
    """silu (generated from ops.yaml)."""
    return _call('silu', _impl_activation.silu, (x,), {})


def swish(x, name=None):
    """swish (generated from ops.yaml)."""
    return _call('swish', _impl_activation.swish, (x,), {})


def mish(x, name=None):
    """mish (generated from ops.yaml)."""
    return _call('mish', _impl_activation.mish, (x,), {})


def softplus(x, beta=1.0, threshold=20.0, name=None):
    """softplus (generated from ops.yaml)."""
    return _call('softplus', _impl_activation.softplus, (x,), {'beta': beta, 'threshold': threshold})


def softsign(x, name=None):
    """softsign (generated from ops.yaml)."""
    return _call('softsign', _impl_activation.softsign, (x,), {})


def softshrink(x, threshold=0.5, name=None):
    """softshrink (generated from ops.yaml)."""
    return _call('softshrink', _impl_activation.softshrink, (x,), {'threshold': threshold})


def hardshrink(x, threshold=0.5, name=None):
    """hardshrink (generated from ops.yaml)."""
    return _call('hardshrink', _impl_activation.hardshrink, (x,), {'threshold': threshold})


def tanhshrink(x, name=None):
    """tanhshrink (generated from ops.yaml)."""
    return _call('tanhshrink', _impl_activation.tanhshrink, (x,), {})


def hardtanh(x, min=-1.0, max=1.0, name=None):
    """hardtanh (generated from ops.yaml)."""
    return _call('hardtanh', _impl_activation.hardtanh, (x,), {'min': min, 'max': max})


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    """hardsigmoid (generated from ops.yaml)."""
    return _call('hardsigmoid', _impl_activation.hardsigmoid, (x,), {'slope': slope, 'offset': offset})


def hardswish(x, name=None):
    """hardswish (generated from ops.yaml)."""
    return _call('hardswish', _impl_activation.hardswish, (x,), {})


def log_sigmoid(x, name=None):
    """log_sigmoid (generated from ops.yaml)."""
    return _call('log_sigmoid', _impl_activation.log_sigmoid, (x,), {})


def softmax(x, axis=-1, dtype=None, name=None):
    """softmax (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('softmax', _impl_activation.softmax, (x,), {'axis': axis, 'dtype': dtype})


def log_softmax(x, axis=-1, dtype=None, name=None):
    """log_softmax (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('log_softmax', _impl_activation.log_softmax, (x,), {'axis': axis, 'dtype': dtype})


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    """gumbel_softmax (generated from ops.yaml)."""
    _key = _split_key()
    return _call('gumbel_softmax', _impl_activation.gumbel_softmax, (x,), {'temperature': temperature, 'hard': hard, 'axis': axis, 'key': _key})


def glu(x, axis=-1, name=None):
    """glu (generated from ops.yaml)."""
    return _call('glu', _impl_activation.glu, (x,), {'axis': axis})


def maxout(x, groups, axis=1, name=None):
    """maxout (generated from ops.yaml)."""
    return _call('maxout', _impl_activation.maxout, (x,), {'groups': groups, 'axis': axis})


def thresholded_relu(x, threshold=1.0, value=0.0, name=None):
    """thresholded_relu (generated from ops.yaml)."""
    return _call('thresholded_relu', _impl_activation.thresholded_relu, (x,), {'threshold': threshold, 'value': value})


def rrelu(x, lower=0.125, upper=0.3333333, training=True, name=None):
    """rrelu (generated from ops.yaml)."""
    _key = _split_key()
    return _call('rrelu', _impl_activation.rrelu, (x,), {'lower': lower, 'upper': upper, 'training': training, 'key': _key})


def swiglu(x, y=None, name=None):
    """swiglu (generated from ops.yaml)."""
    return _call('swiglu', _impl_activation.swiglu, (x, y,), {})


def linear(x, weight, bias=None, name=None):
    """linear (generated from ops.yaml)."""
    return _call('linear', _impl_nn_ops.linear, (x, weight, bias,), {})


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    """conv1d (generated from ops.yaml)."""
    stride = _int_array(stride)
    dilation = _int_array(dilation)
    return _call('conv1d', _impl_nn_ops.conv1d, (x, weight, bias,), {'stride': stride, 'padding': padding, 'dilation': dilation, 'groups': groups, 'data_format': data_format})


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW", name=None):
    """conv2d (generated from ops.yaml)."""
    stride = _int_array(stride)
    dilation = _int_array(dilation)
    return _call('conv2d', _impl_nn_ops.conv2d, (x, weight, bias,), {'stride': stride, 'padding': padding, 'dilation': dilation, 'groups': groups, 'data_format': data_format})


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    """conv3d (generated from ops.yaml)."""
    stride = _int_array(stride)
    dilation = _int_array(dilation)
    return _call('conv3d', _impl_nn_ops.conv3d, (x, weight, bias,), {'stride': stride, 'padding': padding, 'dilation': dilation, 'groups': groups, 'data_format': data_format})


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, data_format="NCL", name=None):
    """conv1d_transpose (generated from ops.yaml)."""
    stride = _int_array(stride)
    output_padding = _int_array(output_padding)
    dilation = _int_array(dilation)
    return _call('conv1d_transpose', _impl_nn_ops.conv1d_transpose, (x, weight, bias,), {'stride': stride, 'padding': padding, 'output_padding': output_padding, 'groups': groups, 'dilation': dilation, 'data_format': data_format})


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, data_format="NCHW", name=None):
    """conv2d_transpose (generated from ops.yaml)."""
    stride = _int_array(stride)
    output_padding = _int_array(output_padding)
    dilation = _int_array(dilation)
    return _call('conv2d_transpose', _impl_nn_ops.conv2d_transpose, (x, weight, bias,), {'stride': stride, 'padding': padding, 'output_padding': output_padding, 'groups': groups, 'dilation': dilation, 'data_format': data_format})


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, groups=1, dilation=1, data_format="NCDHW", name=None):
    """conv3d_transpose (generated from ops.yaml)."""
    stride = _int_array(stride)
    output_padding = _int_array(output_padding)
    dilation = _int_array(dilation)
    return _call('conv3d_transpose', _impl_nn_ops.conv3d_transpose, (x, weight, bias,), {'stride': stride, 'padding': padding, 'output_padding': output_padding, 'groups': groups, 'dilation': dilation, 'data_format': data_format})


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCL", name=None):
    """max_pool1d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('max_pool1d', _impl_nn_ops.max_pool1d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'data_format': data_format})


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW", name=None):
    """max_pool2d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('max_pool2d', _impl_nn_ops.max_pool2d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'data_format': data_format})


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCDHW", name=None):
    """max_pool3d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('max_pool3d', _impl_nn_ops.max_pool3d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'data_format': data_format})


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, count_include_pad=True, data_format="NCL", name=None):
    """avg_pool1d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('avg_pool1d', _impl_nn_ops.avg_pool1d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'count_include_pad': count_include_pad, 'data_format': data_format})


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, count_include_pad=True, data_format="NCHW", name=None):
    """avg_pool2d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('avg_pool2d', _impl_nn_ops.avg_pool2d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'count_include_pad': count_include_pad, 'data_format': data_format})


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, count_include_pad=True, data_format="NCDHW", name=None):
    """avg_pool3d (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('avg_pool3d', _impl_nn_ops.avg_pool3d, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'count_include_pad': count_include_pad, 'data_format': data_format})


def adaptive_avg_pool1d(x, output_size, name=None):
    """adaptive_avg_pool1d (generated from ops.yaml)."""
    output_size = _int_array(output_size)
    return _call('adaptive_avg_pool1d', _impl_nn_ops.adaptive_avg_pool1d, (x,), {'output_size': output_size})


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    """adaptive_avg_pool2d (generated from ops.yaml)."""
    output_size = _int_array(output_size)
    return _call('adaptive_avg_pool2d', _impl_nn_ops.adaptive_avg_pool2d, (x,), {'output_size': output_size, 'data_format': data_format})


def adaptive_max_pool2d(x, output_size, data_format="NCHW", name=None):
    """adaptive_max_pool2d (generated from ops.yaml)."""
    output_size = _int_array(output_size)
    return _call('adaptive_max_pool2d', _impl_nn_ops.adaptive_max_pool2d, (x,), {'output_size': output_size, 'data_format': data_format})


def layer_norm(x, weight=None, bias=None, normalized_shape=None, epsilon=1e-5, name=None):
    """layer_norm (generated from ops.yaml)."""
    normalized_shape = _int_array(normalized_shape)
    return _call('layer_norm', _impl_nn_ops.layer_norm, (x, weight, bias,), {'normalized_shape': normalized_shape, 'epsilon': epsilon})


def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1, name=None):
    """rms_norm (generated from ops.yaml)."""
    return _call('rms_norm', _impl_nn_ops.rms_norm, (x, weight, bias,), {'epsilon': epsilon, 'begin_norm_axis': begin_norm_axis})


def instance_norm(x, weight=None, bias=None, epsilon=1e-5, data_format="NCHW", name=None):
    """instance_norm (generated from ops.yaml)."""
    return _call('instance_norm', _impl_nn_ops.instance_norm, (x, weight, bias,), {'epsilon': epsilon, 'data_format': data_format})


def group_norm(x, weight=None, bias=None, num_groups=1, epsilon=1e-5, data_format="NCHW", name=None):
    """group_norm (generated from ops.yaml)."""
    return _call('group_norm', _impl_nn_ops.group_norm, (x, weight, bias,), {'num_groups': num_groups, 'epsilon': epsilon, 'data_format': data_format})


def local_response_norm(x, size=5, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    """local_response_norm (generated from ops.yaml)."""
    return _call('local_response_norm', _impl_nn_ops.local_response_norm, (x,), {'size': size, 'alpha': alpha, 'beta': beta, 'k': k, 'data_format': data_format})


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """embedding (generated from ops.yaml)."""
    return _call('embedding', _impl_nn_ops.embedding, (x, weight,), {'padding_idx': padding_idx, 'sparse': sparse})


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None, name=None):
    """dropout (generated from ops.yaml)."""
    _key = _split_key()
    return _call('dropout', _impl_nn_ops.dropout, (x,), {'p': p, 'training': training, 'mode': mode, 'axis': axis, 'key': _key})


def alpha_dropout(x, p=0.5, training=True, name=None):
    """alpha_dropout (generated from ops.yaml)."""
    _key = _split_key()
    return _call('alpha_dropout', _impl_nn_ops.alpha_dropout, (x,), {'p': p, 'training': training, 'key': _key})


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean", soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0, name=None):
    """cross_entropy (generated from ops.yaml)."""
    return _call('cross_entropy', _impl_nn_ops.cross_entropy, (input, label, weight,), {'ignore_index': ignore_index, 'reduction': reduction, 'soft_label': soft_label, 'axis': axis, 'use_softmax': use_softmax, 'label_smoothing': label_smoothing})


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, axis=-1, return_softmax=False, name=None):
    """softmax_with_cross_entropy (generated from ops.yaml)."""
    return _call('softmax_with_cross_entropy', _impl_nn_ops.softmax_with_cross_entropy, (logits, label,), {'soft_label': soft_label, 'ignore_index': ignore_index, 'axis': axis, 'return_softmax': return_softmax})


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    """binary_cross_entropy (generated from ops.yaml)."""
    return _call('binary_cross_entropy', _impl_nn_ops.binary_cross_entropy, (input, label, weight,), {'reduction': reduction})


def binary_cross_entropy_with_logits(logit, label, weight=None, pos_weight=None, reduction="mean", name=None):
    """binary_cross_entropy_with_logits (generated from ops.yaml)."""
    return _call('binary_cross_entropy_with_logits', _impl_nn_ops.binary_cross_entropy_with_logits, (logit, label, weight, pos_weight,), {'reduction': reduction})


def mse_loss(input, label, reduction="mean", name=None):
    """mse_loss (generated from ops.yaml)."""
    return _call('mse_loss', _impl_nn_ops.mse_loss, (input, label,), {'reduction': reduction})


def l1_loss(input, label, reduction="mean", name=None):
    """l1_loss (generated from ops.yaml)."""
    return _call('l1_loss', _impl_nn_ops.l1_loss, (input, label,), {'reduction': reduction})


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    """smooth_l1_loss (generated from ops.yaml)."""
    return _call('smooth_l1_loss', _impl_nn_ops.smooth_l1_loss, (input, label,), {'reduction': reduction, 'delta': delta})


def nll_loss(log_prob, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    """nll_loss (generated from ops.yaml)."""
    return _call('nll_loss', _impl_nn_ops.nll_loss, (log_prob, label, weight,), {'ignore_index': ignore_index, 'reduction': reduction})


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    """kl_div (generated from ops.yaml)."""
    return _call('kl_div', _impl_nn_ops.kl_div, (input, label,), {'reduction': reduction, 'log_target': log_target})


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    """hinge_embedding_loss (generated from ops.yaml)."""
    return _call('hinge_embedding_loss', _impl_nn_ops.hinge_embedding_loss, (input, label,), {'margin': margin, 'reduction': reduction})


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    """margin_ranking_loss (generated from ops.yaml)."""
    return _call('margin_ranking_loss', _impl_nn_ops.margin_ranking_loss, (input, other, label,), {'margin': margin, 'reduction': reduction})


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    """cosine_embedding_loss (generated from ops.yaml)."""
    return _call('cosine_embedding_loss', _impl_nn_ops.cosine_embedding_loss, (input1, input2, label,), {'margin': margin, 'reduction': reduction})


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, reduction="mean", name=None):
    """triplet_margin_loss (generated from ops.yaml)."""
    return _call('triplet_margin_loss', _impl_nn_ops.triplet_margin_loss, (input, positive, negative,), {'margin': margin, 'p': p, 'reduction': reduction})


def log_loss(input, label, epsilon=1e-4, name=None):
    """log_loss (generated from ops.yaml)."""
    return _call('log_loss', _impl_nn_ops.log_loss, (input, label,), {'epsilon': epsilon})


def square_error_cost(input, label, name=None):
    """square_error_cost (generated from ops.yaml)."""
    return _call('square_error_cost', _impl_nn_ops.square_error_cost, (input, label,), {})


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    """cosine_similarity (generated from ops.yaml)."""
    return _call('cosine_similarity', _impl_nn_ops.cosine_similarity, (x1, x2,), {'axis': axis, 'eps': eps})


def normalize(x, p=2.0, axis=1, epsilon=1e-12, name=None):
    """normalize (generated from ops.yaml)."""
    return _call('normalize', _impl_nn_ops.normalize, (x,), {'p': p, 'axis': axis, 'epsilon': epsilon})


def label_smooth(label, epsilon=0.1, name=None):
    """label_smooth (generated from ops.yaml)."""
    return _call('label_smooth', _impl_nn_ops.label_smooth, (label,), {'epsilon': epsilon})


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    """pixel_shuffle (generated from ops.yaml)."""
    return _call('pixel_shuffle', _impl_nn_ops.pixel_shuffle, (x,), {'upscale_factor': upscale_factor, 'data_format': data_format})


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    """pixel_unshuffle (generated from ops.yaml)."""
    return _call('pixel_unshuffle', _impl_nn_ops.pixel_unshuffle, (x,), {'downscale_factor': downscale_factor, 'data_format': data_format})


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """unfold (generated from ops.yaml)."""
    kernel_sizes = _int_array(kernel_sizes)
    strides = _int_array(strides)
    dilations = _int_array(dilations)
    return _call('unfold', _impl_nn_ops.unfold, (x,), {'kernel_sizes': kernel_sizes, 'strides': strides, 'paddings': paddings, 'dilations': dilations})


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW", name=None):
    """interpolate (generated from ops.yaml)."""
    size = _int_array(size)
    return _call('interpolate', _impl_nn_ops.interpolate, (x,), {'size': size, 'scale_factor': scale_factor, 'mode': mode, 'align_corners': align_corners, 'data_format': data_format})


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """affine_grid (generated from ops.yaml)."""
    out_shape = _int_array(out_shape)
    return _call('affine_grid', _impl_nn_ops.affine_grid, (theta,), {'out_shape': out_shape, 'align_corners': align_corners})


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True, name=None):
    """grid_sample (generated from ops.yaml)."""
    return _call('grid_sample', _impl_nn_ops.grid_sample, (x, grid,), {'mode': mode, 'padding_mode': padding_mode, 'align_corners': align_corners})


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, scale=None, name=None):
    """scaled_dot_product_attention (generated from ops.yaml)."""
    _key = _split_key()
    return _call('scaled_dot_product_attention', _impl_nn_ops.scaled_dot_product_attention, (query, key, value, attn_mask,), {'dropout_p': dropout_p, 'is_causal': is_causal, 'scale': scale, 'key_rng': _key})


def batch_norm(x, running_mean, running_var, weight=None, bias=None, training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=False, name=None):
    """batch_norm (generated from ops.yaml)."""
    return _call('batch_norm', _impl_nn_ops.batch_norm, (x, running_mean, running_var, weight, bias,), {'training': training, 'momentum': momentum, 'epsilon': epsilon, 'data_format': data_format, 'use_global_stats': use_global_stats})


def batch_norm_with_stats(x, running_mean, running_var, weight=None, bias=None, momentum=0.9, epsilon=1e-5, data_format="NCHW", name=None):
    """batch_norm_with_stats (generated from ops.yaml)."""
    return _call('batch_norm_with_stats', _impl_nn_ops.batch_norm_train, (x, running_mean, running_var, weight, bias,), {'momentum': momentum, 'epsilon': epsilon, 'data_format': data_format})


def bilinear(x1, x2, weight, bias=None, name=None):
    """bilinear (generated from ops.yaml)."""
    return _call('bilinear', _impl_nn_ops.bilinear, (x1, x2, weight, bias,), {})


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    """dropout2d (generated from ops.yaml)."""
    _key = _split_key()
    return _call('dropout2d', _impl_nn_ops.dropout2d, (x,), {'p': p, 'training': training, 'data_format': data_format, 'key': _key})


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    """dropout3d (generated from ops.yaml)."""
    _key = _split_key()
    return _call('dropout3d', _impl_nn_ops.dropout3d, (x,), {'p': p, 'training': training, 'data_format': data_format, 'key': _key})


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW", name=None):
    """upsample (generated from ops.yaml)."""
    size = _int_array(size)
    return _call('upsample', _impl_nn_ops.upsample, (x,), {'size': size, 'scale_factor': scale_factor, 'mode': mode, 'align_corners': align_corners, 'data_format': data_format})


def rnn(x, initial_states, weight_list, mode="LSTM", num_layers=1, time_major=False, dropout=0.0, bidirectional=False, training=True, name=None):
    """rnn (generated from ops.yaml)."""
    _key = _split_key()
    return _call('rnn', _impl_rnn_ops.rnn, (x, initial_states, weight_list,), {'mode': mode, 'num_layers': num_layers, 'time_major': time_major, 'dropout': dropout, 'bidirectional': bidirectional, 'training': training, 'key': _key})


def fft(x, n=None, axis=-1, norm="backward", name=None):
    """fft (generated from ops.yaml)."""
    return _call('fft', _impl_fft_ops.fft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def ifft(x, n=None, axis=-1, norm="backward", name=None):
    """ifft (generated from ops.yaml)."""
    return _call('ifft', _impl_fft_ops.ifft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def rfft(x, n=None, axis=-1, norm="backward", name=None):
    """rfft (generated from ops.yaml)."""
    return _call('rfft', _impl_fft_ops.rfft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def irfft(x, n=None, axis=-1, norm="backward", name=None):
    """irfft (generated from ops.yaml)."""
    return _call('irfft', _impl_fft_ops.irfft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def hfft(x, n=None, axis=-1, norm="backward", name=None):
    """hfft (generated from ops.yaml)."""
    return _call('hfft', _impl_fft_ops.hfft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def ihfft(x, n=None, axis=-1, norm="backward", name=None):
    """ihfft (generated from ops.yaml)."""
    return _call('ihfft', _impl_fft_ops.ihfft, (x,), {'n': n, 'axis': axis, 'norm': norm})


def fft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """fft2 (generated from ops.yaml)."""
    s = _int_array(s)
    return _call('fft2', _impl_fft_ops.fft2, (x,), {'s': s, 'axes': axes, 'norm': norm})


def ifft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """ifft2 (generated from ops.yaml)."""
    s = _int_array(s)
    return _call('ifft2', _impl_fft_ops.ifft2, (x,), {'s': s, 'axes': axes, 'norm': norm})


def rfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """rfft2 (generated from ops.yaml)."""
    s = _int_array(s)
    return _call('rfft2', _impl_fft_ops.rfft2, (x,), {'s': s, 'axes': axes, 'norm': norm})


def irfft2(x, s=None, axes=(-2, -1), norm="backward", name=None):
    """irfft2 (generated from ops.yaml)."""
    s = _int_array(s)
    return _call('irfft2', _impl_fft_ops.irfft2, (x,), {'s': s, 'axes': axes, 'norm': norm})


def fftn(x, s=None, axes=None, norm="backward", name=None):
    """fftn (generated from ops.yaml)."""
    s = _int_array(s)
    axes = _int_array(axes)
    return _call('fftn', _impl_fft_ops.fftn, (x,), {'s': s, 'axes': axes, 'norm': norm})


def ifftn(x, s=None, axes=None, norm="backward", name=None):
    """ifftn (generated from ops.yaml)."""
    s = _int_array(s)
    axes = _int_array(axes)
    return _call('ifftn', _impl_fft_ops.ifftn, (x,), {'s': s, 'axes': axes, 'norm': norm})


def rfftn(x, s=None, axes=None, norm="backward", name=None):
    """rfftn (generated from ops.yaml)."""
    s = _int_array(s)
    axes = _int_array(axes)
    return _call('rfftn', _impl_fft_ops.rfftn, (x,), {'s': s, 'axes': axes, 'norm': norm})


def irfftn(x, s=None, axes=None, norm="backward", name=None):
    """irfftn (generated from ops.yaml)."""
    s = _int_array(s)
    axes = _int_array(axes)
    return _call('irfftn', _impl_fft_ops.irfftn, (x,), {'s': s, 'axes': axes, 'norm': norm})


def fftshift(x, axes=None, name=None):
    """fftshift (generated from ops.yaml)."""
    axes = _int_array(axes)
    return _call('fftshift', _impl_fft_ops.fftshift, (x,), {'axes': axes})


def ifftshift(x, axes=None, name=None):
    """ifftshift (generated from ops.yaml)."""
    axes = _int_array(axes)
    return _call('ifftshift', _impl_fft_ops.ifftshift, (x,), {'axes': axes})


def fftfreq(n, d=1.0, dtype=None, name=None):
    """fftfreq (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('fftfreq', _impl_fft_ops.fftfreq, (), {'n': n, 'd': d, 'dtype': dtype})


def rfftfreq(n, d=1.0, dtype=None, name=None):
    """rfftfreq (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('rfftfreq', _impl_fft_ops.rfftfreq, (), {'n': n, 'd': d, 'dtype': dtype})


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None, position_ids=None, use_neox_rotary_style=True, rotary_emb_base=10000.0, name=None):
    """fused_rotary_position_embedding (generated from ops.yaml)."""
    return _call('fused_rotary_position_embedding', _impl_fused_ops.fused_rotary_position_embedding, (q, k, v, sin, cos, position_ids,), {'use_neox_rotary_style': use_neox_rotary_style, 'rotary_emb_base': rotary_emb_base})


def rope_qk(q, k, position_ids=None, base=10000.0, use_neox_rotary_style=True, name=None):
    """rope_qk (generated from ops.yaml)."""
    return _call('rope_qk', _impl_fused_ops.rope_qk, (q, k, position_ids,), {'base': base, 'use_neox_rotary_style': use_neox_rotary_style})


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """fused_linear (generated from ops.yaml)."""
    return _call('fused_linear', _impl_fused_ops.fused_linear, (x, weight, bias,), {'transpose_weight': transpose_weight})


def moe_gate_dispatch(x, gate_logits, k=2, capacity=0, renormalize=True, name=None):
    """moe_gate_dispatch (generated from ops.yaml)."""
    return _call('moe_gate_dispatch', _impl_moe_ops.moe_gate_dispatch, (x, gate_logits,), {'k': k, 'capacity': capacity, 'renormalize': renormalize})


def moe_combine(expert_out, combine_weights, expert_ids, slots, name=None):
    """moe_combine (generated from ops.yaml)."""
    return _call('moe_combine', _impl_moe_ops.moe_combine, (expert_out, combine_weights, expert_ids, slots,), {})


def moe_ragged_dispatch(x, gate_logits, k=2, renormalize=True, name=None):
    """moe_ragged_dispatch (generated from ops.yaml)."""
    return _call('moe_ragged_dispatch', _impl_moe_ops.moe_ragged_dispatch, (x, gate_logits,), {'k': k, 'renormalize': renormalize})


def moe_ragged_combine(y_sorted, order, combine_weights, name=None):
    """moe_ragged_combine (generated from ops.yaml)."""
    return _call('moe_ragged_combine', _impl_moe_ops.moe_ragged_combine, (y_sorted, order, combine_weights,), {})


def grouped_matmul(lhs, rhs, group_sizes, rhs_scales=None, impl="auto", name=None):
    """grouped_matmul (generated from ops.yaml)."""
    return _call('grouped_matmul', _impl_moe_ops.grouped_matmul, (lhs, rhs, group_sizes, rhs_scales,), {'impl': impl})


def moe_router_logits(x, weight, dtype="float32", name=None):
    """moe_router_logits (generated from ops.yaml)."""
    return _call('moe_router_logits', _impl_moe_ops.moe_router_logits, (x, weight,), {'dtype': dtype})


def moe_held_dispatch(x, gate_logits, k, start, count, rows, renormalize=True, scoring="softmax", bias=None, scale=1.0, n_group=1, topk_group=1, name=None):
    """moe_held_dispatch (generated from ops.yaml)."""
    return _call('moe_held_dispatch', _impl_moe_ops.moe_held_dispatch, (x, gate_logits, bias,), {'k': k, 'start': start, 'count': count, 'rows': rows, 'renormalize': renormalize, 'scoring': scoring, 'scale': scale, 'n_group': n_group, 'topk_group': topk_group})


def moe_group_load(gate_logits, bias=None, n_group=1, topk_group=1, name=None):
    """moe_group_load (generated from ops.yaml)."""
    return _call('moe_group_load', _impl_moe_ops.moe_group_load, (gate_logits, bias,), {'n_group': n_group, 'topk_group': topk_group})


def moe_held_experts(x, w_gate, w_up, w_down, row_token, row_weight, expert_load, rows, impl="auto", name=None):
    """moe_held_experts (generated from ops.yaml)."""
    return _call('moe_held_experts', _impl_moe_ops.moe_held_experts, (x, w_gate, w_up, w_down, row_token, row_weight, expert_load,), {'rows': rows, 'impl': impl})


def zero_centered_rms_norm(x, weight, epsilon=1e-6, name=None):
    """zero_centered_rms_norm (generated from ops.yaml)."""
    return _call('zero_centered_rms_norm', _impl_gdn_ops.zero_centered_rms_norm, (x, weight,), {'epsilon': epsilon})


def partial_rope_qk(q, k, rotary_dim, base=10000.0, name=None):
    """partial_rope_qk (generated from ops.yaml)."""
    return _call('partial_rope_qk', _impl_gdn_ops.partial_rope_qk, (q, k,), {'rotary_dim': rotary_dim, 'base': base})


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, scale, causal=True, impl="auto", name=None):
    """mla_attention (generated from ops.yaml)."""
    return _call('mla_attention', _impl_nn_ops.mla_attention, (q_nope, q_rope, k_nope, k_rope, v,), {'scale': scale, 'causal': causal, 'impl': impl})


def window_attention(query, key, value, window, scale=None, impl="auto", name=None):
    """window_attention (generated from ops.yaml)."""
    return _call('window_attention', _impl_nn_ops.window_attention, (query, key, value,), {'window': window, 'scale': scale, 'impl': impl})


def rope_qk_freqs(q, k, inv_freq, scale=1.0, name=None):
    """rope_qk_freqs (generated from ops.yaml)."""
    return _call('rope_qk_freqs', _impl_fused_ops.rope_qk_freqs, (q, k,), {'inv_freq': inv_freq, 'scale': scale})


def gated_delta_rule(q, k, v, g, beta, chunk=64, impl="auto", num_k_heads=None, name=None):
    """gated_delta_rule (generated from ops.yaml)."""
    return _call('gated_delta_rule', _impl_gdn_ops.gated_delta_rule, (q, k, v, g, beta,), {'chunk': chunk, 'impl': impl, 'num_k_heads': num_k_heads})


def kda(q, k, v, g, beta, chunk=64, impl="auto", num_heads=None, name=None):
    """kda (generated from ops.yaml)."""
    return _call('kda', _impl_gdn_ops.kda, (q, k, v, g, beta,), {'chunk': chunk, 'impl': impl, 'num_heads': num_heads})


def mamba2_ssd(x, dt, a, b, c, d, chunk=256, n_groups=1, impl="auto", name=None):
    """mamba2_ssd (generated from ops.yaml)."""
    return _call('mamba2_ssd', _impl_ssm_ops.mamba2_ssd, (x, dt, a, b, c, d,), {'chunk': chunk, 'n_groups': n_groups, 'impl': impl})


def fused_linear_cross_entropy(x, weight, labels, chunk_size=4096, ignore_index=-100, name=None):
    """fused_linear_cross_entropy (generated from ops.yaml)."""
    return _call('fused_linear_cross_entropy', _impl_fused_ops.fused_linear_cross_entropy, (x, weight, labels,), {'chunk_size': chunk_size, 'ignore_index': ignore_index})


def fused_bias_act(x, bias=None, act_method="gelu", name=None):
    """fused_bias_act (generated from ops.yaml)."""
    return _call('fused_bias_act', _impl_fused_ops.fused_bias_act, (x, bias,), {'act_method': act_method})


def max_pool2d_with_index(x, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW", name=None):
    """max_pool2d_with_index (generated from ops.yaml)."""
    kernel_size = _int_array(kernel_size)
    stride = _int_array(stride)
    return _call('max_pool2d_with_index', _impl_nn_ops.max_pool2d_with_index, (x,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'data_format': data_format})


def gammaln(x, name=None):
    """gammaln (generated from ops.yaml)."""
    return _call('gammaln', _impl_math.gammaln, (x,), {})


def gammaincc(x, y, name=None):
    """gammaincc (generated from ops.yaml)."""
    return _call('gammaincc', _impl_math.gammaincc, (x, y,), {})


def fill(x, value=0.0, name=None):
    """fill (generated from ops.yaml)."""
    value = _scalar(value)
    return _call('fill', _impl_math.fill, (x,), {'value': value})


def fill_diagonal(x, value=0.0, offset=0, wrap=False, name=None):
    """fill_diagonal (generated from ops.yaml)."""
    value = _scalar(value)
    return _call('fill_diagonal', _impl_math.fill_diagonal, (x,), {'value': value, 'offset': offset, 'wrap': wrap})


def clip_by_norm(x, max_norm, name=None):
    """clip_by_norm (generated from ops.yaml)."""
    return _call('clip_by_norm', _impl_math.clip_by_norm, (x,), {'max_norm': max_norm})


def squared_l2_norm(x, name=None):
    """squared_l2_norm (generated from ops.yaml)."""
    return _call('squared_l2_norm', _impl_math.squared_l2_norm, (x,), {})


def renorm(x, p=2.0, axis=0, max_norm=1.0, name=None):
    """renorm (generated from ops.yaml)."""
    return _call('renorm', _impl_math.renorm, (x,), {'p': p, 'axis': axis, 'max_norm': max_norm})


def frobenius_norm(x, axis=None, keepdim=False, name=None):
    """frobenius_norm (generated from ops.yaml)."""
    return _call('frobenius_norm', _impl_math.frobenius_norm, (x,), {'axis': axis, 'keepdim': keepdim})


def is_empty(x, name=None):
    """is_empty (generated from ops.yaml)."""
    return _call('is_empty', _impl_math.is_empty, (x,), {})


def logsigmoid(x, name=None):
    """logsigmoid (generated from ops.yaml)."""
    return _call('logsigmoid', _impl_activation.log_sigmoid, (x,), {})


def tanh_shrink(x, name=None):
    """tanh_shrink (generated from ops.yaml)."""
    return _call('tanh_shrink', _impl_activation.tanhshrink, (x,), {})


def reverse(x, axis, name=None):
    """reverse (generated from ops.yaml)."""
    axis = _int_array(axis)
    return _call('reverse', _impl_manipulation.flip, (x,), {'axis': axis})


def as_strided(x, shape, stride, offset=0, name=None):
    """as_strided (generated from ops.yaml)."""
    return _call('as_strided', _impl_manipulation.as_strided, (x,), {'shape': shape, 'stride': stride, 'offset': offset})


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    """channel_shuffle (generated from ops.yaml)."""
    return _call('channel_shuffle', _impl_manipulation.channel_shuffle, (x,), {'groups': groups, 'data_format': data_format})


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    """temporal_shift (generated from ops.yaml)."""
    return _call('temporal_shift', _impl_manipulation.temporal_shift, (x,), {'seg_num': seg_num, 'shift_ratio': shift_ratio, 'data_format': data_format})


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    """huber_loss (generated from ops.yaml)."""
    return _call('huber_loss', _impl_nn_ops.huber_loss, (input, label,), {'delta': delta, 'reduction': reduction})


def hinge_loss(logits, labels, name=None):
    """hinge_loss (generated from ops.yaml)."""
    return _call('hinge_loss', _impl_nn_ops.hinge_loss, (logits, labels,), {})


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    """sequence_mask (generated from ops.yaml)."""
    dtype = _dtype_attr(dtype)
    return _call('sequence_mask', _impl_nn_ops.sequence_mask, (lengths,), {'maxlen': maxlen, 'dtype': dtype})


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0, output_size=None, name=None):
    """max_unpool2d (generated from ops.yaml)."""
    return _call('max_unpool2d', _impl_nn_ops.max_unpool2d, (x, indices,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'output_size': output_size})


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """fold (generated from ops.yaml)."""
    output_sizes = _int_array(output_sizes)
    kernel_sizes = _int_array(kernel_sizes)
    strides = _int_array(strides)
    paddings = _int_array(paddings)
    dilations = _int_array(dilations)
    return _call('fold', _impl_nn_ops.fold, (x,), {'output_sizes': output_sizes, 'kernel_sizes': kernel_sizes, 'strides': strides, 'paddings': paddings, 'dilations': dilations})


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """spectral_norm (generated from ops.yaml)."""
    return _call('spectral_norm', _impl_nn_ops.spectral_norm, (weight,), {'dim': dim, 'power_iters': power_iters, 'eps': eps})


def frame(x, frame_length, hop_length, axis=-1, name=None):
    """frame (generated from ops.yaml)."""
    return _call('frame', _impl_fft_ops.frame, (x,), {'frame_length': frame_length, 'hop_length': hop_length, 'axis': axis})


def overlap_add(x, hop_length, axis=-1, name=None):
    """overlap_add (generated from ops.yaml)."""
    return _call('overlap_add', _impl_fft_ops.overlap_add, (x,), {'hop_length': hop_length, 'axis': axis})


def gather_tree(ids, parents, name=None):
    """gather_tree (generated from ops.yaml)."""
    return _call('gather_tree', _impl_search.gather_tree, (ids, parents,), {})


def edit_distance(hyps, refs, hyp_lengths=None, ref_lengths=None, normalized=True, name=None):
    """edit_distance (generated from ops.yaml)."""
    return _call('edit_distance', _impl_search.edit_distance, (hyps, refs, hyp_lengths, ref_lengths,), {'normalized': normalized})


def lu_unpack(x, y, unpack_ludata=True, unpack_pivots=True, name=None):
    """lu_unpack (generated from ops.yaml)."""
    return _call('lu_unpack', _impl_linalg.lu_unpack, (x, y,), {'unpack_ludata': unpack_ludata, 'unpack_pivots': unpack_pivots})


def p_norm(x, porder=2.0, axis=None, epsilon=1e-12, keepdim=False, asvector=False, name=None):
    """p_norm (generated from ops.yaml)."""
    return _call('p_norm', _impl_linalg.p_norm, (x,), {'porder': porder, 'axis': axis, 'epsilon': epsilon, 'keepdim': keepdim, 'asvector': asvector})


def binomial(count, prob, name=None):
    """binomial (generated from ops.yaml)."""
    _key = _split_key()
    return _call('binomial', _impl_random.binomial, (count, prob,), {'key': _key})


def exponential(x, lam=1.0, name=None):
    """exponential (generated from ops.yaml)."""
    _key = _split_key()
    return _call('exponential', _impl_random.exponential, (x,), {'lam': lam, 'key': _key})


def dirichlet(alpha, name=None):
    """dirichlet (generated from ops.yaml)."""
    _key = _split_key()
    return _call('dirichlet', _impl_random.dirichlet, (alpha,), {'key': _key})


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0, reduction="mean", norm_by_times=False, name=None):
    """ctc_loss (generated from ops.yaml)."""
    return _call('ctc_loss', _impl_nn_ops.ctc_loss, (log_probs, labels, input_lengths, label_lengths,), {'blank': blank, 'reduction': reduction, 'norm_by_times': norm_by_times})


def lp_pool2d(x, norm_type=2.0, kernel_size=2, stride=None, padding=0, ceil_mode=False, data_format="NCHW", name=None):
    """lp_pool2d (generated from ops.yaml)."""
    return _call('lp_pool2d', _impl_nn_ops.lp_pool2d, (x,), {'norm_type': norm_type, 'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'ceil_mode': ceil_mode, 'data_format': data_format})


def fractional_max_pool2d(x, output_size, kernel_size=None, random_u=None, name=None):
    """fractional_max_pool2d (generated from ops.yaml)."""
    output_size = _int_array(output_size)
    return _call('fractional_max_pool2d', _impl_nn_ops.fractional_max_pool2d, (x,), {'output_size': output_size, 'kernel_size': kernel_size, 'random_u': random_u})


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0, output_size=None, name=None):
    """max_unpool3d (generated from ops.yaml)."""
    return _call('max_unpool3d', _impl_nn_ops.max_unpool3d, (x, indices,), {'kernel_size': kernel_size, 'stride': stride, 'padding': padding, 'output_size': output_size})


TENSOR_METHOD_TABLE = [
    ('abs', 'abs'),
    ('acos', 'acos'),
    ('acosh', 'acosh'),
    ('asin', 'asin'),
    ('asinh', 'asinh'),
    ('atan', 'atan'),
    ('atanh', 'atanh'),
    ('ceil', 'ceil'),
    ('ceil_', 'ceil_'),
    ('cos', 'cos'),
    ('cosh', 'cosh'),
    ('digamma', 'digamma'),
    ('erf', 'erf'),
    ('erfinv', 'erfinv'),
    ('exp', 'exp'),
    ('exp_', 'exp_'),
    ('expm1', 'expm1'),
    ('floor', 'floor'),
    ('floor_', 'floor_'),
    ('lgamma', 'lgamma'),
    ('log', 'log'),
    ('log10', 'log10'),
    ('log1p', 'log1p'),
    ('log2', 'log2'),
    ('logit', 'logit'),
    ('neg', 'neg'),
    ('reciprocal', 'reciprocal'),
    ('reciprocal_', 'reciprocal_'),
    ('round', 'round'),
    ('round_', 'round_'),
    ('rsqrt', 'rsqrt'),
    ('rsqrt_', 'rsqrt_'),
    ('sign', 'sign'),
    ('sin', 'sin'),
    ('sinc', 'sinc'),
    ('sinh', 'sinh'),
    ('sqrt', 'sqrt'),
    ('sqrt_', 'sqrt_'),
    ('square', 'square'),
    ('tan', 'tan'),
    ('tanh', 'tanh'),
    ('tanh_', 'tanh_'),
    ('sigmoid', 'sigmoid'),
    ('sigmoid_', 'sigmoid_'),
    ('trunc', 'trunc'),
    ('frac', 'frac'),
    ('angle', 'angle'),
    ('conj', 'conj'),
    ('real', 'real'),
    ('imag', 'imag'),
    ('i0', 'i0'),
    ('i0e', 'i0e'),
    ('i1', 'i1'),
    ('i1e', 'i1e'),
    ('polygamma', 'polygamma'),
    ('deg2rad', 'deg2rad'),
    ('rad2deg', 'rad2deg'),
    ('nan_to_num', 'nan_to_num'),
    ('signbit', 'signbit'),
    ('add', 'add'),
    ('add_', 'add_'),
    ('subtract', 'subtract'),
    ('subtract_', 'subtract_'),
    ('multiply', 'multiply'),
    ('multiply_', 'multiply_'),
    ('divide', 'divide'),
    ('divide_', 'divide_'),
    ('floor_divide', 'floor_divide'),
    ('remainder', 'remainder'),
    ('mod', 'remainder'),
    ('fmod', 'fmod'),
    ('pow', 'pow'),
    ('maximum', 'maximum'),
    ('minimum', 'minimum'),
    ('fmax', 'fmax'),
    ('fmin', 'fmin'),
    ('atan2', 'atan2'),
    ('logaddexp', 'logaddexp'),
    ('logaddexp2', 'logaddexp2'),
    ('heaviside', 'heaviside'),
    ('nextafter', 'nextafter'),
    ('copysign', 'copysign'),
    ('hypot', 'hypot'),
    ('gcd', 'gcd'),
    ('lcm', 'lcm'),
    ('ldexp', 'ldexp'),
    ('inner', 'inner'),
    ('outer', 'outer'),
    ('scale', 'scale'),
    ('scale_', 'scale_'),
    ('clip', 'clip'),
    ('clip_', 'clip_'),
    ('lerp', 'lerp'),
    ('stanh', 'stanh'),
    ('addmm', 'addmm'),
    ('diff', 'diff'),
    ('sum', 'sum'),
    ('mean', 'mean'),
    ('max', 'max'),
    ('min', 'min'),
    ('amax', 'amax'),
    ('amin', 'amin'),
    ('prod', 'prod'),
    ('all', 'all'),
    ('any', 'any'),
    ('logsumexp', 'logsumexp'),
    ('nansum', 'nansum'),
    ('nanmean', 'nanmean'),
    ('count_nonzero', 'count_nonzero'),
    ('std', 'std'),
    ('var', 'var'),
    ('median', 'median'),
    ('nanmedian', 'nanmedian'),
    ('quantile', 'quantile'),
    ('cumsum', 'cumsum'),
    ('cumprod', 'cumprod'),
    ('cummax', 'cummax'),
    ('cummin', 'cummin'),
    ('logcumsumexp', 'logcumsumexp'),
    ('equal', 'equal'),
    ('not_equal', 'not_equal'),
    ('greater_than', 'greater_than'),
    ('greater_equal', 'greater_equal'),
    ('less_than', 'less_than'),
    ('less_equal', 'less_equal'),
    ('logical_and', 'logical_and'),
    ('logical_or', 'logical_or'),
    ('logical_xor', 'logical_xor'),
    ('logical_not', 'logical_not'),
    ('bitwise_and', 'bitwise_and'),
    ('bitwise_or', 'bitwise_or'),
    ('bitwise_xor', 'bitwise_xor'),
    ('bitwise_not', 'bitwise_not'),
    ('bitwise_left_shift', 'bitwise_left_shift'),
    ('bitwise_right_shift', 'bitwise_right_shift'),
    ('isnan', 'isnan'),
    ('isinf', 'isinf'),
    ('isfinite', 'isfinite'),
    ('isneginf', 'isneginf'),
    ('isposinf', 'isposinf'),
    ('isreal', 'isreal'),
    ('isclose', 'isclose'),
    ('allclose', 'allclose'),
    ('equal_all', 'equal_all'),
    ('matmul', 'matmul'),
    ('mm', 'matmul'),
    ('bmm', 'bmm'),
    ('mv', 'mv'),
    ('dot', 'dot'),
    ('t', 't'),
    ('cross', 'cross'),
    ('kron', 'kron'),
    ('trace', 'trace'),
    ('dist', 'dist'),
    ('cholesky', 'cholesky'),
    ('cholesky_solve', 'cholesky_solve'),
    ('inverse', 'inverse'),
    ('pinv', 'pinv'),
    ('solve', 'solve'),
    ('triangular_solve', 'triangular_solve'),
    ('lstsq', 'lstsq'),
    ('svd', 'svd'),
    ('svdvals', 'svdvals'),
    ('qr', 'qr'),
    ('eig', 'eig'),
    ('eigh', 'eigh'),
    ('eigvals', 'eigvals'),
    ('eigvalsh', 'eigvalsh'),
    ('matrix_power', 'matrix_power'),
    ('matrix_rank', 'matrix_rank'),
    ('det', 'det'),
    ('slogdet', 'slogdet'),
    ('lu', 'lu'),
    ('norm', 'norm'),
    ('histogram', 'histogram'),
    ('bincount', 'bincount'),
    ('corrcoef', 'corrcoef'),
    ('cov', 'cov'),
    ('cdist', 'cdist'),
    ('reshape', 'reshape'),
    ('reshape_', 'reshape_'),
    ('flatten', 'flatten'),
    ('squeeze', 'squeeze'),
    ('squeeze_', 'squeeze_'),
    ('unsqueeze', 'unsqueeze'),
    ('unsqueeze_', 'unsqueeze_'),
    ('transpose', 'transpose'),
    ('moveaxis', 'moveaxis'),
    ('swapaxes', 'swapaxes'),
    ('split', 'split'),
    ('chunk', 'chunk'),
    ('tensor_split', 'tensor_split'),
    ('unbind', 'unbind'),
    ('unstack', 'unstack'),
    ('tile', 'tile'),
    ('expand', 'expand'),
    ('broadcast_to', 'broadcast_to'),
    ('expand_as', 'expand_as'),
    ('gather', 'gather'),
    ('gather_nd', 'gather_nd'),
    ('take', 'take'),
    ('take_along_axis', 'take_along_axis'),
    ('put_along_axis', 'put_along_axis'),
    ('scatter', 'scatter'),
    ('scatter_', 'scatter_'),
    ('scatter_nd_add', 'scatter_nd_add'),
    ('slice_scatter', 'slice_scatter'),
    ('index_select', 'index_select'),
    ('index_sample', 'index_sample'),
    ('index_add', 'index_add'),
    ('index_put', 'index_put'),
    ('masked_select', 'masked_select'),
    ('masked_fill', 'masked_fill'),
    ('masked_fill_', 'masked_fill_'),
    ('masked_scatter', 'masked_scatter'),
    ('roll', 'roll'),
    ('flip', 'flip'),
    ('rot90', 'rot90'),
    ('repeat_interleave', 'repeat_interleave'),
    ('cast', 'cast'),
    ('cast_', 'cast_'),
    ('assign', 'assign'),
    ('numel', 'numel'),
    ('diagonal', 'diagonal'),
    ('diag', 'diag'),
    ('diagflat', 'diagflat'),
    ('diag_embed', 'diag_embed'),
    ('tril', 'tril'),
    ('triu', 'triu'),
    ('one_hot', 'one_hot'),
    ('unique', 'unique'),
    ('unique_consecutive', 'unique_consecutive'),
    ('nonzero', 'nonzero'),
    ('shard_index', 'shard_index'),
    ('as_real', 'as_real'),
    ('as_complex', 'as_complex'),
    ('crop', 'crop'),
    ('argmax', 'argmax'),
    ('argmin', 'argmin'),
    ('argsort', 'argsort'),
    ('sort', 'sort'),
    ('topk', 'topk'),
    ('kthvalue', 'kthvalue'),
    ('mode', 'mode'),
    ('searchsorted', 'searchsorted'),
    ('bucketize', 'bucketize'),
    ('zeros_like', 'zeros_like'),
    ('ones_like', 'ones_like'),
    ('full_like', 'full_like'),
    ('empty_like', 'empty_like'),
    ('bernoulli', 'bernoulli'),
    ('multinomial', 'multinomial'),
    ('poisson', 'poisson'),
    ('relu', 'relu'),
    ('relu_', 'relu_'),
    ('relu6', 'relu6'),
    ('leaky_relu', 'leaky_relu'),
    ('prelu', 'prelu'),
    ('elu', 'elu'),
    ('selu', 'selu'),
    ('celu', 'celu'),
    ('gelu', 'gelu'),
    ('silu', 'silu'),
    ('swish', 'swish'),
    ('mish', 'mish'),
    ('softplus', 'softplus'),
    ('softsign', 'softsign'),
    ('softshrink', 'softshrink'),
    ('hardshrink', 'hardshrink'),
    ('tanhshrink', 'tanhshrink'),
    ('hardtanh', 'hardtanh'),
    ('hardsigmoid', 'hardsigmoid'),
    ('hardswish', 'hardswish'),
    ('log_sigmoid', 'log_sigmoid'),
    ('softmax', 'softmax'),
    ('log_softmax', 'log_softmax'),
    ('gumbel_softmax', 'gumbel_softmax'),
    ('glu', 'glu'),
    ('maxout', 'maxout'),
    ('thresholded_relu', 'thresholded_relu'),
    ('rrelu', 'rrelu'),
    ('swiglu', 'swiglu'),
    ('fft', 'fft'),
    ('ifft', 'ifft'),
    ('rfft', 'rfft'),
    ('irfft', 'irfft'),
    ('hfft', 'hfft'),
    ('ihfft', 'ihfft'),
    ('fft2', 'fft2'),
    ('ifft2', 'ifft2'),
    ('rfft2', 'rfft2'),
    ('irfft2', 'irfft2'),
    ('fftn', 'fftn'),
    ('ifftn', 'ifftn'),
    ('rfftn', 'rfftn'),
    ('irfftn', 'irfftn'),
    ('fftshift', 'fftshift'),
    ('ifftshift', 'ifftshift'),
    ('gammaln', 'gammaln'),
    ('gammaincc', 'gammaincc'),
    ('fill', 'fill'),
    ('fill_diagonal', 'fill_diagonal'),
    ('clip_by_norm', 'clip_by_norm'),
    ('squared_l2_norm', 'squared_l2_norm'),
    ('renorm', 'renorm'),
    ('frobenius_norm', 'frobenius_norm'),
    ('is_empty', 'is_empty'),
    ('logsigmoid', 'logsigmoid'),
    ('tanh_shrink', 'tanh_shrink'),
    ('reverse', 'reverse'),
    ('as_strided', 'as_strided'),
    ('channel_shuffle', 'channel_shuffle'),
    ('temporal_shift', 'temporal_shift'),
    ('p_norm', 'p_norm'),
]

__all__ = [
    'abs',
    'acos',
    'acosh',
    'adaptive_avg_pool1d',
    'adaptive_avg_pool2d',
    'adaptive_max_pool2d',
    'add',
    'add_',
    'addmm',
    'affine_grid',
    'all',
    'allclose',
    'alpha_dropout',
    'amax',
    'amin',
    'angle',
    'any',
    'arange',
    'argmax',
    'argmin',
    'argsort',
    'as_complex',
    'as_real',
    'as_strided',
    'asin',
    'asinh',
    'assign',
    'atan',
    'atan2',
    'atanh',
    'avg_pool1d',
    'avg_pool2d',
    'avg_pool3d',
    'batch_norm',
    'batch_norm_with_stats',
    'bernoulli',
    'bilinear',
    'binary_cross_entropy',
    'binary_cross_entropy_with_logits',
    'bincount',
    'binomial',
    'bitwise_and',
    'bitwise_left_shift',
    'bitwise_not',
    'bitwise_or',
    'bitwise_right_shift',
    'bitwise_xor',
    'bmm',
    'broadcast_tensors',
    'broadcast_to',
    'bucketize',
    'cast',
    'cast_',
    'cdist',
    'ceil',
    'ceil_',
    'celu',
    'channel_shuffle',
    'cholesky',
    'cholesky_solve',
    'chunk',
    'clip',
    'clip_',
    'clip_by_norm',
    'complex',
    'concat',
    'conj',
    'conv1d',
    'conv1d_transpose',
    'conv2d',
    'conv2d_transpose',
    'conv3d',
    'conv3d_transpose',
    'copysign',
    'corrcoef',
    'cos',
    'cosh',
    'cosine_embedding_loss',
    'cosine_similarity',
    'count_nonzero',
    'cov',
    'crop',
    'cross',
    'cross_entropy',
    'ctc_loss',
    'cummax',
    'cummin',
    'cumprod',
    'cumsum',
    'deg2rad',
    'det',
    'diag',
    'diag_embed',
    'diagflat',
    'diagonal',
    'diff',
    'digamma',
    'dirichlet',
    'dist',
    'divide',
    'divide_',
    'dot',
    'dropout',
    'dropout2d',
    'dropout3d',
    'edit_distance',
    'eig',
    'eigh',
    'eigvals',
    'eigvalsh',
    'elu',
    'embedding',
    'empty',
    'empty_like',
    'equal',
    'equal_all',
    'erf',
    'erfinv',
    'exp',
    'exp_',
    'expand',
    'expand_as',
    'expm1',
    'exponential',
    'eye',
    'fft',
    'fft2',
    'fftfreq',
    'fftn',
    'fftshift',
    'fill',
    'fill_diagonal',
    'flatten',
    'flip',
    'floor',
    'floor_',
    'floor_divide',
    'fmax',
    'fmin',
    'fmod',
    'fold',
    'frac',
    'fractional_max_pool2d',
    'frame',
    'frobenius_norm',
    'full',
    'full_like',
    'fused_bias_act',
    'fused_linear',
    'fused_linear_cross_entropy',
    'fused_rotary_position_embedding',
    'gammaincc',
    'gammaln',
    'gated_delta_rule',
    'gather',
    'gather_nd',
    'gather_tree',
    'gaussian',
    'gcd',
    'gelu',
    'glu',
    'greater_equal',
    'greater_than',
    'grid_sample',
    'group_norm',
    'grouped_matmul',
    'gumbel_softmax',
    'hardshrink',
    'hardsigmoid',
    'hardswish',
    'hardtanh',
    'heaviside',
    'hfft',
    'hinge_embedding_loss',
    'hinge_loss',
    'histogram',
    'householder_product',
    'huber_loss',
    'hypot',
    'i0',
    'i0e',
    'i1',
    'i1e',
    'ifft',
    'ifft2',
    'ifftn',
    'ifftshift',
    'ihfft',
    'imag',
    'index_add',
    'index_put',
    'index_sample',
    'index_select',
    'inner',
    'instance_norm',
    'interpolate',
    'inverse',
    'irfft',
    'irfft2',
    'irfftn',
    'is_empty',
    'isclose',
    'isfinite',
    'isinf',
    'isnan',
    'isneginf',
    'isposinf',
    'isreal',
    'kda',
    'kl_div',
    'kron',
    'kthvalue',
    'l1_loss',
    'label_smooth',
    'layer_norm',
    'lcm',
    'ldexp',
    'leaky_relu',
    'lerp',
    'less_equal',
    'less_than',
    'lgamma',
    'linear',
    'linspace',
    'local_response_norm',
    'log',
    'log10',
    'log1p',
    'log2',
    'log_loss',
    'log_sigmoid',
    'log_softmax',
    'logaddexp',
    'logaddexp2',
    'logcumsumexp',
    'logical_and',
    'logical_not',
    'logical_or',
    'logical_xor',
    'logit',
    'logsigmoid',
    'logspace',
    'logsumexp',
    'lp_pool2d',
    'lstsq',
    'lu',
    'lu_unpack',
    'mamba2_ssd',
    'margin_ranking_loss',
    'masked_fill',
    'masked_fill_',
    'masked_scatter',
    'masked_select',
    'matmul',
    'matrix_norm',
    'matrix_power',
    'matrix_rank',
    'max',
    'max_pool1d',
    'max_pool2d',
    'max_pool2d_with_index',
    'max_pool3d',
    'max_unpool2d',
    'max_unpool3d',
    'maximum',
    'maxout',
    'mean',
    'median',
    'meshgrid',
    'min',
    'minimum',
    'mish',
    'mla_attention',
    'mode',
    'moe_combine',
    'moe_gate_dispatch',
    'moe_group_load',
    'moe_held_dispatch',
    'moe_held_experts',
    'moe_ragged_combine',
    'moe_ragged_dispatch',
    'moe_router_logits',
    'moveaxis',
    'mse_loss',
    'multi_dot',
    'multinomial',
    'multiplex',
    'multiply',
    'multiply_',
    'mv',
    'nan_to_num',
    'nanmean',
    'nanmedian',
    'nansum',
    'neg',
    'nextafter',
    'nll_loss',
    'nonzero',
    'norm',
    'normalize',
    'not_equal',
    'numel',
    'one_hot',
    'ones',
    'ones_like',
    'outer',
    'overlap_add',
    'p_norm',
    'pad',
    'partial_rope_qk',
    'pinv',
    'pixel_shuffle',
    'pixel_unshuffle',
    'poisson',
    'polar',
    'polygamma',
    'pow',
    'prelu',
    'prod',
    'put_along_axis',
    'qr',
    'quantile',
    'rad2deg',
    'randint',
    'randperm',
    'real',
    'reciprocal',
    'reciprocal_',
    'relu',
    'relu6',
    'relu_',
    'remainder',
    'renorm',
    'repeat_interleave',
    'reshape',
    'reshape_',
    'reverse',
    'rfft',
    'rfft2',
    'rfftfreq',
    'rfftn',
    'rms_norm',
    'rnn',
    'roll',
    'rope_qk',
    'rope_qk_freqs',
    'rot90',
    'round',
    'round_',
    'rrelu',
    'rsqrt',
    'rsqrt_',
    'scale',
    'scale_',
    'scaled_dot_product_attention',
    'scatter',
    'scatter_',
    'scatter_nd',
    'scatter_nd_add',
    'searchsorted',
    'selu',
    'sequence_mask',
    'shard_index',
    'sigmoid',
    'sigmoid_',
    'sign',
    'signbit',
    'silu',
    'sin',
    'sinc',
    'sinh',
    'slice',
    'slice_scatter',
    'slogdet',
    'smooth_l1_loss',
    'softmax',
    'softmax_with_cross_entropy',
    'softplus',
    'softshrink',
    'softsign',
    'solve',
    'sort',
    'spectral_norm',
    'split',
    'sqrt',
    'sqrt_',
    'square',
    'square_error_cost',
    'squared_l2_norm',
    'squeeze',
    'squeeze_',
    'stack',
    'standard_gamma',
    'stanh',
    'std',
    'strided_slice',
    'subtract',
    'subtract_',
    'sum',
    'svd',
    'svdvals',
    'swapaxes',
    'swiglu',
    'swish',
    't',
    'take',
    'take_along_axis',
    'tan',
    'tanh',
    'tanh_',
    'tanh_shrink',
    'tanhshrink',
    'temporal_shift',
    'tensor_split',
    'tensordot',
    'thresholded_relu',
    'tile',
    'topk',
    'trace',
    'transpose',
    'trapezoid',
    'triangular_solve',
    'tril',
    'tril_indices',
    'triplet_margin_loss',
    'triu',
    'triu_indices',
    'trunc',
    'unbind',
    'unfold',
    'uniform',
    'unique',
    'unique_consecutive',
    'unsqueeze',
    'unsqueeze_',
    'unstack',
    'upsample',
    'vander',
    'var',
    'vector_norm',
    'where',
    'window_attention',
    'zero_centered_rms_norm',
    'zeros',
    'zeros_like',
]
