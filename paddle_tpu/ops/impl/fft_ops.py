"""FFT op family over jnp.fft.

ref: python/paddle/tensor/fft.py (fft/ifft/rfft/irfft/hfft/ihfft + 2d/n
variants, fftfreq/rfftfreq, fftshift/ifftshift). The reference dispatches
to cuFFT/onemkl kernels (phi/kernels/funcs/fft.cc); here each op lowers
to the XLA FFT HLO with the reference's argument contract (n/s size
padding-or-truncation, axis selection, backward/forward/ortho norm).
"""
from __future__ import annotations

import jax.numpy as jnp


def _norm(norm):
    if norm not in ("backward", "forward", "ortho"):
        raise ValueError(
            f"norm must be 'backward', 'forward' or 'ortho', got {norm!r}"
        )
    return norm


def fft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.fft(x, n=n, axis=int(axis), norm=_norm(norm))


def ifft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.ifft(x, n=n, axis=int(axis), norm=_norm(norm))


def rfft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.rfft(x, n=n, axis=int(axis), norm=_norm(norm))


def irfft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.irfft(x, n=n, axis=int(axis), norm=_norm(norm))


def hfft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.hfft(x, n=n, axis=int(axis), norm=_norm(norm))


def ihfft(x, *, n=None, axis=-1, norm="backward"):
    return jnp.fft.ihfft(x, n=n, axis=int(axis), norm=_norm(norm))


def _axes2(axes):
    return tuple(int(a) for a in axes)


def fft2(x, *, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.fft2(x, s=s, axes=_axes2(axes), norm=_norm(norm))


def ifft2(x, *, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.ifft2(x, s=s, axes=_axes2(axes), norm=_norm(norm))


def rfft2(x, *, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.rfft2(x, s=s, axes=_axes2(axes), norm=_norm(norm))


def irfft2(x, *, s=None, axes=(-2, -1), norm="backward"):
    return jnp.fft.irfft2(x, s=s, axes=_axes2(axes), norm=_norm(norm))


def fftn(x, *, s=None, axes=None, norm="backward"):
    axes = None if axes is None else _axes2(axes)
    return jnp.fft.fftn(x, s=s, axes=axes, norm=_norm(norm))


def ifftn(x, *, s=None, axes=None, norm="backward"):
    axes = None if axes is None else _axes2(axes)
    return jnp.fft.ifftn(x, s=s, axes=axes, norm=_norm(norm))


def rfftn(x, *, s=None, axes=None, norm="backward"):
    axes = None if axes is None else _axes2(axes)
    return jnp.fft.rfftn(x, s=s, axes=axes, norm=_norm(norm))


def irfftn(x, *, s=None, axes=None, norm="backward"):
    axes = None if axes is None else _axes2(axes)
    return jnp.fft.irfftn(x, s=s, axes=axes, norm=_norm(norm))


def fftshift(x, *, axes=None):
    # real-only roll: runs natively on TPU, no host detour needed
    axes = None if axes is None else tuple(int(a) for a in axes)
    return jnp.fft.fftshift(x, axes=axes)


def ifftshift(x, *, axes=None):
    axes = None if axes is None else tuple(int(a) for a in axes)
    return jnp.fft.ifftshift(x, axes=axes)


def fftfreq(*, n, d=1.0, dtype=None):
    from ...core.dtype import to_jnp

    out = jnp.fft.fftfreq(int(n), d=float(d))
    return out.astype(to_jnp(dtype)) if dtype is not None else (
        out.astype(jnp.float32)
    )


def rfftfreq(*, n, d=1.0, dtype=None):
    from ...core.dtype import to_jnp

    out = jnp.fft.rfftfreq(int(n), d=float(d))
    return out.astype(to_jnp(dtype)) if dtype is not None else (
        out.astype(jnp.float32)
    )


# ---- r5 signal framing (ref python/paddle/signal.py) ---------------------
def frame(x, *, frame_length, hop_length, axis=-1):
    """Slice overlapping frames along `axis` (ref signal.frame).

    Layout follows the reference: axis=-1 (or the positive last axis of a
    >=2-D input) yields (..., frame_length, num_frames); axis=0 yields
    (num_frames, frame_length, ...). The SIGNED axis decides for 1-D
    input, where 0 and -1 name the same dim but opposite layouts — the
    old ``axis in (-1, ndim - 1)`` test wrongly transposed the 1-D
    axis=0 case."""
    import jax.numpy as jnp

    ax = axis + x.ndim if axis < 0 else axis
    n = x.shape[ax]
    num = 1 + (n - frame_length) // hop_length
    starts = jnp.arange(num) * hop_length
    idx = starts[:, None] + jnp.arange(frame_length)[None, :]  # [num, fl]
    framed = jnp.take(x, idx.reshape(-1), axis=ax)
    shape = list(x.shape)
    framed = framed.reshape(
        tuple(shape[:ax]) + (num, frame_length) + tuple(shape[ax + 1:])
    )
    # ref layout: frame_length BEFORE num_frames when framing the LAST
    # axis. For 1-D input the SIGNED axis decides (axis=-1 -> last-axis
    # layout, axis=0 -> leading layout); other negative non-last axes
    # (e.g. axis=-2 of a 3-D input) keep the unswapped layout.
    last = ax == x.ndim - 1 and (axis < 0 or x.ndim > 1)
    return jnp.swapaxes(framed, -1, -2) if last else framed


def overlap_add(x, *, hop_length, axis=-1):
    """Inverse of frame for the [-2, -1] = (frame_length, num) layout
    (ref signal.overlap_add)."""
    import jax.numpy as jnp

    if axis not in (-1, x.ndim - 1):
        raise NotImplementedError("overlap_add supports axis=-1")
    fl, num = x.shape[-2], x.shape[-1]
    n_out = fl + hop_length * (num - 1)
    # one scatter-add: duplicate target indices accumulate, so the whole
    # overlap-add is a single [fl, num] indexed .add (no unrolled loop)
    idx = (jnp.arange(num) * hop_length)[None, :] +         jnp.arange(fl)[:, None]
    out = jnp.zeros(x.shape[:-2] + (n_out,), x.dtype)
    return out.at[..., idx].add(x)
