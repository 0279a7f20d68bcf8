"""Shared runtime for the Pallas TPU kernels: dispatch + route accounting.

Three concerns every kernel in this package routes through:

  * ``pl_call()`` — the one ``pl.pallas_call`` wrapper. On a TPU the
    kernel is compiled by Mosaic; off-TPU it runs under the Pallas
    interpreter (``interpret_mode()``), so CPU tier-1 exercises the same
    kernel body. There is no third mode: a kernel Mosaic refuses raises
    from the enclosing ``jit`` compile — nothing here catches it.
  * ``spmd_axes()`` — Mosaic kernels "cannot be automatically
    partitioned": in a sharded program the code that knows the mesh (the
    ``dist.parallelize`` wrapper, a tensor-parallel serving adapter)
    declares which mesh axes the batch and head dims ride on, and a
    kernel that is independent along those dims wraps itself in
    ``shard_map`` over them (``flash_attention``). A sharded program that
    reaches a kernel without the declaration fails in the lowering with
    jax's own "wrap the call in a shard_map" error.
  * ``record_fallback()`` — an EXPLICIT Pallas request that the backend
    cannot honour off-TPU (``decode_kernel="pallas"`` on the CPU mesh
    without ``FLAGS_pallas_interpret``) is counted in
    ``paddle_tpu_kernels_fallbacks_total{kernel,reason}`` and warned
    once per (kernel, reason). The ``"auto"`` routes never count: they
    select a path from what they can observe (backend, shape, dtype,
    sharding), and on a TPU the counter staying at 0 is what
    ``chip_smoke.py`` asserts.

A fourth, smaller one: ``record_flash_blocks()`` — the flash kernels
choose their tile from the call's shapes at trace time, and each traced
kernel call bumps ``paddle_tpu_kernels_flash_blocks{kernel,block_q,
block_k}``, so a test, ``chip_smoke.py`` or a reader of the metrics
registry can say which tile a shape got. ``record_mla_blocks()`` is the
same count for the latent-attention kernels (``paddle_tpu_kernels_mla_blocks{kernel,block_q,block_k}``).
``record_gdr_blocks()`` does the same for the two gated-delta-rule kernels' grid step
(``paddle_tpu_kernels_gdr_blocks{kernel,key_heads,chunks}``),
``record_kda_blocks()`` for the two Kimi-delta-attention kernels'
(``paddle_tpu_kernels_kda_blocks{kernel,heads,chunks}``), and
``record_gdr_operands()`` for the form a ``gated_delta_rule`` call's q, k
and v came in (``paddle_tpu_kernels_gdr_operands{form}``: ``flat`` is what
the kernels read in place, ``heads`` costs a copy of each on a TPU);
``record_mla_operands()`` is its twin for an ``mla_attention`` call through
the kernels (``paddle_tpu_kernels_mla_operands{form}``: ``flat`` where the
parts are 128 wide, ``heads`` where they are narrower and are merged).
``record_ssd_chunk()`` and ``record_ssd_blocks()`` do it for the Mamba-2
kernels: ``paddle_tpu_kernels_ssd_chunk{chunk,d_head,d_state,groups}`` once
a traced ``mamba2_ssd`` kernel call, and ``paddle_tpu_kernels_ssd_blocks
{kernel,heads,chunks}`` once a traced kernel (the heads of a grid step, the
chunks a sequence is walked in). ``record_gmm_tiles()`` counts the
three grouped-matmul kernels' traced calls by the tiles
``grouped_matmul.choose_tiles`` (or the caller) gave them
(``paddle_tpu_kernels_gmm_tiles{kernel,tm,tk,tn}``: rows, depth and
columns of a grid step).

A fifth: ``recompute_segment()`` — ``distributed.recompute`` marks the
extent in which its segment is traced, and a ``flash_attention`` or
``mla_attention`` call traced inside it through the kernels bumps
``paddle_tpu_recompute_kept{kernel}`` (``record_recompute_kept()``, read by
``recompute_kept()``): that call's forward rule named the kernel's output
and log-sum-exp, which the segment keeps instead of running the forward
kernel again in the backward pass.
"""
from __future__ import annotations

import contextlib
import threading
import warnings

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import on_tpu


def interpret_mode():
    """True off-TPU: kernels run under the Pallas interpreter so the
    same kernel body is testable on the CPU mesh."""
    return not on_tpu()


def pl_call(kernel, *, name, dimension_semantics=None, interpret=None,
            compiler_params=None, **kwargs):
    """``pl.pallas_call`` with the package-wide defaults applied:
    interpret-mode autoselect (``interpret=None``) and
    ``dimension_semantics`` routed through ``pltpu.CompilerParams``.
    Any explicit ``compiler_params`` wins. ``name`` is required: it is
    the kernel's name in the lowered program and in a device trace
    (docs/kernels.md lists the five), which a benchmark's reduction finds
    the kernel's time by."""
    if compiler_params is None and dimension_semantics is not None:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics)
        )
    if interpret is None:
        interpret = interpret_mode()
    return pl.pallas_call(
        kernel, name=name, compiler_params=compiler_params,
        interpret=interpret, **kwargs,
    )


_spmd = threading.local()


@contextlib.contextmanager
def spmd_axes(mesh, batch_axis=None, head_axis=None):
    """Declare, for the code traced inside, the ``jax.sharding.Mesh`` and
    the names of the mesh axes that shard attention's batch and head dims
    (None = that dim is whole on every device)."""
    prev = getattr(_spmd, "axes", None)
    _spmd.axes = (mesh, batch_axis, head_axis)
    try:
        yield
    finally:
        _spmd.axes = prev


def current_spmd_axes():
    """``(mesh, batch_axis, head_axis)`` of the enclosing ``spmd_axes``,
    or None in a single-device program."""
    return getattr(_spmd, "axes", None)


_recompute = threading.local()


@contextlib.contextmanager
def recompute_segment():
    """The code traced inside is a ``distributed.recompute`` segment."""
    prev = getattr(_recompute, "inside", False)
    _recompute.inside = True
    try:
        yield
    finally:
        _recompute.inside = prev


# (kernel, reason) pairs already warned about — the counter moves on
# every degradation, the warning fires once per pair per process
_warned_fallbacks = set()


def _fallback_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_fallbacks_total",
        "Pallas kernel launches degraded to the XLA fallback",
        labelnames=("kernel", "reason"),
    )


def record_fallback(kernel, reason, hint=None):
    """An explicitly requested Pallas path ran its XLA fallback instead.
    Count it (always) and warn (once per (kernel, reason)). ``hint``
    lets the caller append remediation that applies to ITS degradation
    (e.g. the interpret flag for an off-backend serving request)."""
    _fallback_counter().inc(kernel=kernel, reason=reason)
    if (kernel, reason) not in _warned_fallbacks:
        _warned_fallbacks.add((kernel, reason))
        msg = (
            f"pallas kernel {kernel!r} degraded to the XLA fallback "
            f"({reason})"
        )
        if hint:
            msg += f"; {hint}"
        warnings.warn(msg, stacklevel=3)


def fallbacks_total():
    """Current total of the degradation counter (test/diagnostic
    accessor)."""
    return sum(child.value for _, child in _fallback_counter()._series())


def _flash_blocks_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_flash_blocks",
        "Traced flash-attention kernel calls by the tile they were given",
        labelnames=("kernel", "block_q", "block_k"),
    )


def record_flash_blocks(kernel, block_q, block_k):
    """One traced call of ``kernel`` with this tile (a choice made at
    trace time has no rate; it has a value)."""
    _flash_blocks_counter().inc(
        kernel=kernel, block_q=block_q, block_k=block_k)


def flash_blocks():
    """{(kernel, block_q, block_k): traced calls} (test/diagnostic
    accessor)."""
    return {
        (labels["kernel"], int(labels["block_q"]), int(labels["block_k"])):
        child.value
        for labels, child in _flash_blocks_counter()._series()
    }


def _mla_blocks_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_mla_blocks",
        "Traced latent-attention kernel calls by the tile they were given",
        labelnames=("kernel", "block_q", "block_k"),
    )


def record_mla_blocks(kernel, block_q, block_k):
    """One traced call of a latent-attention ``kernel`` with this tile."""
    _mla_blocks_counter().inc(
        kernel=kernel, block_q=block_q, block_k=block_k)


def mla_blocks():
    """{(kernel, block_q, block_k): traced calls} (test/diagnostic
    accessor)."""
    return {
        (labels["kernel"], int(labels["block_q"]), int(labels["block_k"])):
        child.value
        for labels, child in _mla_blocks_counter()._series()
    }


def _gdr_blocks_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_gdr_blocks",
        "Traced gated-delta-rule kernel calls by the grid step they were "
        "given",
        labelnames=("kernel", "key_heads", "chunks"),
    )


def record_gdr_blocks(kernel, key_heads, chunks):
    """One traced call of ``kernel`` whose grid step holds ``key_heads``
    key heads (with their value heads) over ``chunks`` chunks."""
    _gdr_blocks_counter().inc(
        kernel=kernel, key_heads=key_heads, chunks=chunks)


def gdr_blocks():
    """{(kernel, key_heads, chunks): traced calls} (test/diagnostic
    accessor)."""
    return {
        (labels["kernel"], int(labels["key_heads"]), int(labels["chunks"])):
        child.value
        for labels, child in _gdr_blocks_counter()._series()
    }


def _kda_blocks_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_kda_blocks",
        "Traced Kimi-delta-attention kernel calls by the grid step they "
        "were given",
        labelnames=("kernel", "heads", "chunks"),
    )


def record_kda_blocks(kernel, heads, chunks):
    """One traced call of ``kernel`` (``kda_fwd``, ``kda_bwd``) whose grid
    step holds ``heads`` heads over ``chunks`` chunks."""
    _kda_blocks_counter().inc(kernel=kernel, heads=heads, chunks=chunks)


def kda_blocks():
    """{(kernel, heads, chunks): traced calls} (test/diagnostic
    accessor)."""
    return {
        (labels["kernel"], int(labels["heads"]), int(labels["chunks"])):
        child.value
        for labels, child in _kda_blocks_counter()._series()
    }


def _gdr_operands_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_gdr_operands",
        "Traced gated_delta_rule calls by the form of q, k and v: flat "
        "[B, T, H d] or heads [B, T, H, d]",
        labelnames=("form",),
    )


def record_gdr_operands(form):
    """One traced ``gated_delta_rule`` call whose q, k and v came as
    ``"flat"`` [B, T, H d] or as ``"heads"`` [B, T, H, d]."""
    _gdr_operands_counter().inc(form=form)


def gdr_operands():
    """{form: traced calls} (test/diagnostic accessor)."""
    return {labels["form"]: child.value
            for labels, child in _gdr_operands_counter()._series()}


def _mla_operands_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_mla_operands",
        "Traced mla_attention calls through the kernels by the form their "
        "128-wide parts are read in: flat [B, T, H d] or heads [B H, T, d]",
        labelnames=("form",),
    )


def record_mla_operands(form):
    """One traced ``mla_attention`` call through the kernels whose q_nope,
    k_nope and v (and out and the cotangents) the kernels read ``"flat"``,
    [B, T, H d] in place, or as ``"heads"``, [B H, T, d]."""
    _mla_operands_counter().inc(form=form)


def mla_operands():
    """{form: traced calls} (test/diagnostic accessor)."""
    return {labels["form"]: child.value
            for labels, child in _mla_operands_counter()._series()}


def _ssd_chunk_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_ssd_chunk",
        "Traced mamba2_ssd kernel calls by chunk, head and state sizes",
        labelnames=("chunk", "d_head", "d_state", "groups"),
    )


def record_ssd_chunk(chunk, d_head, d_state, groups):
    """One traced ``mamba2_ssd`` call through the kernels, with the chunk
    its sequence is cut in."""
    _ssd_chunk_counter().inc(
        chunk=chunk, d_head=d_head, d_state=d_state, groups=groups)


def ssd_chunks():
    """{(chunk, d_head, d_state, groups): traced calls} (test/diagnostic
    accessor)."""
    return {
        tuple(int(labels[n]) for n in ("chunk", "d_head", "d_state",
                                       "groups")): child.value
        for labels, child in _ssd_chunk_counter()._series()
    }


def _ssd_blocks_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_ssd_blocks",
        "Traced mamba2_ssd kernels by the heads of a grid step and the "
        "chunks a sequence is walked in",
        labelnames=("kernel", "heads", "chunks"),
    )


def record_ssd_blocks(kernel, heads, chunks):
    """One traced call of ``kernel`` whose grid step holds ``heads`` heads
    of one chunk, over ``chunks`` chunks a sequence."""
    _ssd_blocks_counter().inc(kernel=kernel, heads=heads, chunks=chunks)


def ssd_blocks():
    """{(kernel, heads, chunks): traced calls} (test/diagnostic
    accessor)."""
    return {
        (labels["kernel"], int(labels["heads"]), int(labels["chunks"])):
        child.value
        for labels, child in _ssd_blocks_counter()._series()
    }


def _gmm_tiles_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_kernels_gmm_tiles",
        "Traced grouped-matmul kernel calls by the tiles they were given: "
        "rows, depth and columns of a grid step",
        labelnames=("kernel", "tm", "tk", "tn"),
    )


def record_gmm_tiles(kernel, tm, tk, tn):
    """One traced call of a grouped-matmul ``kernel`` with row tile
    ``tm`` over a [tk, tn] block."""
    _gmm_tiles_counter().inc(kernel=kernel, tm=tm, tk=tk, tn=tn)


def gmm_tiles():
    """{(kernel, tm, tk, tn): traced calls} (test/diagnostic accessor)."""
    return {
        (labels["kernel"],) + tuple(int(labels[n]) for n in ("tm", "tk",
                                                             "tn")):
        child.value
        for labels, child in _gmm_tiles_counter()._series()
    }


def _recompute_kept_counter():
    from ...observability import counter

    return counter(
        "paddle_tpu_recompute_kept",
        "Traced attention calls through the kernels inside a recompute "
        "segment: the forward kernel's output and log-sum-exp are kept",
        labelnames=("kernel",),
    )


def record_recompute_kept(kernel):
    """One traced call of ``kernel`` (``"flash_attention"`` or
    ``"mla_attention"``) through its kernels; counted inside a
    ``recompute_segment()`` only."""
    if getattr(_recompute, "inside", False):
        _recompute_kept_counter().inc(kernel=kernel)


def recompute_kept():
    """{kernel: traced calls inside a recompute segment} (test/diagnostic
    accessor)."""
    return {labels["kernel"]: child.value
            for labels, child in _recompute_kept_counter()._series()}
