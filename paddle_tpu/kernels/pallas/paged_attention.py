"""Pallas TPU paged (block-table) KV-cache attention for incremental decode.

The reference serves long-context decode through a paged KV cache: physical
cache pages indexed per-sequence by a block table
(ref: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu,
python/paddle/incubate/nn/functional/block_multihead_attention.py — the CUDA
kernel walks `block_tables [bsz, block_num_per_seq]` into
`key_cache [max_block_num, num_head, block_size, head_size]`).

TPU-native form: one query token per sequence ([batch, heads, head_dim]),
pages gathered through a scalar-prefetched block table so the page index
feeds the BlockSpec index_map before the grid step runs (Pallas TPU's
analogue of the CUDA kernel's pointer chase), online softmax across the
page sweep. GQA folds query heads into per-kv-head groups so the MXU sees
a [group, page_size] matmul per page instead of a scalar loop.

Layout:
  q            [batch, num_q_heads, head_dim]
  k_pages      [num_kv_heads, num_pages, page_size, head_dim]
  v_pages      [num_kv_heads, num_pages, page_size, head_dim]
  block_tables [batch, pages_per_seq] int32  (logical page i of seq b ->
               physical page block_tables[b, i])
  lengths      [batch] int32  (tokens currently in the cache per sequence)

Quantized (int8) pages: ``k_pages``/``v_pages`` may instead be a
``(pages int8, scales float32 [num_kv_heads, num_pages, page_size])``
pair — one scale per cached token per kv head (quantize-on-write, see
``update_pages``); both the Pallas kernel and the XLA reference
dequantize in-attention (``k = int8 * scale``), so the int8 cache never
materializes a dense float copy.

A sequence with ``lengths[b] == 0`` returns exact zeros (nothing to
attend over) on BOTH paths — serving's inactive-slot convention.

On non-TPU backends the kernel runs under the Pallas interpreter
(``_compat.pl_call``) so numerics are testable on the CPU mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._compat import pl_call

NEG_INF = -1e30


def _split_quant(pages):
    """(pages, scales) for a quantized pair, (pages, None) otherwise."""
    if isinstance(pages, (tuple, list)):
        return pages[0], pages[1]
    return pages, None


def quantize_tokens(kv):
    """Per-token-per-head absmax int8 quantization of new cache entries.

    kv: [..., d] float -> (q int8 [..., d], scale float32 [...]) with
    ``kv ≈ q * scale[..., None]``. The scale floor keeps all-zero tokens
    exact (q == 0, scale == 1e-8)."""
    absmax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(kv.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _decode_kernel(lengths_ref, tables_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, page_size):
    b = pl.program_id(0)
    page = pl.program_id(2)
    n_pages = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(page == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(page * page_size < length)
    def _visit():
        q = q_ref[0, 0].astype(jnp.float32)   # [group_pad, d]
        k = k_ref[0, 0].astype(jnp.float32)   # [page_size, d]
        v = v_ref[0, 0].astype(jnp.float32)
        _online_softmax_step(
            q, k, v, m_scr, l_scr, acc_scr,
            scale=scale, page_size=page_size, page=page, length=length,
        )

    @pl.when(page == n_pages - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_kernel_quant(lengths_ref, tables_ref, q_ref, k_ref, v_ref,
                         ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         scale, page_size):
    """Int8 variant: dequantize in-kernel from the per-token scales.
    The scales arrive as [1, page_size] rows, the layout of the score
    tile's lane axis, so they are applied to the scores and to the
    probabilities instead of to the pages: ``q·(k8*ks)ᵀ == (q·k8ᵀ)*ks``
    and ``p·(v8*vs) == (p*vs)·v8`` — no lane-to-sublane relayout."""
    b = pl.program_id(0)
    page = pl.program_id(2)
    n_pages = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(page == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(page * page_size < length)
    def _visit():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        _online_softmax_step(
            q, k, v, m_scr, l_scr, acc_scr,
            scale=scale, page_size=page_size, page=page, length=length,
            k_scale=ks_ref[0, 0], v_scale=vs_ref[0, 0],
        )

    @pl.when(page == n_pages - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _online_softmax_step(q, k, v, m_scr, l_scr, acc_scr, *, scale,
                         page_size, page, length, k_scale=None,
                         v_scale=None):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [group_pad, page_size]
    if k_scale is not None:
        s = s * k_scale  # [1, page_size] per-token dequant

    # mask cache slots at/after the current length (unwritten tail of
    # the last partially-filled page)
    pos = page * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    s = jnp.where(pos < length, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:] = jnp.broadcast_to(
        l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape,
    )
    pv = p if v_scale is None else p * v_scale
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale=None):
    """Decode-mode paged attention. Returns [batch, num_q_heads, head_dim].

    GQA: num_q_heads must be a multiple of num_kv_heads; query heads are
    grouped per kv head inside the kernel. ``k_pages``/``v_pages`` may be
    int8 ``(pages, scales)`` pairs (module docstring)."""
    k_pages, k_scales = _split_quant(k_pages)
    v_pages, v_scales = _split_quant(v_pages)
    quant = k_scales is not None
    batch, n_q_heads, d = q.shape
    n_kv_heads, n_pages_total, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    if n_q_heads % n_kv_heads:
        raise ValueError(
            f"num_q_heads ({n_q_heads}) must be divisible by num_kv_heads "
            f"({n_kv_heads})"
        )
    group = n_q_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # pad the per-kv-head query group up to the fp32 sublane tile (8) so
    # scratch/block shapes stay tileable; padded rows are sliced off after
    group_pad = max(8, group)
    qg = q.reshape(batch, n_kv_heads, group, d)
    if group_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, group_pad - group), (0, 0)))

    grid = (batch, n_kv_heads, pages_per_seq)

    def q_map(b, h, i, lens, tabs):
        return (b, h, 0, 0)

    def kv_map(b, h, i, lens, tabs):
        return (h, tabs[b, i], 0, 0)

    def sc_map(b, h, i, lens, tabs):
        return (h, tabs[b, i], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, group_pad, d), q_map),
        pl.BlockSpec((1, 1, page_size, d), kv_map),
        pl.BlockSpec((1, 1, page_size, d), kv_map),
    ]
    operands = [qg, k_pages, v_pages]
    if quant:
        kernel = _decode_kernel_quant
        # scale planes go in as [heads, pages, 1, page_size]: Mosaic
        # wants a block's last two dims to be (8, 128)-divisible or the
        # whole array dims, and (1, page_size) is the whole of this view
        in_specs += [
            pl.BlockSpec((1, 1, 1, page_size), sc_map),
            pl.BlockSpec((1, 1, 1, page_size), sc_map),
        ]
        operands += [k_scales[:, :, None, :], v_scales[:, :, None, :]]
    else:
        kernel = _decode_kernel

    out = pl_call(
        functools.partial(
            kernel, scale=float(scale), page_size=page_size,
        ),
        name="paged_attention_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, group_pad, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((group_pad, 128), jnp.float32),
                pltpu.VMEM((group_pad, 128), jnp.float32),
                pltpu.VMEM((group_pad, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, n_kv_heads, group_pad, d), q.dtype
        ),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      *operands)

    return out[:, :, :group, :].reshape(batch, n_q_heads, d)


def paged_attention_xla(q, k_pages, v_pages, block_tables, lengths, *,
                        scale=None):
    """Pure-XLA reference of the same contract (gather + masked softmax).
    Used by tests as the numeric oracle and as the fallback when the
    Pallas path is disabled. Accepts the same int8 ``(pages, scales)``
    pairs (dequantized after the gather, before the softmax)."""
    k_pages, k_scales = _split_quant(k_pages)
    v_pages, v_scales = _split_quant(v_pages)
    batch, n_q_heads, d = q.shape
    n_kv_heads, _, page_size, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    group = n_q_heads // n_kv_heads
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # gather logical caches: [batch, n_kv_heads, pages_per_seq*page_size, d]
    k = jnp.swapaxes(k_pages[:, block_tables], 0, 1)
    v = jnp.swapaxes(v_pages[:, block_tables], 0, 1)
    k = k.reshape(batch, n_kv_heads, pages_per_seq * page_size, d)
    v = v.reshape(batch, n_kv_heads, pages_per_seq * page_size, d)
    if k_scales is not None:
        ks = jnp.swapaxes(k_scales[:, block_tables], 0, 1)
        vs = jnp.swapaxes(v_scales[:, block_tables], 0, 1)
        k = k.astype(jnp.float32) * ks.reshape(
            batch, n_kv_heads, -1
        )[..., None]
        v = v.astype(jnp.float32) * vs.reshape(
            batch, n_kv_heads, -1
        )[..., None]

    qg = q.reshape(batch, n_kv_heads, group, d).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(pages_per_seq * page_size)
    s = jnp.where(
        pos[None, None, None, :] < lengths[:, None, None, None], s, NEG_INF
    )
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    # a length-0 sequence has nothing to attend over: the all-masked
    # softmax is uniform garbage, so pin the row to the Pallas kernel's
    # exact-zero contract (serving never reads inactive slots, but the
    # two paths must agree everywhere)
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(batch, n_q_heads, d).astype(q.dtype)


def update_pages(k_pages, v_pages, k_new, v_new, block_tables, lengths):
    """Write one new token per sequence into its current page slot.

    k_new/v_new: [batch, num_kv_heads, head_dim] — the token at position
    ``lengths[b]`` of sequence b. Returns updated (k_pages, v_pages).
    Scatter form (one dynamic_update_slice per batch via vmap-free scatter)
    so it stages inside a jitted decode step. Sequences already at capacity
    (lengths[b] == pages_per_seq * page_size) are NOT written — their
    scatter row is pushed out of bounds so jax drops it — because the
    gather on block_tables would otherwise clamp to the last page and
    silently overwrite live cache slots; the caller owns capacity policy
    (grow the block table or evict), as in the reference's serving loop.

    With int8 ``(pages, scales)`` pairs the token is quantized on write
    (``quantize_tokens``) and its scale lands in the same slot of the
    scale plane; the page write and the scale write share one routing."""
    kq, k_scales = _split_quant(k_pages)
    vq, v_scales = _split_quant(v_pages)
    page_size = kq.shape[2]
    capacity = block_tables.shape[1] * page_size
    logical_page = jnp.minimum(
        lengths // page_size, block_tables.shape[1] - 1
    )
    slot = lengths % page_size
    phys = jnp.take_along_axis(
        block_tables, logical_page[:, None], axis=1
    )[:, 0]  # [batch]
    # at-capacity rows: point at a nonexistent page so the scatter drops
    phys = jnp.where(lengths < capacity, phys, kq.shape[1])

    # scatter indices: for each (batch, kv_head) write [phys, head, slot]
    n_kv = kq.shape[0]
    heads = jnp.arange(n_kv)
    idx = jnp.stack(
        [
            jnp.broadcast_to(heads[None, :], (phys.shape[0], n_kv)),
            jnp.broadcast_to(phys[:, None], (phys.shape[0], n_kv)),
            jnp.broadcast_to(slot[:, None], (phys.shape[0], n_kv)),
        ],
        axis=-1,
    ).reshape(-1, 3)  # [batch*n_kv, 3]
    k_upd = k_new.reshape(-1, k_new.shape[-1])  # batch-major over kv heads
    v_upd = v_new.reshape(-1, v_new.shape[-1])
    if k_scales is None:
        kq = kq.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(
            k_upd.astype(kq.dtype)
        )
        vq = vq.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(
            v_upd.astype(vq.dtype)
        )
        return kq, vq
    k_q8, k_s = quantize_tokens(k_upd)
    v_q8, v_s = quantize_tokens(v_upd)
    kq = kq.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(k_q8)
    vq = vq.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(v_q8)
    k_scales = k_scales.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(k_s)
    v_scales = v_scales.at[idx[:, 0], idx[:, 1], idx[:, 2]].set(v_s)
    return (kq, k_scales), (vq, v_scales)
