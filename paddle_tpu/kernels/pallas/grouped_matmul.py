"""Pallas TPU grouped (ragged) matrix multiply for MoE expert FFNs.

The megablocks-style dropless-MoE contraction (ref: the reference's
fused_moe_kernel.cu grouped cutlass GEMMs; MegaBlocks, MLSys '23): rows
of ``lhs`` are sorted so each expert's tokens form one contiguous
segment, and every expert multiplies ONLY its own segment against its
own weight matrix —

    out[i] = lhs[i] @ rhs[g(i)]      g(i) = the group row i belongs to

with ``group_sizes [num_groups]`` giving the segment lengths in order.
No capacity padding, no one-hot dispatch tensors: the arithmetic is
exactly ``sum(group_sizes) * k * m`` MACs.

Kernel shape: the row dimension is cut into TM-row tiles and the work
list is the (group, tile) overlap staircase — at most
``num_row_tiles + num_groups`` items, computed as scalar-prefetch
metadata INSIDE the traced program (group sizes are data, the grid is
static). Each item multiplies one row tile against one expert's weight
block and accumulates the rows that belong to that expert; consecutive
items share either the tile (an expert boundary inside a tile) or the
expert (a segment spanning tiles), so the f32 scratch accumulator
carries across a tile's items and is stored once per out block.

Quantized experts: ``rhs`` may be int8 with per-expert-per-output-channel
float32 ``rhs_scales [e, m]`` (weight-only absmax quantization); the
kernel dequantizes in-kernel by scaling each expert's contribution —
``(x @ q) * scale`` is algebraically ``x @ (q * scale)`` for per-column
scales, so no dense float copy of the weights ever exists.

Fallback: ``grouped_matmul_xla`` — the same contraction as a pure-XLA
sort/segment program (tile-aligned segment padding + one batched
matmul). ``impl="auto"`` takes it off-TPU and for dtypes the kernel
body does not handle. Both paths are differentiable. The kernel's VJP
is two more kernels over the same staircase: ``grouped_matmul_dlhs``
(``dlhs[i] = g[i] @ rhs[g(i)]^T``, the forward body contracting the
weight block's last dim, so no transposed copy of the weights exists)
and ``grouped_matmul_drhs`` (``drhs[e] = lhs[rows of e]^T @ g[rows of
e]``, accumulated over an expert's row tiles and stored once an expert;
an expert without rows is visited once and stores zeros).

Work: an item whose span holds no row (a padding item past the rows
sent, an empty expert's visit in ``drhs``) skips the MXU; padding items
repeat the last real item's tile and expert, so their blocks' indices do
not move and they fetch and store nothing. A pass sized above the rows
sent costs its empty half one grid step an item, not a product.

Tiles: chosen from the call's shapes (``choose_tiles``): ``tm`` rows
from the rows an expert gets (an expert boundary inside a tile computes
the whole tile for both experts), and the widest column block whose
weight block (in ``drhs`` the float32 accumulator) stays within 8 MiB,
so an expert of 2304 x 896 bf16 is one block and lhs is read once a row
tile. Operands go to the MXU in their own dtype when lhs and rhs share
it (bf16 stays bf16), float32 accumulation always.

Contract: ``sum(group_sizes) <= lhs.shape[0]``; rows beyond the sum
belong to no group and their output rows are unspecified (a held pass
leaves them there). Empty groups are fine (zero-length segments are
skipped by the staircase metadata).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import on_tpu
from ._compat import pl_call, record_gmm_tiles

__all__ = ["grouped_matmul", "grouped_matmul_xla", "choose_tiles",
           "staircase_items"]

KERNELS = ("grouped_matmul", "grouped_matmul_dlhs", "grouped_matmul_drhs")
FWD, DLHS, DRHS = KERNELS
# row tiles, narrowest first
ROW_TILES = (128, 256, 512)
# an expert boundary inside a row tile costs one item more (the tile is
# computed for both experts): a tile of tm rows is taken only where the
# e boundaries' tiles are at most this share of the pass's rows. A grid
# step costs more than its rows: on a v5e, with half of a pass sent, a
# Qwen3-Next layer's twelve calls (32 experts of 2048 x 512, 40,960 rows)
# take 6.90 / 6.78 / 6.07 ms at 128 / 256 / 512 rows, though at 512 the
# boundaries compute 77 % more rows than are sent
BOUNDARY_SHARE = 1 / 2
# _column_block's default: the weight block [k, tn] of one grid step
_WEIGHT_BLOCK_BYTES = 2 * 2**20
# what choose_tiles gives a weight block (in drhs the float32
# accumulator [tk, tn]), and the whole of a grid step's VMEM, double
# buffering counted
_BLOCK_BYTES = 8 * 2**20
VMEM_BUDGET_BYTES = 40 * 2**20
_VMEM_LIMIT_BYTES = 64 * 2**20


def _column_block(width, depth, itemsize, budget=_WEIGHT_BLOCK_BYTES):
    """The widest multiple of 128 dividing ``width`` whose [depth, block]
    weight block fits ``budget`` (an odd width: the whole)."""
    if width % 128:
        return width
    fits = [c for c in range(128, width + 1, 128)
            if width % c == 0 and depth * c * itemsize <= budget]
    return max(fits) if fits else 128


def _vmem_bytes(kernel, tm, tk, tn, itemsize):
    """Estimated VMEM of one grid step of ``kernel`` at these tiles:
    operand and result blocks double-buffered, the float32 accumulator
    and the product beside it; ``drhs`` also holds its two masked
    operands."""
    if kernel == DRHS:
        return (2 * itemsize * (tm * tk + tm * tn + tk * tn)
                + 2 * 4 * tk * tn + itemsize * tm * (tk + tn))
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 2 * 4 * tm * tn


def choose_tiles(n, k, m, e, itemsize, kernel):
    """(tm, tk, tn) of ``kernel`` (one of ``KERNELS``) for ``n`` rows of
    depth ``k`` into width ``m`` over ``e`` experts, operands of
    ``itemsize`` bytes on the MXU. ``tn``: the widest column block whose
    weight block [k, tn] fits ``_BLOCK_BYTES`` (the whole width wherever
    it does, so lhs is read once a row tile); ``tk`` is k, but in
    ``drhs``, where the float32 accumulator [tk, tn] takes that budget.
    ``tm``: the widest of ``ROW_TILES`` whose e boundary tiles stay within
    ``BOUNDARY_SHARE`` of the rows and whose grid step fits
    ``VMEM_BUDGET_BYTES``, else the narrowest."""
    if kernel == DRHS:
        tn = _column_block(m, k, 4, _BLOCK_BYTES)
        tk = _column_block(k, tn, 4, _BLOCK_BYTES)
    else:
        tk, tn = k, _column_block(m, k, itemsize, _BLOCK_BYTES)
    fits = [tm for tm in ROW_TILES
            if e * tm <= BOUNDARY_SHARE * n
            and _vmem_bytes(kernel, tm, tk, tn, itemsize)
            <= VMEM_BUDGET_BYTES]
    return (fits[-1] if fits else ROW_TILES[0]), tk, tn


def _fit_rows(tm, n):
    """A row tile no taller than ``n`` rows need (rounded up to 8)."""
    return max(8, min(tm, -(-n // 8) * 8))


def _tiles(kernel, tm, tn, n, k, m, e, itemsize):
    """The call's tiles: ``choose_tiles``' where ``tm`` / ``tn`` are None
    (``tn`` names the forward kernels' column block only), recorded in
    ``paddle_tpu_kernels_gmm_tiles``. An odd width takes one block."""
    ctm, tk, ctn = choose_tiles(n, k, m, e, itemsize, kernel)
    tm = ctm if tm is None else tm
    if tn is None or kernel == DRHS:
        tn = ctn
    tn = min(tn, m)
    if m % tn:
        tn = m
    tm = _fit_rows(tm, n)
    record_gmm_tiles(kernel, tm, tk, tn)
    return tm, tk, tn


def staircase_items(group_sizes, rows, tm):
    """(active, total) items of the staircase of one pass of ``rows`` rows
    (group sizes [..., e] summing to at most ``rows``; leading dims are
    passes, counted each): an active item holds rows of its group and
    runs the product, the others are the grid's padding (and ``drhs``'
    visits of empty experts). Host arithmetic, as ``_group_metadata``
    counts them."""
    sizes = np.asarray(group_sizes, np.int64)
    end = np.cumsum(sizes, axis=-1)
    start = end - sizes
    tm = _fit_rows(tm, rows)
    spans = np.where(sizes > 0, (end - 1) // tm - start // tm + 1, 0)
    total = -(-rows // tm) + sizes.shape[-1]
    return spans.sum(axis=-1), np.full(spans.shape[:-1], total)


def _group_metadata(group_sizes, num_row_tiles, tm, visit_empty=False):
    """The (group, tile) staircase as four [T] int32 arrays, T =
    num_row_tiles + num_groups (static): per work item its row tile,
    its group, and the [lo, hi) global-row span of that group (lo == hi
    marks an item without rows: the kernels skip its product). Computed
    with XLA ops over [e]-sized arrays — cheap, and legal inside a jit
    (the group sizes are traced data). ``visit_empty`` gives a group
    without rows one item of its own (the drhs kernel stores that
    expert's zeros from it)."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)]
    )
    start, end = offs[:-1], offs[1:]
    first = jnp.minimum(start // tm, num_row_tiles - 1)
    last = jnp.where(sizes > 0, (end - 1) // tm, first)
    count = jnp.where(sizes > 0, last - first + 1, int(visit_empty))
    istart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(count)]
    )  # [e+1]; istart[g] = first work item of group g
    total = istart[-1]
    t = jnp.arange(num_row_tiles + e, dtype=jnp.int32)
    valid = t < total
    # padding items repeat the LAST real item's tile and group with an
    # empty span: their blocks' indices do not move (no fetch, no store),
    # and the final out block is stored at the final grid step
    t = jnp.minimum(t, jnp.maximum(total - 1, 0))
    # largest g with istart[g] <= t: zero-count groups share their
    # successor's start, so side="right" skips them
    g = (
        jnp.searchsorted(istart[:-1], t, side="right").astype(jnp.int32)
        - 1
    )
    tile_id = first[g] + (t - istart[:-1][g])
    lo = jnp.where(valid, start[g], 0)
    hi = jnp.where(valid, end[g], 0)
    return tile_id, g, lo, hi


def _operands(x, w):
    """Both in their own dtype when they share it, else float32."""
    if x.dtype == w.dtype:
        return x, w
    return x.astype(jnp.float32), w.astype(jnp.float32)


def _gmm_kernel(tile_ref, gid_ref, lo_ref, hi_ref, x_ref, w_ref, *rest,
                tm, n_items, quant, transpose_rhs=False):
    """One (group, row tile) item of ``out = lhs @ rhs[g]``: the float32
    [tm, tn] accumulator carries across a tile's items and is stored
    when the next item is another tile's. Int8 ``rhs`` (``quant``) is
    dequantized per output channel on this expert's contribution."""
    s_ref, o_ref, acc_scr = rest if quant else (None, *rest)
    t = pl.program_id(1)
    tile = tile_ref[t]
    prev = tile_ref[jnp.maximum(t - 1, 0)]
    nxt = tile_ref[jnp.minimum(t + 1, n_items - 1)]

    @pl.when((t == 0) | (prev != tile))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(lo_ref[t] < hi_ref[t])
    def _accumulate():
        x, w = _operands(x_ref[...], w_ref[0])  # [tm, k]; [k, tn] or
        contrib = jax.lax.dot_general(          # transposed, [tn, k]
            x, w, (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                       # [tm, tn]
        if quant:
            contrib = contrib * s_ref[0]        # [1, tn] dequant
        row = tile * tm + jax.lax.broadcasted_iota(
            jnp.int32, contrib.shape, 0
        )
        mask = (row >= lo_ref[t]) & (row < hi_ref[t])
        acc_scr[:] += jnp.where(mask, contrib, 0.0)

    @pl.when((t == n_items - 1) | (nxt != tile))
    def _store():
        o_ref[...] = acc_scr[:].astype(o_ref.dtype)


def _row_tiles(lhs, tm):
    """(lhs padded to whole row tiles of ``tm``, their number)."""
    n = lhs.shape[0]
    n_pad = -(-n // tm) * tm
    if n_pad != n:
        lhs = jnp.pad(lhs, ((0, n_pad - n), (0, 0)))
    return lhs, n_pad // tm


def _gmm_pallas_raw(lhs, rhs, group_sizes, rhs_scales, tm, tn,
                    transpose_rhs=False):
    """``transpose_rhs``: rhs is [e, m, k] and each expert multiplies by
    its transpose (the VJP's dlhs, on the forward's own weights)."""
    n, k = lhs.shape
    e, m = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    quant = rhs_scales is not None
    name = DLHS if transpose_rhs else FWD
    itemsize = 4 if lhs.dtype != rhs.dtype else lhs.dtype.itemsize
    tm, _, tn = _tiles(name, tm, tn, n, k, m, e, itemsize)
    lhs, num_row_tiles = _row_tiles(lhs, tm)
    n_pad = num_row_tiles * tm
    num_col_tiles = m // tn
    n_items = num_row_tiles + e
    tile_id, gid, lo, hi = _group_metadata(
        group_sizes, num_row_tiles, tm
    )

    in_specs = [
        pl.BlockSpec((tm, k), lambda j, t, tile, gid, lo, hi: (tile[t], 0)),
        pl.BlockSpec(
            (1, tn, k), lambda j, t, tile, gid, lo, hi: (gid[t], j, 0)
        ) if transpose_rhs else pl.BlockSpec(
            (1, k, tn), lambda j, t, tile, gid, lo, hi: (gid[t], 0, j)
        ),
    ]
    operands = [lhs, rhs]
    if quant:
        # scales go in as [e, 1, m]: a (1, tn) block over [e, m] breaks
        # Mosaic's rule that a block's second-to-last dim is a multiple
        # of 8 or the whole array dim
        in_specs.append(pl.BlockSpec(
            (1, 1, tn), lambda j, t, tile, gid, lo, hi: (gid[t], 0, j)
        ))
        operands.append(rhs_scales.astype(jnp.float32)[:, None, :])

    out = pl_call(
        functools.partial(
            _gmm_kernel, tm=tm, n_items=n_items, quant=quant,
            transpose_rhs=transpose_rhs,
        ),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(num_col_tiles, n_items),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, t, tile, gid, lo, hi: (tile[t], j)
            ),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, m), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(tile_id, gid, lo, hi, *operands)
    return out[:n]


def _drhs_kernel(tile_ref, gid_ref, lo_ref, hi_ref, x_ref, g_ref, o_ref,
                 acc_scr, *, tm, n_items):
    """One (group, row tile) item of ``drhs[e] = lhs_e^T @ g_e``: the
    float32 [tk, tn] accumulator carries across an expert's row tiles
    and is stored when the next item is another expert's. Rows outside
    the item's span are zeroed in both operands (rows past the last
    group hold anything, and 0 x NaN is NaN)."""
    t = pl.program_id(2)
    gid = gid_ref[t]
    prev = gid_ref[jnp.maximum(t - 1, 0)]
    nxt = gid_ref[jnp.minimum(t + 1, n_items - 1)]

    @pl.when((t == 0) | (prev != gid))
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(lo_ref[t] < hi_ref[t])
    def _accumulate():
        row = tile_ref[t] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mask = (row >= lo_ref[t]) & (row < hi_ref[t])
        x, g = _operands(x_ref[...], g_ref[...])
        x = jnp.where(mask, x, jnp.zeros_like(x))
        g = jnp.where(mask, g, jnp.zeros_like(g))
        acc_scr[:] += jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                       # [tk, tn]

    @pl.when((t == n_items - 1) | (nxt != gid))
    def _store():
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def _drhs_pallas(lhs, g, group_sizes, num_groups, tm, out_dtype):
    """[e, k, m]: every expert's ``lhs_e^T @ g_e`` over the staircase."""
    n, k = lhs.shape
    m = g.shape[1]
    itemsize = 4 if lhs.dtype != g.dtype else lhs.dtype.itemsize
    tm, tk, tn = _tiles(DRHS, tm, None, n, k, m, num_groups, itemsize)
    lhs, num_row_tiles = _row_tiles(lhs, tm)
    g = _row_tiles(g, tm)[0]
    n_items = num_row_tiles + num_groups
    tile_id, gid, lo, hi = _group_metadata(
        group_sizes, num_row_tiles, tm, visit_empty=True)
    return pl_call(
        functools.partial(_drhs_kernel, tm=tm, n_items=n_items),
        name=DRHS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, m // tn, n_items),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, t, tile, gid, lo, hi:
                             (tile[t], i)),
                pl.BlockSpec((tm, tn), lambda i, j, t, tile, gid, lo, hi:
                             (tile[t], j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda i, j, t, tile, gid, lo, hi:
                (gid[t], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, k, m), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(tile_id, gid, lo, hi, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm_pallas(lhs, rhs, group_sizes, tm, tn):
    return _gmm_pallas_raw(lhs, rhs, group_sizes, None, tm, tn)


def _gmm_pallas_fwd(lhs, rhs, group_sizes, tm, tn):
    return _gmm_pallas(lhs, rhs, group_sizes, tm, tn), (
        lhs, rhs, group_sizes,
    )


def _gmm_pallas_bwd(tm, tn, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    dlhs = _gmm_pallas_raw(g, rhs, group_sizes, None, tm, None,
                           transpose_rhs=True)
    drhs = _drhs_pallas(lhs, g, group_sizes, rhs.shape[0], tm, rhs.dtype)
    # integer primal -> symbolic-zero (float0) tangent
    zero_gs = np.zeros(group_sizes.shape, jax.dtypes.float0)
    return dlhs, drhs, zero_gs


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def grouped_matmul_xla(lhs, rhs, group_sizes, rhs_scales=None, *,
                       tm=128):
    """The pure-XLA sort/segment fallback: pad every group's segment up
    to a tile boundary (the aligned form of the kernel's staircase —
    at most ``e`` extra tiles), run ONE batched matmul of row tiles
    against per-tile gathered expert weights, and gather the live rows
    back. No masking pass, no output scatter-add, so XLA executes it at
    plain batched-einsum speed — measured at parity with the
    capacity-padded dense einsum on CPU, unlike ``jax.lax.ragged_dot``
    (~3-6x slower there). Differentiable by construction (scatter /
    batched matmul / gather).

    Int8 expert weights dequantize as a per-tile column scale on the
    matmul output — algebraically identical to the kernel's in-kernel
    dequant, still never materializing dense float weights."""
    n, k = lhs.shape
    e, _, m = rhs.shape
    gs = group_sizes.astype(jnp.int32)
    tm = max(8, min(tm, -(-max(n, 1) // 8) * 8))
    num_tiles = -(-n // tm) + e            # static tile bound
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(gs)]
    )
    # per-group padded tile start (tile units), aligned so no tile
    # spans two groups
    gtiles = -(-gs // tm)                  # cdiv
    tstart = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(gtiles)]
    )  # [e+1]
    # group of each sorted row, and its padded destination row
    i = jnp.arange(n, dtype=jnp.int32)
    gi = (
        jnp.searchsorted(offs, i, side="right").astype(jnp.int32) - 1
    )
    ppos = tstart[gi] * tm + (i - offs[gi])
    x_pad = jnp.zeros((num_tiles * tm, k), lhs.dtype).at[ppos].set(lhs)
    # expert of each tile (empty groups share their successor's start;
    # side="right" skips them); tiles past the padded total are dead —
    # their rows are zero and nothing gathers them back
    t = jnp.arange(num_tiles, dtype=jnp.int32)
    gid = jnp.clip(
        jnp.searchsorted(tstart[:-1], t, side="right").astype(
            jnp.int32
        ) - 1,
        0, e - 1,
    )
    y = jnp.einsum(
        "tik,tkm->tim",
        x_pad.reshape(num_tiles, tm, k),
        rhs[gid],
        preferred_element_type=jnp.float32,
    )
    if rhs_scales is not None:
        y = y * rhs_scales.astype(jnp.float32)[gid][:, None, :]
    return y.reshape(num_tiles * tm, m)[ppos].astype(lhs.dtype)


def _kernel_dtypes(lhs, rhs):
    """The dtypes the kernel body handles. Shapes are not restricted:
    every block is (8, 128)-divisible or spans its whole array dim."""
    return (lhs.dtype in (jnp.float32, jnp.bfloat16)
            and rhs.dtype in (jnp.float32, jnp.bfloat16, jnp.int8))


def grouped_matmul(lhs, rhs, group_sizes, *, rhs_scales=None,
                   impl="auto", tm=None, tn=None):
    """Ragged grouped GEMM: ``out[i] = lhs[i] @ rhs[g(i)]``.

    lhs: [n, k] rows sorted by group; rhs: [e, k, m] stacked expert
    weights (optionally int8 with ``rhs_scales [e, m]``); group_sizes:
    [e] int32 summing to n. Returns [n, m] in ``lhs.dtype`` (f32
    accumulation on every path).

    impl:
      * ``"auto"`` — the Pallas kernel on TPU (FLAGS_use_pallas_kernels)
        for the dtypes its body handles, the XLA fallback elsewhere. A
        kernel this route selects and Mosaic refuses raises from the
        compile; nothing degrades silently.
      * ``"pallas"`` — always the kernel (interpreter off-TPU): the
        parity-testing path.
      * ``"xla"`` — always the fallback.

    The float path is differentiable (the kernel's custom VJP is the
    ``grouped_matmul_dlhs`` and ``grouped_matmul_drhs`` kernels; the
    fallback by construction); the int8 path is inference-only.

    ``tm`` / ``tn``: the kernels' row tile and the forward's column
    block; None (the default) takes each kernel's ``choose_tiles``.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'grouped_matmul impl must be "auto", "pallas" or "xla", '
            f"got {impl!r}"
        )
    if impl == "auto":
        from ...core import flags

        use_pallas = (on_tpu()
                      and flags.get_flag("FLAGS_use_pallas_kernels")
                      and _kernel_dtypes(lhs, rhs))
        impl = "pallas" if use_pallas else "xla"
    if impl == "xla":
        return grouped_matmul_xla(lhs, rhs, group_sizes, rhs_scales)
    if rhs_scales is not None:
        # int8 weights: inference-only, no VJP wrapper
        return _gmm_pallas_raw(
            lhs, rhs, group_sizes.astype(jnp.int32), rhs_scales, tm, tn
        )
    return _gmm_pallas(
        lhs, rhs, group_sizes.astype(jnp.int32), tm, tn
    )
