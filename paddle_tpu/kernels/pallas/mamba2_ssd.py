"""Pallas TPU kernels for Mamba-2's selective state-space scan in its
chunked (state-space duality, SSD) form.

The mixer of the Mamba-2 layers of granite-4.0-h (Dao & Gu, "Transformers
are SSMs", ICML 2024; HF ``modeling_granitemoehybrid.py``
``GraniteMoeHybridMambaLayer.torch_forward``). Per head ``j``, with a
float32 state ``H_0 = 0`` of shape ``[N, P]`` (``N`` the state size, ``P``
the head size)::

    H_t = exp(dt_t A_j) H_{t-1} + dt_t B_t (x) x_t
    y_t = C_t^T H_t + D_j x_t

``dt_t > 0`` is the step after its softplus, ``A_j < 0``; ``B_t`` and
``C_t`` [N] are shared by the heads of a group (``n_groups`` groups of
consecutive heads). The decay is a *scalar* a head and token: no
triangular inverse as the delta rule has, but a decay mask that is each
head's own.

Chunked form, chunk ``Q`` (256 or a divisor): with ``s`` the running sum
of ``dt A`` inside a chunk (float32, <= 0, falling) and ``H`` the state
the chunk starts from,

    L[t, i] = exp(s_t - s_i)                    i <= t, else 0
    Y       = ((C B^T) * L) (dt x) + exp(s) (C H) + D x
    H_next  = exp(s_Q) H + B^T (exp(s_Q - s) dt x)

``s_t - s_i`` is taken before the exponential and masked above the
diagonal: ``exp(s_t) * exp(-s_i)`` overflows float32 once a chunk's decay
passes e^88, which ``A = -64`` does in two tokens. Matmul operands are in
x's dtype with float32 accumulation; ``s``, every decay, the state and
every accumulator are float32.

Kernels, both over the grid (batch, chunks, groups of heads), chunks and
head groups sequential. A grid step holds one chunk of ``heads`` heads:
x, y and their cotangents are read and written in place as ``[B, T, H P]``
blocks of ``heads * P`` lanes; nothing is reshaped to ``[B, T, H, P]``.
``C B^T`` is one product a chunk and B/C group, made by the group's first
step and kept in VMEM scratch for the others; the states of all heads live
in VMEM scratch across a sequence's chunks. Heads narrower than a lane
tile (P 64: half of one) are worked ``128 / P`` side by side: their
``C H``, ``B^T (...)`` and every elementwise pass take whole tiles, and a
head's own ``[Q, Q]`` product takes the pair's ``[Q, 128]`` operand with
the other head's lanes zeroed (the MXU is 128 wide either way). The
per-head vectors come in two small float32 arrays made outside: ``cols``
``[B, T, 2 H]`` (``s`` and ``dt``, a token a sublane; a head's column is
picked by a lane select and sum, not by an unaligned lane slice) and
``rows`` ``[B, T / Q, H, Q]`` (``s``, a token a lane).

``mamba2_ssd_fwd`` walks the chunks in order; for the backward it also
writes the state each chunk starts from. ``mamba2_ssd_bwd`` walks them
from the last to the first with the states' cotangent in scratch and is
written by hand. ``s`` enters the mask as ``s_t - s_i``, so with ``G = dM
* M`` its cotangent takes ``sum_i G[t, i]`` at ``t`` and ``-sum_t G[t,
i]`` at ``i``: both sums of **one** float32 ``[Q, Q]`` tile below the
diagonal, the first leaving as a column of ``dcols``, the second as a row
of ``drows`` (XLA adds the two: both arrays were made from one ``s``). The
sums can be had cheaper, as row sums of ``[Q, P]`` products the kernel has
anyway (``dY_t . (M xs)_t`` and ``xs_i . (M^T dY)_i``), but then a
token's own entry ``G[t, t]`` no longer cancels exactly: it passes two
different bf16 roundings, and for a head whose decay leaves e^-80 of a
neighbour that residue is all there is (on the chip ``dA`` was off by 1.3
of its size so; PERF.md, PR 31). ``dB`` and ``dC`` add up over the heads in
float32 in their output blocks, ``d(C B^T)`` in scratch until the group's
last step.

``chunked_mamba2_ssd`` is the same mathematics in ``jax.numpy`` (a
``lax.scan`` over the chunks, differentiated by ``jax``): the ``"xla"``
path. ``recurrent_mamba2_ssd`` is the recurrence as written, token by
token: what decides right and wrong in the tests.

Off the TPU the kernels run under the Pallas interpreter
(``impl="pallas"``); ``impl="auto"`` takes the ``jax.numpy`` form there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import device
from ._compat import pl_call, record_ssd_blocks, record_ssd_chunk
from .flash_attention import (
    _DEFAULT_SCOPE_BYTES, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES)

__all__ = ["mamba2_ssd", "chunked_mamba2_ssd", "recurrent_mamba2_ssd",
           "choose_tile"]

DEFAULT_CHUNK = 256
# the most heads a grid step holds: their [Q, heads * P] blocks are one
# DMA each, and the step's code is unrolled over them
MAX_HEADS = 16
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32


def _mm(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


def _pad_time(x, pad):
    if not pad:
        return x
    return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))


# ------------------------------------------------ the jax.numpy forms
def recurrent_mamba2_ssd(x, dt, a, b, c, d, *, n_groups=1, block=64):
    """The recurrence token by token (``lax.scan`` over t, checkpointed in
    blocks of ``block`` tokens), float32 throughout at ``HIGHEST``: the
    yardstick. Operands as ``mamba2_ssd``; returns y [B, T, H P] float32."""
    bsz, t, hp = x.shape
    h = dt.shape[-1]
    p, n = hp // h, b.shape[-1] // n_groups
    hi = jax.lax.Precision.HIGHEST
    rep = h // n_groups
    xs = (x.astype(_F32).reshape(bsz, t, h, p), dt.astype(_F32),
          jnp.repeat(b.astype(_F32).reshape(bsz, t, n_groups, n), rep, 2),
          jnp.repeat(c.astype(_F32).reshape(bsz, t, n_groups, n), rep, 2))
    a, d = a.astype(_F32), d.astype(_F32)

    def token(state, inp):
        xt, dtt, bt, ct = inp                    # [B, H, P], [B, H], [B, H, N]
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * bt)[..., :, None] * xt[..., None, :])
        y = jnp.einsum("bhn,bhnp->bhp", ct, state, precision=hi)
        return state, y + d[:, None] * xt

    @jax.checkpoint
    def tokens(state, inp):
        return jax.lax.scan(token, state, inp)

    pad = (-t) % block                            # dt 0: writes nothing
    by_block = tuple(
        jnp.moveaxis(_pad_time(v, pad), 1, 0).reshape(
            ((t + pad) // block, block) + v.shape[:1] + v.shape[2:])
        for v in xs)
    _, y = jax.lax.scan(
        tokens, jnp.zeros((bsz, h, n, p), _F32), by_block)
    y = jnp.moveaxis(y.reshape((t + pad, bsz, h * p)), 0, 1)
    return y[:, :t]


def chunked_mamba2_ssd(x, dt, a, b, c, d, chunk=DEFAULT_CHUNK, *,
                       n_groups=1):
    """The chunked form in ``jax.numpy``, differentiable by ``jax``: a
    ``lax.scan`` over the chunks carrying the states [B, H, N, P]. Matmul
    operands in x's dtype, accumulation, decays and state in float32.
    Returns y [B, T, H P] in x's dtype."""
    bsz, t, hp = x.shape
    h = dt.shape[-1]
    p, n = hp // h, b.shape[-1] // n_groups
    rep, op = h // n_groups, x.dtype
    q = min(chunk, t)
    pad = (-t) % q
    nc = (t + pad) // q
    af, df = a.astype(_F32), d.astype(_F32)
    tri = jnp.tril(jnp.ones((q, q), bool))

    def by_chunk(v, *tail):                       # -> [NC, B, Q, ...]
        v = _pad_time(v, pad).reshape((bsz, nc, q) + tail)
        return jnp.moveaxis(v, 1, 0)

    @jax.checkpoint
    def one_chunk(state, inp):
        xc, dtc, bc, cc = inp        # [B,Q,H,P] [B,Q,H] [B,Q,G,N] [B,Q,G,N]
        xf = xc.astype(_F32)
        s = jnp.cumsum(dtc * af, axis=1)                       # [B, Q, H]
        seg = s[:, :, None, :] - s[:, None, :, :]              # [B, t, i, H]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("btgn,bign->bgti", cc, bc,
                        preferred_element_type=_F32)
        m = (jnp.repeat(cb, rep, axis=1)
             * jnp.moveaxis(decay, 3, 1)).astype(op)           # [B, H, t, i]
        xs = dtc[..., None] * xf
        y = jnp.einsum("bhti,bihp->bthp", m, xs.astype(op),
                       preferred_element_type=_F32)
        ch = jnp.einsum("bthn,bhnp->bthp", jnp.repeat(cc, rep, axis=2),
                        state.astype(op), preferred_element_type=_F32)
        y = y + jnp.exp(s)[..., None] * ch + df[:, None] * xf
        last = s[:, -1:, :]
        fed = (jnp.exp(last - s)[..., None] * xs).astype(op)
        state = (jnp.exp(last)[:, 0, :, None, None] * state
                 + jnp.einsum("bihn,bihp->bhnp", jnp.repeat(bc, rep, axis=2),
                              fed, preferred_element_type=_F32))
        return state, y

    _, y = jax.lax.scan(
        one_chunk, jnp.zeros((bsz, h, n, p), _F32),
        (by_chunk(x, h, p), by_chunk(dt.astype(_F32), h),
         by_chunk(b, n_groups, n), by_chunk(c, n_groups, n)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, t + pad, hp)
    return y[:, :t].astype(x.dtype)


# ----------------------------------------------------------- the kernels
def _pack(heads, p):
    """Heads worked side by side in the lanes: as many as fit a tile."""
    if p >= 128:
        return 1
    return max(k for k in range(1, heads + 1)
               if heads % k == 0 and k * p <= 128)


def _vmem_bytes(heads, h, p, n, q, itemsize):
    """Estimated VMEM of one grid step of the backward kernel, the larger
    of the two: every block double-buffered, the scratch, and what is
    alive inside a step."""
    lanes = lambda width: -(-width // 128) * 128
    w = _pack(heads, p) * p
    blocks = (3 * q * lanes(heads * p) * itemsize       # x, dy, dx
              + 2 * q * lanes(n) * (itemsize + 4)       # B, C, dB, dC
              + 2 * q * lanes(2 * h) * 4                # cols, dcols
              + -(-heads // 8) * 8 * lanes(q) * 4       # rows
              + heads * p // w * n * lanes(w) * 4       # states
              + 8 * lanes(heads * p) * 4 * 2)           # D, dD
    scratch = 2 * q * lanes(q) * 4 + h * p // w * n * lanes(w) * 4
    alive = 8 * q * lanes(q) * 4 + 16 * q * lanes(w) * 4
    return 2 * blocks + scratch + alive


def choose_tile(t, h, p, n, n_groups, chunk, dtype):
    """(heads, chunk) of one grid step of both kernels, from the call's
    shapes and dtype alone. Chunk: ``chunk`` (a sequence shorter than it:
    the whole sequence, to a multiple of 8), halved while no choice of
    heads fits. Heads: a divisor of a group's heads that gives Mosaic
    whole 128-lane column blocks of x and whole sublane tiles of ``rows``
    (or is all the heads), at most ``MAX_HEADS``, the most that fit
    ``VMEM_BUDGET_BYTES`` by ``_vmem_bytes``' estimate. Where none fits,
    the fewest heads at the smallest chunk on the same kernels."""
    itemsize = jnp.dtype(dtype).itemsize
    per_group = h // n_groups
    q = min(chunk, -(-t // 8) * 8)
    legal = [k for k in range(1, per_group + 1)
             if per_group % k == 0
             and (k == h or (k * p % 128 == 0 and k % 8 == 0))]
    legal = legal or [per_group]
    while True:
        fits = [k for k in legal if k <= MAX_HEADS
                and _vmem_bytes(k, h, p, n, q, itemsize)
                <= VMEM_BUDGET_BYTES]
        if fits:
            return fits[-1], q
        if q % 2 or q // 2 < 8:
            return legal[0], q
        q //= 2


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(cols, index):
    """Column ``index`` (a traced scalar) of a [Q, lanes] float32 tile as
    [Q, 1]: a lane select and a lane sum, where a slice of one lane is no
    aligned load."""
    return jnp.sum(jnp.where(_iota(cols.shape, 1) == index, cols, 0.0),
                   axis=1, keepdims=True)


def _spread(values, p):
    """One [rows, 1] a head of a pack -> [rows, W]: head k's value over
    its p lanes."""
    shape = (values[0].shape[0], len(values) * p)
    lane = _iota(shape, 1)
    out = values[-1]
    for k in range(len(values) - 2, -1, -1):
        out = jnp.where(lane < (k + 1) * p, values[k], out)
    return jnp.broadcast_to(out, shape)


def _only(v, k, p, pack):
    """v [rows, W] with the lanes of every head but the pack's k-th
    zeroed."""
    if pack == 1:
        return v
    lane = _iota(v.shape, 1)
    return jnp.where(
        jnp.logical_and(lane >= k * p, lane < (k + 1) * p), v, 0.0)


def _decay_mask(tri, s_col, s_row):
    """L[t, i] = exp(s_t - s_i) on and below the diagonal, 0 above it
    (the difference is positive there and would overflow)."""
    return jnp.exp(jnp.where(tri, s_col - s_row, -jnp.inf))


def _pack_operands(x_ref, cols, g, tile, pk):
    """What both kernels take of one pack of a grid step: its heads and
    lanes, x in float32, each head's ``s`` column, and ``s``, ``dt`` and
    ``dt x`` spread over the pack's lanes."""
    heads, pack, p, _ = tile
    h = cols.shape[1] // 2
    mine = range(pk * pack, (pk + 1) * pack)
    lanes = slice(pk * pack * p, (pk + 1) * pack * p)
    xf = x_ref[0, :, lanes].astype(_F32)
    s_cols = [_column(cols, g * heads + j) for j in mine]
    dt2 = _spread([_column(cols, h + g * heads + j) for j in mine], p)
    return mine, lanes, xf, s_cols, _spread(s_cols, p), dt2, dt2 * xf


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, y_ref,
                *rest, tile):
    """One chunk of ``heads`` heads. The grid's last two axes walk the
    chunks and the head groups; ``h_scr`` carries every head's state
    across the chunks, ``cb_scr`` the chunk's ``C B^T`` across the head
    groups of a B/C group. With one more ref the state each chunk starts
    from is written there (what the backward kernel reads)."""
    heads, pack, p, per_group = tile
    states_ref, cb_scr, h_scr = rest if len(rest) == 3 else (None,) + rest
    ci, g = pl.program_id(1), pl.program_id(2)
    q, op = x_ref.shape[1], x_ref.dtype

    @pl.when(ci == 0)
    def _start():
        h_scr[g] = jnp.zeros(h_scr.shape[1:], _F32)

    @pl.when(g % per_group == 0)
    def _shared():
        cb_scr[...] = _mm(c_ref[0], b_ref[0], _NT)

    cb = cb_scr[...]
    tri = _iota((q, q), 0) >= _iota((q, q), 1)
    cols = cols_ref[0]
    for pk in range(heads // pack):
        mine, lanes, xf, s_cols, s2, _, xs = _pack_operands(
            x_ref, cols, g, tile, pk)
        y = d_ref[:, lanes] * xf
        for k, j in enumerate(mine):
            m = cb * _decay_mask(tri, s_cols[k], rows_ref[0, 0, j:j + 1, :])
            y = y + _mm(m.astype(op), _only(xs, k, p, pack).astype(op),
                        _NN)
        state = h_scr[g, pk]
        if states_ref is not None:
            states_ref[0, 0, pk] = state
        y = y + jnp.exp(s2) * _mm(c_ref[0], state.astype(op), _NN)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        last = s2[q - 1:q, :]
        h_scr[g, pk] = jnp.exp(last) * state + _mm(
            b_ref[0], (jnp.exp(last - s2) * xs).astype(op), _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, states_ref,
                dy_ref, dx_ref, db_ref, dc_ref, dcols_ref, drows_ref, dd_ref,
                cb_scr, dcb_scr, dh_scr, *, tile):
    """The same blocks with the chunks from the last to the first (the
    index maps turn the walk round), the states' cotangent in ``dh_scr``.
    ``dB`` and ``dC`` add up in their float32 output blocks over the head
    groups of a B/C group, ``d(C B^T)`` in ``dcb_scr`` until the group's
    last step; the columns of ``dcols`` (the cotangents of ``s`` and
    ``dt``) add up over all head groups of a chunk, each head's row of
    ``drows`` (the mask's cotangent at ``s_i``) is its own."""
    heads, pack, p, per_group = tile
    ci, g = pl.program_id(1), pl.program_id(2)
    q, op = x_ref.shape[1], x_ref.dtype
    h = cols_ref.shape[2] // 2

    @pl.when(ci == 0)
    def _start():
        dh_scr[g] = jnp.zeros(dh_scr.shape[1:], _F32)

    @pl.when(g % per_group == 0)
    def _shared():
        cb_scr[...] = _mm(c_ref[0], b_ref[0], _NT)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    @pl.when(g == 0)
    def _columns():
        dcols_ref[...] = jnp.zeros_like(dcols_ref)

    cb, b, c = cb_scr[...], b_ref[0], c_ref[0]
    tri = _iota((q, q), 0) >= _iota((q, q), 1)
    strictly = _iota((q, q), 0) > _iota((q, q), 1)
    last_row = _iota((q, 1), 0) == q - 1
    cols = cols_ref[0]
    col_lane = _iota(cols.shape, 1)
    dcols = jnp.zeros(cols.shape, _F32)
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    for pk in range(heads // pack):
        mine, lanes, xf, s_cols, s2, dt2, xs = _pack_operands(
            x_ref, cols, g, tile, pk)
        dyf = dy_ref[0, :, lanes].astype(_F32)
        xs_op = xs.astype(op)
        ds_cols = []
        state = states_ref[0, 0, pk]
        state_op = state.astype(op)
        dnext = dh_scr[g, pk]
        dnext_op = dnext.astype(op)
        last = s2[q - 1:q, :]
        e2, tail, carried = jnp.exp(s2), jnp.exp(last - s2), jnp.exp(last)
        # Y = M xs + exp(s) (C H) + D x;  H' = exp(s_Q) H + B^T (tail xs)
        ch = _mm(c, state_op, _NN)
        edy = e2 * dyf
        edy_op = edy.astype(op)
        dc = dc + _mm(edy_op, state_op, _NT)
        z = _mm(b, dnext_op, _NN)
        fed = tail * xs
        db = db + _mm(fed.astype(op), dnext_op, _NT)
        dh_scr[g, pk] = carried * dnext + _mm(c, edy_op, _TN)
        dxs = tail * z
        for k, j in enumerate(mine):
            decay = _decay_mask(tri, s_cols[k], rows_ref[0, 0, j:j + 1, :])
            dyk = _only(dyf, k, p, pack).astype(op)
            dxs = dxs + _mm((cb * decay).astype(op), dyk, _TN)
            dl = _mm(dyk, xs_op, _NT) * decay
            dcb = dcb + dl
            # s enters the mask as s_t - s_i: G[t, i] = dM M goes to s_t
            # and, negated, to s_i. Both sums are taken of one float32
            # tile, below the diagonal only: a token's own entry cancels
            # exactly, and it is orders larger than what a fast-decaying
            # head's neighbours leave (two roundings of it would not)
            below = jnp.where(strictly, dl * cb, 0.0)
            ds_cols.append(jnp.sum(below, axis=1, keepdims=True))
            drows_ref[0, 0, j:j + 1, :] = -jnp.sum(below, axis=0,
                                                   keepdims=True)
        # the rest of s's cotangent is a row sum of [Q, W] tiles: exp(s) (C
        # H) and the tail exp(s_Q - s); s_Q's own (the tail's and the
        # carried decay's) goes to the chunk's last row
        u = dxs * xf
        fed_z = fed * z
        r = edy * ch - fed_z
        extra = (jnp.sum(fed_z, axis=0, keepdims=True)
                 + carried * jnp.sum(dnext * state, axis=0, keepdims=True))
        for k, j in enumerate(mine):
            ds = ds_cols[k] + jnp.sum(
                _only(r, k, p, pack), axis=1, keepdims=True)
            ds = ds + jnp.where(last_row, jnp.sum(
                _only(extra, k, p, pack), axis=1, keepdims=True), 0.0)
            ddt = jnp.sum(_only(u, k, p, pack), axis=1, keepdims=True)
            dcols = jnp.where(col_lane == g * heads + j, ds, dcols)
            dcols = jnp.where(col_lane == h + g * heads + j, ddt, dcols)
        dx_ref[0, :, lanes] = (dt2 * dxs + d_ref[:, lanes] * dyf).astype(
            dx_ref.dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
    dcols_ref[0] += dcols
    db_ref[0] += db
    dc_ref[0] += dc
    dcb_scr[...] += dcb

    @pl.when(g % per_group == per_group - 1)
    def _shared_cotangent():
        dcb_op = dcb_scr[...].astype(op)
        dc_ref[0] += _mm(dcb_op, b, _NN)
        db_ref[0] += _mm(dcb_op, c, _TN)


def _call(kernel, name, tile, dims, walk, in_names, out_names, out_shape,
          scratch, operands):
    """One of the two kernels over the grid (batch, chunks, head groups);
    ``walk`` maps the grid's chunk index to the chunk of the sequence."""
    heads, pack, p, per_group = tile
    bsz, tp, h, n, q = dims
    w = pack * p

    sp = {
        "x": pl.BlockSpec((1, q, heads * p),
                          lambda i, j, g: (i, walk(j), g)),
        "bc": pl.BlockSpec((1, q, n),
                           lambda i, j, g: (i, walk(j), g // per_group)),
        "cols": pl.BlockSpec((1, q, 2 * h), lambda i, j, g: (i, walk(j), 0)),
        "rows": pl.BlockSpec((1, 1, heads, q),
                             lambda i, j, g: (i, walk(j), g, 0)),
        "d": pl.BlockSpec((1, heads * p), lambda i, j, g: (0, g)),
        "states": pl.BlockSpec((1, 1, heads // pack, n, w),
                               lambda i, j, g: (i, walk(j), g, 0, 0)),
        "dd": pl.BlockSpec((1, 1, 1, heads * p),
                           lambda i, j, g: (i, walk(j), 0, g)),
    }
    chunks = tp // q
    record_ssd_blocks(name, heads, chunks)
    need = _vmem_bytes(heads, h, p, n, q, operands[0].dtype.itemsize)
    square = pltpu.VMEM((q, q), _F32)
    carried = pltpu.VMEM((h // heads, heads // pack, n, w), _F32)
    return pl_call(
        functools.partial(kernel, tile=tile), name=name,
        grid=(bsz, chunks, h // heads),
        in_specs=[sp[k] for k in in_names],
        out_specs=[sp[k] for k in out_names], out_shape=out_shape,
        scratch_shapes=[square] * scratch + [carried],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=(
                VMEM_LIMIT_BYTES if need > _DEFAULT_SCOPE_BYTES // 2
                else None)),
    )(*operands)


def _dims(x, bm, cols, rows, tile):
    """(B, T, H, N, Q) of a call in the kernels' layouts."""
    heads, _, _, per_group = tile
    h, q = cols.shape[2] // 2, rows.shape[3]
    n_groups = h // (heads * per_group)
    return x.shape[0], x.shape[1], h, bm.shape[2] // n_groups, q


_INPUTS = ["x", "bc", "bc", "cols", "rows", "d"]


def _fwd_call(x, bm, cm, cols, rows, dspread, tile, with_states):
    dims = bsz, tp, h, n, q = _dims(x, bm, cols, rows, tile)
    heads, pack, p, _ = tile
    out_names = ["x"]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if with_states:
        out_names.append("states")
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, tp // q, h // pack, n, pack * p), _F32))
    return _call(_fwd_kernel, "mamba2_ssd_fwd", tile, dims, lambda j: j,
                 _INPUTS, out_names, out_shape, 1,
                 (x, bm, cm, cols, rows, dspread))


def _bwd_call(x, bm, cm, cols, rows, dspread, states, dy, tile):
    dims = bsz, tp, h, n, q = _dims(x, bm, cols, rows, tile)
    last = tp // q - 1
    like = lambda v, dtype=None: jax.ShapeDtypeStruct(
        v.shape, dtype or v.dtype)
    return _call(
        _bwd_kernel, "mamba2_ssd_bwd", tile, dims, lambda j: last - j,
        _INPUTS + ["states", "x"], ["x", "bc", "bc", "cols", "rows", "dd"],
        [like(x), like(bm, _F32), like(cm, _F32), like(cols), like(rows),
         jax.ShapeDtypeStruct((bsz, tp // q, 1, x.shape[2]), _F32)], 2,
        (x, bm, cm, cols, rows, dspread, states, dy))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_core(x, bm, cm, cols, rows, dspread, tile):
    """The kernels' own layouts: x [B, T, H P], bm and cm [B, T, G N] with
    T a multiple of the chunk Q; cols [B, T, 2 H] float32 (the running sum
    s of dt A inside each chunk, then dt); rows [B, T / Q, H, Q] float32
    (the same s, a token a lane); dspread [1, H P] float32 (D over each
    head's lanes); ``tile`` = (heads a step, heads side by side in the
    lanes, P, steps a B/C group). Returns y like x. ``rows`` has to hold
    the ``s`` of ``cols``: the backward hands ``cols`` the cotangent of
    both."""
    return _fwd_call(x, bm, cm, cols, rows, dspread, tile, False)[0]


def _ssd_core_fwd(x, bm, cm, cols, rows, dspread, tile):
    y, states = _fwd_call(x, bm, cm, cols, rows, dspread, tile, True)
    return y, (x, bm, cm, cols, rows, dspread, states)


def _ssd_core_bwd(tile, res, dy):
    x, bm, cm, cols, rows, dspread, states = res
    dx, db, dc, dcols, drows, dd = _bwd_call(*res, dy, tile)
    return (dx, db.astype(bm.dtype), dc.astype(cm.dtype), dcols, drows,
            jnp.sum(dd, axis=(0, 1)))


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def _ssd_pallas(x, dt, a, bm, cm, d, chunk, n_groups):
    """``_ssd_core`` of operands of any length: the grid step chosen, the
    tail padded with tokens that write nothing (dt 0), the per-head
    vectors laid out by chunk."""
    bsz, t, hp = x.shape
    h = dt.shape[-1]
    p, n = hp // h, bm.shape[-1] // n_groups
    heads, q = choose_tile(t, h, p, n, n_groups, chunk, x.dtype)
    record_ssd_chunk(q, p, n, n_groups)
    pad = (-t) % q
    x, dt, bm, cm = (_pad_time(v, pad) for v in (x, dt, bm, cm))
    tp = t + pad
    dtf = dt.astype(_F32)
    s = jnp.cumsum(
        (dtf * a.astype(_F32)).reshape(bsz, tp // q, q, h), axis=2)
    cols = jnp.concatenate([s.reshape(bsz, tp, h), dtf], axis=-1)
    tile = (heads, _pack(heads, p), p, h // n_groups // heads)
    y = _ssd_core(x, bm, cm, cols, jnp.moveaxis(s, 2, 3),
                  jnp.repeat(d.astype(_F32), p)[None, :], tile)
    return y[:, :t]


def mamba2_ssd(x, dt, a, b, c, d, chunk=DEFAULT_CHUNK, *, n_groups=1,
               impl="auto"):
    """y of Mamba-2's selective scan, in x's dtype and form.

    x [B, T, H P], a head a block of P consecutive columns (what the
    kernels read in place); dt [B, T, H] after its softplus (> 0); a [H]
    (< 0: ``-exp(A_log)``); b and c [B, T, G N] with ``n_groups`` = G
    groups of H / G consecutive heads; d [H]. ``H`` is dt's last axis.
    Any length: the tail is padded with tokens that write nothing (dt 0).
    ``chunk`` is the most tokens of a chunk (the configuration's
    ``mamba_chunk_size``); the kernels may take a divisor
    (``choose_tile``).

    impl: ``"auto"`` is the kernels on a TPU (FLAGS_use_pallas_kernels)
    and the ``jax.numpy`` chunked form elsewhere; ``"pallas"`` is always
    the kernels (the interpreter off the TPU); ``"xla"`` always the
    ``jax.numpy`` form. All three are differentiable in all six
    operands."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'mamba2_ssd impl must be "auto", "pallas" or "xla", got '
            f"{impl!r}")
    h = dt.shape[-1]
    if x.ndim != 3 or x.shape[-1] % h:
        raise ValueError(
            f"mamba2_ssd: x {x.shape} is not [B, T, H * P] for dt's "
            f"{h} heads")
    if h % n_groups or b.shape[-1] % n_groups or b.shape != c.shape:
        raise ValueError(
            f"mamba2_ssd: {h} heads, b {b.shape} and c {c.shape} do not "
            f"divide into {n_groups} groups")
    if impl == "auto":
        from ...core import flags

        impl = "pallas" if (
            device.on_tpu()
            and flags.get_flag("FLAGS_use_pallas_kernels")
        ) else "xla"
    if impl == "xla":
        return chunked_mamba2_ssd(x, dt, a, b, c, d, chunk,
                                  n_groups=n_groups)
    return _ssd_pallas(x, dt, a, b, c, d, chunk, n_groups)
