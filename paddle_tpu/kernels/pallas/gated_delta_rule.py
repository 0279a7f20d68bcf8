"""Pallas TPU kernel for the chunked gated delta rule (Gated DeltaNet).

The linear-attention mixer of Qwen3-Next (Yang et al., "Gated Delta
Networks", ICLR 2025; HF ``modeling_qwen3_next.py``
``torch_chunk_gated_delta_rule``). Per value head, with a float32 state
``S_0 = 0`` of shape ``[d_k, d_v]``::

    S'  = exp(g_t) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is the log of the gate, ``beta_t`` the write strength. The
caller normalises and scales q and k. Each key head serves
``H_v / H_k`` consecutive value heads.

Chunked (WY) form, chunk ``C`` (64): with ``G`` the running sum of ``g``
inside a chunk and ``S`` the state the chunk starts from,

    A[t, i] = beta_t exp(G_t - G_i) (k_t . k_i)       i < t, else 0
    T       = (I + A)^-1                              unit lower triangular
    U       = T (beta v) - T (beta exp(G) k) S
    O       = (exp(G) q) S + ((q k^T) * exp(G_t - G_i) [i <= t]) U
    S_next  = exp(G_C) S + (exp(G_C - G) k)^T U

Everything is matmuls with bf16 operands and float32 accumulation; the
state, the gates and every accumulator are float32. ``T`` is handed on in
the operands' dtype. It is made by doubling the diagonal blocks
(``_inv_unit_lower_blocks``: X - X L X, ``2 log2(C) - 2`` products of
C x C float32 matrices, no row-by-row substitution): in the kernels each
product in three bf16 passes where the result is rounded to bf16 anyway,
in ``chunked_gated_delta_rule`` at ``HIGHEST``.

Kernels, both over the grid (batch, groups of key heads, blocks of
chunks) with the last axis sequential; ``choose_tile`` sizes a step from
the call's shapes against a VMEM budget. A step holds ``key_heads`` key
heads with all their value heads on a leading batch axis of every
product, so the heads' dependent chains interleave on the MXU and ``k
k^T``, ``q k^T`` are taken once a key head; the chunks of a step are a
``fori_loop``. ``gated_delta_rule_fwd`` walks a sequence's chunks in
order, the states in VMEM scratch; it reads q, k, v in place as ``[B, T,
H * d]`` (a head is a 128-lane column block, so nothing is transposed or
repeated in HBM; ``gated_delta_rule`` takes operands in that form as they
are and reshapes ``[B, T, H, d]`` ones to it), and for the backward it
also writes the state each chunk starts from and the chunk's ``T``.
``gated_delta_rule_bwd`` walks
the chunks from the last to the first with the states' cotangent in VMEM
scratch; a chunk's vector-Jacobian product is ``jax.vjp`` of the
forward's own chunk functions, taken while the kernel is traced, with the
forward's ``T`` in the inverse's place (no second inverse); ``dq`` and
``dk`` of a key head's value heads add up in float32 inside it.
``chunked_gated_delta_rule`` is the same mathematics in ``jax.numpy``
(the intra-chunk part for all chunks at once, a ``lax.scan`` over the
chunk states): the ``"xla"`` path and what the kernels are tested against.

Off the TPU the kernel runs under the Pallas interpreter
(``impl="pallas"``); ``impl="auto"`` takes the ``jax.numpy`` form there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import device
from ._compat import pl_call, record_gdr_blocks, record_gdr_operands
# one chip, one VMEM: the budget a step is sized against, the limit asked
# of Mosaic above half its default scope
from .flash_attention import (
    _DEFAULT_SCOPE_BYTES, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES)

__all__ = ["gated_delta_rule", "chunked_gated_delta_rule", "choose_tile"]

DEFAULT_CHUNK = 64
# the most chunks a grid step walks: a [256, 128] block a head moves four
# times fewer, larger DMAs than a [64, 128] one
MAX_CHUNKS = 4
# the most value heads a grid step holds (a key head's all the same): on a
# v5e 4 / 8 / 16 of 128 take 11.0 / 7.9 / 6.2 ms forward at 4 x 8192 x 32
# heads; 32 do not fit the budget
MAX_VALUE_HEADS = 16
_HI = jax.lax.Precision.HIGHEST
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


# ------------------------------------------------------- the mathematics
def _mm(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _split(x):
    """float32 -> (head, tail) in bf16 with head + tail = x to 2^-16."""
    head = x.astype(jnp.bfloat16)
    return head, (x - head.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm_split(a, b):
    """a b of two float32 matrices given as ``_split`` pairs, in three
    single-pass products of bf16 operands with float32 accumulation (head
    head + head tail + tail head): relative error ~2^-16, where
    ``HIGHEST`` takes six passes for 2^-24."""
    (a_head, a_tail), (b_head, b_tail) = a, b
    return _mm(a_head, b_head, _NN) + (
        _mm(a_head, b_tail, _NN) + _mm(a_tail, b_head, _NN))


def _doubled_blocks(a, row, col, operand, product):
    """``_inv_unit_lower_blocks``' doublings for ``a`` [..., C, lanes] with
    the row and column of each lane's entry and the products given."""
    c = a.shape[-2]
    # blocks of 2: [[1, 0], [-a, 1]]
    inv = (row == col).astype(jnp.float32) - jnp.where(
        row // 2 == col // 2, a, 0.0)
    size = 2
    while size < c:
        below = jnp.where(
            (row // (2 * size) == col // (2 * size))
            & (row // size > col // size), a, 0.0)
        x = operand(inv)
        inv = inv - product(x, operand(product(operand(below), x)))
        size *= 2
    return inv


def _inv_unit_lower_blocks(a, split=False):
    """(I + a)^-1 for a strictly lower triangular [C, C] float32 ``a`` by
    doubling the diagonal blocks: with X the inverses of the blocks of
    size s and L the part of ``a`` below them inside the blocks of 2 s,
    ``[[X1, 0], [-X2 L X1, X2]] = X - X L X``. Two products a doubling,
    ten at C 64, as the nilpotent series ``(I - a)(I + a^2)(I + a^4)...``
    would take; every factor is an inverse of a block of ``I + a`` or a
    part of ``a``, where the series' powers grow like binomials before
    they cancel (unit k rows 0.5 apart: the series at ``HIGHEST`` is off
    by 1e8 of an entry, this by 1e-7).
    ``split``: the products by ``_mm_split`` (three bf16 passes, 2e-5)
    and not at ``HIGHEST`` (six), for a result that is rounded to bf16
    (2^-9) on the next line."""
    c = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    if split:
        return _doubled_blocks(a, row, col, _split, _mm_split)
    return _doubled_blocks(
        a, row, col, lambda x: x,
        functools.partial(_mm, dims=_NN, precision=_HI))


def _inv_unit_lower_pairs(a, split=False):
    """``_inv_unit_lower_blocks`` of an even batch [H, C, C], two matrices
    side by side in the lanes ([H / 2, C, 2 C]: at C 64 a whole 128-lane
    tile, so the elementwise work and the splits touch half the vregs).
    A product takes the right operands of a pair as the diagonal blocks
    of one [2 C, 2 C] matrix: [P1 | P2] diag(Q1, Q2) = [P1 Q1 | P2 Q2],
    one pass of full tiles where two of quarter tiles stood."""
    h, c, _ = a.shape
    a = a.reshape(h // 2, 2, c, c)
    a = jnp.concatenate([a[:, 0], a[:, 1]], axis=-1)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    col = jnp.where(lane >= c, lane - c, lane)
    on_diagonal = (
        (jax.lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 0) >= c)
        == (jax.lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 1) >= c))

    def diagonal(x):
        return jnp.where(on_diagonal, jnp.concatenate([x, x], axis=-2),
                         jnp.zeros((), x.dtype))

    if split:
        operand = _split
        product = jax.vmap(lambda p, q: _mm_split(
            p, jax.tree_util.tree_map(diagonal, q)))
    else:
        operand = lambda x: x
        product = jax.vmap(lambda p, q: _mm(p, diagonal(q), _NN, _HI))
    inv = _doubled_blocks(a, row, col, operand, product)
    return jnp.stack([inv[..., :c], inv[..., c:]], axis=1).reshape(h, c, c)


def _inv_heads(split):
    """The kernels' inverse of one head's chunk; under ``jax.vmap`` over
    an even number of heads it is ``_inv_unit_lower_pairs`` of them all
    where two fit the lanes."""
    single = functools.partial(_inv_unit_lower_blocks, split=split)
    inverse = jax.custom_batching.custom_vmap(single)

    @inverse.def_vmap
    def _(axis_size, in_batched, a):
        if axis_size % 2 == 0 and 2 * a.shape[-1] <= 128:
            return _inv_unit_lower_pairs(a, split), True
        return jax.vmap(single)(a), True

    return inverse


@jax.custom_vjp
def _inv_unit_lower(a):
    return _inv_unit_lower_blocks(a)


def _inv_fwd(a):
    inv = _inv_unit_lower_blocks(a)
    return inv, inv


def _inv_bwd(inv, d_inv):
    # d(I + a)^-1 = -(I + a)^-1 da (I + a)^-1
    return (-_mm(_mm(inv, d_inv, ((0,), (0,)), _HI), inv,
                 ((1,), (1,)), _HI),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


@jax.custom_vjp
def _inv_saved(a, t):
    """(I + a)^-1 where the forward kernel has left it: ``t``, in the
    operands' dtype. The backward kernel's inverse; its cotangent needs
    only ``t``."""
    return t


def _inv_saved_fwd(a, t):
    return t, t


def _inv_saved_bwd(t, d_t):
    # d(I + a)^-1 = -(I + a)^-1 da (I + a)^-1; d_t comes in t's dtype
    if t.dtype == jnp.bfloat16:
        left = _split(_mm(t, d_t, _TN))
        d_a = -(_mm(left[0], t, _NT) + _mm(left[1], t, _NT))
    else:
        d_a = -_mm(_mm(t, d_t, _TN, _HI), t, _NT, _HI)
    return d_a, jnp.zeros_like(t)


_inv_saved.defvjp(_inv_saved_fwd, _inv_saved_bwd)


def _chunk_products(q, k):
    """(k k^T, q k^T) of a chunk of one key head, [C, C] float32: the same
    for every value head it serves."""
    return _mm(k, k, _NT), _mm(q, k, _NT)


def _chunk_parts(q, k, v, grow, brow, inverse, op=None, products=None,
                 with_inverse=False):
    """What one chunk needs that does not depend on the state. q, k
    [C, d_k] and v [C, d_v]; ``op`` is the dtype of the matmuls' operands
    (q's own unless given: the backward kernel hands float32 copies over
    so that their cotangents add up in float32); grow [1, C] the
    running sum of g inside the chunk, brow [1, C] beta (rows: a [C, 1]
    column would be padded to 128 lanes in HBM; the columns are made
    here, through the diagonal). Returns (w_v [C, d_v] f32, w_k [C, d_k],
    q_g [C, d_k], attn [C, C], k_d [C, d_k] in the operands' dtype, decay
    [1, d_v] f32). ``products``: ``_chunk_products`` of q and k in the
    operands' dtype, where the caller shares them between value heads.
    ``with_inverse``: returns (those, the inverse [C, C] in the operands'
    dtype)."""
    c = q.shape[0]
    op = op or q.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def column(r):
        return jnp.sum(jnp.where(row == col, r, 0.0), 1, keepdims=True)

    gcol, bcol = column(grow), column(brow)
    # exp(G_t - G_i) on and below the diagonal, 0 above it (the
    # difference is positive there and would overflow)
    decay = jnp.exp(jnp.where(row >= col, gcol - grow, -jnp.inf))
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    q, k = q.astype(op), k.astype(op)
    kk, qk = products if products is not None else _chunk_products(q, k)
    a = jnp.where(row > col, bcol * decay * kk, 0.0)
    t = inverse(a).astype(op)
    e_g = jnp.exp(gcol)
    w_v = _mm(t, (bcol * vf).astype(op), ((1,), (0,)))
    w_k = _mm(t, (bcol * e_g * kf).astype(op), ((1,), (0,))).astype(op)
    q_g = (e_g * q.astype(jnp.float32)).astype(op)
    attn = (decay * qk).astype(op)
    g_last = gcol[c - 1:c, :]
    k_d = (jnp.exp(g_last - gcol) * kf).astype(op)
    # [1, d_v], not [1, 1]: Mosaic broadcasts along lanes, then sublanes.
    # The column is widened before its last row is cut out: the cotangent
    # of a widened [1, 1] passes, with a batch of heads in front, through
    # a rank-1 array, which Mosaic refuses
    decay_last = jax.lax.broadcast_in_dim(
        e_g, (c, v.shape[1]), (0, 1))[c - 1:c, :]
    parts = w_v, w_k, q_g, attn, k_d, decay_last
    return (parts, t) if with_inverse else parts


def _chunk_apply(parts, state):
    """One chunk from the state it starts with: (o [C, d_v] f32, the
    next state [d_k, d_v] f32)."""
    w_v, w_k, q_g, attn, k_d, decay = parts
    op = w_k.dtype
    s = state.astype(op)
    u = w_v - _mm(w_k, s, ((1,), (0,)))
    u_op = u.astype(op)
    o = _mm(q_g, s, ((1,), (0,))) + _mm(attn, u_op, ((1,), (0,)))
    return o, decay * state + _mm(k_d, u_op, ((0,), (0,)))


def _pad_time(x, pad):
    if not pad:
        return x
    return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))


def _chunk_sums(g, chunk):
    """[B, T, H] -> running sums inside each chunk, [B, H, T/C, C] f32."""
    b, t, h = g.shape
    gc = jnp.moveaxis(g.astype(jnp.float32), 1, 2).reshape(
        b, h, t // chunk, chunk)
    return jnp.cumsum(gc, -1)


def chunked_gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK):
    """The chunked form in ``jax.numpy``, differentiable by ``jax``: what
    the kernel computes, and what its backward differentiates.
    q, k [B, T, H_k, d_k]; v [B, T, H_v, d_v]; g, beta [B, T, H_v].
    Returns o [B, T, H_v, d_v] in v's dtype. Matmul operands are in q's
    dtype, accumulation, gates and state in float32."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep = hv // hk
    pad = (-t) % chunk
    q, k, v, g, beta = (_pad_time(x, pad) for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk

    def heads_first(x, h):                       # -> [B, h, NC, C, d]
        return jnp.moveaxis(x, 1, 2).reshape(b, h, nc, chunk, x.shape[-1])

    qc = jnp.repeat(heads_first(q, hk), rep, axis=1)
    kc = jnp.repeat(heads_first(k, hk), rep, axis=1)
    vc = heads_first(v, hv)
    gsum = _chunk_sums(g, chunk)
    brow = jnp.moveaxis(beta.astype(jnp.float32), 1, 2).reshape(
        b, hv, nc, 1, chunk)
    parts_fn = functools.partial(_chunk_parts, inverse=_inv_unit_lower)
    for _ in range(3):                           # over B, H_v, NC
        parts_fn = jax.vmap(parts_fn)
    parts = parts_fn(qc, kc, vc, gsum[..., None, :], brow)
    apply_fn = jax.vmap(jax.vmap(_chunk_apply))  # over B, H_v

    @jax.checkpoint
    def step(state, chunk_parts):
        o, state = apply_fn(chunk_parts, state)
        return state, o

    by_chunk = jax.tree_util.tree_map(
        lambda x: jnp.moveaxis(x, 2, 0), parts)
    _, o = jax.lax.scan(
        step, jnp.zeros((b, hv, dk, dv), jnp.float32), by_chunk)
    o = jnp.moveaxis(o, 0, 2).reshape(b, hv, t + pad, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t].astype(v.dtype)


# ----------------------------------------------------------- the kernels
def _vmem_bytes(key_heads, rep, dk, dv, chunk, chunks, itemsize):
    """Estimated VMEM of one grid step of the backward kernel, the larger
    of the two: every block double-buffered, the state's cotangent, and
    what is alive inside a chunk."""
    heads, block = key_heads * rep, chunk * chunks
    lanes = lambda width: -(-width // 128) * 128
    # q, k, dq, dk by key head; v, do, dv by value head
    sequences = block * itemsize * (
        4 * lanes(key_heads * dk) + 3 * lanes(heads * dv))
    rows = 4 * chunks * -(-heads // 8) * 8 * lanes(chunk) * 4
    states = chunks * heads * dk * lanes(dv) * 4
    inverses = chunks * heads * chunk * lanes(chunk) * itemsize
    scratch = heads * dk * lanes(dv) * 4
    # a value head's float32 [C, C] and [C, d] tiles, the state's copies
    alive = heads * 4 * (8 * chunk * lanes(chunk)
                         + 8 * chunk * lanes(max(dk, dv))
                         + 3 * dk * lanes(dv))
    return 2 * (sequences + rows + states + inverses) + scratch + alive


def choose_tile(t, hk, rep, dk, dv, chunk, dtype):
    """(key heads, chunks) of one grid step of both kernels, from the
    call's shapes and dtype alone. Chunks: ``MAX_CHUNKS`` or the whole
    sequence. Key heads: a divisor of ``hk`` that gives Mosaic whole
    128-lane column blocks (or is all the heads) and fits
    ``VMEM_BUDGET_BYTES`` by ``_vmem_bytes``' estimate; of those the most
    that keep the step's value heads at ``MAX_VALUE_HEADS``, else the
    fewest. Where none fits, fewer chunks, and at last one key head and
    one chunk on the same kernels."""
    itemsize = jnp.dtype(dtype).itemsize
    chunks = min(MAX_CHUNKS, -(-t // chunk))
    while chunks:
        fits = [
            n for n in range(1, hk + 1)
            if hk % n == 0
            and (n == hk or (n * dk % 128 == 0 and n * rep * dv % 128 == 0))
            and _vmem_bytes(n, rep, dk, dv, chunk, chunks, itemsize)
            <= VMEM_BUDGET_BYTES]
        if fits:
            paying = [n for n in fits if n * rep <= MAX_VALUE_HEADS]
            return (paying[-1] if paying else fits[0]), chunks
        chunks //= 2
    return 1, 1


def _load_heads(ref, rows, count, dtype=None):
    """[count, C, d]: the heads of a [1, block, count * d] block, each a
    column block of its own."""
    width = ref.shape[2] // count
    heads = [ref[0, rows, h * width:(h + 1) * width] for h in range(count)]
    return jnp.stack(heads).astype(dtype or ref.dtype)


def _store_heads(ref, rows, x):
    width = ref.shape[2] // x.shape[0]
    for h in range(x.shape[0]):
        ref[0, rows, h * width:(h + 1) * width] = x[h].astype(ref.dtype)


def _load_rows(ref, c):
    """[heads, 1, C]: a chunk's rows of a [1, chunks, 1, heads, C] block."""
    return jnp.stack(
        [ref[0, c, 0, h:h + 1, :] for h in range(ref.shape[3])])


def _store_rows(ref, c, x):
    for h in range(x.shape[0]):
        ref[0, c, 0, h:h + 1, :] = x[h]


def _heads_parts(q, k, v, grow, brow, rep, op, inverse=None, saved=None):
    """``_chunk_parts`` of one chunk for all of a grid step's heads at once:
    q, k [n, C, d_k] of its key heads; v [n * rep, C, d_v] and grow, brow
    [n * rep, 1, C] of their value heads. The heads' chains are independent
    and ride a leading batch axis, so their products interleave on the MXU;
    ``k k^T`` and ``q k^T`` are taken once a key head. With ``saved``
    [n * rep, C, C] the inverses are the forward kernel's (the backward
    kernel: no series); without, ``inverse`` makes them and they are
    returned beside the parts."""
    shared = jax.vmap(_chunk_products)(q.astype(op), k.astype(op))
    if rep > 1:
        q, k, shared = jax.tree_util.tree_map(
            lambda x: jnp.repeat(x, rep, axis=0), (q, k, shared))
    def one_head(q, k, v, g, b, kk_qk, t=None):
        if t is None:
            return _chunk_parts(q, k, v, g, b, inverse, op, kk_qk,
                                with_inverse=True)
        return _chunk_parts(q, k, v, g, b, lambda a: _inv_saved(a, t), op,
                            kk_qk)

    heads = (q, k, v, grow, brow, shared)
    return jax.vmap(one_head)(*heads + (() if saved is None else (saved,)))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, tile,
                chunk):
    """``chunks`` chunks of ``key_heads`` key heads with their value heads;
    the grid's last axis walks the sequence and carries the states in
    ``s_scr``. With two more refs, the state each chunk starts from and
    the chunk's inverse are written there (what the backward kernel
    reads)."""
    key_heads, rep, chunks = tile
    states_ref, t_ref, s_scr = rest if len(rest) == 3 else (None, None,
                                                             rest[0])
    op = q_ref.dtype
    inverse = _inv_heads(split=op == jnp.bfloat16)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    def walk(c, _):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        state = s_scr[...]
        parts, t = _heads_parts(
            _load_heads(q_ref, rows, key_heads),
            _load_heads(k_ref, rows, key_heads),
            _load_heads(v_ref, rows, key_heads * rep),
            _load_rows(g_ref, c), _load_rows(b_ref, c), rep, op, inverse)
        if states_ref is not None:
            states_ref[0, c] = state
            t_ref[0, c] = t
        o, state = jax.vmap(_chunk_apply)(parts, state)
        _store_heads(o_ref, rows, o)
        s_scr[...] = state

    jax.lax.fori_loop(0, chunks, walk, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, tile,
                chunk):
    """The same blocks from the last to the first, the states' cotangent
    carried in ``ds_scr``. A chunk's vector-Jacobian product is taken by
    ``jax.vjp`` of the forward's own functions while the kernel is traced,
    so the two cannot drift apart; the inverse is the one the forward
    wrote, and ``dq``, ``dk`` of a key head's value heads add up in
    float32 inside the product rule."""
    key_heads, rep, chunks = tile
    op = q_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def walk(i, _):
        c = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        saved = t_ref[0, c]

        def chunk_fn(q, k, v, grow, brow, state):
            parts = _heads_parts(q, k, v, grow, brow, rep, op, saved=saved)
            return jax.vmap(_chunk_apply)(parts, state)

        _, vjp = jax.vjp(
            chunk_fn, _load_heads(q_ref, rows, key_heads, f32),
            _load_heads(k_ref, rows, key_heads, f32),
            _load_heads(v_ref, rows, key_heads * rep, f32),
            _load_rows(g_ref, c), _load_rows(b_ref, c), states_ref[0, c])
        dq, dk, dv, dg, db, d_state = vjp(
            (_load_heads(do_ref, rows, key_heads * rep, f32), ds_scr[...]))
        _store_heads(dq_ref, rows, dq)
        _store_heads(dk_ref, rows, dk)
        _store_heads(dv_ref, rows, dv)
        _store_rows(dg_ref, c, dg)
        _store_rows(db_ref, c, db)
        ds_scr[...] = d_state

    jax.lax.fori_loop(0, chunks, walk, None)


def _record_chunk(chunk, dk, dv):
    from ...observability import counter

    counter(
        "paddle_tpu_kernels_gdr_chunk",
        "Traced gated-delta-rule kernel calls by chunk and head sizes",
        labelnames=("chunk", "d_k", "d_v"),
    ).inc(chunk=chunk, d_k=dk, d_v=dv)


def _call(kernel, name, tile, dims, walk, in_names, out_names, out_shape,
          operands):
    """One of the two kernels over the grid (batch, groups of key heads,
    blocks of chunks); ``walk`` maps the grid's last index to the block of
    the sequence."""
    key_heads, rep, chunks = tile
    b, tp, hk, dk, dv, chunk = dims
    heads, block = key_heads * rep, chunk * chunks

    def sequence(width):
        return pl.BlockSpec((1, block, width),
                            lambda i, h, j: (i, walk(j), h))

    def by_chunk(*tail):
        return pl.BlockSpec((1, chunks) + tail,
                            lambda i, h, j: (i, walk(j), h, 0, 0))

    sp = {"qk": sequence(key_heads * dk), "v": sequence(heads * dv),
          "rows": by_chunk(1, heads, chunk),
          "states": by_chunk(heads, dk, dv),
          "inverses": by_chunk(heads, chunk, chunk)}
    record_gdr_blocks(name, key_heads, chunks)
    need = _vmem_bytes(key_heads, rep, dk, dv, chunk, chunks,
                       operands[0].dtype.itemsize)
    return pl_call(
        functools.partial(kernel, tile=tile, chunk=chunk), name=name,
        grid=(b, hk // key_heads, tp // block),
        in_specs=[sp[n] for n in in_names],
        out_specs=[sp[n] for n in out_names], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(
                VMEM_LIMIT_BYTES if need > _DEFAULT_SCOPE_BYTES // 2
                else None)),
    )(*operands)


def _dims(qf, vf, grow, tile):
    """(B, T, H_k, d_k, d_v, C) of a call in the kernels' layouts."""
    key_heads, rep, _ = tile
    b, tp, _ = vf.shape
    hv, chunk = grow.shape[2] * grow.shape[3], grow.shape[4]
    return b, tp, hv // rep, qf.shape[2] * rep // hv, vf.shape[2] // hv, chunk


def _fwd_call(qf, kf, vf, grow, brow, tile, with_states):
    dims = b, tp, hk, dk, dv, chunk = _dims(qf, vf, grow, tile)
    hv, nc = hk * tile[1], tp // chunk
    _record_chunk(chunk, dk, dv)
    out_names = ["v"]
    out_shape = [jax.ShapeDtypeStruct(vf.shape, vf.dtype)]
    if with_states:
        out_names += ["states", "inverses"]
        out_shape += [
            jax.ShapeDtypeStruct((b, nc, hv, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, hv, chunk, chunk), qf.dtype)]
    return _call(_fwd_kernel, "gated_delta_rule_fwd", tile, dims,
                 lambda j: j, ["qk", "qk", "v", "rows", "rows"], out_names,
                 out_shape, (qf, kf, vf, grow, brow))


def _bwd_call(qf, kf, vf, grow, brow, states, inverses, do, tile):
    dims = _, tp, _, _, _, chunk = _dims(qf, vf, grow, tile)
    last = tp // (chunk * tile[2]) - 1
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return _call(_bwd_kernel, "gated_delta_rule_bwd", tile, dims,
                 lambda j: last - j,
                 ["qk", "qk", "v", "rows", "rows", "states", "inverses",
                  "v"],
                 ["qk", "qk", "v", "rows", "rows"],
                 [like(qf), like(kf), like(vf), like(grow), like(brow)],
                 (qf, kf, vf, grow, brow, states, inverses, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdr_core(qf, kf, vf, grow, brow, tile):
    """The kernels' own layouts: qf, kf [B, T, H_k * d_k] and vf
    [B, T, H_v * d_v] with T a multiple of the block; grow, brow
    [B, T / C, H_v / heads, heads, C] float32 (running sums of g inside a
    chunk, beta; ``heads`` the value heads of a grid step); ``tile`` =
    (key heads a step, value heads a key head, chunks a step). Returns o
    like vf."""
    return _fwd_call(qf, kf, vf, grow, brow, tile, False)[0]


def _gdr_core_fwd(qf, kf, vf, grow, brow, tile):
    o, states, inverses = _fwd_call(qf, kf, vf, grow, brow, tile, True)
    return o, (qf, kf, vf, grow, brow, states, inverses)


def _gdr_core_bwd(tile, res, do):
    return _bwd_call(*res, do, tile)


_gdr_core.defvjp(_gdr_core_fwd, _gdr_core_bwd)


def _gdr_pallas(qf, kf, vf, g, beta, chunk, hk):
    """``_gdr_core`` of flat operands of any length: the grid step chosen,
    the tail padded, the gates laid out by chunk."""
    b, t, _ = qf.shape
    hv = g.shape[2]
    rep, dk, dv = hv // hk, qf.shape[2] // hk, vf.shape[2] // hv
    key_heads, chunks = choose_tile(t, hk, rep, dk, dv, chunk, qf.dtype)
    pad = (-t) % (chunk * chunks)
    qf, kf, vf, g, beta = (_pad_time(x, pad) for x in (qf, kf, vf, g, beta))
    tp = t + pad

    def rows(x):                 # [B, T, H_v] -> [B, NC, groups, heads, C]
        x = x.astype(jnp.float32).reshape(
            b, tp // chunk, chunk, hk // key_heads, key_heads * rep)
        return jnp.moveaxis(x, 2, 4)

    o = _gdr_core(qf, kf, vf, jnp.cumsum(rows(g), -1), rows(beta),
                  (key_heads, rep, chunks))
    return o[:, :t]


def _heads(x, count):
    return x.reshape(x.shape[:2] + (count, x.shape[2] // count))


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


def gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK, *,
                     impl="auto", num_k_heads=None):
    """o of the gated delta rule, in v's dtype and v's form.

    q, k, already normalised and scaled, and v come in one of two forms,
    told apart by their rank. **Heads**: q, k [B, T, H_k, d_k] and v
    [B, T, H_v, d_v]. **Flat**, with ``num_k_heads`` given: q, k
    [B, T, H_k * d_k] and v [B, T, H_v * d_v], a head a block of
    consecutive columns: what the kernels read in place, where the heads
    form is reshaped to it and o back (on a TPU a copy of each: the tiles
    differ). ``H_v``, a multiple of ``H_k``, is the last axis of g (log
    gate, <= 0) and beta, both [B, T, H_v]. Any length: the tail is
    padded with tokens that write nothing (beta 0, g 0).

    impl: ``"auto"`` is the kernel on a TPU (FLAGS_use_pallas_kernels)
    and the ``jax.numpy`` chunked form elsewhere (which takes the heads
    form, so there the flat form is the one reshaped); ``"pallas"`` is
    always the kernel (the interpreter off the TPU); ``"xla"`` always the
    ``jax.numpy`` form. All three are differentiable."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'gated_delta_rule impl must be "auto", "pallas" or "xla", '
            f"got {impl!r}")
    flat = q.ndim == 3
    if flat and num_k_heads is None:
        raise ValueError(
            "gated_delta_rule: q, k, v as [B, T, H * d] need num_k_heads")
    hk, hv = (num_k_heads if flat else q.shape[2]), g.shape[-1]
    if hv % hk:
        raise ValueError(
            f"gated_delta_rule: {hv} value heads over {hk} key heads")
    record_gdr_operands("flat" if flat else "heads")
    if impl == "auto":
        from ...core import flags

        impl = "pallas" if (
            device.on_tpu()
            and flags.get_flag("FLAGS_use_pallas_kernels")
        ) else "xla"
    if impl == "xla":
        if flat:
            q, k, v = _heads(q, hk), _heads(k, hk), _heads(v, hv)
        o = chunked_gated_delta_rule(q, k, v, g, beta, chunk)
        return _flat(o) if flat else o
    if flat:
        return _gdr_pallas(q, k, v, g, beta, chunk, hk)
    return _gdr_pallas(_flat(q), _flat(k), _flat(v), g, beta, chunk,
                       hk).reshape(v.shape)
