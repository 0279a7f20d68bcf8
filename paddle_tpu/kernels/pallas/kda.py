"""Pallas TPU kernels for Kimi Delta Attention (KDA): the delta rule with a
decay per key channel.

ref: Kimi Linear (arXiv 2510.26692), and the ``bailing_hybrid`` config of
inclusionAI/Ling-3.0-flash (``kda_lower_bound`` -5, ``kda_safe_gate``).
Per head, with a float32 state ``S_0 = 0`` of shape ``[d_k, d_v]``::

    S'  = Diag(exp(g_t)) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

``g_t`` [d_k] is a log-decay per key channel in ``[LOWER_BOUND, 0]``;
``beta_t`` the write strength. The caller normalises and scales q and k.
Gated DeltaNet (``gated_delta_rule.py``) is the case of one ``g`` a head.

Chunked (WY) form, chunk ``C`` (64): with ``G`` [C, d_k] the running sum of
``g`` inside a chunk and ``S`` the state the chunk starts from,

    A[t, i]    = beta_t (k_t * exp(G_t - G_i)) . k_i          i < t, else 0
    Attn[t, i] = (q_t * exp(G_t - G_i)) . k_i                 i <= t
    T          = (I + A)^-1                                   unit lower
    U          = T (beta v) - T (beta exp(G) * k) S
    O          = (exp(G) * q) S + Attn U
    S_next     = Diag(exp(G_C)) S + (k * exp(G_C - G))^T U

The decay sits inside the inner products of ``A`` and ``Attn``, so they
are not one product of k with k. Each is taken by row blocks of ``SUB``
(16) rows, each block referred to its own first row ``r``::

    (k_t * exp(G_t - G_r)) . (k_i * exp(G_r - G_i))

For ``i < r`` both exponents are at most 0. For ``i`` inside the block
(the diagonal sub-chunk) the right one is at most ``-LOWER_BOUND * (SUB -
1)`` = 75, finite in float32 and bf16 (exp(88.7) is their largest), where
referring a whole chunk to one row would reach exp(315). Columns past the
block are masked before the exponent is taken. The diagonal of ``Attn``
(decay exp(0)) is ``q_t . k_t`` taken apart: as the blocks' two factors
its gradients would cancel, and with bf16 operands at the floor what is
left of them is larger than the true ``dg``. What is left is products
of bf16 operands with float32 accumulation as in the scalar-decay kernels:
the state, the gates and every accumulator are float32. ``T`` is
``gated_delta_rule``'s inverse by doubling of the diagonal blocks.

Kernels, both over the grid (batch, groups of heads, blocks of chunks) with
the last axis sequential and the state in VMEM scratch. They read q, k, v
in place as ``[B, T, H * d]`` (a head a 128-lane column block) and ``G`` in
the same layout in float32 (the running sums inside each chunk are taken
outside, so the kernels' cotangent of ``G`` turns into ``dg``, a reverse
running sum per channel, by ``jax``). ``kda_fwd`` walks a sequence's
chunks in order and, for the backward, writes the state each chunk starts
from and the chunk's ``T``; ``kda_bwd`` walks them from the last to the
first with the states' cotangent in VMEM scratch, a chunk's
vector-Jacobian product taken by ``jax.vjp`` of the forward's own chunk
function with the forward's ``T``. ``chunked_kda`` is the same
mathematics in ``jax.numpy`` with the decay taken whole, ``[C, C, d_k]`` a
chunk (no blocks, no reference rows): the ``"xla"`` path and what the
kernels are tested against.

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
from ._compat import pl_call, record_kda_blocks
from .flash_attention import (
    _DEFAULT_SCOPE_BYTES, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES)
from .gated_delta_rule import (
    _HI, _NN, _NT, MAX_CHUNKS, MAX_VALUE_HEADS, _inv_heads, _inv_saved,
    _inv_unit_lower, _load_heads, _load_rows, _mm, _pad_time, _store_heads,
    _store_rows)

__all__ = ["kda", "chunked_kda", "choose_tile", "LOWER_BOUND", "SUB"]

DEFAULT_CHUNK = 64
# the log-decay's floor that the kernels' sub-chunks rely on
LOWER_BOUND = -5.0
# rows a block of A and Attn is referred to its first row over: 15 steps
# at the floor are exp(75)
SUB = 16


# ------------------------------------------------------- the mathematics
def _column(r):
    """[1, n] -> [n, 1] through the diagonal (no transpose)."""
    n = r.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(row == col, r, 0.0), 1, keepdims=True)


def _decayed_products(q, k, gsum, op):
    """(k k^T, q k^T) with the decay inside, [C, C] float32: entry (t, i)
    is ``(x_t * exp(G_t - G_i)) . k_i`` for ``i`` before t, each block of
    ``SUB`` rows referred to its first row; on and above the diagonal the
    entries are finite and meaningless (the caller masks them)."""
    c = q.shape[0]
    sub = min(SUB, c)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    at = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    kk, qk = [], []
    for r in range(0, c, sub):
        ref = gsum[r:r + 1, :]
        right = (kf * jnp.exp(jnp.where(at < r + sub, ref - gsum, 0.0))
                 ).astype(op)
        left = jnp.exp(gsum[r:r + sub] - ref)
        both = jnp.concatenate([kf[r:r + sub] * left, qf[r:r + sub] * left],
                               0).astype(op)
        prod = _mm(both, right, _NT)
        kk.append(prod[:sub])
        qk.append(prod[sub:])
    return jnp.concatenate(kk, 0), jnp.concatenate(qk, 0)


def _chunk_parts(q, k, v, gsum, brow, inverse, op=None, with_inverse=False):
    """What one chunk needs that does not depend on the state. q, k
    [C, d_k], v [C, d_v], gsum [C, d_k] float32 the running sum of g inside
    the chunk, brow [1, C] beta; ``op`` the dtype of the matmuls' operands
    (q's own unless given). Returns (w_v [C, d_v] f32, w_k [C, d_k], q_g
    [C, d_k], attn [C, C], k_d [C, d_k] in the operands' dtype, decay
    [d_k, d_v] f32), and with ``with_inverse`` the inverse beside them."""
    c, dv = q.shape[0], v.shape[1]
    op = op or q.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    bcol = _column(brow)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    kk, qk = _decayed_products(q, k, gsum, op)
    a = jnp.where(row > col, bcol * kk, 0.0)
    t = inverse(a).astype(op)
    e_g = jnp.exp(gsum)
    w_v = _mm(t, (bcol * vf).astype(op), _NN)
    w_k = _mm(t, (bcol * e_g * kf).astype(op), _NN).astype(op)
    q_g = (e_g * q.astype(jnp.float32)).astype(op)
    # the diagonal's decay is exp(0): q_t . k_t as it is, and not the two
    # factors' product, whose gradients cancel (bf16 rounding at the floor
    # would leave them larger than the true dg)
    diagonal = jnp.sum(q.astype(jnp.float32) * kf, 1, keepdims=True)
    attn = jnp.where(row > col, qk,
                     jnp.where(row == col, diagonal, 0.0)).astype(op)
    g_last = gsum[c - 1:c, :]
    k_d = (jnp.exp(g_last - gsum) * kf).astype(op)
    decay = jax.lax.broadcast_in_dim(
        _column(jnp.exp(g_last)), (k.shape[1], dv), (0, 1))
    parts = w_v, w_k, q_g, attn, k_d, decay
    return (parts, t) if with_inverse else parts


def _chunk_apply(parts, state):
    """One chunk from the state it starts with: (o [C, d_v] f32, the next
    state [d_k, d_v] f32)."""
    w_v, w_k, q_g, attn, k_d, decay = parts
    op = w_k.dtype
    s = state.astype(op)
    u = w_v - _mm(w_k, s, _NN)
    u_op = u.astype(op)
    o = _mm(q_g, s, _NN) + _mm(attn, u_op, _NN)
    return o, decay * state + _mm(k_d, u_op, ((0,), (0,)))


def _chunk_exact(q, k, v, gsum, beta, state):
    """One chunk of one head in float32 at ``HIGHEST`` with the decay
    taken whole, [C, C, d_k]: ``chunked_kda``'s step. q, k, gsum [C, d_k],
    v [C, d_v], beta [C]. Returns (o [C, d_v], the next state)."""
    c = q.shape[0]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    diff = gsum[:, None, :] - gsum[None, :, :]
    decay = jnp.exp(jnp.where((row >= col)[..., None], diff, -jnp.inf))
    kk = jnp.einsum("tc,ic,tic->ti", k, k, decay, precision=_HI)
    qk = jnp.einsum("tc,ic,tic->ti", q, k, decay, precision=_HI)
    a = jnp.where(row > col, beta[:, None] * kk, 0.0)
    t = _inv_unit_lower(a)
    e_g = jnp.exp(gsum)
    mm = functools.partial(jnp.matmul, precision=_HI)
    u = mm(t, beta[:, None] * v) - mm(t, mm(beta[:, None] * e_g * k, state))
    o = mm(e_g * q, state) + mm(qk, u)
    g_last = gsum[-1]
    state = jnp.exp(g_last)[:, None] * state + mm(
        (jnp.exp(g_last - gsum) * k).T, u)
    return o, state


def chunked_kda(q, k, v, g, beta, chunk=DEFAULT_CHUNK):
    """The chunked form in ``jax.numpy``, float32 at ``HIGHEST``,
    differentiable by ``jax``: the kernels' oracle. q, k, g [B, T, H, d_k];
    v [B, T, H, d_v]; beta [B, T, H]. Returns o [B, T, H, d_v] in v's
    dtype."""
    b, t, h, dk = q.shape
    dv = v.shape[3]
    pad = (-t) % chunk
    f32 = jnp.float32
    q, k, v, g, beta = (_pad_time(x.astype(f32), pad)
                        for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk

    def by_chunk(x):                     # [B, T, H, ...] -> [NC, B, H, C, ...]
        x = x.reshape((b, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    gsum = jnp.cumsum(by_chunk(g), axis=3)
    step_fn = jax.vmap(jax.vmap(_chunk_exact))      # over B, H

    @jax.checkpoint
    def step(state, xs):
        o, state = step_fn(*xs, state)
        return state, o

    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), f32),
        (by_chunk(q), by_chunk(k), by_chunk(v), gsum, by_chunk(beta)))
    o = jnp.moveaxis(o, 2, 3)                        # [NC, B, C, H, d_v]
    o = jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, dv)
    return o[:, :t].astype(v.dtype)


# ----------------------------------------------------------- the kernels
def _vmem_bytes(heads, dk, dv, chunk, chunks, itemsize):
    """Estimated VMEM of one grid step of the backward kernel, the larger
    of the two: every block double-buffered, the state's cotangent, and
    what is alive inside a chunk."""
    block = chunk * chunks
    lanes = lambda width: -(-width // 128) * 128
    # q, k, dq, dk, v, do, dv in the operands' dtype; G and dG in float32
    sequences = block * (itemsize * (4 * lanes(heads * dk)
                                     + 3 * lanes(heads * dv))
                         + 4 * 2 * lanes(heads * dk))
    rows = 2 * chunks * -(-heads // 8) * 8 * lanes(chunk) * 4
    states = chunks * heads * dk * lanes(dv) * 4
    inverses = chunks * heads * chunk * lanes(chunk) * itemsize
    scratch = heads * dk * lanes(dv) * 4
    # a head's float32 [C, C] and [C, d] tiles (the blocks' decayed
    # operands among them), the state's copies
    alive = heads * 4 * (8 * chunk * lanes(chunk)
                         + 24 * chunk * lanes(max(dk, dv))
                         + 3 * dk * lanes(dv))
    return 2 * (sequences + rows + states + inverses) + scratch + alive


def choose_tile(t, h, dk, dv, chunk, dtype):
    """(heads, chunks) of one grid step of both kernels, from the call's
    shapes and dtype alone, by ``gated_delta_rule.choose_tile``'s rule:
    ``MAX_CHUNKS`` chunks or the whole sequence; of the divisors of ``h``
    that give Mosaic whole 128-lane column blocks (or are all the heads)
    and fit ``VMEM_BUDGET_BYTES``, the most up to ``MAX_VALUE_HEADS``,
    else the fewest; where none fits, fewer chunks, at last one head and
    one chunk."""
    itemsize = jnp.dtype(dtype).itemsize
    chunks = min(MAX_CHUNKS, -(-t // chunk))
    while chunks:
        fits = [
            n for n in range(1, h + 1)
            if h % n == 0
            and (n == h or (n * dk % 128 == 0 and n * dv % 128 == 0))
            and _vmem_bytes(n, dk, dv, chunk, chunks, itemsize)
            <= VMEM_BUDGET_BYTES]
        if fits:
            paying = [n for n in fits if n <= MAX_VALUE_HEADS]
            return (paying[-1] if paying else fits[0]), chunks
        chunks //= 2
    return 1, 1


def _heads_parts(q, k, v, gsum, brow, op, inverse=None, saved=None):
    """``_chunk_parts`` of one chunk for all of a grid step's heads, on a
    leading batch axis: q, k, gsum [n, C, d_k], v [n, C, d_v], brow
    [n, 1, C]. With ``saved`` [n, C, C] the inverses are the forward
    kernel's; without, ``inverse`` makes them and they are returned
    beside the parts."""
    def one_head(q, k, v, gs, b, t=None):
        if t is None:
            return _chunk_parts(q, k, v, gs, b, inverse, op,
                                with_inverse=True)
        return _chunk_parts(q, k, v, gs, b, lambda a: _inv_saved(a, t), op)

    heads = (q, k, v, gsum, brow)
    return jax.vmap(one_head)(*heads + (() if saved is None else (saved,)))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, tile,
                chunk):
    """``chunks`` chunks of ``heads`` heads; the grid's last axis walks the
    sequence and carries the states in ``s_scr``. With two more refs, the
    state each chunk starts from and the chunk's inverse are written there
    (what the backward kernel reads)."""
    heads, chunks = tile
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
            _load_heads(q_ref, rows, heads), _load_heads(k_ref, rows, heads),
            _load_heads(v_ref, rows, heads), _load_heads(g_ref, rows, heads),
            _load_rows(b_ref, c), op, inverse)
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
    carried in ``ds_scr``; a chunk's vector-Jacobian product is ``jax.vjp``
    of the forward's own functions with the forward's inverse."""
    heads, chunks = tile
    op = q_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def walk(i, _):
        c = chunks - 1 - i
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        saved = t_ref[0, c]

        def chunk_fn(q, k, v, gsum, brow, state):
            parts = _heads_parts(q, k, v, gsum, brow, op, saved=saved)
            return jax.vmap(_chunk_apply)(parts, state)

        _, vjp = jax.vjp(
            chunk_fn, _load_heads(q_ref, rows, heads, f32),
            _load_heads(k_ref, rows, heads, f32),
            _load_heads(v_ref, rows, heads, f32),
            _load_heads(g_ref, rows, heads), _load_rows(b_ref, c),
            states_ref[0, c])
        dq, dk, dv, dg, db, d_state = vjp(
            (_load_heads(do_ref, rows, heads, f32), ds_scr[...]))
        _store_heads(dq_ref, rows, dq)
        _store_heads(dk_ref, rows, dk)
        _store_heads(dv_ref, rows, dv)
        _store_heads(dg_ref, rows, dg)
        _store_rows(db_ref, c, db)
        ds_scr[...] = d_state

    jax.lax.fori_loop(0, chunks, walk, None)


def _call(kernel, name, tile, dims, walk, in_names, out_names, out_shape,
          operands):
    """One of the two kernels over the grid (batch, groups of heads,
    blocks of chunks); ``walk`` maps the grid's last index to the block of
    the sequence."""
    heads, chunks = tile
    b, tp, h, dk, dv, chunk = dims
    block = chunk * chunks

    def sequence(width):
        return pl.BlockSpec((1, block, width),
                            lambda i, j, n: (i, walk(n), j))

    def by_chunk(*tail):
        return pl.BlockSpec((1, chunks) + tail,
                            lambda i, j, n: (i, walk(n), j, 0, 0))

    sp = {"k": sequence(heads * dk), "v": sequence(heads * dv),
          "rows": by_chunk(1, heads, chunk),
          "states": by_chunk(heads, dk, dv),
          "inverses": by_chunk(heads, chunk, chunk)}
    record_kda_blocks(name, heads, chunks)
    need = _vmem_bytes(heads, dk, dv, chunk, chunks,
                       operands[0].dtype.itemsize)
    return pl_call(
        functools.partial(kernel, tile=tile, chunk=chunk), name=name,
        grid=(b, h // heads, tp // block),
        in_specs=[sp[n] for n in in_names],
        out_specs=[sp[n] for n in out_names], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(
                VMEM_LIMIT_BYTES if need > _DEFAULT_SCOPE_BYTES // 2
                else None)),
    )(*operands)


def _dims(qf, vf, brow, tile):
    """(B, T, H, d_k, d_v, C) of a call in the kernels' layouts."""
    b, tp, _ = vf.shape
    h, chunk = brow.shape[2] * brow.shape[3], brow.shape[4]
    return b, tp, h, qf.shape[2] // h, vf.shape[2] // h, chunk


def _fwd_call(qf, kf, vf, gsum, brow, tile, with_states):
    dims = b, tp, h, dk, dv, chunk = _dims(qf, vf, brow, tile)
    nc = tp // chunk
    out_names = ["v"]
    out_shape = [jax.ShapeDtypeStruct(vf.shape, vf.dtype)]
    if with_states:
        out_names += ["states", "inverses"]
        out_shape += [
            jax.ShapeDtypeStruct((b, nc, h, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, chunk, chunk), qf.dtype)]
    return _call(_fwd_kernel, "kda_fwd", tile, dims, lambda n: n,
                 ["k", "k", "v", "k", "rows"], out_names, out_shape,
                 (qf, kf, vf, gsum, brow))


def _bwd_call(qf, kf, vf, gsum, brow, states, inverses, do, tile):
    dims = _, tp, _, _, _, chunk = _dims(qf, vf, brow, tile)
    last = tp // (chunk * tile[1]) - 1
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return _call(_bwd_kernel, "kda_bwd", tile, dims, lambda n: last - n,
                 ["k", "k", "v", "k", "rows", "states", "inverses", "v"],
                 ["k", "k", "v", "k", "rows"],
                 [like(qf), like(kf), like(vf), like(gsum), like(brow)],
                 (qf, kf, vf, gsum, brow, states, inverses, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_core(qf, kf, vf, gsum, brow, tile):
    """The kernels' own layouts: qf, kf [B, T, H d_k], vf [B, T, H d_v]
    with T a multiple of the block; gsum like kf in float32 (running sums
    of g inside each chunk); brow [B, T / C, H / heads, heads, C] float32
    (beta); ``tile`` = (heads a step, chunks a step). Returns o like
    vf."""
    return _fwd_call(qf, kf, vf, gsum, brow, tile, False)[0]


def _kda_core_fwd(qf, kf, vf, gsum, brow, tile):
    o, states, inverses = _fwd_call(qf, kf, vf, gsum, brow, tile, True)
    return o, (qf, kf, vf, gsum, brow, states, inverses)


def _kda_core_bwd(tile, res, do):
    return _bwd_call(*res, do, tile)


_kda_core.defvjp(_kda_core_fwd, _kda_core_bwd)


def _kda_pallas(qf, kf, vf, g, beta, chunk, h):
    """``_kda_core`` of flat operands of any length: the grid step chosen,
    the tail padded, g's running sums taken inside each chunk, beta laid
    out by chunk."""
    b, t, _ = qf.shape
    dk, dv = qf.shape[2] // h, vf.shape[2] // h
    heads, chunks = choose_tile(t, h, dk, dv, chunk, qf.dtype)
    pad = (-t) % (chunk * chunks)
    qf, kf, vf, g, beta = (_pad_time(x, pad) for x in (qf, kf, vf, g, beta))
    tp = t + pad
    gsum = jnp.cumsum(
        g.astype(jnp.float32).reshape(b, tp // chunk, chunk, h * dk),
        axis=2).reshape(b, tp, h * dk)
    brow = jnp.moveaxis(beta.astype(jnp.float32).reshape(
        b, tp // chunk, chunk, h // heads, heads), 2, 4)
    o = _kda_core(qf, kf, vf, gsum, brow, (heads, chunks))
    return o[:, :t]


def _heads(x, count):
    return x.reshape(x.shape[:2] + (count, x.shape[2] // count))


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


def kda(q, k, v, g, beta, chunk=DEFAULT_CHUNK, *, impl="auto",
        num_heads=None):
    """o of Kimi Delta Attention, in v's dtype and v's form.

    q, k (normalised and scaled), v and g come in one of two forms, told
    apart by their rank. **Heads**: q, k, g [B, T, H, d_k] and v
    [B, T, H, d_v]. **Flat**, with ``num_heads`` given: q, k, g
    [B, T, H d_k] and v [B, T, H d_v], a head a block of consecutive
    columns: what the kernels read in place. g is the log-decay per key
    channel, float32, in ``[LOWER_BOUND, 0]`` (the kernels' blocks rely on
    the floor); beta [B, T, H]. Any length: the tail is padded with tokens
    that write nothing (beta 0, g 0).

    impl: ``"auto"`` is the kernels on a TPU (FLAGS_use_pallas_kernels)
    and the ``jax.numpy`` chunked form elsewhere; ``"pallas"`` always the
    kernels (the interpreter off the TPU); ``"xla"`` always the
    ``jax.numpy`` form. All three are differentiable."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'kda impl must be "auto", "pallas" or "xla", got {impl!r}')
    flat = q.ndim == 3
    if flat and num_heads is None:
        raise ValueError("kda: q, k, v, g as [B, T, H * d] need num_heads")
    h = num_heads if flat else q.shape[2]
    if beta.shape[-1] != h or g.shape != k.shape:
        raise ValueError(
            f"kda: beta {beta.shape} and g {g.shape} against {h} heads of "
            f"k {k.shape}")
    if impl == "auto":
        from ...core import flags

        impl = "pallas" if (
            device.on_tpu()
            and flags.get_flag("FLAGS_use_pallas_kernels")
        ) else "xla"
    if impl == "xla":
        if flat:
            q, k, v, g = _heads(q, h), _heads(k, h), _heads(v, h), _heads(g, h)
        o = chunked_kda(q, k, v, g, beta, chunk)
        return _flat(o) if flat else o
    if flat:
        return _kda_pallas(q, k, v, g, beta, chunk, h)
    return _kda_pallas(_flat(q), _flat(k), _flat(v), _flat(g), beta, chunk,
                       h).reshape(v.shape)
