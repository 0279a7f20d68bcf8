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

``T`` comes from the nilpotent series ``(I - A)(I + A^2)(I + A^4)...``:
``log2(C)`` squarings and as many products of C x C float32 matrices, no
row-by-row substitution. Everything else is matmuls with bf16 operands
and float32 accumulation; the state and every accumulator are float32.

Kernels: ``gated_delta_rule_fwd`` walks a sequence's chunks in order,
one (batch, value head) a grid row, the state in VMEM scratch; it reads
q, k, v in place as ``[B, T, H * d]`` (a head is a 128-lane column
block, so nothing is transposed or repeated in HBM), and for the backward
it also writes the state each chunk starts from. ``gated_delta_rule_bwd``
walks the chunks from the last to the first with the state's cotangent in
VMEM scratch; a chunk's vector-Jacobian product is ``jax.vjp`` of the
forward's own chunk function, taken while the kernel is traced.
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

from ...core.device import on_tpu
from ._compat import pl_call

__all__ = ["gated_delta_rule", "chunked_gated_delta_rule"]

DEFAULT_CHUNK = 64
# chunks one grid step walks: a [256, 128] block a head moves four times
# fewer, larger DMAs than a [64, 128] one
CHUNKS_PER_STEP = 4
_HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------- the mathematics
def _mm(a, b, dims, precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _inv_unit_lower_series(a):
    """(I + a)^-1 for a strictly lower triangular [C, C] float32 ``a``:
    (I - a)(I + a^2)(I + a^4)... ; a^C = 0 ends the series."""
    c = a.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
           ).astype(jnp.float32)
    power = -a
    inv = eye + power
    span = 2
    while span < c:
        power = _mm(power, power, ((1,), (0,)), _HI)
        inv = inv + _mm(inv, power, ((1,), (0,)), _HI)
        span *= 2
    return inv


@jax.custom_vjp
def _inv_unit_lower(a):
    return _inv_unit_lower_series(a)


def _inv_fwd(a):
    inv = _inv_unit_lower_series(a)
    return inv, inv


def _inv_bwd(inv, d_inv):
    # d(I + a)^-1 = -(I + a)^-1 da (I + a)^-1
    return (-_mm(_mm(inv, d_inv, ((0,), (0,)), _HI), inv,
                 ((1,), (1,)), _HI),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def _chunk_parts(q, k, v, grow, brow, inverse, op=None):
    """What one chunk needs that does not depend on the state. q, k
    [C, d_k] and v [C, d_v]; ``op`` is the dtype of the matmuls' operands
    (q's own unless given: the backward kernel hands float32 copies over
    so that their cotangents add up in float32); grow [1, C] the
    running sum of g inside the chunk, brow [1, C] beta (rows: a [C, 1]
    column would be padded to 128 lanes in HBM; the columns are made
    here, through the diagonal). Returns (w_v [C, d_v] f32, w_k [C, d_k],
    q_g [C, d_k], attn [C, C], k_d [C, d_k] in the operands' dtype, decay
    [1, d_v] f32)."""
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
    a = jnp.where(row > col,
                  bcol * decay * _mm(k, k, ((1,), (1,))), 0.0)
    t = inverse(a).astype(op)
    e_g = jnp.exp(gcol)
    w_v = _mm(t, (bcol * vf).astype(op), ((1,), (0,)))
    w_k = _mm(t, (bcol * e_g * kf).astype(op), ((1,), (0,))).astype(op)
    q_g = (e_g * q.astype(jnp.float32)).astype(op)
    attn = (decay * _mm(q, k, ((1,), (1,)))).astype(op)
    g_last = gcol[c - 1:c, :]
    k_d = (jnp.exp(g_last - gcol) * kf).astype(op)
    # [1, d_v], not [1, 1]: Mosaic broadcasts along lanes, then sublanes
    decay_last = jnp.exp(jnp.broadcast_to(g_last, (1, v.shape[1])))
    return w_v, w_k, q_g, attn, k_d, decay_last


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
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunk,
                chunks):
    """One block of ``chunks`` chunks of one (batch, value head); the grid's
    last axis walks the sequence and carries the state in ``s_scr``. With a
    seventh ref, the state each chunk starts from is written there (what
    the backward kernel reads)."""
    states_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    state = s_scr[:]
    for c in range(chunks):
        rows = pl.ds(c * chunk, chunk)
        if states_ref is not None:
            states_ref[0, 0, c] = state
        parts = _chunk_parts(
            q_ref[0, rows, :], k_ref[0, rows, :], v_ref[0, rows, :],
            g_ref[0, 0, c], b_ref[0, 0, c], _inv_unit_lower_series)
        o, state = _chunk_apply(parts, state)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
    s_scr[:] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, *, chunk,
                chunks):
    """The blocks of one (batch, value head) from the last to the first,
    the state's cotangent carried in ``ds_scr``. A chunk's
    vector-Jacobian product is taken by ``jax.vjp`` of the forward's own
    chunk function while the kernel is traced, so the two cannot drift
    apart: Mosaic is handed the transposed matmuls as plain operations."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    op = q_ref.dtype
    f32 = jnp.float32

    def chunk_fn(q, k, v, grow, brow, state):
        return _chunk_apply(
            _chunk_parts(q, k, v, grow, brow, _inv_unit_lower, op), state)

    d_state = ds_scr[:]
    for c in reversed(range(chunks)):
        rows = pl.ds(c * chunk, chunk)
        _, vjp = jax.vjp(
            chunk_fn, q_ref[0, rows, :].astype(f32),
            k_ref[0, rows, :].astype(f32), v_ref[0, rows, :].astype(f32),
            g_ref[0, 0, c], b_ref[0, 0, c], states_ref[0, 0, c])
        dq, dk, dv, dg, db, d_state = vjp(
            (do_ref[0, rows, :].astype(f32), d_state))
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, 0, c] = dg
        db_ref[0, 0, c] = db
    ds_scr[:] = d_state


def _record_chunk(chunk, dk, dv):
    from ...observability import counter

    counter(
        "paddle_tpu_kernels_gdr_chunk",
        "Traced gated-delta-rule kernel calls by chunk and head sizes",
        labelnames=("chunk", "d_k", "d_v"),
    ).inc(chunk=chunk, d_k=dk, d_v=dv)


def _specs(dims, walk):
    """Block specs of the kernels' operands in their HBM layouts; ``walk``
    maps the grid's last index to the block of the sequence."""
    rep, dk, dv, block, chunks, chunk = dims
    return {
        "qk": pl.BlockSpec(
            (1, block, dk), lambda i, h, j: (i, walk(j), h // rep)),
        "qk_by_value_head": pl.BlockSpec(
            (1, block, dk), lambda i, h, j: (i, walk(j), h)),
        "v": pl.BlockSpec((1, block, dv), lambda i, h, j: (i, walk(j), h)),
        "rows": pl.BlockSpec((1, 1, chunks, 1, chunk),
                             lambda i, h, j: (i, h, walk(j), 0, 0)),
        "states": pl.BlockSpec((1, 1, chunks, dk, dv),
                               lambda i, h, j: (i, h, walk(j), 0, 0)),
    }


def _fwd_call(qf, kf, vf, grow, brow, rep, with_states):
    b, tp, _ = vf.shape
    hv, nc, chunk = grow.shape[1], grow.shape[2], grow.shape[4]
    dk, dv = qf.shape[2] * rep // hv, vf.shape[2] // hv
    chunks = min(CHUNKS_PER_STEP, nc)
    block = chunk * chunks
    sp = _specs((rep, dk, dv, block, chunks, chunk), lambda j: j)
    _record_chunk(chunk, dk, dv)
    out_specs = [sp["v"]]
    out_shape = [jax.ShapeDtypeStruct(vf.shape, vf.dtype)]
    if with_states:
        out_specs.append(sp["states"])
        out_shape.append(
            jax.ShapeDtypeStruct((b, hv, nc, dk, dv), jnp.float32))
    return pl_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks),
        name="gated_delta_rule_fwd",
        grid=(b, hv, tp // block),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["rows"], sp["rows"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(qf, kf, vf, grow, brow)


def _bwd_call(qf, kf, vf, grow, brow, states, do, rep):
    b, tp, _ = vf.shape
    hv, nc, chunk = grow.shape[1], grow.shape[2], grow.shape[4]
    dk, dv = qf.shape[2] * rep // hv, vf.shape[2] // hv
    chunks = min(CHUNKS_PER_STEP, nc)
    block = chunk * chunks
    last = tp // block - 1
    sp = _specs((rep, dk, dv, block, chunks, chunk), lambda j: last - j)
    by_head = jax.ShapeDtypeStruct((b, tp, hv * dk), qf.dtype)
    rows = jax.ShapeDtypeStruct(grow.shape, jnp.float32)
    return pl_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks),
        name="gated_delta_rule_bwd",
        grid=(b, hv, tp // block),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["rows"], sp["rows"],
                  sp["states"], sp["v"]],
        out_specs=[sp["qk_by_value_head"], sp["qk_by_value_head"], sp["v"],
                   sp["rows"], sp["rows"]],
        out_shape=[by_head, by_head,
                   jax.ShapeDtypeStruct(vf.shape, vf.dtype), rows, rows],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(qf, kf, vf, grow, brow, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdr_core(qf, kf, vf, grow, brow, rep):
    """The kernels' own layouts: qf, kf [B, T, H_k * d_k] and vf
    [B, T, H_v * d_v] with T a multiple of the block; grow, brow
    [B, H_v, T / C, 1, C] float32 (running sums of g inside a chunk,
    beta). Returns o like vf."""
    return _fwd_call(qf, kf, vf, grow, brow, rep, False)[0]


def _gdr_core_fwd(qf, kf, vf, grow, brow, rep):
    o, states = _fwd_call(qf, kf, vf, grow, brow, rep, True)
    return o, (qf, kf, vf, grow, brow, states)


def _gdr_core_bwd(rep, res, do):
    qf, kf, vf, grow, brow, states = res
    dq, dk, dv, dg, db = _bwd_call(qf, kf, vf, grow, brow, states, do, rep)
    b, tp, width = qf.shape
    d_k = width * rep // grow.shape[1]

    def over_key_heads(x):      # the value heads that share a key head
        x = x.reshape(b, tp, -1, rep, d_k).astype(jnp.float32).sum(3)
        return x.reshape(qf.shape).astype(qf.dtype)

    return over_key_heads(dq), over_key_heads(dk), dv, dg, db


_gdr_core.defvjp(_gdr_core_fwd, _gdr_core_bwd)


def _gdr_pallas(q, k, v, g, beta, chunk):
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    block = chunk * min(CHUNKS_PER_STEP, -(-t // chunk))
    pad = (-t) % block
    q, k, v, g, beta = (_pad_time(x, pad) for x in (q, k, v, g, beta))
    tp = t + pad
    grow = _chunk_sums(g, chunk)[:, :, :, None, :]   # [B, H_v, NC, 1, C]
    brow = jnp.moveaxis(beta.astype(jnp.float32), 1, 2).reshape(grow.shape)
    o = _gdr_core(q.reshape(b, tp, hk * dk), k.reshape(b, tp, hk * dk),
                  v.reshape(b, tp, hv * dv), grow, brow, hv // hk)
    return o.reshape(b, tp, hv, dv)[:, :t]


def gated_delta_rule(q, k, v, g, beta, chunk=DEFAULT_CHUNK, *,
                     impl="auto"):
    """o [B, T, H_v, d_v] of the gated delta rule, in v's dtype.

    q, k: [B, T, H_k, d_k], already normalised and scaled; v:
    [B, T, H_v, d_v] with ``H_v`` a multiple of ``H_k``; g (log gate,
    <= 0) and beta: [B, T, H_v]. Any length: the tail is padded with
    tokens that write nothing (beta 0, g 0).

    impl: ``"auto"`` is the kernel on a TPU (FLAGS_use_pallas_kernels)
    and the ``jax.numpy`` chunked form elsewhere; ``"pallas"`` is always
    the kernel (the interpreter off the TPU); ``"xla"`` always the
    ``jax.numpy`` form. All three are differentiable."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'gated_delta_rule impl must be "auto", "pallas" or "xla", '
            f"got {impl!r}")
    if v.shape[2] % q.shape[2]:
        raise ValueError(
            f"gated_delta_rule: {v.shape[2]} value heads over "
            f"{q.shape[2]} key heads")
    if impl == "auto":
        from ...core import flags

        impl = "pallas" if (
            on_tpu() and flags.get_flag("FLAGS_use_pallas_kernels")
        ) else "xla"
    if impl == "xla":
        return chunked_gated_delta_rule(q, k, v, g, beta, chunk)
    return _gdr_pallas(q, k, v, g, beta, chunk)
