"""Pallas TPU flash attention.

Replaces the reference's dynloaded flash-attn CUDA kernels
(ref: python/paddle/nn/functional/flash_attention.py:242,
phi/backends/dynload/flashattn.cc) with a TPU-native Pallas kernel pair:
online-softmax forward saving per-row logsumexp, blocked backward
recomputing probabilities (no s×s materialization in HBM either way).

Layout contract matches the public API: q/k/v are [batch, seq, heads,
head_dim]; the kernel operates in [batch*heads, seq, head_dim].

Grid: (bh, q_blocks, k_blocks) with the k dimension innermost/"arbitrary"
so the scratch carry (running max / sum / accumulator) is valid across the
sequential k sweep. Causal blocks above the diagonal are skipped via
pl.when.

On non-TPU backends the kernels run in interpreter mode so the numerics
are testable on the 8-device CPU mesh (conftest).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._compat import current_spmd_axes, pl_call

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, seq_k):
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kj = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qi >= kj, s, NEG_INF)

        # m/l scratches are lane-replicated [bq, 128] (TPU tile shape);
        # column 0 is authoritative
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(kb * block_k <= qb * block_q + (block_q - 1))
        def _():
            _visit()
    else:
        _visit()

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse stored sublane-replicated [8, block_q] (TPU block rule:
        # trailing block dims divisible by (8, 128))
        lse = (m_scr[:, :1] + jnp.log(l_safe)).reshape(1, -1)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))

    out, lse = pl_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_k=sk,
        ),
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, block_q, block_k):
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(block_q, 1)
        delta = delta_ref[0, 0].reshape(block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kj = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qi >= kj, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        acc_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(kb * block_k <= qb * block_q + (block_q - 1))
        def _():
            _visit()
    else:
        _visit()

    @pl.when(kb == nk - 1)
    def _fin():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k):
    qb = pl.program_id(2)
    kb = pl.program_id(1)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _visit():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(block_q, 1)
        delta = delta_ref[0, 0].reshape(block_q, 1)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            qi = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            kj = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(qi >= kj, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # this k block only sees q blocks at or below the diagonal
        @pl.when(qb * block_q + (block_q - 1) >= kb * block_k)
        def _():
            _visit()
    else:
        _visit()

    @pl.when(qb == nq - 1)
    def _fin():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    delta_row = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [bh, sq]
    # sublane-replicated like lse (TPU block tiling rule)
    delta = jnp.broadcast_to(delta_row[:, None, :], (bh, 8, sq))

    dq = pl_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name="flash_attention_bwd_dq",
        grid=(bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(q, k, v, do, lse, delta)

    dk, dv = pl_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name="flash_attention_bwd_dkv",
        grid=(bh, pl.cdiv(sk, block_k), pl.cdiv(sq, block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, scale, causal, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_core_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, out, lse, do, scale, causal, block_q, block_k
    )
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_4d(q, k, v, scale, causal, block_q, block_k):
    """[b, s, h, d] in and out around the [b*h, s, d] kernel layout."""
    b, sq, h, d = q.shape

    def _merge(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    out = _flash_core(_merge(q), _merge(k), _merge(v), scale, causal,
                      block_q, block_k)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def flash_attention(q, k, v, *, causal=True, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """q/k/v: [batch, seq, heads, head_dim] -> same-shape output.

    Requirements: no attention mask (causal flag instead), no dropout —
    callers fall back to the math sdpa otherwise (nn_ops dispatch).

    Inside a sharded program the caller declares the mesh axes of the
    batch and head dims with ``_compat.spmd_axes`` and the kernel runs
    per shard under ``shard_map``: Mosaic kernels have no automatic SPMD
    rule, and attention is independent per batch row and per head."""
    sq, d = q.shape[1], q.shape[3]
    sk = k.shape[1]
    # The kernel has no padding mask for partial tail blocks; out-of-range
    # rows/cols would silently attend to block padding.
    if sq % min(int(block_q), sq) or sk % min(int(block_k), sk):
        raise ValueError(
            f"flash_attention requires seq lengths divisible by the block "
            f"sizes: got sq={sq}, sk={sk} with block_q={block_q}, "
            f"block_k={block_k}; pad the sequence or use the math sdpa"
        )
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    fn = functools.partial(
        _flash_4d, scale=float(scale), causal=bool(causal),
        block_q=int(block_q), block_k=int(block_k),
    )
    axes = current_spmd_axes()
    if axes is not None:
        mesh, batch_axis, head_axis = axes
        # an axis that does not divide its dim leaves the dim whole
        # (computed redundantly along that axis) instead of failing
        if batch_axis is not None and q.shape[0] % mesh.shape[batch_axis]:
            batch_axis = None
        if head_axis is not None and q.shape[2] % mesh.shape[head_axis]:
            head_axis = None
        spec = P(batch_axis, None, head_axis, None)
        fn = jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    return fn(q, k, v)
