"""Pallas TPU flash attention.

Replaces the reference's dynloaded flash-attn CUDA kernels
(ref: python/paddle/nn/functional/flash_attention.py:242,
phi/backends/dynload/flashattn.cc) with three TPU-native Pallas kernels:
an online-softmax forward saving per-row logsumexp, and a blocked backward
(`dq`; `dk` and `dv`) recomputing probabilities (no s×s materialization in
HBM either way).

Layout contract matches the public API: q/k/v are [batch, seq, heads,
head_dim]; the kernel operates in [batch*heads, seq, head_dim].

Grid: (bh, q_blocks, k_blocks) with the k dimension innermost/"arbitrary"
so the scratch carry (running max / sum / accumulator) is valid across the
sequential k sweep; `dk`/`dv` sweep the q blocks of one k block instead.

Tiles. ``choose_blocks`` picks (block_q, block_k) for each kernel from
the call's shapes and dtype: the pair of 128-multiples up to 1024 that
divide the lengths, fit ``VMEM_BUDGET_BYTES`` by ``_vmem_bytes``'
estimate and leave the fewest grid steps. A grid step has a fixed cost
(pipeline bookkeeping, block DMAs, the scratch's read-modify-write) of
about 0.4 us on a v5e, the MXU time of a 128x128 tile at head_dim 128
is a tenth of that, so the tile is as large as VMEM lets it be. A
caller's ``block_q=``/``block_k=`` win, for all three kernels.

Operands. The matmuls take q, k, v and dO in the input's dtype, and the
probabilities and dS are cast to it for the second matmuls; every
matmul accumulates in float32, and the running max and sum, logsumexp,
delta, the exponentials and the accumulators are float32. ``scale`` is
folded into the resident operand once a sweep (q for the forward and
`dq`, k for `dk`/`dv`) and out of dS into the float32 accumulators at
the sweep's end.

Causal schedule. A block above the diagonal is not computed, and its
index map names the block the sweep already holds (the last one the q
block needs; the first, for `dk`/`dv`), so no DMA is issued for it
either. Only a block the diagonal crosses builds the iota mask; blocks
wholly below it run the unmasked body.

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

from ._compat import current_spmd_axes, pl_call, record_flash_blocks

NEG_INF = -1e30

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")
FWD, BWD_DQ, BWD_DKV = KERNELS

# ---------------------------------------------------------------- tiling
BLOCK_CANDIDATES = tuple(range(1024, 0, -128))
# What the chooser lets one grid step hold, by _vmem_bytes' estimate, and
# what Mosaic is then allowed (its own temporaries come on top of the
# estimate). A v5e core has 128 MiB of VMEM; Mosaic's default scope is
# 16 MiB, and a call whose estimate stays under half of that keeps it.
VMEM_BUDGET_BYTES = 40 * 2**20
VMEM_LIMIT_BYTES = 64 * 2**20
_DEFAULT_SCOPE_BYTES = 16 * 2**20

# [bq, bk] tiles alive in one grid step: (float32, input dtype). Forward:
# scores, probabilities, the two iotas of a diagonal block; p for p.v.
# Backward: scores, p, dp, ds and the iotas; ds (and p, for dv) as
# operands.
_SCORE_TILES = {FWD: (4, 1), BWD_DQ: (6, 1), BWD_DKV: (6, 2)}
# [bq, d] and [bk, d] operand and result blocks, each double-buffered
_BLOCKS = {FWD: (2, 2), BWD_DQ: (3, 2), BWD_DKV: (2, 4)}


def _vmem_bytes(kernel, block_q, block_k, head_dim, itemsize):
    """Estimated VMEM of one grid step of ``kernel``."""
    n32, nin = _SCORE_TILES[kernel]
    tiles = block_q * block_k * (4 * n32 + itemsize * nin)
    nq, nk = _BLOCKS[kernel]
    blocks = 2 * itemsize * head_dim * (nq * block_q + nk * block_k)
    rows = 2 * 2 * 8 * block_q * 4          # lse, delta: [8, bq] float32
    if kernel == BWD_DKV:
        scratch = block_k * head_dim * (2 * 4 + itemsize)
    else:
        # accumulator, scaled q, two lane-replicated [bq, 128] columns
        scratch = block_q * (head_dim * (4 + itemsize) + 2 * 128 * 4)
    return tiles + blocks + rows + scratch


def _divisors(seq):
    if seq < 128:
        return (seq,)  # one block spanning the whole (short) dim
    return tuple(c for c in BLOCK_CANDIDATES if seq % c == 0) or (128,)


def choose_blocks(sq, sk, head_dim, dtype, kernel):
    """(block_q, block_k) of ``kernel`` (one of ``KERNELS``) for a call of
    these lengths, head_dim and dtype: among the candidate pairs that
    divide the lengths and fit the VMEM budget, the one with the fewest
    grid steps. Of equals, the forward takes the larger block_k (its
    running max, sum and rescaling are paid once a step and q row: on a
    v5e 512 x 1024 takes 7.3 ms where 1024 x 512 takes 11.3), the
    backward the larger block_q (no running statistics; 7.9 against 8.3
    ms for `dq`, nothing for `dk`/`dv`). A length that no candidate
    divides gets 128, and the public entry refuses it."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = [
        (bq, bk) for bq in _divisors(sq) for bk in _divisors(sk)
        if _vmem_bytes(kernel, bq, bk, head_dim, itemsize)
        <= VMEM_BUDGET_BYTES
    ] or [(_divisors(sq)[-1], _divisors(sk)[-1])]
    wider = 1 if kernel == FWD else 0
    return max(fits, key=lambda b: (b[0] * b[1], b[wider]))


def _compiler_params(kernel, block_q, block_k, head_dim, dtype):
    need = _vmem_bytes(kernel, block_q, block_k, head_dim,
                       jnp.dtype(dtype).itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=(
            VMEM_LIMIT_BYTES if need > _DEFAULT_SCOPE_BYTES // 2 else None),
    )


# ------------------------------------------------------- causal schedule
def _visit_block(visit, causal, qb, kb, block_q, block_k):
    """Run ``visit(masked)`` for block (qb, kb): not at all above the
    diagonal, with the mask where the diagonal crosses the block, without
    it wholly below."""
    if not causal:
        visit(False)
        return
    q_lo, k_lo = qb * block_q, kb * block_k
    below = k_lo + (block_k - 1) <= q_lo
    seen = k_lo <= q_lo + (block_q - 1)
    pl.when(below)(lambda: visit(False))
    pl.when(jnp.logical_and(seen, jnp.logical_not(below)))(
        lambda: visit(True))


def _causal_mask(q_lo, k_lo, shape, q_dim):
    """q position >= k position over a tile whose dim ``q_dim`` runs along
    q (the `dk`/`dv` kernel holds the tile transposed)."""
    qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    kj = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    return qi >= kj


def _nt(a, b):
    """a @ b.T, float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(x, scale):
    """x * scale in x's dtype, multiplied in float32 (a bf16 multiply
    would round ``scale`` itself)."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _column(row_ref):
    """[8, bq] sublane-replicated rows -> lane-replicated [bq, 128]."""
    col = row_ref[0, 0].reshape(-1, 1)
    return jnp.broadcast_to(col, (col.shape[0], 128))


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs_scr, m_scr, l_scr,
                acc_scr, *, scale, causal, block_q, block_k):
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        qs_scr[:] = _scaled(q_ref[0], scale)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit(masked):
        v = v_ref[0]
        s = _nt(qs_scr[:], k_ref[0])  # [bq, bk]
        if masked:
            s = jnp.where(
                _causal_mask(qb * block_q, kb * block_k, s.shape, 0),
                s, NEG_INF)

        # m/l scratches are lane-replicated [bq, 128] (TPU tile shape);
        # column 0 is authoritative
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _nn(p.astype(v.dtype), v)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _visit_block(_visit, causal, qb, kb, block_q, block_k)

    @pl.when(kb == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # lse stored sublane-replicated [8, block_q] (TPU block rule:
        # trailing block dims divisible by (8, 128))
        lse = (m_scr[:, :1] + jnp.log(l_safe)).reshape(1, -1)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _kv_index(causal, block_q, block_k, nk):
    """Index map of a [block_k, d] operand in a (b, q block, k block)
    grid. Causal: a step above the diagonal names the last block the q
    block sees any of, which is resident, so nothing is fetched for it."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def last(i):
        return jnp.minimum((i * block_q + block_q - 1) // block_k, nk - 1)

    return lambda b, i, j: (b, jnp.minimum(j, last(i)), 0)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    record_flash_blocks(FWD, block_q, block_k)
    kv = pl.BlockSpec((1, block_k, d), _kv_index(causal, block_q, block_k, nk))

    out, lse = pl_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name=FWD,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            kv, kv,
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
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(FWD, block_q, block_k, d, q.dtype),
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   qs_scr, lse_scr, delta_scr, acc_scr, *, scale, causal,
                   block_q, block_k):
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        qs_scr[:] = _scaled(q_ref[0], scale)
        # the rows' statistics, turned into columns once a sweep
        lse_scr[:] = _column(lse_ref)
        delta_scr[:] = _column(delta_ref)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit(masked):
        k = k_ref[0]
        s = _nt(qs_scr[:], k)
        if masked:
            s = jnp.where(
                _causal_mask(qb * block_q, kb * block_k, s.shape, 0),
                s, NEG_INF)
        p = jnp.exp(s - lse_scr[:, :1])
        dp = _nt(do_ref[0], v_ref[0])
        ds = p * (dp - delta_scr[:, :1])  # scale: at the sweep's end
        acc_scr[:] += _nn(ds.astype(k.dtype), k)

    _visit_block(_visit, causal, qb, kb, block_q, block_k)

    @pl.when(kb == nk - 1)
    def _fin():
        dq_ref[0] = (acc_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, ks_scr, dk_scr, dv_scr, *, scale,
                    causal, block_q, block_k):
    """The tiles are held transposed, [bk, bq]: the rows' statistics then
    broadcast along sublanes as the [1, bq] rows they are stored as, and
    both accumulating matmuls are plain [bk, bq] x [bq, d]."""
    qb = pl.program_id(2)
    kb = pl.program_id(1)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        ks_scr[:] = _scaled(k_ref[0], scale)
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _visit(masked):
        q = q_ref[0]
        do = do_ref[0]
        st = _nt(ks_scr[:], q)  # [bk, bq]
        if masked:
            st = jnp.where(
                _causal_mask(qb * block_q, kb * block_k, st.shape, 1),
                st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, :1, :])
        dv_scr[:] += _nn(pt.astype(do.dtype), do)
        dpt = _nt(v_ref[0], do)
        dst = pt * (dpt - delta_ref[0, :1, :])  # scale: at the sweep's end
        dk_scr[:] += _nn(dst.astype(q.dtype), q)

    _visit_block(_visit, causal, qb, kb, block_q, block_k)

    @pl.when(qb == nq - 1)
    def _fin():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, causal, dq_blocks, dkv_blocks):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta_row = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [bh, sq]
    # sublane-replicated like lse (TPU block tiling rule)
    delta = jnp.broadcast_to(delta_row[:, None, :], (bh, 8, sq))

    block_q, block_k = dq_blocks
    nq, nk = sq // block_q, sk // block_k
    record_flash_blocks(BWD_DQ, block_q, block_k)
    qd = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv = pl.BlockSpec((1, block_k, d), _kv_index(causal, block_q, block_k, nk))
    row = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))
    dq = pl_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name=BWD_DQ,
        grid=(bh, nq, nk),
        in_specs=[qd, kv, kv, qd, row, row],
        out_specs=qd,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(BWD_DQ, block_q, block_k, d,
                                         q.dtype),
    )(q, k, v, do, lse, delta)

    block_q, block_k = dkv_blocks
    nq, nk = sq // block_q, sk // block_k
    record_flash_blocks(BWD_DKV, block_q, block_k)
    if causal:
        # a step above the diagonal names the first q block that sees any
        # of this k block: fetched once, before the sweep reaches it
        def q_of(j, i):
            first = jnp.minimum((j * block_k) // block_q, nq - 1)
            return jnp.maximum(i, first)
    else:
        def q_of(j, i):
            return i
    qd = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, q_of(j, i), 0))
    kv = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row = pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, q_of(j, i)))
    dk, dv = pl_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name=BWD_DKV,
        grid=(bh, nk, nq),
        in_specs=[qd, kv, kv, qd, row, row],
        out_specs=[kv, kv],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), k.dtype),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(BWD_DKV, block_q, block_k, d,
                                         q.dtype),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, scale, causal, blocks):
    out, _ = _flash_fwd(q, k, v, scale, causal, *blocks[0])
    return out


def _flash_core_fwd(q, k, v, scale, causal, blocks):
    out, lse = _flash_fwd(q, k, v, scale, causal, *blocks[0])
    return out, (q, k, v, out, lse)


def _flash_core_bwd(scale, causal, blocks, res, do):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, do, scale, causal, *blocks[1:])


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_4d(q, k, v, scale, causal, blocks):
    """[b, s, h, d] in and out around the [b*h, s, d] kernel layout."""
    b, sq, h, d = q.shape

    def _merge(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    out = _flash_core(_merge(q), _merge(k), _merge(v), scale, causal, blocks)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def flash_attention(q, k, v, *, causal=True, scale=None,
                    block_q=None, block_k=None):
    """q/k/v: [batch, seq, heads, head_dim] -> same-shape output.

    Requirements: no attention mask (causal flag instead), no dropout —
    callers fall back to the math sdpa otherwise (nn_ops dispatch).

    ``block_q``/``block_k`` left None are chosen for each of the three
    kernels by ``choose_blocks``; one that is given holds for all three.

    Inside a sharded program the caller declares the mesh axes of the
    batch and head dims with ``_compat.spmd_axes`` and the kernel runs
    per shard under ``shard_map``: Mosaic kernels have no automatic SPMD
    rule, and attention is independent per batch row and per head."""
    sq, d = q.shape[1], q.shape[3]
    sk = k.shape[1]
    blocks = []
    for kernel in KERNELS:
        bq, bk = choose_blocks(sq, sk, d, q.dtype, kernel)
        bq = bq if block_q is None else min(int(block_q), sq)
        bk = bk if block_k is None else min(int(block_k), sk)
        # The kernel has no padding mask for partial tail blocks;
        # out-of-range rows/cols would silently attend to block padding.
        if sq % bq or sk % bk:
            raise ValueError(
                f"flash_attention requires seq lengths divisible by the "
                f"block sizes: got sq={sq}, sk={sk} with block_q={bq}, "
                f"block_k={bk}; pad the sequence or use the math sdpa"
            )
        blocks.append((bq, bk))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    fn = functools.partial(
        _flash_4d, scale=float(scale), causal=bool(causal),
        blocks=tuple(blocks),
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
