"""Pallas TPU flash attention.

Replaces the reference's dynloaded flash-attn CUDA kernels
(ref: python/paddle/nn/functional/flash_attention.py:242,
phi/backends/dynload/flashattn.cc) with three TPU-native Pallas kernels:
an online-softmax forward saving per-row logsumexp, and a blocked backward
(`dq`; `dk` and `dv`) recomputing probabilities (no s×s materialization in
HBM either way).

Layout contract. The public API takes q/k/v as [batch, seq, heads,
head_dim]. A kernel operand comes in one of two forms, told apart by its
leading dim (``_head_spec``, one index-map helper): [batch*heads, seq, d],
a head a row, which ``flash_attention`` hands its kernels; or flat [batch,
seq, heads*d], a head a column block of d, read in place where d is a
multiple of 128 (a lane tile), which ``mla_attention`` hands them for its
128-wide parts. On a TPU a reshape between [b, s, h*d] and [b, s, h, d] is
a relayout, so the flat form spares a transpose of every such operand, of
the output and of each cotangent, forward and backward. An output and a
cotangent take their operand's form. The per-row statistics (logsumexp,
delta) are [batch*heads, 8, seq] in either; delta = rowsum(dO * O) is an
XLA reduction of the first form and the `dq` kernel's of the second.

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

Latent attention (MLA). The kernels take the score as a sum of products
into one tile (``parts``): ``mla_attention`` gives them ``q_nope k_nope^T``
over each head's own 128 columns and ``q_rope k_rope^T`` over 64 rotary
columns whose key is ONE head read by all, under values of their own
width. q_nope, k_nope and v (so out, dO and their cotangents) are flat,
[batch, seq, heads*128], as the block's projections write them
(``_compat.record_mla_operands``: ``flat``; parts narrower than 128 are
merged: ``heads``); the rotary query, half a lane tile wide, is [batch*
heads, seq, 64]. The shared key is an operand of its own, [batch, seq,
64], whose index map drops the head: it is never broadcast to the heads
in HBM, and no head is padded to a common width. `dk`/`dv` then walks
(batch, k block, head, q block): the shared key's block stays resident
for all heads and its cotangent is summed over the head axis in VMEM (on
a v5e at [2, 8192, 32 heads] forward + backward take 56.2 ms so, 56.9
with a float32 [batch * heads, seq, 64] output summed by XLA). The same
three kernels, under names of their own (``MLA_KERNELS``).

Residuals. Beside q, k and v the backward kernels read what the forward
kernel wrote: the output (in v's form) and the per-row logsumexp. The
forward rules name the two (``ATTENTION_OUT``, ``ATTENTION_LSE``;
``checkpoint_name`` is the identity and lowers to nothing), so that a
caller's ``jax.checkpoint`` can keep them by policy:
``distributed.recompute`` does, and a rematerialised layer then runs the
forward kernel once a step, not twice.

On non-TPU backends the kernels run in interpreter mode so the numerics
are testable on the 8-device CPU mesh (conftest).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._compat import (current_spmd_axes, pl_call, record_flash_blocks,
                      record_mla_blocks, record_mla_operands,
                      record_recompute_kept)

NEG_INF = -1e30

KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")
FWD, BWD_DQ, BWD_DKV = KERNELS
MLA_KERNELS = ("mla_attention_fwd", "mla_attention_bwd_dq",
               "mla_attention_bwd_dkv")
# the forward kernel's two outputs, as the forward rules name them for a
# caller's checkpoint policy (the docstring's Residuals)
ATTENTION_OUT = "attention_out"
ATTENTION_LSE = "attention_lse"

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


def _compiler_params(kernel, block_q, block_k, head_dim, dtype, sweeps=1):
    need = _vmem_bytes(kernel, block_q, block_k, head_dim,
                       jnp.dtype(dtype).itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + sweeps * ("arbitrary",),
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




def _score(pairs):
    """The score tile: the sum over the parts' (a, b) of a @ b^T,
    float32."""
    s = None
    for a, b in pairs:
        part = _nt(a, b)
        s = part if s is None else s + part
    return s


def _split(refs, *counts):
    """``refs`` cut into consecutive groups of ``counts`` refs."""
    out, at = [], 0
    for n in counts:
        out.append(refs[at:at + n])
        at += n
    assert at == len(refs)
    return out


# ---------------------------------------------------------------- forward
def _fwd_kernel(*refs, parts, scale, causal, block_q, block_k):
    """refs: ``parts`` q and ``parts`` k operands (the score is the sum
    of their products), v; o, lse; scratch."""
    q_refs, k_refs, (v_ref, o_ref, lse_ref), qs_scrs, (
        m_scr, l_scr, acc_scr) = _split(refs, parts, parts, 3, parts, 3)
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        for qs_scr, q_ref in zip(qs_scrs, q_refs):
            qs_scr[:] = _scaled(q_ref[0], scale)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit(masked):
        v = v_ref[0]
        s = _score((qs_scr[:], k_ref[0])             # [bq, bk]
                   for qs_scr, k_ref in zip(qs_scrs, k_refs))
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
    """Where a [block_k, d] operand's block lies along the sequence in a
    (b, q block, k block) grid. Causal: a step above the diagonal names the
    last block the q block sees any of, which is resident, so nothing is
    fetched for it."""
    if not causal:
        return lambda b, i, j: j

    def last(i):
        return jnp.minimum((i * block_q + block_q - 1) // block_k, nk - 1)

    return lambda b, i, j: jnp.minimum(j, last(i))


def _head_spec(x, block, bh, rows, cols):
    """The BlockSpec of one head's [block, d] rows of ``x``, in the form
    ``x`` comes in (its leading dim tells): [b*h, s, d], a head a row, at
    ``rows(*grid)`` -> (row, block along s); or flat [b, s, h*d], a head a
    column block (d a multiple of 128 on a TPU), at ``cols(*grid)`` ->
    (batch, block along s, head)."""
    if x.shape[0] == bh:
        return pl.BlockSpec((1, block, x.shape[-1]),
                            lambda *g: (*rows(*g), 0))
    return pl.BlockSpec((1, block, _width(x, bh)), cols)


def _width(x, bh):
    """One head's columns of an operand in either form of ``_head_spec``."""
    return x.shape[-1] * x.shape[0] // bh


def _by_row(seq, heads):
    """(rows, cols) of ``_head_spec`` in a grid whose first index is the
    head's row b*h and ``seq(*grid)`` the block along s."""
    def rows(r, *g):
        return r, seq(r, *g)

    def cols(r, *g):
        return r // heads, seq(r, *g), r % heads

    return rows, cols


def _k_specs(ks, block_k, bh, at, heads):
    """A [block_k, d] block of every key part at ``at`` (``_by_row``'s
    pair); with ``heads``, the last part is one head shared by that many,
    [batch, seq, d], and its index drops the head."""
    rows, _ = at
    specs = [_head_spec(k, block_k, bh, *at) for k in ks]
    if heads:
        specs[-1] = pl.BlockSpec(
            (1, block_k, ks[-1].shape[-1]),
            lambda b, *ij: (b // heads, rows(b, *ij)[1], 0))
    return specs


def _record(name, block_q, block_k):
    record = record_mla_blocks if name in MLA_KERNELS else record_flash_blocks
    record(name, block_q, block_k)


def _flash_fwd(qs, ks, v, scale, causal, block_q, block_k, name=FWD,
               heads=0):
    """qs, ks: the score's parts, tuples of [bh, s, d_part] or, with
    ``heads``, flat [b, s, heads * d_part] (``_head_spec``); with
    ``heads`` the last key part is [b, s, d_part], shared by that many
    heads. The output takes v's form."""
    bh = ks[-1].shape[0] * heads if heads else qs[0].shape[0]
    sq, sk = qs[0].shape[1], ks[0].shape[1]
    widths = [_width(q, bh) for q in qs]
    dv = _width(v, bh)
    nq, nk = sq // block_q, sk // block_k
    _record(name, block_q, block_k)
    at_q = _by_row(lambda b, i, j: i, heads)
    at_k = _by_row(_kv_index(causal, block_q, block_k, nk), heads)

    out, lse = pl_call(
        functools.partial(
            _fwd_kernel, parts=len(qs), scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        name=name,
        grid=(bh, nq, nk),
        in_specs=[
            *(_head_spec(q, block_q, bh, *at_q) for q in qs),
            *_k_specs(ks, block_k, bh, at_k, heads),
            _head_spec(v, block_k, bh, *at_k),
        ],
        out_specs=[
            _head_spec(v, block_q, bh, *at_q),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape[:1] + (sq,) + v.shape[2:],
                                 v.dtype),
            jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            *(pltpu.VMEM((block_q, w), q.dtype) for q, w in zip(qs, widths)),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(FWD, block_q, block_k, sum(widths),
                                         v.dtype),
    )(*qs, *ks, v)
    return out, lse


# --------------------------------------------------------------- backward
def _bwd_dq_kernel(*refs, parts, scale, causal, block_q, block_k,
                   own_delta=False):
    """With ``own_delta`` the kernel reads O beside dO, takes delta =
    rowsum(dO * O) of its q block once a sweep and writes it out for
    `dk`/`dv`: XLA would take it of the flat form only through a relayout
    of the float32 product."""
    if own_delta:
        q_refs, k_refs, (v_ref, do_ref, out_ref, lse_ref), dq_refs, (
            delta_ref,), qs_scrs, (lse_scr, delta_scr), acc_scrs = _split(
                refs, parts, parts, 4, parts, 1, parts, 2, parts)
    else:
        q_refs, k_refs, (v_ref, do_ref, lse_ref, delta_ref), dq_refs, \
            qs_scrs, (lse_scr, delta_scr), acc_scrs = _split(
                refs, parts, parts, 4, parts, parts, 2, parts)
    kb = pl.program_id(2)
    qb = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        for qs_scr, q_ref in zip(qs_scrs, q_refs):
            qs_scr[:] = _scaled(q_ref[0], scale)
        # the rows' statistics, turned into columns once a sweep
        lse_scr[:] = _column(lse_ref)
        if own_delta:
            col = jnp.sum(do_ref[0].astype(jnp.float32)
                          * out_ref[0].astype(jnp.float32),
                          axis=1, keepdims=True)
            delta_scr[:] = jnp.broadcast_to(col, delta_scr.shape)
            delta_ref[0] = jnp.broadcast_to(col.reshape(1, -1),
                                            delta_ref.shape[1:])
        else:
            delta_scr[:] = _column(delta_ref)
        for acc_scr in acc_scrs:
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit(masked):
        ks = [k_ref[0] for k_ref in k_refs]
        s = _score((qs_scr[:], k) for qs_scr, k in zip(qs_scrs, ks))
        if masked:
            s = jnp.where(
                _causal_mask(qb * block_q, kb * block_k, s.shape, 0),
                s, NEG_INF)
        p = jnp.exp(s - lse_scr[:, :1])
        dp = _nt(do_ref[0], v_ref[0])
        ds = p * (dp - delta_scr[:, :1])  # scale: at the sweep's end
        for acc_scr, k in zip(acc_scrs, ks):
            acc_scr[:] += _nn(ds.astype(k.dtype), k)

    _visit_block(_visit, causal, qb, kb, block_q, block_k)

    @pl.when(kb == nk - 1)
    def _fin():
        for dq_ref, acc_scr in zip(dq_refs, acc_scrs):
            dq_ref[0] = (acc_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, parts, heads, scale, causal, block_q, block_k):
    """The tiles are held transposed, [bk, bq]: the rows' statistics then
    broadcast along sublanes as the [1, bq] rows they are stored as, and
    both accumulating matmuls are plain [bk, bq] x [bq, d].

    Grid (b * h, k block, q block). With ``heads``: (b, k block, head, q
    block), and the last key part is one head shared by that many: its
    block, scaled copy and accumulator live through all the heads' sweeps,
    so its cotangent leaves summed over them."""
    q_refs, k_refs, (v_ref, do_ref, lse_ref, delta_ref), dk_refs, (
        dv_ref,), ks_scrs, dk_scrs, (dv_scr,) = _split(
            refs, parts, parts, 4, parts, 1, parts, parts, 1)
    sweep = 3 if heads else 2
    qb = pl.program_id(sweep)
    kb = pl.program_id(1)
    nq = pl.num_programs(sweep)
    own = parts - 1 if heads else parts     # parts of this head alone

    @pl.when(qb == 0)
    def _init():
        for ks_scr, k_ref in zip(ks_scrs[:own], k_refs):
            ks_scr[:] = _scaled(k_ref[0], scale)
        for dk_scr in dk_scrs[:own]:
            dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if heads:
        head = pl.program_id(2)

        @pl.when(jnp.logical_and(qb == 0, head == 0))
        def _init_shared():
            ks_scrs[-1][:] = _scaled(k_refs[-1][0], scale)
            dk_scrs[-1][:] = jnp.zeros_like(dk_scrs[-1])

    def _visit(masked):
        qs = [q_ref[0] for q_ref in q_refs]
        do = do_ref[0]
        st = _score((ks_scr[:], q)                    # [bk, bq]
                    for ks_scr, q in zip(ks_scrs, qs))
        if masked:
            st = jnp.where(
                _causal_mask(qb * block_q, kb * block_k, st.shape, 1),
                st, NEG_INF)
        pt = jnp.exp(st - lse_ref[0, :1, :])
        dv_scr[:] += _nn(pt.astype(do.dtype), do)
        dpt = _nt(v_ref[0], do)
        dst = pt * (dpt - delta_ref[0, :1, :])  # scale: at the sweep's end
        for dk_scr, q in zip(dk_scrs, qs):
            dk_scr[:] += _nn(dst.astype(q.dtype), q)

    _visit_block(_visit, causal, qb, kb, block_q, block_k)

    @pl.when(qb == nq - 1)
    def _fin():
        for dk_ref, dk_scr in zip(dk_refs[:own], dk_scrs):
            dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if heads:
        @pl.when(jnp.logical_and(qb == nq - 1, head == heads - 1))
        def _fin_shared():
            dk_refs[-1][0] = (dk_scrs[-1][:] * scale).astype(
                dk_refs[-1].dtype)


def _flash_bwd(qs, ks, v, out, lse, do, scale, causal, dq_blocks,
               dkv_blocks, names=KERNELS, heads=0):
    """-> (dqs, dks, dv), the parts' cotangents as tuples, each in its
    operand's form (``_flash_fwd``'s)."""
    bh = lse.shape[0]
    sq, sk = qs[0].shape[1], ks[0].shape[1]
    widths = [_width(q, bh) for q in qs]
    d, dv = sum(widths), _width(v, bh)
    parts = len(qs)
    # delta = rowsum(dO * O): of the [bh, s, d] form one XLA reduction,
    # sublane-replicated like lse (TPU block tiling rule); of the flat
    # form the `dq` kernel's (``own_delta``)
    own_delta = out.shape[0] != bh
    if not own_delta:
        delta_row = jnp.sum(
            do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )  # [bh, sq]
        delta = jnp.broadcast_to(delta_row[:, None, :], (bh, 8, sq))

    block_q, block_k = dq_blocks
    nq, nk = sq // block_q, sk // block_k
    _record(names[1], block_q, block_k)
    at_q = _by_row(lambda b, i, j: i, heads)
    at_k = _by_row(_kv_index(causal, block_q, block_k, nk), heads)
    row = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))
    q_specs = [_head_spec(q, block_q, bh, *at_q) for q in qs]
    do_spec = _head_spec(do, block_q, bh, *at_q)
    delta_shape = jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32)
    dqs = pl_call(
        functools.partial(
            _bwd_dq_kernel, parts=parts, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, own_delta=own_delta,
        ),
        name=names[1],
        grid=(bh, nq, nk),
        in_specs=[*q_specs,
                  *_k_specs(ks, block_k, bh, at_k, heads),
                  _head_spec(v, block_k, bh, *at_k), do_spec,
                  *((_head_spec(out, block_q, bh, *at_q), row) if own_delta
                    else (row, row))],
        out_specs=[*q_specs, *((row,) if own_delta else ())],
        out_shape=[*(jax.ShapeDtypeStruct(q.shape, q.dtype) for q in qs),
                   *((delta_shape,) if own_delta else ())],
        scratch_shapes=[
            *(pltpu.VMEM((block_q, w), q.dtype) for q, w in zip(qs, widths)),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            *(pltpu.VMEM((block_q, w), jnp.float32) for w in widths),
        ],
        compiler_params=_compiler_params(BWD_DQ, block_q, block_k, d,
                                         v.dtype),
    )(*qs, *ks, v, do, *((out, lse) if own_delta else (lse, delta)))
    if own_delta:
        *dqs, delta = dqs

    block_q, block_k = dkv_blocks
    nq, nk = sq // block_q, sk // block_k
    _record(names[2], block_q, block_k)
    if causal:
        # a step above the diagonal names the first q block that sees any
        # of this k block: fetched once, before the sweep reaches it
        def q_of(j, i):
            first = jnp.minimum((j * block_k) // block_q, nq - 1)
            return jnp.maximum(i, first)
    else:
        def q_of(j, i):
            return i
    if heads:
        # (batch, k block, head, q block): a head's own operands at row
        # batch * heads + head or at column block head of row batch, the
        # shared key's at batch
        grid = (bh // heads, nk, heads, nq)
        at_q = (lambda b, j, h, i: (b * heads + h, q_of(j, i)),
                lambda b, j, h, i: (b, q_of(j, i), h))
        at_k = (lambda b, j, h, i: (b * heads + h, j),
                lambda b, j, h, i: (b, j, h))
        at_row = lambda b, j, h, i: (b * heads + h, 0, q_of(j, i))
    else:
        grid = (bh, nk, nq)
        at_q = (lambda b, j, i: (b, q_of(j, i)), None)
        at_k = (lambda b, j, i: (b, j), None)
        at_row = lambda b, j, i: (b, 0, q_of(j, i))
    k_specs = [_head_spec(k, block_k, bh, *at_k) for k in ks]
    if heads:
        k_specs[-1] = pl.BlockSpec((1, block_k, ks[-1].shape[-1]),
                                   lambda b, j, h, i: (b, j, 0))
    v_spec = _head_spec(v, block_k, bh, *at_k)
    row = pl.BlockSpec((1, 8, block_q), at_row)
    *dks, dv_out = pl_call(
        functools.partial(
            _bwd_dkv_kernel, parts=parts, heads=heads, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k,
        ),
        name=names[2],
        grid=grid,
        in_specs=[*(_head_spec(q, block_q, bh, *at_q) for q in qs),
                  *k_specs, v_spec, _head_spec(do, block_q, bh, *at_q),
                  row, row],
        out_specs=[*k_specs, v_spec],
        out_shape=[
            *(jax.ShapeDtypeStruct(k.shape, k.dtype) for k in ks),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            *(pltpu.VMEM((block_k, w), k.dtype) for k, w in zip(ks, widths)),
            *(pltpu.VMEM((block_k, w), jnp.float32) for w in widths),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        compiler_params=_compiler_params(BWD_DKV, block_q, block_k, d,
                                         v.dtype, sweeps=len(grid) - 2),
    )(*qs, *ks, v, do, lse, delta)
    return tuple(dqs), tuple(dks), dv_out


# ------------------------------------------------------------- public op
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, scale, causal, blocks):
    out, _ = _flash_fwd((q,), (k,), v, scale, causal, *blocks[0])
    return out


def _named(out, lse):
    """The forward kernel's output and log-sum-exp, in the kernels' own
    layouts ([b*h, s, d] or flat [b, s, h*d]; [b*h, 8, s]), under their
    names."""
    return (checkpoint_name(out, ATTENTION_OUT),
            checkpoint_name(lse, ATTENTION_LSE))


def _flash_core_fwd(q, k, v, scale, causal, blocks):
    out, lse = _named(
        *_flash_fwd((q,), (k,), v, scale, causal, *blocks[0]))
    return out, (q, k, v, out, lse)


def _flash_core_bwd(scale, causal, blocks, res, do):
    q, k, v, out, lse = res
    (dq,), (dk,), dv = _flash_bwd(
        (q,), (k,), v, out, lse, do, scale, causal, *blocks[1:])
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_4d(q, k, v, scale, causal, blocks):
    """[b, s, h, d] in and out around the [b*h, s, d] kernel layout."""
    b, sq, h, d = q.shape

    def _merge(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

    out = _flash_core(_merge(q), _merge(k), _merge(v), scale, causal, blocks)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def _blocks_for(what, sq, sk, d, dtype, block_q, block_k):
    """(block_q, block_k) of the three kernels: ``choose_blocks``', or
    the caller's where given. The kernels have no padding mask for a
    partial tail block (out-of-range rows and columns would silently
    attend to block padding), so a length no block divides is refused."""
    blocks = []
    for kernel in KERNELS:
        bq, bk = choose_blocks(sq, sk, d, dtype, kernel)
        bq = bq if block_q is None else min(int(block_q), sq)
        bk = bk if block_k is None else min(int(block_k), sk)
        if sq % bq or sk % bk:
            raise ValueError(
                f"{what} requires seq lengths divisible by the "
                f"block sizes: got sq={sq}, sk={sk} with block_q={bq}, "
                f"block_k={bk}; pad the sequence or use the math sdpa"
            )
        blocks.append((bq, bk))
    return tuple(blocks)


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
    blocks = _blocks_for("flash_attention", sq, sk, d, q.dtype, block_q,
                         block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    record_recompute_kept("flash_attention")
    fn = functools.partial(
        _flash_4d, scale=float(scale), causal=bool(causal), blocks=blocks,
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


# ------------------------------------------------------- latent attention
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _mla_core(qn, qr, kn, kr, v, heads, scale, causal, blocks):
    out, _ = _flash_fwd((qn, qr), (kn, kr), v, scale, causal, *blocks[0],
                        name=MLA_KERNELS[0], heads=heads)
    return out


def _mla_core_fwd(qn, qr, kn, kr, v, heads, scale, causal, blocks):
    out, lse = _named(
        *_flash_fwd((qn, qr), (kn, kr), v, scale, causal, *blocks[0],
                    name=MLA_KERNELS[0], heads=heads))
    return out, (qn, qr, kn, kr, v, out, lse)


def _mla_core_bwd(heads, scale, causal, blocks, res, do):
    qn, qr, kn, kr, v, out, lse = res
    dqs, dks, dv = _flash_bwd(
        (qn, qr), (kn, kr), v, out, lse, do, scale, causal, *blocks[1:],
        names=MLA_KERNELS, heads=heads)
    return (*dqs, *dks, dv)


_mla_core.defvjp(_mla_core_fwd, _mla_core_bwd)


def mla_attention_xla(q_nope, q_rope, k_nope, k_rope, v, *, scale,
                      causal=True):
    """``mla_attention`` in plain ``jax.numpy``, float32: the path off
    the TPU, and what the kernels are tested against."""
    f32 = jnp.float32
    s = scale * (
        jnp.einsum("bqhd,bkhd->bhqk", q_nope.astype(f32), k_nope.astype(f32))
        + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(f32),
                     k_rope[:, :, 0].astype(f32)))
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                     v.astype(f32))
    return out.astype(v.dtype)


def mla_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale, causal=True,
                  block_q=None, block_k=None, impl="auto"):
    """Attention whose score is ``(q_nope . k_nope + q_rope . k_rope) *
    scale`` with the rotary key one head shared by all: q_nope, k_nope
    [batch, seq, heads, d_nope], q_rope [batch, seq, heads, d_rope],
    k_rope [batch, seq, 1, d_rope], v [batch, seq, heads, d_v] -> [batch,
    seq, heads, d_v]. Differentiable in all five; k_rope's cotangent is
    the sum over the heads.

    impl: ``"auto"`` is the kernels on a TPU (FLAGS_use_pallas_kernels)
    at lengths 128 divides and ``mla_attention_xla`` elsewhere;
    ``"pallas"`` is always the kernels (the interpreter off the TPU);
    ``"xla"`` always the ``jax.numpy`` form. Tiles as ``flash_attention``'s,
    chosen at the score's whole width."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(
            f'mla_attention impl must be "auto", "pallas" or "xla", got '
            f"{impl!r}")
    b, sq, heads, _ = q_nope.shape
    sk = k_nope.shape[1]
    if k_rope.shape != (b, sk, 1, q_rope.shape[-1]):
        raise ValueError(
            f"mla_attention: k_rope {k_rope.shape} is not one head "
            f"[{b}, {sk}, 1, {q_rope.shape[-1]}] for q_rope {q_rope.shape}")
    if impl == "auto":
        from ...core import device, flags

        impl = "pallas" if (
            device.on_tpu() and flags.get_flag("FLAGS_use_pallas_kernels")
            and sq % 128 == 0 and sk % 128 == 0
        ) else "xla"
    if impl == "xla":
        return mla_attention_xla(q_nope, q_rope, k_nope, k_rope, v,
                                 scale=scale, causal=causal)
    if current_spmd_axes() is not None:
        raise NotImplementedError(
            "mla_attention: the kernels have no shard_map wrapper yet; "
            "a sharded program takes impl='xla'")
    width = q_nope.shape[-1] + q_rope.shape[-1]
    blocks = _blocks_for("mla_attention", sq, sk, width, v.dtype, block_q,
                         block_k)
    record_recompute_kept("mla_attention")
    # a 128-wide part is read in place, its heads column blocks of [b, s,
    # h*d] (the reshape folds into the producer's); a narrower one would be
    # part of a lane tile, so it is merged to [b*h, s, d], as the rotary
    # query always is
    flat = q_nope.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
    record_mla_operands("flat" if flat else "heads")

    def merge(x):           # [b, s, h, d] -> [b*h, s, d]
        return jnp.swapaxes(x, 1, 2).reshape(-1, x.shape[1], x.shape[3])

    def join(x):            # [b, s, h, d] -> [b, s, h*d]
        return x.reshape(b, x.shape[1], -1)

    wide = join if flat else merge
    out = _mla_core(wide(q_nope), merge(q_rope), wide(k_nope),
                    k_rope[:, :, 0], wide(v), heads, float(scale),
                    bool(causal), blocks)
    if flat:
        return out.reshape(b, sq, heads, v.shape[-1])
    return jnp.swapaxes(out.reshape(b, heads, sq, v.shape[-1]), 1, 2)
