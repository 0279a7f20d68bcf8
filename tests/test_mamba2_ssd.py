"""Mamba-2's selective scan (kernels/pallas/mamba2_ssd.py): the Pallas
kernels under the interpreter and the jax.numpy chunked form against the
recurrence run token by token, the output and the gradients of all six
operands (x, dt, A, B, C, D), at lengths that are and are not multiples of
the chunk, with the carry crossing chunks and head groups, with heads in
several B/C groups, and with decays so large that ``exp(s_t) *
exp(-s_i)`` would overflow.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.pallas import mamba2_ssd as S

OPERANDS = ("x", "dt", "A", "B", "C", "D")


def plain_recurrence(x, dt, a, b, c, d, n_groups=1):
    """The recurrence as written, in numpy float64, one token at a time:
    independent of the module's own yardstick."""
    x, dt, a, b, c, d = (np.asarray(v, np.float64) for v in (x, dt, a, b, c,
                                                              d))
    bsz, t, hp = x.shape
    h = dt.shape[-1]
    p, n = hp // h, b.shape[-1] // n_groups
    rep = h // n_groups
    x = x.reshape(bsz, t, h, p)
    b = np.repeat(b.reshape(bsz, t, n_groups, n), rep, axis=2)
    c = np.repeat(c.reshape(bsz, t, n_groups, n), rep, axis=2)
    state = np.zeros((bsz, h, n, p))
    y = np.zeros((bsz, t, h, p))
    for i in range(t):
        state = (np.exp(dt[:, i] * a)[..., None, None] * state
                 + (dt[:, i, :, None] * b[:, i])[..., None]
                 * x[:, i, :, None, :])
        y[:, i] = np.einsum("bhn,bhnp->bhp", c[:, i], state) + (
            d[:, None] * x[:, i])
    return y.reshape(bsz, t, hp)


def _inputs(b, t, h, p, n, g=1, seed=0, dtype=jnp.float32, steep=False):
    """x, B, C as a convolution's silu leaves them, dt after a softplus
    around 1, A from a mild decay to -64 (steep: a chunk's decay passes
    e^-88, so exp(-s_i) alone is infinite in float32), D around 1."""
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h * p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) + 1.0)
    a = -jnp.exp(jax.random.uniform(
        ks[2], (h,), minval=-2.0, maxval=jnp.log(64.0) if steep else 1.0))
    bm = jax.random.normal(ks[3], (b, t, g * n)).astype(dtype)
    cm = jax.random.normal(ks[4], (b, t, g * n)).astype(dtype)
    d = 1.0 + 0.3 * jax.random.normal(ks[5], (h,))
    w = jax.random.normal(ks[6], (b, t, h * p))
    return (x, dt, a, bm, cm, d), w


def _value_and_grads(fn, w):
    def loss(*ops):
        y = fn(*ops)
        return jnp.sum(y.astype(jnp.float32) * w), y
    return jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


# (b, t, h, p, n, groups, chunk): why the case is here
CASES = {
    "carry_crosses_chunks": (2, 32, 4, 8, 16, 1, 8),
    "length_not_a_multiple_of_the_chunk": (1, 37, 4, 8, 16, 1, 8),
    "shorter_than_a_chunk": (1, 5, 4, 8, 16, 1, 8),
    "two_b_c_groups": (1, 24, 4, 8, 16, 2, 8),
    "two_head_groups_a_chunk": (1, 48, 32, 8, 16, 1, 16),
    "heads_of_64_in_pairs": (1, 32, 16, 64, 128, 1, 16),
    "head_groups_in_two_b_c_groups": (2, 24, 32, 64, 16, 2, 8),
}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_forward_and_six_gradients_match_the_recurrence(impl, name):
    b, t, h, p, n, g, chunk = CASES[name]
    ops, w = _inputs(b, t, h, p, n, g, seed=1, steep=True)
    (_, want), want_grads = _value_and_grads(
        lambda *o: S.recurrent_mamba2_ssd(*o, n_groups=g), w)(*ops)
    (_, got), grads = _value_and_grads(
        lambda *o: S.mamba2_ssd(*o, chunk, n_groups=g, impl=impl), w)(*ops)
    # float32 throughout: what is left is the order of the sums
    assert _gap(got, want) < 2e-6
    for name_, a, r in zip(OPERANDS, grads, want_grads):
        assert a.shape == r.shape and a.dtype == r.dtype
        assert _gap(a, r) < 2e-5, name_


def test_the_modules_yardstick_is_the_recurrence_as_written():
    ops, _ = _inputs(2, 19, 4, 8, 16, 2, seed=2, steep=True)
    np.testing.assert_allclose(
        S.recurrent_mamba2_ssd(*ops, n_groups=2, block=8),
        plain_recurrence(*ops, n_groups=2), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_decay_that_would_overflow_as_a_product_of_exponentials(impl):
    """A = -64, dt ~ 1.3: s falls by ~83 a token, so exp(-s_i) is infinite
    in float32 from the second token of a chunk on. The chunked forms take
    s_t - s_i first and stay finite and right."""
    ops, w = _inputs(1, 32, 4, 8, 16, seed=3)
    ops = ops[:2] + (jnp.asarray([-64.0, -30.0, -8.0, -0.5]),) + ops[3:]
    s = jnp.cumsum(ops[1][0, :8] * ops[2], axis=0)
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.asarray(s, np.float32))).all()
    (_, got), grads = _value_and_grads(
        lambda *o: S.mamba2_ssd(*o, 8, impl=impl), w)(*ops)
    assert np.isfinite(np.asarray(got)).all()
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    np.testing.assert_allclose(got, plain_recurrence(*ops), rtol=2e-5,
                               atol=2e-6)


def test_bfloat16_operands_keep_the_fast_heads_gradient():
    """bf16 x, B, C (the cell's dtype) against the float32 recurrence: the
    output and five gradients to bf16 rounding, and dA **head by head**:
    a head with A = -64 keeps e^-80 of a token's neighbour, its dA is
    1e-12 of a slow head's, and it is still right to a few per cent, because
    the mask's cotangent is summed below the diagonal of one float32 tile
    (summed as two [Q, P] products that pass different bf16 roundings, a
    token's own entry leaves a residue 1e9 times that head's gradient)."""
    b, t, h, p, n = 1, 128, 16, 64, 128
    ops, w = _inputs(b, t, h, p, n, seed=4, dtype=jnp.bfloat16)
    ops = ops[:2] + (-4.0 * jnp.arange(1, h + 1, dtype=jnp.float32),
                     0.5 * ops[3], 0.5 * ops[4], ops[5])
    (_, want), want_grads = _value_and_grads(S.recurrent_mamba2_ssd, w)(*ops)
    (_, got), grads = _value_and_grads(
        lambda *o: S.mamba2_ssd(*o, 64, impl="pallas"), w)(*ops)
    assert _gap(got, want) < 1e-2
    for name, a, r in zip(OPERANDS, grads, want_grads):
        assert _gap(a, r) < 2e-2, name
    da, want_da = np.asarray(grads[2]), np.asarray(want_grads[2])
    assert abs(want_da[-1]) < 1e-9 * abs(want_da[0])
    np.testing.assert_allclose(da, want_da, rtol=0.1)


def test_padding_writes_nothing():
    ops, _ = _inputs(1, 40, 4, 8, 16, seed=5)
    full = S.mamba2_ssd(*ops, 8, impl="pallas")
    cut = tuple(v[:, :37] if v.ndim == 3 else v for v in ops)
    np.testing.assert_allclose(
        S.mamba2_ssd(*cut, 8, impl="pallas"), full[:, :37], atol=2e-6)


def test_the_kernels_carry_their_names_into_the_lowered_program():
    ops, w = _inputs(1, 16, 4, 8, 16, seed=6)
    text = _value_and_grads(
        lambda *o: S.mamba2_ssd(*o, 8, impl="pallas"), w).lower(
            *ops).as_text(debug_info=True)
    assert "mamba2_ssd_fwd" in text and "mamba2_ssd_bwd" in text


def test_a_traced_call_is_counted_by_its_chunk_and_its_grid_step():
    from paddle_tpu.kernels.pallas._compat import ssd_blocks, ssd_chunks

    chunks, blocks = ssd_chunks(), ssd_blocks()
    ops, w = _inputs(1, 48, 32, 8, 16, seed=7)
    _value_and_grads(lambda *o: S.mamba2_ssd(*o, 16, impl="pallas"), w)(*ops)
    key = (16, 8, 16, 1)                 # chunk, d_head, d_state, groups
    # the forward is traced twice under value_and_grad (with and without
    # its residuals); each traced kernel call is counted once
    assert ssd_chunks().get(key, 0) > chunks.get(key, 0)
    for kernel in ("mamba2_ssd_fwd", "mamba2_ssd_bwd"):
        step = (kernel, 16, 3)           # 16 of 32 heads a step, 3 chunks
        assert ssd_blocks().get(step, 0) > blocks.get(step, 0)
    # the jax.numpy form goes through no kernel and counts nothing
    before = ssd_chunks()
    S.mamba2_ssd(*ops, 16, impl="xla")
    assert ssd_chunks() == before


TILES = {
    # the cell: 64 heads of 64 over 8192 in bf16, the published chunk
    "the_cell": ((8192, 64, 64, 128, 1, 256, jnp.bfloat16), (16, 256)),
    "all_heads_when_they_are_few": ((64, 4, 8, 16, 1, 8, jnp.float32),
                                    (4, 8)),
    "a_group_bounds_the_step": ((64, 32, 64, 16, 2, 8, jnp.float32),
                                (16, 8)),
    "a_short_sequence_is_one_chunk": ((5, 4, 8, 16, 1, 256, jnp.float32),
                                      (4, 8)),
}


@pytest.mark.parametrize("name", sorted(TILES))
def test_the_tile_is_chosen_from_the_shapes(name):
    (t, h, p, n, g, chunk, dtype), tile = TILES[name]
    heads, q = S.choose_tile(t, h, p, n, g, chunk, dtype)
    assert (heads, q) == tile
    assert (h // g) % heads == 0 and heads <= S.MAX_HEADS
    assert S._vmem_bytes(heads, h, p, n, q, jnp.dtype(dtype).itemsize) <= (
        S.VMEM_BUDGET_BYTES)
    assert S.VMEM_BUDGET_BYTES < S.VMEM_LIMIT_BYTES


def test_a_budget_nothing_fits_halves_the_chunk_then_takes_the_least(
        monkeypatch):
    monkeypatch.setattr(S, "VMEM_BUDGET_BYTES", 6 * 2**20)
    heads, q = S.choose_tile(8192, 64, 64, 128, 1, 256, jnp.bfloat16)
    assert q < 256 and 256 % q == 0 and heads in (8, 16)
    monkeypatch.setattr(S, "VMEM_BUDGET_BYTES", 2**10)
    assert S.choose_tile(8192, 64, 64, 128, 1, 256, jnp.bfloat16) == (8, 8)


def test_the_op_is_registered_beside_the_delta_rule():
    import paddle_tpu as paddle
    from paddle_tpu import ops as F

    ops, _ = _inputs(1, 16, 4, 8, 16, seed=8)
    out = F.mamba2_ssd(*(paddle.to_tensor(np.asarray(v)) for v in ops),
                       chunk=8)
    np.testing.assert_allclose(out.numpy(), plain_recurrence(*ops),
                               rtol=2e-5, atol=2e-6)
    # one tape entry, differentiable through the tape in every operand
    tensors = [paddle.to_tensor(np.asarray(v), stop_gradient=False)
               for v in ops]
    F.mamba2_ssd(*tensors, chunk=8, impl="pallas").sum().backward()
    assert all(t.grad is not None and np.isfinite(t.grad.numpy()).all()
               for t in tensors)


@pytest.mark.parametrize("bad", ["cuda", "interpret"])
def test_unknown_impl_is_refused(bad):
    ops, _ = _inputs(1, 8, 4, 8, 16)
    with pytest.raises(ValueError, match="impl"):
        S.mamba2_ssd(*ops, impl=bad)


def test_shapes_that_do_not_divide_are_refused():
    ops, _ = _inputs(1, 8, 4, 8, 16)
    with pytest.raises(ValueError, match="groups"):
        S.mamba2_ssd(*ops, n_groups=3)
    with pytest.raises(ValueError, match="heads"):
        S.mamba2_ssd(ops[0][:, :, :30], *ops[1:])
