"""Kimi Delta Attention (kernels/pallas/kda.py): the Pallas kernels under
the interpreter and the jax.numpy chunked form against the recurrence run
token by token, outputs and all five gradients; decays pinned at the floor
over whole chunks, where referring a chunk to one row would overflow; the
scalar-per-head decay against the gated delta rule's own chunked form;
bf16 operands; the tile and the counters.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.pallas import gated_delta_rule as G
from paddle_tpu.kernels.pallas import kda as K

HI = jax.lax.Precision.HIGHEST


def recurrent(q, k, v, g, beta):
    """The recurrence as written, token by token, in float32: what the
    tests hold the chunked forms to. q, k, g [B, T, H, d_k]."""
    b, t, h, dk = q.shape
    dv = v.shape[3]
    f32 = jnp.float32

    def step(state, x):
        qt, kt, vt, gt, bt = x                   # [B, H, d], [B, H]
        state = jnp.exp(gt)[..., None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt,
                                             precision=HI))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=HI)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0)
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _inputs(b, t, h, dk, dv, seed=0, dtype=jnp.float32, floor=False):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    if floor:   # every channel at the floor's edge, every token
        g = jnp.full((b, t, h, dk), K.LOWER_BOUND * 0.999)
    else:       # the safe gate over its whole range, a value a channel
        g = K.LOWER_BOUND * jax.nn.sigmoid(
            2.0 * jax.random.normal(ks[3], (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _value_and_grads(fn, weights):
    """jit(value and the five gradients) of sum(fn(...) * weights)."""
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * weights), o
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True))


def _rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


@pytest.mark.parametrize("impl,t,chunk", [
    ("pallas", 128, 64), ("pallas", 37, 16), ("xla", 100, 64),
    ("xla", 37, 16)])
def test_chunked_forms_match_the_recurrence(impl, t, chunk):
    """Output and all five gradients, at lengths that are and are not
    multiples of the chunk."""
    args = _inputs(2, t, 2, 32, 16, seed=t)
    w = jnp.cos(jnp.arange(16.0))
    (_, o), grads = _value_and_grads(
        lambda *a: K.kda(*a, chunk=chunk, impl=impl), w)(*args)
    (_, ref), want = _value_and_grads(recurrent, w)(*args)
    assert o.shape == ref.shape
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-6,
                               rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), grads, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * scale, name
    if impl == "pallas":
        # and against chunked_kda, the kernels' oracle, directly
        (_, oc), chunked = _value_and_grads(
            lambda *a: K.chunked_kda(*a, chunk=chunk), w)(*args)
        np.testing.assert_allclose(np.asarray(o), np.asarray(oc), atol=2e-6,
                                   rtol=1e-5)
        for name, a, b in zip("q k v g beta".split(), grads, chunked):
            assert _rel(a, b) <= 2e-5, (name, _rel(a, b))


def test_decays_at_the_floor_over_whole_chunks_stay_finite():
    """g at the floor on every channel of every token of two chunks of 64:
    a chunk referred to one row would need exp(315) and overflow; the
    blocks of 16 need exp(75)."""
    args = _inputs(1, 128, 2, 32, 16, seed=2, floor=True)
    assert float(-args[3].sum(axis=1).max()) > 600       # the running sum
    w = jnp.sin(jnp.arange(16.0))
    (_, o), grads = _value_and_grads(
        lambda *a: K.kda(*a, impl="pallas"), w)(*args)
    (_, ref), want = _value_and_grads(recurrent, w)(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-6,
                               rtol=1e-5)
    for name, a, b in zip("q k v g beta".split(), grads, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # the running sums reach -640 here, where float32's spacing is 6e-5
        assert _rel(a, b) <= 1e-4, (name, _rel(a, b))


def test_one_chunk_referred_to_one_row_would_overflow():
    """What the blocks guard against, shown: at the floor the factor a
    chunk of 64 would carry is inf in float32, a block of 16's is not."""
    g = jnp.full((64,), K.LOWER_BOUND * 0.999)
    gsum = jnp.cumsum(g)
    assert not bool(jnp.isfinite(jnp.exp(gsum[0] - gsum[-1])))
    assert bool(jnp.isfinite(jnp.exp(gsum[0] - gsum[K.SUB - 1])))


def test_one_decay_a_head_is_the_gated_delta_rule():
    """g equal over a head's channels: the kernels are Gated DeltaNet, and
    agree with its chunked form, forward and gradients."""
    q, k, v, g, beta = _inputs(1, 96, 2, 32, 16, seed=3)
    scalar = g[..., 0]
    w = jnp.cos(jnp.arange(16.0))
    wide = lambda s: jnp.broadcast_to(s[..., None], g.shape)
    (_, o), grads = _value_and_grads(
        lambda q, k, v, s, b: K.kda(q, k, v, wide(s), b, impl="pallas"),
        w)(q, k, v, scalar, beta)
    (_, ref), want = _value_and_grads(G.chunked_gated_delta_rule, w)(
        q, k, v, scalar, beta)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-6,
                               rtol=1e-5)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5 * float(jnp.max(jnp.abs(b))))


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    """Also at the floor, where dg is small beside the products it comes
    from (the diagonal of Attn taken apart from the blocks' factors: 124 %
    off without that)."""
    w = jnp.cos(jnp.arange(128.0))
    kernels = _value_and_grads(lambda *a: K.kda(*a, impl="pallas"), w)
    plain = _value_and_grads(recurrent, w)
    for floor in (False, True):
        args = _inputs(1, 128, 2, 128, 128, seed=4, dtype=jnp.bfloat16,
                       floor=floor)
        (_, o), grads = kernels(*args)
        (_, ref), want = plain(*(x.astype(jnp.float32) for x in args))
        assert o.dtype == jnp.bfloat16
        gap = float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref)))
        assert gap < 3e-2 * float(jnp.max(jnp.abs(ref)))
        for name, a, b in zip("q k v g beta".split(), grads, want):
            assert _rel(a, b) < 2e-2, (floor, name, _rel(a, b))


def test_flat_operands_equal_the_heads_form():
    q, k, v, g, beta = _inputs(2, 64, 2, 32, 16, seed=5)
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    o = jax.jit(lambda *a: K.kda(*a, num_heads=2, impl="pallas"))(
        flat(q), flat(k), flat(v), flat(g), beta)
    want = jax.jit(lambda *a: K.kda(*a, impl="pallas"))(q, k, v, g, beta)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(flat(want)))
    with pytest.raises(ValueError, match="num_heads"):
        K.kda(flat(q), flat(k), flat(v), flat(g), beta)


def test_the_cells_shape_gets_eight_heads_a_step_under_the_budget():
    heads, chunks = K.choose_tile(8192, 32, 128, 128, 64, jnp.bfloat16)
    assert (heads, chunks) == (8, 4)
    from paddle_tpu.kernels.pallas.flash_attention import VMEM_BUDGET_BYTES
    assert K._vmem_bytes(heads, 128, 128, 64, chunks, 2) <= VMEM_BUDGET_BYTES
    assert K._vmem_bytes(16, 128, 128, 64, chunks, 2) > VMEM_BUDGET_BYTES
    assert K.choose_tile(100, 2, 32, 16, 64, jnp.float32) == (2, 2)


def test_a_traced_kernel_call_is_counted_by_its_tile():
    from paddle_tpu.kernels.pallas import _compat

    args = _inputs(1, 64, 2, 32, 16, seed=7)
    before = _compat.kda_blocks()
    jax.jit(jax.grad(lambda *a: jnp.sum(K.kda(*a, impl="pallas")),
                     argnums=(0,))).lower(*args)
    after = _compat.kda_blocks()
    for kernel in ("kda_fwd", "kda_bwd"):
        assert after.get((kernel, 2, 1), 0) == before.get(
            (kernel, 2, 1), 0) + 1


@pytest.mark.parametrize("bad", ["cuda", "", None])
def test_unknown_impl_is_refused(bad):
    args = _inputs(1, 16, 1, 8, 8)
    with pytest.raises(ValueError, match="impl"):
        K.kda(*args, impl=bad)


def test_a_decay_a_head_is_refused_where_one_a_channel_is_asked():
    q, k, v, g, beta = _inputs(1, 16, 2, 8, 8)
    with pytest.raises(ValueError, match="g"):
        K.kda(q, k, v, g[..., 0], beta)


def test_the_op_is_the_kernels_public_face():
    import paddle_tpu as paddle

    args = _inputs(1, 16, 2, 16, 16, seed=8)
    o = paddle.ops.kda(*(paddle.to_tensor(np.asarray(x)) for x in args))
    np.testing.assert_allclose(o.numpy(), np.asarray(recurrent(*args)),
                               atol=2e-6, rtol=1e-5)
