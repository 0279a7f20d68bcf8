"""The chunked gated delta rule (kernels/pallas/gated_delta_rule.py): the
Pallas kernels under the interpreter and the jax.numpy chunked form against
the recurrence run token by token, outputs and all five gradients, at
lengths that are and are not multiples of the chunk.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.pallas import gated_delta_rule as G


def recurrent(q, k, v, g, beta):
    """The recurrence as written, token by token, in float32: what the
    tests hold the chunked forms to."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    rep = hv // hk
    f32 = jnp.float32
    q = jnp.repeat(q.astype(f32), rep, axis=2)
    k = jnp.repeat(k.astype(f32), rep, axis=2)

    def step(state, x):
        qt, kt, vt, gt, bt = x                   # [B, H, d], [B, H]
        state = jnp.exp(gt)[..., None, None] * state
        u = bt[..., None] * (vt - jnp.einsum(
            "bhkv,bhk->bhv", state, kt, precision=jax.lax.Precision.HIGHEST))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt, precision=jax.lax.Precision.HIGHEST)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (
        q, k, v.astype(f32), g.astype(f32), beta.astype(f32)))
    _, o = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


def _inputs(b, t, hk, hv, dk, dv, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, t, hk, dk))
    k = jax.random.normal(ks[1], (b, t, hk, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, hv, dv))
    # gates from a mild to a hard decay, as A in (0, 16) gives
    g = -0.3 * jnp.exp(jax.random.uniform(ks[3], (b, t, hv), minval=-3,
                                          maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


LENGTHS = [(64, 16), (128, 64), (100, 16), (37, 16), (16, 64)]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("t,chunk", LENGTHS)
def test_chunked_forward_matches_the_recurrence(impl, t, chunk):
    args = _inputs(2, t, 2, 4, 32, 16)
    ref = recurrent(*args)
    out = G.gated_delta_rule(*args, chunk=chunk, impl=impl)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("t,chunk", [(64, 16), (100, 16), (37, 16)])
def test_chunked_gradients_match_the_recurrence(impl, t, chunk):
    args = _inputs(2, t, 2, 4, 32, 16, seed=1)
    w = jax.random.normal(jax.random.key(9), (2, t, 4, 16))
    ref = jax.grad(
        lambda *a: jnp.sum(recurrent(*a) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(
        lambda *a: jnp.sum(
            G.gated_delta_rule(*a, chunk=chunk, impl=impl) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 5e-6 * scale, name


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    """bf16 operands on the MXU, float32 state and accumulators: the
    kernel's outputs and gradients lie within bf16 rounding of the
    recurrence on the same (rounded) inputs."""
    args = _inputs(1, 128, 2, 4, 32, 16, seed=2, dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    w = jax.random.normal(jax.random.key(3), (1, 128, 4, 16))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    ref = jax.grad(loss(recurrent),
                   argnums=(0, 1, 2, 3, 4))(*wide)
    got = jax.grad(
        loss(lambda *a: G.gated_delta_rule(*a, chunk=16, impl="pallas")),
        argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, ref):
        err = jnp.linalg.norm(a.astype(jnp.float32) - b)
        assert float(err / jnp.linalg.norm(b)) < 2e-2


def test_value_heads_share_their_key_head_in_order():
    """Value head h reads key head h // (H_v / H_k), as
    repeat_interleave gives: permuting the key heads changes the result,
    repeating them by hand does not."""
    q, k, v, g, beta = _inputs(1, 32, 2, 4, 8, 8, seed=4)
    out = G.gated_delta_rule(q, k, v, g, beta, chunk=16, impl="pallas")
    by_hand = G.gated_delta_rule(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta,
        chunk=16, impl="pallas")
    np.testing.assert_allclose(out, by_hand, atol=1e-6)
    swapped = G.gated_delta_rule(q[:, :, ::-1], k[:, :, ::-1], v, g, beta,
                                 chunk=16, impl="pallas")
    assert float(jnp.abs(out - swapped).max()) > 1e-3


def test_padding_writes_nothing():
    """A length that is no multiple of the block is padded with tokens of
    beta 0 and g 0: the first tokens' outputs are those of the longer
    run."""
    args = _inputs(1, 48, 1, 2, 16, 16, seed=5)
    short = tuple(a[:, :37] for a in args)
    full = G.gated_delta_rule(*args, chunk=16, impl="pallas")
    cut = G.gated_delta_rule(*short, chunk=16, impl="pallas")
    np.testing.assert_allclose(cut, full[:, :37], atol=2e-6)


def test_a_traced_kernel_call_is_counted_by_its_sizes():
    from paddle_tpu.observability import counter

    series = counter("paddle_tpu_kernels_gdr_chunk", "",
                     labelnames=("chunk", "d_k", "d_v"))

    def total():
        return {tuple(sorted(labels.items())): child.value
                for labels, child in series._series()}

    before = total()
    args = _inputs(1, 32, 1, 2, 16, 8, seed=6)
    G.gated_delta_rule(*args, chunk=16, impl="pallas")
    key = (("chunk", "16"), ("d_k", "16"), ("d_v", "8"))
    assert total().get(key, 0) == before.get(key, 0) + 1


@pytest.mark.parametrize("bad", ["cuda", "interpret"])
def test_unknown_impl_is_refused(bad):
    args = _inputs(1, 16, 1, 1, 8, 8)
    with pytest.raises(ValueError, match="impl"):
        G.gated_delta_rule(*args, impl=bad)


def test_value_heads_must_be_a_multiple_of_key_heads():
    q, k, v, g, beta = _inputs(1, 16, 2, 3, 8, 8)
    with pytest.raises(ValueError, match="value heads"):
        G.gated_delta_rule(q, k, v, g, beta)
