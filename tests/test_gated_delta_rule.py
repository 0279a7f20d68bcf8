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


# (t, chunk, hk, hv, d): what the tile should be, and why the case is here
TILES = {
    "rep1_all_key_heads": ((64, 16, 8, 8, 32), (8, 4)),
    "rep2_two_groups": ((64, 16, 16, 32, 32), (8, 4)),
    "rep4_two_groups": ((64, 16, 8, 32, 32), (4, 4)),
    # 8 key heads would hold 16 value heads, and 12 is no multiple of 8
    "key_heads_the_widest_step_does_not_divide": ((64, 16, 12, 24, 32),
                                                  (4, 4)),
    "one_chunk": ((16, 16, 2, 4, 32), (2, 1)),
    # 100 = 6.25 chunks: padded to two blocks of 4
    "padded_to_the_block": ((100, 16, 2, 4, 32), (2, 4)),
}


def _tile_case(name):
    (t, chunk, hk, hv, d), tile = TILES[name]
    chosen = G.choose_tile(t, hk, hv // hk, d, d, chunk, jnp.float32)
    return (t, chunk, hk, hv, d), tile, chosen


@pytest.mark.parametrize("name", sorted(TILES))
def test_the_tile_is_chosen_from_the_shapes(name):
    (t, chunk, hk, hv, d), tile, chosen = _tile_case(name)
    assert chosen == tile
    key_heads, chunks = chosen
    assert hk % key_heads == 0 and chunks * chunk <= -(-t // chunk) * chunk
    assert G._vmem_bytes(key_heads, hv // hk, d, d, chunk, chunks,
                         4) <= G.VMEM_BUDGET_BYTES


def test_the_cells_shape_gets_several_key_heads_under_the_budget():
    """qwen3next.pretrain-8k: 16 key and 32 value heads of 128 over 8192
    tokens in bf16."""
    key_heads, chunks = G.choose_tile(8192, 16, 2, 128, 128, 64,
                                      jnp.bfloat16)
    assert key_heads > 1 and 16 % key_heads == 0 and chunks > 1
    assert key_heads * 2 <= G.MAX_VALUE_HEADS
    need = G._vmem_bytes(key_heads, 2, 128, 128, 64, chunks, 2)
    assert need <= G.VMEM_BUDGET_BYTES < G.VMEM_LIMIT_BYTES


def test_a_shape_no_wider_step_fits_falls_back_to_one_key_head(monkeypatch):
    monkeypatch.setattr(G, "VMEM_BUDGET_BYTES", 2**20)
    assert G.choose_tile(8192, 16, 2, 128, 128, 64, jnp.bfloat16) == (1, 1)


@pytest.mark.parametrize("name", sorted(TILES))
def test_kernel_forward_matches_the_recurrence_at_each_tile(name):
    (t, chunk, hk, hv, d), _, _ = _tile_case(name)
    args = _inputs(1, t, hk, hv, d, d, seed=7)
    out = G.gated_delta_rule(*args, chunk=chunk, impl="pallas")
    np.testing.assert_allclose(out, recurrent(*args), atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("name", sorted(TILES))
def test_kernel_gradients_match_the_recurrence_at_each_tile(name):
    (t, chunk, hk, hv, d), _, _ = _tile_case(name)
    args = _inputs(1, t, hk, hv, d, d, seed=8)
    w = jax.random.normal(jax.random.key(9), (1, t, hv, d))
    ref = jax.grad(
        lambda *a: jnp.sum(recurrent(*a) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(
        lambda *a: jnp.sum(
            G.gated_delta_rule(*a, chunk=chunk, impl="pallas") * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    for label, a, b in zip("q k v g beta".split(), got, ref):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 5e-6 * scale, label


@pytest.mark.parametrize("rep", [2, 4])
def test_dq_and_dk_summed_in_the_kernel_equal_the_sum_made_outside(rep):
    """The value heads of a key head add their dq and dk in float32 inside
    the kernel: the same as a call with the key heads repeated by hand
    (a value head each), summed afterwards."""
    hk, t, d = 2, 48, 16
    q, k, v, g, beta = _inputs(1, t, hk, hk * rep, d, d, seed=10)
    w = jax.random.normal(jax.random.key(11), (1, t, hk * rep, d))

    def grads(q, k):
        return jax.grad(
            lambda q, k: jnp.sum(G.gated_delta_rule(
                q, k, v, g, beta, chunk=16, impl="pallas") * w),
            argnums=(0, 1))(q, k)

    inside = grads(q, k)
    outside = grads(jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2))
    for a, b in zip(inside, outside):
        summed = b.reshape(1, t, hk, rep, d).sum(3)
        np.testing.assert_allclose(a, summed, atol=1e-6, rtol=1e-5)


def test_a_traced_kernel_call_is_counted_by_its_tile():
    from paddle_tpu.kernels.pallas._compat import gdr_blocks

    before = gdr_blocks()
    args = _inputs(1, 40, 2, 4, 16, 8, seed=6)
    jax.grad(lambda *a: jnp.sum(G.gated_delta_rule(
        *a, chunk=16, impl="pallas")))(*args)
    after = gdr_blocks()
    # the forward under jax.grad and the backward, 2 key heads x 3 chunks
    for kernel in ("gated_delta_rule_fwd", "gated_delta_rule_bwd"):
        key = (kernel, 2, 3)
        assert after.get(key, 0) == before.get(key, 0) + 1
    assert set(after) - set(before) <= {
        ("gated_delta_rule_fwd", 2, 3), ("gated_delta_rule_bwd", 2, 3)}


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_flat_operands_equal_the_heads_form(impl):
    """q, k [B, T, H_k d_k] and v [B, T, H_v d_v] with ``num_k_heads``
    (what the kernels read in place, and what the DeltaNet mixer hands
    over) against the four-dimensional call: o comes back in v's form,
    and it and all five gradients are the same numbers. 40 tokens: the
    tail is padded in either form."""
    q, k, v, g, beta = _inputs(2, 40, 2, 4, 16, 8, seed=12)
    w = jax.random.normal(jax.random.key(13), v.shape)

    def run(w, *args, **kwargs):
        def loss(*a):
            o = G.gated_delta_rule(*a, chunk=16, impl=impl, **kwargs)
            return jnp.sum(o * w), o
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)

    heads, o_heads = run(w, q, k, v, g, beta)
    flat, o_flat = run(_flat(w), _flat(q), _flat(k), _flat(v), g, beta,
                       num_k_heads=2)
    assert o_flat.shape == (2, 40, 4 * 8) and o_flat.dtype == v.dtype
    np.testing.assert_array_equal(o_flat, _flat(o_heads))
    for name, a, b in zip("q k v g beta".split(), flat, heads):
        assert a.shape == (b.shape if b.ndim == 3 else _flat(b).shape), name
        np.testing.assert_array_equal(a, b.reshape(a.shape), err_msg=name)


def test_a_traced_call_is_counted_by_the_form_of_its_operands():
    from paddle_tpu.kernels.pallas._compat import gdr_operands

    q, k, v, g, beta = _inputs(1, 32, 1, 2, 16, 8, seed=6)
    before = gdr_operands()
    G.gated_delta_rule(q, k, v, g, beta, chunk=16, impl="xla")
    once = gdr_operands()
    assert once.get("heads", 0) == before.get("heads", 0) + 1
    assert once.get("flat", 0) == before.get("flat", 0)
    G.gated_delta_rule(_flat(q), _flat(k), _flat(v), g, beta, chunk=16,
                       impl="pallas", num_k_heads=1)
    twice = gdr_operands()
    assert twice.get("flat", 0) == once.get("flat", 0) + 1
    assert twice.get("heads", 0) == once.get("heads", 0)


def test_flat_operands_need_the_number_of_key_heads():
    q, k, v, g, beta = _inputs(1, 16, 2, 4, 8, 8)
    with pytest.raises(ValueError, match="num_k_heads"):
        G.gated_delta_rule(_flat(q), _flat(k), _flat(v), g, beta)
    # and the value heads, read from g, are held to them in this form too
    with pytest.raises(ValueError, match="value heads"):
        G.gated_delta_rule(_flat(q), _flat(k), _flat(v), g, beta,
                           num_k_heads=3)


def _adversarial_system(c, closeness, seed):
    """A = tril(beta exp(G_t - G_i) k_t . k_i, -1) of a chunk with beta 1,
    g 0 and unit k rows within ``closeness`` of one direction: the largest
    entries the inverse can have."""
    ks = jax.random.split(jax.random.key(seed), 2)
    base = jax.random.normal(ks[0], (1, 128))
    k = base + closeness * jax.random.normal(ks[1], (c, 128))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return jnp.tril(k @ k.T, -1).astype(jnp.float32)


@pytest.mark.parametrize("closeness", [0.1, 0.5, 1.0, 3.0, 10.0])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_three_pass_inverse_holds_on_adversarial_chunks(closeness, seed):
    """The bf16 kernels' inverse (doubled diagonal blocks, three
    single-pass products where ``HIGHEST`` takes six) against the exact
    inverse: under 1e-4 of its largest entry (1: the diagonal), 2^4 finer
    than the rounding to bf16 that follows, however close the k rows lie."""
    a = _adversarial_system(64, closeness, seed)
    exact = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    scale = np.abs(exact).max()
    high = np.asarray(G._inv_unit_lower_blocks(a))
    cheap = np.asarray(G._inv_unit_lower_blocks(a, split=True))
    assert np.abs(high - exact).max() < 1e-6 * scale
    assert np.abs(cheap - exact).max() < 1e-4 * scale
    as_bf16 = np.asarray(jnp.asarray(high).astype(jnp.bfloat16), np.float32)
    assert np.abs(cheap - exact).max() < np.abs(as_bf16 - high).max() / 16


@pytest.mark.parametrize("closeness", [0.1, 0.5])
def test_the_chunked_form_holds_on_correlated_keys(closeness):
    """``chunked_gated_delta_rule`` (the ``"xla"`` path, ``"auto"`` off the
    TPU, the yardstick of the kernels' tests) against the recurrence run
    token by token in float64, on unit keys within ``closeness`` of one
    direction with beta 1 and g 0, as a trained model's neighbouring keys
    are. A nilpotent series for the inverse is off by thousands there."""
    t, dk, dv = 128, 128, 16
    ks = jax.random.split(jax.random.key(7), 4)
    k = jax.random.normal(ks[0], (1, dk)) + closeness * jax.random.normal(
        ks[1], (t, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[2], (t, dk)) / dk
    v = jax.random.normal(ks[3], (t, dv))
    state = np.zeros((dk, dv))
    exact = np.zeros((t, dv))
    for i, (qt, kt, vt) in enumerate(zip(*(
            np.asarray(x, np.float64) for x in (q, k, v)))):
        state += np.outer(kt, vt - state.T @ kt)
        exact[i] = state.T @ qt
    got = G.chunked_gated_delta_rule(
        q[None, :, None], k[None, :, None], v[None, :, None],
        jnp.zeros((1, t, 1)), jnp.ones((1, t, 1)), chunk=64)
    assert np.abs(np.asarray(got)[0, :, 0] - exact).max() < (
        1e-5 * np.abs(exact).max())


@pytest.mark.parametrize("c", [2, 4, 12, 16, 64])
def test_the_block_inverse_at_other_chunk_sizes_and_in_pairs(c):
    """Any chunk size (12: the last block of a doubling is partial), one
    matrix at a time and two side by side in the lanes, which is what a
    batch of heads under ``jax.vmap`` gets."""
    a = jnp.stack([_adversarial_system(c, 1.0, seed) for seed in range(4)])
    exact = np.stack([np.linalg.inv(np.eye(c) + np.asarray(x, np.float64))
                      for x in a])
    for split, atol in ((False, 1e-6), (True, 1e-4)):
        one = jax.vmap(lambda x: G._inv_unit_lower_blocks(x, split))(a)
        np.testing.assert_allclose(one, exact, atol=atol)
        pairs = G._inv_unit_lower_pairs(a, split)
        np.testing.assert_allclose(pairs, exact, atol=atol)
        np.testing.assert_array_equal(
            jax.vmap(G._inv_heads(split))(a), pairs)
        # an odd batch falls back to one at a time
        np.testing.assert_allclose(
            jax.vmap(G._inv_heads(split))(a[:3]), exact[:3], atol=atol)


def test_bfloat16_gap_is_no_wider_than_the_highest_inverse_gives():
    """The kernels' gap to the float32 recurrence, output and gradients,
    against the gap of the ``jax.numpy`` form (the inverse at ``HIGHEST``,
    its own inverse in the backward) on the same bf16 inputs: the cheaper
    inverse and the kept one eat none of the room."""
    args = _inputs(1, 128, 2, 4, 32, 16, seed=2, dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    w = jax.random.normal(jax.random.key(3), (1, 128, 4, 16))

    def readings(fn, inputs):
        def loss(*a):
            o = fn(*a).astype(jnp.float32)
            return jnp.sum(o * w), o
        grads, o = jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            *inputs)
        return (o,) + tuple(x.astype(jnp.float32) for x in grads)

    ref = readings(recurrent, wide)
    gaps = {}
    for impl in ("pallas", "xla"):
        got = readings(
            lambda *a: G.gated_delta_rule(*a, chunk=16, impl=impl), args)
        gaps[impl] = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                      for a, b in zip(got, ref)]
    for kernel, plain in zip(gaps["pallas"], gaps["xla"]):
        assert kernel <= 1.02 * plain


@pytest.mark.parametrize("bad", ["cuda", "interpret"])
def test_unknown_impl_is_refused(bad):
    args = _inputs(1, 16, 1, 1, 8, 8)
    with pytest.raises(ValueError, match="impl"):
        G.gated_delta_rule(*args, impl=bad)


def test_value_heads_must_be_a_multiple_of_key_heads():
    q, k, v, g, beta = _inputs(1, 16, 2, 3, 8, 8)
    with pytest.raises(ValueError, match="value heads"):
        G.gated_delta_rule(q, k, v, g, beta)
