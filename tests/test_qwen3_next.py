"""models/qwen3_next.py and the expert layer that holds a share
(incubate/moe.py `held=`): the block's own operations against plain numpy,
the shares of a layer adding up to the whole, the grouped matmul's kernel
VJP, and the model on jit.TrainStep's normal path. The parity with the
plain reference (loss, every leaf's gradient, AdamW steps) is in
tests/benchmarks/test_qwen3_next_benchmark.py, beside the reference.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as F
from paddle_tpu.jit.api import _rng_lift
from paddle_tpu.incubate.moe import MoELayer
from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
from paddle_tpu.models import qwen3_next as Q
from paddle_tpu.models.qwen3_next import Qwen3NextMLP


def _t(a):
    return paddle.to_tensor(np.asarray(a))


# ------------------------------------------------------------ the block ops
def test_zero_centered_norm_is_one_plus_weight():
    x = np.random.default_rng(0).normal(size=(3, 8)).astype("float32")
    w = np.random.default_rng(1).normal(size=(8,)).astype("float32") * 0.1
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(
        F.zero_centered_rms_norm(_t(x), _t(w), 1e-6).numpy(), want,
        rtol=1e-5, atol=1e-6)
    # at its initial value the weight leaves the normalised input alone
    np.testing.assert_allclose(
        F.zero_centered_rms_norm(_t(x), _t(np.zeros(8, "float32"))).numpy(),
        x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6), rtol=1e-5)


def test_gated_norm_multiplies_by_silu_of_the_gate():
    rng = np.random.default_rng(2)
    x, z = rng.normal(size=(2, 5, 8)), rng.normal(size=(2, 5, 8))
    w = rng.normal(size=(8,))
    want = w * x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * (
        z / (1 + np.exp(-z)))
    got = Q._op(Q._gated_rms_norm, _t(x.astype("float32")),
                _t(w.astype("float32")), _t(z.astype("float32")),
                epsilon=1e-6)
    np.testing.assert_allclose(got.numpy(), want, rtol=2e-5, atol=1e-6)


def test_partial_rope_turns_the_first_dims_only():
    rng = np.random.default_rng(3)
    q = rng.normal(size=(1, 6, 2, 16)).astype("float32")
    k = rng.normal(size=(1, 6, 1, 16)).astype("float32")
    qr, kr = F.partial_rope_qk(_t(q), _t(k), rotary_dim=4, base=100.0)
    np.testing.assert_array_equal(qr.numpy()[..., 4:], q[..., 4:])
    np.testing.assert_array_equal(kr.numpy()[..., 4:], k[..., 4:])
    # rotate-half over the first 4 dims, frequencies over those 4
    pos = np.arange(6)[:, None]
    inv = 100.0 ** (-np.arange(2) / 2)
    c, s = np.cos(pos * inv)[None, :, None], np.sin(pos * inv)[None, :, None]
    x1, x2 = q[..., :2], q[..., 2:4]
    want = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    np.testing.assert_allclose(qr.numpy()[..., :4], want, atol=1e-5)
    # position 0 is not turned
    np.testing.assert_allclose(qr.numpy()[:, 0], q[:, 0], atol=1e-6)


def test_causal_conv_sees_the_past_only():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 7, 3)).astype("float32")
    w = rng.normal(size=(4, 3)).astype("float32")
    pad = np.concatenate([np.zeros((1, 3, 3), "float32"), x], 1)
    lin = sum(w[j] * pad[:, j:j + 7] for j in range(4))
    want = lin / (1 + np.exp(-lin))
    got = Q._op(Q._causal_conv_silu, _t(x), _t(w)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    x2 = x.copy()
    x2[:, 5:] += 1.0                    # the future of tokens 0..4
    got2 = Q._op(Q._causal_conv_silu, _t(x2), _t(w)).numpy()
    np.testing.assert_array_equal(got2[:, :5], got[:, :5])


def _hf_unpack(y, hk, hv, dk, dv):
    """HF's fix_query_key_value_ordering on the last axis of a projection
    (per key head: q | k | v of its value heads | z of them) -> the parts
    [q of all heads | k | v | z]: what the mixer did on activations before
    its weight took the interleave."""
    rep = hv // hk
    heads = y.reshape(y.shape[:-1] + (hk, 2 * dk + 2 * rep * dv))
    bounds = [0, dk, 2 * dk, 2 * dk + rep * dv, 2 * dk + 2 * rep * dv]
    return np.concatenate(
        [heads[..., lo:hi].reshape(y.shape[:-1] + (-1,))
         for lo, hi in zip(bounds, bounds[1:])], -1)


@pytest.mark.parametrize("hk,hv,dk,dv", [(2, 4, 3, 5), (2, 4, 128, 128)],
                         ids=["tiny_widths", "whole_lane_blocks"])
def test_unpack_follows_the_per_key_head_layout(hk, hv, dk, dv):
    """The interleave undone on the weight: the product with the permuted
    weight is, bit for bit, the unpacked product with the stored one
    (eighths of small integers: every sum is exact, whatever its order),
    and the gradient comes back in HF's column order."""
    rng = np.random.default_rng(10)
    rep, hidden = hv // hk, 16
    widths = (dk, dk, rep * dv, rep * dv)
    cols = hk * sum(widths)
    w = rng.integers(-8, 9, (hidden, cols)).astype("float32") / 8
    x = rng.integers(-8, 9, (2, 3, hidden)).astype("float32") / 8
    flat = Q._op(Q._head_major, _t(w), num_k_heads=hk, widths=widths)
    order = _hf_unpack(np.arange(cols), hk, hv, dk, dv)
    np.testing.assert_array_equal(flat.numpy(), w[:, order])
    np.testing.assert_array_equal(
        F.linear(_t(x), flat).numpy(),
        _hf_unpack(F.linear(_t(x), _t(w)).numpy(), hk, hv, dk, dv))
    # q of key head 1 stands after q of key head 0, k after all of q
    assert order[dk] == sum(widths) and order[hk * dk] == dk
    cot = rng.integers(-8, 9, (2, 3, cols)).astype("float32") / 8
    grad = jax.grad(lambda w: jnp.sum(jnp.matmul(x, Q._head_major(
        w, num_k_heads=hk, widths=widths)) * cot))(jnp.asarray(w))
    want = np.zeros_like(w)
    want[:, order] = np.einsum("bth,btc->hc", x, cot)
    np.testing.assert_array_equal(np.asarray(grad), want)


def test_the_gate_projection_is_unpacked_on_its_weight_too():
    """[b of a key head's value heads | a of them] -> [b of all | a]."""
    hk, rep = 2, 2
    ba = np.arange(3 * hk * 2 * rep, dtype="float32").reshape(3, -1)
    flat = Q._op(Q._head_major, _t(ba), num_k_heads=hk,
                 widths=(rep, rep)).numpy()
    heads = ba.reshape(3, hk, 2 * rep)
    np.testing.assert_array_equal(
        flat, np.concatenate([heads[:, :, :rep].reshape(3, -1),
                              heads[:, :, rep:].reshape(3, -1)], -1))


def test_prepare_normalises_scales_and_gates():
    rng = np.random.default_rng(5)
    hk, hv, dk = 2, 4, 4
    qk = rng.normal(size=(1, 5, 2 * hk * dk)).astype("float32")
    b, a = (rng.normal(size=(1, 5, hv)).astype("float32") for _ in "ba")
    a_log = np.log(rng.uniform(0.1, 16, hv)).astype("float32")
    dt = np.ones(hv, "float32")
    q, k, g, beta = (x.numpy() for x in Q._op(
        Q._delta_rule_inputs, _t(qk), _t(b), _t(a), _t(a_log), _t(dt),
        num_k_heads=hk, head_k_dim=dk))
    # the delta rule's flat form: a head is dk consecutive columns
    assert q.shape == k.shape == (1, 5, hk * dk)
    np.testing.assert_allclose(
        np.linalg.norm(k.reshape(1, 5, hk, dk), axis=-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(
        np.linalg.norm(q.reshape(1, 5, hk, dk), axis=-1), dk ** -0.5,
        atol=1e-4)
    np.testing.assert_allclose(
        g, -np.exp(a_log) * np.log1p(np.exp(a + dt)), rtol=1e-5)
    np.testing.assert_allclose(beta, 1 / (1 + np.exp(-b)), rtol=1e-5)
    assert (g < 0).all() and g.dtype == np.float32


@pytest.mark.parametrize("which", ["l2norm", "gated_norm"])
def test_the_flat_norms_equal_the_per_head_form(which):
    """The per-head sums taken on [b, t, heads * d] (a product with the
    heads' indicator, no reshape to heads) against plain numpy over
    [b, t, heads, d] in float64: float32 rounding apart."""
    rng = np.random.default_rng(11)
    heads, d = 4, 128
    x = rng.normal(size=(2, 6, heads * d)).astype("float32")
    by_head = x.astype("float64").reshape(2, 6, heads, d)
    if which == "l2norm":
        zeros = np.zeros((2, 6, heads), "float32")
        q, k, _, _ = Q._op(
            Q._delta_rule_inputs, _t(np.concatenate([x, x], -1)),
            _t(zeros), _t(zeros), _t(np.zeros(heads, "float32")),
            _t(np.zeros(heads, "float32")), num_k_heads=heads, head_k_dim=d)
        want = (by_head / np.sqrt(
            (by_head ** 2).sum(-1, keepdims=True) + 1e-6)).reshape(x.shape)
        np.testing.assert_allclose(q.numpy(), want * d ** -0.5, rtol=1e-6,
                                   atol=1e-8)
        got = k.numpy()
    else:
        z = rng.normal(size=x.shape).astype("float32")
        w = rng.normal(size=(d,)).astype("float32")
        zf = z.astype("float64").reshape(by_head.shape)
        want = (w * by_head / np.sqrt(
            (by_head ** 2).mean(-1, keepdims=True) + 1e-6) * (
            zf / (1 + np.exp(-zf)))).reshape(x.shape)
        got = Q._op(Q._gated_rms_norm, _t(x), _t(w), _t(z),
                    epsilon=1e-6).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_router_logits_in_float32_do_not_round_the_operands():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(4, 64)).astype("float32")
    w = rng.normal(size=(64, 8)).astype("float32")
    x16 = jnp.asarray(x).astype(jnp.bfloat16)
    got = F.moe_router_logits(paddle.to_tensor(x16), _t(w), dtype="float32")
    assert str(got.dtype).endswith("float32")
    want = np.asarray(x16.astype(jnp.float32)) @ w
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------- the layer that holds a share
E, K, D, FF = 16, 4, 32, 24


def _whole_and_shares(shared, parts=4):
    paddle.seed(0)
    cfg = Qwen3NextConfig.tiny(hidden_size=D)

    def make(start, count):
        return MoELayer(
            D, E, d_ff=FF, k=K, held=(start, count), router_dtype="float32",
            shared_expert=(lambda: Qwen3NextMLP(cfg, FF)) if shared else None)

    whole = make(0, E)
    per = E // parts
    shares = []
    for r in range(parts):
        part = make(r * per, per)
        part.gate.weight._rebind(whole.gate.weight._data)
        for n in ("w_gate", "w_up", "w_down"):
            getattr(part.experts, n)._rebind(
                getattr(whole.experts, n)._data[r * per:(r + 1) * per])
        if shared:
            for a, b in zip(part.shared_expert.parameters(),
                            whole.shared_expert.parameters()):
                a._rebind(b._data)
            part.shared_gate.weight._rebind(whole.shared_gate.weight._data)
        shares.append(part)
    return whole, shares


def _plain_moe(x, layer):
    """The uncut layer in plain numpy: softmax over all experts, top-k,
    weights normalised over the k, every chosen expert's SwiGLU."""
    w_r = layer.gate.weight.numpy().astype("float64")
    logits = x @ w_r
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = np.argsort(-p, -1)[:, :K]
    out = np.zeros_like(x)
    wg, wu, wd = (getattr(layer.experts, n).numpy().astype("float64")
                  for n in ("w_gate", "w_up", "w_down"))
    silu = lambda v: v / (1 + np.exp(-v))
    for t in range(x.shape[0]):
        w = p[t, idx[t]] / p[t, idx[t]].sum()
        for j, e in enumerate(idx[t]):
            out[t] += w[j] * ((silu(x[t] @ wg[e]) * (x[t] @ wu[e])) @ wd[e])
    return out


@pytest.mark.parametrize("shared", [False, True])
def test_the_shares_add_up_to_the_uncut_layer(shared):
    """Four shares of a 16-expert layer, the shared expert counted once,
    equal the uncut reference layer; no share drops a token."""
    whole, shares = _whole_and_shares(shared)
    x = np.random.default_rng(7).normal(size=(2, 24, D)).astype("float32")
    flat = x.reshape(-1, D).astype("float64")
    want = _plain_moe(flat, whole)
    common = 0.0
    if shared:
        sg = whole.shared_gate.weight.numpy().astype("float64")
        mlp = whole.shared_expert
        g, u, d = (getattr(mlp, n).weight.numpy().astype("float64")
                   for n in ("gate_proj", "up_proj", "down_proj"))
        gate = flat @ g
        common = (1 / (1 + np.exp(-(flat @ sg)))) * (
            (gate / (1 + np.exp(-gate)) * (flat @ u)) @ d)
    total, loads = 0.0, []
    for part in shares:
        y, aux = part(_t(x))
        total = total + y.numpy().reshape(-1, D).astype("float64")
        loads.append(part.expert_load.numpy())
        assert float(aux.numpy()) == 0.0
    # every share computed the shared expert: count it once
    total = total - (len(shares) - 1) * common
    np.testing.assert_allclose(total, want + common, atol=2e-6)
    y_whole, _ = whole(_t(x))
    np.testing.assert_allclose(
        y_whole.numpy().reshape(-1, D), want + common, atol=2e-6)
    loads = np.concatenate(loads)
    assert loads.sum() == 2 * 24 * K            # nothing dropped
    np.testing.assert_array_equal(loads, whole.expert_load.numpy())


def test_a_share_routes_over_all_experts_and_normalises_over_all_k():
    """The weights of the kept assignments are those normalised over all
    k chosen experts, not over the kept ones; the kept assignments come
    first, sorted by expert."""
    _, shares = _whole_and_shares(False)
    part = shares[1]
    x = np.random.default_rng(8).normal(size=(1, 8, D)).astype("float32")
    flat = _t(x.reshape(-1, D))
    logits = F.moe_router_logits(flat, part.gate.weight)
    rows = part.held_rows(8)
    tok, w, load = F.moe_held_dispatch(
        flat, logits, k=K, start=4, count=4, rows=rows)
    n = int(load.numpy().sum())
    assert len(tok.numpy()) % rows == 0 and len(tok.numpy()) >= 8 * K
    p = np.exp(logits.numpy() - logits.numpy().max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, -1)[:, :K]
    top = -np.sort(-p, -1)[:, :K]
    by_token = np.zeros(8)
    np.add.at(by_token, tok.numpy()[:n], w.numpy()[:n])
    held = np.where((chosen >= 4) & (chosen < 8), top, 0).sum(-1) / top.sum(
        -1)
    np.testing.assert_allclose(by_token, held, atol=1e-6)
    assert (w.numpy()[n:] == 0).all() and (tok.numpy()[n:] == 0).all()
    np.testing.assert_array_equal(
        load.numpy(), [((chosen == e).sum()) for e in range(4, 8)])


def test_more_rows_than_a_pass_take_further_passes_and_lose_nothing():
    """A router that sends this rank far more than its share: the sorted
    assignments are worked off in several passes of `rows`, and the
    result and every gradient equal those of one pass that holds all."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(32, 8)).astype("float32"))
    # every token chooses experts 0 and 1 of 4: 64 rows for 2 held experts
    logits = jnp.asarray(np.tile(np.arange(4, dtype="float32")[::-1] * 9,
                                 (32, 1)) + rng.normal(size=(32, 4)) * 0.1)
    wg, wu = (jnp.asarray(rng.normal(size=(2, 8, 6)).astype("float32"))
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(2, 6, 8)).astype("float32"))
    from paddle_tpu.ops.impl import moe_ops

    def run(rows):
        def loss(x, wg, wu, wd, logits):
            tok, w, load = moe_ops.moe_held_dispatch(
                x, logits, k=2, start=0, count=2, rows=rows)
            out = moe_ops.moe_held_experts(x, wg, wu, wd, tok, w, load,
                                           rows=rows)
            return jnp.sum(out ** 2), (out, load)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(x, wg, wu, wd, logits)

    (_, (whole, load)), whole_grads = run(64)
    assert load.tolist() == [32, 32]
    for rows in (40, 16, 24):               # 2, 4 and 3 passes (one ragged)
        (_, (out, _)), grads = run(rows)
        np.testing.assert_allclose(out, whole, rtol=1e-5, atol=1e-5)
        for a, b in zip(grads, whole_grads):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-4)
    # and against the plain sum over both experts
    silu = lambda v: v / (1 + np.exp(-v))
    p = np.exp(np.asarray(logits) - np.asarray(logits).max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = sum((p[:, e] / (p[:, 0] + p[:, 1]))[:, None] * (
        (silu(np.asarray(x) @ np.asarray(wg[e])) * (
            np.asarray(x) @ np.asarray(wu[e]))) @ np.asarray(wd[e]))
        for e in range(2))
    np.testing.assert_allclose(whole, want, rtol=1e-4, atol=1e-4)


def test_a_step_that_is_sent_nothing_adds_nothing():
    x = jnp.ones((8, 4))
    logits = jnp.asarray(np.tile(np.arange(4, dtype="float32"), (8, 1)))
    from paddle_tpu.ops.impl import moe_ops

    tok, w, load = moe_ops.moe_held_dispatch(
        x, logits, k=2, start=0, count=2, rows=8)      # all choose 2 and 3
    assert load.tolist() == [0, 0]
    out = moe_ops.moe_held_experts(
        x, jnp.ones((2, 4, 3)), jnp.ones((2, 4, 3)), jnp.ones((2, 3, 4)),
        tok, w, load, rows=8)
    assert not np.asarray(out).any()


@pytest.mark.parametrize("held", [(-1, 4), (14, 4), (0, 0)])
def test_a_held_range_outside_the_experts_is_refused(held):
    with pytest.raises(ValueError, match="held"):
        MoELayer(D, E, d_ff=FF, k=K, held=held)


def test_held_rows_bound():
    layer = MoELayer(D, 512, d_ff=8, k=10, held=(32, 32))
    # a pass: twice the uniform share of 32,768 tokens x 10 / 16
    assert layer.held_rows(32768) == 40960
    assert MoELayer(D, E, d_ff=8, k=K, held=(0, E)).held_rows(10) == 40


# ------------------------------------------------ the grouped matmul's VJP
@pytest.mark.parametrize("sizes,rows", [
    pytest.param([3, 0, 5, 1, 0, 7], None, id="sizes0"),
    pytest.param([0, 0, 16, 0], None, id="sizes1"),
    pytest.param([40, 1, 300, 0, 43], None, id="sizes2"),
    pytest.param([0, 0, 0], None, id="sizes3"),
    # a pass's occupancy: every group empty, one group, the rows sent at a
    # quarter, half and all of the pass
    pytest.param([0, 0, 0, 0], 64, id="pass_empty"),
    pytest.param([0, 0, 37, 0], 64, id="pass_one_group"),
    pytest.param([5, 3, 0, 8], 64, id="pass_quarter"),
    pytest.param([9, 0, 14, 9], 64, id="pass_half"),
    pytest.param([16, 17, 15, 16], 64, id="pass_full"),
])
def test_grouped_matmul_vjp_kernels_match_the_xla_form(sizes, rows):
    """`grouped_matmul_dlhs` and `grouped_matmul_drhs` against jax.grad of
    the XLA form, with empty and uneven groups and rows past the last
    group (which belong to none): 5 of them, or a pass of ``rows``."""
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul

    n, e, k, m = rows or sum(sizes) + 5, len(sizes), 24, 40
    ks = jax.random.split(jax.random.key(1), 3)
    lhs = jax.random.normal(ks[0], (n, k))
    rhs = jax.random.normal(ks[1], (e, k, m))
    w = jax.random.normal(ks[2], (n, m))
    gs = jnp.asarray(sizes, jnp.int32)
    valid = (jnp.arange(n) < sum(sizes))[:, None]

    def loss(impl):
        return lambda a, b: jnp.sum(jnp.where(
            valid, grouped_matmul(a, b, gs, impl=impl, tm=16), 0) * w)

    got = jax.grad(loss("pallas"), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss("xla"), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(jnp.where(valid, got[0], 0),
                               jnp.where(valid, want[0], 0), atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
    empty = np.asarray(sizes) == 0
    assert (np.asarray(got[1])[empty] == 0).all()


def test_grouped_matmul_keeps_bf16_operands_and_chooses_a_wide_block():
    from paddle_tpu.kernels.pallas import grouped_matmul as gm

    # an expert of 2048 x 512 bf16 is one weight block; float32 halves it
    assert gm._column_block(512, 2048, 2) == 512
    assert gm._column_block(2048, 512, 2) == 2048
    assert gm._column_block(512, 2048, 4) == 256
    assert gm._column_block(40, 24, 4) == 40
    x = jnp.ones((8, 16), jnp.bfloat16)
    a, b = gm._operands(x, jnp.ones((16, 8), jnp.bfloat16))
    assert a.dtype == b.dtype == jnp.bfloat16
    a, b = gm._operands(x, jnp.ones((16, 8), jnp.float32))
    assert a.dtype == b.dtype == jnp.float32


# ----------------------------------------------------------------- the model
def test_layers_alternate_in_periods_of_four():
    cfg = Qwen3NextConfig.tiny(num_hidden_layers=8)
    model = Qwen3NextForCausalLM(cfg)
    kinds = [layer.is_attention for layer in model.model.layers]
    assert kinds == [False, False, False, True] * 2
    assert hasattr(model.model.layers[3], "self_attn")
    assert hasattr(model.model.layers[0], "linear_attn")


def test_parameters_are_listed_in_the_order_they_are_created():
    """benchmarks hand weights over in creation order and read them back
    through parameters(): the two orders are one."""
    from paddle_tpu.nn import initializer as I

    shapes = []

    def record(shape, dtype=None):
        shapes.append(tuple(shape))
        return jnp.zeros(shape, jnp.float32)

    with I.param_init_override(record):
        model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny(
            held_experts=(4, 8)))
    assert shapes == [tuple(p.shape) for p in model.parameters()]
    assert tuple(model.model.layers[0].mlp.experts.w_gate.shape) == (
        8, 32, 16)
    assert tuple(model.model.layers[0].mlp.gate.weight.shape) == (32, 16)


def test_the_mixers_parameters_keep_hfs_names_and_shapes():
    """The interleave is undone inside the forward: what a checkpoint, the
    optimizer and benchmarks/weights_qwen3_next.py see is HF's."""
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny())
    mixer = model.model.layers[0].linear_attn
    assert [(n, tuple(p.shape)) for n, p in mixer.named_parameters()] == [
        ("conv_weight", (4, 64)), ("dt_bias", (4,)), ("A_log", (4,)),
        ("norm_weight", (8,)), ("in_proj_qkvz.weight", (32, 96)),
        ("in_proj_ba.weight", (32, 8)), ("out_proj.weight", (32, 32))]
    keys = set(model.state_dict())
    assert {"model.layers.0.linear_attn.in_proj_qkvz.weight",
            "model.layers.0.linear_attn.in_proj_ba.weight"} <= keys
    ids = _t(np.random.default_rng(4).integers(0, 128, (1, 16)).astype(
        "int32"))
    model(ids, labels=ids)[1].backward()
    for proj in (mixer.in_proj_qkvz, mixer.in_proj_ba):
        assert tuple(proj.weight.grad.shape) == tuple(proj.weight.shape)
        assert np.abs(proj.weight.grad.numpy()).max() > 0


@pytest.mark.parametrize("recompute", [False, True])
def test_trains_through_train_step_and_carries_the_load(recompute):
    paddle.seed(0)
    cfg = Qwen3NextConfig.tiny(held_experts=(4, 8), recompute=recompute,
                               fused_loss_chunk=16)
    model = Qwen3NextForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m(ids, labels=ids)[1], opt)
    ids = _t(np.random.default_rng(0).integers(0, 128, (2, 48)).astype(
        "int32"))
    losses = [float(step(ids).numpy()) for _ in range(6)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    load = np.stack([layer.mlp.expert_load.numpy()
                     for layer in model.model.layers])
    assert load.shape == (4, 8) and load.dtype == np.int32
    # 96 tokens x 4 choices over 16 experts, 8 held: about half, never all
    assert 0 < load.sum(axis=1).min() and load.sum(axis=1).max() < 96 * 4


def test_recompute_does_not_change_the_step():
    def run(recompute):
        paddle.seed(0)
        model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny(
            recompute=recompute, num_hidden_layers=2,
            full_attention_interval=2))
        ids = _t(np.random.default_rng(1).integers(0, 128, (1, 32)).astype(
            "int32"))
        loss = model(ids, labels=ids)[1]
        loss.backward()
        return float(loss.numpy()), [
            np.asarray(p.grad.numpy()) for p in model.parameters()]

    (l0, g0), (l1, g1) = run(False), run(True)
    assert l0 == pytest.approx(l1, rel=1e-6)
    for a, b in zip(g0, g1):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_logits_and_the_fused_loss_agree():
    paddle.seed(0)
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny())
    ids = _t(np.random.default_rng(2).integers(0, 128, (2, 32)).astype(
        "int32"))
    logits = model(ids)
    assert tuple(logits.shape) == (2, 32, 128)
    _, plain = model(ids, labels=ids)
    model.config.fused_loss_chunk = 16
    none, fused = model(ids, labels=ids)
    assert none is None
    assert float(fused.numpy()) == pytest.approx(float(plain.numpy()),
                                                 rel=1e-5)


def test_the_model_is_causal():
    paddle.seed(0)
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny())
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 128, (1, 40)).astype("int32")
    other = ids.copy()
    other[:, 25:] = rng.integers(0, 128, (1, 15))
    a, b = model(_t(ids)).numpy(), model(_t(other)).numpy()
    np.testing.assert_allclose(a[:, :25], b[:, :25], atol=1e-5)
    assert np.abs(a[:, 25:] - b[:, 25:]).max() > 1e-3


def test_scopes_and_counters_of_a_traced_step():
    """The device scopes of PR 25's vocabulary and the registry counter
    bumped once a traced call."""
    from paddle_tpu.kernels.pallas._compat import gdr_operands
    from paddle_tpu.observability import counter

    paddle.seed(0)
    cfg = Qwen3NextConfig.tiny(held_experts=(0, 4), fused_loss_chunk=16)
    model = Qwen3NextForCausalLM(cfg)
    series = counter("paddle_tpu_moe_held", "",
                     labelnames=("experts", "held", "k"))
    key = {"experts": "16", "held": "4", "k": "4"}

    def count():
        return sum(child.value for labels, child in series._series()
                   if labels == key)

    before, forms = count(), gdr_operands()
    params = [p._data for p in model.parameters()]

    def loss(arrays, ids):
        old = [p._data for p in model.parameters()]
        for p, a in zip(model.parameters(), arrays):
            p._data = a
        try:
            # attention draws a key: the generator gets its own back, as
            # under jit.TrainStep, and keeps no tracer
            with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                return model(paddle.to_tensor(ids), labels=paddle.to_tensor(
                    ids))[1]._data
        finally:
            for p, a in zip(model.parameters(), old):
                p._data = a

    ids = jnp.zeros((1, 32), jnp.int32)
    text = jax.jit(loss).trace(params, ids).lower().as_text(debug_info=True)
    assert count() == before + 4                  # one a layer
    # every DeltaNet layer hands the delta rule its flat form, none heads
    assert gdr_operands().get("flat", 0) == forms.get("flat", 0) + 3
    assert gdr_operands().get("heads", 0) == forms.get("heads", 0)
    for name in ("embedding", "linear_attention", "attention", "moe",
                 "moe.router", "moe.experts", "moe.shared_expert",
                 "lm_head_loss"):
        assert f"/{name}/" in text or f"{name}/" in text, name
