"""models/granite_hybrid.py: the block's own operations against plain
numpy, the model against the plain reference
(benchmarks/reference/granite_hybrid.py: float32 `highest`, the recurrence
token by token) on seeded weights at the tiny size (logits, loss, every
leaf's gradient), the four Granite multipliers each shown to matter, the
tied head, and the model on jit.TrainStep's normal path with its scope and
counters. The runner's control flow and the controls are in
tests/benchmarks/test_granite_hybrid_benchmark.py.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.jit.api import _rng_lift
from paddle_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM
from paddle_tpu.models import granite_hybrid as G

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEED = 2**31 + 11


def _t(a):
    return paddle.to_tensor(np.asarray(a))


def tiny_cfg(**changes):
    """GraniteHybridConfig.tiny() as the benchmark's files spell a
    configuration: two Mamba layers around one attention layer, 4 Mamba
    heads x 8, state 16, chunk 8 (32 tokens: the carry crosses chunks)."""
    c = GraniteHybridConfig.tiny()
    cfg = {k: getattr(c, k) for k in (
        "vocab_size", "hidden_size", "shared_intermediate_size",
        "num_hidden_layers", "layer_types", "num_attention_heads",
        "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
        "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_n_groups",
        "mamba_chunk_size", "embedding_multiplier", "residual_multiplier",
        "attention_multiplier", "logits_scaling", "rms_norm_eps")}
    cfg.update(mamba_conv_bias=True, mamba_proj_bias=False,
               tie_word_embeddings=True, num_local_experts=0,
               position_embedding_type="nope", initializer_range=0.3,
               torch_dtype="float32")
    cfg.update(changes)
    return cfg


def _model(cfg, seed=SEED, **extra):
    from benchmarks import train_granite_hybrid as T

    return T.build_model(cfg, seed, **extra)


def _ids(cfg, rows=2, seq=32, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, seq)).astype("int32")


# ------------------------------------------------------------ the block ops
def test_causal_conv_with_bias_sees_the_past_only():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(1, 7, 3)).astype("float32")
    w = rng.normal(size=(4, 3)).astype("float32")
    bias = rng.normal(size=(3,)).astype("float32")
    pad = np.concatenate([np.zeros((1, 3, 3), "float32"), x], 1)
    lin = bias + sum(w[j] * pad[:, j:j + 7] for j in range(4))
    want = lin / (1 + np.exp(-lin))
    got = G._op(G._causal_conv_bias_silu, _t(x), _t(w), _t(bias)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    x2 = x.copy()
    x2[:, 5:] += 1.0                    # the future of tokens 0..4
    got2 = G._op(G._causal_conv_bias_silu, _t(x2), _t(w), _t(bias)).numpy()
    np.testing.assert_array_equal(got2[:, :5], got[:, :5])
    # the bias is inside the SiLU: a token with no past and no input
    # still reads silu(bias)
    zero = G._op(G._causal_conv_bias_silu, _t(np.zeros_like(x)), _t(w),
                 _t(bias)).numpy()
    np.testing.assert_allclose(zero[0, 0], bias / (1 + np.exp(-bias)),
                               rtol=1e-5)


def test_gated_norm_gates_before_it_normalises():
    rng = np.random.default_rng(2)
    y, z = rng.normal(size=(2, 5, 8)), rng.normal(size=(2, 5, 8))
    w = rng.normal(size=(8,))
    g = y * z / (1 + np.exp(-z))
    want = w * g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)
    got = G._op(G._gated_rms_norm, _t(y.astype("float32")),
                _t(z.astype("float32")), _t(w.astype("float32")),
                epsilon=1e-5).numpy()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    # not the other order (the Qwen3-Next mixer's): norm, then gate
    other = w * y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) * (
        z / (1 + np.exp(-z)))
    assert np.abs(got - other).max() > 0.1


def test_step_and_decay_are_float32_whatever_the_leaves_are():
    rng = np.random.default_rng(3)
    raw = jnp.asarray(rng.normal(size=(1, 6, 4)), jnp.bfloat16)
    bias = jnp.ones((4,), jnp.bfloat16)
    a_log = jnp.log(jnp.arange(1.0, 5.0)).astype(jnp.bfloat16)
    dt, a = G._op(G._step_and_decay, paddle.to_tensor(raw),
                  paddle.to_tensor(bias), paddle.to_tensor(a_log))
    assert str(dt.dtype).endswith("float32") and str(a.dtype).endswith(
        "float32")
    np.testing.assert_allclose(
        dt.numpy(), np.log1p(np.exp(np.asarray(raw, np.float32) + 1.0)),
        rtol=1e-5)
    np.testing.assert_allclose(
        a.numpy(), -np.exp(np.asarray(a_log, np.float32)), rtol=1e-6)
    assert (dt.numpy() > 0).all() and (a.numpy() < 0).all()


# ------------------------------------------------- model against reference
def _reference_readings(cfg, ids, **fault):
    from benchmarks.reference import granite_hybrid as reference

    params = reference.float32_params(cfg, SEED, jnp.float32)
    logits = jnp.stack([reference.logits(params, jnp.asarray(row), cfg,
                                         **fault) for row in ids])

    def loss(p):
        return jnp.mean(jnp.stack([
            reference.row_loss(p, jnp.asarray(row), cfg, **fault)
            for row in ids]))

    value, grads = jax.value_and_grad(loss)(params)
    return np.asarray(logits), float(value), {
        n: np.asarray(g) for n, g in grads.items()}


def _leaf_gaps(got, want):
    """Each leaf's gap: the norm of the difference over the leaf's norm
    or the median leaf's, whichever is larger (train.worst_leaf_gap's
    rule, on whole arrays)."""
    norms = {n: np.linalg.norm(g) for n, g in want.items()}
    median = np.median(list(norms.values()))
    return {n: np.linalg.norm(got[n] - want[n]) / max(norms[n], median)
            for n in want}


# float32 against float32 `highest`: what is left is the order of the sums
# (the chunked form against the recurrence, XLA's CPU matmuls). Each limit
# stands 5 to 10 times above what this size reads (logits 2.1e-7 of the
# largest, loss under 1e-7, worst leaf 8.1e-7) and far below what a
# bfloat16 state inside the recurrence reads (logits 4.8e-4, worst leaf
# 6.6e-3). Weights are N(0, 0.3): at 0.1 the recurrence adds so little to
# this toy that a bfloat16 state moves the logits by 3e-6
LOGITS_TOL, LOSS_TOL, LEAF_TOL = 2e-6, 5e-7, 5e-6


@pytest.fixture(scope="module")
def agreement():
    cfg = tiny_cfg()
    ids = _ids(cfg)
    model = _model(cfg)
    logits = model(_t(ids)).numpy()
    _, loss = model(_t(ids), labels=_t(ids))
    loss.backward()
    grads = {n: p.grad.numpy() for n, p in model.named_parameters()}
    return cfg, ids, (logits, float(loss.numpy()), grads), (
        _reference_readings(cfg, ids))


def test_logits_and_loss_agree_with_the_reference(agreement):
    _, _, (logits, loss, _), (ref_logits, ref_loss, _) = agreement
    assert logits.shape == ref_logits.shape == (2, 32, 128)
    assert np.abs(logits - ref_logits).max() / np.abs(
        ref_logits).max() < LOGITS_TOL
    assert abs(loss - ref_loss) / ref_loss < LOSS_TOL


def test_every_leafs_gradient_agrees_with_the_reference(agreement):
    cfg, _, (_, _, grads), (_, _, ref_grads) = agreement
    from benchmarks import weights_granite_hybrid as W

    assert list(grads) == [n for n, _, _ in W.leaf_specs(cfg)]
    assert set(grads) == set(ref_grads)
    gaps = _leaf_gaps(grads, ref_grads)
    assert max(gaps.values()) < LEAF_TOL, max(gaps.items(),
                                              key=lambda kv: kv[1])
    # every leaf took part (the convolution's bias and D among them)
    assert min(np.abs(g).max() for g in ref_grads.values()) > 0


def test_bfloat16_inside_the_recurrence_fails_the_tolerances(agreement):
    """The comparison is tight enough that computing the recurrence's
    state in the precision below the stated one is caught."""
    cfg, ids, _, (ref_logits, _, ref_grads) = agreement
    low_logits, _, low_grads = _reference_readings(
        cfg, ids, scan_dtype=jnp.bfloat16)
    assert np.abs(low_logits - ref_logits).max() / np.abs(
        ref_logits).max() > LOGITS_TOL
    assert max(_leaf_gaps(low_grads, ref_grads).values()) > LEAF_TOL


def test_the_kernels_give_the_same_model(agreement, monkeypatch):
    """The tiny model with its scans through the Pallas kernels (the
    interpreter here) instead of the jax.numpy form."""
    from paddle_tpu.ops.impl import ssm_ops

    cfg, ids, _, (ref_logits, ref_loss, ref_grads) = agreement
    plain = ssm_ops.mamba2_ssd
    monkeypatch.setattr(
        ssm_ops, "mamba2_ssd",
        lambda *a, **kw: plain(*a, **{**kw, "impl": "pallas"}))
    model = _model(cfg)
    _, loss = model(_t(ids), labels=_t(ids))
    loss.backward()
    assert abs(float(loss.numpy()) - ref_loss) / ref_loss < LOSS_TOL
    grads = {n: p.grad.numpy() for n, p in model.named_parameters()}
    assert max(_leaf_gaps(grads, ref_grads).values()) < LEAF_TOL


# (the configuration's key, another value): each changes the logits
MULTIPLIERS = {
    "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
    "attention_multiplier": 8 ** -0.5, "logits_scaling": 1.0}


@pytest.mark.parametrize("key", sorted(MULTIPLIERS))
def test_each_multiplier_matters_and_follows_the_reference(key, agreement):
    from benchmarks.reference import granite_hybrid as reference

    cfg, ids, (logits, _, _), _ = agreement
    other = tiny_cfg(**{key: MULTIPLIERS[key]})
    assert other[key] != cfg[key]
    got = _model(other)(_t(ids)).numpy()
    assert np.abs(got - logits).max() / np.abs(logits).max() > 1e-2, key
    params = reference.float32_params(other, SEED, jnp.float32)
    want = np.stack([np.asarray(reference.logits(
        params, jnp.asarray(row), other)) for row in ids])
    assert np.abs(got - want).max() / np.abs(want).max() < LOGITS_TOL


def test_the_published_multipliers_are_the_defaults():
    c = GraniteHybridConfig()
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (
                12.0, 0.22, 0.015625, 8.0)
    assert c.attention_multiplier != (c.hidden_size
                                      // c.num_attention_heads) ** -0.5
    assert c.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(c.layer_types) if t == "attention"] == [
        5, 15, 25, 35]


def test_the_head_is_the_embedding():
    """Tied: no head parameter, and the embedding's gradient carries both
    the gather's rows and the head's."""
    cfg = tiny_cfg()
    model = _model(cfg)
    names = [n for n, _ in model.named_parameters()]
    assert not any("lm_head" in n for n in names)
    assert names[0] == "model.embed_tokens.weight" and (
        names[-1] == "model.norm.weight")
    ids = np.full((1, 8), 3, "int32")         # one token only
    model(_t(ids), labels=_t(ids))[1].backward()
    grad = model.model.embed_tokens.weight.grad.numpy()
    # rows never looked up still have a gradient: the head's
    assert np.abs(grad[5]).max() > 0 and np.abs(grad[3]).max() > 0
    logits = model(_t(ids)).numpy()
    hidden = model.model(_t(ids)).numpy()
    np.testing.assert_allclose(
        logits, hidden @ model.model.embed_tokens.weight.numpy().T / 8.0,
        rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------- the model
def test_layers_follow_layer_types():
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    kinds = [layer.is_attention for layer in model.model.layers]
    assert kinds == [False, True, False]
    assert hasattr(model.model.layers[0], "mamba")
    assert hasattr(model.model.layers[1], "self_attn")
    mixer = model.model.layers[0].mamba
    assert [(n, tuple(p.shape)) for n, p in mixer.named_parameters()] == [
        ("conv_weight", (4, 64)), ("conv_bias", (64,)), ("dt_bias", (4,)),
        ("A_log", (4,)), ("D", (4,)), ("norm_weight", (32,)),
        ("in_proj.weight", (16, 100)), ("out_proj.weight", (32, 16))]
    np.testing.assert_allclose(mixer.A_log.numpy(), np.log([1, 2, 3, 4]),
                               rtol=1e-6)
    assert (mixer.D.numpy() == 1).all() and (mixer.dt_bias.numpy() == 1).all()
    assert (mixer.conv_bias.numpy() == 0).all()


def test_parameters_are_listed_in_the_order_they_are_created():
    from paddle_tpu.nn import initializer as I

    shapes = []

    def record(shape, dtype=None):
        shapes.append(tuple(shape))
        return jnp.zeros(shape, jnp.float32)

    with I.param_init_override(record):
        model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    assert shapes == [tuple(p.shape) for p in model.parameters()]


@pytest.mark.parametrize("bad", [
    dict(num_local_experts=8), dict(position_embedding_type="rope"),
    dict(tie_word_embeddings=False), dict(mamba_conv_bias=False),
    dict(mamba_proj_bias=True), dict(layer_types=["mamba", "attention"]),
    dict(layer_types=["mamba", "attention", "window"]),
    dict(mamba_expand=3), dict(mamba_n_groups=3)])
def test_what_is_not_implemented_is_refused(bad):
    with pytest.raises((NotImplementedError, ValueError)):
        GraniteHybridConfig.tiny(**bad)


@pytest.mark.parametrize("recompute", [False, True])
def test_trains_through_train_step(recompute):
    paddle.seed(0)
    cfg = GraniteHybridConfig.tiny(recompute=recompute, fused_loss_chunk=16)
    model = GraniteHybridForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m(ids, labels=ids)[1], opt)
    ids = _t(np.random.default_rng(0).integers(0, 128, (2, 32)).astype(
        "int32"))
    losses = [float(step(ids).numpy()) for _ in range(6)]
    assert losses[-1] < losses[0] and np.isfinite(losses).all()


def test_recompute_does_not_change_the_step():
    def run(recompute):
        paddle.seed(0)
        model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny(
            recompute=recompute))
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
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    ids = _t(np.random.default_rng(2).integers(0, 128, (2, 32)).astype(
        "int32"))
    assert tuple(model(ids).shape) == (2, 32, 128)
    _, plain = model(ids, labels=ids)
    model.config.fused_loss_chunk = 16
    none, fused = model(ids, labels=ids)
    assert none is None
    assert float(fused.numpy()) == pytest.approx(float(plain.numpy()),
                                                 rel=1e-5)


def test_the_model_is_causal():
    paddle.seed(0)
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 128, (1, 40)).astype("int32")
    other = ids.copy()
    other[:, 25:] = rng.integers(0, 128, (1, 15))
    a, b = model(_t(ids)).numpy(), model(_t(other)).numpy()
    np.testing.assert_allclose(a[:, :25], b[:, :25], atol=1e-5)
    assert np.abs(a[:, 25:] - b[:, 25:]).max() > 1e-3


def test_a_length_that_is_no_multiple_of_the_chunk_is_padded_not_refused():
    paddle.seed(0)
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    ids = np.random.default_rng(5).integers(0, 128, (1, 37)).astype("int32")
    whole = model(_t(ids)).numpy()
    np.testing.assert_allclose(model(_t(ids[:, :29])).numpy(),
                               whole[:, :29], atol=1e-5)


def test_scope_and_counters_of_a_traced_step(monkeypatch):
    """The `state_space` device scope beside PR 25's vocabulary, and the
    `ssd` registry counters bumped once a Mamba layer by a traced step
    that goes through the kernels."""
    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat, mamba2_ssd

    paddle.seed(0)
    cfg = GraniteHybridConfig.tiny(fused_loss_chunk=16)
    model = GraniteHybridForCausalLM(cfg)
    params = [p._data for p in model.parameters()]

    def loss(arrays, ids):
        old = [p._data for p in model.parameters()]
        for p, a in zip(model.parameters(), arrays):
            p._data = a
        try:
            with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                return model(paddle.to_tensor(ids), labels=paddle.to_tensor(
                    ids))[1]._data
        finally:
            for p, a in zip(model.parameters(), old):
                p._data = a

    ids = jnp.zeros((1, 32), jnp.int32)
    # "auto" as on the chip: the kernels (still the interpreter here)
    monkeypatch.setattr(mamba2_ssd.device, "on_tpu", lambda: True)
    chunks, blocks = _compat.ssd_chunks(), _compat.ssd_blocks()
    text = jax.jit(loss).trace(params, ids).lower().as_text(debug_info=True)
    assert core_device.on_tpu()
    key = (8, 8, 16, 1)                  # chunk, d_head, d_state, groups
    assert _compat.ssd_chunks().get(key, 0) == chunks.get(key, 0) + 2
    step = ("mamba2_ssd_fwd", 4, 4)      # 4 heads a step, 4 chunks of 8
    assert _compat.ssd_blocks().get(step, 0) == blocks.get(step, 0) + 2
    assert "mamba2_ssd_fwd" in text
    for name in ("embedding", "state_space", "attention", "mlp",
                 "lm_head_loss"):
        assert f"/{name}/" in text or f"{name}/" in text, name
