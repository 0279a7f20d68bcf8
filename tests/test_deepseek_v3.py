"""models/deepseek_v3.py, the MLA kernels of kernels/pallas/
flash_attention.py and the sigmoid router of incubate/moe.py: the model
against the plain reference (benchmarks/reference/deepseek_v3.py: float32,
`highest`, imports nothing of paddle_tpu) on seeded random weights, the
kernels under the interpreter against plain attention, the router's
selection bias, the shares of a layer adding up to the whole, the
de-interleaved weights against the interleaved rope, and the scopes and
counters of a traced step. Three AdamW steps through the runner are in
tests/benchmarks/test_kanana2_benchmark.py, beside the configuration.
"""
import contextlib
import io
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu import ops as F                               # noqa: E402
from paddle_tpu.incubate.moe import MoELayer                  # noqa: E402
from paddle_tpu.jit.api import _rng_lift                      # noqa: E402
from paddle_tpu.kernels.pallas import _compat                 # noqa: E402
from paddle_tpu.kernels.pallas import flash_attention as fa   # noqa: E402
from paddle_tpu.models import (DeepseekV3Config,              # noqa: E402
                               DeepseekV3ForCausalLM)
from paddle_tpu.models import deepseek_v3 as D                # noqa: E402

from benchmarks import weights_deepseek_v3 as W               # noqa: E402
from benchmarks.reference import deepseek_v3 as reference     # noqa: E402


def _t(a):
    return paddle.to_tensor(np.asarray(a))


# a configuration file's keys at toy widths: what the reference reads
CFG = {
    "hidden_size": 32, "vocab_size": 96, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "kv_lora_rank": 16, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_shared_experts": 2,
    "n_routed_experts": 4, "published": {"n_routed_experts": 16},
    "expert_parallel": {"ranks": 4, "rank": 2}, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 3,
    "rope_theta": 1000.0, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
    "routed_scaling_factor": 2.448, "initializer_range": 0.3,
}
SEQ = 24


def _bias(layer):
    """A seeded non-zero selection bias, so that `chooses, does not weigh`
    is under test in every comparison with the reference."""
    return 0.2 * np.random.default_rng(100 + layer).normal(
        size=16).astype("float32")


@pytest.fixture(scope="module")
def model_and_params():
    """The program's model holding weights_deepseek_v3's seeded leaves
    (float32) and a non-zero selection bias, and the same for the
    reference."""
    made = W.make_weights(CFG, 11, jnp.float32)
    queue = list(made.values())
    cfg = DeepseekV3Config(
        **{k: CFG[k] for k in (
            "hidden_size", "vocab_size", "num_attention_heads",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank", "intermediate_size", "moe_intermediate_size",
            "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "moe_layer_freq", "num_hidden_layers",
            "rope_theta", "rms_norm_eps", "routed_scaling_factor")},
        n_routed_experts=16, held_experts=(8, 4))
    with paddle.nn.initializer.param_init_override(
            lambda shape, dtype=None: queue.pop(0)):
        model = DeepseekV3ForCausalLM(cfg)
    assert not queue
    assert [(n, tuple(p.shape)) for n, p in model.named_parameters()] == [
        (n, s) for n, s, _ in W.leaf_specs(CFG)]
    biases = {}
    for i, layer in enumerate(model.model.layers):
        if layer.is_expert_layer:
            biases[i] = _bias(i)
            layer.mlp.gate.e_score_correction_bias._rebind(
                jnp.asarray(biases[i]))
    return model, dict(made), biases


def _ids(seed=0, rows=2):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (rows, SEQ)).astype("int32")


# -------------------------------------------------- model against reference
def test_logits_and_loss_agree_with_the_reference(model_and_params):
    """float32 on both sides: what is left is the order of summation
    (1e-6 of a logit of size ~3). A bfloat16 score reads 1e-2 and a
    dropped rotary term 0.3 (both held below)."""
    model, params, biases = model_and_params
    ids = _ids()
    got = model(_t(ids)).numpy()
    for row, out in zip(ids, got):
        want = reference.row_logits(params, jnp.asarray(row), CFG,
                                    biases=biases)
        np.testing.assert_allclose(out, np.asarray(want), atol=2e-5)
    loss = float(model(_t(ids), labels=_t(ids))[1].numpy())
    want = np.mean([float(reference.row_loss(
        params, jnp.asarray(row), CFG, biases=biases)) for row in ids])
    assert loss == pytest.approx(want, rel=2e-6)


@pytest.mark.parametrize("fault,least", [
    ({"mode": "bfloat16"}, 2e-3), ({"drop_rope": True}, 2e-2),
    ({"drop_held": True}, 2e-2), ({"biases": None}, 2e-3)])
def test_the_tolerance_would_catch_what_it_has_to(model_and_params, fault,
                                                  least):
    """The reference with a lower precision, without the rotary score,
    without the routed experts or without the selection bias differs from
    the sound one by far more than the 2e-5 the model is held to."""
    _, params, biases = model_and_params
    row = jnp.asarray(_ids()[0])
    sound = reference.row_logits(params, row, CFG, biases=biases)
    other = reference.row_logits(params, row, CFG,
                                 **{"biases": biases, **fault})
    assert float(jnp.abs(sound - other).max()) > least


def test_every_leafs_gradient_agrees_with_the_reference(model_and_params):
    """Gradient of the mean loss of two rows, leaf by leaf: the largest
    gap over a leaf against that leaf's largest entry, 1e-4 (float32
    sums in another order; a gradient through a bfloat16 score reads
    1e-2)."""
    model, params, biases = model_and_params
    ids = _ids(1)
    leaves = list(model.parameters())

    def loss(arrays):
        old = [p._data for p in leaves]
        for p, a in zip(leaves, arrays):
            p._data = a
        try:
            with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                return model(_t(ids), labels=_t(ids))[1]._data
        finally:
            for p, a in zip(leaves, old):
                p._data = a

    got = jax.grad(loss)([p._data for p in leaves])
    want = jax.grad(lambda p: sum(
        reference.row_loss(p, jnp.asarray(row), CFG, biases=biases)
        for row in ids) / len(ids))(params)
    for (name, _, _), g in zip(W.leaf_specs(CFG), got):
        ref = np.asarray(want[name])
        assert np.abs(ref).max() > 0, name            # every leaf took part
        gap = np.abs(np.asarray(g) - ref).max() / np.abs(ref).max()
        assert gap < 1e-4, (name, gap)


def test_the_model_trains_through_trainstep_and_is_causal():
    paddle.seed(0)
    cfg = DeepseekV3Config.tiny(held_experts=(4, 8), recompute=True,
                                fused_loss_chunk=16)
    model = DeepseekV3ForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda m, x: m(x, labels=x)[1], opt)
    ids = _t(np.random.default_rng(0).integers(0, 128, (2, 32)).astype(
        "int32"))
    losses = [float(step(ids).numpy()) for _ in range(4)]
    assert losses[-1] < losses[0]
    loads = [layer.mlp.expert_load.numpy()
             for layer in model.model.layers[1:]]
    assert all(0 < load.sum() <= 2 * 32 * 4 for load in loads)
    rng = np.random.default_rng(3)
    a = rng.integers(0, 128, (1, 40)).astype("int32")
    b = a.copy()
    b[:, 25:] = rng.integers(0, 128, (1, 15))
    model.config.recompute = False
    la, lb = model(_t(a)).numpy(), model(_t(b)).numpy()
    np.testing.assert_allclose(la[:, :25], lb[:, :25], atol=1e-5)
    assert np.abs(la[:, 25:] - lb[:, 25:]).max() > 1e-3


def test_config_refuses_what_is_not_implemented():
    for kw in ({"q_lora_rank": 1536}, {"n_group": 8}, {"topk_group": 4},
               {"scoring_func": "softmax"},
               {"rope_scaling": {"type": "yarn"}}):
        with pytest.raises(NotImplementedError):
            DeepseekV3Config(**kw)
    cfg = DeepseekV3Config(first_k_dense_replace=3, moe_layer_freq=2,
                           num_hidden_layers=8)
    assert [cfg.is_expert_layer(i) for i in range(8)] == [
        False, False, False, False, True, False, True, False]


# ------------------------------------------------------------- the kernels
def _mla_operands(b, t, h, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 6)
    shapes = ((b, t, h, 128), (b, t, h, 64), (b, t, h, 128), (b, t, 1, 64),
              (b, t, h, 128))
    ops = [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]
    return ops, jax.random.normal(ks[5], shapes[-1], jnp.float32)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 256)])
def test_mla_kernels_agree_with_plain_attention(block_q, block_k):
    """Forward and all five cotangents, the shared key's (summed over the
    heads inside `dk`/`dv`) among them, under the interpreter at a length
    of several blocks; float32 operands, so 1e-5 of entries of size ~3."""
    ops, w = _mla_operands(2, 512, 3)

    def loss(fn, **kw):
        def run(*a):
            out = fn(*a, scale=192 ** -0.5, causal=True, **kw)
            return jnp.sum(out * w), out
        return jax.value_and_grad(run, argnums=(0, 1, 2, 3, 4), has_aux=True)

    before = _compat.mla_blocks()
    (_, out), grads = loss(fa.mla_attention, impl="pallas", block_q=block_q,
                           block_k=block_k)(*ops)
    (_, ref), ref_grads = loss(fa.mla_attention_xla)(*ops)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    assert grads[3].shape == (2, 512, 1, 64)
    for got, want in zip(grads, ref_grads):
        np.testing.assert_allclose(got, want, atol=2e-5)
    after = _compat.mla_blocks()
    for kernel in fa.MLA_KERNELS:
        key = (kernel, block_q, block_k)
        assert after.get(key, 0) == before.get(key, 0) + 1, key


def test_mla_kernels_with_bf16_operands_and_no_causal_mask():
    ops, w = _mla_operands(1, 256, 2, seed=1, dtype=jnp.bfloat16)

    def run(fn, **kw):
        return jax.grad(lambda *a: jnp.sum(fn(
            *a, scale=0.07, causal=False, **kw).astype(jnp.float32) * w),
            argnums=(0, 1, 2, 3, 4))(*ops)

    got = run(fa.mla_attention, impl="pallas", block_q=128, block_k=128)
    want = run(fa.mla_attention_xla)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        err = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()
        assert float(err) < 3e-2 * max(1.0, float(jnp.abs(
            b.astype(jnp.float32)).max()))


def _merged_path(q_nope, q_rope, k_nope, k_rope, v, *, scale, causal,
                 block_q, block_k):
    """The kernels as the parent handed them every part: merged to [b*h,
    s, d] and back."""
    b, s, h, _ = q_nope.shape
    merge = lambda x: jnp.swapaxes(x, 1, 2).reshape(b * h, s, x.shape[-1])
    blocks = fa._blocks_for("mla_attention", s, s, 192, v.dtype, block_q,
                            block_k)
    out = fa._mla_core(merge(q_nope), merge(q_rope), merge(k_nope),
                       k_rope[:, :, 0], merge(v), h, float(scale),
                       bool(causal), blocks)
    return jnp.swapaxes(out.reshape(b, h, s, -1), 1, 2)


@pytest.mark.parametrize("causal,block_q,block_k,kept", [
    (True, 128, 128, False), (False, 128, 256, False),
    (True, 256, 128, True), (False, 128, 128, True)])
def test_mla_kernels_read_the_flat_parts_in_place(causal, block_q, block_k,
                                                  kept):
    """Five heads of their own random data (a head read at another's
    column block fails), several q and k blocks: the forward bit for bit
    the merged path's (each tile does the same arithmetic) and all five
    cotangents against plain attention. ``kept``: under jax.checkpoint
    with `recompute`'s policy, which keeps the forward kernel's output in
    the flat form."""
    ops, w = _mla_operands(2, 512, 5, seed=7)
    b, s, h, d = ops[-1].shape
    scale = 192 ** -0.5
    policy = jax.checkpoint_policies.save_only_these_names(
        fa.ATTENTION_OUT, fa.ATTENTION_LSE)

    def forward(fn, **kw):
        def run(*a):
            out = fn(*a, scale=scale, causal=causal, **kw)
            return jnp.sum(out * w), out
        return jax.checkpoint(run, policy=policy) if kept else run

    def loss(fn, **kw):
        return jax.value_and_grad(forward(fn, **kw), argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)

    forms = _compat.mla_operands()
    (_, out), grads = loss(fa.mla_attention, impl="pallas", block_q=block_q,
                           block_k=block_k)(*ops)
    assert _compat.mla_operands().get("flat", 0) == forms.get("flat", 0) + 1
    (_, ref), ref_grads = loss(fa.mla_attention_xla)(*ops)
    merged = _merged_path(*ops, scale=scale, causal=causal, block_q=block_q,
                          block_k=block_k)
    assert np.array_equal(out, merged)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    for got, want in zip(grads, ref_grads):
        np.testing.assert_allclose(got, want, atol=2e-5)
    if kept:
        # what the checkpoint keeps: out flat, [b, s, h*d], and lse
        listing = io.StringIO()
        with contextlib.redirect_stdout(listing):
            jax.ad_checkpoint.print_saved_residuals(
                lambda *a: forward(fa.mla_attention, impl="pallas",
                                   block_q=block_q, block_k=block_k)(*a)[0],
                *ops)
        # (jax lists `out` as the output of the no-op reduce_precision it
        # guards a residual with that the forward pass also reads)
        kept_rows = {line.split(" ")[0]
                     for line in listing.getvalue().splitlines()
                     if " from the argument " not in line
                     and " from a constant" not in line}
        assert kept_rows == {f"f32[{b},{s},{h * d}]", f"f32[{b * h},8,{s}]"}


def test_narrower_parts_are_merged_and_counted_so():
    """Parts narrower than a lane tile (here 64 and 32 wide) are merged to
    [b*h, s, d] and read a head a row: the `heads` form."""
    ks = jax.random.split(jax.random.key(2), 6)
    shapes = ((1, 256, 3, 64), (1, 256, 3, 32), (1, 256, 3, 64),
              (1, 256, 1, 32), (1, 256, 3, 64))
    ops = [jax.random.normal(k, sh) for k, sh in zip(ks, shapes)]
    forms = _compat.mla_operands()
    grads = jax.grad(lambda *a: jnp.sum(fa.mla_attention(
        *a, scale=0.1, impl="pallas", block_q=128, block_k=128) * ops[0]),
        argnums=(0, 1, 2, 3, 4))(*ops)
    want = jax.grad(lambda *a: jnp.sum(fa.mla_attention_xla(
        *a, scale=0.1) * ops[0]), argnums=(0, 1, 2, 3, 4))(*ops)
    assert _compat.mla_operands().get("heads", 0) == forms.get("heads", 0) + 1
    assert _compat.mla_operands().get("flat", 0) == forms.get("flat", 0)
    for got, ref in zip(grads, want):
        np.testing.assert_allclose(got, ref, atol=2e-5)


def test_mla_attention_checks_its_arguments_and_chooses_its_path():
    ops, _ = _mla_operands(1, 128, 2)
    with pytest.raises(ValueError, match="impl"):
        fa.mla_attention(*ops, scale=1.0, impl="cuda")
    with pytest.raises(ValueError, match="one head"):
        fa.mla_attention(ops[0], ops[1], ops[2],
                         jnp.repeat(ops[3], 2, axis=2), ops[4], scale=1.0)
    with pytest.raises(ValueError, match="divisible"):
        fa.mla_attention(*[o[:, :100] for o in ops], scale=1.0,
                         impl="pallas", block_q=64)
    # off the TPU "auto" is the jax.numpy form: the op face gives it
    out = F.mla_attention(*[_t(o) for o in ops], scale=0.1)
    np.testing.assert_allclose(
        out.numpy(), fa.mla_attention_xla(*ops, scale=0.1), atol=1e-6)
    # the tiles are chosen at the score's whole width, 192
    for kernel in fa.KERNELS:
        bq, bk = fa.choose_blocks(8192, 8192, 192, jnp.bfloat16, kernel)
        assert 8192 % bq == 0 and 8192 % bk == 0
        assert fa._vmem_bytes(kernel, bq, bk, 192, 2) <= fa.VMEM_BUDGET_BYTES


# -------------------------------------------------------------- the router
K, E = 6, 128


def _dispatch(logits, bias, **kw):
    x = _t(np.zeros((logits.shape[0], 4), "float32"))
    tok, w, load = F.moe_held_dispatch(
        x, _t(logits), k=K, start=0, count=E, rows=logits.shape[0] * K,
        scoring="sigmoid", bias=None if bias is None else _t(bias), **kw)
    return tok.numpy(), w.numpy(), load.numpy()


def test_the_selection_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(64, E)).astype("float32")
    bias = rng.normal(size=(E,)).astype("float32")
    scores = 1 / (1 + np.exp(-logits.astype("float64")))
    for b in (None, bias):
        tok, w, load = _dispatch(logits, b, scale=2.448)
        chosen = np.argsort(-(scores + (0 if b is None else b)), -1)[:, :K]
        np.testing.assert_array_equal(
            load, [(chosen == e).sum() for e in range(E)])
        # a token's weights are its chosen experts' scores over their sum,
        # whatever the bias, and sum to the scaling factor
        by_token = np.zeros(64)
        np.add.at(by_token, tok, w)
        np.testing.assert_allclose(by_token, 2.448, rtol=1e-5)
        want = np.take_along_axis(scores, chosen, -1)
        want = np.sort(2.448 * want / want.sum(-1, keepdims=True), -1)
        got = np.stack([np.sort(w[tok == t]) for t in range(64)])
        np.testing.assert_allclose(got, want, rtol=1e-5)
    plain, biased = _dispatch(logits, None)[2], _dispatch(logits, bias)[2]
    assert (plain != biased).any()              # the bias changed the choice


def test_unnormalised_weights_are_the_scores_themselves():
    logits = np.random.default_rng(6).normal(size=(8, E)).astype("float32")
    tok, w, _ = _dispatch(logits, None, renormalize=False, scale=1.5)
    scores = 1 / (1 + np.exp(-logits.astype("float64")))
    top = -np.sort(-scores, -1)[:, :K]
    by_token = np.zeros(8)
    np.add.at(by_token, tok, w)
    np.testing.assert_allclose(by_token, 1.5 * top.sum(-1), rtol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        F.moe_held_dispatch(_t(np.zeros((8, 4), "float32")), _t(logits),
                            k=K, start=0, count=E, rows=48, scoring="tanh")


def test_the_bias_is_a_buffer_and_takes_no_gradient():
    paddle.seed(0)
    layer = MoELayer(16, 8, d_ff=8, k=2, held=(0, 8), scoring="sigmoid",
                     router_dtype="float32", routed_scaling_factor=2.0)
    names = [n for n, _ in layer.named_parameters()]
    assert "gate.e_score_correction_bias" not in names
    buffers = dict(layer.named_buffers())
    bias = buffers["gate.e_score_correction_bias"]
    assert bias.shape == [8] and str(bias.dtype).endswith("float32")
    assert (bias.numpy() == 0).all()
    x = _t(np.random.default_rng(0).normal(size=(1, 8, 16)).astype(
        "float32"))
    out, _ = layer(x)
    out.sum().backward()
    assert layer.gate.weight.grad is not None
    assert np.abs(layer.gate.weight.grad.numpy()).max() > 0
    with pytest.raises(ValueError, match="scoring"):
        MoELayer(16, 8, d_ff=8, k=2, held=(0, 8), scoring="tanh")
    with pytest.raises(ValueError, match="held"):
        MoELayer(16, 8, d_ff=8, k=2, scoring="sigmoid")


# ------------------------------------------------------ the shares add up
def test_eight_shares_add_up_to_the_uncut_reference_layer():
    """Eight MoELayer(held=(16 i, 16)) parts of a 128-expert layer behind
    the sigmoid router with a non-zero bias, the ungated shared experts
    counted once, equal the reference's whole layer (all 128 held)."""
    cfg = dict(CFG, n_routed_experts=128, num_experts_per_tok=6,
               published={"n_routed_experts": 128},
               expert_parallel={"ranks": 1, "rank": 0})
    specs = [(n, s, k) for n, s, k in W.layer_leaves(cfg, 1)
             if n.startswith("mlp.")]
    key = jax.random.key(3)
    wl = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
          for i, (n, s, _) in enumerate(specs)}
    bias = 0.2 * np.random.default_rng(9).normal(size=128).astype("float32")
    x = np.random.default_rng(7).normal(size=(2, 24, 32)).astype("float32")
    flat = jnp.asarray(x.reshape(-1, 32))
    want = np.asarray(reference.moe(flat, wl, cfg, "float32",
                                    bias=jnp.asarray(bias)))
    shared = np.asarray(reference.swiglu(
        flat, wl["mlp.shared_experts.gate_proj.weight"],
        wl["mlp.shared_experts.up_proj.weight"],
        wl["mlp.shared_experts.down_proj.weight"], "float32"))
    total, loads = 0.0, []
    for i in range(8):
        queue = [wl["mlp.gate.weight"]] + [
            wl[f"mlp.experts.{n}"][16 * i:16 * i + 16]
            for n in ("w_gate", "w_up", "w_down")] + [
            wl[f"mlp.shared_experts.{n}.weight"]
            for n in ("gate_proj", "up_proj", "down_proj")]
        mcfg = DeepseekV3Config.tiny(moe_intermediate_size=16)
        with paddle.nn.initializer.param_init_override(
                lambda shape, dtype=None: queue.pop(0)):
            part = MoELayer(
                32, 128, d_ff=16, k=6, held=(16 * i, 16),
                router_dtype="float32", scoring="sigmoid",
                routed_scaling_factor=2.448,
                shared_expert=lambda: D.DeepseekV3MLP(mcfg, 32),
                shared_gate=False, shared_expert_name="shared_experts")
        assert not queue
        part.gate.e_score_correction_bias._rebind(jnp.asarray(bias))
        y, aux = part(_t(x))
        total = total + y.numpy().reshape(-1, 32).astype("float64")
        loads.append(part.expert_load.numpy())
    # every share computed the shared experts: count them once
    total = total - 7 * shared
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert np.concatenate(loads).sum() == 2 * 24 * 6      # nothing dropped


# --------------------------------------------- interleave undone on weights
def test_deinterleaved_weights_give_the_interleaved_ropes_scores():
    """q and the shared key from the de-interleaved weights under the
    rotate-half rope give, head by head, the scores the reference's
    interleaved rope gives from the weights as they are stored."""
    from benchmarks.reference.decoder import rope

    rng = np.random.default_rng(4)
    heads, nope, rot, rank, h, s = 3, 16, 8, 16, 32, 12
    wq = rng.normal(size=(h, heads * (nope + rot))).astype("float32")
    wkva = rng.normal(size=(h, rank + rot)).astype("float32")
    x = jnp.asarray(rng.normal(size=(s, h)).astype("float32"))
    pos = jnp.arange(s)
    q = (x @ wq).reshape(s, heads, nope + rot)
    want = jnp.einsum(
        "shd,td->hst",
        reference.rope_interleaved(q[..., nope:], pos, 1000.0),
        reference.rope_interleaved((x @ wkva)[:, None, rank:], pos,
                                   1000.0)[:, 0])
    wq2 = D._part_major(jnp.asarray(wq), heads=heads, widths=(nope, rot),
                        interleaved=(1,))
    wkva2 = D._rope_tail(jnp.asarray(wkva), width=rot, interleaved=True)
    # the nope columns are the stored ones, head by head; the latent's too
    np.testing.assert_array_equal(
        np.asarray(wq2[:, :heads * nope]).reshape(h, heads, nope),
        wq.reshape(h, heads, nope + rot)[:, :, :nope])
    np.testing.assert_array_equal(np.asarray(wkva2[:, :rank]),
                                  wkva[:, :rank])
    q_rope = rope((x @ wq2)[:, heads * nope:].reshape(s, heads, rot), pos,
                  1000.0)
    k_rope = rope((x @ wkva2)[:, None, rank:], pos, 1000.0)[:, 0]
    got = jnp.einsum("shd,td->hst", q_rope, k_rope)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # without the interleave nothing moves but the heads' grouping
    np.testing.assert_array_equal(
        D._rope_tail(jnp.asarray(wkva), width=rot, interleaved=False), wkva)


# ----------------------------------------------------- scopes and counters
def test_scopes_and_counters_of_a_traced_step():
    """Every device scope of the block, and the registry counters bumped
    once a traced call: the MLA kernels' tile and the `flat` form of their
    operands once a layer and pass (the kernels are taken through the
    interpreter here), the router's scoring and the held share once an
    expert layer."""
    from unittest import mock

    from paddle_tpu.observability import counter

    paddle.seed(0)
    cfg = DeepseekV3Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=2, kv_lora_rank=16, n_routed_experts=128,
        num_experts_per_tok=6, held_experts=(0, 16), fused_loss_chunk=32)
    model = DeepseekV3ForCausalLM(cfg)

    def series(name, labels, key):
        c = counter(name, "", labelnames=labels)
        return lambda: sum(child.value for found, child in c._series()
                           if found == key)

    held = series("paddle_tpu_moe_held", ("experts", "held", "k"),
                  {"experts": "128", "held": "16", "k": "6"})
    router = series("paddle_tpu_moe_router", ("scoring", "experts", "k"),
                    {"scoring": "sigmoid", "experts": "128", "k": "6"})
    before = held(), router(), _compat.mla_blocks()
    forms = _compat.mla_operands()
    leaves = list(model.parameters())

    def loss(arrays, ids):
        old = [p._data for p in leaves]
        for p, a in zip(leaves, arrays):
            p._data = a
        try:
            with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                return model(paddle.to_tensor(ids),
                             labels=paddle.to_tensor(ids))[1]._data
        finally:
            for p, a in zip(leaves, old):
                p._data = a

    ids = jnp.zeros((1, 128), jnp.int32)
    real = fa.mla_attention
    with mock.patch.object(
            fa, "mla_attention",
            lambda *a, **kw: real(*a, **{**kw, "impl": "pallas"})):
        text = jax.jit(jax.grad(loss)).trace(
            [p._data for p in leaves], ids).lower().as_text(debug_info=True)
    assert held() == before[0] + 2 and router() == before[1] + 2
    after = _compat.mla_blocks()
    for kernel in fa.MLA_KERNELS:            # three layers, one pass each
        key = (kernel, 128, 128)
        assert after.get(key, 0) == before[2].get(key, 0) + 3, key
        assert kernel in text
    # the 128-wide parts read in place, once a layer and pass
    assert _compat.mla_operands().get("flat", 0) == forms.get("flat", 0) + 3
    assert _compat.mla_operands().get("heads", 0) == forms.get("heads", 0)
    for name in ("embedding", "attention", "attention.latent",
                 "attention.expand", "attention.core", "attention.out",
                 "mlp", "moe", "moe.router", "moe.experts",
                 "moe.shared_expert", "lm_head_loss"):
        # `name/...` on the way forward, `jvp(name)` where the scope is
        # the outermost of a differentiated operation
        assert f"{name}/" in text or f"({name})" in text, name
