"""The Ling 3.0 decoder (models/ling3.py) and what it brought: the
group-limited sigmoid router (`moe_held_dispatch(n_group=)`,
`moe_group_load`) against a plain NumPy top-k of groups, the held shares
of every rank adding up to the whole layer, and the model's loss and
gradients against the plain reference (benchmarks/reference/ling3.py) on
seeded weights.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as F
from paddle_tpu.incubate.moe import MoELayer
from paddle_tpu.models import Ling3Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

E, GROUPS, KEPT, K = 32, 4, 2, 4


def _t(a):
    return paddle.to_tensor(a)


def _numpy_route(logits, bias, n_group, topk_group, k):
    """The group-limited choice written out in NumPy: a group's score is
    the sum of its two best s + bias; the best topk_group groups are kept;
    the k best s + bias among them are chosen."""
    s = 1 / (1 + np.exp(-logits.astype("float64")))
    choose = s + (0 if bias is None else bias)
    n, e = choose.shape
    per = e // n_group
    best2 = np.sort(choose.reshape(n, n_group, per), -1)[..., -2:].sum(-1)
    kept = np.argsort(-best2, -1)[:, :topk_group]
    mask = np.zeros((n, n_group), bool)
    np.put_along_axis(mask, kept, True, -1)
    masked = np.where(np.repeat(mask, per, -1), choose, -np.inf)
    return s, np.argsort(-masked, -1)[:, :k], mask


def _dispatch(logits, bias, **kw):
    x = _t(np.zeros((logits.shape[0], 4), "float32"))
    tok, w, load = F.moe_held_dispatch(
        x, _t(logits), k=K, start=0, count=E, rows=logits.shape[0] * K,
        scoring="sigmoid", bias=None if bias is None else _t(bias),
        scale=2.5, **kw)
    return tok.numpy(), w.numpy(), load.numpy()


@pytest.mark.parametrize("biased", [False, True])
def test_group_limited_choice_is_numpys_top_k_of_groups(biased):
    rng = np.random.default_rng(1)
    logits = rng.normal(size=(96, E)).astype("float32")
    bias = rng.normal(size=(E,)).astype("float32") if biased else None
    tok, w, load = _dispatch(logits, bias, n_group=GROUPS, topk_group=KEPT)
    s, chosen, kept = _numpy_route(logits, bias, GROUPS, KEPT, K)
    np.testing.assert_array_equal(
        load, [(chosen == e).sum() for e in range(E)])
    # every chosen expert lies in a kept group
    assert kept[np.arange(96)[:, None], chosen // (E // GROUPS)].all()
    # the weights: the chosen scores over their sum, times the scale
    want = np.take_along_axis(s, chosen, -1)
    want = np.sort(2.5 * want / want.sum(-1, keepdims=True), -1)
    got = np.stack([np.sort(w[tok == t]) for t in range(96)])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the tokens that kept each group
    group_load = F.moe_group_load(
        _t(logits), None if bias is None else _t(bias), n_group=GROUPS,
        topk_group=KEPT).numpy()
    np.testing.assert_array_equal(group_load, kept.sum(0))
    assert group_load.sum() == 96 * KEPT
    # without the limit the choice differs somewhere
    plain = _dispatch(logits, bias)[2]
    assert (plain != load).any()


def test_one_group_is_the_plain_sigmoid_choice():
    rng = np.random.default_rng(2)
    logits = rng.normal(size=(64, E)).astype("float32")
    bias = rng.normal(size=(E,)).astype("float32")
    for kw in ({}, {"n_group": 1, "topk_group": 1}):
        _, _, load = _dispatch(logits, bias, **kw)
        _, chosen, _ = _numpy_route(logits, bias, 1, 1, K)
        np.testing.assert_array_equal(
            load, [(chosen == e).sum() for e in range(E)])


def test_groups_are_the_sigmoid_routers_and_must_divide_the_experts():
    with pytest.raises(ValueError, match="n_group"):
        MoELayer(16, E, d_ff=8, k=2, held=(0, 8), n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="n_group"):
        MoELayer(16, 30, d_ff=8, k=2, held=(0, 8), scoring="sigmoid",
                 n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="n_group"):
        MoELayer(16, E, d_ff=8, k=2, held=(0, 8), scoring="sigmoid",
                 n_group=4, topk_group=5)
    one = MoELayer(16, E, d_ff=8, k=2, held=(0, 8), scoring="sigmoid")
    assert not hasattr(one, "group_load")
    with pytest.raises(ValueError, match="n_group"):
        F.moe_held_dispatch(_t(np.zeros((8, 4), "float32")),
                            _t(np.zeros((8, E), "float32")), k=2, start=0,
                            count=E, rows=16, n_group=4, topk_group=2)


def _layer(held, seed=0):
    paddle.seed(seed)
    return MoELayer(
        16, E, d_ff=8, k=K, held=held, router_dtype="float32",
        scoring="sigmoid", routed_scaling_factor=2.5, shared_gate=False,
        shared_expert_name="shared_experts", n_group=GROUPS,
        topk_group=KEPT, shared_expert=lambda: paddle.nn.Linear(
            16, 16, bias_attr=False))


def test_every_ranks_held_part_adds_up_to_the_references_whole_layer():
    """Four ranks of eight experts each (one group each): their held parts
    add up to the plain reference's layer over all 32 experts (its shared
    expert zero: each rank adds the shared expert once of its own), and
    their loads to its routing's."""
    from benchmarks.reference import ling3 as reference

    whole = _layer((0, E), seed=4)
    x = np.random.default_rng(5).normal(size=(24, 16)).astype("float32")
    cfg = {"hidden_size": 16, "vocab_size": 8, "num_attention_heads": 1,
           "head_dim": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
           "v_head_dim": 8, "kv_lora_rank": 8, "short_conv_kernel_size": 4,
           "intermediate_size": 8, "published": {"num_experts": E},
           "num_experts": E, "expert_parallel": {"rank": 0},
           "num_experts_per_tok": K, "n_group": GROUPS, "topk_group": KEPT,
           "moe_intermediate_size": 8, "num_shared_experts": 1,
           "moe_shared_expert_intermediate_size": 8,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    wl = {"mlp.gate.weight": whole.gate.weight.numpy(),
          "mlp.experts.w_gate": whole.experts.w_gate.numpy(),
          "mlp.experts.w_up": whole.experts.w_up.numpy(),
          "mlp.experts.w_down": whole.experts.w_down.numpy(),
          "mlp.shared_experts.gate_proj.weight": np.zeros((16, 8), "f4"),
          "mlp.shared_experts.up_proj.weight": np.zeros((16, 8), "f4"),
          "mlp.shared_experts.down_proj.weight": np.zeros((8, 16), "f4")}
    want = np.asarray(reference.moe(jnp.asarray(x), wl, cfg, "float32"))
    _, idx = reference.route(jnp.asarray(x), wl["mlp.gate.weight"], cfg,
                             "float32")
    total, loads = np.zeros_like(x), []
    for rank in range(GROUPS):
        part = _layer((rank * 8, 8))
        part.gate.weight._rebind(whole.gate.weight._data)
        for name in ("w_gate", "w_up", "w_down"):
            getattr(part.experts, name)._rebind(
                getattr(whole.experts, name)._data[rank * 8:rank * 8 + 8])
        xs = _t(x[None])
        total += (part(xs)[0] - part.shared_experts(xs)).numpy()[0]
        loads.append(part.expert_load.numpy())
        assert part.group_load.numpy().sum() == 24 * KEPT
    np.testing.assert_allclose(total, want, atol=1e-5)
    np.testing.assert_array_equal(
        np.concatenate(loads), [(np.asarray(idx) == e).sum()
                                for e in range(E)])
    dropped = np.asarray(reference.moe(jnp.asarray(x), wl, cfg, "float32",
                                       no_group_limit=True))
    assert np.abs(dropped - want).max() > 1e-3


# ----------------------------------------------------------------- the model
def _tiny_cfg():
    """A tiny Ling of two layers: published layers 0 (dense, KDA) and 11
    (an expert layer, MLA) at toy widths, one rank of four, float32."""
    return {
        "vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 16,
        "num_hidden_layers": 2, "num_attention_heads": 2, "head_dim": 16,
        "kv_lora_rank": 16, "q_lora_rank": None, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
        "rope_interleave": True, "rope_scaling": None, "rms_norm_eps": 1e-6,
        "first_k_dense_replace": 2, "layer_group_size": 6,
        "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
        "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "score_function": "sigmoid",
        "use_qk_norm": True, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "max_position_embeddings": 128, "initializer_range": 0.3,
        "torch_dtype": "float32", "published": {"num_experts": 16},
        "expert_parallel": {"ranks": 4, "rank": 1},
        "kept_layers": [0, 11]}


def test_the_stack_is_the_published_pattern():
    tiny = Ling3Config.tiny()
    assert [(tiny.is_latent_attention_layer(i), tiny.is_expert_layer(i))
            for i in range(7)] == [(False, False)] + [(False, True)] * 5 + [
                (True, True)]
    full = Ling3Config(num_hidden_layers=42)
    mla = [i for i in range(42) if full.is_latent_attention_layer(i)]
    assert mla == [5, 11, 17, 23, 29, 35, 41]
    assert [i for i in range(42) if not full.is_expert_layer(i)] == [0, 1]
    with pytest.raises(NotImplementedError, match="kda_lower_bound"):
        Ling3Config.tiny(kda_lower_bound=-6)
    with pytest.raises(NotImplementedError, match="no_kda_lora"):
        Ling3Config.tiny(no_kda_lora=False)
    with pytest.raises(ValueError, match="layer_ids"):
        Ling3Config.tiny(num_hidden_layers=3)


def test_loss_and_gradients_match_the_plain_reference():
    """Seeded weights through the runner's own build: the loss of two rows
    and every leaf's gradient against reference/ling3.py."""
    from benchmarks import train_ling3 as T
    from benchmarks import weights_ling3 as W
    from benchmarks.reference import ling3 as reference

    cfg = _tiny_cfg()
    model = T.build_model(cfg, 7)
    assert [n for n, _ in model.named_parameters()] == [
        n for n, _, _ in W.leaf_specs(cfg)]
    assert [(type(layer.attention).__name__, layer.is_expert_layer)
            for layer in model.model.layers] == [
                ("Ling3KDA", False), ("Ling3Attention", True)]
    ids = np.random.default_rng(0).integers(0, 64, (2, 40))
    loss_fn = lambda m, i: m(i, labels=i)[1]
    loss = loss_fn(model, _t(ids))
    loss.backward()
    params = reference.float32_params(cfg, 7, jnp.float32)
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: sum(reference.row_loss(p, jnp.asarray(r), cfg)
                      for r in ids) / len(ids)))(params)
    assert float(loss) == pytest.approx(float(value), rel=2e-5)
    for name, p in model.named_parameters():
        want = np.asarray(grads[name])
        got = p.grad.numpy()
        scale = max(np.abs(want).max(), 1e-8)
        assert np.abs(got - want).max() <= 1e-3 * scale, name
    # the expert layer's group loads: 80 tokens keep 2 groups
    for layer in model.model.layers[1:]:
        assert layer.mlp.group_load.numpy().sum() == 80 * 2
