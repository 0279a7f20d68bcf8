"""models/mellum2.py, the band schedule of kernels/pallas/flash_attention.py
and YaRN (ops/impl/fused_ops.py): the window kernels under the interpreter
against a dense band-masked reference, forward and both backward kernels,
at windows shorter than, equal to and not a multiple of the tile and at
one longer than the row (which is causal attention); YaRN's frequencies
against hand-worked values at the published widths; the model against the
plain reference (benchmarks/reference/mellum2.py: float32, `highest`,
imports nothing of paddle_tpu) on seeded random weights; the held shares
of a softmax top-8 layer adding up to the whole; and the scopes and
counters of a traced step. Three AdamW steps through the runner are in
tests/benchmarks/test_mellum2_benchmark.py, beside the configuration.
"""
import math
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
from paddle_tpu.models import Mellum2Config, Mellum2ForCausalLM  # noqa: E402
from paddle_tpu.ops.impl import fused_ops, nn_ops             # noqa: E402

from benchmarks import weights_mellum2 as W                   # noqa: E402
from benchmarks.reference import mellum2 as reference         # noqa: E402

SLIDING, FULL = "sliding_attention", "full_attention"
ROPE = {SLIDING: {"rope_type": "default", "rope_theta": 500000},
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782}}
# a configuration file's keys at toy widths: what the reference reads
CFG = {
    "hidden_size": 32, "vocab_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 7,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL], "num_hidden_layers": 4,
    "rope_parameters": ROPE, "moe_intermediate_size": 16, "num_experts": 4,
    "published": {"num_experts": 16}, "expert_parallel": {"ranks": 4,
                                                          "rank": 2},
    "num_experts_per_tok": 8, "rms_norm_eps": 1e-6, "initializer_range": 0.3,
}
SEQ = 24


def _t(a):
    return paddle.to_tensor(np.asarray(a))


def _config(**extra):
    return Mellum2Config(
        **{k: CFG[k] for k in (
            "hidden_size", "vocab_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window",
            "layer_types", "num_hidden_layers", "rope_parameters",
            "moe_intermediate_size", "num_experts_per_tok", "rms_norm_eps")},
        num_experts=16, held_experts=(8, 4), **extra)


# ------------------------------------------------------ the band schedule
def _band_reference(q, k, v, window):
    """Dense softmax attention over the band mask, float32."""
    s, d = q.shape[1], q.shape[-1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / np.sqrt(d)
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


@pytest.mark.parametrize("seq,window,block", [
    (512, 64, 128),      # shorter than the tile
    (512, 128, 128),     # the tile
    (512, 300, 128),     # not a multiple of the tile
    (384, 200, 128),
    (512, 256, None),    # the chooser's tile: the window, edges in halves
    (1024, 300, 256),    # edges in halves, the far edge off the tile's
    (1024, 512, 256),    # halves beside a block wholly inside the band
    (384, 1000, 128),    # longer than the row: causal attention
])
def test_window_kernels_agree_with_the_band_masked_reference(seq, window,
                                                             block):
    rng = np.random.default_rng(seq + window)
    q, k, v, g = (jnp.asarray(rng.standard_normal((2, seq, 2, 64)),
                              jnp.float32) for _ in range(4))
    kw = {} if block is None else {"block_q": block, "block_k": block}

    def got(q, k, v):
        return fa.flash_attention(q, k, v, window=window, **kw)

    out = got(q, k, v)
    np.testing.assert_allclose(out, _band_reference(q, k, v, window),
                               atol=2e-5)
    grads = jax.grad(lambda *a: jnp.sum(got(*a) * g), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_band_reference(*a, window) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(grads, want):                 # dq, dk, dv
        np.testing.assert_allclose(a, b, atol=5e-5)
    if window >= seq:
        causal = fa.flash_attention(q, k, v, **kw)
        np.testing.assert_allclose(out, causal, atol=2e-6)


def test_the_window_kernels_have_names_and_tiles_of_their_own():
    """window=None is the causal kernels and their tiles as they were; a
    window takes WINDOW_KERNELS, square tiles as wide as the window, and
    a band schedule that computes 1.5 times the band's pairs at the
    cell's shape, not the triangle's 4.3."""
    from benchmarks import work_mellum2 as work

    q = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.float32)

    def names(**kw):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            fa.flash_attention(*a, **kw)), (0, 1, 2)))(q, q, q))
        return {n for n in fa.KERNELS + fa.WINDOW_KERNELS
                if f"name={n}" in text or f"'{n}'" in text or n in text}

    assert names() == set(fa.KERNELS)
    assert names(window=128) == set(fa.WINDOW_KERNELS)
    assert not set(fa.WINDOW_KERNELS) & (set(fa.KERNELS) | set(
        fa.MLA_KERNELS))
    bf16 = jnp.bfloat16
    for kernel in fa.KERNELS:        # the causal kernels' tiles unchanged
        assert fa.choose_blocks(8192, 8192, 128, bf16, kernel) == (1024,
                                                                   1024)
    for kernel in fa.WINDOW_KERNELS:
        assert fa.choose_window_blocks(8192, 1024, 128, bf16, kernel) == (
            1024, 1024)
        assert fa.choose_window_blocks(8192, 100, 128, bf16, kernel) == (
            128, 128)
    # 8 q blocks of 1024 over 2 key blocks each, the first clipped; every
    # one a diagonal or far-edge tile, three quarters of it computed
    assert fa.band_steps(8192, 1024, 1024, 1024) == (2, 2)
    assert work.visited_pairs(8192, 1024, 1024, 1024) == (
        (8 * 2 - 1) * 3 * 1024**2 // 4)
    assert work.visited_pairs(8192, 1024, 1024, 1024) / work.band_pairs(
        8192, 1024) == pytest.approx(1.4999, abs=1e-3)
    # tiles of 512: the middle tile of a q block's three is wholly inside
    assert work.visited_pairs(8192, 1024, 512, 512) == (
        (14 * 10 + (4 + 3) + 3) * 512**2 // 4)
    assert work.visited_pairs(8192, 1024, 512, 512) / work.band_pairs(
        8192, 1024) == pytest.approx(1.2499, abs=1e-3)
    # tiles that are not run in halves compute the whole tile
    assert work.visited_pairs(8192, 1024, 128, 128) == (
        64 * 9 - 36) * 128**2
    # the causal triangle of tiles of 1024 against the band: 4.8 times
    assert 36 * 1024**2 / work.band_pairs(8192, 1024) == pytest.approx(
        4.8, abs=0.01)
    with pytest.raises(ValueError):
        fa.flash_attention(jnp.zeros((1, 256, 1, 8)), jnp.zeros(
            (1, 256, 1, 8)), jnp.zeros((1, 256, 1, 8)), window=64,
            causal=False)


def test_edge_masks_are_the_band_mask_where_an_edge_crosses_a_block():
    """Square tiles no wider than the window: the diagonal block and the
    far-edge block (q - k offset D) are told by position, and the one
    comparison each makes is the band mask there, over the whole tile and
    over each of its halves, held as [q, k] or transposed; other shapes
    compare with the block's offsets (``_mask``)."""
    assert fa._band_edges(None, 512, 512) is None
    assert fa._band_edges(1024, 512, 256) is None        # not square
    assert fa._band_edges(64, 128, 128) is None          # wider than w
    assert fa._band_edges(1024, 512, 512) == 1024
    assert fa._band_edges(300, 128, 128) == 384
    for window, block in ((1024, 512), (300, 128), (128, 128), (512, 512)):
        d = fa._band_edges(window, block, block)
        r = np.arange(block)[:, None]
        c = np.arange(block)[None, :]
        for kind, offset in (("diag", 0), ("far", d)):
            gap = offset + r - c
            seen = (gap >= 0) & (gap < window)
            whole = slice(None)
            assert seen.any() and not seen.all()
            for transposed in (False, True):
                parts = fa._edge_halves(kind, block, transposed) or (
                    (whole, whole),)
                want = seen.T if transposed else seen
                covered = np.zeros_like(want)
                for rows, cols in parts:
                    shape = want[rows, cols].shape
                    got = np.asarray(fa._edge_mask(
                        kind, shape, int(transposed), window, d,
                        (rows, cols)))
                    np.testing.assert_array_equal(got, want[rows, cols])
                    covered[rows, cols] = True
                assert covered[want].all()       # no pair it sees left out


def test_window_attention_op_takes_the_kernels_or_the_math():
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
               for _ in range(3))
    want = _band_reference(q, k, v, 40)
    for impl in ("xla", "pallas", "auto"):
        got = nn_ops.window_attention(q, k, v, window=40, impl=impl)
        np.testing.assert_allclose(got, want, atol=2e-5)
    got = F.window_attention(_t(q), _t(k), _t(v), 40).numpy()
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError):
        nn_ops.window_attention(q, k, v, window=40, impl="cuda")


# ------------------------------------------------------------------- YaRN
def test_yarn_parameters_hand_worked():
    """d 128, theta 500,000, factor 16 over 8,192 original positions: the
    correction range is pairs 18 (32 rotations: 18.08, floored) to 35 (one
    rotation: 34.98, ceiled); pairs below keep the source's frequency,
    pairs from 35 on take it over 16, pairs between blend along the ramp;
    cos and sin scale by 0.1 ln 16 + 1."""
    inv, scale = fused_ops.yarn_parameters(128, 500000.0, 16.0, 8192,
                                           32.0, 1.0, 1.2772588722239782)
    assert inv.shape == (64,) and inv.dtype == np.float32
    assert scale == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    assert fused_ops.yarn_parameters(128, 500000.0, 16.0, 8192)[1] == (
        pytest.approx(1.2772588722239782))
    dim = lambda rot: 128 * math.log(8192 / (rot * 2 * math.pi)) / (
        2 * math.log(500000))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    base = 500000.0 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    ramp = (26 - 18) / 17
    assert inv[26] == pytest.approx(base[26] / 16 * ramp + base[26] * (
        1 - ramp), rel=1e-6)
    assert inv[17] == pytest.approx(0.030634523, rel=1e-6)
    assert inv[63] == pytest.approx(1.5344629e-07, rel=1e-5)
    # the reference's own reckoning agrees
    ref, ref_scale = reference.yarn_inv_freq(128, ROPE[FULL])
    np.testing.assert_allclose(inv, np.asarray(ref), rtol=1e-6)
    assert ref_scale == scale


def test_rope_qk_freqs_is_rope_at_the_given_frequencies():
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.standard_normal((2, 10, 3, 16)), jnp.float32)
            for _ in range(2))
    plain = tuple(float(f) for f in 1e4 ** (-np.arange(0, 16, 2) / 16))
    a = fused_ops.rope_qk_freqs(q, k, inv_freq=plain)
    b = fused_ops.rope_qk(q, k, base=1e4)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-5)
    scaled = fused_ops.rope_qk_freqs(q, k, inv_freq=plain, scale=1.5)
    np.testing.assert_allclose(scaled[0], 1.5 * np.asarray(a[0]), atol=1e-5)
    got = F.rope_qk_freqs(_t(q), _t(k), inv_freq=plain, scale=1.5)
    np.testing.assert_allclose(got[1].numpy(), scaled[1], atol=1e-6)


# -------------------------------------------------- model against reference
@pytest.fixture(scope="module")
def model_and_params():
    made = W.make_weights(CFG, 11, jnp.float32)
    queue = list(made.values())
    with paddle.nn.initializer.param_init_override(
            lambda shape, dtype=None: queue.pop(0)):
        model = Mellum2ForCausalLM(_config())
    assert not queue
    assert [(n, tuple(p.shape)) for n, p in model.named_parameters()] == [
        (n, s) for n, s, _ in W.leaf_specs(CFG)]
    return model, dict(made)


def _ids(seed=0, rows=2):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (rows, SEQ)).astype("int32")


def test_loss_agrees_with_the_reference(model_and_params):
    model, params = model_and_params
    ids = _ids()
    loss = float(model(_t(ids), labels=_t(ids))[1].numpy())
    want = np.mean([float(reference.row_loss(params, jnp.asarray(row), CFG))
                    for row in ids])
    assert loss == pytest.approx(want, rel=2e-6)


@pytest.mark.parametrize("fault", [
    {"mode": "bfloat16"}, {"full_window": True}, {"plain_rope": True}])
def test_the_tolerance_would_catch_what_it_has_to(model_and_params, fault):
    """The reference with a lower precision, with the window left out or
    with YaRN left out differs from the sound one by far more than the
    2e-6 the model's loss is held to."""
    _, params = model_and_params
    row = jnp.asarray(_ids()[0])
    sound = float(reference.row_loss(params, row, CFG))
    other = float(reference.row_loss(params, row, CFG, **fault))
    assert abs(sound - other) / sound > 2e-4


def test_every_leafs_gradient_agrees_with_the_reference(model_and_params):
    """Gradient of the mean loss of two rows, leaf by leaf: the largest
    gap over a leaf against that leaf's largest entry, 1e-4."""
    model, params = model_and_params
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
        reference.row_loss(p, jnp.asarray(row), CFG)
        for row in ids) / len(ids))(params)
    for (name, _, _), g in zip(W.leaf_specs(CFG), got):
        ref = np.asarray(want[name])
        assert np.abs(ref).max() > 0, name            # every leaf took part
        gap = np.abs(np.asarray(g) - ref).max() / np.abs(ref).max()
        assert gap < 1e-4, (name, gap)


def test_the_model_trains_through_trainstep_and_sees_its_window():
    """Four steps through TrainStep with recompute and the fused loss; a
    sliding layer alone does not see past its window, a full layer does."""
    paddle.seed(0)
    cfg = Mellum2Config.tiny(held_experts=(2, 4), recompute=True,
                             fused_loss_chunk=16)
    model = Mellum2ForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda m, x: m(x, labels=x)[1], opt)
    ids = _t(np.random.default_rng(0).integers(0, 128, (2, 32)).astype(
        "int32"))
    losses = [float(step(ids).numpy()) for _ in range(4)]
    assert losses[-1] < losses[0]
    loads = [layer.mlp.expert_load.numpy() for layer in model.model.layers]
    assert all(0 < load.sum() <= 2 * 32 * 2 for load in loads)
    model.config.recompute = False
    attn = model.model.layers[0].self_attn
    x = _t(np.random.default_rng(3).normal(size=(1, 40, 32)).astype(
        "float32"))
    y = x.numpy().copy()
    y[:, :10] += 1.0                   # outside the window of position 17+
    a, b = attn(x).numpy(), attn(_t(y)).numpy()
    np.testing.assert_allclose(a[:, 17:], b[:, 17:], atol=1e-5)
    assert np.abs(a[:, 10:17] - b[:, 10:17]).max() > 1e-3
    full = model.model.layers[3].self_attn
    assert np.abs(full(x).numpy()[:, 17:] - full(_t(y)).numpy()[:, 17:]
                  ).max() > 1e-3


def test_config_reads_layer_types_and_rope_parameters():
    cfg = Mellum2Config.tiny()
    assert cfg.layer_types == [SLIDING] * 3 + [FULL]
    assert cfg.yarn(SLIDING) is None
    inv, scale = cfg.yarn(FULL)
    assert len(inv) == 8 and scale == pytest.approx(1.2772588722239782)
    layers = Mellum2ForCausalLM(cfg).model.layers
    assert [layer.self_attn.window for layer in layers] == [8, 8, 8, None]
    with pytest.raises(ValueError):
        Mellum2Config.tiny(layer_types=["sliding_attention"] * 3 + ["x"])
    with pytest.raises(NotImplementedError):
        Mellum2Config.tiny(rope_parameters={
            SLIDING: {"rope_type": "linear", "rope_theta": 1.0},
            FULL: {"rope_type": "default", "rope_theta": 1.0}})
    with pytest.raises(NotImplementedError):
        Mellum2Config.tiny(norm_topk_prob=False)


# ------------------------------------------------------ the shares add up
def test_four_shares_add_up_to_the_uncut_reference_layer():
    """Four MoELayer(held=(16 i, 16)) parts of a 64-expert softmax top-8
    layer equal the reference's whole layer (all 64 held)."""
    cfg = dict(CFG, num_experts=64, published={"num_experts": 64},
               expert_parallel={"ranks": 1, "rank": 0})
    specs = [(n, s) for n, s, _ in W.layer_leaves(cfg, 0)
             if n.startswith("mlp.")]
    key = jax.random.key(3)
    wl = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, i), s)
          for i, (n, s) in enumerate(specs)}
    x = np.random.default_rng(7).normal(size=(2, 24, 32)).astype("float32")
    want = np.asarray(reference.moe(jnp.asarray(x.reshape(-1, 32)), wl, cfg,
                                    "float32"))
    total, loads = 0.0, []
    for i in range(4):
        queue = [wl["mlp.gate.weight"]] + [
            wl[f"mlp.experts.{n}"][16 * i:16 * i + 16]
            for n in ("w_gate", "w_up", "w_down")]
        with paddle.nn.initializer.param_init_override(
                lambda shape, dtype=None: queue.pop(0)):
            part = MoELayer(32, 64, d_ff=16, k=8, held=(16 * i, 16),
                            router_dtype="float32")
        assert not queue
        y, _ = part(_t(x))
        total = total + y.numpy().reshape(-1, 32).astype("float64")
        loads.append(part.expert_load.numpy())
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert np.concatenate(loads).sum() == 2 * 24 * 8      # nothing dropped


# ----------------------------------------------------- scopes and counters
def test_scopes_and_counters_of_a_traced_step():
    """The device scopes of both layer kinds and the expert layer, the
    window kernels on the sliding layers (taken through the interpreter
    here) with their tile recorded once a layer and pass, the causal
    kernels nowhere."""
    from unittest import mock

    paddle.seed(0)
    model = Mellum2ForCausalLM(Mellum2Config.tiny(
        sliding_window=64, held_experts=(0, 4), fused_loss_chunk=64))
    before = _compat.flash_blocks()
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

    ids = jnp.zeros((1, 256), jnp.int32)
    real = nn_ops.window_attention
    with mock.patch.object(
            nn_ops, "window_attention",
            lambda *a, **kw: real(*a, **{**kw, "impl": "pallas"})):
        text = jax.jit(jax.grad(loss)).trace(
            [p._data for p in leaves], ids).lower().as_text(debug_info=True)
    after = _compat.flash_blocks()
    for kernel in fa.WINDOW_KERNELS:          # three sliding layers, once
        key = (kernel, 128, 128)
        assert after.get(key, 0) == before.get(key, 0) + 3, key
        assert kernel in text
    for kernel in fa.KERNELS:
        assert f"{kernel}_" not in text and f"{kernel}\"" not in text
    for name in ("embedding", SLIDING, FULL, "moe", "moe.router",
                 "moe.experts", "lm_head_loss"):
        assert f"{name}/" in text or f"({name})" in text, name


def test_a_traced_step_records_the_grouped_matmul_tiles():
    """The held experts through the grouped-matmul kernels (the
    interpreter here): each kernel's traced calls carry the tiles
    `choose_tiles` gives the layer's pass, and no other."""
    from unittest import mock

    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    from paddle_tpu.ops.impl import moe_ops

    paddle.seed(0)
    model = Mellum2ForCausalLM(Mellum2Config.tiny(
        sliding_window=64, held_experts=(0, 4), fused_loss_chunk=64))
    leaves = list(model.parameters())
    ids = jnp.zeros((1, 256), jnp.int32)
    mlp = model.model.layers[0].mlp
    rows, experts = mlp.held_rows(ids.size), mlp.held[1]
    hidden, ff = mlp.experts.w_gate.shape[1:]
    itemsize = jnp.dtype(mlp.experts.w_gate._data.dtype).itemsize
    want = {(kernel, *gm.choose_tiles(rows, k, m, experts, itemsize, kernel))
            for k, m in ((hidden, ff), (ff, hidden))
            for kernel, kk, mm in ((gm.FWD, k, m), (gm.DLHS, m, k),
                                   (gm.DRHS, k, m))}
    want = {(kernel, min(tm, rows), tk, tn)
            for kernel, tm, tk, tn in want}

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

    before = _compat.gmm_tiles()
    real = moe_ops.moe_held_experts
    with mock.patch.object(
            moe_ops, "moe_held_experts",
            lambda *a, **kw: real(*a, **{**kw, "impl": "pallas"})):
        jax.jit(jax.grad(loss)).trace([p._data for p in leaves], ids)
    moved = {key for key, n in _compat.gmm_tiles().items()
             if n != before.get(key, 0)}
    assert moved == want
