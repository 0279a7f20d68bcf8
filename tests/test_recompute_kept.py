"""What a rematerialised layer keeps (PERF.md section 3, PR 34): the flash
and MLA forward kernels name their output and log-sum-exp in their forward
rules (`ATTENTION_OUT`, `ATTENTION_LSE`), and `distributed.recompute`
checkpoints its segment under a policy that saves exactly those names. The
attention backward needs nothing else beyond q, k and v, so the backward
pass of a recomputed layer holds no second forward kernel; everything else
in the layer is recomputed as before, and a segment without such a kernel
keeps nothing but its inputs.

Witnesses: the kernels of a traced gradient (the Pallas interpreter off the
TPU), `paddle_tpu_recompute_kept`, jax's own listing of saved residuals,
the numbers against the parent's way (a bare `jax.checkpoint` of the same
segment) bit for bit, and one MLA block compiled for a described v5e
(nothing runs; tests/test_deepseek_v3_layout.py is the pattern). The
topology is described inside a fixture; where none can be described those
tests skip.
"""
import collections
import contextlib
import io
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import recompute
from paddle_tpu.jit.api import _rng_lift
from paddle_tpu.kernels.pallas import _compat
from paddle_tpu.kernels.pallas import flash_attention as fa

LAYERS, SEQ = 2, 128


# ------------------------------------------------------------ tiny models
def _mla_model(rc):
    """Two MLA layers, the first dense and the second an expert layer."""
    from paddle_tpu.models import DeepseekV3Config, DeepseekV3ForCausalLM

    paddle.seed(0)
    return DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=LAYERS,
        num_attention_heads=2, kv_lora_rank=16, n_routed_experts=128,
        num_experts_per_tok=6, held_experts=(0, 16), fused_loss_chunk=32,
        recompute=rc))


def _llama_model(rc):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=LAYERS, recompute=rc))


@contextlib.contextmanager
def _mla_through_the_kernels():
    real = fa.mla_attention
    with mock.patch.object(
            fa, "mla_attention",
            lambda *a, **kw: real(*a, **{**kw, "impl": "pallas"})):
        yield


@contextlib.contextmanager
def _flash_through_the_kernels():
    """sdpa takes the kernels from this length on (2048 on the chip)."""
    paddle.set_flags({"FLAGS_flash_attention_min_seq": SEQ})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_flash_attention_min_seq": 2048})


MODELS = {
    "mla": (_mla_model, _mla_through_the_kernels, "mla_attention",
            fa.MLA_KERNELS),
    "flash": (_llama_model, _flash_through_the_kernels, "flash_attention",
              fa.KERNELS),
}


def _loss_of(model):
    """loss(arrays, ids) over the model's parameters as plain arrays."""
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

    return loss, [p._data for p in leaves], [p.name for p in leaves]


def _ids():
    return jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, SEQ)), jnp.int32)


@contextlib.contextmanager
def _the_parents_way():
    """`recompute` as it was: a bare `jax.checkpoint`, nothing kept."""
    real = jax.checkpoint
    with mock.patch.object(jax, "checkpoint",
                           lambda fn, **policy: real(fn)):
        yield


# --------------------------------------------------------- reading a jaxpr
def _kernel_calls(jaxpr, found=None):
    """{kernel name: pallas_calls} of a jaxpr and of every jaxpr inside it
    (`remat`, `custom_vjp_call`, `pjit`, ...); a kernel's own body is not a
    place to look."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, found)
    return found


def _traced_kernels(which, rc):
    make, through_the_kernels, _, _ = MODELS[which]
    loss, arrays, _ = _loss_of(make(rc))
    with through_the_kernels():
        return _kernel_calls(
            jax.make_jaxpr(jax.grad(loss))(arrays, _ids()).jaxpr)


# ------------------------------------------------- the mechanism, traced
@pytest.mark.parametrize("which", sorted(MODELS))
def test_a_recomputed_layer_runs_the_forward_kernel_once(which):
    _, _, op, (fwd, bwd_dq, bwd_dkv) = MODELS[which]
    before = _compat.recompute_kept().get(op, 0)
    calls = _traced_kernels(which, rc=True)
    # once a layer, in the forward pass; the backward pass reads what that
    # call wrote
    assert calls == {fwd: LAYERS, bwd_dq: LAYERS, bwd_dkv: LAYERS}
    assert _compat.recompute_kept()[op] == before + LAYERS
    with _the_parents_way():
        assert _traced_kernels(which, rc=True) == {
            fwd: 2 * LAYERS, bwd_dq: LAYERS, bwd_dkv: LAYERS}


@pytest.mark.parametrize("which", sorted(MODELS))
def test_without_recompute_nothing_changes_and_nothing_is_counted(which):
    _, _, op, (fwd, bwd_dq, bwd_dkv) = MODELS[which]
    before = _compat.recompute_kept().get(op, 0)
    assert _traced_kernels(which, rc=False) == {
        fwd: LAYERS, bwd_dq: LAYERS, bwd_dkv: LAYERS}
    assert _compat.recompute_kept().get(op, 0) == before


def test_attention_through_jax_numpy_is_not_counted():
    """Off the TPU `mla_attention` is its jax.numpy form, whose backward
    needs the probabilities: nothing is named, kept or counted."""
    before = _compat.recompute_kept().get("mla_attention", 0)
    loss, arrays, _ = _loss_of(_mla_model(True))
    assert not _kernel_calls(
        jax.make_jaxpr(jax.grad(loss))(arrays, _ids()).jaxpr)
    assert _compat.recompute_kept().get("mla_attention", 0) == before


# ------------------------------------------------------ what a segment saves
def _saved(segment, *arrays):
    """jax's listing of what the gradient of `recompute(segment, ...)`
    keeps from the forward pass (`print_saved_residuals`, a line each:
    `f32[4,8] from the argument ...`): (shape, where it comes from)."""
    def f(*arrays):
        with paddle.no_grad(), _rng_lift(jax.random.key(0)):
            out = recompute(segment, *map(paddle.to_tensor, arrays))
        return jnp.sum(out._data.astype(jnp.float32))

    listing = io.StringIO()
    with contextlib.redirect_stdout(listing):
        jax.ad_checkpoint.print_saved_residuals(f, *arrays)
    rows = [re.fullmatch(r"\w+\[([\d,]*)\] (.*)", line)
            for line in listing.getvalue().splitlines()]
    return [(tuple(map(int, filter(None, row[1].split(",")))), row[2])
            for row in rows]


def _inputs_only(saved):
    """Every row is an argument of the segment or a weight it closes over."""
    return bool(saved) and all(
        where.startswith(("from the argument", "from a constant"))
        for _, where in saved)


def _sdpa(t):
    return paddle.scaled_dot_product_attention(t, t, t, None, 0.0, True)


def test_a_segment_without_attention_saves_nothing_but_its_inputs():
    paddle.seed(0)
    mlp = paddle.nn.Sequential(
        paddle.nn.Linear(8, 32), paddle.nn.ReLU(), paddle.nn.Linear(32, 8))
    saved = _saved(mlp, jnp.ones((4, 8), jnp.float32))
    assert _inputs_only(saved), saved
    assert len(saved) == 4              # x, two weights and the one bias read
    # attention through jax.numpy: the probabilities are not kept
    q = jnp.ones((1, SEQ, 2, 64), jnp.float32)
    assert _saved(_sdpa, q) == [(q.shape, "from the argument arrays[0]")]


def test_a_segment_with_attention_saves_the_kernels_two_outputs():
    q = jnp.ones((1, SEQ, 2, 64), jnp.float32)
    with _flash_through_the_kernels():
        saved = _saved(_sdpa, q)
        with _the_parents_way():
            assert _saved(_sdpa, q) == [
                (q.shape, "from the argument arrays[0]")]
    kept = [row for row in saved if not _inputs_only([row])]
    # in the kernels' own layout: [b*h, s, d] and its row sums [b*h, 8, s]
    # (jax lists `out`, which the segment's output is computed from as well,
    # under the no-op reduce_precision it guards such a residual with)
    assert sorted(shape for shape, _ in kept) == [(2, 8, SEQ), (2, SEQ, 64)]
    assert any(f"named '{fa.ATTENTION_LSE}'" in where for _, where in kept)
    assert len(saved) == len(kept) + 1


def test_under_shard_map_the_segment_keeps_them_too():
    """In a sharded program `flash_attention` runs its kernels per shard
    under `shard_map`; the policy reaches the names inside it."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    q = jnp.ones((2, SEQ, 2, 64), jnp.float32)

    def f(q):
        with paddle.no_grad(), _rng_lift(jax.random.key(0)), \
                _compat.spmd_axes(mesh, "dp", None):
            out = recompute(_sdpa, paddle.to_tensor(q))
        return jnp.sum(out._data)

    fwd, bwd_dq, bwd_dkv = fa.KERNELS
    with _flash_through_the_kernels():
        jaxpr = jax.make_jaxpr(jax.grad(f))(q)
        assert "shard_map" in str(jaxpr)
        assert _kernel_calls(jaxpr.jaxpr) == {fwd: 1, bwd_dq: 1, bwd_dkv: 1}
        with _the_parents_way():
            assert _kernel_calls(jax.make_jaxpr(jax.grad(f))(q).jaxpr) == {
                fwd: 2, bwd_dq: 1, bwd_dkv: 1}


# --------------------------------------------------------- the same numbers
@pytest.mark.parametrize("which", sorted(MODELS))
def test_loss_and_gradients_are_the_parents_bit_for_bit(which):
    make, through_the_kernels, _, _ = MODELS[which]
    ids = _ids()

    def value_and_grads(rc):
        loss, arrays, names = _loss_of(make(rc))
        with through_the_kernels():
            value, grads = jax.jit(jax.value_and_grad(loss))(arrays, ids)
        return np.asarray(value), dict(zip(names, map(np.asarray, grads)))

    kept_loss, kept = value_and_grads(True)
    with _the_parents_way():
        parent_loss, parent = value_and_grads(True)
    plain_loss, plain = value_and_grads(False)
    assert kept_loss == parent_loss
    np.testing.assert_allclose(kept_loss, plain_loss, rtol=1e-5)
    for (name, g), p, q in zip(kept.items(), parent.values(),
                               plain.values()):
        assert np.array_equal(g, p), name
        np.testing.assert_allclose(
            g, q, rtol=1e-5, atol=1e-5 * np.abs(q).max(), err_msg=name)
    assert any(np.abs(g).max() > 0 for g in kept.values())


# ------------------------------------------- compiled for a described v5e
V5E_HBM = 15.75 * 2**30
BATCH, LONG = 2, 8192


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _forward_kernel_calls(compiled, kernel):
    return len(re.findall(
        rf"^\s*(?:ROOT )?%\w*?{kernel}[_.\d]* = .*custom-call\(",
        compiled.as_text(), re.M))


def test_a_recomputed_mla_block_compiles_to_one_forward_kernel(
        one_chip, no_persistent_cache):
    """One DeepseekV3Attention at the published widths and the cell's
    shape, bf16: value and gradient of its `recompute`d forward. The value
    keeps the forward pass alive, so the parent's way has two forward
    kernels in the module and this tree one."""
    import paddle_tpu.core.device as core_device
    from paddle_tpu.models import DeepseekV3Config
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Attention

    cfg = DeepseekV3Config()
    with paddle.nn.initializer.param_init_override(
            lambda shape, dtype=None: jnp.zeros(shape, jnp.bfloat16)):
        layer = DeepseekV3Attention(cfg)
    params = list(layer.parameters())

    def block(arrays, x):
        old = [p._data for p in params]
        for p, a in zip(params, arrays):
            p._data = a
        try:
            with paddle.no_grad(), _rng_lift(jax.random.key(0)):
                out = recompute(layer, paddle.to_tensor(x))
            return jnp.sum(out._data.astype(jnp.float32))
        finally:
            for p, a in zip(params, old):
                p._data = a

    abstract = lambda shape: jax.ShapeDtypeStruct(
        tuple(shape), jnp.bfloat16, sharding=one_chip)
    args = ([abstract(p.shape) for p in params],
            abstract((BATCH, LONG, cfg.hidden_size)))

    def compiled():
        # the TPU's branch: the kernels, through Mosaic
        with mock.patch.object(core_device, "on_tpu", lambda: True), \
                mock.patch.object(_compat, "on_tpu", lambda: True):
            return jax.jit(jax.value_and_grad(block, argnums=(0, 1))).trace(
                *args).lower(lowering_platforms=("tpu",)).compile()

    assert _forward_kernel_calls(compiled(), "mla_attention_fwd") == 1
    with _the_parents_way():
        assert _forward_kernel_calls(compiled(), "mla_attention_fwd") == 2


@pytest.mark.slow          # 120 s alone, 1.4 GB of zero weights on the host
def test_the_kanana2_step_keeps_six_forwards_and_fits_the_chip(
        one_chip, no_persistent_cache):
    """The whole step of `kanana2.pretrain-8k` at batch 2 x 8192: six
    `mla_attention_fwd` calls where the parent has twelve, and the
    ahead-of-time memory (12.76 GiB where the parent's step takes 13.40;
    PERF.md section 4) under the chip's."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks import aot_deepseek_v3, run

    cfg = run.load(root, "benchmarks", "configs",
                   "kanana-2-30b-a3b-train1.json")
    traffic = run.load(root, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        compiled = aot_deepseek_v3.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip).compile()
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    layers = cfg["num_hidden_layers"]
    assert _forward_kernel_calls(compiled, "mla_attention_fwd") == layers
    assert _forward_kernel_calls(compiled, "mla_attention_bwd_dq") == layers
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM
